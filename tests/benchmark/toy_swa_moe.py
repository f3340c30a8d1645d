"""The window-and-global attention / expert-layer cell at toy size, for rehearsals on the CPU:
`tests/benchmark/toy.py`'s root with this configuration's YAML cut to one period (three window
layers and one global layer) of width 128: 4 query heads on 2 key/value heads of 48 (4 x 48 is
192, not 128), a window of 32 at sequence 128, YaRN extending from 64; 16 experts of 64 of which
a token takes 4 and this model holds 4 (from the fifth). Nothing here is measured; the chip
measures the real cell."""

from __future__ import annotations

from pathlib import Path

import yaml

from tests.benchmark.toy import TOY_SEQ, make_toy_root

CELL = "train-mellum2-12b-16k"
CONFIG = "mellum2-12b-a2p5b-d12"
TOY_TYPES = ["sliding_attention", "sliding_attention", "sliding_attention", "full_attention"]
# the source's keys at the top of the YAML, which `model_raw.config` reads its widths from
TOY_PUBLISHED = {"hidden_size": 128, "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 48, "vocab_size": 512,
                 "num_experts": 16, "num_experts_per_tok": 4, "moe_intermediate_size": 64, "sliding_window": 32,
                 "layer_types_held": TOY_TYPES}
TOY_HELD, TOY_OFFSET = 4, 4
TOY_ROWS = 2  # the cell's microbatch is one row of 16,384; two rows here, so that a step can leave half its batch out


def shrink(raw: dict) -> dict:
    """The configuration's YAML (as `yaml.safe_load` gives it) at toy size, in place."""
    raw.update(TOY_PUBLISHED)
    raw["rope_parameters"]["full_attention"]["original_max_position_embeddings"] = TOY_SEQ // 2
    model = raw["model_raw"]["config"]
    model.update(n_layer=len(TOY_TYPES), ffn_hidden=384, lm_head_chunk_size=64,
                 n_embd="${hidden_size}", n_head_q="${num_attention_heads}", n_head_kv="${num_key_value_heads}",
                 vocab_size="${vocab_size}")
    model["moe_config"].update(experts_held=TOY_HELD, expert_offset=TOY_OFFSET)
    model["attention_config"]["qkv_transforms"][0]["config"].update(n_embd="${hidden_size}", n_head="${num_attention_heads}")
    for norm in ("attention_norm_config", "ffn_norm_config", "lm_head_norm_config"):
        model[norm]["config"]["ndim"] = "${hidden_size}"
    raw["model"]["config"]["model_initializer"]["config"]["num_layers"] = len(TOY_TYPES)
    raw["settings"]["step_profile"]["local_train_micro_batch_size"] = TOY_ROWS
    raw["settings"]["training_target"]["num_target_tokens"] = raw["settings"]["training_target"]["num_target_steps"] * TOY_ROWS * TOY_SEQ
    return raw


def make_toy_swa_moe_root(dst: Path) -> Path:
    """`make_toy_root` (which cuts every configuration's sequence, corpus, warm-up and mesh), then this configuration's own sizes."""
    root = make_toy_root(dst)
    path = root / "benchmark" / "configs" / CONFIG / "train.yaml"
    raw = shrink(yaml.safe_load(path.read_text()))
    assert raw["settings"]["step_profile"]["sequence_length"] == TOY_SEQ
    path.write_text(yaml.safe_dump(raw, sort_keys=False))
    return root
