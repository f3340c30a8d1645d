"""The gated-delta-rule / gated-attention / expert-layer cell at toy size, for rehearsals on the CPU:
`tests/benchmark/toy.py`'s root with this configuration's YAML cut to two layers (a
`linear_attention` layer, then a `full_attention` layer: a run of each kind) of width 128: the rule's mixer with 2 key
heads and 4 value heads of 16 (chunks of 64 at sequence 128: a state carried over two chunks), 4
taps; 4 query heads on 2 key/value heads of 32 with the rotary on a quarter of a head (8 channels)
and the output gate; 16 experts of 64 of which a token takes 4 and this model holds 4 (from the
fifth), beside a gated shared expert of 64. Nothing here is measured; the chip measures the real cell."""

from __future__ import annotations

from pathlib import Path

import yaml

from tests.benchmark.toy import TOY_SEQ, make_toy_root

CELL = "train-qwen3next-80b-16k"
CONFIG = "qwen3-next-80b-a3b-d4"
TOY_TYPES = ["linear_attention", "full_attention"]
# the source's keys at the top of the YAML, which `model_raw.config` reads its widths from
TOY_PUBLISHED = {"hidden_size": 128, "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 32, "vocab_size": 512,
                 "num_experts": 16, "num_experts_per_tok": 4, "moe_intermediate_size": 64, "shared_expert_intermediate_size": 64,
                 "linear_num_key_heads": 2, "linear_num_value_heads": 4, "linear_key_head_dim": 16, "linear_value_head_dim": 16,
                 "layer_types_held": TOY_TYPES}
TOY_HELD, TOY_OFFSET = 4, 4
TOY_ROWS = 2  # the cell's microbatch is one row of 16,384; two rows here, so that a step can leave half its batch out


def shrink(raw: dict) -> dict:
    """The configuration's YAML (as `yaml.safe_load` gives it) at toy size, in place."""
    raw.update(TOY_PUBLISHED)
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


def make_toy_gdn_moe_root(dst: Path) -> Path:
    """`make_toy_root` (which cuts every configuration's sequence, corpus, warm-up and mesh), then this configuration's own sizes."""
    root = make_toy_root(dst)
    path = root / "benchmark" / "configs" / CONFIG / "train.yaml"
    raw = shrink(yaml.safe_load(path.read_text()))
    assert raw["settings"]["step_profile"]["sequence_length"] == TOY_SEQ
    path.write_text(yaml.safe_dump(raw, sort_keys=False))
    return root
