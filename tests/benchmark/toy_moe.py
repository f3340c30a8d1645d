"""The latent-attention / expert-layer cell at toy size, for rehearsals on the CPU:
`tests/benchmark/toy.py`'s root with this configuration's YAML cut to 1 dense + 2 expert
layers of width 128: 4 heads of 32 + 16 for q and k and 32 for v, a latent of 64, 8
experts of 64 of which a token takes 3 and this model holds 4 (from the third), one
shared. Nothing here is measured; the chip measures the real cell."""

from __future__ import annotations

from pathlib import Path

import yaml

from tests.benchmark.toy import TOY_SEQ, make_toy_root

CELL = "train-kanana2-30b-8k"
CONFIG = "kanana2-30b-a3b-d9"
# the source's keys at the top of the YAML, which `model_raw.config` reads its widths from
TOY_PUBLISHED = {"hidden_size": 128, "num_attention_heads": 4, "num_key_value_heads": 4, "vocab_size": 512, "kv_lora_rank": 64,
                 "qk_nope_head_dim": 32, "qk_rope_head_dim": 16, "qk_head_dim": 48, "v_head_dim": 32, "n_routed_experts": 8,
                 "num_experts_per_tok": 3, "moe_intermediate_size": 64, "n_shared_experts": 1, "intermediate_size": 256}
TOY_LAYERS, TOY_HELD, TOY_OFFSET = 3, 4, 2


def shrink(raw: dict) -> dict:
    """The configuration's YAML (as `yaml.safe_load` gives it) at toy size, in place."""
    raw.update(TOY_PUBLISHED)
    model = raw["model_raw"]["config"]
    model.update(n_layer=TOY_LAYERS, ffn_hidden=384, lm_head_chunk_size=64,
                 n_embd="${hidden_size}", n_head_q="${num_attention_heads}", n_head_kv="${num_key_value_heads}",
                 vocab_size="${vocab_size}")
    model["moe_config"].update(experts_held=TOY_HELD, expert_offset=TOY_OFFSET)
    for norm in ("attention_norm_config", "ffn_norm_config", "lm_head_norm_config"):
        model[norm]["config"]["ndim"] = "${hidden_size}"
    raw["model"]["config"]["model_initializer"]["config"]["num_layers"] = TOY_LAYERS
    return raw


def make_toy_moe_root(dst: Path) -> Path:
    """`make_toy_root` (which cuts every configuration's sequence, corpus, warm-up and
    mesh), then this configuration's own sizes."""
    root = make_toy_root(dst)
    path = root / "benchmark" / "configs" / CONFIG / "train.yaml"
    raw = shrink(yaml.safe_load(path.read_text()))
    assert raw["settings"]["step_profile"]["sequence_length"] == TOY_SEQ
    path.write_text(yaml.safe_dump(raw, sort_keys=False))
    return root
