"""The Mamba-2 / NoPE-attention / expert-layer cell at toy size, for rehearsals on the CPU: `tests/benchmark/toy.py`'s root
with this configuration's YAML cut to three layers (`mamba`, `attention`, `mamba`: three runs) of width 128: the Mamba-2 mixer with
2 of 8 heads of 16 held, a state of 16, 4 taps and chunks of 16 (sequence 128: a state carried over eight chunks); 2 query heads
on 1 key/value head of 32 of a published 8 on 4, the scores scaled by 1/32; 16 experts of 64 of which a token takes 4 and this
model holds 4 (from the fifth), beside a quarter of a shared expert of 128; the four multipliers as published. Nothing here is
measured; the chip measures the real cell."""

from __future__ import annotations

from pathlib import Path

import yaml

from tests.benchmark.toy import TOY_SEQ, make_toy_root

CELL = "train-granite4h-32b-8k"
CONFIG = "granite-4.0-h-small-d10"
TOY_TYPES = ["mamba", "attention", "mamba"]
# the source's keys at the top of the YAML, which `model_raw.config` reads its widths from
TOY_PUBLISHED = {"hidden_size": 128, "num_attention_heads": 8, "num_key_value_heads": 4, "vocab_size": 512, "num_local_experts": 16,
                 "num_experts_per_tok": 4, "intermediate_size": 64, "shared_intermediate_size": 128, "mamba_n_heads": 8, "mamba_d_head": 16,
                 "mamba_d_state": 16, "mamba_chunk_size": 16, "attention_multiplier": 1 / 32, "layer_types_held": TOY_TYPES}
TOY_HELD, TOY_OFFSET = 4, 4
TOY_ROWS = 2  # the cell's microbatch is one row of 8,192; two rows here, so that a step can leave half its batch out


def shrink(raw: dict) -> dict:
    """The configuration's YAML (as `yaml.safe_load` gives it) at toy size, in place."""
    raw.update(TOY_PUBLISHED)
    model = raw["model_raw"]["config"]
    model.update(n_layer=len(TOY_TYPES), ffn_hidden=384, lm_head_chunk_size=64, n_head_q=2, n_head_kv=1, head_dim=32)
    model["moe_config"].update(experts_held=TOY_HELD, expert_offset=TOY_OFFSET)
    model["ssd_config"].update(heads_held=2)
    raw["model"]["config"]["model_initializer"]["config"]["num_layers"] = len(TOY_TYPES)
    raw["settings"]["step_profile"]["local_train_micro_batch_size"] = TOY_ROWS
    raw["settings"]["training_target"]["num_target_tokens"] = raw["settings"]["training_target"]["num_target_steps"] * TOY_ROWS * TOY_SEQ
    return raw


def make_toy_ssd_moe_root(dst: Path) -> Path:
    """`make_toy_root` (which cuts every configuration's sequence, corpus, warm-up and mesh), then this configuration's own sizes."""
    root = make_toy_root(dst)
    path = root / "benchmark" / "configs" / CONFIG / "train.yaml"
    raw = shrink(yaml.safe_load(path.read_text()))
    assert raw["settings"]["step_profile"]["sequence_length"] == TOY_SEQ
    path.write_text(yaml.safe_dump(raw, sort_keys=False))
    return root
