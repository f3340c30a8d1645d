"""The hybrid cell at toy size, for rehearsals on the CPU: `tests/benchmark/toy.py`'s
root with the hybrid configuration's YAML cut to a period of 4 layers (attention at
index 2) of width 128. Nothing here is measured; the chip measures the real cell."""

from __future__ import annotations

from pathlib import Path

import yaml

from tests.benchmark.toy import TOY_SEQ, make_toy_root

CELL = "train-jamba2-3b-4k"
CONFIG = "jamba2-3b-d14"
# the source's keys at the top of the YAML, which `model_raw.config` reads its widths from
TOY_PUBLISHED = {"hidden_size": 128, "num_attention_heads": 4, "num_key_value_heads": 1, "vocab_size": 512,
                 "attn_layer_period": 4, "attn_layer_offset": 2, "mamba_d_state": 8, "mamba_dt_rank": 8}
TOY_LAYERS = 4


def shrink(raw: dict) -> dict:
    """The hybrid YAML (as `yaml.safe_load` gives it) at toy size, in place."""
    raw.update(TOY_PUBLISHED)
    model = raw["model_raw"]["config"]
    model.update(n_layer=TOY_LAYERS, ffn_hidden=384, lm_head_chunk_size=64,
                 n_embd="${hidden_size}", n_head_q="${num_attention_heads}", n_head_kv="${num_key_value_heads}",
                 vocab_size="${vocab_size}")
    for norm in ("attention_norm_config", "ffn_norm_config", "lm_head_norm_config"):
        model[norm]["config"]["ndim"] = "${hidden_size}"
    raw["model"]["config"]["model_initializer"]["config"]["num_layers"] = TOY_LAYERS
    # two rows a step, so that a step which leaves half its batch out differs from a sound one (the cell has one)
    raw["settings"]["step_profile"]["local_train_micro_batch_size"] = 2
    if isinstance(raw["settings"]["training_target"].get("num_target_steps"), int):
        target = raw["settings"]["training_target"]
        target["num_target_tokens"] = target["num_target_steps"] * 2 * raw["settings"]["step_profile"]["sequence_length"]
    return raw


def make_toy_hybrid_root(dst: Path) -> Path:
    """`make_toy_root` (which cuts every configuration's sequence, corpus, warm-up and
    mesh), then the hybrid configuration's own sizes."""
    root = make_toy_root(dst)
    path = root / "benchmark" / "configs" / CONFIG / "train.yaml"
    raw = shrink(yaml.safe_load(path.read_text()))
    assert raw["settings"]["step_profile"]["sequence_length"] == TOY_SEQ
    path.write_text(yaml.safe_dump(raw, sort_keys=False))
    return root
