"""The looped cell at toy size, for rehearsals on the CPU: `tests/benchmark/toy.py`'s root with this
configuration's YAML cut to 3 layers of width 128 walked 4 times: 4 heads of 32 (a key/value head a
query head), SwiGLU 256, vocabulary 512. Nothing here is measured; the chip measures the real cell."""

from __future__ import annotations

from pathlib import Path

import yaml

from tests.benchmark.toy import TOY_SEQ, make_toy_root

CELL = "train-ouro-2p6b-4k"
CONFIG = "ouro-2p6b-t4"
# the source's keys at the top of the YAML, which `model_raw.config` reads its widths and its walks from
TOY_PUBLISHED = {"hidden_size": 128, "num_attention_heads": 4, "num_key_value_heads": 4, "head_dim": 32, "vocab_size": 512,
                 "intermediate_size": 256, "total_ut_steps": 4}
TOY_LAYERS, TOY_ROWS = 3, 2  # two rows a step where the cell runs one, so that a step can leave half its batch out
NORMS = ("attention_norm_config", "post_attention_norm_config", "ffn_norm_config", "post_ffn_norm_config", "lm_head_norm_config")


def shrink(raw: dict) -> dict:
    """The configuration's YAML (as `yaml.safe_load` gives it) at toy size, in place."""
    raw.update(TOY_PUBLISHED)
    model = raw["model_raw"]["config"]
    model.update(n_layer=TOY_LAYERS, ffn_hidden=384, lm_head_chunk_size=64, n_embd="${hidden_size}",
                 n_head_q="${num_attention_heads}", n_head_kv="${num_key_value_heads}", vocab_size="${vocab_size}")
    model["attention_config"]["qkv_transforms"][0]["config"].update(n_embd="${hidden_size}", n_head="${num_attention_heads}")
    for norm in NORMS:
        model[norm]["config"]["ndim"] = "${hidden_size}"
    raw["model"]["config"]["model_initializer"]["config"]["num_layers"] = TOY_LAYERS
    raw["settings"]["step_profile"]["local_train_micro_batch_size"] = TOY_ROWS
    raw["settings"]["training_target"]["num_target_tokens"] = raw["settings"]["training_target"]["num_target_steps"] * TOY_ROWS * TOY_SEQ
    return raw


def make_toy_looped_root(dst: Path) -> Path:
    """`make_toy_root` (which cuts every configuration's sequence, corpus, warm-up and mesh, and gives every model
    block the dense toy's two key/value heads), then this configuration's own sizes."""
    root = make_toy_root(dst)
    path = root / "benchmark" / "configs" / CONFIG / "train.yaml"
    raw = shrink(yaml.safe_load(path.read_text()))
    assert raw["settings"]["step_profile"]["sequence_length"] == TOY_SEQ
    path.write_text(yaml.safe_dump(raw, sort_keys=False))
    return root
