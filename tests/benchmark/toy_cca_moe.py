"""The compressed-convolutional-attention / expert-layer cell at toy size, for rehearsals on the CPU:
`tests/benchmark/toy.py`'s root with this configuration's YAML cut to three hybrid layers of width 128: 4 query
heads on 2 key/value heads of 32 (a latent of 192), two taps and two, the rotary on half a head; a router state of
32, 8 experts of 128 and the skip column, one choice a token, 4 experts held (from the third). Nothing here is
measured; the chip measures the real cell."""

from __future__ import annotations

from pathlib import Path

import yaml

from tests.benchmark.toy import TOY_SEQ, make_toy_root

CELL = "train-zaya1-8b-8k"
CONFIG = "zaya1-8b-ep2"
TOY_LAYERS = 3
# the source's keys at the top of the YAML, which `model_raw.config` reads its widths from
TOY_PUBLISHED = {"hidden_size": 128, "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 32, "vocab_size": 528,
                 "num_experts": 8, "moe_intermediate_size": 128, "router_hidden_size": 32, "layer_types_held": ["hybrid"] * TOY_LAYERS}
TOY_HELD, TOY_OFFSET = 4, 2


def shrink(raw: dict) -> dict:
    """The configuration's YAML (as `yaml.safe_load` gives it) at toy size, in place."""
    raw.update(TOY_PUBLISHED)
    model = raw["model_raw"]["config"]
    model.update(n_layer=TOY_LAYERS, ffn_hidden=384, lm_head_chunk_size=64,
                 n_embd="${hidden_size}", n_head_q="${num_attention_heads}", n_head_kv="${num_key_value_heads}",
                 vocab_size="${vocab_size}")
    model["moe_config"].update(experts_held=TOY_HELD, expert_offset=TOY_OFFSET)
    model["attention_config"]["qkv_transforms"][0]["config"].update(n_embd="${hidden_size}", n_head="${num_attention_heads}")
    for norm in ("attention_norm_config", "ffn_norm_config", "lm_head_norm_config"):
        model[norm]["config"]["ndim"] = "${hidden_size}"
    raw["model"]["config"]["model_initializer"]["config"]["num_layers"] = TOY_LAYERS
    rows = raw["settings"]["step_profile"]["local_train_micro_batch_size"]
    raw["settings"]["training_target"]["num_target_tokens"] = raw["settings"]["training_target"]["num_target_steps"] * rows * TOY_SEQ
    return raw


def make_toy_cca_moe_root(dst: Path) -> Path:
    """`make_toy_root` (which cuts every configuration's sequence, corpus, warm-up and mesh), then this configuration's own sizes."""
    root = make_toy_root(dst)
    path = root / "benchmark" / "configs" / CONFIG / "train.yaml"
    raw = shrink(yaml.safe_load(path.read_text()))
    assert raw["settings"]["step_profile"]["sequence_length"] == TOY_SEQ
    path.write_text(yaml.safe_dump(raw, sort_keys=False))
    return root
