"""The hybrid configuration's YAML against its source: the numbers of AI21-Jamba2-3B's
config.json (as the catalog beside the `model-configs` guide records them, copied here
because the test machine has no such catalog), what `reduced` says was changed, and what
the model block makes of them: every width uncut."""

import json
from pathlib import Path

import yaml

from benchmark.weights_hybrid import HybridShape, resolved

REPO = Path(__file__).resolve().parents[2]
CONFIG_DIR = REPO / "benchmark" / "configs" / "jamba2-3b-d14"
PUBLISHED = {
    "attn_layer_offset": 7, "attn_layer_period": 14, "expert_layer_offset": 1, "expert_layer_period": 2, "hidden_act": "silu",
    "hidden_size": 2560, "intermediate_size": 8192, "mamba_conv_bias": True, "mamba_d_conv": 4, "mamba_d_state": 16,
    "mamba_dt_rank": 160, "mamba_expand": 2, "mamba_proj_bias": False, "max_position_embeddings": 262144, "model_type": "jamba",
    "num_attention_heads": 20, "num_experts": 1, "num_experts_per_tok": 1, "num_hidden_layers": 28, "num_key_value_heads": 1,
    "num_logits_to_keep": 1, "rms_norm_eps": 1e-06, "sliding_window": None, "tie_word_embeddings": True, "use_mamba_kernels": True,
    "vocab_size": 65536,
}


def test_the_file_is_json_and_holds_the_sources_numbers_but_for_what_reduced_names():
    text = (CONFIG_DIR / "train.yaml").read_text()
    raw = json.loads(text)
    assert raw == yaml.safe_load(text), "one object, whichever parser reads it"
    meta = json.loads((CONFIG_DIR / "meta.json").read_text())
    differing = {key for key, value in PUBLISHED.items() if raw.get(key, "absent") != value}
    assert differing == {"vocab_size"} and raw["vocab_size"] == 32768 == PUBLISHED["vocab_size"] // 2
    # `n_layer` is the source's num_hidden_layers in this repo's spelling; the published 28 stays at the top level
    assert set(meta["reduced"]) == {"n_layer", "vocab_size"} and raw["model_raw"]["config"]["n_layer"] == 14
    assert not [key for key in meta["reduced"] if key.endswith(("_dim", "_rank"))]


def test_the_model_block_reads_every_width_from_the_published_keys():
    raw = yaml.safe_load((CONFIG_DIR / "train.yaml").read_text())
    model = resolved(raw["model_raw"]["config"], raw)
    assert (model["n_embd"], model["n_head_q"], model["n_head_kv"], model["vocab_size"]) == (2560, 20, 1, 32768)
    assert model["ssm_config"] == {"d_state": 16, "d_conv": 4, "expand": 2, "dt_rank": 160, "conv_bias": True, "norm_eps": 1e-06}
    assert (model["attn_layer_period"], model["attn_layer_offset"], model["use_weight_tying"]) == (14, 7, True)
    assert all(model[n]["config"] == {"ndim": 2560, "bias": False, "epsilon": 1e-06} for n in ("attention_norm_config", "ffn_norm_config", "lm_head_norm_config"))
    shape = HybridShape.from_yaml(raw)
    assert shape.ffn_hidden == PUBLISHED["intermediate_size"] and shape.head_dim == 128 and shape.d_inner == 5120
    assert shape.kinds == ("ssm",) * 7 + ("attn",) + ("ssm",) * 6, "one whole period, attention at index 7"
    # full rematerialization, the existing variant, and the `ssm` group out of the weight decay
    assert raw["remat_model"]["config"]["activation_checkpointing_variant"] == "full_activation_checkpointing"
    assert raw["model"]["config"]["model"]["instance_key"] == "remat_model"
    assert raw["optimizer"]["config"]["weight_decay_groups_excluded"] == ["embedding", "norm", "ssm"]


def test_the_traffic_is_packed_4ks_letter_for_letter():
    traffic = REPO / "benchmark" / "traffic"
    dense, hybrid = (json.loads((traffic / f"{name}.json").read_text()) for name in ("packed-4k", "packed-4k-hybrid"))
    assert {k: v for k, v in dense.items() if k not in ("mode", "why")} == {k: v for k, v in hybrid.items() if k not in ("mode", "why")}
    assert hybrid["mode"] == "train_hybrid"
