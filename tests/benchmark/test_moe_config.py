"""The latent-attention / expert-layer configuration's YAML against its source: the
numbers of kanana-2-30b-a3b-instruct-2601's config.json (as the catalog beside the
`model-configs` guide records them, copied here because the test machine has no such
catalog), what `reduced` says was changed, and what the model block makes of them: every
width uncut."""

import json
from pathlib import Path

import yaml

from benchmark.weights_hybrid import resolved
from benchmark.weights_moe import MoEMLAShape
from tests.benchmark.accepted import DRIVER_SECONDS, REAL_COST_S, check_seconds, full_check_seconds

REPO = Path(__file__).resolve().parents[2]
CONFIG_DIR = REPO / "benchmark" / "configs" / "kanana2-30b-a3b-d9"
PUBLISHED = {
    "attention_bias": False, "first_k_dense_replace": 1, "head_dim": 64, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 6144, "kv_lora_rank": 512, "max_position_embeddings": 32768, "model_type": "deepseek_v3",
    "moe_intermediate_size": 768, "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 128, "n_shared_experts": 2,
    "norm_topk_prob": True, "num_attention_heads": 32, "num_experts_per_tok": 6, "num_hidden_layers": 48, "num_key_value_heads": 32,
    "q_lora_rank": None, "qk_head_dim": 192, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_interleave": True, "rope_scaling": None, "rope_theta": 1000000, "routed_scaling_factor": 2.448, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "topk_group": 1, "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 128256,
}


def test_the_file_is_json_and_holds_the_sources_numbers_but_for_what_reduced_names():
    text = (CONFIG_DIR / "train.yaml").read_text()
    raw = json.loads(text)
    assert raw == yaml.safe_load(text), "one object, whichever parser reads it"
    meta = json.loads((CONFIG_DIR / "meta.json").read_text())
    differing = {key for key, value in PUBLISHED.items() if raw.get(key, "absent") != value}
    # this chip's eighth of the table padded to 126 x 1024 rows
    assert differing == {"vocab_size"} and raw["vocab_size"] == 16128 == 126 * 1024 // 8 >= -(-PUBLISHED["vocab_size"] // 8)
    # `n_layer` is the source's num_hidden_layers in this repo's spelling, `experts_held` the share of its n_routed_experts
    assert set(meta["reduced"]) == {"n_layer", "experts_held", "vocab_size"}
    assert not [key for key in meta["reduced"] if key.endswith(("_dim", "_rank"))]
    assert {"stands_for", "assumed", "memory_analysis", "source", "parameters"} <= set(meta)


def test_the_model_block_reads_every_width_from_the_published_keys():
    raw = yaml.safe_load((CONFIG_DIR / "train.yaml").read_text())
    model = resolved(raw["model_raw"]["config"], raw)
    assert (model["n_embd"], model["n_head_q"], model["n_head_kv"], model["vocab_size"], model["n_layer"]) == (2048, 32, 32, 16128, 9)
    assert model["mla_config"] == {"kv_lora_rank": 512, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128,
                                   "rope_theta": 1000000, "rope_interleave": True, "q_lora_rank": None, "rope_scaling": None, "norm_eps": 1e-06}
    assert model["moe_config"] == {"n_routed_experts": 128, "num_experts_per_tok": 6, "moe_intermediate_size": 768, "n_shared_experts": 2,
                                   "first_k_dense_replace": 1, "moe_layer_freq": 1, "routed_scaling_factor": 2.448, "norm_topk_prob": True,
                                   "scoring_func": "sigmoid", "topk_method": "noaux_tc", "n_group": 1, "topk_group": 1,
                                   "experts_held": 16, "expert_offset": 0, "bias_update_speed": 0.1}
    assert (model["use_weight_tying"], model["bias"]) == (False, False)
    assert all(model[n]["config"] == {"ndim": 2048, "bias": False, "epsilon": 1e-06} for n in ("attention_norm_config", "ffn_norm_config", "lm_head_norm_config"))
    shape = MoEMLAShape.from_yaml(raw)
    assert shape.ffn_hidden == PUBLISHED["intermediate_size"] and shape.qk_head_dim == PUBLISHED["qk_head_dim"]
    assert shape.shared_hidden == 2 * 768 and shape.kinds == ("mlp",) + ("moe",) * 8, "the leading dense layer and 8 expert layers"
    # full rematerialization, the existing variant; decay on every matrix, none on the embedding, the norms and the selection bias
    assert raw["remat_model"]["config"]["activation_checkpointing_variant"] == "full_activation_checkpointing"
    assert raw["model"]["config"]["model"]["instance_key"] == "remat_model"
    assert raw["optimizer"]["config"]["weight_decay_groups_excluded"] == ["embedding", "norm", "router_bias"]
    assert (raw["settings"]["step_profile"]["sequence_length"], raw["settings"]["step_profile"]["local_train_micro_batch_size"]) == (8192, 2)


def test_the_traffic_is_packed_4ks_letter_for_letter():
    traffic = REPO / "benchmark" / "traffic"
    dense, moe = (json.loads((traffic / f"{name}.json").read_text()) for name in ("packed-4k", "packed-8k-moe"))
    assert {k: v for k, v in dense.items() if k not in ("mode", "why")} == {k: v for k, v in moe.items() if k not in ("mode", "why")}
    assert moe["mode"] == "train_moe"


def test_a_full_check_at_this_cells_real_cost_fits_the_drivers_budget():
    """`test_manifest.py` does the driver's arithmetic with `run_seconds` + 60 = 100 s a run and 90 s more for two cold
    runs a cell. This cell's runs take longer (my chip runs, PR 30: 120-143 s warm, set-up 42 + window 40 + reference
    34-40; 287-292 s where everything compiles), which ISSUE 30's own budget (100 s and 180 s) did not foresee: a
    float32 reference of 1.02 B parameters through two gradients is 31 s of chip time at `highest` precision. Four
    later cells cost as much for the same reason, so the check is counted (PR 49) as the sixth cell's test counts it:
    every long cell at its real cost (`accepted.REAL_COST_S`, one table for all these tests), the rest at the usual
    one, against half of the driver's time (15,458 of 21,600 s at seven cells). A benchmark of 24 cells could hold 5
    cells of this cost beside 19 of the manifest's and not 6, which a `benchmark` issue that adds cells has to count."""
    manifest = json.loads((REPO / "BENCHMARK.json").read_text())
    warm, cold = REAL_COST_S["train-kanana2-30b-8k"]
    assert (warm, cold) == (143, 292)
    assert full_check_seconds(manifest) <= DRIVER_SECONDS // 2
    run_seconds = manifest["run_seconds"]
    assert check_seconds(run_seconds, 19, [(warm, cold)] * 5) <= DRIVER_SECONDS < check_seconds(run_seconds, 18, [(warm, cold)] * 6)
