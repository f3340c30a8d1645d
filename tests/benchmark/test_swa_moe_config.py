"""The window-and-global attention / expert-layer configuration's YAML against its source: the numbers of
Mellum2-12B-A2.5B-Instruct's config.json (as the catalog beside the `model-configs` guide records them, copied here
because the test machine has no such catalog), what `reduced` says was changed, and what the model block makes of
them: every width uncut."""

import json
from pathlib import Path

import yaml

from benchmark.weights_hybrid import resolved
from benchmark.weights_swa_moe import SwaMoEShape
from tests.benchmark.accepted import ACCEPTED_CELLS, DRIVER_SECONDS, REAL_COST_S, full_check_seconds, holds_at_least, up_to

REPO = Path(__file__).resolve().parents[2]
CONFIG_DIR = REPO / "benchmark" / "configs" / "mellum2-12b-a2p5b-d12"
CELL = "train-mellum2-12b-16k"
PERIOD = ["sliding_attention", "sliding_attention", "sliding_attention", "full_attention"]
YARN = {"rope_type": "yarn", "rope_theta": 500000, "factor": 16, "original_max_position_embeddings": 8192, "beta_fast": 32, "beta_slow": 1,
        "attention_factor": 1.2772588722239782}
PUBLISHED = {
    "attention_bias": False, "head_dim": 128, "hidden_act": "silu", "hidden_size": 2304, "intermediate_size": 7168,
    "layer_types": PERIOD * 7, "mlp_layer_types": ["sparse"] * 28, "max_position_embeddings": 131072, "max_window_layers": 0,
    "model_type": "mellum", "moe_intermediate_size": 896, "norm_topk_prob": True, "num_attention_heads": 32, "num_experts": 64,
    "num_experts_per_tok": 8, "num_hidden_layers": 28, "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_parameters": {"full_attention": YARN, "sliding_attention": {"rope_type": "default", "rope_theta": 500000}},
    "sliding_window": 1024, "tie_word_embeddings": False, "vocab_size": 98304, "use_sliding_window": True,
}


def test_the_file_is_json_and_holds_the_sources_numbers_but_for_what_reduced_names():
    text = (CONFIG_DIR / "train.yaml").read_text()
    raw = json.loads(text)
    assert raw == yaml.safe_load(text), "one object, whichever parser reads it"
    meta = json.loads((CONFIG_DIR / "meta.json").read_text())
    differing = {key for key, value in PUBLISHED.items() if raw.get(key, "absent") != value}
    assert differing == {"vocab_size"} and raw["vocab_size"] == 12288 == PUBLISHED["vocab_size"] // 8, "this chip's eighth of the rows, no padding"
    assert raw["layer_types_held"] == PUBLISHED["layer_types"][:12] == PERIOD * 3, "the first three whole periods"
    # `n_layer` is the source's num_hidden_layers in this repo's spelling, `experts_held` the share of its num_experts
    assert set(meta["reduced"]) == {"n_layer", "experts_held", "vocab_size"}
    assert not [key for key in meta["reduced"] if key.endswith(("_dim", "_rank"))]
    assert {"stands_for", "assumed", "memory_analysis", "source", "parameters"} <= set(meta)
    assert {"scoring_func", "qk_norm", "window", "balance_loss", "mtp", "training_job", "packed_rows"} <= set(meta["assumed"])


def test_the_model_block_reads_every_width_from_the_published_keys():
    raw = yaml.safe_load((CONFIG_DIR / "train.yaml").read_text())
    model = resolved(raw["model_raw"]["config"], raw)
    assert (model["n_embd"], model["n_head_q"], model["n_head_kv"], model["head_dim"], model["vocab_size"], model["n_layer"]) == (2304, 32, 4, 128, 12288, 12)
    assert model["layer_types"] == PERIOD * 3 and model["sliding_window"] == 1024 and model["rope_parameters"] == PUBLISHED["rope_parameters"]
    assert model["moe_config"] == {"n_routed_experts": 64, "num_experts_per_tok": 8, "moe_intermediate_size": 896, "n_shared_experts": 0,
                                   "first_k_dense_replace": 0, "norm_topk_prob": True, "scoring_func": "softmax", "topk_method": "greedy",
                                   "experts_held": 8, "expert_offset": 0, "router_aux_loss_coef": model["moe_config"]["router_aux_loss_coef"]}
    assert model["moe_config"]["router_aux_loss_coef"] in (0.001, 0.02), "the families' default, or Mixtral's published value (meta.json, assumed)"
    assert (model["use_weight_tying"], model["bias"]) == (False, False) and "qk_norm_config" not in model["attention_config"]
    assert all(model[n]["config"] == {"ndim": 2304, "bias": False, "epsilon": 1e-06} for n in ("attention_norm_config", "ffn_norm_config", "lm_head_norm_config"))
    assert 2 * model["ffn_hidden"] // 3 == PUBLISHED["intermediate_size"], "the dense width no layer uses"
    shape = SwaMoEShape.from_yaml(raw)
    assert shape.kinds == ("swa", "swa", "swa", "attn") * 3 and [length for _, _, length in shape.runs] == [3, 1] * 3
    assert shape.rotary_of("swa").rope_type == "default" and shape.rotary_of("attn").rope_type == "yarn" and shape.rotary_of("attn").original == 8192
    assert shape.rotary_of("attn").attention_factor == 1.2772588722239782 and shape.rotary_of("swa").theta == shape.rotary_of("attn").theta == 500000
    # full rematerialization, the existing variant; decay on every matrix, none on the embedding and the norms
    assert raw["remat_model"]["config"]["activation_checkpointing_variant"] == "full_activation_checkpointing"
    assert raw["model"]["config"]["model"]["instance_key"] == "remat_model"
    assert raw["optimizer"]["config"]["weight_decay_groups_excluded"] == ["embedding", "norm"]
    assert (raw["settings"]["step_profile"]["sequence_length"], raw["settings"]["step_profile"]["local_train_micro_batch_size"]) == (16384, 1)


def test_the_traffic_is_packed_4ks_corpus_letter_for_letter():
    traffic = REPO / "benchmark" / "traffic"
    dense, swa = (json.loads((traffic / f"{name}.json").read_text()) for name in ("packed-4k", "packed-16k-swa-moe"))
    same = lambda mix: {k: v for k, v in mix.items() if k not in ("mode", "why", "sequences")}  # noqa: E731
    assert same(dense) == same(swa) and swa["mode"] == "train_swa_moe"
    assert swa["sequences"] * 16384 == dense["sequences"] * 2 * 4096, "as many tokens as the dense cell's corpus holds rows of its two-row batches"


def test_the_cell_joins_the_accepted_lists_and_brings_its_own_metrics():
    manifest = json.loads((REPO / "BENCHMARK.json").read_text())
    listed = {m["name"] for m in manifest["end_to_end"] + manifest["per_layer"] if CELL in m.get("workloads", ())}
    own = {"train_swa_fwd_ms", "train_swa_bwd_ms", "train_swa_optimizer_ms", "train_swa_attn_window_ms", "train_swa_attn_global_ms",
           "train_swa_moe_ms", "train_swa_moe_dispatch_ms", "train_swa_head_loss_ms", "train_swa_layer_carry_ms",
           "train_swa_unattributed_pct", "train_swa_mfu_pct", "moe_pairs_held_per_token", "moe_aux_loss",
           "flash_attention_window_roofline", "flash_attention_global_roofline"}
    assert holds_at_least(listed, own | {"train_tokens_per_s", "train_host_stall_pct", "train_step_ms", "device_idle_pct.train", "fused_ce_roofline",
                                         "moe_load_max_over_mean"})
    for name in own:
        entry = next(m for m in manifest["per_layer"] if m["name"] == name)
        assert holds_at_least(entry["workloads"], [CELL]) and entry["moves"] == "train_tokens_per_s", "a later cell may join a metric's list"
        spec = json.loads((REPO / "benchmark" / "metrics" / f"{name}.json").read_text())
        assert spec.get("rules", "train_swa_moe") == "train_swa_moe"
    rules = json.loads((REPO / "benchmark" / "scopes" / "train_swa_moe.json").read_text())
    for name in own:
        spec = json.loads((REPO / "benchmark" / "metrics" / f"{name}.json").read_text())
        if spec["reader"] == "scope_time" and "list" in spec:
            assert set(spec["buckets"]) <= {bucket for _, bucket in rules[spec["list"]]}, name
    # the two kinds of attention layer are read apart, and the rules that tell them come before the one that takes any `attn/`
    buckets = [bucket for _, bucket in rules["component"]]
    assert buckets.index("attn_window") < buckets.index("attn") and buckets.index("attn_global") < buckets.index("attn")
    names = [w["name"] for w in manifest["workloads"]]
    assert holds_at_least(names, up_to(ACCEPTED_CELLS, CELL)), "new entries after the accepted ones, wherever later cells go"


def test_the_two_rooflines_count_by_label_and_by_positions():
    window, whole = (json.loads((REPO / "benchmark" / "metrics" / f"flash_attention_{kind}_roofline.json").read_text()) for kind in ("window", "global"))
    assert window["pattern"] == "^flash_attention_window_(fwd|bwd)$" and whole["pattern"] == "^flash_attention_(fwd|bwd)$"
    assert window["shape_function"] == whole["shape_function"] == "flash_attention_window"


def test_a_full_check_at_this_cells_real_cost_fits_the_drivers_budget():
    """`test_manifest.py` does the driver's arithmetic with `run_seconds` + 60 = 100 s a run. This cell's runs take longer, as the
    expert and looped cells' do and for their reason (a float32 reference through two gradients at `highest` precision): its warm and
    cold seconds are my chip runs' (PR 38, PERF.md section 2), in the tests' one table of real costs (`accepted.REAL_COST_S`). With the other long cells' beside them the check of every cell the benchmark has stays inside half of the driver's time."""
    manifest = json.loads((REPO / "BENCHMARK.json").read_text())
    assert CELL in REAL_COST_S
    assert full_check_seconds(manifest) <= DRIVER_SECONDS // 2

