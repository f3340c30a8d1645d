"""The Mamba-2 / NoPE-attention / expert-layer configuration's YAML against its source: the numbers of granite-4.0-h-small's
config.json (as the catalog beside the `model-configs` guide records them, copied here because the test machine has no such
catalog), what `reduced` says was changed, and what the model block makes of them: every width uncut. The manifest is read for
THIS cell's own entries and for "the accepted cells come first, in their order": a later cell appended after this one turns
nothing here red."""

import json
from pathlib import Path

import yaml

from benchmark.weights_hybrid import resolved
from benchmark.weights_ssd_moe import SsdMoEShape
from tests.benchmark.accepted import ACCEPTED_CELLS, DRIVER_SECONDS, REAL_COST_S, check_seconds, holds_at_least
from tests.benchmark.test_mesh_config import CELL as MESH_CELL, COST_S as MESH_COST_S

REPO = Path(__file__).resolve().parents[2]
CONFIG = "granite-4.0-h-small-d10"
CONFIG_DIR = REPO / "benchmark" / "configs" / CONFIG
CELL = "train-granite4h-32b-8k"
SOURCE = "https://huggingface.co/ibm-granite/granite-4.0-h-small/blob/main/config.json"
PERIOD = ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
PUBLISHED = {
    "attention_bias": False, "attention_multiplier": 0.0078125, "embedding_multiplier": 12, "hidden_act": "silu", "hidden_size": 4096,
    "intermediate_size": 768, "layer_types": PERIOD * 4, "logits_scaling": 16, "mamba_chunk_size": 256, "mamba_conv_bias": True, "mamba_d_conv": 4,
    "mamba_d_head": 64, "mamba_d_state": 128, "mamba_expand": 2, "mamba_n_groups": 1, "mamba_n_heads": 128, "mamba_proj_bias": False,
    "max_position_embeddings": 131072, "model_type": "granitemoehybrid", "normalization_function": "rmsnorm", "num_attention_heads": 32,
    "num_experts_per_tok": 10, "num_hidden_layers": 40, "num_key_value_heads": 8, "num_local_experts": 72, "position_embedding_type": "nope",
    "residual_multiplier": 0.22, "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000, "shared_intermediate_size": 1536,
    "tie_word_embeddings": True, "vocab_size": 100352,
}
REDUCED = ["n_layer", "experts_held", "vocab_size", "heads_held", "n_head_q", "n_head_kv", "shared_expert_shards"]
NOT_DECAYED = ["embedding", "norm", "ssd_vectors"]
NORM = {"norm_type": "rms_norm", "config": {"ndim": 4096, "bias": False, "epsilon": 1e-05}}
OWN = {"train_ssd_fwd_ms", "train_ssd_bwd_ms", "train_ssd_optimizer_ms", "train_ssd_mixer_ms", "train_ssd_scan_ms", "train_ssd_scan_state_ms",
       "train_ssd_conv_gates_ms", "train_ssd_attn_ms", "train_ssd_moe_ms", "train_ssd_moe_dispatch_ms", "train_ssd_head_loss_ms",
       "train_ssd_layer_carry_ms", "train_ssd_unattributed_pct", "train_ssd_mfu_pct", "ssd_decay_mean", "flash_attention_ssd_roofline"}
JOINED = {"train_tokens_per_s", "train_step_ms", "train_host_stall_pct", "device_idle_pct.train", "fused_ce_roofline", "moe_pairs_held_per_token",
          "moe_load_max_over_mean", "moe_aux_loss", "setup_outside_spans_s", "setup_build_components_s", "setup_init_s", "setup_preflight_s",
          "setup_first_step_s", "setup_warm_steps_s", "setup_compile_miss_s", "setup_compile_hit_s", "train_host_work_ms", "train_loop_unspanned_pct"}
ACCEPTED = [*ACCEPTED_CELLS, MESH_CELL]  # the cells accepted before this one, as the manifest stands after PR 50
COST_S = (146, 406)  # 132-146 s a run warm (set-up 52-56, window 40, reference 21-27), 406 where everything compiles (set-up 166-174, reference 123): my chip runs, PR 52 (PERF.md section 2)


def test_the_file_is_json_and_holds_the_sources_numbers_but_for_what_reduced_names():
    text = (CONFIG_DIR / "train.yaml").read_text()
    raw = json.loads(text)
    assert raw == yaml.safe_load(text), "one object, whichever parser reads it"
    meta = json.loads((CONFIG_DIR / "meta.json").read_text())
    differing = {key for key, value in PUBLISHED.items() if raw.get(key, "absent") != value}
    assert differing == {"vocab_size"} and raw["vocab_size"] == 12544 == PUBLISHED["vocab_size"] // 8 == 98 * 128, "this chip's eighth of the table's rows"
    assert raw["layer_types_held"] == PERIOD == raw["layer_types"][:10], "layers 0 to 9: one whole period, the attention layer at 5"
    assert [i for i, kind in enumerate(raw["layer_types"]) if kind == "attention"] == [5, 15, 25, 35]
    assert list(meta["reduced"]) == REDUCED
    for key, published in (("n_layer", "40"), ("experts_held", "72"), ("vocab_size", "100,352"), ("heads_held", "128"), ("n_head_q", "32"),
                           ("n_head_kv", "8"), ("shared_expert_shards", "1536")):
        assert published in meta["reduced"][key], key
    assert not [key for key in meta["reduced"] if key.endswith(("_dim", "_rank")) or any(w in key for w in ("hidden", "intermediate", "state", "head_dim"))]
    assert {"stands_for", "assumed", "memory_analysis", "source", "parameters", "catalog"} <= set(meta) and meta["source"] == SOURCE
    assert {"balance_loss", "initial_values", "time_step_limit", "hidden_act", "training_job", "precision", "weight_decay", "weights", "packed_rows", "remat",
            "lm_head_chunk_size"} <= set(meta["assumed"])
    for said in ("four such hosts", "expert parallel 8", "tensor-parallel over 4", "32 of a Mamba-2 layer's 128 heads", "over the 2,048 channels this chip holds",
                 "half of what the host would bring it"):
        assert said in meta["stands_for"], said
    assert "1,198,665,824" in meta["parameters"] and "32,207,337,984" in meta["parameters"] and meta["memory_analysis"].startswith("AS THE STEP STANDS")
    assert "ROUNDED to bfloat16 ONCE" in meta["assumed"]["precision"]


def test_the_model_block_reads_every_width_from_the_published_keys():
    raw = yaml.safe_load((CONFIG_DIR / "train.yaml").read_text())
    model = resolved(raw["model_raw"]["config"], raw)
    assert (model["n_embd"], model["n_head_q"], model["n_head_kv"], model["head_dim"], model["vocab_size"], model["n_layer"]) == (4096, 8, 2, 128, 12544, 10)
    assert model["head_dim"] == PUBLISHED["hidden_size"] // PUBLISHED["num_attention_heads"], "the published head, whatever share of the heads is held"
    assert model["layer_types"] == PERIOD and "rope_parameters" not in model and "sliding_window" not in model
    assert model["ssd_config"] == {"mamba_n_heads": 128, "mamba_d_head": 64, "mamba_d_state": 128, "mamba_n_groups": 1, "mamba_d_conv": 4,
                                   "mamba_conv_bias": True, "mamba_chunk_size": 256, "heads_held": 32}
    assert model["moe_config"] == {"n_routed_experts": 72, "num_experts_per_tok": 10, "moe_intermediate_size": 768, "shared_expert_intermediate_size": 1536,
                                   "shared_expert_shards": 4, "first_k_dense_replace": 0, "norm_topk_prob": True, "scoring_func": "softmax",
                                   "topk_method": "greedy", "experts_held": 9, "expert_offset": 0,
                                   "router_aux_loss_coef": model["moe_config"]["router_aux_loss_coef"]}
    assert model["moe_config"]["router_aux_loss_coef"] in (0.001, 0.02), "the family's default, or ISSUE 44's other weight: chosen by the spread (meta.json)"
    assert (model["embedding_multiplier"], model["residual_multiplier"], model["attention_multiplier"], model["logits_scaling"]) == (12, 0.22, 1 / 128, 16)
    assert (model["use_weight_tying"], model["bias"], model["poe_type"]) == (True, False, "NOPE")
    assert all(model[n] == NORM for n in ("attention_norm_config", "ffn_norm_config", "lm_head_norm_config"))
    assert [t["type_hint"] for t in model["attention_config"]["qkv_transforms"]] == ["IdentityTransform"] and "qk_norm_config" not in model["attention_config"]
    assert 2 * model["ffn_hidden"] // 3 == PUBLISHED["intermediate_size"], "the dense width no layer uses, spelt as this repo's key wants it"
    shape = SsdMoEShape.from_yaml(raw)
    assert (shape.n_layer, shape.kinds.count("ssd"), shape.kinds.index("attn"), shape.inner, shape.conv_width, shape.in_width) == (10, 9, 5, 2048, 2304, 4384)
    assert (shape.heads, shape.heads_held, shape.head_dim, shape.state, shape.taps, shape.chunk, shape.shared_hidden, shape.norm_eps) == (128, 32, 64, 128, 4, 256, 384, 1e-5)
    assert (shape.n_head_q_all, shape.n_head_kv_all, shape.n_head_q, shape.n_head_kv, shape.attn_head_dim) == (32, 8, 8, 2, 128)
    assert (shape.ssd_params(), shape.layer_params("ssd"), shape.layer_params("attn"), shape.all_params()) == (26_359_136, 116_315_488, 100_442_112, 1_198_665_824)
    # by required products the mixer is over half of a Mamba-2 layer: 55 M operations a token forward against 34 M for its expert layer
    mixer, experts = 2 * shape.ssd_matmul_params() + shape.scan_forward_ops_per_token(), 2 * (shape.outside_experts_params() + 1.25 * shape.expert_params())
    assert round(mixer / 1e6) == 55 and round(experts / 1e6) == 34
    # full rematerialization, the existing variant; decay on every matrix, none on the table, the norms and the mixer's vectors
    assert raw["remat_model"]["config"]["activation_checkpointing_variant"] == "full_activation_checkpointing"
    assert raw["model"]["config"]["model"]["instance_key"] == "remat_model"
    assert raw["optimizer"]["config"]["weight_decay_groups_excluded"] == NOT_DECAYED
    assert (raw["settings"]["step_profile"]["sequence_length"], raw["settings"]["step_profile"]["local_train_micro_batch_size"]) == (8192, 1)
    assert raw["settings"]["step_profile"]["gradient_accumulation_steps"] == 1 and model["lm_head_chunk_size"] == 1024


def test_the_uncut_model_is_the_published_32b():
    """The shape with nothing cut counts what the name says: 36 Mamba-2 layers, 4 attention layers, the whole table."""
    import dataclasses

    raw = yaml.safe_load((CONFIG_DIR / "train.yaml").read_text())
    whole = dataclasses.replace(SsdMoEShape.from_yaml(raw), kinds=tuple("attn" if kind == "attention" else "ssd" for kind in raw["layer_types"]),
                                heads_held=128, n_head_q=32, n_head_kv=8, shared_shards=1, experts_held=72, vocab_size=100352)
    assert (whole.layer_params("ssd"), whole.layer_params("attn")) == (800_941_696, 740_597_760)
    assert whole.all_params() == 36 * 800_941_696 + 4 * 740_597_760 + 411_045_888 == 32_207_337_984


def test_the_traffic_is_packed_4ks_corpus_letter_for_letter():
    traffic = REPO / "benchmark" / "traffic"
    dense, ssd = (json.loads((traffic / f"{name}.json").read_text()) for name in ("packed-4k", "packed-8k-ssd-moe"))
    same = lambda mix: {k: v for k, v in mix.items() if k not in ("mode", "why", "sequences")}  # noqa: E731
    assert same(dense) == same(ssd) and ssd["mode"] == "train_ssd_moe" and ssd["sequences"] == 1024
    assert 2 * ssd["sequences"] * 8192 == dense["sequences"] * 2 * 4096, "half as many tokens as the dense cell's corpus: 1,024 rows, a new one a step"


def test_the_cell_joins_the_accepted_lists_after_the_accepted_cells_and_brings_its_own_metrics():
    manifest = json.loads((REPO / "BENCHMARK.json").read_text())
    listed = {m["name"] for m in manifest["end_to_end"] + manifest["per_layer"] if CELL in m.get("workloads", ())}
    assert holds_at_least(listed, OWN | JOINED)
    assert all("workloads" in m for m in manifest["per_layer"]), "every per-layer metric lists its cells"
    for name in JOINED:  # appended to a shared list: the cells it held before come first, in the order they had
        cells = next(m for m in manifest["end_to_end"] + manifest["per_layer"] if m["name"] == name)["workloads"]
        assert cells.index(CELL) == len([c for c in cells if c in ACCEPTED]) and [c for c in cells if c in ACCEPTED] == [c for c in ACCEPTED if c in cells]
    rules = json.loads((REPO / "benchmark" / "scopes" / "train_ssd_moe.json").read_text())
    for name in OWN:
        entry = next(m for m in manifest["per_layer"] if m["name"] == name)
        assert holds_at_least(entry["workloads"], [CELL]) and entry["moves"] == "train_tokens_per_s"
        spec = json.loads((REPO / "benchmark" / "metrics" / f"{name}.json").read_text())
        assert spec.get("rules", "train_ssd_moe") == "train_ssd_moe"
        if spec["reader"] == "scope_time" and "list" in spec:
            assert set(spec["buckets"]) <= {bucket for _, bucket in rules[spec["list"]]}, name
    buckets = [bucket for _, bucket in rules["component"]]
    assert buckets.index("ssd_scan_intra") < buckets.index("ssd_scan") and buckets.index("ssd_scan_state") < buckets.index("ssd_scan") < buckets.index("ssd")
    assert buckets.index("attn_core") < buckets.index("attn") and buckets.index("moe_shared") < buckets.index("moe")
    names = [w["name"] for w in manifest["workloads"]]
    assert holds_at_least(names, [*ACCEPTED, CELL]), "after the cells accepted before it, wherever later cells go"
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 1
    cell = manifest["workloads"][names.index(CELL)]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "packed-8k-ssd-moe", 1) and len(cell["why"]) <= 200
    config = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    assert config["reduced"] == REDUCED and config["file"] == f"benchmark/configs/{CONFIG}/train.yaml" and len(config["why"]) <= 200
    assert config["source"] == SOURCE


def test_the_cells_limits_say_where_each_came_from():
    spec = json.loads((REPO / "benchmark" / "workloads" / f"{CELL}.json").read_text())
    assert set(spec["limits"]) == {"loss_rel_gap", "grad_norm_rel_gap", "grad_rel_error", "grad_pooled_rel_error", "param_change_rel_gap",
                                   "pairs_held_rel_gap", "aux_loss_rel_gap", "loss_rise_over_window"}
    assert (spec["yaml"], spec["warm_steps"]) == ("train.yaml", 5) and "PR 52" in spec["limits_from"] and "PLACEHOLDER" not in spec["limits_from"]
    assert isinstance(spec["weights_seed"], int) and spec["weights_seed"] > 2**31, "the cell's own weights: --seed draws the corpus alone"
    assert spec["limits"]["param_change_rel_gap"] < 1.0, "between the first reading and 1, what a state left unchanged reads"
    for variant in ("int8", "no_decay", "no_skip_d", "no_conv_silu", "no_gate", "no_gate_norm", "no_dt_softplus", "residual_1", "attention_rsqrt_d",
                    "embedding_1", "logits_1", "no_gate_renorm"):
        assert variant in spec["limits_from"], f"the control's variant {variant} is read and named"


def test_a_full_check_at_this_cells_real_cost_fits_the_drivers_budget():
    """`test_manifest.py` does the driver's arithmetic with `run_seconds` + 60 = 100 s a run. This cell's runs take longer, as the other
    long cells' do and for their reason (a float32 reference through two gradients at `highest` precision, here with nine recurrences
    over 8,192 positions): its warm and cold seconds are my chip runs' (PR 52, PERF.md section 2) and stand in this file, beside the
    four-chip cell's in its own (`accepted.REAL_COST_S` is a file of the accepted benchmark, and holds the others'). With all seven
    long cells at their real costs the check of every cell the benchmark has stays inside half of the driver's time, and no room is
    left there for a further long cell: the next cell needs a `benchmark` issue first (ROADMAP.md, R12)."""
    manifest = json.loads((REPO / "BENCHMARK.json").read_text())
    cells = [w["name"] for w in manifest["workloads"]]
    long_costs = [REAL_COST_S[c] for c in cells if c in REAL_COST_S] + [MESH_COST_S, COST_S]
    usual = len(cells) - len(long_costs)
    assert CELL not in REAL_COST_S and MESH_CELL not in REAL_COST_S and usual >= 0
    needed = check_seconds(manifest["run_seconds"], usual, long_costs)
    assert needed <= DRIVER_SECONDS // 2, needed
    assert needed + 14 * 131 > DRIVER_SECONDS // 2, "a further long cell (the shortest of them, at its warm cost alone) would not fit"
