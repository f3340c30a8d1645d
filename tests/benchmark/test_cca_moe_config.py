"""The compressed-convolutional-attention / expert-layer configuration's YAML against its source: the numbers of ZAYA1-8B's
config.json (as the catalog beside the `model-configs` guide records them, copied here because the test machine has no such
catalog), what `reduced` says was changed, and what the model block makes of them: every width uncut. The uncut recipe
`configs/config_zaya1_8b.yaml` is held to the same numbers."""

import json
from pathlib import Path

import yaml

from benchmark.weights_cca_moe import CcaMoEShape
from benchmark.weights_hybrid import resolved
from tests.benchmark.accepted import ACCEPTED_CELLS, DRIVER_SECONDS, REAL_COST_S, full_check_seconds, holds_at_least, up_to

REPO = Path(__file__).resolve().parents[2]
CONFIG_DIR = REPO / "benchmark" / "configs" / "zaya1-8b-ep2"
CELL = "train-zaya1-8b-8k"
DEPTH = 10  # of the source's 40 layers (meta.json, reduced and memory_analysis: the lever ISSUE 40 gave for a step under 12 GiB)
HYBRID = {"partial_rotary_factor": 0.5, "rope_theta": 5000000, "rope_type": "default"}
PUBLISHED = {
    "attention_bias": False, "cca_time0": 2, "cca_time1": 2, "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
    "layer_types": ["hybrid"] * 40, "lm_head_bias": False, "max_position_embeddings": 131072, "model_type": "zaya",
    "moe_intermediate_size": 2048, "num_attention_heads": 8, "num_experts": 16, "num_experts_per_tok": 1, "num_hidden_layers": 40,
    "num_key_value_heads": 2, "partial_rotary_factor": 0.5, "rms_norm_eps": 1e-05,
    "rope_parameters": {"hybrid": HYBRID, "hybrid_sliding": {"partial_rotary_factor": 0.5, "rope_theta": 10000, "rope_type": "default"}, "rope_type": "default"},
    "router_hidden_size": 256, "sliding_window": None, "tie_word_embeddings": True, "vocab_size": 262272,
}
FROM_THE_SIBLING_ROW = {"zaya_use_eda": True, "zaya_use_mod": True, "scale_residual_merge": True, "zaya_high_prec": True}  # ZAYA1-base's keys
NOT_DECAYED = ["embedding", "norm", "router_bias", "cca_vectors", "residual_merge", "router_vectors"]
OWN = {"train_cca_fwd_ms", "train_cca_bwd_ms", "train_cca_optimizer_ms", "train_cca_attn_ms", "train_cca_mixing_ms", "train_cca_moe_ms",
       "train_cca_router_ms", "train_cca_moe_dispatch_ms", "train_cca_residual_ms", "train_cca_head_loss_ms", "train_cca_layer_carry_ms",
       "train_cca_unattributed_pct", "train_cca_mfu_pct", "moe_skip_share", "flash_attention_cca_roofline"}


def test_the_file_is_json_and_holds_the_sources_numbers_but_for_what_reduced_names():
    text = (CONFIG_DIR / "train.yaml").read_text()
    raw = json.loads(text)
    assert raw == yaml.safe_load(text), "one object, whichever parser reads it"
    meta = json.loads((CONFIG_DIR / "meta.json").read_text())
    differing = {key for key, value in PUBLISHED.items() if raw.get(key, "absent") != value}
    assert differing == {"vocab_size"} and raw["vocab_size"] == 32784 == PUBLISHED["vocab_size"] // 8, "this chip's eighth of the tied table's rows"
    assert raw["vocab_size"] % 128 and not raw["vocab_size"] % 16, "16 x 2049: no multiple of 128; the kernels pad it to their blocks"
    assert raw["layer_types_held"] == PUBLISHED["layer_types"][:DEPTH]
    assert {key: raw[key] for key in FROM_THE_SIBLING_ROW} == FROM_THE_SIBLING_ROW
    # `n_layer` is the source's num_hidden_layers in this repo's spelling, `experts_held` the share of its num_experts
    assert set(meta["reduced"]) == {"n_layer", "experts_held", "vocab_size"}
    assert not [key for key in meta["reduced"] if key.endswith(("_dim", "_rank")) or "hidden" in key]
    assert {"stands_for", "assumed", "memory_analysis", "source", "parameters", "catalog"} <= set(meta)
    assert {"skip_column", "key_temperature", "carried_state", "bias_rule", "conv_bias_and_first_merge", "padded_vocabulary", "bias_update_speed",
            "initialisers", "ffn_hidden", "training_job", "packed_rows", "weight_decay", "gelu"} <= set(meta["assumed"])
    assert meta["source"] == "https://huggingface.co/Zyphra/ZAYA1-8B/blob/main/config.json"


def test_the_model_block_reads_every_width_from_the_published_keys():
    raw = yaml.safe_load((CONFIG_DIR / "train.yaml").read_text())
    model = resolved(raw["model_raw"]["config"], raw)
    assert (model["n_embd"], model["n_head_q"], model["n_head_kv"], model["head_dim"], model["vocab_size"], model["n_layer"]) == (2048, 8, 2, 128, 32784, DEPTH)
    assert model["layer_types"] == ["hybrid"] * DEPTH and model["rope_parameters"] == {"hybrid": HYBRID} and "sliding_window" not in model
    assert model["cca_config"] == {"cca_time0": 2, "cca_time1": 2} and model["scale_residual_merge"] is True
    assert model["moe_config"] == {"n_routed_experts": 16, "num_experts_per_tok": 1, "moe_intermediate_size": 2048, "n_shared_experts": 0,
                                   "first_k_dense_replace": 0, "norm_topk_prob": False, "scoring_func": "softmax", "topk_method": "noaux_tc",
                                   "experts_held": 8, "expert_offset": 0, "router": "mlp", "router_hidden_size": 256, "use_eda": True, "use_mod": True,
                                   "bias_update_speed": model["moe_config"]["bias_update_speed"]}
    assert 0 < model["moe_config"]["bias_update_speed"] <= 0.1, "chosen by the spread of the cell's rate over six seeds (meta.json, assumed)"
    assert (model["use_weight_tying"], model["bias"]) == (True, False) and "qk_norm_config" not in model["attention_config"]
    assert all(model[n]["config"] == {"ndim": 2048, "bias": False, "epsilon": 1e-05} for n in ("attention_norm_config", "ffn_norm_config", "lm_head_norm_config"))
    assert 2 * model["ffn_hidden"] // 3 == PUBLISHED["moe_intermediate_size"], "the dense width no layer uses, spelt as this repo's key wants it"
    shape = CcaMoEShape.from_yaml(raw)
    assert (shape.n_layer, shape.latent_heads * shape.head_dim, shape.rotated, shape.rope_theta, shape.router_width) == (DEPTH, 1280, 64, 5e6, 17)
    assert (shape.time0, shape.time1, shape.router_hidden, shape.use_eda, shape.skip_column, shape.norm_eps) == (2, 2, 256, True, True, 1e-5)
    # full rematerialization, the existing variant; decay on every matrix, none on the embedding, the norms and the vectors
    assert raw["remat_model"]["config"]["activation_checkpointing_variant"] == "full_activation_checkpointing"
    assert raw["model"]["config"]["model"]["instance_key"] == "remat_model"
    assert raw["optimizer"]["config"]["weight_decay_groups_excluded"] == NOT_DECAYED
    assert (raw["settings"]["step_profile"]["sequence_length"], raw["settings"]["step_profile"]["local_train_micro_batch_size"]) == (8192, 2)


def test_the_uncut_recipe_holds_the_same_numbers():
    raw = yaml.safe_load((REPO / "configs" / "config_zaya1_8b.yaml").read_text())
    model = resolved(raw["model_raw"]["config"], raw)
    cut = yaml.safe_load((CONFIG_DIR / "train.yaml").read_text())
    held = resolved(cut["model_raw"]["config"], cut)
    assert (model["n_embd"], model["n_head_q"], model["n_head_kv"], model["head_dim"], model["vocab_size"], model["n_layer"]) == (2048, 8, 2, 128, 262272, 40)
    assert model["layer_types"] == PUBLISHED["layer_types"] and model["rope_parameters"] == {"hybrid": HYBRID}
    for key in ("cca_config", "scale_residual_merge", "use_weight_tying", "bias", "ffn_hidden", "attention_norm_config", "poe_type", "activation_type"):
        assert model[key] == held[key], key
    differing = {key for key in held["moe_config"] if model["moe_config"].get(key, "absent") != held["moe_config"][key]}
    assert differing <= {"experts_held", "expert_offset", "bias_update_speed"} and "experts_held" not in model["moe_config"], "all 16 experts on every chip"
    assert raw["optimizer"]["config"]["weight_decay_groups_excluded"] == NOT_DECAYED
    assert CcaMoEShape.from_yaml(raw).all_params() == 8_840_485_624


def test_the_traffic_is_packed_4ks_corpus_letter_for_letter():
    traffic = REPO / "benchmark" / "traffic"
    dense, cca = (json.loads((traffic / f"{name}.json").read_text()) for name in ("packed-4k", "packed-8k-cca-moe"))
    same = lambda mix: {k: v for k, v in mix.items() if k not in ("mode", "why", "sequences")}  # noqa: E731
    assert same(dense) == same(cca) and cca["mode"] == "train_cca_moe"
    assert cca["sequences"] * 8192 == dense["sequences"] * 2 * 4096, "as many tokens as the dense cell's corpus holds"


def test_the_cell_joins_the_accepted_lists_and_brings_its_own_metrics():
    manifest = json.loads((REPO / "BENCHMARK.json").read_text())
    listed = {m["name"] for m in manifest["end_to_end"] + manifest["per_layer"] if CELL in m.get("workloads", ())}
    assert holds_at_least(listed, OWN | {"train_tokens_per_s", "train_host_stall_pct", "train_step_ms", "device_idle_pct.train", "fused_ce_roofline",
                                         "moe_load_max_over_mean", "moe_pairs_held_per_token"})
    assert all("workloads" in m for m in manifest["per_layer"]), "every per-layer metric lists its cells"
    for name in OWN:
        entry = next(m for m in manifest["per_layer"] if m["name"] == name)
        assert holds_at_least(entry["workloads"], [CELL]) and entry["moves"] == "train_tokens_per_s", "a later cell may join a metric's list"
        spec = json.loads((REPO / "benchmark" / "metrics" / f"{name}.json").read_text())
        assert spec.get("rules", "train_cca_moe") == "train_cca_moe"
    rules = json.loads((REPO / "benchmark" / "scopes" / "train_cca_moe.json").read_text())
    buckets = [bucket for _, bucket in rules["component"]]
    for name in OWN:
        spec = json.loads((REPO / "benchmark" / "metrics" / f"{name}.json").read_text())
        if spec["reader"] == "scope_time" and "list" in spec:
            assert set(spec["buckets"]) <= {bucket for _, bucket in rules[spec["list"]]}, name
    # the mixer's parts are read apart, the router's three inside it before the rule that takes any `moe/router/`
    assert buckets.index("cca_conv") < buckets.index("cca") < buckets.index("attn") and buckets.index("moe_router_mlp") < buckets.index("moe_router")
    names = [w["name"] for w in manifest["workloads"]]
    assert holds_at_least(names, up_to(ACCEPTED_CELLS, CELL)), "after the cells accepted before it, wherever later cells go"
    cell = manifest["workloads"][names.index(CELL)]
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("zaya1-8b-ep2", "packed-8k-cca-moe", 1) and len(cell["why"]) <= 200
    config = next(c for c in manifest["configs"] if c["name"] == "zaya1-8b-ep2")
    assert config["reduced"] == ["n_layer", "experts_held", "vocab_size"] and config["file"] == "benchmark/configs/zaya1-8b-ep2/train.yaml" and len(config["why"]) <= 200
    assert config["source"] == "https://huggingface.co/Zyphra/ZAYA1-8B/blob/main/config.json"


def test_the_scope_rules_read_the_mixers_parts_and_the_routers(tmp_path):
    from benchmark import xscope

    rules = xscope.load_rules(REPO / "benchmark" / "scopes" / "train_cca_moe.json")
    step = "jit(train_step)/jit(main)/transpose(jvp(GPT2Module))/run_0/layer_carry/while/body/closed_call/blocks/blocks/checkpoint/rematted_computation/block"
    forward = "jit(train_step)/jit(main)/jvp(GPT2Module)/run_0/layer_carry/while/body/closed_call/blocks/block"
    paths = {
        f"{step}/cca/attn_core/flash_attention_bwd": ("backward", "cca_attn_core"),
        f"{step}/cca/conv/dot_general": ("backward", "cca_conv"),
        f"{forward}/cca/latent/v_attn_prev/dot_general": ("forward", "cca_latent"),
        f"{forward}/cca/qk_norm/rsqrt": ("forward", "cca_qk_norm"),
        f"{forward}/cca/value_shift/pad": ("forward", "cca_value_shift"),
        f"{forward}/cca/out/c_proj/dot_general": ("forward", "cca_out"),
        f"{forward}/cca/rope/cos": ("forward", "cca_rope"),
        f"{forward}/cca/dropout/select": ("forward", "cca"),
        f"{step}/moe/router/router/down/dot_general": ("backward", "moe_router_down"),
        f"{step}/moe/router/router/eda/mul": ("backward", "moe_router_eda"),
        f"{step}/moe/router/router/mlp/fc1/dot_general": ("backward", "moe_router_mlp"),
        f"{step}/moe/router/router/top_k": ("backward", "moe_router"),
        f"{step}/moe/while/body/experts/dot_general": ("backward", "moe_experts"),
        f"{step}/residual/attn_merge/mul": ("backward", "residual"),
        "jit(train_step)/jit(main)/jvp(GPT2Module)/run_0/layer_carry/while/body/add": ("forward", "layer_carry"),
    }
    for path, (want_pass, want_component) in paths.items():
        assert (xscope.bucket_of(path, rules["pass"]), xscope.bucket_of(path, rules["component"])) == (want_pass, want_component), path


def test_a_full_check_at_this_cells_real_cost_fits_the_drivers_budget():
    """`test_manifest.py` does the driver's arithmetic with `run_seconds` + 60 = 100 s a run. This cell's runs take longer, as the
    expert, looped and window-and-global cells' do and for their reason (a float32 reference through two gradients at `highest`
    precision): its warm and cold seconds are my chip runs' (PR 40, PERF.md section 2), in the tests' one table of real costs
    (`accepted.REAL_COST_S`). With the other long cells' beside them the check of every cell the benchmark has
    stays inside half of the driver's time."""
    manifest = json.loads((REPO / "BENCHMARK.json").read_text())
    assert CELL in REAL_COST_S
    assert full_check_seconds(manifest) <= DRIVER_SECONDS // 2

