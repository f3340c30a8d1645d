"""The gated-delta-rule / gated-attention / expert-layer configuration's YAML against its source: the numbers of
Qwen3-Next-80B-A3B-Instruct's config.json (as the catalog beside the `model-configs` guide records them, copied here because the
test machine has no such catalog), what `reduced` says was changed, and what the model block makes of them: every width uncut. The
uncut recipe `configs/config_qwen3_next_80b.yaml` is held to the same numbers. The manifest is read for THIS cell's own entries
and for "the accepted cells come first, in their order": a later cell appended after this one turns nothing here red."""

import json
from pathlib import Path

import yaml

from benchmark.weights_gdn_moe import GdnMoEShape
from benchmark.weights_hybrid import resolved
from tests.benchmark.accepted import ACCEPTED_CELLS, DRIVER_SECONDS, REAL_COST_S, full_check_seconds, holds_at_least, up_to

REPO = Path(__file__).resolve().parents[2]
CONFIG = "qwen3-next-80b-a3b-d4"
CONFIG_DIR = REPO / "benchmark" / "configs" / CONFIG
CELL = "train-qwen3next-80b-16k"
SOURCE = "https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct/blob/main/config.json"
PUBLISHED = {
    "decoder_sparse_step": 1, "full_attention_interval": 4, "head_dim": 256, "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 5120,
    "linear_conv_kernel_dim": 4, "linear_key_head_dim": 128, "linear_num_key_heads": 16, "linear_num_value_heads": 32, "linear_value_head_dim": 128,
    "max_position_embeddings": 262144, "mlp_only_layers": [], "model_type": "qwen3_next", "moe_intermediate_size": 512, "norm_topk_prob": True,
    "num_attention_heads": 16, "num_experts": 512, "num_experts_per_tok": 10, "num_hidden_layers": 48, "num_key_value_heads": 2,
    "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 10000000, "shared_expert_intermediate_size": 512,
    "tie_word_embeddings": False, "use_sliding_window": False, "vocab_size": 151936,
}
PERIOD = ["linear_attention", "linear_attention", "linear_attention", "full_attention"]
NOT_DECAYED = ["embedding", "norm", "gdn_vectors", "shared_expert_gate"]
ZERO_CENTRED = lambda dim: {"norm_type": "rms_norm", "config": {"ndim": dim, "bias": False, "epsilon": 1e-06, "zero_centered": True}}  # noqa: E731
OWN = {"train_gdn_fwd_ms", "train_gdn_bwd_ms", "train_gdn_optimizer_ms", "train_gdn_mixer_ms", "train_gdn_rule_ms", "train_gdn_rule_state_ms",
       "train_gdn_conv_gates_ms", "train_gdn_attn_ms", "train_gdn_moe_ms", "train_gdn_moe_dispatch_ms", "train_gdn_head_loss_ms",
       "train_gdn_layer_carry_ms", "train_gdn_unattributed_pct", "train_gdn_mfu_pct", "gdn_decay_mean", "flash_attention_gdn_roofline"}
JOINED = {"train_tokens_per_s", "train_host_stall_pct", "train_step_ms", "device_idle_pct.train", "fused_ce_roofline", "moe_load_max_over_mean",
          "moe_pairs_held_per_token", "moe_aux_loss"}
ACCEPTED = up_to(ACCEPTED_CELLS, CELL)[:-1]  # the cells accepted before this one


def test_the_file_is_json_and_holds_the_sources_numbers_but_for_what_reduced_names():
    text = (CONFIG_DIR / "train.yaml").read_text()
    raw = json.loads(text)
    assert raw == yaml.safe_load(text), "one object, whichever parser reads it"
    meta = json.loads((CONFIG_DIR / "meta.json").read_text())
    differing = {key for key, value in PUBLISHED.items() if raw.get(key, "absent") != value}
    assert differing == {"vocab_size"} and raw["vocab_size"] == 18992 == PUBLISHED["vocab_size"] // 8, "this chip's eighth of the table's rows"
    assert raw["vocab_size"] % 128 and not raw["vocab_size"] % 16, "16 x 1187: no multiple of 128; the kernels pad it to their blocks"
    # `full_attention_interval` written out for the layers held: layer i holds full attention where (i + 1) % interval == 0
    assert raw["layer_types_held"] == PERIOD == ["full_attention" if (i + 1) % raw["full_attention_interval"] == 0 else "linear_attention" for i in range(4)]
    assert set(meta["reduced"]) == {"n_layer", "experts_held", "vocab_size"}
    assert "48" in meta["reduced"]["n_layer"] and "512" in meta["reduced"]["experts_held"] and "151,936" in meta["reduced"]["vocab_size"]
    assert not [key for key in meta["reduced"] if key.endswith(("_dim", "_rank")) or "hidden" in key]
    assert {"stands_for", "assumed", "memory_analysis", "source", "parameters", "catalog"} <= set(meta)
    assert {"balance_loss", "mtp", "initial_values", "attention_bias", "padded_vocabulary", "ffn_hidden", "training_job", "precision", "weight_decay",
            "packed_rows", "remat", "lm_head_chunk_size"} <= set(meta["assumed"])
    assert "12 such hosts" in meta["stands_for"] and "expert parallel 8" in meta["stands_for"] and meta["source"] == SOURCE
    assert "15.053 GiB" in meta["memory_analysis"] and "1,028,320,320" in meta["parameters"]
    assert meta["memory_analysis"].startswith("AS THE STEP STANDS") and "twice a step" in meta["assumed"]["remat"], "what the program does now comes first"


def test_the_model_block_reads_every_width_from_the_published_keys():
    raw = yaml.safe_load((CONFIG_DIR / "train.yaml").read_text())
    model = resolved(raw["model_raw"]["config"], raw)
    assert (model["n_embd"], model["n_head_q"], model["n_head_kv"], model["head_dim"], model["vocab_size"], model["n_layer"]) == (2048, 16, 2, 256, 18992, 4)
    assert model["layer_types"] == PERIOD and "sliding_window" not in model and model["attn_output_gate"] is True
    assert model["rope_parameters"] == {"full_attention": {"rope_type": "default", "rope_theta": 10000000, "partial_rotary_factor": 0.25}}
    assert model["gdn_config"] == {"linear_num_key_heads": 16, "linear_num_value_heads": 32, "linear_key_head_dim": 128, "linear_value_head_dim": 128,
                                   "linear_conv_kernel_dim": 4}
    assert model["moe_config"] == {"n_routed_experts": 512, "num_experts_per_tok": 10, "moe_intermediate_size": 512, "shared_expert_intermediate_size": 512,
                                   "shared_expert_gate": True, "first_k_dense_replace": 0, "norm_topk_prob": True, "scoring_func": "softmax",
                                   "topk_method": "greedy", "experts_held": 64, "expert_offset": 0,
                                   "router_aux_loss_coef": model["moe_config"]["router_aux_loss_coef"]}
    assert model["moe_config"]["router_aux_loss_coef"] in (0.001, 0.02), "the family's default, or ISSUE 38's other weight: chosen by the spread (meta.json)"
    assert (model["use_weight_tying"], model["bias"]) == (False, False)
    assert all(model[n] == ZERO_CENTRED(2048) for n in ("attention_norm_config", "ffn_norm_config", "lm_head_norm_config"))
    assert model["attention_config"]["qk_norm_config"] == ZERO_CENTRED(256) and len(model["attention_config"]["qkv_transforms"]) == 1
    assert 2 * model["ffn_hidden"] // 3 == PUBLISHED["intermediate_size"], "the dense width no layer uses, spelt as this repo's key wants it"
    shape = GdnMoEShape.from_yaml(raw)
    assert (shape.n_layer, shape.kinds, shape.rotary_dim, shape.rope_theta, shape.conv_width) == (4, ("gdn", "gdn", "gdn", "attn"), 64, 1e7, 8192)
    assert (shape.key_heads, shape.value_heads, shape.key_dim, shape.value_dim, shape.taps, shape.shared_hidden, shape.norm_eps) == (16, 32, 128, 128, 4, 512, 1e-6)
    # full rematerialization, the existing variant; decay on every matrix, none on the embedding, the norms, the rule's vectors and taps, the gate
    assert raw["remat_model"]["config"]["activation_checkpointing_variant"] == "full_activation_checkpointing"
    assert raw["model"]["config"]["model"]["instance_key"] == "remat_model"
    assert raw["optimizer"]["config"]["weight_decay_groups_excluded"] == NOT_DECAYED
    assert (raw["settings"]["step_profile"]["sequence_length"], raw["settings"]["step_profile"]["local_train_micro_batch_size"]) == (16384, 1)


def test_the_uncut_recipe_holds_the_same_numbers():
    raw = yaml.safe_load((REPO / "configs" / "config_qwen3_next_80b.yaml").read_text())
    model = resolved(raw["model_raw"]["config"], raw)
    cut = yaml.safe_load((CONFIG_DIR / "train.yaml").read_text())
    held = resolved(cut["model_raw"]["config"], cut)
    assert (model["n_embd"], model["n_head_q"], model["n_head_kv"], model["head_dim"], model["vocab_size"], model["n_layer"]) == (2048, 16, 2, 256, 151936, 48)
    assert model["layer_types"] == PERIOD * 12 and model["layer_types"].count("full_attention") == 12
    for key in ("gdn_config", "attn_output_gate", "rope_parameters", "use_weight_tying", "bias", "ffn_hidden", "attention_norm_config", "attention_config",
                "lm_head_norm_config", "poe_type", "activation_type"):
        assert model[key] == held[key], key
    differing = {key for key in held["moe_config"] if model["moe_config"].get(key, "absent") != held["moe_config"][key]}
    assert differing <= {"experts_held", "expert_offset", "router_aux_loss_coef"} and "experts_held" not in model["moe_config"], "all 512 experts on every chip"
    assert raw["optimizer"]["config"]["weight_decay_groups_excluded"] == NOT_DECAYED
    assert GdnMoEShape.from_yaml(raw).all_params() == 79_674_391_296


def test_the_traffic_is_packed_4ks_corpus_letter_for_letter():
    traffic = REPO / "benchmark" / "traffic"
    dense, gdn = (json.loads((traffic / f"{name}.json").read_text()) for name in ("packed-4k", "packed-16k-gdn-moe"))
    same = lambda mix: {k: v for k, v in mix.items() if k not in ("mode", "why", "sequences")}  # noqa: E731
    assert same(dense) == same(gdn) and gdn["mode"] == "train_gdn_moe" and gdn["sequences"] == 1024
    assert gdn["sequences"] * 16384 == dense["sequences"] * 2 * 4096, "as many tokens as the dense cell's corpus holds"


def test_the_cell_joins_the_accepted_lists_after_the_accepted_cells_and_brings_its_own_metrics():
    manifest = json.loads((REPO / "BENCHMARK.json").read_text())
    listed = {m["name"] for m in manifest["end_to_end"] + manifest["per_layer"] if CELL in m.get("workloads", ())}
    assert holds_at_least(listed, OWN | JOINED)
    assert all("workloads" in m for m in manifest["per_layer"]), "every per-layer metric lists its cells"
    for name in JOINED:  # appended to a shared list: the cells it held before come first, in the order they had
        cells = next(m for m in manifest["end_to_end"] + manifest["per_layer"] if m["name"] == name)["workloads"]
        assert cells.index(CELL) == len([c for c in cells if c in ACCEPTED]) and [c for c in cells if c in ACCEPTED] == [c for c in ACCEPTED if c in cells]
    rules = json.loads((REPO / "benchmark" / "scopes" / "train_gdn_moe.json").read_text())
    for name in OWN:
        entry = next(m for m in manifest["per_layer"] if m["name"] == name)
        assert holds_at_least(entry["workloads"], [CELL]) and entry["moves"] == "train_tokens_per_s"
        spec = json.loads((REPO / "benchmark" / "metrics" / f"{name}.json").read_text())
        assert spec.get("rules", "train_gdn_moe") == "train_gdn_moe"
        if spec["reader"] == "scope_time" and "list" in spec:
            assert set(spec["buckets"]) <= {bucket for _, bucket in rules[spec["list"]]}, name
    buckets = [bucket for _, bucket in rules["component"]]
    # a group's `intra` lies inside the outer `state` scope: taken first; the rule's parts before the rest of the rule, the mixer's before the rest of it
    assert buckets.index("gdn_rule_intra") < buckets.index("gdn_rule_state") < buckets.index("gdn_rule") < buckets.index("gdn")
    assert buckets.index("attn_gate") < buckets.index("attn") and buckets.index("moe_shared_gate") < buckets.index("moe_shared") < buckets.index("moe")
    names = [w["name"] for w in manifest["workloads"]]
    assert holds_at_least(names, [*ACCEPTED, CELL]), "after the cells accepted before it, wherever later cells go"
    cell = manifest["workloads"][names.index(CELL)]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "packed-16k-gdn-moe", 1) and len(cell["why"]) <= 200
    config = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    assert config["reduced"] == ["n_layer", "experts_held", "vocab_size"] and config["file"] == f"benchmark/configs/{CONFIG}/train.yaml" and len(config["why"]) <= 200
    assert config["source"] == SOURCE


def test_the_cells_limits_say_where_each_came_from():
    spec = json.loads((REPO / "benchmark" / "workloads" / f"{CELL}.json").read_text())
    assert set(spec["limits"]) == {"loss_rel_gap", "grad_norm_rel_gap", "grad_rel_error", "grad_pooled_rel_error", "param_change_rel_gap",
                                   "pairs_held_rel_gap", "aux_loss_rel_gap", "loss_rise_over_window"}
    assert (spec["yaml"], spec["warm_steps"]) == ("train.yaml", 5) and "PR 44" in spec["limits_from"] and "PLACEHOLDER" not in spec["limits_from"]
    assert 0.17 <= spec["limits"]["param_change_rel_gap"] < 1.0, "between the first reading and 1, what a state left unchanged reads"


def test_a_full_check_at_this_cells_real_cost_fits_the_drivers_budget():
    """`test_manifest.py` does the driver's arithmetic with `run_seconds` + 60 = 100 s a run. This cell's runs take longer, as the
    other long cells' do and for their reason (a float32 reference through two gradients at `highest` precision, here with the
    rule as a recurrence over 16,384 positions): its warm and cold seconds are my chip runs' (PR 44, PERF.md section 2), in the tests'
    one table of real costs (`accepted.REAL_COST_S`). With the other long cells' beside them the check of every
    cell the benchmark has stays inside half of the driver's time."""
    manifest = json.loads((REPO / "BENCHMARK.json").read_text())
    assert CELL in REAL_COST_S
    assert full_check_seconds(manifest) <= DRIVER_SECONDS // 2

