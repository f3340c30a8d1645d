"""The looped configuration's YAML against its source: the numbers of Ouro-2.6B's config.json (as the catalog
beside the `model-configs` guide records them, copied here because the test machine has no such catalog), what
`reduced` says was changed, and what the model block makes of them: every width uncut, all four walks."""

import json
from pathlib import Path

import yaml

from benchmark.weights_hybrid import resolved
from benchmark.weights_looped import LoopedShape
from tests.benchmark.accepted import ACCEPTED_CELLS, ACCEPTED_CONFIGS, DRIVER_SECONDS, REAL_COST_S, full_check_seconds, holds_at_least, up_to

REPO = Path(__file__).resolve().parents[2]
CONFIG_DIR = REPO / "benchmark" / "configs" / "ouro-2p6b-t4"
PUBLISHED = {
    "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 5632, "layer_types": ["full_attention"] * 48,
    "max_position_embeddings": 65536, "max_window_layers": 48, "model_type": "ouro", "num_attention_heads": 16, "num_hidden_layers": 48,
    "num_key_value_heads": 16, "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
    "tie_word_embeddings": False, "total_ut_steps": 4, "early_exit_threshold": 1, "use_sliding_window": False, "vocab_size": 49152,
}
NORMS = ("attention_norm_config", "post_attention_norm_config", "ffn_norm_config", "post_ffn_norm_config", "lm_head_norm_config")


def test_the_file_is_json_and_holds_every_number_of_the_source():
    text = (CONFIG_DIR / "train.yaml").read_text()
    raw = json.loads(text)
    assert raw == yaml.safe_load(text), "one object, whichever parser reads it"
    assert {key for key, value in PUBLISHED.items() if raw.get(key, "absent") != value} == set(), "no top-level key differs: the vocabulary is whole"
    meta = json.loads((CONFIG_DIR / "meta.json").read_text())
    assert set(meta["reduced"]) == {"n_layer"}, "only depth is cut (`n_layer`: the source's num_hidden_layers in this repo's spelling)"
    assert {"stands_for", "assumed", "memory_analysis", "source", "parameters"} <= set(meta)
    for stated in ("four_norms_a_block", "final_norm_inside_the_loop", "exit_gate", "loss", "beta", "sequence_length", "ffn_hidden", "remat"):
        assert stated in meta["assumed"], f"what config.json does not state is under `assumed`: {stated}"


def test_the_model_block_reads_every_width_and_the_walks_from_the_published_keys():
    raw = yaml.safe_load((CONFIG_DIR / "train.yaml").read_text())
    model = resolved(raw["model_raw"]["config"], raw)
    assert (model["n_embd"], model["n_head_q"], model["n_head_kv"], model["vocab_size"], model["n_layer"]) == (2048, 16, 16, 49152, 16)
    assert model["loop_config"] == {"total_ut_steps": 4, "exit_gate": True, "beta": 0.1, "early_exit_threshold": 1}
    assert model["attention_config"]["qkv_transforms"] == [{"type_hint": "RotaryTransform", "config": {
        "n_embd": 2048, "n_head": 16, "seq_length_dim": -2, "base_freq": 1000000}}]
    assert all(model[n] == {"norm_type": "rms_norm", "config": {"ndim": 2048, "bias": False, "epsilon": 1e-06}} for n in NORMS)
    assert (model["use_weight_tying"], model["bias"], model["activation_type"], model["poe_type"]) == (False, False, "swiglu", "NOPE")
    shape = LoopedShape.from_yaml(raw)
    assert shape.ffn_hidden == PUBLISHED["intermediate_size"] and shape.head_dim == PUBLISHED["head_dim"] and shape.rope_base == 1e6
    assert (shape.total_ut_steps, shape.n_layer, shape.applications, shape.beta, shape.norm_eps) == (4, 16, 64, 0.1, 1e-6)
    # full rematerialization, the existing variant; decay on every matrix, none on the embedding, the norms and the gate
    assert raw["remat_model"]["config"]["activation_checkpointing_variant"] == "full_activation_checkpointing"
    assert raw["model"]["config"]["model"]["instance_key"] == "remat_model" and raw["loss_fn"]["variant_key"] == "looped_exit_loss"
    assert raw["optimizer"]["config"]["weight_decay_groups_excluded"] == ["embedding", "norm", "exit_gate"]
    profile = raw["settings"]["step_profile"]
    assert (profile["sequence_length"], profile["local_train_micro_batch_size"], profile["gradient_accumulation_steps"]) == (4096, 1, 1)


def test_the_traffic_is_packed_4ks_letter_for_letter():
    traffic = REPO / "benchmark" / "traffic"
    dense, looped = (json.loads((traffic / f"{name}.json").read_text()) for name in ("packed-4k", "packed-4k-looped"))
    assert {k: v for k, v in dense.items() if k not in ("mode", "why")} == {k: v for k, v in looped.items() if k not in ("mode", "why")}
    assert looped["mode"] == "train_looped"


def test_the_cell_joins_the_accepted_lists_and_brings_its_own_metrics():
    manifest = json.loads((REPO / "BENCHMARK.json").read_text())
    cell = "train-ouro-2p6b-4k"
    listed = {m["name"] for m in manifest["end_to_end"] + manifest["per_layer"] if cell in m.get("workloads", ())}
    own = {"train_looped_fwd_ms", "train_looped_bwd_ms", "train_looped_optimizer_ms", "train_looped_attn_ms", "train_looped_mlp_ms",
           "train_looped_norms_ms", "train_looped_head_loss_ms", "train_looped_loop_carry_ms", "train_looped_unattributed_pct",
           "train_looped_mfu_pct", "loop_expected_exit"}
    assert holds_at_least(listed, own | {"train_tokens_per_s", "train_host_stall_pct", "train_step_ms", "device_idle_pct.train", "fused_ce_roofline",
                                         "flash_attention_roofline"})
    for name in own:
        entry = next(m for m in manifest["per_layer"] if m["name"] == name)
        assert holds_at_least(entry["workloads"], [cell]) and entry["moves"] == "train_tokens_per_s"
        spec = json.loads((REPO / "benchmark" / "metrics" / f"{name}.json").read_text())
        assert spec.get("rules", "train_looped") == "train_looped"
    rules = json.loads((REPO / "benchmark" / "scopes" / "train_looped.json").read_text())
    for name in own:
        spec = json.loads((REPO / "benchmark" / "metrics" / f"{name}.json").read_text())
        if spec["reader"] == "scope_time" and "list" in spec:
            assert set(spec["buckets"]) <= {bucket for _, bucket in rules[spec["list"]]}, name
    assert holds_at_least([w["name"] for w in manifest["workloads"]], up_to(ACCEPTED_CELLS, cell)), "after the cells accepted before it, wherever later cells go"
    assert holds_at_least([c["name"] for c in manifest["configs"]], up_to(ACCEPTED_CONFIGS, "ouro-2p6b-t4"))


def test_a_full_check_at_this_cells_real_cost_fits_the_drivers_budget():
    """`test_manifest.py` does the driver's arithmetic with `run_seconds` + 60 = 100 s a run. This cell's runs take
    longer, as the expert cell's do and for its reason (a float32 reference of 1.02 B parameters through two gradients
    at `highest` precision): its warm and cold seconds are my chip runs' (PR 32, PERF.md section 2), in the tests' one
    table of real costs (`accepted.REAL_COST_S`). With the other long cells' beside them the check
    of every cell the benchmark has stays inside half of the driver's time."""
    manifest = json.loads((REPO / "BENCHMARK.json").read_text())
    assert "train-ouro-2p6b-4k" in REAL_COST_S
    assert full_check_seconds(manifest) <= DRIVER_SECONDS // 2

