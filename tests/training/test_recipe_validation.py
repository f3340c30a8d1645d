"""BASELINE acceptance recipes must stay v5p-ready: the full sharded train step for
each pod-scale config lowers over a 64-device virtual mesh and the per-chip state +
activation budget stays inside v5p HBM (VERDICT r3 item 1; BASELINE.md "Target").

Runs each validation in a subprocess (run_validation_subprocess) because the configs
need 64 virtual devices while the ambient test session is pinned to 8.
"""

from pathlib import Path

import pytest

from modalities_tpu.utils.recipe_validation import run_validation_subprocess


CONFIGS_DIR = Path(__file__).parents[2] / "configs"

RECIPES = [
    ("config_2p7b_dp.yaml", {"dp_shard": 64}, 2.6e9, 2.8e9),
    ("config_7b_tp_fsdp.yaml", {"dp_shard": 8, "tp": 8}, 7.3e9, 7.5e9),
    ("config_7b_warmstart_32k.yaml", {"dp_shard": 2, "cp": 4, "tp": 8}, 7.3e9, 7.5e9),
]


_REPORT_CACHE: dict = {}


def _report_for(config_name: str) -> dict:
    if config_name not in _REPORT_CACHE:
        _REPORT_CACHE[config_name] = run_validation_subprocess(CONFIGS_DIR / config_name)
    return _REPORT_CACHE[config_name]


@pytest.mark.parametrize("config_name,mesh_expect,params_lo,params_hi", RECIPES)
def test_recipe_lowers_and_fits_v5p_hbm(config_name, mesh_expect, params_lo, params_hi):
    report = _report_for(config_name)

    assert report["lowering"] == "ok", report
    assert report["world_size"] == 64
    for axis, degree in mesh_expect.items():
        assert report["mesh"][axis] == degree, (axis, report["mesh"])
    assert params_lo < report["num_params"] < params_hi, report["num_params"]

    per_device = report["per_device"]
    assert per_device["total_bytes"] < report["hbm_budget_bytes"], per_device
    assert report["fits_budget"] is True
    # exact state bytes must be the sharded fractions, not the global tree
    assert per_device["params_bytes"] < 2 * 2 * report["num_params"] / report["world_size"] * mesh_expect.get(
        "cp", 1
    ), "params are not actually sharded across the mesh"


def test_warmstart_recipe_full_remat_detected():
    """The 32k recipe must carry full activation checkpointing into the estimate."""
    report = _report_for("config_7b_warmstart_32k.yaml")
    assert report["per_device"]["activation_estimate"]["remat_mode"] == "full"


def test_compile_memory_check_reports_xla_accounting(tmp_path):
    """--compile_memory_check compiles the lowered step and records XLA's own
    per-device memory next to the formula, with the known CPU-graph deltas
    quantified (VERDICT r4 #7). Runs on a dimension-shrunk twin of the 32k
    warmstart recipe so the compile stays test-sized; the full-recipe numbers
    live in docs/scaling_experiments/v5p_readiness.md."""
    import yaml

    cfg = yaml.safe_load((CONFIGS_DIR / "config_7b_warmstart_32k.yaml").read_text())
    for key, val in {
        "n_layer": 2, "n_embd": 128, "n_head_q": 8, "n_head_kv": 2,
        "ffn_hidden": 256, "vocab_size": 256, "lm_head_chunk_size": 64,
    }.items():
        cfg["model_raw"]["config"][key] = val
    mesh = cfg["device_mesh"]["config"]
    mesh.update(device_type="cpu", data_parallel_shard_degree=1,
                context_parallel_degree=4, tensor_parallel_degree=2, world_size=8)
    sp = cfg["settings"]["step_profile"]
    sp["local_train_micro_batch_size"], sp["sequence_length"] = 1, 256
    # the synthetic warmstart folder encodes seen_steps_100000 / 13.1B seen tokens;
    # the twin target extends it consistently at 256 tokens/step (1 mbs x 256 x dp1)
    tt = cfg["settings"]["training_target"]
    tt["num_target_steps"], tt["num_target_tokens"] = 100050, 13107200000 + 50 * 256
    iv = cfg["settings"]["intervals"]
    iv["training_log_interval_in_steps"] = 10
    iv["checkpointing_interval_in_steps"] = 50
    iv["evaluation_interval_in_steps"] = 50
    twin = tmp_path / "twin_32k.yaml"
    twin.write_text(yaml.safe_dump(cfg, default_flow_style=False, sort_keys=False))

    report = run_validation_subprocess(twin, compile_memory_check=True)
    assert report["lowering"] == "ok"
    xla = report["per_device"]["xla_compiled_memory"]
    assert xla["backend"] == "cpu_virtual_mesh"
    assert xla["temp_bytes"] > 0
    assert xla["formula_activations_plus_grads_bytes"] > 0
    assert "temp_over_formula" in xla
    # dao_flash recipe => the SDPA-fallback s^2 delta is quantified, remat-aware
    # (full remat => one block's worth: 1 * b * (Hq/tp) * (S/cp)^2 * 4 bytes)
    assert xla["cpu_sdpa_fallback_s2_residuals_bytes"] == 1 * 1 * (8 // 2) * (256 // 4) ** 2 * 4
    if xla["disagrees_gt_15pct"]:
        assert any("XLA compiled temp" in w for w in report.get("warnings", []))
