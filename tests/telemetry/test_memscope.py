"""memscope (telemetry/memscope.py): the static HBM attribution closure the
PR-17 acceptance criterion pins (bucket sums == memory_analysis totals on the
CPU dryrun config, for BOTH the train-step and serving-decode executables), the
timeline/snapshot bitwise pin, and the carving / lever / fits-check / replay
units."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from click.testing import CliRunner
from flax.core import meta

from modalities_tpu.__main__ import main as cli_main
from modalities_tpu.telemetry.memscope import (
    BUCKETS,
    FITS_CHECK_ENV,
    FitsCheckFailure,
    MemoryTimeline,
    MemscopeWindow,
    classify_memory,
    format_memscope_table,
    is_oom_error,
    memscope_for_config,
    memscope_from_compiled,
    preflight_fits_check,
    rank_levers,
    write_oom_dump,
)
from modalities_tpu.telemetry.metrics import MetricsRegistry
from modalities_tpu.telemetry.slo import (
    replay_memscope_into_registry,
    replay_sink_into_registry,
)

CONFIG = "configs/config_lorem_ipsum_tpu.yaml"


def _assert_closure(report: dict):
    """The report invariant: every memory_analysis byte landed in exactly one
    bucket, so the bucket sums ARE the predicted peak."""
    assert set(report["buckets"]) == set(BUCKETS)
    assert sum(report["buckets"].values()) == report["memory_analysis"]["total_bytes"]
    assert report["predicted_peak_bytes"] == report["memory_analysis"]["total_bytes"]
    assert all(v >= 0 for v in report["buckets"].values())


# ------------------------------------------------------------- carving units


def test_carving_precedence_and_closure_identity():
    categories = {
        "argument_bytes": 1000, "output_bytes": 300, "temp_bytes": 800, "alias_bytes": 50,
    }
    known = {"params": 400, "optimizer_moments": 500, "gradients_accumulators": 300}
    buckets = classify_memory(categories, known)
    assert buckets["params"] == 400
    assert buckets["optimizer_moments"] == 500
    assert buckets["gradients_accumulators"] == 300
    assert buckets["activations_workspace"] == 500  # temp remainder
    # leftover args (100) + the output bytes that alias no argument
    assert buckets["other"] == 100 + (300 - 50)
    assert sum(buckets.values()) == 1000 + 800 + (300 - 50)


def test_carving_clamps_overclaimed_known_bytes():
    """A known tree bigger than the argument bytes (donated/aliased args) must
    not invent bytes: each bucket takes min(known, remaining)."""
    categories = {"argument_bytes": 100, "output_bytes": 0, "temp_bytes": 10, "alias_bytes": 0}
    buckets = classify_memory(categories, {"params": 80, "optimizer_moments": 80, "kv_pool": 80})
    assert buckets["params"] == 80
    assert buckets["optimizer_moments"] == 20  # clamped to what is left
    assert buckets["kv_pool"] == 0
    assert sum(buckets.values()) == 110


def test_classify_with_no_known_bytes_is_still_closed():
    categories = {"argument_bytes": 7, "output_bytes": 3, "temp_bytes": 5, "alias_bytes": 2}
    buckets = classify_memory(categories, None)
    assert buckets["activations_workspace"] == 5
    assert buckets["other"] == 7 + (3 - 2)
    assert sum(buckets.values()) == 13


def test_memscope_from_compiled_on_a_jitted_fn():
    def f(a, b):
        return jnp.tanh(a @ b)

    a = jnp.ones((64, 128), jnp.float32)
    b = jnp.ones((128, 32), jnp.float32)
    compiled = jax.jit(f).lower(a, b).compile()
    report = memscope_from_compiled(compiled, {"params": a.nbytes}, {"kind": "train"})
    _assert_closure(report)
    assert report["levers"], "rank_levers must never return empty"


# -------------------------------------------------------------- lever ranking


def _report(buckets, context):
    total = sum(buckets.values())
    return {
        "buckets": buckets, "context": context,
        "memory_analysis": {"total_bytes": total}, "predicted_peak_bytes": total,
    }


def test_levers_rank_by_modeled_savings_and_respect_context():
    report = _report(
        {"params": 100, "optimizer_moments": 8000, "gradients_accumulators": 100,
         "activations_workspace": 2000, "kv_pool": 0, "other": 0},
        {"kind": "train", "zero_stage": 0, "dp_replicate": 4, "remat_variant": None},
    )
    levers = rank_levers(report)
    names = [entry["lever"] for entry in levers]
    # zero-1 sheds 3/4 of 8000 — the biggest modeled lever leads the message
    assert names[0] == "zero_stage"
    assert levers[0]["modeled_savings_bytes"] == 8000 * 3 // 4
    assert "remat" in names and "gradient_accumulation_steps" in names
    assert "quant_kv" not in names  # no KV pool in a train step
    # already-sharded optimizer: the zero lever disappears
    report["context"]["zero_stage"] = 1
    assert "zero_stage" not in [entry["lever"] for entry in rank_levers(report)]


def test_serving_levers_target_the_kv_pool_and_never_suggest_remat():
    report = _report(
        {"params": 500, "optimizer_moments": 0, "gradients_accumulators": 0,
         "activations_workspace": 100, "kv_pool": 6000, "other": 0},
        {"kind": "serving", "kv_cache": "paged", "paged_num_blocks": 64, "quant_kv": "none"},
    )
    names = [entry["lever"] for entry in rank_levers(report)]
    assert names[0] in ("paged_num_blocks", "quant_kv")  # both model kv/2
    assert "remat" not in names and "gradient_accumulation_steps" not in names
    # int8 KV already: only the block-count lever remains
    report["context"]["quant_kv"] = "int8"
    assert "quant_kv" not in [entry["lever"] for entry in rank_levers(report)]


def test_levers_fall_back_to_remat_when_nothing_is_modeled():
    levers = rank_levers(_report({name: 0 for name in BUCKETS}, {"kind": "serving"}))
    assert levers and levers[0]["lever"] == "remat"


# ------------------------------------------------------------ fits-check units


def test_fits_check_passes_under_budget_and_fails_over_it():
    report = _report(
        {"params": 0, "optimizer_moments": 0, "gradients_accumulators": 0,
         "activations_workspace": 900, "kv_pool": 0, "other": 0},
        {"kind": "train", "remat_variant": None},
    )
    report["levers"] = rank_levers(report)
    verdict = preflight_fits_check(report, bytes_limit=1000, env={})
    assert verdict["checked"] and verdict["fits"] is True
    with pytest.raises(FitsCheckFailure) as err:
        preflight_fits_check(report, bytes_limit=800, env={})
    # the failure names the levers and the escape hatch
    assert "remat" in str(err.value)
    assert f"{FITS_CHECK_ENV}=warn" in str(err.value)


def test_fits_check_warn_and_off_modes_downgrade_the_verdict():
    report = _report({name: 100 for name in BUCKETS}, {"kind": "train"})
    warn = preflight_fits_check(report, bytes_limit=1, env={FITS_CHECK_ENV: "warn"})
    assert warn["checked"] and warn["fits"] is False  # logged, not raised
    off = preflight_fits_check(report, bytes_limit=1, env={FITS_CHECK_ENV: "off"})
    assert off["checked"] is False and off["fits"] is None


def test_fits_check_is_inert_without_a_budget():
    """CPU backends report no bytes_limit: there is no budget to miss."""
    report = _report({name: 10**12 for name in BUCKETS}, {"kind": "train"})
    verdict = preflight_fits_check(report, bytes_limit=None, env={})
    assert verdict["checked"] is False  # min_bytes_limit() is None on CPU


# --------------------------------------------- the acceptance-criterion pins


@pytest.fixture(scope="module")
def dryrun_memscope():
    """ONE lower+compile of the dryrun config's train step for every static pin
    in this module (the compile dominates this file's wall time)."""
    return memscope_for_config(CONFIG)


def test_train_step_closure_on_the_cpu_dryrun_config(dryrun_memscope):
    """`data analyze_memscope` acceptance pin, in-process (the CLI subprocess
    runs this same memscope_for_config): bucket sums == memory_analysis totals
    on the dryrun recipe's real compiled train step."""
    assert dryrun_memscope["world_size"] == jax.device_count() == 8
    report = dryrun_memscope["executables"]["train_step"]
    _assert_closure(report)
    # the fsdp train step has real params/moments/grads attributed
    assert report["buckets"]["params"] > 0
    assert report["buckets"]["optimizer_moments"] > report["buckets"]["params"]  # adam: 2 moments
    assert report["buckets"]["gradients_accumulators"] > 0
    assert report["context"]["kind"] == "train"
    assert report["levers"]
    # and it renders: every bucket row plus the predicted peak line
    table = format_memscope_table(dryrun_memscope)
    assert "train_step" in table and "params" in table and "predicted per-device peak" in table


def test_serving_decode_closure_on_the_tiny_model():
    """The second executable the criterion names: the engine's batched decode
    step closes the same way, with the KV pool carved out of argument bytes."""
    from modalities_tpu.serving.engine import ServingEngine
    from tests.models.test_gpt2_model import tiny_gpt2

    model = tiny_gpt2("manual")
    params = meta.unbox(model.init_params(jax.random.PRNGKey(0)))
    engine = ServingEngine(model, params, max_batch_slots=2)
    report = engine.memscope_report()
    _assert_closure(report)
    assert report["buckets"]["params"] > 0
    assert report["buckets"]["kv_pool"] > 0
    assert report["context"]["kind"] == "serving"
    # no training lever may leak into a serving report
    assert "remat" not in [entry["lever"] for entry in report["levers"]]
    # the report is cached for the engine's OOM dump path
    assert engine._memscope_cache is report


# ------------------------------------------------- timeline + snapshot window


def test_memscope_window_from_env(monkeypatch, tmp_path):
    monkeypatch.delenv("MODALITIES_TPU_MEMSCOPE_AT_STEP", raising=False)
    monkeypatch.delenv("MODALITIES_TPU_MEMSCOPE_DIR", raising=False)
    assert MemscopeWindow.from_env() is None

    monkeypatch.setenv("MODALITIES_TPU_MEMSCOPE_AT_STEP", "7")
    w = MemscopeWindow.from_env(fallback_dir=tmp_path)
    assert (w.start_step, w.num_steps, w.out_dir) == (7, 1, tmp_path)

    monkeypatch.setenv("MODALITIES_TPU_MEMSCOPE_AT_STEP", "7:3")
    monkeypatch.setenv("MODALITIES_TPU_MEMSCOPE_DIR", str(tmp_path / "mem"))
    w = MemscopeWindow.from_env(fallback_dir=tmp_path)
    assert (w.start_step, w.num_steps, w.out_dir) == (7, 3, tmp_path / "mem")

    monkeypatch.setenv("MODALITIES_TPU_MEMSCOPE_AT_STEP", "nope")
    with pytest.raises(ValueError, match="expected N or N:K"):
        MemscopeWindow.from_env()

    with pytest.raises(ValueError, match="num_steps"):
        MemscopeWindow(start_step=1, num_steps=0)


def test_timeline_and_snapshot_are_bitwise_invisible(tmp_path):
    """A jitted step with the memory timeline sampling and a live-array
    snapshot window armed produces bit-identical outputs to one without —
    observation must never change the math (the perfscope-window pin, memory
    edition)."""

    @jax.jit
    def step(x, key):
        noise = jax.random.normal(key, x.shape, x.dtype)
        return jnp.tanh(x @ x.T) + 0.01 * noise

    x = jnp.linspace(-1.0, 1.0, 64 * 64, dtype=jnp.float32).reshape(64, 64)
    key = jax.random.PRNGKey(7)
    baseline = [np.asarray(step(x, key)) for _ in range(3)]

    timeline = MemoryTimeline(executable="train_step")
    window = MemscopeWindow(start_step=1, num_steps=1, out_dir=tmp_path / "mem")
    observed = []
    for step_id in range(3):
        out = step(x, key)
        timeline.sample(step_id)
        window.maybe_snapshot(step_id)
        observed.append(np.asarray(out))
    for a, b in zip(baseline, observed):
        np.testing.assert_array_equal(a, b)  # bitwise
    # the snapshot window actually wrote its attribution artifact
    snapshot = json.loads((tmp_path / "mem" / "memscope_live_arrays_step_1.json").read_text())
    assert snapshot["step"] == 1 and snapshot["count"] >= 1
    assert snapshot["arrays"] and snapshot["arrays"][0]["nbytes"] >= snapshot["arrays"][-1]["nbytes"]
    assert window.maybe_snapshot(2) is None  # outside [N, N+K): a no-op


# ------------------------------------------------------------- OOM dump units


def test_is_oom_error_matches_the_allocation_family_only():
    assert is_oom_error(RuntimeError("RESOURCE_EXHAUSTED: Out of memory allocating 1 bytes"))
    assert is_oom_error(ValueError("backend says: out of memory"))
    assert not is_oom_error(RuntimeError("shape mismatch"))


def test_oom_dump_is_parseable_and_names_levers(tmp_path):
    timeline = MemoryTimeline(executable="train_step")
    timeline.recent.append({"step": 4, "bytes_in_use": 123})
    static = _report(
        {"params": 10, "optimizer_moments": 600, "gradients_accumulators": 10,
         "activations_workspace": 40, "kv_pool": 0, "other": 0},
        {"kind": "train", "zero_stage": 0, "dp_replicate": 8},
    )
    path = write_oom_dump(
        tmp_path / "artifacts", rank=0, step=5,
        exc=RuntimeError("RESOURCE_EXHAUSTED: failed to allocate"),
        static_report=static, timeline=timeline,
    )
    assert path is not None and path.name == "oom_dump_rank_0_step_5.json"
    dump = json.loads(path.read_text())
    assert dump["event"] == "oom" and dump["step"] == 5
    assert "RESOURCE_EXHAUSTED" in dump["error"]
    assert dump["timeline_tail"] == [{"step": 4, "bytes_in_use": 123}]
    # at least one concrete lever, ranked from the static report
    assert dump["suggested_levers"][0]["lever"] == "zero_stage"
    assert not path.with_suffix(".json.tmp").exists()  # atomic


def test_oom_dump_without_a_static_report_suggests_the_default_levers(tmp_path):
    path = write_oom_dump(tmp_path, rank=1, step=0, exc=RuntimeError("Out of memory"))
    dump = json.loads(path.read_text())
    assert {entry["lever"] for entry in dump["suggested_levers"]} >= {"zero_stage", "remat"}


# --------------------------------------------------------------- SLO replay


def test_replay_folds_timeline_events_to_max_in_use_and_min_headroom(tmp_path):
    sink = tmp_path / "telemetry_rank_0.jsonl"
    rows = [
        {"event": "memscope_timeline", "step": 1, "bytes_in_use": 100,
         "headroom_bytes": {"tpu:0": 900, "tpu:1": 700}},
        {"event": "memscope_timeline", "step": 2, "bytes_in_use": 250,
         "headroom_bytes": {"tpu:0": 750, "tpu:1": 950}},
    ]
    sink.write_text("".join(json.dumps(r) + "\n" for r in rows))
    reg = MetricsRegistry()
    assert replay_sink_into_registry(sink, reg) >= 2  # goodput lift may add one
    # max in-use (a ceiling objective judges the worst moment) ...
    assert reg.gauge("training_hbm_bytes_in_use", "").value() == 250.0
    # ... and per-device MIN headroom (a floor objective judges the tightest)
    headroom = reg.gauge("memscope_device_headroom_bytes", "")
    assert headroom.value(device="tpu:0") == 750.0
    assert headroom.value(device="tpu:1") == 700.0


def test_replay_memscope_report_lifts_buckets_and_predicted_peak(tmp_path):
    report = {"executables": {"train_step": {
        "buckets": {"params": 40, "other": 10},
        "memory_analysis": {"total_bytes": 50},
    }}}
    path = tmp_path / "memscope.json"
    path.write_text(json.dumps(report))
    reg = MetricsRegistry()
    assert replay_memscope_into_registry(path, reg) == 3  # 2 buckets + the peak
    bucket = reg.gauge("memscope_bucket_bytes", "")
    assert bucket.value(executable="train_step", bucket="params") == 40.0
    assert reg.gauge("memscope_predicted_peak_bytes", "").value(executable="train_step") == 50.0


def test_check_slo_judges_a_memscope_report_offline(tmp_path):
    """`data check_slo --memscope_path` makes bucket-level memory objectives
    judgeable from the recorded artifact alone."""
    (tmp_path / "memscope.json").write_text(json.dumps(
        {"executables": {"train_step": {
            "buckets": {"params": 2 * 10**9}, "memory_analysis": {"total_bytes": 2 * 10**9},
        }}}
    ))
    spec = tmp_path / "slo.yaml"
    spec.write_text(
        "objectives:\n"
        "  - name: peak_under_4g\n"
        "    expr: 'memscope_predicted_peak_bytes < 4e9'\n"
    )
    result = CliRunner().invoke(cli_main, [
        "data", "check_slo", "--slo_path", str(spec),
        "--memscope_path", str(tmp_path / "memscope.json"),
    ])
    assert result.exit_code == 0, result.output
    assert "all ok" in result.output
    # and the same artifact breaches a tighter budget
    spec.write_text(
        "objectives:\n"
        "  - name: peak_under_1g\n"
        "    expr: 'memscope_predicted_peak_bytes < 1e9'\n"
    )
    result = CliRunner().invoke(cli_main, [
        "data", "check_slo", "--slo_path", str(spec),
        "--memscope_path", str(tmp_path / "memscope.json"),
    ])
    assert result.exit_code != 0
    assert "BREACH" in result.output and "peak_under_1g" in result.output


def test_analyze_memscope_cli_is_registered():
    """The subprocess path re-runs memscope_for_config (pinned in-process
    above); here pin the CLI wiring: command exists with the perfscope-family
    options."""
    result = CliRunner().invoke(cli_main, ["data", "analyze_memscope", "--help"])
    assert result.exit_code == 0, result.output
    assert "--config_file_path" in result.output
    assert "--report_path" in result.output and "--as_json" in result.output
