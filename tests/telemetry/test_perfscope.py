"""Perfscope (telemetry/perfscope.py): the static HLO cost scope, the config
closure the PR-13 acceptance criterion pins (per-bucket costs sum to the module
total on the CPU dryrun config), the profiler-capture bitwise pin, and the
anomaly-detector / profile-window units."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from modalities_tpu.telemetry.perfscope import (
    AnomalyDetector,
    HwSpec,
    ProfileWindow,
    analyze_hlo_text,
    format_perfscope_table,
    perfscope_for_config,
    perfscope_from_compiled,
    write_report,
)

CONFIG = "configs/config_lorem_ipsum_tpu.yaml"


def _assert_closure(mod: dict):
    """The report invariant: every counted instruction landed in exactly one
    bucket, so the bucket sums ARE the module total."""
    total = mod["total"]
    for key in ("ops", "flops", "bytes"):
        assert sum(b[key] for b in mod["buckets"].values()) == total[key], key
    assert sum(b["est_time_s"] for b in mod["buckets"].values()) == pytest.approx(
        total["est_time_s"], rel=1e-9
    )


# ------------------------------------------------------------- HLO walk units


@pytest.mark.xfail(
    strict=False,
    reason="perfscope reads a dot's contracting size from operand shapes printed inline; "
    "this jaxlib's compiled text names operands without shapes, so flops read 2*M*N "
    "(docs/known_failures.md; ROADMAP D6)",
)
def test_matmul_and_elementwise_buckets_on_a_jitted_dot():
    def f(a, b):
        return jnp.tanh(a @ b)

    a = jnp.ones((64, 128), jnp.float32)
    b = jnp.ones((128, 32), jnp.float32)
    compiled = jax.jit(f).lower(a, b).compile()
    report = perfscope_from_compiled(compiled)
    _assert_closure(report)
    assert "matmul" in report["buckets"]
    # dot flops = 2*M*N*K exactly (one dot in the module)
    assert report["buckets"]["matmul"]["flops"] == 2 * 64 * 32 * 128
    # XLA's own cost analysis agrees on flops (the independent cross-check)
    xla_flops = report["xla_cost_analysis"].get("flops")
    assert xla_flops is not None
    assert report["total"]["flops"] == pytest.approx(xla_flops, rel=0.05)


def test_collective_bucket_is_keyed_by_mesh_axis():
    from jax.experimental.shard_map import shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()[:8]).reshape(2, 4), ("dp", "tp"))

    def f(x):
        return jax.lax.psum(x, "tp")

    shmapped = shard_map(f, mesh=mesh, in_specs=P("dp", "tp"), out_specs=P("dp", None))
    x = jnp.ones((8, 16), jnp.float32)
    compiled = jax.jit(shmapped).lower(x).compile()
    report = perfscope_from_compiled(compiled, mesh_axis_sizes={"dp": 2, "tp": 4})
    _assert_closure(report)
    collective = [k for k in report["buckets"] if k.startswith("collective:")]
    assert collective, f"no collective bucket in {sorted(report['buckets'])}"
    # the psum spans the 4-wide tp axis: replica_groups of size 4 resolve to it
    assert "collective:tp" in collective


def test_collective_axis_classifies_dcn_crossing_groups_by_geometry():
    """Multi-slice classification: on a dcn2 x dp_shard4 mesh (partition id =
    slice * 4 + local), a group spanning two slices lands in `collective:dcn`
    even when its size coincides with an ICI axis, while the intra-slice
    all-reduce keeps its axis bucket — and bucket sums still close."""
    hlo = """
HloModule dcn_test

ENTRY %main (a: f32[16]) -> f32[16] {
  %a = f32[16] parameter(0)
  %intra = f32[16] all-reduce(f32[16] %a), replica_groups={{0,1,2,3},{4,5,6,7}}, to_apply=%add
  %cross = f32[16] all-reduce(f32[16] %intra), replica_groups=[4,2]<=[2,4]T(1,0), to_apply=%add
  ROOT %r = f32[16] add(f32[16] %cross, f32[16] %a)
}
"""
    sizes = {"dcn": 2, "dp_shard": 4}  # dict order == mesh axis order, dcn outer
    report = analyze_hlo_text(hlo, mesh_axis_sizes=sizes)
    _assert_closure(report)
    assert report["buckets"]["collective:dp_shard"]["ops"] == 1
    # the iota form [4,2]<=[2,4]T(1,0) pairs {0,4},{1,5},... — each group
    # spans both slices, so it is dcn despite being size 2
    assert report["buckets"]["collective:dcn"]["ops"] == 1
    # same module on a single-slice mesh: no geometry check, size matching only
    single = analyze_hlo_text(hlo, mesh_axis_sizes={"dcn": 1, "dp_shard": 4, "tp": 2})
    assert "collective:dcn" not in single["buckets"]
    assert single["buckets"]["collective:dp_shard"]["ops"] == 1
    assert single["buckets"]["collective:tp"]["ops"] == 1


def test_fusion_double_count_rule_splits_flops_and_bytes():
    """A fused computation: the fusion instruction carries bytes but no flops,
    its inner ops flops but no bytes — each side counted exactly once."""
    hlo = """
HloModule fused_test

%fused_computation (p0: f32[128,128]) -> f32[128,128] {
  %p0 = f32[128,128] parameter(0)
  %mul = f32[128,128] multiply(%p0, %p0)
  ROOT %add = f32[128,128] add(%mul, %p0)
}

ENTRY %main (a: f32[128,128]) -> f32[128,128] {
  %a = f32[128,128] parameter(0)
  ROOT %fusion = f32[128,128] fusion(f32[128,128] %a), kind=kLoop, calls=%fused_computation
}
"""
    report = analyze_hlo_text(hlo)
    _assert_closure(report)
    ew = report["buckets"]["elementwise"]
    assert ew["flops"] == 2 * 128 * 128  # mul + add, once each
    # traffic counted on the fusion only: one operand in + one result out
    assert ew["bytes"] == 2 * 128 * 128 * 4


def test_host_transfer_and_unknown_ops_fall_into_their_buckets():
    hlo = """
HloModule buckets

ENTRY %main (a: f32[16]) -> f32[16] {
  %a = f32[16] parameter(0)
  %out = token[] outfeed(f32[16] %a)
  %rsh = f32[4,4] reshape(f32[16] %a)
  ROOT %r = f32[16] add(f32[16] %a, f32[16] %a)
}
"""
    report = analyze_hlo_text(hlo)
    _assert_closure(report)
    assert report["buckets"]["host_transfer"]["ops"] == 1
    assert report["buckets"]["other"]["ops"] >= 1  # reshape: data movement only


# --------------------------------------------- the acceptance-criterion pin


@pytest.fixture(scope="module")
def dryrun_report():
    """ONE lower+compile of the dryrun config's train step, shared by every
    pin in this module — perfscope_for_config dominates this file's wall time,
    so new consumers (the PR-15 waterfall pin) must ride this fixture instead
    of recompiling."""
    return perfscope_for_config(CONFIG)


def test_perfscope_closure_on_the_cpu_dryrun_config(dryrun_report):
    """`data analyze_perfscope` acceptance pin, in-process (the CLI subprocess
    runs this same perfscope_for_config): the dryrun recipe's train step
    lowers, and every bucket cost sums to the module total."""
    report = dryrun_report
    assert report["world_size"] == jax.device_count() == 8
    mod = report["executables"]["train_step"]
    _assert_closure(mod)
    assert mod["mesh_axes"].get("dp_shard") == 8
    assert mod["total"]["flops"] > 0 and mod["total"]["ops"] > 100
    # an fsdp recipe's step must show dp_shard collectives (the gather/scatter)
    assert "collective:dp_shard" in mod["buckets"]
    # the report round-trips through write_report and renders as a table
    table = format_perfscope_table(report)
    assert "train_step" in table and "matmul" in table


def test_mfu_waterfall_closure_on_the_cpu_dryrun_config(dryrun_report):
    """PR-15 acceptance pin: the MFU waterfall built from the dryrun config's
    REAL perfscope collective fraction closes exactly — deductions sum to
    peak - achieved as a float identity, every term non-negative."""
    from modalities_tpu.telemetry.waterfall import (
        DEDUCTIONS,
        collective_fractions,
        mfu_waterfall,
    )

    fractions = collective_fractions(dryrun_report)
    # the fsdp dryrun step HAS exposed collectives: the fraction is real
    assert fractions is not None
    cf, dcn_cf = fractions
    assert 0.0 < cf < 1.0
    assert dcn_cf == 0.0  # single-slice dryrun mesh: nothing crosses DCN
    buckets = {
        "init": 4.0, "compile_first_step": 9.0, "train_step": 80.0,
        "data_stall": 3.0, "eval": 1.5, "checkpoint": 1.5, "publish": 0.5,
        "other": 0.5,
    }
    waterfall = mfu_waterfall(
        0.41, 100.0, buckets, collective_frac=cf, dcn_collective_frac=dcn_cf
    )
    deductions = waterfall["deductions"]
    assert set(deductions) == set(DEDUCTIONS)
    assert sum(deductions.values()) == waterfall["gap"]  # EXACT, not approx
    assert waterfall["peak"] - waterfall["achieved"] == waterfall["gap"]
    assert all(v >= 0.0 for v in deductions.values())
    # the in-step split used the report's fraction: both sides are charged
    assert deductions["collective_exposure_ici"] > 0.0
    assert deductions["collective_exposure_dcn"] == 0.0
    assert deductions["kernel_inefficiency"] > 0.0


def test_write_report_is_atomic_and_json(tmp_path):
    path = tmp_path / "out" / "perfscope.json"
    write_report({"total": {"ops": 1}}, path)
    assert json.loads(path.read_text()) == {"total": {"ops": 1}}
    assert not path.with_suffix(".json.tmp").exists()


# -------------------------------------------------- profiler capture window


def test_profile_window_capture_is_bitwise_invisible(tmp_path):
    """A jitted step with the profiler window armed produces bit-identical
    outputs to one without — capture must never change the math."""

    @jax.jit
    def step(x, key):
        noise = jax.random.normal(key, x.shape, x.dtype)
        return jnp.tanh(x @ x.T) + 0.01 * noise

    x = jnp.linspace(-1.0, 1.0, 64 * 64, dtype=jnp.float32).reshape(64, 64)
    key = jax.random.PRNGKey(7)

    baseline = [np.asarray(step(x, key)) for _ in range(3)]

    window = ProfileWindow(start_step=1, num_steps=2, out_dir=tmp_path / "prof")
    captured = []
    for step_id in range(3):
        window.maybe_start(step_id)
        out = step(x, key)
        window.maybe_stop(step_id, block_on=out)
        captured.append(np.asarray(out))
    assert window.completed and not window.active
    for a, b in zip(baseline, captured):
        np.testing.assert_array_equal(a, b)  # bitwise
    # the capture actually wrote an xplane artifact
    assert list((tmp_path / "prof").rglob("*.xplane.pb"))


def test_profile_window_from_env(monkeypatch, tmp_path):
    monkeypatch.delenv("MODALITIES_TPU_PROFILE_AT_STEP", raising=False)
    monkeypatch.delenv("MODALITIES_TPU_PROFILE_DIR", raising=False)
    assert ProfileWindow.from_env() is None

    monkeypatch.setenv("MODALITIES_TPU_PROFILE_AT_STEP", "12")
    w = ProfileWindow.from_env(fallback_dir=tmp_path)
    assert (w.start_step, w.num_steps, w.out_dir) == (12, 1, tmp_path)

    monkeypatch.setenv("MODALITIES_TPU_PROFILE_AT_STEP", "12:3")
    monkeypatch.setenv("MODALITIES_TPU_PROFILE_DIR", str(tmp_path / "xp"))
    w = ProfileWindow.from_env(fallback_dir=tmp_path)
    assert (w.start_step, w.num_steps, w.out_dir) == (12, 3, tmp_path / "xp")

    monkeypatch.setenv("MODALITIES_TPU_PROFILE_AT_STEP", "nope")
    with pytest.raises(ValueError, match="expected N or N:K"):
        ProfileWindow.from_env()

    with pytest.raises(ValueError, match="num_steps"):
        ProfileWindow(start_step=1, num_steps=0)


def test_profile_window_outside_the_window_is_a_noop(tmp_path):
    window = ProfileWindow(start_step=5, num_steps=1, out_dir=tmp_path)
    assert window.maybe_start(4) is False
    assert window.maybe_stop(4) is False
    assert not window.active and not window.completed


# ------------------------------------------------------------ anomaly units


def test_anomaly_detector_flags_a_spike_but_not_noise():
    det = AnomalyDetector(window=32, zscore_threshold=6.0, min_history=8)
    rng = np.random.default_rng(0)
    verdicts = [det.observe(1.0 + 0.01 * rng.standard_normal()) for _ in range(20)]
    assert not any(v.is_anomaly for v in verdicts)  # steady state: quiet
    spike = det.observe(3.0)  # a 3x step-time excursion
    assert spike.is_anomaly and spike.zscore > 6.0
    assert det.anomalies == 1
    # EWMA tracks the stream (pulled up slightly by the spike)
    assert 1.0 < spike.ewma < 1.5


def test_anomaly_detector_warmup_and_constant_window():
    det = AnomalyDetector(window=16, min_history=4)
    for _ in range(3):
        assert det.observe(5.0).zscore == 0.0  # no verdicts before min_history
    for _ in range(4):
        det.observe(5.0)
    verdict = det.observe(5.1)  # zero MAD: ANY deviation is infinitely surprising
    assert verdict.zscore == float("inf") and verdict.is_anomaly
    # faster is never an anomaly (one-sided gate)
    assert not det.observe(4.0).is_anomaly
    with pytest.raises(ValueError):
        AnomalyDetector(window=1)
