"""bench.py TPU-probe retry ladder + CPU-fallback provenance (VERDICT r3 #3):
wedged-chip windows have cleared mid-round before, so the probe must retry on a
ladder — but ONLY on the transient wedged condition — and a final CPU line must
carry the best verified hardware number."""

import importlib.util
from pathlib import Path

import pytest


@pytest.fixture
def bench(monkeypatch):
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    monkeypatch.setenv("BENCH_PROBE_LADDER", "0,0,0")
    # the budget guard pins its deadline in this env var; setting it to "" here
    # makes monkeypatch restore "" afterwards, so no test leaks a deadline into
    # the next one (an expired inherited deadline would os._exit the test runner)
    monkeypatch.setenv("BENCH_DEADLINE_TS", "")
    spec = importlib.util.spec_from_file_location(
        "bench_under_test", Path(__file__).parents[1] / "bench.py"
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_ladder_retries_until_wedge_clears(bench):
    calls = []

    def probe(timeout_s=180):
        calls.append(1)
        return "tpu" if len(calls) >= 3 else "wedged"

    bench._probe_tpu = probe
    assert bench._probe_tpu_ladder() is True
    assert len(calls) == 3


def test_ladder_exhausts_then_reports_unreachable(bench):
    calls = []
    bench._probe_tpu = lambda timeout_s=180: (calls.append(1), "wedged")[1]
    assert bench._probe_tpu_ladder() is False
    assert len(calls) == 3  # one per ladder rung, no infinite retry


def test_clean_no_tpu_short_circuits_without_retry(bench):
    """'No TPU on this host' is permanent: the ladder must NOT burn 30 minutes of
    sleeps re-probing a laptop/CI box."""
    calls = []
    bench._probe_tpu = lambda timeout_s=180: (calls.append(1), "no_tpu")[1]
    assert bench._probe_tpu_ladder() is False
    assert len(calls) == 1


def test_empty_ladder_env_still_probes_once(bench, monkeypatch):
    """BENCH_PROBE_LADDER='' must not silently skip probing a healthy TPU."""
    monkeypatch.setenv("BENCH_PROBE_LADDER", "")
    calls = []
    bench._probe_tpu = lambda timeout_s=180: (calls.append(1), "tpu")[1]
    assert bench._probe_tpu_ladder() is True
    assert len(calls) == 1


def test_ladder_skip_flag(bench, monkeypatch):
    monkeypatch.setenv("BENCH_TPU_PROBE", "0")
    bench._probe_tpu = lambda timeout_s=180: pytest.fail("probe must not run when skipped")
    assert bench._probe_tpu_ladder() is True


def test_cpu_platform_short_circuits(bench, monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert bench._probe_tpu_ladder() is False


def test_last_verified_tpu_provenance(bench):
    """The CPU-fallback provenance block must carry the verified measurement and
    point at a source document that exists and contains the number."""
    info = bench.LAST_VERIFIED_TPU
    assert info["mfu"] == pytest.approx(0.6882)
    source = Path(__file__).parents[1] / info["source"].split(" ")[0]
    assert source.is_file(), info["source"]
    assert str(info["mfu"]) in source.read_text()


def test_probe_error_short_circuits_without_retry(bench):
    """A crashed probe child WITHOUT TPU-runtime markers (broken venv, libtpu ABI
    mismatch) is permanent: fall back immediately and loudly, never sleep the
    ladder against it."""
    calls = []
    bench._probe_tpu = lambda timeout_s=180: (calls.append(1), "probe_error")[1]
    assert bench._probe_tpu_ladder() is False
    assert len(calls) == 1


def test_probe_budget_caps_the_ladder(bench, monkeypatch):
    """A wedged chip must cost at most BENCH_PROBE_BUDGET_S: rungs whose sleep
    leaves no room for a useful probe are skipped outright (no sleeping against a
    dead budget), so the r5 failure mode — the ladder alone outliving the driver
    window and emitting NO JSON — cannot recur."""
    monkeypatch.setenv("BENCH_PROBE_LADDER", "0,600,1200")
    monkeypatch.setenv("BENCH_PROBE_BUDGET_S", "200")
    calls = []
    bench._probe_tpu = lambda timeout_s=180: (calls.append(timeout_s), "wedged")[1]
    assert bench._probe_tpu_ladder() is False
    # rung 1 probes (sleep 0); rung 2's 600 s sleep exceeds the remaining budget
    # and is skipped BEFORE sleeping — exactly one probe, near-instant return
    assert len(calls) == 1


def test_probe_budget_shrinks_probe_timeout(bench, monkeypatch):
    """The probe child's own timeout is clamped to the remaining budget, so even
    the FIRST probe cannot run past BENCH_PROBE_BUDGET_S."""
    monkeypatch.setenv("BENCH_PROBE_LADDER", "0")
    monkeypatch.setenv("BENCH_PROBE_BUDGET_S", "100")
    timeouts = []
    bench._probe_tpu = lambda timeout_s=180: (timeouts.append(timeout_s), "wedged")[1]
    assert bench._probe_tpu_ladder() is False
    assert len(timeouts) == 1 and timeouts[0] <= 100.0


# ------------------------------------------------- leader-first window flow


class _FakeTpuDev:
    platform = "tpu"
    device_kind = "TPU v5e"


def _drive_main(bench, monkeypatch, capsys, candidate_results):
    """Run bench.main() with a fake TPU and stubbed candidate timings.
    candidate_results: {config_name: result-dict | Exception}."""
    import json

    monkeypatch.setenv("BENCH_TPU_PROBE", "0")
    monkeypatch.delenv("BENCH_CONFIG", raising=False)
    import jax

    monkeypatch.setattr(jax, "devices", lambda *a, **k: [_FakeTpuDev()])
    runs = []

    def fake_run(cand, iters):
        name = cand[0]
        runs.append(name)
        outcome = candidate_results.get(name, RuntimeError(f"unexpected candidate {name}"))
        if isinstance(outcome, Exception):
            raise outcome
        return json.loads(json.dumps(outcome))  # fresh copy per call

    monkeypatch.setattr(bench, "_run_candidate", fake_run)
    bench.main()
    line = [ln for ln in capsys.readouterr().out.splitlines() if ln.startswith("{")][-1]
    return json.loads(line), runs


def _result(name, value):
    return {"metric": "gpt_train_mfu_single_chip", "value": value,
            "unit": "MFU", "vs_baseline": 1.0, "detail": {"config": name}}


def test_window_times_leader_first_then_explores_and_keeps_leader(bench, monkeypatch, capsys):
    """Leader-first ordering (VERDICT r4 weak #7): the 64k leader is timed before
    the 80k head; a slower exploration is recorded, not promoted."""
    out, runs = _drive_main(bench, monkeypatch, capsys, {
        "680m_64k_flash_chunked": _result("680m_64k_flash_chunked", 0.69),
        "680m_80k_flash_chunked": _result("680m_80k_flash_chunked", 0.66),
    })
    assert runs[0] == "680m_64k_flash_chunked"
    assert out["detail"]["config"] == "680m_64k_flash_chunked" and out["value"] == 0.69
    assert out["detail"]["exploration"]["outcome"].startswith("slower")


def test_window_promotes_faster_exploration_but_carries_leader_number(bench, monkeypatch, capsys):
    """When 80k wins, the fresh leader re-time (the round's key evidence) rides
    along in detail.leader_rerun, and the never-lower guard does NOT burn a third
    run even though the value is below the verified 0.6882."""
    out, runs = _drive_main(bench, monkeypatch, capsys, {
        "680m_64k_flash_chunked": _result("680m_64k_flash_chunked", 0.60),
        "680m_80k_flash_chunked": _result("680m_80k_flash_chunked", 0.65),
    })
    assert out["detail"]["config"] == "680m_80k_flash_chunked" and out["value"] == 0.65
    assert out["detail"]["leader_rerun"]["value"] == 0.60
    assert runs == ["680m_64k_flash_chunked", "680m_80k_flash_chunked"]  # exactly two


def test_window_keeps_leader_when_exploration_crashes(bench, monkeypatch, capsys):
    out, runs = _drive_main(bench, monkeypatch, capsys, {
        "680m_64k_flash_chunked": _result("680m_64k_flash_chunked", 0.69),
        "680m_80k_flash_chunked": RuntimeError("RESOURCE_EXHAUSTED: hbm"),
    })
    assert out["value"] == 0.69
    assert out["detail"]["exploration"]["outcome"].startswith("failed")


def test_never_lower_guard_only_when_leader_was_not_timed(bench, monkeypatch, capsys):
    """Leader OOMs -> ladder falls to 32k; its sub-verified score triggers ONE
    leader retry (which also fails) and the 32k result stands."""
    oom = RuntimeError("RESOURCE_EXHAUSTED: out of memory")
    out, runs = _drive_main(bench, monkeypatch, capsys, {
        "680m_64k_flash_chunked": oom,
        "680m_80k_flash_chunked": oom,
        "680m_32k_flash_chunked": _result("680m_32k_flash_chunked", 0.64),
    })
    assert out["detail"]["config"] == "680m_32k_flash_chunked"
    # leader tried once by the ladder; guard does not retry it again (it already
    # failed this run), and exploration never runs without a leader result
    assert runs.count("680m_64k_flash_chunked") == 1


# ------------------------------------------------- end-to-end CPU smoke


@pytest.mark.slow  # ~41 s; bench e2e family — the ladder/JSON-line contract stays
# in tier-1 via test_wedged_ladder_emits_probe_wedged_json_and_exits_clean (and
# the subprocess budget e2e below already rides slow)
def test_bench_cpu_smoke_emits_one_json_line():
    """The whole bench, minimally configured, as the driver runs it: forced CPU,
    probe off, one iteration — must exit 0 and print EXACTLY one parseable JSON
    line carrying the wall/device split keys."""
    import json
    import os
    import subprocess
    import sys

    env = {**os.environ, "JAX_PLATFORMS": "cpu", "BENCH_TPU_PROBE": "0",
           "BENCH_ITERS": "1", "BENCH_REPEATS": "1"}
    proc = subprocess.run(
        [sys.executable, str(Path(__file__).parents[1] / "bench.py")],
        capture_output=True, text=True, timeout=420, env=env,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    json_lines = [ln for ln in proc.stdout.splitlines() if ln.startswith("{")]
    assert len(json_lines) == 1, proc.stdout
    out = json.loads(json_lines[0])
    assert out["metric"] and isinstance(out["value"], float)
    detail = out["detail"]
    for key in ("wall_step_time_s", "tokens_per_sec_wall", "mfu_wall",
                "host_stall_s", "boundary_stall_s", "goodput"):
        assert key in detail, (key, sorted(detail))
    # same schema as the telemetry subsystem's ledger: % + bucket seconds that
    # sum to the candidate's wall time (the untracked remainder is in `other`)
    goodput = detail["goodput"]
    assert 0.0 < goodput["goodput_pct"] <= 100.0
    assert goodput["buckets"]["train_step"] > 0.0
    assert goodput["buckets"]["compile_first_step"] > 0.0
    assert sum(goodput["buckets"].values()) == pytest.approx(goodput["wall_s"], rel=0.05)


def test_wedged_ladder_emits_probe_wedged_json_and_exits_clean(bench, monkeypatch, capsys):
    """Probe ladder exhausts fully wedged -> main() must emit EXACTLY one valid
    JSON line with probe_wedged=true (value 0.0, verified-TPU provenance riding
    in detail) and return without ever starting a CPU fallback run — the
    BENCH_r05 failure mode (rc=124, parsed null) must stay dead."""
    import json

    bench._probe_tpu = lambda timeout_s=180: "wedged"
    monkeypatch.setattr(
        bench, "_run_candidate",
        lambda *a, **k: pytest.fail("wedged exit must not run any candidate"),
    )
    bench.main()
    json_lines = [ln for ln in capsys.readouterr().out.splitlines() if ln.startswith("{")]
    assert len(json_lines) == 1
    out = json.loads(json_lines[0])
    assert out["probe_wedged"] is True
    assert out["value"] == 0.0 and out["vs_baseline"] == 0.0
    assert out["detail"]["last_verified_tpu"]["mfu"] == pytest.approx(0.6882)


def test_provisional_json_emitted_before_nonzero_retry_sleep(bench, monkeypatch, capsys):
    """A wedged probe about to sleep a retry rung must FIRST leave a parsed
    provisional line on stdout: a driver kill mid-sleep then still parses the
    last JSON line instead of scoring null. Emitted once, before the sleep."""
    import json

    monkeypatch.setenv("BENCH_PROBE_LADDER", "0,7,7")
    naps = []
    monkeypatch.setattr(bench.time, "sleep", naps.append)
    bench._probe_tpu = lambda timeout_s=180: "wedged"
    assert bench._probe_tpu_ladder() is False
    assert naps == [7, 7]
    json_lines = [ln for ln in capsys.readouterr().out.splitlines() if ln.startswith("{")]
    assert len(json_lines) == 1  # once, not once per rung
    out = json.loads(json_lines[0])
    assert out["provisional"] is True and out["probe_wedged"] is True
    assert out["value"] == 0.0
    assert out["detail"]["last_verified_tpu"]["mfu"] == pytest.approx(0.6882)


def test_zero_sleep_ladder_emits_no_provisional_line(bench, capsys):
    """The exactly-one-JSON-line contract of the smoke/wedged paths: a ladder
    with no retry sleeps (the test default "0,0,0") never needs — and never
    gets — a provisional line."""
    bench._probe_tpu = lambda timeout_s=180: "wedged"
    assert bench._probe_tpu_ladder() is False
    assert [ln for ln in capsys.readouterr().out.splitlines() if ln.startswith("{")] == []


# --------------------------------------------------- total wall-time budget


def test_budget_guard_emits_fallback_line_and_exits_when_budget_expires(bench, monkeypatch, capsys):
    import json
    import time as _time

    monkeypatch.setenv("BENCH_TOTAL_BUDGET_S", "0.05")
    exits = []
    thread = bench._arm_total_budget_guard(exit_fn=exits.append)
    deadline = _time.monotonic() + 10.0
    while not exits and _time.monotonic() < deadline:
        _time.sleep(0.01)
    thread.join(timeout=10.0)
    assert exits == [0]  # exit 0: a parsed line beats an rc=124 kill
    json_lines = [ln for ln in capsys.readouterr().out.splitlines() if ln.startswith("{")]
    assert len(json_lines) == 1
    out = json.loads(json_lines[0])
    assert out["budget_exhausted"] is True and out["value"] == 0.0
    assert out["detail"]["last_verified_tpu"]["mfu"] == pytest.approx(0.6882)


def test_budget_guard_stands_down_once_the_result_is_out(bench, monkeypatch, capsys):
    monkeypatch.setenv("BENCH_TOTAL_BUDGET_S", "0.2")
    thread = bench._arm_total_budget_guard(exit_fn=lambda code: pytest.fail("guard must not fire"))
    bench._BENCH_DONE.set()  # what main() does right after printing the result
    thread.join(timeout=10.0)
    assert not thread.is_alive()
    assert [ln for ln in capsys.readouterr().out.splitlines() if ln.startswith("{")] == []


def test_budget_deadline_is_pinned_across_reexec(bench, monkeypatch):
    """_reexec_on_cpu's child must inherit the ORIGINAL absolute deadline via
    BENCH_DEADLINE_TS, not grant itself a fresh BENCH_TOTAL_BUDGET_S."""
    import os

    monkeypatch.setenv("BENCH_TOTAL_BUDGET_S", "3300")
    bench._arm_total_budget_guard(exit_fn=lambda code: None)
    pinned = os.environ["BENCH_DEADLINE_TS"]
    assert float(pinned) > 0
    bench._BENCH_DONE.set()
    # a second arming (= the re-exec'd child) reuses the pinned deadline verbatim
    bench._arm_total_budget_guard(exit_fn=lambda code: None)
    assert os.environ["BENCH_DEADLINE_TS"] == pinned


def test_budget_guard_disabled_with_zero_budget(bench, monkeypatch):
    monkeypatch.setenv("BENCH_TOTAL_BUDGET_S", "0")
    assert bench._arm_total_budget_guard(exit_fn=lambda code: None) is None


@pytest.mark.slow  # subprocess jax import dominates; the guard logic is unit-tested above
def test_bench_subprocess_respects_total_budget_end_to_end():
    """The whole bench under a tiny wall-time budget, as the driver would run a
    pathologically slow window: must exit 0 WELL before the CPU run could finish,
    with exactly one parseable JSON line flagged budget_exhausted."""
    import json
    import os
    import subprocess
    import sys
    import time

    env = {**os.environ, "JAX_PLATFORMS": "cpu", "BENCH_TPU_PROBE": "0",
           "BENCH_TOTAL_BUDGET_S": "3"}
    env.pop("BENCH_DEADLINE_TS", None)
    t0 = time.monotonic()
    proc = subprocess.run(
        [sys.executable, str(Path(__file__).parents[1] / "bench.py")],
        capture_output=True, text=True, timeout=120, env=env,
    )
    elapsed = time.monotonic() - t0
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert elapsed < 60, elapsed  # the guard fired, not the full CPU bench
    json_lines = [ln for ln in proc.stdout.splitlines() if ln.startswith("{")]
    assert len(json_lines) == 1, proc.stdout
    out = json.loads(json_lines[0])
    assert out["budget_exhausted"] is True
    assert out["detail"]["last_verified_tpu"]["mfu"] == pytest.approx(0.6882)


def test_transient_wedge_that_clears_does_not_mark_wedged(bench):
    """A wedge that clears on a later rung is a healthy TPU: the wedged flag must
    NOT stick from the early rungs."""
    calls = []

    def probe(timeout_s=180):
        calls.append(1)
        return "tpu" if len(calls) >= 2 else "wedged"

    bench._probe_tpu = probe
    assert bench._probe_tpu_ladder() is True
    assert bench._PROBE_WEDGED is False


def test_clean_no_tpu_exhaustion_is_not_wedged(bench):
    """'No TPU on this host' exhaustion must fall through to the CPU run (the
    laptop/CI path), not the wedged short-circuit."""
    bench._probe_tpu = lambda timeout_s=180: "no_tpu"
    assert bench._probe_tpu_ladder() is False
    assert bench._PROBE_WEDGED is False
