"""SLO engine (telemetry/slo.py): objective grammar, fake-clock multi-window
burn rates (fast-window trip, slow-window hysteresis/recovery, budget
exhaustion), engine gauges + events, spec loading, recorded-run replay, and
the `data check_slo` exit-code pins."""

import json

import pytest
from click.testing import CliRunner

from modalities_tpu.__main__ import main as cli_main
from modalities_tpu.resilience.events import counts_since, snapshot_counts
from modalities_tpu.telemetry.metrics import MetricsRegistry
from modalities_tpu.telemetry.slo import (
    BurnRateEvaluator,
    SLOEngine,
    evaluate_objective,
    evaluate_recorded,
    load_slo_spec,
    parse_objective,
    replay_sink_into_registry,
    tenant_objectives,
)

# ---------------------------------------------------------------- grammar


def test_parse_quantile_ratio_and_value_expressions():
    q = parse_objective("ttft", "serve_ttft_seconds p99 < 0.5")
    assert (q.kind, q.metric, q.quantile, q.op, q.threshold) == (
        "quantile", "serve_ttft_seconds", 0.99, "<", 0.5,
    )
    r = parse_objective("err", "serve_request_errors_total / serve_requests_total <= 0.01")
    assert (r.kind, r.metric, r.denominator, r.op) == (
        "ratio", "serve_request_errors_total", "serve_requests_total", "<=",
    )
    v = parse_objective("goodput", "training_goodput_ratio >= 0.85")
    assert (v.kind, v.metric, v.op, v.threshold) == (
        "value", "training_goodput_ratio", ">=", 0.85,
    )
    # whitespace is normalized into the canonical expr string
    assert parse_objective("x", "  a_metric   <   1  ").expr == "a_metric < 1"


def test_parse_rejects_garbage_and_out_of_range_quantiles():
    with pytest.raises(ValueError, match="cannot parse"):
        parse_objective("bad", "serve_ttft_seconds is fast")
    with pytest.raises(ValueError, match="cannot parse"):
        parse_objective("bad", "a == 1")  # == is not an op
    with pytest.raises(ValueError, match="outside"):
        parse_objective("bad", "serve_ttft_seconds p100 < 0.5")
    with pytest.raises(ValueError, match="outside"):
        parse_objective("bad", "serve_ttft_seconds p0 < 0.5")


def test_label_selectors_judge_one_series_and_tenant_objectives():
    """PR-20 grammar: a `{tenant="x"}` selector on any objective form judges
    exactly that labeled series, and `tenant_objectives` auto-derives one
    shed-rate objective per declared tenant riding the same grammar."""
    q = parse_objective("t", 'serve_ttft_seconds{tenant="acme"} p95 < 0.5')
    assert (q.kind, q.labels) == ("quantile", {"tenant": "acme"})
    v = parse_objective("slots", 'serve_tenant_active_slots{tenant="acme"} <= 4')
    assert (v.kind, v.labels) == ("value", {"tenant": "acme"})
    r = parse_objective(
        "shed",
        'serve_tenant_shed_total{tenant="bulk", reason="brownout"} / '
        'serve_tenant_requests_total{tenant="bulk"} <= 0.05',
    )
    assert r.labels == {"tenant": "bulk", "reason": "brownout"}
    assert r.den_labels == {"tenant": "bulk"}

    reg = MetricsRegistry()
    shed = reg.counter("serve_tenant_shed_total", "")
    reqs = reg.counter("serve_tenant_requests_total", "")
    for _ in range(10):
        reqs.inc(tenant="bulk")
        reqs.inc(tenant="acme")
    for _ in range(6):
        shed.inc(tenant="bulk")

    objs = tenant_objectives(["bulk", "acme"], threshold=0.05)
    assert [o.name for o in objs] == [
        "tenant_bulk_error_rate", "tenant_acme_error_rate",
    ]
    by_name = {o.name: o for o in objs}
    # the flooded tenant breaches ITS objective (6/10 shed), while the quiet
    # tenant's own series stays green — the whole point of the selector:
    # one tenant's burn never judges another's
    ok, value = evaluate_objective(by_name["tenant_bulk_error_rate"], reg)
    assert ok is False and value == pytest.approx(0.6)
    ok, value = evaluate_objective(by_name["tenant_acme_error_rate"], reg)
    assert ok is True and value == 0.0


def test_load_slo_spec_from_mapping_and_yaml(tmp_path):
    spec = {
        "sample_interval_s": 2.5,
        "objectives": [
            {"name": "ttft", "expr": "serve_ttft_seconds p99 < 0.5", "budget": 0.05},
            {"name": "goodput", "expr": "training_goodput_ratio >= 0.85"},
        ],
    }
    objectives, options = load_slo_spec(spec)
    assert [o.name for o in objectives] == ["ttft", "goodput"]
    assert objectives[0].budget == 0.05
    assert options == {"sample_interval_s": 2.5}

    path = tmp_path / "slo.yaml"
    path.write_text(
        "objectives:\n  - name: ttft\n    expr: 'serve_ttft_seconds p99 < 0.5'\n"
    )
    objectives, options = load_slo_spec(path)
    assert objectives[0].quantile == 0.99 and options == {}

    with pytest.raises(ValueError, match="needs an 'objectives'"):
        load_slo_spec({"objective": []})
    with pytest.raises(ValueError, match="unknown keys"):
        load_slo_spec({"objectives": [
            {"name": "x", "expr": "a < 1", "thresold": 2},
        ]})


# ----------------------------------------------------------- live evaluation


def test_evaluate_objective_kinds_and_unjudgeable_cases():
    reg = MetricsRegistry()
    # absent metric: unjudgeable, never breaching
    assert evaluate_objective(parse_objective("x", "nope_seconds p99 < 1"), reg) == (None, None)

    hist = reg.histogram("serve_ttft_seconds", "")
    # histogram with no observations: unjudgeable (booting quiet != outage)
    assert evaluate_objective(parse_objective("x", "serve_ttft_seconds p99 < 1"), reg) == (None, None)
    for _ in range(50):
        hist.observe(0.01)
    ok, value = evaluate_objective(parse_objective("x", "serve_ttft_seconds p99 < 1"), reg)
    assert ok is True and 0 < value < 1

    num = reg.counter("errs_total", "")
    den = reg.counter("reqs_total", "")
    ratio = parse_objective("err", "errs_total / reqs_total < 0.5")
    # zero denominator: unjudgeable
    assert evaluate_objective(ratio, reg) == (None, None)
    den.inc(); den.inc(); num.inc()
    ok, value = evaluate_objective(ratio, reg)
    assert ok is False and value == 0.5  # 0.5 < 0.5 fails

    g = reg.gauge("training_goodput_ratio", "")
    g.set(0.9)
    ok, value = evaluate_objective(parse_objective("gp", "training_goodput_ratio >= 0.85"), reg)
    assert ok is True and value == 0.9


# -------------------------------------------------- burn-rate state machine


def _fake_clock():
    t = {"now": 0.0}
    return t, (lambda: t["now"])


def test_fast_window_trips_the_breach():
    """Defaults: budget 1%, fast burn 14x/60 s, slow burn 2x/600 s. A long
    healthy history keeps the slow window quiet; a burst of bad samples in the
    last minute trips the FAST window alone — minutes-scale detection without
    waiting for the slow window to notice."""
    t, clock = _fake_clock()
    ev = BurnRateEvaluator(parse_objective("x", "m < 1"), time_fn=clock)
    for _ in range(540):  # 9 minutes of health at one sample/s
        t["now"] += 1.0
        assert ev.observe(True, 0.5) is None
    transitions = []
    for _ in range(9):  # a one-minute burst of bad samples
        t["now"] += 1.0
        transitions.append(ev.observe(False, 2.0))
    assert transitions[-1] == "breach" and transitions[:-1].count("breach") == 0
    # the verdict came from the fast window: slow is still under its 2x gate
    assert ev.fast_burn_rate >= 14.0
    assert ev.slow_burn_rate < 2.0
    assert ev.breaching


def test_recovery_requires_the_slow_window_to_drain():
    """Hysteresis: once breached, a clean fast window is NOT enough — the
    breach holds until the slow window's burn drops too, then recovers."""
    t, clock = _fake_clock()
    ev = BurnRateEvaluator(parse_objective("x", "m < 1"), time_fn=clock)
    for _ in range(3):
        t["now"] += 1.0
        ev.observe(False, 2.0)
    assert ev.breaching
    # 90 s of good samples: the bad ones age out of the 60 s fast window...
    for _ in range(9):
        t["now"] += 10.0
        assert ev.observe(True, 0.5) is None
    assert ev.fast_burn_rate == 0.0
    # ...but the 600 s slow window still remembers them: 3/12 = 25% bad
    # >> 2 * 1% budget, so the breach holds
    assert ev.breaching and ev.slow_burn_rate > 2.0
    # jump past the slow horizon: everything drains, recovery fires
    t["now"] += 700.0
    assert ev.observe(True, 0.5) == "recovered"
    assert not ev.breaching


def test_budget_exhaustion_and_refill():
    t, clock = _fake_clock()
    ev = BurnRateEvaluator(parse_objective("x", "m < 1"), time_fn=clock)
    assert ev.budget_remaining() == 1.0  # untouched before any sample
    for _ in range(5):
        t["now"] += 1.0
        ev.observe(False, 2.0)
    assert ev.budget_remaining() == 0.0  # slow burn 100x: fully exhausted
    t["now"] += 700.0  # bad samples age out of the slow window
    ev.observe(True, 0.5)
    assert ev.budget_remaining() == 1.0


def test_unjudgeable_samples_never_breach():
    t, clock = _fake_clock()
    ev = BurnRateEvaluator(parse_objective("x", "m < 1"), time_fn=clock)
    for _ in range(100):
        t["now"] += 1.0
        assert ev.observe(None) is None
    assert not ev.breaching and ev.budget_remaining() == 1.0


# ------------------------------------------------------------------ engine


def test_engine_updates_gauges_and_emits_transition_events():
    t, clock = _fake_clock()
    reg = MetricsRegistry()
    gauge = reg.gauge("training_goodput_ratio", "")
    gauge.set(0.9)
    engine = SLOEngine(
        [parse_objective("goodput", "training_goodput_ratio >= 0.85")],
        reg, sample_interval_s=1.0, time_fn=clock,
    )
    t["now"] += 1.0
    engine.sample_once()
    assert engine.breaching() == []
    assert reg.get("slo_status").value(objective="goodput") == 1.0
    assert reg.get("slo_error_budget_remaining").value(objective="goodput") == 1.0

    snapshot = snapshot_counts()
    gauge.set(0.5)
    t["now"] += 1.0
    engine.sample_once()
    assert engine.breaching() == ["goodput"]
    assert reg.get("slo_status").value(objective="goodput") == 0.0
    assert reg.get("slo_breaches_total").value(objective="goodput") == 1.0
    assert counts_since(snapshot).get("slo") == 1  # the slo/breach event

    # recovery: good samples until both windows drain
    snapshot = snapshot_counts()
    gauge.set(0.9)
    t["now"] += 700.0
    engine.sample_once()
    assert engine.breaching() == []
    assert reg.get("slo_status").value(objective="goodput") == 1.0
    assert counts_since(snapshot).get("slo") == 1  # the slo/recovered event
    assert engine.status()["goodput"]["last_value"] == 0.9


def test_engine_sampler_thread_start_stop():
    reg = MetricsRegistry()
    reg.gauge("training_goodput_ratio", "").set(0.9)
    engine = SLOEngine(
        [parse_objective("goodput", "training_goodput_ratio >= 0.85")],
        reg, sample_interval_s=0.01,
    )
    assert engine.start() is engine
    import time as _time

    deadline = _time.monotonic() + 2.0
    while _time.monotonic() < deadline:
        if reg.get("slo_status").value(objective="goodput") == 1.0:
            break
        _time.sleep(0.01)
    engine.stop()
    assert reg.get("slo_status").value(objective="goodput") == 1.0
    assert engine._thread is None  # stop() reaps the sampler


def test_engine_interval_from_env(monkeypatch):
    monkeypatch.setenv("MODALITIES_TPU_SLO_SAMPLE_S", "7.5")
    engine = SLOEngine([], MetricsRegistry())
    assert engine.sample_interval_s == 7.5
    engine2 = SLOEngine([], MetricsRegistry(), sample_interval_s=1.0)
    assert engine2.sample_interval_s == 1.0  # explicit wins over env


# ------------------------------------------------------- recorded-run replay


def _write_serve_sink(folder, ttft_s, n=20, errors=0):
    folder.mkdir(parents=True, exist_ok=True)
    rows = []
    for i in range(n):
        rows.append({
            "event": "serve_request", "ttft_s": ttft_s, "latency_s": ttft_s + 0.05,
            "finish_reason": "error" if i < errors else "eod",
        })
    rows.append({
        "event": "span", "name": "train_step", "ts": 0.0, "dur_s": 8.0,
        "self_s": 8.0, "thread": "MainThread", "timeline": True,
    })
    rows.append({
        "event": "mfu_waterfall", "peak": 1.0, "achieved": 0.4, "gap": 0.6,
        "deductions": {"kernel_inefficiency": 0.6},
    })
    (folder / "telemetry_rank_0.jsonl").write_text(
        "\n".join(json.dumps(r) for r in rows) + "\n"
    )
    return folder


def test_replay_sink_rebuilds_judgeable_series(tmp_path):
    sink = _write_serve_sink(tmp_path / "sink", ttft_s=0.02, errors=2)
    reg = MetricsRegistry()
    replayed = replay_sink_into_registry(sink, reg)
    assert replayed == 22  # 20 serve_request + 1 waterfall + 1 goodput ratio
    assert reg.get("serve_requests_total").value() == 20.0
    assert reg.get("serve_request_errors_total").value() == 2.0
    assert reg.get("serve_ttft_seconds").count() == 20
    assert reg.get("training_mfu_achieved").value() == 0.4
    assert reg.get("training_goodput_ratio").value() == 1.0  # all-train_step sink


def test_evaluate_recorded_splits_ok_breaching_skipped(tmp_path):
    sink = _write_serve_sink(tmp_path / "sink", ttft_s=2.0)
    reg = MetricsRegistry()
    replay_sink_into_registry(sink, reg)
    objectives, _ = load_slo_spec({"objectives": [
        {"name": "ttft", "expr": "serve_ttft_seconds p99 < 0.5"},
        {"name": "errs", "expr": "serve_request_errors_total / serve_requests_total < 0.01"},
        {"name": "mystery", "expr": "not_a_metric >= 1"},
    ]})
    report = evaluate_recorded(objectives, reg)
    assert report["breaching"] == ["ttft"]
    assert report["ok"] == ["errs"]
    assert report["skipped"] == ["mystery"]
    assert report["values"]["ttft"] > 0.5


# --------------------------------------------------------- check_slo CLI pins


def _spec_file(tmp_path):
    path = tmp_path / "slo.yaml"
    path.write_text(
        "objectives:\n"
        "  - name: ttft_p99\n"
        "    expr: 'serve_ttft_seconds p99 < 0.5'\n"
        "  - name: error_rate\n"
        "    expr: 'serve_request_errors_total / serve_requests_total < 0.01'\n"
    )
    return path


def test_check_slo_exits_zero_on_a_healthy_recording(tmp_path):
    sink = _write_serve_sink(tmp_path / "healthy", ttft_s=0.01)
    result = CliRunner().invoke(cli_main, [
        "data", "check_slo", "--slo_path", str(_spec_file(tmp_path)),
        "--sink_path", str(sink),
    ])
    assert result.exit_code == 0, result.output
    assert "all ok" in result.output


def test_check_slo_exits_nonzero_on_a_poisoned_recording(tmp_path):
    sink = _write_serve_sink(tmp_path / "poisoned", ttft_s=2.0)
    result = CliRunner().invoke(cli_main, [
        "data", "check_slo", "--slo_path", str(_spec_file(tmp_path)),
        "--sink_path", str(sink),
    ])
    assert result.exit_code != 0
    assert "BREACH" in result.output and "ttft_p99" in result.output


def test_check_slo_as_json_reports_skipped_objectives(tmp_path):
    sink = _write_serve_sink(tmp_path / "healthy", ttft_s=0.01)
    spec = tmp_path / "slo.yaml"
    spec.write_text(
        "objectives:\n  - name: ghost\n    expr: 'never_observed_seconds p99 < 1'\n"
    )
    result = CliRunner().invoke(cli_main, [
        "data", "check_slo", "--slo_path", str(spec),
        "--sink_path", str(sink), "--as_json",
    ])
    assert result.exit_code == 0, result.output  # skipped never fails the gate
    report = json.loads(result.output)
    assert report["skipped"] == ["ghost"] and report["breaching"] == []
    assert report["records_replayed"] > 0
