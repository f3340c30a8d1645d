"""Metrics registry contracts (telemetry/metrics.py): histogram bucketing and
quantile estimation, Prometheus text-exposition rendering + round-trip parsing,
get-or-create registration, and concurrent-update safety."""

import math
import threading

import pytest

from modalities_tpu.telemetry.metrics import (
    LATENCY_BUCKETS,
    MetricsRegistry,
    log_buckets,
    parse_prometheus_text,
)


# ----------------------------------------------------------------- buckets


def test_log_buckets_spacing_and_validation():
    bounds = log_buckets(0.001, 2.0, 4)
    assert bounds == (0.001, 0.002, 0.004, 0.008)
    for bad in [(0, 2.0, 4), (0.1, 1.0, 4), (0.1, 2.0, 0)]:
        with pytest.raises(ValueError):
            log_buckets(*bad)
    assert len(LATENCY_BUCKETS) == 24
    assert LATENCY_BUCKETS[0] == pytest.approx(0.0005)


def test_histogram_bucketing_sum_count_and_inf_bucket():
    reg = MetricsRegistry()
    h = reg.histogram("lat_seconds", "latency", buckets=(0.1, 1.0, 10.0))
    for v in (0.05, 0.5, 0.5, 5.0, 50.0):  # last one lands in +Inf
        h.observe(v)
    assert h.count() == 5
    assert h.sum() == pytest.approx(56.05)
    parsed = parse_prometheus_text(reg.render())
    buckets = parsed["lat_seconds_bucket"]
    assert buckets[(("le", "0.1"),)] == 1  # cumulative
    assert buckets[(("le", "1"),)] == 3
    assert buckets[(("le", "10"),)] == 4
    assert buckets[(("le", "+Inf"),)] == 5
    assert parsed["lat_seconds_sum"][()] == pytest.approx(56.05)
    assert parsed["lat_seconds_count"][()] == 5


def test_histogram_rejects_non_increasing_bounds():
    reg = MetricsRegistry()
    with pytest.raises(ValueError, match="strictly increasing"):
        reg.histogram("h", buckets=(1.0, 1.0, 2.0))


def test_histogram_quantile_interpolates():
    reg = MetricsRegistry()
    h = reg.histogram("q_seconds", buckets=(1.0, 2.0, 4.0))
    for v in [0.5] * 50 + [1.5] * 50:  # median at the bucket seam
        h.observe(v)
    direct = h.quantile(0.5)
    assert 0.9 <= direct <= 1.1  # linear interpolation near the seam
    assert h.quantile(1.0) <= 2.0
    assert reg.histogram("empty_seconds").quantile(0.5) is None


def test_histogram_inf_tail_clamps_to_largest_finite_bound():
    reg = MetricsRegistry()
    h = reg.histogram("tail_seconds", buckets=(1.0, 2.0))
    h.observe(100.0)
    assert h.quantile(0.99) == 2.0


# ------------------------------------------------------- counters and gauges


def test_counter_labels_monotonic_and_negative_rejected():
    reg = MetricsRegistry()
    c = reg.counter("reqs_total", "requests")
    c.inc()
    c.inc(2, reason="eod")
    c.inc(reason="budget")
    assert c.value() == 1
    assert c.value(reason="eod") == 2
    with pytest.raises(ValueError, match="cannot decrease"):
        c.inc(-1)
    parsed = parse_prometheus_text(reg.render())
    assert parsed["reqs_total"][(("reason", "eod"),)] == 2


def test_gauge_set_inc_and_scrape_time_callback():
    reg = MetricsRegistry()
    g = reg.gauge("depth", "queue depth")
    g.set(3)
    g.inc(2)
    assert g.value() == 5
    live = {"v": 7.0}
    g2 = reg.gauge("live")
    g2.set_fn(lambda: live["v"])
    assert g2.value() == 7.0
    live["v"] = 9.0
    parsed = parse_prometheus_text(reg.render())
    assert parsed["live"][()] == 9.0  # callback evaluated at render time


# ------------------------------------------------------------- registration


def test_get_or_create_returns_same_metric_and_rejects_kind_mismatch():
    reg = MetricsRegistry()
    c1 = reg.counter("x_total")
    assert reg.counter("x_total") is c1
    with pytest.raises(ValueError, match="already registered"):
        reg.gauge("x_total")
    with pytest.raises(ValueError, match="invalid metric name"):
        reg.counter("bad name")
    assert reg.names() == ["x_total"]


# ---------------------------------------------------------------- rendering


def test_render_is_valid_exposition_with_help_type_and_escaping():
    reg = MetricsRegistry()
    reg.counter("a_total", 'has "quotes"\nand newline').inc(reason='say "hi"\n')
    text = reg.render()
    assert '# HELP a_total has \\"quotes\\"\\nand newline' in text
    assert "# TYPE a_total counter" in text
    parsed = parse_prometheus_text(text)
    assert parsed["a_total"][(("reason", 'say "hi"\n'),)] == 1  # unescapes back


def test_parse_rejects_malformed_sample_line():
    with pytest.raises(ValueError, match="malformed"):
        parse_prometheus_text("ok_total 1\nbro{ken 2\n")


def test_unobserved_metrics_still_render_a_zero_sample():
    reg = MetricsRegistry()
    reg.counter("never_total")
    reg.histogram("never_seconds", buckets=(1.0,))
    parsed = parse_prometheus_text(reg.render())
    assert parsed["never_total"][()] == 0
    assert parsed["never_seconds_count"][()] == 0
    assert parsed["never_seconds_bucket"][(("le", "+Inf"),)] == 0


# --------------------------------------------------------------- concurrency


def test_concurrent_updates_lose_nothing():
    reg = MetricsRegistry()
    c = reg.counter("conc_total")
    h = reg.histogram("conc_seconds", buckets=(0.5, 1.5))
    n_threads, per_thread = 8, 500

    def work(i):
        for k in range(per_thread):
            c.inc(reason=str(i % 2))
            h.observe(1.0 if k % 2 else 0.25)

    threads = [threading.Thread(target=work, args=(i,)) for i in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60.0)
        assert not t.is_alive(), "a counting thread never finished"
    total = n_threads * per_thread
    assert c.value(reason="0") + c.value(reason="1") == total
    assert h.count() == total
    parsed = parse_prometheus_text(reg.render())
    assert parsed["conc_seconds_bucket"][(("le", "+Inf"),)] == total
    assert not math.isnan(parsed["conc_seconds_sum"][()])


# --------------------------------------------- PR 13: exemplars + identity


def test_histogram_exemplar_stored_rendered_and_parse_safe():
    reg = MetricsRegistry()
    h = reg.histogram("ex_seconds", buckets=(0.5, 1.5))
    h.observe(0.25)  # no exemplar
    assert h.exemplar() is None
    h.observe(1.0, exemplar="abc123def4567890")
    assert h.exemplar() == ("abc123def4567890", 1.0)
    h.observe(0.75, exemplar="fedcba9876543210")  # last one wins
    assert h.exemplar() == ("fedcba9876543210", 0.75)

    text = reg.render()
    assert "# EXEMPLAR ex_seconds" in text and "fedcba9876543210" in text
    # the comment line never breaks the exposition parser or the samples
    parsed = parse_prometheus_text(text)
    assert parsed["ex_seconds_count"][()] == 3.0

    h.reset()
    assert h.exemplar() is None  # reset drops exemplars with the series


def test_histogram_exemplar_is_per_label_set():
    reg = MetricsRegistry()
    h = reg.histogram("exl_seconds", buckets=(1.0,))
    h.observe(0.5, exemplar="trace-a", worker="w0")
    h.observe(0.7, exemplar="trace-b", worker="w1")
    assert h.exemplar(worker="w0") == ("trace-a", 0.5)
    assert h.exemplar(worker="w1") == ("trace-b", 0.7)
    assert h.exemplar(worker="w2") is None


def test_register_process_metrics_build_info_and_gauges():
    from modalities_tpu.telemetry.metrics import register_process_metrics

    reg = MetricsRegistry()
    register_process_metrics(reg, version="0.1.0", config_hash="cafe01234567")
    register_process_metrics(reg, version="0.1.0", config_hash="cafe01234567")  # idempotent

    parsed = parse_prometheus_text(reg.render())
    key = (("config_hash", "cafe01234567"), ("version", "0.1.0"))
    assert parsed["modalities_tpu_build_info"][key] == 1.0
    assert parsed["process_uptime_seconds"][()] >= 0.0
    # RSS of a live python process with jax imported is comfortably > 10 MiB
    assert parsed["process_resident_memory_bytes"][()] > 10 * 1024 * 1024
    # unset labels fall back to "unknown", never empty strings
    reg2 = MetricsRegistry()
    register_process_metrics(reg2)
    parsed2 = parse_prometheus_text(reg2.render())
    assert (("config_hash", "unknown"), ("version", "unknown")) in parsed2[
        "modalities_tpu_build_info"
    ]


def test_config_hash_of_is_stable_and_tolerant(tmp_path):
    from modalities_tpu.telemetry.metrics import config_hash_of

    cfg = tmp_path / "c.yaml"
    cfg.write_text("a: 1\n")
    h1 = config_hash_of(cfg)
    assert len(h1) == 12 and h1 == config_hash_of(cfg)
    cfg.write_text("a: 2\n")
    assert config_hash_of(cfg) != h1
    assert config_hash_of(tmp_path / "missing.yaml") == "unknown"


def test_registry_snapshot_covers_all_kinds_and_survives_broken_callbacks():
    reg = MetricsRegistry()
    reg.counter("snap_total", "c").inc(reason="x")
    reg.gauge("snap_gauge", "g").set(7.0)
    reg.histogram("snap_seconds", buckets=(1.0,)).observe(0.5)
    reg.gauge("snap_broken", "b").set_fn(lambda: 1 / 0)

    snap = reg.snapshot()
    assert snap["snap_total"]["series"]['{reason="x"}'] == 1.0
    assert snap["snap_gauge"]["series"]["{}"] == 7.0
    assert snap["snap_seconds"]["series"]["{}"] == {"sum": 0.5, "count": 1}
    assert "error" in snap["snap_broken"]  # broken callback never sinks the dump
    import json

    json.dumps(snap)  # the whole snapshot is JSON-safe (watchdog embeds it)
