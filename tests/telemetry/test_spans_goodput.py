"""Span recorder + goodput ledger + sink: the span stream must partition the
timeline thread's wall time (exclusive-time accounting), classify into buckets
summing to wall time, and leave a parseable always-flushed JSONL record."""

import json
import re
import threading
import time
from pathlib import Path

import pytest

import modalities_tpu
from modalities_tpu.telemetry import NOOP_TELEMETRY, Telemetry, get_active_telemetry, set_active_telemetry, span
from modalities_tpu.telemetry.goodput import _NAME_TO_BUCKET, BUCKETS, GoodputLedger, bucket_of, summarize_sink
from modalities_tpu.telemetry.spans import NULL_CONTEXT, PROCESS_LOG, SpanLog, SpanRecord, SpanRecorder


def wired_span_names() -> dict[str, str]:
    """{first path segment of a span's name: a file that opens it}, from the program's
    source: every string literal inside a `with ... span(...)` (the free function or a method)."""
    found: dict[str, str] = {}
    package = Path(modalities_tpu.__file__).parent
    for path in sorted(package.rglob("*.py")):
        for call in re.findall(r"with [^\n]*?(?<![\w])span\(([^\n]*)", path.read_text()):
            for literal in re.findall(r'f?"([^"]+)"', call):
                name = literal.split("/", 1)[0]
                if re.fullmatch(r"[a-z_]+", name):
                    found.setdefault(name, str(path.relative_to(package)))
    return found


WIRED = wired_span_names()
# the autotune sweep's `tune/<kernel>/<candidate>` spans go to a recorder of the caller's and are no phase of a run
NO_BUCKET_OF_THEIR_OWN = {"tune"}


def test_nested_spans_report_exclusive_time():
    records = []
    recorder = SpanRecorder(on_record=records.append, use_jax_annotations=False)
    with recorder.span("outer"):
        time.sleep(0.02)
        with recorder.span("inner"):
            time.sleep(0.03)
    by_name = {r.name: r for r in records}
    assert by_name["inner"].self_s == pytest.approx(by_name["inner"].dur_s)
    # outer's exclusive time excludes inner entirely
    assert by_name["outer"].self_s == pytest.approx(by_name["outer"].dur_s - by_name["inner"].dur_s)
    assert by_name["outer"].self_s >= 0.015
    assert by_name["outer"].timeline and by_name["inner"].timeline


def test_background_thread_spans_are_not_timeline():
    records = []
    recorder = SpanRecorder(on_record=records.append, use_jax_annotations=False)

    def work():
        with recorder.span("bg"):
            pass

    t = threading.Thread(target=work, name="bg-thread")
    t.start()
    t.join(timeout=30.0)
    assert not t.is_alive(), "the background span's thread never finished"
    (record,) = records
    assert record.thread == "bg-thread" and not record.timeline
    # and the ledger ignores it: overlapped background work must not double-count
    ledger = GoodputLedger()
    ledger.add_record(record)
    assert sum(ledger.bucket_seconds().values()) == 0.0


def test_span_survives_exception_and_still_records():
    records = []
    recorder = SpanRecorder(on_record=records.append, use_jax_annotations=False)
    with pytest.raises(RuntimeError):
        with recorder.span("doomed"):
            raise RuntimeError("boom")
    assert records and records[0].name == "doomed"
    # the per-thread stack unwound: a following span nests at top level again
    with recorder.span("after"):
        pass
    assert records[-1].name == "after" and records[-1].self_s == pytest.approx(records[-1].dur_s)


EXPECTED_BUCKET = {
    "backend_start": "init", "build_components": "init", "init": "init", "state_init": "init",
    "checkpoint_restore": "init", "preflight_memscope": "compile_first_step", "collective_plan": "compile_first_step",
    "first_step": "compile_first_step",
    "train_step": "train_step", "metrics_fetch": "train_step",  # device wait = goodput
    "data_wait": "data_stall", "eval": "eval", "checkpoint_save": "checkpoint", "checkpoint_drain": "checkpoint",
    "publish": "publish", "preempt": "recovery", "ckpt_retry": "recovery", "serve": "serve", "tune": "other",
}


@pytest.mark.parametrize("name", sorted(WIRED))
def test_bucket_mapping_covers_all_wired_span_names(name):
    """One case a span name some call site of the program opens: it has the bucket
    this table says, and (but for the tuner's) one of its own in the ledger's map."""
    assert name in EXPECTED_BUCKET, f"{WIRED[name]} opens a span {name!r} this test has no bucket for"
    assert bucket_of(name) == bucket_of(f"{name}/anything") == EXPECTED_BUCKET[name]  # namespaced: first segment decides
    assert (name in _NAME_TO_BUCKET) == (name not in NO_BUCKET_OF_THEIR_OWN)


def test_the_ledger_maps_no_name_that_nothing_opens():
    assert set(_NAME_TO_BUCKET) == set(WIRED) - NO_BUCKET_OF_THEIR_OWN
    assert {"backend_start", "build_components", "state_init", "preflight_memscope", "first_step"} <= set(WIRED)
    assert bucket_of("no_such_span") == "other" and bucket_of("heartbeat") == "other"


def test_ledger_summary_folds_untracked_into_other_and_sums_to_wall():
    ledger = GoodputLedger()
    ledger.add_seconds("train_step", 6.0)
    ledger.add_seconds("data_stall", 1.0)
    summary = ledger.summary(wall_s=10.0)
    assert summary["buckets"]["other"] == pytest.approx(3.0)
    assert sum(summary["buckets"].values()) == pytest.approx(10.0)
    assert summary["goodput_pct"] == pytest.approx(60.0)
    assert set(summary["buckets"]) == set(BUCKETS)


def test_telemetry_sink_jsonl_schema_and_rank0_summary(tmp_path):
    telemetry = Telemetry(output_folder_path=tmp_path, watchdog_deadline_s=0)
    with telemetry.span("train_step"):
        time.sleep(0.01)
    telemetry.close()
    lines = [json.loads(ln) for ln in telemetry.sink_path.read_text().splitlines()]
    span_events = [e for e in lines if e["event"] == "span"]
    assert span_events and span_events[0]["name"] == "train_step"
    for key in ("rank", "ts", "dur_s", "self_s", "thread", "timeline"):
        assert key in span_events[0]
    assert lines[-1]["event"] == "run_summary" and "goodput_pct" in lines[-1]
    assert (tmp_path / "goodput_summary.json").is_file()
    # offline aggregation replays the sink into the same bucket schema
    summary = summarize_sink(tmp_path)
    assert summary["ranks"][0]["buckets"]["train_step"] >= 0.009


def test_disabled_telemetry_is_noop_and_allocation_free(tmp_path):
    telemetry = Telemetry(enabled=False, output_folder_path=tmp_path)
    assert telemetry.span("x") is NULL_CONTEXT  # shared instance: no per-call alloc
    assert telemetry.step_annotation(3) is NULL_CONTEXT
    assert telemetry.throughput_metrics() == {}
    telemetry.arm_watchdog(1)
    telemetry.beat_watchdog(1)
    telemetry.close()
    assert list(tmp_path.iterdir()) == []  # no sink, no artifacts


def test_active_telemetry_routing_and_restore(tmp_path):
    telemetry = Telemetry(output_folder_path=tmp_path, watchdog_deadline_s=0)
    previous = set_active_telemetry(telemetry)
    try:
        assert previous is NOOP_TELEMETRY
        with span("checkpoint_save"):
            pass
    finally:
        restored = set_active_telemetry(previous)
    assert restored is telemetry
    with span("after_restore"):  # back to no instance: the process's own recorder takes it
        pass
    assert span("x") is not NULL_CONTEXT and PROCESS_LOG.records[-1].name == "after_restore"
    telemetry.close()
    events = [json.loads(ln) for ln in telemetry.sink_path.read_text().splitlines()]
    assert any(e.get("name") == "checkpoint_save" for e in events)
    assert not any(e.get("name") == "after_restore" for e in events)


def test_span_overhead_is_small():
    """The disabled path must be negligible and the enabled path cheap enough for
    a per-step call (<50us/span enabled is orders below any real step time)."""
    telemetry_off = Telemetry(enabled=False)
    n = 2000
    t0 = time.perf_counter()
    for _ in range(n):
        with telemetry_off.span("s"):
            pass
    off_per_span = (time.perf_counter() - t0) / n
    assert off_per_span < 5e-6
    telemetry_on = Telemetry(watchdog_deadline_s=0, use_jax_annotations=False)
    t0 = time.perf_counter()
    for _ in range(n):
        with telemetry_on.span("s"):
            pass
    on_per_span = (time.perf_counter() - t0) / n
    assert on_per_span < 5e-5


# ------------------------------------- PR 34: the process's own log of spans


def test_process_log_keeps_start_parent_and_step():
    telemetry = Telemetry(watchdog_deadline_s=0, use_jax_annotations=False)
    before = time.perf_counter()
    with telemetry.span("log_outer_a"):
        pass
    telemetry.arm_watchdog(7)
    with telemetry.span("log_outer_b"):
        with telemetry.span("log_inner_b"):
            time.sleep(0.005)
    telemetry.beat_watchdog(7)  # step 7 is done: what opens now belongs to step 8
    with telemetry.span("log_outer_c"):
        pass
    after = time.perf_counter()
    mine = {r.name: r for r in PROCESS_LOG.records if r.name.startswith("log_")}
    assert [mine[n].step for n in ("log_outer_a", "log_outer_b", "log_inner_b", "log_outer_c")] == [None, 7, 7, 8]
    assert [mine[n].parent for n in ("log_outer_a", "log_outer_b", "log_inner_b")] == [None, None, "log_outer_b"]
    inner, outer = mine["log_inner_b"], mine["log_outer_b"]
    assert PROCESS_LOG.origin <= before <= outer.t0 <= inner.t0 and inner.t0 + inner.dur_s <= outer.t0 + outer.dur_s <= after
    assert outer.ts == pytest.approx(time.time() - (time.perf_counter() - outer.t0), abs=0.05)  # both clocks name one instant
    assert all(r.timeline for r in mine.values())


def test_process_log_is_bounded_and_drops_the_oldest():
    log = SpanLog(origin=0.0, capacity=4)
    for i in range(10):
        log.add(SpanRecord(name=f"s{i}", ts=0.0, dur_s=1.0, self_s=1.0, thread="t", timeline=True, t0=float(i)), claimed=i % 2 == 0)
    assert [r.name for r in log.records] == ["s6", "s7", "s8", "s9"]
    since, unclaimed = log.claim()
    assert since == 0.0 and [r.name for r in unclaimed] == ["s3", "s5", "s7", "s9"]  # bounded too
    assert log.claim() == (0.0, []) and len(log.records) == 4
    assert PROCESS_LOG.records.maxlen >= 5 * (150 * 5 + 40)  # a window of 150 steps and a set-up, several times over


def test_process_log_takes_spans_with_no_instance_active_and_from_a_second_after_the_first_is_gone():
    assert get_active_telemetry() is NOOP_TELEMETRY
    with span("orphan_outer"):
        with span("orphan_inner"):
            pass
    first = Telemetry(watchdog_deadline_s=0, use_jax_annotations=False)
    with first.span("of_the_first"):
        pass
    first.close()
    del first
    second = Telemetry(watchdog_deadline_s=0, use_jax_annotations=False)
    with second.span("of_the_second"):
        with span("free_inside_the_second"):  # another recorder, one stack a thread
            pass
    mine = {r.name: r for r in PROCESS_LOG.records}
    assert {"orphan_outer", "orphan_inner", "of_the_first", "of_the_second", "free_inside_the_second"} <= set(mine)
    assert mine["orphan_inner"].parent == "orphan_outer" and mine["free_inside_the_second"].parent == "of_the_second"
    assert mine["of_the_second"].self_s == pytest.approx(
        mine["of_the_second"].dur_s - mine["free_inside_the_second"].dur_s, abs=1e-9)
    assert Telemetry(enabled=False).span("never") is NULL_CONTEXT  # a disabled instance still hands out the no-op


def test_a_ledger_made_late_counts_the_earlier_build_components_and_starts_where_the_gap_began(tmp_path):
    """What `python -m modalities_tpu run` does: the set-up's spans are recorded with no
    instance active; the run's `Telemetry` is activated afterwards, and its ledger, wall
    clock and sink hold them."""
    PROCESS_LOG.claim()  # what earlier tests of this process left behind is not this run's
    with span("build_components"):
        time.sleep(0.02)
    telemetry = Telemetry(watchdog_deadline_s=0, use_jax_annotations=False)
    made_at = telemetry.ledger.origin
    previous = set_active_telemetry(telemetry)
    try:
        assert telemetry.ledger.origin < made_at  # set back to where the unaccounted stretch began
        assert telemetry.ledger.bucket_seconds()["init"] >= 0.02
        with span("init"):
            time.sleep(0.01)
        telemetry.set_output_folder(tmp_path)  # the sink opens after the claim: it writes them then
    finally:
        set_active_telemetry(previous)
    summary = telemetry.goodput_summary()
    telemetry.close()
    assert summary["buckets"]["init"] >= 0.03 and summary["wall_s"] >= summary["buckets"]["init"]
    spans = [e for e in map(json.loads, telemetry.sink_path.read_text().splitlines()) if e["event"] == "span"]
    assert spans[0]["name"] == "build_components" and spans[0]["timeline"] and spans[0]["parent"] is None
    assert 0 <= spans[0]["start_s"] == pytest.approx(
        next(r.t0 for r in PROCESS_LOG.records if r.name == "build_components" and r.dur_s == pytest.approx(spans[0]["dur_s"], abs=1e-5))
        - PROCESS_LOG.origin, abs=1e-4)
    # a second instance takes the stretch after the first stepped down, not the first's spans
    with span("between_the_two"):
        pass
    later = Telemetry(watchdog_deadline_s=0, use_jax_annotations=False)
    previous = set_active_telemetry(later)
    set_active_telemetry(previous)
    assert later.ledger.bucket_seconds()["init"] == 0.0 and later.ledger.bucket_seconds()["other"] > 0
    assert telemetry.ledger.origin < later.ledger.origin <= made_at + 10


def test_the_first_instance_of_a_process_starts_its_wall_clock_at_the_logs_origin():
    log = SpanLog(origin=12.5)
    log.add(SpanRecord(name="backend_start", ts=0.0, dur_s=2.0, self_s=2.0, thread="t", timeline=True, t0=13.0), claimed=False)
    log.add(SpanRecord(name="of_an_instance", ts=0.0, dur_s=1.0, self_s=1.0, thread="t", timeline=True, t0=15.0), claimed=True)
    since, records = log.claim()
    assert since == 12.5 == log.origin and [r.name for r in records] == ["backend_start"]
    log.release()
    assert log.claim()[0] > 12.5


def test_split_names_what_the_timeline_thread_was_in():
    log = SpanLog(origin=0.0)
    for name, t0, dur, parent, timeline in (("data_wait", 10.0, 0.5, None, True), ("train_step", 10.6, 0.1, None, True),
                                            ("inner", 10.61, 0.05, "train_step", True), ("metrics_fetch", 10.8, 3.0, None, True),
                                            ("transfer", 10.0, 4.0, None, False), ("publish", 13.9, 0.4, None, True)):
        log.add(SpanRecord(name=name, ts=0.0, dur_s=dur, self_s=dur, thread="t", timeline=timeline, t0=t0, parent=parent), claimed=True)
    split = log.split(10.2, 14.0)
    assert split == pytest.approx({"data_wait": 0.3, "train_step": 0.1, "metrics_fetch": 3.0, "publish": 0.1, "unspanned": 0.3})
    assert sum(split.values()) == pytest.approx(3.8)


# ------------------------------------- PR 13: stragglers + step-time anomalies


def _summary_with(rank_buckets: dict) -> dict:
    return {
        "ranks": {
            rank: {"buckets": dict(buckets)} for rank, buckets in rank_buckets.items()
        }
    }


def test_straggler_summary_names_slowest_rank_per_bucket():
    from modalities_tpu.telemetry.goodput import format_straggler_table, straggler_summary

    summary = _summary_with({
        0: {"train_step": 8.0, "data_stall": 1.0},
        1: {"train_step": 8.1, "data_stall": 0.9},
        2: {"train_step": 8.0, "data_stall": 4.0},  # the data straggler
    })
    stragglers = straggler_summary(summary)
    assert stragglers["data_stall"]["slowest_rank"] == 2
    assert stragglers["data_stall"]["seconds"] == 4.0
    assert stragglers["data_stall"]["median_s"] == 1.0
    assert stragglers["data_stall"]["ratio_vs_median"] == 4.0
    assert stragglers["train_step"]["slowest_rank"] == 1
    assert "checkpoint" not in stragglers  # no rank recorded any: dropped
    table = format_straggler_table(stragglers)
    assert "rank 2" in table and "data_stall" in table


def test_straggler_summary_single_rank_and_empty():
    from modalities_tpu.telemetry.goodput import format_straggler_table, straggler_summary

    # one rank has no peer to lag behind: no degenerate self-straggler table
    assert straggler_summary(_summary_with({0: {"train_step": 5.0}})) == {}
    assert straggler_summary({"ranks": {}}) == {}
    assert "no per-rank" in format_straggler_table({})


def test_observe_step_time_feeds_gauges_counter_and_sink(tmp_path):
    telemetry = Telemetry(
        output_folder_path=tmp_path, watchdog_deadline_s=0,
        anomaly_zscore=6.0, anomaly_window=32,
    )
    for step in range(12):
        telemetry.observe_step_time(1.0 + 0.001 * (step % 3), step_id=step)
    reg = telemetry.metrics
    assert reg.counter("training_step_time_anomaly_total").value() == 0
    assert reg.gauge("training_step_time_ewma_seconds").value() == pytest.approx(1.0, abs=0.01)

    telemetry.observe_step_time(5.0, step_id=12)  # a 5x excursion
    assert reg.counter("training_step_time_anomaly_total").value() == 1
    assert reg.gauge("training_step_time_zscore").value() > 6.0
    telemetry.close()
    events = [json.loads(ln) for ln in telemetry.sink_path.read_text().splitlines()]
    anomalies = [e for e in events if e.get("name") == "anomaly/step_time"]
    assert len(anomalies) == 1 and anomalies[0]["step_id"] == 12
    assert anomalies[0]["seconds"] == 5.0


def test_bucket_delta_zscore_localizes_the_anomalous_phase(tmp_path):
    telemetry = Telemetry(
        output_folder_path=tmp_path, watchdog_deadline_s=0, anomaly_window=16,
    )
    try:
        # steady publishes: every interval adds ~1s train_step, ~0.1s data_stall
        totals = {"train_step": 0.0, "data_stall": 0.0}
        for i in range(10):
            totals["train_step"] += 1.0
            totals["data_stall"] += 0.1
            telemetry._observe_bucket_deltas(dict(totals))
        gauge = telemetry.metrics.gauge("training_goodput_bucket_zscore")
        assert abs(gauge.value(bucket="data_stall")) < 6.0
        # one interval suddenly stalls 3s on data: only that bucket's z spikes
        totals["train_step"] += 1.0
        totals["data_stall"] += 3.0
        telemetry._observe_bucket_deltas(dict(totals))
        assert gauge.value(bucket="data_stall") > 6.0
        assert abs(gauge.value(bucket="train_step")) < 6.0
    finally:
        telemetry.close()
