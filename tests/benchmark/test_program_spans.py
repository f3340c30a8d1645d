"""The reader of the program's own timeline (benchmark/readers/program_spans.py): its
arithmetic on a hand-made log, what it returns where there is nothing to read, and, at
toy size on the CPU, the set-up of a whole run tiled by the rows it makes from the
process's record. Nothing here is a measurement."""

import json
import sys
import time
from pathlib import Path
from types import SimpleNamespace

import pytest

from benchmark import run as bench_run
from benchmark.device import device_info
from benchmark.manifest import load_cell, load_manifest
from benchmark.readers import program_spans as reader
from tests.benchmark.accepted import ACCEPTED_CELLS, holds_at_least
from tests.benchmark.toy import make_toy_root

REPO = Path(__file__).resolve().parents[2]
CELL = "train-2p7b-4k"
METRICS = sorted(path.stem for path in (REPO / "benchmark" / "metrics").glob("*.json")
                 if json.loads(path.read_text())["reader"] == "program_spans")
SETUP = ["setup_outside_spans_s", "setup_build_components_s", "setup_init_s", "setup_preflight_s", "setup_first_step_s", "setup_warm_steps_s"]


def span(name, t0, dur, parent=None, timeline=True, thread="MainThread", step=None):
    """A finished span as the reader takes it: the fields of the program's `SpanRecord` it reads, and no import of it."""
    return SimpleNamespace(name=name, t0=t0, dur_s=dur, parent=parent, timeline=timeline, thread=thread, step=step)


def CompileRecord(at, function, seconds, cache_hit):
    return SimpleNamespace(at=at, function=function, seconds=seconds, cache_hit=cache_hit)


# a set-up from 100.0 to a window that starts at 130.0, and a window of five steps of 0.3 s, one of them of 2.3 s
ORIGIN, WINDOW_START = 100.0, 130.0
STEP_SECONDS = [0.3, 0.3, 2.3, 0.3, 0.3]
HAND_MADE = [
    span("build_components", 109.0, 2.0),
    span("state_init", 111.5, 3.0, parent="init"), span("init", 111.1, 3.9),
    span("transfer", 112.0, 9.0, timeline=False, thread="device-feeder"),  # a background thread's: no row, no share
    span("data_wait", 119.95, 0.05, step=1),
    span("preflight_memscope", 120.0, 1.5, step=1), span("first_step", 121.6, 4.4, step=1),
    span("metrics_fetch", 126.5, 0.5, step=2), span("publish", 129.9, 0.2, step=3),  # warm-up; the window's stamp falls inside this publish
]
for k, (start, seconds) in enumerate(zip([130.0, 130.3, 130.6, 132.9, 133.2], STEP_SECONDS)):
    HAND_MADE += [span("data_wait", start + 0.11, 0.01, step=k + 4), span("train_step", start + 0.12, 0.002, step=k + 4),
                  span("metrics_fetch", start + 0.125, seconds - 0.13, step=k + 4), span("publish", start + seconds - 0.004, 0.104, step=k + 4)]
COMPILES = [CompileRecord(99.0, "before_the_origin", 5.0, False), CompileRecord(108.0, "convert_element_type", 0.1, False),
            CompileRecord(114.0, "init_state", 2.0, True), CompileRecord(121.4, "train_step", 1.2, True),
            CompileRecord(125.9, "train_step", 4.0, False), CompileRecord(131.0, "inside_the_window", 9.0, False)]


def test_setup_rows_tile_origin_to_window_start_and_hold_their_compiles():
    rows = reader.setup_rows(HAND_MADE, COMPILES, ORIGIN, WINDOW_START)
    assert [r["name"] for r in rows] == [reader.OUTSIDE, "build_components", reader.OUTSIDE, "init", reader.OUTSIDE,
                                         "preflight_memscope", reader.OUTSIDE, "first_step", reader.WARM]
    assert rows[0]["start"] == ORIGIN and rows[-1]["start"] + rows[-1]["seconds"] == pytest.approx(WINDOW_START)
    for before, after in zip(rows, rows[1:]):  # consecutive: each starts where the one before it ends
        assert after["start"] == pytest.approx(before["start"] + before["seconds"])
    by_name = {r["name"]: r for r in rows if r["name"] not in (reader.OUTSIDE,)}
    assert by_name["init"]["children"] == [("state_init", 3.0)] and by_name["build_components"]["children"] == []
    assert [c.function for c in rows[0]["compiles"]] == ["convert_element_type"]  # not the one before the origin
    assert [(c.function, c.cache_hit) for c in by_name["init"]["compiles"]] == [("init_state", True)]
    assert [(c.function, c.cache_hit) for c in by_name["preflight_memscope"]["compiles"]] == [("train_step", True)]
    assert [(c.function, c.cache_hit) for c in by_name["first_step"]["compiles"]] == [("train_step", False)]
    assert by_name[reader.WARM]["compiles"] == []  # nor the one inside the window


def test_the_six_setup_metrics_sum_to_the_setup_and_the_compiles_cut_it_another_way():
    six = reader.setup_seconds(reader.setup_rows(HAND_MADE, COMPILES, ORIGIN, WINDOW_START))
    assert sorted(six) == sorted(SETUP)
    assert six == pytest.approx({"setup_build_components_s": 2.0, "setup_init_s": 3.9, "setup_preflight_s": 1.5, "setup_first_step_s": 4.4,
                                 "setup_warm_steps_s": 4.0, "setup_outside_spans_s": 9.0 + 0.1 + 5.0 + 0.1})
    assert sum(six.values()) == pytest.approx(WINDOW_START - ORIGIN)
    assert reader.compile_seconds(COMPILES, ORIGIN, WINDOW_START) == pytest.approx({"setup_compile_miss_s": 4.1, "setup_compile_hit_s": 3.2})


def test_a_process_that_ran_before_leaves_its_spans_in_the_gap_and_the_last_first_step_opens_the_warm_up():
    earlier = [span("build_components", 101.0, 1.0), span("first_step", 102.5, 1.0), span("train_step", 104.0, 0.5), span("eval", 105.0, 1.0)]
    rows = reader.setup_rows(earlier + HAND_MADE, COMPILES, ORIGIN, WINDOW_START)
    assert [r["name"] for r in rows].count("first_step") == 2 and [r["name"] for r in rows].count(reader.WARM) == 1
    assert sum(reader.setup_seconds(rows).values()) == pytest.approx(WINDOW_START - ORIGIN)
    no_step_yet = reader.setup_rows([span("build_components", 101.0, 1.0)], [], ORIGIN, 103.0)
    assert [r["name"] for r in no_step_yet] == [reader.OUTSIDE, "build_components", reader.OUTSIDE]


def test_steps_of_the_window_split_by_span_and_the_far_off_step_names_what_held_it():
    splits = reader.step_splits(HAND_MADE, WINDOW_START, STEP_SECONDS)
    assert [s["seconds"] for s in splits] == STEP_SECONDS
    for s in splits:
        assert sum(s["split"].values()) == pytest.approx(s["seconds"])
    usual, far_off = splits[1], splits[2]
    # a step runs from one publish's stamp to the next: the rest of that publish (0.1), the loop's tail (0.01 in no span), the
    # wait for a batch, the dispatch, the wait for the device, and the next publish up to its stamp (0.004)
    assert usual["split"] == pytest.approx({"publish": 0.104, "data_wait": 0.01, "train_step": 0.002, "metrics_fetch": 0.17, "unspanned": 0.014})
    assert far_off["split"]["metrics_fetch"] == pytest.approx(2.17) and far_off["host_work"] == pytest.approx(usual["host_work"]) == pytest.approx(0.12)
    numbers = reader.window_metrics(splits)
    assert numbers["train_host_work_ms"] == pytest.approx(120.0)
    assert numbers["train_loop_unspanned_pct"] == pytest.approx(100 * 5 * 0.014 / 3.5)
    text = reader.describe(reader.setup_rows(HAND_MADE, COMPILES, ORIGIN, WINDOW_START), splits, ORIGIN, "process start")
    assert all(line.startswith("[spans]") for line in text.splitlines())
    assert "init [state_init 3.00]  compiles: init_state 2.00 s hit" in text and "train_step 4.00 s miss" in text
    assert "slowest: step 3 of the window, 2300.0 ms: metrics_fetch 2170.0" in text


class _Traced:
    devices = ["a TPU plane"]


@pytest.fixture
def hand_made_process(monkeypatch):
    """The process's record as the reader finds it, holding the hand-made log."""
    log = SimpleNamespace(records=HAND_MADE, origin=ORIGIN)
    monkeypatch.setitem(sys.modules, "modalities_tpu.telemetry.spans", SimpleNamespace(PROCESS_LOG=log))
    monkeypatch.setitem(sys.modules, "modalities_tpu.telemetry.compile_log", SimpleNamespace(PROCESS_COMPILES=COMPILES))
    return {"window": (WINDOW_START, WINDOW_START + sum(STEP_SECONDS)), "step_seconds": list(STEP_SECONDS)}


@pytest.mark.parametrize("metric", METRICS)
def test_every_metric_reads_a_number_from_the_record_on_a_traced_run(hand_made_process, metric, capsys):
    assert len(METRICS) == 10
    spec = json.loads((REPO / "benchmark" / "metrics" / f"{metric}.json").read_text())
    value = reader.read(spec, hand_made_process, _Traced(), {})
    assert isinstance(value, float) and value >= 0
    assert capsys.readouterr().out.count("[spans] set-up") == 1
    assert reader.read(spec, hand_made_process, _Traced(), {}) == value and capsys.readouterr().out == ""  # printed once a run
    if metric in SETUP:
        assert sum(hand_made_process["program_spans"][name] for name in SETUP) == pytest.approx(WINDOW_START - ORIGIN)


@pytest.mark.parametrize("metric", METRICS)
def test_every_metric_is_none_on_a_program_without_the_record(hand_made_process, monkeypatch, metric):
    """The parent commit under this PR's benchmark files: its modules are there and keep no log."""
    monkeypatch.setitem(sys.modules, "modalities_tpu.telemetry.spans", SimpleNamespace(SpanRecorder=object))
    spec = json.loads((REPO / "benchmark" / "metrics" / f"{metric}.json").read_text())
    assert reader.read(spec, hand_made_process, _Traced(), {}) is None
    monkeypatch.delitem(sys.modules, "modalities_tpu.telemetry.spans")
    assert reader.read(spec, dict(hand_made_process, program_spans=None), _Traced(), {}) is None
    assert reader.process_record() is None


def test_every_metric_is_none_on_a_run_without_a_device_trace(hand_made_process):
    for metric in METRICS:
        spec = json.loads((REPO / "benchmark" / "metrics" / f"{metric}.json").read_text())
        assert reader.read(spec, hand_made_process, None, {}) is None
        assert reader.read(spec, hand_made_process, SimpleNamespace(devices=[]), {}) is None
    assert "program_spans" not in hand_made_process  # nothing was read or printed


def test_the_ten_entries_kept_for_a_benchmark_issue_are_ones_the_manifest_can_take():
    """Since PR 49 `BENCHMARK.json` lists the ten (they waited in a file beside the reader from PR 34 on): each in every
    cell, with the unit, source, arrow and layer they were kept with. The manifest is the one list."""
    manifest = load_manifest(REPO)
    mine = {m["name"]: m for m in manifest["per_layer"] if m["name"] in METRICS}
    assert sorted(mine) == METRICS and len(mine) == 10
    assert [m["name"] for m in manifest["per_layer"] if m["name"] in mine] == SETUP + [
        "setup_compile_miss_s", "setup_compile_hit_s", "train_host_work_ms", "train_loop_unspanned_pct"], "in the order they were kept in"
    assert holds_at_least([w["name"] for w in manifest["workloads"]], ACCEPTED_CELLS)
    perf = (REPO / "PERF.md").read_text()
    for name, entry in mine.items():
        assert set(entry) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert holds_at_least(entry["workloads"], ACCEPTED_CELLS) and entry["better"] == "lower", "a later cell may join the list"
        assert entry["unit"] == ("ms" if name.endswith("_ms") else "%" if name.endswith("_pct") else "s")
        assert entry["moves"] == ("setup_s" if name.startswith("setup_") else "train_tokens_per_s")
        assert entry["source"] == ("program_counter" if "compile" in name else "program_span")
        assert entry["layer"] in ("entry points, runtime seam", "trainer loop") and f"| {entry['layer']} |" in perf
        assert f"`{name}`" in perf.split("## 3. Layers")[1].split("## 4. Cells")[0], "section 3 names the metric beside its span or counter"
    assert not (REPO / "benchmark" / "readers" / "program_spans.entries.json").exists()


@pytest.mark.parametrize("cell_name", ACCEPTED_CELLS)  # the cells the ten were listed in: a later cell's case is for the PR that lists them in it
def test_with_the_entries_appended_the_harness_finds_the_ten_in_every_cell_and_reads_them(tmp_path, hand_made_process, cell_name, capsys):
    """In every cell the ten come after what the cell reported before them, which keeps its order, and the
    reader reads all ten from the hand-made record, the six set-up rows summing to origin-to-window."""
    root = make_toy_root(tmp_path / "root")
    manifest = load_manifest(root)
    cell = load_cell(cell_name, root)
    ten = [m["name"] for m in manifest["per_layer"] if m["name"] in METRICS]
    at = cell.per_layer.index(ten[0])
    before = cell.per_layer[:at]
    assert at >= 9 and list(cell.per_layer[at:at + 10]) == ten, "the ten together, after what the cell reported before them"
    (root / "BENCHMARK.json").write_text(json.dumps({**manifest, "per_layer": [m for m in manifest["per_layer"] if m["name"] not in METRICS]}))
    assert holds_at_least(load_cell(cell_name, root).per_layer, before), "appended: what the cell reported before stays where it was"
    values = {name: cell.module("readers", cell.metric_spec(name)["reader"]).read(cell.metric_spec(name), hand_made_process, _Traced(), {})
              for name in ten}
    assert all(isinstance(v, float) for v in values.values()) and capsys.readouterr().out.count("[spans] set-up") == 1
    assert sum(values[name] for name in SETUP) == pytest.approx(WINDOW_START - ORIGIN)


# ------------------------------------------------------------------ a whole run at toy size


@pytest.fixture(scope="module")
def toy_run(tmp_path_factory):
    from modalities_tpu.telemetry import Telemetry

    root = make_toy_root(tmp_path_factory.mktemp("toy_spans"))
    made, construct = [], Telemetry.__init__

    def remembered(self, *args, **kwargs):
        construct(self, *args, **kwargs)
        made.append(self)

    Telemetry.__init__ = remembered  # the mode drops its instance unclosed: its watchdog thread would outlive this file
    before = time.perf_counter()
    try:
        result = bench_run.execute(CELL, 2**31 + 34, 0.4, trace=False, root=root, device_gate=lambda chips: device_info())
    finally:
        Telemetry.__init__ = construct
        for telemetry in made:
            telemetry.close()
    if reader.process_record() is None:
        pytest.skip("this program keeps no record of its spans and compiles (a commit from before PR 34)")
    return before, result


def test_an_untraced_toy_run_leaves_a_record_whose_rows_tile_its_setup(toy_run):
    """The mode has dropped its `Telemetry`, trainer and components; the process still has the run's timeline."""
    before, result = toy_run
    setup_s = result["metrics"]["setup_s"]["value"]
    window_start = bench_run.PROCESS_START + setup_s
    spans, compiles, log_origin = reader.process_record()
    assert bench_run.PROCESS_START <= before and bench_run.PROCESS_START <= log_origin and spans and compiles
    rows = reader.setup_rows(spans, compiles, bench_run.PROCESS_START, window_start)
    six = reader.setup_seconds(rows)
    assert len(six) == 6 and sum(six.values()) == pytest.approx(setup_s, abs=0.05)
    for earlier, later in zip(rows, rows[1:]):
        assert later["start"] == pytest.approx(earlier["start"] + earlier["seconds"], abs=1e-9)
    this_run = [r for r in rows if r["start"] >= before]
    assert [r["name"] for r in this_run if r["name"] != reader.OUTSIDE] == ["build_components", "init", "first_step", reader.WARM]
    assert all(six[name] > 0 for name in ("setup_build_components_s", "setup_init_s", "setup_first_step_s", "setup_warm_steps_s"))
    init = next(r for r in this_run if r["name"] == "init")
    assert [name for name, _ in init["children"]] == ["state_init"] and init["compiles"], "the jitted init compiled inside `init`"
    assert any("train_step" in c.function for r in this_run if r["name"] == "first_step" for c in r["compiles"])
    assert six["setup_preflight_s"] == 0.0  # a CPU reports no bytes_limit: the preflight does not run


def test_the_toy_runs_window_splits_into_the_loops_spans_with_their_steps(toy_run):
    before, result = toy_run
    spans = [s for s in reader.process_record()[0] if s.t0 >= before]
    loop = [s for s in spans if s.name in ("data_wait", "train_step", "metrics_fetch", "publish")]
    assert {s.name for s in loop} == {"data_wait", "train_step", "metrics_fetch", "publish"} and all(s.timeline and s.parent is None for s in loop)
    steps = [s.step for s in loop if s.name == "train_step"]
    assert steps == list(range(2, 2 + len(steps))) and len(steps) >= 6 + result["attempted"] - 1  # `first_step` is step 1
    first = next(s for s in spans if s.name == "first_step")
    assert first.step == 1 and [s.step for s in spans if s.name in ("build_components", "init", "state_init")] == [None, None, None]
    splits = reader.step_splits(spans, first.t0 + first.dur_s, [0.05] * 4)
    assert all(sum(s["split"].values()) == pytest.approx(0.05) for s in splits)
