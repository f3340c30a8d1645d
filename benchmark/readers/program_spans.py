"""The host's own timeline as the program recorded it: the process's log of finished
spans (`modalities_tpu.telemetry.spans.PROCESS_LOG`) and of backend compiles
(`modalities_tpu.telemetry.compile_log.PROCESS_COMPILES`), both kept by the process and
not by the `Telemetry`, trainer or components that the mode has dropped by the time
readers run, both on `time.perf_counter()`, the clock of `observed["window"]`.

`key` names what is read. Six numbers tile the set-up, origin to window start:
`setup_build_components_s`, `setup_init_s`, `setup_preflight_s`, `setup_first_step_s` (the
spans `build_components`, `init`, `preflight_memscope`, `first_step`),
`setup_warm_steps_s` (end of `first_step` to window start) and `setup_outside_spans_s`
(all the rest: interpreter, imports, backend start, the benchmark's corpus and seeded
weights, the gaps between the program's calls). Two cut the same seconds another way,
the backend compiles that ended before the window: `setup_compile_miss_s` (the
persistent cache did not answer) and `setup_compile_hit_s` (it did: their load time).
Two read the window: `train_host_work_ms` (per step, the timeline thread's time outside
`metrics_fetch` and `data_wait`: dispatch, publish and what no span holds; median over
the steps) and `train_loop_unspanned_pct` (share of the window the timeline thread was
inside no span).

The origin is `PROCESS_START` of the module running as `__main__` where it has one
(`benchmark/run.py` as the command runs it), else the log's own. The first metric of a
run prints the `[spans]` table and keeps the result with what the run observed, for the
others. Every metric is `None` on a program without the record (a commit from before
it) and on a run without a device trace (every CPU run: a host timing from a CPU has no
place under these names), so the arithmetic is in functions that take the records.
"""

from __future__ import annotations

import sys

from benchmark.stats import median

SETUP_SPANS = ("backend_start", "build_components", "init", "checkpoint_restore", "preflight_memscope", "first_step")
OUTSIDE, WARM = "(outside spans)", "(warm-up steps)"
ROW_OF_METRIC = {"setup_build_components_s": "build_components", "setup_init_s": "init",
                 "setup_preflight_s": "preflight_memscope", "setup_first_step_s": "first_step", "setup_warm_steps_s": WARM}
NOT_HOST_WORK = ("metrics_fetch", "data_wait")  # the loop waits there: for the device, for a batch
LISTED_FROM_S = 0.5  # a compile this long is listed by name in the table, shorter ones are counted


def _outermost(spans, start: float, end: float) -> list:
    """Spans of the timeline thread that no other span encloses and that touch [start, end], by start."""
    return sorted((s for s in spans if s.timeline and s.parent is None and s.t0 < end and s.t0 + s.dur_s > start),
                  key=lambda s: s.t0)


def setup_rows(spans, compiles, origin: float, window_start: float) -> list[dict]:
    """Consecutive rows that tile [origin, window_start]: each span of the set-up, the gap
    before it, and from the end of the last `first_step` on the warm-up steps. A row holds
    the compiles that ended inside it and the spans directly inside its span."""
    spans = list(spans)
    top = [s for s in _outermost(spans, origin, window_start) if s.t0 >= origin and s.t0 + s.dur_s <= window_start]
    first_steps = [s for s in top if s.name == "first_step"]
    warm_from = first_steps[-1].t0 + first_steps[-1].dur_s if first_steps else window_start
    rows, cursor = [], origin

    def add(name: str, start: float, end: float, span=None) -> None:
        inside = [c for c in compiles if start < c.at <= end]
        children = [(s.name, s.dur_s) for s in spans if span is not None and s.parent == span.name
                    and span.t0 <= s.t0 and s.t0 + s.dur_s <= end and s.thread == span.thread]
        rows.append({"name": name, "start": start, "seconds": end - start, "compiles": inside, "children": children})

    for span in top:
        if span.name not in SETUP_SPANS or span.t0 + span.dur_s > warm_from:
            continue  # anything else before the first step (an earlier run of this process) is part of the gap
        if span.t0 > cursor:
            add(OUTSIDE, cursor, span.t0)
        add(span.name, span.t0, span.t0 + span.dur_s, span)
        cursor = span.t0 + span.dur_s
    if window_start > cursor:
        add(WARM if first_steps else OUTSIDE, cursor, window_start)
    return rows


def setup_seconds(rows: list[dict]) -> dict[str, float]:
    """The six set-up metrics from the rows: they sum to what the rows tile."""
    out = {metric: sum(r["seconds"] for r in rows if r["name"] == name) for metric, name in ROW_OF_METRIC.items()}
    out["setup_outside_spans_s"] = sum(r["seconds"] for r in rows if r["name"] not in ROW_OF_METRIC.values())
    return out


def compile_seconds(compiles, origin: float, window_start: float) -> dict[str, float]:
    before = [c for c in compiles if origin < c.at <= window_start]
    return {"setup_compile_miss_s": sum(c.seconds for c in before if not c.cache_hit),
            "setup_compile_hit_s": sum(c.seconds for c in before if c.cache_hit)}


def step_splits(spans, window_start: float, step_seconds) -> list[dict]:
    """For each step of the window (from one publish stamp to the next): the seconds its
    stretch of the timeline thread spent in each outermost span, `unspanned` in none, and
    `host_work` outside the two spans the loop waits in."""
    end = window_start + sum(step_seconds)
    top = _outermost(spans, window_start, end)
    out, start = [], window_start
    for k, seconds in enumerate(step_seconds):
        stop = start + seconds
        split: dict[str, float] = {}
        for s in top:
            held = min(stop, s.t0 + s.dur_s) - max(start, s.t0)
            if held > 0:
                split[s.name] = split.get(s.name, 0.0) + held
        split["unspanned"] = max(0.0, seconds - sum(split.values()))
        out.append({"index": k, "seconds": seconds, "split": split,
                    "host_work": seconds - sum(split.get(name, 0.0) for name in NOT_HOST_WORK)})
        start = stop
    return out


def window_metrics(splits: list[dict]) -> dict[str, float]:
    window = sum(s["seconds"] for s in splits)
    return {"train_host_work_ms": 1e3 * median([s["host_work"] for s in splits]),
            "train_loop_unspanned_pct": 100.0 * sum(s["split"]["unspanned"] for s in splits) / window}


def _compiles_text(compiles) -> str:
    if not compiles:
        return ""
    listed = [f"{c.function} {c.seconds:.2f} s {'hit' if c.cache_hit else 'miss'}" for c in compiles if c.seconds >= LISTED_FROM_S]
    rest = [c for c in compiles if c.seconds < LISTED_FROM_S]
    if rest:
        listed.append(f"{len(rest)} under {LISTED_FROM_S} s: {sum(c.seconds for c in rest):.2f} s, {sum(c.cache_hit for c in rest)} hit")
    return "  compiles: " + "; ".join(listed)


def describe(rows: list[dict], splits: list[dict], origin: float, origin_of: str) -> str:
    lines = [f"[spans] set-up, {sum(r['seconds'] for r in rows):.2f} s from {origin_of} to the window's start:"]
    for r in rows:
        children = "".join(f" [{name} {seconds:.2f}]" for name, seconds in r["children"])
        lines.append(f"[spans]   +{r['start'] - origin:7.2f} {r['seconds']:8.2f} s  {r['name']}{children}{_compiles_text(r['compiles'])}")
    names = sorted({name for s in splits for name in s["split"]}, key=lambda n: (n == "unspanned", n))
    medians = ", ".join(f"{name} {1e3 * median([s['split'].get(name, 0.0) for s in splits]):.3f}" for name in names)
    lines.append(f"[spans] a step of the window ({len(splits)} steps), median ms by outermost span of the loop's thread: {medians}; "
                 f"outside {' and '.join(NOT_HOST_WORK)} {1e3 * median([s['host_work'] for s in splits]):.3f}")
    for s in sorted(splits, key=lambda s: s["seconds"], reverse=True)[:3]:
        held = ", ".join(f"{name} {1e3 * seconds:.1f}" for name, seconds in sorted(s["split"].items(), key=lambda kv: -kv[1]) if seconds >= 5e-5)
        lines.append(f"[spans]   slowest: step {s['index'] + 1} of the window, {1e3 * s['seconds']:.1f} ms: {held}")
    return "\n".join(lines)


def process_record():
    """(spans, compiles, the log's origin) of this process, or None where the program keeps no such record.
    Looked up among the modules the run has imported: the reader imports nothing of the program."""
    log = getattr(sys.modules.get("modalities_tpu.telemetry.spans"), "PROCESS_LOG", None)
    compiles = getattr(sys.modules.get("modalities_tpu.telemetry.compile_log"), "PROCESS_COMPILES", None)
    if log is None or compiles is None:
        return None
    return list(log.records), list(compiles), log.origin


def _found(observed: dict):
    if "program_spans" not in observed:
        observed["program_spans"] = None
        record = process_record()
        if record is not None and observed.get("step_seconds"):
            spans, compiles, origin = record
            origin_of = "the span log's origin"
            start_of_main = getattr(sys.modules.get("__main__"), "PROCESS_START", None)
            if isinstance(start_of_main, float):
                origin, origin_of = start_of_main, "process start"
            window_start = observed["window"][0]
            rows = setup_rows(spans, compiles, origin, window_start)
            splits = step_splits(spans, window_start, observed["step_seconds"])
            print(describe(rows, splits, origin, origin_of), flush=True)
            observed["program_spans"] = {**setup_seconds(rows), **compile_seconds(compiles, origin, window_start), **window_metrics(splits)}
    return observed["program_spans"]


def read(spec: dict, observed: dict, trace, env: dict):
    if trace is None or not trace.devices:
        return None
    found = _found(observed)
    return None if found is None else found[spec["key"]]
