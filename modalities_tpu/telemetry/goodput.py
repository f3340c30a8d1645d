"""Goodput ledger: attribute every wall-clock second of a run to one bucket.

Buckets (cf. the MPMD-pipeline paper's bubble/stall attribution in PAPERS.md):

- ``init``               backend start, component build, state init, checkpoint restore
- ``compile_first_step`` the preflight's AOT compile of the step and the first train
                         step of the run (jit trace + compile or cache load)
- ``train_step``         step dispatch + the device-execution wait when interval
                         metrics are fetched — the *goodput* numerator
- ``data_stall``         the step loop blocked waiting for a host batch
- ``eval``               evaluation passes
- ``checkpoint``         checkpoint save + end-of-run drain
- ``publish``            assembling/publishing interval results to the broker
- ``recovery``           resilience work: checkpoint-IO retries, forced
                         preemption checkpoints, rollback/fallback resolution
- ``other``              explicit unknown spans + all wall time not covered by
                         any timeline span (loop scaffolding, callbacks, ...)

The ledger consumes the exclusive time (``self_s``) of *timeline-thread* spans
only, so every second of the step loop's wall time lands in at most one bucket
and the bucket sum can never exceed wall time. ``summary()`` folds the untracked
remainder into ``other``, which makes "bucket seconds sum to wall time" hold by
construction — the interesting signal is how small ``other`` is.

``goodput_pct`` = 100 * train_step / wall: the fraction of the run the devices
spent advancing the model. The wall clock of the ledger of an ACTIVE `Telemetry` starts
where the process's span log does (`spans.PROCESS_LOG`: the package's import), or where
the instance active before it stepped down, and takes the timeline spans recorded since
with no instance to hand them to: the set-up is part of the run it is charged to.
"""

from __future__ import annotations

import json
import threading
import time
from pathlib import Path
from typing import Iterable, Optional, Union

from modalities_tpu.telemetry.spans import SpanRecord

BUCKETS = (
    "init",
    "compile_first_step",
    "train_step",
    "data_stall",
    "eval",
    "checkpoint",
    "publish",
    "recovery",
    "serve",
    "other",
)

# span name (first path segment) -> bucket: a name is here only if a call site opens
# a span of it (tests/telemetry/test_spans_goodput.py finds them in the source)
_NAME_TO_BUCKET = {
    "backend_start": "init",
    "build_components": "init",
    "init": "init",
    "state_init": "init",
    "checkpoint_restore": "init",
    "preflight_memscope": "compile_first_step",
    "collective_plan": "compile_first_step",  # inside preflight_memscope, on a mesh of several devices
    "first_step": "compile_first_step",
    "train_step": "train_step",
    "metrics_fetch": "train_step",
    "data_wait": "data_stall",
    "eval": "eval",
    "checkpoint_save": "checkpoint",
    "checkpoint_drain": "checkpoint",
    "publish": "publish",
    "preempt": "recovery",
    "ckpt_retry": "recovery",
    # serving engine (serving/engine.py): "serve/prefill", "serve/decode",
    # "serve/admission" all land in one bucket — decode-step seconds over total
    # serve seconds is the engine's goodput
    "serve": "serve",
}


def bucket_of(span_name: str) -> str:
    """Spans may namespace with '/' (e.g. "eval/val_loader"); the first segment
    decides the bucket."""
    return _NAME_TO_BUCKET.get(span_name.split("/", 1)[0], "other")


class GoodputLedger:
    """Thread-safe accumulator from the span stream (or direct `add_seconds`,
    for callers that time segments without span machinery)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._seconds = {bucket: 0.0 for bucket in BUCKETS}
        self._t0 = time.perf_counter()

    def start(self, at: Optional[float] = None) -> None:
        """(Re)set the wall-clock origin used by `wall_s()`: now, or `at` on `time.perf_counter()`."""
        self._t0 = time.perf_counter() if at is None else at

    @property
    def origin(self) -> float:
        return self._t0

    def wall_s(self) -> float:
        return time.perf_counter() - self._t0

    def add_record(self, record: SpanRecord) -> None:
        if not record.timeline:
            return  # background threads overlap the main timeline
        self.add_seconds(bucket_of(record.name), record.self_s)

    def add_seconds(self, bucket: str, seconds: float) -> None:
        if bucket not in self._seconds:
            bucket = "other"
        with self._lock:
            self._seconds[bucket] += seconds

    def bucket_seconds(self) -> dict[str, float]:
        with self._lock:
            return dict(self._seconds)

    def summary(self, wall_s: Optional[float] = None) -> dict:
        """{"wall_s", "goodput_pct", "buckets": {bucket: seconds}} with the
        untracked remainder folded into "other" so the buckets sum to wall_s."""
        if wall_s is None:
            wall_s = self.wall_s()
        buckets = self.bucket_seconds()
        tracked = sum(buckets.values())
        buckets["other"] += max(0.0, wall_s - tracked)
        goodput_pct = 100.0 * buckets["train_step"] / wall_s if wall_s > 0 else 0.0
        return {
            "wall_s": round(wall_s, 6),
            "goodput_pct": round(goodput_pct, 3),
            "buckets": {bucket: round(seconds, 6) for bucket, seconds in buckets.items()},
        }


# ------------------------------------------------------------------ sink analysis
# Offline replay of one or more JSONL sink files into per-rank goodput summaries
# (the `analyze_telemetry` CLI and cross-rank aggregation path).


def _iter_sink_events(path: Path) -> Iterable[dict]:
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                yield json.loads(line)
            except json.JSONDecodeError:
                continue  # a torn tail line from a killed run must not sink the analysis


def summarize_sink(path: Union[str, Path]) -> dict:
    """Summarize a telemetry sink — a single `telemetry_rank_N.jsonl` file or the
    folder holding them — into per-rank goodput summaries.

    Returns {"ranks": {rank: summary}, "combined": summary-averaged-over-ranks}.
    """
    path = Path(path)
    files = sorted(path.glob("telemetry_rank_*.jsonl")) if path.is_dir() else [path]
    files = [file for file in files if file.exists()]

    ranks: dict[int, dict] = {}
    for file in files:
        ledger = GoodputLedger()
        rank = 0
        t_min = t_max = None
        for event in _iter_sink_events(file):
            rank = int(event.get("rank", rank))
            if event.get("event") == "span":
                ledger.add_record(
                    SpanRecord(
                        name=event.get("name", "other"),
                        ts=float(event.get("ts", 0.0)),
                        dur_s=float(event.get("dur_s", 0.0)),
                        self_s=float(event.get("self_s", 0.0)),
                        thread=event.get("thread", "?"),
                        timeline=bool(event.get("timeline", False)),
                    )
                )
                t0 = float(event.get("ts", 0.0))
                t1 = t0 + float(event.get("dur_s", 0.0))
                t_min = t0 if t_min is None else min(t_min, t0)
                t_max = t1 if t_max is None else max(t_max, t1)
            elif event.get("event") == "run_summary" and "wall_s" in event:
                # prefer the run's own wall clock when the sink recorded one
                t_min, t_max = 0.0, float(event["wall_s"])
        wall_s = (t_max - t_min) if (t_min is not None and t_max is not None) else 0.0
        ranks[rank] = ledger.summary(wall_s=wall_s)

    if not ranks:
        # an empty/missing sink (run died before the first flush) analyzes to a
        # clean zero summary, not a crash — the CLIs print "no records" tables
        empty = GoodputLedger().summary(wall_s=0.0)
        return {"ranks": {}, "combined": empty}

    n = len(ranks)
    combined = {
        "wall_s": round(sum(s["wall_s"] for s in ranks.values()) / n, 6),
        "goodput_pct": round(sum(s["goodput_pct"] for s in ranks.values()) / n, 3),
        "buckets": {
            bucket: round(sum(s["buckets"][bucket] for s in ranks.values()) / n, 6)
            for bucket in BUCKETS
        },
    }
    return {"ranks": ranks, "combined": combined}


def straggler_summary(summary: dict) -> dict:
    """Cross-rank straggler attribution over a `summarize_sink` result (PR 13):
    per goodput bucket, name the slowest rank and how far it sits above the
    cross-rank median — a data_stall bucket where rank 3 spends 4x the median
    IS the straggler the ROADMAP's multi-host rounds need named.

    Returns {bucket: {"slowest_rank", "seconds", "median_s", "ratio_vs_median"}}
    for buckets where any rank recorded time. With fewer than two ranks there
    is no peer to lag behind, so the answer is empty — not a table of every
    bucket "straggling" behind itself at ratio 1.0."""
    ranks = summary.get("ranks") or {}
    if len(ranks) < 2:
        return {}
    out: dict[str, dict] = {}
    for bucket in BUCKETS:
        per_rank = {
            rank: float(s["buckets"].get(bucket, 0.0)) for rank, s in ranks.items()
        }
        worst_rank = max(per_rank, key=per_rank.get)
        worst = per_rank[worst_rank]
        if worst <= 0.0:
            continue
        values = sorted(per_rank.values())
        n = len(values)
        median = (
            values[n // 2] if n % 2 else 0.5 * (values[n // 2 - 1] + values[n // 2])
        )
        out[bucket] = {
            "slowest_rank": worst_rank,
            "seconds": round(worst, 6),
            "median_s": round(median, 6),
            "ratio_vs_median": round(worst / median, 3) if median > 0 else None,
        }
    return out


def format_straggler_table(stragglers: dict) -> str:
    if not stragglers:
        return "no per-rank bucket time recorded"
    lines = [f"{'bucket':<20} {'slowest':>8} {'seconds':>11} {'median':>11} {'x median':>9}"]
    for bucket, row in stragglers.items():
        ratio = f"{row['ratio_vs_median']:.2f}" if row["ratio_vs_median"] is not None else "-"
        lines.append(
            f"{bucket:<20} {('rank ' + str(row['slowest_rank'])):>8} "
            f"{row['seconds']:>10.3f}s {row['median_s']:>10.3f}s {ratio:>9}"
        )
    return "\n".join(lines)


def format_goodput_table(summary: dict) -> str:
    """Render a summarize_sink() result as an aligned text table."""
    if not summary.get("ranks"):
        return "no telemetry span records found"
    lines = []
    header = f"{'bucket':<20}" + "".join(f"rank {r:>2}      " for r in sorted(summary["ranks"]))
    lines.append(header.rstrip())
    for bucket in BUCKETS:
        row = f"{bucket:<20}"
        for rank in sorted(summary["ranks"]):
            row += f"{summary['ranks'][rank]['buckets'][bucket]:>10.3f} s "
        lines.append(row.rstrip())
    row = f"{'wall':<20}"
    for rank in sorted(summary["ranks"]):
        row += f"{summary['ranks'][rank]['wall_s']:>10.3f} s "
    lines.append(row.rstrip())
    row = f"{'goodput':<20}"
    for rank in sorted(summary["ranks"]):
        row += f"{summary['ranks'][rank]['goodput_pct']:>10.2f} % "
    lines.append(row.rstrip())
    lines.append(f"combined goodput: {summary['combined']['goodput_pct']:.2f} %")
    return "\n".join(lines)
