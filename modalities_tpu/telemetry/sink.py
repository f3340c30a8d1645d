"""Per-rank JSONL telemetry sink.

Every event is written AND flushed immediately — the whole point is that a run
killed by rc=124 still leaves a complete record up to the kill (VERDICT r5).
Rank 0 additionally writes an aggregate `goodput_summary.json` at close;
cross-rank offline aggregation is `goodput.summarize_sink(folder)` / the
`analyze_telemetry` CLI, which read all `telemetry_rank_*.jsonl` siblings.
"""

from __future__ import annotations

import json
import threading
import time
from pathlib import Path
from typing import Optional

from modalities_tpu.telemetry.spans import PROCESS_LOG, SpanRecord


class TelemetrySink:
    def __init__(self, output_folder_path: Path, global_rank: int = 0):
        self.global_rank = global_rank
        self.folder = Path(output_folder_path)
        self.folder.mkdir(parents=True, exist_ok=True)
        self.path = self.folder / f"telemetry_rank_{global_rank}.jsonl"
        self._lock = threading.Lock()
        self._file = open(self.path, "w")

    def emit(self, event: dict) -> None:
        line = json.dumps({"rank": self.global_rank, **event})
        with self._lock:
            if self._file.closed:
                return  # a straggler background span after close is not an error
            self._file.write(line + "\n")
            self._file.flush()

    def emit_span(self, record: SpanRecord) -> None:
        self.emit(
            {
                "event": "span",
                "name": record.name,
                "ts": round(record.ts, 6),
                # the same start in seconds since the process log's origin: the clock of the
                # `compile` events' `end_s` and of the run summary's `wall_s`
                "start_s": round(record.t0 - PROCESS_LOG.origin, 6),
                "dur_s": round(record.dur_s, 6),
                "self_s": round(record.self_s, 6),
                "thread": record.thread,
                "timeline": record.timeline,
                "parent": record.parent,
                "step": record.step,
            }
        )

    def close(self, run_summary: Optional[dict] = None) -> None:
        if run_summary is not None:
            self.emit({"event": "run_summary", "wall_time": time.time(), **run_summary})
            if self.global_rank == 0:
                summary_path = self.folder / "goodput_summary.json"
                with open(summary_path, "w") as f:
                    json.dump(run_summary, f, indent=1)
        with self._lock:
            if not self._file.closed:
                self._file.close()
