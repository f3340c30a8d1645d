"""Resilience event stream: every recovery-path action (anomaly, retry, preempt,
rollback) is counted in-process AND emitted to the telemetry sink.

The in-process counters exist so callers that need a *synchronous* answer to
"did anything degrade this window?" — the chaos and elastic tests — don't
have to tail and parse the JSONL sink. Counters are keyed by the
event's first path segment (``anomaly/nonfinite`` counts under ``anomaly``),
matching the goodput ledger's bucket convention.
"""

from __future__ import annotations

import threading

from modalities_tpu.telemetry import get_active_telemetry

_lock = threading.Lock()
_counts: dict[str, int] = {}


def record_event(name: str, **payload) -> None:
    """Count the event and emit it to the active telemetry sink (no-op sink when
    telemetry is disabled — the counter still advances)."""
    group = name.split("/", 1)[0]
    with _lock:
        _counts[group] = _counts.get(group, 0) + 1
    get_active_telemetry().emit_event(name, payload)


def snapshot_counts() -> dict[str, int]:
    with _lock:
        return dict(_counts)


def counts_since(snapshot: dict[str, int]) -> dict[str, int]:
    """Per-group event counts accumulated since `snapshot` (zero entries dropped)."""
    with _lock:
        current = dict(_counts)
    delta = {k: v - snapshot.get(k, 0) for k, v in current.items()}
    return {k: v for k, v in delta.items() if v > 0}


def reset_counts() -> None:
    """Test isolation hook."""
    with _lock:
        _counts.clear()
