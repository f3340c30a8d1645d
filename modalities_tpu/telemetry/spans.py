"""Host-side span recording: the process's own timeline, and the event source for the
goodput ledger and the sink.

A span is `with span("checkpoint_save"): ...` round a host phase. Each finished span
is one `SpanRecord`: when it started (epoch `ts` for the sink, `t0` on
`time.perf_counter()` for everything that does arithmetic), how long it took, its
EXCLUSIVE time (duration minus enclosed child spans, tracked per thread across all
recorders), the span that enclosed it and the train step in flight when it opened. A
span stream can so be bucketed into wall-time accounting without interval arithmetic:
every second of a thread's timeline lands in exactly one span's exclusive time.

Every record goes to `PROCESS_LOG`, a bounded log the process keeps from
`modalities_tpu.IMPORTED_AT` on, whoever opened the span: a `Telemetry` instance's
recorder, or `PROCESS_RECORDER` while no instance is active (the free `span()` of the
package routes there: the whole set-up of a run happens before its `Telemetry` is
active). The log outlives every instance, so a reader that comes after the trainer is
gone (the benchmark's `readers/program_spans.py`) still has the run's timeline; an
instance that becomes active claims what no instance had accounted for yet.

Every span doubles as a `jax.profiler.TraceAnnotation`, so host phases appear by
name on the host rows of an XPlane/Perfetto trace next to the device streams; and
`step_trace_annotation(step_id)` wraps a train-step dispatch in
`jax.profiler.StepTraceAnnotation` so device work is step-aligned in the trace
viewer. Both degrade to no-ops when jax (or its profiler) is unavailable.

Threading: spans may be opened from any thread (the serving engine's scheduler and its
HTTP handlers do). Only spans from a recorder's designated *timeline thread* (the step
loop; for `PROCESS_RECORDER` the thread that imported the package) carry
`timeline=True`; the goodput ledger ignores the rest, because background-thread work
overlaps the main timeline and would double-count wall seconds.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from dataclasses import dataclass
from typing import Callable, Optional

from modalities_tpu import IMPORTED_AT

# A benchmark window of 150 steps is about 750 timeline spans, a set-up under 40:
# ten such runs fit. A record is about 200 bytes, so the full log holds under 2 MB.
LOG_CAPACITY = 8192


@dataclass
class SpanRecord:
    name: str
    ts: float  # epoch seconds at span start (the sink's clock)
    dur_s: float  # wall duration of the span
    self_s: float  # duration minus enclosed child spans (exclusive time)
    thread: str
    timeline: bool  # True when recorded on the designated step-loop thread
    t0: float = 0.0  # `time.perf_counter()` at span start: the clock of `SpanLog.origin`
    parent: Optional[str] = None  # name of the span that enclosed this one on its thread
    step: Optional[int] = None  # train step (or scheduler round) in flight when it opened


class SpanLog:
    """The finished spans of this process, oldest dropped first beyond `capacity`.
    `origin` is where its clock starts. `claim()` hands an instance that becomes
    active the stretch of the timeline no instance has accounted for: since when,
    and the spans that no instance was handed as they finished."""

    def __init__(self, origin: float, capacity: int = LOG_CAPACITY):
        self.origin = origin
        self.records: deque[SpanRecord] = deque(maxlen=capacity)
        self._unclaimed: deque[SpanRecord] = deque(maxlen=capacity)
        self._unclaimed_since = origin

    def add(self, record: SpanRecord, claimed: bool) -> None:
        self.records.append(record)  # deque.append is atomic: spans finish on any thread
        if not claimed:
            self._unclaimed.append(record)

    def claim(self) -> tuple[float, list[SpanRecord]]:
        taken = []
        while self._unclaimed:
            taken.append(self._unclaimed.popleft())
        return self._unclaimed_since, taken

    def release(self) -> None:
        """The active instance stepped down: what follows is unaccounted for again."""
        self._unclaimed_since = time.perf_counter()

    def split(self, start: float, end: float) -> dict[str, float]:
        """[start, end] of the timeline thread by the name of the outermost span that
        held each second, and under `unspanned` what no span held."""
        out: dict[str, float] = {}
        for record in list(self.records):
            if record.timeline and record.parent is None:
                held = min(end, record.t0 + record.dur_s) - max(start, record.t0)
                if held > 0:
                    out[record.name] = out.get(record.name, 0.0) + held
        out["unspanned"] = max(0.0, end - start - sum(out.values()))
        return out


PROCESS_LOG = SpanLog(origin=IMPORTED_AT)
_TLS = threading.local()  # one stack of open spans a thread, whichever recorder opened them


def _resolve_trace_annotation():
    try:
        from jax.profiler import TraceAnnotation

        return TraceAnnotation
    except Exception:
        return None


class _NullContext:
    """Shared allocation-free no-op context manager (the disabled fast path)."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc_val, exc_tb):
        return False


NULL_CONTEXT = _NullContext()


class _Span:
    __slots__ = ("_recorder", "name", "_ts", "_t0", "_children_s", "_annotation", "_parent", "_step")

    def __init__(self, recorder: "SpanRecorder", name: str):
        self._recorder = recorder
        self.name = name
        self._annotation = None

    def __enter__(self) -> "_Span":
        recorder = self._recorder
        stack = getattr(_TLS, "stack", None)
        if stack is None:
            stack = _TLS.stack = []
        self._parent = stack[-1].name if stack else None
        self._step = recorder.step
        stack.append(self)
        self._children_s = 0.0
        if recorder._trace_annotation is not None:
            try:
                self._annotation = recorder._trace_annotation(self.name)
                self._annotation.__enter__()
            except Exception:  # a broken profiler must never take the span down
                self._annotation = None
        self._ts = time.time()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc_val, exc_tb) -> bool:
        dur_s = time.perf_counter() - self._t0
        if self._annotation is not None:
            self._annotation.__exit__(exc_type, exc_val, exc_tb)
        recorder = self._recorder
        stack = _TLS.stack
        stack.pop()
        if stack:
            stack[-1]._children_s += dur_s
        record = SpanRecord(
            name=self.name,
            ts=self._ts,
            dur_s=dur_s,
            self_s=max(0.0, dur_s - self._children_s),
            thread=threading.current_thread().name,
            timeline=threading.get_ident() == recorder._timeline_ident,
            t0=self._t0,
            parent=self._parent,
            step=self._step,
        )
        PROCESS_LOG.add(record, claimed=recorder._on_record is not None)
        if recorder._on_record is not None:
            recorder._on_record(record)
        return False


class SpanRecorder:
    """Thread-safe span source. Every finished span goes to `PROCESS_LOG`, and to
    `on_record(SpanRecord)` where one is given (on the exiting span's own thread:
    consumers must be thread-safe). `step` is what its owner last announced as in
    flight; spans carry it."""

    def __init__(
        self,
        on_record: Optional[Callable[[SpanRecord], None]] = None,
        use_jax_annotations: bool = True,
    ):
        self._on_record = on_record
        self.step: Optional[int] = None
        self._timeline_ident = threading.get_ident()
        self._trace_annotation = _resolve_trace_annotation() if use_jax_annotations else None

    def set_timeline_thread(self, ident: Optional[int] = None) -> None:
        """Designate the thread whose spans carry `timeline=True` (default: the
        thread that constructed the recorder)."""
        self._timeline_ident = threading.get_ident() if ident is None else ident

    def span(self, name: str) -> _Span:
        return _Span(self, name)


# what the package's free `span()` records with while no `Telemetry` is active
PROCESS_RECORDER = SpanRecorder()


def step_trace_annotation(step_id: int, name: str = "train_step"):
    """`jax.profiler.StepTraceAnnotation` for one train-step dispatch: device
    traces group by step id in TensorBoard/Perfetto. No-op without jax."""
    try:
        from jax.profiler import StepTraceAnnotation
    except Exception:
        return NULL_CONTEXT
    return StepTraceAnnotation(name, step_num=step_id)
