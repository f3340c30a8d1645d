"""Every backend compile of this process, as JAX's own monitoring reports it: the
jitted function's name, the seconds it took, and whether the persistent compilation
cache answered it.

`PROCESS_COMPILES` is the process's own record, kept from the first import of the
telemetry package on and bounded like the span log beside it (`spans.PROCESS_LOG`):
each compile with its stamp on `time.perf_counter()`, so that a reader can lay the
compiles over the spans (which span of the set-up held the train step's compile, and
whether the cache answered). The active `Telemetry` is forwarded each one
(`forward_to`) and turns it into the counters `compile_total` /
`compile_seconds_total` and one `compile` event on the sink (with the step or scheduler
round it fell in): how an operator sees a serving warm-up that left a second prefill
shape uncompiled, or a train step that recompiled. `CompileLog` is a listener of one's
own for a stretch of the process: `chip_smoke.py` keeps one per phase and prints its
summary.
"""

from __future__ import annotations

import time
from collections import deque
from typing import Callable, NamedTuple, Optional

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"
COMPILES_CAPACITY = 4096  # a run compiles tens of functions, a server a few per shape


def _listen(on_compile: Callable[[str, float, bool], None]) -> Callable[[], None]:
    """Call `on_compile(function, seconds, from the cache)` for every backend compile
    from now on; returns what stops it (JAX keeps a listener until it is taken away)."""
    import jax

    hit = False

    def on_event(event: str, **kwargs) -> None:
        nonlocal hit
        if event == CACHE_HIT:
            hit = True  # raised inside the compile whose duration comes next

    def on_duration(event: str, seconds: float, **kwargs) -> None:
        nonlocal hit
        if event == BACKEND_COMPILE:
            from_cache, hit = hit, False
            on_compile(str(kwargs.get("fun_name", "?")), seconds, from_cache)

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)

    def stop() -> None:
        for unregister, callback in ((jax.monitoring.unregister_event_duration_listener, on_duration),
                                     (jax.monitoring.unregister_event_listener, on_event)):
            try:
                unregister(callback)
            except (AssertionError, ValueError):  # not registered any more
                pass

    return stop


class CompileLog:
    def __init__(self, on_compile: Optional[Callable[[str, float, bool], None]] = None):
        self.compiles: list[tuple[str, float, bool]] = []  # (function, seconds, from the cache)
        self._on_compile = on_compile
        self._stop = _listen(self._record)

    def close(self) -> None:
        """Stop listening (idempotent)."""
        self._stop()

    def _record(self, function: str, seconds: float, cache_hit: bool) -> None:
        self.compiles.append((function, seconds, cache_hit))
        if self._on_compile is not None:
            self._on_compile(function, seconds, cache_hit)

    def summary(self, *names: str) -> dict:
        """Per name in `names` (a substring of the jitted function's name), and for
        all the rest together: how many compiles, how many of them cache hits, and
        the seconds of the first and of all."""
        groups = {name: [c for c in self.compiles if name in c[0]] for name in names}
        groups["other"] = [c for c in self.compiles if not any(name in c[0] for name in names)]
        return {
            name: {
                "count": len(group),
                "cache_hits": sum(hit for _, _, hit in group),
                "first_s": round(group[0][1], 2) if group else None,
                "total_s": round(sum(secs for _, secs, _ in group), 2),
            }
            for name, group in groups.items()
        }


class CompileRecord(NamedTuple):
    at: float  # `time.perf_counter()` when the compile (or the cache's load) ended
    function: str
    seconds: float
    cache_hit: bool


PROCESS_COMPILES: deque[CompileRecord] = deque(maxlen=COMPILES_CAPACITY)
_forward: Optional[Callable[[str, float, bool], None]] = None


def forward_to(on_compile: Optional[Callable[[str, float, bool], None]]) -> None:
    """Who, beside the process's record, is told of each compile (the active `Telemetry`)."""
    global _forward
    _forward = on_compile


def _record_for_process(function: str, seconds: float, cache_hit: bool) -> None:
    PROCESS_COMPILES.append(CompileRecord(time.perf_counter(), function, seconds, cache_hit))
    if _forward is not None:
        _forward(function, seconds, cache_hit)


try:
    _listen(_record_for_process)  # for the life of the process
except ImportError:  # no jax: nothing will compile
    pass
