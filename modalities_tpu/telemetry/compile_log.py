"""Every backend compile of this process, as JAX's own monitoring reports it: the
jitted function's name, the seconds it took, and whether the persistent compilation
cache answered it. `Telemetry` keeps one for the active instance and turns each compile
into the counters `compile_total` / `compile_seconds_total` and one `compile` event on
the sink (with the step or scheduler round it fell in): how an operator sees a serving
warm-up that left a second prefill shape uncompiled, or a train step that recompiled.
`chip_smoke.py` keeps one per phase and prints its summary.
"""

from __future__ import annotations

from typing import Callable, Optional

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"


class CompileLog:
    def __init__(self, on_compile: Optional[Callable[[str, float, bool], None]] = None):
        import jax

        self.compiles: list[tuple[str, float, bool]] = []  # (function, seconds, from the cache)
        self._on_compile = on_compile
        self._hit = False
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def close(self) -> None:
        """Stop listening (idempotent): JAX keeps a listener until it is taken away."""
        import jax

        for unregister, callback in ((jax.monitoring.unregister_event_duration_listener, self._on_duration),
                                     (jax.monitoring.unregister_event_listener, self._on_event)):
            try:
                unregister(callback)
            except (AssertionError, ValueError):  # not registered any more
                pass

    def _on_event(self, event: str, **kwargs) -> None:
        if event == CACHE_HIT:
            self._hit = True  # raised inside the compile whose duration comes next

    def _on_duration(self, event: str, seconds: float, **kwargs) -> None:
        if event == BACKEND_COMPILE:
            function, hit = str(kwargs.get("fun_name", "?")), self._hit
            self._hit = False
            self.compiles.append((function, seconds, hit))
            if self._on_compile is not None:
                self._on_compile(function, seconds, hit)

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
