"""Every backend compile of this process, with its time on the host's clock, so that a
run can show that nothing compiled inside its measured window. Copied from
`chip_smoke.CompileLog` (PR 21), which stays the program's."""

from __future__ import annotations

import time


class CompileLog:
    def __init__(self):
        import jax

        self.compiles: list[dict] = []  # {"function", "seconds", "cache_hit", "at"}
        self._hit = False
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **kwargs) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self._hit = True  # raised inside the compile whose duration comes next

    def _on_duration(self, event: str, seconds: float, **kwargs) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles.append({"function": str(kwargs.get("fun_name", "?")), "seconds": seconds,
                                  "cache_hit": self._hit, "at": time.perf_counter()})
            self._hit = False

    def between(self, start: float, end: float) -> list[dict]:
        """Compiles that ended inside [start, end] on `time.perf_counter()`."""
        return [c for c in self.compiles if start <= c["at"] <= end]

    def summary(self) -> dict:
        return {"count": len(self.compiles), "cache_hits": sum(c["cache_hit"] for c in self.compiles),
                "seconds": sum(c["seconds"] for c in self.compiles)}
