"""In-process prefill+decode pair: two ServingEngines, one scheduler loop.

The pair is the disagg substrate everything in-process rides on — the bitwise
parity oracle and the int8 handoff seam test of `tests/serving/test_disagg.py`. It drives
both engines' `step()` off ONE clock and hands `HandoffRecord`s across by
reference (serialization is the HTTP legs' concern, not a semantic one): a
prefill-tier finish with reason "handoff" becomes an `import_handoff()` on the
decode tier, `arrival_offset_s` stamped at the moment of handoff so the decode
engine's `disagg_handoff_seconds` histogram measures handoff->seeded latency
(pool-full starvation inflates exactly this tail).
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Callable, Optional

from modalities_tpu.serving.engine import ServeResult


@dataclass
class PairResult:
    """One request's merged view: token #1 came off the prefill tier inside
    the handoff, the rest streamed from the decode tier. `tokens` is the
    client-visible stream — bitwise the combined engine's output."""

    rid: int  # prefill-side rid (the pair's handle)
    tokens: list[int] = field(default_factory=list)
    finish_reason: str = ""
    trace_id: str = ""
    prefill: Optional[ServeResult] = None
    decode: Optional[ServeResult] = None  # None when prefill short-circuited

    @property
    def ttft_s(self) -> float:
        """End-to-end TTFT: prefill arrival to first token (prefill tier)."""
        return self.prefill.ttft_s

    @property
    def token_times_s(self) -> list[float]:
        times = list(self.prefill.token_times_s)
        if self.decode is not None:
            times += list(self.decode.token_times_s)
        return times


class DisaggPair:
    """Drive a `role="prefill"` engine and a `role="decode"` engine as one
    serving surface. `submit()` mirrors the combined engine's signature;
    `run()` returns prefill-rid -> PairResult."""

    def __init__(
        self,
        prefill,
        decode,
        *,
        time_fn: Optional[Callable[[], float]] = None,
    ):
        if prefill.role != "prefill" or decode.role != "decode":
            raise ValueError(
                f"DisaggPair needs (prefill, decode) roles, got "
                f"({prefill.role!r}, {decode.role!r})"
            )
        self.prefill = prefill
        self.decode = decode
        self._now = time_fn if time_fn is not None else time.monotonic
        self._handled: set[int] = set()  # prefill rids already harvested
        self._imported: dict[int, int] = {}  # prefill rid -> decode rid
        self.handoff_failures: list[tuple[int, str]] = []  # (prefill rid, reason)

    def submit(self, *args, **kwargs) -> int:
        return self.prefill.submit(*args, **kwargs)

    def _harvest_handoffs(self, t0: float) -> None:
        """Move freshly finished prefill results across the tier boundary."""
        for rid, res in list(self.prefill._results.items()):
            if rid in self._handled:
                continue
            self._handled.add(rid)
            if res.finish_reason != "handoff":
                continue  # eod/budget/error at prefill: terminal, no decode leg
            now = self._now() - t0
            try:
                drid = self.decode.import_handoff(
                    res.handoff,
                    arrival_offset_s=now,
                    trace_id=res.trace_id,
                    trace_hop=res.trace_hop + 1,
                )
            except Exception as exc:  # HandoffRejected: recorded, not fatal
                self.handoff_failures.append((rid, getattr(exc, "reason", "error")))
                continue
            self._imported[rid] = drid

    def _pending(self) -> bool:
        return bool(
            self.prefill._queue
            or self.prefill._active_count()
            or self.decode._queue
            or self.decode._active_count()
        )

    def run(self) -> dict[int, PairResult]:
        t0 = self._now()
        while True:
            did = self.prefill.step(t0)
            self._harvest_handoffs(t0)
            did = self.decode.step(t0) or did
            if not self._pending():
                break
            if not did:
                # nothing running anywhere: the earliest queued arrival is
                # what we're waiting for (same contract as ServingEngine.run)
                heads = [
                    q[0].arrival_offset_s
                    for q in (self.prefill._queue, self.decode._queue)
                    if q
                ]
                if not heads:
                    continue  # import in flight between the two steps
                wait = min(heads) - (self._now() - t0)
                if wait > 0:
                    time.sleep(min(wait, 0.05))
        return self.results()

    def results(self) -> dict[int, PairResult]:
        out: dict[int, PairResult] = {}
        for rid, pres in self.prefill._results.items():
            merged = PairResult(
                rid=rid, tokens=list(pres.tokens),
                finish_reason=pres.finish_reason,
                trace_id=pres.trace_id, prefill=pres,
            )
            drid = self._imported.get(rid)
            if drid is not None and drid in self.decode._results:
                dres = self.decode._results[drid]
                merged.decode = dres
                merged.tokens += list(dres.tokens)
                merged.finish_reason = dres.finish_reason
            out[rid] = merged
        return out
