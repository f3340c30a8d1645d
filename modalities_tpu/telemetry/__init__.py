"""Telemetry subsystem: the process's own timeline, goodput ledger, hang watchdog, sink.

The process keeps one record whoever is listening: `spans.PROCESS_LOG` (every finished
span, from the package's import on), `compile_log.PROCESS_COMPILES` (every backend
compile, from this package's import on) and `collective_plan.PROCESS_PLANS` (the
collectives of each sharded program the trainer's preflight compiled), all bounded and
all on `time.perf_counter()`. One `Telemetry` object per run composes the rest:

- `spans.SpanRecorder` — host phases as spans doubling as profiler annotations
- `goodput.GoodputLedger` — every wall second classified into a bucket
- `watchdog.Watchdog` — per-step heartbeat; wedged step -> crash artifact
- `sink.TelemetrySink` — per-rank always-flushed JSONL event stream

Deep call sites (the entry points' set-up, checkpointing, the evaluator, the serving
engine) use the module-level `span("name")` free function, which routes to the
process-global active telemetry, or, while none is active, to the process's own
recorder: the span still lands in the log, and the next instance to become active
takes it into its ledger and its sink (`set_active_telemetry`), with its wall clock
set back to where the unaccounted stretch began. `Main` constructs/activates the
instance (it is a registry component, on by default); an instance built with
`enabled=False` is an allocation-free no-op, so library code never guards its
telemetry calls.
"""

from __future__ import annotations

import json
import os
import statistics
import tempfile
import time
from pathlib import Path
from typing import Callable, Optional, Union

from modalities_tpu.telemetry import collective_plan, compile_log
from modalities_tpu.telemetry.goodput import BUCKETS, GoodputLedger
from modalities_tpu.telemetry.metrics import MetricsRegistry
from modalities_tpu.telemetry.sink import TelemetrySink
from modalities_tpu.telemetry.spans import (
    NULL_CONTEXT,
    PROCESS_LOG,
    PROCESS_RECORDER,
    SpanRecord,
    SpanRecorder,
    step_trace_annotation,
)
from modalities_tpu.telemetry.watchdog import Watchdog
from modalities_tpu.utils.logging import get_logger

logger = get_logger(__name__)


def _default_rank() -> int:
    try:
        return int(os.environ["RANK"])
    except (KeyError, ValueError):
        pass
    try:
        import jax

        return jax.process_index()
    except Exception:
        return 0


class Telemetry:
    """Facade over recorder + ledger + watchdog + sink.

    `enabled=False` is the fast path: `span()`/`step_annotation()` return a shared
    no-op context manager and every other method returns immediately — safe to
    call unconditionally from hot loops.
    """

    def __init__(
        self,
        enabled: bool = True,
        output_folder_path: Optional[Union[str, Path]] = None,
        watchdog_deadline_s: float = 1800.0,
        watchdog_first_step_factor: float = 4.0,
        use_jax_annotations: bool = True,
        global_rank: Optional[int] = None,
        anomaly_zscore: float = 6.0,
        anomaly_window: int = 64,
        slo: Optional[dict] = None,
    ):
        self.enabled = enabled
        self.watchdog_deadline_s = float(watchdog_deadline_s)
        self.watchdog_first_step_factor = float(watchdog_first_step_factor)
        self._sink: Optional[TelemetrySink] = None
        self._watchdog: Optional[Watchdog] = None
        self._pending_state_providers: list[Callable[[], dict]] = []
        self._folder: Optional[Path] = None
        # one scrape surface per process: the serving engine, HTTP front end, and
        # training publish path all register into this registry (PR 10); present
        # even when disabled so instrumented code never guards its metric calls
        self.metrics = MetricsRegistry()
        # step-time / goodput-bucket anomaly detection (PR 13): lazily built
        # robust-z detectors; inert when disabled
        self.anomaly_zscore = float(anomaly_zscore)
        self.anomaly_window = int(anomaly_window)
        self._step_time_detector = None
        self._bucket_detectors: dict[str, object] = {}
        # `_in_flight` is the step or scheduler round the watchdog calls announce: spans
        # carry it, and so do the compiles forwarded while this instance is the active one
        self._in_flight: Optional[int] = None
        # spans taken over from the process log (`_claim_process_log`) before the sink opened
        self._claimed_before_sink: list[SpanRecord] = []
        self._emitted_once: set[tuple] = set()
        self._last_bucket_seconds: dict[str, float] = {}
        # optional SLO engine (PR 15): judged objectives over self.metrics;
        # None (the default) keeps every publish path on the pre-SLO behavior
        self.slo_engine = None
        if not enabled:
            self.global_rank = 0
            self._recorder = None
            self.ledger = GoodputLedger()  # inert but present: summary() stays callable
            return
        self.global_rank = _default_rank() if global_rank is None else global_rank
        self.ledger = GoodputLedger()
        self._recorder = SpanRecorder(on_record=self._on_record, use_jax_annotations=use_jax_annotations)
        if output_folder_path is not None:
            self.set_output_folder(output_folder_path)
        if slo:
            # built but NOT started: the trainer samples it at each interval
            # publish, so training verdicts stay deterministic per interval
            # (serving paths start their own sampler threads instead)
            from modalities_tpu.telemetry.slo import SLOEngine, load_slo_spec

            objectives, options = load_slo_spec(slo)
            self.slo_engine = SLOEngine(objectives, self.metrics, **options)

    # ------------------------------------------------------------------ spans

    def span(self, name: str):
        if not self.enabled:
            return NULL_CONTEXT
        return self._recorder.span(name)

    def step_annotation(self, step_id: int):
        if not self.enabled:
            return NULL_CONTEXT
        return step_trace_annotation(step_id)

    def set_timeline_thread(self) -> None:
        """Mark the CALLING thread as the step-loop timeline (ledger source)."""
        if self.enabled:
            self._recorder.set_timeline_thread()

    def _on_record(self, record) -> None:
        self.ledger.add_record(record)
        if self._sink is not None:
            self._sink.emit_span(record)

    def _claim_process_log(self) -> None:
        """This instance became the active one: its wall clock starts where the stretch
        no instance accounted for began (the process log's origin for the first), and the
        spans recorded since with nobody to hand them to (the entry point's
        `backend_start` and `build_components`) count in its ledger and go to its sink,
        now or when it opens."""
        if not self.enabled:
            return
        since, records = PROCESS_LOG.claim()
        self.ledger.start(at=min(self.ledger.origin, since))
        for record in records:
            self._on_record(record)
            if self._sink is None:
                self._claimed_before_sink.append(record)

    # ------------------------------------------------------------------- sink

    def set_output_folder(self, output_folder_path: Union[str, Path]) -> None:
        """Open the JSONL sink (idempotent; Main calls this once the experiment
        folder is known). Watchdog artifacts land in the same folder."""
        if not self.enabled or self._sink is not None:
            return
        self._folder = Path(output_folder_path)
        self._sink = TelemetrySink(self._folder, global_rank=self.global_rank)
        for record in self._claimed_before_sink:
            self._sink.emit_span(record)
        self._claimed_before_sink.clear()
        if self._watchdog is not None:
            self._watchdog.artifact_dir = self._folder

    @property
    def sink_path(self) -> Optional[Path]:
        return self._sink.path if self._sink is not None else None

    def emit_event(self, name: str, payload: Optional[dict] = None) -> None:
        """Emit a named point event (anomaly/*, preempt/*, ckpt_retry/*, ...) to
        the JSONL sink. No-op when disabled or before the sink is open."""
        if not self.enabled or self._sink is None:
            return
        self._sink.emit({"event": "resilience", "name": name, **(payload or {})})

    def emit_event_once(self, name: str, payload: dict) -> None:
        """`emit_event` for a fact that code finds while it is traced (a kernel's tile
        plan for a shape): on this instance's sink once per distinct payload, however
        often the shape is traced, and never per step."""
        key = (name, json.dumps(payload, sort_keys=True, default=str))  # a payload may nest (a plan's rows)
        if self._sink is None or key in self._emitted_once:
            return
        self._emitted_once.add(key)
        self.emit_event(name, payload)

    def emit_serve_trace(self, record: dict) -> None:
        """Write one per-request serving lifecycle record (`event:
        "serve_request"`) to the JSONL sink — the `analyze_serve` CLI's input.
        No-op when disabled or before the sink is open."""
        if not self.enabled or self._sink is None:
            return
        self._sink.emit({"event": "serve_request", **record})

    # --------------------------------------------------------------- watchdog

    def _ensure_watchdog(self) -> Optional[Watchdog]:
        if not self.enabled or self.watchdog_deadline_s <= 0:
            return None
        if self._watchdog is None:
            artifact_dir = self._folder or Path(tempfile.gettempdir()) / "modalities_tpu_telemetry"
            self._watchdog = Watchdog(
                deadline_s=self.watchdog_deadline_s,
                artifact_dir=artifact_dir,
                global_rank=self.global_rank,
                # a hang artifact carries the live scrape surface too (PR 13):
                # counters to correlate the wedged step against
                metrics_provider=self.metrics.snapshot,
            )
            for provider in self._pending_state_providers:
                self._watchdog.register_state_provider(provider)
            self._pending_state_providers.clear()
            self._watchdog.start()
        return self._watchdog

    def _announce(self, step_id: int) -> None:
        self._in_flight = step_id
        if self._recorder is not None:
            self._recorder.step = step_id

    def arm_watchdog(self, step_id: int, first_step: bool = False) -> None:
        self._announce(step_id)
        watchdog = self._ensure_watchdog()
        if watchdog is None:
            return
        deadline_s = self.watchdog_deadline_s * (self.watchdog_first_step_factor if first_step else 1.0)
        watchdog.arm(step_id, deadline_s=deadline_s)

    def beat_watchdog(self, step_id: int) -> None:
        self._announce(step_id + 1)  # `step_id` is done: what opens or compiles now belongs to the next
        if self._watchdog is not None:
            self._watchdog.beat(step_id)

    def disarm_watchdog(self) -> None:
        if self._watchdog is not None:
            self._watchdog.disarm()

    def register_watchdog_state_provider(self, provider: Callable[[], dict]) -> None:
        if not self.enabled:
            return
        if self._watchdog is not None:
            self._watchdog.register_state_provider(provider)
        else:
            self._pending_state_providers.append(provider)

    @property
    def watchdog_artifacts(self) -> list[Path]:
        return list(self._watchdog.fired_artifacts) if self._watchdog is not None else []

    # --------------------------------------------------------------- compiles

    def _on_compile(self, function: str, seconds: float, cache_hit: bool) -> None:
        """One backend compile, as the process's record forwards it to the active instance."""
        hit = "true" if cache_hit else "false"
        self.metrics.counter(
            "compile_total", "Backend compiles of this process, by whether the persistent cache answered"
        ).inc(cache_hit=hit)
        self.metrics.counter(
            "compile_seconds_total", "Seconds spent in backend compiles (a cache hit's are its load time)"
        ).inc(seconds, cache_hit=hit)
        if self._sink is not None:
            self._sink.emit({"event": "compile", "function": function, "seconds": round(seconds, 6),
                             "cache_hit": cache_hit, "step": self._in_flight,
                             "end_s": round(time.perf_counter() - PROCESS_LOG.origin, 6)})

    def _on_collective_plan(self, plan: dict) -> None:
        """The collectives of one compiled program, as the process's record forwards them to the active
        instance (`collective_plan.record_from_compiled`): a step's bytes and count by mesh axis and kind
        on the scrape surface, and one `collective_plan` event on the sink a distinct plan."""
        bytes_gauge = self.metrics.gauge(
            "train_collective_bytes", "Bytes a step of the compiled train step's collectives, by mesh axis and kind")
        count_gauge = self.metrics.gauge(
            "train_collective_count", "Collectives a step of the compiled train step, by mesh axis and kind")
        for key, total in plan["totals"].items():
            axis, kind = key.split("|")
            bytes_gauge.set(total["bytes_a_run"], axis=axis, kind=kind)
            count_gauge.set(total["count_a_run"], axis=axis, kind=kind)
        self.emit_event_once("collective_plan", collective_plan.event_payload(plan))

    # ---------------------------------------------------------------- goodput

    def goodput_summary(self) -> dict:
        return self.ledger.summary()

    def throughput_metrics(self) -> dict[str, float]:
        """Cumulative goodput metrics for the interval publish: goodput % plus
        per-bucket seconds. Empty when disabled (publishers skip cleanly)."""
        if not self.enabled:
            return {}
        summary = self.ledger.summary()
        metrics = {"goodput [%]": summary["goodput_pct"]}
        for bucket in BUCKETS:
            metrics[f"goodput/{bucket} [s]"] = summary["buckets"][bucket]
        # same numbers onto the Prometheus scrape surface: one job covers both
        # training and serving workloads (PR 10)
        self.metrics.gauge(
            "training_goodput_ratio", "Fraction of wall time spent in train_step"
        ).set(summary["goodput_pct"] / 100.0)
        bucket_gauge = self.metrics.gauge(
            "training_goodput_bucket_seconds",
            "Cumulative wall seconds attributed to each goodput bucket",
        )
        for bucket in BUCKETS:
            bucket_gauge.set(summary["buckets"][bucket], bucket=bucket)
        self._observe_bucket_deltas(summary["buckets"])
        return metrics

    def publish_mfu_waterfall(
        self,
        mfu_achieved: float,
        collective_frac: Optional[float] = None,
        dcn_collective_frac: Optional[float] = None,
    ) -> Optional[dict]:
        """Decompose the cumulative wall-clock MFU against the goodput ledger
        (telemetry/waterfall.py) and publish: `training_mfu_achieved` plus one
        `training_mfu_waterfall_deduction{cause}` gauge per named cause on the
        scrape surface, and an `mfu_waterfall` record on the sink for
        `data analyze_telemetry`. Returns the waterfall (None when disabled)."""
        if not self.enabled:
            return None
        from modalities_tpu.telemetry.waterfall import DEDUCTIONS, mfu_waterfall

        summary = self.ledger.summary()
        waterfall = mfu_waterfall(
            mfu_achieved,
            wall_s=summary["wall_s"],
            buckets=summary["buckets"],
            collective_frac=collective_frac,
            dcn_collective_frac=dcn_collective_frac,
        )
        self.metrics.gauge(
            "training_mfu_achieved", "Cumulative wall-clock MFU of the run"
        ).set(waterfall["achieved"])
        deduction_gauge = self.metrics.gauge(
            "training_mfu_waterfall_deduction",
            "MFU lost to each named cause; causes sum exactly to peak - achieved",
        )
        for cause in DEDUCTIONS:
            deduction_gauge.set(waterfall["deductions"][cause], cause=cause)
        if self._sink is not None:
            # full precision on purpose: the deductions sum to gap EXACTLY, and
            # rounding here would break that identity for sink replays
            self._sink.emit({
                "event": "mfu_waterfall",
                "peak": waterfall["peak"],
                "achieved": waterfall["achieved"],
                "gap": waterfall["gap"],
                "deductions": dict(waterfall["deductions"]),
            })
        return waterfall

    # ------------------------------------------------------- anomaly detection

    def _detector(self):
        from modalities_tpu.telemetry.perfscope import AnomalyDetector

        return AnomalyDetector(
            window=self.anomaly_window, zscore_threshold=self.anomaly_zscore
        )

    def observe_step_time(
        self, seconds: float, step_id: Optional[int] = None, window: Optional[tuple[float, float]] = None
    ) -> None:
        """Feed one step's wall time through the rolling robust-z detector
        (PR 13). An anomalous step bumps `training_step_time_anomaly_total`,
        the live z/EWMA land on gauges, and the sink gets an `anomaly/step_time`
        event the analyze CLI can line up against the goodput buckets. `window` is the
        stretch of `time.perf_counter()` the time was taken over (one step, or an
        interval of several): the event then says what the loop's thread was in while
        it lasted, `split_s` by outermost span with `unspanned` for the rest
        (`spans.PROCESS_LOG.split`), so that a step of seconds names its cause."""
        if not self.enabled:
            return
        if self._step_time_detector is None:
            self._step_time_detector = self._detector()
        verdict = self._step_time_detector.observe(seconds)
        z = verdict.zscore if verdict.zscore not in (float("inf"), float("-inf")) else 1e9
        self.metrics.gauge(
            "training_step_time_zscore", "Robust z-score of the latest step's wall time"
        ).set(z)
        self.metrics.gauge(
            "training_step_time_ewma_seconds", "EWMA of per-step wall time"
        ).set(verdict.ewma)
        if verdict.is_anomaly:
            self.metrics.counter(
                "training_step_time_anomaly_total",
                "Steps whose wall time scored over the anomaly z-score threshold",
            ).inc()
            payload = {"step_id": step_id, "seconds": round(seconds, 6),
                       "zscore": round(z, 3), "ewma_s": round(verdict.ewma, 6)}
            if window is not None:
                payload["window_s"] = round(window[1] - window[0], 6)
                payload["split_s"] = {name: round(held, 6) for name, held in PROCESS_LOG.split(*window).items()}
                # a device's steps are alike to a ten-thousandth, so z passes the threshold on a step a
                # millisecond late: the log gets only the steps worth a look, the sink every one
                usual = statistics.median(self._step_time_detector.window)
                if seconds > 2.0 * usual:
                    logger.warning(
                        "step %s took %.3f s, %.1f times the usual %.3f s; where the loop's thread was meanwhile: %s",
                        step_id, seconds, seconds / usual, usual,
                        ", ".join(f"{name} {held:.3f} s" for name, held in sorted(payload["split_s"].items(), key=lambda kv: -kv[1])),
                    )
            self.emit_event("anomaly/step_time", payload)

    def _observe_bucket_deltas(self, bucket_seconds: dict) -> None:
        """Per-publish goodput-bucket deltas through per-bucket detectors: a
        publish interval that suddenly spends 10x its usual data_stall seconds
        scores high on `training_goodput_bucket_zscore{bucket="data_stall"}`."""
        zscore_gauge = self.metrics.gauge(
            "training_goodput_bucket_zscore",
            "Robust z-score of each goodput bucket's seconds over the last publish interval",
        )
        for bucket in BUCKETS:
            total = float(bucket_seconds.get(bucket, 0.0))
            delta = total - self._last_bucket_seconds.get(bucket, 0.0)
            self._last_bucket_seconds[bucket] = total
            detector = self._bucket_detectors.get(bucket)
            if detector is None:
                detector = self._bucket_detectors[bucket] = self._detector()
            verdict = detector.observe(delta)
            z = verdict.zscore if abs(verdict.zscore) != float("inf") else 1e9
            zscore_gauge.set(z, bucket=bucket)
            if verdict.is_anomaly:
                self.emit_event(
                    "anomaly/goodput_bucket",
                    {"bucket": bucket, "delta_s": round(delta, 6), "zscore": round(z, 3)},
                )

    def publish_resource_gauges(
        self,
        hbm_headroom_mb: Optional[float] = None,
        peak_memory_mb: Optional[float] = None,
    ) -> None:
        """Device-memory gauges for the shared scrape surface; the trainer calls
        this from its interval publish with the numbers it already computes."""
        if hbm_headroom_mb is not None:
            self.metrics.gauge(
                "training_hbm_headroom_mbytes", "Min over devices of free HBM (MB)"
            ).set(hbm_headroom_mb)
        if peak_memory_mb is not None:
            self.metrics.gauge(
                "training_peak_memory_mbytes", "Max over devices of peak HBM in use (MB)"
            ).set(peak_memory_mb)

    def publish_memory_timeline(self, sample: dict) -> None:
        """One memscope timeline sample (telemetry/memscope.py) onto the scrape
        surface and the sink: worst-device bytes in use, per-device headroom
        (the SLO floor objective's source), and a `memscope_timeline` sink event
        so headroom objectives replay offline via `data check_slo`."""
        if not self.enabled:
            return
        self.metrics.gauge(
            "training_hbm_bytes_in_use", "Max over devices of HBM bytes in use"
        ).set(sample["bytes_in_use"])
        headroom_gauge = self.metrics.gauge(
            "memscope_device_headroom_bytes",
            "Per-device bytes_limit - bytes_in_use (absent on backends with no limit)",
        )
        for device, headroom in (sample.get("headroom_bytes") or {}).items():
            headroom_gauge.set(headroom, device=device)
        if self._sink is not None:
            self._sink.emit({
                "event": "memscope_timeline",
                "step": sample.get("step"),
                "executable": sample.get("executable"),
                "bytes_in_use": sample["bytes_in_use"],
                "headroom_bytes": dict(sample.get("headroom_bytes") or {}),
            })

    def publish_memscope_report(self, report: dict, executable: str = "train_step") -> None:
        """Static memscope buckets onto the scrape surface:
        `memscope_bucket_bytes{executable,bucket}` — the memory sibling of the
        goodput bucket gauges, closed against memory_analysis() by construction."""
        if not self.enabled:
            return
        bucket_gauge = self.metrics.gauge(
            "memscope_bucket_bytes",
            "Static per-device bytes attributed to each memscope bucket; buckets "
            "sum exactly to the executable's memory_analysis total",
        )
        for bucket, nbytes in (report.get("buckets") or {}).items():
            bucket_gauge.set(nbytes, executable=executable, bucket=bucket)

    # -------------------------------------------------------------- lifecycle

    def close(self) -> None:
        """Stop the watchdog and seal the sink with a run summary. Idempotent;
        safe on the exception path."""
        if self.slo_engine is not None:
            self.slo_engine.stop()
        if self._watchdog is not None:
            self._watchdog.stop()
        if self._sink is not None:
            self._sink.close(run_summary=self.goodput_summary())


# -------------------------------------------------------- process-global routing

NOOP_TELEMETRY = Telemetry(enabled=False)
_active: Telemetry = NOOP_TELEMETRY


def get_active_telemetry() -> Telemetry:
    return _active


def set_active_telemetry(telemetry: Optional[Telemetry]) -> Telemetry:
    """Install the process-global telemetry (None -> no-op). Returns the previous
    one so callers can restore it in a finally block."""
    global _active
    previous = _active
    _active = telemetry if telemetry is not None else NOOP_TELEMETRY
    if previous is not _active:
        if previous.enabled:
            PROCESS_LOG.release()
        compile_log.forward_to(_active._on_compile if _active.enabled else None)
        collective_plan.forward_to(_active._on_collective_plan if _active.enabled else None)
        _active._claim_process_log()
    return previous


def span(name: str):
    """`with span("checkpoint_save"): ...` against the active telemetry — the
    zero-plumbing entry point for deep call sites. While none is active the span is
    recorded all the same, by the process's own recorder: it lands in
    `spans.PROCESS_LOG`, and the next instance to become active takes it over."""
    if _active is NOOP_TELEMETRY:
        return PROCESS_RECORDER.span(name)
    return _active.span(name)
