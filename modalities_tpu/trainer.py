"""The training loop (reference: src/modalities/trainer.py:201).

Differences from the reference, by design:
- forward/backward/clip/optimizer/schedule live inside ONE donated jit step
  (training/train_step.py); the Python loop only feeds batches and reads metrics.
- gradient accumulation happens inside the step (lax.scan), so the loop advances one
  *optimizer* step per iteration over stacked microbatches.
- the host path (microbatch stacking + sharded device transfer) runs in the
  DeviceFeeder's background pipeline (dataloader/device_feeder.py), which stays
  `prefetch_to_device` batches ahead — the step loop iterates DEVICE-READY batches
  and the transfer for step N+1 overlaps the device executing step N.
- metrics are fetched from device only at the log interval — no per-step host sync;
  the explicit loss `Reducer` all-reduce (reference trainer.py:307) is unnecessary
  because the in-jit mean already spans the mesh.
- Python GC is disabled during the loop and collected every `gc_frequency` steps
  (reference trainer.py:30 GarbageCollection) to avoid jitter.

Interval throughput semantics (deferred-publish overlap): a completed interval is
published one step later, with the next step already in flight, so the metrics
fetch never idles the device. Each interval window runs fetch-return to
fetch-return — the windows tile wall time exactly — and the publish carries BOTH
sides of the split:
- "tokens/s" / "MFU": WALL-CLOCK numbers over the window (what a stopwatch sees —
  the honest scoreboard, includes every stall).
- "tokens/s (device)" / "MFU (device)": the same tokens over the window minus the
  measured stalls — the device-execution estimate, comparable to a
  per-iteration device timing.
- "host stall [s]": time the step loop spent blocked waiting for a device-ready
  batch (the feeder's queue wait; with `prefetch_to_device: 0`, the full inline
  stack+transfer time).
- "boundary stall [s]": time spent inside the evaluation/checkpointing callbacks.
"""

from __future__ import annotations

import gc
import os
import time
from pathlib import Path
from typing import Callable, Optional

import numpy as np

from modalities_tpu.batch import EvaluationResultBatch, ResultItem
from modalities_tpu.dataloader.device_feeder import DeviceBatchIterator, DeviceFeeder
from modalities_tpu.logging_broker.messages import ExperimentStatus, MessageTypes, ProgressUpdate
from modalities_tpu.logging_broker.publisher import MessagePublisher
from modalities_tpu.resilience.coordination import (
    BALLOT_KEY,
    VOTE_CONTINUE,
    VOTE_ROLLBACK,
    VOTE_STOP,
    make_ballot,
)
from modalities_tpu.resilience.errors import AnomalyRollback, PreemptionShutdown
from modalities_tpu.resilience.events import record_event
from modalities_tpu.resilience.faults import (
    fire_oom_if_armed,
    fire_sigterm_if_armed,
    fire_sigterm_one_rank_if_armed,
    host_loss_if_armed,
    peer_death_if_armed,
    peer_hang_if_armed,
)
from modalities_tpu.telemetry import Telemetry, get_active_telemetry
from modalities_tpu.telemetry.device_memory import (
    hbm_headroom_mb,
    min_bytes_limit,
    peak_memory_mb,
)
from modalities_tpu.telemetry.memscope import (
    MemoryTimeline,
    MemscopeWindow,
    is_oom_error,
    oom_forensics,
    preflight_fits_check,
)
from modalities_tpu.telemetry.perfscope import ProfileWindow
from modalities_tpu.training.train_step import COUNTER_PREFIX, StepFunctions
from modalities_tpu.training.training_progress import TrainingProgress
from modalities_tpu.utils.logging import get_logger

logger = get_logger(__name__)


class Trainer:
    def __init__(
        self,
        progress_publisher: MessagePublisher,
        evaluation_result_publisher: MessagePublisher,
        gradient_acc_steps: int = 1,
        global_num_tokens_per_train_step: int = 0,
        num_seen_train_steps: int = 0,
        global_num_seen_tokens: int = 0,
        training_log_interval_in_steps: int = 1,
        mfu_calculator=None,
        profiler=None,
        gc_frequency: int = 10,
        debug_stats_logger=None,
        device_feeder: Optional[DeviceFeeder] = None,
        telemetry: Optional[Telemetry] = None,
        anomaly_tracker=None,
        preemption=None,
        stop_consensus: bool = False,
    ) -> None:
        self.progress_publisher = progress_publisher
        self.evaluation_result_publisher = evaluation_result_publisher
        self.gradient_acc_steps = gradient_acc_steps
        self.global_num_tokens_per_train_step = global_num_tokens_per_train_step
        self.num_seen_train_steps = num_seen_train_steps
        self.global_num_seen_tokens = global_num_seen_tokens
        self.training_log_interval_in_steps = training_log_interval_in_steps
        self.mfu_calculator = mfu_calculator
        self.profiler = profiler
        self.gc_frequency = gc_frequency
        # debugging_enriched model variant: per-rank jsonl stats on params/grads
        self.debug_stats_logger = debug_stats_logger
        # async prefetch is the default path; prefetch_to_device=0 restores sync
        self.device_feeder = device_feeder if device_feeder is not None else DeviceFeeder()
        # None -> resolve the process-global telemetry at train() time (no-op unless
        # Main activated one), so direct Trainer construction needs no plumbing
        self.telemetry = telemetry
        # resilience (both optional): the anomaly tracker replaces the raise-only
        # non-finite guard at interval boundaries; the preemption handler turns
        # SIGTERM into a forced checkpoint + PreemptionShutdown
        self.anomaly_tracker = anomaly_tracker
        self.preemption = preemption
        # stop-flag consensus (must match the TrainStepBuilder's flag): local
        # stop/rollback votes ride the step as a replicated ballot so every
        # process exits at the same step boundary (resilience/coordination.py)
        self.stop_consensus = stop_consensus
        self._boundary_stall_s = 0.0
        self._first_interval = True

    def _telemetry(self) -> Telemetry:
        return self.telemetry if self.telemetry is not None else get_active_telemetry()

    @staticmethod
    def _preflight_memscope(
        step_functions: StepFunctions, device_batch, telemetry: Optional[Telemetry] = None
    ) -> Optional[dict]:
        """Static memscope report + fits-check before the first dispatch. Only runs where it can act: a backend with a
        bytes_limit (TPU) and a check mode other than off — on CPU this is a no-op, so e2e tests pay nothing. A
        FitsCheckFailure propagates (fail-fast is the point); any other failure degrades to 'no static report', never a
        dead run. Where it runs it is the span `preflight_memscope`: `memscope_report` compiles the step ahead of time
        (`lower_train_step(...).compile()`), before the first dispatch compiles or loads the `jit` path's executable
        inside `first_step`; on a mesh of several devices the same compiled step's collectives are then recorded, in
        the child span `collective_plan` (`_with_collective_plan`, below). This function keeps its line count: the
        frames of `train` and of the lowering it calls are part of every step's compile-cache key."""
        from modalities_tpu.telemetry.memscope import FITS_CHECK_ENV

        mode = (os.environ.get(FITS_CHECK_ENV) or "fail").strip().lower()
        if (
            mode == "off"
            or getattr(step_functions, "lower_train_step", None) is None
            or min_bytes_limit() is None
        ):
            return None
        telemetry = telemetry if telemetry is not None else get_active_telemetry()
        with telemetry.span("preflight_memscope"):
            try:
                report = step_functions.memscope_report(device_batch)
                kept, limit = getattr(step_functions, "kept_attention", None), min_bytes_limit()
                while kept is not None and kept.plan and kept.plan["kept"] and report["predicted_peak_bytes"] > limit:
                    # the count said what the blocks keep fits and the compiler says it does not: the step one rung down the
                    # plan's ladder is built in its place (one more trace and lowering a rung), and only the step that keeps
                    # nothing, the one the model had before, can fail the check
                    logger.warning(
                        "memscope: the step whose blocks keep %s (%d bytes of attention, %d of the gated delta rule) is predicted at %d "
                        "bytes, over the device's %d: building the step one rung down", " and ".join(kept.plan["kept"]),
                        kept.plan["kept_bytes"] * kept.plan["keep"], kept.plan["rule_kept_bytes"] * kept.plan["keep_rule"],
                        report["predicted_peak_bytes"], limit)
                    kept.drop()
                    report = step_functions.memscope_report(device_batch)
            except Exception:
                logger.exception("memscope: static report failed; fits-check skipped")
                return None
            preflight_fits_check(report)
            return _with_collective_plan(report, step_functions, telemetry)

    def train(
        self,
        step_functions: StepFunctions,
        train_loader,
        training_progress: TrainingProgress,
        evaluation_callback: Callable[[int], None],
        checkpointing_callback: Callable[[TrainingProgress], None],
    ) -> None:
        state = step_functions.app_state_handle.state
        train_step = step_functions.train_step
        telemetry = self._telemetry()
        # THIS thread's spans are the run's wall-clock timeline (goodput source)
        telemetry.set_timeline_thread()

        # initial callbacks at "step -1" semantics (reference trainer.py:250-259)
        evaluation_callback(self.num_seen_train_steps)

        if self.gc_frequency > 0:
            gc.disable()
            gc.collect(1)

        pending_metrics: list[dict] = []
        deferred_publish = None  # a completed interval awaiting its overlap-publish
        interval_start = time.perf_counter()
        step_id = self.num_seen_train_steps
        target_steps = training_progress.num_target_steps
        self._boundary_stall_s = 0.0
        # the first published interval holds the run's first step (trace + compile):
        # `_publish_interval` keeps it from the step-time detector
        self._first_interval = True
        exhausted = False

        # --- stop-flag consensus state: each dispatch carries this process's
        # current vote as a device-sharded ballot; the decision is the PREVIOUS
        # step's reduced ballot (complete by the time the next dispatch returns,
        # so reading it costs no per-step stall). All processes read the same
        # replicated value and exit at the same step boundary.
        consensus = self.stop_consensus
        mesh_handle = getattr(step_functions, "mesh_handle", None)
        if mesh_handle is None:
            consensus = False  # step functions built without a mesh can't ballot
        local_vote = VOTE_CONTINUE
        prev_ballot = None
        pending_rollback: Optional[AnomalyRollback] = None

        feed = self.device_feeder.feed_train(
            train_loader, step_functions.put_batch, self.gradient_acc_steps
        )
        queue_state = getattr(feed, "queue_state", None)
        if queue_state is not None:
            telemetry.register_watchdog_state_provider(lambda: {"device_feeder": queue_state()})
        first_step_id = step_id
        # first deadline is stretched: the first step legitimately traces + compiles
        telemetry.arm_watchdog(step_id + 1, first_step=True)
        # env-armed programmatic profiler capture (MODALITIES_TPU_PROFILE_AT_STEP=N[:K]):
        # purely observational — the capture window must never change step outputs
        # (pinned bitwise by tests/telemetry/test_perfscope.py)
        profile_window = ProfileWindow.from_env(
            fallback_dir=telemetry.sink_path.parent if telemetry.sink_path is not None else None
        )
        # memscope runtime pillar: per-step memory timeline (inert on backends
        # with no numeric memory_stats), env-armed live-array snapshots, and the
        # static report for the preflight fits-check + OOM forensics. Purely
        # observational — pinned bitwise by tests/telemetry/test_memscope.py.
        mem_timeline = MemoryTimeline(telemetry=telemetry, executable="train_step")
        memscope_window = MemscopeWindow.from_env(
            fallback_dir=telemetry.sink_path.parent if telemetry.sink_path is not None else None
        )
        memscope_static: Optional[dict] = None
        fits_checked = False
        profiler_cm = self.profiler
        if profiler_cm is not None:
            profiler_cm.__enter__()
        try:
            while True:
                with telemetry.span("data_wait"):
                    try:
                        device_batch = next(feed)
                    except StopIteration:
                        exhausted = True
                        break
                # the debug step variant (grads in metrics) runs ONLY on logging ticks
                # so the extra grad tree isn't materialized on every step
                debug_tick = (
                    self.debug_stats_logger is not None
                    and step_functions.train_step_debug is not None
                    and (step_id + 1) % self.debug_stats_logger.log_interval_steps == 0
                )
                step_fn = step_functions.train_step_debug if debug_tick else train_step
                if consensus:
                    # fold the local stop flag into this dispatch's vote NOW (not
                    # via the feeder) so the ballot is never stale by prefetch depth
                    if (
                        self.preemption is not None
                        and self.preemption.should_stop()
                        and local_vote < VOTE_STOP
                    ):
                        local_vote = VOTE_STOP
                        record_event(
                            "consensus/stop_vote_cast",
                            step=step_id,
                            signal=self.preemption.received_signal or "request_stop",
                        )
                    device_batch = dict(device_batch)
                    device_batch[BALLOT_KEY] = make_ballot(local_vote, mesh_handle)
                if profile_window is not None:
                    profile_window.maybe_start(step_id + 1)
                if not fits_checked:
                    # preflight fits-check: on backends with a bytes_limit, AOT-
                    # compile the step's memory scope and compare its predicted
                    # peak against the budget BEFORE the first dispatch — an
                    # over-budget run fails here with levers named instead of
                    # dying inside XLA allocation. CPU (no limit): skipped.
                    fits_checked = True
                    memscope_static = self._preflight_memscope(step_functions, device_batch, telemetry)
                    if memscope_static is not None:
                        telemetry.publish_memscope_report(memscope_static, executable="train_step")
                try:
                    fire_oom_if_armed(step_id + 1)  # chaos: oom@N
                    with telemetry.step_annotation(step_id + 1):
                        with telemetry.span("first_step" if step_id == first_step_id else "train_step"):
                            state, metrics = step_fn(state, device_batch)
                except Exception as e:
                    if is_oom_error(e):
                        # forensics first (static scope + timeline tail + live
                        # arrays + levers), then exit resumable: a degraded
                        # warmstart beats a dead pod with an opaque traceback
                        raise oom_forensics(
                            telemetry.sink_path.parent if telemetry.sink_path is not None else Path("."),
                            rank=telemetry.global_rank,
                            step=step_id + 1,
                            exc=e,
                            static_report=memscope_static,
                            timeline=mem_timeline,
                            window=memscope_window,
                            metrics_snapshot=telemetry.metrics.snapshot(),
                        ) from e
                    raise
                debug_grads = metrics.pop("grads", None)  # exposed only when debugging
                decided = VOTE_CONTINUE
                if consensus:
                    # read the PREVIOUS step's reduced ballot: with this step's
                    # dispatch already in flight that value is long complete, so
                    # the fetch costs no device idle time. Every process reads
                    # the same replicated scalar -> same decision, same boundary.
                    if prev_ballot is not None:
                        decided = int(np.asarray(prev_ballot).max())
                    prev_ballot = metrics.pop(BALLOT_KEY, None)
                # publish the PREVIOUS interval now, with this step already in
                # flight: the publish's metrics fetch blocks until that interval's
                # last step completed, but the device is not idle while it does —
                # dispatch ahead, fetch behind,
                # so in-app throughput stops paying a per-interval stall.
                # The fetch-return instant starts the next clock,
                # and the stall accumulators are drained AT the publish, so every
                # stalled second lands in exactly one window.
                if deferred_publish is not None:
                    interval_start = self._publish_interval(*deferred_publish, feed)
                    deferred_publish = None

                pending_metrics.append(metrics)
                step_id += 1
                training_progress.num_seen_steps_current_run += 1
                training_progress.num_seen_tokens_current_run += self.global_num_tokens_per_train_step

                self.progress_publisher.publish_message(
                    ProgressUpdate(step_id, ExperimentStatus.TRAIN, train_loader.dataloader_tag),
                    MessageTypes.BATCH_PROGRESS_UPDATE,
                )

                if step_id % self.training_log_interval_in_steps == 0:
                    # with the non-finite guard ARMED, check the interval's flags
                    # EAGERLY — before the boundary callbacks below can save a
                    # NaN-poisoned checkpoint as the latest resume target. The
                    # host sync this costs is exactly what error_if_nonfinite
                    # opts into: per-interval safety over overlap. An anomaly
                    # tracker (resilience component) replaces the raise-only
                    # guard with the configured policy at the same point.
                    if self.anomaly_tracker is not None and self.anomaly_tracker.should_observe(
                        pending_metrics[0]
                    ):
                        try:
                            self.anomaly_tracker.observe_interval(pending_metrics, step_id)
                        except AnomalyRollback as rollback:
                            if not consensus:
                                raise
                            # under consensus a rollback escalation is a VOTE, not
                            # a unilateral exit: hold the exception, ride the
                            # ballot, and raise it when every rank has agreed
                            pending_rollback = rollback
                            if local_vote < VOTE_ROLLBACK:
                                local_vote = VOTE_ROLLBACK
                                record_event("consensus/rollback_vote_cast", step=step_id)
                    elif "nonfinite_grads" in pending_metrics[0]:
                        self._raise_on_nonfinite(pending_metrics, step_id)
                    # snapshot the token count AT the boundary: by publish time the
                    # in-flight step has already been counted into training_progress
                    deferred_publish = (
                        pending_metrics, step_id, train_loader.dataloader_tag,
                        interval_start, training_progress.num_seen_tokens_total,
                    )
                    pending_metrics = []

                if self.debug_stats_logger is not None:
                    trees = {"params": state.params}
                    if debug_grads is not None:
                        trees["grads"] = debug_grads
                    self.debug_stats_logger.log(step_id, **trees)

                if self.gc_frequency > 0 and step_id % self.gc_frequency == 0:
                    gc.collect(1)

                step_functions.app_state_handle.state = state
                boundary_t0 = time.perf_counter()
                evaluation_callback(step_id)
                checkpointing_callback(training_progress)
                self._boundary_stall_s += time.perf_counter() - boundary_t0

                if profiler_cm is not None:
                    profiler_cm.step()
                if profile_window is not None:
                    # block on this step's metrics so the captured device work has
                    # actually executed before the trace closes
                    profile_window.maybe_stop(step_id, block_on=metrics)
                mem_timeline.sample(step_id)
                if memscope_window is not None:
                    memscope_window.maybe_snapshot(step_id)

                # step completed end-to-end (callbacks included): re-arm the hang
                # deadline for the next one
                telemetry.beat_watchdog(step_id)

                # distributed chaos fire sites (multi-process tests arm these in
                # ONE rank's environment): a wedged peer, an abrupt peer death,
                # a permanently lost host, a SIGTERM delivered to a single rank
                peer_hang_if_armed(step_id)
                peer_death_if_armed(step_id)
                host_loss_if_armed(step_id)
                if self.preemption is not None:
                    fired = fire_sigterm_if_armed(step_id)  # chaos: sigterm_at_step@N
                    fired = fire_sigterm_one_rank_if_armed(step_id) or fired
                    if fired:
                        # the real SIGTERM is in flight, but Python runs signal
                        # handlers at a later bytecode boundary — request the stop
                        # directly so the chaos test is deterministic about WHICH
                        # step the shutdown lands on
                        self.preemption.request_stop()
                    if not consensus and self.preemption.should_stop() and step_id < target_steps:
                        # the in-flight step has completed (we are past the
                        # callbacks); force an out-of-schedule checkpoint at this
                        # exact step so the supervisor can warmstart from it, then
                        # exit resumable. Async commits drain in Gym's finally.
                        signal_name = self.preemption.received_signal or "request_stop"
                        record_event(
                            "preempt/shutdown_requested", step=step_id, signal=signal_name
                        )
                        logger.warning(
                            "preemption signal (%s) received — saving out-of-schedule "
                            "checkpoint at step %d and exiting resumable",
                            signal_name, step_id,
                        )
                        with telemetry.span("preempt/forced_checkpoint"):
                            checkpointing_callback(training_progress, force=True)
                        record_event("preempt/checkpoint_saved", step=step_id)
                        raise PreemptionShutdown(
                            f"preempted by {signal_name} at step {step_id}; "
                            "checkpoint saved — warmstart to resume"
                        )

                if consensus and decided != VOTE_CONTINUE and step_id < target_steps:
                    self._coordinated_stop(
                        decided, step_id, pending_rollback, training_progress,
                        checkpointing_callback, telemetry,
                    )

                if step_id >= target_steps:
                    break
        except BaseException:
            # a COMPLETED interval held for the overlap-publish must not vanish
            # because a later step (callbacks, loader, transfer) crashed — before
            # the deferral it had already been published at the boundary
            if deferred_publish is not None:
                try:
                    self._publish_interval(*deferred_publish, feed)
                    deferred_publish = None
                except Exception:
                    logger.warning(
                        "failed to flush the completed metrics interval while "
                        "propagating a training error", exc_info=True,
                    )
            raise
        finally:
            # post-loop drain work (publish flush, checkpoint drain) is not a hang
            telemetry.disarm_watchdog()
            feed.close()
            if profile_window is not None and profile_window.active:
                # the loop exited mid-window (crash, preemption, exhausted loader):
                # close the trace so the partial capture is still readable
                profile_window.maybe_stop(profile_window.start_step + profile_window.num_steps)
            if profiler_cm is not None:
                profiler_cm.__exit__(None, None, None)
            if self.gc_frequency > 0:
                gc.enable()

        # flush the deferred interval and any tail metrics when the loop exits
        # (target steps reached or loader exhausted) so token/loss accounting stays
        # honest and ordered
        if deferred_publish is not None:
            interval_start = self._publish_interval(*deferred_publish, feed)
        if pending_metrics:
            self._publish_interval(
                pending_metrics, step_id, train_loader.dataloader_tag, interval_start,
                training_progress.num_seen_tokens_total, feed,
            )
        dropped = feed.counters["dropped_microbatches"] if exhausted else 0
        if dropped:
            logger.warning(
                "dropping %d trailing microbatches at end of dataloader (< gradient_acc_steps=%d); "
                "their tokens are not counted",
                dropped,
                self.gradient_acc_steps,
            )

        step_functions.app_state_handle.state = state

    def _coordinated_stop(
        self,
        decided: int,
        step_id: int,
        pending_rollback: Optional[AnomalyRollback],
        training_progress: TrainingProgress,
        checkpointing_callback: Callable[[TrainingProgress], None],
        telemetry: Telemetry,
    ) -> None:
        """The stop ballot came back nonzero: EVERY process sees the same reduced
        vote at the same step boundary, so the exits below are cluster-wide
        collective-safe (the forced save is a well-formed Orbax collective)."""
        if decided >= VOTE_ROLLBACK:
            record_event("consensus/rollback_agreed", step=step_id)
            logger.warning(
                "stop ballot agreed on anomaly rollback at step %d — exiting "
                "resumable (no forced checkpoint: the newest verified one wins)",
                step_id,
            )
            # the local tracker raised (pending_rollback) or a PEER escalated —
            # either way the run exits resumable without checkpointing the
            # possibly-poisoned state
            raise pending_rollback or AnomalyRollback(
                f"peer-escalated anomaly rollback at step {step_id} (stop ballot)"
            )
        signal_name = None
        if self.preemption is not None and self.preemption.should_stop():
            signal_name = self.preemption.received_signal or "request_stop"
        signal_name = signal_name or "peer_vote"
        record_event("consensus/shutdown_agreed", step=step_id, signal=signal_name)
        # mirror the local-path preempt/* events so supervisor tooling and the
        # goodput ledger see one uniform shutdown shape either way
        record_event("preempt/shutdown_requested", step=step_id, signal=signal_name)
        logger.warning(
            "stop ballot agreed (%s) — saving out-of-schedule checkpoint at "
            "step %d on all ranks and exiting resumable",
            signal_name, step_id,
        )
        with telemetry.span("preempt/forced_checkpoint"):
            checkpointing_callback(training_progress, force=True)
        record_event("preempt/checkpoint_saved", step=step_id)
        raise PreemptionShutdown(
            f"coordinated stop agreed ({signal_name}) at step {step_id}; "
            "checkpoint saved — warmstart to resume"
        )

    @staticmethod
    def _raise_on_nonfinite(pending_metrics: list[dict], step_id: int) -> None:
        """Host-syncs the interval's non-finite flags and names the first bad step."""
        flags = np.asarray([int(m["nonfinite_grads"]) for m in pending_metrics])
        if flags.any():
            first_bad = step_id - len(pending_metrics) + 1 + int(flags.argmax())
            raise RuntimeError(
                f"non-finite gradient norm at train step {first_bad} "
                "(gradient_clipper.error_if_nonfinite=True)"
            )

    def _publish_interval(
        self,
        pending_metrics: list[dict],
        step_id: int,
        dataloader_tag: str,
        interval_start: float,
        tokens_total: int,
        feed: Optional[DeviceBatchIterator] = None,
    ) -> float:
        """Fetch + publish one interval's metrics. Returns the post-fetch timestamp —
        the honest start-of-clock for the NEXT interval under the deferred-publish
        overlap. Drains the host/boundary stall accumulators, so each stalled second
        is attributed to exactly one interval window."""
        telemetry = self._telemetry()
        # single host sync point per interval: fetch the accumulated device metrics.
        # The fetch blocks until the interval's device work finished, so its span
        # counts toward the train_step goodput bucket, not overhead.
        with telemetry.span("metrics_fetch"):
            # when an anomaly tracker owns the policy, the interval boundary
            # already observed these metrics — re-raising here would bypass the
            # configured skip/rollback policy
            if self.anomaly_tracker is None and "nonfinite_grads" in pending_metrics[0]:
                self._raise_on_nonfinite(pending_metrics, step_id)
            losses = np.asarray([m["loss"] for m in pending_metrics], dtype=np.float64)
            grad_norms = np.asarray([m["grad_norm"] for m in pending_metrics], dtype=np.float64)
            lrs = np.asarray([m["lr"] for m in pending_metrics], dtype=np.float64)
            # what the model counted beside its loss (train_step.COUNTER_PREFIX): fetched with the rest, no further sync
            counters = {
                key[len(COUNTER_PREFIX):]: np.asarray([m[key] for m in pending_metrics], dtype=np.float64)
                for key in pending_metrics[0] if key.startswith(COUNTER_PREFIX)
            }
        fetch_done = time.perf_counter()
        wall_elapsed = max(fetch_done - interval_start, 1e-9)
        # the step-time detector is fed the step as the device paces it: the time from
        # the return of the last interval's fetch to the return of this one's, over the
        # interval's steps. (The dispatch alone returns in a millisecond, two steps are
        # in flight at most, and the wait for the device lives in `metrics_fetch`.)
        if self._first_interval:
            self._first_interval = False
        else:
            telemetry.observe_step_time(
                wall_elapsed / len(pending_metrics), step_id=step_id, window=(interval_start, fetch_done)
            )
        host_stall_s = feed.take_stall_s() if feed is not None else 0.0
        boundary_stall_s, self._boundary_stall_s = self._boundary_stall_s, 0.0
        device_elapsed = max(wall_elapsed - host_stall_s - boundary_stall_s, 1e-9)
        num_steps = len(pending_metrics)
        interval_tokens = num_steps * self.global_num_tokens_per_train_step
        tokens_per_second_wall = interval_tokens / wall_elapsed
        tokens_per_second_device = interval_tokens / device_elapsed

        throughput = {
            "train steps/s": ResultItem(num_steps / wall_elapsed, 2),
            # wall-clock is the scoreboard number; the device split is what
            # a per-iteration device timing is comparable to (module docstring).
            # The bare "tokens/s"/"MFU" keys stay for dashboard compat; the
            # explicit "(wall)" aliases make the to-disc JSONL self-describing so
            # scoreboard numbers stay auditable offline without knowing that
            # convention.
            "tokens/s": ResultItem(tokens_per_second_wall, 1),
            "tokens/s (wall)": ResultItem(tokens_per_second_wall, 1),
            "tokens/s (device)": ResultItem(tokens_per_second_device, 1),
            "host stall [s]": ResultItem(host_stall_s, 3),
            "boundary stall [s]": ResultItem(boundary_stall_s, 3),
        }
        if self.mfu_calculator is not None:
            mfu_wall = self.mfu_calculator.compute(tokens_per_second_wall)
            throughput["MFU"] = ResultItem(mfu_wall, 4)
            throughput["MFU (wall)"] = ResultItem(mfu_wall, 4)
            throughput["MFU (device)"] = ResultItem(
                self.mfu_calculator.compute(tokens_per_second_device), 4
            )
        peak_mb = self._peak_memory_mb()
        if peak_mb is not None:
            throughput["peak memory [MB]"] = ResultItem(peak_mb, 1)
        headroom_mb = self._hbm_headroom_mb()
        if headroom_mb is not None:
            throughput["HBM headroom [MB]"] = ResultItem(headroom_mb, 1)
        telemetry.publish_resource_gauges(hbm_headroom_mb=headroom_mb, peak_memory_mb=peak_mb)
        goodput_metrics = telemetry.throughput_metrics()
        if goodput_metrics:
            # cumulative since run start: goodput % plus per-bucket wall seconds
            throughput["goodput [%]"] = ResultItem(goodput_metrics.pop("goodput [%]"), 2)
            for key, seconds in goodput_metrics.items():
                throughput[key] = ResultItem(seconds, 3)
            if self.mfu_calculator is not None:
                # cumulative wall-clock MFU decomposed into named deductions
                # against the same goodput ledger (telemetry/waterfall.py)
                wall_s = telemetry.ledger.wall_s()
                if wall_s > 0:
                    telemetry.publish_mfu_waterfall(
                        self.mfu_calculator.compute(tokens_total / wall_s)
                    )
        if telemetry.slo_engine is not None:
            telemetry.slo_engine.sample_once()
            if self.anomaly_tracker is not None:
                self.anomaly_tracker.observe_slo(
                    telemetry.slo_engine.breaching(), step_id
                )

        result = EvaluationResultBatch(
            dataloader_tag=dataloader_tag,
            num_train_steps_done=step_id,
            losses={
                "train loss avg": ResultItem(losses.mean(), 5),
                "train loss last": ResultItem(losses[-1], 5),
            },
            metrics={
                "grad norm avg": ResultItem(grad_norms.mean(), 5),
                "grad norm last": ResultItem(grad_norms[-1], 5),
                "lr mean": ResultItem(lrs.mean(), 8),
                "consumed tokens": ResultItem(tokens_total, 0),
                **{name: ResultItem(values.max() if name.endswith("_max") else values.mean(), 2) for name, values in counters.items()},
            },
            throughput_metrics=throughput,
        )
        with telemetry.span("publish"):
            self.evaluation_result_publisher.publish_message(result, MessageTypes.EVALUATION_RESULT)
        return fetch_done

    # thin delegations to the shared device-stat walk (telemetry/device_memory.py)
    # — kept as methods so interval-publish call sites and their tests are stable

    @classmethod
    def _peak_memory_mb(cls) -> Optional[float]:
        """Max peak_bytes_in_use across ALL local devices, in MB."""
        return peak_memory_mb()

    @classmethod
    def _hbm_headroom_mb(cls) -> Optional[float]:
        """Min over local devices of ``bytes_limit - peak_bytes_in_use``, in MB —
        the tightest remaining on-device allocation margin. None when the backend
        does not report a bytes_limit (CPU), so the key is simply absent there."""
        return hbm_headroom_mb()


def _with_collective_plan(report: dict, step_functions, telemetry: Telemetry) -> dict:
    """`report`, after the collectives of the compiled step `memscope_report` handed on have been recorded
    (`telemetry/collective_plan.py`: the process's record, the sink's event, the gauges), inside the span
    `collective_plan` so that a timeline shows what the walk over the optimized HLO costs. Only where the mesh has
    more than one device: a program on one device holds no collective, `memscope_report` hands nothing on there, and
    no span opens. A failure costs the plan, never the run. At the end of the file: see `_preflight_memscope`."""
    compiled = getattr(step_functions, "preflight_compiled", None)
    if compiled is None:
        return report
    step_functions.preflight_compiled = None  # the executable was the preflight's own: nothing else holds it
    with telemetry.span("collective_plan"):
        try:
            from modalities_tpu.telemetry.collective_plan import record_from_compiled

            record_from_compiled(compiled, {k: int(v) for k, v in step_functions.mesh_handle.mesh.shape.items()})
        except Exception:
            logger.exception("collective plan: the walk over the compiled step failed; no plan recorded")
    return report
