"""Watchdog: a wedged step must leave a crash artifact (all-thread stacks, feeder
state) BEFORE the scheduler kills the job; normal stepping must never fire; the
thread must join cleanly on the normal and the exception-propagation path."""

import json
import threading
import time
from types import SimpleNamespace

import pytest

from modalities_tpu.telemetry import Telemetry
from modalities_tpu.telemetry.watchdog import Watchdog, collect_thread_stacks


def _wait_for(predicate, timeout_s=5.0):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if predicate():
            return True
        time.sleep(0.01)
    return predicate()


def test_deadline_fires_and_artifact_contains_feeder_thread(tmp_path):
    wedged = threading.Event()

    def fake_feeder():  # stands in for the device-feeder producer parked on a queue
        wedged.wait()

    feeder_thread = threading.Thread(target=fake_feeder, name="device-feeder", daemon=True)
    feeder_thread.start()
    watchdog = Watchdog(deadline_s=0.1, artifact_dir=tmp_path, poll_interval_s=0.01)
    watchdog.register_state_provider(lambda: {"device_feeder": {"queue_size": 2, "producer_alive": True}})
    watchdog.start()
    watchdog.arm(step_id=7)
    try:
        assert _wait_for(lambda: watchdog.fired_artifacts)
    finally:
        wedged.set()
        watchdog.stop()
    artifact = json.loads(watchdog.fired_artifacts[0].read_text())
    assert artifact["armed_step"] == 7
    assert artifact["state"]["device_feeder"]["queue_size"] == 2
    # ALL thread stacks, the wedged feeder's included, with real frames
    stacks = artifact["thread_stacks"]
    feeder_keys = [k for k in stacks if k.startswith("device-feeder")]
    assert feeder_keys, sorted(stacks)
    assert any("fake_feeder" in frame for frame in stacks[feeder_keys[0]])
    assert any(k.startswith("MainThread") for k in stacks)
    # one dump per armed period: no artifact spam while still wedged
    time.sleep(0.3)
    assert len(watchdog.fired_artifacts) == 1


def test_heartbeat_under_normal_stepping_never_fires(tmp_path, monkeypatch):
    """Seven beats a third of the deadline apart, on a clock the test steps: on the wall clock a busy machine's `sleep(0.05)`
    that took 0.15 s would have been the watchdog's 'hang' (the poll itself stays real: it sees every stepped instant)."""
    from modalities_tpu.telemetry import watchdog as watchdog_module

    now = [1000.0]
    monkeypatch.setattr(watchdog_module, "time", SimpleNamespace(monotonic=lambda: now[0], time=time.time))
    watchdog = Watchdog(deadline_s=0.15, artifact_dir=tmp_path, poll_interval_s=0.01)
    watchdog.start()
    watchdog.arm(step_id=1)
    try:
        for step in range(1, 8):  # 0.35 s of stepping, each beat well inside the deadline
            now[0] += 0.05
            time.sleep(0.02)  # two polls at this instant
            watchdog.beat(step)
    finally:
        watchdog.stop()
    assert watchdog.fired_artifacts == []
    assert not list(tmp_path.glob("watchdog_dump_*.json"))


def test_rearm_after_fire_allows_recovery_then_fires_again(tmp_path):
    watchdog = Watchdog(deadline_s=0.08, artifact_dir=tmp_path, poll_interval_s=0.01)
    watchdog.start()
    try:
        watchdog.arm(step_id=1)
        assert _wait_for(lambda: len(watchdog.fired_artifacts) == 1)
        watchdog.beat(step_id=1)  # the step eventually completed: re-armed
        assert _wait_for(lambda: len(watchdog.fired_artifacts) == 2)
    finally:
        watchdog.stop()


def test_stop_joins_cleanly_on_normal_exit(tmp_path):
    watchdog = Watchdog(deadline_s=30.0, artifact_dir=tmp_path)
    watchdog.start()
    assert watchdog.is_alive
    watchdog.stop()
    assert not watchdog.is_alive
    watchdog.stop()  # idempotent


def test_stop_joins_cleanly_on_exception_propagation(tmp_path):
    """The telemetry close runs in a finally while a training error propagates —
    the watchdog thread must be gone afterwards, not leaked."""
    def watchdogs():
        return {t for t in threading.enumerate() if t.name == "telemetry-watchdog"}

    before = watchdogs()  # another test's, in this worker, is not this telemetry's leak
    telemetry = Telemetry(output_folder_path=tmp_path, watchdog_deadline_s=30.0)
    with pytest.raises(RuntimeError, match="train blew up"):
        try:
            telemetry.arm_watchdog(1, first_step=True)
            assert telemetry._watchdog.is_alive
            raise RuntimeError("train blew up")
        finally:
            telemetry.close()
    assert telemetry._watchdog is not None and not telemetry._watchdog.is_alive
    assert watchdogs() <= before


def test_disarm_suspends_checking(tmp_path):
    watchdog = Watchdog(deadline_s=0.05, artifact_dir=tmp_path, poll_interval_s=0.01)
    watchdog.start()
    try:
        watchdog.arm(step_id=1)
        watchdog.disarm()
        time.sleep(0.2)
        assert watchdog.fired_artifacts == []
    finally:
        watchdog.stop()


def test_first_step_deadline_is_stretched(tmp_path):
    """arm(first_step=True) through Telemetry multiplies the deadline so a
    legitimate compile does not trip the watchdog."""
    telemetry = Telemetry(
        output_folder_path=tmp_path, watchdog_deadline_s=0.1, watchdog_first_step_factor=20.0
    )
    telemetry.arm_watchdog(1, first_step=True)
    time.sleep(0.4)  # 4x the base deadline, well under the 20x first-step budget
    assert telemetry.watchdog_artifacts == []
    telemetry.close()


def test_collect_thread_stacks_names_every_live_thread():
    stacks = collect_thread_stacks()
    assert any(key.startswith("MainThread") for key in stacks)
    me = [frames for key, frames in stacks.items() if key.startswith("MainThread")][0]
    assert any("collect_thread_stacks" in frame or "test_collect" in frame for frame in me)


def test_zero_deadline_rejected(tmp_path):
    with pytest.raises(ValueError, match="deadline_s"):
        Watchdog(deadline_s=0.0, artifact_dir=tmp_path)


def test_dump_embeds_metrics_snapshot_and_weights_generation(tmp_path):
    """PR 13: a hang artifact carries the registry's counters (not just thread
    stacks) and, when a serving engine registered state, its live
    weights_generation — the two correlates an on-call actually needs."""
    from modalities_tpu.telemetry.metrics import MetricsRegistry

    reg = MetricsRegistry()
    reg.counter("serve_decode_steps_total", "d").inc()
    reg.counter("serve_decode_steps_total", "d").inc()
    watchdog = Watchdog(
        deadline_s=0.05, artifact_dir=tmp_path, poll_interval_s=0.01,
        metrics_provider=reg.snapshot,
    )
    watchdog.register_state_provider(
        lambda: {"serving_engine": {"weights_generation": 4, "active": 1}}
    )
    watchdog.start()
    watchdog.arm(step_id=3)
    try:
        assert _wait_for(lambda: watchdog.fired_artifacts)
    finally:
        watchdog.stop()
    artifact = json.loads(watchdog.fired_artifacts[0].read_text())
    assert artifact["metrics"]["serve_decode_steps_total"]["series"]["{}"] == 2.0
    assert artifact["weights_generation"] == 4


def test_dump_metrics_provider_failure_never_sinks_the_artifact(tmp_path):
    watchdog = Watchdog(
        deadline_s=0.05, artifact_dir=tmp_path, poll_interval_s=0.01,
        metrics_provider=lambda: 1 / 0,
    )
    watchdog.start()
    watchdog.arm(step_id=1)
    try:
        assert _wait_for(lambda: watchdog.fired_artifacts)
    finally:
        watchdog.stop()
    artifact = json.loads(watchdog.fired_artifacts[0].read_text())
    assert "error" in artifact["metrics"]
    assert artifact["thread_stacks"]  # the stacks still landed


def test_telemetry_watchdog_wires_its_own_registry_snapshot(tmp_path):
    """The Telemetry-owned watchdog dumps the Telemetry-owned registry."""
    telemetry = Telemetry(output_folder_path=tmp_path, watchdog_deadline_s=0.05)
    telemetry.metrics.counter("training_step_time_anomaly_total", "a").inc()
    telemetry.arm_watchdog(step_id=1)
    try:
        assert _wait_for(lambda: telemetry.watchdog_artifacts)
    finally:
        telemetry.close()
    artifact = json.loads(telemetry.watchdog_artifacts[0].read_text())
    assert artifact["metrics"]["training_step_time_anomaly_total"]["series"]["{}"] == 1.0
    assert artifact["weights_generation"] is None  # not serving: explicit null
