"""Fleet SLO rollback e2e (PR 15 acceptance pin): two REAL engine workers
(FakeModel — no compile cost) behind the real router, per-worker SLO engines
wired exactly as serving/fleet/component.py wires them, and a client thread
streaming requests through the router the whole time.

The canary's first probation tick sees latency 4x over the declared
`serve_ttft_seconds p99 < 0.5` objective: the rollout must roll back on the
SLO verdict (``fleet/rollback stage=slo``), the canary's /healthz must flip to
"degraded" while the breach window drains (and the router must deprioritize
it), and NOT ONE client request may drop — the zero-drop contract holds
through swap, breach, and rollback.
"""

import http.client
import json
import threading
import time

import pytest

from modalities_tpu.serving.engine import ServingEngine
from modalities_tpu.serving.fleet.controller import EngineWorker, RolloutController
from modalities_tpu.serving.fleet.router import FleetRouter, WorkerHandle
from modalities_tpu.serving.server import ServingHTTPServer
from modalities_tpu.telemetry import Telemetry, set_active_telemetry
from modalities_tpu.telemetry.metrics import MetricsRegistry, parse_prometheus_text
from modalities_tpu.telemetry.slo import SLOEngine, load_slo_spec
from tests.conftest import start_and_await_first_sweep
from tests.serving.test_observability import FakeModel

SLO_SPEC = {"objectives": [{"name": "ttft_p99", "expr": "serve_ttft_seconds p99 < 0.5"}]}


def _post_generate(port, body, timeout=30.0):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request(
            "POST", "/generate", body=json.dumps(body),
            headers={"Content-Type": "application/json"},
        )
        resp = conn.getresponse()
        if resp.status != 200:
            return resp.status, json.loads(resp.read())
        raw = resp.read()
        events = [
            json.loads(chunk[len(b"data: "):])
            for chunk in raw.split(b"\n\n")
            if chunk.startswith(b"data: ")
        ]
        return resp.status, events
    finally:
        conn.close()


def _get(port, path, timeout=10.0):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request("GET", path)
        resp = conn.getresponse()
        return resp.status, json.loads(resp.read())
    finally:
        conn.close()


def test_latency_poisoned_canary_rolls_back_on_slo_with_zero_drops(tmp_path):
    telemetry = Telemetry(
        output_folder_path=tmp_path, watchdog_deadline_s=0.0, use_jax_annotations=False
    )
    prior = set_active_telemetry(telemetry)
    workers, router = [], None
    results, poisoned = [], []
    try:
        for i in range(2):
            engine = ServingEngine(
                FakeModel(), {}, max_batch_slots=2, eod_token_id=-1,
                metrics=MetricsRegistry(),  # per-worker: canary metrics stay isolated
            )
            server = ServingHTTPServer(
                engine,
                encode=lambda s: [int(t) for t in s.split()],
                decode=lambda ids: " ".join(str(t) for t in ids),
                port=0,
            )
            server.start()
            workers.append(EngineWorker(f"worker{i}", engine, server))

        # the component's wiring, verbatim: one SLO engine per worker over that
        # worker's isolated registry, /healthz fed by breaching()
        objectives, options = load_slo_spec(SLO_SPEC)
        slo_engines = {
            w.name: SLOEngine(objectives, w.engine.metrics, scope=w.name, **options)
            for w in workers
        }
        for worker in workers:
            worker.server.slo_status_fn = slo_engines[worker.name].breaching

        def slo_verdict(worker):
            slo_engine = slo_engines[worker.name]
            if worker.engine.weights_generation == 1 and not poisoned:
                # first probation tick on the new generation: its traffic
                # comes back at 2s TTFT, 4x over the declared objective
                ttft = worker.engine.metrics.get("serve_ttft_seconds")
                assert ttft is not None
                for _ in range(20):
                    ttft.observe(2.0)
                poisoned.append(worker.name)
            slo_engine.sample_once()  # probation ticks outpace the sampler thread
            return slo_engine.breaching()

        fleet_registry = MetricsRegistry()
        controller = RolloutController(
            workers,
            metrics=fleet_registry,
            probation_s=5.0,
            probation_tick_s=0.05,
            slo_verdict_fn=slo_verdict,
        )
        router = FleetRouter(
            [WorkerHandle(w.name, "127.0.0.1", w.server.port) for w in workers],
            metrics=fleet_registry,
            health_interval_s=0.1,
        )
        start_and_await_first_sweep(router)  # before traffic

        stop = threading.Event()

        def client():  # ordinary traffic through the router, the whole time
            while not stop.is_set():
                results.append(
                    _post_generate(router.port, {"prompt": "3 4", "max_new_tokens": 3})
                )
                time.sleep(0.01)

        client_thread = threading.Thread(target=client, daemon=True)
        client_thread.start()
        time.sleep(0.5)  # healthy generation-0 traffic establishes a baseline

        # ---- the deploy: SLO verdict rolls the canary back mid-probation
        assert controller.deploy({}, step=1) is False
        assert len(poisoned) == 1
        canary = next(w for w in workers if w.name == poisoned[0])
        peer = next(w for w in workers if w is not canary)
        assert canary.engine.weights_generation == 0  # back on the donor
        assert peer.engine.weights_generation == 0  # peer never saw generation 1
        assert controller.generation == 0

        # the breach window has not drained: the canary serves but degraded,
        # and the router's next sweep deprioritizes it
        status, health = _get(canary.server.port, "/healthz")
        assert (status, health["status"]) == (200, "degraded")
        assert health["slo_breaching"] == ["ttft_p99"]
        # a sweep marks the worker at its probe and counts the degraded at the round's end: wait for both
        deadline = time.monotonic() + 30.0
        while time.monotonic() < deadline:
            _, table = _get(router.port, "/fleet")
            by_name = {w["name"]: w for w in table["workers"]}
            parsed = parse_prometheus_text(fleet_registry.render())
            if by_name[canary.name]["degraded"] and parsed["fleet_workers_degraded"][()] == 1.0:
                break
            time.sleep(0.05)
        else:
            pytest.fail("router sweep never marked and counted the canary degraded")
        assert by_name[peer.name]["degraded"] is False
        assert parsed["fleet_rollbacks_total"][()] == 1.0

        # traffic keeps flowing after the rollback — wait for round-trips, not
        # wall time, so a loaded box with slow decodes still accumulates enough
        deadline = time.monotonic() + 30.0
        while time.monotonic() < deadline and len(results) < 3:
            time.sleep(0.05)
        stop.set()
        client_thread.join(timeout=30.0)
        assert not client_thread.is_alive()
    finally:
        if router is not None:
            router.close()
        for worker in workers:
            worker.server.close()
        telemetry.close()
        set_active_telemetry(prior)

    # ---- zero dropped requests: every client call through swap, breach, and
    # rollback came back 200 with one complete budget-finished answer (the
    # round-trips are slow enough that the count stays small; completeness of
    # every answer is the contract, not the throughput)
    assert len(results) >= 3
    for status, events in results:
        assert status == 200, events
        done = [e for e in events if e.get("done")]
        assert len(done) == 1
        assert done[0]["finish_reason"] == "budget"
    assert all(w.engine.stats()["request_errors"] == 0 for w in workers)

    # ---- the verdict is attributed: fleet/rollback stage=slo, naming the
    # breaching objective, in the telemetry stream
    rollbacks = []
    for path in sorted(tmp_path.glob("telemetry_rank_*.jsonl")):
        for line in path.read_text().splitlines():
            event = json.loads(line)
            if event.get("name") == "fleet/rollback":
                rollbacks.append(event)
    assert len(rollbacks) == 1
    assert rollbacks[0]["stage"] == "slo"
    assert rollbacks[0]["worker"] == poisoned[0] and rollbacks[0]["step"] == 1
    assert "ttft_p99" in rollbacks[0]["reason"]
