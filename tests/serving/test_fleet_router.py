"""Fleet router e2e (serving/fleet/router.py) against scripted loopback
workers speaking the real worker wire protocol (the server module's own
helpers), plus the per-worker /admin/swap endpoint on a live FakeModel engine.

The load-bearing scenario is MID-STREAM FAILOVER: a worker dies after
streaming part of its answer, and the client — one ordinary POST /generate
against the router — still receives exactly one complete answer, because the
router replays the request on a peer and forwards only the token events past
what the client already has (deterministic replicas make the splice exact).
"""

import asyncio
import http.client
import json
import threading
import time

import pytest

from modalities_tpu.serving.engine import ServingEngine
from modalities_tpu.serving.fleet.component import FleetServingComponent
from modalities_tpu.serving.fleet.controller import EngineWorker
from modalities_tpu.serving.fleet.router import FleetRouter, WorkerHandle
from modalities_tpu.serving.server import (
    SSE_HEADER_BYTES,
    ServingHTTPServer,
    json_response_bytes,
    read_http_request,
    sse_event_bytes,
)
from modalities_tpu.telemetry.metrics import MetricsRegistry, parse_prometheus_text
from tests.conftest import start_and_await_first_sweep
from tests.serving.test_observability import VOCAB, FakeModel

ANSWER = [11, 12, 13, 14, 15]


class _ScriptedWorker:
    """A loopback asyncio server speaking the worker protocol from a script:
    answers /healthz and /stats, and streams `tokens` on POST /generate —
    dying after `abort_after` token events when set (no done event, connection
    cut: the failover trigger)."""

    def __init__(self, tokens, abort_after=None, load=0, sink_path=None):
        self.tokens = tokens
        self.abort_after = abort_after
        self.load = load
        self.generates = 0
        self.generate_headers = []  # headers of every /generate received
        self.sink_path = sink_path  # write a serve_request record here (like a real worker)
        self.port = None
        self._loop = None
        self._started = threading.Event()

    def _record_leg(self, headers, emitted):
        if self.sink_path is None:
            return
        record = {
            "event": "serve_request", "rank": 0, "rid": self.generates,
            "trace_id": headers.get("x-trace-id", ""),
            "hop": int(headers.get("x-trace-hop") or 0),
            "tokens": emitted, "finish_reason": "budget", "arrival_s": 0.0,
        }
        with open(self.sink_path, "a") as f:
            f.write(json.dumps(record) + "\n")

    async def _handle(self, reader, writer):
        req = await read_http_request(reader)
        if req is None:
            return
        method, path, headers, _body = req
        try:
            if method == "GET" and path == "/healthz":
                writer.write(json_response_bytes(200, {"status": "ok"}))
            elif method == "GET" and path == "/stats":
                writer.write(
                    json_response_bytes(200, {"active_slots": self.load, "queue_depth": 0})
                )
            elif method == "POST" and path == "/generate":
                self.generates += 1
                self.generate_headers.append(dict(headers))
                writer.write(SSE_HEADER_BYTES)
                for i, token in enumerate(self.tokens):
                    if self.abort_after is not None and i >= self.abort_after:
                        # mid-stream death: close without a done event; a real
                        # worker's engine still finishes and records the request
                        self._record_leg(headers, i)
                        return
                    writer.write(sse_event_bytes({"token_id": token, "token": str(token)}))
                    await writer.drain()
                writer.write(
                    sse_event_bytes(
                        {"done": True, "token_ids": self.tokens, "finish_reason": "budget"}
                    )
                )
                self._record_leg(headers, len(self.tokens))
            await writer.drain()
        finally:
            writer.close()

    def _main(self):
        loop = asyncio.new_event_loop()
        self._loop = loop
        asyncio.set_event_loop(loop)

        async def _bind():
            server = await asyncio.start_server(self._handle, "127.0.0.1", 0)
            self.port = server.sockets[0].getsockname()[1]

        loop.run_until_complete(_bind())
        self._started.set()
        loop.run_forever()
        loop.close()

    def start(self):
        threading.Thread(target=self._main, daemon=True).start()
        self._started.wait(5.0)
        assert self.port is not None
        return self

    def stop(self):
        if self._loop is not None:
            self._loop.call_soon_threadsafe(self._loop.stop)


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
        body = resp.read()
        if (resp.getheader("Content-Type") or "").startswith("application/json"):
            return resp.status, json.loads(body)
        return resp.status, body.decode()
    finally:
        conn.close()


def test_mid_stream_failover_splices_one_answer():
    """Worker A dies after 2 of 5 tokens; the client still sees the 5-token
    answer exactly once, spliced from A's prefix and B's replay."""
    dying = _ScriptedWorker(ANSWER, abort_after=2).start()
    backup = _ScriptedWorker(ANSWER).start()
    registry = MetricsRegistry()
    router = FleetRouter(
        [
            WorkerHandle("dying", "127.0.0.1", dying.port),
            WorkerHandle("backup", "127.0.0.1", backup.port),
        ],
        metrics=registry,
        health_interval_s=30.0,  # no probe mid-test: failover state stays visible
    )
    # the FIRST health round is over before traffic: a probe in flight during the failover would race the
    # unhealthy mark (the next sweep is 30 s out, so after this the failover state stays visible)
    start_and_await_first_sweep(router)
    try:
        status, events = _post_generate(router.port, {"prompt": "x", "max_new_tokens": 5})
        assert status == 200
        streamed = [e["token_id"] for e in events if "token_id" in e]
        assert streamed == ANSWER  # no gap, no duplicated overlap tokens
        done = [e for e in events if e.get("done")]
        assert len(done) == 1 and done[0]["token_ids"] == ANSWER
        assert dying.generates == 1 and backup.generates == 1

        assert router.failovers == 1
        status, table = _get(router.port, "/fleet")
        by_name = {w["name"]: w for w in table["workers"]}
        assert by_name["dying"]["healthy"] is False  # out of rotation
        assert by_name["backup"]["healthy"] is True
        status, text = _get(router.port, "/metrics")
        parsed = parse_prometheus_text(text)
        assert parsed["fleet_failovers_total"][()] == 1.0
        assert parsed["fleet_workers_healthy"][()] == 1.0

        # the dead worker is excluded from routing now: next request goes
        # straight to the backup, no second failover
        status, events = _post_generate(router.port, {"prompt": "x"})
        assert [e["token_id"] for e in events if "token_id" in e] == ANSWER
        assert router.failovers == 1 and dying.generates == 1
    finally:
        router.close()
        dying.stop()
        backup.stop()


def test_least_loaded_routing_and_health_deadline():
    """Routing prefers the lower-load worker once probes scraped /stats, and a
    worker that stops answering probes goes unhealthy after the deadline."""
    idle = _ScriptedWorker(ANSWER, load=0).start()
    busy = _ScriptedWorker(ANSWER, load=7).start()
    router = FleetRouter(
        [
            WorkerHandle("busy", "127.0.0.1", busy.port),  # listed first on purpose
            WorkerHandle("idle", "127.0.0.1", idle.port),
        ],
        health_interval_s=0.05,
        heartbeat_deadline_s=0.4,
    )
    router.start()
    try:
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:  # wait for the first /stats scrape
            if all(w.load == exp for w, exp in zip(router.workers, (7, 0))):
                break
            time.sleep(0.02)
        else:
            pytest.fail("health loop never scraped worker loads")
        for _ in range(2):
            _post_generate(router.port, {"prompt": "x"})
        assert idle.generates == 2 and busy.generates == 0

        # kill the idle worker's listener: probes fail, deadline flips health
        idle.stop()
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            status, health = _get(router.port, "/healthz")
            if health["workers_healthy"] == 1:
                break
            time.sleep(0.05)
        else:
            pytest.fail("dead worker never went unhealthy")
        # traffic keeps flowing on the survivor
        status, events = _post_generate(router.port, {"prompt": "x"})
        assert status == 200
        assert [e["token_id"] for e in events if "token_id" in e] == ANSWER
        assert busy.generates == 1
    finally:
        router.close()
        busy.stop()


def test_no_healthy_workers_is_a_503():
    dead = _ScriptedWorker(ANSWER).start()
    dead.stop()
    router = FleetRouter(
        [WorkerHandle("dead", "127.0.0.1", dead.port)],
        health_interval_s=0.05,
        heartbeat_deadline_s=0.1,
    )
    router.start()
    try:
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            _, health = _get(router.port, "/healthz")
            if health["workers_healthy"] == 0:
                break
            time.sleep(0.05)
        status, body = _post_generate(router.port, {"prompt": "x"})
        assert status == 503 and "error" in body
    finally:
        router.close()


def test_admin_swap_endpoint_on_live_worker():
    """POST /admin/swap on a worker's own front end: the component's handler
    loads the named folder and hot-swaps THAT worker between decode steps."""
    engine = ServingEngine(FakeModel(), {}, max_batch_slots=2, eod_token_id=-1)
    server = ServingHTTPServer(
        engine,
        encode=lambda s: [int(t) for t in s.split()],
        decode=lambda ids: " ".join(str(i) for i in ids),
        port=0,
    )
    worker = EngineWorker("w0", engine, server)
    loads = []
    server.swap_handler = FleetServingComponent._swap_handler(
        worker, lambda folder, **kw: loads.append(folder) or {}
    )
    server.start()
    try:
        conn = http.client.HTTPConnection("127.0.0.1", server.port, timeout=30.0)
        conn.request("POST", "/admin/swap", body=json.dumps({}))
        resp = conn.getresponse()
        assert resp.status == 500  # handler demands a checkpoint_folder
        assert "checkpoint_folder" in json.loads(resp.read())["error"]
        conn.close()

        conn = http.client.HTTPConnection("127.0.0.1", server.port, timeout=30.0)
        conn.request(
            "POST", "/admin/swap", body=json.dumps({"checkpoint_folder": "ring/step9"})
        )
        resp = conn.getresponse()
        payload = json.loads(resp.read())
        conn.close()
        assert resp.status == 200
        assert payload == {"ok": True, "worker": "w0", "weights_generation": 1}
        assert loads == ["ring/step9"]
        assert engine.weights_generation == 1

        # the swap shows on the worker's health surface + serving still works
        status, health = _get(server.port, "/healthz")
        assert health["weights_generation"] == 1
        status, events = _post_generate(server.port, {"prompt": "3 4", "max_new_tokens": 3})
        assert status == 200
        assert [e["token_id"] for e in events if "token_id" in e] == [5 % VOCAB, 6, 7]
    finally:
        server.close()


def test_failover_one_trace_id_across_router_workers_and_stitched_tree(tmp_path):
    """The PR-13 tracing acceptance pin: a mid-stream failover carries ONE
    trace_id end to end — the router's `fleet/request` record, BOTH worker legs
    (the dying scripted worker's record from the propagated X-Trace-Id header,
    and the real server→engine path on the replay leg), and the stitched
    `analyze_fleet` span tree."""
    from modalities_tpu.serving.analyze import (
        format_fleet_trace_tree,
        load_fleet_records,
        stitch_fleet_traces,
    )
    from modalities_tpu.telemetry import Telemetry, set_active_telemetry

    telemetry = Telemetry(
        output_folder_path=tmp_path, watchdog_deadline_s=0.0, use_jax_annotations=False
    )
    prior = set_active_telemetry(telemetry)
    dying = _ScriptedWorker(
        ANSWER, abort_after=2, sink_path=tmp_path / "scripted_worker.jsonl"
    ).start()
    # the replay leg is a REAL worker: ServingHTTPServer + engine, so the
    # header→body→engine.submit→serve_request propagation is the actual code path
    engine = ServingEngine(FakeModel(), {}, max_batch_slots=2, eod_token_id=-1)
    backup = ServingHTTPServer(
        engine,
        encode=lambda s: [int(t) for t in s.split()],
        decode=lambda ids: " ".join(str(i) for i in ids),
        port=0,
    )
    backup.start()
    router = FleetRouter(
        [
            WorkerHandle("dying", "127.0.0.1", dying.port),
            WorkerHandle("backup", "127.0.0.1", backup.port),
        ],
        health_interval_s=30.0,
    )
    start_and_await_first_sweep(router)
    try:
        status, events = _post_generate(
            router.port, {"prompt": "3 4", "max_new_tokens": 5}
        )
        assert status == 200
        done = [e for e in events if e.get("done")]
        assert len(done) == 1
        trace_id = done[0]["trace_id"]
        assert trace_id  # the SSE done event carries the trace back to the client

        # the router SENT the trace headers to the first (dying) worker
        assert dying.generate_headers[0]["x-trace-id"] == trace_id
        assert dying.generate_headers[0]["x-trace-hop"] == "0"
    finally:
        router.close()
        dying.stop()
        backup.close()
        telemetry.close()
        set_active_telemetry(prior)

    records = load_fleet_records([tmp_path])
    # router's half: one fleet/request record naming both legs + one failover
    assert len(records["fleet_requests"]) == 1
    req = records["fleet_requests"][0]
    assert req["trace_id"] == trace_id and req["outcome"] == "done"
    assert [(leg["worker"], leg["hop"]) for leg in req["legs"]] == [
        ("dying", 0), ("backup", 1)
    ]
    assert [f["trace_id"] for f in records["failovers"]] == [trace_id]
    # worker legs: the scripted hop-0 record and the real engine's hop-1 record
    # share the ONE trace_id
    legs = {(r["trace_id"], r["hop"]) for r in records["serve_requests"]}
    assert legs == {(trace_id, 0), (trace_id, 1)}

    traces = stitch_fleet_traces(records)
    assert [t["trace_id"] for t in traces] == [trace_id]
    trace = traces[0]
    assert trace["router"] is req
    assert [leg["hop"] for leg in trace["worker_legs"]] == [0, 1]
    assert len(trace["failovers"]) == 1
    tree = format_fleet_trace_tree(traces)
    assert tree.count(trace_id) == 1  # one request, one tree
    assert "failover off dying" in tree


def test_admin_swap_without_handler_is_503():
    engine = ServingEngine(FakeModel(), {}, max_batch_slots=1, eod_token_id=-1)
    server = ServingHTTPServer(
        engine, encode=lambda s: [3], decode=lambda ids: "", port=0
    )
    server.start()
    try:
        conn = http.client.HTTPConnection("127.0.0.1", server.port, timeout=30.0)
        conn.request("POST", "/admin/swap", body=json.dumps({"checkpoint_folder": "x"}))
        resp = conn.getresponse()
        assert resp.status == 503
        assert "swap handler" in json.loads(resp.read())["error"]
        conn.close()
    finally:
        server.close()
