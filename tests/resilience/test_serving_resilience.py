"""Serving-side resilience chaos (PR 19/20): deadline propagation, SLO-driven
load shedding, retry budgets, circuit breakers, multi-tenant isolation
(weighted DRR admission, quotas, token-rate 429s, burn-aware victim
selection), and the six serving fault points (serve_worker_hang,
serve_slow_decode, handoff_corrupt, sse_torn, queue_storm, tenant_flood).

The flagship scenario is the STORM: a wedged worker plus a queue_storm
arrival burst must degrade into shedding (429s / finish reason "shed") and
deadline cancellations — never into a collapse — while every stream the fleet
DOES deliver stays exactly-once token-for-token and the paged pool audit
(`free + Σ unique owned == num_blocks`) holds afterwards. Deadline
cancellation is pinned at all four seams: queue admission, ring chunk
boundary, decode step boundary, and the disagg import queue."""

import http.client
import json
import logging
import socket
import time

import numpy as np
import pytest

from modalities_tpu.resilience.faults import arm_faults
from modalities_tpu.serving.engine import ServingEngine
from modalities_tpu.serving.resilience import (
    BrownoutController,
    CircuitBreaker,
    ProbeBackoff,
    RetryBudget,
    TenantRegistry,
    TenantSpec,
    TokenBucket,
    deadline_expired,
    default_deadline_ms,
    resolve_deadline_ms,
    resolve_tenant,
)
from modalities_tpu.serving.fleet.router import FleetRouter, WorkerHandle
from modalities_tpu.serving.server import ServingHTTPServer
from modalities_tpu.telemetry.metrics import MetricsRegistry
from tests.conftest import start_and_await_first_sweep
from tests.serving.test_fleet_router import _ScriptedWorker, _get
from tests.serving.test_observability import VOCAB, FakeModel, _tick_clock

ANSWER = [11, 12, 13, 14, 15]


def _engine(**kw):
    kw.setdefault("max_batch_slots", 2)
    return ServingEngine(
        FakeModel(), {}, eod_token_id=-1, metrics=MetricsRegistry(), **kw
    )


def _paged(**kw):
    kw.setdefault("paged_block_size", 4)
    kw.setdefault("paged_max_len", 16)
    return _engine(kv_cache="paged", **kw)


def _post(port, path, body, headers=None, timeout=30.0):
    """POST returning (status, events-or-error, response headers)."""
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        h = {"Content-Type": "application/json"}
        h.update(headers or {})
        conn.request("POST", path, body=json.dumps(body), headers=h)
        resp = conn.getresponse()
        raw = resp.read()
        resp_headers = dict(resp.getheaders())
        if resp.status != 200:
            return resp.status, json.loads(raw), resp_headers
        events = [
            json.loads(chunk[len(b"data: "):])
            for chunk in raw.split(b"\n\n")
            if chunk.startswith(b"data: ")
        ]
        return resp.status, events, resp_headers
    finally:
        conn.close()


# ------------------------------------------------------- resilience primitives


def test_brownout_controller_queue_hysteresis():
    ctl = BrownoutController(queue_high=4, queue_low=2)
    assert ctl.update(3) == "ok" and not ctl.active
    assert ctl.shed_target(3) == 0  # inactive controller never sheds
    assert ctl.update(4) == "brownout" and ctl.active
    assert ctl.shed_target(6) == 4  # down to queue_low, not to zero
    # hysteresis: dropping below queue_high is NOT enough to recover
    assert ctl.update(3) == "brownout"
    assert ctl.update(2) == "ok"
    assert ctl.transitions == 2


def test_brownout_controller_slo_signal_and_defaults():
    breaching = {"v": True}
    ctl = BrownoutController(lambda: breaching["v"], queue_high=None)
    assert ctl.queue_low == 0  # purely SLO-driven: drain the whole queue
    assert ctl.update(0) == "brownout"
    breaching["v"] = False
    assert ctl.update(0) == "ok"
    assert BrownoutController(queue_high=8).queue_low == 4  # default: high // 2
    with pytest.raises(ValueError, match="breaching_fn or queue_high"):
        BrownoutController()


def test_circuit_breaker_trip_probe_and_recovery():
    clock = {"t": 0.0}
    cb = CircuitBreaker(
        failure_threshold=3, open_s=1.0, max_open_s=4.0, jitter=0.0,
        time_fn=lambda: clock["t"],
    )
    assert cb.allow() and cb.state == "closed"
    cb.record_failure(); cb.record_failure()
    assert cb.allow()  # two consecutive failures: still closed
    cb.record_failure()
    assert cb.state == "open" and cb.state_value() == 2.0
    assert not cb.allow()
    clock["t"] = 1.0  # backoff elapsed: exactly ONE half-open probe
    assert cb.allow() and cb.state == "half_open" and cb.state_value() == 1.0
    assert not cb.allow()
    cb.record_failure()  # the probe failed: re-open with DOUBLED backoff
    assert cb.state == "open"
    clock["t"] = 2.5
    assert not cb.allow()  # 1s would have elapsed; the doubled 2s has not
    clock["t"] = 3.1
    assert cb.allow()
    cb.record_success()
    assert cb.state == "closed" and cb.failures == 0 and cb.state_value() == 0.0
    # success also reset the backoff to base
    cb.record_failure(); cb.record_failure(); cb.record_failure()
    assert clock["t"] + 1.0 == cb._until


def test_retry_budget_is_funded_by_successes():
    budget = RetryBudget(ratio=0.5, cap=2.0, initial=1.0)
    assert budget.try_retry() and budget.tokens == 0.0
    assert not budget.try_retry() and budget.exhausted == 1
    for _ in range(6):
        budget.record_success()
    assert budget.tokens == 2.0  # capped, not 3.0
    assert budget.try_retry() and budget.try_retry()
    assert not budget.try_retry() and budget.exhausted == 2


def test_retry_budget_ratio_from_env(monkeypatch):
    monkeypatch.setenv("MODALITIES_TPU_FLEET_RETRY_BUDGET_RATIO", "0.5")
    assert RetryBudget().ratio == 0.5
    monkeypatch.delenv("MODALITIES_TPU_FLEET_RETRY_BUDGET_RATIO")
    assert RetryBudget().ratio == 0.2


def test_probe_backoff_doubles_with_jitter_and_resets(monkeypatch):
    monkeypatch.setenv("MODALITIES_TPU_FLEET_PROBE_BACKOFF_MAX_S", "2.0")
    backoff = ProbeBackoff(base_s=0.5, jitter=0.25, rng=lambda: 1.0)
    assert backoff.max_s == 2.0 and backoff.due(0.0)
    backoff.failed(0.0)
    assert not backoff.due(0.6)  # 0.5 * (1 + 0.25) = 0.625
    assert backoff.due(0.7)
    backoff.failed(0.7)  # delay doubled to 1.0 -> jittered 1.25
    assert not backoff.due(1.9) and backoff.due(1.95)
    backoff.failed(2.0); backoff.failed(5.0)
    assert backoff._delay == 2.0  # capped at max_s
    assert backoff.failures == 4
    backoff.reset()
    assert backoff.due(0.0) and backoff.failures == 0


def test_resolve_deadline_ms_header_env_and_garbage(monkeypatch):
    monkeypatch.delenv("MODALITIES_TPU_SERVE_DEADLINE_DEFAULT_MS", raising=False)
    assert default_deadline_ms() is None
    assert resolve_deadline_ms(None) is None
    assert resolve_deadline_ms("250") == 250.0  # client header wins
    assert resolve_deadline_ms(-5) is None  # explicit non-positive: disabled
    monkeypatch.setenv("MODALITIES_TPU_SERVE_DEADLINE_DEFAULT_MS", "1500")
    assert resolve_deadline_ms(None) == 1500.0
    assert resolve_deadline_ms("nonsense") == 1500.0  # unparseable -> default
    assert resolve_deadline_ms(40) == 40.0
    monkeypatch.setenv("MODALITIES_TPU_SERVE_DEADLINE_DEFAULT_MS", "0")
    assert resolve_deadline_ms(None) is None
    # the seam predicate measures from LOCAL arrival, clamped at 0
    assert not deadline_expired(0.0, 100.0, 0.05)
    assert deadline_expired(0.0, 100.0, 0.1)
    assert not deadline_expired(-3.0, 100.0, 0.05)  # negative arrival clamps
    assert not deadline_expired(0.0, None, 1e9)


# --------------------------------------------- deadline seams (engine-level)


def test_deadline_seam1_expires_in_queue_before_dispatch():
    """Seam 1: a queued request whose deadline lapses is cancelled at the next
    admission sweep — finish reason "deadline", ZERO tokens (it never reached
    a decode step), and the slot-holder in front of it is untouched."""
    engine = _engine(max_batch_slots=1, time_fn=_tick_clock())
    rid_busy = engine.submit([3], 6, temperature=0.0, seed=0)
    rid_dead = engine.submit([7], 6, temperature=0.0, seed=1, deadline_ms=0.5)
    results = engine.run()
    assert results[rid_busy].finish_reason == "budget"
    assert results[rid_busy].tokens == [(3 + i) % VOCAB for i in range(1, 7)]
    assert results[rid_dead].finish_reason == "deadline"
    assert results[rid_dead].tokens == []
    stats = engine.stats()
    assert stats["deadline_expired_requests"] == 1
    assert all(s is None for s in engine._slot_states)


def test_deadline_seam2_expires_at_ring_chunk_boundary():
    """Seam 2: the ring prefill ladder re-checks the deadline BETWEEN chunks.
    The clock jumps 10s once the first chunk has been dispatched, so the
    21-token prompt (16 + 4 + 1 ladder) dies mid-prefill: reason "deadline",
    no first token, and no further chunk is ever dispatched."""
    state = {"t": 0.0, "eng": None}

    def clock():
        state["t"] += 0.001
        eng = state["eng"]
        chunks = eng._m_prefill_chunks.value() if eng is not None else 0
        return state["t"] + (10.0 if chunks >= 1 else 0.0)

    engine = _engine(
        max_batch_slots=1, cache_capacity=64, prefill_chunks=(16, 4, 1),
        time_fn=clock,
    )
    state["eng"] = engine
    rid = engine.submit(list(range(21)), 4, temperature=0.0, seed=0,
                        deadline_ms=5000.0)
    results = engine.run()
    assert results[rid].finish_reason == "deadline"
    assert results[rid].tokens == []
    assert engine._m_prefill_chunks.value() == 1  # the ladder stopped at chunk 1
    assert engine.stats()["deadline_expired_requests"] == 1
    assert all(s is None for s in engine._slot_states)


def test_deadline_seam3_expires_at_decode_step_boundary():
    """Seam 3: an ACTIVE decoder whose deadline lapses is cancelled between
    decode steps — it keeps the tokens already delivered, finishes "deadline",
    and its blocks return to the paged pool (audit exact)."""
    tokens_seen = {"n": 0}
    state = {"t": 0.0}

    def clock():
        state["t"] += 0.001
        return state["t"] + (10.0 if tokens_seen["n"] >= 2 else 0.0)

    engine = _paged(
        max_batch_slots=1, time_fn=clock,
        on_token=lambda rid, tok: tokens_seen.__setitem__("n", tokens_seen["n"] + 1),
    )
    rid = engine.submit([3, 4, 5], 8, temperature=0.0, seed=0, deadline_ms=5000.0)
    results = engine.run()
    assert results[rid].finish_reason == "deadline"
    assert 1 <= len(results[rid].tokens) < 8  # mid-flight, not post-hoc
    assert results[rid].tokens == [(5 + i) % VOCAB
                                   for i in range(1, len(results[rid].tokens) + 1)]
    stats = engine.stats()
    assert stats["deadline_expired_requests"] == 1
    assert stats["free_blocks"] == stats["num_blocks"]
    engine._table_state.check()
    assert all(s is None for s in engine._slot_states)


def test_deadline_seam4_rides_handoff_and_expires_at_import():
    """Seam 4: the deadline rides the sealed HandoffRecord (outside the
    digest, like the trace id), restarts from the decode tier's LOCAL arrival,
    and an expired import is cancelled at the sweep BEFORE any block
    allocation or payload scatter."""
    from modalities_tpu.serving.disagg.handoff import HandoffRecord

    peng = _paged(role="prefill", time_fn=_tick_clock(1e-6))
    rid = peng.submit([3, 4, 5], 5, temperature=0.0, seed=0, deadline_ms=40.0)
    record = peng.run()[rid].handoff
    assert record is not None and record.deadline_ms == 40.0
    # the wire roundtrip preserves it
    wired = HandoffRecord.from_wire(record.to_wire())
    assert wired.deadline_ms == 40.0
    wired.verify_digest()  # deadline sits OUTSIDE the digest

    deng = _paged(role="decode", time_fn=_tick_clock(0.05))  # 50ms per read
    drid = deng.import_handoff(wired)
    results = deng.run()
    assert results[drid].finish_reason == "deadline"
    assert results[drid].tokens == []
    stats = deng.stats()
    assert stats["deadline_expired_requests"] == 1
    assert stats["handoffs_imported"] == 0  # cancelled before admission
    assert stats["free_blocks"] == stats["num_blocks"]
    deng._table_state.check()


def test_handoff_corrupt_fault_is_rejected_by_digest():
    """Chaos: handoff_corrupt@rid flips one payload byte AFTER sealing; the
    decode tier's digest check must reject the import as retryable
    (digest_mismatch) rather than decode from corrupt KV."""
    from modalities_tpu.serving.disagg.handoff import HandoffRejected

    arm_faults("handoff_corrupt@0")
    peng = _paged(role="prefill")
    rid = peng.submit([3, 4, 5], 5, temperature=0.0, seed=0)
    record = peng.run()[rid].handoff
    deng = _paged(role="decode")
    with pytest.raises(HandoffRejected) as exc:
        deng.import_handoff(record)
    assert exc.value.reason == "digest_mismatch"
    assert deng._m_handoff_failures.value(reason="digest_mismatch") == 1
    # nothing was admitted: the decode pool is untouched
    stats = deng.stats()
    assert stats["free_blocks"] == stats["num_blocks"]


# --------------------------------------------------------- overload protection


def test_queue_limit_and_note_rejected(monkeypatch):
    engine = _engine(max_batch_slots=1, max_queue_depth=1)
    assert engine.overload_reason() is None
    engine.submit([3], 2, temperature=0.0, seed=0)
    assert engine.overload_reason() == "queue_full"
    engine.note_rejected("queue_full")
    assert engine.stats()["shed_requests"] == 1
    # env default: MODALITIES_TPU_SERVE_QUEUE_LIMIT, 0 = unbounded
    monkeypatch.setenv("MODALITIES_TPU_SERVE_QUEUE_LIMIT", "3")
    assert _engine().max_queue_depth == 3
    monkeypatch.setenv("MODALITIES_TPU_SERVE_QUEUE_LIMIT", "0")
    assert _engine().max_queue_depth is None


def test_http_429_retry_after_under_brownout():
    """SLO-driven brownout at the HTTP seam: once the fast-window signal
    breaches, already-QUEUED work is shed (the waiting client sees finish
    reason "shed" on its stream) and NEW arrivals get 429 + Retry-After
    without ever reaching the engine queue."""
    import threading

    breaching = {"v": False}
    engine = _paged(
        max_batch_slots=1, paged_block_size=16, paged_max_len=2048,
        brownout=BrownoutController(lambda: breaching["v"], queue_high=None),
    )
    server = ServingHTTPServer(
        engine, encode=lambda s: [int(t) for t in s.split()],
        decode=lambda ids: " ".join(str(i) for i in ids), port=0,
    )
    server.start()
    outcomes = {}

    def post(key, body):
        outcomes[key] = _post(server.port, "/generate", body)

    try:
        # A holds the single slot for ~1000 decode steps; B queues behind it
        ta = threading.Thread(target=post, args=("a", {"prompt": "3", "max_new_tokens": 1000}))
        ta.start()
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline and engine.stats()["active_slots"] == 0:
            time.sleep(0.005)
        tb = threading.Thread(target=post, args=("b", {"prompt": "5", "max_new_tokens": 3}))
        tb.start()
        while time.monotonic() < deadline and engine.stats()["queue_depth"] == 0:
            time.sleep(0.002)
        assert engine.stats()["queue_depth"] == 1, "B never queued behind A"
        breaching["v"] = True  # the SLO burn trips: brownout next sweep
        tb.join(timeout=10.0)
        status, events, _ = outcomes["b"]
        assert status == 200
        done = [e for e in events if e.get("done")]
        assert len(done) == 1 and done[0]["finish_reason"] == "shed"
        assert done[0]["token_ids"] == []
        # new arrivals are refused at the door while browned out
        status, body, headers = _post(server.port, "/generate", {"prompt": "7"})
        assert status == 429
        assert body["reason"] == "brownout_reject"
        # derived Retry-After (PR 20): the queue already drained to the
        # brownout floor, so the estimate bottoms out at the 1 s minimum
        assert headers.get("Retry-After") == "1"
        # the slot-holder is untouched by the brownout: exactly-once delivery
        ta.join(timeout=30.0)
        status, events, _ = outcomes["a"]
        assert status == 200
        a_done = [e for e in events if e.get("done")][0]
        assert a_done["finish_reason"] == "budget"
        assert len(a_done["token_ids"]) == 1000
    finally:
        server.close()
    assert engine.stats()["shed_requests"] == 2  # one queue shed + one 429


def test_serve_slow_decode_fault_stalls_one_step():
    """Chaos: serve_slow_decode:ms wedges exactly one decode dispatch — TPOT
    burns but tokens stay bitwise identical to the unfaulted run."""
    arm_faults("serve_slow_decode:60")
    engine = _engine(max_batch_slots=1)
    rid = engine.submit([3], 3, temperature=0.0, seed=0)
    t0 = time.monotonic()
    results = engine.run()
    assert time.monotonic() - t0 >= 0.06
    assert results[rid].finish_reason == "budget"
    assert results[rid].tokens == [4, 5, 6]


# ------------------------------------------------ multi-tenant isolation (PR 20)


def test_tenant_spec_and_registry_validation():
    with pytest.raises(ValueError, match="class"):
        TenantSpec("x", tenant_class="batch")
    with pytest.raises(ValueError, match="weight"):
        TenantSpec("x", weight=0)
    with pytest.raises(ValueError, match="max_slots"):
        TenantSpec("x", max_slots=0)
    with pytest.raises(ValueError, match="rate"):
        TenantSpec("x", rate=0.0)
    with pytest.raises(ValueError, match="unknown keys"):
        TenantRegistry.from_config({"x": {"wieght": 2}})
    reg = TenantRegistry.from_config({
        "b": {"class": "bulk", "weight": 2, "rate": 5.0},
        "a": {"max_slots": 3},
    })
    assert reg.names() == ["a", "b"]  # sorted: the DRR rotation is deterministic
    assert reg.spec("b").is_bulk
    assert reg.spec("b").burst == 5.0  # default burst: one second of rate
    assert reg.spec("a").max_slots == 3 and reg.spec("a").rate is None
    # an undeclared tenant degrades to best-effort defaults, not an error
    ghost = reg.spec("ghost")
    assert not ghost.is_bulk and ghost.weight == 1.0 and ghost.max_slots is None


def test_resolve_tenant_and_engine_seam(monkeypatch):
    monkeypatch.delenv("MODALITIES_TPU_SERVE_TENANT_DEFAULT", raising=False)
    assert resolve_tenant(None) == "default"
    assert resolve_tenant("  ") == "default"
    assert resolve_tenant(" acme ") == "acme"
    monkeypatch.setenv("MODALITIES_TPU_SERVE_TENANT_DEFAULT", "team-a")
    assert resolve_tenant(None) == "team-a"
    # the shared ingress seam: tenants OFF collapses every id to the implicit
    # "" tenant (no per-tenant series, the HEAD scheduler); tenants ON resolves
    assert _engine().resolve_submit_tenant("acme") == ""
    on = _engine(tenants=TenantRegistry.from_config({"acme": {}}))
    assert on.resolve_submit_tenant(None) == "team-a"
    assert on.resolve_submit_tenant("acme") == "acme"


def test_token_bucket_refill_and_retry_after():
    with pytest.raises(ValueError, match="rate > 0"):
        TokenBucket(0.0, 1.0)
    bucket = TokenBucket(rate=10.0, burst=20.0)
    assert bucket.try_take(20.0, now=0.0)  # the full burst fits...
    assert not bucket.try_take(5.0, now=0.0)  # ...and a refusal never partial-charges
    assert bucket.retry_after_s(5.0, now=0.0) == 0.5  # exact refill time
    assert bucket.try_take(5.0, now=0.5)
    # demand beyond the bucket depth reports the FULL-burst refill, not never
    assert bucket.retry_after_s(1000.0, now=0.5) == 2.0


def test_rate_limit_gate_charges_bucket_and_derives_retry_after():
    clock = {"t": 0.0}
    engine = _engine(
        tenants=TenantRegistry.from_config({"metered": {"rate": 4.0, "burst": 8.0}}),
        time_fn=lambda: clock["t"],
    )
    # two 4-token admissions drain the burst; each one charged the bucket
    assert engine.tenant_reject_reason("metered", 4) is None
    assert engine.tenant_reject_reason("metered", 4) is None
    reason, retry_after = engine.tenant_reject_reason("metered", 4)
    assert reason == "rate_limited"
    assert retry_after == 1.0  # 4 tokens at 4 tokens/s
    clock["t"] = 1.0
    assert engine.tenant_reject_reason("metered", 4) is None  # refilled
    # unmetered tenants / tenant-off engines are never throttled
    assert engine.tenant_reject_reason("ghost", 10_000) is None
    assert _engine().tenant_reject_reason("metered", 10_000) is None
    # the HTTP layer charges a 429 to the tenant's shed + rate-limit series
    engine.note_rejected("rate_limited", tenant="metered")
    assert engine._m_tenant_rate_limited.value(tenant="metered") == 1
    assert engine._m_tenant_shed.value(tenant="metered") == 1


def test_retry_after_derived_from_queue_state():
    engine = _engine(max_queue_depth=1)  # 2 slots (_engine default)
    for i in range(5):
        engine.submit([3], 1, temperature=0.0, seed=i)
    # 5 queued over a limit of 1: 5 excess requests / 2-slot drain width
    assert engine.retry_after_s("queue_full") == 3.0
    assert engine.retry_after_s("unknown") == 1.0
    browned = _engine(brownout=BrownoutController(queue_high=4, queue_low=2))
    for i in range(6):
        browned.submit([3], 1, temperature=0.0, seed=i)
    # recovery needs the queue at/below queue_low=2: 4 excess over 2 slots
    assert browned.retry_after_s("brownout_reject") == 2.0
    # floor: an already-drained queue never tells the client 0
    assert _engine(max_queue_depth=8).retry_after_s("queue_full") == 1.0


def test_drr_admission_converges_to_weight_ratio():
    registry = TenantRegistry.from_config(
        {"gold": {"weight": 3}, "bronze": {"weight": 1}}
    )
    engine = _engine(max_batch_slots=1, tenants=registry, time_fn=_tick_clock())
    rids = {"gold": [], "bronze": []}
    for i in range(6):
        rids["gold"].append(
            engine.submit([3], 1, temperature=0.0, seed=i, tenant="gold")
        )
        rids["bronze"].append(
            engine.submit([5], 1, temperature=0.0, seed=i, tenant="bronze")
        )
    results = engine.run()
    tenant_of = {r: t for t, tenant_rids in rids.items() for r in tenant_rids}
    order = sorted(results, key=lambda r: results[r].first_token_s)
    first8 = [tenant_of[r] for r in order[:8]]
    # bronze banks 1 credit per rotation, gold banks 3: a 3:1 admission ratio
    assert first8.count("gold") == 6 and first8.count("bronze") == 2
    # FIFO within a tenant survives the interleave
    for tenant_rids in rids.values():
        firsts = [results[r].first_token_s for r in tenant_rids]
        assert firsts == sorted(firsts)
    assert all(s is None for s in engine._slot_states)


def test_victim_selection_is_burn_aware():
    budgets = {"inter": 0.1, "bulk": 0.9, "greedy": 0.5}
    registry = TenantRegistry.from_config({
        "inter": {"class": "interactive", "weight": 1, "max_slots": 2},
        "bulk": {"class": "bulk", "weight": 1},
        "greedy": {"class": "interactive", "weight": 1, "max_slots": 1},
    })
    engine = _engine(
        max_batch_slots=2, tenants=registry,
        tenant_budget_fn=lambda t: budgets[t],
    )
    counts = {"inter": 1, "bulk": 1}
    total = engine._demand_weight(counts)
    # a bulk candidate always outranks an under-budget interactive tenant
    assert engine._victim_key("bulk", counts, total) > engine._victim_key(
        "inter", counts, total
    )
    # ...but an over-quota tenant outranks even bulk
    counts = {"greedy": 2, "bulk": 1}
    total = engine._demand_weight(counts)
    assert engine._victim_key("greedy", counts, total) > engine._victim_key(
        "bulk", counts, total
    )
    # ties inside a class break on the LEAST-burned budget (max remaining)
    key_fresh = engine._victim_key("bulk", {}, 0.0)
    budgets["bulk"] = 0.2
    assert key_fresh > engine._victim_key("bulk", {}, 0.0)


def test_http_tenant_rate_limit_429_with_refill_retry_after():
    """X-Tenant-Id rides the header seam like X-Deadline-Ms: a metered tenant
    that outruns its token bucket gets a per-tenant 429 whose Retry-After is
    the bucket's refill time, while other tenants sail through."""
    engine = _engine(
        tenants=TenantRegistry.from_config({"metered": {"rate": 0.5, "burst": 4.0}})
    )
    server = ServingHTTPServer(
        engine, encode=lambda s: [int(t) for t in s.split()],
        decode=lambda ids: " ".join(str(i) for i in ids), port=0,
    )
    server.start()
    try:
        body = {"prompt": "3", "max_new_tokens": 4}
        status, _events, _h = _post(
            server.port, "/generate", body, headers={"X-Tenant-Id": "metered"}
        )
        assert status == 200  # charged the full burst, served normally
        status, err, headers = _post(
            server.port, "/generate", body, headers={"X-Tenant-Id": "metered"}
        )
        assert status == 429 and err["reason"] == "rate_limited"
        # refill-derived: 4 tokens at 0.5/s is ~8 s, rounded up, never 0
        assert 1 <= int(headers["Retry-After"]) <= 8
        # an unmetered tenant is untouched by the neighbor's empty bucket
        status, _events, _h = _post(
            server.port, "/generate", body, headers={"X-Tenant-Id": "other"}
        )
        assert status == 200
        assert engine._m_tenant_rate_limited.value(tenant="metered") == 1
        assert engine.stats()["tenants"]["metered"]["rate_limited"] == 1
    finally:
        server.close()


def test_tenant_flood_chaos_isolates_the_interactive_tenant():
    """The PR-20 acceptance flood: tenant_flood amplifies the first submit
    with 6 bulk-tenant clones while a brownout controller is armed. The DRR
    scheduler + burn-aware shedder must contain the noisy neighbor: every
    interactive stream is bitwise identical to its flood-free twin, the
    interactive tenant is never shed or preempted, ALL sheds land on the
    bulk tenant (counter-pinned on serve_tenant_shed_total{tenant="bulk"}),
    the paged pool audit stays exact, and the decode path never recompiles."""
    cfg = {
        "interactive": {"class": "interactive", "weight": 4},
        "bulk": {"class": "bulk", "weight": 1},
    }
    reqs = [([3, 4, 5], 3, seed) for seed in range(3)]

    # the flood-free twin first: the reference tokens
    twin = _paged(tenants=TenantRegistry.from_config(cfg))
    twin_rids = [
        twin.submit(p, b, temperature=0.0, seed=s, tenant="interactive")
        for p, b, s in reqs
    ]
    twin_results = twin.run()
    twin_tokens = [twin_results[rid].tokens for rid in twin_rids]

    arm_faults("tenant_flood@0:6")
    engine = _paged(
        tenants=TenantRegistry.from_config(cfg),
        brownout=BrownoutController(queue_high=4, queue_low=4),
    )
    rids = [
        engine.submit(p, b, temperature=0.0, seed=s, tenant="interactive")
        for p, b, s in reqs
    ]
    results = engine.run()
    assert len(results) == 9  # 3 interactive + 6 flood clones
    flood_rids = set(results) - set(rids)

    # every interactive stream: bitwise equal to the twin, finished "budget"
    for rid, want in zip(rids, twin_tokens):
        assert results[rid].finish_reason == "budget"
        assert results[rid].tokens == want
    # the brownout shed ONLY flood clones: depth 9 -> queue_low 4 = 5 victims
    shed = {r for r, res in results.items() if res.finish_reason == "shed"}
    assert shed <= flood_rids and len(shed) == 5
    assert all(results[r].tokens == [] for r in shed)
    # counter pin: every shed charged to the bulk tenant, none to interactive
    assert engine._m_tenant_shed.value(tenant="bulk") == 5
    assert engine._m_tenant_shed.value(tenant="interactive") == 0
    assert engine._m_tenant_preempt.value(tenant="interactive") == 0
    stats = engine.stats()
    assert stats["shed_requests"] == 5
    assert stats["tenants"]["interactive"]["shed"] == 0
    assert stats["tenants"]["interactive"]["finished"] == 3
    assert stats["tenants"]["bulk"]["shed"] == 5
    # the pool audit holds and the flood never forced a recompile
    assert stats["free_blocks"] == stats["num_blocks"]
    engine._table_state.check()
    assert all(s is None for s in engine._slot_states)
    assert stats["decode_executables"] == 1
    assert stats["prefill_executables"] == 1


# ----------------------------------------------------------- the chaos storm


def test_chaos_storm_sheds_and_cancels_instead_of_collapsing():
    """The PR-19 acceptance storm: a queue_storm arrival burst lands while
    serve_worker_hang wedges the scheduler. The engine must (a) deliver every
    surviving stream token-for-token, (b) shed the synthetic burst (reason
    "shed") without ever dispatching a decode step for it, (c) cancel the
    lapsed-deadline request at the queue seam, and (d) leave the paged pool
    audit (`free + Σ unique owned == num_blocks`) exact."""
    arm_faults("serve_worker_hang:0.06,queue_storm@1:6")
    engine = _paged(
        max_batch_slots=1,
        brownout=BrownoutController(queue_high=4, queue_low=4),
    )
    rid0 = engine.submit([3, 4, 5], 3, temperature=0.0, seed=0)
    rid1 = engine.submit([3, 4, 5], 3, temperature=0.0, seed=1)  # storm trigger
    rid2 = engine.submit([3, 4, 5], 3, temperature=0.0, seed=2, deadline_ms=5.0)
    rid3 = engine.submit([3, 4, 5], 3, temperature=0.0, seed=3)
    t0 = time.monotonic()
    results = engine.run()
    assert time.monotonic() - t0 >= 0.06  # the hang really fired
    assert len(results) == 10  # 4 submitted + 6 storm clones

    # (a) every delivered stream is exact: no token dropped, none duplicated
    for rid in (rid0, rid1, rid3):
        assert results[rid].finish_reason == "budget"
        assert results[rid].tokens == [6, 7, 8]
    # (b) the storm was shed, and shed work never decoded a single token
    shed = {r for r, res in results.items() if res.finish_reason == "shed"}
    assert shed == set(results) - {rid0, rid1, rid2, rid3}
    assert all(results[r].tokens == [] for r in shed)
    # (c) the 5ms-deadline request lapsed during the hang and was cancelled
    #     at the queue seam — zero tokens, so it never dispatched either
    assert results[rid2].finish_reason == "deadline"
    assert results[rid2].tokens == []
    stats = engine.stats()
    assert stats["shed_requests"] == 6
    assert stats["deadline_expired_requests"] == 1
    # (d) the pool audit holds after the storm
    assert stats["free_blocks"] == stats["num_blocks"]
    engine._table_state.check()
    assert all(s is None for s in engine._slot_states)
    # the non-deadline path stayed on the pinned executables
    assert stats["decode_executables"] == 1
    assert stats["prefill_executables"] == 1


def test_sse_torn_failover_delivers_exactly_once():
    """Chaos: sse_torn cuts worker w0's first stream after one token. The
    fleet router fails over to w1 and splices — the client still sees the
    full deterministic answer exactly once, token-for-token."""
    arm_faults("sse_torn@1")
    engines, servers = [], []
    for _ in range(2):
        engine = _engine()
        server = ServingHTTPServer(
            engine, encode=lambda s: [int(t) for t in s.split()],
            decode=lambda ids: " ".join(str(i) for i in ids), port=0,
        )
        server.start()
        engines.append(engine); servers.append(server)
    router = FleetRouter(
        [WorkerHandle(f"w{i}", "127.0.0.1", s.port) for i, s in enumerate(servers)],
        metrics=MetricsRegistry(), health_interval_s=30.0,
    )
    start_and_await_first_sweep(router)
    try:
        status, events, _ = _post(
            router.port, "/generate", {"prompt": "3 4", "max_new_tokens": 5}
        )
        assert status == 200
        streamed = [e["token_id"] for e in events if "token_id" in e]
        done = [e for e in events if e.get("done")]
        assert len(done) == 1
        assert streamed == [5, 6, 7, 8, 9]  # FakeModel: (tok + 1) % VOCAB
        assert done[0]["token_ids"] == streamed  # exactly-once, token-for-token
        assert router.failovers == 1
        assert router._breakers["w0"].failures == 1  # the tear was charged
    finally:
        router.close()
        for server in servers:
            server.close()


def test_retry_budget_exhaustion_is_counter_pinned():
    """A fleet-wide flap (every replay target dies too) must degrade into a
    BOUNDED number of retries: with a budget of exactly one token, the second
    failover is refused — the client gets a retry-budget error event, the
    counter and /fleetz both record it, and no further worker is attacked."""
    dying1 = _ScriptedWorker(ANSWER, abort_after=2).start()
    dying2 = _ScriptedWorker(ANSWER, abort_after=2).start()
    backup = _ScriptedWorker(ANSWER).start()
    registry = MetricsRegistry()
    router = FleetRouter(
        [
            WorkerHandle("dying1", "127.0.0.1", dying1.port),
            WorkerHandle("dying2", "127.0.0.1", dying2.port),
            WorkerHandle("backup", "127.0.0.1", backup.port),
        ],
        metrics=registry, health_interval_s=30.0,
    )
    router.retry_budget = RetryBudget(ratio=0.0, cap=1.0)  # one funded retry
    start_and_await_first_sweep(router)
    try:
        status, events, _ = _post(
            router.port, "/generate", {"prompt": "x"},
            headers={"X-Deadline-Ms": "60000"},
        )
        assert status == 200  # SSE headers went out before the flap
        assert [e["token_id"] for e in events if "token_id" in e] == ANSWER[:2]
        assert not any(e.get("done") for e in events)
        assert any("retry budget" in str(e.get("error", "")) for e in events)
        # exactly ONE funded retry: dying2 was attacked once, backup never
        assert dying1.generates == 1 and dying2.generates == 1
        assert backup.generates == 0
        assert router.retry_budget.exhausted == 1
        # the deadline rode the router: BOTH legs carried X-Deadline-Ms
        assert dying1.generate_headers[0]["x-deadline-ms"] == "60000"
        assert dying2.generate_headers[0]["x-deadline-ms"] == "60000"
        # /fleet surfaces budget + per-worker circuit state
        _, table = _get(router.port, "/fleet")
        assert table["retry_budget_exhausted"] == 1
        assert table["retry_budget_tokens"] == 0.0
        circuits = {w["name"]: w["circuit"] for w in table["workers"]}
        assert set(circuits) == {"dying1", "dying2", "backup"}
        assert all(state == "closed" for state in circuits.values())
    finally:
        router.close()
        for worker in (dying1, dying2, backup):
            worker.stop()


def test_dead_worker_probe_backoff_and_deduped_log():
    """Satellite: probes of a DEAD worker back off exponentially (jittered)
    and the probe-failure log collapses to ONE line per outage instead of one
    per probe."""
    sock = socket.socket()
    sock.bind(("127.0.0.1", 0))
    dead_port = sock.getsockname()[1]
    sock.close()  # nothing listens here: every probe fails fast
    router = FleetRouter(
        [WorkerHandle("w0", "127.0.0.1", dead_port)],
        metrics=MetricsRegistry(), health_interval_s=0.05,
        heartbeat_deadline_s=0.05,
    )
    # handler attached directly: the health loop logs from the router thread
    records = []
    handler = logging.Handler()
    handler.emit = records.append
    router_logger = logging.getLogger("modalities_tpu.serving.fleet.router")
    prior_level = router_logger.level
    router_logger.addHandler(handler)
    router_logger.setLevel(logging.INFO)
    try:
        router.start()
        try:
            deadline = time.monotonic() + 5.0
            while time.monotonic() < deadline:
                if router._probe_backoff["w0"].failures >= 2:
                    break
                time.sleep(0.02)
            else:
                pytest.fail("dead-worker probes never entered backoff")
        finally:
            router.close()
    finally:
        router_logger.removeHandler(handler)
        router_logger.setLevel(prior_level)
    assert not router.workers[0].healthy
    probe_lines = [
        r for r in records if "probe of dead worker" in r.getMessage()
    ]
    assert len(probe_lines) == 1  # deduped: one line for the whole outage
