"""Disaggregated prefill/decode serving acceptance (ISSUE 18).

The tentpole invariant: splitting serving into a prefill tier and a decode
tier with an explicit KV handoff changes WHERE work runs, never the tokens —
disaggregated output is bitwise the combined paged engine's, greedy AND
sampled. Around that pin: per-tier executable discipline (prefill workers
never build the decode step, decode workers never build prefill), the wire
contract of the versioned HandoffRecord (digest / generation / version /
config gates with their `disagg_handoff_failures_total` reasons), pool-full
import requeues that never corrupt resident streams, int8 payloads shipping
verbatim at ~half the bf16 bytes, prefix sharing + speculative decoding on
imported blocks, and the DisaggRouter's two-leg HTTP flow: one SSE answer,
ONE trace_id across the router record and both worker legs (stitched by
analyze_fleet), and a decode-leg failover that replays via fresh prefill with
an exact token splice.
"""

import asyncio
import copy
import http.client
import json
import threading

import jax
import numpy as np
import pytest
from flax.core import meta

from modalities_tpu.resilience.events import counts_since, snapshot_counts
from modalities_tpu.serving.disagg.handoff import (
    HANDOFF_VERSION,
    HandoffRecord,
    HandoffRejected,
)
from modalities_tpu.serving.disagg.pair import DisaggPair
from modalities_tpu.serving.disagg.router import DisaggRouter
from modalities_tpu.serving.engine import ServingEngine
from modalities_tpu.serving.fleet.router import WorkerHandle
from modalities_tpu.serving.server import (
    SSE_HEADER_BYTES,
    ServingHTTPServer,
    json_response_bytes,
    read_http_request,
    sse_event_bytes,
)
from modalities_tpu.telemetry.metrics import MetricsRegistry
from tests.conftest import start_and_await_first_sweep
from tests.models.test_gpt2_model import tiny_gpt2

# mixed greedy/sampled, short/multi-block (17 tokens spans 3 blocks at bs=8),
# plus a budget-1 request that short-circuits at the prefill tier (no decode
# leg: the handoff would carry an empty budget)
REQS = [
    ([3, 17, 42, 9, 77], 8, 0.0, 0),
    ([7, 7, 7], 5, 0.8, 1),
    (list(range(1, 18)), 6, 0.0, 2),
    ([99, 3, 55, 8, 120], 6, 0.8, 3),
    ([5, 6], 1, 0.0, 4),
]


@pytest.fixture(scope="module")
def model():
    return tiny_gpt2("manual")


@pytest.fixture(scope="module")
def params(model):
    return meta.unbox(model.init_params(jax.random.PRNGKey(0)))


def _engine(model, params, role, **kw):
    kw.setdefault("max_batch_slots", 2)
    kw.setdefault("paged_max_len", 64)
    return ServingEngine(
        model, params, eod_token_id=-1, kv_cache="paged", paged_block_size=8,
        metrics=MetricsRegistry(), role=role, **kw,
    )


@pytest.fixture(scope="module")
def pair(model, params):
    """The module's 1-prefill + 1-decode pair (bf16). Tests that only READ
    engine state come after the parity run that populates it."""
    return _engine(model, params, "prefill"), _engine(model, params, "decode")


@pytest.fixture(scope="module")
def combined(model, params):
    return _engine(model, params, "combined")


@pytest.fixture(scope="module")
def pair_results(pair):
    """REQS through the DisaggPair, keyed by submit order."""
    peng, deng = pair
    dp = DisaggPair(peng, deng)
    rids = [dp.submit(p, b, temperature=t, seed=s) for p, b, t, s in REQS]
    results = dp.run()
    assert not dp.handoff_failures
    return [results[rid] for rid in rids]


@pytest.fixture(scope="module")
def combined_results(combined):
    rids = [combined.submit(p, b, temperature=t, seed=s) for p, b, t, s in REQS]
    results = combined.run()
    return [results[rid] for rid in rids]


# ------------------------------------------------------------ bitwise parity


def test_disagg_tokens_bitwise_equal_combined_greedy_and_sampled(
    pair_results, combined_results
):
    """The headline pin: the same mixed trace through the tiered pair and the
    combined paged engine yields IDENTICAL token streams — greedy rows and
    sampled rows (the handoff ships the post-first-draw key, so the decode
    tier's key-split discipline continues bitwise where prefill left it)."""
    for (prompt, budget, temp, seed), dres, cres in zip(
        REQS, pair_results, combined_results
    ):
        assert dres.tokens == list(cres.tokens), (prompt, temp, seed)
        assert dres.finish_reason == cres.finish_reason
        assert len(dres.tokens) == budget


def test_budget_one_request_short_circuits_at_prefill(pair_results):
    """max_new_tokens=1 finishes INSIDE the prefill tier (nothing left to
    decode): no handoff, no decode leg."""
    short = pair_results[-1]
    assert short.finish_reason == "budget"
    assert short.decode is None
    assert len(short.tokens) == 1


# ------------------------------------------------------- executable discipline


def test_per_tier_executable_pins(pair, pair_results):
    """Prefill workers never build the decode step; decode workers never build
    prefill. One gather executable exports every handoff (per-block jit, so
    mixed 1-block and 3-block records reuse it); one scatter executable
    imports them."""
    peng, deng = pair
    pstats, dstats = peng.stats(), deng.stats()
    assert pstats["role"] == "prefill" and dstats["role"] == "decode"
    assert pstats["prefill_executables"] == 1
    assert pstats["decode_executables"] == 0
    assert pstats["handoff_executables"] == 1
    assert pstats["handoffs_exported"] == 4  # REQS minus the budget-1 row
    assert pstats["handoff_bytes_shipped"] > 0
    assert dstats["decode_executables"] == 1
    assert dstats["prefill_executables"] == 0
    assert dstats["import_executables"] == 1
    assert dstats["handoffs_imported"] == 4
    # both pools drained clean: every block (donor and imported) returned
    for engine in (peng, deng):
        stats = engine.stats()
        assert stats["free_blocks"] == stats["num_blocks"]
        engine._table_state.check()


# ------------------------------------------------------------- wire contract


def _record_of(peng, idx=0):
    """A sealed HandoffRecord off the module prefill tier (REQS[idx])."""
    rids = sorted(peng._results)
    res = peng._results[rids[idx]]
    assert res.finish_reason == "handoff"
    return res.handoff


def test_wire_roundtrip_preserves_payload_and_digest(pair, pair_results):
    peng, _ = pair
    record = _record_of(peng, idx=2)  # the 3-block record
    wire = record.to_wire()
    json.dumps(wire)  # the wire form IS the HTTP body: must be JSON-clean
    back = HandoffRecord.from_wire(wire)
    back.verify_digest()
    assert back.version == HANDOFF_VERSION
    assert back.window == record.window
    assert back.last_token == record.last_token
    assert back.remaining == record.remaining
    assert np.array_equal(back.key, record.key)
    assert len(back.payload) == len(record.payload)
    for a, b in zip(back.payload, record.payload):
        assert a.dtype == b.dtype and np.array_equal(a, b)
    assert back.kv_bytes == record.kv_bytes


def test_import_rejection_reasons_and_counters(pair, pair_results):
    """Each validation gate raises HandoffRejected with its failure-counter
    reason — and a rejection never touches the decode pool."""
    peng, deng = pair
    record = _record_of(peng)
    free0 = deng._table_state.pool.free_count
    fails = deng._m_handoff_failures

    tampered = copy.deepcopy(record)
    tampered.last_token = int(tampered.last_token) + 1
    with pytest.raises(HandoffRejected) as exc:
        deng.import_handoff(tampered)
    assert exc.value.reason == "digest_mismatch"
    assert fails.value(reason="digest_mismatch") == 1

    skewed = copy.deepcopy(record)
    skewed.generation += 1
    skewed.seal()  # digest honest: the GENERATION gate must fire, not sha256
    before = snapshot_counts()
    with pytest.raises(HandoffRejected) as exc:
        deng.import_handoff(skewed)
    assert exc.value.reason == "generation_mismatch"
    assert fails.value(reason="generation_mismatch") == 1
    # a cross-generation import is a rollback-class event, not a wire fault
    # (resilience counters key by path head, so fleet/* land under "fleet";
    # the one delta in this window IS the fleet/rollback stage=generation)
    assert counts_since(before).get("fleet") == 1

    future = copy.deepcopy(record)
    future.version = HANDOFF_VERSION + 1
    with pytest.raises(HandoffRejected) as exc:
        deng.import_handoff(future)
    assert exc.value.reason == "version_mismatch"

    mis = copy.deepcopy(record)
    mis.quant_kv = "int8"
    with pytest.raises(HandoffRejected) as exc:
        deng.import_handoff(mis)
    assert exc.value.reason == "config_mismatch"

    assert deng._table_state.pool.free_count == free0


def test_import_into_wrong_role_raises(pair, combined):
    peng, _ = pair
    record = _record_of(peng)
    with pytest.raises(ValueError, match="role='decode'"):
        combined.import_handoff(record)


# -------------------------------------------------------- pool-full requeue


def test_pool_full_requeues_import_without_corruption(model, params, pair,
                                                      pair_results):
    """A decode pool too small for two concurrent imports: the second stays
    QUEUED (one `pool_full` count) while the first decodes to completion on
    uncorrupted blocks, then admits and finishes identically."""
    peng, _ = pair
    record = _record_of(peng, idx=2)  # 3 blocks resident, budget 6 -> 3 total
    # 5 blocks is the smallest legal pool at max_len 40 (one max-length
    # request = 5-block table width must fit): one 3-block import admits,
    # two can't coexist (prefix sharing off so the twin can't dedupe its
    # way around the pressure)
    deng = _engine(model, params, "decode", paged_max_len=40,
                   paged_num_blocks=5, prefix_sharing=False)
    r1 = deng.import_handoff(copy.deepcopy(record))
    r2 = deng.import_handoff(copy.deepcopy(record))
    results = deng.run()
    assert results[r1].tokens == results[r2].tokens
    assert results[r1].finish_reason == results[r2].finish_reason == "budget"
    stats = deng.stats()
    assert stats["import_requeues"] == 1
    assert deng._m_handoff_failures.value(reason="pool_full") == 1
    assert stats["handoffs_imported"] == 2
    assert stats["free_blocks"] == stats["num_blocks"]
    deng._table_state.check()


# ------------------------------------------- prefix sharing + spec on imports


def test_prefix_sharing_and_spec_decode_on_imported_blocks(model, params, pair,
                                                           combined,
                                                           pair_results):
    """Imported blocks are full citizens of the decode tier: a second import
    of the same window forks the shared full blocks out of the prefix index
    (fewer scattered blocks, same tokens), and the ngram spec-decode path
    proposes/verifies over them — all bitwise the combined engine's output."""
    prompt = [5, 6] * 8  # periodic: the ngram proposer actually fires
    budget = 8
    rid_c = combined.submit(prompt, budget, temperature=0.0, seed=9)
    ref = list(combined.run()[rid_c].tokens)

    peng, _ = pair
    deng = _engine(model, params, "decode", spec_decode={"k": 2})
    prid = peng.submit(prompt, budget, temperature=0.0, seed=9)
    record = peng.run()[prid].handoff
    assert record is not None

    # both imports in flight TOGETHER: prefix entries live only while their
    # blocks are refcounted, so the twin must admit while the first still
    # holds the window (a sequential re-import would find a pruned index)
    r1 = deng.import_handoff(copy.deepcopy(record))
    r2 = deng.import_handoff(copy.deepcopy(record))
    results = deng.run()
    first, second = results[r1], results[r2]

    assert [int(record.last_token)] + list(first.tokens) == ref
    assert list(second.tokens) == list(first.tokens)
    stats = deng.stats()
    assert stats["prefix_hit_requests"] == 1  # the re-import matched
    assert stats["prefix_hit_blocks"] == 2  # both full blocks of the window
    assert stats["spec_proposed"] > 0  # spec decode ran over imported KV
    assert stats["imported_blocks"] < 2 * record.num_blocks  # hits skip scatter
    assert stats["free_blocks"] == stats["num_blocks"]
    deng._table_state.check()


# ------------------------------------------------------------- int8 handoff


def test_int8_handoff_ships_verbatim_at_half_bytes_and_passes_oracle(
    model, params, pair, pair_results
):
    """quant_kv=int8 pair: the record carries int8 blocks + their f32 scale
    mirror VERBATIM (~0.56x the bf16 bytes), the imported request decodes
    bitwise-identically to the combined int8 engine, and the full disagg
    transcript passes the teacher-forced bf16 logit oracle (PR 14's gate)."""
    from modalities_tpu.quant.oracle import _greedy_paged_run

    prompt, budget = [3, 17, 42, 9, 77], 8
    peng8 = _engine(model, params, "prefill", quant_kv="int8")
    deng8 = _engine(model, params, "decode", quant_kv="int8")
    dp = DisaggPair(peng8, deng8)
    rid = dp.submit(prompt, budget, temperature=0.0, seed=0)
    tokens = dp.run()[rid].tokens

    comb8 = _engine(model, params, "combined", quant_kv="int8")
    crid = comb8.submit(prompt, budget, temperature=0.0, seed=0)
    assert tokens == list(comb8.run()[crid].tokens)

    record8 = peng8._results[rid].handoff
    dtypes = {str(arr.dtype) for arr in record8.payload}
    assert dtypes == {"int8", "float32"}  # data blocks + scale mirror
    bf16_ref = _record_of(pair[0], idx=0)  # same prompt, module bf16 pair
    assert record8.num_blocks == bf16_ref.num_blocks
    ratio = record8.kv_bytes / bf16_ref.kv_bytes
    assert ratio < 0.6, ratio

    # teacher-forced oracle: force the disagg transcript through the bf16
    # reference; its argmax must agree at >= 99% of positions
    _, ref_argmax = _greedy_paged_run(
        model, params, prompt, budget, "none", teacher_tokens=tokens
    )
    match = sum(int(a == b) for a, b in zip(ref_argmax, tokens)) / budget
    assert match >= 0.99, (match, tokens, ref_argmax)


# ----------------------------------------------------------- tier pressure


def test_tier_pressure_events_name_the_tier_to_grow():
    """A breaching decode worker flips `fleet/tier_pressure tier=decode
    action=grow` exactly once; recovery emits `action=hold`. (Health-round
    hook driven directly: no sockets needed.)"""
    router = DisaggRouter(
        [WorkerHandle("p0", "127.0.0.1", 1)],
        [WorkerHandle("d0", "127.0.0.1", 2)],
        metrics=MetricsRegistry(),
        health_interval_s=3600.0,
    )
    d0 = next(w for w in router.workers if w.tier == "decode")
    # resilience counters key by path head: every fleet/* event lands under
    # "fleet", and with the sweep thread never started the ONLY fleet events
    # in this window are the tier_pressure transitions we drive below
    before = snapshot_counts()
    router._after_health_round()  # all quiet: no events
    assert counts_since(before).get("fleet") is None

    d0.degraded = True
    d0.slo_breaching = ["tpot_p99"]
    router._after_health_round()
    router._after_health_round()  # sustained breach: still ONE grow event
    assert counts_since(before).get("fleet") == 1

    d0.degraded = False
    d0.slo_breaching = []
    router._after_health_round()
    assert counts_since(before).get("fleet") == 2  # the hold


# --------------------------------------------------- scripted two-leg router
# Loopback workers speaking the tier wire protocols, so the router's splice /
# retry / rejection logic is tested without engine compiles (the real-engine
# HTTP path is covered by the stitched-trace test below).

FIRST = 11
DECODE_TOKENS = [12, 13, 14, 15]


class _ScriptedPrefill:
    """Answers /disagg/prefill with a one-token handoff response; the record
    is an opaque dict (the router ships it verbatim)."""

    def __init__(self):
        self.requests = []  # headers of every prefill leg received
        self.port = None
        self._started = threading.Event()
        self._loop = None

    async def _handle(self, reader, writer):
        req = await read_http_request(reader)
        if req is None:
            return
        method, path, headers, _ = req
        try:
            if method == "GET" and path == "/healthz":
                writer.write(json_response_bytes(200, {"status": "ok"}))
            elif method == "GET" and path == "/stats":
                writer.write(json_response_bytes(200, {"active_slots": 0, "queue_depth": 0}))
            elif method == "POST" and path == "/disagg/prefill":
                self.requests.append(dict(headers))
                writer.write(
                    json_response_bytes(
                        200,
                        {
                            "rid": len(self.requests), "finish_reason": "handoff",
                            "token_ids": [FIRST], "completion": str(FIRST),
                            "truncated": False, "prompt_len": 2, "ttft_s": 0.01,
                            "weights_generation": 0,
                            "trace_id": headers.get("x-trace-id", ""),
                            "record": {"opaque": "kv"},
                        },
                    )
                )
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


class _ScriptedDecode(_ScriptedPrefill):
    """Streams DECODE_TOKENS on /disagg/import. `abort_after` cuts the
    connection mid-stream (peer_down); `reject_reasons` pops one SSE error
    event per request until the list drains (retryable rejection)."""

    def __init__(self, abort_after=None, reject_reasons=()):
        super().__init__()
        self.abort_after = abort_after
        self.reject_reasons = list(reject_reasons)

    async def _handle(self, reader, writer):
        req = await read_http_request(reader)
        if req is None:
            return
        method, path, headers, _ = req
        try:
            if method == "GET" and path == "/healthz":
                writer.write(json_response_bytes(200, {"status": "ok"}))
            elif method == "GET" and path == "/stats":
                writer.write(json_response_bytes(200, {"active_slots": 0, "queue_depth": 0}))
            elif method == "POST" and path == "/disagg/import":
                self.requests.append(dict(headers))
                writer.write(SSE_HEADER_BYTES)
                if self.reject_reasons:
                    reason = self.reject_reasons.pop(0)
                    writer.write(
                        sse_event_bytes(
                            {"error": "bad record", "reason": reason, "retryable": True}
                        )
                    )
                    await writer.drain()
                    return
                for i, token in enumerate(DECODE_TOKENS):
                    if self.abort_after is not None and i >= self.abort_after:
                        return  # mid-stream death, no done event
                    writer.write(sse_event_bytes({"token_id": token, "text": str(token)}))
                    await writer.drain()
                writer.write(
                    sse_event_bytes(
                        {
                            "done": True, "token_ids": DECODE_TOKENS,
                            "completion": "".join(str(t) for t in DECODE_TOKENS),
                            "finish_reason": "budget",
                        }
                    )
                )
            await writer.drain()
        finally:
            writer.close()


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


def test_decode_leg_failover_replays_same_trace_exact_splice():
    """A decode worker dies after 2 of 4 tokens: the request replays through a
    FRESH prefill on the healthy pair — same trace_id on all four legs, hop
    incrementing, and the client sees each token exactly once."""
    prefill = _ScriptedPrefill().start()
    dying = _ScriptedDecode(abort_after=2).start()
    backup = _ScriptedDecode().start()
    registry = MetricsRegistry()
    router = DisaggRouter(
        [WorkerHandle("p0", "127.0.0.1", prefill.port)],
        [
            WorkerHandle("dying", "127.0.0.1", dying.port),
            WorkerHandle("backup", "127.0.0.1", backup.port),
        ],
        metrics=registry,
        health_interval_s=30.0,  # no probe mid-test: failover state stays visible
    )
    start_and_await_first_sweep(router)
    try:
        status, events = _post_generate(router.port, {"prompt": "3 4", "max_new_tokens": 5})
        assert status == 200
        streamed = [e["token_id"] for e in events if "token_id" in e]
        assert streamed == [FIRST] + DECODE_TOKENS  # exact splice, no repeats
        done = [e for e in events if e.get("done")]
        assert len(done) == 1
        assert done[0]["token_ids"] == [FIRST] + DECODE_TOKENS
        trace_id = done[0]["trace_id"]
        assert trace_id

        # the replay re-ran the PREFILL leg too (fresh record for the pair),
        # with one trace_id threading hops 0->1 then 2->3
        assert [h["x-trace-id"] for h in prefill.requests] == [trace_id] * 2
        assert [h["x-trace-hop"] for h in prefill.requests] == ["0", "2"]
        assert dying.requests[0]["x-trace-id"] == trace_id
        assert dying.requests[0]["x-trace-hop"] == "1"
        assert backup.requests[0]["x-trace-hop"] == "3"

        dead = next(w for w in router.workers if w.name == "dying")
        assert not dead.healthy
        assert router._m_handoff_failures.value(reason="peer_down") == 1
    finally:
        router.close()


def test_rejected_import_keeps_worker_in_rotation_and_replays():
    """A RETRYABLE rejection (generation skew after a hot swap) is a record
    fault, not a worker fault: the decode worker stays healthy, the request
    replays via fresh prefill onto the SAME worker, and the rejection lands
    in `fleet/handoff_rejected` + the router's failure counter."""
    prefill = _ScriptedPrefill().start()
    decode = _ScriptedDecode(reject_reasons=["generation_mismatch"]).start()
    router = DisaggRouter(
        [WorkerHandle("p0", "127.0.0.1", prefill.port)],
        [WorkerHandle("d0", "127.0.0.1", decode.port)],
        metrics=MetricsRegistry(),
        health_interval_s=30.0,
    )
    start_and_await_first_sweep(router)
    try:
        before = snapshot_counts()
        status, events = _post_generate(router.port, {"prompt": "3 4", "max_new_tokens": 5})
        assert status == 200
        done = [e for e in events if e.get("done")]
        assert len(done) == 1
        assert done[0]["token_ids"] == [FIRST] + DECODE_TOKENS
        assert len(decode.requests) == 2  # rejected once, then served the replay
        d0 = next(w for w in router.workers if w.tier == "decode")
        assert d0.healthy  # never failed out
        assert router.failovers == 0
        # group-keyed resilience counters: this request's window holds exactly
        # TWO fleet events — the handoff_rejected and the final fleet/request
        counts = counts_since(before)
        assert counts.get("fleet") == 2
        assert (
            router._m_handoff_failures.value(reason="generation_mismatch") == 1
        )
    finally:
        router.close()


def test_router_requires_both_tiers():
    with pytest.raises(ValueError, match="EACH tier"):
        DisaggRouter([WorkerHandle("p0", "127.0.0.1", 1)], [],
                     metrics=MetricsRegistry())


# ------------------------------------------- real engines behind the router


def test_http_two_leg_one_trace_id_and_stitched_tier_tree(
    model, params, tmp_path
):
    """The full HTTP path on REAL tiered engines: POST /generate against the
    DisaggRouter streams one bitwise-correct answer, 409s guard misrouted
    tier endpoints, and ONE trace_id spans all three record streams — the
    router's `fleet/request` (tier-tagged legs), the prefill worker's
    serve_request, and the decode worker's — stitched into one analyze_fleet
    tree with per-role leg lines."""
    from modalities_tpu.serving.analyze import (
        format_fleet_trace_tree,
        load_fleet_records,
        stitch_fleet_traces,
    )
    from modalities_tpu.telemetry import Telemetry, set_active_telemetry

    telemetry = Telemetry(
        output_folder_path=tmp_path, watchdog_deadline_s=0.0,
        use_jax_annotations=False,
    )
    prior = set_active_telemetry(telemetry)
    peng = _engine(model, params, "prefill")
    deng = _engine(model, params, "decode")
    servers = []
    for engine in (peng, deng):
        server = ServingHTTPServer(
            engine,
            encode=lambda s: [int(t) for t in s.split()],
            decode=lambda ids: " ".join(str(i) for i in ids),
            port=0,
        )
        server.start()
        servers.append(server)
    router = DisaggRouter(
        [WorkerHandle("p0", "127.0.0.1", servers[0].port)],
        [WorkerHandle("d0", "127.0.0.1", servers[1].port)],
        metrics=MetricsRegistry(),
        health_interval_s=30.0,
    )
    start_and_await_first_sweep(router)
    try:

        # misrouted tier endpoints refuse loudly instead of half-serving
        for port, path in ((servers[1].port, "/disagg/prefill"),
                           (servers[0].port, "/disagg/import"),
                           (servers[0].port, "/generate")):
            conn = http.client.HTTPConnection("127.0.0.1", port, timeout=10.0)
            conn.request("POST", path, body=json.dumps({"prompt": "3", "record": {}}))
            assert conn.getresponse().status == 409, path
            conn.close()

        status, events = _post_generate(
            router.port, {"prompt": "3 17 42 9 77", "max_new_tokens": 6}
        )
        assert status == 200
        streamed = [e["token_id"] for e in events if "token_id" in e]
        done = [e for e in events if e.get("done")]
        assert len(done) == 1
        assert done[0]["token_ids"] == streamed and len(streamed) == 6
        assert done[0]["finish_reason"] == "budget"
        trace_id = done[0]["trace_id"]
        assert trace_id

        # the answer is the combined engine's, over the wire
        ref = _engine(model, params, "combined")
        rid = ref.submit([3, 17, 42, 9, 77], 6, temperature=0.0, seed=0)
        assert streamed == list(ref.run()[rid].tokens)
    finally:
        router.close()
        for server in servers:
            server.close()
        telemetry.close()
        set_active_telemetry(prior)

    records = load_fleet_records([tmp_path])
    assert len(records["fleet_requests"]) == 1
    req = records["fleet_requests"][0]
    assert req["trace_id"] == trace_id and req["outcome"] == "done"
    assert req["disagg"] is True
    assert [(leg["worker"], leg["tier"]) for leg in req["legs"]] == [
        ("p0", "prefill"), ("d0", "decode")
    ]
    # both worker legs flushed serve_request records under the ONE trace_id,
    # each stamped with its engine's role (the ref combined engine's direct
    # run shares the sink but rides its own trace_id — a router-less trace)
    legs = {(r["trace_id"], r["hop"], r.get("role"))
            for r in records["serve_requests"] if r["trace_id"] == trace_id}
    assert legs == {(trace_id, 0, "prefill"), (trace_id, 1, "decode")}

    traces = stitch_fleet_traces(records)
    # router traces sort ahead of router-less ones; ours is the only one
    assert traces[0]["trace_id"] == trace_id
    assert traces[0]["router"] is not None
    tree = format_fleet_trace_tree([traces[0]])
    assert tree.count(trace_id) == 1
    assert "tier=prefill" in tree and "tier=decode" in tree
    assert "prefill leg" in tree and "decode leg" in tree
