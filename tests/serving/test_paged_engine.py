"""Paged KV-cache serving acceptance (serving/engine.py kv_cache="paged").

Three load-bearing contracts on top of the ring battery (test_engine.py):

1. BATCH-INVARIANCE SURVIVES PAGING: the gathered K/V row is position-ordered
   and masked garbage contributes exact zeros, so a paged slot emits
   token-for-token what the interactive `_generate_cached` path emits — alone
   or in a mixed batch — with ONE compiled decode step and ONE compiled
   cross-request prefill step.
2. THE LENGTH CEILING LIFTS: blocks are allocated on demand and the admission
   budget clamp bounds positions below the table-width ceiling, so requests
   finish "eod"/"budget", NEVER "capacity"; a request that overflows the ring
   runs to completion under paged. Pool exhaustion preempts the youngest slot
   (blocks freed, request requeued, identical tokens on re-admission).
3. NO LEAKS: a randomized scheduler property (fake clock, random
   arrivals/lengths/budgets, both cache modes) — every request finishes, slots
   and blocks return to pristine, occupancy accounting matches dispatched
   decode tokens, admission stays FIFO.
"""

import jax
import numpy as np
import pytest
from flax.core import meta

from modalities_tpu.inference.text.inference_component import TextInferenceComponent
from modalities_tpu.serving.engine import ServingEngine, _kv_cache_from_env
from tests.models.test_gpt2_model import tiny_gpt2
from tests.serving.test_engine import _IdTok

PROMPT = [3, 17, 42, 9, 77, 5, 23]


@pytest.fixture(scope="module")
def model():
    return tiny_gpt2("manual")


@pytest.fixture(scope="module")
def params(model):
    return meta.unbox(model.init_params(jax.random.PRNGKey(0)))


@pytest.fixture(scope="module")
def ref(model, params):
    """Interactive-path reference (one component per temperature, as in
    test_engine.py)."""
    comps = {}

    def generate(prompt, budget, temperature, seed, eod_id=-1):
        t = 0.0 if temperature is None else float(temperature)
        comp = comps.get(t)
        if comp is None:
            comp = TextInferenceComponent(
                model=model, params=params, tokenizer=_IdTok(),
                prompt_template="{prompt}", sequence_length=32,
                temperature=t, eod_token="<eod>",
            )
            comps[t] = comp
        comp.tokenizer.eod = eod_id
        return comp.generate_tokens(prompt, max_new_tokens=budget, seed=seed)

    return generate


def paged_engine(model, params, **kwargs):
    kwargs.setdefault("paged_block_size", 8)
    return ServingEngine(model, params, kv_cache="paged", **kwargs)


# ----------------------------------------------------------- batch invariance


def test_paged_single_slot_matches_interactive_path_bitwise(model, params, ref):
    """ISSUE acceptance: 1 paged slot == _generate_cached, token for token,
    across greedy / sampled / temperature=None."""
    engine = paged_engine(model, params, max_batch_slots=1)
    for temperature, seed in [(0.0, 0), (0.8, 1), (None, 3)]:
        rid = engine.submit(PROMPT, 10, temperature=temperature, seed=seed)
        result = engine.run()[rid]
        assert result.tokens == ref(PROMPT, 10, temperature, seed), (temperature, seed)
        assert result.finish_reason == "budget"
    assert engine.stats()["decode_executables"] == 1


def test_paged_mixed_batch_matches_references_one_executable_each(model, params, ref):
    """Mixed temperatures/seeds/budgets through 2 paged slots: bitwise equal to
    the solo references, ONE decode executable, ONE cross-request prefill
    executable (the fixed [slots, block_size] dispatch replaces the ring's
    per-request ladder), and all pool blocks returned."""
    engine = paged_engine(model, params, max_batch_slots=2)
    reqs = [
        (PROMPT, 10, 0.0, 0),
        ([7, 7, 7], 4, 0.8, 1),
        (list(range(1, 18)), 8, 0.0, 2),  # prompt spans 3 blocks -> 3 chunks
        ([99, 3, 55, 8, 120], 6, 0.8, 3),
        ([11] * 15, 12, 0.0, 4),
        ([4, 2], 5, None, 5),  # default-temperature path rides along
    ]
    rids = [engine.submit(p, b, temperature=t, seed=s) for p, b, t, s in reqs]
    results = engine.run()
    for rid, (p, b, t, s) in zip(rids, reqs):
        assert results[rid].tokens == ref(p, b, t, s), (rid, t, s)
        assert results[rid].finish_reason == "budget"
    stats = engine.stats()
    assert stats["max_concurrent"] == 2
    assert stats["decode_executables"] == 1
    assert stats["prefill_executables"] == 1
    assert stats["free_blocks"] == stats["num_blocks"]  # all blocks released


# ------------------------------------------------------- length-ceiling lift


def test_paged_lifts_the_ring_length_ceiling(model, params, ref):
    """ISSUE acceptance: a (prompt, budget) that overflows the 32-token ring
    runs to its full budget under paged with a lifted max_len — finish reasons
    are "budget"/"eod", NEVER "capacity"."""
    prompt = list(range(1, 21))  # 20 prompt tokens + 40 generated > 32
    ring = ServingEngine(model, params, max_batch_slots=1)
    rid = ring.submit(prompt, 40, temperature=0.0, seed=0)
    ring_result = ring.run()[rid]
    assert ring_result.finish_reason == "capacity"
    assert len(ring_result.tokens) < 40

    engine = paged_engine(model, params, max_batch_slots=1, paged_max_len=64)
    rid = engine.submit(prompt, 40, temperature=0.0, seed=0)
    result = engine.run()[rid]
    assert result.finish_reason == "budget"
    assert len(result.tokens) == 40
    # the ring's shorter run is a prefix of the paged one (same trajectory)
    assert result.tokens[: len(ring_result.tokens)] == ring_result.tokens


def test_paged_budget_clamped_to_table_ceiling_never_capacity(model, params):
    """A budget larger than the table can hold is clamped at admission: the
    request still finishes "budget" (the last emitted token needs no cache
    write, hence the +1)."""
    engine = paged_engine(model, params, max_batch_slots=1, paged_max_len=16,
                          paged_block_size=4)
    rid = engine.submit([1, 2, 3, 4], 500, temperature=0.0, seed=0)
    result = engine.run()[rid]
    assert result.finish_reason == "budget"
    assert len(result.tokens) == 16 - 4 + 1
    assert engine.stats()["free_blocks"] == engine.stats()["num_blocks"]


def test_paged_overlong_prompt_truncated_and_clamped(model, params, ref):
    """Truncation semantics carry over to paged mode: prompt clipped to the
    last max_len-1 tokens, `truncated` flagged, budget clamped to the table
    ceiling — finish is "budget", never "capacity"."""
    engine = paged_engine(model, params, max_batch_slots=1, paged_block_size=4,
                          paged_max_len=16)
    prompt = list(range(1, 21))  # 20 tokens > window of 15
    rid = engine.submit(prompt, 10, temperature=0.0, seed=0)
    result = engine.run()[rid]
    assert result.truncated is True
    assert result.finish_reason == "budget"
    assert len(result.tokens) == 16 - 15 + 1
    assert result.tokens == ref(prompt[-15:], 2, 0.0, 0)
    assert engine.stats()["truncated_requests"] == 1


# ------------------------------------------------ exhaustion: preempt+requeue


def test_pool_exhaustion_preempts_youngest_and_requeues(model, params, ref):
    """ISSUE acceptance: with a pool too small for two long requests, the
    youngest slot is preempted (blocks freed, request requeued) instead of
    corrupting tables — and deterministic sampling reproduces the identical
    completion on re-admission."""
    # table_width = 24/4 = 6 blocks; a pool of 9 is one block short of the two
    # requests' peak concurrent demand (6 + 4), so growth must preempt
    engine = paged_engine(model, params, max_batch_slots=2, paged_block_size=4,
                          paged_max_len=24, paged_num_blocks=9)
    reqs = [(list(range(1, 9)), 15, 0.0, 0), ([5, 9, 2], 20, 0.8, 1)]
    rids = [engine.submit(p, b, temperature=t, seed=s) for p, b, t, s in reqs]
    results = engine.run()
    for rid, (p, b, t, s) in zip(rids, reqs):
        assert results[rid].tokens == ref(p, b, t, s), (rid, t, s)
        assert results[rid].finish_reason == "budget"
    stats = engine.stats()
    assert stats["preemptions"] >= 1
    assert stats["free_blocks"] == stats["num_blocks"]
    engine._table_state.check()


def test_admission_gates_on_free_blocks(model, params):
    """Admission gates on the PROMPT's block demand: while the first request
    holds the pool, a second whose prompt doesn't fit waits in the queue (no
    concurrency) and is admitted FIFO once blocks free up."""
    ticks = {"v": 0.0}

    def clock():
        ticks["v"] += 0.01
        return ticks["v"]

    engine = paged_engine(model, params, max_batch_slots=2, paged_block_size=4,
                          paged_max_len=16, paged_num_blocks=4, time_fn=clock)
    # first: prompt 2 blocks, grows to 3; second: prompt needs 3 blocks -> the
    # single remaining free block can never admit it concurrently
    first = engine.submit([1, 2, 3, 4, 5], 8, temperature=0.0, seed=0)
    second = engine.submit([9, 8, 7, 6, 5, 4, 3, 2, 1], 8, temperature=0.0, seed=1)
    results = engine.run()
    assert results[first].finish_reason == "budget"
    assert results[second].finish_reason == "budget"
    assert results[first].first_token_s < results[second].first_token_s
    stats = engine.stats()
    assert stats["max_concurrent"] == 1  # never enough blocks for both
    assert stats["preemptions"] == 0  # gating, not preemption, did the waiting


# ------------------------------------------------------- construction / knobs


def test_kv_cache_env_knob_validation(monkeypatch):
    monkeypatch.setenv("MODALITIES_TPU_SERVE_KV_CACHE", "paged")
    assert _kv_cache_from_env() == "paged"
    monkeypatch.delenv("MODALITIES_TPU_SERVE_KV_CACHE")
    assert _kv_cache_from_env() == "ring"
    monkeypatch.setenv("MODALITIES_TPU_SERVE_KV_CACHE", "vllm")
    with pytest.raises(ValueError, match="SERVE_KV_CACHE"):
        _kv_cache_from_env()


def test_paged_construction_guards(model, params):
    # pool smaller than one max-length request would livelock preemption
    with pytest.raises(ValueError, match="table width"):
        paged_engine(model, params, paged_block_size=4, paged_max_len=32,
                     paged_num_blocks=4)
    with pytest.raises(ValueError, match="must be 'ring' or 'paged'"):
        ServingEngine(model, params, kv_cache="flat")


def test_paged_max_len_rejected_for_absolute_poe(params):
    """The ceiling lift only exists for relative-position models: ABSOLUTE wpe
    has no rows past the trained sequence length."""
    abs_model = tiny_gpt2("manual", poe_type="ABSOLUTE")
    abs_params = meta.unbox(abs_model.init_params(jax.random.PRNGKey(0)))
    with pytest.raises(ValueError, match="ABSOLUTE"):
        ServingEngine(abs_model, abs_params, kv_cache="paged", paged_max_len=64)


# ------------------------------------------------- scheduler property (fuzz)


@pytest.mark.parametrize(
    "kv_cache,case_seed",
    [
        ("ring", 0),
        ("ring", 1),
        ("paged", 0),
        ("paged", 1),  # seed 1 shrinks the pool to 8 blocks -> forces preemption
        # seed 2 layers serving v3 onto the same invariants: half the prompts
        # share an 8-token prefix (2 full blocks -> refcount forking) and the
        # n-gram drafter speculates (k=2) over the mixed greedy/sampled trace
        ("paged", 2),
        # seed 3 runs the QUANTIZED pool (int8 blocks + scale arrays) under the
        # seed-1 squeeze: preemptions and replay must hold with scale pools in
        # the cache tree, and the pool/scale audit stays clean
        ("paged", 3),
    ],
)
def test_scheduler_property_randomized(model, params, kv_cache, case_seed):
    """Randomized trace through a fake clock, both cache modes: every request
    finishes with a legal reason, slots/blocks return to pristine, occupancy
    accounting matches dispatched decode tokens, admission is FIFO."""
    rng = np.random.default_rng(1000 + case_seed)
    ticks = {"v": 0.0}

    def clock():
        ticks["v"] += 0.01
        return ticks["v"]

    slots = int(rng.integers(2, 4))
    kwargs = dict(max_batch_slots=slots, time_fn=clock)
    if kv_cache == "paged":
        # seed 1 squeezes the pool to force preemptions mid-trace; seed 2 runs
        # serving v3 (prefix forking + speculation) under a mid-size pool
        kwargs.update(kv_cache="paged", paged_block_size=4, paged_max_len=24,
                      paged_num_blocks=24 if case_seed == 0 else 8)
        if case_seed == 2:
            kwargs.update(paged_num_blocks=12, spec_decode={"k": 2})
        if case_seed == 3:
            kwargs.update(quant_kv="int8")  # tight pool, quantized blocks
    engine = ServingEngine(model, params, **kwargs)

    shared = [int(x) for x in rng.integers(0, 127, size=8)]  # 2 full blocks
    t = 0.0
    budgets = {}
    for i in range(int(rng.integers(6, 11))):
        # seed 2 packs arrivals tight so later sharers queue behind busy slots
        # and admit AFTER the donor's registration (sharing is temporal)
        t += float(rng.exponential(0.05 if case_seed != 2 else 0.005))
        plen = int(rng.integers(1, 13))
        budget = int(rng.integers(1, 9))
        prompt = [int(x) for x in rng.integers(0, 127, size=plen)]
        if case_seed == 2 and (i == 0 or rng.random() < 0.5):
            prompt = shared + prompt[:4]  # candidate for a prefix-index hit
            if i == 0:
                budget = 12  # donor fills max_len: resident while sharers land
        rid = engine.submit(
            prompt,
            budget,
            temperature=float(rng.choice([0.0, 0.8])),
            seed=i,
            arrival_offset_s=t,
        )
        budgets[rid] = budget
    results = engine.run()

    legal = ("eod", "budget", "capacity") if kv_cache == "ring" else ("eod", "budget")
    assert sorted(results) == sorted(budgets)
    for rid, result in results.items():
        assert result.finish_reason in legal, (rid, result.finish_reason)
        assert len(result.tokens) <= budgets[rid]
        assert len(result.token_times_s) == len(result.tokens)
    # no slot leak; occupancy bookkeeping == dispatched decode tokens (a spec
    # verify round can emit several accepted tokens per occupied slot, so the
    # 1:1 equality only holds with speculation off)
    assert all(s is None for s in engine._slot_states)
    if not engine.spec.enabled:
        assert engine._occupancy_sum == engine.decode_token_count
    stats = engine.stats()
    assert 0.0 < stats["slot_occupancy"] <= 1.0
    if kv_cache == "paged":
        engine._table_state.check()  # block audit: free + owned tile the pool
        assert stats["free_blocks"] == stats["num_blocks"]
        assert engine._table_state.active_requests() == []
    if case_seed == 3 and kv_cache == "paged":
        # quantized pool actually engaged: int8 data + scale leaves in the tree
        assert stats["quant_kv"] == "int8"
        import jax.numpy as jnp

        dtypes = {jnp.dtype(leaf.dtype) for leaf in jax.tree.leaves(engine.cache)}
        assert jnp.dtype(jnp.int8) in dtypes and jnp.dtype(jnp.float32) in dtypes
    if case_seed == 2 and kv_cache == "paged":
        # the v3 machinery actually engaged on this trace (deterministic rng):
        # forked admissions and scored proposals, with coherent counters
        assert stats["prefix_hit_requests"] >= 1
        assert stats["shared_blocks"] == 0 and stats["prefix_index_size"] == 0
        assert 0 <= stats["spec_accepted"] <= stats["spec_proposed"]
        assert stats["verify_executables"] <= 1
    if stats["preemptions"] == 0:
        # FIFO: earlier rids (arrivals are non-decreasing) start no later
        firsts = [results[r].first_token_s for r in sorted(results)]
        assert firsts == sorted(firsts)


@pytest.mark.parametrize("kv_cache", ["ring", "paged"])
def test_scheduler_property_deadlines_and_shedding(model, params, kv_cache):
    """PR-19 extension of the scheduler property: deadlines + brownout
    shedding join the trace. Legal finish reasons now include "deadline" and
    "shed"; cancellation at the queue seam never dispatches a decode step for
    the victim; slots/blocks still return to pristine; and FIFO holds WITHIN
    a priority class (the shedder only ever reorders across classes)."""
    from modalities_tpu.serving.resilience import BrownoutController

    ticks = {"v": 0.0}

    def clock():
        ticks["v"] += 0.01
        return ticks["v"]

    brownout = BrownoutController(queue_high=4, queue_low=2)
    kwargs = dict(max_batch_slots=1, time_fn=clock, brownout=brownout)
    if kv_cache == "paged":
        kwargs.update(kv_cache="paged", paged_block_size=4, paged_max_len=24)
    engine = ServingEngine(model, params, **kwargs)

    rng = np.random.default_rng(7)
    expected = {"deadline": set(), "sheddable": set(), "normal": set()}
    budgets = {}
    for i in range(9):
        plen = int(rng.integers(2, 9))
        prompt = [int(x) for x in rng.integers(0, 127, size=plen)]
        budget = int(rng.integers(2, 6))
        if i in (1, 2):
            # dead on arrival: the fake clock ticks 10 ms per read, so a
            # 0.5 ms deadline expires before the first admission sweep
            kind, deadline, priority = "deadline", 0.5, 0
        elif i % 2 == 1:
            kind, deadline, priority = "sheddable", None, 1
        else:
            kind, deadline, priority = "normal", None, 0
        rid = engine.submit(
            prompt, budget, temperature=0.0, seed=i, arrival_offset_s=0.0,
            deadline_ms=deadline, priority=priority,
        )
        expected[kind].add(rid)
        budgets[rid] = budget
    results = engine.run()

    legal = ("eod", "budget", "deadline", "shed")
    legal += ("capacity",) if kv_cache == "ring" else ()
    assert sorted(results) == sorted(budgets)
    for rid, result in results.items():
        assert result.finish_reason in legal, (rid, result.finish_reason)
    # every dead-on-arrival deadline fired at the queue seam: reason
    # "deadline", zero tokens — the request never dispatched a decode step
    for rid in expected["deadline"]:
        assert results[rid].finish_reason == "deadline", rid
        assert results[rid].tokens == []
    # the queue (7+ deep behind 1 slot) crossed queue_high: brownout engaged
    # and shed lowest-priority queued work, which also never decoded
    shed = {r for r, res in results.items() if res.finish_reason == "shed"}
    assert shed, "brownout never shed despite queue_high=4"
    # class ordering: the shedder only touches priority-0 work after every
    # queued priority-1 request has already been shed
    if shed - expected["sheddable"]:
        assert expected["sheddable"] <= shed
    for rid in shed:
        assert results[rid].tokens == []
    assert brownout.transitions >= 1
    # no leaks: slots empty, paged pool tiles exactly
    assert all(s is None for s in engine._slot_states)
    stats = engine.stats()
    assert stats["deadline_expired_requests"] == len(expected["deadline"])
    assert stats["shed_requests"] == len(shed)
    if kv_cache == "paged":
        engine._table_state.check()
        assert stats["free_blocks"] == stats["num_blocks"]
    # FIFO within a priority class: priority-0 survivors start in rid order
    if stats["preemptions"] == 0:
        served = [r for r in sorted(results)
                  if r in expected["normal"] and results[r].tokens]
        firsts = [results[r].first_token_s for r in served]
        assert firsts == sorted(firsts)


@pytest.mark.parametrize("kv_cache", ["ring", "paged"])
def test_scheduler_property_multitenant(model, params, kv_cache):
    """PR-20 extension of the scheduler property: a TenantRegistry joins the
    trace on both cache modes. Per-tenant slot quotas are never exceeded,
    FIFO holds within a (tenant, class), the weighted DRR share shows up
    under saturation, finish reasons stay legal, and slots/blocks return to
    pristine (zero leak)."""
    from modalities_tpu.serving.resilience import TenantRegistry

    registry = TenantRegistry.from_config({
        "gold": {"class": "interactive", "weight": 3},
        "silver": {"class": "interactive", "weight": 1, "max_slots": 1},
        "bulk": {"class": "bulk", "weight": 1},
    })
    ticks = {"v": 0.0}

    def clock():
        ticks["v"] += 0.01
        return ticks["v"]

    holder = {}
    quota_violations = []

    def watch(rid, tok):
        # sampled at every delivered token: the quota must hold mid-flight
        if holder["eng"]._tenant_active_slots("silver") > 1:
            quota_violations.append(rid)

    kwargs = dict(max_batch_slots=2, time_fn=clock, tenants=registry,
                  on_token=watch)
    if kv_cache == "paged":
        # pool generous enough that preemption never reorders the trace: the
        # FIFO-within-tenant check needs admission order == serve order
        kwargs.update(kv_cache="paged", paged_block_size=4, paged_max_len=24,
                      paged_num_blocks=24)
    engine = ServingEngine(model, params, **kwargs)
    holder["eng"] = engine

    rng = np.random.default_rng(2000)
    plan = ["gold"] * 8 + ["silver"] * 4 + ["bulk"] * 4
    rids = {"gold": [], "silver": [], "bulk": []}
    budgets = {}
    for i, tenant in enumerate(plan):
        plen = int(rng.integers(2, 9))
        prompt = [int(x) for x in rng.integers(0, 127, size=plen)]
        budget = int(rng.integers(2, 6))
        # arrival 0 for everyone: the queue is saturated from the first sweep,
        # so admissions are a pure DRR decision
        rid = engine.submit(prompt, budget, temperature=0.0, seed=i,
                            arrival_offset_s=0.0, tenant=tenant)
        rids[tenant].append(rid)
        budgets[rid] = budget
    results = engine.run()

    legal = ("eod", "budget", "capacity") if kv_cache == "ring" else ("eod", "budget")
    assert sorted(results) == sorted(budgets)
    for rid, result in results.items():
        assert result.finish_reason in legal, (rid, result.finish_reason)
        assert len(result.tokens) <= budgets[rid]
    # the silver slot quota held at every delivered token
    assert quota_violations == []
    # FIFO within each (tenant, class): per-tenant first tokens in rid order
    assert engine.stats()["preemptions"] == 0
    for tenant_rids in rids.values():
        firsts = [results[r].first_token_s for r in tenant_rids]
        assert firsts == sorted(firsts)
    # weighted share under saturation: in the first 10 admissions gold
    # (weight 3) is served well clear of the weight-1 tenants
    tenant_of = {r: t for t, trids in rids.items() for r in trids}
    order = sorted(results, key=lambda r: results[r].first_token_s)
    first10 = [tenant_of[r] for r in order[:10]]
    assert first10.count("gold") >= 2 * first10.count("bulk")
    assert first10.count("gold") >= 5
    # zero leak: slots empty, paged pool tiles exactly, per-tenant stats add up
    assert all(s is None for s in engine._slot_states)
    stats = engine.stats()
    assert sum(row["finished"] for row in stats["tenants"].values()) == len(plan)
    assert stats["tenants"]["silver"]["active_slots"] == 0
    if kv_cache == "paged":
        engine._table_state.check()
        assert stats["free_blocks"] == stats["num_blocks"]
        assert engine._table_state.active_requests() == []


# ------------------------------------------------------------ mesh sharding


def test_paged_mesh_decode_carries_named_shardings_and_matches(model, params, ref):
    """ISSUE acceptance: under a dp_shard x tp mesh the paged pool leaves carry
    mesh NamedShardings (blocks ride the dp axis, kv heads the tp axis), the
    lowered decode HLO is annotated, and tokens stay bitwise equal."""
    from jax.sharding import NamedSharding

    from modalities_tpu.running_env.device_mesh import get_device_mesh

    if len(jax.devices()) < 4:
        pytest.skip("needs 4 virtual CPU devices")
    handle = get_device_mesh(
        device_type="cpu", data_parallel_shard_degree=2, tensor_parallel_degree=2,
        world_size=4, devices=jax.devices()[:4],
    )

    with pytest.raises(ValueError, match="paged_num_blocks.*divisible"):
        paged_engine(model, params, max_batch_slots=2, paged_num_blocks=9,
                     mesh_handle=handle)

    engine = paged_engine(model, params, max_batch_slots=2, mesh_handle=handle)
    # scanned pool leaf: [layers, num_blocks, block_size, kv_heads, head_dim]
    for leaf in jax.tree.leaves(engine.cache):
        assert isinstance(leaf.sharding, NamedSharding)
        spec = tuple(leaf.sharding.spec)
        assert spec[1] in ("dp_shard", ("dp_shard",)), spec  # blocks on dp
        assert spec[3] in ("tp", ("tp",)), spec  # kv heads on tp
    rids = [engine.submit(PROMPT, 8, temperature=0.0, seed=0),
            engine.submit([9, 8, 7, 6], 6, temperature=0.8, seed=5)]
    results = engine.run()
    assert results[rids[0]].tokens == ref(PROMPT, 8, 0.0, 0)
    assert results[rids[1]].tokens == ref([9, 8, 7, 6], 6, 0.8, 5)
    assert engine.stats()["decode_executables"] == 1
    assert "sharding" in engine.decode_lowered_text()


def test_blocks_in_use_peak_restarts_with_run_and_queue_wait_is_on_the_result(model, params):
    """What the first serving cell reads from the program (PERF.md section 7): the
    high-water mark of KV blocks in use, per `run()` like the engine's clock, and each
    request's queue wait on its `ServeResult`."""
    engine = paged_engine(model, params, max_batch_slots=1, paged_block_size=4, paged_max_len=24)
    first = engine.submit(PROMPT, 10, temperature=0.0, seed=0)  # the last token is never written: 7 + 9 positions, 4 blocks of 4
    second = engine.submit([4, 2], 3, temperature=0.0, seed=1)  # waits for the one slot
    results = engine.run()
    stats = engine.stats()
    assert stats["free_blocks"] == stats["num_blocks"] and stats["blocks_in_use_peak"] == 4
    assert engine.metrics.gauge("serve_paged_blocks_in_use_peak", "").value() == 4
    engine._table_state.check()  # the pool audit holds the mark between in-use and the pool's size
    assert results[first].queue_wait_s < results[second].queue_wait_s
    assert results[second].queue_wait_s == pytest.approx(results[first].finish_s - results[second].arrival_s, abs=0.05)
    assert results[second].queue_wait_s <= results[second].ttft_s

    third = engine.submit([9, 1, 1], 2, temperature=0.0, seed=2)  # 3 + 1 positions: 1 block
    assert engine.run()[third].finish_reason == "budget"
    assert engine.stats()["blocks_in_use_peak"] == 1, "a new run starts the mark again"
    engine._table_state.check()


def test_the_decode_program_names_its_operations_by_the_same_scopes(model, params):
    """`ServingEngine.scope_table()`: the decode step runs the modules the train step
    runs, so a trace of it reads by the same vocabulary (telemetry/scopes.py)."""
    table = paged_engine(model, params, max_batch_slots=2).scope_table()
    paths = set(table.values())
    for scope in ("/attn_core/", "/rope/", "/residual/", "/wte/", "/blocks/block/mlp/", "/layer_carry/"):
        assert any(scope in path for path in paths), scope
    assert all(name and "/" not in name for name in table), "keys are instruction names"
