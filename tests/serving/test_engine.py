"""Continuous-batching engine acceptance (serving/engine.py).

The load-bearing contract is BATCH-INVARIANCE: the engine must emit
token-for-token what the interactive single-request path
(TextInferenceComponent._generate_cached) emits for the same (prompt, budget,
temperature, seed) — same key-split sequence, same categorical operand shapes —
whether the slot runs alone or inside a mixed concurrent batch. On top of that:
ONE compiled decode executable for the whole trace (per-slot sampling/stopping
folded in via jnp.where), a bounded prefill ladder, FIFO admission into freed
slots, and mesh NamedShardings on params + KV cache when a device mesh is given.
"""

import jax
import numpy as np
import pytest
from flax.core import meta

from modalities_tpu.inference.text.inference_component import TextInferenceComponent
from modalities_tpu.serving.engine import ServingEngine, _prefill_chunks_from_env
from tests.models.test_gpt2_model import tiny_gpt2

PROMPT = [3, 17, 42, 9, 77, 5, 23]


class _IdTok:
    """Identity 'tokenizer': prompts/completions stay token-id lists, so the
    reference path's generate_tokens compares directly against engine tokens."""

    def __init__(self):
        self.eod = -1

    def tokenize(self, ids):
        return list(ids)

    def decode(self, ids):
        return list(ids)

    def get_token_id(self, token):
        return self.eod


@pytest.fixture(scope="module")
def model():
    return tiny_gpt2("manual")


@pytest.fixture(scope="module")
def params(model):
    return meta.unbox(model.init_params(jax.random.PRNGKey(0)))


@pytest.fixture(scope="module")
def ref(model, params):
    """Interactive-path reference generator (one component per temperature —
    the fused decode loop bakes the temperature in at trace time)."""
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


# ----------------------------------------------------------- batch invariance


def test_single_slot_matches_interactive_path_bitwise(model, params, ref):
    """ISSUE acceptance: 1 active slot == _generate_cached, token for token,
    across greedy / sampled / temperature=None."""
    engine = ServingEngine(model, params, max_batch_slots=1)
    for temperature, seed in [(0.0, 0), (0.8, 1), (None, 3)]:
        rid = engine.submit(PROMPT, 10, temperature=temperature, seed=seed)
        result = engine.run()[rid]
        expected = ref(PROMPT, 10, temperature, seed)
        assert result.tokens == expected, (temperature, seed)
        assert result.finish_reason == "budget"
        assert result.ttft_s >= 0.0
        assert len(result.token_times_s) == len(result.tokens)
    assert engine.stats()["decode_executables"] == 1


def test_mixed_concurrent_batch_matches_sequential_references(model, params, ref):
    """Five requests with mixed temperatures/seeds/budgets through 2 slots:
    every completion must equal its solo interactive reference (concurrency is
    invisible in the tokens), admission must actually overlap requests, and the
    whole trace must use ONE decode executable and a bounded prefill ladder."""
    engine = ServingEngine(model, params, max_batch_slots=2)
    reqs = [
        (PROMPT, 10, 0.0, 0),
        ([7, 7, 7], 4, 0.8, 1),
        (list(range(1, 18)), 8, 0.0, 2),
        ([99, 3, 55, 8, 120], 6, 0.8, 3),
        # prompt + budget must fit the 32-token ring: past capacity the engine
        # finishes with "capacity" while the reference re-forwards (documented
        # divergence, covered by test_ring_capacity_finishes_request)
        ([11] * 15, 12, 0.0, 4),
    ]
    rids = [engine.submit(p, b, temperature=t, seed=s) for p, b, t, s in reqs]
    results = engine.run()
    for rid, (p, b, t, s) in zip(rids, reqs):
        assert results[rid].tokens == ref(p, b, t, s), (rid, t, s)
    stats = engine.stats()
    assert stats["max_concurrent"] == 2  # continuous batching actually batched
    assert stats["decode_executables"] == 1
    assert stats["prefill_executables"] <= len(engine.prefill_chunks)
    # freed slots were reused: fewer dispatches than running the five in sequence
    assert stats["decode_steps"] < sum(b - 1 for _, b, _, _ in reqs)


def test_eod_stops_generation_without_emitting(model, params, ref):
    reference = ref(PROMPT, 10, 0.0, 0)
    eod = reference[3]
    expected = reference[: reference.index(eod)]
    engine = ServingEngine(model, params, max_batch_slots=1, eod_token_id=eod)
    rid = engine.submit(PROMPT, 10, temperature=0.0, seed=0)
    result = engine.run()[rid]
    assert result.tokens == expected
    assert eod not in result.tokens
    assert result.finish_reason == "eod"
    # and the interactive path agrees (shared eod semantics)
    assert ref(PROMPT, 10, 0.0, 0, eod_id=eod) == expected


def test_ring_capacity_finishes_request(model, params):
    """Cache full -> finish with reason 'capacity' (the engine's documented
    divergence from the interactive sliding-window re-forward)."""
    engine = ServingEngine(model, params, max_batch_slots=1, cache_capacity=8)
    rid = engine.submit([5, 9, 2, 31], 50, temperature=0.0, seed=0)
    result = engine.run()[rid]
    assert result.finish_reason == "capacity"
    assert 0 < len(result.tokens) < 50


def test_overlong_prompt_truncation_is_flagged_not_silent(model, params, ref):
    """A prompt longer than the admission window is clipped to the last
    capacity-1 tokens — and the clipping is RECORDED: `truncated` on the
    result, engine counter, telemetry event (not silently dropped)."""
    engine = ServingEngine(model, params, max_batch_slots=1, cache_capacity=8)
    prompt = list(range(1, 13))  # 12 tokens > window of 7
    rid = engine.submit(prompt, 3, temperature=0.0, seed=0)
    result = engine.run()[rid]
    assert result.truncated is True
    assert result.prompt_len == 12  # original length, not the window
    assert engine.stats()["truncated_requests"] == 1
    # the served window IS the clipped tail: tokens match the reference fed it
    expected = ref(prompt[-7:], 3, 0.0, 0)
    assert result.tokens == expected[: len(result.tokens)]
    # an in-window prompt stays unflagged
    rid2 = engine.submit([1, 2, 3], 2, temperature=0.0, seed=1)
    assert engine.run()[rid2].truncated is False
    assert engine.stats()["truncated_requests"] == 1


# ----------------------------------------------------- scheduler / admission


def test_queue_admits_into_freed_slots_fifo(model, params):
    """More requests than slots: all finish, the batch stays full (occupancy),
    and arrival gating keeps FIFO order."""
    engine = ServingEngine(model, params, max_batch_slots=2)
    rids = [engine.submit([i + 1, i + 2], 6, temperature=0.0, seed=i) for i in range(6)]
    results = engine.run()
    assert sorted(results.keys()) == sorted(rids)
    assert all(results[r].finish_reason == "budget" for r in rids)
    stats = engine.stats()
    assert stats["max_concurrent"] == 2
    assert stats["slot_occupancy"] > 0.5


def test_arrival_offsets_delay_admission(model, params):
    # fake clock advancing a fixed tick per engine read: arrival gating becomes
    # deterministic without real sleeps mattering
    ticks = {"v": 0.0}

    def clock():
        ticks["v"] += 0.05
        return ticks["v"]

    engine = ServingEngine(model, params, max_batch_slots=2, time_fn=clock)
    early = engine.submit([1, 2, 3], 3, temperature=0.0, seed=0, arrival_offset_s=0.0)
    late = engine.submit([4, 5, 6], 3, temperature=0.0, seed=1, arrival_offset_s=0.5)

    results = engine.run()
    assert set(results.keys()) == {early, late}
    assert results[late].tokens
    # the late request was only admitted once its arrival time had passed, and
    # strictly after the early one started
    assert results[late].first_token_s >= 0.5
    assert results[early].first_token_s < results[late].first_token_s


def test_zero_budget_and_empty_prompt(model, params):
    engine = ServingEngine(model, params, max_batch_slots=1)
    with pytest.raises(ValueError, match="empty prompt"):
        engine.submit([], 4)
    rid = engine.submit([1, 2], 0, temperature=0.0)
    result = engine.run()[rid]
    assert result.tokens == [] and result.finish_reason == "budget"


# ------------------------------------------------------------- construction


def test_engine_rejects_models_without_slot_cache_api(params):
    with pytest.raises(ValueError, match="slot-cache decode API"):
        ServingEngine(object(), params)


def test_engine_rejects_degenerate_capacity(model, params):
    with pytest.raises(ValueError, match="cache_capacity"):
        ServingEngine(model, params, cache_capacity=1)


def test_prefill_chunk_ladder_env_knob(monkeypatch):
    monkeypatch.setenv("MODALITIES_TPU_SERVE_PREFILL_CHUNKS", "32,8,1")
    assert _prefill_chunks_from_env() == (32, 8, 1)
    for bad in ("8,32,1", "32,8", ""):
        monkeypatch.setenv("MODALITIES_TPU_SERVE_PREFILL_CHUNKS", bad)
        if bad:
            with pytest.raises(ValueError, match="PREFILL_CHUNKS"):
                _prefill_chunks_from_env()
        else:  # unset/empty falls back to the default ladder
            assert _prefill_chunks_from_env()[-1] == 1


# ------------------------------------------------------------ mesh sharding


def test_mesh_sharded_decode_carries_named_shardings_and_matches(model, params, ref):
    """ISSUE acceptance: under a dp_shard x tp mesh the decode step's params and
    KV cache carry mesh NamedShardings (slots ride the batch/dp axis, kv heads
    the tp axis) and the tokens stay bitwise equal to the interactive path."""
    from jax.sharding import NamedSharding

    from modalities_tpu.running_env.device_mesh import get_device_mesh

    if len(jax.devices()) < 4:
        pytest.skip("needs 4 virtual CPU devices")
    handle = get_device_mesh(
        device_type="cpu", data_parallel_shard_degree=2, tensor_parallel_degree=2,
        world_size=4, devices=jax.devices()[:4],
    )

    with pytest.raises(ValueError, match="divisible"):
        ServingEngine(model, params, max_batch_slots=3, mesh_handle=handle)

    engine = ServingEngine(model, params, max_batch_slots=2, mesh_handle=handle)
    # scanned cache leaf: [layers, slots, capacity, kv_heads, head_dim]
    for leaf in jax.tree.leaves(engine.cache):
        assert isinstance(leaf.sharding, NamedSharding)
        spec = tuple(leaf.sharding.spec)
        assert spec[1] in ("dp_shard", ("dp_shard",)), spec  # slots on the dp axis
        assert spec[3] in ("tp", ("tp",)), spec  # kv heads on the tp axis
    assert all(
        isinstance(leaf.sharding, NamedSharding) for leaf in jax.tree.leaves(engine.params)
    )

    rids = [engine.submit(PROMPT, 8, temperature=0.0, seed=0),
            engine.submit([9, 8, 7, 6], 6, temperature=0.8, seed=5)]
    results = engine.run()
    assert results[rids[0]].tokens == ref(PROMPT, 8, 0.0, 0)
    assert results[rids[1]].tokens == ref([9, 8, 7, 6], 6, 0.8, 5)
    assert engine.stats()["decode_executables"] == 1
    assert "sharding" in engine.decode_lowered_text()


# ------------------------------------------------------- performance scope


def test_perfscope_report_closure_on_the_decode_step(model, params):
    """Serving half of the PR-13 perfscope: the batched decode step compiles
    and its per-bucket costs sum exactly to the module total, with the
    matmul work (the qkv/attn/mlp dots) visible as its own bucket."""
    engine = ServingEngine(model, params, max_batch_slots=2, eod_token_id=-1)
    report = engine.perfscope_report()
    total = report["total"]
    for key in ("ops", "flops", "bytes"):
        assert sum(b[key] for b in report["buckets"].values()) == total[key], key
    assert total["flops"] > 0
    assert "matmul" in report["buckets"]
