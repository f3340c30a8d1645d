"""Copy-on-write prefix sharing acceptance (serving/paged_cache.py prefix
index + engine admission forking).

Contracts on top of the pool-level unit tests (test_paged_cache.py):

1. FORKING IS INVISIBLE IN THE TOKENS: a request admitted onto shared prompt
   blocks emits bitwise what the interactive path emits — the gathered K/V
   rows are the same rows, just refcount-shared. Holds for partial matches,
   and for a FULL prompt match where the first-token re-forward lands in a
   shared block and must copy-on-write first.
2. SHARING CHANGES ONLY THE WORK, NEVER THE PROGRAMS: prefill skips matched
   full blocks (fewer packed rows), yet prefill/decode executable counts stay
   at one each; the CoW device copy is its own single executable.
3. NOTHING LEAKS AND NOBODY FREES A DONOR: after the run the pool audit is
   clean, every block returns, and the index holds no entries once the last
   holder releases (refcount-0 pruning).
"""

import jax
import pytest
from flax.core import meta

from modalities_tpu.serving.engine import ServingEngine, _prefix_sharing_from_env
from tests.models.test_gpt2_model import tiny_gpt2
from tests.serving.test_paged_engine import paged_engine
from tests.serving.test_engine import _IdTok  # noqa: F401  (ref fixture dep)

# 32 deterministic tokens = 4 full blocks at block_size 8: the donor prompt
PREFIX = [(i * 7 + 3) % 127 for i in range(32)]


@pytest.fixture(scope="module")
def model():
    return tiny_gpt2("manual")


@pytest.fixture(scope="module")
def params(model):
    return meta.unbox(model.init_params(jax.random.PRNGKey(0)))


@pytest.fixture(scope="module")
def ref(model, params):
    from modalities_tpu.inference.text.inference_component import TextInferenceComponent

    comps = {}

    def generate(prompt, budget, temperature, seed, eod_id=-1):
        t = 0.0 if temperature is None else float(temperature)
        comp = comps.get(t)
        if comp is None:
            comp = TextInferenceComponent(
                model=model, params=params, tokenizer=_IdTok(),
                prompt_template="{prompt}", sequence_length=64,
                temperature=t, eod_token="<eod>",
            )
            comps[t] = comp
        comp.tokenizer.eod = eod_id
        return comp.generate_tokens(prompt, max_new_tokens=budget, seed=seed)

    return generate


def _shared_prefix_scenario(engine):
    """Four requests through 2 slots, ordered so the donor (r1) registers its
    prompt blocks before the sharers arrive and stays resident while they run:

      r1  PREFIX + tail   5 prefill chunks, long budget — the donor
      r2  long unrelated   6 chunks, budget 1 — keeps slot 2 busy past r1's
                           registration, then frees it for the sharers
      r3  == PREFIX        FULL match (4 blocks): CoW on the first-token
                           re-forward, prefill collapses to one packed row
      r4  PREFIX[:8]+tail  partial match (1 block): chunked prefill on the
                           3-token unmatched tail only
    """
    reqs = [
        (PREFIX + [60, 61, 62], 12, 0.0, 0),
        # in-vocab ids only (vocab 128): an out-of-range id NaN-fills its
        # embedding row and the PR-12 canary gate finishes the request "error"
        (list(range(87, 128)), 1, 0.8, 1),
        (PREFIX, 6, 0.0, 0),
        (PREFIX[:8] + [50, 51, 52], 4, 0.8, 3),
    ]
    rids = [engine.submit(p, b, temperature=t, seed=s) for p, b, t, s in reqs]
    return reqs, rids, engine.run()


def test_prefix_sharing_forks_cow_and_stays_bitwise(model, params, ref):
    """ISSUE acceptance: shared-prefix admission (partial AND full match with
    CoW) emits bitwise-identical tokens to the interactive path, with ONE
    prefill + ONE decode + ONE CoW executable and a clean pool."""
    engine = paged_engine(model, params, max_batch_slots=2, paged_max_len=64)
    reqs, rids, results = _shared_prefix_scenario(engine)
    for rid, (p, b, t, s) in zip(rids, reqs):
        assert results[rid].tokens == ref(p, b, t, s), (rid, t, s)
        assert results[rid].finish_reason == "budget"

    stats = engine.stats()
    assert stats["prefix_hit_requests"] == 2  # r3 (full) + r4 (partial)
    # r3 re-prefills only its last prompt token (31 saved), r4 only its
    # 3-token tail (8 saved)
    assert results[rids[2]].prefix_hit_tokens == len(PREFIX) - 1
    assert results[rids[3]].prefix_hit_tokens == 8
    assert stats["prefix_hit_tokens"] == len(PREFIX) - 1 + 8
    assert stats["prefix_hit_blocks"] == 4 + 1
    assert stats["cow_copies"] == 1  # r3's first-token write into a shared block
    assert stats["cow_executables"] == 1
    assert stats["prefill_executables"] == 1
    assert stats["decode_executables"] == 1
    # everything returns: no leak, no donor freed early, index pruned empty
    assert stats["free_blocks"] == stats["num_blocks"]
    assert stats["shared_blocks"] == 0
    assert stats["prefix_index_size"] == 0
    engine._table_state.check()


def test_prefix_sharing_off_is_bitwise_identical_with_zero_hits(model, params, ref):
    """kwarg off-switch: same scenario, no forking — tokens unchanged (sharing
    is purely an admission-work optimization), hit counters stay zero."""
    engine = paged_engine(
        model, params, max_batch_slots=2, paged_max_len=64, prefix_sharing=False
    )
    reqs, rids, results = _shared_prefix_scenario(engine)
    for rid, (p, b, t, s) in zip(rids, reqs):
        assert results[rid].tokens == ref(p, b, t, s), (rid, t, s)
    stats = engine.stats()
    assert stats["prefix_sharing"] is False
    assert stats["prefix_hit_requests"] == 0
    assert stats["prefix_hit_tokens"] == 0
    assert stats["cow_copies"] == 0
    assert stats["prefix_index_size"] == 0
    assert stats["free_blocks"] == stats["num_blocks"]
    engine._table_state.check()


def test_prefix_sharing_env_knob(monkeypatch):
    monkeypatch.delenv("MODALITIES_TPU_SERVE_PREFIX_SHARING", raising=False)
    assert _prefix_sharing_from_env() is True  # default ON
    for raw, want in (("0", False), ("off", False), ("no", False),
                      ("1", True), ("on", True), ("true", True)):
        monkeypatch.setenv("MODALITIES_TPU_SERVE_PREFIX_SHARING", raw)
        assert _prefix_sharing_from_env() is want, raw
    monkeypatch.setenv("MODALITIES_TPU_SERVE_PREFIX_SHARING", "maybe")
    with pytest.raises(ValueError, match="PREFIX_SHARING"):
        _prefix_sharing_from_env()


def test_preempting_a_sharer_never_frees_donor_blocks(model, params, ref):
    """Pool squeeze with live sharing: the youngest slot (a sharer holding
    forked donor blocks) gets preempted — the donor keeps decoding unharmed
    and the sharer replays bitwise on re-admission."""
    engine = paged_engine(
        model, params, max_batch_slots=2, paged_block_size=4, paged_max_len=28,
        paged_num_blocks=9,
    )
    donor_prompt = PREFIX[:12]  # 3 full blocks at block_size 4
    reqs = [
        # donor: grows to 7 blocks and holds them through the round where the
        # sharer (2 positions behind) wants its 7th — budget 16 fills max_len
        (donor_prompt, 16, 0.0, 0),
        (list(range(80, 97)), 1, 0.8, 1),  # occupies slot 2 past registration
        # sharer: forks 3 blocks, grows to 7 — peak demand 3 shared + 4 + 4
        # own = 11 blocks > the 9-block pool, so the squeeze lands on it while
        # the donor is mid-decode
        (donor_prompt + [33], 14, 0.0, 2),
    ]
    rids = [engine.submit(p, b, temperature=t, seed=s) for p, b, t, s in reqs]
    results = engine.run()
    for rid, (p, b, t, s) in zip(rids, reqs):
        assert results[rid].tokens == ref(p, b, t, s), (rid, t, s)
    stats = engine.stats()
    # 2 hits: the sharer's first admission AND its post-preemption re-admission
    # re-match the donor's still-live index entries (replay re-forks)
    assert stats["prefix_hit_requests"] == 2
    assert stats["preemptions"] >= 1
    assert stats["free_blocks"] == stats["num_blocks"]
    assert stats["prefix_index_size"] == 0
    engine._table_state.check()
