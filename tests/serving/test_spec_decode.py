"""Speculative-decoding acceptance (serving/spec_decode.py + engine verify path).

Load-bearing contracts on top of the paged battery (test_paged_engine.py):

1. GREEDY IS PROPOSAL-INDEPENDENT: whatever the n-gram drafter proposes, the
   emitted greedy tokens are bitwise identical to the interactive
   `_generate_cached` path — acceptance only changes how many dispatches it
   takes, never which tokens come out. Sampled slots ride the same verify
   batch unchanged (row-level batch invariance, pinned since PR 9).
2. EXECUTABLES PINNED AT 1 DECODE + 1 VERIFY: the verify step is one
   fixed-shape `[slots, k+1]` program compiled once; accept/reject folds in
   via cumulative-match on device + host replay. Prefill count is untouched.
3. EDGE RULES REPLAY THE SEQUENTIAL STOPPING LOGIC: eod inside an accepted
   run stops emission exactly where plain decode would; the budget clamp cuts
   an accepted run mid-way; preemption replays bitwise (drafter is a pure
   function of the context).
"""

import jax
import pytest
from flax.core import meta

from modalities_tpu.serving.engine import ServingEngine
from modalities_tpu.serving.spec_decode import (
    SpecDecodeConfig,
    propose_ngram,
    resolve_spec_config,
)
from tests.models.test_gpt2_model import tiny_gpt2
from tests.serving.test_paged_engine import paged_engine
from tests.serving.test_engine import _IdTok  # noqa: F401  (ref fixture dep)

# periodic prompt: the drafter fires every step and the tiny model's greedy
# trajectory locks onto the repeated token, so acceptance is near-total
REPEAT = [1, 2, 3] * 6
# this prompt's greedy trajectory emits thirteen 82s then a 109 — pointing
# eod_token_id at 109 makes eod land MID-verify-run, after accepted drafts
# (a fresh-init model's argmax: found again by search when a jaxlib moves it,
# docs/known_failures.md)
EOD_PROMPT = [72, 13, 46, 56, 41, 79, 82]
EOD_ID = 109


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
                prompt_template="{prompt}", sequence_length=32,
                temperature=t, eod_token="<eod>",
            )
            comps[t] = comp
        comp.tokenizer.eod = eod_id
        return comp.generate_tokens(prompt, max_new_tokens=budget, seed=seed)

    return generate


# --------------------------------------------------- drafter (pure host code)


def test_propose_ngram_periodic_context_full_k():
    # trailing 3-gram [3,1,2] recurs one period back; followers are the period
    assert propose_ngram([1, 2, 3, 1, 2, 3, 1, 2], k=3, ngram_max=3, ngram_min=1) == [3, 1, 2]


def test_propose_ngram_prefers_recent_match_with_full_followers():
    # trailing [5,6,7] occurs at 0 (followers [9,5]) and 4 (followers [8,5]):
    # recency wins among matches that can serve the full k
    ctx = [5, 6, 7, 9, 5, 6, 7, 8, 5, 6, 7]
    assert propose_ngram(ctx, k=2, ngram_max=3, ngram_min=1) == [8, 5]


def test_propose_ngram_falls_back_to_short_followers():
    # the only match sits right before the context end: fewer than k followers
    # beats no proposal at all
    assert propose_ngram([4, 9, 9], k=3, ngram_max=3, ngram_min=1) == [9]


def test_propose_ngram_none_when_nothing_recurs():
    assert propose_ngram([1, 2, 3, 4], k=3, ngram_max=3, ngram_min=1) is None
    assert propose_ngram([7], k=3, ngram_max=3, ngram_min=1) is None


def test_spec_config_validation_and_env(monkeypatch):
    assert not SpecDecodeConfig().enabled  # k=0 is the default: spec off
    assert SpecDecodeConfig(k=4).enabled
    with pytest.raises(ValueError, match="k must be >= 0"):
        SpecDecodeConfig(k=-1)
    with pytest.raises(ValueError, match="only 'ngram'"):
        SpecDecodeConfig(k=2, drafter="tree")
    with pytest.raises(ValueError, match="ngram_min"):
        SpecDecodeConfig(k=2, ngram_min=3, ngram_max=2)
    monkeypatch.setenv("MODALITIES_TPU_SERVE_SPEC_K", "3")
    assert resolve_spec_config(None).k == 3
    monkeypatch.delenv("MODALITIES_TPU_SERVE_SPEC_K")
    assert resolve_spec_config(None).k == 0
    assert resolve_spec_config({"k": 2, "ngram_max": 4}).ngram_max == 4
    with pytest.raises(ValueError, match="spec_decode must be"):
        resolve_spec_config("fast")


def test_spec_requires_paged_cache(model, params):
    with pytest.raises(ValueError, match="requires kv_cache='paged'"):
        ServingEngine(model, params, kv_cache="ring", spec_decode={"k": 2})


# ------------------------------------------------ greedy identity + pinning


def test_spec_greedy_solo_bitwise_with_budget_clamp(model, params, ref):
    """ISSUE acceptance: greedy spec decode == interactive path token for
    token; a second request on the SAME engine whose budget cuts an accepted
    run mid-way stays bitwise too; verify stays ONE executable across both."""
    engine = paged_engine(model, params, max_batch_slots=1, spec_decode={"k": 4})
    rid = engine.submit(REPEAT, 14, temperature=0.0, seed=0)
    result = engine.run()[rid]
    assert result.tokens == ref(REPEAT, 14, 0.0, 0)
    assert result.finish_reason == "budget"
    stats = engine.stats()
    assert stats["verify_steps"] > 0 and stats["spec_accepted"] > 0

    # budget 3 lands inside an accepted draft run: the clamp must cut exactly
    rid = engine.submit(REPEAT, 3, temperature=0.0, seed=0)
    result = engine.run()[rid]
    assert result.tokens == ref(REPEAT, 3, 0.0, 0)
    assert result.finish_reason == "budget"

    stats = engine.stats()
    assert stats["spec_k"] == 4
    assert stats["decode_executables"] == 1
    assert stats["verify_executables"] == 1  # ONE [slots, k+1] verify program
    assert stats["prefill_executables"] == 1  # prefill path untouched by spec
    assert stats["free_blocks"] == stats["num_blocks"]
    engine._table_state.check()


def test_spec_mixed_batch_bitwise_with_eod_and_sampled_rider(model, params, ref):
    """A verify batch mixing an accepting greedy slot, a greedy slot whose eod
    fires mid-run, and a SAMPLED slot (never speculated, decoded through
    column 0 of the same verify program) — every slot bitwise equal to its
    solo interactive reference, still 1 decode + 1 verify executable."""
    engine = paged_engine(
        model, params, max_batch_slots=3, eod_token_id=EOD_ID, spec_decode={"k": 3}
    )
    reqs = [
        (REPEAT, 12, 0.0, 0),
        (EOD_PROMPT, 20, 0.0, 0),  # greedy run hits 109 == eod before budget
        ([7, 7, 7], 6, 0.8, 1),  # sampled rider: proposal-exempt by design
    ]
    rids = [engine.submit(p, b, temperature=t, seed=s) for p, b, t, s in reqs]
    results = engine.run()
    for rid, (p, b, t, s) in zip(rids, reqs):
        assert results[rid].tokens == ref(p, b, t, s, eod_id=EOD_ID), (rid, t)
    assert results[rids[1]].finish_reason == "eod"
    stats = engine.stats()
    # every round had live proposals here, so the plain decode program may
    # never even compile — the pin is "at most 1 of each", 2 decode-side total
    assert stats["decode_executables"] <= 1
    assert stats["verify_executables"] == 1
    assert stats["spec_proposed"] > stats["spec_accepted"] >= 0
    assert stats["free_blocks"] == stats["num_blocks"]
    engine._table_state.check()


def test_spec_preemption_replays_bitwise(model, params, ref):
    """Pool exhaustion preempts a speculating slot: on re-admission the pure
    drafter re-proposes from the identical context and the greedy trajectory
    is proposal-independent, so the completion is bitwise unchanged."""
    engine = paged_engine(
        model, params, max_batch_slots=2, paged_block_size=4, paged_max_len=24,
        paged_num_blocks=8, spec_decode={"k": 3},
    )
    # both slots speculate (greedy + periodic), so block demand grows ~k tokens
    # per round on each — the 8-block pool dries before either peak (6 + 6)
    reqs = [(REPEAT[:12], 11, 0.0, 0), ([4, 9] * 4, 16, 0.0, 1)]
    rids = [engine.submit(p, b, temperature=t, seed=s) for p, b, t, s in reqs]
    results = engine.run()
    for rid, (p, b, t, s) in zip(rids, reqs):
        assert results[rid].tokens == ref(p, b, t, s), (rid, t, s)
    stats = engine.stats()
    assert stats["preemptions"] >= 1
    assert stats["verify_executables"] <= 1
    assert stats["free_blocks"] == stats["num_blocks"]
    engine._table_state.check()
