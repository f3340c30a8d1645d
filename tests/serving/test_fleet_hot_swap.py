"""Live weight hot-swap acceptance on the real engine (serving/engine.py +
tiny GPT-2): the fleet deployment loop's zero-drop / zero-recompile contract.

Pinned here (tier-1, one compiled engine for the whole module):
- swapping in a bitwise-identical copy of the weights MID-FLIGHT changes no
  token of any request (same-weights swaps are invisible — the bench oracle's
  `--hot_swap_every` assertion, as a test);
- in-flight requests FINISH across a swap (zero dropped), and the single
  decode executable survives it (zero recompiles);
- a poisoned generation (NaN weights — the bad-checkpoint canary) turns
  requests into clean `finish_reason == "error"` results instead of emitting
  garbage, and swapping the donor generation back restores bitwise-reference
  serving on the SAME executable.
"""

import jax
import numpy as np
import pytest
from flax.core import meta

from modalities_tpu.serving.engine import ServingEngine
from modalities_tpu.telemetry.metrics import parse_prometheus_text
from tests.models.test_gpt2_model import tiny_gpt2

REQS = [
    ([3, 17, 42, 9], 8, 0.0, 0),
    ([7, 7, 7], 6, 0.8, 1),
    ([99, 3, 55, 8, 120], 8, 0.8, 3),
]


@pytest.fixture(scope="module")
def engine():
    model = tiny_gpt2("manual")
    params = meta.unbox(model.init_params(jax.random.PRNGKey(0)))
    return ServingEngine(model, params, max_batch_slots=2)


@pytest.fixture(scope="module")
def reference(engine):
    """Swap-free run of the module's request set on the same engine."""
    rids = [engine.submit(p, b, temperature=t, seed=s) for p, b, t, s in REQS]
    results = engine.run()
    return [results[rid].tokens for rid in rids]


def test_same_weights_swap_is_bitwise_invisible_and_drops_nothing(engine, reference):
    params_copy = jax.tree.map(lambda x: x.copy(), engine.params)
    rids = [engine.submit(p, b, temperature=t, seed=s) for p, b, t, s in REQS]
    t0 = engine._now()
    swaps_before = engine.weight_swaps
    steps = 0
    while engine._queue or engine._active_count():
        engine.step(t0)
        steps += 1
        assert steps < 10_000, "the engine never drained its queue and slots"
        if steps % 3 == 0:  # swap every third step, while requests are live
            engine.swap_weights(params_copy)
    assert engine.weight_swaps > swaps_before
    assert any(r["in_flight"] > 0 for r in engine.swap_history)  # truly mid-flight

    results = engine._results
    for rid, expected in zip(rids, reference):
        assert results[rid].tokens == expected  # bitwise: the swap is invisible
        assert results[rid].finish_reason == "budget"  # nothing dropped/errored
    # zero recompiles: the one decode executable survived every swap
    assert engine.stats()["decode_executables"] == 1
    # results carry the generation that was serving when they finished: every
    # request outlived at least one swap, none claims a generation that never
    # existed at its finish time
    finish_gens = [results[rid].weights_generation for rid in rids]
    assert min(finish_gens) >= 1
    assert max(finish_gens) <= engine.weights_generation


def test_nan_generation_errors_cleanly_then_donor_restores(engine, reference):
    """The engine-level canary seam: a poisoned generation yields clean error
    finishes (what the controller's error-delta gate watches), and rolling the
    donor back restores reference-exact serving without a recompile."""
    donor = engine.params
    donor_gen = engine.weights_generation
    poisoned = jax.tree.map(lambda x: jax.numpy.full_like(x, jax.numpy.nan), donor)
    engine.swap_weights(poisoned)
    bad_gen = engine.weights_generation

    prompt, budget, temperature, seed = REQS[0]
    rid = engine.submit(prompt, budget, temperature=temperature, seed=seed)
    result = engine.run()[rid]
    assert result.finish_reason == "error"  # NaN logits never become tokens
    assert result.weights_generation == bad_gen
    parsed = parse_prometheus_text(engine.metrics.render())
    assert parsed["serve_request_errors_total"][()] >= 1.0
    assert parsed["serve_weights_generation"][()] == float(bad_gen)

    # rollback: generation moves BACKWARD to the donor, serving is bitwise again
    engine.swap_weights(donor, donor_gen)
    assert engine.weights_generation == donor_gen
    rid = engine.submit(prompt, budget, temperature=temperature, seed=seed)
    assert engine.run()[rid].tokens == reference[0]
    assert engine.stats()["decode_executables"] == 1  # still zero recompiles


def test_swap_rejects_architecture_drift(engine):
    wrong = jax.tree.map(lambda x: np.zeros(x.shape + (1,), x.dtype), engine.params)
    with pytest.raises(ValueError, match="does not match"):
        engine.swap_weights(wrong)
