"""ops/expert_dispatch.py: the dropless dispatch of (token, choice) pairs to the held experts,
held to the dense-over-experts form (every held expert on every token, the weight zero where
not chosen) in values and gradients, at the extremes of load; its plan and the counters read
off it against a count made in numpy."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from modalities_tpu.ops import expert_dispatch as xd

T, D, F, ROUTED, K, HELD, OFFSET, TILE = 50, 16, 24, 8, 3, 2, 2, 8


@pytest.fixture(scope="module")
def operands():
    rng = np.random.default_rng(0)
    normal = lambda *shape, scale=1.0: jnp.asarray(rng.normal(size=shape) * scale, jnp.float32)  # noqa: E731
    weights = jnp.asarray(rng.uniform(0.1, 0.9, size=(T, K)), jnp.float32)
    return normal(T, D), weights, normal(HELD, D, F, scale=0.3), normal(HELD, D, F, scale=0.3), normal(HELD, F, D, scale=0.3)


def loads():
    rng = np.random.default_rng(1)
    everywhere = np.stack([rng.choice(ROUTED, size=K, replace=False) for _ in range(T)])
    return {
        "spread over all the router's experts, 50 tokens on tiles of 8": everywhere,
        "every token to one held expert": np.broadcast_to(np.array([OFFSET, 0, 1]), (T, K)),
        "no token to any held expert": np.broadcast_to(np.array([5, 0, 1]), (T, K)),
        "every pair on held experts: the tables' full size": np.broadcast_to(np.array([OFFSET, OFFSET + 1, 7]), (T, K)) % ROUTED,
    }


@pytest.mark.parametrize("load", sorted(loads()))
def test_values_and_gradients_are_the_dense_over_experts_form(operands, load):
    choice = jnp.asarray(loads()[load], jnp.int32)
    direction = jnp.asarray(np.random.default_rng(2).normal(size=(T, D)), jnp.float32)
    ours = lambda *v: jnp.sum(xd.routed_experts(v[0], choice, *v[1:], offset=OFFSET, tile=TILE) * direction)  # noqa: E731
    dense = lambda *v: jnp.sum(xd.dense_over_experts(v[0], choice, *v[1:], offset=OFFSET) * direction)  # noqa: E731
    got, got_grads = jax.value_and_grad(ours, argnums=tuple(range(5)))(*operands)
    want, want_grads = jax.value_and_grad(dense, argnums=tuple(range(5)))(*operands)
    np.testing.assert_allclose(xd.routed_experts(operands[0], choice, *operands[1:], offset=OFFSET, tile=TILE),
                               xd.dense_over_experts(operands[0], choice, *operands[1:], offset=OFFSET), atol=2e-5)
    assert float(got) == pytest.approx(float(want), abs=1e-4)
    for name, g, w in zip(("x", "weights", "W", "V", "W_2"), got_grads, want_grads):
        np.testing.assert_allclose(g, w, atol=3e-5 * max(1.0, float(jnp.abs(w).max())), err_msg=name)
    if "no token" in load:
        assert float(jnp.abs(got_grads[0]).max()) == 0.0 and all(float(jnp.abs(g).max()) == 0.0 for g in got_grads[2:])


@pytest.mark.parametrize("load", sorted(loads()))
def test_the_plan_places_every_held_pair_once_and_counts_as_numpy_does(load):
    choice = loads()[load]
    plan = jax.device_get(xd.plan_dispatch(jnp.asarray(choice, jnp.int32), OFFSET, HELD, TILE))
    pairs = choice.size
    counts = np.bincount(choice.ravel(), minlength=ROUTED)[OFFSET:OFFSET + HELD]
    np.testing.assert_array_equal(plan.group_sizes, counts)
    np.testing.assert_array_equal(plan.tiles, -(-counts // TILE))
    np.testing.assert_array_equal(plan.first_tile, np.cumsum(plan.tiles) - plan.tiles)
    rows = xd.rows_for(pairs, HELD, TILE)
    assert plan.row_pair.shape == (rows,) and rows % TILE == 0 and rows >= pairs, "sized for every pair on held experts"
    live = plan.row_pair[plan.row_pair < pairs]
    held_pairs = np.flatnonzero((choice.ravel() >= OFFSET) & (choice.ravel() < OFFSET + HELD))
    np.testing.assert_array_equal(np.sort(live), held_pairs)  # each once, none of an absent expert
    for pair in held_pairs:  # the two tables agree, and a pair sits in its expert's tiles
        row = plan.pair_row[pair]
        assert plan.row_pair[row] == pair
        expert = choice.ravel()[pair] - OFFSET
        assert plan.first_tile[expert] <= row // TILE < plan.first_tile[expert] + plan.tiles[expert]
    assert np.all(plan.pair_row[np.setdiff1d(np.arange(pairs), held_pairs)] == rows)
    # inside a tile tokens ascend and none comes twice: a group is sorted by pair, and a token picks an expert once
    for tile in plan.row_pair.reshape(-1, TILE):
        tokens = tile[tile < pairs] // K
        assert np.all(np.diff(tokens) > 0)


def test_bfloat16_operands_accumulate_in_float32(operands):
    x, weights, gate, up, down = operands
    choice = jnp.asarray(loads()["spread over all the router's experts, 50 tokens on tiles of 8"], jnp.int32)
    half = lambda v: v.astype(jnp.bfloat16)  # noqa: E731
    got = xd.routed_experts(half(x), choice, weights, half(gate), half(up), half(down), offset=OFFSET, tile=TILE)
    want = xd.dense_over_experts(half(x), choice, weights, half(gate), half(up), half(down), offset=OFFSET)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(got.astype(jnp.float32), want.astype(jnp.float32), atol=0.02 * float(jnp.abs(want.astype(jnp.float32)).max()))
