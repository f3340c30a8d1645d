"""ops/expert_dispatch.py: the dropless dispatch of (token, choice) pairs to the held experts,
held to the dense-over-experts form (every held expert on every token, the weight zero where
not chosen) in values and gradients, at the extremes of load and in both forms of the sum by token
(`combine_plan`: the k gathers, and the kernel `ops/pallas/moe_combine.py` interpreted); its plan and
the counters read off it against a count made in numpy; the kernel against the gathers on one table."""

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


# the sum by token as `combine_plan` would have it off the chip (the parent's k gathers), and the kernel interpreted: the 50
# tokens as one block of 64, and as four blocks of 16 with the last one padded
COMBINES = {"gathers": ("gathers", 256), "slabs_one_block": ("slabs", 256), "slabs_blocks_of_16": ("slabs", 16)}


@pytest.mark.parametrize("combine", sorted(COMBINES))
@pytest.mark.parametrize("load", sorted(loads()))
def test_values_and_gradients_are_the_dense_over_experts_form(operands, load, combine, monkeypatch):
    form, block = COMBINES[combine]
    monkeypatch.setattr(xd, "COMBINE_BLOCK", block)
    choice = jnp.asarray(loads()[load], jnp.int32)
    direction = jnp.asarray(np.random.default_rng(2).normal(size=(T, D)), jnp.float32)
    routed = lambda *v: xd.routed_experts(v[0], choice, *v[1:], offset=OFFSET, tile=TILE, combine=form)  # noqa: E731
    ours = lambda *v: jnp.sum(routed(*v) * direction)  # noqa: E731
    dense = lambda *v: jnp.sum(xd.dense_over_experts(v[0], choice, *v[1:], offset=OFFSET) * direction)  # noqa: E731
    got, got_grads = jax.value_and_grad(ours, argnums=tuple(range(5)))(*operands)
    want, want_grads = jax.value_and_grad(dense, argnums=tuple(range(5)))(*operands)
    np.testing.assert_allclose(routed(*operands), xd.dense_over_experts(operands[0], choice, *operands[1:], offset=OFFSET), atol=2e-5)
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


@pytest.mark.parametrize("combine", ["gathers", "slabs"])
def test_bfloat16_operands_accumulate_in_float32(operands, combine):
    x, weights, gate, up, down = operands
    choice = jnp.asarray(loads()["spread over all the router's experts, 50 tokens on tiles of 8"], jnp.int32)
    half = lambda v: v.astype(jnp.bfloat16)  # noqa: E731
    got = xd.routed_experts(half(x), choice, weights, half(gate), half(up), half(down), offset=OFFSET, tile=TILE, combine=combine)
    want = xd.dense_over_experts(half(x), choice, weights, half(gate), half(up), half(down), offset=OFFSET)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(got.astype(jnp.float32), want.astype(jnp.float32), atol=0.02 * float(jnp.abs(want.astype(jnp.float32)).max()))


# ------------------------------------------------------- the sum by token: the kernel against the k gathers, one table


def _draws(tokens, k, routed):
    rng = np.random.default_rng(3)
    return np.stack([rng.choice(routed, size=k, replace=False) for _ in range(tokens)])


def _one_token_holds_all(tokens, k, routed, held):
    choice = np.broadcast_to(np.arange(held, held + k), (tokens, k)).copy()  # nobody a held expert ...
    choice[tokens // 3] = np.arange(k)  # ... but one token, all its k
    return choice


# tokens, k, the router's experts, held, offset, tile, the kernel's block, the choices [T, k]
SUMS = {
    "balanced random routing, 96 tokens in blocks of 32": (96, 3, 8, 4, 2, 8, 32, _draws(96, 3, 8)),
    "balanced random routing, one block": (96, 3, 8, 4, 2, 8, 256, _draws(96, 3, 8)),
    "collapsed routing: every token one held expert": (96, 3, 8, 4, 2, 8, 32, np.broadcast_to(np.array([3, 0, 7]), (96, 3))),
    "no pair held": (96, 3, 8, 4, 2, 8, 32, np.broadcast_to(np.array([0, 1, 7]), (96, 3))),
    "one token holds all k, the others none": (96, 3, 8, 4, 0, 8, 32, _one_token_holds_all(96, 3, 8, 4)),
    # every token expert 0 and, by turns, expert 1 or 2: on tiles of 8 the groups start at rows 0, 40 and 64
    "group starts that are not multiples of 16": (40, 2, 4, 3, 0, 8, 16, np.stack([np.zeros(40, int), 1 + np.arange(40) % 2], axis=1)),
    # tile 1 and every pair held: no padding row anywhere, the last group's last row is row R - 1
    "the last group ends at R": (48, 2, 3, 3, 0, 1, 16, np.stack([np.arange(48) % 2, np.full(48, 2)], axis=1)),
    "tokens that are not whole blocks": (50, 3, 8, 4, 2, 8, 16, _draws(50, 3, 8)),
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32_rows", "bfloat16_rows"])
@pytest.mark.parametrize("weighted", [True, False], ids=["weighted", "unweighted"])
@pytest.mark.parametrize("case", sorted(SUMS))
def test_the_kernel_sums_by_token_what_the_k_gathers_sum(case, weighted, dtype, monkeypatch):
    """`moe_combine` (interpreted) on a table of random rows, against `_sum_by_token` on the same rows: the forward's weighted
    sum and the backward's unweighted one. A float32 row is taken exactly and the float32 additions differ in order alone;
    a bfloat16 output is the gathers' float32 sum rounded, or its neighbour."""
    tokens, k, routed, held, offset, tile, block, choice = SUMS[case]
    assert all(len(set(row)) == k for row in choice.tolist()), "a router's k choices are distinct"
    monkeypatch.setattr(xd, "COMBINE_BLOCK", block)
    rng = np.random.default_rng(4)
    plan = xd.plan_dispatch(jnp.asarray(choice, jnp.int32), offset, held, tile)
    slabs = xd.slab_tables(plan, tokens, k, tile)
    rows = plan.row_pair.shape[0]
    if "last group ends" in case:
        assert int(plan.pair_row.max()) == rows - 1
    if "not multiples of 16" in case:
        assert any(int(start) % 16 for start in plan.first_tile * tile)
    table = jnp.asarray(rng.normal(size=(rows, 24)), dtype)
    padded = jnp.concatenate([table, jnp.zeros((xd._table_rows(plan, slabs) - rows, 24), dtype)])
    weights = jnp.asarray(rng.uniform(0.1, 0.9, size=(tokens, k)), jnp.float32) if weighted else None
    want = xd._sum_by_token(table, plan, tokens, k, weights)
    got = xd._sum_by_slabs(padded, plan, slabs, tokens, k, weights)
    assert got.shape == want.shape and got.dtype == dtype
    if "no pair held" in case:
        assert float(jnp.abs(got.astype(jnp.float32)).max()) == 0.0 and int(slabs.count.sum()) == 0
    if dtype == jnp.float32:
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    else:  # one unit in the last place of a bfloat16 is 2**-7 of its value at most
        gap = np.abs(np.asarray(got.astype(jnp.float32)) - np.asarray(want.astype(jnp.bfloat16).astype(jnp.float32)))
        assert np.all(gap <= 2.0**-7 * np.abs(np.asarray(want)) + 1e-30)


def test_the_kernels_tables_are_the_plans_rows_by_block_and_expert(monkeypatch):
    tokens, k, routed, held, offset, tile, block, choice = SUMS["tokens that are not whole blocks"]
    monkeypatch.setattr(xd, "COMBINE_BLOCK", block)
    plan = xd.plan_dispatch(jnp.asarray(choice, jnp.int32), offset, held, tile)
    slabs = jax.device_get(xd.slab_tables(plan, tokens, k, tile))
    pair_row = np.asarray(plan.pair_row).reshape(tokens, k)
    assert slabs.pos.shape == (64, held) and slabs.start.shape == slabs.count.shape == (4, held)
    for t in range(64):
        for e in range(held):
            mine = [pair_row[t, j] for j in range(k) if t < tokens and choice[t, j] - offset == e]
            assert slabs.pos[t, e] == (mine[0] if mine else -1)
    for b in range(4):
        for e in range(held):
            owned = slabs.pos[b * block:(b + 1) * block, e]
            owned = owned[owned >= 0]
            assert slabs.count[b, e] == len(owned)
            if len(owned):  # consecutive rows from `start`: what lets a block read them as one slab
                np.testing.assert_array_equal(owned, slabs.start[b, e] + np.arange(len(owned)))


# tokens, k, held, width: one layer as a chip holds it
PLANS = {
    "train-mellum2-12b-16k: 8 of 64 held, 8 choices": ((16384, 8, 8, 2304), "slabs"),
    "train-kanana2-30b-8k: 16 of 128 held, 6 choices": ((16384, 6, 16, 2048), "slabs"),
    "all 64 experts held, 8 choices": ((16384, 8, 64, 2304), "gathers"),
    "all 128 experts held, 6 choices": ((16384, 6, 128, 2048), "gathers"),
    "fewer tokens than a block (the initializer's dummy)": ((8, 8, 8, 2304), "gathers"),
    "a width that is not whole lane tiles": ((16384, 8, 8, 2300), "gathers"),
}


@pytest.mark.parametrize("case", sorted(PLANS))
def test_combine_plan_by_shapes(case):
    shape, form = PLANS[case]
    assert xd.combine_plan(*shape) == form


@pytest.mark.parametrize("kernels, mesh, form", [(False, False, "gathers"), (True, False, "slabs"), (True, True, "gathers")],
                         ids=["off_the_chip", "kernels_interpreted", "kernels_interpreted_under_a_mesh"])
def test_combine_form_by_tier_and_mesh(kernels, mesh, form):
    """Off the chip the parent's gathers; where kernels run (here interpreted, as the tests' seam has them) the kernel; under
    a mesh of several devices the gathers stay (GSPMD partitions them; the kernel has no per-shard plan)."""
    import contextlib

    from jax.sharding import Mesh

    from modalities_tpu.ops import tiers
    from modalities_tpu.parallel.sharding import activation_rules

    shape = PLANS["train-mellum2-12b-16k: 8 of 64 held, 8 choices"][0]
    with tiers.interpreted_kernels() if kernels else contextlib.nullcontext():
        with activation_rules((), Mesh(np.array(jax.devices()[:2]), ("dp_shard",))) if mesh else contextlib.nullcontext():
            assert xd.combine_form(*shape) == form
