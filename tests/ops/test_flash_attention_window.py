"""The flash kernels under a window (PR 38): position i sees itself and the W - 1 before it.

The kernels run in Pallas interpret mode against the masked softmax written out
(`masked_attention` with the band as its mask), forward and all three gradients, through
the fused backward and through the two kernels it replaced. Tolerances are the unwindowed
tests' (`test_flash_attention.py`): float32 inputs, 2e-5 on the forward (the running
softmax reorders a row's sums), 5e-4 on the gradients (p and ds are recomputed from the
saved logsumexp). The plan's tests hold the tables: no pair behind the window's edge, every
class where its place says, and `window=None` the tables a call has always had."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from modalities_tpu.ops.attention import masked_attention
from modalities_tpu.ops.pallas import flash_attention as flash
from modalities_tpu.ops.pallas.flash_attention import pallas_flash_attention


def band(seq: int, window: int):
    i, j = np.arange(seq)[:, None], np.arange(seq)[None, :]
    return (j <= i) & (i - j < window)


def _rand_qkv(seed, batch, seq, hq, hkv, d):
    rng = jax.random.PRNGKey(seed)
    shape = lambda h: (batch, seq, h, d)  # noqa: E731
    return tuple(jax.random.normal(jax.random.fold_in(rng, i), shape(h), jnp.float32) for i, h in enumerate((hq, hkv, hkv)))


# (seq, block, window): smaller than a block, equal to it, larger, not a multiple of it, wider than the sequence
CASES = {
    "smaller_than_a_block": (64, 16, 5),
    "equal_to_a_block": (64, 16, 16),
    "two_blocks": (64, 16, 32),
    "larger_and_not_a_multiple": (64, 16, 23),
    "one": (32, 8, 1),
    "wider_than_the_sequence": (32, 8, 100),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_forward_and_gradients_match_the_masked_softmax(case, monkeypatch):
    seq, block, window = CASES[case]
    monkeypatch.setattr(flash, "_DIAG_SUB_BLOCK", 8)  # blocks of 16 walk their diagonal and edge tiles in squares of 8
    q, k, v = _rand_qkv(7, 2, seq, 8, 1, 16)  # GQA 8:1
    w = jax.random.normal(jax.random.PRNGKey(3), q.shape)
    mask = jnp.asarray(band(seq, window))

    kernel = lambda q, k, v: pallas_flash_attention(  # noqa: E731
        q, k, v, causal=True, block_q=block, block_k=block, interpret=True, window=window)
    oracle = lambda q, k, v: masked_attention(q, k, v, mask)  # noqa: E731
    np.testing.assert_allclose(np.asarray(kernel(q, k, v)), np.asarray(oracle(q, k, v)), rtol=2e-5, atol=2e-5)
    got = jax.grad(lambda *a: (kernel(*a) * w).sum(), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: (oracle(*a) * w).sum(), argnums=(0, 1, 2))(q, k, v)
    for g, o, name in zip(got, want, "qkv"):
        np.testing.assert_allclose(np.asarray(g), np.asarray(o), rtol=5e-4, atol=5e-4, err_msg=f"d{name}")


@pytest.mark.parametrize("blocks", [(16, 8), (8, 16)])
def test_unequal_blocks_mask_whole_tiles(blocks):
    q, k, v = _rand_qkv(8, 1, 64, 4, 2, 16)
    got = pallas_flash_attention(q, k, v, causal=True, block_q=blocks[0], block_k=blocks[1], interpret=True, window=24)
    np.testing.assert_allclose(np.asarray(got), np.asarray(masked_attention(q, k, v, jnp.asarray(band(64, 24)))), rtol=2e-5, atol=2e-5)


def test_the_two_kernel_backward_reads_the_same_plan(monkeypatch):
    """A row whose dq does not fit VMEM takes `bwd_dq` and `bwd_dkv`: the same window through the same flags."""
    monkeypatch.setattr(flash, "FUSED_BWD_VMEM_BUDGET", 0)
    q, k, v = _rand_qkv(9, 1, 64, 4, 2, 16)
    w = jax.random.normal(jax.random.PRNGKey(4), q.shape)
    mask = jnp.asarray(band(64, 32))
    got = jax.grad(lambda *a: (pallas_flash_attention(*a, causal=True, block_q=16, block_k=16, interpret=True, window=32) * w).sum(),
                   argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: (masked_attention(*a, mask) * w).sum(), argnums=(0, 1, 2))(q, k, v)
    for g, o, name in zip(got, want, "qkv"):
        np.testing.assert_allclose(np.asarray(g), np.asarray(o), rtol=5e-4, atol=5e-4, err_msg=f"d{name}")


def test_windowed_calls_carry_their_own_kernel_names():
    q, k, v = _rand_qkv(1, 1, 32, 2, 1, 16)
    text = lambda window: str(jax.make_jaxpr(jax.grad(lambda q: pallas_flash_attention(  # noqa: E731
        q, k, v, causal=True, block_q=8, block_k=8, interpret=True, window=window).sum()))(q))
    assert "flash_attention_window_fwd" in text(16) and "flash_attention_window_bwd" in text(16)
    assert "flash_attention_window" not in text(None) and "flash_attention_fwd" in text(None)


# ------------------------------------------------------------------ the tile plan

# (seq, block_q, block_k, window): (computed, interior, diagonal, window_edge)
PLANS = {
    # the new cell: a q tile computes its diagonal tile and the edge tile before it; three of four at 512
    "s16384_b1024_w1024": ((16384, 1024, 1024, 1024), (31, 0, 16, 15)),
    "s16384_b512_w1024": ((16384, 512, 512, 1024), (93, 31, 32, 30)),
    "s16384_bq1024_bk512_w1024": ((16384, 1024, 512, 1024), (62, 0, 32, 30)),
    "window_under_a_block_crosses_the_diagonal_tile": ((64, 16, 16, 5), (7, 0, 4, 7)),
    "window_not_a_multiple": ((64, 16, 16, 23), (9, 0, 4, 5)),
    "window_wider_than_the_sequence_is_the_causal_plan": ((64, 16, 16, 64), (10, 6, 4, 0)),
}


@pytest.mark.parametrize("case", sorted(PLANS))
def test_tile_plan_with_a_window(case):
    (seq, block_q, block_k, window), (computed, interior, diagonal, edge) = PLANS[case]
    plan = flash.tile_plan(seq, seq, block_q, block_k, True, window)
    assert (plan.computed, plan.interior, plan.diagonal, plan.window_edge) == (computed, interior, diagonal, edge)
    visible = band(seq, window)
    for table in (plan.q_major, plan.kv_major):
        covered = np.zeros_like(visible)
        for iq, jk, flags in table.T:
            rows, cols = slice(iq * block_q, (iq + 1) * block_q), slice(jk * block_k, (jk + 1) * block_k)
            tile = visible[rows, cols]
            assert tile.any()  # no pair wholly behind the window's edge, none above the diagonal
            assert bool(flags & flash._KIND) == (not tile.all())  # the unmasked body only where nothing is hidden
            causal_cut = not (np.arange(seq)[rows, None] >= np.arange(seq)[None, cols]).all()
            if flags & flash._EDGE:  # walked above its own diagonal: square, and the window's edge on it
                assert block_q == block_k and iq * block_q - jk * block_k == window and not causal_cut
            elif flags & flash._KIND:
                assert bool(flags & (flash._MASKED | flash._DIAGONAL)) == causal_cut
            covered[rows, cols] = True
        assert covered[visible].all()  # every position some row sees is in a pair


SHAPES_OF_THE_ACCEPTED_CELLS = [(4096, 1024, 1024), (8192, 1024, 512), (8192, 1024, 1024), (4096, 512, 512)]


@pytest.mark.parametrize("seq,block_q,block_k", SHAPES_OF_THE_ACCEPTED_CELLS)
def test_no_window_gives_the_tables_a_call_always_got(seq, block_q, block_k):
    """`window=None` is the plan of before PR 38, array for array: written out here as that plan was."""
    plan = flash.tile_plan(seq, seq, block_q, block_k, True)
    same = flash.tile_plan(seq, seq, block_q, block_k, True, None)
    assert all(np.array_equal(a, b) for a, b in zip(plan[:2], same[:2])) and plan[2:] == same[2:]
    num_q, num_k = seq // block_q, seq // block_k
    pairs = [(i, j) for i in range(num_q) for j in range(num_k) if j * block_k <= i * block_q + block_q - 1]

    def flags(i, j):
        interior = i * block_q >= j * block_k + block_k - 1
        return 0 if interior else (flash._DIAGONAL if block_q == block_k and i == j else flash._MASKED)

    def table(order, row):
        out = []
        for t, (i, j) in enumerate(order):
            first = t == 0 or order[t - 1][row] != (i, j)[row]
            last = t == len(order) - 1 or order[t + 1][row] != (i, j)[row]
            out.append((i, j, flags(i, j) | first * flash._FIRST | last * flash._LAST))
        return np.asarray(out, np.int32).T

    np.testing.assert_array_equal(plan.q_major, table(pairs, 0))
    np.testing.assert_array_equal(plan.kv_major, table(sorted(pairs, key=lambda p: (p[1], p[0])), 1))
    assert plan.window_edge == 0 and "window_edge" not in plan.counts()
    assert set(np.unique(plan.q_major[2] & flash._KIND)) <= {0, flash._MASKED, flash._DIAGONAL}


def test_a_window_is_refused_where_it_is_not_written():
    with pytest.raises(ValueError, match="window is causal"):
        flash.tile_plan(64, 64, 16, 16, False, 8)
    with pytest.raises(ValueError, match="window is causal"):
        flash.tile_plan(64, 128, 16, 16, True, 8)


def test_the_aligned_edge_tile_is_walked_in_sub_blocks():
    """At 1024 x 1024 and W 1024 the diagonal tile and the edge tile each take 10 of 16 sub-squares: 1.25 tiles
    of products for the 1.0 a q tile's rows can see, where two whole tiles would be 2.0."""
    walked = lambda cls: sum(rows * cols for _, rows, _, cols, _ in flash._rectangles(cls, 1024, 1024))  # noqa: E731
    assert walked(flash._EDGE) == walked(flash._DIAGONAL) == 10 * 256 * 256
    masked = [(r0, rows, c0, cols) for r0, rows, c0, cols, m in flash._rectangles(flash._EDGE, 1024, 1024) if m]
    assert masked == [(lo, 256, lo, 256) for lo in range(0, 1024, 256)]


def test_a_windowed_call_takes_the_blocks_an_unwindowed_call_of_its_shape_takes(monkeypatch, tune_table):
    """No bucket of the tuning table is a window's own: the chip read the default's 1024 x 1024 fastest at window 1024 and head
    128 (PERF.md section 6, PR 38). So the dispatcher asks for a windowed call's blocks as for any call's, an operator's
    table reaches both, and the plan it says holds those blocks beside the window."""
    from modalities_tpu.ops import attention, tiers
    from modalities_tpu.ops.pallas import autotune

    assert autotune.lookup("flash_attention_window", "w1024", "bfloat16", device_kind="TPU v5 lite") is None
    said = []
    monkeypatch.setattr(tiers, "on_tpu", lambda: True)
    monkeypatch.setattr(flash, "pallas_flash_attention", lambda q, k, v, **kw: said.append(kw) or q)
    tune_table({"flash_attention|*|*": {"block_q": 64, "block_k": 32}})
    q = jnp.zeros((1, 128, 2, 16), jnp.bfloat16)
    for window in (None, 48):
        attention.flash_attention_or_fallback(q, q, q, causal=True, window=window)
    (plain, windowed) = said
    assert (plain["block_q"], plain["block_k"], plain["bwd_blocks"]) == (64, 32, (64, 32)) == (windowed["block_q"], windowed["block_k"], windowed["bwd_blocks"])
    assert "window" not in plain and windowed["window"] == 48
