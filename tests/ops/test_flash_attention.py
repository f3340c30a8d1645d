"""Flash-attention kernel correctness vs the manual oracle (fwd + grads), in Pallas
interpret mode on CPU (the reference's cross-impl equivalence pattern, SURVEY.md §4)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from modalities_tpu.ops.attention import manual_attention
from modalities_tpu.ops.pallas import flash_attention as flash
from modalities_tpu.ops.pallas.flash_attention import pallas_flash_attention


def _rand_qkv(rng_seed, batch, seq, hq, hkv, d, dtype=jnp.float32):
    rng = jax.random.PRNGKey(rng_seed)
    q = jax.random.normal(jax.random.fold_in(rng, 0), (batch, seq, hq, d), dtype)
    k = jax.random.normal(jax.random.fold_in(rng, 1), (batch, seq, hkv, d), dtype)
    v = jax.random.normal(jax.random.fold_in(rng, 2), (batch, seq, hkv, d), dtype)
    return q, k, v


@pytest.mark.parametrize("hq,hkv", [(4, 4), (4, 2), (4, 1)])
def test_forward_matches_oracle(hq, hkv):
    q, k, v = _rand_qkv(0, 2, 64, hq, hkv, 32)
    expected = manual_attention(q, k, v)
    got = pallas_flash_attention(q, k, v, causal=True, block_q=16, block_k=16, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected), rtol=2e-5, atol=2e-5)


def test_forward_non_divisible_block_fallback():
    q, k, v = _rand_qkv(1, 1, 48, 2, 2, 16)  # 48 not divisible by 128 -> one tile of 48 (PR 42: no block under a lane tile but the whole row)
    expected = manual_attention(q, k, v)
    got = pallas_flash_attention(q, k, v, causal=True, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected), rtol=2e-5, atol=2e-5)


def test_gradients_match_oracle():
    q, k, v = _rand_qkv(2, 1, 32, 2, 1, 16)

    def loss_flash(q, k, v):
        return pallas_flash_attention(q, k, v, causal=True, block_q=8, block_k=8, interpret=True).sum()

    def loss_oracle(q, k, v):
        return manual_attention(q, k, v).sum()

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_oracle = jax.grad(loss_oracle, argnums=(0, 1, 2))(q, k, v)
    for gf, go, name in zip(g_flash, g_oracle, "qkv"):
        np.testing.assert_allclose(
            np.asarray(gf), np.asarray(go), rtol=5e-4, atol=5e-4, err_msg=f"d{name} mismatch"
        )


def test_weighted_gradient_cotangent():
    """Non-uniform cotangent exercises delta/lse paths properly."""
    q, k, v = _rand_qkv(3, 1, 32, 2, 2, 16)
    w = jax.random.normal(jax.random.PRNGKey(9), (1, 32, 2, 16))

    g_flash = jax.grad(
        lambda q: (pallas_flash_attention(q, k, v, causal=True, block_q=8, block_k=8, interpret=True) * w).sum()
    )(q)
    g_oracle = jax.grad(lambda q: (manual_attention(q, k, v) * w).sum())(q)
    np.testing.assert_allclose(np.asarray(g_flash), np.asarray(g_oracle), rtol=5e-4, atol=5e-4)


def test_non_causal():
    q, k, v = _rand_qkv(4, 1, 16, 2, 2, 16)
    expected = jax.nn.dot_product_attention(q, k, v, is_causal=False)
    got = pallas_flash_attention(q, k, v, causal=False, block_q=8, block_k=8, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected), rtol=2e-5, atol=2e-5)


# ------------------------------------------------------------------ the tile plan

PLANS = {
    # (seq_q, seq_k, block_q, block_k, causal): (computed, interior, diagonal)
    "s4096_b1024": ((4096, 4096, 1024, 1024, True), (10, 6, 4)),
    "s32768_b1024": ((32768, 32768, 1024, 1024, True), (528, 496, 32)),
    "not_causal_is_the_rectangle": ((4096, 2048, 1024, 512, False), (16, 16, 0)),
    "more_q_than_k": ((128, 64, 32, 32, True), (7, 5, 2)),
    "more_k_than_q_keeps_one_masked_pair_a_kv_tile": ((64, 128, 32, 32, True), (5, 1, 4)),
    "bq_over_bk_masks_whole_tiles": ((96, 96, 32, 16, True), (12, 6, 6)),
    "bk_over_bq_masks_whole_tiles": ((96, 96, 16, 32, True), (12, 6, 6)),
    "shorter_than_a_block": ((16, 16, 16, 16, True), (1, 0, 1)),
}


@pytest.mark.parametrize("case", sorted(PLANS))
def test_tile_plan_classifies_every_tile_from_the_shapes(case):
    (seq_q, seq_k, block_q, block_k, causal), (computed, interior, diagonal) = PLANS[case]
    plan = flash.tile_plan(seq_q, seq_k, block_q, block_k, causal)
    assert plan.counts() == {"computed": computed, "interior": interior, "diagonal": diagonal, "skipped_steps": 0}
    q_pos, k_pos = np.arange(seq_q)[:, None], np.arange(seq_k)[None, :]
    visible = (q_pos >= k_pos) if causal else np.ones((seq_q, seq_k), bool)
    for table, row in ((plan.q_major, 0), (plan.kv_major, 1)):
        assert table.dtype == np.int32 and table.shape == (3, computed)
        covered = np.zeros_like(visible)
        for t, (iq, jk, flags) in enumerate(table.T):
            tile = visible[iq * block_q:(iq + 1) * block_q, jk * block_k:(jk + 1) * block_k]
            masked = bool(flags & (flash._MASKED | flash._DIAGONAL))
            assert masked == (not tile.all())  # the unmasked body only where nothing is hidden
            if flags & flash._DIAGONAL:  # walked below the diagonal: square, and on it
                assert block_q == block_k and iq == jk
            covered[iq * block_q:(iq + 1) * block_q, jk * block_k:(jk + 1) * block_k] = True
            # init and finish fire on the first and last pair of the accumulating kernel's row
            first = t == 0 or table[row, t - 1] != table[row, t]
            last = t == computed - 1 or table[row, t + 1] != table[row, t]
            assert (bool(flags & flash._FIRST), bool(flags & flash._LAST)) == (first, last)
        assert covered[visible].all()  # nothing visible is left out
        assert len(set(table[row])) == (seq_k // block_k if row else seq_q // block_q)  # every output tile is written
        assert (np.diff(table[row]) >= 0).all()  # and its pairs are contiguous
    assert sorted(map(tuple, plan.q_major[:2].T)) == sorted(map(tuple, plan.kv_major[:2].T))


def test_diagonal_tile_is_walked_in_five_eighths_of_its_squares():
    rects = flash._rectangles(flash._DIAGONAL, 1024, 1024)
    assert sum(rows * cols for _, rows, _, cols, _ in rects) == 1024 * 1024 * 5 // 8
    assert sum(rows * cols for _, rows, _, cols, masked in rects if masked) == 4 * 256 * 256
    visible = np.tril(np.ones((1024, 1024), bool))
    for r0, rows, c0, cols, masked in rects:  # every visible score once; unmasked only where all are visible
        assert masked or visible[r0:r0 + rows, c0:c0 + cols].all()
        visible[r0:r0 + rows, c0:c0 + cols] = False
    assert not visible.any()
    assert flash._rectangles(0, 512, 256) == [(0, 512, 0, 256, False)]
    assert flash._rectangles(flash._MASKED, 512, 256) == [(0, 512, 0, 256, True)]


# ------------------------------------------- values and gradients, class by class


def _oracle(q, k, v, causal):
    group = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
    if causal:
        s = jnp.where(jnp.arange(q.shape[1])[:, None] >= jnp.arange(k.shape[1])[None, :], s, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)


KERNEL_CASES = {
    # (seq_q, seq_k, block_q, block_k, causal, head_dim, kv heads of 4 q heads): at least three tiles a side
    "diagonal_walked_in_sub_blocks_d40": (96, 96, 32, 32, True, 40, 1),
    "diagonal_walked_in_sub_blocks_d80": (96, 96, 32, 32, True, 80, 1),
    "diagonal_walked_in_sub_blocks_d40_group_2": (96, 96, 32, 32, True, 40, 2),
    "not_causal_d40": (96, 96, 32, 32, False, 40, 1),
    "whole_tile_mask_bq_over_bk_d40": (96, 96, 32, 16, True, 40, 1),
    "whole_tile_mask_bk_over_bq_d40": (96, 96, 16, 32, True, 40, 1),
    "whole_tile_mask_bq_over_bk_d40_group_1": (96, 96, 32, 16, True, 40, 4),
    "more_k_than_q_d40": (96, 192, 32, 32, True, 40, 1),
    "more_q_than_k_not_causal_d80": (192, 96, 32, 32, False, 80, 1),
}
BACKWARDS = ("fused", "two_kernels")  # PR 31: one kernel for dq, dk and dv; the two it replaced where its dq row does not fit


def _grads_under(backward, loss, q, k, v, block_q, block_k):
    """The gradients of `loss` with the backward rule brought to choose `backward` for these shapes, as it would by size."""
    with pytest.MonkeyPatch.context() as patched:
        if backward == "two_kernels":
            patched.setattr(flash, "FUSED_BWD_VMEM_BUDGET", 0)
        assert flash.backward_plan(q.shape[1], block_q, block_k, q.shape[-1], v.shape[-1], q.dtype)["backward"] == backward
        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)


def _values_and_gradients(kernel, reference, q, k, v, w, block_q, block_k, diag_sub_block=8):
    """What the cases of one shape share, computed once (a case a form of the backward reads its part): the kernel's values and the
    reference's, and the gradients of the sum weighed by `w` under each form of the backward and through the reference."""
    with pytest.MonkeyPatch.context() as patched:
        patched.setattr(flash, "_DIAG_SUB_BLOCK", diag_sub_block)  # 8: four squares along a 32-wide diagonal tile
        loss = lambda q, k, v: (kernel(q, k, v) * w).sum()  # noqa: E731
        found = {"out": kernel(q, k, v), "reference": reference(q, k, v)}
        found.update({backward: _grads_under(backward, loss, q, k, v, block_q, block_k) for backward in BACKWARDS})
        found["oracle"] = jax.grad(lambda q, k, v: (reference(q, k, v) * w).sum(), argnums=(0, 1, 2))(q, k, v)
    return jax.tree.map(np.asarray, found)


def _held_to_the_oracle(found, backward):
    np.testing.assert_allclose(found["out"], found["reference"], rtol=2e-5, atol=2e-5)
    for g, e, name in zip(found[backward], found["oracle"], "qkv"):
        np.testing.assert_allclose(g, e, rtol=5e-4, atol=5e-4, err_msg=f"d{name}")
    if backward == "fused":  # the same sums in the same order as the two kernels: not close, equal
        for g, e, name in zip(found["fused"], found["two_kernels"], "qkv"):
            np.testing.assert_array_equal(g, e, err_msg=f"d{name}")


@functools.lru_cache(maxsize=None)
def _tile_class(case):
    seq_q, seq_k, block_q, block_k, causal, head_dim, kv_heads = KERNEL_CASES[case]
    rng = jax.random.PRNGKey(11)
    q = jax.random.normal(jax.random.fold_in(rng, 0), (2, seq_q, 4, head_dim))
    k = jax.random.normal(jax.random.fold_in(rng, 1), (2, seq_k, kv_heads, head_dim))
    v = jax.random.normal(jax.random.fold_in(rng, 2), (2, seq_k, kv_heads, head_dim))
    w = jax.random.normal(jax.random.fold_in(rng, 3), q.shape)
    kernel = functools.partial(
        pallas_flash_attention, causal=causal, block_q=block_q, block_k=block_k, interpret=True
    )
    reference = manual_attention if causal and seq_q == seq_k else functools.partial(_oracle, causal=causal)
    return _values_and_gradients(kernel, reference, q, k, v, w, block_q, block_k)


@pytest.mark.parametrize("backward", BACKWARDS)
@pytest.mark.parametrize("case", sorted(KERNEL_CASES))
def test_values_and_gradients_match_oracle_in_every_tile_class(case, backward):
    _held_to_the_oracle(_tile_class(case), backward)


# ------------------------------------------- two head sizes: q and k wider than v (latent attention)


TWO_WIDTH_CASES = {
    # (seq, block_q, block_k, heads, head_dim, head_dim_v): at least three tiles a side
    "d48_dv32_diagonal_walked": (96, 32, 32, 2, 48, 32),
    "d48_dv32_whole_tile_mask": (96, 32, 16, 2, 48, 32),
    "d192_dv128_diagonal_walked": (48, 16, 16, 1, 192, 128),
    "d192_dv128_whole_tile_mask": (48, 16, 8, 1, 192, 128),
}


@functools.lru_cache(maxsize=None)
def _two_widths(case):
    seq, block_q, block_k, heads, d, dv = TWO_WIDTH_CASES[case]
    rng = jax.random.PRNGKey(13)
    q = jax.random.normal(jax.random.fold_in(rng, 0), (2, seq, heads, d))
    k = jax.random.normal(jax.random.fold_in(rng, 1), (2, seq, heads, d))
    v = jax.random.normal(jax.random.fold_in(rng, 2), (2, seq, heads, dv))
    w = jax.random.normal(jax.random.fold_in(rng, 3), (2, seq, heads, dv))
    kernel = functools.partial(pallas_flash_attention, causal=True, block_q=block_q, block_k=block_k, interpret=True)
    return _values_and_gradients(kernel, manual_attention, q, k, v, w, block_q, block_k)


@pytest.mark.parametrize("backward", BACKWARDS)
@pytest.mark.parametrize("case", sorted(TWO_WIDTH_CASES))
def test_two_head_sizes_match_manual_attention(case, backward):
    """v, the output, its cotangent and dv at one width, q, k, dq and dk at another; the scale is that of q's."""
    seq, _, _, heads, d, dv = TWO_WIDTH_CASES[case]
    found = _two_widths(case)
    assert found["out"].shape == (2, seq, heads, dv)
    assert [g.shape[-1] for g in found[backward]] == [d, d, dv]
    _held_to_the_oracle(found, backward)


def _pallas_calls(fn, *args):
    """Every `pallas_call` equation of the traced program, in order, through all nested programs."""
    found = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                found.append(eqn)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return found


def _kernels(fn, *args):
    """(`name=`, grid) of every Pallas call in the traced program, in order."""
    return [(eqn.params["name"], tuple(eqn.params["grid_mapping"].grid)) for eqn in _pallas_calls(fn, *args)]


def _kernel_names(fn, *args):
    return [name for name, _ in _kernels(fn, *args)]


def test_a_differentiated_call_holds_the_fused_backward_and_neither_kernel_it_replaced():
    """PR 31: the backward rule calls one kernel, `flash_attention_bwd`, wherever a (batch, q head)'s dq row fits VMEM;
    `flash_attention_bwd_dq` and `_bwd_dkv` are in no program a model's step traces at such a shape."""
    q, k, v = _rand_qkv(5, 1, 64, 2, 1, 32)
    loss = lambda q, k, v: pallas_flash_attention(q, k, v, causal=True, block_q=16, block_k=16, interpret=True).sum()  # noqa: E731
    assert _kernel_names(jax.grad(loss, argnums=(0, 1, 2)), q, k, v) == ["flash_attention_fwd", "flash_attention_bwd"]
    assert _kernel_names(loss, q, k, v) == ["flash_attention_fwd"]


def test_the_shape_rule_takes_the_two_kernels_where_the_dq_row_does_not_fit(monkeypatch):
    """The choice is by the counted VMEM need against a module constant, nothing else: a row too long for the budget
    (here the budget shrunk under a short row's need) runs `bwd_dq` and `bwd_dkv` as before PR 31, to the same gradients."""
    q, k, v = _rand_qkv(6, 1, 96, 4, 2, 32)
    w = jax.random.normal(jax.random.PRNGKey(7), q.shape)
    loss = lambda q, k, v: (pallas_flash_attention(q, k, v, causal=True, block_q=32, block_k=16, interpret=True) * w).sum()  # noqa: E731
    grad = jax.grad(loss, argnums=(0, 1, 2))
    plan = flash.backward_plan(96, 32, 16, 32, 32, q.dtype)
    assert plan == {"backward": "fused", "backward_block_q": 32, "backward_block_k": 16, "dq_resident_bytes": 96 * 128 * (4 + 2 * 4),
                    "backward_vmem_bytes": flash.fused_backward_vmem_bytes(96, 32, 16, 32, 32, 4)}
    assert plan["dq_resident_bytes"] < plan["backward_vmem_bytes"] <= flash.FUSED_BWD_VMEM_BUDGET
    fused = grad(q, k, v)
    monkeypatch.setattr(flash, "FUSED_BWD_VMEM_BUDGET", plan["backward_vmem_bytes"] - 1)
    assert flash.backward_plan(96, 32, 16, 32, 32, q.dtype) == {**plan, "backward": "two_kernels"}
    assert _kernel_names(grad, q, k, v) == ["flash_attention_fwd", "flash_attention_bwd_dq", "flash_attention_bwd_dkv"]
    for g, e, name in zip(grad(q, k, v), fused, "qkv"):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(e), err_msg=f"d{name}")
    # the rows the cells and recipes hold, at the table's blocks (bfloat16): 32k x 128 is the one that falls back
    monkeypatch.undo()
    rows = {(4096, 1024, 1024, 80, 80): "fused", (4096, 1024, 1024, 128, 128): "fused", (8192, 1024, 1024, 192, 128): "fused",
            (32768, 1024, 1024, 128, 128): "two_kernels"}
    for shape, backward in rows.items():
        assert flash.backward_plan(*shape, jnp.bfloat16)["backward"] == backward, shape
    assert flash.dq_resident_bytes(8192, 192, 2) == 16 * 2**20  # float32 8 MiB (192 lanes pad to 256) and the bf16 block twice


def test_the_fused_backward_takes_its_own_blocks():
    """`bwd_blocks` tiles the fused backward apart from the forward (the tuning table's `flash_attention_bwd` entry: at
    192/128 the backward wants 1024 x 1024 where the forward and the two-kernel fallback cannot leave 1024 x 512): the
    program holds the backward at those blocks, the gradients are the oracle's, and the fallback keeps the forward's."""
    q, k, v = _rand_qkv(8, 1, 96, 2, 2, 32)
    w = jax.random.normal(jax.random.PRNGKey(9), q.shape)
    kernel = functools.partial(pallas_flash_attention, causal=True, block_q=32, block_k=16, bwd_blocks=(48, 48), interpret=True)
    loss = lambda q, k, v: (kernel(q, k, v) * w).sum()  # noqa: E731
    got = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(lambda q, k, v: (manual_attention(q, k, v) * w).sum(), argnums=(0, 1, 2))(q, k, v)
    for g, e, name in zip(got, want, "qkv"):
        np.testing.assert_allclose(np.asarray(g), np.asarray(e), rtol=5e-4, atol=5e-4, err_msg=f"d{name}")
    # grids are (batch, heads, pairs of the plan at the kernel's blocks)
    assert _kernels(jax.grad(loss, argnums=(0, 1, 2)), q, k, v) == [
        ("flash_attention_fwd", (1, 2, flash.tile_plan(96, 96, 32, 16, True).computed)),
        ("flash_attention_bwd", (1, 2, flash.tile_plan(96, 96, 48, 48, True).computed))]


def test_equal_head_sizes_lower_to_the_program_they_always_did():
    """The second width changes nothing where it equals the first: the same tilings under both names, so the
    same jaxpr for the kernels as a v of q's width always gave (blocks, scratch and out_shape by value): one call each.
    Changed on purpose by PR 42, for every width alike: "the program they always did" now hands lse and delta between
    the kernels as [B, H, 1, S] rows (`test_the_statistics_cross_between_the_kernels_as_rows_of_numbers` holds that)."""
    q, k, v = _rand_qkv(5, 1, 64, 2, 1, 32)
    loss = lambda q, k, v: pallas_flash_attention(q, k, v, causal=True, block_q=16, block_k=16, interpret=True).sum()  # noqa: E731
    assert sorted(_kernel_names(jax.grad(loss, argnums=(0, 1, 2)), q, k, v)) == ["flash_attention_bwd", "flash_attention_fwd"]
    # and the dispatcher's fallback off the TPU: SDPA where it can, the plain softmax where v is narrower
    from modalities_tpu.ops.attention import flash_attention_or_fallback

    narrow = v[..., :16]
    np.testing.assert_allclose(flash_attention_or_fallback(q, k, narrow), manual_attention(q, k, narrow), rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(flash_attention_or_fallback(q, k, v), manual_attention(q, k, v), rtol=2e-5, atol=2e-5)


def test_blocks_are_looked_up_by_both_widths_only_where_they_differ(monkeypatch):
    """The accepted cells read `flash_attention|*|*`; latent attention reads the bucket of its two widths, at any sequence."""
    from modalities_tpu.ops.pallas import autotune

    asked = []
    monkeypatch.setattr(autotune, "lookup", lambda kernel, bucket, dtype: asked.append((kernel, bucket)) or None)
    flash.flash_blocks(4096, 4096, head_dim=80, head_dim_v=80)
    flash.flash_blocks(4096, 4096)
    flash.flash_blocks(8192, 8192, head_dim=192, head_dim_v=128)
    flash.flash_blocks(4096, 4096, head_dim=192, head_dim_v=128)
    assert asked == [("flash_attention", bucket) for bucket in ("sq4096_sk4096", "sq4096_sk4096", "d192_dv128", "d192_dv128")]
    del asked[:]  # the fused backward's blocks: its own entry of the same bucket first, the forward's where it has none
    flash.flash_blocks(8192, 8192, head_dim=192, head_dim_v=128, backward=True)
    assert asked == [("flash_attention_bwd", "d192_dv128"), ("flash_attention", "d192_dv128")]
    monkeypatch.undo()
    assert autotune.lookup("flash_attention_bwd", "d192_dv128", "bfloat16", device_kind="TPU v5 lite") == {"block_q": 1024, "block_k": 1024}
    assert autotune.lookup("flash_attention_bwd", "sq4096_sk4096", "bfloat16", device_kind="TPU v5 lite") is None  # the forward's there
    assert autotune.lookup("flash_attention", "sq4096_sk4096", "bfloat16", device_kind="TPU v5 lite") == {"block_q": 1024, "block_k": 1024}
    hit = autotune.lookup("flash_attention", "d192_dv128", "bfloat16", device_kind="TPU v5 lite")
    assert hit is not None and (hit["block_q"], hit["block_k"]) != (1024, 1024), "1024 x 1024 does not fit VMEM at 192/128 (bwd_dq)"


# batch, rows, q heads, kv heads, width of q and k, of v, forward blocks, backward blocks, window: each cell's call as
# `ops/attention.py` makes it (benchmark/configs/*/train.yaml, tuning_tables/v5e.json), the 32k recipe's two-kernel
# backward, and the 8 positions of `init_params`' dummy forward; traced, not run
CALLS = {
    "train-2p7b-4k": (2, 4096, 32, 8, 80, 80, (1024, 1024), None, None),
    "train-jamba2-3b-4k": (1, 4096, 20, 1, 128, 128, (1024, 1024), None, None),
    "train-ouro-2p6b-4k": (1, 4096, 16, 16, 128, 128, (1024, 1024), None, None),
    "train-kanana2-30b-8k": (2, 8192, 32, 32, 192, 128, (1024, 512), (1024, 1024), None),
    "train-mellum2-12b-16k-window": (1, 16384, 32, 4, 128, 128, (1024, 1024), None, 1024),
    "train-mellum2-12b-16k-global": (1, 16384, 32, 4, 128, 128, (1024, 1024), None, None),
    "train-zaya1-8b-8k": (2, 8192, 8, 2, 128, 128, (1024, 1024), None, None),
    "rows-of-32k-two-kernels": (1, 32768, 12, 4, 128, 128, (1024, 1024), None, None),
    "dummy-forward-of-8": (1, 8, 4, 4, 128, 128, (1024, 1024), None, None),
}


@pytest.mark.parametrize("call", sorted(CALLS))
def test_the_statistics_cross_between_the_kernels_as_rows_of_numbers(call):
    """PR 42: lse (forward to backward) and delta (into the backward) are [B, H, 1, S] float32, which the chip lays out
    dense, where [B, H, S, 1] took a lane tile of 512 bytes a number (256 MiB for 2 MiB at 2 x 32 x 8192). Read off the
    jaxpr of a differentiated call: no float32 operand or result of any kernel has a trailing dimension of 1, and the
    backward kernels' lse IS the forward kernel's result: nothing squeezes or spreads it on the way."""
    batch, seq, hq, hkv, d, dv, (block_q, block_k), bwd_blocks, window = CALLS[call]
    shapes = [jax.ShapeDtypeStruct((batch, seq, heads, width), jnp.bfloat16) for heads, width in ((hq, d), (hkv, d), (hkv, dv))]
    kernel = functools.partial(pallas_flash_attention, block_q=block_q, block_k=block_k, bwd_blocks=bwd_blocks, window=window, interpret=True)
    calls = _pallas_calls(jax.grad(lambda q, k, v: kernel(q, k, v).astype(jnp.float32).sum(), argnums=(0, 1, 2)), *shapes)
    forward, backward = calls[0], calls[1:]
    assert len(backward) == (2 if seq == 32768 else 1)
    rows = (batch, hq, 1, seq)
    for eqn in calls:
        for var in (*eqn.invars, *eqn.outvars):
            if var.aval.dtype == jnp.float32:
                assert var.aval.shape == rows, (eqn.params["name"], var.aval)
    lse = forward.outvars[1]
    assert lse.aval.shape == rows and lse.aval.dtype == jnp.float32
    for eqn in backward:
        statistics = [var for var in eqn.invars if var.aval.dtype == jnp.float32]
        assert len(statistics) == 2 and statistics[0] is lse  # lse as the forward wrote it, then delta


@functools.lru_cache(maxsize=None)
def _row_of_200():
    q, k, v = _rand_qkv(11, 1, 200, 2, 1, 16)
    w = jax.random.normal(jax.random.PRNGKey(12), q.shape)
    kernel = functools.partial(pallas_flash_attention, causal=True, block_q=40, block_k=40, interpret=True)
    return _values_and_gradients(kernel, manual_attention, q, k, v, w, 40, 40, diag_sub_block=flash._DIAG_SUB_BLOCK)  # the module's own


@pytest.mark.parametrize("backward", BACKWARDS)
def test_a_row_that_is_no_multiple_of_a_lane_tile(backward):
    """200 positions in tiles of 40: the rows of statistics are sliced at lanes no tile boundary falls on (interpreted:
    on a TPU such a row is one tile, as the dummy forward of 8 is, or its blocks are multiples of 128)."""
    found = _row_of_200()
    np.testing.assert_allclose(found["out"], found["reference"], rtol=2e-5, atol=2e-5)
    for g, e, name in zip(found[backward], found["oracle"], "qkv"):
        np.testing.assert_allclose(g, e, rtol=5e-4, atol=5e-4, err_msg=f"d{name}")
