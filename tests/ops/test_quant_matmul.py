"""Parity + dispatch pins for the fused dequant-matmul (ops/quant_matmul.py,
ops/pallas/quant_matmul.py): interpret-mode kernel output is BITWISE equal to
the pure-jnp reference where one tile holds N (K is never split, so the contraction
order matches) and within a few roundings of it where N takes several tiles,
the kernel runs where `ops/tiers.py` says, and its blocks come from the tuning table or the defaults."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from modalities_tpu.ops.pallas.quant_matmul import (
    flops_and_bytes,
    quant_matmul,
    reference_quant_matmul,
)
from modalities_tpu.ops import tiers
from modalities_tpu.ops.quant_matmul import quant_matmul_or_fallback, resolve_quant_matmul_blocks
from modalities_tpu.quant.core import quantize_per_channel


def _case(m, k, n, seed=0, dtype=jnp.float32):
    kx, kw = jax.random.split(jax.random.PRNGKey(seed))
    x = jax.random.normal(kx, (m, k), dtype=dtype)
    w = jax.random.normal(kw, (n, k))
    wq_t, scale = quantize_per_channel(w, axis=-1)  # [N, K] rows -> per-N scales
    return x, wq_t.T, jnp.squeeze(scale, -1)  # wq [K, N], scale [N]


@pytest.mark.parametrize(
    "m,k,n,bm,bn",
    [
        (8, 16, 24, 8, 8),  # multi-tile both ways
        (5, 16, 9, 8, 8),  # ragged M and N (padding path)
        (16, 32, 16, 16, 16),  # exact tiles
    ],
)
def test_interpret_kernel_bitwise_matches_reference(m, k, n, bm, bn):
    x, wq, scale = _case(m, k, n)
    got = quant_matmul(x, wq, scale, block_m=bm, block_n=bn, interpret=True)
    want = reference_quant_matmul(x, wq, scale)
    assert got.shape == (m, n) and got.dtype == want.dtype
    # one tile of N is the reference's own dot, bit for bit; several tiles are dots
    # of another width, which a CPU backend may sum in another order: 4 float32
    # roundings of the largest output (docs/known_failures.md)
    atol = 0.0 if n <= bn else 4 * np.finfo(np.float32).eps * float(np.abs(want).max())
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0.0, atol=atol)


def test_bf16_inputs_round_trip():
    x, wq, scale = _case(4, 16, 8, dtype=jnp.bfloat16)
    got = quant_matmul(x, wq, scale, block_m=4, block_n=8, interpret=True)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_array_equal(
        np.asarray(got.astype(jnp.float32)),
        np.asarray(reference_quant_matmul(x, wq, scale).astype(jnp.float32)),
    )


def test_reference_dequant_is_exactly_scaled_int_matmul():
    x, wq, scale = _case(4, 8, 6)
    want = (x @ wq.astype(x.dtype)) * scale
    np.testing.assert_allclose(
        np.asarray(reference_quant_matmul(x, wq, scale)), np.asarray(want), rtol=1e-6
    )


def test_the_kernel_runs_where_the_rule_says_and_the_reference_elsewhere(monkeypatch):
    """Off a TPU the pure-jnp expression; the kernel, interpreted, by the wrapper's keyword or inside the tests' seam:
    counted by the calls that reach `ops/pallas/quant_matmul.quant_matmul` through the dispatcher."""
    import modalities_tpu.ops.quant_matmul as dispatch

    reached = []
    monkeypatch.setattr(dispatch, "quant_matmul", lambda *a, **kw: reached.append(kw["interpret"]) or quant_matmul(*a, **kw))
    x, wq, scale = _case(4, 8, 6)
    want = np.asarray(reference_quant_matmul(x, wq, scale))
    np.testing.assert_array_equal(np.asarray(quant_matmul_or_fallback(x, wq, scale)), want)
    assert not tiers.kernels_run() and reached == []
    np.testing.assert_array_equal(np.asarray(quant_matmul_or_fallback(x, wq, scale, interpret=True)), want)
    with tiers.interpreted_kernels():
        assert tiers.kernels_run()
        np.testing.assert_array_equal(np.asarray(quant_matmul_or_fallback(x, wq, scale)), want)
    assert reached == [True, True] and not tiers.kernels_run()  # the seam closes behind itself


def test_blocks_come_from_the_tuning_table_or_the_defaults(tune_table):
    from modalities_tpu.ops.pallas.quant_matmul import DEFAULT_BLOCK_M, DEFAULT_BLOCK_N

    tune_table({})  # a table that answers for nothing
    assert resolve_quant_matmul_blocks(4096, jnp.bfloat16) == (DEFAULT_BLOCK_M, DEFAULT_BLOCK_N)
    tune_table({"quant_matmul|m4096|*": {"block_m": 32, "block_n": 64}, "quant_matmul|*|*": {"block_m": 64}})
    assert resolve_quant_matmul_blocks(4096, jnp.bfloat16) == (32, 64)
    assert resolve_quant_matmul_blocks(512, jnp.bfloat16) == (64, DEFAULT_BLOCK_N)  # the wildcard entry, its missing size at the default
    tune_table({"quant_matmul|m4096|*": {"block_m": "notanint"}})
    with pytest.raises(ValueError):  # a malformed size raises: it never quietly becomes another block
        resolve_quant_matmul_blocks(4096, jnp.bfloat16)


def test_flops_and_bytes_accounting():
    cost = flops_and_bytes(8, 16, 24, x_bytes=4, w_bytes=1)
    assert cost["flops"] == 2 * 8 * 16 * 24
    assert cost["bytes"] == 8 * 16 * 4 + 16 * 24 * 1 + 8 * 24 * 4 + 4 * 24
    # int8 weights move 4x less weight traffic than f32 at the same shape
    assert cost["bytes"] < flops_and_bytes(8, 16, 24, x_bytes=4, w_bytes=4)["bytes"]
