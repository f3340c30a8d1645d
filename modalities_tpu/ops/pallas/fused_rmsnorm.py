"""Pallas TPU fused RMSNorm (forward + custom_vjp backward).

The reference implementation (models/components/layer_norms.py) lowers to ~6
separate HBM round-trips per call (square, mean, rsqrt, scale-mul, bias-add,
dtype casts). Here each row block makes one trip: x is read once, y written
once, with the fp32 row statistic `r = rsqrt(mean(x^2) + eps)` saved as a
`[N, 1]` residual for the backward.

Backward math (g = dy * scale, x_hat = x * r):
    dx     = r * (g - x_hat * mean(g * x_hat, axis=-1))
    dscale = sum_rows dy * x_hat
    dbias  = sum_rows dy
dscale/dbias are emitted as per-row-block partials `[n_blocks, 1, E]` (each grid
step owns one output row — no cross-step races) and summed outside the kernel.
The singleton middle dim is there for Mosaic: a block's last two dims must tile
(8, 128) or equal the array's, and a `(1, E)` block of an `[n_blocks, E]` array
does neither.

`interpret=True` runs the same kernel under the Pallas CPU emulator for exact
tier-1 parity tests, mirroring flash_attention.py / fused_ce.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl


def _row_block(n: int, preferred: int) -> int:
    return max(8, min(preferred, 1 << max(0, int(n) - 1).bit_length()))


def _fwd_kernel(x_ref, s_ref, b_ref, y_ref, r_ref, *, eps):
    x = x_ref[...].astype(jnp.float32)  # [bn, E]
    scale = s_ref[...].astype(jnp.float32)  # [1, E]
    bias = b_ref[...].astype(jnp.float32)  # [1, E]
    r = jax.lax.rsqrt((x * x).mean(axis=-1, keepdims=True) + eps)
    y_ref[...] = (x * r * scale + bias).astype(y_ref.dtype)
    r_ref[...] = r


def _bwd_kernel(x_ref, s_ref, r_ref, dy_ref, dx_ref, dsp_ref, dbp_ref):
    x = x_ref[...].astype(jnp.float32)
    scale = s_ref[...].astype(jnp.float32)
    r = r_ref[...]
    dy = dy_ref[...].astype(jnp.float32)
    x_hat = x * r
    g = dy * scale
    dx = r * (g - x_hat * (g * x_hat).mean(axis=-1, keepdims=True))
    dx_ref[...] = dx.astype(dx_ref.dtype)
    dsp_ref[0] = (dy * x_hat).sum(axis=0, keepdims=True)
    dbp_ref[0] = dy.sum(axis=0, keepdims=True)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _fused_rms(x2, scale2, bias2, eps, block_n, interpret):
    y, _ = _fused_rms_fwd(x2, scale2, bias2, eps, block_n, interpret)
    return y


def _fused_rms_fwd(x2, scale2, bias2, eps, block_n, interpret):
    n, e = x2.shape
    y, r = pl.pallas_call(
        functools.partial(_fwd_kernel, eps=eps),
        grid=(n // block_n,),
        in_specs=[
            pl.BlockSpec((block_n, e), lambda i: (i, 0)),
            pl.BlockSpec((1, e), lambda i: (0, 0)),
            pl.BlockSpec((1, e), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_n, e), lambda i: (i, 0)),
            pl.BlockSpec((block_n, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, e), x2.dtype),
            jax.ShapeDtypeStruct((n, 1), jnp.float32),
        ],
        interpret=interpret,
        name="fused_rmsnorm_fwd",
    )(x2, scale2, bias2)
    return y, (x2, scale2, bias2, r)


def _fused_rms_bwd(eps, block_n, interpret, residuals, dy):
    x2, scale2, bias2, r = residuals
    n, e = x2.shape
    n_blocks = n // block_n
    dx, dscale_partial, dbias_partial = pl.pallas_call(
        _bwd_kernel,
        grid=(n_blocks,),
        in_specs=[
            pl.BlockSpec((block_n, e), lambda i: (i, 0)),
            pl.BlockSpec((1, e), lambda i: (0, 0)),
            pl.BlockSpec((block_n, 1), lambda i: (i, 0)),
            pl.BlockSpec((block_n, e), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_n, e), lambda i: (i, 0)),
            pl.BlockSpec((1, 1, e), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, 1, e), lambda i: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, e), x2.dtype),
            jax.ShapeDtypeStruct((n_blocks, 1, e), jnp.float32),
            jax.ShapeDtypeStruct((n_blocks, 1, e), jnp.float32),
        ],
        interpret=interpret,
        name="fused_rmsnorm_bwd",
    )(x2, scale2, r, dy)
    dscale = dscale_partial.sum(axis=0).astype(scale2.dtype)
    dbias = dbias_partial.sum(axis=0).astype(bias2.dtype)
    return dx, dscale, dbias


_fused_rms.defvjp(_fused_rms_fwd, _fused_rms_bwd)


def fused_rms_norm(x, scale=None, bias=None, *, eps: float = 1e-6, block_rows: int = 256, interpret: bool = False):
    """RMSNorm over the last axis of `x` in one HBM round-trip per row block.

    x: [..., E]; scale/bias: optional [E] params (None means identity — the
    kernel always runs with materialized ones/zeros so there is exactly one
    code path, and gradients to the constants are simply dropped by autodiff).
    Returns y with x's shape and dtype; math accumulates in fp32.
    """
    e = x.shape[-1]
    n = int(np.prod(x.shape[:-1])) if x.ndim > 1 else x.shape[0]
    x2 = x.reshape(n, e)
    scale2 = jnp.ones((1, e), dtype=jnp.float32) if scale is None else scale.reshape(1, e)
    bias2 = jnp.zeros((1, e), dtype=jnp.float32) if bias is None else bias.reshape(1, e)

    bn = _row_block(n, block_rows)
    n_pad = -n % bn
    if n_pad:
        x2 = jnp.pad(x2, ((0, n_pad), (0, 0)))
    y = _fused_rms(x2, scale2, bias2, float(eps), bn, interpret)
    if n_pad:
        y = y[:n]
    return y.reshape(x.shape)
