"""Pallas TPU kernels for the two row norms of the gated delta rule's mixer over a head's channels (forward and
`custom_vjp` backward each): every operand is read once and every result written once a pass, in the inputs' dtype,
and the float32 arithmetic between stays in VMEM.

An operand `[B, S, H, width]` is taken as `[B S, H width]`, positions by every head's channels side by side, which is how
its producers write it and its consumers read it on the chip (the projections' products, the convolution over the
sequence, `out_proj`'s contraction over heads and channels together: step 1 of PR 46 found the plain forms' time in
float32 relayouts between that and a heads-on-sublanes `[B S H, width]`, PERF.md section 5). `width` is a whole number
of 128-lane tiles, so a head is a column of whole lane tiles and a block of `block_rows` positions of one head is whole
tiles as they lie in HBM: the grid walks (blocks of positions, heads), and a grid step walks its block in slabs of
`SLAB` rows, a loop whose passes are independent and long enough to hide their own latencies:

- `head_l2_norm(x, scale)`: `y = scale * x * r`, `r = rsqrt(sum(x^2) + 1e-6)` (`models/gpt2/gdn.l2_normalised`).
  Backward: `dx = scale * r * (dy - x * r^2 * sum(dy * x))`.
- `gated_head_rms_norm(o, z, w, eps)`: `y = o * r * w * silu(z)`, `r = rsqrt(mean(o^2) + eps)`. Backward, with `n = o * r`,
  `s = silu(z)`, `g = dy * w * s`: `do = r * (g - n * mean(g * n))`, `dz = dy * n * w * silu'(z)`,
  `dw = sum over rows of dy * n * s`, which leaves as one partial row a grid step `[blocks, heads, 1, width]` (each step
  owns its row: no race) and is summed outside, as `fused_rmsnorm_bwd`'s `dscale`.

`r` is computed again in the backward, not kept: a `[rows, 1]` float32 residual lies in HBM a lane tile a number (512
bytes a row where the row itself is 256 in bfloat16), and a second reduction over 128 lanes costs the backward kernel
nothing it can see beside its five arrays' traffic. So the residuals are the operands alone.

All arithmetic is float32 from the inputs' dtype, each output rounded once: what the plain forms do.
`interpret=True` runs the same kernels under the Pallas CPU emulator (tests).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES = 128
# positions a grid step, and of them a slab (one pass of the kernel's loop): swept 512 to 8192 and 64 to 512 on a v5e at the cell's shapes
# (`scripts/head_norm_bench.py`, PERF.md section 6, PR 46). A slab's chain (row sum across the lanes, rsqrt, the products) leaves about 120
# cycles of waiting a pass that only more independent rows fill: the four kernels took 0.49 / 0.50 / 0.98 / 1.87 ms at slabs of 64 rows,
# 0.15 / 0.23 / 0.67 / 1.04 at 512. At 8192 positions the gated backward's ten blocks pass Mosaic's 16 MiB of scoped VMEM.
BLOCK_ROWS = 4096
SLAB = 512
L2_EPS = 1e-6
KERNELS = {"l2": ("head_l2_norm_fwd", "head_l2_norm_bwd"), "gated": ("gated_head_rms_norm_fwd", "gated_head_rms_norm_bwd")}

_F32 = jnp.float32


def sublane_tile(dtype) -> int:
    """Rows of a `[rows, 128]` tile of this dtype in VMEM: 8 for float32, 16 for bfloat16; 0 for a dtype the kernels do not take."""
    return {jnp.dtype(jnp.float32): 8, jnp.dtype(jnp.bfloat16): 16}.get(jnp.dtype(dtype), 0)


def plan_rows(rows: int, width: int, dtype, block_rows: int = BLOCK_ROWS) -> int:
    """Positions a grid step takes of `rows` (each with `width` channels a head), 0 where the kernels do not serve the shape: the
    last axis fills whole lane tiles, the positions whole sublane tiles of the dtype. Whole slabs, at most `block_rows`; the last
    grid step may hold fewer rows than that."""
    tile = sublane_tile(dtype)
    if not tile or width % LANES or rows <= 0 or rows % tile:
        return 0
    slab = min(SLAB, block_rows)
    return rows if rows < slab else min(block_rows, rows) // slab * slab


def _slabs(block: int, body, carry=None):
    """`body(rows, carry)` over the slabs of a block of `block` rows, `rows` a slice of whole sublane tiles."""
    slab = min(SLAB, block)

    def step(i, carry):
        return body(pl.ds(pl.multiple_of(i * slab, slab), slab), carry)

    return jax.lax.fori_loop(0, block // slab, step, carry)


def _row_sum(x):
    return jnp.sum(x, axis=-1, keepdims=True)


def _l2_fwd_kernel(x_ref, y_ref, *, scale):
    def slab(rows, _):
        x = x_ref[rows, :].astype(_F32)
        y_ref[rows, :] = (x * (scale * jax.lax.rsqrt(_row_sum(x * x) + L2_EPS))).astype(y_ref.dtype)

    _slabs(x_ref.shape[0], slab)


def _l2_bwd_kernel(x_ref, dy_ref, dx_ref, *, scale):
    def slab(rows, _):
        x, dy = x_ref[rows, :].astype(_F32), dy_ref[rows, :].astype(_F32)
        r = jax.lax.rsqrt(_row_sum(x * x) + L2_EPS)
        dx_ref[rows, :] = ((scale * r) * (dy - x * (r * r * _row_sum(dy * x)))).astype(dx_ref.dtype)

    _slabs(x_ref.shape[0], slab)


def _gated_fwd_kernel(o_ref, z_ref, w_ref, y_ref, *, eps):
    w = w_ref[...].astype(_F32)  # [1, width]
    share = 1.0 / o_ref.shape[1]

    def slab(rows, _):
        o, z = o_ref[rows, :].astype(_F32), z_ref[rows, :].astype(_F32)
        r = jax.lax.rsqrt(_row_sum(o * o) * share + eps)
        y_ref[rows, :] = (o * r * w * (z * jax.nn.sigmoid(z))).astype(y_ref.dtype)

    _slabs(o_ref.shape[0], slab)


def _gated_bwd_kernel(o_ref, z_ref, w_ref, dy_ref, do_ref, dz_ref, dw_ref, *, eps, rows_in_all):
    w = w_ref[...].astype(_F32)
    block, width = o_ref.shape
    share = 1.0 / width
    first = pl.program_id(0) * block  # the last grid step may reach past the array: its rows there hold anything

    def slab(rows, dw):
        o, z, dy = o_ref[rows, :].astype(_F32), z_ref[rows, :].astype(_F32), dy_ref[rows, :].astype(_F32)
        r = jax.lax.rsqrt(_row_sum(o * o) * share + eps)
        n = o * r
        gate = jax.nn.sigmoid(z)
        s = z * gate
        g = dy * w * s
        do_ref[rows, :] = (r * (g - n * (_row_sum(g * n) * share))).astype(do_ref.dtype)
        dz_ref[rows, :] = (dy * n * w * (gate * (1.0 + z * (1.0 - gate)))).astype(dz_ref.dtype)
        at = first + rows.start + jax.lax.broadcasted_iota(jnp.int32, (rows.size, 1), 0)
        return dw + jnp.where(at < rows_in_all, dy * n * s, 0.0)

    dw = _slabs(block, slab, jnp.zeros((min(SLAB, block), width), _F32))
    dw_ref[...] = jnp.sum(dw, axis=0, keepdims=True)


def _head(block: int, width: int):
    """A block of positions by one head's lanes of `[positions, heads * width]`: the grid's second axis walks the heads."""
    return pl.BlockSpec((block, width), lambda i, h: (i, h))


def _whole(width: int):
    return pl.BlockSpec((1, width), lambda i, h: (0, 0))


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4))
def _l2(x2, scale, heads, block, interpret):
    return _l2_fwd(x2, scale, heads, block, interpret)[0]


def _l2_fwd(x2, scale, heads, block, interpret):
    width = x2.shape[1] // heads
    y = pl.pallas_call(
        functools.partial(_l2_fwd_kernel, scale=scale),
        grid=(pl.cdiv(x2.shape[0], block), heads),
        in_specs=[_head(block, width)],
        out_specs=_head(block, width),
        out_shape=jax.ShapeDtypeStruct(x2.shape, x2.dtype),
        interpret=interpret,
        name=KERNELS["l2"][0],
    )(x2)
    return y, x2


def _l2_bwd(scale, heads, block, interpret, x2, dy):
    width = x2.shape[1] // heads
    dx = pl.pallas_call(
        functools.partial(_l2_bwd_kernel, scale=scale),
        grid=(pl.cdiv(x2.shape[0], block), heads),
        in_specs=[_head(block, width)] * 2,
        out_specs=_head(block, width),
        out_shape=jax.ShapeDtypeStruct(x2.shape, x2.dtype),
        interpret=interpret,
        name=KERNELS["l2"][1],
    )(x2, dy)
    return (dx,)


_l2.defvjp(_l2_fwd, _l2_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _gated(o2, z2, w2, eps, heads, block, interpret):
    return _gated_fwd(o2, z2, w2, eps, heads, block, interpret)[0]


def _gated_fwd(o2, z2, w2, eps, heads, block, interpret):
    width = o2.shape[1] // heads
    y = pl.pallas_call(
        functools.partial(_gated_fwd_kernel, eps=eps),
        grid=(pl.cdiv(o2.shape[0], block), heads),
        in_specs=[_head(block, width), _head(block, width), _whole(width)],
        out_specs=_head(block, width),
        out_shape=jax.ShapeDtypeStruct(o2.shape, o2.dtype),
        interpret=interpret,
        name=KERNELS["gated"][0],
    )(o2, z2, w2)
    return y, (o2, z2, w2)


def _gated_bwd(eps, heads, block, interpret, residuals, dy):
    o2, z2, w2 = residuals
    rows, width = o2.shape[0], o2.shape[1] // heads
    blocks = pl.cdiv(rows, block)
    do, dz, dw = pl.pallas_call(
        functools.partial(_gated_bwd_kernel, eps=eps, rows_in_all=rows),
        grid=(blocks, heads),
        in_specs=[_head(block, width), _head(block, width), _whole(width), _head(block, width)],
        out_specs=[_head(block, width), _head(block, width), pl.BlockSpec((None, None, 1, width), lambda i, h: (i, h, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct(o2.shape, o2.dtype), jax.ShapeDtypeStruct(z2.shape, z2.dtype),
                   jax.ShapeDtypeStruct((blocks, heads, 1, width), _F32)],
        interpret=interpret,
        name=KERNELS["gated"][1],
    )(o2, z2, w2, dy)
    return do, dz, dw.sum(axis=(0, 1)).astype(w2.dtype)


_gated.defvjp(_gated_fwd, _gated_bwd)


def _by_position(x):
    """`x [..., heads, width]` (or `[rows, width]`: one head) as `[positions, heads * width]`, and its heads; with positions of
    zeros up to a whole sublane tile where a shard holds fewer (a zero row's norm and gradients are zero)."""
    heads = x.shape[-2] if x.ndim > 2 else 1
    x2 = x.reshape(-1, heads * x.shape[-1])
    short = -x2.shape[0] % (sublane_tile(x.dtype) or 1)
    return (jnp.pad(x2, ((0, short), (0, 0))) if short else x2), heads


def _shaped_like(y2, x):
    positions = x.size // y2.shape[1]
    return (y2 if y2.shape[0] == positions else y2[:positions]).reshape(x.shape)


def head_l2_norm(x, scale: float = 1.0, *, block_rows: int = BLOCK_ROWS, interpret: bool = False):
    """`scale * x * rsqrt(sum(x^2) + 1e-6)` over the last axis of `x [..., heads, width]`, in x's dtype; float32 inside."""
    x2, heads = _by_position(x)
    block = plan_rows(x2.shape[0], x.shape[-1], x.dtype, block_rows)
    if not block:
        raise ValueError(f"head_l2_norm: no kernel for rows of {x.shape[-1]} {x.dtype}")
    return _shaped_like(_l2(x2, float(scale), heads, block, interpret), x)


def gated_head_rms_norm(o, z, w, *, eps: float, block_rows: int = BLOCK_ROWS, interpret: bool = False):
    """`o * rsqrt(mean(o^2) + eps) * w * silu(z)` over the last axis of `o`, `z [..., heads, width]` with `w [width]`, in o's
    dtype; float32 inside. `w` keeps its dtype and gets its gradient."""
    (o2, heads), (z2, _) = _by_position(o), _by_position(z)
    block = plan_rows(o2.shape[0], o.shape[-1], o.dtype, block_rows)
    if not block:
        raise ValueError(f"gated_head_rms_norm: no kernel for rows of {o.shape[-1]} {o.dtype}")
    return _shaped_like(_gated(o2, z2, w.reshape(1, -1), float(eps), heads, block, interpret), o)
