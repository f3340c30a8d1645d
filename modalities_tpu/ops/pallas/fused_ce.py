"""Pallas TPU vocab-streaming fused cross-entropy.

The LM head + CE is the single biggest HBM hog left in train_step: even the
chunked scan materializes a `[B, chunk, V]` fp32 logits buffer per step and
recomputes the whole chunk projection in the backward under `jax.checkpoint`.
This kernel family never writes logits to HBM in either pass:

- forward: stream the vocab dimension tile-by-tile, keeping the per-row running
  max / exp-sum (flash-style online logsumexp) and the gathered correct-class
  logit in `[block_rows, 1]` VMEM scratch; only `lse` and `corr` (two `[N, 1]`
  vectors) ever reach HBM.
- backward (custom_vjp): regenerate the softmax tile-wise from the saved `lse`
  — `ds = g * mask * (exp(s - lse) - onehot(label))` — and contract it on the
  fly into `d_hidden` (vocab-innermost accumulation) and `d_head_weight`
  (rows-innermost accumulation). The `[*, V]` tensor never exists.

All tile math accumulates in fp32 regardless of input dtype (bf16 hidden is the
production case). `interpret=True` runs the same kernels under the Pallas CPU
emulator so tier-1 tests check exact numerics, mirroring flash_attention.py.

Shape handling: the public wrapper flattens rows, then pads rows and vocab up
to block multiples *outside* the custom_vjp — padded label rows carry
`ignore_index` (mask 0, so they touch neither the loss nor any gradient) and
padded vocab columns are masked to -inf inside the kernel before the exp (so
they contribute exactly 0 to the softmax). Autodiff through the pad/slice
returns gradients for the original shapes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _pow2_ceil(n: int) -> int:
    return 1 << max(0, int(n) - 1).bit_length()


def _row_block(n: int, preferred: int) -> int:
    # sublane-aligned (multiple of 8) and never absurdly larger than n
    return max(8, min(preferred, _pow2_ceil(n)))


def _vocab_block(v: int, preferred: int) -> int:
    # lane-aligned (multiple of 128); the wrapper pads V up to a multiple
    return max(128, min(preferred, _pow2_ceil(v)))


# Mosaic gives a kernel 16 MiB of scoped VMEM on a v5e, the smallest of the
# supported chips. The estimate below ran 0.1-0.25 MiB under what the compiler
# reported for bf16 at E 2560 and 4096, hence the margin.
_VMEM_BUDGET_BYTES = 15 * 2**20


def _fit_blocks_to_vmem(block_n: int, block_v: int, e: int, itemsize: int) -> tuple[int, int]:
    """Halve the larger block until both backward kernels fit scoped VMEM.

    Each backward kernel holds, for the side it accumulates over (vocab tiles
    for d_head_weight, row tiles for d_hidden), a double-buffered input block,
    a double-buffered output block and an fp32 accumulator; for the side it
    streams, a double-buffered input block; plus one fp32 score tile. At
    256x512 that is 18 MiB for bf16 at E 2560 — the shipped blocks were sized
    at E 1536, where it is 11."""

    def need(acc_rows: int, stream_rows: int) -> int:
        return e * (acc_rows * (4 * itemsize + 4) + stream_rows * 2 * itemsize) + 4 * block_n * block_v

    while max(need(block_v, block_n), need(block_n, block_v)) > _VMEM_BUDGET_BYTES:
        if block_v >= block_n and block_v > 128:
            block_v //= 2
        elif block_n > 8:
            block_n //= 2
        else:
            break
    return block_n, block_v


# ------------------------------------------------------------------ forward


def _fwd_kernel(h_ref, w_ref, y_ref, lse_ref, corr_ref, m_ref, l_ref, c_ref, *, block_v, vocab):
    jv = pl.program_id(1)
    nv = pl.num_programs(1)

    @pl.when(jv == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        c_ref[...] = jnp.zeros_like(c_ref)

    h = h_ref[...].astype(jnp.float32)  # [bn, E]
    w = w_ref[...].astype(jnp.float32)  # [bv, E]
    labels = y_ref[...]  # [bn, 1] int32
    block_n = h.shape[0]

    s = jax.lax.dot_general(h, w, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    col = jv * block_v + jax.lax.broadcasted_iota(jnp.int32, (block_n, block_v), 1)
    valid = col < vocab  # padded vocab columns must not enter the softmax
    s = jnp.where(valid, s, NEG_INF)

    # gathered correct-class logit: at most one hit per row across all tiles
    c_ref[...] += jnp.where(col == labels, s, 0.0).sum(axis=-1, keepdims=True)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    l_ref[...] = l_ref[...] * jnp.exp(m_prev - m_new) + jnp.exp(s - m_new).sum(axis=-1, keepdims=True)
    m_ref[...] = m_new

    @pl.when(jv == nv - 1)
    def _finish():
        lse_ref[...] = m_ref[...] + jnp.log(jnp.maximum(l_ref[...], 1e-37))
        corr_ref[...] = c_ref[...]


def _ce_forward(h, w, labels2, block_n, block_v, vocab, interpret):
    n, e = h.shape
    v_padded = w.shape[0]
    grid = (n // block_n, v_padded // block_v)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, block_v=block_v, vocab=vocab),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, e), lambda i, j: (i, 0)),
            pl.BlockSpec((block_v, e), lambda i, j: (j, 0)),
            pl.BlockSpec((block_n, 1), lambda i, j: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_n, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((block_n, 1), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, 1), jnp.float32),
            jax.ShapeDtypeStruct((n, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_n, 1), jnp.float32),
            pltpu.VMEM((block_n, 1), jnp.float32),
            pltpu.VMEM((block_n, 1), jnp.float32),
        ],
        interpret=interpret,
        name="fused_ce_fwd",
    )(h, w, labels2)


# ----------------------------------------------------------------- backward


def _softmax_delta(h_ref, w_ref, y_ref, lse_ref, gm_ref, jv, *, block_v, vocab):
    """Regenerate one `[bn, bv]` tile of ds = gm * (softmax(s) - onehot(label))."""
    h = h_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)
    labels = y_ref[...]
    lse = lse_ref[...]
    gm = gm_ref[...]
    block_n = h.shape[0]

    s = jax.lax.dot_general(h, w, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    col = jv * block_v + jax.lax.broadcasted_iota(jnp.int32, (block_n, block_v), 1)
    s = jnp.where(col < vocab, s, NEG_INF)
    p = jnp.exp(s - lse)
    return gm * (p - jnp.where(col == labels, 1.0, 0.0))


def _bwd_dh_kernel(h_ref, w_ref, y_ref, lse_ref, gm_ref, dh_ref, acc_ref, *, block_v, vocab):
    jv = pl.program_id(1)
    nv = pl.num_programs(1)

    @pl.when(jv == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    ds = _softmax_delta(h_ref, w_ref, y_ref, lse_ref, gm_ref, jv, block_v=block_v, vocab=vocab)
    w = w_ref[...].astype(jnp.float32)
    acc_ref[...] += jax.lax.dot_general(ds, w, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(jv == nv - 1)
    def _finish():
        dh_ref[...] = acc_ref[...].astype(dh_ref.dtype)


def _bwd_dw_kernel(h_ref, w_ref, y_ref, lse_ref, gm_ref, dw_ref, acc_ref, *, block_v, vocab):
    jv = pl.program_id(0)
    ir = pl.program_id(1)
    nr = pl.num_programs(1)

    @pl.when(ir == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    ds = _softmax_delta(h_ref, w_ref, y_ref, lse_ref, gm_ref, jv, block_v=block_v, vocab=vocab)
    h = h_ref[...].astype(jnp.float32)
    acc_ref[...] += jax.lax.dot_general(ds, h, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(ir == nr - 1)
    def _finish():
        dw_ref[...] = acc_ref[...].astype(dw_ref.dtype)


def _ce_backward(h, w, labels2, lse, gm, block_n, block_v, vocab, interpret):
    n, e = h.shape
    v_padded = w.shape[0]
    row_specs = dict(h=(block_n, e), y=(block_n, 1))
    dh = pl.pallas_call(
        functools.partial(_bwd_dh_kernel, block_v=block_v, vocab=vocab),
        grid=(n // block_n, v_padded // block_v),  # vocab innermost: acc over tiles
        in_specs=[
            pl.BlockSpec(row_specs["h"], lambda i, j: (i, 0)),
            pl.BlockSpec((block_v, e), lambda i, j: (j, 0)),
            pl.BlockSpec(row_specs["y"], lambda i, j: (i, 0)),
            pl.BlockSpec((block_n, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((block_n, 1), lambda i, j: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_n, e), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, e), h.dtype),
        scratch_shapes=[pltpu.VMEM((block_n, e), jnp.float32)],
        interpret=interpret,
        name="fused_ce_bwd_dh",
    )(h, w, labels2, lse, gm)
    dw = pl.pallas_call(
        functools.partial(_bwd_dw_kernel, block_v=block_v, vocab=vocab),
        grid=(v_padded // block_v, n // block_n),  # rows innermost: acc over tiles
        in_specs=[
            pl.BlockSpec(row_specs["h"], lambda j, i: (i, 0)),
            pl.BlockSpec((block_v, e), lambda j, i: (j, 0)),
            pl.BlockSpec(row_specs["y"], lambda j, i: (i, 0)),
            pl.BlockSpec((block_n, 1), lambda j, i: (i, 0)),
            pl.BlockSpec((block_n, 1), lambda j, i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_v, e), lambda j, i: (j, 0)),
        out_shape=jax.ShapeDtypeStruct((v_padded, e), w.dtype),
        scratch_shapes=[pltpu.VMEM((block_v, e), jnp.float32)],
        interpret=interpret,
        name="fused_ce_bwd_dw",
    )(h, w, labels2, lse, gm)
    return dh, dw


# ---------------------------------------------------------------- custom_vjp


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _fused_ce(h, w, labels2, ignore_index, block_n, block_v, vocab, interpret):
    (total, count), _ = _fused_ce_fwd(h, w, labels2, ignore_index, block_n, block_v, vocab, interpret)
    return total, count


def _fused_ce_fwd(h, w, labels2, ignore_index, block_n, block_v, vocab, interpret):
    lse, corr = _ce_forward(h, w, labels2, block_n, block_v, vocab, interpret)
    mask = (labels2 != ignore_index).astype(jnp.float32)  # [N, 1]
    total = ((lse - corr) * mask).sum()
    count = mask.sum()
    return (total, count), (h, w, labels2, lse, mask)


def _fused_ce_bwd(ignore_index, block_n, block_v, vocab, interpret, residuals, cotangents):
    h, w, labels2, lse, mask = residuals
    g_total, _g_count = cotangents  # count is a function of the int labels only
    gm = (g_total * mask).astype(jnp.float32)  # [N, 1]
    dh, dw = _ce_backward(h, w, labels2, lse, gm, block_n, block_v, vocab, interpret)
    dlabels = np.zeros(labels2.shape, dtype=jax.dtypes.float0)
    return dh, dw, dlabels


_fused_ce.defvjp(_fused_ce_fwd, _fused_ce_bwd)


# ------------------------------------------------------------- public entry


def fused_ce_sum_and_count(
    hidden,
    head_weight,
    labels,
    *,
    ignore_index: int = -100,
    block_rows: int = 256,
    block_vocab: int = 512,
    interpret: bool = False,
):
    """Streaming-softmax CE over `hidden @ head_weight.T` without materializing
    logits. Returns `(total_loss, token_count)` as fp32 scalars, matching the
    contract of `CLMCrossEntropyLoss.sum_and_count(logits, labels)`.

    hidden: [..., E] (any leading shape; bf16 or fp32), head_weight: [V, E],
    labels: [...] int, `ignore_index` rows excluded from both sum and count.
    Differentiable wrt hidden and head_weight (fp32 accumulation throughout).
    """
    e = hidden.shape[-1]
    v = head_weight.shape[0]
    n = int(np.prod(hidden.shape[:-1])) if hidden.ndim > 1 else hidden.shape[0]

    h2 = hidden.reshape(n, e)
    lab2 = labels.reshape(n, 1).astype(jnp.int32)

    bn, bv = _fit_blocks_to_vmem(
        _row_block(n, block_rows), _vocab_block(v, block_vocab), e, jnp.dtype(hidden.dtype).itemsize
    )
    n_pad = -n % bn
    v_pad = -v % bv
    if n_pad:
        h2 = jnp.pad(h2, ((0, n_pad), (0, 0)))
        lab2 = jnp.pad(lab2, ((0, n_pad), (0, 0)), constant_values=ignore_index)
    w = jnp.pad(head_weight, ((0, v_pad), (0, 0))) if v_pad else head_weight

    return _fused_ce(h2, w, lab2, ignore_index, bn, bv, v, interpret)
