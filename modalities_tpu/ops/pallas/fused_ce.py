"""Pallas TPU vocab-streaming fused cross-entropy.

The LM head + CE is the single biggest HBM hog left in train_step: even the
chunked scan materializes a `[B, chunk, V]` fp32 logits buffer per step and
recomputes the whole chunk projection in the backward under `jax.checkpoint`.
This kernel family never writes logits to HBM in either pass, and computes them
twice a step (8 N E V of matmul for the 6 required; without a saved `[N, V]`
array that is the floor):

- forward, no gradient wanted (`fused_ce_eval`): stream the vocab dimension
  tile-by-tile, keeping the per-row running max / exp-sum (flash-style online
  logsumexp) and the gathered correct-class logit in `[block_rows, 1]` VMEM
  scratch; only `lse` and `corr` (two `[N, 1]` vectors) ever reach HBM.
- forward under differentiation (`fused_ce_fwd`, the custom_vjp's forward rule):
  the same pass also carries `sum_v softmax(s)[v] * W[v]`, the softmax's mean row
  of W, in an fp32 `[block_rows, E]` accumulator that is rescaled with the
  running max as flash attention's output is. The gradient of the hidden rows is
  that mean minus `W[label]`, so the backward needs no kernel for it:
  `d_hidden = g * mask * (mean - W[label])`, a gather and one elementwise pass.
- backward (`fused_ce_bwd_dw`): regenerate the softmax tile-wise from the saved
  `lse` — `ds = g * mask * (exp(s - lse) - onehot(label))` — and contract it on
  the fly into `d_head_weight` (rows-innermost accumulation). It needs `lse` over
  the whole vocabulary before any tile of it is right, so this recomputation
  cannot ride the forward. The `[*, V]` tensor never exists.

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


def _forward_vmem_bytes(block_n: int, block_v: int, e: int, itemsize: int, dh_in_forward: bool = True) -> int:
    """What the forward kernel holds: the hidden block and the streamed head block
    double-buffered, one fp32 score tile, the three `[block_n, 1]` columns (a lane tile
    wide each); carrying dh adds the fp32 accumulator and the fp32 output block,
    double-buffered."""
    lean = e * (block_n + block_v) * 2 * itemsize + 4 * block_n * block_v + 3 * 512 * block_n
    return lean + (12 * block_n * e if dh_in_forward else 0)


def _bwd_dw_vmem_bytes(block_n: int, block_v: int, e: int, itemsize: int) -> int:
    """What `fused_ce_bwd_dw` holds: head block in and gradient block out, both
    double-buffered, the fp32 accumulator, the streamed hidden block and one score tile."""
    return e * (block_v * (4 * itemsize + 4) + block_n * 2 * itemsize) + 4 * block_n * block_v


# Inside a whole train step the compiler asks more for the forward than for the kernel
# compiled alone. One reading (PR 30, the first step at that width): at E 2048, 256 x 512,
# against an untied head, Mosaic refused the step asking 16.80 MiB where the estimate (and the
# kernel alone) say 12.88: 3.92 MiB more, which is 4 bytes for every element of the [E, 512]
# head block (4.00 MiB). Counted so, the 256 x 256 that E 2560 steps down to reads 15.62 MiB
# and E 1536 at 256 x 512 12.88, and both compile inside their steps. The budget is the chip's
# own 16 MiB of scoped VMEM, not a fitted number: what was fitted is the one term above.
_STEP_BUDGET_BYTES = 16 * 2**20


def _forward_in_step_vmem_bytes(block_n: int, block_v: int, e: int, itemsize: int) -> int:
    return _forward_vmem_bytes(block_n, block_v, e, itemsize) + 4 * block_v * e


def _fit_blocks_to_vmem(block_n: int, block_v: int, e: int, itemsize: int) -> tuple[int, int]:
    """Halve the larger block until the differentiated forward and the backward
    kernel both fit scoped VMEM. At 256x512 the backward is 18 MiB for bf16 at
    E 2560 (the shipped blocks were sized at E 1536, where it is 11); at 256x256
    the forward is 13.1 MiB there and the backward 10.2. At E 2048 it is the forward
    inside a step that sends 256x512 down to 256x256."""
    while max(
        _forward_vmem_bytes(block_n, block_v, e, itemsize), _bwd_dw_vmem_bytes(block_n, block_v, e, itemsize)
    ) > _VMEM_BUDGET_BYTES or _forward_in_step_vmem_bytes(block_n, block_v, e, itemsize) > _STEP_BUDGET_BYTES:
        if block_v >= block_n and block_v > 128:
            block_v //= 2
        elif block_n > 8:
            block_n //= 2
        else:
            break
    return block_n, block_v


def ce_plan(rows: int, vocab: int, vocab_padded: int, e: int, block_n: int, block_v: int, itemsize: int,
            dh_in_forward: bool) -> dict:
    """What one traced call does: the facts of the sink event `fused_ce_plan`. `rows` as the
    kernels hold them (padded to the block; one shard's under a mesh). A differentiated call
    computes the logits in `fused_ce_fwd` and again in `fused_ce_bwd_dw`; a call nobody
    differentiates runs `fused_ce_eval` alone."""
    steps = (rows // block_n) * (vocab_padded // block_v)
    return {
        "rows": rows, "vocab": vocab, "vocab_padded": vocab_padded, "n_embd": e,
        "block_rows": block_n, "block_vocab": block_v,
        "dh_in_forward": dh_in_forward,
        "grid_steps_forward": steps, "grid_steps_bwd_dw": steps if dh_in_forward else 0,
        # matmul operations in units of rows x n_embd x vocab: done (logits and mean row, logits
        # and dW; or the logits alone) beside required (logits, dh, dW; or the logits)
        "nev_done": 8 if dh_in_forward else 2, "nev_required": 6 if dh_in_forward else 2,
        "forward_vmem_bytes": _forward_vmem_bytes(block_n, block_v, e, itemsize, dh_in_forward),
    }


def _say_plan(h, w, block_n: int, block_v: int, vocab: int, dh_in_forward: bool) -> None:
    """Runs while tracing, from the custom_vjp's primal and from its forward rule (only they
    know whether the call is differentiated): once per shape on the sink, nothing per step."""
    from modalities_tpu.telemetry import get_active_telemetry

    plan = ce_plan(h.shape[0], vocab, w.shape[0], h.shape[1], block_n, block_v, jnp.dtype(h.dtype).itemsize, dh_in_forward)
    get_active_telemetry().emit_event_once("fused_ce_plan", plan)


# ------------------------------------------------------------------ forward


def _running_softmax_tile(h_ref, w_ref, y_ref, m_ref, l_ref, c_ref, *, block_v, vocab):
    """One vocabulary tile of the online logsumexp: updates the running max, exp-sum and
    gathered correct-class logit, and hands back the tile of `exp(s - m_new)`, the factor
    `exp(m_prev - m_new)` that brings earlier sums to the new max, and the head block."""
    jv = pl.program_id(1)

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
    rescale = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_ref[...] = l_ref[...] * rescale + p.sum(axis=-1, keepdims=True)
    m_ref[...] = m_new
    return p, rescale, w


def _write_stats(lse_ref, corr_ref, m_ref, l_ref, c_ref):
    lse_ref[...] = m_ref[...] + jnp.log(jnp.maximum(l_ref[...], 1e-37))
    corr_ref[...] = c_ref[...]


def _eval_kernel(h_ref, w_ref, y_ref, lse_ref, corr_ref, m_ref, l_ref, c_ref, *, block_v, vocab):
    _running_softmax_tile(h_ref, w_ref, y_ref, m_ref, l_ref, c_ref, block_v=block_v, vocab=vocab)

    @pl.when(pl.program_id(1) == pl.num_programs(1) - 1)
    def _finish():
        _write_stats(lse_ref, corr_ref, m_ref, l_ref, c_ref)


def _fwd_kernel(h_ref, w_ref, y_ref, lse_ref, corr_ref, mean_ref, m_ref, l_ref, c_ref, acc_ref, *, block_v, vocab):
    """`_eval_kernel` that also carries `sum_v exp(s[v] - m) W[v]` with the running max, as
    flash attention carries its output; over the exp-sum at the last tile it is the
    softmax's mean row of W, which is d_hidden but for `W[label]`, the mask and the scale."""
    @pl.when(pl.program_id(1) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    p, rescale, w = _running_softmax_tile(h_ref, w_ref, y_ref, m_ref, l_ref, c_ref, block_v=block_v, vocab=vocab)
    acc_ref[...] = acc_ref[...] * rescale + jax.lax.dot_general(
        p, w, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )

    @pl.when(pl.program_id(1) == pl.num_programs(1) - 1)
    def _finish():
        _write_stats(lse_ref, corr_ref, m_ref, l_ref, c_ref)
        mean_ref[...] = acc_ref[...] / jnp.maximum(l_ref[...], 1e-37)


def _ce_forward(h, w, labels2, block_n, block_v, vocab, interpret, dh_in_forward):
    """`(lse, corr)`, and with `dh_in_forward` the fp32 `[N, E]` mean row of W as a third."""
    n, e = h.shape
    column = pl.BlockSpec((block_n, 1), lambda i, j: (i, 0))
    rows = pl.BlockSpec((block_n, e), lambda i, j: (i, 0))
    out_specs = [column, column]
    out_shape = [jax.ShapeDtypeStruct((n, 1), jnp.float32)] * 2
    scratch_shapes = [pltpu.VMEM((block_n, 1), jnp.float32)] * 3
    if dh_in_forward:
        out_specs.append(rows)
        out_shape.append(jax.ShapeDtypeStruct((n, e), jnp.float32))
        scratch_shapes.append(pltpu.VMEM((block_n, e), jnp.float32))
    return pl.pallas_call(
        functools.partial(_fwd_kernel if dh_in_forward else _eval_kernel, block_v=block_v, vocab=vocab),
        grid=(n // block_n, w.shape[0] // block_v),  # vocab innermost: the running sums over tiles
        in_specs=[rows, pl.BlockSpec((block_v, e), lambda i, j: (j, 0)), column],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch_shapes,
        interpret=interpret,
        # the differentiated step's forward keeps the name the train cells' traces are read by
        name="fused_ce_fwd" if dh_in_forward else "fused_ce_eval",
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


def _ce_backward_dw(h, w, labels2, lse, gm, block_n, block_v, vocab, interpret):
    n, e = h.shape
    v_padded = w.shape[0]
    column = pl.BlockSpec((block_n, 1), lambda j, i: (i, 0))
    return pl.pallas_call(
        functools.partial(_bwd_dw_kernel, block_v=block_v, vocab=vocab),
        grid=(v_padded // block_v, n // block_n),  # rows innermost: acc over tiles
        in_specs=[
            pl.BlockSpec((block_n, e), lambda j, i: (i, 0)),
            pl.BlockSpec((block_v, e), lambda j, i: (j, 0)),
            column,  # labels, lse, g * mask
            column,
            column,
        ],
        out_specs=pl.BlockSpec((block_v, e), lambda j, i: (j, 0)),
        out_shape=jax.ShapeDtypeStruct((v_padded, e), w.dtype),
        scratch_shapes=[pltpu.VMEM((block_v, e), jnp.float32)],
        interpret=interpret,
        name="fused_ce_bwd_dw",
    )(h, w, labels2, lse, gm)


# ---------------------------------------------------------------- custom_vjp


def _row_losses(lse, corr, labels2, ignore_index):
    """Every row's loss `[N, 1]` (0 where the label is ignored) and the float32 mask of what counts."""
    mask = (labels2 != ignore_index).astype(jnp.float32)  # [N, 1]
    return (lse - corr) * mask, mask


def _sum_and_count(lse, corr, labels2, ignore_index):
    rows, mask = _row_losses(lse, corr, labels2, ignore_index)
    return rows.sum(), mask.sum(), mask


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _fused_ce(h, w, labels2, ignore_index, block_n, block_v, vocab, interpret):
    """Traced only where nobody differentiates the call (an evaluator, the first pass of a
    head under `jax.checkpoint`): the lean kernel, which pays for no dh."""
    _say_plan(h, w, block_n, block_v, vocab, dh_in_forward=False)
    lse, corr = _ce_forward(h, w, labels2, block_n, block_v, vocab, interpret, dh_in_forward=False)
    total, count, _ = _sum_and_count(lse, corr, labels2, ignore_index)
    return total, count


def _fused_ce_fwd(h, w, labels2, ignore_index, block_n, block_v, vocab, interpret):
    _say_plan(h, w, block_n, block_v, vocab, dh_in_forward=True)
    lse, corr, mean_w = _ce_forward(h, w, labels2, block_n, block_v, vocab, interpret, dh_in_forward=True)
    total, count, mask = _sum_and_count(lse, corr, labels2, ignore_index)
    return (total, count), (h, w, labels2, lse, mask, mean_w)


def _fused_ce_bwd(ignore_index, block_n, block_v, vocab, interpret, residuals, cotangents):
    h, w, labels2, lse, mask, mean_w = residuals
    g_total, _g_count = cotangents  # count is a function of the int labels only
    gm = (g_total * mask).astype(jnp.float32)  # [N, 1]
    # the one-hot term is a row of the head per label (the embedding lookup's access pattern);
    # an ignored row's label may be any number: it reads row 0 and the mask zeroes it
    label_rows = w[jnp.where(mask > 0, labels2, 0)[:, 0]].astype(jnp.float32)
    dh = (gm * (mean_w - label_rows)).astype(h.dtype)
    dw = _ce_backward_dw(h, w, labels2, lse, gm, block_n, block_v, vocab, interpret)
    dlabels = np.zeros(labels2.shape, dtype=jax.dtypes.float0)
    return dh, dw, dlabels


_fused_ce.defvjp(_fused_ce_fwd, _fused_ce_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _fused_ce_rows(h, w, labels2, ignore_index, block_n, block_v, vocab, interpret):
    """The per-row form: every row's loss `[N, 1]` (0 where the label is ignored) where `_fused_ce` gives
    their sum, and in the backward a cotangent for every row where it takes one scalar. The same kernels
    on the same operands: the sum moved outside (a loss that weighs each row before it sums)."""
    _say_plan(h, w, block_n, block_v, vocab, dh_in_forward=False)
    lse, corr = _ce_forward(h, w, labels2, block_n, block_v, vocab, interpret, dh_in_forward=False)
    return _row_losses(lse, corr, labels2, ignore_index)[0]


def _fused_ce_rows_fwd(h, w, labels2, ignore_index, block_n, block_v, vocab, interpret):
    _say_plan(h, w, block_n, block_v, vocab, dh_in_forward=True)
    lse, corr, mean_w = _ce_forward(h, w, labels2, block_n, block_v, vocab, interpret, dh_in_forward=True)
    rows, mask = _row_losses(lse, corr, labels2, ignore_index)
    return rows, (h, w, labels2, lse, mask, mean_w)


def _fused_ce_rows_bwd(ignore_index, block_n, block_v, vocab, interpret, residuals, g_rows):
    # a row's cotangent stands where `g_total` stood: the rest is `_fused_ce_bwd`
    return _fused_ce_bwd(ignore_index, block_n, block_v, vocab, interpret, residuals, (g_rows, None))


_fused_ce_rows.defvjp(_fused_ce_rows_fwd, _fused_ce_rows_bwd)


# ------------------------------------------------------------- public entry


def _flat_padded(hidden, head_weight, labels, ignore_index, block_rows, block_vocab):
    """Rows flattened and, with the vocabulary, padded to the blocks that fit VMEM: `(h2, w, lab2, bn, bv, v, n)`."""
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
    return h2, w, lab2, bn, bv, v, n


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
    h2, w, lab2, bn, bv, v, _ = _flat_padded(hidden, head_weight, labels, ignore_index, block_rows, block_vocab)
    return _fused_ce(h2, w, lab2, ignore_index, bn, bv, v, interpret)


def fused_ce_rows(
    hidden,
    head_weight,
    labels,
    *,
    ignore_index: int = -100,
    block_rows: int = 256,
    block_vocab: int = 512,
    interpret: bool = False,
):
    """`fused_ce_sum_and_count` before the sum: the cross entropy of every row, float32 in the
    shape of `labels`, 0 where the label is `ignore_index`. Differentiable wrt hidden and
    head_weight under any cotangent of the rows (a loss that weighs each row by something of
    its own, as the loss over a looped model's exits does)."""
    h2, w, lab2, bn, bv, v, n = _flat_padded(hidden, head_weight, labels, ignore_index, block_rows, block_vocab)
    return _fused_ce_rows(h2, w, lab2, ignore_index, bn, bv, v, interpret)[:n, 0].reshape(labels.shape)
