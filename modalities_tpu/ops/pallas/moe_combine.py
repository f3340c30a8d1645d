"""Pallas TPU kernel for the expert layer's sum by token: slabs of rows, not rows.

`ops/expert_dispatch.plan_dispatch` sorts the (token, choice) pairs by expert and, within an
expert's group, by token. So for a block of consecutive tokens and one held expert, the rows of
the experts' output table that belong to those tokens are ONE contiguous run of the table, at
most as long as the block. The sum by token therefore needs no gather of rows (which the chip
runs at some 40 ns a row whether the row is real or the fill value):

    grid: one step a block of `block` tokens; the output block [block, d] is summed in a
    float32 scratch and written once in the table's dtype

    for e in held experts:
        m = count[block, e]                   rows of e that this block's tokens own (SMEM)
        when m > 0:                           an expert none of the block's tokens chose costs nothing
            a     = start[block, e] rounded down to ALIGN
            slab  = rows[a : a + block + ALIGN]             one DMA, HBM -> VMEM, the next expert's in flight meanwhile
            pick  = (pos[t, e] == a + c)                    0/1 [block, block + ALIGN]; pos = the row of t's pair on e, -1 if none
            acc  += weight[t, e] * dot(pick, slab)          the row exactly (one 1 a row), float32 weight, float32 add

A row of a table in bfloat16 is taken exactly by the product (a single 1 in a row of `pick`, float32
accumulation); a float32 table takes `Precision.HIGHEST` so the MXU's passes lose nothing of it.
Rows of the table must be finite: a slab's rows that no token picks are multiplied by 0. The table
carries `pad_rows(block)` rows past its last real row, so that the last slab stays in bounds.

`interpret=True` runs the same kernel under the Pallas CPU emulator, as the other kernels' tests do.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

ALIGN = 16  # rows a slab's start is rounded down to: a sublane tile of bfloat16 (two of float32)
CHUNKS = (256, 128)  # lanes of the table a product takes at a time, widest first; the first that divides the width


def pad_rows(block: int) -> int:
    """Rows (of zeros) a table holds past its last real row: a slab starts at most `ALIGN - 1` rows
    before its first real row and is `block + ALIGN` long."""
    return block + ALIGN


def vmem_bytes(block: int, width: int, held: int, itemsize: int) -> int:
    """What one grid step holds in VMEM, in bytes: two slabs, the float32 sum, the output block twice
    (the pipeline's two buffers), `pos` and `weight` twice with their lanes padded to 128."""
    return 2 * (block + ALIGN) * width * itemsize + block * width * 4 + 2 * block * width * itemsize + 2 * 2 * block * max(held, 128) * 4


def _kernel(start_ref, count_ref, *refs, held: int, block: int, chunk: int, weighted: bool):
    if weighted:
        pos_ref, weight_ref, rows_ref, out_ref, slab_ref, acc_ref, sem = refs
    else:
        (pos_ref, rows_ref, out_ref, slab_ref, acc_ref, sem), weight_ref = refs, None
    first = pl.program_id(0) * held  # of this block's entries in the two flat tables
    slab_rows, width = slab_ref.shape[1], slab_ref.shape[2]
    exact = lax.Precision.HIGHEST if slab_ref.dtype == jnp.float32 else None

    def aligned_start(e):
        return pl.multiple_of(start_ref[first + e] // ALIGN * ALIGN, ALIGN)

    def slab_copy(e, slot):
        return pltpu.make_async_copy(rows_ref.at[pl.ds(aligned_start(e), slab_rows)], slab_ref.at[slot], sem.at[slot])

    acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(count_ref[first] > 0)
    def _first_slab():
        slab_copy(0, 0).start()

    def one_expert(e, carry):
        slot = e % 2
        following = jnp.minimum(e + 1, held - 1)

        @pl.when((e + 1 < held) & (count_ref[first + following] > 0))
        def _next_slab():  # in flight while this expert's product runs
            slab_copy(following, 1 - slot).start()

        @pl.when(count_ref[first + e] > 0)
        def _add():
            slab_copy(e, slot).wait()
            column = lax.broadcasted_iota(jnp.int32, (block, held), 1) == e
            position = jnp.sum(jnp.where(column, pos_ref[...], 0), axis=1, keepdims=True) - aligned_start(e)  # [block, 1]
            pick = (position == lax.broadcasted_iota(jnp.int32, (block, slab_rows), 1)).astype(slab_ref.dtype)
            if weighted:
                weight = jnp.sum(jnp.where(column, weight_ref[...], 0.0), axis=1, keepdims=True)
            for c in range(0, width, chunk):
                picked = jnp.dot(pick, slab_ref[slot, :, c:c + chunk], preferred_element_type=jnp.float32, precision=exact)
                acc_ref[:, c:c + chunk] += picked * weight if weighted else picked

        return carry

    lax.fori_loop(0, held, one_expert, 0)
    out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def moe_combine(rows, pos, start, count, weight=None, *, block: int, interpret: bool = False):
    """For every token the sum over the held experts it chose of its row of `rows` (times its weight on
    that expert, where given), summed in float32 and returned in `rows`' dtype.

    rows [R + pad_rows(block), d]; pos int32 [T, held]: the row of the token's pair on that expert, -1
    where it has none; weight float32 [T, held] or None; start, count int32 [T / block, held]: the first
    row a block's tokens own of an expert's group and how many (consecutive: the group is sorted by token).
    `T` is a multiple of `block`, `block` of ALIGN."""
    tokens, held = pos.shape
    width = rows.shape[1]
    if tokens % block or block % ALIGN or start.shape != (tokens // block, held):
        raise ValueError(f"moe_combine: {tokens} tokens in blocks of {block} (a multiple of {ALIGN}), tables {start.shape}")
    chunk = next((c for c in CHUNKS if width % c == 0), width)
    by_block = pl.BlockSpec((block, held), lambda b, start, count: (b, 0))
    operands = (pos,) if weight is None else (pos, weight)
    return pl.pallas_call(
        functools.partial(_kernel, held=held, block=block, chunk=chunk, weighted=weight is not None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(tokens // block,),
            in_specs=[by_block] * len(operands) + [pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((block, width), lambda b, start, count: (b, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, block + ALIGN, width), rows.dtype),
                pltpu.VMEM((block, width), jnp.float32),
                pltpu.SemaphoreType.DMA((2,)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((tokens, width), rows.dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)),
        interpret=interpret,
        name="moe_combine",
    )(start.reshape(-1), count.reshape(-1), *operands, rows)
