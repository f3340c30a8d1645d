"""Pallas TPU kernels for the Mamba-1 recurrence: the state stays in VMEM.

    h_t[n, d] = exp(dt_t[d] * A[n, d]) * h_{t-1}[n, d] + dt_t[d] * x_t[d] * B_t[n]
    y_t[d]    = sum_n h_t[n, d] * C_t[n]

Both kernels walk a grid (batch, chunks of the sequence, blocks of `d_inner`), in that
order and one step after the other; the state `[d_state, block_d]` of every block
(`d_state` on the sublanes, `d_inner` on the lanes) lives in VMEM scratch from one chunk
to the next, and the time loop over a chunk's steps runs inside the kernel, eight steps
to a loop body. Everything is float32; `exp(dt A)` is taken as `exp2(dt (A log2 e))`,
which is how the hardware takes it, with the constant folded into `A` once.

- `selective_scan_fwd` reads `x`, `dt` `[B, S, D]` and `b`, `c` `[B, S, N]` as they
  are, writes `y`, the state at each chunk's start (what the backward starts from) and
  the last state.
- `selective_scan_bwd` walks the chunks from the last to the first with `dh` and the
  running `dA` in scratch. Per chunk it computes the chunk's states again from the kept
  start into VMEM (`chunk x d_state x block_d` floats, never HBM), then walks the steps
  backwards with the mathematics of `ops/selective_scan._chunk_backward`. `dB_t` and
  `dC_t` are sums over `d_inner`, which lies on the lanes: a step adds its lane tiles
  into one `[d_state, 128]` partial, every block of `d_inner` adds into the same
  partials, and the sum over the 128 lanes is taken once a chunk, for all its steps
  and blocks together.

`b_t` and `c_t` arrive with their 16 values on the lanes and are needed on the
sublanes, against a state whose lanes are `d_inner`. That is settled once a chunk:
`[chunk, N]` is spread to `[chunk, N, 128]` (each value over a whole lane tile) in
scratch by the chunk's first block of `d_inner`, for all of them, and a step reads its
`[N, 128]` slice and uses it for every lane tile. The sums over `d_state` (`y_t`, and in
the backward the two that make `ddt_t` and `dx_t`) run over sublanes: eight steps' sums
are taken together (`_sums_to_rows`) and leave as one whole tile of rows.

What binds both kernels on a v5e is the vector unit's four slots a cycle (the bundle
dumps and the kernel with parts taken out, PERF.md section 6), not memory and not `exp`.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES, SUBLANES = 128, 8
LOG2_E = 1.4426950408889634  # exp(v) = exp2(v * LOG2_E): folded into A once a grid step, not multiplied in at every step
UNROLL = 8  # steps of the time loop traced into one loop body: one sublane tile of the operands' rows
BLOCKS_D = (512, 256, 128)  # widths of a `d_inner` block, widest first; the first that divides the shard's `d_inner` and fits is taken
VMEM_BUDGET = 24 * 2**20  # bytes the backward kernel may plan for (a v5e core has 128 MiB; Mosaic's default scope is 16)


def backward_vmem_bytes(chunk: int, block_d: int, d_inner: int, d_state: int) -> int:
    """What `selective_scan_bwd` holds in VMEM for one grid step, in bytes: scratch, and
    every operand's block twice (the pipeline's two buffers)."""
    states = (chunk + 1) * d_state * block_d
    spread = 4 * chunk * d_state * LANES  # b, c spread over a lane tile; the partial dB, dC
    carried = 2 * d_state * d_inner  # dh and dA of every block
    rows = 2 * 5 * chunk * block_d  # x, dt, dy, dx, ddt
    narrow = 2 * 4 * chunk * LANES  # b, c, dB, dC: N values a row, padded to a lane tile
    small = 2 * 5 * d_state * block_d  # a, start, dh_last, dA out, dh0 out
    return 4 * (states + spread + carried + rows + narrow + small)


def plan_blocks(seq: int, d_inner: int, d_state: int, chunk: int) -> tuple[int, int]:
    """(chunk, block_d) for a shape, static: the chunk asked for, shortened to the
    sequence and rounded up to whole sublane tiles; the widest `d_inner` block that
    divides `d_inner` and whose backward fits `VMEM_BUDGET`. Shapes the layout cannot
    hold raise."""
    if d_inner % LANES or d_state % SUBLANES:
        raise ValueError(
            f"selective scan kernels: d_inner {d_inner} (as this shard holds it) must be a multiple of {LANES} "
            f"and d_state {d_state} a multiple of {SUBLANES}: the state is laid out [d_state, d_inner] on (sublanes, lanes)")
    chunk = -(-min(chunk, seq) // SUBLANES) * SUBLANES
    for block_d in BLOCKS_D:
        if d_inner % block_d == 0 and backward_vmem_bytes(chunk, block_d, d_inner, d_state) <= VMEM_BUDGET:
            return chunk, block_d
    raise ValueError(
        f"selective scan kernels: no d_inner block of {BLOCKS_D} divides d_inner {d_inner} and fits "
        f"{VMEM_BUDGET} bytes of VMEM at chunk {chunk}, d_state {d_state}")


def _spread(rows):
    """`[chunk, N]` (N on the lanes) -> `[chunk, N, 128]`: every value over a lane tile, N on the sublanes."""
    return jnp.broadcast_to(rows[:, :, None], (*rows.shape, LANES))


def _over_tiles(tile, block_d: int):
    """`[N, 128]` -> `[N, block_d]`: the same lane tile under every tile of the state."""
    return tile if block_d == LANES else jnp.concatenate([tile] * (block_d // LANES), axis=1)


def _add_slices(v, axis: int, width: int):
    """The slices of `width` along `axis` added up: over the lane tiles, `[N, block_d]` -> `[N, 128]` (lane l holds
    the sum over d = l mod 128); over the sublane tiles, `[N, block_d]` -> `[8, block_d]`, which leaves a sum over 8 sublanes to take."""
    slices = [lax.slice_in_dim(v, lo, lo + width, axis=axis) for lo in range(0, v.shape[axis], width)]
    return functools.reduce(jnp.add, slices)


def _row(ref, t):
    return ref[0, pl.ds(t, 1), :]


def _advance(h, dt_t, x_t, a2, b_t):
    """One step of the recurrence: the state after it. `a2` is `A log2 e`, `b_t` `[N, block_d]`."""
    return jnp.exp2(dt_t * a2) * h + (dt_t * x_t) * b_t


def _sums_to_rows(parts):
    """Eight `[8, block_d]` -> one `[8, block_d]` whose row i is the sum of `parts[i]` over its sublanes.

    A sum over sublanes taken one array at a time is three rotate-and-adds for one useful
    row in eight. Here two arrays share a rotate-and-add: each keeps the half of its
    sublanes the other does not need, so seven such steps (shifts 1, 2, 4) do all eight
    sums and leave them in order, a whole tile to store."""
    sublane = lax.broadcasted_iota(jnp.int32, parts[0].shape, 0)
    shift = 1
    while len(parts) > 1:
        low = (sublane & shift) == 0  # these sublanes go on with the first of a pair, the others with the second
        parts = [
            jnp.where(low, first, second) + pltpu.roll(jnp.where(low, second, first), shift, axis=0)
            for first, second in zip(parts[0::2], parts[1::2])
        ]
        shift *= 2
    return parts[0]


def _time_loop(steps: int, body, carry, finish=None, reverse: bool = False):
    """`carry, part = body(t, carry)` over the steps (from the last one with `reverse`),
    UNROLL of them to a loop body, and after each such group `finish(t0, parts)`, if
    given, with the group's first step and its parts in the order of the steps."""
    groups = steps // UNROLL

    def unrolled(group, carry):
        t0 = pl.multiple_of((groups - 1 - group if reverse else group) * UNROLL, UNROLL)
        parts = [None] * UNROLL
        for i in (reversed(range(UNROLL)) if reverse else range(UNROLL)):
            carry, parts[i] = body(t0 + i, carry)
        if finish is not None:
            finish(t0, parts)
        return carry

    return lax.fori_loop(0, groups, unrolled, carry)


# the chunk axis carries the state and the block axis shares the chunk's spread b, c (and dB, dC): both sequential
_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "arbitrary", "arbitrary"), vmem_limit_bytes=VMEM_BUDGET + 8 * 2**20)


# ---------------------------------------------------------------- forward


def _fwd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, h0_ref, y_ref, starts_ref, last_ref, h_ref, b_s, c_s, *, chunk):
    k, j = pl.program_id(1), pl.program_id(2)
    block_d = h_ref.shape[2]

    @pl.when(k == 0)
    def _first_chunk():
        h_ref[j] = h0_ref[0]

    @pl.when(j == 0)
    def _first_block():  # once a chunk, for every block of d_inner
        b_s[...] = _spread(b_ref[0])
        c_s[...] = _spread(c_ref[0])

    starts_ref[0, 0] = h_ref[j]
    a2 = a_ref[...] * LOG2_E

    def step(t, h):
        h = _advance(h, _row(dt_ref, t), _row(x_ref, t), a2, _over_tiles(b_s[t], block_d))
        return h, _add_slices(h * _over_tiles(c_s[t], block_d), 0, SUBLANES)

    def rows_of_y(t0, parts):
        y_ref[0, pl.ds(t0, UNROLL), :] = _sums_to_rows(parts)

    h = _time_loop(chunk, step, h_ref[j], finish=rows_of_y)
    h_ref[j] = h

    @pl.when(k == pl.num_programs(1) - 1)
    def _last_chunk():
        last_ref[0] = h


def scan_forward(x, dt, a_t, b, c, h0_t, *, chunk: int, block_d: int, interpret: bool):
    """x, dt `[B, S, D]`, b, c `[B, S, N]` with `chunk` dividing S; a_t `[N, D]`, h0_t
    `[B, N, D]`. Returns y `[B, S, D]`, the state at every chunk's start `[chunks, B, N,
    D]` and the last state `[B, N, D]`."""
    batch, seq, d_inner = x.shape
    d_state, chunks, blocks = a_t.shape[0], seq // chunk, d_inner // block_d
    rows = pl.BlockSpec((1, chunk, block_d), lambda i, k, j: (i, k, j))
    narrow = pl.BlockSpec((1, chunk, d_state), lambda i, k, j: (i, k, 0))
    state = pl.BlockSpec((1, d_state, block_d), lambda i, k, j: (i, 0, j))
    return pl.pallas_call(
        functools.partial(_fwd_kernel, chunk=chunk),
        grid=(batch, chunks, blocks),  # blocks innermost: b, c are spread once a chunk; the state of every block waits in scratch
        in_specs=[rows, rows, pl.BlockSpec((d_state, block_d), lambda i, k, j: (0, j)), narrow, narrow, state],
        out_specs=[rows, pl.BlockSpec((1, 1, d_state, block_d), lambda i, k, j: (k, i, 0, j)), state],
        out_shape=[
            jax.ShapeDtypeStruct((batch, seq, d_inner), jnp.float32),
            jax.ShapeDtypeStruct((chunks, batch, d_state, d_inner), jnp.float32),
            jax.ShapeDtypeStruct((batch, d_state, d_inner), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((blocks, d_state, block_d), jnp.float32),  # the state, from chunk to chunk
            pltpu.VMEM((chunk, d_state, LANES), jnp.float32),  # b spread
            pltpu.VMEM((chunk, d_state, LANES), jnp.float32),  # c spread
        ],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
        name="selective_scan_fwd",
    )(x, dt, a_t, b, c, h0_t)


# ---------------------------------------------------------------- backward


def _bwd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, start_ref, dy_ref, dh_last_ref,
                dx_ref, ddt_ref, db_ref, dc_ref, da_ref, dh0_ref,
                dh_ref, da_acc, states, b_s, c_s, db_s, dc_s, *, chunk):
    k, j = pl.program_id(1), pl.program_id(2)
    block_d = dh_ref.shape[2]

    @pl.when(k == 0)
    def _last_chunk_first():
        dh_ref[j] = dh_last_ref[0]
        da_acc[j] = jnp.zeros(da_acc.shape[1:], jnp.float32)

    @pl.when(j == 0)
    def _first_block():
        b_s[...] = _spread(b_ref[0])
        c_s[...] = _spread(c_ref[0])
        db_s[...] = jnp.zeros_like(db_s)  # dB, dC gather every block's share, lane by lane
        dc_s[...] = jnp.zeros_like(dc_s)

    a = a_ref[...]
    a2 = a * LOG2_E

    def again(t, h):  # the chunk's states, from its kept start: states[t] is the state before step t
        states[t] = h
        return _advance(h, _row(dt_ref, t), _row(x_ref, t), a2, _over_tiles(b_s[t], block_d)), None

    states[chunk] = _time_loop(chunk, again, start_ref[0, 0])

    def step(t, carry):
        dh, da = carry
        dt_t, dy_t = _row(dt_ref, t), _row(dy_ref, t)
        h_before = states[t]
        decay = jnp.exp2(dt_t * a2)
        g = dh + _over_tiles(c_s[t], block_d) * dy_t  # the whole cotangent of this step's state
        dc_s[t] += _add_slices(states[t + 1] * dy_t, 1, LANES)
        d_exponent = g * h_before * decay  # of dt_t * a
        db_s[t] += _add_slices(g * (dt_t * _row(x_ref, t)), 1, LANES)
        sums = _add_slices(d_exponent * a, 0, SUBLANES), _add_slices(g * _over_tiles(b_s[t], block_d), 0, SUBLANES)  # over n: what is left of ddt_t, and the cotangent of dt_t * x_t
        return (g * decay, da + d_exponent * dt_t), sums

    def rows_of_dx_ddt(t0, parts):
        of_exponent = _sums_to_rows([part[0] for part in parts])
        of_input = _sums_to_rows([part[1] for part in parts])
        tile = pl.ds(t0, UNROLL)
        ddt_ref[0, tile, :] = of_exponent + of_input * x_ref[0, tile, :]
        dx_ref[0, tile, :] = of_input * dt_ref[0, tile, :]

    dh, da = _time_loop(chunk, step, (dh_ref[j], da_acc[j]), finish=rows_of_dx_ddt, reverse=True)
    dh_ref[j] = dh
    da_acc[j] = da

    @pl.when(j == pl.num_programs(2) - 1)
    def _last_block():  # over the lanes, once a chunk: all its steps and every block of d_inner together
        db_ref[0] = jnp.sum(db_s[...], axis=2)
        dc_ref[0] = jnp.sum(dc_s[...], axis=2)

    @pl.when(k == pl.num_programs(1) - 1)
    def _first_chunk_last():
        da_ref[0] = da
        dh0_ref[0] = dh


def scan_backward(x, dt, a_t, b, c, starts, dy, dh_last_t, *, chunk: int, block_d: int, interpret: bool):
    """The operands of `scan_forward`, its kept chunk starts, and the cotangents of y
    `[B, S, D]` and of the last state `[B, N, D]`. Returns dx, ddt `[B, S, D]`; dB, dC
    `[B, S, N]`; dA of every sequence `[B, N, D]`; dh0 `[B, N, D]`."""
    batch, seq, d_inner = x.shape
    d_state, chunks, blocks = a_t.shape[0], seq // chunk, d_inner // block_d
    rows = pl.BlockSpec((1, chunk, block_d), lambda i, k, j: (i, chunks - 1 - k, j))
    narrow = pl.BlockSpec((1, chunk, d_state), lambda i, k, j: (i, chunks - 1 - k, 0))
    state = pl.BlockSpec((1, d_state, block_d), lambda i, k, j: (i, 0, j))
    of_blocks = pltpu.VMEM((blocks, d_state, block_d), jnp.float32)
    spread = pltpu.VMEM((chunk, d_state, LANES), jnp.float32)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, chunk=chunk),
        grid=(batch, chunks, blocks),
        in_specs=[
            rows, rows, pl.BlockSpec((d_state, block_d), lambda i, k, j: (0, j)), narrow, narrow,
            pl.BlockSpec((1, 1, d_state, block_d), lambda i, k, j: (chunks - 1 - k, i, 0, j)), rows, state,
        ],
        out_specs=[rows, rows, narrow, narrow, state, state],
        out_shape=[
            jax.ShapeDtypeStruct((batch, seq, d_inner), jnp.float32),
            jax.ShapeDtypeStruct((batch, seq, d_inner), jnp.float32),
            jax.ShapeDtypeStruct((batch, seq, d_state), jnp.float32),
            jax.ShapeDtypeStruct((batch, seq, d_state), jnp.float32),
            jax.ShapeDtypeStruct((batch, d_state, d_inner), jnp.float32),
            jax.ShapeDtypeStruct((batch, d_state, d_inner), jnp.float32),
        ],
        scratch_shapes=[
            of_blocks, of_blocks,  # dh, from chunk to chunk; dA, over the whole sequence
            pltpu.VMEM((chunk + 1, d_state, block_d), jnp.float32),  # the chunk's states, and the one after it
            spread, spread, spread, spread,  # b, c; the per-lane partial dB, dC
        ],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
        name="selective_scan_bwd",
    )(x, dt, a_t, b, c, starts, dy, dh_last_t)


# ---------------------------------------------------------------- custom_vjp


def _padded(v, seq_padded: int):
    pad = seq_padded - v.shape[1]
    return jnp.pad(v, ((0, 0), (0, pad), (0, 0))) if pad else v  # steps of dt = 0 leave the state as it is


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def _scan(x, dt, a, b, c, h0, chunk, block_d, interpret):
    return _scan_fwd(x, dt, a, b, c, h0, chunk, block_d, interpret)[0]


def _scan_fwd(x, dt, a, b, c, h0, chunk, block_d, interpret):
    seq = x.shape[1]
    seq_padded = -(-seq // chunk) * chunk
    x, dt, b, c = (_padded(v, seq_padded) for v in (x, dt, b, c))
    a_t = a.T
    y, starts, h_last = scan_forward(x, dt, a_t, b, c, jnp.swapaxes(h0, 1, 2), chunk=chunk, block_d=block_d, interpret=interpret)
    return (y[:, :seq], jnp.swapaxes(h_last, 1, 2)), ((x, dt, b, c), a_t, starts)


def _scan_bwd(chunk, block_d, interpret, kept, cotangents):
    (x, dt, b, c), a_t, starts = kept
    dy, dh_last = cotangents
    seq = dy.shape[1]
    dx, ddt, db, dc, da_t, dh0_t = scan_backward(
        x, dt, a_t, b, c, starts, _padded(dy, x.shape[1]), jnp.swapaxes(dh_last, 1, 2),
        chunk=chunk, block_d=block_d, interpret=interpret)
    return dx[:, :seq], ddt[:, :seq], da_t.sum(axis=0).T, db[:, :seq], dc[:, :seq], jnp.swapaxes(dh0_t, 1, 2)


_scan.defvjp(_scan_fwd, _scan_bwd)


def pallas_selective_scan(x, dt, a, b, c, h0, *, chunk: int, interpret: bool = False):
    """x, dt `[B, S, D]`; a `[D, N]`; b, c `[B, S, N]`; h0 `[B, D, N]`, all float32.
    Returns y `[B, S, D]` and the state after the last step `[B, D, N]`. `chunk` is the
    number of steps between the states kept for the backward; what the kernels run with
    is `plan_blocks`' answer for the shape."""
    chunk, block_d = plan_blocks(x.shape[1], x.shape[2], a.shape[1], chunk)
    return _scan(x, dt, a, b, c, h0, chunk, block_d, interpret)
