"""Pallas TPU kernels for the gated delta rule's walk over a group's chunks: the state stays in VMEM.

What `ops/gated_delta_rule._group` prepares for all of a group's chunks at once (`intra`) is walked chunk by chunk
here, a value head's `[d_k, d_v]` float32 state resident from the group's first chunk to its last. A chunk, a head:

    Vn = U - W S        O = Q S + A Vn        S' = d S + K^T Vn

with `U [C, d_v]`, `W`, `Q`, `K [C, d_k]`, `A [C, C]` (`within`: what a chunk's own keys give its queries) and `d` the
chunk's whole decay. The operands are read where `_group` left them, `[N, B, Hk, r, C, d]`, through the block index
maps; a grid step takes `heads` key heads with the `r` value heads of each (`plan_heads`), the chunks are the
sequential axis of the grid. `d` arrives as a lane row `[1, d_v]` a head (a scalar a head and chunk, spread over
`d_v` by the caller), and its cotangent leaves as one (summed over `d_k` here, over the lanes by the caller).

- `gated_delta_state_fwd` writes `O` a chunk and the state that goes on.
- `gated_delta_state_bwd` sweeps the chunks twice on one grid axis of `2 N` steps. Forward first, with `Vn` and `S'`
  alone (two of the four products; `Q`, `A` and `dO` are not read), the state that came into each chunk kept in VMEM
  scratch (`N` states a head: 16 MiB at 32 chunks and 8 heads of 128 x 128, never in HBM); then from the last chunk
  to the first with `dS` resident:

      dVn = A^T dO + K dS'     dA = dO Vn^T     dQ = dO S^T     dK = Vn dS'^T     dd = <dS', S>
      dU = dVn                 dW = -dVn S^T    dS = d dS' + Q^T dO - W^T dVn

  A block that only the backward sweep touches has the last chunk's index all through the forward sweep: a block
  whose index stands still is neither fetched again nor written back, so it is read once and first written back
  after the backward sweep's first step filled it.

Precision is `ops/gated_delta_rule._dot`'s: a product's operands are in the inputs' dtype (`S`, `Vn`, `dS'`, `dVn` and
`dO` rounded to it where they are an operand, never where they are carried or summed), the sums float32; with float32
inputs every product is float32 at `highest`.

Both kernels are written phase by phase over the heads of a grid step, not head by head: the heads are independent,
and one head's products fill the wait for another's (the forward's grid step of 8 heads is 2,613 scheduled bundles
head by head and 1,276 so; with `W S` and `Q S` one product over the same weights; the bundle dumps of a compile for
a described v5e, PR 45). So written they wait for HBM, not for the MXU.

`walk` joins the two by a `custom_vjp` that keeps the operands alone. A forward whose outputs nothing reads (a
rematerialized group's, which is traced for its backward alone) is dead code.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
HEADS = 8  # value heads a grid step at most, unless one key head has more: a head's step is 7 MFLOP, under a grid step's fixed cost
VMEM_BUDGET = 40 * 2**20  # bytes the backward kernel may plan for (a v5e core has 128 MiB; Mosaic's default scope is 16)

_NT = (((1,), (1,)), ((), ()))  # a b^T
_TN = (((0,), (0,)), ((), ()))  # a^T b


def backward_vmem_bytes(heads: int, chunks: int, chunk: int, key_dim: int, value_dim: int, itemsize: int) -> int:
    """What `gated_delta_state_bwd` holds in VMEM for one grid step of `heads` value heads, in bytes: every operand's
    block twice (the pipeline's two buffers), `within` and its cotangent padded to a lane tile, the carried state
    or `dS`, and the state that came into each of the group's `chunks` chunks."""
    wide = max(chunk, LANES)
    rows = itemsize * chunk * (3 * key_dim + 2 * value_dim + wide)  # w, q, k; u, dO; within
    rows += itemsize * chunk * (3 * key_dim + value_dim + wide)  # dW, dQ, dK; dU; d within
    decay = 2 * 4 * 8 * value_dim  # d and dd: a row, padded to a sublane tile
    whole = 4 * 4 * key_dim * value_dim  # the state that came into the group and its cotangent, the cotangent of the one that goes on, its place as an output
    return heads * (2 * (rows + decay + whole) + (1 + chunks) * 4 * key_dim * value_dim)


def plan_heads(key_heads: int, per_key_head: int, chunks: int, chunk: int, key_dim: int, value_dim: int, dtype) -> int:
    """Key heads a grid step takes (each with its `per_key_head` value heads), 0 where the kernels do not serve the
    shape: `d_k` and `d_v` fill whole lane tiles, the chunk whole sublane tiles of the dtype, and the backward's
    blocks with the states of a group of `chunks` chunks fit `VMEM_BUDGET`. The most heads under `HEADS` that divide
    what a shard holds."""
    itemsize = jnp.dtype(dtype).itemsize
    if itemsize not in (2, 4) or key_dim % LANES or value_dim % LANES or chunk % (32 // itemsize):
        return 0
    for heads in range(max(HEADS // per_key_head, 1), 0, -1):
        if key_heads % heads == 0 and backward_vmem_bytes(heads * per_key_head, chunks, chunk, key_dim, value_dim, itemsize) <= VMEM_BUDGET:
            return heads
    return 0


def _dot(a, b, dims=(((1,), (0,)), ((), ()))):
    """A product of operands already in the inputs' dtype, summed in float32; float32 operands multiply exactly."""
    precision = jax.lax.Precision.HIGHEST if a.dtype == jnp.float32 else None
    return jax.lax.dot_general(a, b, dims, precision=precision, preferred_element_type=jnp.float32)


_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"), vmem_limit_bytes=VMEM_BUDGET + 8 * 2**20)


def _per_chunk(heads: int, r: int, chunk_of):
    """`spec(*last)`: a grid step's (batch, block of key heads, step `n`) block of an array `[N, B, Hk, r, *last]`, `heads`
    key heads with their `r` value heads of chunk `chunk_of(n)`."""
    return lambda *last: pl.BlockSpec((1, 1, heads, r, *last), lambda b, h, n: (chunk_of(n), b, h, 0, 0, 0))


def _whole(heads: int, r: int, key_dim: int, value_dim: int):
    """A state a head, `[B, Hk, r, d_k, d_v]`: the same block every chunk."""
    return pl.BlockSpec((1, heads, r, key_dim, value_dim), lambda b, h, n: (b, h, 0, 0, 0))


# ---------------------------------------------------------------- forward


def _fwd_kernel(state_ref, u_ref, w_ref, a_ref, q_ref, k_ref, d_ref, out_ref, last_ref, s_ref):
    n = pl.program_id(2)  # s_ref [heads, r, d_k, d_v] float32: the state, from chunk to chunk
    dtype = u_ref.dtype

    @pl.when(n == 0)
    def _first_chunk():
        s_ref[...] = state_ref[0]

    # every head's step phase by phase (module docstring)
    chunk = u_ref.shape[-2]
    heads = [(i, j) for i in range(s_ref.shape[0]) for j in range(s_ref.shape[1])]
    states = [s_ref[i, j] for i, j in heads]
    operands = [state.astype(dtype) for state in states]
    # W S and Q S as one product: the state is the MXU's weights once
    both = [_dot(jnp.concatenate([w_ref[0, 0, i, j], q_ref[0, 0, i, j]], axis=0), operand) for (i, j), operand in zip(heads, operands)]
    v_new = [(u_ref[0, 0, i, j].astype(jnp.float32) - ws_qs[:chunk]).astype(dtype) for (i, j), ws_qs in zip(heads, both)]
    for (i, j), ws_qs, v in zip(heads, both, v_new):
        out_ref[0, 0, i, j] = (ws_qs[chunk:] + _dot(a_ref[0, 0, i, j], v)).astype(dtype)
    for (i, j), state, v in zip(heads, states, v_new):
        s_ref[i, j] = d_ref[0, 0, i, j] * state + _dot(k_ref[0, 0, i, j], v, _TN)

    @pl.when(n == pl.num_programs(2) - 1)
    def _last_chunk():
        last_ref[0] = s_ref[...]


def walk_forward(state, u, w, within, q_in, k_out, decay_rows, *, heads: int, interpret: bool):
    """state `[B, Hk, r, d_k, d_v]` float32; u `[N, B, Hk, r, C, d_v]`, w, q_in, k_out `[N, B, Hk, r, C, d_k]`, within
    `[N, B, Hk, r, C, C]` in one dtype; decay_rows `[N, B, Hk, r, 1, d_v]` float32. Returns the state after the last
    chunk and o `[N, B, Hk, r, C, d_v]` in u's dtype."""
    chunks, batch, key_heads, r, chunk, value_dim = u.shape
    key_dim = w.shape[-1]
    per_chunk, whole = _per_chunk(heads, r, lambda n: n), _whole(heads, r, key_dim, value_dim)
    out, last = pl.pallas_call(
        _fwd_kernel,
        grid=(batch, key_heads // heads, chunks),
        in_specs=[whole, per_chunk(chunk, value_dim), per_chunk(chunk, key_dim), per_chunk(chunk, chunk),
                  per_chunk(chunk, key_dim), per_chunk(chunk, key_dim), per_chunk(1, value_dim)],
        out_specs=[per_chunk(chunk, value_dim), whole],
        out_shape=[jax.ShapeDtypeStruct(u.shape, u.dtype), jax.ShapeDtypeStruct(state.shape, jnp.float32)],
        scratch_shapes=[pltpu.VMEM((heads, r, key_dim, value_dim), jnp.float32)],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
        name="gated_delta_state_fwd",
    )(state, u, w, within, q_in, k_out, decay_rows)
    return last, out


# ---------------------------------------------------------------- backward


def _bwd_kernel(state_ref, u_ref, w_ref, a_ref, q_ref, k_ref, d_ref, do_ref, dlast_ref,
                du_ref, dw_ref, da_ref, dq_ref, dk_ref, dd_ref, dstate_ref, s_ref, kept_ref):
    """Two sweeps over the group's chunks on one grid axis of `2 N` steps: forward, the state that came into each chunk
    kept in VMEM (`kept_ref [N, heads, r, d_k, d_v]`; `s_ref` carries the state), then backward (`s_ref` carries `dS`)."""
    n, chunks = pl.program_id(2), pl.num_programs(2) // 2
    dtype, chunk = u_ref.dtype, u_ref.shape[-2]
    heads = [(i, j) for i in range(s_ref.shape[0]) for j in range(s_ref.shape[1])]  # phase by phase, as the forward kernel

    @pl.when(n == 0)
    def _first_chunk():
        s_ref[...] = state_ref[0]

    @pl.when(n < chunks)
    def _forward_sweep():
        states = [s_ref[i, j] for i, j in heads]
        for (i, j), state in zip(heads, states):
            kept_ref[n, i, j] = state
        v_new = [(u_ref[0, 0, i, j].astype(jnp.float32) - _dot(w_ref[0, 0, i, j], state.astype(dtype))).astype(dtype) for (i, j), state in zip(heads, states)]
        for (i, j), state, v in zip(heads, states, v_new):
            s_ref[i, j] = d_ref[0, 0, i, j] * state + _dot(k_ref[0, 0, i, j], v, _TN)

    @pl.when(n == chunks)
    def _last_chunk_first():
        s_ref[...] = dlast_ref[0]

    @pl.when(n >= chunks)
    def _backward_sweep():
        states = [kept_ref[2 * chunks - 1 - n, i, j] for i, j in heads]  # the state that came into the chunk
        d_next = [s_ref[i, j] for i, j in heads]  # the cotangent of the one that goes on
        for (i, j), state, d_state in zip(heads, states, d_next):
            dd_ref[0, 0, i, j] = jnp.sum(d_state * state, axis=0, keepdims=True)
        operands, d_operands = [state.astype(dtype) for state in states], [d_state.astype(dtype) for d_state in d_next]
        v_new = [(u_ref[0, 0, i, j].astype(jnp.float32) - _dot(w_ref[0, 0, i, j], operand)).astype(dtype) for (i, j), operand in zip(heads, operands)]
        d_v_new = [(_dot(a_ref[0, 0, i, j], do_ref[0, 0, i, j], _TN) + _dot(k_ref[0, 0, i, j], d_operand)).astype(dtype)
                   for (i, j), d_operand in zip(heads, d_operands)]
        for (i, j), operand, d_operand, v, d_v in zip(heads, operands, d_operands, v_new, d_v_new):
            d_out = do_ref[0, 0, i, j]
            du_ref[0, 0, i, j] = d_v
            da_ref[0, 0, i, j] = _dot(d_out, v, _NT).astype(dtype)
            dk_ref[0, 0, i, j] = _dot(v, d_operand, _NT).astype(dtype)
            both = _dot(jnp.concatenate([d_out, d_v], axis=0), operand, _NT)  # dO S^T and dVn S^T as one product
            dq_ref[0, 0, i, j] = both[:chunk].astype(dtype)
            dw_ref[0, 0, i, j] = (-both[chunk:]).astype(dtype)
        for (i, j), d_state, d_v in zip(heads, d_next, d_v_new):
            # Q^T dO - W^T dVn as one product, its contraction as deep as the MXU
            rows, d_rows = jnp.concatenate([q_ref[0, 0, i, j], w_ref[0, 0, i, j]], axis=0), jnp.concatenate([do_ref[0, 0, i, j], -d_v], axis=0)
            s_ref[i, j] = d_ref[0, 0, i, j] * d_state + _dot(rows, d_rows, _TN)

    @pl.when(n == 2 * chunks - 1)
    def _first_chunk_last():
        dstate_ref[0] = s_ref[...]


def walk_backward(state, u, w, within, q_in, k_out, decay_rows, d_out, d_last, *, heads: int, interpret: bool):
    """The operands of `walk_forward` and the cotangents of o and of the state after the last chunk. Returns the
    cotangents of (state, u, w, within, q_in, k_out, decay_rows)."""
    chunks, batch, key_heads, r, chunk, value_dim = u.shape
    key_dim = w.shape[-1]
    # the forward sweep walks chunk n and reads u, w, k_out and the decay alone: what only the backward sweep touches waits at the
    # last chunk, where that sweep starts (a block whose index stands still is neither fetched again nor written back)
    both_sweeps = _per_chunk(heads, r, lambda n: jnp.where(n < chunks, n, 2 * chunks - 1 - n))
    second_sweep = _per_chunk(heads, r, lambda n: jnp.where(n < chunks, chunks - 1, 2 * chunks - 1 - n))
    whole = _whole(heads, r, key_dim, value_dim)
    wide, narrow, square, row = second_sweep(chunk, value_dim), second_sweep(chunk, key_dim), second_sweep(chunk, chunk), second_sweep(1, value_dim)
    du, dw, da, dq, dk, dd, d_state = pl.pallas_call(
        _bwd_kernel,
        grid=(batch, key_heads // heads, 2 * chunks),
        in_specs=[whole, both_sweeps(chunk, value_dim), both_sweeps(chunk, key_dim), square, narrow, both_sweeps(chunk, key_dim), both_sweeps(1, value_dim), wide, whole],
        out_specs=[wide, narrow, square, narrow, narrow, row, whole],
        out_shape=[jax.ShapeDtypeStruct(a.shape, a.dtype) for a in (u, w, within, q_in, k_out, decay_rows, d_last)],
        scratch_shapes=[pltpu.VMEM((heads, r, key_dim, value_dim), jnp.float32), pltpu.VMEM((chunks, heads, r, key_dim, value_dim), jnp.float32)],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
        name="gated_delta_state_bwd",
    )(state, u, w, within, q_in, k_out, decay_rows, d_out, d_last)
    return d_state, du, dw, da, dq, dk, dd


# ---------------------------------------------------------------- custom_vjp


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8))
def _walk(state, u, w, within, q_in, k_out, decay_rows, heads, interpret):
    return walk_forward(state, u, w, within, q_in, k_out, decay_rows, heads=heads, interpret=interpret)


def _walk_fwd(state, u, w, within, q_in, k_out, decay_rows, heads, interpret):
    operands = (state, u, w, within, q_in, k_out, decay_rows)
    return walk_forward(*operands, heads=heads, interpret=interpret), operands


def _walk_bwd(heads, interpret, operands, cotangents):
    d_last, d_out = cotangents
    return walk_backward(*operands, d_out, d_last, heads=heads, interpret=interpret)


_walk.defvjp(_walk_fwd, _walk_bwd)


def walk(state, u, w, within, q_in, k_out, carry_decay, *, heads: int, interpret: bool = False):
    """The walk over a group's chunks: the operands of `walk_forward` with carry_decay `[N, B, Hk, r]` float32, the
    chunk's whole decay a head. Returns the state after the last chunk and o `[N, B, Hk, r, C, d_v]`. `heads` is
    `plan_heads`' answer for the shapes."""
    decay_rows = jnp.broadcast_to(carry_decay[..., None, None], (*carry_decay.shape, 1, u.shape[-1]))  # its transpose sums dd over the lanes
    return _walk(state, u, w, within, q_in, k_out, decay_rows, heads, interpret)
