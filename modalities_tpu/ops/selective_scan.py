"""The selective scan of a Mamba-1 layer, and the causal depthwise convolution before it.

The recurrence, per sequence, channel `d` and state index `n`, in float32:

    h_t[d, n] = exp(dt_t[d] * A[d, n]) * h_{t-1}[d, n] + dt_t[d] * x_t[d] * B_t[n]
    y_t[d]    = sum_n h_t[d, n] * C_t[n]

`selective_scan` walks the sequence in chunks of `chunk` steps and carries the state
`[batch, d_inner, d_state]` from chunk to chunk. Its backward pass keeps the state at
each chunk's start and nothing else of the states: it walks the chunks from the last to
the first, computes one chunk's states again from its start and walks that chunk's steps
backwards, so the states of one chunk are all it ever holds — `[S, d_inner, d_state]`
for a whole sequence (1.34 GB a layer in float32 at S 4096, d_inner 5120, d_state 16) is
never built. The backward step is written out by hand (`_chunk_backward`): what
autodiff makes of the loop keeps three tensors a step and ran 18 device operations a
step where this runs a few. A sequence that `chunk` does not divide is padded with
steps of `dt = 0`, which leave the state as it is.

Two forms of one algorithm, in the pattern of ops/attention.py. On a TPU the Pallas
kernels `selective_scan_fwd` / `selective_scan_bwd` (ops/pallas/selective_scan.py) run:
the time loop inside the kernel, the state and, in the backward, one chunk's states in
VMEM. Whatever they raise is raised: there is no second tier behind them on the chip,
and a shape their layout cannot hold (`d_inner` of a shard no multiple of 128, `d_state`
no multiple of 8) is refused with the shape in the message. Off a TPU the plain form
below runs: `lax.scan` over time inside a chunk, a few steps unrolled, every step a few
XLA fusions (on the chip 18.1 ms of device time a layer at the hybrid cell's shape,
forward and backward, where the kernels take 2.5: PERF.md section 6). It is what CPU runs and
what the tests hold the kernels against (`interpret=True` runs the kernels' own code
there). Under a mesh the kernels run per shard (parallel/sharding.per_shard): the
recurrence is independent per sequence and per channel, so `batch` and `d_inner`
(logical axis `mlp`) may both be split.

Inside, the state is laid out `[batch, d_state, d_inner]`: `d_inner` is a multiple of
128 and fills the lanes, `d_state` (16) the sublanes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from modalities_tpu.ops import tiers

# steps between the states the backward pass keeps. On the chip at the hybrid cell's shape (1 x 4096 x 5120 x 16, PR 26, forward and
# backward of one layer): 64 steps 31.0 ms, 128 steps 26.6, 256 steps 29.5; the step's memory does not move with it (15.17-15.28 GiB)
CHUNK = 128
UNROLL = 16  # steps of the time loop traced into one loop body (chip, PR 26: 8 and 16 run the forward alike, 16 the backward 15% faster)


def scan_plan(batch: int, seq: int, d_inner: int, d_state: int, chunk: int, kernel: bool = False) -> dict:
    """What `selective_scan` does for a shape: the facts of the sink event `ssm_scan_plan`.
    With `kernel`, what the Pallas kernels run with; `batch` and `d_inner` are then what one shard holds."""
    chunk, block_d = min(chunk, seq), 0
    if kernel:
        from modalities_tpu.ops.pallas.selective_scan import plan_blocks

        chunk, block_d = plan_blocks(seq, d_inner, d_state, chunk)
    chunks = -(-seq // chunk)
    state_bytes = 4 * batch * d_inner * d_state
    return {
        "batch": batch, "seq": seq, "chunk": chunk, "chunks": chunks, "d_inner": d_inner, "d_state": d_state,
        "state_bytes_carried": state_bytes,
        "boundary_state_bytes": state_bytes * chunks,  # kept from the forward pass for the backward
        "backward_bytes_per_chunk": chunk * state_bytes,  # the backward of one chunk holds the state before each of its steps
        "kernel": kernel,  # the Pallas kernels were traced; the three below are 0 for the plain form
        "block_d": block_d,
        "grid_steps": batch * (d_inner // block_d) * chunks if kernel else 0,  # of each of the two kernels
        "vmem_state_bytes": 4 * chunk * d_state * block_d,  # the backward's chunk of states: in VMEM, never in HBM
    }


def _say_plan(x, a, chunk: int, kernel: bool) -> None:
    """Runs while tracing: the operator sees once per shape how the scan walks it; nothing per step."""
    from modalities_tpu.telemetry import get_active_telemetry

    get_active_telemetry().emit_event_once("ssm_scan_plan", scan_plan(*x.shape, a.shape[1], chunk, kernel=kernel))


def _chunk_loop(h, dt, x, b, c, a_t, keep_states: bool = False):
    """One chunk, step by step. h `[B, N, D]`; dt, x `[L, B, D]`; b, c `[L, B, N]`; a_t
    `[N, D]` (A transposed). Returns the state after the chunk and y `[L, B, D]`, or,
    with `keep_states`, the state before every step `[L, B, N, D]` in y's place."""

    def step(h, inputs):
        dt_t, x_t, b_t, c_t = inputs
        decay = jnp.exp(dt_t[:, None, :] * a_t)
        h_next = decay * h + (dt_t * x_t)[:, None, :] * b_t[:, :, None]
        return h_next, h if keep_states else jnp.sum(h_next * c_t[:, :, None], axis=1)

    return lax.scan(step, h, (dt, x, b, c), unroll=min(UNROLL, dt.shape[0]))


def _chunk_backward(h_start, dt, x, b, c, a_t, dh, dy):
    """The cotangents of one chunk's inputs from those of its outputs: `dh` `[B, N, D]` of
    the state after the chunk, `dy` `[L, B, D]`. Computes the chunk's states again, then
    walks its steps backwards. Returns the cotangents of (h_start, dt, x, b, c, a_t)."""
    _, before = _chunk_loop(h_start, dt, x, b, c, a_t, keep_states=True)

    def step(carry, inputs):
        dh, da_t = carry
        dt_t, x_t, b_t, c_t, dy_t, h_before = inputs
        decay = jnp.exp(dt_t[:, None, :] * a_t)
        h_after = decay * h_before + (dt_t * x_t)[:, None, :] * b_t[:, :, None]
        g = dh + c_t[:, :, None] * dy_t[:, None, :]  # the whole cotangent of this step's state
        dc_t = jnp.sum(h_after * dy_t[:, None, :], axis=2)
        d_exponent = g * h_before * decay  # of dt_t * a_t
        g_b = jnp.sum(g * b_t[:, :, None], axis=1)  # of dt_t * x_t
        ddt_t = jnp.sum(d_exponent * a_t, axis=1) + g_b * x_t
        db_t = jnp.sum(g * (dt_t * x_t)[:, None, :], axis=2)
        da_t = da_t + jnp.sum(d_exponent * dt_t[:, None, :], axis=0)
        return (g * decay, da_t), (ddt_t, g_b * dt_t, db_t, dc_t)

    (dh_start, da_t), (ddt, dx, db, dc) = lax.scan(
        step, (dh, jnp.zeros_like(a_t)), (dt, x, b, c, dy, before), reverse=True, unroll=min(UNROLL, dt.shape[0]))
    return dh_start, ddt, dx, db, dc, da_t


def _to_chunks(v, chunk: int):
    """`[B, S, F]` -> `[chunks, chunk, B, F]`, time-major, padded with zeros at the end."""
    batch, seq, feat = v.shape
    pad = -seq % chunk
    if pad:
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0)))
    return jnp.moveaxis(v, 1, 0).reshape((seq + pad) // chunk, chunk, batch, feat)


def _from_chunks(v, seq: int):
    chunks, chunk, batch, feat = v.shape
    return jnp.moveaxis(v.reshape(chunks * chunk, batch, feat), 0, 1)[:, :seq]


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _scan(x, dt, a, b, c, h0, chunk):
    return _scan_fwd(x, dt, a, b, c, h0, chunk)[0]


def _scan_fwd(x, dt, a, b, c, h0, chunk):
    seq = x.shape[1]
    a_t = a.T
    inputs = tuple(_to_chunks(v, chunk) for v in (dt, x, b, c))

    def over_chunks(h, chunk_inputs):
        h_next, y = _chunk_loop(h, *chunk_inputs, a_t)
        return h_next, (y, h)

    h_last, (y, starts) = lax.scan(over_chunks, jnp.swapaxes(h0, 1, 2), inputs)
    return (_from_chunks(y, seq), jnp.swapaxes(h_last, 1, 2)), (inputs, a_t, starts)


def _scan_bwd(chunk, kept, cotangents):
    (dt, x, b, c), a_t, starts = kept
    dy, dh_last = cotangents
    seq = dy.shape[1]

    def over_chunks(carry, per_chunk):
        dh, da_t = carry
        h_start, dy_c, *chunk_inputs = per_chunk
        dh_start, ddt, dx, db, dc, da_c = _chunk_backward(h_start, *chunk_inputs, a_t, dh, dy_c)  # the chunk's states live here, and only here
        return (dh_start, da_t + da_c), (ddt, dx, db, dc)

    (dh0, da_t), (ddt, dx, db, dc) = lax.scan(
        over_chunks, (jnp.swapaxes(dh_last, 1, 2), jnp.zeros_like(a_t)), (starts, _to_chunks(dy, chunk), dt, x, b, c),
        reverse=True)
    ddt, dx, db, dc = (_from_chunks(v, seq) for v in (ddt, dx, db, dc))
    return dx, ddt, da_t.T, db, dc, jnp.swapaxes(dh0, 1, 2)


_scan.defvjp(_scan_fwd, _scan_bwd)


_ROWS, _NARROW, _STATE = ("batch", None, "mlp"), ("batch", None, None), ("batch", "mlp", None)


def uses_kernels(interpret: bool = False) -> bool:
    """Whether `selective_scan` runs the Pallas kernels here: where kernels run (`ops/tiers.py`: on a TPU), elsewhere when asked to interpret them."""
    return interpret or tiers.kernels_run()


def selective_scan(x, dt, a, b, c, *, chunk: int = CHUNK, h0=None, interpret: bool = False):
    """x, dt `[B, S, D]`; a `[D, N]` (negative); b, c `[B, S, N]`; h0 `[B, D, N]` or None
    for zeros. Returns y `[B, S, D]` and the state after the last step `[B, D, N]`,
    both float32. `chunk` is the number of steps between kept states (tests pass others
    than CHUNK). `interpret` runs the kernels' code off a TPU (tests)."""
    f32 = lambda v: v.astype(jnp.float32)  # noqa: E731
    batch, seq, d_inner = x.shape
    if h0 is None:
        h0 = jnp.zeros((batch, d_inner, a.shape[1]), jnp.float32)
    operands = (f32(x), f32(dt), f32(a), f32(b), f32(c), f32(h0))
    if not uses_kernels(interpret):
        _say_plan(x, a, chunk, kernel=False)
        return _scan(*operands, int(min(chunk, seq)))
    from modalities_tpu.ops.pallas.selective_scan import pallas_selective_scan
    from modalities_tpu.parallel.sharding import per_shard

    def kernels(_axes, *local):
        _say_plan(local[0], local[2], chunk, kernel=True)
        return pallas_selective_scan(*local, chunk=chunk, interpret=tiers.interpret(interpret))

    # b, c and a are whole on the axes they do not name, and the shard_map's transpose adds
    # their cotangents up over those: dB, dC over the axes d_inner was split on, dA over batch's
    return per_shard(kernels, (_ROWS, _ROWS, ("mlp", None), _NARROW, _NARROW, _STATE), (_ROWS, _STATE))(*operands)


def causal_depthwise_conv(x, kernel, bias=None):
    """x `[B, S, D]`, kernel `[K, D]`, bias `[D]`: `y_t = bias + sum_k kernel[k] * x_{t-(K-1)+k}`
    with zeros before the sequence starts (the last tap weighs the current step)."""
    taps, seq = kernel.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    y = sum(padded[:, k:k + seq] * kernel[k].astype(x.dtype) for k in range(taps))
    return y if bias is None else y + bias.astype(x.dtype)
