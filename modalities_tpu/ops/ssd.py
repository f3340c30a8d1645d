"""The recurrence of a Mamba-2 layer (state-space duality, Dao and Gu, arXiv 2405.21060) over a row, in plain `jax.numpy`.

A head `j` of `P` channels keeps a state `h [P, N]`, decayed by ONE scalar a token and fed by the outer product of the
token's input and `B_t`; `B` and `C` `[N]` are the same for every head (`n_groups` 1). For `t = 0 .. S-1`, from `h = 0`:

    h_t = exp(a_t) h_{t-1} + (dt_t x_t) B_t^T        a_t = -exp(A_log) dt_t <= 0: the log of the decay, float32
    y_t = h_t C_t

(the skip `D x` and the gate are the mixer's, `models/gpt2/ssd.py`). Two forms of one function, in the pattern of
`ops/attention.py`. `ssd_recurrent` is that walk, a `lax.scan` over positions in float32: the form the tests hold the
other against, and the one the benchmark's reference is written after. `ssd_chunked` is what the program runs, on every
platform (listing 1 of the paper, `ssd_minimal_discrete`): the scalar decay a head is what lets a chunk of `Q` positions
be four matrix products. In a chunk, `s_i = a_0 + ... + a_i` and `X_j = dt_j x_j`:

    L[i, j]  = exp(s_i - s_j) for i >= j, else 0          from the difference, never as exp(s_i) exp(-s_j)
    Y_in[i]  = sum_j (C_i . B_j) L[i, j] X_j              `C B^T` is one [Q, Q] product a chunk, shared by the heads
    S_c      = sum_j exp(s_last - s_j) X_j B_j^T          the chunk's own state [P, N]
    H_c      = exp(s_last) H_{c-1} + S_c,  H_{-1} = 0     one pass over the S / Q chunks
    Y_off[i] = exp(s_i) H_{c-1} C_i
    y        = Y_in + Y_off

`Y_in`, `S_c` and `Y_off` are computed for all chunks at once, batched products on the matrix unit; the one sequential
part is the pass over the chunks, an elementwise step on `[B, H, P, N]` a chunk (no product inside it). A row that `Q`
does not divide is padded with steps of `dt = 0` (and `a = 0`), which leave the state as it is, and cut again. The state
runs through the whole row and starts at zero with it.

Precision. `a`, its sums, `L`, the decays to a chunk's end and the carried state are float32. The four products take
operands in the inputs' dtype (bfloat16 in training: `X`, `C B^T o L`, the decayed `X` and the state that came into a
chunk are rounded to it once, where they are an operand) and accumulate in float32; with float32 inputs every product
is float32 at `highest`.

Backward. Autodiff of the form above. A rematerialized block computes a layer's chunk matrices again in its backward
and holds them for that one layer; the states at the chunks' starts (`state_bytes`) are the scan's own residuals.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from modalities_tpu.telemetry import scopes

FORM = "chunked_jnp"  # the form that ran, for `ssd_plan`: there is no kernel behind `tiers.kernels_run()` yet


def state_bytes(tokens: int, heads: int, head_dim: int, state: int, chunk: int) -> int:
    """Bytes of the float32 states at the chunks' starts of one row of one layer: `[chunks, H, P, N]`."""
    return -(-tokens // chunk) * heads * head_dim * state * 4


def _dot(spec: str, a, b, dtype):
    """A product with operands in `dtype` and a float32 result; float32 operands multiply exactly."""
    precision = jax.lax.Precision.HIGHEST if jnp.dtype(dtype) == jnp.float32 else None
    return jnp.einsum(spec, a.astype(dtype), b.astype(dtype), precision=precision, preferred_element_type=jnp.float32)


def ssd_chunked(x, dt, a, b, c, *, chunk: int):
    """x `[B, S, H, P]`, dt and a `[B, S, H]` float32 (`a <= 0`, the log of the decay), b and c `[B, S, N]`.
    Returns y `[B, S, H, P]` in x's dtype. The chunked form (module docstring)."""
    bsz, s, h, p = x.shape
    n, dtype, f32 = b.shape[-1], x.dtype, jnp.float32
    pad = -s % chunk
    if pad:  # steps that change nothing: no input, no decay
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt, a = (jnp.pad(v, ((0, 0), (0, pad), (0, 0))) for v in (dt, a))
        b, c = (jnp.pad(v, ((0, 0), (0, pad), (0, 0))) for v in (b, c))
    chunks = (s + pad) // chunk
    with jax.named_scope(scopes.SSD_INTRA):
        xc = (x.astype(f32) * dt.astype(f32)[..., None]).astype(dtype).reshape(bsz, chunks, chunk, h, p)  # X = dt x
        bc, cc = b.reshape(bsz, chunks, chunk, n), c.reshape(bsz, chunks, chunk, n)
        cum = jnp.cumsum(a.astype(f32).reshape(bsz, chunks, chunk, h), axis=2)  # s_i [B, n, Q, H]
        by_head = cum.transpose(0, 1, 3, 2)  # [B, n, H, Q]
        row, col = jnp.arange(chunk)[:, None], jnp.arange(chunk)[None, :]
        # exp of a difference that is never positive: above the diagonal it would be, and could overflow
        decay = jnp.where(row >= col, jnp.exp(jnp.where(row >= col, by_head[..., :, None] - by_head[..., None, :], 0.0)), 0.0)
        cb = _dot("bnis,bnjs->bnij", cc, bc, dtype)  # one product a chunk, read by every head
        y_in = _dot("bnhij,bnjhp->bnihp", cb[:, :, None] * decay, xc, dtype)
    with jax.named_scope(scopes.SSD_STATE):
        to_end = jnp.exp(cum[:, :, -1:, :] - cum)  # exp(s_last - s_j) [B, n, Q, H]
        own = _dot("bnjhp,bnjs->bnhps", xc.astype(f32) * to_end[..., None], bc, dtype)  # S_c [B, n, H, P, N]
        carry_decay = jnp.exp(cum[:, :, -1, :])  # exp(s_last) [B, n, H]

        def step(state, per_chunk):
            decay_c, own_c = per_chunk
            return decay_c[..., None, None] * state + own_c, state  # what goes on, and what came in

        _, came_in = jax.lax.scan(step, jnp.zeros((bsz, h, p, n), f32), (jnp.moveaxis(carry_decay, 1, 0), jnp.moveaxis(own, 1, 0)))
        y_off = _dot("bnis,bnhps->bnihp", cc, jnp.moveaxis(came_in, 0, 1), dtype) * jnp.exp(cum)[..., None]
        y = (y_in + y_off).astype(dtype).reshape(bsz, s + pad, h, p)
    return y[:, :s] if pad else y


def ssd_recurrent(x, dt, a, b, c):
    """The recurrence of the module docstring position by position, float32: what the chunked form is held against."""
    bsz, _, h, p = x.shape
    f32, highest = jnp.float32, jax.lax.Precision.HIGHEST

    def step(state, at):
        x_t, dt_t, a_t, b_t, c_t = at  # [B, H, P], [B, H], [B, H], [B, N], [B, N]
        state = jnp.exp(a_t)[..., None, None] * state + (dt_t[..., None] * x_t)[..., None] * b_t[:, None, None, :]
        return state, jnp.einsum("bhpn,bn->bhp", state, c_t, precision=highest)

    by_position = lambda v: jnp.moveaxis(v.astype(f32), 1, 0)  # noqa: E731
    _, out = jax.lax.scan(step, jnp.zeros((bsz, h, p, b.shape[-1]), f32), tuple(by_position(v) for v in (x, dt, a, b, c)))
    return jnp.moveaxis(out, 0, 1).astype(x.dtype)
