"""Pallas TPU flash attention — the framework's `dao_flash` tier
(replaces the reference's flash-attn CUDA dependency, pyproject.toml:48,
gpt2_model.py:643-655).

Design (FlashAttention-2 style, TPU-first):
- forward: grid (B, Hq, Sq/BQ, Sk/BK) with the kv dimension innermost ("arbitrary"
  semantics): k/v stream through VMEM one [BK, D] tile per step while fp32
  accumulators (acc, m, l) persist in VMEM scratch — VMEM stays O(BQ*D + BK*D)
  regardless of sequence length; logsumexp is saved for the backward.
- backward: two kernels with the same streaming structure — dq over q blocks
  (kv innermost) and dk/dv over kv blocks (q innermost) — recomputing probabilities
  blockwise from the saved logsumexp (no S x S materialization anywhere). GQA folds
  the q-head group into the kv index map; dk/dv are accumulated per q-head and
  group-summed outside the kernel.
- causal blocks above the diagonal are skipped via predicated bodies (@pl.when).
- block sizes: this module's own defaults are 128 (the MXU tile), but the shipped
  configuration is 1024x1024 via the ops/attention.py dispatch wrapper (1.8x faster
  at 1.3B/seq-2048 on v5e — grid overhead dominates at tile-sized blocks), with
  automatic step-down for short sequences; interpret mode keeps CPU tests exact.
- TPU layout: per-row statistics (lse, delta) carry a trailing singleton lane dim
  ([B, H, S, 1] arrays, [block_q, 1] in-kernel tiles) because Mosaic requires the
  last two block dims to tile (8, 128) or equal the array dims — a bare [S] row
  vector does not lower (the official jax kernel lane-broadcasts to 128 instead;
  the singleton costs 128x less HBM for identical in-kernel code).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


# --------------------------------------------------------------------------- fwd


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref,
                *, sm_scale, causal, block_q, block_k):
    iq = pl.program_id(2)
    jk = pl.program_id(3)
    num_kv = pl.num_programs(3)

    @pl.when(jk == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    # causal: blocks entirely above the diagonal contribute nothing
    needed = jnp.logical_or(not causal, jk * block_k <= iq * block_q + block_q - 1)

    @pl.when(needed)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * sm_scale  # [BQ, D]
        k = k_ref[0, 0].astype(jnp.float32)  # [BK, D]
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        if causal:
            q_pos = iq * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
            k_pos = jk * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        m_prev, l_prev = m_ref[:], l_ref[:]  # [BQ, 1] column stats
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[:] = l_prev * alpha + p.sum(axis=-1, keepdims=True)
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_ref[:] = m_new

    @pl.when(jk == num_kv - 1)
    def _finish():
        l_safe = jnp.maximum(l_ref[:], 1e-30)
        o_ref[0, 0] = (acc_ref[:] / l_safe).astype(o_ref.dtype)
        lse_ref[0, 0] = m_ref[:] + jnp.log(l_safe)


# ---------------------------------------------------------------------- bwd: dq


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_acc_ref,
                   *, sm_scale, causal, block_q, block_k):
    iq = pl.program_id(2)
    jk = pl.program_id(3)
    num_kv = pl.num_programs(3)

    @pl.when(jk == 0)
    def _init():
        dq_acc_ref[:] = jnp.zeros_like(dq_acc_ref)

    needed = jnp.logical_or(not causal, jk * block_k <= iq * block_q + block_q - 1)

    @pl.when(needed)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0]  # [BQ, 1]
        delta = delta_ref[0, 0]  # [BQ, 1]
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q * sm_scale, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        if causal:
            q_pos = iq * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
            k_pos = jk * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * sm_scale
        dq_acc_ref[:] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    @pl.when(jk == num_kv - 1)
    def _finish():
        dq_ref[0, 0] = dq_acc_ref[:].astype(dq_ref.dtype)


# -------------------------------------------------------------------- bwd: dkdv


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
                    dk_acc_ref, dv_acc_ref, *, sm_scale, causal, block_q, block_k):
    jk = pl.program_id(2)
    iq = pl.program_id(3)
    num_q = pl.num_programs(3)

    @pl.when(iq == 0)
    def _init():
        dk_acc_ref[:] = jnp.zeros_like(dk_acc_ref)
        dv_acc_ref[:] = jnp.zeros_like(dv_acc_ref)

    needed = jnp.logical_or(not causal, iq * block_q + block_q - 1 >= jk * block_k)

    @pl.when(needed)
    def _compute():
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        q = q_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0]  # [BQ, 1]
        delta = delta_ref[0, 0]  # [BQ, 1]
        s = jax.lax.dot_general(
            q * sm_scale, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        if causal:
            q_pos = iq * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
            k_pos = jk * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        p = jnp.exp(s - lse)
        dv_acc_ref[:] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * sm_scale
        dk_acc_ref[:] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    @pl.when(iq == num_q - 1)
    def _finish():
        dk_ref[0, 0] = dk_acc_ref[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc_ref[:].astype(dv_ref.dtype)


# ------------------------------------------------------------------- entry point


def _pick_block(seq: int, preferred: int) -> int:
    if seq % preferred == 0:
        return preferred
    for cand in (512, 256, 128, 64, 32, 16, 8):
        if seq % cand == 0 and cand <= seq:
            return cand
    return seq


def env_flash_blocks(seq_q: int, seq_k: int, dtype="bfloat16") -> tuple[int, int]:
    """The (block_q, block_k) tuning knobs, shared by every kernel consumer
    (ops/attention.py dispatch, the ring tier). Precedence per knob:
    MODALITIES_TPU_FLASH_BLOCK_Q/_K env override > the per-device autotune table
    (ops/pallas/autotune.py, consulted at trace time) > 1024 (see ops/attention.py
    for the v5e tuning evidence) — then stepped down to divide the sequence. A
    malformed override raises (int()) — it must never silently demote the call to
    a fallback tier."""
    import os

    env_q = os.environ.get("MODALITIES_TPU_FLASH_BLOCK_Q")
    env_k = os.environ.get("MODALITIES_TPU_FLASH_BLOCK_K")
    block_q = int(env_q) if env_q is not None else None
    block_k = int(env_k) if env_k is not None else None
    if block_q is None or block_k is None:
        from modalities_tpu.ops.pallas import autotune

        hit = autotune.lookup(
            "flash_attention",
            f"sq{autotune.shape_bucket(seq_q)}_sk{autotune.shape_bucket(seq_k)}",
            jnp.dtype(dtype).name,
        )
        if hit:
            block_q = block_q if block_q is not None else int(hit.get("block_q", 1024))
            block_k = block_k if block_k is not None else int(hit.get("block_k", 1024))
    if block_q is None:
        block_q = 1024
    if block_k is None:
        block_k = 1024
    return _pick_block(seq_q, block_q), _pick_block(seq_k, block_k)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_attention_bhsd(q, k, v, sm_scale, causal, block_q, block_k, interpret):
    out, _ = _flash_fwd(q, k, v, sm_scale, causal, block_q, block_k, interpret)
    return out


def _flash_fwd(q, k, v, sm_scale, causal, block_q, block_k, interpret):
    """q: [B, Hq, Sq, D]; k/v: [B, Hkv, Sk, D] -> (out, residuals)."""
    batch, num_heads, seq_q, head_dim = q.shape
    num_kv_heads, seq_k = k.shape[1], k.shape[2]
    group = num_heads // num_kv_heads

    kernel = functools.partial(
        _fwd_kernel, sm_scale=sm_scale, causal=causal, block_q=block_q, block_k=block_k
    )
    out, lse = pl.pallas_call(
        kernel,
        grid=(batch, num_heads, seq_q // block_q, seq_k // block_k),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, head_dim), lambda b, h, iq, jk: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, block_k, head_dim), lambda b, h, iq, jk: (b, h // group, jk, 0)),
            pl.BlockSpec((1, 1, block_k, head_dim), lambda b, h, iq, jk: (b, h // group, jk, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, head_dim), lambda b, h, iq, jk: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, block_q, 1), lambda b, h, iq, jk: (b, h, iq, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((batch, num_heads, seq_q, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, head_dim), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        interpret=interpret,
        name="flash_attention_fwd",
    )(q, k, v)
    return out, (q, k, v, out, lse)


def flash_fwd_out_lse(q, k, v, *, causal, sm_scale, block_q, block_k, interpret):
    """Raw kernel forward WITH the log-sum-exp exposed: [B, H, S, D] ->
    (out [B, H, S, D], lse [B, H, Sq, 1] fp32). (out, lse) is the information-
    equivalent of unnormalized (o, m, l) block stats — o = out * exp(lse - m) * ...
    collapses to this pair — and it is exactly what an online-softmax merge needs:
    ring attention (parallel/ring_attention.py) merges per-hop (out, lse) pairs
    across k/v rotations. No custom_vjp here: the caller owns differentiation."""
    out, (_, _, _, _, lse) = _flash_fwd(q, k, v, sm_scale, causal, block_q, block_k, interpret)
    return out, lse


def _flash_fwd_vjp(q, k, v, sm_scale, causal, block_q, block_k, interpret):
    # custom_vjp fwd receives arguments in the primal order (nondiff included in place)
    out, res = _flash_fwd(q, k, v, sm_scale, causal, block_q, block_k, interpret)
    return out, res


def flash_bwd_dq(q, k, v, do, lse, delta, *, causal, sm_scale, block_q, block_k, interpret):
    """dq for one (q, k, v) pairing given GLOBAL (lse, delta) — reusable by the ring
    backward, where lse/delta come from the merged multi-hop softmax. All [B,H,S,D];
    lse/delta [B,H,Sq,1] fp32."""
    batch, num_heads, seq_q, head_dim = q.shape
    seq_k = k.shape[2]
    group = num_heads // k.shape[1]

    return pl.pallas_call(
        functools.partial(
            _bwd_dq_kernel, sm_scale=sm_scale, causal=causal, block_q=block_q, block_k=block_k
        ),
        grid=(batch, num_heads, seq_q // block_q, seq_k // block_k),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, head_dim), lambda b, h, iq, jk: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, block_k, head_dim), lambda b, h, iq, jk: (b, h // group, jk, 0)),
            pl.BlockSpec((1, 1, block_k, head_dim), lambda b, h, iq, jk: (b, h // group, jk, 0)),
            pl.BlockSpec((1, 1, block_q, head_dim), lambda b, h, iq, jk: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, block_q, 1), lambda b, h, iq, jk: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, block_q, 1), lambda b, h, iq, jk: (b, h, iq, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, head_dim), lambda b, h, iq, jk: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, head_dim), jnp.float32)],
        interpret=interpret,
        name="flash_attention_bwd_dq",
    )(q, k, v, do, lse, delta)

def flash_bwd_dkv(q, k, v, do, lse, delta, *, causal, sm_scale, block_q, block_k, interpret):
    """(dk, dv) for one (q, k, v) pairing given GLOBAL (lse, delta), GQA group-summed
    down to the kv heads ([B, Hkv, Sk, D]). Reusable by the ring backward, where the
    accumulators ride the k/v rotation."""
    batch, num_heads, seq_q, head_dim = q.shape
    num_kv_heads, seq_k = k.shape[1], k.shape[2]
    group = num_heads // num_kv_heads

    # dk/dv per q-head (q blocks innermost), then summed over the GQA group
    dk_h, dv_h = pl.pallas_call(
        functools.partial(
            _bwd_dkv_kernel, sm_scale=sm_scale, causal=causal, block_q=block_q, block_k=block_k
        ),
        grid=(batch, num_heads, seq_k // block_k, seq_q // block_q),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, head_dim), lambda b, h, jk, iq: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, block_k, head_dim), lambda b, h, jk, iq: (b, h // group, jk, 0)),
            pl.BlockSpec((1, 1, block_k, head_dim), lambda b, h, jk, iq: (b, h // group, jk, 0)),
            pl.BlockSpec((1, 1, block_q, head_dim), lambda b, h, jk, iq: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, block_q, 1), lambda b, h, jk, iq: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, block_q, 1), lambda b, h, jk, iq: (b, h, iq, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_k, head_dim), lambda b, h, jk, iq: (b, h, jk, 0)),
            pl.BlockSpec((1, 1, block_k, head_dim), lambda b, h, jk, iq: (b, h, jk, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((batch, num_heads, seq_k, head_dim), q.dtype),
            jax.ShapeDtypeStruct((batch, num_heads, seq_k, head_dim), q.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, head_dim), jnp.float32),
            pltpu.VMEM((block_k, head_dim), jnp.float32),
        ],
        interpret=interpret,
        name="flash_attention_bwd_dkv",
    )(q, k, v, do, lse, delta)

    if group > 1:
        dk = dk_h.reshape(batch, num_kv_heads, group, seq_k, head_dim).sum(axis=2)
        dv = dv_h.reshape(batch, num_kv_heads, group, seq_k, head_dim).sum(axis=2)
    else:
        dk, dv = dk_h, dv_h
    return dk.astype(k.dtype), dv.astype(v.dtype)


def _flash_bwd_vjp(sm_scale, causal, block_q, block_k, interpret, res, do):
    q, k, v, out, lse = res
    # [B, H, Sq, 1] — trailing singleton lane dim (see module docstring)
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1, keepdims=True)
    kw = dict(causal=causal, sm_scale=sm_scale, block_q=block_q, block_k=block_k, interpret=interpret)
    dq = flash_bwd_dq(q, k, v, do, lse, delta, **kw)
    dk, dv = flash_bwd_dkv(q, k, v, do, lse, delta, **kw)
    return dq, dk, dv


_flash_attention_bhsd.defvjp(_flash_fwd_vjp, _flash_bwd_vjp)


def pallas_flash_attention(
    q, k, v, causal: bool = True, sm_scale: float | None = None,
    block_q: int = 128, block_k: int = 128, interpret: bool = False,
):
    """Public entry. q: [B, S, Hq, D], k/v: [B, S, Hkv, D] (model layout) -> [B, S, Hq, D]."""
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    seq_q, seq_k = q.shape[1], k.shape[1]
    block_q = _pick_block(seq_q, block_q)
    block_k = _pick_block(seq_k, block_k)
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    out = _flash_attention_bhsd(qt, kt, vt, sm_scale, causal, block_q, block_k, interpret)
    return out.transpose(0, 2, 1, 3)
