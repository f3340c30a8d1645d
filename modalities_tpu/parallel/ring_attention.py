"""Ring attention over the `cp` mesh axis — real context parallelism.

The reference materializes a `cp` mesh dim but consumes it nowhere (SURVEY.md §5.7:
no ring attention/Ulysses/blockwise attention exist; trainer.py:165 has only a
commented-out CP context). This module fills that slot TPU-first:

- sequence dim sharded over `cp`; each device holds local q/k/v chunks
- k/v chunks rotate around the ring via `lax.ppermute` (ICI neighbor hops) while each
  device accumulates attention for its q chunk with an online-softmax merge — peak
  memory O(S_local * block) per device instead of O(S^2), communication overlappable
- two forms of a hop: on TPU each hop runs the in-repo Pallas flash kernel
  (ops/pallas/flash_attention.py) and hops merge their normalized (out, lse) pairs
  with the flash-decoding rule; off-TPU a dense/k-blocked einsum path keeps tests
  exact. Chunk-level causality is decided OUTSIDE the kernel (full/diagonal/skip
  branches under lax.switch), so the kernel needs no traced position offsets.
- differentiable end-to-end: the dense tier by plain autodiff (reverse ring derived
  by JAX); the flash tier by an explicit custom_vjp that re-runs the ring with the
  flash backward kernels against the global (lse, delta), with dk/dv accumulators
  riding the k/v rotation.

Composable with GQA (kv-head grouping) and remat (the block remat wraps this).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from modalities_tpu.ops import tiers

NEG_INF = -1e30


# k-block size for the fused (flash-style) local attention in the DENSE tier: above
# this key length the per-hop logits are computed block-by-block under lax.scan with
# an online-softmax merge, so per-device peak memory is O(S_local * BLOCK_K) instead
# of O(S_local^2). On TPU the ring instead runs the Pallas flash kernel per hop
# (the `flash` tier below), merging per-hop (out, lse) pairs.
BLOCK_K = 1024


def _dense_chunk_stats(q, k, v, q_offset, k_offset, causal: bool, sm_scale: float):
    """One dense logits block. q: [B,Sq,Hq,D], k/v: [B,Sk,Hkv,D]
    -> (o_unnorm [B,Sq,Hq,D] f32, m, l [B,Sq,Hq] f32)."""
    b, sq, hq, d = q.shape
    hkv = k.shape[2]
    group = hq // hkv
    qg = q.reshape(b, sq, hkv, group, d).astype(jnp.float32)
    s = jnp.einsum("bshgd,bthd->bhgst", qg * sm_scale, k.astype(jnp.float32))  # [B,Hkv,G,Sq,Sk]
    if causal:
        q_pos = q_offset + jnp.arange(sq)
        k_pos = k_offset + jnp.arange(k.shape[1])
        mask = q_pos[:, None] >= k_pos[None, :]
        s = jnp.where(mask[None, None, None, :, :], s, NEG_INF)
    m = s.max(axis=-1)  # [B,Hkv,G,Sq]
    p = jnp.exp(s - m[..., None])
    # fully-masked rows: m == NEG_INF -> force p to 0 so l stays 0
    p = jnp.where((m == NEG_INF)[..., None], 0.0, p)
    l = p.sum(axis=-1)
    o = jnp.einsum("bhgst,bthd->bshgd", p, v.astype(jnp.float32))
    o = o.reshape(b, sq, hq, d)
    m = m.transpose(0, 3, 1, 2).reshape(b, sq, hq)
    l = l.transpose(0, 3, 1, 2).reshape(b, sq, hq)
    return o, m, l


def _merge_stats(acc, m_run, l_run, o_r, m_r, l_r):
    """Online-softmax merge of one partial block into the running (acc, m, l)."""
    m_new = jnp.maximum(m_run, m_r)
    alpha = jnp.where(m_run == NEG_INF, 0.0, jnp.exp(m_run - m_new))
    beta = jnp.where(m_r == NEG_INF, 0.0, jnp.exp(m_r - m_new))
    acc = acc * alpha[..., None] + o_r * beta[..., None]
    l_run = l_run * alpha + l_r * beta
    return acc, m_new, l_run


def _chunk_attention_stats(
    q, k, v, q_offset, k_offset, causal: bool, sm_scale: float, block_k: int = BLOCK_K
):
    """Local attention with global-position causal mask, fused over k blocks when the
    key chunk is long (the memory profile CP exists for at 32k+ contexts)."""
    sk = k.shape[1]
    if sk <= 2 * block_k or sk % block_k != 0:
        return _dense_chunk_stats(q, k, v, q_offset, k_offset, causal, sm_scale)

    b, sq, hq, d = q.shape
    num_blocks = sk // block_k
    k_blocks = k.reshape(b, num_blocks, block_k, *k.shape[2:]).transpose(1, 0, 2, 3, 4)
    v_blocks = v.reshape(b, num_blocks, block_k, *v.shape[2:]).transpose(1, 0, 2, 3, 4)

    # remat the block body: without it, scan-autodiff saves every block's softmax
    # residuals and backward peak memory is O(Sq*Sk) again (flash-attention practice:
    # recompute per-block stats in the backward pass)
    @jax.checkpoint
    def body(carry, xs):
        acc, m_run, l_run = carry
        blk_index, k_b, v_b = xs
        o_r, m_r, l_r = _dense_chunk_stats(
            q, k_b, v_b, q_offset, k_offset + blk_index * block_k, causal, sm_scale
        )
        return _merge_stats(acc, m_run, l_run, o_r, m_r, l_r), None

    init = (
        jnp.zeros((b, sq, hq, d), jnp.float32),
        jnp.full((b, sq, hq), NEG_INF, jnp.float32),
        jnp.zeros((b, sq, hq), jnp.float32),
    )
    (acc, m_run, l_run), _ = jax.lax.scan(
        body, init, (jnp.arange(num_blocks), k_blocks, v_blocks)
    )
    return acc, m_run, l_run


def _ring_dense_local(q, k, v, *, axis_name: str, causal: bool, sm_scale: float):
    """Dense/einsum ring body (CPU and fallback tier). q/k/v: [B, S_local, H(, kv), D]."""
    cp = jax.lax.psum(1, axis_name)
    my_index = jax.lax.axis_index(axis_name)
    s_local = q.shape[1]
    b, _, hq, d = q.shape

    acc = jnp.zeros((b, s_local, hq, d), jnp.float32)
    m_run = jnp.full((b, s_local, hq), NEG_INF, jnp.float32)
    l_run = jnp.zeros((b, s_local, hq), jnp.float32)

    k_cur, v_cur = k, v
    perm = [(i, (i + 1) % cp) for i in range(cp)]

    for r in range(cp):
        j_index = (my_index - r) % cp  # which chunk we currently hold
        o_r, m_r, l_r = _chunk_attention_stats(
            q, k_cur, v_cur,
            q_offset=my_index * s_local,
            k_offset=j_index * s_local,
            causal=causal,
            sm_scale=sm_scale,
        )
        acc, m_run, l_run = _merge_stats(acc, m_run, l_run, o_r, m_r, l_r)
        if r != cp - 1:
            k_cur = jax.lax.ppermute(k_cur, axis_name, perm)
            v_cur = jax.lax.ppermute(v_cur, axis_name, perm)

    l_safe = jnp.maximum(l_run, 1e-30)
    return (acc / l_safe[..., None]).astype(q.dtype)


# ------------------------------------------------------- flash-kernel ring tier
#
# The ring hop runs the Pallas flash kernel (ops/pallas/flash_attention.py) instead
# of dense einsums (VERDICT r4 #5). Two design moves keep the kernel unchanged:
#
# 1. (out, lse) replaces unnormalized (o, m, l): the kernel's normalized output plus
#    its log-sum-exp carry the same information (o = out * exp(lse), m+log l = lse),
#    and two hops merge exactly with the flash-decoding rule
#        lse' = logaddexp(lse_a, lse_b);  out' = out_a e^{lse_a-lse'} + out_b e^{lse_b-lse'}
# 2. chunk-level causality never enters the kernel: with whole-chunk hops a (q_i, k_j)
#    pairing is either fully visible (j < i: plain non-causal kernel), diagonal
#    (j == i: plain causal kernel, offsets cancel), or fully masked (j > i: skip —
#    constants, no kernel launch). The traced j-vs-i decision selects between the
#    three compiled branches with lax.switch, so no traced offsets reach Mosaic.
#
# Backward is the standard ring reversal: after the forward, (lse, delta) describe
# the GLOBAL softmax, so each hop can run the flash backward kernels blockwise
# (p = exp(s - lse)); dk/dv accumulators ride the k/v rotation and arrive home after
# cp hops. Differentiation is a custom_vjp over the whole per-shard ring.


def _hop_blocks(seq_q: int, seq_k: int):
    from modalities_tpu.ops.pallas.flash_attention import flash_blocks

    return flash_blocks(seq_q, seq_k)


def _hop_fwd(q, k, v, idx, sm_scale, interpret):
    """One ring hop, all [B, H, S, D]: lax.switch over (full | diagonal | skip).
    Returns (out fp32 [B,Hq,S,D], lse fp32 [B,Hq,S]: the kernel's [B,Hq,1,S] rows as the numbers the merge works on)."""
    from modalities_tpu.ops.pallas.flash_attention import flash_fwd_out_lse

    bq, bk = _hop_blocks(q.shape[2], k.shape[2])

    def make_hop(causal):  # one body, two causal flavors — keep the branches twins
        def hop(k_, v_):
            o, lse = flash_fwd_out_lse(
                q, k_, v_, causal=causal, sm_scale=sm_scale,
                block_q=bq, block_k=bk, interpret=interpret,
            )
            return o.astype(jnp.float32), lse[:, :, 0]

        return hop

    def skip(k_, v_):
        b, hq, sq, d = q.shape
        return (
            jnp.zeros((b, hq, sq, d), jnp.float32),
            jnp.full((b, hq, sq), NEG_INF, jnp.float32),
        )

    return jax.lax.switch(idx, (make_hop(causal=False), make_hop(causal=True), skip), k, v)


def _merge_out_lse(out_a, lse_a, out_b, lse_b):
    """Flash-decoding merge of two normalized partials (out [B,H,S,D], lse [B,H,S]). NEG_INF
    sentinels (not real -inf) keep the arithmetic NaN-free: exp(NEG_INF - finite) underflows to 0."""
    lse_m = jnp.maximum(lse_a, lse_b)
    lse_new = lse_m + jnp.log(jnp.exp(lse_a - lse_m) + jnp.exp(lse_b - lse_m))
    wa = jnp.exp(lse_a - lse_new)
    wb = jnp.exp(lse_b - lse_new)
    return out_a * wa[..., None] + out_b * wb[..., None], lse_new


def _branch_index(causal: bool, my_index, j_index):
    if not causal:
        return jnp.int32(0)
    return jnp.where(j_index == my_index, 1, jnp.where(j_index < my_index, 0, 2)).astype(jnp.int32)


def _ring_flash_fwd_res(q, k, v, axis_name, causal, sm_scale, interpret):
    """[B, S, H, D] inputs -> (out [B,S,Hq,D], residuals in kernel layout)."""
    cp = jax.lax.psum(1, axis_name)
    my_index = jax.lax.axis_index(axis_name)
    perm = [(i, (i + 1) % cp) for i in range(cp)]

    qt = q.transpose(0, 2, 1, 3)  # [B, Hq, S, D]
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)

    b, hq, s, d = qt.shape
    out_run = jnp.zeros((b, hq, s, d), jnp.float32)
    lse_run = jnp.full((b, hq, s), NEG_INF, jnp.float32)

    k_cur, v_cur = kt, vt
    for r in range(cp):
        j_index = (my_index - r) % cp
        o_r, lse_r = _hop_fwd(q=qt, k=k_cur, v=v_cur,
                              idx=_branch_index(causal, my_index, j_index),
                              sm_scale=sm_scale, interpret=interpret)
        out_run, lse_run = _merge_out_lse(out_run, lse_run, o_r, lse_r)
        if r != cp - 1:
            k_cur = jax.lax.ppermute(k_cur, axis_name, perm)
            v_cur = jax.lax.ppermute(v_cur, axis_name, perm)

    out_t = out_run.astype(q.dtype)  # [B, Hq, S, D]
    return out_t.transpose(0, 2, 1, 3), (qt, kt, vt, out_t, lse_run[:, :, None])  # lse as the backward kernels read it: [B,Hq,1,S] rows


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _ring_flash_local(q, k, v, axis_name, causal, sm_scale, interpret):
    return _ring_flash_fwd_res(q, k, v, axis_name, causal, sm_scale, interpret)[0]


def _ring_flash_vjp_fwd(q, k, v, axis_name, causal, sm_scale, interpret):
    return _ring_flash_fwd_res(q, k, v, axis_name, causal, sm_scale, interpret)


def _ring_flash_vjp_bwd(axis_name, causal, sm_scale, interpret, res, do):
    from modalities_tpu.ops.pallas.flash_attention import flash_bwd_dkv, flash_bwd_dq

    qt, kt, vt, out_t, lse = res
    cp = jax.lax.psum(1, axis_name)
    my_index = jax.lax.axis_index(axis_name)
    perm = [(i, (i + 1) % cp) for i in range(cp)]

    do_t = do.transpose(0, 2, 1, 3).astype(qt.dtype)  # [B, Hq, S, D]
    delta = jnp.sum(do_t.astype(jnp.float32) * out_t.astype(jnp.float32), axis=-1)[:, :, None]  # [B,Hq,1,S], as lse
    bq, bk = _hop_blocks(qt.shape[2], kt.shape[2])

    def make_bwd_hop(causal):  # one body, two causal flavors — keep the branches twins
        def hop(k_, v_):
            kw = dict(causal=causal, sm_scale=sm_scale, block_q=bq, block_k=bk, interpret=interpret)
            dq_r = flash_bwd_dq(qt, k_, v_, do_t, lse, delta, **kw)
            dk_r, dv_r = flash_bwd_dkv(qt, k_, v_, do_t, lse, delta, **kw)
            return dq_r.astype(jnp.float32), dk_r.astype(jnp.float32), dv_r.astype(jnp.float32)

        return hop

    def skip_hop(k_, v_):
        return (
            jnp.zeros(qt.shape, jnp.float32),
            jnp.zeros(k_.shape, jnp.float32),
            jnp.zeros(v_.shape, jnp.float32),
        )

    dq_total = jnp.zeros(qt.shape, jnp.float32)
    # dk/dv accumulators ride the rotation with their chunk; after cp rotations the
    # chunk (and its fully-accumulated gradient) is back on its home device
    k_cur, v_cur = kt, vt
    dk_cur = jnp.zeros(kt.shape, jnp.float32)
    dv_cur = jnp.zeros(vt.shape, jnp.float32)

    for r in range(cp):
        j_index = (my_index - r) % cp
        idx = _branch_index(causal, my_index, j_index)
        dq_r, dk_r, dv_r = jax.lax.switch(
            idx, (make_bwd_hop(causal=False), make_bwd_hop(causal=True), skip_hop), k_cur, v_cur
        )
        dq_total = dq_total + dq_r
        dk_cur = dk_cur + dk_r
        dv_cur = dv_cur + dv_r
        if r != cp - 1:
            k_cur, v_cur, dk_cur, dv_cur = (
                jax.lax.ppermute(x, axis_name, perm) for x in (k_cur, v_cur, dk_cur, dv_cur)
            )
        else:
            # k/v are never read again — only the gradient accumulators take the
            # final hop home (saves 2 dead chunk transfers per layer per backward)
            dk_cur, dv_cur = (
                jax.lax.ppermute(x, axis_name, perm) for x in (dk_cur, dv_cur)
            )

    dq_out = dq_total.astype(qt.dtype).transpose(0, 2, 1, 3)
    dk_out = dk_cur.astype(kt.dtype).transpose(0, 2, 1, 3)
    dv_out = dv_cur.astype(vt.dtype).transpose(0, 2, 1, 3)
    return dq_out, dk_out, dv_out


_ring_flash_local.defvjp(_ring_flash_vjp_fwd, _ring_flash_vjp_bwd)


def _ring_attention_local(q, k, v, *, axis_name: str, causal: bool, sm_scale: float, flash: bool, interpret: bool):
    """Runs on each cp shard inside shard_map. q/k/v: [B, S_local, H(, kv), D].
    `flash` (Pallas hops, `interpret`ed off a TPU) or dense hops is resolved by the
    caller BEFORE entering the shard_map body (asking the platform can start the
    backend, which must never happen mid-trace inside the body) and is baked into
    the traced program."""
    if flash:
        return _ring_flash_local(q, k, v, axis_name, causal, sm_scale, interpret)
    return _ring_dense_local(q, k, v, axis_name=axis_name, causal=causal, sm_scale=sm_scale)


def ring_attention(
    q, k, v, mesh, *, axis_name: str = "cp", causal: bool = True, sm_scale: float | None = None
):
    """Context-parallel attention. q: [B, S, Hq, D], k/v: [B, S, Hkv, D], with S
    sharded over `axis_name`; all other axes left to GSPMD (shard_map auto mode).

    Flash hops where kernels run (`ops/tiers.py`: on a TPU), dense hops elsewhere:
    resolved HERE, at trace time, outside the shard_map body.
    """
    from jax.sharding import PartitionSpec as P

    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    # mesh is None during mesh-context-free traces (eval_shape); shapes are identical
    # on the fallback path, so abstract evaluation stays faithful
    if mesh is None or axis_name not in mesh.axis_names or mesh.shape[axis_name] == 1:
        return jax.nn.dot_product_attention(q, k, v, is_causal=causal, scale=sm_scale)

    hops = dict(flash=tiers.kernels_run(), interpret=tiers.interpret())

    # Already inside a manual region over cp (e.g. the pp pipeline's shard_map binds
    # {pp, cp})? Then q/k/v are per-shard local and collectives over cp are legal
    # directly — run the ring body without nesting another shard_map.
    from modalities_tpu.parallel.jax_compat import manual_axes, shard_map

    if axis_name in manual_axes():
        return _ring_attention_local(
            q, k, v, axis_name=axis_name, causal=causal, sm_scale=sm_scale, **hops
        )

    spec = P(None, axis_name, None, None)
    # only `cp` is manual; dp/tp stay auto so GSPMD keeps partitioning batch/heads
    fn = shard_map(
        functools.partial(
            _ring_attention_local, axis_name=axis_name, causal=causal, sm_scale=sm_scale, **hops
        ),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        axis_names=frozenset({axis_name}),
        check_vma=False,
    )
    return fn(q, k, v)
