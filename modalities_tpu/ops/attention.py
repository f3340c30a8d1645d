"""Attention kernel dispatch — the framework's `dao_flash` tier
(reference: flash-attn CUDA kernels used via gpt2_model.py:22-25, :643-655).

On a TPU the custom Pallas flash kernel (ops/pallas/flash_attention.py) runs, and
whatever it raises is raised: there is no second tier behind it. On CPU (tests) the
XLA-fused SDPA path is used so numerics stay exact.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from modalities_tpu.ops.tiers import on_tpu

_Q_AXES = ("batch", None, "heads", None)
_KV_AXES = ("batch", None, "kv_heads", None)


def _plain_attention(q, k, v, causal: bool, sm_scale: float | None, window: int | None = None):
    """Off the TPU, where v is not as wide as q and k or a window hides what lies behind it: the masked softmax
    written out, float32 scores."""
    group = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    scores = jnp.einsum("bshd,bthd->bhst", q, k).astype(jnp.float32) * (q.shape[-1] ** -0.5 if sm_scale is None else sm_scale)
    if causal:
        behind = jnp.arange(q.shape[1])[:, None] - jnp.arange(k.shape[1])[None, :]  # how far a key lies behind its query
        keep = behind >= 0 if window is None else (behind >= 0) & (behind < window)
        scores = jnp.where(keep, scores, jnp.finfo(jnp.float32).min)
    return jnp.einsum("bhst,bthd->bshd", jax.nn.softmax(scores, axis=-1).astype(v.dtype), v)


def flash_attention_or_fallback(q, k, v, causal: bool = True, sm_scale: float | None = None, window: int | None = None,
                                kept: bool = False):
    """q: [B,S,Hq,D], k: [B,S,Hkv,D], v: [B,S,Hkv,Dv] -> [B,S,Hq,Dv]. Dv is D everywhere but
    in latent attention (192 and 128); the kernels read both widths off the arrays.

    `window` W (PR 38; causal calls only): position i sees itself and the W - 1 before it. The
    same kernels run over a plan with a second edge (`tile_plan(..., window)`: a tile wholly
    behind it is no grid step, one it crosses is masked from that side) under labels of their
    own (`flash_attention_window_{fwd,bwd,bwd_dq,bwd_dkv}`), at the blocks an unwindowed call of
    the same shape gets; off the TPU the written-out softmax masks the same
    positions. Ring attention carries no window, and the serving paths' masks have none: both
    refuse a model that asks for one (`gpt2_model.py`). A call without a window gets the plan,
    the kernels and the event it always got.

    `kept` (PR 41): the call sits in a rematerialized block whose policy saves the kernel's o and lse
    (`gpt2_model._remat_block_cls` under `spec.remat_keep_flash`); a differentiated call then hands its
    backward those two under the names the policy reads (`flash_attention._flash_fwd_vjp`), lse as the kernel wrote it,
    `[B, H, 1, S]` rows of numbers (PR 42).
    Off the TPU there is no kernel and nothing to keep. A call without it binds what it always bound.

    Block sizes come from `env_flash_blocks`: MODALITIES_TPU_FLASH_BLOCK_Q / _BLOCK_K,
    else the device's tuning table (1024 x 1024 on a v5e; 1024 x 512 at 192/128), stepped
    down automatically for shorter sequences. What the driver's record holds for that
    choice is the benchmark's cells (PERF.md, sections 5 and 6): in `train-2p7b-4k` (S 4096,
    32 q / 8 kv heads of 80) the three kernels took 68.1 ms of a 340.3 ms step at
    1024 x 1024 (ledger, PR 24) and 50.7 of 322.0 once every score tile got only the work
    its place asks for (PR 25, which also read 512 x 512 in the cell: 333.3 ms a step).

    The backward of a differentiated call is one kernel since PR 31, `flash_attention_bwd`
    (dq, dk and dv from one evaluation of p and ds a tile, a q head's dq row resident in
    VMEM), wherever `backward_plan` counts it within its VMEM budget: every cell's shape
    and every recipe's but rows of 32k, which keep `flash_attention_bwd_dq` + `_bwd_dkv`.
    The `flash_tile_plan` event says which (`backward`, `dq_resident_bytes`,
    `backward_vmem_bytes`); what each form costs alone is
    `scripts/moe_mla_parts_bench.py --parts flash`, and PERF.md section 6 (PR 31) has the
    chip's readings.

    Under a mesh the kernel runs per shard, split over batch and heads
    (parallel/sharding.per_shard)."""
    if window is not None and not causal:
        raise ValueError("flash attention: a window is written for causal calls only")
    if not on_tpu():
        if v.shape[-1] != q.shape[-1] or window is not None:
            return _plain_attention(q, k, v, causal, sm_scale, window)  # SDPA takes one width for q, k and v
        return jax.nn.dot_product_attention(q, k, v, is_causal=causal, scale=sm_scale)
    from modalities_tpu.ops.pallas.flash_attention import backward_plan, env_flash_blocks, pallas_flash_attention, tile_plan
    from modalities_tpu.parallel.sharding import per_shard
    from modalities_tpu.telemetry import get_active_telemetry

    shape = dict(dtype=q.dtype, head_dim=q.shape[-1], head_dim_v=v.shape[-1])
    windowed = {} if window is None else {"window": window}  # a call without a window says and binds what it always did
    block_q, block_k = env_flash_blocks(q.shape[1], k.shape[1], **shape)
    bwd_blocks = env_flash_blocks(q.shape[1], k.shape[1], backward=True, **shape)
    plan = {"seq_q": q.shape[1], "seq_k": k.shape[1], "block_q": block_q, "block_k": block_k, "causal": causal}
    plan.update(windowed)  # with it the counts hold `window_edge`, the tiles its edge crosses
    # runs while tracing: the operator sees once per shape how many score tiles a
    # (batch, head) computes, which share takes the masked body, and which backward a
    # differentiated call would run (by the shape alone: a mesh splits batch and heads,
    # and the resident dq is one head's); nothing per step
    get_active_telemetry().emit_event_once(
        "flash_tile_plan", {**plan, "head_dim": q.shape[-1], "head_dim_v": v.shape[-1], **tile_plan(**plan).counts(),
                            **backward_plan(q.shape[1], *bwd_blocks, q.shape[-1], v.shape[-1], q.dtype)})
    kernel = functools.partial(
        pallas_flash_attention, causal=causal, sm_scale=sm_scale, block_q=block_q, block_k=block_k, bwd_blocks=bwd_blocks,
        kept=kept, **windowed
    )
    return per_shard(
        lambda _axes, q, k, v: kernel(q, k, v), (_Q_AXES, _KV_AXES, _KV_AXES), _Q_AXES
    )(q, k, v)
