"""Attention: the one ladder that picks a mixer's attention function (`causal_attention`), the
functions it picks among, and the kernel dispatch behind the `dao_flash` rung
(reference: flash-attn CUDA kernels used via gpt2_model.py:22-25, :643-655).

The mixers (`models/gpt2/gpt2_model.CausalSelfAttention`, `mla.py`, `cca.py`) keep what is
theirs (projections, rotary, norms, scopes, the `attn_out` checkpoint name) and call
`causal_attention`; the serving cache paths call `masked_attention` with their own masks.

On a TPU the custom Pallas flash kernel (ops/pallas/flash_attention.py) runs, and whatever it
raises is raised: there is no second form behind it. Off a TPU (tests) the XLA-fused SDPA
path is used so numerics stay exact (`ops/tiers.py` has the rule). The two written-out
softmaxes (`_plain_attention` behind the kernel's rung off a TPU, `masked_attention` for the
`manual` rung and the caches) differ in rounding and are not merged: ROADMAP.md, D3.
"""

from __future__ import annotations

import functools
import math
from enum import Enum
from typing import Optional

import jax
import jax.numpy as jnp

from modalities_tpu.ops import tiers


class AttentionImplementation(str, Enum):
    """The config's `attention_implementation`: which rung of `causal_attention` a model asks for."""

    MANUAL = "manual"
    PYTORCH_FLASH = "pytorch_flash"  # config-compat alias for the XLA-fused SDPA tier
    DAO_FLASH = "dao_flash"  # Pallas flash-attention kernel tier


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
    refuse a model that asks for one (`models/gpt2/gpt2_model.py`). A call without a window gets the plan,
    the kernels and the event it always got.

    `kept` (PR 41): the call sits in a rematerialized block whose policy saves the kernel's o and lse
    (`gpt2_model._remat_block_cls` under `spec.remat_keep_flash`); a differentiated call then hands its
    backward those two under the names the policy reads (`flash_attention._flash_fwd_vjp`), lse as the kernel wrote it,
    `[B, H, 1, S]` rows of numbers (PR 42).
    Off the TPU there is no kernel and nothing to keep (an interpreted kernel, in tests, keeps as the chip's does). A call without it binds what it always bound.

    Block sizes come from `flash_blocks`: the device's tuning table (1024 x 1024 on a v5e;
    1024 x 512 at 192/128), stepped down automatically for shorter sequences. What the driver's record holds for that
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
    if not tiers.kernels_run():
        if v.shape[-1] != q.shape[-1] or window is not None:
            return _plain_attention(q, k, v, causal, sm_scale, window)  # SDPA takes one width for q, k and v
        return jax.nn.dot_product_attention(q, k, v, is_causal=causal, scale=sm_scale)
    from modalities_tpu.ops.pallas.flash_attention import backward_plan, flash_blocks, pallas_flash_attention, tile_plan
    from modalities_tpu.parallel.sharding import per_shard
    from modalities_tpu.telemetry import get_active_telemetry

    shape = dict(dtype=q.dtype, head_dim=q.shape[-1], head_dim_v=v.shape[-1])
    windowed = {} if window is None else {"window": window}  # a call without a window says and binds what it always did
    block_q, block_k = flash_blocks(q.shape[1], k.shape[1], **shape)
    bwd_blocks = flash_blocks(q.shape[1], k.shape[1], backward=True, **shape)
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
        kept=kept, interpret=tiers.interpret(), **windowed
    )
    return per_shard(
        lambda _axes, q, k, v: kernel(q, k, v), (_Q_AXES, _KV_AXES, _KV_AXES), _Q_AXES
    )(q, k, v)


def masked_attention(q, k, v, mask, dropout_rate: float = 0.0, dropout_rng=None, sm_scale: Optional[float] = None):
    """einsum + fp32 softmax attention with an explicit boolean mask — [Sq, Sk]
    shared across the batch, or [B, Sq, Sk] per-batch-row (slot decode: each slot
    attends up to its own cache length).
    q: [B,Sq,Hq,D], k: [B,Sk,Hkv,D], v: [B,Sk,Hkv,Dv]; GQA convention: q head h uses kv head h // group.

    `dropout_rate` > 0 applies inverted dropout to the attention *probabilities*
    (the reference semantic: manual_scaled_dot_product_attention / SDPA `dropout_p`,
    reference gpt2_model.py:595-658) — NOT to the attention output."""
    b, sq, hq, d = q.shape
    hkv = k.shape[2]
    group = hq // hkv
    qg = q.reshape(b, sq, hkv, group, d)
    logits = jnp.einsum("bshgd,bthd->bhgst", qg, k).astype(jnp.float32)
    logits = logits / math.sqrt(d) if sm_scale is None else logits * sm_scale
    mask_b = mask[None, None, None, :, :] if mask.ndim == 2 else mask[:, None, None, :, :]
    logits = jnp.where(mask_b, logits, jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(logits, axis=-1)
    if dropout_rate > 0.0:
        if dropout_rng is None:
            raise ValueError(
                "masked_attention: dropout_rate > 0 requires dropout_rng — refusing "
                "to silently skip attention-probability dropout"
            )
        keep = jax.random.bernoulli(dropout_rng, 1.0 - dropout_rate, probs.shape)
        probs = jnp.where(keep, probs / (1.0 - dropout_rate), 0.0)
    probs = probs.astype(v.dtype)
    out = jnp.einsum("bhgst,bthd->bshgd", probs, v)
    return out.reshape(b, sq, hq, v.shape[-1])


def manual_attention(q, k, v, dropout_rate: float = 0.0, dropout_rng=None, window: Optional[int] = None, sm_scale: Optional[float] = None):
    """Oracle attention: causal mask over a square sequence (reference :595-658); under `window`
    a position sees itself and the `window - 1` before it."""
    s = q.shape[1]
    mask = jnp.tril(jnp.ones((s, s), dtype=bool))
    if window is not None:
        mask = mask & ~jnp.tril(jnp.ones((s, s), dtype=bool), -window)
    return masked_attention(q, k, v, mask, dropout_rate=dropout_rate, dropout_rng=dropout_rng, sm_scale=sm_scale)


def sdpa_attention(q, k, v, sm_scale: Optional[float] = None):
    """XLA-fused scaled dot product attention with native GQA support."""
    return jax.nn.dot_product_attention(q, k, v, is_causal=True, scale=sm_scale)


def flash_attention(q, k, v, window: Optional[int] = None, kept: bool = False, sm_scale: Optional[float] = None):
    """The `dao_flash` rung: the Pallas kernels on a TPU, SDPA off it (under a window or two widths, the masked softmax
    written out). `kept`: the call sits in a rematerialized block that keeps the kernel's o and lse (`spec.remat_keep_flash`).
    `sm_scale`: the scores' scale where it is not `1 / sqrt(D)` (`attention_multiplier`)."""
    return flash_attention_or_fallback(q, k, v, causal=True, sm_scale=sm_scale, window=window, kept=kept)


def takes_kernel(impl: str, dropout_rate: float = 0.0, cp_axis: Optional[str] = None) -> bool:
    """Whether `causal_attention` answers from the kernel's rung: the ladder's own question, and that of a planner
    that counts the kernel calls a model holds (`GPT2LLM.remat_flash_calls`)."""
    return impl == AttentionImplementation.DAO_FLASH.value and dropout_rate == 0.0 and cp_axis is None


def causal_attention(q, k, v, *, impl: str, window: Optional[int] = None, kept: bool = False, dropout_rate: float = 0.0,
                     dropout_rng=None, cp_axis: Optional[str] = None, flash=flash_attention, sm_scale: Optional[float] = None):
    """The attention function of a training forward, for every mixer: q `[B,S,Hq,D]`, k `[B,S,Hkv,D]`, v `[B,S,Hkv,Dv]`
    -> `[B,S,Hq,Dv]`, scores scaled by `1 / sqrt(D)` (by `sm_scale` where a config gives the scale itself: `attention_multiplier`; the
    ring carries none and refuses one), position i seeing 0..i (the last `window` of them under one).

    `impl` is the config's `attention_implementation` (`manual` | `pytorch_flash` | `dao_flash`); `dropout_rate` is the
    attention-probability dropout in force for this call (0 where the module is deterministic: the reference passes
    `dropout` into the attention itself, manual attn_dropout(att) / SDPA+flash dropout_p); `cp_axis` the mesh axis the
    sequence is split over, if any. The rungs, in order:

    1. under a cp axis the ring (`parallel/ring_attention.py`), which carries no dropout: it merges per-chunk softmax
       statistics that dropout would invalidate;
    2. under dropout the written-out softmax, which drops probabilities as the reference does (the fused XLA SDPA has
       no dropout hook, so `pytorch_flash` lands here too; the Pallas kernel does not sample, so `dao_flash` refuses
       rather than silently training a different model: docs/components.md, section 2.4);
    3. `dao_flash`: the kernel's rung (`flash`; told `kept` only where the block keeps: a call that is not told is the
       call it always was);
    4. `manual`, a window (fused SDPA's mask is causal, no more) or a v narrower than q (SDPA takes one width): the
       written-out softmax;
    5. else XLA's fused SDPA.

    `flash` is `gpt2_model`'s own name for rung 3, which `tests/benchmark/` replaces to drop a window; no other caller passes it."""
    scaled = {} if sm_scale is None else {"sm_scale": sm_scale}  # a call without a scale of its own is the call it always was
    if cp_axis is not None:
        if sm_scale is not None:
            raise NotImplementedError("a scale on the scores other than 1 / sqrt(head_dim) (attention_multiplier) is not written for ring "
                                      "attention (context parallelism). Run it without a cp mesh axis.")
        if dropout_rate > 0.0:
            raise NotImplementedError(
                "attention-probability dropout (dropout > 0) is not implemented for "
                "ring attention (context parallelism): the ring merges per-chunk "
                "softmax statistics that dropout would invalidate. Set dropout: 0.0 "
                "or run without a cp mesh axis."
            )
        # real context parallelism: ring attention over the cp axis (the slot the reference leaves unfilled, SURVEY.md §5.7)
        from modalities_tpu.parallel.ring_attention import ring_attention
        from modalities_tpu.running_env.device_mesh import current_mesh

        return ring_attention(q, k, v, current_mesh(), axis_name=cp_axis)
    if dropout_rate > 0.0:
        if takes_kernel(impl):
            raise NotImplementedError(
                "attention-probability dropout (dropout > 0) is not implemented in "
                "the dao_flash Pallas kernel. Use attention_implementation: manual "
                "or pytorch_flash (both apply the reference's attention-weight "
                "dropout semantics), or set dropout: 0.0."
            )
        return manual_attention(q, k, v, dropout_rate=dropout_rate, dropout_rng=dropout_rng, window=window, **scaled)
    if takes_kernel(impl):
        return flash(q, k, v, window, kept=True, **scaled) if kept else flash(q, k, v, window, **scaled)
    if impl == AttentionImplementation.MANUAL.value or window is not None or v.shape[-1] != q.shape[-1]:
        return manual_attention(q, k, v, window=window, **scaled)
    return sdpa_attention(q, k, v, **scaled)
