"""Attention kernel dispatch — the framework's `dao_flash` tier
(reference: flash-attn CUDA kernels used via gpt2_model.py:22-25, :643-655).

On a TPU the custom Pallas flash kernel (ops/pallas/flash_attention.py) runs, and
whatever it raises is raised: there is no second tier behind it. On CPU (tests) the
XLA-fused SDPA path is used so numerics stay exact.
"""

from __future__ import annotations

import functools

import jax

from modalities_tpu.ops.tiers import on_tpu

_Q_AXES = ("batch", None, "heads", None)
_KV_AXES = ("batch", None, "kv_heads", None)


def flash_attention_or_fallback(q, k, v, causal: bool = True, sm_scale: float | None = None):
    """q: [B,S,Hq,D], k/v: [B,S,Hkv,D] -> [B,S,Hq,D].

    Block sizes are tunable via MODALITIES_TPU_FLASH_BLOCK_Q / _BLOCK_K. Default
    1024 (stepped down automatically for shorter sequences): on a v5e, growing the
    blocks 128 -> 1024 took a 1.3B GPT2 train step from 0.31 to 0.57 MFU — grid
    overhead dominates the kernel at MXU-tile-sized blocks; 1024x1024 fp32 score
    tiles still fit VMEM comfortably (4 MB).

    Under a mesh the kernel runs per shard, split over batch and heads
    (parallel/sharding.per_shard)."""
    if not on_tpu():
        return jax.nn.dot_product_attention(q, k, v, is_causal=causal, scale=sm_scale)
    from modalities_tpu.ops.pallas.flash_attention import env_flash_blocks, pallas_flash_attention
    from modalities_tpu.parallel.sharding import per_shard

    block_q, block_k = env_flash_blocks(q.shape[1], k.shape[1], dtype=q.dtype)
    kernel = functools.partial(
        pallas_flash_attention, causal=causal, sm_scale=sm_scale, block_q=block_q, block_k=block_k
    )
    return per_shard(
        lambda _axes, q, k, v: kernel(q, k, v), (_Q_AXES, _KV_AXES, _KV_AXES), _Q_AXES
    )(q, k, v)
