"""Fused dequant-matmul dispatch — same tier pattern as ops/rmsnorm.py.

Tier resolution via `MODALITIES_TPU_QUANT_MATMUL`: "auto" (default) uses the
Pallas kernel on TPU and the pure-jnp dequant fallback everywhere else (CPU
tier-1 sees the fallback, whose expression is bitwise-identical by
construction); "on" forces the kernel (interpret mode off-TPU — the parity
tests' path); "off" pins the fallback. Malformed values raise.

Block sizes: `MODALITIES_TPU_QUANT_MM_BLOCK_M` / `_BLOCK_N` > autotune table
(`quant_matmul|m{bucket}|{dtype}`) > 128x128.
"""

from __future__ import annotations

import os

import jax.numpy as jnp

from modalities_tpu.ops.pallas.quant_matmul import (
    DEFAULT_BLOCK_M,
    DEFAULT_BLOCK_N,
    quant_matmul,
    reference_quant_matmul,
)
from modalities_tpu.ops.tiers import KernelTier, on_tpu, resolve_tier


def quant_matmul_tier(spec_setting=None) -> KernelTier:
    return resolve_tier("MODALITIES_TPU_QUANT_MATMUL", spec_setting)


def resolve_quant_matmul_blocks(m: int, dtype) -> tuple[int, int]:
    env_m = os.environ.get("MODALITIES_TPU_QUANT_MM_BLOCK_M")
    env_n = os.environ.get("MODALITIES_TPU_QUANT_MM_BLOCK_N")
    if env_m is not None or env_n is not None:
        # malformed must raise, never demote
        return (
            int(env_m) if env_m is not None else DEFAULT_BLOCK_M,
            int(env_n) if env_n is not None else DEFAULT_BLOCK_N,
        )
    from modalities_tpu.ops.pallas import autotune

    hit = autotune.lookup("quant_matmul", f"m{autotune.shape_bucket(m)}", jnp.dtype(dtype).name)
    if hit:
        return (
            int(hit.get("block_m", DEFAULT_BLOCK_M)),
            int(hit.get("block_n", DEFAULT_BLOCK_N)),
        )
    return DEFAULT_BLOCK_M, DEFAULT_BLOCK_N


def quant_matmul_or_fallback(x, wq, scale, *, tier: KernelTier | None = None, interpret: bool = False):
    """`(x [M,K] @ wq [K,N] quantized) * scale [N]` through the tier ladder.

    Whatever the kernel raises is raised, on a TPU as in interpret mode (tests):
    the jnp dequant expression is the `off` tier, not a net under the kernel."""
    if tier is None:
        tier = quant_matmul_tier()
    if not tier.enabled and not interpret:
        return reference_quant_matmul(x, wq, scale)
    block_m, block_n = resolve_quant_matmul_blocks(x.shape[0], x.dtype)
    return quant_matmul(
        x, wq, scale, block_m=block_m, block_n=block_n,
        interpret=interpret or tier.interpret or not on_tpu(),
    )
