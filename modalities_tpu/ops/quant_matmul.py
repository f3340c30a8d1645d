"""Fused dequant-matmul dispatch (serving's weight-only-quantized layers).

By `ops/tiers.py`'s one rule: the Pallas kernel (ops/pallas/quant_matmul.py) on a TPU, the
pure-jnp dequant expression everywhere else (CPU tier-1 sees it; its expression is
bitwise-identical to the kernel's by construction).

Block sizes: the tuning table (`ops/pallas/autotune.blocks`, `quant_matmul|m{bucket}|{dtype}`),
else 128x128.
"""

from __future__ import annotations

from modalities_tpu.ops import tiers
from modalities_tpu.ops.pallas import autotune
from modalities_tpu.ops.pallas.quant_matmul import (
    DEFAULT_BLOCK_M,
    DEFAULT_BLOCK_N,
    quant_matmul,
    reference_quant_matmul,
)


def resolve_quant_matmul_blocks(m: int, dtype) -> tuple[int, int]:
    return autotune.blocks("quant_matmul", f"m{autotune.shape_bucket(m)}", dtype, block_m=DEFAULT_BLOCK_M, block_n=DEFAULT_BLOCK_N)


def quant_matmul_or_fallback(x, wq, scale, *, interpret: bool = False):
    """`(x [M,K] @ wq [K,N] quantized) * scale [N]`.

    Whatever the kernel raises is raised, on a TPU as in interpret mode (tests):
    the jnp dequant expression is the form off a TPU, not a net under the kernel."""
    if not (interpret or tiers.kernels_run()):
        return reference_quant_matmul(x, wq, scale)
    block_m, block_n = resolve_quant_matmul_blocks(x.shape[0], x.dtype)
    return quant_matmul(x, wq, scale, block_m=block_m, block_n=block_n, interpret=tiers.interpret(interpret))
