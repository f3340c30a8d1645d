"""Fused RMSNorm dispatch: the Pallas kernel (ops/pallas/fused_rmsnorm.py) per shard of the rows.

Whether a model's RMS norms take it is `ops/tiers.py`'s one rule, asked where the norm is built
(`models/components/layer_norms.build_norm`: on a TPU; elsewhere the reference linen norms, so
CPU tier-1 numerics are the seed's). A call that gets here runs the kernel, interpreted off a TPU.

Block size: the tuning table (`ops/pallas/autotune.blocks`), else 256 rows; fewer where the width would not leave them room in VMEM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from modalities_tpu.ops import tiers
from modalities_tpu.ops.pallas import autotune

DEFAULT_BLOCK_ROWS = 256


# what the backward kernel holds in VMEM for an element of its block of rows (x, dy and dx twice over, the float32 working copies):
# the compiler asked 23.99 MB of scoped VMEM for 256 rows of 4096 (PR 52), against the 16 MiB a kernel may have
VMEM_BYTES_AN_ELEMENT, SCOPED_VMEM_BYTES = 24, 16 * 2**20


def resolve_rmsnorm_block_rows(n_embd: int, dtype) -> int:
    """The table's rows a block, halved until the backward kernel's block fits its scoped VMEM: 256 up to a width of 2730, 128 at 4096."""
    rows = autotune.blocks("fused_rmsnorm", f"e{autotune.shape_bucket(n_embd)}", dtype, block_rows=DEFAULT_BLOCK_ROWS)[0]
    while rows > 8 and rows * n_embd * VMEM_BYTES_AN_ELEMENT > SCOPED_VMEM_BYTES:
        rows //= 2
    return rows


# how the rows of a norm's input lie on the mesh, by rank: the residual stream
# [B, S, E], its rows "seq_sp" (over cp then tp: as the model leaves them between a block's products), and the
# per-head q/k of QK-norm [B, S, H, D], inside a mixer: rows over cp alone, heads over tp; anything else over its batch only
_ROW_AXES = {3: ("batch", "seq_sp", None), 4: ("batch", "seq", "heads", None)}


def rms_norm_or_fallback(x, scale=None, bias=None, *, eps: float = 1e-6, interpret: bool = False, zero_centered: bool = False):
    """Single-HBM-round-trip RMSNorm. Whatever the kernel raises is raised, on a
    TPU as in interpret mode (tests): there is no reference tier behind it. Under
    a mesh the kernel runs per shard of the rows (parallel/sharding.per_shard).
    `zero_centered`: `scale` is `w` of `y = norm(x) * (1 + w)`; the one is added here, where the
    scale is read, and the kernel (whose `dscale` is `dw`) is the same."""
    from modalities_tpu.ops.pallas.fused_rmsnorm import fused_rms_norm
    from modalities_tpu.parallel.sharding import per_shard

    kernel = functools.partial(
        fused_rms_norm,
        eps=eps,
        block_rows=resolve_rmsnorm_block_rows(x.shape[-1], x.dtype),
        interpret=tiers.interpret(interpret),
    )
    # the identity params the kernel would make for itself, made here so that
    # every shard is handed the same three operands
    scale = jnp.ones((x.shape[-1],), jnp.float32) if scale is None else scale + 1.0 if zero_centered else scale
    bias = jnp.zeros((x.shape[-1],), jnp.float32) if bias is None else bias
    x_axes = _ROW_AXES.get(x.ndim, ("batch",) + (None,) * (x.ndim - 1))
    return per_shard(
        lambda _axes, x, scale, bias: kernel(x, scale, bias), (x_axes, (None,), (None,)), x_axes
    )(x, scale, bias)


def reference_rms_norm(x, scale=None, bias=None, *, eps: float = 1e-6, zero_centered: bool = False):
    """Same math as layer_norms.RMSNormWithBias: the parity tests' oracle. `zero_centered`: times `1 + scale`."""
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt((x32 * x32).mean(axis=-1, keepdims=True) + eps)
    if scale is not None:
        y = y * (scale + 1.0 if zero_centered else scale)
    if bias is not None:
        y = y + bias
    return y.astype(x.dtype)
