"""The Mamba-1 sequence mixer as `model_type: jamba` computes it (Hugging Face's
`modeling_jamba.py`): the second kind of mixer a block's seat can hold, beside attention.

On `u [B, S, d]`, with `d_inner = expand * d`, state size `N`, rank `R` and a
convolution of `K` taps:

    (x, z)    = split(u @ in_proj)                     in_proj [d, 2 d_inner]
    x         = silu(causal_depthwise_conv(x))          conv [K, d_inner] with bias
    (r, B, C) = split(x @ x_proj) into R, N, N          x_proj [d_inner, R + 2 N]
    r, B, C   = dt_norm(r), b_norm(B), c_norm(C)        RMS norms with learned scales (jamba's own)
    dt        = softplus(r @ dt_proj + dt_bias)         dt_proj [R, d_inner]
    y         = selective_scan(x, dt, -exp(A_log), B, C) + D * x
    out       = (y * silu(z)) @ out_proj                out_proj [d_inner, d]

The three large kernels (`in_proj`, `x_proj`, `out_proj`) are kept in the model's
parameter dtype (bfloat16 under the recipes' mixed precision). `A_log`, `D`, the biases,
the norm scales and the scan with its state are float32, and so are the two small
kernels whose published initial values are large, the convolution's (up to 0.5) and
`dt_proj`'s (up to R**-0.5): a bfloat16 weight of that size is further from its
neighbours (2e-3 and 5e-4) than one AdamW step at the recipes' learning rates moves it,
so in bfloat16, without a float32 master copy, they would never train. Initial values are Mamba's: `A_log = log(1..N)` in every channel, `D = 1`,
`dt_proj` uniform in `+-R**-0.5`, the `dt` bias the inverse softplus of a log-uniform
draw in `[1e-3, 1e-1]` — a scan whose decay is all 0 or all 1 would test nothing.

The state runs over the whole row: across document boundaries of a packed row, as
attention does in this repo (there are no `segment_ids`).
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Annotated, Literal

import flax.linen as nn
import jax
import jax.numpy as jnp
from pydantic import BaseModel, Field

from modalities_tpu.telemetry import scopes

DT_MIN, DT_MAX, DT_FLOOR = 1e-3, 1e-1, 1e-4


class SSMConfig(BaseModel):
    """The `ssm_config` block of a `model.gpt2` config; keys as Mamba publishes them."""

    d_state: Annotated[int, Field(strict=True, ge=1)] = 16
    d_conv: Annotated[int, Field(strict=True, ge=1)] = 4
    expand: Annotated[int, Field(strict=True, ge=1)] = 2
    dt_rank: Annotated[int, Field(strict=True, ge=1)] | Literal["auto"] = "auto"  # auto: ceil(n_embd / 16)
    conv_bias: bool = True
    norm_eps: Annotated[float, Field(gt=0.0)] = 1e-6  # of the three norms on dt, B and C


@dataclass(frozen=True)
class SSMSpec:
    d_inner: int
    d_state: int
    d_conv: int
    dt_rank: int
    conv_bias: bool
    norm_eps: float

    @classmethod
    def from_config(cls, config: SSMConfig | dict, n_embd: int) -> "SSMSpec":
        if isinstance(config, dict):
            config = SSMConfig(**config)
        rank = math.ceil(n_embd / 16) if config.dt_rank == "auto" else config.dt_rank
        return cls(d_inner=config.expand * n_embd, d_state=config.d_state, d_conv=config.d_conv, dt_rank=rank,
                   conv_bias=config.conv_bias, norm_eps=config.norm_eps)


def layer_kinds(n_layer: int, attn_layer_period: int | None, attn_layer_offset: int) -> tuple[str, ...]:
    """The mixer of every layer from the two keys jamba publishes: attention where
    `i % period == offset`, the state-space mixer elsewhere; no period, attention everywhere."""
    if attn_layer_period is None:
        return ("attn",) * n_layer
    return tuple("attn" if i % attn_layer_period == attn_layer_offset else "ssm" for i in range(n_layer))


def layer_runs(kinds: tuple[str, ...]) -> tuple[tuple[str, int], ...]:
    """Runs of equal kind, in order: `(kind, length)`."""
    runs: list[tuple[str, int]] = []
    for kind in kinds:
        if runs and runs[-1][0] == kind:
            runs[-1] = (kind, runs[-1][1] + 1)
        else:
            runs.append((kind, 1))
    return tuple(runs)


def _dt_bias_init(key, shape, dtype=jnp.float32):
    dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32) * (math.log(DT_MAX) - math.log(DT_MIN)) + math.log(DT_MIN))
    dt = jnp.maximum(dt, DT_FLOOR)
    return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)  # softplus's inverse


def _uniform(bound: float):
    return lambda key, shape, dtype=jnp.float32: jax.random.uniform(key, shape, dtype, -bound, bound)


def _a_log_init(key, shape, dtype=jnp.float32):
    return jnp.broadcast_to(jnp.log(jnp.arange(1, shape[1] + 1, dtype=jnp.float32)), shape).astype(dtype)


class _ScaleNorm(nn.Module):
    """RMS norm over the last axis with a learned float32 scale, computed in float32."""

    epsilon: float

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.with_logical_partitioning(nn.initializers.ones, (None,)), (x.shape[-1],), jnp.float32)
        x = x.astype(jnp.float32)
        return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + self.epsilon) * scale


class _CausalConv(nn.Module):
    """Causal depthwise convolution along the sequence; kernel `[K, d_inner]` (the last
    tap weighs the current step) and bias, both float32."""

    taps: int
    use_bias: bool

    @nn.compact
    def __call__(self, x):
        from modalities_tpu.ops.selective_scan import causal_depthwise_conv

        bound = self.taps ** -0.5  # torch's default for a depthwise Conv1d: the fan-in is the taps
        kernel = self.param("kernel", nn.with_logical_partitioning(_uniform(bound), (None, "mlp")),
                            (self.taps, x.shape[-1]), jnp.float32)
        bias = None
        if self.use_bias:
            bias = self.param("bias", nn.with_logical_partitioning(_uniform(bound), ("mlp",)), (x.shape[-1],), jnp.float32)
        return causal_depthwise_conv(x, kernel, bias)


class _DtProj(nn.Module):
    """`softplus(r @ kernel + bias)`: the step size of every channel, float32. Kernel and
    bias are kept in float32; the matmul runs in the block's compute dtype."""

    features: int

    @nn.compact
    def __call__(self, r):
        kernel = self.param("kernel", nn.with_logical_partitioning(_uniform(r.shape[-1] ** -0.5), (None, "mlp")),
                            (r.shape[-1], self.features), jnp.float32)
        bias = self.param("bias", nn.with_logical_partitioning(_dt_bias_init, ("mlp",)), (self.features,), jnp.float32)
        return jax.nn.softplus(jnp.dot(r, kernel.astype(r.dtype)).astype(jnp.float32) + bias)


class MambaMixer(nn.Module):
    """The state-space mixer; sits in a block's mixer seat under the name `ssm`."""

    spec: object  # GPT2ModelSpec (its `ssm` is the SSMSpec)

    @nn.compact
    def __call__(self, u):
        from modalities_tpu.models.gpt2.gpt2_model import with_logical_constraint
        from modalities_tpu.ops import selective_scan as scan_ops

        spec, ssm = self.spec, self.spec.ssm

        def dense(features, name, axes):
            return nn.Dense(features, use_bias=False, name=name, dtype=u.dtype, param_dtype=jnp.dtype(spec.param_dtype),
                            kernel_init=nn.with_logical_partitioning(nn.initializers.normal(0.02), axes))

        xz = dense(2 * ssm.d_inner, "in_proj", ("embed", "mlp"))(u)
        xz = with_logical_constraint(xz, ("batch", "seq", "mlp"), spec)
        x, z = jnp.split(xz, 2, axis=-1)
        x = nn.silu(_CausalConv(ssm.d_conv, ssm.conv_bias, name=scopes.SSM_CONV)(x))

        low = dense(ssm.dt_rank + 2 * ssm.d_state, "x_proj", ("mlp", None))(x)
        r, b, c = jnp.split(low, [ssm.dt_rank, ssm.dt_rank + ssm.d_state], axis=-1)
        r = _ScaleNorm(ssm.norm_eps, name="dt_norm")(r)
        b = _ScaleNorm(ssm.norm_eps, name="b_norm")(b)
        c = _ScaleNorm(ssm.norm_eps, name="c_norm")(c)
        dt = _DtProj(ssm.d_inner, name="dt_proj")(r.astype(u.dtype))
        a_log = self.param("A_log", nn.with_logical_partitioning(_a_log_init, ("mlp", None)), (ssm.d_inner, ssm.d_state), jnp.float32)
        skip = self.param("D", nn.with_logical_partitioning(nn.initializers.ones, ("mlp",)), (ssm.d_inner,), jnp.float32)
        a = -jnp.exp(a_log)

        # the recurrence and its backward, nothing else; says `ssm_scan_plan` once per shape while tracing
        with jax.named_scope(scopes.SSM_SCAN):
            y, _ = scan_ops.selective_scan(x, dt, a, b, c, chunk=scan_ops.CHUNK)
        with jax.named_scope(scopes.SSM_GATE):
            y = ((y + skip * x.astype(jnp.float32)) * nn.silu(z.astype(jnp.float32))).astype(u.dtype)
        y = with_logical_constraint(y, ("batch", "seq", "mlp"), spec)
        return dense(spec.n_embd, "out_proj", ("mlp", "embed"))(y)
