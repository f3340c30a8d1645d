"""The Mamba-2 mixer (state-space duality, Dao and Gu, arXiv 2405.21060) as `model_type: granitemoehybrid` runs it
(`transformers`' `GraniteMoeHybridMambaLayer` with `GraniteMoeHybridRMSNormGated`): the sixth mixer a block's seat can
hold, under the module name `ssd` (a layer whose published type is `mamba`).

`H` heads of `P` channels (`d_in = H P`) each keep a state `[P, N]` decayed by ONE scalar a head and a token; `B` and
`C` `[N]` are the same for every head (`mamba_n_groups` 1) and come out of the same convolution as `x`; `dt` comes out
of the input projection itself. On the block's normed input `h [S, E]`:

    (z, xBC, dt) = split(h W_in) into d_in, d_in + 2 N, H                 no bias
    xBC          = silu(conv(xBC) + b_conv)                               depthwise, causal, `mamba_d_conv` taps, zeros before t = 0
    (x, B, C)    = split(xBC) into d_in, N, N;  x read as [S, H, P]
    dt           = softplus(dt + dt_bias)                                 float32; no clamp (`time_step_limit` (0, inf))
    a            = -exp(A_log) * dt                                       float32, the log of the decay, <= 0
    y            = the recurrence over the row (`ops/ssd.py`: chunked, the state from zero) + D x        D [H], a head's skip
    g            = flatten(y) * silu(z)
    out          = (g / sqrt(mean(g^2 over d_in) + eps) * w_g) W_out      w_g [d_in] from 1: the norm comes AFTER the gate

What is not Mamba-1's (`ssm.py`): no `x_proj`, no `dt_proj`, no norms on dt, B and C; a decay a head, not a channel and
a state index; a state eight times as wide; the norm behind the gate.

**The share.** The mixer is told how many of the published heads it holds (`heads_held`, default all): `W_in` then has
the columns of the held heads' z, x and dt and ALL of B and C (every chip computes the same B and C), the convolution,
`A_log`, `D`, `dt_bias` and `w_g` the held heads' entries, `W_out` their rows; what the absent heads would add to the
residual is the other chips' to add (the exchange is not written: a `tp` axis is refused by name). One thing a share
changes: the gated norm's mean square runs over the channels held, where a deployment would all-reduce one scalar a token.

What this mixer does not have: a cache (serving would keep the convolution's last taps and the `[H, P, N]` state of
every sequence and layer), a cp axis (the state and the convolution cross a shard's edge), a reset of the state at a
document's edge. The first two are refused by name (`gpt2_model.py`).

Counted in a pass (no gradient): the mean of `exp(a)` over tokens and heads (how fast a state forgets). The block
hands it up beside the expert layer's row where it has one.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Annotated, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from pydantic import BaseModel, ConfigDict, Field, model_validator

from modalities_tpu.telemetry import scopes

COUNTERS = ("ssd_decay_mean",)  # a layer's


class SSDConfig(BaseModel):
    """The `ssd_config` block of a `model.gpt2` config; keys as `granitemoehybrid` publishes them, and the one that says
    how many of the heads this mixer holds."""

    model_config = ConfigDict(extra="forbid")

    mamba_n_heads: Annotated[int, Field(strict=True, ge=1)]
    mamba_d_head: Annotated[int, Field(strict=True, ge=1)]
    mamba_d_state: Annotated[int, Field(strict=True, ge=1)]
    mamba_n_groups: Annotated[int, Field(strict=True, ge=1)] = 1
    mamba_d_conv: Annotated[int, Field(strict=True, ge=1)] = 4
    mamba_conv_bias: bool = True
    mamba_chunk_size: Annotated[int, Field(strict=True, ge=1)] = 256
    heads_held: Optional[Annotated[int, Field(strict=True, ge=1)]] = None  # default: all of them

    @model_validator(mode="after")
    def refuse_what_is_not_written(self) -> "SSDConfig":
        if self.mamba_n_groups != 1:
            raise ValueError("ssd_config.mamba_n_groups: B and C shared by all heads (1 group) is written; several groups of heads "
                             "with a B and C each are not, and are not guessed")
        if self.heads_held is not None and self.heads_held > self.mamba_n_heads:
            raise ValueError("ssd_config.heads_held exceeds mamba_n_heads")
        return self


@dataclass(frozen=True)
class SSDSpec:
    heads: int  # the published count
    head_dim: int
    state: int
    taps: int
    conv_bias: bool
    chunk: int
    heads_held: int

    @classmethod
    def from_config(cls, config: "SSDConfig | dict") -> "SSDSpec":
        config = SSDConfig(**config) if isinstance(config, dict) else config
        return cls(config.mamba_n_heads, config.mamba_d_head, config.mamba_d_state, config.mamba_d_conv, config.mamba_conv_bias,
                   config.mamba_chunk_size, config.mamba_n_heads if config.heads_held is None else config.heads_held)

    @property
    def inner(self) -> int:
        """The inner width held: the held heads' channels."""
        return self.heads_held * self.head_dim

    @property
    def conv_width(self) -> int:
        """The channels the convolution runs over: x of the held heads, B and C."""
        return self.inner + 2 * self.state

    @property
    def in_width(self) -> int:
        return 2 * self.inner + 2 * self.state + self.heads_held


def _a_log_init(key, shape, dtype=jnp.float32):
    """`log(u)`, `u` uniform on [1, 16] (Mamba-2's own draw)."""
    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


class Mamba2Mixer(nn.Module):
    """The mixer; sits in a block's seat under the name `ssd`. x: the block's normed input `[B, S, E]`.
    Returns `(out [B, S, E], float32 [1])`: the second is what the step counts of this layer (`COUNTERS`)."""

    spec: object  # GPT2ModelSpec (its `ssd` is the SSDSpec)
    deterministic: bool = True

    @nn.compact
    def __call__(self, x):
        from modalities_tpu.models.gpt2.ssm import _dt_bias_init, _uniform
        from modalities_tpu.ops import ssd as ops
        from modalities_tpu.ops.selective_scan import causal_depthwise_conv
        from modalities_tpu.telemetry import get_active_telemetry

        spec, ssd = self.spec, self.spec.ssd
        h, p, n, inner, f32 = ssd.heads_held, ssd.head_dim, ssd.state, ssd.inner, jnp.float32
        b, s, _ = x.shape
        get_active_telemetry().emit_event_once("ssd_plan", {  # runs while tracing: once per shape, nothing per step
            "tokens": b * s, "sequence": s, "heads": ssd.heads, "heads_held": h, "head_dim": p, "state": n, "chunk": ssd.chunk,
            "chunks": -(-s // ssd.chunk), "conv_taps": ssd.taps, "conv_width": ssd.conv_width, "in_width": ssd.in_width,
            "state_bytes_a_layer": b * ops.state_bytes(s, h, p, n, ssd.chunk), "form": ops.FORM, "kernels": (),
        })
        param_dtype = jnp.dtype(spec.param_dtype)
        vector = lambda name, init, size: self.param(name, nn.with_logical_partitioning(init, ("mlp",)), (size,), f32)  # noqa: E731

        with jax.named_scope(scopes.SSD_IN_PROJ):
            u = nn.Dense(ssd.in_width, use_bias=False, name="in_proj", dtype=x.dtype, param_dtype=param_dtype,
                         kernel_init=nn.with_logical_partitioning(nn.initializers.normal(0.02), ("embed", "mlp")))(x)
        z, xbc, dt = u[..., :inner], u[..., inner: inner + ssd.conv_width], u[..., inner + ssd.conv_width:]
        with jax.named_scope(scopes.SSD_CONV):
            bound = ssd.taps ** -0.5  # torch's default for a depthwise Conv1d, as the other mixers': the fan-in is the taps
            taps = self.param("conv_kernel", nn.with_logical_partitioning(_uniform(bound), (None, "mlp")), (ssd.taps, ssd.conv_width), f32)
            bias = vector("conv_bias", _uniform(bound), ssd.conv_width) if ssd.conv_bias else None
            xbc = nn.silu(causal_depthwise_conv(xbc, taps, bias))
        xs, bm, cm = xbc[..., :inner].reshape(b, s, h, p), xbc[..., inner: inner + n], xbc[..., inner + n:]
        with jax.named_scope(scopes.SSD_SCAN):
            a_log, dt_bias = vector("A_log", _a_log_init, h), vector("dt_bias", _dt_bias_init, h)
            dt = jax.nn.softplus(dt.astype(f32) + dt_bias)
            a = -jnp.exp(a_log) * dt
            counters = jax.lax.stop_gradient(jnp.mean(jnp.exp(a))[None])
            y = ops.ssd_chunked(xs, dt, a, bm, cm, chunk=ssd.chunk)
        with jax.named_scope(scopes.SSD_GATE):
            skip, w_g = vector("D", nn.initializers.ones, h), vector("norm_scale", nn.initializers.ones, inner)
            y = y.astype(f32) + skip[:, None] * xs.astype(f32)
            self.sow("intermediates", "y", y)  # binds nothing unless a caller asks for the collection (the test of the share)
            g = y.reshape(b, s, inner) * nn.silu(z.astype(f32))
            g = (g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + spec.attn_norm.eps) * w_g).astype(x.dtype)
        with jax.named_scope(scopes.SSD_OUT_PROJ):
            out = nn.Dense(spec.n_embd, use_bias=False, name="out_proj", dtype=x.dtype, param_dtype=param_dtype,
                           kernel_init=nn.with_logical_partitioning(nn.initializers.normal(0.02), ("mlp", "embed")))(g)
        out = nn.Dropout(rate=spec.dropout)(out, deterministic=self.deterministic or spec.dropout == 0.0)
        return out, counters
