"""The gated delta rule's mixer (Gated DeltaNet, arXiv 2412.06464) as `model_type: qwen3_next` runs it
(`transformers`' `Qwen3NextGatedDeltaNet`): the fifth mixer a block's seat can hold, under the module name
`gdn` (a layer whose published type is `linear_attention`).

A value head keeps a matrix `[d_k, d_v]` for a state, decayed by a scalar a head and a token and corrected by
the delta rule; `n_v` value heads read `n_k` key heads, `r = n_v / n_k` to one. On the block's normed input
`h [S, E]`:

    u  = h W_qkvz                 [S, n_k, 2 d_k + 2 r d_v]   no bias; a key head's group is [q d_k | k d_k | v r x d_v | z r x d_v]
    ba = h W_ba                   [S, n_k, 2 r]               a group is [b r | a r]
    c  = silu(conv(concat(q, k, v)))      q, k, v flattened over heads and concatenated in that order; depthwise, causal,
                                          `linear_conv_kernel_dim` taps, zeros before t = 0, no bias; then split back
    beta = sigmoid(b)             g = -exp(A_log) * softplus(a + dt_bias)       [S, n_v], float32; g <= 0 is the log of the decay
    q = q / sqrt(sum(q^2) + 1e-6) / sqrt(d_k)      k = k / sqrt(sum(k^2) + 1e-6)      per head and position, float32
    o  = the gated delta rule over the row (`ops/gated_delta_rule.py`: chunked, the state from zero)
    y  = o / sqrt(mean(o^2 over d_v) + eps) * w_n * silu(z)       w_n [d_v] from 1, one for all heads; float32
    out = flatten(y) W_o          [S, n_v d_v] -> [S, E]

What this mixer does not have: a cache (serving would keep the convolution's last taps and the `[n_v, d_k, d_v]` state
of every sequence and layer), a cp axis (the state and the convolution cross a shard's edge), a reset of the
state at a document's edge (the state runs across the documents packed into a row, as attention does here). The
first two are refused by name (`gpt2_model.py`).

Counted in a pass (no gradient): the mean of `exp(g)` over tokens and heads (how fast a state forgets) and the
mean of `beta`. The block hands them up beside the expert layer's row where it has one.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Annotated

import flax.linen as nn
import jax
import jax.numpy as jnp
from pydantic import BaseModel, ConfigDict, Field, model_validator

from modalities_tpu.telemetry import scopes

COUNTERS = ("gdn_decay_mean", "gdn_beta_mean")  # a layer's, in this order


class GDNConfig(BaseModel):
    """The `gdn_config` block of a `model.gpt2` config; keys as `qwen3_next` publishes them."""

    model_config = ConfigDict(extra="forbid")

    linear_num_key_heads: Annotated[int, Field(strict=True, ge=1)]
    linear_num_value_heads: Annotated[int, Field(strict=True, ge=1)]
    linear_key_head_dim: Annotated[int, Field(strict=True, ge=1)]
    linear_value_head_dim: Annotated[int, Field(strict=True, ge=1)]
    linear_conv_kernel_dim: Annotated[int, Field(strict=True, ge=1)] = 4

    @model_validator(mode="after")
    def check_heads(self) -> "GDNConfig":
        if self.linear_num_value_heads % self.linear_num_key_heads:
            raise ValueError("gdn_config: linear_num_value_heads must be a multiple of linear_num_key_heads (value head j reads key head j // r)")
        return self


@dataclass(frozen=True)
class GDNSpec:
    key_heads: int
    value_heads: int
    key_dim: int
    value_dim: int
    taps: int = 4

    @classmethod
    def from_config(cls, config: "GDNConfig | dict") -> "GDNSpec":
        config = GDNConfig(**config) if isinstance(config, dict) else config
        return cls(config.linear_num_key_heads, config.linear_num_value_heads, config.linear_key_head_dim,
                   config.linear_value_head_dim, config.linear_conv_kernel_dim)

    @property
    def conv_width(self) -> int:
        """The channels the convolution runs over: q, k and v flattened over their heads."""
        return 2 * self.key_heads * self.key_dim + self.value_heads * self.value_dim


def l2_normalised(x, scale: float = 1.0):
    """`scale * x / sqrt(sum(x^2) + 1e-6)` over the last axis, float32 (the source's `l2norm`, eps inside the root)."""
    x = x.astype(jnp.float32)
    return x * (scale * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6))


def _a_log_init(key, shape, dtype=jnp.float32):
    """`log(a)`, `a` uniform on [1, 16] (the source draws on (0, 16); a seeded draw must not meet 0)."""
    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


class GatedDeltaNet(nn.Module):
    """The mixer; sits in a block's seat under the name `gdn`. x: the block's normed input `[B, S, E]`.
    Returns `(out [B, S, E], float32 [2])`: the second is what the step counts of this layer (`COUNTERS`)."""

    spec: object  # GPT2ModelSpec (its `gdn` is the GDNSpec)
    deterministic: bool = True

    @nn.compact
    def __call__(self, x):
        from modalities_tpu.models.gpt2.ssm import _uniform
        from modalities_tpu.ops import gated_delta_rule as rule
        from modalities_tpu.ops import head_norm
        from modalities_tpu.ops.selective_scan import causal_depthwise_conv
        from modalities_tpu.telemetry import get_active_telemetry

        spec, gdn = self.spec, self.spec.gdn
        nk, nv, dk, dv = gdn.key_heads, gdn.value_heads, gdn.key_dim, gdn.value_dim
        r, f32 = nv // nk, jnp.float32
        b, s, _ = x.shape
        kernels = rule.walk_kernels(r, rule.groups_of(s)[1], rule.CHUNK, dk, dv, x.dtype)  # of the walk over a group's chunks: the rest of the rule is the plain form
        # of the two norms over a head's channels (`ops/head_norm.py`): none off a TPU and at heads that are no whole lane tiles, where the plain forms below run
        qk_norm, out_norm = head_norm.kernels("l2", (b, s, nk, dk), x.dtype), head_norm.kernels("gated", (b, s, nv, dv), x.dtype)
        # where the block is rematerialized its recomputed forward runs the rule once more, unless it kept the rule's o and group states
        # (the trace that initializes the parameters is of no step: it says what a block that is not rematerialized says)
        forwards, recomputed = rule.RECOMPUTED[spec.remat_keep_rule] if spec.remat_variant and not self.is_initializing() else (2, "")
        get_active_telemetry().emit_event_once("gdn_plan", {  # runs while tracing: once per shape, nothing per step
            "tokens": b * s, "sequence": s, "chunk": rule.CHUNK, "chunks": -(-s // rule.CHUNK), "key_heads": nk, "value_heads": nv,
            "key_dim": dk, "value_dim": dv, "conv_taps": gdn.taps, "conv_width": gdn.conv_width,
            "state_bytes_a_layer": b * rule.state_bytes(s, nv, dk, dv), "inverse": rule.HOW_T, "backward": rule.BACKWARD[bool(kernels)] + recomputed, "kernels": kernels,
            "norm_kernels": qk_norm + out_norm, "forwards_a_step": forwards,
        })
        param_dtype = jnp.dtype(spec.param_dtype)

        def dense(features, name, axes):
            return nn.DenseGeneral(features=features, use_bias=False, name=name, dtype=x.dtype, param_dtype=param_dtype,
                                   kernel_init=nn.with_logical_partitioning(nn.initializers.normal(0.02), axes))

        with jax.named_scope(scopes.GDN_IN_PROJ):
            u = dense((nk, 2 * dk + 2 * r * dv), "qkvz", ("embed", "heads", None))(x)
            ba = dense((nk, 2 * r), "ba", ("embed", "heads", None))(x)
        q, k = u[..., :dk], u[..., dk: 2 * dk]
        v = u[..., 2 * dk: 2 * dk + r * dv].reshape(b, s, nv, dv)
        z = u[..., 2 * dk + r * dv:].reshape(b, s, nv, dv)
        with jax.named_scope(scopes.GDN_CONV):
            # torch's default for a depthwise Conv1d, as the state-space mixer's: the fan-in is the taps
            taps = self.param("conv_kernel", nn.with_logical_partitioning(_uniform(gdn.taps ** -0.5), (None, None)),
                              (gdn.taps, gdn.conv_width), f32)
            # q, k and v flattened over their heads and concatenated in that order are the taps' channels; a depthwise
            # convolution of the concatenation is the three parts' own, so nothing is concatenated and split again
            # (two copies of [B, S, conv_width] less for the backward to keep)
            conv = lambda part, first: nn.silu(causal_depthwise_conv(  # noqa: E731
                part.reshape(b, s, -1), taps[:, first: first + part.shape[2] * part.shape[3]])).reshape(part.shape)
            q, k, v = conv(q, 0), conv(k, nk * dk), conv(v, 2 * nk * dk)
        with jax.named_scope(scopes.GDN_GATES):
            a_log = self.param("A_log", nn.with_logical_partitioning(_a_log_init, (None,)), (nv,), f32)
            dt_bias = self.param("dt_bias", nn.with_logical_partitioning(nn.initializers.ones, (None,)), (nv,), f32)
            beta = jax.nn.sigmoid(ba[..., :r].astype(f32)).reshape(b, s, nv)
            g = -jnp.exp(a_log) * jax.nn.softplus(ba[..., r:].astype(f32).reshape(b, s, nv) + dt_bias)
            counters = jax.lax.stop_gradient(jnp.stack([jnp.mean(jnp.exp(g)), jnp.mean(beta)]))
        with jax.named_scope(scopes.GDN_QK_NORM):
            if qk_norm:
                q, k = head_norm.head_l2_norm(q, dk ** -0.5), head_norm.head_l2_norm(k)
            else:
                q, k = l2_normalised(q, dk ** -0.5).astype(x.dtype), l2_normalised(k).astype(x.dtype)
        with jax.named_scope(scopes.GDN_RULE):
            o = rule.gated_delta_rule(q, k, v, g, beta)
        with jax.named_scope(scopes.GDN_OUT_NORM):
            w_n = self.param("out_norm_scale", nn.with_logical_partitioning(nn.initializers.ones, (None,)), (dv,), f32)
            if out_norm:
                y = head_norm.gated_head_rms_norm(o, z, w_n, eps=spec.attn_norm.eps)
            else:
                o = o.astype(f32)
                y = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + spec.attn_norm.eps) * w_n * nn.silu(z.astype(f32))
                y = y.astype(x.dtype)
        with jax.named_scope(scopes.GDN_OUT):
            out = nn.DenseGeneral(
                features=spec.n_embd, axis=(-2, -1), use_bias=False, name="out_proj", dtype=x.dtype, param_dtype=param_dtype,
                kernel_init=nn.with_logical_partitioning(nn.initializers.normal(0.02), ("heads", "head_dim", "embed")),
            )(y)
        out = nn.Dropout(rate=spec.dropout)(out, deterministic=self.deterministic or spec.dropout == 0.0)
        return out, counters
