"""Compressed convolutional attention (CCA, arXiv 2510.04476, the grouped form), as
`model_type: zaya` runs it: the fourth mixer a block's seat can hold, under the module
name `cca` (a layer whose published type is `hybrid`).

The scores are taken in a latent narrower than the residual: q is `n_head_q * head_dim`
wide and k, v `n_head_kv * head_dim` (1024 and 256 out of 2048 in the source), and nothing
is expanded again before the scores (latent attention, `mla.py`, is a low-rank bottleneck
that is). Between projection and rotary q and k are mixed along the sequence and across a
head's channels. On the block's normed input `h [S, E]`, `G = n_head_q / n_head_kv`,
`d = head_dim`:

    q0 = h W_q  [S, Hq, d]          k0 = h W_k  [S, Hkv, d]                       no bias
    u  = concat(q0, k0) over heads  [S, Hq + Hkv, d]
    c  = conv0(u)   c[t] = sum_j a_j * u[t - (K0 - 1) + j] + b        depthwise: a weight a tap a channel, `cca_time0` taps, zeros before t = 0
    e  = conv1(c)   e[t, g] = sum_j B_j[g] c[t - (K1 - 1) + j, g] + b'    grouped by head: B_j[g] is [d, d], `cca_time1` taps
    (qc, kc) = split(e)
    mq[t, i] = (q0[t, i] + k0[t, i // G]) / 2          mk[t, j] = the mean over the G query heads i of group j of mq[t, i]
    q = qc + mq                     k = kc + mk
    q = sqrt(d) q / ||q||           k = tau[j] sqrt(d) k / ||k||      per head and position, float32; tau [Hkv] learned, from 1
    q, k = rope(q), rope(k)         on the first `partial_rotary_factor * d` channels of a head (`rope_parameters`), the rest passed
    v[t, :Hkv/2] = h[t] W_v         v[t, Hkv/2:] = h[t - 1] W_v'      (h[-1] = 0): half the value heads are the previous position's
    o = softmax(q k^T / sqrt(d), causal) v,  grouped Hq : Hkv         `ops/attention.causal_attention`
    a = o W_o

The norm divides by `max(||x||, 1e-12)` (a zero vector stays zero). The previous position's
values are the projection's output shifted (the projection has no bias, so `h[t - 1] W` is
`(h W)[t - 1]`: 128 channels moved for 2048). Heads, `head_dim`, the rotary and eps are the
model config's own keys; `CCAConfig` holds the two tap counts.

What this mixer does not have: a cache (serving would keep the previous position's `h`, `u`
and `c` of every sequence and layer beside keys and values), a cp axis (both convolutions
and the value shift reach across a shard's edge), attention-probability dropout inside the
flash kernel. Each is refused by name (`gpt2_model.py`).
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Annotated

import flax.linen as nn
import jax
import jax.numpy as jnp
from pydantic import BaseModel, ConfigDict, Field

from modalities_tpu.ops import tiers
from modalities_tpu.ops.attention import causal_attention
from modalities_tpu.telemetry import scopes


class CCAConfig(BaseModel):
    """The `cca_config` block of a `model.gpt2` config: the taps of the two convolutions, keys as `zaya` publishes them."""

    model_config = ConfigDict(extra="forbid")

    cca_time0: Annotated[int, Field(strict=True, ge=1)] = 2  # the depthwise convolution's taps
    cca_time1: Annotated[int, Field(strict=True, ge=1)] = 2  # the grouped convolution's


@dataclass(frozen=True)
class CCASpec:
    time0: int = 2
    time1: int = 2

    @classmethod
    def from_config(cls, config: "CCAConfig | dict") -> "CCASpec":
        config = CCAConfig(**config) if isinstance(config, dict) else config
        return cls(config.cca_time0, config.cca_time1)


def shift_right(x, steps: int):
    """`x [B, S, ...]` moved `steps` positions later along the sequence, zeros coming in: one pad and one slice."""
    if steps == 0:
        return x
    return jnp.pad(x, ((0, 0), (steps, 0)) + ((0, 0),) * (x.ndim - 2))[:, : x.shape[1]]


def qk_mean(q0, k0):
    """The mean shared by q and k, float32: for a query head the mean of its own pre-convolution latent and its key
    head's, for a key head the mean of those over its group. q0 [B, S, Hq, d], k0 [B, S, Hkv, d]."""
    b, s, hq, d = q0.shape
    hkv = k0.shape[2]
    q0, k0 = q0.astype(jnp.float32), k0.astype(jnp.float32)
    mq = (q0.reshape(b, s, hkv, hq // hkv, d) + k0[:, :, :, None, :]) * 0.5
    return mq.reshape(b, s, hq, d), jnp.mean(mq, axis=3)


def l2_normalised(x, scale):
    """`scale * x / max(||x||, 1e-12)` over the last axis, float32."""
    x = x.astype(jnp.float32)
    return x * (scale * jax.lax.rsqrt(jnp.maximum(jnp.sum(x * x, axis=-1, keepdims=True), 1e-24)))


def grouped_conv(c, kernel, bias):
    """`c [B, S, H, d]`, `kernel [K, H, d, d]`, `bias [H, d]`: a causal convolution of K taps that mixes the channels
    inside a head, as K head-batched products on the array and its shifts (the last tap weighs the current step). Each
    product leaves in c's dtype (accumulated in float32, as every matmul of the block); their sum and the bias are float32."""
    taps = kernel.shape[0]
    out = sum(jnp.einsum("bshd,hde->bshe", shift_right(c, taps - 1 - j), kernel[j].astype(c.dtype)).astype(jnp.float32)
              for j in range(taps))
    return out + bias.astype(jnp.float32)


class CompressedConvAttention(nn.Module):
    """The mixer; sits in a block's seat under the name `cca`. x: the block's normed input `[B, S, E]`.
    Returns `(a [B, S, E], the mean key temperature)`: the second is what the step counts of this layer."""

    spec: object  # GPT2ModelSpec (its `cca` is the CCASpec)
    deterministic: bool = True

    @nn.compact
    def __call__(self, x):
        from jax.ad_checkpoint import checkpoint_name

        from modalities_tpu.models.gpt2 import gpt2_model as g
        from modalities_tpu.models.gpt2.ssm import _uniform
        from modalities_tpu.ops.selective_scan import causal_depthwise_conv
        from modalities_tpu.telemetry import get_active_telemetry

        spec, cca = self.spec, self.spec.cca
        hq, hkv, d = spec.n_head_q, spec.n_head_kv, spec.head_dim
        b, s, _ = x.shape
        heads = hq + hkv
        rope = spec.rope_of("cca")
        rotated = g.rotary_dim(d, rope) if spec.use_rope else 0
        get_active_telemetry().emit_event_once("cca_plan", {  # runs while tracing: once per shape, nothing per step
            "tokens": b * s, "sequence": s, "q_heads": hq, "kv_heads": hkv, "head_dim": d, "latent_width": heads * d,
            "taps": (cca.time0, cca.time1), "rotated_channels": rotated, "shifted_value_heads": hkv // 2,
            "flash_blocks": _flash_blocks(s, d, x.dtype),
        })
        f32 = jnp.float32
        with jax.named_scope(scopes.CCA_LATENT):
            q0 = g._dense_general(spec, (hq, d), "q_attn", ("embed", "heads", "head_dim"), x.dtype)(x)
            k0 = g._dense_general(spec, (hkv, d), "k_attn", ("embed", "kv_heads", "head_dim"), x.dtype)(x)
            v_now = g._dense_general(spec, (hkv - hkv // 2, d), "v_attn", ("embed", "kv_heads", "head_dim"), x.dtype)(x)
            v_prev = g._dense_general(spec, (hkv // 2, d), "v_attn_prev", ("embed", "kv_heads", "head_dim"), x.dtype)(x)
        with jax.named_scope(scopes.CCA_VALUE_SHIFT):
            v = jnp.concatenate([v_now, shift_right(v_prev, 1)], axis=2)
        with jax.named_scope(scopes.CCA_CONV):
            bound = cca.time0 ** -0.5  # torch's default for a depthwise Conv1d, as the state-space mixer's: the fan-in is the taps
            a = self.param("conv0_kernel", nn.with_logical_partitioning(_uniform(bound), (None, None)), (cca.time0, heads * d), f32)
            a_bias = self.param("conv0_bias", nn.with_logical_partitioning(nn.initializers.zeros, (None,)), (heads * d,), f32)
            big = self.param("conv1_kernel", nn.with_logical_partitioning(nn.initializers.normal(0.02), (None, None, None, None)),
                             (cca.time1, heads, d, d), jnp.dtype(spec.param_dtype))
            big_bias = self.param("conv1_bias", nn.with_logical_partitioning(nn.initializers.zeros, (None, None)), (heads, d), f32)
            u = jnp.concatenate([q0, k0], axis=2)
            c = causal_depthwise_conv(u.reshape(b, s, heads * d), a, a_bias).reshape(b, s, heads, d)
            e = grouped_conv(c, big, big_bias)
        with jax.named_scope(scopes.CCA_QK_MEAN):
            mq, mk = qk_mean(q0, k0)
            q, k = e[:, :, :hq] + mq, e[:, :, hq:] + mk
        with jax.named_scope(scopes.CCA_QK_NORM):
            tau = self.param("key_temperature", nn.with_logical_partitioning(nn.initializers.ones, (None,)), (hkv,), f32)
            q = l2_normalised(q, math.sqrt(d)).astype(x.dtype)
            k = l2_normalised(k, tau[None, None, :, None] * math.sqrt(d)).astype(x.dtype)
        if spec.use_rope:
            with jax.named_scope(scopes.ROPE):
                cos, sin = g._rope_tables(rotated, s, spec.rope_base_freq, dtype=x.dtype, rope=rope)
                q, k = g.apply_rope(q, cos, sin), g.apply_rope(k, cos, sin)
        with jax.named_scope(scopes.ATTN_CORE):
            q = g.with_logical_constraint(q, ("batch", "seq", "heads", "head_dim"), spec)
            k = g.with_logical_constraint(k, ("batch", "seq", "kv_heads", "head_dim"), spec)
            dropping = spec.dropout > 0.0 and not self.deterministic  # on the probabilities, as the plain attention's
            y = causal_attention(q, k, v, impl=spec.attention_impl, kept=spec.remat_keep_flash,
                                 dropout_rate=spec.dropout if dropping else 0.0, dropout_rng=self.make_rng("dropout") if dropping else None)
            y = checkpoint_name(y, "attn_out")
        with jax.named_scope(scopes.CCA_OUT):
            out = nn.DenseGeneral(
                features=spec.n_embd, axis=(-2, -1), use_bias=spec.bias, name="c_proj",
                kernel_init=nn.with_logical_partitioning(nn.initializers.normal(0.02), ("heads", "head_dim", "embed")),
                bias_init=nn.with_logical_partitioning(nn.initializers.zeros, ("embed",)),
                dtype=x.dtype, param_dtype=jnp.dtype(spec.param_dtype),
            )(y)
        out = nn.Dropout(rate=spec.dropout)(out, deterministic=self.deterministic or spec.dropout == 0.0)
        return out, jax.lax.stop_gradient(jnp.mean(tau))


def _flash_blocks(seq: int, head_dim: int, dtype):
    """The flash kernels' forward blocks at this shape where they run (a TPU); None elsewhere."""
    if not tiers.kernels_run():
        return None
    from modalities_tpu.ops.pallas.flash_attention import flash_blocks

    return tuple(flash_blocks(seq, seq, dtype=dtype, head_dim=head_dim, head_dim_v=head_dim))
