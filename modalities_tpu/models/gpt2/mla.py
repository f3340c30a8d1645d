"""Multi-head latent attention as `model_type: deepseek_v3` computes it for training
(Hugging Face's `modeling_deepseek_v3.py`, the expanded form): the second attention a
block's mixer seat can hold, under the same module name `attn`.

On `x [B, S, d]`, with `H` heads, `d_n = qk_nope_head_dim`, `d_r = qk_rope_head_dim`,
`d_v = v_head_dim` and the latent's rank `r = kv_lora_rank`:

    q            = x @ q_proj                      q_proj [d, H, d_n + d_r]; a head splits into q_n, q_r
    (c, k_r)     = split(x @ kv_a_proj)            kv_a_proj [d, r + d_r]: the latent c, and ONE rotary key for all heads
    (k_n, v)     = split(kv_a_norm(c) @ kv_b_proj) kv_b_proj [r, H, d_n + d_v], a head
    q_r, k_r     = rope(q_r), rope(k_r)            pairs (2i, 2i+1) turn by pos * theta^(-2i / d_r)
    q, k         = [q_n, q_r], [k_n, k_r on every head]     both d_n + d_r wide
    out          = softmax(q k^T / sqrt(d_n + d_r), causal) v    heads of d_v
    y            = out @ c_proj                    c_proj [H, d_v, d]

No bias anywhere. The rotary turns neighbouring pairs (`rope_interleave: true`). Hugging
Face first moves a head's even members to its front half and then rotates halves; the
scores are the same, since q and k are permuted alike (`tests/models/test_moe_mla.py`
holds the two together). There is no query low-rank (`q_lora_rank: null`) and no rotary
scaling (`rope_scaling: null`): a config that asks for either is refused.

The absorbed form (queries against the 576-wide latent, one shared key/value) is
serving's. It needs a latent cache, which `serving/` does not have, so a model with
this attention trains and does not decode.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Annotated, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from pydantic import BaseModel, Field, model_validator

from modalities_tpu.ops.attention import causal_attention
from modalities_tpu.telemetry import scopes


class MLAConfig(BaseModel):
    """The `mla_config` block of a `model.gpt2` config; keys as `deepseek_v3` publishes them."""

    kv_lora_rank: Annotated[int, Field(strict=True, ge=1)]
    qk_nope_head_dim: Annotated[int, Field(strict=True, ge=1)]
    qk_rope_head_dim: Annotated[int, Field(strict=True, ge=2)]
    v_head_dim: Annotated[int, Field(strict=True, ge=1)]
    rope_theta: Annotated[float, Field(gt=0.0)] = 10000.0
    rope_interleave: bool = True
    q_lora_rank: Optional[int] = None
    rope_scaling: Optional[dict] = None
    norm_eps: Annotated[float, Field(gt=0.0)] = 1e-6  # of the norm on the latent

    @model_validator(mode="after")
    def refuse_what_is_not_written(self) -> "MLAConfig":
        if self.q_lora_rank is not None:
            raise ValueError("mla_config.q_lora_rank: a query low-rank path (q_a_proj, its norm, q_b_proj) is not written; only null is")
        if self.rope_scaling is not None:
            raise ValueError("mla_config.rope_scaling: no scaling of latent attention's rotary frequencies (yarn and the like) is written; "
                             "only null is (YaRN is the plain attention's, by `rope_parameters`)")
        if not self.rope_interleave:
            raise ValueError("mla_config.rope_interleave: only the interleaved rotary (pairs 2i, 2i+1) is written")
        if self.qk_rope_head_dim % 2:
            raise ValueError("mla_config.qk_rope_head_dim must be even: the rotary turns pairs")
        return self


@dataclass(frozen=True)
class MLASpec:
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    rope_theta: float
    norm_eps: float

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @classmethod
    def from_config(cls, config: "MLAConfig | dict") -> "MLASpec":
        if isinstance(config, dict):
            config = MLAConfig(**config)
        return cls(kv_lora_rank=config.kv_lora_rank, qk_nope_head_dim=config.qk_nope_head_dim,
                   qk_rope_head_dim=config.qk_rope_head_dim, v_head_dim=config.v_head_dim,
                   rope_theta=float(config.rope_theta), norm_eps=config.norm_eps)


def interleaved_rope(x, theta: float, offset=0):
    """x [B, S, ..., D] with D even: the pair (2i, 2i+1) of position p turns by
    p * theta^(-2i / D), in float32; `offset` shifts every position alike."""
    d = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angle = (jnp.asarray(offset, jnp.float32) + jnp.arange(x.shape[1], dtype=jnp.float32))[:, None] * inv_freq  # [S, D/2]
    angle = angle.reshape((1, x.shape[1]) + (1,) * (x.ndim - 3) + (d // 2,))
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    pairs = x.astype(jnp.float32).reshape(x.shape[:-1] + (d // 2, 2))
    even, odd = pairs[..., 0], pairs[..., 1]
    return jnp.stack([even * cos - odd * sin, even * sin + odd * cos], axis=-1).reshape(x.shape).astype(x.dtype)


class LatentAttention(nn.Module):
    """Latent attention, expanded form; sits in a block's mixer seat under the name `attn`."""

    spec: object  # GPT2ModelSpec (its `mla` is the MLASpec)
    deterministic: bool = True

    @nn.compact
    def __call__(self, x):
        from modalities_tpu.models.gpt2.gpt2_model import with_logical_constraint
        from modalities_tpu.models.gpt2.ssm import _ScaleNorm  # an RMS norm with a learned float32 scale, computed in float32

        spec, mla = self.spec, self.spec.mla
        heads, d_n, d_r, d_v = spec.n_head_q, mla.qk_nope_head_dim, mla.qk_rope_head_dim, mla.v_head_dim
        if spec.context_parallel_axis is not None:
            raise NotImplementedError("latent attention under a cp mesh axis: the ring carries one head size for q, k and v")
        if spec.dropout > 0.0 and not self.deterministic:
            raise NotImplementedError("latent attention has no attention-probability dropout: set dropout: 0.0")

        def dense(features, name, axes, axis=-1):
            return nn.DenseGeneral(features=features, axis=axis, use_bias=False, name=name, dtype=x.dtype,
                                   param_dtype=jnp.dtype(spec.param_dtype),
                                   kernel_init=nn.with_logical_partitioning(nn.initializers.normal(0.02), axes))

        q = dense((heads, d_n + d_r), "q_proj", ("embed", "heads", "head_dim"))(x)
        latent = dense(mla.kv_lora_rank + d_r, "kv_a_proj", ("embed", "latent"))(x)
        c, k_r = latent[..., : mla.kv_lora_rank], latent[..., mla.kv_lora_rank:]
        kv = dense((heads, d_n + d_v), "kv_b_proj", ("latent", "heads", "head_dim"))(_ScaleNorm(mla.norm_eps, name="kv_a_norm")(c).astype(x.dtype))
        k_n, v = kv[..., :d_n], kv[..., d_n:]

        with jax.named_scope(scopes.ROPE):
            q_r = interleaved_rope(q[..., d_n:], mla.rope_theta)
            k_r = interleaved_rope(k_r[:, :, None, :], mla.rope_theta)
            q = jnp.concatenate([q[..., :d_n], q_r], axis=-1)
            k = jnp.concatenate([k_n, jnp.broadcast_to(k_r, k_n.shape[:-1] + (d_r,))], axis=-1)

        with jax.named_scope(scopes.ATTN_CORE):
            q = with_logical_constraint(q, ("batch", "seq", "heads", "head_dim"), spec)
            k = with_logical_constraint(k, ("batch", "seq", "heads", "head_dim"), spec)
            v = with_logical_constraint(v, ("batch", "seq", "heads", "head_dim"), spec)
            # scale 1 / sqrt(d_n + d_r), off q's width; SDPA takes one width for q, k and v, so at d_v < d_n + d_r
            # whatever is not the kernel is the written-out softmax
            y = causal_attention(q, k, v, impl=spec.attention_impl, kept=spec.remat_keep_flash)
            from jax.ad_checkpoint import checkpoint_name

            y = checkpoint_name(y, "attn_out")
        return dense(spec.n_embd, "c_proj", ("heads", "head_dim", "embed"), axis=(-2, -1))(y)
