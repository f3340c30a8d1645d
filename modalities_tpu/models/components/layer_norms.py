"""Layer norm variants and their configs (reference: src/modalities/models/components/layer_norms.py:9).

All three reference variants (custom RMSNorm, nn.LayerNorm, nn.RMSNorm) map onto flax
linen norms; the distinction kept is bias/epsilon handling so configs translate 1:1.
"""

from __future__ import annotations

from enum import Enum
from typing import Optional

from pydantic import BaseModel, Field
from typing_extensions import Annotated


class LayerNorms(Enum):
    rms_norm = "rms_norm"
    layer_norm = "layer_norm"
    pytorch_rms_norm = "pytorch_rms_norm"  # config-compat alias; identical on TPU


class LayerNormConfig(BaseModel):
    normalized_shape: Annotated[int, Field(strict=True, ge=1)]
    eps: Annotated[float, Field(gt=0)] = 1e-5
    elementwise_affine: bool = True
    bias: bool = True


class RMSLayerNormConfig(BaseModel):
    ndim: Annotated[int, Field(strict=True, ge=1)]
    epsilon: Annotated[float, Field(gt=0)] = 1e-6
    bias: bool = True
    # the leaf `scale` is `w` of `y = norm(x) * (1 + w)` and starts at 0 (`Qwen3NextRMSNorm`, Gemma's): no bias goes with it
    zero_centered: bool = False


class PytorchRMSLayerNormConfig(BaseModel):
    normalized_shape: Annotated[int, Field(strict=True, ge=1)]
    eps: Annotated[float, Field(gt=0)] = 1e-6


class LayerNormWrapperConfig(BaseModel):
    norm_type: LayerNorms
    config: dict


class NormSpec(BaseModel):
    """Resolved norm description consumed by linen modules (frozen => hashable, so it
    can live inside the static GPT2ModelSpec)."""

    model_config = {"frozen": True}

    kind: LayerNorms
    dim: int
    eps: float
    use_bias: bool
    use_scale: bool = True
    zero_centered: bool = False  # the scale leaf is applied as `1 + w` and starts at 0

    @staticmethod
    def from_wrapper_config(wrapper: Optional[LayerNormWrapperConfig | dict], default_dim: int) -> "NormSpec":
        if wrapper is None:
            return NormSpec(kind=LayerNorms.rms_norm, dim=default_dim, eps=1e-6, use_bias=False)
        if isinstance(wrapper, dict):
            wrapper = LayerNormWrapperConfig(**wrapper)
        cfg = wrapper.config
        if wrapper.norm_type == LayerNorms.layer_norm:
            parsed = LayerNormConfig(**cfg)
            return NormSpec(
                kind=wrapper.norm_type,
                dim=parsed.normalized_shape,
                eps=parsed.eps,
                use_bias=parsed.bias and parsed.elementwise_affine,
                use_scale=parsed.elementwise_affine,
            )
        if wrapper.norm_type == LayerNorms.rms_norm:
            parsed = RMSLayerNormConfig(**cfg)
            if parsed.zero_centered and parsed.bias:
                raise ValueError("rms_norm: zero_centered (the scale applied as 1 + w) with a bias is not written; set bias false")
            return NormSpec(kind=wrapper.norm_type, dim=parsed.ndim, eps=parsed.epsilon, use_bias=parsed.bias,
                            zero_centered=parsed.zero_centered)
        parsed = PytorchRMSLayerNormConfig(**cfg)
        return NormSpec(kind=wrapper.norm_type, dim=parsed.normalized_shape, eps=parsed.eps, use_bias=False)


def build_norm(spec: NormSpec, name: str, dtype=None):
    """Instantiate the linen norm module for a NormSpec.

    `dtype` is the *output/compute* dtype (internals always reduce in fp32); pass the
    block compute dtype (bf16) to keep residual streams stable under lax.scan.

    RMS-family norms take the fused Pallas kernel where kernels run (`ops/tiers.py`:
    on a TPU) and the reference modules elsewhere, so CPU tier-1 numerics are untouched;
    the fused module uses the same param names ("scale"/"bias"), so checkpoints
    are interchangeable between the two."""
    import flax.linen as nn

    if spec.kind == LayerNorms.layer_norm:
        return nn.LayerNorm(
            epsilon=spec.eps, use_bias=spec.use_bias, use_scale=spec.use_scale, name=name, dtype=dtype
        )
    from modalities_tpu.ops import tiers

    if tiers.kernels_run():
        return FusedRMSNorm(epsilon=spec.eps, use_bias=spec.use_bias, use_scale=spec.use_scale, dtype=dtype, name=name,
                            zero_centered=spec.zero_centered)
    if spec.zero_centered:
        return ZeroCentredRMSNorm(epsilon=spec.eps, dtype=dtype, name=name)
    if spec.use_bias:
        return RMSNormWithBias(epsilon=spec.eps, name=name)
    return nn.RMSNorm(epsilon=spec.eps, use_scale=spec.use_scale, name=name, dtype=dtype)


try:  # define lazily-importable module class at module scope
    import flax.linen as _nn
    import jax.numpy as _jnp
    from jax import lax as _lax

    class RMSNormWithBias(_nn.Module):
        """RMS norm with a learned bias (reference layer_norms.py:9 supports bias)."""

        epsilon: float = 1e-6

        @_nn.compact
        def __call__(self, x):
            dtype = x.dtype
            x32 = x.astype(_jnp.float32)
            scale = self.param("scale", _nn.initializers.ones, (x.shape[-1],))
            bias = self.param("bias", _nn.initializers.zeros, (x.shape[-1],))
            y = x32 * _lax.rsqrt((x32 * x32).mean(-1, keepdims=True) + self.epsilon)
            return (y * scale + bias).astype(dtype)

    class ZeroCentredRMSNorm(_nn.Module):
        """`y = x / sqrt(mean(x^2) + eps) * (1 + w)`, float32 inside; the leaf `scale` is `w`, from 0."""

        epsilon: float = 1e-6
        dtype: Optional[object] = None

        @_nn.compact
        def __call__(self, x):
            from modalities_tpu.ops.rmsnorm import reference_rms_norm

            scale = self.param("scale", _nn.initializers.zeros, (x.shape[-1],))
            y = reference_rms_norm(x, scale, eps=self.epsilon, zero_centered=True)
            return y.astype(self.dtype) if self.dtype is not None else y

    class FusedRMSNorm(_nn.Module):
        """RMS norm through the fused Pallas kernel (ops/pallas/fused_rmsnorm.py):
        one HBM round-trip per row block instead of ~6. Parameter names match the
        reference modules ("scale"/"bias") so both forms share checkpoints."""

        epsilon: float = 1e-6
        use_bias: bool = False
        use_scale: bool = True
        dtype: Optional[object] = None
        zero_centered: bool = False  # the leaf is `w` of `1 + w`, from 0: one add where the wrapper reads it

        @_nn.compact
        def __call__(self, x):
            from modalities_tpu.ops.rmsnorm import rms_norm_or_fallback

            init = _nn.initializers.zeros if self.zero_centered else _nn.initializers.ones
            scale = self.param("scale", init, (x.shape[-1],)) if self.use_scale else None
            bias = (
                self.param("bias", _nn.initializers.zeros, (x.shape[-1],)) if self.use_bias else None
            )
            y = rms_norm_or_fallback(x, scale, bias, eps=self.epsilon, zero_centered=self.zero_centered)
            return y.astype(self.dtype) if self.dtype is not None else y

except ImportError:  # pragma: no cover
    RMSNormWithBias = None
    ZeroCentredRMSNorm = None
    FusedRMSNorm = None


# Registry builders for the `layer_norm` component entities (reference
# components.py:396-398 registers nn.LayerNorm / RMSLayerNorm / nn.RMSNorm; here a
# layer_norm component node resolves to the NormSpec the linen modules consume —
# usable by custom models registered through Main.add_custom_component).


def build_rms_norm_spec(ndim: int, epsilon: float = 1e-6, bias: bool = True) -> NormSpec:
    return NormSpec.from_wrapper_config(
        {"norm_type": "rms_norm", "config": {"ndim": ndim, "epsilon": epsilon, "bias": bias}},
        default_dim=ndim,
    )


def build_layer_norm_spec(
    normalized_shape: int, eps: float = 1e-5, elementwise_affine: bool = True, bias: bool = True
) -> NormSpec:
    return NormSpec.from_wrapper_config(
        {
            "norm_type": "layer_norm",
            "config": {
                "normalized_shape": normalized_shape,
                "eps": eps,
                "elementwise_affine": elementwise_affine,
                "bias": bias,
            },
        },
        default_dim=normalized_shape,
    )


def build_pytorch_rms_norm_spec(normalized_shape: int, eps: float = 1e-6) -> NormSpec:
    return NormSpec.from_wrapper_config(
        {"norm_type": "pytorch_rms_norm", "config": {"normalized_shape": normalized_shape, "eps": eps}},
        default_dim=normalized_shape,
    )
