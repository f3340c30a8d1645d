"""GPT2-family decoder LLM, TPU-first (reference: src/modalities/models/gpt2/gpt2_model.py).

Capability parity with the reference model (:816): separate q/k/v projections with GQA
(:447-461), RoPE or identity qkv transforms (:114-229), optional QK-norm (:487-502),
three attention tiers (manual / fused SDPA / flash kernel, :595-658), GELU-MLP or
SwiGLU blocks (:780-788), pre-norm residual blocks (:801-813), ABSOLUTE vs NOPE
positions (:888-896), weight tying (:940-943), dict-in/dict-out forward keyed by
sample/prediction keys (:973-1020).

TPU-first design choices (not translations):
- flax.linen with **logical partitioning axes** on every param; the 5-D mesh rules in
  parallel/sharding.py map ("embed", "vocab", "heads", "mlp", ...) onto (dp_shard, tp)
  so FSDP/TP/SP are sharding annotations, not wrapper modules.
- ``nn.scan`` over stacked transformer blocks ("layers" axis): O(1) compile time in
  depth, and the stacked params split naturally across pipeline stages.
- attention tiers: manual einsum softmax (oracle), ``jax.nn.dot_product_attention``
  (XLA-fused), and a Pallas flash kernel (ops/) as the dao_flash equivalent.
- embeddings/logits kept fp32, block compute in bf16 (MXU-native), loss-side logits
  fp32 for a stable softmax.
"""

from __future__ import annotations

import contextlib
import math
import re
from dataclasses import dataclass
from enum import Enum
from typing import Annotated, Literal, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from pydantic import BaseModel, ConfigDict, Field, model_validator

from modalities_tpu.loss_functions import exit_counter_names
from modalities_tpu.models.components.layer_norms import (
    LayerNormWrapperConfig,
    NormSpec,
    build_norm,
)
from modalities_tpu.models.gpt2.cca import CCAConfig, CCASpec, CompressedConvAttention
from modalities_tpu.models.gpt2.gdn import COUNTERS as GDN_COUNTERS, GatedDeltaNet, GDNConfig, GDNSpec
from modalities_tpu.models.gpt2.mla import LatentAttention, MLAConfig, MLASpec
from modalities_tpu.models.gpt2.moe import (AUX_LOSS, BIAS_LEAF, COUNTERS, EXPERT_LOAD, SKIP_SHARE, MoE, MoEConfig, MoESpec, ffn_kinds,
                                            update_selection_bias)
from modalities_tpu.models.gpt2.ssm import MambaMixer, SSMConfig, SSMSpec, layer_kinds, layer_runs
from modalities_tpu.models.model import NNModel
from modalities_tpu.ops import tiers
# `flash_attention` is this module's name for the ladder's kernel rung: tests/benchmark/ replaces it here to drop a window
from modalities_tpu.ops.attention import AttentionImplementation, causal_attention, flash_attention, masked_attention, takes_kernel
from modalities_tpu.ops.embedding import embedding_lookup
from modalities_tpu.telemetry import get_active_telemetry, scopes


def with_logical_constraint(x, axes, spec=None, explicit=False):
    """Sharding hint over logical axis names; resolved by parallel/sharding.py rules
    (active only when the train step installs an axis_rules context). Skipped for
    blocks running under the pp pipeline (spec.pipeline_axis set): inside that manual
    shard_map region values are per-shard and mesh-axis constraints are invalid."""
    if spec is not None and spec.pipeline_axis is not None:
        return x
    from modalities_tpu.parallel.sharding import constrain_activation

    return constrain_activation(x, axes, explicit=explicit)


class PositionTypes(str, Enum):
    ABSOLUTE = "ABSOLUTE"
    NOPE = "NOPE"


class ActivationType(str, Enum):
    GELU = "gelu"
    SWIGLU = "swiglu"
    FUSED_SWIGLU = "fused_swiglu"  # config-compat: XLA fuses SwiGLU on TPU anyway


class QueryKeyValueTransformType(Enum):
    IdentityTransform = "IdentityTransform"
    RotaryTransform = "RotaryTransform"


class AttentionConfig(BaseModel):
    class QueryKeyValueTransformConfig(BaseModel):
        class IdentityTransformConfig(BaseModel):
            pass

        class RotaryTransformConfig(BaseModel):
            n_embd: Annotated[int, Field(strict=True, ge=0)]
            n_head: Annotated[int, Field(strict=True, ge=0)]
            seq_length_dim: Annotated[int, Field(strict=True)] = -2
            base_freq: Annotated[int, Field(strict=True, ge=10000)] = 10000

        type_hint: QueryKeyValueTransformType
        config: RotaryTransformConfig | IdentityTransformConfig

    qkv_transforms: list[QueryKeyValueTransformConfig] = []
    qk_norm_config: Optional[LayerNormWrapperConfig] = None


SLIDING, FULL = "sliding_attention", "full_attention"  # a layer's kind of attention, as `layer_types` publishes it
HYBRID = "hybrid"  # `model_type: zaya`'s one kind of layer: compressed convolutional attention (`cca_config`), then the expert layer
LINEAR = "linear_attention"  # `model_type: qwen3_next`'s layers between the `full_attention` ones: the gated delta rule's mixer (`gdn_config`)
MAMBA, ATTENTION = "mamba", "attention"  # `model_type: granitemoehybrid`'s two: the Mamba-2 mixer (`ssd_config`), and plain attention over all that came before
LayerType = Literal["sliding_attention", "full_attention", "hybrid", "linear_attention", "mamba", "attention"]
MULTIPLIERS = ("embedding_multiplier", "residual_multiplier", "attention_multiplier", "logits_scaling")  # `granitemoehybrid`'s four scalars, config and spec alike


class RopeParameters(BaseModel):
    """The rotary of one kind of attention layer, keys as Hugging Face's `rope_parameters` publishes them.
    `default`: `inv_freq_n = rope_theta^(-2n/D)`. `yarn` (Peng et al., arXiv 2309.00071, as
    `transformers.modeling_rope_utils._compute_yarn_parameters` computes it): the frequencies a context of
    `original_max_position_embeddings` turns fewer than `beta_slow` times are divided by `factor`, those it turns
    more than `beta_fast` times are left, a linear ramp between the two bounds (rounded outwards), and cos
    and sin are both multiplied by `attention_factor` (default `0.1 ln(factor) + 1`). `partial_rotary_factor` below 1 (a
    `hybrid` layer's, PR 40; a `full_attention` layer's, PR 44): the first `partial_rotary_factor * head_dim` channels of a head are turned, by the D' / 2
    frequencies `rope_theta^(-2n/D')` of that width D', and the rest pass as they are. A key that is not below
    (`truncate`, `mscale`, ...) is refused: a rule nobody wrote is not run under the model's name."""

    model_config = ConfigDict(extra="forbid")

    rope_type: Literal["default", "yarn"] = "default"
    rope_theta: Annotated[float, Field(gt=1.0)] = 10000.0
    factor: Annotated[float, Field(ge=1.0)] = 1.0
    original_max_position_embeddings: Optional[Annotated[int, Field(strict=True, ge=1)]] = None
    beta_fast: Annotated[float, Field(gt=0.0)] = 32.0
    beta_slow: Annotated[float, Field(gt=0.0)] = 1.0
    attention_factor: Optional[Annotated[float, Field(gt=0.0)]] = None
    partial_rotary_factor: Annotated[float, Field(gt=0.0, le=1.0)] = 1.0

    @model_validator(mode="after")
    def check_yarn(self) -> "RopeParameters":
        if self.rope_type == "yarn" and self.original_max_position_embeddings is None:
            raise ValueError("rope_parameters: rope_type yarn needs original_max_position_embeddings (the context the frequencies were trained at)")
        if self.rope_type == "yarn" and self.partial_rotary_factor != 1.0:
            raise ValueError("rope_parameters: yarn on part of a head is not written (which of the fewer frequencies the ramp's bounds name); "
                             "partial_rotary_factor goes with rope_type default")
        return self


@dataclass(frozen=True)
class RopeSpec:
    rope_type: str
    theta: float
    factor: float = 1.0
    original: Optional[int] = None
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 1.0
    partial_rotary: float = 1.0  # the share of a head's channels the rotary turns, from the first

    @classmethod
    def from_config(cls, config: "RopeParameters | dict") -> "RopeSpec":
        config = RopeParameters(**config) if isinstance(config, dict) else config
        if config.rope_type == "default":
            return cls("default", float(config.rope_theta), partial_rotary=float(config.partial_rotary_factor))
        scale = config.attention_factor if config.attention_factor is not None else 0.1 * math.log(config.factor) + 1.0
        return cls("yarn", float(config.rope_theta), float(config.factor), config.original_max_position_embeddings,
                   float(config.beta_fast), float(config.beta_slow), float(scale))


def rotary_dim(head_dim: int, rope: Optional[RopeSpec]) -> int:
    """The channels of a head the rotary turns: all of them, or the first `partial_rotary_factor * head_dim`."""
    return head_dim if rope is None else int(head_dim * rope.partial_rotary)


def yarn_bounds(head_dim: int, rope: RopeSpec) -> tuple[int, int]:
    """Between which two of the D/2 frequencies YaRN's ramp runs: the frequency the original context turns `beta`
    times is number `D ln(original / (2 pi beta)) / (2 ln theta)`; `beta_fast` gives the lower bound, `beta_slow` the upper."""
    turns = lambda beta: head_dim * math.log(rope.original / (2 * math.pi * beta)) / (2 * math.log(rope.theta))  # noqa: E731
    return max(math.floor(turns(rope.beta_fast)), 0), min(math.ceil(turns(rope.beta_slow)), head_dim - 1)


def rope_inv_freq(head_dim: int, rope: RopeSpec) -> np.ndarray:
    """The D/2 rotary frequencies of `rope`, float32 (computed in float64 from the static numbers)."""
    inv_freq = rope.theta ** -(np.arange(0, head_dim, 2, dtype=np.float64) / head_dim)
    if rope.rope_type == "yarn":
        low, high = yarn_bounds(head_dim, rope)
        ramp = np.clip((np.arange(head_dim // 2, dtype=np.float64) - low) / max(high - low, 1e-3), 0.0, 1.0)
        inv_freq = inv_freq * ((1.0 - ramp) + ramp / rope.factor)
    return inv_freq.astype(np.float32)


class LoopConfig(BaseModel):
    """`model_type: ouro` (a looped decoder): the stack of layers is walked `total_ut_steps` times over
    ONE set of weights, the final norm closes every walk and its output is both that walk's exit and
    the next walk's input. With `exit_gate` a gate `sigmoid(w . h + b)` (float32) is read off every
    exit and training takes the loss over all exits (`loss_functions.LoopedExitLoss`, entropy weight
    `beta`); without it training sees the last exit alone. Evaluation and `apply` report the last exit,
    which is what `early_exit_threshold: 1` runs: exit by the gate's cumulative distribution is not written."""

    total_ut_steps: Annotated[int, Field(strict=True, ge=1)]
    exit_gate: bool = True
    beta: Annotated[float, Field(ge=0.0)] = 0.1
    early_exit_threshold: float = 1.0

    @model_validator(mode="after")
    def check_no_early_exit(self) -> "LoopConfig":
        if self.early_exit_threshold != 1.0:
            raise ValueError("loop_config: early_exit_threshold below 1 asks for exit by the gate's cumulative distribution, "
                             "which is not written: every walk runs; leave it at 1")
        return self


@dataclass(frozen=True)
class LoopSpec:
    total_ut_steps: int
    exit_gate: bool = True
    beta: float = 0.1

    @classmethod
    def from_config(cls, config: "LoopConfig | dict") -> "LoopSpec":
        config = LoopConfig(**config) if isinstance(config, dict) else config
        return cls(config.total_ut_steps, config.exit_gate, config.beta)


class GPT2LLMConfig(BaseModel):
    """Config surface kept 1:1 with the reference (gpt2_model.py:320-408)."""

    sample_key: str
    prediction_key: str
    use_meta_device: Optional[bool] = False  # no-op: JAX initializes abstractly by default
    poe_type: PositionTypes
    sequence_length: Annotated[int, Field(strict=True, ge=1)]
    vocab_size: Annotated[int, Field(strict=True, ge=1)]
    n_layer: Annotated[int, Field(strict=True, ge=1)]
    n_head_q: Annotated[int, Field(strict=True, ge=1)]
    n_head_kv: Annotated[int, Field(strict=True, ge=1)]
    n_embd: Annotated[int, Field(strict=True, ge=1)]
    ffn_hidden: Annotated[int, Field(strict=True, ge=1)]
    dropout: Annotated[float, Field(ge=0.0)]
    bias: bool
    attention_config: AttentionConfig
    attention_implementation: AttentionImplementation
    activation_type: ActivationType
    attention_norm_config: LayerNormWrapperConfig
    ffn_norm_config: LayerNormWrapperConfig
    lm_head_norm_config: LayerNormWrapperConfig
    use_weight_tying: bool
    seed: Optional[int] = None
    enforce_swiglu_hidden_dim_multiple_of: int = 256
    # fuse lm-head + loss per sequence chunk (long-context memory: [B,S,V] fp32
    # logits never materialize); None = whole-sequence logits. A non-divisor
    # chunk is fine: the scan covers the divisible prefix and the remainder runs
    # as one short chunk (odd eval lengths need no config change).
    lm_head_chunk_size: Optional[Annotated[int, Field(strict=True, ge=1)]] = None
    # A stack of two kinds of layer, by the two keys `model_type: jamba` publishes:
    # layer i holds attention where i % attn_layer_period == attn_layer_offset and the
    # state-space mixer of `ssm_config` (models/gpt2/ssm.py) elsewhere. Unset: attention
    # in every layer.
    attn_layer_period: Optional[Annotated[int, Field(strict=True, ge=1)]] = None
    attn_layer_offset: Annotated[int, Field(strict=True, ge=0)] = 0
    ssm_config: Optional[SSMConfig] = None
    # `model_type: deepseek_v3`. `mla_config` puts latent attention (models/gpt2/mla.py) in every
    # attention seat: heads of qk_nope_head_dim + qk_rope_head_dim for q and k and of v_head_dim
    # for v, whatever n_embd / n_head_q is, with its own interleaved rotary (poe_type NOPE, no
    # RotaryTransform). `moe_config` puts the routed-and-shared expert layer (models/gpt2/moe.py)
    # in the feed-forward seat of every layer from `first_k_dense_replace` on; the layers before
    # keep the dense one of `ffn_hidden`. `experts_held` / `expert_offset` say which of the
    # router's experts this model holds (default: all).
    mla_config: Optional[MLAConfig] = None
    moe_config: Optional[MoEConfig] = None
    # `model_type: ouro`. `loop_config` walks the stack several times over one set of weights
    # (`LoopConfig`); the two norms below, set, make a block's norms a sandwich: one after each
    # sub-layer, on what it adds to the residual stream, beside the one before it.
    loop_config: Optional[LoopConfig] = None
    post_attention_norm_config: Optional[LayerNormWrapperConfig] = None
    post_ffn_norm_config: Optional[LayerNormWrapperConfig] = None
    # `model_type: mellum` and its like (PR 38), keys as the source names them. `head_dim`: a head's width where it is
    # not n_embd / n_head_q (q is n_head_q * head_dim wide out of n_embd, c_proj brings it back). `layer_types`: the kind
    # of attention of every layer; a `sliding_attention` layer sees itself and the `sliding_window - 1` positions before
    # it, a `full_attention` layer all that came before. `rope_parameters`: the rotary by kind of layer (`RopeParameters`;
    # needs a RotaryTransform in `qkv_transforms`, whose base_freq it replaces). All unset: the tree and the program of before.
    head_dim: Optional[Annotated[int, Field(strict=True, ge=2)]] = None
    layer_types: Optional[list[LayerType]] = None
    sliding_window: Optional[Annotated[int, Field(strict=True, ge=1)]] = None
    rope_parameters: Optional[dict[LayerType, RopeParameters]] = None
    # `model_type: zaya` (PR 40). `layer_types` of `hybrid` puts compressed convolutional attention (models/gpt2/cca.py) in the
    # mixer seat, its taps in `cca_config`; heads, `head_dim`, eps and the rotary (`rope_parameters.hybrid`, with its
    # `partial_rotary_factor`) are the keys above. `scale_residual_merge`: a block's two merges are
    # `(x + r_b) * r_s + (a + f_b) * f_s` with four learned `[n_embd]` leaves each (scales from 1, shifts from 0), and the
    # first layer's first merge has none on the residual side (the embedding passes as it is). The router that is an MLP over
    # a state handed from layer to layer is `moe_config`'s (`router: mlp`). All unset: the tree and the program of before.
    cca_config: Optional[CCAConfig] = None
    scale_residual_merge: bool = False
    # `model_type: qwen3_next` (PR 44). `layer_types` of `linear_attention` puts the gated delta rule's mixer (models/gpt2/gdn.py) in
    # the mixer seat, its heads and taps in `gdn_config`; the `full_attention` layers beside them are the plain attention's
    # (`full_attention_interval` is read into `layer_types` where the YAML is written, not here). `attn_output_gate`: `q_attn` is
    # twice as wide, a head's query and a head's gate, and the attention's output is multiplied by `sigmoid(gate)` before `c_proj`.
    # `rope_parameters.full_attention.partial_rotary_factor` turns the first part of a head. Zero-centred norms are a norm's own key
    # (`zero_centered`), the gated shared expert `moe_config`'s. All unset: the tree and the program of before.
    gdn_config: Optional[GDNConfig] = None
    attn_output_gate: bool = False
    # `model_type: granitemoehybrid` (PR 52). `layer_types` of `mamba` puts the Mamba-2 mixer (models/gpt2/ssd.py) in the mixer seat, its
    # heads, state, taps and chunk in `ssd_config` (validated as `ssd.SSDConfig` where set: a model without such a layer imports nothing
    # of it); the `attention` layers beside them are the plain attention's. The four multipliers, keys as the source names them:
    # the table's output times `embedding_multiplier`, each of a block's two branches times `residual_multiplier` before it is added,
    # the attention's scores times `attention_multiplier` in place of `1 / sqrt(head_dim)`, the logits divided by `logits_scaling`
    # (applied to the normed hidden state before the head: the same function, and the fused cross entropy needs no scale). The
    # shared expert's share of its width is `moe_config`'s (`shared_expert_shards`). All unset: the tree and the program of before.
    ssd_config: Optional[dict] = None
    embedding_multiplier: Optional[Annotated[float, Field(gt=0.0)]] = None
    residual_multiplier: Optional[Annotated[float, Field(gt=0.0)]] = None
    attention_multiplier: Optional[Annotated[float, Field(gt=0.0)]] = None
    logits_scaling: Optional[Annotated[float, Field(gt=0.0)]] = None

    @model_validator(mode="after")
    def check_mamba_layers(self) -> "GPT2LLMConfig":
        mamba = self.layer_types is not None and MAMBA in self.layer_types
        if mamba != (self.ssd_config is not None):
            raise ValueError("ssd_config gives a mamba layer its heads, state, taps and chunk: layer_types of mamba and ssd_config go together")
        kinds = set(self.layer_types or ())
        if kinds & {MAMBA, ATTENTION} and kinds - {MAMBA, ATTENTION}:
            raise ValueError("layer_types: mamba and attention layers stand beside each other or alone; beside sliding_attention, full_attention, "
                             "hybrid or linear_attention layers they are not written")
        multipliers = [name for name in MULTIPLIERS if getattr(self, name) is not None]
        if multipliers and (self.loop_config is not None or self.scale_residual_merge or self.mla_config is not None or self.cca_config is not None):
            raise ValueError(f"{', '.join(multipliers)} beside loop_config, scale_residual_merge, mla_config or cca_config: a scalar on the table, "
                             "the branches, the scores or the logits is written for the plain block and the plain attention, in a stack walked once")
        if not mamba:
            return self
        from modalities_tpu.models.gpt2.ssd import SSDConfig

        SSDConfig(**self.ssd_config)  # refuses an unknown key, and several groups, by name
        beside = [name for name, value in (("attn_layer_period", self.attn_layer_period), ("ssm_config", self.ssm_config), ("mla_config", self.mla_config),
                                           ("loop_config", self.loop_config), ("cca_config", self.cca_config), ("gdn_config", self.gdn_config),
                                           ("sliding_window", self.sliding_window), ("rope_parameters", self.rope_parameters)) if value is not None]
        if beside:
            raise ValueError(f"ssd_config beside {', '.join(beside)}: the Mamba-2 mixer shares a stack with plain attention over all that came "
                             "before, spelled by layer_types, in a stack walked once; with the Mamba-1 mixer's period, latent or compressed "
                             "attention, the gated delta rule, a loop, a window or a rotary by kind of layer it is not written")
        return self

    @model_validator(mode="after")
    def check_linear_layers(self) -> "GPT2LLMConfig":
        linear = self.layer_types is not None and LINEAR in self.layer_types
        if linear != (self.gdn_config is not None):
            raise ValueError("gdn_config gives a linear_attention layer its heads and taps: layer_types of linear_attention and gdn_config go together")
        if not linear:
            return self
        if set(self.layer_types) - {LINEAR, FULL}:
            raise ValueError("layer_types: linear_attention layers stand beside full_attention layers or alone; beside sliding_attention "
                             "or hybrid layers they are not written")
        beside = [name for name, value in (("mla_config", self.mla_config), ("loop_config", self.loop_config), ("cca_config", self.cca_config),
                                           ("ssm_config", self.ssm_config), ("sliding_window", self.sliding_window)) if value is not None]
        if beside:
            raise ValueError(f"gdn_config beside {', '.join(beside)}: the gated delta rule's mixer shares a stack with plain attention over all "
                             "that came before, in a stack walked once; with latent or compressed attention, state-space layers, a loop or a "
                             "window it is not written")
        return self

    @model_validator(mode="after")
    def check_hybrid_layers(self) -> "GPT2LLMConfig":
        hybrid = self.layer_types is not None and HYBRID in self.layer_types
        if hybrid != (self.cca_config is not None):
            raise ValueError("cca_config gives a hybrid layer's two convolutions their taps: layer_types of hybrid and cca_config go together")
        partial = [kind for kind, rope in (self.rope_parameters or {}).items() if rope.partial_rotary_factor != 1.0 and kind not in (HYBRID, FULL)]
        if partial:
            raise ValueError(f"rope_parameters {partial}: partial_rotary_factor below 1 is written for hybrid layers (the cca mixer) and "
                             "full_attention layers; a sliding_attention layer's rotary turns the whole head")
        full = (self.rope_parameters or {}).get(FULL)
        if full is not None and full.partial_rotary_factor != 1.0:
            head_dim = self.head_dim if self.head_dim is not None else self.n_embd // self.n_head_q
            if int(head_dim * full.partial_rotary_factor) % 2:
                raise ValueError("rope_parameters.full_attention.partial_rotary_factor: the rotated part of a head must be even (the rotary turns halves)")
        if not hybrid:
            return self
        if set(self.layer_types) != {HYBRID}:
            raise ValueError("layer_types: hybrid layers beside sliding_attention or full_attention layers are not written; every layer is hybrid or none")
        if self.mla_config is not None or self.loop_config is not None or self.sliding_window is not None or self.attn_layer_period is not None:
            raise ValueError("cca_config beside mla_config, loop_config, sliding_window or attn_layer_period: compressed convolutional attention "
                             "takes its scores in its own latent over all that came before, in a stack walked once; none of the four is written with it")
        if self.n_head_kv % 2:
            raise ValueError("cca_config: half the key/value heads read their values off the previous position; n_head_kv must be even")
        if self.attention_config.qk_norm_config is not None:
            raise ValueError("cca_config: q and k are L2-normalised with a learned key temperature; leave qk_norm_config unset")
        head_dim = self.head_dim if self.head_dim is not None else self.n_embd // self.n_head_q
        if int(head_dim * (self.rope_parameters or {}).get(HYBRID, RopeParameters()).partial_rotary_factor) % 2:
            raise ValueError("rope_parameters.hybrid.partial_rotary_factor: the rotated part of a head must be even (the rotary turns halves)")
        return self

    @model_validator(mode="after")
    def check_loop(self) -> "GPT2LLMConfig":
        if self.loop_config is not None and (self.attn_layer_period is not None or self.mla_config is not None
                                             or self.moe_config is not None or self.layer_types is not None):
            raise ValueError("loop_config walks ONE run of equal dense-decoder layers; a stack of several kinds of layer "
                             "(attn_layer_period, moe_config, layer_types) or latent attention under it is not written")
        return self

    @model_validator(mode="after")
    def check_layer_types(self) -> "GPT2LLMConfig":
        rotary = any(t.type_hint == QueryKeyValueTransformType.RotaryTransform for t in self.attention_config.qkv_transforms)
        if self.mla_config is not None and (self.rope_parameters is not None or self.layer_types is not None or self.head_dim is not None):
            raise ValueError("mla_config: latent attention has its own rotary (no YaRN: rope_parameters is the plain attention's), "
                             "its own head widths and no window; leave rope_parameters, layer_types and head_dim unset")
        if self.rope_parameters is not None and not rotary:
            raise ValueError("rope_parameters gives the rotary its frequencies by kind of layer: put a RotaryTransform in attention_config.qkv_transforms")
        if self.head_dim is not None and self.head_dim % 2:
            raise ValueError("head_dim must be even: the rotary turns halves")
        if self.layer_types is None:
            if self.sliding_window is not None:
                raise ValueError("sliding_window needs layer_types: without it every layer sees all that came before")
            return self
        if len(self.layer_types) != self.n_layer:
            raise ValueError(f"layer_types names {len(self.layer_types)} layers, n_layer is {self.n_layer}")
        if self.attn_layer_period is not None:
            raise ValueError("layer_types beside attn_layer_period: window layers in a stack with state-space layers are not written")
        if SLIDING in self.layer_types and self.sliding_window is None:
            raise ValueError("layer_types holds sliding_attention layers: give sliding_window")
        return self

    @model_validator(mode="after")
    def check_latent_attention(self) -> "GPT2LLMConfig":
        if self.mla_config is None:
            return self
        if self.n_head_q != self.n_head_kv:
            raise ValueError("mla_config: latent attention gives every head its own key and value; n_head_kv must equal n_head_q")
        if self.attention_config.qk_norm_config is not None or any(
            t.type_hint == QueryKeyValueTransformType.RotaryTransform for t in self.attention_config.qkv_transforms
        ):
            raise ValueError("mla_config: latent attention has its own rotary on part of a head and no QK norm; "
                             "leave qkv_transforms to IdentityTransform and qk_norm_config unset")
        if self.poe_type != PositionTypes.NOPE:
            raise ValueError("mla_config: positions are latent attention's own rotary; poe_type must be NOPE")
        return self

    @model_validator(mode="after")
    def check_layer_pattern(self) -> "GPT2LLMConfig":
        if self.attn_layer_period is None:
            if self.ssm_config is not None or self.attn_layer_offset:
                raise ValueError("ssm_config and attn_layer_offset need attn_layer_period: without it every layer holds attention")
        elif self.attn_layer_offset >= self.attn_layer_period:
            raise ValueError("attn_layer_offset must be below attn_layer_period")
        elif self.ssm_config is None:
            raise ValueError("attn_layer_period puts the state-space mixer in the other layers: give ssm_config")
        return self

    @model_validator(mode="after")
    def check_divisibility(self) -> "GPT2LLMConfig":
        if self.n_head_q % self.n_head_kv != 0:
            raise ValueError("n_head_q must be divisible by n_head_kv")
        return self

    @model_validator(mode="after")
    def check_dropout_supported(self) -> "GPT2LLMConfig":
        # fail at config parse time, not NotImplementedError at the first forward
        # deep inside a run: the Pallas dao_flash kernel fuses softmax statistics
        # that attention-probability dropout would invalidate (see GPT2Attention)
        if self.dropout > 0.0 and self.attention_implementation == AttentionImplementation.DAO_FLASH:
            raise ValueError(
                "dropout > 0 is not supported with attention_implementation: dao_flash "
                "(the fused Pallas kernel has no dropout hook). Use manual or "
                "pytorch_flash for exact reference dropout semantics, or set dropout: 0.0."
            )
        return self

    @model_validator(mode="after")
    def validate_sizes(self) -> "GPT2LLMConfig":
        for param, name in zip([self.ffn_hidden, self.n_embd], ["ffn_hidden", "n_embd"]):
            if param % 128 != 0:
                # MXU tiles are 128-wide; unaligned dims waste systolic-array cycles
                raise ValueError(f"{name} with value {param} should be divisible by 128 for efficient training.")
        if self.vocab_size % 16 != 0:
            # a chip's share of a published table is often no multiple of 128 (an eighth of 262,272 rows is 16 x 2049): the
            # head's kernels pad the vocabulary to their blocks, the embedding's chunks take any count; whole sublane tiles stay
            raise ValueError(f"vocab_size with value {self.vocab_size} should be divisible by 128 for efficient training, and must be by 16.")
        return self


def swiglu_hidden_dim(ffn_hidden: int, multiple_of: int = 256) -> int:
    """2/3 scale-down + round up to a TP-shardable multiple (reference model.py:116-141)."""
    adjusted = int(2 * ffn_hidden / 3)
    return ((adjusted + multiple_of - 1) // multiple_of) * multiple_of


@dataclass(frozen=True)
class SlotDecodeSpec:
    """Static shape of the serving engine's batched KV cache (serving/engine.py).

    kind="ring" (serving v1): one [slots, capacity] ring row per slot.
    `mode="prefill"` runs a batch-1 forward over a prompt chunk and writes its k/v
    into cache slot `slot` starting at position `positions` (both traced scalars);
    `mode="decode"` advances every slot by one token — tokens [slots, 1] written at
    per-slot `positions` [slots]. Shapes are static so ONE compiled decode step (plus
    a bounded prefill-chunk ladder) serves every request mix.

    kind="paged" (serving v2, vLLM-style): ONE global [num_blocks, block_size] pool
    per scanned layer; a slot owns an ordered list of blocks (its block table, a
    traced int32 arg — table entry m covers the slot's logical positions
    m*block_size..(m+1)*block_size-1, so the gathered K/V sequence is position-
    ordered regardless of physical block ids). `capacity` is the max gathered length
    (table width x block_size). Writes carry explicit (block, offset) coordinates;
    out-of-range block ids are DROPPED (idle slots / padded prefill tails write
    nowhere instead of clamping onto a live block). `mode="prefill"` packs chunks
    from several requests as rows of one [rows, chunk] dispatch — the Sarathi-style
    cross-request prefill step."""

    mode: str  # "prefill" | "decode"
    slots: int
    capacity: int  # ring: per-slot ring length; paged: table_width * block_size
    kind: str = "ring"  # "ring" | "paged"
    num_blocks: int = 0  # paged only: global pool blocks per layer
    block_size: int = 0  # paged only: tokens per block
    # paged only: "none" | "int8" — int8 pools store quantized K/V rows plus a
    # float32 scale per (block, row, kv_head) alongside (quant/kv.py)
    kv_quant: str = "none"


@dataclass(frozen=True)
class GPT2ModelSpec:
    """Static (hashable) hyperparameters consumed by the linen modules."""

    vocab_size: int
    sequence_length: int
    n_layer: int
    n_head_q: int
    n_head_kv: int
    n_embd: int
    ffn_hidden: int
    dropout: float
    bias: bool
    poe_type: str
    activation: str
    attention_impl: str
    use_rope: bool
    rope_base_freq: int
    use_qk_norm: bool
    use_weight_tying: bool
    swiglu_hidden: int
    attn_norm: NormSpec
    ffn_norm: NormSpec
    lm_head_norm: NormSpec
    qk_norm: Optional[NormSpec]
    scan_layers: bool = True
    remat_variant: Optional[str] = None
    remat_freq: int = 1
    remat_save_list: tuple[str, ...] = ()
    # under `full`: a rematerialized block keeps the flash kernel's o and lse beside its input, so that its backward does
    # not run the forward kernel again. Not a key of any config: `training/activation_checkpointing.attention_keep_plan`
    # decides it from the shapes and the device's bytes when the train step is traced (`training/train_step.py`)
    remat_keep_flash: bool = False
    # and, of a block whose mixer is the gated delta rule, the rule's o and the state that came into each group of chunks
    # (`ops/gated_delta_rule.KEPT_OUT` / `KEPT_STATES`): decided in the same place, one rung above the flash kernel's two
    remat_keep_rule: bool = False
    # fuse lm-head + CE per sequence chunk of this size (train/eval step): the
    # [B,S,V] fp32 logits never materialize — at 32k ctx x 50k vocab that tensor
    # alone is 6.6 GB, more than a v5e can give it. None = whole-sequence logits.
    lm_head_chunk_size: Optional[int] = None
    context_parallel_axis: Optional[str] = None  # set when the mesh has cp > 1
    pipeline_axis: Optional[str] = None  # set when the mesh has pp > 1
    pp_num_microbatches: Optional[int] = None  # GPipe microbatches (default: pp degree)
    pp_schedule: str = "gpipe"  # "gpipe" = in-module autodiff GPipe; "1f1b"/"interleaved_1f1b"/"zbv"/"dualpipev" = scheduled executor
    pp_num_virtual: int = 1  # virtual chunks per device (interleaved_1f1b)
    param_dtype: str = "float32"  # storage dtype (MixedPrecisionSpec.param_dtype)
    compute_dtype: str = "bfloat16"  # block compute dtype (MXU-native)
    # "stats" | "shape" | None — compiles a jax.debug.print of each block output
    # into the forward (model_debugging_hook.print_forward_hook; the jit-native
    # analogue of the reference's eager print hook, debug_components.py:50-70)
    debug_print_activations: Optional[str] = None
    # weight-only quantized serving (quant/weights.py): "none" | "int8" | "fp8".
    # Non-"none" swaps every dense layer for QuantDenseGeneral (kernel stored
    # quantized + float32 per-output-channel scale, dequant fused into the
    # matmul). Serving-only — the train step never sets this.
    quant_weights: str = "none"
    # the mixer of every layer, "attn", "ssm" or "swa" (attention under a window) (empty: attention everywhere), and the
    # state-space mixer's sizes. Runs of equal kind are stacked and scanned one after
    # another; one run is the dense decoder, with the tree and the program it always had
    layer_kinds: tuple[str, ...] = ()
    ssm: Optional[SSMSpec] = None
    # latent attention in the attention seats, and the expert layer in the feed-forward seat of
    # the layers `moe.first_k_dense_replace` on: a layer's kind is (mixer, feed-forward)
    mla: Optional[MLASpec] = None
    moe: Optional[MoESpec] = None
    # the stack walked several times over one parameter tree, and a block's two further norms
    # (after each sub-layer); all unset: the tree and the program the decoder always had
    loop: Optional[LoopSpec] = None
    post_attn_norm: Optional[NormSpec] = None
    post_ffn_norm: Optional[NormSpec] = None
    # a head's width where the config gives it (`head_dim`); the window of the layers whose mixer is "swa" (`layer_kinds`
    # then holds "swa" for a `sliding_attention` layer and "attn" for a `full_attention` one); the rotary by that kind
    head_dim_key: Optional[int] = None
    sliding_window: Optional[int] = None
    rope_by_kind: tuple[tuple[str, RopeSpec], ...] = ()
    # compressed convolutional attention in the layers whose mixer is "cca" (`layer_types`: `hybrid`), and a block's merges in
    # the scaled form; a router state handed from layer to layer is `moe.state_width`
    cca: Optional[CCASpec] = None
    scale_residual_merge: bool = False
    # the gated delta rule's mixer in the layers whose mixer is "gdn" (`layer_types`: `linear_attention`), and the plain
    # attention's output under a gate read off a `q_attn` twice as wide
    gdn: Optional[GDNSpec] = None
    attn_output_gate: bool = False
    # the Mamba-2 mixer in the layers whose mixer is "ssd" (`layer_types`: `mamba`; an `ssd.SSDSpec`), and the four scalar multipliers
    # on the table's output, a block's branches, the attention's scores and the logits (None: none, the program of before)
    ssd: Optional[object] = None
    embedding_multiplier: Optional[float] = None
    residual_multiplier: Optional[float] = None
    attention_multiplier: Optional[float] = None
    logits_scaling: Optional[float] = None

    @property
    def router_state_width(self) -> int:
        """The width of the state an expert layer's router takes from the layer before and hands on; 0: none."""
        return self.moe.state_width if self.moe is not None else 0

    @property
    def counter_row_width(self) -> int:
        """What an expert layer's block hands up a pass: `COUNTERS`, the router's columns' loads, a matrix softmax router's
        balance term, and after them the mean key temperature where the block's mixer is `cca`, or the two of `gdn.COUNTERS`
        where the stack holds the gated delta rule's mixer, or the one of `ssd.COUNTERS` where it holds the Mamba-2 mixer (zeros
        in the row of a layer whose mixer is another)."""
        return len(COUNTERS) + self.moe.router_width + self.moe.counts_aux_loss + (self.cca is not None) + self.mixer_counters

    @property
    def mixer_counter_names(self) -> tuple[str, ...]:
        """What a row holds after the expert layer's own: the counters of the stack's `gdn` layers, or of its `ssd` layers."""
        if self.ssd is not None:
            from modalities_tpu.models.gpt2.ssd import COUNTERS as SSD_COUNTERS

            return SSD_COUNTERS
        return GDN_COUNTERS if self.gdn is not None else ()

    @property
    def mixer_counters(self) -> int:
        return len(self.mixer_counter_names)

    @property
    def has_multipliers(self) -> bool:
        return any(getattr(self, name) is not None for name in MULTIPLIERS)

    @property
    def head_dim(self) -> int:
        return self.head_dim_key if self.head_dim_key is not None else self.n_embd // self.n_head_q

    def rope_of(self, mixer: str) -> Optional[RopeSpec]:
        """The rotary of the layers whose mixer is `mixer`; None: `rope_base_freq`, unscaled, as before there were kinds."""
        return dict(self.rope_by_kind).get(mixer)

    @property
    def has_window(self) -> bool:
        return "swa" in self.layer_kinds

    @property
    def kinds(self) -> tuple[str, ...]:
        return self.layer_kinds or ("attn",) * self.n_layer

    @property
    def ffn_kinds(self) -> tuple[str, ...]:
        return ffn_kinds(self.n_layer, self.moe)

    @property
    def runs(self) -> tuple[tuple[str, int], ...]:
        return layer_runs(self.kinds)

    @property
    def stack_runs(self) -> tuple[tuple[str, str, int], ...]:
        """Runs of layers equal in mixer and feed-forward, in order: `(mixer, ffn, length)`."""
        return tuple((mixer, ffn, length) for (mixer, ffn), length in layer_runs(tuple(zip(self.kinds, self.ffn_kinds))))

    @property
    def has_ssm(self) -> bool:
        return "ssm" in self.layer_kinds

    @property
    def has_moe(self) -> bool:
        return "moe" in self.ffn_kinds

    def __hash__(self):
        # hash a subset of the fields __eq__ compares (never id()): value-equal specs
        # must hash equal so jit/linen caches keyed on static module fields hit
        return hash(
            (
                self.vocab_size,
                self.sequence_length,
                self.n_layer,
                self.n_head_q,
                self.n_head_kv,
                self.n_embd,
                self.ffn_hidden,
                self.dropout,
                self.bias,
                self.poe_type,
                self.activation,
                self.attention_impl,
                self.use_rope,
                self.rope_base_freq,
                self.use_qk_norm,
                self.use_weight_tying,
                self.swiglu_hidden,
                self.scan_layers,
                self.remat_variant,
                self.remat_freq,
                self.remat_save_list,
                self.remat_keep_flash,
                self.lm_head_chunk_size,
                self.context_parallel_axis,
                self.pipeline_axis,
                self.pp_num_microbatches,
                self.pp_schedule,
                self.pp_num_virtual,
                self.param_dtype,
                self.compute_dtype,
                self.debug_print_activations,
                self.quant_weights,
                self.layer_kinds,
                self.ssm,
                self.mla,
                self.moe,
                self.loop,
                self.post_attn_norm,
                self.post_ffn_norm,
                self.head_dim_key,
                self.sliding_window,
                self.rope_by_kind,
                self.cca,
                self.scale_residual_merge,
                self.gdn,
                self.attn_output_gate,
                self.ssd,
                self.embedding_multiplier,
                self.residual_multiplier,
                self.attention_multiplier,
                self.logits_scaling,
            )
        )


def _rope_tables(head_dim: int, seq_len: int, base_freq: int, dtype=jnp.float32, offset=0, rope: Optional[RopeSpec] = None):
    """cos/sin tables, rotate-half convention matching the reference RotaryTransform
    (gpt2_model.py:114-229). `offset` (int or traced scalar) shifts positions to
    `offset .. offset+seq_len-1` — required inside manual cp regions where the local
    sequence chunk starts at a nonzero global position. `rope` (a kind of layer's own
    rotary, `rope_parameters`) replaces `base_freq` by its theta and, for `yarn`, scales
    the frequencies (`rope_inv_freq`) and multiplies both tables by its attention factor."""
    if rope is not None and rope.rope_type != "default":
        inv_freq, scale = jnp.asarray(rope_inv_freq(head_dim, rope)), rope.attention_factor
    else:
        base_freq = base_freq if rope is None else rope.theta
        inv_freq, scale = 1.0 / (base_freq ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)), 1.0
    t = jnp.asarray(offset, jnp.float32) + jnp.arange(seq_len, dtype=jnp.float32)
    freqs = jnp.einsum("i,j->ij", t, inv_freq)
    emb = jnp.concatenate([freqs, freqs], axis=-1)
    if scale != 1.0:
        return (jnp.cos(emb) * scale).astype(dtype), (jnp.sin(emb) * scale).astype(dtype)
    return jnp.cos(emb).astype(dtype), jnp.sin(emb).astype(dtype)


def _manual_axis_active(axis_name: Optional[str]) -> bool:
    """True when tracing inside a shard_map region that binds `axis_name` manually."""
    if axis_name is None:
        return False
    from modalities_tpu.parallel.jax_compat import manual_axes

    return axis_name in manual_axes()


def cp_shard_offset(axis_name: Optional[str], local_seq_len: int):
    """Global position offset of this shard's sequence chunk, when running inside a
    shard_map region that binds `axis_name` manually (e.g. the pp×cp pipeline body);
    0 otherwise. Positions are global semantics — RoPE phases and absolute position
    embeddings must use the shard's true offset, not restart at 0 per chunk."""
    if _manual_axis_active(axis_name):
        return jax.lax.axis_index(axis_name) * local_seq_len
    return 0


def _rotate_half(x):
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([-x2, x1], axis=-1)


def apply_rope(x, cos, sin):
    """x: [B, S, H, D]; cos/sin: [S, D] shared across the batch, or [B, S, D]
    per-batch-row (slot decode: every slot sits at its own position). Tables narrower than a
    head (`partial_rotary_factor`) turn its first channels and pass the rest."""
    if cos.shape[-1] < x.shape[-1]:
        return jnp.concatenate([apply_rope(x[..., : cos.shape[-1]], cos, sin), x[..., cos.shape[-1]:]], axis=-1)
    if cos.ndim == 2:
        cos = cos[None, :, None, :]
        sin = sin[None, :, None, :]
    else:
        cos = cos[:, :, None, :]
        sin = sin[:, :, None, :]
    return x * cos + _rotate_half(x) * sin


class QuantDenseGeneral(nn.Module):
    """DenseGeneral over a weight-only-quantized kernel (quant/weights.py layout).

    Params: `kernel` in the quantized storage dtype with the SAME shape and
    logical axes as the bf16 layer it replaces, plus a float32 `scale` shaped
    like the output feature dims (one symmetric per-output-channel scale),
    plus the usual float32 bias. The tree therefore matches what
    `quantize_params` produces from a restored checkpoint — load/swap install
    quantized params straight into a model whose spec selects this layer.

    The matmul runs through `quant_matmul_or_fallback` (ops/quant_matmul.py):
    the quantized kernel is widened in VMEM inside the fused Pallas kernel on
    TPU, and the bitwise-identical pure-jnp dequant expression elsewhere.
    `n_contract` input dims are flattened into one contraction (always the
    LEADING kernel dims — matches every use site: axis=-1 projections and the
    attention c_proj's axis=(-2, -1))."""

    features: tuple  # output feature dims
    kernel_axes: tuple
    mode: str  # "int8" | "fp8"
    n_contract: int = 1  # leading kernel dims that contract (trailing x dims)
    use_bias: bool = False
    param_dtype: str = "float32"  # bias storage dtype (kernel/scale are fixed)

    @nn.compact
    def __call__(self, x):
        from modalities_tpu.ops.quant_matmul import quant_matmul_or_fallback
        from modalities_tpu.quant.weights import quant_storage_dtype

        feats = tuple(int(f) for f in self.features)
        in_shape = tuple(int(d) for d in x.shape[x.ndim - self.n_contract :])
        storage = quant_storage_dtype(self.mode)
        kernel = self.param(
            "kernel",
            nn.with_logical_partitioning(nn.initializers.zeros, self.kernel_axes),
            in_shape + feats,
            storage,
        )
        scale_axes = self.kernel_axes[self.n_contract :]
        scale = self.param(
            "scale",
            nn.with_logical_partitioning(nn.initializers.ones, scale_axes),
            feats,
            jnp.float32,
        )
        k_flat = math.prod(in_shape)
        n_flat = math.prod(feats)
        batch_shape = x.shape[: x.ndim - self.n_contract]
        y2 = quant_matmul_or_fallback(
            x.reshape(-1, k_flat), kernel.reshape(k_flat, n_flat), scale.reshape(n_flat)
        )
        y = y2.reshape(batch_shape + feats)
        if self.use_bias:
            bias = self.param(
                "bias",
                nn.with_logical_partitioning(nn.initializers.zeros, scale_axes),
                feats,
                jnp.dtype(self.param_dtype),
            )
            y = y + bias.astype(y.dtype)
        return y


def _dense_general(spec, features, name, kernel_axes, dtype, dot_general=None):
    """`dot_general`: the product where it is not `jax.lax.dot_general` (a sequence-parallel region's: `_seq_region`)."""
    bias_axes = kernel_axes[1:] if isinstance(features, tuple) else (kernel_axes[-1],)
    if getattr(spec, "quant_weights", "none") != "none":
        return QuantDenseGeneral(
            features=features if isinstance(features, tuple) else (features,),
            kernel_axes=tuple(kernel_axes),
            mode=spec.quant_weights,
            n_contract=1,
            use_bias=spec.bias,
            param_dtype=spec.param_dtype,
            name=name,
        )
    return nn.DenseGeneral(
        features=features,
        use_bias=spec.bias,
        name=name,
        kernel_init=nn.with_logical_partitioning(nn.initializers.normal(0.02), kernel_axes),
        bias_init=nn.with_logical_partitioning(nn.initializers.zeros, bias_axes),
        dtype=dtype,
        param_dtype=jnp.dtype(spec.param_dtype),
        dot_general=dot_general,
    )


def _seq_region(spec, x, *split_over_tp):
    """The sequence-parallel region (`parallel/sharding.seq_region`) for the products that take `x` [B, S, E] in or
    give it out, or None, which leaves them `jax.lax.dot_general` and their collectives the partitioner's: always for
    quantized weights, whose product is a kernel of its own."""
    from modalities_tpu.parallel.sharding import seq_region

    return seq_region(x.shape[0], x.shape[1], *split_over_tp) if spec.quant_weights == "none" else None


def _column_products(module, region, x, products):
    """`products(x, dot_general)`, a mixer's or an MLP's column-parallel products of the block's normed rows `x`,
    behind the region's opening edge: the rows are gathered over tp once for all of them, and the gather and the
    products are one checkpoint, so that what the backward keeps is the split rows and it gathers them again (the
    products themselves keep nothing else and are not run twice). With no region: `products(x, None)`."""
    if region is None:
        return products(x, None)
    from modalities_tpu.parallel.sharding import column_product, gather_seq

    def seq_gathered(_, rows):  # the name the scope takes: `attn.seq_gathered/q_attn/...`
        return jax.tree.map(lambda out: out[0], products(gather_seq(region, rows), column_product(region)))

    return nn.remat(seq_gathered, prevent_cse=True)(module, x)


class CausalSelfAttention(nn.Module):
    """GQA causal attention with separate q/k/v projections (reference :447-502).

    `decode=True` enables the autoregressive KV cache: k/v for incoming positions are
    written into a ``cache`` variable collection at the running index and attention
    runs the new queries against the full cached prefix (O(1) work per new token
    instead of re-forwarding the whole context). Prefill works by calling with the
    whole prompt at once (index advances by its length)."""

    spec: GPT2ModelSpec
    deterministic: bool = True
    decode: bool = False
    slot_spec: Optional[SlotDecodeSpec] = None
    kind: str = "attn"  # "swa": under the spec's window; each kind has its own rotary where `rope_parameters` gives one

    @nn.compact
    def __call__(self, x, slot=None, positions=None):
        spec = self.spec
        head_dim = spec.head_dim
        window = spec.sliding_window if self.kind == "swa" else None

        def products(rows, dot):
            return (_dense_general(spec, (spec.n_head_q, 2 * head_dim if spec.attn_output_gate else head_dim), "q_attn",
                                   ("embed", "heads", "head_dim"), x.dtype, dot)(rows),
                    _dense_general(spec, (spec.n_head_kv, head_dim), "k_attn", ("embed", "kv_heads", "head_dim"), x.dtype, dot)(rows),
                    _dense_general(spec, (spec.n_head_kv, head_dim), "v_attn", ("embed", "kv_heads", "head_dim"), x.dtype, dot)(rows))

        q, k, v = _column_products(self, _seq_region(spec, x, spec.n_head_q, spec.n_head_kv), x, products)
        gate = None
        if spec.attn_output_gate:  # a head's `2 * head_dim` are its query, then its gate
            q, gate = q[..., :head_dim], q[..., head_dim:]

        if spec.use_qk_norm and spec.qk_norm is not None:
            q = build_norm(spec.qk_norm, "q_norm", dtype=x.dtype)(q)
            k = build_norm(spec.qk_norm, "k_norm", dtype=x.dtype)(k)

        if self.slot_spec is not None:
            if self.slot_spec.kind == "paged":
                return self._paged_slot_attention(x, q, k, v, positions)
            return self._slot_attention(x, q, k, v, slot, positions)

        if self.decode:
            return self._decode_attention(x, q, k, v)

        if spec.use_rope:
            # inside a manual cp region (pp×cp pipeline body) x holds a LOCAL chunk:
            # phases must use the chunk's global offset or cross-chunk relative
            # positions in the ring come out shifted by cp_rank * S_local
            offset = cp_shard_offset(spec.context_parallel_axis, x.shape[1])
            with jax.named_scope(scopes.ROPE):
                rope = spec.rope_of(self.kind)
                cos, sin = _rope_tables(rotary_dim(head_dim, rope), x.shape[1], spec.rope_base_freq, dtype=x.dtype, offset=offset, rope=rope)
                q = apply_rope(q, cos, sin)
                k = apply_rope(k, cos, sin)

        with jax.named_scope(scopes.ATTN_CORE):
            q = with_logical_constraint(q, ("batch", "seq", "heads", "head_dim"), spec)
            k = with_logical_constraint(k, ("batch", "seq", "kv_heads", "head_dim"), spec)

            # attention-probability dropout is in force where the module is not deterministic; the ladder
            # (`ops/attention.causal_attention`) picks the function, this module's name for the kernel's rung handed in
            dropping = spec.dropout > 0.0 and not self.deterministic
            y = causal_attention(
                q, k, v, impl=spec.attention_impl, window=window, kept=spec.remat_keep_flash, cp_axis=spec.context_parallel_axis,
                dropout_rate=spec.dropout if dropping else 0.0, dropout_rng=self.make_rng("dropout") if dropping else None,
                flash=flash_attention, sm_scale=spec.attention_multiplier,
            )

            # named save point for selective-op remat (reference SAVE_DICT saves the SDPA
            # output, activation_checkpointing.py:67-83): save_list=("attn_out",) stores
            # this tensor and recomputes the rest of the block. That skips the XLA-fused
            # tiers' attention; it does NOT skip the Pallas kernel, whose backward reads
            # o and lse as the kernel wrote them (lse has no name here and y is a
            # transposed copy of o). Under `full` a block keeps those two instead
            # (`remat_keep_flash`, `flash_attention.KEPT_OUT` / `KEPT_LSE`)
            from jax.ad_checkpoint import checkpoint_name

            y = checkpoint_name(y, "attn_out")
        if gate is not None:
            with jax.named_scope(scopes.ATTN_GATE):
                y = (y.astype(jnp.float32) * jax.nn.sigmoid(gate.astype(jnp.float32))).astype(y.dtype)
        return self._project_out(x, y)

    def _decode_attention(self, x, q, k, v):
        """KV-cached attention step: new positions [B, S_in] appended at the running
        cache index; S_in > 1 = prefill, S_in == 1 = one decode step."""
        spec = self.spec
        head_dim = spec.head_dim
        b, s_in = x.shape[0], x.shape[1]
        max_len = spec.sequence_length

        cached_k = self.variable(
            "cache", "cached_key", jnp.zeros, (b, max_len, spec.n_head_kv, head_dim), k.dtype
        )
        cached_v = self.variable(
            "cache", "cached_value", jnp.zeros, (b, max_len, spec.n_head_kv, head_dim), v.dtype
        )
        cache_index = self.variable("cache", "cache_index", lambda: jnp.zeros((), jnp.int32))
        i = cache_index.value

        if spec.use_rope:
            with jax.named_scope(scopes.ROPE):
                cos, sin = _rope_tables(head_dim, max_len, spec.rope_base_freq, dtype=x.dtype)
                cos_i = jax.lax.dynamic_slice_in_dim(cos, i, s_in)
                sin_i = jax.lax.dynamic_slice_in_dim(sin, i, s_in)
                q = apply_rope(q, cos_i, sin_i)
                k = apply_rope(k, cos_i, sin_i)

        with jax.named_scope(scopes.ATTN_CORE):
            k_all = jax.lax.dynamic_update_slice(cached_k.value, k, (0, i, 0, 0))
            v_all = jax.lax.dynamic_update_slice(cached_v.value, v, (0, i, 0, 0))
            if not self.is_initializing():
                cached_k.value = k_all
                cached_v.value = v_all
                cache_index.value = i + s_in

            # position t of this call attends to cache positions <= i + t
            mask = jnp.arange(max_len)[None, :] <= (i + jnp.arange(s_in))[:, None]
            y = masked_attention(q, k_all, v_all, mask)
        return self._project_out(x, y)

    def _paged_slot_attention(self, x, q, k, v, positions):
        """Serving v2's paged (block-table) KV cache (serving/paged_cache.py).

        The cache is ONE global pool [num_blocks, block_size, Hkv, D] per layer;
        `positions` is a pytree of traced arrays:
          pos    — absolute positions: prefill [R, C] per token, decode [S] per slot
          tables — [B, MB] int32 block table per row (entry m = pool block holding
                   logical positions m*bs..(m+1)*bs-1; unused entries are 0 and
                   masked out by `pos`)
          wblk/woff — write coordinates per incoming token (prefill [R, C],
                   decode [S]); wblk >= num_blocks means "write nowhere" (idle
                   slots, padded prefill tails) — scatter mode="drop"
        The gathered K/V per row is position-ordered (table order == logical
        order), so the masked softmax is the same math as the ring row — which is
        what keeps paged mode inside the batch-invariance contract."""
        spec = self.spec
        ss = self.slot_spec
        head_dim = spec.head_dim
        nb, bs = ss.num_blocks, ss.block_size
        kv_int8 = ss.kv_quant == "int8"
        pos = positions["pos"]
        tables = positions["tables"]
        wblk, woff = positions["wblk"], positions["woff"]

        # int8 pools store quantized rows; a float32 scale per (block, row,
        # kv_head) rides ALONGSIDE in the same cache tree (rows land at
        # different steps, so the scale must be per written row, never per
        # block). Zero-init scales dequantize untouched rows to exactly the
        # bf16 path's zeros.
        pool_dtype = jnp.int8 if kv_int8 else k.dtype
        cached_k = self.variable(
            "cache", "cached_key", jnp.zeros, (nb, bs, spec.n_head_kv, head_dim), pool_dtype
        )
        cached_v = self.variable(
            "cache", "cached_value", jnp.zeros, (nb, bs, spec.n_head_kv, head_dim), pool_dtype
        )
        if kv_int8:
            k_scale = self.variable(
                "cache", "cached_key_scale", jnp.zeros, (nb, bs, spec.n_head_kv, 1), jnp.float32
            )
            v_scale = self.variable(
                "cache", "cached_value_scale", jnp.zeros, (nb, bs, spec.n_head_kv, 1), jnp.float32
            )

        if spec.use_rope:
            with jax.named_scope(scopes.ROPE):
                cos, sin = _rope_tables(head_dim, ss.capacity, spec.rope_base_freq, dtype=x.dtype)
                if ss.mode == "prefill":  # pos [R, C] -> per-token tables [R, C, D]
                    cos_i, sin_i = jnp.take(cos, pos, axis=0), jnp.take(sin, pos, axis=0)
                else:  # pos [S] -> [S, 1, D]
                    cos_i = jnp.take(cos, pos, axis=0)[:, None, :]
                    sin_i = jnp.take(sin, pos, axis=0)[:, None, :]
                q = apply_rope(q, cos_i, sin_i)
                k = apply_rope(k, cos_i, sin_i)

        with jax.named_scope(scopes.ATTN_CORE):
            # scatter the incoming k/v into the pool at explicit (block, offset)
            # coordinates; out-of-range blocks are dropped, never clamped.
            # Quantize-on-write: int8 mode quantizes each incoming row (symmetric
            # absmax over head_dim, one scale per kv-head) and scatters value and
            # scale with the SAME coordinates — a dropped write drops both.
            k_flat = k.reshape(-1, spec.n_head_kv, head_dim)
            v_flat = v.reshape(-1, spec.n_head_kv, head_dim)
            blk, off = wblk.reshape(-1), woff.reshape(-1)
            if kv_int8:
                from modalities_tpu.quant.core import quantize_per_channel

                k_flat, k_s = quantize_per_channel(k_flat, axis=-1)
                v_flat, v_s = quantize_per_channel(v_flat, axis=-1)
                ks_pool = k_scale.value.at[blk, off].set(k_s, mode="drop")
                vs_pool = v_scale.value.at[blk, off].set(v_s, mode="drop")
            k_pool = cached_k.value.at[blk, off].set(k_flat, mode="drop")
            v_pool = cached_v.value.at[blk, off].set(v_flat, mode="drop")
            if not self.is_initializing():
                cached_k.value = k_pool
                cached_v.value = v_pool
                if kv_int8:
                    k_scale.value = ks_pool
                    v_scale.value = vs_pool

            # gather each row's K/V tiles via its block table -> [B, MB*bs, Hkv, D];
            # gathered index IS the logical position (tables are position-ordered).
            # Dequant-at-gather: int8 mode gathers the quantized pool and its scale
            # pool through the same tables and broadcasts the multiply back to
            # x.dtype before the softmax.
            b_rows, mb = tables.shape

            def gather(pool):
                return jnp.take(pool, tables, axis=0).reshape(
                    b_rows, mb * bs, spec.n_head_kv, pool.shape[-1]
                )

            if kv_int8:
                k_all = (gather(k_pool).astype(jnp.float32) * gather(ks_pool)).astype(x.dtype)
                v_all = (gather(v_pool).astype(jnp.float32) * gather(vs_pool)).astype(x.dtype)
            else:
                k_all, v_all = gather(k_pool), gather(v_pool)
            key_pos = jnp.arange(mb * bs)
            if ss.mode == "prefill":
                mask = key_pos[None, None, :] <= pos[:, :, None]  # [R, C, L]
            else:
                mask = key_pos[None, None, :] <= pos[:, None, None]  # [S, 1, L]
            # recycled pool blocks hold whatever their previous owner wrote — and a
            # masked logit drops out of the softmax, but 0-weight x NaN/inf V still
            # poisons the output einsum. Zero every V row no query references, so a
            # dirty recycled block behaves exactly like a fresh zeroed one (K needs
            # no scrub: masked logits are replaced before the softmax).
            valid = mask.any(axis=-2)  # [B, L] key rows referenced by any query
            v_all = jnp.where(valid[:, :, None, None], v_all, 0.0)
            y = masked_attention(q, k_all, v_all, mask)
        return self._project_out(x, y)

    def _slot_attention(self, x, q, k, v, slot, positions):
        """Serving engine's batched ring KV cache (slot_spec; serving/engine.py).

        Unlike `_decode_attention` there is NO in-cache position counter: positions
        are explicit traced arguments, so one compiled step serves every slot state.
        Cache layout: [slots, capacity, Hkv, D] per layer (leading "layers" axis added
        by the scan). Prefill (batch 1): write a prompt chunk into row `slot` starting
        at scalar `positions`. Decode: write one token per slot at its own
        `positions[b]` and attend each row up to its own length — the math per slot is
        bitwise the batch=1 `_decode_attention` step (same table rows, same update,
        same masked softmax), which is what the batch-invariance test pins."""
        spec = self.spec
        ss = self.slot_spec
        head_dim = spec.head_dim
        cap, slots = ss.capacity, ss.slots

        cached_k = self.variable(
            "cache", "cached_key", jnp.zeros, (slots, cap, spec.n_head_kv, head_dim), k.dtype
        )
        cached_v = self.variable(
            "cache", "cached_value", jnp.zeros, (slots, cap, spec.n_head_kv, head_dim), v.dtype
        )

        if ss.mode == "prefill":
            s_in = x.shape[1]
            start = positions  # scalar: tokens occupy cache positions start..start+s_in-1
            if spec.use_rope:
                with jax.named_scope(scopes.ROPE):
                    cos, sin = _rope_tables(head_dim, cap, spec.rope_base_freq, dtype=x.dtype)
                    cos_i = jax.lax.dynamic_slice_in_dim(cos, start, s_in)
                    sin_i = jax.lax.dynamic_slice_in_dim(sin, start, s_in)
                    q = apply_rope(q, cos_i, sin_i)
                    k = apply_rope(k, cos_i, sin_i)
            with jax.named_scope(scopes.ATTN_CORE):
                row_k = jax.lax.dynamic_slice(
                    cached_k.value, (slot, 0, 0, 0), (1, cap, spec.n_head_kv, head_dim)
                )
                row_v = jax.lax.dynamic_slice(
                    cached_v.value, (slot, 0, 0, 0), (1, cap, spec.n_head_kv, head_dim)
                )
                k_all = jax.lax.dynamic_update_slice(row_k, k, (0, start, 0, 0))
                v_all = jax.lax.dynamic_update_slice(row_v, v, (0, start, 0, 0))
                if not self.is_initializing():
                    cached_k.value = jax.lax.dynamic_update_slice(cached_k.value, k_all, (slot, 0, 0, 0))
                    cached_v.value = jax.lax.dynamic_update_slice(cached_v.value, v_all, (slot, 0, 0, 0))
                mask = jnp.arange(cap)[None, :] <= (start + jnp.arange(s_in))[:, None]
                y = masked_attention(q, k_all, v_all, mask)
        else:  # decode: one new token per slot, each at its own position
            if spec.use_rope:
                with jax.named_scope(scopes.ROPE):
                    cos, sin = _rope_tables(head_dim, cap, spec.rope_base_freq, dtype=x.dtype)
                    cos_i = jnp.take(cos, positions, axis=0)[:, None, :]
                    sin_i = jnp.take(sin, positions, axis=0)[:, None, :]
                    q = apply_rope(q, cos_i, sin_i)
                    k = apply_rope(k, cos_i, sin_i)

            def write_row(buf, new, p):
                return jax.lax.dynamic_update_slice(buf, new, (p, 0, 0))

            with jax.named_scope(scopes.ATTN_CORE):
                k_all = jax.vmap(write_row)(cached_k.value, k, positions)
                v_all = jax.vmap(write_row)(cached_v.value, v, positions)
                if not self.is_initializing():
                    cached_k.value = k_all
                    cached_v.value = v_all
                mask = jnp.arange(cap)[None, None, :] <= positions[:, None, None]
                y = masked_attention(q, k_all, v_all, mask)
        return self._project_out(x, y)

    def _project_out(self, x, y):
        # no dropout on y here: the reference drops attention *probabilities* inside
        # the attention op (handled in __call__) and residuals after c_proj — never
        # the raw attention output (reference gpt2_model.py:676 resid_dropout(c_proj))
        spec = self.spec
        if spec.quant_weights != "none":
            out = QuantDenseGeneral(
                features=(spec.n_embd,),
                kernel_axes=("heads", "head_dim", "embed"),
                mode=spec.quant_weights,
                n_contract=2,  # kernel [H, D, E]: heads x head_dim contract
                use_bias=spec.bias,
                param_dtype=spec.param_dtype,
                name="c_proj",
            )(y)
        else:
            from modalities_tpu.parallel.sharding import scatter_seq

            region = _seq_region(spec, x, spec.n_head_q)
            out = nn.DenseGeneral(
                features=spec.n_embd,
                axis=(-2, -1),
                use_bias=spec.bias,
                name="c_proj",
                kernel_init=nn.with_logical_partitioning(
                    nn.initializers.normal(0.02), ("heads", "head_dim", "embed")
                ),
                bias_init=nn.with_logical_partitioning(nn.initializers.zeros, ("embed",)),
                dtype=x.dtype,
                param_dtype=jnp.dtype(spec.param_dtype),
                dot_general=None if region is None else scatter_seq(region),
            )(y)
        return nn.Dropout(rate=spec.dropout)(out, deterministic=self.deterministic or spec.dropout == 0.0)


class MLP(nn.Module):
    """GELU MLP (reference nn/mlp.py:6) or SwiGLU (reference models/model.py:75-153)."""

    spec: GPT2ModelSpec
    deterministic: bool = True

    @nn.compact
    def __call__(self, x):
        from modalities_tpu.parallel.sharding import scatter_seq

        spec = self.spec
        gelu = spec.activation == ActivationType.GELU.value
        hidden = spec.ffn_hidden if gelu else spec.swiglu_hidden
        region = _seq_region(spec, x, hidden)
        scatter = None if region is None else scatter_seq(region)
        if gelu:
            h = _column_products(self, region, x, lambda rows, dot: _dense_general(spec, hidden, "c_fc", ("embed", "mlp"), x.dtype, dot)(rows))
            h = with_logical_constraint(h, ("batch", "seq", "mlp"), spec)
            out = _dense_general(spec, spec.n_embd, "c_proj", ("mlp", "embed"), x.dtype, scatter)(nn.gelu(h))
        else:  # swiglu / fused_swiglu
            w, v = _column_products(self, region, x, lambda rows, dot: (_dense_general(spec, hidden, "W", ("embed", "mlp"), x.dtype, dot)(rows),
                                                                        _dense_general(spec, hidden, "V", ("embed", "mlp"), x.dtype, dot)(rows)))
            h = nn.silu(w) * v
            h = with_logical_constraint(h, ("batch", "seq", "mlp"), spec)
            out = _dense_general(spec, spec.n_embd, "W_2", ("mlp", "embed"), x.dtype, scatter)(h)
        return nn.Dropout(rate=spec.dropout)(out, deterministic=self.deterministic or spec.dropout == 0.0)


class _ResidualMerge(nn.Module):
    """A block's merge in the scaled form (`scale_residual_merge`): `(x + r_b) * r_s + (a + f_b) * f_s`, four `[n_embd]`
    float32 leaves (scales from 1, shifts from 0), computed in float32 and rounded once. `passes` (a flag, traced or
    not) marks the merge whose residual side has no scale or shift: the first layer's first, where the embedding passes
    as it is; its two leaves are in the tree (a scanned run's layers hold equal leaves) and get no gradient there."""

    @nn.compact
    def __call__(self, x, a, passes=None):
        leaf = lambda name, init: self.param(name, nn.with_logical_partitioning(init, ("embed",)), (x.shape[-1],), jnp.float32)  # noqa: E731
        r_s, r_b = leaf("residual_scale", nn.initializers.ones), leaf("residual_bias", nn.initializers.zeros)
        f_s, f_b = leaf("out_scale", nn.initializers.ones), leaf("out_bias", nn.initializers.zeros)
        if passes is not None:
            r_s, r_b = jnp.where(passes, 1.0, r_s), jnp.where(passes, 0.0, r_b)
        return ((x.astype(jnp.float32) + r_b) * r_s + (a.astype(jnp.float32) + f_b) * f_s).astype(x.dtype)


class GPT2Block(nn.Module):
    """Pre-norm residual block (reference :801-813)."""

    spec: GPT2ModelSpec
    deterministic: bool = True
    decode: bool = False
    slot_spec: Optional[SlotDecodeSpec] = None
    mixer: str = "attn"  # what sits in the mixer seat: "attn", "ssm", "swa" (attention under the spec's window), "cca", "gdn" or "ssd"
    ffn: str = "mlp"  # what sits in the feed-forward seat: "mlp" or "moe"; with "moe" the block returns (x, what the layer counted)

    def _merge(self, x, branch):
        """The residual stream plus a branch: times `residual_multiplier` where the config has one, in float32 and rounded once."""
        factor = self.spec.residual_multiplier
        return x + branch if factor is None else (x.astype(jnp.float32) + factor * branch.astype(jnp.float32)).astype(x.dtype)

    @nn.compact
    def __call__(self, x, slot=None, positions=None, router_state=None, layer_index=None):
        """`router_state`: the previous layer's, where the expert layer's router is handed one (`moe.state_width`; the block
        then returns `(x, counters, its own state)`). `layer_index` (a whole number or a traced one): which layer of the
        stack this is, for the one thing a scanned block cannot know otherwise (`scale_residual_merge`: the first layer's
        first merge)."""
        spec = self.spec
        x = with_logical_constraint(x, ("batch", "seq_sp", "embed"), spec)
        h = build_norm(spec.attn_norm, "attention_norm", dtype=x.dtype)(x)
        key_temperature = mixer_counted = None
        if self.mixer == "cca":
            a, key_temperature = CompressedConvAttention(spec, self.deterministic, name=scopes.CCA)(h)
        elif self.mixer == "gdn":
            a, mixer_counted = GatedDeltaNet(spec, self.deterministic, name=scopes.GDN)(h)
        elif self.mixer == "ssd":
            from modalities_tpu.models.gpt2.ssd import Mamba2Mixer

            a, mixer_counted = Mamba2Mixer(spec, self.deterministic, name=scopes.SSD)(h)
        elif self.mixer == "ssm":
            a = MambaMixer(spec, name=scopes.SSM)(h)
            a = nn.Dropout(rate=spec.dropout)(a, deterministic=self.deterministic or spec.dropout == 0.0)
        elif spec.mla is not None:
            a = LatentAttention(spec, self.deterministic, name="attn")(h)
            a = nn.Dropout(rate=spec.dropout)(a, deterministic=self.deterministic or spec.dropout == 0.0)
        else:
            # in a stack of both kinds of attention layer each goes under a name of its own: `block/window/attn/...`, `block/global/attn/...`
            kind_scope = (jax.named_scope(scopes.ATTN_WINDOW if self.mixer == "swa" else scopes.ATTN_GLOBAL)
                          if spec.has_window else contextlib.nullcontext())
            with kind_scope:
                a = CausalSelfAttention(
                    spec, self.deterministic, self.decode, slot_spec=self.slot_spec, kind=self.mixer, name="attn"
                )(h, slot, positions)
        if spec.post_attn_norm is not None:
            a = build_norm(spec.post_attn_norm, scopes.POST_ATTENTION_NORM, dtype=x.dtype)(a)
        with jax.named_scope(scopes.RESIDUAL):
            if spec.scale_residual_merge:
                x = _ResidualMerge(name="attn_merge")(x, a, passes=None if layer_index is None else layer_index == 0)
            else:
                x = self._merge(x, a)
        h2 = build_norm(spec.ffn_norm, "ffn_norm", dtype=x.dtype)(x)
        counters = None
        if self.ffn == "moe" and spec.router_state_width:
            m, counters, router_state = MoE(spec, self.deterministic, name=scopes.MOE)(h2, router_state)
        elif self.ffn == "moe":
            m, counters = MoE(spec, self.deterministic, name=scopes.MOE)(h2)
        else:
            m = MLP(spec, self.deterministic, name="mlp")(h2)
        if spec.post_ffn_norm is not None:
            m = build_norm(spec.post_ffn_norm, scopes.POST_FFN_NORM, dtype=x.dtype)(m)
        with jax.named_scope(scopes.RESIDUAL):
            x = _ResidualMerge(name="ffn_merge")(x, m) if spec.scale_residual_merge else self._merge(x, m)
        if counters is not None and key_temperature is not None:
            counters = jnp.concatenate([counters, key_temperature[None]])
        if counters is not None and spec.mixer_counters:  # a `gdn` layer's two or an `ssd` layer's one; zeros from a layer of the same stack whose mixer is another
            counters = jnp.concatenate([counters, mixer_counted if mixer_counted is not None else jnp.zeros((spec.mixer_counters,), jnp.float32)])
        if spec.debug_print_activations == "shape":
            jax.debug.print(
                "block out shape=" + str(tuple(x.shape)) + " dtype=" + str(x.dtype)
            )
        elif spec.debug_print_activations == "stats":
            xf = x.astype(jnp.float32)
            jax.debug.print(
                "block out mean={m:.6f} std={s:.6f} nan={n}",
                m=jnp.mean(xf),
                s=jnp.std(xf),
                n=jnp.isnan(xf).sum(),
            )
        if self.ffn == "moe" and spec.router_state_width:
            return x, counters, router_state
        return x if counters is None else (x, counters)


def _layer_remats(spec: "GPT2ModelSpec", layer_index: int) -> bool:
    """Whether block `layer_index` is remat-wrapped (reference
    ActivationCheckpointing semantics: SELECTIVE_LAYER remats every ac_freq-th
    block; FULL/SELECTIVE_OP remat every block)."""
    if spec.remat_variant in ("full", "selective_op"):
        return True
    if spec.remat_variant == "selective_layer":
        return layer_index % max(spec.remat_freq, 1) == 0
    return False


def _remat_block_cls(spec: "GPT2ModelSpec"):
    """GPT2Block wrapped in nn.remat with the spec's checkpoint policy (shared by
    the scan body and the unrolled-blocks path so their remat behavior never
    diverges)."""
    policy = None
    if spec.remat_variant == "selective_op":
        from modalities_tpu.training.activation_checkpointing import save_list_policy

        policy = save_list_policy(spec.remat_save_list)
    elif spec.remat_variant == "full" and (spec.remat_keep_flash or spec.remat_keep_rule):
        # the block's input and, as the plan said, the flash kernel's o and lse and the gated delta rule's o and group states;
        # a block with no such call (state-space, the ffn) has no value under these names and keeps what `None` kept: its input
        from modalities_tpu.ops import gated_delta_rule as rule
        from modalities_tpu.ops.pallas.flash_attention import KEPT_LSE, KEPT_OUT

        names = (KEPT_OUT, KEPT_LSE) * spec.remat_keep_flash + (rule.KEPT_OUT, rule.KEPT_STATES) * spec.remat_keep_rule
        policy = jax.checkpoint_policies.save_only_these_names(*names)
    return nn.remat(GPT2Block, prevent_cse=False, policy=policy)


def head_project(spec: "GPT2ModelSpec", inner_params, h):
    """fp32 vocab logits from post-lm_head_norm hidden `h` — the single source of
    the tied/untied head projection for every params-based (non-module) path:
    chunked head+loss, the scheduled pipeline's head stage. Applies the
    vocab_logits constraint so loss-parallel (vocab over tp) works identically to
    the in-module head."""
    h = h.astype(jnp.float32)
    if spec.use_weight_tying:
        logits = jnp.einsum("bse,ve->bsv", h, inner_params["wte"].astype(jnp.float32))
    else:
        head = inner_params["lm_head"]
        kernel = head["kernel"].astype(jnp.float32)
        if "scale" in head:  # weight-only quantized head: dequant per vocab column
            kernel = kernel * head["scale"].astype(jnp.float32)
        logits = h @ kernel
    return with_logical_constraint(logits, ("batch", "seq", "vocab_logits"))


class _BlockScanBody(nn.Module):
    """scan body: carry = activations; applies (optionally remat-wrapped) block. Where the expert layer's router is
    handed a state from the layer before (`moe.state_width`) the carry is `(activations, that state)`, and where the
    block must know which layer it is (`scale_residual_merge`) the scan's input is the layer's index; every other
    run carries the activations alone over no input, as it always did."""

    spec: GPT2ModelSpec
    deterministic: bool = True
    decode: bool = False
    mixer: str = "attn"
    ffn: str = "mlp"

    @nn.compact
    def __call__(self, carry, layer_index):
        spec = self.spec
        block_cls = GPT2Block
        if spec.remat_variant in ("full", "selective_layer", "selective_op") and not self.decode:
            if spec.remat_variant == "selective_layer" and spec.remat_freq > 1:
                raise ValueError(
                    "selective_layer activation checkpointing with ac_freq > 1 needs "
                    "per-layer remat decisions, which the scan-over-layers "
                    "representation cannot express (one traced body serves every "
                    "layer). Set the model's scan_layers=False (unrolled blocks) to "
                    "use ac_freq > 1, or use ac_freq=1 / 'full'."
                )
            block_cls = _remat_block_cls(spec)
        block = block_cls(spec, self.deterministic, self.decode, mixer=self.mixer, ffn=self.ffn, name="block")
        if self.ffn == "moe" and spec.router_state_width:
            x, counters, state = block(carry[0], None, None, carry[1], layer_index)
            return (x, state), counters
        out = block(carry) if layer_index is None else block(carry, None, None, None, layer_index)
        return out if self.ffn == "moe" else (out, None)  # what an expert layer counted is the scan's output, one row a layer


class _LayerRun(nn.Module):
    """One run of `length` equal layers of a stack that holds more than one kind: the
    same scan over stacked blocks as the dense decoder's, under the run's own name
    (`run_<i>/blocks/block/...`)."""

    spec: GPT2ModelSpec
    deterministic: bool
    mixer: str
    length: int
    ffn: str = "mlp"
    first: int = 0  # the index of the run's first layer in the stack

    @nn.compact
    def __call__(self, x, router_state=None):
        """`router_state`: what the run before handed on (None before the first layer: zeros go in, which add nothing).
        A run that carries one returns `(x, counters, the last layer's state)`."""
        scanned = nn.scan(
            _BlockScanBody,
            variable_axes={"params": 0},
            split_rngs={"params": True, "dropout": True},
            length=self.length,
            metadata_params={nn.meta.PARTITION_NAME: "layers"},
        )(self.spec, self.deterministic, False, self.mixer, self.ffn, name="blocks")
        layers = self.first + jnp.arange(self.length, dtype=jnp.int32) if self.spec.scale_residual_merge else None
        with jax.named_scope(scopes.LAYER_CARRY):
            if self.ffn == "moe" and self.spec.router_state_width:
                if router_state is None:
                    router_state = jnp.zeros((*x.shape[:2], self.spec.router_state_width), jnp.float32)
                (x, router_state), counters = scanned((x, router_state), layers)
                return x, counters, router_state
            return scanned(x, layers)  # (x, what the expert layers counted [length, 3 + E] or None)


class _SlotBlockScanBody(nn.Module):
    """scan body for the serving slot cache: carry = (activations, slot, positions).
    slot/positions must ride the carry — they are traced values, and module
    attributes must be static. Inner block named "block" so trained params line up
    with the `_BlockScanBody` layout exactly."""

    spec: GPT2ModelSpec
    deterministic: bool = True
    slot_spec: Optional[SlotDecodeSpec] = None

    @nn.compact
    def __call__(self, carry, _):
        x, slot, positions = carry
        x = GPT2Block(
            self.spec, self.deterministic, False, slot_spec=self.slot_spec, name="block"
        )(x, slot, positions)
        return (x, slot, positions), None


_NO_LATENT_CACHE = (
    "this model has latent attention (mla_config), and serving it needs a latent cache (the 512-wide latent and the shared "
    "rotary key of every position, read through the absorbed form of the projections) in place of the per-head KV cache, "
    "which serving/ does not have: it trains, it does not decode"
)
_NO_DECODE_THROUGH_DISPATCH = (
    "this model has expert layers (moe_config), and serving them needs a decode path through the dispatch (a step of a "
    "few tokens a slot sorted to the held experts, beside the prefill's), which serving/ does not have: it trains, it does not decode"
)
_NO_RECURRENT_STATE_CACHE = (
    "this model has state-space layers (attn_layer_period), and serving them needs a recurrent-state cache "
    "(the convolution's last taps and the scan's state [d_inner, d_state] for every sequence and layer, beside "
    "the attention layers' KV cache), which serving/ does not have: it trains, it does not decode"
)


_NO_CACHE_ENTRY_PER_WALK = (
    "this model walks its layers several times (loop_config), and serving it needs a cache entry for every walk AND layer "
    "(a position's key and value differ from walk to walk: total_ut_steps x n_layer entries), with an exit by the gate's "
    "cumulative distribution beside it, which serving/ does not have: it trains, it does not decode"
)
_NO_STAGE_PLAN_THAT_CLOSES = (
    "pipeline parallelism hands an activation from each stage to the next and stops at the last; a model that walks its "
    "layers several times (loop_config) needs a stage plan that closes on itself (the last stage feeds the first again, "
    "total_ut_steps times, the final norm between), which parallel/pipeline*.py does not have. Run it without a pp axis."
)


_NO_CACHE_BY_LAYER_KIND = (
    "this model has window layers (layer_types: sliding_attention, sliding_window), and serving them needs a cache allocator by "
    "layer kind (a window layer keeps its last sliding_window positions and frees the blocks behind them, a full_attention layer "
    "keeps all: serving/paged_cache.py gives every layer the same table) and the window in the decode and prefill masks, which "
    "serving/ does not have: it trains, it does not decode"
)
_NO_WINDOW_IN_THE_RING = (
    "this model has window layers (layer_types: sliding_attention), and context parallelism runs attention as a ring over the cp "
    "axis (parallel/ring_attention.py), which carries no window: every hop's block would need the window's edge against the hop's "
    "offset, and the hops wholly behind it skipped. Run it without a cp axis."
)
_NO_WINDOW_LAYERS_IN_STAGES = (
    "this model has window layers (layer_types), a stack of two kinds of attention layer; pipeline parallelism splits ONE stack "
    "of equal layers over its stages (parallel/pipeline*.py), and a stage plan that knows a layer's kind is not written. Run it without a pp axis."
)


_NO_CONV_AND_SHIFT_STATE_CACHE = (
    "this model has compressed convolutional attention (cca_config), and serving it needs a cache of convolution and shift state "
    "beside keys and values (of every sequence and layer the previous position's normed input for the shifted value heads, and "
    "the last cca_time0 - 1 and cca_time1 - 1 positions of the latent before each convolution), which serving/paged_cache.py and "
    "serving/engine.py do not have: it trains, it does not decode"
)
_NO_CONV_ACROSS_A_SHARD_EDGE = (
    "this model has compressed convolutional attention (cca_config), whose two convolutions over the sequence and whose value shift "
    "read the positions before a token: under context parallelism a shard's first positions would need the last of the shard before "
    "(a halo exchange beside parallel/ring_attention.py's ring), which is not written. Run it without a cp axis."
)
_NO_ROUTER_STATE_ACROSS_STAGES = (
    "this model's expert layers hand their router's state from layer to layer (moe_config.use_eda); pipeline parallelism hands ONE "
    "activation from each stage to the next (parallel/pipeline*.py), and a second array across the stage boundary is not written. "
    "Run it without a pp axis."
)


_NO_MATRIX_STATE_CACHE = (
    "this model has layers of the gated delta rule (gdn_config, layer_types: linear_attention), and serving them needs a cache of the "
    "convolution's last linear_conv_kernel_dim - 1 inputs and of the [value heads, key_head_dim, value_head_dim] state of every sequence "
    "and layer beside the attention layers' keys and values, which serving/paged_cache.py and serving/engine.py do not have: it trains, "
    "it does not decode"
)
_NO_STATE_ACROSS_A_SHARD_EDGE = (
    "this model has layers of the gated delta rule (gdn_config), whose state and whose convolution over the sequence run from the row's "
    "first position to its last: under context parallelism a shard would need the state and the last taps of the shard before (a hand-off "
    "along the cp axis beside parallel/ring_attention.py's ring), which is not written. Run it without a cp axis."
)
_NO_SSD_STATE_CACHE = (
    "this model has Mamba-2 layers (ssd_config, layer_types: mamba), and serving them needs a cache of the convolution's last mamba_d_conv - 1 "
    "inputs (x, B and C) and of the [heads, mamba_d_head, mamba_d_state] state of every sequence and layer beside the attention layers' keys "
    "and values, which serving/paged_cache.py and serving/engine.py do not have: it trains, it does not decode"
)
_NO_SSD_STATE_ACROSS_A_SHARD_EDGE = (
    "this model has Mamba-2 layers (ssd_config), whose state and whose convolution over the sequence run from the row's first position to "
    "its last: under context parallelism a shard would need the state and the last taps of the shard before (the state's hand-off along "
    "the cp axis beside parallel/ring_attention.py's ring), which is not written. Run it without a cp axis."
)
_NO_NORM_SUM_ACROSS_TP = (
    "this model has Mamba-2 layers (ssd_config) with mamba_n_groups 1: split over a tp axis the heads would share one B and C and the gated "
    "norm's mean square would run across the shards (one scalar a token to all-reduce before the norm's scale), which is not written. Run it "
    "without a tp axis; a chip's share of the heads is ssd_config.heads_held."
)
_NO_MULTIPLIERS_IN_THE_CACHED_FORWARD = (
    "this model scales its table's output, its branches, its scores or its logits (embedding_multiplier, residual_multiplier, "
    "attention_multiplier, logits_scaling), and the cached forwards of serving (the decode, slot and paged paths of CausalSelfAttention, "
    "whose masked softmax divides by sqrt(head_dim)) do not carry the scores' scale: it trains, it does not decode"
)
_NO_MULTIPLIERS_IN_STAGES = (
    "this model scales its table's output and its logits (embedding_multiplier, logits_scaling); pipeline parallelism embeds and projects in "
    "stage functions of its own (GPT2LLM.pp_stage_fns), which do not carry them. Run it without a pp axis."
)
_NO_GATE_IN_THE_CACHED_FORWARD = (
    "this model gates its attention's output (attn_output_gate), and the cached forwards of serving (the decode, slot and paged paths "
    "of CausalSelfAttention) do not carry the gate to the output projection: it trains, it does not decode"
)


def refuse_uneven_heads(spec: "GPT2ModelSpec") -> None:
    """A tp axis that does not divide the gated delta rule's key heads or the attention's key/value heads is refused by name: the
    rules would leave such a dim whole on every chip (`parallel/sharding.fit_spec_to_shape`) and split its neighbours."""
    from modalities_tpu.parallel.sharding import installed_axis_size

    tp = installed_axis_size("tp")
    if spec.gdn is not None and tp > 1 and (spec.gdn.key_heads % tp or spec.n_head_kv % tp):
        raise NotImplementedError(
            f"a tp axis of {tp} does not divide the gated delta rule's {spec.gdn.key_heads} key heads and the attention's {spec.n_head_kv} "
            "key/value heads: a value head's state stays with its key head, and a split that leaves one of the two whole is not written. "
            "Run it with a tp axis that divides both, or without one.")


KEY_TEMPERATURE = "cca_key_temperature"  # counted by a model whose mixer is `cca`: the mean of the learned key temperatures

_MIXER_OF = {SLIDING: "swa", FULL: "attn", HYBRID: "cca", LINEAR: "gdn", MAMBA: "ssd", ATTENTION: "attn"}  # a published layer type as the block's mixer seat names it


def refuse_serving(spec: "GPT2ModelSpec") -> None:
    """A cache, or a forward that reads one, is refused by the name of what serving lacks for this model."""
    for missing, reason in ((spec.has_ssm, _NO_RECURRENT_STATE_CACHE), (spec.mla is not None, _NO_LATENT_CACHE),
                            (spec.has_window, _NO_CACHE_BY_LAYER_KIND), (spec.cca is not None, _NO_CONV_AND_SHIFT_STATE_CACHE),
                            (spec.gdn is not None, _NO_MATRIX_STATE_CACHE), (spec.attn_output_gate, _NO_GATE_IN_THE_CACHED_FORWARD),
                            (spec.ssd is not None, _NO_SSD_STATE_CACHE), (spec.has_multipliers, _NO_MULTIPLIERS_IN_THE_CACHED_FORWARD),
                            (spec.has_moe, _NO_DECODE_THROUGH_DISPATCH), (spec.loop is not None, _NO_CACHE_ENTRY_PER_WALK)):
        if missing:
            raise NotImplementedError(reason)


def _exit_gate() -> nn.Dense:
    """The gate read off every walk's exit, float32. From zero: every token's gate at 1/2, and nothing reaches
    the stack through the gate before it has moved."""
    return nn.Dense(
        1, name=scopes.EXIT_GATE, dtype=jnp.float32, param_dtype=jnp.float32,
        kernel_init=nn.with_logical_partitioning(nn.initializers.zeros, ("embed", None)),
        bias_init=nn.with_logical_partitioning(nn.initializers.zeros, (None,)),
    )


def _stack_bytes_a_shard(spec: "GPT2ModelSpec", stacked, x) -> int:
    """Bytes of the layers' stacked gradient `[L, ...]` (the weights' shapes and dtypes) as one shard holds them
    under the rules and mesh the step installed; the whole stack's where none are."""
    from modalities_tpu.parallel.sharding import shard_shape

    one = jax.eval_shape(GPT2Block(spec, mixer=spec.kinds[0]).init, jax.random.PRNGKey(0), jax.ShapeDtypeStruct(x.shape, x.dtype))
    axes = nn.get_partition_spec(one)["params"]  # a layer's logical axes; the scan puts `layers` before them
    return sum(
        math.prod(shard_shape(leaf.shape, ("layers", *names))) * leaf.dtype.itemsize
        for leaf, names in zip(jax.tree.leaves(stacked), jax.tree.leaves(axes, is_leaf=lambda a: isinstance(a, jax.sharding.PartitionSpec)))
    )


def _walks_in_place(spec: "GPT2ModelSpec", deterministic: bool, carry_dtype):
    """The walks of a looped stack under full remat as a function of the layers' stacked tree `[L, ...]`, the
    tree shared beside it (`lm_head_norm`, `exit_gate`), the embedded tokens and a dropout key an application
    (`[T, L]` or None), with its backward written by hand: `GPT2Module._walks` says why. The forward is the
    two loops autodiff's form runs, and keeps every application's input `[T, L, B, S, E]` and every walk's
    last block output `[T, B, S, E]` (the final norm's input). Scope names are the step's vocabulary:
    `layer_carry` round the layer loop, `blocks/block/...` on a block, `lm_head_norm`, `exit_gate`."""
    layers, walks, gated = spec.n_layer, spec.loop.total_ut_steps, spec.loop.exit_gate

    def block(layer, x, key):
        with jax.named_scope("blocks"):
            return GPT2Block(spec, deterministic, mixer=spec.kinds[0], name="block").apply(
                {"params": layer}, x, rngs=None if key is None else {"dropout": key})

    def close(shared, u):
        h = build_norm(spec.lm_head_norm, "lm_head_norm").apply({"params": shared["lm_head_norm"]}, u)
        h = with_logical_constraint(h, ("batch", "seq_sp", "embed"))
        gate = _exit_gate().apply({"params": shared[scopes.EXIT_GATE]}, h.astype(jnp.float32))[..., 0] if gated else None
        return h.astype(carry_dtype), (h, gate)

    def forward(stacked, shared, x, keys):
        def walk(carry, walk_keys):
            def layer(c, per_layer):
                return block(per_layer[0], c, per_layer[1]), c

            with jax.named_scope(scopes.LAYER_CARRY):
                u, inputs = jax.lax.scan(layer, carry, (stacked, walk_keys), length=layers)
            carry, exits = close(shared, u)
            return carry, (exits, inputs, u)

        _, (exits, inputs, pre_norm) = jax.lax.scan(walk, x, keys, length=walks)
        return exits, (inputs, pre_norm)

    @jax.custom_vjp
    def stack(stacked, shared, x, keys):
        return forward(stacked, shared, x, keys)[0]

    def stack_fwd(stacked, shared, x, keys):
        exits, kept = forward(stacked, shared, x, keys)
        return exits, (stacked, shared, keys, *kept)

    def recomputed(fn):
        """`fn` for `jax.vjp`, which writes its transforms round the FIRST scope opened inside it
        (`transpose(jvp(<scope>))/...`): that scope is the name JAX's own remat gives a recomputed forward,
        so that the names readers select by (`blocks/block/...`, `lm_head_norm`) stand whole after it."""
        def named(*args):
            with jax.named_scope("rematted_computation"):
                return fn(*args)
        return named

    def stack_bwd(residuals, d_exits):
        stacked, shared, keys, inputs, pre_norm = residuals
        zeros = lambda tree: jax.tree.map(jnp.zeros_like, tree)  # noqa: E731

        def walk(carry, per_walk):
            t, u, d_exit = per_walk
            acc, acc_shared, d_carry = carry
            d_shared, d_u = jax.vjp(recomputed(close), shared, u)[1]((d_carry, d_exit))
            acc_shared = jax.tree.map(jnp.add, acc_shared, d_shared)

            def layer(c, per_layer):
                weights, l = per_layer
                acc, d = c
                x_in = jax.lax.dynamic_slice(inputs, (t, l, *(0,) * d.ndim), (1, 1, *d.shape)).reshape(d.shape)
                d_weights, d = jax.vjp(recomputed(lambda w, a: block(w, a, None if keys is None else keys[t, l])), weights, x_in)[1](d)
                # the accumulator's slice read, added, written where it stands: not `.at[l].add`, a scatter under a traced index
                acc = jax.tree.map(lambda a, g: jax.lax.dynamic_update_index_in_dim(
                    a, jax.lax.dynamic_index_in_dim(a, l, 0, keepdims=False) + g, l, 0), acc, d_weights)
                return (acc, d), None

            with jax.named_scope(scopes.LAYER_CARRY):
                (acc, d_carry), _ = jax.lax.scan(layer, (acc, d_u), (stacked, jnp.arange(layers)), reverse=True)
            return (acc, acc_shared, d_carry), None

        start = (zeros(stacked), zeros(shared), jnp.zeros(inputs.shape[2:], carry_dtype))
        (acc, acc_shared, d_x), _ = jax.lax.scan(walk, start, (jnp.arange(walks), pre_norm, d_exits), reverse=True)
        return acc, acc_shared, d_x, None

    stack.defvjp(stack_fwd, stack_bwd)
    return stack


class GPT2Module(nn.Module):
    """The linen module behind GPT2LLM: wte/wpe -> blocks -> lm_head_norm -> lm_head.

    `decode=True`: autoregressive KV-cache mode — pass tokens for NEW positions only;
    per-layer k/v caches and the running position live in the ``cache`` collection.
    `output_hidden=True`: stop after lm_head_norm and return the [B,S,E] hidden
    state instead of logits (the chunked head+loss path computes the vocab
    projection per sequence chunk outside the module).
    `output_exits=True` (a looped model with its exit gate, training): return
    `{"exits": [T, B, S, E], "gate_logits": [T, B, S]}`, every walk's exit and the gate read off
    it, for the loss over all exits (`loss_functions.LoopedExitLoss`)."""

    spec: GPT2ModelSpec
    deterministic: bool = True
    decode: bool = False
    output_hidden: bool = False
    slot_spec: Optional[SlotDecodeSpec] = None
    output_exits: bool = False

    def _walks(self, x):
        """`loop.total_ut_steps` walks of the layer scan over ONE parameter tree, traced once. Returns every
        walk's exit `[T, B, S, E]` and gate logits `[T, B, S]` (None without a gate). The tree is the dense
        decoder's (`blocks/block/...`, `lm_head_norm`) with `exit_gate` beside it, whatever T is.

        A walk is the layer scan, then the final norm, then the gate; the walks are an outer loop over it.
        What differs by remat variant is who writes the backward, because a shared weight's gradient is a
        sum over the walks and the transpose decides what that sum costs:

        - **full remat** (a block keeps its input and nothing else): `_walks_in_place`, whose backward is
          written by hand (`jax.custom_vjp`). It walks the `T x L` applications last to first, recomputes each
          block from its kept input (`jax.vjp`: what full remat means), and adds the block's weight gradient
          into ONE accumulator `[L, ...]` at that layer's index, slice read, added, slice written
          (`dynamic_update_index_in_dim` on the loop's carry, which XLA updates where it stands): one copy of
          the layers' gradient, and no byte of it moved that the sum does not need.
        - **no remat, `selective_op`** (residuals only autodiff knows): an outer `nn.scan` with the parameters
          broadcast, its body the layer scan with the weights as scanned inputs. Autodiff transposes that to an
          inner scan that emits a walk's gradient as a stack `[L, ...]` and an outer scan that carries the
          accumulator and adds the whole stack once a walk (`add_any`): two copies of the layers' gradient,
          each read and written once more a walk than the products need (28.8 ms of 941 and 1.53 GiB at
          16 x 51.4 M parameters, four walks: PERF.md, PR 37).

        Two shorter forms were not taken. The weights as a closed-over stack indexed by the layer inside one
        scan: a dynamic index transposes to an add of the whole stack every LAYER. `acc.at[l].add(dW)` in the
        hand-written rule: with a traced index that is a scatter, which the chip runs a row at a time."""
        spec, loop = self.spec, self.spec.loop
        carry_dtype = x.dtype

        def walk(mdl, carry, _):
            scanned = nn.scan(
                _BlockScanBody,
                variable_axes={"params": 0},
                split_rngs={"params": True, "dropout": True},
                length=spec.n_layer,
                metadata_params={nn.meta.PARTITION_NAME: "layers"},
            )(spec, mdl.deterministic, False, spec.kinds[0], name="blocks")
            with jax.named_scope(scopes.LAYER_CARRY):
                u, _ = scanned(carry, None)
            h = build_norm(spec.lm_head_norm, "lm_head_norm")(u)
            h = with_logical_constraint(h, ("batch", "seq_sp", "embed"))
            gate = _exit_gate()(h.astype(jnp.float32))[..., 0] if loop.exit_gate else None
            return h.astype(carry_dtype), (h, gate)

        if self.is_initializing():  # one walk makes the tree: no walk has a parameter of its own
            _, (h, gate) = walk(self, x, None)
            return h[None], None if gate is None else gate[None]
        layers, walks = spec.n_layer, loop.total_ut_steps
        in_place = spec.remat_variant == "full"
        kept = walks * layers if spec.remat_variant is not None else None  # under remat a block keeps its input and nothing else
        params = nn.meta.unbox(self.variables["params"])
        get_active_telemetry().emit_event_once("loop_plan", {
            "walks": walks, "layers": layers, "applications": walks * layers, "exit_gate": loop.exit_gate,
            "block_inputs_kept": kept, "block_input_bytes": None if kept is None else kept * x.size * x.dtype.itemsize,
            "head_rows": (walks if loop.exit_gate and self.output_exits else 1) * x.shape[0] * x.shape[1],
            "shared_gradient": "in_place" if in_place else "summed_by_walk", "shared_gradient_copies": 1 if in_place else 2,
            "shared_gradient_bytes": _stack_bytes_a_shard(spec, params["blocks"]["block"], x),
        })
        with jax.named_scope(scopes.LOOP):  # the carry between walks, and in the backward the sum of a weight's gradient over them
            if in_place:
                keys = None
                if spec.dropout > 0.0 and not self.deterministic:  # a key an application, the recomputed block's the forward's
                    keys = jax.random.split(self.make_rng("dropout"), (walks, layers))
                shared = {name: params[name] for name in ("lm_head_norm", scopes.EXIT_GATE) if name in params}
                return _walks_in_place(spec, self.deterministic, carry_dtype)(params["blocks"]["block"], shared, x, keys)
            _, exits = nn.scan(
                walk, variable_broadcast="params", split_rngs={"params": False, "dropout": True}, length=walks,
            )(self, x, None)
        return exits

    @nn.compact
    def __call__(self, input_ids, slot=None, positions=None):
        spec = self.spec
        compute_dtype = jnp.dtype(spec.compute_dtype)
        param_dtype = jnp.dtype(spec.param_dtype)
        wte = self.param(
            "wte",
            nn.with_logical_partitioning(nn.initializers.normal(0.02), ("vocab", "embed")),
            (spec.vocab_size, spec.n_embd),
            param_dtype,
        )
        # FSDP-gather the table's embed dim BEFORE the lookup (keep vocab on tp for
        # the vocab-parallel gather+psum): if the gather output inherits wte's
        # embed-over-dp_shard sharding, GSPMD can only reach the (batch, seq)
        # activation layout via an involuntary full rematerialization of the
        # activations (spmd_partitioner.cc:652 warnings in the pp×dp×cp dryrun) —
        # at scale that all-gathers [B,S,E] per step instead of the [V,E] table
        with jax.named_scope(scopes.WTE):
            wte_lookup = with_logical_constraint(wte, ("vocab", "embed_lookup"), explicit=True)
            x = embedding_lookup(wte_lookup, input_ids)
            if spec.embedding_multiplier is not None:
                x = x.astype(jnp.float32) * spec.embedding_multiplier
            x = x.astype(compute_dtype)
            # the lookup's own output, whole over tp: the partial sums of a table whose vocabulary tp splits are summed
            # once; the stream's split below is then a slice a chip (asked for here, a reduce-scatter comes with
            # collective-permutes of the whole output round it, forward and backward: PERF.md, PR 51)
            x = with_logical_constraint(x, ("batch", "seq", "embed"))
        if spec.poe_type == PositionTypes.ABSOLUTE.value:
            wpe = self.param(
                "wpe",
                nn.with_logical_partitioning(nn.initializers.normal(0.02), ("seq_param", "embed")),
                (spec.sequence_length, spec.n_embd),
                param_dtype,
            )
            if self.slot_spec is not None:
                # positions are explicit (no wpe_index counter): ring prefill gets
                # the scalar chunk start, decode a per-slot position vector; paged
                # mode passes a pytree with per-token absolute positions
                pos_arr = positions["pos"] if isinstance(positions, dict) else positions
                if self.slot_spec.kind == "paged" and self.slot_spec.mode == "prefill":
                    # pos [R, C] per token (cross-request packed rows)
                    x = x + jnp.take(wpe, pos_arr, axis=0).astype(compute_dtype)
                elif self.slot_spec.mode == "prefill":
                    pos = pos_arr + jnp.arange(input_ids.shape[1])
                    x = x + jnp.take(wpe, pos, axis=0)[None].astype(compute_dtype)
                else:
                    x = x + jnp.take(wpe, pos_arr, axis=0)[:, None, :].astype(compute_dtype)
            elif self.decode:
                pos_var = self.variable("cache", "wpe_index", lambda: jnp.zeros((), jnp.int32))
                pos = pos_var.value + jnp.arange(input_ids.shape[1])
                if not self.is_initializing():
                    pos_var.value = pos_var.value + input_ids.shape[1]
                x = x + jnp.take(wpe, pos, axis=0)[None].astype(compute_dtype)
            else:
                x = x + wpe[None, : input_ids.shape[1], :].astype(compute_dtype)
        x = nn.Dropout(rate=spec.dropout)(x, deterministic=self.deterministic or spec.dropout == 0.0)
        x = with_logical_constraint(x, ("batch", "seq_sp", "embed"))

        if self.decode or self.slot_spec is not None:
            refuse_serving(spec)
        if spec.has_window and spec.context_parallel_axis is not None:
            raise NotImplementedError(_NO_WINDOW_IN_THE_RING)
        if spec.has_window and spec.pipeline_axis is not None:
            raise NotImplementedError(_NO_WINDOW_LAYERS_IN_STAGES)
        if spec.cca is not None and spec.context_parallel_axis is not None:
            raise NotImplementedError(_NO_CONV_ACROSS_A_SHARD_EDGE)
        if spec.router_state_width and spec.pipeline_axis is not None:
            raise NotImplementedError(_NO_ROUTER_STATE_ACROSS_STAGES)
        if spec.gdn is not None:
            if spec.context_parallel_axis is not None:
                raise NotImplementedError(_NO_STATE_ACROSS_A_SHARD_EDGE)
            refuse_uneven_heads(spec)
        if spec.ssd is not None:
            from modalities_tpu.parallel.sharding import installed_axis_size

            if spec.context_parallel_axis is not None:
                raise NotImplementedError(_NO_SSD_STATE_ACROSS_A_SHARD_EDGE)
            if installed_axis_size("tp") > 1:
                raise NotImplementedError(_NO_NORM_SUM_ACROSS_TP)
        if spec.has_multipliers and spec.pipeline_axis is not None:
            raise NotImplementedError(_NO_MULTIPLIERS_IN_STAGES)
        if spec.pipeline_axis is not None and (spec.has_moe or spec.mla is not None or len(spec.stack_runs) > 1):
            raise NotImplementedError(
                "pipeline parallelism splits ONE stack of equal dense-decoder layers over its stages; a model whose "
                "layers are of more than one kind (attn_layer_period, moe_config) or hold latent attention is not "
                "written for it. Run it without a pp axis."
            )
        if spec.loop is not None and spec.pipeline_axis is not None:
            raise NotImplementedError(_NO_STAGE_PLAN_THAT_CLOSES)
        layer_counters = []  # of the expert layers, a [layers, 3 + E] array a run
        if spec.loop is not None:
            if not spec.scan_layers:
                raise NotImplementedError("loop_config walks the layer scan: scan_layers must stay on")
            exits, gate_logits = self._walks(x)
            if self.output_exits:
                return {"exits": exits, "gate_logits": gate_logits}
            x = exits[-1]
        elif spec.scan_layers and (len(spec.stack_runs) > 1 or spec.has_moe):
            router_state, first = None, 0  # the state starts as none before the first layer and is dropped after the last
            for i, (mixer, ffn, length) in enumerate(spec.stack_runs):
                run = _LayerRun(spec, self.deterministic, mixer, length, ffn, first, name=f"run_{i}")
                if ffn == "moe" and spec.router_state_width:
                    x, counters, router_state = run(x, router_state)
                else:
                    x, counters = run(x)
                first += length
                if counters is not None:
                    layer_counters.append(counters)
        elif spec.scan_layers and self.slot_spec is not None:
            # serving slot-cache path: slot/positions are traced values and must ride
            # the scan carry; same "blocks"/"block" naming so trained params apply
            scanned = nn.scan(
                _SlotBlockScanBody,
                variable_axes={"params": 0, "cache": 0},
                split_rngs={"params": True, "dropout": True},
                length=spec.n_layer,
                metadata_params={nn.meta.PARTITION_NAME: "layers"},
            )(spec, self.deterministic, self.slot_spec, name="blocks")
            with jax.named_scope(scopes.LAYER_CARRY):  # the scan's own stacking and slicing; blocks name themselves
                (x, _, _), _ = scanned((x, slot, positions), None)
        elif spec.scan_layers:
            scanned = nn.scan(
                _BlockScanBody,
                variable_axes={"params": 0, "cache": 0},
                split_rngs={"params": True, "dropout": True},
                length=spec.n_layer,
                metadata_params={nn.meta.PARTITION_NAME: "layers"},
            )(spec, self.deterministic, self.decode, spec.kinds[0], name="blocks")
            # decode never pipelines: generation is single-host and must go through
            # the scanned path so the per-layer KV caches are read/written
            if spec.pipeline_axis is not None and not self.is_initializing() and not self.decode:
                # GPipe over the pp axis: same scan-stacked params (created by the init
                # path below), applied stage-wise by parallel/pipeline.py
                from modalities_tpu.parallel.pipeline import pipeline_blocks
                from modalities_tpu.running_env.device_mesh import current_mesh

                block_params = scanned.variables["params"]
                deterministic = self.deterministic
                pp_dropout_rng = (
                    self.make_rng("dropout")
                    if spec.dropout > 0.0 and not self.deterministic
                    else None
                )

                def block_apply(layer_params, xx, rng=None):
                    def fn(p, a, r):
                        return GPT2Block(spec, deterministic).apply(
                            {"params": p["block"]},
                            a,
                            rngs={"dropout": r} if r is not None else None,
                        )

                    if spec.remat_variant is not None:
                        fn = jax.checkpoint(fn, prevent_cse=False)
                    return fn(layer_params, xx, rng)

                x = pipeline_blocks(
                    block_params,
                    x,
                    current_mesh(),
                    block_apply,
                    axis_name=spec.pipeline_axis,
                    num_microbatches=spec.pp_num_microbatches,
                    seq_shard_axis=spec.context_parallel_axis,
                    dropout_rng=pp_dropout_rng,
                )
            else:
                with jax.named_scope(scopes.LAYER_CARRY):  # the scan's own stacking and slicing; blocks name themselves
                    x, _ = scanned(x, None)
        else:
            router_state = None
            for i in range(spec.n_layer):
                block_cls = (
                    _remat_block_cls(spec)
                    if not self.decode and self.slot_spec is None and _layer_remats(spec, i)
                    else GPT2Block
                )
                block = block_cls(
                    spec, self.deterministic, self.decode, slot_spec=self.slot_spec, mixer=spec.kinds[i],
                    ffn=spec.ffn_kinds[i], name=f"h_{i}"
                )
                if spec.scale_residual_merge or spec.router_state_width:  # the layer's index as an array: a remat-wrapped block traces its arguments
                    x = block(x, slot, positions, router_state, jnp.int32(i))
                else:
                    x = block(x, slot, positions)
                if spec.ffn_kinds[i] == "moe" and spec.router_state_width:
                    x, counters, router_state = x
                    layer_counters.append(counters[None])
                elif spec.ffn_kinds[i] == "moe":
                    x, counters = x
                    layer_counters.append(counters[None])
        if layer_counters and not self.is_initializing() and self.is_mutable_collection("counters"):
            self.sow("counters", "moe", jnp.concatenate(layer_counters, axis=0), reduce_fn=lambda _, new: new,
                     init_fn=lambda: jnp.zeros((0, spec.counter_row_width), jnp.float32))

        if spec.loop is None:  # a looped model's final norm closes every walk (`_walks`)
            x = build_norm(spec.lm_head_norm, "lm_head_norm")(x)
            if spec.logits_scaling is not None:  # `logits / s` is `(h / s) W^T`: on the hidden state, so that every head (fused, chunked, dense) is scaled
                x = x / spec.logits_scaling
            x = with_logical_constraint(x, ("batch", "seq_sp", "embed"))
        if self.output_hidden:
            return x
        if spec.use_weight_tying:
            with jax.named_scope(scopes.LM_HEAD):
                logits = jnp.einsum("bse,ve->bsv", x.astype(jnp.float32), wte.astype(jnp.float32))
        elif spec.quant_weights != "none":
            logits = QuantDenseGeneral(
                features=(spec.vocab_size,),
                kernel_axes=("embed", "vocab"),
                mode=spec.quant_weights,
                use_bias=False,
                param_dtype=spec.param_dtype,
                name="lm_head",
            )(x.astype(jnp.float32))
        else:
            logits = nn.Dense(
                spec.vocab_size,
                use_bias=False,
                name="lm_head",
                kernel_init=nn.with_logical_partitioning(nn.initializers.normal(0.02), ("embed", "vocab")),
                dtype=jnp.float32,  # logits compute stays fp32 for a stable softmax
                param_dtype=param_dtype,
            )(x.astype(jnp.float32))
        return with_logical_constraint(logits, ("batch", "seq", "vocab_logits"))


def _ssd_spec(ssd_config):
    """The Mamba-2 mixer's sizes from `ssd_config`; None, and nothing of `models/gpt2/ssd.py` imported, where there is none."""
    if ssd_config is None:
        return None
    from modalities_tpu.models.gpt2.ssd import SSDSpec

    return SSDSpec.from_config(ssd_config)


class GPT2LLM(NNModel):
    """Framework-level GPT2 model (reference: gpt2_model.py:816)."""

    def __init__(
        self,
        sample_key: str,
        prediction_key: str,
        poe_type: PositionTypes,
        sequence_length: int,
        vocab_size: int,
        n_layer: int,
        n_head_q: int,
        n_head_kv: int,
        n_embd: int,
        ffn_hidden: int,
        dropout: float,
        bias: bool,
        attention_config: AttentionConfig,
        attention_implementation: AttentionImplementation,
        activation_type: ActivationType,
        attention_norm_config,
        ffn_norm_config,
        lm_head_norm_config,
        use_weight_tying: bool,
        use_meta_device: bool = False,
        seed: Optional[int] = None,
        enforce_swiglu_hidden_dim_multiple_of: int = 256,
        lm_head_chunk_size: Optional[int] = None,
        attn_layer_period: Optional[int] = None,
        attn_layer_offset: int = 0,
        ssm_config: Optional[SSMConfig | dict] = None,
        mla_config: Optional[MLAConfig | dict] = None,
        moe_config: Optional[MoEConfig | dict] = None,
        loop_config: Optional[LoopConfig | dict] = None,
        post_attention_norm_config=None,
        post_ffn_norm_config=None,
        head_dim: Optional[int] = None,
        layer_types: Optional[list[str]] = None,
        sliding_window: Optional[int] = None,
        rope_parameters: Optional[dict] = None,
        cca_config: Optional[CCAConfig | dict] = None,
        scale_residual_merge: bool = False,
        gdn_config: Optional[GDNConfig | dict] = None,
        attn_output_gate: bool = False,
        ssd_config: Optional[dict] = None,
        embedding_multiplier: Optional[float] = None,
        residual_multiplier: Optional[float] = None,
        attention_multiplier: Optional[float] = None,
        logits_scaling: Optional[float] = None,
    ):
        super().__init__(
            sample_key=sample_key,
            prediction_key=prediction_key,
            seed=seed,
            weight_decay_groups={
                # group names match the reference (gpt2_model.py:871-875) so its
                # YAMLs' weight_decay_groups_excluded lists resolve unchanged
                "linear": [r".*(q_attn|k_attn|v_attn|c_proj|c_fc|W|V|W_2|lm_head).*kernel.*"],
                # the matrices a deepseek_v3 model adds: latent attention's projections, the router, the experts' stacks
                "latent_and_experts": [r".*/attn/(q_proj|kv_a_proj|kv_b_proj)/kernel$", r".*/moe/router/kernel$",
                                       r".*/moe/experts/(W|V|W_2)$"],
                # the router's selection bias: a buffer, which the optimizer must leave as it is
                "router_bias": [r".*/moe/router/e_score_correction_bias$"],
                "embedding": [r".*(wte|wpe).*"],
                "layernorm": [r".*(norm).*"],
                # a looped model's exit gate: a vector and a scalar, float32
                "exit_gate": [r".*/exit_gate/(kernel|bias)$"],
                # what Mamba marks `_no_weight_decay` in the state-space mixer, and its biases
                "ssm": [r".*/ssm/(A_log|D)$", r".*/ssm/.*bias$"],
                # what a `zaya` model adds (PR 40). Matrices, which a recipe decays: the convolutions of compressed
                # convolutional attention (its projections are `linear`'s by their names), the MLP router's kernels
                "cca_convolutions": [r".*/cca/conv[01]_kernel$"],
                "router_mlp": [r".*/moe/router/(down|fc1|fc2|out)/kernel$"],
                # and vectors, which it does not: the key temperature and the convolutions' biases, a merge's scales and
                # shifts, the router's biases, its gate on the state handed on and its norm's scale
                "cca_vectors": [r".*/cca/(key_temperature|conv[01]_bias)$"],
                "residual_merge": [r".*/(attn_merge|ffn_merge)/(residual|out)_(scale|bias)$"],
                "router_vectors": [r".*/moe/router/(down|fc1|fc2)/bias$", r".*/moe/router/(eda_gate|norm_scale)$"],
                # what a `qwen3_next` model adds (PR 44). Matrices, which a recipe decays: the gated delta rule's three projections
                "gdn_projections": [r".*/gdn/(qkvz|ba|out_proj)/kernel$"],
                # and what it does not: the decay's `A_log` and `dt_bias`, the convolution's taps, the norm a head after the rule,
                # and the shared expert's gate `w_g [d, 1]`
                "gdn_vectors": [r".*/gdn/(A_log|dt_bias|conv_kernel|out_norm_scale)$"],
                "shared_expert_gate": [r".*/moe/shared_gate$"],
                # what a `granitemoehybrid` model adds (PR 52). Matrices, which a recipe decays: the Mamba-2 mixer's two projections
                "ssd_projections": [r".*/ssd/(in_proj|out_proj)/kernel$"],
                # and what it does not: the decay's `A_log`, the skip `D`, `dt_bias`, the convolution's taps and bias, the gated norm's scale
                "ssd_vectors": [r".*/ssd/(A_log|D|dt_bias|conv_kernel|conv_bias|norm_scale)$"],
            },
        )
        if n_head_q % n_head_kv != 0:
            raise ValueError("n_head_q must be divisible by n_head_kv")
        if n_embd % n_head_q != 0:
            raise ValueError("n_embd must be divisible by n_head_q")
        if isinstance(attention_config, dict):
            attention_config = AttentionConfig(**attention_config)
        use_rope = any(
            t.type_hint == QueryKeyValueTransformType.RotaryTransform for t in attention_config.qkv_transforms
        )
        rope_base = 10000
        for t in attention_config.qkv_transforms:
            if t.type_hint == QueryKeyValueTransformType.RotaryTransform:
                rope_base = t.config.base_freq

        poe_value = poe_type.value if isinstance(poe_type, PositionTypes) else str(poe_type)
        act_value = activation_type.value if isinstance(activation_type, ActivationType) else str(activation_type)
        impl_value = (
            attention_implementation.value
            if isinstance(attention_implementation, AttentionImplementation)
            else str(attention_implementation)
        )
        self.config_spec = GPT2ModelSpec(
            vocab_size=vocab_size,
            sequence_length=sequence_length,
            n_layer=n_layer,
            n_head_q=n_head_q,
            n_head_kv=n_head_kv,
            n_embd=n_embd,
            ffn_hidden=ffn_hidden,
            dropout=dropout,
            bias=bias,
            poe_type=poe_value,
            activation=act_value,
            attention_impl=impl_value,
            use_rope=use_rope,
            rope_base_freq=rope_base,
            use_qk_norm=attention_config.qk_norm_config is not None,
            use_weight_tying=use_weight_tying,
            swiglu_hidden=swiglu_hidden_dim(ffn_hidden, enforce_swiglu_hidden_dim_multiple_of),
            attn_norm=NormSpec.from_wrapper_config(attention_norm_config, n_embd),
            ffn_norm=NormSpec.from_wrapper_config(ffn_norm_config, n_embd),
            lm_head_norm=NormSpec.from_wrapper_config(lm_head_norm_config, n_embd),
            qk_norm=(
                NormSpec.from_wrapper_config(attention_config.qk_norm_config, head_dim if head_dim is not None else n_embd // n_head_q)
                if attention_config.qk_norm_config is not None
                else None
            ),
            lm_head_chunk_size=lm_head_chunk_size,
            layer_kinds=(layer_kinds(n_layer, attn_layer_period, attn_layer_offset) if attn_layer_period
                         else tuple(_MIXER_OF[kind] for kind in layer_types or ())),
            ssm=SSMSpec.from_config(ssm_config, n_embd) if ssm_config is not None else None,
            mla=MLASpec.from_config(mla_config) if mla_config is not None else None,
            moe=MoESpec.from_config(moe_config) if moe_config is not None else None,
            loop=LoopSpec.from_config(loop_config) if loop_config is not None else None,
            post_attn_norm=NormSpec.from_wrapper_config(post_attention_norm_config, n_embd) if post_attention_norm_config is not None else None,
            post_ffn_norm=NormSpec.from_wrapper_config(post_ffn_norm_config, n_embd) if post_ffn_norm_config is not None else None,
            head_dim_key=head_dim,
            sliding_window=sliding_window if layer_types and SLIDING in layer_types else None,
            rope_by_kind=tuple(sorted((_MIXER_OF[kind], RopeSpec.from_config(rope)) for kind, rope in (rope_parameters or {}).items())),
            cca=CCASpec.from_config(cca_config) if cca_config is not None else None,
            scale_residual_merge=scale_residual_merge,
            gdn=GDNSpec.from_config(gdn_config) if gdn_config is not None else None,
            attn_output_gate=attn_output_gate,
            ssd=_ssd_spec(ssd_config),
            embedding_multiplier=embedding_multiplier, residual_multiplier=residual_multiplier,
            attention_multiplier=attention_multiplier, logits_scaling=logits_scaling,
        )
        self.sequence_length = sequence_length
        self.vocab_size = vocab_size

    @property
    def module(self) -> GPT2Module:
        return GPT2Module(self.config_spec, deterministic=True)

    def train_module(self) -> GPT2Module:
        return GPT2Module(self.config_spec, deterministic=False)

    def with_spec_updates(self, **changes) -> "GPT2LLM":
        """Rebuild with updated static spec fields (remat variant, attention impl, ...)."""
        from dataclasses import replace

        self.config_spec = replace(self.config_spec, **changes)
        return self

    def remat_flash_calls(self, rows: int, seq: int) -> Optional[dict]:
        """The flash kernel calls that sit in blocks `_remat_block_cls` wraps under `full`, for a microbatch of `rows` rows of
        `seq` as one shard sees it under the installed rules: `blocks` and the bytes of one block's input `[rows, seq, E]`
        (what every rematerialized block keeps already), and for each kind of attention layer how many layers, the bytes
        of the kernel's `o` `[rows, H, seq, Dv]` and of `lse` as numbers, `[rows, H, seq]` float32, and the bytes its
        backward holds round the kernel (`backward_bytes`: q, k, v, o and its cotangent, dq, dk and dv a q head, lse and
        delta as the kernel lays them out: `[rows, H, 1, seq]` rows of float32, dense since PR 42); and, where the stack holds
        layers of the gated delta rule, under `rule` how many and the bytes a layer of what such a block may keep of
        `ops/gated_delta_rule.py`: `o` `[rows, seq, Hv, Dv]` and the float32 states that come into the groups of chunks
        (`states_bytes`). It is what `training/activation_checkpointing.attention_keep_plan` counts. None where no block is wrapped so: another variant
        or none, a looped stack (`_walks_in_place` recomputes by hand and takes no policy), pipeline stages
        (`jax.checkpoint` of their own), ring attention (no call of this kernel), a tier that is not the kernel, and off
        the TPU, where `ops/attention.py` runs XLA's attention and there is no kernel to keep anything of."""
        from modalities_tpu.parallel.sharding import shard_shape

        spec = self.config_spec
        if (spec.remat_variant != "full" or spec.loop is not None or spec.pipeline_axis is not None or not tiers.kernels_run()
                or not takes_kernel(spec.attention_impl, spec.dropout, spec.context_parallel_axis)):
            return None
        itemsize = jnp.dtype(spec.compute_dtype).itemsize
        # latent attention hands the kernel every head's own k and v, 192 and 128 wide; the others `n_head_kv` heads of `head_dim`
        kv_heads, width, width_v = ((spec.n_head_q, spec.mla.qk_head_dim, spec.mla.v_head_dim) if spec.mla is not None
                                    else (spec.n_head_kv, spec.head_dim, spec.head_dim))
        b, s, h = shard_shape((rows, seq, spec.n_head_q), ("batch", None, "heads"))  # as `ops/attention.py` splits a call
        h_kv = shard_shape((kv_heads,), ("kv_heads",))[0]
        lse_bytes = b * h * s * 4  # delta's too
        call = {"o_bytes": b * h * s * width_v * itemsize, "lse_bytes": lse_bytes,
                "backward_bytes": b * s * itemsize * (3 * h * width + 3 * h * width_v + h_kv * (width + width_v)) + 2 * lse_bytes}
        held = {"blocks": spec.n_layer, "calls": [{"kind": kind, "layers": spec.kinds.count(kind), **call} for kind in ("attn", "swa", "cca") if kind in spec.kinds],
                "block_input_bytes": math.prod(shard_shape((rows, seq, spec.n_embd), ("batch", "seq_sp", "embed"))) * itemsize}
        if "gdn" in spec.kinds:  # the rule's o over the row padded to whole groups of chunks, and a float32 state a group and a value head
            from modalities_tpu.ops import gated_delta_rule as rule

            groups, chunks = rule.groups_of(seq)
            b, s, hv = shard_shape((rows, groups * chunks * rule.CHUNK, spec.gdn.value_heads), ("batch", None, "heads"))
            held["rule"] = {"layers": spec.kinds.count("gdn"), "o_bytes": b * s * hv * spec.gdn.value_dim * itemsize,
                            "states_bytes": b * rule.state_bytes(seq, hv, spec.gdn.key_dim, spec.gdn.value_dim)}
        return held

    def init_params(self, rng):
        dummy = jnp.zeros((1, min(8, self.sequence_length)), dtype=jnp.int32)
        return self.module.init(rng, dummy)

    def apply(self, params, inputs: dict, train: bool = False, rngs=None) -> dict:
        module = self.train_module() if train else self.module
        logits = module.apply(params, inputs[self.sample_key], rngs=rngs)
        return {self.prediction_key: logits}

    # ------------------------------------------------------- chunked head + loss
    def apply_hidden(self, params, inputs: dict, train: bool = False, rngs=None):
        """Backbone through lm_head_norm -> [B, S, E] hidden state (no logits).
        Pair with `head_logits` per sequence chunk so the [B,S,V] fp32 logits
        tensor never materializes (spec.lm_head_chunk_size; consumed by
        TrainStepBuilder)."""
        module = GPT2Module(
            self.config_spec, deterministic=not train, output_hidden=True
        )
        return module.apply(params, inputs[self.sample_key], rngs=rngs)

    @property
    def counted(self) -> dict[str, tuple[int, ...]]:
        """What a training pass of the expert layers counts: the three of `moe.COUNTERS`, which a
        step publishes (pairs held and mean load: the mean over the expert layers; the largest
        load: over all of them), and every expert's load a layer, by which `after_update` moves
        the selection bias."""
        spec = self.config_spec
        if self.trains_on_exits:  # what the loss over the exits counts (`LoopedExitLoss`): the step publishes them with the model's own
            return {name: () for name in exit_counter_names(spec.loop.total_ut_steps)}
        if not spec.has_moe:
            return {}
        aux = {AUX_LOSS: ()} if spec.moe.counts_aux_loss else {}  # the balance term, the mean over the expert layers (a softmax router's)
        if spec.moe.skip_column:  # the share of a layer's tokens that chose the column with no expert behind it, the mean over the layers
            aux[SKIP_SHARE] = ()
        if spec.cca is not None:  # the mean key temperature of compressed convolutional attention, over heads and layers
            aux[KEY_TEMPERATURE] = ()
        # the gated delta rule's mean decay and mean beta, or the Mamba-2 mixer's mean decay, over tokens, heads and its layers
        aux.update({name: () for name in spec.mixer_counter_names})
        return {**{name: () for name in COUNTERS}, EXPERT_LOAD: (spec.ffn_kinds.count("moe"), spec.moe.router_width), **aux}

    @property
    def trains_on_exits(self) -> bool:
        """A looped model with its exit gate: training takes every walk's exit and gate, not one hidden state."""
        loop = self.config_spec.loop
        return loop is not None and loop.exit_gate

    def apply_counted(self, params, inputs: dict, train: bool = False, rngs=None, hidden: bool = False):
        if hidden and train and self.trains_on_exits:
            module = GPT2Module(self.config_spec, deterministic=False, output_exits=True)
            return module.apply(params, inputs[self.sample_key], rngs=rngs), {}
        if not self.config_spec.has_moe:
            return super().apply_counted(params, inputs, train=train, rngs=rngs, hidden=hidden)
        module = GPT2Module(self.config_spec, deterministic=not train, output_hidden=hidden)
        out, state = module.apply(params, inputs[self.sample_key], rngs=rngs, mutable=["counters"])
        rows = state["counters"]["moe"]  # [expert layers, `counter_row_width`]: 3, the router's columns, then what only some models count
        moe = self.config_spec.moe
        loads = rows[:, len(COUNTERS): len(COUNTERS) + moe.router_width]
        counted = {COUNTERS[0]: rows[:, 0].mean(), COUNTERS[1]: rows[:, 1].max(), COUNTERS[2]: rows[:, 2].mean(), EXPERT_LOAD: loads}
        if moe.counts_aux_loss:
            # the one thing counted that carries a gradient (`loss_from_layers`): the row's last entry, but for a key temperature or
            # a `gdn` layer's two after it
            counted[AUX_LOSS] = rows[:, -1 - (self.config_spec.cca is not None) - self.config_spec.mixer_counters].mean()
        if moe.skip_column:
            counted[SKIP_SHARE] = jnp.mean(loads[:, -1] / jnp.maximum(jnp.sum(loads, axis=1), 1.0))
        if self.config_spec.cca is not None:
            counted[KEY_TEMPERATURE] = rows[:, -1].mean()
        if self.config_spec.mixer_counters:  # the mean over the expert layers whose mixer counts (a row a layer, in the stack's order)
            spec = self.config_spec
            counting = "ssd" if spec.ssd is not None else "gdn"
            of_mixer = [row for row, layer in enumerate(i for i, ffn in enumerate(spec.ffn_kinds) if ffn == "moe") if spec.kinds[layer] == counting]
            for column, name in enumerate(spec.mixer_counter_names, start=-spec.mixer_counters):
                counted[name] = rows[jnp.asarray(of_mixer), column].mean() if of_mixer else jnp.zeros((), jnp.float32)
        return (out if hidden else {self.prediction_key: out}), counted

    def loss_from_layers(self, counted: dict):
        """The loss term that comes from the layers and not from the logits: `router_aux_loss_coef` times the mean
        over the expert layers of the balance term, where the config asks for one; None (nothing to add) elsewhere."""
        moe = self.config_spec.moe
        if moe is None or not moe.router_aux_loss_coef:
            return None
        return moe.router_aux_loss_coef * counted[AUX_LOSS]

    def after_update(self, params, counted: dict):
        """The selection bias of every expert layer moved by its rule (`moe.update_selection_bias`)
        from the step's loads; the tree as it is where `bias_update_speed` is 0."""
        spec = self.config_spec
        if not spec.has_moe or not spec.moe.bias_update_speed or not spec.moe.selection_bias:
            return params
        expert_layers = [i for i, ffn in enumerate(spec.ffn_kinds) if ffn == "moe"]
        first_of_run = [sum(length for _, _, length in spec.stack_runs[:r]) for r in range(len(spec.stack_runs))]

        def moved(path, leaf):
            name = "/".join(str(getattr(p, "key", getattr(p, "name", p))) for p in path)
            if BIAS_LEAF not in name:
                return leaf
            where = re.search(r"(run|h)_(\d+)/", name)  # a run's stacked layers [length, E], or one unrolled layer [E]
            first = first_of_run[int(where[2])] if where[1] == "run" else int(where[2])
            row = expert_layers.index(first)
            load = counted[EXPERT_LOAD][row: row + leaf.shape[0]] if leaf.ndim == 2 else counted[EXPERT_LOAD][row]
            return update_selection_bias(leaf, load, spec.moe.bias_update_speed)

        return jax.tree_util.tree_map_with_path(moved, params)

    def head_logits(self, params, hidden_chunk):
        """fp32 logits for a [B, C, E] hidden chunk (weight-tied or lm_head),
        vocab-constrained like the in-module head (loss parallel works)."""
        return head_project(self.config_spec, params["params"], hidden_chunk)

    def head_weight(self, params):
        """The `[V, E]` head projection matrix (tied wte, or lm_head kernel
        transposed) — consumed by the Pallas fused-CE tier, which contracts it
        against hidden states tile-by-tile instead of materializing logits.
        Gradients flow back through the transpose/tie via autodiff."""
        inner = params["params"]
        if self.config_spec.use_weight_tying:
            return inner["wte"]
        head = inner["lm_head"]
        if "scale" in head:  # weight-only quantized head: dequant per vocab column
            return (head["kernel"].astype(jnp.float32) * head["scale"].astype(jnp.float32)).T
        return head["kernel"].T

    # ----------------------------------------------------------- KV-cache decoding
    def _refuse_without_recurrent_state_cache(self) -> None:
        refuse_serving(self.config_spec)

    def init_decode_cache(self, params, batch_size: int):
        """Zeroed per-layer KV caches + position counters for `decode_step`. Shapes
        come from an abstract init (eval_shape) — no parameter materialization."""
        self._refuse_without_recurrent_state_cache()
        module = GPT2Module(self.config_spec, deterministic=True, decode=True)
        dummy = jnp.zeros((batch_size, 1), dtype=jnp.int32)
        abstract = jax.eval_shape(lambda: module.init(jax.random.PRNGKey(0), dummy))
        return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), abstract["cache"])

    def decode_step(self, params, cache, tokens):
        """One cached autoregressive step (tokens = NEW positions only, [B, S_in];
        S_in > 1 prefills the prompt). Returns (logits [B, S_in, V], updated cache).
        O(1) work per generated token vs. the reference's full re-forward
        (inference/text/inference_component.py:60-72)."""
        module = GPT2Module(self.config_spec, deterministic=True, decode=True)
        logits, mutated = module.apply(
            {**params, "cache": cache}, tokens, mutable=["cache"]
        )
        return logits, mutated["cache"]

    # ------------------------------------------------- slot-batched serving decode
    # The continuous-batching engine's model surface (serving/engine.py): a batched
    # ring KV cache of static [slots, capacity] shape with EXPLICIT per-slot
    # positions (no in-cache counter), so one compiled decode step plus a bounded
    # prefill ladder serves every request mix without recompiles.

    @staticmethod
    def _slot_cache_dims(cache) -> tuple[int, int]:
        """(slots, capacity) recovered from the cache leaf shapes — static, so the
        engine never has to thread them alongside the tree."""
        for leaf in jax.tree.leaves(cache):
            if leaf.ndim == 5:  # scanned: [layers, slots, capacity, Hkv, D]
                return int(leaf.shape[1]), int(leaf.shape[2])
            if leaf.ndim == 4:  # unrolled blocks: [slots, capacity, Hkv, D]
                return int(leaf.shape[0]), int(leaf.shape[1])
        raise ValueError("not a slot KV cache: no [.., slots, capacity, heads, head_dim] leaf")

    def init_slot_cache(self, params, max_batch_slots: int, cache_capacity: Optional[int] = None):
        """Zeroed [slots, capacity] ring KV cache for `prefill_slot`/`decode_slots`.
        Shapes via abstract init (eval_shape) — no materialization."""
        self._refuse_without_recurrent_state_cache()
        cap = self.config_spec.sequence_length if cache_capacity is None else int(cache_capacity)
        if (
            cap > self.config_spec.sequence_length
            and self.config_spec.poe_type == PositionTypes.ABSOLUTE.value
        ):
            raise ValueError(
                f"cache_capacity {cap} exceeds sequence_length "
                f"{self.config_spec.sequence_length}: ABSOLUTE position embeddings "
                "have no rows past the trained sequence length"
            )
        sspec = SlotDecodeSpec("decode", int(max_batch_slots), cap)
        module = GPT2Module(self.config_spec, deterministic=True, slot_spec=sspec)
        tokens = jnp.zeros((int(max_batch_slots), 1), dtype=jnp.int32)
        positions = jnp.zeros((int(max_batch_slots),), dtype=jnp.int32)
        abstract = jax.eval_shape(
            lambda: module.init(jax.random.PRNGKey(0), tokens, None, positions)
        )
        return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), abstract["cache"])

    def prefill_slot(self, params, cache, tokens, slot, start_pos):
        """Forward a [1, C] prompt chunk, writing k/v into cache row `slot` at
        positions start_pos..start_pos+C-1. Returns (logits [1, C, V], cache).
        Chunk length C is the only shape that varies — the engine buckets it on the
        power-of-two ladder so the jit cache stays bounded."""
        slots, cap = self._slot_cache_dims(cache)
        module = GPT2Module(
            self.config_spec, deterministic=True, slot_spec=SlotDecodeSpec("prefill", slots, cap)
        )
        logits, mutated = module.apply(
            {**params, "cache": cache}, tokens, slot, start_pos, mutable=["cache"]
        )
        return logits, mutated["cache"]

    def decode_slots(self, params, cache, tokens, positions):
        """ONE batched decode step: tokens [slots, 1] written at per-slot
        `positions` [slots]; every slot advances one token per dispatch. Returns
        (logits [slots, 1, V], cache). Idle slots compute garbage harmlessly — the
        engine masks them on the host and re-prefills over their rows."""
        slots, cap = self._slot_cache_dims(cache)
        module = GPT2Module(
            self.config_spec, deterministic=True, slot_spec=SlotDecodeSpec("decode", slots, cap)
        )
        logits, mutated = module.apply(
            {**params, "cache": cache}, tokens, None, positions, mutable=["cache"]
        )
        return logits, mutated["cache"]

    # --------------------------------------------------- paged (block-table) decode
    # Serving v2's model surface (serving/paged_cache.py + engine kv_cache="paged"):
    # ONE global [num_blocks, block_size] K/V pool per scanned layer, per-slot block
    # tables as traced int32 args, explicit write coordinates. Same ONE-executable
    # discipline as the ring API; the per-slot length ceiling becomes the table
    # width instead of a static ring row.

    @staticmethod
    def _paged_cache_dims(cache) -> tuple[int, int]:
        """(num_blocks, block_size) recovered from the pool leaf shapes."""
        for leaf in jax.tree.leaves(cache):
            if leaf.ndim == 5:  # scanned: [layers, num_blocks, block_size, Hkv, D]
                return int(leaf.shape[1]), int(leaf.shape[2])
            if leaf.ndim == 4:  # unrolled blocks
                return int(leaf.shape[0]), int(leaf.shape[1])
        raise ValueError("not a paged KV cache: no [.., blocks, block_size, heads, head_dim] leaf")

    @staticmethod
    def _paged_cache_quant(cache) -> str:
        """KV quant mode read off the cache leaves: an int8 pool leaf means the
        cache was built with kv_quant="int8" — recovered statically so the
        prefill/decode surfaces never grow a mode argument."""
        for leaf in jax.tree.leaves(cache):
            if jnp.dtype(leaf.dtype) == jnp.int8:
                return "int8"
        return "none"

    def init_paged_cache(self, params, num_blocks: int, block_size: int, kv_quant: str = "none"):
        """Zeroed global block pool ([num_blocks, block_size, Hkv, D] per layer,
        leading layers axis added by the scan). Shapes via abstract init.
        kv_quant="int8" stores int8 pools plus float32 scale pools
        ([num_blocks, block_size, Hkv, 1]) alongside in the same tree."""
        self._refuse_without_recurrent_state_cache()
        nb, bs = int(num_blocks), int(block_size)
        if nb < 1 or bs < 1:
            raise ValueError(f"paged cache needs num_blocks >= 1 and block_size >= 1, got {nb}/{bs}")
        if kv_quant not in ("none", "int8"):
            raise ValueError(f"unknown kv_quant {kv_quant!r} (expected none|int8)")
        sspec = SlotDecodeSpec(
            "decode", 1, bs, kind="paged", num_blocks=nb, block_size=bs, kv_quant=kv_quant
        )
        module = GPT2Module(self.config_spec, deterministic=True, slot_spec=sspec)
        tokens = jnp.zeros((1, 1), dtype=jnp.int32)
        positions = {
            "pos": jnp.zeros((1,), jnp.int32),
            "tables": jnp.zeros((1, 1), jnp.int32),
            "wblk": jnp.full((1,), nb, jnp.int32),  # out of range: init writes nothing
            "woff": jnp.zeros((1,), jnp.int32),
        }
        abstract = jax.eval_shape(
            lambda: module.init(jax.random.PRNGKey(0), tokens, None, positions)
        )
        return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), abstract["cache"])

    def prefill_paged(self, params, cache, tokens, positions, tables, wblk, woff):
        """Cross-request packed prefill: row r of `tokens` [R, C] is a chunk of
        some request, written at absolute positions `positions` [R, C] through the
        row's block table `tables` [R, MB] with write coordinates wblk/woff [R, C]
        (wblk >= num_blocks drops the write — padded tails). Returns
        (logits [R, C, V], cache)."""
        nb, bs = self._paged_cache_dims(cache)
        sspec = SlotDecodeSpec(
            "prefill", int(tokens.shape[0]), int(tables.shape[1]) * bs,
            kind="paged", num_blocks=nb, block_size=bs,
            kv_quant=self._paged_cache_quant(cache),
        )
        module = GPT2Module(self.config_spec, deterministic=True, slot_spec=sspec)
        pos_tree = {"pos": positions, "tables": tables, "wblk": wblk, "woff": woff}
        logits, mutated = module.apply(
            {**params, "cache": cache}, tokens, None, pos_tree, mutable=["cache"]
        )
        return logits, mutated["cache"]

    def decode_paged(self, params, cache, tokens, positions, tables, wblk, woff):
        """ONE batched paged decode step: tokens [S, 1] at per-slot `positions`
        [S], K/V gathered through per-slot block tables [S, MB]; writes land at
        wblk/woff [S] (out-of-range = idle slot, dropped). Returns
        (logits [S, 1, V], cache)."""
        nb, bs = self._paged_cache_dims(cache)
        sspec = SlotDecodeSpec(
            "decode", int(tokens.shape[0]), int(tables.shape[1]) * bs,
            kind="paged", num_blocks=nb, block_size=bs,
            kv_quant=self._paged_cache_quant(cache),
        )
        module = GPT2Module(self.config_spec, deterministic=True, slot_spec=sspec)
        pos_tree = {"pos": positions, "tables": tables, "wblk": wblk, "woff": woff}
        logits, mutated = module.apply(
            {**params, "cache": cache}, tokens, None, pos_tree, mutable=["cache"]
        )
        return logits, mutated["cache"]

    def verify_paged(self, params, cache, tokens, positions, tables, wblk, woff):
        """Speculative-decoding verification forward (serving v3): row s of
        `tokens` [S, k+1] is `[fed_token, draft_1 .. draft_k]` at absolute
        positions `positions` [S, k+1]; ONE fixed-shape batched forward scores
        every proposal column, and the engine folds the per-slot accept length
        out of the returned logits with `jnp.where`/cumprod — no per-k shapes,
        so the verify step compiles exactly once beside the 1-token decode.

        The math is the packed-prefill contract verbatim (per-column causal
        masking over the block tables, write coordinates wblk/woff [S, k+1]
        with out-of-range = dropped), so this delegates to it: a draft column
        attends exactly the K/V a sequential decode at that position would,
        which is what makes greedy spec-decode bitwise equal to plain decode."""
        return self.prefill_paged(params, cache, tokens, positions, tables, wblk, woff)

    # ------------------------------------------------------- scheduled pipelining
    def split_pp_params(self, params):
        """(stacked_block_params, shared_params) for the scheduled pipeline executor
        (parallel/pipeline_scheduled.py). Stacked = the scan-over-layers subtree
        (pp-sharded on its leading axis); shared = embeddings + head norm (+ head)."""
        inner = dict(params["params"])
        stacked = inner.pop("blocks")
        return stacked, {"params": inner}

    def merge_pp_grads(self, stacked_grads, shared_grads):
        inner = dict(shared_grads["params"])
        inner["blocks"] = stacked_grads
        return {"params": inner}

    def pp_stage_fns(self, loss_fn):
        """Stage functions for the scheduled 1F1B pipeline: embed / block / head+loss.
        Mirrors GPT2Module.__call__ exactly (same submodule names so param subtrees
        line up); the head computes fp32 logits like the module path."""
        from modalities_tpu.parallel.pipeline_scheduled import PipelineStageFns

        spec = self.config_spec
        compute_dtype = jnp.dtype(spec.compute_dtype)
        prediction_key = self.prediction_key
        target_key = loss_fn.target_key

        cp_axis = spec.context_parallel_axis

        def embed(shared, tokens, rng):
            p = shared["params"]
            with jax.named_scope(scopes.WTE):
                x = embedding_lookup(p["wte"], tokens).astype(compute_dtype)
            if spec.poe_type == PositionTypes.ABSOLUTE.value:
                # tokens are a LOCAL seq chunk under cp: slice wpe at the global offset
                offset = cp_shard_offset(cp_axis, tokens.shape[1])
                wpe = jax.lax.dynamic_slice_in_dim(p["wpe"], offset, tokens.shape[1], 0)
                x = x + wpe[None].astype(compute_dtype)
            if spec.dropout > 0.0 and rng is not None:
                keep = jax.random.bernoulli(rng, 1.0 - spec.dropout, x.shape)
                x = jnp.where(keep, x / (1.0 - spec.dropout), jnp.zeros_like(x))
            return x

        def block(layer_params, x, rng):
            deterministic = rng is None
            return GPT2Block(spec, deterministic).apply(
                {"params": layer_params["block"]},
                x,
                rngs={"dropout": rng} if rng is not None else None,
            )

        has_sum_count = hasattr(loss_fn, "sum_and_count")
        head_chunk = spec.lm_head_chunk_size if has_sum_count else None

        def _norm_head_sum(p, xc, lc):
            """(sum of token losses, valid-token count) for one sequence chunk —
            the lm-head norm is per-token, so chunking before it is exact."""
            h = build_norm(spec.lm_head_norm, "lm_head_norm").apply(
                {"params": p.get("lm_head_norm", {})}, xc
            )
            return loss_fn.sum_and_count(head_project(spec, p, h), lc)

        # backward recomputes each chunk's logits instead of storing them — same
        # remat trade as the unpipelined fused chunked head+loss in train_step
        chunk_sum_count = jax.checkpoint(_norm_head_sum, prevent_cse=False)

        @jax.named_scope(scopes.HEAD_LOSS)
        def head_loss(shared, x, targets):
            """Returns (mean loss over this microbatch, valid-token weight). The weight
            lets the executor reproduce the GLOBAL token mean exactly even when
            ignore_index masking makes microbatch token counts unequal. Honors
            spec.lm_head_chunk_size: the [B,S,V] logits never materialize — the
            head+loss run per sequence chunk, accumulating (sum, count)."""
            p = shared["params"]
            seq = x.shape[1]
            if head_chunk is not None and seq > head_chunk:
                # ragged tail: scan the divisible prefix, then one short chunk for
                # the remainder — odd eval lengths need no config change and the
                # [B,S,V] logits still never materialize (mirrors train_step)
                num_chunks, tail = divmod(seq, head_chunk)

                def body(acc, i):
                    xc = jax.lax.dynamic_slice_in_dim(x, i * head_chunk, head_chunk, 1)
                    lc = jax.lax.dynamic_slice_in_dim(targets, i * head_chunk, head_chunk, 1)
                    s, c = chunk_sum_count(p, xc, lc)
                    return (acc[0] + s, acc[1] + c), None

                (total, count), _ = jax.lax.scan(
                    body,
                    (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
                    jnp.arange(num_chunks),
                )
                if tail:
                    s, c = chunk_sum_count(
                        p,
                        jax.lax.slice_in_dim(x, num_chunks * head_chunk, seq, axis=1),
                        jax.lax.slice_in_dim(targets, num_chunks * head_chunk, seq, axis=1),
                    )
                    total, count = total + s, count + c
            elif has_sum_count:
                total, count = _norm_head_sum(p, x, targets)
            else:
                # loss fns without the accumulation form: whole-sequence logits;
                # the valid-token weight still honors an ignore_index if exposed
                h = build_norm(spec.lm_head_norm, "lm_head_norm").apply(
                    {"params": p.get("lm_head_norm", {})}, x
                )
                loss = loss_fn({prediction_key: head_project(spec, p, h)}, {target_key: targets})
                ignore_index = getattr(loss_fn, "ignore_index", None)
                if ignore_index is None:
                    count = jnp.asarray(targets.size, jnp.float32)
                else:
                    count = (targets != ignore_index).sum().astype(jnp.float32)
                total = loss * jnp.maximum(count, 1.0)
            # under cp the chunk's (sum, count) are partial along the sequence: reduce
            # over the ring so every shard sees the microbatch-global mean and weight
            # (the psum transpose routes each shard its own local cotangent slice)
            if _manual_axis_active(cp_axis):
                total = jax.lax.psum(total, cp_axis)
                count = jax.lax.psum(count, cp_axis)
            weight = jnp.maximum(count, 1.0)
            return total / weight, weight

        return PipelineStageFns(embed=embed, block=block, head_loss=head_loss)
