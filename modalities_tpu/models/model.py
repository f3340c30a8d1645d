"""Framework model base (reference: src/modalities/models/model.py:26-72).

A model here is a *description*: a flax linen module plus metadata (sample/prediction
keys, seed, weight-decay groups) and a ``TrainSpec`` accumulating the transforms the
registry variants apply (sharding rules, init routine, remat policy, mixed precision).
Unlike the reference — which mutates torch modules in place (FSDP wrap, compile, AC
wrap) — JAX composes these as pure transforms when the jitted train step is built.
"""

from __future__ import annotations

from dataclasses import dataclass, field, replace
from typing import Any, Callable, Optional

import numpy as np

from modalities_tpu.batch import DatasetBatch, InferenceResultBatch

WeightDecayGroups = dict[str, list[str]]


@dataclass
class RematSpec:
    """Activation-checkpointing variant (reference: training/activation_checkpointing/).

    variant: 'full' | 'selective_layer' | 'selective_op' | None
    """

    variant: Optional[str] = None
    ac_freq: int = 1  # selective_layer: checkpoint every ac_freq-th block
    save_list: tuple[str, ...] = ()  # selective_op: checkpoint-policy saveable names


@dataclass
class MixedPrecisionSpec:
    param_dtype: str = "float32"
    compute_dtype: str = "bfloat16"
    reduce_dtype: str = "float32"


@dataclass
class TrainSpec:
    """Accumulated model-transform descriptors applied at train-step build time."""

    sharding_rules: tuple[tuple[str, Optional[str | tuple[str, ...]]], ...] = ()
    mixed_precision: MixedPrecisionSpec = field(default_factory=MixedPrecisionSpec)
    remat: RematSpec = field(default_factory=RematSpec)
    init_routines: tuple[Any, ...] = ()
    compiled: bool = True  # jit is the default on TPU; kept for config parity


class NNModel:
    """Base class binding a linen module to the framework's dict-in/dict-out contract."""

    def __init__(
        self,
        sample_key: str,
        prediction_key: str,
        seed: Optional[int] = None,
        weight_decay_groups: Optional[WeightDecayGroups] = None,
    ):
        self.sample_key = sample_key
        self.prediction_key = prediction_key
        self.seed = seed if seed is not None else 42
        self._weight_decay_groups = weight_decay_groups or {}
        self.train_spec = TrainSpec()

    @property
    def weight_decay_groups(self) -> WeightDecayGroups:
        return self._weight_decay_groups

    # --- to be provided by concrete models ---
    @property
    def module(self):  # pragma: no cover - abstract
        raise NotImplementedError

    def init_params(self, rng):  # pragma: no cover - abstract
        raise NotImplementedError

    def apply(self, params, inputs: dict, train: bool = False, rngs=None) -> dict:  # pragma: no cover
        raise NotImplementedError

    # --- what a training pass counts beside its loss (an expert layer's routing); nothing, for most models ---
    @property
    def counted(self) -> dict[str, tuple[int, ...]]:
        """Name and shape (float32) of everything `apply_counted` counts. The train step sums each over
        the microbatches (their mean; the largest where the name ends in `_max`), publishes the
        scalars with the step's metrics and hands all of them to `after_update`."""
        return {}

    def apply_counted(self, params, inputs: dict, train: bool = False, rngs=None, hidden: bool = False):
        """`apply` (`apply_hidden` with `hidden`), and what the pass counted: `{name: array}` as `counted` names them."""
        forward = self.apply_hidden if hidden else self.apply
        return forward(params, inputs, train=train, rngs=rngs), {}

    def loss_from_layers(self, counted: dict):
        """A loss term that comes from the layers and not from the logits, out of what the pass counted (an expert
        layer's balance term); None where the model has none. The train step adds it before the gradient."""
        return None

    def after_update(self, params, counted: dict):
        """The parameters after the optimizer's update, with the buffers moved that a step moves by a
        rule of their own from what it counted (an expert layer's selection bias)."""
        return params

    def update_train_spec(self, **changes) -> "NNModel":
        self.train_spec = replace(self.train_spec, **changes)
        return self


def model_predict_batch(model: NNModel, params, batch: DatasetBatch) -> InferenceResultBatch:
    """Forward a DatasetBatch through the model (reference: models/model.py:157)."""
    predictions = model.apply(params, batch.samples, train=False)
    return InferenceResultBatch(targets=batch.targets, predictions=predictions)
