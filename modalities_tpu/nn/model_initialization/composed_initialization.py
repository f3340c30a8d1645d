"""Weight init routines (reference: src/modalities/nn/model_initialization/composed_initialization.py:89-154,
initialization_routines.py:62-131, parameter_name_filters.py).

Reference semantics: regex-targeted re-initialization per group —
- plain: N(0, std) with std a float or "auto" = sqrt(2/(5*hidden_dim))
- scaled: plain std divided by sqrt(2*num_layers) for residual-out projections
- scaled_embed: N(0, sqrt(0.4)) for embeddings

In JAX these are pure param-tree transforms applied right after (sharded) init — the
deferred-init/`reset_parameters` replay of the reference (model_factory.py:271-281)
is unnecessary because init already runs jitted and sharded.
"""

from __future__ import annotations

import math
import re
from dataclasses import dataclass, field
from typing import Optional

from modalities_tpu.nn.model_initialization.initialization_if import ModelInitializationIF

# regex groups per supported model type (reference parameter_name_filters.py)
NAMED_PARAMETER_INIT_GROUPS = {
    "gpt2": {
        # the state-space mixer's three large kernels among them; its convolution, dt_proj, A_log and D
        # keep the initial values Mamba publishes (models/gpt2/ssm.py)
        # latent attention's projections, the router and the experts' stacks (bare leaves, no `kernel` under them)
        # among them; the router's selection bias keeps its zeros (models/gpt2/moe.py)
        "weighted_layers": [r".*(q_attn|k_attn|v_attn|c_proj|c_fc|W|V|W_2|in_proj|x_proj|out_proj|q_proj|kv_a_proj|kv_b_proj|router)/kernel.*",
                            r".*/experts/(W|V|W_2)(/[^/]*)?$", r".*wte.*", r".*wpe.*",
                            # the gated delta rule's two projections in (its `out_proj` is above) and the shared expert's gate; its
                            # convolution, A_log and dt_bias keep the initial values the source publishes (models/gpt2/gdn.py)
                            r".*/gdn/(qkvz|ba)/kernel.*", r".*/moe/shared_gate$"],
        "embedding_layers": [r".*(wte|wpe).*"],
        "projection_layers": [r".*(c_proj|W_2|out_proj)/kernel.*", r".*/experts/W_2(/[^/]*)?$"],
        "norm_layers": [r".*(norm|scale).*"],
    },
    "coca": {
        "weighted_layers": [r".*kernel.*"],
        "embedding_layers": [r".*(embedding|wte|wpe).*"],
        "projection_layers": [r".*(c_proj|W_2|out_proj)/kernel.*"],
        "norm_layers": [r".*(norm|scale).*"],
    },
}


def _param_name(path) -> str:
    return "/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)


@dataclass
class InitializationRoutine:
    """One regex-targeted re-init: N(mean, std) over matching parameters."""

    patterns: list[str]
    std: float
    mean: float = 0.0

    def apply(self, params, rng):
        import jax
        import jax.numpy as jnp

        compiled = [re.compile(p) for p in self.patterns]
        flat = jax.tree_util.tree_flatten_with_path(params)
        counter = 0
        new_leaves = []
        for path, leaf in flat[0]:
            name = _param_name(path)
            if any(c.search(name) for c in compiled) and hasattr(leaf, "shape") and leaf.ndim >= 1:
                key = jax.random.fold_in(rng, counter)
                new_leaves.append(
                    (self.mean + self.std * jax.random.normal(key, leaf.shape, leaf.dtype)).astype(leaf.dtype)
                )
            else:
                new_leaves.append(leaf)
            counter += 1
        return jax.tree_util.tree_unflatten(flat[1], new_leaves)


class ComposedModelInitialization(ModelInitializationIF):
    """Plain + optional scaled + optional scaled_embed, regex-targeted
    (reference: composed_initialization.py:89-154)."""

    def __init__(
        self,
        model_type: str,
        weight_init_type: str,  # plain | scaled | scaled_embed (reference WeightInitTypes)
        mean: float = 0.0,
        std: float | str = 0.02,  # float or "auto"
        num_layers: Optional[int] = None,
        hidden_dim: Optional[int] = None,
    ):
        if model_type not in NAMED_PARAMETER_INIT_GROUPS:
            raise ValueError(
                f"Unknown model_type {model_type!r}; known: {sorted(NAMED_PARAMETER_INIT_GROUPS)}"
            )
        groups = NAMED_PARAMETER_INIT_GROUPS[model_type]

        if std == "auto":
            if hidden_dim is None:
                raise ValueError('std="auto" requires hidden_dim')
            std_value = math.sqrt(2 / (5 * hidden_dim))
        else:
            std_value = float(std)

        self.routines: list[InitializationRoutine] = [
            InitializationRoutine(patterns=groups["weighted_layers"], std=std_value, mean=mean)
        ]
        if weight_init_type in ("scaled", "scaled_embed"):
            if num_layers is None:
                raise ValueError("scaled init requires num_layers")
            self.routines.append(
                InitializationRoutine(
                    patterns=groups["projection_layers"],
                    std=std_value / math.sqrt(2 * num_layers),
                    mean=mean,
                )
            )
        if weight_init_type == "scaled_embed":
            self.routines.append(
                InitializationRoutine(patterns=groups["embedding_layers"], std=math.sqrt(0.4), mean=mean)
            )

    def initialize_in_place(self, params, rng):
        for i, routine in enumerate(self.routines):
            import jax

            params = routine.apply(params, jax.random.fold_in(rng, i))
        return params
