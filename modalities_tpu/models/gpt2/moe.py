"""The routed-and-shared expert layer as `model_type: deepseek_v3` computes it (Hugging
Face's `modeling_deepseek_v3.py`): the second feed-forward a block's `MLP` seat can hold,
under the module name `moe`.

On `x [T, d]`, with `E = n_routed_experts`, `k = num_experts_per_tok`:

    s        = sigmoid(x @ router)                 float32, router [d, E]
    choice   = the k largest of s + b              b [E]: `e_score_correction_bias`, a buffer (no gradient, no decay)
    w        = s[choice] / (sum of them + 1e-20) * routed_scaling_factor     (`norm_topk_prob`)
    expert e = W_2_e(silu(W_e x) * (V_e x))        d -> moe_intermediate_size -> d
    out      = sum over the chosen experts of w * expert(x) + shared(x)
    shared   = one SwiGLU d -> n_shared_experts * moe_intermediate_size -> d, on every token (`shared_expert_intermediate_size`
               gives the width where the source names it so); with `shared_expert_gate` times `sigmoid(x w_g)`, `w_g [d, 1]`;
               with `shared_expert_shards` n, one of n equal slices of that width (a chip's share, as the routed experts have theirs)

`n_group` and `topk_group` other than 1 (group-limited routing) are not written and are
refused. No token is dropped; there is no capacity.

**A softmax router** (PR 38: `scoring_func: softmax`, the `qwen3_moe` / `mixtral` / `mellum`
families' way): `s = softmax(x @ router)` over all E, float32, the rest as above. With
`topk_method: greedy` the choice is the k largest of `s` alone: the router holds no
`e_score_correction_bias` leaf and `after_update` moves nothing. Such a model balances its
experts' loads by a loss term, which the layer computes (Switch Transformer, arXiv
2101.03961, eq. 4-6, for k choices):

    aux = E * sum_e f_e P_e      f_e: the share of the layer's k T pairs that went to expert e (no gradient)
                                 P_e: the mean of s_e over the layer's T tokens; both over all E, held or not

1 at balance, E / k where k experts take everything. It is a layer's own (not pooled over
layers, as Hugging Face's `load_balancing_loss_func` pools them: twelve collapsed layers that
picked different experts would count as balanced), rides up with the counters as their last
column, is published as `counter/moe_aux_loss` (the mean over the expert layers), and
`router_aux_loss_coef` times that mean is added to the loss inside the differentiated
function (`GPT2LLM.loss_from_layers`, `training/train_step.py`). Under expert parallelism
the group's counts and means would be summed over the chips (not written: no `ep` axis).

**The share.** The layer is told which experts it holds: `experts_held` of them from
`expert_offset` (default: all). The router keeps its E outputs, the choice its k and the
weights their normalisation over all k chosen; the sum runs over the chosen experts that
are held (`ops/expert_dispatch.py`). What absent experts would have added is left out:
under expert parallelism it is the other chips' to add (the exchange is not written:
there is no `ep` mesh axis yet), on one chip it is simply absent, and that partial result
goes on. Gradients follow: the router learns through the weights of held experts and
through the normaliser.

`b` sits in the parameter tree so that a checkpoint keeps it; only the choice's indices
depend on it, so its gradient is exactly zero, and the `router_bias` weight-decay group
keeps decay off it: AdamW's update of it is zero. How it moves between steps is not in a
`config.json`; it is DeepSeek-V3's published rule (arXiv 2412.19437, section 2.1.2,
"auxiliary-loss-free load balancing"; Wang et al., arXiv 2408.15664): after a step,

    b_e = b_e + bias_update_speed * sign(mean load of the E experts - load of e)

with the loads counted over the step's tokens and all E experts, held or not (the router
sees every choice; under expert parallelism the counts of the group's chips would be
summed). `bias_update_speed` 0, the default, leaves `b` as initialised or loaded.
`update_selection_bias` is the rule; the train step calls it through
`GPT2LLM.after_update` once the optimizer is done.

**A router of kind `mlp`** (PR 40: `model_type: zaya`, `router: mlp`; arXiv 2511.17127). The scores come from a small
MLP over a state `router_hidden_size` wide that is handed from layer to layer, all of it float32:

    s_l    = x W_d + b_d                        [T, R]
    s_l    = s_l + g_l * s_{l-1}                `use_eda`: the previous layer's s (after its own sum, before its norm; zeros
                                                before the first layer); g_l [R] learned, from 1
    z      = RMSNorm_R(s_l)
    logits = W_3 gelu(W_2 gelu(W_1 z + b_1) + b_2)      W_1, W_2 [R, R]; W_3 [R, E + 1] without bias where `use_mod` adds a
                                                column with no expert behind it (a token that picks it skips the layer), else [R, E]
    p      = softmax(logits) over the columns;  choice = the k largest of p + b;  w = p[choice]

gelu is the exact one (erf). The skip column is a router column that no share holds: `plan_dispatch` leaves out a choice
outside `[expert_offset, expert_offset + experts_held)`, so a token that picked it gets zero from the layer and passes by the
residual; the loads, the selection bias and its rule run over all the columns. With one choice a token `norm_topk_prob`
must be off (the normalised weight would be 1 and the router would get no gradient through it). The layer then takes the
previous state beside its input and returns its own beside its output; `GPT2Block` and the layer scan carry it.

Counted in a pass, from the choice: the pairs held, the largest and mean load of a held
expert, and the load of each of the E experts. The block hands them up beside its output
as one row (`COUNTERS`, then the E loads); `GPT2Module` puts them into the `counters`
collection for the train step to publish (the three) and to move `b` by (the loads).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Annotated, Literal, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from pydantic import BaseModel, Field, model_validator

from modalities_tpu.telemetry import scopes

COUNTERS = ("moe_pairs_held", "moe_load_max", "moe_load_mean")  # a layer's, in this order
AUX_LOSS = "moe_aux_loss"  # a softmax router's balance term: the row's last column, after the E loads, and the one with a gradient
SKIP_SHARE = "moe_skip_share"  # where the router has a skip column: the share of a layer's tokens that chose it, the mean over the expert layers
EXPERT_LOAD = "moe_expert_load"  # [expert layers, E]: the pairs each of the router's experts got, held or not
BIAS_LEAF = "moe/router/e_score_correction_bias"


class MoEConfig(BaseModel):
    """The `moe_config` block of a `model.gpt2` config; keys as `deepseek_v3` publishes
    them, and the two that say which experts this layer holds."""

    n_routed_experts: Annotated[int, Field(strict=True, ge=2)]
    num_experts_per_tok: Annotated[int, Field(strict=True, ge=1)]
    moe_intermediate_size: Annotated[int, Field(strict=True, ge=1)]
    n_shared_experts: Annotated[int, Field(strict=True, ge=0)] = 0
    first_k_dense_replace: Annotated[int, Field(strict=True, ge=0)] = 0  # the leading layers that keep the dense `mlp`
    moe_layer_freq: Annotated[int, Field(strict=True, ge=1)] = 1
    routed_scaling_factor: float = 1.0
    norm_topk_prob: bool = True
    scoring_func: str = "sigmoid"
    topk_method: str = "noaux_tc"
    n_group: int = 1
    topk_group: int = 1
    experts_held: Optional[Annotated[int, Field(strict=True, ge=1)]] = None  # default: all of them
    expert_offset: Annotated[int, Field(strict=True, ge=0)] = 0
    # a recipe's, not a config.json's: how far a step moves the selection bias of an expert whose load is off the mean (0: never)
    bias_update_speed: Annotated[float, Field(ge=0.0)] = 0.0
    # a recipe's too: the weight of the balance term in the loss (a softmax router's; 0: counted and published, not added)
    router_aux_loss_coef: Annotated[float, Field(ge=0.0)] = 0.0
    # `model_type: zaya` (PR 40). `router: mlp` scores by an MLP over a state `router_hidden_size` wide (the module docstring);
    # `use_eda` (the source's `zaya_use_eda`) adds the previous layer's state under a learned gate, `use_mod` (`zaya_use_mod`)
    # a column with no expert behind it. `matrix`: one `[d, E]` matrix, as before there were kinds.
    router: Literal["matrix", "mlp"] = "matrix"
    router_hidden_size: Optional[Annotated[int, Field(strict=True, ge=1)]] = None
    use_eda: bool = False
    use_mod: bool = False
    # `model_type: qwen3_next` / `qwen2_moe` (PR 44). `shared_expert_intermediate_size`: the shared expert's width where the source
    # names it so (in place of `n_shared_experts`, which counts widths of `moe_intermediate_size`). `shared_expert_gate`: the shared
    # expert's output times `sigmoid(x w_g)`, `w_g [d, 1]` without bias, a token's own scalar.
    shared_expert_intermediate_size: Optional[Annotated[int, Field(strict=True, ge=1)]] = None
    shared_expert_gate: bool = False
    # `model_type: granitemoehybrid` (PR 52): the shared expert's share. This layer holds ONE of that many equal slices of the shared
    # expert's published width (columns of `W` and `V`, rows of `W_2`), as a chip of a tensor-parallel group would; the width above
    # stays as published. What the other slices would add is the other chips' to add (the exchange is not written). 1: the whole width.
    shared_expert_shards: Annotated[int, Field(strict=True, ge=1)] = 1

    @model_validator(mode="after")
    def refuse_what_is_not_written(self) -> "MoEConfig":
        if self.scoring_func not in ("sigmoid", "softmax"):
            raise ValueError(f"moe_config.scoring_func {self.scoring_func!r}: sigmoid and softmax scores are written")
        if self.n_group != 1 or self.topk_group != 1:
            raise ValueError("moe_config.n_group / topk_group: group-limited routing is not written; both must be 1")
        if self.topk_method not in ("noaux_tc", "greedy"):
            raise ValueError(f"moe_config.topk_method {self.topk_method!r}: noaux_tc (scores plus a selection bias) and greedy "
                             "(the scores alone: no bias leaf) are written")
        if self.topk_method == "greedy" and self.bias_update_speed:
            raise ValueError("moe_config.bias_update_speed moves the selection bias, and topk_method greedy has none")
        if self.router_aux_loss_coef and self.scoring_func != "softmax":
            raise ValueError("moe_config.router_aux_loss_coef weighs the balance term of a softmax router (the mean probability "
                             "of an expert against its share of the pairs); sigmoid scores balance by the selection bias")
        if self.moe_layer_freq != 1:
            raise ValueError("moe_config.moe_layer_freq: every layer after the leading dense ones is an expert layer; only 1 is written")
        if self.num_experts_per_tok > self.n_routed_experts:
            raise ValueError("moe_config.num_experts_per_tok exceeds n_routed_experts")
        held = self.n_routed_experts if self.experts_held is None else self.experts_held
        if self.expert_offset + held > self.n_routed_experts:  # the skip column (`use_mod`) is a column, not an expert: nobody holds it
            raise ValueError("moe_config: expert_offset + experts_held exceeds n_routed_experts")
        if self.shared_expert_intermediate_size is not None and self.n_shared_experts:
            raise ValueError("moe_config: shared_expert_intermediate_size and n_shared_experts both give the shared expert its width; set one")
        shared_width = self.shared_expert_intermediate_size or self.n_shared_experts * self.moe_intermediate_size
        if shared_width % self.shared_expert_shards:
            raise ValueError(f"moe_config.shared_expert_shards {self.shared_expert_shards} does not divide the shared expert's width {shared_width}")
        if self.shared_expert_gate and not (self.shared_expert_intermediate_size or self.n_shared_experts):
            raise ValueError("moe_config.shared_expert_gate gates a shared expert: give shared_expert_intermediate_size (or n_shared_experts)")
        if self.router == "matrix" and (self.router_hidden_size is not None or self.use_eda or self.use_mod):
            raise ValueError("moe_config.router_hidden_size, use_eda and use_mod belong to the router of kind mlp (router: mlp); "
                             "the matrix router has no state to hand on and no skip column")
        if self.router == "mlp":
            if self.router_hidden_size is None:
                raise ValueError("moe_config.router mlp needs router_hidden_size (the width of the state its MLP reads)")
            if self.scoring_func != "softmax" or self.topk_method != "noaux_tc" or self.router_aux_loss_coef:
                raise ValueError("moe_config.router mlp scores by softmax over its columns and balances by the selection bias: "
                                 "scoring_func softmax, topk_method noaux_tc, no router_aux_loss_coef")
            if self.num_experts_per_tok == 1 and self.norm_topk_prob:
                raise ValueError("moe_config.norm_topk_prob with one choice a token makes every weight 1 and leaves the router "
                                 "no gradient through it: set it false")
            if self.n_shared_experts or self.shared_expert_intermediate_size or self.first_k_dense_replace:
                raise ValueError("moe_config.router mlp: a shared expert or leading dense layers beside the carried state are not written")
        return self


@dataclass(frozen=True)
class MoESpec:
    n_routed_experts: int
    num_experts_per_tok: int
    moe_intermediate_size: int
    shared_hidden: int  # the shared expert's width as held: the published one (or n_shared_experts * moe_intermediate_size) over `shared_expert_shards`; 0: no shared expert
    first_k_dense_replace: int
    routed_scaling_factor: float
    norm_topk_prob: bool
    experts_held: int
    expert_offset: int
    bias_update_speed: float = 0.0
    scoring_func: str = "sigmoid"
    selection_bias: bool = True  # `topk_method: noaux_tc`; False (`greedy`): no bias leaf in the tree
    router_aux_loss_coef: float = 0.0
    router: str = "matrix"  # "mlp": an MLP over a state `router_hidden` wide (PR 40)
    router_hidden: int = 0
    use_eda: bool = False  # the state is handed from layer to layer
    skip_column: bool = False  # a last router column with no expert behind it
    shared_gate: bool = False  # the shared expert's output times `sigmoid(x w_g)`

    @property
    def counts_aux_loss(self) -> bool:
        """A softmax matrix router computes its balance term every pass (the row's last column), whatever its weight in the
        loss; the MLP router balances by its selection bias and has none."""
        return self.scoring_func == "softmax" and self.router == "matrix"

    @property
    def router_width(self) -> int:
        """The router's columns: every expert, held or not, and the skip column where there is one."""
        return self.n_routed_experts + self.skip_column

    @property
    def state_width(self) -> int:
        """The width of the state a layer takes from the one before and hands on; 0: none."""
        return self.router_hidden if self.use_eda else 0

    @classmethod
    def from_config(cls, config: "MoEConfig | dict") -> "MoESpec":
        if isinstance(config, dict):
            config = MoEConfig(**config)
        return cls(
            n_routed_experts=config.n_routed_experts, num_experts_per_tok=config.num_experts_per_tok,
            moe_intermediate_size=config.moe_intermediate_size,
            shared_hidden=(config.shared_expert_intermediate_size or config.n_shared_experts * config.moe_intermediate_size) // config.shared_expert_shards,
            first_k_dense_replace=config.first_k_dense_replace, routed_scaling_factor=float(config.routed_scaling_factor),
            norm_topk_prob=config.norm_topk_prob,
            experts_held=config.n_routed_experts if config.experts_held is None else config.experts_held,
            expert_offset=config.expert_offset, bias_update_speed=float(config.bias_update_speed),
            scoring_func=config.scoring_func, selection_bias=config.topk_method == "noaux_tc",
            router_aux_loss_coef=float(config.router_aux_loss_coef), router=config.router,
            router_hidden=config.router_hidden_size or 0, use_eda=config.use_eda, skip_column=config.use_mod,
            shared_gate=config.shared_expert_gate,
        )


def update_selection_bias(bias, load, speed: float):
    """DeepSeek-V3's rule for `b` after a step. bias, load [..., E]: an expert that got more
    than the mean of the E loses `speed`, one that got less gains it, one on the mean stays."""
    return bias + speed * jnp.sign(jnp.mean(load, axis=-1, keepdims=True) - load).astype(bias.dtype)


def ffn_kinds(n_layer: int, moe: Optional[MoESpec]) -> tuple[str, ...]:
    """The feed-forward of every layer from the one published key: the first
    `first_k_dense_replace` layers keep the dense `mlp`, every later one is `moe`."""
    if moe is None:
        return ("mlp",) * n_layer
    return tuple("mlp" if i < moe.first_k_dense_replace else "moe" for i in range(n_layer))


class _Router(nn.Module):
    """Scores, choice and weights, in float32 whatever the compute dtype."""

    moe: MoESpec

    @nn.compact
    def __call__(self, x):
        moe = self.moe
        kernel = self.param("kernel", nn.with_logical_partitioning(nn.initializers.normal(0.02), ("embed", "router")),
                            (x.shape[-1], moe.n_routed_experts), jnp.float32)
        logits = jnp.dot(x.astype(jnp.float32), kernel, precision=jax.lax.Precision.HIGHEST)
        scores = jax.nn.softmax(logits, axis=-1) if moe.scoring_func == "softmax" else jax.nn.sigmoid(logits)
        return _chosen(self, moe, scores, x.shape[0])


def _chosen(module: nn.Module, moe: MoESpec, scores, tokens: int):
    """Choice, weights, loads and the balance term from a router's scores `[T, columns]`: what both kinds of router share.
    Holds the selection bias of the calling router module."""
    columns = scores.shape[-1]
    selection = scores
    if moe.selection_bias:
        bias = module.param("e_score_correction_bias", nn.with_logical_partitioning(nn.initializers.zeros, ("router",)),
                            (columns,), jnp.float32)
        selection = scores + jax.lax.stop_gradient(bias)
    _, choice = jax.lax.top_k(selection, moe.num_experts_per_tok)
    # the scores at the chosen experts, as a compare against all the experts and a sum: `take_along_axis` is a gather
    # forward and a scatter backward, 65 ns an index on the chip (13 ms a step here), this a few elementwise passes
    chosen = choice[..., None] == jnp.arange(columns, dtype=choice.dtype)
    weights = jnp.sum(jnp.where(chosen, scores[:, None, :], 0.0), axis=-1)
    load = jnp.sum(chosen, axis=(0, 1), dtype=jnp.float32)  # of every expert the router knows, held or not
    if moe.norm_topk_prob:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-20)
    aux = None
    if moe.counts_aux_loss:  # E sum_e f_e P_e: the gradient reaches the router through P alone
        share = jax.lax.stop_gradient(load) / (tokens * moe.num_experts_per_tok)
        aux = moe.n_routed_experts * jnp.sum(share * jnp.mean(scores, axis=0))
    return choice, weights * moe.routed_scaling_factor, load, aux


class _MLPRouter(nn.Module):
    """The router of kind `mlp` (the module docstring), float32 whatever the compute dtype. Takes the layer's tokens and
    the previous layer's state (None: there is none to add), returns what `_Router` returns and the state it hands on."""

    moe: MoESpec
    eps: float

    @nn.compact
    def __call__(self, x, previous=None):
        moe, width, f32 = self.moe, self.moe.router_hidden, jnp.float32
        highest = jax.lax.Precision.HIGHEST

        def dense(features, name, use_bias=True):
            return nn.Dense(features, use_bias=use_bias, name=name, dtype=f32, param_dtype=f32, precision=highest,
                            kernel_init=nn.with_logical_partitioning(nn.initializers.normal(0.02), (None, None)),
                            bias_init=nn.with_logical_partitioning(nn.initializers.zeros, (None,)))

        state = dense(width, scopes.ROUTER_DOWN)(x.astype(f32))
        if moe.use_eda:
            with jax.named_scope(scopes.ROUTER_EDA):
                gate = self.param("eda_gate", nn.with_logical_partitioning(nn.initializers.ones, (None,)), (width,), f32)
                if previous is not None:
                    state = state + gate * previous.astype(f32)
        with jax.named_scope(scopes.ROUTER_MLP):
            scale = self.param("norm_scale", nn.with_logical_partitioning(nn.initializers.ones, (None,)), (width,), f32)
            z = state * jax.lax.rsqrt(jnp.mean(state * state, axis=-1, keepdims=True) + self.eps) * scale
            hidden = jax.nn.gelu(dense(width, "fc1")(z), approximate=False)
            hidden = jax.nn.gelu(dense(width, "fc2")(hidden), approximate=False)
            scores = jax.nn.softmax(dense(moe.router_width, "out", use_bias=False)(hidden), axis=-1)
        return (*_chosen(self, moe, scores, x.shape[0]), state)


class _SharedExpert(nn.Module):
    """The dense SwiGLU every token passes: the `MLP`'s arithmetic and leaf names at another hidden size."""

    spec: object
    hidden: int

    @nn.compact
    def __call__(self, x):
        from modalities_tpu.models.gpt2.gpt2_model import _dense_general, with_logical_constraint

        spec = self.spec
        h = nn.silu(_dense_general(spec, self.hidden, "W", ("embed", "mlp"), x.dtype)(x)) * _dense_general(
            spec, self.hidden, "V", ("embed", "mlp"), x.dtype)(x)
        h = with_logical_constraint(h, ("batch", "seq", "mlp"), spec)
        return _dense_general(spec, spec.n_embd, "W_2", ("mlp", "embed"), x.dtype)(h)


class _Experts(nn.Module):
    """The held experts' three stacks `[held, d, f]`, `[held, d, f]`, `[held, f, d]`."""

    spec: object

    @nn.compact
    def __call__(self):
        spec, moe = self.spec, self.spec.moe
        dtype = jnp.dtype(spec.param_dtype)
        init = nn.initializers.normal(0.02)
        shape_in, shape_out = (moe.experts_held, spec.n_embd, moe.moe_intermediate_size), (moe.experts_held, moe.moe_intermediate_size, spec.n_embd)
        w = self.param("W", nn.with_logical_partitioning(init, ("experts", "embed", "expert_mlp")), shape_in, dtype)
        v = self.param("V", nn.with_logical_partitioning(init, ("experts", "embed", "expert_mlp")), shape_in, dtype)
        w_2 = self.param("W_2", nn.with_logical_partitioning(init, ("experts", "expert_mlp", "embed")), shape_out, dtype)
        return w, v, w_2


class MoE(nn.Module):
    """The expert layer; sits in a block's `MLP` seat under the name `moe`. Returns its
    output and what the layer counted (float32 [3 + columns]: `COUNTERS`, then the load of each of the router's columns, its
    experts and the skip column where it has one; a softmax matrix router's balance term after them, one entry more, the
    one that carries a gradient)."""

    spec: object  # GPT2ModelSpec (its `moe` is the MoESpec)
    deterministic: bool = True

    @nn.compact
    def __call__(self, x, router_state=None):
        """`router_state` [B, S, R]: the previous layer's, for a router that is handed one (`use_eda`; None before the first
        layer). Such a layer returns `(out, counters, its own state)`, every other `(out, counters)`."""
        from modalities_tpu.ops import expert_dispatch
        from modalities_tpu.telemetry import get_active_telemetry

        spec, moe = self.spec, self.spec.moe
        batch, seq, width = x.shape
        tokens = x.reshape(batch * seq, width)
        pairs = batch * seq * moe.num_experts_per_tok
        combine = expert_dispatch.combine_form(batch * seq, moe.num_experts_per_tok, moe.experts_held, width)
        block = expert_dispatch.combine_block(batch * seq)
        # runs while tracing: once per shape, nothing per step
        get_active_telemetry().emit_event_once("moe_dispatch_plan", {
            "tokens": batch * seq, "router_width": moe.n_routed_experts, "choices": moe.num_experts_per_tok,
            "experts_held": moe.experts_held, "expert_offset": moe.expert_offset, "tile": expert_dispatch.TILE,
            "rows": expert_dispatch.rows_for(pairs, moe.experts_held, expert_dispatch.TILE),
            "kernels": ("moe_combine",) if combine == "slabs" else (),  # the grouped products and the tiles' gathers are the plain form
            "combine": combine, "combine_block": block, "combine_blocks_at_most": moe.experts_held * -(-batch * seq // block),
            **({"router": "mlp", "skip_column": moe.skip_column, "router_state": moe.state_width} if moe.router == "mlp" else {}),
        })

        state = None
        with jax.named_scope(scopes.MOE_ROUTER):
            if moe.router == "mlp":
                previous = None if router_state is None else router_state.reshape(batch * seq, moe.router_hidden)
                choice, weights, load, aux, state = _MLPRouter(moe, spec.ffn_norm.eps, name="router")(tokens, previous)
            else:
                choice, weights, load, aux = _Router(moe, name="router")(tokens)
        with jax.named_scope(scopes.MOE_DISPATCH):
            plan = expert_dispatch.plan_dispatch(choice, moe.expert_offset, moe.experts_held)
            held = jnp.sum(plan.group_sizes).astype(jnp.float32)
            counters = jax.lax.stop_gradient(jnp.concatenate(
                [jnp.stack([held, jnp.max(plan.group_sizes).astype(jnp.float32), held / moe.experts_held]), load]))
            if aux is not None:
                counters = jnp.concatenate([counters, aux[None]])
        w, v, w_2 = _Experts(spec, name="experts")()
        # one loop over the tiles in use; inside it the gather is `dispatch`, the products `experts`; the sum by token after it `combine`
        routed = expert_dispatch.routed_experts(tokens, choice, weights, w, v, w_2, offset=moe.expert_offset, plan=plan, combine=combine)
        out = routed.reshape(x.shape)
        if moe.shared_hidden:
            shared = _SharedExpert(spec, moe.shared_hidden, name=scopes.MOE_SHARED)(x)
            if moe.shared_gate:
                with jax.named_scope(scopes.MOE_SHARED_GATE):
                    w_g = self.param("shared_gate", nn.with_logical_partitioning(nn.initializers.normal(0.02), ("embed", None)),
                                     (width, 1), jnp.dtype(spec.param_dtype))
                    gate = jnp.einsum("bsd,do->bso", x, w_g.astype(x.dtype), preferred_element_type=jnp.float32)
                    shared = (shared.astype(jnp.float32) * jax.nn.sigmoid(gate)).astype(x.dtype)
            with jax.named_scope(scopes.MOE_COMBINE):
                out = out + shared
        out = nn.Dropout(rate=spec.dropout)(out, deterministic=self.deterministic or spec.dropout == 0.0)
        if moe.state_width:
            return out, counters, state.reshape(batch, seq, moe.router_hidden)
        return out, counters
