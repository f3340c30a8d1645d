"""Weights of the window-and-global attention / expert-layer decoder (`model_type: mellum`),
made by the benchmark from `--seed`: the twin of `benchmark/weights_moe.py` for a stack whose
layers all hold grouped-query attention, under a window or over all that came before, by
the published `layer_types`, and a routed expert layer with no shared expert and no selection
bias. The program under test and the plain reference
(`benchmark/reference/swa_moe_decoder_f32.py`) both get their weights from here. One layer's
tensors depend only on (seed, layer index), and one routed expert's on (seed, layer index,
the expert's index among ALL the router's experts): a layer told to hold experts 8..15 gets
the tensors the uncut layer has there.

Distribution. Matmul kernels: normal, std 0.02, and 0.02 / sqrt(2 L) for the projections
that write into the residual stream (`c_proj`, every expert's `W_2`): the recipe's "scaled"
init. The router's matrix normal std 0.02, float32 as the program keeps it. Norm scales are
1. The large kernels are bfloat16 as the program trains them.
"""

from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.weights import _described, seed_key  # noqa: F401  (the same key for the same seed as the dense decoder's)
from benchmark.weights_hybrid import resolved
from benchmark.weights_moe import embedding, expert_weights, head  # noqa: F401  (they read vocab_size, n_embd, moe_intermediate_size, n_layer off any shape)

STD = 0.02
FLOAT32_LEAVES = ("attention_norm", "ffn_norm", "router")
ATTENTION = ("q_attn", "k_attn", "v_attn", "c_proj")
EXPERTS = ("experts_W", "experts_V", "experts_W_2")
SCALED = ("c_proj", "experts_W_2")  # what writes into the residual stream
MIXER_OF = {"sliding_attention": "swa", "full_attention": "attn"}  # a published layer type as the program's block names its mixer seat


@dataclass(frozen=True)
class Rotary:
    """One kind of layer's rotary, as `rope_parameters` states it."""

    rope_type: str  # "default" | "yarn"
    theta: float
    factor: float = 1.0
    original: int = 0  # original_max_position_embeddings
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 1.0

    @classmethod
    def from_config(cls, config: dict | None, base_freq: float) -> "Rotary":
        if not config:
            return cls("default", float(base_freq))
        if config.get("rope_type", "default") == "default":
            return cls("default", float(config.get("rope_theta", base_freq)))
        factor = float(config["factor"])
        scale = config.get("attention_factor")
        return cls("yarn", float(config["rope_theta"]), factor, int(config["original_max_position_embeddings"]),
                   float(config.get("beta_fast", 32.0)), float(config.get("beta_slow", 1.0)),
                   float(scale) if scale is not None else 0.1 * math.log(factor) + 1.0)


@dataclass(frozen=True)
class SwaMoEShape:
    """Sizes of the decoder, as the configuration's `model` block states them."""

    vocab_size: int
    kinds: tuple  # the attention of every layer: "swa" (under the window) or "attn" (all that came before)
    n_head_q: int
    n_head_kv: int
    head_dim: int
    n_embd: int
    sliding_window: int
    rotary: tuple  # ((kind, Rotary), ...) for both kinds
    n_routed_experts: int
    num_experts_per_tok: int
    moe_intermediate_size: int
    experts_held: int
    expert_offset: int
    norm_topk_prob: bool
    router_aux_loss_coef: float
    norm_eps: float

    @property
    def n_layer(self) -> int:
        return len(self.kinds)

    @property
    def runs(self) -> tuple:
        """Runs of equal kind, in order: (kind, first layer, length): one scan of the program each."""
        out = []
        for i, kind in enumerate(self.kinds):
            if out and out[-1][0] == kind:
                out[-1] = (kind, out[-1][1], out[-1][2] + 1)
            else:
                out.append((kind, i, 1))
        return tuple(out)

    def rotary_of(self, kind: str) -> Rotary:
        return dict(self.rotary)[kind]

    @classmethod
    def from_yaml(cls, raw: dict) -> "SwaMoEShape":
        """`raw` is the cell's YAML as `yaml.safe_load` gives it. Only the untied, unbiased RMSNorm decoder with
        rotary grouped-query attention by `layer_types` and a softmax-routed expert layer (no shared expert, no
        selection bias) in every layer is understood; anything else is an error."""
        model = resolved(raw["model_raw"]["config"], raw)
        moe, types = model.get("moe_config"), model.get("layer_types")
        transforms = model.get("attention_config", {}).get("qkv_transforms", [])
        problems = []
        if not moe or not types:
            problems.append("moe_config and layer_types must be set")
        elif (moe.get("scoring_func") != "softmax" or moe.get("topk_method") != "greedy" or moe.get("n_shared_experts", 0)
              or moe.get("first_k_dense_replace", 0) or float(moe.get("routed_scaling_factor", 1.0)) != 1.0):
            problems.append("the router scores by softmax and chooses greedily, every layer is an expert layer, no shared expert, no scaling")
        if model.get("mla_config") or model.get("attn_layer_period") or model.get("loop_config"):
            problems.append("no latent attention, no state-space layers, no loop")
        if model.get("poe_type") != "NOPE" or [t.get("type_hint") for t in transforms] != ["RotaryTransform"]:
            problems.append("positions are the rotary's (poe_type NOPE, one RotaryTransform)")
        if model.get("attention_config", {}).get("qk_norm_config"):
            problems.append("no QK norm")
        if model.get("use_weight_tying") or model.get("bias"):
            problems.append("the head is not tied and nothing has a bias")
        norms = [model.get(k, {}) for k in ("attention_norm_config", "ffn_norm_config", "lm_head_norm_config")]
        if any(n.get("norm_type") != "rms_norm" for n in norms):
            problems.append("norms must be rms_norm")
        if problems:
            raise ValueError("benchmark weights: " + "; ".join(problems))
        base = transforms[0]["config"].get("base_freq", 10000)
        rope = model.get("rope_parameters") or {}
        routed, held = int(moe["n_routed_experts"]), moe.get("experts_held")
        head_dim = model.get("head_dim")
        return cls(
            vocab_size=int(model["vocab_size"]), kinds=tuple(MIXER_OF[t] for t in types), n_head_q=int(model["n_head_q"]),
            n_head_kv=int(model["n_head_kv"]), head_dim=int(head_dim) if head_dim is not None else int(model["n_embd"]) // int(model["n_head_q"]),
            n_embd=int(model["n_embd"]), sliding_window=int(model["sliding_window"]),
            rotary=tuple((mixer, Rotary.from_config(rope.get(published), base)) for published, mixer in MIXER_OF.items()),
            n_routed_experts=routed, num_experts_per_tok=int(moe["num_experts_per_tok"]), moe_intermediate_size=int(moe["moe_intermediate_size"]),
            experts_held=routed if held is None else int(held), expert_offset=int(moe.get("expert_offset", 0)),
            norm_topk_prob=bool(moe.get("norm_topk_prob", True)), router_aux_loss_coef=float(moe.get("router_aux_loss_coef", 0.0)),
            norm_eps=float(norms[0].get("config", {}).get("epsilon", 1e-6)),
        )

    # ---- counts, for the shape functions and the configuration's arithmetic

    def attention_params(self) -> int:
        """q, k, v and the output projection: q is n_head_q * head_dim wide out of n_embd, whatever n_embd / n_head_q is."""
        return self.n_embd * self.head_dim * (2 * self.n_head_q + 2 * self.n_head_kv)

    def expert_params(self) -> int:
        return 3 * self.n_embd * self.moe_intermediate_size

    def router_params(self) -> int:
        return self.n_embd * self.n_routed_experts

    def layer_matmul_params_passed(self, pairs_held_per_token: float) -> float:
        """Parameters of one layer that ONE token multiplies: attention's four, the router, and as many routed
        experts as the token's pairs that land on held experts (a mean, as the program counted it)."""
        return self.attention_params() + self.router_params() + pairs_held_per_token * self.expert_params()

    def layer_params(self) -> int:
        """Every parameter one layer holds here (the two norms' scales with it)."""
        return self.attention_params() + self.router_params() + self.experts_held * self.expert_params() + 2 * self.n_embd

    def all_params(self) -> int:
        return self.n_layer * self.layer_params() + 2 * self.vocab_size * self.n_embd + self.n_embd

    def positions_seen(self, kind: str, seq: int) -> float:
        """Key positions a query sees, the mean over a row of `seq`: itself and all before it, or no more than the window."""
        w = min(self.sliding_window, seq) if kind == "swa" else seq
        return (w * (w + 1) / 2 + (seq - w) * w) / seq


def _layer_shapes(s: SwaMoEShape) -> dict[str, tuple]:
    e, d = s.n_embd, s.head_dim
    return {"q_attn": (e, s.n_head_q, d), "k_attn": (e, s.n_head_kv, d), "v_attn": (e, s.n_head_kv, d), "c_proj": (s.n_head_q, d, e),
            "router": (e, s.n_routed_experts)}


def layer_weights(shape: SwaMoEShape, key, layer, dtype=jnp.bfloat16) -> dict:
    """Every leaf of layer `layer` (a whole number or a traced index), under the reference's names; kernels in
    `dtype`, the rest float32. Both kinds of layer hold the same leaves. The three expert stacks hold the experts
    `expert_offset .. expert_offset + experts_held - 1`."""
    layer_key = jax.random.fold_in(key, layer)
    out = {}
    for i, (name, dims) in enumerate(_layer_shapes(shape).items()):
        std = STD / np.sqrt(2 * shape.n_layer) if name in SCALED else STD
        value = jax.random.normal(jax.random.fold_in(layer_key, i), dims, jnp.float32) * std
        out[name] = value.astype(jnp.float32 if name in FLOAT32_LEAVES else dtype)
    out.update(attention_norm=jnp.ones((shape.n_embd,), jnp.float32), ffn_norm=jnp.ones((shape.n_embd,), jnp.float32))
    out.update(jax.lax.map(lambda e: expert_weights(shape, layer_key, e, dtype), shape.expert_offset + jnp.arange(shape.experts_held)))
    return out


def run_weights(shape: SwaMoEShape, key, first: int, length: int, dtype=jnp.bfloat16) -> dict:
    """The layers `first .. first + length - 1` stacked on a leading axis."""
    return jax.lax.map(lambda l: layer_weights(shape, key, l, dtype), first + jnp.arange(length))  # one layer's program, compiled once


def _program_block(w: dict) -> dict:
    """One run's stacked leaves in the layout of the program's block."""
    return {"attention_norm": {"scale": w["attention_norm"]}, "ffn_norm": {"scale": w["ffn_norm"]},
            "attn": {name: {"kernel": w[name]} for name in ATTENTION},
            "moe": {"router": {"kernel": w["router"]}, "experts": {name[len("experts_"):]: w[name] for name in EXPERTS}}}


def reference_layout(program_params) -> dict:
    """The program's parameter tree (or a tree shaped like it: gradients, moments), renamed to the reference's
    layout (no copy): `{"runs": [stacked leaves of a run, ...], "wte", "lm_head", "final_norm"}`."""
    p = program_params["params"]
    runs = []
    for i in range(sum(name.startswith("run_") for name in p)):
        block = p[f"run_{i}"]["blocks"]["block"]
        runs.append({"attention_norm": block["attention_norm"]["scale"], "ffn_norm": block["ffn_norm"]["scale"],
                     **{name: block["attn"][name]["kernel"] for name in ATTENTION}, "router": block["moe"]["router"]["kernel"],
                     **{name: block["moe"]["experts"][name[len("experts_"):]] for name in EXPERTS}})
    return {"runs": runs, "wte": p["wte"], "lm_head": p["lm_head"]["kernel"], "final_norm": p["lm_head_norm"]["scale"]}


def program_tree(shape: SwaMoEShape, key, dtype=jnp.bfloat16) -> dict:
    """The whole parameter tree in the layout the program keeps for this stack: `{"params": {"run_<i>": {"blocks":
    {"block": ...stacked over the run's layers}}, "lm_head", "lm_head_norm", "wte"}}`, a run for every stretch of
    layers of one kind. Traceable, and `key` (from `seed_key`) is an argument, so that one compiled program serves every seed."""
    params = {f"run_{i}": {"blocks": {"block": _program_block(run_weights(shape, key, first, length, dtype))}}
              for i, (_, first, length) in enumerate(shape.runs)}
    params["lm_head_norm"] = {"scale": jnp.ones((shape.n_embd,), jnp.float32)}
    params["wte"] = embedding(shape, key, dtype)
    params["lm_head"] = {"kernel": head(shape, key, dtype)}
    return {"params": params}


def make_program_tree(shape: SwaMoEShape, seed: int, like, match_dtypes: bool = True):
    """`program_tree` materialized on the device in one jitted call, with the shardings of `like`: the program's own
    parameter tree (arrays, or shapes from `jax.eval_shape`), whose paths and shapes the result must have: anything
    else means the program's layout changed (or the program has no such model), and is an error."""
    key = seed_key(seed)
    make = lambda key: program_tree(shape, key)  # noqa: E731
    want = _described(like, match_dtypes)
    have = _described(jax.eval_shape(make, key), match_dtypes)
    if want != have:
        differing = sorted(k for k in want.keys() | have.keys() if want.get(k) != have.get(k))
        raise ValueError(
            "benchmark weights do not fit the program's parameter tree: "
            + "; ".join(f"{k}: program {want.get(k)}, benchmark {have.get(k)}" for k in differing[:12])
        )
    shardings = [getattr(x, "sharding", None) for x in jax.tree.leaves(like)]
    if any(s is None for s in shardings):
        return jax.jit(make)(key)
    return jax.jit(make, out_shardings=jax.tree.unflatten(jax.tree.structure(like), shardings))(key)
