"""Weights of the latent-attention / expert-layer decoder (`model_type: deepseek_v3`), made
by the benchmark from `--seed`: the twin of `benchmark/weights.py` for a stack whose
first layers keep a dense feed-forward and whose later layers hold a routed-and-shared
expert layer. The program under test and the plain reference
(`benchmark/reference/moe_mla_decoder_f32.py`) both get their weights from here. One
layer's tensors depend only on (seed, layer index), and one routed expert's on (seed,
layer index, the expert's index among ALL the router's experts): a layer told to hold
experts 16..31 gets the tensors the uncut layer has there.

Distribution. Matmul kernels: normal, std 0.02, and 0.02 / sqrt(2 L) for the projections
that write into the residual stream (`c_proj`, `W_2`, every expert's and the shared
expert's `W_2`): the recipe's "scaled" init. The router's matrix normal std 0.02 and its
selection bias `b` normal std 0.02 (seeded and non-zero, so that a program that ignores
it chooses other experts), both float32 as the program keeps them. Norm scales are 1.
The large kernels are bfloat16 as the program trains them.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.weights import _described, seed_key  # noqa: F401  (the same key for the same seed as the dense decoder's)
from benchmark.weights_hybrid import resolved

STD = 0.02
FLOAT32_LEAVES = ("attention_norm", "ffn_norm", "kv_a_norm", "router", "router_bias")
ATTENTION = ("q_proj", "kv_a_proj", "kv_b_proj", "c_proj")
SCALED = ("c_proj", "W_2", "experts_W_2", "shared_W_2")  # what writes into the residual stream


@dataclass(frozen=True)
class MoEMLAShape:
    """Sizes of the decoder, as the configuration's `model` block states them."""

    vocab_size: int
    n_layer: int
    n_head: int
    n_embd: int
    ffn_hidden: int  # the dense layers' SwiGLU hidden size actually used (6144)
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    rope_theta: float
    n_routed_experts: int
    num_experts_per_tok: int
    moe_intermediate_size: int
    shared_hidden: int  # n_shared_experts * moe_intermediate_size
    first_k_dense_replace: int
    routed_scaling_factor: float
    experts_held: int
    expert_offset: int
    norm_eps: float
    bias_update_speed: float = 0.0  # how far a step moves the selection bias of an expert whose load is off the mean

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def kinds(self) -> tuple:
        """The feed-forward of every layer: "mlp" or "moe" (the mixer is latent attention everywhere)."""
        return tuple("mlp" if i < self.first_k_dense_replace else "moe" for i in range(self.n_layer))

    @property
    def runs(self) -> tuple:
        """Runs of equal kind, in order: (kind, first layer, length)."""
        out = []
        for i, kind in enumerate(self.kinds):
            if out and out[-1][0] == kind:
                out[-1] = (kind, out[-1][1], out[-1][2] + 1)
            else:
                out.append((kind, i, 1))
        return tuple(out)

    @classmethod
    def from_yaml(cls, raw: dict) -> "MoEMLAShape":
        """`raw` is the cell's YAML as `yaml.safe_load` gives it. Only the untied, unbiased
        SwiGLU / RMSNorm decoder with latent attention everywhere and an expert layer after
        the leading dense ones is understood; anything else is an error."""
        model = resolved(raw["model_raw"]["config"], raw)
        mla, moe = model.get("mla_config"), model.get("moe_config")
        problems = []
        if model.get("activation_type") != "swiglu":
            problems.append("activation_type must be swiglu")
        if not mla or not moe:
            problems.append("mla_config and moe_config must be set")
        if model.get("poe_type") != "NOPE" or any(
                t.get("type_hint") != "IdentityTransform" for t in model.get("attention_config", {}).get("qkv_transforms", [])):
            problems.append("positions are latent attention's own rotary (poe_type NOPE, no rotary transform)")
        if model.get("use_weight_tying") or model.get("bias"):
            problems.append("the head is not tied and nothing has a bias")
        norms = [model.get(k, {}) for k in ("attention_norm_config", "ffn_norm_config", "lm_head_norm_config")]
        if any(n.get("norm_type") != "rms_norm" for n in norms):
            problems.append("norms must be rms_norm")
        if problems:
            raise ValueError("benchmark weights: " + "; ".join(problems))
        multiple = int(model.get("enforce_swiglu_hidden_dim_multiple_of", 256))
        hidden = ((int(2 * int(model["ffn_hidden"]) / 3) + multiple - 1) // multiple) * multiple
        routed = int(moe["n_routed_experts"])
        held = moe.get("experts_held")
        return cls(
            vocab_size=int(model["vocab_size"]), n_layer=int(model["n_layer"]), n_head=int(model["n_head_q"]),
            n_embd=int(model["n_embd"]), ffn_hidden=hidden, kv_lora_rank=int(mla["kv_lora_rank"]),
            qk_nope_head_dim=int(mla["qk_nope_head_dim"]), qk_rope_head_dim=int(mla["qk_rope_head_dim"]),
            v_head_dim=int(mla["v_head_dim"]), rope_theta=float(mla.get("rope_theta", 10000.0)), n_routed_experts=routed,
            num_experts_per_tok=int(moe["num_experts_per_tok"]), moe_intermediate_size=int(moe["moe_intermediate_size"]),
            shared_hidden=int(moe.get("n_shared_experts", 0)) * int(moe["moe_intermediate_size"]),
            first_k_dense_replace=int(moe.get("first_k_dense_replace", 0)),
            routed_scaling_factor=float(moe.get("routed_scaling_factor", 1.0)),
            experts_held=routed if held is None else int(held), expert_offset=int(moe.get("expert_offset", 0)),
            norm_eps=float(norms[0].get("config", {}).get("epsilon", 1e-6)),
            bias_update_speed=float(moe.get("bias_update_speed", 0.0)),
        )

    # ---- counts, for the shape functions and the configuration's arithmetic

    def attention_params(self) -> int:
        """The four projections of latent attention (its norm's scale is no matmul)."""
        e, h = self.n_embd, self.n_head
        return (e * h * self.qk_head_dim + e * (self.kv_lora_rank + self.qk_rope_head_dim)
                + self.kv_lora_rank * h * (self.qk_nope_head_dim + self.v_head_dim) + h * self.v_head_dim * e)

    def expert_params(self) -> int:
        return 3 * self.n_embd * self.moe_intermediate_size

    def layer_matmul_params_passed(self, kind: str, pairs_held_per_token: float) -> float:
        """Parameters of one layer that ONE token multiplies: all of attention, and of the
        feed-forward the dense one, or the router, the shared expert and as many routed
        experts as the token's pairs that land on held experts (a mean, as the program counted it)."""
        if kind == "mlp":
            return self.attention_params() + 3 * self.n_embd * self.ffn_hidden
        return (self.attention_params() + self.n_embd * self.n_routed_experts + 3 * self.n_embd * self.shared_hidden
                + pairs_held_per_token * self.expert_params())

    def layer_params(self, kind: str) -> int:
        """Every parameter one layer holds here."""
        other = self.attention_params() + self.kv_lora_rank + 2 * self.n_embd  # the latent's norm, the block's two
        if kind == "mlp":
            return other + 3 * self.n_embd * self.ffn_hidden
        return (other + self.n_embd * self.n_routed_experts + self.n_routed_experts + 3 * self.n_embd * self.shared_hidden
                + self.experts_held * self.expert_params())

    def all_params(self) -> int:
        return sum(self.layer_params(k) for k in self.kinds) + 2 * self.vocab_size * self.n_embd + self.n_embd


def _layer_shapes(s: MoEMLAShape, kind: str) -> dict[str, tuple]:
    e, h = s.n_embd, s.n_head
    shapes = {"q_proj": (e, h, s.qk_head_dim), "kv_a_proj": (e, s.kv_lora_rank + s.qk_rope_head_dim),
              "kv_b_proj": (s.kv_lora_rank, h, s.qk_nope_head_dim + s.v_head_dim), "c_proj": (h, s.v_head_dim, e)}
    if kind == "mlp":
        shapes.update(W=(e, s.ffn_hidden), V=(e, s.ffn_hidden), W_2=(s.ffn_hidden, e))
    else:
        shapes.update(router=(e, s.n_routed_experts), router_bias=(s.n_routed_experts,),
                      shared_W=(e, s.shared_hidden), shared_V=(e, s.shared_hidden), shared_W_2=(s.shared_hidden, e))
    return shapes


def expert_weights(shape: MoEMLAShape, layer_key, expert, dtype=jnp.bfloat16) -> dict:
    """The three matrices of routed expert `expert` (its index among all the router's; a
    whole number or a traced index) of the layer whose key is `layer_key`."""
    key = jax.random.fold_in(jax.random.fold_in(layer_key, 7_000_003), expert)
    e, f = shape.n_embd, shape.moe_intermediate_size
    draw = lambda i, dims, std: (jax.random.normal(jax.random.fold_in(key, i), dims, jnp.float32) * std).astype(dtype)  # noqa: E731
    return {"experts_W": draw(0, (e, f), STD), "experts_V": draw(1, (e, f), STD),
            "experts_W_2": draw(2, (f, e), STD / np.sqrt(2 * shape.n_layer))}


def layer_weights(shape: MoEMLAShape, key, layer, kind: str, dtype=jnp.bfloat16) -> dict:
    """Every leaf of layer `layer` (a whole number or a traced index) of kind `kind`,
    under the reference's names; kernels in `dtype`, the rest float32. An expert layer's
    three stacks hold the experts `expert_offset .. expert_offset + experts_held - 1`."""
    layer_key = jax.random.fold_in(key, layer)
    out = {}
    for i, (name, dims) in enumerate(_layer_shapes(shape, kind).items()):
        std = STD / np.sqrt(2 * shape.n_layer) if name in SCALED else STD
        value = jax.random.normal(jax.random.fold_in(layer_key, i), dims, jnp.float32) * std
        out[name] = value.astype(jnp.float32 if name in FLOAT32_LEAVES else dtype)
    ones = lambda *dims: jnp.ones(dims, jnp.float32)  # noqa: E731
    out.update(attention_norm=ones(shape.n_embd), ffn_norm=ones(shape.n_embd), kv_a_norm=ones(shape.kv_lora_rank))
    if kind == "moe":
        out.update(jax.lax.map(lambda e: expert_weights(shape, layer_key, e, dtype),
                               shape.expert_offset + jnp.arange(shape.experts_held)))
    return out


def embedding(shape: MoEMLAShape, key, dtype=jnp.bfloat16):
    """The embedding table [V, E]."""
    return (jax.random.normal(jax.random.fold_in(jax.random.fold_in(key, 1_000_003), 0),
                              (shape.vocab_size, shape.n_embd), jnp.float32) * STD).astype(dtype)


def head(shape: MoEMLAShape, key, dtype=jnp.bfloat16):
    """The untied head [E, V]."""
    return (jax.random.normal(jax.random.fold_in(jax.random.fold_in(key, 1_000_003), 1),
                              (shape.n_embd, shape.vocab_size), jnp.float32) * STD).astype(dtype)


def run_weights(shape: MoEMLAShape, key, first: int, length: int, kind: str, dtype=jnp.bfloat16) -> dict:
    """The layers `first .. first + length - 1`, all of kind `kind`, stacked on a leading axis."""
    return jax.lax.map(lambda l: layer_weights(shape, key, l, kind, dtype), first + jnp.arange(length))  # one layer's program, compiled once


def _program_block(w: dict, kind: str) -> dict:
    """One run's stacked leaves in the layout of the program's block."""
    block = {"attention_norm": {"scale": w["attention_norm"]}, "ffn_norm": {"scale": w["ffn_norm"]},
             "attn": {**{name: {"kernel": w[name]} for name in ATTENTION}, "kv_a_norm": {"scale": w["kv_a_norm"]}}}
    if kind == "mlp":
        block["mlp"] = {name: {"kernel": w[name]} for name in ("W", "V", "W_2")}
    else:
        block["moe"] = {
            "router": {"kernel": w["router"], "e_score_correction_bias": w["router_bias"]},
            "experts": {name: w[f"experts_{name}"] for name in ("W", "V", "W_2")},
            "shared": {name: {"kernel": w[f"shared_{name}"]} for name in ("W", "V", "W_2")},
        }
    return block


def reference_layout(program_params) -> dict:
    """The program's parameter tree (or a tree shaped like it: gradients, moments),
    renamed to the reference's layout (no copy): `{"runs": [stacked leaves of a run, ...],
    "wte", "lm_head", "final_norm"}`."""
    p = program_params["params"]
    runs = []
    for i in range(sum(name.startswith("run_") for name in p)):
        block = p[f"run_{i}"]["blocks"]["block"]
        w = {"attention_norm": block["attention_norm"]["scale"], "ffn_norm": block["ffn_norm"]["scale"],
             "kv_a_norm": block["attn"]["kv_a_norm"]["scale"], **{name: block["attn"][name]["kernel"] for name in ATTENTION}}
        if "mlp" in block:
            w.update({name: block["mlp"][name]["kernel"] for name in ("W", "V", "W_2")})
        else:
            moe = block["moe"]
            w.update(router=moe["router"]["kernel"], router_bias=moe["router"]["e_score_correction_bias"])
            w.update({f"experts_{name}": moe["experts"][name] for name in ("W", "V", "W_2")})
            w.update({f"shared_{name}": moe["shared"][name]["kernel"] for name in ("W", "V", "W_2")})
        runs.append(w)
    return {"runs": runs, "wte": p["wte"], "lm_head": p["lm_head"]["kernel"], "final_norm": p["lm_head_norm"]["scale"]}


def program_tree(shape: MoEMLAShape, key, dtype=jnp.bfloat16) -> dict:
    """The whole parameter tree in the layout the program keeps for this stack:
    `{"params": {"run_<i>": {"blocks": {"block": ...stacked over the run's layers}},
    "lm_head", "lm_head_norm", "wte"}}`. Traceable, and `key` (from `seed_key`) is an
    argument, so that one compiled program serves every seed."""
    params = {f"run_{i}": {"blocks": {"block": _program_block(run_weights(shape, key, first, length, kind, dtype), kind)}}
              for i, (kind, first, length) in enumerate(shape.runs)}
    params["lm_head_norm"] = {"scale": jnp.ones((shape.n_embd,), jnp.float32)}
    params["wte"] = embedding(shape, key, dtype)
    params["lm_head"] = {"kernel": head(shape, key, dtype)}
    return {"params": params}


def make_program_tree(shape: MoEMLAShape, seed: int, like, match_dtypes: bool = True):
    """`program_tree` materialized on the device in one jitted call, with the shardings
    of `like`: the program's own parameter tree (arrays, or shapes from `jax.eval_shape`),
    whose paths and shapes the result must have: anything else means the program's
    layout changed (or the program has no such model), and is an error."""
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
