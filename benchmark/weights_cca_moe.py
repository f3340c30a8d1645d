"""Weights of the compressed-convolutional-attention / expert-layer decoder (`model_type:
zaya`), made by the benchmark from `--seed`: the twin of `benchmark/weights_swa_moe.py` for
a stack whose layers all hold attention in a compressed latent (two convolutions over the
sequence on q and k, a mean shared by them, an L2 norm with a key temperature, half the
value heads read off the previous position), scaled residual merges, and an expert layer
whose router is an MLP over a state handed from layer to layer, with one choice a token and
a column that skips. The program under test and the plain reference
(`benchmark/reference/cca_moe_decoder_f32.py`) both get their weights from here. One
layer's tensors depend only on (seed, layer index), and one routed expert's on (seed, layer
index, the expert's index among ALL the router's experts): a layer told to hold experts
8..15 gets the tensors the uncut layer has there.

Distribution. Matrices: normal, std 0.02, and 0.02 / sqrt(2 L) for the projections that
write into the residual stream (`c_proj`, every expert's `W_2`): the recipe's "scaled" init;
the grouped convolution's `[d, d]` matrices a tap and a head and the router's matrices with
them (the router's float32, as the program keeps them). The depthwise convolution's taps:
uniform in +-K**-0.5, float32 (torch's default for a depthwise Conv1d, as the state-space
cell's). Ones: norm scales, the merges' scales, the key temperature, the router's gate on
the state handed on. Zeros: every bias and shift, and the selection bias. The large
matrices are bfloat16 as the program trains them.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.weights import _described, seed_key  # noqa: F401  (the same key for the same seed as the dense decoder's)
from benchmark.weights_hybrid import resolved
from benchmark.weights_moe import embedding, expert_weights  # noqa: F401  (they read vocab_size, n_embd, moe_intermediate_size, n_layer off any shape)

STD = 0.02
ATTENTION = ("q_attn", "k_attn", "v_attn", "v_attn_prev", "c_proj")
CONVOLUTIONS = ("conv0_kernel", "conv0_bias", "conv1_kernel", "conv1_bias")
MERGE = ("residual_scale", "residual_bias", "out_scale", "out_bias")
MERGES = tuple(f"{merge}_{leaf}" for merge in ("attn_merge", "ffn_merge") for leaf in MERGE)
ROUTER_DENSE = ("down", "fc1", "fc2")  # with a bias each; `router_out` has none
EXPERTS = ("experts_W", "experts_V", "experts_W_2")
SCALED = ("c_proj", "experts_W_2")  # what writes into the residual stream
BFLOAT16_LEAVES = (*ATTENTION, "conv1_kernel", *EXPERTS)  # the rest is float32 in the program
ONES = ("attention_norm", "ffn_norm", "key_temperature", "eda_gate", "router_norm", *(m for m in MERGES if m.endswith("scale")))
ZEROS = ("conv0_bias", "conv1_bias", "router_bias", *(f"router_{n}_bias" for n in ROUTER_DENSE), *(m for m in MERGES if m.endswith("bias")))


@dataclass(frozen=True)
class CcaMoEShape:
    """Sizes of the decoder, as the configuration's `model` block states them."""

    vocab_size: int
    n_layer: int
    n_head_q: int
    n_head_kv: int
    head_dim: int
    n_embd: int
    time0: int  # taps of the depthwise convolution
    time1: int  # taps of the grouped one
    rotated: int  # the channels of a head the rotary turns, from the first
    rope_theta: float
    n_routed_experts: int
    num_experts_per_tok: int
    moe_intermediate_size: int
    experts_held: int
    expert_offset: int
    router_hidden: int
    use_eda: bool
    skip_column: bool
    bias_update_speed: float
    norm_eps: float
    without: tuple = ()  # steps of the equations a control leaves out (`benchmark/tools/control_cca_moe.py`); a cell's YAML never sets one

    @property
    def latent_heads(self) -> int:
        return self.n_head_q + self.n_head_kv

    @property
    def router_width(self) -> int:
        """The router's columns: the experts, held or not, and the skip column where there is one."""
        return self.n_routed_experts + self.skip_column

    @classmethod
    def from_yaml(cls, raw: dict) -> "CcaMoEShape":
        """`raw` is the cell's YAML as `yaml.safe_load` gives it. Only the tied, unbiased RMSNorm decoder of `hybrid` layers
        (compressed convolutional attention, scaled merges, the MLP router with its state handed on, one choice without
        normalisation, no shared expert) is understood; anything else is an error."""
        model = resolved(raw["model_raw"]["config"], raw)
        moe, types, cca = model.get("moe_config"), model.get("layer_types"), model.get("cca_config")
        transforms = model.get("attention_config", {}).get("qkv_transforms", [])
        problems = []
        if not moe or not types or not cca or set(types) != {"hybrid"}:
            problems.append("moe_config, cca_config and layer_types of hybrid must be set")
        elif (moe.get("router") != "mlp" or moe.get("scoring_func") != "softmax" or moe.get("topk_method", "noaux_tc") != "noaux_tc"
              or moe.get("norm_topk_prob", True) or moe.get("n_shared_experts", 0) or moe.get("first_k_dense_replace", 0)
              or float(moe.get("routed_scaling_factor", 1.0)) != 1.0 or moe.get("router_aux_loss_coef", 0.0)):
            problems.append("the router is the MLP (router: mlp) with softmax scores, a selection bias, weights not normalised; every layer an expert layer, no shared expert, no scaling, no balance term")
        if not model.get("scale_residual_merge"):
            problems.append("scale_residual_merge must be set")
        if model.get("mla_config") or model.get("attn_layer_period") or model.get("loop_config") or model.get("sliding_window"):
            problems.append("no latent attention, no state-space layers, no loop, no window")
        if model.get("poe_type") != "NOPE" or [t.get("type_hint") for t in transforms] != ["RotaryTransform"]:
            problems.append("positions are the rotary's (poe_type NOPE, one RotaryTransform)")
        if not model.get("use_weight_tying") or model.get("bias"):
            problems.append("the head is tied to the table and nothing in attention or head has a bias")
        norms = [model.get(k, {}) for k in ("attention_norm_config", "ffn_norm_config", "lm_head_norm_config")]
        if any(n.get("norm_type") != "rms_norm" for n in norms):
            problems.append("norms must be rms_norm")
        if problems:
            raise ValueError("benchmark weights: " + "; ".join(problems))
        rope = (model.get("rope_parameters") or {}).get("hybrid") or {}
        if rope.get("rope_type", "default") != "default":
            raise ValueError("benchmark weights: the hybrid layers' rotary is the default one")
        routed, held = int(moe["n_routed_experts"]), moe.get("experts_held")
        head_dim = model.get("head_dim")
        head_dim = int(head_dim) if head_dim is not None else int(model["n_embd"]) // int(model["n_head_q"])
        return cls(
            vocab_size=int(model["vocab_size"]), n_layer=int(model["n_layer"]), n_head_q=int(model["n_head_q"]), n_head_kv=int(model["n_head_kv"]),
            head_dim=head_dim, n_embd=int(model["n_embd"]), time0=int(cca.get("cca_time0", 2)), time1=int(cca.get("cca_time1", 2)),
            rotated=int(head_dim * float(rope.get("partial_rotary_factor", 1.0))),
            rope_theta=float(rope.get("rope_theta", transforms[0]["config"].get("base_freq", 10000))),
            n_routed_experts=routed, num_experts_per_tok=int(moe["num_experts_per_tok"]), moe_intermediate_size=int(moe["moe_intermediate_size"]),
            experts_held=routed if held is None else int(held), expert_offset=int(moe.get("expert_offset", 0)),
            router_hidden=int(moe["router_hidden_size"]), use_eda=bool(moe.get("use_eda", False)), skip_column=bool(moe.get("use_mod", False)),
            bias_update_speed=float(moe.get("bias_update_speed", 0.0)), norm_eps=float(norms[0].get("config", {}).get("epsilon", 1e-6)),
        )

    # ---- counts, for the shape functions and the configuration's arithmetic

    def attention_params(self) -> int:
        """The four projections into the latent (q, k, and v's two halves) and the one out of it."""
        return self.n_embd * self.head_dim * (2 * self.n_head_q + 2 * self.n_head_kv)

    def grouped_conv_params(self) -> int:
        """The grouped convolution's matrices: `time1` taps of `[d, d]` a latent head."""
        return self.time1 * self.latent_heads * self.head_dim * self.head_dim

    def conv_params(self) -> int:
        """Both convolutions with their biases."""
        return (self.time0 + 1) * self.latent_heads * self.head_dim + self.grouped_conv_params() + self.latent_heads * self.head_dim

    def router_matmul_params(self) -> int:
        """The router's four matrices: down, the two hidden layers, the columns."""
        return self.n_embd * self.router_hidden + 2 * self.router_hidden ** 2 + self.router_hidden * self.router_width

    def router_params(self) -> int:
        """The router's matrices, its three biases, its gate on the state handed on, its norm's scale and the selection bias."""
        return self.router_matmul_params() + 5 * self.router_hidden + self.router_width

    def expert_params(self) -> int:
        return 3 * self.n_embd * self.moe_intermediate_size

    def layer_matmul_params_passed(self, pairs_held_per_token: float) -> float:
        """Parameters of one layer that ONE token multiplies: the latent's projections, the grouped convolution, the router's
        matrices, and as many held experts as the token's pairs that land on one (a mean, as the program counted it)."""
        return self.attention_params() + self.grouped_conv_params() + self.router_matmul_params() + pairs_held_per_token * self.expert_params()

    def layer_params(self) -> int:
        """Every parameter one layer holds here: attention, convolutions, key temperature, router, the held experts, two norms, two merges."""
        return (self.attention_params() + self.conv_params() + self.n_head_kv + self.router_params()
                + self.experts_held * self.expert_params() + 2 * self.n_embd + 8 * self.n_embd)

    def all_params(self) -> int:
        return self.n_layer * self.layer_params() + self.vocab_size * self.n_embd + self.n_embd


def _drawn_shapes(s: CcaMoEShape) -> dict[str, tuple]:
    """The leaves drawn from a normal (std 0.02, or scaled); `conv0_kernel` is uniform, the rest ones or zeros."""
    e, d, r, h = s.n_embd, s.head_dim, s.router_hidden, s.latent_heads
    return {"q_attn": (e, s.n_head_q, d), "k_attn": (e, s.n_head_kv, d), "v_attn": (e, s.n_head_kv - s.n_head_kv // 2, d),
            "v_attn_prev": (e, s.n_head_kv // 2, d), "c_proj": (s.n_head_q, d, e), "conv1_kernel": (s.time1, h, d, d),
            "router_down": (e, r), "router_fc1": (r, r), "router_fc2": (r, r), "router_out": (r, s.router_width)}


def _constant_shapes(s: CcaMoEShape) -> dict[str, tuple]:
    e, d, r, h = s.n_embd, s.head_dim, s.router_hidden, s.latent_heads
    out = {"attention_norm": (e,), "ffn_norm": (e,), "key_temperature": (s.n_head_kv,), "eda_gate": (r,), "router_norm": (r,),
           "conv0_bias": (h * d,), "conv1_bias": (h, d), "router_bias": (s.router_width,), **{name: (e,) for name in MERGES}}
    out.update({f"router_{name}_bias": (r,) for name in ROUTER_DENSE})
    return out


def layer_weights(shape: CcaMoEShape, key, layer, dtype=jnp.bfloat16) -> dict:
    """Every leaf of layer `layer` (a whole number or a traced index), under the reference's names; the large matrices in
    `dtype`, the rest float32. The three expert stacks hold the experts `expert_offset .. expert_offset + experts_held - 1`."""
    layer_key = jax.random.fold_in(key, layer)
    out = {}
    for i, (name, dims) in enumerate(_drawn_shapes(shape).items()):
        std = STD / np.sqrt(2 * shape.n_layer) if name in SCALED else STD
        value = jax.random.normal(jax.random.fold_in(layer_key, i), dims, jnp.float32) * std
        out[name] = value.astype(dtype if name in BFLOAT16_LEAVES else jnp.float32)
    bound = shape.time0 ** -0.5
    out["conv0_kernel"] = jax.random.uniform(jax.random.fold_in(layer_key, 101), (shape.time0, shape.latent_heads * shape.head_dim),
                                             jnp.float32, -bound, bound)
    for name, dims in _constant_shapes(shape).items():
        out[name] = (jnp.ones if name in ONES else jnp.zeros)(dims, jnp.float32)
    out.update(jax.lax.map(lambda e: expert_weights(shape, layer_key, e, dtype), shape.expert_offset + jnp.arange(shape.experts_held)))
    return out


def stack_weights(shape: CcaMoEShape, key, dtype=jnp.bfloat16) -> dict:
    """All layers stacked on a leading axis: the program's one scanned run."""
    return jax.lax.map(lambda l: layer_weights(shape, key, l, dtype), jnp.arange(shape.n_layer))  # one layer's program, compiled once


def _program_block(w: dict) -> dict:
    """The stacked leaves in the layout of the program's block."""
    router = {name: {"kernel": w[f"router_{name}"], "bias": w[f"router_{name}_bias"]} for name in ROUTER_DENSE}
    router.update(out={"kernel": w["router_out"]}, eda_gate=w["eda_gate"], norm_scale=w["router_norm"], e_score_correction_bias=w["router_bias"])
    return {"attention_norm": {"scale": w["attention_norm"]}, "ffn_norm": {"scale": w["ffn_norm"]},
            "cca": {**{name: {"kernel": w[name]} for name in ATTENTION}, **{name: w[name] for name in CONVOLUTIONS}, "key_temperature": w["key_temperature"]},
            **{merge: {leaf: w[f"{merge}_{leaf}"] for leaf in MERGE} for merge in ("attn_merge", "ffn_merge")},
            "moe": {"router": router, "experts": {name[len("experts_"):]: w[name] for name in EXPERTS}}}


def reference_layout(program_params) -> dict:
    """The program's parameter tree (or a tree shaped like it: gradients, moments), renamed to the reference's layout (no
    copy): `{"runs": [the stacked leaves of the one run], "wte", "final_norm"}`."""
    p = program_params["params"]
    block = p["run_0"]["blocks"]["block"]
    router = block["moe"]["router"]
    run = {"attention_norm": block["attention_norm"]["scale"], "ffn_norm": block["ffn_norm"]["scale"],
           **{name: block["cca"][name]["kernel"] for name in ATTENTION}, **{name: block["cca"][name] for name in CONVOLUTIONS},
           "key_temperature": block["cca"]["key_temperature"],
           **{f"{merge}_{leaf}": block[merge][leaf] for merge in ("attn_merge", "ffn_merge") for leaf in MERGE},
           **{f"router_{name}": router[name]["kernel"] for name in ROUTER_DENSE}, **{f"router_{name}_bias": router[name]["bias"] for name in ROUTER_DENSE},
           "router_out": router["out"]["kernel"], "eda_gate": router["eda_gate"], "router_norm": router["norm_scale"],
           "router_bias": router["e_score_correction_bias"],
           **{name: block["moe"]["experts"][name[len("experts_"):]] for name in EXPERTS}}
    return {"runs": [run], "wte": p["wte"], "final_norm": p["lm_head_norm"]["scale"]}


def program_tree(shape: CcaMoEShape, key, dtype=jnp.bfloat16) -> dict:
    """The whole parameter tree in the layout the program keeps for this stack: `{"params": {"run_0": {"blocks": {"block":
    ...stacked over the layers}}, "lm_head_norm", "wte"}}` (the head is the table's). Traceable, and `key` (from `seed_key`)
    is an argument, so that one compiled program serves every seed."""
    return {"params": {"run_0": {"blocks": {"block": _program_block(stack_weights(shape, key, dtype))}},
                       "lm_head_norm": {"scale": jnp.ones((shape.n_embd,), jnp.float32)}, "wte": embedding(shape, key, dtype)}}


def make_program_tree(shape: CcaMoEShape, seed: int, like, match_dtypes: bool = True):
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
