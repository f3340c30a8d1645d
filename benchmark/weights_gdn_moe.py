"""Weights of the gated-delta-rule / gated-attention / expert-layer decoder (`model_type:
qwen3_next`), made by the benchmark from `--seed`: the twin of `benchmark/weights_swa_moe.py` for a
stack whose layers hold, by the published `full_attention_interval`, the gated delta rule's mixer
or softmax attention with an output gate, and in every layer a softmax-routed expert layer beside
a gated shared expert. The program under test and the plain reference
(`benchmark/reference/gdn_moe_decoder_f32.py`) both get their weights from here. One layer's
tensors depend only on (seed, layer index), and one routed expert's on (seed, layer index, the
expert's index among ALL the router's experts): a layer told to hold experts 64..127 gets the
tensors the uncut layer has there.

Distribution (ISSUE 44, `assumed`). Matmul kernels: normal, std 0.02, and 0.02 / sqrt(2 L) for the
projections that write into the residual stream (`c_proj`, `out_proj`, every expert's and the shared
expert's `W_2`): the recipe's "scaled" init. The router's matrix and the shared expert's gate `w_g`
normal std 0.02. `A_log = log(a)`, `a` uniform on [1, 16]; `dt_bias` 1; the gated norm's `w_n` 1; every
zero-centred norm leaf 0. The convolution's taps uniform on (-1/2, 1/2): torch's default for a depthwise
`Conv1d` of 4 taps, which the source's module leaves as it is; taps of std 0.02 would put the SiLU after
them on its straight part, and a program without it could not be told apart. The large kernels are
bfloat16 as the program trains them; norm leaves, `A_log`, `dt_bias`, the taps and the router float32.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.weights import _described, seed_key  # noqa: F401  (the same key for the same seed as the dense decoder's)
from benchmark.weights_hybrid import resolved
from benchmark.weights_moe import embedding, expert_weights, head  # noqa: F401  (they read vocab_size, n_embd, moe_intermediate_size, n_layer off any shape)

STD = 0.02
GDN_MATRICES = ("qkvz", "ba", "out_proj")
ATTENTION = ("q_attn", "k_attn", "v_attn", "c_proj")
EXPERTS = ("experts_W", "experts_V", "experts_W_2")
SHARED = ("shared_W", "shared_V", "shared_W_2")
SCALED = ("c_proj", "out_proj", "experts_W_2", "shared_W_2")  # what writes into the residual stream
FLOAT32_LEAVES = ("router",)
MIXER_OF = {"linear_attention": "gdn", "full_attention": "attn"}  # a published layer type as the program's block names its mixer seat


@dataclass(frozen=True)
class GdnMoEShape:
    """Sizes of the decoder, as the configuration's `model` block states them."""

    vocab_size: int
    kinds: tuple  # the mixer of every layer: "gdn" (the gated delta rule) or "attn" (gated softmax attention)
    n_head_q: int
    n_head_kv: int
    head_dim: int
    n_embd: int
    rotary_dim: int  # the channels of a head the rotary turns, from the first
    rope_theta: float
    key_heads: int
    value_heads: int
    key_dim: int
    value_dim: int
    taps: int
    n_routed_experts: int
    num_experts_per_tok: int
    moe_intermediate_size: int
    shared_hidden: int
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

    @property
    def conv_width(self) -> int:
        return 2 * self.key_heads * self.key_dim + self.value_heads * self.value_dim

    @classmethod
    def from_yaml(cls, raw: dict) -> "GdnMoEShape":
        """`raw` is the cell's YAML as `yaml.safe_load` gives it. Only the untied, unbiased decoder with zero-centred RMS
        norms, `layer_types` of `linear_attention` and `full_attention`, a gated attention with per-head norms on q and k
        and a softmax-routed expert layer beside a gated shared expert in every layer is understood; anything else is an error."""
        model = resolved(raw["model_raw"]["config"], raw)
        moe, types, gdn = model.get("moe_config"), model.get("layer_types"), model.get("gdn_config")
        attention = model.get("attention_config", {})
        transforms = attention.get("qkv_transforms", [])
        problems = []
        if not moe or not types or not gdn:
            problems.append("moe_config, layer_types and gdn_config must be set")
        elif (moe.get("scoring_func") != "softmax" or moe.get("topk_method") != "greedy" or moe.get("n_shared_experts", 0)
              or moe.get("first_k_dense_replace", 0) or float(moe.get("routed_scaling_factor", 1.0)) != 1.0
              or not moe.get("shared_expert_gate") or not moe.get("shared_expert_intermediate_size")):
            problems.append("the router scores by softmax and chooses greedily, every layer is an expert layer with a gated shared expert "
                            "(shared_expert_intermediate_size, shared_expert_gate), no scaling")
        if types and set(types) - set(MIXER_OF):
            problems.append("layer_types holds linear_attention and full_attention layers only")
        if model.get("mla_config") or model.get("attn_layer_period") or model.get("loop_config") or model.get("cca_config") or model.get("sliding_window"):
            problems.append("no latent or compressed attention, no state-space layers, no loop, no window")
        if model.get("poe_type") != "NOPE" or [t.get("type_hint") for t in transforms] != ["RotaryTransform"]:
            problems.append("positions are the rotary's (poe_type NOPE, one RotaryTransform)")
        if not model.get("attn_output_gate"):
            problems.append("the attention's output is gated (attn_output_gate)")
        if model.get("use_weight_tying") or model.get("bias"):
            problems.append("the head is not tied and nothing has a bias")
        norms = [model.get(k, {}) for k in ("attention_norm_config", "ffn_norm_config", "lm_head_norm_config")] + [attention.get("qk_norm_config") or {}]
        if any(n.get("norm_type") != "rms_norm" or not n.get("config", {}).get("zero_centered") for n in norms):
            problems.append("the block's norms, the final norm and the norms on q and k must be rms_norm with zero_centered")
        if problems:
            raise ValueError("benchmark weights: " + "; ".join(problems))
        rope = (model.get("rope_parameters") or {}).get("full_attention") or {}
        if rope.get("rope_type", "default") != "default":
            raise ValueError("benchmark weights: the full_attention layers' rotary is the default one (no scaling)")
        routed, held = int(moe["n_routed_experts"]), moe.get("experts_held")
        head_dim = int(model["head_dim"]) if model.get("head_dim") is not None else int(model["n_embd"]) // int(model["n_head_q"])
        return cls(
            vocab_size=int(model["vocab_size"]), kinds=tuple(MIXER_OF[t] for t in types), n_head_q=int(model["n_head_q"]),
            n_head_kv=int(model["n_head_kv"]), head_dim=head_dim, n_embd=int(model["n_embd"]),
            rotary_dim=int(head_dim * float(rope.get("partial_rotary_factor", 1.0))),
            rope_theta=float(rope.get("rope_theta", transforms[0]["config"].get("base_freq", 10000))),
            key_heads=int(gdn["linear_num_key_heads"]), value_heads=int(gdn["linear_num_value_heads"]),
            key_dim=int(gdn["linear_key_head_dim"]), value_dim=int(gdn["linear_value_head_dim"]), taps=int(gdn.get("linear_conv_kernel_dim", 4)),
            n_routed_experts=routed, num_experts_per_tok=int(moe["num_experts_per_tok"]), moe_intermediate_size=int(moe["moe_intermediate_size"]),
            shared_hidden=int(moe["shared_expert_intermediate_size"]), experts_held=routed if held is None else int(held),
            expert_offset=int(moe.get("expert_offset", 0)), norm_topk_prob=bool(moe.get("norm_topk_prob", True)),
            router_aux_loss_coef=float(moe.get("router_aux_loss_coef", 0.0)),
            norm_eps=float(norms[0].get("config", {}).get("epsilon", 1e-6)),
        )

    # ---- counts, for the shape functions and the configuration's arithmetic

    @property
    def qkvz_width(self) -> int:
        """What a key head's group of `qkvz` holds: its q, its k, and v and z of the value heads that read it."""
        return 2 * self.key_dim + 2 * (self.value_heads // self.key_heads) * self.value_dim

    def gdn_matmul_params(self) -> int:
        """The rule's mixer's three projections: `qkvz`, `ba` and the output projection."""
        share = self.value_heads // self.key_heads
        return self.n_embd * self.key_heads * (self.qkvz_width + 2 * share) + self.value_heads * self.value_dim * self.n_embd

    def gdn_params(self) -> int:
        """Every parameter of the rule's mixer: the projections, the taps, `A_log`, `dt_bias` and the gated norm's `w_n`."""
        return self.gdn_matmul_params() + self.taps * self.conv_width + 2 * self.value_heads + self.value_dim

    def attention_matmul_params(self) -> int:
        """q (twice as wide: a head's query and its gate), k, v and the output projection."""
        return self.n_embd * self.head_dim * (3 * self.n_head_q + 2 * self.n_head_kv)

    def attention_params(self) -> int:
        return self.attention_matmul_params() + 2 * self.head_dim

    def expert_params(self) -> int:
        return 3 * self.n_embd * self.moe_intermediate_size

    def outside_experts_params(self) -> int:
        """What an expert layer holds outside the routed experts: the router, the shared expert and its gate."""
        return self.n_embd * self.n_routed_experts + 3 * self.n_embd * self.shared_hidden + self.n_embd

    def layer_params(self, kind: str) -> int:
        """Every parameter one layer of kind `kind` holds here (the two norms' leaves with it)."""
        mixer = self.gdn_params() if kind == "gdn" else self.attention_params()
        return mixer + self.outside_experts_params() + self.experts_held * self.expert_params() + 2 * self.n_embd

    def all_params(self) -> int:
        return sum(self.layer_params(kind) for kind in self.kinds) + 2 * self.vocab_size * self.n_embd + self.n_embd

    def rule_forward_ops_per_token(self, chunk: int = 64) -> float:
        """Forward operations a token of ONE layer's chunked rule, beside its projections and taps: a chunk of C positions
        and a value head take `k k^T` and `q k^T` (2 C^2 d_k each, once a key head), `T` against its two right sides
        (2 C^2 (d_k + d_v)), `W S`, `q S` and `k^T V'` (2 C d_k d_v each) and the lower product (2 C^2 d_v). The series that
        builds `T` is how this program inverts a `[C, C]` system, not what the rule requires (forward substitution is C^3 / 3
        and is left out with it: under a tenth of the rest)."""
        c, dk, dv, share = chunk, self.key_dim, self.value_dim, self.value_heads // self.key_heads
        a_head = 2 * 2 * c * c * dk / share + 2 * c * c * (dk + dv) + 3 * 2 * c * dk * dv + 2 * c * c * dv
        return self.value_heads * a_head / c


def _layer_shapes(s: GdnMoEShape, kind: str) -> dict[str, tuple]:
    e = s.n_embd
    common = {"router": (e, s.n_routed_experts), "shared_W": (e, s.shared_hidden), "shared_V": (e, s.shared_hidden),
              "shared_W_2": (s.shared_hidden, e), "shared_gate": (e, 1)}
    if kind == "gdn":
        share = s.value_heads // s.key_heads
        return {"qkvz": (e, s.key_heads, s.qkvz_width), "ba": (e, s.key_heads, 2 * share),
                "out_proj": (s.value_heads, s.value_dim, e), **common}
    return {"q_attn": (e, s.n_head_q, 2 * s.head_dim), "k_attn": (e, s.n_head_kv, s.head_dim), "v_attn": (e, s.n_head_kv, s.head_dim),
            "c_proj": (s.n_head_q, s.head_dim, e), **common}


def layer_weights(shape: GdnMoEShape, key, layer, kind: str, dtype=jnp.bfloat16) -> dict:
    """Every leaf of layer `layer` (a whole number or a traced index) of kind `kind`, under the reference's names; kernels
    in `dtype`, the rest float32. The three expert stacks hold the experts `expert_offset .. expert_offset + experts_held - 1`."""
    layer_key = jax.random.fold_in(key, layer)
    out = {}
    for i, (name, dims) in enumerate(_layer_shapes(shape, kind).items()):
        std = STD / np.sqrt(2 * shape.n_layer) if name in SCALED else STD
        value = jax.random.normal(jax.random.fold_in(layer_key, i), dims, jnp.float32) * std
        out[name] = value.astype(jnp.float32 if name in FLOAT32_LEAVES else dtype)
    zeros = lambda *dims: jnp.zeros(dims, jnp.float32)  # noqa: E731  a zero-centred norm's leaf
    out.update(attention_norm=zeros(shape.n_embd), ffn_norm=zeros(shape.n_embd))
    if kind == "gdn":
        draw = jax.random.fold_in(layer_key, 5_000_011)
        out.update(
            conv=jax.random.uniform(jax.random.fold_in(draw, 0), (shape.taps, shape.conv_width), jnp.float32, -shape.taps ** -0.5, shape.taps ** -0.5),
            A_log=jnp.log(jax.random.uniform(jax.random.fold_in(draw, 1), (shape.value_heads,), jnp.float32, 1.0, 16.0)),
            dt_bias=jnp.ones((shape.value_heads,), jnp.float32), out_norm=jnp.ones((shape.value_dim,), jnp.float32))
    else:
        out.update(q_norm=zeros(shape.head_dim), k_norm=zeros(shape.head_dim))
    out.update(jax.lax.map(lambda e: expert_weights(shape, layer_key, e, dtype), shape.expert_offset + jnp.arange(shape.experts_held)))
    return out


def run_weights(shape: GdnMoEShape, key, first: int, length: int, kind: str, dtype=jnp.bfloat16) -> dict:
    """The layers `first .. first + length - 1`, all of kind `kind`, stacked on a leading axis."""
    return jax.lax.map(lambda l: layer_weights(shape, key, l, kind, dtype), first + jnp.arange(length))  # one layer's program, compiled once


def _program_block(w: dict, kind: str) -> dict:
    """One run's stacked leaves in the layout of the program's block."""
    block = {"attention_norm": {"scale": w["attention_norm"]}, "ffn_norm": {"scale": w["ffn_norm"]},
             "moe": {"router": {"kernel": w["router"]}, "experts": {name[len("experts_"):]: w[name] for name in EXPERTS},
                     "shared": {name[len("shared_"):]: {"kernel": w[name]} for name in SHARED}, "shared_gate": w["shared_gate"]}}
    if kind == "gdn":
        block["gdn"] = {**{name: {"kernel": w[name]} for name in GDN_MATRICES}, "conv_kernel": w["conv"], "A_log": w["A_log"],
                        "dt_bias": w["dt_bias"], "out_norm_scale": w["out_norm"]}
    else:
        block["attn"] = {**{name: {"kernel": w[name]} for name in ATTENTION}, "q_norm": {"scale": w["q_norm"]}, "k_norm": {"scale": w["k_norm"]}}
    return block


def reference_layout(program_params) -> dict:
    """The program's parameter tree (or a tree shaped like it: gradients, moments), renamed to the reference's
    layout (no copy): `{"runs": [stacked leaves of a run, ...], "wte", "lm_head", "final_norm"}`."""
    p = program_params["params"]
    runs = []
    for i in range(sum(name.startswith("run_") for name in p)):
        block = p[f"run_{i}"]["blocks"]["block"]
        moe = block["moe"]
        w = {"attention_norm": block["attention_norm"]["scale"], "ffn_norm": block["ffn_norm"]["scale"], "router": moe["router"]["kernel"],
             **{name: moe["experts"][name[len("experts_"):]] for name in EXPERTS},
             **{name: moe["shared"][name[len("shared_"):]]["kernel"] for name in SHARED}, "shared_gate": moe["shared_gate"]}
        if "gdn" in block:
            gdn = block["gdn"]
            w.update({name: gdn[name]["kernel"] for name in GDN_MATRICES}, conv=gdn["conv_kernel"], A_log=gdn["A_log"],
                     dt_bias=gdn["dt_bias"], out_norm=gdn["out_norm_scale"])
        else:
            attn = block["attn"]
            w.update({name: attn[name]["kernel"] for name in ATTENTION}, q_norm=attn["q_norm"]["scale"], k_norm=attn["k_norm"]["scale"])
        runs.append(w)
    return {"runs": runs, "wte": p["wte"], "lm_head": p["lm_head"]["kernel"], "final_norm": p["lm_head_norm"]["scale"]}


def program_tree(shape: GdnMoEShape, key, dtype=jnp.bfloat16) -> dict:
    """The whole parameter tree in the layout the program keeps for this stack: `{"params": {"run_<i>": {"blocks":
    {"block": ...stacked over the run's layers}}, "lm_head", "lm_head_norm", "wte"}}`, a run for every stretch of
    layers of one kind. Traceable, and `key` (from `seed_key`) is an argument, so that one compiled program serves every seed."""
    params = {f"run_{i}": {"blocks": {"block": _program_block(run_weights(shape, key, first, length, kind, dtype), kind)}}
              for i, (kind, first, length) in enumerate(shape.runs)}
    params["lm_head_norm"] = {"scale": jnp.zeros((shape.n_embd,), jnp.float32)}
    params["wte"] = embedding(shape, key, dtype)
    params["lm_head"] = {"kernel": head(shape, key, dtype)}
    return {"params": params}


def make_program_tree(shape: GdnMoEShape, seed: int, like, match_dtypes: bool = True):
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
