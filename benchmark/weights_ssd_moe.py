"""Weights of the Mamba-2 / NoPE-attention / expert-layer decoder (`model_type: granitemoehybrid`), made by the
benchmark from a seed: the twin of `benchmark/weights_gdn_moe.py` for a stack whose layers hold, by the published
`layer_types`, the Mamba-2 mixer or plain attention without positions, and in every layer a softmax-routed expert layer
beside an ungated shared expert, under a tied table. The program under test and the plain reference
(`benchmark/reference/ssd_moe_decoder_f32.py`) both get their weights from here.

One layer's tensors depend only on (seed, layer index). What a chip holds a SHARE of depends also on the part's index
among ALL the published parts, so that a share gets the tensors the uncut layer has there: a routed expert on its index
among the router's experts, a Mamba-2 head (its columns of `in_proj`, its taps, `A_log`, `D`, `dt_bias`, its rows of
`out_proj`) on its index among the published heads, an attention head on its index among the published query or
key/value heads, a slice of the shared expert on its index among `shared_expert_shards`. `share` says which share of
the mixers and of the shared expert this is (0: the first; the cell's); the experts have `expert_offset`.

Distribution (ISSUE 52, `assumed`): Mamba-2's own draws. Matmul kernels normal, std 0.02, and 0.02 / sqrt(2 L) for the
projections that write into the residual stream (`c_proj`, `out_proj`, every expert's and the shared expert's `W_2`).
`A_log = log(u)`, `u` uniform on [1, 16]; `dt_bias` the inverse softplus of a log-uniform draw in [1e-3, 1e-1]; `D` 1;
the gated norm's scale 1; the convolution's taps and bias uniform on (-1/2, 1/2). The large kernels are bfloat16 as
the program trains them; norm leaves, `A_log`, `D`, `dt_bias`, the taps, their bias and the router float32.
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
DT_MIN, DT_MAX = 1e-3, 1e-1
SSD_LEAVES = ("in_proj", "conv", "conv_bias", "A_log", "D", "dt_bias", "gate_norm", "out_proj")
ATTENTION = ("q_attn", "k_attn", "v_attn", "c_proj")
EXPERTS = ("experts_W", "experts_V", "experts_W_2")
SHARED = ("shared_W", "shared_V", "shared_W_2")
MIXER_OF = {"mamba": "ssd", "attention": "attn"}  # a published layer type as the program's block names its mixer seat
MULTIPLIERS = ("embedding_multiplier", "residual_multiplier", "attention_multiplier", "logits_scaling")


@dataclass(frozen=True)
class SsdMoEShape:
    """Sizes of the decoder, as the configuration's `model` block states them, with the published counts of what is shared."""

    vocab_size: int
    kinds: tuple  # the mixer of every layer: "ssd" (Mamba-2) or "attn" (attention without positions)
    n_embd: int
    heads: int  # the published Mamba-2 heads
    heads_held: int
    head_dim: int  # P, a Mamba-2 head's channels
    state: int  # N
    taps: int
    chunk: int
    n_head_q_all: int  # the published attention heads
    n_head_kv_all: int
    n_head_q: int  # and those held
    n_head_kv: int
    attn_head_dim: int
    embedding_multiplier: float
    residual_multiplier: float
    attention_multiplier: float
    logits_scaling: float
    n_routed_experts: int
    num_experts_per_tok: int
    moe_intermediate_size: int
    shared_width: int  # the shared expert's published width
    shared_shards: int
    experts_held: int
    expert_offset: int
    router_aux_loss_coef: float
    norm_eps: float
    share: int = 0  # which share of the mixers' heads and of the shared expert's width: its index

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
    def inner(self) -> int:
        return self.heads_held * self.head_dim

    @property
    def conv_width(self) -> int:
        return self.inner + 2 * self.state

    @property
    def in_width(self) -> int:
        return 2 * self.inner + 2 * self.state + self.heads_held

    @property
    def shared_hidden(self) -> int:
        return self.shared_width // self.shared_shards

    @property
    def norm_topk_prob(self) -> bool:  # what the shared pieces of the other references read: the chosen gates are renormalised
        return True

    @classmethod
    def from_yaml(cls, raw: dict) -> "SsdMoEShape":
        """`raw` is the cell's YAML as `yaml.safe_load` gives it. Only the tied, unbiased decoder with plain RMS norms,
        `layer_types` of `mamba` and `attention`, no positions, the four multipliers and a softmax-routed expert layer beside
        an ungated shared expert in every layer is understood; anything else is an error. The published counts of the
        Mamba-2 heads come from `ssd_config.mamba_n_heads`, of the attention's heads from the file's top level where it has
        them (`num_attention_heads`, `num_key_value_heads`; else those held)."""
        model = resolved(raw["model_raw"]["config"], raw)
        moe, types, ssd = model.get("moe_config"), model.get("layer_types"), model.get("ssd_config")
        transforms = model.get("attention_config", {}).get("qkv_transforms", [])
        problems = []
        if not moe or not types or not ssd:
            problems.append("moe_config, layer_types and ssd_config must be set")
        elif (moe.get("scoring_func") != "softmax" or moe.get("topk_method") != "greedy" or moe.get("n_shared_experts", 0)
              or moe.get("first_k_dense_replace", 0) or float(moe.get("routed_scaling_factor", 1.0)) != 1.0 or not moe.get("norm_topk_prob", True)
              or moe.get("shared_expert_gate") or not moe.get("shared_expert_intermediate_size")):
            problems.append("the router scores by softmax, chooses greedily and renormalises the chosen gates, every layer is an expert "
                            "layer with an ungated shared expert (shared_expert_intermediate_size), no scaling")
        if types and set(types) - set(MIXER_OF):
            problems.append("layer_types holds mamba and attention layers only")
        if ssd and (int(ssd.get("mamba_n_groups", 1)) != 1 or not ssd.get("mamba_conv_bias", True)):
            problems.append("one group of B and C, and the convolution has its bias")
        if model.get("poe_type") != "NOPE" or any(t.get("type_hint") != "IdentityTransform" for t in transforms):
            problems.append("no positions (poe_type NOPE, no RotaryTransform)")
        if not model.get("use_weight_tying") or model.get("bias"):
            problems.append("the head is the table and nothing has a bias")
        if any(model.get(name) is None for name in MULTIPLIERS):
            problems.append(f"the four multipliers must be set ({', '.join(MULTIPLIERS)})")
        norms = [model.get(k, {}) for k in ("attention_norm_config", "ffn_norm_config", "lm_head_norm_config")]
        if any(n.get("norm_type") != "rms_norm" or n.get("config", {}).get("zero_centered") for n in norms):
            problems.append("the block's norms and the final norm must be plain rms_norm")
        if problems:
            raise ValueError("benchmark weights: " + "; ".join(problems))
        routed, held = int(moe["n_routed_experts"]), moe.get("experts_held")
        heads, q_held, kv_held = int(ssd["mamba_n_heads"]), int(model["n_head_q"]), int(model["n_head_kv"])
        head_dim = int(model["head_dim"]) if model.get("head_dim") is not None else int(model["n_embd"]) // q_held
        return cls(
            vocab_size=int(model["vocab_size"]), kinds=tuple(MIXER_OF[t] for t in types), n_embd=int(model["n_embd"]),
            heads=heads, heads_held=int(ssd.get("heads_held") or heads), head_dim=int(ssd["mamba_d_head"]), state=int(ssd["mamba_d_state"]),
            taps=int(ssd.get("mamba_d_conv", 4)), chunk=int(ssd.get("mamba_chunk_size", 256)),
            n_head_q_all=int(raw.get("num_attention_heads", q_held)), n_head_kv_all=int(raw.get("num_key_value_heads", kv_held)),
            n_head_q=q_held, n_head_kv=kv_held, attn_head_dim=head_dim,
            **{name: float(model[name]) for name in MULTIPLIERS},
            n_routed_experts=routed, num_experts_per_tok=int(moe["num_experts_per_tok"]), moe_intermediate_size=int(moe["moe_intermediate_size"]),
            shared_width=int(moe["shared_expert_intermediate_size"]), shared_shards=int(moe.get("shared_expert_shards", 1)),
            experts_held=routed if held is None else int(held), expert_offset=int(moe.get("expert_offset", 0)),
            router_aux_loss_coef=float(moe.get("router_aux_loss_coef", 0.0)),
            norm_eps=float(norms[0].get("config", {}).get("epsilon", 1e-5)),
        )

    # ---- counts, for the shape functions and the configuration's arithmetic

    def ssd_matmul_params(self) -> int:
        """The Mamba-2 mixer's two projections as held."""
        return self.n_embd * self.in_width + self.inner * self.n_embd

    def ssd_params(self) -> int:
        """Every parameter of the mixer as held: the projections, the taps and their bias, `A_log`, `D`, `dt_bias`, the gated norm's scale."""
        return self.ssd_matmul_params() + (self.taps + 1) * self.conv_width + 3 * self.heads_held + self.inner

    def attention_params(self) -> int:
        return self.n_embd * self.attn_head_dim * (2 * self.n_head_q + 2 * self.n_head_kv)

    def expert_params(self) -> int:
        return 3 * self.n_embd * self.moe_intermediate_size

    def outside_experts_params(self) -> int:
        """What an expert layer holds outside the routed experts: the router and the shared expert's slice."""
        return self.n_embd * self.n_routed_experts + 3 * self.n_embd * self.shared_hidden

    def layer_params(self, kind: str) -> int:
        """Every parameter one layer of kind `kind` holds here (the two norms' leaves with it)."""
        mixer = self.ssd_params() if kind == "ssd" else self.attention_params()
        return mixer + self.outside_experts_params() + self.experts_held * self.expert_params() + 2 * self.n_embd

    def all_params(self) -> int:
        return sum(self.layer_params(kind) for kind in self.kinds) + self.vocab_size * self.n_embd + self.n_embd

    def scan_forward_ops_per_token(self) -> float:
        """Forward operations a token of ONE layer's chunked recurrence, beside its projections and taps: a chunk of Q positions
        takes `C B^T` once (2 Q^2 N) and a held head `(L o C B^T) X` (2 Q^2 P), its own state and `C H` (2 Q P N each)."""
        q, p, n = self.chunk, self.head_dim, self.state
        return (2 * q * q * n + self.heads_held * (2 * q * q * p + 2 * 2 * q * p * n)) / q


def _normal(key, dims, std):
    return jax.random.normal(key, dims, jnp.float32) * std


def _by_part(draw, key, first: int, held: int):
    """`draw(key of a part)` for the parts `first .. first + held - 1` (indices among ALL the published parts), stacked in front."""
    return jax.vmap(lambda part: draw(jax.random.fold_in(key, part)))(first + jnp.arange(held))


def ssd_weights(s: SsdMoEShape, layer_key, dtype=jnp.bfloat16) -> dict:
    """The Mamba-2 mixer's leaves as held: a head's part of each drawn by the head's index among all `s.heads`, B's and C's once."""
    key = jax.random.fold_in(layer_key, 3_000_017)
    e, p, n, k = s.n_embd, s.head_dim, s.state, s.taps
    scaled = STD / np.sqrt(2 * s.n_layer)

    def head(head_key):
        at = lambda i: jax.random.fold_in(head_key, i)  # noqa: E731
        dt = jnp.exp(jax.random.uniform(at(6), (), jnp.float32) * (np.log(DT_MAX) - np.log(DT_MIN)) + np.log(DT_MIN))
        return {"z": _normal(at(0), (e, p), STD), "x": _normal(at(1), (e, p), STD), "dt": _normal(at(2), (e,), STD),
                "taps": jax.random.uniform(at(3), (k, p), jnp.float32, -0.5, 0.5), "bias": jax.random.uniform(at(4), (p,), jnp.float32, -0.5, 0.5),
                "A_log": jnp.log(jax.random.uniform(at(5), (), jnp.float32, 1.0, 16.0)), "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                "out": _normal(at(7), (p, e), scaled)}

    heads = _by_part(head, jax.random.fold_in(key, 0), s.share * s.heads_held, s.heads_held)  # every leaf [held, ...]
    both = jax.random.fold_in(key, 1)  # B and C: the same on every share
    b_c = {"in": _normal(jax.random.fold_in(both, 0), (e, 2 * n), STD), "taps": jax.random.uniform(jax.random.fold_in(both, 1), (k, 2 * n), jnp.float32, -0.5, 0.5),
           "bias": jax.random.uniform(jax.random.fold_in(both, 2), (2 * n,), jnp.float32, -0.5, 0.5)}
    columns = lambda a: jnp.moveaxis(a, 0, 1).reshape(e, -1)  # noqa: E731  [held, e, p] -> [e, held p]
    return {
        "in_proj": jnp.concatenate([columns(heads["z"]), columns(heads["x"]), b_c["in"], heads["dt"].T], axis=1).astype(dtype),
        "conv": jnp.concatenate([jnp.moveaxis(heads["taps"], 0, 1).reshape(k, -1), b_c["taps"]], axis=1),
        "conv_bias": jnp.concatenate([heads["bias"].reshape(-1), b_c["bias"]]),
        "A_log": heads["A_log"], "D": jnp.ones((s.heads_held,), jnp.float32), "dt_bias": heads["dt_bias"],
        "gate_norm": jnp.ones((s.inner,), jnp.float32), "out_proj": heads["out"].reshape(s.inner, e).astype(dtype),
    }


def attention_weights(s: SsdMoEShape, layer_key, dtype=jnp.bfloat16) -> dict:
    """The attention's four kernels as held: a head's part drawn by its index among all the published query or key/value heads."""
    key = jax.random.fold_in(layer_key, 3_000_029)
    e, d = s.n_embd, s.attn_head_dim
    part = lambda i, first, held, dims, std: _by_part(lambda k: _normal(k, dims, std), jax.random.fold_in(key, i), first, held)  # noqa: E731
    q0, kv0 = s.share * s.n_head_q, s.share * s.n_head_kv
    return {"q_attn": jnp.moveaxis(part(0, q0, s.n_head_q, (e, d), STD), 0, 1).astype(dtype),
            "k_attn": jnp.moveaxis(part(1, kv0, s.n_head_kv, (e, d), STD), 0, 1).astype(dtype),
            "v_attn": jnp.moveaxis(part(2, kv0, s.n_head_kv, (e, d), STD), 0, 1).astype(dtype),
            "c_proj": part(3, q0, s.n_head_q, (d, e), STD / np.sqrt(2 * s.n_layer)).astype(dtype)}


def shared_weights(s: SsdMoEShape, layer_key, dtype=jnp.bfloat16) -> dict:
    """This share's slice of the shared expert: drawn by the slice's index among `shared_shards`."""
    key = jax.random.fold_in(jax.random.fold_in(layer_key, 3_000_037), s.share)
    e, f = s.n_embd, s.shared_hidden
    return {"shared_W": _normal(jax.random.fold_in(key, 0), (e, f), STD).astype(dtype), "shared_V": _normal(jax.random.fold_in(key, 1), (e, f), STD).astype(dtype),
            "shared_W_2": _normal(jax.random.fold_in(key, 2), (f, e), STD / np.sqrt(2 * s.n_layer)).astype(dtype)}


def layer_weights(shape: SsdMoEShape, key, layer, kind: str, dtype=jnp.bfloat16) -> dict:
    """Every leaf of layer `layer` (a whole number or a traced index) of kind `kind`, under the reference's names; kernels
    in `dtype`, the rest float32. The three expert stacks hold the experts `expert_offset .. expert_offset + experts_held - 1`."""
    layer_key = jax.random.fold_in(key, layer)
    out = {"router": _normal(jax.random.fold_in(layer_key, 0), (shape.n_embd, shape.n_routed_experts), STD),
           "attention_norm": jnp.ones((shape.n_embd,), jnp.float32), "ffn_norm": jnp.ones((shape.n_embd,), jnp.float32)}
    out.update(ssd_weights(shape, layer_key, dtype) if kind == "ssd" else attention_weights(shape, layer_key, dtype))
    out.update(shared_weights(shape, layer_key, dtype))
    out.update(jax.lax.map(lambda e: expert_weights(shape, layer_key, e, dtype), shape.expert_offset + jnp.arange(shape.experts_held)))
    return out


def run_weights(shape: SsdMoEShape, key, first: int, length: int, kind: str, dtype=jnp.bfloat16) -> dict:
    """The layers `first .. first + length - 1`, all of kind `kind`, stacked on a leading axis."""
    return jax.lax.map(lambda l: layer_weights(shape, key, l, kind, dtype), first + jnp.arange(length))  # one layer's program, compiled once


def _program_mixer(w: dict, kind: str) -> dict:
    if kind == "ssd":
        return {"in_proj": {"kernel": w["in_proj"]}, "conv_kernel": w["conv"], "conv_bias": w["conv_bias"], "A_log": w["A_log"], "D": w["D"],
                "dt_bias": w["dt_bias"], "norm_scale": w["gate_norm"], "out_proj": {"kernel": w["out_proj"]}}
    return {name: {"kernel": w[name]} for name in ATTENTION}


def _program_block(w: dict, kind: str) -> dict:
    """One run's stacked leaves (or one layer's) in the layout of the program's block."""
    return {"attention_norm": {"scale": w["attention_norm"]}, "ffn_norm": {"scale": w["ffn_norm"]}, kind: _program_mixer(w, kind),
            "moe": {"router": {"kernel": w["router"]}, "experts": {name[len("experts_"):]: w[name] for name in EXPERTS},
                    "shared": {name[len("shared_"):]: {"kernel": w[name]} for name in SHARED}}}


def reference_layout(program_params) -> dict:
    """The program's parameter tree (or a tree shaped like it: gradients, moments), renamed to the reference's
    layout (no copy): `{"runs": [stacked leaves of a run, ...], "wte", "final_norm"}`."""
    p = program_params["params"]
    runs = []
    for i in range(sum(name.startswith("run_") for name in p)):
        block = p[f"run_{i}"]["blocks"]["block"]
        moe = block["moe"]
        w = {"attention_norm": block["attention_norm"]["scale"], "ffn_norm": block["ffn_norm"]["scale"], "router": moe["router"]["kernel"],
             **{name: moe["experts"][name[len("experts_"):]] for name in EXPERTS},
             **{name: moe["shared"][name[len("shared_"):]]["kernel"] for name in SHARED}}
        if "ssd" in block:
            ssd = block["ssd"]
            w.update(in_proj=ssd["in_proj"]["kernel"], conv=ssd["conv_kernel"], conv_bias=ssd["conv_bias"], A_log=ssd["A_log"], D=ssd["D"],
                     dt_bias=ssd["dt_bias"], gate_norm=ssd["norm_scale"], out_proj=ssd["out_proj"]["kernel"])
        else:
            w.update({name: block["attn"][name]["kernel"] for name in ATTENTION})
        runs.append(w)
    return {"runs": runs, "wte": p["wte"], "final_norm": p["lm_head_norm"]["scale"]}


def program_tree(shape: SsdMoEShape, key, dtype=jnp.bfloat16) -> dict:
    """The whole parameter tree in the layout the program keeps for this stack: `{"params": {"run_<i>": {"blocks":
    {"block": ...stacked over the run's layers}}, "lm_head_norm", "wte"}}`, a run for every stretch of layers of one
    kind. Traceable, and `key` (from `seed_key`) is an argument, so that one compiled program serves every seed."""
    params = {f"run_{i}": {"blocks": {"block": _program_block(run_weights(shape, key, first, length, kind, dtype), kind)}}
              for i, (kind, first, length) in enumerate(shape.runs)}
    params["lm_head_norm"] = {"scale": jnp.ones((shape.n_embd,), jnp.float32)}
    params["wte"] = embedding(shape, key, dtype)
    return {"params": params}


def make_program_tree(shape: SsdMoEShape, seed: int, like, match_dtypes: bool = True):
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
