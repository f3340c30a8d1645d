"""Weights of the attention / state-space hybrid decoder (`model_type: jamba`), made by
the benchmark from `--seed`: the twin of `benchmark/weights.py` for a stack of two kinds
of layer. The program under test and the plain reference
(`benchmark/reference/hybrid_ssm_decoder_f32.py`) both get their weights from here, and
one layer's tensors depend only on (seed, layer index).

Distribution. Matmul kernels: normal, std 0.02, and 0.02 / sqrt(2 L) for the three
projections that write into the residual stream (`c_proj`, `out_proj`, `W_2`) — the
recipe's "scaled" init. The state-space mixer's own leaves as Mamba publishes them,
because a scan whose decay is all 0 or all 1 tests nothing: `A_log = log(1..N)` in every
channel, `D = 1`, the convolution uniform in +-K**-0.5 (kernel and bias), `dt_proj`
uniform in +-R**-0.5, the `dt` bias the inverse softplus of a log-uniform draw in
[1e-3, 1e-1]. Norm scales are 1. The large kernels are bfloat16 as the program trains them;
`A_log`, `D`, biases, norm scales and the two small kernels with large initial values
(the convolution's and `dt_proj`'s) float32, as the program keeps them.
"""

from __future__ import annotations

import math
import re
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.weights import _described, seed_key  # noqa: F401  (the same key for the same seed as the dense decoder's)

STD = 0.02
DT_MIN, DT_MAX, DT_FLOOR = 1e-3, 1e-1, 1e-4
FLOAT32_LEAVES = ("conv_kernel", "conv_bias", "dt_proj", "dt_bias", "A_log", "D", "attention_norm", "ffn_norm", "dt_norm", "b_norm", "c_norm")
_REFERENCE = re.compile(r"^\$\{([A-Za-z0-9_.]+)\}$")


def resolved(node, document: dict):
    """`node` with every value that is one `${dotted.path}` into `document` replaced by
    what stands there (the cell's YAML keeps the source's numbers at its top level and
    its model block reads them so). Resolver calls (`${name:arg}`) are left as they are."""
    if isinstance(node, dict):
        return {k: resolved(v, document) for k, v in node.items()}
    if isinstance(node, list):
        return [resolved(v, document) for v in node]
    match = _REFERENCE.match(node) if isinstance(node, str) else None
    if not match:
        return node
    value = document
    for part in match.group(1).split("."):
        value = value[part]
    return resolved(value, document)


@dataclass(frozen=True)
class HybridShape:
    """Sizes of the hybrid decoder, as the configuration's `model` block states them."""

    vocab_size: int
    kinds: tuple  # the mixer of every layer: "attn" or "ssm"
    n_head_q: int
    n_head_kv: int
    n_embd: int
    ffn_hidden: int  # the SwiGLU hidden size actually used (8192)
    d_inner: int
    d_state: int
    dt_rank: int
    d_conv: int
    norm_eps: float

    @property
    def n_layer(self) -> int:
        return len(self.kinds)

    @property
    def head_dim(self) -> int:
        return self.n_embd // self.n_head_q

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
    def from_yaml(cls, raw: dict) -> "HybridShape":
        """`raw` is the cell's YAML as `yaml.safe_load` gives it. Only the tied, unbiased
        SwiGLU / RMSNorm decoder without positions is understood; anything else is an error."""
        model = resolved(raw["model_raw"]["config"], raw)
        problems = []
        if model.get("activation_type") != "swiglu":
            problems.append("activation_type must be swiglu")
        transforms = model.get("attention_config", {}).get("qkv_transforms", [])
        if model.get("poe_type") != "NOPE" or any(t.get("type_hint") != "IdentityTransform" for t in transforms):
            problems.append("the model has no positional encoding (poe_type NOPE, no rotary transform)")
        if not model.get("use_weight_tying") or model.get("bias"):
            problems.append("the head is tied to the embedding and nothing outside the mixer has a bias")
        norms = [model.get(k, {}) for k in ("attention_norm_config", "ffn_norm_config", "lm_head_norm_config")]
        if any(n.get("norm_type") != "rms_norm" for n in norms):
            problems.append("norms must be rms_norm")
        ssm = model.get("ssm_config")
        if not ssm or not model.get("attn_layer_period") or not ssm.get("conv_bias", True):
            problems.append("attn_layer_period and ssm_config (with conv_bias) must be set")
        if problems:
            raise ValueError("benchmark weights: " + "; ".join(problems))
        multiple = int(model.get("enforce_swiglu_hidden_dim_multiple_of", 256))
        hidden = ((int(2 * int(model["ffn_hidden"]) / 3) + multiple - 1) // multiple) * multiple
        period, offset = int(model["attn_layer_period"]), int(model.get("attn_layer_offset", 0))
        n_embd = int(model["n_embd"])
        rank = ssm.get("dt_rank", "auto")
        return cls(
            vocab_size=int(model["vocab_size"]),
            kinds=tuple("attn" if i % period == offset else "ssm" for i in range(int(model["n_layer"]))),
            n_head_q=int(model["n_head_q"]), n_head_kv=int(model["n_head_kv"]), n_embd=n_embd, ffn_hidden=hidden,
            d_inner=int(ssm.get("expand", 2)) * n_embd, d_state=int(ssm.get("d_state", 16)),
            dt_rank=math.ceil(n_embd / 16) if rank == "auto" else int(rank), d_conv=int(ssm.get("d_conv", 4)),
            norm_eps=float(norms[0].get("config", {}).get("epsilon", 1e-6)),
        )

    # ---- counts, for the shape functions and the configuration's arithmetic

    def layer_matmul_params(self, kind: str) -> int:
        """Parameters of one layer that take part in matrix multiplications."""
        e, d, mlp = self.n_embd, self.head_dim, 3 * self.n_embd * self.ffn_hidden
        if kind == "attn":
            return 2 * e * self.n_head_q * d + 2 * e * self.n_head_kv * d + mlp
        return (e * 2 * self.d_inner + self.d_inner * (self.dt_rank + 2 * self.d_state)
                + self.dt_rank * self.d_inner + self.d_inner * e + mlp)

    def layer_params(self, kind: str) -> int:
        other = 2 * self.n_embd  # the block's two norms
        if kind == "ssm":  # convolution with bias, dt bias, A_log, D, three small norms
            other += (self.d_conv + 1) * self.d_inner + self.d_inner + self.d_inner * self.d_state + self.d_inner
            other += self.dt_rank + 2 * self.d_state
        return self.layer_matmul_params(kind) + other

    def matmul_params(self) -> int:
        """Every kernel of every block, and the tied table once, as the head (the
        embedding's use of it is a gather)."""
        return sum(self.layer_matmul_params(k) for k in self.kinds) + self.vocab_size * self.n_embd

    def all_params(self) -> int:
        return sum(self.layer_params(k) for k in self.kinds) + self.vocab_size * self.n_embd + self.n_embd


def _layer_shapes(s: HybridShape, kind: str) -> dict[str, tuple]:
    e, d = s.n_embd, s.head_dim
    mlp = {"W": (e, s.ffn_hidden), "V": (e, s.ffn_hidden), "W_2": (s.ffn_hidden, e)}
    if kind == "attn":
        return {"q_attn": (e, s.n_head_q, d), "k_attn": (e, s.n_head_kv, d), "v_attn": (e, s.n_head_kv, d),
                "c_proj": (s.n_head_q, d, e), **mlp}
    return {"in_proj": (e, 2 * s.d_inner), "conv_kernel": (s.d_conv, s.d_inner), "conv_bias": (s.d_inner,),
            "x_proj": (s.d_inner, s.dt_rank + 2 * s.d_state), "dt_proj": (s.dt_rank, s.d_inner), "dt_bias": (s.d_inner,),
            "out_proj": (s.d_inner, e), **mlp}


def layer_weights(shape: HybridShape, key, layer, kind: str, dtype=jnp.bfloat16) -> dict:
    """Every leaf of layer `layer` (a whole number or a traced index) of kind `kind`,
    under the reference's names; kernels in `dtype`, the rest float32."""
    layer_key = jax.random.fold_in(key, layer)
    out = {}
    for i, (name, dims) in enumerate(_layer_shapes(shape, kind).items()):
        k = jax.random.fold_in(layer_key, i)
        if name in ("conv_kernel", "conv_bias"):
            value = jax.random.uniform(k, dims, jnp.float32, -1.0, 1.0) * shape.d_conv ** -0.5
        elif name == "dt_proj":
            value = jax.random.uniform(k, dims, jnp.float32, -1.0, 1.0) * shape.dt_rank ** -0.5
        elif name == "dt_bias":
            dt = jnp.exp(jax.random.uniform(k, dims, jnp.float32) * (math.log(DT_MAX) - math.log(DT_MIN)) + math.log(DT_MIN))
            dt = jnp.maximum(dt, DT_FLOOR)
            value = dt + jnp.log(-jnp.expm1(-dt))  # softplus's inverse
        else:
            std = STD / np.sqrt(2 * shape.n_layer) if name in ("c_proj", "out_proj", "W_2") else STD
            value = jax.random.normal(k, dims, jnp.float32) * std
        out[name] = value.astype(jnp.float32 if name in FLOAT32_LEAVES else dtype)
    ones = lambda *dims: jnp.ones(dims, jnp.float32)  # noqa: E731
    out.update(attention_norm=ones(shape.n_embd), ffn_norm=ones(shape.n_embd))
    if kind == "ssm":
        out.update(
            A_log=jnp.broadcast_to(jnp.log(jnp.arange(1, shape.d_state + 1, dtype=jnp.float32)), (shape.d_inner, shape.d_state)),
            D=ones(shape.d_inner), dt_norm=ones(shape.dt_rank), b_norm=ones(shape.d_state), c_norm=ones(shape.d_state))
    return out


def embedding(shape: HybridShape, key, dtype=jnp.bfloat16):
    """The table [V, E]: the embedding and, transposed, the head."""
    return (jax.random.normal(jax.random.fold_in(jax.random.fold_in(key, 1_000_003), 0),
                              (shape.vocab_size, shape.n_embd), jnp.float32) * STD).astype(dtype)


def run_weights(shape: HybridShape, key, first: int, length: int, kind: str, dtype=jnp.bfloat16) -> dict:
    """The layers `first .. first + length - 1`, all of kind `kind`, stacked on a leading axis."""
    return jax.lax.map(lambda l: layer_weights(shape, key, l, kind, dtype), first + jnp.arange(length))  # one layer's program, compiled once


def _program_block(w: dict, kind: str) -> dict:
    """One run's stacked leaves in the layout of the program's block."""
    block = {"attention_norm": {"scale": w["attention_norm"]}, "ffn_norm": {"scale": w["ffn_norm"]},
             "mlp": {name: {"kernel": w[name]} for name in ("W", "V", "W_2")}}
    if kind == "attn":
        block["attn"] = {name: {"kernel": w[name]} for name in ("q_attn", "k_attn", "v_attn", "c_proj")}
    else:
        block["ssm"] = {
            "in_proj": {"kernel": w["in_proj"]}, "conv": {"kernel": w["conv_kernel"], "bias": w["conv_bias"]},
            "x_proj": {"kernel": w["x_proj"]}, "dt_norm": {"scale": w["dt_norm"]}, "b_norm": {"scale": w["b_norm"]},
            "c_norm": {"scale": w["c_norm"]}, "dt_proj": {"kernel": w["dt_proj"], "bias": w["dt_bias"]},
            "A_log": w["A_log"], "D": w["D"], "out_proj": {"kernel": w["out_proj"]},
        }
    return block


def reference_layout(program_params) -> dict:
    """The program's parameter tree (or a tree shaped like it: gradients, moments),
    renamed to the reference's layout (no copy): `{"runs": [stacked leaves of a run, ...],
    "wte", "final_norm"}`."""
    p = program_params["params"]
    runs = []
    for i in range(sum(name.startswith("run_") for name in p)):
        block = p[f"run_{i}"]["blocks"]["block"]
        w = {"attention_norm": block["attention_norm"]["scale"], "ffn_norm": block["ffn_norm"]["scale"],
             **{name: block["mlp"][name]["kernel"] for name in ("W", "V", "W_2")}}
        if "attn" in block:
            w.update({name: block["attn"][name]["kernel"] for name in ("q_attn", "k_attn", "v_attn", "c_proj")})
        else:
            ssm = block["ssm"]
            w.update({name: ssm[name]["kernel"] for name in ("in_proj", "x_proj", "dt_proj", "out_proj")})
            w.update({name: ssm[name]["scale"] for name in ("dt_norm", "b_norm", "c_norm")})
            w.update(conv_kernel=ssm["conv"]["kernel"], conv_bias=ssm["conv"]["bias"], dt_bias=ssm["dt_proj"]["bias"],
                     A_log=ssm["A_log"], D=ssm["D"])
        runs.append(w)
    return {"runs": runs, "wte": p["wte"], "final_norm": p["lm_head_norm"]["scale"]}


def program_tree(shape: HybridShape, key, dtype=jnp.bfloat16) -> dict:
    """The whole parameter tree in the layout the program keeps for a stack of more than
    one kind of layer: `{"params": {"run_<i>": {"blocks": {"block": ...stacked over the
    run's layers}}, "lm_head_norm", "wte"}}`. Traceable, and `key` (from `seed_key`) is
    an argument, so that one compiled program serves every seed."""
    if len(shape.runs) < 2:
        raise ValueError("a stack of one kind of layer is the dense decoder's layout (benchmark/weights.py)")
    params = {f"run_{i}": {"blocks": {"block": _program_block(run_weights(shape, key, first, length, kind, dtype), kind)}}
              for i, (kind, first, length) in enumerate(shape.runs)}
    params["lm_head_norm"] = {"scale": jnp.ones((shape.n_embd,), jnp.float32)}
    params["wte"] = embedding(shape, key, dtype)
    return {"params": params}


def make_program_tree(shape: HybridShape, seed: int, like, match_dtypes: bool = True):
    """`program_tree` materialized on the device in one jitted call, with the shardings
    of `like`: the program's own parameter tree (arrays, or shapes from `jax.eval_shape`),
    whose paths and shapes the result must have — anything else means the program's
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
