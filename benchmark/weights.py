"""Weights of the dense decoder, made by the benchmark from `--seed`.

The program under test and the plain reference both get their weights from here, so
the reference takes nothing the program has made. One layer's tensors depend only on
(seed, layer index): the program gets all layers stacked, in one jitted call on the
device and in the type it trains them in (bfloat16 kernels, float32 norm
scales); the reference asks for one layer at a time and upcasts the same values.

Distribution: the recipe's "scaled" init — normal, std 0.02, and 0.02 / sqrt(2 L) for
the two projections that write into the residual stream; norm scales are 1.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

STD = 0.02


@dataclass(frozen=True)
class DecoderShape:
    """Sizes of the dense decoder, as the configuration's `model` block states them."""

    vocab_size: int
    n_layer: int
    n_head_q: int
    n_head_kv: int
    n_embd: int
    ffn_hidden: int  # the SwiGLU hidden size actually used (7680 in the 2.7B recipe)
    rope_base: float = 10000.0
    norm_eps: float = 1e-5

    @property
    def head_dim(self) -> int:
        return self.n_embd // self.n_head_q

    @classmethod
    def from_model_config(cls, model: dict) -> "DecoderShape":
        """`model` is the `config` of a `model.gpt2` block. Only the dense SwiGLU/RoPE/
        RMSNorm/untied decoder is understood; anything else is an error, not a guess."""
        problems = []
        if model.get("activation_type") != "swiglu":
            problems.append("activation_type must be swiglu")
        if model.get("poe_type") != "NOPE" or not model.get("attention_config", {}).get("qkv_transforms"):
            problems.append("positions must be rotary (poe_type NOPE + RotaryTransform)")
        if model.get("use_weight_tying") or model.get("bias"):
            problems.append("weight tying and biases are not supported")
        norms = [model.get(k, {}) for k in ("attention_norm_config", "ffn_norm_config", "lm_head_norm_config")]
        if any(n.get("norm_type") != "rms_norm" for n in norms):
            problems.append("norms must be rms_norm")
        if problems:
            raise ValueError("benchmark weights: " + "; ".join(problems))
        multiple = int(model.get("enforce_swiglu_hidden_dim_multiple_of", 256))
        hidden = int(2 * int(model["ffn_hidden"]) / 3)
        hidden = ((hidden + multiple - 1) // multiple) * multiple
        rotary = model["attention_config"]["qkv_transforms"][0]["config"]
        return cls(
            vocab_size=int(model["vocab_size"]), n_layer=int(model["n_layer"]),
            n_head_q=int(model["n_head_q"]), n_head_kv=int(model["n_head_kv"]),
            n_embd=int(model["n_embd"]), ffn_hidden=hidden,
            rope_base=float(rotary.get("base_freq", 10000)),
            norm_eps=float(norms[0].get("config", {}).get("epsilon", 1e-5)),
        )

    def matmul_params(self) -> int:
        """Parameters that take part in matrix multiplications: every kernel of every
        block and the head; embedding rows (a gather) and norm scales are not."""
        e, d = self.n_embd, self.head_dim
        per_layer = e * self.n_head_q * d * 2 + e * self.n_head_kv * d * 2 + 3 * e * self.ffn_hidden
        return self.n_layer * per_layer + e * self.vocab_size

    def all_params(self) -> int:
        return self.matmul_params() + self.vocab_size * self.n_embd + (2 * self.n_layer + 1) * self.n_embd


def seed_key(seed: int):
    """A key from any whole number up to 2**63: the driver's seeds pass 2**31."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(np.uint32(seed >> 32)), np.uint32(seed & 0xFFFFFFFF))


def _layer_shapes(s: DecoderShape) -> dict[str, tuple]:
    e, d = s.n_embd, s.head_dim
    return {
        "q_attn": (e, s.n_head_q, d), "k_attn": (e, s.n_head_kv, d), "v_attn": (e, s.n_head_kv, d),
        "c_proj": (s.n_head_q, d, e), "W": (e, s.ffn_hidden), "V": (e, s.ffn_hidden), "W_2": (s.ffn_hidden, e),
    }


def layer_weights(shape: DecoderShape, key, layer, dtype=jnp.bfloat16) -> dict:
    """The seven kernels of layer `layer` (a whole number or a traced index)."""
    layer_key = jax.random.fold_in(key, layer)
    out = {}
    for i, (name, dims) in enumerate(_layer_shapes(shape).items()):
        std = STD / np.sqrt(2 * shape.n_layer) if name in ("c_proj", "W_2") else STD
        out[name] = (jax.random.normal(jax.random.fold_in(layer_key, i), dims, jnp.float32) * std).astype(dtype)
    return out


def outer_weights(shape: DecoderShape, key, dtype=jnp.bfloat16) -> dict:
    """Embedding table [V, E] and head kernel [E, V]."""
    outer_key = jax.random.fold_in(key, 1_000_003)
    wte = jax.random.normal(jax.random.fold_in(outer_key, 0), (shape.vocab_size, shape.n_embd), jnp.float32) * STD
    head = jax.random.normal(jax.random.fold_in(outer_key, 1), (shape.n_embd, shape.vocab_size), jnp.float32) * STD
    return {"wte": wte.astype(dtype), "lm_head": head.astype(dtype)}


def program_tree(shape: DecoderShape, key, dtype=jnp.bfloat16) -> dict:
    """The whole parameter tree in the layout the program's dense decoder keeps
    (`{"params": {"blocks": {"block": ...stacked over layers}, "wte", "lm_head", ...}}`).
    Traceable, and `key` (from `seed_key`) is an argument: a jitted caller passes it in,
    so that one compiled program serves every seed."""
    stacked = jax.vmap(lambda l: layer_weights(shape, key, l, dtype))(jnp.arange(shape.n_layer))
    ones = lambda *dims: jnp.ones(dims, jnp.float32)  # noqa: E731
    outer = outer_weights(shape, key, dtype)
    block = {
        "attention_norm": {"scale": ones(shape.n_layer, shape.n_embd)},
        "attn": {name: {"kernel": stacked[name]} for name in ("q_attn", "k_attn", "v_attn", "c_proj")},
        "ffn_norm": {"scale": ones(shape.n_layer, shape.n_embd)},
        "mlp": {name: {"kernel": stacked[name]} for name in ("W", "V", "W_2")},
    }
    return {"params": {
        "blocks": {"block": block},
        "lm_head": {"kernel": outer["lm_head"]},
        "lm_head_norm": {"scale": ones(shape.n_embd)},
        "wte": outer["wte"],
    }}


def _described(tree, with_dtypes: bool) -> dict:
    return {
        jax.tree_util.keystr(path): (tuple(x.shape), str(jnp.dtype(x.dtype))) if with_dtypes else tuple(x.shape)
        for path, x in jax.tree_util.tree_leaves_with_path(tree)
    }


def make_program_tree(shape: DecoderShape, seed: int, like, match_dtypes: bool = True):
    """`program_tree` materialized on the device in one jitted call, with the shardings
    of `like`: the program's own parameter tree (arrays, or shapes from `jax.eval_shape`),
    whose paths and shapes the result must have — anything else means the program's
    layout changed, and is an error. Training passes its state's parameters and
    `match_dtypes=True`; a caller holding a fresh float32 init passes False and gets bfloat16 kernels."""
    key = seed_key(seed)
    make = lambda key: program_tree(shape, key)  # noqa: E731
    want = _described(like, match_dtypes)
    have = _described(jax.eval_shape(make, key), match_dtypes)
    if want != have:
        differing = sorted(k for k in want.keys() | have.keys() if want.get(k) != have.get(k))
        raise ValueError(
            "benchmark weights do not fit the program's parameter tree: "
            + "; ".join(f"{k}: program {want.get(k)}, benchmark {have.get(k)}" for k in differing)
        )
    shardings = [getattr(x, "sharding", None) for x in jax.tree.leaves(like)]
    if any(s is None for s in shardings):
        return jax.jit(make)(key)
    return jax.jit(make, out_shardings=jax.tree.unflatten(jax.tree.structure(like), shardings))(key)
