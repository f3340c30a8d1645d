"""Weights of the looped decoder (`model_type: ouro`), made by the benchmark from `--seed`:
the twin of `benchmark/weights.py` for a stack that is walked several times over ONE set
of weights. The program under test and the plain reference
(`benchmark/reference/looped_decoder_f32.py`) both get their weights from here, and one
layer's tensors depend only on (seed, layer index): no walk has a tensor of its own.

Distribution: the recipe's "scaled" init — normal, std 0.02, and 0.02 / sqrt(2 L) for the
two projections that write into the residual stream (L the layers held, not the layer
applications); the four norm scales of a block and the final norm's are 1; the exit gate's
vector and bias 0, as the program's own initializer leaves them (`LoopedShape.gate_std`): a
fresh model's gates sit at 1/2 for every token, its exit distribution at 1/2, 1/4, 1/8, 1/8,
and nothing flows into the stack through the gate until the gate has moved. (Seeded normal,
std 0.02, the entropy term's push, alike for every token, came back through the one vector
into every weight: the first gradient's norm read 18-26 and its bfloat16 error 2.4-6.1% by
seed against the int8 control's 7-20%, which no limit separates with room: PERF.md section
2. The CPU tests seed it so, to hold the path.) Kernels are bfloat16 as the program trains
them; norm scales and the gate float32, as the program keeps them.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.weights import _described, seed_key  # noqa: F401  (the same key for the same seed as the dense decoder's)
from benchmark.weights_hybrid import resolved

STD = 0.02
KERNELS = ("q_attn", "k_attn", "v_attn", "c_proj", "W", "V", "W_2")
NORMS = ("attention_norm", "post_attention_norm", "ffn_norm", "post_ffn_norm")  # N_1 .. N_4 of a block, in the order they are applied
OUTER = ("wte", "lm_head", "final_norm", "gate_w", "gate_b")


@dataclass(frozen=True)
class LoopedShape:
    """Sizes of the looped decoder, as the configuration's `model` block states them."""

    vocab_size: int
    n_layer: int
    n_head: int  # query heads = key/value heads
    n_embd: int
    ffn_hidden: int  # the SwiGLU hidden size actually used (5632)
    total_ut_steps: int
    beta: float
    rope_base: float = 1e6
    norm_eps: float = 1e-6
    gate_std: float = 0.0  # of the seeded gate vector; 0 in every cell (the program's own initial value), 0.02 where a test holds the path through the gate

    @property
    def head_dim(self) -> int:
        return self.n_embd // self.n_head

    @classmethod
    def from_yaml(cls, raw: dict) -> "LoopedShape":
        """`raw` is the cell's YAML as `yaml.safe_load` gives it (its model block reads the
        source's numbers from the top level). Only the looped SwiGLU/RoPE/RMSNorm/untied
        decoder with sandwich norms and an exit gate is understood; anything else is an
        error, not a guess."""
        model = resolved(raw["model_raw"]["config"], raw)
        problems = []
        if model.get("activation_type") != "swiglu":
            problems.append("activation_type must be swiglu")
        if model.get("poe_type") != "NOPE" or model["attention_config"]["qkv_transforms"][0]["type_hint"] != "RotaryTransform":
            problems.append("positions must be rotary (poe_type NOPE + RotaryTransform)")
        if model.get("use_weight_tying") or model.get("bias") or int(model["n_head_q"]) != int(model["n_head_kv"]):
            problems.append("weight tying, biases and grouped key/value heads are not supported")
        names = ("attention_norm_config", "post_attention_norm_config", "ffn_norm_config", "post_ffn_norm_config", "lm_head_norm_config")
        norms = [model.get(k) or {} for k in names]
        if any(n.get("norm_type") != "rms_norm" for n in norms):
            problems.append("a block needs its four rms_norm norms (sandwich) and the final norm: " + ", ".join(names))
        loop = model.get("loop_config") or {}
        if not loop.get("exit_gate", True) or "total_ut_steps" not in loop:
            problems.append("loop_config must give total_ut_steps and keep the exit gate")
        if problems:
            raise ValueError("benchmark weights: " + "; ".join(problems))
        multiple = int(model.get("enforce_swiglu_hidden_dim_multiple_of", 256))
        hidden = int(2 * int(model["ffn_hidden"]) / 3)
        hidden = ((hidden + multiple - 1) // multiple) * multiple
        rotary = model["attention_config"]["qkv_transforms"][0]["config"]
        return cls(
            vocab_size=int(model["vocab_size"]), n_layer=int(model["n_layer"]), n_head=int(model["n_head_q"]),
            n_embd=int(model["n_embd"]), ffn_hidden=hidden, total_ut_steps=int(loop["total_ut_steps"]),
            beta=float(loop.get("beta", 0.1)), rope_base=float(rotary.get("base_freq", 10000)),
            norm_eps=float(norms[0]["config"].get("epsilon", 1e-6)),
        )

    def layer_matmul_params(self) -> int:
        """A layer's parameters that take part in matrix multiplications: its seven kernels."""
        return 4 * self.n_embd * self.n_embd + 3 * self.n_embd * self.ffn_hidden

    def layer_params(self) -> int:
        return self.layer_matmul_params() + len(NORMS) * self.n_embd

    def outer_params(self) -> int:
        """The two tables, the final norm, the gate's vector and bias."""
        return 2 * self.vocab_size * self.n_embd + self.n_embd + self.n_embd + 1

    def all_params(self) -> int:
        """One parameter tree whatever `total_ut_steps` is."""
        return self.n_layer * self.layer_params() + self.outer_params()

    @property
    def applications(self) -> int:
        return self.n_layer * self.total_ut_steps


def _layer_shapes(s: LoopedShape) -> dict[str, tuple]:
    e, h, d = s.n_embd, s.n_head, s.head_dim
    return {"q_attn": (e, h, d), "k_attn": (e, h, d), "v_attn": (e, h, d), "c_proj": (h, d, e),
            "W": (e, s.ffn_hidden), "V": (e, s.ffn_hidden), "W_2": (s.ffn_hidden, e)}


def layer_weights(shape: LoopedShape, key, layer, dtype=jnp.bfloat16) -> dict:
    """The seven kernels of layer `layer` (a whole number or a traced index) and its four norm scales."""
    layer_key = jax.random.fold_in(key, layer)
    out = {}
    for i, (name, dims) in enumerate(_layer_shapes(shape).items()):
        std = STD / np.sqrt(2 * shape.n_layer) if name in ("c_proj", "W_2") else STD
        out[name] = (jax.random.normal(jax.random.fold_in(layer_key, i), dims, jnp.float32) * std).astype(dtype)
    out.update({name: jnp.ones((shape.n_embd,), jnp.float32) for name in NORMS})
    return out


def outer_weights(shape: LoopedShape, key, dtype=jnp.bfloat16) -> dict:
    """Embedding table [V, E], head kernel [E, V], the final norm's scale, the gate's vector [E] and bias []."""
    outer_key = jax.random.fold_in(key, 1_000_003)
    draw = lambda i, dims: jax.random.normal(jax.random.fold_in(outer_key, i), dims, jnp.float32) * STD  # noqa: E731
    return {"wte": draw(0, (shape.vocab_size, shape.n_embd)).astype(dtype), "lm_head": draw(1, (shape.n_embd, shape.vocab_size)).astype(dtype),
            "final_norm": jnp.ones((shape.n_embd,), jnp.float32), "gate_w": draw(2, (shape.n_embd,)) * (shape.gate_std / STD),
            "gate_b": jnp.zeros((), jnp.float32)}


def program_tree(shape: LoopedShape, key, dtype=jnp.bfloat16) -> dict:
    """The whole parameter tree in the layout the program's looped decoder keeps: the dense
    decoder's, with a block's two further norms and `exit_gate` beside the head. Traceable,
    and `key` (from `seed_key`) is an argument: one compiled program serves every seed."""
    stacked = jax.vmap(lambda l: layer_weights(shape, key, l, dtype))(jnp.arange(shape.n_layer))
    outer = outer_weights(shape, key, dtype)
    block = {
        **{name: {"scale": stacked[name]} for name in NORMS},
        "attn": {name: {"kernel": stacked[name]} for name in ("q_attn", "k_attn", "v_attn", "c_proj")},
        "mlp": {name: {"kernel": stacked[name]} for name in ("W", "V", "W_2")},
    }
    return {"params": {
        "blocks": {"block": block},
        "exit_gate": {"kernel": outer["gate_w"][:, None], "bias": outer["gate_b"][None]},
        "lm_head": {"kernel": outer["lm_head"]},
        "lm_head_norm": {"scale": outer["final_norm"]},
        "wte": outer["wte"],
    }}


def reference_layout(program_params) -> dict:
    """The program's parameter tree (or its gradient, or a moment), renamed to the reference's layout (no copy)."""
    p = program_params["params"]
    block = p["blocks"]["block"]
    layers = {name: block["attn"][name]["kernel"] for name in ("q_attn", "k_attn", "v_attn", "c_proj")}
    layers.update({name: block["mlp"][name]["kernel"] for name in ("W", "V", "W_2")})
    layers.update({name: block[name]["scale"] for name in NORMS})
    return {"layers": layers, "wte": p["wte"], "lm_head": p["lm_head"]["kernel"], "final_norm": p["lm_head_norm"]["scale"],
            "gate_w": p["exit_gate"]["kernel"][:, 0], "gate_b": p["exit_gate"]["bias"][0]}


def make_program_tree(shape: LoopedShape, seed: int, like, match_dtypes: bool = True):
    """`program_tree` materialized on the device in one jitted call, with the shardings of
    `like`: the program's own parameter tree (arrays, or shapes from `jax.eval_shape`), whose
    paths and shapes the result must have — anything else means the program's layout
    changed, and is an error."""
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
