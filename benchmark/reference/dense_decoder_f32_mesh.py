"""The dense reference (`dense_decoder_f32.py`, every function of its arithmetic imported, none copied) followed
through its first optimizer steps on SEVERAL chips, for a model whose float32 weights one chip cannot hold.

What is added is where the arrays live, not what is computed. Every parameter is split over one flat axis of the
devices along the dimension a tensor-parallel layout would split (heads, the SwiGLU's hidden width, the vocabulary),
the jitted loss and gradient take and return arrays in that layout, and the partitioner does the rest: the same
`batch_loss` over both rows of a step, the same `highest`-precision products, summed over devices instead of over one
chip's loop. The plain reference's batch is the GLOBAL batch, every row of a step whichever data-parallel group of
the program it went to, so a program whose groups each step on their own rows does not match it.

Memory. At the 2.7B recipe's own depth the float32 parameters are 2.7 GB a chip of four, and so is a gradient; Adam's
two moments would be two more of each (14.9 GiB a chip by PR 23's compile: too tight). So no moment is kept: after t
steps m = (1 - b1) sum_j b1^(t-j) g_j and v = (1 - b2) sum_j b2^(t-j) g_j^2, and the two steps this follows need the
first clipped gradient once more, which stays on the devices (one array set where the moments are two) while the second
is computed. `train_steps` returns what `dense_decoder_f32.train_steps` returns, under the same names.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from benchmark.reference.dense_decoder_f32 import NOT_DECAYED, batch_loss, leaf_difference_norms, leaf_norms, reference_params
from benchmark.weights import DecoderShape, seed_key

AXIS = "chips"
# the dimension of each leaf that is split over the devices (stacked leaves carry the layer first)
SPLIT = {"q_attn": 2, "k_attn": 2, "v_attn": 2, "c_proj": 1, "W": 2, "V": 2, "W_2": 1, "attention_norm": None, "ffn_norm": None,
         "wte": 0, "lm_head": 1, "final_norm": None}


def mesh_of(devices) -> Mesh:
    return Mesh(np.array(list(devices)), (AXIS,))


def shardings(shape: DecoderShape, mesh: Mesh) -> dict:
    """The layout of `reference_params`: every kernel split along `SPLIT`'s dimension where the devices divide it, whole otherwise."""
    sizes = jax.eval_shape(lambda key: reference_params(shape, key), seed_key(0))

    def of(name: str, leaf) -> NamedSharding:
        axis = SPLIT[name]
        if axis is None or leaf.shape[axis] % mesh.size:
            return NamedSharding(mesh, P())
        return NamedSharding(mesh, P(*([None] * axis), AXIS))

    return {"layers": {name: of(name, leaf) for name, leaf in sizes["layers"].items()},
            **{name: of(name, leaf) for name, leaf in sizes.items() if name != "layers"}}


def train_steps(shape: DecoderShape, seed: int, batches, hyper: dict, devices, precision: str = "f32",
                other_first_grad=None, keep_first_grad: bool = False, log=None) -> dict:
    """Follow the first `len(batches)` optimizer steps (one or two) from the seeded weights on `devices`.

    `batches` is a list of (tokens [B, S], targets [B, S]), each the whole batch of a step; `hyper` as
    `dense_decoder_f32.train_steps` takes it, and the result is what it returns: the loss of each step, the norm of each
    leaf of the first clipped gradient, the norm of each leaf of the parameters' change after the last step, with
    `other_first_grad` the norm of each leaf of its difference from this first gradient, and with `keep_first_grad` that
    gradient itself, on the host."""
    if not 1 <= len(batches) <= 2:
        raise ValueError(f"the reference over several chips follows one or two steps, not {len(batches)}")
    say = log or (lambda line: None)
    mesh = mesh_of(devices)
    layout = shardings(shape, mesh)
    whole = NamedSharding(mesh, P())
    key = seed_key(seed)
    b1, b2 = hyper["b1"], hyper["b2"]
    seeded = jax.jit(lambda key: reference_params(shape, key, precision), out_shardings=layout)
    loss_and_grad = jax.jit(jax.value_and_grad(functools.partial(batch_loss, shape=shape)),
                            in_shardings=(layout, whole, whole), out_shardings=(whole, layout))

    @functools.partial(jax.jit, donate_argnums=(0,), out_shardings=(layout, whole))
    def clip(grads):
        norm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
        factor = jnp.minimum(1.0, hyper["clip_norm"] / jnp.maximum(norm, 1e-30))
        return jax.tree.map(lambda g: g * factor, grads), norm

    def decayed(path) -> bool:
        return path[-1].key not in NOT_DECAYED

    def updated(params, gradients, lr, t):
        """AdamW's step `t` from the clipped gradients of steps 1..t, the moments written out as their sums."""
        def one(path, p, *gs):
            m = (1 - b1) * sum(b1 ** (len(gs) - 1 - j) * g for j, g in enumerate(gs))
            v = (1 - b2) * sum(b2 ** (len(gs) - 1 - j) * g * g for j, g in enumerate(gs))
            step = (m / (1 - b1 ** t)) / (jnp.sqrt(v / (1 - b2 ** t)) + hyper["eps"])
            return p - lr * (step + hyper["weight_decay"] * p if decayed(path) else step)

        return jax.tree_util.tree_map_with_path(one, params, *gradients)

    first_update = jax.jit(lambda params, g1, lr: updated(params, [g1], lr, 1), donate_argnums=(0,), out_shardings=layout)
    second_update = jax.jit(lambda params, g1, g2, lr: updated(params, [g1, g2], lr, 2), donate_argnums=(0,), out_shardings=layout)

    params = seeded(key)
    losses, extra, first = [], {}, None
    for i, (tokens, targets) in enumerate(batches):
        loss, grads = loss_and_grad(params, jnp.asarray(tokens, jnp.int32), jnp.asarray(targets, jnp.int32))
        losses.append(float(loss))
        grads, _ = clip(grads)
        say(f"[reference] step {i + 1}: loss {losses[-1]:.6f} over {np.shape(tokens)[0]} row(s) on {mesh.size} device(s)")
        if i == 0:
            first_grad_norms = jax.device_get(jax.jit(leaf_norms)(grads))
            if other_first_grad is not None:
                extra["first_grad_difference_norms"] = leaf_difference_norms(grads, other_first_grad)
            if keep_first_grad:
                extra["first_grad"] = jax.device_get(grads)
            params = first_update(params, grads, jnp.float32(hyper["lr"][0]))
            first = grads
        else:
            params = second_update(params, first, grads, jnp.float32(hyper["lr"][1]))
            first = None
        del grads
    del first

    @jax.jit
    def change(params, key):
        start = jax.lax.with_sharding_constraint(reference_params(shape, key, precision), layout)
        return leaf_norms(jax.tree.map(lambda a, b: a - b, params, start))

    delta_norms = jax.device_get(change(params, key))
    return {"losses": losses, "first_grad_norms": first_grad_norms, "delta_norms": delta_norms, **extra}
