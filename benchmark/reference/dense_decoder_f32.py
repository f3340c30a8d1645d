"""The plain reference of the dense decoder: forward pass, loss, gradients and AdamW in
straightforward `jax.numpy`, float32, every matmul under
`jax.default_matmul_precision("highest")`. No kernels, no cache, no batching tricks, and
no import of the program under test: its weights come from `benchmark/weights.py`.

Architecture, as the 2.7B recipe states it: token embedding (no position embedding),
pre-norm blocks [RMSNorm -> grouped-query causal attention with rotary positions
(rotate-half, base 10000) -> residual; RMSNorm -> SwiGLU (silu(x W) * (x V)) W_2 ->
residual], final RMSNorm, untied head, mean cross entropy over all positions.

Departures from a textbook forward, all for memory and none for the numbers: attention
is computed in blocks of query rows, the head in blocks of positions, rows of a batch
one after another, each block rematerialized in the backward pass.

`precision` selects the control of "How `correct` is decided": "f32" is the reference;
"int8" rounds every matmul weight to 8 bits (symmetric, one scale per output channel)
before use, the nearest precision below the bfloat16 the configurations state.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.weights import DecoderShape, layer_weights, outer_weights, seed_key

HIGHEST = "highest"
Q_BLOCK = 512  # query rows per attention block
HEAD_BLOCK = 1024  # positions per head/loss block

# which axes of each kernel are summed over in its matmul (the others are output channels)
CONTRACT_AXES = {"q_attn": (0,), "k_attn": (0,), "v_attn": (0,), "c_proj": (0, 1), "W": (0,), "V": (0,),
                 "W_2": (0,), "lm_head": (0,), "wte": (1,)}


def fake_quant_int8(w, contract_axes):
    """`w` rounded to int8 and back: symmetric, one scale per output channel."""
    scale = jnp.max(jnp.abs(w), axis=contract_axes, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return jnp.round(w / scale).clip(-127, 127) * scale


def _as_precision(name: str, w, precision: str):
    w = w.astype(jnp.float32)
    if precision == "f32":
        return w
    if precision == "int8":
        return fake_quant_int8(w, CONTRACT_AXES[name])
    raise ValueError(f"unknown precision {precision!r}")


# ------------------------------------------------------------------ the layers


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def rotary(x, positions, base):
    """x [S, H, D], positions [S]; rotate-half convention."""
    d = x.shape[-1]
    inv_freq = 1.0 / (base ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angles = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    angles = jnp.concatenate([angles, angles], axis=-1)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return x * jnp.cos(angles) + jnp.concatenate([-x2, x1], axis=-1) * jnp.sin(angles)


def causal_attention(q, k, v):
    """q [S, Hq, D], k/v [S, Hkv, D] -> [S, Hq, D]. Query head h reads kv head
    h // (Hq / Hkv). Softmax in float32, in blocks of Q_BLOCK query rows."""
    s, hq, d = q.shape
    group = hq // k.shape[1]
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    block = min(Q_BLOCK, s)
    pad = (-s) % block
    qp = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(-1, block, hq, d)
    starts = jnp.arange(qp.shape[0]) * block

    @jax.checkpoint
    def one_block(args):
        qb, start = args
        scores = jnp.einsum("qhd,khd->hqk", qb, k, precision=HIGHEST) / np.sqrt(d)
        rows = start + jnp.arange(block)
        mask = rows[:, None] >= jnp.arange(s)[None, :]
        scores = jnp.where(mask[None], scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        return jnp.einsum("hqk,khd->qhd", probs, v, precision=HIGHEST)

    out = jax.lax.map(one_block, (qp, starts))
    return out.reshape(-1, hq, d)[:s]


def block_forward(x, w, shape: DecoderShape):
    """One pre-norm block on one sequence. x [S, E]; w: the layer's seven kernels and two
    norm scales, float32."""
    positions = jnp.arange(x.shape[0])
    h = rms_norm(x, w["attention_norm"], shape.norm_eps)
    q = jnp.einsum("se,ehd->shd", h, w["q_attn"], precision=HIGHEST)
    k = jnp.einsum("se,ehd->shd", h, w["k_attn"], precision=HIGHEST)
    v = jnp.einsum("se,ehd->shd", h, w["v_attn"], precision=HIGHEST)
    q, k = rotary(q, positions, shape.rope_base), rotary(k, positions, shape.rope_base)
    a = causal_attention(q, k, v)
    x = x + jnp.einsum("shd,hde->se", a, w["c_proj"], precision=HIGHEST)
    h = rms_norm(x, w["ffn_norm"], shape.norm_eps)
    gate = jnp.einsum("se,ef->sf", h, w["W"], precision=HIGHEST)
    up = jnp.einsum("se,ef->sf", h, w["V"], precision=HIGHEST)
    return x + jnp.einsum("sf,fe->se", jax.nn.silu(gate) * up, w["W_2"], precision=HIGHEST)


def head_logits(x, final_norm, lm_head, shape: DecoderShape):
    """x [S, E] -> float32 logits [S, V]."""
    return jnp.einsum("se,ev->sv", rms_norm(x, final_norm, shape.norm_eps), lm_head, precision=HIGHEST)


# ------------------------------------------------------------------ the forward pass, layer by layer


def reference_layer(shape: DecoderShape, key, layer, precision: str = "f32") -> dict:
    """Layer `layer` of the seeded weights (`key` from `seed_key`): the bfloat16 values the
    program is given, upcast (and, for the control, rounded to int8)."""
    raw = layer_weights(shape, key, layer)
    w = {name: _as_precision(name, value, precision) for name, value in raw.items()}
    w["attention_norm"] = jnp.ones((shape.n_embd,), jnp.float32)
    w["ffn_norm"] = jnp.ones((shape.n_embd,), jnp.float32)
    return w


def reference_outer(shape: DecoderShape, key, precision: str = "f32") -> dict:
    raw = outer_weights(shape, key)
    out = {name: _as_precision(name, value, precision) for name, value in raw.items()}
    out["final_norm"] = jnp.ones((shape.n_embd,), jnp.float32)
    return out


def logits_layer_by_layer(shape: DecoderShape, seed: int, tokens, precision: str = "f32"):
    """Float32 logits [N, S, V] of `tokens` [N, S] (int32; rows padded at the end are
    harmless under the causal mask). One layer's float32 weights live at a time, so a
    model whose float32 copy would not fit beside anything else still fits alone. The
    seed's key is an argument of every jitted piece: one compile serves every seed."""
    tokens = jnp.asarray(tokens, jnp.int32)
    key = seed_key(seed)

    @jax.jit
    def embed(wte, tokens):
        return jnp.take(wte, tokens, axis=0)

    @functools.partial(jax.jit, static_argnums=0)
    def one_layer(layer_precision, x, key, layer):
        w = reference_layer(shape, key, layer, layer_precision)
        return jax.lax.map(lambda row: block_forward(row, w, shape), x)

    @jax.jit
    def head(x, final_norm, lm_head):
        return jax.lax.map(lambda row: head_logits(row, final_norm, lm_head, shape), x)

    outer = jax.jit(lambda key: reference_outer(shape, key, precision))(key)
    x = embed(outer["wte"], tokens)
    for layer in range(shape.n_layer):
        x = one_layer(precision, x, key, jnp.int32(layer))
    return head(x, outer["final_norm"], outer["lm_head"])


# ------------------------------------------------------------------ training: loss, gradients, AdamW


def reference_params(shape: DecoderShape, key, precision: str = "f32") -> dict:
    """All weights, float32, layers stacked on a leading axis. Traceable."""
    stacked = jax.vmap(lambda l: layer_weights(shape, key, l))(jnp.arange(shape.n_layer))
    layers = {name: jax.vmap(lambda w, name=name: _as_precision(name, w, precision))(value)
              for name, value in stacked.items()}
    layers["attention_norm"] = jnp.ones((shape.n_layer, shape.n_embd), jnp.float32)
    layers["ffn_norm"] = jnp.ones((shape.n_layer, shape.n_embd), jnp.float32)
    outer = reference_outer(shape, key, precision)
    return {"layers": layers, **outer}


def sequence_loss_sum(params, tokens, targets, shape: DecoderShape):
    """Sum of the cross entropy over the positions of one sequence."""
    x = jnp.take(params["wte"], tokens, axis=0)

    @jax.checkpoint
    def body(x, w):
        return block_forward(x, w, shape), None

    x, _ = jax.lax.scan(body, x, params["layers"])
    s = x.shape[0]
    block = min(HEAD_BLOCK, s)
    pad = (-s) % block
    xp = jnp.pad(x, ((0, pad), (0, 0))).reshape(-1, block, x.shape[-1])
    tp = jnp.pad(targets, (0, pad)).reshape(-1, block)
    valid = (jnp.arange(s + pad) < s).reshape(-1, block)

    @jax.checkpoint
    def one_block(args):
        xb, tb, vb = args
        logits = head_logits(xb, params["final_norm"], params["lm_head"], shape)
        nll = jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(logits, tb[:, None], axis=-1)[:, 0]
        return jnp.sum(jnp.where(vb, nll, 0.0))

    return jnp.sum(jax.lax.map(one_block, (xp, tp, valid)))


def batch_loss(params, tokens, targets, shape: DecoderShape):
    """Mean cross entropy over every position of every row. tokens/targets [B, S]."""
    sums = jax.lax.map(
        jax.checkpoint(lambda row: sequence_loss_sum(params, row[0], row[1], shape)), (tokens, targets)
    )
    return jnp.sum(sums) / (tokens.shape[0] * tokens.shape[1])


# norm scales and the embedding are not decayed, as the recipe's optimizer block says
# (`weight_decay_groups_excluded: [embedding, norm]`)
NOT_DECAYED = ("attention_norm", "ffn_norm", "final_norm", "wte")


def _decay_mask(params):
    return {
        "layers": {name: name not in NOT_DECAYED for name in params["layers"]},
        **{name: name not in NOT_DECAYED for name in params if name != "layers"},
    }


def leaf_norms(tree) -> dict:
    """Euclidean norm of every leaf; a stacked leaf [L, ...] gives one norm per layer."""
    out = {}
    for name, value in tree["layers"].items():
        out[f"layers.{name}"] = jnp.sqrt(jnp.sum(value.astype(jnp.float32) ** 2, axis=tuple(range(1, value.ndim))))
    for name, value in tree.items():
        if name != "layers":
            out[name] = jnp.sqrt(jnp.sum(value.astype(jnp.float32) ** 2))
    return out


def leaf_difference_norms(ours, theirs) -> dict:
    """Euclidean norm of (ours - theirs) for every leaf, one leaf of `theirs` (a tree of
    host arrays, any float type) on the device at a time; a stacked leaf gives one norm
    per layer."""
    norm = jax.jit(lambda a, b: jnp.sqrt(jnp.sum((a - b.astype(jnp.float32)) ** 2, axis=tuple(range(1, a.ndim)))
                                         if a.ndim > 1 else jnp.sum((a - b.astype(jnp.float32)) ** 2)))
    out = {f"layers.{name}": norm(value, theirs["layers"][name]) for name, value in ours["layers"].items()}
    for name, value in ours.items():
        if name != "layers":
            stacked = value.ndim > 1
            out[name] = norm(value[None], theirs[name][None])[0] if stacked else norm(value, theirs[name])
    return jax.device_get(out)


def train_steps(shape: DecoderShape, seed: int, batches, hyper: dict, precision: str = "f32",
                other_first_grad=None, keep_first_grad: bool = False) -> dict:
    """Follow the first `len(batches)` optimizer steps from the seeded weights.

    `batches` is a list of (tokens [B, S], targets [B, S]); `hyper` holds `lr` (a list,
    one learning rate per step), `b1`, `b2`, `eps`, `weight_decay`, `clip_norm`. AdamW as
    the recipe's optimizer block describes it: global-norm clipping, bias-corrected
    moments, decoupled decay scaled by the learning rate.

    Returns the loss of each step, the norm of each leaf of the first clipped gradient,
    and the norm of each leaf of the parameters' change after the last step. With
    `other_first_grad` (someone else's first gradient as their optimizer got it, a tree
    of host arrays in this layout) also the norm of each leaf of its difference from
    this one; with `keep_first_grad` this first gradient itself, on the host."""
    key = seed_key(seed)
    params = jax.jit(lambda key: reference_params(shape, key, precision))(key)
    zeros = jax.jit(lambda p: jax.tree.map(jnp.zeros_like, p))
    mu, nu = zeros(params), zeros(params)
    mask = _decay_mask(params)
    loss_and_grad = jax.jit(jax.value_and_grad(functools.partial(batch_loss, shape=shape)))

    @functools.partial(jax.jit, donate_argnums=(0,))
    def clip(grads):
        norm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
        factor = jnp.minimum(1.0, hyper["clip_norm"] / jnp.maximum(norm, 1e-30))
        return jax.tree.map(lambda g: g * factor, grads), norm

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def update(params, mu, nu, grads, lr, t):
        def one(p, m, v, g, decayed):
            m = hyper["b1"] * m + (1 - hyper["b1"]) * g
            v = hyper["b2"] * v + (1 - hyper["b2"]) * g * g
            step = (m / (1 - hyper["b1"] ** t)) / (jnp.sqrt(v / (1 - hyper["b2"] ** t)) + hyper["eps"])
            if decayed:
                step = step + hyper["weight_decay"] * p
            return p - lr * step, m, v

        out = jax.tree.map(one, params, mu, nu, grads, mask)
        pick = lambda i: jax.tree.map(lambda o: o[i], out, is_leaf=lambda o: isinstance(o, tuple))  # noqa: E731
        return pick(0), pick(1), pick(2)

    losses, first_grad_norms = [], None
    for i, (tokens, targets) in enumerate(batches):
        loss, grads = loss_and_grad(params, jnp.asarray(tokens, jnp.int32), jnp.asarray(targets, jnp.int32))
        losses.append(float(loss))
        grads, _ = clip(grads)
        if i == 0:
            first_grad_norms = jax.device_get(jax.jit(leaf_norms)(grads))
            extra = {}
            if other_first_grad is not None:
                extra["first_grad_difference_norms"] = leaf_difference_norms(grads, other_first_grad)
            if keep_first_grad:
                extra["first_grad"] = jax.device_get(grads)
        params, mu, nu = update(params, mu, nu, grads, jnp.float32(hyper["lr"][i]), jnp.float32(i + 1))
        del grads
    del mu, nu

    @functools.partial(jax.jit, donate_argnums=(0,))
    def change(params, key):
        start = reference_params(shape, key, precision)
        return leaf_norms(jax.tree.map(lambda a, b: a - b, params, start))

    delta_norms = jax.device_get(change(params, key))
    return {"losses": losses, "first_grad_norms": first_grad_norms, "delta_norms": delta_norms, **extra}
