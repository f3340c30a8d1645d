"""The plain reference of the latent-attention / expert-layer decoder (`model_type:
deepseek_v3`, as Hugging Face's `modeling_deepseek_v3.py` computes it): forward pass,
loss, gradients and AdamW in straightforward `jax.numpy`, float32, every matmul under
`jax.default_matmul_precision("highest")`. No kernels, no cache, no dispatch, and no
import of the program under test: its weights come from `benchmark/weights_moe.py`.

Architecture. Token embedding, then layers that are each `h = h + attn(RMSNorm(h))`,
`h = h + ffn(RMSNorm(h))`; final RMSNorm, an untied head, mean cross entropy over all
positions. `d` the width, `H` heads, `d_n`/`d_r`/`d_v` = `qk_nope_head_dim`/
`qk_rope_head_dim`/`v_head_dim`, `r = kv_lora_rank`; no bias anywhere.

Latent attention (every layer), on `x [S, d]`:

    q         = x W_q                     W_q [d, H, d_n + d_r]; a head splits into q_n, q_r
    (c, k_r)  = split(x W_kva)            W_kva [d, r + d_r]: the latent c and ONE rotary key for all heads
    (k_n, v)  = split(RMSNorm(c) W_kvb)   W_kvb [r, H, d_n + d_v], a head
    rotary on q_r of every head and on k_r: Hugging Face's way for `rope_interleave`: first
    move a head's even members to its front half, then rotate halves by pos * theta^(-2i/d_r)
    q = [q_n, q_r], k = [k_n, k_r on every head]; softmax(q k^T / sqrt(d_n + d_r), causal) v; W_o [H, d_v, d]

Feed-forward: the first `first_k_dense_replace` layers a SwiGLU `W_2(silu(W x) * (V x))`;
every later layer the expert layer, on `x [S, d]`:

    s       = sigmoid(x W_r)                       W_r [d, E]
    choice  = the k largest of s + b               b [E], a buffer: only the indices are used, so it has no gradient
    w       = s[choice] / (their sum + 1e-20) * routed_scaling_factor
    out     = sum over the chosen experts e of w_e * W_2_e(silu(W_e x) * (V_e x)) + shared(x)

**The share.** The layer holds the experts `[expert_offset, expert_offset + experts_held)`.
Router, choice and the weights' normalisation run over all E; the sum runs over the
chosen experts that are held, and what the absent ones would have added is left out, as
the program leaves it out. It is computed the plain way: every held expert on every
token, with the weight zero where the token did not choose it.

Departures from Hugging Face's module, none of which changes a number: `W_q`, `W_kvb`
and `W_o` are kept with their head axis apart (`[d, H, .]`, theirs `[d, H .]`); the
experts are three stacks `[held, d, f]`; `n_group = topk_group = 1` make the group step
the identity and it is not written. Not in `config.json`: any balance loss (none is
written), and how `b` moves between steps. That is DeepSeek-V3's published rule (arXiv
2412.19437, section 2.1.2): after a step `b_e += bias_update_speed * sign(mean load of the
E experts - load of e)`, the loads counted over the step's tokens and all E experts, held or
not (`moved_bias`; `train_steps` applies it after each step's AdamW update, which leaves
`b` alone); at speed 0 `b` stays as seeded.

Departures from a textbook forward, all for memory: attention is computed in blocks of
query rows, the head in blocks of positions, the experts one at a time, the mixer and the
feed-forward of a layer each rematerialized. Training walks the layers one at a time
(`gradient_stream`: the forward pass keeps each layer's input, the backward pass
differentiates one layer at a time), which computes what `jax.grad` of `batch_loss`
computes (a test holds the two together). At 1.02 B parameters float32 weights and one
gradient are 8.2 GB, so Adam's moments cannot live beside them on a 16 GB chip:
`train_steps` keeps each earlier step's clipped gradient on the host and forms both moments
from them where a later update needs them (the hybrid reference computes the earlier gradient
again instead: a third of this reference's time at two steps).

`precision`: "f32" is the reference; "int8" rounds every kernel the program keeps in
bfloat16 to 8 bits (symmetric, one scale per output channel) before use, the nearest
precision below the bfloat16 the configuration states: the control of "How `correct` is
decided". The router's matrix and `b` are float32 in the program and stay as they are.
"""

from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.weights_moe import MoEMLAShape, embedding, head as head_matrix, layer_weights, run_weights, seed_key

HIGHEST = "highest"
Q_BLOCK = 512  # query rows per attention block
HEAD_BLOCK = 1024  # positions per head/loss block

# which axes of each kernel are summed over where it is used (the others are output channels)
CONTRACT_AXES = {"q_proj": (0,), "kv_a_proj": (0,), "kv_b_proj": (0,), "c_proj": (0, 1), "W": (0,), "V": (0,), "W_2": (0,),
                 "experts_W": (1,), "experts_V": (1,), "experts_W_2": (1,), "shared_W": (0,), "shared_V": (0,), "shared_W_2": (0,),
                 "wte": (1,), "lm_head": (0,)}
# what AdamW does not decay: the configuration's `weight_decay_groups_excluded: [embedding, norm, router_bias]`
NOT_DECAYED = ("attention_norm", "ffn_norm", "kv_a_norm", "final_norm", "wte", "router_bias")
OUTER = ("wte", "lm_head", "final_norm")


def fake_quant_int8(w, contract_axes):
    """`w` rounded to int8 and back: symmetric, one scale per output channel."""
    scale = jnp.max(jnp.abs(w), axis=contract_axes, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return jnp.round(w / scale).clip(-127, 127) * scale


def _as_precision(name: str, w, precision: str):
    w = w.astype(jnp.float32)
    if precision == "f32" or name not in CONTRACT_AXES:
        return w
    if precision == "int8":
        return fake_quant_int8(w, CONTRACT_AXES[name])
    raise ValueError(f"unknown precision {precision!r}")


# ------------------------------------------------------------------ the layers


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def rope_move_then_rotate_halves(x, theta: float, offset=0):
    """Hugging Face's rotary for `rope_interleave`, on x [S, ..., D]: the even members of
    the last axis go to its front half and the odd ones to its back half, then position p
    rotates halves by p * theta^(-2i / D). `offset` shifts every position alike."""
    s, d = x.shape[0], x.shape[-1]
    x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angle = (offset + jnp.arange(s, dtype=jnp.float32))[:, None] * inv_freq
    angle = jnp.concatenate([angle, angle], axis=-1).reshape((s,) + (1,) * (x.ndim - 2) + (d,))
    rotated = jnp.concatenate([-x[..., d // 2:], x[..., : d // 2]], axis=-1)
    return x * jnp.cos(angle) + rotated * jnp.sin(angle)


def causal_attention(q, k, v):
    """q, k [S, H, D], v [S, H, Dv] -> [S, H, Dv]. Scores q k^T / sqrt(D), softmax in
    float32, in blocks of Q_BLOCK query rows."""
    s, h, d = q.shape
    block = min(Q_BLOCK, s)
    pad = (-s) % block
    qp = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(-1, block, h, d)
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
    return out.reshape(-1, h, v.shape[-1])[:s]


def latent_attention(x, w, shape: MoEMLAShape):
    d_n, r = shape.qk_nope_head_dim, shape.kv_lora_rank
    q = jnp.einsum("se,ehd->shd", x, w["q_proj"], precision=HIGHEST)
    latent = jnp.einsum("se,ef->sf", x, w["kv_a_proj"], precision=HIGHEST)
    c, k_r = latent[:, :r], latent[:, r:]
    kv = jnp.einsum("sr,rhd->shd", rms_norm(c, w["kv_a_norm"], shape.norm_eps), w["kv_b_proj"], precision=HIGHEST)
    k_n, v = kv[..., :d_n], kv[..., d_n:]
    q_r = rope_move_then_rotate_halves(q[..., d_n:], shape.rope_theta)
    k_r = rope_move_then_rotate_halves(k_r[:, None, :], shape.rope_theta)
    q = jnp.concatenate([q[..., :d_n], q_r], axis=-1)
    k = jnp.concatenate([k_n, jnp.broadcast_to(k_r, k_n.shape[:-1] + (k_r.shape[-1],))], axis=-1)
    return jnp.einsum("shd,hde->se", causal_attention(q, k, v), w["c_proj"], precision=HIGHEST)


def swiglu(h, gate, up, down):
    a = jnp.einsum("se,ef->sf", h, gate, precision=HIGHEST)
    b = jnp.einsum("se,ef->sf", h, up, precision=HIGHEST)
    return jnp.einsum("sf,fe->se", jax.nn.silu(a) * b, down, precision=HIGHEST)


def route(x, w, shape: MoEMLAShape):
    """The choice [S, k] over all E experts and its weights [S, k]."""
    scores = jax.nn.sigmoid(jnp.einsum("se,ex->sx", x, w["router"], precision=HIGHEST))
    _, choice = jax.lax.top_k(scores + w["router_bias"], shape.num_experts_per_tok)
    weights = jnp.take_along_axis(scores, choice, axis=-1)
    weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-20) * shape.routed_scaling_factor
    return choice, weights


def moved_bias(bias, load, speed: float):
    """`b` after a step in which the E experts got `load` [E] pairs: down by `speed` where an expert got more than
    the mean of the E, up where it got less."""
    return bias + speed * jnp.sign(jnp.mean(load) - load)


def expert_layer(x, w, shape: MoEMLAShape):
    """x [S, d]. Every held expert on every token, the weight zero where not chosen, plus the shared
    expert; and how many of the sequence's (token, choice) pairs each of the E experts got, held or not."""
    choice, weights = route(x, w, shape)
    held = jax.nn.one_hot(choice - shape.expert_offset, shape.experts_held, dtype=jnp.float32)  # an absent expert gives no one
    per_expert = jnp.einsum("sk,ske->se", weights, held)  # [S, held]

    @jax.checkpoint
    def one_expert(out, args):
        gate, up, down, weight = args
        return out + weight[:, None] * swiglu(x, gate, up, down), None

    out, _ = jax.lax.scan(one_expert, jnp.zeros_like(x), (w["experts_W"], w["experts_V"], w["experts_W_2"], per_expert.T))
    if shape.shared_hidden:
        out = out + swiglu(x, w["shared_W"], w["shared_V"], w["shared_W_2"])
    return out, jnp.sum(jax.nn.one_hot(choice, shape.n_routed_experts, dtype=jnp.float32), axis=(0, 1))


def block_forward(x, w, kind: str, shape: MoEMLAShape, count: bool = False):
    """One pre-norm layer on one sequence. x [S, E]; w: the layer's leaves, float32. With
    `count` also the pairs each of the layer's E experts got ([E]; zeros for a dense layer)."""

    @jax.checkpoint
    def mixer(x, w):
        return x + latent_attention(rms_norm(x, w["attention_norm"], shape.norm_eps), w, shape)

    @jax.checkpoint
    def ffn(x, w):
        h = rms_norm(x, w["ffn_norm"], shape.norm_eps)
        out, load = ((swiglu(h, w["W"], w["V"], w["W_2"]), jnp.zeros((shape.n_routed_experts,), jnp.float32)) if kind == "mlp"
                     else expert_layer(h, w, shape))
        return x + out, load

    y, load = ffn(mixer(x, w), w)
    return (y, load) if count else y


def head_logits(x, final_norm, lm_head, shape: MoEMLAShape):
    """x [S, E] -> float32 logits [S, V], against the untied head [E, V]."""
    return jnp.einsum("se,ev->sv", rms_norm(x, final_norm, shape.norm_eps), lm_head, precision=HIGHEST)


# ------------------------------------------------------------------ the forward pass, layer by layer


def reference_layer(shape: MoEMLAShape, key, layer: int, precision: str = "f32") -> dict:
    """Layer `layer` of the seeded weights: the values the program is given, upcast
    (and, for the control, its kernels rounded to int8)."""
    raw = layer_weights(shape, key, layer, shape.kinds[layer])
    return {name: _as_precision(name, value, precision) for name, value in raw.items()}


def logits_layer_by_layer(shape: MoEMLAShape, seed: int, tokens, precision: str = "f32"):
    """Float32 logits [N, S, V] of `tokens` [N, S]; one layer's float32 weights live at a time."""
    tokens = jnp.asarray(tokens, jnp.int32)
    key = seed_key(seed)

    @functools.partial(jax.jit, static_argnums=(0,))
    def one_layer(layer, x, key):
        w = reference_layer(shape, key, layer, precision)
        return jax.lax.map(lambda row: block_forward(row, w, shape.kinds[layer], shape), x)

    @jax.jit
    def head(x, lm_head):
        return jax.lax.map(lambda row: head_logits(row, jnp.ones((shape.n_embd,), jnp.float32), lm_head, shape), x)

    wte = jax.jit(lambda key: _as_precision("wte", embedding(shape, key), precision))(key)
    x = jnp.take(wte, tokens, axis=0)
    for layer in range(shape.n_layer):
        x = one_layer(layer, x, key)
    return head(x, jax.jit(lambda key: _as_precision("lm_head", head_matrix(shape, key), precision))(key))


# ------------------------------------------------------------------ loss and gradients, the whole model at once


def reference_params(shape: MoEMLAShape, key, precision: str = "f32") -> dict:
    """All weights, float32: `{"runs": [a run's layers stacked on a leading axis, ...],
    "wte", "lm_head", "final_norm"}`. Traceable."""
    runs = []
    for kind, first, length in shape.runs:
        stacked = run_weights(shape, key, first, length, kind)
        runs.append({name: jax.vmap(lambda w, name=name: _as_precision(name, w, precision))(value)
                     for name, value in stacked.items()})
    return {"runs": runs, "wte": _as_precision("wte", embedding(shape, key), precision),
            "lm_head": _as_precision("lm_head", head_matrix(shape, key), precision),
            "final_norm": jnp.ones((shape.n_embd,), jnp.float32)}


def head_loss_sum(x, outer, targets, shape: MoEMLAShape):
    """Sum of the cross entropy over the positions of one sequence, from x [S, E] after the
    last layer; `outer` holds `wte`, `lm_head` and `final_norm`. In blocks of positions, each rematerialized."""
    s = x.shape[0]
    block = min(HEAD_BLOCK, s)
    pad = (-s) % block
    xp = jnp.pad(x, ((0, pad), (0, 0))).reshape(-1, block, x.shape[-1])
    tp = jnp.pad(targets, (0, pad)).reshape(-1, block)
    valid = (jnp.arange(s + pad) < s).reshape(-1, block)

    @jax.checkpoint
    def one_block(args):
        xb, tb, vb = args
        logits = head_logits(xb, outer["final_norm"], outer["lm_head"], shape)
        nll = jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(logits, tb[:, None], axis=-1)[:, 0]
        return jnp.sum(jnp.where(vb, nll, 0.0))

    return jnp.sum(jax.lax.map(one_block, (xp, tp, valid)))


def sequence_loss_sum(params, tokens, targets, shape: MoEMLAShape):
    """Sum of the cross entropy over the positions of one sequence."""
    x = jnp.take(params["wte"], tokens, axis=0)
    for (kind, _, _), stacked in zip(shape.runs, params["runs"]):
        x, _ = jax.lax.scan(lambda x, w, kind=kind: (block_forward(x, w, kind, shape), None), x, stacked)
    return head_loss_sum(x, params, targets, shape)


def batch_loss(params, tokens, targets, shape: MoEMLAShape):
    """Mean cross entropy over every position of every row. tokens/targets [B, S]."""
    sums = jax.lax.map(
        jax.checkpoint(lambda row: sequence_loss_sum(params, row[0], row[1], shape)), (tokens, targets)
    )
    return jnp.sum(sums) / (tokens.shape[0] * tokens.shape[1])


# ------------------------------------------------------------------ the same loss and gradients, one layer at a time; AdamW


@functools.lru_cache(maxsize=None)
def _layer_programs(shape: MoEMLAShape, kind: str):
    """One layer of kind `kind` on rows x [B, S, E]: its forward pass, and its backward
    pass from the layer's input (the forward is computed again inside)."""
    forward = lambda w, x: jax.lax.map(lambda row: block_forward(row, w, kind, shape), x)  # noqa: E731

    def counting(w, x):
        y, load = jax.lax.map(lambda row: block_forward(row, w, kind, shape, count=True), x)
        return y, jnp.sum(load, axis=0)

    def backward(w, x, dy):
        _, pull = jax.vjp(forward, w, x)
        return pull(dy)

    return jax.jit(counting), jax.jit(backward, donate_argnums=(2,))


@functools.lru_cache(maxsize=None)
def _outer_programs(shape: MoEMLAShape):
    def head_loss(x, outer, targets):
        """Mean cross entropy of rows x [B, S, E] after the last layer."""
        sums = jax.lax.map(lambda row: head_loss_sum(row[0], outer, row[1], shape), (x, targets))
        return jnp.sum(sums) / (x.shape[0] * x.shape[1])

    embed = jax.jit(lambda wte, tokens: jnp.take(wte, tokens, axis=0))
    head = jax.jit(jax.value_and_grad(head_loss, argnums=(0, 1)))
    # the table's gradient: what the head gave it plus the rows the embedding read
    add_embedding = jax.jit(lambda dwte, tokens, dx: dwte.at[tokens].add(dx), donate_argnums=(0,))
    return embed, head, add_embedding


def gradient_stream(shape: MoEMLAShape, layer_of, outer, tokens, targets):
    """The loss of a batch and then its gradient, layer by layer. A generator: first the
    loss with the pairs each expert layer's E experts got (`[expert layers, E]`, on the host), then `(i, gradient of layer i's leaves)` for i from the last layer to the first,
    then `("outer", gradient of wte, lm_head and final_norm)`. `layer_of(i)` gives layer i's leaves;
    the forward pass keeps every layer's input, and nothing else of a layer."""
    embed, head, add_embedding = _outer_programs(shape)
    tokens, targets = jnp.asarray(tokens, jnp.int32), jnp.asarray(targets, jnp.int32)
    inputs, loads = [embed(outer["wte"], tokens)], []
    for i, kind in enumerate(shape.kinds):
        y, load = _layer_programs(shape, kind)[0](layer_of(i), inputs[-1])
        inputs.append(y)
        loads.extend([load] if kind == "moe" else [])
    loss, (dx, d_outer) = head(inputs.pop(), outer, targets)
    yield loss, np.asarray(jax.device_get(loads), np.float64).reshape(len(loads), shape.n_routed_experts)
    for i in reversed(range(shape.n_layer)):
        dw, dx = _layer_programs(shape, shape.kinds[i])[1](layer_of(i), inputs.pop(), dx)
        yield i, dw
    d_outer["wte"] = add_embedding(d_outer["wte"], tokens, dx)
    yield "outer", d_outer


def pairs_held(shape: MoEMLAShape, loads) -> float:
    """What the program's counter `moe_pairs_held` counts: the pairs the held experts got, the mean over the expert layers."""
    return float(loads[:, shape.expert_offset: shape.expert_offset + shape.experts_held].sum(axis=1).mean()) if len(loads) else 0.0


def loss_and_gradients(shape: MoEMLAShape, layers: list, outer: dict, tokens, targets):
    """Mean cross entropy over every position of every row, its gradient as `(list of a
    layer's leaves, {"wte", "lm_head", "final_norm"})`, and the pairs every expert got (`[expert layers, E]`)."""
    stream = gradient_stream(shape, layers.__getitem__, outer, tokens, targets)
    loss, loads = next(stream)
    grads = dict(stream)
    return loss, ([grads[i] for i in range(shape.n_layer)], grads["outer"]), loads


def by_run(shape: MoEMLAShape, per_layer: list, outer: dict, stack=jnp.stack) -> dict:
    """Per-layer trees and the outer leaves as the comparison names them: `run<r>.<leaf>`
    stacked over the run's layers, `wte`, `lm_head`, `final_norm`."""
    out = {f"run{r}.{name}": stack([per_layer[first + k][name] for k in range(length)])
           for r, (_, first, length) in enumerate(shape.runs) for name in per_layer[first]}
    out.update(outer)
    return out


def _squares(tree):
    return jax.tree.map(lambda v: jnp.sum(v.astype(jnp.float32) ** 2), tree)


def leaf_norms(tree) -> dict:
    """Euclidean norm of every leaf of a tree in the run-stacked layout (`{"runs": [a
    run's leaves stacked on a leading axis, ...], "wte", "lm_head", "final_norm"}`): a run's leaf
    gives one norm per layer. Traceable (the program's side of the comparison uses it)."""
    out = {}
    for r, run in enumerate(tree["runs"]):
        for name, value in run.items():
            out[f"run{r}.{name}"] = jnp.sqrt(jnp.sum(value.astype(jnp.float32) ** 2, axis=tuple(range(1, value.ndim))))
    for name in OUTER:
        out[name] = jnp.sqrt(jnp.sum(tree[name].astype(jnp.float32) ** 2))
    return out


def train_steps(shape: MoEMLAShape, seed: int, batches, hyper: dict, precision: str = "f32",
                other_first_grad=None, other_scale: float = 1.0, keep_first_grad: bool = False, log=None) -> dict:
    """Follow the first `len(batches)` optimizer steps from the seeded weights.

    `batches` is a list of (tokens [B, S], targets [B, S]); `hyper` holds `lr` (a list,
    one learning rate per step), `b1`, `b2`, `eps`, `weight_decay`, `clip_norm`. AdamW as
    the configuration's optimizer block describes it: global-norm clipping, bias-corrected
    moments, decoupled decay scaled by the learning rate, no decay on NOT_DECAYED.

    Memory. The parameters and one gradient fill the chip at the cell's size, so Adam's
    moments are never kept there: m_t = (1 - b1) sum_j b1^(t-j) g_j and v_t = (1 - b2) sum_j
    b2^(t-j) g_j^2, and each earlier clipped gradient g_j waits on the host (4.1 GB a step
    at the cell's size, float32 as it was computed) and comes back a layer at a time beside
    the update that needs it. Two steps so cost two gradients and one round trip of the first.

    Returns the loss of each step, the pairs an expert layer's held experts got in each
    step (the mean over the expert layers: what the program's counter `moe_pairs_held` counts), the norm of each leaf of the first clipped gradient,
    and the norm of each leaf of the parameters' change after the last step. With
    `other_first_grad` (someone else's first gradient as their optimizer got it, host
    arrays in the run-stacked layout, to be multiplied by `other_scale`: Adam's first
    moment after one step is (1 - b1) times the gradient) also the norm of each leaf of
    its difference from this one; with `keep_first_grad` this first gradient itself, on
    the host, in that layout. `log` is called with a line at each stage."""
    key = seed_key(seed)
    t0 = time.perf_counter()
    say = (lambda what: log(f"[reference] {time.perf_counter() - t0:7.2f} s {what}")) if log else (lambda what: None)
    b1, b2, steps, n = hyper["b1"], hyper["b2"], len(batches), shape.n_layer
    seeded = {kind: jax.jit(lambda key, i, kind=kind: {
        name: _as_precision(name, value, precision) for name, value in layer_weights(shape, key, i, kind).items()})
        for kind in set(shape.kinds)}
    seeded_layer = lambda i: seeded[shape.kinds[i]](key, jnp.int32(i))  # noqa: E731
    seeded_table = jax.jit(lambda key: {"wte": _as_precision("wte", embedding(shape, key), precision),
                                        "lm_head": _as_precision("lm_head", head_matrix(shape, key), precision)})
    seeded_outer = lambda: {**seeded_table(key), "final_norm": jnp.ones((shape.n_embd,), jnp.float32)}  # noqa: E731
    scale_tree = jax.jit(lambda tree, factor: jax.tree.map(lambda g: g * factor, tree), donate_argnums=(0,))
    squares = jax.jit(_squares)
    difference = jax.jit(lambda ours, theirs: _squares(jax.tree.map(lambda a, b: a - other_scale * b.astype(jnp.float32), ours, theirs)))

    def one_leaf(name, p, gs, lr, t):
        m = (1 - b1) * sum(b1 ** (len(gs) - 1 - j) * g for j, g in enumerate(gs))
        v = (1 - b2) * sum(b2 ** (len(gs) - 1 - j) * g * g for j, g in enumerate(gs))
        step = (m / (1 - b1 ** t)) / (jnp.sqrt(v / (1 - b2 ** t)) + hyper["eps"])
        return p - lr * (step + hyper["weight_decay"] * p if name not in NOT_DECAYED else step)

    update = jax.jit(lambda tree, gs, lr, t: {name: one_leaf(name, p, [g[name] for g in gs], lr, t) for name, p in tree.items()},
                     donate_argnums=(0,))

    layers, outer = [seeded_layer(i) for i in range(n)], seeded_outer()
    say("the seeded weights")
    losses, held, extra = [], [], {}
    kept: list[tuple[list, dict]] = []  # the clipped gradients of the steps before, on the host: (a layer's leaves each, the outer leaves)
    expert_layers = [i for i, kind in enumerate(shape.kinds) if kind == "moe"]
    move_bias = jax.jit(lambda bias, load: moved_bias(bias, load, shape.bias_update_speed))
    first_squares = None
    for t, (tokens, targets) in enumerate(batches, start=1):
        loss, (grads, outer_grads), loads = loss_and_gradients(shape, layers, outer, tokens, targets)
        losses.append(float(loss))
        held.append(pairs_held(shape, loads))
        norm = float(np.sqrt(sum(float(v) for tree in (*grads, outer_grads) for v in squares(tree).values())))
        factor = min(1.0, hyper["clip_norm"] / max(norm, 1e-30))
        grads, outer_grads = [scale_tree(g, factor) for g in grads], scale_tree(outer_grads, factor)
        say(f"step {t}: loss and gradients")
        if t == 1:
            first_squares = ([jax.device_get(squares(g)) for g in grads], jax.device_get(squares(outer_grads)))
            if other_first_grad is not None:
                theirs = [{name: other_first_grad["runs"][r][name][k] for name in grads[first + k]}
                          for r, (_, first, length) in enumerate(shape.runs) for k in range(length)]
                gaps = [jax.device_get(difference(g, their)) for g, their in zip(grads, theirs)]
                outer_gaps = jax.device_get(difference(outer_grads, {name: other_first_grad[name] for name in outer_grads}))
                extra["first_grad_difference_norms"] = {name: np.sqrt(value) for name, value in by_run(shape, gaps, outer_gaps, np.stack).items()}
                say("the other first gradient measured against this one")
            if keep_first_grad:
                host = by_run(shape, jax.device_get(grads), jax.device_get(outer_grads), np.stack)
                extra["first_grad"] = {"runs": [{name[len(f"run{r}."):]: v for name, v in host.items() if name.startswith(f"run{r}.")}
                                                for r in range(len(shape.runs))], **{name: host[name] for name in OUTER}}
        lr, tt = jnp.float32(hyper["lr"][t - 1]), jnp.float32(t)
        waits = t < steps  # a later step's update needs this gradient again
        host_layers = [None] * n
        for i in reversed(range(n)):
            if waits:
                host_layers[i] = jax.device_get(grads[i])
            layers[i] = update(layers[i], [*(earlier[0][i] for earlier in kept), grads[i]], lr, tt)
            if shape.kinds[i] == "moe" and shape.bias_update_speed:  # AdamW left `b` where it was: its gradient is zero
                load = jnp.asarray(loads[expert_layers.index(i)], jnp.float32)
                layers[i] = {**layers[i], "router_bias": move_bias(layers[i]["router_bias"], load)}
            grads[i] = None
        host_outer = jax.device_get(outer_grads) if waits else None
        outer = update(outer, [*(earlier[1] for earlier in kept), outer_grads], lr, tt)
        if waits:
            kept.append((host_layers, host_outer))
        del grads, outer_grads
        say(f"step {t}: update" + (f", with the gradients of {t - 1} earlier step(s) from the host" if t > 1 else ""))
    kept.clear()

    change = jax.jit(lambda now, then: _squares(jax.tree.map(lambda a, b: a - b, now, then)))
    moved = [jax.device_get(change(layers[i], seeded_layer(i))) for i in range(n)]
    moved_outer = jax.device_get(change(outer, seeded_outer()))
    say("the parameters' change")
    root = lambda named: {name: np.sqrt(value) for name, value in named.items()}  # noqa: E731
    return {"losses": losses, "pairs_held": held, "first_grad_norms": root(by_run(shape, *first_squares, np.stack)),
            "delta_norms": root(by_run(shape, moved, moved_outer, np.stack)), **extra}
