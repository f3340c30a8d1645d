"""The plain reference of the looped decoder (`model_type: ouro`; ByteDance, Ouro LoopLM,
arXiv 2510.25741): forward pass, loss over the exits, gradients and AdamW in straightforward
`jax.numpy`, float32, every matmul under `jax.default_matmul_precision("highest")`. No
kernels, no cache, no batching tricks, and no import of the program under test: its weights
come from `benchmark/weights_looped.py`.

The equations, with `x` the token ids, `E` the embedding, `T = total_ut_steps`, `L` layers and
`N_k` RMSNorm (eps 1e-6, a scale each):

    h^(0) = E[x];  for t = 1..T:  u = h^(t-1);  for l = 1..L: u = Block_l(u);  h^(t) = N_f(u)
    Block(u):  a = Attn(N_1(u));  u' = u + N_2(a);  m = W_2(silu(W u'') * V u''), u'' = N_3(u');  out = u' + N_4(m)
    Attn: causal, one key/value head a query head, rotary on the whole head (rotate-half, theta 1e6), no bias, no QK norm
    exit t:  logits^(t) = W_head h^(t);  gate g_t = sigmoid(w_g . h^(t) + b_g)
    p_1 = g_1,  p_t = g_t prod_{j<t} (1 - g_j)  (t < T),  p_T = prod_{j<T} (1 - g_j)      (from log-sigmoids)
    loss = mean_i [ sum_t p_i(t) CE(logits_i^(t), y_i) - beta H(p_i) ],  H(p) = -sum_t p(t) log p(t)

`Block_l` and `N_f` are the same weights for every walk `t`; `h^(t)` is both exit `t`'s hidden
state and the next walk's input; no walk has an embedding or a parameter of its own, and
nothing is held out of the gradient: it flows through `p` and through every cross entropy.
`config.json` states `total_ut_steps`, `early_exit_threshold` and the widths; the four norms
of a block, the final norm inside the loop, the gate's form and the loss are the published
model file's and the paper's as the builder of PR 32 knew them without a network
(`benchmark/configs/ouro-2p6b-t4/meta.json`, `assumed`, `beta = 0.1` among them).

Departures from a textbook forward, all for memory and none for the numbers: attention in
blocks of query rows, the head and the loss in blocks of positions, rows of a batch one after
another, and the backward pass walked by hand a layer application at a time from the kept
inputs (64 of them at the cell's size), each layer's forward computed again inside its
`jax.vjp`, the gradient of a layer's weights summed over the walks as it comes.

`precision` selects the control of "How `correct` is decided": "f32" is the reference;
"int8" rounds every matmul weight to 8 bits (symmetric, one scale per output channel) before
use, the nearest precision below the bfloat16 the configuration states. The gate's vector
and the norm scales, float32 in the program, stay as they are.
"""

from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.weights_looped import NORMS, OUTER, LoopedShape, layer_weights, outer_weights, seed_key

HIGHEST = "highest"
Q_BLOCK = 512  # query rows per attention block
HEAD_BLOCK = 512  # positions per head/loss block: T exits' float32 logits of a block live at once

# which axes of each kernel are summed over in its matmul (the others are output channels)
CONTRACT_AXES = {"q_attn": (0,), "k_attn": (0,), "v_attn": (0,), "c_proj": (0, 1), "W": (0,), "V": (0,),
                 "W_2": (0,), "lm_head": (0,), "wte": (1,)}
# norm scales, the embedding and the gate are not decayed (`weight_decay_groups_excluded: [embedding, norm, exit_gate]`)
NOT_DECAYED = (*NORMS, "final_norm", "wte", "gate_w", "gate_b")


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


def rotary(x, positions, base):
    """x [S, H, D], positions [S]; rotate-half convention on the whole head."""
    d = x.shape[-1]
    inv_freq = 1.0 / (base ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angles = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    angles = jnp.concatenate([angles, angles], axis=-1)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return x * jnp.cos(angles) + jnp.concatenate([-x2, x1], axis=-1) * jnp.sin(angles)


def causal_attention(q, k, v):
    """q, k, v [S, H, D] -> [S, H, D]. Softmax in float32, in blocks of Q_BLOCK query rows."""
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
    return out.reshape(-1, h, d)[:s]


def block_forward(u, w, shape: LoopedShape):
    """One sandwich-norm block on one sequence. u [S, E]; w: the layer's seven kernels and four norm scales, float32."""
    positions = jnp.arange(u.shape[0])
    h = rms_norm(u, w["attention_norm"], shape.norm_eps)
    q = jnp.einsum("se,ehd->shd", h, w["q_attn"], precision=HIGHEST)
    k = jnp.einsum("se,ehd->shd", h, w["k_attn"], precision=HIGHEST)
    v = jnp.einsum("se,ehd->shd", h, w["v_attn"], precision=HIGHEST)
    q, k = rotary(q, positions, shape.rope_base), rotary(k, positions, shape.rope_base)
    a = jnp.einsum("shd,hde->se", causal_attention(q, k, v), w["c_proj"], precision=HIGHEST)
    u = u + rms_norm(a, w["post_attention_norm"], shape.norm_eps)
    h = rms_norm(u, w["ffn_norm"], shape.norm_eps)
    gate = jnp.einsum("se,ef->sf", h, w["W"], precision=HIGHEST)
    up = jnp.einsum("se,ef->sf", h, w["V"], precision=HIGHEST)
    m = jnp.einsum("sf,fe->se", jax.nn.silu(gate) * up, w["W_2"], precision=HIGHEST)
    return u + rms_norm(m, w["post_ffn_norm"], shape.norm_eps)


def gate_logits(h, outer):
    """The exit gate's logit of every position: h [..., E] -> [...]."""
    return jnp.einsum("...e,e->...", h, outer["gate_w"], precision=HIGHEST) + outer["gate_b"]


def exit_distribution(logits):
    """`(log p, p)` over the exits (axis 0) from the gates' logits `[T, ...]`, from log-sigmoids."""
    log_stay = jax.nn.log_sigmoid(-logits)
    log_reach = jnp.cumsum(log_stay, axis=0) - log_stay
    log_p = jnp.concatenate([log_reach[:-1] + jax.nn.log_sigmoid(logits[:-1]), log_reach[-1:]], axis=0)
    return log_p, jnp.exp(log_p)


def exit_terms(ce, logits, beta: float):
    """Per position, from every exit's cross entropy and gate logit `[T, S]`: the loss, the expected exit, the entropy."""
    log_p, p = exit_distribution(logits)
    entropy = -(p * log_p).sum(axis=0)
    steps = jnp.arange(1, ce.shape[0] + 1, dtype=jnp.float32)[:, None]
    return (p * ce).sum(axis=0) - beta * entropy, (p * steps).sum(axis=0), entropy


def head_sums(hs, outer, targets, shape: LoopedShape):
    """One sequence's exits `hs` [T, S, E] -> sums over its positions: the loss; every exit's cross entropy [T], the
    expected exit and the exit distribution's entropy (what the program's step counts)."""
    t, s, e = hs.shape
    block = min(HEAD_BLOCK, s)
    pad = (-s) % block
    hp = jnp.moveaxis(jnp.pad(hs, ((0, 0), (0, pad), (0, 0))).reshape(t, -1, block, e), 1, 0)
    tp = jnp.pad(targets, (0, pad)).reshape(-1, block)
    valid = (jnp.arange(s + pad) < s).reshape(-1, block)

    @jax.checkpoint
    def one_block(args):
        hb, tb, vb = args  # [T, block, E], [block], [block]
        logits = jnp.einsum("tse,ev->tsv", hb, outer["lm_head"], precision=HIGHEST)
        ce = jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(logits, tb[None, :, None], axis=-1)[..., 0]
        loss, expected, entropy = exit_terms(ce, gate_logits(hb, outer), shape.beta)
        keep = lambda rows: jnp.sum(jnp.where(vb, rows, 0.0), axis=-1)  # noqa: E731
        return keep(loss), keep(ce), keep(expected), keep(entropy)

    loss, ce, expected, entropy = jax.lax.map(one_block, (hp, tp, valid))
    return loss.sum(), (ce.sum(axis=0), expected.sum(), entropy.sum())


# ------------------------------------------------------------------ weights in a precision


def reference_layer(shape: LoopedShape, key, layer, precision: str = "f32") -> dict:
    """Layer `layer` of the seeded weights: the bfloat16 values the program is given, upcast (and, for the control, rounded to int8)."""
    return {name: _as_precision(name, value, precision) for name, value in layer_weights(shape, key, layer).items()}


def reference_outer(shape: LoopedShape, key, precision: str = "f32") -> dict:
    return {name: _as_precision(name, value, precision) for name, value in outer_weights(shape, key).items()}


# ------------------------------------------------------------------ one sequence, a layer application at a time


@functools.lru_cache(maxsize=None)
def _programs(shape: LoopedShape):
    """The jitted pieces, one compile each for every layer, walk and seed: the weights are arguments."""
    block = lambda w, u: block_forward(u, w, shape)  # noqa: E731
    close = lambda scale, u: rms_norm(u, scale, shape.norm_eps)  # noqa: E731

    def pull(fn):
        def back(w, u, dy):
            _, vjp = jax.vjp(fn, w, u)
            return vjp(dy)
        return jax.jit(back)

    def head(hs, outer, targets, weight):
        loss, counted = head_sums(hs, outer, targets, shape)
        return loss * weight, counted

    return {
        "embed": jax.jit(lambda wte, tokens: jnp.take(wte, tokens, axis=0)),
        "block": jax.jit(block), "block_back": pull(block), "close": jax.jit(close), "close_back": pull(close),
        # the gradient wrt the exits and wrt the head, the gate's vector and bias (the rest of `outer` gets zeros)
        "head": jax.jit(jax.value_and_grad(head, argnums=(0, 1), has_aux=True)),
        "add": jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b), donate_argnums=(0,)),
        "add_embedding": jax.jit(lambda dwte, tokens, dx: dwte.at[tokens].add(dx), donate_argnums=(0,)),
    }


def forward_exits(shape: LoopedShape, layers: list, outer: dict, tokens):
    """One sequence's walks: `(inputs [T][L] of every layer application, u^(t) before the closing norm [T], exits h^(t) [T])`."""
    run = _programs(shape)
    x = run["embed"](outer["wte"], jnp.asarray(tokens, jnp.int32))
    inputs, before, exits = [], [], []
    for _ in range(shape.total_ut_steps):
        walk = []
        for w in layers:
            walk.append(x)
            x = run["block"](w, x)
        inputs.append(walk)
        before.append(x)
        x = run["close"](outer["final_norm"], x)
        exits.append(x)
    return inputs, before, exits


def exits_of(shape: LoopedShape, seed: int, tokens, precision: str = "f32"):
    """Every exit's hidden state `[T, N, S, E]` and gate logit `[T, N, S]` of `tokens` [N, S]: what the tests hold the
    program's forward pass to (the logits are `hidden @ lm_head`)."""
    key = seed_key(seed)
    layers = [jax.jit(lambda key, i: reference_layer(shape, key, i, precision))(key, jnp.int32(i)) for i in range(shape.n_layer)]
    outer = jax.jit(lambda key: reference_outer(shape, key, precision))(key)
    hidden = jnp.stack([jnp.stack(forward_exits(shape, layers, outer, row)[2]) for row in tokens], axis=1)
    return hidden, gate_logits(hidden, outer), outer


def loss_and_gradients(shape: LoopedShape, layers: list, outer: dict, tokens, targets):
    """The loss over the exits, the mean over every position of every row, and its gradient as `(list of a layer's
    leaves, the outer leaves)`; with them the means the program's step counts: every exit's cross entropy [T], the
    expected exit, the exit distribution's entropy."""
    run = _programs(shape)
    rows, seq = np.shape(tokens)
    weight = jnp.float32(1.0 / (rows * seq))
    loss, counted, layer_grads, outer_grads = 0.0, None, None, None
    for row_tokens, row_targets in zip(jnp.asarray(tokens, jnp.int32), jnp.asarray(targets, jnp.int32)):
        inputs, before, exits = forward_exits(shape, layers, outer, row_tokens)
        (row_loss, row_counted), (d_exits, d_outer) = run["head"](jnp.stack(exits), outer, row_targets, weight)
        del exits
        dx = jnp.zeros_like(before[0])  # what the next walk's input hands back to this walk's exit
        d_layers = [None] * shape.n_layer
        for t in reversed(range(shape.total_ut_steps)):
            d_scale, du = run["close_back"](outer["final_norm"], before.pop(), d_exits[t] + dx)
            d_outer["final_norm"] = d_outer["final_norm"] + d_scale
            walk = inputs.pop()
            for l in reversed(range(shape.n_layer)):
                dw, du = run["block_back"](layers[l], walk.pop(), du)
                d_layers[l] = dw if d_layers[l] is None else run["add"](d_layers[l], dw)  # one set of weights: the sum over the walks
            dx = du
        d_outer["wte"] = run["add_embedding"](d_outer["wte"], row_tokens, dx)
        loss = loss + float(row_loss)
        counted = row_counted if counted is None else jax.tree.map(jnp.add, counted, row_counted)
        layer_grads = d_layers if layer_grads is None else [run["add"](a, b) for a, b in zip(layer_grads, d_layers)]
        outer_grads = d_outer if outer_grads is None else run["add"](outer_grads, d_outer)
    exit_ce, expected, entropy = (np.asarray(c, np.float64) * float(weight) for c in counted)
    return loss, (layer_grads, outer_grads), {"exit_ce": exit_ce.tolist(), "expected_exit": float(expected), "gate_entropy": float(entropy)}


# ------------------------------------------------------------------ training: AdamW


def by_layer(per_layer: list, outer: dict, stack=jnp.stack) -> dict:
    """Per-layer trees and the outer leaves as the comparison names them: `layers.<leaf>` stacked over the layers, and the outer leaves."""
    out = {f"layers.{name}": stack([layer[name] for layer in per_layer]) for name in per_layer[0]}
    out.update(outer)
    return out


def _squares(tree):
    return jax.tree.map(lambda v: jnp.sum(v.astype(jnp.float32) ** 2), tree)


def leaf_norms(tree) -> dict:
    """Euclidean norm of every leaf of a tree in the reference's layout (`{"layers": {leaf: stacked on a leading
    axis}, ...the outer leaves}`): a stacked leaf gives one norm per layer. Traceable (the program's side uses it)."""
    out = {}
    for name, value in tree["layers"].items():
        out[f"layers.{name}"] = jnp.sqrt(jnp.sum(value.astype(jnp.float32) ** 2, axis=tuple(range(1, value.ndim))))
    for name in OUTER:
        out[name] = jnp.sqrt(jnp.sum(tree[name].astype(jnp.float32) ** 2))
    return out


def train_steps(shape: LoopedShape, seed: int, batches, hyper: dict, precision: str = "f32",
                other_first_grad=None, other_scale: float = 1.0, keep_first_grad: bool = False, log=None) -> dict:
    """Follow the first `len(batches)` optimizer steps from the seeded weights.

    `batches` is a list of (tokens [B, S], targets [B, S]); `hyper` holds `lr` (a list, one learning rate per step),
    `b1`, `b2`, `eps`, `weight_decay`, `clip_norm`. AdamW as the configuration's optimizer block describes it:
    global-norm clipping, bias-corrected moments, decoupled decay scaled by the learning rate, no decay on NOT_DECAYED.

    Memory. Float32 parameters, one gradient and the 64 kept inputs of a row's layer applications are 10 GB of a
    16 GB chip at the cell's size, so Adam's moments are never kept there: m_t = (1 - b1) sum_j b1^(t-j) g_j and
    v_t = (1 - b2) sum_j b2^(t-j) g_j^2, and each earlier clipped gradient g_j waits on the host (4.1 GB a step,
    float32 as it was computed) and comes back a layer at a time beside the update that needs it. Two steps so cost
    two gradients and one round trip of the first.

    Returns the loss of each step, each step's `exit_ce` (every exit's mean cross entropy) and `expected_exit`, the norm
    of each leaf of the first clipped gradient, and the norm of each leaf of the parameters' change after the last step.
    With `other_first_grad` (someone else's first gradient as their optimizer got it, host arrays in the reference's
    layout, to be multiplied by `other_scale`: Adam's first moment after one step is (1 - b1) times the gradient) also
    the norm of each leaf of its difference from this one; with `keep_first_grad` this first gradient itself, on the
    host, in that layout. `log` is called with a line at each stage."""
    key = seed_key(seed)
    t0 = time.perf_counter()
    say = (lambda what: log(f"[reference] {time.perf_counter() - t0:7.2f} s {what}")) if log else (lambda what: None)
    b1, b2, steps, n = hyper["b1"], hyper["b2"], len(batches), shape.n_layer
    seeded = jax.jit(lambda key, i: reference_layer(shape, key, i, precision))
    seeded_layer = lambda i: seeded(key, jnp.int32(i))  # noqa: E731
    seeded_outer = jax.jit(lambda key: reference_outer(shape, key, precision))
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

    layers, outer = [seeded_layer(i) for i in range(n)], seeded_outer(key)
    say("the seeded weights")
    losses, counted, extra = [], [], {}
    kept: list[tuple[list, dict]] = []  # the clipped gradients of the steps before, on the host: (a layer's leaves each, the outer leaves)
    first_squares = None
    for t, (tokens, targets) in enumerate(batches, start=1):
        loss, (grads, outer_grads), step_counted = loss_and_gradients(shape, layers, outer, tokens, targets)
        losses.append(float(loss))
        counted.append(step_counted)
        norm = float(np.sqrt(sum(float(v) for tree in (*grads, outer_grads) for v in squares(tree).values())))
        factor = min(1.0, hyper["clip_norm"] / max(norm, 1e-30))
        grads, outer_grads = [scale_tree(g, factor) for g in grads], scale_tree(outer_grads, factor)
        say(f"step {t}: loss {loss:.6f} and gradients (norm {norm:.4f})")
        if t == 1:
            first_squares = ([jax.device_get(squares(g)) for g in grads], jax.device_get(squares(outer_grads)))
            if other_first_grad is not None:
                theirs = [{name: other_first_grad["layers"][name][i] for name in grads[i]} for i in range(n)]
                gaps = [jax.device_get(difference(g, their)) for g, their in zip(grads, theirs)]
                outer_gaps = jax.device_get(difference(outer_grads, {name: other_first_grad[name] for name in outer_grads}))
                extra["first_grad_difference_norms"] = {name: np.sqrt(value) for name, value in by_layer(gaps, outer_gaps, np.stack).items()}
                say("the other first gradient measured against this one")
            if keep_first_grad:
                host = by_layer(jax.device_get(grads), jax.device_get(outer_grads), np.stack)
                extra["first_grad"] = {"layers": {name[len("layers."):]: v for name, v in host.items() if name.startswith("layers.")},
                                       **{name: host[name] for name in OUTER}}
        lr, tt = jnp.float32(hyper["lr"][t - 1]), jnp.float32(t)
        waits = t < steps  # a later step's update needs this gradient again
        host_layers = [None] * n
        for i in reversed(range(n)):
            if waits:
                host_layers[i] = jax.device_get(grads[i])
            layers[i] = update(layers[i], [*(earlier[0][i] for earlier in kept), grads[i]], lr, tt)
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
    moved_outer = jax.device_get(change(outer, seeded_outer(key)))
    say("the parameters' change")
    root = lambda named: {name: np.sqrt(value) for name, value in named.items()}  # noqa: E731
    return {"losses": losses, "exit_ce": [c["exit_ce"] for c in counted], "expected_exit": [c["expected_exit"] for c in counted],
            "gate_entropy": [c["gate_entropy"] for c in counted], "first_grad_norms": root(by_layer(*first_squares, np.stack)),
            "delta_norms": root(by_layer(moved, moved_outer, np.stack)), **extra}
