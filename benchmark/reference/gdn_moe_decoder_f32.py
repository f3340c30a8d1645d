"""The plain reference of the gated-delta-rule / gated-attention / expert-layer decoder (`model_type:
qwen3_next`; the equations of ISSUE 44, written from the source's `config.json` and `transformers`'
`modeling_qwen3_next.py`): forward pass, loss, gradients and AdamW in straightforward `jax.numpy`,
float32, every matmul under precision `highest`. No kernels, no cache, no dispatch, no chunked rule, no
scan over layers, and no import of the program under test: its weights come from
`benchmark/weights_gdn_moe.py`. What is not this model's own (the causal softmax in blocks of rows and
heads, rotate-half, the SwiGLU, the routed experts one at a time, the balance term, the head's loss in
blocks, norms of leaves) is `benchmark/reference/swa_moe_decoder_f32.py`'s, imported.

Architecture. Token embedding, then layers that are each `h = x + Mixer_kind(N0(x))`, `y = h + Experts(N0(h))`;
final `N0`, an untied head, mean cross entropy over all positions, plus `router_aux_loss_coef` times the mean
over the layers of a layer's balance term. `N0(x) = x / sqrt(mean(x^2) + eps) * (1 + w)`, `w` from 0 (zero-centred:
the block's two norms, the norms on q and k, the final norm). No bias anywhere.

The gated delta rule's mixer (a `linear_attention` layer, "gdn" here), on `h [S, d]`; `n_k` key heads, `n_v` value
heads, `r = n_v / n_k`, `d_k`, `d_v`:

    u  = h W_qkvz   [S, n_k, 2 d_k + 2 r d_v]: a key head's group is [q d_k | k d_k | v r x d_v | z r x d_v]
    ba = h W_ba     [S, n_k, 2 r]: a group is [b r | a r]
    c  = silu(conv(concat(q, k, v)))   flattened over heads, concatenated in that order; depthwise, causal (zeros before
                                       t = 0), `taps` taps, the last weighing the current position, no bias; split back
    beta = sigmoid(b)      g = -exp(A_log) softplus(a + dt_bias)         [S, n_v]
    q, k repeated r times (value head j reads key head j // r)
    q = q / sqrt(sum(q^2) + 1e-6) / sqrt(d_k)       k = k / sqrt(sum(k^2) + 1e-6)
    per value head, S_0 = 0 [d_k, d_v], for t = 0 .. S-1:
        S = exp(g_t) S;  delta = beta_t (v_t - S^T k_t);  S = S + k_t delta^T;  o_t = S^T q_t
    y  = o / sqrt(mean(o^2) + eps) * w_n * silu(z)                       w_n [d_v] from 1 (not zero-centred)
    out = flatten(y) W_o

**The rule is the per-token recurrence, not the chunked form the program runs**: a `lax.scan` over positions
inside a `lax.scan` over blocks of `RULE_BLOCK` positions, each block rematerialized, so that a row of 16,384
keeps one `[n_v, d_k, d_v]` state (2 MiB at the source's sizes) a block and a block's own while its backward runs.

Gated attention (a `full_attention` layer, "attn"), `Hq` query heads on `Hkv` key/value heads of `D`:

    qg = h W_q  [S, Hq, 2 D]: a head's [query D | gate D];  k = h W_k, v = h W_v  [S, Hkv, D]
    q = N0_D(query), k = N0_D(k)          one [D] leaf each for all heads
    q, k rotated on channels 0 .. R-1 of a head (rotate-half within them, R/2 frequencies theta^(-2n/R)), the rest passed
    o = softmax(q k^T / sqrt(D), causal) v, query head h on key head h // (Hq / Hkv)
    out = (flatten(o) * sigmoid(flatten(gate))) W_o

Expert layer, on `x [T, d]`: `p = softmax(x W_r)` over all E; the k largest, `w_e = p_e / sum over the chosen`;
`y = sum over the chosen experts that are HELD of w_e E_e(x) + sigmoid(x w_g) E_s(x)`, `E(x) = W2 (silu(W x) * (V x))`;
`w_g [d, 1]`. The balance term of a layer is `E sum_e f_e P_e` over all E experts, held or not.

Departures from ISSUE 44's equations: none. Left open by the public keys and set by the issue (`meta.json`,
`assumed`): the balance term (per layer, as above); no multi-token-prediction module; initial values (`A_log =
log(a)`, `a` uniform on [1, 16]; `dt_bias` 1; `w_n` 1; zero-centred `w` 0; matrices normal 0.02; the taps uniform on
(-1/2, 1/2), torch's default, which the issue does not name: `benchmark/weights_gdn_moe.py` says why); no bias.

`precision`: "f32" is the reference; "int8" rounds every kernel the program keeps in bfloat16 to 8 bits (symmetric,
one scale per output channel) before use: the control. `skip` names steps of the equations left out, one program
with a fault each, which `correct` must fail (`benchmark/tools/control_gdn_moe.py --variant`; a vector of flags and an
argument of the compiled programs, `skip_flags`, so that all the faulty programs and the sound one are compiled once): `decay` (g = 0),
`beta` (beta = 1), `qk_l2norm`, `conv_silu`, `attn_gate`, `partial_rotary` (the whole head turned), `shared_gate`.
"""

from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.swa_moe_decoder_f32 import (HIGHEST, _squares, attention_core, balance_term, by_run, fake_quant_int8, head_loss,
                                                     leaf_norms, pairs_held, rotate, swiglu)
from benchmark.reference.swa_moe_decoder_f32 import expert_layer as routed_experts  # noqa: F401  (the router, the held experts, the loads)
from benchmark.weights_gdn_moe import GdnMoEShape, embedding, head as head_matrix, layer_weights, run_weights, seed_key

RULE_BLOCK = 128  # positions a rematerialized block of the recurrence holds
SKIPS = ("decay", "beta", "qk_l2norm", "conv_silu", "attn_gate", "partial_rotary", "shared_gate")


def skip_flags(*names: str):
    """Which steps of the equations are left out, as a float32 vector over `SKIPS` (1: left out). A traced ARGUMENT of every
    program below, not a static one: the sound reference and each faulty one are ONE compiled program a kind of layer."""
    unknown = set(names) - set(SKIPS)
    if unknown:
        raise ValueError(f"no such step to leave out: {sorted(unknown)} (known: {SKIPS})")
    return np.asarray([1.0 if name in names else 0.0 for name in SKIPS], np.float32)


NONE = skip_flags()


def _unless(skip, name: str, kept, left_out):
    """`kept`, or `left_out` where step `name` is left out."""
    return jnp.where(skip[SKIPS.index(name)] > 0, left_out, kept)

# which axes of each kernel are summed over where it is used (the others are output channels)
CONTRACT_AXES = {"qkvz": (0,), "ba": (0,), "out_proj": (0, 1), "q_attn": (0,), "k_attn": (0,), "v_attn": (0,), "c_proj": (0, 1),
                 "experts_W": (1,), "experts_V": (1,), "experts_W_2": (1,), "shared_W": (0,), "shared_V": (0,), "shared_W_2": (0,),
                 "shared_gate": (0,), "wte": (1,), "lm_head": (0,)}
# what AdamW does not decay: the configuration's `weight_decay_groups_excluded: [embedding, norm, gdn_vectors, shared_expert_gate]`
NOT_DECAYED = ("attention_norm", "ffn_norm", "final_norm", "wte", "q_norm", "k_norm", "out_norm", "A_log", "dt_bias", "conv", "shared_gate")
OUTER = ("wte", "lm_head", "final_norm")


def _as_precision(name: str, w, precision: str):
    w = w.astype(jnp.float32)
    if precision == "f32" or name not in CONTRACT_AXES:
        return w
    if precision == "int8":
        return fake_quant_int8(w, CONTRACT_AXES[name])
    raise ValueError(f"unknown precision {precision!r}")


# ------------------------------------------------------------------ the layers


def norm0(x, w, eps):
    """The zero-centred RMS norm: the leaf is `w` of `1 + w`."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * (1.0 + w)


def depthwise_conv(x, taps):
    """x `[S, C]`, taps `[K, C]`: `y_t = sum_j taps[j] x_{t - (K - 1) + j}`, zeros before the row starts."""
    k, s = taps.shape[0], x.shape[0]
    padded = jnp.pad(x, ((k - 1, 0), (0, 0)))
    return sum(padded[j: j + s] * taps[j] for j in range(k))


def delta_rule(q, k, v, g, beta):
    """The recurrence, position by position. q, k `[S, H, d_k]` (already repeated to the value heads), v `[S, H, d_v]`,
    g, beta `[S, H]` -> o `[S, H, d_v]`. Blocks of `RULE_BLOCK` positions, each rematerialized."""
    s, h, dk = q.shape
    dv = v.shape[-1]
    block = min(RULE_BLOCK, s)
    pad = -s % block

    def blocks(a):  # padding positions change nothing: no key, no correction, no decay
        return jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1)).reshape(-1, block, *a.shape[1:])

    def position(state, at):
        q_t, k_t, v_t, g_t, beta_t = at
        state = state * jnp.exp(g_t)[:, None, None]
        delta = beta_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", state, k_t, precision=HIGHEST))
        state = state + k_t[:, :, None] * delta[:, None, :]
        return state, jnp.einsum("hkv,hk->hv", state, q_t, precision=HIGHEST)

    @jax.checkpoint
    def one_block(state, xs):
        return jax.lax.scan(position, state, xs)

    _, out = jax.lax.scan(one_block, jnp.zeros((h, dk, dv), jnp.float32), tuple(blocks(a) for a in (q, k, v, g, beta)))
    return out.reshape(-1, h, dv)[:s]


def gdn_parts(h, w, shape: GdnMoEShape, skip=NONE) -> dict:
    """Every step of the rule's mixer on `h [S, d]`, by name: what the tests hold the program's own steps against."""
    s = h.shape[0]
    nk, nv, dk, dv = shape.key_heads, shape.value_heads, shape.key_dim, shape.value_dim
    r = nv // nk
    u = jnp.einsum("se,ehw->shw", h, w["qkvz"], precision=HIGHEST)
    ba = jnp.einsum("se,ehw->shw", h, w["ba"], precision=HIGHEST)
    q, k = u[..., :dk], u[..., dk: 2 * dk]
    v, z = u[..., 2 * dk: 2 * dk + r * dv].reshape(s, nv, dv), u[..., 2 * dk + r * dv:].reshape(s, nv, dv)
    mixed = depthwise_conv(jnp.concatenate([q.reshape(s, -1), k.reshape(s, -1), v.reshape(s, -1)], axis=-1), w["conv"])
    mixed = _unless(skip, "conv_silu", jax.nn.silu(mixed), mixed)
    q, k = mixed[:, : nk * dk].reshape(s, nk, dk), mixed[:, nk * dk: 2 * nk * dk].reshape(s, nk, dk)
    v = mixed[:, 2 * nk * dk:].reshape(s, nv, dv)
    beta = _unless(skip, "beta", jax.nn.sigmoid(ba[..., :r].reshape(s, nv)), 1.0)
    g = _unless(skip, "decay", -jnp.exp(w["A_log"]) * jax.nn.softplus(ba[..., r:].reshape(s, nv) + w["dt_bias"]), 0.0)
    q, k = jnp.repeat(q, r, axis=1), jnp.repeat(k, r, axis=1)  # value head j reads key head j // r
    q = _unless(skip, "qk_l2norm", q * jax.lax.rsqrt(jnp.sum(q * q, axis=-1, keepdims=True) + 1e-6), q)
    k = _unless(skip, "qk_l2norm", k * jax.lax.rsqrt(jnp.sum(k * k, axis=-1, keepdims=True) + 1e-6), k)
    q = q / np.sqrt(dk)
    o = delta_rule(q, k, v, g, beta)
    y = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + shape.norm_eps) * w["out_norm"] * jax.nn.silu(z)
    return {"q": q, "k": k, "v": v, "z": z, "beta": beta, "g": g, "o": o, "y": y,
            "out": jnp.einsum("shd,hde->se", y, w["out_proj"], precision=HIGHEST)}


def gdn_mixer(h, w, shape: GdnMoEShape, skip=NONE):
    return gdn_parts(h, w, shape, skip)["out"]


def rotary_tables(seq: int, rotated: int, theta: float):
    """cos and sin `[S, R]` of the `R` channels the rotary turns, the R/2 angles twice (rotate-half)."""
    inv_freq = 1.0 / (theta ** (jnp.arange(0, rotated, 2, dtype=jnp.float32) / rotated))
    angle = jnp.arange(seq, dtype=jnp.float32)[:, None] * inv_freq
    angle = jnp.concatenate([angle, angle], axis=-1)
    return jnp.cos(angle), jnp.sin(angle)


def rotate_part(x, cos, sin):
    """x [S, H, D] with its first `cos.shape[-1]` channels turned and the rest passed."""
    rotated = cos.shape[-1]
    return jnp.concatenate([rotate(x[..., :rotated], cos, sin), x[..., rotated:]], axis=-1)


def gated_attention(h, w, shape: GdnMoEShape, skip=NONE):
    d = shape.head_dim
    qg = jnp.einsum("se,ehd->shd", h, w["q_attn"], precision=HIGHEST)
    q, gate = qg[..., :d], qg[..., d:]
    k = jnp.einsum("se,ehd->shd", h, w["k_attn"], precision=HIGHEST)
    v = jnp.einsum("se,ehd->shd", h, w["v_attn"], precision=HIGHEST)
    q, k = norm0(q, w["q_norm"], shape.norm_eps), norm0(k, w["k_norm"], shape.norm_eps)
    cos, sin = rotary_tables(h.shape[0], shape.rotary_dim, shape.rope_theta)
    whole = rotary_tables(h.shape[0], d, shape.rope_theta)  # the fault: the whole head turned, by its own D/2 frequencies
    q, k = (_unless(skip, "partial_rotary", rotate_part(a, cos, sin), rotate_part(a, *whole)) for a in (q, k))
    o = attention_core(q, k, v, None)
    o = _unless(skip, "attn_gate", o * jax.nn.sigmoid(gate), o)
    return jnp.einsum("shd,hde->se", o, w["c_proj"], precision=HIGHEST)


def shared_expert(x, w, skip=NONE):
    out = swiglu(x, w["shared_W"], w["shared_V"], w["shared_W_2"])
    return _unless(skip, "shared_gate", out * jax.nn.sigmoid(jnp.einsum("se,eo->so", x, w["shared_gate"], precision=HIGHEST)), out)


def expert_layer(x, w, shape: GdnMoEShape, skip=NONE):
    """x [S, d]. The held experts' weighted sum and the gated shared expert; how many of the sequence's (token, choice)
    pairs each of the E experts got, held or not; and the sum over the sequence's tokens of each expert's score."""
    routed, load, score_sum = routed_experts(x, w, shape)
    return routed + shared_expert(x, w, skip), load, score_sum


def block_forward(x, w, kind: str, shape: GdnMoEShape, skip=NONE):
    """One pre-norm layer on one sequence. x [S, d]; w: the layer's leaves, float32. Returns the layer's output,
    the pairs each of the E experts got [E] and the sum of each expert's score over the sequence [E]."""

    @jax.checkpoint
    def mixer(x, w):
        h = norm0(x, w["attention_norm"], shape.norm_eps)
        return x + (gdn_mixer(h, w, shape, skip) if kind == "gdn" else gated_attention(h, w, shape, skip))

    @jax.checkpoint
    def ffn(x, w):
        out, load, score_sum = expert_layer(norm0(x, w["ffn_norm"], shape.norm_eps), w, shape, skip)
        return x + out, load, score_sum

    return ffn(mixer(x, w), w)


def layer_forward(w, x, kind: str, shape: GdnMoEShape, skip=NONE):
    """One layer on rows x [B, S, d]: its output, its balance term (over the B S tokens) and its pairs by expert [E]."""
    y, load, score_sum = jax.lax.map(lambda row: block_forward(row, w, kind, shape, skip), x)
    load = jnp.sum(load, axis=0)
    return y, balance_term(load, jnp.sum(score_sum, axis=0), x.shape[0] * x.shape[1], shape), load


def _head_loss(x, outer, targets, shape: GdnMoEShape):
    """Mean cross entropy of rows x [B, S, d] after the last layer: the shared blockwise loss, given the final norm's
    leaf as the `1 + w` it multiplies by."""
    return head_loss(x, {**outer, "final_norm": 1.0 + outer["final_norm"]}, targets, shape)


# ------------------------------------------------------------------ loss and gradients, the whole model at once


def reference_params(shape: GdnMoEShape, key, precision: str = "f32") -> dict:
    """All weights, float32: `{"runs": [a run's layers stacked on a leading axis, ...], "wte", "lm_head", "final_norm"}`. Traceable."""
    runs = []
    for kind, first, length in shape.runs:
        stacked = run_weights(shape, key, first, length, kind)
        runs.append({name: jax.vmap(lambda w, name=name: _as_precision(name, w, precision))(value) for name, value in stacked.items()})
    return {"runs": runs, "wte": _as_precision("wte", embedding(shape, key), precision),
            "lm_head": _as_precision("lm_head", head_matrix(shape, key), precision),
            "final_norm": jnp.zeros((shape.n_embd,), jnp.float32)}


def batch_loss(params, tokens, targets, shape: GdnMoEShape, with_parts: bool = False, skip=NONE):
    """Mean cross entropy over every position of every row plus `router_aux_loss_coef` times the mean over the
    layers of the balance term. tokens/targets [B, S]. With `with_parts` also (cross entropy, that mean, the pairs
    every layer's experts got [layers, E]). Layer after layer, written out: no scan over layers."""
    x = jnp.take(params["wte"], tokens, axis=0)
    terms, loads = [], []
    for (kind, _, length), stacked in zip(shape.runs, params["runs"]):
        for i in range(length):
            x, aux, load = layer_forward(jax.tree.map(lambda leaf, i=i: leaf[i], stacked), x, kind, shape, skip)
            terms.append(aux)
            loads.append(load)
    ce, aux = _head_loss(x, params, targets, shape), jnp.mean(jnp.stack(terms))
    loss = ce + shape.router_aux_loss_coef * aux
    return (loss, (ce, aux, jnp.stack(loads))) if with_parts else loss


# ------------------------------------------------------------------ the same loss and gradients, one layer at a time; AdamW


@functools.lru_cache(maxsize=None)
def _layer_programs(shape: GdnMoEShape, kind: str):
    """One layer of kind `kind` on rows x [B, S, d]: its forward pass (output, balance term, pairs by expert), and its
    backward pass from the layer's input and the cotangents of its output and of its balance term (the forward is computed
    again inside). `skip` (`skip_flags`) is an argument of both."""
    forward = lambda w, x, skip: layer_forward(w, x, kind, shape, skip)  # noqa: E731

    def backward(w, x, dy, daux, skip):
        _, pull = jax.vjp(lambda w, x: forward(w, x, skip)[:2], w, x)
        return pull((dy, daux))

    return jax.jit(forward), jax.jit(backward, donate_argnums=(2,))


@functools.lru_cache(maxsize=None)
def _outer_programs(shape: GdnMoEShape):
    embed = jax.jit(lambda wte, tokens: jnp.take(wte, tokens, axis=0))
    head = jax.jit(jax.value_and_grad(lambda x, outer, targets: _head_loss(x, outer, targets, shape), argnums=(0, 1)))
    # the table's gradient: what the head gave it plus the rows the embedding read
    add_embedding = jax.jit(lambda dwte, tokens, dx: dwte.at[tokens].add(dx), donate_argnums=(0,))
    return embed, head, add_embedding


def gradient_stream(shape: GdnMoEShape, layer_of, outer, tokens, targets, skip=NONE):
    """The loss of a batch and then its gradient, layer by layer. A generator: first `(loss, cross entropy, the mean
    balance term, the pairs every layer's E experts got [layers, E] on the host)`, then `(i, gradient of layer i's
    leaves)` for i from the last layer to the first, then `("outer", gradient of wte, lm_head and final_norm)`.
    `layer_of(i)` gives layer i's leaves; the forward pass keeps every layer's input, and nothing else of a layer."""
    embed, head, add_embedding = _outer_programs(shape)
    tokens, targets = jnp.asarray(tokens, jnp.int32), jnp.asarray(targets, jnp.int32)
    inputs, loads, terms = [embed(outer["wte"], tokens)], [], []
    for i, kind in enumerate(shape.kinds):
        y, aux, load = _layer_programs(shape, kind)[0](layer_of(i), inputs[-1], skip)
        inputs.append(y)
        loads.append(load)
        terms.append(aux)
    ce, (dx, d_outer) = head(inputs.pop(), outer, targets)
    aux = float(np.mean(jax.device_get(terms)))
    yield float(ce) + shape.router_aux_loss_coef * aux, float(ce), aux, np.asarray(jax.device_get(loads), np.float64)
    daux = jnp.float32(shape.router_aux_loss_coef / shape.n_layer)
    for i in reversed(range(shape.n_layer)):
        dw, dx = _layer_programs(shape, shape.kinds[i])[1](layer_of(i), inputs.pop(), dx, daux, skip)
        yield i, dw
    d_outer["wte"] = add_embedding(d_outer["wte"], tokens, dx)
    yield "outer", d_outer


def loss_and_gradients(shape: GdnMoEShape, layers: list, outer: dict, tokens, targets, skip=NONE):
    """The loss, its gradient as `(list of a layer's leaves, {"wte", "lm_head", "final_norm"})`, and `(cross entropy,
    the mean balance term, the pairs every expert got [layers, E])`."""
    stream = gradient_stream(shape, layers.__getitem__, outer, tokens, targets, skip)
    loss, *parts = next(stream)
    grads = dict(stream)
    return loss, ([grads[i] for i in range(shape.n_layer)], grads["outer"]), tuple(parts)


def first_mixer_output(shape: GdnMoEShape, seed: int, tokens, layer: int = 0):
    """What layer `layer`'s mixer (the rule's, for layer 0) adds to the residual on the first row of `tokens`, from the
    seeded weights and the embedded tokens as its input: `[S, d]`, for the distance by position the mode prints."""
    key = seed_key(seed)
    w = jax.jit(lambda key: {name: value.astype(jnp.float32) for name, value in layer_weights(shape, key, layer, shape.kinds[layer]).items()})(key)
    wte = jax.jit(lambda key: embedding(shape, key).astype(jnp.float32))(key)

    @jax.jit
    def mixer(w, wte, row):
        h = norm0(jnp.take(wte, row, axis=0), w["attention_norm"], shape.norm_eps)
        return gdn_mixer(h, w, shape) if shape.kinds[layer] == "gdn" else gated_attention(h, w, shape)

    return mixer(w, wte, jnp.asarray(tokens, jnp.int32)[0])


@functools.lru_cache(maxsize=None)
def _step_programs(shape: GdnMoEShape, precision: str, b1: float, b2: float, eps: float, weight_decay: float, other_scale: float):
    """The small programs `train_steps` runs beside a layer's two: the seeded leaves of a layer of each kind and of the tables,
    a tree scaled, its squares, its squared distance from another's, AdamW's update of a tree, the squares of a tree's change.
    Compiled once for a shape and a recipe, whatever is followed with them (the sound reference, the control, a faulty one)."""
    seeded = {kind: jax.jit(lambda key, i, kind=kind: {name: _as_precision(name, value, precision)
                                                       for name, value in layer_weights(shape, key, i, kind).items()}) for kind in set(shape.kinds)}
    seeded_table = jax.jit(lambda key: {"wte": _as_precision("wte", embedding(shape, key), precision),
                                        "lm_head": _as_precision("lm_head", head_matrix(shape, key), precision)})
    scale_tree = jax.jit(lambda tree, factor: jax.tree.map(lambda g: g * factor, tree), donate_argnums=(0,))
    squares = jax.jit(_squares)
    difference = jax.jit(lambda ours, theirs: _squares(jax.tree.map(lambda a, b: a - other_scale * b.astype(jnp.float32), ours, theirs)))

    def one_leaf(name, p, gs, lr, t):
        m = (1 - b1) * sum(b1 ** (len(gs) - 1 - j) * g for j, g in enumerate(gs))
        v = (1 - b2) * sum(b2 ** (len(gs) - 1 - j) * g * g for j, g in enumerate(gs))
        step = (m / (1 - b1 ** t)) / (jnp.sqrt(v / (1 - b2 ** t)) + eps)
        return p - lr * (step + weight_decay * p if name not in NOT_DECAYED else step)

    update = jax.jit(lambda tree, gs, lr, t: {name: one_leaf(name, p, [g[name] for g in gs], lr, t) for name, p in tree.items()},
                     donate_argnums=(0,))
    change = jax.jit(lambda now, then: _squares(jax.tree.map(lambda a, b: a - b, now, then)))
    return seeded, seeded_table, scale_tree, squares, difference, update, change


def train_steps(shape: GdnMoEShape, seed: int, batches, hyper: dict, precision: str = "f32", skip=(),
                other_first_grad=None, other_scale: float = 1.0, keep_first_grad: bool = False, log=None) -> dict:
    """Follow the first `len(batches)` optimizer steps from the seeded weights.

    `batches` is a list of (tokens [B, S], targets [B, S]); `hyper` holds `lr` (a list, one learning rate per step),
    `b1`, `b2`, `eps`, `weight_decay`, `clip_norm`. AdamW as the configuration's optimizer block describes it:
    global-norm clipping, bias-corrected moments, decoupled decay scaled by the learning rate, no decay on NOT_DECAYED.
    `precision` and `skip` (names of `SKIPS`) put another model in this one's place (the module docstring): the control's.

    Memory. Adam's moments are never kept on the device: m_t = (1 - b1) sum_j b1^(t-j) g_j and v_t = (1 - b2) sum_j
    b2^(t-j) g_j^2, and each earlier clipped gradient g_j waits on the host (float32 as it was computed) and comes
    back a layer at a time beside the update that needs it.

    Returns the loss of each step (with the balance term as the configuration weighs it), each step's cross entropy
    alone (`ce`), the norm of its whole gradient before clipping (`grad_norm`), its balance term (`aux_loss`: the mean
    over the layers, what the program's counter `moe_aux_loss` counts), the pairs the held experts got (`pairs_held`:
    the mean over the layers, the program's `moe_pairs_held`) and every layer's pairs by expert (`loads`), the norm of
    each leaf of the first clipped gradient, and the norm of each leaf of the parameters' change after the last step.
    With `other_first_grad` (someone else's first gradient as their optimizer got it, host arrays in the run-stacked
    layout, to be multiplied by `other_scale`) also the norm of each leaf of its difference from this one; with
    `keep_first_grad` this first gradient itself, on the host, in that layout. `log` is called with a line at each stage."""
    key = seed_key(seed)
    skip = skip_flags(*skip)
    t0 = time.perf_counter()
    say = (lambda what: log(f"[reference] {time.perf_counter() - t0:7.2f} s {what}")) if log else (lambda what: None)
    b1, b2, steps, n = hyper["b1"], hyper["b2"], len(batches), shape.n_layer
    seeded, seeded_table, scale_tree, squares, difference, update, change = _step_programs(
        shape, precision, b1, b2, hyper["eps"], hyper["weight_decay"], float(other_scale))
    seeded_layer = lambda i: seeded[shape.kinds[i]](key, jnp.int32(i))  # noqa: E731
    seeded_outer = lambda: {**seeded_table(key), "final_norm": jnp.zeros((shape.n_embd,), jnp.float32)}  # noqa: E731

    layers, outer = [seeded_layer(i) for i in range(n)], seeded_outer()
    say("the seeded weights")
    losses, ces, terms, held, all_loads, norms, extra = [], [], [], [], [], [], {}
    kept: list[tuple[list, dict]] = []  # the clipped gradients of the steps before, on the host: (a layer's leaves each, the outer leaves)
    first_squares = None
    for t, (tokens, targets) in enumerate(batches, start=1):
        loss, (grads, outer_grads), (ce, aux, loads) = loss_and_gradients(shape, layers, outer, tokens, targets, skip)
        losses.append(loss)
        ces.append(ce)
        terms.append(aux)
        held.append(pairs_held(shape, loads))
        all_loads.append(loads)
        norm = float(np.sqrt(sum(float(v) for tree in (*grads, outer_grads) for v in squares(tree).values())))
        norms.append(norm)
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
            grads[i] = None
        host_outer = jax.device_get(outer_grads) if waits else None
        outer = update(outer, [*(earlier[1] for earlier in kept), outer_grads], lr, tt)
        if waits:
            kept.append((host_layers, host_outer))
        del grads, outer_grads
        say(f"step {t}: update" + (f", with the gradients of {t - 1} earlier step(s) from the host" if t > 1 else ""))
    kept.clear()

    moved = [jax.device_get(change(layers[i], seeded_layer(i))) for i in range(n)]
    moved_outer = jax.device_get(change(outer, seeded_outer()))
    say("the parameters' change")
    root = lambda named: {name: np.sqrt(value) for name, value in named.items()}  # noqa: E731
    return {"losses": losses, "ce": ces, "aux_loss": terms, "pairs_held": held, "loads": all_loads, "grad_norm": norms,
            "first_grad_norms": root(by_run(shape, *first_squares, np.stack)),
            "delta_norms": root(by_run(shape, moved, moved_outer, np.stack)), **extra}
