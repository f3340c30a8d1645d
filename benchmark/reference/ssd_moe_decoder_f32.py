"""The plain reference of the Mamba-2 / NoPE-attention / expert-layer decoder (`model_type: granitemoehybrid`; the
equations of ISSUE 52, written from the source's `config.json`, Dao and Gu's arXiv 2405.21060 and `transformers`'
`modeling_granitemoehybrid.py`): forward pass, loss, gradients and AdamW in straightforward `jax.numpy`, float32, every
matmul under precision `highest`. No kernels, no cache, no dispatch, no chunked form, no scan over layers, and no import
of the program under test: its weights come from `benchmark/weights_ssd_moe.py`. What is not this model's own (the
causal softmax in blocks of rows and heads, the SwiGLU, the balance term, the head's loss in blocks) is
`benchmark/reference/swa_moe_decoder_f32.py`'s, imported.

Architecture. `x_0 = embedding_multiplier * table[ids]`; per layer `x = x + residual_multiplier * Mixer_kind(N(x))`, then
`x = x + residual_multiplier * (routed(N(x)) + shared(N(x)))`; `logits = N(x) table^T / logits_scaling` (the head is the
table), mean cross entropy over all positions, plus `router_aux_loss_coef` times the mean over the layers of a layer's
balance term. `N(x) = x / sqrt(mean(x^2) + eps) * w`, `w` from 1. No bias but the convolution's.

The Mamba-2 mixer (a `mamba` layer, "ssd" here), on `h [S, d]`; `H` heads held of `P` channels, state `N`, `d_in = H P`:

    (z, xBC, dt) = split(h W_in) into d_in, d_in + 2 N, H
    xBC          = silu(conv(xBC) + b_conv)       depthwise, causal (zeros before t = 0), `taps` taps, the last weighing the current position
    (x, B, C)    = split(xBC) into d_in, N, N;  x read as [S, H, P];  B, C the same for every head
    dt           = softplus(dt + dt_bias);   a = -exp(A_log) * dt
    per head, h_{-1} = 0 [P, N], for t = 0 .. S-1:   h_t = exp(a_t) h_{t-1} + dt_t x_t B_t^T;   y_t = h_t C_t + D x_t
    g            = flatten(y) * silu(z)
    out          = (g / sqrt(mean(g^2 over the d_in held) + eps) * w_g) W_out

**The recurrence is walked position by position, not in the chunked form the program runs**: a `lax.scan` over positions
inside a `lax.scan` over blocks of `TIME_BLOCK` positions, each block rematerialized, so that a row of 8,192 keeps one
`[H, P, N]` state (1 MiB at the cell's sizes) a block and a block's own while its backward runs.

Attention (an `attention` layer, "attn"), `Hq` query heads on `Hkv` key/value heads of `D`, no positions:
`o = softmax(q k^T * attention_multiplier, causal) v`, `out = flatten(o) W_o`.

Expert layer, on `x [T, d]`: `l = x W_r` over all E; the k largest; gates = softmax over those k logits (which is the
softmax over all E renormalised over the chosen); `y = sum over the chosen experts that are HELD of gate_e E_e(x) + E_s(x)`,
`E(x) = W2 (silu(W x) * (V x))`, the shared expert `E_s` at the width held. The balance term of a layer is
`E sum_e f_e P_e` over all E experts, held or not, `P` the softmax over all E.

Departures from the published model, each also in the configuration's `meta.json`:
- **the share**: of every layer this holds `experts_held` of the routed experts, `heads_held` of the Mamba-2 heads, the
  attention's heads and the slice of the shared expert's width that the shape states, and of the table the rows it
  states; what the absent parts would add is left out, here as in the program.
- **the gated norm's mean square runs over the channels held** (`heads_held * P`), where the published norm runs over all
  of the inner width: a deployment all-reduces one scalar a token there, and that exchange is not run or imitated.
- **initial values** are Mamba-2's own draws (`benchmark/weights_ssd_moe.py`), where the source's module fills `dt_bias`
  with 1: a decay that is all but 0 or all but 1 would test nothing.
- `time_step_limit` (0, inf): no clamp on dt. Packed rows run state and attention across document boundaries.

`precision`: "f32" is the reference; "int8" rounds every kernel the program keeps in bfloat16 to 8 bits (symmetric, one
scale per output channel) before use: the control. `skip` names steps of the equations left out, one program with a
fault each, which `correct` must fail (`benchmark/tools/control_ssd_moe.py --variant`; a vector of flags and an argument
of the compiled programs, `skip_flags`, so that all the faulty programs and the sound one are compiled once): `decay`
(a = 0), `skip_d` (no `D x`), `conv_silu`, `gate` (no `silu(z)`), `gate_norm`, `dt_softplus`, `residual_multiplier` (1),
`attention_multiplier` (1 / sqrt(D)), `embedding_multiplier` (1), `logits_scaling` (1), `gate_renorm` (the softmax over
all E at the chosen, not renormalised).
"""

from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.swa_moe_decoder_f32 import (HIGHEST, _squares, attention_core, balance_term, by_run, fake_quant_int8, head_loss,
                                                     pairs_held, rms_norm, swiglu)
from benchmark.weights_ssd_moe import SsdMoEShape, embedding, layer_weights, run_weights, seed_key

TIME_BLOCK = 128  # positions a rematerialized block of the recurrence holds
SKIPS = ("decay", "skip_d", "conv_silu", "gate", "gate_norm", "dt_softplus", "residual_multiplier", "attention_multiplier",
         "embedding_multiplier", "logits_scaling", "gate_renorm")


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
CONTRACT_AXES = {"in_proj": (0,), "out_proj": (0,), "q_attn": (0,), "k_attn": (0,), "v_attn": (0,), "c_proj": (0, 1),
                 "experts_W": (1,), "experts_V": (1,), "experts_W_2": (1,), "shared_W": (0,), "shared_V": (0,), "shared_W_2": (0,), "wte": (1,)}
# what AdamW does not decay: the configuration's `weight_decay_groups_excluded: [embedding, norm, ssd_vectors]`
NOT_DECAYED = ("attention_norm", "ffn_norm", "final_norm", "wte", "conv", "conv_bias", "A_log", "D", "dt_bias", "gate_norm")
OUTER = ("wte", "final_norm")


def _as_precision(name: str, w, precision: str):
    w = w.astype(jnp.float32)
    if precision == "f32" or name not in CONTRACT_AXES:
        return w
    if precision == "int8":
        return fake_quant_int8(w, CONTRACT_AXES[name])
    raise ValueError(f"unknown precision {precision!r}")


def leaf_norms(tree) -> dict:
    """Euclidean norm of every leaf of a tree in the run-stacked layout (`{"runs": [a run's leaves stacked on a leading
    axis, ...], "wte", "final_norm"}`): a run's leaf gives one norm per layer. Traceable (the program's side of the comparison uses it)."""
    out = {}
    for r, run in enumerate(tree["runs"]):
        for name, value in run.items():
            out[f"run{r}.{name}"] = jnp.sqrt(jnp.sum(value.astype(jnp.float32) ** 2, axis=tuple(range(1, value.ndim))))
    for name in OUTER:
        out[name] = jnp.sqrt(jnp.sum(tree[name].astype(jnp.float32) ** 2))
    return out


# ------------------------------------------------------------------ the layers


def depthwise_conv(x, taps, bias):
    """x `[S, C]`, taps `[K, C]`, bias `[C]`: `y_t = bias + sum_j taps[j] x_{t - (K - 1) + j}`, zeros before the row starts."""
    k, s = taps.shape[0], x.shape[0]
    padded = jnp.pad(x, ((k - 1, 0), (0, 0)))
    return sum(padded[j: j + s] * taps[j] for j in range(k)) + bias


def recurrence(x, dt, a, b, c):
    """The walk, position by position. x `[S, H, P]`, dt and a `[S, H]`, b and c `[S, N]` -> y `[S, H, P]` (without the skip).
    Blocks of `TIME_BLOCK` positions, each rematerialized."""
    s, h, p = x.shape
    block = min(TIME_BLOCK, s)
    pad = -s % block

    def blocks(v):  # padding positions change nothing: no input, no decay
        return jnp.pad(v, ((0, pad),) + ((0, 0),) * (v.ndim - 1)).reshape(-1, block, *v.shape[1:])

    def position(state, at):
        x_t, dt_t, a_t, b_t, c_t = at
        state = jnp.exp(a_t)[:, None, None] * state + (dt_t[:, None] * x_t)[:, :, None] * b_t[None, None, :]
        return state, jnp.einsum("hpn,n->hp", state, c_t, precision=HIGHEST)

    @jax.checkpoint
    def one_block(state, xs):
        return jax.lax.scan(position, state, xs)

    _, out = jax.lax.scan(one_block, jnp.zeros((h, p, b.shape[-1]), jnp.float32), tuple(blocks(v) for v in (x, dt, a, b, c)))
    return out.reshape(-1, h, p)[:s]


def ssd_parts(h, w, shape: SsdMoEShape, skip=NONE) -> dict:
    """Every step of the Mamba-2 mixer on `h [S, d]`, by name: what the tests hold the program's own steps against."""
    s, heads, p, n, inner = h.shape[0], shape.heads_held, shape.head_dim, shape.state, shape.inner
    u = jnp.einsum("se,ew->sw", h, w["in_proj"], precision=HIGHEST)
    z, xbc, dt = u[:, :inner], u[:, inner: inner + shape.conv_width], u[:, inner + shape.conv_width:]
    xbc = depthwise_conv(xbc, w["conv"], w["conv_bias"])
    xbc = _unless(skip, "conv_silu", jax.nn.silu(xbc), xbc)
    x, b, c = xbc[:, :inner].reshape(s, heads, p), xbc[:, inner: inner + n], xbc[:, inner + n:]
    dt = _unless(skip, "dt_softplus", jax.nn.softplus(dt + w["dt_bias"]), dt + w["dt_bias"])
    a = _unless(skip, "decay", -jnp.exp(w["A_log"]) * dt, 0.0)
    y = recurrence(x, dt, a, b, c) + _unless(skip, "skip_d", w["D"][:, None] * x, 0.0)
    g = y.reshape(s, inner)
    g = _unless(skip, "gate", g * jax.nn.silu(z), g)
    normed = _unless(skip, "gate_norm", g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + shape.norm_eps), g) * w["gate_norm"]
    return {"z": z, "x": x, "B": b, "C": c, "dt": dt, "a": a, "y": y, "g": g,
            "out": jnp.einsum("sf,fe->se", normed, w["out_proj"], precision=HIGHEST)}


def ssd_mixer(h, w, shape: SsdMoEShape, skip=NONE):
    return ssd_parts(h, w, shape, skip)["out"]


def attention(h, w, shape: SsdMoEShape, skip=NONE):
    d = shape.attn_head_dim
    q = jnp.einsum("se,ehd->shd", h, w["q_attn"], precision=HIGHEST)
    k = jnp.einsum("se,ehd->shd", h, w["k_attn"], precision=HIGHEST)
    v = jnp.einsum("se,ehd->shd", h, w["v_attn"], precision=HIGHEST)
    # the shared core divides its scores by sqrt(D): q times `multiplier sqrt(D)` makes them `q k^T multiplier`
    scale = _unless(skip, "attention_multiplier", shape.attention_multiplier * np.sqrt(d), 1.0)
    return jnp.einsum("shd,hde->se", attention_core(q * scale, k, v, None), w["c_proj"], precision=HIGHEST)


def route(x, w, shape: SsdMoEShape, skip=NONE):
    """The softmax over all E experts [S, E] (the balance term's), the choice [S, k] and its gates [S, k]: the softmax over the k chosen logits."""
    logits = jnp.einsum("se,ex->sx", x, w["router"], precision=HIGHEST)
    top, choice = jax.lax.top_k(logits, shape.num_experts_per_tok)
    scores = jax.nn.softmax(logits, axis=-1)
    gates = _unless(skip, "gate_renorm", jax.nn.softmax(top, axis=-1), jnp.take_along_axis(scores, choice, axis=-1))
    return scores, choice, gates


def expert_layer(x, w, shape: SsdMoEShape, skip=NONE):
    """x [S, d]. Every held expert on every token, the gate zero where not chosen, plus the shared expert's slice; how many of
    the sequence's (token, choice) pairs each of the E experts got, held or not; and the sum over the tokens of each expert's score."""
    scores, choice, gates = route(x, w, shape, skip)
    held = jax.nn.one_hot(choice - shape.expert_offset, shape.experts_held, dtype=jnp.float32)  # an absent expert gives no one
    per_expert = jnp.einsum("sk,ske->se", gates, held)  # [S, held]

    @jax.checkpoint
    def one_expert(out, args):
        gate, up, down, weight = args
        return out + weight[:, None] * swiglu(x, gate, up, down), None

    out, _ = jax.lax.scan(one_expert, jnp.zeros_like(x), (w["experts_W"], w["experts_V"], w["experts_W_2"], per_expert.T))
    out = out + swiglu(x, w["shared_W"], w["shared_V"], w["shared_W_2"])
    return out, jnp.sum(jax.nn.one_hot(choice, shape.n_routed_experts, dtype=jnp.float32), axis=(0, 1)), jnp.sum(scores, axis=0)


def block_forward(x, w, kind: str, shape: SsdMoEShape, skip=NONE):
    """One pre-norm layer on one sequence. x [S, d]; w: the layer's leaves, float32. Returns the layer's output,
    the pairs each of the E experts got [E] and the sum of each expert's score over the sequence [E]."""
    branch = _unless(skip, "residual_multiplier", shape.residual_multiplier, 1.0)

    @jax.checkpoint
    def mixer(x, w):
        h = rms_norm(x, w["attention_norm"], shape.norm_eps)
        return x + branch * (ssd_mixer(h, w, shape, skip) if kind == "ssd" else attention(h, w, shape, skip))

    @jax.checkpoint
    def ffn(x, w):
        out, load, score_sum = expert_layer(rms_norm(x, w["ffn_norm"], shape.norm_eps), w, shape, skip)
        return x + branch * out, load, score_sum

    return ffn(mixer(x, w), w)


def layer_forward(w, x, kind: str, shape: SsdMoEShape, skip=NONE):
    """One layer on rows x [B, S, d]: its output, its balance term (over the B S tokens) and its pairs by expert [E]."""
    y, load, score_sum = jax.lax.map(lambda row: block_forward(row, w, kind, shape, skip), x)
    load = jnp.sum(load, axis=0)
    return y, balance_term(load, jnp.sum(score_sum, axis=0), x.shape[0] * x.shape[1], shape), load


def embed(wte, tokens, shape: SsdMoEShape, skip=NONE):
    return jnp.take(wte, tokens, axis=0) * _unless(skip, "embedding_multiplier", shape.embedding_multiplier, 1.0)


def _head_loss(x, outer, targets, shape: SsdMoEShape, skip=NONE):
    """Mean cross entropy of rows x [B, S, d] after the last layer: the shared blockwise loss against the table itself,
    the logits divided by `logits_scaling`."""
    scale = _unless(skip, "logits_scaling", shape.logits_scaling, 1.0)
    return head_loss(x, {"final_norm": outer["final_norm"], "lm_head": outer["wte"].T / scale}, targets, shape)


# ------------------------------------------------------------------ loss and gradients, the whole model at once


def reference_params(shape: SsdMoEShape, key, precision: str = "f32") -> dict:
    """All weights, float32: `{"runs": [a run's layers stacked on a leading axis, ...], "wte", "final_norm"}`. Traceable."""
    runs = []
    for kind, first, length in shape.runs:
        stacked = run_weights(shape, key, first, length, kind)
        runs.append({name: jax.vmap(lambda w, name=name: _as_precision(name, w, precision))(value) for name, value in stacked.items()})
    return {"runs": runs, "wte": _as_precision("wte", embedding(shape, key), precision), "final_norm": jnp.ones((shape.n_embd,), jnp.float32)}


def batch_loss(params, tokens, targets, shape: SsdMoEShape, with_parts: bool = False, skip=NONE):
    """Mean cross entropy over every position of every row plus `router_aux_loss_coef` times the mean over the
    layers of the balance term. tokens/targets [B, S]. With `with_parts` also (cross entropy, that mean, the pairs
    every layer's experts got [layers, E]). Layer after layer, written out: no scan over layers."""
    x = embed(params["wte"], tokens, shape, skip)
    terms, loads = [], []
    for (kind, _, length), stacked in zip(shape.runs, params["runs"]):
        for i in range(length):
            x, aux, load = layer_forward(jax.tree.map(lambda leaf, i=i: leaf[i], stacked), x, kind, shape, skip)
            terms.append(aux)
            loads.append(load)
    ce, aux = _head_loss(x, params, targets, shape, skip), jnp.mean(jnp.stack(terms))
    loss = ce + shape.router_aux_loss_coef * aux
    return (loss, (ce, aux, jnp.stack(loads))) if with_parts else loss


# ------------------------------------------------------------------ the same loss and gradients, one layer at a time; AdamW


@functools.lru_cache(maxsize=None)
def _layer_programs(shape: SsdMoEShape, kind: str):
    """One layer of kind `kind` on rows x [B, S, d]: its forward pass (output, balance term, pairs by expert), and its
    backward pass from the layer's input and the cotangents of its output and of its balance term (the forward is computed
    again inside). `skip` (`skip_flags`) is an argument of both."""
    forward = lambda w, x, skip: layer_forward(w, x, kind, shape, skip)  # noqa: E731

    def backward(w, x, dy, daux, skip):
        _, pull = jax.vjp(lambda w, x: forward(w, x, skip)[:2], w, x)
        return pull((dy, daux))

    return jax.jit(forward), jax.jit(backward, donate_argnums=(2,))


@functools.lru_cache(maxsize=None)
def _outer_programs(shape: SsdMoEShape):
    embedded = jax.jit(lambda wte, tokens, skip: embed(wte, tokens, shape, skip))
    head = jax.jit(jax.value_and_grad(lambda x, outer, targets, skip: _head_loss(x, outer, targets, shape, skip), argnums=(0, 1)))
    # the table's gradient: what the head gave it plus the rows the embedding read, times the multiplier
    add_embedding = jax.jit(lambda dwte, tokens, dx, skip: dwte.at[tokens].add(dx * _unless(skip, "embedding_multiplier", shape.embedding_multiplier, 1.0)),
                            donate_argnums=(0,))
    return embedded, head, add_embedding


def gradient_stream(shape: SsdMoEShape, layer_of, outer, tokens, targets, skip=NONE):
    """The loss of a batch and then its gradient, layer by layer. A generator: first `(loss, cross entropy, the mean
    balance term, the pairs every layer's E experts got [layers, E] on the host)`, then `(i, gradient of layer i's
    leaves)` for i from the last layer to the first, then `("outer", gradient of wte and final_norm)`.
    `layer_of(i)` gives layer i's leaves; the forward pass keeps every layer's input, and nothing else of a layer."""
    embedded, head, add_embedding = _outer_programs(shape)
    tokens, targets = jnp.asarray(tokens, jnp.int32), jnp.asarray(targets, jnp.int32)
    inputs, loads, terms = [embedded(outer["wte"], tokens, skip)], [], []
    for i, kind in enumerate(shape.kinds):
        y, aux, load = _layer_programs(shape, kind)[0](layer_of(i), inputs[-1], skip)
        inputs.append(y)
        loads.append(load)
        terms.append(aux)
    ce, (dx, d_outer) = head(inputs.pop(), outer, targets, skip)
    aux = float(np.mean(jax.device_get(terms)))
    yield float(ce) + shape.router_aux_loss_coef * aux, float(ce), aux, np.asarray(jax.device_get(loads), np.float64)
    daux = jnp.float32(shape.router_aux_loss_coef / shape.n_layer)
    for i in reversed(range(shape.n_layer)):
        dw, dx = _layer_programs(shape, shape.kinds[i])[1](layer_of(i), inputs.pop(), dx, daux, skip)
        yield i, dw
    d_outer["wte"] = add_embedding(d_outer["wte"], tokens, dx, skip)
    yield "outer", d_outer


def loss_and_gradients(shape: SsdMoEShape, layers: list, outer: dict, tokens, targets, skip=NONE):
    """The loss, its gradient as `(list of a layer's leaves, {"wte", "final_norm"})`, and `(cross entropy,
    the mean balance term, the pairs every expert got [layers, E])`."""
    stream = gradient_stream(shape, layers.__getitem__, outer, tokens, targets, skip)
    loss, *parts = next(stream)
    grads = dict(stream)
    return loss, ([grads[i] for i in range(shape.n_layer)], grads["outer"]), tuple(parts)


def first_mixer_output(shape: SsdMoEShape, seed: int, tokens, layer: int = 0):
    """What layer `layer`'s mixer (Mamba-2's, for layer 0) gives before the residual's multiplier on the first row of `tokens`,
    from the seeded weights and the embedded tokens as its input: `[S, d]`, for the distance by position the mode prints."""
    key = seed_key(seed)
    w = jax.jit(lambda key: {name: value.astype(jnp.float32) for name, value in layer_weights(shape, key, layer, shape.kinds[layer]).items()})(key)
    wte = jax.jit(lambda key: embedding(shape, key).astype(jnp.float32))(key)

    @jax.jit
    def mixer(w, wte, row):
        h = rms_norm(embed(wte, row, shape), w["attention_norm"], shape.norm_eps)
        return ssd_mixer(h, w, shape) if shape.kinds[layer] == "ssd" else attention(h, w, shape)

    return mixer(w, wte, jnp.asarray(tokens, jnp.int32)[0])


@functools.lru_cache(maxsize=None)
def _step_programs(shape: SsdMoEShape, precision: str, b1: float, b2: float, eps: float, weight_decay: float, other_scale: float):
    """The small programs `train_steps` runs beside a layer's two: the seeded leaves of a layer of each kind and of the table,
    a tree scaled, its squares, its squared distance from another's, AdamW's update of a tree, the squares of a tree's change.
    Compiled once for a shape and a recipe, whatever is followed with them (the sound reference, the control, a faulty one)."""
    seeded = {kind: jax.jit(lambda key, i, kind=kind: {name: _as_precision(name, value, precision)
                                                       for name, value in layer_weights(shape, key, i, kind).items()}) for kind in set(shape.kinds)}
    seeded_table = jax.jit(lambda key: {"wte": _as_precision("wte", embedding(shape, key), precision)})
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


def train_steps(shape: SsdMoEShape, seed: int, batches, hyper: dict, precision: str = "f32", skip=(),
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
    seeded_outer = lambda: {**seeded_table(key), "final_norm": jnp.ones((shape.n_embd,), jnp.float32)}  # noqa: E731

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
        factor = min(1.0, hyper["clip_norm"] / max(norm, 1e-30)) if np.isfinite(norm) else 1.0
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
