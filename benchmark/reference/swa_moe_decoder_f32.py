"""The plain reference of the window-and-global attention / expert-layer decoder (`model_type:
mellum`; the equations of ISSUE 38, written from the source's `config.json`): forward pass,
loss, gradients and AdamW in straightforward `jax.numpy`, float32, every matmul under
precision `highest`. No kernels, no cache, no dispatch, and no import of the program under
test: its weights come from `benchmark/weights_swa_moe.py`.

Architecture. Token embedding, then layers that are each `h = x + Attn_kind(RMSNorm(x))`,
`y = h + MoE(RMSNorm(h))`; final RMSNorm, an untied head, mean cross entropy over all
positions, plus `router_aux_loss_coef` times the mean over the layers of a layer's balance
term. `d` the width, `Hq` query heads on `Hkv` key/value heads of `D` (`head_dim`, a key of
its own: `Hq D` is not `d`); no bias anywhere.

Attention, on `x [S, d]`, by the layer's KIND (`layer_types`: `sliding_attention` is "swa"
here, `full_attention` is "attn"):

    q = x Wq [S, Hq, D], k = x Wk [S, Hkv, D], v = x Wv [S, Hkv, D]; q and k rotated (rotate-half, the whole head) by
    this kind's tables; scores q k^T / sqrt(D), query head h on key head h // (Hq / Hkv); position i sees j with
    j <= i, and on a window layer also i - j < W (itself and the W - 1 before it); softmax; P v; Wo [Hq, D, d]
    rotary `default`: angle of position p and pair n is p * theta^(-2n/D), n = 0 .. D/2 - 1
    rotary `yarn` (Peng et al., arXiv 2309.00071, as `transformers.modeling_rope_utils._compute_yarn_parameters`):
        t(b)   = D ln(original / (2 pi b)) / (2 ln theta)       the pair that a context of `original` turns b times
        low, high = floor(t(beta_fast)), ceil(t(beta_slow))     rounded outwards, kept inside [0, D - 1]
        ramp_n = clip((n - low) / (high - low), 0, 1)
        angle  = p * theta^(-2n/D) * ((1 - ramp_n) + ramp_n / factor);  cos and sin both times `attention_factor`

Expert layer, on `x [T, d]` (T the layer's tokens: all rows of the batch), `E` experts, `k` a token:

    p       = softmax(x Wr) over all E            Wr [d, E]
    choice  = the k largest of p                  no selection bias
    w       = p[choice] / (their sum + 1e-20)     (`norm_topk_prob`)
    out     = sum over the chosen experts e of w_e * W2_e(silu(W_e x) * (V_e x))
    aux     = E * sum_e f_e P_e                   f_e = (pairs that went to e) / (k T), no gradient; P_e = mean over the T tokens of p_e
                                                  (Switch Transformer, arXiv 2101.03961, eq. 4-6, for k choices; 1 at balance)
    loss    = CE + router_aux_loss_coef * mean over the layers of aux

**The share.** The layer holds the experts `[expert_offset, expert_offset + experts_held)`.
Router, choice, the weights' normalisation, `f` and `P` run over all E; the sum runs over the
chosen experts that are held, and what the absent ones would have added is left out, as the
program leaves it out. It is computed the plain way: every held expert on every token, with
the weight zero where the token did not choose it.

Departures from the published description, none of which changes a number: `Wq`, `Wk`, `Wv`,
`Wo` are kept with their head axis apart; the experts are three stacks `[held, d, f]`; the
balance term is a layer's own and the loss takes the mean over the layers (Hugging Face's
`load_balancing_loss_func` concatenates every layer's router logits and computes one term:
pooled, twelve collapsed layers that picked different experts count as balanced; ISSUE 38
and `meta.json` `assumed`); `described_as` mentions an MTP head for which `config.json` has
no key: none is run. Not in `config.json`: QK norm (none is run), the scoring function
(softmax, as every family that publishes `num_experts` with `norm_topk_prob` has it).

Departures from a textbook forward, all for memory: attention is computed one key/value
head's group of query heads at a time and in blocks of query rows, a window layer's block
against only the keys its window can reach; the head in blocks of positions; the experts
one at a time; the mixer and the feed-forward of a layer each rematerialized. Training walks
the layers one at a time (`gradient_stream`: the forward pass keeps each layer's input, the
backward pass differentiates one layer at a time, the balance term's cotangent beside the
activation's), which computes what `jax.grad` of `batch_loss` computes (a test holds the two
together). Adam's moments are never kept on the device: `train_steps` keeps each earlier
step's clipped gradient on the host, as the expert cell's reference does.

`precision`: "f32" is the reference; "int8" rounds every kernel the program keeps in bfloat16
to 8 bits (symmetric, one scale per output channel) before use, the nearest precision below
the bfloat16 the configuration states: the control of "How `correct` is decided". The
router's matrix is float32 in the program and stays as it is.
"""

from __future__ import annotations

import functools
import math
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.weights_swa_moe import Rotary, SwaMoEShape, embedding, head as head_matrix, layer_weights, run_weights, seed_key

HIGHEST = "highest"
Q_BLOCK = 512  # query rows per attention block
HEAD_BLOCK = 1024  # positions per head/loss block

# which axes of each kernel are summed over where it is used (the others are output channels)
CONTRACT_AXES = {"q_attn": (0,), "k_attn": (0,), "v_attn": (0,), "c_proj": (0, 1),
                 "experts_W": (1,), "experts_V": (1,), "experts_W_2": (1,), "wte": (1,), "lm_head": (0,)}
# what AdamW does not decay: the configuration's `weight_decay_groups_excluded: [embedding, norm]`
NOT_DECAYED = ("attention_norm", "ffn_norm", "final_norm", "wte")
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


def yarn_bounds(head_dim: int, rotary: Rotary) -> tuple[int, int]:
    """Between which two of the D/2 pairs YaRN's ramp runs (18 and 35 for the source's numbers)."""
    turned = lambda b: head_dim * math.log(rotary.original / (2 * math.pi * b)) / (2 * math.log(rotary.theta))  # noqa: E731
    return max(math.floor(turned(rotary.beta_fast)), 0), min(math.ceil(turned(rotary.beta_slow)), head_dim - 1)


def rotary_tables(seq: int, head_dim: int, rotary: Rotary):
    """cos and sin `[S, D]` of one kind of layer's rotary, the D/2 angles twice (rotate-half)."""
    if rotary.rope_type == "yarn":
        low, high = yarn_bounds(head_dim, rotary)
        pair = np.arange(head_dim // 2, dtype=np.float64)
        ramp = np.clip((pair - low) / max(high - low, 1e-3), 0.0, 1.0)
        inv_freq = jnp.asarray((rotary.theta ** (-2.0 * pair / head_dim) * ((1.0 - ramp) + ramp / rotary.factor)).astype(np.float32))
    else:
        inv_freq = 1.0 / (rotary.theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    angle = jnp.arange(seq, dtype=jnp.float32)[:, None] * inv_freq
    angle = jnp.concatenate([angle, angle], axis=-1)
    return jnp.cos(angle) * rotary.attention_factor, jnp.sin(angle) * rotary.attention_factor


def rotate(x, cos, sin):
    """x [S, H, D] turned by the tables [S, D]: x cos + rotate_half(x) sin."""
    half = x.shape[-1] // 2
    turned = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos[:, None, :] + turned * sin[:, None, :]


def attention_core(q, k, v, window: int | None):
    """q [S, Hq, D], k, v [S, Hkv, D] -> [S, Hq, D]: softmax(q k^T / sqrt(D)) v where position i sees j <= i and, under
    `window`, only i - j < window. One key/value head's group of query heads at a time, in blocks of Q_BLOCK query rows;
    under a window a block meets only the keys from `window` before its first row to its last row."""
    s, hq, d = q.shape
    hkv = k.shape[1]
    block = min(Q_BLOCK, s)
    pad = (-s) % block
    banded = window is not None and window < s
    reach = window if banded else 0  # keys before a block's first row that its rows can see
    starts = jnp.arange((s + pad) // block) * block

    def one_group(args):
        qg, kh, vh = args  # [S, G, D], [S, D], [S, D]
        qp = jnp.pad(qg, ((0, pad), (0, 0), (0, 0))).reshape(-1, block, qg.shape[1], d)
        kp, vp = (jnp.pad(a, ((reach, pad), (0, 0))) for a in (kh, vh)) if banded else (kh, vh)

        @jax.checkpoint
        def one_block(args):
            qb, start = args
            if banded:
                keys = jax.lax.dynamic_slice_in_dim(kp, start, reach + block, 0)
                values = jax.lax.dynamic_slice_in_dim(vp, start, reach + block, 0)
                at = start - reach + jnp.arange(reach + block)  # the keys' positions; below 0: padding
            else:
                keys, values, at = kp, vp, jnp.arange(s)
            rows = start + jnp.arange(block)
            behind = rows[:, None] - at[None, :]
            seen = (behind >= 0) & (at[None, :] >= 0)
            if window is not None:
                seen = seen & (behind < window)
            scores = jnp.einsum("qgd,kd->gqk", qb, keys, precision=HIGHEST) / np.sqrt(d)
            probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), axis=-1)
            return jnp.einsum("gqk,kd->qgd", probs, values, precision=HIGHEST)

        return jax.lax.map(one_block, (qp, starts)).reshape(-1, qg.shape[1], d)[:s]

    grouped = q.reshape(s, hkv, hq // hkv, d).transpose(1, 0, 2, 3)  # query head h reads key head h // group
    out = jax.lax.map(one_group, (grouped, k.transpose(1, 0, 2), v.transpose(1, 0, 2)))
    return out.transpose(1, 0, 2, 3).reshape(s, hq, d)


def attention(x, w, kind: str, shape: SwaMoEShape):
    q = jnp.einsum("se,ehd->shd", x, w["q_attn"], precision=HIGHEST)
    k = jnp.einsum("se,ehd->shd", x, w["k_attn"], precision=HIGHEST)
    v = jnp.einsum("se,ehd->shd", x, w["v_attn"], precision=HIGHEST)
    cos, sin = rotary_tables(x.shape[0], shape.head_dim, shape.rotary_of(kind))
    out = attention_core(rotate(q, cos, sin), rotate(k, cos, sin), v, shape.sliding_window if kind == "swa" else None)
    return jnp.einsum("shd,hde->se", out, w["c_proj"], precision=HIGHEST)


def swiglu(h, gate, up, down):
    a = jnp.einsum("se,ef->sf", h, gate, precision=HIGHEST)
    b = jnp.einsum("se,ef->sf", h, up, precision=HIGHEST)
    return jnp.einsum("sf,fe->se", jax.nn.silu(a) * b, down, precision=HIGHEST)


def route(x, w, shape: SwaMoEShape):
    """The scores [S, E] over all E experts, the choice [S, k] and its weights [S, k]."""
    scores = jax.nn.softmax(jnp.einsum("se,ex->sx", x, w["router"], precision=HIGHEST), axis=-1)
    _, choice = jax.lax.top_k(scores, shape.num_experts_per_tok)
    weights = jnp.take_along_axis(scores, choice, axis=-1)
    if shape.norm_topk_prob:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-20)
    return scores, choice, weights


def expert_layer(x, w, shape: SwaMoEShape):
    """x [S, d]. Every held expert on every token, the weight zero where not chosen; how many of the sequence's (token,
    choice) pairs each of the E experts got, held or not; and the sum over the sequence's tokens of each expert's score."""
    scores, choice, weights = route(x, w, shape)
    held = jax.nn.one_hot(choice - shape.expert_offset, shape.experts_held, dtype=jnp.float32)  # an absent expert gives no one
    per_expert = jnp.einsum("sk,ske->se", weights, held)  # [S, held]

    @jax.checkpoint
    def one_expert(out, args):
        gate, up, down, weight = args
        return out + weight[:, None] * swiglu(x, gate, up, down), None

    out, _ = jax.lax.scan(one_expert, jnp.zeros_like(x), (w["experts_W"], w["experts_V"], w["experts_W_2"], per_expert.T))
    return out, jnp.sum(jax.nn.one_hot(choice, shape.n_routed_experts, dtype=jnp.float32), axis=(0, 1)), jnp.sum(scores, axis=0)


def block_forward(x, w, kind: str, shape: SwaMoEShape):
    """One pre-norm layer on one sequence. x [S, d]; w: the layer's leaves, float32. Returns the layer's output,
    the pairs each of the E experts got [E] and the sum of each expert's score over the sequence [E]."""

    @jax.checkpoint
    def mixer(x, w):
        return x + attention(rms_norm(x, w["attention_norm"], shape.norm_eps), w, kind, shape)

    @jax.checkpoint
    def ffn(x, w):
        out, load, score_sum = expert_layer(rms_norm(x, w["ffn_norm"], shape.norm_eps), w, shape)
        return x + out, load, score_sum

    return ffn(mixer(x, w), w)


def balance_term(load, score_sum, tokens: int, shape: SwaMoEShape):
    """`E sum_e f_e P_e` of one layer from its pairs by expert and the sums of its scores, both over all `tokens` of the layer."""
    share = jax.lax.stop_gradient(load) / (shape.num_experts_per_tok * tokens)
    return shape.n_routed_experts * jnp.sum(share * score_sum / tokens)


def layer_forward(w, x, kind: str, shape: SwaMoEShape):
    """One layer on rows x [B, S, d]: its output, its balance term (over the B S tokens) and its pairs by expert [E]."""
    y, load, score_sum = jax.lax.map(lambda row: block_forward(row, w, kind, shape), x)
    load = jnp.sum(load, axis=0)
    return y, balance_term(load, jnp.sum(score_sum, axis=0), x.shape[0] * x.shape[1], shape), load


def head_logits(x, final_norm, lm_head, shape: SwaMoEShape):
    """x [S, d] -> float32 logits [S, V], against the untied head [d, V]."""
    return jnp.einsum("se,ev->sv", rms_norm(x, final_norm, shape.norm_eps), lm_head, precision=HIGHEST)


# ------------------------------------------------------------------ the forward pass, layer by layer


def reference_layer(shape: SwaMoEShape, key, layer: int, precision: str = "f32") -> dict:
    """Layer `layer` of the seeded weights: the values the program is given, upcast (and, for the control, its kernels rounded to int8)."""
    return {name: _as_precision(name, value, precision) for name, value in layer_weights(shape, key, layer).items()}


def logits_layer_by_layer(shape: SwaMoEShape, seed: int, tokens, precision: str = "f32"):
    """Float32 logits [N, S, V] of `tokens` [N, S]; one layer's float32 weights live at a time."""
    tokens = jnp.asarray(tokens, jnp.int32)
    key = seed_key(seed)

    @functools.partial(jax.jit, static_argnums=(0,))
    def one_layer(layer, x, key):
        return layer_forward(reference_layer(shape, key, layer, precision), x, shape.kinds[layer], shape)[0]

    @jax.jit
    def head(x, lm_head):
        return jax.lax.map(lambda row: head_logits(row, jnp.ones((shape.n_embd,), jnp.float32), lm_head, shape), x)

    wte = jax.jit(lambda key: _as_precision("wte", embedding(shape, key), precision))(key)
    x = jnp.take(wte, tokens, axis=0)
    for layer in range(shape.n_layer):
        x = one_layer(layer, x, key)
    return head(x, jax.jit(lambda key: _as_precision("lm_head", head_matrix(shape, key), precision))(key))


# ------------------------------------------------------------------ loss and gradients, the whole model at once


def reference_params(shape: SwaMoEShape, key, precision: str = "f32") -> dict:
    """All weights, float32: `{"runs": [a run's layers stacked on a leading axis, ...], "wte", "lm_head", "final_norm"}`. Traceable."""
    runs = []
    for _, first, length in shape.runs:
        stacked = run_weights(shape, key, first, length)
        runs.append({name: jax.vmap(lambda w, name=name: _as_precision(name, w, precision))(value) for name, value in stacked.items()})
    return {"runs": runs, "wte": _as_precision("wte", embedding(shape, key), precision),
            "lm_head": _as_precision("lm_head", head_matrix(shape, key), precision),
            "final_norm": jnp.ones((shape.n_embd,), jnp.float32)}


def head_loss_sum(x, outer, targets, shape: SwaMoEShape):
    """Sum of the cross entropy over the positions of one sequence, from x [S, d] after the last layer; `outer`
    holds `wte`, `lm_head` and `final_norm`. In blocks of positions, each rematerialized."""
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


def head_loss(x, outer, targets, shape: SwaMoEShape):
    """Mean cross entropy of rows x [B, S, d] after the last layer."""
    sums = jax.lax.map(lambda row: head_loss_sum(row[0], outer, row[1], shape), (x, targets))
    return jnp.sum(sums) / (x.shape[0] * x.shape[1])


def batch_loss(params, tokens, targets, shape: SwaMoEShape, with_parts: bool = False):
    """Mean cross entropy over every position of every row plus `router_aux_loss_coef` times the mean over the
    layers of the balance term. tokens/targets [B, S]. With `with_parts` also (cross entropy, that mean)."""
    x = jnp.take(params["wte"], tokens, axis=0)
    terms = []
    for (kind, _, _), stacked in zip(shape.runs, params["runs"]):
        x, aux = jax.lax.scan(lambda x, w, kind=kind: layer_forward(w, x, kind, shape)[:2], x, stacked)
        terms.append(aux)
    ce, aux = head_loss(x, params, targets, shape), jnp.mean(jnp.concatenate(terms))
    loss = ce + shape.router_aux_loss_coef * aux
    return (loss, (ce, aux)) if with_parts else loss


# ------------------------------------------------------------------ the same loss and gradients, one layer at a time; AdamW


@functools.lru_cache(maxsize=None)
def _layer_programs(shape: SwaMoEShape, kind: str):
    """One layer of kind `kind` on rows x [B, S, d]: its forward pass (output, balance term, pairs by expert), and its
    backward pass from the layer's input and the cotangents of its output and of its balance term (the forward is computed again inside)."""
    forward = lambda w, x: layer_forward(w, x, kind, shape)  # noqa: E731

    def backward(w, x, dy, daux):
        _, pull = jax.vjp(lambda w, x: forward(w, x)[:2], w, x)
        return pull((dy, daux))

    return jax.jit(forward), jax.jit(backward, donate_argnums=(2,))


@functools.lru_cache(maxsize=None)
def _outer_programs(shape: SwaMoEShape):
    embed = jax.jit(lambda wte, tokens: jnp.take(wte, tokens, axis=0))
    head = jax.jit(jax.value_and_grad(lambda x, outer, targets: head_loss(x, outer, targets, shape), argnums=(0, 1)))
    # the table's gradient: what the head gave it plus the rows the embedding read
    add_embedding = jax.jit(lambda dwte, tokens, dx: dwte.at[tokens].add(dx), donate_argnums=(0,))
    return embed, head, add_embedding


def gradient_stream(shape: SwaMoEShape, layer_of, outer, tokens, targets):
    """The loss of a batch and then its gradient, layer by layer. A generator: first `(loss, cross entropy, the mean
    balance term, the pairs every layer's E experts got [layers, E] on the host)`, then `(i, gradient of layer i's
    leaves)` for i from the last layer to the first, then `("outer", gradient of wte, lm_head and final_norm)`.
    `layer_of(i)` gives layer i's leaves; the forward pass keeps every layer's input, and nothing else of a layer."""
    embed, head, add_embedding = _outer_programs(shape)
    tokens, targets = jnp.asarray(tokens, jnp.int32), jnp.asarray(targets, jnp.int32)
    inputs, loads, terms = [embed(outer["wte"], tokens)], [], []
    for i, kind in enumerate(shape.kinds):
        y, aux, load = _layer_programs(shape, kind)[0](layer_of(i), inputs[-1])
        inputs.append(y)
        loads.append(load)
        terms.append(aux)
    ce, (dx, d_outer) = head(inputs.pop(), outer, targets)
    aux = float(np.mean(jax.device_get(terms)))
    yield float(ce) + shape.router_aux_loss_coef * aux, float(ce), aux, np.asarray(jax.device_get(loads), np.float64)
    daux = jnp.float32(shape.router_aux_loss_coef / shape.n_layer)
    for i in reversed(range(shape.n_layer)):
        dw, dx = _layer_programs(shape, shape.kinds[i])[1](layer_of(i), inputs.pop(), dx, daux)
        yield i, dw
    d_outer["wte"] = add_embedding(d_outer["wte"], tokens, dx)
    yield "outer", d_outer


def pairs_held(shape: SwaMoEShape, loads) -> float:
    """What the program's counter `moe_pairs_held` counts: the pairs the held experts got, the mean over the expert layers."""
    return float(loads[:, shape.expert_offset: shape.expert_offset + shape.experts_held].sum(axis=1).mean())


def loss_and_gradients(shape: SwaMoEShape, layers: list, outer: dict, tokens, targets):
    """The loss, its gradient as `(list of a layer's leaves, {"wte", "lm_head", "final_norm"})`, and `(cross entropy,
    the mean balance term, the pairs every expert got [layers, E])`."""
    stream = gradient_stream(shape, layers.__getitem__, outer, tokens, targets)
    loss, *parts = next(stream)
    grads = dict(stream)
    return loss, ([grads[i] for i in range(shape.n_layer)], grads["outer"]), tuple(parts)


def by_run(shape: SwaMoEShape, per_layer: list, outer: dict, stack=jnp.stack) -> dict:
    """Per-layer trees and the outer leaves as the comparison names them: `run<r>.<leaf>` stacked over the run's layers, `wte`, `lm_head`, `final_norm`."""
    out = {f"run{r}.{name}": stack([per_layer[first + k][name] for k in range(length)])
           for r, (_, first, length) in enumerate(shape.runs) for name in per_layer[first]}
    out.update(outer)
    return out


def _squares(tree):
    return jax.tree.map(lambda v: jnp.sum(v.astype(jnp.float32) ** 2), tree)


def leaf_norms(tree) -> dict:
    """Euclidean norm of every leaf of a tree in the run-stacked layout (`{"runs": [a run's leaves stacked on a leading
    axis, ...], "wte", "lm_head", "final_norm"}`): a run's leaf gives one norm per layer. Traceable (the program's side of the comparison uses it)."""
    out = {}
    for r, run in enumerate(tree["runs"]):
        for name, value in run.items():
            out[f"run{r}.{name}"] = jnp.sqrt(jnp.sum(value.astype(jnp.float32) ** 2, axis=tuple(range(1, value.ndim))))
    for name in OUTER:
        out[name] = jnp.sqrt(jnp.sum(tree[name].astype(jnp.float32) ** 2))
    return out


def train_steps(shape: SwaMoEShape, seed: int, batches, hyper: dict, precision: str = "f32",
                other_first_grad=None, other_scale: float = 1.0, keep_first_grad: bool = False, log=None) -> dict:
    """Follow the first `len(batches)` optimizer steps from the seeded weights.

    `batches` is a list of (tokens [B, S], targets [B, S]); `hyper` holds `lr` (a list, one learning rate per step),
    `b1`, `b2`, `eps`, `weight_decay`, `clip_norm`. AdamW as the configuration's optimizer block describes it:
    global-norm clipping, bias-corrected moments, decoupled decay scaled by the learning rate, no decay on NOT_DECAYED.

    Memory. Adam's moments are never kept on the device: m_t = (1 - b1) sum_j b1^(t-j) g_j and v_t = (1 - b2) sum_j
    b2^(t-j) g_j^2, and each earlier clipped gradient g_j waits on the host (float32 as it was computed) and comes
    back a layer at a time beside the update that needs it.

    Returns the loss of each step (with the balance term as the configuration weighs it), each step's cross entropy
    alone (`ce`), the norm of its whole gradient before clipping (`grad_norm`), its balance term (`aux_loss`: the mean over the layers, what the program's counter `moe_aux_loss`
    counts) and the pairs the held experts got (`pairs_held`: the mean over the layers, the program's `moe_pairs_held`),
    the norm of each leaf of the first clipped gradient, and the norm of each leaf of the parameters' change after the
    last step. With `other_first_grad` (someone else's first gradient as their optimizer got it, host arrays in the
    run-stacked layout, to be multiplied by `other_scale`: Adam's first moment after one step is (1 - b1) times the
    gradient) also the norm of each leaf of its difference from this one; with `keep_first_grad` this first gradient
    itself, on the host, in that layout. `log` is called with a line at each stage."""
    key = seed_key(seed)
    t0 = time.perf_counter()
    say = (lambda what: log(f"[reference] {time.perf_counter() - t0:7.2f} s {what}")) if log else (lambda what: None)
    b1, b2, steps, n = hyper["b1"], hyper["b2"], len(batches), shape.n_layer
    seeded = jax.jit(lambda key, i: {name: _as_precision(name, value, precision) for name, value in layer_weights(shape, key, i).items()})
    seeded_layer = lambda i: seeded(key, jnp.int32(i))  # noqa: E731
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
    losses, ces, terms, held, norms, extra = [], [], [], [], [], {}
    kept: list[tuple[list, dict]] = []  # the clipped gradients of the steps before, on the host: (a layer's leaves each, the outer leaves)
    first_squares = None
    for t, (tokens, targets) in enumerate(batches, start=1):
        loss, (grads, outer_grads), (ce, aux, loads) = loss_and_gradients(shape, layers, outer, tokens, targets)
        losses.append(loss)
        ces.append(ce)
        terms.append(aux)
        held.append(pairs_held(shape, loads))
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

    change = jax.jit(lambda now, then: _squares(jax.tree.map(lambda a, b: a - b, now, then)))
    moved = [jax.device_get(change(layers[i], seeded_layer(i))) for i in range(n)]
    moved_outer = jax.device_get(change(outer, seeded_outer()))
    say("the parameters' change")
    root = lambda named: {name: np.sqrt(value) for name, value in named.items()}  # noqa: E731
    return {"losses": losses, "ce": ces, "aux_loss": terms, "pairs_held": held, "grad_norm": norms,
            "first_grad_norms": root(by_run(shape, *first_squares, np.stack)),
            "delta_norms": root(by_run(shape, moved, moved_outer, np.stack)), **extra}
