"""The plain reference of the compressed-convolutional-attention / expert-layer decoder
(`model_type: zaya`; the equations of ISSUE 40, written from the source's `config.json`, the
sibling row's four framework keys and arXiv 2510.04476 / 2511.17127): forward pass, loss,
gradients, AdamW and the selection bias's rule in straightforward `jax.numpy`, float32,
every matmul under precision `highest`. No kernels, no cache, no dispatch, no scan over
layers, and no import of the program under test: its weights come from
`benchmark/weights_cca_moe.py`.

Architecture. Token embedding, then layers that are each an attention sub-layer, a merge, an
expert sub-layer, a merge; final RMSNorm, the head tied to the table, mean cross entropy over
all positions. `E` the width, `Hq` query heads on `Hkv` key/value heads of `d`, `G = Hq / Hkv`.

Attention sub-layer (CCA, the grouped form), on `h = RMSNorm(x)` `[S, E]`:

    q0 = h W_q [S, Hq, d]      k0 = h W_k [S, Hkv, d]      u = concat(q0, k0) over heads
    c[t]    = sum_j a_j * u[t - (K0 - 1) + j] + b                      depthwise, `cca_time0` taps, zeros before t = 0
    e[t, g] = sum_j B_j[g] c[t - (K1 - 1) + j, g] + b'                 grouped by head, B_j[g] [d, d], `cca_time1` taps
    (qc, kc) = split(e);   mq[t, i] = (q0[t, i] + k0[t, i // G]) / 2;   mk[t, j] = mean over group j's query heads of mq
    q = sqrt(d) (qc + mq) / ||.||      k = tau[j] sqrt(d) (kc + mk) / ||.||           tau [Hkv] learned
    q, k rotated on the first `rotated` channels of a head (rotate-half inside them, angle p theta^(-2n/rotated)), the rest passed
    v[t, :Hkv/2] = h[t] W_v      v[t, Hkv/2:] = h[t - 1] W_v'   (h[-1] = 0)
    a = (softmax(q k^T / sqrt(d), causal) v, query head i on key head i // G) W_o

Merge: `x = (x + r_b) r_s + (a + f_b) f_s`, four `[E]` leaves; layer 0's first merge has no `r_*`.

Expert sub-layer, on `h = RMSNorm(x)` `[T, E]` (T the layer's tokens: all rows of the batch), router in float32:

    s_l = h W_d + b_d;   s_l = s_l + g_l s_{l-1} for l > 0   (the previous layer's s after its own sum, before its norm)
    z = RMSNorm_R(s_l);  logits = W_3 gelu(W_2 gelu(W_1 z + b_1) + b_2)  (exact gelu);  p = softmax(logits) over the E + 1 columns
    c = argmax(p + beta);   y = p[c] expert_c(h) if c is a held expert, 0 if it is the skip column or an expert not held
    expert_e(h) = W2_e (silu(W_e h) * (V_e h))

after a step `beta_e += bias_update_speed * sign(mean load of the columns - load of e)` over all E + 1 columns
(`moved_bias`; `train_steps` applies it after each step's AdamW update, which leaves `beta` alone: its gradient is zero).

**The share.** The layer holds the experts `[expert_offset, expert_offset + experts_held)`. Router, choice and loads run
over all the columns; the sum runs over the chosen experts that are held, and what the absent ones would have added is
left out, as the program leaves it out: every held expert on every token, the weight zero where not chosen.

Departures from the published description, none known to change a number but where said: five details no `config.json`
fixes are set as ISSUE 40 sets them (`meta.json`, `assumed`; a reader of the published modelling code, `modeling_zaya.py`,
would look in the attention module's forward, the router module's forward and the decoder layer's residual scaling):
(1) the skip column's output is zero and its `beta` starts at 0 like the others; (2) `tau` multiplies the normalised key
(not its exponential) and q has none; (3) the state handed on is taken before the router's norm; (4) `beta` moves by
DeepSeek-V3's sign rule; (5) both convolutions carry a bias, and layer 0's first merge has no scale or shift on the
residual side. The L2 norm divides by `max(||x||, 1e-12)`. `W_q`, `W_k`, `W_v`, `W_o` are kept with their head axis
apart; the experts are three stacks `[held, E, f]`.

Departures from a textbook forward, all for memory: attention is computed one key/value head's group of query heads at a
time and in blocks of query rows; the head in blocks of positions; the experts one at a time; the two sub-layers each
rematerialized. Training walks the layers one at a time (`gradient_stream`: the forward pass keeps each layer's input and
the state handed to it, the backward pass differentiates one layer at a time and hands the state's cotangent back beside
the activation's), which computes what `jax.grad` of `batch_loss` computes (a test holds the two together). Adam's
moments are never kept on the device: `train_steps` keeps each earlier step's clipped gradient on the host.

`precision`: "f32" is the reference; "int8" rounds every kernel the program keeps in bfloat16 to 8 bits (symmetric, one
scale per output channel) before use, the nearest precision below the bfloat16 the configuration states: the control of
"How `correct` is decided". What the program keeps in float32 (the router, the depthwise taps, every vector) stays as it is.
`shape.without` names steps of the equations a control leaves out (`no_conv`: q = mq, k = mk; `no_value_shift`: every
value head from the current position; `no_qk_mean`: q = qc, k = kc; `no_eda`: no state added; `full_rotary` is a shape
with `rotated = head_dim`): what a program with that fault would compute.
"""

from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.weights_cca_moe import CcaMoEShape, embedding, layer_weights, seed_key, stack_weights

HIGHEST = "highest"
Q_BLOCK = 512  # query rows per attention block
HEAD_BLOCK = 1024  # positions per head/loss block

# which axes of each kernel are summed over where it is used (the others are output channels)
CONTRACT_AXES = {"q_attn": (0,), "k_attn": (0,), "v_attn": (0,), "v_attn_prev": (0,), "c_proj": (0, 1), "conv1_kernel": (2,),
                 "experts_W": (1,), "experts_V": (1,), "experts_W_2": (1,), "wte": (1,)}
# what AdamW decays: the matrices. Every vector, the embedding and the selection bias are left (the configuration's `weight_decay_groups_excluded`)
DECAYED = ("q_attn", "k_attn", "v_attn", "v_attn_prev", "c_proj", "conv0_kernel", "conv1_kernel", "router_down", "router_fc1", "router_fc2",
           "router_out", "experts_W", "experts_V", "experts_W_2")
BIAS = "router_bias"  # the selection bias: no gradient, no decay, moved by its rule
OUTER = ("wte", "final_norm")


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


def earlier(x, steps: int):
    """x [S, ...] moved `steps` positions later, zeros coming in: `earlier(x, 1)[t] = x[t - 1]`."""
    if steps == 0:
        return x
    return jnp.concatenate([jnp.zeros_like(x[:steps]), x[:-steps]], axis=0)


def depthwise_conv(u, kernel, bias):
    """u [S, C], kernel [K, C] (the last tap weighs the current position), bias [C]."""
    taps = kernel.shape[0]
    return sum(kernel[j] * earlier(u, taps - 1 - j) for j in range(taps)) + bias


def grouped_conv(c, kernel, bias):
    """c [S, H, d], kernel [K, H, d, d], bias [H, d]: the channels mix inside a head only."""
    taps = kernel.shape[0]
    return sum(jnp.einsum("shd,hde->she", earlier(c, taps - 1 - j), kernel[j], precision=HIGHEST) for j in range(taps)) + bias


def l2_normalised(x, scale):
    return x * scale / jnp.maximum(jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True)), 1e-12)


def rotate_part(x, rotated: int, theta: float):
    """x [S, H, d]: the first `rotated` channels of every head turned (rotate-half inside them), the rest passed."""
    if rotated == 0:
        return x
    inv_freq = 1.0 / (theta ** (jnp.arange(0, rotated, 2, dtype=jnp.float32) / rotated))
    angle = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv_freq
    angle = jnp.concatenate([angle, angle], axis=-1)[:, None, :]
    part, rest = x[..., :rotated], x[..., rotated:]
    half = rotated // 2
    turned = jnp.concatenate([-part[..., half:], part[..., :half]], axis=-1)
    return jnp.concatenate([part * jnp.cos(angle) + turned * jnp.sin(angle), rest], axis=-1)


def attention_core(q, k, v):
    """q [S, Hq, d], k, v [S, Hkv, d] -> [S, Hq, d]: softmax(q k^T / sqrt(d)) v where position i sees j <= i. One key/value
    head's group of query heads at a time, in blocks of Q_BLOCK query rows."""
    s, hq, d = q.shape
    hkv = k.shape[1]
    block = min(Q_BLOCK, s)
    pad = (-s) % block
    starts = jnp.arange((s + pad) // block) * block

    def one_group(args):
        qg, kh, vh = args  # [S, G, d], [S, d], [S, d]
        qp = jnp.pad(qg, ((0, pad), (0, 0), (0, 0))).reshape(-1, block, qg.shape[1], d)

        @jax.checkpoint
        def one_block(args):
            qb, start = args
            seen = (start + jnp.arange(block))[:, None] >= jnp.arange(s)[None, :]
            scores = jnp.einsum("qgd,kd->gqk", qb, kh, precision=HIGHEST) / np.sqrt(d)
            probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), axis=-1)
            return jnp.einsum("gqk,kd->qgd", probs, vh, precision=HIGHEST)

        return jax.lax.map(one_block, (qp, starts)).reshape(-1, qg.shape[1], d)[:s]

    grouped = q.reshape(s, hkv, hq // hkv, d).transpose(1, 0, 2, 3)  # query head i reads key head i // group
    out = jax.lax.map(one_group, (grouped, k.transpose(1, 0, 2), v.transpose(1, 0, 2)))
    return out.transpose(1, 0, 2, 3).reshape(s, hq, d)


def latent_qkv(h, w, shape: CcaMoEShape):
    """q, k (normalised, before the rotary) and v of the attention sub-layer on one sequence's normed input h [S, E]."""
    hq, hkv, d = shape.n_head_q, shape.n_head_kv, shape.head_dim
    q0 = jnp.einsum("se,ehd->shd", h, w["q_attn"], precision=HIGHEST)
    k0 = jnp.einsum("se,ehd->shd", h, w["k_attn"], precision=HIGHEST)
    u = jnp.concatenate([q0, k0], axis=1)
    c = depthwise_conv(u.reshape(u.shape[0], -1), w["conv0_kernel"], w["conv0_bias"]).reshape(u.shape)
    e = grouped_conv(c, w["conv1_kernel"], w["conv1_bias"])
    mq = (q0.reshape(-1, hkv, hq // hkv, d) + k0[:, :, None, :]) / 2
    mk = jnp.mean(mq, axis=2)
    mq = mq.reshape(q0.shape)
    if "no_conv" in shape.without:
        e = jnp.zeros_like(e)
    if "no_qk_mean" in shape.without:
        mq, mk = jnp.zeros_like(mq), jnp.zeros_like(mk)
    q = l2_normalised(e[:, :hq] + mq, np.sqrt(d))
    k = l2_normalised(e[:, hq:] + mk, w["key_temperature"][None, :, None] * np.sqrt(d))
    h_prev = h if "no_value_shift" in shape.without else earlier(h, 1)
    v = jnp.concatenate([jnp.einsum("se,ehd->shd", h, w["v_attn"], precision=HIGHEST),
                         jnp.einsum("se,ehd->shd", h_prev, w["v_attn_prev"], precision=HIGHEST)], axis=1)
    return q, k, v


def attention(h, w, shape: CcaMoEShape):
    q, k, v = latent_qkv(h, w, shape)
    q, k = rotate_part(q, shape.rotated, shape.rope_theta), rotate_part(k, shape.rotated, shape.rope_theta)
    return jnp.einsum("shd,hde->se", attention_core(q, k, v), w["c_proj"], precision=HIGHEST)


def merge(x, a, w, which: str, passes: bool = False):
    """`(x + r_b) r_s + (a + f_b) f_s`; with `passes` the residual side has no scale or shift."""
    out = (a + w[f"{which}_out_bias"]) * w[f"{which}_out_scale"]
    return x + out if passes else (x + w[f"{which}_residual_bias"]) * w[f"{which}_residual_scale"] + out


def swiglu(h, gate, up, down):
    a = jnp.einsum("se,ef->sf", h, gate, precision=HIGHEST)
    b = jnp.einsum("se,ef->sf", h, up, precision=HIGHEST)
    return jnp.einsum("sf,fe->se", jax.nn.silu(a) * b, down, precision=HIGHEST)


def route(h, w, previous, shape: CcaMoEShape):
    """The probabilities [S, columns], the choice [S, k], its weights [S, k], and the state this layer hands on [S, R]."""
    dense = lambda x, name: jnp.einsum("sr,rq->sq", x, w[f"router_{name}"], precision=HIGHEST) + w[f"router_{name}_bias"]  # noqa: E731
    state = jnp.einsum("se,er->sr", h, w["router_down"], precision=HIGHEST) + w["router_down_bias"]
    if shape.use_eda and previous is not None and "no_eda" not in shape.without:
        state = state + w["eda_gate"] * previous
    z = rms_norm(state, w["router_norm"], shape.norm_eps)
    hidden = jax.nn.gelu(dense(jax.nn.gelu(dense(z, "fc1"), approximate=False), "fc2"), approximate=False)
    probs = jax.nn.softmax(jnp.einsum("sr,rc->sc", hidden, w["router_out"], precision=HIGHEST), axis=-1)
    _, choice = jax.lax.top_k(probs + w[BIAS], shape.num_experts_per_tok)
    return probs, choice, jnp.take_along_axis(probs, choice, axis=-1), state


def expert_layer(h, w, previous, shape: CcaMoEShape):
    """h [S, E]. Every held expert on every token, the weight zero where not chosen; how many of the sequence's tokens
    chose each of the router's columns, held or not; the state handed on."""
    _, choice, weights, state = route(h, w, previous, shape)
    held = jax.nn.one_hot(choice - shape.expert_offset, shape.experts_held, dtype=jnp.float32)  # an absent expert or the skip column gives no one
    per_expert = jnp.einsum("sk,ske->se", weights, held)  # [S, held]

    @jax.checkpoint
    def one_expert(out, args):
        gate, up, down, weight = args
        return out + weight[:, None] * swiglu(h, gate, up, down), None

    out, _ = jax.lax.scan(one_expert, jnp.zeros_like(h), (w["experts_W"], w["experts_V"], w["experts_W_2"], per_expert.T))
    return out, jnp.sum(jax.nn.one_hot(choice, shape.router_width, dtype=jnp.float32), axis=(0, 1)), state


def block_forward(x, previous, w, first: bool, shape: CcaMoEShape):
    """One layer on one sequence. x [S, E]; `previous` [S, R]: the state the layer before handed on (None: there is none);
    w: the layer's leaves, float32; `first`: layer 0, whose first merge passes the residual. Returns the layer's output,
    the state it hands on and the tokens each router column got."""

    @jax.checkpoint
    def mixer(x, w):
        return merge(x, attention(rms_norm(x, w["attention_norm"], shape.norm_eps), w, shape), w, "attn_merge", passes=first)

    @jax.checkpoint
    def ffn(x, previous, w):
        out, load, state = expert_layer(rms_norm(x, w["ffn_norm"], shape.norm_eps), w, previous, shape)
        return merge(x, out, w, "ffn_merge"), state, load

    return ffn(mixer(x, w), previous, w)


def layer_forward(w, x, previous, first: bool, shape: CcaMoEShape):
    """One layer on rows x [B, S, E] with the states handed to it [B, S, R] (None before layer 0): its output, the states
    it hands on, and the tokens each router column got [columns] over all rows."""
    if previous is None:
        y, state, load = jax.lax.map(lambda row: block_forward(row, None, w, first, shape), x)
    else:
        y, state, load = jax.lax.map(lambda rows: block_forward(rows[0], rows[1], w, first, shape), (x, previous))
    return y, state, jnp.sum(load, axis=0)


def head_logits(x, final_norm, wte, shape: CcaMoEShape):
    """x [S, E] -> float32 logits [S, V], against the table [V, E]."""
    return jnp.einsum("se,ve->sv", rms_norm(x, final_norm, shape.norm_eps), wte, precision=HIGHEST)


# ------------------------------------------------------------------ the forward pass, layer by layer


def reference_layer(shape: CcaMoEShape, key, layer: int, precision: str = "f32") -> dict:
    """Layer `layer` of the seeded weights: the values the program is given, upcast (and, for the control, its kernels rounded to int8)."""
    return {name: _as_precision(name, value, precision) for name, value in layer_weights(shape, key, layer).items()}


def logits_layer_by_layer(shape: CcaMoEShape, seed: int, tokens, precision: str = "f32"):
    """Float32 logits [N, S, V] of `tokens` [N, S]; one layer's float32 weights live at a time."""
    tokens = jnp.asarray(tokens, jnp.int32)
    key = seed_key(seed)

    @functools.partial(jax.jit, static_argnums=(0,))
    def one_layer(layer, x, previous, key):
        return layer_forward(reference_layer(shape, key, layer, precision), x, previous, layer == 0, shape)[:2]

    @jax.jit
    def head(x, wte):
        return jax.lax.map(lambda row: head_logits(row, jnp.ones((shape.n_embd,), jnp.float32), wte, shape), x)

    wte = jax.jit(lambda key: _as_precision("wte", embedding(shape, key), precision))(key)
    x, state = jnp.take(wte, tokens, axis=0), None
    for layer in range(shape.n_layer):
        x, state = one_layer(layer, x, state, key)
    return head(x, wte)


# ------------------------------------------------------------------ loss and gradients, the whole model at once


def reference_params(shape: CcaMoEShape, key, precision: str = "f32") -> dict:
    """All weights, float32: `{"runs": [the layers stacked on a leading axis], "wte", "final_norm"}`. Traceable."""
    stacked = stack_weights(shape, key)
    return {"runs": [{name: jax.vmap(lambda w, name=name: _as_precision(name, w, precision))(value) for name, value in stacked.items()}],
            "wte": _as_precision("wte", embedding(shape, key), precision), "final_norm": jnp.ones((shape.n_embd,), jnp.float32)}


def head_loss_sum(x, outer, targets, shape: CcaMoEShape):
    """Sum of the cross entropy over the positions of one sequence, from x [S, E] after the last layer; `outer` holds
    `wte` and `final_norm`. In blocks of positions, each rematerialized."""
    s = x.shape[0]
    block = min(HEAD_BLOCK, s)
    pad = (-s) % block
    xp = jnp.pad(x, ((0, pad), (0, 0))).reshape(-1, block, x.shape[-1])
    tp = jnp.pad(targets, (0, pad)).reshape(-1, block)
    valid = (jnp.arange(s + pad) < s).reshape(-1, block)

    @jax.checkpoint
    def one_block(args):
        xb, tb, vb = args
        logits = head_logits(xb, outer["final_norm"], outer["wte"], shape)
        nll = jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(logits, tb[:, None], axis=-1)[:, 0]
        return jnp.sum(jnp.where(vb, nll, 0.0))

    return jnp.sum(jax.lax.map(one_block, (xp, tp, valid)))


def head_loss(x, outer, targets, shape: CcaMoEShape):
    """Mean cross entropy of rows x [B, S, E] after the last layer."""
    sums = jax.lax.map(lambda row: head_loss_sum(row[0], outer, row[1], shape), (x, targets))
    return jnp.sum(sums) / (x.shape[0] * x.shape[1])


def batch_loss(params, tokens, targets, shape: CcaMoEShape):
    """Mean cross entropy over every position of every row. tokens/targets [B, S]. The layers one after another in
    plain Python: no scan, layer 0 without a state handed to it and with its first merge passing the residual."""
    x, state = jnp.take(params["wte"], tokens, axis=0), None
    for layer in range(shape.n_layer):
        w = {name: value[layer] for name, value in params["runs"][0].items()}
        x, state, _ = layer_forward(w, x, state, layer == 0, shape)
    return head_loss(x, params, targets, shape)


# ------------------------------------------------------------------ the same loss and gradients, one layer at a time; AdamW


@functools.lru_cache(maxsize=None)
def _layer_programs(shape: CcaMoEShape, first: bool):
    """One layer on rows x [B, S, E] and the states handed to it: its forward pass (output, state handed on, tokens by
    column), and its backward pass from the layer's inputs and the cotangents of its output and of the state it hands
    on (the forward is computed again inside). Layer 0 (`first`) takes no state and gives no cotangent for one."""
    if first:
        forward = lambda w, x: layer_forward(w, x, None, True, shape)  # noqa: E731
    else:
        forward = lambda w, x, previous: layer_forward(w, x, previous, False, shape)  # noqa: E731

    def backward(dy, dstate, *inputs):
        _, pull = jax.vjp(lambda *a: forward(*a)[:2], *inputs)
        return pull((dy, dstate))

    return jax.jit(forward), jax.jit(backward, donate_argnums=(0, 1))


@functools.lru_cache(maxsize=None)
def _outer_programs(shape: CcaMoEShape):
    embed = jax.jit(lambda wte, tokens: jnp.take(wte, tokens, axis=0))
    head = jax.jit(jax.value_and_grad(lambda x, outer, targets: head_loss(x, outer, targets, shape), argnums=(0, 1)))
    # the table's gradient: what the head gave it plus the rows the embedding read
    add_embedding = jax.jit(lambda dwte, tokens, dx: dwte.at[tokens].add(dx), donate_argnums=(0,))
    return embed, head, add_embedding


def gradient_stream(shape: CcaMoEShape, layer_of, outer, tokens, targets):
    """The loss of a batch and then its gradient, layer by layer. A generator: first `(loss, the tokens every layer's
    router columns got [layers, columns] on the host)`, then `(i, gradient of layer i's leaves)` for i from the last layer
    to the first, then `("outer", gradient of wte and final_norm)`. `layer_of(i)` gives layer i's leaves; the forward
    pass keeps every layer's input and the state handed to it, and nothing else of a layer."""
    embed, head, add_embedding = _outer_programs(shape)
    tokens, targets = jnp.asarray(tokens, jnp.int32), jnp.asarray(targets, jnp.int32)
    inputs, states, loads = [embed(outer["wte"], tokens)], [None], []
    for i in range(shape.n_layer):
        args = (layer_of(i), inputs[-1]) if i == 0 else (layer_of(i), inputs[-1], states[-1])
        y, state, load = _layer_programs(shape, i == 0)[0](*args)
        inputs.append(y)
        states.append(state)
        loads.append(load)
    loss, (dx, d_outer) = head(inputs.pop(), outer, targets)
    yield float(loss), np.asarray(jax.device_get(loads), np.float64)
    dstate = jnp.zeros_like(states.pop())  # the last layer's state is dropped: nothing comes back for it
    for i in reversed(range(shape.n_layer)):
        x, previous = inputs.pop(), states.pop()
        if i == 0:
            dw, dx = _layer_programs(shape, True)[1](dx, dstate, layer_of(i), x)
        else:
            dw, dx, dstate = _layer_programs(shape, False)[1](dx, dstate, layer_of(i), x, previous)
        yield i, dw
    d_outer["wte"] = add_embedding(d_outer["wte"], tokens, dx)
    yield "outer", d_outer


def pairs_held(shape: CcaMoEShape, loads) -> float:
    """What the program's counter `moe_pairs_held` counts: the tokens the held experts got, the mean over the layers."""
    return float(loads[:, shape.expert_offset: shape.expert_offset + shape.experts_held].sum(axis=1).mean())


def skip_share(shape: CcaMoEShape, loads) -> float:
    """What the program's counter `moe_skip_share` counts: the share of a layer's tokens that chose the skip column, the mean over the layers."""
    return float((loads[:, -1] / loads.sum(axis=1)).mean()) if shape.skip_column else 0.0


def moved_bias(bias, load, speed: float):
    """The selection bias after a step: a column that got more than the mean of all columns loses `speed`, one that got less gains it."""
    return bias + speed * jnp.sign(jnp.mean(load) - load)


def loss_and_gradients(shape: CcaMoEShape, layers: list, outer: dict, tokens, targets):
    """The loss, its gradient as `(list of a layer's leaves, {"wte", "final_norm"})`, and the tokens every column got [layers, columns]."""
    stream = gradient_stream(shape, layers.__getitem__, outer, tokens, targets)
    loss, loads = next(stream)
    grads = dict(stream)
    return loss, ([grads[i] for i in range(shape.n_layer)], grads["outer"]), loads


def by_run(shape: CcaMoEShape, per_layer: list, outer: dict, stack=jnp.stack) -> dict:
    """Per-layer trees and the outer leaves as the comparison names them: `run0.<leaf>` stacked over the layers, `wte`, `final_norm`."""
    out = {f"run0.{name}": stack([per_layer[k][name] for k in range(shape.n_layer)]) for name in per_layer[0]}
    out.update(outer)
    return out


def _squares(tree):
    return jax.tree.map(lambda v: jnp.sum(v.astype(jnp.float32) ** 2), tree)


def leaf_norms(tree) -> dict:
    """Euclidean norm of every leaf of a tree in the run-stacked layout (`{"runs": [the layers' leaves stacked on a leading
    axis], "wte", "final_norm"}`): a run's leaf gives one norm per layer. Traceable (the program's side of the comparison uses it)."""
    out = {}
    for r, run in enumerate(tree["runs"]):
        for name, value in run.items():
            out[f"run{r}.{name}"] = jnp.sqrt(jnp.sum(value.astype(jnp.float32) ** 2, axis=tuple(range(1, value.ndim))))
    for name in OUTER:
        out[name] = jnp.sqrt(jnp.sum(tree[name].astype(jnp.float32) ** 2))
    return out


def train_steps(shape: CcaMoEShape, seed: int, batches, hyper: dict, precision: str = "f32",
                other_first_grad=None, other_scale: float = 1.0, keep_first_grad: bool = False, log=None) -> dict:
    """Follow the first `len(batches)` optimizer steps from the seeded weights.

    `batches` is a list of (tokens [B, S], targets [B, S]); `hyper` holds `lr` (a list, one learning rate per step),
    `b1`, `b2`, `eps`, `weight_decay`, `clip_norm`. AdamW as the configuration's optimizer block describes it:
    global-norm clipping, bias-corrected moments, decoupled decay scaled by the learning rate on DECAYED alone; after
    each update the selection bias of every layer moves by its rule from the step's loads.

    Memory. Adam's moments are never kept on the device: m_t = (1 - b1) sum_j b1^(t-j) g_j and v_t = (1 - b2) sum_j
    b2^(t-j) g_j^2, and each earlier clipped gradient g_j waits on the host (float32 as it was computed) and comes
    back a layer at a time beside the update that needs it.

    Returns the loss of each step, the norm of its whole gradient before clipping (`grad_norm`), the tokens the held
    experts got (`pairs_held`: the mean over the layers, the program's `moe_pairs_held`), the share that chose the skip
    column (`skip_share`, the program's `moe_skip_share`), every column's load a layer (`loads`), the norm of each leaf of
    the first clipped gradient, and the norm of each leaf of the parameters' change after the last step. With
    `other_first_grad` (someone else's first gradient as their optimizer got it, host arrays in the run-stacked layout, to
    be multiplied by `other_scale`: Adam's first moment after one step is (1 - b1) times the gradient) also the norm of
    each leaf of its difference from this one; with `keep_first_grad` this first gradient itself, on the host, in that
    layout. `log` is called with a line at each stage."""
    key = seed_key(seed)
    t0 = time.perf_counter()
    say = (lambda what: log(f"[reference] {time.perf_counter() - t0:7.2f} s {what}")) if log else (lambda what: None)
    b1, b2, steps, n = hyper["b1"], hyper["b2"], len(batches), shape.n_layer
    seeded = jax.jit(lambda key, i: {name: _as_precision(name, value, precision) for name, value in layer_weights(shape, key, i).items()})
    seeded_layer = lambda i: seeded(key, jnp.int32(i))  # noqa: E731
    seeded_table = jax.jit(lambda key: _as_precision("wte", embedding(shape, key), precision))
    seeded_outer = lambda: {"wte": seeded_table(key), "final_norm": jnp.ones((shape.n_embd,), jnp.float32)}  # noqa: E731
    scale_tree = jax.jit(lambda tree, factor: jax.tree.map(lambda g: g * factor, tree), donate_argnums=(0,))
    squares = jax.jit(_squares)
    difference = jax.jit(lambda ours, theirs: _squares(jax.tree.map(lambda a, b: a - other_scale * b.astype(jnp.float32), ours, theirs)))

    def one_leaf(name, p, gs, lr, t):
        m = (1 - b1) * sum(b1 ** (len(gs) - 1 - j) * g for j, g in enumerate(gs))
        v = (1 - b2) * sum(b2 ** (len(gs) - 1 - j) * g * g for j, g in enumerate(gs))
        step = (m / (1 - b1 ** t)) / (jnp.sqrt(v / (1 - b2 ** t)) + hyper["eps"])
        return p - lr * (step + hyper["weight_decay"] * p if name in DECAYED else step)

    update = jax.jit(lambda tree, gs, lr, t: {name: one_leaf(name, p, [g[name] for g in gs], lr, t) for name, p in tree.items()},
                     donate_argnums=(0,))
    move_bias = jax.jit(lambda bias, load: moved_bias(bias, load, shape.bias_update_speed))

    layers, outer = [seeded_layer(i) for i in range(n)], seeded_outer()
    say("the seeded weights")
    losses, held, skipped, all_loads, norms, extra = [], [], [], [], [], {}
    kept: list[tuple[list, dict]] = []  # the clipped gradients of the steps before, on the host: (a layer's leaves each, the outer leaves)
    first_squares = None
    for t, (tokens, targets) in enumerate(batches, start=1):
        loss, (grads, outer_grads), loads = loss_and_gradients(shape, layers, outer, tokens, targets)
        losses.append(loss)
        held.append(pairs_held(shape, loads))
        skipped.append(skip_share(shape, loads))
        all_loads.append(loads)
        norm = float(np.sqrt(sum(float(v) for tree in (*grads, outer_grads) for v in squares(tree).values())))
        norms.append(norm)
        factor = min(1.0, hyper["clip_norm"] / max(norm, 1e-30))
        grads, outer_grads = [scale_tree(g, factor) for g in grads], scale_tree(outer_grads, factor)
        say(f"step {t}: loss and gradients")
        if t == 1:
            first_squares = ([jax.device_get(squares(g)) for g in grads], jax.device_get(squares(outer_grads)))
            if other_first_grad is not None:
                theirs = [{name: other_first_grad["runs"][0][name][k] for name in grads[k]} for k in range(n)]
                gaps = [jax.device_get(difference(g, their)) for g, their in zip(grads, theirs)]
                outer_gaps = jax.device_get(difference(outer_grads, {name: other_first_grad[name] for name in outer_grads}))
                extra["first_grad_difference_norms"] = {name: np.sqrt(value) for name, value in by_run(shape, gaps, outer_gaps, np.stack).items()}
                say("the other first gradient measured against this one")
            if keep_first_grad:
                host = by_run(shape, jax.device_get(grads), jax.device_get(outer_grads), np.stack)
                extra["first_grad"] = {"runs": [{name[len("run0."):]: v for name, v in host.items() if name.startswith("run0.")}],
                                       **{name: host[name] for name in OUTER}}
        lr, tt = jnp.float32(hyper["lr"][t - 1]), jnp.float32(t)
        waits = t < steps  # a later step's update needs this gradient again
        host_layers = [None] * n
        for i in reversed(range(n)):
            if waits:
                host_layers[i] = jax.device_get(grads[i])
            layers[i] = update(layers[i], [*(before[0][i] for before in kept), grads[i]], lr, tt)
            if shape.bias_update_speed:  # AdamW left `beta` where it was: its gradient is zero
                layers[i] = {**layers[i], BIAS: move_bias(layers[i][BIAS], jnp.asarray(loads[i], jnp.float32))}
            grads[i] = None
        host_outer = jax.device_get(outer_grads) if waits else None
        outer = update(outer, [*(before[1] for before in kept), outer_grads], lr, tt)
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
    return {"losses": losses, "pairs_held": held, "skip_share": skipped, "loads": all_loads, "grad_norm": norms,
            "first_grad_norms": root(by_run(shape, *first_squares, np.stack)),
            "delta_norms": root(by_run(shape, moved, moved_outer, np.stack)), **extra}
