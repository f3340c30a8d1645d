"""The plain reference of the attention / state-space hybrid decoder (`model_type:
jamba`, as Hugging Face's `modeling_jamba.py` computes it): forward pass, loss,
gradients and AdamW in straightforward `jax.numpy`, float32, every matmul under
`jax.default_matmul_precision("highest")`. No kernels, no cache, no chunked-scan
algebra, and no import of the program under test: its weights come from
`benchmark/weights_hybrid.py`.

Architecture. Token embedding (no positional encoding of any kind), then layers that
are each `h = h + mixer(RMSNorm(h))`, `h = h + W_2(silu(W x) * (V x))` on `x = RMSNorm(h)`;
final RMSNorm, head tied to the embedding, mean cross entropy over all positions. The
mixer of layer `i` is grouped-query causal attention where `i % period == offset`
(no rotary transform, no bias, no QK norm), and elsewhere the Mamba-1 mixer, on
`u [S, d]`:

    (x, z)    = split(u W_in)
    x         = silu(conv(x))        causal depthwise convolution, K taps, bias, K - 1 zeros on the left
    (r, B, C) = split(x W_x)         each through an RMSNorm with a learned scale (jamba's own)
    dt        = softplus(r W_dt + b_dt)
    h_t       = exp(dt_t[:, None] * A) * h_{t-1} + (dt_t * x_t)[:, None] * B_t[None, :],  A = -exp(A_log), h_0 = 0
    y_t       = h_t C_t + D * x_t
    out       = (y * silu(z)) W_out

Departures from Hugging Face's module, none of which changes a number: the convolution's
kernel is kept `[K, d_inner]` (theirs `[d_inner, 1, K]`: the same taps, transposed), the
last tap weighing the current step; the state is laid out `[d_state, d_inner]`. The
state runs across the document boundaries of a packed row, as attention does in this
repo (there are no `segment_ids`): an assumption of the configuration, shared with the program.

Departures from a textbook forward, all for memory: the recurrence is a plain loop over
time, cut into blocks of TIME_BLOCK steps that are rematerialized in the backward pass;
attention is computed in blocks of query rows, the head in blocks of positions, the
mixer and the feed-forward of a layer each rematerialized. Training walks the layers one
at a time (`gradient_stream`: the forward pass keeps each layer's input, the backward
pass differentiates one layer at a time), which computes what `jax.grad` of `batch_loss`
computes (a test holds the two together) in five small programs instead of one large
one. At 1.5 B parameters float32 weights and one gradient fill the chip, so Adam's
moments cannot live beside them: `train_steps` says how it does without them.

`precision`: "f32" is the reference; "int8" rounds every kernel to 8 bits (symmetric,
one scale per output channel) before use, the nearest precision below the bfloat16 the
configuration states: the control of "How `correct` is decided".
"""

from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.weights_hybrid import HybridShape, embedding, layer_weights, run_weights, seed_key

HIGHEST = "highest"
Q_BLOCK = 512  # query rows per attention block
HEAD_BLOCK = 1024  # positions per head/loss block
TIME_BLOCK = 64  # steps of the recurrence per rematerialized block

# which axes of each kernel are summed over where it is used (the others are output channels)
CONTRACT_AXES = {"q_attn": (0,), "k_attn": (0,), "v_attn": (0,), "c_proj": (0, 1), "W": (0,), "V": (0,), "W_2": (0,),
                 "in_proj": (0,), "conv_kernel": (0,), "x_proj": (0,), "dt_proj": (0,), "out_proj": (0,), "wte": (1,)}
# what AdamW does not decay: the configuration's `weight_decay_groups_excluded: [embedding, norm, ssm]`
NOT_DECAYED = ("attention_norm", "ffn_norm", "final_norm", "dt_norm", "b_norm", "c_norm", "wte",
               "A_log", "D", "conv_bias", "dt_bias")


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


def attention_mixer(h, w):
    q = jnp.einsum("se,ehd->shd", h, w["q_attn"], precision=HIGHEST)
    k = jnp.einsum("se,ehd->shd", h, w["k_attn"], precision=HIGHEST)
    v = jnp.einsum("se,ehd->shd", h, w["v_attn"], precision=HIGHEST)
    return jnp.einsum("shd,hde->se", causal_attention(q, k, v), w["c_proj"], precision=HIGHEST)


def causal_conv(x, kernel, bias):
    """x [S, D], kernel [K, D], bias [D]: y_t = bias + sum_k kernel[k] * x_{t - (K - 1) + k}, zeros before the row starts."""
    taps, s = kernel.shape[0], x.shape[0]
    padded = jnp.pad(x, ((taps - 1, 0), (0, 0)))
    return bias + sum(kernel[k] * padded[k:k + s] for k in range(taps))


def recurrence(x, dt, a, b, c):
    """The selective scan as a loop over time from h_0 = 0. x, dt [S, D]; a [D, N]; b, c [S, N].
    Returns y [S, D] (without the skip)."""
    s, d = x.shape
    block = min(TIME_BLOCK, s)
    pad = (-s) % block  # steps of dt = 0 leave the state as it is
    a_t = a.T
    blocks = tuple(jnp.pad(v, ((0, pad), (0, 0))).reshape(-1, block, v.shape[-1]) for v in (dt, x, b, c))

    def step(h, inputs):
        dt_t, x_t, b_t, c_t = inputs
        h = jnp.exp(dt_t[None, :] * a_t) * h + (dt_t * x_t)[None, :] * b_t[:, None]
        return h, jnp.sum(h * c_t[:, None], axis=0)

    @jax.checkpoint
    def one_block(h, inputs):
        return jax.lax.scan(step, h, inputs)

    _, y = jax.lax.scan(one_block, jnp.zeros((a.shape[1], d), jnp.float32), blocks)
    return y.reshape(-1, d)[:s]


def ssm_mixer(u, w, shape: HybridShape):
    xz = jnp.einsum("se,ef->sf", u, w["in_proj"], precision=HIGHEST)
    x, z = jnp.split(xz, 2, axis=-1)
    x = jax.nn.silu(causal_conv(x, w["conv_kernel"], w["conv_bias"]))
    low = jnp.einsum("sf,fr->sr", x, w["x_proj"], precision=HIGHEST)
    r, b, c = jnp.split(low, [shape.dt_rank, shape.dt_rank + shape.d_state], axis=-1)
    r = rms_norm(r, w["dt_norm"], shape.norm_eps)
    b = rms_norm(b, w["b_norm"], shape.norm_eps)
    c = rms_norm(c, w["c_norm"], shape.norm_eps)
    dt = jax.nn.softplus(jnp.einsum("sr,rf->sf", r, w["dt_proj"], precision=HIGHEST) + w["dt_bias"])
    y = recurrence(x, dt, -jnp.exp(w["A_log"]), b, c)
    y = (y + w["D"] * x) * jax.nn.silu(z)
    return jnp.einsum("sf,fe->se", y, w["out_proj"], precision=HIGHEST)


def feed_forward(h, w):
    gate = jnp.einsum("se,ef->sf", h, w["W"], precision=HIGHEST)
    up = jnp.einsum("se,ef->sf", h, w["V"], precision=HIGHEST)
    return jnp.einsum("sf,fe->se", jax.nn.silu(gate) * up, w["W_2"], precision=HIGHEST)


def block_forward(x, w, kind: str, shape: HybridShape):
    """One pre-norm layer on one sequence. x [S, E]; w: the layer's leaves, float32."""

    @jax.checkpoint
    def mixer(x, w):
        h = rms_norm(x, w["attention_norm"], shape.norm_eps)
        return x + (attention_mixer(h, w) if kind == "attn" else ssm_mixer(h, w, shape))

    @jax.checkpoint
    def ffn(x, w):
        return x + feed_forward(rms_norm(x, w["ffn_norm"], shape.norm_eps), w)

    return ffn(mixer(x, w), w)


def head_logits(x, final_norm, wte, shape: HybridShape):
    """x [S, E] -> float32 logits [S, V], against the tied table."""
    return jnp.einsum("se,ve->sv", rms_norm(x, final_norm, shape.norm_eps), wte, precision=HIGHEST)


# ------------------------------------------------------------------ the forward pass, layer by layer


def reference_layer(shape: HybridShape, key, layer: int, precision: str = "f32") -> dict:
    """Layer `layer` of the seeded weights: the values the program is given, upcast
    (and, for the control, its kernels rounded to int8)."""
    raw = layer_weights(shape, key, layer, shape.kinds[layer])
    return {name: _as_precision(name, value, precision) for name, value in raw.items()}


def logits_layer_by_layer(shape: HybridShape, seed: int, tokens, precision: str = "f32"):
    """Float32 logits [N, S, V] of `tokens` [N, S]; one layer's float32 weights live at a time."""
    tokens = jnp.asarray(tokens, jnp.int32)
    key = seed_key(seed)

    @functools.partial(jax.jit, static_argnums=(0,))
    def one_layer(layer, x, key):
        w = reference_layer(shape, key, layer, precision)
        return jax.lax.map(lambda row: block_forward(row, w, shape.kinds[layer], shape), x)

    @jax.jit
    def head(x, wte):
        return jax.lax.map(lambda row: head_logits(row, jnp.ones((shape.n_embd,), jnp.float32), wte, shape), x)

    wte = jax.jit(lambda key: _as_precision("wte", embedding(shape, key), precision))(key)
    x = jnp.take(wte, tokens, axis=0)
    for layer in range(shape.n_layer):
        x = one_layer(layer, x, key)
    return head(x, wte)


# ------------------------------------------------------------------ loss and gradients, the whole model at once


def reference_params(shape: HybridShape, key, precision: str = "f32") -> dict:
    """All weights, float32: `{"runs": [a run's layers stacked on a leading axis, ...],
    "wte", "final_norm"}`. Traceable."""
    runs = []
    for kind, first, length in shape.runs:
        stacked = run_weights(shape, key, first, length, kind)
        runs.append({name: jax.vmap(lambda w, name=name: _as_precision(name, w, precision))(value)
                     for name, value in stacked.items()})
    return {"runs": runs, "wte": _as_precision("wte", embedding(shape, key), precision),
            "final_norm": jnp.ones((shape.n_embd,), jnp.float32)}


def head_loss_sum(x, outer, targets, shape: HybridShape):
    """Sum of the cross entropy over the positions of one sequence, from x [S, E] after the
    last layer; `outer` holds `wte` and `final_norm`. In blocks of positions, each rematerialized."""
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


def sequence_loss_sum(params, tokens, targets, shape: HybridShape):
    """Sum of the cross entropy over the positions of one sequence."""
    x = jnp.take(params["wte"], tokens, axis=0)
    for (kind, _, _), stacked in zip(shape.runs, params["runs"]):
        x, _ = jax.lax.scan(lambda x, w, kind=kind: (block_forward(x, w, kind, shape), None), x, stacked)
    return head_loss_sum(x, params, targets, shape)


def batch_loss(params, tokens, targets, shape: HybridShape):
    """Mean cross entropy over every position of every row. tokens/targets [B, S]."""
    sums = jax.lax.map(
        jax.checkpoint(lambda row: sequence_loss_sum(params, row[0], row[1], shape)), (tokens, targets)
    )
    return jnp.sum(sums) / (tokens.shape[0] * tokens.shape[1])


# ------------------------------------------------------------------ the same loss and gradients, one layer at a time; AdamW


@functools.lru_cache(maxsize=None)
def _layer_programs(shape: HybridShape, kind: str):
    """One layer of kind `kind` on rows x [B, S, E]: its forward pass, and its backward
    pass from the layer's input (the forward is computed again inside)."""
    forward = lambda w, x: jax.lax.map(lambda row: block_forward(row, w, kind, shape), x)  # noqa: E731

    def backward(w, x, dy):
        _, pull = jax.vjp(forward, w, x)
        return pull(dy)

    return jax.jit(forward), jax.jit(backward, donate_argnums=(2,))


@functools.lru_cache(maxsize=None)
def _outer_programs(shape: HybridShape):
    def head_loss(x, outer, targets):
        """Mean cross entropy of rows x [B, S, E] after the last layer."""
        sums = jax.lax.map(lambda row: head_loss_sum(row[0], outer, row[1], shape), (x, targets))
        return jnp.sum(sums) / (x.shape[0] * x.shape[1])

    embed = jax.jit(lambda wte, tokens: jnp.take(wte, tokens, axis=0))
    head = jax.jit(jax.value_and_grad(head_loss, argnums=(0, 1)))
    # the table's gradient: what the head gave it plus the rows the embedding read
    add_embedding = jax.jit(lambda dwte, tokens, dx: dwte.at[tokens].add(dx), donate_argnums=(0,))
    return embed, head, add_embedding


def gradient_stream(shape: HybridShape, layer_of, outer, tokens, targets):
    """The loss of a batch and then its gradient, layer by layer. A generator: first the
    loss, then `(i, gradient of layer i's leaves)` for i from the last layer to the first,
    then `("outer", gradient of wte and final_norm)`. `layer_of(i)` gives layer i's leaves;
    the forward pass keeps every layer's input, and nothing else of a layer."""
    embed, head, add_embedding = _outer_programs(shape)
    tokens, targets = jnp.asarray(tokens, jnp.int32), jnp.asarray(targets, jnp.int32)
    inputs = [embed(outer["wte"], tokens)]
    for i, kind in enumerate(shape.kinds):
        inputs.append(_layer_programs(shape, kind)[0](layer_of(i), inputs[-1]))
    loss, (dx, d_outer) = head(inputs.pop(), outer, targets)
    yield loss
    for i in reversed(range(shape.n_layer)):
        dw, dx = _layer_programs(shape, shape.kinds[i])[1](layer_of(i), inputs.pop(), dx)
        yield i, dw
    d_outer["wte"] = add_embedding(d_outer["wte"], tokens, dx)
    yield "outer", d_outer


def loss_and_gradients(shape: HybridShape, layers: list, outer: dict, tokens, targets):
    """Mean cross entropy over every position of every row, and its gradient as
    `(list of a layer's leaves, {"wte", "final_norm"})`."""
    stream = gradient_stream(shape, layers.__getitem__, outer, tokens, targets)
    loss = next(stream)
    grads = dict(stream)
    return loss, ([grads[i] for i in range(shape.n_layer)], grads["outer"])


def by_run(shape: HybridShape, per_layer: list, outer: dict, stack=jnp.stack) -> dict:
    """Per-layer trees and the outer leaves as the comparison names them: `run<r>.<leaf>`
    stacked over the run's layers, `wte`, `final_norm`."""
    out = {f"run{r}.{name}": stack([per_layer[first + k][name] for k in range(length)])
           for r, (_, first, length) in enumerate(shape.runs) for name in per_layer[first]}
    out.update(outer)
    return out


def _squares(tree):
    return jax.tree.map(lambda v: jnp.sum(v.astype(jnp.float32) ** 2), tree)


def leaf_norms(tree) -> dict:
    """Euclidean norm of every leaf of a tree in the run-stacked layout (`{"runs": [a
    run's leaves stacked on a leading axis, ...], "wte", "final_norm"}`): a run's leaf
    gives one norm per layer. Traceable (the program's side of the comparison uses it)."""
    out = {}
    for r, run in enumerate(tree["runs"]):
        for name, value in run.items():
            out[f"run{r}.{name}"] = jnp.sqrt(jnp.sum(value.astype(jnp.float32) ** 2, axis=tuple(range(1, value.ndim))))
    for name in ("wte", "final_norm"):
        out[name] = jnp.sqrt(jnp.sum(tree[name].astype(jnp.float32) ** 2))
    return out


def train_steps(shape: HybridShape, seed: int, batches, hyper: dict, precision: str = "f32",
                other_first_grad=None, other_scale: float = 1.0, keep_first_grad: bool = False, log=None) -> dict:
    """Follow the first `len(batches)` optimizer steps from the seeded weights.

    `batches` is a list of (tokens [B, S], targets [B, S]); `hyper` holds `lr` (a list,
    one learning rate per step), `b1`, `b2`, `eps`, `weight_decay`, `clip_norm`. AdamW as
    the configuration's optimizer block describes it: global-norm clipping, bias-corrected
    moments, decoupled decay scaled by the learning rate, no decay on NOT_DECAYED.

    Memory. The parameters and one gradient fill the chip at the cell's size, so Adam's
    moments are never kept: m_t = (1 - b1) sum_j b1^(t-j) g_j and v_t = (1 - b2) sum_j
    b2^(t-j) g_j^2, and each earlier clipped gradient g_j is computed again when a later
    step's update needs it, layer by layer beside the update (`gradient_stream`), from
    the parameters it was first computed at. Those are kept on the device for as long as
    a later step needs them; the parameters before the first step come from the seed
    again. Two steps so cost three gradients, and hold one set of parameters.

    Returns the loss of each step, the norm of each leaf of the first clipped gradient,
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
    seeded_table = jax.jit(lambda key: _as_precision("wte", embedding(shape, key), precision))
    seeded_outer = lambda: {"wte": seeded_table(key), "final_norm": jnp.ones((shape.n_embd,), jnp.float32)}  # noqa: E731
    scale_tree = jax.jit(lambda tree, factor: jax.tree.map(lambda g: g * factor, tree), donate_argnums=(0,))
    squares = jax.jit(_squares)
    difference = jax.jit(lambda ours, theirs: _squares(jax.tree.map(lambda a, b: a - other_scale * b.astype(jnp.float32), ours, theirs)))

    def update(in_place: bool):
        def one_leaf(name, p, gs, lr, t):
            m = (1 - b1) * sum(b1 ** (len(gs) - 1 - j) * g for j, g in enumerate(gs))
            v = (1 - b2) * sum(b2 ** (len(gs) - 1 - j) * g * g for j, g in enumerate(gs))
            step = (m / (1 - b1 ** t)) / (jnp.sqrt(v / (1 - b2 ** t)) + hyper["eps"])
            return p - lr * (step + hyper["weight_decay"] * p if name not in NOT_DECAYED else step)

        return jax.jit(lambda tree, gs, lr, t: {name: one_leaf(name, p, [g[name] for g in gs], lr, t) for name, p in tree.items()},
                       donate_argnums=(0,) if in_place else ())

    update_in_place, update_copy = update(True), update(False)

    # versions[j]: the parameters after j steps, as (layers, outer); the first come from the seed
    versions: dict[int, tuple] = {}
    layers, outer = [seeded_layer(i) for i in range(n)], seeded_outer()
    say("the seeded weights")
    losses, factors, extra = [], [], {}
    first_squares = None
    for t, (tokens, targets) in enumerate(batches, start=1):
        loss, (grads, outer_grads) = loss_and_gradients(shape, layers, outer, tokens, targets)
        losses.append(float(loss))
        norm = float(np.sqrt(sum(float(v) for tree in (*grads, outer_grads) for v in squares(tree).values())))
        factors.append(min(1.0, hyper["clip_norm"] / max(norm, 1e-30)))
        grads, outer_grads = [scale_tree(g, factors[-1]) for g in grads], scale_tree(outer_grads, factors[-1])
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
                                                for r in range(len(shape.runs))], "wte": host["wte"], "final_norm": host["final_norm"]}
        # the clipped gradients of the steps before, each computed again from where it was first computed
        earlier = []
        for j in range(1, t):
            then_layers, then_outer = versions.get(j - 1, (None, None))
            stream = gradient_stream(shape, then_layers.__getitem__ if then_layers else seeded_layer,
                                     then_outer or seeded_outer(), *batches[j - 1])
            next(stream)  # its loss is known
            earlier.append((stream, factors[j - 1]))
        # this version is needed again if a later step computes this step's gradient again: not the seeded one, not the last
        keep = 1 < t < steps
        step_update = update_copy if keep else update_in_place
        lr, tt = jnp.float32(hyper["lr"][t - 1]), jnp.float32(t)
        new_layers = list(layers)
        for i in reversed(range(n)):
            gs = [scale_tree(next(stream)[1], factor) for stream, factor in earlier] + [grads[i]]
            new_layers[i] = step_update(layers[i], gs, lr, tt)
            grads[i] = None
        outer_gs = [scale_tree(next(stream)[1], factor) for stream, factor in earlier] + [outer_grads]
        new_outer = step_update(outer, outer_gs, lr, tt)
        if keep:
            versions[t - 1] = (layers, outer)
        layers, outer = new_layers, new_outer
        del grads, outer_grads, earlier
        say(f"step {t}: update" + (f", with the gradients of {t - 1} earlier step(s) computed again" if t > 1 else ""))
    versions.clear()

    change = jax.jit(lambda now, then: _squares(jax.tree.map(lambda a, b: a - b, now, then)))
    moved = [jax.device_get(change(layers[i], seeded_layer(i))) for i in range(n)]
    moved_outer = jax.device_get(change(outer, seeded_outer()))
    say("the parameters' change")
    root = lambda named: {name: np.sqrt(value) for name, value in named.items()}  # noqa: E731
    return {"losses": losses, "first_grad_norms": root(by_run(shape, *first_squares, np.stack)),
            "delta_norms": root(by_run(shape, moved, moved_outer, np.stack)), **extra}
