"""The gated delta rule (Yang, Kautz, Hatamizadeh, arXiv 2412.06464) over a row, in plain `jax.numpy`.

A value head `j` keeps a state `S [d_k, d_v]`, a matrix, decayed by a scalar a token and corrected by the
delta rule; it reads key head `j // r` (`r = value heads / key heads`). For `t = 0 .. S-1`, from `S = 0`:

    S      = exp(g_t) S                 g_t <= 0: the log of the decay
    delta  = beta_t (v_t - S^T k_t)
    S      = S + k_t delta^T
    o_t    = S^T q_t

`gated_delta_rule_recurrent` is that, a `lax.scan` over positions in float32: the form tests hold the other
against. `gated_delta_rule` is the chunked form the program runs (Yang et al., arXiv 2406.06484;
`transformers`' `torch_chunk_gated_delta_rule`), the same function of its inputs. In a chunk of `C` positions,
`G_i = sum_{m <= i} g_m` and `D_ij = exp(G_i - G_j)` for `i >= j`, else 0:

    L   = strictly_lower((beta k) k^T * D)                   [C, C]
    T   = (I + L)^-1                                         L is nilpotent: (I - L)(I + L^2)(I + L^4) ... (I + L^(C/2))
    U   = T (beta v)          W = T (beta k * exp(G))        [C, d_v], [C, d_k]
    then chunk by chunk, carrying S [d_k, d_v]:
    V'  = U - W S
    o   = (q * exp(G)) S + lower_with_diagonal(q k^T * D) V'
    S   = exp(G_C) S + (k * exp(G_C - G))^T V'

Everything a chunk needs but the state (`L`, `T`, `U`, `W`, the lower products) is computed for all chunks at
once, batched products on the MXU, under the scope `intra`; the pass over the chunks (`_walk`, under `state`) is a
pair of Pallas kernels that keep the state in VMEM (`ops/pallas/gated_delta_state.py`) where kernels run
(`ops/tiers.py`: on a TPU) and `walk_kernels` says they serve the shapes (head sizes that fill whole lane tiles, a
chunk of whole sublane tiles, a group's states within the kernels' VMEM budget), per shard of batch and heads under a
mesh; else, and off a TPU, one `lax.scan`. The row is padded to whole chunks with positions that change nothing
(`k = 0`, `beta = 0`, `g = 0`) and cut again. The state is carried through the whole row and starts at zero with it.

Precision. `g`, `G`, `D`, `L`, `T` and the carried `S` are float32, and `T` is built at precision `highest`
(a product of matrices whose entries grow with the chunk: one bfloat16 pass would cost it three digits). The
other products (`k k^T`, `q k^T`, `T` times its two right sides, and the three a chunk step takes with the state)
take operands in the inputs' dtype (bfloat16 in training, `S`, `T`, `U`, `W` rounded to it where they are an
operand) and accumulate in float32; with float32 inputs every product is float32 at `highest`.

Backward. The rule's own (`jax.custom_vjp` on `_rule`, PR 48), in the form autodiff had when it was told what to keep
(decided from `memory_analysis()` of the cell's step, PR 44: with the whole row's `intra` arrays kept for the backward,
the float32 `[C, C]` matrices of the series among them, the step compiled to 18.2 GiB against the chip's 15.75). The row
is walked in GROUPS of `GROUP_CHUNKS` chunks, an outer `lax.scan` that carries the state; a group is what is described
above (its chunks' `intra` batched, then the scan over them). The forward keeps the rule's inputs and the float32 state
that came into each group (`state_bytes`: one a group and a head); the backward is the same scan from the last group to
the first, carrying the state's cotangent: `jax.vjp` of a group from its kept state computes the group's matrices again,
one group's working set at a time (under the scope `group`), then pulls back. That second forward of every group is what
keeps the step under the chip's memory, and it stays. A THIRD one went with PR 48: a block under `full` remat used to run
the whole rule again in its recomputed forward, only to have `o` for the gated norm and the states for this backward. `o`
and the states go out under the names `KEPT_OUT` and `KEPT_STATES` (`jax.ad_checkpoint.checkpoint_name`), which bind
nothing outside a `jax.checkpoint` whose policy lists them; where the block's policy does
(`gpt2_model._remat_block_cls`, decided by `training/activation_checkpointing.attention_keep_plan`: 144 MiB a layer at the
cell's shapes) its recomputed forward holds no `intra` and no walk. `gdn_plan` says which (`forwards_a_step`, `backward`).
Inside a group the arrays the walk reads are rounded to the operands' dtype once, and `T = (I + L)^-1` has its own rule
(`dL = -T^T dT T^T`: `T` is all it keeps of the series). The walk's backward needs the state that came into each chunk,
for one group (64 MiB at 32 chunks and 32 heads of 128 x 128). The kernels' walk has its own rule (`custom_vjp`) that keeps
the walk's operands alone: its backward kernel sweeps the group's chunks forward once more with those states kept in VMEM,
then from the last chunk with `dS` resident. The plain scan's is autodiff over a rematerialized chunk step, which keeps the
states as its carry, in HBM. `gdn_plan` says which (`kernels`, `backward`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from modalities_tpu.ops import tiers
from modalities_tpu.telemetry import scopes

CHUNK = 64
GROUP_CHUNKS = 32  # chunks a rematerialized group holds: 2,048 positions, 1,024 `[C, C]` systems at 32 heads
HOW_T = "nilpotent_product"  # how `(I + L)^-1` is computed, for `gdn_plan`
WALK_KERNELS = ("gated_delta_state_fwd", "gated_delta_state_bwd")
# what a rematerialized block may keep of a call beside its input (`gpt2_model._remat_block_cls`): o as the rule returns it and the
# state that came into each group of chunks, so that the block's recomputed forward holds no `intra` and no walk
KEPT_OUT, KEPT_STATES = "gdn_rule_out", "gdn_rule_states"
_GROUPS = "rematerialized groups of chunks: a state a group kept, a group's matrices computed again; "
# what the backward is, for `gdn_plan`, by whether the walk's kernels run
BACKWARD = {False: _GROUPS + "autodiff over a rematerialized chunk step",
            True: _GROUPS + "the backward kernel sweeps a group's chunks forward, the states a chunk kept in VMEM, then backward"}
# and what a rematerialized block adds to it, by whether it keeps `KEPT_OUT` and `KEPT_STATES`: the rule's forwards a step
RECOMPUTED = {False: (3, "; the block's recomputed forward runs the rule once more"),
              True: (2, "; the block kept o and the group states, its recomputed forward holds no rule")}


def groups_of(tokens: int, chunk: int = CHUNK, group: int = GROUP_CHUNKS) -> tuple[int, int]:
    """(groups, chunks a group) a row of `tokens` positions is walked in: whole chunks, the groups alike, at most `group` chunks each."""
    chunks = -(-tokens // chunk)
    groups = -(-chunks // group)
    return groups, -(-chunks // groups)


def state_bytes(tokens: int, value_heads: int, key_dim: int, value_dim: int, chunk: int = CHUNK, group: int = GROUP_CHUNKS) -> int:
    """Bytes of the float32 states the backward keeps of one layer: one `[d_k, d_v]` a group of chunks and a value head."""
    return groups_of(tokens, chunk, group)[0] * value_heads * key_dim * value_dim * 4


def _dot(spec: str, a, b, dtype):
    """A product with operands in `dtype` and a float32 result; float32 operands multiply exactly."""
    precision = jax.lax.Precision.HIGHEST if jnp.dtype(dtype) == jnp.float32 else None
    return jnp.einsum(spec, a.astype(dtype), b.astype(dtype), precision=precision, preferred_element_type=jnp.float32)


def _mm(a, b):
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


@jax.custom_vjp
def _unit_lower_inverse(lower):
    """`(I + L)^-1` of a strictly lower triangular `L [..., C, C]` (float32): `L^C = 0`, so the inverse is the
    finite product `(I - L)(I + L^2)(I + L^4) ...`, log2(C) squarings and as many products, all on the MXU.
    Its backward is the inverse's own, `dL = -T^T dT T^T`, and keeps `T` alone of the series."""
    size = lower.shape[-1]
    eye = jnp.eye(size, dtype=lower.dtype)
    inverse, power, reach = eye - lower, lower, 2  # `inverse` holds the series up to L^(reach - 1)
    while reach < size:
        power = _mm(power, power)
        inverse = _mm(inverse, eye + power)
        reach *= 2
    return inverse


def _unit_lower_inverse_fwd(lower):
    inverse = _unit_lower_inverse(lower)
    return inverse, inverse


def _unit_lower_inverse_bwd(inverse, d_inverse):
    transposed = jnp.swapaxes(inverse, -1, -2)
    return (-_mm(_mm(transposed, d_inverse), transposed),)


_unit_lower_inverse.defvjp(_unit_lower_inverse_fwd, _unit_lower_inverse_bwd)


def walk_kernels(per_key_head: int, chunks: int, chunk: int, key_dim: int, value_dim: int, dtype) -> tuple[str, ...]:
    """The kernels the walk over a group of `chunks` chunks takes for these shapes, `()` for the plain scan: where kernels
    run and their planner serves the shapes (one key head a grid step is the least it may take, whatever a shard holds)."""
    if not tiers.kernels_run():
        return ()
    from modalities_tpu.ops.pallas.gated_delta_state import plan_heads

    return WALK_KERNELS if plan_heads(1, per_key_head, chunks, chunk, key_dim, value_dim, dtype) else ()


_PER_CHUNK, _STATE = (None, "batch", "heads", None, None, None), ("batch", "heads", None, None, None)


def _plain_walk(state, u, w, within, q_in, k_out, carry_decay):
    """`_walk` as one `lax.scan` over the chunks, the step rematerialized: its backward keeps a state a chunk."""
    dtype = u.dtype

    @jax.checkpoint
    def step(state, per_chunk):
        u_c, w_c, within_c, q_c, k_c, decay_c = per_chunk
        v_new = u_c - _dot("bhrid,bhrde->bhrie", w_c, state, dtype)
        out = _dot("bhrid,bhrde->bhrie", q_c, state, dtype) + _dot("bhrij,bhrje->bhrie", within_c, v_new, dtype)
        state = decay_c[..., None, None] * state + _dot("bhrid,bhrie->bhrde", k_c, v_new, dtype)
        return state, out.astype(dtype)

    return jax.lax.scan(step, state, (u, w, within, q_in, k_out, carry_decay))


def _walk(state, u, w, within, q_in, k_out, carry_decay):
    """The chunks of a group one after the other from the state that came in `[B, Hk, r, d_k, d_v]` float32: u, w,
    within, q_in, k_out `[N, B, Hk, r, C, d]` in the operands' dtype, carry_decay `[N, B, Hk, r]`. Returns the state
    that goes on and o `[N, B, Hk, r, C, d_v]`."""
    if not walk_kernels(u.shape[3], u.shape[0], u.shape[4], w.shape[-1], u.shape[-1], u.dtype):
        return _plain_walk(state, u, w, within, q_in, k_out, carry_decay)
    from modalities_tpu.ops.pallas.gated_delta_state import plan_heads, walk
    from modalities_tpu.parallel.sharding import per_shard

    def kernels(_axes, state, u, *rest):
        heads = plan_heads(u.shape[2], u.shape[3], u.shape[0], u.shape[4], state.shape[-2], u.shape[-1], u.dtype)  # of the key heads this shard holds
        return walk(state, u, *rest, heads=heads, interpret=tiers.interpret())

    return per_shard(kernels, (_STATE, *[_PER_CHUNK] * 5, _PER_CHUNK[:4]), (_STATE, _PER_CHUNK))(state, u, w, within, q_in, k_out, carry_decay)


def _group(state, q, k, v, g, beta, chunk: int):
    """`n` chunks from the state that came in: q, k `[B, n C, Hk, d_k]`, v `[B, n C, Hv, d_v]`, g, beta `[B, n C, Hv]`, state
    `[B, Hk, r, d_k, d_v]` float32. Returns the state that goes on and o `[B, n C, Hv, d_v]`."""
    b, length, hk, dk = q.shape
    hv, dv = v.shape[2], v.shape[3]
    r, n, dtype, f32 = hv // hk, length // chunk, v.dtype, jnp.float32
    with jax.named_scope(scopes.GDN_INTRA):
        # chunks lead (the scan walks them), then batch, key head, the value heads that read it
        qc = q.reshape(b, n, chunk, hk, dk).transpose(1, 0, 3, 2, 4)  # [N, B, Hk, C, d_k]
        kc = k.reshape(b, n, chunk, hk, dk).transpose(1, 0, 3, 2, 4)
        vc = v.reshape(b, n, chunk, hk, r, dv).transpose(1, 0, 3, 4, 2, 5)  # [N, B, Hk, r, C, d_v]
        gc = g.astype(f32).reshape(b, n, chunk, hk, r).transpose(1, 0, 3, 4, 2)  # [N, B, Hk, r, C]
        bc = beta.astype(f32).reshape(b, n, chunk, hk, r).transpose(1, 0, 3, 4, 2)
        cum = jnp.cumsum(gc, axis=-1)
        row, col = jnp.arange(chunk)[:, None], jnp.arange(chunk)[None, :]
        # exp of a difference that is never positive: above the diagonal it would be, and could overflow
        decay = jnp.where(row >= col, jnp.exp(jnp.where(row >= col, cum[..., :, None] - cum[..., None, :], 0.0)), 0.0)
        kk = _dot("nbhid,nbhjd->nbhij", kc, kc, dtype)[:, :, :, None]  # one product a key head, read by its r value heads
        qk = _dot("nbhid,nbhjd->nbhij", qc, kc, dtype)[:, :, :, None]
        lower = jnp.where(row > col, bc[..., :, None] * kk * decay, 0.0)
        solve = _unit_lower_inverse(lower)  # T [N, B, Hk, r, C, C]
        # what the chunk scan reads, rounded to the operands' dtype once
        u = _dot("nbhrij,nbhrjd->nbhrid", solve, vc.astype(f32) * bc[..., None], dtype).astype(dtype)
        w = _dot("nbhrij,nbhrjd->nbhrid", solve, kc.astype(f32)[:, :, :, None] * (bc * jnp.exp(cum))[..., None], dtype).astype(dtype)
        within = (qk * decay).astype(dtype)  # lower with its diagonal: what a chunk's own keys give its queries
        q_in = (qc.astype(f32)[:, :, :, None] * jnp.exp(cum)[..., None]).astype(dtype)  # against the state that came in
        k_out = (kc.astype(f32)[:, :, :, None] * jnp.exp(cum[..., -1:] - cum)[..., None]).astype(dtype)  # into the state that goes on
        carry_decay = jnp.exp(cum[..., -1])  # [N, B, Hk, r]

    with jax.named_scope(scopes.GDN_STATE):
        state, out = _walk(state, u, w, within, q_in, k_out, carry_decay)
        out = out.transpose(1, 0, 4, 2, 3, 5).reshape(b, length, hv, dv)  # [N, B, Hk, r, C, d_v] -> [B, n C, Hv, d_v]
    return state, out


def _by_group(a, groups: int):
    """`[B, S, ...]` as `[groups, B, S / groups, ...]`: what the scan over the groups walks."""
    return jnp.moveaxis(a.reshape(a.shape[0], groups, a.shape[1] // groups, *a.shape[2:]), 1, 0)


def _forward(q, k, v, g, beta, chunk: int, groups: int):
    """The outer scan over `groups` groups of whole chunks from a state of zeros: o `[B, S, Hv, d_v]` and the state that came
    into each group `[groups, B, Hk, r, d_k, d_v]` float32, all the rule's backward keeps beside its inputs."""
    b, s, hk, dk = q.shape
    hv, dv = v.shape[2], v.shape[3]

    def one(state, xs):
        state_out, out = _group(state, *xs, chunk)
        return state_out, (state, out)

    with jax.named_scope(scopes.GDN_STATE):  # the carry from group to group; a group names its own two scopes inside
        _, (states_in, out) = jax.lax.scan(one, jnp.zeros((b, hk, hv // hk, dk, dv), jnp.float32), tuple(_by_group(a, groups) for a in (q, k, v, g, beta)))
    return jnp.moveaxis(out, 0, 1).reshape(b, s, hv, dv), states_in


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _rule(q, k, v, g, beta, chunk: int, groups: int):
    """The rule over a row of `groups` groups of whole chunks, with a backward of its own (module docstring, "Backward.")."""
    return _forward(q, k, v, g, beta, chunk, groups)[0]


def _rule_fwd(q, k, v, g, beta, chunk: int, groups: int):
    out, states_in = _forward(q, k, v, g, beta, chunk, groups)
    # names bind nothing outside a `jax.checkpoint` whose policy lists them (`gpt2_model._remat_block_cls`)
    return checkpoint_name(out, KEPT_OUT), (q, k, v, g, beta, checkpoint_name(states_in, KEPT_STATES))


def _rule_bwd(chunk: int, groups: int, kept, d_out):
    """From the last group to the first, carrying the state's cotangent: each group computed again from the state that came
    into it (`jax.vjp` of `_group`: one group's matrices live at a time), then its backward."""
    *inputs, states_in = kept

    def one(d_state, xs):
        state, d_o, *group_inputs = xs
        # under a scope of its own: `jax.vjp` writes its transforms round the first scope inside it (`jvp(group)/intra/...`)
        pull = jax.vjp(lambda state, *ins: jax.named_scope(scopes.GDN_GROUP)(_group)(state, *ins, chunk), state, *group_inputs)[1]
        d_state, *d_inputs = pull((d_state, d_o))
        return d_state, tuple(d_inputs)

    with jax.named_scope(scopes.GDN_STATE):
        # nothing reads the state the last group leaves: its cotangent is zero
        _, d_inputs = jax.lax.scan(one, jnp.zeros_like(states_in[0]), (states_in, *(_by_group(a, groups) for a in (d_out, *inputs))), reverse=True)
    return tuple(jnp.moveaxis(d, 0, 1).reshape(a.shape) for d, a in zip(d_inputs, inputs))


_rule.defvjp(_rule_fwd, _rule_bwd)


def gated_delta_rule(q, k, v, g, beta, *, chunk: int = CHUNK, group_chunks: int = GROUP_CHUNKS):
    """q, k `[B, S, Hk, d_k]` (already normalised and scaled by the caller), v `[B, S, Hv, d_v]`, g and beta
    `[B, S, Hv]` float32 (`g <= 0`). Returns o `[B, S, Hv, d_v]` in v's dtype. The chunked form (module docstring)."""
    s, hk, hv = q.shape[1], q.shape[2], v.shape[2]
    if hv % hk:
        raise ValueError(f"gated_delta_rule: {hv} value heads do not share {hk} key heads evenly")
    groups, chunks_a_group = groups_of(s, chunk, group_chunks)
    pad = groups * chunks_a_group * chunk - s
    if pad:  # positions that change nothing: no key, no correction, no decay
        q, k, v = (jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0))) for a in (q, k, v))
        g, beta = (jnp.pad(a, ((0, 0), (0, pad), (0, 0))) for a in (g, beta))
    out = _rule(q, k, v, g, beta, chunk, groups)
    return out[:, :s] if pad else out


def gated_delta_rule_recurrent(q, k, v, g, beta):
    """The recurrence of the module docstring position by position, float32: what the chunked form is held against."""
    b, s, hk, dk = q.shape
    hv, dv = v.shape[2], v.shape[3]
    r, f32 = hv // hk, jnp.float32
    q, k = (jnp.repeat(a.astype(f32), r, axis=2) for a in (q, k))  # value head j reads key head j // r
    highest = jax.lax.Precision.HIGHEST

    def step(state, at):
        q_t, k_t, v_t, g_t, beta_t = at  # [B, Hv, d], [B, Hv]
        state = state * jnp.exp(g_t)[..., None, None]
        delta = beta_t[..., None] * (v_t - jnp.einsum("bhkv,bhk->bhv", state, k_t, precision=highest))
        state = state + k_t[..., :, None] * delta[..., None, :]
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t, precision=highest)

    by_position = lambda a: jnp.moveaxis(a.astype(f32), 1, 0)  # noqa: E731
    _, out = jax.lax.scan(step, jnp.zeros((b, hv, dk, dv), f32), tuple(by_position(a) for a in (q, k, v, g, beta)))
    return jnp.moveaxis(out, 0, 1).astype(v.dtype)
