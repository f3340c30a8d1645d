"""Dropless dispatch of tokens to the experts a layer holds, under static shapes.

An expert layer routes every token to `k` of `n_routed` experts and holds the experts
`[offset, offset + held)` (all of them, or one chip's share under expert parallelism).
Of the `k T` (token, choice) pairs only those of a held expert cost anything here:

    plan      sort the pairs by expert, those of experts not held last; give every held
              expert's group whole row tiles (a group is padded to a multiple of `tile`,
              so a tile has one expert); per row its pair, per pair its row, per expert
              its first tile and its tiles
    experts   for every held expert, for every tile of its group (a loop whose length is
              the group's: data dependent, `tile` rows a turn): gather the tile's tokens,
              `W_2(silu(W x) * (V x))` with the expert's three matrices, the tile's rows
              written where the plan put them
    combine   for every token the sum over its k pairs of the pair's row times its routing
              weight, a pair of an expert not held adding nothing; in one of two forms
              (`combine_plan`): `slabs`, a Pallas kernel over blocks of tokens that reads, for
              every held expert one of the block's tokens chose, the one run of rows they own
              (`ops/pallas/moe_combine.py`), or `gathers`, k gathers of [T, d]

No token is dropped at any load: the tables are sized for every pair landing on held
experts (`rows`), but the loops run over the tiles in use, so the matrix products grow
with the pairs held and not with `held x T`. All pairs on one expert is `k T / tile`
turns of one loop; none on any expert is no turn at all.

The backward pass is written by hand (`jax.custom_vjp`): a loop whose length is data
dependent has no transpose, and the forward is cheap to compute again beside it. It
walks the same tiles: the rows' cotangent is gathered by token, each expert's three
gradients are summed over its tiles in float32 and written once, the tokens' gradient
is the same sum by token over the rows' own, and the routing weights' gradient is the
row's output (before weighing) against its cotangent.

Nothing is added by token with a scatter: a first form did (`out.at[token].add(rows)` a
tile, indices sorted and unique), and the chip ran it a row at a time, 0.9 us a row, a
quarter of the cell's step (PERF.md section 6, PR 30). The second form summed by token with
k gathers of [T, d], `k T` rows at some 40 ns a row whatever the load, most of them the fill
value where a chip holds an eighth of the experts (PR 39: 136 ms of an 884 ms step). The plan
sorts a group's rows by token, so the rows a block of consecutive tokens owns of one expert
are one contiguous run: the `slabs` form reads that run whole and picks each token's row out
of it with a 0/1 matrix on the MXU, and costs by the (block, expert) pairs in use, none for an
expert no token of the block chose. At worst that is `held x T / COMBINE_BLOCK` slabs, so a
layer that holds many experts to a choice keeps the gathers: `combine_plan` draws the line
from the shapes, by these readings (`scripts/moe_mla_parts_bench.py --parts moe`, the sum alone
at 16,384 tokens, device ms from traces on a v5e, PR 39; `uniform` is every token its own draw of
k experts, the slabs' worst: every held expert in every block):

    width 2304, k 8 (held 8 in its cell)          width 2048, k 6 (held 16 in its cell)
    routing, held        gathers   slabs  in use  routing, held        gathers   slabs  in use
    all tokens one, 8     6.11     0.50      64   all tokens one, 16    3.92     0.48      64
    none held, 8          6.17     0.14       0   none held, 16         4.90     0.14       0
    uniform, 8            6.71     1.63     512   uniform, 16           4.78     2.62   1,024
    uniform, 16           6.69     2.94   1,024   uniform, 24           4.29     3.78   1,536
    uniform, 24           6.58     4.27   1,536   uniform, 32           4.30     4.95   2,048
    uniform, 32           6.39     5.58   2,048   uniform, 48           4.27     7.29   3,072
    uniform, 48           6.02     8.21   3,072   uniform, 64           4.21     9.61   4,096
    uniform, 64           5.88    10.85   4,096

A (block, expert) pair in use costs 2.4-2.9 us (its slab's DMA, a 256 x 272 x d product, the
weighted add), 5.5 where it is a block's only one (nothing hides its DMA), a block with none 1.8
(its zeros written); a gathered row 45-50 ns, real or not. Under uniform routing the forms meet
at 4.5 experts held to a choice, so the line is `held <= 4 k`; lumpier routing only helps the slabs.

PR 44 read the line again where a layer holds 64 of 512 experts to 10 choices of width 2048 (6.4 held to a choice; the same
script, `--moe_shapes qwen3next`, host ms a call, TPU v5 lite), and it stands. The sum alone under uniform routing: 8.42 by
gathers against 10.27 by slabs, as the table above says it would be. The layer's forward and backward together meet there
(23.07 against 22.92), and the slabs win it under lumpier routing (all tokens the same ten experts with one held 16.37 against
10.63, none held 13.21 against 7.34, all ten held 43.60 against 39.32), but a rematerialized block runs the layer's forward
once more (14.24 against 17.03 under uniform routing), and **in the cell** (`train-qwen3next-80b-16k`, whose routing stays near
uniform for most of a window: 1.25 pairs a token, the largest load 3 to 5 times the mean) the step read 668.7 ms with the
gathers against 692.0 with the slabs on traced runs (`moe/combine` 56.9 against 84.4 ms), 690-693 over eighteen untraced ones.
The products, the tiles' gathers and everything in the loops grow with the pairs held.

Products take the compute dtype's operands and accumulate in float32; `silu(a) * b` is
rounded to the compute dtype before the third product and a row to it before the sum by
token (which is in float32: a bfloat16 row exactly, times a float32 weight, added in float32
and rounded once, in both forms; the gathers add a token's rows in the order of its choices,
the slabs in the order of the held experts), as a dense SwiGLU's are.

The grouped products and the tiles' gathers are the plain form. Pallas kernels for them
(`grouped_matmul_*`) are the next step where a trace asks for them; PERF.md has the readings.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from modalities_tpu.ops.pallas.moe_combine import ALIGN, moe_combine, pad_rows
from modalities_tpu.ops import tiers
from modalities_tpu.telemetry import scopes

TILE = 256  # rows a turn of an expert's loop takes: two MXU passes high, and at most 255 padding rows a held expert
COMBINE_BLOCK = 256  # tokens a grid step of the sum by token's kernel takes: a slab of 272 rows and a 256 x 272 product an expert in use
SLABS_UP_TO_HELD_PER_CHOICE = 4  # `combine_plan`: slabs where a layer holds at most this many experts to a choice


class DispatchPlan(NamedTuple):
    """Where every (token, choice) pair of a held expert sits in the sorted, tile-aligned
    order. `P = k T` pairs, `R` rows (static, `rows_for`)."""

    row_pair: jax.Array  # int32 [R]: the pair of a row; P where the row is padding
    pair_row: jax.Array  # int32 [P]: the row of a pair; R where the pair's expert is not held
    first_tile: jax.Array  # int32 [held]: the first tile of an expert's group
    tiles: jax.Array  # int32 [held]: the tiles of an expert's group
    group_sizes: jax.Array  # int32 [held]: the pairs of an expert's group (its load)


class SlabTables(NamedTuple):
    """What the sum by token's kernel reads beside the rows (`slab_tables`); `T` padded to whole blocks."""

    pos: jax.Array  # int32 [T, held]: the row of the token's pair on that expert; -1 where it has none
    start: jax.Array  # int32 [T / block, held]: the first row that a block's tokens own of an expert's group
    count: jax.Array  # int32 [T / block, held]: how many they own: consecutive rows, a group being sorted by token

    @property
    def block(self) -> int:
        return self.pos.shape[0] // self.start.shape[0]


def rows_for(pairs: int, held: int, tile: int) -> int:
    """Rows the tables are sized for: every pair on held experts, each group's last tile padded."""
    return -(-(pairs + held * (tile - 1)) // tile) * tile


def plan_dispatch(choice, offset: int, held: int, tile: int = TILE) -> DispatchPlan:
    """`choice` int32 [T, k]: the experts every token chose, of all the router's."""
    pairs = choice.size
    rows = rows_for(pairs, held, tile)
    local = choice.reshape(-1).astype(jnp.int32) - offset
    key = jnp.where((local >= 0) & (local < held), local, held)  # experts not held sort last
    order = jnp.argsort(key, stable=True).astype(jnp.int32)  # within a group by pair, hence by token
    # Counts and look-ups in a table of `held` entries are compares against all of it and a sum: a scatter or a
    # gather costs the chip some 65 ns an index whatever the table's size (7 ms for these k T), a compare nothing.
    experts = jnp.arange(held, dtype=jnp.int32)
    sizes = jnp.sum(key[:, None] == experts[None, :], axis=0, dtype=jnp.int32)
    padded = -(-sizes // tile) * tile
    start = jnp.cumsum(sizes) - sizes  # of a group, among the sorted pairs
    row_start = jnp.cumsum(padded) - padded  # of a group, among the rows

    def of_group(table, group):
        return jnp.sum(jnp.where(group[:, None] == experts[None, :], table[None, :], 0), axis=1)

    # row -> pair: the row's group from the padded boundaries, its rank in the group, the sorted pair there
    row = jnp.arange(rows, dtype=jnp.int32)
    group = jnp.minimum(jnp.sum(row[:, None] >= (row_start + padded)[None, :], axis=1, dtype=jnp.int32), held - 1)
    rank = row - of_group(row_start, group)
    live = (rank >= 0) & (rank < of_group(sizes, group))
    row_pair = jnp.where(live, order[jnp.clip(of_group(start, group) + rank, 0, pairs - 1)], pairs)
    # pair -> row: where the pair stands among the sorted pairs, less its group's start, on its group's rows
    position = jnp.argsort(order).astype(jnp.int32)  # the inverse of a permutation is its argsort
    pair_row = jnp.where(key < held, of_group(row_start - start, key) + position, rows)
    return DispatchPlan(row_pair.astype(jnp.int32), pair_row.astype(jnp.int32), (row_start // tile).astype(jnp.int32),
                        (padded // tile).astype(jnp.int32), sizes)


@jax.named_scope(scopes.MOE_DISPATCH)
def _tile_rows(plan_row_pair, weights_flat, x, i, tile: int, k: int):
    """Tile `i`: its tokens (a padding row's lies past the last, which a gather fills with
    zeros), their rows of `x`, their routing weights."""
    pair = jax.lax.dynamic_slice(plan_row_pair, (i * tile,), (tile,))
    token = jnp.where(pair < weights_flat.shape[0], pair // k, x.shape[0])
    xs = jnp.take(x, token, axis=0, mode="fill", fill_value=0)
    weight = jnp.take(weights_flat, pair, mode="fill", fill_value=0)
    return token, xs, weight


def _dot(a, b, contract):
    return jax.lax.dot_general(a, b, ((contract[0], contract[1]), ((), ())), preferred_element_type=jnp.float32)


def combine_plan(tokens: int, k: int, held: int, width: int) -> str:
    """How the sum by token is taken, from shapes as one chip holds them: `"slabs"` (the kernel
    `ops/pallas/moe_combine.py`: a block of tokens reads, for every held expert one of its tokens chose,
    the one run of rows they own) or `"gathers"` (`_sum_by_token`: k gathers of [T, d]).

    Slabs cost by the (block, expert) pairs in use, at worst `held x T / COMBINE_BLOCK`; gathers cost
    `k T` rows whatever the load. The line between them is the module docstring's table."""
    if tokens < COMBINE_BLOCK or width % 128:  # under one block there is nothing to win; lanes the kernel was not compiled at
        return "gathers"
    return "slabs" if held <= SLABS_UP_TO_HELD_PER_CHOICE * k else "gathers"


def combine_form(tokens: int, k: int, held: int, width: int) -> str:
    """`combine_plan` where the kernel may run (`ops/tiers.py`'s one rule: on a TPU); the gathers elsewhere,
    and under a mesh (GSPMD partitions them with the rest of the layer; the kernel would want a plan of its
    own a shard: not written)."""
    from modalities_tpu.parallel.sharding import installed_mesh_size

    if not tiers.kernels_run() or installed_mesh_size() > 1:
        return "gathers"
    return combine_plan(tokens, k, held, width)


def combine_block(tokens: int) -> int:
    """Tokens a grid step of the kernel takes: `COMBINE_BLOCK`, or all of fewer (in whole sublane tiles)."""
    return min(COMBINE_BLOCK, -(-tokens // ALIGN) * ALIGN)


@jax.named_scope(scopes.MOE_DISPATCH)
def slab_tables(plan: DispatchPlan, tokens: int, k: int, tile: int) -> SlabTables:
    """The kernel's tables from the plan, with compares as `plan_dispatch` builds its own (no scatter,
    no gather of indices). A pair is on expert `e` where its row lies in `e`'s tiles; a token has at
    most one pair on an expert (a router's k choices are distinct)."""
    block = combine_block(tokens)
    row = plan.pair_row.reshape(tokens, k)[:, :, None]
    group_start = (plan.first_tile * tile)[None, None, :]
    on_expert = (row >= group_start) & (row < group_start + (plan.tiles * tile)[None, None, :])  # [T, k, held]
    pos = jnp.max(jnp.where(on_expert, row, -1), axis=1)
    pos = jnp.pad(pos, ((0, -tokens % block), (0, 0)), constant_values=-1)
    count = jnp.sum((pos >= 0).reshape(-1, block, pos.shape[1]), axis=1, dtype=jnp.int32)
    start = group_start[0] + jnp.cumsum(count, axis=0) - count  # a group's rows are its pairs in order of token
    return SlabTables(pos.astype(jnp.int32), start.astype(jnp.int32), count)


@jax.named_scope(scopes.MOE_COMBINE)
def _sum_by_token(rows, plan: DispatchPlan, tokens: int, k: int, weights=None):
    """For every token the sum over its k pairs of the pair's row of `rows` [R, d] (times the
    pair's weight, where given), in float32; a pair whose expert is not held has no row (its
    index lies past the last) and adds nothing. k gathers of [T, d], `k T` rows whatever the
    load: the form `combine_plan` calls `"gathers"`, for a layer that holds many experts to a
    choice, under a mesh and off the chip. The sum needs no add by token, which the chip runs a
    row at a time (PERF.md section 6, PR 30: 0.9 us a row against 20 to 40 ns a gathered row)."""
    pair_row = plan.pair_row.reshape(tokens, k)
    out = jnp.zeros((tokens, rows.shape[1]), jnp.float32)
    for j in range(k):
        picked = jnp.take(rows, pair_row[:, j], axis=0, mode="fill", fill_value=0).astype(jnp.float32)
        out = out + (picked if weights is None else picked * weights[:, j, None])
    return out


def _sum_by_slabs(rows, plan: DispatchPlan, slabs: SlabTables, tokens: int, k: int, weights=None):
    """The same sum by the kernel, in `rows`' dtype: every token's rows read out of the slab its block
    fetched for that expert, times the float32 weight, added in float32 by held expert (the gathers add
    by choice: the one difference), rounded once. `rows` carries the kernel's padding rows. Off a TPU the
    kernel is interpreted (tests)."""
    by_expert = None
    if weights is not None:
        with jax.named_scope(scopes.MOE_DISPATCH):  # the weights laid out by held expert: a pair's weight where its row is the token's on that expert
            on_expert = plan.pair_row.reshape(tokens, k)[:, :, None] == slabs.pos[:tokens, None, :]
            by_expert = jnp.sum(jnp.where(on_expert, weights[:, :, None], 0.0), axis=1)
            by_expert = jnp.pad(by_expert, ((0, slabs.pos.shape[0] - tokens), (0, 0)))
    with jax.named_scope(scopes.MOE_COMBINE):
        out = moe_combine(rows, slabs.pos, slabs.start, slabs.count, by_expert, block=slabs.block, interpret=tiers.interpret())
    return out[:tokens]


def _table_rows(plan: DispatchPlan, slabs: SlabTables | None) -> int:
    """Rows of the experts' output table: the plan's, and past them the zero rows the kernel's last slab reaches into."""
    rows = plan.row_pair.shape[0]
    return rows if slabs is None else rows + pad_rows(slabs.block)


def _forward(x, weights_flat, w_gate, w_up, w_down, plan: DispatchPlan, slabs: SlabTables | None, tile: int, k: int):
    def one_expert(ys, per_expert):
        gate, up, down, first, count = per_expert

        def one_tile(j, ys):
            _, xs, _ = _tile_rows(plan.row_pair, weights_flat, x, first + j, tile, k)
            with jax.named_scope(scopes.MOE_EXPERTS):
                h = (jax.nn.silu(_dot(xs, gate, ((1,), (0,)))) * _dot(xs, up, ((1,), (0,)))).astype(x.dtype)
                y = _dot(h, down, ((1,), (0,))).astype(x.dtype)
            return jax.lax.dynamic_update_slice(ys, y, ((first + j) * tile, 0))  # the tile's rows, where the plan put them

        return jax.lax.fori_loop(0, count, one_tile, ys), None

    # rows past the tiles in use are never read by a gather (every pair of a held expert has its row in a tile that was
    # written) and are read by a slab, times 0: they are zeros, never uninitialised
    ys, _ = jax.lax.scan(one_expert, jnp.zeros((_table_rows(plan, slabs), x.shape[1]), x.dtype), (w_gate, w_up, w_down, plan.first_tile, plan.tiles))
    weights = weights_flat.reshape(x.shape[0], k)
    if slabs is None:
        return _sum_by_token(ys, plan, x.shape[0], k, weights).astype(x.dtype)
    return _sum_by_slabs(ys, plan, slabs, x.shape[0], k, weights)


def _backward(x, weights_flat, w_gate, w_up, w_down, plan: DispatchPlan, slabs: SlabTables | None, dout, tile: int, k: int):
    dtype = x.dtype

    def one_expert(carry, per_expert):
        gate, up, down, first, count = per_expert

        def one_tile(j, carry):
            dxs, d_row_weight, d_gate, d_up, d_down = carry
            token, xs, weight = _tile_rows(plan.row_pair, weights_flat, x, first + j, tile, k)
            with jax.named_scope(scopes.MOE_COMBINE):  # the transpose of the sum by token, and of the weighing
                g = jnp.take(dout, token, axis=0, mode="fill", fill_value=0)
                g_weighted = (g.astype(jnp.float32) * weight[:, None]).astype(dtype)
            with jax.named_scope(scopes.MOE_EXPERTS):
                a, b = _dot(xs, gate, ((1,), (0,))), _dot(xs, up, ((1,), (0,)))
                sig = jax.nn.sigmoid(a)
                silu = a * sig
                h = (silu * b).astype(dtype)
                dh_unweighted = _dot(g, down, ((1,), (1,)))  # [tile, f]
                d_down = d_down + _dot(h, g_weighted, ((0,), (0,)))
                dh = dh_unweighted * weight[:, None]
                da = (dh * b * (sig + silu * (1.0 - sig))).astype(dtype)
                db = (dh * silu).astype(dtype)
                d_gate = d_gate + _dot(xs, da, ((0,), (0,)))
                d_up = d_up + _dot(xs, db, ((0,), (0,)))
                dx_rows = (_dot(da, gate, ((1,), (1,))) + _dot(db, up, ((1,), (1,)))).astype(dtype)
            with jax.named_scope(scopes.MOE_COMBINE):
                d_row_weight = jax.lax.dynamic_update_slice(
                    d_row_weight, jnp.sum(h.astype(jnp.float32) * dh_unweighted, axis=-1), ((first + j) * tile,))
            return jax.lax.dynamic_update_slice(dxs, dx_rows, ((first + j) * tile, 0)), d_row_weight, d_gate, d_up, d_down

        dxs, d_row_weight = carry
        zeros = lambda w: jnp.zeros(w.shape, jnp.float32)  # noqa: E731
        dxs, d_row_weight, d_gate, d_up, d_down = jax.lax.fori_loop(
            0, count, one_tile, (dxs, d_row_weight, zeros(gate), zeros(up), zeros(down)))
        return (dxs, d_row_weight), (d_gate.astype(gate.dtype), d_up.astype(up.dtype), d_down.astype(down.dtype))

    init = (jnp.zeros((_table_rows(plan, slabs), x.shape[1]), dtype), jnp.zeros((plan.row_pair.shape[0],), jnp.float32))
    (dxs, d_row_weight), (d_gate, d_up, d_down) = jax.lax.scan(
        one_expert, init, (w_gate, w_up, w_down, plan.first_tile, plan.tiles))
    # the transpose of the tiles' gathers of their tokens
    dx = _sum_by_token(dxs, plan, x.shape[0], k) if slabs is None else _sum_by_slabs(dxs, plan, slabs, x.shape[0], k)
    with jax.named_scope(scopes.MOE_COMBINE):
        d_weights = jnp.take(d_row_weight, plan.pair_row, mode="fill", fill_value=0).astype(weights_flat.dtype)
    return dx.astype(dtype), d_weights, d_gate, d_up, d_down


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8))
def _experts(x, weights_flat, w_gate, w_up, w_down, plan, slabs, tile, k):
    return _forward(x, weights_flat, w_gate, w_up, w_down, plan, slabs, tile, k)


def _experts_fwd(x, weights_flat, w_gate, w_up, w_down, plan, slabs, tile, k):
    return _forward(x, weights_flat, w_gate, w_up, w_down, plan, slabs, tile, k), (x, weights_flat, w_gate, w_up, w_down, plan, slabs)


def _experts_bwd(tile, k, residuals, dout):
    x, weights_flat, w_gate, w_up, w_down, plan, slabs = residuals
    grads = _backward(x, weights_flat, w_gate, w_up, w_down, plan, slabs, dout, tile, k)
    return (*grads, *jax.tree.map(lambda t: np.zeros(t.shape, jax.dtypes.float0), (plan, slabs)))


_experts.defvjp(_experts_fwd, _experts_bwd)


def routed_experts(x, choice, weights, w_gate, w_up, w_down, *, offset: int, plan: DispatchPlan | None = None, tile: int = TILE,
                   combine: str | None = None):
    """The held experts' part of an expert layer's output.

    x [T, d]; choice int32 [T, k] over all the router's experts, a token's k distinct; weights
    float32 [T, k] (normalised over all k chosen, held or not); w_gate, w_up [held, d, f], w_down
    [held, f, d]: the experts `offset .. offset + held - 1`. Returns [T, d] in x's dtype:
    for every token the weighted sum over its chosen experts that are held, zero where it
    chose none. Differentiable in x, weights and the three stacks. `combine` names the form of
    the sum by token (`"slabs"`, `"gathers"`) for a caller that has asked `combine_form` itself
    or wants one form (tests, the parts' bench); None asks it here."""
    tokens, k, held = x.shape[0], choice.shape[1], w_gate.shape[0]
    if plan is None:
        plan = plan_dispatch(choice, offset, held, tile)
    if combine is None:
        combine = combine_form(tokens, k, held, x.shape[1])
    slabs = slab_tables(plan, tokens, k, tile) if combine == "slabs" else None
    return _experts(x, weights.reshape(-1).astype(jnp.float32), w_gate, w_up, w_down, plan, slabs, tile, k)


def dense_over_experts(x, choice, weights, w_gate, w_up, w_down, *, offset: int):
    """The same sum the costly way, every held expert on every token with the weight zero
    where not chosen: what the dispatch is held against in tests."""
    held = w_gate.shape[0]
    onehot = jax.nn.one_hot(choice - offset, held, dtype=jnp.float32)  # [T, k, held]; an expert not held gives no one
    per_expert = jnp.einsum("tk,tke->te", weights.astype(jnp.float32), onehot)
    h = (jax.nn.silu(jnp.einsum("td,edf->etf", x, w_gate, preferred_element_type=jnp.float32))
         * jnp.einsum("td,edf->etf", x, w_up, preferred_element_type=jnp.float32)).astype(x.dtype)
    y = jnp.einsum("etf,efd->etd", h, w_down, preferred_element_type=jnp.float32)
    return jnp.einsum("etd,te->td", y, per_expert).astype(x.dtype)
