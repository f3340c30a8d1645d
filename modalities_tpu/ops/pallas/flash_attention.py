"""Pallas TPU flash attention — the framework's `dao_flash` tier
(replaces the reference's flash-attn CUDA dependency, pyproject.toml:48,
gpt2_model.py:643-655).

Design (FlashAttention-2 style, TPU-first):
- forward: k/v stream through VMEM one [BK, D] tile per grid step while fp32
  accumulators (acc, m, l) persist in VMEM scratch — VMEM stays O(BQ*D + BK*D)
  regardless of sequence length; logsumexp is saved for the backward. v, the output,
  its cotangent and dv take their width from v (`head_dim_v`), q, k, dq and dk from q:
  latent attention has 192 and 128, and pads neither to the other.
- backward: one kernel, `flash_attention_bwd` (PR 31), walks the plan's kv-major table once
  and recomputes a tile's probabilities p and ds = p (dp - delta) once from the saved
  logsumexp (no S x S materialization anywhere): 5 products a tile, the scores transposed. dk and dv accumulate
  over a kv tile's q tiles in [BK, D] scratch; dq's terms for a q tile come from every kv
  tile at or below it, so one (batch, q head)'s whole dq row stays in VMEM as float32
  ([S/BQ, BQ, D], zeroed at the head's first pair, cast and written at its last) under a
  raised `vmem_limit_bytes`. `backward_plan` picks it from the shapes wherever its counted
  need (`fused_backward_vmem_bytes`) is within `FUSED_BWD_VMEM_BUDGET`. Rows too long for
  that (32k x 128), and ring attention's backward (which calls them by name with merged
  lse / delta), run the two kernels the fused one replaced, with the same streaming
  structure and the same sums in the same order: `flash_bwd_dq` over q blocks (kv
  innermost) and `flash_bwd_dkv` over kv blocks (q innermost), each recomputing p and ds
  (7 products a tile between them). GQA folds the q-head group into the kv index map;
  dk/dv are accumulated per q-head and group-summed outside the kernel.
- every score tile gets only the work its place asks for (PR 25). `tile_plan`
  classifies the [BQ, BK] tiles at trace time from the static shapes and every
  kernel runs on a grid (batch, head, pair) over the tiles that compute at all, looked
  up in a scalar-prefetched table (`pltpu.PrefetchScalarGridSpec`; index maps read the
  pair's q and kv tile from it, init and finish fire on a row's first and last pair):
  a tile above the causal diagonal is no grid step and fetches nothing; a tile wholly
  below it runs a body without iota, compare and select; a tile the diagonal crosses
  is masked, and where it is square and on the diagonal (BQ == BK) only its lower
  triangle is walked, column by column in sub-blocks of 256: 5/8 of its matmuls.
  `causal=False` (ring attention's off-diagonal hops) is the same code over the whole
  rectangle, every tile interior.
- a window (PR 38; `window=W` on a causal call over one sequence: position i sees itself
  and the W - 1 before it) is a second edge of the same plan, `i - j = W`, and one more
  term of the same mask, not a second set of kernels: a tile wholly behind the edge is no
  grid step; a tile the edge crosses is masked from that side (`_WINDOW`, beside `_MASKED`
  where the diagonal crosses it too: a window under a block); where the tile is square and
  the edge lies on its own diagonal (W a multiple of the block: `_EDGE`) only what lies
  above that diagonal is walked, in the diagonal tile's sub-blocks mirrored, so that at
  1024 x 1024 and W 1024 a q tile computes 20 of 32 sub-squares for its diagonal and edge
  tiles where two whole tiles would be 32. Forward, fused backward and the two kernels read
  the same flags. Windowed calls carry their own `name=`
  (`flash_attention_window_{fwd,bwd,bwd_dq,bwd_dkv}`): readers count by label and per call.
  Still whole: the fused backward's resident dq row (a kv tile's window writes a band of it
  only), and a tile both lines cross (masked whole, not walked). Not written: a window in
  a non-causal call or over two sequences of different length (ring attention's hops), which
  `tile_plan` refuses. `window=None` gives the tables and the kernels of before, array for array.
- block sizes: this module's own defaults are 128 (the MXU tile), but the shipped
  configuration is 1024x1024 via the ops/attention.py dispatch wrapper
  (`tuning_tables/v5e.json`), with automatic step-down for short sequences; interpret
  mode keeps CPU tests exact. What the chip says of each choice, and of the fused
  backward beside the two kernels (`scripts/moe_mla_parts_bench.py --parts flash`), is in
  PERF.md, sections 5 and 6.
- TPU layout: the per-row statistics (lse out of the forward, lse and delta into the backward) cross HBM as rows of
  numbers, [B, H, 1, S] float32 in (1, 1, 1, block_q) blocks (PR 42): the chip lays such an array out dense (`T(1,128)`:
  2 MiB at 2 x 32 x 8192), and the last two block dims equal the array's or tile (.., 128), which Mosaic asks for: a
  row of several tiles has blocks of a multiple of 128 and any other row is one tile (`_pick_block`; `init_params`' dummy
  forward of 8). Before, they were [B, H, S, 1] columns, a lane tile of 512 bytes a number (256 MiB each at that
  shape; PERF.md section 6, PR 42). The forward keeps its running max and sum as lane-replicated [block_q, 128] columns
  and turns them into the row once a q tile (`_finish`: one transpose on the XLU). The backward kernels never need the
  column: they take the scores transposed (`k q^T`, [block_k, block_q]: `_transposed_terms`), where a q row's statistic
  is a sublane broadcast of the stored row, dv and dk are plain products and only dq contracts over a transposed operand.
  What a rematerialized block KEEPS of a call from its forward to its backward (`kept=True`, PR 41) is o and that lse, as
  the kernel wrote it and as the backward kernel reads it.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

# ---------------------------------------------------------------- the tile plan

# bits of a pair's flags, as the kernels read them from the prefetched table
_FIRST, _LAST, _MASKED, _DIAGONAL, _WINDOW, _EDGE = 1, 2, 4, 8, 16, 32
_KIND = _MASKED | _DIAGONAL | _WINDOW | _EDGE  # a pair's class: which body it runs
# side of the squares an aligned diagonal tile is walked in (a module constant, not a
# knob: tests shrink it to cross the diagonal at interpret-mode sizes)
_DIAG_SUB_BLOCK = 256


class TilePlan(NamedTuple):
    """Which score tiles a call computes, in the order its kernels walk them.

    `q_major` and `kv_major` are int32 [3, n] tables (q tile, kv tile, flags) over the
    same n pairs: kv innermost for `fwd` and `bwd_dq`, q innermost for `bwd` and `bwd_dkv`.
    Flags: _FIRST / _LAST pair of its row in that order (init / finish fire there),
    _MASKED (the diagonal crosses the tile: whole-tile mask), _DIAGONAL (the tile is
    square and sits on the diagonal: only its lower triangle is walked); with a window,
    _WINDOW (the window's edge `i - j = W` crosses the tile: whole-tile mask from the other
    side, beside _MASKED where the diagonal crosses it too) and _EDGE (the tile is square and
    its own diagonal IS the window's edge: only what lies above that diagonal is walked)."""

    q_major: np.ndarray
    kv_major: np.ndarray
    computed: int  # pairs = grid steps of each kernel per (batch, head)
    interior: int  # wholly at or below the diagonal (and inside the window): no mask
    diagonal: int  # crossed by the diagonal: masked, or walked below it
    skipped_steps: int  # grid steps that compute nothing: 0 (6 of 16 at S 4096 before PR 25)
    window_edge: int = 0  # crossed by the window's edge (a tile both lines cross counts here and under `diagonal`)

    def counts(self) -> dict[str, int]:
        names = ("computed", "interior", "diagonal", "skipped_steps")
        return {name: getattr(self, name) for name in names + (("window_edge",) if self.window_edge else ())}


@functools.lru_cache(maxsize=None)
def tile_plan(seq_q: int, seq_k: int, block_q: int, block_k: int, causal: bool, window: int | None = None) -> TilePlan:
    """Classify every [block_q, block_k] score tile from the static shapes. Causal
    means q position i sees k positions <= i (both counted from 0). A tile above the
    diagonal is no pair at all; the rest are interior or diagonal. A kv tile no q row
    can see (seq_k > seq_q) keeps one fully masked pair so that its dk/dv are written.

    `window` W (causal, seq_q == seq_k) hides also what lies W or more behind: i sees j with
    `i - W < j <= i`, itself and the W - 1 before it. A tile wholly behind the window's edge is
    no pair at all, as one above the diagonal is; one the edge crosses is masked from that side,
    and where it is square with the edge on its own diagonal (W a multiple of the block) it is
    walked in sub-blocks above that diagonal as the diagonal's tile is below its own.
    `window=None` gives the tables it always gave, array for array."""
    num_q, num_k = seq_q // block_q, seq_k // block_k
    iq, jk = np.meshgrid(np.arange(num_q), np.arange(num_k), indexing="ij")
    offset = iq * block_q - jk * block_k  # a tile's first q position less its first k position
    if causal:
        needed = offset + block_q - 1 >= 0
        interior = offset >= block_k - 1
        needed[num_q - 1, ~needed.any(axis=0)] = True
    else:
        needed = interior = np.ones_like(iq, dtype=bool)
    on_diagonal = needed & ~interior & (block_q == block_k) & (iq == jk)
    kind = np.where(interior, 0, np.where(on_diagonal, _DIAGONAL, _MASKED))
    crossed = np.zeros_like(needed)
    if window is not None:
        if not causal or seq_q != seq_k or window < 1:
            raise ValueError("flash attention: a window is causal, over one sequence (seq_q == seq_k), and at least 1 wide")
        needed = needed & (offset - (block_k - 1) < window)  # the tile's nearest pair (first q row, last k column) is inside the window
        crossed = needed & (offset + block_q - 1 >= window)  # its farthest pair (last q row, first k column) is not
        on_edge = crossed & interior & (block_q == block_k) & (offset == window)
        # a tile both lines cross (W under a block) is masked whole from both sides: no walk in sub-blocks there
        kind = np.where(on_edge, _EDGE, np.where(crossed, np.where(interior, _WINDOW, _MASKED | _WINDOW), kind))

    def table(pairs, row):
        """[3, n] for `pairs` ([n, 2], sorted with column `row` outermost): a row's
        first and last pair are where that column changes."""
        first = np.r_[True, pairs[1:, row] != pairs[:-1, row]]
        flags = kind[pairs[:, 0], pairs[:, 1]] | first * _FIRST | np.r_[first[1:], True] * _LAST
        out = np.stack([pairs[:, 0], pairs[:, 1], flags]).astype(np.int32)
        out.setflags(write=False)  # the plan is cached: every caller gets these arrays
        return out

    pairs = np.argwhere(needed)  # sorted by q tile, then kv tile
    computed, n_diagonal = len(pairs), int((needed & ~interior).sum())
    return TilePlan(
        table(pairs, 0), table(pairs[np.lexsort((pairs[:, 0], pairs[:, 1]))], 1),
        computed, int((needed & interior & ~crossed).sum()), n_diagonal, 0, int(crossed.sum()),
    )


def _sub_block(block_q: int, block_k: int) -> int:
    """Side of the squares an aligned diagonal tile is walked in."""
    return _DIAG_SUB_BLOCK if block_q == block_k and block_q % _DIAG_SUB_BLOCK == 0 else block_q


def _rectangles(cls: int, block_q: int, block_k: int):
    """The static rectangles (row start, rows, col start, cols, masked) a tile of one
    class is computed in. Interior and whole-tile masked: the tile itself. Diagonal:
    column by column in sub-blocks, each given only the q rows from its own first row
    down — the square on the diagonal, masked, and what lies below it, unmasked. On the
    window's edge the mirror image: each column of sub-blocks is given the q rows down to
    its own last row — what lies above the square on the tile's diagonal, unmasked, and that square, masked."""
    sub = _sub_block(block_q, block_k)
    if not cls & (_DIAGONAL | _EDGE) or sub == block_q:
        return [(0, block_q, 0, block_k, cls != 0)]
    out = []
    for lo in range(0, block_q, sub):
        if cls & _EDGE and lo:
            out.append((0, lo, lo, sub, False))
        out.append((lo, sub, lo, sub, True))
        if cls & _DIAGONAL and lo + sub < block_q:
            out.append((lo + sub, block_q - lo - sub, lo, sub, False))
    return out


def _by_class(classes, flags, offset, block_q, block_k, body, window=None):
    """Run `body(rows, cols, mask)` over the rectangles of this pair's class. `mask` is None
    where nothing is hidden, else `(causal, edge)`: how far the rectangle's first q position lies
    past its first k position, for the diagonal's side of the mask, and that less the window, for
    the window's side (each None where that side hides nothing here; a static 0 on an aligned
    square, else a traced scalar). Only the classes the plan holds are traced at all."""
    for cls in classes:
        def run(cls=cls):
            for r0, rows, c0, cols, masked in _rectangles(cls, block_q, block_k):
                if not masked:
                    mask = None
                elif cls & (_DIAGONAL | _EDGE):
                    mask = (0, None) if cls & _DIAGONAL else (None, 0)
                else:
                    mask = (offset if cls & _MASKED else None, offset - window if cls & _WINDOW else None)
                body(pl.ds(r0, rows), pl.ds(c0, cols), mask)

        if len(classes) == 1:
            run()
        else:
            pl.when(flags & _KIND == cls)(run)


def _pair(plan_ref, num_pairs, block_q, block_k):
    """This grid step's flags, and how far its tile's first q position lies past its
    first k position, from the prefetched plan (a [3, num_pairs] table laid out flat)."""
    t = pl.program_id(2)
    return plan_ref[2 * num_pairs + t], plan_ref[t] * block_q - plan_ref[num_pairs + t] * block_k


def _keep(shape, causal, edge, transposed=False):
    """The mask of a rectangle whose first q position is `causal` past its first k position
    (the diagonal's side: row + causal >= col) and `edge` past it less the window (the window's
    side: row + edge < col); a side that is None hides nothing, a static 0 is an aligned square.
    `transposed`: the scores are [cols, rows], k positions down and q positions across."""
    row = jax.lax.broadcasted_iota(jnp.int32, shape, int(transposed))
    col = jax.lax.broadcasted_iota(jnp.int32, shape, 1 - int(transposed))
    static_zero = lambda side: isinstance(side, int) and side == 0  # noqa: E731
    keep = None
    if causal is not None:
        keep = row >= col if static_zero(causal) else row + causal >= col
    if edge is not None:
        inside = row < col if static_zero(edge) else row + edge < col
        keep = inside if keep is None else keep & inside
    return keep


def _scores(q, k):
    return jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)


def _masked_scores(q, k, mask, transposed=False):
    s = _scores(k, q) if transposed else _scores(q, k)  # [C, R] or [R, C]
    return s if mask is None else jnp.where(_keep(s.shape, *mask, transposed), s, NEG_INF)


# --------------------------------------------------------------------------- fwd


def _stat_lanes(block_q: int, block_k: int) -> int:
    """Lanes the forward's running max and sum are kept in: 128 (a vreg's) wherever
    every rectangle's width is a multiple of it, which is every shape a TPU runs at
    blocks of 128 and up; the widths' common divisor at interpret-mode sizes."""
    return math.gcd(128, block_k, _sub_block(block_q, block_k))


def _across(x, width: int):
    """A lane-replicated [R, W] statistic as [R, width]."""
    lanes = x.shape[1]
    if width <= lanes:
        return x[:, :width]
    return pltpu.repeat(x, width // lanes, 1) if width % lanes == 0 else jnp.broadcast_to(x[:, :1], (x.shape[0], width))


def _fwd_kernel(plan_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref,
                *, sm_scale, block_q, block_k, num_pairs, classes, window=None):
    flags, offset = _pair(plan_ref, num_pairs, block_q, block_k)
    lanes = m_ref.shape[1]

    @pl.when(flags & _FIRST != 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    def rectangle(rows, cols, mask):
        q = q_ref[0, 0, rows, :].astype(jnp.float32) * sm_scale  # [R, D]
        k = k_ref[0, 0, cols, :].astype(jnp.float32)  # [C, D]
        v = v_ref[0, 0, cols, :].astype(jnp.float32)
        s = _masked_scores(q, k, mask)
        # The running max is kept replicated over `lanes` lanes and the running sum as
        # `lanes` partial sums, folded once at the end: [R, 1] columns cost a row of
        # vregs an operation whatever the tile's width, which made a 1024 x 512 tile
        # as dear as a 1024 x 1024 one and a walk in narrow rectangles a loss (PERF.md,
        # section 6, PR 25).
        m_prev = m_ref[rows, :]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - _across(m_new, s.shape[1]))
        l_ref[rows, :] = l_ref[rows, :] * alpha + functools.reduce(
            jnp.add, [p[:, c:c + lanes] for c in range(0, p.shape[1], lanes)]
        )
        acc_ref[rows, :] = acc_ref[rows, :] * _across(alpha, acc_ref.shape[1]) + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_ref[rows, :] = m_new

    _by_class(classes, flags, offset, block_q, block_k, rectangle, window)

    @pl.when(flags & _LAST != 0)
    def _finish():
        l_safe = jnp.maximum(l_ref[:].sum(axis=-1, keepdims=True), 1e-30)
        o_ref[0, 0] = (acc_ref[:] / l_safe).astype(o_ref.dtype)
        # lse leaves as the row it is read as, [1, block_q]: the lane-replicated column turned once a q tile (the XLU's
        # work, 128 vregs at 1024 rows), one row of the result written
        lse_ref[0, 0] = (m_ref[:] + jnp.log(l_safe)).T[:1]


# ------------------------------------------- bwd: a rectangle's p and ds, transposed


def _transposed_terms(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, rows, cols, mask, sm_scale):
    """A rectangle's p and ds from the saved logsumexp, both TRANSPOSED: scores as `k q^T`, [C, R], k positions down the
    sublanes and q positions across the lanes, so that lse and delta are read as the [1, R] rows they are stored as (a
    sublane broadcast, no column a row), and `p^T do` (dv), `ds^T q` (dk) are plain products; only dq = ds k contracts
    over ds^T's first dimension (`_contract_rows`: the one transpose a tile; `p^T do` and `ds^T q` from [R, C] take two).
    Returns (p^T, ds^T, q, k, do), the operands float32 as the products take them."""
    k = k_ref[0, 0, cols, :].astype(jnp.float32)
    v = v_ref[0, 0, cols, :].astype(jnp.float32)
    q = q_ref[0, 0, rows, :].astype(jnp.float32)
    do = do_ref[0, 0, rows, :].astype(jnp.float32)
    lse = lse_ref[0, 0, :, rows]  # [1, R]
    delta = delta_ref[0, 0, :, rows]  # [1, R]
    p_t = jnp.exp(_masked_scores(q * sm_scale, k, mask, transposed=True) - lse)
    ds_t = p_t * (_scores(v, do) - delta) * sm_scale
    return p_t, ds_t, q, k, do


def _contract_rows(a_t, b):
    """a b for a given transposed: [C, R] x [C, D] -> [R, D]."""
    return jax.lax.dot_general(a_t, b, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------- bwd: dq


def _bwd_dq_kernel(plan_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_acc_ref,
                   *, sm_scale, block_q, block_k, num_pairs, classes, window=None):
    flags, offset = _pair(plan_ref, num_pairs, block_q, block_k)

    @pl.when(flags & _FIRST != 0)
    def _init():
        dq_acc_ref[:] = jnp.zeros_like(dq_acc_ref)

    def rectangle(rows, cols, mask):
        _, ds_t, _, k, _ = _transposed_terms(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, rows, cols, mask, sm_scale)
        dq_acc_ref[rows, :] += _contract_rows(ds_t, k)

    _by_class(classes, flags, offset, block_q, block_k, rectangle, window)

    @pl.when(flags & _LAST != 0)
    def _finish():
        dq_ref[0, 0] = dq_acc_ref[:].astype(dq_ref.dtype)


# -------------------------------------------------------------------- bwd: dkdv


def _add_dkv_terms(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_acc_ref, dv_acc_ref, rows, cols, mask, sm_scale):
    """A rectangle's terms added into the dk and dv accumulators; (ds^T, k) go back to the kernel that also forms ds k."""
    p_t, ds_t, q, k, do = _transposed_terms(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, rows, cols, mask, sm_scale)
    dv_acc_ref[cols, :] += jax.lax.dot_general(p_t, do, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    dk_acc_ref[cols, :] += jax.lax.dot_general(ds_t, q, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    return ds_t, k


def _bwd_dkv_kernel(plan_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
                    dk_acc_ref, dv_acc_ref, *, sm_scale, block_q, block_k, num_pairs, classes, window=None):
    flags, offset = _pair(plan_ref, num_pairs, block_q, block_k)

    @pl.when(flags & _FIRST != 0)
    def _init():
        dk_acc_ref[:] = jnp.zeros_like(dk_acc_ref)
        dv_acc_ref[:] = jnp.zeros_like(dv_acc_ref)

    def rectangle(rows, cols, mask):
        _add_dkv_terms(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_acc_ref, dv_acc_ref, rows, cols, mask, sm_scale)

    _by_class(classes, flags, offset, block_q, block_k, rectangle, window)

    @pl.when(flags & _LAST != 0)
    def _finish():
        dk_ref[0, 0] = dk_acc_ref[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc_ref[:].astype(dv_ref.dtype)


# ----------------------------------------- bwd: dq, dk and dv from one pass (PR 31)

# What the fused backward may plan to hold in VMEM (a v5e core has 128 MiB; Mosaic's default
# scope is 16). A row whose resident dq does not fit beside the tiles takes the two kernels.
FUSED_BWD_VMEM_BUDGET = 48 * 2**20
_LANES = 128


def _lane_padded(width: int) -> int:
    return -(-width // _LANES) * _LANES


def dq_resident_bytes(seq_q: int, head_dim: int, itemsize: int) -> int:
    """Bytes of one (batch, q head)'s dq that stay in VMEM while the fused backward walks its
    pairs: the float32 accumulator and the output block in q's dtype, which the pipeline
    holds twice. Lanes padded to 128: 192 takes the room of 256."""
    return seq_q * _lane_padded(head_dim) * (4 + 2 * itemsize)


def fused_backward_vmem_bytes(seq_q: int, block_q: int, block_k: int, head_dim: int, head_dim_v: int,
                              itemsize: int) -> int:
    """What `flash_attention_bwd` holds in VMEM for one grid step, in bytes, lanes padded: the
    resident dq; the tiles of q, do, k, v, dk, dv and the two [1, block_q] rows of statistics (a
    sublane tile of 8 each), each twice (the pipeline's two buffers); the dk and dv accumulators;
    and a rectangle's float32 temporaries, taken as four score tiles (s and p, dp and ds, the
    transpose of ds, one to spare) and one copy of each operand tile. Counted from the shapes, not
    asked of the compiler, and on the high side of what Mosaic allots (PERF.md, section 6, PR 31)."""
    wide, narrow = _lane_padded(head_dim), _lane_padded(head_dim_v)
    q_side, kv_side = block_q * (wide + narrow), block_k * (wide + narrow)  # elements of q and do; of k and v, of dk and dv
    pipelined = 2 * (itemsize * (q_side + 2 * kv_side) + 2 * 4 * 8 * block_q)
    accumulators = 4 * kv_side
    temporaries = 4 * (4 * block_q * block_k + q_side + kv_side)
    return dq_resident_bytes(seq_q, head_dim, itemsize) + pipelined + accumulators + temporaries


def backward_plan(seq_q: int, block_q: int, block_k: int, head_dim: int, head_dim_v: int, dtype) -> dict:
    """Which backward a differentiated call of these shapes runs, chosen from the shapes alone:
    the fused kernel wherever its counted VMEM need is within `FUSED_BWD_VMEM_BUDGET`, else
    `bwd_dq` and `bwd_dkv` as before PR 31 (at the forward's blocks). `block_q` x `block_k` are
    the fused kernel's own (`flash_blocks(backward=True)`). The fields `flash_tile_plan` reports."""
    itemsize = jnp.dtype(dtype).itemsize
    need = fused_backward_vmem_bytes(seq_q, block_q, block_k, head_dim, head_dim_v, itemsize)
    return {
        "backward": "fused" if need <= FUSED_BWD_VMEM_BUDGET else "two_kernels",
        "backward_block_q": block_q, "backward_block_k": block_k,
        "dq_resident_bytes": dq_resident_bytes(seq_q, head_dim, itemsize),
        "backward_vmem_bytes": need,
    }


def _bwd_kernel(plan_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dk_ref, dv_ref,
                dq_acc_ref, dk_acc_ref, dv_acc_ref, *, sm_scale, block_q, block_k, num_pairs, classes, window=None):
    """The plan's kv-major walk, once: p and ds of a rectangle feed dv, dk (accumulated over
    the kv tile's q tiles, as `_bwd_dkv_kernel` does) and dq, whose float32 rows of the whole
    (batch, q head) stay in `dq_acc_ref` [q tiles, block_q, D] from the head's first pair to
    its last: a q tile's terms arrive in rising kv order, the order `_bwd_dq_kernel` adds them in."""
    flags, offset = _pair(plan_ref, num_pairs, block_q, block_k)
    t = pl.program_id(2)
    q_tile = plan_ref[t]

    @pl.when(t == 0)
    def _init_dq():
        dq_acc_ref[:] = jnp.zeros_like(dq_acc_ref)

    @pl.when(flags & _FIRST != 0)
    def _init():
        dk_acc_ref[:] = jnp.zeros_like(dk_acc_ref)
        dv_acc_ref[:] = jnp.zeros_like(dv_acc_ref)

    def rectangle(rows, cols, mask):
        ds_t, k = _add_dkv_terms(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_acc_ref, dv_acc_ref, rows, cols, mask, sm_scale)
        dq_acc_ref[q_tile, rows, :] += _contract_rows(ds_t, k)

    _by_class(classes, flags, offset, block_q, block_k, rectangle, window)

    @pl.when(flags & _LAST != 0)
    def _finish():
        dk_ref[0, 0] = dk_acc_ref[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc_ref[:].astype(dv_ref.dtype)

    @pl.when(t == num_pairs - 1)
    def _finish_dq():
        for i in range(dq_acc_ref.shape[0]):
            dq_ref[0, 0, pl.ds(i * block_q, block_q), :] = dq_acc_ref[i].astype(dq_ref.dtype)


def _tiled_call(kernel, table, name, *, sm_scale, batch, num_heads, group, block_q, block_k, head_dim, head_dim_v,
                inputs, outputs, out_shape, scratch_shapes, interpret, vmem_limit_bytes=None, aliases=None, window=None):
    """One `pallas_call` of `kernel` over grid (batch, head, pair), each pair's tiles
    looked up in `table` (a plan's int32 [3, n]: q tile, kv tile, flags), which goes in
    flat as the one scalar-prefetched operand. `inputs` / `outputs` name each operand's
    tiling: "q" (a [block_q, D] tile of q head h), "kv" (a [block_k, D] tile of kv head
    h // group), "k_out" (a [block_k, D] tile per q head), "row" (a q tile's [1, block_q] row of statistics),
    "q_rows" (all the plan's q tiles of q head h: a block that stays while the pairs go by).
    q and k are `head_dim` wide; what is as wide as v (`head_dim_v`: v, the output and its
    cotangent, dv) is tiled alike under "qv", "v" and "v_out". Equal widths give the same
    blocks under both names: the program is the one it was before there were two.
    `aliases` {input: output} (positions in `inputs` / `outputs`) lets an output take its input's buffer."""
    n = table.shape[1]

    def tilings(width):
        return (
            pl.BlockSpec((1, 1, block_q, width), lambda b, h, t, plan: (b, h, plan[t], 0)),
            pl.BlockSpec((1, 1, block_k, width), lambda b, h, t, plan: (b, h // group, plan[n + t], 0)),
            pl.BlockSpec((1, 1, block_k, width), lambda b, h, t, plan: (b, h, plan[n + t], 0)),
        )

    specs = dict(zip(("q", "kv", "k_out"), tilings(head_dim)), **dict(zip(("qv", "v", "v_out"), tilings(head_dim_v))))
    specs["row"] = pl.BlockSpec((1, 1, 1, block_q), lambda b, h, t, plan: (b, h, 0, plan[t]))
    specs["q_rows"] = pl.BlockSpec((1, 1, (int(table[0].max()) + 1) * block_q, head_dim), lambda b, h, t, plan: (b, h, 0, 0))
    classes = np.unique(table[2] & _KIND).tolist()
    windowed = {} if window is None else {"window": window}  # a call without a window binds what it always bound
    call = pl.pallas_call(
        functools.partial(kernel, sm_scale=sm_scale, block_q=block_q, block_k=block_k, num_pairs=n, classes=classes, **windowed),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(batch, num_heads, n),
            in_specs=[specs[kind] for kind in inputs],
            out_specs=[specs[kind] for kind in outputs],
            scratch_shapes=scratch_shapes,
        ),
        out_shape=out_shape,
        input_output_aliases={1 + i: o for i, o in (aliases or {}).items()},  # the plan's table is operand 0
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"), vmem_limit_bytes=vmem_limit_bytes),
        interpret=interpret,
        name=name,
    )
    return functools.partial(call, table.ravel())


# ------------------------------------------------------------------- entry point


def _pick_block(seq: int, preferred: int) -> int:
    if seq % preferred == 0:
        return preferred
    for cand in (512, 256, 128):  # never under a lane tile: a row's [1, block_q] block of statistics tiles (.., 128) or spans the row
        if seq % cand == 0 and cand <= seq:
            return cand
    return seq


def flash_blocks(seq_q: int, seq_k: int, dtype="bfloat16", head_dim: int | None = None,
                 head_dim_v: int | None = None, backward: bool = False) -> tuple[int, int]:
    """The (block_q, block_k) of a call, shared by every kernel consumer (ops/attention.py
    dispatch, the ring's hops): the per-device tuning table (`ops/pallas/autotune.blocks`,
    consulted at trace time), else 1024 (PERF.md section 6 has the chip's readings), then
    stepped down to divide the sequence.

    The table's bucket is the two sequence lengths; where v is not as wide as q and k
    (latent attention) or a head is wider than a lane tile of 128 (PR 44: heads of 256) it is
    the two widths instead (`d192_dv128`, `d256_dv256`), whatever the sequence: what fits VMEM
    depends on the blocks and the widths alone, and at 192/128 `bwd_dq` asks 17.27 MiB of the
    16 at 1024 x 1024, at 2 x 8192 and at 4 x 4096 alike. Heads of 128 and narrower keep the
    bucket of their sequence lengths.

    `backward=True` asks for the fused backward's blocks: the table's `flash_attention_bwd`
    entry of the same bucket where it has one (192/128 on a v5e: 1024 x 1024, which that
    kernel's own VMEM limit holds and which read 25.90 ms a layer against 28.75 at the
    forward's 1024 x 512; PERF.md section 6, PR 31), else the forward's blocks.

    A windowed call reads the same entries: at window 1024 and head 128 the chip read the
    default's 1024 x 1024 fastest of three pairs (PERF.md section 6, PR 38), so it has no
    bucket of its own until a window or a width reads faster at other blocks."""
    from modalities_tpu.ops.pallas import autotune

    bucket = f"sq{autotune.shape_bucket(seq_q)}_sk{autotune.shape_bucket(seq_k)}"
    if head_dim is not None and head_dim_v is not None and (head_dim != head_dim_v or head_dim > _LANES):
        bucket = f"d{head_dim}_dv{head_dim_v}"
    kernels = ("flash_attention_bwd", "flash_attention") if backward else "flash_attention"
    block_q, block_k = autotune.blocks(kernels, bucket, dtype, block_q=1024, block_k=1024)
    return _pick_block(seq_q, block_q), _pick_block(seq_k, block_k)


def _name(kernel: str, window) -> str:
    """A windowed call's kernels carry their own label: readers count by label and per call, and a window
    layer's call does other work than a global layer's at the same shapes."""
    return f"flash_attention_{kernel}" if window is None else f"flash_attention_window_{kernel}"


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9, 10))
def _flash_attention_bhsd(q, k, v, sm_scale, causal, block_q, block_k, bwd_blocks, interpret, window=None, kept=False):
    out, _ = _flash_fwd(q, k, v, sm_scale, causal, block_q, block_k, interpret, window)
    return out


def _flash_fwd(q, k, v, sm_scale, causal, block_q, block_k, interpret, window=None):
    """q: [B, Hq, Sq, D]; k/v: [B, Hkv, Sk, D] -> (out, residuals)."""
    batch, num_heads, seq_q, head_dim = q.shape
    num_kv_heads, seq_k, head_dim_v = k.shape[1], k.shape[2], v.shape[3]
    group = num_heads // num_kv_heads

    lanes = _stat_lanes(block_q, block_k)
    out, lse = _tiled_call(
        _fwd_kernel, tile_plan(seq_q, seq_k, block_q, block_k, causal, window).q_major, _name("fwd", window), window=window,
        sm_scale=sm_scale, batch=batch, num_heads=num_heads, group=group, block_q=block_q, block_k=block_k,
        head_dim=head_dim, head_dim_v=head_dim_v,
        inputs=("q", "kv", "v"), outputs=("qv", "row"),
        out_shape=[
            jax.ShapeDtypeStruct((batch, num_heads, seq_q, head_dim_v), q.dtype),
            jax.ShapeDtypeStruct((batch, num_heads, 1, seq_q), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, head_dim_v), jnp.float32),
            pltpu.VMEM((block_q, lanes), jnp.float32),
            pltpu.VMEM((block_q, lanes), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
    return out, (q, k, v, out, lse)


def flash_fwd_out_lse(q, k, v, *, causal, sm_scale, block_q, block_k, interpret):
    """Raw kernel forward WITH the log-sum-exp exposed: [B, H, S, D] ->
    (out [B, H, S, D], lse [B, H, 1, Sq] fp32, rows). (out, lse) is the information-
    equivalent of unnormalized (o, m, l) block stats — o = out * exp(lse - m) * ...
    collapses to this pair — and it is exactly what an online-softmax merge needs:
    ring attention (parallel/ring_attention.py) merges per-hop (out, lse) pairs
    across k/v rotations. No custom_vjp here: the caller owns differentiation."""
    out, (_, _, _, _, lse) = _flash_fwd(q, k, v, sm_scale, causal, block_q, block_k, interpret)
    return out, lse


KEPT_OUT, KEPT_LSE = "flash_out", "flash_lse"  # what a rematerialized block may keep of a call (`kept`): o and lse, as the kernel wrote them


def _flash_fwd_vjp(q, k, v, sm_scale, causal, block_q, block_k, bwd_blocks, interpret, window=None, kept=False):
    # custom_vjp fwd receives arguments in the primal order (nondiff included in place)
    out, res = _flash_fwd(q, k, v, sm_scale, causal, block_q, block_k, interpret, window)
    if kept:
        # the call sits in a block whose remat policy saves these two names (`training/activation_checkpointing.py`):
        # q, k and v are made again from the block's input, and with o and lse at hand nothing of the recomputed
        # forward reads this kernel's results, so the compiler drops the second call. o goes on under its name as the
        # primal too (what follows the kernel reads the saved array); lse is kept as the kernel wrote it, [B, H, 1, S]
        # rows of numbers, and is the backward kernel's operand as it stands
        from jax.ad_checkpoint import checkpoint_name

        q, k, v, out, lse = res
        out = checkpoint_name(out, KEPT_OUT)
        res = (q, k, v, out, checkpoint_name(lse, KEPT_LSE))
    return out, res


def flash_bwd_dq(q, k, v, do, lse, delta, *, causal, sm_scale, block_q, block_k, interpret, window=None):
    """dq for one (q, k, v) pairing given GLOBAL (lse, delta) — reusable by the ring
    backward, where lse/delta come from the merged multi-hop softmax. All [B,H,S,D];
    lse/delta [B,H,1,Sq] fp32."""
    batch, num_heads, seq_q, head_dim = q.shape
    seq_k = k.shape[2]
    group = num_heads // k.shape[1]

    (dq,) = _tiled_call(
        _bwd_dq_kernel, tile_plan(seq_q, seq_k, block_q, block_k, causal, window).q_major, _name("bwd_dq", window), window=window,
        sm_scale=sm_scale, batch=batch, num_heads=num_heads, group=group, block_q=block_q, block_k=block_k,
        head_dim=head_dim, head_dim_v=v.shape[3],
        inputs=("q", "kv", "v", "qv", "row", "row"), outputs=("q",),
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype)],
        scratch_shapes=[pltpu.VMEM((block_q, head_dim), jnp.float32)],
        interpret=interpret,
    )(q, k, v, do, lse, delta)
    return dq


def flash_bwd_dkv(q, k, v, do, lse, delta, *, causal, sm_scale, block_q, block_k, interpret, window=None):
    """(dk, dv) for one (q, k, v) pairing given GLOBAL (lse, delta), GQA group-summed
    down to the kv heads ([B, Hkv, Sk, D]). Reusable by the ring backward, where the
    accumulators ride the k/v rotation."""
    batch, num_heads, seq_q, head_dim = q.shape
    num_kv_heads, seq_k, head_dim_v = k.shape[1], k.shape[2], v.shape[3]
    group = num_heads // num_kv_heads

    # dk/dv per q-head (q blocks innermost), then summed over the GQA group
    dk_h, dv_h = _tiled_call(
        _bwd_dkv_kernel, tile_plan(seq_q, seq_k, block_q, block_k, causal, window).kv_major, _name("bwd_dkv", window), window=window,
        sm_scale=sm_scale, batch=batch, num_heads=num_heads, group=group, block_q=block_q, block_k=block_k,
        head_dim=head_dim, head_dim_v=head_dim_v,
        inputs=("q", "kv", "v", "qv", "row", "row"), outputs=("k_out", "v_out"),
        out_shape=[
            jax.ShapeDtypeStruct((batch, num_heads, seq_k, head_dim), q.dtype),
            jax.ShapeDtypeStruct((batch, num_heads, seq_k, head_dim_v), q.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, head_dim), jnp.float32),
            pltpu.VMEM((block_k, head_dim_v), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v, do, lse, delta)

    return _group_sum(dk_h, dv_h, k, v)


def _group_sum(dk_h, dv_h, k, v):
    """dk and dv as the kernels write them, per q head, summed over the GQA group down to the kv heads."""
    batch, num_kv_heads, seq_k = k.shape[:3]
    group = dk_h.shape[1] // num_kv_heads
    if group > 1:
        dk_h = dk_h.reshape(batch, num_kv_heads, group, seq_k, k.shape[3]).sum(axis=2)
        dv_h = dv_h.reshape(batch, num_kv_heads, group, seq_k, v.shape[3]).sum(axis=2)
    return dk_h.astype(k.dtype), dv_h.astype(v.dtype)


def flash_bwd(q, k, v, do, lse, delta, *, causal, sm_scale, block_q, block_k, interpret, window=None):
    """(dq, dk, dv) from one kernel, `flash_attention_bwd`: one walk of the plan's kv-major
    table, p and ds evaluated once a tile (5 products for the 7 of `flash_bwd_dq` +
    `flash_bwd_dkv`, whose sums it repeats in their order). The caller has checked
    `backward_plan`: a (batch, q head)'s whole dq row lives in VMEM."""
    batch, num_heads, seq_q, head_dim = q.shape
    num_kv_heads, seq_k, head_dim_v = k.shape[1], k.shape[2], v.shape[3]

    dq, dk_h, dv_h = _tiled_call(
        _bwd_kernel, tile_plan(seq_q, seq_k, block_q, block_k, causal, window).kv_major, _name("bwd", window), window=window,
        sm_scale=sm_scale, batch=batch, num_heads=num_heads, group=num_heads // num_kv_heads,
        block_q=block_q, block_k=block_k, head_dim=head_dim, head_dim_v=head_dim_v,
        inputs=("q", "kv", "v", "qv", "row", "row"), outputs=("q_rows", "k_out", "v_out"),
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((batch, num_heads, seq_k, head_dim), q.dtype),
            jax.ShapeDtypeStruct((batch, num_heads, seq_k, head_dim_v), q.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((seq_q // block_q, block_q, head_dim), jnp.float32),
            pltpu.VMEM((block_k, head_dim), jnp.float32),
            pltpu.VMEM((block_k, head_dim_v), jnp.float32),
        ],
        interpret=interpret, vmem_limit_bytes=FUSED_BWD_VMEM_BUDGET + 8 * 2**20,
        # dq is written where q was read (a head's row at its last pair, when its every tile has been read): three
        # results at once would else hold 192 MiB more of the expert cell's step than dq, then dk and dv, did
        aliases={0: 0},
    )(q, k, v, do, lse, delta)
    return dq, *_group_sum(dk_h, dv_h, k, v)


def _flash_bwd_vjp(sm_scale, causal, block_q, block_k, bwd_blocks, interpret, window, kept, res, do):
    q, k, v, out, lse = res
    # rows of numbers like lse, [B, H, 1, Sq]: a reduction's dense result, not a lane tile a row
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)[:, :, None, :]
    kw = dict(causal=causal, sm_scale=sm_scale, interpret=interpret, window=window)
    if backward_plan(q.shape[2], *bwd_blocks, q.shape[3], v.shape[3], q.dtype)["backward"] == "fused":
        return flash_bwd(q, k, v, do, lse, delta, block_q=bwd_blocks[0], block_k=bwd_blocks[1], **kw)
    # the two kernels at the forward's blocks: what such a row ran before PR 31
    dq = flash_bwd_dq(q, k, v, do, lse, delta, block_q=block_q, block_k=block_k, **kw)
    dk, dv = flash_bwd_dkv(q, k, v, do, lse, delta, block_q=block_q, block_k=block_k, **kw)
    return dq, dk, dv


_flash_attention_bhsd.defvjp(_flash_fwd_vjp, _flash_bwd_vjp)


def pallas_flash_attention(
    q, k, v, causal: bool = True, sm_scale: float | None = None,
    block_q: int = 128, block_k: int = 128, interpret: bool = False,
    bwd_blocks: tuple[int, int] | None = None, window: int | None = None, kept: bool = False,
):
    """Public entry. q: [B, S, Hq, D], k: [B, S, Hkv, D], v: [B, S, Hkv, Dv] (model layout)
    -> [B, S, Hq, Dv]. Dv may differ from D (latent attention: 192 for q and k, 128 for v);
    the default scale is that of D. `bwd_blocks`: (block_q, block_k) of the fused backward
    where the tuning table gives it its own; the forward's otherwise. `window` W (causal only):
    position i sees itself and the W - 1 before it (`tile_plan`); None sees all that came before.
    `kept`: the call sits in a rematerialized block whose policy saves `KEPT_OUT` and `KEPT_LSE`, and a differentiated
    call hands its backward o and lse under those names (`_flash_fwd_vjp`); False binds what it always bound."""
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    seq_q, seq_k = q.shape[1], k.shape[1]
    block_q = _pick_block(seq_q, block_q)
    block_k = _pick_block(seq_k, block_k)
    bwd_blocks = (block_q, block_k) if bwd_blocks is None else (_pick_block(seq_q, bwd_blocks[0]), _pick_block(seq_k, bwd_blocks[1]))
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    out = _flash_attention_bhsd(qt, kt, vt, sm_scale, causal, block_q, block_k, bwd_blocks, interpret, window, kept)
    return out.transpose(0, 2, 1, 3)
