"""The embedding lookup, with a backward rule of its own.

`embedding_lookup(table, ids)` is `jnp.take(table, ids, axis=0)` to the instruction: a call
nobody differentiates (serving, evaluation) lowers as it did. Differentiated, its gradient is
the `[V, E]` array in the table's dtype that `jnp.take`'s transpose builds, by the same
additions (a scatter-add of every looked-up row into zeros: nothing dropped, nothing in a
lower precision). What the rule chooses is how many scatters do it.

XLA's TPU compiler rewrites a scatter-add whose indices number more than an eighth of the
operand's rows into sort + gather of the updates + scatter with `indices_are_sorted=true`
(exact: at 50,304 rows 6,288 indices compile to the plain scatter, 6,296 to the sorted form).
What that sorted form costs on a v5e follows the table's WIDTH, not its size
(`scripts/embedding_grad_bench.py`, the gradient alone, device ms from traces, PR 33; `plain` is
the same lookup cut into pieces of at most V/8 rows, each a plain scatter):

    rows    E     V        sorted    plain         rows    E     V        sorted    plain
    8,192   2560  50,304   19.79     2.62          16,384  768   50,304    0.80     1.69
    16,384  2560  50,304   20.46     4.94          16,384  1024  32,768    0.93     1.71
    16,384  2560  32,768   13.81     4.77          16,384  1280  50,304    1.54     2.95
    16,384  2560  16,128    7.48     3.36          16,384  1536  32,768    1.90     2.55
    16,384  2048  16,128    1.82     2.73          16,384  2304  32,768    4.16     4.35
    8,192   2048  16,128    1.28     1.43          16,384  3072  32,768    3.81     5.64
    16,384  2048  32,768    2.54     4.07          16,384  3584  32,768    8.00     6.48
    16,384  2048  50,304    3.33     4.21          16,384  5120  32,768   59.95     8.99
    8,192   2048  50,304    2.79     2.24          16,384  6144  32,768   11.52    10.71
                                                   16,384  4096  128,256   7.28     8.70

The plain scatter costs 0.17-0.33 us a row at E 2048-2560 (0.10 at E 768, 0.55 at E 5120) whatever
the number of pieces and whatever the ids (uniform, a tenth of them one token, a Zipf draw: within
4%). The sorted form costs about 0.066 us an id plus 0.044 us for every row of the TABLE at E 2048,
so it wins from about rows = V/4 up; at widths whose odd factor is 5 or 7 (2560, 3584, 5120) it
pays 0.4-1.8 us for every row of the table and loses by 2 to 7 times (1280 is the exception, and
still wins). Choosing pieces where the sorted form would have won costs at most a millisecond or
two; choosing the sorted form where it is slow cost the dense cell 17 ms a step. So `grad_plan`
leaves a scatter to the compiler's sort only where these readings show it no slower.
`tests/ops/test_tpu_compile.py` pins the compiler's switch.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

# the compiler keeps the plain scatter up to rows // SORT_SWITCH indices, and sorts beyond (libtpu 0.0.34)
SORT_SWITCH = 8
# from rows // SORTED_WINS_FROM indices the sorted form is the cheaper one, at a width it is fast at
SORTED_WINS_FROM = 4
FAST_SORTED_ODD_FACTORS = (1, 3)  # of n_embd: 768, 1024, 1536, 2048, 3072, 4096, 6144; not 2560, 3584, 5120


def grad_plan(ids_shape: tuple[int, ...], vocab: int, n_embd: int, itemsize: int) -> dict:
    """How the gradient of a lookup of `ids_shape` rows in a `[vocab, n_embd]` table is added up:
    the facts of the sink event `embedding_grad_plan`. Shapes as one shard holds them.

    `default`: the one scatter-add `jnp.take`'s transpose emits, where the compiler keeps it plain
    (at most `vocab // 8` rows) or its sorted form is the cheaper one (the module's table). `chunked`:
    the last axis of the ids cut into `chunks` even pieces of at most `vocab // 8` rows each, every
    piece a plain scatter-add into the running gradient, in place.
    """
    rows, lead = math.prod(ids_shape), math.prod(ids_shape[:-1])
    plain_rows = vocab // SORT_SWITCH
    plan = {"rows": rows, "vocab": vocab, "n_embd": n_embd, "table_bytes": vocab * n_embd * itemsize,
            "form": "default", "chunks": 1, "rows_per_chunk": rows}
    odd_factor = n_embd // (n_embd & -n_embd)
    sorted_is_cheaper = rows >= vocab // SORTED_WINS_FROM and odd_factor in FAST_SORTED_ODD_FACTORS
    if rows <= plain_rows or sorted_is_cheaper or lead > plain_rows:  # (more leading rows than a piece may hold: the compiler's)
        return plan
    chunks = -(-ids_shape[-1] // (plain_rows // lead))
    return {**plan, "form": "chunked", "chunks": chunks, "rows_per_chunk": lead * -(-ids_shape[-1] // chunks)}


def table_gradient(ids, rows, vocab: int, chunks: int = 1):
    """The cotangent of the table from the cotangent `rows` `[*ids.shape, E]` of the looked-up rows. With one
    chunk, the scatter-add into zeros that the transpose of `jnp.take`'s gather is."""
    # jnp.take's own treatment of an id: a negative one counts from the end, one out of range adds nothing
    ids = jnp.where(ids < 0, ids + vocab, ids)
    dnums = lax.ScatterDimensionNumbers(
        update_window_dims=(ids.ndim,), inserted_window_dims=(0,), scatter_dims_to_operand_dims=(0,))
    grad = jnp.zeros((vocab, rows.shape[-1]), rows.dtype)
    if chunks == 1:  # ids of any rank, a scalar among them
        return lax.scatter_add(grad, ids[..., None], rows, dnums, mode=lax.GatherScatterMode.FILL_OR_DROP)
    step = -(-ids.shape[-1] // chunks)
    for start in range(0, ids.shape[-1], step):
        grad = lax.scatter_add(
            grad, ids[..., start:start + step, None], rows[..., start:start + step, :], dnums,
            mode=lax.GatherScatterMode.FILL_OR_DROP)
    return grad


def _plan_of(table, ids) -> dict:
    from modalities_tpu.parallel.sharding import shard_shape

    vocab, n_embd = shard_shape(table.shape, ("vocab", "embed_lookup"))
    ids_shape = shard_shape(ids.shape, ("batch", "seq")) if ids.ndim == 2 else ids.shape
    return grad_plan(ids_shape, vocab, n_embd, jnp.dtype(table.dtype).itemsize)


def embedding_lookup(table, ids):
    """`table[ids]` for a `[V, E]` table and integer ids of any shape, as `jnp.take(table, ids, axis=0)`."""
    return _lookup(table.shape[0], tuple(_plan_of(table, ids).items()), table, ids)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _lookup(table_rows, plan, table, ids):
    return jnp.take(table, ids, axis=0)


def _lookup_fwd(table_rows, plan, table, ids):
    """Runs while tracing, and only when the call is differentiated: the plan goes to the sink once per shape."""
    from modalities_tpu.telemetry import get_active_telemetry

    get_active_telemetry().emit_event_once("embedding_grad_plan", dict(plan))
    return jnp.take(table, ids, axis=0), ids


def _lookup_bwd(table_rows, plan, ids, rows):
    return table_gradient(ids, rows, table_rows, dict(plan)["chunks"]), np.zeros(ids.shape, dtype=jax.dtypes.float0)


_lookup.defvjp(_lookup_fwd, _lookup_bwd)
