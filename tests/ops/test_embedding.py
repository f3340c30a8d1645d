"""The embedding lookup's own backward rule (ops/embedding.py) against `jax.grad` of plain `jnp.take`:
the same gradient by the same additions whatever the number of scatters, the forward untouched, the
plan by the shapes one shard holds, and one event a shape."""

import json
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from modalities_tpu.ops.embedding import embedding_lookup, grad_plan
from modalities_tpu.parallel.sharding import activation_rules, constrain_activation, default_logical_axis_rules
from modalities_tpu.running_env.device_mesh import get_device_mesh
from modalities_tpu.telemetry import Telemetry, set_active_telemetry
from tests.telemetry.test_scopes import without_metadata

VOCAB, N_EMBD = 64, 20  # the compiler's switch at 8 rows; a width (odd factor 5) at which the sorted form is never taken for cheaper
# batch rows -> sequence lengths that put the rows below, at and above VOCAB / 8; 13 is divided by no piece
SEQ = {1: {"below": 5, "at": 8, "above": 13}, 2: {"below": 3, "at": 4, "above": 13}, 4: {"below": 1, "at": 2, "above": 13}}
CHUNKS = {1: 2, 2: 4, 4: 7}  # of the 13 above: pieces of 7 + 6; 4 + 4 + 4 + 1; six of 2 and one of 1


def ids_of(kind: str, batch: int, seq: int) -> jnp.ndarray:
    if kind == "equal":
        return jnp.full((batch, seq), 7, jnp.int32)
    if kind == "distinct":
        return jnp.asarray(np.random.default_rng(0).permutation(VOCAB)[: batch * seq].reshape(batch, seq), jnp.int32)
    return jnp.asarray(np.random.default_rng(0).integers(0, VOCAB, size=(batch, seq)), jnp.int32)


def gradients(lookup, table, ids, weights):
    return jax.jit(jax.grad(lambda t: (lookup(t, ids).astype(jnp.float32) * weights).sum()))(table)


def plain_take(table, ids):
    return jnp.take(table, ids, axis=0)


@pytest.mark.parametrize("kind", ["equal", "distinct", "uniform"])
@pytest.mark.parametrize("rows", ["below", "at", "above"])
@pytest.mark.parametrize("batch", [1, 2, 4])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gradient_is_that_of_jnp_take(dtype, batch, rows, kind):
    """Whole numbers as cotangents, so every sum is exact in both dtypes whatever the order of its additions: a row
    dropped, added twice or added in a lower precision cannot hide. Then normal cotangents: float32 to round-off,
    bf16 within one ulp of the largest row (the order in which a repeated row's additions land may differ)."""
    seq = SEQ[batch][rows]
    plan = grad_plan((batch, seq), VOCAB, N_EMBD, jnp.dtype(dtype).itemsize)
    assert (plan["form"], plan["chunks"]) == (("chunked", CHUNKS[batch]) if rows == "above" else ("default", 1))
    assert plan["rows_per_chunk"] <= VOCAB // 8 or plan["form"] == "default"
    rng = np.random.default_rng(1)
    table = jnp.asarray(rng.normal(size=(VOCAB, N_EMBD)), dtype)
    ids = ids_of(kind, batch, seq)
    whole = jnp.asarray(rng.integers(-3, 4, size=(batch, seq, N_EMBD)), jnp.float32)
    np.testing.assert_array_equal(
        np.asarray(gradients(embedding_lookup, table, ids, whole), np.float32),
        np.asarray(gradients(plain_take, table, ids, whole), np.float32))
    normal = jnp.asarray(rng.normal(size=(batch, seq, N_EMBD)), jnp.float32)
    got, want = (np.asarray(gradients(f, table, ids, normal), np.float32) for f in (embedding_lookup, plain_take))
    if dtype == jnp.float32:
        np.testing.assert_allclose(got, want, rtol=0, atol=4 * np.finfo(np.float32).eps * np.abs(want).max())
    elif kind != "equal":  # 52 bf16 additions into one row are a matter of their order in either form
        np.testing.assert_allclose(got, want, rtol=0, atol=2.0**-8 * np.abs(want).max())


@pytest.mark.parametrize("n_embd, rows, form", [(16, 26, "default"), (16, 13, "chunked"), (48, 26, "default"), (20, 26, "chunked")],
                         ids=["e16_over_a_quarter", "e16_under_a_quarter", "e48_over_a_quarter", "e20_over_a_quarter"])
def test_the_sorted_form_is_kept_only_at_a_fast_width_and_from_a_quarter_of_the_tables_rows(n_embd, rows, form):
    """16 of 64 rows is the quarter; 16 and 48 have the odd factors 1 and 3, 20 has 5 (ops/embedding.py's table)."""
    assert grad_plan((rows // 13, 13), VOCAB, n_embd, 2)["form"] == form
    table = jnp.asarray(np.random.default_rng(5).normal(size=(VOCAB, n_embd)), jnp.bfloat16)
    ids = ids_of("uniform", rows // 13, 13)
    whole = jnp.asarray(np.random.default_rng(6).integers(-3, 4, size=(rows // 13, 13, n_embd)), jnp.float32)
    np.testing.assert_array_equal(
        np.asarray(gradients(embedding_lookup, table, ids, whole), np.float32),
        np.asarray(gradients(plain_take, table, ids, whole), np.float32))


@pytest.mark.parametrize("ids", [np.array([[-1, 3, 64, -64, -65, 200]]), np.array([5, 5, 9]), np.array(11)],
                         ids=["negative_and_out_of_range", "one_axis", "scalar"])
def test_ids_are_treated_as_jnp_take_treats_them(ids):
    """A negative id counts from the end, one out of range reads zeros and adds nothing; ids of any rank."""
    table = jnp.asarray(np.random.default_rng(2).normal(size=(VOCAB, N_EMBD)), jnp.float32)
    ids = jnp.asarray(ids, jnp.int32)
    weights = jnp.asarray(np.random.default_rng(3).integers(-3, 4, size=(*ids.shape, N_EMBD)), jnp.float32)
    np.testing.assert_array_equal(embedding_lookup(table, ids), plain_take(table, ids))
    np.testing.assert_array_equal(gradients(embedding_lookup, table, ids, weights), gradients(plain_take, table, ids, weights))


def test_a_call_nobody_differentiates_lowers_as_jnp_take_does():
    table, ids = jnp.zeros((VOCAB, N_EMBD), jnp.bfloat16), jnp.zeros((2, 13), jnp.int32)
    text = lambda f: re.sub(r"loc\(.*\)|#loc.*|@\w+", "", jax.jit(f).lower(table, ids).as_text())  # noqa: E731
    assert text(lambda t, i: embedding_lookup(t, i)) == text(lambda t, i: plain_take(t, i))
    compiled = lambda f: without_metadata(jax.jit(f).lower(table, ids).compile().as_text())  # noqa: E731
    assert compiled(lambda t, i: embedding_lookup(t, i)) == compiled(lambda t, i: plain_take(t, i))


@pytest.mark.parametrize("shape, form, chunks, rows_per_chunk", [
    (((2, 4096), 50304, 2560), "chunked", 2, 4096),  # train-2p7b-4k: 8,192 rows against 6,288
    (((4, 4096), 50304, 2560), "chunked", 3, 5464),  # the 2.7B recipe's uncut microbatch
    (((1, 4096), 32768, 2560), "default", 1, 4096),  # train-jamba2-3b-4k: V/8 exactly
    (((1, 4096), 49152, 2048), "default", 1, 4096),  # train-ouro-2p6b-4k
    (((2, 8192), 16128, 2048), "default", 1, 16384),  # train-kanana2-30b-8k: over V/4 at a width the sorted form is fast at
    (((2, 8192), 16128, 2560), "chunked", 9, 1822),  # the same rows at the dense cell's width: 7.48 ms sorted, 3.36 in pieces
    (((2, 4096), 50304, 2048), "chunked", 2, 4096),  # a fast width, but between V/8 and V/4 the pieces win (2.24 ms for 2.79)
    (((8192, 2), 50304, 2560), "default", 1, 16384),  # more leading rows than a piece may hold: left to the compiler
], ids=["dense_cell", "dense_recipe_microbatch_4", "hybrid_cell", "looped_cell", "expert_cell", "expert_cell_rows_at_e2560",
        "dense_cell_rows_at_e2048", "leading_rows_over_the_switch"])
def test_plan_at_the_cells_shapes(shape, form, chunks, rows_per_chunk):
    (ids_shape, vocab, n_embd) = shape
    plan = grad_plan(ids_shape, vocab, n_embd, 2)
    assert plan == {"rows": ids_shape[0] * ids_shape[1], "vocab": vocab, "n_embd": n_embd, "table_bytes": vocab * n_embd * 2,
                    "form": form, "chunks": chunks, "rows_per_chunk": rows_per_chunk}


@pytest.mark.parametrize("axes, shard_rows, shard_vocab", [({"data_parallel_shard_degree": 2}, 26, 64), ({"tensor_parallel_degree": 2}, 52, 32)],
                         ids=["dp_shard_2", "tp_2"])
def test_under_a_mesh_loss_and_gradient_agree_with_one_device_and_the_plan_is_a_shards(tmp_path, axes, shard_rows, shard_vocab):
    """Batch rows over dp_shard, or the vocabulary's rows over tp as `GPT2Module` constrains the table before its lookup:
    GSPMD partitions the chunked scatters as it does the default one, and the plan counts what one shard holds."""
    handle = get_device_mesh(device_type="cpu", world_size=2, **{"data_parallel_shard_degree": 1, "tensor_parallel_degree": 1, **axes})
    rng = np.random.default_rng(4)
    table = jnp.asarray(rng.normal(size=(VOCAB, N_EMBD)), jnp.float32)
    ids = ids_of("uniform", 4, 13)
    weights = jnp.asarray(rng.integers(-3, 4, size=(4, 13, N_EMBD)), jnp.float32)

    def loss(table, ids):
        rows = embedding_lookup(constrain_activation(table, ("vocab", "embed_lookup"), explicit=True), ids)
        return (constrain_activation(rows, ("batch", "seq", "embed")) * weights).sum()

    want_loss, want = jax.jit(jax.value_and_grad(loss))(table, ids)
    telemetry = Telemetry(output_folder_path=tmp_path, watchdog_deadline_s=0)
    previous = set_active_telemetry(telemetry)
    try:
        rules = default_logical_axis_rules(handle)
        with handle.mesh, activation_rules(rules, handle.mesh):
            sharded = lambda x, *spec: jax.device_put(x, NamedSharding(handle.mesh, P(*spec)))  # noqa: E731
            got_loss, got = jax.jit(jax.value_and_grad(loss))(
                sharded(table, dict(rules)["vocab"], None), sharded(ids, dict(rules)["batch"], None))
    finally:
        set_active_telemetry(previous)
    assert float(got_loss) == pytest.approx(float(want_loss), rel=1e-5)  # a sum over two shards, then over the shards
    np.testing.assert_array_equal(got, want)
    (plan,) = [e for e in map(json.loads, telemetry.sink_path.read_text().splitlines()) if e.get("name") == "embedding_grad_plan"]
    assert (plan["rows"], plan["vocab"], plan["form"]) == (shard_rows, shard_vocab, "chunked")
    assert plan["rows_per_chunk"] <= shard_vocab // 8


def test_embedding_grad_plan_is_one_event_per_traced_shape_and_none_per_step(tmp_path):
    """Only the rule's forward knows that a call is differentiated: one event a shape from there, however often the
    shape is traced and the executable run; a call nobody differentiates says nothing."""
    table = jnp.ones((VOCAB, N_EMBD), jnp.bfloat16)
    above, below = jnp.zeros((2, 13), jnp.int32), jnp.zeros((2, 3), jnp.int32)
    loss = lambda table, ids: embedding_lookup(table, ids).astype(jnp.float32).sum()  # noqa: E731
    telemetry = Telemetry(output_folder_path=tmp_path, watchdog_deadline_s=0)
    previous = set_active_telemetry(telemetry)
    try:
        step = jax.jit(jax.grad(lambda table: loss(table, above) + loss(2 * table, above) + loss(table, below)))
        for _ in range(3):
            jax.block_until_ready(step(table))
        jax.block_until_ready(jax.jit(loss)(table, above))
    finally:
        set_active_telemetry(previous)
    plans = [e for e in map(json.loads, telemetry.sink_path.read_text().splitlines()) if e.get("name") == "embedding_grad_plan"]
    shape = {"vocab": VOCAB, "n_embd": N_EMBD, "table_bytes": VOCAB * N_EMBD * 2}
    assert [{k: v for k, v in e.items() if k not in ("event", "name", "rank")} for e in plans] == [
        {**shape, "rows": 26, "form": "chunked", "chunks": 4, "rows_per_chunk": 8},
        {**shape, "rows": 6, "form": "default", "chunks": 1, "rows_per_chunk": 6},
    ]
