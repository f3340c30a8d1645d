"""Vocab-streaming fused cross-entropy vs the dense optax oracle (fwd + grads),
in Pallas interpret mode on CPU — same pattern as test_flash_attention.py."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from modalities_tpu.ops.pallas.fused_ce import fused_ce_sum_and_count


def _oracle_sum_and_count(hidden, head_weight, labels, ignore_index=-100):
    logits = jnp.einsum(
        "...e,ve->...v", hidden.astype(jnp.float32), head_weight.astype(jnp.float32)
    )
    mask = (labels != ignore_index).astype(jnp.float32)
    safe = jnp.where(labels != ignore_index, labels, 0)
    per_token = optax.softmax_cross_entropy_with_integer_labels(logits, safe)
    return (per_token * mask).sum(), mask.sum()


def _inputs(seed, rows, vocab, embd, dtype=jnp.float32, w_dtype=None):
    rng = jax.random.PRNGKey(seed)
    h = jax.random.normal(jax.random.fold_in(rng, 0), (rows, embd), dtype)
    w = jax.random.normal(jax.random.fold_in(rng, 1), (vocab, embd), w_dtype or dtype)
    y = jax.random.randint(jax.random.fold_in(rng, 2), (rows,), 0, vocab)
    return h, w, y


@pytest.mark.parametrize(
    "rows,vocab,block_rows,block_vocab",
    [
        (32, 256, 16, 128),  # divisible everywhere
        (21, 256, 16, 128),  # ragged rows (padded with ignore_index)
        (32, 200, 16, 128),  # non-divisible vocab tail (padded cols masked to -inf)
        (21, 200, 16, 128),  # both ragged
    ],
)
def test_forward_matches_oracle(rows, vocab, block_rows, block_vocab):
    h, w, y = _inputs(0, rows, vocab, 64)
    exp_total, exp_count = _oracle_sum_and_count(h, w, y)
    got_total, got_count = fused_ce_sum_and_count(
        h, w, y, block_rows=block_rows, block_vocab=block_vocab, interpret=True
    )
    np.testing.assert_allclose(float(got_total), float(exp_total), rtol=1e-5)
    assert float(got_count) == float(exp_count)


def test_ignore_index_rows_masked():
    h, w, y = _inputs(1, 24, 128, 32)
    y = y.at[:7].set(-100)
    exp_total, exp_count = _oracle_sum_and_count(h, w, y)
    got_total, got_count = fused_ce_sum_and_count(
        h, w, y, block_rows=8, block_vocab=128, interpret=True
    )
    np.testing.assert_allclose(float(got_total), float(exp_total), rtol=1e-5)
    assert float(got_count) == float(exp_count) == 17.0


def test_all_rows_ignored_zero_count():
    h, w, _ = _inputs(2, 16, 128, 32)
    y = jnp.full((16,), -100, dtype=jnp.int32)
    got_total, got_count = fused_ce_sum_and_count(
        h, w, y, block_rows=8, block_vocab=128, interpret=True
    )
    assert float(got_total) == 0.0
    assert float(got_count) == 0.0


def _argmax_in_last_tile(seed, rows, vocab, embd):
    """Every row's largest logit sits in the last vocabulary tile: the running max moves at
    the last step, so everything the forward has summed by then is rescaled at once."""
    h, w, y = _inputs(seed, rows, vocab, embd)
    w = w.at[vocab - rows:].set(3.0 * h)  # row i's own direction, three times over, in column vocab - rows + i
    assert (jnp.argmax(h @ w.T, axis=-1) == vocab - rows + jnp.arange(rows)).all()
    return h, w, y


GRADIENT_CASES = {
    # what each case changes of the defaults in the test: blocks 8 x 128, float32, cotangent 1, no row ignored
    "padded_rows_and_vocab_one_ignored": dict(rows=21, vocab=200, embd=48, ignored=[2]),
    "vocab_50304_against_block_512": dict(rows=12, vocab=50304, embd=16, block_rows=8, block_vocab=512),
    "rows_ignored": dict(rows=24, vocab=256, embd=32, ignored=range(7)),
    "all_rows_ignored": dict(rows=16, vocab=256, embd=32, ignored=range(16)),
    "bf16_hidden_and_head": dict(rows=32, vocab=256, embd=64, dtype=jnp.bfloat16, tolerance=2e-2),
    "cotangent_not_one": dict(rows=16, vocab=384, embd=32, cotangent=-2.5),
    "largest_logit_in_last_tile": dict(rows=16, vocab=384, embd=32, inputs=_argmax_in_last_tile),
}


@pytest.mark.parametrize("case", sorted(GRADIENT_CASES))
def test_gradients_match_oracle(case):
    """d_hidden comes out of the forward kernel's running sums, d_head_weight out of the
    backward kernel: both against the float32 oracle's, at the primal shapes and dtypes."""
    spec = {"block_rows": 8, "block_vocab": 128, "dtype": jnp.float32, "tolerance": 1e-4, "cotangent": 1.0,
            "ignored": [], "inputs": _inputs, **GRADIENT_CASES[case]}
    h, w, y = spec["inputs"](3, spec["rows"], spec["vocab"], spec["embd"])
    h, w = h.astype(spec["dtype"]), w.astype(spec["dtype"])
    y = y.at[jnp.asarray(list(spec["ignored"]), jnp.int32)].set(-100)  # an ignored row must contribute zero grad

    def mean_loss(sum_and_count, h, w):
        total, count = sum_and_count(h, w, y)
        return spec["cotangent"] * total / jnp.maximum(count, 1.0)

    fused = functools.partial(
        fused_ce_sum_and_count, block_rows=spec["block_rows"], block_vocab=spec["block_vocab"], interpret=True
    )
    gh_f, gw_f = jax.grad(functools.partial(mean_loss, fused), argnums=(0, 1))(h, w)
    gh_o, gw_o = jax.grad(functools.partial(mean_loss, _oracle_sum_and_count), argnums=(0, 1))(
        h.astype(jnp.float32), w.astype(jnp.float32)
    )
    # padded-row / padded-vocab pollution check: grads carry the primal shapes and dtypes
    assert (gh_f.shape, gh_f.dtype, gw_f.shape, gw_f.dtype) == (h.shape, h.dtype, w.shape, w.dtype)
    tolerance = spec["tolerance"]
    for got, want in ((gh_f, gh_o), (gw_f, gw_o)):
        scale = max(float(jnp.abs(want).max()), 1e-30)
        np.testing.assert_allclose(np.asarray(got, np.float32) / scale, np.asarray(want) / scale, rtol=tolerance, atol=tolerance)
    if len(list(spec["ignored"])) == spec["rows"]:
        assert not np.asarray(gh_f).any() and not np.asarray(gw_f).any()


def test_tied_table_gets_both_gradients():
    """One `[V, E]` table looked up as the embedding and used as the head (the hybrid cell's
    `wte`): its gradient is the lookup's scatter plus the head's, and the backward's own row
    gather for the one-hot term reads the same array."""
    table = jax.random.normal(jax.random.PRNGKey(0), (200, 32)) * 0.3
    tokens = jax.random.randint(jax.random.PRNGKey(1), (24,), 0, 200)
    y = jax.random.randint(jax.random.PRNGKey(2), (24,), 0, 200).at[5].set(-100)

    def loss(sum_and_count, table):
        total, count = sum_and_count(jnp.tanh(table[tokens]), table, y)
        return total / count

    fused = functools.partial(fused_ce_sum_and_count, block_rows=8, block_vocab=128, interpret=True)
    got = jax.grad(functools.partial(loss, fused))(table)
    want = jax.grad(functools.partial(loss, _oracle_sum_and_count))(table)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-6)


def _pallas_calls(jaxpr):
    """Every `pallas_call` equation of a jaxpr, those inside its sub-jaxprs too."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append(eqn)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _pallas_calls(sub)
    return found


def test_a_differentiated_call_runs_two_kernels_and_the_logits_twice():
    h, w, y = _inputs(6, 32, 256, 64)
    loss = lambda h, w: fused_ce_sum_and_count(h, w, y, block_rows=16, block_vocab=128, interpret=True)[0]  # noqa: E731
    calls = _pallas_calls(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(h, w).jaxpr)
    assert sorted(c.params["name"] for c in calls) == ["fused_ce_bwd_dw", "fused_ce_fwd"]
    # d_hidden leaves the forward kernel as the softmax's mean row of the head, float32 [N, E]
    forward = next(c for c in calls if c.params["name"] == "fused_ce_fwd")
    assert [(v.aval.shape, v.aval.dtype) for v in forward.outvars] == [((32, 1), jnp.float32)] * 2 + [((32, 64), jnp.float32)]


def test_a_call_nobody_differentiates_runs_the_lean_kernel():
    h, w, y = _inputs(6, 32, 256, 64)
    calls = _pallas_calls(jax.make_jaxpr(
        lambda h, w: fused_ce_sum_and_count(h, w, y, block_rows=16, block_vocab=128, interpret=True))(h, w).jaxpr)
    assert [c.params["name"] for c in calls] == ["fused_ce_eval"]
    assert [v.aval.shape for v in calls[0].outvars] == [(32, 1), (32, 1)]  # lse and the label's logit: no [N, E] array


def test_bf16_hidden_fp32_accumulation():
    """bf16 activations, fp32 stats: totals must match the oracle computed on the
    same bf16 inputs upcast to fp32 (accumulation is what the kernel controls)."""
    h, w, y = _inputs(4, 32, 256, 64, dtype=jnp.bfloat16, w_dtype=jnp.float32)
    exp_total, exp_count = _oracle_sum_and_count(h, w, y)
    got_total, got_count = fused_ce_sum_and_count(
        h, w, y, block_rows=16, block_vocab=128, interpret=True
    )
    assert got_total.dtype == jnp.float32
    np.testing.assert_allclose(float(got_total), float(exp_total), rtol=1e-3)
    assert float(got_count) == float(exp_count)

    def loss_fused(h):
        total, count = fused_ce_sum_and_count(
            h, w, y, block_rows=16, block_vocab=128, interpret=True
        )
        return total / count

    gh = jax.grad(loss_fused)(h)
    assert gh.dtype == h.dtype  # cotangent lands back in the activation dtype


def test_multidim_hidden_flattened():
    """[B, S, E] hidden / [B, S] labels round-trip through the row flattening."""
    rng = jax.random.PRNGKey(5)
    h = jax.random.normal(jax.random.fold_in(rng, 0), (2, 9, 32))
    w = jax.random.normal(jax.random.fold_in(rng, 1), (100, 32))
    y = jax.random.randint(jax.random.fold_in(rng, 2), (2, 9), 0, 100)
    exp_total, exp_count = _oracle_sum_and_count(h, w, y)
    got_total, got_count = fused_ce_sum_and_count(
        h, w, y, block_rows=8, block_vocab=128, interpret=True
    )
    np.testing.assert_allclose(float(got_total), float(exp_total), rtol=1e-5)
    assert float(got_count) == float(exp_count)

    def loss(h):
        total, count = fused_ce_sum_and_count(
            h, w, y, block_rows=8, block_vocab=128, interpret=True
        )
        return total / count

    assert jax.grad(loss)(h).shape == h.shape


def test_blocks_step_down_by_width_as_the_chip_compiles_them():
    """The blocks the shipped 256 x 512 comes down to: unmoved at the widths the accepted cells run (E 2560: 256 x 256;
    E 1536: as shipped), and at E 2048, where the forward inside a whole step asked 16.80 MiB of the 16 at 256 x 512
    (PR 30: the kernel alone compiles there), 256 x 256, which also divides the 16,128 rows of that cell's head."""
    from modalities_tpu.ops.pallas import fused_ce

    fitted = {e: fused_ce._fit_blocks_to_vmem(256, 512, e, 2) for e in (1536, 2048, 2560, 4096)}
    assert fitted == {1536: (256, 512), 2048: (256, 256), 2560: (256, 256), 4096: (128, 128)}
    assert fused_ce._forward_in_step_vmem_bytes(256, 512, 2048, 2) > 16 * 2**20 > fused_ce._forward_in_step_vmem_bytes(256, 256, 2560, 2)
    assert 16128 % 256 == 0


def test_width_2304_takes_the_blocks_of_its_neighbours_and_the_plan_says_so():
    """A fifth width through the fit (PR 38, the window-and-global cell: E 2304 against 12,288 rows): 256 x 256, as at 2048 and
    2560, which divides the head's rows; `ce_plan` reports the choice as for every width. The whole step compiled for a described
    v5e at these blocks (meta.json, memory_analysis) and tests/ops/test_tpu_compile.py compiles the kernels alone."""
    from modalities_tpu.ops.pallas import fused_ce

    assert fused_ce._fit_blocks_to_vmem(256, 512, 2304, 2) == (256, 256) and 12288 % 256 == 0
    assert fused_ce._forward_in_step_vmem_bytes(256, 512, 2304, 2) > 16 * 2**20 > fused_ce._forward_in_step_vmem_bytes(256, 256, 2304, 2)
    plan = fused_ce.ce_plan(16384, 12288, 12288, 2304, 256, 256, 2, dh_in_forward=True)
    assert (plan["n_embd"], plan["block_rows"], plan["block_vocab"], plan["grid_steps_forward"]) == (2304, 256, 256, 64 * 48)
    assert plan["forward_vmem_bytes"] == fused_ce._forward_vmem_bytes(256, 256, 2304, 2, True) < 16 * 2**20


# ------------------------------------------------------------------ the per-row entry (a looped model's loss over its exits)


@pytest.mark.parametrize("rows,vocab", [(32, 256), (21, 200)])
def test_row_losses_and_both_gradients_under_random_row_cotangents(rows, vocab):
    """`fused_ce_rows` hands out every row's loss and takes a cotangent for every row: the plain form's numbers for
    the losses and for both gradients whatever weighs the rows (ragged rows and vocabulary, an ignored row)."""
    from modalities_tpu.ops.pallas.fused_ce import fused_ce_rows

    h, w, y = _inputs(7, rows, vocab, 64)
    y = y.at[3].set(-100)
    weights = jax.random.normal(jax.random.PRNGKey(8), (rows,))

    def plain(h, w):
        logits = jnp.einsum("ne,ve->nv", h, w)
        per_row = optax.softmax_cross_entropy_with_integer_labels(logits, jnp.where(y == -100, 0, y))
        return per_row * (y != -100)

    fused = lambda h, w: fused_ce_rows(h, w, y, block_rows=16, block_vocab=128, interpret=True)  # noqa: E731
    got, want = fused(h, w), plain(h, w)
    assert got.shape == (rows,) and got.dtype == jnp.float32 and float(got[3]) == 0.0
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)
    got_grads = jax.grad(lambda h, w: (fused(h, w) * weights).sum(), argnums=(0, 1))(h, w)
    want_grads = jax.grad(lambda h, w: (plain(h, w) * weights).sum(), argnums=(0, 1))(h, w)
    for g, wnt in zip(got_grads, want_grads):
        np.testing.assert_allclose(np.asarray(g), np.asarray(wnt), rtol=1e-4, atol=1e-5)
    assert float(jnp.abs(got_grads[0][3]).max()) == 0.0, "an ignored row gets no gradient whatever its cotangent"


def test_rows_keep_the_shape_of_the_labels_and_run_the_same_two_kernels():
    """`[T, B, S, E]` exits are `T x B x S` rows of one call; differentiated it is `fused_ce_fwd` and `fused_ce_bwd_dw`
    on the operands the sum form gives them, and undifferentiated the lean kernel."""
    from modalities_tpu.ops.pallas.fused_ce import fused_ce_rows

    h, w, y = _inputs(9, 48, 256, 64)
    h4, y3 = h.reshape(3, 2, 8, 64), y.reshape(3, 2, 8)
    rows = fused_ce_rows(h4, w, y3, block_rows=16, block_vocab=128, interpret=True)
    assert rows.shape == (3, 2, 8)
    total, _ = fused_ce_sum_and_count(h, w, y, block_rows=16, block_vocab=128, interpret=True)
    np.testing.assert_allclose(float(rows.sum()), float(total), rtol=1e-6)

    def kernels(fn):
        return sorted((c.params["name"], tuple(v.aval.shape for v in c.invars)) for c in _pallas_calls(jax.make_jaxpr(fn)(h, w).jaxpr))

    rows_loss = lambda h, w: fused_ce_rows(h, w, y, block_rows=16, block_vocab=128, interpret=True).sum()  # noqa: E731
    sum_loss = lambda h, w: fused_ce_sum_and_count(h, w, y, block_rows=16, block_vocab=128, interpret=True)[0]  # noqa: E731
    assert kernels(jax.grad(rows_loss, argnums=(0, 1))) == kernels(jax.grad(sum_loss, argnums=(0, 1)))
    assert [name for name, _ in kernels(jax.grad(rows_loss, argnums=(0, 1)))] == ["fused_ce_bwd_dw", "fused_ce_fwd"]
    assert [name for name, _ in kernels(rows_loss)] == ["fused_ce_eval"]


def test_the_sum_form_lowers_as_it_did():
    """The sum form is the accepted cells' path: its differentiated jaxpr holds the two kernels, one scalar cotangent
    broadcast over the mask, and no per-row entry."""
    h, w, y = _inputs(6, 32, 256, 64)
    loss = lambda h, w: fused_ce_sum_and_count(h, w, y, block_rows=16, block_vocab=128, interpret=True)[0]  # noqa: E731
    text = str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(h, w))
    assert "_fused_ce_rows" not in text and text.count("pallas_call") == 2
