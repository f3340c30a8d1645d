"""The chunked (state-space dual) form of the Mamba-2 recurrence (`ops/ssd.py`) against the per-token walk, float32 on
seeded inputs: values and gradients, a length the chunk does not divide, a state carried over more than two chunks,
decays near 0 and near 1, fewer heads than the layer publishes, and bfloat16 operands within bfloat16's resolution."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from modalities_tpu.ops import ssd

HIGHEST = jax.default_matmul_precision("highest")


def inputs(seq: int, heads: int = 4, batch: int = 2, p: int = 8, n: int = 16, decay=(0.0, 1.0), dtype=jnp.float32, seed: int = 0):
    """x, dt, a, b, c as a mixer would hand them over: dt a softplus, a = -exp(A_log) dt with `A_log` drawn in `decay` (log space)."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(keys[0], (batch, seq, heads, p)).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(keys[1], (batch, seq, heads)))
    a = -jnp.exp(jax.random.uniform(keys[2], (heads,), minval=decay[0], maxval=decay[1])) * dt
    b, c = (jax.random.normal(k, (batch, seq, n)).astype(dtype) for k in keys[3:])
    return x, dt, a, b, c


def both(args, chunk: int):
    with HIGHEST:
        return jax.jit(lambda *v: ssd.ssd_chunked(*v, chunk=chunk))(*args), jax.jit(ssd.ssd_recurrent)(*args)


def gradients(fn, args):
    probe = jnp.cos(jnp.arange(np.prod(args[0].shape), dtype=jnp.float32)).reshape(args[0].shape)
    with HIGHEST:
        return jax.jit(jax.grad(lambda *v: jnp.sum(fn(*v).astype(jnp.float32) * probe), argnums=(0, 1, 2, 3, 4)))(*args)


CASES = {"whole_chunks": (64, 16), "a_length_the_chunk_does_not_divide": (37, 8), "a_state_over_five_chunks": (80, 16),
         "one_chunk_longer_than_the_row": (24, 32), "a_chunk_of_one": (9, 1)}


@pytest.mark.parametrize("seq, chunk", CASES.values(), ids=CASES.keys())
def test_the_chunked_form_is_the_recurrence(seq, chunk):
    got, want = both(inputs(seq), chunk)
    assert got.shape == want.shape and float(jnp.abs(want).max()) > 1.0
    assert float(jnp.abs(got - want).max()) < 2e-5 * float(jnp.abs(want).max())


@pytest.mark.parametrize("seq, chunk", [(48, 16), (37, 8)], ids=["whole_chunks", "padded"])
def test_its_gradients_are_the_recurrences(seq, chunk):
    args = inputs(seq)
    for name, got, want in zip("x dt a b c".split(), gradients(lambda *v: ssd.ssd_chunked(*v, chunk=chunk), args), gradients(ssd.ssd_recurrent, args)):
        assert float(jnp.abs(want).max()) > 0 and float(jnp.abs(got - want).max()) < 2e-4 * float(jnp.abs(want).max()), name


@pytest.mark.parametrize("decay", [(3.0, 4.0), (-9.0, -8.0)], ids=["a_state_that_forgets_in_a_token", "a_state_that_forgets_nothing"])
def test_decays_near_0_and_near_1(decay):
    """`exp(a)` about e^-30 a token (the chunk's own decay matrix underflows to its diagonal, the carried state to nothing) and about
    1 - 2e-4 (the state is a running sum over the whole row): `L` is taken from differences of the sums, so neither overflows."""
    args = inputs(64, decay=decay)
    got, want = both(args, 16)
    assert np.all(np.isfinite(np.asarray(got))) and float(jnp.abs(got - want).max()) < 2e-5 * float(jnp.abs(want).max())
    assert all(np.all(np.isfinite(np.asarray(g))) for g in gradients(lambda *v: ssd.ssd_chunked(*v, chunk=16), args))


def test_a_head_is_its_own_whatever_stands_beside_it():
    """`heads_held` below all: the heads read one B and C and nothing of each other, so two of four heads alone give what they give among four."""
    x, dt, a, b, c = inputs(48)
    whole, _ = both((x, dt, a, b, c), 16)
    part, _ = both((x[:, :, 1:3], dt[:, :, 1:3], a[:, :, 1:3], b, c), 16)
    np.testing.assert_allclose(part, whole[:, :, 1:3], atol=1e-6)


def test_the_state_reaches_across_chunks():
    """An input at position 0 alone, a decay near 1: every later chunk's output comes through the carried state (`Y_off`) and is not zero."""
    x, dt, a, b, c = inputs(64, decay=(-9.0, -8.0))
    x = x.at[:, 1:].set(0.0)
    got, want = both((x, dt, a, b, c), 16)
    assert float(jnp.abs(got[:, 48:]).max()) > 0.1 and float(jnp.abs(got - want).max()) < 2e-5 * float(jnp.abs(want).max())


def test_bfloat16_operands_stay_within_bfloat16s_resolution():
    args = inputs(64, dtype=jnp.bfloat16)
    got = jax.jit(lambda *v: ssd.ssd_chunked(*v, chunk=16))(*args)
    with HIGHEST:
        want = jax.jit(ssd.ssd_recurrent)(*(v.astype(jnp.float32) for v in args))
    assert got.dtype == jnp.bfloat16
    assert float(jnp.sqrt(jnp.sum((got.astype(jnp.float32) - want) ** 2) / jnp.sum(want ** 2))) < 0.01


def test_the_states_the_backward_keeps_are_counted():
    assert ssd.state_bytes(8192, 32, 64, 128, 256) == 32 * 32 * 64 * 128 * 4 and ssd.state_bytes(37, 4, 8, 16, 8) == 5 * 4 * 8 * 16 * 4
