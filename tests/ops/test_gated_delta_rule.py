"""The chunked gated delta rule (`ops/gated_delta_rule.py`) against the per-token recurrence beside it: the same
function of its inputs, forward and gradients, whatever the chunk, the group of chunks or the row's length."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from modalities_tpu.ops import gated_delta_rule as rule

B, HK, HV, DK, DV = 2, 2, 4, 16, 8


def inputs(seq: int, g_level: float, seed: int = 0):
    """q and k normalised as the mixer hands them over; `g` about `g_level` (the log of the decay a token)."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    q, k = (jax.random.normal(key, (B, seq, HK, DK)) for key in keys[:2])
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) / np.sqrt(DK)
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(keys[2], (B, seq, HV, DV))
    g = g_level * jnp.exp(0.5 * jax.random.normal(keys[3], (B, seq, HV)))
    beta = jax.nn.sigmoid(jax.random.normal(keys[4], (B, seq, HV)))
    return q, k, v, g, beta


@functools.lru_cache(maxsize=None)
def both(chunk: int, group_chunks: int):
    """Output and gradients of both forms, one jitted program a (chunk, group) pair, shared by the cases."""
    def programs(fn):
        loss = lambda *xs: jnp.sum(jnp.sin(fn(*xs)))  # noqa: E731
        return jax.jit(fn), jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4)))

    return programs(functools.partial(rule.gated_delta_rule, chunk=chunk, group_chunks=group_chunks)), programs(rule.gated_delta_rule_recurrent)


# the row, the decay's level, the chunk and the chunks a group: whole chunks; a row that is no multiple of the chunk (padded with
# positions that change nothing, by name in the module's docstring); a state that barely decays and one that is gone in a token;
# a state carried over four chunks inside one group, and over four groups of one and of two chunks
CASES = {"two_chunks": (128, -1.0, 64, 32), "a_row_of_200_is_padded": (200, -1.0, 64, 32), "g_near_0": (128, -1e-3, 64, 32),
         "g_near_minus_20": (128, -20.0, 64, 32), "four_chunks_one_group": (64, -0.3, 16, 32), "four_groups_of_one_chunk": (64, -0.3, 16, 1),
         "seven_chunks_in_groups_of_two": (100, -0.3, 16, 2)}


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_chunked_form_is_the_recurrence(case):
    seq, g_level, chunk, group = CASES[case]
    xs = inputs(seq, g_level)
    (chunked, chunked_grad), (recurrent, recurrent_grad) = both(chunk, group)
    got, want = chunked(*xs), recurrent(*xs)
    assert got.shape == want.shape == (B, seq, HV, DV)
    np.testing.assert_allclose(got, want, atol=2e-5 * float(jnp.max(jnp.abs(want))))
    for name, a, b in zip("q k v g beta".split(), chunked_grad(*xs), recurrent_grad(*xs)):
        # the two gates' gradients are sums of terms that all but cancel where the decay is strong (3e-4 at g about -20): a looser hold
        assert float(jnp.max(jnp.abs(a - b))) <= (5e-4 if name in ("g", "beta") else 5e-5) * max(float(jnp.max(jnp.abs(b))), 1e-6), (case, name)


def test_the_state_is_carried_from_chunk_to_chunk():
    """What a row's last chunk reads of its first: with the first chunk's keys and values wiped the last chunk's output changes
    (slow decay), and the same rows cut into two halves that each start from zero differ from the whole."""
    xs = inputs(64, -0.05)
    chunked = both(16, 2)[0][0]
    whole = chunked(*xs)
    halves = jnp.concatenate([chunked(*(a[:, :32] for a in xs)), chunked(*(a[:, 32:] for a in xs))], axis=1)
    np.testing.assert_allclose(whole[:, :32], halves[:, :32], atol=1e-6)
    assert float(jnp.max(jnp.abs(whole[:, 48:] - halves[:, 48:]))) > 1e-3


def test_the_inverse_of_a_unit_lower_triangle_and_its_own_rule():
    lower = jnp.tril(jax.random.normal(jax.random.PRNGKey(1), (3, 16, 16)) * 0.3, k=-1)
    inverse = rule._unit_lower_inverse(lower)
    np.testing.assert_allclose(inverse @ (jnp.eye(16) + lower), jnp.broadcast_to(jnp.eye(16), lower.shape), atol=1e-5)
    weigh = jax.random.normal(jax.random.PRNGKey(2), lower.shape)
    got = jax.grad(lambda l: jnp.sum(rule._unit_lower_inverse(l) * weigh))(lower)
    want = jax.grad(lambda l: jnp.sum(jnp.linalg.inv(jnp.eye(16) + l) * weigh))(lower)
    np.testing.assert_allclose(got, want, atol=1e-4)


def test_value_heads_must_share_the_key_heads_evenly():
    q, k, v, g, beta = inputs(16, -1.0)
    with pytest.raises(ValueError, match="do not share"):
        rule.gated_delta_rule(q, k, v[:, :, :3], g[:, :, :3], beta[:, :, :3])


def test_bfloat16_inputs_take_bfloat16_operands_and_keep_a_float32_state():
    """The products' operands are the inputs' dtype, the carried state and the inverse float32: read off the jaxpr."""
    xs = [a.astype(jnp.bfloat16) if i < 3 else a for i, a in enumerate(inputs(128, -1.0))]
    text = str(jax.make_jaxpr(rule.gated_delta_rule)(*xs))
    assert "f32[2,2,2,16,8]" in text  # the state [B, Hk, r, d_k, d_v]
    assert rule.gated_delta_rule(*xs).dtype == jnp.bfloat16
    assert rule.state_bytes(16384, 32, 128, 128) == 8 * 32 * 128 * 128 * 4  # a state a group of 32 chunks and a head
