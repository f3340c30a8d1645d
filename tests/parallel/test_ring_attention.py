"""Ring-attention CP correctness: sharded-vs-single-device logit equivalence (the
acceptance oracle SURVEY.md §5.7 prescribes for the cp mesh dim)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from modalities_tpu.ops.attention import manual_attention
from modalities_tpu.parallel.ring_attention import ring_attention


def _mesh(cp=4, dp=2):
    devices = np.asarray(jax.devices()[: cp * dp]).reshape(dp, cp)
    return Mesh(devices, ("dp_shard", "cp"))


def _rand(seed, b, s, hq, hkv, d):
    rng = jax.random.PRNGKey(seed)
    q = jax.random.normal(jax.random.fold_in(rng, 0), (b, s, hq, d))
    k = jax.random.normal(jax.random.fold_in(rng, 1), (b, s, hkv, d))
    v = jax.random.normal(jax.random.fold_in(rng, 2), (b, s, hkv, d))
    return q, k, v


@pytest.mark.parametrize("hq,hkv", [(4, 4), (4, 2)])
def test_ring_attention_matches_oracle(hq, hkv):
    mesh = _mesh(cp=4, dp=2)
    q, k, v = _rand(0, 2, 32, hq, hkv, 16)
    expected = manual_attention(q, k, v)

    sharding = NamedSharding(mesh, P("dp_shard", "cp", None, None))
    qs, ks, vs = (jax.device_put(x, sharding) for x in (q, k, v))

    got = jax.jit(lambda q, k, v: ring_attention(q, k, v, mesh, causal=True))(qs, ks, vs)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected), rtol=2e-5, atol=2e-5)


def test_ring_attention_non_causal():
    mesh = _mesh(cp=4, dp=2)
    q, k, v = _rand(1, 1, 16, 2, 2, 16)
    expected = jax.nn.dot_product_attention(q, k, v, is_causal=False)
    sharding = NamedSharding(mesh, P(None, "cp", None, None))
    qs, ks, vs = (jax.device_put(x, sharding) for x in (q, k, v))
    got = jax.jit(lambda q, k, v: ring_attention(q, k, v, mesh, causal=False))(qs, ks, vs)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected), rtol=2e-5, atol=2e-5)


def test_ring_attention_gradients_match():
    mesh = _mesh(cp=4, dp=2)
    q, k, v = _rand(2, 1, 16, 2, 1, 8)
    sharding = NamedSharding(mesh, P(None, "cp", None, None))
    qs, ks, vs = (jax.device_put(x, sharding) for x in (q, k, v))

    g_ring = jax.jit(
        jax.grad(lambda q, k, v: ring_attention(q, k, v, mesh, causal=True).sum(), argnums=(0, 1, 2))
    )(qs, ks, vs)
    g_oracle = jax.grad(lambda q, k, v: manual_attention(q, k, v).sum(), argnums=(0, 1, 2))(q, k, v)
    for gr, go, name in zip(g_ring, g_oracle, "qkv"):
        np.testing.assert_allclose(
            np.asarray(gr), np.asarray(go), rtol=5e-4, atol=5e-4, err_msg=f"d{name} mismatch"
        )


def test_ring_attention_no_cp_axis_fallback():
    devices = np.asarray(jax.devices()[:8])
    mesh = Mesh(devices, ("dp_shard",))
    q, k, v = _rand(3, 1, 16, 2, 2, 8)
    got = ring_attention(q, k, v, mesh, causal=True)
    expected = manual_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected), rtol=2e-5, atol=2e-5)


def test_blocked_chunk_stats_match_dense():
    """The fused k-blocked local attention (flash-style online softmax inside each
    ring hop) must be numerically identical to the dense logits path."""
    from modalities_tpu.parallel.ring_attention import _chunk_attention_stats, _dense_chunk_stats

    rng = jax.random.PRNGKey(0)
    b, sq, sk, hq, hkv, d = 2, 16, 64, 4, 2, 8
    q = jax.random.normal(jax.random.fold_in(rng, 0), (b, sq, hq, d))
    k = jax.random.normal(jax.random.fold_in(rng, 1), (b, sk, hkv, d))
    v = jax.random.normal(jax.random.fold_in(rng, 2), (b, sk, hkv, d))
    for causal, q_off, k_off in [(True, 48, 0), (True, 0, 0), (False, 0, 32)]:
        dense = _dense_chunk_stats(q, k, v, q_off, k_off, causal, 0.25)
        blocked = _chunk_attention_stats(q, k, v, q_off, k_off, causal, 0.25, block_k=16)
        for a, b_ in zip(dense, blocked):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_), rtol=2e-5, atol=2e-5)


def test_blocked_chunk_stats_gradients_match_dense():
    from modalities_tpu.parallel.ring_attention import _chunk_attention_stats, _dense_chunk_stats

    rng = jax.random.PRNGKey(3)
    b, sq, sk, hq, hkv, d = 1, 8, 64, 2, 2, 4
    q = jax.random.normal(jax.random.fold_in(rng, 0), (b, sq, hq, d))
    k = jax.random.normal(jax.random.fold_in(rng, 1), (b, sk, hkv, d))
    v = jax.random.normal(jax.random.fold_in(rng, 2), (b, sk, hkv, d))

    def loss(fn, q, k, v):
        o, m, l = fn(q, k, v, 32, 0, True, 0.5)
        return (o / jnp.maximum(l, 1e-30)[..., None]).sum()

    g_dense = jax.grad(lambda q, k, v: loss(_dense_chunk_stats, q, k, v), argnums=(0, 1, 2))(q, k, v)
    g_blocked = jax.grad(
        lambda q, k, v: loss(
            lambda *a: _chunk_attention_stats(*a, block_k=16), q, k, v
        ),
        argnums=(0, 1, 2),
    )(q, k, v)
    for a, b_ in zip(g_dense, g_blocked):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), rtol=2e-4, atol=2e-4)


# ------------------------------------------------------------- flash-kernel tier


@pytest.fixture
def flash_ring(kernels_interpreted):
    """Route the ring through the Pallas-kernel hops in interpret mode (the CPU
    equivalence harness for the TPU tier, VERDICT r4 #5): the hops a TPU takes,
    by the tests' seam."""


@pytest.mark.parametrize("hq,hkv", [(4, 4), (4, 2)])
def test_flash_ring_matches_oracle(flash_ring, hq, hkv):
    """Flash-hop ring (interpret mode) vs single-device oracle, causal + GQA."""
    mesh = _mesh(cp=4, dp=2)
    q, k, v = _rand(0, 2, 32, hq, hkv, 16)
    expected = manual_attention(q, k, v)
    sharding = NamedSharding(mesh, P("dp_shard", "cp", None, None))
    qs, ks, vs = (jax.device_put(x, sharding) for x in (q, k, v))
    got = jax.jit(lambda q, k, v: ring_attention(q, k, v, mesh, causal=True))(qs, ks, vs)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected), rtol=2e-5, atol=2e-5)


def test_flash_ring_non_causal(flash_ring):
    mesh = _mesh(cp=4, dp=2)
    q, k, v = _rand(1, 1, 16, 2, 2, 16)
    expected = jax.nn.dot_product_attention(q, k, v, is_causal=False)
    sharding = NamedSharding(mesh, P(None, "cp", None, None))
    qs, ks, vs = (jax.device_put(x, sharding) for x in (q, k, v))
    got = jax.jit(lambda q, k, v: ring_attention(q, k, v, mesh, causal=False))(qs, ks, vs)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("hq,hkv", [(2, 1), (2, 2)])
def test_flash_ring_gradients_match_oracle(flash_ring, hq, hkv):
    """The custom_vjp ring backward (flash bwd kernels + rotating dk/dv accumulators)
    vs plain autodiff through the single-device oracle."""
    mesh = _mesh(cp=4, dp=2)
    q, k, v = _rand(2, 1, 16, hq, hkv, 8)
    sharding = NamedSharding(mesh, P(None, "cp", None, None))
    qs, ks, vs = (jax.device_put(x, sharding) for x in (q, k, v))

    def weighted(o):
        # position-dependent weights make dk/dv asymmetric across chunks, so a
        # misrouted accumulator rotation cannot cancel out
        w = jnp.arange(o.shape[1], dtype=o.dtype)[None, :, None, None] + 1.0
        return (o * w).sum()

    g_ring = jax.jit(
        jax.grad(lambda q, k, v: weighted(ring_attention(q, k, v, mesh, causal=True)), argnums=(0, 1, 2))
    )(qs, ks, vs)
    g_oracle = jax.grad(lambda q, k, v: weighted(manual_attention(q, k, v)), argnums=(0, 1, 2))(q, k, v)
    for gr, go, name in zip(g_ring, g_oracle, "qkv"):
        np.testing.assert_allclose(
            np.asarray(gr), np.asarray(go), rtol=5e-4, atol=5e-4, err_msg=f"d{name} mismatch"
        )


def test_flash_ring_matches_dense_ring(flash_ring):
    """Flash tier vs the dense ring tier on identical shards — the two inner-loop
    implementations must agree, not just both approximate the oracle."""
    from modalities_tpu.parallel.ring_attention import _ring_dense_local, _ring_flash_local
    from functools import partial

    mesh = _mesh(cp=4, dp=1)
    q, k, v = _rand(4, 1, 32, 4, 2, 8)
    sharding = NamedSharding(mesh, P(None, "cp", None, None))
    qs, ks, vs = (jax.device_put(x, sharding) for x in (q, k, v))
    sm = 1.0 / np.sqrt(q.shape[-1])

    def run(body):
        from modalities_tpu.parallel.jax_compat import shard_map

        fn = shard_map(
            body, mesh=mesh,
            in_specs=(P(None, "cp", None, None),) * 3,
            out_specs=P(None, "cp", None, None),
            axis_names=frozenset({"cp"}), check_vma=False,
        )
        return jax.jit(fn)(qs, ks, vs)

    dense = run(partial(_ring_dense_local, axis_name="cp", causal=True, sm_scale=sm))
    flash = run(lambda a, b, c: _ring_flash_local(a, b, c, "cp", True, sm, True))
    np.testing.assert_allclose(np.asarray(flash), np.asarray(dense), rtol=2e-5, atol=2e-5)
