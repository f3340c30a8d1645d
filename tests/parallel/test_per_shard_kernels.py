"""Pallas kernels under a mesh: GSPMD cannot partition a Mosaic custom call, so the
dispatchers run their kernels per shard (parallel/sharding.per_shard). Here the
train-path kernels (the fused norm, the fused loss, flash attention, the selective scan) run in interpret mode on a dp_shard 2 x tp 2 mesh of
virtual CPU devices and are held, values and gradients, to their unsharded
references. What only the chip's compiler can say — that the sharded step lowers
at all — chip_smoke.py --chips 4 checks on four chips."""


import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from modalities_tpu.parallel.sharding import activation_rules, default_logical_axis_rules, per_shard
from modalities_tpu.running_env.device_mesh import get_device_mesh


@pytest.fixture(scope="module")
def mesh_rules():
    handle = get_device_mesh(
        device_type="cpu", data_parallel_shard_degree=2, tensor_parallel_degree=2,
        world_size=4, devices=jax.devices()[:4],
    )
    return handle.mesh, default_logical_axis_rules(handle)


def _on_mesh(mesh_rules, fn, *args):
    mesh, rules = mesh_rules
    with mesh, activation_rules(rules, mesh):
        return jax.jit(fn)(*args)


def _rmsnorm_pair():
    from modalities_tpu.ops.rmsnorm import reference_rms_norm, rms_norm_or_fallback

    x = jax.random.normal(jax.random.PRNGKey(0), (4, 16, 32))
    scale = jax.random.normal(jax.random.PRNGKey(1), (32,)) + 1.0
    kernel = lambda x, scale: (rms_norm_or_fallback(x, scale, interpret=True) ** 2).sum()  # noqa: E731
    reference = lambda x, scale: (reference_rms_norm(x, scale) ** 2).sum()  # noqa: E731
    return kernel, reference, (x, scale)


def _fused_ce_pair():
    from modalities_tpu.ops.cross_entropy import fused_ce_sum_and_count

    hidden = jax.random.normal(jax.random.PRNGKey(0), (4, 16, 32))
    head = jax.random.normal(jax.random.PRNGKey(1), (300, 32)) * 0.1
    labels = jax.random.randint(jax.random.PRNGKey(2), (4, 16), 0, 300).at[0, :3].set(-100)

    def kernel(hidden, head):
        total, count = fused_ce_sum_and_count(hidden, head, labels, interpret=True)
        return total / count

    def reference(hidden, head):
        mask = labels != -100
        losses = optax.softmax_cross_entropy_with_integer_labels(
            jnp.einsum("bse,ve->bsv", hidden, head), jnp.where(mask, labels, 0)
        )
        return (losses * mask).sum() / mask.sum()

    return kernel, reference, (hidden, head)


def _flash_pair():
    """The attention dispatcher has no interpret switch of its own: traced inside the
    tests' seam it takes the kernel a TPU takes, interpreted."""
    import modalities_tpu.ops.attention as attention
    from modalities_tpu.ops import tiers

    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(keys[0], (4, 32, 4, 16))
    k = jax.random.normal(keys[1], (4, 32, 2, 16))  # GQA: q and kv heads split over tp together
    v = jax.random.normal(keys[2], (4, 32, 2, 16))

    def kernel(q, k, v):
        with tiers.interpreted_kernels():
            return (attention.flash_attention_or_fallback(q, k, v) ** 2).sum()

    reference = lambda q, k, v: (jax.nn.dot_product_attention(q, k, v, is_causal=True) ** 2).sum()  # noqa: E731
    return kernel, reference, (q, k, v)


def _selective_scan_pair():
    """Batch over dp_shard, d_inner over tp: b, c are whole on tp and a on dp_shard, so dB, dC
    are added up over tp and dA over dp_shard by the shard_map's transpose."""
    from modalities_tpu.ops import selective_scan as scan_ops

    keys = jax.random.split(jax.random.PRNGKey(0), 6)
    x, b, c = (jax.random.normal(k, shape) for k, shape in zip(keys, ((4, 16, 256), (4, 16, 8), (4, 16, 8))))
    dt = jax.random.uniform(keys[3], (4, 16, 256), minval=0.01, maxval=0.5)
    a = -jax.random.uniform(keys[4], (256, 8), minval=0.5, maxval=4.0)
    w = jax.random.normal(keys[5], (4, 256, 8))
    weighed = lambda y, h: (y**2).sum() + (h * w).sum()  # noqa: E731
    kernel = lambda *v: weighed(*scan_ops.selective_scan(*v, chunk=8, interpret=True))  # noqa: E731
    reference = lambda *v: weighed(*scan_ops.selective_scan(*v, chunk=8))  # noqa: E731
    return kernel, reference, (x, dt, a, b, c)


@pytest.mark.parametrize("case", ["fused_rmsnorm", "fused_ce", "flash_attention", "selective_scan"])
def test_kernel_per_shard_matches_unsharded_reference(case, mesh_rules):
    kernel, reference, args = {
        "fused_rmsnorm": _rmsnorm_pair,
        "fused_ce": _fused_ce_pair,
        "flash_attention": _flash_pair,
        "selective_scan": _selective_scan_pair,
    }[case]()
    argnums = tuple(range(len(args)))
    got = _on_mesh(mesh_rules, jax.value_and_grad(kernel, argnums=argnums), *args)
    want = jax.value_and_grad(reference, argnums=argnums)(*args)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-4), got, want)


def test_per_shard_splits_what_divides_and_gathers_the_rest(mesh_rules):
    seen = {}

    def fn(axes, x, w):
        seen.update(axes=axes, x=x.shape, w=w.shape)
        return x.sum(axis=-1)

    call = per_shard(fn, (("batch", "seq_sp", None), (None, None)), ("batch", "seq_sp"))
    x, w = jnp.ones((4, 6, 8)), jnp.ones((3, 8))
    out = _on_mesh(mesh_rules, call, x, w)
    assert seen == {"axes": ("dp_shard", "tp"), "x": (2, 3, 8), "w": (3, 8)} and out.shape == (4, 6)
    # a one-token sequence cannot split over tp: the call leaves tp out altogether
    # (as it does for q heads when the kv heads do not divide)
    _on_mesh(mesh_rules, call, jnp.ones((4, 1, 8)), w)
    assert seen == {"axes": ("dp_shard",), "x": (2, 1, 8), "w": (3, 8)}
    # no mesh installed: the function as it is
    call(x, w)
    assert seen == {"axes": (), "x": (4, 6, 8), "w": (3, 8)}
