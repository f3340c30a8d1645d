"""The two edges of a sequence-parallel region (`parallel/sharding.gather_seq`, `column_product`, `scatter_seq`) on
meshes of the CPU's virtual devices: values and gradients held to the plain products on one device, the cases in
which the products stay `jax.lax.dot_general` (no `shard_map` in the jaxpr), and the dense toy step over
`dp_shard 2 x tp 2` against the step on one device. Whether the chip's compiler keeps the edges' collectives as
reduce-scatters only a compile for the chip says: `tests/ops/test_tpu_compile.py`."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import linen as nn

from modalities_tpu.parallel import sharding
from modalities_tpu.parallel.jax_compat import shard_map
from modalities_tpu.running_env.device_mesh import get_device_mesh

SEQ, EMBED, HEADS, HEAD_DIM = 16, 32, 4, 8
MESHES = {"dp2_tp2": dict(data_parallel_shard_degree=2, tensor_parallel_degree=2),
          "dp1_tp4": dict(data_parallel_shard_degree=1, tensor_parallel_degree=4),
          "cp2_tp2": dict(data_parallel_shard_degree=1, context_parallel_degree=2, tensor_parallel_degree=2)}


def mesh_rules(name):
    handle = get_device_mesh(device_type="cpu", world_size=4, devices=jax.devices()[:4], **MESHES[name])
    return handle.mesh, sharding.default_logical_axis_rules(handle)


class Region(nn.Module):
    """A region as the dense block has them: column-parallel products of the rows behind `gather_seq` (the gather and
    the products one checkpoint), something a head does on its own, a row-parallel product through `scatter_seq`,
    added to the rows."""

    bias: bool

    @nn.compact
    def __call__(self, x):
        region = sharding.seq_region(x.shape[0], x.shape[1], HEADS)
        dense = lambda name, features, axis, dot: nn.DenseGeneral(  # noqa: E731
            features, axis=axis, use_bias=self.bias, name=name, bias_init=nn.initializers.normal(0.5), dot_general=dot)

        def products(_, rows):
            if region is None:
                return dense("up", (HEADS, HEAD_DIM), -1, None)(rows), dense("gate", (HEADS, HEAD_DIM), -1, None)(rows)
            copies, dot = sharding.gather_seq(region, rows), sharding.column_product(region)
            return dense("up", (HEADS, HEAD_DIM), -1, dot)(copies)[0], dense("gate", (HEADS, HEAD_DIM), -1, dot)(copies)[0]

        u, g = products(self, x) if region is None else nn.remat(products)(self, x)
        out = dense("down", EMBED, (-2, -1), None if region is None else sharding.scatter_seq(region))(jnp.tanh(u) * g)
        return sharding.constrain_activation(x, ("batch", "seq_sp", "embed")) + out


def value_and_grads(module, params, x, twice=False):
    """`twice`: the same weights walked a second time in one step, as the looped stack walks its layers."""
    walks = lambda p, x: module.apply(p, module.apply(p, x)) if twice else module.apply(p, x)  # noqa: E731
    return jax.jit(jax.value_and_grad(lambda p, x: (walks(p, x) ** 2).sum(), argnums=(0, 1)))(params, x)


@pytest.mark.parametrize("twice", [False, True], ids=["once", "weights_used_twice"])
@pytest.mark.parametrize("bias", [False, True], ids=["no_bias", "bias"])
@pytest.mark.parametrize("rows_a_chip", [1, 2])
@pytest.mark.parametrize("mesh_name", list(MESHES))
def test_a_region_between_its_two_edges_is_the_plain_products_on_one_device(mesh_name, rows_a_chip, bias, twice):
    mesh, rules = mesh_rules(mesh_name)
    batch = rows_a_chip * mesh.shape["dp_shard"]
    x = jax.random.normal(jax.random.PRNGKey(0), (batch, SEQ, EMBED))
    module = Region(bias)
    params = module.init(jax.random.PRNGKey(1), x)
    plain, (plain_dp, plain_dx) = value_and_grads(module, params, x, twice)
    with mesh, sharding.activation_rules(rules, mesh):
        assert "shard_map" in str(jax.make_jaxpr(lambda p, x: module.apply(p, x))(params, x))
        edged, (edged_dp, edged_dx) = value_and_grads(module, params, x, twice)
    np.testing.assert_allclose(edged, plain, rtol=2e-5)
    np.testing.assert_allclose(edged_dx, plain_dx, rtol=2e-4, atol=2e-4)
    for got, want in zip(jax.tree.leaves(edged_dp), jax.tree.leaves(plain_dp)):
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_the_edges_sum_in_the_compute_dtype_as_the_partitioners_sum_does():
    mesh, rules = mesh_rules("dp2_tp2")
    x = jax.random.normal(jax.random.PRNGKey(0), (2, SEQ, EMBED), jnp.bfloat16)
    module = Region(bias=False)
    params = jax.tree.map(lambda p: p.astype(jnp.bfloat16), module.init(jax.random.PRNGKey(1), x))
    plain, _ = value_and_grads(module, params, x)
    with mesh, sharding.activation_rules(rules, mesh):
        edged, (edged_dp, _) = value_and_grads(module, params, x)
    assert all(leaf.dtype == jnp.bfloat16 for leaf in jax.tree.leaves(edged_dp))
    np.testing.assert_allclose(np.float32(edged), np.float32(plain), rtol=2e-2)


def jaxpr_of_the_region(x):
    module = Region(bias=False)
    params = jax.eval_shape(lambda: module.init(jax.random.PRNGKey(1), x))
    return str(jax.make_jaxpr(lambda p, x: module.apply(p, x))(params, x))


@pytest.mark.parametrize("case", ["no_rules", "tp_1", "rows_tp_does_not_divide", "heads_tp_does_not_divide", "rows_dp_does_not_divide"])
def test_where_the_mesh_gives_no_region_the_products_are_the_plain_ones(case):
    x = jnp.zeros((2, SEQ, EMBED))
    if case == "no_rules":
        assert sharding.seq_region(2, SEQ, HEADS) is None and "shard_map" not in jaxpr_of_the_region(x)
        return
    degrees = dict(data_parallel_shard_degree=4) if case == "tp_1" else MESHES["dp1_tp4" if case == "heads_tp_does_not_divide" else "dp2_tp2"]
    handle = get_device_mesh(device_type="cpu", world_size=4, devices=jax.devices()[:4], **degrees)
    shape, heads = {"tp_1": ((4, SEQ), HEADS), "rows_tp_does_not_divide": ((2, SEQ + 1), HEADS),
                    "heads_tp_does_not_divide": ((2, SEQ), 6), "rows_dp_does_not_divide": ((3, SEQ), HEADS)}[case]
    with handle.mesh, sharding.activation_rules(sharding.default_logical_axis_rules(handle), handle.mesh):
        assert sharding.seq_region(*shape, heads) is None
        if heads == HEADS:
            assert "shard_map" not in jaxpr_of_the_region(jnp.zeros((*shape, EMBED)))
        assert (sharding.seq_region(4, SEQ, HEADS) is None) == (case == "tp_1")  # the same mesh has a region for shapes it divides


def test_inside_a_manual_region_the_products_are_the_plain_ones():
    mesh, rules = mesh_rules("dp2_tp2")
    seen = []

    def body(x):
        seen.append(sharding.seq_region(x.shape[0], x.shape[1], HEADS))
        return x

    with mesh, sharding.activation_rules(rules, mesh):
        assert sharding.seq_region(2, SEQ, HEADS) is not None
        jax.make_jaxpr(shard_map(body, mesh=mesh, in_specs=jax.sharding.PartitionSpec("tp"), out_specs=jax.sharding.PartitionSpec("tp"),
                                 axis_names={"tp"}))(jnp.zeros((2, SEQ, EMBED)))
    assert seen == [None]


def test_the_rows_of_the_residual_stream_are_split_over_cp_then_tp():
    mesh, rules = mesh_rules("cp2_tp2")
    assert dict(rules)["seq_sp"] == ("cp", "tp") and dict(rules)["seq"] == "cp"
    with mesh, sharding.activation_rules(rules, mesh):
        region = sharding.seq_region(1, SEQ, HEADS)
        assert region.axes == ("cp", "tp") and region.cp == ("cp",)
        assert sharding.shard_shape((1, SEQ, EMBED), ("batch", "seq_sp", "embed")) == (1, SEQ // 4, EMBED)
        copies = jax.jit(lambda x: sharding.gather_seq(region, x))(jnp.arange(SEQ, dtype=jnp.float32)[None, :, None] * jnp.ones((1, 1, EMBED)))
    assert copies.shape == (2, 1, SEQ, EMBED)  # a copy a tp shard, each of all the rows in their order
    np.testing.assert_array_equal(copies[:, 0, :, 0], np.tile(np.arange(SEQ, dtype=np.float32), (2, 1)))


# ---------------------------------------------------------------- the dense toy step
# the limits `tests/benchmark/test_rehearsal_train_mesh.py` holds the mesh cell's toy run to against its float32 reference
TOY_LIMITS = {"loss_rel_gap": 1e-3, "grad_norm_rel_gap": 0.01, "grad_rel_error": 0.012}


def toy_step(mesh_handle):
    from tests.models.test_gpt2_model import tiny_gpt2
    from tests.training.test_train_step import _batch, _builder

    builder = _builder(tiny_gpt2("pytorch_flash", activation_type="swiglu"), mesh_handle, clip=1.0)
    builder.expose_grads = True
    fns = builder.build(seed=0)
    raw = _batch(np.random.default_rng(5), 1, 4, 16)
    text = fns.lower_train_step(fns.put_batch(raw)).as_text()
    _, metrics = fns.train_step_debug(fns.app_state_handle.state, fns.put_batch(raw))
    return text, jax.device_get(metrics)


@pytest.fixture(scope="module")
def toy_steps():
    one = get_device_mesh(device_type="cpu", data_parallel_shard_degree=1, world_size=1, devices=jax.devices()[:1])
    four = get_device_mesh(device_type="cpu", world_size=4, devices=jax.devices()[:4], **MESHES["dp2_tp2"])
    return toy_step(one), toy_step(four)


def test_the_dense_toy_step_over_dp_shard_2_x_tp_2_takes_the_edges_and_the_step_on_one_device_does_not(toy_steps):
    (one_text, _), (four_text, _) = toy_steps
    assert "reduce_scatter" not in one_text and "all_gather" not in one_text
    # a block's four edges forward (two gathers, two scatters), each with its transpose, and the two gathers again in the backward
    assert four_text.count("stablehlo.reduce_scatter") >= 4 and four_text.count("stablehlo.all_gather") >= 4


@pytest.mark.parametrize("what", list(TOY_LIMITS))
def test_the_dense_toy_step_over_dp_shard_2_x_tp_2_keeps_the_one_device_steps(toy_steps, what):
    (_, one), (_, four) = toy_steps
    flat = lambda metrics: np.concatenate([np.ravel(np.float64(g)) for g in jax.tree.leaves(metrics["grads"])])  # noqa: E731
    got = {"loss_rel_gap": abs(float(four["loss"]) - float(one["loss"])) / abs(float(one["loss"])),
           "grad_norm_rel_gap": abs(float(four["grad_norm"]) - float(one["grad_norm"])) / float(one["grad_norm"]),
           "grad_rel_error": np.linalg.norm(flat(four) - flat(one)) / np.linalg.norm(flat(one))}[what]
    assert got <= TOY_LIMITS[what], (what, got)
