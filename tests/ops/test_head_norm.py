"""The rule mixer's two norms over a head's channels as Pallas kernels (`ops/pallas/head_norm.py`, PR 46), interpreted
on the CPU against the plain forms `models/gpt2/gdn.py` keeps: outputs and every gradient, the planner, the dispatch
under a mesh, and the whole mixer with the kernels in against the same mixer traced plain."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax.core import meta

from modalities_tpu.models.gpt2 import gdn
from modalities_tpu.ops import head_norm, tiers
from modalities_tpu.ops.pallas import head_norm as kernels

EPS = 1e-6
BLOCK = 128  # positions a grid step in these tests: two slabs of 64


@pytest.fixture(autouse=True)
def slabs_of_64(monkeypatch):
    """The kernels' loop over a block's slabs takes more than one pass at these sizes (read while tracing)."""
    monkeypatch.setattr(kernels, "SLAB", 64)


def plain_l2(x, scale):
    return gdn.l2_normalised(x, scale).astype(x.dtype)


def plain_gated(o, z, w, eps=EPS):
    """`GatedDeltaNet`'s own lines under `gdn/out_norm`."""
    o32 = o.astype(jnp.float32)
    y = o32 * jax.lax.rsqrt(jnp.mean(o32 * o32, axis=-1, keepdims=True) + eps) * w * jax.nn.silu(z.astype(jnp.float32))
    return y.astype(o.dtype)


def drawn(shape, dtype, seed):
    return (3.0 * jax.random.normal(jax.random.PRNGKey(seed), shape)).astype(dtype)


def gap(got, want) -> float:
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)), 1e-30))


# one rounding of the output's dtype, and float32 sums in another order
LIMIT = {jnp.float32: 2e-6, jnp.bfloat16: 2 ** -7}
# positions (one row of S, every head a column of the grid) that fill whole blocks of 128, that leave the last grid step short, fewer than a slab
ROWS = {"whole_blocks": 256, "a_short_last_block": 144, "under_a_slab": 32}


@pytest.mark.parametrize("rows", sorted(ROWS))
@pytest.mark.parametrize("scale", [1.0, 128 ** -0.5])
@pytest.mark.parametrize("heads", [16, 32])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bfloat16", "float32"])
def test_the_l2_kernels_are_the_plain_form_output_and_gradient(dtype, heads, scale, rows):
    x, dy = drawn((1, ROWS[rows], heads, 128), dtype, 0), drawn((1, ROWS[rows], heads, 128), jnp.float32, 1)
    kernel = functools.partial(kernels.head_l2_norm, scale=scale, block_rows=BLOCK, interpret=True)
    both = lambda fn: jax.jit(lambda x: (fn(x), jax.grad(lambda x: (fn(x).astype(jnp.float32) * dy).sum())(x)))  # noqa: E731  one program a form
    (got, got_dx), (want, want_dx) = both(kernel)(x), both(functools.partial(plain_l2, scale=scale))(x)
    assert got.dtype == got_dx.dtype == x.dtype and got.shape == x.shape
    assert gap(got, want) <= LIMIT[dtype]
    assert gap(got_dx, want_dx) <= LIMIT[dtype]


@pytest.mark.parametrize("rows", sorted(ROWS))
@pytest.mark.parametrize("heads", [16, 32])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bfloat16", "float32"])
def test_the_gated_norms_kernels_are_the_plain_form_output_and_every_gradient(dtype, heads, rows):
    shape = (1, ROWS[rows], heads, 128)
    o, z, dy = drawn(shape, dtype, 2), drawn(shape, dtype, 3), drawn(shape, jnp.float32, 4)
    w = 1.0 + 0.1 * jax.random.normal(jax.random.PRNGKey(5), (128,))
    kernel = functools.partial(kernels.gated_head_rms_norm, eps=EPS, block_rows=BLOCK, interpret=True)
    both = lambda fn: jax.jit(lambda o, z, w: (fn(o, z, w), *jax.grad(lambda o, z, w: (fn(o, z, w).astype(jnp.float32) * dy).sum(), argnums=(0, 1, 2))(o, z, w)))  # noqa: E731
    got, want = both(kernel)(o, z, w), both(plain_gated)(o, z, w)
    assert [g.dtype for g in got] == [dtype, dtype, dtype, jnp.float32]  # `w` stays a float32 parameter with a float32 gradient
    for name, a, b in zip(("y", "do", "dz", "dw"), got, want):
        # dw: a sum over the rows of products the plain form rounds to the output's dtype on the way back, the kernel does not
        assert gap(a, b) <= (LIMIT[dtype] if name != "dw" or dtype == jnp.float32 else 2 ** -6), name


def test_a_block_that_reaches_past_the_rows_adds_nothing_to_dw():
    """Rows of 144 in blocks of 128: the second grid step's last 112 rows lie outside the array. Their `dw` must not count,
    whatever the emulator (NaN) or the chip (what the buffer held) puts there."""
    o, z, dy = (drawn((144, 128), jnp.float32, seed) for seed in (6, 7, 8))
    w = jnp.ones((128,))
    dw = jax.grad(lambda w: (kernels.gated_head_rms_norm(o, z, w, eps=EPS, block_rows=BLOCK, interpret=True) * dy).sum())(w)
    assert np.all(np.isfinite(np.asarray(dw))) and gap(dw, jax.grad(lambda w: (plain_gated(o, z, w) * dy).sum())(w)) <= 2e-6


# positions, width, dtype -> positions a grid step (0: the plain form)
PLANS = {"the_cells_row_of_16384": ((16384, 128, jnp.bfloat16), kernels.BLOCK_ROWS),
         "float32": ((4096, 128, jnp.float32), kernels.BLOCK_ROWS), "two_lane_tiles": ((4096, 256, jnp.bfloat16), kernels.BLOCK_ROWS),
         "fewer_rows_than_a_block": ((1040, 128, jnp.bfloat16), 1024), "fewer_rows_than_a_slab": ((32, 128, jnp.bfloat16), 32),
         "the_tests_row_of_128": ((128, 128, jnp.bfloat16), 128),
         "heads_of_16": ((4096, 16, jnp.bfloat16), 0), "heads_of_80": ((4096, 80, jnp.bfloat16), 0), "heads_of_192": ((4096, 192, jnp.bfloat16), 0),
         "rows_that_are_no_whole_bfloat16_tiles": ((24, 128, jnp.bfloat16), 0), "eight_float32_rows": ((8, 128, jnp.float32), 8),
         "float16": ((4096, 128, jnp.float16), 0), "int8": ((4096, 128, jnp.int8), 0)}


@pytest.mark.parametrize("case", sorted(PLANS))
def test_the_planner_serves_whole_tiles_and_the_rest_takes_the_plain_form(case):
    (rows, width, dtype), block = PLANS[case]
    assert kernels.plan_rows(rows, width, dtype) == block
    shape = (1, rows, 8, width)
    for norm in ("l2", "gated"):
        assert head_norm.kernels(norm, shape, dtype) == ()  # off a TPU the plain form, whatever the shape
        with tiers.interpreted_kernels():
            assert head_norm.kernels(norm, shape, dtype) == (kernels.KERNELS[norm] if block else ())
    if not kernels.plan_rows(4096, width, dtype):  # rows short of a tile are filled up with zeros (a shard's); a width or a dtype is refused by name
        with pytest.raises(ValueError, match="no kernel for rows of"):
            kernels.head_l2_norm(jnp.zeros((rows, width), dtype), interpret=True)


def test_a_last_axis_of_16_or_80_takes_the_plain_form_in_the_mixer():
    """Traced, not run: under `interpreted_kernels()` a mixer at heads of 16 (the tests' toy) and of 80 holds the walk's scan and
    no `pallas_call` of a norm; at heads of 128 it holds all four."""
    from tests.models.test_gdn_moe import GDN, SEQ, build

    def traced(dim: int) -> str:
        spec = build(gdn_config={**GDN, "linear_key_head_dim": dim, "linear_value_head_dim": dim}).config_spec
        with tiers.interpreted_kernels():
            fn = lambda h: gdn.GatedDeltaNet(spec).init_with_output(jax.random.PRNGKey(0), h)[0][0].astype(jnp.float32).sum()  # noqa: E731
            return str(jax.make_jaxpr(jax.grad(fn))(jnp.zeros((1, SEQ, 128), jnp.bfloat16)))

    for text in map(traced, (16, 80)):
        assert "head_l2_norm" not in text and "gated_head_rms_norm" not in text
    text = traced(128)
    for name in (*kernels.KERNELS["l2"], *kernels.KERNELS["gated"]):
        assert name in text, name


@pytest.fixture(scope="module")
def mixer_at_heads_of_128():
    """A mixer at 2 key and 4 value heads of 128, computing in bfloat16 on float32 parameters moved off their constants, its
    input and the weights of the sum its gradients are taken of."""
    from tests.models.test_gdn_moe import GDN, SEQ, build, stirred

    spec = build(gdn_config={**GDN, "linear_key_head_dim": 128, "linear_value_head_dim": 128}).with_spec_updates(compute_dtype="bfloat16").config_spec
    module = gdn.GatedDeltaNet(spec)
    h = drawn((2, SEQ, 128), jnp.bfloat16, 11)
    params = stirred(meta.unbox(module.init(jax.random.PRNGKey(0), h)))
    weights = drawn((2, SEQ, 128), jnp.float32, 12)

    def program():
        return jax.jit(jax.value_and_grad(lambda p, h: (module.apply(p, h)[0].astype(jnp.float32) * weights).sum(), argnums=(0, 1)))

    return program, params, h


def test_the_whole_mixer_with_the_kernels_in_is_the_mixer_traced_plain(mixer_at_heads_of_128):
    """Output (through a weighted sum) and the gradient of every parameter and of the input: the kernels' walk is in both
    traces' place only where the seam is open, so the plain side here is what a CPU run computes."""
    program, params, h = mixer_at_heads_of_128
    want, (want_params, want_h) = program()(params, h)
    with tiers.interpreted_kernels():
        text = str(jax.make_jaxpr(program())(params, h))
        assert all(name in text for names in kernels.KERNELS.values() for name in names)
        got, (got_params, got_h) = program()(params, h)
    assert abs(float(got) - float(want)) <= 2e-2 * abs(float(want)) + 1e-2
    assert gap(got_h, want_h) <= 3e-2
    flat = lambda tree: {jax.tree_util.keystr(path): leaf for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]}  # noqa: E731
    got_params, want_params = flat(got_params), flat(want_params)
    assert got_params.keys() == want_params.keys() and any("out_norm_scale" in name for name in got_params)
    for name, leaf in got_params.items():
        assert gap(leaf, want_params[name]) <= 3e-2, name


def test_under_a_mesh_the_norms_run_per_shard_of_batch_and_heads():
    """dp_shard 2 x tp 2 on the CPU's virtual devices: both norms go through `per_shard` (a `shard_map` over both axes), `w` whole
    on every shard and its gradient summed over them; outputs and gradients are the plain forms'."""
    from modalities_tpu.parallel.sharding import activation_rules, default_logical_axis_rules
    from modalities_tpu.running_env.device_mesh import get_device_mesh

    handle = get_device_mesh(device_type="cpu", world_size=4, data_parallel_shard_degree=2, tensor_parallel_degree=2)
    shape = (2, 8, 4, 128)
    x, o, z, dy = (drawn(shape, jnp.float32, seed) for seed in (13, 14, 15, 16))
    w = 1.0 + 0.1 * jax.random.normal(jax.random.PRNGKey(17), (128,))
    l2 = lambda fn: jax.grad(lambda x: (fn(x, 0.5) * dy).sum())  # noqa: E731
    gated = lambda fn: jax.grad(lambda o, z, w: (fn(o, z, w, eps=EPS) * dy).sum(), argnums=(0, 1, 2))  # noqa: E731
    with handle.mesh, activation_rules(default_logical_axis_rules(handle), handle.mesh), tiers.interpreted_kernels():
        text = str(jax.make_jaxpr(gated(head_norm.gated_head_rms_norm))(o, z, w)) + str(jax.make_jaxpr(l2(head_norm.head_l2_norm))(x))
        assert "shard_map" in text and all(name in text for names in kernels.KERNELS.values() for name in names)
        got_y, got_n = jax.jit(functools.partial(head_norm.gated_head_rms_norm, eps=EPS))(o, z, w), jax.jit(head_norm.head_l2_norm)(x)
        got = (*jax.jit(gated(head_norm.gated_head_rms_norm))(o, z, w), jax.jit(l2(head_norm.head_l2_norm))(x))
    want = (*gated(plain_gated)(o, z, w), l2(plain_l2)(x))
    assert gap(got_y, plain_gated(o, z, w)) <= 2e-6 and gap(got_n, plain_l2(x, 1.0)) <= 2e-6
    for name, a, b in zip(("do", "dz", "dw", "dx"), got, want):
        assert gap(a, b) <= 2e-6, name
