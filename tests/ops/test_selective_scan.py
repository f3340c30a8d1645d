"""ops/selective_scan.py: the chunked scan with its hand-written backward pass against
the recurrence as a plain loop over time that autodiff differentiates, in both of its
forms (the plain `lax.scan` form that CPU runs, and the Pallas kernels of
ops/pallas/selective_scan.py in interpret mode), and the causal depthwise convolution
against a direct sum."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from modalities_tpu.ops import selective_scan as scan_ops
from modalities_tpu.ops.pallas.selective_scan import plan_blocks
from modalities_tpu.ops.selective_scan import causal_depthwise_conv, scan_plan, selective_scan

B, S, D, N = 2, 37, 24, 4


def loop(x, dt, a, b, c, h0):
    """h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t; y_t = h_t C_t, one step at a time."""

    def step(h, inputs):
        dt_t, x_t, b_t, c_t = inputs
        h = jnp.exp(dt_t[:, :, None] * a) * h + (dt_t * x_t)[:, :, None] * b_t[:, None, :]
        return h, jnp.einsum("bdn,bn->bd", h, c_t)

    h, y = jax.lax.scan(step, h0, tuple(jnp.moveaxis(v, 1, 0) for v in (dt, x, b, c)))
    return jnp.moveaxis(y, 0, 1), h


@functools.lru_cache(maxsize=None)
def plain_inputs():
    rng = np.random.default_rng(0)
    f = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)  # noqa: E731
    return {"x": f(B, S, D), "dt": jnp.asarray(rng.uniform(0.01, 0.5, size=(B, S, D)), jnp.float32),
            "a": -jnp.asarray(rng.uniform(0.5, 4, size=(D, N)), jnp.float32), "b": f(B, S, N), "c": f(B, S, N),
            "h0": f(B, D, N), "wy": f(B, S, D), "wh": f(B, D, N)}


@pytest.fixture(scope="module")
def inputs():
    return plain_inputs()


def weighed_scan(v, scan):
    """`scan`'s values and the gradients in all six arguments of its outputs' sum weighed by `wy` and `wh`, as one jitted program."""
    def both(*args):
        weighed = lambda *a: (lambda y, h: (jnp.sum(y * v["wy"]) + jnp.sum(h * v["wh"]), (y, h)))(*scan(*a))  # noqa: E731
        (_, values), grads = jax.value_and_grad(weighed, argnums=tuple(range(6)), has_aux=True)(*args)
        return values, grads

    return jax.jit(both)


def arguments(v, carried):
    return (v["x"], v["dt"], v["a"], v["b"], v["c"], v["h0"] if carried else jnp.zeros_like(v["h0"]))


@functools.lru_cache(maxsize=None)
def the_loop(batch, seq, carried):
    """The loop's values and gradients on the inputs of one shape (seq 0: the plain form's), walked once for all the chunkings held to it."""
    v = kernel_inputs(batch, seq) if seq else plain_inputs()
    return jax.tree.map(np.asarray, weighed_scan(v, loop)(*arguments(v, carried)))


def held_to_the_loop(got, want, atol_y):
    (y, h), grads = got
    (want_y, want_h), want_grads = want
    np.testing.assert_allclose(y, want_y, atol=atol_y)
    np.testing.assert_allclose(h, want_h, atol=2e-6)
    for name, g, w in zip(("x", "dt", "a", "b", "c", "h0"), grads, want_grads):
        assert float(np.abs(np.asarray(g) - w).max() / np.abs(w).max()) < 2e-6, name


# chunks that divide the 37 steps (37, 1), that do not (8, 5), and one longer than the sequence
@pytest.mark.parametrize("chunk", [8, 37, 5, 1, 64])
@pytest.mark.parametrize("carried", [False, True], ids=["from_zero", "carried_state"])
def test_chunked_scan_is_the_loop_in_values_and_gradients(inputs, chunk, carried):
    v, args = inputs, arguments(inputs, carried)
    want = the_loop(B, 0, carried)
    if not carried:  # without a state handed in the scan starts from zero
        y, h = selective_scan(*args[:5], chunk=chunk)
        np.testing.assert_allclose(y, want[0][0], atol=2e-6)
        np.testing.assert_allclose(h, want[0][1], atol=2e-6)
    held_to_the_loop(weighed_scan(v, lambda *a: selective_scan(*a[:5], chunk=chunk, h0=a[5]))(*args), want, atol_y=2e-6)


@functools.lru_cache(maxsize=None)
def kernel_inputs(batch, seq, d_inner=384, d_state=8):
    """d_inner 384 is three blocks of 128 (512 and 256 do not divide it), so the partial dB, dC of the blocks are summed."""
    rng = np.random.default_rng(seq)
    f = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)  # noqa: E731
    return {"x": f(batch, seq, d_inner), "dt": jnp.asarray(rng.uniform(0.01, 0.5, size=(batch, seq, d_inner)), jnp.float32),
            "a": -jnp.asarray(rng.uniform(0.5, 4, size=(d_inner, d_state)), jnp.float32), "b": f(batch, seq, d_state),
            "c": f(batch, seq, d_state), "h0": f(batch, d_inner, d_state), "wy": f(batch, seq, d_inner), "wh": f(batch, d_inner, d_state)}


# the kernels walk whole sublane tiles: 40 steps in chunks of 8 (divides) and 16 (pads to 48), 37 in chunks of 8 (pads
# to 40) and 24 (pads to 48), and a chunk longer than the sequence (one chunk of 40)
@pytest.mark.parametrize("seq, chunk", [(40, 8), (40, 16), (37, 8), (37, 24), (37, 64)])
@pytest.mark.parametrize("batch, carried", [(1, False), (2, True), (2, False)], ids=["b1_from_zero", "b2_carried_state", "b2_from_zero"])
def test_interpreted_kernels_are_the_loop_in_values_and_gradients(seq, chunk, batch, carried):
    """Same tolerance as the plain form's test: what differs from the loop is float32
    round-off of another summation order (dA over the steps, dB and dC over lanes and
    blocks) and `exp2(v log2 e)` for `exp(v)`; a dropped term would be 1e-2 and more."""
    v = kernel_inputs(batch, seq)
    args, want = arguments(v, carried), the_loop(batch, seq, carried)
    assert plan_blocks(seq, 384, 8, chunk)[1] == 128
    if not carried:  # without a state handed in the kernels start from zero
        y, h = selective_scan(*args[:5], chunk=chunk, interpret=True)
        np.testing.assert_allclose(y, want[0][0], atol=4e-6)
        np.testing.assert_allclose(h, want[0][1], atol=2e-6)
    held_to_the_loop(weighed_scan(v, lambda *a: selective_scan(*a[:5], chunk=chunk, h0=a[5], interpret=True))(*args), want, atol_y=4e-6)


def test_the_plain_form_runs_off_a_tpu_and_the_kernels_refuse_what_their_layout_cannot_hold(inputs):
    v = inputs
    assert not scan_ops.uses_kernels() and scan_ops.uses_kernels(interpret=True)
    plain = jax.make_jaxpr(lambda *a: selective_scan(*a, chunk=8))(v["x"], v["dt"], v["a"], v["b"], v["c"])
    assert "pallas_call" not in str(plain) and "scan" in str(plain)
    k = kernel_inputs(1, 16, d_inner=128)
    kernels = jax.make_jaxpr(lambda *a: selective_scan(*a, chunk=8, interpret=True))(k["x"], k["dt"], k["a"], k["b"], k["c"])
    assert str(kernels).count("pallas_call") == 1 and "selective_scan_fwd" in str(kernels)
    # d_inner 24 is no multiple of 128, d_state 4 no multiple of 8: no second tier takes them, the shape is in the message
    with pytest.raises(ValueError, match=r"d_inner 24 .* multiple of 128"):
        selective_scan(v["x"], v["dt"], v["a"], v["b"], v["c"], chunk=8, interpret=True)
    with pytest.raises(ValueError, match=r"d_state 4 .* multiple of 8"):
        selective_scan(k["x"], k["dt"], k["a"][:, :4], k["b"][..., :4], k["c"][..., :4], chunk=8, interpret=True)
    with pytest.raises(ValueError, match="no d_inner block"):
        plan_blocks(4096, 5120, 16, 4096)  # a chunk of 4096 steps: its states do not fit the VMEM budget at any block


def test_bfloat16_inputs_are_widened_and_the_state_stays_float32(inputs):
    v = inputs
    y, h = selective_scan(v["x"].astype(jnp.bfloat16), v["dt"], v["a"], v["b"], v["c"], chunk=16)
    assert y.dtype == jnp.float32 and h.dtype == jnp.float32
    want_y, _ = loop(v["x"].astype(jnp.bfloat16).astype(jnp.float32), v["dt"], v["a"], v["b"], v["c"], jnp.zeros_like(v["h0"]))
    np.testing.assert_allclose(y, want_y, atol=2e-6)


def test_causal_depthwise_convolution_is_the_direct_sum():
    rng = np.random.default_rng(1)
    x, kernel, bias = rng.normal(size=(2, 9, 6)), rng.normal(size=(4, 6)), rng.normal(size=(6,))
    want = np.zeros_like(x)
    for t in range(9):
        for k in range(4):
            if t - 3 + k >= 0:  # the last tap weighs the current step; nothing before the row starts
                want[:, t] += kernel[k] * x[:, t - 3 + k]
    got = causal_depthwise_conv(jnp.asarray(x, jnp.float32), jnp.asarray(kernel, jnp.float32), jnp.asarray(bias, jnp.float32))
    np.testing.assert_allclose(got, want + bias, atol=1e-5)
    # causal: a later input moves no earlier output
    moved = causal_depthwise_conv(jnp.asarray(x, jnp.float32).at[:, 5].add(1.0), jnp.asarray(kernel, jnp.float32))
    np.testing.assert_allclose(moved[:, :5], (want)[:, :5], atol=1e-5)


def test_scan_plan_counts_the_cells_shape():
    plan = scan_plan(batch=1, seq=4096, d_inner=5120, d_state=16, chunk=128)
    assert plan["chunks"] == 32 and plan["state_bytes_carried"] == 4 * 5120 * 16 == 327_680
    assert plan["boundary_state_bytes"] == 32 * 327_680 and plan["backward_bytes_per_chunk"] == 128 * 327_680
    # the whole sequence's states, which are never held: 1.34 GB
    assert 4096 * 327_680 == 1_342_177_280
    assert scan_plan(1, 100, 8, 2, 48)["chunks"] == 3 and scan_plan(1, 8, 8, 2, 48)["chunk"] == 8
    assert (plan["kernel"], plan["block_d"], plan["grid_steps"], plan["vmem_state_bytes"]) == (False, 0, 0, 0)
    # what the Pallas kernels run with at that shape: ten blocks of 512 channels, 320 grid steps a kernel, and the
    # backward's chunk of states (128 x 16 x 512 floats) in VMEM where the plain form held 42 MB in HBM
    kernels = scan_plan(batch=1, seq=4096, d_inner=5120, d_state=16, chunk=128, kernel=True)
    assert {**kernels, "kernel": False, "block_d": 0, "grid_steps": 0, "vmem_state_bytes": 0} == plan
    assert (kernels["kernel"], kernels["block_d"], kernels["grid_steps"], kernels["vmem_state_bytes"]) == (True, 512, 320, 4 * 2**20)
    # a shard of d_inner under tp 4 (1280 channels) takes blocks of 256; a short sequence one chunk of whole sublane tiles
    assert scan_plan(2, 4096, 1280, 16, 128, kernel=True)["block_d"] == 256
    short = scan_plan(2, 37, 256, 8, 128, kernel=True)
    assert (short["chunk"], short["chunks"], short["grid_steps"]) == (40, 1, 2)
