"""ops/selective_scan.py: the chunked scan with its hand-written backward pass against
the recurrence as a plain loop over time that autodiff differentiates, and the causal
depthwise convolution against a direct sum."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

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


@pytest.fixture(scope="module")
def inputs():
    rng = np.random.default_rng(0)
    f = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)  # noqa: E731
    return {"x": f(B, S, D), "dt": jnp.asarray(rng.uniform(0.01, 0.5, size=(B, S, D)), jnp.float32),
            "a": -jnp.asarray(rng.uniform(0.5, 4, size=(D, N)), jnp.float32), "b": f(B, S, N), "c": f(B, S, N),
            "h0": f(B, D, N), "wy": f(B, S, D), "wh": f(B, D, N)}


# chunks that divide the 37 steps (37, 1), that do not (8, 5), and one longer than the sequence
@pytest.mark.parametrize("chunk", [8, 37, 5, 1, 64])
@pytest.mark.parametrize("carried", [False, True], ids=["from_zero", "carried_state"])
def test_chunked_scan_is_the_loop_in_values_and_gradients(inputs, chunk, carried):
    v = inputs
    h0 = v["h0"] if carried else jnp.zeros_like(v["h0"])
    args = (v["x"], v["dt"], v["a"], v["b"], v["c"], h0)
    y, h = selective_scan(*args[:5], chunk=chunk, h0=h0 if carried else None)
    want_y, want_h = loop(*args)
    np.testing.assert_allclose(y, want_y, atol=2e-6)
    np.testing.assert_allclose(h, want_h, atol=2e-6)

    weighed = lambda y, h: jnp.sum(y * v["wy"]) + jnp.sum(h * v["wh"])  # noqa: E731
    got = jax.grad(lambda *a: weighed(*selective_scan(*a[:5], chunk=chunk, h0=a[5])), argnums=range(6))(*args)
    want = jax.grad(lambda *a: weighed(*loop(*a)), argnums=range(6))(*args)
    for name, g, w in zip(("x", "dt", "a", "b", "c", "h0"), got, want):
        assert float(jnp.abs(g - w).max() / jnp.abs(w).max()) < 2e-6, name


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
