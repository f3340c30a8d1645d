"""The state-space mixer's leaves under a mesh: their logical axes (`d_inner` as the MLP's
hidden axis is, `embed` as elsewhere) let the train step of a two-kind stack compile and
run under dp_shard 2 and under tp 2 on CPU devices, with one key/value head (which tp 2
cannot split, and `fit_spec_to_shape` replicates); and the selective scan's kernels, which
a TPU runs per shard of that mesh, in interpret mode against the plain form on one device.
No cell measures this yet."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from modalities_tpu.models.gpt2.gpt2_model import GPT2LLM
from modalities_tpu.ops import selective_scan as scan_ops
from modalities_tpu.parallel.sharding import activation_rules, default_logical_axis_rules, fit_spec_to_shape
from modalities_tpu.running_env.device_mesh import get_device_mesh
from tests.models.test_hybrid_ssm import HYBRID
from tests.training.test_train_step import _batch, _builder

TWO_LAYERS = {**HYBRID, "sequence_length": 16, "n_layer": 2, "attn_layer_period": 2, "attn_layer_offset": 1}


def test_an_axis_that_does_not_divide_a_dim_is_left_out():
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("dp_shard", "tp"))
    assert fit_spec_to_shape(P(None, "dp_shard", "tp", None), (1, 128, 1, 32), mesh) == P(None, "dp_shard", None, None)
    assert fit_spec_to_shape(P(("dp_shard", "tp"), None), (6, 3), mesh) == P(None, None)
    assert fit_spec_to_shape(P("tp"), (8, 3), mesh) == P("tp", None)


def test_step_compiles_and_agrees_under_dp_shard_2_and_tp_2():
    raw = _batch(np.random.default_rng(3), 1, 2, 16, vocab=512)
    losses, sharded_over = {}, {}
    for name, layout in (("dp_shard_2", {"data_parallel_shard_degree": 2}), ("tp_2", {"data_parallel_shard_degree": 1, "tensor_parallel_degree": 2})):
        fns = _builder(GPT2LLM(**TWO_LAYERS), get_device_mesh(device_type="cpu", world_size=2, **layout), clip=1.0).build(seed=0)
        state = fns.app_state_handle.state
        ssm = state.params["params"]["run_0"]["blocks"]["block"]["ssm"]
        sharded_over[name] = {leaf: ssm[leaf]["kernel"].sharding.spec for leaf in ("in_proj", "out_proj")}
        sharded_over[name]["k_attn"] = state.params["params"]["run_1"]["blocks"]["block"]["attn"]["k_attn"]["kernel"].sharding.spec
        _, metrics = fns.train_step(state, fns.put_batch(raw))
        losses[name] = float(metrics["loss"])
    # [layers, d, 2 d_inner] and [layers, d_inner, d]: `embed` over dp_shard, `d_inner` over tp as the MLP's hidden axis is
    assert sharded_over["dp_shard_2"]["in_proj"][1] == "dp_shard" and sharded_over["tp_2"]["in_proj"][2] == "tp"
    assert sharded_over["tp_2"]["out_proj"][1] == "tp" and sharded_over["dp_shard_2"]["out_proj"][2] == "dp_shard"
    assert "tp" not in tuple(sharded_over["tp_2"]["k_attn"]), "one key/value head is replicated over tp"
    assert losses["dp_shard_2"] == pytest.approx(losses["tp_2"], rel=5e-3) and np.isfinite(losses["tp_2"])


def test_scan_kernels_per_shard_of_tp_2_agree_with_the_plain_form_on_one_device(monkeypatch):
    """`d_inner` (logical axis `mlp`) split over tp 2: each shard runs the kernels on its half
    of the channels with b, c whole, so dB and dC of the shards are added up over tp (the
    shard_map's transpose does it); dA, dx, ddt, dh0 come back split as their operands are."""
    handle = get_device_mesh(device_type="cpu", world_size=2, data_parallel_shard_degree=1, tensor_parallel_degree=2)
    rng = np.random.default_rng(5)
    f = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)  # noqa: E731
    batch, seq, d_inner, d_state = 2, 24, 256, 8
    x, b, c, h0 = f(batch, seq, d_inner), f(batch, seq, d_state), f(batch, seq, d_state), f(batch, d_inner, d_state)
    dt = jnp.asarray(rng.uniform(0.01, 0.5, size=(batch, seq, d_inner)), jnp.float32)
    a = -jnp.asarray(rng.uniform(0.5, 4, size=(d_inner, d_state)), jnp.float32)
    w_y, w_h = f(batch, seq, d_inner), f(batch, d_inner, d_state)

    def outputs_and_gradients(interpret):
        scan = lambda x, dt, a, b, c, h0: scan_ops.selective_scan(x, dt, a, b, c, chunk=8, h0=h0, interpret=interpret)  # noqa: E731
        loss = lambda *v: (lambda y, h: jnp.sum(y * w_y) + jnp.sum(h * w_h))(*scan(*v))  # noqa: E731
        return jax.jit(lambda *v: (scan(*v), jax.grad(loss, argnums=range(6))(*v)))(x, dt, a, b, c, h0)

    from modalities_tpu.ops.pallas import selective_scan as kernels

    seen, planned = [], kernels.plan_blocks
    monkeypatch.setattr(kernels, "plan_blocks", lambda seq, d_inner, *rest: seen.append(d_inner) or planned(seq, d_inner, *rest))
    with handle.mesh, activation_rules(default_logical_axis_rules(handle), handle.mesh):
        (y, h), got = outputs_and_gradients(interpret=True)
    assert seen and set(seen) == {128}, "each shard planned its kernels for its own half of d_inner"
    (want_y, want_h), want = outputs_and_gradients(interpret=False)
    np.testing.assert_allclose(y, want_y, atol=4e-6)
    np.testing.assert_allclose(h, want_h, atol=2e-6)
    for name, g, w in zip(("x", "dt", "a", "b", "c", "h0"), got, want):
        assert float(jnp.abs(g - w).max() / jnp.abs(w).max()) < 2e-6, name
