"""p1/p2/inf gradient-norm clipping + error_if_nonfinite
(reference: fsdp_gradient_clipper.py:118,161-170)."""

import jax.numpy as jnp
import numpy as np
import pytest

from modalities_tpu.running_env.device_mesh import get_device_mesh
from modalities_tpu.trainer import Trainer
from modalities_tpu.training.gradient_clipping import (
    GradientClipper,
    GradientClippingMode,
    clip_by_norm_mode,
    global_norm_by_mode,
)
from tests.models.test_gpt2_model import tiny_gpt2
from tests.training.test_train_step import _batch, _builder


def test_global_norm_modes():
    tree = {"a": jnp.asarray([3.0, -4.0]), "b": jnp.asarray([[0.0, 12.0]])}
    assert float(global_norm_by_mode(tree, GradientClippingMode.P2_NORM)) == pytest.approx(13.0)
    assert float(global_norm_by_mode(tree, GradientClippingMode.P1_NORM)) == pytest.approx(19.0)
    assert float(global_norm_by_mode(tree, GradientClippingMode.MAX_NORM)) == pytest.approx(12.0)


@pytest.mark.parametrize("mode", [GradientClippingMode.P1_NORM, GradientClippingMode.MAX_NORM])
def test_clip_by_norm_mode_scales_to_max_norm(mode):
    tree = {"a": jnp.asarray([3.0, -4.0]), "b": jnp.asarray([[0.0, 12.0]])}
    tx = clip_by_norm_mode(max_norm=1.0, mode=mode)
    clipped, _ = tx.update(tree, tx.init(tree))
    assert float(global_norm_by_mode(clipped, mode)) == pytest.approx(1.0, rel=1e-5)
    # direction preserved
    ratio = float(clipped["a"][0] / clipped["a"][1])
    assert ratio == pytest.approx(3.0 / -4.0, rel=1e-5)


def test_clip_by_norm_mode_no_op_below_max_norm():
    tree = {"a": jnp.asarray([0.1, -0.2])}
    tx = clip_by_norm_mode(max_norm=10.0, mode=GradientClippingMode.P1_NORM)
    clipped, _ = tx.update(tree, tx.init(tree))
    np.testing.assert_allclose(clipped["a"], tree["a"])


@pytest.mark.parametrize(
    "norm_type",
    [
        "p1_norm",
        "max_norm",
    ],
)
def test_train_step_with_non_p2_clipper(norm_type):
    mesh = get_device_mesh(device_type="cpu", data_parallel_shard_degree=8, world_size=8)
    model = tiny_gpt2("pytorch_flash")
    builder = _builder(model, mesh)
    builder.grad_clipper = GradientClipper(max_norm=0.5, norm_type=norm_type)
    fns = builder.build(seed=0)
    rng = np.random.default_rng(0)
    batch = fns.put_batch(_batch(rng, 1, 8, 16))
    state = fns.app_state_handle.state
    losses = []
    for _ in range(10):
        state, metrics = fns.train_step(state, batch)
        losses.append(float(metrics["loss"]))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0]
    # the reported norm is the clipping-mode norm of the unclipped grads
    assert float(metrics["grad_norm"]) > 0


def test_error_if_nonfinite_flag_in_metrics():
    mesh = get_device_mesh(device_type="cpu", data_parallel_shard_degree=8, world_size=8)
    model = tiny_gpt2("pytorch_flash")
    builder = _builder(model, mesh)
    builder.grad_clipper = GradientClipper(max_norm=1.0, norm_type="p2_norm", error_if_nonfinite=True)
    fns = builder.build(seed=0)
    rng = np.random.default_rng(0)
    batch = fns.put_batch(_batch(rng, 1, 8, 16))
    state, metrics = fns.train_step(fns.app_state_handle.state, batch)
    assert int(metrics["nonfinite_grads"]) == 0


def test_trainer_raises_on_nonfinite_grads():
    trainer = Trainer(progress_publisher=None, evaluation_result_publisher=None)
    metrics = [
        {"loss": 1.0, "grad_norm": 1.0, "lr": 1e-3, "nonfinite_grads": 0},
        {"loss": float("nan"), "grad_norm": float("nan"), "lr": 1e-3, "nonfinite_grads": 1},
    ]
    with pytest.raises(RuntimeError, match="non-finite gradient norm at train step 8"):
        trainer._publish_interval(metrics, 8, "train", 0.0, None)
