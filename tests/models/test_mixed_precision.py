"""Mixed-precision policy wiring (reference: model_factory.py:201 MixedPrecisionPolicy).

The MixedPrecisionSpec recorded by the fsdp2_wrapped variant must have an observable
effect: param_dtype governs the storage dtype of dense kernels/embeddings, compute
stays in compute_dtype, and reduce_dtype governs gradient accumulation."""

import jax
import numpy as np
import pytest

from modalities_tpu.models.model import MixedPrecisionSpec
from modalities_tpu.models.model_factory import ModelFactory
from modalities_tpu.running_env.device_mesh import get_device_mesh
from tests.models.test_gpt2_model import tiny_gpt2
from tests.training.test_train_step import _batch, _builder


def _kernel_dtypes(params):
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        name = "/".join(str(getattr(p, "key", p)) for p in path)
        out[name] = leaf.dtype
    return out


def test_param_dtype_default_is_float32():
    mesh = get_device_mesh(device_type="cpu", data_parallel_shard_degree=8, world_size=8)
    model = tiny_gpt2("pytorch_flash")
    fns = _builder(model, mesh).build(seed=0)
    dtypes = _kernel_dtypes(fns.app_state_handle.state.params)
    assert all(dt == np.float32 for dt in dtypes.values()), dtypes


def test_bf16_param_dtype_is_honored_and_trains():
    mesh = get_device_mesh(device_type="cpu", data_parallel_shard_degree=8, world_size=8)
    model = tiny_gpt2("pytorch_flash")
    # the registry path: fsdp2_wrapped records the policy; the train step applies it
    ModelFactory.get_fsdp2_wrapped_model(
        model,
        device_mesh=mesh,
        mixed_precision_settings={"param_dtype": "bfloat16", "reduce_dtype": "float32"},
    )
    fns = _builder(model, mesh, acc=2).build(seed=0)
    state = fns.app_state_handle.state
    dtypes = _kernel_dtypes(state.params)
    assert any(dt == jax.numpy.bfloat16 for dt in dtypes.values()), dtypes
    # dense kernels and embeddings are bf16; norm scales stay f32
    for name, dt in dtypes.items():
        if "kernel" in name or "wte" in name or "wpe" in name:
            assert dt == jax.numpy.bfloat16, (name, dt)
        if "norm" in name:
            assert dt == np.float32, (name, dt)

    rng = np.random.default_rng(0)
    batch = fns.put_batch(_batch(rng, 2, 8, 16))
    losses = []
    for _ in range(10):
        state, metrics = fns.train_step(state, batch)
        losses.append(float(metrics["loss"]))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0] - 0.5, f"bf16 params did not train: {losses[0]} -> {losses[-1]}"
    # params stay bf16 across steps (no silent upcast through the optimizer)
    dtypes_after = _kernel_dtypes(state.params)
    assert dtypes_after == dtypes


@pytest.mark.slow  # ~27 s; dropout determinism also pinned by the pp dropout tests in
# test_train_step.py and test_manual_and_sdpa_tiers_share_attn_dropout_path
def test_dropout_rng_seeded_and_per_microbatch():
    """ADVICE r1: dropout masks must derive from the build seed (different seeds =>
    different training) and be deterministic for the same seed."""
    mesh = get_device_mesh(device_type="cpu", data_parallel_shard_degree=8, world_size=8)

    def run(seed):
        model = tiny_gpt2("pytorch_flash", dropout=0.5)
        fns = _builder(model, mesh, acc=2).build(seed=seed)
        state = fns.app_state_handle.state
        rng = np.random.default_rng(0)
        batch = fns.put_batch(_batch(rng, 2, 8, 16))
        state, metrics = fns.train_step(state, batch)
        state, metrics = fns.train_step(state, batch)
        return float(metrics["loss"])

    l0a, l0b, l1 = run(0), run(0), run(1)
    assert l0a == l0b, "same seed must reproduce identical dropout"
    assert l0a != l1, "dropout must depend on the configured seed"
