"""CP at depth (VERDICT r1 #9): a long-context training step where the ring hop's
local attention takes the fused k-blocked path (sequence long enough that
S_local > 2*BLOCK_K), composed with full remat — the memory profile the 32k
acceptance config (configs/config_long_context_32k.yaml) relies on. The 32k/cp>1
full-size run needs real chips; this exercises the identical code path at CPU scale."""

import numpy as np
import pytest

from modalities_tpu.parallel import ring_attention as ra
from modalities_tpu.running_env.device_mesh import get_device_mesh
from tests.models.test_gpt2_model import tiny_gpt2
from tests.training.test_train_step import _batch, _builder


def test_long_context_cp_step_uses_blocked_path(monkeypatch):
    # shrink the block threshold so the CP chunk attention takes the fused path at
    # test scale; the blocked-vs-dense unit tests pin its numerics at any block size
    monkeypatch.setattr(ra, "BLOCK_K", 64)
    seen = {"blocked": False}
    orig = ra._chunk_attention_stats

    def spy(q, k, v, q_offset, k_offset, causal, sm_scale, block_k=None):
        block_k = ra.BLOCK_K if block_k is None else block_k
        if k.shape[1] > 2 * block_k and k.shape[1] % block_k == 0:
            seen["blocked"] = True
        return orig(q, k, v, q_offset, k_offset, causal, sm_scale, block_k=block_k)

    monkeypatch.setattr(ra, "_chunk_attention_stats", spy)

    mesh = get_device_mesh(
        device_type="cpu", data_parallel_shard_degree=2, context_parallel_degree=4, world_size=8
    )
    model = tiny_gpt2("pytorch_flash", sequence_length=1024)
    model.with_spec_updates(remat_variant="full")
    fns = _builder(model, mesh, clip=1.0).build(seed=0)
    rng = np.random.default_rng(0)
    batch = fns.put_batch(_batch(rng, 1, 2, 1024))
    state = fns.app_state_handle.state
    losses = []
    for _ in range(2):
        state, metrics = fns.train_step(state, batch)
        losses.append(float(metrics["loss"]))
    assert np.isfinite(losses).all()
    assert seen["blocked"], "local ring attention never took the fused k-blocked path"
    assert losses[1] < losses[0]
