"""Checkpointing tests: strategy semantics (reference test_checkpoint_strategies.py),
Orbax save/load round-trip, and the topology-change warmstart equivalence oracle
(the reference's strongest correctness test, test_fsdp2_warmstart_pp_tp.py:48-60)."""

import json
from pathlib import Path

import numpy as np
import pytest

from modalities_tpu.checkpointing.checkpoint_saving import CheckpointSaving
from modalities_tpu.checkpointing.checkpoint_saving_strategies import (
    SaveEveryKStepsCheckpointingStrategy,
    SaveKMostRecentCheckpointsStrategy,
)
from modalities_tpu.checkpointing.orbax.orbax_checkpoint_loading import OrbaxCheckpointLoading
from modalities_tpu.checkpointing.orbax.orbax_checkpoint_saving import (
    OrbaxCheckpointSaving,
    checkpoint_folder_path,
)
from modalities_tpu.running_env.device_mesh import get_device_mesh
from modalities_tpu.training.training_progress import TrainingProgress
from modalities_tpu.utils.number_conversion import NumberConversion
from tests.models.test_gpt2_model import tiny_gpt2
from tests.training.test_train_step import _batch, _builder


def _progress(steps, tokens=None):
    return TrainingProgress(
        num_seen_steps_current_run=steps,
        num_seen_tokens_current_run=tokens if tokens is not None else steps * 100,
        num_target_steps=100,
        num_target_tokens=10000,
    )


def test_k_most_recent_strategy_ring():
    s = SaveKMostRecentCheckpointsStrategy(k=2)
    i1 = s.get_checkpoint_instruction(_progress(1))
    i2 = s.get_checkpoint_instruction(_progress(2))
    i3 = s.get_checkpoint_instruction(_progress(3))
    assert i1.savable and not i1.checkpoints_to_delete
    assert i2.savable and not i2.checkpoints_to_delete
    assert i3.savable and [p.num_seen_steps_total for p in i3.checkpoints_to_delete] == [1]


def test_k_most_recent_strategy_keep_all_and_none():
    keep_all = SaveKMostRecentCheckpointsStrategy(k=-1)
    for i in range(5):
        inst = keep_all.get_checkpoint_instruction(_progress(i))
        assert inst.savable and not inst.checkpoints_to_delete
    keep_none = SaveKMostRecentCheckpointsStrategy(k=0)
    assert not keep_none.get_checkpoint_instruction(_progress(1)).savable


def test_every_k_steps_strategy():
    s = SaveEveryKStepsCheckpointingStrategy(k=3)
    assert not s.get_checkpoint_instruction(_progress(2)).savable
    assert s.get_checkpoint_instruction(_progress(3)).savable
    assert s.get_checkpoint_instruction(_progress(6)).savable


def test_folder_name_roundtrips_through_number_conversion(tmp_path):
    p = checkpoint_folder_path(tmp_path, "exp42", _progress(64, 524288))
    assert NumberConversion.get_num_seen_steps_from_checkpoint_path(p) == 64
    assert NumberConversion.get_global_num_seen_tokens_from_checkpoint_path(p) == 524288
    assert NumberConversion.get_global_num_target_tokens_from_checkpoint_path(p) == 10000


def test_orbax_save_load_roundtrip_and_info_file(tmp_path):
    mesh = get_device_mesh(device_type="cpu", data_parallel_shard_degree=8, world_size=8)
    model = tiny_gpt2("pytorch_flash")
    fns = _builder(model, mesh).build(seed=0)
    rng = np.random.default_rng(0)
    batch = fns.put_batch(_batch(rng, 1, 8, 16))
    state = fns.app_state_handle.state
    for _ in range(3):
        state, _ = fns.train_step(state, batch)
    fns.app_state_handle.state = state

    saving = CheckpointSaving(
        SaveKMostRecentCheckpointsStrategy(k=1),
        OrbaxCheckpointSaving(tmp_path, experiment_id="e2e"),
    )
    saving.save_checkpoint(_progress(3), fns.app_state_handle)

    info = json.loads((tmp_path / "last_checkpoint_info.json").read_text())
    folder = Path(info["checkpoint_folder_path"])
    assert folder.exists()

    # fresh build, load, states match
    fns2 = _builder(model, mesh).build(seed=123)  # different seed -> different init
    loaded = OrbaxCheckpointLoading().load_app_state(fns2.app_state_handle, folder)
    assert int(loaded.step) == 3
    import jax

    for a, b in zip(jax.tree.leaves(state.params), jax.tree.leaves(loaded.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_ring_deletion_on_disk(tmp_path):
    mesh = get_device_mesh(device_type="cpu", data_parallel_shard_degree=8, world_size=8)
    model = tiny_gpt2("pytorch_flash")
    fns = _builder(model, mesh).build(seed=0)
    saving = CheckpointSaving(
        SaveKMostRecentCheckpointsStrategy(k=2),
        OrbaxCheckpointSaving(tmp_path, experiment_id="ring"),
    )
    for step in (1, 2, 3):
        saving.save_checkpoint(_progress(step), fns.app_state_handle)
    folders = sorted(p.name for p in tmp_path.iterdir() if p.is_dir())
    assert len(folders) == 2
    assert all("seen_steps_1-" not in f for f in folders)


def test_double_load_guard(tmp_path):
    mesh = get_device_mesh(device_type="cpu", data_parallel_shard_degree=8, world_size=8)
    model = tiny_gpt2("pytorch_flash")
    fns = _builder(model, mesh).build(seed=0)
    saving = CheckpointSaving(
        SaveKMostRecentCheckpointsStrategy(k=1), OrbaxCheckpointSaving(tmp_path, "dbl")
    )
    saving.save_checkpoint(_progress(1), fns.app_state_handle)
    folder = checkpoint_folder_path(tmp_path, "dbl", _progress(1))
    loader = OrbaxCheckpointLoading()
    loader.load_app_state(fns.app_state_handle, folder)
    with pytest.raises(RuntimeError, match="already loaded"):
        loader.load_app_state(fns.app_state_handle, folder)


def test_warmstart_topology_change_equivalence(tmp_path):
    """Train 6 steps on dp4 x tp2; resume from step 3's checkpoint on dp8; the last
    3 losses must match the uninterrupted run (reference warmstart oracle)."""
    model = tiny_gpt2("pytorch_flash")
    mesh_a = get_device_mesh(
        device_type="cpu", data_parallel_shard_degree=4, tensor_parallel_degree=2, world_size=8
    )
    mesh_b = get_device_mesh(device_type="cpu", data_parallel_shard_degree=8, world_size=8)
    rng = np.random.default_rng(7)
    batches = [_batch(rng, 1, 8, 16) for _ in range(6)]

    # run A: 6 uninterrupted steps on mesh_a, checkpoint at step 3
    fns_a = _builder(model, mesh_a, clip=1.0).build(seed=0)
    state = fns_a.app_state_handle.state
    losses_a = []
    saving = CheckpointSaving(
        SaveKMostRecentCheckpointsStrategy(k=-1), OrbaxCheckpointSaving(tmp_path, "wsrt")
    )
    for i, raw in enumerate(batches):
        state, metrics = fns_a.train_step(state, fns_a.put_batch(raw))
        losses_a.append(float(metrics["loss"]))
        fns_a.app_state_handle.state = state
        if i == 2:
            saving.save_checkpoint(_progress(3), fns_a.app_state_handle)

    # run B: fresh build on mesh_b, restore step-3 checkpoint, replay last 3 batches
    fns_b = _builder(model, mesh_b, clip=1.0).build(seed=99)
    folder = checkpoint_folder_path(tmp_path, "wsrt", _progress(3))
    OrbaxCheckpointLoading().load_app_state(fns_b.app_state_handle, folder)
    state_b = fns_b.app_state_handle.state
    assert int(state_b.step) == 3
    losses_b = []
    for raw in batches[3:]:
        state_b, metrics = fns_b.train_step(state_b, fns_b.put_batch(raw))
        losses_b.append(float(metrics["loss"]))

    np.testing.assert_allclose(losses_a[3:], losses_b, rtol=2e-4, atol=2e-4)


def test_async_save_defers_resume_pointer_until_commit(tmp_path):
    """ADVICE r1: with use_async=True the resume pointer must only ever reference a
    COMMITTED checkpoint — it is written at the next save (which waits for the
    previous commit) or at wait_until_finished, never right after save() returns."""
    mesh = get_device_mesh(device_type="cpu", data_parallel_shard_degree=8, world_size=8)
    model = tiny_gpt2("pytorch_flash")
    fns = _builder(model, mesh).build(seed=0)
    execution = OrbaxCheckpointSaving(tmp_path, experiment_id="async", use_async=True)
    saving = CheckpointSaving(SaveKMostRecentCheckpointsStrategy(k=2), execution)

    saving.save_checkpoint(_progress(1), fns.app_state_handle)
    # pointer for save 1 is pending, not yet on disk
    assert not (tmp_path / "last_checkpoint_info.json").exists()
    assert execution._pending_info_folder is not None

    saving.save_checkpoint(_progress(2), fns.app_state_handle)
    # save 2 waited for save 1's commit -> save 1's pointer flushed
    info = json.loads((tmp_path / "last_checkpoint_info.json").read_text())
    assert "seen_steps_1-" in info["checkpoint_folder_path"]
    assert Path(info["checkpoint_folder_path"]).exists()

    saving.wait_until_finished()
    info = json.loads((tmp_path / "last_checkpoint_info.json").read_text())
    assert "seen_steps_2-" in info["checkpoint_folder_path"]
    assert Path(info["checkpoint_folder_path"]).exists()


def test_restore_preserves_optimizer_moments_bitwise(tmp_path):
    """Loss-curve continuity can hide small optimizer-state drift; pin the sharper
    contract directly: every adam moment leaf (mu/nu), the step counter, and the
    params restore BITWISE (reference's DCP tests compare state_dicts leaf-wise)."""
    import jax

    mesh = get_device_mesh(device_type="cpu", data_parallel_shard_degree=8, world_size=8)
    model = tiny_gpt2("pytorch_flash")
    fns = _builder(model, mesh).build(seed=0)
    rng = np.random.default_rng(1)
    batch = fns.put_batch(_batch(rng, 1, 8, 16))
    state = fns.app_state_handle.state
    for _ in range(4):
        state, _ = fns.train_step(state, batch)
    fns.app_state_handle.state = state

    saving = CheckpointSaving(
        SaveKMostRecentCheckpointsStrategy(k=1), OrbaxCheckpointSaving(tmp_path, "moments")
    )
    saving.save_checkpoint(_progress(4), fns.app_state_handle)
    folder = checkpoint_folder_path(tmp_path, "moments", _progress(4))

    fns2 = _builder(model, mesh).build(seed=999)
    loaded = OrbaxCheckpointLoading().load_app_state(fns2.app_state_handle, folder)

    src_leaves = jax.tree_util.tree_flatten_with_path(state.opt_state)[0]
    dst_leaves = jax.tree_util.tree_flatten_with_path(loaded.opt_state)[0]
    assert len(src_leaves) == len(dst_leaves) and len(src_leaves) > 0
    for (path_a, a), (path_b, b) in zip(src_leaves, dst_leaves):
        assert path_a == path_b
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b), err_msg=str(path_a))
    assert int(loaded.step) == int(state.step) == 4


def test_restore_reshards_leaves_bitwise_across_topologies(tmp_path):
    """Sharper than the loss-continuation oracle: save under dp4 x tp2, restore into
    dp8 abstract shardings, and compare every GLOBAL param + opt leaf bitwise —
    Orbax must re-lay out each shard for the new mesh with no value change."""
    import jax

    model = tiny_gpt2("pytorch_flash")
    mesh_a = get_device_mesh(
        device_type="cpu", data_parallel_shard_degree=4, tensor_parallel_degree=2, world_size=8
    )
    fns_a = _builder(model, mesh_a).build(seed=0)
    rng = np.random.default_rng(2)
    batch = fns_a.put_batch(_batch(rng, 1, 8, 16))
    state = fns_a.app_state_handle.state
    for _ in range(2):
        state, _ = fns_a.train_step(state, batch)
    fns_a.app_state_handle.state = state
    saving = CheckpointSaving(
        SaveKMostRecentCheckpointsStrategy(k=1), OrbaxCheckpointSaving(tmp_path, "reshard")
    )
    saving.save_checkpoint(_progress(2), fns_a.app_state_handle)
    folder = checkpoint_folder_path(tmp_path, "reshard", _progress(2))

    mesh_b = get_device_mesh(device_type="cpu", data_parallel_shard_degree=8, world_size=8)
    fns_b = _builder(model, mesh_b).build(seed=7)
    loaded = OrbaxCheckpointLoading().load_app_state(fns_b.app_state_handle, folder)

    for tree_a, tree_b, tag in (
        (state.params, loaded.params, "params"),
        (state.opt_state, loaded.opt_state, "opt_state"),
    ):
        la = jax.tree.leaves(tree_a)
        lb = jax.tree.leaves(tree_b)
        assert len(la) == len(lb) and la, tag
        for a, b in zip(la, lb):
            assert a.shape == b.shape
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b), err_msg=tag)
        # and the restore honored the NEW mesh's shardings, not the saved ones
    for leaf, sh in zip(
        jax.tree.leaves(loaded.params), jax.tree.leaves(fns_b.app_state_handle.state_shardings.params)
    ):
        assert leaf.sharding.is_equivalent_to(sh, leaf.ndim), (leaf.sharding, sh)
