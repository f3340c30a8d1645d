"""Worker for the 2-process distributed CPU test (run via subprocess, not pytest).

Each process owns 4 virtual CPU devices of a global 8-device dp mesh, feeds ONLY its
own rows of the global batch through put_batch's make_array_from_process_local_data
branch, and runs one real train step. Prints `LOSS <value>` — the parent asserts both
processes agree with the single-process oracle. (Reference: multi-rank test tier,
tests/run_distributed_tests.sh:36-50.)

Usage: multiprocess_worker.py <coordinator_port> <process_id> <num_processes>
       multiprocess_worker.py single            # single-process oracle
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
# devices per process: 4 by default; the single-process oracle must recreate the
# GLOBAL mesh (same shape -> bit-comparable reductions), so the test passes 8
_n_dev = os.environ.get("MP_WORKER_DEVICES", "4")
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + f" --xla_force_host_platform_device_count={_n_dev}"
).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import numpy as np  # noqa: E402


def build_and_step(local_rows_slice, mode="dp"):
    from modalities_tpu.loss_functions import CLMCrossEntropyLoss
    from modalities_tpu.optimizers.optimizer_factory import OptimizerFactory
    from modalities_tpu.running_env.device_mesh import get_data_loading_info, get_device_mesh
    from modalities_tpu.training.train_step import TrainStepBuilder
    from tests.models.test_gpt2_model import tiny_gpt2

    world = len(jax.devices())
    if mode == "pp":
        # pp2 x dp(world/2): the pp axis is outermost, so with 2 processes the
        # scheduled executor's ppermute/psum hops CROSS the process boundary (the
        # DCN-shaped tier); every process owns ALL dp coordinates, so the per-host
        # loader must report ONE loading rank and each process feeds the full batch
        mesh = get_device_mesh(
            device_type="cpu",
            pipeline_parallel_degree=2,
            data_parallel_shard_degree=world // 2,
            world_size=world,
        )
    elif mode == "cp":
        # cp spanning the WHOLE world: with 2 processes the ring attention k/v
        # rotation (lax.ppermute over cp) crosses the process boundary — the DCN
        # tier of SURVEY §5.7 context parallelism, which no single-process test
        # can exercise
        mesh = get_device_mesh(
            device_type="cpu",
            data_parallel_shard_degree=1,
            context_parallel_degree=world,
            world_size=world,
        )
    elif mode == "hsdp":
        # HSDP with the replicate axis OUTERMOST: with 2 processes each process is
        # one replica group (the reference's HYBRID_SHARD multi-node story —
        # param all-reduce over dp_replicate rides the DCN tier), and the batch
        # still shards over (dp_replicate, dp_shard), so each process loads its
        # replica group's distinct rows
        mesh = get_device_mesh(
            device_type="cpu",
            data_parallel_replicate_degree=2,
            data_parallel_shard_degree=world // 2,
            world_size=world,
        )
    else:
        mesh = get_device_mesh(
            device_type="cpu", data_parallel_shard_degree=world, world_size=world
        )
    num_ranks, rank = get_data_loading_info(mesh)
    if mode == "pp" and jax.process_count() > 1:
        assert (num_ranks, rank) == (1, 0), (num_ranks, rank)

    model = tiny_gpt2("pytorch_flash", n_layer=4)
    if mode == "pp":
        model.with_spec_updates(pp_schedule="1f1b", pp_num_microbatches=2)
    opt = OptimizerFactory.get_adam_w(
        lr=1e-3, betas=(0.9, 0.95), eps=1e-8, weight_decay=0.1,
        weight_decay_groups_excluded=["norm", "embedding"], wrapped_model=model,
    )
    fns = TrainStepBuilder(
        model=model,
        loss_fn=CLMCrossEntropyLoss(target_key="target_ids", prediction_key="logits"),
        optimizer_spec=opt,
        mesh_handle=mesh,
        gradient_acc_steps=1,
        grad_clip_norm=1.0,
    ).build(seed=0)

    # the GLOBAL batch is the same on every process; each feeds only its rows
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, 128, size=(1, 8, 17))
    rows_per_rank = 8 // num_ranks
    lo = rank * rows_per_rank
    local = tokens[:, lo : lo + rows_per_rank] if local_rows_slice else tokens
    batch = fns.put_batch(
        {
            "samples": {"input_ids": local[:, :, :-1].astype(np.int32)},
            "targets": {"target_ids": local[:, :, 1:].astype(np.int32)},
        }
    )
    state, metrics = fns.train_step(fns.app_state_handle.state, batch)
    return float(metrics["loss"])


def feeder_run() -> list[float]:
    """DeviceFeeder equivalence over the cp ring (tentpole guard): 3 train steps on
    a cp-over-the-whole-world mesh, microbatches staged through DeviceFeeder with
    MP_FEEDER_PREFETCH (2 = async background transfers, 0 = sync inline). The
    parent compares a single-process sync oracle against the 2-process async run —
    guarding BOTH the feeder's multi-host enqueue-order contract and put_batch's
    `local_seq_slice` (each process must transfer only its contiguous cp block of
    the sequence, from a background thread)."""
    from modalities_tpu.batch import DatasetBatch
    from modalities_tpu.dataloader.device_feeder import DeviceFeeder
    from modalities_tpu.loss_functions import CLMCrossEntropyLoss
    from modalities_tpu.optimizers.optimizer_factory import OptimizerFactory
    from modalities_tpu.running_env.device_mesh import get_device_mesh
    from modalities_tpu.training.train_step import TrainStepBuilder
    from tests.models.test_gpt2_model import tiny_gpt2

    world = len(jax.devices())
    mesh = get_device_mesh(
        device_type="cpu",
        data_parallel_shard_degree=1,
        context_parallel_degree=world,
        world_size=world,
    )
    model = tiny_gpt2("pytorch_flash", n_layer=2)
    opt = OptimizerFactory.get_adam_w(
        lr=1e-3, betas=(0.9, 0.95), eps=1e-8, weight_decay=0.1,
        weight_decay_groups_excluded=["norm", "embedding"], wrapped_model=model,
    )
    fns = TrainStepBuilder(
        model=model,
        loss_fn=CLMCrossEntropyLoss(target_key="target_ids", prediction_key="logits"),
        optimizer_spec=opt,
        mesh_handle=mesh,
        gradient_acc_steps=1,
        grad_clip_norm=1.0,
    ).build(seed=0)

    def microbatches():
        # dp=1: the batch dim is unsharded, so every process loads the SAME full
        # rows; put_batch slices the cp-sharded sequence dim per process itself
        for s in range(3):
            rng = np.random.default_rng(200 + s)
            tokens = rng.integers(0, 128, size=(8, 17))
            yield DatasetBatch(
                samples={"input_ids": tokens[:, :-1].astype(np.int32)},
                targets={"target_ids": tokens[:, 1:].astype(np.int32)},
            )

    prefetch = int(os.environ.get("MP_FEEDER_PREFETCH", "2"))
    feed = DeviceFeeder(prefetch_to_device=prefetch).feed_train(
        microbatches(), fns.put_batch, gradient_acc_steps=1
    )
    losses = []
    state = fns.app_state_handle.state
    try:
        for device_batch in feed:
            state, metrics = fns.train_step(state, device_batch)
            losses.append(float(metrics["loss"]))
    finally:
        feed.close()
    return losses


def ckpt_run(phase: str) -> list[float]:
    """Multi-process Orbax checkpointing contract (VERDICT r4 #3). Phases over the
    same deterministic 5-step curriculum (per-step seeded batches, dp over ALL
    global devices):
      - oracle: 5 uninterrupted steps (single process, global mesh)
      - save:   steps 0-2, then save through the REAL CheckpointSaving stack
                (strategy + OrbaxCheckpointSaving) — per-process shard writes,
                primary-host resume pointer
      - resume: restore via OrbaxCheckpointLoading into the CURRENT process
                topology (2-process or single-process), run steps 3-4
    The parent asserts resume losses continue the oracle EXACTLY under both
    process counts. Checkpoint dir comes from MP_CKPT_DIR."""
    import json
    from pathlib import Path

    from modalities_tpu.loss_functions import CLMCrossEntropyLoss
    from modalities_tpu.optimizers.optimizer_factory import OptimizerFactory
    from modalities_tpu.running_env.device_mesh import get_data_loading_info, get_device_mesh
    from modalities_tpu.training.train_step import TrainStepBuilder
    from tests.models.test_gpt2_model import tiny_gpt2

    ckpt_dir = Path(os.environ["MP_CKPT_DIR"])
    world = len(jax.devices())
    mesh = get_device_mesh(device_type="cpu", data_parallel_shard_degree=world, world_size=world)
    num_ranks, rank = get_data_loading_info(mesh)

    model = tiny_gpt2("pytorch_flash", n_layer=4)
    opt = OptimizerFactory.get_adam_w(
        lr=1e-3, betas=(0.9, 0.95), eps=1e-8, weight_decay=0.1,
        weight_decay_groups_excluded=["norm", "embedding"], wrapped_model=model,
    )
    fns = TrainStepBuilder(
        model=model,
        loss_fn=CLMCrossEntropyLoss(target_key="target_ids", prediction_key="logits"),
        optimizer_spec=opt,
        mesh_handle=mesh,
        gradient_acc_steps=1,
        grad_clip_norm=1.0,
    ).build(seed=0)
    handle = fns.app_state_handle

    def batch_for(step: int):
        rng = np.random.default_rng(100 + step)
        tokens = rng.integers(0, 128, size=(1, 8, 17))
        rows = 8 // num_ranks
        local = tokens[:, rank * rows : (rank + 1) * rows]
        return fns.put_batch(
            {
                "samples": {"input_ids": local[:, :, :-1].astype(np.int32)},
                "targets": {"target_ids": local[:, :, 1:].astype(np.int32)},
            }
        )

    tokens_per_step = 8 * 16
    if phase == "ckpt_resume":
        from modalities_tpu.checkpointing.orbax.orbax_checkpoint_loading import (
            OrbaxCheckpointLoading,
        )

        info = json.loads((ckpt_dir / "last_checkpoint_info.json").read_text())
        assert "seen_steps_3-" in info["checkpoint_folder_path"]
        OrbaxCheckpointLoading().load_app_state(handle, Path(info["checkpoint_folder_path"]))
        steps = range(3, 5)
    else:
        steps = range(5) if phase == "ckpt_oracle" else range(3)

    losses = []
    for s in steps:
        handle.state, metrics = fns.train_step(handle.state, batch_for(s))
        losses.append(float(metrics["loss"]))

    if phase == "ckpt_save":
        from modalities_tpu.checkpointing.checkpoint_saving import CheckpointSaving
        from modalities_tpu.checkpointing.checkpoint_saving_strategies import (
            SaveKMostRecentCheckpointsStrategy,
        )
        from modalities_tpu.checkpointing.orbax.orbax_checkpoint_saving import (
            OrbaxCheckpointSaving,
        )
        from modalities_tpu.training.training_progress import TrainingProgress

        saving = CheckpointSaving(
            SaveKMostRecentCheckpointsStrategy(k=2),
            OrbaxCheckpointSaving(ckpt_dir, experiment_id="mp_ckpt"),
        )
        saving.save_checkpoint(
            TrainingProgress(
                num_seen_steps_current_run=3,
                num_seen_tokens_current_run=3 * tokens_per_step,
                num_target_steps=5,
                num_target_tokens=5 * tokens_per_step,
            ),
            handle,
        )
        saving.wait_until_finished()
    return losses


def main() -> None:
    if sys.argv[1] == "single":
        mode = sys.argv[2] if len(sys.argv) > 2 else "dp"
        if mode.startswith("ckpt"):
            for loss in ckpt_run(mode):
                print(f"LOSS {loss:.6f}", flush=True)
            return
        if mode == "feeder_cp":
            for loss in feeder_run():
                print(f"LOSS {loss:.6f}", flush=True)
            return
        print(f"LOSS {build_and_step(local_rows_slice=False, mode=mode):.6f}", flush=True)
        return
    port, pid, nprocs = int(sys.argv[1]), int(sys.argv[2]), int(sys.argv[3])
    mode = sys.argv[4] if len(sys.argv) > 4 else "dp"
    jax.distributed.initialize(
        coordinator_address=f"127.0.0.1:{port}", num_processes=nprocs, process_id=pid
    )
    assert jax.process_count() == nprocs, jax.process_count()

    # the --test_comm pre-flight: rank-stamped all_gather across BOTH processes'
    # devices (the multi-host tier of utils/communication_test.py, SURVEY §5.8)
    from modalities_tpu.utils.communication_test import run_communication_test

    run_communication_test()
    print("COMM OK", flush=True)

    # experiment-id sync contract (reference tests/utils/test_experiment_id_generation.py):
    # process 0 generates, every process adopts — the parent asserts both EID lines
    # match even though each process' own clock/hash input could differ
    from modalities_tpu.util import get_synced_experiment_id_of_run

    print(f"EID {get_synced_experiment_id_of_run('configs/config_lorem_ipsum_tpu.yaml')}", flush=True)

    if mode.startswith("ckpt"):
        for loss in ckpt_run(mode):
            print(f"LOSS {loss:.6f}", flush=True)
        return
    if mode == "feeder_cp":
        for loss in feeder_run():
            print(f"LOSS {loss:.6f}", flush=True)
        return
    loss = build_and_step(local_rows_slice=True, mode=mode)
    print(f"LOSS {loss:.6f}", flush=True)


if __name__ == "__main__":
    main()
