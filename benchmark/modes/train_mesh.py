"""Train mode over a mesh of several chips: `benchmark/modes/train.py` with the program sharded as its YAML asks
(`dp_shard x tp`), a reference that follows it on the same chips, and the program's own record of its collectives.

The run is the dense mode's, piece for piece (its module docstring says how the window is counted): the program's
compiled train step with its state, built under the mesh the YAML's `device_mesh` block gives, holding the benchmark's
seeded weights (`benchmark/weights.py`, made in one jitted call under the program's own shardings), ONE `Trainer.train`
call with the `DeviceFeeder` live and a new packed batch every step, the window counted in whole steps from the trainer's
published intervals, `compiles_inside_window` as every mode has it. What differs:

- one process drives every chip of the host, and a step's batch is GLOBAL: `local_train_micro_batch_size` rows for each
  `dp_shard` group, which the loader hands out as one array and `put_batch` splits; `tokens_per_step` counts them all.
- the reference (`benchmark/reference/dense_decoder_f32_mesh.py`: the dense reference's arithmetic, its float32 arrays
  split over the chips) follows the first TWO steps on ALL the rows of each, whichever group got them, after the
  window has closed and the program's state is freed. Two, not three: it keeps no moments on the device, and the second
  step is the one that needs them carried. The five rows compared are the dense cell's.
- the program's telemetry writes to a sink under the run's scratch directory, and the run prints the plans emitted while
  the step was traced and compiled (`collective_plan`: the compiled step's collectives by mesh axis and kind, from the
  trainer's preflight, and the cost model's guess of their seconds beside which a traced run prints the measured share;
  `flash_tile_plan`, `fused_ce_plan`).
- `observed["run"]` holds what ONE chip holds of a step (its query and key/value heads, its rows, its share of the
  sequence where sequence parallelism splits it, its share of the head's rows), so that a shape function reads a chip's work.

A program that cannot build the mesh (fewer devices than `world_size`) fails at once, in `build_components`.
"""

from __future__ import annotations

import json
import time

import yaml

from benchmark.modes.train import (LimitedLoader, StepWatcher, _mean, _median, _Silent, adam_first_moment, build_program, compare,
                                   free, hyperparameters, program_memory, reference_layout)
from benchmark.modes.train_hybrid import sink_events

CHECK_STEPS = 2  # the steps the reference follows
PLANS = ("collective_plan", "flash_tile_plan", "fused_ce_plan")


def layer_delta_norms(shape, params, key):
    """Norm of (the program's parameters - the seeded ones) for every leaf, one norm a layer for the stacked leaves, in
    the reference's names: the seeded weights are made again one layer at a time, so no second copy of the model is held."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference.dense_decoder_f32 import leaf_norms
    from benchmark.weights import layer_weights, outer_weights

    ours = reference_layout(params)
    norm = lambda a, b: jnp.sqrt(jnp.sum((a.astype(jnp.float32) - b.astype(jnp.float32)) ** 2))  # noqa: E731

    def one_layer(args):
        layer, leaves = args
        seeded = layer_weights(shape, key, layer)
        seeded.update(attention_norm=jnp.ones((shape.n_embd,), jnp.float32), ffn_norm=jnp.ones((shape.n_embd,), jnp.float32))
        return {f"layers.{name}": norm(leaf, seeded[name]) for name, leaf in leaves.items()}

    out = jax.lax.map(one_layer, (jnp.arange(shape.n_layer), ours["layers"]))
    outer = {**outer_weights(shape, key), "final_norm": jnp.ones((shape.n_embd,), jnp.float32)}
    out.update({name: norm(ours[name], outer[name]) for name in outer})
    assert set(out) == set(jax.eval_shape(leaf_norms, ours))
    return out


def drive(ctx, components, fns, raw: dict, shape, telemetry) -> dict:
    """One `Trainer.train` call: set-up steps, then the window. Returns what was observed."""
    import jax
    import jax.numpy as jnp

    from modalities_tpu.logging_broker.message_broker import MessageBroker
    from modalities_tpu.logging_broker.messages import MessageTypes
    from modalities_tpu.logging_broker.publisher import MessagePublisher
    from modalities_tpu.trainer import Trainer
    from modalities_tpu.training.training_progress import TrainingProgress

    from benchmark.reference.dense_decoder_f32 import leaf_norms
    from benchmark.weights import seed_key

    cell, settings = ctx.cell, components.settings
    keys = raw["settings"]["referencing_keys"]
    profile = settings.step_profile
    tokens_per_step = (profile.local_train_micro_batch_size * profile.sequence_length
                       * profile.gradient_accumulation_steps * profile.dp_degree)
    warm_steps = int(cell.spec["warm_steps"])
    loader = LimitedLoader(components.train_dataloader, CHECK_STEPS, keys["sample_key"], keys["target_key"])
    watcher = StepWatcher(loader, warm_steps, ctx.seconds, ctx.trace_dir,
                          int(cell.spec["trace_after_steps"]), int(cell.spec["trace_steps"]))
    broker = MessageBroker()
    broker.add_subscriber(MessageTypes.EVALUATION_RESULT, watcher)
    broker.add_subscriber(MessageTypes.BATCH_PROGRESS_UPDATE, _Silent())
    trainer = Trainer(
        progress_publisher=MessagePublisher(broker), evaluation_result_publisher=MessagePublisher(broker),
        gradient_acc_steps=profile.gradient_accumulation_steps, global_num_tokens_per_train_step=tokens_per_step,
        training_log_interval_in_steps=settings.intervals.training_log_interval_in_steps,
        mfu_calculator=components.mfu_calculator, device_feeder=components.device_feeder, telemetry=telemetry,
    )
    if settings.intervals.training_log_interval_in_steps != 1:
        raise SystemExit("benchmark: the cell's YAML must log every step (training_log_interval_in_steps: 1)")

    b1 = float(raw["optimizer"]["config"]["betas"][0])
    grad_norms = jax.jit(lambda opt: leaf_norms(jax.tree.map(
        lambda m: m.astype(jnp.float32) / (1 - b1), reference_layout(adam_first_moment(opt)))))
    delta_norms = jax.jit(lambda params, key: layer_delta_norms(shape, params, key))
    key = seed_key(ctx.seed)
    snapshots: dict[str, dict] = {}

    def at_step_boundary(progress, force: bool = False) -> None:
        step = progress.num_seen_steps_current_run
        if step == 1:
            opt_state = fns.app_state_handle.state.opt_state
            snapshots["first_grad_norms"] = grad_norms(opt_state)
            # the gradient itself, to the host (bfloat16 as the optimizer keeps it, gathered from the chips that hold its
            # parts): the reference measures its distance from its own once the devices are free
            snapshots["first_moment"] = jax.device_get(reference_layout(adam_first_moment(opt_state)))
        if step == CHECK_STEPS:
            snapshots["delta_norms"] = delta_norms(fns.app_state_handle.state.params, key)

    progress = TrainingProgress(
        num_seen_steps_current_run=0, num_seen_tokens_current_run=0,
        num_target_steps=settings.training_target.num_target_steps,
        num_target_tokens=settings.training_target.num_target_tokens,
        num_seen_steps_previous_run=0, num_seen_tokens_previous_run=0,
    )
    try:
        trainer.train(step_functions=fns, train_loader=loader, training_progress=progress,
                      evaluation_callback=lambda step: None, checkpointing_callback=at_step_boundary)
    finally:
        watcher.close()

    steps_done = max(watcher.done_at) if watcher.done_at else 0
    if watcher.target_steps is None or steps_done <= warm_steps:
        raise SystemExit(f"benchmark: training ended after {steps_done} steps, before the window opened")
    window = (watcher.done_at[warm_steps], watcher.done_at[steps_done])
    in_window = steps_done - warm_steps
    gaps = [watcher.done_at[k] - watcher.done_at[k - 1] for k in range(warm_steps + 1, steps_done + 1)]
    window_losses = [watcher.loss[k] for k in range(warm_steps + 1, steps_done + 1)]
    return {
        "window": window, "attempted": watcher.target_steps, "failed": watcher.target_steps - in_window,
        "tokens_per_step": tokens_per_step, "steps_in_window": in_window, "step_seconds": gaps,
        "warm_step_s": watcher.warm_step_s,
        "host_stall_s": sum(watcher.host_stall_s[k] for k in range(warm_steps + 1, steps_done + 1)),
        "losses": [watcher.loss[k] for k in range(1, CHECK_STEPS + 1)],
        "loss_start": _mean(window_losses[: max(1, len(window_losses) // 4)]),
        "loss_end": _mean(window_losses[-max(1, len(window_losses) // 4):]),
        "first_grad_norms": jax.device_get(snapshots["first_grad_norms"]),
        "delta_norms": jax.device_get(snapshots["delta_norms"]),
        "first_grad": jax.tree.map(lambda m: m.astype("float32") / (1 - b1), snapshots["first_moment"]),
        "first_batches": loader.first, "trace_window": watcher.trace_window, "first_step_at": watcher.done_at[1],
    }


def chip_share(raw: dict, shape, sequence_length: int) -> dict:
    """What ONE chip holds of a step under the YAML's mesh: the shape functions' arguments."""
    mesh = raw["device_mesh"]["config"]
    tp = int(mesh.get("tensor_parallel_degree", 1))
    micro_batch = int(raw["settings"]["step_profile"]["local_train_micro_batch_size"])
    return {
        "sequence_length": sequence_length, "rows_per_chip": micro_batch,  # a `dp_shard` group's rows: every chip of the group sees them whole in attention
        "q_heads_per_chip": shape.n_head_q // tp, "kv_heads_per_chip": max(1, shape.n_head_kv // tp),
        "sequence_share_per_chip": sequence_length // tp,  # of the residual stream and the norms, which sequence parallelism splits over `tp`
        "ce_rows_per_chip": micro_batch * sequence_length // tp, "vocab_per_chip": shape.vocab_size,  # the head's rows split over batch and sequence, its columns whole
        "chips": int(mesh["world_size"]), "dp_shard": int(mesh["data_parallel_shard_degree"]), "tp": tp,
    }


def run(ctx) -> dict:
    import jax

    from modalities_tpu.telemetry import Telemetry, set_active_telemetry

    from benchmark.device import live_peak_bytes
    from benchmark.reference import dense_decoder_f32_mesh as reference
    from benchmark.weights import DecoderShape

    cell = ctx.cell
    raw = yaml.safe_load(cell.yaml_path.read_text())
    world = int(raw["device_mesh"]["config"]["world_size"])
    if cell.chips != world:
        raise SystemExit(f"benchmark: the cell asks for {cell.chips} chip(s) and its YAML's mesh for {world}")
    shape = DecoderShape.from_model_config(raw["model_raw"]["config"])
    sequence_length = int(raw["settings"]["step_profile"]["sequence_length"])
    generator = cell.module("traffic", cell.traffic["generator"])
    written = generator.generate(cell.traffic, ctx.seed, ctx.scratch / "data" / "train.pbin",
                                 vocab_size=shape.vocab_size, sequence_length=sequence_length)
    print(f"[train] corpus from seed {ctx.seed}: {written}; {shape.n_layer} layers, {shape.all_params():,} parameters over {world} chips", flush=True)

    telemetry = Telemetry(output_folder_path=ctx.scratch / "telemetry")  # active while the step is traced and compiled: the plans land here
    previous = set_active_telemetry(telemetry)
    try:
        t0 = time.perf_counter()
        components, fns = build_program(cell, ctx.seed, ctx.scratch, shape)
        built_s = time.perf_counter() - t0
        observed = drive(ctx, components, fns, raw, shape, telemetry)
        first_step_s = observed.pop("first_step_at") - t0 - built_s
    finally:
        set_active_telemetry(previous)
    for plan in (e for e in sink_events(telemetry) if e.get("name") in PLANS):
        print("[train] plan " + json.dumps({k: v for k, v in plan.items() if k not in ("ts", "rank", "event")}), flush=True)
    print(f"[train] program built in {built_s:.1f} s, first step published {first_step_s:.1f} s later", flush=True)
    slowest = sorted(observed["step_seconds"], reverse=True)[:3]
    print(f"[train] {observed['steps_in_window']} steps in the window, median {_median(observed['step_seconds']) * 1e3:.2f} ms; "
          f"the three slowest took {[round(s * 1e3, 1) for s in slowest]} ms", flush=True)
    observed["memory_peak_bytes"] = max(
        live_peak_bytes(), program_memory(fns, observed["first_batches"][0], raw["settings"]["referencing_keys"]))
    free(fns)
    del components, fns

    t0 = time.perf_counter()
    want = reference.train_steps(shape, ctx.seed, observed["first_batches"], hyperparameters(raw),
                                 jax.devices()[:world], other_first_grad=observed.pop("first_grad"), log=lambda line: print(line, flush=True))
    observed["reference_s"] = time.perf_counter() - t0
    observed["compared"] = compare(observed, want, cell.spec["limits"])
    observed["shape"] = shape
    observed["run"] = chip_share(raw, shape, sequence_length)
    rate = observed["steps_in_window"] * observed["tokens_per_step"] / (observed["window"][1] - observed["window"][0])
    observed["end_to_end"] = {"train_tokens_per_s": rate}
    # what the shares of the peak are taken from: in a traced run the window holds the step in which the profiler stops and
    # writes four devices' events, so there the rate is the median step's (as the hybrid mode has it), not the window's
    observed["tokens_per_s"] = observed["tokens_per_step"] / _median(observed["step_seconds"]) if ctx.trace_dir is not None else rate
    return observed
