"""Train mode for a decoder whose layers are of two kinds (attention and a state-space
mixer): `benchmark/modes/train.py` with another layout, shape and reference.

The run is the dense mode's, piece for piece (its module docstring says how the window
is counted): the program's compiled train step with its state, given the benchmark's
seeded weights (`benchmark/weights_hybrid.py`), ONE `Trainer.train` call with the
`DeviceFeeder` live and a new packed batch every step, the window counted in whole
steps from the trainer's published intervals. What differs:

- the reference (`benchmark/reference/hybrid_ssm_decoder_f32.py`) follows the first TWO
  steps, not three: at 1.6 B parameters float32 weights and one gradient fill the chip,
  so the gradients of earlier steps wait on the host, and each further step moves
  another 13 GB over the host's link. The second step is the one that needs Adam's
  moments carried; a third would only repeat it.
- a traced run's `tokens_per_s` (which only the share of the peak reads) is that of the
  median step: the step in which the profiler stops takes a minute here.
- the program's telemetry writes to a sink under the run's scratch directory, and the
  run prints the plans its kernels and scans emitted while they were traced
  (`ssm_scan_plan`, `flash_tile_plan`): once per shape.

A program that has no state-space mixer (the parent of the PR that added this mode)
fails at once, when its config factory refuses the model block's keys.
"""

from __future__ import annotations

import json
import os
import time
from pathlib import Path

import yaml

from benchmark.modes.train import (LimitedLoader, StepWatcher, _mean, _median, _Silent, adam_first_moment, compare, free,
                                   hyperparameters, program_memory)

CHECK_STEPS = 2  # the steps the reference follows
PLANS = ("ssm_scan_plan", "flash_tile_plan")
SLOW_COMPILE_S = 2.0  # compiles at least this long are printed, so that a slow set-up explains itself


def build_program(cell, seed: int, scratch: Path, shape):
    """The program's components and its compiled step with its state, holding the
    benchmark's seeded weights. `scratch` becomes the working directory."""
    import jax

    from modalities_tpu.main import Main

    from benchmark.weights_hybrid import make_program_tree

    os.chdir(scratch)
    main = Main(cell.yaml_path, experiment_id="bench")
    components = main.build_components()
    fns = Main.build_step_functions(components)
    state = fns.app_state_handle.state
    fns.app_state_handle.state = state.replace(params=make_program_tree(shape, seed, state.params))
    del state
    jax.block_until_ready(fns.app_state_handle.state.params)
    return components, fns


def drive(ctx, components, fns, raw: dict, shape, telemetry) -> dict:
    """One `Trainer.train` call: set-up steps, then the window. Returns what was observed."""
    import jax
    import jax.numpy as jnp

    from modalities_tpu.logging_broker.message_broker import MessageBroker
    from modalities_tpu.logging_broker.messages import MessageTypes
    from modalities_tpu.logging_broker.publisher import MessagePublisher
    from modalities_tpu.trainer import Trainer
    from modalities_tpu.training.training_progress import TrainingProgress

    from benchmark.reference.hybrid_ssm_decoder_f32 import leaf_norms
    from benchmark.weights_hybrid import program_tree, reference_layout, seed_key

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
    delta_norms = jax.jit(lambda params, key: leaf_norms(jax.tree.map(
        lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32),
        reference_layout(params), reference_layout(program_tree(shape, key)))))
    key = seed_key(ctx.seed)
    snapshots: dict[str, dict] = {}

    def at_step_boundary(progress, force: bool = False) -> None:
        step = progress.num_seen_steps_current_run
        if step == 1:
            opt_state = fns.app_state_handle.state.opt_state
            snapshots["first_grad_norms"] = grad_norms(opt_state)
            # the gradient itself, to the host (bfloat16 as the optimizer keeps it): the
            # reference measures its distance from its own once the device is free
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
        "first_moment": snapshots["first_moment"], "first_moment_scale": 1.0 / (1 - b1),
        "first_batches": loader.first, "trace_window": watcher.trace_window, "first_step_at": watcher.done_at[1],
    }


def judged(program: dict, reference: dict, limits: dict) -> list[dict]:
    """The dense mode's rows, and one more: the first gradient's distance from the reference's over ALL leaves (the norm
    of the whole difference against the norm of the whole reference). The worst leaf of this model is a small one (a
    norm's 16 scales, a layer's `x_proj`) and moves by half between seeds; the pooled distance moves by a twentieth,
    and separates bfloat16 from the int8 control by 2.8 where the worst leaf separates them by 2.0."""
    rows = compare(program, reference, limits)
    pooled = next(row for row in rows if row["name"] == "first_grad_worst_leaf_rel_error")["pooled"]
    rows.append({"name": "first_grad_pooled_rel_error", "value": pooled, "limit": limits["grad_pooled_rel_error"],
                 "ok": bool(pooled <= limits["grad_pooled_rel_error"])})
    return rows


def sink_events(telemetry) -> list[dict]:
    path = telemetry.sink_path
    if path is None or not Path(path).is_file():
        return []
    return [json.loads(line) for line in Path(path).read_text().splitlines() if line.strip()]


def run(ctx) -> dict:
    from modalities_tpu.telemetry import Telemetry, set_active_telemetry

    from benchmark.device import live_peak_bytes
    from benchmark.reference import hybrid_ssm_decoder_f32 as reference
    from benchmark.weights_hybrid import HybridShape

    cell = ctx.cell
    if cell.chips != 1:
        raise SystemExit("benchmark: train_hybrid mode drives one chip; a mesh of several needs a mode of its own")
    raw = yaml.safe_load(cell.yaml_path.read_text())
    shape = HybridShape.from_yaml(raw)
    sequence_length = int(raw["settings"]["step_profile"]["sequence_length"])
    generator = cell.module("traffic", cell.traffic["generator"])
    written = generator.generate(cell.traffic, ctx.seed, ctx.scratch / "data" / "train.pbin",
                                 vocab_size=shape.vocab_size, sequence_length=sequence_length)
    print(f"[train] corpus from seed {ctx.seed}: {written}; layers {''.join(k[0] for k in shape.kinds)} "
          f"({shape.all_params():,} parameters)", flush=True)

    telemetry = Telemetry(output_folder_path=ctx.scratch / "telemetry")  # active while the step is traced: the plans land here
    previous = set_active_telemetry(telemetry)
    try:
        t0 = time.perf_counter()
        components, fns = build_program(cell, ctx.seed, ctx.scratch, shape)
        built_s = time.perf_counter() - t0
        observed = drive(ctx, components, fns, raw, shape, telemetry)
        first_step_s = observed.pop("first_step_at") - t0 - built_s
    finally:
        set_active_telemetry(previous)
    events = sink_events(telemetry)
    for plan in (e for e in events if e.get("name") in PLANS):  # what the scans and kernels said of their shapes while traced
        print("[train] plan " + json.dumps({k: v for k, v in plan.items() if k not in ("ts", "rank", "event")}), flush=True)
    slow = [(round(e["seconds"], 1), e.get("function"), "hit" if e.get("cache_hit") else "compiled")
            for e in events if e.get("event") == "compile" and e.get("seconds", 0) >= SLOW_COMPILE_S]
    print(f"[train] program built in {built_s:.1f} s, first step published {first_step_s:.1f} s later; compiles of {SLOW_COMPILE_S} s and more: {slow}", flush=True)
    slowest = sorted(observed["step_seconds"], reverse=True)[:3]
    print(f"[train] {observed['steps_in_window']} steps in the window, median {_median(observed['step_seconds']) * 1e3:.2f} ms; "
          f"the three slowest took {[round(s * 1e3, 1) for s in slowest]} ms", flush=True)
    observed["memory_peak_bytes"] = max(
        live_peak_bytes(), program_memory(fns, observed["first_batches"][0], raw["settings"]["referencing_keys"]))
    free(fns)
    del components, fns

    t0 = time.perf_counter()
    hyper = hyperparameters(raw)
    want = reference.train_steps(shape, ctx.seed, observed["first_batches"], hyper, other_first_grad=observed.pop("first_moment"),
                                 other_scale=observed.pop("first_moment_scale"), log=lambda line: print(line, flush=True))
    observed["reference_s"] = time.perf_counter() - t0
    observed["compared"] = judged(observed, want, cell.spec["limits"])
    observed["shape"] = shape
    micro_batch = int(raw["settings"]["step_profile"]["local_train_micro_batch_size"])
    observed["run"] = {  # what the one chip holds of a step: the shape functions' arguments
        "sequence_length": sequence_length, "rows_per_chip": micro_batch,
        "q_heads_per_chip": shape.n_head_q, "kv_heads_per_chip": shape.n_head_kv,
        "ce_rows_per_chip": micro_batch * sequence_length, "vocab_per_chip": shape.vocab_size,
    }
    rate = observed["steps_in_window"] * observed["tokens_per_step"] / (observed["window"][1] - observed["window"][0])
    observed["end_to_end"] = {"train_tokens_per_s": rate}
    # what the share of the peak is taken from. In a traced run the step in which the profiler stops takes a minute
    # (the plain scan runs 0.7 M device operations a step, and every one is an event of the trace), so there the
    # rate is that of the median step and not of the window
    observed["tokens_per_s"] = observed["tokens_per_step"] / _median(observed["step_seconds"]) if ctx.trace_dir is not None else rate
    return observed
