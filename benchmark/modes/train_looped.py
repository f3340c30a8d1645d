"""Train mode for a looped decoder (`model_type: ouro`: a stack of layers walked several
times over one set of weights, an exit gate and the loss over all exits):
`benchmark/modes/train.py` with another layout, shape and reference, as `train_hybrid.py`
and `train_moe.py` are for their models.

The run is the dense mode's, piece for piece (its module docstring says how the window
is counted): the program's compiled train step with its state, given the benchmark's
seeded weights (`benchmark/weights_looped.py`), ONE `Trainer.train` call with the
`DeviceFeeder` live and a new packed batch every step, the window counted in whole
steps from the trainer's published intervals. What differs:

- the reference (`benchmark/reference/looped_decoder_f32.py`) follows the first TWO
  steps, as the hybrid's and the expert cell's do and for their reason: at 1.02 B
  parameters float32 weights and one gradient are 8.2 GB of a 16 GB chip, so Adam's
  moments are not kept there; the first step's clipped gradient waits on the host for the
  second step's update.
- the step's counters (`loop_exit_ce_1..T`: every exit's mean cross entropy;
  `loop_expected_exit`: the mean over tokens of sum_t t p(t); `loop_gate_entropy`: summed
  on the device, published by the trainer with every step's metrics) are read off the
  published intervals. On the followed steps every exit's cross entropy and the expected
  exit are compared with the reference's own: a program that leaves an exit out of its
  loss, or weighs the exits by another distribution, reads the same total within the
  loss's limit only by accident, and not these. The window's steps give the metric
  `loop_expected_exit`.
- a traced run's `tokens_per_s` (which only the share of the peak reads) is that of the
  median step, as in the hybrid's mode.
- the program's telemetry writes to a sink under the run's scratch directory, and the
  run prints the plans emitted while the step was traced (`loop_plan`, `fused_ce_plan`,
  `flash_tile_plan`): once per shape.

A program that has no loop (the parent of the PR that added this mode) fails at once,
when its config factory refuses the model block's keys, and the run then takes its
scratch directory with it: the checkout is left as it was found.
"""

from __future__ import annotations

import os
import shutil
import time
from pathlib import Path

import numpy as np
import yaml

from benchmark.modes.train import (LimitedLoader, StepWatcher, _mean, _median, _Silent, adam_first_moment, free,
                                   hyperparameters, program_memory)
from benchmark.modes.train_hybrid import SLOW_COMPILE_S, judged, sink_events

CHECK_STEPS = 2  # the steps the reference follows
PLANS = ("loop_plan", "fused_ce_plan", "flash_tile_plan")


def counter_names(walks: int) -> tuple[str, ...]:
    """What the program's step counts for a model of `walks` exits (`loss_functions.exit_counter_names`, spelt out: no import of the program)."""
    return tuple(f"loop_exit_ce_{t}" for t in range(1, walks + 1)) + ("loop_expected_exit", "loop_gate_entropy")


class CountingWatcher(StepWatcher):
    """`StepWatcher`, keeping also the counters each published step carries."""

    def __init__(self, names, *args, **kwargs):
        super().__init__(*args, **kwargs)
        self.names = names
        self.counters: dict[int, dict[str, float]] = {}

    def consume_message(self, message) -> None:
        result = message.payload
        missing = [name for name in self.names if name not in result.metrics]
        if missing:
            raise SystemExit(f"benchmark: the trainer published no {missing}: the step's counters did not reach its metrics")
        self.counters[int(result.num_train_steps_done)] = {name: float(np.asarray(result.metrics[name].value)) for name in self.names}
        super().consume_message(message)


def build_program(cell, seed: int, scratch: Path, shape):
    """The program's components and its compiled step with its state, holding the
    benchmark's seeded weights. `scratch` becomes the working directory."""
    import jax

    from modalities_tpu.main import Main

    from benchmark.weights_looped import make_program_tree

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

    from benchmark.reference.looped_decoder_f32 import leaf_norms
    from benchmark.weights_looped import program_tree, reference_layout, seed_key

    cell, settings = ctx.cell, components.settings
    keys = raw["settings"]["referencing_keys"]
    profile = settings.step_profile
    tokens_per_step = (profile.local_train_micro_batch_size * profile.sequence_length
                       * profile.gradient_accumulation_steps * profile.dp_degree)
    warm_steps = int(cell.spec["warm_steps"])
    loader = LimitedLoader(components.train_dataloader, CHECK_STEPS, keys["sample_key"], keys["target_key"])
    counted = counter_names(shape.total_ut_steps)
    exits = counted[: shape.total_ut_steps]
    watcher = CountingWatcher(counted, loader, warm_steps, ctx.seconds, ctx.trace_dir,
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
    window_steps = range(warm_steps + 1, steps_done + 1)
    gaps = [watcher.done_at[k] - watcher.done_at[k - 1] for k in window_steps]
    window_losses = [watcher.loss[k] for k in window_steps]
    return {
        "window": window, "attempted": watcher.target_steps, "failed": watcher.target_steps - in_window,
        "tokens_per_step": tokens_per_step, "steps_in_window": in_window, "step_seconds": gaps,
        "warm_step_s": watcher.warm_step_s,
        "host_stall_s": sum(watcher.host_stall_s[k] for k in window_steps),
        "losses": [watcher.loss[k] for k in range(1, CHECK_STEPS + 1)],
        "exit_ce": [[watcher.counters[k][name] for name in exits] for k in range(1, CHECK_STEPS + 1)],
        "expected_exit": [watcher.counters[k]["loop_expected_exit"] for k in range(1, CHECK_STEPS + 1)],
        "window_exit_ce": [_mean([watcher.counters[k][name] for k in window_steps]) for name in exits],
        "loop_expected_exit": [watcher.counters[k]["loop_expected_exit"] for k in window_steps],
        "loop_gate_entropy": [watcher.counters[k]["loop_gate_entropy"] for k in window_steps],
        "loss_start": _mean(window_losses[: max(1, len(window_losses) // 4)]),
        "loss_end": _mean(window_losses[-max(1, len(window_losses) // 4):]),
        "first_grad_norms": jax.device_get(snapshots["first_grad_norms"]),
        "delta_norms": jax.device_get(snapshots["delta_norms"]),
        "first_moment": snapshots["first_moment"], "first_moment_scale": 1.0 / (1 - b1),
        "first_batches": loader.first, "trace_window": watcher.trace_window, "first_step_at": watcher.done_at[1],
    }


def judged_with_exits(program: dict, reference: dict, limits: dict) -> list[dict]:
    """The hybrid mode's rows, and two kinds of row for the loop. For each followed step every exit's mean cross
    entropy (the program's counters `loop_exit_ce_<t>`) against the reference's own, the largest relative gap over the
    exits (`exit_ce_rel_gap`): the loss is a weighted sum of them, and holds none of them alone. And the expected
    exit, `mean_i sum_t t p_i(t)` (`loop_expected_exit`), against the reference's (`expected_exit_gap`, in exits): what
    the gate made of every exit's hidden state, which no cross entropy sees."""
    rows = judged(program, reference, limits)
    for i, (got, want) in enumerate(zip(program["exit_ce"], reference["exit_ce"])):
        gaps = [abs(g - w) / abs(w) if np.isfinite(g) else float("inf") for g, w in zip(got, want)]
        rows.append({"name": f"exit_ce_step{i + 1}_rel_gap", "value": max(gaps), "limit": limits["exit_ce_rel_gap"],
                     "ok": bool(max(gaps) <= limits["exit_ce_rel_gap"]), "program": got, "reference": want})
    for i, (got, want) in enumerate(zip(program["expected_exit"], reference["expected_exit"])):
        gap = abs(got - want) if np.isfinite(got) else float("inf")
        rows.append({"name": f"expected_exit_step{i + 1}_gap", "value": gap, "limit": limits["expected_exit_gap"],
                     "ok": bool(gap <= limits["expected_exit_gap"]), "program": got, "reference": want})
    return rows


def run(ctx) -> dict:
    import json

    from modalities_tpu.telemetry import Telemetry, set_active_telemetry

    from benchmark.device import live_peak_bytes
    from benchmark.reference import looped_decoder_f32 as reference
    from benchmark.weights_looped import LoopedShape

    cell = ctx.cell
    if cell.chips != 1:
        raise SystemExit("benchmark: train_looped mode drives one chip; a mesh of several needs a mode of its own")
    raw = yaml.safe_load(cell.yaml_path.read_text())
    shape = LoopedShape.from_yaml(raw)
    sequence_length = int(raw["settings"]["step_profile"]["sequence_length"])
    generator = cell.module("traffic", cell.traffic["generator"])
    written = generator.generate(cell.traffic, ctx.seed, ctx.scratch / "data" / "train.pbin",
                                 vocab_size=shape.vocab_size, sequence_length=sequence_length)
    print(f"[train] corpus from seed {ctx.seed}: {written}; {shape.n_layer} layers walked {shape.total_ut_steps} times "
          f"({shape.applications} layer applications over {shape.all_params():,} parameters)", flush=True)

    telemetry = Telemetry(output_folder_path=ctx.scratch / "telemetry")  # active while the step is traced: the plans land here
    previous = set_active_telemetry(telemetry)
    try:
        t0 = time.perf_counter()
        try:
            components, fns = build_program(cell, ctx.seed, ctx.scratch, shape)
        except BaseException:
            # a program that cannot build this model (one with no loop) ends here: it leaves the checkout
            # as it found it, without the corpus, for the runs of other cells that follow in the same checkout
            os.chdir(cell.root)
            shutil.rmtree(ctx.scratch, ignore_errors=True)
            raise
        built_s = time.perf_counter() - t0
        observed = drive(ctx, components, fns, raw, shape, telemetry)
        first_step_s = observed.pop("first_step_at") - t0 - built_s
    finally:
        set_active_telemetry(previous)
    events = sink_events(telemetry)
    for plan in (e for e in events if e.get("name") in PLANS):  # what the dispatch and the kernels said of their shapes while traced
        print("[train] plan " + json.dumps({k: v for k, v in plan.items() if k not in ("ts", "rank", "event")}), flush=True)
    slow = [(round(e["seconds"], 1), e.get("function"), "hit" if e.get("cache_hit") else "compiled")
            for e in events if e.get("event") == "compile" and e.get("seconds", 0) >= SLOW_COMPILE_S]
    print(f"[train] program built in {built_s:.1f} s, first step published {first_step_s:.1f} s later; compiles of {SLOW_COMPILE_S} s and more: {slow}", flush=True)
    slowest = sorted(observed["step_seconds"], reverse=True)[:3]
    print(f"[train] {observed['steps_in_window']} steps in the window, median {_median(observed['step_seconds']) * 1e3:.2f} ms; "
          f"the three slowest took {[round(s * 1e3, 1) for s in slowest]} ms (the loop waited {observed['host_stall_s'] * 1e3:.1f} ms for batches in all); "
          f"over the window the exits' cross entropy {[round(c, 4) for c in observed.pop('window_exit_ce')]}, expected exit "
          f"{_median(observed['loop_expected_exit']):.4f}, entropy of the exit distribution {_median(observed.pop('loop_gate_entropy')):.4f}", flush=True)
    observed["memory_peak_bytes"] = max(
        live_peak_bytes(), program_memory(fns, observed["first_batches"][0], raw["settings"]["referencing_keys"]))
    free(fns)
    del components, fns

    t0 = time.perf_counter()
    hyper = hyperparameters(raw)
    hyper["lr"] = hyper["lr"][:CHECK_STEPS]
    want = reference.train_steps(shape, ctx.seed, observed["first_batches"], hyper, other_first_grad=observed.pop("first_moment"),
                                 other_scale=observed.pop("first_moment_scale"), log=lambda line: print(line, flush=True))
    observed["reference_s"] = time.perf_counter() - t0
    observed["compared"] = judged_with_exits(observed, want, cell.spec["limits"])
    observed["shape"] = shape
    micro_batch = int(raw["settings"]["step_profile"]["local_train_micro_batch_size"])
    observed["run"] = {  # what the one chip holds of a step: the shape functions' arguments
        "sequence_length": sequence_length, "rows_per_chip": micro_batch,
        "q_heads_per_chip": shape.n_head, "kv_heads_per_chip": shape.n_head,
        # every exit's rows against the one head: T x B x S rows of one fused call
        "ce_rows_per_chip": shape.total_ut_steps * micro_batch * sequence_length, "vocab_per_chip": shape.vocab_size,
    }
    rate = observed["steps_in_window"] * observed["tokens_per_step"] / (observed["window"][1] - observed["window"][0])
    observed["end_to_end"] = {"train_tokens_per_s": rate}
    # what the share of the peak is taken from: in a traced run the step in which the profiler stops is far over the median
    observed["tokens_per_s"] = observed["tokens_per_step"] / _median(observed["step_seconds"]) if ctx.trace_dir is not None else rate
    return observed
