"""Train mode for a decoder of window and global attention layers with a softmax-routed
expert layer in every block (`model_type: mellum`): `benchmark/modes/train_moe.py` with
another layout, shape and reference.

The run is the expert cell's, piece for piece: the program's compiled train step with its
state, given the benchmark's seeded weights (`benchmark/weights_swa_moe.py`), ONE
`Trainer.train` call with the `DeviceFeeder` live and a new packed batch every step, the
window counted in whole steps from the trainer's published intervals. What differs:

- the reference (`benchmark/reference/swa_moe_decoder_f32.py`) follows the first TWO steps;
  it has no selection bias to move. Its loss holds the balance term as the configuration
  weighs it (`router_aux_loss_coef` times the mean over the layers), as the program's does.
- the step's counters are four: `moe_pairs_held`, `moe_load_max`, `moe_load_mean` as the
  expert cell's, and `moe_aux_loss` (the balance term, the mean over the expert layers).
  Pairs held and the balance term on the first followed step are compared with the
  reference's own (the second step's gaps are printed and not held); the window's steps give `moe_load_max_over_mean`, `moe_pairs_held_per_token` (which
  the share of the peak counts the routed work by) and `moe_aux_loss`, and the run prints
  the pairs held a token step by step.
- the run prints the plans emitted while the step was traced (`moe_dispatch_plan`,
  `flash_tile_plan` for the window layers' call and for the global layers', `fused_ce_plan`).

A program that has no window layer (the parent of the PR that added this mode) fails at
once, when its config factory refuses the model block's keys, and the run then takes its
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
PLANS = ("moe_dispatch_plan", "flash_tile_plan", "fused_ce_plan")
COUNTERS = ("moe_pairs_held", "moe_load_max", "moe_load_mean", "moe_aux_loss")


class CountingWatcher(StepWatcher):
    """`StepWatcher`, keeping also the counters each published step carries."""

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        self.counters: dict[int, dict[str, float]] = {}
        self.grad_norm: dict[int, float] = {}  # the whole gradient's norm before clipping, as the trainer publishes it

    def consume_message(self, message) -> None:
        result = message.payload
        missing = [name for name in COUNTERS if name not in result.metrics]
        if missing:
            raise SystemExit(f"benchmark: the trainer published no {missing}: the step's counters did not reach its metrics")
        self.counters[int(result.num_train_steps_done)] = {name: float(np.asarray(result.metrics[name].value)) for name in COUNTERS}
        self.grad_norm[int(result.num_train_steps_done)] = float(np.asarray(result.metrics["grad norm last"].value))
        super().consume_message(message)


def build_program(cell, seed: int, scratch: Path, shape):
    """The program's components and its compiled step with its state, holding the benchmark's seeded weights.
    `scratch` becomes the working directory."""
    import jax

    from modalities_tpu.main import Main

    from benchmark.weights_swa_moe import make_program_tree

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

    from benchmark.reference.swa_moe_decoder_f32 import leaf_norms
    from benchmark.weights_swa_moe import program_tree, reference_layout, seed_key

    cell, settings = ctx.cell, components.settings
    keys = raw["settings"]["referencing_keys"]
    profile = settings.step_profile
    tokens_per_step = (profile.local_train_micro_batch_size * profile.sequence_length
                       * profile.gradient_accumulation_steps * profile.dp_degree)
    warm_steps = int(cell.spec["warm_steps"])
    loader = LimitedLoader(components.train_dataloader, CHECK_STEPS, keys["sample_key"], keys["target_key"])
    watcher = CountingWatcher(loader, warm_steps, ctx.seconds, ctx.trace_dir,
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
    followed = range(1, CHECK_STEPS + 1)
    gaps = [watcher.done_at[k] - watcher.done_at[k - 1] for k in window_steps]
    window_losses = [watcher.loss[k] for k in window_steps]
    counted = lambda name, steps: [watcher.counters[k][name] for k in steps]  # noqa: E731
    return {
        "window": window, "attempted": watcher.target_steps, "failed": watcher.target_steps - in_window,
        "tokens_per_step": tokens_per_step, "steps_in_window": in_window, "step_seconds": gaps,
        "warm_step_s": watcher.warm_step_s,
        "host_stall_s": sum(watcher.host_stall_s[k] for k in window_steps),
        "losses": [watcher.loss[k] for k in followed],
        "pairs_held": counted("moe_pairs_held", followed), "aux_loss": counted("moe_aux_loss", followed),
        "grad_norm": [watcher.grad_norm[k] for k in followed],
        "warm_pairs_held": counted("moe_pairs_held", range(1, warm_steps + 1)),
        "window_pairs_held": counted("moe_pairs_held", window_steps), "window_aux_loss": counted("moe_aux_loss", window_steps),
        "moe_load_max_over_mean": [watcher.counters[k]["moe_load_max"] / max(watcher.counters[k]["moe_load_mean"], 1e-9)
                                   for k in window_steps],
        "loss_start": _mean(window_losses[: max(1, len(window_losses) // 4)]),
        "loss_end": _mean(window_losses[-max(1, len(window_losses) // 4):]),
        "first_grad_norms": jax.device_get(snapshots["first_grad_norms"]),
        "delta_norms": jax.device_get(snapshots["delta_norms"]),
        "first_moment": snapshots["first_moment"], "first_moment_scale": 1.0 / (1 - b1),
        "first_batches": loader.first, "trace_window": watcher.trace_window, "first_step_at": watcher.done_at[1],
    }


def routing_gaps(program: dict, reference: dict) -> list[dict]:
    """On each followed step: the pairs the held experts got (the program's counter `moe_pairs_held`) and the balance
    term (`moe_aux_loss`), each against the reference's own, as rows without a limit."""
    rows = []
    for i, (got, want) in enumerate(zip(program["pairs_held"], reference["pairs_held"])):
        rows.append({"name": f"pairs_held_step{i + 1}_rel_gap", "value": abs(got - want) / max(want, 1.0), "program": got, "reference": want})
    for i, (got, want) in enumerate(zip(program["aux_loss"], reference["aux_loss"])):
        rows.append({"name": f"aux_loss_step{i + 1}_rel_gap", "value": abs(got - want) / abs(want) if np.isfinite(got) else float("inf"),
                     "program": got, "reference": want})
    return rows


def judged_with_routing(program: dict, reference: dict, limits: dict) -> list[dict]:
    """The hybrid mode's rows over every leaf, and two rows for the routing on the FIRST followed step: the pairs the
    held experts got against the reference's own count, and the balance term against the reference's own. The
    dispatch drops no token at any load, so the counts differ only by the tokens whose eighth and ninth scores
    bfloat16 activations order otherwise than float32 ones; the balance term is a sum over all 64 experts of such
    counts times mean scores, and moves less. A router that scored otherwise (sigmoid, or a bias nobody asked for), a
    term pooled over the layers or not computed at all (a program that publishes 0) reads far off.

    The SECOND step's two gaps are read and printed (`routing_gaps`) and NOT held: each side has then taken its own
    first update at the peak learning rate, sound runs read up to 0.015 and 0.006 there, and no fault the first
    step's rows do not already read moves them by ten times that (the cell's file has the readings)."""
    rows = judged(program, reference, limits)
    for row in routing_gaps(program, reference):
        if "_step1_" in row["name"]:
            limit = limits[row["name"].replace("_step1", "")]
            rows.append({**row, "limit": limit, "ok": bool(row["value"] <= limit)})
    return rows


def by_kind_of_leaf(difference: dict, reference: dict) -> dict[str, dict]:
    """The first gradient's distance from the reference's, leaf kinds apart (a layer leaf's name without its run; all
    layers together): each kind's own distance against its own norm, its share of the pooled distance's square
    (which row `first_grad_pooled_rel_error` is the root of, over the whole reference's square) and of the whole
    reference's square. What a pooled reading that moved is made of."""
    kinds: dict[str, list[float]] = {}
    for name in reference:
        sums = kinds.setdefault(name.split(".", 1)[1] if name.startswith("run") else name, [0.0, 0.0])
        sums[0] += float(np.sum(np.asarray(difference[name], np.float64) ** 2))
        sums[1] += float(np.sum(np.asarray(reference[name], np.float64) ** 2))
    all_diff, all_ref = (sum(v[i] for v in kinds.values()) for i in (0, 1))
    return {kind: {"rel_error": round((d / max(r, 1e-300)) ** 0.5, 5), "share_of_pooled_square": round(d / max(all_diff, 1e-300), 4),
                   "share_of_gradient_square": round(r / max(all_ref, 1e-300), 4)}
            for kind, (d, r) in sorted(kinds.items(), key=lambda item: -item[1][0])}


def run(ctx) -> dict:
    import json

    from modalities_tpu.telemetry import Telemetry, set_active_telemetry

    from benchmark.device import live_peak_bytes
    from benchmark.reference import swa_moe_decoder_f32 as reference
    from benchmark.weights_swa_moe import SwaMoEShape

    cell = ctx.cell
    if cell.chips != 1:
        raise SystemExit("benchmark: train_swa_moe mode drives one chip; a mesh of several (an `ep` axis with its exchange) needs a mode of its own")
    raw = yaml.safe_load(cell.yaml_path.read_text())
    shape = SwaMoEShape.from_yaml(raw)
    sequence_length = int(raw["settings"]["step_profile"]["sequence_length"])
    generator = cell.module("traffic", cell.traffic["generator"])
    written = generator.generate(cell.traffic, ctx.seed, ctx.scratch / "data" / "train.pbin",
                                 vocab_size=shape.vocab_size, sequence_length=sequence_length)
    print(f"[train] corpus from seed {ctx.seed}: {written}; layers {''.join('w' if k == 'swa' else 'g' for k in shape.kinds)} (w: window of "
          f"{shape.sliding_window}, g: global; every layer {shape.experts_held} of {shape.n_routed_experts} experts held from {shape.expert_offset}, "
          f"{shape.num_experts_per_tok} a token; {shape.all_params():,} parameters)", flush=True)

    telemetry = Telemetry(output_folder_path=ctx.scratch / "telemetry")  # active while the step is traced: the plans land here
    previous = set_active_telemetry(telemetry)
    try:
        t0 = time.perf_counter()
        try:
            components, fns = build_program(cell, ctx.seed, ctx.scratch, shape)
        except BaseException:
            # a program that cannot build this model (one with no window layer) ends here: it leaves the checkout
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
    tokens = observed["tokens_per_step"]
    window_pairs = observed["window_pairs_held"]
    pairs_per_token = _mean(window_pairs) / tokens
    print(f"[train] {observed['steps_in_window']} steps in the window, median {_median(observed['step_seconds']) * 1e3:.2f} ms; "
          f"the three slowest took {[round(s * 1e3, 1) for s in slowest]} ms (the loop waited {observed['host_stall_s'] * 1e3:.1f} ms for batches in all); "
          f"a token brought {pairs_per_token:.4f} pairs to held experts (an expert layer, mean over the window; nominal "
          f"{shape.num_experts_per_tok * shape.experts_held / shape.n_routed_experts:.2f}), largest over mean load {_median(observed['moe_load_max_over_mean']):.3f}, "
          f"balance term {_median(observed['window_aux_loss']):.4f} (median; 1 at balance)", flush=True)
    print(f"[train] pairs held a token, step by step: warm-up {[round(p / tokens, 3) for p in observed.pop('warm_pairs_held')]}, "
          f"window {[round(p / tokens, 3) for p in window_pairs]}", flush=True)
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
    observed["compared"] = judged_with_routing(observed, want, cell.spec["limits"])
    print("[train] read and not held (the second followed step's routing): "
          + json.dumps([row for row in routing_gaps(observed, want) if "_step1_" not in row["name"]]), flush=True)
    print(f"[train] the whole gradient's norm before clipping, followed steps: program {observed['grad_norm']}, reference {want['grad_norm']}", flush=True)
    print("[train] first gradient's distance by kind of leaf: "
          + json.dumps(by_kind_of_leaf(want["first_grad_difference_norms"], want["first_grad_norms"])), flush=True)
    observed["shape"] = shape
    micro_batch = int(raw["settings"]["step_profile"]["local_train_micro_batch_size"])
    observed["run"] = {  # what the one chip holds of a step: the shape functions' arguments
        "sequence_length": sequence_length, "rows_per_chip": micro_batch,
        "q_heads_per_chip": shape.n_head_q, "kv_heads_per_chip": shape.n_head_kv,
        "ce_rows_per_chip": micro_batch * sequence_length, "vocab_per_chip": shape.vocab_size,
        "pairs_held_per_token": pairs_per_token,  # as the program's counter read them in the window
    }
    rate = observed["steps_in_window"] * observed["tokens_per_step"] / (observed["window"][1] - observed["window"][0])
    observed["end_to_end"] = {"train_tokens_per_s": rate}
    # what the share of the peak is taken from: in a traced run the step in which the profiler stops is far over the median
    observed["tokens_per_s"] = observed["tokens_per_step"] / _median(observed["step_seconds"]) if ctx.trace_dir is not None else rate
    return observed
