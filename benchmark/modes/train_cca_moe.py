"""Train mode for a decoder of compressed-convolutional-attention layers with scaled residual
merges and an expert layer whose router is an MLP over a state handed from layer to layer,
one choice a token and a column that skips (`model_type: zaya`): `benchmark/modes/train_swa_moe.py`
with another layout, shape and reference.

The run is the expert cells', piece for piece: the program's compiled train step with its
state, given the benchmark's seeded weights (`benchmark/weights_cca_moe.py`), ONE
`Trainer.train` call with the `DeviceFeeder` live and a new packed batch every step, the
window counted in whole steps from the trainer's published intervals. What differs:

- the reference (`benchmark/reference/cca_moe_decoder_f32.py`) follows the first TWO steps and
  moves the selection bias after each by the configuration's rule, from its own count of every
  column's load, as the program does.
- the step's counters are five: `moe_pairs_held`, `moe_load_max`, `moe_load_mean` as the
  expert cells', `moe_skip_share` (the share of a layer's tokens that chose the column with no
  expert behind it, the mean over the layers) and `cca_key_temperature` (the mean of the learned
  key temperatures). Pairs held and the skip share on both followed steps are compared with
  the reference's own, each row held where the cell's file gives it a limit and printed
  where it gives none; the window's steps give `moe_load_max_over_mean`,
  `moe_pairs_held_per_token` (which the share of the peak counts the routed work by) and
  `moe_skip_share`, and the run prints the pairs held a token step by step.
- one choice a token: a token whose two largest `p + beta` lie closer than bfloat16 activations
  resolve goes to another column than in float32, and that is all of the token's pair. After the
  window the mode runs the program's forward pass once more on the first followed batch from
  the seeded weights (`choice_gap`), reads every layer's load by column, and prints beside the
  reference's own the least share of a layer's tokens that must have chosen otherwise (half the
  sum over the columns of the loads' difference): read, not held.
- the run prints the plans emitted while the step was traced (`cca_plan`, `moe_dispatch_plan`,
  `flash_tile_plan`, `fused_ce_plan`).

A program that has no such layer (the parent of the PR that added this mode) fails at
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
from benchmark.modes.train_swa_moe import by_kind_of_leaf  # noqa: F401  (the control prints it too)

CHECK_STEPS = 2  # the steps the reference follows
PLANS = ("cca_plan", "moe_dispatch_plan", "flash_tile_plan", "fused_ce_plan")
COUNTERS = ("moe_pairs_held", "moe_load_max", "moe_load_mean", "moe_skip_share", "cca_key_temperature")


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

    from benchmark.weights_cca_moe import make_program_tree

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

    from benchmark.reference.cca_moe_decoder_f32 import leaf_norms
    from benchmark.weights_cca_moe import program_tree, reference_layout, seed_key

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
    key = seed_key(ctx.weights_seed)
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
        "pairs_held": counted("moe_pairs_held", followed), "skip_share": counted("moe_skip_share", followed),
        "grad_norm": [watcher.grad_norm[k] for k in followed],
        "warm_pairs_held": counted("moe_pairs_held", range(1, warm_steps + 1)),
        "window_pairs_held": counted("moe_pairs_held", window_steps), "window_skip_share": counted("moe_skip_share", window_steps),
        "window_key_temperature": counted("cca_key_temperature", window_steps),
        "moe_load_max_over_mean": [watcher.counters[k]["moe_load_max"] / max(watcher.counters[k]["moe_load_mean"], 1e-9)
                                   for k in window_steps],
        "loss_start": _mean(window_losses[: max(1, len(window_losses) // 4)]),
        "loss_end": _mean(window_losses[-max(1, len(window_losses) // 4):]),
        "first_grad_norms": jax.device_get(snapshots["first_grad_norms"]),
        "delta_norms": jax.device_get(snapshots["delta_norms"]),
        "first_moment": snapshots["first_moment"], "first_moment_scale": 1.0 / (1 - b1),
        "first_batches": loader.first, "trace_window": watcher.trace_window, "first_step_at": watcher.done_at[1],
    }


BIAS = "router_bias"  # the leaf the optimizer leaves alone and the configuration's rule moves


def routing_gaps(program: dict, reference: dict, tokens: int) -> list[dict]:
    """On each followed step: the tokens the held experts got (the program's counter `moe_pairs_held`, the mean over the
    layers) against the reference's own count, as a share of the step's tokens, and the share that chose the skip
    column (`moe_skip_share`) against the reference's own, as rows without a limit."""
    rows = []
    for i, (got, want) in enumerate(zip(program["pairs_held"], reference["pairs_held"])):
        rows.append({"name": f"pairs_held_step{i + 1}_gap_per_token", "value": abs(got - want) / tokens, "program": got, "reference": want})
    for i, (got, want) in enumerate(zip(program["skip_share"], reference["skip_share"])):
        rows.append({"name": f"skip_share_step{i + 1}_gap", "value": abs(got - want) if np.isfinite(got) else float("inf"), "program": got, "reference": want})
    return rows


def limit_name(row_name: str) -> str:
    """The key of the cell's `limits` that a row of `routing_gaps` is held to, where the file has it."""
    return row_name.replace("_step1", "") if "_step1_" in row_name else row_name.replace("_step2", "_after_move")


def judged_with_routing(program: dict, reference: dict, limits: dict, shape, tokens: int) -> list[dict]:
    """The hybrid mode's rows over every leaf the optimizer moves, and three kinds of row for the routing.

    For each followed step the tokens the held experts got and the share that chose the skip column, each against the
    reference's own count and as a share of the step's tokens (one choice a token: a pair is a token, and the gap is
    the net share of tokens that crossed between the held experts, or the skip column, and the rest). The first step's
    routing is the seeded bias's (zeros) on both sides: the gap is the tokens whose two largest probabilities bfloat16
    activations order otherwise than float32 ones (`pairs_held_gap_per_token`, `skip_share_gap`). From the second step
    on each side has moved the bias by the sign of ITS count of every column's load against the mean and taken its own
    first update at the peak learning rate (`pairs_held_after_move_gap_per_token`, `skip_share_after_move_gap`).

    A row is held where the cell's file gives it a limit. The sixth cell's gives none to the skip share after the move
    (since PR 49): a column whose load lies within a few tokens of the mean moves up on one side and down on the other,
    and where that column is a layer's skip column a sound run reads 0.004 against under 0.001 otherwise, while a
    program that never moves the bias reads 0.003 to 0.008 (the cell's file has the readings): the run prints it
    beside the held rows and it is judged by nothing.

    The selection bias's own change is judged apart from the other leaves' (`bias_change_gap`), as the expert cell's:
    the largest difference between the two sides' norms of a layer's change against the norm of one move of all the
    columns; a program that leaves the bias where it was reads what the reference's largest layer moved."""
    without_bias = lambda side: {**side, "delta_norms": {k: v for k, v in side["delta_norms"].items() if not k.endswith(BIAS)}}  # noqa: E731
    rows = judged(without_bias(program), without_bias(reference), limits)
    for row in routing_gaps(program, reference, tokens):
        limit = limits.get(limit_name(row["name"]))
        if limit is not None:
            rows.append({**row, "limit": limit, "ok": bool(row["value"] <= limit)})
    if shape.bias_update_speed:
        one_move = shape.bias_update_speed * np.sqrt(shape.router_width)
        moved = {k: (np.asarray(program["delta_norms"][k], np.float64), np.asarray(v, np.float64)) for k, v in reference["delta_norms"].items() if k.endswith(BIAS)}
        gap = max(float(np.abs(got - want).max()) for got, want in moved.values()) / one_move
        rows.append({"name": "bias_change_gap", "value": gap, "limit": limits["bias_change_gap"], "ok": bool(gap <= limits["bias_change_gap"]),
                     "program": {k: np.round(got, 5).tolist() for k, (got, _) in moved.items()},
                     "reference": {k: np.round(want, 5).tolist() for k, (_, want) in moved.items()}})
    return rows


def choice_gap(model, shape, seed: int, like, first_batch, reference_loads) -> dict:
    """The program's forward pass on the first followed batch from the seeded weights, once more: every layer's load
    by router column against the reference's own. A token that chose another column than the reference's takes one
    from a column and gives one to another, so half the sum of the loads' differences over the columns is the LEAST
    number of tokens of a layer that chose otherwise (moves that cancel are not seen). Returns the share by layer."""
    import jax

    from benchmark.weights_cca_moe import make_program_tree

    params = make_program_tree(shape, seed, like)
    tokens, _ = first_batch
    _, counted = jax.jit(lambda p, ids: model.apply_counted(p, {model.sample_key: ids}, train=True, hidden=True))(params, tokens)
    got = np.asarray(jax.device_get(counted["moe_expert_load"]), np.float64)
    for leaf in jax.tree.leaves(params):
        leaf.delete()
    want = np.asarray(reference_loads, np.float64)
    return {"least_share_of_tokens_that_chose_otherwise_by_layer": np.round(np.abs(got - want).sum(axis=1) / (2 * tokens.size), 5).tolist(),
            "program_loads_first_layer": got[0].tolist(), "reference_loads_first_layer": want[0].tolist(),
            "program_loads_last_layer": got[-1].tolist(), "reference_loads_last_layer": want[-1].tolist()}


def run(ctx) -> dict:
    import json

    import jax

    from modalities_tpu.telemetry import Telemetry, set_active_telemetry

    from benchmark.device import live_peak_bytes
    from benchmark.reference import cca_moe_decoder_f32 as reference
    from benchmark.weights_cca_moe import CcaMoEShape

    cell = ctx.cell
    if cell.chips != 1:
        raise SystemExit("benchmark: train_cca_moe mode drives one chip; a mesh of several (an `ep` axis with its exchange) needs a mode of its own")
    raw = yaml.safe_load(cell.yaml_path.read_text())
    shape = CcaMoEShape.from_yaml(raw)
    sequence_length = int(raw["settings"]["step_profile"]["sequence_length"])
    generator = cell.module("traffic", cell.traffic["generator"])
    written = generator.generate(cell.traffic, ctx.seed, ctx.scratch / "data" / "train.pbin",
                                 vocab_size=shape.vocab_size, sequence_length=sequence_length)
    print(f"[train] corpus from seed {ctx.seed}, weights from seed {ctx.weights_seed}: {written}; {shape.n_layer} hybrid layers ({shape.n_head_q} query heads on {shape.n_head_kv} of {shape.head_dim} in a "
          f"latent of {shape.latent_heads * shape.head_dim}, taps {shape.time0} and {shape.time1}, {shape.rotated} channels of a head turned; every layer "
          f"{shape.experts_held} of {shape.n_routed_experts} experts held from {shape.expert_offset}, {shape.num_experts_per_tok} of {shape.router_width} columns a token, "
          f"selection bias moved by {shape.bias_update_speed} a step; {shape.all_params():,} parameters)", flush=True)

    telemetry = Telemetry(output_folder_path=ctx.scratch / "telemetry")  # active while the step is traced: the plans land here
    previous = set_active_telemetry(telemetry)
    try:
        t0 = time.perf_counter()
        try:
            components, fns = build_program(cell, ctx.weights_seed, ctx.scratch, shape)
        except BaseException:
            # a program that cannot build this model (one with no such mixer or router) ends here: it leaves the checkout
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
          f"{shape.num_experts_per_tok * shape.experts_held / shape.router_width:.3f}), largest over mean load {_median(observed['moe_load_max_over_mean']):.3f}, "
          f"{_mean(observed['window_skip_share']):.4f} of a layer's tokens chose the skip column (mean; nominal {1 / shape.router_width:.4f}), "
          f"key temperature {_mean(observed['window_key_temperature']):.5f}", flush=True)
    print(f"[train] skip share, step by step: {[round(v, 3) for v in observed['window_skip_share']]}", flush=True)
    print(f"[train] pairs held a token, step by step: warm-up {[round(p / tokens, 3) for p in observed.pop('warm_pairs_held')]}, "
          f"window {[round(p / tokens, 3) for p in window_pairs]}", flush=True)
    observed["memory_peak_bytes"] = max(
        live_peak_bytes(), program_memory(fns, observed["first_batches"][0], raw["settings"]["referencing_keys"]))
    like = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding), fns.app_state_handle.state.params)
    model = components.app_state.model
    free(fns)
    del components, fns

    t0 = time.perf_counter()
    hyper = hyperparameters(raw)
    hyper["lr"] = hyper["lr"][:CHECK_STEPS]
    want = reference.train_steps(shape, ctx.weights_seed, observed["first_batches"], hyper, other_first_grad=observed.pop("first_moment"),
                                 other_scale=observed.pop("first_moment_scale"), log=lambda line: print(line, flush=True))
    observed["reference_s"] = time.perf_counter() - t0
    observed["compared"] = judged_with_routing(observed, want, cell.spec["limits"], shape, tokens)
    print("[train] read and not held (routing rows the cell's file gives no limit): "
          + json.dumps([row for row in routing_gaps(observed, want, tokens) if limit_name(row["name"]) not in cell.spec["limits"]]), flush=True)
    try:
        print("[train] read and not held (the first followed step's choices, the program's forward pass once more from the seeded weights): "
              + json.dumps(choice_gap(model, shape, ctx.weights_seed, like, observed["first_batches"][0], want["loads"][0])), flush=True)
    except Exception as error:  # a reading, not a limit: a failure here costs the line and nothing else
        print(f"[train] the choices' reading failed: {type(error).__name__}: {error}", flush=True)
    del model
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
