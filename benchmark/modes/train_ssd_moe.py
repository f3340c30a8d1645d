"""Train mode for a decoder of Mamba-2 and NoPE-attention layers with a softmax-routed expert layer beside an
ungated shared expert in every block, four scalar multipliers and a tied table (`model_type: granitemoehybrid`):
`benchmark/modes/train_gdn_moe.py` with another layout, shape and reference.

The run is the gated-delta-rule cell's, piece for piece: the program's compiled train step with its state, given
the benchmark's seeded weights (`benchmark/weights_ssd_moe.py`), ONE `Trainer.train` call with the `DeviceFeeder`
live and a new packed batch every step, the window counted in whole steps from the trainer's published intervals.
What differs:

- the reference (`benchmark/reference/ssd_moe_decoder_f32.py`) walks the recurrence position by position, where
  the program runs its chunked form; it follows the first TWO steps, its loss holding the balance term as the
  configuration weighs it.
- the step's counters are five: the window-and-global cell's four and `ssd_decay_mean` (the Mamba-2 layers' mean
  `exp(a)`); the window's steps give `ssd_decay_mean` beside the three routing metrics.
- after the rows the run prints readings that decide nothing: the second followed step's routing gaps; of every
  layer the least share of (token, choice) pairs whose expert differs from the reference's on the first followed
  step; and the distance of the first Mamba-2 layer's output from the reference's BY POSITION, the first and the
  last 1,024 of the row beside the whole, so that a drift along the row cannot hide in a limit set on the whole.
- the run prints the plans emitted while the step was traced (`ssd_plan`, `moe_dispatch_plan`, `flash_tile_plan`,
  `fused_ce_plan`).

A program that has no such layer (the parent of the PR that added this mode) fails at once, when its config
factory refuses the model block's keys, and the run then takes its scratch directory with it: the checkout is left
as it was found.
"""

from __future__ import annotations

import os
import shutil
import time
from pathlib import Path

import numpy as np
import yaml

from benchmark.modes import train_swa_moe as base
from benchmark.modes.train import (LimitedLoader, _mean, _median, _Silent, adam_first_moment, free, hyperparameters,
                                   program_memory)
from benchmark.modes.train_hybrid import SLOW_COMPILE_S, sink_events
from benchmark.modes.train_swa_moe import by_kind_of_leaf, judged_with_routing, routing_gaps  # noqa: F401  (the control reads them here)

CHECK_STEPS = 2  # the steps the reference follows
PLANS = ("ssd_plan", "moe_dispatch_plan", "flash_tile_plan", "fused_ce_plan")
SSD_COUNTERS = ("ssd_decay_mean",)
EDGE = 1024  # positions at each end of the row whose distance is printed apart


class CountingWatcher(base.CountingWatcher):
    """The window-and-global mode's watcher, keeping also the Mamba-2 layers' counter of each published step."""

    def consume_message(self, message) -> None:
        result = message.payload
        missing = [name for name in SSD_COUNTERS if name not in result.metrics]
        if missing:
            raise SystemExit(f"benchmark: the trainer published no {missing}: the Mamba-2 layers' counter did not reach its metrics")
        step = int(result.num_train_steps_done)
        super().consume_message(message)
        self.counters[step].update({name: float(np.asarray(result.metrics[name].value)) for name in SSD_COUNTERS})


def build_program(cell, seed: int, scratch: Path, shape):
    """The program's components and its compiled step with its state, holding the benchmark's seeded weights.
    `scratch` becomes the working directory."""
    import jax

    from modalities_tpu.main import Main

    from benchmark.weights_ssd_moe import make_program_tree

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

    from benchmark.reference.ssd_moe_decoder_f32 import leaf_norms
    from benchmark.weights_ssd_moe import program_tree, reference_layout, seed_key

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
        "pairs_held": counted("moe_pairs_held", followed), "aux_loss": counted("moe_aux_loss", followed),
        "grad_norm": [watcher.grad_norm[k] for k in followed],
        "warm_pairs_held": counted("moe_pairs_held", range(1, warm_steps + 1)),
        "window_pairs_held": counted("moe_pairs_held", window_steps), "window_aux_loss": counted("moe_aux_loss", window_steps),
        "window_ssd_decay_mean": counted("ssd_decay_mean", window_steps),
        "moe_load_max_over_mean": [watcher.counters[k]["moe_load_max"] / max(watcher.counters[k]["moe_load_mean"], 1e-9)
                                   for k in window_steps],
        "loss_start": _mean(window_losses[: max(1, len(window_losses) // 4)]),
        "loss_end": _mean(window_losses[-max(1, len(window_losses) // 4):]),
        "first_grad_norms": jax.device_get(snapshots["first_grad_norms"]),
        "delta_norms": jax.device_get(snapshots["delta_norms"]),
        "first_moment": snapshots["first_moment"], "first_moment_scale": 1.0 / (1 - b1),
        "first_batches": loader.first, "trace_window": watcher.trace_window, "first_step_at": watcher.done_at[1],
    }


def choice_gap(model, shape, seed: int, like, first_batch, reference_loads) -> dict:
    """The program's forward pass on the first followed batch from the seeded weights, once more: every layer's load by
    expert against the reference's own. A pair that went to another expert than the reference's takes one from an
    expert and gives one to another, so half the sum of the loads' differences over the experts is the LEAST number of
    a layer's (token, choice) pairs whose expert differs (moves that cancel are not seen). Returns the share by layer."""
    import jax

    from benchmark.weights_ssd_moe import make_program_tree

    params = make_program_tree(shape, seed, like)
    tokens, _ = first_batch
    _, counted = jax.jit(lambda p, ids: model.apply_counted(p, {model.sample_key: ids}, train=True, hidden=True))(params, tokens)
    got = np.asarray(jax.device_get(counted["moe_expert_load"]), np.float64)
    for leaf in jax.tree.leaves(params):
        leaf.delete()
    want = np.asarray(reference_loads, np.float64)
    pairs = tokens.size * shape.num_experts_per_tok
    return {"least_share_of_pairs_whose_expert_differs_by_layer": np.round(np.abs(got - want).sum(axis=1) / (2 * pairs), 5).tolist(),
            "pairs_on_held_experts_by_layer": {"program": got[:, shape.expert_offset: shape.expert_offset + shape.experts_held].sum(axis=1).tolist(),
                                               "reference": want[:, shape.expert_offset: shape.expert_offset + shape.experts_held].sum(axis=1).tolist()}}


def mixer_distance_by_position(spec, shape, seed: int, first_batch, reference) -> dict:
    """What the program's FIRST Mamba-2 layer's mixer gives on the first followed row, from the seeded weights and the
    embedded tokens (the program's own modules: its norm, `Mamba2Mixer` with the chunked form, in the compute dtype),
    against the reference's (float32, the walk): the norm of the difference over the norm of the reference's, on the
    row's first and last `EDGE` positions and on the whole row."""
    import jax
    import jax.numpy as jnp

    from modalities_tpu.models.components.layer_norms import build_norm
    from modalities_tpu.models.gpt2.ssd import Mamba2Mixer

    from benchmark.weights_ssd_moe import _program_mixer, embedding, layer_weights, seed_key

    layer = shape.kinds.index("ssd")
    tokens = jnp.asarray(first_batch[0], jnp.int32)[:1]
    compute = jnp.dtype(spec.compute_dtype)

    @jax.jit
    def program(key, row):
        w = layer_weights(shape, key, layer, "ssd")
        x = (jnp.take(embedding(shape, key), row, axis=0).astype(jnp.float32) * shape.embedding_multiplier).astype(compute)
        h = build_norm(spec.attn_norm, "attention_norm", dtype=compute).apply({"params": {"scale": w["attention_norm"]}}, x)
        return Mamba2Mixer(spec).apply({"params": _program_mixer(w, "ssd")}, h)[0][0].astype(jnp.float32)

    got = program(seed_key(seed), tokens)
    want = reference.first_mixer_output(shape, seed, tokens, layer)
    distance = jax.jit(lambda a, b: jnp.sqrt(jnp.sum((a - b) ** 2)) / jnp.sqrt(jnp.sum(b ** 2)))
    edge = min(EDGE, got.shape[0])
    return {"layer": layer, "first_positions": edge, "first": round(float(distance(got[:edge], want[:edge])), 6),
            "last": round(float(distance(got[-edge:], want[-edge:])), 6), "whole_row": round(float(distance(got, want)), 6)}


def run(ctx) -> dict:
    import json

    import jax

    from modalities_tpu.telemetry import Telemetry, set_active_telemetry

    from benchmark.device import live_peak_bytes
    from benchmark.reference import ssd_moe_decoder_f32 as reference
    from benchmark.weights_ssd_moe import SsdMoEShape

    cell = ctx.cell
    if cell.chips != 1:
        raise SystemExit("benchmark: train_ssd_moe mode drives one chip; a mesh of several (an `ep` axis with its exchange) needs a mode of its own")
    raw = yaml.safe_load(cell.yaml_path.read_text())
    shape = SsdMoEShape.from_yaml(raw)
    sequence_length = int(raw["settings"]["step_profile"]["sequence_length"])
    generator = cell.module("traffic", cell.traffic["generator"])
    written = generator.generate(cell.traffic, ctx.seed, ctx.scratch / "data" / "train.pbin",
                                 vocab_size=shape.vocab_size, sequence_length=sequence_length)
    print(f"[train] corpus from seed {ctx.seed}, weights from seed {ctx.weights_seed}: {written}; layers {''.join('m' if k == 'ssd' else 'a' for k in shape.kinds)} "
          f"(m: Mamba-2, {shape.heads_held} of {shape.heads} heads of {shape.head_dim} held, state {shape.state}, chunks of {shape.chunk}; a: attention "
          f"without positions, {shape.n_head_q} on {shape.n_head_kv} heads of {shape.attn_head_dim} of {shape.n_head_q_all} on {shape.n_head_kv_all}, "
          f"scores x {shape.attention_multiplier}; every layer {shape.experts_held} of {shape.n_routed_experts} experts held from {shape.expert_offset}, "
          f"{shape.num_experts_per_tok} a token, beside {shape.shared_hidden} of the shared expert's {shape.shared_width}; {shape.all_params():,} parameters)", flush=True)

    telemetry = Telemetry(output_folder_path=ctx.scratch / "telemetry")  # active while the step is traced: the plans land here
    previous = set_active_telemetry(telemetry)
    try:
        t0 = time.perf_counter()
        try:
            components, fns = build_program(cell, ctx.weights_seed, ctx.scratch, shape)
        except BaseException:
            # a program that cannot build this model (one with no such layer) ends here: it leaves the checkout
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
    for plan in (e for e in events if e.get("name") in PLANS):  # what the mixer, the dispatch and the kernels said of their shapes while traced
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
          f"balance term {_median(observed['window_aux_loss']):.4f} (median; 1 at balance); the Mamba-2 layers' mean decay "
          f"{_median(observed['window_ssd_decay_mean']):.5f}", flush=True)
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
    observed["compared"] = judged_with_routing(observed, want, cell.spec["limits"])
    print("[train] read and not held (the second followed step's routing): "
          + json.dumps([row for row in routing_gaps(observed, want) if "_step1_" not in row["name"]]), flush=True)
    for what, read in (("the first followed step's choices, the program's forward pass once more from the seeded weights",
                        lambda: choice_gap(model, shape, ctx.weights_seed, like, observed["first_batches"][0], want["loads"][0])),
                       ("the first Mamba-2 layer's output against the reference's, by position",
                        lambda: mixer_distance_by_position(model.config_spec, shape, ctx.weights_seed, observed["first_batches"][0], reference))):
        try:
            print(f"[train] read and not held ({what}): " + json.dumps(read()), flush=True)
        except Exception as error:  # a reading, not a limit: a failure here costs the line and nothing else
            print(f"[train] a reading failed ({what}): {type(error).__name__}: {error}", flush=True)
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
