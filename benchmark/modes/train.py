"""Train mode: the program's normal training path, timed over a window of whole steps.

Set-up builds one object — the program's compiled train step with its state
(`Main.build_components`, `Main.build_step_functions`) — gives it the benchmark's
seeded weights, and starts ONE `Trainer.train` call with the program's `DeviceFeeder`
live and a new packed batch every step. The first steps of that call are the set-up
(compile, the three steps the reference follows, a few timed warm-up steps); the same
call, object and feed then run the window. A window in seconds becomes a count of
steps when the last warm-up step's metrics arrive: target = seconds / median warm step,
and a wrapper round the loader ends the epoch after that many more batches, so the
trainer stops through its ordinary "loader exhausted" exit — no stop request, hence
no forced checkpoint. The trainer publishes every step's metrics (log interval 1)
right after fetching them, with the next step already in flight; a subscriber stamps
those publishes on the host's clock, and the window runs from the stamp of the last
warm-up step to the stamp of the last step.

`correct`: the reference (benchmark/reference/dense_decoder_f32.py) follows the first
three steps from the same seeded weights on the same rows, after the window has closed
and the program's state is freed. Compared, each against its limit in the cell's file:
the loss of each of the three steps; the first gradient as the optimizer got it (from
Adam's first moment after one step): its norm and its distance from the reference's,
each by the worst leaf; the parameters' change after
the three steps, by the worst leaf; and the rise of the loss from the window's first
quarter of steps to its last (mean against mean), which a sound run keeps below zero.
"""

from __future__ import annotations

import math
import os
import time
from pathlib import Path

import numpy as np
import yaml

CHECK_STEPS = 3  # the steps the reference follows


# ------------------------------------------------------------------ pieces


class LimitedLoader:
    """The program's train loader, ending its epoch after `stop_after` batches once
    that is set, and remembering the first `keep` batches as the host made them."""

    def __init__(self, loader, keep: int, sample_key: str, target_key: str):
        self._loader = loader
        self._keep, self._sample_key, self._target_key = keep, sample_key, target_key
        self.stop_after: int | None = None
        self.handed = 0
        self.first: list[tuple[np.ndarray, np.ndarray]] = []

    def __iter__(self):
        for batch in self._loader:
            if self.stop_after is not None and self.handed >= self.stop_after:
                return
            if len(self.first) < self._keep:
                self.first.append((np.array(batch.samples[self._sample_key]), np.array(batch.targets[self._target_key])))
            self.handed += 1
            yield batch

    def __len__(self):
        return len(self._loader)

    def __getattr__(self, name):
        return getattr(self._loader, name)


class StepWatcher:
    """Subscriber to the trainer's published intervals (one step each). Stamps each on
    the host's clock, turns the window's seconds into a step target at the last warm-up
    step, and opens and closes the profiler inside the window when asked."""

    def __init__(self, loader: LimitedLoader, warm_steps: int, seconds: float, trace_dir: Path | None,
                 trace_after: int, trace_steps: int):
        self.loader, self.warm_steps, self.seconds = loader, warm_steps, seconds
        self.trace_dir, self.trace_after, self.trace_steps = trace_dir, trace_after, trace_steps
        self.done_at: dict[int, float] = {}
        self.loss: dict[int, float] = {}
        self.host_stall_s: dict[int, float] = {}
        self.target_steps: int | None = None
        self.trace_window: tuple[float, float] | None = None
        self._trace_t0: float | None = None

    def consume_message(self, message) -> None:
        now = time.perf_counter()
        result = message.payload
        step = int(result.num_train_steps_done)
        self.done_at[step] = now
        self.loss[step] = float(np.asarray(result.losses["train loss avg"].value))
        self.host_stall_s[step] = float(np.asarray(result.throughput_metrics["host stall [s]"].value))
        if step == self.warm_steps:
            gaps = [self.done_at[k] - self.done_at[k - 1] for k in range(CHECK_STEPS + 2, step + 1)]
            self.warm_step_s = _median(gaps)
            self.target_steps = max(4, round(self.seconds / self.warm_step_s))
            self.loader.stop_after = self.warm_steps + self.target_steps
        if self.trace_dir is not None:
            if step == self.warm_steps + self.trace_after:
                from benchmark import xtrace

                xtrace.start_profiler(self.trace_dir)
                self._trace_t0 = time.perf_counter()
            elif step >= self.warm_steps + self.trace_after + self.trace_steps:
                self.close()

    def close(self) -> None:
        """Close the profiler if it is open (also when the run ended inside the trace window)."""
        if self._trace_t0 is not None:
            import jax

            jax.profiler.stop_trace()
            self.trace_window = (self._trace_t0, time.perf_counter())
            self._trace_t0 = None


class _Silent:
    def consume_message(self, message) -> None:
        pass


def _mean(values) -> float:
    return sum(values) / len(values)


def _median(values) -> float:
    return sorted(values)[len(values) // 2]


def learning_rate(scheduler: dict, step: int) -> float:
    """`linear_warmup_cosine_annealing_lr` as the recipe's scheduler block states it."""
    c = scheduler["config"]
    if scheduler["variant_key"] != "linear_warmup_cosine_annealing_lr":
        raise SystemExit(f"benchmark: no formula for scheduler {scheduler['variant_key']!r}")
    warm, total = int(c["warmup_steps"]), int(c["total_steps"])
    if step < warm:
        return c["initial_lr"] + (c["max_lr"] - c["initial_lr"]) * step / max(1, warm)
    frac = min(max((step - warm) / max(1, total - warm), 0.0), 1.0)
    return c["final_lr"] + 0.5 * (c["max_lr"] - c["final_lr"]) * (1 + math.cos(math.pi * frac))


def hyperparameters(raw: dict) -> dict:
    opt = raw["optimizer"]["config"]
    return {
        "lr": [learning_rate(raw["scheduler"], step) for step in range(CHECK_STEPS)],
        "b1": float(opt["betas"][0]), "b2": float(opt["betas"][1]), "eps": float(opt["eps"]),
        "weight_decay": float(opt["weight_decay"]), "clip_norm": float(raw["gradient_clipper"]["config"]["max_norm"]),
    }


def reference_layout(program_params) -> dict:
    """The program's parameter tree, renamed to the reference's layout (no copy)."""
    p = program_params["params"]
    block = p["blocks"]["block"]
    layers = {name: block["attn"][name]["kernel"] for name in ("q_attn", "k_attn", "v_attn", "c_proj")}
    layers.update({name: block["mlp"][name]["kernel"] for name in ("W", "V", "W_2")})
    layers["attention_norm"] = block["attention_norm"]["scale"]
    layers["ffn_norm"] = block["ffn_norm"]["scale"]
    return {"layers": layers, "wte": p["wte"], "lm_head": p["lm_head"]["kernel"], "final_norm": p["lm_head_norm"]["scale"]}


def adam_first_moment(opt_state):
    import jax

    is_adam = lambda x: hasattr(x, "mu") and hasattr(x, "nu")  # noqa: E731
    found = [x for x in jax.tree.leaves(opt_state, is_leaf=is_adam) if is_adam(x)]
    if len(found) != 1:
        raise SystemExit(f"benchmark: expected one Adam state in the optimizer state, found {len(found)}")
    return found[0].mu


def worst_leaf_gap(program: dict, reference: dict) -> tuple[float, str]:
    """Largest |program norm - reference norm| over leaves (one per layer for stacked
    leaves), against the reference's norm of that leaf or of the median leaf, whichever
    is larger: some gradients are all but zero."""
    gaps = {k: np.abs(np.asarray(program[k], np.float64) - np.asarray(reference[k], np.float64)) for k in reference}
    errors = leaf_errors(gaps, reference)
    return errors["worst"], errors["worst_leaf"]


def leaf_errors(difference: dict, reference: dict) -> dict:
    """Norm of (program - reference) for every leaf against the reference's norm of that
    leaf or of the median leaf, whichever is larger; `pooled` is the norm of the whole
    difference against the norm of the whole reference."""
    flat = lambda tree: np.concatenate([np.atleast_1d(np.asarray(tree[k], np.float64)) for k in sorted(tree)])  # noqa: E731
    names = [f"{k}[{i}]" if np.ndim(reference[k]) else k for k in sorted(reference) for i in range(np.size(reference[k]))]
    diff, ref = flat(difference), flat(reference)
    errors = diff / np.maximum(ref, float(np.median(ref)))
    if not np.all(np.isfinite(errors)):
        return {"worst": math.inf, "worst_leaf": names[int(np.argmax(~np.isfinite(errors)))], "median": math.inf, "pooled": math.inf}
    return {"worst": float(errors.max()), "worst_leaf": names[int(errors.argmax())], "median": float(np.median(errors)),
            "pooled": float(np.sqrt((diff**2).sum() / (ref**2).sum()))}


def compare(program: dict, reference: dict, limits: dict) -> list[dict]:
    """The numbers `correct` rests on, each beside its limit."""
    rows = []
    for i, (got, want) in enumerate(zip(program["losses"], reference["losses"])):
        gap = abs(got - want) / abs(want) if math.isfinite(got) else math.inf
        rows.append({"name": f"loss_step{i + 1}_rel_gap", "value": gap, "limit": limits["loss_rel_gap"],
                     "program": got, "reference": want})
    gap, where = worst_leaf_gap(program["first_grad_norms"], reference["first_grad_norms"])
    rows.append({"name": "first_grad_norm_worst_leaf_rel_gap", "value": gap, "limit": limits["grad_norm_rel_gap"], "leaf": where})
    errors = leaf_errors(reference["first_grad_difference_norms"], reference["first_grad_norms"])
    rows.append({"name": "first_grad_worst_leaf_rel_error", "value": errors["worst"], "limit": limits["grad_rel_error"], **errors})
    gap, where = worst_leaf_gap(program["delta_norms"], reference["delta_norms"])
    rows.append({"name": "param_change_norm_worst_leaf_rel_gap", "value": gap, "limit": limits["param_change_rel_gap"], "leaf": where})
    rise = program["loss_end"] - program["loss_start"]
    rows.append({"name": "loss_rise_over_window", "value": rise if math.isfinite(rise) else math.inf,
                 "limit": limits["loss_rise_over_window"], "start": program["loss_start"], "end": program["loss_end"]})
    for row in rows:
        row["ok"] = bool(row["value"] <= row["limit"])
    return rows


# ------------------------------------------------------------------ the run


def build_program(cell, seed: int, scratch: Path, shape):
    """The program's components and its compiled step with its state, holding the
    benchmark's seeded weights. `scratch` becomes the working directory: the YAML's
    paths are relative, as in the recipe."""
    import jax

    from modalities_tpu.main import Main

    from benchmark.weights import make_program_tree

    os.chdir(scratch)
    main = Main(cell.yaml_path, experiment_id="bench")
    components = main.build_components()
    fns = Main.build_step_functions(components)
    state = fns.app_state_handle.state
    fns.app_state_handle.state = state.replace(params=make_program_tree(shape, seed, state.params))
    del state
    jax.block_until_ready(fns.app_state_handle.state.params)
    return components, fns


def drive(ctx, components, fns, raw: dict, shape) -> dict:
    """One `Trainer.train` call: set-up steps, then the window. Returns what was observed."""
    import jax
    import jax.numpy as jnp

    from modalities_tpu.logging_broker.message_broker import MessageBroker
    from modalities_tpu.logging_broker.messages import MessageTypes
    from modalities_tpu.logging_broker.publisher import MessagePublisher
    from modalities_tpu.telemetry import Telemetry, set_active_telemetry
    from modalities_tpu.trainer import Trainer
    from modalities_tpu.training.training_progress import TrainingProgress

    from benchmark.reference.dense_decoder_f32 import leaf_norms
    from benchmark.weights import program_tree, seed_key

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
    telemetry = Telemetry()
    previous = set_active_telemetry(telemetry)
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
        elif step == CHECK_STEPS:
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
        set_active_telemetry(previous)

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
        "first_grad": jax.tree.map(lambda m: np.asarray(m, np.float32) / (1 - b1), snapshots["first_moment"]),
        "first_batches": loader.first, "trace_window": watcher.trace_window,
    }


def program_memory(fns, first_batch, keys: dict) -> int:
    """The compiled step's own peak by `memory_analysis()` (a cache hit by now)."""
    from benchmark.device import program_peak_bytes

    tokens, targets = first_batch
    host = {"samples": {keys["sample_key"]: tokens[None]}, "targets": {keys["target_key"]: targets[None]}}
    return program_peak_bytes(fns.lower_train_step(fns.put_batch(host, has_acc_dim=True)).compile())


def free(fns) -> None:
    """Give the device back before the reference runs."""
    import jax

    for leaf in jax.tree.leaves(fns.app_state_handle.state):
        leaf.delete()


def run(ctx) -> dict:
    from benchmark.device import live_peak_bytes
    from benchmark.reference import dense_decoder_f32 as reference
    from benchmark.weights import DecoderShape

    cell = ctx.cell
    if cell.chips != 1:
        raise SystemExit("benchmark: train mode drives one chip; a mesh of several needs a mode of its own (PERF.md section 7)")
    raw = yaml.safe_load(cell.yaml_path.read_text())
    shape = DecoderShape.from_model_config(raw["model_raw"]["config"])
    sequence_length = int(raw["settings"]["step_profile"]["sequence_length"])
    generator = cell.module("traffic", cell.traffic["generator"])
    written = generator.generate(cell.traffic, ctx.seed, ctx.scratch / "data" / "train.pbin",
                                 vocab_size=shape.vocab_size, sequence_length=sequence_length)
    print(f"[train] corpus from seed {ctx.seed}: {written}", flush=True)

    components, fns = build_program(cell, ctx.seed, ctx.scratch, shape)
    observed = drive(ctx, components, fns, raw, shape)
    slowest = sorted(observed["step_seconds"], reverse=True)[:3]
    print(f"[train] {observed['steps_in_window']} steps in the window, median {_median(observed['step_seconds']) * 1e3:.2f} ms; "
          f"the three slowest took {[round(s * 1e3, 1) for s in slowest]} ms (a step far over the median is the machine "
          "or the host standing still, and explains a run that reads far off)", flush=True)
    observed["memory_peak_bytes"] = max(
        live_peak_bytes(), program_memory(fns, observed["first_batches"][0], raw["settings"]["referencing_keys"]))
    free(fns)
    del components, fns

    t0 = time.perf_counter()
    want = reference.train_steps(shape, ctx.seed, observed["first_batches"], hyperparameters(raw),
                                 other_first_grad=observed.pop("first_grad"))
    observed["reference_s"] = time.perf_counter() - t0
    observed["compared"] = compare(observed, want, cell.spec["limits"])
    observed["shape"] = shape
    micro_batch = int(raw["settings"]["step_profile"]["local_train_micro_batch_size"])
    observed["run"] = {  # what the one chip holds of a step: the shape functions' arguments
        "sequence_length": sequence_length, "rows_per_chip": micro_batch,
        "q_heads_per_chip": shape.n_head_q, "kv_heads_per_chip": shape.n_head_kv,
        "ce_rows_per_chip": micro_batch * sequence_length, "vocab_per_chip": shape.vocab_size,
    }
    observed["tokens_per_s"] = observed["steps_in_window"] * observed["tokens_per_step"] / (
        observed["window"][1] - observed["window"][0])
    observed["end_to_end"] = {"train_tokens_per_s": observed["tokens_per_s"]}
    return observed
