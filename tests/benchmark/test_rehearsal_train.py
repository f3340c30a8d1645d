"""Train mode rehearsed at toy size on the CPU through the harness's own functions: the
whole of a run but the look for a chip. Then the same with the timed path broken
underneath (a step that leaves half its batch out; a step that returns its state
unchanged), which has to come out not correct;
and the control of "How `correct` is decided" at toy size: the reference in int8 in the
program's place has to fail the comparison that the sound program passes.

Nothing here is a measurement: a CPU run says whether the control flow is right."""

import json
import math

import pytest

from benchmark import run as bench_run
from benchmark.device import device_info
from benchmark.manifest import load_cell
from tests.benchmark.toy import make_toy_root

CELL = "train-2p7b-4k"
SEED = 2**31 + 5  # the driver's seeds pass 32 signed bits
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
# toy limits, read on the CPU (PR 23). The one the control has to fail is the first gradient's
# distance from the reference's: 0.0077-0.0080 for the sound program, 0.0166 for int8 weights.
TOY_LIMITS = {"loss_rel_gap": 1e-3, "grad_norm_rel_gap": 0.01, "grad_rel_error": 0.012, "param_change_rel_gap": 0.5, "loss_rise_over_window": 0.05}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = make_toy_root(tmp_path_factory.mktemp("toy"))
    path = root / "benchmark" / "workloads" / f"{CELL}.json"
    path.write_text(json.dumps({**json.loads(path.read_text()), "limits": TOY_LIMITS}))
    return root


def on_the_cpu(chips: int) -> dict:
    return device_info()


@pytest.fixture(scope="module")
def sound(root):
    return bench_run.execute(CELL, SEED, 0.4, trace=False, root=root, device_gate=on_the_cpu)


def test_result_has_the_contracts_keys_and_the_cells_end_to_end_metrics(sound):
    assert set(sound) == RESULT_KEYS
    assert set(sound["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert sound["metrics"]["train_tokens_per_s"]["unit"] == "tokens/s" and sound["metrics"]["setup_s"]["unit"] == "s"
    assert all(math.isfinite(m["value"]) and m["value"] > 0 for m in sound["metrics"].values())
    assert set(sound["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}
    json.dumps(sound)


def test_sound_run_is_correct_and_counts_whole_steps(sound):
    assert sound["correct"] is True
    assert sound["attempted"] >= 4 and sound["failed"] == 0


def test_traced_run_reports_the_per_layer_metrics_it_can_read(root):
    traced = bench_run.execute(CELL, SEED + 1, 0.4, trace=True, root=root, device_gate=on_the_cpu)
    # no TPU plane in a CPU trace and no peak for a CPU: only the counted metrics are there
    assert set(traced["metrics"]) == {"train_host_stall_pct", "train_step_ms"}
    assert "breakdown" not in traced and "busy_s" not in traced["device"]


def half_a_batch(step):
    """Every row becomes the first: the rest of the batch is left out."""

    def broken(state, batch):
        batch = {part: {k: v.at[:, 1:].set(v[:, :1]) for k, v in batch[part].items()} for part in ("samples", "targets")}
        return step(state, batch)

    return broken


def state_unchanged(step):
    """The step computes its metrics and hands back the state it was given."""
    import jax
    import jax.numpy as jnp

    def broken(state, batch):
        kept = jax.tree.map(jnp.copy, state)  # the step donates its argument
        _, metrics = step(state, batch)
        return kept, metrics

    return broken


@pytest.mark.parametrize("fault", [half_a_batch, state_unchanged])
def test_a_run_with_the_timed_path_broken_underneath_is_not_correct(root, monkeypatch, fault):
    from modalities_tpu.main import Main

    build = Main.build_step_functions

    def broken(components, *args, **kwargs):
        fns = build(components, *args, **kwargs)
        fns.train_step = fault(fns.train_step)
        return fns

    monkeypatch.setattr(Main, "build_step_functions", staticmethod(broken))
    result = bench_run.execute(CELL, SEED, 0.4, trace=False, root=root, device_gate=on_the_cpu)
    assert result["correct"] is False


def test_the_int8_control_fails_where_the_program_passes(root):
    """The control at a size a test run can hold: the reference with int8 weights in the
    program's place, on the same rows. On the chip it ran at the cell's own size on three
    seeds (benchmark/tools/control.py; readings in PERF.md section 2)."""
    import numpy as np
    import yaml

    from benchmark.reference import dense_decoder_f32 as reference
    from benchmark.weights import DecoderShape

    cell = load_cell(CELL, root)
    train = cell.module("modes", "train")
    raw = yaml.safe_load(cell.yaml_path.read_text())
    shape = DecoderShape.from_model_config({**raw["model_raw"]["config"], "sequence_length": 128})
    rng = np.random.default_rng(3)
    batches = []
    for _ in range(train.CHECK_STEPS):
        stream = rng.integers(0, shape.vocab_size - 1, size=(2, 129))
        batches.append((stream[:, :-1], stream[:, 1:]))
    hyper = train.hyperparameters(raw)
    control = reference.train_steps(shape, SEED, batches, hyper, precision="int8", keep_first_grad=True)
    want = reference.train_steps(shape, SEED, batches, hyper, other_first_grad=control.pop("first_grad"))
    control.update(loss_start=0.0, loss_end=0.0)
    judged = {row["name"]: row for row in train.compare(control, want, TOY_LIMITS)}
    assert not judged["first_grad_worst_leaf_rel_error"]["ok"], judged
    # the norms int8 weights leave all but untouched: they are there for other faults
    assert judged["first_grad_norm_worst_leaf_rel_gap"]["ok"] and judged["param_change_norm_worst_leaf_rel_gap"]["ok"]
