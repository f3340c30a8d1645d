"""The mesh mode rehearsed at toy size on four of the CPU's virtual devices, through the harness's own functions: the whole
of a run but the look for the chips, under `dp_shard 2 x tp 2`. Then the cell's own fault, a gradient that is not reduced
over `dp_shard` (each group stepping on its own rows: `benchmark/tools/control_mesh.py` puts it under the program), which
has to come out not correct; the int8 control in the program's place, which has to fail the comparison the sound program
passes; and the reference spread over four devices against the same reference on one.

Nothing here is a measurement: a CPU run says whether the control flow is right."""

import json
import math

import numpy as np
import pytest
import yaml

from benchmark import run as bench_run
from benchmark.device import device_info
from benchmark.manifest import load_cell, load_module
from tests.benchmark.toy import REPO, make_toy_root

CELL = "train-2p7b-4k-x4"
SEED = 2**31 + 9  # the driver's seeds pass 32 signed bits
# toy limits, read on the CPU (PR 50): the first gradient's distance from the reference's is 0.0085 for the sound program
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


def test_a_sound_run_over_the_mesh_is_correct_and_reports_the_cells_end_to_end_metrics(sound):
    assert set(sound) == {"correct", "attempted", "failed", "metrics", "device"}
    assert set(sound["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert all(math.isfinite(m["value"]) and m["value"] > 0 for m in sound["metrics"].values())
    assert sound["correct"] is True and sound["attempted"] >= 4 and sound["failed"] == 0
    json.dumps(sound)


def test_a_traced_run_reports_what_a_cpu_can_read_and_none_of_the_mesh_metrics(root):
    traced = bench_run.execute(CELL, SEED + 1, 0.4, trace=True, root=root, device_gate=on_the_cpu)
    # no TPU plane in a CPU trace, no peak for a CPU, and the preflight that records the plan acts only where the backend
    # states a limit: the counted metrics alone
    assert set(traced["metrics"]) == {"train_host_stall_pct", "train_step_ms"} and traced["correct"] is True


def test_a_gradient_not_reduced_over_dp_shard_is_not_correct(root, monkeypatch):
    from modalities_tpu.main import Main

    tool = load_module(REPO, "tools", "control_mesh")
    build = Main.build_step_functions

    def broken(components, *args, **kwargs):
        fns = build(components, *args, **kwargs)
        fns.train_step = tool.first_groups_rows(fns.train_step, groups=2)
        return fns

    monkeypatch.setattr(Main, "build_step_functions", staticmethod(broken))
    result = bench_run.execute(CELL, SEED, 0.4, trace=False, root=root, device_gate=on_the_cpu)
    assert result["correct"] is False


def toy_batches(shape, steps: int):
    rng = np.random.default_rng(3)
    streams = [rng.integers(0, shape.vocab_size - 1, size=(2, 129)) for _ in range(steps)]
    return [(stream[:, :-1], stream[:, 1:]) for stream in streams]


@pytest.fixture(scope="module")
def toy(root):
    from benchmark.weights import DecoderShape

    cell = load_cell(CELL, root)
    mode = cell.module("modes", "train_mesh")
    raw = yaml.safe_load(cell.yaml_path.read_text())
    shape = DecoderShape.from_model_config({**raw["model_raw"]["config"], "sequence_length": 128})
    return mode, shape, mode.hyperparameters(raw)


def test_the_int8_control_fails_where_the_program_passes(toy):
    import jax

    from benchmark.reference import dense_decoder_f32_mesh as reference

    mode, shape, hyper = toy
    batches, devices = toy_batches(shape, mode.CHECK_STEPS), jax.devices()[:4]
    control = reference.train_steps(shape, SEED, batches, hyper, devices, precision="int8", keep_first_grad=True)
    want = reference.train_steps(shape, SEED, batches, hyper, devices, other_first_grad=control.pop("first_grad"))
    control.update(loss_start=0.0, loss_end=0.0)
    judged = {row["name"]: row for row in mode.compare(control, want, TOY_LIMITS)}
    assert not judged["first_grad_worst_leaf_rel_error"]["ok"], judged
    assert judged["first_grad_norm_worst_leaf_rel_gap"]["ok"] and judged["param_change_norm_worst_leaf_rel_gap"]["ok"]


def test_the_reference_over_four_devices_is_the_reference_on_one(toy):
    """Two steps without a moment kept, the arrays split over four devices, against `dense_decoder_f32.train_steps` with
    Adam's moments on one: the same losses, gradient and parameter change to float32's rounding."""
    import jax

    from benchmark.reference import dense_decoder_f32, dense_decoder_f32_mesh

    mode, shape, hyper = toy
    batches = toy_batches(shape, 2)
    one = dense_decoder_f32.train_steps(shape, SEED, batches, hyper, keep_first_grad=True)
    four = dense_decoder_f32_mesh.train_steps(shape, SEED, batches, hyper, jax.devices()[:4], other_first_grad=one["first_grad"])
    assert four["losses"] == pytest.approx(one["losses"], rel=1e-6)
    for name in one["first_grad_norms"]:
        assert np.asarray(four["first_grad_norms"][name]) == pytest.approx(np.asarray(one["first_grad_norms"][name]), rel=1e-4), name
        assert np.asarray(four["delta_norms"][name]) == pytest.approx(np.asarray(one["delta_norms"][name]), rel=1e-3), name
        assert np.all(np.asarray(four["first_grad_difference_norms"][name]) <= 1e-4 * np.maximum(np.asarray(one["first_grad_norms"][name]), 1e-6)), name
    split = dense_decoder_f32_mesh.shardings(shape, dense_decoder_f32_mesh.mesh_of(jax.devices()[:4]))
    assert str(split["layers"]["W"].spec) == "PartitionSpec(None, None, 'chips')" and str(split["wte"].spec) == "PartitionSpec('chips',)"
    assert split["layers"]["k_attn"].spec == split["final_norm"].spec  # 2 kv heads over 4 devices: whole, like a norm's scale
