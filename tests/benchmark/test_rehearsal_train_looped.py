"""Mode `train_looped` rehearsed at toy size on the CPU through the harness's own functions: the whole of a run of
the cell `train-ouro-2p6b-4k` but the look for a chip. The same with the timed path broken underneath is
test_rehearsal_train_looped_broken.py (a file of its own, so that the two files run side by side); here also the
control at toy size: the reference on int8 kernels in the program's place has to fail the comparison that the sound
program passes.

Nothing here is a measurement: a CPU run says whether the control flow is right."""

import json
import math

import pytest

from benchmark import run as bench_run
from benchmark.device import device_info
from benchmark.manifest import load_cell
from tests.benchmark.accepted import holds_at_least
from tests.benchmark.toy_looped import CELL, make_toy_looped_root

SEED = 2**31 + 5  # the driver's seeds pass 32 signed bits
# toy limits, read on the CPU (PR 32; three sound seeds, two control; the gate seeded at 0, as the cell's). The rows the
# control has to fail are the first gradient's distance from the reference's: 0.0137-0.0174 on the worst leaf and
# 0.0117-0.0156 pooled for the sound program, 0.0471-0.0536 and 0.0374-0.0445 for int8 kernels; each limit at the geometric
# mean of the nearest two readings. The other rows at toy size: loss gaps to 6e-5, every exit's cross entropy to 9e-5 (control
# 2e-4), the expected exit to 6e-6 exits (the gate has moved by one step of the optimizer; control 1.2e-4), the parameters'
# change 0.046-0.051 (a state left unchanged reads 1).
TOY_LIMITS = {"loss_rel_gap": 1e-3, "grad_norm_rel_gap": 0.05, "grad_rel_error": 0.0286, "grad_pooled_rel_error": 0.0241,
              "param_change_rel_gap": 0.5, "exit_ce_rel_gap": 1e-3, "expected_exit_gap": 0.02, "loss_rise_over_window": 0.05}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = make_toy_looped_root(tmp_path_factory.mktemp("toy_looped"))
    path = root / "benchmark" / "workloads" / f"{CELL}.json"
    path.write_text(json.dumps({**json.loads(path.read_text()), "limits": TOY_LIMITS}))
    return root


def on_the_cpu(chips: int) -> dict:
    return device_info()


@pytest.fixture(scope="module")
def sound(root):
    return bench_run.execute(CELL, SEED, 0.4, trace=False, root=root, device_gate=on_the_cpu)


def test_sound_run_is_correct_and_reports_the_cells_end_to_end_metrics(sound):
    assert sound["correct"] is True and sound["attempted"] >= 4 and sound["failed"] == 0
    assert set(sound["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert all(math.isfinite(m["value"]) and m["value"] > 0 for m in sound["metrics"].values())
    assert set(sound["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}
    json.dumps(sound)


def test_a_traced_run_reports_what_a_cpu_can_read_and_leaves_the_rest_out(root):
    """No device trace and no peak here: the line holds the counters and the host's clock, and no scope time or share."""
    traced = bench_run.execute(CELL, SEED + 1, 0.4, trace=True, root=root, device_gate=on_the_cpu)
    assert traced["correct"] is True
    assert {"train_step_ms", "train_host_stall_pct", "loop_expected_exit"} <= set(traced["metrics"]) and "setup_s" not in traced["metrics"]
    assert 1.0 <= traced["metrics"]["loop_expected_exit"]["value"] <= 4.0 and traced["metrics"]["loop_expected_exit"]["unit"] == "exits"
    assert not [name for name in traced["metrics"] if "mfu" in name or "roofline" in name]


def test_the_cell_reads_its_own_rules_file_and_the_accepted_shares_of_a_peak(root):
    cell = load_cell(CELL, root)
    assert cell.mode == "train_looped" and cell.chips == 1 and cell.end_to_end == ("train_tokens_per_s", "setup_s")
    assert holds_at_least(cell.per_layer, {"train_host_stall_pct", "train_step_ms", "fused_ce_roofline", "flash_attention_roofline", "device_idle_pct.train",
                                           "train_looped_fwd_ms", "train_looped_bwd_ms", "train_looped_optimizer_ms", "train_looped_attn_ms",
                                           "train_looped_mlp_ms", "train_looped_norms_ms", "train_looped_head_loss_ms", "train_looped_loop_carry_ms",
                                           "train_looped_unattributed_pct", "train_looped_mfu_pct", "loop_expected_exit"})
    assert {cell.metric_spec(name)["rules"] for name in cell.per_layer if cell.metric_spec(name)["reader"] == "scope_time"} == {"train_looped"}


def test_the_program_counter_reaches_the_observed_metric(root):
    cell = load_cell(CELL, root)
    spec = cell.metric_spec("loop_expected_exit")
    assert cell.module("readers", spec["reader"]).read(spec, {"loop_expected_exit": [1.9, 2.1, 2.0]}, None, {}) == 2.0
    assert cell.module("readers", spec["reader"]).read(spec, {}, None, {}) is None, "a program without the counter: nothing, and no error"


def test_the_int8_control_fails_where_the_program_passes(root):
    """The control at a size a test run can hold: the reference with int8 kernels in the program's place, on the same
    rows. On the chip it ran at the cell's own size (benchmark/tools/control_looped.py; readings in PERF.md section 2)."""
    import numpy as np
    import yaml

    from benchmark.reference import looped_decoder_f32 as reference
    from benchmark.weights_looped import LoopedShape

    cell = load_cell(CELL, root)
    mode = cell.module("modes", "train_looped")
    raw = yaml.safe_load(cell.yaml_path.read_text())
    shape = LoopedShape.from_yaml(raw)
    rng = np.random.default_rng(3)
    batches = []
    for _ in range(mode.CHECK_STEPS):
        stream = rng.integers(0, shape.vocab_size - 1, size=(2, 129))
        batches.append((stream[:, :-1], stream[:, 1:]))
    hyper = mode.hyperparameters(raw)
    hyper["lr"] = hyper["lr"][: mode.CHECK_STEPS]
    control = reference.train_steps(shape, SEED, batches, hyper, precision="int8", keep_first_grad=True)
    want = reference.train_steps(shape, SEED, batches, hyper, other_first_grad=control.pop("first_grad"))
    control.update(loss_start=0.0, loss_end=0.0)
    judged = {row["name"]: row for row in mode.judged_with_exits(control, want, TOY_LIMITS)}
    assert not judged["first_grad_worst_leaf_rel_error"]["ok"] and not judged["first_grad_pooled_rel_error"]["ok"], judged
    assert judged["param_change_norm_worst_leaf_rel_gap"]["ok"] and judged["exit_ce_step1_rel_gap"]["ok"] and judged["expected_exit_step1_gap"]["ok"], judged
