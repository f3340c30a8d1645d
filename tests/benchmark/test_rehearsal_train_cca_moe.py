"""Mode `train_cca_moe` rehearsed at toy size on the CPU through the harness's own functions: the whole of a run of the
cell `train-zaya1-8b-8k` but the look for a chip. The same with the timed path broken underneath is
test_rehearsal_train_cca_moe_broken.py (a file of its own, so that the two files run side by side); here also the controls
at toy size: the reference on int8 kernels, and with each of five steps of the equations left out, in the program's place has
to fail the comparison that the sound program passes.

Nothing here is a measurement: a CPU run says whether the control flow is right."""

import dataclasses
import json
import math

import pytest

from benchmark import run as bench_run
from benchmark.device import device_info
from benchmark.manifest import load_cell
from tests.benchmark.accepted import holds_at_least
from tests.benchmark.toy_cca_moe import CELL, make_toy_cca_moe_root

SEED = 2**31 + 5  # the driver's seeds pass 32 signed bits
# toy limits, read on the CPU (PR 40): the sound program's rows are under them, each control's failing rests on the rows named in
# its test below. The row the int8 control has to fail is the first gradient's distance pooled over all leaves: 0.0073 for the sound
# program, 0.0111 for int8 kernels, the limit at the geometric mean; the worst leaf (an expert's stack, by whole tokens that went
# elsewhere) reads 0.27 sound and 0.11 under the control and separates nothing at this size: its limit is held against the five
# programs with a step of the equations left out, which read 0.87 to 10.9 there. The routing rows move by whole tokens of 256 (one
# choice a token): 0.004 is one token.
TOY_LIMITS = {"loss_rel_gap": 3e-4, "grad_norm_rel_gap": 0.05, "grad_rel_error": 0.6, "grad_pooled_rel_error": 0.009,
              "param_change_rel_gap": 0.3, "pairs_held_gap_per_token": 0.03, "pairs_held_after_move_gap_per_token": 0.06,
              "skip_share_gap": 0.03, "bias_change_gap": 1.0, "loss_rise_over_window": 0.05}


def toy_root(dst):
    root = make_toy_cca_moe_root(dst)
    path = root / "benchmark" / "workloads" / f"{CELL}.json"
    path.write_text(json.dumps({**json.loads(path.read_text()), "limits": TOY_LIMITS}))
    return root


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return toy_root(tmp_path_factory.mktemp("toy_cca_moe"))


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


def test_the_cell_reads_its_own_rules_file_and_its_own_shares_of_a_peak(root):
    from tests.benchmark.test_cca_moe_config import OWN

    cell = load_cell(CELL, root)
    assert cell.mode == "train_cca_moe" and cell.chips == 1 and cell.end_to_end == ("train_tokens_per_s", "setup_s")
    assert holds_at_least(cell.per_layer, OWN | {"train_host_stall_pct", "train_step_ms", "fused_ce_roofline", "device_idle_pct.train", "moe_load_max_over_mean",
                                                 "moe_pairs_held_per_token"})
    assert {cell.metric_spec(name)["rules"] for name in cell.per_layer if cell.metric_spec(name)["reader"] == "scope_time"} == {"train_cca_moe"}


def test_the_program_counters_reach_the_observed_metrics(root):
    """What a traced run's line would read off the counters, from a CPU run's observation (no trace, no peak)."""
    cell = load_cell(CELL, root)
    observed = {"moe_load_max_over_mean": [1.5, 1.25, 2.0], "window_pairs_held": [8192.0, 0.0, 16384.0], "tokens_per_step": 16384,
                "window_skip_share": [0.1, 0.0, 0.2]}
    read = lambda name, seen: cell.module("readers", cell.metric_spec(name)["reader"]).read(cell.metric_spec(name), seen, None, {})  # noqa: E731
    assert read("moe_load_max_over_mean", observed) == 1.5 and read("moe_pairs_held_per_token", observed) == 0.5 and read("moe_skip_share", observed) == 0.1
    for name in ("moe_load_max_over_mean", "moe_pairs_held_per_token", "moe_skip_share"):
        assert read(name, {}) is None, "a program without the counter: nothing, and no error"


@pytest.fixture(scope="module")
def followed(root):
    """The float32 reference over the mode's two steps on rows of the toy's size, and what the controls are held against."""
    import numpy as np
    import yaml

    from benchmark.reference import cca_moe_decoder_f32 as reference
    from benchmark.weights_cca_moe import CcaMoEShape

    cell = load_cell(CELL, root)
    mode = cell.module("modes", "train_cca_moe")
    raw = yaml.safe_load(cell.yaml_path.read_text())
    shape = CcaMoEShape.from_yaml(raw)
    rng = np.random.default_rng(3)
    batches = []
    for _ in range(mode.CHECK_STEPS):
        stream = rng.integers(0, shape.vocab_size - 1, size=(2, 129))
        batches.append((stream[:, :-1], stream[:, 1:]))
    hyper = mode.hyperparameters(raw)

    def judged(other, precision="f32"):
        control = reference.train_steps(other, SEED, batches, hyper, precision=precision, keep_first_grad=True)
        want = reference.train_steps(shape, SEED, batches, hyper, other_first_grad=control.pop("first_grad"))
        control.update(loss_start=0.0, loss_end=0.0)
        return {row["name"]: row for row in mode.judged_with_routing(control, want, TOY_LIMITS, shape, 256)}, control, want

    return mode, shape, judged


def test_the_int8_control_fails_where_the_program_passes(followed):
    """The control at a size a test run can hold: the reference with int8 kernels in the program's place, on the same rows.
    On the chip it ran at the cell's own size (benchmark/tools/control_cca_moe.py; readings in PERF.md section 2)."""
    mode, shape, judged = followed
    rows, control, want = judged(shape, "int8")
    assert not rows["first_grad_pooled_rel_error"]["ok"], rows
    assert all(rows[name]["ok"] for name in ("loss_step1_rel_gap", "param_change_norm_worst_leaf_rel_gap", "bias_change_gap")), rows
    # a routing row is held where the cell's file gives it a limit: the skip share after the move has none, and no row of `correct` has its name
    read = {row["name"]: row for row in mode.routing_gaps(control, want, 256)}
    assert set(read) == {f"{what}_step{i}_{unit}" for what, unit in (("pairs_held", "gap_per_token"), ("skip_share", "gap")) for i in (1, 2)}
    assert set(read) - set(rows) == {"skip_share_step2_gap"} and all("ok" not in row and "limit" not in row for row in read.values())
    assert rows["pairs_held_step2_gap_per_token"]["limit"] == TOY_LIMITS["pairs_held_after_move_gap_per_token"]
    # the pooled distance by kind of leaf adds up to the row's own number
    kinds = mode.by_kind_of_leaf(want["first_grad_difference_norms"], want["first_grad_norms"])
    assert sum(kind["share_of_pooled_square"] for kind in kinds.values()) == pytest.approx(1.0, abs=1e-3)
    # a program that leaves the selection bias where it was reads what the reference's largest layer moved
    still = {**control, "delta_norms": {k: (0.0 * v if k.endswith("router_bias") else v) for k, v in control["delta_norms"].items()}}
    moved = max(float(v.max()) for k, v in want["delta_norms"].items() if k.endswith("router_bias"))
    unmoved = {row["name"]: row for row in mode.judged_with_routing(still, want, TOY_LIMITS, shape, 256)}["bias_change_gap"]
    assert unmoved["value"] == pytest.approx(moved / (shape.bias_update_speed * shape.router_width ** 0.5))


@pytest.mark.parametrize("variant", ["no_conv", "no_value_shift", "no_qk_mean", "no_eda", "full_rotary"])
def test_a_program_with_a_step_of_the_equations_left_out_is_not_correct(followed, variant):
    """`benchmark/tools/control_cca_moe.py --variant`: the reference's arithmetic with one step left out, in the program's place."""
    _, shape, judged = followed
    other = dataclasses.replace(shape, rotated=shape.head_dim) if variant == "full_rotary" else dataclasses.replace(shape, without=(variant,))
    rows, _, _ = judged(other)
    failed = {name for name, row in rows.items() if not row["ok"]}
    assert failed & {"first_grad_worst_leaf_rel_error", "first_grad_pooled_rel_error", "first_grad_norm_worst_leaf_rel_gap"}, (variant, rows)


def test_a_program_that_never_moves_the_selection_bias_fails_the_biass_own_row(followed):
    """`benchmark/tools/control_cca_moe.py --variant no_bias_move`: the fault the second step's routing was once held against. The
    bias's own row reads it (what the reference's largest layer moved, over one move of all columns)."""
    mode, shape, judged = followed
    rows, control, want = judged(dataclasses.replace(shape, bias_update_speed=0.0))
    assert not rows["bias_change_gap"]["ok"] and rows["bias_change_gap"]["value"] >= 1.0, rows
    assert all(rows[name]["ok"] for name in ("loss_step1_rel_gap", "pairs_held_step1_gap_per_token", "skip_share_step1_gap")), rows
