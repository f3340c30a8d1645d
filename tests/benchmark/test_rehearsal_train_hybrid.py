"""Mode `train_hybrid` rehearsed at toy size on the CPU through the harness's own
functions: the whole of a run of the cell `train-jamba2-3b-4k` but the look for a chip.
The same with the timed path broken underneath is test_rehearsal_train_hybrid_broken.py
(a file of its own, so that the two files run side by side); here also
the control at toy size: the reference on int8 kernels in the program's place has to
fail the comparison that the sound program passes.

Nothing here is a measurement: a CPU run says whether the control flow is right."""

import json
import math

import pytest

from benchmark import run as bench_run
from benchmark.device import device_info
from benchmark.manifest import load_cell
from tests.benchmark.accepted import holds_at_least
from tests.benchmark.toy_hybrid import CELL, make_toy_hybrid_root

SEED = 2**31 + 5  # the driver's seeds pass 32 signed bits
# toy limits, read on the CPU (PR 26). The one the control has to fail is the first gradient's distance from the
# reference's: 0.016-0.026 on the worst leaf and 0.0081 pooled for the sound program, 0.045 and 0.0116 for int8 kernels. Two rows make a step here.
TOY_LIMITS = {"loss_rel_gap": 1e-3, "grad_norm_rel_gap": 0.03, "grad_rel_error": 0.036, "grad_pooled_rel_error": 0.0097, "param_change_rel_gap": 0.5, "loss_rise_over_window": 0.05}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = make_toy_hybrid_root(tmp_path_factory.mktemp("toy_hybrid"))
    path = root / "benchmark" / "workloads" / f"{CELL}.json"
    path.write_text(json.dumps({**json.loads(path.read_text()), "limits": TOY_LIMITS}))
    return root


def on_the_cpu(chips: int) -> dict:
    return device_info()


@pytest.fixture(scope="module")
def sound(root):
    return bench_run.execute(CELL, SEED, 0.4, trace=False, root=root, device_gate=on_the_cpu)


def test_sound_run_is_correct_and_reports_the_cells_end_to_end_metrics(sound, capsys):
    assert sound["correct"] is True and sound["attempted"] >= 4 and sound["failed"] == 0
    assert set(sound["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert all(math.isfinite(m["value"]) and m["value"] > 0 for m in sound["metrics"].values())
    assert set(sound["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}
    json.dumps(sound)


def test_the_cell_reads_its_own_rules_file_and_its_own_share_of_the_peak(root):
    cell = load_cell(CELL, root)
    assert cell.mode == "train_hybrid" and cell.chips == 1 and cell.end_to_end == ("train_tokens_per_s", "setup_s")
    assert holds_at_least(cell.per_layer, {"train_host_stall_pct", "train_step_ms", "flash_attention_roofline", "fused_ce_roofline", "device_idle_pct.train",
                                           "train_ssm_ms", "train_ssm_scan_ms", "train_hybrid_unattributed_pct", "train_hybrid_mfu_pct"})
    # not this cell's: the dense decoder's formula, and the eight metrics of the dense rules (which read `blocks/block/`
    # literally, and whose `workloads` open with the dense cell)
    assert {cell.metric_spec(name)["rules"] for name in cell.per_layer if cell.metric_spec(name)["reader"] == "scope_time"} == {"train_hybrid"}


def test_the_int8_control_fails_where_the_program_passes(root):
    """The control at a size a test run can hold: the reference with int8 kernels in the
    program's place, on the same rows. On the chip it ran at the cell's own size
    (benchmark/tools/control_hybrid.py; readings in PERF.md section 2)."""
    import numpy as np
    import yaml

    from benchmark.reference import hybrid_ssm_decoder_f32 as reference
    from benchmark.weights_hybrid import HybridShape

    cell = load_cell(CELL, root)
    mode = cell.module("modes", "train_hybrid")
    raw = yaml.safe_load(cell.yaml_path.read_text())
    shape = HybridShape.from_yaml(raw)
    rng = np.random.default_rng(3)
    batches = []
    for _ in range(mode.CHECK_STEPS):
        stream = rng.integers(0, shape.vocab_size - 1, size=(2, 129))
        batches.append((stream[:, :-1], stream[:, 1:]))
    hyper = mode.hyperparameters(raw)
    control = reference.train_steps(shape, SEED, batches, hyper, precision="int8", keep_first_grad=True)
    want = reference.train_steps(shape, SEED, batches, hyper, other_first_grad=control.pop("first_grad"))
    control.update(loss_start=0.0, loss_end=0.0)
    judged = {row["name"]: row for row in mode.judged(control, want, TOY_LIMITS)}
    assert not judged["first_grad_worst_leaf_rel_error"]["ok"] and not judged["first_grad_pooled_rel_error"]["ok"], judged
    assert judged["param_change_norm_worst_leaf_rel_gap"]["ok"]
