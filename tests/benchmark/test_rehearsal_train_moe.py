"""Mode `train_moe` rehearsed at toy size on the CPU through the harness's own functions:
the whole of a run of the cell `train-kanana2-30b-8k` but the look for a chip. The same
with the timed path broken underneath is test_rehearsal_train_moe_broken.py (a file of its
own, so that the two files run side by side); here also the control at toy size: the
reference on int8 kernels in the program's place has to fail the comparison that the
sound program passes.

Nothing here is a measurement: a CPU run says whether the control flow is right."""

import json
import math

import pytest

from benchmark import run as bench_run
from benchmark.device import device_info
from benchmark.manifest import load_cell
from tests.benchmark.accepted import holds_at_least
from tests.benchmark.toy_moe import CELL, make_toy_moe_root

SEED = 2**31 + 5  # the driver's seeds pass 32 signed bits
# toy limits, read on the CPU (PR 30). The rows the control has to fail are the first gradient's distance from the
# reference's: 0.092 on the worst leaf (an expert layer's `experts_W_2`) and 0.0202 pooled for the sound program, 0.136 and
# 0.0276 for int8 kernels; each limit at the geometric mean of its two readings. The pairs held differ by half a pair of
# 437 (one token of 256 in one of two layers whose third and fourth scores bfloat16 activations order otherwise).
TOY_LIMITS = {"loss_rel_gap": 1e-3, "grad_norm_rel_gap": 0.05, "grad_rel_error": 0.112, "grad_pooled_rel_error": 0.0236,
              "param_change_rel_gap": 0.5, "pairs_held_rel_gap": 0.02, "pairs_held_after_move_rel_gap": 0.1, "bias_change_gap": 0.3,
              "loss_rise_over_window": 0.05}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = make_toy_moe_root(tmp_path_factory.mktemp("toy_moe"))
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


def test_the_cell_reads_its_own_rules_file_and_its_own_shares_of_a_peak(root):
    cell = load_cell(CELL, root)
    assert cell.mode == "train_moe" and cell.chips == 1 and cell.end_to_end == ("train_tokens_per_s", "setup_s")
    assert holds_at_least(cell.per_layer, {"train_host_stall_pct", "train_step_ms", "fused_ce_roofline", "device_idle_pct.train",
                                           "train_moe_ms", "train_moe_dispatch_ms", "train_mla_attn_ms", "train_moe_unattributed_pct",
                                           "flash_attention_mla_roofline", "train_moe_mfu_pct", "moe_load_max_over_mean",
                                           "train_moe_fwd_ms", "train_moe_bwd_ms", "train_moe_optimizer_ms", "train_moe_head_loss_ms",
                                           "train_moe_layer_carry_ms", "train_moe_dense_mlp_ms"})
    # every bucket a metric of this cell reads is one its rules file fills
    rules = json.loads((root / "benchmark" / "scopes" / "train_moe.json").read_text())
    for name in cell.per_layer:
        spec = cell.metric_spec(name)
        if spec["reader"] == "scope_time" and "list" in spec:
            assert set(spec["buckets"]) <= {bucket for _, bucket in rules[spec["list"]]}, name
    # not this cell's: the shape function of one head size, the dense formula, and the rules of the other two cells
    assert {cell.metric_spec(name)["rules"] for name in cell.per_layer if cell.metric_spec(name)["reader"] == "scope_time"} == {"train_moe"}
    assert cell.metric_spec("flash_attention_mla_roofline")["pattern"] == cell.metric_spec("flash_attention_roofline")["pattern"]


def test_the_program_counters_reach_the_observed_metrics(root):
    """What a traced run's line would read off the counters, from a CPU run's observation (no trace, no peak)."""
    cell = load_cell(CELL, root)
    observed = {"moe_load_max_over_mean": [1.5, 1.25, 2.0]}
    spec = cell.metric_spec("moe_load_max_over_mean")
    assert cell.module("readers", spec["reader"]).read(spec, observed, None, {}) == 1.5
    assert cell.module("readers", spec["reader"]).read(spec, {}, None, {}) is None, "a program without the counter: nothing, and no error"


def test_the_int8_control_fails_where_the_program_passes(root):
    """The control at a size a test run can hold: the reference with int8 kernels in the
    program's place, on the same rows. On the chip it ran at the cell's own size
    (benchmark/tools/control_moe.py; readings in PERF.md section 2)."""
    import numpy as np
    import yaml

    from benchmark.reference import moe_mla_decoder_f32 as reference
    from benchmark.weights_moe import MoEMLAShape

    cell = load_cell(CELL, root)
    mode = cell.module("modes", "train_moe")
    raw = yaml.safe_load(cell.yaml_path.read_text())
    shape = MoEMLAShape.from_yaml(raw)
    rng = np.random.default_rng(3)
    batches = []
    for _ in range(mode.CHECK_STEPS):
        stream = rng.integers(0, shape.vocab_size - 1, size=(2, 129))
        batches.append((stream[:, :-1], stream[:, 1:]))
    hyper = mode.hyperparameters(raw)
    control = reference.train_steps(shape, SEED, batches, hyper, precision="int8", keep_first_grad=True)
    want = reference.train_steps(shape, SEED, batches, hyper, other_first_grad=control.pop("first_grad"))
    control.update(loss_start=0.0, loss_end=0.0)
    judged = {row["name"]: row for row in mode.judged_with_pairs(control, want, TOY_LIMITS, shape)}
    assert not judged["first_grad_worst_leaf_rel_error"]["ok"] and not judged["first_grad_pooled_rel_error"]["ok"], judged
    assert judged["param_change_norm_worst_leaf_rel_gap"]["ok"] and "router_bias" not in judged["param_change_norm_worst_leaf_rel_gap"]["leaf"]
    # the selection bias's change is judged apart, by quanta of its speed; a side that never moved it reads what the other's largest layer moved
    assert shape.bias_update_speed == 0.1 and judged["bias_change_gap"]["ok"]
    still = {**control, "delta_norms": {k: (0 * v if k.endswith("router_bias") else v) for k, v in control["delta_norms"].items()}}
    moved = max(float(np.max(v)) for k, v in want["delta_norms"].items() if k.endswith("router_bias"))
    unmoved = {row["name"]: row for row in mode.judged_with_pairs(still, want, TOY_LIMITS, shape)}["bias_change_gap"]
    assert unmoved["value"] == pytest.approx(moved / (0.1 * np.sqrt(shape.n_routed_experts))) and unmoved["ok"] == (unmoved["value"] <= 0.3)
