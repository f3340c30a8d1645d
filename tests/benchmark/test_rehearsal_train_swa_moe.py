"""Mode `train_swa_moe` rehearsed at toy size on the CPU through the harness's own functions: the whole of a run of the
cell `train-mellum2-12b-16k` but the look for a chip. The same with the timed path broken underneath is
test_rehearsal_train_swa_moe_broken.py (a file of its own, so that the two files run side by side); here also the control
at toy size: the reference on int8 kernels in the program's place has to fail the comparison that the sound program passes.

Nothing here is a measurement: a CPU run says whether the control flow is right."""

import json
import math

import pytest

from benchmark import run as bench_run
from benchmark.device import device_info
from benchmark.manifest import load_cell
from tests.benchmark.accepted import holds_at_least
from tests.benchmark.toy_swa_moe import CELL, make_toy_swa_moe_root

SEED = 2**31 + 5  # the driver's seeds pass 32 signed bits
# toy limits, read on the CPU (PR 38). The row the control has to fail is the first gradient's distance from the reference's
# pooled over all leaves: 0.0074 for the sound program, 0.0165-0.0179 for int8 kernels over three seeds, the limit at the geometric
# mean. The worst leaf (an expert's stack, by whole tokens that went elsewhere) reads 0.089 sound and 0.09-0.18 under the control
# and separates nothing at this size; its limit and the gradient norm's are held against the two broken programs of the other
# file: a dropped window reads 0.71 and 0.14 there (and moves the routing: the balance term and the pairs held), a plain rotary on the global layer 0.53 and 0.32 (on
# that layer's q_attn, and nothing on the loss: from seeded weights the positions barely move the logits).
TOY_LIMITS = {"loss_rel_gap": 3.1e-4, "grad_norm_rel_gap": 0.05, "grad_rel_error": 0.25, "grad_pooled_rel_error": 0.011,
              "param_change_rel_gap": 0.5, "pairs_held_rel_gap": 0.02, "aux_loss_rel_gap": 0.003, "loss_rise_over_window": 0.05}


def toy_root(dst):
    root = make_toy_swa_moe_root(dst)
    path = root / "benchmark" / "workloads" / f"{CELL}.json"
    path.write_text(json.dumps({**json.loads(path.read_text()), "limits": TOY_LIMITS}))
    return root


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return toy_root(tmp_path_factory.mktemp("toy_swa_moe"))


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
    assert cell.mode == "train_swa_moe" and cell.chips == 1 and cell.end_to_end == ("train_tokens_per_s", "setup_s")
    assert holds_at_least(cell.per_layer, {"train_host_stall_pct", "train_step_ms", "fused_ce_roofline", "device_idle_pct.train", "moe_load_max_over_mean",
                                           "train_swa_fwd_ms", "train_swa_bwd_ms", "train_swa_optimizer_ms", "train_swa_attn_window_ms",
                                           "train_swa_attn_global_ms", "train_swa_moe_ms", "train_swa_moe_dispatch_ms", "train_swa_head_loss_ms",
                                           "train_swa_layer_carry_ms", "train_swa_unattributed_pct", "train_swa_mfu_pct", "moe_pairs_held_per_token",
                                           "moe_aux_loss", "flash_attention_window_roofline", "flash_attention_global_roofline"})
    assert {cell.metric_spec(name)["rules"] for name in cell.per_layer if cell.metric_spec(name)["reader"] == "scope_time"} == {"train_swa_moe"}


def test_the_program_counters_reach_the_observed_metrics(root):
    """What a traced run's line would read off the counters, from a CPU run's observation (no trace, no peak)."""
    cell = load_cell(CELL, root)
    observed = {"moe_load_max_over_mean": [1.5, 1.25, 2.0], "window_pairs_held": [16000.0, 16384.0, 18000.0], "tokens_per_step": 16384,
                "window_aux_loss": [1.02, 1.01, 1.5]}
    read = lambda name, seen: cell.module("readers", cell.metric_spec(name)["reader"]).read(cell.metric_spec(name), seen, None, {})  # noqa: E731
    assert read("moe_load_max_over_mean", observed) == 1.5 and read("moe_pairs_held_per_token", observed) == 1.0 and read("moe_aux_loss", observed) == 1.02
    for name in ("moe_load_max_over_mean", "moe_pairs_held_per_token", "moe_aux_loss"):
        assert read(name, {}) is None, "a program without the counter: nothing, and no error"


def test_the_scope_rules_read_a_window_layers_attention_apart_from_a_global_layers(root):
    from benchmark import xscope

    rules = xscope.load_rules(root / "benchmark" / "scopes" / "train_swa_moe.json")
    step = "jit(train_step)/jit(main)/transpose(jvp(GPT2Module))/run_2/layer_carry/while/body/closed_call/blocks/blocks/checkpoint/rematted_computation/block"
    paths = {
        f"{step}/window/attn/attn_core/flash_attention_window_bwd": ("backward", "attn_window"),
        f"{step}/global/attn/rope/cos": ("backward", "attn_global"),
        "jit(train_step)/jit(main)/jvp(GPT2Module)/run_1/layer_carry/while/body/closed_call/blocks/block/global/attn/q_attn/dot_general": ("forward", "attn_global"),
        f"{step}/moe/router/reduce_sum": ("backward", "moe_router"),
        f"{step}/moe/while/body/experts/dot_general": ("backward", "moe_experts"),
    }
    for path, (want_pass, want_component) in paths.items():
        assert (xscope.bucket_of(path, rules["pass"]), xscope.bucket_of(path, rules["component"])) == (want_pass, want_component), path


def test_the_int8_control_fails_where_the_program_passes(root):
    """The control at a size a test run can hold: the reference with int8 kernels in the program's place, on the same rows.
    On the chip it ran at the cell's own size (benchmark/tools/control_swa_moe.py; readings in PERF.md section 2)."""
    import numpy as np
    import yaml

    from benchmark.reference import swa_moe_decoder_f32 as reference
    from benchmark.weights_swa_moe import SwaMoEShape

    cell = load_cell(CELL, root)
    mode = cell.module("modes", "train_swa_moe")
    raw = yaml.safe_load(cell.yaml_path.read_text())
    shape = SwaMoEShape.from_yaml(raw)
    rng = np.random.default_rng(3)
    batches = []
    for _ in range(mode.CHECK_STEPS):
        stream = rng.integers(0, shape.vocab_size - 1, size=(2, 129))
        batches.append((stream[:, :-1], stream[:, 1:]))
    hyper = mode.hyperparameters(raw)
    control = reference.train_steps(shape, SEED, batches, hyper, precision="int8", keep_first_grad=True)
    want = reference.train_steps(shape, SEED, batches, hyper, other_first_grad=control.pop("first_grad"))
    control.update(loss_start=0.0, loss_end=0.0)
    judged = {row["name"]: row for row in mode.judged_with_routing(control, want, TOY_LIMITS)}
    assert not judged["first_grad_pooled_rel_error"]["ok"], judged
    assert all(judged[name]["ok"] for name in ("loss_step1_rel_gap", "param_change_norm_worst_leaf_rel_gap", "pairs_held_step1_rel_gap",
                                               "aux_loss_step1_rel_gap")), judged
    # the second step's routing is read beside them and held to nothing: no row of `correct` has its name
    read = {row["name"]: row for row in mode.routing_gaps(control, want)}
    assert set(read) == {f"{what}_step{i}_rel_gap" for what in ("pairs_held", "aux_loss") for i in (1, 2)}
    assert not {"pairs_held_step2_rel_gap", "aux_loss_step2_rel_gap"} & set(judged) and all("ok" not in row and "limit" not in row for row in read.values())
    # and the pooled distance by kind of leaf adds up to the row's own number
    kinds = mode.by_kind_of_leaf(want["first_grad_difference_norms"], want["first_grad_norms"])
    assert sum(kind["share_of_pooled_square"] for kind in kinds.values()) == pytest.approx(1.0, abs=1e-3)
    assert sum(k["rel_error"] ** 2 * k["share_of_gradient_square"] for k in kinds.values()) ** 0.5 == pytest.approx(
        judged["first_grad_pooled_rel_error"]["value"], rel=0.02)
    # a program that publishes no balance term (0) or one pooled over the layers reads far off its row
    silent = {row["name"]: row for row in mode.judged_with_routing({**control, "aux_loss": [0.0, 0.0]}, want, TOY_LIMITS)}
    assert not silent["aux_loss_step1_rel_gap"]["ok"] and silent["aux_loss_step1_rel_gap"]["value"] == 1.0
