"""Mode `train_ssd_moe` rehearsed at toy size on the CPU through the harness's own functions: the whole of a run of the cell
`train-granite4h-32b-8k` but the look for a chip; and the control at toy size, the reference on int8 kernels and the reference
with each of eleven steps of its equations left out in the program's place, as CASES OF ONE COMPILED TOY (the step left out is an
argument of the reference's programs, not a program of its own): each has to fail the comparison that the sound program passes,
or be named as one this toy's rows cannot tell apart. A run with the timed path broken underneath (half a batch, a state
unchanged) is `test_rehearsal_train_ssd_moe_broken.py`'s.

Nothing here is a measurement: a CPU run says whether the control flow is right."""

import json
import math

import numpy as np
import pytest
import yaml

from benchmark import run as bench_run
from benchmark.device import device_info
from benchmark.manifest import load_cell
from tests.benchmark.accepted import holds_at_least
from tests.benchmark.toy_ssd_moe import CELL, make_toy_ssd_moe_root

SEED = 2**31 + 5  # the driver's seeds pass 32 signed bits
# toy limits, read on the CPU (PR 52). Sound program: gradient norm gap 0.040 (a router), worst leaf 0.114 (a router, by whole pairs that went
# elsewhere), pooled distance 0.0056, parameter change 0.050, loss 1e-6, pairs held 0.0027, balance term 3e-5. The int8 control reads 0.179 on
# the worst leaf, 0.043 on the norm and 0.0066 pooled: the worst leaf is the row it has to fail, its limit at the geometric mean of the two
# sides. Every faulty program reads 0.105 (the scores' scale) to 17 on the norm gap, the decay's 0.42 among them; without dt's softplus
# the decay is a growth and nothing is finite.
TOY_LIMITS = {"loss_rel_gap": 3.1e-4, "grad_norm_rel_gap": 0.06, "grad_rel_error": 0.14, "grad_pooled_rel_error": 0.018,
              "param_change_rel_gap": 0.5, "pairs_held_rel_gap": 0.02, "aux_loss_rel_gap": 0.003, "loss_rise_over_window": 0.05}
OWN = {"train_ssd_fwd_ms", "train_ssd_bwd_ms", "train_ssd_optimizer_ms", "train_ssd_mixer_ms", "train_ssd_scan_ms", "train_ssd_scan_state_ms",
       "train_ssd_conv_gates_ms", "train_ssd_attn_ms", "train_ssd_moe_ms", "train_ssd_moe_dispatch_ms", "train_ssd_head_loss_ms",
       "train_ssd_layer_carry_ms", "train_ssd_unattributed_pct", "train_ssd_mfu_pct", "ssd_decay_mean", "flash_attention_ssd_roofline"}


def toy_root(dst):
    root = make_toy_ssd_moe_root(dst)
    path = root / "benchmark" / "workloads" / f"{CELL}.json"
    path.write_text(json.dumps({**json.loads(path.read_text()), "limits": TOY_LIMITS}))
    return root


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return toy_root(tmp_path_factory.mktemp("toy_ssd_moe"))


def on_the_cpu(chips: int) -> dict:
    return device_info()


def test_sound_run_is_correct_and_reports_the_cells_end_to_end_metrics(root, capsys):
    sound = bench_run.execute(CELL, SEED, 0.4, trace=False, root=root, device_gate=on_the_cpu)
    assert sound["correct"] is True and sound["attempted"] >= 4 and sound["failed"] == 0
    assert set(sound["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert all(math.isfinite(m["value"]) and m["value"] > 0 for m in sound["metrics"].values())
    assert set(sound["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}
    json.dumps(sound)
    out = capsys.readouterr().out
    plans = [json.loads(line[len("[train] plan "):]) for line in out.splitlines() if line.startswith("[train] plan ")]
    assert {"ssd_plan", "moe_dispatch_plan"} <= {plan["name"] for plan in plans}
    # the three readings that decide nothing are printed: the second step's routing, the choices' least difference, the first Mamba-2 layer by position
    for reading in ("the second followed step's routing", "least_share_of_pairs_whose_expert_differs_by_layer", '"first_positions"'):
        assert reading in out, reading
    assert "a reading failed" not in out


def test_the_cell_reads_its_own_rules_file_and_its_own_shares_of_a_peak(root):
    cell = load_cell(CELL, root)
    assert cell.mode == "train_ssd_moe" and cell.chips == 1 and cell.end_to_end == ("train_tokens_per_s", "setup_s")
    assert holds_at_least(cell.per_layer, OWN | {"train_host_stall_pct", "train_step_ms", "fused_ce_roofline", "device_idle_pct.train", "moe_load_max_over_mean",
                                                 "moe_pairs_held_per_token", "moe_aux_loss"})
    assert {cell.metric_spec(name)["rules"] for name in cell.per_layer if cell.metric_spec(name)["reader"] == "scope_time"} == {"train_ssd_moe"}


def test_the_program_counters_reach_the_observed_metrics(root):
    """What a traced run's line would read off the counters, from a CPU run's observation (no trace, no peak)."""
    cell = load_cell(CELL, root)
    observed = {"moe_load_max_over_mean": [1.5, 1.25, 2.0], "window_pairs_held": [16384.0, 20480.0, 24576.0], "tokens_per_step": 16384,
                "window_aux_loss": [1.02, 1.01, 1.5], "window_ssd_decay_mean": [0.83, 0.84, 0.85]}
    read = lambda name, seen: cell.module("readers", cell.metric_spec(name)["reader"]).read(cell.metric_spec(name), seen, None, {})  # noqa: E731
    assert read("moe_pairs_held_per_token", observed) == 1.25 and read("moe_aux_loss", observed) == 1.02 and read("ssd_decay_mean", observed) == 0.84
    for name in ("ssd_decay_mean", "moe_pairs_held_per_token", "moe_aux_loss"):
        assert read(name, {}) is None, "a program without the counter: nothing, and no error"
    # a trace without the cell's scopes or kernels (the parent's): the readers of a trace return nothing where there is none
    for name in ("train_ssd_scan_ms", "flash_attention_ssd_roofline", "train_ssd_unattributed_pct"):
        spec = cell.metric_spec(name)
        assert cell.module("readers", spec["reader"]).read(spec, {}, None, {"peaks": None}) is None


def test_the_scope_rules_read_the_mixers_parts_the_attention_and_the_shared_expert(root):
    from benchmark import xscope

    rules = xscope.load_rules(root / "benchmark" / "scopes" / "train_ssd_moe.json")
    step = "jit(train_step)/jit(main)/transpose(jvp(GPT2Module))/run_0/layer_carry/while/body/closed_call/blocks/blocks/checkpoint/rematted_computation/block"
    forward = "jit(train_step)/jit(main)/jvp(GPT2Module)/run_0/layer_carry/while/body/closed_call/blocks/block"
    attn = "jit(train_step)/jit(main)/transpose(jvp(GPT2Module))/run_1/layer_carry/while/body/closed_call/blocks/blocks/checkpoint/block"
    paths = {
        f"{forward}/ssd/in_proj/in_proj/dot_general": ("forward", "ssd_in_proj"),
        f"{forward}/ssd/conv/mul": ("forward", "ssd_conv"),
        f"{forward}/ssd/scan/softplus": ("forward", "ssd_scan"),
        f"{forward}/ssd/scan/intra/dot_general": ("forward", "ssd_scan_intra"),
        f"{step}/ssd/scan/intra/cumsum": ("backward", "ssd_scan_intra"),
        f"{step}/ssd/scan/state/while/body/mul": ("backward", "ssd_scan_state"),
        f"{forward}/ssd/scan/state/dot_general": ("forward", "ssd_scan_state"),
        f"{forward}/ssd/gate/rsqrt": ("forward", "ssd_gate"),
        f"{forward}/ssd/out_proj/out_proj/dot_general": ("forward", "ssd_out_proj"),
        f"{forward}/ssd/dropout/select": ("forward", "ssd"),
        f"{attn}/attn/attn_core/flash_attention_bwd": ("backward", "attn_core"),
        f"{attn}/attn/q_attn/dot_general": ("backward", "attn"),
        f"{step}/moe/shared/W/dot_general": ("backward", "moe_shared"),
        f"{step}/moe/router/reduce_sum": ("backward", "moe_router"),
        f"{step}/moe/while/body/experts/dot_general": ("backward", "moe_experts"),
        f"{step}/moe/combine/moe_combine": ("backward", "moe_combine"),
        f"{step}/residual/mul": ("backward", "residual"),
        "jit(train_step)/jit(main)/jvp(GPT2Module)/wte/mul": ("forward", "wte"),
        "jit(train_step)/jit(main)/jvp(GPT2Module)/run_0/layer_carry/while/body/add": ("forward", "layer_carry"),
    }
    for path, (want_pass, want_component) in paths.items():
        assert (xscope.bucket_of(path, rules["pass"]), xscope.bucket_of(path, rules["component"])) == (want_pass, want_component), path


# ------------------------------------------------------------------ the control and the eleven faulty programs, one compiled toy


@pytest.fixture(scope="module")
def followed(root):
    """The toy cell's mode, shape, batches and recipe, and the sound reference followed over two steps, its first gradient kept."""
    from benchmark.reference import ssd_moe_decoder_f32 as reference
    from benchmark.weights_ssd_moe import SsdMoEShape

    cell = load_cell(CELL, root)
    mode = cell.module("modes", "train_ssd_moe")
    raw = yaml.safe_load(cell.yaml_path.read_text())
    shape = SsdMoEShape.from_yaml(raw)
    rng = np.random.default_rng(3)
    batches = []
    for _ in range(mode.CHECK_STEPS):
        stream = rng.integers(0, shape.vocab_size - 1, size=(2, 129))
        batches.append((stream[:, :-1], stream[:, 1:]))
    hyper = mode.hyperparameters(raw)
    return mode, reference, shape, batches, hyper, reference.train_steps(shape, SEED, batches, hyper, keep_first_grad=True)


def judged_against(followed, root, **other):
    """The control tool's own function (`benchmark/tools/control_ssd_moe.py`): another model followed in the program's place and
    judged against the sound reference, which is not followed again."""
    mode, reference, shape, batches, hyper, want = followed
    tool = load_cell(CELL, root).module("tools", "control_ssd_moe")
    return tool.judged_against(mode, reference, shape, SEED, batches, hyper, want, TOY_LIMITS, **other)


def test_the_int8_control_fails_where_the_program_passes(followed, root):
    mode = followed[0]
    judged, control, want = judged_against(followed, root, precision="int8")
    assert not judged["first_grad_worst_leaf_rel_error"]["ok"], judged
    assert all(judged[name]["ok"] for name in ("loss_step1_rel_gap", "param_change_norm_worst_leaf_rel_gap", "pairs_held_step1_rel_gap",
                                               "aux_loss_step1_rel_gap")), judged
    # the second step's routing is read beside them and held to nothing: no row of `correct` has its name
    read = {row["name"]: row for row in mode.routing_gaps(control, want)}
    assert set(read) == {f"{what}_step{i}_rel_gap" for what in ("pairs_held", "aux_loss") for i in (1, 2)}
    assert not {"pairs_held_step2_rel_gap", "aux_loss_step2_rel_gap"} & set(judged) and all("ok" not in row and "limit" not in row for row in read.values())
    kinds = mode.by_kind_of_leaf(want["first_grad_difference_norms"], want["first_grad_norms"])
    assert sum(kind["share_of_pooled_square"] for kind in kinds.values()) == pytest.approx(1.0, abs=1e-3)
    # a program that publishes no balance term (0) reads far off its row
    silent = {row["name"]: row for row in mode.judged_with_routing({**control, "aux_loss": [0.0, 0.0]}, want, TOY_LIMITS)}
    assert not silent["aux_loss_step1_rel_gap"]["ok"] and silent["aux_loss_step1_rel_gap"]["value"] == 1.0


@pytest.mark.parametrize("variant", ["no_decay", "no_skip_d", "no_conv_silu", "no_gate", "no_gate_norm", "no_dt_softplus", "residual_1", "attention_rsqrt_d",
                                     "embedding_1", "logits_1", "no_gate_renorm"])
def test_a_program_with_a_step_of_the_equations_left_out_is_not_correct(root, followed, variant):
    variants = load_cell(CELL, root).module("tools", "control_ssd_moe").VARIANTS  # the tool's own table: a variant's name, the step it leaves out
    assert len(variants) == 11
    judged, _, _ = judged_against(followed, root, skip=(variants[variant],))
    failed = {name for name, row in judged.items() if not row["ok"]}
    assert "first_grad_norm_worst_leaf_rel_gap" in failed, (variant, failed)


def test_a_program_that_cannot_build_the_model_fails_and_leaves_the_checkout_as_it_found_it(root, monkeypatch):
    """The parent of the PR that added the cell: its config factory refuses the model block's keys. The run ends with that
    error, prints no result and leaves no scratch directory (no corpus) behind for the other cells' runs there."""
    from modalities_tpu.main import Main

    def refuses(self, *args, **kwargs):
        raise ValueError("unknown keys: ssd_config, embedding_multiplier; layer_types: mamba")

    monkeypatch.setattr(Main, "build_components", refuses)
    with pytest.raises(ValueError, match="ssd_config"):
        bench_run.execute(CELL, SEED, 0.4, trace=True, root=root, device_gate=on_the_cpu)
    assert not (root / bench_run.SCRATCH / CELL).exists()
