"""Mode `train_ssd_moe` with the timed path broken underneath, at toy size on the CPU (the sound run is
test_rehearsal_train_ssd_moe.py): a step that returns its state unchanged and a program
that adds its branches without their multiplier have to come out not correct."""

import pytest

from benchmark import run as bench_run
from tests.benchmark.test_rehearsal_train import state_unchanged
from tests.benchmark.test_rehearsal_train_ssd_moe import SEED, on_the_cpu, toy_root
from tests.benchmark.toy_ssd_moe import CELL


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return toy_root(tmp_path_factory.mktemp("toy_ssd_moe_broken"))


@pytest.mark.parametrize("fault", [state_unchanged])  # half a batch is the other modes' twins' to show: the harness's part is the same code
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


def no_multiplier_on_the_branches(monkeypatch):
    """A block that adds its branches as they are: `residual_multiplier` read and not applied."""
    from modalities_tpu.models.gpt2 import gpt2_model

    monkeypatch.setattr(gpt2_model.GPT2Block, "_merge", lambda self, x, branch: x + branch)
    return {"first_grad_norm_worst_leaf_rel_gap", "first_grad_pooled_rel_error"}


@pytest.mark.parametrize("fault", [no_multiplier_on_the_branches])
def test_a_program_without_the_branches_multiplier_is_not_correct(root, monkeypatch, capsys, fault):
    import json

    rows_that_read_it = fault(monkeypatch)
    result = bench_run.execute(CELL, SEED, 0.4, trace=False, root=root, device_gate=on_the_cpu)
    assert result["correct"] is False
    compared = [json.loads(line[len("[compared] "):]) for line in capsys.readouterr().out.splitlines() if line.startswith("[compared] ")]
    failed = {row["name"] for row in compared if not row["ok"]}
    assert rows_that_read_it <= failed, failed
