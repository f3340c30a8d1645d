"""Mode `train_looped` with the timed path broken underneath, at toy size on the CPU (the sound run is
test_rehearsal_train_looped.py): a step that leaves half its batch out, a step that returns its state unchanged, and a
loss that leaves one exit out, have to come out not correct."""

import json

import pytest

from benchmark import run as bench_run
from tests.benchmark.test_rehearsal_train import half_a_batch, state_unchanged
from tests.benchmark.test_rehearsal_train_looped import SEED, TOY_LIMITS, on_the_cpu
from tests.benchmark.toy_looped import CELL, make_toy_looped_root


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = make_toy_looped_root(tmp_path_factory.mktemp("toy_looped_broken"))
    path = root / "benchmark" / "workloads" / f"{CELL}.json"
    path.write_text(json.dumps({**json.loads(path.read_text()), "limits": TOY_LIMITS}))
    return root


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


def test_a_loss_that_leaves_one_exit_out_is_not_correct(root, monkeypatch, capsys):
    """The second exit's cross entropy never reaches the loss (the step still counts it): the loss, the gradient and the
    expected exit the gates are trained to all move, and the per-exit rows still read the reference's numbers."""
    from modalities_tpu.loss_functions import LoopedExitLoss

    whole = LoopedExitLoss.exit_loss

    def without_the_second_exit(self, row_ce, gate_logits, labels, beta):
        _, counted = whole(self, row_ce, gate_logits, labels, beta)
        loss, _ = whole(self, row_ce.at[1].set(0.0), gate_logits, labels, beta)
        return loss, counted

    monkeypatch.setattr(LoopedExitLoss, "exit_loss", without_the_second_exit)
    result = bench_run.execute(CELL, SEED, 0.4, trace=False, root=root, device_gate=on_the_cpu)
    assert result["correct"] is False
    rows = {row["name"]: row for row in (json.loads(line[len("[compared] "):]) for line in capsys.readouterr().out.splitlines() if line.startswith("[compared] "))}
    assert not rows["loss_step1_rel_gap"]["ok"] and not rows["first_grad_worst_leaf_rel_error"]["ok"] and rows["exit_ce_step1_rel_gap"]["ok"]


def test_a_program_that_cannot_build_the_model_fails_and_leaves_the_checkout_as_it_found_it(root, monkeypatch):
    """The parent of the PR that added the cell: its config factory refuses the model block's keys. The run ends with
    that error, prints no result and leaves no scratch directory (no corpus) behind for the other cells' runs there."""
    from modalities_tpu.main import Main

    def refuses(self, *args, **kwargs):
        raise ValueError("unknown keys: loop_config, post_attention_norm_config, post_ffn_norm_config")

    monkeypatch.setattr(Main, "build_components", refuses)
    with pytest.raises(ValueError, match="loop_config"):
        bench_run.execute(CELL, SEED, 0.4, trace=True, root=root, device_gate=on_the_cpu)
    assert not (root / bench_run.SCRATCH / CELL).exists()
