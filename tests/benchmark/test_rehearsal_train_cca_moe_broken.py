"""Mode `train_cca_moe` with the timed path broken underneath, at toy size on the CPU (the sound run is
test_rehearsal_train_cca_moe.py): a step that leaves half its batch out, a step that returns its state unchanged, a program
whose value heads all read the current position, and a program whose router is handed no state, have to come out not correct."""

import pytest

from benchmark import run as bench_run
from tests.benchmark.test_rehearsal_train import half_a_batch, state_unchanged
from tests.benchmark.test_rehearsal_train_cca_moe import SEED, on_the_cpu, toy_root
from tests.benchmark.toy_cca_moe import CELL


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return toy_root(tmp_path_factory.mktemp("toy_cca_moe_broken"))


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


def no_value_shift(monkeypatch):
    """Every value head reads the current position: the shift moves nothing."""
    from modalities_tpu.models.gpt2 import cca

    monkeypatch.setattr(cca, "shift_right", lambda x, steps: x)  # the convolutions' shifts with it: a program that forgot the sequence axis
    return {"first_grad_worst_leaf_rel_error", "first_grad_pooled_rel_error"}


def no_state_handed_on(monkeypatch):
    """Every layer's router starts from its own projection alone: the carry's second array is zeros."""
    from modalities_tpu.models.gpt2 import moe

    handed = moe._MLPRouter.__call__
    monkeypatch.setattr(moe._MLPRouter, "__call__", lambda self, x, previous=None: handed(self, x, None))
    return {"first_grad_worst_leaf_rel_error"}


@pytest.mark.parametrize("fault", [no_value_shift, no_state_handed_on])
def test_a_program_without_the_shift_or_without_the_carried_state_is_not_correct(root, monkeypatch, capsys, fault):
    import json

    rows_that_read_it = fault(monkeypatch)
    result = bench_run.execute(CELL, SEED, 0.4, trace=False, root=root, device_gate=on_the_cpu)
    assert result["correct"] is False
    compared = [json.loads(line[len("[compared] "):]) for line in capsys.readouterr().out.splitlines() if line.startswith("[compared] ")]
    failed = {row["name"] for row in compared if not row["ok"]}
    assert rows_that_read_it <= failed, failed


def test_a_program_that_cannot_build_the_model_fails_and_leaves_the_checkout_as_it_found_it(root, monkeypatch):
    """The parent of the PR that added the cell: its config factory refuses the model block's keys. The run ends with that
    error, prints no result and leaves no scratch directory (no corpus) behind for the other cells' runs there."""
    from modalities_tpu.main import Main

    def refuses(self, *args, **kwargs):
        raise ValueError("unknown keys: cca_config, scale_residual_merge; layer_types: hybrid")

    monkeypatch.setattr(Main, "build_components", refuses)
    with pytest.raises(ValueError, match="cca_config"):
        bench_run.execute(CELL, SEED, 0.4, trace=True, root=root, device_gate=on_the_cpu)
    assert not (root / bench_run.SCRATCH / CELL).exists()
