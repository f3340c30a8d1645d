"""Mode `train_swa_moe` with the timed path broken underneath, at toy size on the CPU (the sound run is
test_rehearsal_train_swa_moe.py): a step that leaves half its batch out, a step that returns its state unchanged, a program
whose window layers see all that came before, and a program whose global layers turn by the window layers' plain rotary, have
to come out not correct."""

import pytest

from benchmark import run as bench_run
from tests.benchmark.test_rehearsal_train import half_a_batch, state_unchanged
from tests.benchmark.test_rehearsal_train_swa_moe import SEED, on_the_cpu, toy_root
from tests.benchmark.toy_swa_moe import CELL


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return toy_root(tmp_path_factory.mktemp("toy_swa_moe_broken"))


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


def drop_the_window(monkeypatch):
    """Every layer sees all that came before: the window layers' attention call loses its window."""
    from modalities_tpu.models.gpt2 import gpt2_model

    with_window = gpt2_model.flash_attention
    monkeypatch.setattr(gpt2_model, "flash_attention", lambda q, k, v, window=None: with_window(q, k, v, None))
    return {"first_grad_worst_leaf_rel_error", "first_grad_pooled_rel_error", "first_grad_norm_worst_leaf_rel_gap"}


def plain_rotary_on_global_layers(monkeypatch):
    """The global layers turn by the window layers' tables: no scaled frequencies, no attention factor."""
    from modalities_tpu.models.gpt2 import gpt2_model

    monkeypatch.setattr(gpt2_model.GPT2ModelSpec, "rope_of", lambda self, mixer: dict(self.rope_by_kind).get("swa"))
    return {"first_grad_worst_leaf_rel_error", "first_grad_norm_worst_leaf_rel_gap"}


@pytest.mark.parametrize("fault", [drop_the_window, plain_rotary_on_global_layers])
def test_a_program_without_the_window_or_without_yarn_is_not_correct(root, monkeypatch, capsys, fault):
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
        raise ValueError("unknown keys: head_dim, layer_types, sliding_window, rope_parameters")

    monkeypatch.setattr(Main, "build_components", refuses)
    with pytest.raises(ValueError, match="layer_types"):
        bench_run.execute(CELL, SEED, 0.4, trace=True, root=root, device_gate=on_the_cpu)
    assert not (root / bench_run.SCRATCH / CELL).exists()
