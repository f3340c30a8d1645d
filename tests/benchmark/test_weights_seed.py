"""A cell whose work follows its weights draws them from a seed of its own (`weights_seed` in its workload file), so that
every `--seed` does the same work on another draw of the corpus; every other cell draws them from the run's seed.

Since PR 49's refused check (PERF.md section 6): the rate of the cell named here follows the pairs its held experts get
(140 ms a step for every pair a token), the pairs followed the draw of the router more than the draw of the corpus, and the
driver's sets on six seeds spread by 1.7% of the median. `train-mellum2-12b-16k`, refused in the same check, was tried with
weights of its own on the chip and spread as widely as without (its pairs swing from step to step by the corpus alone), so
it draws its weights from the run's seed as it did."""

import json
from pathlib import Path

import pytest

from benchmark import run as bench_run
from benchmark.device import device_info
from benchmark.manifest import load_cell
from tests.benchmark.accepted import ACCEPTED_CELLS
from tests.benchmark.toy_cca_moe import CELL as CCA_CELL
from tests.benchmark.toy_cca_moe import make_toy_cca_moe_root

OWN_WEIGHTS = {"train-zaya1-8b-8k": 2147500701}  # the first seed the cell's limits were read on (PR 40)
SEED = 2**31 + 77  # the driver's seeds pass 32 signed bits


@pytest.mark.parametrize("name", ACCEPTED_CELLS)
def test_the_weights_come_from_the_cells_own_seed_where_it_names_one_and_from_the_runs_seed_else(name):
    cell = load_cell(name)
    assert cell.spec.get("weights_seed") == OWN_WEIGHTS.get(name)
    for seed in (7, SEED):
        ctx = bench_run.Context(cell=cell, seed=seed, seconds=1.0, scratch=Path("."), trace_dir=None)
        assert ctx.weights_seed == OWN_WEIGHTS.get(name, seed)


class _WeightsAsked(Exception):
    pass


@pytest.mark.parametrize("own", [True, False])
def test_a_run_packs_its_corpus_from_the_runs_seed_and_asks_for_the_weights_of_the_cells(tmp_path, monkeypatch, capsys, own):
    """The mode up to the point where it gives the program its weights: the corpus is packed from `--seed`, the weights are
    asked for with the cell's own seed, or with the run's where the workload file names none."""
    import benchmark.weights_cca_moe as weights

    cell_name = CCA_CELL
    root = make_toy_cca_moe_root(tmp_path / "root")
    path = root / "benchmark" / "workloads" / f"{cell_name}.json"
    spec = json.loads(path.read_text())
    assert spec["weights_seed"] == OWN_WEIGHTS[cell_name]
    if not own:
        del spec["weights_seed"]
        path.write_text(json.dumps(spec))
    asked = []

    def refuse(shape, seed, like):
        asked.append(seed)
        raise _WeightsAsked

    monkeypatch.setattr(weights, "make_program_tree", refuse)
    with pytest.raises(_WeightsAsked):
        bench_run.execute(cell_name, SEED, 0.4, trace=False, root=root, device_gate=lambda chips: device_info())
    assert asked == [OWN_WEIGHTS[cell_name] if own else SEED]
    assert f"corpus from seed {SEED}, weights from seed {asked[0]}" in capsys.readouterr().out
    assert not (root / ".bench_scratch" / cell_name).exists()  # the run that ended there took its scratch with it
