"""The command as the driver runs it, where there is no chip: it fails and prints no
result. And what a later PR does: a cell, a configuration, a mix and a metric added as
files and manifest entries only, with no edit to a file that is there."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmark import run as bench_run
from benchmark.device import device_info
from benchmark.manifest import load_cell
from tests.benchmark.accepted import holds_at_least
from tests.benchmark.toy import REPO, make_toy_root

CELL = "train-2p7b-4k"


def on_the_cpu(chips: int) -> dict:
    return device_info()


def command(cwd, env):
    return subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed", "1", "--seconds", "1", "--trace", "0"],
        env={**env, "JAX_PLATFORMS": "cpu"}, capture_output=True, text=True, timeout=300, cwd=cwd,
    )


def test_without_a_tpu_the_command_fails_and_prints_no_result():
    proc = command(REPO, dict(os.environ))
    assert proc.returncode != 0
    assert "There is no CPU run" in proc.stderr and '"correct"' not in proc.stdout


def test_alone_with_its_own_files_the_command_fails_and_prints_no_result(tmp_path):
    shutil.copytree(REPO / "benchmark", tmp_path / "benchmark", ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    proc = command(tmp_path, {k: v for k, v in os.environ.items() if k != "PYTHONPATH"})
    assert proc.returncode != 0 and '"correct"' not in proc.stdout


def test_a_metric_appended_for_every_cell_leaves_each_accepted_cells_rows_first_and_in_their_order(tmp_path):
    """The guard of `accepted.holds_at_least`: what the next PR does to the manifest (one more per-layer entry, with
    its metric file, listed in all cells) takes nothing from what any accepted cell reported, and moves none of it."""
    root = make_toy_root(tmp_path)
    manifest = json.loads((root / "BENCHMARK.json").read_text())
    cells = [w["name"] for w in manifest["workloads"]]
    accepted = {name: load_cell(name, root).per_layer for name in cells}
    (root / "benchmark" / "readers" / "made_up_reader.py").write_text("def read(spec, observed, trace, env):\n    return 1.0\n")
    (root / "benchmark" / "metrics" / "made_up_count.json").write_text(json.dumps({"reader": "made_up_reader"}))
    manifest["per_layer"].append({"name": "made_up_count", "unit": "steps", "better": "higher", "source": "program_counter",
                                  "layer": "trainer loop", "moves": "train_tokens_per_s", "workloads": cells})
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))
    for name in cells:
        cell = load_cell(name, root)
        assert holds_at_least(cell.per_layer, accepted[name]) and holds_at_least(cell.per_layer, set(accepted[name]))
        assert cell.per_layer[len(accepted[name]):] == ("made_up_count",) and cell.metric_spec("made_up_count") == {"reader": "made_up_reader"}
    # what the helper refuses: a name missing, a name moved, a newcomer in front
    assert not holds_at_least(["a", "c"], ["a", "b"]) and not holds_at_least(["b", "a", "c"], ["a", "b"]) and not holds_at_least(["x", "a", "b"], ["a", "b"])
    assert not holds_at_least(["a", "c"], {"a", "b"}) and holds_at_least(["c", "b", "a"], {"a", "b"}) and holds_at_least(["a", "b", "c"], ["a", "b"])


def test_a_cell_and_a_metric_added_as_files_only_are_found(tmp_path):
    root = make_toy_root(tmp_path)
    bench = root / "benchmark"
    manifest = json.loads((root / "BENCHMARK.json").read_text())
    shutil.copytree(bench / "configs" / "modalities-2p7b-d6", bench / "configs" / "dummy-model")
    manifest["configs"].append({**manifest["configs"][0], "name": "dummy-model", "file": "benchmark/configs/dummy-model/train.yaml"})
    mix = json.loads((bench / "traffic" / "packed-4k.json").read_text())
    (bench / "traffic" / "dummy-short-docs.json").write_text(json.dumps({**mix, "doc_len_median": 20}))
    spec = json.loads((bench / "workloads" / f"{CELL}.json").read_text())
    spec["limits"] = {**spec["limits"], "loss_rel_gap": 1e-3, "grad_rel_error": 0.02, "param_change_rel_gap": 0.5, "loss_rise_over_window": 0.05}
    (bench / "workloads" / "dummy-cell.json").write_text(json.dumps(spec))
    manifest["workloads"].append({"name": "dummy-cell", "config": "dummy-model", "traffic": "dummy-short-docs", "chips": 1, "why": "a test"})
    for metric in manifest["end_to_end"]:
        if CELL in metric.get("workloads", []):
            metric["workloads"].append("dummy-cell")
    (bench / "readers" / "dummy_reader.py").write_text(
        "def read(spec, observed, trace, env):\n    return spec['times'] * observed['steps_in_window']\n")
    (bench / "metrics" / "dummy_steps_x3.json").write_text(json.dumps({"reader": "dummy_reader", "times": 3}))
    manifest["per_layer"].append({"name": "dummy_steps_x3", "unit": "steps", "better": "higher", "source": "program_counter",
                                  "layer": "trainer loop", "moves": "train_tokens_per_s", "workloads": ["dummy-cell"]})
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))

    cell = load_cell("dummy-cell", root)
    assert cell.per_layer == ("dummy_steps_x3",) and cell.end_to_end == ("train_tokens_per_s", "setup_s")
    result = bench_run.execute("dummy-cell", 3, 0.4, trace=True, root=root, device_gate=on_the_cpu)
    assert result["metrics"]["dummy_steps_x3"]["unit"] == "steps"
    assert result["metrics"]["dummy_steps_x3"]["value"] == pytest.approx(3 * (result["attempted"] - result["failed"]))
    assert load_cell(CELL, root).per_layer == load_cell(CELL, REPO).per_layer, "the cells that were there read what they read"
