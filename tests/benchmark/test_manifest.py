"""BENCHMARK.json against the contract it was written to, and against the files it names."""

import json
import re
from pathlib import Path

import pytest

from benchmark.run import SHARES_OF_A_PEAK

REPO = Path(__file__).resolve().parents[2]
MANIFEST = json.loads((REPO / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
WIDTH_WORDS = ("hidden", "intermediate", "latent", "state", "projection", "head_size", "head_dim", "expansion", "n_embd")

CELLS = {w["name"]: w for w in MANIFEST["workloads"]}
END_TO_END = {m["name"]: m for m in MANIFEST["end_to_end"]}
PER_LAYER = {m["name"]: m for m in MANIFEST["per_layer"]}


def reporting(metric: dict) -> set:
    return set(metric.get("workloads", CELLS))


def test_top_level_keys_and_limits():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    assert MANIFEST["command"][:2] == ["python3", "benchmark/run.py"] and len(MANIFEST["command"]) <= 32
    assert MANIFEST["paths"] == ["benchmark", "tests/benchmark"]
    assert isinstance(MANIFEST["run_seconds"], int) and 1 <= MANIFEST["run_seconds"] <= 51
    assert len((REPO / "BENCHMARK.json").read_bytes()) <= 64 * 1024
    # a full check of 24 cells at this length has to fit the driver's budget
    assert (2 + 14 * 24) * (MANIFEST["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


@pytest.mark.parametrize("entry", MANIFEST["configs"], ids=lambda c: c["name"])
def test_configuration_entry(entry):
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(entry["name"]) and entry["source"].startswith("https://")
    assert entry["file"].startswith("benchmark/") and (REPO / entry["file"]).is_file()
    assert any(w["config"] == entry["name"] for w in MANIFEST["workloads"]), "every configuration is used by a cell"
    assert len(entry["reduced"]) <= 16 and all(NAME.match(k) for k in entry["reduced"])
    assert not [k for k in entry["reduced"] if k.endswith(("_dim", "_rank")) or any(w in k for w in WIDTH_WORDS)]
    assert 1 <= len(entry["why"]) <= 200 and "\n" not in entry["why"]
    meta = json.loads(((REPO / entry["file"]).parent / "meta.json").read_text())
    assert meta["source"] == entry["source"] and set(entry["reduced"]) == set(meta["reduced"])
    assert {"stands_for", "assumed", "memory_analysis"} <= set(meta)


@pytest.mark.parametrize("cell", MANIFEST["workloads"], ids=lambda w: w["name"])
def test_cell_entry_and_its_files(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert all(NAME.match(cell[k]) for k in ("name", "config", "traffic")) and cell["chips"] in (1, 4)
    assert 1 <= len(cell["why"]) <= 200 and "\n" not in cell["why"] and "\t" not in cell["why"]
    assert cell["config"] in {c["name"] for c in MANIFEST["configs"]}
    spec = json.loads((REPO / "benchmark" / "workloads" / f"{cell['name']}.json").read_text())
    mix = json.loads((REPO / "benchmark" / "traffic" / f"{cell['traffic']}.json").read_text())
    assert (REPO / "benchmark" / "traffic" / f"{mix['generator']}.py").is_file()
    assert (REPO / "benchmark" / "modes" / f"{mix['mode']}.py").is_file()
    config_file = next(c["file"] for c in MANIFEST["configs"] if c["name"] == cell["config"])
    assert ((REPO / config_file).parent / spec["yaml"]) == REPO / config_file
    assert spec["limits"], "every cell states the limits `correct` is decided by"
    reports = [m for m in END_TO_END.values() if cell["name"] in reporting(m)]
    assert {"setup_s"} < {m["name"] for m in reports}, "set-up and at least one other end-to-end metric"
    assert any(cell["name"] in reporting(m) for m in PER_LAYER.values())


def test_cells_are_unique_and_few_take_four_chips():
    pairs = [(w["config"], w["traffic"]) for w in MANIFEST["workloads"]]
    assert len(set(pairs)) == len(pairs) == len(CELLS) <= 24
    four = sum(w["chips"] == 4 for w in MANIFEST["workloads"])
    assert four <= max(1, len(CELLS) // 4)


@pytest.mark.parametrize("metric", MANIFEST["end_to_end"], ids=lambda m: m["name"])
def test_end_to_end_metric(metric):
    assert set(metric) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"]) and metric["better"] in ("lower", "higher")
    assert metric["source"] in ("host_clock", "device_trace") and 0.01 <= metric["bound"] <= 0.1
    assert reporting(metric) <= set(CELLS)


def test_setup_is_reported_by_every_cell():
    assert "workloads" not in END_TO_END["setup_s"] and END_TO_END["setup_s"]["bound"] <= 0.1


@pytest.mark.parametrize("metric", MANIFEST["per_layer"], ids=lambda m: m["name"])
def test_per_layer_metric_and_its_arrow(metric):
    assert set(metric) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"]) and metric["source"] in SOURCES
    assert 1 <= len(metric["layer"]) <= 200 and "\n" not in metric["layer"]
    moved = END_TO_END[metric["moves"]]
    assert reporting(metric) <= reporting(moved), "every cell of a layer metric reports the end-to-end metric it moves"
    spec = json.loads((REPO / "benchmark" / "metrics" / f"{metric['name']}.json").read_text())
    if spec["reader"] in SHARES_OF_A_PEAK:  # the readers the harness holds to 100%: the contract's names and unit
        assert metric["unit"] == "%" and ("mfu" in metric["name"] or metric["name"].endswith("_roofline"))
    else:
        assert "mfu" not in metric["name"] and "roofline" not in metric["name"], "a share of a peak is read by a reader held to 100%"
    assert (REPO / "benchmark" / "readers" / f"{spec['reader']}.py").is_file()
    if "shape_function" in spec:
        assert (REPO / "benchmark" / "shapes" / f"{spec['shape_function']}.py").is_file()


def test_names_are_unique_and_layers_are_perf_mds():
    names = list(END_TO_END) + list(PER_LAYER)
    assert len(names) == len(MANIFEST["end_to_end"]) + len(MANIFEST["per_layer"]) == len(set(names))
    perf = (REPO / "PERF.md").read_text()
    for layer in {m["layer"] for m in MANIFEST["per_layer"]}:
        assert f"| {layer} |" in perf, f"PERF.md's list of layers lacks {layer!r}"


def test_every_file_under_paths_has_a_plain_name():
    allowed = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for root in MANIFEST["paths"]:
        for path in (REPO / root).rglob("*"):
            if "__pycache__" in path.parts or path.suffix == ".pyc":
                continue
            assert allowed.match(str(path.relative_to(REPO))), path
    for path in (REPO / "benchmark" / "traffic").glob("*"):
        if path.is_file() and path.suffix != ".py":
            assert path.suffix in (".json", ".jsonl", ".toml", ".txt", ".csv")
