"""Finding the benchmark's files by the names BENCHMARK.json gives them.

    BENCHMARK.json                         cells, configurations, metrics, bounds
    benchmark/workloads/<cell>.json        the cell's YAML, its correctness limits, warm-up
    benchmark/configs/<configuration>/     the YAML(s) as run, and meta.json
    benchmark/traffic/<traffic>.json       a traffic mix: its mode, its generator, its parameters
    benchmark/traffic/<generator>.py       a seeded generator, `generate(params, seed, ...)`
    benchmark/modes/<mode>.py              how a mode is set up, driven and checked, `run(ctx)`
    benchmark/metrics/<metric>.json        a per-layer metric: its reader and the reader's arguments
    benchmark/readers/<reader>.py          a kind of reader, `read(spec, observed)`
    benchmark/shapes/<function>.py         operations and bytes from shapes, `count(shape, run)`
    benchmark/peaks.json                   the table of peaks, by device kind

A later PR adds files and manifest entries; nothing here needs an edit for a new cell,
configuration, mix, metric, reader or shape function. Every function takes the root
it looks under, so a test can point the harness at a copy with files added.
"""

from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]


def read_json(path: Path) -> dict:
    try:
        return json.loads(Path(path).read_text())
    except FileNotFoundError:
        raise SystemExit(f"benchmark: {path} is missing") from None


def load_manifest(root: Path = REPO) -> dict:
    return read_json(Path(root) / "BENCHMARK.json")


def load_module(root: Path, kind: str, name: str):
    """`benchmark/<kind>/<name>.py` under `root`, imported from its path."""
    path = Path(root) / "benchmark" / kind / f"{name}.py"
    if not path.is_file():
        raise SystemExit(f"benchmark: no {kind[:-1]} {name!r} ({path} is missing)")
    spec = importlib.util.spec_from_file_location(f"benchmark_{kind}_{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@dataclass(frozen=True)
class Cell:
    root: Path
    name: str
    chips: int
    config_name: str
    config_dir: Path
    meta: dict  # configs/<configuration>/meta.json
    traffic_name: str
    traffic: dict  # traffic/<traffic>.json
    spec: dict  # workloads/<cell>.json
    end_to_end: tuple  # names of the end-to-end metrics this cell reports
    per_layer: tuple  # names of the per-layer metrics this cell reports

    @property
    def mode(self) -> str:
        return self.traffic["mode"]

    @property
    def yaml_path(self) -> Path:
        return self.config_dir / self.spec["yaml"]

    def metric_spec(self, name: str) -> dict:
        return read_json(self.root / "benchmark" / "metrics" / f"{name}.json")

    def module(self, kind: str, name: str):
        return load_module(self.root, kind, name)


def _reported_by(metric: dict, cell_name: str, unless_listed: bool) -> bool:
    """A metric with a `workloads` list is reported by the cells it lists, one without by `unless_listed`."""
    cells = metric.get("workloads")
    return cell_name in cells if cells is not None else unless_listed


def load_cell(name: str, root: Path = REPO) -> Cell:
    root = Path(root)
    manifest = load_manifest(root)
    entries = {w["name"]: w for w in manifest["workloads"]}
    if name not in entries:
        raise SystemExit(f"benchmark: no cell {name!r} in BENCHMARK.json (cells: {sorted(entries)})")
    entry = entries[name]
    config = {c["name"]: c for c in manifest["configs"]}[entry["config"]]
    config_dir = (root / config["file"]).parent
    end_to_end = tuple(m["name"] for m in manifest["end_to_end"] if _reported_by(m, name, True))
    per_layer = tuple(m["name"] for m in manifest["per_layer"] if _reported_by(m, name, m["moves"] in end_to_end))
    return Cell(
        root=root, name=name, chips=int(entry["chips"]), config_name=entry["config"], config_dir=config_dir,
        meta=read_json(config_dir / "meta.json"), traffic_name=entry["traffic"],
        traffic=read_json(root / "benchmark" / "traffic" / f"{entry['traffic']}.json"),
        spec=read_json(root / "benchmark" / "workloads" / f"{name}.json"),
        end_to_end=end_to_end, per_layer=per_layer,
    )
