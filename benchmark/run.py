"""One run of one cell of BENCHMARK.json:

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A new process that sets up (weights and traffic from the seed, compile or cache hit,
warm-up), measures for `--seconds`, checks what the timed path produced against the
plain reference, prints each number compared beside its limit and, as the last line of
its standard output, one JSON object: `correct`, `attempted`, `failed`, `metrics`
(end to end with `--trace 0`, per layer with `--trace 1`), `device`, and with
`--trace 1` `breakdown`. Without the TPU chips the cell asks for it exits non-zero and
prints no result line: there is no CPU run.
"""

from __future__ import annotations

import time

PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from dataclasses import dataclass  # noqa: E402
from pathlib import Path  # noqa: E402

REPO = Path(__file__).resolve().parents[1]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from benchmark.manifest import Cell, load_cell, load_manifest  # noqa: E402

SCRATCH = ".bench_scratch"  # inside the checkout, listed in .gitignore
SHARES_OF_A_PEAK = ("mfu", "roofline")  # the kinds of reader whose number cannot pass 100%


@dataclass
class Context:
    cell: Cell
    seed: int
    seconds: float
    scratch: Path
    trace_dir: Path | None

    @property
    def weights_seed(self) -> int:
        """What a mode draws the WEIGHTS from: the run's seed, or the cell's own `weights_seed` (its workload file) where
        the cell's work follows its weights, as an expert cell's routing does: `--seed` then draws the corpus alone, and
        the runs of a set differ less by the draw (PERF.md section 6, PR 49)."""
        return int(self.cell.spec.get("weights_seed", self.seed))


def _units(root: Path) -> dict[str, str]:
    manifest = load_manifest(root)
    return {m["name"]: m["unit"] for m in manifest["end_to_end"] + manifest["per_layer"]}


def per_layer_metrics(cell: Cell, observed: dict, trace, device: dict) -> dict[str, float]:
    """Every per-layer metric of the cell whose reader finds something to read."""
    from benchmark.device import peaks

    env = {
        "peaks": peaks(device["kind"], cell.root) if device["platform"] == "tpu" else None,
        "chips": cell.chips, "shape": observed.get("shape"), "run": observed.get("run", {}),
        "shape_function": lambda name: cell.module("shapes", name).count,
    }
    out = {}
    for name in cell.per_layer:
        spec = cell.metric_spec(name)
        if env["peaks"] is None and spec["reader"] in SHARES_OF_A_PEAK:
            continue  # a share of a peak exists only on a device that has one
        value = cell.module("readers", spec["reader"]).read(spec, observed, trace, env)
        if value is None:
            continue
        if not math.isfinite(value):
            raise SystemExit(f"benchmark: per-layer metric {name} read {value}")
        if spec["reader"] in SHARES_OF_A_PEAK and value > 100.0:
            raise SystemExit(f"benchmark: {name} read {value:.2f}% of a peak: the operations or bytes are counted "
                             "too high, or the time leaves out part of the work")
        out[name] = value
    return out


def execute(workload: str, seed: int, seconds: float, trace: bool, root: Path = REPO, device_gate=None) -> dict:
    """Everything a run does after its arguments are read. `device_gate` is the look for
    the chips (tests replace it; the command never does). Returns the result object."""
    cell = load_cell(workload, root)
    # as `run` does, before the first jax call: the YAML's performance.xla_flags are
    # read once, at backend start-up, and are part of every cache key
    from modalities_tpu.running_env.env import configure_compilation_cache
    from modalities_tpu.running_env.xla_flags import apply_xla_flags_from_config

    apply_xla_flags_from_config(cell.yaml_path)
    cache_dir = configure_compilation_cache()

    from benchmark import xtrace
    from benchmark.compile_log import CompileLog
    from benchmark.device import require_tpu

    compile_log = CompileLog()
    device = (device_gate or require_tpu)(cell.chips)
    scratch = Path(root) / SCRATCH / cell.name
    shutil.rmtree(scratch, ignore_errors=True)
    (scratch / "data").mkdir(parents=True)
    ctx = Context(cell=cell, seed=int(seed), seconds=float(seconds), scratch=scratch,
                  trace_dir=scratch / "trace" if trace else None)
    print(f"[run] cell {cell.name} ({cell.config_name} x {cell.traffic_name}, {cell.chips} chip(s)), seed {seed}, "
          f"{seconds} s, trace {int(trace)}; compile cache at {cache_dir}", flush=True)

    started_in = os.getcwd()
    try:
        observed = cell.module("modes", cell.mode).run(ctx)  # works from the scratch directory: the YAMLs' paths are relative
    finally:
        os.chdir(started_in)  # a caller that goes on living (a test) keeps its working directory

    start, end = observed["window"]
    observed["window_s"] = end - start
    setup_s = start - PROCESS_START
    inside = compile_log.between(start, end)
    compared = list(observed["compared"])
    compared.append({"name": "compiles_inside_window", "value": len(inside), "limit": 0, "ok": not inside,
                     "functions": [c["function"] for c in inside]})
    for row in compared:
        print("[compared] " + json.dumps(row), flush=True)
    print(f"[run] set-up {setup_s:.2f} s, window {observed['window_s']:.3f} s, reference {observed.get('reference_s', 0):.2f} s, "
          f"compiles {compile_log.summary()}", flush=True)

    units = _units(root)
    device_line = {"platform": device["platform"], "kind": device["kind"], "count": device["count"],
                   "memory_peak_bytes": int(observed["memory_peak_bytes"])}
    result = {"correct": all(row["ok"] for row in compared), "attempted": int(observed["attempted"]),
              "failed": int(observed["failed"])}
    if trace:
        reduced = None
        if observed.get("trace_window") is not None:
            reduced = xtrace.load(xtrace.find_xplane(ctx.trace_dir))
            if not reduced.devices:
                reduced = None
        values = per_layer_metrics(cell, observed, reduced, device)
        if reduced is not None:
            t0, t1 = reduced.window
            device_line["busy_s"] = xtrace.busy_seconds(reduced)
            device_line["window_s"] = t1 - t0
            result["breakdown"] = xtrace.breakdown(reduced)
    else:
        values = {name: observed["end_to_end"][name] for name in cell.end_to_end if name != "setup_s"}
        values["setup_s"] = setup_s
    result["metrics"] = {name: {"value": value, "unit": units[name]} for name, value in values.items()}
    result["device"] = device_line
    shutil.rmtree(scratch, ignore_errors=True)
    return result


def main(argv=None) -> None:
    parser = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)
    result = execute(args.workload, args.seed, args.seconds, bool(args.trace))
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
