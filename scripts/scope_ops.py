"""A traced run of a benchmark cell, and the device operations under some scopes one by one: which HLO
instruction, its kind, how often a step, its own device time a step, its result's shape and layout.

`benchmark/run.py --trace 1` prints a step's time by scope and throws the trace away; this runs the same
`benchmark.run.execute` and reads the trace before it goes (`benchmark/xscope.py`'s table from the profile itself, the
whole executions of the step program, own time as `benchmark/xtrace.py` counts it), so that a scope's time can be
split into the compiler's fusions, copies and kernels: what PERF.md section 5 quotes for `gdn/qk_norm` and
`gdn/out_norm` (PR 46). A device event is named by its whole HLO instruction, so the shapes and layouts are the
executable's own. The builder's tool, not a cell: nothing in `benchmark/` reads it.

Usage (TPU): chiprun -- python3 scripts/scope_ops.py --workload train-qwen3next-80b-16k --seed 7 --seconds 20 \
                 --scopes gdn/qk_norm,gdn/out_norm --out chiprun_out/scope_ops.txt [--root .bench_checkout/parent]
"""

from __future__ import annotations

import argparse
import json
import re
import sys
import types
from pathlib import Path


def listing(trace_dir: Path, program: str, scopes: list[str], width: int) -> str:
    from benchmark import xscope, xtrace

    xplane = xtrace.find_xplane(trace_dir)
    table = xscope.table_from_profile(xplane, program)
    if table is None:
        return f"{xplane}: names no operation's scope"
    trace = xtrace.load(xplane)
    rows: dict[str, list] = {}  # instruction -> [own seconds, events, scope path, the event's name]
    by_label: dict[str, dict[str, float]] = {scope: {} for scope in scopes}
    executions = 0
    for device in trace.devices:
        runs, _ = xscope.whole_runs(device, program)
        executions += len(runs)
        edges = sorted((run.start, run.end) for run in runs)
        for event, own in xtrace.self_seconds(device.ops):
            if not any(start <= event.start < end for start, end in edges):
                continue
            path = xscope.path_of(event, table)
            scope = next((scope for scope in scopes if scope in path), None)
            if scope is None:
                continue
            row = rows.setdefault(xscope.instruction_of(event.name), [0.0, 0, path, event.name])
            row[0] += own
            row[1] += 1
            label = xtrace.op_label(event)
            by_label[scope][label] = by_label[scope].get(label, 0.0) + own
    if not executions:
        return f"{xplane}: no whole execution of a program named like {program!r}"
    lines = [f"{xplane}: {executions} whole execution(s) of {program}; own device ms a step"]
    for scope in scopes:
        total = sum(by_label[scope].values()) / executions
        lines.append(f"[ops] {scope}: {total * 1e3:.3f} ms; by label: " + json.dumps(
            {label: round(s / executions * 1e3, 3) for label, s in sorted(by_label[scope].items(), key=lambda kv: -kv[1])}))
    for instruction, (own, events, path, name) in sorted(rows.items(), key=lambda kv: -kv[1][0]):
        passes = "recomputed" if "rematted_computation" in path else "backward" if "transpose(jvp" in path else "forward"
        lines.append(f"[ops] {own / executions * 1e3:>8.3f} ms {events / executions:>5.1f} x  {passes:<10} {'/'.join(path.split('/')[-3:])}")
        lines.append("          " + re.sub(r", metadata=\{.*?\}", "", name)[:width])
    return "\n".join(lines)


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=7)
    parser.add_argument("--seconds", type=float, default=20.0)
    parser.add_argument("--scopes", required=True, help="comma-separated parts of scope paths")
    parser.add_argument("--program", default="train_step")
    parser.add_argument("--root", type=Path, default=Path(__file__).resolve().parents[1], help="the checkout to run (its benchmark and its program)")
    parser.add_argument("--out", type=Path, required=True)
    parser.add_argument("--width", type=int, default=420, help="characters of an instruction's text kept")
    args = parser.parse_args()

    root = args.root.resolve()
    sys.path.insert(0, str(root))
    import shutil

    from benchmark import run

    out = args.out.resolve()
    out.parent.mkdir(parents=True, exist_ok=True)

    def read_then_remove(path, **how):
        if (Path(path) / "trace").exists():
            out.write_text(listing(Path(path) / "trace", args.program, args.scopes.split(","), args.width) + "\n")
        shutil.rmtree(path, **how)

    run.shutil = types.SimpleNamespace(rmtree=read_then_remove)  # `execute` empties its scratch as its last act
    result = run.execute(args.workload, args.seed, args.seconds, True, root=root)
    print(json.dumps(result), flush=True)
    print(out.read_text()[:6000], flush=True)


if __name__ == "__main__":
    main()
