"""Print a trace's device time by scope: per execution of a program, the own time of its
operations by the scope path each carries (the vocabulary is
modalities_tpu/telemetry/scopes.py), and by the buckets of a rules file where one is given.
For any trace and any program, train or serve: whoever builds the next cell reads its
decode or prefill program this way before writing rules for it.

    python benchmark/tools/describe_scopes.py <trace dir or .xplane.pb> --program train_step --rules train_dense
    python benchmark/tools/describe_scopes.py <trace> --program decode --table table.json --top 60

The table comes from the profile itself (a TPU's xplane names every operation's `op_name`).
`--table` takes another: a JSON object {instruction: op_name} as `StepFunctions.scope_table`
or `ServingEngine.scope_table` return it, or the text of an optimized HLO module
(`compiled.as_text()`), which the program's own `perfscope.scope_table` reads.
"""

import argparse
import json
import re
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmark import xscope, xtrace  # noqa: E402

NO_RULES = {name: [(re.compile(""), xscope.UNATTRIBUTED)] for name in xscope.LISTS}


def read_table(path: Path) -> dict[str, str]:
    text = Path(path).read_text()
    if text.lstrip().startswith("{"):
        return json.loads(text)
    from modalities_tpu.telemetry.perfscope import scope_table

    return scope_table(text)


def by_scope(found: xscope.ScopeTime, top: int) -> str:
    """Own milliseconds per execution by scope path without its plumbing, the largest first."""
    from modalities_tpu.telemetry.scopes import scope_path

    merged: dict[str, float] = {}
    for path, seconds in found.scopes.items():
        key = path if path.startswith(xscope.NO_OP_NAME) else f"{scope_path(path)} [{path.rsplit('/', 1)[-1]}]"
        merged[key] = merged.get(key, 0.0) + seconds
    rows = sorted(merged.items(), key=lambda kv: -kv[1])
    lines = [f"{'ms':>10} {'share':>7}  scope [primitive]"]
    lines += [f"{s * 1e3:>10.3f} {s / found.busy_s:>7.2%}  {k}" for k, s in rows[:top]]
    if len(rows) > top:
        rest = sum(s for _, s in rows[top:])
        lines.append(f"{rest * 1e3:>10.3f} {rest / found.busy_s:>7.2%}  ({len(rows) - top} more)")
    return "\n".join(lines)


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    parser.add_argument("trace", type=Path)
    parser.add_argument("--program", required=True, help="regular expression over the names on the trace's 'XLA Modules' line")
    parser.add_argument("--rules", help="a file of benchmark/scopes/ (without .json), for the buckets")
    parser.add_argument("--table", type=Path, help="a scope table, where the profile's own is not wanted or not there")
    parser.add_argument("--top", type=int, default=40)
    args = parser.parse_args()

    xplane = args.trace if args.trace.is_file() else xtrace.find_xplane(args.trace)
    table = read_table(args.table) if args.table else xscope.table_from_profile(xplane, args.program)
    if table is None:
        raise SystemExit(f"describe_scopes: {xplane} names no operation's scope (not a TPU's profile, or tensorflow's "
                         "xplane_pb2 is not installed): give --table")
    rules = xscope.load_rules(ROOT / "benchmark" / "scopes" / f"{args.rules}.json") if args.rules else NO_RULES
    found = xscope.scope_time(xtrace.load(xplane), table, rules, args.program)
    print(f"{xplane}: table of {len(table)} instructions")
    if args.rules:
        print(xscope.describe(found, top=0))
    else:
        print(f"{found.executions} whole execution(s) of {found.seen}; busy {found.busy_s * 1e3:.3f} ms per execution")
    print(by_scope(found, args.top))


if __name__ == "__main__":
    main()
