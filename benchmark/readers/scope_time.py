"""Own device time per step by scope (benchmark/xscope.py): the operations of the whole
executions of `program` in the run's trace, joined to the scope path the profile holds
for each and sorted by the rules file `rules` (benchmark/scopes/<rules>.json). `list`
and `buckets` name what is summed, in milliseconds per execution; `share_of_busy` gives
instead the time that either list leaves unattributed, as a percentage of busy time.

The reader is handed the reduced trace and no path, so it finds the xplane itself,
under the root it serves. The first metric of a run reads the trace, prints the whole
table of buckets and keeps the result with what the run observed, for the others. It
returns nothing where there is no device trace or no operation names its scope (a
profile from another kind of device), and raises only where a number would be a lie: a
trace with no whole execution of the program.
"""

from pathlib import Path

from benchmark import xscope, xtrace

ROOT = Path(__file__).resolve().parents[2]
SCRATCH = ".bench_scratch"  # benchmark/run.py keeps a run's trace under <root>/.bench_scratch/<cell>/trace until the readers are done


def _found(spec: dict, observed: dict, trace):
    kept = observed.setdefault("scope_time", {})
    key = (spec["rules"], spec["program"])
    if key not in kept:
        kept[key] = None
        xplanes = sorted((ROOT / SCRATCH).glob("*/trace/plugins/profile/*/*.xplane.pb"), key=lambda f: f.stat().st_mtime)
        table = xscope.table_from_profile(xplanes[-1], spec["program"]) if xplanes else None  # the newest: this run's
        if table is not None:
            rules = xscope.load_rules(ROOT / "benchmark" / "scopes" / f"{spec['rules']}.json")
            kept[key] = xscope.scope_time(trace, table, rules, spec["program"])
            print(xscope.describe(kept[key]), flush=True)
            print(f"[scope] busy over the whole trace (xtrace.busy_seconds): {xtrace.busy_seconds(trace) * 1e3:.3f} ms; "
                  f"scope table of {len(table)} instructions", flush=True)
    return kept[key]


def read(spec: dict, observed: dict, trace, env: dict):
    if trace is None or not trace.devices:
        return None
    found = _found(spec, observed, trace)
    if found is None:
        return None
    if spec.get("share_of_busy") == "unattributed":
        return 100.0 * found.unattributed_s / found.busy_s
    buckets = found.lists[spec["list"]]
    return 1e3 * sum(buckets.get(bucket, 0.0) for bucket in spec["buckets"])
