"""From the result lines of two sets of runs to the bound the contract asks for.

    python benchmark/tools/spread.py set1/*.out -- set2/*.out

Each file's last line is a result of benchmark/run.py. For each end-to-end metric the
spread of a set is the distance between its first and third quartile
(`statistics.quantiles(values, n=4)`) as a share of its median; the bound is about five
times the wider of the two sets' spreads, never under 1%. Also printed: both medians and
how far the second lies from the first (the driver wants that inside the bound), and for
the driver's own tightness rule the mean of the two spreads with each set's run farthest
from its median left out.
"""

import json
import statistics
import sys


def last_result(path: str) -> dict:
    lines = [line for line in open(path).read().splitlines() if line.startswith('{"correct"')]
    if not lines:
        raise SystemExit(f"{path}: no result line")
    return json.loads(lines[-1])


def spread(values: list[float]) -> float:
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def trimmed(values: list[float]) -> list[float]:
    middle = statistics.median(values)
    return sorted(values, key=lambda v: abs(v - middle))[:-1]


def main() -> None:
    args = sys.argv[1:]
    cut = args.index("--")
    sets = [[last_result(p) for p in group] for group in (args[:cut], args[cut + 1:])]
    for group in sets:
        assert all(r["correct"] for r in group), [r["correct"] for r in group]
    for name in sets[0][0]["metrics"]:
        values = [[r["metrics"][name]["value"] for r in group] for group in sets]
        if name == "setup_s":  # each side's first run compiles; it is recorded apart
            values = [v[1:] for v in values]
        spreads = [spread(v) for v in values]
        medians = [statistics.median(v) for v in values]
        tight = statistics.mean(spread(trimmed(v)) for v in values)
        print(json.dumps({
            "metric": name, "medians": medians, "second_vs_first": medians[1] / medians[0] - 1,
            "spreads": spreads, "bound_from_spread": max(0.01, 5 * max(spreads)),
            "driver_tightness_spread": tight, "all_runs_spread": spread(values[0] + values[1]),
            "values": values,
        }))


if __name__ == "__main__":
    main()
