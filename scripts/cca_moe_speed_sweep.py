"""Read what the cell `train-zaya1-8b-8k` does at several `bias_update_speed`s, on the chip: for every speed a copy of the
benchmark's files under `.bench_checkout/speed_<speed>/` with that one number changed in the cell's YAML, and for every seed
one whole run of `benchmark/run.py` there (its own process, the compile cache shared). Prints, and writes to
`chiprun_out/speed_sweep.jsonl`, one line a run: speed, seed, `correct`, `train_tokens_per_s`, `setup_s`, the rows compared,
and the run's `[train]` lines that say how the routing went; then per speed the median and the interquartile range of
`train_tokens_per_s` over its seeds as a share of the median (the spread the driver admits a cell by). PERF.md section 4 has the readings
the cell's speed was chosen from (PR 40).

    chiprun --timeout 3000 -- python3 scripts/cca_moe_speed_sweep.py --speeds 0.001,0.01 --seeds 2147500701,2147500702
"""

import argparse
import json
import os
import shutil
import statistics
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]
CELL, CONFIG = "train-zaya1-8b-8k", "zaya1-8b-ep2"


def root_at(speed: str, sequences: int | None) -> Path:
    root = REPO / ".bench_checkout" / f"speed_{speed}"
    shutil.rmtree(root, ignore_errors=True)
    shutil.copytree(REPO / "benchmark", root / "benchmark", ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", root / "BENCHMARK.json")
    path = root / "benchmark" / "configs" / CONFIG / "train.yaml"
    raw = json.loads(path.read_text())
    raw["model_raw"]["config"]["moe_config"]["bias_update_speed"] = float(speed)
    path.write_text(json.dumps(raw, indent=1))
    if sequences is not None:
        mix = root / "benchmark" / "traffic" / "packed-8k-cca-moe.json"
        mix.write_text(json.dumps({**json.loads(mix.read_text()), "sequences": sequences}))
    return root


def spread(values: list[float]) -> float:
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    parser.add_argument("--speeds", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--seconds", type=float, default=40.0)
    parser.add_argument("--sequences", type=int, default=None, help="the traffic mix's `sequences`, where another than the cell's is to be read")
    args = parser.parse_args()
    out = REPO / "chiprun_out"
    out.mkdir(exist_ok=True)
    rates: dict[str, list[float]] = {}
    with open(out / "speed_sweep.jsonl", "a") as sink:
        for speed in args.speeds.split(","):
            root = root_at(speed, args.sequences)
            for seed in args.seeds.split(","):
                done = subprocess.run([sys.executable, str(root / "benchmark" / "run.py"), "--workload", CELL, "--seed", seed,
                                       "--seconds", str(args.seconds), "--trace", "0"], cwd=root, capture_output=True, text=True,
                                      env={**os.environ, "PYTHONPATH": str(REPO)})
                (out / f"speed_{speed}_seed_{seed}.log").write_text(done.stdout + "\n--- stderr ---\n" + done.stderr[-20000:])
                lines = done.stdout.splitlines()
                line = {"speed": float(speed), "seed": int(seed), "rc": done.returncode}
                if done.returncode == 0:
                    result = json.loads(lines[-1])
                    line.update(correct=result["correct"], memory_peak_bytes=result["device"]["memory_peak_bytes"],
                                **{name: m["value"] for name, m in result["metrics"].items()})
                    rates.setdefault(speed, []).append(result["metrics"]["train_tokens_per_s"]["value"])
                line["compared"] = {row["name"]: row["value"] for row in (json.loads(x[len("[compared] "):]) for x in lines if x.startswith("[compared] "))}
                line["said"] = [x for x in lines if x.startswith("[train] ") and ("steps in the window" in x or "step by step" in x or "read and not held" in x or "plan" in x)]
                if done.returncode != 0:
                    line["stderr"] = done.stderr[-3000:]
                print(json.dumps(line), flush=True)
                sink.write(json.dumps(line) + "\n")
                sink.flush()
    for speed, values in rates.items():
        if len(values) >= 2:
            print(json.dumps({"speed": float(speed), "runs": len(values), "median_tokens_per_s": statistics.median(values),
                              "iqr_over_median": spread(values), "min": min(values), "max": max(values)}), flush=True)


if __name__ == "__main__":
    main()
