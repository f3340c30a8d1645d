"""`benchmark/tools/control.py` for a cell of mode `train_looped`: read what the limits
of `correct` are set from, on the chip at the cell's own size: the numbers the control
gives (the reference computed with int8 kernels, the nearest precision below the
bfloat16 the configuration states) as `compare` would judge them.

    python benchmark/tools/control_looped.py --workload train-ouro-2p6b-4k --seeds 11,12,13

The control is SIMULATED, as the other cells' are: the train path has no lower-precision
path of its own, so nothing of the program runs here. Per seed the tool packs the
corpus, takes the first batches in the stream's order, follows them with the reference
on int8 kernels (the gate's float32 vector and the norm scales as they are) and in float32, and prints the control's numbers beside the limits. The
control computes in float32 and so carries no bfloat16 compute noise; the program's own
numbers come from runs of benchmark/run.py, which print them (PERF.md section 2).
"""

import argparse
import json
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))


def control(cell, seeds) -> None:
    import numpy as np
    import yaml

    from benchmark.reference import looped_decoder_f32 as reference
    from benchmark.weights_looped import LoopedShape

    mode = cell.module("modes", "train_looped")
    raw = yaml.safe_load(cell.yaml_path.read_text())
    shape = LoopedShape.from_yaml(raw)
    profile, mesh = raw["settings"]["step_profile"], raw["device_mesh"]["config"]
    seq = int(profile["sequence_length"])
    rows = int(profile["local_train_micro_batch_size"]) * int(mesh["data_parallel_shard_degree"])
    generator = cell.module("traffic", cell.traffic["generator"])
    scratch = cell.root / ".bench_scratch" / f"{cell.name}-control"
    hyper = mode.hyperparameters(raw)
    hyper["lr"] = hyper["lr"][: mode.CHECK_STEPS]
    for seed in seeds:
        t0 = time.perf_counter()
        generator.generate({**cell.traffic, "sequences": 4 * rows}, seed, scratch / "train.pbin",
                           vocab_size=shape.vocab_size, sequence_length=seq)
        raw_bytes = (scratch / "train.pbin").read_bytes()
        stream = np.frombuffer(raw_bytes[12 : 12 + int.from_bytes(raw_bytes[:8], "little")], dtype="<u2").astype(np.int32)
        batches = []
        for step in range(mode.CHECK_STEPS):
            starts = [(step * rows + r) * seq for r in range(rows)]
            batches.append((np.stack([stream[s : s + seq] for s in starts]), np.stack([stream[s + 1 : s + seq + 1] for s in starts])))
        got = reference.train_steps(shape, seed, batches, hyper, precision="int8", keep_first_grad=True)
        want = reference.train_steps(shape, seed, batches, hyper, other_first_grad=got.pop("first_grad"))
        got.update(loss_start=0.0, loss_end=0.0)
        judged = mode.judged_with_exits(got, want, cell.spec["limits"])
        print("[control] " + json.dumps({"seed": seed, "seconds": round(time.perf_counter() - t0, 1),
                                         **{row["name"]: row["value"] for row in judged},
                                         "param_change_leaf": next(r for r in judged if r["name"].startswith("param_change"))["leaf"],
                                         "first_grad_errors": next(r for r in judged if "pooled" in r)}), flush=True)


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    args = parser.parse_args()

    from benchmark.device import require_tpu
    from benchmark.manifest import load_cell

    cell = load_cell(args.workload, REPO)
    print(f"[control] {require_tpu(cell.chips)}", flush=True)
    if cell.mode != "train_looped":
        raise SystemExit(f"control_looped: the cell's mode is {cell.mode!r}; control.py, control_hybrid.py and control_moe.py read the other cells")
    control(cell, [int(s) for s in args.seeds.split(",")])


if __name__ == "__main__":
    main()
