"""Read what the limits of `correct` are set from, on the chip at a cell's own size:
the numbers sound runs give and the numbers the control gives (the reference computed
with int8 weights, the nearest precision below the bfloat16 the configurations state).

    python benchmark/tools/control.py --workload train-2p7b-4k --seeds 11,12,13

The control is simulated: the train path has no lower-precision path of its own
(`quant_weights` is a serving option), so nothing of the program runs here. Per seed the
tool packs the corpus, takes the first three batches in the stream's order, follows them
with the reference in float32 and with the reference on int8 weights, and prints the
control's numbers as `compare` would judge them. The control computes in float32 and so
carries no bfloat16 compute noise; the program's own numbers come from runs of
benchmark/run.py, which print them (PERF.md section 2).
"""

import argparse
import json
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))


def train_control(cell, seeds) -> None:
    import numpy as np
    import yaml

    from benchmark.reference import dense_decoder_f32 as reference
    from benchmark.weights import DecoderShape

    train = cell.module("modes", "train")
    raw = yaml.safe_load(cell.yaml_path.read_text())
    shape = DecoderShape.from_model_config(raw["model_raw"]["config"])
    profile, mesh = raw["settings"]["step_profile"], raw["device_mesh"]["config"]
    seq = int(profile["sequence_length"])
    rows = int(profile["local_train_micro_batch_size"]) * int(mesh["data_parallel_shard_degree"])
    generator = cell.module("traffic", cell.traffic["generator"])
    scratch = REPO / ".bench_scratch" / f"{cell.name}-control"
    for seed in seeds:
        t0 = time.perf_counter()
        generator.generate({**cell.traffic, "sequences": 4 * rows}, seed, scratch / "train.pbin",
                           vocab_size=shape.vocab_size, sequence_length=seq)
        raw_bytes = (scratch / "train.pbin").read_bytes()
        stream = np.frombuffer(raw_bytes[12 : 12 + int.from_bytes(raw_bytes[:8], "little")], dtype="<u2").astype(np.int32)
        batches = []
        for step in range(train.CHECK_STEPS):
            starts = [(step * rows + r) * seq for r in range(rows)]
            batches.append((np.stack([stream[s : s + seq] for s in starts]), np.stack([stream[s + 1 : s + seq + 1] for s in starts])))
        got = reference.train_steps(shape, seed, batches, train.hyperparameters(raw), precision="int8", keep_first_grad=True)
        want = reference.train_steps(shape, seed, batches, train.hyperparameters(raw), other_first_grad=got.pop("first_grad"))
        got.update(loss_start=0.0, loss_end=0.0)
        judged = train.compare(got, want, cell.spec["limits"])
        print("[control] " + json.dumps({"seed": seed, "seconds": round(time.perf_counter() - t0, 1),
                                         **{row["name"]: row["value"] for row in judged},
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
    if cell.mode != "train":
        raise SystemExit(f"control: no control for mode {cell.mode!r}")
    train_control(cell, [int(s) for s in args.seeds.split(",")])


if __name__ == "__main__":
    main()
