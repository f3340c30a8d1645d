"""`benchmark/tools/control.py` for a cell of mode `train_cca_moe`: read what the limits
of `correct` are set from, on the chip at the cell's own size: the numbers the control
gives (the reference computed with int8 kernels, the nearest precision below the
bfloat16 the configuration states) as `compare` would judge them.

    python benchmark/tools/control_cca_moe.py --workload train-zaya1-8b-8k --seeds 11,12,13

`--variant` puts another model in the control's place, in float32: the reference's arithmetic
with one step of the equations left out, which `correct` must fail: `no_conv` (q and k are the
shared mean alone: neither convolution), `no_value_shift` (every value head from the current
position), `no_qk_mean` (q and k are the convolutions' output alone), `no_eda` (no state handed
from layer to layer), `full_rotary` (the rotary on a whole head, not its first half), `no_bias_move`
(the selection bias left where it was). What a program with that fault would read, row by row, the
routing rows the cell's file gives no limit among them.

The control is simulated, as the other cells' are: the train path has no lower-precision
path of its own, so nothing of the program runs here. Per seed the tool packs the
corpus, takes the first batches in the stream's order, follows them with the reference
on int8 kernels (or in the variant's arithmetic) and in float32, and prints the control's
numbers beside the limits. The control computes in float32 and so carries no bfloat16
compute noise; the program's own numbers come from runs of benchmark/run.py, which print
them (PERF.md section 2).
"""

import argparse
import json
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))


def control(cell, seeds, variant: str = "int8") -> None:
    import dataclasses

    import numpy as np
    import yaml

    from benchmark.reference import cca_moe_decoder_f32 as reference
    from benchmark.weights_cca_moe import CcaMoEShape

    mode = cell.module("modes", "train_cca_moe")
    raw = yaml.safe_load(cell.yaml_path.read_text())
    shape = CcaMoEShape.from_yaml(raw)
    other, precision = shape, "int8"
    if variant == "full_rotary":  # the rotary turns a whole head
        other, precision = dataclasses.replace(shape, rotated=shape.head_dim), "f32"
    elif variant == "no_bias_move":  # the selection bias stays where it was: the bias's own row, and the second step's routing, read it
        other, precision = dataclasses.replace(shape, bias_update_speed=0.0), "f32"
    elif variant != "int8":  # one step of the equations left out (`benchmark/reference/cca_moe_decoder_f32.py`, `shape.without`)
        other, precision = dataclasses.replace(shape, without=(variant,)), "f32"
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
        weights_seed = int(cell.spec.get("weights_seed", seed))  # the cell's own weights where it names them: the corpus alone follows the seed
        got = reference.train_steps(other, weights_seed, batches, hyper, precision=precision, keep_first_grad=True)
        want = reference.train_steps(shape, weights_seed, batches, hyper, other_first_grad=got.pop("first_grad"))
        got.update(loss_start=0.0, loss_end=0.0)
        tokens = rows * seq
        judged = mode.judged_with_routing(got, want, cell.spec["limits"], shape, tokens)
        print("[control] " + json.dumps({"variant": variant, "seed": seed, "seconds": round(time.perf_counter() - t0, 1),
                                         **{row["name"]: row["value"] for row in (*mode.routing_gaps(got, want, tokens), *judged)},  # held or not
                                         "failed": [row["name"] for row in judged if not row["ok"]],
                                         "grad_norm": [got["grad_norm"], want["grad_norm"]],
                                         "first_grad_by_kind": mode.by_kind_of_leaf(want["first_grad_difference_norms"], want["first_grad_norms"]),
                                         "param_change_leaf": next(r for r in judged if r["name"].startswith("param_change"))["leaf"],
                                         "first_grad_errors": next(r for r in judged if "pooled" in r)}), flush=True)


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--variant", choices=("int8", "no_conv", "no_value_shift", "no_qk_mean", "no_eda", "full_rotary", "no_bias_move"), default="int8")
    args = parser.parse_args()

    from benchmark.device import require_tpu
    from benchmark.manifest import load_cell

    cell = load_cell(args.workload, REPO)
    print(f"[control] {require_tpu(cell.chips)}", flush=True)
    if cell.mode != "train_cca_moe":
        raise SystemExit(f"control_swa_moe: the cell's mode is {cell.mode!r}; control.py, control_hybrid.py, control_moe.py, control_looped.py and control_swa_moe.py read the other cells")
    control(cell, [int(s) for s in args.seeds.split(",")], args.variant)


if __name__ == "__main__":
    main()
