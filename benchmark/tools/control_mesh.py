"""Read what the limits of `correct` are set from in a cell of mode `train_mesh`, on the chips at the cell's own size:
the numbers the control gives (the reference computed with int8 weights, the nearest precision below the bfloat16 the
configuration states), and the numbers the cell's own fault gives.

    python benchmark/tools/control_mesh.py --workload train-2p7b-4k-x4 --seeds 11,12,13
    python benchmark/tools/control_mesh.py --workload train-2p7b-4k-x4 --seeds 11 --variant unreduced
    python benchmark/tools/control_mesh.py --workload train-2p7b-4k-x4 --seeds 11 --variant unreduced_program

`benchmark/tools/control.py` cannot take the mesh: its reference holds the whole float32 model on one chip. This is that
tool over `benchmark/reference/dense_decoder_f32_mesh.py`. `int8` (the default) is simulated as there: nothing of the
program runs; per seed the tool packs the corpus, takes the first two batches in the stream's order, both rows of each,
follows them with the reference in float32 and with the reference on int8 weights, and prints the control's numbers as
the mode's `compare` would judge them.

The fault that is this cell's own is a gradient that is not reduced over `dp_shard`: each group stepping on its own rows.
Under GSPMD no line of the program holds that reduction (the partitioner derives it from the mean over the batch), so
the fault is put where it can be. `unreduced` puts the float32 reference itself in the control's place, following the
first group's rows alone (what that group would step on without the reduction), against the reference on every row:
a minute a seed beside the int8 control, in the same process (`--variant int8,unreduced`). `unreduced_program` runs the
PROGRAM: the step is handed batches in which every group's rows are the first group's, while the reference follows
the rows the loader handed out; one whole run of the cell (`--seconds`) a seed, the rows it fails in its `[compared]`
lines (`tests/benchmark/test_rehearsal_train_mesh.py` does the same at toy size on CPU devices).
"""

import argparse
import json
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))


def reference_controls(cell, seeds, variants) -> None:
    import jax
    import numpy as np
    import yaml

    from benchmark.reference import dense_decoder_f32_mesh as reference
    from benchmark.weights import DecoderShape

    mode = cell.module("modes", "train_mesh")
    raw = yaml.safe_load(cell.yaml_path.read_text())
    shape = DecoderShape.from_model_config(raw["model_raw"]["config"])
    profile, mesh = raw["settings"]["step_profile"], raw["device_mesh"]["config"]
    seq, groups = int(profile["sequence_length"]), int(mesh["data_parallel_shard_degree"])
    rows = int(profile["local_train_micro_batch_size"]) * groups
    devices = jax.devices()[: int(mesh["world_size"])]
    generator = cell.module("traffic", cell.traffic["generator"])
    scratch = REPO / ".bench_scratch" / f"{cell.name}-control"
    hyper = mode.hyperparameters(raw)
    for seed in seeds:
        generator.generate({**cell.traffic, "sequences": 4 * rows}, seed, scratch / "train.pbin", vocab_size=shape.vocab_size, sequence_length=seq)
        raw_bytes = (scratch / "train.pbin").read_bytes()
        stream = np.frombuffer(raw_bytes[12: 12 + int.from_bytes(raw_bytes[:8], "little")], dtype="<u2").astype(np.int32)
        batches = []
        for step in range(mode.CHECK_STEPS):
            starts = [(step * rows + r) * seq for r in range(rows)]
            batches.append((np.stack([stream[s: s + seq] for s in starts]), np.stack([stream[s + 1: s + seq + 1] for s in starts])))
        alone = [(tokens[: rows // groups], targets[: rows // groups]) for tokens, targets in batches]  # the first group's rows
        for variant in variants:
            t0 = time.perf_counter()
            if variant == "int8":
                got = reference.train_steps(shape, seed, batches, hyper, devices, precision="int8", keep_first_grad=True)
            else:
                got = reference.train_steps(shape, seed, alone, hyper, devices, keep_first_grad=True)
            want = reference.train_steps(shape, seed, batches, hyper, devices, other_first_grad=got.pop("first_grad"))
            got.update(loss_start=0.0, loss_end=0.0)
            judged = mode.compare(got, want, cell.spec["limits"])
            print("[control] " + json.dumps({"variant": variant, "seed": seed, "seconds": round(time.perf_counter() - t0, 1),
                                             **{row["name"]: row["value"] for row in judged},
                                             "failed": [row["name"] for row in judged if not row["ok"]],
                                             "first_grad_errors": next(r for r in judged if "pooled" in r)}), flush=True)


def first_groups_rows(step, groups: int):
    """`step` handed batches in which every `dp_shard` group's rows are the first group's: what that group would step on alone."""

    def broken(state, batch):
        def alone(v):  # [accumulation, rows, ...], the rows group by group
            per_group = v.shape[1] // groups
            return v.at[:, per_group:].set(v[:, :per_group].repeat(groups - 1, axis=1))

        return step(state, {part: {k: alone(v) for k, v in batch[part].items()} for part in ("samples", "targets")})

    return broken


def unreduced_program(cell, seeds, seconds: float) -> None:
    import yaml

    from modalities_tpu.main import Main

    from benchmark import run as bench_run

    groups = int(yaml.safe_load(cell.yaml_path.read_text())["device_mesh"]["config"]["data_parallel_shard_degree"])
    build = Main.build_step_functions

    def broken(components, *args, **kwargs):
        fns = build(components, *args, **kwargs)
        fns.train_step = first_groups_rows(fns.train_step, groups)
        return fns

    Main.build_step_functions = staticmethod(broken)
    for seed in seeds:
        result = bench_run.execute(cell.name, seed, seconds, trace=False)
        print("[control] " + json.dumps({"variant": "unreduced_program", "seed": seed, "correct": result["correct"]}), flush=True)


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--variant", default="int8", help="int8 | unreduced | both as int8,unreduced | unreduced_program")
    parser.add_argument("--seconds", type=float, default=4.0, help="the window of a run of the broken program")
    args = parser.parse_args()

    from benchmark.manifest import load_cell

    cell = load_cell(args.workload, REPO)
    if cell.mode != "train_mesh":
        raise SystemExit(f"control_mesh: no control for mode {cell.mode!r}")
    seeds, variants = [int(s) for s in args.seeds.split(",")], args.variant.split(",")
    if variants == ["unreduced_program"]:
        unreduced_program(cell, seeds, args.seconds)  # `execute` looks for the chips itself
        return
    if not set(variants) <= {"int8", "unreduced"}:
        raise SystemExit(f"control_mesh: no variant {args.variant!r}")
    from benchmark.device import require_tpu

    print(f"[control] {require_tpu(cell.chips)}", flush=True)
    reference_controls(cell, seeds, variants)


if __name__ == "__main__":
    main()
