"""`benchmark/tools/control.py` for a cell of mode `train_ssd_moe`: read what the limits
of `correct` are set from, on the chip at the cell's own size: the numbers the control
gives (the reference computed with int8 kernels, the nearest precision below the
bfloat16 the configuration states) as `compare` would judge them.

    python benchmark/tools/control_ssd_moe.py --workload train-granite4h-32b-8k --seeds 11,12,13

`--variant` puts another model in the control's place, in float32: the reference's arithmetic with
one step of the equations left out (`no_decay`: a = 0; `no_skip_d`: no `D x`; `no_conv_silu`; `no_gate`: no
`silu(z)`; `no_gate_norm`; `no_dt_softplus`; `residual_1`: 1 for `residual_multiplier`; `attention_rsqrt_d`:
1 / sqrt(128) for `attention_multiplier`; `embedding_1`; `logits_1`; `no_gate_renorm`: the softmax over all 72 at
the chosen, not renormalised). What a program with that fault would read, row by row: each must fail `correct`,
or the cell's `limits_from` names it with its readings and the reason the rows cannot see it.

The control is simulated, as the other cells' are: the train path has no lower-precision
path of its own, so nothing of the program runs here. Per seed the tool packs the
corpus, takes the first batches in the stream's order, follows them with the reference
in float32 once and on int8 kernels (or with a step left out: one compiled program serves them all, the step left out
being an argument, so `--variant all` costs one compile), and prints the control's numbers beside the limits. The control computes in float32 and so carries no bfloat16 compute noise; the
program's own numbers come from runs of benchmark/run.py, which print them (PERF.md section 2).
"""

import argparse
import json
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))

# a variant's name and the step of the reference's equations it leaves out (`ssd_moe_decoder_f32.SKIPS`)
VARIANTS = {"no_decay": "decay", "no_skip_d": "skip_d", "no_conv_silu": "conv_silu", "no_gate": "gate", "no_gate_norm": "gate_norm",
            "no_dt_softplus": "dt_softplus", "residual_1": "residual_multiplier", "attention_rsqrt_d": "attention_multiplier",
            "embedding_1": "embedding_multiplier", "logits_1": "logits_scaling", "no_gate_renorm": "gate_renorm"}


def judged_against(mode, reference, shape, seed: int, batches, hyper: dict, want: dict, limits: dict, **other):
    """Another model (`precision="int8"`, or `skip=(step,)`) followed in the program's place and judged against the sound
    reference `want` (followed once, `keep_first_grad=True`, and not again for every variant): the distance of the two first
    gradients is taken here, leaf by leaf, as `train_steps(other_first_grad=...)` takes it. Returns the rows by name, what the
    other model read, and the sound side as the mode's functions want it."""
    import numpy as np

    got = reference.train_steps(shape, seed, batches, hyper, keep_first_grad=True, **other)
    ours, theirs = want["first_grad"], got.pop("first_grad")
    gaps = {**{f"run{r}.{name}": np.sqrt(np.sum((ours["runs"][r][name] - leaf) ** 2, axis=tuple(range(1, leaf.ndim))))
               for r, run in enumerate(theirs["runs"]) for name, leaf in run.items()},
            **{name: np.sqrt(np.sum((ours[name] - theirs[name]) ** 2)) for name in reference.OUTER}}
    got.update(loss_start=0.0, loss_end=0.0)
    sound = {**{k: v for k, v in want.items() if k != "first_grad"}, "first_grad_difference_norms": gaps}
    return {row["name"]: row for row in mode.judged_with_routing(got, sound, limits)}, got, sound


def control(cell, seeds, variants=("int8",)) -> list[dict]:
    import numpy as np
    import yaml

    from benchmark.reference import ssd_moe_decoder_f32 as reference
    from benchmark.weights_ssd_moe import SsdMoEShape

    mode = cell.module("modes", "train_ssd_moe")
    raw = yaml.safe_load(cell.yaml_path.read_text())
    shape = SsdMoEShape.from_yaml(raw)
    profile, mesh = raw["settings"]["step_profile"], raw["device_mesh"]["config"]
    seq = int(profile["sequence_length"])
    rows = int(profile["local_train_micro_batch_size"]) * int(mesh["data_parallel_shard_degree"])
    generator = cell.module("traffic", cell.traffic["generator"])
    scratch = cell.root / ".bench_scratch" / f"{cell.name}-control"
    hyper = mode.hyperparameters(raw)
    hyper["lr"] = hyper["lr"][: mode.CHECK_STEPS]
    out = []
    for seed in seeds:
        generator.generate({**cell.traffic, "sequences": 4 * rows}, seed, scratch / "train.pbin",
                           vocab_size=shape.vocab_size, sequence_length=seq)
        raw_bytes = (scratch / "train.pbin").read_bytes()
        stream = np.frombuffer(raw_bytes[12 : 12 + int.from_bytes(raw_bytes[:8], "little")], dtype="<u2").astype(np.int32)
        batches = []
        for step in range(mode.CHECK_STEPS):
            starts = [(step * rows + r) * seq for r in range(rows)]
            batches.append((np.stack([stream[s : s + seq] for s in starts]), np.stack([stream[s + 1 : s + seq + 1] for s in starts])))
        weights_seed = int(cell.spec.get("weights_seed", seed))  # the cell's own weights where it names them: the corpus alone follows the seed
        want = reference.train_steps(shape, weights_seed, batches, hyper, keep_first_grad=True)  # the sound reference, once a seed
        for variant in variants:
            t0 = time.perf_counter()
            other = dict(precision="int8") if variant == "int8" else dict(skip=(VARIANTS[variant],))
            judged, got, sound = judged_against(mode, reference, shape, weights_seed, batches, hyper, want, cell.spec["limits"], **other)
            row = {"variant": variant, "seed": seed, "seconds": round(time.perf_counter() - t0, 1),
                   "correct": all(r["ok"] for r in judged.values()), "failed_rows": [name for name, r in judged.items() if not r["ok"]],
                   **{r["name"]: r["value"] for r in (*judged.values(), *mode.routing_gaps(got, sound)) if "ok" in r or "_step2_" in r["name"]},
                   "grad_norm": [got["grad_norm"], sound["grad_norm"]],
                   "first_grad_by_kind": mode.by_kind_of_leaf(sound["first_grad_difference_norms"], sound["first_grad_norms"]),
                   "param_change_leaf": judged["param_change_norm_worst_leaf_rel_gap"]["leaf"],
                   "first_grad_errors": judged["first_grad_worst_leaf_rel_error"]}
            print("[control] " + json.dumps(row), flush=True)
            out.append(row)
    return out


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--variant", default="int8", help="int8, all, one of " + ", ".join(VARIANTS) + ", or several separated by commas")
    args = parser.parse_args()

    from benchmark.device import require_tpu
    from benchmark.manifest import load_cell

    cell = load_cell(args.workload, REPO)
    print(f"[control] {require_tpu(cell.chips)}", flush=True)
    if cell.mode != "train_ssd_moe":
        raise SystemExit(f"control_ssd_moe: the cell's mode is {cell.mode!r}; the other cells have control tools of their own")
    variants = ["int8", *VARIANTS] if args.variant == "all" else args.variant.split(",")
    for variant in variants:
        if variant != "int8" and variant not in VARIANTS:
            raise SystemExit(f"control_ssd_moe: no variant {variant!r} (int8, {', '.join(VARIANTS)}, all)")
    control(cell, [int(s) for s in args.seeds.split(",")], variants)


if __name__ == "__main__":
    main()
