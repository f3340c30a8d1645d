"""The rule mixer's two norms over a head's channels alone on one chip: the plain forms beside the Pallas kernels.

Times `models/gpt2/gdn.py`'s plain forms and `ops/pallas/head_norm.py`'s kernels on prepared arrays at the cell
`train-qwen3next-80b-16k`'s shapes (one row of 16,384; q and k `[1, 16384, 16, 128]`, o and z `[1, 16384, 32, 128]`,
bfloat16, row-major as the kernels take them), forward alone and forward with backward (the gradient of a weighted sum
in every operand), each as its own jitted program, the kernels at several counts of rows a grid step (`--blocks`) and
rows a slab (`--slabs`: the kernels' `SLAB`, replaced for the call). It is the builder's tool for the numbers PERF.md
quotes, not a cell: nothing in `benchmark/` reads it. Alone, the plain forms are one fusion a pass over row-major
arrays; in the cell their time is the compiler's relayouts between the convolution's layout and the rule's (PERF.md
section 5), which this script does not show.

Prints one JSON line a form: the host's clock (the least of `--iters` calls) and, under the profiler, the device's own
ms a call by operation label (`benchmark/xtrace.py`), the bytes the pass has to move over that time as a share of the
chip's 819 GB/s, and for the kernels the largest gap of each output to the plain form's over the plain form's largest value.

Usage (TPU): chiprun -- python3 scripts/head_norm_bench.py [--blocks 256,1024,2048,4096] [--slabs 64,128]
CPU smoke:   JAX_PLATFORMS=cpu python scripts/head_norm_bench.py --tokens 64 --blocks 128 --slabs 64 --interpret
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
from pathlib import Path

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

HBM_BYTES_PER_S = 819e9  # a v5e's, benchmark/peaks.json
EPS = 1e-6


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    p.add_argument("--tokens", type=int, default=16384)
    p.add_argument("--key_heads", type=int, default=16)
    p.add_argument("--value_heads", type=int, default=32)
    p.add_argument("--dim", type=int, default=128)
    p.add_argument("--dtype", default="bfloat16")
    p.add_argument("--blocks", default="256,512,1024,2048,4096", help="rows a grid step to try")
    p.add_argument("--slabs", default="64,128", help="rows a slab to try")
    p.add_argument("--iters", type=int, default=5)
    p.add_argument("--interpret", action="store_true", help="Pallas interpret mode (CPU smoke)")
    p.add_argument("--trace", default=None, help="directory for the profiler's traces (default: a temporary one)")
    args = p.parse_args()

    import jax
    import jax.numpy as jnp

    from modalities_tpu.models.gpt2.gdn import l2_normalised
    from modalities_tpu.ops.pallas import head_norm
    from scripts.gdn_state_bench import timed

    device = jax.devices()[0]
    if device.platform != "tpu" and not args.interpret:
        sys.exit("no TPU here: a time from the CPU is no device number (--interpret smokes the code at a small shape)")
    dtype, f32 = jnp.dtype(args.dtype), jnp.float32
    keys = jax.random.split(jax.random.PRNGKey(0), 6)
    qk, ov = (1, args.tokens, args.key_heads, args.dim), (1, args.tokens, args.value_heads, args.dim)
    x, o, z = (jax.random.normal(key, shape).astype(dtype) for key, shape in zip(keys, (qk, ov, ov)))
    w = 1.0 + 0.1 * jax.random.normal(keys[3], (args.dim,))
    w_x, w_y = jax.random.normal(keys[4], qk), jax.random.normal(keys[5], ov)
    scale = args.dim ** -0.5
    print("[head_norm_bench] " + json.dumps({"tokens": args.tokens, "key_heads": args.key_heads, "value_heads": args.value_heads, "dim": args.dim,
                                             "dtype": dtype.name, "device": device.device_kind}), flush=True)

    def plain_gated(o, z, w):
        o = o.astype(f32)
        return (o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + EPS) * w * jax.nn.silu(z.astype(f32))).astype(dtype)

    forms = {"plain": (lambda x: l2_normalised(x, scale).astype(dtype), plain_gated)}
    for slab in map(int, args.slabs.split(",")):
        for block in map(int, args.blocks.split(",")):
            if block >= slab:
                forms[f"kernels_block{block}_slab{slab}"] = (
                    lambda x, block=block: head_norm.head_l2_norm(x, scale, block_rows=block, interpret=args.interpret),
                    lambda o, z, w, block=block: head_norm.gated_head_rms_norm(o, z, w, eps=EPS, block_rows=block, interpret=args.interpret))
    traces = Path(args.trace or tempfile.mkdtemp(prefix="head_norm_")) if device.platform == "tpu" else None
    # what a pass has to move: every operand read once, every result written once, in the arrays' dtype
    floor_ms = {"l2_fwd": 2 * x.nbytes, "l2_fwd_bwd": 5 * x.nbytes, "gated_fwd": 3 * o.nbytes, "gated_fwd_bwd": 8 * o.nbytes}
    kept = {}
    for form, (l2, gated) in forms.items():
        line = {"form": form}
        if form != "plain":
            head_norm.SLAB = int(form.rsplit("slab", 1)[1])  # read while tracing
        programs = {
            "l2_fwd": (jax.jit(l2), (x,)),
            "l2_fwd_bwd": (jax.jit(jax.grad(lambda x: jnp.sum(l2(x).astype(f32) * w_x))), (x,)),
            "gated_fwd": (jax.jit(gated), (o, z, w)),
            "gated_fwd_bwd": (jax.jit(jax.grad(lambda o, z, w: jnp.sum(gated(o, z, w).astype(f32) * w_y), argnums=(0, 1, 2))), (o, z, w)),
        }
        try:
            for name, (fn, values) in programs.items():
                row = timed(fn, values, args.iters, traces and traces / form / name)
                if "device_ms" in row:
                    row["share_of_hbm_pct"] = round(100 * floor_ms[name] / HBM_BYTES_PER_S / (row["device_ms"] * 1e-3), 1)
                line[name] = row
            flat = lambda out: out if isinstance(out, tuple) else (out,)  # noqa: E731
            kept[form] = [leaf for fn, values in programs.values() for leaf in flat(fn(*values))]
            if form != "plain":
                gap = lambda got, want: float(jnp.abs(got.astype(f32) - want.astype(f32)).max() / jnp.abs(want.astype(f32)).max())  # noqa: E731
                line["gap_to_plain"] = {name: round(gap(got, want), 6) for name, got, want in zip(("y_l2", "dx", "y_gated", "do", "dz", "dw"), kept[form], kept["plain"])}
        except Exception as e:  # noqa: BLE001  what the compiler refuses at this block is the reading
            line["refused"] = str(e)[:400]
        print("[head_norm_bench] " + json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
