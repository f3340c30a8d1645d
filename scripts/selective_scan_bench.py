"""The selective scan alone on one chip: the plain `lax.scan` form beside the Pallas kernels.

Times one Mamba-1 layer's recurrence at the hybrid cell's shape (1 x 4096 x 5120 x 16 by
default: `benchmark/configs/jamba2-3b-d14`), forward alone and forward with backward (the
gradient of a weighted sum of `y` and the last state in all six operands), each as its own
jitted program between `block_until_ready`s. It is the builder's tool for the ms-a-layer
numbers PERF.md quotes, not a cell: nothing in `benchmark/` reads it.

Prints one JSON line per form: {"form", "fwd_ms", "fwd_bwd_ms", ...} (the least of
`--iters` calls), and for the kernels the largest gap of each output to the plain form's,
over the plain form's largest value.

The host's clock holds about a millisecond of dispatch and fetch a call beside the device's
time, so with `--trace <dir>` the forward-with-backward program of each form also runs three
times under the profiler and one more line gives the device's own time by operation, ms a
call: what each kernel takes, and what XLA puts round them (`benchmark/xtrace.py` reads the
trace).

Usage (TPU): python scripts/selective_scan_bench.py [--seq 4096] [--d_inner 5120] [--trace chiprun_out/scan_trace]
CPU smoke:   JAX_PLATFORMS=cpu python scripts/selective_scan_bench.py --seq 64 --d_inner 256 --interpret
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def least_ms(fn, args, iters: int) -> tuple[float, float]:
    """(compile and first call in s, least of `iters` later calls in ms)."""
    import jax

    start = time.perf_counter()
    jax.block_until_ready(fn(*args))
    first = time.perf_counter() - start
    times = []
    for _ in range(iters):
        start = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - start)
    return first, min(times) * 1e3


def operands(batch: int, seq: int, d_inner: int, d_state: int, seed: int = 0):
    """x, dt, a, b, c, h0 as a Mamba layer hands them over (dt in [1e-3, 1e-1], A = -(1..N)), and the weights of the sum."""
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    normal = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)  # noqa: E731
    dt = jnp.asarray(np.exp(rng.uniform(np.log(1e-3), np.log(1e-1), size=(batch, seq, d_inner))), jnp.float32)
    a = -jnp.broadcast_to(jnp.arange(1, d_state + 1, dtype=jnp.float32), (d_inner, d_state))
    args = (normal(batch, seq, d_inner), dt, a, normal(batch, seq, d_state), normal(batch, seq, d_state),
            normal(batch, d_inner, d_state))
    return args, (normal(batch, seq, d_inner), normal(batch, d_inner, d_state))


def forms(chunk: int, interpret: bool):
    from modalities_tpu.ops import selective_scan as plain
    from modalities_tpu.ops.pallas.selective_scan import pallas_selective_scan

    return {
        "plain": lambda *v: plain._scan(*v, min(chunk, v[0].shape[1])),
        "kernels": lambda *v: pallas_selective_scan(*v, chunk=chunk, interpret=interpret),
    }


def programs(scan, weights):
    """The two jitted programs of one form: forward, and forward with backward."""
    import jax
    import jax.numpy as jnp

    w_y, w_h = weights
    loss = lambda *v: (lambda y, h: jnp.sum(y * w_y) + jnp.sum(h * w_h))(*scan(*v))  # noqa: E731
    return jax.jit(scan), jax.jit(jax.grad(loss, argnums=tuple(range(6))))


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--batch", type=int, default=1)
    p.add_argument("--seq", type=int, default=4096)
    p.add_argument("--d_inner", type=int, default=5120)
    p.add_argument("--d_state", type=int, default=16)
    p.add_argument("--chunk", type=int, default=None, help="steps between kept states (default: ops/selective_scan.CHUNK)")
    p.add_argument("--iters", type=int, default=5)
    p.add_argument("--interpret", action="store_true", help="Pallas interpret mode (CPU smoke)")
    p.add_argument("--trace", default=None, help="directory for a profiler trace of the kernels' two programs")
    args = p.parse_args()

    import jax
    import jax.numpy as jnp

    from modalities_tpu.ops.pallas.selective_scan import plan_blocks
    from modalities_tpu.ops.selective_scan import CHUNK

    chunk = args.chunk or CHUNK
    device = jax.devices()[0]
    if device.platform != "tpu" and not args.interpret:
        sys.exit("no TPU here: a time from the CPU is no device number (--interpret smokes the code at a small shape)")
    values, weights = operands(args.batch, args.seq, args.d_inner, args.d_state)
    shape = {"batch": args.batch, "seq": args.seq, "d_inner": args.d_inner, "d_state": args.d_state, "device": device.device_kind}
    kept = {}
    for form, scan in forms(chunk, args.interpret).items():
        forward, both = programs(scan, weights)
        line = {"form": form, **shape, "chunk": chunk}
        if form == "kernels":
            line["chunk"], line["block_d"] = plan_blocks(args.seq, args.d_inner, args.d_state, chunk)
        line["fwd_first_s"], line["fwd_ms"] = least_ms(forward, values, args.iters)
        line["fwd_bwd_first_s"], line["fwd_bwd_ms"] = least_ms(both, values, args.iters)
        kept[form] = (*forward(*values), *both(*values))
        if form == "kernels":
            names = ("y", "h_last", "dx", "ddt", "dA", "dB", "dC", "dh0")
            line["gap_to_plain"] = {
                name: float(jnp.abs(got - want).max() / jnp.abs(want).max())
                for name, got, want in zip(names, kept["kernels"], kept["plain"])
            }
        print("[selective_scan_bench] " + json.dumps(line), flush=True)
        if args.trace:
            print("[selective_scan_bench] " + json.dumps({"form": form, **traced(os.path.join(args.trace, form), both, values)}), flush=True)


def traced(trace_dir: str, fn, values, calls: int = 3) -> dict:
    """The device's own ms a call, in all and by operation label (the eight largest), over `calls` calls of `fn` under the profiler."""
    from pathlib import Path

    import jax

    from benchmark import xtrace

    xtrace.start_profiler(Path(trace_dir))
    for _ in range(calls):
        jax.block_until_ready(fn(*values))
    jax.profiler.stop_trace()
    by_label = xtrace.time_by_label(xtrace.load(xtrace.find_xplane(Path(trace_dir))))
    ms = {k: round(v * 1e3 / calls, 4) for k, v in sorted(by_label.items(), key=lambda kv: -kv[1])}
    return {"fwd_bwd_device_ms": round(sum(ms.values()), 4), "device_ms_by_label": dict(list(ms.items())[:8]), "trace": trace_dir}


if __name__ == "__main__":
    main()
