"""The windowed flash kernels alone at the cell `train-mellum2-12b-16k`'s shape (one row of 16,384, 32 query heads on 4
key/value heads of 128, window 1024, bfloat16), for each pair of blocks: the forward, and the forward with the fused
backward (`flash_attention_window_fwd`, `flash_attention_window_bwd`), beside a global layer's call at the same shape.

    chiprun -- python3 scripts/flash_window_bench.py [--blocks 1024x1024,1024x512,512x512] [--window 1024] [--seq 16384]

Times are the host's clock round `iterations` calls of one jitted program each, the device drained before and after (a
call is tens of milliseconds, so dispatch adds under a percent). What says that a windowed call needs no bucket of
its own in the tuning table (PERF.md section 6, PR 38: the default's 1024 x 1024 read fastest). Without a TPU it exits 1 and runs nothing."""

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    parser.add_argument("--blocks", default="1024x1024,1024x512,512x512")
    parser.add_argument("--window", type=int, default=1024)
    parser.add_argument("--seq", type=int, default=16384)
    parser.add_argument("--iterations", type=int, default=5)
    args = parser.parse_args()

    import jax
    import jax.numpy as jnp

    if jax.devices()[0].platform != "tpu":
        raise SystemExit("flash_window_bench: no TPU here; a CPU's time says nothing of the kernels")
    from modalities_tpu.ops.pallas.flash_attention import backward_plan, pallas_flash_attention, tile_plan

    key = jax.random.PRNGKey(0)
    q, k, v = (jax.random.normal(jax.random.fold_in(key, i), (1, args.seq, h, 128), jnp.bfloat16) for i, h in enumerate((32, 4, 4)))
    cotangent = jax.random.normal(jax.random.fold_in(key, 3), q.shape, jnp.bfloat16)

    def timed(fn, *inputs):
        jax.block_until_ready(fn(*inputs))  # compiles
        jax.block_until_ready(fn(*inputs))
        t0 = time.perf_counter()
        for _ in range(args.iterations):
            out = fn(*inputs)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / args.iterations * 1e3

    for window in (args.window, None):
        for pair in args.blocks.split(","):
            block_q, block_k = (int(b) for b in pair.split("x"))
            call = lambda q, k, v: pallas_flash_attention(q, k, v, causal=True, block_q=block_q, block_k=block_k, window=window)  # noqa: E731
            forward = jax.jit(call)
            both = jax.jit(lambda q, k, v, w: jax.grad(lambda q, k, v: (call(q, k, v).astype(jnp.float32) * w).sum(), argnums=(0, 1, 2))(q, k, v))
            row = {"window": window, "block_q": block_q, "block_k": block_k, **tile_plan(args.seq, args.seq, block_q, block_k, True, window).counts(),
                   "backward": backward_plan(args.seq, block_q, block_k, 128, 128, q.dtype)["backward"]}
            try:
                row["fwd_ms"] = round(timed(forward, q, k, v), 3)
                row["fwd_bwd_ms"] = round(timed(both, q, k, v, cotangent), 3)
            except Exception as e:  # noqa: BLE001  what the compiler refuses at this pair is the reading
                row["refused"] = str(e)[:300]
            print("[flash_window] " + json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
