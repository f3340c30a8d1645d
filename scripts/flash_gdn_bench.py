"""The flash kernels alone at the cell `train-qwen3next-80b-16k`'s shape (one row of 16,384, 16 query heads on 2
key/value heads of 256, bfloat16), for pairs of forward and backward blocks: the forward, and the forward with its
backward, which `backward_plan` makes the fused kernel (`flash_attention_bwd`) where a q head's resident dq and the
tiles fit its VMEM budget at the backward's blocks, else `flash_attention_bwd_dq` + `_bwd_dkv` at the forward's.

    chiprun -- python3 scripts/flash_gdn_bench.py [--pairs 1024x1024/512x512,...] [--seq 16384]

A pair is `<forward blocks>/<backward blocks>`. Times are the host's clock round `iterations` calls of one jitted program
each, the device drained before and after (a call is tens of milliseconds, so dispatch adds under a percent). What the
`d256_dv256` entries of `ops/pallas/tuning_tables/v5e.json` rest on (PERF.md section 6, PR 44). Without a TPU it exits 1
and runs nothing."""

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

PAIRS = "1024x1024/512x512,1024x1024/1024x256,1024x1024/256x1024,1024x512/512x512,512x512/512x512,1024x512/1024x1024,512x512/1024x1024"


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    parser.add_argument("--pairs", default=PAIRS)
    parser.add_argument("--seq", type=int, default=16384)
    parser.add_argument("--heads", default="16x2x256", help="query heads x key/value heads x head_dim")
    parser.add_argument("--iterations", type=int, default=5)
    args = parser.parse_args()

    import jax
    import jax.numpy as jnp

    if jax.devices()[0].platform != "tpu":
        raise SystemExit("flash_gdn_bench: no TPU here; a CPU's time says nothing of the kernels")
    from modalities_tpu.ops.pallas.flash_attention import backward_plan, pallas_flash_attention

    hq, hkv, d = (int(n) for n in args.heads.split("x"))
    key = jax.random.PRNGKey(0)
    q, k, v = (jax.random.normal(jax.random.fold_in(key, i), (1, args.seq, h, d), jnp.bfloat16) for i, h in enumerate((hq, hkv, hkv)))
    cotangent = jax.random.normal(jax.random.fold_in(key, 3), q.shape, jnp.bfloat16)

    def timed(fn, *inputs):
        jax.block_until_ready(fn(*inputs))  # compiles
        jax.block_until_ready(fn(*inputs))
        t0 = time.perf_counter()
        for _ in range(args.iterations):
            out = fn(*inputs)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / args.iterations * 1e3

    for pair in args.pairs.split(","):
        fwd, bwd = (tuple(int(b) for b in blocks.split("x")) for blocks in pair.split("/"))
        call = lambda q, k, v: pallas_flash_attention(q, k, v, causal=True, block_q=fwd[0], block_k=fwd[1], bwd_blocks=bwd)  # noqa: E731
        forward = jax.jit(call)
        both = jax.jit(lambda q, k, v, w: jax.grad(lambda q, k, v: (call(q, k, v).astype(jnp.float32) * w).sum(), argnums=(0, 1, 2))(q, k, v))
        row = {"forward_blocks": fwd, "backward_blocks": bwd, **backward_plan(args.seq, *bwd, d, d, q.dtype)}
        try:
            row["fwd_ms"] = round(timed(forward, q, k, v), 3)
            row["fwd_bwd_ms"] = round(timed(both, q, k, v, cotangent), 3)
        except Exception as e:  # noqa: BLE001  what the compiler refuses at this pair is the reading
            row["refused"] = str(e)[:300]
        print("[flash_gdn] " + json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
