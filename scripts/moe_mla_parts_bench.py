"""The parts of the latent-attention / expert-layer step alone on one chip, at the shapes of
the cell `train-kanana2-30b-8k` (`benchmark/configs/kanana2-30b-a3b-d9`): the builder's
tool for the per-part prices PERF.md quotes, not a cell: nothing in `benchmark/` reads it.

- `flash`: the flash kernels, forward with backward, at 2 x 8192 x 32 heads of 192
  (q, k) / 128 (v) for each `--blocks` pair, beside 128 / 128 at the table's 1024 x 1024;
  and for each, the backward alone in both its forms whatever the shape rule would pick
  (`ops/pallas/flash_attention.backward_plan`): `flash_bwd_fused_*` is `flash_attention_bwd`
  (PR 31), `flash_bwd_two_kernels_*` is `flash_attention_bwd_dq` + `_bwd_dkv`.
- `moe`: one expert layer's routed part (`ops/expert_dispatch.py`: plan, gathers, grouped
  products, add back by token), forward with backward, at 16,384 tokens of width 2048
  routed 6 of 128 with the experts `0 .. held - 1` of 768 held; loads uniform, and for
  the extremes all pairs on one held expert and none on any.

Each program runs `--iters` times between `block_until_ready`s (the host's clock, which holds
about a millisecond of dispatch a call) and three times under the profiler: the line's
`device_ms` is the device's own time a call, by operation label where that says more.

Usage (TPU): python scripts/moe_mla_parts_bench.py [--parts flash,moe] [--trace chiprun_out/parts]
CPU smoke:   JAX_PLATFORMS=cpu python scripts/moe_mla_parts_bench.py --smoke
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def timed(name: str, fn, values, iters: int, trace_root: str | None, calls: int = 3, **facts) -> None:
    import jax

    start = time.perf_counter()
    jax.block_until_ready(fn(*values))
    line = {"part": name, **facts, "first_s": round(time.perf_counter() - start, 2)}
    times = []
    for _ in range(iters):
        start = time.perf_counter()
        jax.block_until_ready(fn(*values))
        times.append(time.perf_counter() - start)
    line["host_ms"] = round(min(times) * 1e3, 3)
    if trace_root:
        from benchmark import xtrace

        trace_dir = Path(trace_root) / name
        xtrace.start_profiler(trace_dir)
        for _ in range(calls):
            jax.block_until_ready(fn(*values))
        jax.profiler.stop_trace()
        by_label = xtrace.time_by_label(xtrace.load(xtrace.find_xplane(trace_dir)))
        ms = {k: round(v * 1e3 / calls, 4) for k, v in sorted(by_label.items(), key=lambda kv: -kv[1])}
        line["device_ms"] = round(sum(ms.values()), 4)
        line["device_ms_by_label"] = dict(list(ms.items())[:10])
    print("[parts] " + json.dumps(line), flush=True)


def flash_part(args, interpret: bool) -> None:
    import jax
    import jax.numpy as jnp

    from modalities_tpu.ops.pallas.flash_attention import (
        flash_bwd, flash_bwd_dkv, flash_bwd_dq, flash_fwd_out_lse, pallas_flash_attention)

    def two_kernels(*values, **kw):
        return flash_bwd_dq(*values, **kw), *flash_bwd_dkv(*values, **kw)

    batch, seq, heads = (1, 256, 2) if args.smoke else (2, 8192, 32)
    rng = np.random.default_rng(0)
    draw = lambda width: jnp.asarray(rng.normal(size=(batch, seq, heads, width)), jnp.bfloat16)  # noqa: E731
    cases = [(192, 128, bq, bk) for bq, bk in args.blocks] + [(128, 128, 1024, 1024)]
    for d, dv, bq, bk in cases:
        bq, bk = (min(bq, 128), min(bk, 128)) if args.smoke else (bq, bk)
        q, k, v, w = draw(d), draw(d), draw(dv), draw(dv)

        def loss(q, k, v, bq=bq, bk=bk, w=w):
            out = pallas_flash_attention(q, k, v, block_q=bq, block_k=bk, interpret=interpret)
            return jnp.sum(out.astype(jnp.float32) * w.astype(jnp.float32))

        # the backward's operands as the custom_vjp hands them over: [B, H, S, D], lse from a forward at blocks that always fit
        qt, kt, vt, wt = (x.transpose(0, 2, 1, 3) for x in (q, k, v, w))
        kw = dict(causal=True, sm_scale=d**-0.5, interpret=interpret)
        out, lse = jax.jit(functools.partial(flash_fwd_out_lse, block_q=min(bq, 512), block_k=min(bk, 512), **kw))(qt, kt, vt)
        delta = jnp.sum(wt.astype(jnp.float32) * out.astype(jnp.float32), axis=-1, keepdims=True)
        programs = {
            f"flash_d{d}_dv{dv}_{bq}x{bk}": (jax.jit(jax.grad(loss, argnums=(0, 1, 2))), (q, k, v)),
            **{f"flash_bwd_{form}_d{d}_dv{dv}_{bq}x{bk}": (jax.jit(functools.partial(fn, block_q=bq, block_k=bk, **kw)), (qt, kt, vt, wt, lse, delta))
               for form, fn in (("fused", flash_bwd), ("two_kernels", two_kernels))},
        }
        for name, (fn, values) in programs.items():
            try:
                timed(name, fn, values, args.iters, args.trace, head_dim=d, head_dim_v=dv, block_q=bq, block_k=bk)
            except Exception as e:  # what Mosaic refuses (VMEM) is a reading too
                print("[parts] " + json.dumps({"part": name, "refused": str(e)[-300:]}), flush=True)


def moe_part(args) -> None:
    import jax
    import jax.numpy as jnp

    from modalities_tpu.ops import expert_dispatch

    tokens, width, hidden, routed, chosen, held = (256, 128, 64, 8, 3, 4) if args.smoke else (16384, 2048, 768, 128, 6, args.held)
    tile = 8 if args.smoke else expert_dispatch.TILE
    rng = np.random.default_rng(0)
    normal = lambda *shape, scale=1.0, dtype=jnp.bfloat16: jnp.asarray(rng.normal(size=shape) * scale, dtype)  # noqa: E731
    x, w_out = normal(tokens, width), normal(tokens, width)
    gate, up, down = normal(held, width, hidden, scale=0.02), normal(held, width, hidden, scale=0.02), normal(held, hidden, width, scale=0.02)
    weights = jnp.asarray(rng.uniform(0.2, 0.6, size=(tokens, chosen)), jnp.float32)
    uniform = jnp.asarray(np.stack([rng.choice(routed, size=chosen, replace=False) for _ in range(tokens)]), jnp.int32)
    loads = {
        "uniform": uniform,
        "all_on_one": jnp.broadcast_to(jnp.asarray([0] + list(range(held, held + chosen - 1)), jnp.int32), (tokens, chosen)),
        "none_held": jnp.broadcast_to(jnp.arange(held, held + chosen, dtype=jnp.int32), (tokens, chosen)),
    }

    def loss(x, weights, gate, up, down, choice):
        out = expert_dispatch.routed_experts(x, choice, weights, gate, up, down, offset=0, tile=tile)
        return jnp.sum(out.astype(jnp.float32) * w_out.astype(jnp.float32))

    both = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4)))
    forward = jax.jit(lambda x, weights, gate, up, down, choice: expert_dispatch.routed_experts(
        x, choice, weights, gate, up, down, offset=0, tile=tile))
    for name, choice in loads.items():
        pairs = int(jnp.sum(choice < held))
        facts = {"tokens": tokens, "held": held, "pairs_held": pairs, "tile": tile,
                 "required_gflop_fwd": round(pairs * 6 * width * hidden / 1e9, 2)}
        timed(f"moe_fwd_{name}", forward, (x, weights, gate, up, down, choice), args.iters, args.trace, **facts)
        timed(f"moe_fwd_bwd_{name}", both, (x, weights, gate, up, down, choice), args.iters, args.trace, **facts)


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--parts", default="flash,moe")
    p.add_argument("--blocks", default="512x1024,1024x512,512x512,1024x1024", help="block_q x block_k pairs tried at 192/128")
    p.add_argument("--held", type=int, default=16)
    p.add_argument("--iters", type=int, default=5)
    p.add_argument("--smoke", action="store_true", help="tiny shapes, Pallas in interpret mode (CPU)")
    p.add_argument("--trace", default=None, help="directory for the profiler traces")
    args = p.parse_args()
    args.blocks = [tuple(int(n) for n in pair.split("x")) for pair in args.blocks.split(",")]

    import jax

    device = jax.devices()[0]
    if device.platform != "tpu" and not args.smoke:
        sys.exit("no TPU here: a time from the CPU is no device number (--smoke runs the code at a small shape)")
    print("[parts] " + json.dumps({"device": device.device_kind}), flush=True)
    if "flash" in args.parts:
        flash_part(args, interpret=args.smoke)
    if "moe" in args.parts:
        moe_part(args)


if __name__ == "__main__":
    main()
