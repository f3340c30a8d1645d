"""The parts of the latent-attention / expert-layer step alone on one chip, at the shapes of
the cell `train-kanana2-30b-8k` (`benchmark/configs/kanana2-30b-a3b-d9`): the builder's
tool for the per-part prices PERF.md quotes, not a cell: nothing in `benchmark/` reads it.

- `flash`: the flash kernels, forward with backward, at 2 x 8192 x 32 heads of 192
  (q, k) / 128 (v) for each `--blocks` pair, beside 128 / 128 at the table's 1024 x 1024;
  and for each, the backward alone in both its forms whatever the shape rule would pick
  (`ops/pallas/flash_attention.backward_plan`): `flash_bwd_fused_*` is `flash_attention_bwd`
  (PR 31), `flash_bwd_two_kernels_*` is `flash_attention_bwd_dq` + `_bwd_dkv`.
- `moe`: one expert layer's routed part (`ops/expert_dispatch.py`: plan, gathers, grouped
  products, sum by token), forward and forward with backward, and its sum by token alone, each
  in both forms of the sum (`combine_plan`: `gathers`, k gathers of [T, d]; `slabs`, the kernel
  `moe_combine`), at both expert cells' shapes (`MOE_SHAPES`) under three or four routings
  (`moe_routings`), with the (block, expert) pairs the kernel has in use; then the sum alone
  under uniform routing with `--held` experts held: the readings `combine_plan`'s line is set by.

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
        delta = jnp.sum(wt.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)[:, :, None]  # [B, H, 1, S] rows, as lse
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


# tokens, width, an expert's hidden size, the router's experts, a token's choices, the experts held: one layer as a cell's chip holds it
MOE_SHAPES = {
    "mellum2": (16384, 2304, 896, 64, 8, 8),  # benchmark/configs/mellum2-12b-a2p5b-d12
    "kanana2": (16384, 2048, 768, 128, 6, 16),  # benchmark/configs/kanana2-30b-a3b-d9
    "qwen3next": (16384, 2048, 512, 512, 10, 64),  # benchmark/configs/qwen3-next-80b-a3b-d4 (PR 44): 6.4 experts held to a choice, over `combine_plan`'s line
}


def moe_routings(rng, tokens: int, routed: int, chosen: int, held: int) -> dict:
    """Three routings of `tokens` tokens: `collapsed` (every token the same experts, one of them held: what both cells'
    windows hold, an expert all tokens or none), `uniform` (every token its own draw: what a trained router sends, every
    held expert a share of every block), `none_held`; and where a token can have all its choices held, `all_held`."""
    import jax.numpy as jnp

    same = lambda experts: jnp.broadcast_to(jnp.asarray(experts, jnp.int32), (tokens, chosen))  # noqa: E731
    routings = {
        "collapsed": same([0] + list(range(held, held + chosen - 1))),
        "uniform": jnp.asarray(np.argsort(rng.random((tokens, routed)), axis=1)[:, :chosen], jnp.int32),  # k distinct experts a token
        "none_held": same(range(held, held + chosen)),
    }
    if chosen <= held:
        routings["all_held"] = same(range(chosen))
    return routings


def moe_part(args) -> None:
    """One expert layer's routed part, and its sum by token alone, in both forms of the sum (`combine_plan`'s `gathers` and
    `slabs`) at each of `--moe_shapes` under each routing; then the sum alone under `uniform` with more experts held, which
    is where `combine_plan`'s line between the forms is read from."""
    import jax
    import jax.numpy as jnp

    from modalities_tpu.ops import expert_dispatch as xd

    shapes = {"smoke": (256, 128, 64, 8, 3, 4)} if args.smoke else {name: MOE_SHAPES[name] for name in args.moe_shapes.split(",")}
    tile = 8 if args.smoke else xd.TILE
    rng = np.random.default_rng(0)
    normal = lambda *shape, scale=1.0, dtype=jnp.bfloat16: jnp.asarray(rng.normal(size=shape) * scale, dtype)  # noqa: E731

    def sum_alone(name, tokens, width, chosen, held, choice, weights, **facts):
        """The sum by token of a table of finite rows, both forms, the plan and the tables made beforehand; and the tables' own price."""
        plan = jax.jit(lambda c: xd.plan_dispatch(c, 0, held, tile))(choice)
        tables = jax.jit(lambda p: xd.slab_tables(p, tokens, chosen, tile))
        slabs = tables(plan)
        in_use = int(jnp.sum(slabs.count > 0))
        facts = {**facts, "tokens": tokens, "width": width, "choices": chosen, "held": held, "pairs_held": int(jnp.sum(plan.group_sizes)),
                 "blocks_in_use": in_use, "blocks_at_most": int(slabs.count.size), "plan": xd.combine_plan(tokens, chosen, held, width)}
        rows = normal(xd._table_rows(plan, slabs), width)  # with the kernel's padding rows, which the gathers' table does not have
        plain = rows[:plan.row_pair.shape[0]]
        gathers = jax.jit(lambda r, p, w: xd._sum_by_token(r, p, tokens, chosen, w).astype(r.dtype))
        kernel = jax.jit(lambda r, p, s, w: xd._sum_by_slabs(r, p, s, tokens, chosen, w))
        gap = jnp.max(jnp.abs(gathers(plain, plan, weights).astype(jnp.float32) - kernel(rows, plan, slabs, weights).astype(jnp.float32)))
        facts["slabs_against_gathers_max_gap"] = float(gap)  # of sums rounded to the rows' dtype: 0, or one unit in the last place
        timed(f"sum_gathers_{name}", gathers, (plain, plan, weights), args.iters, args.trace, **facts)
        timed(f"sum_slabs_{name}", kernel, (rows, plan, slabs, weights), args.iters, args.trace, **facts)
        timed(f"sum_slab_tables_{name}", tables, (plan,), args.iters, args.trace, **facts)

    for shape, (tokens, width, hidden, routed, chosen, held) in shapes.items():
        x, w_out = normal(tokens, width), normal(tokens, width)
        gate, up, down = normal(held, width, hidden, scale=0.02), normal(held, width, hidden, scale=0.02), normal(held, hidden, width, scale=0.02)
        weights = jnp.asarray(rng.uniform(0.2, 0.6, size=(tokens, chosen)), jnp.float32)
        for routing, choice in moe_routings(rng, tokens, routed, chosen, held).items():
            sum_alone(f"{shape}_{routing}", tokens, width, chosen, held, choice, weights, shape=shape, routing=routing)
            pairs = int(jnp.sum(choice < held))
            facts = {"shape": shape, "routing": routing, "tokens": tokens, "held": held, "pairs_held": pairs, "tile": tile,
                     "required_gflop_fwd": round(pairs * 6 * width * hidden / 1e9, 2)}
            for form in ("gathers", "slabs"):
                def layer(x, weights, gate, up, down, choice, form=form):
                    return xd.routed_experts(x, choice, weights, gate, up, down, offset=0, tile=tile, combine=form)

                def loss(*values):
                    return jnp.sum(layer(*values).astype(jnp.float32) * w_out.astype(jnp.float32))

                values = (x, weights, gate, up, down, choice)
                timed(f"moe_fwd_{form}_{shape}_{routing}", jax.jit(layer), values, args.iters, args.trace, combine=form, **facts)
                timed(f"moe_fwd_bwd_{form}_{shape}_{routing}", jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))), values, args.iters, args.trace, combine=form, **facts)
        # the line of `combine_plan`: uniform routing is the slabs' worst (every held expert in every block), more experts held their cost
        for more in ([] if args.smoke else args.held):
            if more > held and more <= routed:
                choice = moe_routings(rng, tokens, routed, chosen, more)["uniform"]
                sum_alone(f"{shape}_uniform_held{more}", tokens, width, chosen, more, choice, weights, shape=shape, routing="uniform")


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--parts", default="flash,moe")
    p.add_argument("--blocks", default="512x1024,1024x512,512x512,1024x1024", help="block_q x block_k pairs tried at 192/128")
    p.add_argument("--moe_shapes", default="mellum2,kanana2", help="of MOE_SHAPES")
    p.add_argument("--held", default="16,24,32,48,64", help="experts held, beyond a shape's own, that the sum by token alone is also timed at")
    p.add_argument("--iters", type=int, default=5)
    p.add_argument("--smoke", action="store_true", help="tiny shapes, Pallas in interpret mode (CPU)")
    p.add_argument("--trace", default=None, help="directory for the profiler traces")
    args = p.parse_args()
    args.blocks = [tuple(int(n) for n in pair.split("x")) for pair in args.blocks.split(",")]
    args.held = [int(n) for n in args.held.split(",")]

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
