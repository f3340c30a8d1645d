"""The gated delta rule's walk over a group's chunks alone on one chip: the plain `lax.scan` beside the Pallas kernels.

Times `ops/gated_delta_rule._walk`'s two forms on prepared arrays at the cell `train-qwen3next-80b-16k`'s shapes (one
group of 32 chunks of 64, 16 key heads with 2 value heads each, heads of 128 x 128, bfloat16), forward alone and forward
with backward (the gradient of a weighted sum of `o` and the state that goes on in all seven operands), each as its own
jitted program, and the kernels at several counts of key heads a grid step (`--heads`; `plan_heads` picks the one the
program runs). It is the builder's tool for the numbers PERF.md quotes, not a cell: nothing in `benchmark/` reads it.

Prints one JSON line a form: the host's clock (the least of `--iters` calls, which holds about a millisecond of
dispatch and fetch beside the device's time) and, under the profiler, the device's own ms a call by operation label
(`benchmark/xtrace.py` reads the trace), and for the kernels the largest gap of each output to the plain form's over
the plain form's largest value.

Usage (TPU): chiprun -- python3 scripts/gdn_state_bench.py [--heads 1,2,4,8] [--trace chiprun_out/gdn_state]
CPU smoke:   JAX_PLATFORMS=cpu python scripts/gdn_state_bench.py --chunks 2 --key_heads 2 --chunk 16 --heads 1,2 --interpret
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time
from pathlib import Path

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

NAMES = ("state", "o", "d_state", "d_u", "d_w", "d_within", "d_q_in", "d_k_out", "d_carry_decay")


def operands(chunks: int, batch: int, key_heads: int, r: int, chunk: int, key_dim: int, value_dim: int, dtype, seed: int = 0):
    """What `_group` hands the walk, at the sizes it has there (keys of norm 1, a decay a chunk in (0, 1)), and the weights of the sum."""
    import jax
    import jax.numpy as jnp

    keys = jax.random.split(jax.random.PRNGKey(seed), 9)
    lead = (chunks, batch, key_heads, r, chunk)
    normal = lambda key, *shape, scale=1.0: (scale * jax.random.normal(key, shape)).astype(dtype)  # noqa: E731
    u, w = normal(keys[0], *lead, value_dim), normal(keys[1], *lead, key_dim, scale=key_dim ** -0.5)
    within = (jnp.tril(jax.random.normal(keys[2], (*lead, chunk))) * key_dim ** -0.5).astype(dtype)
    q_in, k_out = normal(keys[3], *lead, key_dim, scale=key_dim ** -0.5), normal(keys[4], *lead, key_dim, scale=key_dim ** -0.5)
    carry_decay = jax.nn.sigmoid(jax.random.normal(keys[5], lead[:4]))
    state = jax.random.normal(keys[6], (batch, key_heads, r, key_dim, value_dim))
    weights = jax.random.normal(keys[7], u.shape), jax.random.normal(keys[8], state.shape)
    return (state, u, w, within, q_in, k_out, carry_decay), weights


def programs(walk, weights):
    """The two jitted programs of one form: forward, and forward with backward."""
    import jax
    import jax.numpy as jnp

    w_o, w_state = weights
    loss = lambda *v: (lambda state, o: jnp.sum(o.astype(jnp.float32) * w_o) + jnp.sum(state * w_state))(*walk(*v))  # noqa: E731
    return jax.jit(walk), jax.jit(jax.grad(loss, argnums=tuple(range(7))))


def timed(fn, values, iters: int, trace_dir: Path | None) -> dict:
    """The least of `iters` calls on the host's clock and, with a trace, the device's own ms a call in all and by label."""
    import jax

    jax.block_until_ready(fn(*values))  # compiles
    times = []
    for _ in range(iters):
        start = time.perf_counter()
        jax.block_until_ready(fn(*values))
        times.append(time.perf_counter() - start)
    row = {"host_ms": round(min(times) * 1e3, 3)}
    if trace_dir is not None:
        from benchmark import xtrace

        xtrace.start_profiler(trace_dir)
        for _ in range(3):
            jax.block_until_ready(fn(*values))
        jax.profiler.stop_trace()
        by_label = xtrace.time_by_label(xtrace.load(xtrace.find_xplane(trace_dir)))
        ms = {k: round(v * 1e3 / 3, 4) for k, v in sorted(by_label.items(), key=lambda kv: -kv[1])}
        row.update(device_ms=round(sum(ms.values()), 4), device_ms_by_label=dict(list(ms.items())[:6]))
    return row


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    p.add_argument("--chunks", type=int, default=32)
    p.add_argument("--batch", type=int, default=1)
    p.add_argument("--key_heads", type=int, default=16)
    p.add_argument("--per_key_head", type=int, default=2)
    p.add_argument("--chunk", type=int, default=64)
    p.add_argument("--key_dim", type=int, default=128)
    p.add_argument("--value_dim", type=int, default=128)
    p.add_argument("--dtype", default="bfloat16")
    p.add_argument("--heads", default="1,2,4,8", help="key heads a grid step to try, beside the plain scan")
    p.add_argument("--iters", type=int, default=5)
    p.add_argument("--interpret", action="store_true", help="Pallas interpret mode (CPU smoke)")
    p.add_argument("--trace", default=None, help="directory for the profiler's traces (default: a temporary one; the device's times need them)")
    args = p.parse_args()

    import jax
    import jax.numpy as jnp

    from modalities_tpu.ops import gated_delta_rule as rule
    from modalities_tpu.ops.pallas.gated_delta_state import backward_vmem_bytes, plan_heads, walk

    device = jax.devices()[0]
    if device.platform != "tpu" and not args.interpret:
        sys.exit("no TPU here: a time from the CPU is no device number (--interpret smokes the code at a small shape)")
    dtype = jnp.dtype(args.dtype)
    sizes = (args.chunks, args.chunk, args.key_dim, args.value_dim)  # what the planner reads beside the heads
    values, weights = operands(args.chunks, args.batch, args.key_heads, args.per_key_head, *sizes[1:], dtype)
    shape = {"chunks": args.chunks, "batch": args.batch, "key_heads": args.key_heads, "per_key_head": args.per_key_head, "chunk": args.chunk,
             "key_dim": args.key_dim, "value_dim": args.value_dim, "dtype": dtype.name, "device": device.device_kind,
             "planned_heads": plan_heads(args.key_heads, args.per_key_head, *sizes, dtype)}
    print("[gdn_state_bench] " + json.dumps(shape), flush=True)
    forms = {"plain": rule._plain_walk}
    forms.update({f"kernels_heads{h}": (lambda *v, h=h: walk(*v, heads=h, interpret=args.interpret)) for h in map(int, args.heads.split(","))})
    traces = Path(args.trace or tempfile.mkdtemp(prefix="gdn_state_")) if device.platform == "tpu" else None
    kept = {}
    for form, fn in forms.items():
        line = {"form": form}
        if form != "plain":
            heads = int(form.removeprefix("kernels_heads")) * args.per_key_head
            line.update(value_heads_a_step=heads, backward_vmem_bytes=backward_vmem_bytes(heads, *sizes, dtype.itemsize))
        try:
            forward, both = programs(fn, weights)
            line["fwd"] = timed(forward, values, args.iters, traces and traces / form / "fwd")
            line["fwd_bwd"] = timed(both, values, args.iters, traces and traces / form / "fwd_bwd")
            kept[form] = (*forward(*values), *both(*values))
            if form != "plain":
                gap = lambda got, want: float(jnp.abs(got.astype(jnp.float32) - want.astype(jnp.float32)).max() / jnp.abs(want.astype(jnp.float32)).max())  # noqa: E731
                line["gap_to_plain"] = {name: round(gap(got, want), 6) for name, got, want in zip(NAMES, kept[form], kept["plain"])}
        except Exception as e:  # noqa: BLE001  what the compiler refuses at this count of heads is the reading
            line["refused"] = str(e)[:400]
        print("[gdn_state_bench] " + json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
