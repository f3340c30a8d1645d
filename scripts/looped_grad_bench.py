"""The sum of a looped stack's shared gradient over its walks alone on one chip: whole stacks added, beside
a layer at a time into one stack.

A looped decoder (`loop_config`) applies each of its L layers T times a step, so a layer's weight gradient is
a sum of T contributions. Two programs make that sum from the same contributions, at the looped cell's shapes
(`train-ouro-2p6b-4k`: 16 layers of 4 x [2048, 2048] + 2 x [2048, 5632] + [5632, 2048] bf16, four walks):

- `summed_by_walk`, what autodiff's transpose of a scan of scans does: a walk's layer loop emits its
  contributions as a stack `[L, ...]`, and the loop over the walks adds the whole stack into the accumulator it
  carries (`add_any`): two stacks live, each byte of both read and written once more a walk than a contribution needs;
- `in_place`, what `models/gpt2/gpt2_model._walks_in_place`'s hand-written backward does: one accumulator
  `[L, ...]` carried through both loops, a layer's slice read, added to and written where it stands.

A contribution is made one of two ways: `fill` (a scalar of the walk and the layer broadcast: nothing but the
sum's own traffic) and `product` (`x^T dy` of `[tokens, in]` and `[tokens, out]` operands, as a block's backward
makes it: whether the slice's read hides under the MXU's time). It is the builder's tool for PERF.md's table
(PR 37), not a cell: nothing in `benchmark/` reads it.

Prints one JSON line a form and contribution: the device's own ms a call from a profiler trace
(`benchmark/xtrace.py`), the largest operations by label, `memory_analysis()`'s temporaries, and the largest gap
to `summed_by_walk` over the sum's largest value.

Usage (TPU): python scripts/looped_grad_bench.py --trace chiprun_out/looped_grad
CPU smoke:   JAX_PLATFORMS=cpu python scripts/looped_grad_bench.py --layers 3 --n_embd 64 --ffn 128 --tokens 32 --smoke --trace /tmp/looped_grad
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def layer_shapes(n_embd: int, ffn: int) -> dict:
    """A block's kernels, `[in, out]`: attention's four projections and SwiGLU's three (the norms' 8,192 floats left out)."""
    return {**{name: (n_embd, n_embd) for name in ("q_attn", "k_attn", "v_attn", "c_proj")},
            "W": (n_embd, ffn), "V": (n_embd, ffn), "W_2": (ffn, n_embd)}


def contribution(kind: str, shapes: dict, operands: dict, t, l, layers: int):
    """Application (t, l)'s gradient of one layer, every leaf different by walk and layer so that no loop's body is
    hoisted, and from its neighbours of equal shape so that no two products are merged into one."""
    import jax.numpy as jnp

    scales = {name: (8 * (1 + t * layers + l) + i).astype(jnp.bfloat16) / 512 for i, name in enumerate(shapes)}
    if kind == "fill":
        return {name: jnp.full(shape, scales[name], jnp.bfloat16) for name, shape in shapes.items()}
    return {name: jnp.einsum("ni,no->io", operands[d_in] * scales[name], operands[d_out]) for name, (d_in, d_out) in shapes.items()}


def forms(kind: str, shapes: dict, layers: int, walks: int) -> dict:
    import jax
    import jax.numpy as jnp
    from jax import lax

    zeros = lambda: {name: jnp.zeros((layers, *shape), jnp.bfloat16) for name, shape in shapes.items()}  # noqa: E731

    def summed_by_walk(operands):
        def walk(acc, t):
            _, stack = lax.scan(lambda _, l: (None, contribution(kind, shapes, operands, t, l, layers)), None, jnp.arange(layers), reverse=True)
            return jax.tree.map(jnp.add, acc, stack), None

        return lax.scan(walk, zeros(), jnp.arange(walks), reverse=True)[0]

    def in_place(operands):
        def walk(acc, t):
            def layer(acc, l):
                grads = contribution(kind, shapes, operands, t, l, layers)
                return jax.tree.map(lambda a, g: lax.dynamic_update_index_in_dim(
                    a, lax.dynamic_index_in_dim(a, l, 0, keepdims=False) + g, l, 0), acc, grads), None

            return lax.scan(layer, acc, jnp.arange(layers), reverse=True)[0], None

        return lax.scan(walk, zeros(), jnp.arange(walks), reverse=True)[0]

    return {"summed_by_walk": summed_by_walk, "in_place": in_place}


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--layers", type=int, default=16)
    p.add_argument("--walks", type=int, default=4)
    p.add_argument("--n_embd", type=int, default=2048)
    p.add_argument("--ffn", type=int, default=5632)
    p.add_argument("--tokens", type=int, default=4096)
    p.add_argument("--kinds", default="fill,product")
    p.add_argument("--calls", type=int, default=3)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--smoke", action="store_true", help="run without a TPU (no device number comes of it)")
    p.add_argument("--trace", required=True, help="directory for the profiler traces the device times are read from")
    args = p.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from scripts.embedding_grad_bench import traced_ms

    device = jax.devices()[0]
    if device.platform != "tpu" and not args.smoke:
        sys.exit("no TPU here: a time from the CPU is no device number (--smoke runs the code at a small shape)")
    shapes = layer_shapes(args.n_embd, args.ffn)
    rng = np.random.default_rng(args.seed)
    operands = {width: jnp.asarray(rng.normal(size=(args.tokens, width)) / np.sqrt(args.tokens), jnp.bfloat16) for width in (args.n_embd, args.ffn)}
    stack_bytes = 2 * args.layers * sum(a * b for a, b in shapes.values())
    for kind in args.kinds.split(","):
        want = None
        for form, fn in forms(kind, shapes, args.layers, args.walks).items():
            compiled = jax.jit(fn).lower(operands).compile()
            got = compiled(operands)
            want = got if want is None else want
            gap = max(float(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)).max() / jnp.abs(b.astype(jnp.float32)).max())
                      for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)))
            del got
            ms, by_label = traced_ms(Path(args.trace) / f"{kind}_{form}", compiled, (operands,), args.calls)
            print("[looped_grad_bench] " + json.dumps({
                "layers": args.layers, "walks": args.walks, "n_embd": args.n_embd, "ffn": args.ffn, "tokens": args.tokens,
                "contribution": kind, "form": form, "device": device.device_kind, "stack_bytes": stack_bytes,
                "device_ms": ms, "device_ms_by_label": by_label, "temp_bytes": compiled.memory_analysis().temp_size_in_bytes,
                "gap_to_summed_by_walk": gap,
            }), flush=True)


if __name__ == "__main__":
    main()
