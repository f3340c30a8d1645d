"""The embedding's gradient alone on one chip: the compiler's default scatter beside the chunked one.

Times `ops/embedding.table_gradient` (the `[V, E]` bf16 gradient of a lookup from the `[B, S, E]`
cotangent of its rows) at the four cells' shapes, each in the default form (one scatter-add: XLA's
TPU compiler sorts its indices when they number more than V/8) and cut along the sequence into
pieces of at most 4,096, 2,048 and V/8 rows, under three draws of ids: uniform (what
`benchmark/traffic/packed_documents.py` hands every cell), `repeat` (a tenth of the ids one token,
as a document's end token repeats) and `zipf` (a Zipf draw, exponent 1.1). `onehot` prices the
fallback: the one-hot rows of a block of ids times the cotangent, on the MXU. It is the builder's
tool for the rows-a-microsecond numbers `ops/embedding.grad_plan` rests on and PERF.md quotes, not a
cell: nothing in `benchmark/` reads it.

Prints one JSON line per shape, form and draw: the device's own ms a call from a profiler trace
(`benchmark/xtrace.py`; the host's clock holds a millisecond of dispatch beside so small a
program), `us_per_row`, the largest operations by label, `scatters` and `sorts` of the compiled
text, and the largest gap to the default form's gradient over the gradient's largest value.

Usage (TPU): python scripts/embedding_grad_bench.py --trace chiprun_out/embedding_grad
CPU smoke:   JAX_PLATFORMS=cpu python scripts/embedding_grad_bench.py --shapes 2x64x32x256 --smoke --trace /tmp/embedding_grad
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys
from pathlib import Path

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# batch x sequence x n_embd x vocab: train-2p7b-4k, train-jamba2-3b-4k, train-ouro-2p6b-4k, train-kanana2-30b-8k
CELLS = "2x4096x2560x50304,1x4096x2560x32768,1x4096x2048x49152,2x8192x2048x16128"
ONEHOT_BLOCK = 1024  # ids a block of the one-hot product: [1024, V] bf16 is 98 MiB at the dense cell's V


def draw_ids(kind: str, batch: int, seq: int, vocab: int, seed: int) -> np.ndarray:
    rng = np.random.default_rng(seed)
    if kind == "zipf":
        return ((rng.zipf(1.1, size=(batch, seq)) - 1) % vocab).astype(np.int32)
    ids = rng.integers(0, vocab, size=(batch, seq), dtype=np.int32)
    if kind == "repeat":
        ids[rng.random((batch, seq)) < 0.1] = vocab - 1
    return ids


def onehot_gradient(ids, rows, vocab: int):
    """The same sum as a product: float32 accumulation over blocks of ids, rounded once at the end."""
    import jax
    import jax.numpy as jnp

    ids, rows = ids.reshape(-1), rows.reshape(-1, rows.shape[-1])
    grad = jnp.zeros((vocab, rows.shape[-1]), jnp.float32)
    for start in range(0, ids.shape[0], ONEHOT_BLOCK):
        hot = jax.nn.one_hot(ids[start:start + ONEHOT_BLOCK], vocab, dtype=rows.dtype)
        grad += jnp.einsum("nv,ne->ve", hot, rows[start:start + ONEHOT_BLOCK], preferred_element_type=jnp.float32)
    return grad.astype(rows.dtype)


def forms(batch: int, seq: int, vocab: int, chunk_rows: list[str], with_onehot: bool) -> dict:
    """Form name -> gradient function of (ids, rows). A chunk count that an earlier name already has is left out."""
    from modalities_tpu.ops.embedding import table_gradient

    chunked = lambda chunks: lambda ids, rows: table_gradient(ids, rows, vocab, chunks)  # noqa: E731
    out, seen = {"default": chunked(1)}, {1}
    for name in chunk_rows:
        chunks = -(-seq // max(1, (vocab // 8 if name == "v8" else int(name)) // batch))
        if chunks not in seen:
            seen.add(chunks)
            out[f"rows_{name}_chunks_{chunks}"] = chunked(chunks)
    if with_onehot:
        out["onehot"] = lambda ids, rows: onehot_gradient(ids, rows, vocab)
    return out


def traced_ms(trace_dir: Path, fn, args, calls: int) -> tuple[float, dict]:
    """The device's own ms a call, in all and by operation label (the five largest), over `calls` calls under the profiler."""
    import jax

    from benchmark import xtrace

    xtrace.start_profiler(trace_dir)
    for _ in range(calls):
        jax.block_until_ready(fn(*args))
    jax.profiler.stop_trace()
    by_label = xtrace.time_by_label(xtrace.load(xtrace.find_xplane(trace_dir)))
    ms = {k: round(v * 1e3 / calls, 4) for k, v in sorted(by_label.items(), key=lambda kv: -kv[1])}
    return round(sum(ms.values()), 4), dict(list(ms.items())[:5])


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--shapes", default=CELLS, help="comma-separated batch x seq x n_embd x vocab")
    p.add_argument("--draws", default="uniform,repeat,zipf")
    p.add_argument("--chunk_rows", default="4096,2048,v8", help="the most rows a piece of the chunked forms holds; v8 is vocab // 8")
    p.add_argument("--calls", type=int, default=5)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--onehot", action="store_true", help="price the one-hot product too")
    p.add_argument("--smoke", action="store_true", help="run without a TPU (no device number comes of it)")
    p.add_argument("--trace", required=True, help="directory for the profiler traces the device times are read from")
    args = p.parse_args()

    import jax
    import jax.numpy as jnp

    device = jax.devices()[0]
    if device.platform != "tpu" and not args.smoke:
        sys.exit("no TPU here: a time from the CPU is no device number (--smoke runs the code at a small shape)")
    for shape in args.shapes.split(","):
        batch, seq, n_embd, vocab = (int(v) for v in shape.split("x"))
        rows = jnp.asarray(np.random.default_rng(args.seed).normal(size=(batch, seq, n_embd)), jnp.bfloat16)
        ids = {kind: jnp.asarray(draw_ids(kind, batch, seq, vocab, args.seed)) for kind in args.draws.split(",")}
        want = {}
        for form, gradient in forms(batch, seq, vocab, args.chunk_rows.split(","), args.onehot).items():
            compiled = jax.jit(gradient).lower(next(iter(ids.values())), rows).compile()
            text = compiled.as_text()
            for kind, kind_ids in ids.items():
                got = compiled(kind_ids, rows).astype(jnp.float32)
                want.setdefault(kind, got)
                ms, by_label = traced_ms(Path(args.trace) / f"{shape}_{form}_{kind}", compiled, (kind_ids, rows), args.calls)
                print("[embedding_grad_bench] " + json.dumps({
                    "batch": batch, "seq": seq, "n_embd": n_embd, "vocab": vocab, "rows": batch * seq, "form": form,
                    "ids": kind, "distinct_ids": int(np.unique(np.asarray(kind_ids)).size), "device": device.device_kind,
                    "device_ms": ms, "us_per_row": round(ms * 1e3 / (batch * seq), 4), "device_ms_by_label": by_label,
                    "scatters": len(re.findall(r" scatter\(", text)), "sorts": len(re.findall(r" sort\(", text)),
                    "temp_bytes": compiled.memory_analysis().temp_size_in_bytes,
                    "gap_to_default": float(jnp.abs(got - want[kind]).max() / jnp.abs(want[kind]).max()),
                }), flush=True)


if __name__ == "__main__":
    main()
