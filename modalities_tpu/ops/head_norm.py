"""Dispatch of the gated delta rule mixer's two row norms over a head's channels: the Pallas kernels
(`ops/pallas/head_norm.py`) per shard of batch, sequence and heads.

Whether a call takes them is `ops/tiers.py`'s one rule and the kernels' planner on the call's shapes (`kernels`: a last
axis of whole 128-lane tiles, positions of whole sublane tiles, float32 or bfloat16); the caller asks first and runs its own
plain form where the answer is none (`models/gpt2/gdn.py`: every CPU run, the tests' heads of 16). A call that gets
here runs the kernel, interpreted off a TPU. No config key, environment variable or switch chooses.
"""

from __future__ import annotations

import functools
import math

from modalities_tpu.ops import tiers

_ROWS = ("batch", "seq", "heads", None)  # how `[B, S, H, width]` lies on the mesh


def kernels(norm: str, shape: tuple[int, ...], dtype) -> tuple[str, ...]:
    """The kernels (forward, backward) the norm `l2` | `gated` takes over the last axis of an array of `shape [B, S, H, width]`, `()`
    for the caller's plain form: where kernels run and their planner serves the shape."""
    if not tiers.kernels_run():
        return ()
    from modalities_tpu.ops.pallas.head_norm import KERNELS, plan_rows

    return KERNELS[norm] if plan_rows(math.prod(shape[:-2]), shape[-1], dtype) else ()


def head_l2_norm(x, scale: float = 1.0, *, interpret: bool = False):
    """`scale * x * rsqrt(sum(x^2) + 1e-6)` over the last axis of `x [B, S, H, width]`, in x's dtype."""
    from modalities_tpu.ops.pallas.head_norm import head_l2_norm as kernel
    from modalities_tpu.parallel.sharding import per_shard

    kernel = functools.partial(kernel, scale=scale, interpret=tiers.interpret(interpret))
    return per_shard(lambda _axes, x: kernel(x), (_ROWS,), _ROWS)(x)


def gated_head_rms_norm(o, z, w, *, eps: float, interpret: bool = False):
    """`o * rsqrt(mean(o^2) + eps) * w * silu(z)` over the last axis of `o`, `z [B, S, H, width]` with `w [width]` (float32,
    one for all heads, with its gradient), in o's dtype."""
    from modalities_tpu.ops.pallas.head_norm import gated_head_rms_norm as kernel
    from modalities_tpu.parallel.sharding import per_shard

    kernel = functools.partial(kernel, eps=eps, interpret=tiers.interpret(interpret))
    return per_shard(lambda _axes, o, z, w: kernel(o, z, w), (_ROWS, _ROWS, (None,)), _ROWS)(o, z, w)
