"""Quantized paged-KV helpers: mode resolution and the
host-side scale-allocation mirror the pool fuzz audits.

The device-side work (int8 pools, per-(block, row, head) float32 scales,
quantize-on-write / dequant-at-gather) lives in the model's
`_paged_slot_attention`; this module owns the HOST-side contracts:

- `resolve_quant_kv_mode` reads the mode (env over config). The engine reports
  what the pools hold, data and float32 scales together, as `serve_kv_pool_bytes`.
- `KVScaleMirror` subscribes to `BlockPool`'s observer hooks and tracks which
  blocks' scale slots are live. The 500-step fuzz asserts the mirror never
  disagrees with the pool: scale allocation tracks block allocation exactly,
  so a leaked block is also a leaked scale row and vice versa.
"""

from __future__ import annotations

import os

KV_MODES = ("none", "int8")
_ENV_VAR = "MODALITIES_TPU_QUANT_KV"


def resolve_quant_kv_mode(setting=None) -> str:
    """Env > config > "none". Malformed values raise naming the source."""
    env = os.environ.get(_ENV_VAR)
    if env is not None:
        source, value = f"env {_ENV_VAR}", env
    else:
        source, value = "config quant.kv", setting
    if value is None:
        return "none"
    v = str(value).strip().lower()
    if v in ("", "none", "off", "0", "no", "false"):
        return "none"
    if v in KV_MODES:
        return v
    raise ValueError(f"{source}: invalid KV quant mode {value!r} (expected none|int8)")


class KVScaleMirror:
    """Host mirror of the per-block scale slots, driven by BlockPool's
    observer hooks (`pool.add_observer(mirror)`).

    Invariant: a scale slot is live iff its block is allocated. The fuzz
    attaches one of these and calls `check(pool)` every step; any divergence
    (double-allocate, free-without-allocate, leak) raises immediately with the
    offending block id rather than surfacing later as a corrupt gather.
    """

    def __init__(self, num_blocks: int):
        self.num_blocks = int(num_blocks)
        self.live: set = set()
        self.allocs = 0
        self.frees = 0

    def on_allocate(self, block: int) -> None:
        if not (0 <= block < self.num_blocks):
            raise ValueError(f"scale mirror: allocate of out-of-range block {block}")
        if block in self.live:
            raise ValueError(f"scale mirror: block {block} allocated while its scale slot is live")
        self.live.add(block)
        self.allocs += 1

    def on_free(self, block: int) -> None:
        if block not in self.live:
            raise ValueError(f"scale mirror: block {block} freed without a live scale slot")
        self.live.remove(block)
        self.frees += 1

    def check(self, pool) -> None:
        """Scale slots must equal the pool's allocated set, exactly."""
        allocated = set(pool.allocated_blocks())
        if self.live != allocated:
            leaked = sorted(self.live - allocated)
            missing = sorted(allocated - self.live)
            raise AssertionError(
                f"scale mirror diverged from pool: leaked scale slots {leaked}, "
                f"blocks without scale slots {missing}"
            )
