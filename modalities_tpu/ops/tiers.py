"""Kernel-tier resolution shared by the dispatch wrappers (attention, fused CE,
fused RMSNorm).

A tier setting is "auto" | "on" | "off":
- "auto": the Pallas kernel runs on TPU, the exact fallback everywhere else
  (CPU tests see reference numerics, mirroring ops/attention.py).
- "on": the kernel runs unconditionally — off-TPU it runs in interpret mode so
  numerics stay exact (this is how CPU tests exercise the kernel path and how
  the no-[B,S,V]-buffer HLO assertion is made on a CPU-only CI box).
- "off": the fallback tier runs everywhere.

Precedence: env var > config/spec knob > "auto". A malformed value raises — it
must never silently demote a training run to the fallback tier.
"""

from __future__ import annotations

import os
from dataclasses import dataclass
from typing import Optional

_ON = ("1", "on", "true", "yes", "force")
_OFF = ("0", "off", "false", "no")


def on_tpu() -> bool:
    """A backend that fails to initialise raises here: answering "not a TPU" would
    turn every `auto` kernel into its reference and every forced one into
    interpret mode without a word."""
    import jax

    return jax.devices()[0].platform == "tpu"


@dataclass(frozen=True)
class KernelTier:
    enabled: bool
    # run the Pallas kernel in interpret mode (forced-on off-TPU: exact CPU
    # emulation, same kernel code path as the hardware lowering)
    interpret: bool


def resolve_tier(env_name: str, spec_setting: Optional[str] = None) -> KernelTier:
    env = os.environ.get(env_name)
    raw = (env if env is not None else (spec_setting or "auto")).strip().lower()
    if raw in _OFF:
        return KernelTier(enabled=False, interpret=False)
    if raw in _ON:
        return KernelTier(enabled=True, interpret=not on_tpu())
    if raw == "auto":
        return KernelTier(enabled=on_tpu(), interpret=False)
    source = env_name if env is not None else "config"
    raise ValueError(
        f"{source}={raw!r}: expected one of auto/on/off (a malformed tier setting "
        "must raise, never silently demote the kernel to a fallback tier)"
    )
