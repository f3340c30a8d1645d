"""The device a run is on: what JAX reports, the peaks it is held against, and the
refusal to run anywhere but on the TPUs the cell asks for."""

from __future__ import annotations

import json
from pathlib import Path


def device_info() -> dict:
    import jax

    devices = jax.devices()
    return {"platform": devices[0].platform, "kind": devices[0].device_kind, "count": len(devices)}


def require_tpu(chips: int) -> dict:
    """The device line of the result, or no result at all: there is no CPU run."""
    info = device_info()
    if info["platform"] != "tpu" or info["count"] != chips:
        raise SystemExit(f"benchmark: this cell needs {chips} TPU chip(s); jax found {info}. There is no CPU run.")
    return info


def peaks(kind: str, root: Path) -> dict:
    table = json.loads((Path(root) / "benchmark" / "peaks.json").read_text())
    if kind not in table or kind.startswith("_"):
        raise SystemExit(f"benchmark: no peaks for device kind {kind!r} in benchmark/peaks.json")
    return table[kind]


def live_peak_bytes() -> int:
    """`peak_bytes_in_use` of the fullest chip. On this runtime it counts live arrays
    (state, weights, cache), not a program's temporaries (PERF.md section 7)."""
    import jax

    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in jax.devices())


def program_peak_bytes(compiled) -> int:
    """Peak of one compiled program on one chip by the compiler's own account:
    arguments + temporaries + the outputs that alias no argument."""
    m = compiled.memory_analysis()
    return int(m.argument_size_in_bytes + m.temp_size_in_bytes + m.output_size_in_bytes - m.alias_size_in_bytes)
