"""memscope: HBM memory attribution, preflight fits-check, and OOM forensics
(PR 17) — the memory-axis sibling of perfscope.

perfscope (PR 10) made *time* attributable: every HLO op lands in exactly one
cost bucket and bucket sums equal the module totals by construction. memscope
applies the same closure discipline to *bytes*. Three pillars:

1. **Static executable scope** — read ``compiled.memory_analysis()`` off an
   already-jitted executable and carve its argument/output/temp/alias bytes
   into semantic buckets (params, optimizer moments, gradients/accumulators,
   activations+workspace, KV pool, other) by matching against the known
   per-device byte counts of the param/opt-state trees and the serving KV pool
   config. Every category byte is assigned exactly once, so **bucket sums ==
   memory_analysis totals by construction** — the closure pin tests this for
   both the train-step and serving-decode executables.

2. **Preflight fits-check** — after compile but before the first dispatch,
   compare the predicted per-device peak against ``memory_stats()``'s
   ``bytes_limit``. An over-budget run fails fast with the actual levers named
   in rank order of modeled savings (zero_stage, remat, gradient accumulation,
   paged_num_blocks, quant_kv) instead of dying minutes later inside an XLA
   allocation. ``MODALITIES_TPU_MEMSCOPE_FITS_CHECK=warn|off`` downgrades the
   verdict; backends without a bytes_limit (CPU) make the check inert.

3. **Runtime timeline + OOM forensics** — per-step per-device
   ``memory_stats()`` sampling into registry gauges and sink events,
   ``jax.live_arrays()`` snapshots at ``MODALITIES_TPU_MEMSCOPE_AT_STEP=N[:K]``,
   and a RESOURCE_EXHAUSTED catch at the trainer/serving dispatch seams that
   writes ``oom_dump_rank_*_step_*.json`` (static report + timeline tail +
   top-K live arrays + metrics snapshot + suggested levers) before re-raising
   as a resumable exit so the supervisor warmstarts degraded. The ``oom@step``
   fault point makes the whole path e2e-testable on CPU.
"""

from __future__ import annotations

import json
import logging
import os
import re
import time
from collections import deque
from pathlib import Path
from typing import Optional, Union

from modalities_tpu.telemetry.device_memory import (
    device_memory_stats,
    min_bytes_limit,
)

# atomic-write helper shared with perfscope: same artifact discipline
from modalities_tpu.telemetry.perfscope import write_report  # noqa: F401  (re-export)

logger = logging.getLogger(__name__)

FITS_CHECK_ENV = "MODALITIES_TPU_MEMSCOPE_FITS_CHECK"
SNAPSHOT_ENV = "MODALITIES_TPU_MEMSCOPE_AT_STEP"
SNAPSHOT_DIR_ENV = "MODALITIES_TPU_MEMSCOPE_DIR"

# The buckets. Order matters: carving precedence for argument bytes is
# params -> optimizer_moments -> kv_pool (an argument byte claimed by an earlier
# bucket is gone), temp bytes split gradients_accumulators -> activations.
BUCKETS = (
    "params",
    "optimizer_moments",
    "gradients_accumulators",
    "activations_workspace",
    "kv_pool",
    "other",
)

# What the OOM dump suggests when no static report is on hand — rank order
# follows the ROADMAP item-1 MFU attack plan. With a static report the levers
# are re-ranked by modeled savings instead.
DEFAULT_LEVERS = (
    "zero_stage",
    "remat",
    "gradient_accumulation_steps",
    "paged_num_blocks",
    "quant_kv",
)

# Substrings that mark a device allocation failure across backends. XLA raises
# RESOURCE_EXHAUSTED; some paths stringify to "Out of memory", with either
# capital.
OOM_MARKERS = ("RESOURCE_EXHAUSTED", "Out of memory", "out of memory")


class FitsCheckFailure(RuntimeError):
    """Predicted per-device peak exceeds the device allocation budget.

    Deliberately NOT a ResumableError: warmstarting the same over-budget config
    would fail the same way. This is a config problem — the message names the
    levers; the operator picks one."""


def is_oom_error(exc: BaseException) -> bool:
    """True when the exception stringifies to a device allocation failure."""
    text = str(exc)
    return any(marker in text for marker in OOM_MARKERS)


# ---------------------------------------------------------- static attribution


def _memory_analysis_categories(compiled) -> dict:
    """The four byte categories XLA's memory analysis reports, tolerantly read
    (older/other backends omit attributes; absent == 0)."""
    try:
        stats = compiled.memory_analysis()
    except Exception as e:
        raise RuntimeError(f"memory_analysis() unavailable on this executable: {e!r}") from e
    out = {}
    for key, attr in (
        ("argument_bytes", "argument_size_in_bytes"),
        ("output_bytes", "output_size_in_bytes"),
        ("temp_bytes", "temp_size_in_bytes"),
        ("alias_bytes", "alias_size_in_bytes"),
    ):
        out[key] = int(getattr(stats, attr, 0) or 0)
    return out


def classify_memory(categories: dict, known_bytes: Optional[dict] = None) -> dict:
    """Carve the four memory_analysis categories into the semantic buckets.

    Closure by construction: params/optimizer_moments/kv_pool are carved out of
    argument bytes in that order (each takes ``min(known, remaining)``),
    gradients/accumulators out of temp bytes, the rest of temp is
    activations+workspace, and whatever argument bytes remain plus the output
    bytes that alias no argument land in ``other``. An aliased output (a donated
    state tree) lives in its argument's memory, so alias bytes count once, as
    arguments: ``sum(buckets) == peak_bytes(categories)`` is an identity, not an
    approximation — same invariant family as perfscope's op-classifier and the
    MFU waterfall."""
    known = known_bytes or {}
    buckets = {name: 0 for name in BUCKETS}

    arg_left = int(categories.get("argument_bytes", 0))
    for bucket in ("params", "optimizer_moments", "kv_pool"):
        take = min(int(known.get(bucket, 0)), arg_left)
        if take > 0:
            buckets[bucket] = take
            arg_left -= take

    temp_left = int(categories.get("temp_bytes", 0))
    grads = min(int(known.get("gradients_accumulators", 0)), temp_left)
    if grads > 0:
        buckets["gradients_accumulators"] = grads
        temp_left -= grads
    buckets["activations_workspace"] = temp_left

    buckets["other"] = arg_left + _fresh_output_bytes(categories)
    return buckets


def _fresh_output_bytes(categories: dict) -> int:
    return max(int(categories.get("output_bytes", 0)) - int(categories.get("alias_bytes", 0)), 0)


def peak_bytes(categories: dict) -> int:
    """What the allocator must fit: arguments, temporaries, and the outputs that
    alias no argument. Counting a donated train state as argument, output and
    alias made a 14.7 GiB step read 22.6 GiB, which the preflight check would
    have refused on a 15.75 GiB chip."""
    return (
        int(categories.get("argument_bytes", 0))
        + int(categories.get("temp_bytes", 0))
        + _fresh_output_bytes(categories)
    )


def memscope_from_compiled(
    compiled, known_bytes: Optional[dict] = None, context: Optional[dict] = None
) -> dict:
    """One executable's memory report: raw categories, closed buckets, the
    predicted per-device peak (`peak_bytes` — what the allocator must fit),
    and the savings-ranked lever list."""
    categories = _memory_analysis_categories(compiled)
    total = peak_bytes(categories)
    report = {
        "memory_analysis": {**categories, "total_bytes": total},
        "buckets": classify_memory(categories, known_bytes),
        "predicted_peak_bytes": total,
        "known_bytes": dict(known_bytes or {}),
        "context": dict(context or {}),
    }
    report["levers"] = rank_levers(report)
    return report


def train_step_known_bytes(app_state_handle, mesh_handle=None) -> dict:
    """Per-device byte counts of the train step's known argument/temp trees,
    computed leaf-by-leaf with each leaf's real shard shape (the same math the
    recipe validator's budget check uses). Gradients materialize fp32 in temp
    space, so the gradient bucket is the fp32 param footprint."""
    import numpy as np

    from modalities_tpu.utils.recipe_validation import (
        _matched_shardings,
        _per_device_bytes,
    )

    state = app_state_handle.state
    shardings = app_state_handle.state_shardings

    params_pd = 0
    param_count_pd = 0
    leaves, shards = _matched_shardings(state.params, getattr(shardings, "params", None))
    for leaf, s in zip(leaves, shards):
        params_pd += _per_device_bytes(leaf, s)
        shape = tuple(leaf.shape)
        if s is not None and hasattr(s, "shard_shape") and shape:
            shape = s.shard_shape(shape)
        param_count_pd += int(np.prod(shape, dtype=np.int64)) if shape else 1

    opt_pd = 0
    leaves, shards = _matched_shardings(state.opt_state, getattr(shardings, "opt_state", None))
    for leaf, s in zip(leaves, shards):
        if hasattr(leaf, "shape") and hasattr(leaf, "dtype"):
            opt_pd += _per_device_bytes(leaf, s)

    return {
        "params": int(params_pd),
        "optimizer_moments": int(opt_pd),
        "gradients_accumulators": int(param_count_pd) * 4,  # fp32 grads in temp
    }


# ------------------------------------------------------------------ the levers


def rank_levers(report: dict) -> list:
    """The actual knobs this stack exposes that shed bytes, ranked by modeled
    savings against THIS report's buckets — so the fits-check/OOM message names
    the biggest lever first instead of reciting a generic list. Never empty:
    remat-harder is always applicable as a fallback."""
    buckets = report.get("buckets") or {}
    ctx = report.get("context") or {}
    opt = int(buckets.get("optimizer_moments", 0))
    act = int(buckets.get("activations_workspace", 0))
    kv = int(buckets.get("kv_pool", 0))
    levers = []

    dp = int(ctx.get("dp_replicate", 1) or 1)
    if int(ctx.get("zero_stage", 0) or 0) == 0 and dp > 1 and opt > 0:
        levers.append(
            {
                "lever": "zero_stage",
                "suggestion": f"set zero_stage=1 to shard optimizer moments over dp_replicate={dp}",
                "modeled_savings_bytes": opt * (dp - 1) // dp,
            }
        )
    remat = str(ctx.get("remat_variant") or "")
    if ctx.get("kind") != "serving" and "full" not in remat:
        levers.append(
            {
                "lever": "remat",
                "suggestion": f"switch remat_variant to full (currently {remat or 'none'}) to recompute activations in backward",
                "modeled_savings_bytes": act // 2,
            }
        )
    if ctx.get("kind") != "serving":
        levers.append(
            {
                "lever": "gradient_accumulation_steps",
                "suggestion": "double gradient_accumulation_steps to halve the live microbatch",
                "modeled_savings_bytes": act // 2,
            }
        )
    if kv > 0 and ctx.get("kv_cache") == "paged":
        levers.append(
            {
                "lever": "paged_num_blocks",
                "suggestion": f"halve paged_num_blocks (currently {ctx.get('paged_num_blocks')}) to shrink the KV pool",
                "modeled_savings_bytes": kv // 2,
            }
        )
    if kv > 0 and ctx.get("quant_kv") != "int8":
        levers.append(
            {
                "lever": "quant_kv",
                "suggestion": "set quant_kv=int8 to halve KV pool bytes (bf16 -> int8 paged blocks)",
                "modeled_savings_bytes": kv // 2,
            }
        )
    levers.sort(key=lambda entry: -(entry["modeled_savings_bytes"] or 0))
    if not levers:
        levers.append(
            {
                "lever": "remat",
                "suggestion": "increase rematerialization / reduce batch geometry to shed workspace bytes",
                "modeled_savings_bytes": None,
            }
        )
    return levers


def _format_levers(levers: list) -> str:
    lines = []
    for entry in levers:
        saved = entry.get("modeled_savings_bytes")
        saved_s = f"~{saved / (1024 ** 2):.0f} MiB" if saved else "unmodeled"
        lines.append(f"  - {entry['lever']}: {entry['suggestion']} ({saved_s})")
    return "\n".join(lines)


# ------------------------------------------------------------ preflight checks


def preflight_fits_check(
    report: dict, bytes_limit: Optional[int] = None, env: Optional[dict] = None
) -> dict:
    """Compare the report's predicted per-device peak against the device
    allocation budget, after compile but before the first dispatch.

    Returns a verdict dict; raises :class:`FitsCheckFailure` when over budget
    and the mode is ``fail`` (the default). ``MODALITIES_TPU_MEMSCOPE_FITS_CHECK``
    = ``warn`` logs instead, ``off`` skips entirely. On backends with no
    bytes_limit (CPU) the check is inert — there is no budget to miss."""
    env = os.environ if env is None else env
    mode = (env.get(FITS_CHECK_ENV) or "fail").strip().lower()
    verdict = {
        "checked": False,
        "fits": None,
        "predicted_peak_bytes": int(report.get("predicted_peak_bytes", 0)),
        "bytes_limit": None,
        "mode": mode,
    }
    if mode == "off":
        return verdict
    limit = bytes_limit if bytes_limit is not None else min_bytes_limit()
    if not limit:
        return verdict  # CPU / no-budget backend: inert
    verdict["bytes_limit"] = int(limit)
    verdict["checked"] = True
    verdict["fits"] = verdict["predicted_peak_bytes"] <= int(limit)
    if verdict["fits"]:
        return verdict
    levers = report.get("levers") or rank_levers(report)
    message = (
        f"memscope fits-check: predicted per-device peak "
        f"{verdict['predicted_peak_bytes'] / (1024 ** 3):.2f} GiB exceeds the device "
        f"budget {int(limit) / (1024 ** 3):.2f} GiB — this run would die in XLA "
        "allocation. Levers, biggest modeled savings first:\n"
        f"{_format_levers(levers)}\n"
        f"Set {FITS_CHECK_ENV}=warn to proceed anyway."
    )
    if mode == "warn":
        logger.warning(message)
        return verdict
    raise FitsCheckFailure(message)


# ------------------------------------------------------------ runtime timeline


class MemoryTimeline:
    """Per-step per-device ``memory_stats()`` sampling into registry gauges and
    sink events, keeping a short tail in memory for the OOM dump. Sampling a
    backend with no numeric stats (CPU) returns None and publishes nothing —
    the timeline is inert, never noisy."""

    def __init__(self, telemetry=None, executable: str = "train_step", keep: int = 32):
        self.telemetry = telemetry
        self.executable = executable
        self.recent: deque = deque(maxlen=int(keep))

    def sample(self, step_id: int) -> Optional[dict]:
        try:
            devices = device_memory_stats()
        except Exception:
            logger.exception("memscope: timeline sample failed")
            return None
        numeric = {
            name: stats for name, stats in devices.items() if "error" not in stats and stats
        }
        if not numeric:
            return None
        in_use = max(
            s.get("bytes_in_use", s.get("peak_bytes_in_use", 0)) for s in numeric.values()
        )
        headroom = {
            name: s["bytes_limit"] - s.get("bytes_in_use", s.get("peak_bytes_in_use", 0))
            for name, s in numeric.items()
            if s.get("bytes_limit")
        }
        sample = {
            "step": int(step_id),
            "executable": self.executable,
            "bytes_in_use": int(in_use),
            "devices": numeric,
            "headroom_bytes": headroom,
        }
        self.recent.append(sample)
        telemetry = self.telemetry
        if telemetry is None:
            try:
                from modalities_tpu.telemetry import get_active_telemetry

                telemetry = get_active_telemetry()
            except Exception:
                telemetry = None
        if telemetry is not None:
            try:
                telemetry.publish_memory_timeline(sample)
            except Exception:
                logger.exception("memscope: timeline publish failed")
        return sample


def live_arrays_snapshot(top_k: int = 32) -> dict:
    """Top-K live device arrays by bytes — who actually holds the HBM when the
    step is over budget."""
    import jax

    arrays = []
    total = 0
    count = 0
    for arr in jax.live_arrays():
        try:
            nbytes = int(arr.nbytes)
            arrays.append(
                {"nbytes": nbytes, "shape": list(arr.shape), "dtype": str(arr.dtype)}
            )
            total += nbytes
            count += 1
        except Exception:
            continue
    arrays.sort(key=lambda a: -a["nbytes"])
    return {"total_bytes": total, "count": count, "arrays": arrays[: int(top_k)]}


class MemscopeWindow:
    """``jax.live_arrays()`` attribution snapshots armed by env var, the memory
    sibling of perfscope's ProfileWindow: ``MODALITIES_TPU_MEMSCOPE_AT_STEP=N``
    (one step) or ``N:K`` (K steps starting at N);
    ``MODALITIES_TPU_MEMSCOPE_DIR`` overrides the output folder. Snapshot
    failures are logged, never raised."""

    TOP_K = 32

    def __init__(self, start_step: int, num_steps: int = 1, out_dir: Optional[Path] = None):
        if num_steps < 1:
            raise ValueError(f"memscope window needs num_steps >= 1, got {num_steps}")
        self.start_step = int(start_step)
        self.num_steps = int(num_steps)
        self.out_dir = Path(out_dir) if out_dir is not None else None
        self.last_snapshot: Optional[dict] = None

    @classmethod
    def from_env(cls, fallback_dir: Optional[Path] = None) -> Optional["MemscopeWindow"]:
        raw = os.environ.get(SNAPSHOT_ENV, "").strip()
        if not raw:
            return None
        try:
            if ":" in raw:
                start_s, num_s = raw.split(":", 1)
                start, num = int(start_s), int(num_s)
            else:
                start, num = int(raw), 1
        except ValueError as e:
            raise ValueError(
                f"{SNAPSHOT_ENV}={raw!r}: expected N or N:K "
                "(snapshot K steps starting at step N)"
            ) from e
        out = os.environ.get(SNAPSHOT_DIR_ENV)
        out_dir = Path(out) if out else fallback_dir
        return cls(start, num, out_dir)

    def maybe_snapshot(self, step_id: int) -> Optional[dict]:
        """Call after `step_id` completed; snapshots inside [N, N+K)."""
        if not (self.start_step <= step_id < self.start_step + self.num_steps):
            return None
        try:
            snapshot = live_arrays_snapshot(top_k=self.TOP_K)
            snapshot["step"] = int(step_id)
            self.last_snapshot = snapshot
            out_dir = self.out_dir or Path(os.getcwd())
            write_report(snapshot, out_dir / f"memscope_live_arrays_step_{step_id}.json")
            logger.info(
                "memscope: live-array snapshot at step %d (%d arrays, %.1f MiB)",
                step_id, snapshot["count"], snapshot["total_bytes"] / (1024 ** 2),
            )
            return snapshot
        except Exception:
            logger.exception("memscope: live-array snapshot failed")
            return None


# --------------------------------------------------------------- OOM forensics


def write_oom_dump(
    artifact_dir,
    rank: int,
    step: int,
    exc: BaseException,
    static_report: Optional[dict] = None,
    timeline: Optional[MemoryTimeline] = None,
    window: Optional[MemscopeWindow] = None,
    metrics_snapshot: Optional[dict] = None,
) -> Optional[Path]:
    """Forensic artifact for a device allocation failure: what the static scope
    predicted, what the timeline saw last, who held the arrays, and which
    levers to pull. Atomic write, watchdog-dump style; never raises — the OOM
    itself still propagates, the dump is best-effort context."""
    try:
        levers = (
            rank_levers(static_report)
            if static_report
            else [
                {"lever": name, "suggestion": f"reduce memory via {name}", "modeled_savings_bytes": None}
                for name in DEFAULT_LEVERS
            ]
        )
        live = window.last_snapshot if window is not None else None
        if live is None:
            try:
                live = live_arrays_snapshot()
            except Exception:
                live = None
        artifact = {
            "event": "oom",
            "rank": int(rank),
            "step": int(step),
            "error": str(exc)[:2000],
            "wall_time": time.time(),
            "device_memory": device_memory_stats(),
            "static_report": static_report,
            "timeline_tail": list(timeline.recent) if timeline is not None else [],
            "live_arrays": live,
            "metrics": metrics_snapshot,
            "suggested_levers": levers,
        }
        artifact_dir = Path(artifact_dir)
        artifact_dir.mkdir(parents=True, exist_ok=True)
        path = artifact_dir / f"oom_dump_rank_{rank}_step_{step}.json"
        tmp = path.with_suffix(".json.tmp")
        with open(tmp, "w") as f:
            json.dump(artifact, f, indent=1, default=str)
            f.flush()
        tmp.rename(path)
        logger.error("memscope: OOM forensics dump written -> %s", path)
        return path
    except Exception:
        logger.exception("memscope: OOM dump failed (the OOM still propagates)")
        return None


def oom_forensics(
    artifact_dir,
    rank: int,
    step: int,
    exc: BaseException,
    static_report: Optional[dict] = None,
    timeline: Optional[MemoryTimeline] = None,
    window: Optional[MemscopeWindow] = None,
    metrics_snapshot: Optional[dict] = None,
):
    """Write the dump and build the resumable :class:`OutOfMemory` to raise in
    its place (``raise oom_forensics(...) from e``) so the supervisor
    warmstarts the run instead of burying the allocation failure in a generic
    crash."""
    from modalities_tpu.resilience.errors import OutOfMemory

    path = write_oom_dump(
        artifact_dir, rank, step, exc,
        static_report=static_report, timeline=timeline, window=window,
        metrics_snapshot=metrics_snapshot,
    )
    where = str(path) if path is not None else "(dump failed; see log)"
    return OutOfMemory(
        f"device allocation failed at step {step}: {str(exc)[:500]} — "
        f"forensics dump: {where}; exiting resumable so the supervisor can "
        "warmstart (possibly degraded: see suggested_levers) to resume"
    )


# --------------------------------------------------- train-step report (config)


def memscope_for_config(
    config_file_path: Union[str, Path],
    warmstart_checkpoint_folder: Optional[str] = None,
) -> dict:
    """Build the recipe's train step over its real mesh (virtual CPU devices
    suffice), compile it, and return the memscope report — same build path and
    contract as perfscope_for_config."""
    from modalities_tpu.utils.recipe_validation import build_lowered_train_step

    built = build_lowered_train_step(
        Path(config_file_path), warmstart_checkpoint_folder=warmstart_checkpoint_folder
    )
    report = built.fns.memscope_report(built.batch_abstract)
    return {
        "config": str(config_file_path),
        "world_size": built.world_size,
        "executables": {"train_step": report},
    }


def run_memscope_subprocess(
    config_file_path: Union[str, Path],
    warmstart_checkpoint_folder: Optional[str] = None,
) -> dict:
    """Re-exec `python -m modalities_tpu.telemetry.memscope` with the CPU
    backend forced and world_size virtual devices — works from any ambient
    environment, same mechanics as run_perfscope_subprocess."""
    import subprocess
    import sys

    import yaml

    config_file_path = Path(config_file_path)
    with open(config_file_path) as f:
        raw = yaml.safe_load(f)
    try:
        world_size = int(raw["device_mesh"]["config"]["world_size"])
    except (KeyError, TypeError, ValueError) as e:
        raise ValueError(
            f"{config_file_path}: could not read a literal device_mesh.config."
            "world_size — memscope needs it to size the virtual device pool"
        ) from e

    env = os.environ.copy()
    env["JAX_PLATFORMS"] = "cpu"
    flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "", env.get("XLA_FLAGS", ""))
    env["XLA_FLAGS"] = (flags + f" --xla_force_host_platform_device_count={world_size}").strip()

    cmd = [sys.executable, "-m", "modalities_tpu.telemetry.memscope", str(config_file_path)]
    if warmstart_checkpoint_folder:
        cmd += ["--warmstart_checkpoint_folder", warmstart_checkpoint_folder]
    proc = subprocess.run(cmd, env=env, capture_output=True, text=True)
    if proc.returncode != 0:
        raise RuntimeError(
            f"memscope failed for {config_file_path} (exit {proc.returncode}):\n"
            f"{proc.stdout[-2000:]}\n{proc.stderr[-4000:]}"
        )
    return json.loads(proc.stdout.strip().splitlines()[-1])


# ------------------------------------------------------------------- rendering


def format_memscope_table(report: dict) -> str:
    """Aligned text table: per-executable static buckets (MiB + share) with the
    runtime-peak/headroom line beside the static estimate when the backend
    reports memory stats."""
    executables = report.get("executables") or {"executable": report}
    runtime = device_memory_stats()
    peak = max(
        (s.get("peak_bytes_in_use", 0) for s in runtime.values() if "error" not in s),
        default=0,
    )
    limit = min_bytes_limit()
    lines = []
    for name, mod in executables.items():
        analysis = mod.get("memory_analysis") or {}
        total = int(analysis.get("total_bytes") or mod.get("predicted_peak_bytes") or 0)
        lines.append(f"{name}: predicted per-device peak {total / (1024 ** 2):.1f} MiB")
        lines.append(f"  {'bucket':<24} {'MiB':>10} {'share':>7}")
        for bucket, nbytes in sorted(
            (mod.get("buckets") or {}).items(), key=lambda kv: -kv[1]
        ):
            share = nbytes / total if total else 0.0
            lines.append(f"  {bucket:<24} {nbytes / (1024 ** 2):>10.1f} {share:>6.1%}")
        if limit:
            headroom = limit - total
            lines.append(
                f"  vs device budget: limit {limit / (1024 ** 2):.1f} MiB, "
                f"runtime peak {peak / (1024 ** 2):.1f} MiB, "
                f"static headroom {headroom / (1024 ** 2):.1f} MiB"
            )
        else:
            lines.append("  (no bytes_limit on this backend: headroom n/a)")
        lines.append("")
    return "\n".join(lines).rstrip()


# ---------------------------------------------------------- subprocess entry


def _main() -> None:
    import argparse

    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("config_file_path", type=Path)
    parser.add_argument("--warmstart_checkpoint_folder", default=None)
    args = parser.parse_args()
    report = memscope_for_config(
        args.config_file_path,
        warmstart_checkpoint_folder=args.warmstart_checkpoint_folder,
    )
    print(json.dumps(report))


if __name__ == "__main__":
    _main()
