"""Per-device kernel autotune table.

The flash-attention docstring admits its 1024x1024 blocks were tuned exactly
once (1.3B / seq-2048 / v5e); every other (device_kind, shape) pair runs an
untuned guess. This module closes that gap with a *table*, not a heuristic:

- JSON tables keyed ``{kernel}|{shape_bucket}|{dtype}`` map to block-size dicts
  (e.g. ``{"block_q": 1024, "block_k": 1024}``). ``*`` is a wildcard for the
  shape-bucket and/or dtype component.
- One file per device kind (``v5e.json``, ``v5p.json``, ...). Shipped defaults
  live in ``modalities_tpu/ops/pallas/tuning_tables/``; an operator-run sweep
  writes to ``MODALITIES_TPU_TUNE_DIR``, which takes precedence.
- ``blocks()`` is what the dispatch wrappers ask at trace time, and the one reader
  of the tables (through ``lookup()``):

      MODALITIES_TPU_TUNE_DIR table  >  shipped table  >  the caller's default

  Nothing stands in front of the tables: to give a kernel other blocks, an operator
  (or a test) puts a table under ``MODALITIES_TPU_TUNE_DIR``.
- ``tune_kernels()`` runs the timed sweep (the ``data tune_kernels`` CLI is
  its only caller) and persists what it measured. On a
  non-TPU host the sweep runs in interpret mode: the table round-trips and the
  plumbing is exercised, but the timings are emulation smoke numbers — only a
  TPU-run table is worth shipping.

Tables are data, never code: a corrupt or missing file degrades to the next
precedence level with a one-time warning, it never takes the trainer down.
"""

from __future__ import annotations

import json
import os
import re
import time
from pathlib import Path
from typing import Any, Dict, Optional

from modalities_tpu.utils.logging import get_logger

logger = get_logger(__name__)

SHIPPED_TABLE_DIR = Path(__file__).parent / "tuning_tables"
TUNE_DIR_ENV = "MODALITIES_TPU_TUNE_DIR"

# (slug, table-file stem) in match order — mirrors utils/mfu.py TPU_PEAK_FLOPS
# substring matching ("v6e" before "v6", "v5 lite" is marketing for v5e).
_DEVICE_SLUGS = (
    ("v6e", "v6e"),
    ("v6", "v6e"),
    ("v5p", "v5p"),
    ("v5e", "v5e"),
    ("v5 lite", "v5e"),
    ("v4", "v4"),
)

_table_cache: Dict[str, Optional[Dict[str, Any]]] = {}
_warned_files: set = set()


def clear_cache() -> None:
    """Drop the process-level table cache (tests re-point MODALITIES_TPU_TUNE_DIR)."""
    _table_cache.clear()
    _warned_files.clear()


def device_kind_slug(device_kind: Optional[str] = None) -> str:
    """Map a raw device_kind string ('TPU v5 lite', 'TPU v5e', ...) to a table
    file stem. Unknown kinds get a sanitized slug so operator sweeps on new
    hardware still round-trip to a loadable file name."""
    if device_kind is None:
        import jax

        device_kind = jax.devices()[0].device_kind
    lowered = device_kind.lower()
    for marker, slug in _DEVICE_SLUGS:
        if marker in lowered:
            return slug
    return re.sub(r"[^a-z0-9]+", "_", lowered).strip("_") or "unknown"


def shape_bucket(*dims: int) -> str:
    """Bucket each dim to the next power of two: lookups stay stable across the
    long tail of near-identical shapes while distinct regimes stay distinct."""
    return "x".join(str(1 << max(0, int(d) - 1).bit_length()) for d in dims)


def _load_table_file(path: Path) -> Optional[Dict[str, Any]]:
    key = str(path)
    if key in _table_cache:
        return _table_cache[key]
    table = None
    if path.is_file():
        try:
            raw = json.loads(path.read_text())
            entries = raw.get("entries", raw)
            if not isinstance(entries, dict):
                raise ValueError("tuning table 'entries' must be a JSON object")
            table = entries
        except (ValueError, OSError) as exc:
            if key not in _warned_files:
                _warned_files.add(key)
                logger.warning(f"ignoring unreadable tuning table {path}: {exc}")
            table = None
    _table_cache[key] = table
    return table


def _candidate_tables(slug: str):
    tune_dir = os.environ.get(TUNE_DIR_ENV)
    if tune_dir:
        yield Path(tune_dir) / f"{slug}.json"
    yield SHIPPED_TABLE_DIR / f"{slug}.json"


def lookup(
    kernel: str,
    bucket: str,
    dtype: str,
    device_kind: Optional[str] = None,
) -> Optional[Dict[str, Any]]:
    """Return the tuned block-size dict for (kernel, shape-bucket, dtype) on the
    current (or given) device kind, or None when no table has an answer.

    Within each table, exact keys beat wildcards; the operator's tune-dir table
    beats the shipped one."""
    slug = device_kind_slug(device_kind)
    probes = (
        f"{kernel}|{bucket}|{dtype}",
        f"{kernel}|{bucket}|*",
        f"{kernel}|*|{dtype}",
        f"{kernel}|*|*",
    )
    for path in _candidate_tables(slug):
        table = _load_table_file(path)
        if table is None:
            continue
        for probe in probes:
            hit = table.get(probe)
            if isinstance(hit, dict):
                return dict(hit)
    return None


def blocks(kernel, bucket: str, dtype, **defaults: int) -> tuple:
    """A kernel call's block sizes, in the order of `defaults`: the entry of the first of `kernel`'s
    names a table answers for (one name, or several in order: the fused flash backward asks for its
    own entry before the forward's), each size the entry lacks at the caller's default. A size a
    table holds that is no integer raises: it must never quietly become another block."""
    import jax.numpy as jnp

    dtype_name = jnp.dtype(dtype).name
    names = (kernel,) if isinstance(kernel, str) else kernel
    hit = next(filter(None, (lookup(name, bucket, dtype_name) for name in names)), {})
    return tuple(int(hit.get(size, default)) for size, default in defaults.items())


def save_table(out_dir: Path, slug: str, entries: Dict[str, Dict[str, Any]]) -> Path:
    """Merge ``entries`` into ``{out_dir}/{slug}.json`` (existing keys are
    overwritten, unrelated keys survive) and return the path written."""
    out_dir = Path(out_dir)
    out_dir.mkdir(parents=True, exist_ok=True)
    path = out_dir / f"{slug}.json"
    merged: Dict[str, Any] = {}
    if path.is_file():
        try:
            raw = json.loads(path.read_text())
            merged = raw.get("entries", raw) if isinstance(raw, dict) else {}
        except (ValueError, OSError):
            merged = {}
    merged.update(entries)
    path.write_text(json.dumps({"device_kind": slug, "entries": merged}, indent=2, sort_keys=True) + "\n")
    _table_cache.pop(str(path), None)
    return path


# --------------------------------------------------------------------- sweep


def _time_candidate(fn, iters: int = 3) -> float:
    """Best-of-N wall time of ``fn()`` (which must block on the device)."""
    fn()  # warm up / compile outside the timed region
    best = float("inf")
    for _ in range(iters):
        start = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - start)
    return best


def tune_kernels(
    out_dir: Optional[Path] = None,
    *,
    rows: int = 4096,
    n_embd: int = 1024,
    vocab_size: int = 16384,
    seq_len: int = 2048,
    n_heads: int = 8,
    head_dim: int = 128,
    dtype: str = "bfloat16",
    iters: int = 3,
    interpret: Optional[bool] = None,
    recorder=None,
    smoke: bool = False,
) -> Dict[str, Any]:
    """Timed block-size sweep for the dispatchable Pallas kernels; persists the winners.

    ``recorder`` is an optional telemetry SpanRecorder — each candidate timing
    runs inside a ``tune/{kernel}/{label}`` span so sweeps publish through the
    same pipeline as training steps. ``smoke=True`` shrinks every shape to the
    minimum that still exercises multi-tile grids (CI / CPU interpret runs).
    """
    import jax
    import jax.numpy as jnp

    from modalities_tpu.ops.pallas.flash_attention import pallas_flash_attention
    from modalities_tpu.ops.pallas.fused_ce import fused_ce_sum_and_count
    from modalities_tpu.ops.pallas.fused_rmsnorm import fused_rms_norm
    from modalities_tpu.telemetry.spans import NULL_CONTEXT

    platform = jax.devices()[0].platform
    if interpret is None:
        interpret = platform != "tpu"
    if smoke:
        rows, n_embd, vocab_size, seq_len, n_heads, head_dim = 64, 128, 384, 128, 2, 128

    def span(name):
        return recorder.span(name) if recorder is not None else NULL_CONTEXT

    slug = device_kind_slug()
    jdtype = jnp.dtype(dtype)
    rng = jax.random.PRNGKey(0)
    entries: Dict[str, Dict[str, Any]] = {}
    timings: Dict[str, Dict[str, float]] = {}

    def sweep(kernel: str, bucket: str, candidates, make_fn):
        results: Dict[str, float] = {}
        best_label, best_time, best_params = None, float("inf"), None
        for params in candidates:
            label = ",".join(f"{k}={v}" for k, v in params.items())
            try:
                fn = make_fn(**params)
                with span(f"tune/{kernel}/{label}"):
                    elapsed = _time_candidate(fn, iters=iters)
            except Exception as exc:  # an invalid block config is data, not a crash
                logger.warning(f"tune {kernel} candidate {label} failed: {exc}")
                continue
            results[label] = elapsed
            if elapsed < best_time:
                best_label, best_time, best_params = label, elapsed, params
        timings[kernel] = results
        if best_params is not None:
            entries[f"{kernel}|{bucket}|{dtype}"] = dict(best_params)
            logger.info(f"tune {kernel}: best {best_label} ({best_time * 1e3:.2f} ms)")

    # ---- flash attention: block_q x block_k over the seq bucket
    q = jax.random.normal(rng, (1, seq_len, n_heads, head_dim), dtype=jdtype)  # [B, S, H, D]

    def make_flash(block_q, block_k):
        f = jax.jit(
            lambda q: pallas_flash_attention(
                q, q, q, causal=True, block_q=block_q, block_k=block_k, interpret=interpret
            )
        )
        return lambda: jax.block_until_ready(f(q))

    flash_blocks = sorted({b for b in (128, 256, 512, 1024) if b <= seq_len})
    sweep(
        "flash_attention",
        f"sq{shape_bucket(seq_len)}_sk{shape_bucket(seq_len)}",
        [{"block_q": bq, "block_k": bk} for bq in flash_blocks for bk in flash_blocks],
        make_flash,
    )

    # ---- fused CE: block_rows x block_vocab over the (rows, vocab, embd) bucket
    hidden = jax.random.normal(rng, (rows, n_embd), dtype=jdtype)
    head_w = jax.random.normal(rng, (vocab_size, n_embd), dtype=jnp.float32)
    labels = jax.random.randint(rng, (rows,), 0, vocab_size)

    def make_ce(block_rows, block_vocab):
        f = jax.jit(
            lambda h, w, y: fused_ce_sum_and_count(
                h, w, y, block_rows=block_rows, block_vocab=block_vocab, interpret=interpret
            )
        )
        return lambda: jax.block_until_ready(f(hidden, head_w, labels))

    row_blocks = sorted({b for b in (128, 256, 512) if b <= rows} or {min(rows, 128)})
    vocab_blocks = sorted({b for b in (256, 512, 1024) if b <= vocab_size} or {min(vocab_size, 256)})
    sweep(
        "fused_ce",
        f"n{shape_bucket(rows)}_v{shape_bucket(vocab_size)}_e{shape_bucket(n_embd)}",
        [{"block_rows": bn, "block_vocab": bv} for bn in row_blocks for bv in vocab_blocks],
        make_ce,
    )

    # ---- fused RMSNorm: block_rows over the embd bucket
    x = jax.random.normal(rng, (rows, n_embd), dtype=jdtype)
    scale = jnp.ones((n_embd,), dtype=jnp.float32)

    def make_rms(block_rows):
        f = jax.jit(
            lambda x, s: fused_rms_norm(x, s, None, block_rows=block_rows, interpret=interpret)
        )
        return lambda: jax.block_until_ready(f(x, scale))

    sweep(
        "fused_rmsnorm",
        f"e{shape_bucket(n_embd)}",
        [{"block_rows": bn} for bn in row_blocks],
        make_rms,
    )

    # ---- quant matmul: block_m x block_n over the rows bucket (serving's
    # fused dequant-matmul; ops/quant_matmul.py looks winners up by row count)
    from modalities_tpu.ops.pallas.quant_matmul import quant_matmul

    wq = jax.random.randint(rng, (n_embd, 4 * n_embd), -127, 128, dtype=jnp.int8)
    wscale = jnp.full((4 * n_embd,), 0.01, dtype=jnp.float32)
    xq = jax.random.normal(rng, (rows, n_embd), dtype=jdtype)

    def make_quant_mm(block_m, block_n):
        f = jax.jit(
            lambda x, w, s: quant_matmul(
                x, w, s, block_m=block_m, block_n=block_n, interpret=interpret
            )
        )
        return lambda: jax.block_until_ready(f(xq, wq, wscale))

    mm_m_blocks = sorted({b for b in (64, 128, 256) if b <= rows} or {min(rows, 64)})
    mm_n_blocks = sorted({b for b in (128, 256, 512) if b <= 4 * n_embd} or {128})
    sweep(
        "quant_matmul",
        f"m{shape_bucket(rows)}",
        [{"block_m": bm, "block_n": bn} for bm in mm_m_blocks for bn in mm_n_blocks],
        make_quant_mm,
    )

    summary: Dict[str, Any] = {
        "device_kind": slug,
        "platform": platform,
        "interpret": bool(interpret),
        "dtype": dtype,
        "entries": entries,
        "timings": timings,
    }
    if out_dir is not None and entries:
        summary["path"] = str(save_table(Path(out_dir), slug, entries))
    return summary
