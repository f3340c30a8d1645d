"""Continuous-batching decode engine over the slot-indexed GPT2 KV cache.

Design (the GSPMD serving argument, arXiv 2105.04663): training already produced
mesh-sharded params and sharding rules; serving reuses them unchanged. KV memory
is allocated ONCE at a static shape and annotated with the same NamedShardings
(slots/blocks ride the "batch" logical axis, kv heads the "kv_heads"/tp axis,
layers the pp axis), so XLA partitions the decode step exactly like a train step
— no serving-specific parallelism code.

Two cache layouts, selected by the static `kv_cache` knob:

- `ring` (serving v1): per-slot ring rows [max_batch_slots, cache_capacity].
  Prompt prefill is per-request on the `_PREFILL_CHUNKS` power-of-two ladder;
  a request whose prompt+generation hits the ring end finishes `"capacity"`.
- `paged` (serving v2, vLLM-style): ONE global block pool per scanned layer
  [num_blocks, block_size, kv_heads, head_dim] plus host-side block tables
  (serving/paged_cache.py) passed to the jitted step as traced int32 arrays.
  Blocks are allocated on demand, so the `"capacity"` finish disappears — the
  per-request ceiling is the static table width, and the generation budget is
  clamped to it at admission ("budget", never "capacity"). Pool exhaustion
  preempts the YOUNGEST slot back to the queue (blocks freed, request requeued
  — deterministic sampling reproduces the same tokens on re-admission).
  Prefill is chunked ACROSS requests (Sarathi-style): one fixed-shape
  [slots, block_size] dispatch packs prompt chunks from several waiting
  requests, so long prompts no longer head-of-line-block decode.

Execution model:
- decode: ONE compiled step advances every slot by one token per dispatch.
  Per-slot temperature/greedy sampling and per-slot eod/budget stopping are
  folded into the step via `jnp.where` — no per-request recompiles, no host
  round-trip per token beyond the single small (tokens, finished) fetch.
- scheduling (plain Python, off the jitted path): a FIFO queue admits requests
  into idle slots at token boundaries; finished slots are evicted immediately,
  so under load the batch stays full instead of draining to the slowest
  request. `stop_fn` (graceful drain) stops admission; in-flight slots finish.

Serving v3 (paged only) adds the two big tokens/s multipliers:

- prefix sharing: admission looks the prompt window up in the block-table
  state's prefix index; matched full blocks are FORKED into the new request's
  table (refcount bump, no re-prefill) and the chunked prefill runs only on
  the unmatched tail. A full-window match copy-on-writes the last shared
  block (fresh block + one jitted device row-copy) and re-forwards just the
  final prompt token to produce the first-token logits. Shared blocks are
  never written (generated positions live in private blocks), `release` only
  returns a block to the free list at refcount 0, and preempting a holder of
  shared blocks can therefore free zero blocks without ever corrupting a
  donor.
- speculative decoding (`spec_decode` config block, k > 0): a zero-cost
  prompt-lookup n-gram drafter proposes up to k tokens per greedy slot, and
  ONE fixed-shape `[slots, k+1]` verify forward (model.verify_paged) scores
  every proposal; accept lengths fold in via cumprod/`jnp.where`, so the
  decode side stays exactly TWO executables (1-token decode + verify) no
  matter what k accepts. Greedy emission takes the verify argmax row, which
  IS the sequential greedy trajectory — bitwise identity with the
  interactive path is proposal-independent by construction.

Batch-invariance contract (pinned by tests/serving/test_engine.py and
test_paged_engine.py): with exactly one active slot the engine emits
token-for-token what the interactive `_generate_cached` path emits for the same
(prompt, budget, temperature, seed) — same key-split sequence, same categorical
shapes, bitwise-identical logits rows — in BOTH cache modes. For paged mode the
gathered K/V row is position-ordered and garbage positions are masked to exact
zeros, so the softmax reduction matches the ring row bitwise. Prefix sharing
and spec decode both preserve the contract: forked blocks hold bitwise the
bytes the request's own prefill would have produced (chunk packing is
bitwise-invariant, pinned since PR 9), and spec verify columns attend exactly
the K/V a sequential decode would.
"""

from __future__ import annotations

import os
import threading
import time
import uuid
from collections import deque
from dataclasses import dataclass, field
from typing import Callable, Optional

import numpy as np

from pathlib import Path

from modalities_tpu.resilience.faults import (
    fire_handoff_corrupt_if_armed,
    fire_oom_if_armed,
    fire_queue_storm_if_armed,
    fire_serve_worker_hang_if_armed,
    fire_slow_decode_if_armed,
    fire_tenant_flood_if_armed,
)
from modalities_tpu.serving.paged_cache import BlockTableState, blocks_for_tokens
from modalities_tpu.serving.resilience import (
    TenantRegistry,
    deadline_expired,
    resolve_tenant,
)
from modalities_tpu.serving.spec_decode import propose_ngram, resolve_spec_config
from modalities_tpu.telemetry import get_active_telemetry, span
from modalities_tpu.telemetry.metrics import MetricsRegistry

# mirror of TextInferenceComponent._PREFILL_CHUNKS: the same power-of-two ladder,
# overridable via MODALITIES_TPU_SERVE_PREFILL_CHUNKS (comma list, descending,
# must end in 1 so any prompt length decomposes)
_DEFAULT_PREFILL_CHUNKS = (64, 16, 4, 1)

_IDLE_REMAINING = np.int32(2**30)  # idle slots never trip the budget stop


def _prefill_chunks_from_env() -> tuple[int, ...]:
    raw = os.environ.get("MODALITIES_TPU_SERVE_PREFILL_CHUNKS")
    if not raw:
        return _DEFAULT_PREFILL_CHUNKS
    chunks = tuple(int(c) for c in raw.split(",") if c.strip())
    if not chunks or chunks[-1] != 1 or list(chunks) != sorted(chunks, reverse=True):
        raise ValueError(
            f"MODALITIES_TPU_SERVE_PREFILL_CHUNKS={raw!r}: need a descending comma "
            "list ending in 1 (e.g. '64,16,4,1')"
        )
    return chunks


def _prefix_sharing_from_env() -> bool:
    raw = os.environ.get("MODALITIES_TPU_SERVE_PREFIX_SHARING", "1").strip().lower()
    if raw in ("1", "true", "on", "yes"):
        return True
    if raw in ("0", "false", "off", "no"):
        return False
    raise ValueError(
        f"MODALITIES_TPU_SERVE_PREFIX_SHARING={raw!r}: must be a boolean "
        "(1/0/true/false/on/off)"
    )


def _kv_cache_from_env() -> str:
    raw = os.environ.get("MODALITIES_TPU_SERVE_KV_CACHE", "ring")
    if raw not in ("ring", "paged"):
        raise ValueError(
            f"MODALITIES_TPU_SERVE_KV_CACHE={raw!r}: must be 'ring' or 'paged'"
        )
    return raw


@dataclass
class ServeRequest:
    """One generation request. `temperature=None` inherits the engine default
    (which itself defaults to greedy); `arrival_offset_s` is seconds after
    `run()` starts — the load generator replays traces with it."""

    rid: int
    prompt_tokens: list[int]
    max_new_tokens: int
    temperature: Optional[float] = None
    seed: int = 0
    arrival_offset_s: float = 0.0
    # serving resilience (PR 19): `deadline_ms` is the request's budget from
    # LOCAL arrival — once elapsed the scheduler cancels it at the next seam
    # (finish reason "deadline"); `priority` orders brownout shedding (higher
    # number = shed first), FIFO is preserved within a priority class
    deadline_ms: Optional[float] = None
    priority: int = 0
    # multi-tenant isolation (PR 20): the tenant this request is charged to.
    # "" = the engine runs tenant-off (single implicit tenant, pure FIFO)
    tenant: str = ""


@dataclass
class ServeResult:
    rid: int
    tokens: list[int] = field(default_factory=list)
    finish_reason: str = ""  # "eod" | "budget" | "capacity" | "error" | "handoff" | "deadline" | "shed"
    prompt_len: int = 0
    weights_generation: int = 0  # generation serving when the request finished
    truncated: bool = False  # prompt window-clipped at admission
    prefix_hit_tokens: int = 0  # prompt tokens served from shared blocks (v3)
    arrival_s: float = 0.0  # engine-clock arrival
    first_token_s: float = 0.0  # engine-clock time the first token was available
    finish_s: float = 0.0
    queue_wait_s: float = 0.0  # enqueue (and every requeue) to slot admission, summed; set at finish
    token_times_s: list[float] = field(default_factory=list)
    # fleet-wide request tracing (PR 13): ONE trace_id spans router -> every
    # worker leg (a failover replay keeps the id, hop increments per leg)
    trace_id: str = ""
    trace_hop: int = 0
    # disaggregated serving (serving/disagg/): a prefill-tier engine finishes
    # with reason "handoff" and parks the exported record here for the caller
    # (HTTP /disagg/prefill or the in-process pair) to ship to the decode tier
    handoff: Optional[object] = None

    @property
    def ttft_s(self) -> float:
        return self.first_token_s - self.arrival_s


@dataclass
class _ImportRequest(ServeRequest):
    """A queued KV import on a decode-tier engine. Rides the same FIFO queue
    and preemption path as a plain request (``_preempt`` requeues it at the
    front; re-admission re-imports from the retained record — deterministic
    replay from the sealed sampler state)."""

    record: object = None  # HandoffRecord (kept untyped: no disagg import here)
    pool_full_seen: bool = False  # count the pool_full failure once per import


@dataclass
class _SlotState:
    request: ServeRequest
    result: ServeResult
    remaining: int  # tokens still allowed, counting the one in flight
    phase: str = "decode"  # "prefill" (paged, prompt in flight) | "decode"
    window: Optional[list[int]] = None  # paged: the admitted prompt window
    prefill_pos: int = 0  # paged: prompt tokens already forwarded
    key: object = None  # paged: jax PRNG key while prefilling
    temp: float = 0.0
    seq: int = 0  # admission order — preemption picks the max (youngest)
    imported: bool = False  # disagg: seeded from a handoff (TTFT = first decode)


class ServingEngine:
    """See module docstring. `params` is the unboxed variables dict
    ({"params": ...}); `mesh_handle` (optional) shards params + cache over the
    training mesh via parallel/sharding.py rules."""

    def __init__(
        self,
        model,
        params,
        *,
        max_batch_slots: int = 8,
        cache_capacity: Optional[int] = None,
        eod_token_id: int = -1,
        default_temperature: Optional[float] = None,
        prefill_chunks: Optional[tuple[int, ...]] = None,
        kv_cache: Optional[str] = None,
        paged_block_size: int = 16,
        paged_num_blocks: Optional[int] = None,
        paged_max_len: Optional[int] = None,
        prefix_sharing: Optional[bool] = None,
        spec_decode=None,
        quant_weights: Optional[str] = None,
        quant_kv: Optional[str] = None,
        max_queue_depth: Optional[int] = None,
        brownout=None,
        tenants: Optional[TenantRegistry] = None,
        tenant_budget_fn: Optional[Callable[[str], float]] = None,
        stop_fn: Optional[Callable[[], bool]] = None,
        on_token: Optional[Callable[[int, int], None]] = None,
        on_finish: Optional[Callable[[int, ServeResult], None]] = None,
        mesh_handle=None,
        time_fn=None,
        metrics: Optional[MetricsRegistry] = None,
        role: str = "combined",
    ):
        if role not in ("combined", "prefill", "decode"):
            raise ValueError(
                f"role={role!r}: must be 'combined', 'prefill' or 'decode'"
            )
        self.role = role
        if not (hasattr(model, "init_slot_cache") and hasattr(model, "decode_slots")):
            raise ValueError(
                f"{type(model).__name__} does not expose the slot-cache decode API "
                "(init_slot_cache/prefill_slot/decode_slots)"
            )
        self.kv_cache = kv_cache if kv_cache is not None else _kv_cache_from_env()
        if self.kv_cache not in ("ring", "paged"):
            raise ValueError(f"kv_cache={self.kv_cache!r}: must be 'ring' or 'paged'")
        if self.kv_cache == "paged" and not hasattr(model, "init_paged_cache"):
            raise ValueError(
                f"{type(model).__name__} does not expose the paged decode API "
                "(init_paged_cache/prefill_paged/decode_paged)"
            )
        # quantized inference (quant/): weight-only quantization swaps the model
        # for its QuantDenseGeneral variant and (idempotently) quantizes the
        # params — a tree already quantized by load_serving_params passes
        # through unchanged, so every entry path yields the same generation.
        from modalities_tpu.quant.kv import resolve_quant_kv_mode
        from modalities_tpu.quant.weights import (
            infer_quant_mode,
            quantize_params,
            quantized_model,
            resolve_quant_weights_mode,
            weights_bytes_saved,
        )

        self.quant_weights = resolve_quant_weights_mode(quant_weights)
        self.quant_kv = resolve_quant_kv_mode(quant_kv)
        if self.quant_kv != "none" and self.kv_cache != "paged":
            raise ValueError(
                f"quant_kv={self.quant_kv!r} requires kv_cache='paged': only the "
                "block pool stores per-block scales alongside the K/V data"
            )
        pre_mode = infer_quant_mode(params)
        if pre_mode not in ("none", self.quant_weights):
            raise ValueError(
                f"params arrive quantized as {pre_mode!r} but the engine is "
                f"configured for quant_weights={self.quant_weights!r} — quantize "
                "every generation through the same load_serving_params seam"
            )
        self._quant_bytes_saved = 0
        if self.quant_weights != "none":
            model = quantized_model(model, self.quant_weights)
            params = quantize_params(params, self.quant_weights)
            self._quant_bytes_saved = weights_bytes_saved(params)
        self._infer_quant_mode = infer_quant_mode  # swap drift check reuses it

        spec_len = int(model.config_spec.sequence_length)
        self.model = model
        self.params = params
        self.slots = int(max_batch_slots)
        self.capacity = min(int(cache_capacity), spec_len) if cache_capacity else spec_len
        self.eod_token_id = int(eod_token_id)
        self.default_temperature = default_temperature
        self.prefill_chunks = tuple(prefill_chunks) if prefill_chunks else _prefill_chunks_from_env()
        self.prefix_sharing = (
            bool(prefix_sharing) if prefix_sharing is not None else _prefix_sharing_from_env()
        )
        self.spec = resolve_spec_config(spec_decode)
        if self.kv_cache != "paged":
            # both v3 multipliers ride the paged block tables; on the ring they
            # silently degrade to the v1 path (sharing) or are rejected (spec)
            self.prefix_sharing = False
            if self.spec.enabled:
                raise ValueError(
                    "spec_decode.k > 0 requires kv_cache='paged': the verify "
                    "forward runs through the paged block tables"
                )
        # disaggregated roles (serving/disagg/): the handoff payload is pool
        # blocks, so both tiers require the paged cache; the prefill tier never
        # decodes, so speculative decode there is a config error, not a no-op
        if self.role != "combined" and self.kv_cache != "paged":
            raise ValueError(
                f"role={self.role!r} requires kv_cache='paged': the KV handoff "
                "ships pool blocks"
            )
        if self.role == "prefill" and self.spec.enabled:
            raise ValueError(
                "role='prefill' excludes spec_decode: the prefill tier stops at "
                "the first token and never builds a decode (or verify) program"
            )
        self._now = time_fn if time_fn is not None else time.monotonic
        self._stop_fn = stop_fn
        self._on_token = on_token
        self._on_finish = on_finish
        if self.slots < 1:
            raise ValueError("max_batch_slots must be >= 1")
        if self.capacity < 2:
            raise ValueError("cache_capacity must be >= 2 (1 prompt token + 1 generated)")

        if self.kv_cache == "paged":
            from modalities_tpu.models.gpt2.gpt2_model import PositionTypes

            bs = int(paged_block_size)
            if bs < 1:
                raise ValueError(f"paged_block_size must be >= 1, got {bs}")
            # per-request length ceiling = static table width * block size; the
            # default inherits the ring semantics (cache_capacity / seq len) but
            # paged_max_len may exceed sequence_length for relative-position
            # models — that is the length-ceiling lift
            max_len = int(paged_max_len) if paged_max_len else self.capacity
            if max_len < 2:
                raise ValueError("paged_max_len must be >= 2")
            if (
                max_len > spec_len
                and model.config_spec.poe_type == PositionTypes.ABSOLUTE.value
            ):
                raise ValueError(
                    f"paged_max_len {max_len} exceeds sequence_length {spec_len}: "
                    "ABSOLUTE position embeddings have no rows past the trained "
                    "sequence length"
                )
            self.block_size = bs
            self.table_width = blocks_for_tokens(max_len, bs)
            self.max_len = self.table_width * bs  # round the ceiling up to blocks
            self.num_blocks = (
                int(paged_num_blocks) if paged_num_blocks else self.slots * self.table_width
            )
            if self.num_blocks < self.table_width:
                raise ValueError(
                    f"paged_num_blocks {self.num_blocks} < table width "
                    f"{self.table_width}: one max-length request must fit the pool "
                    "(otherwise preemption livelocks)"
                )
        else:
            self.block_size = 0
            self.table_width = 0
            self.max_len = self.capacity
            self.num_blocks = 0

        self._mesh_handle = mesh_handle
        self._rules = None
        self._cache_shardings = None
        if mesh_handle is not None:
            self._install_shardings(mesh_handle)

        import jax
        import jax.numpy as jnp

        self._jnp = jnp
        if self.kv_cache == "paged":
            self.cache = model.init_paged_cache(
                params, self.num_blocks, self.block_size, kv_quant=self.quant_kv
            )
            self._table_state = BlockTableState(
                self.num_blocks, self.block_size, self.table_width
            )
        else:
            self.cache = model.init_slot_cache(params, self.slots, self.capacity)
            self._table_state = None
        if self._cache_shardings is not None:
            self.cache = jax.device_put(self.cache, self._cache_shardings)
        # handoff payloads are per-leaf host arrays in tree-flatten order; the
        # treedef rebuilds them into a cache-shaped tree on the import side
        self._cache_treedef = (
            jax.tree.structure(self.cache) if self.kv_cache == "paged" else None
        )

        # host-side mirrors of the per-slot device state
        b = self.slots
        self._tokens = np.zeros((b, 1), np.int32)
        self._positions = np.zeros((b,), np.int32)
        self._keys = np.zeros((b, 2), np.uint32)
        self._temps = np.ones((b,), np.float32)
        self._eods = np.full((b,), -1, np.int32)
        self._remaining = np.full((b,), _IDLE_REMAINING, np.int32)
        self._slot_states: list[Optional[_SlotState]] = [None] * b
        if self.kv_cache == "paged":
            self._tables = np.zeros((b, self.table_width), np.int32)
            self._wblk = np.full((b,), self.num_blocks, np.int32)  # idle: dropped
            self._woff = np.zeros((b,), np.int32)

        self._queue: deque[ServeRequest] = deque()
        self._results: dict[int, ServeResult] = {}
        self._next_rid = 0
        self._admit_seq = 0
        # overload protection (PR 19): a bounded queue is the 429 signal for
        # the HTTP layer; `brownout` (serving/resilience.py) is the SLO-driven
        # shedder the scheduler consults once per round. Both default off, so
        # existing entry points are untouched.
        if max_queue_depth is None:
            env_depth = int(os.environ.get("MODALITIES_TPU_SERVE_QUEUE_LIMIT", "0"))
            max_queue_depth = env_depth if env_depth > 0 else None
        self.max_queue_depth = max_queue_depth
        self.brownout = brownout
        # multi-tenant isolation (PR 20): with a TenantRegistry the admission
        # order becomes weighted deficit-round-robin across tenants (within
        # each priority class, FIFO within a tenant) and every destructive
        # choice (shed, preempt) becomes burn-aware. `tenants=None` keeps the
        # HEAD scheduler byte-for-byte: single implicit tenant, pure FIFO.
        self._tenants = tenants
        self._tenant_budget_fn = tenant_budget_fn
        self._drr_deficit: dict[str, float] = {}
        self._drr_cursor: str = ""
        self._tenant_stats: dict[str, dict] = {}
        self._streamed: dict[int, int] = {}  # rid -> tokens already on_token'd
        self._truncated_rids: set[int] = set()  # count once even across preemption

        # trace counters: the traced fn bodies run once per COMPILATION, so these
        # pin "one decode executable, bounded prefill ladder" in tests; serving
        # v3 adds _verify_traces (must stay <= 1: the SECOND decode-side
        # program) and _cow_traces (one jitted row-copy, traced src/dst)
        self._decode_traces = 0
        self._prefill_traces = 0
        self._verify_traces = 0
        self._cow_traces = 0
        self.decode_steps = 0
        self.decode_token_count = 0
        self._occupancy_sum = 0
        self.max_concurrent = 0
        self.preemptions = 0
        self.truncated_requests = 0
        # serving v3 counters (all under _stats_lock)
        self.prefix_hit_requests = 0
        self.prefix_hit_blocks = 0
        self.prefix_hit_tokens = 0
        self.cow_copies = 0
        self.verify_steps = 0
        self.spec_proposed = 0
        self.spec_accepted = 0
        # disaggregated serving (serving/disagg/): export/import accounting plus
        # the two extra one-executable pins (_handoff_traces on the prefill
        # tier's gather, _import_traces on the decode tier's scatter)
        self._handoff_traces = 0
        self._import_traces = 0
        self.handoffs_exported = 0
        self.handoffs_imported = 0
        self.import_requeues = 0
        self.imported_blocks = 0
        self.handoff_bytes_shipped = 0
        self.prefill_chunk_count = 0  # packed prefill rows (modeled-cost clocks)
        # counters/gauges above mutate only under this lock, and stats() reads
        # under it — /stats sees one consistent snapshot, never a mid-dispatch
        # tear (decode_tokens without its decode_steps)
        self._stats_lock = threading.Lock()

        # fleet hot swap (PR 12): request_swap() queues new params from any
        # thread; step() installs them at the next token boundary. Generation
        # tags every finished result/trace; swap_history keeps one record a swap.
        self.weights_generation = 0
        self.weight_swaps = 0
        self.request_errors = 0  # finishes with reason "error" (non-finite logits)
        self.deadline_expired_requests = 0  # finishes with reason "deadline"
        self.shed_requests = 0  # finishes with reason "shed" (brownout)
        self.swap_history: list[dict] = []
        self._swap_lock = threading.Lock()
        self._pending_swap: Optional[tuple] = None

        # request-lifecycle tracing (PR 10): per-rid monotonic event streams,
        # flushed as one `serve_request` JSONL record at finish; a preempted
        # request keeps its stream across requeue/replay
        self._traces: dict[int, dict] = {}
        self._dispatch_seq = 0  # watchdog heartbeat id for serve dispatches

        self.metrics = metrics if metrics is not None else get_active_telemetry().metrics
        reg = self.metrics
        self._m_ttft = reg.histogram(
            "serve_ttft_seconds", "Time from request arrival to its first token"
        )
        self._m_tpot = reg.histogram(
            "serve_tpot_seconds", "Latency between consecutive generated tokens"
        )
        self._m_queue_wait = reg.histogram(
            "serve_queue_wait_seconds", "Time from enqueue/requeue to slot admission"
        )
        self._m_e2e = reg.histogram(
            "serve_e2e_latency_seconds", "Time from request arrival to finish"
        )
        self._m_submitted = reg.counter(
            "serve_requests_submitted_total", "Requests accepted by submit()"
        )
        self._m_finished = reg.counter(
            "serve_requests_finished_total", "Finished requests by finish reason"
        )
        self._m_tokens = reg.counter(
            "serve_tokens_generated_total", "Generated tokens emitted to clients"
        )
        self._m_prompt_tokens = reg.counter(
            "serve_prompt_tokens_total", "Prompt tokens accepted at submit()"
        )
        self._m_prefill_chunks = reg.counter(
            "serve_prefill_chunks_total", "Prefill chunk dispatches (ring) / packed rows (paged)"
        )
        self._m_decode_steps = reg.counter(
            "serve_decode_steps_total", "Batched decode dispatches"
        )
        self._m_preempt = reg.counter(
            "serve_preemptions_total", "Slots preempted on paged pool exhaustion"
        )
        self._m_trunc = reg.counter(
            "serve_truncated_requests_total", "Requests whose prompt was window-clipped"
        )
        # scheduler gauges are scrape-time callbacks: a GET /metrics racing the
        # engine thread reads LIVE state, never a value one dispatch stale
        reg.gauge("serve_active_slots", "Slots holding a live request").set_fn(
            self._active_count
        )
        reg.gauge("serve_queue_depth", "Requests waiting in the FIFO queue").set_fn(
            lambda: len(self._queue)
        )
        reg.gauge(
            "serve_slot_occupancy_ratio", "Decoding slots over total slots, cumulative mean"
        ).set_fn(self._occupancy_ratio)
        reg.gauge("serve_slots_total", "Configured max_batch_slots").set(self.slots)
        self._m_prefix_hit_blocks = reg.counter(
            "serve_prefix_hit_blocks_total", "Prompt blocks served from the prefix index"
        )
        self._m_prefix_hit_requests = reg.counter(
            "serve_prefix_hit_requests_total", "Admissions that forked shared prefix blocks"
        )
        self._m_cow = reg.counter(
            "serve_cow_copies_total", "Copy-on-write block copies (shared block first write)"
        )
        self._m_spec_proposed = reg.counter(
            "serve_spec_proposed_total", "Draft tokens proposed to the spec-decode verifier"
        )
        self._m_spec_accepted = reg.counter(
            "serve_spec_accepted_total", "Draft tokens accepted by the spec-decode verifier"
        )
        self._m_swaps = reg.counter(
            "serve_weight_swaps_total", "Hot weight swaps installed by the engine"
        )
        self._m_req_errors = reg.counter(
            "serve_request_errors_total",
            "Requests finished with reason=error (non-finite logits)",
        )
        # serving resilience (PR 19): cancellation + overload accounting
        self._m_deadline_expired = reg.counter(
            "serve_deadline_expired_total",
            "Requests cancelled at a scheduler seam after their deadline expired",
        )
        self._m_shed = reg.counter(
            "serve_shed_total",
            "Requests shed under overload, by reason (brownout = queued work "
            "dropped by the SLO shedder, queue_full/brownout_reject = new "
            "arrivals refused with 429 at the HTTP layer)",
        )
        # multi-tenant isolation (PR 20): every series carries a tenant label;
        # the families are registered unconditionally so a tenant-off scrape
        # still names them, but series only appear once tenants move traffic
        self._m_tenant_requests = reg.counter(
            "serve_tenant_requests_total", "Requests accepted by submit(), by tenant"
        )
        self._m_tenant_tokens = reg.counter(
            "serve_tenant_tokens_total", "Generated tokens delivered, by tenant"
        )
        self._m_tenant_shed = reg.counter(
            "serve_tenant_shed_total",
            "Requests shed under overload, by tenant (brownout sheds + HTTP-layer "
            "429 rejections)",
        )
        self._m_tenant_preempt = reg.counter(
            "serve_tenant_preemptions_total", "Slots preempted on pool exhaustion, by tenant"
        )
        self._m_tenant_rate_limited = reg.counter(
            "serve_tenant_rate_limited_total",
            "Requests refused 429 by the per-tenant token-rate bucket",
        )
        self._m_tenant_active = reg.gauge(
            "serve_tenant_active_slots", "Slots holding a live request, by tenant"
        )
        if self._tenants is not None:
            for _name in self._tenants.names():
                self._m_tenant_active.set_fn(
                    lambda n=_name: self._tenant_active_slots(n), tenant=_name
                )
        self._m_generation = reg.gauge(
            "serve_weights_generation", "Weights generation currently installed"
        )
        self._m_generation.set(0)
        # quantized inference (quant/): pool/weight byte accounting + the mode
        # info gauge (value always 1; the modes ride the labels, Prometheus
        # *_info convention)
        from modalities_tpu.quant.core import tree_bytes

        self.kv_pool_bytes = tree_bytes(self.cache)
        reg.gauge(
            "serve_kv_pool_bytes",
            "Device bytes held by the serving KV cache (pools + quant scales)",
        ).set(self.kv_pool_bytes)
        reg.gauge(
            "serve_quant_weights_bytes_saved",
            "Param bytes saved by weight-only quantization (net of scale arrays)",
        ).set(self._quant_bytes_saved)
        reg.gauge(
            "serve_quant_mode_info",
            "Active quantization modes as labels (weights=, kv=); value is always 1",
        ).set(1.0, weights=self.quant_weights, kv=self.quant_kv)
        if self.kv_cache == "paged":
            reg.gauge(
                "serve_paged_free_blocks", "Free blocks in the paged KV pool"
            ).set_fn(lambda: self._table_state.pool.free_count)
            reg.gauge(
                "serve_paged_blocks_in_use_peak",
                "High-water mark of paged KV blocks in use since run() last started",
            ).set_fn(lambda: self._table_state.pool.peak_used)
            reg.gauge("serve_paged_total_blocks", "Configured paged KV pool size").set(
                self.num_blocks
            )
            reg.gauge(
                "serve_shared_blocks", "Pool blocks referenced by more than one table"
            ).set_fn(lambda: self._table_state.pool.shared_count)

        # disaggregated serving: both tiers register the family so a scrape of
        # either worker names every series; the prefill tier moves handoffs_total
        # + kv_bytes, the decode tier moves failures + the handoff latency
        # histogram (arrival -> slot seeded, so pool_full starvation shows up as
        # tail inflation — the runbook signal)
        self._m_handoffs = reg.counter(
            "disagg_handoffs_total", "KV handoff records exported by the prefill tier"
        )
        self._m_handoff_failures = reg.counter(
            "disagg_handoff_failures_total",
            "Handoff imports rejected or requeued, by reason "
            "(pool_full, digest_mismatch, generation_mismatch, peer_down, ...)",
        )
        self._m_kv_shipped = reg.counter(
            "disagg_kv_bytes_shipped_total",
            "KV payload bytes shipped across the prefill->decode tier boundary",
        )
        self._m_handoff_seconds = reg.histogram(
            "disagg_handoff_seconds",
            "Handoff latency: prefill-side export (or import arrival) to the "
            "decode-tier slot being seeded",
        )

        # a wedged serve dispatch dumps the same watchdog artifact as a wedged
        # train step, with the engine's own stats in the `state` section
        get_active_telemetry().register_watchdog_state_provider(
            lambda: {"serving_engine": self.stats()}
        )

        self._build_jits()

    # ------------------------------------------------------------------ sharding
    def _install_shardings(self, mesh_handle) -> None:
        import jax
        from jax.sharding import NamedSharding

        from modalities_tpu.parallel.sharding import (
            default_logical_axis_rules,
            logical_to_mesh_spec,
            params_shardings,
        )

        self._rules = default_logical_axis_rules(mesh_handle)
        dp = int(mesh_handle.degrees.get("dp_replicate", 1)) * int(
            mesh_handle.degrees.get("dp_shard", 1)
        )
        if self.slots % max(dp, 1) != 0:
            raise ValueError(
                f"max_batch_slots={self.slots} must be divisible by the mesh's data-"
                f"parallel degree {dp}: cache slots ride the 'batch' logical axis"
            )
        if self.kv_cache == "paged" and self.num_blocks % max(dp, 1) != 0:
            raise ValueError(
                f"paged_num_blocks={self.num_blocks} must be divisible by the mesh's "
                f"data-parallel degree {dp}: pool blocks ride the 'batch' logical axis"
            )
        mesh = mesh_handle.mesh

        def leaf_sharding(leaf):
            # scanned cache leaf: [layers, slots|blocks, capacity|block_size,
            # kv_heads, head_dim] — ring rows and pool blocks ride the same axes
            if leaf.ndim == 5:
                axes = ("layers", "batch", None, "kv_heads", "head_dim")
            elif leaf.ndim == 4:  # unrolled blocks
                axes = ("batch", None, "kv_heads", "head_dim")
            else:
                axes = (None,) * leaf.ndim
            logical = tuple(a if a is not None else "head_dim" for a in axes)
            spec = logical_to_mesh_spec(logical, self._rules)
            # "head_dim" resolves to None in the rules — used here as the
            # explicit "replicated dim" placeholder
            return NamedSharding(mesh, spec)

        if self.kv_cache == "paged":
            abstract_cache = jax.eval_shape(
                lambda: self.model.init_paged_cache(
                    self.params, self.num_blocks, self.block_size, kv_quant=self.quant_kv
                )
            )
        else:
            abstract_cache = jax.eval_shape(
                lambda: self.model.init_slot_cache(self.params, self.slots, self.capacity)
            )
        self._cache_shardings = jax.tree.map(leaf_sharding, abstract_cache)

        abstract_params = jax.eval_shape(
            lambda: self.model.init_params(jax.random.PRNGKey(0))
        )
        self.params = jax.device_put(
            self.params, params_shardings(abstract_params, self._rules, mesh)
        )

    def _rules_ctx(self):
        from contextlib import nullcontext

        if self._rules is None:
            return nullcontext()
        from modalities_tpu.parallel.sharding import activation_rules

        return activation_rules(self._rules, self._mesh_handle.mesh)

    # ------------------------------------------------------------------ hot swap
    def request_swap(self, params, generation: Optional[int] = None) -> threading.Event:
        """Queue a weight swap from ANY thread; the engine thread installs it at
        the next step() boundary (between decode dispatches — never mid-token).
        Returns an event set once the swap is installed. Only the latest pending
        swap survives: a superseded one has its event set without installing."""
        done = threading.Event()
        with self._swap_lock:
            if self._pending_swap is not None:
                self._pending_swap[2].set()
            self._pending_swap = (params, generation, done)
        return done

    def _maybe_apply_swap(self) -> None:
        with self._swap_lock:
            pending, self._pending_swap = self._pending_swap, None
        if pending is None:
            return
        params, generation, done = pending
        try:
            self.swap_weights(params, generation)
        finally:
            done.set()

    def swap_weights(self, params, generation: Optional[int] = None) -> dict:
        """Install new params between decode steps — the hot half of the fleet
        deployment loop (serving/fleet/). Zero dropped requests: slot state,
        KV cache and queue are untouched, in-flight requests simply continue
        under the new weights. Zero recompiles: every leaf is device_put onto
        the OLD leaf's sharding after an aval check, so the pinned decode/
        prefill/verify executables see identical (shape, dtype, sharding)
        arguments. The prefix-sharing index is flushed — resident KV was
        computed under the old weights and must not be forked into
        new-generation requests (live holders keep their blocks).

        `generation` may move backward (canary rollback re-installs the donor
        generation). Call from the engine thread; other threads go through
        request_swap()."""
        import jax

        start = self._now()
        gen = int(generation) if generation is not None else self.weights_generation + 1
        # quantization-mode drift gate (before any leaf comparison): a fleet
        # rollout must never install a generation quantized differently from
        # the incumbent — mixed bf16/int8 leaves would either fail the aval
        # check leaf-by-leaf with a misleading message or, worse, silently
        # change serving numerics mid-fleet
        new_mode = self._infer_quant_mode(params)
        if new_mode != self.quant_weights:
            from modalities_tpu.resilience.events import record_event

            record_event(
                "fleet/rollback",
                stage="quant",
                installed=self.quant_weights,
                offered=new_mode,
                generation=gen,
            )
            raise ValueError(
                f"swap_weights: quantization mode drift (installed "
                f"{self.quant_weights!r}, offered {new_mode!r}) — every generation "
                "must be quantized through the same load_serving_params seam"
            )
        old_leaves, old_def = jax.tree.flatten(self.params)
        new_leaves, new_def = jax.tree.flatten(params)
        if old_def != new_def:
            raise ValueError(
                f"swap_weights: param tree changed ({new_def} != {old_def}) — a hot "
                "swap must keep the architecture identical"
            )
        placed = []
        for old, new in zip(old_leaves, new_leaves):
            if (old.shape, old.dtype) != (new.shape, new.dtype):
                raise ValueError(
                    f"swap_weights: leaf {new.shape}/{new.dtype} does not match the "
                    f"installed {old.shape}/{old.dtype} — identical avals are what "
                    "keep the ONE decode executable warm"
                )
            sharding = getattr(old, "sharding", None)
            placed.append(
                jax.device_put(new, sharding) if sharding is not None else jax.device_put(new)
            )
        jax.block_until_ready(placed)
        in_flight = self._active_count()
        flushed = 0
        if self._table_state is not None and self.prefix_sharing:
            flushed = self._table_state.flush_prefix_index()
        self.params = jax.tree.unflatten(old_def, placed)
        self.weights_generation = gen
        latency = self._now() - start
        with self._stats_lock:
            self.weight_swaps += 1
        self._m_swaps.inc()
        self._m_generation.set(gen)
        record = {
            "generation": gen,
            "latency_s": latency,
            "in_flight": in_flight,
            "prefix_entries_flushed": flushed,
        }
        self.swap_history.append(record)
        get_active_telemetry().emit_event("serve/weight_swap", dict(record))
        return record

    # ---------------------------------------------------------------- jitted fns
    def _build_jits(self) -> None:
        import jax
        import jax.numpy as jnp

        model = self.model
        cache_shardings = self._cache_shardings
        engine = self

        def _constrain_cache(cache):
            if cache_shardings is None:
                return cache
            return jax.tree.map(
                lambda x, s: jax.lax.with_sharding_constraint(x, s), cache, cache_shardings
            )

        def samp(key, row, temp):
            greedy = temp <= 0.0
            ks = jax.random.split(key)
            # row[None, :]: categorical must see the interactive path's [1, V]
            # operand so the gumbel draw is bitwise identical per key
            tok_s = jax.random.categorical(ks[1], row[None, :] / jnp.maximum(temp, 1e-6))[0]
            tok_g = jnp.argmax(row)
            tok = jnp.where(greedy, tok_g, tok_s).astype(jnp.int32)
            # the key advances only when a sample was actually drawn — exactly
            # the interactive path's key-split discipline
            return tok, jnp.where(greedy, key, ks[0])

        def prefill_fn(params, cache, tokens, slot, start, key, temp, sample_flag):
            engine._prefill_traces += 1  # trace-time side effect: 1 per compiled shape
            logits, cache = model.prefill_slot(params, cache, tokens, slot, start)
            last = logits[:, -1, :]  # [1, V] — same shape the interactive path samples
            greedy = temp <= 0.0
            ks = jax.random.split(key)
            tok_s = jax.random.categorical(ks[1], last / jnp.maximum(temp, 1e-6))[0]
            tok_g = jnp.argmax(last, axis=-1)[0]
            tok = jnp.where(greedy, tok_g, tok_s).astype(jnp.int32)
            # the key advances only when a sample was actually drawn (last chunk,
            # non-greedy) — exactly the interactive path's key-split discipline
            new_key = jnp.where(sample_flag & ~greedy, ks[0], key)
            tok = jnp.where(sample_flag, tok, jnp.int32(-1))
            # canary gating (PR 12): a non-finite logits row marks the request
            # "error" on the host — NaN weights regress serve_request_errors_total
            ok = jnp.isfinite(last).all()
            return _constrain_cache(cache), tok, new_key, ok

        def decode_fn(params, cache, tokens, positions, keys, temps, eods, remaining):
            engine._decode_traces += 1  # must stay 1: ONE executable for the whole trace
            logits, cache = model.decode_slots(params, cache, tokens, positions)
            rows = logits[:, 0, :]  # [slots, V]
            toks, new_keys = jax.vmap(samp)(keys, rows, temps)
            # per-slot stopping folded into the step: eod never emits, budget
            # emits its last token then stops — the host only reads flags
            finished = (toks == eods) | (remaining <= 1)
            ok = jnp.isfinite(rows).all(axis=-1)
            return _constrain_cache(cache), toks, new_keys, finished, ok

        def paged_prefill_fn(
            params, cache, tokens, pos, tables, wblk, woff, last_idx, keys, temps, flags
        ):
            # ONE fixed [slots, block_size] shape -> one compiled prefill for the
            # whole trace (the cross-request packing replaces the ring's ladder)
            engine._prefill_traces += 1
            logits, cache = model.prefill_paged(params, cache, tokens, pos, tables, wblk, woff)
            # per row: the logits at that row's last valid token ([R, V])
            rows = jnp.take_along_axis(logits, last_idx[:, None, None], axis=1)[:, 0, :]
            toks, new_keys = jax.vmap(samp)(keys, rows, temps)
            toks = jnp.where(flags, toks, jnp.int32(-1))
            new_keys = jnp.where(flags[:, None], new_keys, keys)
            ok = jnp.isfinite(rows).all(axis=-1)
            return _constrain_cache(cache), toks, new_keys, ok

        def paged_decode_fn(
            params, cache, tokens, positions, tables, wblk, woff, keys, temps, eods, remaining
        ):
            engine._decode_traces += 1  # must stay 1: ONE executable for the whole trace
            logits, cache = model.decode_paged(
                params, cache, tokens, positions, tables, wblk, woff
            )
            rows = logits[:, 0, :]  # [slots, V]
            toks, new_keys = jax.vmap(samp)(keys, rows, temps)
            finished = (toks == eods) | (remaining <= 1)
            ok = jnp.isfinite(rows).all(axis=-1)
            return _constrain_cache(cache), toks, new_keys, finished, ok

        spec_k = self.spec.k

        def spec_verify_fn(params, cache, tokens, positions, tables, wblk, woff, keys, temps, prop_len):
            # the SECOND (and last) decode-side executable: ONE fixed
            # [slots, k+1] verify forward scores every slot's proposals; the
            # accept length folds in via cumprod so k acceptances never retrace
            engine._verify_traces += 1
            logits, cache = model.verify_paged(
                params, cache, tokens, positions, tables, wblk, woff
            )
            g = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # [S, k+1] greedy cont.
            # column 0 through samp(): sampled slots draw their token (and
            # advance their key) exactly like a plain decode step — greedy
            # slots get argmax back and keep their key, bitwise as always
            toks0, new_keys = jax.vmap(samp)(keys, logits[:, 0, :], temps)
            # draft j (fed at column j) is accepted iff it equals the greedy
            # continuation of column j-1 and every earlier draft was accepted
            match = (tokens[:, 1:] == g[:, :-1]) & (
                jnp.arange(spec_k)[None, :] < prop_len[:, None]
            )
            acc = jnp.cumprod(match.astype(jnp.int32), axis=1).sum(axis=1)  # [S]
            # column 0 only: trailing columns past the valid window are fully
            # masked and legitimately non-finite; NaN WEIGHTS poison column 0 too
            ok = jnp.isfinite(logits[:, 0, :]).all(axis=-1)
            return _constrain_cache(cache), g, toks0, new_keys, acc, ok

        def cow_fn(cache, src, dst):
            # copy-on-write: duplicate pool row `src` into the freshly
            # allocated `dst`. src/dst are traced int32 scalars, so every CoW
            # reuses ONE executable
            engine._cow_traces += 1

            def copy_leaf(leaf):
                axis = 1 if leaf.ndim == 5 else 0  # scanned [L, NB, ...] | unrolled
                row = jax.lax.dynamic_index_in_dim(leaf, src, axis=axis, keepdims=False)
                return jax.lax.dynamic_update_index_in_dim(leaf, row, dst, axis=axis)

            return _constrain_cache(jax.tree.map(copy_leaf, cache))

        def handoff_gather_fn(cache, src):
            # disagg export (prefill tier): read pool row `src` out of every
            # leaf — int8 data and f32 scales leave as-is, no dequant. src is
            # a traced int32 scalar so every exported block reuses ONE
            # executable; the cache is NOT donated (blocks stay live until
            # _finish releases the table)
            engine._handoff_traces += 1

            def gather_leaf(leaf):
                axis = 1 if leaf.ndim == 5 else 0  # same layout rule as cow_fn
                return jax.lax.dynamic_index_in_dim(leaf, src, axis=axis, keepdims=False)

            return jax.tree.map(gather_leaf, cache)

        def handoff_scatter_fn(cache, rows, dst):
            # disagg import (decode tier): write one foreign block row into
            # pool row `dst` of every leaf. dst is traced -> ONE executable;
            # the cache IS donated (in-place pool update, like cow_fn)
            engine._import_traces += 1

            def scatter_leaf(leaf, row):
                axis = 1 if leaf.ndim == 5 else 0
                return jax.lax.dynamic_update_index_in_dim(leaf, row, dst, axis=axis)

            return _constrain_cache(jax.tree.map(scatter_leaf, cache, rows))

        if self.kv_cache == "paged":
            self._prefill_jit = jax.jit(paged_prefill_fn, donate_argnums=(1,))
            self._decode_jit = jax.jit(paged_decode_fn, donate_argnums=(1,))
            self._verify_jit = jax.jit(spec_verify_fn, donate_argnums=(1,))
            self._cow_jit = jax.jit(cow_fn, donate_argnums=(0,))
            self._handoff_gather_jit = jax.jit(handoff_gather_fn)
            self._handoff_scatter_jit = jax.jit(handoff_scatter_fn, donate_argnums=(0,))
        else:
            self._prefill_jit = jax.jit(prefill_fn, donate_argnums=(1,))
            self._decode_jit = jax.jit(decode_fn, donate_argnums=(1,))

    # ---------------------------------------------------------------- submission
    def submit(
        self,
        prompt_tokens: list[int],
        max_new_tokens: int,
        temperature: Optional[float] = ...,
        seed: int = 0,
        arrival_offset_s: float = 0.0,
        trace_id: Optional[str] = None,
        trace_hop: int = 0,
        deadline_ms: Optional[float] = None,
        priority: int = 0,
        tenant: str = "",
    ) -> int:
        if self.role == "decode":
            raise ValueError(
                "role='decode' engines take work via import_handoff(), not "
                "submit(): the decode tier never prefills a raw prompt"
            )
        if not prompt_tokens:
            raise ValueError("empty prompt: the engine needs at least one prompt token")
        rid = self._next_rid
        self._next_rid += 1
        temp = self.default_temperature if temperature is ... else temperature
        self._queue.append(
            ServeRequest(
                rid=rid,
                prompt_tokens=[int(t) for t in prompt_tokens],
                max_new_tokens=int(max_new_tokens),
                temperature=temp,
                seed=int(seed),
                arrival_offset_s=float(arrival_offset_s),
                deadline_ms=float(deadline_ms) if deadline_ms else None,
                priority=int(priority),
                tenant=str(tenant or ""),
            )
        )
        arrival = max(float(arrival_offset_s), 0.0)
        # fleet tracing: honor a propagated id (router/X-Trace-Id), mint otherwise
        # — either way every record this request produces carries the same id
        self._traces[rid] = {"events": [], "preemptions": 0, "wait_from": arrival,
                             "queue_wait_s": 0.0,
                             "trace_id": trace_id or uuid.uuid4().hex[:16],
                             "trace_hop": int(trace_hop),
                             "tenant": str(tenant or "")}
        self._trace_event(rid, "enqueue", arrival)
        self._m_submitted.inc()
        self._m_prompt_tokens.inc(len(prompt_tokens))
        if tenant:
            self._m_tenant_requests.inc(tenant=tenant)
            self._tenant_stat(tenant, "submitted")
        # chaos: an armed queue_storm amplifies this submit with lowest-priority
        # synthetic clones (one-shot, so the recursion fires exactly once)
        for _ in range(fire_queue_storm_if_armed(rid)):
            self.submit(
                prompt_tokens, max_new_tokens, temperature=temp, seed=seed,
                arrival_offset_s=arrival_offset_s, deadline_ms=deadline_ms,
                priority=max(int(priority), 0) + 9, tenant=tenant,
            )
        # chaos: an armed tenant_flood amplifies this submit with clones charged
        # to a BULK tenant — the noisy neighbor the DRR scheduler must contain
        for _ in range(fire_tenant_flood_if_armed(rid)):
            self.submit(
                prompt_tokens, max_new_tokens, temperature=temp, seed=seed,
                arrival_offset_s=arrival_offset_s, deadline_ms=deadline_ms,
                priority=int(priority), tenant=self._flood_tenant(),
            )
        return rid

    def _flood_tenant(self) -> str:
        """The tenant a tenant_flood clone is charged to: the first declared
        bulk tenant, falling back to the name "bulk"."""
        if self._tenants is not None:
            for name in self._tenants.names():
                if self._tenants.spec(name).is_bulk:
                    return name
        return "bulk"

    # ----------------------------------------------------------- disagg imports
    def _check_import_generation(self, record, trace_id: str = "") -> None:
        """Cross-generation KV must never splice under different weights: the
        decode would be silently wrong in a way no digest can catch. Rejection
        is recorded as a `fleet/rollback stage=generation` resilience event —
        the same stream the quant-drift gate uses."""
        from modalities_tpu.serving.disagg.handoff import HandoffRejected

        if int(record.generation) != int(self.weights_generation):
            from modalities_tpu.resilience.events import record_event

            record_event(
                "fleet/rollback",
                stage="generation",
                offered=int(record.generation),
                installed=int(self.weights_generation),
                trace_id=trace_id or record.trace_id,
            )
            raise HandoffRejected(
                "generation_mismatch",
                f"handoff KV computed under weights generation {record.generation} "
                f"cannot splice under generation {self.weights_generation} — "
                "re-prefill on the current generation instead",
            )

    def import_handoff(
        self,
        record,
        *,
        arrival_offset_s: float = 0.0,
        trace_id: Optional[str] = None,
        trace_hop: int = 0,
    ) -> int:
        """Decode tier: validate a sealed HandoffRecord and queue it for slot
        seeding. Validation (digest, version, pool-config, weights generation)
        happens HERE so a bad record fails the caller synchronously — raises
        HandoffRejected and counts `disagg_handoff_failures_total{reason=}`.
        Admission (local block allocation + payload scatter + slot arm) runs
        inside step() under the same FIFO/arrival/pool invariants as a plain
        request: pool-full leaves the import queued, never corrupts."""
        from modalities_tpu.serving.disagg.handoff import HANDOFF_VERSION, HandoffRejected

        if self.role != "decode":
            raise ValueError(
                f"import_handoff() needs role='decode' (engine is {self.role!r})"
            )
        try:
            if int(record.version) != HANDOFF_VERSION:
                raise HandoffRejected(
                    "version_mismatch",
                    f"handoff version {record.version} != engine {HANDOFF_VERSION}",
                )
            if int(record.block_size) != self.block_size:
                raise HandoffRejected(
                    "config_mismatch",
                    f"handoff block_size {record.block_size} != pool {self.block_size}",
                )
            if str(record.quant_kv) != self.quant_kv:
                raise HandoffRejected(
                    "config_mismatch",
                    f"handoff quant_kv {record.quant_kv!r} != pool {self.quant_kv!r}",
                )
            if len(record.window) < 1 or len(record.window) > self.max_len - 1:
                raise HandoffRejected(
                    "config_mismatch",
                    f"handoff window {len(record.window)} tokens does not fit "
                    f"max_len {self.max_len}",
                )
            record.verify_digest()
            self._check_import_generation(record, trace_id or "")
        except HandoffRejected as exc:
            self._m_handoff_failures.inc(reason=exc.reason)
            raise
        rid = self._next_rid
        self._next_rid += 1
        deadline_ms = getattr(record, "deadline_ms", None)
        req = _ImportRequest(
            rid=rid,
            prompt_tokens=[int(t) for t in record.window],
            max_new_tokens=int(record.remaining),
            temperature=float(record.temperature),
            seed=int(record.seed),
            arrival_offset_s=float(arrival_offset_s),
            # the deadline rides the handoff record (outside the digest, like
            # the trace id) and restarts from the decode tier's local arrival
            deadline_ms=float(deadline_ms) if deadline_ms else None,
            # the tenant rides the record the same way (outside the digest)
            tenant=str(getattr(record, "tenant", "") or ""),
            record=record,
        )
        self._queue.append(req)
        arrival = max(float(arrival_offset_s), 0.0)
        self._traces[rid] = {
            "events": [], "preemptions": 0, "wait_from": arrival,
            "queue_wait_s": 0.0,
            "trace_id": trace_id or record.trace_id or uuid.uuid4().hex[:16],
            "trace_hop": int(trace_hop or record.trace_hop),
            "tenant": req.tenant,
        }
        self._trace_event(
            rid, "import_enqueue", arrival,
            blocks=record.num_blocks, kv_bytes=record.kv_bytes,
            source_rid=int(record.rid),
        )
        self._m_submitted.inc()
        return rid

    # ------------------------------------------------------------------ tracing
    def _trace_event(self, rid: int, name: str, t: float, **fields) -> None:
        trace = self._traces.get(rid)
        if trace is not None:
            trace["events"].append({"name": name, "t": round(float(t), 6), **fields})

    def _trace_admit(self, rid: int, now: float) -> None:
        """Admission: close the current queue-wait interval (enqueue or the last
        requeue opened it) and observe it."""
        self._trace_event(rid, "admit", now)
        trace = self._traces.get(rid)
        if trace is not None:
            wait = max(0.0, now - trace["wait_from"])
            trace["queue_wait_s"] += wait
            self._m_queue_wait.observe(wait)

    def _record_first_token(self, result: ServeResult, now: float) -> None:
        """First token of an admission. TTFT is observed once per request — a
        preempted request's replay re-fires the trace event (the timeline shows
        both) but not the histogram sample (the client saw the FIRST one)."""
        self._trace_event(result.rid, "first_token", now)
        trace = self._traces.get(result.rid)
        if trace is None or not trace.get("ttft_observed"):
            if trace is not None:
                trace["ttft_observed"] = True
            self._m_ttft.observe(
                max(0.0, now - result.arrival_s),
                exemplar=trace.get("trace_id") if trace is not None else None,
            )

    def _flush_trace(self, result: ServeResult) -> None:
        """Finish: fold the lifecycle stream into ONE JSONL record on the
        per-rank telemetry sink (analyze_serve's input)."""
        trace = self._traces.pop(result.rid, None)
        if trace is None:
            return
        result.queue_wait_s = trace["queue_wait_s"]
        times = result.token_times_s
        tpot_mean = (
            (times[-1] - times[0]) / (len(times) - 1) if len(times) >= 2 else None
        )
        get_active_telemetry().emit_serve_trace(
            {
                "rid": result.rid,
                "trace_id": result.trace_id,
                "hop": result.trace_hop,
                # disagg: tier tag so analyze_fleet can render "prefill leg" /
                # "decode leg" spans; combined engines stay unlabelled
                **({"role": self.role} if self.role != "combined" else {}),
                # tenant tag (PR 20): analyze_serve's per-tenant breakdown
                # keys on it; tenant-off records stay unlabelled
                **({"tenant": trace["tenant"]} if trace.get("tenant") else {}),
                "prompt_len": result.prompt_len,
                "tokens": len(result.tokens),
                "finish_reason": result.finish_reason,
                "truncated": result.truncated,
                "weights_generation": result.weights_generation,
                "prefix_hit_tokens": result.prefix_hit_tokens,
                "spec_proposed": trace.get("spec_proposed", 0),
                "spec_accepted": trace.get("spec_accepted", 0),
                "preemptions": trace["preemptions"],
                "arrival_s": round(result.arrival_s, 6),
                "queue_wait_s": round(trace["queue_wait_s"], 6),
                "ttft_s": round(result.ttft_s, 6),
                "e2e_s": round(result.finish_s - result.arrival_s, 6),
                "tpot_mean_s": round(tpot_mean, 6) if tpot_mean is not None else None,
                "events": trace["events"],
            }
        )

    def _stopping(self) -> bool:
        return self._stop_fn is not None and bool(self._stop_fn())

    # ---------------------------------------------------------------- scheduling
    def _emit_token(self, result: ServeResult, tok: int, now: float) -> None:
        """Append + stream a token. `_streamed` survives preemption (the result
        list is reset but regenerated tokens are identical by determinism), so
        `on_token` fires exactly once per final token position."""
        if result.token_times_s:
            self._m_tpot.observe(max(0.0, now - result.token_times_s[-1]))
        result.tokens.append(tok)
        result.token_times_s.append(now)
        n = len(result.tokens)
        if n > self._streamed.get(result.rid, 0):
            self._streamed[result.rid] = n
            self._m_tokens.inc()
            if self._on_token is not None:
                self._on_token(result.rid, tok)

    def _record_result(self, result: ServeResult, reason: str, now: float) -> None:
        result.finish_reason = reason
        result.finish_s = now
        result.weights_generation = self.weights_generation
        trace = self._traces.get(result.rid)
        if trace is not None:
            result.trace_id = trace.get("trace_id", "")
            result.trace_hop = int(trace.get("trace_hop", 0))
        if reason == "error":
            with self._stats_lock:
                self.request_errors += 1
            self._m_req_errors.inc()
        tenant = trace.get("tenant") if trace is not None else ""
        if tenant:
            self._tenant_stat(tenant, "finished")
            if result.tokens:
                self._m_tenant_tokens.inc(len(result.tokens), tenant=tenant)
                self._tenant_stat(tenant, "tokens", len(result.tokens))
        self._results[result.rid] = result
        self._streamed.pop(result.rid, None)
        self._trace_event(
            result.rid, "finish", now, reason=reason, tokens=len(result.tokens),
            truncated=result.truncated,
        )
        self._m_finished.inc(reason=reason)
        self._m_e2e.observe(
            max(0.0, now - result.arrival_s), exemplar=result.trace_id or None
        )
        self._flush_trace(result)
        if self._on_finish is not None:
            self._on_finish(result.rid, result)

    def _clear_slot(self, slot: int) -> None:
        self._slot_states[slot] = None
        self._remaining[slot] = _IDLE_REMAINING
        self._eods[slot] = -1
        self._temps[slot] = 1.0
        if self.kv_cache == "paged":
            self._tables[slot] = 0
            self._wblk[slot] = self.num_blocks
            self._positions[slot] = 0

    def _finish(self, slot: int, reason: str, now: float) -> None:
        state = self._slot_states[slot]
        if self._table_state is not None:
            self._table_state.release(state.request.rid)
        self._record_result(state.result, reason, now)
        self._clear_slot(slot)

    def _finish_immediate(self, result: ServeResult, reason: str, now: float) -> None:
        self._record_result(result, reason, now)

    # ------------------------------------------------- resilience (PR 19)
    def _deadline_expired(self, req: ServeRequest, now: float) -> bool:
        return deadline_expired(req.arrival_offset_s, req.deadline_ms, now)

    def overload_reason(self) -> Optional[str]:
        """Why new work should be refused right now (None = admit): the HTTP
        layer turns this into a 429 + Retry-After."""
        if self.max_queue_depth is not None and len(self._queue) >= self.max_queue_depth:
            return "queue_full"
        if self.brownout is not None and self.brownout.active:
            return "brownout_reject"
        return None

    def note_rejected(self, reason: str, tenant: str = "") -> None:
        """Count one refused arrival (the HTTP layer's 429) on the engine's
        shed counter, so shedding has ONE metric family whatever the seam."""
        with self._stats_lock:
            self.shed_requests += 1
        self._m_shed.inc(reason=reason)
        if tenant:
            self._m_tenant_shed.inc(tenant=tenant)
            self._tenant_stat(tenant, "shed")
            if reason == "rate_limited":
                self._m_tenant_rate_limited.inc(tenant=tenant)
                self._tenant_stat(tenant, "rate_limited")

    # ------------------------------------------------- multi-tenancy (PR 20)
    def _tenant_stat(self, tenant: str, key: str, amount: int = 1) -> None:
        with self._stats_lock:
            bucket = self._tenant_stats.setdefault(
                tenant,
                {"submitted": 0, "finished": 0, "tokens": 0, "shed": 0,
                 "preemptions": 0, "rate_limited": 0},
            )
            bucket[key] += amount

    def _tenant_active_slots(self, tenant: str) -> int:
        return sum(
            1 for s in self._slot_states
            if s is not None and s.request.tenant == tenant
        )

    def _tenant_slot_counts(self) -> dict[str, int]:
        counts: dict[str, int] = {}
        for s in self._slot_states:
            if s is not None:
                counts[s.request.tenant] = counts.get(s.request.tenant, 0) + 1
        return counts

    def _tenant_budget_remaining(self, tenant: str) -> float:
        """This tenant's SLO error budget still unburned (1 = untouched) — a
        tenant with MORE budget left is the preferred victim ("least burned"):
        destroying its work costs the least reliability promise."""
        if self._tenant_budget_fn is None:
            return 1.0
        try:
            return float(self._tenant_budget_fn(tenant))
        except Exception:
            return 1.0

    def _demand_weight(self, slot_counts: dict[str, int]) -> float:
        names = set(slot_counts) | {r.tenant for r in self._queue}
        return sum(self._tenants.spec(n).weight for n in names if n)

    def _victim_key(
        self, tenant: str, slot_counts: dict[str, int], total_weight: float
    ) -> tuple:
        """Burn-aware victim ordering (max = preferred victim): over-quota or
        over-fair-share tenants first, then bulk before interactive — an
        under-budget interactive tenant is NEVER picked while any bulk
        candidate exists — then the least-burned error budget."""
        spec = self._tenants.spec(tenant)
        count = slot_counts.get(tenant, 0)
        fair = (
            self.slots * spec.weight / total_weight if total_weight > 0 else self.slots
        )
        over_quota = spec.max_slots is not None and count > spec.max_slots
        over = over_quota or count > fair
        return (
            1 if over else 0,
            1 if spec.is_bulk else 0,
            self._tenant_budget_remaining(tenant),
        )

    def resolve_submit_tenant(self, value) -> str:
        """Ingress tenant resolution, shared by both front ends (mirrors how
        `resolve_deadline_ms` rides the deadline seam): with tenants
        configured a missing/blank id maps to the env-default tenant; with
        tenants off everything collapses to the implicit "" tenant so the
        engine stays bitwise on its pre-tenant behavior."""
        if self._tenants is None:
            return ""
        return resolve_tenant(value)

    def tenant_reject_reason(self, tenant: str, max_new_tokens: int):
        """Per-tenant admission gate for the HTTP layer, BEFORE submit():
        ``None`` to admit (the token bucket was charged ``max_new_tokens``),
        else ``("rate_limited", retry_after_s)`` with the refill-derived
        wait."""
        if self._tenants is None or not tenant:
            return None
        retry_after = self._tenants.rate_limit_retry_after_s(
            tenant, float(max_new_tokens), self._now()
        )
        if retry_after is None:
            return None
        return ("rate_limited", retry_after)

    def retry_after_s(self, reason: str) -> float:
        """Derived Retry-After for an overload rejection: the time for the
        queue to drain to where the reason clears, estimated as the excess
        requests over the parallel drain width (one slot retires roughly one
        request per recovery interval). Floor 1s — never tell a client 0."""
        depth = len(self._queue)
        if reason == "queue_full" and self.max_queue_depth is not None:
            excess = depth - self.max_queue_depth + 1
        elif reason == "brownout_reject" and self.brownout is not None:
            # brownout hysteresis: recovery needs the queue at/below queue_low
            excess = depth - int(self.brownout.queue_low)
        else:
            return 1.0
        return float(max(1, -(-max(excess, 0) // max(self.slots, 1))))

    def _next_admittable(self, now: float) -> Optional[ServeRequest]:
        """Pop the next request to admit (None = nothing admissible).
        Tenant-off: the FIFO head, arrival-gated — later requests never jump
        an unarrived head (the pinned HEAD order). Tenant-on: weighted
        deficit-round-robin across tenants (see `_drr_pick`)."""
        if self._tenants is None:
            if not self._queue:
                return None
            req = self._queue[0]
            if req.arrival_offset_s > now:
                return None
            self._queue.popleft()
            return req
        req = self._drr_pick(self._drr_candidates(now, set()))
        if req is not None:
            self._queue.remove(req)
        return req

    def _drr_candidates(
        self, now: float, blocked: set
    ) -> dict[str, ServeRequest]:
        """Per-tenant admission heads: for each tenant (not `blocked`, not at
        its slot quota) the FIRST queued arrived request of the best (lowest
        number) priority class present — DRR schedules within one priority
        class at a time, FIFO within (tenant, class)."""
        counts = self._tenant_slot_counts()
        eligible = []
        for r in self._queue:
            if r.arrival_offset_s > now or r.tenant in blocked:
                continue
            spec = self._tenants.spec(r.tenant)
            if spec.max_slots is not None and counts.get(r.tenant, 0) >= spec.max_slots:
                continue
            eligible.append(r)
        if not eligible:
            return {}
        best = min(r.priority for r in eligible)
        heads: dict[str, ServeRequest] = {}
        for r in eligible:
            if r.priority == best and r.tenant not in heads:
                heads[r.tenant] = r
        return heads

    def _drr_pick(self, heads: dict[str, ServeRequest]) -> Optional[ServeRequest]:
        """One weighted deficit-round-robin selection over the per-tenant
        heads: unit cost per request, quantum = weight, so under saturation
        admissions converge to the weight ratio. The deficit of a tenant with
        no eligible work resets (an idle tenant banks no credit); the cursor
        keeps rotation position across rounds."""
        if not heads:
            return None
        for name in list(self._drr_deficit):
            if name not in heads:
                del self._drr_deficit[name]
        names = sorted(heads)
        idx = 0
        for i, n in enumerate(names):
            if n >= self._drr_cursor:
                idx = i
                break
        name = names[idx]
        deficit = self._drr_deficit.get(name, 0.0)
        if deficit < 1.0:
            deficit += self._tenants.spec(name).weight
        deficit -= 1.0
        self._drr_deficit[name] = deficit
        # stay on this tenant while it has credit, else advance the rotation
        self._drr_cursor = name if deficit >= 1.0 else names[(idx + 1) % len(names)]
        return heads[name]

    def _finish_queued(self, req: ServeRequest, reason: str, now: float) -> None:
        """Drop one QUEUED request (deadline/shed): it owns no slot and no
        blocks, so the cancellation is a pure dequeue + result record."""
        result = ServeResult(
            rid=req.rid, prompt_len=len(req.prompt_tokens),
            arrival_s=max(req.arrival_offset_s, 0.0),
        )
        result.first_token_s = now
        if reason == "deadline":
            with self._stats_lock:
                self.deadline_expired_requests += 1
            self._m_deadline_expired.inc()
        else:
            with self._stats_lock:
                self.shed_requests += 1
            self._m_shed.inc(reason="brownout")
            if req.tenant:
                self._m_tenant_shed.inc(tenant=req.tenant)
                self._tenant_stat(req.tenant, "shed")
        self._trace_event(req.rid, reason, now, queued=True)
        self._finish_immediate(result, reason, now)

    def _sweep_queue(self, t0: float) -> None:
        """Seam 1 (queue admission): expire dead-on-arrival work, then let the
        brownout controller shed the lowest-priority queued requests. Runs
        before every admission round; a queue with no deadlines and no
        brownout controller passes through untouched."""
        now = self._now() - t0
        if any(req.deadline_ms is not None for req in self._queue):
            kept: deque[ServeRequest] = deque()
            for req in self._queue:
                if self._deadline_expired(req, now):
                    self._finish_queued(req, "deadline", now)
                else:
                    kept.append(req)
            self._queue = kept
        if self.brownout is None:
            return
        self.brownout.update(len(self._queue))
        for _ in range(self.brownout.shed_target(len(self._queue))):
            if self._tenants is None:
                # shed the YOUNGEST request of the LOWEST-priority class: older
                # work and higher classes keep their FIFO positions
                victim = None
                for req in self._queue:
                    if victim is None or req.priority >= victim.priority:
                        victim = req
            else:
                # burn-aware (PR 20): over-quota tenants first, bulk before
                # interactive, least-burned budget next; priority and
                # youngest-within-class break ties (the `>=` keeps the HEAD
                # youngest-wins rule inside an equal key)
                slot_counts = self._tenant_slot_counts()
                total_w = self._demand_weight(slot_counts)
                victim = None
                victim_key = None
                for req in self._queue:
                    key = self._victim_key(req.tenant, slot_counts, total_w) + (
                        req.priority,
                    )
                    if victim is None or key >= victim_key:
                        victim, victim_key = req, key
            if victim is None:
                break
            self._queue.remove(victim)
            self._finish_queued(victim, "shed", now)

    def _expire_active(self, t0: float) -> None:
        """Seams 2+3 (chunk/step boundary): cancel expired slots between
        dispatches. `_finish` releases the block-table entry, so the pool
        audit (`free + Σ unique owned == num_blocks`) stays exact, and the
        cancelled request never occupies another device step."""
        now = self._now() - t0
        for slot in range(self.slots):
            state = self._slot_states[slot]
            if state is None or state.request.deadline_ms is None:
                continue
            if self._deadline_expired(state.request, now):
                with self._stats_lock:
                    self.deadline_expired_requests += 1
                self._m_deadline_expired.inc()
                self._trace_event(
                    state.request.rid, "deadline", now, phase=state.phase
                )
                if not state.result.tokens:
                    # never streamed: ttft_s reads as time-to-cancellation
                    # (matching _finish_queued), not a garbage negative
                    state.result.first_token_s = now
                self._finish(slot, "deadline", now)

    def _truncate_window(self, req: ServeRequest, result: ServeResult) -> list[int]:
        """Clip the prompt to the admission window (capacity-1 / max_len-1 so at
        least one token can be generated). Truncation is RECORDED, not silent:
        result flag + telemetry event + engine counter."""
        window = req.prompt_tokens[-(self.max_len - 1) :]
        if len(window) < len(req.prompt_tokens):
            result.truncated = True
            if req.rid not in self._truncated_rids:  # once, even across preemption
                self._truncated_rids.add(req.rid)
                with self._stats_lock:
                    self.truncated_requests += 1
                self._m_trunc.inc()
                get_active_telemetry().emit_event(
                    "serve/prompt_truncated",
                    {"rid": req.rid, "prompt_len": len(req.prompt_tokens), "window": len(window)},
                )
        return window

    def _admit(self, t0: float) -> None:
        """Fill idle slots from the queue (FIFO, arrival-gated). Ring: chunked
        prefill into the freed slot right here, first token sampled on-device by
        the last chunk. Paged: gate on free blocks for the prompt window, then
        hand the slot to the cross-request prefill dispatcher. A draining engine
        (`stop_fn`) admits nothing."""
        if self._stopping():
            return
        self._sweep_queue(t0)
        if self.role == "decode":
            self._admit_imports(t0)
            return
        if self.kv_cache == "paged":
            self._admit_paged(t0)
            return
        import jax

        jnp = self._jnp
        for slot in range(self.slots):
            if not self._queue:
                break
            if self._slot_states[slot] is not None:
                continue
            now = self._now() - t0
            req = self._next_admittable(now)
            if req is None:
                break  # FIFO: later requests can't jump an unarrived head
            with span("serve/admission"):
                temp = req.temperature if req.temperature is not None else 0.0
                result = ServeResult(
                    rid=req.rid, prompt_len=len(req.prompt_tokens),
                    arrival_s=max(req.arrival_offset_s, 0.0),
                )
                self._trace_admit(req.rid, now)
                window = self._truncate_window(req, result)
                if req.max_new_tokens <= 0:
                    now2 = self._now() - t0
                    result.first_token_s = now2
                    self._finish_immediate(result, "budget", now2)
                    continue
                key = jax.random.PRNGKey(req.seed)
                pos = 0
                expired_mid_prefill = False
                with span("serve/prefill"):
                    while pos < len(window):
                        chunk = next(c for c in self.prefill_chunks if c <= len(window) - pos)
                        toks = np.asarray([window[pos : pos + chunk]], dtype=np.int32)
                        is_last = pos + chunk >= len(window)
                        with self._rules_ctx():
                            self.cache, tok, key, ok = self._prefill_jit(
                                self.params, self.cache, jnp.asarray(toks),
                                np.int32(slot), np.int32(pos), key,
                                np.float32(temp), np.bool_(is_last),
                            )
                        self._m_prefill_chunks.inc()
                        self._trace_event(
                            req.rid, "prefill_chunk", self._now() - t0, start=pos, ntok=chunk
                        )
                        pos += chunk
                        # seam 2 (chunk boundary): an expired request stops
                        # burning prefill chunks; the ring slot holds no pooled
                        # resources, so reuse just overwrites it
                        if pos < len(window) and self._deadline_expired(
                            req, self._now() - t0
                        ):
                            expired_mid_prefill = True
                            break
                if expired_mid_prefill:
                    now2 = self._now() - t0
                    result.first_token_s = now2
                    with self._stats_lock:
                        self.deadline_expired_requests += 1
                    self._m_deadline_expired.inc()
                    self._trace_event(req.rid, "deadline", now2, phase="prefill")
                    self._finish_immediate(result, "deadline", now2)
                    continue
                first_tok = int(tok)  # device sync: the request's TTFT point
                now2 = self._now() - t0
                result.first_token_s = now2
                if not bool(ok):  # non-finite logits: no token to trust
                    self._finish_immediate(result, "error", now2)
                    continue
                self._record_first_token(result, now2)
                if first_tok == self.eod_token_id:
                    self._finish_immediate(result, "eod", now2)
                    continue
                self._emit_token(result, first_tok, now2)
                if req.max_new_tokens == 1:
                    self._finish_immediate(result, "budget", now2)
                    continue
                # arm the slot: the admitted request joins the next decode dispatch
                self._slot_states[slot] = _SlotState(
                    request=req, result=result, remaining=req.max_new_tokens - 1,
                    seq=self._admit_seq,
                )
                self._admit_seq += 1
                self._tokens[slot, 0] = first_tok
                self._positions[slot] = len(window)
                self._keys[slot] = np.asarray(key)
                self._temps[slot] = temp
                self._eods[slot] = self.eod_token_id
                self._remaining[slot] = req.max_new_tokens - 1

    def _paged_admission_need(self, req: ServeRequest) -> tuple:
        """(window, matched, full_match, need) for one admission candidate.

        full-window match: every prompt position is already resident, but the
        LAST token must be re-forwarded to produce the first-token logits —
        its K/V write lands in the final shared block, so admission
        copy-on-writes that block (one fresh block + a jitted device row
        copy). `need` is the admission gate's free-block demand: unmatched
        tail blocks + the CoW copy."""
        window = req.prompt_tokens[-(self.max_len - 1) :]
        ts = self._table_state
        matched = ts.match_prefix(window) if self.prefix_sharing else []
        full_match = matched and len(matched) * self.block_size >= len(window)
        need = (
            blocks_for_tokens(len(window), self.block_size)
            - len(matched)
            + (1 if full_match else 0)
        )
        return window, matched, full_match, need

    def _admit_paged(self, t0: float) -> None:
        import jax

        ts = self._table_state
        for slot in range(self.slots):
            if not self._queue:
                break
            if self._slot_states[slot] is not None:
                continue
            now = self._now() - t0
            if self._tenants is None:
                req = self._queue[0]
                if req.arrival_offset_s > now:
                    break  # FIFO: later requests can't jump an unarrived head
                window, matched, full_match, need = self._paged_admission_need(req)
                # admission gate (BEFORE popleft): the demand must fit in free
                # blocks, or the head stays queued
                if ts.pool.free_count < need:
                    break  # head stays queued; decoders will free blocks
                self._queue.popleft()
            else:
                # per-tenant head-of-line (PR 20): a tenant whose head does
                # not fit the pool is blocked for THIS round only — its big
                # prompt never stalls the other tenants' admissions
                req = None
                blocked: set = set()
                while True:
                    heads = self._drr_candidates(now, blocked)
                    unfit = {
                        name
                        for name, cand in heads.items()
                        if ts.pool.free_count < self._paged_admission_need(cand)[3]
                    }
                    if unfit:
                        blocked |= unfit
                        continue
                    req = self._drr_pick(heads)
                    break
                if req is None:
                    break  # nothing arrived, under quota, AND pool-admissible
                window, matched, full_match, need = self._paged_admission_need(req)
                self._queue.remove(req)
            with span("serve/admission"):
                temp = req.temperature if req.temperature is not None else 0.0
                result = ServeResult(
                    rid=req.rid, prompt_len=len(req.prompt_tokens),
                    arrival_s=max(req.arrival_offset_s, 0.0),
                )
                self._trace_admit(req.rid, now)
                window = self._truncate_window(req, result)
                if req.max_new_tokens <= 0:
                    now2 = self._now() - t0
                    result.first_token_s = now2
                    self._finish_immediate(result, "budget", now2)
                    continue
                if matched:
                    ts.fork_prefix(req.rid, matched)
                if not ts.ensure(req.rid, len(window)):
                    raise AssertionError("paged admission gate let a dry pool through")
                tail_start = len(matched) * self.block_size
                if full_match:
                    tail_start = len(window) - 1
                    cow = ts.ensure_writable(req.rid, tail_start)
                    # matched blocks were just forked, so the write target is
                    # shared by construction and CoW always triggers
                    assert isinstance(cow, tuple), "full-match block unexpectedly private"
                    self._cow_copy(*cow)
                if matched:
                    result.prefix_hit_tokens = tail_start
                    with self._stats_lock:
                        self.prefix_hit_requests += 1
                        self.prefix_hit_blocks += len(matched)
                        self.prefix_hit_tokens += tail_start
                    self._m_prefix_hit_requests.inc()
                    self._m_prefix_hit_blocks.inc(len(matched))
                    self._trace_event(
                        req.rid, "prefix_hit", now,
                        blocks=len(matched), tokens=tail_start,
                    )
                self._slot_states[slot] = _SlotState(
                    request=req, result=result, remaining=0,
                    phase="prefill", window=window, prefill_pos=tail_start,
                    key=jax.random.PRNGKey(req.seed), temp=temp, seq=self._admit_seq,
                )
                self._admit_seq += 1

    def _admit_imports(self, t0: float) -> None:
        """Decode tier: seed idle slots from queued KV imports (FIFO,
        arrival-gated, pool gate BEFORE popleft — exactly the plain-admission
        invariants). Seeding allocates local blocks, scatters the foreign
        payload in (int8 data + f32 scales verbatim — no dequant/requant),
        registers the prompt in the prefix index, and arms the slot straight
        into the shared decode dispatch. A full pool leaves the head queued
        and counts ONE `disagg_handoff_failures_total{reason=pool_full}` per
        import; preemption later requeues the _ImportRequest whole, so replay
        re-imports deterministically from the retained record."""
        import jax

        from modalities_tpu.serving.disagg.handoff import HandoffRejected

        jnp = self._jnp
        ts = self._table_state
        for slot in range(self.slots):
            if not self._queue:
                break
            if self._slot_states[slot] is not None:
                continue
            now = self._now() - t0
            req = self._queue[0]
            if req.arrival_offset_s > now:
                break  # FIFO: later imports can't jump an unarrived head
            record = req.record
            with span("serve/import"):
                window = [int(t) for t in record.window]
                wl = len(window)
                matched = ts.match_prefix(window) if self.prefix_sharing else []
                nblk = blocks_for_tokens(wl, self.block_size)
                # admission gate (BEFORE popleft): unmatched payload blocks
                # must fit, or the head stays queued until decoders free blocks
                # (the first decode write past wl is _ensure_decode_blocks'
                # job, same as a locally-prefilled slot)
                need = nblk - len(matched)
                if ts.pool.free_count < need:
                    if not req.pool_full_seen:  # once per import, not per round
                        req.pool_full_seen = True
                        with self._stats_lock:
                            self.import_requeues += 1
                        self._m_handoff_failures.inc(reason="pool_full")
                        self._trace_event(
                            req.rid, "import_requeue", now,
                            free=ts.pool.free_count, need=need,
                        )
                    break
                result = ServeResult(
                    rid=req.rid, prompt_len=int(record.prompt_len) or wl,
                    arrival_s=max(req.arrival_offset_s, 0.0),
                    truncated=bool(record.truncated),
                )
                # generation re-check at admission: a hot swap may have landed
                # between import_handoff() and this slot coming free — stale KV
                # finishes "error" here rather than decoding garbage
                try:
                    self._check_import_generation(record)
                except HandoffRejected as exc:
                    self._queue.popleft()
                    self._m_handoff_failures.inc(reason=exc.reason)
                    self._trace_event(req.rid, "import_rejected", now, reason=exc.reason)
                    now2 = self._now() - t0
                    result.first_token_s = now2
                    self._finish_immediate(result, "error", now2)
                    continue
                self._queue.popleft()
                self._trace_admit(req.rid, now)
                if matched:
                    ts.fork_prefix(req.rid, matched)
                if not ts.ensure(req.rid, wl):
                    raise AssertionError("import admission gate let a dry pool through")
                # scatter ONLY the unmatched tail: matched blocks already hold
                # byte-identical KV (same tokens, same weights generation — the
                # prefix-index contract), so a prefix hit saves wire bytes AND
                # pool writes
                table_blocks = ts.blocks(req.rid)
                scattered = 0
                with self._rules_ctx():
                    for i in range(len(matched), nblk):
                        rows = jax.tree.unflatten(
                            self._cache_treedef,
                            [jnp.asarray(arr[i]) for arr in record.payload],
                        )
                        self.cache = self._handoff_scatter_jit(
                            self.cache, rows, np.int32(table_blocks[i])
                        )
                        scattered += 1
                if self.prefix_sharing:
                    ts.register_prefix(req.rid, window, upto=wl)
                if matched:
                    hit_tokens = min(len(matched) * self.block_size, wl)
                    result.prefix_hit_tokens = hit_tokens
                    with self._stats_lock:
                        self.prefix_hit_requests += 1
                        self.prefix_hit_blocks += len(matched)
                        self.prefix_hit_tokens += hit_tokens
                    self._m_prefix_hit_requests.inc()
                    self._m_prefix_hit_blocks.inc(len(matched))
                    self._trace_event(
                        req.rid, "prefix_hit", now,
                        blocks=len(matched), tokens=hit_tokens,
                    )
                # arm the slot exactly where the combined engine stands after
                # its prefill completion branch: last_token pending at position
                # wl, sampler key already past the first-token draw. window
                # grows the shipped token so spec-decode's ngram proposals see
                # the same context string as the combined path.
                self._slot_states[slot] = _SlotState(
                    request=req, result=result, remaining=int(record.remaining),
                    phase="decode", window=window + [int(record.last_token)],
                    temp=float(record.temperature), seq=self._admit_seq,
                    imported=True,
                )
                self._admit_seq += 1
                self._tokens[slot, 0] = int(record.last_token)
                self._positions[slot] = wl
                self._keys[slot] = np.asarray(record.key, dtype=np.uint32)
                self._temps[slot] = float(record.temperature)
                self._eods[slot] = self.eod_token_id
                self._remaining[slot] = int(record.remaining)
                with self._stats_lock:
                    self.handoffs_imported += 1
                    self.imported_blocks += scattered
                self._m_handoff_seconds.observe(
                    max(0.0, now - max(req.arrival_offset_s, 0.0)),
                    exemplar=self._traces.get(req.rid, {}).get("trace_id"),
                )
                self._trace_event(
                    req.rid, "import_seeded", now,
                    blocks=nblk, scattered=scattered, kv_bytes=record.kv_bytes,
                )

    def _cow_copy(self, src: int, dst: int) -> None:
        """Device row copy backing a copy-on-write: pool block `src` -> `dst`
        (one executable — src/dst are traced scalars)."""
        with span("serve/cow"):
            with self._rules_ctx():
                self.cache = self._cow_jit(self.cache, np.int32(src), np.int32(dst))
        with self._stats_lock:
            self.cow_copies += 1
        self._m_cow.inc()

    def _active_count(self) -> int:
        return sum(1 for s in self._slot_states if s is not None)

    def _decoding_count(self) -> int:
        return sum(1 for s in self._slot_states if s is not None and s.phase == "decode")

    def _prefilling_slots(self) -> list[int]:
        order = [
            (s.seq, i)
            for i, s in enumerate(self._slot_states)
            if s is not None and s.phase == "prefill"
        ]
        return [i for _, i in sorted(order)]

    def _preempt(self, slot: int, t0: float) -> None:
        """Pool exhausted: push this slot's request back to the FRONT of the
        queue (it is older than everything queued) and free its blocks. The
        request restarts deterministically on re-admission — `_streamed` keeps
        on_token exactly-once."""
        state = self._slot_states[slot]
        rid = state.request.rid
        freed = self._table_state.release(rid)
        with self._stats_lock:
            self.preemptions += 1
        self._m_preempt.inc()
        if state.request.tenant:
            self._m_tenant_preempt.inc(tenant=state.request.tenant)
            self._tenant_stat(state.request.tenant, "preemptions")
        now = self._now() - t0
        self._trace_event(
            rid, "preempt", now,
            blocks_freed=freed, tokens_discarded=len(state.result.tokens),
        )
        self._trace_event(rid, "requeue", now)
        trace = self._traces.get(rid)
        if trace is not None:
            trace["preemptions"] += 1
            trace["wait_from"] = now  # re-admission closes a NEW queue-wait interval
        get_active_telemetry().emit_event(
            "serve/preempt",
            {"rid": rid, "blocks_freed": freed, "tokens_discarded": len(state.result.tokens)},
        )
        # reset the result: generation restarts from the prompt on re-admission
        state.result.tokens = []
        state.result.token_times_s = []
        self._queue.appendleft(state.request)
        self._clear_slot(slot)

    def _ensure_decode_blocks(self, t0: float, widths: Optional[dict] = None) -> None:
        """Before a paged decode/verify dispatch: every decoding slot needs the
        blocks covering its write range [p, p+w-1] (`widths` maps slot -> w;
        default 1; w > 1 under spec decode), each exclusively owned — a shared
        block is copy-on-written first. Allocation failure preempts the
        YOUNGEST active slot (never an older one — FIFO fairness, no livelock:
        the pool admits at least one max-length request by construction);
        tenant mode replaces that order with the burn-aware `_victim_key`."""
        ts = self._table_state
        for slot in range(self.slots):
            state = self._slot_states[slot]
            if state is None or state.phase != "decode":
                continue
            rid = state.request.rid
            p = int(self._positions[slot])
            w = int(widths.get(slot, 1)) if widths else 1
            while True:
                if ts.ensure(rid, p + w):
                    # defensive CoW sweep: engine flows keep generated-region
                    # blocks private (prompt sharing CoWs at admission), but a
                    # shared write target here must still copy, never corrupt
                    dry = False
                    for bi in range(p // self.block_size, (p + w - 1) // self.block_size + 1):
                        res = ts.ensure_writable(rid, bi * self.block_size)
                        if res is False:
                            dry = True  # pool ran dry mid-CoW: preempt + retry
                            break
                        if isinstance(res, tuple):
                            self._cow_copy(*res)
                    if not dry:
                        break
                if self._tenants is None:
                    victims = [
                        (s.seq, i) for i, s in enumerate(self._slot_states) if s is not None
                    ]
                else:
                    # burn-aware (PR 20): over-quota tenants first, bulk
                    # before interactive, least-burned budget next — an
                    # under-quota interactive slot survives while any bulk
                    # slot exists; seq keeps youngest-first inside a tenant
                    slot_counts = self._tenant_slot_counts()
                    total_w = self._demand_weight(slot_counts)
                    victims = [
                        (
                            self._victim_key(s.request.tenant, slot_counts, total_w)
                            + (s.seq,),
                            i,
                        )
                        for i, s in enumerate(self._slot_states)
                        if s is not None
                    ]
                _, victim = max(victims)
                self._preempt(victim, t0)
                if victim == slot:
                    break
            if self._slot_states[slot] is None:
                continue  # preempted itself
            blk, off = ts.write_coords(rid, p)
            self._wblk[slot] = blk
            self._woff[slot] = off
            self._tables[slot] = ts.table(rid)

    def _prefill_dispatch(self, t0: float) -> None:
        """Paged cross-request chunked prefill: ONE [slots, block_size] dispatch
        packs up to `slots` block-aligned prompt chunks, taken FIFO across the
        prefilling slots (a long prompt takes several consecutive rows — rows of
        one dispatch see each other's K/V writes, so this is exact). Rows whose
        chunk ends its prompt sample the request's first token on-device."""
        import jax

        self._expire_active(t0)  # seam 2: no chunk for an expired request
        jnp = self._jnp
        R, C = self.slots, self.block_size
        nb = self.num_blocks
        rows: list[tuple[int, int, int, bool]] = []  # (slot, start, ntok, is_last)
        for slot in self._prefilling_slots():
            state = self._slot_states[slot]
            wl = len(state.window)
            pos = state.prefill_pos
            while pos < wl and len(rows) < R:
                ntok = min(C, wl - pos)
                rows.append((slot, pos, ntok, pos + ntok >= wl))
                pos += ntok
            if len(rows) >= R:
                break
        if not rows:
            return

        toks = np.zeros((R, C), np.int32)
        pos_a = np.zeros((R, C), np.int32)
        tables = np.zeros((R, self.table_width), np.int32)
        wblk = np.full((R, C), nb, np.int32)  # default: write nowhere
        woff = np.zeros((R, C), np.int32)
        last_idx = np.zeros((R,), np.int32)
        keys = np.zeros((R, 2), np.uint32)
        temps = np.zeros((R,), np.float32)
        flags = np.zeros((R,), bool)
        for r, (slot, start, ntok, is_last) in enumerate(rows):
            state = self._slot_states[slot]
            rid = state.request.rid
            table = self._table_state.table(rid)
            tables[r] = table
            toks[r, :ntok] = state.window[start : start + ntok]
            pos_a[r, :ntok] = np.arange(start, start + ntok)
            for c in range(ntok):
                wblk[r, c] = table[(start + c) // C]
                woff[r, c] = (start + c) % C
            last_idx[r] = ntok - 1
            flags[r] = is_last
            if is_last:
                keys[r] = np.asarray(state.key)
                temps[r] = state.temp

        with span("serve/prefill"):
            with self._rules_ctx():
                self.cache, toks_d, keys_d, ok_d = self._prefill_jit(
                    self.params, self.cache,
                    jnp.asarray(toks), jnp.asarray(pos_a), jnp.asarray(tables),
                    jnp.asarray(wblk), jnp.asarray(woff), jnp.asarray(last_idx),
                    jnp.asarray(keys), jnp.asarray(temps), jnp.asarray(flags),
                )
            out_toks, out_keys, out_ok = jax.device_get((toks_d, keys_d, ok_d))

        now = self._now() - t0
        self._m_prefill_chunks.inc(len(rows))
        with self._stats_lock:
            self.prefill_chunk_count += len(rows)  # modeled-cost clocks read this
        for r, (slot, start, ntok, is_last) in enumerate(rows):
            state = self._slot_states[slot]
            state.prefill_pos = start + ntok
            self._trace_event(
                state.request.rid, "prefill_chunk", now, start=start, ntok=ntok
            )
            if not is_last:
                continue
            req, result = state.request, state.result
            wl = len(state.window)
            if not bool(out_ok[r]):
                # non-finite first-token row: finish "error" and NEVER publish
                # this request's blocks into the prefix index
                result.first_token_s = now
                self._finish(slot, "error", now)
                continue
            if self.prefix_sharing:
                # prompt fully resident: publish the full PROMPT blocks into
                # the prefix index (first writer wins — forked/CoW duplicates
                # stay out). Generated positions live past `wl` and are never
                # registered, so indexed blocks are write-immutable for their
                # owner and CoW-guarded for everyone else.
                self._table_state.register_prefix(req.rid, state.window, upto=wl)
            first_tok = int(out_toks[r])
            result.first_token_s = now
            self._record_first_token(result, now)
            if first_tok == self.eod_token_id:
                self._finish(slot, "eod", now)
                continue
            self._emit_token(result, first_tok, now)
            # budget clamped to the table ceiling: the last emitted token never
            # needs a cache write, so max_len - wl + 1 tokens fit -> the stop is
            # always "budget"/"eod", never "capacity"
            allowed = min(req.max_new_tokens, self.max_len - wl + 1)
            if allowed <= 1:
                self._finish(slot, "budget", now)
                continue
            if self.role == "prefill":
                # disagg: the prefill tier stops at the first token — export
                # the live pool blocks + sampler state as a sealed handoff
                # record (gather runs BEFORE _finish releases the table) and
                # finish "handoff"; the decode tier continues from out_keys[r]
                result.handoff = self._export_handoff(
                    state, first_tok, out_keys[r], allowed - 1, now
                )
                self._finish(slot, "handoff", now)
                continue
            state.phase = "decode"
            state.remaining = allowed - 1
            self._tokens[slot, 0] = first_tok
            self._positions[slot] = wl
            self._keys[slot] = out_keys[r]
            self._temps[slot] = state.temp
            self._eods[slot] = self.eod_token_id
            self._remaining[slot] = allowed - 1

    def _export_handoff(self, state, first_tok, key, remaining, now):
        """Prefill tier: gather the request's pool blocks (position order, ONE
        jitted gather reused per block) to host and seal them with the sampler
        state into a HandoffRecord. Quantized pools ship int8 data + f32
        scales verbatim — the decode tier scatters the same bytes."""
        import jax

        from modalities_tpu.serving.disagg.handoff import HANDOFF_VERSION, HandoffRecord

        req, result = state.request, state.result
        rid = req.rid
        wl = len(state.window)
        nblk = blocks_for_tokens(wl, self.block_size)
        blocks = self._table_state.blocks(rid)[:nblk]
        with span("serve/handoff_export"):
            with self._rules_ctx():
                gathered = [
                    self._handoff_gather_jit(self.cache, np.int32(b)) for b in blocks
                ]
            host_rows = [jax.tree.flatten(jax.device_get(row))[0] for row in gathered]
        payload = [
            np.stack([row[leaf] for row in host_rows])
            for leaf in range(len(host_rows[0]))
        ]
        trace = self._traces.get(rid) or {}
        record = HandoffRecord(
            version=HANDOFF_VERSION,
            generation=int(self.weights_generation),
            quant_kv=self.quant_kv,
            block_size=self.block_size,
            window=list(state.window),
            last_token=int(first_tok),
            key=np.asarray(key, dtype=np.uint32),
            temperature=float(state.temp),
            remaining=int(remaining),
            seed=int(req.seed),
            payload=payload,
            trace_id=str(trace.get("trace_id", "")),
            trace_hop=int(trace.get("trace_hop", 0)),
            rid=rid,
            prompt_len=len(req.prompt_tokens),
            truncated=bool(result.truncated),
            deadline_ms=req.deadline_ms,
            tenant=req.tenant,
        ).seal()
        if fire_handoff_corrupt_if_armed(rid):
            # flip one payload byte AFTER sealing: the decode tier's digest
            # check must reject the import (retryable) rather than decode
            # from corrupt KV
            record.payload[0].view(np.uint8).flat[0] ^= 0xFF
        with self._stats_lock:
            self.handoffs_exported += 1
            self.handoff_bytes_shipped += record.kv_bytes
        self._m_handoffs.inc()
        self._m_kv_shipped.inc(record.kv_bytes)
        self._trace_event(
            rid, "handoff_export", now,
            blocks=record.num_blocks, kv_bytes=record.kv_bytes,
        )
        return record

    def _decode_dispatch(self, t0: float) -> None:
        """ONE compiled step for the whole batch, then host bookkeeping on the
        small (tokens, finished) fetch. Idle slots compute garbage harmlessly:
        their positions never advance and admission re-prefills over their rows."""
        import jax

        self._expire_active(t0)  # seam 3: no step for an expired request
        if self._decoding_count() == 0:
            return  # every decoder just expired
        fire_slow_decode_if_armed(self._dispatch_seq)
        jnp = self._jnp
        if self.kv_cache == "paged":
            props = self._collect_proposals() if self.spec.enabled else {}
            widths = {
                slot: min(len(d) + 1, self._slot_states[slot].remaining)
                for slot, d in props.items()
            }
            self._ensure_decode_blocks(t0, widths or None)
            if self._decoding_count() == 0:
                return  # every decoder was preempted into the queue
            props = {
                slot: d
                for slot, d in props.items()
                if self._slot_states[slot] is not None
                and self._slot_states[slot].phase == "decode"
            }
            if props:
                # at least one slot has drafts to score: the round goes
                # through the verify executable (slots without proposals ride
                # along as plain 1-token columns). No proposals anywhere ->
                # plain decode below, so BOTH decode-side programs stay warm
                self._spec_verify_dispatch(t0, props)
                return
        with span("serve/decode"):
            with self._rules_ctx():
                if self.kv_cache == "paged":
                    self.cache, toks_d, keys_d, fin_d, ok_d = self._decode_jit(
                        self.params, self.cache,
                        jnp.asarray(self._tokens), jnp.asarray(self._positions),
                        jnp.asarray(self._tables), jnp.asarray(self._wblk),
                        jnp.asarray(self._woff),
                        jnp.asarray(self._keys), jnp.asarray(self._temps),
                        jnp.asarray(self._eods), jnp.asarray(self._remaining),
                    )
                else:
                    self.cache, toks_d, keys_d, fin_d, ok_d = self._decode_jit(
                        self.params, self.cache,
                        jnp.asarray(self._tokens), jnp.asarray(self._positions),
                        jnp.asarray(self._keys), jnp.asarray(self._temps),
                        jnp.asarray(self._eods), jnp.asarray(self._remaining),
                    )
            toks, keys, finished, ok = jax.device_get((toks_d, keys_d, fin_d, ok_d))
        now = self._now() - t0
        active = self._decoding_count()
        emitted = 0
        for slot in range(self.slots):
            state = self._slot_states[slot]
            if state is None or state.phase != "decode":
                continue
            self._positions[slot] += 1  # the fed token landed in the cache
            tok = int(toks[slot])
            self._keys[slot] = keys[slot]
            if state.imported and not state.result.token_times_s:
                # decode-tier TTFT: the first LOCAL token (the request's 2nd
                # overall — token #1 shipped inside the handoff record)
                state.result.first_token_s = now
                self._record_first_token(state.result, now)
            if not bool(ok[slot]):  # non-finite logits: the token is garbage
                self._finish(slot, "error", now)
                continue
            if tok == self.eod_token_id:
                self._finish(slot, "eod", now)
                continue
            self._emit_token(state.result, tok, now)
            emitted += 1
            if finished[slot]:  # budget exhausted (eod handled above)
                self._finish(slot, "budget", now)
                continue
            state.remaining -= 1
            self._remaining[slot] = state.remaining
            self._tokens[slot, 0] = tok
            if self.kv_cache == "ring" and self._positions[slot] >= self.capacity:
                # ring full: the interactive path falls back to a sliding-window
                # re-forward; the engine finishes the request instead (documented
                # divergence — docs/components.md serving section). Paged mode
                # never takes this exit: the admission budget clamp bounds
                # positions below max_len
                self._finish(slot, "capacity", now)
        with self._stats_lock:
            self.decode_steps += 1
            self._occupancy_sum += active
            self.max_concurrent = max(self.max_concurrent, active)
            self.decode_token_count += emitted
        self._m_decode_steps.inc()

    def _collect_proposals(self) -> dict:
        """Prompt-lookup drafts per decoding slot. Greedy slots only (sampled
        slots have nothing to verify against — their token is a draw, not an
        argmax), and only while >1 token of budget remains (the final token is
        a plain decode either way). Deterministic: a pure function of the
        request's own context, so preemption replay re-proposes identically."""
        props: dict[int, list[int]] = {}
        for slot in range(self.slots):
            state = self._slot_states[slot]
            if state is None or state.phase != "decode":
                continue
            if state.temp > 0.0 or state.remaining <= 1:
                continue
            drafts = propose_ngram(
                state.window + state.result.tokens,
                self.spec.k, self.spec.ngram_max, self.spec.ngram_min,
            )
            if drafts:
                props[slot] = drafts
        return props

    def _spec_verify_dispatch(self, t0: float, props: dict) -> None:
        """ONE [slots, k+1] verify forward for the whole batch: column 0 feeds
        each slot's pending token (so a slot with no drafts behaves exactly
        like a plain decode column — sampled slots draw via samp() on column
        0), columns 1..n feed the drafts. The device returns the greedy
        continuation per column + the folded accept length; the host replays
        the sequential stopping rule over the accepted run, so eod/budget
        semantics — and the emitted tokens — are bitwise the plain-decode
        trajectory."""
        import jax

        jnp = self._jnp
        S, K1 = self.slots, self.spec.k + 1
        ts = self._table_state
        toks = np.zeros((S, K1), np.int32)
        pos_a = np.zeros((S, K1), np.int32)
        wblk = np.full((S, K1), self.num_blocks, np.int32)  # default: write nowhere
        woff = np.zeros((S, K1), np.int32)
        prop_len = np.zeros((S,), np.int32)
        for slot in range(S):
            state = self._slot_states[slot]
            if state is None or state.phase != "decode":
                continue
            p = int(self._positions[slot])
            drafts = props.get(slot, [])
            n = len(drafts)
            toks[slot, 0] = self._tokens[slot, 0]
            toks[slot, 1 : 1 + n] = drafts
            pos_a[slot] = p + np.arange(K1)
            prop_len[slot] = n
            # write window: rejected-draft positions hold garbage afterwards,
            # but the next dispatch's contiguous writes overwrite any garbage
            # position before a query can attend it (key_pos <= pos masks the
            # rest), and columns past the budget drop their writes entirely
            w = min(n + 1, state.remaining)
            rid = state.request.rid
            for j in range(w):
                blk, off = ts.write_coords(rid, p + j)
                wblk[slot, j] = blk
                woff[slot, j] = off
        with span("serve/decode"):
            with self._rules_ctx():
                self.cache, g_d, toks0_d, keys_d, acc_d, ok_d = self._verify_jit(
                    self.params, self.cache,
                    jnp.asarray(toks), jnp.asarray(pos_a), jnp.asarray(self._tables),
                    jnp.asarray(wblk), jnp.asarray(woff),
                    jnp.asarray(self._keys), jnp.asarray(self._temps),
                    jnp.asarray(prop_len),
                )
            g, toks0, keys, acc, ok = jax.device_get((g_d, toks0_d, keys_d, acc_d, ok_d))
        now = self._now() - t0
        active = self._decoding_count()
        emitted_total = 0
        proposed_total = 0
        accepted_total = 0
        for slot in range(S):
            state = self._slot_states[slot]
            if state is None or state.phase != "decode":
                continue
            self._keys[slot] = keys[slot]
            if state.imported and not state.result.token_times_s:
                # imported slot's first round took the verify path: same
                # decode-tier TTFT point as the plain-decode branch
                state.result.first_token_s = now
                self._record_first_token(state.result, now)
            if not bool(ok[slot]):  # non-finite logits: nothing here is a token
                self._finish(slot, "error", now)
                continue
            p = int(self._positions[slot])
            drafts = props.get(slot, [])
            if drafts:
                L = int(acc[slot])
                e = min(L + 1, state.remaining)  # emitted run, all valid columns
                emitted_seq = [int(g[slot, j]) for j in range(e)]
                used = min(L, e - 1)  # drafts that actually advanced the slot
                proposed_total += len(drafts)
                accepted_total += used
                trace = self._traces.get(state.request.rid)
                if trace is not None:
                    trace["spec_proposed"] = trace.get("spec_proposed", 0) + len(drafts)
                    trace["spec_accepted"] = trace.get("spec_accepted", 0) + used
            else:
                emitted_seq = [int(toks0[slot])]
            # replay the sequential stopping rule over the accepted run
            n_emit = 0
            fin = None
            rem = state.remaining
            for tok in emitted_seq:
                if tok == self.eod_token_id:
                    fin = "eod"
                    break
                self._emit_token(state.result, tok, now)
                n_emit += 1
                if rem <= 1:
                    fin = "budget"
                    break
                rem -= 1
            emitted_total += n_emit
            if fin is not None:
                self._finish(slot, fin, now)
                continue
            state.remaining = rem
            self._remaining[slot] = rem
            self._positions[slot] = p + n_emit
            self._tokens[slot, 0] = emitted_seq[-1]
        with self._stats_lock:
            self.decode_steps += 1
            self.verify_steps += 1
            self._occupancy_sum += active
            self.max_concurrent = max(self.max_concurrent, active)
            self.decode_token_count += emitted_total
            self.spec_proposed += proposed_total
            self.spec_accepted += accepted_total
        self._m_decode_steps.inc()
        if proposed_total:
            self._m_spec_proposed.inc(proposed_total)
        if accepted_total:
            self._m_spec_accepted.inc(accepted_total)

    def _occupancy_ratio(self) -> float:
        with self._stats_lock:
            if not self.decode_steps:
                return 0.0
            return self._occupancy_sum / (self.decode_steps * self.slots)

    def step(self, t0: float) -> bool:
        """One scheduler round: admit, (paged) prefill dispatch, decode
        dispatch. Returns True if any device work was dispatched — the run loop
        and the HTTP server's engine thread both drive this.

        Watchdog: each round with pending work arms the hang watchdog (the same
        one guarding Trainer steps), beating on a dispatched round and disarming
        on an idle one — a wedged prefill/decode produces a `watchdog_dump_*`
        artifact with the engine's stats in its state section."""
        telemetry = get_active_telemetry()
        self._maybe_apply_swap()  # token boundary: install any queued weight swap
        armed = bool(self._queue) or self._active_count() > 0
        if armed:
            self._dispatch_seq += 1
            telemetry.arm_watchdog(self._dispatch_seq, first_step=self._dispatch_seq == 1)
        self._admit(t0)
        did = False
        try:
            fire_oom_if_armed(self._dispatch_seq)
            fire_serve_worker_hang_if_armed(self._dispatch_seq)
            if self.kv_cache == "paged" and self._prefilling_slots():
                self._prefill_dispatch(t0)
                did = True
            if self._decoding_count():
                self._decode_dispatch(t0)
                did = True
        except Exception as e:
            from modalities_tpu.telemetry.memscope import is_oom_error, oom_forensics

            if is_oom_error(e):
                raise oom_forensics(
                    telemetry.sink_path.parent if telemetry.sink_path is not None else Path("."),
                    rank=telemetry.global_rank,
                    step=self._dispatch_seq,
                    exc=e,
                    static_report=getattr(self, "_memscope_cache", None),
                    metrics_snapshot=self.metrics.snapshot(),
                ) from e
            raise
        if armed:
            if did:
                telemetry.beat_watchdog(self._dispatch_seq)
            else:
                telemetry.disarm_watchdog()  # idle round: not wedged, just waiting
        return did

    def run(self) -> dict[int, ServeResult]:
        """Serve until queue and slots drain — or, when `stop_fn` trips, until
        in-flight slots finish (graceful drain: no new admissions, queued
        requests are left unserved). Returns rid -> ServeResult."""
        t0 = self._now()
        if self.kv_cache == "paged":
            self._table_state.pool.reset_peak()  # the high-water mark is per run, like the clock
        try:
            while True:
                stopping = self._stopping()
                if stopping:
                    if self._active_count() == 0:
                        break
                elif not self._queue and self._active_count() == 0:
                    break
                did = self.step(t0)
                if not did:
                    if stopping or not self._queue:
                        break
                    # nothing running and the head hasn't arrived: wait for it
                    wait = self._queue[0].arrival_offset_s - (self._now() - t0)
                    if wait > 0:
                        time.sleep(min(wait, 0.05))
        finally:
            get_active_telemetry().disarm_watchdog()
        return self._results

    # -------------------------------------------------------------------- stats
    def stats(self) -> dict:
        """One consistent snapshot: counters are read under the same lock their
        dispatch-end updates hold, so a concurrent /stats never sees a
        mid-dispatch tear (e.g. decode_tokens without its decode_steps)."""
        with self._stats_lock:
            decode_steps = self.decode_steps
            decode_tokens = self.decode_token_count
            occupancy_sum = self._occupancy_sum
            max_concurrent = self.max_concurrent
            preemptions = self.preemptions
            truncated = self.truncated_requests
            prefix_hit_requests = self.prefix_hit_requests
            prefix_hit_blocks = self.prefix_hit_blocks
            prefix_hit_tokens = self.prefix_hit_tokens
            cow_copies = self.cow_copies
            verify_steps = self.verify_steps
            spec_proposed = self.spec_proposed
            spec_accepted = self.spec_accepted
            weight_swaps = self.weight_swaps
            request_errors = self.request_errors
            deadline_expired = self.deadline_expired_requests
            shed = self.shed_requests
            handoffs_exported = self.handoffs_exported
            handoffs_imported = self.handoffs_imported
            import_requeues = self.import_requeues
            imported_blocks = self.imported_blocks
            handoff_bytes = self.handoff_bytes_shipped
            prefill_chunk_count = self.prefill_chunk_count
            tenant_stats = {t: dict(b) for t, b in self._tenant_stats.items()}
        occupancy = occupancy_sum / (decode_steps * self.slots) if decode_steps else 0.0
        out = {
            "role": self.role,
            "kv_cache": self.kv_cache,
            "decode_steps": decode_steps,
            "decode_tokens": decode_tokens,
            "slot_occupancy": occupancy,
            "max_concurrent": max_concurrent,
            "decode_executables": self._decode_traces,
            "prefill_executables": self._prefill_traces,
            "slots": self.slots,
            "capacity": self.capacity,
            "preemptions": preemptions,
            "truncated_requests": truncated,
            "queue_depth": len(self._queue),
            "active_slots": self._active_count(),
            "weights_generation": self.weights_generation,
            "weight_swaps": weight_swaps,
            "request_errors": request_errors,
            "deadline_expired_requests": deadline_expired,
            "shed_requests": shed,
            "quant_weights": self.quant_weights,
            "quant_kv": self.quant_kv,
            "kv_pool_bytes": self.kv_pool_bytes,
            "quant_bytes_saved": self._quant_bytes_saved,
        }
        if self.kv_cache == "paged":
            out.update(
                max_len=self.max_len,
                block_size=self.block_size,
                num_blocks=self.num_blocks,
                free_blocks=self._table_state.pool.free_count,
                blocks_in_use_peak=self._table_state.pool.peak_used,
                prefix_sharing=self.prefix_sharing,
                prefix_hit_requests=prefix_hit_requests,
                prefix_hit_blocks=prefix_hit_blocks,
                prefix_hit_tokens=prefix_hit_tokens,
                cow_copies=cow_copies,
                cow_executables=self._cow_traces,
                shared_blocks=self._table_state.pool.shared_count,
                prefix_index_size=self._table_state.prefix_index_size,
                spec_k=self.spec.k,
                verify_steps=verify_steps,
                verify_executables=self._verify_traces,
                spec_proposed=spec_proposed,
                spec_accepted=spec_accepted,
                prefill_chunk_count=prefill_chunk_count,
            )
        if self.role != "combined":
            out.update(
                handoffs_exported=handoffs_exported,
                handoffs_imported=handoffs_imported,
                import_requeues=import_requeues,
                imported_blocks=imported_blocks,
                handoff_bytes_shipped=handoff_bytes,
                handoff_executables=self._handoff_traces,
                import_executables=self._import_traces,
            )
        if self._tenants is not None:
            slot_counts = self._tenant_slot_counts()
            queued: dict[str, int] = {}
            for r in self._queue:
                queued[r.tenant] = queued.get(r.tenant, 0) + 1
            tenants_out = {}
            for name in sorted(
                set(self._tenants.names()) | set(tenant_stats) | set(queued)
            ):
                spec = self._tenants.spec(name)
                row = dict(
                    tenant_stats.get(
                        name,
                        {"submitted": 0, "finished": 0, "tokens": 0, "shed": 0,
                         "preemptions": 0, "rate_limited": 0},
                    )
                )
                row.update(
                    tenant_class=spec.tenant_class,
                    weight=spec.weight,
                    max_slots=spec.max_slots,
                    active_slots=slot_counts.get(name, 0),
                    queued=queued.get(name, 0),
                )
                tenants_out[name] = row
            out["tenants"] = tenants_out
        return out

    def decode_lowered_text(self) -> str:
        """Lowered HLO of the decode step with the CURRENT arg shardings — the
        sharding acceptance test greps this for mesh annotations."""
        return self._decode_lowered().as_text()

    def decode_compiled_text(self) -> str:
        """Optimized HLO of the decode step as the backend compiled it — where a
        Pallas kernel shows as a `tpu_custom_call`."""
        with self._rules_ctx():
            return self._decode_lowered().compile().as_text()

    def _decode_lowered(self):
        """The decode step's `jax.stages.Lowered` with the CURRENT arg shardings."""
        jnp = self._jnp
        with self._rules_ctx():
            if self.kv_cache == "paged":
                return self._decode_jit.lower(
                    self.params, self.cache,
                    jnp.asarray(self._tokens), jnp.asarray(self._positions),
                    jnp.asarray(self._tables), jnp.asarray(self._wblk),
                    jnp.asarray(self._woff),
                    jnp.asarray(self._keys), jnp.asarray(self._temps),
                    jnp.asarray(self._eods), jnp.asarray(self._remaining),
                )
            return self._decode_jit.lower(
                self.params, self.cache,
                jnp.asarray(self._tokens), jnp.asarray(self._positions),
                jnp.asarray(self._keys), jnp.asarray(self._temps),
                jnp.asarray(self._eods), jnp.asarray(self._remaining),
            )

    def perfscope_report(self, hw=None) -> dict:
        """Compile the batched decode step and bucket its optimized-HLO cost by
        op class (telemetry/perfscope.py) — the serving half of performance
        attribution. Decode is the steady-state executable, so its matmul-vs-
        bytes split IS the engine's roofline position."""
        from modalities_tpu.telemetry.perfscope import perfscope_from_compiled

        mesh_axis_sizes = None
        if self._mesh_handle is not None:
            mesh_axis_sizes = {
                k: int(v) for k, v in self._mesh_handle.mesh.shape.items()
            }
        with self._rules_ctx():
            compiled = self._decode_lowered().compile()
        return perfscope_from_compiled(compiled, mesh_axis_sizes, hw)

    def scope_table(self) -> dict[str, str]:
        """{instruction name: op_name} of the compiled decode step
        (telemetry/perfscope.py, the vocabulary in telemetry/scopes.py), for a
        reader that names a device trace's events by scope."""
        from modalities_tpu.telemetry.perfscope import scope_table

        with self._rules_ctx():
            return scope_table(self._decode_lowered().compile().as_text())

    def memscope_report(self) -> dict:
        """Compile the batched decode step and carve its memory_analysis() bytes
        into semantic buckets (telemetry/memscope.py): params + KV pool dominate
        a decode executable, and the KV bucket is the one paged_num_blocks /
        quant_kv actually move. Cached — the OOM forensics dump reuses it."""
        from modalities_tpu.quant.core import tree_bytes
        from modalities_tpu.telemetry.memscope import memscope_from_compiled

        known = {
            "params": int(tree_bytes(self.params)),
            "kv_pool": int(self.kv_pool_bytes),
        }
        context = {
            "kind": "serving",
            "kv_cache": self.kv_cache,
            "quant_kv": self.quant_kv,
            "quant_weights": self.quant_weights,
        }
        if self.kv_cache == "paged":
            context["paged_num_blocks"] = self.num_blocks
        with self._rules_ctx():
            compiled = self._decode_lowered().compile()
        report = memscope_from_compiled(compiled, known, context)
        self._memscope_cache = report
        return report
