"""Performance-attribution scope (PR 13): static HLO cost breakdown, programmatic
profiler capture windows, and step-time anomaly detection.

Three pillars, all host-side observability (nothing here touches the jitted
step's math — pinned by the bitwise profiler test):

1. **HLO cost scope.** `analyze_hlo_text` walks an OPTIMIZED (post-SPMD) HLO
   module — the text `jax.jit(...).lower(...).compile().as_text()` returns —
   and buckets every instruction's FLOPs / bytes / roofline time estimate into
   op classes: `matmul`, `custom_call` (Pallas kernels), `collective:<axis>`
   (per mesh axis, matched by replica-group size), `host_transfer`,
   `elementwise`, and `other`. The per-bucket totals sum to the module total
   *by construction* (every instruction lands in exactly one bucket), so the
   report's closure is a structural invariant, not a float coincidence — the
   tier-1 test pins it. This is the GSPMD observation (arXiv 2105.04663) made
   operational: the partitioned program statically names every collective and
   matmul, so "where does the roofline say the MFU went" is answerable on a
   CPU host without a single device second.
2. **Profiler capture windows.** `ProfileWindow.from_env()` parses
   `MODALITIES_TPU_PROFILE_AT_STEP=N[:K]` and arms `jax.profiler`
   start/stop_trace around steps [N, N+K) — the trainer calls
   `maybe_start`/`maybe_stop` unconditionally; both are no-ops outside the
   window. Capture must never perturb results: the step fn is untouched, only
   host-side trace collection toggles.
3. **Anomaly detection.** `AnomalyDetector` keeps a rolling window and scores
   each observation with a robust z (median/MAD, 0.6745 normalization) plus an
   EWMA; the `Telemetry` facade feeds per-step wall time and per-goodput-bucket
   deltas through detectors into the PR-10 metrics registry
   (`training_step_time_anomaly_total`, `training_goodput_bucket_zscore`).

The module doubles as a subprocess entry point (mirroring
utils/recipe_validation.py): `python -m modalities_tpu.telemetry.perfscope
<config.yaml>` builds the recipe's train step over a virtual CPU mesh of its
world_size, lowers + compiles it, and prints the perfscope report JSON — the
`data analyze_perfscope` CLI's engine.
"""

from __future__ import annotations

import json
import math
import os
import re
import statistics
from collections import deque
from dataclasses import dataclass
from pathlib import Path
from typing import Iterator, NamedTuple, Optional, Union

from modalities_tpu.telemetry.scopes import scope_path
from modalities_tpu.utils.logging import get_logger

logger = get_logger(__name__)

# ------------------------------------------------------------------ HLO parsing

_DTYPE_BYTES = {
    "pred": 1, "s2": 1, "s4": 1, "s8": 1, "u2": 1, "u4": 1, "u8": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
    "token": 0, "opaque": 0,
}

# one typed array literal inside an HLO instruction line: dtype[dims]{layout}?
_SHAPE_RE = re.compile(r"\b([a-z][a-z0-9]*)\[([0-9,]*)\](?:\{[^}]*\})?")
# instruction line: "  %name = <shapes> opcode(...), attrs" (ROOT optional)
_INSTR_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
# first bare identifier followed by '(' after the output shape(s) is the opcode
_OPCODE_RE = re.compile(r"\b([a-z][a-z0-9\-]*)\(")
# computation header: "name (params) -> result {" or "name {"; an instruction line has
# "name = ..." instead. The parameter list may nest parentheses (tuple types).
_COMP_START_RE = re.compile(r"^\s*(?:ENTRY\s+)?%?([\w.\-]+)\s+(?:\(.*\)\s*->.*\{|\{)\s*$")
_CALLS_RE = re.compile(r"calls=%?([\w.\-]+)")
_REPLICA_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_REPLICA_GROUPS_LIT_RE = re.compile(r"replica_groups=\{\{([^}]*)\}")
# full-geometry forms of the same attribute: the iota form with its source dims
# and optional transpose, and the literal form with every group captured — the
# multi-slice classifier expands these to explicit partition-id sets
_REPLICA_GROUPS_IOTA_FULL_RE = re.compile(
    r"replica_groups=\[(\d+),(\d+)\]<=\[([0-9,]+)\](?:T\(([0-9,]+)\))?"
)
_REPLICA_GROUPS_LIT_FULL_RE = re.compile(
    r"replica_groups=\{(\{[^}]*\}(?:,\s*\{[^}]*\})*)\}"
)
_CONTRACT_RE = re.compile(r"lhs_contracting_dims=\{([0-9,]*)\}")
_CUSTOM_TARGET_RE = re.compile(r'custom_call_target="([^"]*)"')
_OP_NAME_RE = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_TO_APPLY_RE = re.compile(r"to_apply=%?([\w.\-]+)")

# instruction opcodes that are pure bookkeeping: no data moved, no flops
_SKIP_OPS = frozenset(
    ("parameter", "constant", "tuple", "get-tuple-element", "bitcast",
     "after-all", "partition-id", "replica-id", "domain", "opt-barrier")
)
_COLLECTIVE_OPS = frozenset(
    ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
     "collective-permute", "collective-broadcast",
     "all-reduce-start", "all-gather-start", "collective-permute-start")
)
# *-done halves complete an async pair whose cost the *-start already carries
_COLLECTIVE_DONE_OPS = frozenset(
    ("all-reduce-done", "all-gather-done", "collective-permute-done",
     "async-done", "async-update")
)
_HOST_OPS = frozenset(("send", "recv", "send-done", "recv-done", "infeed", "outfeed"))
_MATMUL_OPS = frozenset(("dot", "convolution"))
# ops that do ~1 flop per output element (the elementwise/reduction family);
# everything else with shapes is data movement -> "other"
_ELEMENTWISE_OPS = frozenset(
    ("add", "subtract", "multiply", "divide", "power", "maximum", "minimum",
     "abs", "negate", "exponential", "exponential-minus-one", "log",
     "log-plus-one", "logistic", "tanh", "sqrt", "rsqrt", "cbrt", "sign",
     "sine", "cosine", "tan", "atan2", "erf", "floor", "ceil", "round",
     "round-nearest-even", "compare", "select", "clamp", "and", "or", "xor",
     "not", "shift-left", "shift-right-logical", "shift-right-arithmetic",
     "remainder", "is-finite", "reduce", "reduce-window", "map",
     "select-and-scatter", "sort", "rng", "rng-bit-generator", "iota",
     "stochastic-convert", "convert", "reduce-precision", "exp")
)

# annotation-only custom calls the SPMD pipeline leaves behind — zero cost
_ANNOTATION_CUSTOM_CALLS = frozenset(
    ("Sharding", "SPMDFullToShardShape", "SPMDShardToFullShape",
     "MoveToHost", "MoveToDevice", "AllocateBuffer")
)


@dataclass
class HwSpec:
    """Roofline constants for the time estimate. Defaults are TPU v5p-ish
    (bf16 peak, HBM3 bandwidth, one ICI link); override per call or leave as-is
    — bucket *shares* are what the report is for, not absolute seconds."""

    peak_flops: float = 459e12  # bf16 FLOP/s
    hbm_bw: float = 2.765e12  # bytes/s
    collective_bw: float = 4.8e11  # bytes/s over ICI
    collective_latency_s: float = 1e-6  # per-op launch/sync cost

    def as_dict(self) -> dict:
        return {
            "peak_flops": self.peak_flops,
            "hbm_bw": self.hbm_bw,
            "collective_bw": self.collective_bw,
            "collective_latency_s": self.collective_latency_s,
        }


def _shape_bytes(dtype: str, dims: str) -> tuple[int, int]:
    """(element_count, bytes) for one dtype[dims] literal."""
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n, n * _DTYPE_BYTES.get(dtype, 4)


def _line_shapes(text: str) -> list[tuple[int, int, int]]:
    """Every (position, elements, bytes) shape literal in an instruction line."""
    out = []
    for m in _SHAPE_RE.finditer(text):
        if m.group(1) not in _DTYPE_BYTES and not m.group(2):
            continue
        elems, nbytes = _shape_bytes(m.group(1), m.group(2))
        out.append((m.start(), elems, nbytes))
    return out


def _parse_replica_groups(line: str) -> Optional[list[list[int]]]:
    """Explicit replica groups (lists of partition ids) from either HLO syntax.

    The iota form ``[G,S]<=[d0,d1,..]T(perm)`` is expanded exactly: an iota over
    prod(dims) partition ids, reshaped to ``dims``, transposed by ``perm``, and
    regrouped row-major into G groups of S. Returns None when the line carries
    no replica-group attribute (or an inconsistent one)."""
    m = _REPLICA_GROUPS_IOTA_FULL_RE.search(line)
    if m:
        n_groups, group_size = int(m.group(1)), int(m.group(2))
        dims = [int(d) for d in m.group(3).split(",") if d]
        n = n_groups * group_size
        if math.prod(dims) != n:
            return None
        perm = (
            [int(i) for i in m.group(4).split(",") if i]
            if m.group(4)
            else list(range(len(dims)))
        )
        strides = [1] * len(dims)
        for i in range(len(dims) - 2, -1, -1):
            strides[i] = strides[i + 1] * dims[i + 1]
        perm_dims = [dims[p] for p in perm]
        perm_strides = [strides[p] for p in perm]
        vals = []
        for j in range(n):
            rem, v = j, 0
            for size, stride in zip(reversed(perm_dims), reversed(perm_strides)):
                v += (rem % size) * stride
                rem //= size
            vals.append(v)
        return [vals[g * group_size : (g + 1) * group_size] for g in range(n_groups)]
    m = _REPLICA_GROUPS_LIT_FULL_RE.search(line)
    if m:
        return [
            [int(x) for x in grp.split(",") if x.strip()]
            for grp in re.findall(r"\{([^}]*)\}", m.group(1))
        ]
    return None


_SOURCE_TARGET_PAIRS_RE = re.compile(r"source_target_pairs=\{(\{[^}]*\}(?:,\s*\{[^}]*\})*)\}")
_CHANNEL_ID_RE = re.compile(r"channel_id=(\d+)")
_CHAIN_ID_RE = re.compile(r"chain_id=\"(\d+)\"")


def _axes_of_groups(groups: list[list[int]], sizes: dict[str, int]) -> Optional[tuple[list[str], bool]]:
    """Where replica groups lie in the mesh: (the axes along which a group's members
    differ, in the mesh's order; whether every group is the whole extent of exactly
    those axes). A partition id unravels row-major over the axis sizes in the mesh's own
    axis order, which is the compiled program's device assignment (`mesh.devices.flat`).
    None where the groups name a partition the mesh does not have: no geometry is known."""
    names = list(sizes)
    dims = [sizes[name] for name in names]
    members = [d for g in groups for d in g]
    if not members or not dims or max(members) >= math.prod(dims) or min(members) < 0:
        return None
    strides = [math.prod(dims[i + 1:]) for i in range(len(dims))]
    differing: set[int] = set()
    for group in groups:
        for i, (size, stride) in enumerate(zip(dims, strides)):
            if len({(d // stride) % size for d in group}) > 1:
                differing.add(i)
    extent = math.prod(dims[i] for i in differing)
    whole = all(len(set(group)) == extent for group in groups)
    return [names[i] for i in sorted(differing)], whole


def _collective_axis(line: str, mesh_axis_sizes: Optional[dict[str, int]]) -> str:
    """Name the mesh axis, or axes, a collective runs over, from where its replica groups
    lie in the mesh (`_axes_of_groups`; `mesh_axis_sizes` must preserve the mesh's axis
    order): `{{0,1},{2,3}}` on `dp_shard 2 x tp 2` is `tp`, `{{0,2},{1,3}}` is
    `dp_shard`, `{{0,1,2,3}}` is `dp_shard+tp`, and the iota forms are expanded to the
    same sets first. A collective-permute is read off its source-target pairs the same
    way. Two sizes that coincide can so never trade places, which matching by size did
    on every 2 x 2 mesh.

    The slow fabric is a case of the rule: a group whose members differ along `dcn`
    lands in the `dcn` bucket whatever else it spans and whatever its size, because a
    cross-slice hop must never hide in an ICI bucket. Matching by size stays as the
    fall-back where no geometry is known (no mesh, groups that name partitions the mesh
    lacks, or groups that are not the whole extent of the axes they differ along), and
    an unmatched size keeps a `size<g>` tag so the bucket is still stable and greppable."""
    sizes = {k: int(v) for k, v in (mesh_axis_sizes or {}).items()}
    groups = _parse_replica_groups(line)
    pairs = None  # of a collective-permute, which has no groups
    m = None if groups else _SOURCE_TARGET_PAIRS_RE.search(line)
    if m:
        pairs = [[int(x) for x in pair.split(",") if x.strip()] for pair in re.findall(r"\{([^}]*)\}", m.group(1))]
    if groups:
        group_size = len(groups[0])
    else:
        group_size = None
        m = _REPLICA_GROUPS_IOTA_RE.search(line)
        if m:  # iota format [groups,size]<=[n]
            group_size = int(m.group(2))
        else:
            m = _REPLICA_GROUPS_LIT_RE.search(line)
            if m:  # literal format {{0,1},{2,3}}: size of the first group
                group_size = len([t for t in m.group(1).split(",") if t.strip()])
    if pairs is None and (group_size is None or group_size <= 1):
        return "all"
    found = _axes_of_groups(groups or pairs, sizes) if sizes else None
    geometry_known = found is not None
    if geometry_known:
        axes, whole = found
        if "dcn" in axes:
            return "dcn"
        if axes and (whole or pairs is not None):
            return "+".join(axes)
    if group_size is None:
        return "all"
    for axis, size in sorted(sizes.items()):
        if axis == "dcn" and geometry_known:
            continue  # geometry already proved these groups stay intra-slice
        if size == group_size:
            return axis
    return f"size{group_size}"


def _instruction_cost(opcode: str, line: str, rhs: str, opcode_pos: int) -> tuple[int, int]:
    """(flops, bytes) for one instruction line. Output shapes precede the
    opcode; operand shapes follow it. Bytes = operands read + outputs written
    (the HBM traffic a roofline charges); flops are per-op-family estimates."""
    shapes = _line_shapes(rhs)
    out_elems = sum(e for pos, e, _ in shapes if pos < opcode_pos)
    out_bytes = sum(b for pos, _, b in shapes if pos < opcode_pos)
    in_bytes = sum(b for pos, _, b in shapes if pos > opcode_pos)
    nbytes = out_bytes + in_bytes

    if opcode in _MATMUL_OPS:
        contract = 1
        m = _CONTRACT_RE.search(line)
        if m and opcode == "dot":
            # contracting size = product of the lhs dims named in the attr;
            # the lhs shape is the first operand literal after the opcode
            operand_shapes = [
                (pos, _SHAPE_RE.match(rhs, pos)) for pos, _, _ in shapes if pos > opcode_pos
            ]
            if operand_shapes:
                lhs = operand_shapes[0][1]
                dims = [int(d) for d in lhs.group(2).split(",") if d]
                for idx in (int(i) for i in m.group(1).split(",") if i):
                    if 0 <= idx < len(dims):
                        contract *= dims[idx]
        flops = 2 * out_elems * max(contract, 1)
        return flops, nbytes
    if opcode in _ELEMENTWISE_OPS:
        return out_elems, nbytes
    return 0, nbytes


class _Instruction(NamedTuple):
    computation: Optional[str]
    name: str
    opcode: str
    opcode_pos: int  # where the opcode starts in `rhs`: output shapes before it, operands after
    rhs: str
    line: str
    is_root: bool


def _instructions(hlo_text: str) -> Iterator[_Instruction]:
    """Walk an HLO module's text, one `_Instruction` per instruction line."""
    current_comp = None
    for raw_line in hlo_text.splitlines():
        comp_m = _COMP_START_RE.match(raw_line)
        if comp_m:
            current_comp = comp_m.group(1)
            continue
        instr = _INSTR_RE.match(raw_line)
        if instr is None:
            continue
        rhs = instr.group(2)
        op_m = _OPCODE_RE.search(rhs)
        if op_m is None:
            continue
        yield _Instruction(current_comp, instr.group(1), op_m.group(1), op_m.start(), rhs, raw_line,
                           raw_line.lstrip().startswith("ROOT "))


def _op_name(line: str) -> Optional[str]:
    m = _OP_NAME_RE.search(line)
    return m.group(1).replace("\\'", "'").replace('\\"', '"') if m else None


def scope_table(hlo_text: str) -> dict[str, str]:
    """{instruction name: op_name} of an optimized HLO module: the scope path JAX
    wrote while tracing (telemetry/scopes.py), for every instruction that can run as
    an operation of its own and so show as one event of a device trace — those of the
    entry computation, of loop bodies and conditions, of calls and branches. A fusion
    whose own metadata is empty carries its root's (the last named instruction of its
    fused computation where the root has none). Left out: bookkeeping instructions
    (parameters, tuples, bitcasts: `_SKIP_OPS`), the insides of fusions, and the
    bodies of reducers (`to_apply=` of anything but a `call`), none of which is ever
    an event. An instruction without an `op_name` is left out too; a reader counts
    what it cannot find as unattributed."""
    rows = list(_instructions(hlo_text))
    inside = set()  # computations whose instructions never run on their own
    for row in rows:
        if row.opcode == "fusion":
            inside.update(_CALLS_RE.findall(row.line))
        elif row.opcode != "call":
            inside.update(_TO_APPLY_RE.findall(row.line))
    root_name: dict[str, str] = {}  # fused computation -> its root's op_name, or its last named instruction's
    rooted = set()
    for row in rows:
        if row.computation in inside and row.computation not in rooted:
            name = _op_name(row.line)
            if name is not None:
                root_name[row.computation] = name
                if row.is_root:
                    rooted.add(row.computation)
    table: dict[str, str] = {}
    for row in rows:
        if row.computation in inside or row.opcode in _SKIP_OPS:
            continue
        name = _op_name(row.line)
        if name is None and row.opcode == "fusion":
            called = _CALLS_RE.search(row.line)
            name = root_name.get(called.group(1)) if called else None
        if name is not None:
            table[row.name] = name
    return table


class _Wrapper(NamedTuple):
    name: str  # the wrapping instruction: what a device trace prints for the collective inside
    computation: Optional[str]  # where the wrapping instruction sits: the pieces of one cut collective sit in one
    fused: bool  # a fusion (the chip's compiler), not an `async-start` round a called computation
    done: bool  # the fusion that completes a collective cut into several (it holds the `AsyncCollectiveDone` custom call)
    op_name: Optional[str]


def _collective_wrappers(instructions: list[_Instruction]) -> dict[str, _Wrapper]:
    """{computation: the instruction that wraps it}, for the computations a collective can sit in without being an
    operation of its own: a fused computation (a TPU's optimized module fuses a reduce-scatter into
    `fusion(...), calls=%all-reduce-scatter.N`, and cuts an asynchronous all-gather into the fusions
    `async-collective-start.N`, compute fusions that carry its steps, and `async-collective-done.N`, the last known by
    the `AsyncCollectiveDone` custom call it holds) and the computation an
    `async-start` calls (the generic asynchronous form of a reduce-scatter or an all-to-all)."""
    holds_a_collective = {row.computation for row in instructions if row.opcode in _COLLECTIVE_OPS}
    completes_one = {row.computation for row in instructions
                     if row.opcode == "custom-call" and 'custom_call_target="AsyncCollectiveDone"' in row.line}
    return {called: _Wrapper(row.name, row.computation, row.opcode == "fusion", called in completes_one, _op_name(row.line))
            for row in instructions if row.opcode in ("fusion", "async-start")
            for called in _CALLS_RE.findall(row.line) if called in holds_a_collective}


_WHILE_RE = re.compile(r"condition=%?([\w.\-]+),\s*body=%?([\w.\-]+)")
_INT_CONSTANT_RE = re.compile(r"\bconstant\((\d+)\)")
_CALLED_RE = re.compile(r"(?:calls|to_apply|true_computation|false_computation)=%?([\w.\-]+)|branch_computations=\{([^}]*)\}")


def _times_a_run(instructions: list[_Instruction]) -> dict[str, int]:
    """{computation: how often its instructions run in one execution of the module}: 1 for the entry computation,
    times the trip count of every loop round it. A loop's trip count is read off its condition where that is the
    form a scan lowers to (one integer constant, compared `LT` with a counter that starts at 0: the layer scan's
    `constant(32)`); a loop of any other form counts once, so a number of times is a floor, and a trace has the
    count that ran (`benchmark/readers/collectives.py` prints both)."""
    by_computation: dict[str, list[_Instruction]] = {}
    for row in instructions:
        by_computation.setdefault(row.computation, []).append(row)

    def trip_count(condition: str) -> int:
        rows = by_computation.get(condition, [])
        constants = [int(c) for row in rows if row.opcode == "constant" for c in _INT_CONSTANT_RE.findall(row.rhs)]
        bounded = any(row.opcode == "compare" and "direction=LT" in row.line for row in rows)
        return constants[0] if bounded and len(constants) == 1 and constants[0] > 0 else 1

    called_from: dict[str, tuple[str, int]] = {}  # computation -> (the computation that calls it, times a call)
    for row in instructions:
        loop = _WHILE_RE.search(row.line) if row.opcode == "while" else None
        if loop:
            called_from.setdefault(loop.group(2), (row.computation, trip_count(loop.group(1))))
            continue
        for single, several in _CALLED_RE.findall(row.line):
            for called in ([single] if single else [c.strip().lstrip("%") for c in several.split(",")]):
                called_from.setdefault(called, (row.computation, 1))

    times: dict[str, int] = {}

    def of(computation: str, depth: int = 0) -> int:
        if computation not in times:
            caller = called_from.get(computation)
            times[computation] = 1 if caller is None or depth > 64 else caller[1] * of(caller[0], depth + 1)
        return times[computation]

    return {computation: of(computation) for computation in by_computation}


def _first_operand(rhs: str, opcode_pos: int) -> Optional[str]:
    """The name of an instruction's first operand: what a `-done` completes."""
    found = re.search(r"\(\s*(?:[a-z][a-z0-9]*\[[^\]]*\](?:\{[^}]*\})?\s+)?%?([\w.\-]+)", rhs[opcode_pos:])
    return found.group(1) if found else None


def analyze_hlo_text(
    hlo_text: str,
    mesh_axis_sizes: Optional[dict[str, int]] = None,
    hw: Optional[HwSpec] = None,
    top_ops: int = 5,
) -> dict:
    """Bucket one optimized HLO module's instructions into op-class costs.

    Fusion double-count rule: a `fusion` instruction carries the HBM traffic
    (its operand/output shapes ARE what the fused kernel reads/writes) but no
    flops; the instructions inside the fused computation carry their flops but
    no bytes (their intermediates live in registers/VMEM). Every instruction
    therefore contributes to exactly one bucket once, and the report total is
    the sum of the buckets by construction.
    """
    hw = hw or HwSpec()
    # computations referenced by fusion instructions: inner ops = flops only
    fused_comps = set(_CALLS_RE.findall(hlo_text))
    module_name = ""
    m = re.search(r"HloModule\s+([\w.\-]+)", hlo_text)
    if m:
        module_name = m.group(1)
    instructions = list(_instructions(hlo_text))
    wrappers = _collective_wrappers(instructions)
    times_a_run = _times_a_run(instructions)

    buckets: dict[str, dict] = {}
    by_scope: dict[str, dict] = {}
    collectives: list[dict] = []  # one row a collective, under the name a device trace prints for it
    row_of: dict[str, dict] = {}  # by the name of a row's instruction, and by the channel of a fused one

    def _bucket(name: str) -> dict:
        b = buckets.get(name)
        if b is None:
            b = buckets[name] = {"ops": 0, "flops": 0, "bytes": 0, "est_time_s": 0.0, "top_ops": []}
        return b

    for current_comp, instr_name, opcode, opcode_pos, rhs, raw_line, _ in instructions:
        if opcode in _SKIP_OPS:
            continue
        in_fusion = current_comp in fused_comps

        flops, nbytes = _instruction_cost(opcode, raw_line, rhs, opcode_pos)
        if opcode == "fusion":
            flops = 0  # inner ops carry the flops
        elif in_fusion and opcode not in _COLLECTIVE_OPS:
            nbytes = 0  # the fusion instruction carries the traffic (a collective's bytes go over the links, not through HBM)

        if opcode in _COLLECTIVE_DONE_OPS:
            started = row_of.get(_first_operand(rhs, opcode_pos))
            if started is not None:
                started["done"] = instr_name  # the pair is one row: a trace shows both names
            continue  # cost carried by the matching *-start
        if opcode in _COLLECTIVE_OPS:
            wrapper = wrappers.get(current_comp)  # a fusion or an async-start round this computation: what the trace names
            channel = _CHANNEL_ID_RE.search(raw_line)
            # the pieces of one cut collective share its channel; a `shard_map`'s collectives all come with channel 1, so
            # the compiler's own number for the cut (`chain_id`, counted a computation) tells two of those apart
            chain = _CHAIN_ID_RE.search(raw_line)
            cut = (f"channel {channel.group(1)}" + (f" chain {chain.group(1)} in {wrapper.computation}" if chain else "")
                   if wrapper is not None and wrapper.fused and channel else None)
            phase_of = row_of.get(cut)
            if phase_of is not None:
                # the chip's compiler cuts one collective into fusions (a start, steps fused into compute, a done),
                # each with its own copy of the instruction on the collective's channel: one row, counted once
                if wrapper.done:
                    phase_of["done"] = wrapper.name
                else:
                    phase_of["steps"].append(wrapper.name)
                continue
            axis = _collective_axis(raw_line, mesh_axis_sizes)
            kind = opcode[: -len("-start")] if opcode.endswith("-start") else opcode
            if kind == "reduce-scatter":
                # counted by its operand, what a chip puts in, as a fused one is (the all-reduce inside the chip's
                # `all-reduce-scatter` fusion has the operand's shape): the output times the group. The line's other
                # shapes are not operands (the emitter's notes print `original_shape: ...`).
                groups = _parse_replica_groups(raw_line)
                nbytes = sum(b for pos, _, b in _line_shapes(rhs) if pos < opcode_pos) * (len(groups[0]) if groups else 1)
            bucket_name = f"collective:{axis}"
            est = nbytes / hw.collective_bw + hw.collective_latency_s
            if wrapper is not None and "reduce-scatter" in current_comp and kind == "all-reduce":
                kind = "reduce-scatter"  # the chip's form of one: an all-reduce and the slice of it, in one fusion
            row = {"name": wrapper.name if wrapper is not None else instr_name, "done": None, "steps": [], "kind": kind,
                   "axis": axis, "bytes": nbytes, "times": times_a_run.get(current_comp, 1),
                   "scope": scope_path(_op_name(raw_line) or (wrapper.op_name if wrapper is not None else None))}
            collectives.append(row)
            row_of[row["name"]] = row
            if cut is not None:
                row_of[cut] = row
        elif opcode in _HOST_OPS:
            bucket_name = "host_transfer"
            est = nbytes / hw.hbm_bw
        elif opcode in _MATMUL_OPS:
            bucket_name = "matmul"
            est = max(flops / hw.peak_flops, nbytes / hw.hbm_bw)
        elif opcode == "custom-call":
            target_m = _CUSTOM_TARGET_RE.search(raw_line)
            target = target_m.group(1) if target_m else ""
            if target in _ANNOTATION_CUSTOM_CALLS:
                continue  # SPMD annotation, not a kernel
            if "gemm" in target.lower() or "dot" in target.lower():
                bucket_name = "matmul"
            else:
                bucket_name = "custom_call"
            est = max(flops / hw.peak_flops, nbytes / hw.hbm_bw)
        elif opcode in _ELEMENTWISE_OPS or opcode == "fusion":
            bucket_name = "elementwise"
            est = max(flops / hw.peak_flops, nbytes / hw.hbm_bw)
        else:
            bucket_name = "other"
            est = nbytes / hw.hbm_bw

        b = _bucket(bucket_name)
        b["ops"] += 1
        b["flops"] += flops
        b["bytes"] += nbytes
        b["est_time_s"] += est
        b["top_ops"].append(
            {"op": f"{opcode} %{instr_name}", "flops": flops, "bytes": nbytes,
             "est_time_s": est}
        )
        # the same instruction once more, by the scope its metadata names: the
        # by-scope column closes on the module total exactly as the buckets do
        row = by_scope.setdefault(
            scope_path(_op_name(raw_line)), {"ops": 0, "flops": 0, "bytes": 0, "est_time_s": 0.0}
        )
        row["ops"] += 1
        row["flops"] += flops
        row["bytes"] += nbytes
        row["est_time_s"] += est

    for b in buckets.values():
        b["top_ops"] = sorted(b["top_ops"], key=lambda o: -o["est_time_s"])[:top_ops]
        b["est_time_s"] = round(b["est_time_s"], 12)
        for o in b["top_ops"]:
            o["est_time_s"] = round(o["est_time_s"], 12)

    # module total = sum of buckets, BY CONSTRUCTION (the closure the tier-1
    # test pins): every counted instruction incremented exactly one bucket
    total = {
        "ops": sum(b["ops"] for b in buckets.values()),
        "flops": sum(b["flops"] for b in buckets.values()),
        "bytes": sum(b["bytes"] for b in buckets.values()),
        "est_time_s": round(sum(b["est_time_s"] for b in buckets.values()), 12),
    }
    return {
        "module": module_name,
        "mesh_axes": dict(mesh_axis_sizes or {}),
        "hw": hw.as_dict(),
        "buckets": {k: buckets[k] for k in sorted(buckets)},
        "by_scope": {k: {**v, "est_time_s": round(v["est_time_s"], 12)} for k, v in sorted(by_scope.items())},
        "collectives": collectives,
        "total": total,
    }


def perfscope_from_compiled(
    compiled, mesh_axis_sizes: Optional[dict[str, int]] = None,
    hw: Optional[HwSpec] = None,
) -> dict:
    """Report for one `jax.stages.Compiled` executable: the optimized-HLO walk
    plus XLA's own cost analysis as an independent cross-check column."""
    report = analyze_hlo_text(compiled.as_text(), mesh_axis_sizes, hw)
    try:
        cost = compiled.cost_analysis()
        if isinstance(cost, (list, tuple)):  # some jaxlibs return one dict per device
            cost = cost[0] if cost else {}
        report["xla_cost_analysis"] = {
            k: float(v) for k, v in cost.items()
            if k in ("flops", "bytes accessed", "optimal_seconds")
        }
    except Exception as e:  # cost analysis is a bonus column, never a failure
        report["xla_cost_analysis"] = {"error": repr(e)}
    return report


def write_report(report: dict, path: Union[str, Path]) -> Path:
    path = Path(path)
    path.parent.mkdir(parents=True, exist_ok=True)
    tmp = path.with_suffix(path.suffix + ".tmp")
    with open(tmp, "w") as f:
        json.dump(report, f, indent=1)
    tmp.rename(path)
    return path


SCOPE_ROWS = 24  # scopes printed by est. time before the rest is summed into one row


def format_perfscope_table(report: dict) -> str:
    """Aligned text table for one or many module reports ({"executables": ...}
    or a single analyze_hlo_text result)."""
    modules = report.get("executables") or {report.get("module") or "module": report}
    lines = []
    for name, mod in modules.items():
        total = mod["total"]
        lines.append(
            f"{name}: {total['ops']} ops, {total['flops'] / 1e9:.3f} GFLOP, "
            f"{total['bytes'] / 1e6:.3f} MB, est {total['est_time_s'] * 1e3:.4f} ms"
        )
        lines.append(f"  {'bucket':<24} {'ops':>6} {'GFLOP':>10} {'MB':>10} {'est ms':>10} {'share':>7}")
        for bucket, b in sorted(
            mod["buckets"].items(), key=lambda kv: -kv[1]["est_time_s"]
        ):
            share = b["est_time_s"] / total["est_time_s"] if total["est_time_s"] else 0.0
            lines.append(
                f"  {bucket:<24} {b['ops']:>6} {b['flops'] / 1e9:>10.3f} "
                f"{b['bytes'] / 1e6:>10.3f} {b['est_time_s'] * 1e3:>10.4f} {share:>6.1%}"
            )
        xla = mod.get("xla_cost_analysis") or {}
        if "flops" in xla:
            lines.append(
                f"  xla cost_analysis cross-check: {xla['flops'] / 1e9:.3f} GFLOP, "
                f"{xla.get('bytes accessed', 0.0) / 1e6:.3f} MB"
            )
        scoped = sorted((mod.get("by_scope") or {}).items(), key=lambda kv: -kv[1]["est_time_s"])
        if scoped:
            lines.append(f"  {'scope (telemetry/scopes.py)':<72} {'ops':>6} {'est ms':>10} {'share':>7}")
            for scope, b in scoped[:SCOPE_ROWS]:
                share = b["est_time_s"] / total["est_time_s"] if total["est_time_s"] else 0.0
                lines.append(f"  {scope[-72:]:<72} {b['ops']:>6} {b['est_time_s'] * 1e3:>10.4f} {share:>6.1%}")
            rest = scoped[SCOPE_ROWS:]
            if rest:
                est = sum(b["est_time_s"] for _, b in rest)
                lines.append(f"  {f'({len(rest)} more scopes)':<72} {sum(b['ops'] for _, b in rest):>6} {est * 1e3:>10.4f} "
                             f"{est / total['est_time_s'] if total['est_time_s'] else 0.0:>6.1%}")
        lines.append("")
    return "\n".join(lines).rstrip()


# --------------------------------------------------- train-step report (config)


def perfscope_for_config(
    config_file_path: Union[str, Path],
    warmstart_checkpoint_folder: Optional[str] = None,
    hw: Optional[HwSpec] = None,
) -> dict:
    """Build the recipe's train step over its real mesh (virtual CPU devices
    suffice), lower + compile it, and return the perfscope report. Requires
    jax.device_count() >= the config's world_size — same contract as
    utils/recipe_validation.validate_recipe, and the same build path."""
    from modalities_tpu.utils.recipe_validation import build_lowered_train_step

    built = build_lowered_train_step(
        Path(config_file_path), warmstart_checkpoint_folder=warmstart_checkpoint_folder
    )
    mesh_axis_sizes = {k: int(v) for k, v in built.mesh_handle.mesh.shape.items()}
    report = perfscope_from_compiled(built.lowered.compile(), mesh_axis_sizes, hw)
    return {
        "config": str(config_file_path),
        "world_size": built.world_size,
        "executables": {"train_step": report},
    }


def run_perfscope_subprocess(
    config_file_path: Union[str, Path],
    warmstart_checkpoint_folder: Optional[str] = None,
) -> dict:
    """Re-exec `python -m modalities_tpu.telemetry.perfscope` with the CPU
    backend forced and world_size virtual devices — works from any ambient
    environment (one whose JAX already claimed a TPU, or has too few devices)."""
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
            "world_size — perfscope needs it to size the virtual device pool"
        ) from e

    env = os.environ.copy()
    env["JAX_PLATFORMS"] = "cpu"
    flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "", env.get("XLA_FLAGS", ""))
    env["XLA_FLAGS"] = (flags + f" --xla_force_host_platform_device_count={world_size}").strip()

    cmd = [sys.executable, "-m", "modalities_tpu.telemetry.perfscope", str(config_file_path)]
    if warmstart_checkpoint_folder:
        cmd += ["--warmstart_checkpoint_folder", warmstart_checkpoint_folder]
    proc = subprocess.run(cmd, env=env, capture_output=True, text=True)
    if proc.returncode != 0:
        raise RuntimeError(
            f"perfscope failed for {config_file_path} (exit {proc.returncode}):\n"
            f"{proc.stdout[-2000:]}\n{proc.stderr[-4000:]}"
        )
    return json.loads(proc.stdout.strip().splitlines()[-1])


# ------------------------------------------------------------- profiler windows


class ProfileWindow:
    """Programmatic `jax.profiler` capture armed by env var: start an xplane
    trace right before step N and stop it after K steps, no code edits.

    `MODALITIES_TPU_PROFILE_AT_STEP=N` (one step) or `N:K` (K steps);
    `MODALITIES_TPU_PROFILE_DIR` overrides the output folder (default: the
    `fallback_dir` the trainer passes, its telemetry folder). Both hooks are
    cheap no-ops outside the window, and a profiler failure is logged, never
    raised — observability must not take a run down."""

    def __init__(self, start_step: int, num_steps: int = 1, out_dir: Optional[Path] = None):
        if num_steps < 1:
            raise ValueError(f"profile window needs num_steps >= 1, got {num_steps}")
        self.start_step = int(start_step)
        self.num_steps = int(num_steps)
        self.out_dir = Path(out_dir) if out_dir is not None else None
        self.active = False
        self.completed = False

    @classmethod
    def from_env(cls, fallback_dir: Optional[Path] = None) -> Optional["ProfileWindow"]:
        raw = os.environ.get("MODALITIES_TPU_PROFILE_AT_STEP", "").strip()
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
                f"MODALITIES_TPU_PROFILE_AT_STEP={raw!r}: expected N or N:K "
                "(capture K steps starting at step N)"
            ) from e
        out = os.environ.get("MODALITIES_TPU_PROFILE_DIR")
        out_dir = Path(out) if out else fallback_dir
        return cls(start, num, out_dir)

    def maybe_start(self, step_id: int) -> bool:
        """Call before dispatching `step_id`; starts the trace on the window's
        first step. Returns True if capture is running."""
        if self.active:
            return True
        if self.completed or step_id != self.start_step:
            return False
        try:
            import jax

            out_dir = self.out_dir or Path(os.getcwd()) / "profile"
            out_dir.mkdir(parents=True, exist_ok=True)
            jax.profiler.start_trace(str(out_dir))
            self.active = True
            logger.info(
                "perfscope: profiler capture started at step %d for %d step(s) -> %s",
                step_id, self.num_steps, out_dir,
            )
        except Exception:
            logger.exception("perfscope: profiler start failed; window disabled")
            self.completed = True
        return self.active

    def maybe_stop(self, step_id: int, block_on=None) -> bool:
        """Call after `step_id` completed; stops the trace once the window's
        last step is done. Returns True if capture stopped on this call.

        `block_on`: optional pytree of arrays to `block_until_ready` before
        stopping, so the async-dispatched device work of the captured steps is
        actually in the trace (dispatch returns long before execution)."""
        if not self.active or step_id < self.start_step + self.num_steps - 1:
            return False
        try:
            import jax

            if block_on is not None:
                jax.block_until_ready(block_on)
            jax.profiler.stop_trace()
            logger.info("perfscope: profiler capture stopped after step %d", step_id)
        except Exception:
            logger.exception("perfscope: profiler stop failed")
        self.active = False
        self.completed = True
        return True


# ----------------------------------------------------------- anomaly detection


@dataclass
class Anomaly:
    value: float
    zscore: float
    ewma: float
    is_anomaly: bool


class AnomalyDetector:
    """Rolling robust z-score + EWMA over a univariate stream (per-step wall
    time, per-bucket goodput seconds). Robust z = 0.6745 * (v - median) / MAD —
    outliers in the window don't inflate their own yardstick the way a plain
    stdev z does. No verdicts until `min_history` observations; a zero MAD
    (constant window) scores any deviation as `inf`."""

    def __init__(
        self,
        window: int = 64,
        zscore_threshold: float = 6.0,
        min_history: int = 8,
        ewma_alpha: float = 0.2,
    ):
        if window < 2:
            raise ValueError(f"anomaly window must be >= 2, got {window}")
        self.window: deque[float] = deque(maxlen=int(window))
        self.zscore_threshold = float(zscore_threshold)
        self.min_history = max(2, int(min_history))
        self.ewma_alpha = float(ewma_alpha)
        self.ewma: Optional[float] = None
        self.anomalies = 0

    def observe(self, value: float) -> Anomaly:
        value = float(value)
        self.ewma = (
            value if self.ewma is None
            else self.ewma_alpha * value + (1.0 - self.ewma_alpha) * self.ewma
        )
        z = 0.0
        if len(self.window) >= self.min_history:
            med = statistics.median(self.window)
            mad = statistics.median(abs(v - med) for v in self.window)
            dev = value - med
            if mad > 0.0:
                z = 0.6745 * dev / mad
            elif dev != 0.0:
                z = math.copysign(math.inf, dev)
        is_anomaly = z > self.zscore_threshold  # one-sided: slow is the anomaly
        if is_anomaly:
            self.anomalies += 1
        self.window.append(value)
        return Anomaly(value=value, zscore=z, ewma=self.ewma, is_anomaly=is_anomaly)


# ---------------------------------------------------------- subprocess entry


def _main() -> None:
    import argparse

    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("config_file_path", type=Path)
    parser.add_argument("--warmstart_checkpoint_folder", default=None)
    args = parser.parse_args()
    report = perfscope_for_config(
        args.config_file_path,
        warmstart_checkpoint_folder=args.warmstart_checkpoint_folder,
    )
    print(json.dumps(report))


if __name__ == "__main__":
    _main()
