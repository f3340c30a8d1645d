"""The collectives of a compiled program, as the compiler placed them: which instruction,
of which kind, over which mesh axis, how many bytes, for which scope.

Under GSPMD nobody writes a step's collectives: the partitioner inserts them, and each
inherits the `op_name` of the operation that forced it (an all-gather of a weight that
`dp_shard` splits carries `.../blocks/block/mlp/W/dot_general`). So the compiled step's
optimized HLO is the one place that says what moves between chips and why, and a device
trace names the same instructions. `plan_from_hlo_text` keeps that as rows
(`perfscope.analyze_hlo_text` walks the module once, for its buckets and for these):

    name    the instruction as a trace prints it: `all-reduce.93`, `all-gather-start.7`, or the
            wrapper the chip's compiler put round the collective (`fusion.225` round an
            all-reduce and its slice, `async-collective-start.3`)
    done    the instruction that completes an asynchronous one (`all-gather-done.7`,
            `async-collective-done.3`), else None
    steps   compute fusions that carry the collective's steps between the two: the core's time
            there is compute, and the collective is under way meanwhile
    kind    all-gather | all-reduce | reduce-scatter | all-to-all | collective-permute | ...
    axis    the mesh axis or axes, by the geometry of the replica groups (`tp`, `dp_shard+tp`)
    bytes   of one execution, as the `collective:<axis>` buckets of perfscope count them
    times   executions in one run of the module (the trip counts of the loops round it)
    scope   `telemetry/scopes.scope_path` of the `op_name`

`PROCESS_PLANS` is the process's own record, beside `spans.PROCESS_LOG` and
`compile_log.PROCESS_COMPILES` and on the same pattern: one entry a compiled program,
kept whoever listens, forwarded to the active `Telemetry` (event `collective_plan`,
gauges `train_collective_bytes` / `train_collective_count`), readable by a run that
opens no sink (`benchmark/readers/collectives.py`). The trainer records the train step's
in its preflight, inside the span `collective_plan`, and only on a mesh of more than one
device: a program on one device holds no collective, and its set-up pays for no walk.
"""

from __future__ import annotations

import time
from collections import deque
from typing import Callable, Optional

from modalities_tpu.utils.logging import get_logger

logger = get_logger(__name__)

PLANS_CAPACITY = 64  # a run compiles a handful of sharded programs
LARGEST_ROWS = 10  # rows the sink's event names one by one

PROCESS_PLANS: deque[dict] = deque(maxlen=PLANS_CAPACITY)
_forward: Optional[Callable[[dict], None]] = None


def forward_to(on_plan: Optional[Callable[[dict], None]]) -> None:
    """Who, beside the process's record, is told of each plan (the active `Telemetry`)."""
    global _forward
    _forward = on_plan


def totals(rows: list[dict]) -> dict[str, dict]:
    """{"<axis>|<kind>": count of instructions, bytes of one execution of each, executions and bytes a run of the module}."""
    out: dict[str, dict] = {}
    for row in rows:
        total = out.setdefault(f"{row['axis']}|{row['kind']}", {"count": 0, "bytes": 0, "count_a_run": 0, "bytes_a_run": 0})
        total["count"] += 1
        total["bytes"] += row["bytes"]
        total["count_a_run"] += row["times"]
        total["bytes_a_run"] += row["bytes"] * row["times"]
    return dict(sorted(out.items()))


def plan_from_hlo_text(hlo_text: str, mesh_axis_sizes: Optional[dict[str, int]]) -> dict:
    from modalities_tpu.telemetry.perfscope import HwSpec, analyze_hlo_text

    report = analyze_hlo_text(hlo_text, mesh_axis_sizes)
    rows = report["collectives"]
    hw = HwSpec()
    return {
        "module": report["module"], "mesh_axes": report["mesh_axes"], "rows": rows, "totals": totals(rows),
        "bytes_a_run": sum(row["bytes"] * row["times"] for row in rows),
        # the cost model's guess, beside which a trace's exposed share is printed: bytes over one link's rate, no overlap
        "est_seconds_a_run": sum(row["times"] * (row["bytes"] / hw.collective_bw + hw.collective_latency_s) for row in rows),
    }


def record_from_compiled(compiled, mesh_axis_sizes: Optional[dict[str, int]]) -> dict:
    """The plan of one `jax.stages.Compiled`, kept in the process's record and handed on."""
    plan = plan_from_hlo_text(compiled.as_text(), mesh_axis_sizes)
    plan["at"] = time.perf_counter()
    PROCESS_PLANS.append(plan)
    logger.info(
        "collective plan of %s on %s: %d collectives, %.3f GB a run; by axis and kind: %s", plan["module"], plan["mesh_axes"],
        len(plan["rows"]), plan["bytes_a_run"] / 1e9,
        ", ".join(f"{key} {t['count']} ({t['bytes_a_run'] / 1e9:.3f} GB)" for key, t in plan["totals"].items()) or "none")
    if _forward is not None:
        _forward(plan)
    return plan


def event_payload(plan: dict) -> dict:
    """What the sink's `collective_plan` event holds: the totals, and the largest rows by bytes a run with their scope."""
    largest = sorted(plan["rows"], key=lambda row: -row["bytes"] * row["times"])[:LARGEST_ROWS]
    return {"module": plan["module"], "mesh_axes": dict(plan["mesh_axes"]), "collectives": len(plan["rows"]),
            "bytes_a_run": plan["bytes_a_run"], "est_seconds_a_run": round(plan["est_seconds_a_run"], 9), "totals": plan["totals"],
            "largest": [{k: row[k] for k in ("name", "kind", "axis", "bytes", "times", "scope")} for row in largest]}
