"""Device time by scope: a trace's operations (benchmark/xtrace.py) joined to the scope
path each instruction carries (`op_name`, the vocabulary in
modalities_tpu/telemetry/scopes.py), and sorted into buckets by rules that are data
(benchmark/scopes/<rules>.json).

A table maps an instruction's name — what opens a device event's name,
`%fusion.168 = bf16[...] fusion(...)` -> `fusion.168` — to its scope path. It comes
from the profile itself (`table_from_profile`): on a TPU the xplane's device plane
carries, in each operation's metadata, the `op_name` of the program that ran, so a
traced run reads the scopes of the executable it timed, whatever a compile cache
handed it. The same table from a program that is not traced is its `scope_table`
(modalities_tpu/telemetry/perfscope.py); benchmark/tools/describe_scopes.py takes either.

Rules are two ordered lists of `[regular expression, bucket]`, one for the pass and one
for the component. An event goes to the bucket of the first rule that matches its scope
path, in each list; the last rule of each list takes every path and is named
`unattributed`. An instruction the table does not hold (what the compiler put in
itself carries no `op_name`: copies between memory spaces, their starts and dones) gets
the path `(no op_name)/<instruction without its number>`, so that a rule can name what
is known about it and a table from another program reads as unattributed. So each list
is a partition of the events, and its buckets add up to the operations' own time over
the same executions, which is the device's busy time there (a loop's event contains
its body's events: own time is what the loop adds).

Time is counted over the whole executions of the step program inside the trace: an
execution cut by the trace's edge is left out, so that a number per execution is a
number per step.
"""

from __future__ import annotations

import json
import re
from bisect import bisect_left
from dataclasses import dataclass
from pathlib import Path

from benchmark import xtrace
from benchmark.stats import union_length

UNATTRIBUTED = "unattributed"
LISTS = ("pass", "component")
_INSTRUCTION = re.compile(r"^%?([^\s=(]+)")


def instruction_of(event_name: str) -> str:
    """`%fusion.168 = bf16[...] fusion(...)` -> `fusion.168`: the key of a scope table."""
    found = _INSTRUCTION.match(event_name)
    return found.group(1) if found else event_name


def load_rules(path: Path) -> dict[str, list[tuple[re.Pattern, str]]]:
    """The two ordered lists of a rules file, compiled. Each list has to end in a rule
    that takes every path and is named `unattributed`: that is what closes it."""
    raw = json.loads(Path(path).read_text())
    rules = {}
    for name in LISTS:
        rows = [(re.compile(pattern), bucket) for pattern, bucket in raw[name]]
        if not rows or rows[-1][1] != UNATTRIBUTED or rows[-1][0].pattern != "":
            raise SystemExit(f"benchmark: the list {name!r} of {path} has to end in [\"\", \"{UNATTRIBUTED}\"]")
        rules[name] = rows
    return rules


NO_OP_NAME = "(no op_name)"


def path_of(event: xtrace.Event, table: dict[str, str]) -> str:
    """The scope path of an event's instruction, or `(no op_name)/<its label>` (the instruction without its number)."""
    return table.get(instruction_of(event.name)) or f"{NO_OP_NAME}/{xtrace.op_label(event)}"


def bucket_of(path: str, rows: list[tuple[re.Pattern, str]]) -> str:
    """The bucket of the first rule that matches `path`."""
    for pattern, bucket in rows:
        if pattern.search(path):
            return bucket
    return UNATTRIBUTED


def whole_runs(device: xtrace.DeviceTrace, pattern: str) -> tuple[list[xtrace.Event], int]:
    """The executions of the programs named like `pattern` that the trace holds whole,
    and how many it holds in all. A program runs the same operations every time, so
    a whole execution is one that holds as many operation events as the fullest: one
    that the trace's edge cut holds fewer."""
    wanted = re.compile(pattern)
    runs = [m for m in device.modules if wanted.search(m.name)]
    starts = sorted(e.start for e in device.ops)
    counts = [bisect_left(starts, run.end) - bisect_left(starts, run.start) for run in runs]
    most = max(counts, default=0)
    return [run for run, count in zip(runs, counts) if count == most and most > 0], len(runs)


@dataclass
class ScopeTime:
    executions: int  # whole executions of the step program the numbers are over (per device)
    seen: int  # executions in the trace, the cut ones among them
    busy_s: float  # seconds in which an operation ran inside those executions, per execution
    lists: dict[str, dict[str, float]]  # list -> bucket -> own seconds per execution
    unattributed_s: float  # own seconds per execution of the events either list leaves unattributed
    scopes: dict[str, float]  # scope path -> own seconds per execution
    events: int  # operation events per execution
    missing: int  # of them, those whose instruction the table does not hold


def scope_time(trace: xtrace.Trace, table: dict[str, str], rules: dict, program: str) -> ScopeTime:
    """Own device seconds per execution of `program`, by the buckets of both lists,
    averaged over the devices."""
    lists = {name: {} for name in LISTS}
    scopes: dict[str, float] = {}
    executions = seen = events = missing = 0
    busy = unattributed = 0.0
    for device in trace.devices:
        runs, in_all = whole_runs(device, program)
        seen += in_all
        executions += len(runs)
        edges = sorted((run.start, run.end) for run in runs)
        inside = []
        for event, own in xtrace.self_seconds(device.ops):
            if any(start <= event.start < end for start, end in edges):
                inside.append(event)
                path = path_of(event, table)
                events += 1
                missing += path.startswith(NO_OP_NAME)
                buckets = {name: bucket_of(path, rules[name]) for name in LISTS}
                for name, bucket in buckets.items():
                    lists[name][bucket] = lists[name].get(bucket, 0.0) + own
                if UNATTRIBUTED in buckets.values():
                    unattributed += own
                scopes[path] = scopes.get(path, 0.0) + own
        busy += union_length((e.start, e.end) for e in inside)
    if not executions:
        raise SystemExit(f"benchmark: the trace holds no whole execution of a program named like {program!r} "
                         f"({seen} cut by its edges): nothing to count time by scope over")
    per = 1.0 / executions
    return ScopeTime(
        executions=executions // len(trace.devices), seen=seen // len(trace.devices), busy_s=busy * per,
        lists={name: {bucket: s * per for bucket, s in sorted(buckets.items(), key=lambda kv: -kv[1])}
               for name, buckets in lists.items()},
        unattributed_s=unattributed * per, scopes={k: v * per for k, v in scopes.items()},
        events=events // executions, missing=missing // executions,
    )


def describe(found: ScopeTime, top: int = 30) -> str:
    """The whole table of buckets, and the largest scopes, as a run's log prints them."""
    ms = 1e3
    lines = [f"[scope] {found.executions} whole execution(s) of {found.seen} in the trace; per execution: busy "
             f"{found.busy_s * ms:.3f} ms in {found.events} events, {found.missing} of them not in the table"]
    for name, buckets in found.lists.items():
        total = sum(buckets.values())
        lines.append(f"[scope] by {name}: sum {total * ms:.3f} ms, gap to busy {abs(total - found.busy_s) * ms:.6f} ms "
                     f"({abs(total - found.busy_s) / found.busy_s:.2e} of it)")
        for bucket, seconds in buckets.items():
            lines.append(f"[scope]   {bucket:<20} {seconds * ms:>10.3f} ms {seconds / found.busy_s:>7.2%}")
    lines.append(f"[scope] unattributed in either list: {found.unattributed_s * ms:.3f} ms "
                 f"({found.unattributed_s / found.busy_s:.2%} of busy)")
    if top:
        lines.append(f"[scope] the {top} largest scopes:")
        for path, seconds in sorted(found.scopes.items(), key=lambda kv: -kv[1])[:top]:
            lines.append(f"[scope]   {seconds * ms:>9.3f} ms  {path}")
    return "\n".join(lines)


# ------------------------------------------------------------------ where a table comes from


def table_from_profile(xplane: Path, program: str | None = None) -> dict[str, str] | None:
    """{instruction: op_name} as the profile itself holds it: on a device plane every
    operation's metadata names its instruction (`display_name`) and carries the
    `op_name` of its HLO metadata as the stat `tf_op`, exactly as the device ran it.
    With `program`, only the operations of the programs named like it. None where no
    operation carries one, or where what parses an xplane's metadata is not installed
    (`jax.profiler.ProfileData` gives an event's own stats and not its metadata's)."""
    try:
        from tensorflow.tsl.profiler.protobuf import xplane_pb2
    except ImportError:
        return None
    space = xplane_pb2.XSpace()
    space.ParseFromString(Path(xplane).read_bytes())
    wanted = re.compile(program) if program else None
    table: dict[str, str] = {}
    for plane in space.planes:
        if not xtrace.DEVICE_PLANE.match(plane.name):
            continue
        names = {key: meta.name for key, meta in plane.stat_metadata.items()}
        programs = {}
        if wanted is not None:  # program id -> name, from the line of executed programs
            for line in plane.lines:
                if line.name == xtrace.MODULES_LINE:
                    for event in line.events:
                        name = plane.event_metadata[event.metadata_id].name  # jit_train_step(<program id>)
                        programs[name[name.rfind("(") + 1:-1]] = name
        for meta in plane.event_metadata.values():
            stats = {names.get(stat.metadata_id): stat for stat in meta.stats}
            if "tf_op" not in stats:
                continue
            if wanted is not None:
                program_id = _stat_value(stats.get("program_id"), names)
                if not wanted.search(programs.get(str(program_id), "")):
                    continue
            op_name = str(_stat_value(stats["tf_op"], names)).rstrip(":")
            if op_name:
                table[meta.display_name or instruction_of(meta.name)] = op_name
    return table or None


def _stat_value(stat, names: dict):
    if stat is None:
        return None
    kind = stat.WhichOneof("value")
    value = getattr(stat, kind)
    return names.get(value, "") if kind == "ref_value" else value
