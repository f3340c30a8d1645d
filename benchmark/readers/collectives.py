"""The trace read against the program's own record of its collectives: how long a collective was under way on each
device, how much of that the core spent on nothing else, by mesh axis, and how idle the idlest device was.

The program's record is `modalities_tpu.telemetry.collective_plan.PROCESS_PLANS` (one entry a compiled program: the
trainer's preflight records the train step's on a mesh of several devices), looked up among the modules the run has
imported: the reader imports nothing of the program. A row of a plan names a collective as a device trace prints it
(`all-reduce.93`, `fusion.225` round an all-reduce and its slice, `async-collective-start.3` with its `done` and the
compute fusions that carry its `steps`), with its kind, mesh axis, bytes, times a run and scope. Joined by instruction name
inside the executions of the plan's module (`jit_train_step`); an instruction number means nothing in another program.

On a device, with `xtrace.exposed_collective_seconds`' definitions and `benchmark/stats.py`'s interval arithmetic:

    under way   the core is inside the collective's own instruction (a synchronous one, a start, a done), or the
                collective is in flight beside the core: from its start's begin to its done's end, and whatever the
                "Async XLA Ops" line shows under its name
    compute     every other operation the core ran (a fusion that carries a collective's steps is compute)
    exposed     under way and no compute: what overlap did not hide

`key` names what a metric reads, over the stretch every device was traced in (`common_window`): `exposed_pct` and
`in_flight_pct` (mean of the devices; with `axis`, the plan's rows on that axis alone), `gb_per_step` (the plan's bytes a
run of the module: a count, no trace needed but the module's name), `idle_max_pct` (the idlest device's 1 - busy).

A run without a plan (a commit from before the record, or a program on one device) falls back to the events
`xtrace.COLLECTIVE` names: the two shares over all collectives are still read, the by-axis ones and the bytes are not.
An event that looks like a collective and is in no row, or a row no event matches, is counted and printed
(`[mesh] unmatched`); where more than 2% of the collectives' seconds are unmatched the by-axis metrics are missing,
not guessed. The first metric of a run prints the `[mesh]` table and keeps the result with what the run observed.
"""

from __future__ import annotations

import re
import sys
from bisect import bisect_left, bisect_right

from benchmark import xtrace
from benchmark.stats import merge, subtract, union_length
from benchmark.xscope import instruction_of

UNMATCHED_LIMIT = 0.02  # of the collectives' seconds
WRAPPED = re.compile(r"async-collective|all-reduce-scatter")  # what the chip's compiler names a wrapped collective, beside xtrace.COLLECTIVE
_CALLS = re.compile(r"calls=%?([\w.\-]+)")
BOTH = "(all)"


def process_plans():
    """The process's plans, or None where the program keeps no such record."""
    return getattr(sys.modules.get("modalities_tpu.telemetry.collective_plan"), "PROCESS_PLANS", None)


def plan_for(trace: xtrace.Trace, plans) -> dict | None:
    """The newest plan whose module the trace holds executions of."""
    ran = {m.name for d in trace.devices for m in d.modules}
    for plan in reversed(list(plans or [])):
        if plan.get("rows") and any(name.startswith(plan["module"]) for name in ran):
            return plan
    return None


def _own_names(event_name: str) -> str:
    """Of an event's name, which is its whole HLO instruction, what names the instruction itself: its own name and the
    computation it calls. The operands' names are left out: a fusion that consumes `%async-collective-done.4` is no collective."""
    called = _CALLS.search(event_name)
    return f"{instruction_of(event_name)} {called.group(1) if called else ''}"


def _minus(intervals, cover) -> list[tuple[float, float]]:
    """`stats.subtract`, handed for each interval only the stretch of `cover` that can touch it (both merged): a device's
    compute is ten thousand intervals a step, and the whole of it for each of a row's hundred costs seconds a row."""
    starts, ends = [start for start, _ in cover], [end for _, end in cover]
    out = []
    for start, end in intervals:
        out.extend(subtract([(start, end)], cover[bisect_right(ends, start):bisect_left(starts, end)]))
    return out


def _inside(events, runs):
    """The events that start inside one of `runs` (merged intervals)."""
    return [e for e in events if any(start <= e.start < end for start, end in runs)]


def _paired(starts, dones):
    """In-flight intervals of an asynchronous collective: each start with the first done that begins after it."""
    out, dones = [], sorted(dones, key=lambda e: e.start)
    for start in sorted(starts, key=lambda e: e.start):
        while dones and dones[0].start < start.start:
            out.append((dones[0].start, dones[0].end))  # a done whose start the trace's edge cut
            dones.pop(0)
        out.append((start.start, dones.pop(0).end) if dones else (start.start, start.end))
    return out + [(d.start, d.end) for d in dones]


def device_reading(device: xtrace.DeviceTrace, plan: dict | None, window: tuple[float, float]) -> dict:
    """One device's seconds: busy, and per group of rows ("(all)", each axis, each row) under way and exposed."""
    leaves = xtrace.leaf_events(device.ops)
    runs = merge((m.start, m.end) for m in device.modules if plan is None or m.name.startswith(plan["module"]))
    ops_in, async_in = (_inside(device.ops, runs), _inside(device.async_ops, runs)) if plan is not None else (device.ops, device.async_ops)
    by_name: dict[str, list] = {}  # every operation event, a loop or a wrapper with events inside it too
    for event in ops_in:
        by_name.setdefault(instruction_of(event.name), []).append(event)
    async_by_name: dict[str, list] = {}
    for event in async_in:
        async_by_name.setdefault(instruction_of(event.name), []).append(event)

    looks_like = lambda e: bool(xtrace.COLLECTIVE.search(xtrace.op_label(e)) or WRAPPED.search(_own_names(e.name)))  # noqa: E731
    rows = plan["rows"] if plan is not None else []
    flight: dict[str, list] = {}  # row name -> intervals in which the collective is under way
    own: dict[str, list] = {}  # row name -> intervals in which the core is inside the row's own instructions
    unmatched_rows = []
    for row in rows:
        names = [row["name"], *([row["done"]] if row["done"] else [])]
        starts, dones = by_name.get(row["name"], []), by_name.get(row["done"], []) if row["done"] else []
        own[row["name"]] = [(e.start, e.end) for e in starts + dones]
        flight[row["name"]] = ((_paired(starts, dones) if row["done"] else list(own[row["name"]]))
                               + [(e.start, e.end) for name in names for e in async_by_name.get(name, [])])
        if not flight[row["name"]]:
            unmatched_rows.append(row["name"])
    core_names = {name for row in rows for name in (row["name"], row["done"]) if name}
    # what the trace nests inside a row's own event (a wrapper's inner collective) is part of it: neither compute nor a stranger
    core = merge(i for intervals in own.values() for i in intervals)
    core_starts = [start for start, _ in core]

    def within_core(e) -> bool:
        at = bisect_right(core_starts, e.start) - 1
        return at >= 0 and e.end <= core[at][1]

    others = [e for e in leaves if instruction_of(e.name) not in core_names and not within_core(e)]
    in_a_run = (lambda e: any(start <= e.start < end for start, end in runs)) if plan is not None else (lambda e: True)  # noqa: E731
    on_the_core = [e for e in others if looks_like(e) and in_a_run(e)]
    strangers = on_the_core + [e for e in async_in if looks_like(e) and instruction_of(e.name) not in core_names]
    if plan is None:  # no record: the events the old pattern names, all in one group
        flight[BOTH], own[BOTH] = [(e.start, e.end) for e in strangers], [(e.start, e.end) for e in on_the_core]
        names = {instruction_of(e.name) for e in on_the_core}
        others, strangers = [e for e in others if instruction_of(e.name) not in names], []
    compute = merge((e.start, e.end) for e in others)
    every_own = merge(i for intervals in own.values() for i in intervals)

    def seconds(members: list[str]) -> tuple[float, float]:
        """(under way, exposed) of a group of rows. An instant is exposed where the core is inside one of the group's own
        instructions, or one of them is in flight while the core runs no compute and is inside no OTHER group's collective:
        a gather in flight while the core waits in another axis's all-reduce is that all-reduce's exposure, so the axes'
        parts add up to the whole (but where collectives of two groups are in flight over an idle core at once)."""
        under = merge(i for name in members for i in flight[name])
        theirs = merge(i for name in members for i in own[name])
        elsewhere = _minus(every_own, theirs)  # the core runs one operation at a time: what is not theirs is another group's
        return union_length(under), union_length(merge([*theirs, *_minus(_minus(under, compute), elsewhere)]))

    groups = {BOTH: list(flight)}
    for row in rows:
        groups.setdefault(row["axis"], []).append(row["name"])
    return {"ordinal": device.ordinal, "busy_s": union_length((e.start, e.end) for e in device.ops),
            "groups": {name: seconds(members) for name, members in groups.items()},
            "rows": {name: seconds([name]) for name in flight if name != BOTH},
            "events": {row["name"]: len(by_name.get(row["name"], [])) for row in rows},
            "unmatched_rows": unmatched_rows, "unmatched_s": union_length((e.start, e.end) for e in strangers),
            "unmatched_names": sorted({instruction_of(e.name) for e in strangers}),
            "module_runs": len([m for m in device.modules if plan is not None and m.name.startswith(plan["module"])]),
            "idle_gaps": subtract([window], merge((e.start, e.end) for e in device.ops))}


def common_window(trace: xtrace.Trace) -> tuple[float, float]:
    """From the moment the last device's first operation began to the moment the first device's last one ended: the
    stretch EVERY device was traced in. The profiler starts and stops inside a step and reaches the devices a few
    milliseconds apart (28 ms in the cell's first trace), so over `trace.window`, first to last thing ANY device did, the
    device it reached last reads as idle at the edge, and a straggler could not be told from the profiler's own skew."""
    return (max(min(e.start for e in d.ops) for d in trace.devices if d.ops),
            min(max(e.end for e in d.ops) for d in trace.devices if d.ops))


def clipped(device: xtrace.DeviceTrace, start: float, end: float) -> xtrace.DeviceTrace:
    """`device` with every event cut to [start, end] and what lies outside dropped."""
    cut = lambda events: [xtrace.Event(e.name, max(e.start, start), min(e.end, end), e.thread)  # noqa: E731
                          for e in events if e.end > start and e.start < end]
    return xtrace.DeviceTrace(device.ordinal, cut(device.ops), cut(device.modules), cut(device.async_ops))


def reading(trace: xtrace.Trace, plan: dict | None) -> dict:
    """Every number the metrics and the table take, from a trace and the plan of the module that ran (or None)."""
    start, end = common_window(trace)
    window = end - start
    devices = [device_reading(clipped(d, start, end), plan, (start, end)) for d in trace.devices]
    n = len(devices)
    mean = lambda name, k: sum(d["groups"].get(name, (0.0, 0.0))[k] for d in devices) / n  # noqa: E731
    axes = sorted({row["axis"] for row in plan["rows"]}) if plan is not None else []
    matched_s = mean(BOTH, 0)
    unmatched_s = sum(d["unmatched_s"] for d in devices) / n
    unmatched_share = unmatched_s / (matched_s + unmatched_s) if matched_s + unmatched_s > 0 else 0.0
    return {
        "window_s": window, "devices": devices, "plan": plan, "axes": axes,
        "in_flight_pct": 100.0 * mean(BOTH, 0) / window, "exposed_pct": 100.0 * mean(BOTH, 1) / window,
        "by_axis": {axis: {"in_flight_pct": 100.0 * mean(axis, 0) / window, "exposed_pct": 100.0 * mean(axis, 1) / window} for axis in axes},
        "idle_max_pct": 100.0 * max(1.0 - d["busy_s"] / window for d in devices),
        "gb_per_step": plan["bytes_a_run"] / 1e9 if plan is not None else None,
        "unmatched_share": unmatched_share, "by_axis_held": plan is not None and unmatched_share <= UNMATCHED_LIMIT,
    }


def describe(found: dict, host_spans, step_s: float | None = None) -> str:
    """The `[mesh]` table: a row a device, a row an axis and kind, the scopes with the most exposed seconds, every device's idle gaps."""
    ms, window, plan = 1e3, found["window_s"], found["plan"]
    lines = [f"[mesh] traced window {window * ms:.3f} ms on {len(found['devices'])} device(s); a collective under way "
             f"{found['in_flight_pct']:.3f}% of it, exposed {found['exposed_pct']:.3f}% (mean of the devices); the idlest device idle {found['idle_max_pct']:.4f}%"]
    lines.append(f"[mesh]   {'device':<8} {'busy ms':>10} {'idle %':>8} {'under way ms':>13} {'exposed ms':>11} {'exposed %':>10} {'module runs':>12}")
    for d in found["devices"]:
        under, exposed = d["groups"][BOTH]
        lines.append(f"[mesh]   {d['ordinal']:<8} {d['busy_s'] * ms:>10.3f} {100 * (1 - d['busy_s'] / window):>8.4f} {under * ms:>13.3f} "
                     f"{exposed * ms:>11.3f} {100 * exposed / window:>10.3f} {d['module_runs']:>12}")
    if plan is None:
        lines.append("[mesh] no collective plan in this process (a program from before the record, or a mesh of one device): the shares "
                     f"above are over the events named like {xtrace.COLLECTIVE.pattern!r}; nothing is read by axis")
        return "\n".join(lines + _idle_lines(found, host_spans))
    n = len(found["devices"])
    a_run = (f"{found['in_flight_pct'] / 100 * step_s * ms:.3f} ms under way and {found['exposed_pct'] / 100 * step_s * ms:.3f} ms exposed a step of {step_s * ms:.2f} ms"
             if step_s else f"{found['in_flight_pct']:.3f}% under way and {found['exposed_pct']:.3f}% exposed")
    lines.append(f"[mesh] plan of {plan['module']} on {plan['mesh_axes']}: {len(plan['rows'])} collectives, {plan['bytes_a_run'] / 1e9:.3f} GB a run; "
                 f"the cost model's estimate {plan['est_seconds_a_run'] * ms:.3f} ms a run (bytes over one link's rate, no overlap) beside {a_run} in the trace")
    lines.append(f"[mesh]   {'axis':<14} {'kind':<20} {'rows':>5} {'times a run':>12} {'GB a run':>10} {'under way %':>12} {'exposed %':>10}")
    kinds: dict[tuple, dict] = {}
    for row in plan["rows"]:
        k = kinds.setdefault((row["axis"], row["kind"]), {"rows": 0, "times": 0, "bytes": 0, "under": 0.0, "exposed": 0.0})
        k["rows"] += 1
        k["times"] += row["times"]
        k["bytes"] += row["bytes"] * row["times"]
        k["under"] += sum(d["rows"][row["name"]][0] for d in found["devices"]) / n
        k["exposed"] += sum(d["rows"][row["name"]][1] for d in found["devices"]) / n
    for (axis, kind), k in sorted(kinds.items()):
        lines.append(f"[mesh]   {axis:<14} {kind:<20} {k['rows']:>5} {k['times']:>12} {k['bytes'] / 1e9:>10.3f} {100 * k['under'] / window:>12.3f} {100 * k['exposed'] / window:>10.3f}")
    for axis in found["axes"]:
        a = found["by_axis"][axis]
        lines.append(f"[mesh]   {axis:<14} {'(union of its rows)':<20} {'':>5} {'':>12} {'':>10} {a['in_flight_pct']:>12.3f} {a['exposed_pct']:>10.3f}")
    scopes: dict[str, float] = {}
    for row in plan["rows"]:
        scopes[row["scope"]] = scopes.get(row["scope"], 0.0) + sum(d["rows"][row["name"]][1] for d in found["devices"]) / n
    lines.append("[mesh] the five scopes with the most exposed seconds (a row's own, so two in flight together count twice):")
    for scope, seconds in sorted(scopes.items(), key=lambda kv: -kv[1])[:5]:
        lines.append(f"[mesh]   {seconds * ms:>9.3f} ms {100 * seconds / window:>7.3f}%  {scope}")
    first = found["devices"][0]
    off = [f"{row['name']} x{row['times']} in the plan, {first['events'][row['name']]} events over {first['module_runs']} run(s)"
           for row in plan["rows"] if not row["times"] * max(0, first["module_runs"] - 2) <= first["events"][row["name"]] <= row["times"] * first["module_runs"]]
    if off:
        lines.append(f"[mesh] times a run, plan against device {first['ordinal']}'s trace, where they differ by more than the runs the trace's edges cut: " + "; ".join(off[:8]))
    for d in found["devices"]:
        if d["unmatched_rows"] or d["unmatched_names"]:
            lines.append(f"[mesh] unmatched on device {d['ordinal']}: {len(d['unmatched_rows'])} row(s) of the plan with no event {d['unmatched_rows'][:6]}; "
                         f"{len(d['unmatched_names'])} instruction(s) that look like collectives and are in no row {d['unmatched_names'][:6]}, {d['unmatched_s'] * ms:.3f} ms")
    lines.append(f"[mesh] unmatched: {100 * found['unmatched_share']:.3f}% of the collectives' seconds (the by-axis metrics are "
                 f"{'reported' if found['by_axis_held'] else f'MISSING: over {100 * UNMATCHED_LIMIT:.0f}%'})")
    return "\n".join(lines + _idle_lines(found, host_spans))


def _idle_lines(found: dict, host_spans) -> list[str]:
    lines = []
    for d in found["devices"]:
        named: dict[str, float] = {}
        for gap in d["idle_gaps"]:
            name = xtrace._span_over(gap, host_spans)
            named[name] = named.get(name, 0.0) + (gap[1] - gap[0])
        gaps = ", ".join(f"{name} {seconds * 1e3:.3f}" for name, seconds in sorted(named.items(), key=lambda kv: -kv[1])[:4])
        lines.append(f"[mesh] idle on device {d['ordinal']}, ms by the host span open meanwhile: {gaps or 'none'}")
    return lines


def _found(observed: dict, trace: xtrace.Trace):
    if "collectives" not in observed:
        found = reading(trace, plan_for(trace, process_plans()))
        steps = sorted(observed.get("step_seconds") or [])
        print(describe(found, trace.host_spans, steps[len(steps) // 2] if steps else None), flush=True)
        observed["collectives"] = found
    return observed["collectives"]


def read(spec: dict, observed: dict, trace, env: dict):
    if trace is None or not trace.devices:
        return None
    found = _found(observed, trace)
    key, axis = spec["key"], spec.get("axis")
    if axis is not None:
        return found["by_axis"][axis][key] if found["by_axis_held"] and axis in found["by_axis"] else None
    return found[key]
