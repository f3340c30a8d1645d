"""From a profiler trace (`*.xplane.pb`) to what the per-layer metrics read.

A trace holds one plane per device (`/device:TPU:<n>`) and one for the host. On a
device plane the line "XLA Ops" holds one event per executed operation and the line
"XLA Modules" one per executed program; loops and calls appear as events that contain
their bodies' events, so an operation's own time is its duration minus what its
children cover. The host plane holds the program's spans (every span of the program is
a `TraceAnnotation`), on the same clock.

Reduced here, for every PR alike: the seconds in which any operation ran on a device
(the union of its events), the idle share of a window, time by operation and by
program, the longest idle gaps by the host span that was open in them, and the part of
the collectives' time during which nothing else ran on that device.
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field
from pathlib import Path

from benchmark.stats import merge, subtract, union_length

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE, ASYNC_LINE, MODULES_LINE = "XLA Ops", "Async XLA Ops", "XLA Modules"
HOST_PLANE, PYTHON_LINE = "/host:CPU", "python"
COLLECTIVE = re.compile(r"all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute|collective-broadcast")


@dataclass
class Event:
    name: str
    start: float  # seconds on the trace's clock
    end: float
    thread: int = 0  # of a host span: which of the host plane's Python lines it lies on

    @property
    def seconds(self) -> float:
        return self.end - self.start


@dataclass
class DeviceTrace:
    ordinal: int
    ops: list[Event]  # what the core executed, one after another (loops contain their bodies)
    modules: list[Event]
    async_ops: list[Event] = field(default_factory=list)  # copies and collectives in flight beside the core


@dataclass
class Trace:
    devices: list[DeviceTrace]
    host_spans: list[Event]

    @property
    def window(self) -> tuple[float, float]:
        """From the first to the last thing any device did."""
        events = [e for d in self.devices for e in d.ops + d.modules]
        return min(e.start for e in events), max(e.end for e in events)


def start_profiler(trace_dir: Path) -> None:
    """Open the profiler as every traced run does: device events and the program's spans
    (TraceMe), without the Python tracer, whose events would swamp both."""
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(str(trace_dir), profiler_options=options)


def find_xplane(trace_dir: Path) -> Path:
    files = sorted(Path(trace_dir).glob("plugins/profile/*/*.xplane.pb"))
    if not files:
        raise SystemExit(f"benchmark: the profiler left no .xplane.pb under {trace_dir}")
    return files[-1]


def _events(line, thread: int = 0) -> list[Event]:
    return [Event(str(e.name), e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9, thread) for e in line.events]


def load(path: Path) -> Trace:
    """Read an `.xplane.pb`: the device planes' operations and programs, and the events
    of the host's Python threads (the program's spans)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    devices, host_spans = [], []
    for plane in data.planes:
        match = DEVICE_PLANE.match(plane.name)
        if match:
            lines = {line.name: line for line in plane.lines}
            devices.append(DeviceTrace(
                ordinal=int(match.group(1)),
                ops=_events(lines[OPS_LINE]) if OPS_LINE in lines else [],
                modules=_events(lines[MODULES_LINE]) if MODULES_LINE in lines else [],
                async_ops=_events(lines[ASYNC_LINE]) if ASYNC_LINE in lines else [],
            ))
        elif plane.name == HOST_PLANE:
            for thread, line in enumerate(plane.lines):
                if line.name.startswith(PYTHON_LINE):  # the program's spans: TraceAnnotations of its threads
                    host_spans.extend(e for e in _events(line, thread) if e.seconds > 0)
    devices.sort(key=lambda d: d.ordinal)
    return Trace(devices, host_spans)


# ------------------------------------------------------------------ reductions


def busy_intervals(device: DeviceTrace) -> list[tuple[float, float]]:
    return merge((e.start, e.end) for e in device.ops)


def busy_seconds(trace: Trace) -> float:
    """Seconds in which an operation ran, averaged over the devices."""
    return sum(union_length((e.start, e.end) for e in d.ops) for d in trace.devices) / max(1, len(trace.devices))


def idle_share(trace: Trace) -> float:
    start, end = trace.window
    return 1.0 - busy_seconds(trace) / (end - start)


def _own_time(events: list[Event]) -> list[tuple[Event, float, bool]]:
    """(event, its duration minus what the events inside it cover, whether any is inside it)."""
    ordered = sorted(events, key=lambda e: (e.start, -e.end))
    out, stack = [], []  # stack: [event, covered seconds, has children]
    for e in ordered:
        while stack and stack[-1][0].end <= e.start:
            done, covered, parent = stack.pop()
            out.append((done, max(done.seconds - covered, 0.0), parent))
        if stack and e.end <= stack[-1][0].end + 1e-12:
            stack[-1][1] += e.seconds
            stack[-1][2] = True
        stack.append([e, 0.0, False])
    while stack:
        done, covered, parent = stack.pop()
        out.append((done, max(done.seconds - covered, 0.0), parent))
    return out


def self_seconds(events: list[Event]) -> list[tuple[Event, float]]:
    """Each event's own time: its duration minus what the events inside it cover."""
    return [(event, own) for event, own, _ in _own_time(events)]


def leaf_events(events: list[Event]) -> list[Event]:
    """The events that contain no other: operations, not the loops and calls round them."""
    return [event for event, _, parent in _own_time(events) if not parent]


_INSTRUCTION = re.compile(r"^%?([^\s=(]+)")


def op_label(event: Event) -> str:
    """The name a person would look for. A device event is named by its whole HLO
    instruction (`%fusion.12 = bf16[...] fusion(...)`): keep the instruction's name,
    without its numbering and without the `transpose_jvp_..._` wrapping that autodiff
    puts round a kernel's `name=`, so that a Pallas kernel reads as its own name."""
    found = _INSTRUCTION.match(event.name)
    name = re.sub(r"\.\d+", "", found.group(1) if found else event.name)  # fusion.180.remat_compressed -> fusion.remat_compressed
    wrapped = re.fullmatch(r"(?:transpose_)?(?:jvp_)?(?:transpose_)?(.+?)_*", name)
    return wrapped.group(1) if wrapped and wrapped.group(1) else name


def time_by_label(trace: Trace) -> dict[str, float]:
    """Own seconds by label, averaged over the devices."""
    out: dict[str, float] = {}
    for device in trace.devices:
        for event, seconds in self_seconds(device.ops):
            key = op_label(event)
            out[key] = out.get(key, 0.0) + seconds
    return {k: v / max(1, len(trace.devices)) for k, v in out.items()}


def label_events(trace: Trace, pattern: str) -> list[list[Event]]:
    """Per device, the operations whose label matches `pattern` (a regular expression)."""
    wanted = re.compile(pattern)
    return [[e for e in d.ops if wanted.search(op_label(e))] for d in trace.devices]


def module_runs(trace: Trace, pattern: str) -> list[Event]:
    """Executions of the programs whose name matches `pattern`, on the first device."""
    wanted = re.compile(pattern)
    return [e for e in trace.devices[0].modules if wanted.search(e.name)] if trace.devices else []


def _span_over(gap: tuple[float, float], spans: list[Event]) -> str:
    """The host span that covers most of `gap`, named as the program names it. Of the
    spans of ONE thread that cover the gap alike, the longest: spans of a thread nest,
    the program's spans enclose the annotations JAX opens inside them
    (`metrics_fetch` round `np.asarray(jax.Array)`), and the program's name is the one
    an operator knows. Between threads the shortest of those: the loop's
    `metrics_fetch` of one step, not a background thread's span that lasts many."""
    outermost: dict[int, tuple[float, float, str]] = {}  # by thread: (seconds of the gap covered, the span's own seconds, its name)
    for span in spans:
        overlap = min(gap[1], span.end) - max(gap[0], span.start)
        if overlap > 0:
            found = (round(overlap, 6), span.seconds, span.name)
            outermost[span.thread] = max(found, outermost.get(span.thread, found))
    if not outermost:
        return "(no span)"
    return max(outermost.values(), key=lambda found: (found[0], -found[1]))[2]


def idle_gaps(trace: Trace, top: int = 10) -> list[tuple[str, float]]:
    """Idle seconds of the first device inside the traced window, by the host span that
    was open during each gap (`(no span)` where none was); the largest first."""
    if not trace.devices:
        return []
    start, end = trace.window
    named: dict[str, float] = {}
    for gap in subtract([(start, end)], busy_intervals(trace.devices[0])):
        name = _span_over(gap, trace.host_spans)
        named[name] = named.get(name, 0.0) + (gap[1] - gap[0])
    return sorted(named.items(), key=lambda kv: -kv[1])[:top]


def exposed_collective_seconds(trace: Trace) -> float:
    """Seconds in which a collective was under way on a device (in flight beside the core,
    or the core itself inside one) and the core ran nothing else, averaged over the
    devices. Waiting in a collective's `-done` is exposed time, not compute."""
    total = 0.0
    for device in trace.devices:
        leaves = leaf_events(device.ops)
        collective = merge((e.start, e.end) for e in leaves + device.async_ops if COLLECTIVE.search(op_label(e)))
        compute = merge((e.start, e.end) for e in leaves if not COLLECTIVE.search(op_label(e)))
        total += union_length(subtract(collective, compute))
    return total / max(1, len(trace.devices))


def breakdown(trace: Trace, top: int = 10) -> dict:
    ops = sorted(time_by_label(trace).items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v] for k, v in ops], "idle_gaps": [[k, v] for k, v in idle_gaps(trace, top)]}


def describe(path: Path, limit: int = 12) -> str:
    """A page about a trace, for whoever has not seen one from this device yet."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    lines = []
    for plane in data.planes:
        lines.append(f"PLANE {plane.name}")
        for line in plane.lines:
            events = list(line.events)
            lines.append(f"  LINE {line.name}: {len(events)} events")
            for e in events[:limit]:
                try:
                    stats = {str(k): str(v)[:160] for k, v in e.stats}
                except Exception as err:  # noqa: BLE001
                    stats = {"stats_error": repr(err)}
                lines.append(f"    {e.name[:100]!r} start={e.start_ns:.0f} dur={e.duration_ns:.0f} {stats}")
    return "\n".join(lines)
