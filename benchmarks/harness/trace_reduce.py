"""From a profiler trace to numbers: device busy time, time per device
operation, idle gaps and what the host was doing in them.

What a v5e trace looks like today (jax 0.9.0, libtpu 0.0.34; looked at by
hand with benchmarks/tools/trace_dump.py in PR 22): the plane
"/device:TPU:<n>" has the lines "Steps", "XLA Modules" (one event per
executed program, `jit_step(<fingerprint>)`), "XLA Ops" (one event per
executed HLO instruction, nested ones included) and "Async XLA Ops" (the
start-to-done span of each asynchronous copy or collective).  An op event's
NAME is the whole HLO instruction text,

    %fusion.12 = f32[8192,2048]{...} fusion(...operands...), kind=kOutput, ...

and it carries no category stat, so `parse_op` takes the instruction's name,
result shape and opcode from that text.  The program has no
`jax.named_scope` and its Pallas calls no `name=`: a Pallas kernel is a
`custom-call` whose text holds `custom_call_target="tpu_custom_call"`, and
which kernel it is follows from the program it runs in (the train step's are
flash attention, the decode step's are paged attention).  Host threads are
lines of "/host:CPU", with the runtime's own TraceMe events
(`PjitFunction(step)`, `np.asarray(jax.Array)`, transfers).

The reduction works on plain `Event` lists, so the recorded traces the tests
check it against are small JSON files of such events (`events_from_json`),
and a real run reads `.xplane.pb` through `jax.profiler.ProfileData`
(`events_from_xplane`).
"""

from __future__ import annotations

import dataclasses
import glob
import json
import os
import re

DEVICE_PLANE_PREFIX = "/device:TPU:"
HOST_PLANE_PREFIX = "/host:CPU"
OP_LINE, ASYNC_LINE, MODULE_LINE = "XLA Ops", "Async XLA Ops", "XLA Modules"
PALLAS_TARGET = 'custom_call_target="tpu_custom_call"'
# instructions whose interval only wraps the instructions of their body,
# which the trace lists too
CONTAINERS = ("while", "conditional", "call")
COLLECTIVES = ("all-reduce", "reduce-scatter", "all-gather", "all-to-all",
               "collective-permute")
_OP = re.compile(r"^%?(?P<instr>\S+) = (?P<shape>.*?) (?P<kind>[\w\-]+)\(")


def parse_op(name: str) -> tuple:
    """(instruction name, result shape, opcode) of an op event's name; a
    name that is not HLO text is its own instruction, with no opcode."""
    m = _OP.match(name)
    if not m:
        return name, "", ""
    return m.group("instr"), m.group("shape"), m.group("kind")


@dataclasses.dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns

    @property
    def kind(self) -> str:
        return parse_op(self.name)[2]

    @property
    def label(self) -> str:
        """A name short enough to print: instruction, opcode and the first
        array of the result."""
        instr, shape, kind = parse_op(self.name)
        first = re.search(r"\w+\[[\d,]*\]", shape)
        return " ".join(x for x in (instr, kind,
                                    first.group(0) if first else "") if x)

    @property
    def is_pallas(self) -> bool:
        return self.kind == "custom-call" and PALLAS_TARGET in self.name

    @property
    def is_collective(self) -> bool:
        return self.kind.startswith(COLLECTIVES)


@dataclasses.dataclass(frozen=True)
class Trace:
    device_ops: dict        # device plane name -> [Event] of executed ops
    async_ops: dict         # device plane name -> [Event] start-to-done spans
    modules: dict           # device plane name -> [Event] executed programs
    host: list              # [Event] of host threads
    window_ns: tuple        # (start, end) of the traced slice


# -- recording and reading ---------------------------------------------------

def start_profiler(log_dir: str) -> None:
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0       # the runtime's TraceMe events only
    jax.profiler.start_trace(log_dir, profiler_options=opts)



def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def events_from_xplane(path: str) -> list[Event]:
    from jax.profiler import ProfileData

    out = []
    _read_planes(ProfileData.from_file(path), out)
    return out


def _read_planes(data, out: list) -> None:
    for plane in data.planes:
        is_dev = plane.name.startswith(DEVICE_PLANE_PREFIX)
        if not (is_dev or plane.name.startswith(HOST_PLANE_PREFIX)):
            continue
        for line in plane.lines:
            for ev in line.events:
                out.append(Event(plane.name, line.name, ev.name,
                                 float(ev.start_ns), float(ev.duration_ns)))


def events_to_json(events: list[Event], path: str) -> None:
    with open(path, "w") as f:
        json.dump([dataclasses.astuple(e) for e in events], f)


def events_from_json(path: str) -> list[Event]:
    with open(path) as f:
        return [Event(*row) for row in json.load(f)]


def build(events: list[Event]) -> Trace:
    """Split events into device ops, async spans, programs and host events.
    A device plane's ops are its "XLA Ops" line; the other lines cover the
    same time again."""
    ops: dict = {}
    spans: dict = {}
    modules: dict = {}
    host = []
    for e in events:
        if e.dur_ns <= 0:
            continue
        if e.plane.startswith(DEVICE_PLANE_PREFIX):
            into = {OP_LINE: ops, ASYNC_LINE: spans,
                    MODULE_LINE: modules}.get(e.line)
            if into is not None:
                into.setdefault(e.plane, []).append(e)
        elif e.plane.startswith(HOST_PLANE_PREFIX):
            host.append(e)
    for group in (ops, spans, modules):
        for evs in group.values():
            evs.sort(key=lambda e: e.start_ns)
    every = [e for evs in ops.values() for e in evs] or host
    if not every:
        raise ValueError("the trace holds no event")
    window = (min(e.start_ns for e in every), max(e.end_ns for e in every))
    return Trace(ops, spans, modules, host, window)


# -- reduction ---------------------------------------------------------------

def merged_intervals(events) -> list[tuple]:
    """Union of the events' intervals, as sorted disjoint (start, end)."""
    out: list[list] = []
    for s, e in sorted((ev.start_ns, ev.end_ns) for ev in events):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_seconds(trace: Trace) -> float:
    """Seconds in which an op ran on the device, averaged over the devices
    that ran any."""
    per_dev = [sum(e - s for s, e in merged_intervals(ops)) * 1e-9
               for ops in trace.device_ops.values() if ops]
    return sum(per_dev) / len(per_dev) if per_dev else 0.0


def window_seconds(trace: Trace, wall_s: float | None = None) -> float:
    """Length of the traced slice: the wall time between start_trace and
    stop_trace where the caller measured it, or first to last event where
    that is longer (the profiler records from a little before start_trace
    returns)."""
    span = (trace.window_ns[1] - trace.window_ns[0]) * 1e-9
    return span if wall_s is None else max(wall_s, span)


def first_device(trace: Trace, group: str = "device_ops") -> list[Event]:
    planes = getattr(trace, group)
    for plane in sorted(planes):
        if planes[plane]:
            return planes[plane]
    return []


def op_seconds(trace: Trace, wanted) -> float:
    """Summed time on the first device of the ops `wanted(event)` picks.
    Containers (while, conditional, call) are never summed: their bodies'
    ops are events of their own."""
    return sum(e.dur_ns for e in first_device(trace)
               if e.kind not in CONTAINERS and wanted(e)) * 1e-9


def op_count(trace: Trace, wanted) -> int:
    return sum(1 for e in first_device(trace) if wanted(e))


def async_seconds(trace: Trace, wanted) -> float:
    """Union, on the first device, of the start-to-done spans `wanted`
    picks: how long such transfers were in flight, overlapped or not."""
    spans = [e for e in first_device(trace, "async_ops") if wanted(e)]
    return sum(e - s for s, e in merged_intervals(spans)) * 1e-9


def module_seconds(trace: Trace, program: str) -> list[float]:
    """Device seconds of each execution of the program `jit_<program>` on
    the first device."""
    return [e.dur_ns * 1e-9 for e in first_device(trace, "modules")
            if e.name.split("(", 1)[0] == "jit_" + program]


def top_ops(trace: Trace, n: int = 10) -> list[list]:
    """The device operations that took most time on the first device, by
    their short labels (containers left out)."""
    total: dict = {}
    for e in first_device(trace):
        if e.kind not in CONTAINERS:
            total[e.label] = total.get(e.label, 0.0) + e.dur_ns * 1e-9
    return [[k, v] for k, v in sorted(total.items(),
                                      key=lambda kv: -kv[1])[:n]]


# gaps attributed one by one; the rest (each shorter than every one of these)
# are summed under one name
ATTRIBUTED_GAPS = 400


def idle_gaps(trace: Trace, n: int = 10) -> list[list]:
    """Idle time of the first device, by what the host was doing: each of
    the longest gaps between device ops is given to the host event that
    covers most of it (the shortest such event, so the innermost), and gaps
    are summed under that event's name.  A gap of which no host event covers
    half is "(no host event)" — the host was in code the profiler does not
    mark, such as the program's own Python; the many short gaps inside a
    program are summed as "(short gaps)"."""
    import numpy as np

    busy = merged_intervals(first_device(trace))
    gaps = sorted(((b[0] - a[1], a[1], b[0])
                   for a, b in zip(busy, busy[1:]) if b[0] > a[1]),
                  reverse=True)
    starts = np.array([e.start_ns for e in trace.host])
    ends = np.array([e.end_ns for e in trace.host])
    total: dict = {}
    for length, g0, g1 in gaps[:ATTRIBUTED_GAPS]:
        name = "(no host event)"
        if len(starts):
            overlap = np.minimum(ends, g1) - np.maximum(starts, g0)
            best = overlap.max()
            if best >= 0.5 * length:
                # innermost: among the events covering as much, the shortest
                tied = np.flatnonzero(overlap >= best - 1.0)
                name = trace.host[
                    tied[np.argmin((ends - starts)[tied])]].name
        total[name] = total.get(name, 0.0) + length * 1e-9
    rest = sum(g[0] for g in gaps[ATTRIBUTED_GAPS:]) * 1e-9
    if rest > 0:
        total["(short gaps)"] = rest
    return [[k, v] for k, v in sorted(total.items(),
                                      key=lambda kv: -kv[1])[:n]]
