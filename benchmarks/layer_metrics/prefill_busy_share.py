"""Share of the device's busy time spent inside prefill programs: the device
ops that start within an event of the trace's "XLA Modules" line whose
program is a prefill (`jit_prefill`, `jit_prefill_chunk`), over the union of
all device-op intervals.  What is left is the decode step and the small
programs beside it."""

import bisect

from benchmarks.harness import trace_reduce as tr


def read(obs):
    if obs.trace is None:
        return None
    busy = tr.busy_seconds(obs.trace)
    spans = tr.merged_intervals(
        e for e in tr.first_device(obs.trace, "modules")
        if e.name.split("(", 1)[0].startswith("jit_prefill"))
    if not busy or not spans:
        return None
    starts = [s for s, _ in spans]

    def inside(e):
        i = bisect.bisect_right(starts, e.start_ns) - 1
        return i >= 0 and e.start_ns < spans[i][1]

    ops = [e for e in tr.first_device(obs.trace)
           if e.kind not in tr.CONTAINERS and inside(e)]
    return 100.0 * sum(e - s for s, e in tr.merged_intervals(ops)) * 1e-9 / busy
