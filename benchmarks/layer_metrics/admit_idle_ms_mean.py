"""Mean time the device sat idle per admission, in ms.

Each `generation.refill` span of the traced slice gets a window around it.
It opens at the end of the last decode-step program that started before the
refill (`jit_step` / `jit_verify` on the first device's "XLA Modules"
line), or at the end of a `generation.wait_for_work` span where that is
later (the device's idle while the queue was empty is not the admission's);
where the refill before this one came after both, that refill's window took
the time up to here and this one opens at its own start.  It closes at the
start of the first decode-step program after the refill, or of the next
refill or `wait_for_work` span where one comes first.  So no idle is
counted twice, and a refill with no bound on either side in the slice has
no window.  Idle is the window less the union of the device's op intervals
inside it (the set `device_idle_share` reads); the metric is the idle
summed over the windows over their number.

It splits nothing by span: the split of one admission's idle among the
engine thread's spans is what `breakdown.idle_gaps` cannot give, since it
hands each gap to the one host event that covers most of it."""

from benchmarks.harness import trace_reduce as tr
from benchmarks.layer_metrics import engine_thread

STEP_PROGRAMS = ("jit_step", "jit_verify")


def _named(obs, phase: str) -> list:
    name = engine_thread.PREFIX + phase
    return sorted((e for e in obs.trace.host if e.name == name),
                  key=lambda e: e.start_ns)


def _idle_ns(busy: list, w0: float, w1: float) -> float:
    covered = sum(max(0.0, min(e, w1) - max(s, w0)) for s, e in busy)
    return (w1 - w0) - covered


def read(obs):
    if obs.trace is None:
        return None
    refills = _named(obs, "refill")
    if not refills:
        return None
    waits = _named(obs, "wait_for_work")
    steps = [e for e in tr.first_device(obs.trace, "modules")
             if e.name.split("(", 1)[0] in STEP_PROGRAMS]
    busy = tr.merged_intervals(tr.first_device(obs.trace))
    idle, windows = 0.0, 0
    for i, r in enumerate(refills):
        opens = [s.end_ns for s in steps if s.start_ns < r.start_ns]
        opens += [w.end_ns for w in waits if w.end_ns <= r.start_ns]
        closes = [s.start_ns for s in steps if s.start_ns >= r.start_ns]
        closes += [w.start_ns for w in waits if w.start_ns >= r.end_ns]
        if i + 1 < len(refills):
            closes.append(refills[i + 1].start_ns)
        w0 = max(opens, default=None)
        if i and refills[i - 1].start_ns > (w0 or 0.0):
            w0 = r.start_ns
        if w0 is None or not closes:
            continue
        w1 = min(closes)
        if w1 > w0:
            idle += _idle_ns(busy, w0, w1)
            windows += 1
    return 1e-6 * idle / windows if windows else None
