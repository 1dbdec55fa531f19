"""Share of the traced slice in which no operation ran on the device:
1 - (union of device-op intervals) / slice, averaged over the chips used."""

from benchmarks.harness import trace_reduce as tr


def read(obs):
    if obs.trace is None:
        return None
    window = tr.window_seconds(obs.trace, obs.trace_wall_s)
    return 100.0 * (1.0 - tr.busy_seconds(obs.trace) / window)
