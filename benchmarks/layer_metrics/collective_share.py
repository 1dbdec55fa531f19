"""Share of the traced slice the first chip spent in collectives (the
data-parallel gradient all-reduce): the time of `all-reduce` instructions on
the op line, plus, should a later compiler or program make them asynchronous,
the union of their start-to-done spans.  It is time IN collectives, not time
lost to them: in PR 22 the four-chip step held 39 ms of all-reduce and was
19 ms longer than the one-chip step."""

from benchmarks.harness import trace_reduce as tr


def read(obs):
    if obs.trace is None or obs.counters.get("chips", 1) < 2:
        return None
    window = tr.window_seconds(obs.trace, obs.trace_wall_s)
    sync = tr.op_seconds(
        obs.trace, lambda e: e.is_collective
        and not e.kind.endswith(("-start", "-done")))
    in_flight = tr.async_seconds(obs.trace, lambda e: e.is_collective)
    return 100.0 * (sync + in_flight) / window
