"""Share of the device's busy time spent in the paged-attention kernel: the
Pallas custom calls of a serving trace (prefill below 2048 tokens takes the
dense path, so the decode step's kernel is the only one).  Its roofline
share needs a count of the rows attended inside the engine: the next
tracing issue's."""

from benchmarks.harness import trace_reduce as tr


def read(obs):
    if obs.trace is None:
        return None
    busy = tr.busy_seconds(obs.trace)
    if not busy:
        return None
    return 100.0 * tr.op_seconds(obs.trace, lambda e: e.is_pallas) / busy
