"""Median device time of one execution of the cell's step program — the
train step under `fit()`, the decode step in the engine; both are
`jit_step` on the trace's "XLA Modules" line.  It is what the step costs
the chip whatever the host does between steps: in a serving cell, set it
against `decode_step_ms_p50`, the same step on the host's clock."""

from benchmarks.harness import trace_reduce as tr
from benchmarks.harness.stats import percentile


def read(obs):
    if obs.trace is None:
        return None
    runs = tr.module_seconds(obs.trace, "step")
    return 1e3 * percentile(runs, 50) if runs else None
