"""Median duration of `generation.refill`: from the queue handing the engine
thread a batch of requests to the last of their admissions returning
(prefill, K/V handoff, slot bookkeeping).  No decode step runs meanwhile, so
it is how long one admission holds every live stream."""

from benchmarks.harness.stats import percentile
from benchmarks.layer_metrics import engine_thread


def read(obs):
    refills = engine_thread.spans(obs, "refill")
    return 1e3 * percentile(refills, 50) if refills else None
