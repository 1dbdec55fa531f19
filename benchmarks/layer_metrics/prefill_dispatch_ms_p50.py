"""Median duration of `generation.prefill_dispatch`: the host's cost to
launch one prefill program (a K/V pool's bucket, or one chunk of a row
pool with the rebinding of its donated state), from the jit call's start
to its return.  The device idles under it when nothing is queued, which
during an admission it is not: the step in flight was drained before."""

from benchmarks.harness.stats import percentile
from benchmarks.layer_metrics import engine_thread


def read(obs):
    spans = engine_thread.spans(obs, "prefill_dispatch")
    return 1e3 * percentile(spans, 50) if spans else None
