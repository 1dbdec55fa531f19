"""Share of the device's busy time spent in the `shared_kv_attn` kernel:
the decode step's differential attention of the full layer and every cross
layer over the one shared K/V pool (`shared_kv_attn_roofline.kernel_calls`)."""

from benchmarks.harness import trace_reduce as tr
from benchmarks.layer_metrics import shared_kv_attn_roofline as kernel


def read(obs):
    if obs.trace is None:
        return None
    busy = tr.busy_seconds(obs.trace)
    calls = kernel.kernel_calls(obs)
    if not busy or not calls:
        return None
    return 100.0 * sum(e.dur_ns for e in calls) * 1e-9 / busy
