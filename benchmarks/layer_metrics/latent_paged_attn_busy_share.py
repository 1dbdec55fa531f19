"""Share of the device's busy time spent in the `latent_paged_attn` kernel:
the decode step's attention of the full latent layers over each slot's
whole context in the paged pool
(`latent_paged_attn_roofline.kernel_calls`)."""

from benchmarks.harness import trace_reduce as tr
from benchmarks.layer_metrics import latent_paged_attn_roofline as kernel


def read(obs):
    if obs.trace is None:
        return None
    busy = tr.busy_seconds(obs.trace)
    calls = kernel.kernel_calls(obs)
    if not busy or not calls:
        return None
    return 100.0 * sum(e.dur_ns for e in calls) * 1e-9 / busy
