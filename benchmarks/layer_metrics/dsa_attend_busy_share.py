"""Share of the device's busy time spent in the `dsa_prefill_attn` kernel:
the latent prefill chunks' attention under the DSA selection
(`ops/dsa_prefill_attention.py`), one call per layer per chunk.  Only the
calls of that name count; a program without the kernel reads nothing."""

from benchmarks.harness import trace_reduce as tr

KERNEL = "dsa_prefill_attn"


def kernel_calls(obs):
    """The Pallas events of the slice that are `dsa_prefill_attn` calls."""
    return [e for e in tr.first_device(obs.trace)
            if e.kind not in tr.CONTAINERS and e.is_pallas
            and KERNEL in e.name]


def read(obs):
    if obs.trace is None:
        return None
    busy = tr.busy_seconds(obs.trace)
    calls = kernel_calls(obs)
    if not busy or not calls:
        return None
    return 100.0 * sum(e.dur_ns for e in calls) * 1e-9 / busy
