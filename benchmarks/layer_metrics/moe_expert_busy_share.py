"""Share of the device's busy time spent in the grouped expert products:
device ops whose instruction is a `ragged-dot` (XLA's grouped product) or
a `held_gmm` call (the kernel that fetches only the experts with rows),
over busy time.  None where the slice has neither."""

from benchmarks.harness import trace_reduce as tr

OPS = ("ragged-dot", "held_gmm")


def expert_ops(obs):
    """The ops of the slice's first device that are grouped products."""
    return [e for e in tr.first_device(obs.trace)
            if e.kind not in tr.CONTAINERS
            and tr.parse_op(e.name)[0].lstrip("%").startswith(OPS)]


def read(obs):
    if obs.trace is None:
        return None
    busy = tr.busy_seconds(obs.trace)
    ops = expert_ops(obs)
    if not busy or not ops:
        return None
    return 100.0 * sum(e.dur_ns for e in ops) * 1e-9 / busy
