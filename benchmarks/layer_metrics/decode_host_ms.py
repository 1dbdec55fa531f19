"""Host time per decode step in which the device has nothing queued: the
summed durations of the slice's `generation.decode_prepare`,
`generation.decode_dispatch` and `generation.harvest` spans over the count of
its `generation.decode_dispatch` spans.  The fourth span of a step,
`generation.decode_readback`, is left out: the device runs under it.  This
is the exact split of the gap between two steps' programs, which
`breakdown.idle_gaps` gives whole to one event."""

from benchmarks.layer_metrics import engine_thread


def read(obs):
    dispatch = engine_thread.spans(obs, "decode_dispatch")
    if not dispatch:
        return None
    host = (sum(engine_thread.spans(obs, "decode_prepare")) + sum(dispatch)
            + sum(engine_thread.spans(obs, "harvest")))
    return 1e3 * host / len(dispatch)
