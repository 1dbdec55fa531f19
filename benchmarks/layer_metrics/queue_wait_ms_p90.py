"""90th percentile of the engine's `queue` segment (enqueue to taken by the
decode loop) over the streams admitted in the window."""

from benchmarks.harness.stats import percentile


def read(obs):
    waits = [1e3 * s.lat["queue"] for s in obs.streams if "queue" in s.lat]
    return percentile(waits, 90) if waits else None
