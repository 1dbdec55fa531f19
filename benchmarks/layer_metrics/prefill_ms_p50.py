"""Median of the engine's `prefill` + `handoff` segments: the bucketed
prompt forward, and its K/V rows landing in the pool."""

from benchmarks.harness.stats import percentile


def read(obs):
    both = [1e3 * (s.lat["prefill"] + s.lat.get("handoff", 0.0))
            for s in obs.streams if "prefill" in s.lat]
    return percentile(both, 50) if both else None
