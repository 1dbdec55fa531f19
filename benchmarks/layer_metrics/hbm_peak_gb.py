"""Peak device memory on the fullest chip, from `device.memory_stats()`
right after the window (before the correctness checks allocate)."""


def read(obs):
    peak = obs.counters.get("memory_peak_bytes")
    return peak / 1e9 if peak else None
