"""Median time from a request's due time to its first token.  Recorded, not
bounded: below the knee it carries the phase of the decode step in flight
(see ttft_p90_ms), above the knee it swings with the backlog."""

import math


def read(obs):
    value = obs.e2e.get("ttft_p50_ms")
    return value if value is not None and math.isfinite(value) else None
