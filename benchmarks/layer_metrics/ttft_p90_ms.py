"""90th percentile of the time from a request's due time to its first token
(a failed or unanswered request counts as +inf).  Recorded, not bounded: a
request waits for the decode step in flight when it arrives (0 to one step,
37 ms in `serve_chat`) before its prefill runs, so with some hundred requests
in a window this reads 58.7-65.2 ms over six replays of ONE schedule (4 %
spread, PR 22) — wider than half of any bound a cell may have."""

import math


def read(obs):
    value = obs.e2e.get("ttft_p90_ms")
    return value if value is not None and math.isfinite(value) else None
