"""99th percentile of the gaps between successive tokens of a stream, pooled.
Recorded, not bounded: it sits where two clusters of gaps meet (a decode step
that waited for one large-bucket admission, 74.5-74.9 ms, and the next
cluster, ~80 ms), so over twelve replays of one schedule it read 74.5-74.9 nine
times and 75.8-80.5 three times (PR 22) — a spread that reads 0.3 % or 4 %
depending on which six runs one takes.  `itl_p90_ms`, inside a dense cluster,
is the bounded tail."""


def read(obs):
    return obs.e2e.get("itl_p99_ms")
