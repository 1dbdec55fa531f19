"""Mean duration of the decode loop's drains for an admission:
`dl4jtpu_decode_drain_seconds_total{reason="admit"}` over
`dl4jtpu_decode_drains_total{reason="admit"}`, what the engine's
`generation.drain` spans measured.  It is what an arrival waits for the
step in flight (its readback and harvest) before its prefill can start.
Process totals: the warm-up's streams run one at a time and drain for no
admission."""

from benchmarks.layer_metrics import program_counts

DRAINS = "dl4jtpu_decode_drains_total"
DRAIN_SECONDS = "dl4jtpu_decode_drain_seconds_total"
ADMIT = 'reason="admit"'


def _admit(name: str):
    got = program_counts.series(name)
    if got is None:
        return None
    return sum(v for k, v in got.items() if ADMIT in k)


def read(obs):
    count, seconds = _admit(DRAINS), _admit(DRAIN_SECONDS)
    if not count or seconds is None:
        return None
    return 1e3 * seconds / count
