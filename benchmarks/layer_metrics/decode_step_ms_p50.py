"""Median over completed streams of the engine's `decode_compute` segment
(dispatch + blocking readback of every decode step the stream rode) per
decode step of the stream."""

from benchmarks.harness.stats import percentile


def read(obs):
    per_step = [1e3 * s.lat["decode_compute"] / (s.max_new - 1)
                for s in obs.streams
                if s.completed_by(obs.close) and s.max_new > 1
                and "decode_compute" in s.lat]
    return percentile(per_step, 50) if per_step else None
