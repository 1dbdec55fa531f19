"""What one run observed, in the form the per-layer readers take it.

A reader (`benchmarks/layer_metrics/<name>.py`) is `read(obs) -> number or
None`.  It looks only at this object: the run's counters, its per-stream or
per-step records, and the reduced profiler trace.  A reader that finds
nothing to read returns None and the harness leaves its metric out.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Optional


@dataclasses.dataclass
class Stream:
    """One request of a serving window, as the benchmark saw it."""
    due: float                     # perf_counter time the request was due
    prompt_len: int
    max_new: int
    sent: Optional[float] = None   # when submit() was called
    stamps: list = dataclasses.field(default_factory=list)  # one per token
    handle: Any = None             # the engine's GenerationRequest
    refused: Optional[BaseException] = None
    cut: bool = False              # cancelled by the benchmark at the close

    def stamp(self, token: int, index: int) -> None:
        self.stamps.append(time.perf_counter())

    def stamps_by(self, close: float) -> list:
        return [t for t in self.stamps if t <= close]

    def completed_by(self, close: float) -> bool:
        return len(self.stamps_by(close)) >= self.max_new

    @property
    def failed(self) -> bool:
        """Refused at submit, or ended in an error the benchmark did not
        cause by cancelling it."""
        if self.refused is not None:
            return True
        h = self.handle
        return (h is not None and h.done and h.error is not None
                and not self.cut)

    @property
    def lat(self) -> dict:
        """The engine's own six-segment breakdown for this stream."""
        return getattr(self.handle, "lat", None) or {}


@dataclasses.dataclass
class Observations:
    cell: Any                       # spec.Cell
    family: Any                     # the configuration's family module
    device: dict                    # platform / kind / count
    peaks: Optional[tuple]          # (FLOP/s, bytes/s, bytes) per chip
    close: float = 0.0              # perf_counter time the window closed
    e2e: dict = dataclasses.field(default_factory=dict)
    counters: dict = dataclasses.field(default_factory=dict)
    streams: list = dataclasses.field(default_factory=list)
    trace: Any = None               # trace_reduce.Trace of the traced slice
    trace_wall_s: Optional[float] = None
