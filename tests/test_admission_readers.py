"""ISSUE 37 — the three readers of an admission's cost, on hand-made
traces and a registry of their own (the benchmark's files, read as the
driver reads them: `spec.load(...).reader(name)`).

`admit_idle_ms_mean` gives each `generation.refill` a window: from the end
of the last decode-step program before it (or of a `wait_for_work` span,
or the start of the refill itself where the refill before it took the time
up to there) to the start of the next step program (or of the next refill,
or of a `wait_for_work` span); idle is the window less the device's ops.
All times below are milliseconds; each case is worked by hand."""

import os

import pytest

from benchmarks.harness import spec, trace_reduce
from benchmarks.harness.observe import Observations

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEV, HOST = "/device:TPU:0", "/host:CPU"
MS = 1e6


def _reader(name):
    return spec.load(ROOT).reader(name)


def _program(name, t0, t1):
    """A program on the device: its module event and one op covering it."""
    return [trace_reduce.Event(DEV, "XLA Modules", f"jit_{name}(123)",
                               t0 * MS, (t1 - t0) * MS),
            trace_reduce.Event(DEV, "XLA Ops", f"%fusion.{name}.{t0} = "
                               "f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop",
                               t0 * MS, (t1 - t0) * MS)]


def _span(phase, t0, t1):
    return [trace_reduce.Event(HOST, "python3", "generation." + phase,
                               t0 * MS, (t1 - t0) * MS)]


def _obs(*events, trace=True):
    got = trace_reduce.build([e for evs in events for e in evs])
    return Observations(cell=None, family=None, device={}, peaks=None,
                        trace=got if trace else None)


class TestAdmitIdle:
    read = staticmethod(lambda obs: _reader("admit_idle_ms_mean.chat")(obs))

    def test_a_drain_then_an_admission(self):
        # the step in flight (0-5) is drained (4-5.2), the queue hands a
        # request over (5.2-5.3), the refill runs its prefill program
        # (6-10), the next step starts at 13: a window of 5-13, 4 busy
        obs = _obs(_program("step", 0, 5), _span("drain", 4, 5.2),
                   _span("decode_readback", 4, 5.1), _span("take", 5.2, 5.3),
                   _span("refill", 5.3, 12), _program("prefill", 6, 10),
                   _program("step", 13, 18))
        assert self.read(obs) == pytest.approx(4.0)

    def test_two_refills_with_no_step_between_split_the_time(self):
        # the first refill's window ends where the second starts (5-11:
        # 6 less 2 busy); the second's runs from its start to the step
        # (11-16: 5 less 2 busy)
        obs = _obs(_program("step", 0, 5), _span("refill", 6, 10),
                   _program("prefill", 7, 9), _span("refill", 11, 15),
                   _program("prefill", 12, 14), _program("step", 16, 20))
        assert self.read(obs) == pytest.approx((4.0 + 3.0) / 2)

    def test_a_refill_after_waiting_for_work(self):
        # the device's idle while the queue was empty is not the
        # admission's: its window opens where the wait ends (20-26, 3 busy)
        obs = _obs(_program("step", 0, 5), _span("wait_for_work", 5.5, 20),
                   _span("refill", 20, 25), _program("prefill", 21, 24),
                   _program("step", 26, 30))
        assert self.read(obs) == pytest.approx(3.0)

    def test_a_wait_closes_a_window_and_a_verify_is_a_step(self):
        # a stream that ends at its first token leaves the engine waiting
        # (10-30): the window is 2-10, 2 busy; `jit_verify` opens it too
        obs = _obs(_program("verify", 0, 2), _span("refill", 3, 9),
                   _program("prefill", 4, 6), _span("wait_for_work", 10, 30),
                   _program("step", 31, 33))
        assert self.read(obs) == pytest.approx(6.0)

    def test_no_refill_or_no_bound_reads_none(self):
        assert self.read(_obs(_program("step", 0, 5),
                              _program("step", 6, 9))) is None
        assert self.read(_obs(_program("step", 0, 5),
                              _span("refill", 6, 8), trace=False)) is None
        # a refill with nothing before it in the slice has no window
        assert self.read(_obs(_span("refill", 1, 3),
                              _program("prefill", 1.5, 2.5),
                              _program("step", 4, 6))) is None


def test_prefill_dispatch_is_the_median_span():
    obs = _obs(_program("prefill", 0, 20), _span("prefill_dispatch", 0, 1),
               _span("prefill_dispatch", 5, 9), _span("prefill_dispatch",
                                                      10, 12))
    assert _reader("prefill_dispatch_ms_p50.sat")(obs) == pytest.approx(2.0)
    assert _reader("prefill_dispatch_ms_p50.chat")(
        _obs(_program("step", 0, 5))) is None


@pytest.fixture
def registry_of_its_own(monkeypatch):
    from deeplearning4j_tpu.observe import metrics

    reg = metrics.MetricsRegistry()
    monkeypatch.setattr(metrics, "registry", lambda: reg)
    return reg


def test_admit_drain_is_seconds_over_count_of_admit_drains(
        registry_of_its_own):
    from deeplearning4j_tpu.serving.generation import DECODE_DRAIN_FAMILIES

    read = _reader("admit_drain_ms_mean.chat")
    obs = _obs(_program("step", 0, 5), trace=False)
    # a program from before the drains were counted declares neither
    assert read(obs) is None
    drains, secs = (registry_of_its_own.counter(f)
                    for f in DECODE_DRAIN_FAMILIES)
    assert read(obs) is None                        # declared, none yet
    drains.inc(3, reason="idle")
    secs.inc(0.3, reason="idle")
    assert read(obs) is None                        # no admission drained
    drains.inc(4, reason="admit")
    secs.inc(0.010, reason="admit")
    assert read(obs) == pytest.approx(2.5)
