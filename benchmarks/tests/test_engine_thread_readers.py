"""The four readers PR 25 adds, on a hand-built event list.

`data/recorded_engine_thread_events.json` holds, in the rows
`trace_reduce.events_from_json` reads (`[plane, line, name, start_ns,
duration_ns]`): two decode steps of a 2-layer model on the device (30 ms
each with no gap inside: per layer a fusion and a paged-kernel custom call of
32 us; 1 ms idle between the two), and on the host the engine thread's spans
as a traced run records them — four refills (0.9, 20, 30 and 26 ms), two
whole steps back to back (prepare / dispatch / readback / harvest of 100 /
300 / 30,000 / 600 us and 120 / 280 / 31,000 / 500 us) and the prepare (100
us) of a third step that the slice's end cut.  The expected numbers below are
worked by hand from those."""

import os
import types

import pytest

from benchmarks.harness import spec, trace_reduce
from benchmarks.harness.observe import Observations
from benchmarks.layer_metrics import engine_thread

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
HERE = os.path.dirname(os.path.abspath(__file__))
READERS = ("decode_host_ms", "admit_stall_ms_p50", "decode_slots_mean",
           "paged_attn_roofline")
CONFIG = {"n_layer": 2, "n_head": 4, "n_embd": 32}
# (FLOP/s, bytes/s, bytes): made up so both bounds can be the larger
BYTES_BOUND = (1.0e12, 1.0e10, 1.0e9)
FLOPS_BOUND = (8.0e9, 1.0e12, 1.0e9)


def _reader(name):
    return spec.load(ROOT).reader(name)


def _obs(*, trace=True, kv_dtype="f32", peaks=BYTES_BOUND):
    cell = types.SimpleNamespace(config=CONFIG,
                                 traffic={"engine": {"kv_dtype": kv_dtype}})
    recorded = trace_reduce.build(trace_reduce.events_from_json(os.path.join(
        HERE, "data", "recorded_engine_thread_events.json")))
    return Observations(cell=cell, family=None, device={}, peaks=peaks,
                        trace=recorded if trace else None)


@pytest.fixture
def counted(monkeypatch):
    """A registry of its own with the engine's three counters: 10 steps
    served 35 slot-steps and attended 10,000 rows."""
    from deeplearning4j_tpu.observe import metrics

    reg = metrics.MetricsRegistry()
    reg.counter(engine_thread.STEPS).inc(10)
    reg.counter(engine_thread.SLOT_STEPS).inc(35)
    reg.counter(engine_thread.ROWS).inc(10000)
    monkeypatch.setattr(metrics, "registry", lambda: reg)
    return reg


def test_decode_host_ms_is_the_steps_host_side_without_the_readback():
    # (100 + 300 + 600) + (120 + 280 + 500) + the cut step's 100 us of
    # prepare, over the 2 dispatches the slice holds
    assert _reader("decode_host_ms.chat")(_obs()) == pytest.approx(1.0)


def test_admit_stall_is_the_median_refill():
    # nearest rank of 0.9, 20, 26, 30 ms
    assert _reader("admit_stall_ms_p50.sat")(_obs()) == pytest.approx(20.0)


def test_decode_slots_mean_is_slot_steps_over_steps(counted):
    assert _reader("decode_slots_mean.chat")(_obs(trace=False)) == 3.5


def test_paged_attn_roofline_against_bytes_and_against_flops(counted):
    # 1,000 rows a step x 2 layers x 4 heads x 8 = 64,000 K elements and as
    # many V: 256,000 bytes in bf16 = 25.6 us at 10 GB/s; the kernel takes
    # 2 x 32 us a step
    read = _reader("paged_attn_roofline.chat")
    assert read(_obs()) == pytest.approx(100 * 25.6 / 64)
    # an int8 pool is asked for half the bytes
    assert read(_obs(kv_dtype="int8")) == pytest.approx(100 * 12.8 / 64)
    # 64,000 x 4 FLOPs at 8 GFLOP/s = 32 us: now the larger bound
    assert read(_obs(peaks=FLOPS_BOUND)) == pytest.approx(100 * 32 / 64)
    # a float32 pool is held to bf16's bytes too: the same work
    assert read(_obs(kv_dtype="f32")) == read(_obs(kv_dtype="bf16"))


@pytest.mark.parametrize("name", READERS)
def test_nothing_to_read_is_none_and_never_raises(name, monkeypatch):
    """Without a trace, without steps, and on a program from before PR 25
    (no `generation.*` event, no such counter) a reader returns None."""
    from deeplearning4j_tpu.observe import metrics

    read = _reader(name + ".chat")
    old = metrics.MetricsRegistry()                # declares none of the three
    monkeypatch.setattr(metrics, "registry", lambda: old)
    assert read(_obs(trace=False)) is None
    recorded = _obs()
    bare = trace_reduce.Trace(
        recorded.trace.device_ops, recorded.trace.async_ops,
        recorded.trace.modules,
        [e for e in recorded.trace.host
         if not e.name.startswith("generation.")], recorded.trace.window_ns)
    assert read(Observations(cell=recorded.cell, family=None, device={},
                             peaks=BYTES_BOUND, trace=bare)) is None
    idle = metrics.MetricsRegistry()               # declared, no step yet
    for fam in (engine_thread.STEPS, engine_thread.SLOT_STEPS,
                engine_thread.ROWS):
        idle.counter(fam)
    monkeypatch.setattr(metrics, "registry", lambda: idle)
    if name in ("decode_slots_mean", "paged_attn_roofline"):
        assert read(recorded) is None
    assert read(Observations(cell=recorded.cell, family=None, device={},
                             peaks=None, trace=None)) is None


def test_the_gaps_of_the_device_take_the_engines_names():
    """What the new spans are for: the idle time between two steps'
    programs is given to a phase of the engine's loop where one covers
    half of it, no longer to "(no host event)"."""
    # the one gap: 1 ms from the first step's last op (31.25 ms) to the
    # second's first (32.25 ms); the harvest (31.3-31.9 ms) covers 60 % of it
    assert trace_reduce.idle_gaps(_obs().trace) == [
        ["generation.harvest", pytest.approx(1.0e-3)]]


def test_eight_entries_and_their_readers():
    doc = spec.load(ROOT)
    entries = {m["name"]: m for m in doc.doc["per_layer"]}
    for name in READERS:
        chat, sat = entries[name + ".chat"], entries[name + ".sat"]
        assert chat["moves"] == "itl_p90_ms" and sat["moves"] == "serve_tok_s"
        assert chat["workloads"] == ["serve_chat"]
        assert sat["workloads"] == ["serve_longprompt_sat"]
        assert callable(doc.reader(chat["name"]))
    assert entries["decode_slots_mean.chat"]["better"] == "lower"
    assert entries["decode_slots_mean.sat"]["better"] == "higher"
    assert list(entries)[-8:] == [n + v for n in READERS
                                  for v in (".chat", ".sat")]
