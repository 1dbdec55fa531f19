"""The benchmark's own arithmetic: the traffic generator, percentiles, the
trace reduction on a recorded trace, the table of peaks, and the contract
of BENCHMARK.json.  No model runs here (see test_benchmark_rehearsal.py)."""

import json
import os
import re
import time

import numpy as np
import pytest

from benchmarks.harness import device, serve, spec, stats, trace_reduce
from benchmarks.harness import traffic as tg
from benchmarks.harness.observe import Stream

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
HERE = os.path.dirname(os.path.abspath(__file__))

CHAT = {
    "arrivals": {"rate_per_s": 3.0, "cv": 1.0},
    "schedule_seed": 7,
    "classes": [{"prompt_len": {"dist": "lognormal", "median": 192,
                                "sigma": 0.8, "min": 32, "max": 1024},
                 "output_len": {"dist": "uniform", "min": 32, "max": 160}}],
    "engine": {"page_size": 16, "prefill_quantum": 256},
}


# -- generator -----------------------------------------------------------------

def test_same_seed_same_requests_other_schedule_seed_other_schedule():
    a = tg.serve_requests(CHAT, 50257, 7, 40)
    b = tg.serve_requests(CHAT, 50257, 7, 40)
    c = tg.serve_requests({**CHAT, "schedule_seed": 8}, 50257, 7, 40)
    assert [r.due_s for r in a] == [r.due_s for r in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    assert [r.max_new for r in a] == [r.max_new for r in b]
    assert [r.due_s for r in a] != [r.due_s for r in c]


def test_the_schedule_repeats_and_only_the_contents_follow_the_seed():
    a = tg.serve_requests(CHAT, 50257, 1, 40)
    b = tg.serve_requests(CHAT, 50257, 2, 40)
    assert [r.due_s for r in a] == [r.due_s for r in b]
    assert [len(r.prompt) for r in a] == [len(r.prompt) for r in b]
    assert [r.max_new for r in a] == [r.max_new for r in b]
    assert not any(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    with pytest.raises(KeyError):       # a serving mix must fix its schedule
        tg.serve_requests({k: v for k, v in CHAT.items()
                           if k != "schedule_seed"}, 50257, 1, 40)


def test_every_schedule_offers_the_same_work():
    runs = [tg.serve_requests({**CHAT, "schedule_seed": s}, 50257, 1, 40)
            for s in range(5)]
    assert {len(r) for r in runs} == {120}          # round(rate x seconds)
    for reqs in runs:
        due = [r.due_s for r in reqs]
        assert due == sorted(due) and 0 <= due[0] and due[-1] < 40
        assert all(32 <= len(r.prompt) <= 1024 for r in reqs)
        assert all(32 <= r.max_new <= 160 for r in reqs)
        assert all(r.prompt.dtype == np.int32 and r.prompt.max() < 50257
                   for r in reqs)
    prompt_tokens = [sum(len(r.prompt) for r in reqs) for reqs in runs]
    # stratified lengths: totals agree to well under a percent
    assert max(prompt_tokens) - min(prompt_tokens) < 0.01 * min(prompt_tokens)
    # and the median is the distribution's
    assert 170 <= np.median([len(r.prompt) for r in runs[0]]) <= 215


def test_bursty_arrivals_keep_the_count_and_widen_the_gaps():
    rng = np.random.default_rng(0)
    smooth = np.diff(tg.arrival_times(rng, {"rate_per_s": 50, "cv": 1.0}, 20))
    bursty = np.diff(tg.arrival_times(rng, {"rate_per_s": 50, "cv": 3.0}, 20))
    assert len(smooth) == len(bursty) == 999
    assert np.std(bursty) > 2 * np.std(smooth)


def test_mixture_classes_keep_their_weights():
    mix = {
        "arrivals": {"rate_per_s": 10.0},
        "schedule_seed": 3,
        "classes": [
            {"weight": 3, "prompt_len": {"dist": "uniform", "min": 64,
                                         "max": 64},
             "output_len": {"dist": "uniform", "min": 4, "max": 4}},
            {"weight": 1, "prompt_len": {"dist": "uniform", "min": 500,
                                         "max": 600},
             "output_len": {"dist": "uniform", "min": 8, "max": 8}}],
        "engine": {"page_size": 16, "prefill_quantum": 128},
    }
    reqs = tg.serve_requests(mix, 1000, 3, 10)
    assert [sum(r.klass == k for r in reqs) for k in (0, 1)] == [75, 25]
    assert all(len(r.prompt) == 64 and r.max_new == 4
               for r in reqs if r.klass == 0)
    assert tg.prefill_buckets(mix) == [128, 512, 640]
    assert tg.longest_stream(mix) == 640


def test_buckets_and_longest_stream_of_the_chat_mix():
    assert tg.prefill_buckets(CHAT) == [256, 512, 768, 1024]
    assert tg.longest_stream(CHAT) == 1024 + 160


# -- timing from the due time ----------------------------------------------------

class _InstantEngine:
    """Answers every request at once, `max_new` tokens, on the caller's
    thread."""

    class kv:
        used_pages = 0

    class queue:
        depth = 0

    def __init__(self):
        self.sent = []

    def submit(self, prompt, max_new, on_token=None):
        self.sent.append(time.perf_counter())
        for i in range(max_new):
            on_token(0, i)

        class Handle:
            done, error = True, None
        return Handle()

    def drain(self, timeout):
        return True


def test_requests_go_out_at_their_due_times_and_are_timed_from_them():
    rig = serve.ServeRig.__new__(serve.ServeRig)
    rig.engine = _InstantEngine()
    reqs = [tg.Request(due_s=d, prompt=np.zeros(4, np.int32), max_new=3,
                       klass=0) for d in (0.05, 0.10, 0.22)]
    w = rig.window(reqs, 1.0)
    # never early; how late is the host's doing (other tests share its
    # cores), and is reported, not hidden
    late = [sent - s.due for s, sent in zip(w["streams"], rig.engine.sent)]
    assert all(0 <= x < 0.5 for x in late)
    out = serve.summarize(w["streams"], w["t0"], w["close"])
    assert out["requests"] == out["completed"] == 3 and out["failed"] == 0
    assert out["generator_late_ms_max"] == pytest.approx(1e3 * max(late),
                                                        abs=1.0)
    # timed from the due time: the generator's lateness is inside the TTFT
    assert 1e3 * max(late) <= out["ttft_p99_ms"] < 1e3 * max(late) + 50
    # completed tokens over the span in which they completed
    last = max(s.stamps[-1] for s in w["streams"])
    assert out["serve_tok_s"] == pytest.approx(3 * (4 + 3) / (last - w["t0"]))
    assert out["completed_per_s"] == pytest.approx(3 / 1.0)


def test_a_stall_is_charged_to_the_request_it_delays():
    t0 = 100.0
    fast = Stream(due=t0 + 0.0, prompt_len=10, max_new=2,
                  stamps=[t0 + 0.1, t0 + 0.2])
    held = Stream(due=t0 + 0.1, prompt_len=10, max_new=2, sent=t0 + 0.6,
                  stamps=[t0 + 0.7, t0 + 0.8])       # sent late: still 600 ms
    silent = Stream(due=t0 + 0.5, prompt_len=10, max_new=2, stamps=[t0 + 1.5])
    out = serve.summarize([fast, held, silent], t0, t0 + 1.0)
    assert out["ttft_p50_ms"] == pytest.approx(600.0)
    assert out["ttft_p99_ms"] == float("inf")    # no first token by the close
    assert out["completed"] == 2
    assert out["serve_tok_s"] == pytest.approx(2 * 12 / 0.8)   # last at +0.8
    assert out["tok_s_by_window"] == pytest.approx(2 * 12 / 1.0)
    assert out["token_gaps"] == 2
    assert out["generator_late_ms_max"] == pytest.approx(500.0)


# -- percentiles -----------------------------------------------------------------

def test_percentile_is_nearest_rank():
    xs = list(range(1, 101))
    assert stats.percentile(xs, 50) == 50
    assert stats.percentile(xs, 90) == 90
    assert stats.percentile(xs, 99) == 99
    assert stats.percentile(xs, 100) == 100
    assert stats.percentile([5.0], 99) == 5.0
    assert stats.percentile([1, 2, float("inf")], 50) == 2
    assert stats.percentile([1, 2, float("inf")], 90) == float("inf")
    with pytest.raises(ValueError):
        stats.percentile([], 50)
    with pytest.raises(ValueError):
        stats.percentile([1], 0)


def test_spread_is_interquartile_range_over_median():
    assert stats.spread([10, 10, 10, 10, 10, 10]) == 0
    assert stats.spread([98, 99, 100, 100, 101, 102]) == pytest.approx(
        (100.75 - 99.25) / 100)


# -- peaks ----------------------------------------------------------------------

def test_unknown_device_kind_raises():
    assert device.peaks("TPU v5 lite") == (197.0e12, 8.19e11, 16.0e9)
    with pytest.raises(device.UnknownDeviceKind):
        device.peaks("TPU v9 ultra")
    with pytest.raises(device.UnknownDeviceKind):
        device.peaks("cpu")


def test_without_a_tpu_the_device_check_exits():
    with pytest.raises(SystemExit) as err:
        device.require_tpu(1)
    assert err.value.code not in (0, None)


# -- the trace reduction ---------------------------------------------------------------

DEV0, HOST = "/device:TPU:0", "/host:CPU"
FLASH = ('%transpose_jvp___.12 = f32[64,2048,128]{2,1,0:T(8,128)} custom-call('
         'f32[64,2048,128]{2,1,0:T(8,128)} %bitcast.1438), '
         'custom_call_target="tpu_custom_call", operand_layout_constraints={}')
FUSION = ('%fusion.788 = (f32[2048,2048]{1,0:T(8,128)}, f32[2048,2048]{1,0}) '
          'fusion(f32[4,2048,2048]{2,1,0:T(8,128)S(1)} %custom-call.22), '
          'kind=kOutput, calls=%fused_computation.877')
WHILE = ('%while.4 = (s32[]{:T(128)}, f32[8192,2048]{1,0:T(8,128)}) while('
         '(s32[]{:T(128)}, f32[8192,2048]{1,0:T(8,128)}) %tuple.308), '
         'condition=%c, body=%b')


def _ev(plane, line, name, start, dur):
    return trace_reduce.Event(plane, line, name, float(start), float(dur))


def test_hlo_text_names_are_parsed():
    assert trace_reduce.parse_op(FLASH) == (
        "transpose_jvp___.12", "f32[64,2048,128]{2,1,0:T(8,128)}",
        "custom-call")
    assert trace_reduce.parse_op(FUSION)[2] == "fusion"
    assert trace_reduce.parse_op(WHILE)[2] == "while"
    assert trace_reduce.parse_op("jit_step(123)") == ("jit_step(123)", "", "")
    flash = _ev(DEV0, "XLA Ops", FLASH, 0, 1)
    fusion = _ev(DEV0, "XLA Ops", FUSION, 0, 1)
    assert flash.is_pallas and flash.label == (
        "transpose_jvp___.12 custom-call f32[64,2048,128]")
    # a fusion that READS a custom call is not one
    assert not fusion.is_pallas and fusion.label == (
        "fusion.788 fusion f32[2048,2048]")
    done = _ev(DEV0, "XLA Ops", "%all-reduce-done.3 = f32[8]{0} "
               "all-reduce-done(f32[8]{0} %all-reduce-start.3)", 0, 1)
    assert done.is_collective and not fusion.is_collective


def test_busy_idle_and_gap_attribution_on_hand_made_events():
    events = [
        _ev(DEV0, "XLA Modules", "jit_step(77)", 0, 1000),  # a program, no op
        _ev(DEV0, "XLA Ops", WHILE, 0, 450),                # wraps the next two
        _ev(DEV0, "XLA Ops", FUSION, 0, 300),
        _ev(DEV0, "XLA Ops", FLASH, 250, 150),
        _ev(DEV0, "XLA Ops", FUSION, 700, 300),
        _ev(HOST, "main", "PjitFunction(step)", 380, 400),
        _ev(HOST, "main", "Execute", 420, 200),
        _ev(HOST, "other", "unrelated", 5000, 10),
    ]
    tr = trace_reduce.build(events)
    assert trace_reduce.busy_seconds(tr) == pytest.approx(750e-9)
    assert trace_reduce.window_seconds(tr) == pytest.approx(1000e-9)
    assert trace_reduce.window_seconds(tr, 2.5) == 2.5   # the caller's wall
    pallas = lambda e: e.is_pallas
    assert trace_reduce.op_seconds(tr, pallas) == pytest.approx(150e-9)
    assert trace_reduce.op_count(tr, pallas) == 1
    assert trace_reduce.module_seconds(tr, "step") == [pytest.approx(1e-6)]
    # containers are left out of the ranking: their bodies are listed
    assert trace_reduce.top_ops(tr, 5) == [
        ["fusion.788 fusion f32[2048,2048]", pytest.approx(600e-9)],
        ["transpose_jvp___.12 custom-call f32[64,2048,128]",
         pytest.approx(150e-9)]]
    # the gap 450..700: PjitFunction covers all of it, Execute 170 of it
    assert trace_reduce.idle_gaps(tr) == [
        ["PjitFunction(step)", pytest.approx(250e-9)]]


def test_innermost_host_event_wins_a_tie_and_uncovered_gaps_are_named():
    events = [
        _ev(DEV0, "XLA Ops", "a", 0, 100),
        _ev(DEV0, "XLA Ops", "b", 300, 100),
        _ev(DEV0, "XLA Ops", "c", 900, 100),
        _ev(HOST, "t", "outer", 50, 400),
        _ev(HOST, "t", "inner", 90, 250),
    ]
    gaps = dict(trace_reduce.idle_gaps(trace_reduce.build(events)))
    # 400..900 is covered by `outer` for 50 of 500: under half, so nobody's
    assert gaps == {"inner": pytest.approx(200e-9),
                    "(no host event)": pytest.approx(500e-9)}


def test_two_devices_average_and_collectives_read_the_first():
    start = "%all-reduce-start.1 = f32[8]{0} all-reduce-start(f32[8]{0} %p)"
    done = "%all-reduce-done.1 = f32[8]{0} all-reduce-done(f32[8]{0} %s)"
    events = [
        _ev(DEV0, "XLA Ops", start, 0, 10),
        _ev(DEV0, "XLA Ops", FUSION, 10, 390),
        _ev(DEV0, "XLA Ops", done, 400, 200),
        _ev(DEV0, "Async XLA Ops", start, 0, 600),
        _ev("/device:TPU:1", "XLA Ops", FUSION, 0, 400),
    ]
    tr = trace_reduce.build(events)
    assert trace_reduce.busy_seconds(tr) == pytest.approx(500e-9)
    collective = lambda e: e.is_collective
    assert trace_reduce.async_seconds(tr, collective) == pytest.approx(600e-9)
    assert trace_reduce.op_seconds(
        tr, lambda e: e.is_collective and not e.kind.endswith("-start")
    ) == pytest.approx(200e-9)


def test_events_round_trip_through_json(tmp_path):
    events = [_ev(DEV0, "XLA Ops", FUSION, 1, 2)]
    path = str(tmp_path / "t.json")
    trace_reduce.events_to_json(events, path)
    assert trace_reduce.events_from_json(path) == events


def _recorded(name):
    return trace_reduce.build(trace_reduce.events_from_json(
        os.path.join(HERE, "data", name)))


def test_reduction_of_a_recorded_train_step():
    """700 device ops of a `train_2k` step and the host events beside them,
    recorded on a TPU v5e in PR 22 (names cut to what parse_op reads; see
    data/README.md).  Expected values were summed by hand-written loops over
    the same file."""
    tr = _recorded("recorded_train_step_v5e.json")
    assert trace_reduce.busy_seconds(tr) == pytest.approx(0.127181908)
    assert trace_reduce.window_seconds(tr) == pytest.approx(0.127182789)
    pallas = lambda e: e.is_pallas
    assert trace_reduce.op_count(tr, pallas) == 4        # flash kernels
    assert trace_reduce.op_seconds(tr, pallas) == pytest.approx(0.029014372)
    top = trace_reduce.top_ops(tr, 3)
    assert top[0] == ["fusion.1364 fusion f32[8192]",
                      pytest.approx(0.013725474)]
    assert all("while" not in name for name, _ in top)
    # a training step leaves the chip no gap worth a name
    assert sum(s for _, s in trace_reduce.idle_gaps(tr)) < 2e-6


def test_reduction_of_recorded_decode_steps():
    """1400 device ops (a little over one `serve_chat` decode step) recorded
    on a TPU v5e in PR 22."""
    tr = _recorded("recorded_decode_steps_v5e.json")
    busy, window = (trace_reduce.busy_seconds(tr),
                    trace_reduce.window_seconds(tr))
    assert busy == pytest.approx(0.04772908)
    assert 100 * (1 - busy / window) == pytest.approx(6.1866, abs=1e-3)
    pallas = lambda e: e.is_pallas
    assert trace_reduce.op_count(tr, pallas) == 25       # paged kernel calls
    assert trace_reduce.op_seconds(tr, pallas) == pytest.approx(0.003337944)
    # the undonated whole-pool copies lead, and the gap belongs to the
    # blocking readback
    assert [n for n, _ in trace_reduce.top_ops(tr, 2)] == [
        "copy.310 copy f32[24,650,16,16,128]",
        "copy.309 copy f32[24,650,16,16,128]"]
    assert trace_reduce.idle_gaps(tr)[0] == [
        "np.asarray(jax.Array)", pytest.approx(0.003146763)]


# -- BENCHMARK.json keeps to its contract --------------------------------------------

NAME = re.compile(r"^[A-Za-z0-9][A-Za-z0-9_.\-]{0,63}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def test_benchmark_json_keeps_to_the_contract():
    doc = spec.load(ROOT).doc
    assert set(doc) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024
    assert 1 <= doc["run_seconds"] <= 51
    names = ([c["name"] for c in doc["configs"]]
             + [w["name"] for w in doc["workloads"]]
             + [m["name"] for m in doc["end_to_end"] + doc["per_layer"]])
    assert len(names) == len(set(names)) and all(NAME.match(n) for n in names)
    assert all(len(x["why"]) <= 200
               for x in doc["configs"] + doc["workloads"])
    cells = doc["workloads"]
    assert 2 <= len(cells) <= 24
    assert len({(w["config"], w["traffic"]) for w in cells}) == len(cells)
    assert {w["config"] for w in cells} == {c["name"] for c in doc["configs"]}
    assert sum(w["chips"] == 4 for w in cells) <= max(1, len(cells) // 4)
    for c in doc["configs"]:
        assert c["file"].startswith(tuple(p + "/" for p in doc["paths"]))
        with open(os.path.join(ROOT, c["file"])) as f:
            conf = json.load(f)
        assert conf["reduced"] == c["reduced"]
        assert all(k in conf for k in ("source", "assumed", "departures"))
        assert not any(k.endswith(("_dim", "_rank")) or k in (
            "n_embd", "n_inner", "n_head") for k in c["reduced"])
    e2e = {m["name"]: m for m in doc["end_to_end"]}
    assert e2e["setup_s"]["bound"] == 0.1
    for m in doc["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in {"host_clock", "device_trace"}
    for m in doc["per_layer"]:
        assert m["source"] in SOURCES and m["moves"] in e2e and "bound" not in m
        assert m["unit"] == "%" or not m["name"].endswith("_roofline")


def test_every_cell_finds_its_files_and_reports_something():
    doc = spec.load(ROOT)
    for cell in doc.cells:
        assert cell.traffic["kind"] in ("train", "serve")
        assert spec.family(cell.config, doc.home).build_model
        e2e = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert cell.per_layer
        for m in cell.per_layer:
            assert m["moves"] in e2e
            assert callable(doc.reader(m["name"]))
