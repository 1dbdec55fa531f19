"""ISSUE 25 — the engine thread on the profiler's clock.

Every phase of the decode loop is a recorder span, so a `jax.profiler`
session (here on the CPU, ring DISABLED) shows them on the engine
thread's line of "/host:CPU": the admission spans nested, a turn's
four spans in a row, and the thread's time tiled without holes.  Each
step also counts the slots, the KV rows and the pool pages it served.

ISSUE 36 — a turn of the loop is ``decode_prepare`` and
``decode_dispatch`` of step n + 1, then ``decode_readback`` and
``harvest`` of step n: the dispatch of a step ends before the readback
of the step before it starts, and two more counters say how often.

ISSUE 37 — the admission path is tiled too: a step landed so the host
can act is a ``generation.drain {reason}`` around its readback and
harvest, the queue's hand-over while streams are live is
``generation.take``, a prefill is its programs' ``prefill_dispatch`` and
the ``prefill_readback`` of its first token, and ``first_token`` seats
the stream; the drains are counted by reason, and the engine thread sets
no gauge per step."""

import threading
import time

import numpy as np
import pytest

from deeplearning4j_tpu.observe import tracer
from deeplearning4j_tpu.observe.metrics import registry
from deeplearning4j_tpu.serving import generation as gen_mod
from deeplearning4j_tpu.serving.admission import ServingRejected
from deeplearning4j_tpu.serving.generation import (
    DECODE_COUNT_FAMILIES,
    DECODE_LOOKAHEAD_FAMILIES,
    GEN_BREAKDOWN_SEGMENTS,
    GenerationConfig,
    GenerationEngine,
)
from deeplearning4j_tpu.zoo.transformer import TransformerEncoder

pytestmark = pytest.mark.generation

VOCAB = 31
CFG = dict(slots=4, page_size=8, num_pages=64, max_pages_per_seq=4,
           max_queue=16, default_max_new=8)

STEP_SPANS = ["generation.decode_prepare", "generation.decode_dispatch",
              "generation.decode_readback", "generation.harvest"]
ADMIT_SPANS = ["generation.refill", "generation.admit_to_slot",
               "generation.prefill", "generation.prefill_dispatch",
               "generation.prefill_readback", "generation.kv_handoff",
               "generation.first_token"]
# around a readback and harvest when a step is landed so the host can act
DRAIN = "generation.drain"
TOP_LEVEL = set(STEP_SPANS) | {"generation.wait_for_work",
                               "generation.refill", "generation.take",
                               DRAIN}
# what tiles an admission, from its drain (or take) to the next dispatch
ADMISSION_LEAVES = {"generation.decode_readback", "generation.harvest",
                    "generation.take", "generation.prefill_dispatch",
                    "generation.prefill_readback", "generation.kv_handoff",
                    "generation.first_token", "generation.decode_prepare"}


@pytest.fixture(scope="module")
def model():
    return TransformerEncoder(
        vocab_size=VOCAB, d_model=16, n_heads=2, n_layers=2,
        causal=True, seed=5,
    ).init_model()


def _engine(model, **over):
    return GenerationEngine(
        model=model, config=GenerationConfig(**{**CFG, **over}))


def _prompt(n, seed=0):
    return np.random.default_rng(seed).integers(
        0, VOCAB, n).astype(np.int32)


def _inside(inner, outer):
    return outer["start"] <= inner["start"] and inner["end"] <= outer["end"]


def _holes(events, lo, hi):
    """What no event of ``events`` covers between ``lo`` and ``hi``."""
    covered, reach = 0, lo
    for e in sorted(events, key=lambda e: e["start"]):
        s, t = max(e["start"], reach), min(e["end"], hi)
        if t > s:
            covered += t - s
            reach = t
    return (hi - lo) - covered


@pytest.fixture(scope="module")
def session(model, tmp_path_factory):
    """The engine thread's line of one profiler session (``events``): a
    warm engine (no compile inside the session) serves 3 streams with the
    ring disabled, then stops; and what the engine counted (``stats``)."""
    from conftest import HostProfile

    assert not tracer().enabled
    # streams long enough (39 steps) that one preemption of the thread
    # between two spans stays a small share of the interval
    eng = _engine(model, max_pages_per_seq=8).start()
    try:
        eng.generate(_prompt(5, seed=9), 3, timeout=120.0)     # warm
        warm = eng.stats()
        with HostProfile(str(tmp_path_factory.mktemp("prof"))) as prof:
            reqs = [eng.submit(_prompt(4 + i, seed=i), 20 + 10 * i)
                    for i in range(3)]
            for r in reqs:
                r.result(120.0)
            time.sleep(0.05)        # the idle loop goes back to sleep
            eng.stop()
        stats = {k: v - warm[k] for k, v in eng.stats().items()
                 if k.startswith("decode_") and isinstance(v, int)}
    finally:
        eng.stop()
    # ONE line holds every generation span: the engine thread's (named
    # after the process, not the thread — found by what it holds)
    line = prof.line_with("generation.decode_dispatch")
    for other in prof.lines:
        if other is not line:
            assert not any(e["name"].startswith("generation.")
                           for e in other)
    return {"events": [e for e in line
                       if e["name"].startswith("generation.")],
            "stats": stats}


@pytest.fixture(scope="module")
def engine_line(session):
    return session["events"]


def _named(events, phase):
    return [e for e in events if e["name"] == "generation." + phase]


class TestEngineThreadOnTheProfilersClock:
    def test_every_span_of_the_loop_is_on_the_engine_line(self, engine_line):
        names = {e["name"] for e in engine_line}
        # a take and an admission's drain appear where a submit found
        # streams live, which the timing of three submits decides
        assert set(STEP_SPANS) | set(ADMIT_SPANS) | {
            "generation.wait_for_work", DRAIN} <= names
        assert names <= TOP_LEVEL | set(ADMIT_SPANS)

    def test_step_spans_come_in_turns_and_disjoint(self, engine_line):
        """A turn is prepare + dispatch, readback + harvest, or all four
        in that order: a prepare is followed by its dispatch, a readback
        by its harvest, and nothing lies around any of them."""
        steps = [e for e in engine_line if e["name"] in STEP_SPANS]
        count = {n: sum(e["name"] == n for e in steps) for n in STEP_SPANS}
        assert len(set(count.values())) == 1       # every step: all four
        assert count[STEP_SPANS[0]] >= 39          # the longest stream
        follows = dict(zip(STEP_SPANS[::2], STEP_SPANS[1::2]))
        for a, b in zip(steps, steps[1:]):
            assert a["end"] <= b["start"]
            if a["name"] in follows:
                assert b["name"] == follows[a["name"]]
            else:       # a turn ended: the next starts with either half
                assert b["name"] in follows
        assert steps[0]["name"] == STEP_SPANS[0]
        assert steps[-1]["name"] == STEP_SPANS[3]
        # no span lies around a step's four but a drain, and a drain lies
        # around exactly one readback and its harvest
        for e in steps:
            around = [o for o in engine_line
                      if o is not e and _inside(e, o)]
            assert all(o["name"] == DRAIN for o in around)
            assert len(around) <= 1
            assert not around or e["name"] in STEP_SPANS[2:]
        for d in _named(engine_line, "drain"):
            held = [e["name"] for e in steps if _inside(e, d)]
            assert held == STEP_SPANS[2:]
            assert d["stats"]["reason"] in gen_mod.DRAIN_REASONS
        # the last stream ends with a step in flight: that step is landed
        # with nothing left to build
        assert _named(engine_line, "drain")[-1]["stats"]["reason"] == "idle"

    def test_dispatch_of_the_next_step_ends_before_the_readback(
            self, session):
        """Step k is read back by the k-th readback.  It was dispatched
        before that, and — where the lookahead engaged — so was step
        k + 1: as many steps as the engine says it overlapped."""
        events, stats = session["events"], session["stats"]
        disp, back = _named(events, "decode_dispatch"), _named(
            events, "decode_readback")
        assert len(disp) == len(back) == stats["decode_steps"]
        for d, r in zip(disp, back):
            assert d["end"] <= r["start"]
        ahead = sum(d["end"] <= r["start"]
                    for d, r in zip(disp[1:], back))
        assert ahead == stats["decode_steps_overlapped"]
        # one refill admits the three streams; nothing waits after it, so
        # only the first step was built with nothing in flight
        assert ahead == stats["decode_steps"] - 1 == 38
        assert stats["decode_slot_steps_discarded"] == 0
        # a steady turn in full: prepare, dispatch, readback, harvest
        names = [e["name"] for e in events if e["name"] in STEP_SPANS]
        assert names[2:6] == STEP_SPANS

    def test_admission_spans_nest(self, engine_line):
        by = {n: [e for e in engine_line if e["name"] == n]
              for n in ADMIT_SPANS}
        for n in ("admit_to_slot", "prefill", "prefill_dispatch",
                  "prefill_readback", "kv_handoff", "first_token"):
            assert len(by["generation." + n]) == 3, n
        for adm in by["generation.admit_to_slot"]:
            assert sum(_inside(adm, r) for r in by["generation.refill"]) == 1
            kids = [[k for k in by["generation." + n] if _inside(k, adm)]
                    for n in ("prefill", "kv_handoff", "first_token")]
            assert [len(k) for k in kids] == [1, 1, 1]
            pre, hand, first = (k[0] for k in kids)
            assert pre["end"] <= hand["start"]
            assert hand["end"] <= first["start"]
            # the prefill: its program's dispatch, then its first token
            disp, back = ([k for k in by["generation." + n]
                           if _inside(k, pre)]
                          for n in ("prefill_dispatch", "prefill_readback"))
            assert len(disp) == len(back) == 1
            assert disp[0]["end"] <= back[0]["start"]
            assert disp[0]["stats"]["bucket"] == pre["stats"]["bucket"]
            assert "slot" in adm["stats"]
        assert all("bucket" in p["stats"] for p in by["generation.prefill"])
        assert all(r["stats"]["taken"] >= 1
                   for r in by["generation.refill"])

    def test_dispatch_says_what_the_step_served(self, engine_line):
        disp = [e for e in engine_line
                if e["name"] == "generation.decode_dispatch"]
        for e in disp:
            slots, rows = int(e["stats"]["slots"]), int(e["stats"]["rows"])
            assert 1 <= slots <= CFG["slots"]
            # every live slot attends its prompt and the row it writes
            assert rows >= slots * (4 + 1)
        assert max(int(e["stats"]["slots"]) for e in disp) >= 2

    def test_top_level_spans_tile_the_thread(self, engine_line):
        """The guard against a later unmarked phase: between the first
        admission and the last finish, what no top-level span covers is
        under 10 % of the interval (the loop's top between two turns, some
        50 us here; since the host's work lies under the device's the
        interval is the steps' time alone, and at this toy size that is a
        millisecond a step)."""
        top = [e for e in engine_line if e["name"] in TOP_LEVEL]
        first = min(e["start"] for e in top
                    if e["name"] == "generation.refill")
        last = max(e["end"] for e in top
                   if e["name"] == "generation.harvest")
        inside = [e for e in top if e["start"] >= first and e["end"] <= last]
        holes = _holes(inside, first, last)
        assert inside[0]["start"] == first
        assert max(e["end"] for e in inside) == last
        assert holes < 0.10 * (last - first), (holes, last - first)


class TestRingAndLatencyKeepTheirMeaning:
    def test_chains_and_six_segments_with_the_ring_on(self, model):
        rec = tracer()
        eng = _engine(model).start()
        try:
            eng.generate(_prompt(5, seed=9), 3, timeout=120.0)  # warm
            rec.enable()
            rec.clear()
            reqs = [eng.submit(_prompt(4 + i, seed=i), 6 + 2 * i)
                    for i in range(3)]
            for r in reqs:
                r.result(120.0)
            alone = eng.submit(_prompt(6, seed=7), 8)
            alone.result(120.0)
            reqs.append(alone)
            wall = {e["rid"]: e["latency_s"]
                    for e in eng.slow_streams(spans=False)}
        finally:
            eng.stop()
            rec.disable()
        ring = [e for e in rec.to_chrome_trace()["traceEvents"]
                if e["ph"] == "X"]
        rec.clear()
        for r in reqs:
            chain = rec_chain(ring, r.trace_id)
            names = [e["name"] for e in chain]
            assert names.count("generation.stream") == 1
            assert names.count("generation.admit") == 1
            assert names.count("generation.prefill") == 1
            assert names.count("generation.kv_handoff") == 1
            assert names.count("generation.decode_step") == r.max_new - 1
            assert set(GEN_BREAKDOWN_SEGMENTS) == set(r.lat)
            # the six segments never claim more than the stream's wall
            assert sum(r.lat.values()) <= wall[r.rid] + 1e-3
        # ... and account for it where the stream was admitted alone
        # (in a shared refill a stream also waits, unattributed, for
        # the admissions before its own)
        assert sum(alone.lat.values()) >= 0.9 * wall[alone.rid]
        # one timing per phase: the engine-thread span, the stream's
        # chain entry and its `lat` segment are the same number
        for name, seg in (("generation.prefill", "prefill"),
                          ("generation.kv_handoff", "handoff")):
            on_thread = sorted(e["dur"] for e in ring if e["name"] == name
                               and e["cat"] == "engine")
            in_chains = sorted(e["dur"] for e in ring if e["name"] == name
                               and e["cat"] == "generation")
            assert len(on_thread) == 4 and on_thread == in_chains
            assert sorted(round(r.lat[seg] * 1e6, 3)
                          for r in reqs) == on_thread
        # decode_compute = the dispatch + the readback of the SAME turn
        # (the turn less prepare and harvest): a readback's turn holds a
        # dispatch when that is the step span just before it
        engine = sorted((e for e in ring if e["name"] in STEP_SPANS),
                        key=lambda e: e["ts"])
        turns = [b["dur"] + (a["dur"] if a["name"] == STEP_SPANS[1] else 0.0)
                 for a, b in zip(engine, engine[1:])
                 if b["name"] == STEP_SPANS[2]]
        steps = {e["args"]["step"]: e["dur"] for e in ring
                 if e["name"] == "generation.decode_step"}
        assert len(turns) == len(steps)
        for n, turn in zip(sorted(steps), turns):
            assert abs(steps[n] - turn) < 1.0             # microseconds
        for r in reqs:
            rode = [e["dur"] for e in rec_chain(ring, r.trace_id)
                    if e["name"] == "generation.decode_step"]
            assert abs(sum(rode) - 1e6 * r.lat["decode_compute"]) < 1.0


def rec_chain(ring, trace_id):
    return [e for e in ring
            if (e.get("args") or {}).get("trace") == trace_id]


class _Oracle:
    """A drafter that knows the greedy continuation of every stream."""

    name = "oracle"

    def __init__(self, rows):
        self.rows = [np.asarray(r, np.int32) for r in rows]

    def draft(self, hist, k):
        for row in self.rows:
            if len(row) > len(hist) and np.array_equal(
                    row[:len(hist)], hist):
                return row[len(hist):len(hist) + k]
        return np.zeros(0, np.int32)


COUNT_FAMILIES = DECODE_COUNT_FAMILIES + DECODE_LOOKAHEAD_FAMILIES


def _run_scripted(eng, prompts, max_news, lookahead=False, **submit_kw):
    """Both streams are queued BEFORE the loop starts, so one refill
    admits both and the schedule is fixed by the lengths alone.  Returns
    the rows and the four step counts (``lookahead``: and the two of the
    lookahead), which the registry's families must have moved by."""
    reg = registry()
    reg.collect()
    before = [reg.counter(f).value() for f in COUNT_FAMILIES]
    reqs = [eng.submit(p, n, **submit_kw)
            for p, n in zip(prompts, max_news)]
    eng.start()
    try:
        rows = [np.asarray(r.result(120.0)) for r in reqs]
        assert eng.drain(timeout=30.0)
        st = eng.stats()
    finally:
        eng.stop()
    reg.collect()
    delta = [reg.counter(f).value() - b
             for f, b in zip(COUNT_FAMILIES, before)]
    counted = (st["decode_steps"], st["decode_slot_steps"],
               st["decode_rows_attended"], st["decode_pages_attended"],
               st["decode_steps_overlapped"],
               st["decode_slot_steps_discarded"])
    assert tuple(delta) == counted
    return rows, counted if lookahead else counted[:4]


class TestStepCounts:
    PROMPTS = (5, 3)
    MAX_NEW = (9, 5)

    def test_plain_and_speculative_steps_against_a_hand_count(self, model):
        prompts = [_prompt(n, seed=20 + n) for n in self.PROMPTS]
        rows, counted = _run_scripted(_engine(model), prompts, self.MAX_NEW)
        # plain: a stream of max_new n rides n - 1 steps; the step at
        # seq_len t attends t + 1 rows (the row it writes counts).
        # A: 8 steps, rows 6..13 = 76.  B: 4 steps, rows 4..7 = 22.
        # Pages of 8 rows: A's rows 6..8 lie in one page, 9..13 in two
        # (3 + 5 x 2); B's in one (4).
        assert counted == (8, 8 + 4, 76 + 22, 13 + 4)

        eng = _engine(model, spec_k=3)
        eng.drafter = _Oracle(rows)
        spec_rows, counted = _run_scripted(eng, prompts, self.MAX_NEW)
        for a, b in zip(rows, spec_rows):
            assert np.array_equal(a, b)
        # every draft is accepted, so a chunk of C = 4 emits 4 tokens.
        # step 1: A at seq_len 5 and B at 3 attend 5 + 4 and 3 + 4 rows
        # (each slot's rows once); B is done (1 + 4 = 5 tokens).
        # step 2: A alone at seq_len 9 attends 13; done (1 + 4 + 4).
        # pages: 9 rows in two and 7 in one, then 13 in two
        assert counted == (2, 2 + 1, (9 + 7) + 13, (2 + 1) + 2)
        assert eng.stats()["speculative"]["accepted"] == 9

    @pytest.mark.parametrize("loop", ["overlapped", "drained"])
    def test_lookahead_counts_against_a_hand_count(self, model, loop,
                                                   monkeypatch):
        """Steps built on one in flight + steps built after a drain =
        steps; a stop token costs the one row in flight behind it."""
        if loop == "drained":
            monkeypatch.setattr(GenerationEngine, "_must_drain",
                                lambda self: "drafter")
        flew = loop == "overlapped"
        prompts = [_prompt(n, seed=20 + n) for n in self.PROMPTS]
        # sampled: this model's greedy streams repeat one token
        kw = dict(temperature=1.0, seed=2)
        rows, counted = _run_scripted(_engine(model), prompts, self.MAX_NEW,
                                      lookahead=True, **kw)
        # one refill, 8 steps (A's; B rides the first 4): the first was
        # built with nothing in flight, and B's end by count at step 4 is
        # known before step 5 is built — nothing discarded
        assert counted == (8, 8 + 4, 76 + 22, 13 + 4, 7 * flew, 0)
        # A stops at its 5th token (4 steps harvested), B runs to its
        # count (4 steps): with the lookahead a 5th step was in flight —
        # A's row in it (seq_len 9: 10 rows, 2 pages) goes to nobody
        stop = int(rows[0][len(prompts[0]) + 4])
        assert stop not in rows[0][len(prompts[0]):-5]
        assert stop not in rows[1][len(prompts[1]):]
        cut, counted = _run_scripted(
            _engine(model), prompts, self.MAX_NEW, lookahead=True,
            stop_tokens=(stop,), **kw)
        assert np.array_equal(cut[0], rows[0][:len(prompts[0]) + 5])
        assert np.array_equal(cut[1], rows[1])
        assert counted == (
            4 + flew, 4 + 4 + flew, (6 + 7 + 8 + 9) + 22 + 10 * flew,
            (1 + 1 + 1 + 2) + 4 + 2 * flew, 4 * flew, 1 * flew)

    def test_counts_are_declared_and_engines_sum(self, model):
        reg = registry()
        text = reg.to_prometheus_text()
        for fam in COUNT_FAMILIES:
            assert f"# TYPE {fam} counter" in text
        reg.collect()
        before = [reg.counter(f).value() for f in DECODE_COUNT_FAMILIES]
        engines = [_engine(model).start() for _ in range(2)]
        try:
            for eng in engines:
                eng.generate(_prompt(4, seed=1), 3, timeout=120.0)
            reg.collect()       # live engines: the collector pulls
            mid = [reg.counter(f).value() for f in DECODE_COUNT_FAMILIES]
        finally:
            for eng in engines:
                eng.stop()
        reg.collect()           # stopped engines flushed, nothing twice
        after = [reg.counter(f).value() for f in DECODE_COUNT_FAMILIES]
        # each engine: one stream of 3 tokens = 2 one-slot steps, 5 + 6
        # rows, each time inside one page
        assert [m - b for m, b in zip(mid, before)] == [4, 4, 22, 4]
        assert after == mid

    def test_pages_attended_follow_the_live_pages(self, model):
        """`dl4jtpu_decode_pages_attended_total`: per step the pages
        that hold each live slot's rows, ceil(rows / page size) — what
        the paged kernel's loop visits.  An engine with nothing to
        decode adds nothing, and a slot that has finished adds nothing
        to the steps that follow."""
        pages = DECODE_COUNT_FAMILIES[3]
        assert pages == "dl4jtpu_decode_pages_attended_total"
        reg = registry()
        idle = _engine(model).start()
        try:
            time.sleep(0.05)
            st = idle.stats()
        finally:
            idle.stop()
        assert st["decode_pages_attended"] == 0 == st["decode_steps"]
        reg.collect()
        before = reg.counter(pages).value()
        # A: prompt 15, 4 tokens = 3 steps at 16, 17, 18 rows: 2, 3, 3
        # pages of 8.  B: prompt 7, 3 tokens = 2 steps at 8 and 9 rows:
        # 1 and 2 pages; its third step does not exist.
        _, counted = _run_scripted(
            _engine(model), [_prompt(15, seed=3), _prompt(7, seed=4)],
            (4, 3))
        assert counted == (3, 3 + 2, (16 + 17 + 18) + (8 + 9),
                           (2 + 3 + 3) + (1 + 2))
        reg.collect()
        assert reg.counter(pages).value() - before == 11
        # of the 3 steps x 4 slots x 4 pages the old grid visited
        assert counted[3] / (counted[0] * CFG["slots"]
                             * CFG["max_pages_per_seq"]) == 11 / 48
        assert gen_mod._collect_decode_counts in reg._collectors


def _wait_for(cond, timeout=60.0):
    t_end = time.monotonic() + timeout
    while not cond():
        assert time.monotonic() < t_end, "timed out"
        time.sleep(0.002)


class TestAnAdmissionIsTiled:
    """ONE admission while a stream decodes, in a profiler session: from
    the start of its drain to the start of the next step's dispatch, the
    leaves of the engine line cover all but under 10 %.  At a width whose
    admission takes the CPU a few milliseconds: what no leaf covers is
    some 60-120 us of bookkeeping and span entries (3-4 % here), which at
    the 16-wide toy's 1 ms would already be 6-8 %."""

    @pytest.fixture(scope="class")
    def admission(self, tmp_path_factory):
        from conftest import HostProfile

        wide = TransformerEncoder(
            vocab_size=VOCAB, d_model=128, n_heads=4, n_layers=4,
            causal=True, seed=5,
        ).init_model()
        eng = _engine(wide, max_pages_per_seq=8).start()
        try:
            for n in (5, 20):                   # warm both buckets
                eng.generate(_prompt(n, seed=9), 3, timeout=120.0)
            with HostProfile(str(tmp_path_factory.mktemp("adm"))) as prof:
                a = eng.submit(_prompt(6, seed=1), 40)
                _wait_for(lambda: len(a.tokens) >= 4)
                b = eng.submit(_prompt(20, seed=2), 6)
                a.result(120.0)
                b.result(120.0)
                eng.stop()
        finally:
            eng.stop()
        line = prof.line_with("generation.decode_dispatch")
        return [e for e in line if e["name"].startswith("generation.")]

    def test_a_drain_then_a_take_then_the_refill(self, admission):
        drains = _named(admission, "drain")
        assert [d["stats"]["reason"] for d in drains] == ["admit", "idle"]
        take, = _named(admission, "take")
        refill = [r for r in _named(admission, "refill")
                  if r["start"] >= take["end"]]
        assert drains[0]["end"] <= take["start"]
        assert int(take["stats"]["taken"]) == 1
        assert refill and int(refill[0]["stats"]["taken"]) == 1

    def test_the_children_of_admit_to_slot_tile_it(self, admission):
        """What prefill, handoff and first token leave uncovered of
        `admit_to_slot` (the allocation, the fault consult, the stream's
        bookkeeping) stays under 10 % of it: A's admission and B's."""
        adm = _named(admission, "admit_to_slot")
        assert len(adm) == 2
        kids = [e for e in admission if e["name"] in (
            "generation.prefill", "generation.kv_handoff",
            "generation.first_token")]
        holes = sum(_holes([k for k in kids if _inside(k, a)],
                           a["start"], a["end"]) for a in adm)
        whole = sum(a["end"] - a["start"] for a in adm)
        assert holes < 0.10 * whole, (holes, whole)

    def test_leaves_tile_it_from_the_drain_to_the_next_dispatch(
            self, admission):
        start = _named(admission, "drain")[0]["start"]
        end = min(d["start"] for d in _named(admission, "decode_dispatch")
                  if d["start"] > start)
        leaves = [e for e in admission if e["name"] in ADMISSION_LEAVES
                  and e["start"] >= start and e["end"] <= end]
        assert {e["name"] for e in leaves} == ADMISSION_LEAVES
        holes = _holes(leaves, start, end)
        assert holes < 0.10 * (end - start), (holes, end - start)


DRAINS, DRAIN_SECONDS = gen_mod.DECODE_DRAIN_FAMILIES


class TestDrainsByReason:
    """A hand count of the lookahead's drains on scripted schedules, by
    the engine's stats and by the registry's two families after a
    scrape."""

    @staticmethod
    def _counted(run):
        """The drains ``run()`` made (it returns the engine it ran), by
        reason, as the engine's stats and the registry's counters (moved
        by a scrape) say."""
        reg = registry()
        reg.collect()
        before = {r: (reg.counter(DRAINS).value(reason=r),
                      reg.counter(DRAIN_SECONDS).value(reason=r))
                  for r in gen_mod.DRAIN_REASONS}
        eng = run()
        st = eng.stats()["decode_drains"]
        reg.collect()
        for r, (n, secs) in before.items():
            assert reg.counter(DRAINS).value(reason=r) - n == \
                st[r]["count"]
            assert reg.counter(DRAIN_SECONDS).value(reason=r) - secs == \
                pytest.approx(st[r]["seconds"], abs=1e-5)
            assert (st[r]["seconds"] > 0) == (st[r]["count"] > 0)
        return {r: st[r]["count"] for r in gen_mod.DRAIN_REASONS}

    def test_an_arrival_mid_stream_and_the_last_end(self, model):
        """A decodes; B arrives: ONE drain for its admission.  B ends by
        count while A goes on: no drain.  A ends with the step in flight
        and nothing waits: one drain with nothing left to build."""
        def run():
            eng = _engine(model, max_pages_per_seq=8).start()
            try:
                a = eng.submit(_prompt(6, seed=1), 30)
                _wait_for(lambda: len(a.tokens) >= 4)
                b = eng.submit(_prompt(5, seed=2), 5)
                b.result(120.0)
                a.result(120.0)
                assert eng.drain(timeout=30.0)
            finally:
                eng.stop()
            return eng

        assert self._counted(run) == {"admit": 1, "drafter": 0, "stop": 0,
                                      "idle": 1}

    def test_a_stop_mid_stream(self, model):
        """stop() while a stream decodes lands the step in flight once."""
        def run():
            eng = _engine(model, max_pages_per_seq=8).start()
            a = eng.submit(_prompt(6, seed=1), 50)
            _wait_for(lambda: len(a.tokens) >= 4)
            eng.stop()
            with pytest.raises(ServingRejected):
                a.result(5.0)
            return eng

        assert self._counted(run) == {"admit": 0, "drafter": 0, "stop": 1,
                                      "idle": 0}

    def test_a_drafter_drains_every_step(self, model):
        prompts = [_prompt(n, seed=20 + n) for n in (5, 3)]
        ref = _engine(model)
        rows, _ = _run_scripted(ref, prompts, (9, 5))

        def run():
            eng = _engine(model, spec_k=3)
            eng.drafter = _Oracle(rows)
            _run_scripted(eng, prompts, (9, 5))
            assert eng.stats()["decode_steps"] == 2
            return eng

        assert self._counted(run) == {"admit": 0, "drafter": 2, "stop": 0,
                                      "idle": 0}


class TestNoGaugePerStep:
    def _engine_gauges(self, model, monkeypatch, max_new):
        """The gauges set on the engine thread while it serves ONE
        stream of ``max_new`` tokens, queued before it starts."""
        reg = registry()
        calls = []
        real = reg.gauge

        def spy(name, *a, **kw):
            if threading.current_thread().name == "dl4jtpu-generation":
                calls.append(name)
            return real(name, *a, **kw)

        monkeypatch.setattr(reg, "gauge", spy)
        eng = _engine(model)
        req = eng.submit(_prompt(5, seed=3), max_new)
        eng.start()
        try:
            req.result(120.0)
            assert eng.drain(timeout=30.0)
        finally:
            eng.stop()
            monkeypatch.setattr(reg, "gauge", real)
        return sorted(calls), eng.stats()["decode_steps"]

    def test_a_longer_stream_sets_no_more_gauges(self, model, monkeypatch):
        """Per stream the pool's page gauge and the flight ring's move
        (allocation, release, the fate point); per STEP nothing does."""
        short, steps_short = self._engine_gauges(model, monkeypatch, 4)
        long, steps_long = self._engine_gauges(model, monkeypatch, 20)
        assert steps_long - steps_short == 16
        assert long == short
        assert "dl4jtpu_decode_batch_occupancy" not in long

    def test_occupancy_is_sampled_at_the_scrape(self, model):
        """`dl4jtpu_decode_batch_occupancy` = live streams / slots of the
        running engines when the registry collects: here with A live and
        the engine thread held inside B's first token (B not seated)."""
        reg = registry()
        held, go = threading.Event(), threading.Event()

        def first(token, index):
            held.set()
            assert go.wait(30.0)

        def others():
            live = slots = 0
            for e in list(gen_mod._ENGINES):
                if e is not eng and e._thread is not None \
                        and e._thread.is_alive():
                    live += e.active_streams()
                    slots += e.config.slots
            return live, slots

        eng = _engine(model, max_pages_per_seq=8).start()
        try:
            a = eng.submit(_prompt(6, seed=1), 30)
            _wait_for(lambda: len(a.tokens) >= 2)
            b = eng.submit(_prompt(5, seed=2), 4, on_token=first)
            assert held.wait(30.0)
            live, slots = others()
            reg.collect()
            got = reg.gauge("dl4jtpu_decode_batch_occupancy").value()
            assert got == (1 + live) / (CFG["slots"] + slots)
            go.set()
            b.result(120.0)
            a.result(120.0)
            assert eng.drain(timeout=30.0)
            live, slots = others()
            reg.collect()
            got = reg.gauge("dl4jtpu_decode_batch_occupancy").value()
            assert got == live / (CFG["slots"] + slots)
        finally:
            go.set()
            eng.stop()


def test_one_prefill_dispatch_per_chunk_on_a_row_pool():
    """A stack over row pools (the latent toy of test_latent_serving)
    prefills a prompt as one chunk program after another: one
    `generation.prefill_dispatch {chunk}` each, in order, inside the
    prefill, then one `prefill_readback`; and `generation.prefill`'s
    duration is still `req.lat["prefill"]`."""
    from test_latent_serving import ENGINE, _model

    rec = tracer()
    eng = GenerationEngine(model=_model(),
                           config=GenerationConfig(**ENGINE)).start()
    try:
        rec.enable()
        rec.clear()
        req = eng.submit(np.arange(1, 36, dtype=np.int32) % 90, 2)
        req.result(300.0)
        tid = eng._thread.ident
    finally:
        eng.stop()
        rec.disable()
    ring = sorted((e for e in rec.to_chrome_trace()["traceEvents"]
                   if e["ph"] == "X" and e["cat"] == "engine"
                   and e["tid"] == tid), key=lambda e: e["ts"])
    rec.clear()
    pre, = [e for e in ring if e["name"] == "generation.prefill"]
    inner = [e for e in ring if e["name"] in (
        "generation.prefill_dispatch", "generation.prefill_readback")]
    # 35 tokens in chunks of 16: a bucket of 48, three chunk programs
    assert [e["name"].rsplit("_", 1)[1] for e in inner] == [
        "dispatch"] * 3 + ["readback"]
    assert [e["args"].get("chunk") for e in inner[:3]] == [0, 1, 2]
    for e in inner:
        assert pre["ts"] <= e["ts"]
        assert e["ts"] + e["dur"] <= pre["ts"] + pre["dur"] + 1e-3
    assert round(req.lat["prefill"] * 1e6, 3) == pre["dur"]
