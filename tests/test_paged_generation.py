"""ISSUE 16 — token-level continuous-batching generation serving.

`ops.generation.generate` is the single-request reference; this file
holds `serving.generation.GenerationEngine` to it token-for-token
(greedy AND sampled — the engine reproduces the dense path's `fold_in`
RNG schedule exactly) while exercising the serving ladder around the
decode loop: paged KV allocation with an explicit ``kv_exhausted`` 429,
page-leak-free cancel/abort paths, watchdog wedge recovery, hot-swap
between decode steps with zero dropped streams, the three new fault
sites, the `/v1/generate` HTTP surface, and the prefill/decode
disaggregation seam (engine-to-engine and routed through a
`ServingFleet` with replica roles)."""

import json
import re
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.ops.generation import generate
from deeplearning4j_tpu.runtime import faults
from deeplearning4j_tpu.serving.admission import (
    REJECT_STATUS,
    ServingError,
    ServingRejected,
)
from deeplearning4j_tpu.serving.generation import (
    WEIGHT_BOUND_PREFILL_TOKENS,
    GenerationConfig,
    GenerationEngine,
)
from deeplearning4j_tpu.serving.kv_cache import (
    SCRATCH_PAGE,
    KVPoolExhausted,
    PagedKVCache,
    quantize_page_rows,
)
from deeplearning4j_tpu.serving.server import InferenceServer
from deeplearning4j_tpu.zoo.transformer import TransformerEncoder

pytestmark = pytest.mark.generation

VOCAB, D, HEADS, LAYERS = 31, 16, 2, 2

#: the shared engine shape for most tests: 4 slots, 8-row pages, a
#: 4-wide page table -> streams up to 32 KV positions
CFG = dict(slots=4, page_size=8, num_pages=64, max_pages_per_seq=4,
           max_queue=16, default_max_new=8)


@pytest.fixture(autouse=True)
def _disarm_faults():
    yield
    faults.disarm()


@pytest.fixture(scope="module")
def model():
    return TransformerEncoder(
        vocab_size=VOCAB, d_model=D, n_heads=HEADS, n_layers=LAYERS,
        causal=True, seed=5,
    ).init_model()


def _engine(model, **over):
    return GenerationEngine(
        model=model, config=GenerationConfig(**{**CFG, **over}))


def _dense(model, prompt, max_new, **kw):
    """The reference row: ops.generation.generate on one prompt."""
    return np.asarray(
        generate(model, np.asarray(prompt)[None, :], max_new, **kw))[0]


def _prompt(n, seed=0):
    return np.random.default_rng(seed).integers(
        0, VOCAB, n).astype(np.int32)


def _drain_every_step(monkeypatch):
    """The decode loop as it was before the lookahead: every step is read
    back before the next is built (a test-only hook: no config field)."""
    monkeypatch.setattr(GenerationEngine, "_must_drain",
                        lambda self: "drafter")


# -- the paged KV allocator --------------------------------------------------

class TestPagedKVCache:
    def _kv(self, **over):
        kw = dict(n_layers=2, n_heads=2, head_dim=8, num_pages=8,
                  page_size=8)
        kw.update(over)
        return PagedKVCache(**kw)

    def test_alloc_release_accounting(self):
        kv = self._kv()
        assert kv.free_pages == 7          # page 0 is scratch
        kv.alloc("a", 3)
        kv.alloc("b", 2)
        assert kv.used_pages == 5 and kv.free_pages == 2
        assert len(kv.table("a")) == 3
        assert SCRATCH_PAGE not in kv.table("a")
        kv.release("a")
        kv.release("a")                    # idempotent
        assert kv.used_pages == 2
        kv.release("b")
        assert kv.used_pages == 0 and kv.leak_check() is None

    def test_exhaustion_raises_and_rolls_back(self):
        kv = self._kv()
        kv.alloc("a", 6)
        with pytest.raises(KVPoolExhausted):
            kv.alloc("b", 2)
        # the failed alloc must not leak partial grants
        assert kv.used_pages == 6 and kv.leak_check() is None

    def test_pages_for_and_occupancy(self):
        kv = self._kv()
        assert kv.page_size == 8           # quantized to PAGE_QUANTUM
        assert kv.pages_for(1) == 1
        assert kv.pages_for(8) == 1
        assert kv.pages_for(9) == 2
        kv.alloc("a", 7)
        assert kv.occupancy() == pytest.approx(1.0)
        kv.release("a")
        assert kv.occupancy() == 0.0

    def test_write_prefill_round_trips(self):
        kv = self._kv()
        rng = np.random.default_rng(0)
        k = rng.standard_normal((2, 16, 2, 8)).astype(np.float32)
        v = rng.standard_normal((2, 16, 2, 8)).astype(np.float32)
        kv.alloc("a", 2)
        tbl = kv.write_prefill("a", k, v)
        got = np.concatenate(
            [np.asarray(kv.k_pages[:, p]) for p in tbl], axis=1)
        np.testing.assert_allclose(got, k, rtol=1e-6)

    def test_int8_pages_quantize_within_bound(self):
        kv = self._kv(kv_dtype="int8")
        rng = np.random.default_rng(1)
        k = rng.standard_normal((2, 16, 2, 8)).astype(np.float32)
        v = rng.standard_normal((2, 16, 2, 8)).astype(np.float32)
        kv.alloc("a", 2)
        tbl = kv.write_prefill("a", k, v)
        deq = np.concatenate(
            [np.asarray(kv.k_pages[:, p], np.float32)
             * np.asarray(kv.k_scales[:, p])[..., None]
             for p in tbl], axis=1)
        # symmetric int8: error bounded by half a quantization step
        assert np.max(np.abs(deq - k)) <= np.max(np.abs(k)) / 127.0

    def test_quantize_page_rows_zero_row_safe(self):
        q, s = quantize_page_rows(jnp.zeros((4, 2, 8)))
        assert np.all(np.asarray(q) == 0)
        assert np.all(np.asarray(s) == 1.0)   # never a 0-divide scale

    @pytest.mark.faults
    def test_kv_alloc_fault_site(self):
        kv = self._kv()
        faults.arm("kv.alloc:raise:nth=1")
        with pytest.raises(KVPoolExhausted):
            kv.alloc("a", 1)
        faults.disarm()
        kv.alloc("a", 1)                   # the pool itself is fine
        assert kv.used_pages == 1


# -- numerics: the engine vs the dense reference -----------------------------

class TestDecodeParity:
    def test_greedy_token_identical_to_dense(self, model):
        eng = _engine(model).start()
        try:
            for n, max_new in ((3, 6), (7, 12), (14, 10)):
                p = _prompt(n, seed=n)
                out = np.asarray(eng.generate(p, max_new, timeout=120.0))
                np.testing.assert_array_equal(
                    out, _dense(model, p, max_new), err_msg=f"len {n}")
        finally:
            eng.stop()

    @pytest.mark.slow
    def test_sampled_and_top_k_identical_to_dense(self, model):
        """Not statistically close — IDENTICAL: the engine reproduces
        the dense path's per-token `fold_in` schedule and top-k
        threshold rule exactly."""
        eng = _engine(model).start()
        try:
            p = _prompt(6, seed=9)
            for kw in (dict(temperature=1.0, seed=3),
                       dict(temperature=1.3, top_k=5, seed=7)):
                out = np.asarray(eng.generate(p, 10, timeout=120.0, **kw))
                np.testing.assert_array_equal(
                    out, _dense(model, p, 10, **kw), err_msg=str(kw))
        finally:
            eng.stop()

    @pytest.mark.slow
    def test_concurrent_streams_each_match_their_reference(self, model):
        """The continuous batch is airtight: slots never bleed into
        each other even with mixed lengths, budgets, and sampling."""
        eng = _engine(model, slots=3).start()
        try:
            specs = [
                (_prompt(3, seed=1), 8, dict()),
                (_prompt(9, seed=2), 14, dict(temperature=1.0, seed=4)),
                (_prompt(5, seed=3), 5, dict(temperature=0.9, top_k=4,
                                             seed=8)),
                (_prompt(12, seed=4), 11, dict()),
                (_prompt(4, seed=5), 9, dict(temperature=1.1, seed=2)),
            ]
            reqs = [eng.submit(p, n, **kw) for p, n, kw in specs]
            for req, (p, n, kw) in zip(reqs, specs):
                np.testing.assert_array_equal(
                    np.asarray(req.result(120.0)), _dense(model, p, n, **kw))
        finally:
            eng.stop()

    def test_stop_token_truncates_like_the_reference(self, model):
        p = _prompt(5, seed=6)
        ref = _dense(model, p, 12)
        gen = ref[len(p):]
        stop = int(gen[3])                 # stop on the 4th ref token
        eng = _engine(model).start()
        try:
            out = np.asarray(eng.generate(p, 12, stop_tokens=(stop,),
                                          timeout=120.0))
        finally:
            eng.stop()
        first = int(np.argmax(gen == stop))
        np.testing.assert_array_equal(out, ref[: len(p) + first + 1])
        assert out[-1] == stop

    @pytest.mark.slow
    def test_int8_kv_agreement_gate(self, model):
        """int8 KV pages are gated the way PR 13 gated PTQ: high greedy
        token agreement with the f32 reference, not bit equality."""
        eng = _engine(model, kv_dtype="int8").start()
        try:
            agree = total = 0
            for n in (4, 9):
                p = _prompt(n, seed=20 + n)
                ref = _dense(model, p, 12)[n:]
                out = np.asarray(eng.generate(p, 12, timeout=120.0))[n:]
                m = min(len(ref), len(out))
                agree += int((ref[:m] == out[:m]).sum())
                total += m
        finally:
            eng.stop()
        assert agree / total >= 0.9, f"int8 agreement {agree}/{total}"

    def test_ttft_is_recorded(self, model):
        eng = _engine(model).start()
        try:
            req = eng.submit(_prompt(4), 3)
            req.result(120.0)
            assert req.ttft_s is not None and req.ttft_s > 0
        finally:
            eng.stop()


# -- admission, capacity, and the explicit 429 -------------------------------

class TestAdmission:
    def test_over_capacity_stream_is_a_client_error(self, model):
        eng = _engine(model)
        with pytest.raises(ValueError, match="KV positions"):
            eng.submit(_prompt(8), 40)     # 48 > 4 pages x 8 rows
        with pytest.raises(ValueError, match="empty"):
            eng.submit(np.zeros(0, np.int32), 4)
        with pytest.raises(ValueError, match="max_new"):
            eng.submit(_prompt(4), 0)

    def test_kv_exhaustion_is_an_explicit_429(self, model):
        # 2 usable pages; the stream needs 3 -> admission answers
        # kv_exhausted instead of stalling on HBM that will not come
        eng = _engine(model, num_pages=3).start()
        try:
            req = eng.submit(_prompt(17), 4)
            with pytest.raises(ServingRejected) as ei:
                req.result(60.0)
        finally:
            eng.stop()
        assert ei.value.reason == "kv_exhausted"
        assert ei.value.status == 429
        assert REJECT_STATUS["kv_exhausted"] == 429

    def test_full_queue_rejects(self, model):
        eng = _engine(model, max_queue=2)   # not started: nothing drains
        eng.submit(_prompt(3), 2)
        eng.submit(_prompt(3), 2)
        with pytest.raises(ServingRejected) as ei:
            eng.submit(_prompt(3), 2)
        assert ei.value.reason == "queue_full"

    def test_cancel_releases_every_page(self, model):
        eng = _engine(model).start()
        try:
            req = eng.submit(_prompt(4), 27)
            deadline = time.monotonic() + 60.0
            while not req.tokens_so_far():
                assert time.monotonic() < deadline
                time.sleep(0.01)
            assert eng.kv.used_pages > 0
            req.cancel()
            while eng.kv.used_pages and time.monotonic() < deadline:
                time.sleep(0.01)
            assert eng.kv.used_pages == 0
            assert eng.kv.leak_check() is None
        finally:
            eng.stop()


# -- the one-step lookahead ---------------------------------------------------

SAMPLING = {
    "greedy": dict(),
    "sampled": dict(temperature=1.0, seed=3),
    "top_k": dict(temperature=1.3, top_k=5, seed=7),
}


def _serve_queued(eng, specs, **submit_kw):
    """Queue every stream BEFORE the loop starts (one refill admits what
    the slots hold, so the schedule is fixed by the lengths alone), serve
    them, and return (rows, stats); the pool must be leak-free."""
    reqs = [eng.submit(p, n, **kw, **submit_kw) for p, n, kw in specs]
    eng.start()
    try:
        rows = [np.asarray(r.result(120.0)) for r in reqs]
        assert eng.drain(timeout=30.0)
        st = eng.stats()
        assert eng.kv.used_pages == 0
        assert eng.kv.leak_check() is None
    finally:
        eng.stop()
    return rows, st


class TestLookahead:
    """ISSUE 36: the loop dispatches step n + 1 before it reads step n
    back.  Same programs, same keys, so the same tokens in the same order
    to the same streams as the loop that drains every step, and as the
    dense reference."""

    @pytest.mark.parametrize("sampling", list(SAMPLING))
    def test_tokens_identical_to_the_drained_loop_and_to_dense(
            self, model, sampling, monkeypatch):
        kw = SAMPLING[sampling]
        specs = [(_prompt(3, seed=61), 9, kw), (_prompt(6, seed=62), 6, kw),
                 (_prompt(6, seed=63), 9, dict(kw, seed=11) if kw else kw)]
        rows, st = _serve_queued(_engine(model), specs)
        # one refill, then A and C ride 8 steps and B 5: only the first
        # was built with nothing in flight
        assert st["decode_steps"] == 8
        assert st["decode_steps_overlapped"] == 7
        assert st["decode_slot_steps"] == 8 + 5 + 8
        assert st["decode_slot_steps_discarded"] == 0
        _drain_every_step(monkeypatch)
        drained, st = _serve_queued(_engine(model), specs)
        assert st["decode_steps"] == 8
        assert st["decode_steps_overlapped"] == 0
        for row, same, (p, n, kw_) in zip(rows, drained, specs):
            np.testing.assert_array_equal(row, same)
            np.testing.assert_array_equal(row, _dense(model, p, n, **kw_))

    @pytest.mark.parametrize("loop", ["overlapped", "drained"])
    def test_stop_token_mid_stream_costs_one_discarded_row(
            self, model, loop, monkeypatch):
        """The step after the stop token's was in flight when the token
        came to light: its row reaches no stream and is counted."""
        if loop == "drained":
            _drain_every_step(monkeypatch)
        p = _prompt(5, seed=6)
        ref = _dense(model, p, 12)
        gen = ref[len(p):]
        stop = int(gen[3])
        first = int(np.argmax(gen == stop))
        assert 0 < first < 10              # mid-stream: steps follow it
        seen = []
        (out,), st = _serve_queued(
            _engine(model), [(p, 12, dict())], stop_tokens=(stop,),
            on_token=lambda tok, idx: seen.append((idx, tok)))
        np.testing.assert_array_equal(out, ref[: len(p) + first + 1])
        assert seen == list(enumerate(gen[: first + 1].tolist()))
        flew = loop == "overlapped"
        assert st["decode_slot_steps_discarded"] == flew
        assert st["decode_steps"] == first + flew

    @pytest.mark.parametrize("loop", ["overlapped", "drained"])
    def test_cancel_mid_stream_keeps_the_tokens_before_it(
            self, model, loop, monkeypatch):
        if loop == "drained":
            _drain_every_step(monkeypatch)
        p = _prompt(4, seed=64)
        ref = _dense(model, p, 20)[len(p):]
        eng = _engine(model)
        seen = []

        def on_token(tok, idx):
            seen.append(tok)
            if idx == 5:
                req.cancel()               # on the engine thread itself

        req = eng.submit(p, 20, on_token=on_token)
        eng.start()
        try:
            with pytest.raises(ServingRejected):
                req.result(60.0)
            assert eng.drain(timeout=30.0)
            # the next harvest sees the flag and hands out nothing more
            assert seen == req.tokens_so_far() == ref[:6].tolist()
            assert eng.kv.used_pages == 0
            assert eng.kv.leak_check() is None
            st = eng.stats()
            assert st["decode_slot_steps_discarded"] == (loop == "overlapped")
            # and the slot serves the next stream
            q = _prompt(5, seed=65)
            np.testing.assert_array_equal(
                np.asarray(eng.generate(q, 5, timeout=120.0)),
                _dense(model, q, 5))
        finally:
            eng.stop()

    @pytest.mark.parametrize("max_new", [1, 2])
    def test_streams_of_one_and_two_tokens(self, model, max_new):
        """No step, and one step that nothing is built on top of."""
        specs = [(_prompt(4, seed=66), max_new, dict()),
                 (_prompt(7, seed=67), max_new, SAMPLING["sampled"])]
        rows, st = _serve_queued(_engine(model), specs)
        for row, (p, n, kw) in zip(rows, specs):
            np.testing.assert_array_equal(row, _dense(model, p, n, **kw))
        assert st["decode_steps"] == max_new - 1
        assert st["decode_steps_overlapped"] == 0

    @pytest.mark.parametrize("ends_by", ["stop_token", "count"])
    def test_admission_into_a_slot_freed_one_step_earlier(self, model,
                                                          ends_by):
        """One slot, a second stream waiting for it.  The first ends with
        a step in flight (a stop token) or just before one would be built
        (its count): what that step computed for the old occupant must
        not reach the stream admitted into the slot."""
        a, b = _prompt(5, seed=6), _prompt(4, seed=68)
        ref_a, ref_b = _dense(model, a, 12), _dense(model, b, 7)
        gen_a = ref_a[len(a):]
        if ends_by == "stop_token":
            first = int(np.argmax(gen_a == gen_a[3]))
            spec_a = (a, 12, dict(stop_tokens=(int(gen_a[3]),)))
        else:
            first = 4
            spec_a = (a, first + 1, dict())
        got = {"a": [], "b": []}
        eng = _engine(model, slots=1)
        ra = eng.submit(*spec_a[:2], **spec_a[2],
                        on_token=lambda t, i: got["a"].append(t))
        rb = eng.submit(b, 7, on_token=lambda t, i: got["b"].append(t))
        eng.start()
        try:
            np.testing.assert_array_equal(
                np.asarray(ra.result(120.0)), ref_a[: len(a) + first + 1])
            np.testing.assert_array_equal(np.asarray(rb.result(120.0)),
                                          ref_b)
            assert eng.drain(timeout=30.0)
            st = eng.stats()
            assert eng.kv.leak_check() is None
        finally:
            eng.stop()
        assert got["a"] == gen_a[: first + 1].tolist()
        assert got["b"] == ref_b[len(b):].tolist()
        assert st["decode_slot_steps_discarded"] == (ends_by == "stop_token")
        # every step but each stream's first was built on one in flight
        # (A's count ends it before a step is built on its last)
        assert st["decode_steps"] - st["decode_steps_overlapped"] == 2

    def test_sixteen_slots_fill_and_empty(self, model):
        """Twenty streams through sixteen slots: the slots fill, streams
        leave at different steps, the waiting four are admitted as slots
        free, and the batch empties — every stream its own reference."""
        specs = []
        for i in range(20):
            kw = dict() if i % 3 else dict(temperature=1.0, seed=i)
            specs.append((_prompt((3, 6)[i % 2], seed=70 + i),
                          (3, 6, 9)[i % 3], kw))
        refs = [_dense(model, p, n, **kw) for p, n, kw in specs]
        rows, st = _serve_queued(_engine(model, slots=16, max_queue=32),
                                 specs)
        for row, ref in zip(rows, refs):
            np.testing.assert_array_equal(row, ref)
        assert st["decode_slot_steps"] == sum(n - 1 for _, n, _ in specs)
        assert 0 < st["decode_steps_overlapped"] < st["decode_steps"]
        assert st["decode_slot_steps_discarded"] == 0

    @pytest.mark.parametrize("params", ["uncommitted", "committed"])
    def test_one_call_signature_for_both_token_sources(self, params):
        """The plain step takes its tokens as a device array whether they
        come from the host (after a drain) or from the step before, placed
        as the pool is (committed where the parameters are: the pool comes
        back from the same programs as the tokens): one cache entry once
        the host's source is warm, so no later step traces, lowers or
        compiles."""
        model = TransformerEncoder(
            vocab_size=VOCAB, d_model=D, n_heads=HEADS, n_layers=LAYERS,
            causal=True, seed=5).init_model()
        if params == "committed":
            model.params = jax.device_put(model.params, jax.devices()[0])
        eng = _engine(model).start()
        try:
            # warm: max_new 2 is one step, from the host's tokens (what
            # the benchmark's warm-up runs: it never overlaps a step)
            for i in range(2):
                eng.generate(_prompt(4, seed=79 + i), 2, timeout=120.0)
            assert eng.stats()["decode_steps_overlapped"] == 0
            assert eng._step_fns[1]._cache_size() == 1
            for i in range(3):
                p = _prompt(4 + i, seed=80 + i)
                np.testing.assert_array_equal(
                    np.asarray(eng.generate(p, 6, timeout=120.0)),
                    _dense(model, p, 6))
            assert eng.stats()["decode_steps_overlapped"] > 0
            assert eng._step_fns[1]._cache_size() == 1
        finally:
            eng.stop()


# -- the degradation ladder --------------------------------------------------

class TestLadder:
    @pytest.mark.faults
    def test_prefill_fault_fails_the_stream_not_the_engine(self, model):
        eng = _engine(model).start()
        try:
            faults.arm("serving.prefill:raise:nth=1")
            req = eng.submit(_prompt(4), 4)
            with pytest.raises(ServingError):
                req.result(60.0)
            assert eng.kv.used_pages == 0  # the failed admit released
            faults.disarm()
            out = np.asarray(eng.generate(_prompt(4), 4, timeout=120.0))
            assert out.shape == (8,)
        finally:
            eng.stop()

    @pytest.mark.faults
    def test_decode_fault_fails_active_and_recovers(self, model):
        eng = _engine(model).start()
        try:
            # warm first so the armed consult hits a real decode step
            eng.generate(_prompt(4), 2, timeout=120.0)
            faults.arm("serving.decode:raise:nth=1")
            req = eng.submit(_prompt(4), 6)
            with pytest.raises(ServingError):
                req.result(60.0)
            assert eng.kv.used_pages == 0
            faults.disarm()
            p = _prompt(5, seed=31)
            np.testing.assert_array_equal(
                np.asarray(eng.generate(p, 5, timeout=120.0)),
                _dense(model, p, 5))
        finally:
            eng.stop()

    @pytest.mark.faults
    @pytest.mark.parametrize("loop", ["overlapped", "drained"])
    def test_fault_at_prepare_delivers_the_step_in_flight_first(
            self, model, loop, monkeypatch):
        """The 4th step cannot be built; the 3rd, in flight, is still
        good: its tokens go out, then the stream fails — once."""
        if loop == "drained":
            _drain_every_step(monkeypatch)
        p = _prompt(4, seed=33)
        ref = _dense(model, p, 10)[len(p):]
        eng = _engine(model)
        failed = []
        real = eng._step_failed
        eng._step_failed = lambda *a: (failed.append(a), real(*a))
        faults.arm("serving.decode:raise:nth=4")
        req = eng.submit(p, 10)
        eng.start()
        try:
            with pytest.raises(ServingError, match="decode step failed"):
                req.result(60.0)
            assert req.tokens_so_far() == ref[:4].tolist()
            assert len(failed) == 1
            st = eng.stats()
            assert st["streams"]["outcomes"] == {"error": 1}
            assert st["decode_steps"] == 3
            assert st["kv"]["pool_rebuilds"] == 0
            assert eng.kv.used_pages == 0
            faults.disarm()
            np.testing.assert_array_equal(
                np.asarray(eng.generate(p, 10, timeout=120.0))[len(p):], ref)
            assert eng.kv.leak_check() is None
        finally:
            eng.stop()

    @pytest.mark.faults
    def test_failed_readback_takes_the_step_after_it_along(self, model):
        """Step 3's tokens cannot be read; step 4, dispatched on top of
        it, is dead with it: ONE failure, one revive, no page leaked."""
        p = _prompt(4, seed=34)
        ref = _dense(model, p, 10)[len(p):]
        eng = _engine(model)
        calls = {"failed": 0, "revive": 0, "readback": 0}
        real_failed, real_revive, real_span = (
            eng._step_failed, eng.kv.revive, eng._span)

        def span(name, **a):
            if name == "generation.decode_readback":
                calls["readback"] += 1
                if calls["readback"] == 3:
                    raise RuntimeError("device lost")
            return real_span(name, **a)

        def counted(key, real):
            def call(*a, **kw):
                calls[key] += 1
                return real(*a, **kw)
            return call

        eng._span = span
        eng._step_failed = counted("failed", real_failed)
        eng.kv.revive = counted("revive", real_revive)
        req = eng.submit(p, 10)
        eng.start()
        try:
            with pytest.raises(ServingError, match="device lost"):
                req.result(60.0)
            assert req.tokens_so_far() == ref[:3].tolist()
            assert (calls["failed"], calls["revive"]) == (1, 1)
            st = eng.stats()
            assert st["decode_steps"] == 4          # the 4th was in flight
            assert st["streams"]["outcomes"] == {"error": 1}
            assert eng._flying is None
            assert eng.kv.used_pages == 0
            assert not any(_deleted(eng.kv.pool()))
            np.testing.assert_array_equal(
                np.asarray(eng.generate(p, 10, timeout=120.0))[len(p):], ref)
            assert eng.kv.leak_check() is None
        finally:
            eng.stop()

    @pytest.mark.parametrize("loop", ["overlapped", "drained"])
    def test_stop_with_a_step_in_flight_returns_its_tokens(
            self, model, loop, monkeypatch):
        """Every step that was dispatched hands out its tokens, the one
        in flight when the stop came included."""
        if loop == "drained":
            _drain_every_step(monkeypatch)
        p = _prompt(4, seed=35)
        ref = _dense(model, p, 12)[len(p):]
        eng = _engine(model)

        def on_token(tok, idx):
            if idx == 5:
                eng._stop.set()            # what `stop()` does first

        req = eng.submit(p, 12, on_token=on_token)
        eng.start()
        try:
            eng._thread.join(60.0)
            assert not eng._thread.is_alive()
            assert eng._flying is None
            steps = eng.stats()["decode_steps"]
            assert steps == 5 + (loop == "overlapped")
            assert req.tokens_so_far() == ref[: steps + 1].tolist()
        finally:
            eng.stop()
        with pytest.raises(ServingRejected):
            req.result(1.0)
        assert eng.kv.used_pages == 0
        assert eng.kv.leak_check() is None

    def test_drain_waits_for_the_step_in_flight(self, model):
        p = _prompt(5, seed=36)
        eng = _engine(model).start()
        try:
            req = eng.submit(p, 14, stop_tokens=(int(_dense(
                model, p, 14)[len(p) + 6]),))
            deadline = time.monotonic() + 60.0
            while eng.active_streams() == 0 and not req.done:
                assert time.monotonic() < deadline
                time.sleep(0.001)
            assert eng.drain(timeout=60.0)
            assert req.done and eng._flying is None
            assert not eng.watchdog._armed
            assert eng.kv.used_pages == 0
        finally:
            eng.stop()

    def test_watchdog_is_fed_the_turns_period_not_two_walls(self, model):
        """50 overlapped steps: what the watchdog's EWMA is fed per step
        is the turn's dispatch + readback — time that lies between two
        harvests — and never a step's own wall from ITS dispatch to ITS
        readback, which overlaps its neighbours' and would sum to twice
        the run."""
        eng = _engine(model, max_pages_per_seq=8)
        fed, stamps = [], []
        real = eng.watchdog.disarm

        def disarm(dur=None):
            if dur is not None:
                fed.append(dur)
            real(dur)

        eng.watchdog.disarm = disarm
        req = eng.submit(_prompt(4, seed=37), 52,
                         on_token=lambda t, i: stamps.append(
                             time.perf_counter()))
        eng.start()
        try:
            req.result(120.0)
            assert eng.drain(timeout=30.0)
            st = eng.stats()
        finally:
            eng.stop()
        assert st["decode_steps"] == 51
        assert st["decode_steps_overlapped"] == 50
        # one feed per turn that dispatched and read back: the first turn
        # only dispatched, the last only read back
        assert len(fed) == 50
        # the k-th feed lies between the harvests of tokens k and k + 1
        gaps = np.diff(stamps)
        assert len(gaps) == 51
        assert all(f <= g for f, g in zip(fed, gaps))
        assert sum(fed) <= stamps[-1] - stamps[0]
        assert 0 < eng.watchdog.ewma <= max(fed)

    @pytest.mark.slow
    def test_watchdog_abort_releases_pages_and_respawns(self, model):
        eng = _engine(model).start()
        try:
            req = eng.submit(_prompt(4), 27)
            deadline = time.monotonic() + 60.0
            while eng.active_streams() == 0:
                assert time.monotonic() < deadline
                time.sleep(0.01)
            eng._on_wedged({"stage": "abort", "iteration": 0})
            with pytest.raises(ServingError, match="wedged"):
                req.result(60.0)
            assert eng.kv.used_pages == 0
            assert eng.kv.leak_check() is None
            # the respawned loop serves the next stream
            p = _prompt(3, seed=40)
            np.testing.assert_array_equal(
                np.asarray(eng.generate(p, 4, timeout=120.0)),
                _dense(model, p, 4))
        finally:
            eng.stop()

    def test_hot_swap_drains_with_zero_dropped_streams(self, model):
        srv = InferenceServer(model)
        eng = GenerationEngine(server=srv,
                               config=GenerationConfig(**CFG)).start()
        try:
            reqs = [eng.submit(_prompt(4, seed=50 + i), 20)
                    for i in range(3)]
            deadline = time.monotonic() + 60.0
            while not any(r.tokens_so_far() for r in reqs):
                assert time.monotonic() < deadline
                time.sleep(0.01)
            new = jax.tree_util.tree_map(
                lambda a: a * 1.001
                if jnp.issubdtype(jnp.asarray(a).dtype, jnp.floating)
                else a,
                srv.model.params)
            assert srv.push_weights(new, source="test")
            for r in reqs:
                out = np.asarray(r.result(120.0))
                assert out.shape == (24,)  # full length: zero drops
                assert r.error is None
        finally:
            eng.stop()
            srv.stop()

    def test_kv_occupancy_feeds_shed_pressure(self, model):
        srv = InferenceServer(model)
        eng = GenerationEngine(server=srv,
                               config=GenerationConfig(**CFG))
        try:
            assert srv.generation_engine is eng
            base = srv.shed_pressure()
            eng.kv.alloc("x", 60)          # ~95% of the pool
            assert srv.shed_pressure() >= eng.kv.occupancy() > base
            eng.kv.release("x")
        finally:
            srv.stop()


# -- the pool is held once: donated, rebound, revived -------------------------

def _aval(a):
    return None if a is None else jax.ShapeDtypeStruct(a.shape, a.dtype)


def _step_avals(eng, model, c, params=None):
    """The abstract arguments `eng._make_step(c)` is lowered with
    (``params``: another tree than the model's own)."""
    s, mp = eng.config.slots, eng.config.max_pages_per_seq

    def vec(dtype, *tail):
        return jax.ShapeDtypeStruct((s,) + tail, dtype)

    toks = vec(jnp.int32) if c == 1 else vec(jnp.int32, c)
    return (jax.tree.map(_aval, model.params if params is None else params),
            *[_aval(a) for a in eng.kv.pool()],
            vec(jnp.int32, mp), vec(jnp.int32), toks, vec(jnp.uint32),
            vec(jnp.int32), vec(jnp.float32), vec(jnp.int32))


def _deleted(arrays):
    return [a.is_deleted() for a in arrays if a is not None]


class TestDonatedPool:
    """Every program that writes the pool takes it donated and the pool
    is rebound from the result; a dispatch that fails after consuming it
    leaves a NEW pool behind (`kv.revive`), never a dead one."""

    @pytest.mark.parametrize("kv_dtype", ["f32", "int8"])
    def test_write_prefill_consumes_and_rebinds(self, kv_dtype):
        kv = PagedKVCache(n_layers=2, n_heads=2, head_dim=8, num_pages=8,
                          page_size=8, kv_dtype=kv_dtype)
        rng = np.random.default_rng(3)
        k = rng.standard_normal((2, 16, 2, 8)).astype(np.float32)
        kv.alloc("a", 2)
        before = kv.pool()
        kv.write_prefill("a", k, k)
        assert all(_deleted(before))       # the CPU backend donates
        assert not any(_deleted(kv.pool()))
        assert kv.stats()["pool_rebuilds"] == 0

    def test_write_prefill_is_one_program_per_bucket(self):
        """The second hand-off of a bucket compiles nothing, whether its
        K/V come from the device (inline prefill) or the host (the
        disaggregation seam); a new bucket is ONE new program."""
        from deeplearning4j_tpu.runtime import compile_stats

        # a pool shape no other test uses: `_write_pages` is one jit
        # for the process
        kv = PagedKVCache(n_layers=3, n_heads=3, head_dim=8, num_pages=11,
                          page_size=8)
        rng = np.random.default_rng(4)

        def hand_off(rid, n_pages, on_device):
            k = rng.standard_normal(
                (3, 8 * n_pages, 3, 8)).astype(np.float32)
            kv.alloc(rid, n_pages)
            snap = compile_stats.snapshot()
            kv.write_prefill(rid, *((jnp.asarray(k),) * 2 if on_device
                                    else (k, k)))
            delta = compile_stats.snapshot() - snap
            tbl = kv.table(rid)
            got = np.concatenate(
                [np.asarray(kv.k_pages[:, p]) for p in tbl], axis=1)
            np.testing.assert_array_equal(got, k)
            kv.release(rid)
            return delta.backend_compiles

        assert hand_off("a", 2, on_device=True) == 1
        assert hand_off("b", 2, on_device=True) == 0
        assert hand_off("c", 2, on_device=False) == 0
        assert hand_off("d", 3, on_device=False) == 1
        assert hand_off("e", 2, on_device=True) == 0

    def test_decode_step_consumes_and_rebinds(self, model):
        eng = _engine(model).start()
        try:
            p = _prompt(5, seed=80)
            eng.generate(p, 2, timeout=120.0)
            before = eng.kv.pool()
            np.testing.assert_array_equal(
                np.asarray(eng.generate(p, 6, timeout=120.0)),
                _dense(model, p, 6))
            assert eng.drain(timeout=30.0)
            assert all(_deleted(before))
            assert not any(_deleted(eng.kv.pool()))
            assert eng.kv.stats()["pool_rebuilds"] == 0
        finally:
            eng.stop()

    @pytest.mark.parametrize("program", ["step", "verify"])
    @pytest.mark.parametrize("kv_dtype", ["f32", "int8"])
    def test_compiled_programs_alias_the_whole_pool(self, model, kv_dtype,
                                                    program):
        eng = _engine(model, kv_dtype=kv_dtype, spec_k=2)
        c = 1 if program == "step" else 3
        fn = eng._make_step() if c == 1 else eng._make_step(c)
        assert fn.__name__ == program     # what the profile is read by
        compiled = fn.lower(*_step_avals(eng, model, c)).compile()
        pool_bytes = sum(a.nbytes for a in eng.kv.pool() if a is not None)
        assert compiled.memory_analysis().alias_size_in_bytes == pool_bytes

    @pytest.mark.faults
    @pytest.mark.parametrize("how", ["fault_before_dispatch",
                                     "raises_after_dispatch",
                                     "wedged_after_dispatch"])
    def test_failed_step_leaves_a_usable_pool(self, model, how):
        eng = _engine(model).start()
        release = threading.Event()
        try:
            eng.generate(_prompt(4), 2, timeout=120.0)     # warm
            real = eng._step_fns[1]
            entered = threading.Event()

            def consume_then_fail(*a):
                eng._step_fns[1] = real                    # one shot
                out = real(*a)                             # pool consumed
                if how == "raises_after_dispatch":
                    raise RuntimeError("device lost")
                entered.set()
                release.wait(60.0)                         # "wedged"
                return out

            if how == "fault_before_dispatch":
                faults.arm("serving.decode:raise:nth=1")
            else:
                eng._step_fns[1] = consume_then_fail
            req = eng.submit(_prompt(4), 6)
            if how == "wedged_after_dispatch":
                assert entered.wait(60.0)
                stale = eng._thread
                eng._on_wedged({"stage": "abort", "iteration": 0})
            with pytest.raises(ServingError):
                req.result(60.0)
            faults.disarm()
            assert eng.kv.used_pages == 0
            assert not any(_deleted(eng.kv.pool()))
            rebuilt = 0 if how == "fault_before_dispatch" else 1
            assert eng.kv.stats()["pool_rebuilds"] == rebuilt
            p = _prompt(5, seed=31)
            np.testing.assert_array_equal(
                np.asarray(eng.generate(p, 5, timeout=120.0)),
                _dense(model, p, 5))
            if how == "wedged_after_dispatch":
                # the wedged dispatch returns at last: its pool must not
                # replace the one the respawned loop is serving from
                serving = eng.kv.pool()
                release.set()
                stale.join(30.0)
                assert not stale.is_alive()
                assert all(a is b for a, b in zip(eng.kv.pool(), serving))
                np.testing.assert_array_equal(
                    np.asarray(eng.generate(p, 5, timeout=120.0)),
                    _dense(model, p, 5))
            assert eng.kv.stats()["pool_rebuilds"] == rebuilt
            assert eng.kv.leak_check() is None
        finally:
            release.set()
            eng.stop()


# -- bounded program set -----------------------------------------------------

class TestOneBlock:
    """Every inference program runs `ops.generation.block`, once per
    layer: no program carries a block body of its own (PR 30)."""

    @pytest.mark.parametrize("program", ["generate", "prefill", "step",
                                         "verify", "drafter"])
    def test_every_program_traces_the_one_block(self, model, monkeypatch,
                                                program):
        from deeplearning4j_tpu.ops import generation as dense
        from deeplearning4j_tpu.serving import generation as serving
        from deeplearning4j_tpu.serving.speculative import ModelDrafter

        calls, real = [], dense.block

        def counted(cfg, lp, x, attend):
            calls.append(cfg.name)
            return real(cfg, lp, x, attend)

        monkeypatch.setattr(dense, "block", counted)
        monkeypatch.setattr(serving, "block", counted)
        spec_k = 2
        eng = _engine(model, spec_k=spec_k)
        params = jax.tree.map(_aval, model.params)
        prompt = jax.ShapeDtypeStruct((1, 8), jnp.int32)
        scalar = lambda dt: jax.ShapeDtypeStruct((), dt)
        if program == "generate":
            # a fresh jit of the whole dense program: prefill + ONE scan
            # tick, each of which walks the stack once
            fn = dense._generate_jit(model, dense._plan(model), 4, 0.0, 0)
            fn.lower(params, prompt, jax.random.key(0))
            want = 2 * LAYERS
        elif program == "prefill":
            eng._make_prefill(8).lower(
                params, prompt, scalar(jnp.int32), scalar(jnp.uint32),
                scalar(jnp.float32), scalar(jnp.int32))
            want = LAYERS
        elif program == "drafter":
            ModelDrafter(model)._fn(8).lower(params, prompt,
                                             scalar(jnp.int32))
            want = LAYERS
        else:
            c = 1 if program == "step" else spec_k + 1
            eng._make_step(c).lower(*_step_avals(eng, model, c))
            want = LAYERS
        assert len(calls) == want and set(calls) == {
            b.name for b in eng._stack.blocks}


# -- the programs are dispatched with a serving copy of the tree --------------

#: a weight a program takes as an ARGUMENT and narrows itself
_ARG_NARROWED = re.compile(
    r"stablehlo\.convert %arg\d+ : \(tensor<[0-9x]*xf32>\) -> "
    r"tensor<[0-9x]*xbf16>")


@pytest.fixture(scope="module")
def bf16_lm():
    """The activation type a TPU picks, forced here: the programs then
    cast every matrix at use, as they do on the chip."""
    from conftest import learned_position_lm

    return learned_position_lm(vocab=VOCAB, d=D, heads=HEADS, layers=LAYERS,
                               bf16=True)


def _run_program(eng, program, tree):
    """One dispatch of `program` with `tree`, on fixed inputs and a
    fresh seeded pool (the step donates it); every output, on the host."""
    rng = np.random.default_rng(7)
    if program == "prefill":
        pad = np.zeros((1, 8), np.int32)
        pad[0, :6] = rng.integers(0, VOCAB, 6)
        out = eng._make_prefill(8)(
            tree, pad, np.int32(6), np.uint32(3), np.float32(1.0),
            np.int32(0))
        return [np.asarray(a) for a in out]
    c = 1 if program == "step" else 3
    s, mp = eng.config.slots, eng.config.max_pages_per_seq
    pool = [None if a is None
            else jnp.asarray(rng.standard_normal(a.shape), a.dtype)
            for a in eng.kv.pool()]
    tbl = np.full((s, mp), SCRATCH_PAGE, np.int32)
    tbl[:3] = 1 + np.arange(3 * mp).reshape(3, mp)
    toks = rng.integers(0, VOCAB, (s,) if c == 1 else (s, c))
    out = eng._make_step(c)(
        tree, *pool, tbl, np.array([5, 9, 17, 0], np.int32),
        toks.astype(np.int32), np.arange(s, dtype=np.uint32),
        np.array([1, 4, 2, 0], np.int32),
        np.array([0.0, 1.0, 0.7, 0.0], np.float32),
        np.array([0, 0, 5, 0], np.int32))
    return [np.asarray(a) for a in out if a is not None]


class TestServingCopy:
    """The engine dispatches a copy of the tree whose matrices are in the
    activation type already (`ops.generation.serving_params`): the same
    arithmetic on the same values, the cast made once and not per step."""

    @pytest.mark.parametrize("program", ["step", "prefill", "verify"])
    def test_copy_and_tree_give_the_same_bits(self, bf16_lm, program):
        eng = _engine(bf16_lm, spec_k=2)
        copy = eng._serving_params()
        assert copy[eng._stack.embed.name]["W"].dtype == jnp.bfloat16
        want = _run_program(eng, program, bf16_lm.params)
        got = _run_program(eng, program, copy)
        assert len(got) == len(want) == 3     # K, V and the tokens
        for a, b in zip(got, want):
            np.testing.assert_array_equal(a, b)

    @pytest.mark.parametrize("program", ["step", "prefill", "verify"])
    def test_program_given_the_copy_narrows_no_argument(self, bf16_lm,
                                                        program):
        eng = _engine(bf16_lm, spec_k=2)

        def lowered(tree):
            if program == "prefill":
                scalar = lambda dt: jax.ShapeDtypeStruct((), dt)
                return eng._make_prefill(8).lower(
                    jax.tree.map(_aval, tree),
                    jax.ShapeDtypeStruct((1, 8), jnp.int32),
                    scalar(jnp.int32), scalar(jnp.uint32),
                    scalar(jnp.float32), scalar(jnp.int32)).as_text()
            c = 1 if program == "step" else 3
            return eng._make_step(c).lower(
                *_step_avals(eng, bf16_lm, c, params=tree)).as_text()

        # the f32 tree: every matrix of every block, the table, the head
        assert len(_ARG_NARROWED.findall(lowered(bf16_lm.params))) \
            >= 12 * LAYERS + 3
        assert not _ARG_NARROWED.findall(lowered(eng._serving_params()))

    def test_long_prefill_buckets_keep_the_wide_block_matrices(self,
                                                               bf16_lm):
        """A prompt forward bound by its matmuls is given the blocks'
        entries as the model holds them, beside the copy's table and
        head: the same leaves, no third set of weights, no further
        copy."""
        eng = _engine(bf16_lm)
        stack, live, copy = eng._stack, bf16_lm.params, eng._serving_params()
        assert eng._serving_params(WEIGHT_BOUND_PREFILL_TOKENS) is copy
        long = eng._serving_params(WEIGHT_BOUND_PREFILL_TOKENS + 1)
        assert (jax.tree_util.tree_structure(long)
                == jax.tree_util.tree_structure(copy))
        for b in stack.blocks:
            assert long[b.name] is live[b.name]
            assert copy[b.name]["W1"].dtype == jnp.bfloat16
        for layer in (stack.embed, stack.pos, stack.head):
            assert long[layer.name] is copy[layer.name]
        assert eng.stats()["serving_params_casts"] == 1

    def test_f32_engine_serves_the_tree_itself(self, model):
        eng = _engine(model)
        served = eng._serving_params()
        for a, b in zip(jax.tree.leaves(model.params),
                        jax.tree.leaves(served)):
            assert a is b
        assert eng._serving_params() is served
        assert eng.stats()["serving_params_casts"] == 1

    def test_concurrent_readers_and_swaps_make_one_copy_a_tree(self, model):
        """Readers on more threads than cores against a thread that
        installs trees: a tree is copied once however many readers find
        it new at once, and a reader never gets a copy of a tree that was
        not installed."""
        import sys

        srv = InferenceServer(TransformerEncoder(
            vocab_size=VOCAB, d_model=D, n_heads=HEADS, n_layers=LAYERS,
            causal=True, seed=5).init_model())
        eng = GenerationEngine(server=srv, config=GenerationConfig(**CFG))
        swaps, stop, seen, errors = 8, threading.Event(), set(), []
        trees = [srv.model.params] + [
            jax.tree.map(lambda a, i=i: a + i if jnp.issubdtype(
                a.dtype, jnp.floating) else a, srv.model.params)
            for i in range(1, swaps + 1)]
        own = {id(jax.tree.leaves(t)[0]) for t in trees}

        def read():
            try:
                while not stop.is_set():
                    seen.add(id(jax.tree.leaves(eng._serving_params())[0]))
            except Exception as exc:
                errors.append(exc)

        readers = [threading.Thread(target=read) for _ in range(16)]
        old = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)
        try:
            for t in readers:
                t.start()
            for tree in trees[1:]:
                assert srv.push_weights(tree, source="test")
                eng._serving_params()       # every tree is found once
            stop.set()
            for t in readers:
                t.join(30.0)
                assert not t.is_alive()
        finally:
            stop.set()
            sys.setswitchinterval(old)
            srv.stop()
        assert not errors
        assert seen <= own
        assert eng.stats()["serving_params_casts"] == 1 + swaps

    @pytest.mark.parametrize("loop", ["overlapped", "drained"])
    def test_swap_in_flight_serves_the_new_tree_from_the_next_step(
            self, loop, monkeypatch):
        """The tree is swapped from inside the engine thread's own token
        callback, so the position it lands at is known: the tree is read
        where a step is BUILT, so every token after the step in flight
        (none, when the loop drains every step) is the new weights' — and
        the copy was remade once."""
        if loop == "drained":
            _drain_every_step(monkeypatch)
        fresh = lambda: TransformerEncoder(
            vocab_size=VOCAB, d_model=D, n_heads=HEADS, n_layers=LAYERS,
            causal=True, seed=5).init_model()
        live, swapped = fresh(), fresh()
        stack = GenerationEngine(model=live)._stack
        # another head and another last-block FFN: the next token changes
        # and the K/V rows already in the pool stay the new weights' own
        new = {k: dict(v) for k, v in live.params.items()}
        new[stack.head.name]["W"] = -new[stack.head.name]["W"]
        last = stack.blocks[-1].name
        new[last]["W2"] = -new[last]["W2"]
        swapped.params = new
        srv = InferenceServer(live)
        eng = GenerationEngine(server=srv, config=GenerationConfig(**CFG))
        swap_at, pushed = 4, []
        # overlapped, the step after the callback's was dispatched already
        at = swap_at + (loop == "overlapped")

        def on_token(tok, idx):
            if idx == swap_at - 1:      # `swap_at` tokens are out: swap now
                pushed.append(srv.push_weights(new, source="test"))

        try:
            eng.start()
            assert eng.stats()["serving_params_casts"] == 1
            p = _prompt(5, seed=90)
            old = _dense(live, p, 12)
            out = np.asarray(eng.submit(p, 12, on_token=on_token)
                             .result(120.0))
            assert pushed == [True]
            head = out[:5 + at]
            np.testing.assert_array_equal(head, old[:5 + at])
            np.testing.assert_array_equal(
                out, _dense(swapped, head, 12 - at))
            assert not np.array_equal(out, old)
            assert eng.stats()["serving_params_casts"] == 2
            # a push the verification refuses: copy and count stay
            served = eng._served
            bad = {k: dict(v) for k, v in new.items()}
            bad[last]["W2"] = bad[last]["W2"].at[0, 0].set(jnp.nan)
            assert not srv.push_weights(bad, source="test")
            eng.generate(p, 3, timeout=120.0)
            assert eng._served is served
            assert eng.stats()["serving_params_casts"] == 2
        finally:
            eng.stop()
            srv.stop()


class TestCompileStability:
    def test_zero_fresh_compiles_after_warm_up(self, model):
        from deeplearning4j_tpu.runtime import compile_stats

        eng = _engine(model).start()
        try:
            # warm the step program + the 8- and 16-bucket prefills
            eng.generate(_prompt(4), 3, timeout=120.0)
            eng.generate(_prompt(12), 3, temperature=1.0, seed=1,
                         timeout=120.0)
            snap = compile_stats.snapshot()
            reqs = [
                eng.submit(_prompt(3 + i, seed=60 + i), 4 + i,
                           temperature=float(i % 3) * 0.5,
                           top_k=(i % 4), seed=i)
                for i in range(8)          # all within warmed buckets
            ]
            for r in reqs:
                r.result(120.0)
            delta = compile_stats.snapshot() - snap
            assert delta.fresh_backend_compiles == 0, delta.as_dict()
        finally:
            eng.stop()


# -- prefill/decode disaggregation -------------------------------------------

class TestDisaggregation:
    def test_handoff_between_engines_matches_dense(self, model):
        pre = _engine(model)               # never started: prefill only
        dec = _engine(model).start()
        try:
            p = _prompt(6, seed=70)
            handoff = pre.prefill_detached(p, 10, temperature=1.0, seed=5)
            assert handoff["k"].dtype == np.float32
            out = np.asarray(dec.join_prefilled(handoff).result(120.0))
            np.testing.assert_array_equal(
                out, _dense(model, p, 10, temperature=1.0, seed=5))
        finally:
            dec.stop()

    @pytest.mark.slow
    def test_f32_prefill_feeds_int8_decode(self, model):
        """The handoff crosses the replica boundary in f32 and lands in
        the decode pool's OWN page dtype."""
        pre = _engine(model)
        dec = _engine(model, kv_dtype="int8").start()
        try:
            p = _prompt(5, seed=71)
            out = np.asarray(
                dec.join_prefilled(pre.prefill_detached(p, 8))
                .result(120.0))
            ref = _dense(model, p, 8)
            m = min(len(out), len(ref))
            assert (np.asarray(out[:m]) == ref[:m]).mean() >= 0.8
        finally:
            dec.stop()

    @pytest.mark.slow
    def test_fleet_routes_roles_and_matches_dense(self):
        from deeplearning4j_tpu.serving.fleet import ServingFleet

        def factory():
            return TransformerEncoder(
                vocab_size=VOCAB, d_model=D, n_heads=HEADS,
                n_layers=LAYERS, causal=True, seed=5,
            ).init_model()

        fleet = ServingFleet(
            factory, n_replicas=2, roles=["prefill", "decode"],
            generation_config=GenerationConfig(**CFG),
        ).start()
        try:
            assert [h.role for h in fleet.handles] == ["prefill", "decode"]
            assert fleet.engines["r0"]._thread is None   # no decode loop
            p = _prompt(5, seed=80)
            out = np.asarray(fleet.generate(p, 9, timeout=120.0))
            np.testing.assert_array_equal(
                out, _dense(fleet.replicas[0].model, p, 9))
        finally:
            fleet.stop()

    def test_fleet_roles_must_cover_every_replica(self):
        from deeplearning4j_tpu.serving.fleet import ServingFleet

        with pytest.raises(ValueError, match="roles"):
            ServingFleet(lambda: None, n_replicas=2, roles=["both"])

    def test_router_rejects_when_role_group_empty(self):
        from deeplearning4j_tpu.serving.router import (
            ReplicaHandle, Router,
        )

        class _Stub:
            def health(self):
                return {"status": "serving", "shed_pressure": 0.0,
                        "breaker_state": "closed"}

        h = ReplicaHandle("r0", _Stub(), role="decode")
        router = Router([h])
        assert router.pick_for_role("decode") is h
        with pytest.raises(ServingRejected) as ei:
            router.pick_for_role("prefill")    # nobody serves prefill
        assert ei.value.reason == "no_replicas"
        with pytest.raises(ValueError, match="role"):
            ReplicaHandle("r1", _Stub(), role="oracle")


# -- the HTTP surface --------------------------------------------------------

class TestHTTPGenerate:
    @pytest.fixture()
    def stack(self, model):
        from deeplearning4j_tpu.serving.http import ServingHTTPServer

        srv = InferenceServer(model)
        eng = GenerationEngine(server=srv,
                               config=GenerationConfig(**CFG)).start()
        http = ServingHTTPServer(srv).start()
        yield srv, eng, http
        http.stop()
        eng.stop()
        srv.stop()

    def _post(self, url, payload):
        req = urllib.request.Request(
            url + "v1/generate", json.dumps(payload).encode(),
            {"Content-Type": "application/json"})
        try:
            with urllib.request.urlopen(req, timeout=120) as r:
                return r.status, json.loads(r.read())
        except urllib.error.HTTPError as e:
            return e.code, json.loads(e.read())

    def test_blocking_generate_matches_dense(self, model, stack):
        _, _, http = stack
        p = _prompt(5, seed=90)
        code, doc = self._post(http.url, {
            "prompt": p.tolist(), "max_new_tokens": 7})
        assert code == 200
        np.testing.assert_array_equal(
            np.asarray(doc["tokens"]), _dense(model, p, 7))
        assert doc["prompt_len"] == 5
        assert doc["ttft_ms"] is not None

    def test_streaming_emits_tokens_then_done(self, model, stack):
        _, _, http = stack
        p = _prompt(4, seed=91)
        req = urllib.request.Request(
            http.url + "v1/generate",
            json.dumps({"prompt": p.tolist(), "max_new_tokens": 6,
                        "stream": True}).encode(),
            {"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=120) as r:
            assert r.status == 200
            lines = [json.loads(ln) for ln in r.read().splitlines() if ln]
        assert lines[-1]["done"] is True
        assert lines[-1]["error"] is None
        toks = [ln["token"] for ln in lines[:-1]]
        np.testing.assert_array_equal(
            np.asarray(toks), _dense(model, p, 6)[len(p):])

    def test_over_capacity_and_bad_prompt_are_400(self, stack):
        _, _, http = stack
        code, _ = self._post(http.url, {"prompt": _prompt(8).tolist(),
                                        "max_new_tokens": 40})
        assert code == 400
        code, _ = self._post(http.url, {"prompt": "not tokens"})
        assert code == 400

    def test_replica_without_engine_is_400(self, model):
        from deeplearning4j_tpu.serving.http import ServingHTTPServer

        srv = InferenceServer(model)
        http = ServingHTTPServer(srv).start()
        try:
            code, doc = self._post(http.url, {"prompt": [1, 2]})
            assert code == 400
            assert "engine" in doc["error"]
        finally:
            http.stop()
            srv.stop()

    def test_kv_exhaustion_is_429_over_http(self, model):
        from deeplearning4j_tpu.serving.http import ServingHTTPServer

        srv = InferenceServer(model)
        eng = GenerationEngine(
            server=srv,
            config=GenerationConfig(**{**CFG, "num_pages": 3})).start()
        http = ServingHTTPServer(srv).start()
        try:
            code, doc = self._post(http.url, {
                "prompt": _prompt(17).tolist(), "max_new_tokens": 4})
            assert code == 429
            assert doc["reason"] == "kv_exhausted"
        finally:
            http.stop()
            eng.stop()
            srv.stop()


# -- telemetry ---------------------------------------------------------------

class TestTelemetry:
    def test_token_counter_and_kv_gauges_move(self, model):
        from deeplearning4j_tpu.observe.metrics import registry

        eng = _engine(model).start()
        try:
            before = registry().counter("dl4jtpu_decode_tokens_total").value()
            eng.generate(_prompt(4), 5, timeout=120.0)
            after = registry().counter("dl4jtpu_decode_tokens_total").value()
            assert after >= before + 5
            assert registry().gauge("dl4jtpu_kv_pages_total").value() \
                == CFG["num_pages"] - 1
            st = eng.stats()
            assert st["tokens_generated"] >= 5
            assert st["kv"]["used_pages"] == 0
        finally:
            eng.stop()
