"""Performance attribution (observe/cost.py + trace additions): the
compiled-program registry, XLA cost analysis vs hand-computed FLOPs,
MFU/roofline gauges, build-info, trace ring drop accounting, and the
cross-worker trace merge."""

import numpy as np
import pytest

from deeplearning4j_tpu.data.dataset import DataSet
from deeplearning4j_tpu.models import SequentialModel
from deeplearning4j_tpu.nn.activations import Activation
from deeplearning4j_tpu.nn.conf import (
    Dense,
    InputType,
    NeuralNetConfiguration,
    OutputLayer,
)
from deeplearning4j_tpu.nn.losses import Loss
from deeplearning4j_tpu.nn.updaters import Sgd
from deeplearning4j_tpu.observe import cost, registry

pytestmark = pytest.mark.observe

B, I, O = 64, 256, 128


def dense_model(seed=1):
    conf = (
        NeuralNetConfiguration.builder()
        .seed(seed)
        .updater(Sgd(0.01))
        .list()
        .layer(OutputLayer(n_out=O, loss=Loss.MSE,
                           activation=Activation.IDENTITY))
        .set_input_type(InputType.feed_forward(I))
        .build()
    )
    return SequentialModel(conf).init()


def batch(rng=None):
    rng = rng or np.random.default_rng(0)
    return DataSet(
        rng.normal(size=(B, I)).astype(np.float32),
        rng.normal(size=(B, O)).astype(np.float32),
    )


def train_records(model):
    return [r for r in cost.analyze_model(model) if r.kind == "train"]


def set_cpu_peaks(monkeypatch, flops, membw):
    """Pin the table's per-device CPU row (there is no env override)."""
    monkeypatch.setitem(cost.PEAKS_BY_DEVICE_KIND, "cpu", (flops, membw))


class TestPeaksTable:
    def test_cpu_row_times_local_device_count(self, monkeypatch):
        import jax

        set_cpu_peaks(monkeypatch, 2e12, 3e11)
        n = jax.local_device_count()
        assert cost.peaks() == (2e12 * n, 3e11 * n)

    def test_unknown_device_kind_raises(self, monkeypatch):
        """A device the table does not list is an error, never a
        default peak."""
        monkeypatch.delitem(cost.PEAKS_BY_DEVICE_KIND, "cpu")
        with pytest.raises(cost.UnknownDeviceKind, match="cpu"):
            cost.peaks()

    def test_v5e_reports_as_v5_lite(self):
        assert cost.PEAKS_BY_DEVICE_KIND["TPU v5 lite"] == (197.0e12, 8.19e11)


class TestProgramRegistry:
    def test_flops_match_hand_computed_dense_matmul(self):
        """Acceptance: XLA cost-analysis FLOPs for a known dense-matmul
        model within 5% of hand-computed.  One Dense output layer's
        train step runs the forward matmul (2*B*I*O) and the dW matmul
        (2*B*I*O); the input-gradient matmul is dead code (no upstream
        layer wants it) and XLA DCEs it.  Bias/loss/updater terms are
        O(B*O + I*O) — under 2% at these dims."""
        m = dense_model()
        m.fit([batch()], epochs=1)
        recs = train_records(m)
        assert len(recs) == 1
        rec = recs[0]
        assert rec.analysis == "ok"
        hand = 4.0 * B * I * O
        assert abs(rec.flops - hand) / hand < 0.05
        assert rec.bytes_accessed > 0
        assert rec.signature is not None
        assert rec.dispatches == 1
        # first-dispatch compile tax was captured
        assert rec.backend_compiles >= 1
        assert rec.compile_secs > 0

    def test_memory_analysis_fields_guarded(self):
        m = dense_model()
        m.fit([batch()], epochs=1)
        rec = train_records(m)[0]
        rec.ensure_analysis(memory=True)
        d = rec.as_dict()
        # on the CPU backend these are present; the contract is "present
        # or None, never a raised analysis"
        if rec._memory_done and rec.argument_bytes is not None:
            assert d["argument_bytes"] > 0
            assert d["peak_bytes"] >= d["argument_bytes"]

    def test_no_cross_model_bleed_and_refit_reuses_entry(self):
        m1, m2 = dense_model(1), dense_model(2)
        m1.fit([batch()], epochs=1)
        m2.fit([batch()], epochs=1)
        mine = [r for r in cost.registry().programs()
                if r.owner_ref() in (m1, m2) and r.kind == "train"]
        owners = {id(r.owner_ref()) for r in mine}
        assert len(mine) == 2 and len(owners) == 2
        ids_before = {r.program_id for r in mine}
        # re-fit hits the cached step fn: same registry entries, more
        # dispatches, no new programs
        m1.fit([batch()], epochs=1)
        after = [r for r in cost.registry().programs()
                 if r.owner_ref() in (m1, m2) and r.kind == "train"]
        assert {r.program_id for r in after} == ids_before
        r1 = [r for r in after if r.owner_ref() is m1][0]
        assert r1.dispatches == 2

    def test_eviction_on_step_fn_cache_clear(self):
        """recovery's LR retrace (train/recovery.py) and re-distribute
        clear the model's step-fn cache; the registry must drop those
        programs instead of reporting stale entries."""
        m = dense_model()
        m.fit([batch()], epochs=1)
        assert train_records(m)
        m._step_fns.clear()     # what _LrScaledTx installation does
        assert [r for r in cost.registry().programs()
                if r.owner_ref() is m] == []
        # a fresh fit re-registers under a NEW record
        m.fit([batch()], epochs=1)
        recs = train_records(m)
        assert len(recs) == 1 and recs[0].dispatches == 1

    def test_dead_model_is_pruned(self):
        m = dense_model()
        m.fit([batch()], epochs=1)
        mid = id(m)
        del m
        import gc

        gc.collect()
        assert not any(
            id(r.owner_ref()) == mid
            for r in cost.registry().programs()
            if r.owner_ref() is not None
        )


class TestStepGauges:
    def test_mfu_and_flops_gauges_flow_after_analysis(self, monkeypatch):
        set_cpu_peaks(monkeypatch, 1e12, 1e11)
        m = dense_model()
        m.fit([batch()], epochs=1)
        rec = train_records(m)[0]     # triggers analysis
        reg = registry()
        flops_before = reg.counter(
            "dl4jtpu_step_model_flops_total"
        ).value()
        m.fit([batch()], epochs=3)
        flops_after = reg.counter("dl4jtpu_step_model_flops_total").value()
        assert flops_after - flops_before == pytest.approx(3 * rec.flops)
        ach = reg.gauge("dl4jtpu_step_achieved_flops_per_sec").value()
        mfu = reg.gauge("dl4jtpu_step_mfu").value()
        assert ach > 0
        import jax

        n = jax.local_device_count()
        assert mfu == pytest.approx(ach / (1e12 * n))
        assert reg.gauge("dl4jtpu_step_bytes_per_sec").value() > 0
        assert reg.gauge("dl4jtpu_step_membw_util").value() > 0

    def test_grouped_program_counts_k_steps_of_flops(self):
        """XLA cost analysis counts a lax.scan body ONCE, so the k-step
        grouped program reports ~single-step flops; the per-dispatch
        attribution must multiply by the group size."""
        rng = np.random.default_rng(3)
        m = dense_model()
        batches = [batch(rng) for _ in range(4)]
        m.fit(batches, epochs=1, steps_per_execution=4)
        recs = [r for r in cost.analyze_model(m)
                if r.kind == "train_multi"]
        assert len(recs) == 1
        rec = recs[0]
        # body-once: grouped flops within 10% of the single-step program
        hand = 4.0 * B * I * O
        assert abs(rec.flops - hand) / hand < 0.10
        reg = registry()
        before = reg.counter("dl4jtpu_step_model_flops_total").value()
        m.fit(batches, epochs=1, steps_per_execution=4)
        after = reg.counter("dl4jtpu_step_model_flops_total").value()
        assert after - before == pytest.approx(4 * rec.flops)

    def test_roofline_classification_follows_ridge(self, monkeypatch):
        m = dense_model()
        m.fit([batch()], epochs=1)
        rec = train_records(m)[0]
        ai = rec.arithmetic_intensity()
        assert ai > 0
        # ridge far below AI -> compute-bound; far above -> memory-bound
        set_cpu_peaks(monkeypatch, 1e12, 1e12 / (ai / 10))
        assert rec.roofline() == "compute-bound"
        set_cpu_peaks(monkeypatch, 1e12, 1e12 / (ai * 10))
        assert rec.roofline() == "memory-bound"

    def test_roofline_stamped_on_step_span(self, monkeypatch):
        from deeplearning4j_tpu.observe import tracer

        set_cpu_peaks(monkeypatch, 1e12, 1e11)
        m = dense_model()
        m.fit([batch()], epochs=1)
        train_records(m)              # analyze
        t = tracer()
        t.enable()
        try:
            t.clear()
            m.fit([batch()], epochs=1)
            steps = [
                ev for ev in t.to_chrome_trace()["traceEvents"]
                if ev["name"] == "train_step"
            ]
            assert steps and steps[-1]["args"]["roofline"] in (
                "compute-bound", "memory-bound"
            )
        finally:
            t.disable()

    def test_program_table_shape(self):
        m = dense_model()
        m.fit([batch()], epochs=1)
        table = cost.program_table(analyze=True)
        mine = [row for row in table
                if row["kind"] == "train" and row["flops"]]
        assert mine
        row = mine[-1]
        for k in ("id", "model", "kind", "key", "signature", "dispatches",
                  "compile_secs", "flops", "bytes_accessed",
                  "arithmetic_intensity", "roofline", "analysis"):
            assert k in row


class TestBuildInfo:
    def test_build_info_series_is_self_describing(self):
        import jax

        from deeplearning4j_tpu.version import __version__

        text = registry().to_prometheus_text()
        lines = [l for l in text.splitlines()
                 if l.startswith("dl4jtpu_build_info{")]
        assert len(lines) == 1
        line = lines[0]
        assert f'version="{__version__}"' in line
        assert f'jax="{jax.__version__}"' in line
        assert 'backend="cpu"' in line
        assert 'device_count="' in line
        assert line.endswith(" 1")


class TestTraceDrops:
    def test_ring_wrap_counts_drops_and_stamps_metadata(self):
        from deeplearning4j_tpu.observe.trace import TraceRecorder

        t = TraceRecorder(capacity=8)
        t.enable()
        for i in range(20):
            t.add_complete(f"s{i}", float(i), 0.001)
        assert len(t) == 8
        assert t.spans_dropped == 12
        doc = t.to_chrome_trace()
        assert doc["metadata"]["spans_dropped"] == 12
        assert doc["metadata"]["capacity"] == 8

    def test_global_tracer_bridges_drops_to_counter(self):
        from deeplearning4j_tpu.observe import tracer

        t = tracer()
        was_enabled = t.enabled
        before = t.spans_dropped
        t.enable()
        try:
            for i in range(t.capacity + 5):
                t.add_complete("x", float(i), 0.0)
        finally:
            if not was_enabled:
                t.disable()
        assert t.spans_dropped >= before + 5
        reg = registry()
        reg.collect()
        assert reg.counter(
            "dl4jtpu_trace_spans_dropped_total"
        ).value() >= t.spans_dropped


class TestTraceMerge:
    def test_merged_cluster_trace_pid_mapping(self):
        from deeplearning4j_tpu.observe.trace import merge_chrome_traces

        def doc(name, dropped=0):
            return {
                "traceEvents": [
                    {"name": name, "ph": "X", "ts": 1.0, "dur": 2.0,
                     "pid": 4242, "tid": 1},
                ],
                "metadata": {"spans_dropped": dropped},
            }

        merged = merge_chrome_traces(
            {"w1": doc("a", dropped=3), "w0": doc("b")},
            pids={"w0": 0, "w1": 1},
        )
        evs = merged["traceEvents"]
        # per-worker process_name metadata events under the mapped pids
        names = {(e["pid"], e["args"]["name"]) for e in evs
                 if e.get("ph") == "M"}
        assert names == {(0, "w0"), (1, "w1")}
        spans = {(e["pid"], e["name"]) for e in evs if e.get("ph") == "X"}
        assert spans == {(0, "b"), (1, "a")}
        assert merged["metadata"]["spans_dropped"] == 3
        assert merged["metadata"]["workers"]["w1"]["pid"] == 1

    def test_merge_without_pids_uses_stable_sorted_index(self):
        from deeplearning4j_tpu.observe.trace import merge_chrome_traces

        merged = merge_chrome_traces({
            "b": {"traceEvents": []}, "a": {"traceEvents": []},
        })
        assert merged["metadata"]["workers"]["a"]["pid"] == 0
        assert merged["metadata"]["workers"]["b"]["pid"] == 1

    def test_merge_fallback_pids_stay_disjoint_from_explicit_ranks(self):
        """A rank-less worker's fallback pid must never collide with
        another worker's explicit rank — that would fuse two timelines
        under one Perfetto process."""
        from deeplearning4j_tpu.observe.trace import merge_chrome_traces

        merged = merge_chrome_traces(
            {"ranked": {"traceEvents": []},
             "anon1": {"traceEvents": []},
             "anon2": {"traceEvents": []}},
            pids={"ranked": 1},
        )
        w = merged["metadata"]["workers"]
        pids = {info["pid"] for info in w.values()}
        assert len(pids) == 3
        assert w["ranked"]["pid"] == 1
        assert w["anon1"]["pid"] == 0 and w["anon2"]["pid"] == 2

    def test_merge_duplicate_explicit_ranks_get_distinct_pids(self):
        """An elastic respawn can reuse a dead worker's rank while the
        dead worker's trace is still inside the fleet TTL — the two must
        not fuse under one pid."""
        from deeplearning4j_tpu.observe.trace import merge_chrome_traces

        merged = merge_chrome_traces(
            {"gen1-w": {"traceEvents": []},
             "gen2-w": {"traceEvents": []}},
            pids={"gen1-w": 0, "gen2-w": 0},
        )
        w = merged["metadata"]["workers"]
        assert w["gen1-w"]["pid"] != w["gen2-w"]["pid"]
        assert w["gen1-w"]["pid"] == 0          # first holder keeps it
