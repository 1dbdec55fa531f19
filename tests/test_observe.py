"""Telemetry-spine tests — observe/{metrics,trace,health}: registry
semantics under threads, Prometheus text golden output, Chrome-trace
JSON schema round-trip, per-step span instrumentation of the fit loops,
and the NaN-injection divergence watchdog (all CPU-safe, tier-1)."""

import json
import os
import re
import threading

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
from deeplearning4j_tpu.observe import (
    DivergenceError,
    HealthListener,
    MetricsRegistry,
    registry,
    tracer,
)
from deeplearning4j_tpu.observe.trace import TraceRecorder


pytestmark = pytest.mark.observe


def small_model():
    conf = (
        NeuralNetConfiguration.builder()
        .seed(4)
        .updater(Sgd(0.1))
        .list()
        .layer(Dense(n_out=8, activation=Activation.TANH))
        .layer(OutputLayer(n_out=3, loss=Loss.MCXENT,
                           activation=Activation.SOFTMAX))
        .set_input_type(InputType.feed_forward(5))
        .build()
    )
    return SequentialModel(conf).init()


def batch(seed=0, nan=False):
    rng = np.random.default_rng(seed)
    x = rng.normal(0, 1, (16, 5)).astype(np.float32)
    if nan:
        x[0, 0] = np.nan
    y = np.eye(3, dtype=np.float32)[rng.integers(0, 3, 16)]
    return DataSet(x, y)


class TestRegistry:
    def test_counter_semantics(self):
        reg = MetricsRegistry()
        c = reg.counter("t_events_total", "help")
        c.inc()
        c.inc(2.5)
        assert c.value() == 3.5
        with pytest.raises(ValueError):
            c.inc(-1)
        # labeled series are independent
        c.inc(kind="a")
        c.inc(kind="a")
        c.inc(kind="b")
        assert c.value(kind="a") == 2 and c.value(kind="b") == 1
        assert c.value() == 3.5
        # same name returns the same family; wrong type raises
        assert reg.counter("t_events_total") is c
        with pytest.raises(TypeError):
            reg.gauge("t_events_total")

    def test_counter_set_total_is_monotonic(self):
        reg = MetricsRegistry()
        c = reg.counter("t_bridge_total")
        c.set_total(10)
        c.set_total(7)      # an external source can't go backwards
        assert c.value() == 10

    def test_gauge_semantics(self):
        reg = MetricsRegistry()
        g = reg.gauge("t_gauge")
        g.set(5)
        g.set(2, worker="w0")
        g.inc(1)
        assert g.value() == 6 and g.value(worker="w0") == 2
        g.remove(worker="w0")
        assert g.value(worker="w0") == 0

    def test_histogram_buckets_boundary_and_overflow(self):
        reg = MetricsRegistry()
        h = reg.histogram("t_hist", buckets=(0.1, 1.0))
        for v in (0.05, 0.1, 0.5, 1.0, 99.0):
            h.observe(v)
        assert h.count == 5
        assert h.sum == pytest.approx(100.65)
        text = "\n".join(h.expose())
        # le= is cumulative: 0.1 catches 0.05 AND the boundary 0.1
        assert 't_hist_bucket{le="0.1"} 2' in text
        assert 't_hist_bucket{le="1"} 4' in text
        assert 't_hist_bucket{le="+Inf"} 5' in text
        assert "t_hist_count 5" in text

    def test_thread_safety_exact_counts(self):
        reg = MetricsRegistry()
        c = reg.counter("t_mt_total")
        h = reg.histogram("t_mt_hist", buckets=(0.5,))
        n_threads, per = 8, 2000

        def work():
            for _ in range(per):
                c.inc()
                h.observe(0.25)

        threads = [threading.Thread(target=work) for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert c.value() == n_threads * per
        assert h.count == n_threads * per

    def test_collectors_refresh_and_never_break_the_scrape(self):
        reg = MetricsRegistry()
        g = reg.gauge("t_pull")
        state = {"v": 1.0}
        reg.register_collector(lambda: g.set(state["v"]))

        def broken():
            raise RuntimeError("boom")

        reg.register_collector(broken)
        text = reg.to_prometheus_text()
        assert "t_pull 1" in text
        state["v"] = 2.0
        assert "t_pull 2" in reg.to_prometheus_text()
        reg.unregister_collector(broken)

    def test_snapshot_prefix_filter(self):
        reg = MetricsRegistry()
        reg.counter("aaa_total").inc()
        reg.counter("bbb_total").inc()
        snap = reg.snapshot(prefixes=("aaa_",))
        assert list(snap) == ["aaa_total"]
        assert snap["aaa_total"]["value"] == 1


class TestPrometheusGolden:
    def test_text_exposition_golden(self):
        """Exact text-format 0.0.4 output for a known registry state."""
        reg = MetricsRegistry()
        c = reg.counter("app_requests_total", "Requests served")
        c.inc(3, method="get")
        c.inc(1, method="post")
        g = reg.gauge("app_temp_celsius", "Temperature")
        g.set(36.6)
        h = reg.histogram("app_latency_seconds", "Latency", buckets=(0.1, 1.0))
        h.observe(0.05)
        h.observe(0.5)
        golden = "\n".join([
            "# HELP app_latency_seconds Latency",
            "# TYPE app_latency_seconds histogram",
            'app_latency_seconds_bucket{le="0.1"} 1',
            'app_latency_seconds_bucket{le="1"} 2',
            'app_latency_seconds_bucket{le="+Inf"} 2',
            "app_latency_seconds_sum 0.55",
            "app_latency_seconds_count 2",
            "# HELP app_requests_total Requests served",
            "# TYPE app_requests_total counter",
            'app_requests_total{method="get"} 3',
            'app_requests_total{method="post"} 1',
            "# HELP app_temp_celsius Temperature",
            "# TYPE app_temp_celsius gauge",
            "app_temp_celsius 36.6",
        ]) + "\n"
        assert reg.to_prometheus_text() == golden

    def test_nonfinite_values_expose_as_prometheus_literals(self):
        """A diverged run sets the health gauges to NaN — the scrape
        that matters most must render NaN/+Inf, not raise."""
        reg = MetricsRegistry()
        g = reg.gauge("nf_gauge")
        g.set(float("nan"))
        g.set(float("inf"), kind="hi")
        g.set(float("-inf"), kind="lo")
        text = reg.to_prometheus_text()
        assert "nf_gauge NaN" in text
        assert 'nf_gauge{kind="hi"} +Inf' in text
        assert 'nf_gauge{kind="lo"} -Inf' in text

    def test_label_escaping(self):
        reg = MetricsRegistry()
        c = reg.counter("esc_total")
        c.inc(path='a"b\\c\nd')
        line = [l for l in reg.to_prometheus_text().splitlines()
                if l.startswith("esc_total{")][0]
        assert line == 'esc_total{path="a\\"b\\\\c\\nd"} 1'

    def test_global_registry_predeclares_core_families(self):
        text = registry().to_prometheus_text()
        for family in (
            "dl4jtpu_compile_backend_compiles_total",
            "dl4jtpu_etl_wait_seconds_total",
            "dl4jtpu_data_cache_batches_total",
            "dl4jtpu_step_latency_seconds",
            "dl4jtpu_health_checks_total",
            "dl4jtpu_health_divergence_total",
        ):
            assert f"# TYPE {family}" in text, family


class TestTraceRecorder:
    def test_chrome_trace_schema_roundtrip(self):
        rec = TraceRecorder(capacity=64).enable()
        with rec.span("outer", cat="test", note="x"):
            with rec.span("inner", cat="test"):
                pass
        obj = json.loads(json.dumps(rec.to_chrome_trace()))
        events = obj["traceEvents"]
        assert {e["name"] for e in events} == {"outer", "inner"}
        for e in events:
            assert e["ph"] == "X"
            assert isinstance(e["ts"], (int, float))
            assert isinstance(e["dur"], (int, float)) and e["dur"] >= 0
            assert isinstance(e["pid"], int) and isinstance(e["tid"], int)
        # ts-sorted; inner nests within outer
        outer = next(e for e in events if e["name"] == "outer")
        inner = next(e for e in events if e["name"] == "inner")
        assert outer["ts"] <= inner["ts"]
        assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-3
        assert outer["args"] == {"note": "x"}

    def test_ring_buffer_evicts_oldest(self):
        rec = TraceRecorder(capacity=4).enable()
        for i in range(10):
            rec.add_complete(f"s{i}", float(i), 0.5)
        names = [e["name"] for e in rec.to_chrome_trace()["traceEvents"]]
        assert names == ["s6", "s7", "s8", "s9"]

    def test_disabled_records_nothing(self):
        rec = TraceRecorder()
        with rec.span("nope"):
            pass
        rec.add_complete("nope", 0.0, 1.0)
        assert len(rec) == 0

    def test_save_roundtrip(self, tmp_path):
        rec = TraceRecorder().enable()
        with rec.span("work", cat="func"):
            pass
        path = rec.save(str(tmp_path / "trace.json"))
        import pathlib

        obj = json.loads(pathlib.Path(path).read_text())
        assert any("work" in e["name"] for e in obj["traceEvents"])

    def test_span_keeps_its_one_timing(self):
        """A call site that needs the number reads it off the span: the
        ring entry is that same timing, and a disabled ring still
        times."""
        rec = TraceRecorder()
        with rec.span("quiet") as sp:
            pass
        assert len(rec) == 0 and sp.dur >= 0.0 and sp.t0 > 0.0
        rec.enable()
        with rec.span("loud", cat="test", n=3) as sp:
            pass
        (ev,) = rec.to_chrome_trace()["traceEvents"]
        assert ev["ts"] == round(sp.t0 * 1e6, 3)
        assert ev["dur"] == round(sp.dur * 1e6, 3)
        assert ev["args"] == {"n": 3}

    def test_span_records_on_error_and_does_not_swallow(self):
        rec = TraceRecorder().enable()
        with pytest.raises(KeyError):
            with rec.span("boom") as sp:
                raise KeyError("x")
        assert sp.dur is not None and len(rec) == 1


class TestSpansAreProfilerAnnotations:
    """One span, two sinks: whenever a `jax.profiler` session records,
    a recorder span is an event on its host plane — ring or no ring,
    and nothing to switch."""

    def test_ring_disabled_span_is_a_profiler_event(self, host_profile):
        rec = TraceRecorder()

        def work():
            with rec.span("outer_phase", cat="t", bucket=8):
                with rec.span("inner_phase"):
                    pass

        with host_profile() as prof:
            t = threading.Thread(target=work)
            t.start()
            t.join()
            with rec.span("main_thread_phase"):
                pass
        assert len(rec) == 0
        line = prof.line_with("outer_phase")
        outer = next(e for e in line if e["name"] == "outer_phase")
        inner = next(e for e in line if e["name"] == "inner_phase")
        # the name is bare, the span's args are the event's stats
        assert outer["stats"] == {"bucket": 8}
        assert outer["start"] <= inner["start"] <= inner["end"] \
            <= outer["end"]
        # a line per thread: the main thread's span is on another
        assert prof.line_with("main_thread_phase") is not line

    def test_ring_enabled_span_feeds_both_sinks(self, host_profile):
        rec = TraceRecorder().enable()
        with host_profile() as prof:
            with rec.span("both_sinks", cat="t", k=2) as sp:
                pass
        (ev,) = rec.to_chrome_trace()["traceEvents"]
        assert ev["name"] == "both_sinks" and ev["args"] == {"k": 2}
        (pe,) = [e for e in prof.line_with("both_sinks")
                 if e["name"] == "both_sinks"]
        assert pe["stats"] == {"k": 2}
        # one enter/exit: the two sinks agree on the duration to within
        # the few hundred nanoseconds between their clock reads
        assert abs((pe["end"] - pe["start"]) * 1e-9 - sp.dur) < 1e-4

    def test_add_complete_stays_ring_only(self, host_profile):
        rec = TraceRecorder().enable()
        with host_profile() as prof:
            rec.add_complete("measured_elsewhere", 1.0, 0.5)
            with rec.span("marker"):
                pass
        assert len(rec) == 2
        assert not any(e["name"] == "measured_elsewhere"
                       for ln in prof.lines for e in ln)

    def test_fit_phases_appear_with_the_ring_off(self, host_profile,
                                                 monkeypatch):
        import jax

        rec = tracer()
        assert not rec.enabled
        blocked = []
        real = jax.block_until_ready
        monkeypatch.setattr(jax, "block_until_ready",
                            lambda x: blocked.append(1) or real(x))
        from deeplearning4j_tpu.observe.trace import StepScope

        m = small_model()
        m.fit([batch(0)], epochs=1)            # compile outside
        with host_profile() as prof:
            m.fit([batch(i) for i in range(3)], epochs=1)
            # a session alone never makes the step synchronous
            with StepScope(0) as scope:
                scope.sync(np.zeros(1))
        assert len(rec) == 0 and not blocked
        line = prof.line_with("device_sync")
        names = [e["name"] for e in line]
        for phase in ("host_stage", "dispatch", "device_sync"):
            assert names.count(phase) == 3, phase
        # ... the ring does
        rec.enable()
        try:
            with StepScope(0) as scope:
                scope.sync(np.zeros(1))
        finally:
            rec.disable()
            rec.clear()
        assert blocked == [1]


class TestStepTimeline:
    def test_fit_emits_five_phase_spans(self):
        rec = tracer()
        rec.enable()
        rec.clear()
        try:
            m = small_model()
            m.fit([batch(i) for i in range(3)], epochs=1)
        finally:
            rec.disable()
        names = {e["name"] for e in rec.to_chrome_trace()["traceEvents"]}
        assert {"etl_wait", "host_stage", "dispatch", "device_sync",
                "train_step"} <= names
        # listeners span appears once listeners exist
        rec.enable()
        rec.clear()
        try:
            m2 = small_model()
            m2.set_listeners(HealthListener(frequency=1,
                                            write_reports=False))
            m2.fit([batch(0)], epochs=1)
        finally:
            rec.disable()
        names = {e["name"] for e in rec.to_chrome_trace()["traceEvents"]}
        assert "listeners" in names and "health_check" in names

    def test_step_latency_histogram_and_counters_advance(self):
        reg = registry()
        hist = reg.histogram("dl4jtpu_step_latency_seconds")
        steps = reg.counter("dl4jtpu_train_steps_total")
        wait = reg.counter("dl4jtpu_etl_wait_seconds_total")
        c0, s0, w0 = hist.count, steps.value(), wait.value()
        m = small_model()
        m.fit([batch(i) for i in range(3)], epochs=1)
        assert hist.count == c0 + 3
        assert steps.value() == s0 + 3
        assert wait.value() > w0

    def test_grouped_steps_count_k(self):
        reg = registry()
        steps = reg.counter("dl4jtpu_train_steps_total")
        s0 = steps.value()
        m = small_model()
        m.fit([batch(i) for i in range(4)], epochs=1,
              steps_per_execution=2)
        assert steps.value() == s0 + 4


class TestCachedIteratorBridge:
    def test_cache_source_labels(self, tmp_path):
        from deeplearning4j_tpu.data.cached import CachedDataSetIterator
        from deeplearning4j_tpu.data.iterator import ExistingDataSetIterator

        reg = registry()
        c = reg.counter("dl4jtpu_data_cache_batches_total")
        d0, h0 = c.value(source="decode"), c.value(source="cache")
        base = ExistingDataSetIterator([batch(0), batch(1)])
        it = CachedDataSetIterator(base, str(tmp_path / "cache"))
        assert len(list(it)) == 2          # populate epoch
        assert len(list(it)) == 2          # replay epoch
        assert c.value(source="decode") == d0 + 2
        assert c.value(source="cache") == h0 + 2


class TestCoordinatorBridge:
    def test_heartbeat_age_gauge(self):
        from deeplearning4j_tpu.runtime.coordinator import (
            CoordinatorClient,
            CoordinatorServer,
        )

        server = CoordinatorServer(expected_workers=1).start()
        try:
            client = CoordinatorClient(server.address, "w0")
            client.register()
            client.heartbeat()
            reg = registry()
            reg.collect()
            age = reg.gauge("dl4jtpu_coordinator_heartbeat_age_seconds")
            assert 0.0 <= age.value(worker="w0") < 5.0
            assert reg.gauge("dl4jtpu_coordinator_members").value() == 1
        finally:
            server.stop()
        # stop() drops the series instead of freezing them: a dead
        # coordinator must not keep exporting a small stale age
        text = reg.to_prometheus_text()
        assert 'heartbeat_age_seconds{worker="w0"}' not in text
        assert reg.gauge("dl4jtpu_coordinator_members").value() == 0


class TestHealthListener:
    def test_healthy_run_no_events(self):
        m = small_model()
        hl = HealthListener(frequency=1, write_reports=False)
        m.set_listeners(hl)
        for i in range(4):
            m.fit_batch(batch(i))
        assert hl.events == []
        assert hl.baseline_norm and hl.baseline_norm > 0
        assert hl.last_global_norm > 0
        assert hl.last_update_norm is not None and hl.last_update_norm > 0

    def test_nan_injection_flagged_within_two_monitored_steps(self,
                                                              tmp_path,
                                                              monkeypatch):
        from deeplearning4j_tpu.runtime import crash

        monkeypatch.setenv(crash.ENV_CRASH_DIR, str(tmp_path))
        reg = registry()
        div = reg.counter("dl4jtpu_health_divergence_total")
        m = small_model()
        hl = HealthListener(frequency=1)
        m.set_listeners(hl)
        m.fit_batch(batch(0))
        m.fit_batch(batch(1))
        inject_at = m.iteration + 1
        m.fit_batch(batch(2, nan=True))      # the poisoned step
        m.fit_batch(batch(3))
        assert hl.diverged
        first = hl.events[0]
        assert first["iteration"] - inject_at < 2
        assert first["kind"] in ("nonfinite_score", "nonfinite_params")
        assert div.value(kind=first["kind"]) >= 1
        # routed into runtime/crash.py's report writer
        import pathlib

        assert hl.report_paths
        text = pathlib.Path(hl.report_paths[0]).read_text()
        assert "DIVERGENCE EVENT" in text
        assert first["kind"] in text
        assert "live jax.Array buffers" in text

    def test_norm_explosion_detection(self):
        import jax
        import jax.numpy as jnp

        m = small_model()
        hl = HealthListener(frequency=1, norm_explosion_factor=10.0,
                            write_reports=False)
        m.set_listeners(hl)
        m.fit_batch(batch(0))                # establishes the baseline
        assert hl.baseline_norm is not None
        m.params = jax.tree.map(lambda a: a * 1e4, m.params)
        hl.iteration_done(m, m.iteration + 1, 0, 0.5)
        assert hl.events and hl.events[0]["kind"] == "norm_explosion"

    def test_raise_on_divergence(self):
        m = small_model()
        hl = HealthListener(frequency=1, raise_on_divergence=True,
                            write_reports=False)
        m.set_listeners(hl)
        reg = registry()
        steps = reg.counter("dl4jtpu_train_steps_total")
        s0 = steps.value()
        with pytest.raises(DivergenceError) as ei:
            m.fit_batch(batch(0, nan=True))
        assert ei.value.event["kind"] in ("nonfinite_score",
                                          "nonfinite_params")
        # the listener threw AFTER the device update: the step DID run,
        # so /metrics must agree with model.iteration
        assert steps.value() == s0 + 1
        assert m.iteration == 1

    def test_grouped_dispatch_reduces_once_per_program(self):
        """steps_per_execution dispatches k listener calls after ONE
        device update — the param reduction must run once per program,
        not k times (a re-run on identical params would clobber the
        |Δw| gauge with ~0)."""
        reg = registry()
        checks = reg.counter("dl4jtpu_health_checks_total")
        c0 = checks.value()
        m = small_model()
        hl = HealthListener(frequency=1, write_reports=False)
        m.set_listeners(hl)
        m.fit([batch(i) for i in range(4)], epochs=1,
              steps_per_execution=4)
        assert checks.value() == c0 + 1
        assert hl.events == []

    def test_divergence_reports_get_distinct_paths(self, tmp_path,
                                                   monkeypatch):
        from deeplearning4j_tpu.runtime import crash

        monkeypatch.setenv(crash.ENV_CRASH_DIR, str(tmp_path))
        p1 = crash.write_divergence_report({"kind": "nonfinite_score"})
        p2 = crash.write_divergence_report({"kind": "nonfinite_score"})
        assert p1 != p2 and os.path.exists(p1) and os.path.exists(p2)

    def test_cadence_thins_checks(self):
        reg = registry()
        checks = reg.counter("dl4jtpu_health_checks_total")
        c0 = checks.value()
        m = small_model()
        m.set_listeners(HealthListener(frequency=3, write_reports=False))
        for i in range(7):
            m.fit_batch(batch(i))
        assert checks.value() == c0 + 2      # iterations 3 and 6


class TestBenchMetricsRow:
    def test_entry_carries_metrics_snapshot(self):
        import bench

        row = bench._entry("cfg", 100.0, None, None, 8)
        assert "metrics" in row and row["metrics"] is not None
        assert any(k.startswith("dl4jtpu_compile_") for k in row["metrics"])


METRIC_LINE = re.compile(
    r"^[A-Za-z_:][A-Za-z0-9_:]*(\{[^{}]*\})?$"
)


class TestMetricsEndpointSmoke:
    """CI smoke: boot UIServer on an ephemeral port, scrape /metrics,
    assert the core families are present and every line parses."""

    def test_scrape_parses_and_has_core_families(self):
        import urllib.request

        from deeplearning4j_tpu.ui.server import UIServer

        m = small_model()
        m.set_listeners(HealthListener(frequency=1, write_reports=False))
        m.fit([batch(i) for i in range(2)], epochs=1)
        server = UIServer(port=0)
        try:
            with urllib.request.urlopen(server.url + "metrics") as r:
                assert r.headers["Content-Type"].startswith("text/plain")
                text = r.read().decode()
        finally:
            server.stop()
        for family in (
            "dl4jtpu_compile_backend_compiles_total",   # compile
            "dl4jtpu_etl_wait_seconds_total",           # ETL wait
            "dl4jtpu_data_cache_batches_total",         # cache
            "dl4jtpu_step_latency_seconds_bucket",      # step-latency hist
            "dl4jtpu_health_checks_total",              # health
        ):
            assert family in text, family
        samples = 0
        for line in text.splitlines():
            if not line or line.startswith("#"):
                continue
            name, value = line.rsplit(" ", 1)
            assert METRIC_LINE.match(name), line
            float(value)                    # must parse as a number
            samples += 1
        assert samples >= 10
        # the families fed by the fit above carry real samples
        assert "dl4jtpu_health_checks_total " in text
        assert 'dl4jtpu_step_latency_seconds_bucket{le="+Inf"}' in text
