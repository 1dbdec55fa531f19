"""ISSUE 17 — generation-plane observability.

One admitted stream = one causally-linked span chain across the
disaggregated replicas (admit -> prefill -> kv_handoff -> decode steps
-> finish/abort), visible on the merged cluster timeline; the chain
must be COMPLETE on every abort path too (watchdog abort, KV-pool 429,
client disconnect mid-ndjson).  Plus the always-on per-stream latency
attribution surfaces, the throughput-style SLO wiring, and the serving
flight recorder — including the dump fired by an SLO alert's rising
edge, whose records must account for every admitted stream."""

import http.client
import json
import threading
import time
import urllib.request
from collections import Counter

import numpy as np
import pytest

from deeplearning4j_tpu.observe import chain_is_causal, tracer
from deeplearning4j_tpu.observe.metrics import MetricsRegistry
from deeplearning4j_tpu.observe.slo import BurnWindow, SLOEngine, SLObjective
from deeplearning4j_tpu.runtime import faults
from deeplearning4j_tpu.serving import ServingRejected
from deeplearning4j_tpu.serving.generation import (
    GEN_BREAKDOWN_SEGMENTS,
    GenerationConfig,
    GenerationEngine,
)
from deeplearning4j_tpu.serving.server import InferenceServer
from deeplearning4j_tpu.zoo.transformer import TransformerEncoder

pytestmark = pytest.mark.generation

VOCAB = 31

CFG = dict(slots=4, page_size=8, num_pages=64, max_pages_per_seq=4,
           max_queue=16, default_max_new=8)

#: the span names every completed routed stream's chain must carry
CHAIN_SPANS = {"generation.stream", "generation.admit",
               "generation.prefill", "generation.kv_handoff",
               "generation.decode_step"}


@pytest.fixture(autouse=True)
def _disarm_faults():
    yield
    faults.disarm()


@pytest.fixture(scope="module")
def model():
    return TransformerEncoder(
        vocab_size=VOCAB, d_model=16, n_heads=2, n_layers=2,
        causal=True, seed=5,
    ).init_model()


@pytest.fixture()
def rec():
    r = tracer()
    r.enable()
    r.clear()
    yield r
    r.disable()
    r.clear()


def _engine(model, **over):
    return GenerationEngine(
        model=model, config=GenerationConfig(**{**CFG, **over}))


def _prompt(n, seed=0):
    return np.random.default_rng(seed).integers(
        0, VOCAB, n).astype(np.int32)


def _chains(r):
    """{trace_id: chain} for every causal trace in the ring."""
    return {tid: r.trace_chain(tid) for tid in r.trace_ids()}


def _settle(r, timeout=4.0):
    deadline = time.time() + timeout
    prev = -1
    while time.time() < deadline:
        cur = r.appended_total()
        if cur == prev:
            return
        prev = cur
        time.sleep(0.05)


def _stream_chain(chains, outcome=None):
    """The chains whose root ``generation.stream`` span carries the
    given outcome (all stream chains when outcome is None)."""
    out = []
    for c in chains.values():
        roots = [s for s in c if s["name"] == "generation.stream"]
        if not roots:
            continue
        if outcome is None or roots[0]["args"].get("outcome") == outcome:
            out.append(c)
    return out


def _fleet(model):
    from deeplearning4j_tpu.serving.fleet import ServingFleet

    return ServingFleet(
        lambda: model, n_replicas=2, roles=["prefill", "decode"],
        generation_config=GenerationConfig(**CFG),
    ).start()


# -- one routed stream = one cross-replica chain -----------------------------


class TestCrossReplicaChains:
    def test_routed_stream_is_one_causal_chain(self, model, rec):
        fleet = _fleet(model)
        try:
            fleet.generate(_prompt(5, seed=1), 6, timeout=120.0)
        finally:
            fleet.stop()
        _settle(rec)
        chains = _stream_chain(_chains(rec), outcome="ok")
        assert len(chains) == 1
        chain = chains[0]
        assert chain_is_causal(chain)
        names = Counter(s["name"] for s in chain)
        assert CHAIN_SPANS <= set(names)
        # BOTH router picks joined the stream's chain, naming the
        # replica each phase landed on — the cross-replica causality
        picks = [s for s in chain if s["name"] == "router.pick"]
        assert {p["args"]["role"] for p in picks} == {"prefill",
                                                     "decode"}
        assert all(p["args"]["replica"] for p in picks)
        # the prefill ran detached (on the prefill replica), the
        # handoff span accounts the page write on the decode replica
        pre = [s for s in chain if s["name"] == "generation.prefill"]
        assert pre[0]["args"].get("detached") is True
        steps = [s for s in chain
                 if s["name"] == "generation.decode_step"]
        assert steps and all("batch_tokens" in s["args"] for s in steps)

    def test_chain_lands_on_the_cluster_timeline(self, model, rec):
        from deeplearning4j_tpu.observe.fleet import (
            FleetAggregator, FleetReporter,
        )

        fleet = _fleet(model)
        try:
            fleet.generate(_prompt(4, seed=2), 5, timeout=120.0)
        finally:
            fleet.stop()
        _settle(rec)
        sent = []

        class FakeClient:
            def push_metrics(self, payload):
                sent.append(payload)

        assert FleetReporter(FakeClient(), rank=0, every_s=0.0).push()
        agg = FleetAggregator()
        agg.ingest("w0", sent[-1])
        merged = agg.to_cluster_trace()
        names = {e["name"] for e in merged["traceEvents"]
                 if e.get("ph") == "X"}
        assert CHAIN_SPANS | {"router.pick"} <= names


# -- abort paths still close the chain ---------------------------------------


@pytest.mark.faults
class TestAbortPathChains:
    def test_watchdog_abort_closes_chain_and_dumps(self, model, rec):
        eng = _engine(model).start()
        try:
            faults.arm("serving.decode:delay:every=1,secs=0.25")
            req = eng.submit(_prompt(4, seed=3), 8)
            deadline = time.time() + 10.0
            while time.time() < deadline:
                if eng.stats()["active_streams"] >= 1:
                    break
                time.sleep(0.02)
            eng._on_wedged({"stage": "abort", "iteration": 0})
            faults.disarm()
            with pytest.raises(Exception):
                req.result(30.0)
        finally:
            faults.disarm()
            eng.stop()
        _settle(rec)
        wedged = _stream_chain(_chains(rec), outcome="wedged")
        assert len(wedged) == 1
        assert chain_is_causal(wedged[0])
        assert {"generation.admit", "generation.stream"} <= {
            s["name"] for s in wedged[0]}
        # the abort snapshotted the flight ring with the stream's fate
        assert eng.flight.dumps_written >= 1
        with open(eng.flight.dump_paths[-1]) as f:
            doc = json.load(f)
        assert doc["schema"] == "dl4jtpu-flight-record/1"
        assert doc["trigger"] == "watchdog_abort"
        assert any(r["outcome"] == "wedged" for r in doc["records"])

    def test_kv_exhausted_streams_close_chains_and_spike_dumps(
            self, model, rec):
        eng = _engine(model, num_pages=3).start()
        try:
            for i in range(3):
                with pytest.raises(ServingRejected) as ei:
                    eng.generate(_prompt(17, seed=10 + i), 4,
                                 timeout=30.0)
                assert ei.value.reason == "kv_exhausted"
        finally:
            eng.stop()
        _settle(rec)
        rejected = _stream_chain(_chains(rec), outcome="kv_exhausted")
        assert len(rejected) == 3
        assert all(chain_is_causal(c) for c in rejected)
        # three 429s inside the spike window -> one spike-triggered dump
        assert eng.flight.dumps_written >= 1
        with open(eng.flight.dump_paths[-1]) as f:
            doc = json.load(f)
        assert doc["trigger"] == "kv_exhausted_spike"
        assert doc["context"]["rejects_in_window"] >= 3
        assert eng.stats()["streams"]["outcomes"]["kv_exhausted"] == 3

    def test_client_disconnect_mid_ndjson_closes_chain(self, model,
                                                       rec):
        from deeplearning4j_tpu.serving.http import ServingHTTPServer

        srv = InferenceServer(model)
        eng = GenerationEngine(server=srv,
                               config=GenerationConfig(**CFG)).start()
        http_srv = ServingHTTPServer(srv).start()
        try:
            import socket
            import struct

            faults.arm("serving.decode:delay:every=1,secs=0.15")
            host, port = http_srv.url[7:].rstrip("/").split(":")
            body = json.dumps(
                {"prompt": _prompt(4, seed=20).tolist(),
                 "max_new_tokens": 16, "stream": True}).encode()
            sock = socket.create_connection((host, int(port)),
                                            timeout=30)
            sock.sendall(
                (f"POST /v1/generate HTTP/1.1\r\nHost: {host}\r\n"
                 "Content-Type: application/json\r\n"
                 f"Content-Length: {len(body)}\r\n\r\n").encode()
                + body)
            data = b""
            while b"\r\n\r\n" not in data:   # status line + headers
                data += sock.recv(1024)
            assert b"200" in data.split(b"\r\n", 1)[0]
            while b"token" not in data:      # first ndjson chunk
                data += sock.recv(1024)
            # hang up mid-stream with an RST (SO_LINGER 0), so the
            # server's next ndjson write fails instead of landing in
            # the dead socket's kernel buffer
            sock.setsockopt(socket.SOL_SOCKET, socket.SO_LINGER,
                            struct.pack("ii", 1, 0))
            sock.close()
            deadline = time.time() + 20.0
            while time.time() < deadline:
                outs = eng.stats()["streams"]["outcomes"]
                if outs.get("cancelled"):
                    break
                time.sleep(0.05)
            faults.disarm()
            assert eng.stats()["streams"]["outcomes"].get(
                "cancelled") == 1
        finally:
            faults.disarm()
            http_srv.stop()
            eng.stop()
            srv.stop()
        _settle(rec)
        gone = _stream_chain(_chains(rec), outcome="cancelled")
        assert len(gone) == 1
        assert chain_is_causal(gone[0])
        assert {"generation.admit", "generation.stream"} <= {
            s["name"] for s in gone[0]}


# -- SLO alert rising edge -> flight dump ------------------------------------


class TestSLOAlertFlightDump:
    def test_alert_dump_accounts_every_admitted_stream(self, model):
        eng = _engine(model).start()
        try:
            for i in range(4):
                eng.generate(_prompt(4, seed=30 + i), 4, timeout=120.0)
            settled = eng.stats()["streams"]["settled"]
            assert settled == 4
            # an SLO engine over an isolated registry: drive its one
            # objective into a full-burn rising edge — the module-level
            # listener ring must fan the alert out to the engine's
            # recorder even though the SLO engine knows nothing of it
            reg = MetricsRegistry()
            fam = reg.counter("t_requests_total")
            clock_t = [0.0]
            slo_eng = SLOEngine(
                [SLObjective.availability("avail", target=0.99,
                                          family="t_requests_total")],
                windows=(BurnWindow(10.0, 10.0),),
                clock=lambda: clock_t[0], registry=reg,
            )
            slo_eng.sample()
            fam.inc(10, outcome="error")
            clock_t[0] = 5.0
            assert slo_eng.sample()["avail"]["alert"]
            assert eng.flight.dumps_written >= 1
            with open(eng.flight.dump_paths[-1]) as f:
                doc = json.load(f)
            assert doc["trigger"] == "slo_alert"
            assert doc["context"]["objective"] == "avail"
            # every admitted stream is accounted in the dump
            assert len(doc["records"]) == settled
            assert all(r["outcome"] == "ok" for r in doc["records"])
            assert doc["engine"]["stats"]["streams"]["settled"] \
                == settled
        finally:
            eng.stop()

    def test_detach_on_stop_unhooks_the_listener(self, model):
        from deeplearning4j_tpu.observe import slo as slo_mod

        eng = _engine(model).start()
        listener = eng.flight._slo_listener
        assert listener in slo_mod._ALERT_LISTENERS
        eng.stop()
        assert listener not in slo_mod._ALERT_LISTENERS


# -- latency attribution surfaces --------------------------------------------


class TestLatencySurfaces:
    def test_breakdown_slow_ring_and_stats(self, model, rec):
        eng = _engine(model).start()
        try:
            for i in range(3):
                eng.generate(_prompt(4, seed=40 + i), 5, timeout=120.0)
            st = eng.stats()
        finally:
            eng.stop()
        bd = st["latency_breakdown"]
        assert set(GEN_BREAKDOWN_SEGMENTS) == set(bd)
        fractions = [v["fraction"] for v in bd.values()
                     if v["fraction"] is not None]
        assert fractions
        assert abs(sum(fractions) - 1.0) < 0.01
        assert st["streams"]["outcomes"]["ok"] == 3
        assert st["flight"]["records"] == 3
        slow = eng.slow_streams()
        assert 0 < len(slow) <= 16
        lats = [e["latency_s"] for e in slow]
        assert lats == sorted(lats, reverse=True)
        top = slow[0]
        assert top["kind"] == "generate"
        assert set(GEN_BREAKDOWN_SEGMENTS) <= set(top["breakdown_s"])
        assert top["ttft_s"] is not None
        assert "spans" in top and top["spans"]

    def test_serving_copy_count_is_exported_and_flat_while_decoding(
            self, model, rec):
        """`dl4jtpu_serving_params_casts_total`: one copy of the tree at
        `start()`, none per decode step; the pull collector bridges the
        engine's own count, `engine.stats()` reads the same."""
        from deeplearning4j_tpu.observe.metrics import registry

        fam = "dl4jtpu_serving_params_casts_total"
        reg = registry()
        assert f"# TYPE {fam} counter" in reg.to_prometheus_text()
        reg.collect()
        before = reg.counter(fam).value()
        eng = _engine(model)
        assert eng.stats()["serving_params_casts"] == 0
        eng.start()
        try:
            assert eng.stats()["serving_params_casts"] == 1
            reg.collect()
            assert reg.counter(fam).value() == before + 1
            steps = eng.stats()["decode_steps"]
            for i in range(2):
                eng.generate(_prompt(4, seed=60 + i), 6, timeout=120.0)
            assert eng.stats()["decode_steps"] >= steps + 10
            assert eng.stats()["serving_params_casts"] == 1
        finally:
            eng.stop()
        reg.collect()
        assert reg.counter(fam).value() == before + 1
        assert f"{fam} " in reg.to_prometheus_text()

    def test_status_healthz_and_ui_surfaces(self, model, rec):
        import gc

        from deeplearning4j_tpu.serving.http import ServingHTTPServer
        from deeplearning4j_tpu.ui.server import UIServer

        gc.collect()     # drop earlier tests' dead servers (WeakSet)
        srv = InferenceServer(model)
        eng = GenerationEngine(server=srv,
                               config=GenerationConfig(**CFG)).start()
        http_srv = ServingHTTPServer(srv).start()
        ui = UIServer(port=0)
        try:
            mine = set()
            for i in range(2):
                req = eng.submit(_prompt(4, seed=50 + i), 4)
                req.result(120.0)
                mine.add(req.rid)
            with urllib.request.urlopen(
                    http_srv.url + "v1/status") as r:
                status = json.loads(r.read())
            gen = status["generation"]
            assert gen["streams"]["outcomes"]["ok"] == 2
            assert set(GEN_BREAKDOWN_SEGMENTS) == set(
                gen["latency_breakdown"])
            assert gen["flight"]["records"] == 2
            # the health payload (and thus the fleet push) carries the
            # compact generation block
            health = srv.health()
            assert health["generation"]["stream_outcomes"]["ok"] == 2
            assert "kv_occupancy" in health["generation"]
            # the generation-plane exemplar endpoint: it merges every
            # live engine of the process, so read this engine's streams
            # (another test's engine in the same worker may hold slower
            # ones, untraced)
            with urllib.request.urlopen(
                    ui.url + "api/generation/slow?limit=1000") as r:
                rows = json.loads(r.read())
            assert rows and all(r["kind"] == "generate" for r in rows)
            ours = [r for r in rows if r["rid"] in mine]
            assert len(ours) == 2
            assert all("spans" in r for r in ours)
            # ... and the merged serving view tags both planes
            with urllib.request.urlopen(
                    ui.url + "api/serving/slow?limit=20") as r:
                merged = json.loads(r.read())
            kinds = {r["kind"] for r in merged}
            assert "generate" in kinds
        finally:
            ui.stop()
            http_srv.stop()
            eng.stop()
            srv.stop()

    def test_fleet_generation_view(self, model):
        from deeplearning4j_tpu.observe.fleet import FleetAggregator

        agg = FleetAggregator()
        agg.ingest("w0", {
            "rank": 0,
            "serving": {"servers": [{
                "status": "serving",
                "generation": {"active_streams": 1,
                               "tokens_per_s": 42.0},
            }], "routers": []},
        })
        agg.ingest("w1", {"rank": 1, "serving": {
            "servers": [{"status": "serving"}], "routers": []}})
        view = agg.generation_view()
        assert list(view) == ["w0"]
        assert view["w0"][0]["tokens_per_s"] == 42.0
