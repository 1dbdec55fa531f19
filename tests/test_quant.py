"""ISSUE 14 — int8 post-training quantization + the fused dequant-matmul.

Covers the scheme's core (symmetric per-output-channel scales, the
QuantizedTensor pytree node), the kernel parity contract (pallas /
blocked impls vs the XLA dequantize-then-dot reference within 1e-5
rel), the evaluation-parity gates (top-1 delta <= 1% on a zoo model,
macro-F1 delta <= 0.02 on a modelimport model) and the quantized
serving ladder: verified hot-swap over mixed int8+scale trees,
``/v1/reload`` of a quantized checkpoint, rolling canary deploy with
rollback, and warm start with zero fresh XLA compiles on a second boot
(persistent compile cache).
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.models import SequentialModel
from deeplearning4j_tpu.models.computation_graph import GraphModel
from deeplearning4j_tpu.nn.conf import (
    Conv2D,
    Dense,
    Embedding,
    InputType,
    NeuralNetConfiguration,
    OutputLayer,
    Subsampling,
)
from deeplearning4j_tpu.nn.conf.graph_conf import GraphBuilder
from deeplearning4j_tpu.nn.losses import Loss
from deeplearning4j_tpu.ops.dequant_matmul import (
    dequant_matmul,
    select_impl,
)
from deeplearning4j_tpu.quant import (
    QuantizedTensor,
    dequantize_tree,
    is_quantized,
    parity_check,
    quantize,
    quantized_bytes,
)
from deeplearning4j_tpu.quant.qtensor import quantize_array
from deeplearning4j_tpu.runtime import faults

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

pytestmark = pytest.mark.quant

N_IN, N_OUT = 16, 4


def _conf(seed=7, n_in=N_IN, hidden=32, n_out=N_OUT):
    return (
        NeuralNetConfiguration.builder().seed(seed).list()
        .layer(Dense(n_out=hidden))
        .layer(OutputLayer(n_out=n_out))
        .set_input_type(InputType.feed_forward(n_in)).build()
    )


def _mlp(seed=7):
    return SequentialModel(_conf(seed)).init()


def _x(seed=0, shape=(8, N_IN)):
    return np.random.default_rng(seed).standard_normal(shape).astype(
        np.float32
    )


@pytest.fixture(autouse=True)
def _disarm_faults():
    yield
    faults.disarm()


# -- scheme core -------------------------------------------------------------


class TestQuantizeCore:
    def test_quantize_array_symmetric_per_channel(self):
        rng = np.random.default_rng(0)
        w = rng.standard_normal((64, 32)).astype(np.float32)
        w[:, 5] = 0.0                       # an all-zero channel
        qt = quantize_array(w)
        assert qt.q.dtype == jnp.int8
        assert qt.q.shape == w.shape
        assert qt.scale.shape == (32,)
        q = np.asarray(qt.q)
        scale = np.asarray(qt.scale)
        # symmetric range: -128 never used
        assert q.min() >= -127 and q.max() <= 127
        # per-channel error bound: rounding is at most half a step
        deq = np.asarray(qt.dequant())
        assert np.all(np.abs(deq - w) <= scale[None, :] * 0.5 + 1e-7)
        # the zero channel stays exactly zero (scale falls back to 1.0)
        assert np.all(deq[:, 5] == 0.0)
        assert scale[5] == 1.0

    def test_quantized_tensor_is_a_keyed_pytree(self):
        from deeplearning4j_tpu.utils.pytree import tree_flatten_with_paths

        qt = quantize_array(np.ones((4, 4), np.float32))
        tree = {"layer0": {"W": qt}}
        leaves = jax.tree.leaves(tree)
        assert sorted(str(l.dtype) for l in leaves) == ["float32", "int8"]
        paths = [p for p, _ in tree_flatten_with_paths(tree)]
        assert paths == ["layer0.W.q", "layer0.W.scale"]
        # unflatten rebuilds the node
        flat, treedef = jax.tree.flatten(tree)
        back = jax.tree.unflatten(treedef, flat)
        assert isinstance(back["layer0"]["W"], QuantizedTensor)

    def test_quantize_copy_keeps_source_f32_and_outputs_close(self):
        m = _mlp()
        x = _x()
        before = np.asarray(m.output(x))
        q = quantize(m)
        assert is_quantized(q) and not is_quantized(m)
        assert isinstance(q.params["layer0"]["W"], QuantizedTensor)
        # biases stay plain f32
        assert not isinstance(q.params["layer0"]["b"], QuantizedTensor)
        # the source still serves bit-identical f32
        np.testing.assert_array_equal(np.asarray(m.output(x)), before)
        yq = np.asarray(q.output(x))
        rel = np.abs(yq - before).max() / np.abs(before).max()
        assert rel < 0.05                   # int8 weight rounding only
        assert (yq.argmax(-1) == before.argmax(-1)).all()

    def test_quantize_in_place_drops_training_state(self):
        m = _mlp()
        m.fit_batch_ok = None               # no-op attr; model untrained
        m._step_fns[("probe",)] = object()
        q = quantize(m, copy=False)
        assert q is m
        assert m.opt_state is None
        assert m._step_fns == {}

    def test_quantize_covers_conv_and_embedding_weights(self):
        conv_conf = (
            NeuralNetConfiguration.builder().seed(3).list()
            .layer(Conv2D(n_out=8, kernel=(3, 3), padding="same"))
            .layer(Subsampling(kernel=(2, 2), stride=(2, 2)))
            .layer(Dense(n_out=16))
            .layer(OutputLayer(n_out=N_OUT))
            .set_input_type(InputType.convolutional(8, 8, 1)).build()
        )
        cm = SequentialModel(conv_conf).init()
        x = np.random.default_rng(0).standard_normal(
            (4, 8, 8, 1)
        ).astype(np.float32)
        before = np.asarray(cm.output(x))
        qc = quantize(cm)
        assert isinstance(qc.params["layer0"]["W"], QuantizedTensor)
        assert (np.asarray(qc.output(x)).argmax(-1)
                == before.argmax(-1)).all()

        emb_conf = (
            NeuralNetConfiguration.builder().seed(4).list()
            .layer(Embedding(n_in=64, n_out=8))
            .layer(OutputLayer(n_out=N_OUT))
            .set_input_type(InputType.feed_forward(1)).build()
        )
        em = SequentialModel(emb_conf).init()
        ids = np.arange(8, dtype=np.float32)[:, None]
        before = np.asarray(em.output(ids))
        qe = quantize(em)
        assert isinstance(qe.params["layer0"]["W"], QuantizedTensor)
        assert (np.asarray(qe.output(ids)).argmax(-1)
                == before.argmax(-1)).all()

    def test_graph_model_quantizes_and_serves(self):
        conf = (
            GraphBuilder().add_inputs("in")
            .add_layer("fc1", Dense(n_out=8), "in")
            .add_layer("out", OutputLayer(n_out=3, loss=Loss.MCXENT),
                       "fc1")
            .set_outputs("out")
            .set_input_types(InputType.feed_forward(5)).build()
        )
        gm = GraphModel(conf).init()
        x = _x(3, (4, 5))
        before = np.asarray(gm.output(x))
        qg = quantize(gm)
        assert isinstance(qg.params["fc1"]["W"], QuantizedTensor)
        out = np.asarray(qg.output(x))
        assert (out.argmax(-1) == before.argmax(-1)).all()

    def test_dequantize_tree_and_bytes(self):
        m = _mlp()
        q = quantize(m)
        deq = dequantize_tree(q.params)
        for lname in ("layer0", "layer1"):
            w = np.asarray(m.params[lname]["W"])
            dw = np.asarray(deq[lname]["W"])
            scale = np.asarray(q.params[lname]["W"].scale)
            assert np.all(np.abs(dw - w) <= scale[None, :] * 0.5 + 1e-7)
        b = quantized_bytes(q.params)
        # int8 values + f32 per-channel scales over f32 weights:
        # strictly between 1/4 and 1/2 for these shapes
        assert 0.25 <= b["ratio"] < 0.5
        assert b["tree_bytes"] < sum(
            int(np.prod(l.shape)) * 4
            for l in jax.tree.leaves(m.params)
        )

    def test_params_bytes_gauge_and_parity_counter(self):
        from deeplearning4j_tpu.observe.metrics import registry

        reg = registry()
        m = _mlp(seed=21)
        q = quantize(m)
        g = reg.gauge("dl4jtpu_quant_params_bytes")
        assert g.value(kind="quantized") == quantized_bytes(
            q.params
        )["quantized_bytes"]
        assert g.value(kind="f32_equiv") > g.value(kind="quantized")
        before = reg.counter(
            "dl4jtpu_quant_parity_checks_total"
        ).value(result="pass")
        res = parity_check(m, q, _x(5, (64, N_IN)))
        assert res["pass"] and res["top1_delta"] <= 0.01
        assert reg.counter(
            "dl4jtpu_quant_parity_checks_total"
        ).value(result="pass") == before + 1


# -- fused dequant-matmul kernel ---------------------------------------------


class TestDequantMatmul:
    SHAPES = ((8, 256, 128), (3, 512, 384), (1, 1024, 512))

    def _case(self, m, k, n, seed=0):
        rng = np.random.default_rng(seed)
        x = jnp.asarray(rng.standard_normal((m, k)).astype(np.float32))
        qt = quantize_array(
            rng.standard_normal((k, n)).astype(np.float32)
        )
        return x, qt

    def test_pallas_and_blocked_match_reference_1e5(self):
        for (m, k, n) in self.SHAPES:
            x, qt = self._case(m, k, n)
            ref = np.asarray(
                dequant_matmul(x, qt.q, qt.scale, impl="xla")
            )
            scale = np.abs(ref).max()
            for impl in ("pallas", "blocked"):
                out = np.asarray(
                    dequant_matmul(x, qt.q, qt.scale, impl=impl)
                )
                rel = np.abs(out - ref).max() / scale
                assert rel < 1e-5, (impl, m, k, n, rel)

    def test_reference_matches_dense_dequant_dot(self):
        x, qt = self._case(4, 256, 128)
        ref = np.asarray(x @ qt.dequant())
        out = np.asarray(dequant_matmul(x, qt.q, qt.scale, impl="xla"))
        np.testing.assert_allclose(out, ref, rtol=1e-6, atol=1e-6)

    def test_leading_batch_dims_flow_through(self):
        rng = np.random.default_rng(1)
        x = jnp.asarray(
            rng.standard_normal((2, 7, 256)).astype(np.float32)
        )
        qt = quantize_array(
            rng.standard_normal((256, 128)).astype(np.float32)
        )
        ref = np.asarray(dequant_matmul(x, qt.q, qt.scale, impl="xla"))
        for impl in ("pallas", "blocked"):
            out = np.asarray(
                dequant_matmul(x, qt.q, qt.scale, impl=impl)
            )
            rel = np.abs(out - ref).max() / np.abs(ref).max()
            assert rel < 1e-5

    def test_blocked_falls_back_on_nondividing_k(self):
        # K=100 tiles by no block candidate: blocked must degrade to
        # the xla baseline, not crash or truncate
        rng = np.random.default_rng(2)
        x = jnp.asarray(rng.standard_normal((4, 100)).astype(np.float32))
        qt = quantize_array(
            rng.standard_normal((100, 64)).astype(np.float32)
        )
        ref = np.asarray(dequant_matmul(x, qt.q, qt.scale, impl="xla"))
        out = np.asarray(
            dequant_matmul(x, qt.q, qt.scale, impl="blocked")
        )
        np.testing.assert_allclose(out, ref, rtol=1e-6, atol=1e-6)

    def test_selection_rule_and_env_override(self, monkeypatch):
        # CPU defaults: small weights -> xla; the cache-blocking
        # crossover (>= ~8 megaweights AND >= 2 activation rows) ->
        # blocked; M=1 stays on the baseline even for huge weights
        monkeypatch.delenv("DL4JTPU_QUANT_KERNEL", raising=False)
        assert select_impl(8, 32, 64) == "xla"
        assert select_impl(8, 1024, 1024) == "xla"
        assert select_impl(8, 2048, 2048) == "blocked"
        assert select_impl(1, 4096, 4096) == "xla"
        monkeypatch.setenv("DL4JTPU_QUANT_KERNEL", "pallas")
        assert select_impl(8, 32, 64) == "pallas"

    def test_selection_counter_counts_by_impl(self):
        from deeplearning4j_tpu.observe.metrics import registry

        c = registry().counter("dl4jtpu_quant_dequant_matmul_total")
        before = c.value(impl="blocked")
        x, qt = self._case(2, 256, 128)
        dequant_matmul(x, qt.q, qt.scale, impl="blocked")
        assert c.value(impl="blocked") == before + 1
        # a forced 'blocked' that cannot tile K resolves to the xla
        # fallback BEFORE counting: the impl label must name the
        # kernel that actually ran (review finding, regression)
        rng = np.random.default_rng(3)
        x100 = jnp.asarray(
            rng.standard_normal((4, 100)).astype(np.float32)
        )
        qt100 = quantize_array(
            rng.standard_normal((100, 64)).astype(np.float32)
        )
        b_before = c.value(impl="blocked")
        x_before = c.value(impl="xla")
        dequant_matmul(x100, qt100.q, qt100.scale, impl="blocked")
        assert c.value(impl="blocked") == b_before
        assert c.value(impl="xla") == x_before + 1


# -- evaluation-parity gates -------------------------------------------------


def _blob_images(n, hw, n_classes, seed=0):
    """Trivially separable images: class k has mean intensity k."""
    rng = np.random.default_rng(seed)
    y = rng.integers(0, n_classes, n)
    x = rng.normal(0.0, 0.3, (n, hw, hw, 1)).astype(np.float32)
    x += y[:, None, None, None].astype(np.float32)
    oh = np.eye(n_classes, dtype=np.float32)[y]
    return x, oh, y


class TestEvaluationParity:
    def test_zoo_model_top1_parity_gate(self):
        """Acceptance: top-1 delta <= 1% on a zoo model (LeNet, trained
        on a separable synthetic task so logits carry real margins)."""
        from deeplearning4j_tpu.data.dataset import DataSet
        from deeplearning4j_tpu.zoo.lenet import LeNet

        model = LeNet(num_classes=3, height=14, width=14,
                      learning_rate=5e-3).init_model()
        x, oh, _ = _blob_images(192, 14, 3, seed=1)
        for _ in range(8):
            for i in range(0, len(x), 64):
                model.fit_batch(DataSet(x[i:i + 64], oh[i:i + 64]))
        xe, _, ye = _blob_images(384, 14, 3, seed=2)
        q = quantize(model)
        res = parity_check(model, q, xe, labels=ye,
                           top1_tol=0.01, f1_tol=0.02)
        assert res["pass"], res
        assert res["top1_ref"] > 0.9        # the task WAS learned
        assert res["top1_delta"] <= 0.01
        assert res["f1_delta"] <= 0.02

    def test_modelimport_f1_parity_gate(self, tmp_path):
        """Acceptance: macro-F1 delta <= 0.02 on a modelimport (Keras)
        model, quantized vs f32."""
        tf = pytest.importorskip("tensorflow")
        from deeplearning4j_tpu.data.dataset import DataSet
        from deeplearning4j_tpu.modelimport.keras import (
            import_keras_model,
        )

        keras = tf.keras
        # seeded initializers: the imported weights (and therefore how
        # fast the brief fit converges) must not depend on whatever
        # keras global-RNG state earlier tests left behind
        km = keras.Sequential([
            keras.layers.Input((12,)),
            keras.layers.Dense(
                32, activation="relu",
                kernel_initializer=keras.initializers.GlorotUniform(
                    seed=7
                ),
            ),
            keras.layers.Dense(
                3, activation="softmax",
                kernel_initializer=keras.initializers.GlorotUniform(
                    seed=8
                ),
            ),
        ])
        path = str(tmp_path / "m.h5")
        km.save(path)
        ours = import_keras_model(path)
        # separable 3-class blobs in feature space; fit (early-stopped
        # on train accuracy) gives the imported model real margins
        rng = np.random.default_rng(5)
        y = rng.integers(0, 3, 512)
        x = rng.normal(0, 0.4, (512, 12)).astype(np.float32)
        x[:, :3] += np.eye(3, dtype=np.float32)[y] * 2.0
        oh = np.eye(3, dtype=np.float32)[y]
        for _ in range(12):
            for i in range(0, 512, 64):
                ours.fit_batch(DataSet(x[i:i + 64], oh[i:i + 64]))
            if (ours.predict(x) == y).mean() > 0.95:
                break
        q = quantize(ours)
        res = parity_check(ours, q, x, labels=y,
                           top1_tol=0.01, f1_tol=0.02)
        assert res["pass"], res
        assert res["f1_ref"] > 0.8
        assert res["f1_delta"] <= 0.02


# -- cost registry / program identity ---------------------------------------


class TestCostRegistry:
    def test_quantized_programs_register_distinct_int8_keys(self):
        from deeplearning4j_tpu.observe import cost

        m = _mlp(seed=31)
        q = quantize(m)
        x = _x(0, (2, N_IN))
        m.output(x)
        q.output(x)
        keys = {
            r.key: r for r in cost.registry().programs()
            if r.owner_ref() in (m, q)
        }
        assert "('infer', False)" in keys
        assert "('infer', False, 'int8')" in keys
        rec = keys["('infer', False, 'int8')"]
        assert rec.quantized
        # int8-adjusted params bytes: as-stored < f32 equivalent
        assert rec.params_bytes < rec.params_bytes_f32_equiv
        f32_rec = keys["('infer', False)"]
        assert not f32_rec.quantized
        assert rec.params_bytes < f32_rec.params_bytes


# -- the quantized serving ladder --------------------------------------------


class TestQuantizedServing:
    def _server(self, model, **kw):
        from deeplearning4j_tpu.serving import (
            InferenceServer, ServingConfig,
        )

        kw.setdefault("max_batch", 4)
        kw.setdefault("linger_s", 0.001)
        return InferenceServer(model, ServingConfig(**kw))

    def test_quantized_server_serves_and_advertises(self):
        m = _mlp(seed=41)
        q = quantize(m)
        srv = self._server(q).start()
        try:
            x = _x(1, (N_IN,))
            out = srv.infer(x, deadline_s=60.0)
            np.testing.assert_allclose(
                np.asarray(out), np.asarray(q.output(x[None]))[0],
                rtol=1e-5, atol=1e-6,
            )
            assert srv.health()["quantized"] is True
            assert srv.stats()["quantized"] is True
        finally:
            srv.stop()

    def test_hotswap_verifies_mixed_int8_scale_trees(self):
        from deeplearning4j_tpu.serving import weights_checksum
        from deeplearning4j_tpu.serving.hotswap import (
            SwapVerifyError, verify_weights,
        )

        m = _mlp(seed=42)
        q = quantize(m)
        twin = quantize(SequentialModel(_conf(seed=43)).init())
        # quantized -> quantized with checksum: verifies clean
        verify_weights(twin.params, q.params,
                       checksum=weights_checksum(twin.params))
        # extreme int8 values must NOT trip the finiteness check
        extreme = jax.tree.unflatten(
            jax.tree.structure(q.params),
            [
                jnp.full_like(l, 127) if l.dtype == jnp.int8 else l
                for l in jax.tree.leaves(q.params)
            ],
        )
        verify_weights(extreme, q.params)
        # a NaN SCALE is exactly what finiteness exists to catch
        pw = twin.params["layer0"]["W"]
        poisoned = {
            **twin.params,
            "layer0": {
                **twin.params["layer0"],
                "W": QuantizedTensor(pw.q, pw.scale.at[0].set(jnp.nan)),
            },
        }
        with pytest.raises(SwapVerifyError) as e:
            verify_weights(poisoned, q.params)
        assert e.value.reason == "nonfinite"
        # f32 tree vs quantized live: structure rejection, both ways
        with pytest.raises(SwapVerifyError) as e:
            verify_weights(m.params, q.params)
        assert e.value.reason == "structure"
        with pytest.raises(SwapVerifyError) as e:
            verify_weights(q.params, m.params)
        assert e.value.reason == "structure"

    def test_reload_of_quantized_checkpoint(self, tmp_path):
        """Satellite: /v1/reload of a quantized checkpoint — the
        push_checkpoint path restores the (int8, scale) structure from
        meta and installs through full verification."""
        m = _mlp(seed=44)
        q = quantize(m)
        srv = self._server(q).start()
        try:
            trainer = quantize(SequentialModel(_conf(seed=45)).init())
            path = str(tmp_path / "q.zip")
            trainer.save(path)
            assert srv.push_checkpoint(path)
            assert srv.generation == 1
            x = _x(2, (N_IN,))
            np.testing.assert_allclose(
                np.asarray(srv.infer(x, deadline_s=60.0)),
                np.asarray(trainer.output(x[None]))[0],
                rtol=1e-5, atol=1e-6,
            )
            # HTTP /v1/reload speaks the same path
            from deeplearning4j_tpu.serving.http import ServingHTTPServer

            fe = ServingHTTPServer(srv, port=0).start()
            try:
                import http.client

                conn = http.client.HTTPConnection(
                    "127.0.0.1", fe.port, timeout=30
                )
                conn.request(
                    "POST", "/v1/reload",
                    json.dumps({"path": path}),
                    {"Content-Type": "application/json"},
                )
                resp = conn.getresponse()
                assert resp.status == 200, resp.read()
                assert srv.generation == 2
            finally:
                fe.stop()
        finally:
            srv.stop()

    def test_quantized_checkpoint_restore_is_bit_exact(self, tmp_path):
        from deeplearning4j_tpu.train.checkpoint import ModelSerializer

        q = quantize(_mlp(seed=46))
        path = str(tmp_path / "q.zip")
        q.save(path)
        r = ModelSerializer.restore(path)
        assert is_quantized(r)
        assert isinstance(r.params["layer0"]["W"], QuantizedTensor)
        x = _x(3)
        np.testing.assert_array_equal(
            np.asarray(r.output(x)), np.asarray(q.output(x))
        )

    def test_restore_honors_recorded_min_elements(self, tmp_path):
        """Review finding, regression: a model quantized with
        min_elements>0 leaves small weights f32; restore must re-run
        the structure walk with the RECORDED knob, or the positional
        leaf load mis-counts."""
        from deeplearning4j_tpu.train.checkpoint import ModelSerializer

        m = _mlp(seed=51)
        # layer1 W is 32x4=128 elements: below the floor, stays f32
        q = quantize(m, min_elements=200)
        assert isinstance(q.params["layer0"]["W"], QuantizedTensor)
        assert not isinstance(q.params["layer1"]["W"], QuantizedTensor)
        path = str(tmp_path / "qmin.zip")
        q.save(path)
        r = ModelSerializer.restore(path)
        assert not isinstance(r.params["layer1"]["W"], QuantizedTensor)
        x = _x(4)
        np.testing.assert_array_equal(
            np.asarray(r.output(x)), np.asarray(q.output(x))
        )

    @pytest.mark.faults
    def test_quantized_fleet_canary_deploy_and_rollback(self):
        """Acceptance ladder: a quantized fleet takes a rolling canary
        deploy of a quantized tree; a corrupted canary rolls the whole
        deploy back with at most one replica ever touched."""
        from deeplearning4j_tpu.serving import (
            ServingConfig, ServingFleet,
        )

        conf = _conf(seed=47)
        ex = np.zeros((N_IN,), np.float32)
        fleet = ServingFleet(
            lambda: quantize(SequentialModel(conf).init()),
            n_replicas=2,
            config=ServingConfig(max_batch=4, linger_s=0.001),
            golden_inputs=[ex],
        )
        fleet.warm_start(ex)
        fleet.start()
        try:
            assert all(srv.quantized for srv in fleet.replicas)
            x = _x(4, (N_IN,))
            before = np.asarray(fleet.infer(x, deadline_s=60.0))
            new = quantize(SequentialModel(_conf(seed=48)).init()).params
            res = fleet.deployer.deploy(new, source="quant-test")
            assert res["installed"]
            assert res["replicas_updated"] == 2
            after = np.asarray(fleet.infer(x, deadline_s=60.0))
            assert not np.allclose(after, before)
            # torn canary: observed outputs corrupted -> rollback
            faults.arm("serving.canary:corrupt:nth=1")
            res = fleet.deployer.deploy(
                quantize(SequentialModel(_conf(seed=49)).init()).params,
            )
            faults.disarm()
            assert not res["installed"]
            assert res["rolled_back"] >= 1
            np.testing.assert_allclose(
                np.asarray(fleet.infer(x, deadline_s=60.0)), after,
                rtol=1e-6, atol=1e-7,
            )
        finally:
            fleet.stop()

    def test_warm_start_covers_buckets_with_zero_followup_jits(self):
        from deeplearning4j_tpu.runtime import compile_stats

        q = quantize(_mlp(seed=50))
        srv = self._server(q, max_batch=4).start()
        try:
            warmed = srv.warm_start(np.zeros((N_IN,), np.float32))
            assert len(warmed) == 3           # buckets 1, 2, 4
            snap = compile_stats.snapshot()
            for i in range(4):
                srv.infer(_x(i, (N_IN,)), deadline_s=60.0)
            delta = compile_stats.snapshot() - snap
            assert delta.jit_cache_misses == 0
        finally:
            srv.stop()


# -- second-boot warm start (persistent compile cache) -----------------------

_SECOND_BOOT_SCRIPT = r"""
import json, os, sys
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
from deeplearning4j_tpu.models import SequentialModel
from deeplearning4j_tpu.nn.conf import (
    Dense, InputType, NeuralNetConfiguration, OutputLayer,
)
from deeplearning4j_tpu.quant import quantize
from deeplearning4j_tpu.runtime import compile_stats, init_compile_cache
from deeplearning4j_tpu.serving import InferenceServer, ServingConfig
from deeplearning4j_tpu.train.checkpoint import ModelSerializer

assert init_compile_cache() == os.environ["JAX_COMPILATION_CACHE_DIR"]
ckpt = os.environ["QUANT_CKPT"]
if not os.path.exists(ckpt):
    conf = (NeuralNetConfiguration.builder().seed(0).list()
            .layer(Dense(n_out=16)).layer(OutputLayer(n_out=4))
            .set_input_type(InputType.feed_forward(12)).build())
    quantize(SequentialModel(conf).init()).save(ckpt)
model = ModelSerializer.restore(ckpt)
srv = InferenceServer(model, ServingConfig(max_batch=4)).start()
srv.warm_start(np.zeros((12,), np.float32))
out = srv.infer(np.ones((12,), np.float32), deadline_s=60.0)
assert np.isfinite(np.asarray(out)).all()
srv.stop()
print(json.dumps(compile_stats.snapshot().as_dict()))
"""


def test_quantized_second_boot_warm_starts_with_zero_fresh_compiles(
    tmp_path,
):
    """Acceptance: the same quantized checkpoint warm-started in a
    SECOND process compiles nothing fresh — every XLA compile request
    for the bucket set is served from the persistent cache."""
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "xla_cache"),
        "DL4J_TPU_CACHE_MIN_COMPILE_SECS": "0",
        "QUANT_CKPT": str(tmp_path / "quant.zip"),
    })
    env.pop("XLA_FLAGS", None)

    def run():
        proc = subprocess.run(
            [sys.executable, "-c", _SECOND_BOOT_SCRIPT],
            capture_output=True, text=True, timeout=600, env=env,
            cwd=REPO,
        )
        assert proc.returncode == 0, proc.stderr[-2000:]
        return json.loads(proc.stdout.strip().splitlines()[-1])

    cold = run()
    assert cold["fresh_backend_compiles"] > 0
    assert cold["persistent_cache_puts"] > 0
    warm = run()
    assert warm["backend_compiles"] > 0
    assert warm["fresh_backend_compiles"] == 0
    assert warm["persistent_cache_hits"] == warm["backend_compiles"]
