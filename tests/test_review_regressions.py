"""Regression tests for review findings on the initial core."""

import numpy as np
import pytest

from deeplearning4j_tpu.data import DataSet, NumpyDataSetIterator
from deeplearning4j_tpu.models import SequentialModel
from deeplearning4j_tpu.nn import Adam
from deeplearning4j_tpu.nn.activations import Activation
from deeplearning4j_tpu.nn.conf import (
    Dense,
    InputType,
    NeuralNetConfiguration,
    OutputLayer,
)
from deeplearning4j_tpu.nn.losses import Loss
from deeplearning4j_tpu.nn.updaters import AdamW


def test_regression_head_trains_the_served_function():
    """OutputLayer(activation=TANH, loss=MSE): training must optimize
    tanh(logits), the same function output() serves."""
    rng = np.random.default_rng(0)
    x = rng.normal(size=(256, 3)).astype(np.float32)
    y = np.tanh(x @ rng.normal(size=(3, 1)).astype(np.float32))
    conf = (
        NeuralNetConfiguration.builder()
        .seed(5)
        .updater(Adam(1e-2))
        .list()
        .layer(Dense(n_out=16, activation=Activation.TANH))
        .layer(OutputLayer(n_out=1, loss=Loss.MSE, activation=Activation.TANH))
        .set_input_type(InputType.feed_forward(3))
        .build()
    )
    m = SequentialModel(conf).init()
    m.fit(NumpyDataSetIterator(x, y, batch_size=64, seed=1), epochs=30)
    pred = np.asarray(m.output(x))
    assert np.all(np.abs(pred) <= 1.0)
    mse = float(np.mean((pred - y) ** 2))
    assert mse < 0.01, f"served function not optimized, mse={mse}"


def test_small_dataset_still_trains():
    """Dataset smaller than batch_size must not be silently skipped."""
    x = np.random.default_rng(0).normal(size=(20, 2)).astype(np.float32)
    y = np.eye(2, dtype=np.float32)[np.random.default_rng(1).integers(0, 2, 20)]
    conf = (
        NeuralNetConfiguration.builder()
        .updater(Adam(1e-2))
        .list()
        .layer(OutputLayer(n_out=2, loss=Loss.MCXENT))
        .set_input_type(InputType.feed_forward(2))
        .build()
    )
    m = SequentialModel(conf).init()
    m.fit((x, y), epochs=1)
    assert m.iteration > 0


def test_frozen_layer_immune_to_weight_decay():
    """AdamW decoupled weight decay must not shrink frozen layers."""
    x = np.random.default_rng(0).normal(size=(64, 4)).astype(np.float32)
    y = np.eye(2, dtype=np.float32)[np.random.default_rng(1).integers(0, 2, 64)]
    conf = (
        NeuralNetConfiguration.builder()
        .seed(2)
        .updater(AdamW(learning_rate=1e-2, weight_decay=0.5))
        .list()
        .layer(Dense(n_out=8, frozen=True, activation=Activation.RELU))
        .layer(OutputLayer(n_out=2, loss=Loss.MCXENT))
        .set_input_type(InputType.feed_forward(4))
        .build()
    )
    m = SequentialModel(conf).init()
    w0 = np.asarray(m.params["layer0"]["W"]).copy()
    m.fit(NumpyDataSetIterator(x, y, batch_size=32), epochs=3)
    np.testing.assert_array_equal(np.asarray(m.params["layer0"]["W"]), w0)


def test_duplicate_layer_names_rejected():
    with pytest.raises(ValueError, match="duplicate layer names"):
        (
            NeuralNetConfiguration.builder()
            .list()
            .layer(Dense(n_out=4))
            .layer(Dense(name="layer0", n_out=4))
            .layer(OutputLayer(n_out=2))
            .set_input_type(InputType.feed_forward(2))
            .build()
        )


def test_global_activation_does_not_leak_into_output_layer():
    """builder.activation(RELU) must not override the OutputLayer's
    loss-canonical softmax."""
    x = np.random.default_rng(0).normal(size=(8, 2)).astype(np.float32)
    y = np.eye(2, dtype=np.float32)[[0, 1] * 4]
    conf = (
        NeuralNetConfiguration.builder()
        .activation(Activation.RELU)
        .updater(Adam(1e-3))
        .list()
        .layer(Dense(n_out=4))
        .layer(OutputLayer(n_out=2, loss=Loss.MCXENT))
        .set_input_type(InputType.feed_forward(2))
        .build()
    )
    m = SequentialModel(conf).init()
    out = np.asarray(m.output(x))
    np.testing.assert_allclose(out.sum(axis=1), 1.0, rtol=1e-4)
    assert np.all(out > 0)


def test_async_iterator_early_exit_no_deadlock():
    from deeplearning4j_tpu.data import AsyncDataSetIterator
    import threading

    x = np.zeros((512, 4), np.float32)
    y = np.zeros((512, 2), np.float32)
    base = NumpyDataSetIterator(x, y, batch_size=16, shuffle=False)
    before = threading.active_count()
    for _ in range(5):
        it = iter(AsyncDataSetIterator(base, queue_size=1, device_put=False))
        next(it)
        it.close()  # early abandonment
    # producer threads must have exited
    import time

    deadline = time.time() + 5
    while threading.active_count() > before and time.time() < deadline:
        time.sleep(0.05)
    assert threading.active_count() <= before + 1


def test_async_iterator_full_consumption_matches_base():
    from deeplearning4j_tpu.data import AsyncDataSetIterator

    x = np.arange(64, dtype=np.float32).reshape(16, 4)
    y = np.zeros((16, 2), np.float32)
    base = NumpyDataSetIterator(x, y, batch_size=4, shuffle=False)
    got = [b.features for b in AsyncDataSetIterator(base, device_put=False)]
    want = [b.features for b in base]
    assert len(got) == len(want)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), w)


class TestConvMaxpoolBackward:
    def test_conv_maxpool_backward_finite_jitted(self):
        """Jitted grad(conv 7x7/s2 SAME -> maxpool 3x3/s2 SAME) must be
        finite.  An earlier TPU installation mis-fused this backward into
        NaN and the pool input rode an optimization barrier; on libtpu
        0.0.34 (TPU v5e, PR 21) the gradient is finite and bit-identical
        with and without it, so the barrier is gone and this stays as the
        regression."""
        import jax
        import jax.numpy as jnp
        import numpy as np

        from deeplearning4j_tpu.nn.conf.layers import (
            Conv2D, PoolingType, Subsampling,
        )
        from deeplearning4j_tpu.nn.conf.input_type import InputType

        conv = Conv2D(name="c", n_out=16, kernel=(7, 7), stride=(2, 2),
                      padding="same", has_bias=False)
        pool = Subsampling(pooling=PoolingType.MAX, kernel=(3, 3),
                           stride=(2, 2), padding="same")
        cp, _ = conv.init(jax.random.key(0), InputType.convolutional(32, 32, 3))
        x = jnp.asarray(
            np.random.default_rng(0).normal(0, 1, (4, 32, 32, 3)).astype(np.float32)
        )

        def f(cp):
            y, _ = conv.apply(cp, {}, x, training=False, rng=None)
            y, _ = pool.apply({}, {}, y, training=False, rng=None)
            return jnp.sum(y.astype(jnp.float32) ** 2)

        g = jax.jit(jax.grad(f))(cp)
        assert np.isfinite(np.asarray(g["W"], np.float32)).all()


class TestAdvisorRound3:
    """Regressions for the round-3 advisor findings (ADVICE.md r3)."""

    def test_discrete_space_lone_tuple_warns(self):
        import warnings

        from deeplearning4j_tpu.arbiter.spaces import DiscreteParameterSpace

        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            sp = DiscreteParameterSpace((0.1, 0.01))
        assert any("ONE tuple-valued candidate" in str(x.message) for x in w)
        assert sp.values == ((0.1, 0.01),)   # behavior unchanged, just loud
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            DiscreteParameterSpace((3, 3))   # kernel-size: still warns
            DiscreteParameterSpace([0.1, 0.01])  # canonical: silent
            DiscreteParameterSpace(0.1, 0.01)    # canonical: silent
        assert len(w) == 1

    def test_fit_batch_dead_donated_buffers_raise_clearly(self):
        import jax.numpy as jnp
        import pytest

        from deeplearning4j_tpu.autodiff.samediff import (
            SameDiff, TrainingConfig)
        from deeplearning4j_tpu.nn.updaters import Sgd

        sd = SameDiff()
        x = sd.placeholder("x")
        w = sd.var("w", np.ones((3,), np.float32))
        y = sd.apply("mul", x, w)
        sd.set_loss(sd.apply("sum", y))
        sd.set_training_config(TrainingConfig(updater=Sgd(0.1)))
        feed = {"x": np.ones((3,), np.float32)}
        sd.fit_batch(feed)  # compiles the step

        (key,) = [k for k in sd._compiled if k[0] == "fit"]

        def boom(*a, **k):
            # simulate a post-dispatch failure with donated buffers gone
            sd._values["w"].delete()
            raise RuntimeError("transport dropped")

        sd._compiled[key] = boom
        with pytest.raises(RuntimeError, match="no longer retryable"):
            sd.fit_batch(feed)

    def test_executor_timeout_single_deadline(self, monkeypatch):
        import time as _time

        from deeplearning4j_tpu.datavec import (
            LocalTransformExecutor, Schema, TransformProcess)

        schema = Schema.builder().add_double("v").build()
        tp = TransformProcess.builder(schema).build()
        recs = [[float(i)] for i in range(2048)]
        t0 = _time.monotonic()
        try:
            LocalTransformExecutor.execute(
                tp, recs, num_workers=4, min_records_per_worker=1,
                timeout=0.9)
        except RuntimeError as e:
            assert "timed out" in str(e) or "failed" in str(e)
            # shared deadline: must not stack per-worker timeouts to ~2x
            assert _time.monotonic() - t0 < 2.5
        # fast workers finishing under the timeout is also acceptable

    def test_remote_router_after_close(self):
        from deeplearning4j_tpu.ui.stats import RemoteStatsStorageRouter

        r = RemoteStatsStorageRouter("http://127.0.0.1:9")  # unreachable
        r.close()
        before = r.dropped
        r.put_record({"k": 1})
        assert r.dropped == before + 1    # counted, not silently queued
        r.flush()                          # must not hang after close()
        r.close()                          # idempotent
