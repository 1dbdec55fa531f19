"""Pipeline parallelism integrated into fit() — GPipe over the pipe axis.

The VERDICT-critical property: `distribute(model, ParallelConfig(pipe=k))`
actually pipelines a DSL-built model's repeated-block segment, and training
matches the single-device run (same compiled math, different schedule).
"""

import dataclasses

import jax
import numpy as np
import pytest

from deeplearning4j_tpu.data.dataset import DataSet
from deeplearning4j_tpu.parallel import ParallelConfig, distribute
from deeplearning4j_tpu.zoo.transformer import TransformerEncoder

VOCAB, D, HEADS, LAYERS = 16, 16, 2, 4
BATCH, SEQ = 8, 8

# the pipeline body is manual over "pipe" only; a 2-wide data axis stays
# GSPMD-auto around it (partial-auto shard_map)
DATA = 2


def pipe_devices():
    """The device subset a (data=DATA, pipe=4) mesh needs."""
    return jax.devices()[: DATA * 4]


def make_model():
    return TransformerEncoder(
        vocab_size=VOCAB, d_model=D, n_heads=HEADS, n_layers=LAYERS,
        causal=True, seq_parallel="none", seed=11, learning_rate=1e-2,
    ).init_model()


def batches(n):
    rng = np.random.default_rng(3)
    out = []
    for _ in range(n):
        ids = rng.integers(0, VOCAB, (BATCH, SEQ))
        y = np.eye(VOCAB, dtype=np.float32)[np.roll(ids, -1, axis=1)]
        out.append(DataSet(ids.astype(np.float32), y))
    return out


def params_close(a, b, rtol=2e-4, atol=2e-5):
    import jax

    fa, fb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(fa) == len(fb)
    for x, y in zip(fa, fb):
        np.testing.assert_allclose(
            np.asarray(x), np.asarray(y), rtol=rtol, atol=atol
        )


class TestPipelineTraining:
    def test_pipe4_training_matches_single_device(self):
        data = batches(5)

        ref = make_model()
        for b in data:
            ref.fit_batch(b)

        piped = make_model()
        distribute(piped, ParallelConfig(data=DATA, pipe=4, microbatches=4),
                   devices=pipe_devices())
        assert piped._pipeline_plan.k == 4
        assert len(piped._pipeline_plan.block_names) == LAYERS
        for b in data:
            piped.fit_batch(b)

        assert np.isfinite(piped.score_value)
        params_close(ref.params, piped.params)
        # scores agree too
        assert abs(ref.score_value - piped.score_value) < 1e-3

    def test_pipe2_multiblock_stages(self):
        """4 blocks over 2 stages = 2 blocks per stage (the lax.scan-within-
        stage path)."""
        piped = make_model()
        distribute(piped, ParallelConfig(data=DATA, pipe=2),
                   devices=jax.devices()[: DATA * 2])
        first = None
        for b in batches(6):
            piped.fit_batch(b)
            first = first if first is not None else piped.score_value
        assert piped.score_value < first         # actually learns

    def test_1f1b_training_matches_single_device(self):
        """ParallelConfig(schedule='1f1b') routes fit() onto the
        interleaved-backward pipeline step; training must match the
        single-device run like GPipe does."""
        data = batches(5)

        ref = make_model()
        for b in data:
            ref.fit_batch(b)

        piped = make_model()
        distribute(
            piped,
            ParallelConfig(data=DATA, pipe=4, microbatches=4,
                           schedule="1f1b"),
            devices=pipe_devices(),
        )
        assert piped._pipeline_schedule == "1f1b"
        for b in data:
            piped.fit_batch(b)

        # the 1F1B step must have ACTUALLY run (guard against a silent
        # fallback to GPipe making this parity vacuous)
        assert ("train_1f1b",) in piped._step_fns
        assert np.isfinite(piped.score_value)
        params_close(ref.params, piped.params)
        assert abs(ref.score_value - piped.score_value) < 1e-3

    def test_1f1b_matches_gpipe(self):
        """Same data, same seeds: the two schedules are the same math."""
        data = batches(4)
        gp, ob = make_model(), make_model()
        distribute(gp, ParallelConfig(data=DATA, pipe=4, microbatches=4),
                   devices=pipe_devices())
        distribute(
            ob, ParallelConfig(data=DATA, pipe=4, microbatches=4,
                               schedule="1f1b"),
            devices=pipe_devices(),
        )
        for b in data:
            gp.fit_batch(b)
            ob.fit_batch(b)
        assert ("train_1f1b",) in ob._step_fns
        assert ("train_1f1b",) not in gp._step_fns
        params_close(gp.params, ob.params)

    def test_unknown_schedule_raises(self):
        m = make_model()
        with pytest.raises(ValueError, match="schedule"):
            distribute(m, ParallelConfig(pipe=4, schedule="interleaved"))

    def test_inference_matches_after_pipelined_training(self):
        data = batches(3)
        piped = make_model()
        distribute(piped, ParallelConfig(data=DATA, pipe=4, microbatches=4),
                   devices=pipe_devices())
        for b in data:
            piped.fit_batch(b)
        out = piped.output(data[0].features)
        assert np.all(np.isfinite(np.asarray(out)))

    def test_no_pipelineable_segment_raises(self):
        from deeplearning4j_tpu.zoo.lenet import LeNet

        model = LeNet().init_model()
        with pytest.raises(ValueError, match="identical shape-preserving"):
            distribute(model, ParallelConfig(data=2, pipe=4))

    def test_indivisible_stages_raise(self):
        model = TransformerEncoder(
            vocab_size=VOCAB, d_model=D, n_heads=HEADS, n_layers=6,
            causal=True, seed=11,
        ).init_model()                            # 6 blocks over 4 stages
        with pytest.raises(ValueError, match="not divisible"):
            distribute(model, ParallelConfig(data=2, pipe=4))

    def test_graph_model_pipe_raises(self):
        from deeplearning4j_tpu.models.computation_graph import GraphModel
        from deeplearning4j_tpu.nn.conf import Dense, InputType, OutputLayer
        from deeplearning4j_tpu.nn.conf.graph_conf import GraphBuilder
        from deeplearning4j_tpu.nn.losses import Loss

        conf = (
            GraphBuilder()
            .add_inputs("in")
            .set_input_types(InputType.feed_forward(6))
            .add_layer("d", Dense(n_out=8), "in")
            .add_layer("out", OutputLayer(n_out=2, loss=Loss.MCXENT), "d")
            .set_outputs("out")
            .build()
        )
        m = GraphModel(conf).init()
        with pytest.raises(NotImplementedError, match="pipeline"):
            distribute(m, ParallelConfig(data=2, pipe=4))
