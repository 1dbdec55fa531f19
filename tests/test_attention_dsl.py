"""Attention in the config DSL: SelfAttentionLayer / LearnedSelfAttention /
AttentionVertex / TransformerEncoderBlock, and the seq_parallel knob lowering
to ring/Ulysses over a real multi-device CPU mesh (SURVEY.md §5.7's
config-knob requirement)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.data.dataset import DataSet
from deeplearning4j_tpu.models.computation_graph import GraphModel
from deeplearning4j_tpu.models.sequential import SequentialModel
from deeplearning4j_tpu.nn.activations import Activation
from deeplearning4j_tpu.nn.conf import (
    Embedding,
    InputType,
    LearnedSelfAttentionLayer,
    NeuralNetConfiguration,
    OutputLayer,
    PositionalEncoding,
    RnnOutputLayer,
    SelfAttentionLayer,
    TransformerEncoderBlock,
)
from deeplearning4j_tpu.nn.conf.graph_conf import AttentionVertex, GraphBuilder
from deeplearning4j_tpu.nn.losses import Loss
from deeplearning4j_tpu.nn.updaters import Adam
from deeplearning4j_tpu.ops.attention import mha
from deeplearning4j_tpu.parallel import ParallelConfig, distribute
from deeplearning4j_tpu.utils import serde
from deeplearning4j_tpu.zoo.transformer import TransformerEncoder

KEY = jax.random.key(0)
B, T, F = 2, 8, 12


def _x(seed=0, shape=(B, T, F)):
    return np.random.default_rng(seed).normal(0, 1, shape).astype(np.float32)


# -- SelfAttentionLayer ------------------------------------------------------

def test_self_attention_shapes_and_parity_with_mha():
    layer = SelfAttentionLayer(n_out=8, n_heads=2, name="sa")
    itype = InputType.recurrent(F, T)
    params, _ = layer.init(KEY, itype)
    x = jnp.asarray(_x())
    y, _ = layer.apply(params, {}, x)
    assert y.shape == (B, T, 8)
    # manual recomputation through the raw op
    q = (x @ params["Wq"]).reshape(B, T, 2, 4)
    k = (x @ params["Wk"]).reshape(B, T, 2, 4)
    v = (x @ params["Wv"]).reshape(B, T, 2, 4)
    ref = mha(q, k, v).reshape(B, T, 8) @ params["Wo"]
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref), rtol=1e-5, atol=1e-6)


def test_self_attention_no_projection_requires_matching_dims():
    layer = SelfAttentionLayer(n_out=F, n_heads=3, head_size=4, project_input=False)
    itype = InputType.recurrent(F, T)
    params, _ = layer.init(KEY, itype)
    assert params == {}
    y, _ = layer.apply(params, {}, jnp.asarray(_x()))
    assert y.shape == (B, T, F)
    bad = SelfAttentionLayer(n_out=10, n_heads=2, project_input=False)
    with pytest.raises(ValueError):
        bad.output_type(itype)


def test_self_attention_key_mask_blocks_padded_keys():
    layer = SelfAttentionLayer(n_out=6, n_heads=2, name="sa")
    itype = InputType.recurrent(F, T)
    params, _ = layer.init(KEY, itype)
    x = jnp.asarray(_x(1))
    mask = jnp.asarray((np.arange(T)[None, :] < [[5], [3]]).astype(np.float32))
    y_masked, _ = layer.apply(params, {}, x, mask=mask)
    # perturbing a masked (padded) timestep must not change the output at
    # unmasked positions
    x2 = x.at[:, -1, :].add(100.0)
    y2, _ = layer.apply(params, {}, x2, mask=mask)
    np.testing.assert_allclose(
        np.asarray(y_masked[:, :3]), np.asarray(y2[:, :3]), rtol=1e-4, atol=1e-5
    )


def test_self_attention_gradient_check():
    layer = SelfAttentionLayer(n_out=4, n_heads=2, name="sa")
    itype = InputType.recurrent(5, 4)
    params, _ = layer.init(KEY, itype)
    x = jnp.asarray(_x(2, (2, 4, 5)))

    def loss(p):
        y, _ = layer.apply(p, {}, x)
        return jnp.sum(y**2)

    grads = jax.grad(loss)(params)
    eps = 1e-3
    for pname in ("Wq", "Wo"):
        w = params[pname]
        for idx in [(0, 0), (1, 2)]:
            wp = params | {pname: w.at[idx].add(eps)}
            wm = params | {pname: w.at[idx].add(-eps)}
            fd = (loss(wp) - loss(wm)) / (2 * eps)
            np.testing.assert_allclose(
                float(grads[pname][idx]), float(fd), rtol=2e-2, atol=1e-3
            )


def test_causal_self_attention_ignores_future():
    layer = SelfAttentionLayer(n_out=6, n_heads=1, causal=True, name="sa")
    itype = InputType.recurrent(F, T)
    params, _ = layer.init(KEY, itype)
    x = jnp.asarray(_x(3))
    y1, _ = layer.apply(params, {}, x)
    x2 = x.at[:, -1, :].add(50.0)  # change only the last step
    y2, _ = layer.apply(params, {}, x2)
    np.testing.assert_allclose(
        np.asarray(y1[:, :-1]), np.asarray(y2[:, :-1]), rtol=1e-4, atol=1e-5
    )


# -- LearnedSelfAttentionLayer ----------------------------------------------

def test_learned_queries_shapes():
    layer = LearnedSelfAttentionLayer(n_out=6, n_heads=2, n_queries=3, name="lsa")
    itype = InputType.recurrent(F, T)
    params, _ = layer.init(KEY, itype)
    y, _ = layer.apply(params, {}, jnp.asarray(_x(4)))
    assert y.shape == (B, 3, 6)
    assert layer.output_type(itype).shape == (3, 6)


# -- PositionalEncoding ------------------------------------------------------

def test_sinusoidal_positional_encoding():
    layer = PositionalEncoding(name="pe")
    itype = InputType.recurrent(F, T)
    params, _ = layer.init(KEY, itype)
    assert params == {}
    x = jnp.zeros((B, T, F))
    y, _ = layer.apply(params, {}, x)
    assert y.shape == (B, T, F)
    # position 0: sin(0)=0 on even dims, cos(0)=1 on odd dims
    np.testing.assert_allclose(np.asarray(y[0, 0, 0::2]), 0.0, atol=1e-6)
    np.testing.assert_allclose(np.asarray(y[0, 0, 1::2]), 1.0, atol=1e-6)
    # rows differ across positions
    assert not np.allclose(np.asarray(y[0, 1]), np.asarray(y[0, 2]))


def test_learned_positional_encoding():
    layer = PositionalEncoding(learned=True, max_length=16, name="pe")
    itype = InputType.recurrent(F, T)
    params, _ = layer.init(KEY, itype)
    assert params["P"].shape == (16, F)
    x = jnp.zeros((B, T, F))
    y, _ = layer.apply(params, {}, x)
    np.testing.assert_allclose(np.asarray(y[0]), np.asarray(params["P"][:T]), rtol=1e-6)


# -- TransformerEncoderBlock -------------------------------------------------

def test_transformer_block_shapes_and_residual():
    layer = TransformerEncoderBlock(d_model=F, n_heads=2, name="blk")
    itype = InputType.recurrent(F, T)
    params, _ = layer.init(KEY, itype)
    x = jnp.asarray(_x(5))
    y, _ = layer.apply(params, {}, x)
    assert y.shape == (B, T, F)
    # serde round trip
    blob = serde.dumps(layer)
    back = serde.loads(blob)
    assert back == layer


def test_transformer_trains_on_copy_task():
    """A tiny causal LM must fit a repeated-token sequence."""
    model = SequentialModel(
        NeuralNetConfiguration.builder()
        .seed(7)
        .updater(Adam(1e-2))
        .list()
        .layer(Embedding(n_in=16, n_out=16))
        .layer(PositionalEncoding())
        .layer(TransformerEncoderBlock(d_model=16, n_heads=2, causal=True))
        .layer(
            RnnOutputLayer(n_out=16, loss=Loss.MCXENT, activation=Activation.SOFTMAX)
        )
        .set_input_type(InputType.recurrent(1))
        .build()
    ).init()
    rng = np.random.default_rng(0)
    ids = rng.integers(0, 16, (8, 10)).astype(np.float32)
    labels = np.eye(16, dtype=np.float32)[ids.astype(int)]  # predict self
    ds = DataSet(ids, labels)
    model.fit_batch(ds)
    first = model.score_value
    for _ in range(30):
        model.fit_batch(ds)
    assert model.score_value < first * 0.5, (first, model.score_value)


# -- AttentionVertex in a GraphModel ----------------------------------------

def test_attention_vertex_graph_trains():
    conf = (
        GraphBuilder()
        .add_inputs("in")
        .set_input_types(InputType.recurrent(F, T))
        .add_vertex("attn", AttentionVertex(n_out=8, n_heads=2), "in")
        .add_layer(
            "out",
            RnnOutputLayer(n_out=3, loss=Loss.MCXENT, activation=Activation.SOFTMAX),
            "attn",
        )
        .set_outputs("out")
        .updater(Adam(1e-2))
        .build()
    )
    model = GraphModel(conf).init()
    assert "attn" in model.params and "Wq" in model.params["attn"]
    x = _x(6)
    labels = np.eye(3, dtype=np.float32)[
        np.random.default_rng(1).integers(0, 3, (B, T))
    ]
    model.fit_batch(DataSet(x, labels))
    first = model.score_value
    for _ in range(20):
        model.fit_batch(DataSet(x, labels))
    assert model.score_value < first
    # config round-trips with the vertex
    back = conf.from_json(conf.to_json())
    assert back.nodes[0].vertex == conf.nodes[0].vertex


# -- seq_parallel knob on a real mesh ----------------------------------------

def _tiny_transformer(seq_parallel: str):
    m = TransformerEncoder(
        vocab_size=16,
        d_model=8,
        n_heads=4,
        n_layers=1,
        causal=True,
        seq_parallel=seq_parallel,
        seed=11,
        learning_rate=1e-2,
    ).init_model()
    return m


def _lm_batch():
    rng = np.random.default_rng(3)
    ids = rng.integers(0, 16, (4, 16)).astype(np.float32)
    labels = np.eye(16, dtype=np.float32)[ids.astype(int)]
    return DataSet(ids, labels)


@pytest.mark.parametrize("mode", ["ring", "ulysses"])
def test_seq_parallel_matches_dense_training(mode):
    """The SAME config trained dense vs seq-sharded over 4 devices must
    produce the same loss trajectory (ring/Ulysses are exact)."""
    ds = _lm_batch()
    dense = _tiny_transformer("none")
    losses_dense = []
    for _ in range(3):
        dense.fit_batch(ds)
        losses_dense.append(dense.score_value)

    sharded = _tiny_transformer(mode)
    distribute(sharded, ParallelConfig(data=1, seq=4), devices=jax.devices()[:4])
    losses_sharded = []
    for _ in range(3):
        sharded.fit_batch(ds)
        losses_sharded.append(sharded.score_value)

    np.testing.assert_allclose(losses_sharded, losses_dense, rtol=2e-3, atol=2e-4)


def test_seq_parallel_with_data_parallel_combo():
    """seq x data mesh (4 x 2) over the 8-device CPU platform: the
    ring-attention body is manual over "seq", the data axis stays
    GSPMD-auto around it."""
    data = 2
    ds = _lm_batch()
    model = _tiny_transformer("ring")
    distribute(model, ParallelConfig(data=data, seq=4),
               devices=jax.devices()[: data * 4])
    for _ in range(2):
        model.fit_batch(ds)
    assert np.isfinite(model.score_value)
