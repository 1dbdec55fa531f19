"""KV-cache autoregressive decoding: parity with the dense forward,
sampling behavior, and the stack-shape contract."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.ops.generation import (
    _plan,
    generate,
    serving_params,
)
from deeplearning4j_tpu.zoo.transformer import TransformerEncoder

VOCAB, D, HEADS, LAYERS, T = 31, 16, 2, 2, 6


@pytest.fixture(scope="module")
def model():
    return TransformerEncoder(
        vocab_size=VOCAB, d_model=D, n_heads=HEADS, n_layers=LAYERS,
        causal=True, seed=5,
    ).init_model()


def test_greedy_matches_dense_forward(model):
    """Each greedy token equals argmax of the DENSE model's next-token
    distribution on the growing sequence — the cache is exact, not an
    approximation."""
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, VOCAB, (2, T))
    out = np.asarray(generate(model, prompt, 5, temperature=0.0))
    assert out.shape == (2, T + 5)
    np.testing.assert_array_equal(out[:, :T], prompt)
    seq = prompt.copy()
    for step in range(5):
        probs = np.asarray(model.output(seq.astype(np.float32)))
        nxt = probs[:, -1].argmax(axis=-1)
        np.testing.assert_array_equal(out[:, T + step], nxt,
                                      err_msg=f"step {step}")
        seq = np.concatenate([seq, nxt[:, None]], axis=1)


def test_single_token_decode(model):
    prompt = np.arange(4)[None, :]
    out = np.asarray(generate(model, prompt, 1))
    assert out.shape == (1, 5)


def test_sampling_deterministic_per_seed(model):
    prompt = np.arange(5)[None, :]
    a = np.asarray(generate(model, prompt, 8, temperature=1.0, seed=3))
    b = np.asarray(generate(model, prompt, 8, temperature=1.0, seed=3))
    c = np.asarray(generate(model, prompt, 8, temperature=1.0, seed=4))
    np.testing.assert_array_equal(a, b)
    assert not np.array_equal(a, c)


def test_top_k_restricts_support(model):
    """With top_k=1, sampling at any temperature IS greedy."""
    prompt = np.arange(5)[None, :]
    greedy = np.asarray(generate(model, prompt, 6, temperature=0.0))
    topk1 = np.asarray(generate(model, prompt, 6, temperature=2.0, top_k=1,
                                seed=11))
    np.testing.assert_array_equal(greedy, topk1)


def test_chunked_head_generates(model):
    m = TransformerEncoder(
        vocab_size=VOCAB, d_model=D, n_heads=HEADS, n_layers=1, causal=True,
        seed=6, chunked_vocab_loss=True, vocab_chunk=8,
    ).init_model()
    prompt = np.arange(4)[None, :]
    out = np.asarray(generate(m, prompt, 4))
    assert out.shape == (1, 8)
    assert (out >= 0).all() and (out < VOCAB).all()


def test_non_causal_rejected():
    m = TransformerEncoder(
        vocab_size=VOCAB, d_model=D, n_heads=HEADS, n_layers=1, causal=False,
    ).init_model()
    with pytest.raises(ValueError, match="causal"):
        generate(m, np.arange(4)[None, :], 2)


def test_unsupported_stack_rejected():
    from deeplearning4j_tpu.data import DataSet
    from deeplearning4j_tpu.models import SequentialModel
    from deeplearning4j_tpu.nn.conf import (
        Dense, InputType, NeuralNetConfiguration, OutputLayer,
    )

    conf = (
        NeuralNetConfiguration.builder().list()
        .layer(Dense(n_out=4)).layer(OutputLayer(n_out=2))
        .set_input_type(InputType.feed_forward(3)).build()
    )
    with pytest.raises(ValueError, match="Embedding"):
        generate(SequentialModel(conf).init(), np.arange(3)[None, :], 2)


def test_embedding_activation_respected():
    """A builder-level default activation lands on the Embedding layer;
    generate() must run it like the dense forward does (regression)."""
    from deeplearning4j_tpu.nn.activations import Activation
    from deeplearning4j_tpu.nn.conf import (
        Embedding, InputType, NeuralNetConfiguration,
    )
    from deeplearning4j_tpu.nn.conf.attention import (
        PositionalEncoding, TransformerEncoderBlock,
    )
    from deeplearning4j_tpu.nn.conf.recurrent import RnnOutputLayer
    from deeplearning4j_tpu.models import SequentialModel

    conf = (
        NeuralNetConfiguration.builder().seed(2)
        .activation(Activation.TANH)        # global default -> Embedding too
        .list()
        .layer(Embedding(n_in=VOCAB, n_out=D))
        .layer(PositionalEncoding())
        .layer(TransformerEncoderBlock(d_model=D, n_heads=2, causal=True))
        .layer(RnnOutputLayer(n_out=VOCAB))
        .set_input_type(InputType.recurrent(1))
        .build()
    )
    m = SequentialModel(conf).init()
    prompt = np.arange(5)[None, :]
    out = np.asarray(generate(m, prompt, 3, temperature=0.0))
    probs = np.asarray(m.output(prompt.astype(np.float32)))
    assert out[0, 5] == probs[0, -1].argmax()


# -- the serving copy of a tree (`serving_params`) ----------------------------

def _paths(tree):
    """Flattened-path name -> leaf, a `QuantizedTensor` as ONE leaf."""
    from deeplearning4j_tpu.quant.qtensor import QuantizedTensor

    flat, _ = jax.tree_util.tree_flatten_with_path(
        tree, is_leaf=lambda a: isinstance(a, QuantizedTensor))
    return {jax.tree_util.keystr(path): a for path, a in flat}


@pytest.fixture(scope="module")
def lm():
    from conftest import learned_position_lm

    return learned_position_lm(vocab=VOCAB, d=D, heads=HEADS, layers=LAYERS)


class TestServingParams:
    def test_casts_exactly_the_leaves_the_programs_cast_at_use(self, lm):
        stack = _plan(lm)
        tree = {k: dict(v) for k, v in lm.params.items()}
        # integer leaves: one no program names, one under a cast name
        tree[stack.blocks[0].name]["steps"] = jnp.arange(3)
        tree[stack.head.name]["b"] = jnp.arange(VOCAB)
        copy = serving_params(stack, tree, jnp.bfloat16)
        assert (jax.tree_util.tree_structure(copy)
                == jax.tree_util.tree_structure(tree))
        before, after = _paths(tree), _paths(copy)
        cast = {k for k in before if after[k] is not before[k]}
        want = {f"['{stack.embed.name}']['W']", f"['{stack.head.name}']['W']"}
        for b in stack.blocks:
            want |= {f"['{b.name}']['attn']['{w}']"
                     for w in ("Wq", "Wk", "Wv", "Wo")}
            want |= {f"['{b.name}']['{ln}']['{g}']"
                     for ln in ("ln1", "ln2") for g in ("gamma", "beta")}
            want |= {f"['{b.name}']['{w}']"
                     for w in ("W1", "b1", "W2", "b2")}
        assert cast == want
        for k in cast:
            assert after[k].dtype == jnp.bfloat16
            np.testing.assert_array_equal(
                np.asarray(after[k]),
                np.asarray(before[k].astype(jnp.bfloat16)))
        # the position table is read in f32: it is the tree's own leaf
        assert after[f"['{stack.pos.name}']['P']"].dtype == jnp.float32

    def test_f32_serving_tree_is_the_same_leaves(self, lm):
        copy = serving_params(_plan(lm), lm.params, jnp.float32)
        assert (jax.tree_util.tree_structure(copy)
                == jax.tree_util.tree_structure(lm.params))
        for a, b in zip(jax.tree.leaves(lm.params), jax.tree.leaves(copy)):
            assert a is b

    def test_a_copy_of_the_copy_is_the_copy(self, lm):
        stack = _plan(lm)
        copy = serving_params(stack, lm.params, jnp.bfloat16)
        again = serving_params(stack, copy, jnp.bfloat16)
        for a, b in zip(jax.tree.leaves(copy), jax.tree.leaves(again)):
            assert a is b

    def test_quantized_weights_pass_through(self, model):
        from deeplearning4j_tpu.quant import quantize
        from deeplearning4j_tpu.quant.qtensor import QuantizedTensor

        q = quantize(model)
        before = _paths(q.params)
        after = _paths(serving_params(_plan(q), q.params, jnp.bfloat16))
        packed = {k for k, a in before.items()
                  if isinstance(a, QuantizedTensor)}
        assert packed                      # int8 weights + f32 scales
        for k in packed:
            assert after[k] is before[k]
        # what stayed a float leaf beside them is cast as in an f32 tree
        for k in set(before) - packed:
            assert after[k].dtype == jnp.bfloat16

    def test_generate_reads_the_copy_as_it_reads_the_tree(self):
        """bf16 forced on the CPU: the casts at use, on the copy, are
        casts to the type the leaf already has."""
        from conftest import learned_position_lm

        m = learned_position_lm(vocab=VOCAB, d=D, heads=HEADS,
                                layers=LAYERS, bf16=True)
        prompt = np.random.default_rng(3).integers(0, VOCAB, (2, T))
        want = np.asarray(generate(m, prompt, 6, temperature=1.0, seed=4))
        m.params = serving_params(_plan(m), m.params, jnp.bfloat16)
        got = np.asarray(generate(m, prompt, 6, temperature=1.0, seed=4))
        np.testing.assert_array_equal(got, want)
