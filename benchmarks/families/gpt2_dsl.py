"""Family `gpt2_dsl`: a GPT-2-style decoder LM built with the system's public
config DSL, its plain float32 reference, and its analytic operation counts.

A configuration file (`benchmarks/configs/<name>.json`) names its family;
the harness loads this module by that name and uses only the functions
below.  A new model family is a new file beside this one.

The model is [Embedding, learned PositionalEncoding, TransformerEncoderBlock
x n_layer, ChunkedSoftmaxOutputLayer] — `zoo.TransformerEncoder` does not
expose learned positions, so the stack is written out here.  Layers are
named, because the reference below reads the parameter tree by those names.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np

EMBED, POS, HEAD = "embed", "pos", "head"


def block_names(cfg: dict) -> list[str]:
    return [f"block{i:02d}" for i in range(int(cfg["n_layer"]))]


# -- the system under test -----------------------------------------------------

def build_model(cfg: dict, *, learning_rate: float = 2e-4):
    """An uninitialised `SequentialModel` of the configuration, through
    `NeuralNetConfiguration.builder()` as a user would write it.  The
    configuration's own seed stays fixed: the step program closes over it,
    so a seed that changed with the run would compile a new program every
    run.  The run's seed makes the weights (`init_on_device`)."""
    from deeplearning4j_tpu.models.sequential import SequentialModel
    from deeplearning4j_tpu.nn.conf import (
        ChunkedSoftmaxOutputLayer, Embedding, InputType,
        NeuralNetConfiguration,
    )
    from deeplearning4j_tpu.nn.conf.attention import (
        PositionalEncoding, TransformerEncoderBlock,
    )
    from deeplearning4j_tpu.nn.updaters import Adam
    from deeplearning4j_tpu.nn.weights import WeightInit

    d, vocab = int(cfg["n_embd"]), int(cfg["vocab_size"])
    b = (NeuralNetConfiguration.builder()
         .seed(0)
         .updater(Adam(learning_rate))
         .weight_init(WeightInit.XAVIER)
         .list()
         .layer(Embedding(n_in=vocab, n_out=d, name=EMBED))
         .layer(PositionalEncoding(learned=True,
                                   max_length=int(cfg["n_positions"]),
                                   name=POS)))
    for name in block_names(cfg):
        b.layer(TransformerEncoderBlock(
            d_model=d, n_heads=int(cfg["n_head"]), d_ff=int(cfg["n_inner"]),
            causal=True, name=name))
    b.layer(ChunkedSoftmaxOutputLayer(
        n_out=vocab, chunk=int(cfg["system"]["vocab_chunk"]), name=HEAD))
    return SequentialModel(b.set_input_type(InputType.recurrent(1)).build())


def _init_twin(model, key, optimizer_state: bool):
    """`init()` of a twin of `model` whose configuration carries `key` as
    its seed: the draw `SequentialModel(conf).init()` makes for a user who
    set that seed (to the last bit: one fused program rounds the scaling
    of a normal draw differently from op-by-op execution)."""
    twin = type(model)(dataclasses.replace(model.conf, seed=key))
    twin.init()
    return (twin.params, twin.net_state,
            twin.opt_state if optimizer_state else None)


def init_on_device(model, *, seed: int, optimizer_state: bool):
    """Make the weights on the device from `seed` in ONE jitted call — not
    leaf by leaf, not on the host — with the seed as a run-time argument,
    so every seed runs the same compiled program.  Serving passes
    `optimizer_state=False`: Adam's two moments would triple the resident
    bytes for nothing (a restore without updater state)."""
    init = jax.jit(lambda key: _init_twin(model, key, optimizer_state))
    model.params, model.net_state, model.opt_state = init(
        jax.random.key(int(seed)))
    return model


def abstract_params(cfg: dict):
    """ShapeDtypeStructs of the parameter tree (for offline compiles)."""
    model = build_model(cfg)
    return jax.eval_shape(
        lambda key: _init_twin(model, key, False)[0], jax.random.key(0))


# -- the plain reference ---------------------------------------------------------
#
# Straightforward jax.numpy in float32 at "highest" matmul precision: no
# kernels, no cache, no batching, nothing imported from the package.  It
# follows the block AS CONFIGURED (the config file's `departures`): pre-LN,
# no projection biases, tanh GELU, no final LayerNorm, untied head with bias.

def _layer_norm(x, p, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * p["gamma"] + p["beta"]


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def reference_hidden(cfg: dict, params, tokens):
    """tokens (T,) int32 -> final hidden states (T, n_embd), float32."""
    t = tokens.shape[0]
    h_, d = int(cfg["n_head"]), int(cfg["n_embd"])
    dh, eps = d // h_, float(cfg["layer_norm_epsilon"])
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    x = f32(params[EMBED]["W"])[tokens] + f32(params[POS]["P"])[:t]
    causal = jnp.tril(jnp.ones((t, t), bool))
    for name in block_names(cfg):
        p = jax.tree.map(f32, params[name])
        a = p["attn"]
        y = _layer_norm(x, p["ln1"], eps)
        q = (y @ a["Wq"]).reshape(t, h_, dh)
        k = (y @ a["Wk"]).reshape(t, h_, dh)
        v = (y @ a["Wv"]).reshape(t, h_, dh)
        s = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(dh)
        w = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
        o = jnp.einsum("hqk,khd->qhd", w, v).reshape(t, d)
        x = x + o @ a["Wo"]
        y = _layer_norm(x, p["ln2"], eps)
        x = x + _gelu_tanh(y @ p["W1"] + p["b1"]) @ p["W2"] + p["b2"]
    return x


def _reference_logits(params, hidden):
    return (hidden @ jnp.asarray(params[HEAD]["W"], jnp.float32)
            + jnp.asarray(params[HEAD]["b"], jnp.float32))


def make_reference_loss(cfg: dict):
    """jitted (params, ids (B, T), labels (B, T)) -> mean next-token
    cross-entropy, one sequence at a time."""
    def loss(params, ids, labels):
        def one(args):
            tok, lab = args
            z = _reference_logits(params, reference_hidden(cfg, params, tok))
            picked = jnp.take_along_axis(z, lab[:, None], axis=-1)[:, 0]
            return jnp.mean(jax.nn.logsumexp(z, axis=-1) - picked)

        with jax.default_matmul_precision("highest"):
            return jnp.mean(jax.lax.map(one, (ids, labels)))

    return jax.jit(loss)


def make_reference_gap(cfg: dict):
    """jitted (params, tokens (T,), rows (R,), emitted (R,)) -> (gap (R,),
    max|logit|): at each position `rows[i]`, how far the logit of the token
    the system emitted next sits below the reference's arg-max logit."""
    def gap(params, tokens, rows, emitted):
        with jax.default_matmul_precision("highest"):
            z = _reference_logits(
                params, reference_hidden(cfg, params, tokens)[rows])
        chosen = jnp.take_along_axis(z, emitted[:, None], axis=-1)[:, 0]
        return jnp.max(z, axis=-1) - chosen, jnp.max(jnp.abs(z))

    return jax.jit(gap)


# -- analytic operation counts ---------------------------------------------------
#
# XLA's cost analysis cannot see inside a Pallas kernel, so model FLOPs are
# computed from the shapes (formula copied from bench.py
# `_transformer_fwd_flops`, generalised to n_inner).  Recomputation (flash
# backward, chunked-loss backward) is NOT counted: these are the operations
# the forward and backward passes require.

def forward_flops_per_token(cfg: dict, seq_len: int) -> float:
    d, f = int(cfg["n_embd"]), int(cfg["n_inner"])
    per_layer = 8 * d * d + 4 * d * f + 4 * seq_len * d * 0.5   # causal
    return float(int(cfg["n_layer"]) * per_layer
                 + 2 * d * int(cfg["vocab_size"]))


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward + backward = 3 x forward."""
    return 3.0 * forward_flops_per_token(cfg, seq_len)


def flash_train_flops(cfg: dict, batch: int, seq_len: int) -> float:
    """Required FLOPs of causal attention (QK^T and PV) over one training
    step, forward + backward (2 + 4 matmuls of 2*T*T*Dh each, halved by the
    causal mask), all layers and heads; the backward's recomputed QK^T is
    not counted."""
    d = int(cfg["n_embd"])
    fwd = 4 * seq_len * seq_len * d * 0.5
    return float(batch * int(cfg["n_layer"]) * 3 * fwd)


def flash_train_bytes(cfg: dict, batch: int, seq_len: int) -> float:
    """Least HBM bytes the attention core must move per training step in
    bf16: forward reads q,k,v and writes o; backward reads q,k,v,o,do and
    writes dq,dk,dv."""
    d = int(cfg["n_embd"])
    tensors = 4 + 8
    return float(batch * int(cfg["n_layer"]) * tensors * seq_len * d * 2)


def token_batch(rng: np.random.Generator, cfg: dict, batch: int,
                seq_len: int):
    """One seeded next-token batch: int32 ids and their shift-by-one
    labels.  Ids stay integers end to end — a float feature would be cast
    to bfloat16 at the model's entry on a TPU and lose every id over 256."""
    ids = rng.integers(0, int(cfg["vocab_size"]), (batch, seq_len),
                       dtype=np.int32)
    return ids, np.roll(ids, -1, axis=1)
