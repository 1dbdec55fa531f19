"""KV-cache autoregressive decoding for DSL-built transformer stacks.

The reference's only generation story is RNN `rnnTimeStep` streaming; a
transformer decoded that way recomputes full-sequence attention per token
(O(T^2) per step).  Here `generate()` introspects a SequentialModel built
as [Embedding, PositionalEncoding, TransformerEncoderBlock*, head],
prefills per-block K/V caches from the prompt in ONE dense forward, then
decodes with a `lax.scan` whose body attends one query row against the
cache — O(T) per step, static shapes throughout, the whole decode loop a
single compiled XLA program.  Greedy, temperature, and top-k sampling.

This dense-cache `generate()` is the SINGLE-REQUEST REFERENCE PATH: its
per-position numerics (`cache_row_attention`'s f32 attention, `_sample`'s
greedy/temperature/top-k rules, the `fold_in(rng, i)` key schedule) are
the contract the paged serving engine (`serving/generation.py` over
`ops/paged_attention.py`) must reproduce token-for-token — greedy
exactly, sampled exactly under a shared seed, int8-KV within the PR 13
agreement gate.

What a stack computes is written HERE, once: `_plan` (the stack's
layers), `embed_tokens` (gather, activation, position), `block` (one
transformer block over an `attend(q, k, v)` callback) and `_head_logits`;
`serving_params` names the leaves they cast at use, for a caller that
keeps a tree across calls.  `generate()`, the engine's prefill / step /
verify programs and the model drafter (`serving/speculative.py`) all call
them; a caller only chooses which state the attention runs against — the
whole prompt (`prompt_forward`), a dense cache row
(`cache_row_attention`), or the paged pool (the engine's closure).
Changing decode semantics here changes every one of them; the paged
parity tests (`tests/test_paged_generation.py`) hold the engine to
`generate()`.
"""

from __future__ import annotations

import collections
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from deeplearning4j_tpu.nn.conf.attention import (
    LatentBlock,
    LatentSparseDecoder,
    PositionalEncoding,
    TransformerEncoderBlock,
)
from deeplearning4j_tpu.nn.conf.hybrid import HybridBlock, HybridDecoder
from deeplearning4j_tpu.nn.conf.layers import (
    ChunkedSoftmaxOutputLayer,
    Embedding,
    LayerConfig,
)
from deeplearning4j_tpu.nn.conf.recurrent import RnnOutputLayer
from deeplearning4j_tpu.ops.attention import mha
from deeplearning4j_tpu.ops.hybrid import final_norm, hybrid_block
from deeplearning4j_tpu.ops.latent import final_rows, first_rows, latent_block
from deeplearning4j_tpu.quant.qtensor import QuantizedTensor

#: what `_plan` returns: the stack's layers (each block knows where its
#: entry of the params tree is, `block_params`, and what one token caches
#: in it, `cache_rows`), the decoder whose final norm precedes the head
#: (None: the stack has none), and the model width
_Stack = collections.namedtuple("_Stack", "embed pos blocks head d final")


def cache_rows(cfg) -> dict:
    """What one token caches in a block: pool name -> the row's shape.  A
    `TransformerEncoderBlock` caches a key and a value of ``(heads,
    head_dim)`` each; a `LatentBlock` or a `HybridBlock` states its own
    widths (a hybrid layer other than the full one, and a latent window
    layer, cache nothing per token: what they keep is per stream,
    `slot_rows`)."""
    if isinstance(cfg, (LatentBlock, HybridBlock)):
        return {name: (width,) for name, width in cfg.cache_rows.items()}
    row = (cfg.n_heads, cfg.d_model // cfg.n_heads)
    return {"k": row, "v": row}


def slot_rows(cfg) -> dict:
    """What a block keeps per STREAM (per decode slot), whatever its
    length: name -> (shape, "f32" | "kv"); "kv" is the pool's row type.
    A `HybridBlock`'s Mamba state and window ring, a window `LatentBlock`'s
    ring of latent rows."""
    return (cfg.slot_rows if isinstance(cfg, (LatentBlock, HybridBlock))
            else {})


def block_params(params, cfg):
    """The block's entry of the model's params tree."""
    if isinstance(cfg, (LatentBlock, HybridBlock)):
        return params[cfg.path[0]][cfg.path[1]]
    return params[cfg.name]


def _plan(model):
    """Validate the stack shape and return its `_Stack` record."""
    layers = list(model.conf.layers)
    if not layers or not isinstance(layers[0], Embedding):
        raise ValueError("generate() needs an Embedding first layer")
    embed = layers[0]
    i = 1
    pos = None
    if i < len(layers) and isinstance(layers[i], PositionalEncoding):
        pos = layers[i]
        i += 1
    blocks, final = [], None
    if i < len(layers) and isinstance(layers[i], LatentSparseDecoder):
        # rotary positions inside the blocks; a position table would add
        # a second, absolute, encoding the family does not have
        if pos is not None:
            raise ValueError("a LatentSparseDecoder takes no "
                             "PositionalEncoding before it")
        final = layers[i]
        blocks = list(final.blocks())
        i += 1
    elif i < len(layers) and isinstance(layers[i], HybridDecoder):
        # no position encoding (the scans and the window carry order) and
        # no head layer: the logits are the embedding's matrix, tied
        if pos is not None or i != len(layers) - 1:
            raise ValueError("a HybridDecoder takes no PositionalEncoding "
                             "and is the last layer: its head is the "
                             "embedding's matrix")
        final = layers[i]
        return _Stack(embed, None, tuple(final.blocks()), final,
                      embed.n_out, final)
    else:
        while (i < len(layers)
               and isinstance(layers[i], TransformerEncoderBlock)):
            blocks.append(layers[i])
            i += 1
    if i != len(layers) - 1:
        raise ValueError(
            "generate() supports three stacks: [Embedding, "
            "PositionalEncoding?, TransformerEncoderBlock*, head], "
            "[Embedding, LatentSparseDecoder, head] (latent attention: "
            "selected, grouped or windowed; plain or multi-stream "
            "residual) and [Embedding, HybridDecoder]; layer "
            f"{type(layers[i]).__name__} at position {i} is not supported"
        )
    head = layers[-1]
    if not isinstance(head, (RnnOutputLayer, ChunkedSoftmaxOutputLayer)):
        raise ValueError(
            f"unsupported head {type(head).__name__}; need RnnOutputLayer "
            "or ChunkedSoftmaxOutputLayer"
        )
    for b in blocks:
        if not b.causal:
            raise ValueError(
                "generate() requires causal blocks (bidirectional attention "
                "cannot decode autoregressively)"
            )
    return _Stack(embed, pos, tuple(blocks), head, embed.n_out, final)


def _act_dtype(model):
    return jnp.bfloat16 if model._bf16 else jnp.float32


#: the leaves of a block's entry that `block` casts to the activation type
#: at use (the embedding's are "W", the head's "W" and "b")
_BLOCK_CAST = frozenset(("Wq", "Wk", "Wv", "Wo", "gamma", "beta",
                         "W1", "b1", "W2", "b2"))


@functools.partial(jax.jit, static_argnums=1)
def _cast_leaves(leaves, dt):
    return [a.astype(dt) for a in leaves]


def serving_params(stack, params, dt):
    """The tree a long-lived caller dispatches its programs with: the
    structure of ``params``, in which exactly the leaves `embed_tokens`,
    `block` and `_head_logits` cast at use are in ``dt`` already, so the
    casts there trace to nothing and a program reads a weight at the
    width it multiplies.  Every other leaf is the object ``params``
    holds: the position table (read in f32), integer leaves, any
    `QuantizedTensor`, and every leaf that is ``dt`` already — all of
    them where ``dt`` is f32.  A `LatentSparseDecoder`'s leaves pass as they
    are: its trees are made in the type they are served in (an f32 twin of
    a tree that fills half the chip cannot exist beside it).  One jitted
    cast, made once per tree."""
    cast = {stack.embed.name: ("W",), stack.head.name: ("W", "b"),
            **{b.name: _BLOCK_CAST for b in stack.blocks
               if isinstance(b, TransformerEncoderBlock)}}
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        params, is_leaf=lambda a: isinstance(a, QuantizedTensor))
    leaves = [a for _, a in flat]
    wide = [i for i, (path, a) in enumerate(flat)
            if path[-1].key in cast.get(path[0].key, ())
            and not isinstance(a, QuantizedTensor)
            and jnp.issubdtype(a.dtype, jnp.floating) and a.dtype != dt]
    if wide:
        for i, a in zip(wide, _cast_leaves([leaves[i] for i in wide], dt)):
            leaves[i] = a
    return jax.tree_util.tree_unflatten(treedef, leaves)


def _pe_row(pos_layer, lp, t, d):
    """Positional-encoding row for ONE (traced) position t — the decode
    tick's O(1) counterpart of PositionalEncoding.apply; keep the
    sinusoidal formula in sync with attention.py."""
    if pos_layer is None:
        return jnp.zeros((d,), jnp.float32)
    if pos_layer.learned:
        return lp["P"][t].astype(jnp.float32)
    div = jnp.exp(
        jnp.arange(0, d, 2, dtype=jnp.float32) * (-jnp.log(10000.0) / d)
    )
    tf = t.astype(jnp.float32)
    row = jnp.zeros((d,), jnp.float32)
    row = row.at[0::2].set(jnp.sin(tf * div))
    row = row.at[1::2].set(jnp.cos(tf * div[: d // 2]))
    return row


def _ln(lp, x):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    y = (x - mean) * lax.rsqrt(var + 1e-5)
    return y * lp["gamma"].astype(x.dtype) + lp["beta"].astype(x.dtype)


def embed_tokens(stack, params, toks, positions, dt):
    """Token ids -> the first block's input rows (`_embed`), copied into
    each residual stream where a latent decoder keeps several."""
    x = _embed(stack, params, toks, positions, dt)
    if isinstance(stack.final, LatentSparseDecoder):
        x = first_rows(stack.final, x)
    return x


def _embed(stack, params, toks, positions, dt):
    """Token ids -> the first block's input: the embedding gather through
    the LAYER's semantics (its activation included) plus position.
    ``positions=None`` is a whole prompt from position 0 (the layer's own
    vectorized encoding — a per-position Python loop would unroll O(T)
    ops into the trace); otherwise one traced position for every row of
    ``toks``, or a scalar shared by them all (the decode tick)."""
    E = params[stack.embed.name]["W"].astype(dt)
    x = stack.embed._act()(E[toks])
    pos = stack.pos
    lp = params.get(pos.name, {}) if pos is not None else {}
    if positions is None:
        if pos is not None:
            x, _ = pos.apply(lp, {}, x)
        return x
    if pos is None:
        return x
    row = lambda t: _pe_row(pos, lp, t, stack.d)
    pe = row(positions) if jnp.ndim(positions) == 0 else jax.vmap(row)(
        positions)
    return x + pe.astype(dt)


def block(cfg, lp, x, attend):
    """One decoder block on x, by the block's config; ``attend`` is the
    caller's side of it.  A `TransformerEncoderBlock`: x is (..., D) and
    ``attend(q, k, v)`` takes the (..., H, Dh) projections of these rows
    and returns their attention output, same shape; which K/V it runs
    against — and where it keeps the rows it was handed — is the caller's
    closure.  A `LatentBlock`: x is (n, D) rows — (n, S x D), the residual
    streams side by side, in a decoder with ``mhc`` — and ``attend`` an
    `ops/latent.LatentRows` — the rows' positions, the closure
    ``attend(cfg, q, latent, index, wkvb)`` and what the expert layers
    count.  Either way the block's output rows come back."""
    if isinstance(cfg, LatentBlock):
        return latent_block(cfg, lp, x, attend)
    if isinstance(cfg, HybridBlock):
        return hybrid_block(cfg, lp, x, attend)
    dt = x.dtype
    rows, h_ = x.shape[:-1], cfg.n_heads
    heads = rows + (h_, cfg.d_model // h_)
    ap = lp["attn"]
    hh = _ln(lp["ln1"], x)
    q = (hh @ ap["Wq"].astype(dt)).reshape(heads)
    k = (hh @ ap["Wk"].astype(dt)).reshape(heads)
    v = (hh @ ap["Wv"].astype(dt)).reshape(heads)
    out = attend(q, k, v).reshape(rows + (cfg.d_model,)).astype(dt)
    x = x + out @ ap["Wo"].astype(dt)
    hh = _ln(lp["ln2"], x)
    hh = cfg.ffn_activation(hh @ lp["W1"].astype(dt) + lp["b1"].astype(dt))
    return x + (hh @ lp["W2"].astype(dt) + lp["b2"].astype(dt))


def prompt_forward(stack, params, toks, dt):
    """Dense causal forward over whole prompts, toks: (B, T).  Returns the
    last block's output (B, T, D) and every block's (k, v), each
    (B, T, H, Dh) — the cache seed."""
    if stack.final is not None:
        raise ValueError("prompt_forward seeds a dense K/V cache; a "
                         f"{type(stack.final).__name__} stack has none")
    x = embed_tokens(stack, params, toks, None, dt)
    kvs = []

    def attend(q, k, v):
        kvs.append((k, v))
        return mha(q, k, v, causal=True)

    for cfg in stack.blocks:
        x = block(cfg, params[cfg.name], x, attend)
    return x, kvs


def cache_row_attention(k_cache, v_cache, pos, grown):
    """`attend` for ONE query row per sequence against a dense cache:
    q, k_t, v_t: (B, H, Dh); caches: (B, L, H, Dh); pos: scalar current
    position.  The caches with this row written are appended to
    ``grown``.  f32 scores, ``-inf`` past ``pos``, f32 softmax, f32
    output: the per-position numerics `ops/paged_attention.py` holds
    itself to."""
    def attend(q, k_t, v_t):
        dh, L = q.shape[-1], k_cache.shape[1]
        k_c = lax.dynamic_update_index_in_dim(k_cache, k_t, pos, axis=1)
        v_c = lax.dynamic_update_index_in_dim(v_cache, v_t, pos, axis=1)
        grown.append((k_c, v_c))
        scores = jnp.einsum("bhd,blhd->bhl", q.astype(jnp.float32),
                            k_c.astype(jnp.float32)) / np.sqrt(dh)
        live = jnp.arange(L)[None, None, :] <= pos
        scores = jnp.where(live, scores, -jnp.inf)
        p = jax.nn.softmax(scores, axis=-1)
        return jnp.einsum("bhl,blhd->bhd", p, v_c.astype(jnp.float32))

    return attend


def _head_logits(stack, params, h):
    """h: (..., D), the last block's output -> (..., vocab) logits.  A
    `HybridDecoder`'s head is ``LN_f`` and the embedding's matrix, tied."""
    if isinstance(stack.final, HybridDecoder):
        e = params[stack.embed.name]["W"]
        return jnp.einsum(
            "...d,vd->...v",
            final_norm(stack.final, params, h).astype(e.dtype), e,
            preferred_element_type=jnp.float32)
    head, lp = stack.head, params[stack.head.name]
    if stack.final is not None:
        h = final_rows(stack.final, params[stack.final.name], h)
    if isinstance(head, ChunkedSoftmaxOutputLayer):
        return head.logits(lp, h)
    y = h @ lp["W"].astype(h.dtype)
    if head.has_bias:
        y = y + lp["b"].astype(h.dtype)
    return y


def _sample(logits, *, temperature, top_k, rng):
    logits = logits.astype(jnp.float32)
    if temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = logits / temperature
    if top_k > 0:
        kth = lax.top_k(logits, top_k)[0][..., -1:]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    return jax.random.categorical(rng, logits, axis=-1).astype(jnp.int32)


def generate(model, prompt_ids, max_new_tokens: int, *,
             temperature: float = 0.0, top_k: int = 0, seed: int = 0):
    """Decode `max_new_tokens` continuations of `prompt_ids` (B, T_p) int.

    Returns (B, T_p + max_new_tokens) int32 — prompt followed by the
    generated tokens.  temperature=0 is greedy; top_k>0 restricts
    sampling to the k most likely tokens.  The decode loop is one
    compiled scan; recompilation happens per (prompt length,
    max_new_tokens) shape pair.
    """
    if model.params is None:
        model.init()
    stack = _plan(model)
    if stack.final is not None:
        raise ValueError(
            "generate() decodes against a dense K/V cache; a "
            f"{type(stack.final).__name__} stack is served by "
            "GenerationEngine (serving/generation.py) over its pools")
    pos = stack.pos
    prompt = jnp.asarray(prompt_ids).astype(jnp.int32)
    if prompt.ndim == 1:
        prompt = prompt[None, :]
    if max_new_tokens <= 0:
        return prompt
    if pos is not None and pos.learned:
        total = prompt.shape[1] + max_new_tokens
        if total > pos.max_length:
            # the dense forward raises for over-length sequences; silent
            # index clamping here would reuse the last PE row instead
            raise ValueError(
                f"prompt + max_new_tokens = {total} exceeds the learned "
                f"PositionalEncoding max_length {pos.max_length}"
            )
    key = ("generate", int(max_new_tokens), float(temperature), int(top_k))
    cache = getattr(model, "_gen_fns", None)
    if cache is None:
        cache = model._gen_fns = {}
    if key not in cache:
        cache[key] = _generate_jit(
            model, stack, int(max_new_tokens), float(temperature),
            int(top_k),
        )
    return cache[key](model.params, prompt, jax.random.key(seed))


def _generate_jit(model, stack, max_new, temperature, top_k):
    @jax.jit
    def run(params, prompt, rng):
        b, t_p = prompt.shape
        L = t_p + max_new
        dt = _act_dtype(model)

        # ---- prefill: dense forward over the prompt, caches out ----
        x, kvs = prompt_forward(stack, params, prompt, dt)
        caches = []
        for k, v in kvs:
            k_c = jnp.zeros((b, L) + k.shape[2:], k.dtype)
            v_c = jnp.zeros((b, L) + v.shape[2:], v.dtype)
            caches.append((
                lax.dynamic_update_slice(k_c, k, (0, 0, 0, 0)),
                lax.dynamic_update_slice(v_c, v, (0, 0, 0, 0)),
            ))
        logits = _head_logits(stack, params, x[:, -1])
        first = _sample(logits, temperature=temperature, top_k=top_k,
                        rng=jax.random.fold_in(rng, 0))

        # ---- decode loop: one token per tick against the caches ----
        def tick(carry, i):
            tok, caches = carry
            t = t_p + i                                  # position of tok
            x_t = embed_tokens(stack, params, tok, t, dt)
            grown = []
            for cfg, (k_c, v_c) in zip(stack.blocks, caches):
                x_t = block(cfg, params[cfg.name], x_t,
                            cache_row_attention(k_c, v_c, t, grown))
            logits = _head_logits(stack, params, x_t)
            nxt = _sample(logits, temperature=temperature, top_k=top_k,
                          rng=jax.random.fold_in(rng, i + 1))
            return (nxt, tuple(grown)), tok

        (last, _), toks = lax.scan(
            tick, (first, tuple(caches)), jnp.arange(max_new - 1)
        ) if max_new > 1 else ((first, None), jnp.zeros((0, b), jnp.int32))
        gen = jnp.concatenate([jnp.swapaxes(toks, 0, 1), last[:, None]], axis=1)
        return jnp.concatenate([prompt, gen], axis=1)

    return run
