"""Family `glm_moe_dsa`: the GLM-5.x decoder — latent (MLA) attention, the
DSA indexer with selections shared between layers, sigmoid-routed experts
with one shared expert — built with the system's config DSL as ONE chip's
share of an expert-parallel deployment, its plain float32 reference, and its
analytic operation counts.

The equations (x: one token's residual; every norm RMSNorm, no biases):

    block    x += MLA(norm1 x);  x += FFN(norm2 x);  after the last: norm_f, head
    MLA      c_q = norm(h W_qa);  q = c_q W_qb -> heads x (nope | rope)
             [c_kv | k_r] = h W_kva;  c_kv = norm(c_kv);  rope on q's rope part and k_r
             per head [k_nope | v] = c_kv W_kvb
             score = (q_nope.k_nope + q_rope.k_r) / sqrt(nope + rope) over j <= t, j in S_t
    indexer  ("full" layers) q_I = c_q W_Iq -> hi x di;  k_I = LayerNorm(h W_Ik)
             rope on the first `qk_rope_head_dim` dims of both;  w = h W_Iw / sqrt(hi di)
             I[t, j] = sum_h w[t, h] relu(q_I[t, h].k_I[j]);  S_t = top min(index_topk, t + 1)
             "shared" layers reuse S_t of the nearest full layer before them
    experts  s = sigmoid(h W_r);  top k of s + bias;  g_e = scale s_e / sum_chosen s
             y = sum_{e chosen AND held here} g_e E_e(h) + E_shared(h)
             E(h) = (silu(h W_g) * (h W_u)) W_d;  a dense layer is E at the dense width

The reference below is those lines in `jax.numpy`, float32, "highest"
matmul precision, nothing imported from the package: no cache, no pages, no
batching; queries go in blocks and an expert's matrices are upcast one
expert at a time, so that it fits beside the weights at the timed sizes.
It is GIVEN THE SAME SHARE as the system: the held experts' part of every
expert layer plus the shared expert, and the vocabulary slice.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

EMBED, DECODER, HEAD = "embed", "decoder", "head"
QUERY_BLOCK = 128
#: the embedding's rows are drawn at this standard deviation (the DSL draws
#: them at 1/sqrt(vocabulary); RMSNorm stands before every product, so only
#: their size RELATIVE to what a layer adds matters, about 0.6 a row per
#: feed-forward at the DSL's unit-gain matrices).  Every matrix keeps the
#: DSL's own draw: a routed expert's output has its full weight in the logits
EMBED_STD = 1.0
#: a checked row is LEFT OUT of the comparison where, in the reference, a
#: held expert's routing score ``s + bias`` lies within this of the top-k
#: boundary in any expert layer.  Which of two near-tied experts a row takes
#: is the model's one discrete step per layer: there bfloat16 and float32
#: activations legitimately choose differently, and a whole gated expert
#: output then stands between the system and the reference.  The rule reads
#: the reference alone.  Readings on a v5e (PR 32, PERF.md section 6): of
#: 1,857 checked rows of six streams the 14 that sat more than 0.015 below
#: the reference's arg-max (up to 0.089) all had a margin of 3.1e-3 or less,
#: and the system's first expert layer chose differently nowhere past 2.1e-3
#: in 29,653 rows; twice the larger leaves 63 % of the rows in, which read
#: 0.0041-0.0090 against 0.19-0.28 with the held range shifted by one and
#: 0.044-0.10 with the reference rounded to fp8
ROUTER_TIE = 6e-3


# -- the configuration as run ------------------------------------------------------

def layer_kinds(cfg: dict) -> list[tuple[str, str]]:
    """(indexer kind, mlp kind) of each layer that is run: the published
    lists at the published indices `system.layers_run` names."""
    run = cfg["system"]["layers_run"]
    assert len(run) == int(cfg["num_hidden_layers"])
    return [(cfg["indexer_types"][i], cfg["mlp_layer_types"][i]) for i in run]


def layer_names(cfg: dict) -> list[str]:
    return [f"layer{i:02d}" for i in range(int(cfg["num_hidden_layers"]))]


def deployment(cfg: dict) -> dict:
    return cfg["deployment"]


# -- the system under test -----------------------------------------------------

def build_model(cfg: dict, *, learning_rate: float = 2e-4):
    """An uninitialised `SequentialModel`: [Embedding, LatentSparseDecoder,
    ChunkedSoftmaxOutputLayer], through the public DSL."""
    from deeplearning4j_tpu.models.sequential import SequentialModel
    from deeplearning4j_tpu.nn.conf import (
        ChunkedSoftmaxOutputLayer, Embedding, InputType,
        LatentSparseDecoder, NeuralNetConfiguration,
    )
    from deeplearning4j_tpu.nn.updaters import Adam
    from deeplearning4j_tpu.nn.weights import WeightInit

    d, vocab = int(cfg["hidden_size"]), int(cfg["vocab_size"])
    kinds = layer_kinds(cfg)
    dep = deployment(cfg)
    b = (NeuralNetConfiguration.builder()
         .seed(0)
         .updater(Adam(learning_rate))
         .weight_init(WeightInit.LECUN_NORMAL)
         .list()
         .layer(Embedding(n_in=vocab, n_out=d, name=EMBED))
         .layer(LatentSparseDecoder(
             name=DECODER, d_model=d,
             n_heads=int(cfg["num_attention_heads"]),
             q_lora_rank=int(cfg["q_lora_rank"]),
             kv_lora_rank=int(cfg["kv_lora_rank"]),
             qk_nope_head_dim=int(cfg["qk_nope_head_dim"]),
             qk_rope_head_dim=int(cfg["qk_rope_head_dim"]),
             v_head_dim=int(cfg["v_head_dim"]),
             rope_theta=float(cfg["rope_parameters"]["rope_theta"]),
             rms_eps=float(cfg["rms_norm_eps"]),
             index_n_heads=int(cfg["index_n_heads"]),
             index_head_dim=int(cfg["index_head_dim"]),
             index_topk=int(cfg["index_topk"]),
             indexer_types=tuple(k for k, _ in kinds),
             mlp_types=tuple(m for _, m in kinds),
             d_ff=int(cfg["intermediate_size"]),
             moe_d_ff=int(cfg["moe_intermediate_size"]),
             n_routed=int(dep["n_routed_experts_published"]),
             top_k=int(cfg["num_experts_per_tok"]),
             routed_scale=float(cfg["routed_scaling_factor"]),
             held_first=int(dep["held_experts"][0]),
             n_held=int(cfg["n_routed_experts"])))
         .layer(ChunkedSoftmaxOutputLayer(
             n_out=vocab, chunk=int(cfg["system"]["vocab_chunk"]),
             has_bias=False, name=HEAD)))
    return SequentialModel(b.set_input_type(InputType.recurrent(1)).build())


def _init_tree(model, key, dtype):
    """The DSL's own draw (`init()` of a twin whose configuration carries
    `key`), the embedding rescaled to `EMBED_STD`, every leaf in `dtype`."""
    import dataclasses

    twin = type(model)(dataclasses.replace(model.conf, seed=key))
    twin.init()
    params = dict(twin.params)
    e = params[EMBED]["W"]
    params[EMBED] = {"W": e * (EMBED_STD / jnp.std(e))}
    return jax.tree.map(lambda a: a.astype(dtype), params)


def init_on_device(model, *, seed: int, optimizer_state: bool):
    """The weights on the device from `seed` in ONE jitted call, in
    bfloat16: a float32 tree of the configuration (15.5 GB) cannot exist on
    the chip beside its serving copy, so none is made.  Serving only:
    `optimizer_state` must be False."""
    if optimizer_state:
        raise ValueError("glm_moe_dsa is a serving configuration: it holds "
                         "bfloat16 weights and no optimizer state")
    init = jax.jit(lambda key: _init_tree(model, key, jnp.bfloat16))
    model.params = init(jax.random.key(int(seed)))
    model.net_state, model.opt_state = {}, None
    return model


def abstract_params(cfg: dict):
    """ShapeDtypeStructs of the parameter tree (for offline compiles)."""
    model = build_model(cfg)
    return jax.eval_shape(
        lambda key: _init_tree(model, key, jnp.bfloat16),
        jax.random.key(0))


# -- the plain reference ---------------------------------------------------------

_f32 = lambda a: jnp.asarray(a, jnp.float32)


def _rms(x, gamma, eps):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * _f32(gamma)


def _layer_norm(x, gamma, beta, eps):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, -1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * _f32(gamma) + _f32(beta)


def _rope(x, theta):
    """`_rope_at` for a whole sequence: row i is position i."""
    return _rope_at(x, jnp.arange(x.shape[0]), theta)


def _rope_at(x, positions, theta):
    """Interleaved pairs (x[2i], x[2i+1]) of the last axis rotated by
    position x theta^(-2i/r); x: (T, r) or (T, H, r)."""
    r = x.shape[-1]
    inv = theta ** (-jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    ang = positions.astype(jnp.float32)[:, None] * inv
    if x.ndim == 3:
        ang = ang[:, None, :]
    pairs = x.reshape(x.shape[:-1] + (r // 2, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    return jnp.stack([a * jnp.cos(ang) - b * jnp.sin(ang),
                      a * jnp.sin(ang) + b * jnp.cos(ang)], -1).reshape(x.shape)


def _gated(h, wg, wu, wd):
    return (jax.nn.silu(h @ _f32(wg)) * (h @ _f32(wu))) @ _f32(wd)


def _in_blocks(fn, block, *rows):
    t = rows[0].shape[0]
    if t <= block or t % block:
        return fn(*rows)
    out = lax.map(lambda a: fn(*a), tuple(
        r.reshape((t // block, block) + r.shape[1:]) for r in rows))
    return jax.tree.map(lambda o: o.reshape((t,) + o.shape[2:]), out)


def reference_selection(cfg: dict, h, c_q, ip):
    """The indexer of one full layer over a whole sequence: bool (T, T),
    row t marks S_t — the top min(index_topk, t + 1) of I[t, :t + 1],
    `lax.top_k` (ties to the lower index)."""
    t = h.shape[0]
    hi, di = int(cfg["index_n_heads"]), int(cfg["index_head_dim"])
    dr, eps = int(cfg["qk_rope_head_dim"]), float(cfg["rms_norm_eps"])
    theta = float(cfg["rope_parameters"]["rope_theta"])
    k = min(int(cfg["index_topk"]), t)
    rot = lambda x: jnp.concatenate(
        [_rope(x[..., :dr], theta), x[..., dr:]], -1)
    q_i = rot((c_q @ _f32(ip["Wq"])).reshape(t, hi, di))
    k_i = rot(_layer_norm(h @ _f32(ip["Wk"]), ip["k_gamma"], ip["k_beta"],
                          eps))
    w = (h @ _f32(ip["Ww"])) * (hi ** -0.5 * di ** -0.5)
    pos = jnp.arange(t)

    def select(qb, wb, pb):
        s = jnp.einsum("qhn,qh->qn",
                       jax.nn.relu(jnp.einsum("qhd,nd->qhn", qb, k_i)), wb)
        s = jnp.where(pos[None, :] <= pb[:, None], s, -jnp.inf)
        best, where = lax.top_k(s, k)
        rows = jnp.arange(s.shape[0])[:, None]
        return jnp.zeros(s.shape, bool).at[rows, where].set(best > -jnp.inf)

    return _in_blocks(select, QUERY_BLOCK, q_i, w, pos)


def _reference_moe(cfg: dict, h, f):
    """The held experts' part plus the shared expert, one expert at a time:
    every token through every held expert, weighted by its gate (zero where
    the token did not choose it).  Also, per token, which held experts it
    chose (T, held) and how far the nearest held expert's score lies from
    the top-k boundary (`ROUTER_TIE`): above the best score left out where
    it was chosen, below the last score taken where it was not."""
    first = int(deployment(cfg)["held_experts"][0])
    n_held = int(cfg["n_routed_experts"])
    k = int(cfg["num_experts_per_tok"])
    s = jax.nn.sigmoid(h @ _f32(f["router"]))
    v = s + _f32(f["router_bias"])
    best, ids = lax.top_k(v, k + 1)
    ids = ids[:, :k]
    chosen = jnp.take_along_axis(s, ids, -1)
    gates = float(cfg["routed_scaling_factor"]) * chosen / jnp.sum(
        chosen, -1, keepdims=True)
    held_ids = first + jnp.arange(n_held)
    took = jnp.any(ids[:, :, None] == held_ids, axis=1)          # (T, held)
    v_held = v[:, first:first + n_held]
    margin = jnp.min(jnp.where(took, v_held - best[:, k:],
                               best[:, k - 1:k] - v_held), axis=-1)

    def one(y, args):
        e, wg, wu, wd = args
        g = jnp.sum(jnp.where(ids == first + e, gates, 0.0), -1)
        return y + g[:, None] * _gated(h, wg, wu, wd), None

    ex = f["experts"]
    y, _ = lax.scan(one, jnp.zeros_like(h),
                    (jnp.arange(n_held), ex["Wg"], ex["Wu"], ex["Wd"]))
    sh = f["shared"]
    return y + _gated(h, sh["Wg"], sh["Wu"], sh["Wd"]), margin, took


def reference_hidden(cfg: dict, params, tokens, *, selections=None,
                     routing=None, rounding=None):
    """tokens (T,) int32 -> final hidden states (T, hidden) after norm_f,
    float32.  `selections`, a list, receives each full layer's (T, T)
    selection and `routing` each expert layer's (margin (T,), held experts
    chosen (T, held)) of `_reference_moe`.  `rounding`, a function of an
    array, is applied to the residual and to every normed activation — what
    a system that keeps them in a narrower type rounds (the control of the
    comparison: the reference in the precision below the system's)."""
    t = tokens.shape[0]
    h_ = int(cfg["num_attention_heads"])
    dn, dr = int(cfg["qk_nope_head_dim"]), int(cfg["qk_rope_head_dim"])
    dv, lk = int(cfg["v_head_dim"]), int(cfg["kv_lora_rank"])
    eps = float(cfg["rms_norm_eps"])
    theta = float(cfg["rope_parameters"]["rope_theta"])
    r = rounding or (lambda a: a)
    dec = params[DECODER]
    x = r(_f32(params[EMBED]["W"])[tokens])
    mask = None
    for name, (indexer, mlp) in zip(layer_names(cfg), layer_kinds(cfg)):
        p = dec[name]
        a, f = p["attn"], p["ffn"]
        # what every row needs of every other: keys, values, the selection
        h = r(_rms(x, p["norm1"], eps))
        c_q = r(_rms(h @ _f32(a["Wqa"]), a["q_norm"], eps))
        kv = h @ _f32(a["Wkva"])
        c_kv = r(_rms(kv[:, :lk], a["kv_norm"], eps))
        k_r = r(_rope(kv[:, lk:], theta))
        if indexer == "full":
            mask = reference_selection(cfg, h, c_q, p["indexer"])
            if selections is not None:
                selections.append(mask)
        per_head = (c_kv @ _f32(a["Wkvb"])).reshape(t, h_, dn + dv)
        k_nope, v = per_head[..., :dn], per_head[..., dn:]

        def rows(xb, cb, mb, pb):
            """The rest of the layer for a block of rows at positions pb."""
            q = (cb @ _f32(a["Wqb"])).reshape(-1, h_, dn + dr)
            q_r = _rope_at(q[..., dn:], pb, theta)
            s = (jnp.einsum("qhd,nhd->hqn", q[..., :dn], k_nope)
                 + jnp.einsum("qhd,nd->hqn", q_r, k_r)) / math.sqrt(dn + dr)
            w = jax.nn.softmax(jnp.where(mb[None], s, -jnp.inf), -1)
            o = jnp.einsum("hqn,nhd->qhd", w, v).reshape(-1, h_ * dv)
            xb = r(xb + r(o) @ _f32(a["Wo"]))
            hb = r(_rms(xb, p["norm2"], eps))
            if mlp == "dense":
                return r(xb + _gated(hb, f["Wg"], f["Wu"], f["Wd"])), ()
            y, margin, took = _reference_moe(cfg, hb, f)
            return r(xb + y), (margin, took)

        x, routed = _in_blocks(rows, QUERY_BLOCK, x, c_q, mask, jnp.arange(t))
        if routing is not None and routed:
            routing.append(routed)
    return _rms(x, dec["norm_f"], eps)


def near_tied(routing, tie: float):
    """bool (T,): rows where some expert layer's margin is under `tie`."""
    return functools.reduce(jnp.logical_or,
                            [margin < tie for margin, _ in routing])


def make_reference_gap(cfg: dict, *, tie: float = ROUTER_TIE):
    """jitted (params, tokens (T,), rows (R,), emitted (R,)) -> (gap (R,),
    max|logit|): at each position `rows[i]`, how far the logit of the token
    the system emitted next sits below the reference's arg-max logit; 0 at
    the rows the `ROUTER_TIE` rule leaves out."""
    def gap(params, tokens, rows, emitted):
        routing = []
        with jax.default_matmul_precision("highest"):
            z = reference_hidden(cfg, params, tokens,
                                 routing=routing)[rows] @ _f32(
                params[HEAD]["W"])
        chosen = jnp.take_along_axis(z, emitted[:, None], axis=-1)[:, 0]
        gaps = jnp.max(z, axis=-1) - chosen
        if routing and tie > 0:
            gaps = jnp.where(near_tied(routing, tie)[rows], 0.0, gaps)
        return gaps, jnp.max(jnp.abs(z))

    return jax.jit(gap)


def make_reference_logits(cfg: dict):
    """jitted (params, tokens (T,)) -> logits (T, vocab) float32."""
    def logits(params, tokens):
        with jax.default_matmul_precision("highest"):
            return reference_hidden(cfg, params, tokens) @ _f32(
                params[HEAD]["W"])

    return jax.jit(logits)


# -- analytic operation counts ---------------------------------------------------
#
# REQUIRED floating-point operations (a multiply and an add each), from the
# shapes: what the mathematics above asks for, whatever the program does.
# Attention counts the rows a query ATTENDS (min(t + 1, index_topk)), in
# the plain per-head form; the indexer counts the rows it scores (t + 1).

def _matrix_params(cfg: dict) -> dict:
    """Parameters of the matrices a token's row meets, by part."""
    d, h_ = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    dq, lk = int(cfg["q_lora_rank"]), int(cfg["kv_lora_rank"])
    dn, dr = int(cfg["qk_nope_head_dim"]), int(cfg["qk_rope_head_dim"])
    dv = int(cfg["v_head_dim"])
    hi, di = int(cfg["index_n_heads"]), int(cfg["index_head_dim"])
    f = int(cfg["moe_intermediate_size"])
    return {
        "attention": d * dq + dq * h_ * (dn + dr) + d * (lk + dr)
                     + lk * h_ * (dn + dv) + h_ * dv * d,
        "indexer": dq * hi * di + d * di + d * hi,
        "dense_ffn": 3 * d * int(cfg["intermediate_size"]),
        "expert": 3 * d * f,
        "router": d * int(deployment(cfg)["n_routed_experts_published"]),
    }


def token_matmul_flops(cfg: dict, held_share: float) -> float:
    """FLOPs of the weight matrices for one token through the layers that
    are run, the head left out: every layer's attention, a full layer's
    indexer, the dense FFN or router + shared expert + `held_share` x top-k
    routed experts (the share of a token's assignments that fall on experts
    held here: 1/16 when routing is even)."""
    m = _matrix_params(cfg)
    k = int(cfg["num_experts_per_tok"])
    total = 0.0
    for indexer, mlp in layer_kinds(cfg):
        total += m["attention"] + (m["indexer"] if indexer == "full" else 0)
        total += (m["dense_ffn"] if mlp == "dense" else
                  m["router"] + m["expert"] * (1 + k * held_share))
    return 2.0 * total


def _attention_flops(cfg: dict, contexts) -> float:
    """FLOPs of query rows at the contexts `contexts` (each row's own
    included), all layers: scores and values over the rows a query attends,
    and the indexers' scores over the rows they see."""
    contexts = np.asarray(contexts, np.float64)
    h_ = int(cfg["num_attention_heads"])
    dn, dr = int(cfg["qk_nope_head_dim"]), int(cfg["qk_rope_head_dim"])
    dv = int(cfg["v_head_dim"])
    hi, di = int(cfg["index_n_heads"]), int(cfg["index_head_dim"])
    kinds = layer_kinds(cfg)
    n_full = sum(ix == "full" for ix, _ in kinds)
    attended = np.minimum(contexts, int(cfg["index_topk"])).sum()
    return float(2.0 * len(kinds) * h_ * (dn + dr + dv) * attended
                 + 2.0 * n_full * hi * (di + 1) * contexts.sum())


def row_attention_flops(cfg: dict, context: int) -> float:
    """`_attention_flops` of one query row at a context of `context`."""
    return _attention_flops(cfg, [context])


def request_flops(cfg: dict, prompt_len: int, new_tokens: int,
                  held_share: float) -> float:
    """Required FLOPs of one completed request: `prompt_len + new_tokens -
    1` rows through the layers (the last token is never fed back), each
    attending at its own context, and `new_tokens` rows through the head."""
    rows = prompt_len + new_tokens - 1
    attention = _attention_flops(cfg, np.arange(1, rows + 1))
    head = 2.0 * int(cfg["hidden_size"]) * int(cfg["vocab_size"])
    return float(rows * token_matmul_flops(cfg, held_share) + attention
                 + new_tokens * head)
