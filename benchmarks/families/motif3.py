"""Family `motif3`: the Motif-3 decoder — grouped differential attention over
latent keys and values (GDLA) with an elementwise output gate, a 128-row
window on three layers of every four, four residual streams mixed by
manifold-constrained hyper-connections (mHC), and PolyNorm experts behind a
sigmoid router with one shared expert — built with the system's config DSL
as ONE chip's share of an expert-parallel deployment, its plain float32
reference, and its analytic operation and byte counts.

The equations (X: one token's 4 residual streams of `hidden_size`; every
norm RMSNorm, no biases; layer l of `layer_kinds`):

    streams  X_0 = [e, e, e, e] (the embedding row e copied);  after the last
             layer: logits = RMSNorm(sum_i X_i) W_head
    mHC      per sublayer F (attention, then FFN):  xb = RMSNorm(vec X)
             [a_pre 4 | a_post 4 | a_res 16] = xb Phi
             H_pre = sigmoid(al_0 a_pre + b);  H_post = 2 sigmoid(al_1 a_post + b)
             H_res = Sinkhorn_20(exp(al_2 a_res + b)) (4 x 4: rows, then columns, 20 times)
             X <- clamp(H_res X + H_post^T F(H_pre X), +-hidden_clamp)
    GDLA     h = RMSNorm(u);  c_q = RMSNorm(h W_qa);  q = c_q W_qb -> 80 heads x (nope 128 | rope 64)
             [c_kv | k_r] = h W_kva;  c_kv = RMSNorm(c_kv);  rotary on q's rope part and k_r
             per group g of 16: [k_nope_g | v_g] = c_kv W_kvb,g;  k_g = [k_nope_g | k_r]
             signal head i < 64 reads group i // 4, noise head 64 + g reads group g
             O_j = softmax(q_j . k_g / sqrt(192) over keys j' <= t, and t - j' < 128 in a window layer) v_g
             lam = sigmoid(h W_lam) (64 a token);  O~_i = O_i - lam_i O_(64 + i // 4)
             out = ([O~_i] * sigmoid(h W_gate)) W_o
    rotary   full layers: YaRN frequencies (factor 64 over 4,096 positions, beta 32 / 1, no
             temperature); window layers: theta 10,000;  interleaved pairs (x[2i], x[2i+1])
    FFN      h = RMSNorm(u);  E(h) = (P(h W_g) * (h W_u)) W_d
             P(g) = 0.5 (w0 N(g^3) + w1 N(g^2) + w2 N(g) + clamp(b, +-0.5)), N: RMS over the row
             dense: E at 12,288;  experts: s = sigmoid(h W_r) over all 384;  top 8 of s + bias
             y = sum_{e chosen AND held here} 2 s_e / sum_chosen s E_e(h) + E_shared(h)

The reference below is those lines in `jax.numpy`, float32, "highest"
matmul precision, nothing imported from the package: no cache, no pages, no
slots, no batching.  It makes every row's latent row first, then runs the
attention sublayer over blocks of query rows and the FFN sublayer over
blocks of rows, writing each block's streams back in place — only up to the
last row asked for, since a causal model's later rows change nothing before
them; a window layer's block reads only its band of keys; the head runs in
blocks of the vocabulary; and every matrix is upcast where it is used, one
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
#: query rows a block of the reference's attention takes (its f32 scores
#: are 80 heads x this x the context: 0.35 GB at 33,792 rows)
QUERY_BLOCK = 32
#: rows a block of the reference's latent rows and FFN takes
ROW_BLOCK = 512
#: the largest block of vocabulary columns the reference's head takes
VOCAB_BLOCK = 8192
#: the embedding's rows are drawn at this standard deviation, as in the
#: `glm_moe_dsa` family: RMSNorm stands before every product and the head
#: is untied, so only the rows' size relative to what a layer adds matters
EMBED_STD = 1.0
#: leaves kept in float32 in the served tree: the mixing's scalars and
#: biases, and PolyNorm's weights and biases (under 0.1 MB in all); every
#: matrix and norm is bfloat16
F32_LEAVES = frozenset(("alpha", "b", "poly_w", "poly_b"))
#: a checked row is LEFT OUT of the comparison where, in the reference, a
#: held expert's routing score ``s + bias`` lies within this of the top-8
#: boundary in any expert layer: the `glm_moe_dsa` family's rule and value
#: (the top-8 choice is the model's one discrete step per layer, and
#: bfloat16 and float32 activations legitimately choose differently there)
ROUTER_TIE = 6e-3


# -- the configuration as run ------------------------------------------------------

def widths(cfg: dict) -> dict:
    d, h = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    dr = int(cfg["qk_rope_head_dim"])
    return {"d": d, "heads": h, "groups": int(cfg["num_key_value_heads"]),
            "noise": int(cfg["num_noise_heads"]),
            "signal": h - int(cfg["num_noise_heads"]),
            "dq": int(cfg["q_lora_rank"]), "lk": int(cfg["kv_lora_rank"]),
            "dn": int(cfg["head_dim"]) - dr, "dr": dr,
            "dv": int(cfg["v_head_dim"]),
            "ff": int(cfg["intermediate_size"]),
            "moe_ff": int(cfg["moe_intermediate_size"]),
            "routed": int(deployment(cfg)["num_experts_published"]),
            "held": int(cfg["num_experts"]),
            "first": int(deployment(cfg)["held_experts"][0]),
            "top_k": int(cfg["experts_top_k"]),
            "route_scale": float(cfg["route_scale"]),
            "streams": int(cfg["mhc_expansion_rate"]),
            "sinkhorn": int(cfg["mhc_sinkhorn_iters"]),
            "clamp": float(cfg["hidden_clamp"]),
            "window": int(cfg["sliding_window"]),
            "vocab": int(cfg["vocab_size"]),
            "eps": float(cfg["rms_norm_eps"]),
            "poly_scale": float(cfg["polynorm_output_scale"]),
            "poly_clamp": float(cfg["polynorm_bias_clamp"])}


def deployment(cfg: dict) -> dict:
    return cfg["deployment"]


def layer_kinds(cfg: dict) -> list[tuple[str, int]]:
    """(mlp kind, window) of each layer that is run, from its published
    index (`system.layers_run`): the first `n_dense_first_layers` of the
    run are dense, the rest experts; layer i is full (window 0) where
    ``i mod sliding_window_period`` is the period's last, else it has the
    window (the published interleave: 3 window layers, then a full one)."""
    run = cfg["system"]["layers_run"]
    assert len(run) == int(cfg["num_hidden_layers"])
    period = int(cfg["sliding_window_period"])
    n_dense = int(cfg["n_dense_first_layers"])
    return [("dense" if j < n_dense else "sparse",
             0 if i % period == period - 1 else int(cfg["sliding_window"]))
            for j, i in enumerate(run)]


def layer_names(cfg: dict) -> list[str]:
    return [f"layer{i:02d}" for i in range(int(cfg["num_hidden_layers"]))]


# -- the system under test -----------------------------------------------------

def build_model(cfg: dict):
    """An uninitialised `SequentialModel`: [Embedding, LatentSparseDecoder,
    ChunkedSoftmaxOutputLayer], through the public DSL."""
    from deeplearning4j_tpu.models.sequential import SequentialModel
    from deeplearning4j_tpu.nn.conf import (
        ChunkedSoftmaxOutputLayer, Embedding, InputType,
        LatentSparseDecoder, NeuralNetConfiguration,
    )
    from deeplearning4j_tpu.nn.weights import WeightInit

    w = widths(cfg)
    kinds = layer_kinds(cfg)
    rs = cfg["rope_scaling"]
    b = (NeuralNetConfiguration.builder()
         .seed(0)
         .weight_init(WeightInit.LECUN_NORMAL)
         .list()
         .layer(Embedding(n_in=w["vocab"], n_out=w["d"], name=EMBED))
         .layer(LatentSparseDecoder(
             name=DECODER, d_model=w["d"], n_heads=w["heads"],
             q_lora_rank=w["dq"], kv_lora_rank=w["lk"],
             qk_nope_head_dim=w["dn"], qk_rope_head_dim=w["dr"],
             v_head_dim=w["dv"], rope_theta=float(cfg["rope_theta"]),
             rms_eps=w["eps"],
             indexer_types=("none",) * len(kinds),
             mlp_types=tuple(m for m, _ in kinds),
             windows=tuple(win for _, win in kinds),
             d_ff=w["ff"], moe_d_ff=w["moe_ff"], n_routed=w["routed"],
             top_k=w["top_k"], routed_scale=w["route_scale"],
             held_first=w["first"], n_held=w["held"],
             n_kv_groups=w["groups"], n_noise_heads=w["noise"],
             output_gate=bool(cfg["elementwise_attn_output_gate"]),
             ffn_act="polynorm", polynorm_scale=w["poly_scale"],
             polynorm_clamp=w["poly_clamp"], residual="mhc",
             mhc_streams=w["streams"], mhc_iters=w["sinkhorn"],
             hidden_clamp=w["clamp"], rope_factor=float(rs["factor"]),
             rope_original=int(rs["original_max_position_embeddings"]),
             rope_beta_fast=float(rs["beta_fast"]),
             rope_beta_slow=float(rs["beta_slow"]),
             swa_rope_theta=float(cfg["swa_rope_theta"])))
         .layer(ChunkedSoftmaxOutputLayer(
             n_out=w["vocab"], chunk=int(cfg["system"]["vocab_chunk"]),
             has_bias=False, name=HEAD)))
    return SequentialModel(b.set_input_type(InputType.recurrent(1)).build())


def _init_tree(model, key, dtype):
    """The DSL's own draw (`init()` of a twin whose configuration carries
    `key`), the embedding rescaled to `EMBED_STD`, every leaf in `dtype`
    but `F32_LEAVES`."""
    import dataclasses

    twin = type(model)(dataclasses.replace(model.conf, seed=key))
    twin.init()
    params = dict(twin.params)
    e = params[EMBED]["W"]
    params[EMBED] = {"W": e * (EMBED_STD / jnp.std(e))}
    return jax.tree_util.tree_map_with_path(
        lambda path, a: a.astype(
            jnp.float32 if path[-1].key in F32_LEAVES else dtype), params)


def init_on_device(model, *, seed: int, optimizer_state: bool):
    """The weights on the device from `seed` in ONE jitted call, in
    bfloat16 (`F32_LEAVES` aside): a float32 tree of the cut (15.7 GB)
    cannot exist on the chip beside it.  Serving only."""
    if optimizer_state:
        raise ValueError("motif3 is a serving configuration: it holds "
                         "bfloat16 weights and no optimizer state")
    init = jax.jit(lambda key: _init_tree(model, key, jnp.bfloat16))
    model.params = init(jax.random.key(int(seed)))
    model.net_state, model.opt_state = {}, None
    return model


def abstract_params(cfg: dict):
    """ShapeDtypeStructs of the parameter tree (offline compiles and the
    parameter count)."""
    model = build_model(cfg)
    return jax.eval_shape(
        lambda key: _init_tree(model, key, jnp.bfloat16), jax.random.key(0))


def param_count(cfg: dict) -> int:
    return sum(int(np.prod(a.shape))
               for a in jax.tree.leaves(abstract_params(cfg)))


# -- the plain reference ---------------------------------------------------------

_f32 = lambda a: jnp.asarray(a, jnp.float32)


def _rms(x, gamma, eps):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * _f32(gamma)


def _frequencies(cfg: dict, window: int) -> np.ndarray:
    """The rotary pairs' frequencies of a layer: theta's own in a window
    layer; YaRN's in a full one (DeepSeek-V3's form, no temperature)."""
    r = int(cfg["qk_rope_head_dim"])
    if window:
        theta = float(cfg["swa_rope_theta"])
        return theta ** (-np.arange(0, r, 2, dtype=np.float64) / r)
    rs = cfg["rope_scaling"]
    theta, factor = float(cfg["rope_theta"]), float(rs["factor"])
    original = float(rs["original_max_position_embeddings"])
    base = theta ** (-np.arange(0, r, 2, dtype=np.float64) / r)

    def dim(turns):
        return r * math.log(original / (turns * 2 * math.pi)) / (
            2 * math.log(theta))

    low = max(math.floor(dim(float(rs["beta_fast"]))), 0)
    high = min(math.ceil(dim(float(rs["beta_slow"]))), r - 1)
    ramp = np.clip((np.arange(r // 2) - low) / max(high - low, 1e-3), 0, 1)
    return base / factor * ramp + base * (1 - ramp)


def _rope_at(x, positions, inv):
    """Interleaved pairs (x[2i], x[2i+1]) of the last axis rotated by
    position x inv[i]; x: (T, r) or (T, H, r)."""
    r = x.shape[-1]
    ang = positions.astype(jnp.float32)[:, None] * jnp.asarray(inv,
                                                               jnp.float32)
    if x.ndim == 3:
        ang = ang[:, None, :]
    pairs = x.reshape(x.shape[:-1] + (r // 2, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    return jnp.stack([a * jnp.cos(ang) - b * jnp.sin(ang),
                      a * jnp.sin(ang) + b * jnp.cos(ang)], -1).reshape(x.shape)


def _coefficients(w: dict, m, xb, identity: bool):
    """mHC's (H_pre, H_post, H_res) of rows xb (n, S * D)."""
    s = w["streams"]
    a = _rms(xb, m["gamma"], w["eps"]) @ _f32(m["phi"])
    al, b = _f32(m["alpha"]), _f32(m["b"])
    pre = jax.nn.sigmoid(al[0] * a[:, :s] + b[:s])
    post = 2.0 * jax.nn.sigmoid(al[1] * a[:, s:2 * s] + b[s:2 * s])
    res = jnp.exp(al[2] * a[:, 2 * s:] + b[2 * s:]).reshape(-1, s, s)
    for _ in range(w["sinkhorn"]):
        res = res / jnp.sum(res, -1, keepdims=True)
        res = res / jnp.sum(res, -2, keepdims=True)
    if identity:
        res = jnp.broadcast_to(jnp.eye(s), res.shape)
    return pre, post, res


def _mix(w: dict, xb, post, res, y):
    """X <- clamp(H_res X + H_post^T y) on rows xb (n, S * D)."""
    s, d = w["streams"], w["d"]
    xs = xb.reshape(-1, s, d)
    out = jnp.einsum("nij,njd->nid", res, xs) + post[:, :, None] * y[:, None]
    return jnp.clip(out, -w["clamp"], w["clamp"]).reshape(xb.shape)


def _poly(w: dict, g, pw, pb):
    n = lambda v: v * lax.rsqrt(jnp.mean(v * v, -1, keepdims=True) + w["eps"])
    pw, pb = _f32(pw), _f32(pb)
    return w["poly_scale"] * (pw[0] * n(g ** 3) + pw[1] * n(g ** 2)
                              + pw[2] * n(g)
                              + jnp.clip(pb, -w["poly_clamp"],
                                         w["poly_clamp"]))


def _expert(w: dict, h, f, r):
    """E(h) of one FFN's weights ``f`` (upcast here); ``r`` rounds the down
    product's operand."""
    g = _poly(w, h @ _f32(f["Wg"]), f["poly_w"], f["poly_b"])
    return r(g * (h @ _f32(f["Wu"]))) @ _f32(f["Wd"])


def _moe(w: dict, h, f, r):
    """The held experts' part plus the shared expert, one expert at a time:
    every row through every held expert, weighted by its gate (zero where
    the row did not choose it); and per row how far the nearest held
    expert's score lies from the top-8 boundary (`ROUTER_TIE`)."""
    first, held, k = w["first"], w["held"], w["top_k"]
    s = jax.nn.sigmoid(h @ _f32(f["router"]))
    v = s + _f32(f["router_bias"])
    best, ids = lax.top_k(v, k + 1)
    ids = ids[:, :k]
    chosen = jnp.take_along_axis(s, ids, -1)
    gates = w["route_scale"] * chosen / jnp.sum(chosen, -1, keepdims=True)
    took = jnp.any(ids[:, :, None] == first + jnp.arange(held), axis=1)
    v_held = v[:, first:first + held]
    margin = jnp.min(jnp.where(took, v_held - best[:, k:],
                               best[:, k - 1:k] - v_held), axis=-1)
    ex = f["experts"]

    def one(y, e):
        g = jnp.sum(jnp.where(ids == first + e, gates, 0.0), -1)
        fe = {name: a[e] for name, a in ex.items()}
        return y + g[:, None] * _expert(w, h, fe, r), None

    y, _ = lax.scan(one, jnp.zeros_like(h), jnp.arange(held))
    return y + _expert(w, h, f["shared"], r), margin


def reference_hidden(cfg: dict, params, tokens, *, upto=None, window=None,
                     mhc_identity: bool = False, no_lambda: bool = False,
                     rounding=None, routing=None):
    """tokens (T,) int32 -> RMSNorm(sum of the streams) after the last
    layer, (T, hidden) float32, exact for rows before ``upto`` (a traced
    count; all rows where None).  `routing`, a list, receives each expert
    layer's routing margin (T,).  The controls of the comparison: `window`
    replaces the window layers' window; `mhc_identity` takes H_res = I (no
    Sinkhorn, no mixing of the streams); `no_lambda` takes the noise heads'
    share away at lambda = 0; `rounding`, a function of an array, is applied
    to the streams and to every operand of a product — normed activations,
    queries, keys and values, attention weights and outputs, the FFN's
    hidden rows: what a system computing in that narrower type rounds."""
    w = widths(cfg)
    t = tokens.shape[0]
    s, d, h_, g_ = w["streams"], w["d"], w["heads"], w["groups"]
    dn, dr, dv, lk = w["dn"], w["dr"], w["dv"], w["lk"]
    sig = w["signal"]
    per = sig // g_
    r = rounding or (lambda a: a)
    dec = params[DECODER]
    x = r(jnp.tile(_f32(params[EMBED]["W"])[tokens], (1, s)))
    pos = jnp.arange(t)
    upto = t if upto is None else upto
    # blocks that tile the rows (a length they do not divide is one block)
    qb = QUERY_BLOCK if t % QUERY_BLOCK == 0 else t
    fb = ROW_BLOCK if t % ROW_BLOCK == 0 else t
    n_q = (upto + qb - 1) // qb
    n_f = (upto + fb - 1) // fb
    margins = []

    for name, (mlp, win) in zip(layer_names(cfg), layer_kinds(cfg)):
        p = dec[name]
        a, f = p["attn"], p["ffn"]
        if win and window is not None:
            win = window
        band = win and win + QUERY_BLOCK <= t and t % QUERY_BLOCK == 0
        inv = _frequencies(cfg, win)

        def sub_in(m, xb, norm):
            pre, post, res = _coefficients(w, m, xb, mhc_identity)
            u = jnp.einsum("ns,nsd->nd", pre, xb.reshape(-1, s, d))
            return r(_rms(u, norm, w["eps"])), post, res

        def latent(xb, pb):
            hb, _, _ = sub_in(p["mhc_attn"], xb, p["norm1"])
            kv = hb @ _f32(a["Wkva"])
            return (r(_rms(kv[:, :lk], a["kv_norm"], w["eps"])),
                    r(_rope_at(kv[:, lk:], pb, inv)))

        c_kv, k_r = _in_blocks(latent, fb, x, pos)
        kvg = r(c_kv @ _f32(a["Wkvb"])).reshape(t, g_, dn + dv)
        keys = (kvg[..., :dn], k_r, kvg[..., dn:])

        def attention(i, x):
            start = i * qb
            xb = lax.dynamic_slice_in_dim(x, start, qb)
            pb = start + jnp.arange(qb)
            hb, post, res = sub_in(p["mhc_attn"], xb, p["norm1"])
            c_q = r(_rms(hb @ _f32(a["Wqa"]), a["q_norm"], w["eps"]))
            q = (c_q @ _f32(a["Wqb"])).reshape(qb, h_, dn + dr)
            q = r(jnp.concatenate([q[..., :dn],
                                   _rope_at(q[..., dn:], pb, inv)], -1))
            # per group: its signal heads, then its noise head
            qg = jnp.concatenate([q[:, :sig].reshape(qb, g_, per, dn + dr),
                                  q[:, sig:].reshape(qb, g_, 1, dn + dr)], 2)
            if band:      # the block's band: the window before it, then it
                k0 = jnp.maximum(start - win, 0)
                kn, kr, vv = (lax.dynamic_slice_in_dim(k, k0, win + qb)
                              for k in keys)
                kp = k0 + jnp.arange(win + qb)
            else:
                (kn, kr, vv), kp = keys, pos
            sc = (jnp.einsum("qgjd,ngd->gjqn", qg[..., :dn], kn)
                  + jnp.einsum("qgjd,nd->gjqn", qg[..., dn:], kr)) / math.sqrt(
                dn + dr)
            back = pb[:, None] - kp[None, :]
            keep = (back >= 0) & (kp >= 0)[None, :]
            if win:
                keep = keep & (back < win)
            wts = r(jax.nn.softmax(jnp.where(keep, sc, -jnp.inf), -1))
            o = r(jnp.einsum("gjqn,ngd->qgjd", wts, vv))    # (qb, G, per+1)
            lam = jax.nn.sigmoid(hb @ _f32(a["W_lam"])).reshape(qb, g_, per)
            if no_lambda:
                lam = jnp.zeros_like(lam)
            od = (o[:, :, :per] - lam[..., None] * o[:, :, per:]).reshape(
                qb, sig * dv)
            od = od * jax.nn.sigmoid(hb @ _f32(a["W_gate"]))
            y = r(od) @ _f32(a["Wo"])
            return lax.dynamic_update_slice_in_dim(
                x, r(_mix(w, xb, post, res, y)), start, 0)

        x = lax.fori_loop(0, n_q, attention, x)

        def ffn(i, carry):
            x, margin = carry
            start = i * fb
            xb = lax.dynamic_slice_in_dim(x, start, fb)
            hb, post, res = sub_in(p["mhc_ffn"], xb, p["norm2"])
            if mlp == "dense":
                y, mg = _expert(w, hb, f, r), jnp.full((fb,), jnp.inf)
            else:
                y, mg = _moe(w, hb, f, r)
            return (lax.dynamic_update_slice_in_dim(
                        x, r(_mix(w, xb, post, res, y)), start, 0),
                    lax.dynamic_update_slice_in_dim(margin, mg, start, 0))

        x, margin = lax.fori_loop(0, n_f, ffn,
                                  (x, jnp.full((t,), jnp.inf)))
        if mlp == "sparse":
            margins.append(margin)
    if routing is not None:
        routing.extend(margins)
    xs = jnp.sum(x.reshape(t, s, d), axis=1)
    return r(_rms(xs, dec["norm_f"], w["eps"]))


def _in_blocks(fn, block, *rows):
    t = rows[0].shape[0]
    if t <= block or t % block:
        return fn(*rows)
    out = lax.map(lambda a: fn(*a), tuple(
        r.reshape((t // block, block) + r.shape[1:]) for r in rows))
    return jax.tree.map(lambda o: o.reshape((t,) + o.shape[2:]), out)


def round_fp8(a):
    """a rounded to float8 e4m3 (4 significand bits to bfloat16's 8),
    saturated at its largest finite value, and back to float32: the type
    below the configuration's."""
    top = float(jnp.finfo(jnp.float8_e4m3fn).max)
    return jnp.clip(a, -top, top).astype(jnp.float8_e4m3fn).astype(
        jnp.float32)


def _vocab_block(vocab: int) -> int:
    """The largest divisor of the vocabulary up to `VOCAB_BLOCK`."""
    return max(b for b in range(1, min(vocab, VOCAB_BLOCK) + 1)
               if vocab % b == 0)


def _head_gaps(head, hidden, emitted):
    """Over blocks of the vocabulary (head (hidden, vocab)): each row's
    largest logit, the logit of its emitted token, the largest |logit|."""
    vocab = head.shape[1]
    blk = _vocab_block(vocab)

    def one(carry, at):
        best, chosen, top = carry
        z = hidden @ _f32(lax.dynamic_slice_in_dim(head, at, blk, axis=1))
        here = (emitted >= at) & (emitted < at + blk)
        picked = jnp.take_along_axis(
            z, jnp.clip(emitted - at, 0, blk - 1)[:, None], -1)[:, 0]
        return (jnp.maximum(best, z.max(-1)),
                jnp.where(here, picked, chosen),
                jnp.maximum(top, jnp.abs(z).max())), None

    n = hidden.shape[0]
    (best, chosen, top), _ = lax.scan(
        one, (jnp.full((n,), -jnp.inf), jnp.zeros((n,)), jnp.zeros(())),
        jnp.arange(0, vocab, blk))
    return best, chosen, top


def near_tied(routing, tie: float):
    """bool (T,): rows where some expert layer's margin is under `tie`."""
    return functools.reduce(jnp.logical_or, [m < tie for m in routing])


def make_reference_gap(cfg: dict, *, tie: float = ROUTER_TIE, **controls):
    """jitted (params, tokens (T,), rows (R,), emitted (R,)) -> (gap (R,),
    max|logit|): at each position `rows[i]`, how far the logit of the token
    the system emitted next sits below the reference's arg-max logit; 0 at
    the rows the `ROUTER_TIE` rule leaves out.  `controls` go to
    `reference_hidden` (window, mhc_identity, no_lambda, rounding)."""
    def gap(params, tokens, rows, emitted):
        routing = []
        with jax.default_matmul_precision("highest"):
            hidden = reference_hidden(cfg, params, tokens,
                                      upto=jnp.max(rows) + 1,
                                      routing=routing, **controls)[rows]
            best, chosen, top = _head_gaps(params[HEAD]["W"], hidden,
                                           emitted)
        gaps = best - chosen
        if routing and tie > 0:
            gaps = jnp.where(near_tied(routing, tie)[rows], 0.0, gaps)
        return gaps, top

    return jax.jit(gap)


def make_reference_logits(cfg: dict, **controls):
    """jitted (params, tokens (T,)) -> logits (T, vocab) float32 (small
    vocabularies: the whole head at once)."""
    def logits(params, tokens):
        with jax.default_matmul_precision("highest"):
            return reference_hidden(cfg, params, tokens, **controls) @ _f32(
                params[HEAD]["W"])

    return jax.jit(logits)


# -- analytic operation and byte counts ------------------------------------------
#
# REQUIRED floating-point operations (a multiply and an add each), from the
# shapes: what the mathematics above asks for, whatever the program does.
# Attention counts the keys a query attends (its context, or at most the
# window) in the plain per-head form: a score over nope + rope, a weighted
# value row.

def _matrix_params(cfg: dict) -> dict:
    """Parameters of the matrices a token's row meets, by part."""
    w = widths(cfg)
    d, h_, sig = w["d"], w["heads"], w["signal"]
    mix = w["streams"] * d * w["streams"] * (2 + w["streams"])
    return {
        "attention": (d * w["dq"] + w["dq"] * h_ * (w["dn"] + w["dr"])
                      + d * (w["lk"] + w["dr"])
                      + w["lk"] * w["groups"] * (w["dn"] + w["dv"])
                      + d * sig + d * sig * w["dv"] + sig * w["dv"] * d),
        "mhc": 2 * mix,
        "dense_ffn": 3 * d * w["ff"],
        "expert": 3 * d * w["moe_ff"],
        "router": d * w["routed"],
    }


def token_matmul_flops(cfg: dict, held_share: float) -> float:
    """FLOPs of the weight matrices for one token through the layers that
    are run, the head left out: every layer's attention and mixing, the
    dense FFN or router + shared expert + `held_share` x top-k routed
    experts (the share of a token's assignments that fall on experts held
    here: 1/8 when routing is even)."""
    m = _matrix_params(cfg)
    k = widths(cfg)["top_k"]
    total = 0.0
    for mlp, _ in layer_kinds(cfg):
        total += m["attention"] + m["mhc"]
        total += (m["dense_ffn"] if mlp == "dense" else
                  m["router"] + m["expert"] * (1 + k * held_share))
    return 2.0 * total


def _attention_flops(cfg: dict, contexts) -> float:
    """FLOPs of query rows at the contexts `contexts` (each row's own
    included), all layers: each head's scores (nope + rope) and weighted
    values over the keys it attends."""
    w = widths(cfg)
    ctx = np.asarray(contexts, np.float64)
    per_key = 2.0 * w["heads"] * (w["dn"] + w["dr"] + w["dv"])
    return float(sum(per_key * (np.minimum(ctx, win) if win else ctx).sum()
                     for _, win in layer_kinds(cfg)))


def request_flops(cfg: dict, prompt_len: int, new_tokens: int,
                  held_share: float) -> float:
    """Required FLOPs of one completed request: `prompt_len + new_tokens -
    1` rows through the layers (the last token is never fed back), each
    attending at its own context, and `new_tokens` rows through the head."""
    rows = prompt_len + new_tokens - 1
    attention = _attention_flops(cfg, np.arange(1, rows + 1))
    head = 2.0 * widths(cfg)["d"] * widths(cfg)["vocab"]
    return float(rows * token_matmul_flops(cfg, held_share) + attention
                 + new_tokens * head)


def full_layers(cfg: dict) -> int:
    """Layers that attend the whole context: the ``latent_paged_attn``
    calls of a decode step."""
    return sum(not win for _, win in layer_kinds(cfg))


def latent_paged_attn_bytes(cfg: dict, rows: float, slots: int,
                            elem_bytes: int = 2) -> float:
    """Bytes one ``latent_paged_attn`` call must move for `rows` context
    rows summed over its `slots` slots: each row's latent row (kv_lora_rank
    + rope, bf16) read once, each slot's absorbed queries (heads x the same
    width, bf16) read and its latent outputs (heads x kv_lora_rank, f32)
    written."""
    w = widths(cfg)
    width = w["lk"] + w["dr"]
    return float(rows * width * elem_bytes
                 + slots * w["heads"] * (width * elem_bytes + w["lk"] * 4))


def latent_paged_attn_flops(cfg: dict, rows: float) -> float:
    """FLOPs of the same call: per row and head a score over the latent
    width and a weighted latent row."""
    w = widths(cfg)
    return float(2.0 * rows * w["heads"] * (w["lk"] + w["dr"] + w["lk"]))
