"""Latent (MLA) attention with a learned sparse selection (DSA), as ops.

The decoder block of the `glm_moe_dsa` family (DeepSeek-V3.2's attention
under GLM's names), written once for every caller — the DSL layer's
whole-sequence `apply`, and the serving engine's prefill chunks and decode
step through `ops/generation.block`:

- **MLA.**  ``c_q = norm(h W_qa)``, ``q = c_q W_qb`` split per head into a
  no-position part and a rotary part; ``[c_kv | k_r] = h W_kva``,
  ``c_kv = norm(c_kv)``, rotary on ``k_r``.  What a token CACHES is the
  row ``[c_kv | k_r]`` (`latent_width`), not per-head keys and values:
  those are ``c_kv W_kvb`` and can be made at use (`expand_latents`) or never
  made, with ``W_kvb`` folded into the query and the output
  (`attend_gathered`, "absorbed": same mathematics).
- **The indexer**, in layers whose ``indexer`` is ``"full"``: a small
  multi-head scorer ``I[t, j] = sum_h w[t, h] relu(q_I[t, h] . k_I[j])``
  over a cached 128-wide key per token; a query attends only its top
  ``index_topk`` rows by ``I`` (all of them while there are fewer).
  ``"shared"`` layers hold no indexer and attend the selection of the
  nearest full layer before them, which the caller's `attend` carries.
- The selection is EXACT: `topk_mask` finds each row's k-th largest score
  by counting over the bits of the float (32 compare-and-count passes, no
  sort) and breaks ties toward the lower index, as `lax.top_k` does.
  `kth_largest` is the same count, read back as a float: the serving
  sampler's top-k threshold over the vocabulary.

The block's other parts are data too (the `motif3` family's): ``indexer
"none"`` attends every row of a causal WINDOW (or the whole context) with
no selection; ``n_kv_groups`` makes ``W_kvb`` one key and value per group
of query heads (GDLA: a group's heads are attended together, `group_heads`,
and the decode step folds the group's ``W_UK`` into each head's query,
`absorb_queries`); noise heads take their share away from the signal heads
of their group at a per-token ``lambda``; an elementwise gate precedes
``W_o``; the FFN's activation may be PolyNorm; and the residual may be
several streams mixed by manifold-constrained hyper-connections
(`mhc_sublayer`: the rows between layers are then the streams side by side,
`first_rows` / `final_rows`).

`latent_block(cfg, lp, x, rows)` is the block; ``rows`` (`LatentRows`) is
the caller's side of it: the rows' positions and
``attend(cfg, q, latent, index, wkvb)``, which is handed the
layer, the rows' queries, the rows to cache, the indexer's ``(q_I, k_I, w)``
(None in a shared layer) and ``W_kvb``, and returns the attention output
``(n, heads x v_head_dim)``.  Scopes for the profile: ``mla``,
``dsa_index``, ``dsa_topk``, ``dsa_attend``, ``gdla_combine``, ``mhc`` (and
``moe_route``, ``moe_experts`` in `ops/moe.py`).
"""

from __future__ import annotations

import collections
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from deeplearning4j_tpu.ops.moe import PolyNorm, act_ffn, gated_ffn, moe_ffn

#: rows of the sorted expert assignments one grouped product takes in the
#: engine's programs (`moe_ffn`'s ``row_tile``)
MOE_ROW_TILE = 2048


def rms_norm(x, gamma, eps):
    xf = x.astype(jnp.float32)
    y = xf * lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * gamma.astype(jnp.float32)).astype(x.dtype)


def layer_norm(x, gamma, beta, eps):
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean((xf - mean) ** 2, axis=-1, keepdims=True)
    y = (xf - mean) * lax.rsqrt(var + eps)
    return (y * gamma.astype(jnp.float32)
            + beta.astype(jnp.float32)).astype(x.dtype)


def rope(x, positions, theta, inv=None):
    """Rotary embedding over interleaved pairs ``(x[2i], x[2i+1])`` of the
    last axis, at one position per leading row; x: (n, r) or (n, h, r).
    ``inv``: the pairs' frequencies, where they are not ``theta``'s own
    (`yarn_frequencies`)."""
    r = x.shape[-1]
    if inv is None:
        inv = theta ** (-jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    else:
        inv = jnp.asarray(inv, jnp.float32)
    ang = positions.astype(jnp.float32)[:, None] * inv          # (n, r/2)
    if x.ndim == 3:
        ang = ang[:, None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    pairs = x.astype(jnp.float32).reshape(x.shape[:-1] + (r // 2, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    out = jnp.stack([a * cos - b * sin, a * sin + b * cos], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def yarn_frequencies(r: int, theta: float, factor: float, original: int,
                     beta_fast: float, beta_slow: float) -> np.ndarray:
    """YaRN's rotary frequencies (the DeepSeek-V3 form, without its
    attention temperature): pairs that turn fewer than ``beta_slow`` times
    over ``original`` positions are slowed by ``factor``, those that turn
    more than ``beta_fast`` times keep theta's, and a linear ramp joins
    them.  -> (r / 2,) f32, made on the host."""
    base = theta ** (-np.arange(0, r, 2, dtype=np.float64) / r)

    def dim(turns):
        return r * math.log(original / (turns * 2 * math.pi)) / (
            2 * math.log(theta))

    low = max(math.floor(dim(beta_fast)), 0)
    high = min(math.ceil(dim(beta_slow)), r - 1)
    ramp = np.clip((np.arange(r // 2) - low) / max(high - low, 1e-3), 0, 1)
    return (base / factor * ramp + base * (1 - ramp)).astype(np.float32)


def layer_rope(cfg, x, positions):
    """Rotary positions of a layer: its window's theta in a window layer,
    YaRN's frequencies in a full layer of a stretched model, else theta's."""
    if cfg.window:
        return rope(x, positions, cfg.swa_rope_theta or cfg.rope_theta)
    if cfg.rope_factor > 1:
        return rope(x, positions, cfg.rope_theta, inv=yarn_frequencies(
            x.shape[-1], cfg.rope_theta, cfg.rope_factor, cfg.rope_original,
            cfg.rope_beta_fast, cfg.rope_beta_slow))
    return rope(x, positions, cfg.rope_theta)


def _rope_head(x, positions, theta, n_rot):
    """Rotary on the first ``n_rot`` dims of the last axis."""
    return jnp.concatenate(
        [rope(x[..., :n_rot], positions, theta), x[..., n_rot:]], axis=-1)


# -- the selection -------------------------------------------------------------

def _ordered_bits(scores):
    """f32 -> uint32 whose unsigned order is the floats' order."""
    u = lax.bitcast_convert_type(scores.astype(jnp.float32), jnp.uint32)
    return jnp.where(u >> 31 == 1, ~u, u | jnp.uint32(0x80000000))


def _kth_largest_key(key, k):
    """key (q, n) uint32, k an int or (q,) int32 -> (q,) uint32: per row
    the largest ``t`` with at least ``k`` keys ``>= t`` — the k-th largest
    key — built bit by bit from counts of ``key >= candidate`` (32
    compare-and-count passes, no sort); 0 where no candidate passes."""

    def bit(i, prefix):
        cand = prefix | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        n_ge = jnp.sum((key >= cand[:, None]).astype(jnp.int32), axis=-1)
        return jnp.where(n_ge >= k, cand, prefix)

    return lax.fori_loop(0, 32, bit, jnp.zeros(key.shape[0], jnp.uint32))


def kth_largest(x, k):
    """x (q, n) f32, k (q,) int32 in ``1..n`` -> (q,) f32: each row's k-th
    largest value, equal (``==``) to element ``k - 1`` of the row sorted
    in descending order, found without sorting."""
    thr = _kth_largest_key(_ordered_bits(x), k)
    u = jnp.where(thr >> 31 == 1, thr & jnp.uint32(0x7FFFFFFF), ~thr)
    return lax.bitcast_convert_type(u, jnp.float32)


def topk_mask(scores, valid, k: int):
    """scores, valid: (q, n) -> bool (q, n): per row the ``min(k, number
    valid)`` valid entries of largest score, ties to the lower index —
    the set `lax.top_k` returns, found without sorting: the k-th largest
    key is built bit by bit from counts of ``key >= candidate``."""
    with jax.named_scope("dsa_topk"):
        key = jnp.where(valid, _ordered_bits(lax.stop_gradient(scores)),
                        jnp.uint32(0))                 # no float maps to 0
        # fewer than k valid: no candidate passes, the threshold stays 0
        # and every valid entry is taken
        thr = _kth_largest_key(key, k)[:, None]
        above = key > thr
        tied = (key == thr) & valid
        room = k - jnp.sum(above.astype(jnp.int32), axis=-1, keepdims=True)

        def split_ties(_):
            rank = jnp.cumsum(tied.astype(jnp.int32), axis=-1)
            return above | (tied & (rank <= room))

        exact = jnp.all(jnp.sum(tied.astype(jnp.int32), axis=-1,
                                keepdims=True) <= room)
        return lax.cond(exact, lambda _: (above | tied) & valid,
                        lambda _: split_ties(None) & valid, None)


def index_scores(q_i, w, k_i):
    """``I[t, j] = sum_h w[t, h] relu(q_I[t, h] . k_I[j])``: q_i (q, hi,
    di), w (q, hi) f32, k_i (n, di) -> (q, n) f32, accumulated in f32."""
    s = jnp.einsum("qhd,nd->qhn", q_i, k_i.astype(q_i.dtype),
                   preferred_element_type=jnp.float32)
    return jnp.einsum("qhn,qh->qn", jax.nn.relu(s), w.astype(jnp.float32))


# -- attention over cached latent rows ------------------------------------------

def expand_latents(cfg, latents, wkvb, heads: int | None = None):
    """Cached rows (n, >= latent_width: a pool may pad its rows) ->
    per-head keys and values, made at use: ``(k (n, H, nope + rope), v (n,
    H, v))``, the one rotary key repeated for every head so that a score
    is ONE product over the head's whole width (two products summed cost
    a second pass over the scores).  ``heads``: the key/value heads
    ``W_kvb`` makes where they are not the query heads (`expand_groups`)."""
    dn, dv, lk = cfg.qk_nope_head_dim, cfg.v_head_dim, cfg.kv_lora_rank
    n, h_ = latents.shape[0], heads or cfg.n_heads
    with jax.named_scope("dsa_attend"):
        kv = (latents[:, :lk] @ wkvb.astype(latents.dtype)).reshape(
            n, h_, dn + dv)
        k_r = latents[:, None, lk:lk + cfg.qk_rope_head_dim]
        k = jnp.concatenate(
            [kv[..., :dn], jnp.broadcast_to(k_r, (n, h_, k_r.shape[-1]))],
            axis=-1)
    return k, kv[..., dn:]


def attend_expanded(cfg, q, k, v, mask):
    """Queries q (q, H, nope + rope) over ALL the rows of `expand_latents`
    under ``mask`` (q, n) — the plain form, fewest FLOPs per pair, the
    prefill's: the selection is a mask over dense scores, so no row is
    gathered per query.  Scores and softmax in f32.  -> (q, H * v)."""
    with jax.named_scope("dsa_attend"):
        s = jnp.einsum("qhd,nhd->hqn", q, k,
                       preferred_element_type=jnp.float32)
        s = jnp.where(mask[None], s * cfg.softmax_scale, -jnp.inf)
        p = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
        norm = jnp.sum(p, axis=-1, keepdims=True)               # (H, q, 1)
        o = jnp.einsum("hqn,nhd->qhd", p.astype(v.dtype), v,
                       preferred_element_type=jnp.float32)
        o = o / jnp.swapaxes(norm, 0, 1)
    return o.reshape(q.shape[0], -1).astype(q.dtype)


def blocked(fn, block: int, *rows):
    """``fn`` over blocks of ``block`` leading rows of every array of
    ``rows`` (the whole of them where ``block`` does not divide), results
    joined: bounds what ``fn`` materialises per call."""
    n = rows[0].shape[0]
    if n <= block or n % block:
        return fn(*rows)
    out = lax.map(lambda a: fn(*a), tuple(
        r.reshape((n // block, block) + r.shape[1:]) for r in rows))
    return out.reshape((n,) + out.shape[2:])


def attend_gathered(cfg, q, rows, valid, wkvb):
    """Each query q[i] (H, nope + rope) over ITS OWN gathered latent rows
    ``rows[i]`` (k, >= latent_width), ``valid[i]`` (k,): ``W_kvb`` is folded
    into the query and the output, so no per-head key or value is made
    (the decode step's form).  -> (q, H * v)."""
    dn, dv, lk = cfg.qk_nope_head_dim, cfg.v_head_dim, cfg.kv_lora_rank
    h_ = cfg.n_heads
    dt = rows.dtype
    with jax.named_scope("dsa_attend"):
        w = wkvb.astype(dt).reshape(lk, h_, dn + dv)
        q_lat = jnp.einsum("qhd,chd->qhc", q[..., :dn].astype(dt),
                           w[..., :dn]).astype(dt)
        s = (jnp.einsum("qhc,qkc->qhk", q_lat, rows[..., :lk],
                        preferred_element_type=jnp.float32)
             + jnp.einsum("qhd,qkd->qhk", q[..., dn:].astype(dt),
                          rows[..., lk:lk + cfg.qk_rope_head_dim],
                          preferred_element_type=jnp.float32))
        s = jnp.where(valid[:, None, :], s * cfg.softmax_scale, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        o_lat = jnp.einsum("qhk,qkc->qhc", p.astype(dt), rows[..., :lk],
                           preferred_element_type=jnp.float32)
        o = jnp.einsum("qhc,chd->qhd", o_lat.astype(dt), w[..., dn:],
                       preferred_element_type=jnp.float32)
    return o.reshape(q.shape[0], h_ * dv).astype(q.dtype)


# -- grouped keys and values (GDLA) ------------------------------------------------
#
# With ``n_kv_groups`` G < H, ``W_kvb`` makes ONE key and value per group
# from a cached row, and query head h reads the group of `group_heads`: the
# signal heads split evenly over the groups in order, the noise heads (one
# per group) follow them.  A group's heads are attended together, so the
# group's keys are made, or read, once for them all.

def group_heads(cfg, a):
    """(n, H, w) in head order -> (n, G, heads of a group, w): each group's
    signal heads, then its noise head."""
    n, w = a.shape[0], a.shape[-1]
    g, s = cfg.groups, cfg.signal_heads
    sig = a[:, :s].reshape(n, g, s // g, w)
    if not cfg.n_noise_heads:
        return sig
    return jnp.concatenate([sig, a[:, s:].reshape(n, g, 1, w)], axis=2)


def ungroup_heads(cfg, a):
    """The inverse of `group_heads`: (n, G, heads of a group, w) -> (n, H,
    w)."""
    n, w = a.shape[0], a.shape[-1]
    g, s = cfg.groups, cfg.signal_heads
    sig = a[:, :, :s // g].reshape(n, s, w)
    if not cfg.n_noise_heads:
        return sig
    return jnp.concatenate([sig, a[:, :, s // g:].reshape(n, g, w)], axis=1)


def expand_groups(cfg, latents, wkvb):
    """`expand_latents` for a grouped layer: ``(k (n, G, nope + rope), v
    (n, G, v))``, the rotary key repeated for every group."""
    return expand_latents(cfg, latents, wkvb, cfg.groups)


def attend_groups(cfg, q, k, v, mask):
    """Queries q (q, H, nope + rope) over the rows of `expand_groups` under
    ``mask`` (q, n), each head over its group's keys and values; scores
    and softmax in f32 -> (q, H * v) f32."""
    with jax.named_scope("dsa_attend"):
        qg = group_heads(cfg, q).astype(k.dtype)
        s = jnp.einsum("qgjd,ngd->gjqn", qg, k,
                       preferred_element_type=jnp.float32)
        s = jnp.where(mask[None, None], s * cfg.softmax_scale, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("gjqn,ngd->qgjd", p.astype(v.dtype), v,
                       preferred_element_type=jnp.float32)
    return ungroup_heads(cfg, o).reshape(q.shape[0], -1)


def absorb_queries(cfg, q, wkvb, dt):
    """q (n, H, nope + rope) -> (n, H, latent_width) in ``dt``: each head's
    no-position query folded through its group's key half of ``W_kvb``,
    then its rotary part, times the softmax scale — a score is then ONE
    product with a cached row (the decode step's absorbed form)."""
    dn, dv, lk = cfg.qk_nope_head_dim, cfg.v_head_dim, cfg.kv_lora_rank
    w = wkvb.astype(dt).reshape(lk, cfg.groups, dn + dv)
    with jax.named_scope("dsa_attend"):
        qg = group_heads(cfg, q)
        q_lat = jnp.einsum("ngjd,cgd->ngjc", qg[..., :dn].astype(dt),
                           w[..., :dn], preferred_element_type=jnp.float32)
        qc = jnp.concatenate([q_lat, qg[..., dn:].astype(jnp.float32)], -1)
    return ungroup_heads(cfg, qc * cfg.softmax_scale).astype(dt)


def attend_latent_rows(qc, rows, valid, lk: int):
    """Absorbed queries qc (n, H, w) over each query's own cached rows
    ``rows`` (n, k, >= w) where ``valid`` (n, k) -> the heads' outputs in
    latent space (n, H, lk) f32; every query needs a valid row."""
    w = qc.shape[-1]
    with jax.named_scope("dsa_attend"):
        s = jnp.einsum("nhc,nkc->nhk", qc, rows[..., :w],
                       preferred_element_type=jnp.float32)
        p = jax.nn.softmax(jnp.where(valid[:, None, :], s, -jnp.inf), -1)
        return jnp.einsum("nhk,nkc->nhc", p.astype(rows.dtype),
                          rows[..., :lk], preferred_element_type=jnp.float32)


def absorbed_values(cfg, o_lat, wkvb):
    """Latent outputs o_lat (n, H, lk) -> (n, H * v) f32: each head's
    through its group's value half of ``W_kvb``."""
    dn, dv, lk = cfg.qk_nope_head_dim, cfg.v_head_dim, cfg.kv_lora_rank
    dt = wkvb.dtype
    w = wkvb.reshape(lk, cfg.groups, dn + dv)[..., dn:]
    with jax.named_scope("dsa_attend"):
        o = jnp.einsum("ngjc,cgd->ngjd", group_heads(cfg, o_lat.astype(dt)),
                       w, preferred_element_type=jnp.float32)
    return ungroup_heads(cfg, o).reshape(o_lat.shape[0], -1)


def window_keep(q_pos, k_pos, window: int):
    """bool (nq, nk): key at or before the query and, with a ``window``,
    fewer than ``window`` positions back."""
    back = q_pos[:, None] - k_pos[None, :]
    keep = back >= 0
    return keep & (back < window) if window else keep


def sequence_attend(block_q: int = 256):
    """`attend` for one whole sequence from position 0 with nothing cached:
    scores, selection and attention over the sequence's own rows, in
    blocks of ``block_q`` queries.  The selection of a full layer stays in
    the closure for the shared layers after it.  The DSL layer's `apply`."""
    carried = []

    def attend(cfg, q, latent, index, wkvb):
        pos = jnp.arange(q.shape[0])
        if cfg.indexer == "none":
            keys = expand_groups(cfg, latent, wkvb)
            return blocked(
                lambda qb, pb: attend_groups(
                    cfg, qb, *keys, window_keep(pb, pos, cfg.window)),
                block_q, q, pos)
        if index is not None:
            q_i, k_i, w = index
            with jax.named_scope("dsa_index"):
                carried[:] = [blocked(
                    lambda qb, wb, pb: topk_mask(
                        index_scores(qb, wb, k_i),
                        pos[None, :] <= pb[:, None], cfg.index_topk),
                    block_q, q_i, w, pos)]
        keys = expand_latents(cfg, latent, wkvb)
        return blocked(lambda qb, mb: attend_expanded(cfg, qb, *keys, mb),
                       block_q, q, carried[0])

    return attend


# -- the block ---------------------------------------------------------------------

#: the caller's side of a latent block: its ``attend`` closure, the
#: ``positions`` (n,) of the rows it hands in and, for the expert layers,
#: which rows to count (``count_rows``, `moe_ffn`), where a layer's
#: assignment counts go (``counts_to(cfg, counts)``) and the grouped
#: product's ``moe_row_tile``
LatentRows = collections.namedtuple(
    "LatentRows", "attend positions count_rows counts_to moe_row_tile",
    defaults=(None, None, None))


def first_rows(dec, x):
    """The embedding's rows as the first layer takes them: copied into
    each residual stream, f32, where the decoder has several (``mhc``)."""
    if dec.residual != "mhc":
        return x
    return jnp.tile(x.astype(jnp.float32),
                    (1,) * (x.ndim - 1) + (dec.mhc_streams,))


def final_rows(dec, params, x):
    """After the last layer: the streams summed (``mhc``), then
    ``norm_f``; ``params`` the decoder's."""
    if dec.residual != "mhc":
        return rms_norm(x, params["norm_f"], dec.rms_eps)
    x = jnp.sum(x.reshape(x.shape[:-1] + (dec.mhc_streams, dec.d_model)),
                axis=-2)
    # the head multiplies in the type its weights are served in
    return rms_norm(x, params["norm_f"], dec.rms_eps).astype(
        params["norm_f"].dtype)


def sinkhorn(m, iters: int):
    """``iters`` rounds of rows then columns normalised to sum 1: m (..., S,
    S) positive -> a doubly stochastic matrix, nearly."""
    for _ in range(iters):
        m = m / jnp.sum(m, axis=-1, keepdims=True)
        m = m / jnp.sum(m, axis=-2, keepdims=True)
    return m


def mhc_coefficients(cfg, m, x):
    """The mixing of a sublayer from the streams x (n, S * D): ``x̄ =
    RMSNorm(x)``, ``[a_pre | a_post | a_res] = x̄ Phi`` at the highest
    precision, ``H_pre = sigmoid(alpha_0 a_pre + b)``, ``H_post = 2
    sigmoid(alpha_1 a_post + b)``, ``H_res = Sinkhorn(exp(alpha_2 a_res +
    b))`` -> (pre (n, S), post (n, S), res (n, S, S)), all f32."""
    s = cfg.mhc_streams
    f32 = lambda a: a.astype(jnp.float32)
    xb = rms_norm(f32(x), m["gamma"], cfg.rms_eps)
    a = jnp.dot(xb, f32(m["phi"]), precision=lax.Precision.HIGHEST)
    alpha, b = f32(m["alpha"]), f32(m["b"])
    z = jnp.concatenate([alpha[0] * a[:, :s], alpha[1] * a[:, s:2 * s],
                         alpha[2] * a[:, 2 * s:]], -1) + b
    res = sinkhorn(jnp.exp(z[:, 2 * s:]).reshape(-1, s, s), cfg.mhc_iters)
    return jax.nn.sigmoid(z[:, :s]), 2.0 * jax.nn.sigmoid(z[:, s:2 * s]), res


def mhc_sublayer(cfg, m, x, sublayer):
    """``X <- clamp(H_res X + H_post^T F(H_pre X))`` on rows x (n, S * D)
    f32, F = ``sublayer`` of (n, D) f32 -> (n, D) f32."""
    s, d = cfg.mhc_streams, cfg.d_model
    xs = x.reshape(-1, s, d)
    with jax.named_scope("mhc"):
        pre, post, res = mhc_coefficients(cfg, m, x)
        u = jnp.einsum("ns,nsd->nd", pre, xs)
    y = sublayer(u)
    with jax.named_scope("mhc"):
        out = (jnp.einsum("nij,njd->nid", res, xs)
               + post[:, :, None] * y[:, None, :])
        if cfg.hidden_clamp:
            out = jnp.clip(out, -cfg.hidden_clamp, cfg.hidden_clamp)
    return out.reshape(x.shape)


def _attention(cfg, lp, u, rows: LatentRows, dt, bt):
    """The attention sublayer on rows u (n, D): ``h = norm1 u``; the
    queries and the latent row as MLA makes them; the indexer's ``(q_I,
    k_I, w)`` in a ``full`` layer; ``rows.attend``; then, with noise heads,
    ``O_i -= lambda_i O_noise`` at ``lambda = sigmoid(h W_lam)`` per token
    and head, with the gate ``O *= sigmoid(h W_gate)``, and ``W_o``.
    Products take type ``dt`` and return ``bt``, the type of what lies
    between them -> (n, D) in ``bt``."""
    a = lp["attn"]
    eps, positions, n = cfg.rms_eps, rows.positions, u.shape[0]
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    mm = lambda x, w: jnp.dot(x.astype(dt), w.astype(dt),
                              preferred_element_type=bt)
    with jax.named_scope("mla"):
        h = rms_norm(u.astype(bt), lp["norm1"], eps).astype(dt)
        c_q = rms_norm(mm(h, a["Wqa"]), a["q_norm"], eps)
        q = mm(c_q, a["Wqb"]).reshape(n, cfg.n_heads, dn + dr)
        q = jnp.concatenate(
            [q[..., :dn], layer_rope(cfg, q[..., dn:], positions)],
            -1).astype(dt)
        kv = mm(h, a["Wkva"])
        lk = cfg.kv_lora_rank
        latent = jnp.concatenate(
            [rms_norm(kv[:, :lk], a["kv_norm"], eps),
             layer_rope(cfg, kv[:, lk:], positions)], axis=-1).astype(dt)
    index = None
    if cfg.indexer == "full":
        with jax.named_scope("dsa_index"):
            ip = lp["indexer"]
            hi, di = cfg.index_n_heads, cfg.index_head_dim
            q_i = _rope_head(mm(c_q, ip["Wq"]).reshape(n, hi, di),
                             positions, cfg.rope_theta, dr)
            k_i = _rope_head(
                layer_norm(mm(h, ip["Wk"]), ip["k_gamma"], ip["k_beta"],
                           eps),
                positions, cfg.rope_theta, dr)
            w = mm(h, ip["Ww"]).astype(jnp.float32) * (
                hi ** -0.5 * di ** -0.5)
            index = (q_i, k_i, w)
    o = rows.attend(cfg, q, latent, index, a["Wkvb"])
    if cfg.n_noise_heads or cfg.output_gate:
        hs = cfg.signal_heads
        with jax.named_scope("gdla_combine"):
            o = o.astype(jnp.float32).reshape(n, cfg.n_heads, dv)
            if cfg.n_noise_heads:
                lam = jax.nn.sigmoid(mm(h, a["W_lam"]))           # (n, hs)
                g = cfg.groups
                sig = o[:, :hs].reshape(n, g, hs // g, dv)
                noise = o[:, hs:].reshape(n, g, 1, dv)
                o = (sig - lam.reshape(n, g, hs // g, 1) * noise)
            o = o.reshape(n, hs * dv)
            if cfg.output_gate:
                o = o * jax.nn.sigmoid(mm(h, a["W_gate"]))
    return mm(o, a["Wo"])


def _ffn(cfg, lp, u, rows: LatentRows, dt, bt):
    """The FFN sublayer on rows u (n, D): ``h = norm2 u``, the dense FFN or
    the held experts plus the shared one, with the layer's activation ->
    (n, D) in ``bt``."""
    f = lp["ffn"]
    h = rms_norm(u.astype(bt), lp["norm2"], cfg.rms_eps).astype(dt)
    act = (PolyNorm(cfg.polynorm_scale, cfg.polynorm_clamp, cfg.rms_eps)
           if cfg.ffn_act == "polynorm" else None)
    if cfg.ffn == "dense":
        y = (act_ffn(h, f, act) if act is not None else
             gated_ffn(h, f["Wg"], f["Wu"], f["Wd"]))
        return y.astype(bt)
    y, counts = moe_ffn(h, f, first=cfg.held_first, top_k=cfg.top_k,
                        scale=cfg.routed_scale, count_rows=rows.count_rows,
                        row_tile=rows.moe_row_tile, act=act)
    if rows.counts_to is not None:
        rows.counts_to(cfg, counts)
    return y.astype(bt)


def latent_block(cfg, lp, x, rows: LatentRows):
    """One decoder layer on rows x at ``rows.positions``: the attention
    sublayer, then the FFN, each added to the rows (``plain``: x (n, D),
    computed in x's type) or mixed into the residual streams (``mhc``: x
    (n, S * D) f32, `mhc_sublayer`; the products take the matrices' type,
    what lies between them is f32)."""
    if cfg.residual == "mhc":
        dt, bt = lp["attn"]["Wqa"].dtype, jnp.float32
        x = mhc_sublayer(cfg, lp["mhc_attn"], x,
                         lambda u: _attention(cfg, lp, u, rows, dt, bt))
        return mhc_sublayer(cfg, lp["mhc_ffn"], x,
                            lambda u: _ffn(cfg, lp, u, rows, dt, bt))
    x = x + _attention(cfg, lp, x, rows, x.dtype, x.dtype)
    return x + _ffn(cfg, lp, x, rows, x.dtype, x.dtype)
