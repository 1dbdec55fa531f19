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

`latent_block(cfg, lp, x, rows)` is the block; ``rows`` (`LatentRows`) is
the caller's side of it: the rows' positions and
``attend(cfg, q, latent, index, wkvb)``, which is handed the
layer, the rows' queries, the rows to cache, the indexer's ``(q_I, k_I, w)``
(None in a shared layer) and ``W_kvb``, and returns the attention output
``(n, heads x v_head_dim)``.  Scopes for the profile: ``mla``,
``dsa_index``, ``dsa_topk``, ``dsa_attend`` (and ``moe_route``,
``moe_experts`` in `ops/moe.py`).
"""

from __future__ import annotations

import collections

import jax
import jax.numpy as jnp
from jax import lax

from deeplearning4j_tpu.ops.moe import gated_ffn, moe_ffn

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


def rope(x, positions, theta):
    """Rotary embedding over interleaved pairs ``(x[2i], x[2i+1])`` of the
    last axis, at one position per leading row; x: (n, r) or (n, h, r)."""
    r = x.shape[-1]
    inv = theta ** (-jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    ang = positions.astype(jnp.float32)[:, None] * inv          # (n, r/2)
    if x.ndim == 3:
        ang = ang[:, None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    pairs = x.astype(jnp.float32).reshape(x.shape[:-1] + (r // 2, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    out = jnp.stack([a * cos - b * sin, a * sin + b * cos], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


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

def expand_latents(cfg, latents, wkvb):
    """Cached rows (n, >= latent_width: a pool may pad its rows) ->
    per-head keys and values, made at use: ``(k (n, H, nope + rope), v (n,
    H, v))``, the one rotary key repeated for every head so that a score
    is ONE product over the head's whole width (two products summed cost
    a second pass over the scores)."""
    dn, dv, lk = cfg.qk_nope_head_dim, cfg.v_head_dim, cfg.kv_lora_rank
    n, h_ = latents.shape[0], cfg.n_heads
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


def sequence_attend(block_q: int = 256):
    """`attend` for one whole sequence from position 0 with nothing cached:
    scores, selection and attention over the sequence's own rows, in
    blocks of ``block_q`` queries.  The selection of a full layer stays in
    the closure for the shared layers after it.  The DSL layer's `apply`."""
    carried = []

    def attend(cfg, q, latent, index, wkvb):
        pos = jnp.arange(q.shape[0])
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


def latent_block(cfg, lp, x, rows: LatentRows):
    """One decoder layer on rows x: (n, D) at ``rows.positions``:
    ``x += MLA(norm1 x)``, ``x += FFN(norm2 x)``."""
    attend, positions = rows.attend, rows.positions
    dt, eps = x.dtype, cfg.rms_eps
    n = x.shape[0]
    a = lp["attn"]
    dn, dr = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    with jax.named_scope("mla"):
        h = rms_norm(x, lp["norm1"], eps)
        c_q = rms_norm(h @ a["Wqa"].astype(dt), a["q_norm"], eps)
        q = (c_q @ a["Wqb"].astype(dt)).reshape(n, cfg.n_heads, dn + dr)
        q = jnp.concatenate(
            [q[..., :dn], rope(q[..., dn:], positions, cfg.rope_theta)], -1)
        kv = h @ a["Wkva"].astype(dt)
        lk = cfg.kv_lora_rank
        latent = jnp.concatenate(
            [rms_norm(kv[:, :lk], a["kv_norm"], eps),
             rope(kv[:, lk:], positions, cfg.rope_theta)], axis=-1)
    index = None
    if cfg.indexer == "full":
        with jax.named_scope("dsa_index"):
            ip = lp["indexer"]
            hi, di = cfg.index_n_heads, cfg.index_head_dim
            q_i = _rope_head((c_q @ ip["Wq"].astype(dt)).reshape(n, hi, di),
                             positions, cfg.rope_theta, dr)
            k_i = _rope_head(
                layer_norm(h @ ip["Wk"].astype(dt), ip["k_gamma"],
                           ip["k_beta"], eps),
                positions, cfg.rope_theta, dr)
            w = (h @ ip["Ww"].astype(dt)).astype(jnp.float32) * (
                hi ** -0.5 * di ** -0.5)
            index = (q_i, k_i, w)
    o = attend(cfg, q, latent, index, a["Wkvb"])
    x = x + o.astype(dt) @ a["Wo"].astype(dt)
    h = rms_norm(x, lp["norm2"], eps)
    f = lp["ffn"]
    if cfg.ffn == "dense":
        return x + gated_ffn(h, f["Wg"], f["Wu"], f["Wd"])
    y, counts = moe_ffn(h, f, first=cfg.held_first, top_k=cfg.top_k,
                        scale=cfg.routed_scale, count_rows=rows.count_rows,
                        row_tile=rows.moe_row_tile)
    if rows.counts_to is not None:
        rows.counts_to(cfg, counts)
    return x + y
