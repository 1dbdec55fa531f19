"""The decoder-hybrid-decoder of the `phi4flash` family (SambaY), as ops.

Written once for every caller — the DSL layer's whole-sequence `apply`
(`sequence_forward`), and the serving engine's prefill chunks and decode
step through `ops/generation.block`:

- **Mamba-1.**  ``[u; z] = h W_in``; ``u' = silu(conv1d_causal(u))`` (one
  filter of ``d_conv`` taps per channel, with a bias); ``[d; B; C] = u'
  W_x``; ``dt = softplus(d W_dt + b_dt)``; ``A = -exp(A_log)``; per position
  ``s = exp(dt A) * s + (dt u') B^T``, ``y = s C + D u'``; out ``= (y *
  silu(z)) W_out``.  The scan state and the recurrence are float32.  The
  tap layer's ``y * silu(z)`` is the MEMORY the gated memory units read.
- **Differential attention** with grouped keys and values: the query heads
  are pairs ``(Q_i0, Q_i1)``, the key heads pairs ``(K_j0, K_j1)`` and the
  value heads twice as wide, ``j = i // (pairs per key pair)``;
  ``O_i = softmax(Q_i0 K_j0^T / sqrt(hd)) V_j - lambda softmax(Q_i1 K_j1^T /
  sqrt(hd)) V_j``, then ``RMSNorm(O_i) * subln * (1 - lambda_init)``, with
  ``lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init`` and
  ``lambda_init = 0.8 - 0.6 exp(-0.3 l)``.
- **Gated memory unit**: ``(M * silu(h W_1)) W_2``.

`hybrid_block(cfg, lp, x, rows)` is one layer on rows x: (n, D); ``rows``
(`HybridRows`) is the caller's side of it: ``mamba(cfg, m, u)`` runs the
conv and the scan over the rows from the caller's state and returns ``y``
(n, d_inner) f32; ``attend(cfg, q, k, v)`` is handed the rows' query pairs
(n, P, 2, hd) and — in a window or the full layer — their key pairs (n, KP,
2, hd) and values (n, KP, 2 hd), keeps what it must and returns both
softmax-weighted sums of every query pair, (n, P, 2, 2 hd) f32; ``memory``
is a one-element list the tap layer fills and the gated memory units read.
Scopes for the profile: ``mamba``, ``diff_attn``, ``gmu``.
"""

from __future__ import annotations

import collections

import jax
import jax.numpy as jnp
from jax import lax

from deeplearning4j_tpu.nn.conf.hybrid import CONV, RING, SHARED_KV, SSM
from deeplearning4j_tpu.ops.latent import (
    blocked, layer_norm, rms_norm, window_keep,
)

HybridRows = collections.namedtuple("HybridRows", "mamba attend memory")

#: positions one turn of the prefill's scan runs, unrolled: the state is
#: read and written once a turn, not once a position
SCAN_ROWS = 16
#: query rows per block of a whole-sequence attention
QUERY_BLOCK = 256


def _mm(a, w, dt):
    """``a @ w`` in the activation type ``dt``, accumulated in f32."""
    return jnp.dot(a.astype(dt), w.astype(dt),
                   preferred_element_type=jnp.float32)


# -- Mamba-1 -----------------------------------------------------------------------

def mamba_conv(m, u, prev):
    """The causal depthwise conv over rows u: (n, E) of ONE stream, whose
    ``d_conv - 1`` inputs before them are ``prev``: -> (silu(conv) (n, E)
    f32, the inputs with ``prev`` first, (d_conv - 1 + n, E) f32)."""
    w = m["conv_w"].astype(jnp.float32)                         # (K, E)
    taps, n = w.shape[0], u.shape[0]
    ext = jnp.concatenate([prev.astype(jnp.float32),
                           u.astype(jnp.float32)])
    acc = m["conv_b"].astype(jnp.float32) + sum(
        w[k] * ext[k:k + n] for k in range(taps))
    return jax.nn.silu(acc), ext


def mamba_inputs(cfg, m, u2, dt):
    """u' (n, E) -> the scan's per-position inputs: time step (n, E), B and
    C (n, N), all f32."""
    r, n_state = cfg.dt_rank, cfg.d_state
    dbc = _mm(u2, m["W_x"], dt)
    step = jax.nn.softplus(_mm(dbc[:, :r], m["W_dt"], dt)
                           + m["b_dt"].astype(jnp.float32))
    return step, dbc[:, r:r + n_state], dbc[:, r + n_state:]


def scan_step(s, a, step, u2, b, c, d):
    """One position of the recurrence, over any leading axes: s (..., E,
    N); step, u2 (..., E); b, c (..., N); a (E, N); d (E,) -> (s', y)."""
    s = (jnp.exp(step[..., None] * a) * s
         + (step * u2)[..., None] * b[..., None, :])
    return s, jnp.sum(s * c[..., None, :], axis=-1) + d * u2


def scan_rows(a, d, step, u2, b, c, s0):
    """The recurrence over the rows of one stream from state ``s0`` (E, N):
    -> (y (n, E), the state after the last row).  A row whose step is 0
    leaves the state as it is (``exp(0) = 1``, nothing added): that is how
    a caller keeps pad rows out of it.  `SCAN_ROWS` positions a turn."""
    n = step.shape[0]
    per = SCAN_ROWS if n % SCAN_ROWS == 0 else 1

    def turn(s, blk):
        ys = []
        for r in range(per):
            s, y = scan_step(s, a, *(x[r] for x in blk), d)
            ys.append(y)
        return s, jnp.stack(ys)

    s, y = lax.scan(turn, s0, tuple(
        x.reshape((n // per, per) + x.shape[1:]) for x in (step, u2, b, c)))
    return y.reshape(n, -1), s


def ssm_constants(m):
    """(A, D) of a Mamba layer, f32."""
    return (-jnp.exp(m["A_log"].astype(jnp.float32)),
            m["D"].astype(jnp.float32))


# -- differential attention ----------------------------------------------------------

def attend_pairs(q, k, v, mask):
    """Query pairs q (nq, P, 2, hd) over key pairs k (nk, KP, 2, hd) and
    values v (nk, KP, 2 hd) under ``mask`` (nq, nk): both softmaxes of
    every pair, each weighting the values of its key pair -> (nq, P, 2,
    2 hd) f32.  Scores and softmax in f32; every query row needs a True in
    ``mask``."""
    nq, p, _, hd = q.shape
    kp = k.shape[1]
    with jax.named_scope("diff_attn"):
        qg = q.reshape(nq, kp, p // kp, 2, hd).astype(k.dtype)
        s = jnp.einsum("qkgpd,nkpd->kgpqn", qg, k,
                       preferred_element_type=jnp.float32) * hd ** -0.5
        w = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        o = jnp.einsum("kgpqn,nkd->qkgpd", w.astype(v.dtype), v,
                       preferred_element_type=jnp.float32)
    return o.reshape(nq, p, 2, 2 * hd)


def split_kv(rows, kp: int, hd: int):
    """(..., width) rows of keys then values (then padding) -> key pairs
    (..., kp, 2, hd) and values (..., kp, 2 hd)."""
    kw = kp * 2 * hd
    lead = rows.shape[:-1]
    return (rows[..., :kw].reshape(lead + (kp, 2, hd)),
            rows[..., kw:2 * kw].reshape(lead + (kp, 2 * hd)))


def diff_combine(cfg, m, o):
    """(n, P, 2, 2 hd) softmax-weighted sums -> (n, P * 2 hd) f32: the
    second softmax's share taken away at lambda, each head's RMSNorm."""
    lam = (jnp.exp(jnp.dot(m["lq1"].astype(jnp.float32),
                           m["lk1"].astype(jnp.float32)))
           - jnp.exp(jnp.dot(m["lq2"].astype(jnp.float32),
                             m["lk2"].astype(jnp.float32)))
           + cfg.lambda_init)
    with jax.named_scope("diff_attn"):
        o = o[:, :, 0] - lam * o[:, :, 1]
        o = rms_norm(o, m["subln"], cfg.eps) * (1.0 - cfg.lambda_init)
    return o.reshape(o.shape[0], -1)


# -- the block -----------------------------------------------------------------------

def hybrid_block(cfg, lp, x, rows: HybridRows):
    """One layer on rows x: (n, D): ``x += mixer(LN1 x)``, ``x +=
    SwiGLU(LN2 x)``.  Every product takes its operands in the matrices' own
    type (``dt``: bf16 as served on a TPU) and accumulates in f32; what lies
    between two products — the residual rows, the gates, the memory — stays
    f32, so each activation is rounded to ``dt`` once, where a product
    takes it.  On a twin of 32 layers at hidden 512 against the f32
    reference, rounding every product's output as well moved the logits by
    1.1 % of the largest |logit| (rms over the rows), rounding only the
    products' operands by 0.76 %."""
    dt, n = lp["ffn"]["W_in"].dtype, x.shape[0]
    x = x.astype(jnp.float32)
    m = lp["mixer"]
    h = layer_norm(x, lp["ln1"]["gamma"], lp["ln1"]["beta"],
                   cfg.eps).astype(dt)
    if cfg.kind == "mamba":
        e = cfg.d_inner
        with jax.named_scope("mamba"):
            uz = _mm(h, m["W_in"], dt)
            y = rows.mamba(cfg, m, uz[:, :e])
            mem = y * jax.nn.silu(uz[:, e:])
            if cfg.tap:
                rows.memory[:] = [mem]
            out = _mm(mem, m["W_out"], dt)
    elif cfg.kind == "gmu":
        with jax.named_scope("gmu"):
            gate = jax.nn.silu(_mm(h, m["W_1"], dt))
            out = _mm(rows.memory[0] * gate, m["W_2"], dt)
    else:
        p, kp, hd = cfg.n_heads // 2, cfg.n_kv_heads // 2, cfg.head_dim
        q = (h @ m["Wq"].astype(dt)).reshape(n, p, 2, hd)
        k = v = None
        if cfg.kind != "cross":
            k = (h @ m["Wk"].astype(dt)).reshape(n, kp, 2, hd)
            v = (h @ m["Wv"].astype(dt)).reshape(n, kp, 2 * hd)
        o = rows.attend(cfg, q, k, v)
        out = _mm(diff_combine(cfg, m, o), m["Wo"], dt)
    x = x + out
    f = lp["ffn"]
    h = layer_norm(x, lp["ln2"]["gamma"], lp["ln2"]["beta"],
                   cfg.eps).astype(dt)
    gu = _mm(h, f["W_in"], dt)
    g, u = gu[:, :cfg.d_ff], gu[:, cfg.d_ff:]
    return x + _mm(jax.nn.silu(g) * u, f["W_out"], dt)


def final_norm(dec, params, h):
    """``LN_f`` of the decoder's output rows."""
    nf = params[dec.name]["norm_f"]
    return layer_norm(h, nf["gamma"], nf["beta"], dec.eps)


def sequence_forward(dec, params, x):
    """The DSL layer's `apply` on ONE sequence x: (T, D) from position 0,
    nothing cached: every layer over every row -> ``LN_f`` of the last
    layer's output, (T, D)."""
    t = x.shape[0]
    pos = jnp.arange(t)
    full_kv = []

    def mamba(cfg, m, u):
        a, d = ssm_constants(m)
        u2, _ = mamba_conv(m, u, jnp.zeros((cfg.d_conv - 1, cfg.d_inner)))
        step, b, c = mamba_inputs(cfg, m, u2, x.dtype)
        return scan_rows(a, d, step, u2, b, c,
                         jnp.zeros((cfg.d_inner, cfg.d_state)))[0]

    def attend(cfg, q, k, v):
        if cfg.kind == "full":
            full_kv[:] = [(k, v)]
        elif cfg.kind == "cross":
            k, v = full_kv[0]
        span = cfg.window if cfg.kind == "swa" else t
        return blocked(
            lambda qb, pb: attend_pairs(qb, k, v,
                                        window_keep(pb, pos, span)),
            QUERY_BLOCK, q, pos)

    rows = HybridRows(mamba, attend, [])
    for cfg in dec.blocks():
        x = hybrid_block(cfg, params[cfg.path[-1]], x, rows)
    nf = params["norm_f"]
    return layer_norm(x, nf["gamma"], nf["beta"], dec.eps)


# -- one stream's state in the serving engine's pools ------------------------------
#
# The engine keeps a hybrid stack's state in named pools (`HybridBlock`'s
# `slot_rows` and `cache_rows` name them): per decode slot each Mamba
# layer's scan state (SSM) and last conv inputs (CONV) and each window
# layer's ring (RING), whose row ``p mod window`` holds position p; per
# position, in pages, the full layer's keys then values (SHARED_KV, one
# layer).  ``pools`` maps those names to the arrays, and the functions below
# write it in place; ``at`` maps a block's name to its layer in each pool.

def kv_rows(k, v, pool):
    """Keys and values of n rows -> (n, stored width) rows in ``pool``'s
    type: the keys, then the values, then zeros."""
    n = k.shape[0]
    rows = jnp.concatenate([k.reshape(n, -1), v.reshape(n, -1)], -1)
    return jnp.pad(rows.astype(pool.dtype),
                   ((0, 0), (0, pool.shape[-1] - rows.shape[-1])))


def prefill_rows(pools, at, slot, start, real, *, fresh: bool, dt,
                 query_block: int) -> HybridRows:
    """The self-decoder's side of a prefill chunk of ONE stream, in slot
    ``slot``: rows ``[start, start + n)`` of its prompt, of which the first
    ``real`` are the prompt's and the rest pad.  ``fresh`` (the first
    chunk): the state starts from zeros, which is the slot's reset;
    otherwise from what the chunk before left in the pools.  A pad row's
    time step is 0, so it leaves the scan as it is; the conv keeps the
    ``d_conv - 1`` inputs before the prompt's end; a window layer's ring
    keeps the last ``window`` prompt positions.  A window layer's rows
    attend the ring as the chunk before left it and the chunk's own keys,
    in blocks of ``query_block`` queries over their window alone."""

    def mamba(cfg, m, u):
        li, n = at[cfg.name], u.shape[0]
        if fresh:
            prev = jnp.zeros((cfg.d_conv - 1, cfg.d_inner))
            s0 = jnp.zeros((cfg.d_inner, cfg.d_state))
        else:
            prev = pools[CONV][li[CONV], slot]
            s0 = pools[SSM][li[SSM], slot]
        u2, ext = mamba_conv(m, u, prev)
        step, b, c = mamba_inputs(cfg, m, u2, dt)
        step = jnp.where((jnp.arange(n) < real)[:, None], step, 0.0)
        y, s1 = scan_rows(*ssm_constants(m), step, u2, b, c, s0)
        pools[CONV] = pools[CONV].at[li[CONV], slot].set(
            lax.dynamic_slice_in_dim(ext, real, cfg.d_conv - 1))
        pools[SSM] = pools[SSM].at[li[SSM], slot].set(s1)
        return y

    def attend(cfg, q, k, v):
        w, n = cfg.window, q.shape[0]
        kp, hd = cfg.n_kv_heads // 2, cfg.head_dim
        li = at[cfg.name][RING]
        ring = pools[RING][li, slot]                         # (w, width)
        rows = kv_rows(k, v, ring)
        kk, vv = split_kv(jnp.concatenate([ring, rows]), kp, hd)
        bq = query_block if n % query_block == 0 else n

        def one(bi):
            # query rows [bi bq, (bi + 1) bq) see keys of the ring and
            # chunk rows [bi bq, bi bq + bq + w) (ring first)
            first = bi * bq
            k_pos = start - w + first + jnp.arange(bq + w)
            mask = (window_keep(start + first + jnp.arange(bq), k_pos, w)
                    & (k_pos >= 0)[None, :])
            return attend_pairs(
                lax.dynamic_slice_in_dim(q, first, bq),
                lax.dynamic_slice_in_dim(kk, first, bq + w),
                lax.dynamic_slice_in_dim(vv, first, bq + w), mask)

        o = lax.map(one, jnp.arange(n // bq))
        # ring row r holds the last prompt position before the chunk's
        # real end that is r mod w
        e = start + real
        p_r = e - w + jnp.mod(jnp.arange(w) - e, w)
        pools[RING] = pools[RING].at[li, slot].set(jnp.where(
            (p_r >= start)[:, None], rows[jnp.clip(p_r - start, 0, n - 1)],
            ring))
        return o.reshape((n,) + o.shape[2:])

    return HybridRows(mamba, attend, [])


def full_kv_rows(cfg, lp, x, pool, dt):
    """The full layer's keys and values of rows x, as rows of the shared
    pool: what a prefill keeps of every prompt row (the layer's attention
    runs for the last row only)."""
    h = layer_norm(x, lp["ln1"]["gamma"], lp["ln1"]["beta"],
                   cfg.eps).astype(dt)
    m = lp["mixer"]
    return kv_rows(h @ m["Wk"].astype(dt), h @ m["Wv"].astype(dt), pool)


def context_rows(context, seen, memory) -> HybridRows:
    """The cross-decoder's side for rows that attend ``context`` (ctx,
    width), rows of the shared pool of which the first ``seen`` are the
    stream's: the full and cross layers attend them all (the full layer's
    own keys are among them), the gated memory units read ``memory``."""
    mask = (jnp.arange(context.shape[0]) < seen)[None, :]

    def attend(cfg, q, k, v):
        kc, vc = split_kv(context, cfg.n_kv_heads // 2, cfg.head_dim)
        return attend_pairs(q, kc, vc, mask)

    return HybridRows(None, attend, [memory])


def step_rows(pools, at, positions, page_of, row_of, read_shared,
              dt) -> HybridRows:
    """The decode step's side: one row per slot, at ``positions``.  A Mamba
    layer updates its slot's conv inputs and scan state in place; a window
    layer writes its ring row ``position mod window`` and attends the
    ring's first ``min(position + 1, window)`` rows; the full layer writes
    its row into the shared pool at ``(page_of, row_of)``, and it and every
    cross layer read that pool through ``read_shared(q, pool, key pairs)``
    -> (S, P, 2, 2 hd).  Every slot's state is written, an idle slot's
    too: garbage that the prefill which seats the next stream there writes
    over."""
    slots = jnp.arange(positions.shape[0])
    conv_rows = jax.vmap(mamba_conv, in_axes=(None, 0, 0))

    def mamba(cfg, m, u):
        li = at[cfg.name]
        conv, ssm = pools[CONV], pools[SSM]
        u2, ext = conv_rows(m, u[:, None], conv[li[CONV]])
        u2 = u2[:, 0]
        step, b, c = mamba_inputs(cfg, m, u2, dt)
        a, d = ssm_constants(m)
        s1, y = scan_step(ssm[li[SSM]], a, step, u2, b, c, d)
        pools[CONV] = conv.at[li[CONV]].set(ext[:, 1:])
        pools[SSM] = ssm.at[li[SSM]].set(s1)
        return y

    def attend(cfg, q, k, v):
        kp, hd = cfg.n_kv_heads // 2, cfg.head_dim
        if cfg.kind == "swa":
            w, li = cfg.window, at[cfg.name][RING]
            ring = pools[RING].at[li, slots, positions % w].set(
                kv_rows(k, v, pools[RING]))
            pools[RING] = ring
            kk, vv = split_kv(ring[li], kp, hd)
            live = (jnp.arange(w)[None, :]
                    < jnp.minimum(positions + 1, w)[:, None])
            return jax.vmap(lambda qs, ks, vs, ms: attend_pairs(
                qs[None], ks, vs, ms[None])[0])(q, kk, vv, live)
        if cfg.kind == "full":
            kv = pools[SHARED_KV]
            pools[SHARED_KV] = kv.at[0, page_of, row_of].set(
                kv_rows(k, v, kv))
        return read_shared(q, pools[SHARED_KV], kp)

    return HybridRows(mamba, attend, [])
