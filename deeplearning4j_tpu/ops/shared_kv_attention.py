"""Decode-step differential attention over the ONE shared K/V pool of a
hybrid (`phi4flash`) stack — the kernel ``shared_kv_attn``.

The stack keeps full-context keys and values for one layer only; that layer
and every cross-attention layer after it read them.  The pool is a row pool
of `serving/kv_cache.py` (one layer): position ``p`` of slot ``s`` is row
``p % page_size`` of page ``page_tbl[s, p // page_size]``, and a row is the
position's key pairs ``(KP, 2, hd)`` followed by its values ``(KP, 2 hd)``.
One query row per slot: its query pairs ``(P, 2, hd)``, each pair attending
its key pair's two halves with two softmaxes over the slot's first
``lens[s]`` rows and weighting the pair's values with each:

    out[s, i, h] = softmax_n(q[s, i, h] . k[n, j, h] / sqrt(hd)) @ v[n, j]
    j = i // (P / KP)

-> (S, P, 2, 2 hd) f32; the caller combines the two with lambda.

- ``xla`` — gather-then-attend (`ops/hybrid.attend_pairs` per slot): the CPU
  path, and the reference of the kernel.
- ``pallas`` — grid ``(slots,)``; a slot's program loops over its LIVE
  pages, several a turn, copying each turn's pages from the pool in HBM
  into VMEM itself (the next turn's in flight while this one is attended),
  as ``paged_attn`` does: an idle slot (length 0) fetches nothing, a page
  past the last live one is never read.  Per key pair the turn is two
  matrix products on the MXU: the pair's queries (``2 P/KP`` rows, padded
  to `_QUERY_ROWS`, each holding its query in the half of the row its key
  half sits in and zeros in the other) against the turn's ``(rows, 2 hd)``
  keys, and the softmax weights against its ``(rows, 2 hd)`` values.  The
  online softmax is updated once a turn; rows past the length are taken
  out by selects on the scores and on the value rows.

Selection (``impl=None``): ``pallas`` on a TPU, ``xla`` elsewhere (the
kernel runs on the CPU with ``interpret=True`` in its tests).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deeplearning4j_tpu.ops.hybrid import attend_pairs, split_kv

#: finite stand-in for -inf inside the kernel (see ops/paged_attention.py)
_MASK = -1e30
#: query rows of one key pair's product: a pair's 2 P/KP queries padded
#: to a whole bf16 tile
_QUERY_ROWS = 16
#: pool rows one loop turn attends where the shapes allow
_TURN_ROWS = 256
#: VMEM the page buffers may take (two turns)
_PAGE_BUFFER_BYTES = 8 << 20


def _xla(q, pool, page_tbl, lens, kp):
    s, mp = page_tbl.shape
    ps = pool.shape[2]
    hd = q.shape[-1]
    rows = pool[0][page_tbl].reshape(s, mp * ps, -1)
    k, v = split_kv(rows, kp, hd)
    live = jnp.arange(mp * ps)[None, :] < jnp.maximum(lens, 1)[:, None]
    out = jax.vmap(lambda qs, ks, vs, ms: attend_pairs(
        qs[None], ks, vs, ms[None])[0])(q, k, v, live)
    return jnp.where((lens > 0)[:, None, None, None], out, 0.0)


def _pages_per_turn(page_size: int, page_bytes: int, n_pages: int) -> int:
    want = -(-_TURN_ROWS // page_size)
    fit = _PAGE_BUFFER_BYTES // (2 * page_bytes)
    return max(1, min(want, fit, n_pages))


def _kernel(tbl_ref, len_ref, q_ref, pool_hbm, o_ref, buf, sem, *,
            page_size: int, group: int, kp: int, dv: int):
    s = pl.program_id(0)
    turn_rows = group * page_size
    length = len_ref[s]
    n_live = jax.lax.div(length + (page_size - 1), page_size)
    n_turns = jax.lax.div(n_live + (group - 1), group)

    def each_live_page(t, b, do):
        for g in range(group):
            @pl.when(t * group + g < n_live)
            def _(g=g):
                do(pltpu.make_async_copy(
                    pool_hbm.at[0, tbl_ref[s, t * group + g]],
                    buf.at[b, pl.ds(g * page_size, page_size)], sem.at[b]))

    @pl.when(n_turns > 0)
    def _first():
        each_live_page(0, 0, lambda copy: copy.start())

    def turn(t, carry):
        b = jax.lax.rem(t, 2)

        @pl.when(t + 1 < n_turns)
        def _next():
            each_live_page(t + 1, 1 - b, lambda copy: copy.start())

        each_live_page(t, b, lambda copy: copy.wait())
        base = t * turn_rows
        col_live = (jax.lax.broadcasted_iota(jnp.int32, (1, turn_rows), 1)
                    + base) < length
        row_live = (jax.lax.broadcasted_iota(jnp.int32, (turn_rows, 1), 0)
                    + base) < length
        out = []
        for j in range(kp):
            m, ell, acc = carry[j]
            k = buf[b, :, pl.ds(j * dv, dv)]                    # (R, 2 hd)
            v = jnp.where(row_live, buf[b, :, pl.ds((kp + j) * dv, dv)],
                          jnp.zeros((), buf.dtype))
            sc = jax.lax.dot_general(
                q_ref[0, j], k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)             # (Q, R)
            sc = jnp.where(col_live, sc, _MASK)
            m_new = jnp.maximum(m, jnp.max(sc, axis=1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(sc - m_new)
            ell = ell * alpha + jnp.sum(p, axis=1, keepdims=True)
            acc = acc * alpha + jnp.dot(p.astype(v.dtype), v,
                                        preferred_element_type=jnp.float32)
            out.append((m_new, ell, acc))
        return tuple(out)

    q_rows = q_ref.shape[2]
    init = tuple((jnp.full((q_rows, 1), _MASK, jnp.float32),
                  jnp.zeros((q_rows, 1), jnp.float32),
                  jnp.zeros((q_rows, dv), jnp.float32)) for _ in range(kp))
    final = jax.lax.fori_loop(0, n_turns, turn, init)
    for j, (_, ell, acc) in enumerate(final):
        o_ref[0, j] = acc / jnp.where(ell > 0.0, ell, 1.0)


def _query_rows(q, kp, dtype):
    """q (S, P, 2, hd) -> (S, KP, `_QUERY_ROWS`, 2 hd): per key pair its
    queries, pair-major then half, each in its key half of a 2 hd row and
    scaled by 1 / sqrt(hd); rows past 2 P / KP are zero."""
    s, p, _, hd = q.shape
    g = p // kp
    q = q.astype(jnp.float32).reshape(s, kp, g, 2, hd) * hd ** -0.5
    z = jnp.zeros_like(q[..., 0, :])
    rows = jnp.stack([jnp.concatenate([q[..., 0, :], z], -1),
                      jnp.concatenate([z, q[..., 1, :]], -1)], axis=3)
    rows = rows.reshape(s, kp, 2 * g, 2 * hd)
    return jnp.pad(rows, ((0, 0), (0, 0), (0, _QUERY_ROWS - 2 * g),
                          (0, 0))).astype(dtype)


@functools.partial(jax.jit, static_argnames=("kp", "interpret"))
def _pallas(q, pool, page_tbl, lens, *, kp: int, interpret: bool):
    s, p, _, hd = q.shape
    dv = 2 * hd
    if 2 * p // kp > _QUERY_ROWS:
        raise ValueError(f"{2 * p // kp} queries per key pair: the kernel "
                         f"takes at most {_QUERY_ROWS}")
    n_pages = page_tbl.shape[1]
    page_size, width = pool.shape[2], pool.shape[3]
    group = _pages_per_turn(page_size,
                            page_size * width * pool.dtype.itemsize, n_pages)
    q_block = pl.BlockSpec((1, kp, _QUERY_ROWS, dv),
                           lambda s_, *prefetched: (s_, 0, 0, 0))
    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel",))
    out = pl.pallas_call(
        functools.partial(_kernel, page_size=page_size, group=group, kp=kp,
                          dv=dv),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(s,),
            in_specs=[q_block, pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=q_block,
            scratch_shapes=[
                pltpu.VMEM((2, group * page_size, width), pool.dtype),
                pltpu.SemaphoreType.DMA((2,)),
            ]),
        out_shape=jax.ShapeDtypeStruct((s, kp, _QUERY_ROWS, dv),
                                       jnp.float32),
        interpret=interpret,
        name="shared_kv_attn",
        **kwargs,
    )(page_tbl.astype(jnp.int32), lens.astype(jnp.int32),
      _query_rows(q, kp, pool.dtype), pool)
    return out[:, :, :2 * p // kp].reshape(s, p, 2, dv)


def shared_kv_attention(q, pool, page_tbl, lens, *, kv_pairs: int,
                        impl: str | None = None,
                        interpret: bool | None = None):
    """q (S, P, 2, hd): one row of query pairs per slot; pool (1, pages,
    page_size, width) the shared row pool (``kv_pairs`` key pairs and
    their values per row, then padding); page_tbl (S, max pages); lens
    (S,) rows each slot attends (0: idle, output zeros) -> (S, P, 2, 2 hd)
    f32."""
    from deeplearning4j_tpu.runtime.backend import backend

    on_tpu = backend().is_tpu
    if (impl or ("pallas" if on_tpu else "xla")) == "xla":
        return _xla(q, pool, page_tbl, lens, kv_pairs)
    return _pallas(q, pool, page_tbl, lens, kp=kv_pairs,
                   interpret=not on_tpu if interpret is None else interpret)
