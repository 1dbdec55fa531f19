"""Decode-step latent attention over a slot's whole context in the paged
latent pool — the kernel ``latent_paged_attn``.

A layer that attends every row before the query (a full GDLA layer of the
`motif3` family) reads the stream's cached latent rows ``[c_kv | k_r]``
in place.  The queries come ABSORBED (`ops/latent.absorb_queries`): each
head's no-position query already folded through its group's key half of
``W_kvb`` and joined with its rotary part, times the softmax scale, so a
score is one product with a cached row and the head's output stays in
latent space until `ops/latent.absorbed_values`:

    o_lat[s, h] = softmax_n(qc[s, h] . row[n]) @ row[n, :lk]

over the first ``lens[s]`` rows of slot s (row ``p % page_size`` of page
``page_tbl[s, p // page_size]``) -> (S, H, lk) f32.

- ``xla`` — gather the slot's pages and attend them
  (`ops/latent.attend_latent_rows`): the CPU path, and the kernel's
  reference.  It copies every page of every slot's table each step.
- ``pallas`` — grid ``(slots,)``; a slot's program loops over its LIVE pages
  only, `_TURN_ROWS` rows a turn, copying each turn's pages from the pool in
  HBM into VMEM itself (the next turn's in flight while this one is
  attended), as ``paged_attn`` does since it loops over live pages: an idle
  slot (length 0) fetches nothing, a page past the last live one is never
  read.  A turn is two products on the MXU — the slot's H queries against
  the turn's rows (the stored width, the queries zero-padded to it), and the
  softmax weights against the rows' first ``lk`` columns — and one update
  of the online softmax, in f32; rows past the length are taken out by
  selects on the scores and on the value rows.

Per live row the kernel must read the row (``latent_width`` values) and
compute ``H x (latent_width + lk)`` multiply-adds; the benchmark's family
counts exactly that (`benchmarks/families/motif3.latent_paged_attn_bytes`
and ``_flops``).

Selection (``impl=None``): ``pallas`` on a TPU, ``xla`` elsewhere (the kernel
runs on the CPU with ``interpret=True`` in its tests).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deeplearning4j_tpu.ops.latent import attend_latent_rows

#: finite stand-in for -inf inside the kernel (see ops/paged_attention.py)
_MASK = -1e30
#: pool rows one loop turn attends where the shapes allow
_TURN_ROWS = 512
#: VMEM the page buffers may take (two turns)
_PAGE_BUFFER_BYTES = 8 << 20


def _xla(qc, pool, page_tbl, lens, layer: int, lk: int):
    s, mp = page_tbl.shape
    ps = pool.shape[2]
    rows = pool[layer][page_tbl].reshape(s, mp * ps, -1)
    live = jnp.arange(mp * ps)[None, :] < jnp.maximum(lens, 1)[:, None]
    out = attend_latent_rows(qc, rows, live, lk)
    return jnp.where((lens > 0)[:, None, None], out, 0.0)


def _pages_per_turn(page_size: int, page_bytes: int, n_pages: int) -> int:
    want = -(-_TURN_ROWS // page_size)
    fit = _PAGE_BUFFER_BYTES // (2 * page_bytes)
    return max(1, min(want, fit, n_pages))


def _kernel(tbl_ref, len_ref, q_ref, pool_hbm, o_ref, buf, sem, *,
            page_size: int, group: int, layer: int, lk: int):
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
                    pool_hbm.at[layer, tbl_ref[s, t * group + g]],
                    buf.at[b, pl.ds(g * page_size, page_size)], sem.at[b]))

    @pl.when(n_turns > 0)
    def _first():
        each_live_page(0, 0, lambda copy: copy.start())

    def turn(t, carry):
        m, ell, acc = carry
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
        rows = buf[b]                                         # (R, width)
        sc = jax.lax.dot_general(q_ref[0], rows, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        sc = jnp.where(col_live, sc, _MASK)                   # (H, R)
        m_new = jnp.maximum(m, jnp.max(sc, axis=1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(sc - m_new)
        ell = ell * alpha + jnp.sum(p, axis=1, keepdims=True)
        vals = jnp.where(row_live, rows[:, :lk], jnp.zeros((), rows.dtype))
        acc = acc * alpha + jnp.dot(p.astype(vals.dtype), vals,
                                    preferred_element_type=jnp.float32)
        return m_new, ell, acc

    heads = q_ref.shape[1]
    init = (jnp.full((heads, 1), _MASK, jnp.float32),
            jnp.zeros((heads, 1), jnp.float32),
            jnp.zeros((heads, lk), jnp.float32))
    _, ell, acc = jax.lax.fori_loop(0, n_turns, turn, init)
    o_ref[0] = acc / jnp.where(ell > 0.0, ell, 1.0)


@functools.partial(jax.jit, static_argnames=("layer", "lk", "interpret"))
def _pallas(qc, pool, page_tbl, lens, *, layer: int, lk: int,
            interpret: bool):
    s, heads, w = qc.shape
    n_pages = page_tbl.shape[1]
    page_size, width = pool.shape[2], pool.shape[3]
    group = _pages_per_turn(page_size,
                            page_size * width * pool.dtype.itemsize, n_pages)
    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel",))
    q = jnp.pad(qc.astype(pool.dtype), ((0, 0), (0, 0), (0, width - w)))
    return pl.pallas_call(
        functools.partial(_kernel, page_size=page_size, group=group,
                          layer=layer, lk=lk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(s,),
            in_specs=[pl.BlockSpec((1, heads, width),
                                   lambda s_, *prefetched: (s_, 0, 0)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, heads, lk),
                                   lambda s_, *prefetched: (s_, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, group * page_size, width), pool.dtype),
                pltpu.SemaphoreType.DMA((2,)),
            ]),
        out_shape=jax.ShapeDtypeStruct((s, heads, lk), jnp.float32),
        interpret=interpret,
        name="latent_paged_attn",
        **kwargs,
    )(page_tbl.astype(jnp.int32), lens.astype(jnp.int32), q, pool)


def latent_paged_attention(qc, pool, page_tbl, lens, *, layer: int, lk: int,
                           impl: str | None = None,
                           interpret: bool | None = None):
    """qc (S, H, w): one row of absorbed queries per slot; pool (layers,
    pages, page_size, width >= w) the latent row pool, of which layer
    ``layer`` is read; page_tbl (S, max pages); lens (S,) rows each slot
    attends (0: idle, output zeros) -> (S, H, lk) f32."""
    from deeplearning4j_tpu.runtime.backend import backend

    on_tpu = backend().is_tpu
    if (impl or ("pallas" if on_tpu else "xla")) == "xla":
        return _xla(qc, pool, page_tbl, lens, layer, lk)
    return _pallas(qc, pool, page_tbl, lens, layer=layer, lk=lk,
                   interpret=not on_tpu if interpret is None else interpret)
