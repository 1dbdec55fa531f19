"""Paged decode-step attention — the Pallas kernel library's third kernel.

One query row per sequence attends against K/V that live in PAGES of a
preallocated pool (``serving/kv_cache.py``) instead of a dense per-request
cache: ``page_tbl[s, j]`` names the pool page holding positions
``[j*page_size, (j+1)*page_size)`` of slot ``s``'s sequence, and
``seq_lens[s]`` bounds the live positions.  Three implementations behind
one dispatch, mirroring ``ops/dequant_matmul.py``:

- ``xla`` — gather-then-attend reference: the page table gathers the
  slot's pages into a dense (L, H, Dh) view and the attention math is
  EXACTLY ``ops/generation.py``'s ``cache_row_attention`` (f32 einsum scores,
  ``-inf`` masking past ``seq_len``, f32 softmax, f32 einsum output) —
  masked positions contribute exact zeros, so paged greedy decode is
  token-identical to the dense reference.
- ``pallas`` — the paged TPU kernel: grid (slots, pages), the page
  table rides PrefetchScalarGridSpec so each grid step DMAs ONE pool
  page into VMEM (HBM never sees a gathered dense copy), and the
  softmax is accumulated online (running max / normalizer / weighted
  sum in VMEM scratch) across a slot's pages.  The body is VPU-only
  (one query row per head is a matrix-vector product — nothing for the
  MXU to do).  CPU tier-1 runs the SAME kernel with ``interpret=True``;
  ``tests/test_tpu_lowering.py`` holds it to Mosaic's TPU lowering.
- ``pallas_int8`` — the fused int8-KV variant: pages are int8 with
  per-page scale blocks (``serving/kv_cache.py``'s layout); the kernel
  dequantizes each page IN VMEM (HBM reads ~1 byte per KV element) and
  accumulates in f32 — the decode step is HBM-bandwidth-bound, so on
  TPU the byte ratio is the speedup (bench.py --generate's roofline
  column).

Selection (``impl=None``): the env override ``DL4JTPU_PAGED_KERNEL``
(pallas / xla / auto) wins; auto picks ``pallas`` on TPU, ``xla`` on CPU
(the gather reference IS the fast CPU path — interpret-mode Pallas is a
correctness vehicle, not a fast one).  int8 pages always take the fused
path's numerics (dequantize-then-attend), via the kernel on TPU and via
the XLA reference off it.  Every selection is a TRACE-TIME event counted
host-side on ``dl4jtpu_paged_attention_total{impl=...}`` — never a call
inside the traced body (tpulint TP004).
"""

from __future__ import annotations

import functools
import logging
import os

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

log = logging.getLogger("deeplearning4j_tpu")

ENV_KERNEL = "DL4JTPU_PAGED_KERNEL"
IMPLS = ("pallas", "xla")

#: the in-kernel mask value: a finite stand-in for -inf so the online
#: softmax's ``exp(score - m)`` underflows to an exact 0.0 on masked
#: positions instead of producing ``-inf - -inf = nan``
_MASK = -1e30


def _count_selection(impl: str) -> None:
    """Trace-time telemetry: which impl a paged-attention site lowered
    to.  Never raises into a trace."""
    try:
        from deeplearning4j_tpu.observe.metrics import registry

        registry().counter("dl4jtpu_paged_attention_total").inc(impl=impl)
    except Exception as e:
        log.debug("paged-attention selection metric failed: %s", e)


def select_impl() -> str:
    """env override > TPU -> pallas > xla gather reference."""
    env = os.environ.get(ENV_KERNEL, "").strip().lower()
    if env in IMPLS:
        return env
    from deeplearning4j_tpu.runtime.backend import backend

    return "pallas" if backend().is_tpu else "xla"


# -- xla gather reference ---------------------------------------------------

def _gather_pages(pages, page_tbl, layer=None):
    """(P, ps, ...) pool + (S, maxP) table -> (S, maxP*ps, ...) dense
    view of each slot's sequence (garbage rows past seq_len are masked
    by the caller).  With ``layer`` the pool is the whole (L, P, ps, ...)
    stack and the ONE gather takes that layer's pages out of it: the
    layer is never sliced out first."""
    g = pages[page_tbl] if layer is None else pages[layer, page_tbl]
    s, mp, ps = g.shape[0], g.shape[1], g.shape[2]   # (S, maxP, ps, ...)
    return g.reshape((s, mp * ps) + g.shape[3:])


def _gather_kv(k_pages, v_pages, page_tbl, k_scale, v_scale, layer):
    """Both pools' dense f32 views, int8 pages dequantized by their
    per-row scale blocks."""
    k = _gather_pages(k_pages, page_tbl, layer).astype(jnp.float32)
    v = _gather_pages(v_pages, page_tbl, layer).astype(jnp.float32)
    if k_scale is not None:
        k = k * _gather_pages(k_scale, page_tbl, layer)[..., None]
    if v_scale is not None:
        v = v * _gather_pages(v_scale, page_tbl, layer)[..., None]
    return k, v


def _xla_paged_attention(q, k_pages, v_pages, page_tbl, seq_lens,
                         k_scale=None, v_scale=None, layer=None):
    """Gather-then-attend: `cache_row_attention`'s exact numerics against
    the page-table-indexed view.  q: (S, H, Dh); pools: (P, ps, H, Dh), or
    (L, P, ps, H, Dh) with ``layer``; int8 pools carry (..., P, ps, H)
    per-row scale blocks."""
    dh = q.shape[-1]
    k, v = _gather_kv(k_pages, v_pages, page_tbl, k_scale, v_scale, layer)
    ell = k.shape[1]
    scores = jnp.einsum(
        "shd,slhd->shl", q.astype(jnp.float32), k
    ) / np.sqrt(dh)
    live = jnp.arange(ell)[None, None, :] < seq_lens[:, None, None]
    scores = jnp.where(live, scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1)
    # a fully-masked slot (seq_len 0: an idle decode slot) softmaxes a
    # row of -inf into nans — zero it so idle slots stay finite
    p = jnp.where(seq_lens[:, None, None] > 0, p, 0.0)
    return jnp.einsum("shl,slhd->shd", p, v)


# -- pallas (TPU; interpret on CPU) ----------------------------------------

def _scale_col(row, eye):
    """(1, H) lane-major scale row -> (H, 1) sublane-major column, the
    orientation of the per-head score/weight columns below.  Written as
    mask-and-lane-reduce (broadcast the row down the sublanes, keep the
    (H, H) diagonal `eye`) because Mosaic has no relayout that moves a
    lane axis onto sublanes for an (8-wide) unaligned shape."""
    return jnp.sum(jnp.where(eye, jnp.broadcast_to(row, eye.shape), 0.0),
                   axis=-1, keepdims=True)


def _pa_kernel(tbl_ref, len_ref, q_ref, k_ref, v_ref, *rest,
               page_size: int, n_pages: int, quant: bool):
    """Grid (slots, pages), pages innermost (sequential): online-softmax
    accumulation of one slot's query row over its page-table-indexed
    pages.  Scalar-prefetched ``tbl_ref``/``len_ref`` drive the page
    DMAs via the BlockSpec index maps; this body only needs the mask.

    One query row per head makes the score a matrix-VECTOR product, so
    the body stays on the VPU: every page row ``p`` is one (H, Dh) tile
    (heads on sublanes, head_dim on lanes — the pool's own layout, no
    transpose), its score column is ``sum(q * k_p, lanes)`` -> (H, 1),
    and the weighted sum broadcasts that column back over the lanes.
    The loop over the page's rows is unrolled (``page_size`` is static
    and small), which keeps every value a whole (H, Dh) or (H, 1) tile —
    the batched ``hd,phd->hp`` einsum this replaces has no Mosaic
    lowering (no lhs free dimension, batch dimension mid-rhs)."""
    if quant:
        ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        o_ref, m_ref, l_ref, acc_ref = rest
    s = pl.program_id(0)
    j = pl.program_id(1)
    h, dh = q_ref.shape[1], q_ref.shape[2]

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _MASK)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = len_ref[s]
    base = j * page_size

    # pages wholly past seq_len (the table's scratch-page tail, or every
    # page of an idle slot) contribute exact zeros: skip their compute
    @pl.when(base < length)
    def _page():
        q = q_ref[0].astype(jnp.float32) * (1.0 / np.sqrt(dh))   # (H, Dh)
        if quant:
            eye = (jax.lax.broadcasted_iota(jnp.int32, (h, h), 0)
                   == jax.lax.broadcasted_iota(jnp.int32, (h, h), 1))
        scores = []
        for p in range(page_size):
            sc = jnp.sum(q * k_ref[0, p].astype(jnp.float32),
                         axis=-1, keepdims=True)                 # (H, 1)
            if quant:
                # the row scale commutes with the contraction over Dh
                sc = sc * _scale_col(ks_ref[0, pl.ds(p, 1), :], eye)
            pos = jnp.full((h, 1), base + p, jnp.int32)
            scores.append(jnp.where(pos < length, sc, _MASK))
        m_prev = m_ref[...]                                      # (H, 1)
        m_new = functools.reduce(jnp.maximum, scores, m_prev)
        alpha = jnp.exp(m_prev - m_new)
        ell = l_ref[...] * alpha
        acc = acc_ref[...] * alpha                               # (H, Dh)
        for p in range(page_size):
            w = jnp.exp(scores[p] - m_new)        # (H, 1); masked -> 0.0
            ell = ell + w
            if quant:
                w = w * _scale_col(vs_ref[0, pl.ds(p, 1), :], eye)
            acc = acc + w * v_ref[0, p].astype(jnp.float32)
        m_ref[...] = m_new
        l_ref[...] = ell
        acc_ref[...] = acc

    @pl.when(j == n_pages - 1)
    def _done():
        ell = l_ref[...]
        o_ref[0] = (acc_ref[...]
                    / jnp.where(ell > 0.0, ell, 1.0)).astype(o_ref.dtype)


def _pallas_paged_attention(q, k_pages, v_pages, page_tbl, seq_lens,
                            k_scale=None, v_scale=None, layer=None, *,
                            interpret: bool):
    s, h, dh = q.shape
    n_pages = page_tbl.shape[1]
    page_size = k_pages.shape[-3]
    quant = k_scale is not None
    # page blocks are selected by the scalar-prefetched table: grid step
    # (s, j) DMAs pool page page_tbl[s, j] — the gather never exists in
    # HBM.  Given the whole (L, P, ...) stack, the static `layer` is one
    # more block index (a squeezed leading dim): the operand is the pool
    # itself, the body sees the same (1, page_size, H, Dh) block
    if layer is None:
        lead, at = (), ()
    else:
        lead, at = (None,), (layer,)
    page_spec = pl.BlockSpec(
        lead + (1, page_size, h, dh),
        lambda s_, j, tbl, lens: at + (tbl[s_, j], 0, 0, 0),
    )
    scale_spec = pl.BlockSpec(
        lead + (1, page_size, h),
        lambda s_, j, tbl, lens: at + (tbl[s_, j], 0, 0),
    )
    row_spec = pl.BlockSpec((1, h, dh), lambda s_, j, tbl, lens: (s_, 0, 0))
    in_specs = [row_spec, page_spec, page_spec]
    args = [q, k_pages, v_pages]
    if quant:
        in_specs += [scale_spec, scale_spec]
        args += [k_scale, v_scale]
    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        )
    return pl.pallas_call(
        functools.partial(_pa_kernel, page_size=page_size,
                          n_pages=n_pages, quant=quant),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(s, n_pages),
            in_specs=in_specs,
            out_specs=row_spec,
            scratch_shapes=[
                pltpu.VMEM((h, 1), jnp.float32),       # running max
                pltpu.VMEM((h, 1), jnp.float32),       # running normalizer
                pltpu.VMEM((h, dh), jnp.float32),      # weighted-sum acc
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((s, h, dh), jnp.float32),
        interpret=interpret,
        **kwargs,
    )(page_tbl.astype(jnp.int32), seq_lens.astype(jnp.int32), *args)


# -- dispatch ---------------------------------------------------------------

def _check_call(k_pages, k_scale, v_scale, layer) -> bool:
    """Shared argument check of the two entry points; returns whether
    the pool is int8 (carries scales)."""
    quant = k_scale is not None
    if quant != (v_scale is not None):
        raise ValueError("int8 pages need BOTH k_scale and v_scale")
    if k_pages.ndim != (4 if layer is None else 5):
        raise ValueError(
            f"pool of rank {k_pages.ndim}: pass (P, page_size, H, Dh), or "
            "the (L, P, page_size, H, Dh) stack together with layer=")
    return quant


def _xla_paged_attention_chunk(q, k_pages, v_pages, page_tbl,
                               attend_lens, k_scale=None, v_scale=None,
                               layer=None):
    """Chunk-native gather-then-attend: each slot's pages are gathered
    ONCE and all C chunk queries attend against that view — C× less
    gather traffic than expanding to S*C pseudo-slots, which is what
    makes the verify dispatch cheap relative to C plain steps on the
    gather-bound CPU path.  Per-row numerics are `_xla_paged_attention`
    exactly (f32 einsum scores over the same contraction, -inf mask,
    f32 softmax), just batched over the chunk dim."""
    dh = q.shape[-1]
    k, v = _gather_kv(k_pages, v_pages, page_tbl, k_scale, v_scale, layer)
    ell = k.shape[1]
    scores = jnp.einsum(
        "schd,slhd->schl", q.astype(jnp.float32), k
    ) / np.sqrt(dh)
    live = (jnp.arange(ell)[None, None, None, :]
            < attend_lens[:, :, None, None])
    scores = jnp.where(live, scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1)
    # idle chunk rows (attend_len 0) softmax -inf rows into nans
    p = jnp.where(attend_lens[:, :, None, None] > 0, p, 0.0)
    return jnp.einsum("schl,slhd->schd", p, v)


def paged_attention_chunk(q, k_pages, v_pages, page_tbl, attend_lens, *,
                          k_scale=None, v_scale=None,
                          layer: int | None = None,
                          impl: str | None = None,
                          interpret: bool | None = None):
    """Speculative verify-once attention: a C-token CHUNK per slot
    against the same paged K/V pool.

    ``q``: (S, C, H, Dh) — chunk position ``j`` of slot ``s`` is the
    query at sequence position ``seq_len + j``; ``attend_lens``:
    (S, C) int32 live positions PER CHUNK POSITION (causality inside
    the chunk is expressed as ``attend_lens[s, j] = seq_len + j + 1``
    with all C K/V rows pre-written by the caller — row ``j`` sees
    exactly the prefix the plain decode step would have seen after
    ``j`` sequential steps).  Idle slots carry ``attend_lens == 0``.

    Two routes, same per-row numerics as the 1-query path (which is
    what keeps speculative greedy decode token-identical to plain
    decode):

    - ``xla`` — the chunk-native gather reference: one page gather per
      slot shared by all C queries (`_xla_paged_attention_chunk`).
    - ``pallas`` — pseudo-slot expansion: the page table row repeats C
      times, the lens flatten, and the chunk rides the REGULAR
      `paged_attention` kernel dispatch (int8 variants included) — no
      new kernel, the grid just sees S*C slots.

    A chunk of ONE is the plain call on either route (and counts as
    it): the serving step is this function at ``C == 1``.

    Returns (S, C, H, Dh) f32.
    """
    s, c, h, dh = q.shape
    if c == 1:
        return paged_attention(
            q.reshape(s, h, dh), k_pages, v_pages, page_tbl,
            attend_lens.reshape(s), k_scale=k_scale, v_scale=v_scale,
            layer=layer, impl=impl, interpret=interpret,
        ).reshape(q.shape)
    quant = _check_call(k_pages, k_scale, v_scale, layer)
    chosen = impl or select_impl()
    if chosen == "xla":
        _count_selection("xla_chunk_int8" if quant else "xla_chunk")
        return _xla_paged_attention_chunk(
            q, k_pages, v_pages, page_tbl, attend_lens,
            k_scale=k_scale, v_scale=v_scale, layer=layer,
        )
    out = paged_attention(
        q.reshape(s * c, h, dh),
        k_pages, v_pages,
        jnp.repeat(page_tbl, c, axis=0),
        attend_lens.reshape(s * c),
        k_scale=k_scale, v_scale=v_scale, layer=layer, impl=impl,
        interpret=interpret,
    )
    return out.reshape(s, c, h, dh)


def paged_attention(q, k_pages, v_pages, page_tbl, seq_lens, *,
                    k_scale=None, v_scale=None,
                    layer: int | None = None,
                    impl: str | None = None,
                    interpret: bool | None = None):
    """One decode step of attention against paged K/V.

    ``q``: (S, H, Dh) — one query row per slot; ``k_pages``/``v_pages``:
    (P, page_size, H, Dh) pools (f32, or int8 with ``k_scale``/
    ``v_scale`` (P, page_size, H) per-page scale blocks); ``page_tbl``:
    (S, maxP) int32 pool-page indices; ``seq_lens``: (S,) int32 live
    positions per slot (position ``p`` of slot ``s`` lives at row
    ``p % page_size`` of pool page ``page_tbl[s, p // page_size]``).
    With a static ``layer`` the pools (and scales) are a whole stack's,
    (L, P, page_size, H, Dh), and layer ``layer`` of them is read IN
    PLACE — what the serving step passes, so that no program ever holds
    a per-layer piece of the pool.
    Returns (S, H, Dh) f32.  ``impl`` forces an implementation;
    ``interpret`` forces/suppresses Pallas interpret mode (None =
    interpret off-TPU).
    """
    quant = _check_call(k_pages, k_scale, v_scale, layer)
    chosen = impl or select_impl()
    _count_selection(f"{chosen}_int8" if quant else chosen)
    if chosen == "pallas":
        if interpret is None:
            from deeplearning4j_tpu.runtime.backend import backend

            interpret = not backend().is_tpu
        return _pallas_paged_attention(
            q, k_pages, v_pages, page_tbl, seq_lens,
            k_scale=k_scale, v_scale=v_scale, layer=layer,
            interpret=interpret,
        )
    return _xla_paged_attention(
        q, k_pages, v_pages, page_tbl, seq_lens,
        k_scale=k_scale, v_scale=v_scale, layer=layer,
    )
