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
- ``pallas`` — the paged TPU kernel.  Its iteration space follows the
  LIVE pages, not the page table's shape: grid ``(slots,)``, one
  program per slot, whose body is a ``fori_loop`` over that slot's
  ``ceil(seq_len / page_size)`` pages, several pages a turn
  (`_pages_per_turn`: 64 rows where the page's bytes and the VMEM
  budget allow — chosen from the shapes, no knob).  The pools stay in
  HBM (``memory_space=ANY``; with ``layer`` the whole stack, indexed in
  place); the page table and the lengths ride PrefetchScalarGridSpec
  and the body copies each turn's pages into VMEM itself, the next
  turn's in flight (double buffer) while this one is attended — HBM
  never sees a gathered dense copy.  The softmax is accumulated online,
  ONE update of the running max / normalizer / weighted sum per turn,
  carried in registers.  What a slot costs is its pages: measured on a
  v5e at 16 slots x 80 pages of 16 x 16 x 128 f32, an all-idle call is
  9 us (16 near-empty programs), and a live page adds 0.33-0.39 us —
  its 262 KB of K+V at 680-790 GB/s, so an f32 pool is read near the
  HBM roofline.  A slot of length 0 runs no turn, fetches nothing and
  writes zeros; a page past the last live one is never fetched; rows
  past ``seq_len`` are taken out by selects, so nothing stale — a nan
  included — reaches the output.  The body is VPU-only (one query row
  per head is a matrix-vector product — nothing for the MXU to do).
  CPU tier-1 runs the SAME kernel with ``interpret=True``;
  ``tests/test_tpu_lowering.py`` holds it to Mosaic's TPU lowering.
- ``pallas_int8`` — the same body over int8 pages with per-page scale
  blocks (``serving/kv_cache.py``'s layout): a page is dequantized IN
  VMEM (HBM reads ~1 byte per KV element) and accumulated in f32.  The
  scale blocks alone do not come by the body's own copies: Mosaic
  refuses any DMA out of an HBM array whose minor dimension is under
  128 lanes, and the scale pool's is the head count, so the blocks of
  the table's pages (1/32 of their pages' bytes) are gathered by XLA
  and handed to each slot's program as a VMEM block.

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


#: rows one loop turn attends where the shapes allow: the running max,
#: normaliser and accumulator are rescaled once per turn, and the turn's
#: rows are unrolled in the body, so this bounds the kernel's code too
_TURN_ROWS = 64
#: VMEM the page buffers may take (K and V, two turns each) of the
#: 16 MiB a kernel gets by default on every TPU generation
_PAGE_BUFFER_BYTES = 4 << 20


def _pages_per_turn(page_size: int, page_bytes: int, n_pages: int) -> int:
    """Pages one loop turn fetches and attends, from the shapes alone:
    enough for `_TURN_ROWS` rows, no more than the double-buffered K and
    V pages of `_PAGE_BUFFER_BYTES`, and never past the table's span."""
    want = -(-_TURN_ROWS // page_size)
    fit = _PAGE_BUFFER_BYTES // (4 * page_bytes)
    return max(1, min(want, fit, n_pages))


def _pa_kernel(tbl_ref, len_ref, layer_ref, q_ref, k_hbm, v_hbm, *rest,
               page_size: int, group: int, quant: bool):
    """Grid (slots,): one program per slot, whose work is a loop over
    that slot's ``ceil(seq_len / page_size)`` LIVE pages, ``group`` pages
    a turn.  The pools stay in HBM; the scalar-prefetched ``tbl_ref``
    names the pages this body copies into VMEM itself, the next turn's
    pages in flight (second buffer) while this turn's are attended.  A
    page past the last live one is never fetched, and a slot of length
    0 runs no turn and writes zeros.

    One query row per head makes the score a matrix-VECTOR product, so
    the body stays on the VPU: every page row is one (H, Dh) tile (heads
    on sublanes, head_dim on lanes — the pool's own layout, no
    transpose), its score column is ``sum(q * k_row, lanes)`` -> (H, 1),
    and the weighted sum broadcasts that column back over the lanes.
    The rows of a turn are unrolled (``group * page_size`` is static),
    which keeps every value a whole (H, Dh) or (H, 1) tile — the batched
    ``hd,phd->hp`` einsum this replaces has no Mosaic lowering (no lhs
    free dimension, batch dimension mid-rhs) — and the turn's rows share
    ONE online-softmax update.  Rows past ``seq_len`` (the tail of the
    last live page, and the buffer of a page that was not fetched) are
    taken out by SELECTS on the score and on the value row, never by a
    product with a zero weight: whatever they hold, nan included, does
    not reach the output."""
    if quant:
        ks_ref, vs_ref, *rest = rest
    o_ref, k_buf, v_buf, sem = rest
    streams = ((k_hbm, k_buf), (v_hbm, v_buf))
    s = pl.program_id(0)
    h, dh = q_ref.shape[1], q_ref.shape[2]
    turn_rows = group * page_size
    length = len_ref[s]
    n_live = jax.lax.div(length + (page_size - 1), page_size)
    n_turns = jax.lax.div(n_live + (group - 1), group)

    def turn_copies(t, buf, g):
        """The async copies of page ``g`` of turn ``t`` into buffer
        ``buf``: the layer and the table's page index address the pool
        in place."""
        at = layer_ref[0], tbl_ref[s, t * group + g]
        return [pltpu.make_async_copy(hbm.at[at], vmem.at[buf, g],
                                      sem.at[buf, i])
                for i, (hbm, vmem) in enumerate(streams)]

    def each_live_page(t, buf, do):
        for g in range(group):
            @pl.when(t * group + g < n_live)
            def _(g=g):
                for copy in turn_copies(t, buf, g):
                    do(copy)

    @pl.when(n_turns > 0)
    def _first():
        each_live_page(0, 0, lambda copy: copy.start())

    q = q_ref[0].astype(jnp.float32) * (1.0 / np.sqrt(dh))       # (H, Dh)
    if quant:
        eye = (jax.lax.broadcasted_iota(jnp.int32, (h, h), 0)
               == jax.lax.broadcasted_iota(jnp.int32, (h, h), 1))

    def turn(t, carry):
        m_prev, ell, acc = carry
        buf = jax.lax.rem(t, 2)

        @pl.when(t + 1 < n_turns)
        def _next():
            each_live_page(t + 1, 1 - buf, lambda copy: copy.start())

        each_live_page(t, buf, lambda copy: copy.wait())
        base = t * turn_rows

        def live(r, shape):
            return jnp.full(shape, base + r, jnp.int32) < length

        def scale_col(ref, r):
            # the slot's gathered scale rows end with the table's span,
            # the last turn's rows need not
            at = jnp.minimum(base + r, ref.shape[1] - 1)
            return _scale_col(ref[0, pl.ds(at, 1), :], eye)

        scores = []
        for r in range(turn_rows):
            g, p = divmod(r, page_size)
            sc = jnp.sum(q * k_buf[buf, g, p].astype(jnp.float32),
                         axis=-1, keepdims=True)                 # (H, 1)
            if quant:
                # the row scale commutes with the contraction over Dh
                sc = sc * scale_col(ks_ref, r)
            scores.append(jnp.where(live(r, (h, 1)), sc, _MASK))
        # every turn that runs holds a live row, so m_new is a real score
        m_new = functools.reduce(jnp.maximum, scores, m_prev)
        alpha = jnp.exp(m_prev - m_new)
        ell = ell * alpha
        acc = acc * alpha                                        # (H, Dh)
        for r in range(turn_rows):
            g, p = divmod(r, page_size)
            w = jnp.exp(scores[r] - m_new)        # (H, 1); masked -> 0.0
            ell = ell + w
            if quant:
                # a dead row's scale is as stale as its values
                w = jnp.where(live(r, (h, 1)), w * scale_col(vs_ref, r), 0.0)
            row = jnp.where(live(r, (h, dh)),
                            v_buf[buf, g, p].astype(jnp.float32), 0.0)
            acc = acc + w * row
        return m_new, ell, acc

    _, ell, acc = jax.lax.fori_loop(
        0, n_turns, turn,
        (jnp.full((h, 1), _MASK, jnp.float32),       # running max
         jnp.zeros((h, 1), jnp.float32),             # running normalizer
         jnp.zeros((h, dh), jnp.float32)))           # weighted-sum acc
    o_ref[0] = (acc / jnp.where(ell > 0.0, ell, 1.0)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames="interpret")
def _pallas_paged_attention(q, k_pages, v_pages, page_tbl, seq_lens,
                            k_scale, v_scale, layer, *, interpret: bool):
    """The kernel's call, over (L, P, page_size, H, Dh) pools.  ``layer``
    is DATA here, a (1,) int32 beside the table and the lengths: a
    stack's calls then differ in a scalar and not in their code, so a
    step traces and lowers this function — the 64-row body — once for
    all its layers (a jit inside the step's: the lowered module holds
    one function and a call per layer); a static layer would make every
    layer's call a program of its own, 24 traces of the body a step."""
    s, h, dh = q.shape
    n_pages = page_tbl.shape[1]
    page_size = k_pages.shape[-3]
    page_shape = (page_size, h, dh)
    group = _pages_per_turn(
        page_size, int(np.prod(page_shape)) * k_pages.dtype.itemsize,
        n_pages)
    # the pools are operands AS THEY ARE, in HBM: the prefetched `layer`
    # is one more index of the body's page copies, so the gather never
    # exists in HBM and no program holds a per-layer piece of the pool
    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    row_spec = pl.BlockSpec((1, h, dh), lambda s_, *prefetched: (s_, 0, 0))
    in_specs = [row_spec, in_hbm, in_hbm]
    args = [q, k_pages, v_pages]
    if k_scale is not None:
        # Mosaic copies nothing out of an HBM array whose minor dimension
        # is under 128 lanes, and the scale pool's is the head count: the
        # (page_size, H) scale blocks of the table's pages — 1/32 of their
        # pages' bytes — are gathered here and reach the body as the
        # slot's own (maxP * page_size, H) rows
        span_spec = pl.BlockSpec((1, n_pages * page_size, h),
                                 lambda s_, *prefetched: (s_, 0, 0))
        in_specs += [span_spec, span_spec]
        args += [_gather_pages(k_scale, page_tbl, layer[0]),
                 _gather_pages(v_scale, page_tbl, layer[0])]
    scratch = [
        # K and V pages of two turns: the one attended, the one in flight
        pltpu.VMEM((2, group) + page_shape, k_pages.dtype),
        pltpu.VMEM((2, group) + page_shape, v_pages.dtype),
        pltpu.SemaphoreType.DMA((2, 2)),
    ]
    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel",),
        )
    return pl.pallas_call(
        functools.partial(_pa_kernel, page_size=page_size, group=group,
                          quant=k_scale is not None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(s,),
            in_specs=in_specs,
            out_specs=row_spec,
            scratch_shapes=scratch,
        ),
        out_shape=jax.ShapeDtypeStruct((s, h, dh), jnp.float32),
        interpret=interpret,
        name="paged_attn",
        **kwargs,
    )(page_tbl.astype(jnp.int32), seq_lens.astype(jnp.int32), layer, *args)


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
        pools = k_pages, v_pages, k_scale, v_scale
        if layer is None:
            # a lone pool is a stack of one: a leading 1 moves no byte
            pools = [a if a is None else a[None] for a in pools]
        return _pallas_paged_attention(
            q, *pools[:2], page_tbl, seq_lens, *pools[2:],
            jnp.full((1,), layer or 0, jnp.int32), interpret=interpret,
        )
    return _xla_paged_attention(
        q, k_pages, v_pages, page_tbl, seq_lens,
        k_scale=k_scale, v_scale=v_scale, layer=layer,
    )
