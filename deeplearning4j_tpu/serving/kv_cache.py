"""Paged KV cache — a block allocator over a preallocated HBM pool.

The dense per-request cache `ops/generation.py` seeds is O(max_len) HBM
per request whether the request uses it or not; a serving engine that
admits requests of mixed lengths needs the vLLM/Gemma-serving layout
instead: K/V live in fixed-size PAGES of one preallocated pool, each
request holds a page table (ordered pool-page indices), and pages
free-list back on finish/cancel/abort.  Fragmentation is bounded to
less than one page per sequence, and the decode program's shapes stay
STATIC (pool, page table width) — the compiled program set is bounded
exactly the way `flags.bucket_length` bounds the training set, which is
why ``page_size`` is itself quantized through `bucket_length`.

Layout (per layer, K and V each)::

    pages:  (num_pages, page_size, n_heads, head_dim)   f32 | int8
    scales: (num_pages, page_size, n_heads)             f32 (int8 only)

A stack that caches something else per token states it as ROW POOLS
(``rows=``): for each named row its width and how many layers hold one —
a latent-attention decoder keeps a 576-wide ``latent`` row in every layer
and a 128-wide ``index_key`` in the layers that have an indexer::

    <name>_pages:  (layers holding it, num_pages, page_size, *row)  f32 | bf16

under the same allocator, page tables, donation and `revive`; K/V above is
the same thing with two rows of ``(n_heads, head_dim)`` per layer, plus
the int8 scales and the paged kernel that only that layout has.

A stack that keeps state per STREAM, whatever the stream's length, states
it as SLOT POOLS (``slot_rows=``): for each named state the layers that
hold one, the decode slots, its shape and its type — a Mamba layer's scan
state and conv inputs, a window layer's ring of its last keys and values::

    <name>_slots:  (layers holding it, slots, *shape)   f32 | bf16

A slot pool's rows belong to a slot, not to a position: the stream seated
in the slot owns them (`claim_slot`, given back by `release`), the prefill
program that seats it writes them from scratch, and they are donated,
revived and reported with the pages.  They cannot be rolled back: a scan
state has no earlier version to return to (`truncate_to`).

Position ``p`` of a request lives at row ``p % page_size`` of pool page
``table[p // page_size]``.  Page 0 is RESERVED as the engine's scratch
page (idle decode slots write their garbage rows there), so the
allocator hands out pages ``1..num_pages-1``.

int8 pages follow `quant.quantize_array`'s scheme — symmetric,
``scale = max|row| / 127`` with all-zero rows pinned to scale 1.0 —
applied per (position, head) row over ``head_dim`` (`quantize_page_rows`
below; the per-page scale BLOCK (page_size, n_heads) travels with its
page).  K/V rows are written once and never rescaled, so quantization
error is pure rounding — no clipping against a stale page maximum —
and the parity gate is the PR 13 agreement gate, not exactness.

The allocator is HOST state (free list + page tables + counters) under
one lock.  The device arrays are held ONCE: every program that writes
the pool — the engine's decode and verify steps, `write_prefill` here —
takes the four arrays as DONATED arguments, updates them in place and
returns them, and ``k_pages``/``v_pages``/``k_scales``/``v_scales`` are
rebound from that result at once (`PagedKVCache` "Ownership" below).
Exhaustion raises
`KVPoolExhausted` — mapped by admission to HTTP 429, the explicit
"retry later" backpressure signal, never a silent stall — and the fault
site ``kv.alloc`` makes that path provokable (`raise` = injected
exhaustion).  Occupancy lands on the telemetry spine as
``dl4jtpu_kv_pages_used`` / ``dl4jtpu_kv_pages_total``.
"""

from __future__ import annotations

import functools
import logging
import threading
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.runtime import faults
from deeplearning4j_tpu.runtime.flags import bucket_length

log = logging.getLogger("deeplearning4j_tpu")

#: pool page 0 is the scratch page idle slots scribble on — never handed
#: to a request, never read back
SCRATCH_PAGE = 0

#: page sizes are quantized to a multiple of this, the same
#: recompile-hygiene move `flags.bucket_length` makes for the time axis
PAGE_QUANTUM = 8


class KVPoolExhausted(RuntimeError):
    """The pool has no free page for this allocation.  Admission maps it
    to an explicit 429 (``kv_exhausted``) — backpressure, never a stall."""


def quantize_page_rows(a):
    """Quantize K/V rows to int8 with per-(position, head) scales over
    the last (``head_dim``) axis — `quant.quantize_array`'s symmetric
    scheme (``max|row|/127``, zero rows -> scale 1.0) applied at the
    granularity a paged append needs: each row is written ONCE with its
    own scale, so no append ever clips against another row's maximum.

    ``a``: (..., head_dim) float.  Returns ``(q int8, scale f32)`` with
    ``scale.shape == a.shape[:-1]`` and ``dequant = q * scale[...,None]``.
    """
    a = jnp.asarray(a, jnp.float32)
    amax = jnp.max(jnp.abs(a), axis=-1)
    scale = jnp.where(amax > 0.0, amax / 127.0, 1.0)
    q = jnp.clip(jnp.round(a / scale[..., None]), -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.float32)


@functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3))
def _write_pages(k_pages, v_pages, k_scales, v_scales, idx, k, v):
    """`PagedKVCache.write_prefill`'s program: scatter a prompt's K/V
    rows, (n_layers, n * page_size, H, Dh) f32, into pool pages ``idx``
    (n,) of every layer.  The pool is donated, so the scatter is in
    place; shapes are static per prefill bucket, so there is one small
    program per bucket.  An int8 pool quantizes, then scatters."""
    n_layers, num_pages, ps = k_pages.shape[:3]
    # page (layer, p) is page layer * P + p of the pool seen as one run
    # of L * P pages, and the rows are already in that order: both
    # reshapes are free, and every update is one whole contiguous page
    flat = (jnp.arange(n_layers)[:, None] * num_pages + idx).reshape(-1)

    def put(pool, rows):
        tail = pool.shape[2:]
        return (pool.reshape((n_layers * num_pages,) + tail)
                .at[flat].set(rows.astype(pool.dtype).reshape((-1,) + tail))
                .reshape(pool.shape))

    k = jnp.asarray(k, jnp.float32)
    v = jnp.asarray(v, jnp.float32)
    if k_scales is None:
        return put(k_pages, k), put(v_pages, v), None, None
    kq, ks = quantize_page_rows(k)
    vq, vs = quantize_page_rows(v)
    return (put(k_pages, kq), put(v_pages, vq),
            put(k_scales, ks), put(v_scales, vs))


_ROW_DTYPES = {"f32": jnp.float32, "bf16": jnp.bfloat16}


class PagedKVCache:
    """Pool arrays + the block allocator for one transformer stack.

        kv = PagedKVCache(n_layers=2, n_heads=4, head_dim=32,
                          num_pages=256, page_size=16)
        pages = kv.alloc("req-1", n_pages=3)     # -> [7, 12, 3]
        ...decode...
        kv.release("req-1")                      # pages free-list back

    Device state: ``k_pages``/``v_pages`` are (n_layers, num_pages,
    page_size, n_heads, head_dim); int8 mode adds ``k_scales``/
    ``v_scales`` (n_layers, num_pages, page_size, n_heads).

    Ownership: the pool exists once.  A program that writes it takes
    `pool()` as donated arguments and its caller hands the result to
    `rebind` before doing anything else; the arrays passed in are dead
    from the dispatch on.  So NOBODY may hold a reference to a pool
    array across a dispatch — read ``kv.k_pages`` afresh each time, on
    the thread that dispatches (the engine thread), and copy what has to
    outlive the next step.  A dispatch that raises may have consumed
    the pool with nothing to rebind: `revive` then makes it anew.
    """

    def __init__(self, n_layers: int = 0, n_heads: int = 0,
                 head_dim: int = 0, *, num_pages: int, page_size: int,
                 kv_dtype: str = "f32", rows: Optional[dict] = None,
                 slot_rows: Optional[dict] = None):
        """One pool per named row of ``rows`` = {name: (layers holding
        it, row shape)} (``<name>_pages``).  ``n_layers``, ``n_heads``
        and ``head_dim`` are the short form of a key and a value of
        ``(n_heads, head_dim)`` in each of ``n_layers`` layers.
        ``slot_rows`` = {name: (layers holding it, slots, shape, "f32" |
        "bf16")} adds one slot pool per name (``<name>_slots``), beside
        row pools."""
        self.slot_rows = {
            name: (int(layers), int(slots), tuple(int(w) for w in shape),
                   dtype)
            for name, (layers, slots, shape, dtype) in (
                slot_rows or {}).items()}
        if self.slot_rows and rows is None:
            raise ValueError("slot pools live beside row pools (rows=)")
        if rows is None:
            rows = {name: (n_layers, (n_heads, head_dim))
                    for name in ("k", "v")}
        self.rows = {name: (int(layers), tuple(int(w) for w in row))
                     for name, (layers, row) in rows.items()}
        #: keys and values of (heads, head_dim), one of each per layer:
        #: the layout that has int8 pages with per-row scales, takes a
        #: prompt's rows by `write_prefill` and is read by the paged kernel
        self.kv_layout = (list(self.rows) == ["k", "v"]
                          and self.rows["k"] == self.rows["v"]
                          and len(self.rows["k"][1]) == 2)
        if self.kv_layout and kv_dtype not in ("f32", "int8"):
            raise ValueError(
                "a K/V pool (rows of heads x head_dim) holds f32 or int8 "
                f"pages, got kv_dtype {kv_dtype!r}; bf16 pages are for row "
                "pools (rows=)")
        if not self.kv_layout and kv_dtype not in _ROW_DTYPES:
            raise ValueError(
                "a row pool holds f32 or bf16 pages, got kv_dtype "
                f"{kv_dtype!r}; int8 pages with per-row scales exist for "
                "K/V pools only")
        if num_pages < 2:
            raise ValueError("pool needs >= 2 pages (page 0 is scratch)")
        self.n_layers, (self.n_heads, self.head_dim) = (
            self.rows["k"] if self.kv_layout else (0, (0, 0)))
        #: the pool's arrays, as attributes, in the order of `pool()`
        self._names = (("k_pages", "v_pages", "k_scales", "v_scales")
                       if self.kv_layout else
                       tuple(f"{name}_pages" for name in self.rows)
                       + tuple(f"{name}_slots" for name in self.slot_rows))
        # recompile hygiene: a page size of 13 would give every distinct
        # prompt-length bucket its own page count AND its own tail shape
        self.page_size = bucket_length(page_size, PAGE_QUANTUM)
        self.num_pages = int(num_pages)
        self.kv_dtype = kv_dtype
        self._pool_rebuilds = 0
        self.rebind(*self._fresh_pool())
        self._lock = threading.Lock()
        self._free: list[int] = list(range(self.num_pages - 1, 0, -1))
        self._tables: dict[object, list[int]] = {}
        self._slots: dict[object, int] = {}        # rid -> slot it holds
        self._spec_extra: dict[object, int] = {}   # rid -> overhang pages
        self._alloc_failures = 0
        self._gauge_total()
        self._gauge_used(0)

    # -- the device pool ---------------------------------------------------
    def _fresh_pool(self) -> tuple:
        if not self.kv_layout:
            return tuple(
                jnp.zeros((layers, self.num_pages, self.page_size) + row,
                          _ROW_DTYPES[self.kv_dtype])
                for layers, row in self.rows.values()) + tuple(
                jnp.zeros((layers, slots) + shape, _ROW_DTYPES[dtype])
                for layers, slots, shape, dtype in self.slot_rows.values())
        shape = (self.n_layers, self.num_pages, self.page_size,
                 self.n_heads, self.head_dim)
        if self.kv_dtype != "int8":
            return (jnp.zeros(shape, jnp.float32),
                    jnp.zeros(shape, jnp.float32), None, None)
        # scale 1.0 everywhere: untouched rows dequantize to exact 0
        return (jnp.zeros(shape, jnp.int8), jnp.zeros(shape, jnp.int8),
                jnp.ones(shape[:-1], jnp.float32),
                jnp.ones(shape[:-1], jnp.float32))

    def pool(self) -> tuple:
        """``(k_pages, v_pages, k_scales, v_scales)`` — or one array per
        named row, then one per slot pool — the donated arguments of every
        program that writes the pool, in the order those programs return
        them (scales are None for an f32 pool)."""
        return tuple(getattr(self, name) for name in self._names)

    def rebind(self, *arrays) -> None:
        """Take a donating program's result as the pool."""
        for name, a in zip(self._names, arrays, strict=True):
            setattr(self, name, a)

    def revive(self, wait: bool = False) -> bool:
        """After a dispatch that failed: if it consumed the donated pool
        (an array is deleted) — or, with ``wait``, left it the result of
        a program that failed on the device — make the pool anew, zeros
        as at construction, and count it (``stats()["pool_rebuilds"]``).
        The caller fails every stream and releases every page, so no
        row of the old pool is owed to anyone.  ``wait`` blocks until
        the device is done with the pool: not for a wedged device."""
        arrays = [a for a in self.pool() if a is not None]
        try:
            dead = any(a.is_deleted() for a in arrays)
            if wait and not dead:
                jax.block_until_ready(arrays)
        except Exception as e:
            log.warning("kv pool unusable after a failed dispatch: %s", e)
            dead = True
        if dead:
            del arrays
            self.rebind(*[None] * len(self._names))   # free before allocating
            self.rebind(*self._fresh_pool())
            with self._lock:
                self._pool_rebuilds += 1
        return dead

    # -- geometry ----------------------------------------------------------
    def pages_for(self, length: int) -> int:
        """Pages needed to hold ``length`` positions (>= 1 so even an
        empty table owns its first page before decode writes to it)."""
        return max(1, -(-int(length) // self.page_size))

    def bytes_per_token(self) -> int:
        """HBM bytes one position costs across layers and K+V (the
        residency number `bench.py --generate` reports): int8 pays 1
        byte/element plus the f32 per-(position, head) scale; row pools
        pay every named row in the layers that hold it."""
        if not self.kv_layout:
            size = jnp.dtype(_ROW_DTYPES[self.kv_dtype]).itemsize
            return sum(layers * int(np.prod(row)) * size
                       for layers, row in self.rows.values())
        elems = self.n_layers * 2 * self.n_heads * self.head_dim
        if self.kv_dtype == "int8":
            return elems + self.n_layers * 2 * self.n_heads * 4
        return elems * 4

    def slot_pool_bytes(self) -> int:
        """HBM bytes of the slot pools, whatever the streams' lengths."""
        return sum(layers * slots * int(np.prod(shape))
                   * jnp.dtype(_ROW_DTYPES[dtype]).itemsize
                   for layers, slots, shape, dtype
                   in self.slot_rows.values())

    # -- allocation --------------------------------------------------------
    def alloc(self, rid, n_pages: int) -> list[int]:
        """Allocate ``n_pages`` pool pages for request ``rid`` (appended
        to its table if it already holds some).  Raises `KVPoolExhausted`
        when the free list is short — the caller rejects the request
        explicitly (429) and MUST NOT retry inside the decode loop.
        Fault site ``kv.alloc``: ``raise`` = injected exhaustion."""
        try:
            faults.maybe_fail("kv.alloc")
        except Exception as exc:
            self._count_failure()
            raise KVPoolExhausted(f"injected exhaustion: {exc}") from exc
        n_pages = int(n_pages)
        if n_pages < 0:
            raise ValueError("n_pages must be >= 0")
        with self._lock:
            if n_pages > len(self._free):
                self._alloc_failures += 1
                short = n_pages - len(self._free)
                used = self.num_pages - 1 - len(self._free)
                err = KVPoolExhausted(
                    f"kv pool exhausted: need {n_pages} page(s), "
                    f"{len(self._free)} free ({short} short; "
                    f"{used}/{self.num_pages - 1} in use)"
                )
            else:
                got = [self._free.pop() for _ in range(n_pages)]
                self._tables.setdefault(rid, []).extend(got)
                used = self.num_pages - 1 - len(self._free)
                err = None
        if err is not None:
            self._count_failure()
            raise err
        self._gauge_used(used)
        return got

    def extend(self, rid, length: int) -> list[int]:
        """Grow ``rid``'s table to cover ``length`` positions; returns
        the newly allocated pages (possibly [])."""
        with self._lock:
            have = len(self._tables.get(rid, ()))
        need = self.pages_for(length) - have
        return self.alloc(rid, need) if need > 0 else []

    def reserve_speculative(self, rid, length: int) -> list[int]:
        """Best-effort OVERHANG reservation for speculative decode:
        grow ``rid``'s table to cover ``length`` positions (admission
        span + draft chunk) so draft K/V rows land in real pages instead
        of the scratch page.  Unlike `alloc`, a short free list is NOT
        an error here — speculation is optional capacity, the stream's
        admission guarantee is already funded — so exhaustion returns
        ``[]`` without counting an alloc failure or consulting the
        ``kv.alloc`` fault site.  Returns the pages added.  Pages are all
        that speculation can reserve: rejected draft rows are rolled back
        by `truncate_to`, and a slot pool cannot be (see there)."""
        with self._lock:
            have = len(self._tables.get(rid, ()))
            need = self.pages_for(length) - have
            if need <= 0 or need > len(self._free):
                return []
            got = [self._free.pop() for _ in range(need)]
            self._tables.setdefault(rid, []).extend(got)
            self._spec_extra[rid] = self._spec_extra.get(rid, 0) + len(got)
            used = self.num_pages - 1 - len(self._free)
        self._gauge_used(used)
        return got

    def truncate_to(self, rid, length: int) -> list[int]:
        """Truncate-on-reject: free ``rid``'s TAIL pages beyond what
        ``length`` positions need (rejected speculative overhang, or a
        stream whose drafter was disabled mid-flight).  The kept prefix
        is untouched — garbage rows past ``length`` inside the kept
        pages are masked by seq_len and overwritten as the stream
        grows, exactly like plain decode's own write-ahead row.  Pages
        only: a slot pool's state has taken in every row it was handed
        and keeps no earlier version, so a stack with slot pools has
        nothing to truncate to (its engine refuses a drafter).
        Returns the freed pages (possibly [])."""
        keep = self.pages_for(length)
        with self._lock:
            pages = self._tables.get(rid)
            if not pages or len(pages) <= keep:
                return []
            freed = pages[keep:]
            del pages[keep:]
            self._free.extend(freed)
            self._spec_extra.pop(rid, None)
            used = self.num_pages - 1 - len(self._free)
        self._gauge_used(used)
        return freed

    def claim_slot(self, rid, slot: int) -> None:
        """``rid``, which holds pages, is seated in decode slot ``slot``:
        the slot pools' rows of that slot are its own until `release`."""
        with self._lock:
            self._slots[rid] = int(slot)

    def release(self, rid) -> int:
        """Free every page ``rid`` holds, and its slot (finish, cancel,
        watchdog abort — all exits funnel here).  Idempotent; returns the
        number of pages freed."""
        with self._lock:
            self._slots.pop(rid, None)
            pages = self._tables.pop(rid, None)
            self._spec_extra.pop(rid, None)
            if pages:
                self._free.extend(pages)
            used = self.num_pages - 1 - len(self._free)
        if pages:
            self._gauge_used(used)
        return len(pages or ())

    def table(self, rid) -> list[int]:
        with self._lock:
            return list(self._tables.get(rid, ()))

    # -- introspection -----------------------------------------------------
    @property
    def free_pages(self) -> int:
        with self._lock:
            return len(self._free)

    @property
    def used_pages(self) -> int:
        with self._lock:
            return self.num_pages - 1 - len(self._free)

    def occupancy(self) -> float:
        """Fraction of allocatable pages in use, in [0, 1] — the KV
        component of `shed_pressure` (1.0 = the next alloc is a 429)."""
        with self._lock:
            return 1.0 - len(self._free) / max(1, self.num_pages - 1)

    def stats(self) -> dict:
        with self._lock:
            return {
                "num_pages": self.num_pages,
                "page_size": self.page_size,
                "kv_dtype": self.kv_dtype,
                "used_pages": self.num_pages - 1 - len(self._free),
                "free_pages": len(self._free),
                "requests": len(self._tables),
                "spec_reserved_pages": sum(self._spec_extra.values()),
                "alloc_failures": self._alloc_failures,
                "pool_rebuilds": self._pool_rebuilds,
                "bytes_per_token": self.bytes_per_token(),
                # what a token caches: row name -> [layers, *row shape]
                "rows": {name: [layers, *row]
                         for name, (layers, row) in self.rows.items()},
                # what a stream keeps whatever its length: name ->
                # [layers, slots, *shape], their bytes, the slots held
                "slot_rows": {name: [layers, slots, *shape]
                              for name, (layers, slots, shape, _)
                              in self.slot_rows.items()},
                "slot_pool_bytes": self.slot_pool_bytes(),
                "slots_held": len(self._slots),
            }

    def leak_check(self) -> Optional[str]:
        """None when every non-scratch page is either free or owned by
        exactly one table — the invariant the release-on-every-exit
        discipline maintains (tests assert on this)."""
        with self._lock:
            owned = [p for t in self._tables.values() for p in t]
            seen = set(owned)
            if len(seen) != len(owned):
                return "page owned by two tables"
            if seen & set(self._free):
                return "page both free and owned"
            if SCRATCH_PAGE in seen:
                return "scratch page handed out"
            held = list(self._slots.values())
            if len(set(held)) != len(held):
                return "slot held by two streams"
            if set(self._slots) - set(self._tables):
                return "slot held by a stream that holds no page"
            total = len(self._free) + len(owned)
            if total != self.num_pages - 1:
                return (f"{self.num_pages - 1 - total} page(s) leaked "
                        f"({len(self._free)} free + {len(owned)} owned)")
        return None

    # -- device-side page writes -------------------------------------------
    def write_prefill(self, rid, k, v) -> np.ndarray:
        """Write a prompt's K/V rows into ``rid``'s pages (the prefill
        -> pool handoff).  ``k``/``v``: (n_layers, T, n_heads, head_dim)
        with T a multiple of ``page_size`` (the prefill bucket quantum
        guarantees it); the table must already cover T positions.
        Returns the page table as an int32 array (for the decode step's
        page-table row).  One donated program per prefill bucket
        (`_write_pages`): the pool is written in place and rebound.
        Row pools have no hand-off: the programs that compute their rows
        write them in place."""
        if not self.kv_layout:
            raise ValueError(
                "write_prefill hands over keys and values; this pool "
                f"holds the rows {list(self.rows)}, which the prefill "
                "programs write in place")
        pages = self.table(rid)
        t = int(k.shape[1])
        n = t // self.page_size
        if t % self.page_size or n > len(pages):
            raise ValueError(
                f"prefill length {t} does not fit {len(pages)} page(s) "
                f"of {self.page_size}"
            )
        # rebound by assignment from the call's own result: the form
        # tpulint's use-after-donate rule (RH105) follows
        (self.k_pages, self.v_pages, self.k_scales,
         self.v_scales) = _write_pages(
            self.k_pages, self.v_pages, self.k_scales, self.v_scales,
            np.asarray(pages[:n], np.int32), k, v)
        return np.asarray(pages, np.int32)

    # -- telemetry (never on the allocation's critical path) ---------------
    def _count_failure(self) -> None:
        try:
            from deeplearning4j_tpu.observe.metrics import registry

            registry().counter("dl4jtpu_serving_shed_total").inc(
                reason="kv_exhausted"
            )
        except Exception as e:
            log.debug("kv alloc-failure metric failed: %s", e)

    def _gauge_total(self) -> None:
        try:
            from deeplearning4j_tpu.observe.metrics import registry

            registry().gauge("dl4jtpu_kv_pages_total").set(
                self.num_pages - 1
            )
        except Exception as e:
            log.debug("kv total gauge failed: %s", e)

    def _gauge_used(self, used: int) -> None:
        try:
            from deeplearning4j_tpu.observe.metrics import registry

            registry().gauge("dl4jtpu_kv_pages_used").set(used)
        except Exception as e:
            log.debug("kv used gauge failed: %s", e)
