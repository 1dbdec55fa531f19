"""Token-level continuous-batching generation serving.

`ops/generation.py` decodes ONE prompt against a dense per-request
cache — the right reference semantics, the wrong serving shape: a
request-at-a-time `generate()` leaves the device idle for every other
stream while one stream decodes, and its dense cache reserves
O(prompt + max_new) HBM per request up front.  This module lifts that
loop into the serving plane the way the Gemma-on-TPU serving stack does:

- **one jitted decode step, fixed slot batch** — `GenerationEngine`
  advances `slots` sequences ONE token per dispatch.  Shapes are static
  (slot count, page-table width), so the whole serving life of the
  engine is a single compiled program; requests join and leave the
  running batch BETWEEN steps, never inside one (continuous batching at
  token granularity, not request granularity).
- **paged KV** — K/V live in `serving/kv_cache.py` pool pages indexed
  by per-slot page tables; `ops/paged_attention.py` attends one query
  row per slot against them.  An idle slot points every table entry at
  the pool's scratch page and carries ``seq_len 0`` — it rides the same
  program as live slots and contributes garbage that nobody reads.
- **bucketed prefill** — the prompt runs as a separate program per
  `flags.bucket_length` bucket (bucket quantum = a page-size multiple,
  so prompt KV lands page-aligned), emits the first token (that is the
  TTFT moment) and hands its K/V rows to the pool.  `prefill_detached`
  / `join_prefilled` split that handoff across replicas — the
  prefill/decode disaggregation seam `ServingFleet.generate` routes.
- **the ladder still holds** — admission is a bounded queue (429 when
  full), KV-pool exhaustion is an explicit ``kv_exhausted`` 429 (never
  a silent stall), each decode step runs under a `StepWatchdog` whose
  abort fails every in-flight stream AND releases all their pages, the
  shared breaker trips on step failures, and a hot-swap lands between
  decode steps (every dispatch reads the installed tree under the
  server's weights lock and serves its copy of it, `_serving_params`)
  so in-flight streams finish — on the new weights — with zero drops.

Numerics contract: greedy paged decode is token-identical to
`ops.generation.generate` for f32 (same per-position math, same
`fold_in` RNG schedule, same top-k threshold rule), and int8-KV pages
are gated by agreement the way PR 13 gated PTQ parity.

Observability (docs/observability.md "Generation plane"): every stream
settles through ONE fate point (`_finish`), which records the
``generation.stream`` root span exactly once, bumps the per-outcome
stream counter, observes the six-segment latency breakdown
(queue / prefill / handoff / decode_queue / decode_compute / sampling),
offers the stream to the slowest-streams exemplar ring
(``GET /api/generation/slow``), and appends a flight-recorder record —
so watchdog-aborted, KV-exhausted (429) and client-cancelled streams
get the same complete causal chain as happy ones, per the PR 12
contract.  Span taxonomy per stream: ``generation.admit`` (enqueue to
taken) -> ``generation.prefill`` (bucketed prompt forward, wherever it
ran) -> ``generation.kv_handoff`` (prefill K/V landing in the decode
pool; cross-replica it starts at the prefill replica's completion
mark) -> one ``generation.decode_step`` span per step per co-resident
stream (args carry the batch composition: co-resident rids and
per-stream token counts) -> the ``generation.stream`` root.  The trace
context rides the `prefill_detached` handoff dict, so a disaggregated
stream is one causal chain across replicas on ``/api/trace/cluster``.

The engine THREAD's time is tiled by recorder spans of its own
(`_span`; each a `jax.profiler` annotation whenever a profiler session
records, ring or no ring): ``generation.wait_for_work`` (no live slot:
asleep in the queue) | ``generation.drain`` (arg ``reason``: a step
landed so the host can act) > ``generation.decode_readback`` +
``generation.harvest`` | ``generation.take`` (the queue's hand-over while
streams are live) | ``generation.refill`` (an admission: every live stream
waits) > ``generation.admit_to_slot`` per request >
``generation.prefill`` (> ``generation.prefill_dispatch`` per program +
``generation.prefill_readback``) + ``generation.kv_handoff`` +
``generation.first_token`` | then, per loop turn and with no span around
them, ``generation.decode_prepare`` -> ``generation.decode_dispatch``
(args ``slots``/``rows``) -> ``generation.decode_readback`` ->
``generation.harvest``.  Each phase is timed ONCE, by its span:
`req.lat` and the per-stream chain read the span's ``dur``, and so do the
drain counters.  Every step also counts what it served —
``dl4jtpu_decode_{steps,slot_steps,rows_attended,pages_attended}_total``.

The decode loop looks ONE step ahead (`_decode_step`): a turn prepares
and dispatches step n + 1 while step n still runs on the device, and
only then reads back and harvests step n — the host's work per step
lies under the device's, and the device finds the next step queued
when one ends.  Step n + 1's tokens are step n's output, handed on as
the device array it is; everything else it needs is known at dispatch
(a plain step gives every live slot one row and one token, and a stream
that ends by count leaves the step after).  What is not known — a stop
token, a cancel — costs one discarded row: the harvest hands a token
only to the request that held the slot when the step was built.  The
loop reads the step in flight back BEFORE it goes on (`_must_drain`,
`_refill`, `stop()`) whenever the next action needs the host's view
whole: an admission, a drafter (it reads host tokens), a stop, a
failure.  ``dl4jtpu_decode_steps_overlapped_total`` over
``dl4jtpu_decode_steps_total`` is the share of steps dispatched that
way; ``dl4jtpu_decode_slot_steps_discarded_total`` the rows thrown away;
``dl4jtpu_decode_drains_total{reason}`` and
``dl4jtpu_decode_drain_seconds_total{reason}`` how often the loop let go
of the lookahead, why, and what the ``generation.drain`` spans took.

Every program samples with `_sample_tokens`: greedy rows take the arg-max,
and the draw and the top-k count run only when some row of the batch asks
for them (``dl4jtpu_decode_sampler_steps_total{branch}`` says how often a
decode step did); no program sorts the vocabulary.
"""

from __future__ import annotations

import collections
import functools
import logging
import threading
import time
import weakref
from collections import deque
from dataclasses import dataclass
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.observe import trace as otrace
from deeplearning4j_tpu.ops.generation import (
    _act_dtype,
    _head_logits,
    _plan,
    block,
    block_params,
    cache_rows,
    embed_tokens,
    prompt_forward,
    serving_params,
    slot_rows,
)
from deeplearning4j_tpu.ops.hybrid import (
    SHARED_KV,
    context_rows,
    full_kv_rows,
    prefill_rows,
    step_rows,
)
from deeplearning4j_tpu.nn.conf.attention import LATENT_RING, LatentBlock
from deeplearning4j_tpu.nn.conf.hybrid import HybridDecoder
from deeplearning4j_tpu.ops.dsa_prefill_attention import (
    carry,
    dsa_prefill_attention,
    tile_counts,
    tile_rows,
)
from deeplearning4j_tpu.ops.latent import (
    MOE_ROW_TILE,
    LatentRows,
    absorb_queries,
    absorbed_values,
    attend_gathered,
    attend_latent_rows,
    blocked,
    index_scores,
    kth_largest,
    topk_mask,
    window_keep,
)
from deeplearning4j_tpu.ops.latent_paged_attention import (
    latent_paged_attention,
)
from deeplearning4j_tpu.ops.paged_attention import paged_attention_chunk
from deeplearning4j_tpu.ops.shared_kv_attention import (
    shared_kv_attention,
)
from deeplearning4j_tpu.runtime import faults
from deeplearning4j_tpu.runtime.flags import bucket_length
from deeplearning4j_tpu.runtime.watchdog import StepWatchdog
from deeplearning4j_tpu.serving.admission import (
    AdmissionQueue,
    ServingError,
    ServingRejected,
    ServingTimeout,
)
from deeplearning4j_tpu.serving.flight import FlightRecorder
from deeplearning4j_tpu.serving.kv_cache import (
    SCRATCH_PAGE,
    KVPoolExhausted,
    PagedKVCache,
    quantize_page_rows,
)
from deeplearning4j_tpu.serving import speculative

log = logging.getLogger("deeplearning4j_tpu")

#: slowest-stream exemplars kept per engine (the serving twin of
#: server.SLOW_RING_CAP — bounded, readable mid-incident)
GEN_SLOW_RING_CAP = 16

#: the per-stream latency segments, in lifecycle order (breakdown dict
#: keys, histogram families and docs share this vocabulary);
#: decode_queue is the residual: slot residency not spent in decode
#: compute or sampling
GEN_BREAKDOWN_SEGMENTS = ("queue", "prefill", "handoff", "decode_queue",
                          "decode_compute", "sampling")

_GEN_BREAKDOWN_FAMILIES = None


def _gen_breakdown_families() -> dict:
    """Segment-name -> histogram, resolved once — per-stream
    attribution must not pay registry lookups/locks."""
    global _GEN_BREAKDOWN_FAMILIES
    if _GEN_BREAKDOWN_FAMILIES is None:
        from deeplearning4j_tpu.observe.metrics import registry

        reg = registry()
        _GEN_BREAKDOWN_FAMILIES = {
            seg: reg.histogram(f"dl4jtpu_generation_{seg}_seconds")
            for seg in GEN_BREAKDOWN_SEGMENTS
        }
    return _GEN_BREAKDOWN_FAMILIES


#: what the decode steps served, process totals: the registry families
#: behind the engine's `_steps`, `_slot_steps`, `_rows_attended` and
#: `_pages_attended`
DECODE_COUNT_FAMILIES = ("dl4jtpu_decode_steps_total",
                         "dl4jtpu_decode_slot_steps_total",
                         "dl4jtpu_decode_rows_attended_total",
                         "dl4jtpu_decode_pages_attended_total")

#: how often the lookahead engages, process totals: steps dispatched
#: while the step before was still unread, and slot-rows a step computed
#: for a stream that had ended before its tokens were read (`_overlapped`,
#: `_discarded`)
DECODE_LOOKAHEAD_FAMILIES = ("dl4jtpu_decode_steps_overlapped_total",
                             "dl4jtpu_decode_slot_steps_discarded_total")

#: why the loop landed a step so the host could act (`_must_drain`): a
#: request waits and a slot is or will be free, a drafter reads host
#: tokens, the engine stops, every stream ends with the step in flight
DRAIN_REASONS = ("admit", "drafter", "stop", "idle")

#: the drains by reason, process totals: how many (`_drains[r][0]`) and
#: the seconds of their ``generation.drain`` spans (`_drains[r][1]`)
DECODE_DRAIN_FAMILIES = ("dl4jtpu_decode_drains_total",
                         "dl4jtpu_decode_drain_seconds_total")

#: which branch of the sampler (`_sample_tokens`) the decode steps ran,
#: counted on the host from the arrays a step is dispatched with: greedy
#: (no row at temperature > 0; an idle slot's is 0), sampled (a draw, no row with a
#: top-k), top_k (the draw and the k-th largest count); process totals by
#: ``branch`` in the family
SAMPLER_BRANCHES = ("greedy", "sampled", "top_k")
DECODE_SAMPLER_FAMILY = "dl4jtpu_decode_sampler_steps_total"

#: what the learned sparse selection did, process totals, counted on the
#: host from the lengths (prefill and decode): context rows the indexers
#: scored and rows they selected, per query row per indexer layer
DSA_COUNT_FAMILIES = ("dl4jtpu_dsa_rows_scored_total",
                      "dl4jtpu_dsa_rows_selected_total")

#: expert assignments of the rows served, counted ON THE DEVICE into a
#: small int32 array the programs carry (`_device_counts`) and read only on
#: demand: all of them by whether the expert is held here (``held``), and
#: the held ones by layer and expert
MOE_ASSIGNMENTS_FAMILY = "dl4jtpu_moe_assignments_total"
MOE_EXPERT_FAMILY = "dl4jtpu_moe_expert_assignments_total"

#: a row pool stores its rows at a multiple of this many values (the TPU's
#: lanes): the device lays a [rows, 576] array out COLUMN-major to spare the
#: lane padding, and every program that gathers or scatters rows of it then
#: transposes the whole pool first (offline v5e compile: + 0.66 GB of
#: temporaries and two pool copies a step at 576, none at 640)
ROW_LANES = 128

#: the latent prefill kernel's tiles, counted ON THE DEVICE into the same
#: array, by state: ``run`` (a tile the selection keeps any pair of, times
#: head groups) and ``skipped`` (one it keeps none of: no copy, no product),
#: and by the layer's ``kind``: ``full`` (its keys are the whole context)
#: or ``window`` (a ring and the chunk's own rows)
DSA_PREFILL_TILES_FAMILY = "dl4jtpu_dsa_prefill_tiles_total"
LAYER_KINDS = ("full", "window")

#: latent rows the decode steps' layers without a selection attended,
#: counted ON THE DEVICE into the same array, by ``kind``: a full layer's
#: are the slot's context (seq_len + 1), a window layer's its ring's
#: min(seq_len + 1, window); summed over the layers of each kind
LATENT_ROWS_FAMILY = "dl4jtpu_latent_rows_attended_total"

#: query rows per block of a prefill chunk's indexer scores (latent) and
#: attention (hybrid): bounds the f32 scores a program holds to (heads,
#: this, context) at a time
PREFILL_QUERY_BLOCK = 256

#: serving copies of the parameter tree made (`_serving_params`): one per
#: installed tree, flushed with the decode counts
PARAMS_CASTS_FAMILY = "dl4jtpu_serving_params_casts_total"

#: what a hybrid stack's state was read for, process totals counted on the
#: host from the lengths: rows of the shared K/V pool a decode step's
#: full and cross layers attend (per layer: seq_len + 1 per live slot),
#: rows of its ring a window layer attends (min(seq_len + 1, window)),
#: and the prompt rows the prefill programs took in
HYBRID_COUNT_FAMILIES = ("dl4jtpu_shared_kv_rows_attended_total",
                         "dl4jtpu_window_rows_attended_total",
                         "dl4jtpu_prefill_rows_total")
#: prompt rows a part of the stack did not run, by ``part``: the cross
#: layers (and the full layer's attention) should run on a prompt's last
#: row only; counted as the prompt's length less the rows the prefill
#: program reports it ran them on
PREFILL_SKIPPED_FAMILY = "dl4jtpu_prefill_rows_skipped_total"

#: the families `_flush_decode_counts` moves the engine's plain counts
#: into, in the order it reads them (then the cross-decoder's skipped rows,
#: under their label)
_FLUSHED_FAMILIES = (DECODE_COUNT_FAMILIES + (PARAMS_CASTS_FAMILY,)
                     + DSA_COUNT_FAMILIES + DECODE_LOOKAHEAD_FAMILIES
                     + HYBRID_COUNT_FAMILIES)

#: a prompt forward of fewer tokens than this spends longer READING f32
#: block matrices than multiplying by them (2 FLOPs a token against 4
#: bytes a weight: 481 tokens on a v5e, 330-460 on its siblings), so its
#: bucket is given the copy's narrow ones; a longer bucket gains next to
#: nothing from them (PERF.md section 6, PR 31: 2 % at 1024-1792 tokens)
#: and keeps the wide ones — the TPU compiler writes five times the code
#: for a prompt forward over narrow matrices (19 -> 102 MB a bucket), and
#: every process start pays to load it
WEIGHT_BOUND_PREFILL_TOKENS = 480

_ENGINES: "weakref.WeakSet[GenerationEngine]" = weakref.WeakSet()
_ENGINES_LOCK = threading.Lock()


def _collect_decode_counts() -> None:
    """Pull collector: the engines count their steps in plain ints on
    their own thread (no registry lock per step); a scrape moves what
    was added since the last one into the registry's counters, and
    samples ``dl4jtpu_decode_batch_occupancy``: the live streams over the
    slots of the engines whose decode loop runs (read without their
    locks — a sample, like any gauge)."""
    with _ENGINES_LOCK:
        engines = list(_ENGINES)
    live = slots = 0
    for eng in engines:
        eng._flush_decode_counts()
        t = eng._thread
        if t is not None and t.is_alive():
            live += sum(r is not None for r in eng._slot_req)
            slots += eng.config.slots
    if slots:
        try:
            from deeplearning4j_tpu.observe.metrics import registry

            registry().gauge("dl4jtpu_decode_batch_occupancy").set(
                live / slots)
        except Exception as e:
            log.debug("occupancy gauge failed: %s", e)


@dataclass
class GenerationConfig:
    """Engine knobs.  ``slots`` and ``max_pages_per_seq`` are STATIC
    shape parameters of the one decode program; ``page_size`` times
    ``max_pages_per_seq`` bounds a stream's total length (prompt bucket
    plus generated tokens)."""

    slots: int = 8                 # decode batch width (static)
    page_size: int = 16            # KV page rows (bucket_length-quantized)
    num_pages: int = 128           # pool size (page 0 is scratch)
    max_pages_per_seq: int = 8     # page-table width (static)
    kv_dtype: str = "f32"          # f32 | int8 pages
    prefill_quantum: Optional[int] = None   # default: page_size
    max_queue: int = 128
    default_max_new: int = 32
    attention_impl: Optional[str] = None    # force pallas|xla (None = auto)
    attention_interpret: Optional[bool] = None
    watchdog_floor_s: float = 30.0
    watchdog_cold_floor_s: float = 600.0
    watchdog_k: float = 10.0
    poll_s: float = 0.02           # idle-queue poll granularity
    # speculative decoding (serving/speculative.py): draft length per
    # stream per step (0 = off; None = DL4J_TPU_SPEC_K), the drafter
    # (None = DL4J_TPU_SPEC_DRAFTER, default "ngram"), and the small
    # zoo model the "model" drafter decodes with
    spec_k: Optional[int] = None
    spec_drafter: Optional[str] = None
    spec_draft_model: object = None


class GenerationRequest:
    """One admitted stream: prompt, sampling params, stop conditions,
    and the token sink the decode loop appends into.  The client waits
    on `result()`; streaming readers poll `tokens_so_far()` or get
    ``on_token(token, index)`` callbacks from the engine thread."""

    __slots__ = ("rid", "prompt", "max_new", "temperature", "top_k",
                 "seed", "stop_tokens", "on_token", "tokens", "error",
                 "cancelled", "prefilled", "signature", "seq",
                 "t_submit", "ttft_s", "_event", "_lock",
                 # observability riders (engine-written; see _finish):
                 # trace linkage, latency-segment dict, fate bookkeeping
                 "trace_id", "root_span", "root_parent", "lat",
                 "outcome", "trace_done", "t_offer", "t_slot", "pages",
                 # speculative decode: per-request draft-length override
                 # (None = engine default, 0 = off for this stream),
                 # the mid-stream fallback latch, and acceptance counts
                 "spec_k", "spec_disabled", "spec_drafted",
                 "spec_accepted")

    _next = [0]

    def __init__(self, prompt: np.ndarray, max_new: int, *,
                 temperature: float = 0.0, top_k: int = 0, seed: int = 0,
                 stop_tokens: tuple = (), on_token=None, prefilled=None,
                 spec_k: Optional[int] = None):
        GenerationRequest._next[0] += 1
        self.rid = f"gen-{GenerationRequest._next[0]}"
        self.prompt = np.asarray(prompt, np.int32).reshape(-1)
        self.max_new = int(max_new)
        self.temperature = float(temperature)
        self.top_k = int(top_k)
        self.seed = int(seed)
        self.stop_tokens = tuple(int(t) for t in stop_tokens)
        self.on_token = on_token
        self.prefilled = prefilled     # disaggregation handoff dict
        self.tokens: list[int] = []
        self.error: Optional[BaseException] = None
        self.cancelled = False
        self.signature = ("generate",)  # AdmissionQueue grouping key
        self.seq = 0
        self.t_submit = time.perf_counter()
        self.ttft_s: Optional[float] = None
        self.trace_id: Optional[int] = None
        self.root_span: Optional[int] = None
        self.root_parent: Optional[int] = None
        self.lat: dict = {}            # segment -> seconds (see _finish)
        self.outcome: Optional[str] = None
        self.trace_done = False        # fate settled exactly once
        self.t_offer: Optional[float] = None
        self.t_slot: Optional[float] = None
        self.pages = 0                 # KV pages held at admission
        self.spec_k = None if spec_k is None else max(0, int(spec_k))
        self.spec_disabled = False
        self.spec_drafted = 0
        self.spec_accepted = 0
        self._event = threading.Event()
        self._lock = threading.Lock()

    # -- engine side -------------------------------------------------------
    def _record(self, token: int) -> None:
        with self._lock:
            if self.ttft_s is None:
                self.ttft_s = time.perf_counter() - self.t_submit
            self.tokens.append(int(token))
            idx = len(self.tokens) - 1
        if self.on_token is not None:
            try:
                self.on_token(int(token), idx)
            except Exception:
                log.exception("on_token callback raised")

    def _complete(self) -> None:
        self._event.set()

    def _fail(self, exc: BaseException) -> None:
        self.error = exc
        self._event.set()

    # -- client side -------------------------------------------------------
    def tokens_so_far(self) -> list[int]:
        with self._lock:
            return list(self.tokens)

    @property
    def done(self) -> bool:
        return self._event.is_set()

    def cancel(self) -> None:
        self.cancelled = True

    def result(self, timeout: Optional[float] = None) -> np.ndarray:
        """Block for completion; returns prompt + generated tokens
        (the `ops.generation.generate` row shape)."""
        if not self._event.wait(timeout):
            self.cancelled = True
            raise ServingTimeout(
                f"generation {self.rid} incomplete after {timeout}s"
            )
        if self.error is not None:
            raise self.error
        return np.concatenate(
            [self.prompt, np.asarray(self.tokens_so_far(), np.int32)]
        )


def _sample_tokens(logits, temps, top_ks, keys):
    """`ops.generation._sample` with RUNTIME sampling params, for a batch
    of rows: logits (n, V), temps (n,), top_ks (n,), keys (n,) -> (n,)
    int32.  Temperature and top-k ride the batch as traced per-row values,
    so the sampling config never recompiles a program; what runs follows
    them.  Greedy argmaxes the UNSCALED logits, exactly like the
    reference's ``temperature <= 0`` branch, and is all an all-greedy
    batch runs: the draw (scale, threshold, mask, `categorical` with each
    row's key) sits under ONE `lax.cond` on the batch, and the top-k
    threshold under another inside it.  The threshold is each row's k-th
    largest scaled logit (`kth_largest`, counted, not sorted: the value
    `lax.top_k(x, k)[0][..., -1]` gives the dense reference), k clipped to
    ``1..V``; a row with ``top_k <= 0`` keeps every logit."""
    logits = logits.astype(jnp.float32)
    v = logits.shape[-1]
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    sampled = temps > 0.0
    no_threshold = lambda scaled: jnp.full(scaled.shape[:1], -jnp.inf)

    def top_k(scaled):
        kth = kth_largest(scaled, jnp.clip(top_ks, 1, v))
        return jnp.where(top_ks > 0, kth, -jnp.inf)

    def draw(_):
        scaled = logits / jnp.where(sampled, temps, 1.0)[:, None]
        kth = jax.lax.cond(jnp.any(sampled & (top_ks > 0)), top_k,
                           no_threshold, scaled)
        masked = jnp.where(scaled < kth[:, None], -jnp.inf, scaled)
        samp = jax.vmap(jax.random.categorical)(keys, masked)
        return jnp.where(sampled, samp.astype(jnp.int32), greedy)

    return jax.lax.cond(jnp.any(sampled), draw, lambda _: greedy, None)


def _sampler_branch(temps, top_ks) -> str:
    """The branch of `_sample_tokens` a step runs, from the temps and
    top_ks (S,) it is dispatched with (an idle slot's are 0)."""
    samples = temps > 0.0
    if not samples.any():
        return "greedy"
    return "top_k" if (samples & (top_ks > 0)).any() else "sampled"


def _stored_row(row: tuple) -> tuple:
    """The shape a pool stores a cached row at: a flat row is padded to
    whole `ROW_LANES`; keys and values of (heads, head_dim) stay."""
    return row if len(row) > 1 else (-(-row[0] // ROW_LANES) * ROW_LANES,)


def _to_row_width(rows, pool):
    """rows (n, w) in the pool's type, zero-padded to its stored width."""
    pad = pool.shape[-1] - rows.shape[-1]
    return jnp.pad(rows.astype(pool.dtype), ((0, 0), (0, pad)))


#: where a decode step's S * c rows sit: the slots' page tables (S, mp),
#: each row's page and row in it (the write guard applied), the lengths
#: the rows attend (S, c), their positions, and which rows are live
_StepRows = collections.namedtuple(
    "_StepRows", "page_tbl page_of row_of attend_lens positions active")

#: a decode step dispatched and not read back: its tokens on the device
#: (the next step's ``toks``), and the harvest that hands them out
_Flying = collections.namedtuple("_Flying", "toks harvest")


def _real_rows(prompt_len, start: int, c_rows: int):
    """How many of a prefill chunk's ``c_rows`` rows, from position
    ``start``, are the prompt's: the rows the slot state takes in (the rest
    are pad, and leave it as it is)."""
    return jnp.clip(prompt_len - start, 0, c_rows)


class _PoolView:
    """The program state's arrays by pool name, written through to the
    list the step returns."""

    def __init__(self, arrays: list, at: dict):
        self.arrays, self.at = arrays, at

    def __getitem__(self, name):
        return self.arrays[self.at[name]]

    def __setitem__(self, name, array):
        self.arrays[self.at[name]] = array


def _slot_keys(seeds, gen_counts):
    """Per-slot sampling keys on the dense reference's schedule: the
    g-th generated token of a stream seeded ``s`` uses
    ``fold_in(key(s), g)`` (the reference samples its first token with
    ``fold_in(rng, 0)`` and tick ``i`` with ``fold_in(rng, i + 1)``)."""
    return jax.vmap(
        lambda s, g: jax.random.fold_in(jax.random.key(s), g)
    )(seeds, gen_counts)


class GenerationEngine:
    """Continuous-batching decode engine over a paged KV pool.

        engine = GenerationEngine(model=m, config=GenerationConfig())
        engine.start()
        req = engine.submit(prompt_ids, max_new_tokens=32)
        out = req.result(timeout=30)        # prompt + generated tokens

    Attach to an `InferenceServer` (``server=``) to ride its ladder:
    params snapshot under the server's weights lock (hot-swap lands
    between decode steps), step failures feed the shared breaker,
    admission honors breaker state, and `server.shed_pressure` folds in
    KV-pool occupancy.  Standalone (``model=``) runs the same engine
    with its own lock for tests and benchmarks.
    """

    def __init__(self, model=None, server=None,
                 config: Optional[GenerationConfig] = None):
        if (model is None) == (server is None):
            raise ValueError("pass exactly one of model= or server=")
        self.server = server
        self.model = server.model if server is not None else model
        if self.model.params is None:
            self.model.init()
        self.config = cfg = config or GenerationConfig()
        self._weights_lock = (
            server._weights_lock if server is not None else threading.Lock()
        )
        self.breaker = server.breaker if server is not None else None

        self._stack = stack = _plan(self.model)
        # what the stack's blocks cache decides the pool: per named row,
        # the layers that hold one (and which of them each block's is),
        # and per named slot pool the same
        held: dict = {}
        per_slot: dict = {}
        self._pool_index = {}
        for b in stack.blocks:
            self._pool_index[b.name] = at = {}
            for name, row in cache_rows(b).items():
                at[name] = held.setdefault(name, [0, row])[0]
                held[name][0] += 1
            for name, (shape, dtype) in slot_rows(b).items():
                at[name] = per_slot.setdefault(name, [0, shape, dtype])[0]
                per_slot[name][0] += 1
        # a stack with no block keeps the empty K/V pool it always had
        self.kv = PagedKVCache(
            num_pages=cfg.num_pages, page_size=cfg.page_size,
            kv_dtype=cfg.kv_dtype,
            rows={name: (n, _stored_row(row))
                  for name, (n, row) in held.items()} or None,
            slot_rows={name: (n, cfg.slots, shape,
                              cfg.kv_dtype if dtype == "kv" else dtype)
                       for name, (n, shape, dtype) in per_slot.items()})
        # where each named pool sits among the program state's arrays
        self._state_at = {name: i for i, name in enumerate(
            list(self.kv.rows) + list(self.kv.slot_rows))}
        self._hybrid = isinstance(stack.final, HybridDecoder)
        # expert layers: their place in the device counts; a stack with an
        # indexer, or with latent layers that select nothing: the row of
        # its prefill kernel's tiles; the latter: one more, of the latent
        # rows the decode steps attended
        self._moe_index = {
            b.name: i for i, b in enumerate(
                b for b in stack.blocks if getattr(b, "ffn", "") == "sparse")}
        self._dsa_layers = sum(getattr(b, "indexer", "") == "full"
                               for b in stack.blocks)
        self._plain_latent = any(getattr(b, "indexer", "") == "none"
                                 for b in stack.blocks)
        self._tiles_row = (len(self._moe_index)
                           if self._dsa_layers or self._plain_latent
                           else None)
        self._rows_row = (self._tiles_row + 1 if self._plain_latent
                          else None)
        self._device_counts = self._fresh_device_counts()
        self._device_flushed = None
        self._dsa_topk = max((b.index_topk for b in stack.blocks
                              if getattr(b, "indexer", "") == "full"),
                             default=0)
        self._dsa_scored = 0
        self._dsa_selected = 0
        # a hybrid stack's state, read and skipped (`HYBRID_COUNT_FAMILIES`)
        self._window = max((b.window for b in stack.blocks
                            if getattr(b, "kind", "") == "swa"
                            or isinstance(b, LatentBlock)), default=0)
        self._shared_rows = 0
        self._window_rows = 0
        self._prefill_rows = 0
        self._cross_skipped = 0
        self._quantum = cfg.prefill_quantum or self.kv.page_size
        if self._quantum % self.kv.page_size:
            raise ValueError(
                f"prefill_quantum {self._quantum} must be a multiple of "
                f"the page size {self.kv.page_size} (prompt KV must land "
                "page-aligned)"
            )
        if self._window and self._quantum % self._window:
            raise ValueError(
                f"prefill_quantum {self._quantum} must be a multiple of "
                f"the attention window {self._window}: a chunk starts at "
                "row 0 of the window layers' rings")

        s, mp = cfg.slots, cfg.max_pages_per_seq
        # host slot state; the decode step consumes these by value, so
        # mutating them BETWEEN steps is the continuous-batching join
        self._page_tbl = np.full((s, mp), SCRATCH_PAGE, np.int32)
        self._seq_lens = np.zeros(s, np.int32)
        self._last_tok = np.zeros(s, np.int32)
        self._gen_counts = np.zeros(s, np.int32)
        self._temps = np.zeros(s, np.float32)
        self._top_ks = np.zeros(s, np.int32)
        self._seeds = np.zeros(s, np.uint32)
        self._slot_req: list[Optional[GenerationRequest]] = [None] * s

        self.queue = AdmissionQueue(cfg.max_queue)
        self._mu = threading.Lock()       # slot state + loop generation
        self._stop = threading.Event()
        self._loop_gen = 0
        self._thread: Optional[threading.Thread] = None
        self.watchdog = StepWatchdog(
            floor_s=cfg.watchdog_floor_s,
            cold_floor_s=cfg.watchdog_cold_floor_s,
            k=cfg.watchdog_k, abort=self._on_wedged, name="generation",
        )
        # what the steps served, counted where a step is built (engine
        # thread only): dispatches, live slots summed over them, and KV
        # rows — and the pool pages that hold them — summed over them
        self._steps = 0
        self._slot_steps = 0
        self._rows_attended = 0
        self._pages_attended = 0
        # the lookahead: the newest step dispatched and not read back
        # (written under `_mu` by the loop that owns it), how many steps
        # were dispatched on top of one, and how many of their rows no
        # stream was left to take
        self._flying: Optional[_Flying] = None
        self._overlapped = 0
        self._discarded = 0
        # the drains by reason: [count, seconds of their spans] (every
        # reason present from the start, so a flush never sees a new key)
        self._drains = {r: [0, 0.0] for r in DRAIN_REASONS}
        self._drains_flushed = {r: (0, 0.0) for r in DRAIN_REASONS}
        # the steps by the branch of the sampler they ran
        self._sampler_steps = dict.fromkeys(SAMPLER_BRANCHES, 0)
        self._sampler_flushed = dict.fromkeys(SAMPLER_BRANCHES, 0)
        # `model.params` as the copies below were made from it, the tree
        # the programs are dispatched with, and the long prefill buckets'
        # (`_serving_params`); and how many were made
        self._served = (None, None, None)
        self._params_casts = 0
        self._counts_flushed = (0,) * (len(_FLUSHED_FAMILIES) + 1)
        self._tokens_out = 0
        # the compiled decode programs by chunk width c: 1 is the plain
        # step, spec_k + 1 the speculative verify (built at first use)
        self._step_fns: dict[int, Callable] = {}
        self._prefill_fns: dict[int, Callable] = {}
        # speculative decode: resolve the engine-wide draft length and
        # drafter once (env knobs DL4J_TPU_SPEC_K/DL4J_TPU_SPEC_DRAFTER,
        # overridden by explicit config fields); spec_k == 0 keeps the
        # whole path disabled and the c > 1 program never built
        k = (cfg.spec_k if cfg.spec_k is not None
             else speculative.spec_k_from_env(0))
        self.spec_k = max(0, int(k))
        self.drafter: Optional[speculative.DraftSource] = None
        if self.spec_k > 0 and self.kv.slot_rows:
            raise ValueError(
                f"speculative decoding (spec_k {self.spec_k}) needs rejected "
                "draft rows rolled back, and this stack keeps recurrent "
                f"state per stream ({sorted(self.kv.slot_rows)}): a scan "
                "that took in a rejected token, or a ring row it overwrote, "
                "cannot be truncated")
        if self.spec_k > 0:
            self.drafter = speculative.make_drafter(
                cfg.spec_drafter or speculative.drafter_from_env(),
                draft_model=cfg.spec_draft_model,
            )
        self._vocab = int(
            self.model.params[stack.embed.name]["W"].shape[0])
        self._spec_counts = {"drafted": 0, "accepted": 0, "rejected": 0,
                             "bonus": 0, "emitted": 0,
                             "verify_dispatches": 0,
                             "plain_dispatches": 0, "fallbacks": 0}
        # observability: trace recorder handle, slow-stream exemplar
        # ring, breakdown totals, and the flight recorder with its
        # SLO-alert rising-edge trigger (detached at stop())
        self._rec = otrace.tracer()
        self._stats_lock = threading.Lock()
        self._slow: list[dict] = []
        self._lat_totals = {k: 0.0 for k in GEN_BREAKDOWN_SEGMENTS}
        self._stream_outcomes: dict[str, int] = {}
        self._streams_settled = 0
        self._rate_samples: deque = deque(maxlen=64)  # (t, tokens_out)
        self.flight = FlightRecorder()
        self.flight.context_fn = self._flight_context
        self.flight.attach_slo_trigger()
        if server is not None:
            server.generation_engine = self
        with _ENGINES_LOCK:
            _ENGINES.add(self)
        try:
            from deeplearning4j_tpu.observe.metrics import registry

            registry().register_collector(_collect_decode_counts)
        except Exception as e:
            log.debug("decode count collector install failed: %s", e)

    # -- lifecycle ---------------------------------------------------------
    def start(self) -> "GenerationEngine":
        if self._thread is not None and self._thread.is_alive():
            return self
        self._stop.clear()
        self._serving_params()      # made here, not by the first request
        with self._mu:
            self._loop_gen += 1
            gen = self._loop_gen
        self._thread = threading.Thread(
            target=self._loop, args=(gen,),
            name="dl4jtpu-generation", daemon=True,
        )
        self._thread.start()
        return self

    def stop(self, timeout: float = 10.0) -> None:
        self._stop.set()
        t = self._thread
        if t is not None:
            t.join(timeout)
        for req in self.queue.drain():
            self._finish(req, "shutdown",
                         ServingRejected("shutdown", "engine stopped"))
        with self._mu:
            self._fail_active_locked(
                ServingRejected("shutdown", "engine stopped"),
                outcome="shutdown",
            )
        self.flight.detach_slo_trigger()
        self._flush_decode_counts()

    # -- admission ---------------------------------------------------------
    def submit(self, prompt, max_new_tokens: Optional[int] = None, *,
               temperature: float = 0.0, top_k: int = 0, seed: int = 0,
               stop_tokens: tuple = (), on_token=None,
               trace_ctx=None, spec_k: Optional[int] = None,
               ) -> GenerationRequest:
        """Admit one stream.  Raises `ServingRejected` on a full queue
        or an open breaker; over-capacity streams (longer than the page
        table can hold) are client errors (`ValueError`).  `trace_ctx`
        is an upstream ``(trace_id, root_span)`` pair (the fleet's
        routed path allocates one so the router pick joins the stream
        chain); None allocates fresh ids when tracing is on.  `spec_k`
        overrides the engine's speculative draft length for THIS stream
        (0 = plain decode; capped at the engine's configured k — the
        verify program's chunk width is static)."""
        max_new = int(max_new_tokens if max_new_tokens is not None
                      else self.config.default_max_new)
        req = GenerationRequest(
            prompt, max_new, temperature=temperature, top_k=top_k,
            seed=seed, stop_tokens=stop_tokens, on_token=on_token,
            spec_k=spec_k,
        )
        self._validate(req)
        self._init_trace(req, trace_ctx)
        self._offer_counted(req)
        return req

    def _validate(self, req: GenerationRequest) -> None:
        t_p = req.prompt.shape[0]
        if t_p < 1:
            raise ValueError("empty prompt")
        if req.max_new < 1:
            raise ValueError("max_new_tokens must be >= 1")
        span = max(bucket_length(t_p, self._quantum), t_p + req.max_new)
        if self.kv.pages_for(span) > self.config.max_pages_per_seq:
            cap = self.config.max_pages_per_seq * self.kv.page_size
            raise ValueError(
                f"stream needs {span} KV positions; the page table holds "
                f"{cap} (max_pages_per_seq x page_size)"
            )
        pos = self._stack.pos
        if pos is not None and pos.learned and span > pos.max_length:
            raise ValueError(
                f"stream needs {span} positions; learned "
                f"PositionalEncoding max_length is {pos.max_length}"
            )

    def _offer(self, req: GenerationRequest) -> None:
        if self.breaker is not None and not self.breaker.admits():
            raise ServingRejected(
                "breaker_open", f"circuit breaker is {self.breaker.state}"
            )
        if not self.queue.offer(req):
            raise ServingRejected(
                "queue_full",
                f"generation queue at capacity ({self.queue.max_queue})",
            )

    def _offer_counted(self, req: GenerationRequest) -> None:
        """Offer + admission bookkeeping: a synchronous reject is
        counted as a stream outcome (its reason), an accepted stream
        bumps the demand counter behind throughput SLOs and stamps the
        enqueue mark the queue segment reads."""
        try:
            self._offer(req)
        except ServingRejected as exc:
            self._count_stream(exc.reason)
            raise
        req.t_offer = time.perf_counter()
        self._count_admitted()

    def _init_trace(self, req: GenerationRequest, trace_ctx=None) -> None:
        """Allocate (or adopt) the stream's trace linkage BEFORE the
        queue sees it — same contract as server._admit.  No-op when
        tracing is off: untraced streams still get breakdowns."""
        if not self._rec.enabled:
            return
        if trace_ctx is not None:
            req.trace_id, req.root_span = trace_ctx
        else:
            req.trace_id = otrace.next_id()
            req.root_span = otrace.next_id()

    def _trace_segment(self, req: GenerationRequest, name: str,
                       t0_pc: float, dur: float, **args) -> None:
        """One child span of the stream's root chain (no-op untraced)."""
        if req.trace_id is None or not self._rec.enabled:
            return
        self._rec.add_complete(
            name, t0_pc, dur, cat="generation",
            **otrace.trace_args(req.trace_id, otrace.next_id(),
                                req.root_span),
            **args,
        )

    def _span(self, name: str, **args):
        """One phase of the engine thread: a recorder span, so a
        profiler annotation always (the loop's phases on the session's
        clock, next to the device's idle gaps) and a ring span while the
        ring is enabled.  Its `t0` / `dur` after exit are the phase's
        one timing — `req.lat` and the per-stream chain read them."""
        return self._rec.span(name, cat="engine", **args)

    def generate(self, prompt, max_new_tokens: int, *,
                 temperature: float = 0.0, top_k: int = 0, seed: int = 0,
                 stop_tokens: tuple = (),
                 timeout: Optional[float] = 120.0) -> np.ndarray:
        """Blocking convenience wrapper — submit one stream, wait, and
        return the `ops.generation.generate`-shaped row."""
        return self.submit(
            prompt, max_new_tokens, temperature=temperature, top_k=top_k,
            seed=seed, stop_tokens=stop_tokens,
        ).result(timeout)

    # -- prefill/decode disaggregation ------------------------------------
    def prefill_detached(self, prompt, max_new_tokens: int, *,
                         temperature: float = 0.0, top_k: int = 0,
                         seed: int = 0, stop_tokens: tuple = (),
                         trace_ctx=None, spec_k: Optional[int] = None,
                         ) -> dict:
        """Run ONLY the prefill program here and return a portable
        handoff (prompt K/V rows as host arrays + the first token + the
        stream's sampling state).  A decode-role replica resumes the
        stream from it via `join_prefilled` — K/V cross the replica
        boundary in f32 and land in whatever page dtype the DECODE
        pool uses, so a f32 prefill replica can feed an int8 decode
        replica.  The stream's trace context (adopted from `trace_ctx`
        or allocated here) and timing marks ride the handoff, so the
        decode replica extends the SAME causal chain."""
        if not self.kv.kv_layout:
            raise ValueError(
                "this stack's prefill writes its row pools in place; "
                "handing its rows to another replica is not implemented")
        req = GenerationRequest(
            prompt, int(max_new_tokens), temperature=temperature,
            top_k=top_k, seed=seed, stop_tokens=stop_tokens,
        )
        self._validate(req)
        self._init_trace(req, trace_ctx)
        try:
            faults.maybe_fail("serving.prefill")
        except Exception as exc:
            raise ServingError(f"injected prefill fault: {exc}") from exc
        with self._span("generation.prefill", detached=True) as sp:
            k, v, first, ttft_anchor = self._run_prefill(req)
        pre_s = sp.dur
        self._trace_segment(req, "generation.prefill", sp.t0, pre_s,
                            bucket=int(k.shape[1]), detached=True)
        out = {
            "prompt": req.prompt, "k": np.asarray(k), "v": np.asarray(v),
            "first_token": int(first), "max_new": req.max_new,
            "temperature": req.temperature, "top_k": req.top_k,
            "seed": req.seed, "stop_tokens": req.stop_tokens,
            "t_submit": ttft_anchor,
            "prefill_s": pre_s,
            "t_done_pc": time.perf_counter(),
        }
        if spec_k is not None:
            out["spec_k"] = max(0, int(spec_k))
        if req.trace_id is not None:
            out["trace"] = (req.trace_id, req.root_span)
        return out

    def join_prefilled(self, handoff: dict,
                       on_token=None) -> GenerationRequest:
        """Admit a stream whose prefill already ran elsewhere (the
        decode side of the disaggregation seam).  Adopts the handoff's
        trace context — the root span settles HERE, where the stream's
        fate is decided — and its prefill timing for the breakdown."""
        req = GenerationRequest(
            handoff["prompt"], handoff["max_new"],
            temperature=handoff["temperature"], top_k=handoff["top_k"],
            seed=handoff["seed"], stop_tokens=handoff["stop_tokens"],
            on_token=on_token, prefilled=handoff,
            spec_k=handoff.get("spec_k"),
        )
        req.t_submit = handoff.get("t_submit", req.t_submit)
        self._validate(req)
        self._init_trace(req, handoff.get("trace"))
        if "prefill_s" in handoff:
            req.lat["prefill"] = float(handoff["prefill_s"])
        self._offer_counted(req)
        return req

    # -- compiled programs -------------------------------------------------
    def _serving_params(self, t_b: int = 0):
        """What a program is dispatched with: `model.params` with the
        matrices already in the activation type (`serving_params`), made
        once per installed tree and not once per step.  ``t_b`` is the
        prefill bucket the tree is for (0: the decode programs); a
        bucket past `WEIGHT_BOUND_PREFILL_TOKENS` gets the same tree
        with the blocks' entries as `model.params` holds them — the
        same leaves either way, so no third set of weights.  This read
        is the hot-swap boundary: `push_weights` installs under the same
        lock, so the dispatch after a swap finds another tree there and
        serves its copy — a swap lands BETWEEN decode steps and
        in-flight streams continue, on the new weights, with zero
        drops."""
        with self._weights_lock:
            live = self.model.params
            if self._served[0] is not live:
                self._served = (None, None, None)   # the stale copy goes first
                copy = serving_params(self._stack, live,
                                      _act_dtype(self.model))
                self._served = (live, copy, {
                    **copy, **{b.name: live[b.name]
                               for b in self._stack.blocks
                               if b.name in live}})
                self._params_casts += 1
            long = t_b > WEIGHT_BOUND_PREFILL_TOKENS
            return self._served[2 if long else 1]

    def _make_prefill(self, t_b: int):
        stack = self._stack

        @jax.jit
        def prefill(params, prompt_pad, prompt_len, seed, temp, top_k):
            # prompt_pad: (1, t_b); rows past prompt_len are pad — with
            # causal attention they influence nothing before them, and
            # their garbage K/V rows sit beyond seq_len (masked at
            # decode, overwritten as the stream grows into them)
            x, kvs = prompt_forward(stack, params, prompt_pad,
                                    _act_dtype(self.model))
            logits = _head_logits(stack, params, x[0, prompt_len - 1])
            first = _sample_tokens(
                logits[None], temp[None], top_k[None],
                jax.random.fold_in(jax.random.key(seed), 0)[None])[0]
            return (jnp.stack([k[0] for k, _ in kvs]).astype(jnp.float32),
                    jnp.stack([v[0] for _, v in kvs]).astype(jnp.float32),
                    first)

        return prefill

    def _prefill_fn(self, key: int):
        # `jax.jit` construction is lazy (compilation happens at the
        # first CALL, outside this lock), so memoizing under `_mu` is
        # cheap even with the decode loop live.  A K/V pool's programs
        # are keyed by the prompt's bucket, row pools' by the chunk's index
        make = (self._make_prefill if self.kv.kv_layout
                else self._make_hybrid_prefill_chunk if self._hybrid
                else self._make_prefill_chunk)
        with self._mu:
            fn = self._prefill_fns.get(key)
            if fn is None:
                fn = self._prefill_fns[key] = make(key)
        return fn

    def _run_prefill(self, req: GenerationRequest, slot: int = 0):
        """Dispatch one request's prefill; returns (k, v, first_token,
        ttft_anchor).  A K/V pool: the bucketed whole-prompt program,
        k/v shaped (n_layers, t_bucket, H, Dh) f32 for `write_prefill`.
        Row pools: the prompt's chunk programs in a row, each writing the
        stream's pages in place (the pool is donated through every one)
        and only the last chunk's token read back; k and v are None.
        Inside the caller's ``generation.prefill``: one
        ``generation.prefill_dispatch`` per program (the host's cost to
        launch it; the device idles under it when nothing is queued) and
        the ``generation.prefill_readback`` of the first token.  A stack
        with slot pools is also handed ``slot``, whose rows the first
        chunk writes from scratch: the slot's reset."""
        t_p = req.prompt.shape[0]
        t_b = bucket_length(t_p, self._quantum)
        params = self._serving_params(t_b)
        sampling = (np.int32(t_p), np.uint32(req.seed),
                    np.float32(req.temperature), np.int32(req.top_k))
        if self.kv.kv_layout:
            pad = np.zeros((1, t_b), np.int32)
            pad[0, :t_p] = req.prompt
            with self._span("generation.prefill_dispatch", bucket=t_b):
                k, v, first = self._prefill_fn(t_b)(params, pad, *sampling)
            with self._span("generation.prefill_readback"):
                first = int(first)
            return k, v, first, req.t_submit
        c_rows = self._quantum
        pad = np.zeros(t_b, np.int32)
        pad[:t_p] = req.prompt
        row = np.full(self.config.max_pages_per_seq, SCRATCH_PAGE, np.int32)
        tbl = self.kv.table(req.rid)
        row[: len(tbl)] = tbl
        at_slot = (np.int32(slot),) if self.kv.slot_rows else ()
        for ci in range(t_b // c_rows):
            with self._span("generation.prefill_dispatch", chunk=ci):
                out = self._prefill_fn(ci)(
                    params, *self._program_state(), row,
                    pad[ci * c_rows:(ci + 1) * c_rows], *sampling,
                    *at_slot)
                self._rebind_state(out[:-1])
        self._count_selection(np.arange(1, t_p + 1))
        with self._span("generation.prefill_readback"):
            got = np.asarray(out[-1]).reshape(-1)
        if self._hybrid:
            # the rows the cross-decoder skipped, by the rows the last
            # chunk reports it ran
            self._prefill_rows += t_p
            self._cross_skipped += max(t_p - int(got[1]), 0)
        return None, None, int(got[0]), req.t_submit

    def _count_selection(self, contexts) -> None:
        """Query rows at the contexts ``contexts`` (each row's own
        included) went through the stack: per indexer layer a row's whole
        context was scored and the top ``index_topk`` of it (all of it
        while shorter) selected."""
        self._dsa_scored += self._dsa_layers * int(contexts.sum())
        self._dsa_selected += self._dsa_layers * int(
            np.minimum(contexts, self._dsa_topk).sum())

    # -- what every program carries ---------------------------------------
    def _program_state(self) -> tuple:
        """The device arrays every program that writes them takes DONATED
        and returns first: the pool, and the device counts where the
        stack keeps any (`_fresh_device_counts`)."""
        extra = (() if self._device_counts is None
                 else (self._device_counts,))
        return self.kv.pool() + extra

    def _rebind_state(self, out) -> None:
        n = len(self.kv.pool())
        self.kv.rebind(*out[:n])
        if self._device_counts is not None:
            self._device_counts = out[n]

    def _fresh_device_counts(self):
        """What the programs count on the device, int32: a row per expert
        layer (its assignments to each held expert, then those to experts
        held elsewhere) and, for a latent stack, one more: the prefill
        kernel's tiles ``[run, skipped]`` of its full layers, then of its
        window layers (`_tiles_row`); with layers that select nothing, one
        more: the latent rows the decode steps attended ``[full, window]``
        (`_rows_row`)."""
        rows = (len(self._moe_index) + (self._tiles_row is not None)
                + (self._rows_row is not None))
        if not rows:
            return None
        width = self._stack.final._held() + 1 if self._moe_index else 2
        if self._plain_latent:
            width = max(width, 2 * len(LAYER_KINDS))
        return jnp.zeros((rows, width), jnp.int32)

    def _revive_state(self, wait: bool = False) -> bool:
        """After a failed dispatch: the pool anew if it was consumed, and
        the device counts with it (they restart at zero).  True when
        the pool was made anew: no cached row of any stream survives."""
        dead = self.kv.revive(wait=wait)
        c = self._device_counts
        if c is not None and c.is_deleted():
            self._device_counts = self._fresh_device_counts()
            self._device_flushed = None
        return dead

    def _run_blocks(self, params, x, attend_of):
        """The stack's blocks over rows x, block ``li`` with the
        caller's side ``attend_of(li)`` (`ops/generation.block`)."""
        for li, cfg in enumerate(self._stack.blocks):
            x = block(cfg, block_params(params, cfg), x, attend_of(li))
        return x

    def _counts_sink(self, state):
        """The device counts a program carries — the last of its donated
        arguments ``state`` where the stack counts any — as a one-element
        list (empty without) and the ``counts_to`` that adds an expert
        layer's assignment counts into it."""
        counts = [state[-1]] if self._device_counts is not None else []

        def counts_to(cfg, new):
            counts[0] = counts[0].at[self._moe_index[cfg.name]].add(new)

        return counts, counts_to

    def _make_prefill_chunk(self, ci: int):
        """Prefill chunk ``ci`` of a stack over row pools: the prompt's
        rows ``[ci * C, (ci + 1) * C)`` (C = the prefill quantum) against
        the stream's context so far, which the earlier chunks left in the
        pool.  One program per chunk INDEX — its context length is static
        — so a prompt of n chunks runs programs 0..n-1 and the mix's
        longest prompt warms them all.  Per layer: the chunk's rows are
        written into the stream's pages, the context ``[0, (ci + 1) * C)``
        is read back through the page table, a full layer scores it with
        the indexer and keeps each query's exact top ``index_topk`` as a
        mask (carried to the shared layers after it, with the bitmap of
        the tiles it keeps any pair of), and the chunk attends under that
        mask through `ops/dsa_prefill_attention` — the ``dsa_prefill_attn``
        kernel on a TPU, which counts its tiles run and skipped into the
        device counts: no (heads, T, T) array exists.  Rows past the prompt's
        end are pad: causal attention keeps them from every real row, and
        the decode step overwrites their cached rows as the stream grows.
        Every chunk returns the token sampled after row ``prompt_len - 1``;
        the caller reads the last chunk's."""
        stack, ps = self._stack, self.kv.page_size
        c_rows = self._quantum
        start, ctx = ci * c_rows, (ci + 1) * c_rows
        new_pg = slice(start // ps, ctx // ps)
        n_state = len(self._program_state())
        bq = PREFILL_QUERY_BLOCK
        interp = self.config.attention_interpret
        names = list(self._state_at)

        def prefill_chunk(params, *rest):
            state = rest[:n_state]
            page_row, toks, prompt_len, seed, temp, top_k, *at_slot = rest[
                n_state:]
            # the programs address a layer's pages in the pool flattened
            # over (layer, page), so none slices a layer out of it
            pools = dict(zip(names, state))
            positions = start + jnp.arange(c_rows)
            x = embed_tokens(stack, params, toks, positions,
                             _act_dtype(self.model))
            carried = []
            masks = {}

            def through_pages(name, at, rows):
                """Write the chunk's rows of one layer into the stream's
                pages, and read the context back."""
                pool = pools[name]
                tail = pool.shape[2:]
                pages = at * self.kv.num_pages + page_row
                flat = pool.reshape((-1,) + tail)
                flat = flat.at[pages[new_pg]].set(
                    _to_row_width(rows, pool).reshape((-1,) + tail))
                pools[name] = flat.reshape(pool.shape)
                return flat[pages[: ctx // ps]].reshape((ctx,) + tail[1:])

            def plain(cfg, q, latent, wkvb):
                """A layer that selects nothing: a full one attends the
                stream's whole context through its pages; a window one
                its ring, as the chunk before left it, and the chunk's own
                rows, the ring written after from the real rows only."""
                at = self._pool_index[cfg.name]
                w = cfg.window
                if not w:
                    context = through_pages("latent", at["latent"], latent)
                    key_pos = jnp.arange(ctx)
                else:
                    # ring row r holds position start - w + r (a chunk
                    # starts at a multiple of the window); the ring is
                    # padded in front to whole kernel tiles
                    li = at[LATENT_RING]
                    ring = pools[LATENT_RING][li, at_slot[0]]
                    new = latent.astype(ring.dtype)
                    lead = -(-w // tile_rows(c_rows)) * tile_rows(c_rows) - w
                    context = jnp.concatenate(
                        [jnp.zeros((lead,) + ring.shape[1:], ring.dtype),
                         ring, new])
                    key_pos = jnp.concatenate([
                        jnp.full(lead, -1, jnp.int32),
                        start - w + jnp.arange(w, dtype=jnp.int32),
                        positions.astype(jnp.int32)])
                    e = start + _real_rows(prompt_len, start, c_rows)
                    p_r = e - w + jnp.mod(jnp.arange(w) - e, w)
                    pools[LATENT_RING] = pools[LATENT_RING].at[
                        li, at_slot[0]].set(jnp.where(
                            (p_r >= start)[:, None],
                            new[jnp.clip(p_r - start, 0, c_rows - 1)], ring))
                if w not in masks:
                    masks[w] = carry(cfg, window_keep(
                        positions, key_pos, w) & (key_pos >= 0)[None, :],
                        self.config.attention_impl)
                mask, live = masks[w]
                if live is not None:
                    col = 2 * LAYER_KINDS.index("window" if w else "full")
                    counts[0] = counts[0].at[self._tiles_row,
                                             col:col + 2].add(
                        tile_counts(live, cfg.n_heads, cfg.groups))
                return dsa_prefill_attention(cfg, q, context, wkvb, mask,
                                             live, interpret=interp)

            def attend(cfg, q, latent, index, wkvb):
                if cfg.indexer == "none":
                    return plain(cfg, q, latent, wkvb)
                at = self._pool_index[cfg.name]
                context = through_pages("latent", at["latent"], latent)
                if index is not None:
                    q_i, k_i, w = index
                    keys = through_pages("index_key", at["index_key"],
                                         k_i)[:, :k_i.shape[-1]]
                    seen = jnp.arange(ctx)
                    with jax.named_scope("dsa_index"):
                        carried[:] = carry(cfg, blocked(
                            lambda qb, wb, pb: topk_mask(
                                index_scores(qb, wb, keys),
                                seen[None, :] <= pb[:, None],
                                cfg.index_topk),
                            bq, q_i, w, positions),
                            self.config.attention_impl)
                mask, live = carried
                if live is not None:
                    counts[0] = counts[0].at[self._tiles_row, :2].add(
                        tile_counts(live, cfg.n_heads))
                return dsa_prefill_attention(cfg, q, context, wkvb, mask,
                                             live, interpret=interp)

            counts, counts_to = self._counts_sink(state)
            rows = LatentRows(attend, positions, positions < prompt_len,
                              counts_to, MOE_ROW_TILE)
            x = self._run_blocks(params, x, lambda li: rows)
            last = jnp.clip(prompt_len - 1 - start, 0, c_rows - 1)
            first = _sample_tokens(
                _head_logits(stack, params, x[last])[None], temp[None],
                top_k[None],
                jax.random.fold_in(jax.random.key(seed), 0)[None])[0]
            return (*pools.values(), *counts, first)

        return jax.jit(prefill_chunk,
                       donate_argnums=tuple(range(1, 1 + n_state)))

    def _make_hybrid_prefill_chunk(self, ci: int):
        """Prefill chunk ``ci`` of a hybrid stack (`HybridDecoder`): the
        prompt's rows ``[ci * C, (ci + 1) * C)`` through the self-decoder,
        carrying the slot's state from the chunk before and stopping it at
        ``prompt_len`` (`ops/hybrid.prefill_rows`; chunk 0 starts from
        zeros, which is the slot's reset).  The full layer's keys and
        values of every row go into the stream's pages of the shared pool;
        its attention and every layer after it run only for the prompt's
        LAST row, in the chunk that holds it (a ``lax.cond``): nothing
        they compute is cached, and only that row's logits are wanted.
        Returns the state, then (the token sampled after the last row, the
        rows the cross-decoder ran: 1 in that chunk, 0 in the others)."""
        stack, ps = self._stack, self.kv.page_size
        c_rows = self._quantum
        start, ctx = ci * c_rows, (ci + 1) * c_rows
        new_pg = slice(start // ps, ctx // ps)
        n_state = len(self._program_state())
        names = list(self._state_at)
        at_full = [b.kind for b in stack.blocks].index("full")
        full = stack.blocks[at_full]
        dt = _act_dtype(self.model)

        def prefill_chunk(params, *rest):
            state = rest[:n_state]
            page_row, toks, prompt_len, seed, temp, top_k, slot = rest[
                n_state:]
            pools = dict(zip(names, state))
            x = embed_tokens(stack, params, toks,
                             start + jnp.arange(c_rows), dt)
            rows = prefill_rows(
                pools, self._pool_index, slot, start,
                _real_rows(prompt_len, start, c_rows), fresh=ci == 0,
                dt=dt, query_block=PREFILL_QUERY_BLOCK)
            for cfg in stack.blocks[:at_full]:
                x = block(cfg, block_params(params, cfg), x, rows)
            # the full layer's keys and values of every row, into the pages
            pool = pools[SHARED_KV]
            tail = pool.shape[2:]
            flat = pool.reshape((-1,) + tail).at[page_row[new_pg]].set(
                full_kv_rows(full, block_params(params, full), x, pool,
                             dt).reshape((-1,) + tail))
            pools[SHARED_KV] = flat.reshape(pool.shape)
            last = jnp.clip(prompt_len - 1 - start, 0, c_rows - 1)

            def last_row(_):
                y = x[last][None]           # the rows the cross-decoder runs
                tail_rows = context_rows(
                    flat[page_row[: ctx // ps]].reshape(ctx, -1),
                    prompt_len, rows.memory[0][last][None])
                ran = y.shape[0]
                for cfg in stack.blocks[at_full:]:
                    y = block(cfg, block_params(params, cfg), y, tail_rows)
                token = _sample_tokens(
                    _head_logits(stack, params, y), temp[None], top_k[None],
                    jax.random.fold_in(jax.random.key(seed), 0)[None])[0]
                return jnp.stack([token, jnp.int32(ran)])

            out = jax.lax.cond(prompt_len <= ctx, last_row,
                               lambda _: jnp.zeros(2, jnp.int32), None)
            return (*pools.values(), out)

        return jax.jit(prefill_chunk,
                       donate_argnums=tuple(range(1, 1 + n_state)))

    # -- the decode step's `attend`, by what the pool holds ------------------
    def _kv_step_attend(self, c: int, pool: list, at: "_StepRows",
                        counts_to, counts):
        """Keys and values: layer ``li``'s rows into the pool (the int8
        twin and its scales included), then the chunk against it through
        the paged kernel.  The pool is read and written in place
        (`layer=li`, `.at[li, ...]`): the step never holds a second pool
        or a per-layer piece of one."""
        quant = self.kv.kv_dtype == "int8"
        impl = self.config.attention_impl
        interp = self.config.attention_interpret
        n_slots = self.config.slots
        page_of, row_of = at.page_of, at.row_of

        def attend(li, q, k_t, v_t):
            kp, vp, ksc, vsc = pool
            if quant:
                kq, k_sc = quantize_page_rows(k_t)
                vq, v_sc = quantize_page_rows(v_t)
                kp = kp.at[li, page_of, row_of].set(kq)
                vp = vp.at[li, page_of, row_of].set(vq)
                ksc = ksc.at[li, page_of, row_of].set(k_sc)
                vsc = vsc.at[li, page_of, row_of].set(v_sc)
            else:
                # the scales are None for an f32 pool
                kp = kp.at[li, page_of, row_of].set(k_t.astype(kp.dtype))
                vp = vp.at[li, page_of, row_of].set(v_t.astype(vp.dtype))
            pool[:] = kp, vp, ksc, vsc
            return paged_attention_chunk(
                q.astype(jnp.float32).reshape((n_slots, c) + q.shape[1:]),
                kp, vp, at.page_tbl, at.attend_lens, k_scale=ksc,
                v_scale=vsc,
                layer=li, impl=impl, interpret=interp,
            )

        return lambda li: functools.partial(attend, li)

    def _row_step_attend(self, c: int, pool: list, at: "_StepRows",
                         counts_to, counts):
        """Latent rows and indexer keys: per layer the c rows of every
        slot are written into the latent pool, a full layer scores the
        slot's WHOLE context with the indexer (its cached keys, through
        the page table), takes each row's exact top ``index_topk`` and
        hands the selection to the shared layers after it, and every
        layer gathers just the selected latent rows through the page
        table and attends them with ``W_kvb`` folded into the query and
        the output.  A layer's rows are addressed in the pool flattened
        over (layer, page, row), so none slices a layer out of it.

        A layer that selects nothing attends ABSORBED queries
        (`ops/latent.absorb_queries`): a full one the slot's whole context
        in the pool, through the ``latent_paged_attn`` kernel, which reads
        each slot's live pages in place (one row per slot); a window one
        its ring, whose row ``position mod window`` it writes first, over
        ``min(position + 1, window)`` rows.
        The rows each kind attended are counted into the device counts."""
        ps = self.kv.page_size
        n_slots, mp = self.config.slots, self.config.max_pages_per_seq
        cap, n = mp * ps, n_slots * c
        per_layer = self.kv.num_pages * ps               # rows of one layer
        at_pool = {name: i for i, name in enumerate(self.kv.rows)}
        page_tbl = at.page_tbl
        tbl = jnp.repeat(page_tbl, c, axis=0)                     # (n, mp)
        row_at = at.page_of * ps + at.row_of
        # an idle slot attends its one scratch row (finite garbage nobody
        # reads): a softmax over no row at all is not a number
        seen = (jnp.arange(cap)[None, :]
                < jnp.maximum(at.attend_lens.reshape(n), 1)[:, None])
        carried = []

        def write(name, layer, new):
            stored = pool[at_pool[name]]
            flat = stored.reshape((-1,) + stored.shape[3:])
            flat = flat.at[layer * per_layer + row_at].set(
                _to_row_width(new, stored))
            pool[at_pool[name]] = flat.reshape(stored.shape)
            return flat, layer * per_layer

        def plain(cfg, q, latent, wkvb):
            if c != 1:
                raise NotImplementedError(
                    "a latent layer that selects nothing decodes one row a "
                    "slot: no verify chunk (speculation) reaches it")
            held = self._pool_index[cfg.name]
            w = cfg.window
            qc = absorb_queries(cfg, q, wkvb, pool[0].dtype)
            if w:
                ring_at = self._state_at[LATENT_RING]
                li = held[LATENT_RING]
                rings = pool[ring_at].at[li, jnp.arange(n_slots),
                                         at.positions % w].set(
                    latent.astype(pool[ring_at].dtype))
                pool[ring_at] = rings
                lens = jnp.minimum(at.positions + 1, w)
                o_lat = attend_latent_rows(
                    qc, rings[li], jnp.arange(w)[None, :] < lens[:, None],
                    cfg.kv_lora_rank)
            else:
                write("latent", held["latent"], latent)
                lens = at.attend_lens.reshape(n)
                o_lat = latent_paged_attention(
                    qc, pool[at_pool["latent"]], page_tbl, lens,
                    layer=held["latent"], lk=cfg.kv_lora_rank,
                    impl=self.config.attention_impl,
                    interpret=self.config.attention_interpret)
            col = LAYER_KINDS.index("window" if w else "full")
            counts[0] = counts[0].at[self._rows_row, col].add(
                jnp.sum(jnp.where(at.active, lens, 0)))
            return absorbed_values(cfg, o_lat, wkvb)

        def attend(cfg, q, latent, index, wkvb):
            if cfg.indexer == "none":
                return plain(cfg, q, latent, wkvb)
            held = self._pool_index[cfg.name]
            cached, base = write("latent", held["latent"], latent)
            if index is not None:
                q_i, k_i, w = index
                keys, k_base = write("index_key", held["index_key"], k_i)
                with jax.named_scope("dsa_index"):
                    # the slot's context keys, page by page
                    ctx = keys.reshape((-1, ps) + keys.shape[1:])[
                        k_base // ps + page_tbl].reshape(
                            n_slots, cap, -1)[..., :k_i.shape[-1]]
                    scores = jax.vmap(index_scores)(
                        q_i.reshape((n_slots, c) + q_i.shape[1:]),
                        w.reshape(n_slots, c, -1), ctx).reshape(n, cap)
                with jax.named_scope("dsa_topk"):
                    best, where = jax.lax.top_k(
                        jnp.where(seen, scores, -jnp.inf),
                        min(cfg.index_topk, cap))
                    chosen = tbl[jnp.arange(n)[:, None],
                                 where // ps] * ps + where % ps
                carried[:] = [(chosen, best > -jnp.inf)]
            chosen, valid = carried[0]
            return attend_gathered(cfg, q, cached[base + chosen], valid,
                                   wkvb)

        rows = LatentRows(attend, at.positions, at.active, counts_to,
                          MOE_ROW_TILE)
        return lambda li: rows

    def _hybrid_step_attend(self, c: int, pool: list, at: "_StepRows",
                            counts_to, counts):
        """A hybrid stack's state, one row per slot (`ops/hybrid.step_rows`
        over the pools by name): the full layer's row lands where the
        step's write guard put it, and it and every cross layer read the
        shared pool through the ``shared_kv_attn`` kernel."""
        lens = at.attend_lens.reshape(self.config.slots)

        def read_shared(q, kv, kp):
            return shared_kv_attention(
                q, kv, at.page_tbl, lens, kv_pairs=kp,
                impl=self.config.attention_impl,
                interpret=self.config.attention_interpret)

        rows = step_rows(_PoolView(pool, self._state_at), self._pool_index,
                         at.positions, at.page_of, at.row_of, read_shared,
                         _act_dtype(self.model))
        return lambda li: rows

    def _make_step(self, c: int = 1):
        """The decode program: ONE dispatch advances every slot by a
        ``c``-token chunk through the paged pool.  ``c == 1`` is the
        plain step (``toks``: (S,), the slots' last tokens); ``c ==
        spec_k + 1`` the speculative verify-once pass (``toks``: (S, c),
        the last token plus k draft proposals) — shaped like a short
        prefill, compiled once, so speculation adds one program.

        Chunk row ``j`` of slot ``s`` writes its cached rows at sequence
        position ``seq_len + j`` and attends positions ``< seq_len + j +
        1`` (all c rows are written before the chunk attends; the
        attended lengths express the in-chunk causality), so
        its logits are bit-equal to what ``j`` sequential plain steps
        over the same tokens would produce.  Row ``j``'s token is
        sampled with the baseline key ``fold_in(key(seed),
        gen_count + j)`` — the exact `_slot_keys` schedule — which is
        what makes the harvested accept-prefix + corrected/bonus token
        BYTE-identical to plain decode at any temperature, not merely
        distribution-identical.

        Slots, positions, the write guard, the head and the sampling are
        the same for every stack; what a layer caches and which cached
        rows a row attends is the `attend` that goes with the pool's
        layout (`_kv_step_attend`, `_row_step_attend`)."""
        stack, ps = self._stack, self.kv.page_size
        n_slots, mp = self.config.slots, self.config.max_pages_per_seq
        cap, n = mp * ps, n_slots * c
        n_pool = len(self.kv.pool())
        n_state = len(self._program_state())
        step_attend = (self._kv_step_attend if self.kv.kv_layout
                       else self._hybrid_step_attend if self._hybrid
                       else self._row_step_attend)
        # a per-slot value, once for each of the slot's c chunk rows
        rows = lambda a: jnp.repeat(a, c, axis=0)

        # the pool (and the expert counts) are DONATED and come back first
        def step(params, *rest):
            state = rest[:n_state]
            (page_tbl, seq_lens, toks, seeds, gen_counts, temps,
             top_ks) = rest[n_state:]
            active = seq_lens > 0
            # flattened (S*c, ...) throughout so every matmul keeps the
            # plain step's 2-D shape (only M grows, S -> S*c)
            pos2 = seq_lens[:, None] + jnp.arange(c)[None, :]
            pos_idx = pos2.reshape(n)                # write positions
            x = embed_tokens(stack, params, toks.reshape(n), pos_idx,
                             _act_dtype(self.model))
            # write guard: a row past the table capacity lands on the
            # scratch page — NEVER index-clamp into a real page, that
            # would clobber a live row; rows within capacity but past
            # the allocated table hit entries that are already
            # SCRATCH_PAGE.  Accepted rows always fit (emit <= the
            # admission-funded budget), so only rejected-tail garbage
            # ever spills — and at c == 1 nothing does.
            write_ok = pos_idx < cap
            page_of = jnp.where(
                write_ok,
                rows(page_tbl)[jnp.arange(n),
                               jnp.minimum(pos_idx // ps, mp - 1)],
                SCRATCH_PAGE,
            )
            row_of = jnp.where(write_ok, pos_idx % ps, 0)
            # each row attends its prefix, itself included; idle slots 0
            attend_lens = jnp.where(active[:, None],
                                    jnp.minimum(pos2 + 1, cap), 0)
            pool = list(state[:n_pool])
            counts, counts_to = self._counts_sink(state)
            x = self._run_blocks(params, x, step_attend(
                c, pool, _StepRows(page_tbl, page_of, row_of, attend_lens,
                                   pos_idx, rows(active)), counts_to,
                counts))
            logits = _head_logits(stack, params, x)
            keys = _slot_keys(
                rows(seeds),
                (gen_counts[:, None] + jnp.arange(c)[None, :]).reshape(n),
            )
            nxt = _sample_tokens(logits, rows(temps), rows(top_ks), keys)
            nxt = jnp.where(rows(active), nxt, 0)
            return (*pool, *counts, nxt.reshape(toks.shape))

        # the names the profile is read by: `jit_step`, `jit_verify`
        step.__name__ = step.__qualname__ = "step" if c == 1 else "verify"
        return jax.jit(step, donate_argnums=tuple(range(1, 1 + n_state)))

    # -- the decode loop ---------------------------------------------------
    def _loop(self, my_gen: int) -> None:
        try:
            while not self._stop.is_set():
                with self._mu:
                    if self._loop_gen != my_gen:
                        return
                    n_active = sum(
                        r is not None for r in self._slot_req)
                self._refill(my_gen, block=(n_active == 0))
                with self._mu:
                    if self._loop_gen != my_gen:
                        return
                    n_active = sum(
                        r is not None for r in self._slot_req)
                if n_active == 0:
                    continue
                self._decode_step(my_gen)
            if self._flying is not None:
                # stopped: the step in flight still hands out its tokens
                self._drain(my_gen, self._flying, "stop")
        except Exception as exc:                      # never die silently
            log.exception("generation loop died")
            with self._mu:
                if self._loop_gen == my_gen:
                    self._flying = None
                    self._fail_active_locked(
                        ServingError(f"generation loop died: {exc}"))

    def _free_slots(self) -> list[int]:
        return [i for i, r in enumerate(self._slot_req) if r is None]

    def _refill(self, my_gen: int, block: bool) -> None:
        """Admit queued streams into free slots — the continuous-batching
        join point, strictly BETWEEN decode steps."""
        free = self._free_slots()
        if not free:
            return
        if self.queue.depth == 0 and not block:
            return
        if self._flying is not None:
            # an admission writes the slots the step in flight was built
            # from, and a sleep in the queue would leave it unread
            self._drain(my_gen, self._flying,
                        "admit" if self.queue.depth else "idle")
            free = self._free_slots()
        if block:
            # no slot is live: the engine thread sleeps in the queue
            with self._span("generation.wait_for_work"):
                batch = self._take(len(free))
        else:
            # the hand-over while streams are live (args known at its end)
            with self._span("generation.take") as sp:
                batch = self._take(len(free))
                sp.args["taken"] = len(batch)
                sp.set_metadata(taken=len(batch))
        if batch:
            # every live stream waits for this span: no decode step runs
            # until the last admission returns
            with self._span("generation.refill", taken=len(batch)) as sp:
                self._admit_batch(my_gen, batch, sp.t0)

    def _take(self, n: int) -> list:
        return self.queue.take_batch(
            n, linger_s=0.0, stop=self._stop, poll_s=self.config.poll_s)

    def _admit_batch(self, my_gen: int, batch: list,
                     t_taken: float) -> None:
        for req in batch:
            q0 = req.t_offer if req.t_offer is not None else req.t_submit
            wait = max(0.0, t_taken - q0)
            first_take = "queue" not in req.lat
            req.lat["queue"] = wait
            if first_take:
                # cancelled streams keep the segment too: a client
                # disconnect mid-queue still yields a complete chain
                self._trace_segment(req, "generation.admit", q0, wait)
            if req.cancelled:
                self._finish(req, "cancelled",
                             ServingRejected("shutdown", "cancelled"))
                continue
            slot = self._free_slots()
            if not slot:                  # more takes than slots freed
                self._offer_back(req)
                continue
            with self._span("generation.admit_to_slot", slot=slot[0]):
                self._admit_to_slot(my_gen, slot[0], req)

    def _offer_back(self, req: GenerationRequest) -> None:
        if not self.queue.offer(req):
            self._finish(req, "queue_full",
                         ServingRejected("queue_full", "requeue failed"))

    def _admit_to_slot(self, my_gen: int, slot: int,
                       req: GenerationRequest) -> None:
        t_p = req.prompt.shape[0]
        if req.prefilled is None:
            t_b = bucket_length(t_p, self._quantum)
        else:
            t_b = int(req.prefilled["k"].shape[1])
        span = max(t_b, t_p + req.max_new)
        try:
            self.kv.alloc(req.rid, self.kv.pages_for(span))
        except KVPoolExhausted as exc:
            # the explicit 429 — the stream never stalls waiting on HBM
            self._finish(req, "kv_exhausted",
                         ServingRejected("kv_exhausted", str(exc)))
            try:
                self.flight.note_kv_exhausted()
            except Exception as e:
                log.debug("kv spike note failed: %s", e)
            return
        req.pages = self.kv.pages_for(span)
        if self.kv.slot_rows:
            self.kv.claim_slot(req.rid, slot)
        if self._req_spec_k(req) > 0:
            # best-effort overhang so draft rows land in real pages;
            # a short pool (or a full page table) just means drafts
            # spill to scratch-masked rows (correct, slightly
            # wasteful) — never a 429
            table_cap = self.config.max_pages_per_seq * self.kv.page_size
            self.kv.reserve_speculative(
                req.rid, min(span + self.spec_k, table_cap))
        try:
            if req.prefilled is None:
                faults.maybe_fail("serving.prefill")
                with self._span("generation.prefill", bucket=t_b) as sp:
                    k, v, first, _ = self._run_prefill(req, slot)
                req.lat["prefill"] = sp.dur
                self._trace_segment(req, "generation.prefill",
                                    sp.t0, sp.dur, bucket=t_b)
                hand_t0 = None
            else:
                k, v = req.prefilled["k"], req.prefilled["v"]
                first = req.prefilled["first_token"]
                hand_t0 = req.prefilled.get("t_done_pc")
            with self._span("generation.kv_handoff") as sp:
                # chunk programs wrote the stream's pages themselves
                tbl = (np.asarray(self.kv.table(req.rid), np.int32)
                       if k is None else
                       self.kv.write_prefill(req.rid, k, v))
            t_w1 = sp.t0 + sp.dur
            # cross-replica handoff spans from the PREFILL replica's
            # completion mark (perf_counter is comparable in-process);
            # the lat entry excludes the decode-side queue wait the
            # "queue" segment already owns
            transfer = (max(0.0, req.t_offer - hand_t0)
                        if hand_t0 is not None and req.t_offer is not None
                        else 0.0)
            req.lat["handoff"] = transfer + sp.dur
            span_t0 = hand_t0 if hand_t0 is not None else sp.t0
            self._trace_segment(req, "generation.kv_handoff", span_t0,
                                max(0.0, t_w1 - span_t0), pages=len(tbl))
        except Exception as exc:
            self.kv.release(req.rid)
            self._finish(req, "error",
                         ServingError(f"prefill failed: {exc}"))
            # a program that raised may have consumed the donated pool,
            # and with it every live stream's rows
            with self._mu:
                if (self._loop_gen == my_gen
                        and self._revive_state(wait=True)):
                    self._fail_active_locked(ServingError(
                        f"prefill failed and took the pool: {exc}"))
            return
        with self._span("generation.first_token"):
            self._seat(my_gen, slot, req, first, tbl)

    def _seat(self, my_gen: int, slot: int, req: GenerationRequest,
              first: int, tbl) -> None:
        """The first token out (callbacks, TTFT, the token count), then
        the stream into its slot — or settled here, when that token ends
        it."""
        t_p = req.prompt.shape[0]
        req._record(first)
        self._observe_ttft(req)
        self._count_tokens(1)
        if req.max_new <= 1 or first in req.stop_tokens:
            self.kv.release(req.rid)
            self._finish(req, "ok")
            return
        with self._mu:
            if self._loop_gen != my_gen:
                self.kv.release(req.rid)
                self._finish(
                    req, "error",
                    ServingError("engine respawned during admit"))
                return
            row = np.full(self.config.max_pages_per_seq, SCRATCH_PAGE,
                          np.int32)
            row[: len(tbl)] = tbl
            self._page_tbl[slot] = row
            self._seq_lens[slot] = t_p
            self._last_tok[slot] = first
            self._gen_counts[slot] = 1
            self._temps[slot] = req.temperature
            self._top_ks[slot] = req.top_k
            self._seeds[slot] = np.uint32(req.seed)
            self._slot_req[slot] = req
            req.t_slot = time.perf_counter()

    def _decode_step(self, my_gen: int) -> None:
        """One turn of the decode loop.  With step n in flight it builds
        and dispatches step n + 1 and only then reads step n back, as
        four spans in a row on the engine thread with NO span around them
        (a gap of the device between two steps straddles all four; a
        parent would take every such gap for itself):
        ``generation.decode_prepare`` (fault consult, drafts, argument
        copies, `_serving_params` — the hot-swap boundary — watchdog arm)
        -> ``generation.decode_dispatch`` (the jit call and the donated
        pool's rebinding from its result; annotated with the live slots
        and the KV rows the step attends) -> ``generation.decode_readback``
        (the blocking ``np.asarray`` of the OLDER step's tokens) ->
        ``generation.harvest`` (stop conditions, callbacks, page release,
        slot free).  With nothing in flight the turn ends after
        the dispatch and the next one lands on top of it — unless
        `_must_drain` says the host needs this step's tokens first, when
        all four spans are of one step; with a step in flight and
        `_must_drain` the turn is its readback and harvest alone.  Either
        way such a landing is a ``generation.drain`` (`_drain`).

        A step that cannot be built or dispatched leaves the one in
        flight good: its tokens go out, then the streams fail once.  A
        readback that fails takes the step dispatched on top of it along
        (`_land`)."""
        prev = self._flying
        if prev is not None:
            reason = self._must_drain()
            if reason:
                self._drain(my_gen, prev, reason)
                return
        try:
            with self._span("generation.decode_prepare"):
                plan = self._prepare_step(my_gen, prev)
            if plan is None:
                return                  # a stale loop: `_on_wedged` owns it
            fn, params, args, n_live, rows, harvest = plan
            with self._span("generation.decode_dispatch",
                            slots=n_live, rows=rows) as disp:
                out = fn(params, *self._program_state(), *args)
                # the pool was donated: what `kv` held is dead from here
                # on, so the result becomes the pool before anything can
                # fail.  A loop that `_on_wedged` replaced meanwhile has
                # had its pool revived and drops this one
                with self._mu:
                    if self._loop_gen != my_gen:
                        return
                    self._rebind_state(out[:-1])
                    self._flying = step = _Flying(out[-1], harvest)
        except Exception as exc:
            if prev is None or self._land(my_gen, prev):
                self.watchdog.disarm(None)
                self._step_failed(my_gen, exc)
            return
        if prev is not None:
            self._land(my_gen, prev, disp)
        else:
            reason = self._must_drain()
            if reason:
                self._drain(my_gen, step, reason, disp)

    def _must_drain(self) -> Optional[str]:
        """With a step in flight: must the loop read it back before it
        builds another?  The reason (one of `DRAIN_REASONS`) where the
        next action needs the host's view whole — a drafter proposes from
        host tokens, a stop wants every token out, a slot that is free
        (or ends by count with the step in flight) while a request waits
        is an admission, and when every stream ends with the step in
        flight there is no step to build; None where it need not."""
        if self.drafter is not None:
            return "drafter"
        if self._stop.is_set():
            return "stop"
        with self._mu:
            stays = [r is not None and g + 1 < r.max_new
                     for r, g in zip(self._slot_req, self._gen_counts)]
        if self.queue.depth > 0 and not all(stays):
            return "admit"
        return None if any(stays) else "idle"

    def _drain(self, my_gen: int, step: _Flying, reason: str,
               disp=None) -> None:
        """Land ``step`` so the host can act — not as the second half of
        a turn — under a ``generation.drain {reason}`` span, and count it
        by reason with the span's duration."""
        with self._span("generation.drain", reason=reason) as sp:
            self._land(my_gen, step, disp)
        counts = self._drains[reason]
        counts[0] += 1
        counts[1] += sp.dur

    def _land(self, my_gen: int, step: _Flying, disp=None) -> bool:
        """Read a dispatched step's tokens back and harvest them: the
        second half of a turn, ``disp`` the dispatch span of its first
        half (of the step built on top of this one, or of this step
        itself).  `req.lat["decode_compute"]` is that dispatch plus this
        readback — the turn less prepare and harvest — and the watchdog's
        EWMA is fed the same; it stays armed while another step flies.
        False when the readback failed: the step dispatched on top of
        this one attended rows that never came to be, so both are gone,
        the streams fail ONCE and the pool is revived once."""
        newest = step is self._flying
        try:
            with self._span("generation.decode_readback") as rb:
                toks = np.asarray(step.toks)
        except Exception as exc:
            with self._mu:
                if self._loop_gen == my_gen:
                    self._flying = None
            self.watchdog.disarm(None)
            self._step_failed(my_gen, exc)
            return False
        with self._span("generation.harvest") as hv:
            with self._mu:
                if self._loop_gen != my_gen:
                    return True            # wedged + respawned: stale
                if newest:
                    self._flying = None
            t0, step_s = ((rb.t0, rb.dur) if disp is None
                          else (disp.t0, disp.dur + rb.dur))
            self.watchdog.disarm(None if disp is None else step_s)
            if not newest:
                self.watchdog.arm(self._steps)
            step.harvest(my_gen, toks, t0, step_s, hv.t0)
        return True

    def _prepare_step(self, my_gen: int, prev: Optional[_Flying]):
        """Everything between the loop's decision to step and the jit
        call: returns ``(program, params, host args, live slots, KV rows
        attended, harvest)`` — or None for a stale loop generation; an
        injected fault raises.  The speculative verify program is picked
        when any stream drafted; otherwise the plain one-token program
        (both are warm, so the mix never compiles).

        With ``prev`` in flight the host's view of the slots is one step
        old, and what that step does to it is known: every live slot
        gains one row and one token, and a stream whose count reaches
        ``max_new`` leaves.  The step is built from the view so advanced,
        its tokens are ``prev``'s output as it lies on the device, and
        its harvest remembers who held each slot."""
        faults.maybe_fail("serving.decode")
        drafts = None
        if self.drafter is not None:
            drafts = self._gather_drafts(my_gen)
            if drafts is None:
                # nothing drafted (cold streams, rejection streak, per-
                # request opt-outs, fault fallback)
                with self._stats_lock:
                    self._spec_counts["plain_dispatches"] += 1
        c = 1 if drafts is None else self.spec_k + 1
        with self._mu:
            if self._loop_gen != my_gen:
                return None
            reqs = list(self._slot_req)
            page_tbl = self._page_tbl.copy()
            seq_lens = self._seq_lens.copy()
            gen0 = self._gen_counts.copy()
            seeds = self._seeds.copy()
            temps = self._temps.copy()
            top_ks = self._top_ks.copy()
            if prev is not None:
                toks_in = prev.toks
                for s, req in enumerate(reqs):
                    if req is None:
                        continue
                    if gen0[s] + 1 < req.max_new:
                        seq_lens[s] += 1
                        gen0[s] += 1
                    else:           # ends by count with the step in flight
                        reqs[s] = None
                        page_tbl[s] = SCRATCH_PAGE
                        for a in (seq_lens, gen0, seeds, temps, top_ks):
                            a[s] = 0
            elif drafts is None:
                toks_in = self._last_tok.copy()
            else:
                toks_in = np.zeros((self.config.slots, c), np.int32)
                toks_in[:, 0] = self._last_tok
                dl = np.zeros(self.config.slots, np.int32)
                for s, d in enumerate(drafts):
                    if d is None or d.size == 0:
                        continue
                    m = min(int(d.size), self.spec_k)
                    toks_in[s, 1:1 + m] = d[:m]
                    dl[s] = m
            # built at first use; `jax.jit` construction is lazy, so
            # cheap under the lock (see `_prefill_fn`)
            fn = self._step_fns.get(c)
            if fn is None:
                fn = self._step_fns[c] = self._make_step(c)
        if drafts is not None:
            def harvest(*a):
                self._harvest_verify(*a, toks_in, dl, gen0)
        else:
            harvest = functools.partial(self._harvest_plain, reqs,
                                        self._steps + 1)
            if prev is None:
                # the plain step takes its tokens as a device array, from
                # the host as from the step before: ONE call signature,
                # so one executable.  Placed as the pool is, which comes
                # back from the same programs as the tokens do
                like = self.kv.pool()[0]
                toks_in = jax.device_put(
                    toks_in, like.sharding if like.committed else None)
        args = (page_tbl, seq_lens, toks_in, seeds, gen0, temps, top_ks)

        params = self._serving_params()
        # what the step serves, from the arrays it is dispatched with:
        # a slot is live where seq_len > 0, and attends its seq_len rows
        # plus the c it writes (a verify chunk's union, capped at the
        # page table's span), out of the pages that hold them: the
        # paged kernel's loop runs over exactly those, and an idle slot
        # adds none
        live = seq_lens[seq_lens > 0]
        n_live = int(live.size)
        ps = self.kv.page_size
        cap = self.config.max_pages_per_seq * ps
        attended = np.minimum(live + c, cap)
        rows = int(attended.sum())
        self._steps += 1
        self._overlapped += prev is not None
        self._sampler_steps[_sampler_branch(temps, top_ks)] += 1
        self._slot_steps += n_live
        self._rows_attended += rows
        self._pages_attended += int((-(-attended // ps)).sum())
        if self._hybrid:
            self._shared_rows += rows
            self._window_rows += int(np.minimum(live + 1, self._window).sum())
        if self._dsa_layers:
            self._count_selection(
                np.minimum(live[:, None] + 1 + np.arange(c), cap))
        # the watchdog arms with the chunk width so the EWMA deadline
        # stays per-token-normalized
        self.watchdog.arm(self._steps, n_steps=c)
        return fn, params, args, n_live, rows, harvest

    def _harvest_plain(self, reqs: list, step_no: int, my_gen: int, nxt,
                       t0: float, step_s: float, t_h0: float) -> None:
        """One token for every slot of plain step ``step_no`` whose
        request still holds it: ``reqs`` is who held each slot when the
        step was built.  A stream that ended meanwhile (a stop token or a
        cancel the step before brought to light) left a row nobody takes."""
        with self._mu:
            if self._loop_gen != my_gen:
                return                     # wedged + respawned: stale
            finished: list[tuple[GenerationRequest, bool]] = []
            stepped: list[tuple[GenerationRequest, int]] = []
            n_live = 0
            for s, req in enumerate(reqs):
                if req is None:
                    continue
                if self._slot_req[s] is not req:
                    self._discarded += 1
                    continue
                if req.cancelled:
                    self._clear_slot(s)
                    finished.append((req, False))
                    continue
                n_live += 1
                tok = int(nxt[s])
                req._record(tok)
                self._seq_lens[s] += 1
                self._gen_counts[s] += 1
                self._last_tok[s] = tok
                stepped.append((req, int(self._gen_counts[s])))
                if (self._gen_counts[s] >= req.max_new
                        or tok in req.stop_tokens):
                    self._clear_slot(s)
                    finished.append((req, True))
            if stepped and self._rec.enabled:
                # batch-composition attribution: every co-resident
                # stream gets this step's span, tagged with who shared
                # the dispatch and how far along each stream is
                rids = [r.rid for r, _ in stepped]
                counts = {r.rid: c for r, c in stepped}
                for req, _ in stepped:
                    self._trace_segment(
                        req, "generation.decode_step", t0, step_s,
                        step=step_no, batch=rids,
                        batch_tokens=counts,
                    )
        self._charge_step([r for r, _ in stepped], step_s, t_h0)
        self._count_tokens(n_live)
        self._settle_finished(finished)

    def _charge_step(self, reqs: list, step_s: float, t_h0: float) -> None:
        """Each co-resident stream is charged the full step wall (like
        the shared dispatch segment of /v1/infer; the per-token view
        divides by tokens_generated in stats()) plus the host-side
        sampling bookkeeping: the harvest from its start (`t_h0`) to
        here, before the finishing work the harvest span also covers."""
        samp_s = max(0.0, time.perf_counter() - t_h0)
        for req in reqs:
            req.lat["decode_compute"] = (
                req.lat.get("decode_compute", 0.0) + step_s)
            req.lat["sampling"] = req.lat.get("sampling", 0.0) + samp_s
        if self.breaker is not None:
            self.breaker.record_success()

    def _settle_finished(self, finished: list) -> None:
        for req, ok in finished:
            self.kv.release(req.rid)
            if ok:
                self._finish(req, "ok")
            else:
                self._finish(req, "cancelled",
                             ServingRejected("shutdown", "cancelled"))

    # -- speculative decode ------------------------------------------------
    def _req_spec_k(self, req: GenerationRequest) -> int:
        """Effective draft length for one stream: the engine's k,
        optionally lowered per request, zeroed by the fault-fallback
        latch.  Never above the engine k — the verify program's chunk
        width is static."""
        if self.drafter is None or req.spec_disabled:
            return 0
        k = (self.spec_k if req.spec_k is None
             else min(req.spec_k, self.spec_k))
        return max(0, k)

    def _gather_drafts(self, my_gen: int) -> Optional[list]:
        """Collect draft proposals for every live slot (engine thread,
        between dispatches).  Returns a per-slot list of int32 arrays,
        or None when no stream drafted — the caller falls back to the
        plain one-token program.  The ``serving.draft`` fault site is
        consulted once per drafting stream: ``raise`` latches the
        stream's drafter OFF for the rest of its life (plain decode,
        overhang pages truncated back); ``corrupt`` swaps the proposal
        for deterministic garbage the verify pass must reject with
        output unchanged."""
        with self._mu:
            if self._loop_gen != my_gen:
                return None
            live = list(enumerate(self._slot_req))
            gens = self._gen_counts.copy()
        drafts: list = [None] * self.config.slots
        any_draft = False
        for s, req in live:
            if req is None or req.cancelled:
                continue
            # drafting past the remaining budget is pure waste: the
            # harvest caps emitted tokens at max_new anyway
            k = min(self._req_spec_k(req),
                    req.max_new - int(gens[s]) - 1)
            if k <= 0:
                continue
            try:
                action = faults.maybe_fail("serving.draft")
            except Exception as exc:
                log.warning("drafter disabled for %s: %s", req.rid, exc)
                self._disable_spec(s, req)
                continue
            hist = np.concatenate(
                [req.prompt, np.asarray(req.tokens_so_far(), np.int32)])
            if action == "corrupt":
                # deterministic garbage, independent of the real
                # drafter: rejection sampling must shrug it off
                d = (int(hist[-1]) + 1
                     + np.arange(k, dtype=np.int32) * 17) % self._vocab
                d = d.astype(np.int32)
            else:
                try:
                    d = np.asarray(self.drafter.draft(hist, k),
                                   np.int32).reshape(-1)[:k]
                except Exception as exc:
                    log.warning("drafter failed for %s: %s",
                                req.rid, exc)
                    self._disable_spec(s, req)
                    continue
            if d.size:
                drafts[s] = d
                any_draft = True
        return drafts if any_draft else None

    def _disable_spec(self, s: int, req: GenerationRequest) -> None:
        """Latch one stream to plain decode (the mid-stream fallback)
        and give back its speculative overhang pages — the
        truncate-on-reject rollback, so a disabled drafter can't leak
        reserved capacity for the stream's remaining life."""
        req.spec_disabled = True
        with self._stats_lock:
            self._spec_counts["fallbacks"] += 1
        freed = self.kv.truncate_to(req.rid,
                                    req.pages * self.kv.page_size)
        if freed:
            with self._mu:
                if self._slot_req[s] is req:
                    self._page_tbl[s, req.pages:] = SCRATCH_PAGE

    def _harvest_verify(self, my_gen: int, tgt, t0: float,
                        step_s: float, t_h0: float, chunk, dl,
                        gen0) -> None:
        """The verify-once dispatch scored the (spec_k + 1)-token chunk
        of every live slot: emit each stream's accepted draft prefix
        plus the corrected/bonus sample — 1..k+1 tokens per stream,
        byte-identical to sequential plain decode."""
        sp = {"drafted": 0, "accepted": 0, "rejected": 0, "bonus": 0}
        emitted_total = 0
        with self._mu:
            if self._loop_gen != my_gen:
                return                     # wedged + respawned: stale
            finished: list[tuple[GenerationRequest, bool]] = []
            stepped: list[tuple[GenerationRequest, int, int]] = []
            for s, req in enumerate(self._slot_req):
                if req is None:
                    continue
                if req.cancelled:
                    self._clear_slot(s)
                    finished.append((req, False))
                    continue
                budget = req.max_new - int(gen0[s])
                d_len = int(dl[s])
                row = tgt[s]
                # accept-prefix: row j's target sample IS what plain
                # decode would emit at that position, so a match means
                # the draft token was exactly right; the first
                # mismatch's sample is the corrected token, an all-
                # match chunk appends the bonus sample at row k
                a = 0
                while a < d_len and int(row[a]) == int(chunk[s, a + 1]):
                    a += 1
                emit = min(a + 1, budget)
                toks = [int(row[j]) for j in range(emit)]
                fin = False
                for j, t in enumerate(toks):
                    if t in req.stop_tokens:
                        emit = j + 1
                        toks = toks[:emit]
                        fin = True
                        break
                for t in toks:
                    req._record(t)
                self._seq_lens[s] += emit
                self._gen_counts[s] += emit
                self._last_tok[s] = toks[-1]
                accepted = min(emit, a)
                sp["drafted"] += d_len
                sp["accepted"] += accepted
                sp["rejected"] += d_len - accepted
                sp["bonus"] += emit - accepted
                req.spec_drafted += d_len
                req.spec_accepted += accepted
                emitted_total += emit
                stepped.append((req, int(self._gen_counts[s]), emit))
                if self._gen_counts[s] >= req.max_new or fin:
                    self._clear_slot(s)
                    finished.append((req, True))
            if stepped and self._rec.enabled:
                rids = [r.rid for r, _, _ in stepped]
                counts = {r.rid: n for r, n, _ in stepped}
                emits = {r.rid: e for r, _, e in stepped}
                for req, _, _ in stepped:
                    self._trace_segment(
                        req, "generation.decode_step", t0, step_s,
                        step=self._steps, batch=rids,
                        batch_tokens=counts, emitted=emits,
                        speculative=True,
                    )
        self._charge_step([r for r, _, _ in stepped], step_s, t_h0)
        self._count_tokens(emitted_total)
        self._count_spec(sp, emitted_total)
        self._settle_finished(finished)

    def _count_spec(self, sp: dict, emitted: int) -> None:
        """One verify dispatch's speculative accounting: host counters
        for stats() plus the pre-declared spec metric families."""
        with self._stats_lock:
            for kind, v in sp.items():
                self._spec_counts[kind] += v
            self._spec_counts["emitted"] += emitted
            self._spec_counts["verify_dispatches"] += 1
            drafted = self._spec_counts["drafted"]
            ratio = (self._spec_counts["accepted"] / drafted
                     if drafted else 0.0)
        try:
            from deeplearning4j_tpu.observe.metrics import registry

            reg = registry()
            ctr = reg.counter("dl4jtpu_spec_tokens_total")
            for kind, v in sp.items():
                if v:
                    ctr.inc(v, kind=kind)
            reg.gauge("dl4jtpu_spec_acceptance_ratio").set(
                round(ratio, 4))
            reg.histogram("dl4jtpu_spec_tokens_per_dispatch").observe(
                emitted)
        except Exception as e:
            log.debug("spec metric failed: %s", e)

    def _clear_slot(self, s: int) -> None:
        """Caller holds self._mu.  Pages are released by the caller
        (outside the lock) via kv.release."""
        self._slot_req[s] = None
        self._page_tbl[s, :] = SCRATCH_PAGE
        self._seq_lens[s] = 0
        self._last_tok[s] = 0
        self._gen_counts[s] = 0
        self._temps[s] = 0.0
        self._top_ks[s] = 0
        self._seeds[s] = 0

    # -- failure paths -----------------------------------------------------
    def _step_failed(self, my_gen: int, exc: BaseException) -> None:
        log.error("generation decode step failed: %s", exc)
        tripped = False
        if self.breaker is not None:
            was = self.breaker.state
            self.breaker.record_failure()
            tripped = was != "open" and self.breaker.state == "open"
        with self._mu:
            if self._loop_gen != my_gen:
                return
            # a dispatch that raised may have consumed the donated pool;
            # no stream learns of the failure before the pool is usable
            self._revive_state(wait=True)
            self._fail_active_locked(
                ServingError(f"decode step failed: {exc}"))
        if tripped:
            try:
                self.flight.dump("breaker_open",
                                 context={"error": str(exc)})
            except Exception as e:
                log.debug("breaker flight dump failed: %s", e)

    def _fail_active_locked(self, exc: BaseException,
                            outcome: str = "error") -> None:
        """Caller holds self._mu: fail every in-flight stream and
        release ALL of their pages — the watchdog-abort contract.
        Every stream settles through `_finish`, so aborted streams get
        closed chains, outcome counts and flight records too."""
        for s, req in enumerate(self._slot_req):
            if req is None:
                continue
            self._clear_slot(s)
            self.kv.release(req.rid)
            self._finish(req, outcome, exc)

    def _on_wedged(self, event: dict) -> None:
        """Watchdog stage-3 abort: the dispatched step never returned.
        Fail every in-flight stream, release all their pages, trip the
        breaker, and respawn the loop under a new generation — the
        wedged thread's eventual return sees a stale generation and
        discards itself."""
        log.error("generation decode step wedged: %s", event)
        if self.breaker is not None:
            self.breaker.record_failure()
        with self._mu:
            self._loop_gen += 1
            gen = self._loop_gen
            # the wedged dispatch holds the donated pool: the respawned
            # loop gets a new one (no waiting on a device that is stuck)
            # and no step in flight
            self._flying = None
            self._revive_state()
            self._fail_active_locked(
                ServingError(f"decode step wedged: {event.get('stage')}"),
                outcome="wedged",
            )
        try:
            self.flight.dump("watchdog_abort", context=dict(event))
        except Exception as e:
            log.debug("watchdog flight dump failed: %s", e)
        if not self._stop.is_set():
            self._thread = threading.Thread(
                target=self._loop, args=(gen,),
                name="dl4jtpu-generation", daemon=True,
            )
            self._thread.start()

    # -- the fate point ----------------------------------------------------
    def _finish(self, req: GenerationRequest, outcome: str,
                exc: Optional[BaseException] = None) -> None:
        """Settle one stream EXACTLY ONCE: finalize the latency
        breakdown, record the ``generation.stream`` root span, bump the
        per-outcome counter, offer the stream to the slow ring, append
        the flight record, then release the client (`_fail`/`_complete`).
        Racing settlers (watchdog abort vs stop) claim via `trace_done`
        under the request lock; losers are silent no-ops."""
        with req._lock:
            if req.trace_done:
                return
            req.trace_done = True
            req.outcome = outcome
        t_fate = time.perf_counter()
        latency = max(0.0, t_fate - req.t_submit)
        if req.t_slot is not None:
            resid = (t_fate - req.t_slot
                     - req.lat.get("decode_compute", 0.0)
                     - req.lat.get("sampling", 0.0))
            req.lat["decode_queue"] = max(0.0, resid)
        self._observe_breakdown(req.lat)
        self._count_stream(outcome)
        if req.trace_id is not None and self._rec.enabled:
            args = dict(otrace.trace_args(req.trace_id, req.root_span,
                                          req.root_parent))
            if exc is not None:
                args["error"] = str(exc)
            self._rec.add_complete(
                "generation.stream", req.t_submit, latency,
                cat="generation", outcome=outcome, rid=req.rid,
                tokens=len(req.tokens), **args,
            )
        self._note_slow(req, outcome, latency)
        self._flight_record(req, outcome, latency, exc)
        if exc is not None:
            req._fail(exc)
        else:
            req._complete()

    def _note_slow(self, req: GenerationRequest, outcome: str,
                   latency_s: float) -> None:
        """Offer one settled stream to the slowest-streams exemplar
        ring (bounded, latency-descending — the generation twin of
        server._note_slow)."""
        entry = {
            "kind": "generate",
            "rid": req.rid,
            "trace": (f"{req.trace_id:x}" if req.trace_id is not None
                      else None),
            "trace_id": req.trace_id,
            "outcome": outcome,
            "latency_s": round(latency_s, 6),
            "ttft_s": (round(req.ttft_s, 6) if req.ttft_s is not None
                       else None),
            "tokens": len(req.tokens),
            "t_wall": time.time(),
            "breakdown_s": {k: round(v, 6) for k, v in req.lat.items()},
        }
        with self._stats_lock:
            slow = self._slow
            if len(slow) >= GEN_SLOW_RING_CAP and \
                    latency_s <= slow[-1]["latency_s"]:
                return
            slow.append(entry)
            slow.sort(key=lambda e: -e["latency_s"])
            del slow[GEN_SLOW_RING_CAP:]

    def slow_streams(self, spans: bool = True) -> list[dict]:
        """The slowest-stream exemplars (latency-descending), each with
        its breakdown and — when tracing is on — its full causal span
        chain.  Served at ``GET /api/generation/slow`` and merged into
        ``GET /api/serving/slow``."""
        with self._stats_lock:
            out = [dict(e) for e in self._slow]
        if spans and self._rec.enabled:
            for e in out:
                if e["trace_id"] is not None:
                    e["spans"] = self._rec.trace_chain(e["trace_id"])
        for e in out:
            e.pop("trace_id", None)
        return out

    def _flight_record(self, req: GenerationRequest, outcome: str,
                       latency_s: float,
                       exc: Optional[BaseException]) -> None:
        try:
            self.flight.record({
                "rid": req.rid,
                "trace": (f"{req.trace_id:x}"
                          if req.trace_id is not None else None),
                "outcome": outcome,
                "error": str(exc) if exc is not None else None,
                "prompt_len": int(req.prompt.shape[0]),
                "max_new": req.max_new,
                "tokens": len(req.tokens),
                "ttft_s": req.ttft_s,
                "latency_s": round(latency_s, 6),
                "pages_held": req.pages,
                "breakdown_s": {k: round(v, 6)
                                for k, v in req.lat.items()},
                "t_wall": time.time(),
            })
        except Exception as e:
            log.debug("flight record failed: %s", e)

    def _flight_context(self) -> dict:
        """Engine/KV snapshot merged into every flight dump."""
        return {"stats": self.stats()}

    # -- introspection -----------------------------------------------------
    def active_streams(self) -> int:
        with self._mu:
            return sum(r is not None for r in self._slot_req)

    def drain(self, timeout: float = 30.0) -> bool:
        """Wait until no stream is in flight and the queue is empty —
        True when drained within the timeout."""
        t_end = time.monotonic() + timeout
        while time.monotonic() < t_end:
            if (self.active_streams() == 0 and self.queue.depth == 0
                    and self._flying is None):
                return True
            time.sleep(self.config.poll_s)
        return False

    def stats(self) -> dict:
        with self._mu:
            active = sum(r is not None for r in self._slot_req)
        with self._stats_lock:
            totals = dict(self._lat_totals)
            outcomes = dict(self._stream_outcomes)
            settled = self._streams_settled
            slow_n = len(self._slow)
            spec = dict(self._spec_counts)
        total_s = sum(totals.values())
        counts = self._device_counts_host()
        # per-token normalization: a speculative step emits 1..k+1
        # tokens per dispatch, so cross-config comparisons read the
        # seconds_per_token view, not raw segment walls
        n_tok = max(1, self._tokens_out)
        breakdown = {
            k: {
                "seconds_total": round(v, 6),
                "fraction": (round(v / total_s, 4)
                             if total_s > 0 else 0.0),
                "seconds_per_token": round(v / n_tok, 9),
            }
            for k, v in totals.items()
        }
        drafted = spec["drafted"]
        out = {
            "slots": self.config.slots,
            "active_streams": active,
            "queue_depth": self.queue.depth,
            "decode_steps": self._steps,
            "decode_slot_steps": self._slot_steps,
            "decode_rows_attended": self._rows_attended,
            "decode_pages_attended": self._pages_attended,
            "decode_steps_overlapped": self._overlapped,
            "decode_slot_steps_discarded": self._discarded,
            "decode_drains": {r: {"count": n, "seconds": round(secs, 6)}
                              for r, (n, secs) in self._drains.items()},
            "decode_sampler_steps": dict(self._sampler_steps),
            "serving_params_casts": self._params_casts,
            "dsa": {"rows_scored": self._dsa_scored,
                    "rows_selected": self._dsa_selected},
            "hybrid": {"shared_kv_rows_attended": self._shared_rows,
                       "window_rows_attended": self._window_rows,
                       "prefill_rows": self._prefill_rows,
                       "prefill_rows_skipped_cross": self._cross_skipped},
            "moe": self._moe_stats(counts),
            "dsa_prefill_tiles": self._tiles_stats(counts),
            "latent": self._latent_stats(counts),
            "tokens_generated": self._tokens_out,
            "tokens_per_s": round(self.tokens_per_s(), 4),
            "streams": {"settled": settled, "outcomes": outcomes},
            "latency_breakdown": breakdown,
            "slow_streams": slow_n,
            "flight": {"records": len(self.flight),
                       "dumps": self.flight.dumps_written},
            "kv": self.kv.stats(),
            "speculative": {
                "enabled": self.spec_k > 0,
                "k": self.spec_k,
                "drafter": (self.drafter.name
                            if self.drafter is not None else None),
                "drafted": drafted,
                "accepted": spec["accepted"],
                "rejected": spec["rejected"],
                "bonus": spec["bonus"],
                "acceptance_ratio": (
                    round(spec["accepted"] / drafted, 4)
                    if drafted else 0.0),
                "verify_dispatches": spec["verify_dispatches"],
                "plain_dispatches": spec["plain_dispatches"],
                "tokens_per_dispatch": (
                    round(spec["emitted"] / spec["verify_dispatches"], 4)
                    if spec["verify_dispatches"] else 0.0),
                "fallbacks": spec["fallbacks"],
            },
        }
        return out

    def _device_counts_host(self):
        """The device counts (`_fresh_device_counts`), read now, int64;
        None for a stack that counts nothing on the device.  The engine
        thread donates the array with every dispatch, so a reader that
        catches it mid-step tries again."""
        for _ in range(50):
            a = self._device_counts
            if a is None:
                return None
            try:
                return np.asarray(a).astype(np.int64)
            except RuntimeError:            # donated under the reader
                time.sleep(0.002)
        return None

    def _moe_stats(self, counts) -> Optional[dict]:
        if counts is None or not self._moe_index:
            return None
        counts = counts[:len(self._moe_index)]
        return {"assignments_held": int(counts[:, :-1].sum()),
                "assignments_elsewhere": int(counts[:, -1].sum()),
                "expert_assignments": counts[:, :-1].tolist()}

    def _tiles_stats(self, counts) -> Optional[dict]:
        """The latent prefill kernel's (tile, head group) steps, process
        totals: ``run`` and ``skipped``; over their sum, ``run`` is the
        share of the dense score plane still computed."""
        if counts is None or self._tiles_row is None:
            return None
        run, skipped = counts[self._tiles_row, :2].tolist()
        if self._rows_row is not None:
            run += int(counts[self._tiles_row, 2])
            skipped += int(counts[self._tiles_row, 3])
        return {"run": run, "skipped": skipped}

    def _latent_stats(self, counts) -> Optional[dict]:
        """A stack with latent layers that select nothing: per kind of
        layer (`LAYER_KINDS`), the rows its decode steps attended and the
        prefill kernel's tiles run and skipped, process totals (the rows
        in int32 on the device: read modulo 2^32)."""
        if counts is None or self._rows_row is None:
            return None
        rows = counts[self._rows_row] % 2**32
        tiles = counts[self._tiles_row]
        return {kind: {"rows_attended": int(rows[i]),
                       "tiles_run": int(tiles[2 * i]),
                       "tiles_skipped": int(tiles[2 * i + 1])}
                for i, kind in enumerate(LAYER_KINDS)}

    def health_summary(self) -> dict:
        """Compact generation block for `InferenceServer.health()` —
        the Router (and the fleet push behind it) sees a replica's
        decode pressure and stream outcomes without a /metrics
        scrape."""
        with self._mu:
            active = sum(r is not None for r in self._slot_req)
        with self._stats_lock:
            outcomes = dict(self._stream_outcomes)
            drafted = self._spec_counts["drafted"]
            accepted = self._spec_counts["accepted"]
        out = {
            "active_streams": active,
            "queue_depth": self.queue.depth,
            "kv_occupancy": round(self.kv.occupancy(), 4),
            "tokens_per_s": round(self.tokens_per_s(), 4),
            "stream_outcomes": outcomes,
            "flight_dumps": self.flight.dumps_written,
        }
        if self.spec_k > 0:
            out["spec_acceptance_ratio"] = (
                round(accepted / drafted, 4) if drafted else 0.0)
        return out

    def tokens_per_s(self) -> float:
        """Recent aggregate decode rate over the trailing rate-sample
        window (0.0 until two samples exist)."""
        with self._stats_lock:
            if len(self._rate_samples) < 2:
                return 0.0
            t0, n0 = self._rate_samples[0]
            t1, n1 = self._rate_samples[-1]
        dt = t1 - t0
        return (n1 - n0) / dt if dt > 0 else 0.0

    # -- telemetry ---------------------------------------------------------
    def _count_tokens(self, n: int) -> None:
        if n <= 0:
            return
        self._tokens_out += n
        now = time.perf_counter()
        with self._stats_lock:
            self._rate_samples.append((now, self._tokens_out))
        try:
            from deeplearning4j_tpu.observe.metrics import registry

            registry().counter("dl4jtpu_decode_tokens_total").inc(n)
        except Exception as e:
            log.debug("decode token metric failed: %s", e)

    def _flush_decode_counts(self) -> None:
        """Move what this engine counted since the last flush into the
        process-total counters (scrape time, and once more at stop())."""
        try:
            from deeplearning4j_tpu.observe.metrics import registry

            reg = registry()
            dev = self._device_counts_host()
            with self._stats_lock:
                now = (self._steps, self._slot_steps, self._rows_attended,
                       self._pages_attended,
                       self._params_casts, self._dsa_scored,
                       self._dsa_selected, self._overlapped,
                       self._discarded, self._shared_rows,
                       self._window_rows, self._prefill_rows,
                       self._cross_skipped)
                delta = [a - b for a, b in zip(now, self._counts_flushed)]
                self._counts_flushed = now
                drains = {r: tuple(c) for r, c in self._drains.items()}
                drain_delta = {
                    r: (n - self._drains_flushed[r][0],
                        secs - self._drains_flushed[r][1])
                    for r, (n, secs) in drains.items()}
                self._drains_flushed = drains
                sampler = dict(self._sampler_steps)
                sampler_delta = {b: n - self._sampler_flushed[b]
                                 for b, n in sampler.items()}
                self._sampler_flushed = sampler
                if dev is not None:
                    was = self._device_flushed
                    dev_delta = dev if was is None else dev - was
                    self._device_flushed = dev
            *plain, skipped = delta
            for family, d in zip(_FLUSHED_FAMILIES, plain):
                if d > 0:
                    reg.counter(family).inc(d)
            if skipped > 0:
                reg.counter(PREFILL_SKIPPED_FAMILY).inc(skipped, part="cross")
            n_fam, s_fam = (reg.counter(f) for f in DECODE_DRAIN_FAMILIES)
            for reason, (n, secs) in drain_delta.items():
                if n > 0:
                    n_fam.inc(n, reason=reason)
                if secs > 0:
                    s_fam.inc(secs, reason=reason)
            b_fam = reg.counter(DECODE_SAMPLER_FAMILY)
            for branch, n in sampler_delta.items():
                if n > 0:
                    b_fam.inc(n, branch=branch)
            if dev is not None and self._tiles_row is not None:
                tiles = reg.counter(DSA_PREFILL_TILES_FAMILY)
                kinds = LAYER_KINDS if self._rows_row is not None else (
                    "full",)
                for i, kind in enumerate(kinds):
                    for state, d in zip(("run", "skipped"),
                                        dev_delta[self._tiles_row,
                                                  2 * i:2 * i + 2]):
                        if d > 0:
                            tiles.inc(int(d), kind=kind, state=state)
            if dev is not None and self._rows_row is not None:
                rows = reg.counter(LATENT_ROWS_FAMILY)
                # int32 on the device: a total that wrapped since the last
                # read still gives its delta modulo 2^32
                for kind, d in zip(LAYER_KINDS,
                                   dev_delta[self._rows_row, :2] % 2**32):
                    if d > 0:
                        rows.inc(int(d), kind=kind)
            if dev is not None and self._moe_index:
                blocks = [b for b in self._stack.blocks
                          if b.name in self._moe_index]
                total = reg.counter(MOE_ASSIGNMENTS_FAMILY)
                per = reg.counter(MOE_EXPERT_FAMILY)
                for b, row in zip(blocks, dev_delta):
                    for e, d in enumerate(row[:-1]):
                        if d > 0:
                            per.inc(int(d), layer=b.name,
                                    expert=str(b.held_first + e))
                    if row[:-1].sum() > 0:
                        total.inc(int(row[:-1].sum()), held="true")
                    if row[-1] > 0:
                        total.inc(int(row[-1]), held="false")
        except Exception as e:
            log.debug("decode count flush failed: %s", e)

    def _count_stream(self, outcome: str) -> None:
        """One settled (or synchronously rejected) stream, by outcome —
        the availability numerator/denominator of stream-success SLOs."""
        with self._stats_lock:
            self._streams_settled += 1
            self._stream_outcomes[outcome] = (
                self._stream_outcomes.get(outcome, 0) + 1)
        try:
            from deeplearning4j_tpu.observe.metrics import registry

            registry().counter("dl4jtpu_generation_streams_total").inc(
                outcome=outcome)
        except Exception as e:
            log.debug("stream outcome metric failed: %s", e)

    def _count_admitted(self) -> None:
        """Demand counter behind throughput SLOs: admitted streams keep
        a stalled window non-idle (see SLObjective kind="throughput")."""
        try:
            from deeplearning4j_tpu.observe.metrics import registry

            registry().counter(
                "dl4jtpu_generation_streams_admitted_total").inc()
        except Exception as e:
            log.debug("admitted stream metric failed: %s", e)

    def _observe_breakdown(self, lat: dict) -> None:
        try:
            fams = _gen_breakdown_families()
            with self._stats_lock:
                for seg in GEN_BREAKDOWN_SEGMENTS:
                    v = lat.get(seg)
                    if v is None:
                        continue
                    self._lat_totals[seg] += v
                    fams[seg].observe(v)
        except Exception as e:
            log.debug("generation breakdown observe failed: %s", e)

    def _observe_ttft(self, req: GenerationRequest) -> None:
        try:
            from deeplearning4j_tpu.observe.metrics import registry

            if req.ttft_s is not None:
                registry().histogram("dl4jtpu_ttft_seconds").observe(
                    req.ttft_s)
        except Exception as e:
            log.debug("ttft metric failed: %s", e)
