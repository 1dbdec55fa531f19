"""Performance attribution — what did the device DO with the step time?

The telemetry spine (PR 2) measures how long a step took; this module
measures what that time bought.  Three pieces:

- a process-global **compiled-program registry**: every jitted
  step/decode/eval program is registered at build time (the
  ``_get_step_fn``/``_get_step_fn_multi`` builders in
  `models/sequential.py` / `models/computation_graph.py`, and
  `datavec/device.py`'s lowered decodes route through
  `register_step_program`).  The registration wrapper captures, on the
  program's FIRST dispatch, its concrete input signature and the
  compile-tax delta (`runtime/compile_stats.py`) that dispatch paid.
- **XLA cost/memory analysis**, computed LAZILY and only on demand
  (``/api/programs``, ``bench.py --scaling``, `analyze_model`, tests):
  ``fn.lower(signature).cost_analysis()`` yields the program's model
  FLOPs and bytes accessed WITHOUT a backend compile (one re-trace);
  ``lower().compile().memory_analysis()`` adds peak/argument/output
  bytes but costs a real XLA compile (AOT executables don't share the
  jit dispatch cache), so it sits behind ``memory=True``.  Every field
  is guarded — the CPU backend omits several — and an analysis
  failure is recorded as a reason, never raised into training.
- **MFU / roofline accounting**: once a program's FLOPs are known, every
  `StepScope` exit derives achieved FLOP/s, MFU against the per-device
  peak table (`PEAKS_BY_DEVICE_KIND`; an unlisted accelerator raises),
  bytes/s against peak HBM bandwidth, and a compute- vs memory-bound
  classification (arithmetic intensity vs the machine's ridge point) —
  pushed to the ``dl4jtpu_step_*`` gauges and stamped onto the
  ``train_step`` span as ``roofline=``.

Nothing here costs the hot path more than two attribute reads until an
analysis is requested; until then the gauges simply stay unset.

    from deeplearning4j_tpu.observe import cost
    model.fit(data)                       # programs registered + dispatched
    for rec in cost.analyze_model(model):
        print(rec.kind, rec.flops, rec.roofline())
"""

from __future__ import annotations

import logging
import threading
import time
import weakref
from typing import Any, Callable, Optional

log = logging.getLogger("deeplearning4j_tpu")

# -- per-backend peak table -------------------------------------------------
#
# (dense bf16 peak FLOP/s, peak HBM bytes/s) PER DEVICE, keyed by the
# `device_kind` string jax reports — the ONE peaks table (bench.py reads
# it too).  Source: Google Cloud TPU documentation, the "System
# architecture" page of each generation (v5e: 197 TFLOP/s bf16, 819 GB/s;
# jax reports a v5e chip as "TPU v5 lite", a v6e chip as "TPU v6 lite").
# The "cpu" row is a deliberately rough nominal (one modern x86 core's
# f32 FMA throughput) so CPU test runs read as an indicative ratio, not
# a hardware claim.  A device that is not in the table is an error
# (`UnknownDeviceKind`), never a default: MFU against the wrong peak is
# a wrong number with a right-looking name.
PEAKS_BY_DEVICE_KIND = {
    "TPU v2": (45.0e12, 7.0e11),
    "TPU v3": (123.0e12, 9.0e11),
    "TPU v4": (275.0e12, 1.228e12),
    "TPU v5 lite": (197.0e12, 8.19e11),
    "TPU v5e": (197.0e12, 8.19e11),
    "TPU v5p": (459.0e12, 2.765e12),
    "TPU v6 lite": (918.0e12, 1.64e12),
    "cpu": (1.0e11, 5.0e10),
}


class UnknownDeviceKind(LookupError):
    """The local device's `device_kind` has no row in
    `PEAKS_BY_DEVICE_KIND` — add its datasheet peaks there."""


def peaks() -> tuple[float, float]:
    """(peak FLOP/s, peak bytes/s) for THIS process's local devices: the
    device-kind table's per-device row times jax.local_device_count().
    Raises `UnknownDeviceKind` for a device the table does not list."""
    import jax

    devs = jax.local_devices()
    kind = str(getattr(devs[0], "device_kind", devs[0].platform))
    try:
        flops, membw = PEAKS_BY_DEVICE_KIND[kind]
    except KeyError:
        raise UnknownDeviceKind(
            f"device kind {kind!r} is not in cost.PEAKS_BY_DEVICE_KIND; "
            "add this part's datasheet peaks (with their source) to the "
            "table"
        ) from None
    return flops * len(devs), membw * len(devs)


def _key_repr(key: Any) -> str:
    try:
        return repr(key)
    except Exception as e:                # exotic key types: best effort
        log.debug("program key repr failed: %s", e)
        return object.__repr__(key)


def _signature_of(args: tuple):
    """ShapeDtypeStruct pytree of a call's args — metadata reads only,
    no device sync.  Raises on leaves that aren't array-shaped (the
    caller records the reason)."""
    import jax
    import numpy as np

    def leaf(a):
        dtype = getattr(a, "dtype", None)
        if dtype is None:
            dtype = np.asarray(a).dtype
        return jax.ShapeDtypeStruct(tuple(np.shape(a)), dtype)

    return jax.tree.map(leaf, args)


def _signature_str(sig) -> str:
    import jax

    leaves = jax.tree.leaves(sig)
    parts = []
    for l in leaves[:12]:
        parts.append(f"{getattr(l, 'dtype', '?')}{list(l.shape)}")
    if len(leaves) > 12:
        parts.append(f"...+{len(leaves) - 12}")
    return " ".join(parts)


class ProgramRecord:
    """One registered compiled program: identity, first-dispatch compile
    tax, lazily-filled XLA cost/memory numbers, dispatch counters."""

    def __init__(self, program_id: int, owner, kind: str, key: Any,
                 live: Callable[[], bool]):
        self.program_id = program_id
        self.owner_ref = weakref.ref(owner)
        self.owner_name = type(owner).__name__
        self.kind = kind
        self.key = _key_repr(key)
        self.created = time.time()
        self._live = live
        self._lock = threading.Lock()
        # wrapper/inner fn handles (set by register(); the inner fn is
        # reachable only THROUGH the owner so a dead model's programs
        # prune instead of being pinned by this registry)
        self._fn_ref: Optional[weakref.ref] = None
        # first-dispatch capture
        self._sig = None
        self.signature: Optional[str] = None
        self.compile_secs: Optional[float] = None
        self.backend_compiles: Optional[int] = None
        self.persistent_cache_hits: Optional[int] = None
        # dispatch accounting
        self.dispatches = 0
        self.last_dispatch_seconds: Optional[float] = None
        # analysis results
        self.flops: Optional[float] = None
        self.bytes_accessed: Optional[float] = None
        self.argument_bytes: Optional[int] = None
        self.output_bytes: Optional[int] = None
        self.temp_bytes: Optional[int] = None
        self.peak_bytes: Optional[int] = None
        self.analysis: str = "pending"     # pending|ok|partial|failed: ...
        self._memory_done = False
        # int8 quantization (quant/ptq.py): as-stored params bytes and
        # the f32 equivalent, captured from the owner at registration.
        # XLA's bytes_accessed cannot be trusted for the quantized
        # path — on CPU the dequantize materialization inflates it, on
        # TPU cost_analysis cannot see through the Pallas kernel — so
        # roofline modeling over weight traffic reads THESE.
        self.params_bytes: Optional[int] = None
        self.params_bytes_f32_equiv: Optional[int] = None
        self.quantized = False
        try:
            params = getattr(owner, "params", None)
            if params is not None:
                from deeplearning4j_tpu.utils.pytree import tree_bytes

                self.params_bytes = tree_bytes(params)
                q = getattr(owner, "_quantized", None)
                if q is not None:
                    from deeplearning4j_tpu.quant.ptq import (
                        quantized_bytes,
                    )

                    b = quantized_bytes(params)
                    self.quantized = True
                    self.params_bytes_f32_equiv = (
                        self.params_bytes
                        - b["quantized_bytes"] + b["f32_equiv_bytes"]
                    )
        except Exception as e:
            log.debug("params-bytes capture failed for %s: %s", key, e)

    # -- liveness ----------------------------------------------------------
    def live(self) -> bool:
        owner = self.owner_ref()
        if owner is None:
            return False
        try:
            return bool(self._live())
        except Exception as e:             # owner mutated underneath us
            log.debug("program liveness check failed for %s: %s",
                      self.key, e)
            return False

    # -- first-dispatch capture (called from the wrapper) ------------------
    def _capture_signature(self, args: tuple) -> None:
        try:
            self._sig = _signature_of(args)
            self.signature = _signature_str(self._sig)
        except Exception as e:
            self.analysis = f"failed: signature capture ({e})"

    def _capture_compile_delta(self, before) -> None:
        from deeplearning4j_tpu.runtime import compile_stats

        spent = compile_stats.snapshot() - before
        self.compile_secs = round(spent.compile_secs, 4)
        self.backend_compiles = spent.backend_compiles
        self.persistent_cache_hits = spent.persistent_cache_hits

    # -- lazy XLA analysis -------------------------------------------------
    def _inner_fn(self):
        wrapper = self._fn_ref() if self._fn_ref is not None else None
        if wrapper is None:
            return None
        return getattr(wrapper, "__wrapped__", None)

    def ensure_analysis(self, memory: bool = False) -> "ProgramRecord":
        """Fill cost (and optionally memory) numbers.  Cost analysis
        re-traces the program (no backend compile); memory analysis AOT
        compiles it (the dispatch cache is separate) — only ask for it
        where an extra compile is acceptable."""
        with self._lock:
            self._ensure_analysis_locked(memory)
        return self

    def _ensure_analysis_locked(self, memory: bool) -> None:
        if self.analysis.startswith("failed"):
            return
        if self.flops is not None and (not memory or self._memory_done):
            return
        if self._sig is None:
            self.analysis = "pending first dispatch"
            return
        fn = self._inner_fn()
        if fn is None:
            self.analysis = "failed: program evicted"
            return
        import warnings

        try:
            with warnings.catch_warnings():
                # the AOT re-lowering repeats the dispatch path's
                # donation/sharding advisories (e.g. "donated buffers
                # were not usable" on CPU); under the test suite's
                # warnings-as-errors policy they would abort the analysis
                warnings.simplefilter("ignore")
                lowered = fn.lower(*self._sig)
        except Exception as e:
            self.analysis = f"failed: lower ({type(e).__name__}: {e})"
            return
        if self.flops is None:
            try:
                ca = lowered.cost_analysis()
                if isinstance(ca, (list, tuple)):
                    ca = ca[0] if ca else {}
                ca = ca or {}
                if "flops" in ca:
                    self.flops = float(ca["flops"])
                if "bytes accessed" in ca:
                    self.bytes_accessed = float(ca["bytes accessed"])
                self.analysis = "ok" if self.flops is not None else (
                    "partial: cost_analysis reported no flops"
                )
            except Exception as e:
                self.analysis = (
                    f"failed: cost_analysis ({type(e).__name__}: {e})"
                )
                return
        if memory and not self._memory_done:
            try:
                with warnings.catch_warnings():
                    warnings.simplefilter("ignore")
                    compiled = lowered.compile()
                ma = compiled.memory_analysis()
                self.argument_bytes = getattr(
                    ma, "argument_size_in_bytes", None
                )
                self.output_bytes = getattr(ma, "output_size_in_bytes", None)
                self.temp_bytes = getattr(ma, "temp_size_in_bytes", None)
                known = [
                    b for b in (self.argument_bytes, self.output_bytes,
                                self.temp_bytes)
                    if b is not None
                ]
                self.peak_bytes = sum(known) if known else None
                if self.flops is None:
                    cca = compiled.cost_analysis()
                    if isinstance(cca, (list, tuple)):
                        cca = cca[0] if cca else {}
                    if cca and "flops" in cca:
                        self.flops = float(cca["flops"])
                        self.analysis = "ok"
                self._memory_done = True
            except Exception as e:
                # memory numbers are optional sweetener; keep the cost
                # side's verdict and note the gap
                log.debug("memory_analysis unavailable for %s: %s",
                          self.key, e)
                self.analysis = (
                    f"partial: memory_analysis unavailable "
                    f"({type(e).__name__})"
                )
                self._memory_done = True

    # -- derived -----------------------------------------------------------
    def arithmetic_intensity(self) -> Optional[float]:
        if not self.flops or not self.bytes_accessed:
            return None
        return self.flops / self.bytes_accessed

    def roofline(self) -> Optional[str]:
        """'compute-bound' | 'memory-bound' from arithmetic intensity vs
        the machine ridge point (peak FLOPs / peak bandwidth)."""
        ai = self.arithmetic_intensity()
        if ai is None:
            return None
        pk_f, pk_b = peaks()
        return "compute-bound" if ai >= pk_f / pk_b else "memory-bound"

    def as_dict(self) -> dict:
        ai = self.arithmetic_intensity()
        return {
            "id": self.program_id,
            "model": self.owner_name,
            "kind": self.kind,
            "key": self.key,
            "signature": self.signature,
            "dispatches": self.dispatches,
            "compile_secs": self.compile_secs,
            "backend_compiles": self.backend_compiles,
            "persistent_cache_hits": self.persistent_cache_hits,
            "flops": self.flops,
            "bytes_accessed": self.bytes_accessed,
            "argument_bytes": self.argument_bytes,
            "output_bytes": self.output_bytes,
            "temp_bytes": self.temp_bytes,
            "peak_bytes": self.peak_bytes,
            "arithmetic_intensity": round(ai, 3) if ai else None,
            "roofline": self.roofline(),
            "params_bytes": self.params_bytes,
            "params_bytes_f32_equiv": self.params_bytes_f32_equiv,
            "quantized": self.quantized,
            "last_dispatch_seconds": self.last_dispatch_seconds,
            "analysis": self.analysis,
        }


class ProgramRegistry:
    """Process-global table of registered compiled programs.  Records
    hold only weak references to their owners, so enumeration prunes
    programs whose model died or whose step-fn cache was cleared
    (recovery's LR retrace, distribute()'s re-shard) — eviction is
    observed, not hooked."""

    def __init__(self):
        self._lock = threading.Lock()
        self._records: list[ProgramRecord] = []
        self._next_id = 1

    def register(self, owner, kind: str, key: Any, fn,
                 live: Callable[[], bool]):
        """Wrap ``fn`` (a jitted program) for the registry: the wrapper
        notes every dispatch, captures the first call's signature and
        compile-tax delta, and routes the owner's ``_cost_program``
        pointer so StepScope can attribute the step.  Returns the
        wrapper (store IT in the step-fn cache)."""
        with self._lock:
            rec = ProgramRecord(self._next_id, owner, kind, key, live)
            self._next_id += 1
            self._records.append(rec)
        owner_ref = rec.owner_ref

        def wrapped(*args, **kwargs):
            o = owner_ref()
            if o is not None:
                o._cost_program = rec
            rec.dispatches += 1
            if rec._sig is None:
                from deeplearning4j_tpu.runtime import compile_stats

                rec._capture_signature(args)
                before = compile_stats.snapshot()
                try:
                    return fn(*args, **kwargs)
                finally:
                    rec._capture_compile_delta(before)
            return fn(*args, **kwargs)

        wrapped.__wrapped__ = fn
        wrapped._cost_record = rec
        # Model.compile_stats() reads the per-program jit cache size off
        # the cached step fns; keep that surface on the wrapper.  A plain
        # closure, NOT the bound method: a pybind PjitFunction inside a
        # reference cycle is opaque to the cycle collector, so storing
        # its bound method here would pin dead models forever.
        if hasattr(fn, "_cache_size"):
            def _cache_size(f=fn):
                return f._cache_size()

            wrapped._cache_size = _cache_size
        rec._fn_ref = weakref.ref(wrapped)
        return wrapped

    def programs(self, analyze: bool = False, memory: bool = False
                 ) -> list[ProgramRecord]:
        """Live records (dead owners / evicted step fns pruned)."""
        with self._lock:
            records = list(self._records)
        live = [r for r in records if r.live()]
        if len(live) != len(records):
            dead = {id(r) for r in records} - {id(r) for r in live}
            with self._lock:
                self._records = [
                    r for r in self._records if id(r) not in dead
                ]
        if analyze:
            for r in live:
                r.ensure_analysis(memory=memory)
        return live

    def clear(self) -> None:
        with self._lock:
            self._records.clear()


_REGISTRY: Optional[ProgramRegistry] = None
_REGISTRY_LOCK = threading.Lock()


def registry() -> ProgramRegistry:
    """The process-global program registry (its live-count gauge
    collector installed on first use)."""
    global _REGISTRY
    with _REGISTRY_LOCK:
        if _REGISTRY is None:
            _REGISTRY = ProgramRegistry()
            from deeplearning4j_tpu.observe.metrics import (
                registry as metrics_registry,
            )

            reg = metrics_registry()
            gauge = reg.gauge("dl4jtpu_programs_registered")

            def _collect(r=_REGISTRY, g=gauge):
                # enumeration only — never triggers analysis (an XLA
                # re-trace/compile must not ride the scrape path)
                g.set(len(r.programs()))

            reg.register_collector(_collect)
    return _REGISTRY


def register_step_program(model, key: Any, fn):
    """Register a model step program built by a `_get_step_fn*` builder.
    The record stays live exactly as long as `key` maps to this wrapper
    in the model's ``_step_fns`` cache — `_step_fns.clear()` (recovery's
    LR retrace, re-distribute) evicts it from the registry."""
    kind = key[0] if isinstance(key, tuple) and key else str(key)
    holder: dict = {}
    model_ref = weakref.ref(model)

    def live():
        # weakrefs only: the record must never pin the model (or the
        # step fn, whose closure holds the model) past its natural life
        m = model_ref()
        wr = holder.get("fn")
        if m is None or wr is None:
            return False
        w = wr()
        return w is not None and m._step_fns.get(key) is w

    wrapped = registry().register(model, str(kind), key, fn, live)
    holder["fn"] = weakref.ref(wrapped)
    return wrapped


def register_attr_program(owner, attr: str, kind: str, key: Any, fn):
    """Register a program cached on an attribute slot (GraphModel's
    ``_infer_fn``, DeviceDecode's ``_jit_fn``): live while the slot
    still holds the wrapper."""
    holder: dict = {}
    owner_ref = weakref.ref(owner)

    def live():
        o = owner_ref()
        wr = holder.get("fn")
        if o is None or wr is None:
            return False
        w = wr()
        return w is not None and getattr(o, attr, None) is w

    wrapped = registry().register(owner, kind, key, fn, live)
    holder["fn"] = weakref.ref(wrapped)
    return wrapped


class SignatureAnalysis:
    """Result of a dispatch-free lowering: XLA cost numbers for a
    program traced from an ABSTRACT signature — or the reason the
    analysis could not produce them.  `ok` is True only when flops came
    back; callers (the autosharding planner) must treat a False result
    as "do not price this", never as zero cost."""

    __slots__ = ("flops", "bytes_accessed", "ok", "reason")

    def __init__(self, flops=None, bytes_accessed=None, reason=None):
        self.flops = flops
        self.bytes_accessed = bytes_accessed
        self.ok = flops is not None
        self.reason = reason

    def as_dict(self) -> dict:
        return {
            "flops": self.flops,
            "bytes_accessed": self.bytes_accessed,
            "ok": self.ok,
            "reason": self.reason,
        }


def analyze_signature(fn, sig) -> SignatureAnalysis:
    """Dispatch-free cost analysis: lower `fn` from `sig` (a pytree of
    jax.ShapeDtypeStruct / concrete placeholders — the positional args
    tuple) and read ``cost_analysis()`` off the lowering.  No device
    execution and no backend compile happen — one abstract re-trace.

    The lazy ProgramRecord path (``ensure_analysis``) needs a first
    real dispatch to capture its signature; the autosharding planner
    prices candidate placements BEFORE anything ever runs, so this is
    its entry point.  `fn` may be a registry wrapper (the ``_register_
    program`` product — its ``__wrapped__`` jitted inner is used), a
    raw jitted function, or anything exposing ``.lower``.

    Failures (CPU-backend omissions, untraceable signatures) come
    back as a reason string on the result — the planner records them as
    per-candidate rejection reasons instead of pricing garbage."""
    import warnings

    inner = getattr(fn, "__wrapped__", fn)
    lower = getattr(inner, "lower", None)
    if lower is None:
        return SignatureAnalysis(
            reason=f"not lowerable: {type(inner).__name__} has no .lower"
        )
    try:
        with warnings.catch_warnings():
            # abstract lowering repeats the dispatch path's donation /
            # sharding advisories; under warnings-as-errors they would
            # abort a perfectly good analysis
            warnings.simplefilter("ignore")
            lowered = lower(*sig)
    except Exception as e:
        return SignatureAnalysis(
            reason=f"lower failed ({type(e).__name__}: {e})"
        )
    try:
        ca = lowered.cost_analysis()
    except Exception as e:
        return SignatureAnalysis(
            reason=f"cost_analysis failed ({type(e).__name__}: {e})"
        )
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    ca = ca or {}
    flops = float(ca["flops"]) if "flops" in ca else None
    bytes_accessed = (
        float(ca["bytes accessed"]) if "bytes accessed" in ca else None
    )
    if flops is None:
        return SignatureAnalysis(
            bytes_accessed=bytes_accessed,
            reason="cost_analysis reported no flops",
        )
    return SignatureAnalysis(flops=flops, bytes_accessed=bytes_accessed)


def analyze_model(model, memory: bool = False) -> list[ProgramRecord]:
    """Cost-analyze every live program owned by `model` (lazy trigger
    for tests/bench/reporting)."""
    out = []
    for rec in registry().programs():
        if rec.owner_ref() is model:
            rec.ensure_analysis(memory=memory)
            out.append(rec)
    return out


def program_table(analyze: bool = True, memory: bool = False) -> list[dict]:
    """The /api/programs payload: every live program as a dict."""
    return [
        r.as_dict()
        for r in registry().programs(analyze=analyze, memory=memory)
    ]


# -- per-step gauge updates (called from StepScope.__exit__) ---------------

_STEP_COST_FAMILIES = None


def _step_cost_families():
    global _STEP_COST_FAMILIES
    if _STEP_COST_FAMILIES is None:
        from deeplearning4j_tpu.observe.metrics import (
            registry as metrics_registry,
        )

        reg = metrics_registry()
        _STEP_COST_FAMILIES = (
            reg.counter("dl4jtpu_step_model_flops_total"),
            reg.gauge("dl4jtpu_step_achieved_flops_per_sec"),
            reg.gauge("dl4jtpu_step_mfu"),
            reg.gauge("dl4jtpu_step_bytes_per_sec"),
            reg.gauge("dl4jtpu_step_membw_util"),
        )
    return _STEP_COST_FAMILIES


def note_step(rec: ProgramRecord, dur: float, span_args: dict,
              n_steps: int = 1) -> None:
    """Attribute one dispatched program execution: FLOPs counter,
    achieved FLOP/s, MFU, bytes/s, bandwidth utilization, and the
    roofline class stamped into the step span's args.  No-op (two
    attribute reads) until the record has been cost-analyzed.

    ``n_steps`` scales the FLOPs/bytes: XLA's cost analysis counts a
    ``lax.scan`` BODY once (measured: the k-step grouped program
    reports the same flops as the single-step program), so a grouped /
    TBPTT dispatch's true work is body-flops x its optimizer-step
    count — exactly the n the StepScope was opened with."""
    rec.last_dispatch_seconds = round(dur, 6)
    if rec.flops is None:
        return
    n = max(1, int(n_steps))
    flops_total, achieved, mfu, bytes_ps, membw = _step_cost_families()
    work = rec.flops * n
    flops_total.inc(work)
    if dur <= 0:
        return
    ach = work / dur
    achieved.set(ach)
    pk_f, pk_b = peaks()
    mfu.set(ach / pk_f)
    if rec.bytes_accessed:
        bps = rec.bytes_accessed * n / dur
        bytes_ps.set(bps)
        membw.set(bps / pk_b)
    cls = rec.roofline()
    if cls:
        span_args["roofline"] = cls
