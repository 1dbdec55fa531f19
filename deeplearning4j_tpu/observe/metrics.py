"""MetricsRegistry — the one telemetry spine every signal lands on.

PR 1 left high-value counters scattered across silos: `runtime/
compile_stats.py` keeps compile-tax integers, the fit loops meter
ETL-wait, `CachedDataSetIterator` counts cache hits, the coordinator
knows heartbeat ages, PJRT knows HBM occupancy.  Each had its own ad-hoc
accessor and NO common scrape path — exactly the gap the TensorFlow
system paper calls out by making monitoring a first-class subsystem.

This module is the fix: a thread-safe, process-global registry of
**counters** (monotonic), **gauges** (set-to-current) and **fixed-bucket
histograms**, zero dependencies beyond the stdlib, with Prometheus text
exposition (served by `UIServer` at ``GET /metrics``) and a dict
`snapshot()` (dumped into bench rows and logs).

Two ways signals arrive:

- **push**: hot paths call `counter.inc()` / `hist.observe()` directly
  (ETL wait, step latency, cache hits, health checks).  Cost: one lock
  acquire + an add — noise next to a training step.
- **collectors**: pull-style sources (compile_stats, PJRT memory,
  coordinator membership) register a callback that refreshes their
  families at scrape/snapshot time, so idle processes pay nothing.

Metric families are pre-declared at registry creation, so a fresh
process's ``/metrics`` already exposes every core family (at zero) —
dashboards and alerts can be written before the first divergence.

    from deeplearning4j_tpu.observe import registry
    reg = registry()
    reg.counter("dl4jtpu_my_events_total", "what it counts").inc()
    print(reg.to_prometheus_text())
"""

from __future__ import annotations

import bisect
import threading
from typing import Callable, Optional, Sequence

# Default latency buckets (seconds) — spans sub-ms CPU steps to
# multi-second cold-compile steps.
DEFAULT_LATENCY_BUCKETS = (
    0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1,
    0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0,
)

_RESERVED_LABELS = ("le",)


def _escape_label(v: str) -> str:
    return (
        str(v).replace("\\", "\\\\").replace('"', '\\"').replace("\n", "\\n")
    )


def _fmt(v: float) -> str:
    """Prometheus-friendly number formatting (ints stay ints).  Handles
    non-finite values with the text format's literals — a diverged run
    sets the health gauges to NaN, and the scrape that matters most must
    not 500 on it."""
    f = float(v)
    if f != f:
        return "NaN"
    if f == float("inf"):
        return "+Inf"
    if f == float("-inf"):
        return "-Inf"
    if f == int(f) and abs(f) < 1e15:
        return str(int(f))
    return repr(f)


def _series_key(labels: dict) -> tuple:
    return tuple(sorted(labels.items()))


def _render_labels(key: tuple, extra: str = "") -> str:
    parts = [f'{k}="{_escape_label(v)}"' for k, v in key]
    if extra:
        parts.append(extra)
    return "{" + ",".join(parts) + "}" if parts else ""


class _Metric:
    """Common family plumbing: name, help, label-keyed series."""

    kind = "untyped"

    def __init__(self, name: str, help: str, lock: threading.Lock):
        self.name = name
        self.help = help
        self._lock = lock
        self._series: dict[tuple, float] = {}

    def _key(self, labels: dict) -> tuple:
        for k in labels:
            if k in _RESERVED_LABELS:
                raise ValueError(f"label name {k!r} is reserved")
        return _series_key(labels)

    def value(self, **labels) -> float:
        with self._lock:
            return self._series.get(self._key(labels), 0.0)

    def sum_series(self, **match) -> float:
        """Sum of every series whose label set CONTAINS `match` (no
        match = all series).  The SLO engine's read primitive: good/bad
        event totals out of a labeled counter without a snapshot() (and
        without running the registry's collectors)."""
        want = set(match.items())
        with self._lock:
            return sum(
                v for k, v in self._series.items() if want <= set(k)
            )

    def expose(self) -> list[str]:
        lines = [
            f"# HELP {self.name} {self.help}",
            f"# TYPE {self.name} {self.kind}",
        ]
        with self._lock:
            for key in sorted(self._series):
                lines.append(
                    f"{self.name}{_render_labels(key)} "
                    f"{_fmt(self._series[key])}"
                )
        return lines

    def snapshot(self) -> dict:
        with self._lock:
            if set(self._series) == {()}:
                return {"value": self._series[()]}
            return {
                "series": {
                    _render_labels(k) or "": v
                    for k, v in sorted(self._series.items())
                }
            }


class Counter(_Metric):
    """Monotonic counter; `inc(amount)` only goes up."""

    kind = "counter"

    def inc(self, amount: float = 1.0, **labels) -> None:
        if amount < 0:
            raise ValueError(f"counter {self.name} cannot decrease "
                             f"(inc {amount})")
        key = self._key(labels)
        with self._lock:
            self._series[key] = self._series.get(key, 0.0) + amount

    def set_total(self, value: float, **labels) -> None:
        """Set the cumulative total directly — for COLLECTORS bridging an
        external monotonic source (compile_stats) whose own counter is
        the ground truth.  Never goes backwards."""
        key = self._key(labels)
        with self._lock:
            self._series[key] = max(self._series.get(key, 0.0), float(value))


class Gauge(_Metric):
    """Set-to-current-value metric (memory in use, heartbeat age...)."""

    kind = "gauge"

    def set(self, value: float, **labels) -> None:
        key = self._key(labels)
        with self._lock:
            self._series[key] = float(value)

    def inc(self, amount: float = 1.0, **labels) -> None:
        key = self._key(labels)
        with self._lock:
            self._series[key] = self._series.get(key, 0.0) + amount

    def remove(self, **labels) -> None:
        with self._lock:
            self._series.pop(self._key(labels), None)

    def clear(self) -> None:
        with self._lock:
            self._series.clear()


class Histogram:
    """Fixed-bucket histogram (cumulative buckets + sum + count), the
    Prometheus layout: `name_bucket{le="x"}`, `name_sum`, `name_count`."""

    kind = "histogram"

    def __init__(self, name: str, help: str, lock: threading.Lock,
                 buckets: Sequence[float] = DEFAULT_LATENCY_BUCKETS):
        if not buckets or list(buckets) != sorted(buckets):
            raise ValueError("histogram buckets must be a sorted non-empty "
                             "sequence of upper bounds")
        self.name = name
        self.help = help
        self._lock = lock
        self.buckets = tuple(float(b) for b in buckets)
        self._counts = [0] * (len(self.buckets) + 1)  # +1 for +Inf
        self._sum = 0.0
        self._count = 0

    def observe(self, value: float) -> None:
        v = float(value)
        i = bisect.bisect_left(self.buckets, v)
        with self._lock:
            self._counts[i] += 1
            self._sum += v
            self._count += 1

    @property
    def count(self) -> int:
        with self._lock:
            return self._count

    @property
    def sum(self) -> float:
        with self._lock:
            return self._sum

    def count_le(self, value: float) -> int:
        """Observations <= the largest bucket bound that is <= `value`
        (exactly what a Prometheus latency-SLI query reads off
        ``_bucket{le=...}``).  A threshold below the first bound counts
        nothing, and overflow observations (beyond the last bound) are
        never counted — their magnitude is unknown.  Pick SLO
        thresholds ON bucket bounds for exact accounting."""
        i = bisect.bisect_right(self.buckets, float(value))
        with self._lock:
            return sum(self._counts[:i])

    def expose(self) -> list[str]:
        lines = [
            f"# HELP {self.name} {self.help}",
            f"# TYPE {self.name} histogram",
        ]
        with self._lock:
            acc = 0
            for b, c in zip(self.buckets, self._counts):
                acc += c
                lines.append(
                    f'{self.name}_bucket{{le="{_fmt(b)}"}} {acc}'
                )
            acc += self._counts[-1]
            lines.append(f'{self.name}_bucket{{le="+Inf"}} {acc}')
            lines.append(f"{self.name}_sum {_fmt(self._sum)}")
            lines.append(f"{self.name}_count {self._count}")
        return lines

    def snapshot(self) -> dict:
        with self._lock:
            return {
                "count": self._count,
                "sum": round(self._sum, 6),
                "buckets": {
                    _fmt(b): c for b, c in zip(self.buckets, self._counts)
                    if c
                },
            }


class MetricsRegistry:
    """Thread-safe family registry + collector hooks + exposition."""

    def __init__(self):
        self._lock = threading.Lock()          # registry structure
        self._metrics: dict[str, object] = {}  # name -> metric family
        self._collectors: list[Callable[[], None]] = []

    # -- family creation (idempotent: same name returns the same object) --
    def _get_or_create(self, cls, name: str, help: str, **kw):
        with self._lock:
            m = self._metrics.get(name)
            if m is not None:
                if not isinstance(m, cls):
                    raise TypeError(
                        f"metric {name!r} already registered as "
                        f"{type(m).__name__}, not {cls.__name__}"
                    )
                want = kw.get("buckets")
                if want is not None and tuple(
                    float(b) for b in want
                ) != m.buckets:
                    # silently returning the old boundaries would put
                    # observations in buckets the caller believes don't
                    # exist
                    raise ValueError(
                        f"histogram {name!r} already registered with "
                        f"buckets {m.buckets}, requested {tuple(want)}"
                    )
                return m
            # per-family lock: hot-path incs never contend with registry
            # structure changes or other families
            m = cls(name, help, threading.Lock(), **kw)
            self._metrics[name] = m
            return m

    def counter(self, name: str, help: str = "") -> Counter:
        return self._get_or_create(Counter, name, help)

    def gauge(self, name: str, help: str = "") -> Gauge:
        return self._get_or_create(Gauge, name, help)

    def histogram(self, name: str, help: str = "",
                  buckets: Sequence[float] = DEFAULT_LATENCY_BUCKETS
                  ) -> Histogram:
        return self._get_or_create(Histogram, name, help, buckets=buckets)

    def get(self, name: str):
        """The already-registered family (None when absent): the
        bucket-agnostic READER lookup — the SLO engine must observe a
        histogram family without asserting its bucket layout."""
        with self._lock:
            return self._metrics.get(name)

    # -- collectors --------------------------------------------------------
    def register_collector(self, fn: Callable[[], None]) -> None:
        """Register a callback run before every exposition/snapshot; pull
        sources refresh their gauges there.  A collector that raises is
        dropped from the run, never breaks the scrape."""
        with self._lock:
            if fn not in self._collectors:
                self._collectors.append(fn)

    def unregister_collector(self, fn: Callable[[], None]) -> None:
        with self._lock:
            if fn in self._collectors:
                self._collectors.remove(fn)

    def collect(self) -> None:
        with self._lock:
            collectors = list(self._collectors)
        for fn in collectors:
            try:
                fn()
            except Exception:
                # a broken pull source must not take down the scrape path
                continue

    # -- exposition --------------------------------------------------------
    def to_prometheus_text(self) -> str:
        """Prometheus text exposition format 0.0.4 of every family
        (collectors refreshed first).  Families with no samples yet still
        emit HELP/TYPE so scrapers see the full schema from step 0.

        Meta-observability: the render is timed into
        ``dl4jtpu_scrape_seconds`` AFTER the text is built, so the gauge
        a scraper reads describes the PREVIOUS completed scrape — a slow
        or bloating scrape is itself an outage signal, and it must not
        be invisible just because it is the scrape."""
        import time

        t0 = time.perf_counter()
        self.collect()
        with self._lock:
            metrics = [self._metrics[n] for n in sorted(self._metrics)]
        lines: list[str] = []
        for m in metrics:
            lines.extend(m.expose())
        out = "\n".join(lines) + "\n"
        with self._lock:
            meta = self._metrics.get("dl4jtpu_scrape_seconds")
        if isinstance(meta, Gauge):
            # only the global registry pre-declares the meta family; a
            # bare test registry's exposition stays exactly its own
            meta.set(time.perf_counter() - t0)
        return out

    def snapshot(self, prefixes: Optional[Sequence[str]] = None) -> dict:
        """{family_name: {value|series|histogram}} dict of current state
        (collectors refreshed); `prefixes` filters family names."""
        self.collect()
        with self._lock:
            metrics = dict(self._metrics)
        out = {}
        for name in sorted(metrics):
            if prefixes is not None and not any(
                name.startswith(p) for p in prefixes
            ):
                continue
            out[name] = metrics[name].snapshot()
        return out


# -- process-global registry ----------------------------------------------

_REGISTRY: Optional[MetricsRegistry] = None
_REGISTRY_LOCK = threading.Lock()


def registry() -> MetricsRegistry:
    """The process-global registry, core families pre-declared and the
    default pull collectors (compile stats, device memory) installed."""
    global _REGISTRY
    with _REGISTRY_LOCK:
        if _REGISTRY is None:
            reg = MetricsRegistry()
            _declare_core(reg)
            reg.register_collector(_compile_stats_collector)
            reg.register_collector(_device_memory_collector)
            reg.register_collector(_build_info_collector)
            reg.register_collector(_registry_meta_collector)
            _REGISTRY = reg
    return _REGISTRY


def _declare_core(reg: MetricsRegistry) -> None:
    """Pre-declare the spine's metric families: a fresh process's
    /metrics shows the full schema before the first step runs."""
    # compile taxes (bridged from runtime/compile_stats.py)
    reg.counter("dl4jtpu_compile_jit_cache_misses_total",
                "Fresh jit traces (one per distinct step signature)")
    reg.counter("dl4jtpu_compile_backend_compiles_total",
                "XLA compile requests, incl. persistent-cache retrievals")
    reg.counter("dl4jtpu_compile_seconds_total",
                "Wall seconds inside XLA compilation / cache retrieval")
    reg.counter("dl4jtpu_compile_persistent_cache_hits_total",
                "Programs served from the on-disk compile cache")
    reg.counter("dl4jtpu_compile_persistent_cache_puts_total",
                "Programs written to the on-disk compile cache")
    reg.counter("dl4jtpu_compile_seconds_saved_total",
                "Compile seconds the persistent cache avoided")
    # ETL feed
    reg.counter("dl4jtpu_etl_wait_seconds_total",
                "Seconds fit() sat blocked on the input iterator")
    reg.counter("dl4jtpu_etl_batches_total",
                "Batches pulled through the fit loops' timed feed")
    # disk batch cache (data/cached.py)
    reg.counter("dl4jtpu_data_cache_batches_total",
                "Batches served by CachedDataSetIterator, by source "
                "(cache=mmap replay, decode=base-pipeline population)")
    # pipelined fit loop (data/prefetch.py)
    reg.counter("dl4jtpu_prefetch_batches_total",
                "Batches pulled + staged by the PrefetchIterator "
                "producer thread")
    reg.counter("dl4jtpu_prefetch_overlap_seconds_total",
                "Producer-thread staging seconds hidden behind device "
                "compute (stage time not re-paid as consumer wait)")
    # device-compiled data pipeline (datavec/device.py)
    reg.counter("dl4jtpu_device_decode_batches_total",
                "Batches decoded inside the fused decode+step program")
    reg.counter("dl4jtpu_device_decode_seconds_total",
                "Device seconds attributed to the fused decode stage "
                "(calibrated per input signature: the fused program "
                "hides the stage, so a standalone jitted decode is "
                "timed once per signature and charged per batch)")
    reg.counter("dl4jtpu_device_decode_fallbacks_total",
                "Transform chains that fell back to host application, "
                "by reason")
    reg.counter("dl4jtpu_h2d_bytes_total",
                "Bytes of batch data crossing host->device, by feed "
                "(raw=undecoded bytes for the fused decode path, "
                "decoded=host-transformed arrays)")
    # step engine
    reg.histogram("dl4jtpu_step_latency_seconds",
                  "Host wall time per dispatched training-step program "
                  "(grouped programs observe once for k steps)")
    reg.counter("dl4jtpu_train_steps_total",
                "Optimizer steps run (grouped programs count k)")
    # numeric health (observe/health.py)
    reg.counter("dl4jtpu_health_checks_total",
                "HealthListener monitored steps")
    reg.counter("dl4jtpu_health_divergence_total",
                "Divergence events flagged, by kind")
    reg.gauge("dl4jtpu_health_param_global_norm",
              "Last measured global L2 norm of all params")
    reg.gauge("dl4jtpu_health_update_norm",
              "Last measured global L2 norm of the param delta |w_t - "
              "w_{t-1}| between monitored steps")
    # device memory (PJRT; collector-set)
    reg.gauge("dl4jtpu_device_bytes_in_use",
              "PJRT bytes currently allocated on device 0")
    reg.gauge("dl4jtpu_device_peak_bytes_in_use",
              "PJRT peak bytes allocated on device 0")
    # cluster control plane (runtime/coordinator.py; the server's pull
    # collector refreshes these at scrape time — declaring them here
    # keeps a fresh process's /metrics schema-complete and is what
    # tpulint rule RG301 checks every use against)
    reg.gauge("dl4jtpu_coordinator_heartbeat_age_seconds",
              "Seconds since each member's last heartbeat")
    reg.gauge("dl4jtpu_coordinator_members",
              "Sealed members this generation")
    reg.gauge("dl4jtpu_coordinator_generation",
              "Current cluster generation")
    reg.counter("dl4jtpu_coordinator_evictions_total", "Workers evicted")
    # fault tolerance (runtime/faults.py, runtime/coordinator.py,
    # train/checkpoint.py)
    reg.counter("dl4jtpu_rpc_retries_total",
                "CoordinatorClient request retries, by op")
    reg.counter("dl4jtpu_faults_injected_total",
                "Faults fired by the armed FaultPlan, by site")
    reg.counter("dl4jtpu_ckpt_verify_failures_total",
                "Checkpoints rejected as restore/rollback/serve "
                "targets, by reason (corrupt = manifest/CRC/zip "
                "defects; nonfinite = intact bytes holding NaN/Inf "
                "params)")
    # self-healing (runtime/watchdog.py, train/recovery.py)
    reg.counter("dl4jtpu_watchdog_stalls_total",
                "Step-watchdog escalations, by stage (warn, stack_dump, "
                "abort)")
    reg.counter("dl4jtpu_recovery_events_total",
                "RecoveryPolicy actions, by kind (rollback, oom_split, "
                "oom_restore, batch_skipped, quarantined)")
    reg.counter("dl4jtpu_quarantined_batches_total",
                "Poison batches absorbed by the quarantine, by reason "
                "(decode_error, nonfinite_input)")
    reg.gauge("dl4jtpu_recovery_lr_scale",
              "Cumulative LR backoff factor applied by the active "
              "RecoveryPolicy (1.0 = no rollback yet)")
    # performance attribution (observe/cost.py): per-step derivations
    # from the compiled-program registry's XLA cost analysis.  The
    # gauges stay unset until a program has been cost-analyzed
    # (/api/programs, bench --scaling, cost.analyze_model).
    reg.counter("dl4jtpu_step_model_flops_total",
                "Model FLOPs executed by dispatched step programs "
                "(program cost_analysis flops x optimizer steps per "
                "dispatch — XLA counts a scanned group's body once)")
    reg.gauge("dl4jtpu_step_achieved_flops_per_sec",
              "Last dispatched program's model FLOPs / host wall "
              "seconds")
    reg.gauge("dl4jtpu_step_mfu",
              "Last step's achieved FLOP/s over the device-kind peak "
              "table (cost.PEAKS_BY_DEVICE_KIND; the CPU row is a rough "
              "nominal)")
    reg.gauge("dl4jtpu_step_bytes_per_sec",
              "Last step's XLA bytes-accessed / host wall seconds")
    reg.gauge("dl4jtpu_step_membw_util",
              "Last step's bytes/s over the device-kind table's peak "
              "memory bandwidth")
    reg.gauge("dl4jtpu_programs_registered",
              "Live compiled programs in the cost registry (dead "
              "models / cleared step-fn caches pruned)")
    # ZeRO-1 sharded weight update (parallel/zero.py)
    reg.gauge("dl4jtpu_opt_state_bytes",
              "Per-replica optimizer-state bytes of the last "
              "distribute()d model, by mode (sharded=ZeRO-1 data-axis "
              "shards, replicated=classic DP) — the quantity zero=1 "
              "shrinks ~1/n")
    reg.gauge("dl4jtpu_grad_state_bytes",
              "Per-replica gradient-state bytes of the last "
              "distribute()d model, by mode (zero2=the persistently "
              "sharded grad accumulator, ~params/n per replica; "
              "replicated/sharded=the full params-sized transient "
              "gradient every replica still materializes under "
              "zero∈{0,1}) — the quantity zero=2 shrinks ~1/n")
    reg.counter("dl4jtpu_update_seconds_total",
                "Calibrated standalone weight-update-epilogue seconds, "
                "by mode (sharded/replicated).  The fused step program "
                "hides the epilogue, so attribution times an "
                "equivalent jitted update once per measurement "
                "(parallel/zero.py measure_update_seconds; bench "
                "--scaling's update_time_ms columns)")
    # autosharding planner (parallel/planner.py): candidate pricing is
    # dispatch-free (lowered-only cost analysis), so these are set by
    # plan() itself, not by any step
    reg.counter("dl4jtpu_plan_candidates_total",
                "Candidate ParallelConfigs the autosharding planner "
                "examined, by verdict (priced=entered the argmin, "
                "rejected=legality/divisibility/memory/analysis "
                "failure with a recorded reason)")
    reg.gauge("dl4jtpu_plan_seconds",
              "Wall seconds the last plan() spent enumerating and "
              "pricing its candidate set (no device executions, no "
              "backend compiles)")
    reg.gauge("dl4jtpu_plan_predicted_step_seconds",
              "The cost model's predicted step seconds for the last "
              "plan()'s picked ParallelConfig")
    # serving plane (serving/): admission, batching, degradation and
    # weight hot-swap telemetry — p50/p99 come from the latency
    # histogram's buckets, queue/breaker state from the gauges
    reg.counter("dl4jtpu_serving_requests_total",
                "Admitted serving requests by final outcome (ok, "
                "error, timeout)")
    reg.counter("dl4jtpu_serving_shed_total",
                "Requests rejected EXPLICITLY by the serving plane, by "
                "reason (queue_full backpressure, deadline shed, "
                "breaker_open, admit_fault, shutdown) — overload is "
                "never a silent drop")
    reg.histogram("dl4jtpu_serving_request_latency_seconds",
                  "Admission-to-completion latency per served request")
    reg.gauge("dl4jtpu_serving_queue_depth",
              "Requests waiting in the serving admission queue")
    reg.gauge("dl4jtpu_serving_batch_occupancy",
              "Real requests / padded bucket size of the last "
              "dispatched serving batch")
    reg.counter("dl4jtpu_serving_batches_total",
                "Batched inference programs dispatched by the serving "
                "plane")
    reg.gauge("dl4jtpu_serving_breaker_state",
              "Serving circuit breaker state (0=closed, 0.5=half-open "
              "probe, 1=open)")
    reg.counter("dl4jtpu_serving_breaker_transitions_total",
                "Serving circuit breaker transitions, by target state")
    reg.counter("dl4jtpu_serving_hotswap_total",
                "Weight hot-swap pushes, by result (installed, "
                "rolled_back — a rolled-back push leaves the serving "
                "params untouched; push_error = a serve_into fan-out "
                "target's push raised and was isolated)")
    reg.gauge("dl4jtpu_serving_weights_generation",
              "Monotonic generation of the serving params (bumps on "
              "every installed hot-swap)")
    # serving fleet front door (serving/router.py, serving/fleet.py):
    # health-aware routing, cross-replica retries, hedges, replica
    # ejection and rolling canary weight deploys
    reg.counter("dl4jtpu_router_requests_total",
                "Router-dispatched request tries by router, replica "
                "and outcome (ok, rejected, error, timeout) — one "
                "request may count several tries (retries/hedges), "
                "never zero; the router label keeps two fleets in one "
                "process apart (replica names repeat across fleets)")
    reg.counter("dl4jtpu_router_retries_total",
                "Cross-replica retries the router issued (idempotent "
                "failures re-routed under the explicit retry budget)")
    reg.counter("dl4jtpu_router_hedges_total",
                "Latency hedges the router issued (duplicate dispatch "
                "on a second replica; the slower result is discarded)")
    reg.counter("dl4jtpu_replica_ejections_total",
                "Replicas ejected into probation by the router, by "
                "reason (consecutive_failures, wedged, dead)")
    reg.gauge("dl4jtpu_fleet_deploy_generation",
              "Monotonic generation of the last COMPLETED rolling "
              "fleet weight deploy (a rolled-back deploy does not "
              "bump it)")
    reg.counter("dl4jtpu_canary_failures_total",
                "Canary verifications that failed during a rolling "
                "deploy (golden output mismatch / non-finite / probe "
                "error) — each one rolled the deploy back")
    reg.gauge("dl4jtpu_router_replica_pressure",
              "Last pulled shed pressure per replica (labels: router, "
              "replica), refreshed by the router's registry collector "
              "at scrape time so the fleet scrape carries per-replica "
              "headroom")
    # elastic supervisor crash-loop damping (train/elastic.py): nonzero
    # while the supervisor is backing off before a respawn — respawn
    # storms become visible on /metrics instead of only in logs
    reg.gauge("dl4jtpu_supervisor_backoff_seconds",
              "Crash-loop backoff the ElasticSupervisor is currently "
              "sleeping before respawning (0 = not backing off)")
    # request-level latency attribution (serving/server.py,
    # serving/router.py): per-request decomposition of where one
    # inference request's time went — the histogram families behind
    # /api/serving/slow and the /v1/status breakdown
    reg.histogram("dl4jtpu_serving_queue_wait_seconds",
                  "Per served request: enqueue -> its batch was taken "
                  "(includes the batcher's linger window)")
    reg.histogram("dl4jtpu_serving_batch_form_seconds",
                  "Per served request: batch taken -> dispatch entered "
                  "(coalesce bookkeeping + expiry filtering)")
    reg.histogram("dl4jtpu_serving_dispatch_seconds",
                  "Per served request: its batch's stack + weights "
                  "snapshot + device call + finiteness screen")
    reg.histogram("dl4jtpu_serving_pad_overhead_seconds",
                  "Per served request: the share of its batch's "
                  "dispatch spent computing padding rows "
                  "(dispatch x padded/bucket)")
    reg.counter("dl4jtpu_serving_batch_examples_total",
                "Examples in dispatched serving batches, by kind "
                "(real=admitted requests, pad=zero rows added to reach "
                "the power-of-two bucket) — the batch-occupancy "
                "integral")
    reg.histogram("dl4jtpu_router_overhead_seconds",
                  "Per routed request: client wall minus the WINNING "
                  "try's service time — the retry + hedge + pick "
                  "overhead the front door added")
    # SLO burn-rate engine (observe/slo.py); the engine's registry
    # collector refreshes these at scrape time
    reg.gauge("dl4jtpu_slo_burn_rate",
              "Error-budget burn rate per objective and window "
              "(1.0 = burning exactly the budget; labels: slo, window)")
    reg.gauge("dl4jtpu_slo_error_budget_remaining",
              "Fraction of each objective's error budget left since "
              "the engine started (negative = budget blown)")
    reg.gauge("dl4jtpu_slo_alert_active",
              "1 while an objective's multi-window burn alert is "
              "firing, else 0")
    reg.counter("dl4jtpu_slo_alerts_total",
                "Burn-rate alerts fired per objective (rising edges "
                "only)")
    # int8 post-training quantization (quant/, ops/dequant_matmul.py)
    reg.gauge("dl4jtpu_quant_params_bytes",
              "Bytes of the last quantize()d params tree, by kind "
              "(quantized = int8 values + f32 scales as stored, "
              "f32_equiv = the same weights at f32) — the serving "
              "memory the scheme saves")
    reg.counter("dl4jtpu_quant_dequant_matmul_total",
                "Quantized matmul sites lowered into compiled "
                "programs, by impl (pallas = fused TPU kernel, "
                "blocked = cache-blocked XLA scan, xla = "
                "dequantize-then-dot baseline).  Counted at TRACE "
                "time — once per program signature per site, never "
                "from inside the traced body")
    reg.counter("dl4jtpu_quant_parity_checks_total",
                "Quantized-vs-f32 evaluation-parity gate results, by "
                "result (pass/fail) — bumped by "
                "quant.parity_check() wherever the gate runs "
                "(tests, bench rows, pre-deploy checks)")
    # meta-observability: the scrape path describing itself — a slow or
    # bloating scrape is an outage signal too
    reg.gauge("dl4jtpu_scrape_seconds",
              "Wall seconds the PREVIOUS completed /metrics render "
              "took (collectors + exposition)")
    reg.gauge("dl4jtpu_registry_families",
              "Metric families currently registered")
    reg.gauge("dl4jtpu_registry_series",
              "Label series across all families (histograms count "
              "their exposition lines: buckets + +Inf + sum + count) — "
              "a bloating scrape shows here first")
    # step-timeline ring buffer (observe/trace.py)
    reg.counter("dl4jtpu_trace_spans_dropped_total",
                "Spans evicted by trace ring-buffer wrap-around (the "
                "Chrome export's metadata carries the same count)")
    # build/environment identity: value is always 1, the labels are the
    # payload — every scrape and crash report is self-describing
    reg.gauge("dl4jtpu_build_info",
              "Constant 1; labels carry package/jax/jaxlib versions, "
              "backend and device count")
    # fleet aggregation (observe/fleet.py; the coordinator's collector
    # refreshes these from pushed worker snapshots at scrape time)
    reg.gauge("dl4jtpu_fleet_workers",
              "Workers that have pushed a telemetry snapshot")
    reg.counter("dl4jtpu_fleet_snapshots_total",
                "Telemetry snapshots ingested from workers")
    reg.gauge("dl4jtpu_fleet_step_latency_seconds",
              "Recent mean step latency per worker (windowed between "
              "pushes)")
    reg.gauge("dl4jtpu_fleet_step_latency_skew",
              "Slowest/fastest worker recent mean step latency")
    reg.gauge("dl4jtpu_fleet_stragglers",
              "Workers whose recent mean step latency exceeds "
              "DL4J_TPU_STRAGGLER_FACTOR x the fleet median")
    # token-level generation serving (serving/generation.py + kv_cache.py)
    reg.counter("dl4jtpu_decode_tokens_total",
                "Tokens emitted by the continuous-batching decode "
                "engine (prefill first-tokens included) — the "
                "aggregate tokens/s numerator")
    reg.counter("dl4jtpu_decode_steps_total",
                "Decode dispatches of the generation engines (plain "
                "steps and speculative verify chunks), counted where "
                "the step is built and bridged by a pull collector")
    reg.counter("dl4jtpu_decode_slot_steps_total",
                "Live slots summed over the decode dispatches: over "
                "dl4jtpu_decode_steps_total it is the mean batch "
                "occupancy per step (the occupancy gauge is a "
                "last-value sample)")
    reg.counter("dl4jtpu_decode_rows_attended_total",
                "KV rows the decode dispatches attended, summed over "
                "their live slots, from seq_lens at dispatch: a plain "
                "step counts a slot's rows INCLUDING the one it writes "
                "(seq_len + 1); a verify chunk counts each slot's rows "
                "once (seq_len + spec_k + 1, capped at the page "
                "table's span).  Times layers x 2 x heads x head size x "
                "bytes per element it is the KV bytes demanded")
    reg.counter("dl4jtpu_decode_pages_attended_total",
                "Pool pages that hold the KV rows the decode dispatches "
                "attended, summed over their live slots: ceil(rows / "
                "page size) per slot, rows as "
                "dl4jtpu_decode_rows_attended_total counts them.  The "
                "paged kernel's loop runs over exactly these pages; over "
                "decode steps x slots x pages per sequence it is the "
                "share of the page tables that is live")
    reg.counter("dl4jtpu_shared_kv_rows_attended_total",
                "Rows of a hybrid stack's shared K/V pool (its one "
                "full-attention layer's keys and values) a decode step "
                "attends, summed over its live slots: seq_len + 1 per "
                "slot, per step.  Each of the full layer and the cross "
                "layers reads them once (a shared_kv_attn call each), so "
                "times those layers x 2 x kv heads x head size x bytes "
                "per element it is the bytes the kernel must read")
    reg.counter("dl4jtpu_window_rows_attended_total",
                "Rows of the sliding-window rings a hybrid stack's decode "
                "step attends, per window layer, summed over its live "
                "slots: min(seq_len + 1, window) per slot, per step")
    reg.counter("dl4jtpu_prefill_rows_total",
                "Prompt rows the prefill programs of a hybrid stack took "
                "in (the prompts' lengths, pad rows left out)")
    reg.counter("dl4jtpu_prefill_rows_skipped_total",
                "Prompt rows a part of a hybrid stack did not run, by "
                "part: cross, the full layer's attention and every layer "
                "after it, which run on a prompt's last row only "
                "(prompt length - 1 rows skipped per prompt).  Over "
                "dl4jtpu_prefill_rows_total it is the share skipped")
    reg.counter("dl4jtpu_decode_steps_overlapped_total",
                "Decode steps dispatched while the step before was still "
                "unread (the loop's one-step lookahead): the host's work "
                "for them lay under the device's.  Over "
                "dl4jtpu_decode_steps_total it is the share of steps the "
                "lookahead engaged for; the rest followed a drain (an "
                "admission, a drafter, a stop)")
    reg.counter("dl4jtpu_decode_sampler_steps_total",
                "Decode steps by the branch of the sampler they ran, from "
                "the sampling parameters of their live rows (greedy: no "
                "row at temperature > 0, the arg-max alone; sampled: a "
                "draw, no row with a top-k; top_k: the draw and the k-th "
                "largest count over the vocabulary).  Bridged by the "
                "decode counts' pull collector")
    reg.counter("dl4jtpu_decode_slot_steps_discarded_total",
                "Slot-rows a decode step computed for a stream that had "
                "ended before the step's tokens were read (a stop token "
                "or a cancel the step before brought to light): the "
                "lookahead's cost; 0 where streams end by count")
    reg.counter("dl4jtpu_decode_drains_total",
                "Steps the decode loop landed so the host could act, by "
                "reason (admit: a request waits and a slot is or will "
                "be free; drafter; stop; idle: every stream ends with "
                "the step in flight): where the one-step lookahead let "
                "go.  Bridged by the decode counts' pull collector")
    reg.counter("dl4jtpu_decode_drain_seconds_total",
                "Seconds of the generation.drain spans by reason (the "
                "readback and harvest of the drained step): over "
                "dl4jtpu_decode_drains_total{reason=\"admit\"} it is "
                "what an arrival waits for the step in flight")
    reg.counter("dl4jtpu_serving_params_casts_total",
                "Serving copies of the parameter tree the generation "
                "engines made (the matrices cast to the activation "
                "type once, not in every step): 1 at an engine's "
                "start, + 1 per installed hot-swap, flat across decode "
                "steps.  Bridged by the decode counts' pull collector")
    reg.counter("dl4jtpu_dsa_rows_scored_total",
                "Context rows the sparse-attention indexers scored, per "
                "query row per indexer layer (its whole prefix, itself "
                "included), prefill and decode; counted on the host "
                "from the lengths, bridged by the decode counts' pull "
                "collector")
    reg.counter("dl4jtpu_dsa_rows_selected_total",
                "Rows the indexers' exact top-k kept of those scored: "
                "min(prefix, index_topk) per query row per indexer "
                "layer.  Over dl4jtpu_dsa_rows_scored_total it is the "
                "share of the context the attention then reads")
    reg.counter("dl4jtpu_moe_assignments_total",
                "Expert assignments (rows x experts per token) of the "
                "rows served, by whether the chosen expert is held on "
                "this chip (held=true|false); counted on the device "
                "into an array the programs carry, read at scrape")
    reg.counter("dl4jtpu_moe_expert_assignments_total",
                "The held assignments by layer and expert (expert = "
                "its index among all routed experts): the load each "
                "held expert saw")
    reg.counter("dl4jtpu_dsa_prefill_tiles_total",
                "Tiles of the latent prefill's score plane times head "
                "groups that the dsa_prefill_attn kernel ran (state=run: "
                "the selection, the causal mask or the window keeps a "
                "pair of the tile) and skipped (state=skipped: it keeps "
                "none, so no copy and no product), per layer and chunk, "
                "by the layer's kind (kind=full: its keys are the whole "
                "context; kind=window: its ring and the chunk's rows); "
                "counted on the device into the array the programs carry, "
                "read at scrape.  run over run + skipped is the share of "
                "the dense plane still computed")
    reg.counter("dl4jtpu_latent_rows_attended_total",
                "Latent rows the decode steps' layers that select nothing "
                "attended, summed over the layers of each kind: kind=full "
                "(the slot's whole context, seq_len + 1, read in place by "
                "latent_paged_attn) and kind=window (the ring's "
                "min(seq_len + 1, window)); counted on the device into "
                "the array the programs carry, read at scrape")
    reg.gauge("dl4jtpu_kv_pages_used",
              "KV pool pages currently owned by live streams "
              "(page 0, the scratch page, never counts)")
    reg.gauge("dl4jtpu_kv_pages_total",
              "Allocatable KV pool pages (num_pages - 1; the ratio "
              "used/total is the occupancy term in shed_pressure)")
    reg.histogram("dl4jtpu_ttft_seconds",
                  "Time-to-first-token per stream: submit to the "
                  "prefill program emitting the first sampled token")
    reg.gauge("dl4jtpu_decode_batch_occupancy",
              "Live streams / decode slots of the running generation "
              "engines, sampled at scrape time (1.0 = the batch is "
              "full; sustained low values mean the slot count outruns "
              "the traffic)")
    reg.counter("dl4jtpu_paged_attention_total",
                "Paged-attention sites lowered into compiled "
                "programs, by impl (pallas = online-softmax TPU "
                "kernel, xla = gather-then-attend reference; _int8 "
                "suffix = fused dequant variant).  Counted at TRACE "
                "time, never from inside the traced body")
    reg.counter("dl4jtpu_flash_attention_total",
                "Flash-attention sites lowered into compiled programs, "
                "by the tiling they got (block_q, block_k: rows of the "
                "Q and of the K/V block) and causal.  Counted at TRACE "
                "time, never from inside the traced body")
    # generation-plane observability (serving/generation.py lifecycle
    # instrumentation + serving/flight.py flight recorder)
    reg.counter("dl4jtpu_generation_streams_admitted_total",
                "Streams accepted into the generation admission queue "
                "(label-free; the demand denominator for throughput "
                "SLOs — admitted streams waiting through a stall keep "
                "the window non-idle)")
    reg.counter("dl4jtpu_generation_streams_total",
                "Generation streams by final outcome (ok / cancelled / "
                "kv_exhausted / error / wedged / shutdown) — counted "
                "exactly once at fate settle, same contract as "
                "dl4jtpu_serving_requests_total")
    reg.histogram("dl4jtpu_generation_queue_seconds",
                  "Per-stream admission-queue wait: enqueue to the "
                  "decode loop taking the stream")
    reg.histogram("dl4jtpu_generation_prefill_seconds",
                  "Per-stream prefill compute (bucketed prompt "
                  "forward + first-token sample), wherever the "
                  "prefill ran")
    reg.histogram("dl4jtpu_generation_handoff_seconds",
                  "Per-stream KV handoff: prefill completion to KV "
                  "pages written on the decode replica (local "
                  "admission: just the page write)")
    reg.histogram("dl4jtpu_generation_decode_queue_seconds",
                  "Per-stream slot residency NOT spent in decode "
                  "compute or sampling (waiting for co-resident "
                  "streams, refills, respawns)")
    reg.histogram("dl4jtpu_generation_decode_compute_seconds",
                  "Per-stream accumulated decode-step device wall "
                  "(each co-resident stream is charged the full step, "
                  "like the dispatch segment of /v1/infer)")
    reg.histogram("dl4jtpu_generation_sampling_seconds",
                  "Per-stream accumulated host-side harvest/sampling "
                  "bookkeeping after each decode step")
    reg.gauge("dl4jtpu_flight_records",
              "Per-stream records currently held in the serving "
              "flight-recorder ring")
    reg.counter("dl4jtpu_flight_dumps_total",
                "Flight-recorder post-mortem dumps written, by "
                "trigger (watchdog_abort / breaker_open / "
                "kv_exhausted_spike / slo_alert)")
    # speculative decoding (serving/speculative.py drafters + the
    # generation engine's verify-once dispatch)
    reg.counter("dl4jtpu_spec_tokens_total",
                "Speculative-decode token flow by kind: drafted "
                "(proposed by the stream's drafter), accepted (draft "
                "tokens the verify pass confirmed and emitted), "
                "rejected (drafted - accepted), bonus (the corrected "
                "sample at the first mismatch, or the extra sample "
                "after an all-accepted chunk)")
    reg.gauge("dl4jtpu_spec_acceptance_ratio",
              "Cumulative accepted/drafted over the engine's life "
              "(0.0 until anything is drafted) — the rate the "
              "committed bench speedup is quoted at")
    reg.histogram("dl4jtpu_spec_tokens_per_dispatch",
                  "Tokens emitted per verify-once dispatch, summed "
                  "over the dispatch's live streams (each contributes "
                  "1..spec_k+1: its accepted prefix plus the "
                  "corrected/bonus sample) — the distribution behind "
                  "the speculative speedup")


def _compile_stats_collector() -> None:
    """Bridge runtime/compile_stats.py process-global counters into the
    registry (set_total: compile_stats is the ground truth)."""
    from deeplearning4j_tpu.runtime import compile_stats

    snap = compile_stats.snapshot()
    reg = registry()
    for family, value in (
        ("dl4jtpu_compile_jit_cache_misses_total", snap.jit_cache_misses),
        ("dl4jtpu_compile_backend_compiles_total", snap.backend_compiles),
        ("dl4jtpu_compile_seconds_total", snap.compile_secs),
        ("dl4jtpu_compile_persistent_cache_hits_total",
         snap.persistent_cache_hits),
        ("dl4jtpu_compile_persistent_cache_puts_total",
         snap.persistent_cache_puts),
        ("dl4jtpu_compile_seconds_saved_total", snap.compile_secs_saved),
    ):
        reg.counter(family).set_total(value)


def _build_info_collector() -> None:
    """dl4jtpu_build_info: a constant-1 info gauge whose labels carry
    the process identity (package/jax/jaxlib versions, backend, device
    count).  Version labels are always present; backend/device labels
    appear once the jax backend is up (the sibling device-memory
    collector initializes it on the same scrape, so a scraped process
    is always fully described)."""
    import jax
    import jaxlib

    from deeplearning4j_tpu.version import __version__

    try:
        backend = jax.default_backend()
        device_count = jax.local_device_count()
    except Exception:
        # backend bring-up failed (e.g. the chip is held by another
        # process): the scrape
        # must still carry the version identity
        backend = "unavailable"
        device_count = 0
    reg = registry()
    info = reg.gauge("dl4jtpu_build_info")
    info.clear()        # labels changed (backend came up): one live series
    info.set(
        1,
        version=__version__,
        jax=jax.__version__,
        jaxlib=jaxlib.__version__,
        backend=str(backend),
        device_count=str(device_count),
    )


def _registry_meta_collector() -> None:
    """Registry self-description at scrape time: family count and total
    label-series count (histograms count their exposition lines).  A
    scrape that keeps growing — a label leak, an unbounded per-request
    series — shows up here before it takes the scraper down."""
    reg = registry()
    with reg._lock:
        metrics = list(reg._metrics.values())
    families = len(metrics)
    series = 0
    for m in metrics:
        if isinstance(m, Histogram):
            series += len(m.buckets) + 3        # +Inf, _sum, _count
        else:
            with m._lock:
                series += max(len(m._series), 1)
    reg.gauge("dl4jtpu_registry_families").set(families)
    reg.gauge("dl4jtpu_registry_series").set(series)


def _device_memory_collector() -> None:
    """PJRT memory stats for device 0 (no-op on backends that don't
    report, e.g. CPU)."""
    from deeplearning4j_tpu.ui.stats import device_memory_stats

    stats = device_memory_stats()
    if not stats:
        return
    reg = registry()
    if "bytes_in_use" in stats:
        reg.gauge("dl4jtpu_device_bytes_in_use").set(stats["bytes_in_use"])
    if "peak_bytes_in_use" in stats:
        reg.gauge("dl4jtpu_device_peak_bytes_in_use").set(
            stats["peak_bytes_in_use"]
        )
