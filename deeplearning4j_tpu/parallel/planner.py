"""Cost-model-driven autosharding planner — `distribute(model, auto=True)`.

The user has been hand-picking data/pipe/seq/expert axes and the ZeRO
stage, and every mesh width has a different best answer.  GSPMD
(PAPERS.md) shows placement can be DERIVED from a few annotations plus
a cost model; this module is that derivation for the strategy space
`ParallelConfig` spans:

1. **enumerate** candidate `ParallelConfig`s over the divisors of the
   mesh width (data x pipe x seq x expert, zero in {0,1,2}), filtering
   by divisibility and legality — including the uneven-shard
   restrictions — with every rejection RECORDED as a reason, never a
   crash;
2. **price** each survivor WITHOUT a device run: the model's step
   program is lowered ONCE from an abstract signature
   (`observe.cost.analyze_signature` — no dispatch, no backend
   compile) for its XLA flops/bytes, combined with the roofline peak
   table (compute- vs bandwidth-bound per candidate) and analytic
   collective terms (reduce-scatter/all-gather bytes for ZeRO, the
   pipeline bubble fraction, a per-partition hop penalty);
3. **gate** each candidate on per-replica memory feasibility
   (params + grads + opt state + activation estimate vs the cap);
4. **install** the argmin via `distribute(model, auto=True)`.

The plan is a first-class artifact: `plan()` returns a `PlanReport`
(candidates, per-term prices, rejection reasons, pick), logs a
summary, feeds the `dl4jtpu_plan_*` metric families, and the last
report is served at ``GET /api/plan``.

Capacity model caveat (mirrors BENCH_SCALING's note): virtual CPU
devices share one host's cores, so on the CPU backend the aggregate
peak is held CONSTANT across candidate widths — more virtual devices
buy collective overhead, not compute.  On real TPU devices the peaks
are independent per chip and the trade flips toward wide meshes.  The
committed BENCH_PLAN.json's predicted-vs-measured rank correlation is
the regression test that this model keeps tracking reality.

    report = plan(model, batch=example_batch)
    print(report.summary())
    distribute(model, auto=True, batch=example_batch)   # plan + install
"""

from __future__ import annotations

import dataclasses
import logging
import os
import threading
import time
from typing import Any, Optional

import numpy as np

from deeplearning4j_tpu.parallel.strategy import ParallelConfig

log = logging.getLogger("deeplearning4j_tpu")

# Analytic-term constants.  UPDATE_FLOPS_PER_PARAM is an Adam-shaped
# estimate (2 EMA updates + bias correction + apply); HOP_SECONDS is
# the per-extra-partition overhead (dispatch fan-out, layout
# bookkeeping, collective setup) — the term that makes narrow meshes
# win on shared-core virtual CPU devices, where it dominates measured
# step-time growth.  Both env-overridable for calibration.
UPDATE_FLOPS_PER_PARAM = 12.0
DEFAULT_HOP_SECONDS = {"cpu": 2e-3, "tpu": 5e-6}


class PlanError(RuntimeError):
    """No feasible candidate: the message lists every candidate's
    rejection reason so the caller can fix the actual blocker (batch
    divisibility, memory cap, analysis failure) instead of guessing."""

    def __init__(self, message: str, report: "PlanReport" = None):
        super().__init__(message)
        self.report = report


@dataclasses.dataclass
class Candidate:
    """One enumerated ParallelConfig with its verdict: priced (terms +
    predicted step seconds + memory estimate) or rejected (reason)."""

    config: ParallelConfig
    devices_used: int
    verdict: str = "priced"            # "priced" | "rejected"
    reason: Optional[str] = None
    terms: dict = dataclasses.field(default_factory=dict)
    predicted_step_seconds: Optional[float] = None
    mem_bytes_per_replica: Optional[int] = None

    def label(self) -> str:
        c = self.config
        parts = [f"data={c.data}"]
        for name in ("pipe", "seq", "expert"):
            v = getattr(c, name)
            if v != 1:
                parts.append(f"{name}={v}")
        parts.append(f"zero={c.zero or 0}")
        return " ".join(parts)

    def as_dict(self) -> dict:
        c = self.config
        return {
            "label": self.label(),
            "data": c.data, "pipe": c.pipe, "seq": c.seq,
            "expert": c.expert, "zero": c.zero or 0,
            "devices_used": self.devices_used,
            "verdict": self.verdict,
            "reason": self.reason,
            "terms": {k: round(v, 9) for k, v in self.terms.items()},
            "predicted_step_seconds": (
                round(self.predicted_step_seconds, 9)
                if self.predicted_step_seconds is not None else None
            ),
            "mem_bytes_per_replica": self.mem_bytes_per_replica,
        }


@dataclasses.dataclass
class PlanReport:
    """The whole plan: base analysis, every candidate with its price or
    rejection reason, and the pick.  `as_dict()` is the /api/plan and
    BENCH_PLAN payload."""

    n_devices: int
    batch_size: int
    model_name: str
    signature: str
    base: dict                         # flops/bytes/params/opt numbers
    candidates: list
    pick: Optional[ParallelConfig]
    plan_seconds: float

    @property
    def priced(self) -> list:
        return [c for c in self.candidates if c.verdict == "priced"]

    @property
    def rejected(self) -> list:
        return [c for c in self.candidates if c.verdict == "rejected"]

    def pick_candidate(self) -> Optional[Candidate]:
        if self.pick is None:
            return None
        for c in self.priced:
            if c.config == self.pick:
                return c
        return None

    def summary(self) -> str:
        pc = self.pick_candidate()
        lines = [
            f"plan: {len(self.priced)} priced / {len(self.rejected)} "
            f"rejected over {self.n_devices} devices "
            f"({self.plan_seconds * 1e3:.1f}ms, dispatch-free)",
        ]
        for c in sorted(
            self.priced, key=lambda c: c.predicted_step_seconds
        ):
            mark = " <-- pick" if pc is not None and c is pc else ""
            lines.append(
                f"  {c.label():<28} predicted "
                f"{c.predicted_step_seconds * 1e3:8.3f}ms  "
                f"mem/replica {c.mem_bytes_per_replica or 0:>12,}B{mark}"
            )
        for c in self.rejected:
            lines.append(f"  {c.label():<28} rejected: {c.reason}")
        return "\n".join(lines)

    def as_dict(self) -> dict:
        pc = self.pick_candidate()
        return {
            "schema": "plan-report/1",
            "n_devices": self.n_devices,
            "batch_size": self.batch_size,
            "model": self.model_name,
            "signature": self.signature,
            "base": self.base,
            "candidates": [c.as_dict() for c in self.candidates],
            "pick": pc.as_dict() if pc is not None else None,
            "plan_seconds": round(self.plan_seconds, 6),
        }


_LAST_REPORT: Optional[PlanReport] = None
_LAST_LOCK = threading.Lock()


def last_report() -> Optional[PlanReport]:
    """The most recent plan() result in this process (the /api/plan
    payload source)."""
    with _LAST_LOCK:
        return _LAST_REPORT


def _divisors(n: int) -> list:
    return [d for d in range(1, n + 1) if n % d == 0]


# -- model introspection -----------------------------------------------------

def _conf_layer_types(conf) -> list:
    if hasattr(conf, "layers"):
        return [type(l).__name__ for l in conf.layers]
    return [
        type(n.layer).__name__ for n in conf.nodes if n.layer is not None
    ]


def _batch_signature(model, batch, batch_size):
    """(features ShapeDtypeStruct, labels ShapeDtypeStruct, B): from an
    example batch when given, else derived from the model's input type
    + output layer.  Raises PlanError with the fix when underivable."""
    import jax

    if batch is not None:
        feats = getattr(batch, "features", None)
        labs = getattr(batch, "labels", None)
        if feats is None and isinstance(batch, (tuple, list)):
            feats, labs = batch[0], batch[1]
        if feats is None or labs is None:
            raise PlanError(
                f"cannot read features/labels off {type(batch).__name__};"
                " pass a DataSet or an (x, y) tuple as batch="
            )
        f = np.shape(feats)
        l = np.shape(labs)
        return (
            jax.ShapeDtypeStruct(f, getattr(feats, "dtype", np.float32)),
            jax.ShapeDtypeStruct(l, getattr(labs, "dtype", np.float32)),
            int(f[0]),
        )
    B = int(batch_size or os.environ.get("DL4J_TPU_PLAN_BATCH", "64"))
    itypes = getattr(model, "_itypes", None)
    layers = getattr(model.conf, "layers", None)
    if not itypes or not layers:
        raise PlanError(
            "cannot derive the batch signature for "
            f"{type(model).__name__}; pass an example batch= to "
            "plan()/distribute(auto=True)"
        )
    shape = tuple(int(d) for d in itypes[0].shape)
    if any(d <= 0 for d in shape):
        raise PlanError(
            f"input type {itypes[0]} has variable dims; pass an example "
            "batch= to fix the signature"
        )
    n_out = getattr(layers[-1], "n_out", None)
    if not n_out:
        raise PlanError(
            "cannot derive the label shape (last layer has no n_out); "
            "pass an example batch="
        )
    return (
        jax.ShapeDtypeStruct((B,) + shape, np.float32),
        jax.ShapeDtypeStruct((B, int(n_out)), np.float32),
        B,
    )


def _shapedtype_tree(tree):
    import jax

    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(
            tuple(np.shape(a)), getattr(a, "dtype", np.float32)
        ),
        tree,
    )


def _lower_args(model, feat_sig, lab_sig):
    """(step fn, abstract positional args) for the model's single-batch
    step program — the pricing target.  Mirrors the fit paths' dispatch
    signatures exactly (mask slots are the (0,)-f32 'empty' arrays the
    Sequential path stages, tuples for Graph)."""
    import jax

    p = _shapedtype_tree(model.params)
    o = _shapedtype_tree(model.opt_state)
    s = _shapedtype_tree(model.net_state)
    step_i = jax.ShapeDtypeStruct((), np.uint32)
    empty = jax.ShapeDtypeStruct((0,), np.float32)
    try:
        fn = model._get_step_fn(False, False, False)     # Sequential
        return fn, (p, o, s, step_i, feat_sig, lab_sig, empty, empty, {})
    except TypeError:
        fn = model._get_step_fn(0)                       # Graph
        return fn, (p, o, s, step_i, (feat_sig,), (lab_sig,), ())


# -- capacity model ----------------------------------------------------------

def _capacity(devices_used: int) -> tuple:
    """(aggregate peak FLOP/s, aggregate peak bytes/s, collective
    bytes/s, per-hop seconds, platform) for a candidate using
    `devices_used` devices.  Virtual CPU devices share one host's
    cores, so the CPU aggregate is held constant across widths (the
    per-device nominal IS the host nominal there); independent
    accelerators multiply."""
    import jax

    from deeplearning4j_tpu.observe.cost import peaks

    local = max(1, jax.local_device_count())
    total_f, total_b = peaks()
    per_dev_f, per_dev_b = total_f / local, total_b / local
    platform = jax.local_devices()[0].platform
    if platform == "cpu":
        agg_f, agg_b = per_dev_f, per_dev_b
    else:
        agg_f, agg_b = per_dev_f * devices_used, per_dev_b * devices_used
    env_bw = os.environ.get("DL4J_TPU_PLAN_COLL_BW", "")
    coll_bw = float(env_bw) if env_bw else agg_b
    env_hop = os.environ.get("DL4J_TPU_PLAN_HOP_S", "")
    hop_s = (float(env_hop) if env_hop
             else DEFAULT_HOP_SECONDS.get(platform, 1e-4))
    return agg_f, agg_b, coll_bw, hop_s, platform


# -- enumeration + legality --------------------------------------------------

def _check_legal(model, cand: Candidate, B: int, feat_ndim: int,
                 layer_types: list, n_devices: int) -> Optional[str]:
    """Reason this candidate is illegal, or None.  Every branch here is
    a RECORDED rejection, never an exception out of plan()."""
    import jax

    c = cand.config
    d, p, s, e = c.data, c.pipe, c.seq, c.expert
    zero = c.zero or 0
    if B % d:
        return f"batch {B} not divisible by data={d}"
    if zero >= 1:
        if d == 1:
            return f"zero={zero} is redundant at data=1 (no shards)"
        if p > 1 or s > 1 or e > 1:
            return (
                f"zero={zero} composes with pure data parallelism only"
            )
    if p > 1:
        if not hasattr(model, "_setup_pipeline"):
            return (
                f"{type(model).__name__} has no pipelineable segment "
                "(pipeline runs over a SequentialModel's repeated "
                "blocks)"
            )
        from deeplearning4j_tpu.parallel.pipeline import (
            plan_sequential_pipeline,
        )

        try:
            plan_sequential_pipeline(
                model.conf.layers, model.params, model._itypes, p,
                c.microbatches, net_state=model.net_state,
            )
        except Exception as exc:
            return f"pipeline plan failed for pipe={p}: {exc}"
    if s > 1:
        if not any("Attention" in t for t in layer_types):
            return (
                "sequence parallelism needs attention layers (the seq "
                "axis shards the time dim of attention ops)"
            )
        if feat_ndim < 3:
            return "batch has no time axis to shard over seq"
    if e > 1 and not any(t == "MoELayer" for t in layer_types):
        return "expert parallelism needs MoE layers"
    return None


def enumerate_candidates(model, n_devices: int, B: int, feat_ndim: int
                         ) -> list:
    """Every (data x pipe x seq x expert, zero) combination over the
    divisors of the mesh width — INCLUDING underfilled shapes (a
    narrower mesh than the hardware offers is a legal answer where
    partition overhead outruns the parallel win, and a hand config a
    user might plausibly pick).  Illegal combinations come back as
    rejected candidates with reasons."""
    layer_types = _conf_layer_types(model.conf)
    out = []
    divs = _divisors(n_devices)
    for d in divs:
        for p in divs:
            for s in divs:
                for e in divs:
                    if d * p * s * e > n_devices:
                        continue
                    # ZeRO stages only vary where they are meaningful:
                    # pure DP with real shards
                    zeros = (0, 1, 2) if (
                        d > 1 and p == 1 and s == 1 and e == 1
                    ) else (0,)
                    for z in zeros:
                        cand = Candidate(
                            config=ParallelConfig(
                                data=d, pipe=p, seq=s, expert=e, zero=z
                            ),
                            devices_used=d * p * s * e,
                        )
                        reason = _check_legal(
                            model, cand, B, feat_ndim, layer_types,
                            n_devices,
                        )
                        if reason is not None:
                            cand.verdict = "rejected"
                            cand.reason = reason
                        out.append(cand)
    return out


# -- pricing -----------------------------------------------------------------

def _price(cand: Candidate, base: dict, memory_cap_bytes: Optional[int]
           ) -> None:
    """Fill the candidate's analytic price terms and memory estimate,
    or reject it on the memory gate.  All closed-form — the one XLA
    lowering happened once, in plan()."""
    c = cand.config
    d, p = c.data, c.pipe
    n_used = cand.devices_used
    zero = c.zero or 0
    F = base["flops"]
    Bb = base["bytes_accessed"] or 0.0
    P = base["params_bytes"]
    opt_full = base["opt_state_bytes"]
    n_params = base["param_count"]
    agg_f, agg_b, coll_bw, hop_s, _ = base["_capacity_fn"](n_used)

    compute_s = F / agg_f if agg_f else 0.0
    memory_s = Bb / agg_b if agg_b else 0.0
    roofline_s = max(compute_s, memory_s)
    bound = "compute" if compute_s >= memory_s else "memory"

    # pipeline bubble: with m microbatches and p stages the fraction
    # (p-1)/(m+p-1) of the schedule is idle — multiply the roofline
    # term by (m+p-1)/m
    bubble_frac = 0.0
    if p > 1:
        m = c.microbatches or 2 * p
        bubble_frac = (p - 1) / (m + p - 1)
        roofline_s = roofline_s / (1.0 - bubble_frac)

    # data-axis gradient exchange: all-reduce (zero=0) or the
    # reduce-scatter + all-gather pair (zero>=1) — same ring bytes
    coll_bytes = 2.0 * (d - 1) / d * P if d > 1 else 0.0
    coll_s = coll_bytes / coll_bw if coll_bw else 0.0
    hop_penalty_s = (n_used - 1) * hop_s

    # update epilogue: replicated runs the FULL update on every
    # replica (d x total work on shared cores), sharded runs 1/d per
    # replica (total work constant); ZeRO-2 adds the accumulator add
    update_flops = UPDATE_FLOPS_PER_PARAM * n_params
    if zero >= 1:
        update_total = update_flops
        if zero == 2:
            update_total += n_params / d
    else:
        update_total = update_flops * d
    update_s = update_total / agg_f if agg_f else 0.0

    predicted = roofline_s + coll_s + hop_penalty_s + update_s

    # per-replica memory: replicated params + grads (sharded only
    # under zero=2's persistent accumulator) + opt state (sharded
    # under zero>=1) + an activation estimate from the base program's
    # bytes-accessed split over the mesh
    grads_b = P / d if zero == 2 else P
    opt_b = opt_full / d if zero >= 1 else opt_full
    act_b = Bb / n_used
    mem = int(P + grads_b + opt_b + act_b)

    cand.terms = {
        "compute_seconds": compute_s,
        "memory_seconds": memory_s,
        "bound_" + bound: 1.0,
        "bubble_fraction": bubble_frac,
        "collective_seconds": coll_s,
        "hop_penalty_seconds": hop_penalty_s,
        "update_seconds": update_s,
    }
    cand.predicted_step_seconds = predicted
    cand.mem_bytes_per_replica = mem
    if memory_cap_bytes is not None and mem > memory_cap_bytes:
        cand.verdict = "rejected"
        cand.reason = (
            f"memory infeasible: ~{mem:,}B/replica > cap "
            f"{memory_cap_bytes:,}B (params {int(P):,} + grads "
            f"{int(grads_b):,} + opt {int(opt_b):,} + act "
            f"{int(act_b):,})"
        )


# -- the planner entry point -------------------------------------------------

def plan(model, n_devices: Optional[int] = None, devices=None,
         batch=None, batch_size: Optional[int] = None,
         memory_cap_bytes: Optional[int] = None) -> PlanReport:
    """Enumerate, price and rank candidate placements for `model` on an
    `n_devices`-wide mesh — dispatch-free (one abstract lowering, zero
    device executions, zero backend compiles).  Returns the PlanReport;
    raises PlanError (listing every candidate's reason) when nothing is
    feasible.  `memory_cap_bytes` defaults to DL4J_TPU_PLAN_MEM_CAP."""
    import jax

    from deeplearning4j_tpu.observe import cost
    from deeplearning4j_tpu.parallel.zero import unwrap_opt_state
    from deeplearning4j_tpu.utils.pytree import param_count, tree_bytes

    t0 = time.perf_counter()
    if model.params is None:
        model.init()
    if devices is not None:
        n = n_devices or len(devices)
    else:
        # the GLOBAL device count — distribute(auto=True) installs the
        # pick by slicing jax.devices(), so the priced width must
        # describe the same list
        n = n_devices or jax.device_count()
    if memory_cap_bytes is None:
        cap_env = os.environ.get("DL4J_TPU_PLAN_MEM_CAP", "")
        memory_cap_bytes = int(cap_env) if cap_env else None

    feat_sig, lab_sig, B = _batch_signature(model, batch, batch_size)

    # one dispatch-free lowering of the model's own step program —
    # analysis failure becomes every candidate's rejection reason, not
    # a garbage price
    analysis_reason = None
    ana = None
    try:
        fn, args = _lower_args(model, feat_sig, lab_sig)
        ana = cost.analyze_signature(fn, args)
        if not ana.ok:
            analysis_reason = ana.reason
    except Exception as e:
        analysis_reason = f"step lowering failed ({type(e).__name__}: {e})"

    base = {
        "flops": ana.flops if ana is not None and ana.ok else None,
        "bytes_accessed": (
            ana.bytes_accessed if ana is not None else None
        ),
        "params_bytes": tree_bytes(model.params),
        # inner optax state only: re-planning an already-distributed
        # zero=2 model must not double-count its (params-sized, zeroed)
        # grad accumulator as optimizer state — the grads term already
        # prices gradient residency per candidate
        "opt_state_bytes": (
            tree_bytes(unwrap_opt_state(model.opt_state)[0])
            if model.opt_state is not None else 0
        ),
        "param_count": param_count(model.params),
        "analysis_reason": analysis_reason,
        "_capacity_fn": _capacity,
    }

    candidates = enumerate_candidates(model, n, B, len(feat_sig.shape))
    for cand in candidates:
        if cand.verdict == "rejected":
            continue
        if analysis_reason is not None:
            cand.verdict = "rejected"
            cand.reason = f"analysis: {analysis_reason}"
            continue
        _price(cand, base, memory_cap_bytes)

    priced = [c for c in candidates if c.verdict == "priced"]
    pick = None
    if priced:
        pick = min(priced, key=lambda c: c.predicted_step_seconds).config

    base_out = {k: v for k, v in base.items() if not k.startswith("_")}
    report = PlanReport(
        n_devices=n,
        batch_size=B,
        model_name=type(model).__name__,
        signature=(
            f"{feat_sig.dtype}{list(feat_sig.shape)} "
            f"{lab_sig.dtype}{list(lab_sig.shape)}"
        ),
        base=base_out,
        candidates=candidates,
        pick=pick,
        plan_seconds=time.perf_counter() - t0,
    )
    global _LAST_REPORT
    with _LAST_LOCK:
        _LAST_REPORT = report
    try:
        from deeplearning4j_tpu.observe.metrics import registry

        reg = registry()
        cnt = reg.counter("dl4jtpu_plan_candidates_total")
        cnt.inc(len(report.priced), verdict="priced")
        cnt.inc(len(report.rejected), verdict="rejected")
        reg.gauge("dl4jtpu_plan_seconds").set(report.plan_seconds)
        pc = report.pick_candidate()
        if pc is not None:
            reg.gauge("dl4jtpu_plan_predicted_step_seconds").set(
                pc.predicted_step_seconds
            )
    except Exception as e:          # telemetry must never fail planning
        log.debug("plan metrics failed: %s", e)
    log.info("%s", report.summary())
    if pick is None:
        raise PlanError(
            "no feasible placement for "
            f"{type(model).__name__} on {n} devices:\n"
            + "\n".join(
                f"  {c.label()}: {c.reason}" for c in report.rejected
            ),
            report=report,
        )
    return report
