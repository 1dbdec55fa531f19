"""Autosharding planner (parallel/planner.py, distribute(auto=True)).

The contract under test: candidates are enumerated with recorded
rejection reasons (never crashes), priced WITHOUT any device execution
or backend compile (the dispatch-free contract, compile-stats-asserted),
gated on per-replica memory, and the argmin installed — with the known
scenarios picking what a practitioner would: a tiny model on a wide
shared-core mesh goes pure narrow DP, an opt-state-dominated model
under a tight memory cap goes zero>=1, and an impossible cap raises an
actionable PlanError listing every candidate's reason.
"""

import numpy as np
import pytest

import jax

from deeplearning4j_tpu.models import SequentialModel
from deeplearning4j_tpu.nn import Adam
from deeplearning4j_tpu.nn.activations import Activation
from deeplearning4j_tpu.nn.conf import (
    Dense,
    InputType,
    NeuralNetConfiguration,
    OutputLayer,
)
from deeplearning4j_tpu.nn.losses import Loss
from deeplearning4j_tpu.parallel import (
    ParallelConfig,
    PlanError,
    distribute,
    plan,
)
from deeplearning4j_tpu.parallel.planner import last_report

N_DEV = 8
IN = 64


def mlp_conf(hidden=(64, 32), n_out=8, seed=9):
    b = (
        NeuralNetConfiguration.builder()
        .seed(seed)
        .updater(Adam(1e-2))
        .activation(Activation.RELU)
        .list()
    )
    for h in hidden:
        b = b.layer(Dense(n_out=h))
    return (
        b.layer(OutputLayer(n_out=n_out, loss=Loss.MCXENT,
                            activation=Activation.SOFTMAX))
        .set_input_type(InputType.feed_forward(IN))
        .build()
    )


@pytest.mark.plan
class TestDispatchFreeContract:
    def test_plan_runs_nothing_on_device(self):
        """Zero backend compiles and zero step dispatches during
        planning — the acceptance criterion, compile-stats-asserted."""
        from deeplearning4j_tpu.observe import cost
        from deeplearning4j_tpu.runtime import compile_stats

        m = SequentialModel(mlp_conf()).init()
        before = compile_stats.snapshot()
        report = plan(m, n_devices=N_DEV, batch_size=64)
        spent = compile_stats.snapshot() - before
        assert spent.backend_compiles == 0
        assert all(
            r.dispatches == 0
            for r in cost.registry().programs()
            if r.owner_ref() is m
        )
        assert report.priced and report.pick is not None

    def test_plan_is_fast_on_cpu_host(self):
        """The PROFILE budget: a candidate set prices in < 2s."""
        m = SequentialModel(mlp_conf()).init()
        report = plan(m, n_devices=N_DEV, batch_size=64)
        assert report.plan_seconds < 2.0

    def test_analysis_failure_flows_into_rejection_reasons(self):
        """When the base lowering cannot be priced, candidates are
        rejected with the analysis reason — never priced at garbage."""
        from deeplearning4j_tpu.observe import cost

        ana = cost.analyze_signature(object(), ())
        assert not ana.ok and "lower" in ana.reason

        m = SequentialModel(mlp_conf()).init()
        # poison the step builder so the lowering target raises
        m._get_step_fn = None
        with pytest.raises(PlanError) as ei:
            plan(m, n_devices=N_DEV, batch_size=64)
        rep = ei.value.report
        assert rep is not None
        assert all(c.verdict == "rejected" for c in rep.candidates)
        assert any("analysis" in (c.reason or "")
                   for c in rep.candidates)


@pytest.mark.plan
class TestKnownScenarioPicks:
    def test_tiny_model_on_wide_shared_core_mesh_goes_narrow_dp(self):
        """On the virtual CPU mesh the aggregate peak is constant
        across widths (shared cores), so a tiny fixed-work model's best
        placement is the narrowest: pure DP, no ZeRO shards."""
        m = SequentialModel(mlp_conf(hidden=(16,))).init()
        report = plan(m, n_devices=N_DEV, batch_size=64)
        pick = report.pick
        assert pick.data == 1 and (pick.zero or 0) == 0
        assert pick.pipe == pick.seq == pick.expert == 1

    def test_tight_memory_cap_forces_zero_stage(self):
        """Opt-state-dominated model + a cap the replicated footprint
        cannot meet: only sharded-state candidates survive the gate, so
        the pick carries zero >= 1."""
        m = SequentialModel(mlp_conf(hidden=(256, 256))).init()
        unlimited = plan(m, n_devices=N_DEV, batch_size=64)
        full = max(
            c.mem_bytes_per_replica for c in unlimited.priced
            if (c.config.zero or 0) == 0
        )
        sharded_min = min(
            c.mem_bytes_per_replica for c in unlimited.priced
            if (c.config.zero or 0) >= 1
        )
        cap = (full + sharded_min) // 2
        report = plan(m, n_devices=N_DEV, batch_size=64,
                      memory_cap_bytes=cap)
        assert (report.pick.zero or 0) >= 1
        # the replicated candidates were rejected BY THE GATE, with the
        # arithmetic in the reason
        gated = [c for c in report.rejected
                 if "memory infeasible" in (c.reason or "")]
        assert gated and all("cap" in c.reason for c in gated)

    def test_infeasible_everywhere_raises_actionable_plan_error(self):
        m = SequentialModel(mlp_conf()).init()
        with pytest.raises(PlanError) as ei:
            plan(m, n_devices=N_DEV, batch_size=64,
                 memory_cap_bytes=1024)
        msg = str(ei.value)
        # every candidate's reason is listed
        assert "memory infeasible" in msg
        assert "data=8" in msg and "data=1" in msg
        assert ei.value.report.pick is None

    def test_price_monotonicity_fixed_work_on_accelerator_model(self):
        """On independent accelerators (peaks multiply with width) the
        predicted step time is non-increasing as the mesh grows for the
        fixed-work proxy — the sanity direction of the cost model.  The
        CPU capacity model is exercised via DL4J_TPU_PLAN_HOP_S=0 plus
        a neutral collective bandwidth; independence is simulated by
        pricing per-width plans of the width itself."""
        from deeplearning4j_tpu.parallel import planner

        base = {
            "flops": 1e9, "bytes_accessed": 1e8,
            "params_bytes": 4e6, "opt_state_bytes": 8e6,
            "param_count": 1e6, "analysis_reason": None,
            "_capacity_fn": lambda n: (1e11 * n, 5e10 * n, 5e10 * n,
                                       0.0, "tpu"),
        }
        preds = []
        for n in (1, 2, 4, 8):
            cand = planner.Candidate(
                config=ParallelConfig(data=n, zero=1 if n > 1 else 0),
                devices_used=n,
            )
            planner._price(cand, base, None)
            preds.append(cand.predicted_step_seconds)
        assert all(b <= a * (1 + 1e-9)
                   for a, b in zip(preds, preds[1:])), preds


@pytest.mark.plan
class TestEnumerationLegality:
    def test_rejections_carry_reasons_not_crashes(self):
        m = SequentialModel(mlp_conf()).init()
        report = plan(m, n_devices=N_DEV, batch_size=64)
        reasons = {c.reason for c in report.rejected}
        assert any("expert" in r for r in reasons)
        assert any("attention" in r for r in reasons)
        assert any("pipeline" in r or "pipe" in r for r in reasons)

    def test_batch_divisibility_rejection(self):
        m = SequentialModel(mlp_conf()).init()
        report = plan(m, n_devices=N_DEV, batch_size=60)
        bad = [c for c in report.rejected
               if "not divisible" in (c.reason or "")]
        assert any(c.config.data == 8 for c in bad)

    def test_zero_redundant_at_data_1(self):
        m = SequentialModel(mlp_conf()).init()
        report = plan(m, n_devices=N_DEV, batch_size=64)
        assert not any(
            c.config.data == 1 and (c.config.zero or 0) >= 1
            for c in report.priced
        )

    def test_underfilled_meshes_are_candidates(self):
        """A narrower mesh than the hardware offers is a legal answer
        (and on shared cores, often the right one)."""
        m = SequentialModel(mlp_conf()).init()
        report = plan(m, n_devices=N_DEV, batch_size=64)
        assert any(c.devices_used < N_DEV for c in report.priced)


@pytest.mark.plan
class TestAutoDistribute:
    def test_auto_plans_and_installs_the_pick(self):
        m = SequentialModel(mlp_conf()).init()
        distribute(m, auto=True)
        rep = m._plan_report
        assert rep is not None and rep.pick is not None
        # the installed mesh is exactly the pick's size
        used = rep.pick_candidate().devices_used
        assert int(np.prod(list(m._mesh.shape.values()))) == used
        # and the model still trains
        from deeplearning4j_tpu.data import NumpyDataSetIterator

        rng = np.random.default_rng(0)
        x = rng.normal(size=(128, IN)).astype(np.float32)
        y = np.eye(8, dtype=np.float32)[
            rng.integers(0, 8, 128)
        ]
        m.fit(NumpyDataSetIterator(x, y, batch_size=64, seed=1),
              epochs=1)
        assert np.isfinite(m.score_value)

    def test_auto_with_explicit_config_raises(self):
        m = SequentialModel(mlp_conf()).init()
        with pytest.raises(ValueError, match="auto"):
            distribute(m, ParallelConfig(data=2), auto=True)

    def test_auto_with_explicit_mesh_raises(self):
        """An explicit mesh would silently override the pick's device
        sizing — rejected like config+auto."""
        from deeplearning4j_tpu.runtime.mesh import MeshSpec, make_mesh

        m = SequentialModel(mlp_conf()).init()
        with pytest.raises(ValueError, match="mesh"):
            distribute(m, auto=True,
                       mesh=make_mesh(MeshSpec.data_parallel()))

    def test_env_knob_enables_auto_plan(self, monkeypatch):
        from deeplearning4j_tpu.runtime.flags import environment

        monkeypatch.setattr(environment(), "auto_plan", True)
        m = SequentialModel(mlp_conf()).init()
        distribute(m)               # no config -> env knob -> planner
        assert m._plan_report is not None
        # an explicit config bypasses the planner even with the knob on
        m2 = SequentialModel(mlp_conf()).init()
        distribute(m2, ParallelConfig(data=2), devices=jax.devices()[:2])
        assert getattr(m2, "_plan_report", None) is None

    def test_replan_of_zero2_model_does_not_double_count_opt_state(self):
        """Re-planning an already-distributed zero=2 model: the wrapped
        grad accumulator is GRADIENT state, not optimizer state — the
        base opt_state_bytes must match a fresh model's."""
        from deeplearning4j_tpu.utils.pytree import tree_bytes

        fresh = SequentialModel(mlp_conf()).init()
        fresh_opt = tree_bytes(fresh.opt_state)
        m = SequentialModel(mlp_conf()).init()
        distribute(m, ParallelConfig(data=N_DEV, zero=2))
        report = plan(m, n_devices=N_DEV, batch_size=64)
        assert report.base["opt_state_bytes"] == fresh_opt

    def test_batch_example_fixes_signature(self):
        from deeplearning4j_tpu.data import DataSet

        m = SequentialModel(mlp_conf()).init()
        rng = np.random.default_rng(0)
        ds = DataSet(
            rng.normal(size=(96, IN)).astype(np.float32),
            np.eye(8, dtype=np.float32)[rng.integers(0, 8, 96)],
        )
        report = plan(m, n_devices=N_DEV, batch=ds)
        assert report.batch_size == 96


@pytest.mark.plan
class TestReportSurface:
    def test_report_dict_and_api_payload(self):
        m = SequentialModel(mlp_conf()).init()
        report = plan(m, n_devices=N_DEV, batch_size=64)
        d = report.as_dict()
        assert d["schema"] == "plan-report/1"
        assert d["pick"]["verdict"] == "priced"
        assert all(
            set(c) >= {"label", "verdict", "predicted_step_seconds"}
            for c in d["candidates"]
        )
        priced = [c for c in d["candidates"] if c["verdict"] == "priced"]
        assert all(
            c["terms"].get("compute_seconds") is not None
            for c in priced
        )
        assert last_report() is report

    def test_plan_metrics_families(self):
        from deeplearning4j_tpu.observe.metrics import registry

        m = SequentialModel(mlp_conf()).init()
        reg = registry()
        c = reg.counter("dl4jtpu_plan_candidates_total")
        before_priced = c.value(verdict="priced")
        report = plan(m, n_devices=N_DEV, batch_size=64)
        assert c.value(verdict="priced") == before_priced + len(
            report.priced
        )
        assert reg.gauge("dl4jtpu_plan_seconds").value() > 0
        assert reg.gauge(
            "dl4jtpu_plan_predicted_step_seconds"
        ).value() == pytest.approx(
            report.pick_candidate().predicted_step_seconds
        )

    def test_summary_names_the_pick(self):
        m = SequentialModel(mlp_conf()).init()
        report = plan(m, n_devices=N_DEV, batch_size=64)
        s = report.summary()
        assert "<-- pick" in s and "rejected" in s

    def test_api_plan_endpoint_serves_last_report(self):
        import json
        import urllib.request

        from deeplearning4j_tpu.ui import UIServer

        m = SequentialModel(mlp_conf()).init()
        report = plan(m, n_devices=N_DEV, batch_size=64)
        server = UIServer(port=0)
        try:
            with urllib.request.urlopen(server.url + "api/plan") as r:
                doc = json.loads(r.read())
            assert doc["schema"] == "plan-report/1"
            assert doc["pick"]["label"] == report.pick_candidate().label()
            assert len(doc["candidates"]) == len(report.candidates)
        finally:
            server.stop()
