"""tpulint: golden-fixture rule tests, suppression/baseline semantics,
reporter schema, and the tier-1 drift gate over the real package."""

import json
import os
import subprocess
import sys

import pytest

from deeplearning4j_tpu.analysis import (
    Finding, LintContext, RULE_CATALOG, lint_paths, load_baseline,
    parse_json, render_json, render_text,
)
from deeplearning4j_tpu.analysis.baseline import (
    Baseline, BaselineEntry, BaselineError,
)
from deeplearning4j_tpu.analysis import tomlmini

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
FIXTURES = os.path.join(HERE, "lint_fixtures")
PKG = os.path.join(REPO, "deeplearning4j_tpu")


def lint_fixture(name, **ctx_kw):
    ctx = LintContext(project_root=FIXTURES, **ctx_kw)
    findings, errors = lint_paths(ctx, [os.path.join(FIXTURES, name)])
    assert errors == []
    return findings


def pairs(findings):
    return [(f.rule, f.line) for f in findings]


# -- golden fixtures: one file per rule family -------------------------


class TestGoldenFixtures:
    def test_tp_trace_purity(self):
        got = lint_fixture("tp_violations.py")
        assert pairs(got) == [
            ("TP001", 15),       # time.time() in jitted body
            ("TP002", 16),       # print() in jitted body
            ("TP003", 17),       # global mutation in jitted body
            ("TP004", 24),       # registry() via one-level helper
            ("TP002", 34),       # print() in a keyword-passed scan body
            ("TP001", 44),       # time.time() in a @device_transform body
        ]
        # helper findings say how the traced context reached them
        assert "telemetry_step -> bump_metrics" in got[3].message
        assert got[0].symbol == "impure_step"

    def test_tp_pallas_kernels_are_jit_scopes(self):
        """ISSUE 14: the function handed to pl.pallas_call — bare or
        wrapped in functools.partial — is a traced region for the TP
        family, with the partial's keyword bindings treated as static
        (a kernel's `if causal:` is specialization, not a tracer
        branch); a pure kernel stays silent."""
        got = lint_fixture("tp_pallas.py")
        assert pairs(got) == [
            ("TP001", 14),       # time.time() in a pallas kernel
            ("TP002", 27),       # print() in a partial-wrapped kernel
        ]
        # `if causal:` (static partial kw, line 25) must NOT flag RH102
        assert not any(f.line == 25 for f in got)

    def test_rh_recompile_hazards(self):
        got = lint_fixture("rh_violations.py")
        assert pairs(got) == [
            ("RH101", 14),       # int(x)
            ("RH101", 15),       # x.item()
            ("RH101", 16),       # np.asarray(y)
            ("RH102", 17),       # if x > 0
            ("RH102", 19),       # while y
            ("RH103", 21),       # f"x was {x}"
            ("RH102", 32),       # if on tracer-DERIVED name
            ("RH101", 38),       # float() inside a lax.scan body
            ("RH105", 52),       # params read after donation
            ("RH105", 53),       # opt read after donation
            ("RH105", 69),       # loop back-edge: re-donation, no rebind
            ("RH105", 79),       # shard view through a donated tree
        ]
        # the negative space: static_argnames params, .ndim/.shape
        # branches (lines 27/29), and donated args REBOUND from the
        # call's results (donation_rebound_ok, lines 56-61) must NOT
        # appear
        assert not any(f.line in (27, 29) for f in got)
        assert not any(56 <= f.line <= 61 for f in got)

    def test_lk_lock_discipline(self):
        got = lint_fixture("lk_violations.py")
        assert pairs(got) == [
            ("LK202", 13),       # module dict without module lock
            ("LK201", 28),       # .append() outside with self._lock
            ("LK201", 31),       # item assignment outside lock
            ("LK201", 34),       # container rebinding outside lock
            ("LK202", 46),       # annotated (`X: dict = {}`) container
        ]
        # locked mutations (module_locked / add_locked) stay silent
        assert not any(f.line in (18, 38, 39) for f in got)

    def test_rg_registry_drift(self):
        got = lint_fixture(
            "rg_violations.py",
            declared_families={"dl4jtpu_known_total"},
            fault_sites={"known.site"},
            declared_marks={"slow"},
        )
        assert pairs(got) == [
            ("RG301", 18),       # undeclared metric family
            ("RG302", 26),       # unregistered fault site
            ("RG303", 34),       # undeclared pytest mark
        ]

    def test_eh_error_hygiene(self):
        got = lint_fixture("eh_violations.py")
        assert pairs(got) == [
            ("EH401", 12),       # bare except
            ("EH402", 19),       # except Exception: pass
            ("EH403", 31),       # checkpoint write without tmp+replace
        ]

    def test_clean_file_zero_findings(self):
        assert lint_fixture("clean.py") == []

    def test_shared_helper_reported_once(self, tmp_path):
        # a helper reachable from two jitted roots is one defect site
        p = tmp_path / "shared.py"
        p.write_text(
            "import time\nimport jax\n\n\n"
            "def helper():\n    return time.time()\n\n\n"
            "@jax.jit\ndef a(x):\n    return x + helper()\n\n\n"
            "@jax.jit\ndef b(x):\n    return x - helper()\n"
        )
        ctx = LintContext(project_root=str(tmp_path))
        findings, errors = lint_paths(ctx, [str(p)])
        assert errors == []
        assert [(f.rule, f.line) for f in findings] == [("TP001", 6)]

    def test_every_emitted_rule_is_in_catalog(self):
        seen = set()
        for name in os.listdir(FIXTURES):
            if name.endswith("_violations.py"):
                seen |= {
                    f.rule for f in lint_fixture(
                        name, declared_families=set(), fault_sites=set(),
                        declared_marks=set(),
                    )
                }
        assert seen <= set(RULE_CATALOG)
        # all five families are represented by the fixtures
        assert {r[:2] for r in seen} == {"TP", "RH", "LK", "RG", "EH"}


# -- suppressions ------------------------------------------------------


class TestSuppressions:
    def test_suppressed_file_is_clean(self):
        assert lint_fixture("suppressed.py") == []

    def test_select_filter(self):
        got = lint_fixture("tp_violations.py", select={"TP001"})
        assert [f.rule for f in got] == ["TP001", "TP001"]


# -- baseline ----------------------------------------------------------


class TestBaseline:
    def test_match_by_line_text_survives_drift(self):
        e = BaselineEntry(
            rule="LK201", file="a.py", reason="caller holds lock",
            line_text="self.items.append(x)",
        )
        f = Finding("LK201", "a.py", 99, 0, "msg")
        assert e.matches(f, "        self.items.append(x)")
        assert not e.matches(f, "self.other.append(x)")

    def test_reason_required(self, tmp_path):
        p = tmp_path / "b.toml"
        p.write_text(
            '[[suppress]]\nrule = "LK201"\nfile = "a.py"\nreason = ""\n'
        )
        with pytest.raises(BaselineError, match="reason"):
            load_baseline(str(p))

    def test_unused_entries_reported(self):
        base = Baseline([BaselineEntry(
            rule="TP001", file="gone.py", reason="was a false positive",
        )])
        assert base.match(
            Finding("TP001", "gone.py", 1, 0, "m"), "x"
        )
        assert base.unused() == []
        stale = Baseline([BaselineEntry(
            rule="TP001", file="gone.py", reason="was a false positive",
        )])
        assert len(stale.unused()) == 1

    def test_repo_baseline_is_well_formed(self):
        # every shipped entry must carry a written justification
        load_baseline(os.path.join(PKG, "analysis", "baseline.toml"))


# -- reporters ---------------------------------------------------------


class TestReporters:
    def test_json_round_trip(self):
        findings = lint_fixture("eh_violations.py")
        doc = parse_json(render_json(findings, [], [], [], FIXTURES))
        assert doc["schema"] == "tpulint-report/1"
        assert doc["findings"] == findings
        assert doc["counts"] == {"EH401": 1, "EH402": 1, "EH403": 1}

    def test_json_rejects_foreign_documents(self):
        with pytest.raises(ValueError):
            parse_json(json.dumps({"schema": "something-else"}))

    def test_text_summary(self):
        findings = lint_fixture("tp_violations.py")
        text = render_text(findings, [], [], [])
        assert "tpulint: 6 findings" in text
        assert "tp_violations.py:15:" in text
        clean = render_text([], [], [], [])
        assert clean == "tpulint: clean"


# -- tomlmini ----------------------------------------------------------


class TestTomlMini:
    def test_array_of_tables_and_strings(self):
        doc = tomlmini.parse(
            '# c\n[[suppress]]\nrule = "LK201"\nreason = "x \\"q\\""\n'
            '[[suppress]]\nrule = "TP001"\nreason = "y"\n'
        )
        assert [e["rule"] for e in doc["suppress"]] == ["LK201", "TP001"]
        assert doc["suppress"][0]["reason"] == 'x "q"'

    def test_multiline_string_array(self):
        doc = tomlmini.parse('xs = [\n  "a: one",\n  "b: two",\n]\n')
        assert doc["xs"] == ["a: one", "b: two"]

    def test_out_of_subset_raises(self):
        with pytest.raises(tomlmini.TomlSubsetError):
            tomlmini.parse("x = 5\n")
        with pytest.raises(tomlmini.TomlSubsetError):
            tomlmini.parse("x = { a = 1 }\n")


# -- the tier-1 gate ---------------------------------------------------


class TestTier1Gate:
    def test_package_is_clean_modulo_baseline(self):
        """THE gate: tpulint over deeplearning4j_tpu/ must report zero
        non-baselined findings, with no stale baseline entries and no
        unparseable files.  A new finding = fix it or (false positives
        only, with a reason) baseline it."""
        ctx = LintContext(project_root=REPO)
        findings, errors = lint_paths(ctx, [PKG])
        assert errors == []
        base = load_baseline(os.path.join(PKG, "analysis", "baseline.toml"))
        kept = []
        for f in findings:
            with open(os.path.join(REPO, f.file), encoding="utf-8") as fh:
                line = fh.read().splitlines()[f.line - 1]
            if not base.match(f, line):
                kept.append(f)
        assert kept == [], (
            "new tpulint findings (fix them, or baseline false "
            "positives with a reason):\n"
            + "\n".join(f"{f.file}:{f.line}: {f.rule} {f.message}"
                        for f in kept)
        )
        assert base.unused() == [], (
            "stale baseline entries (the finding is gone; delete them): "
            f"{[(e.rule, e.file) for e in base.unused()]}"
        )

    def test_analyzer_and_fleet_entrypoint_self_check(self):
        """tpulint is clean on itself and on the subprocess fleet
        entrypoint (the script that runs furthest from a debugger)."""
        ctx = LintContext(project_root=REPO)
        findings, errors = lint_paths(ctx, [
            os.path.join(PKG, "analysis"),
            os.path.join(HERE, "elastic_worker.py"),
        ])
        assert errors == []
        assert findings == []

    def test_registry_loaders_see_the_real_tables(self):
        from deeplearning4j_tpu.analysis.rules.registry import (
            load_declared_families, load_declared_marks, load_fault_sites,
        )
        fams = load_declared_families(REPO)
        assert "dl4jtpu_train_steps_total" in fams
        assert "dl4jtpu_coordinator_members" in fams     # PR-4 addition
        # ISSUE-8 performance-attribution / fleet / identity families
        assert {
            "dl4jtpu_step_model_flops_total", "dl4jtpu_step_mfu",
            "dl4jtpu_programs_registered",
            "dl4jtpu_trace_spans_dropped_total", "dl4jtpu_build_info",
            "dl4jtpu_fleet_workers", "dl4jtpu_fleet_step_latency_skew",
            "dl4jtpu_fleet_stragglers",
        } <= fams
        # ISSUE-10 ZeRO-1 sharded-update families
        assert {
            "dl4jtpu_opt_state_bytes", "dl4jtpu_update_seconds_total",
        } <= fams
        # ISSUE-11 serving-plane + supervisor-backoff families
        assert {
            "dl4jtpu_serving_requests_total",
            "dl4jtpu_serving_shed_total",
            "dl4jtpu_serving_request_latency_seconds",
            "dl4jtpu_serving_queue_depth",
            "dl4jtpu_serving_batch_occupancy",
            "dl4jtpu_serving_batches_total",
            "dl4jtpu_serving_breaker_state",
            "dl4jtpu_serving_breaker_transitions_total",
            "dl4jtpu_serving_hotswap_total",
            "dl4jtpu_serving_weights_generation",
            "dl4jtpu_supervisor_backoff_seconds",
        } <= fams
        # ISSUE-12 serving-fleet front-door families
        assert {
            "dl4jtpu_router_requests_total",
            "dl4jtpu_router_retries_total",
            "dl4jtpu_router_hedges_total",
            "dl4jtpu_replica_ejections_total",
            "dl4jtpu_fleet_deploy_generation",
            "dl4jtpu_canary_failures_total",
            "dl4jtpu_router_replica_pressure",
        } <= fams
        # ISSUE-13 request-attribution / SLO / meta-observability families
        assert {
            "dl4jtpu_serving_queue_wait_seconds",
            "dl4jtpu_serving_batch_form_seconds",
            "dl4jtpu_serving_dispatch_seconds",
            "dl4jtpu_serving_pad_overhead_seconds",
            "dl4jtpu_serving_batch_examples_total",
            "dl4jtpu_router_overhead_seconds",
            "dl4jtpu_slo_burn_rate",
            "dl4jtpu_slo_error_budget_remaining",
            "dl4jtpu_slo_alert_active",
            "dl4jtpu_slo_alerts_total",
            "dl4jtpu_scrape_seconds",
            "dl4jtpu_registry_families",
            "dl4jtpu_registry_series",
        } <= fams
        # ISSUE-14 int8 post-training-quantization families
        assert {
            "dl4jtpu_quant_params_bytes",
            "dl4jtpu_quant_dequant_matmul_total",
            "dl4jtpu_quant_parity_checks_total",
        } <= fams
        # ISSUE-15 autosharding-planner + ZeRO-2 families
        assert {
            "dl4jtpu_plan_candidates_total",
            "dl4jtpu_plan_seconds",
            "dl4jtpu_plan_predicted_step_seconds",
            "dl4jtpu_grad_state_bytes",
        } <= fams
        # ISSUE-16 token-generation serving families
        assert {
            "dl4jtpu_decode_tokens_total",
            "dl4jtpu_kv_pages_used",
            "dl4jtpu_kv_pages_total",
            "dl4jtpu_ttft_seconds",
            "dl4jtpu_decode_batch_occupancy",
            "dl4jtpu_paged_attention_total",
            "dl4jtpu_flash_attention_total",
        } <= fams
        # ISSUE-17 generation-plane observability families
        assert {
            "dl4jtpu_generation_streams_admitted_total",
            "dl4jtpu_generation_streams_total",
            "dl4jtpu_generation_queue_seconds",
            "dl4jtpu_generation_prefill_seconds",
            "dl4jtpu_generation_handoff_seconds",
            "dl4jtpu_generation_decode_queue_seconds",
            "dl4jtpu_generation_decode_compute_seconds",
            "dl4jtpu_generation_sampling_seconds",
            "dl4jtpu_flight_records",
            "dl4jtpu_flight_dumps_total",
        } <= fams
        # ISSUE-20 speculative-decoding families
        assert {
            "dl4jtpu_spec_tokens_total",
            "dl4jtpu_spec_acceptance_ratio",
            "dl4jtpu_spec_tokens_per_dispatch",
        } <= fams
        sites = load_fault_sites(REPO)
        assert sites == {
            "coordinator.rpc", "heartbeat.send", "checkpoint.write",
            "checkpoint.fsync", "data.next_batch", "data.prefetch",
            "data.decode", "device.sync", "data.device_decode",
            "serving.admit", "serving.infer", "serving.hotswap",
            "serving.route", "serving.canary",
            "serving.prefill", "serving.decode", "serving.draft",
            "kv.alloc",
        }
        assert {
            "slow", "faults", "serving", "slo", "quant", "plan",
            "generation",
        } <= load_declared_marks(REPO)


# -- CLI ---------------------------------------------------------------


class TestCli:
    def run_cli(self, *args):
        return subprocess.run(
            [sys.executable, "-m", "deeplearning4j_tpu.analysis", *args],
            capture_output=True, text=True, cwd=REPO,
        )

    def test_violations_exit_1_with_json_report(self):
        r = self.run_cli(
            os.path.join(FIXTURES, "eh_violations.py"),
            "--no-baseline", "--format", "json",
        )
        assert r.returncode == 1, r.stderr
        doc = parse_json(r.stdout)
        assert [f.rule for f in doc["findings"]] == [
            "EH401", "EH402", "EH403",
        ]

    def test_package_gate_cli_exits_0(self):
        """Acceptance criterion: `python -m deeplearning4j_tpu.analysis
        deeplearning4j_tpu/` exits 0 with zero non-baselined findings."""
        r = self.run_cli("deeplearning4j_tpu/")
        assert r.returncode == 0, r.stdout + r.stderr
        assert "tpulint: clean" in r.stdout

    def test_clean_file_exit_0(self):
        r = self.run_cli(os.path.join(FIXTURES, "clean.py"))
        assert r.returncode == 0, r.stdout + r.stderr

    def test_list_rules(self):
        r = self.run_cli("--list-rules")
        assert r.returncode == 0
        for rid in RULE_CATALOG:
            assert rid in r.stdout

    def test_write_baseline_surfaces_parse_errors(self, tmp_path):
        bad = tmp_path / "broken.py"
        bad.write_text("def oops(:\n")
        out = tmp_path / "b.toml"
        r = self.run_cli(str(bad), "--write-baseline", str(out))
        assert r.returncode == 1
        assert "error" in r.stderr.lower()
