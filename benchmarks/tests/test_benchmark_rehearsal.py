"""CPU rehearsal of the benchmark: all four cells at a tiny size, through the
same code the chip runs (4 virtual devices for the data-parallel cell).

The tiny cells live in a throw-away copy of the benchmark and are ADDED to it
as new files and new BENCHMARK.json entries only — a configuration, four
traffic mixes, four cells and a per-layer metric with its reader — which is
the proof that a later PR can add a cell without editing a file that is
there.  A rehearsal checks `correct` and the shape of the result; it reports
no metric: a number from a CPU run is not a device number.
"""

import hashlib
import json
import os
import shutil
import subprocess
import sys
import time

import pytest

from benchmarks import run as bench_run

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

TINY_ENGINE = {"page_size": 8, "num_pages": 64, "max_pages_per_seq": 8,
               "prefill_quantum": 16, "kv_dtype": "f32"}
TINY_TRAFFIC = {
    "tiny_train1": {
        "kind": "train", "unit": "tokens", "seq_len": 64, "batch_per_chip": 2,
        "parallel": {"data": 1}, "learning_rate": 1e-3, "warmup_steps": 2,
        "trace_steps": 2},
    "tiny_train4": {
        "kind": "train", "unit": "tokens", "seq_len": 64, "batch_per_chip": 2,
        "parallel": {"data": 4}, "learning_rate": 1e-3, "warmup_steps": 2,
        "trace_steps": 2},
    "tiny_chat": {
        "kind": "serve", "arrivals": {"rate_per_s": 10.0},
        "schedule_seed": 5, "classes": [{
            "prompt_len": {"dist": "lognormal", "median": 16, "sigma": 0.6,
                           "min": 4, "max": 40},
            "output_len": {"dist": "uniform", "min": 4, "max": 8}}],
        "engine": {**TINY_ENGINE, "slots": 4, "max_queue": 64},
        "trace_seconds": 0.3},
    "tiny_sat": {
        "kind": "serve", "arrivals": {"rate_per_s": 400.0},
        "schedule_seed": 5, "classes": [{
            "prompt_len": {"dist": "uniform", "min": 33, "max": 48},
            "output_len": {"dist": "uniform", "min": 2, "max": 4}}],
        "engine": {**TINY_ENGINE, "slots": 2, "max_queue": 1024},
        "trace_seconds": 0.3},
}
# tiny cell -> (chips, the real cell whose metrics it reports)
TINY_CELLS = {
    "tiny_train1": (1, "train_2k"),
    "tiny_train4": (4, "train_2k_dp4"),
    "tiny_chat": (1, "serve_chat"),
    "tiny_sat": (1, "serve_longprompt_sat"),
}
EXTRA_READER = '''"""A throw-away per-layer metric: tokens the window's streams emitted."""


def read(obs):
    return sum(len(s.stamps) for s in obs.streams)
'''


def _digests(top: str) -> dict:
    out = {}
    for folder, _, files in os.walk(top):
        for name in files:
            path = os.path.join(folder, name)
            with open(path, "rb") as f:
                out[os.path.relpath(path, top)] = hashlib.sha256(
                    f.read()).hexdigest()
    return out


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("bench"))
    home = os.path.join(root, "benchmarks")
    shutil.copytree(os.path.join(ROOT, "benchmarks"), home,
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = _digests(home)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        doc = json.load(f)
    original = json.loads(json.dumps(doc))

    with open(os.path.join(home, "configs", "cerebras-gpt-1.3b.json")) as f:
        cfg = json.load(f)
    cfg.update(n_embd=64, n_layer=2, n_head=4, n_inner=256, n_positions=128,
               vocab_size=300, system={"vocab_chunk": 128},
               reduced=["everything: a test preset, never a cell"])
    with open(os.path.join(home, "configs", "tiny.json"), "w") as f:
        json.dump(cfg, f)
    for name, traffic in TINY_TRAFFIC.items():
        with open(os.path.join(home, "traffic", name + ".json"), "w") as f:
            json.dump(traffic, f)
    with open(os.path.join(home, "layer_metrics", "tiny_extra.py"), "w") as f:
        f.write(EXTRA_READER)

    doc["configs"].append({
        "name": "tiny", "source": "none", "reduced": cfg["reduced"],
        "file": "benchmarks/configs/tiny.json", "why": "test preset"})
    for name, (chips, like) in TINY_CELLS.items():
        doc["workloads"].append({"name": name, "config": "tiny",
                                 "traffic": name, "chips": chips,
                                 "why": "test"})
        for m in doc["end_to_end"] + doc["per_layer"]:
            if like in m.get("workloads", ()):
                m["workloads"].append(name)
    doc["per_layer"].append({
        "name": "tiny_extra.chat", "unit": "tokens", "better": "higher",
        "source": "program_counter", "layer": "serve_scheduler",
        "moves": "itl_p90_ms", "workloads": ["tiny_chat"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(doc, f)

    # added, never edited: every file that was there is byte-identical, and
    # every entry that was there is still there, with at most new cell names
    # in its `workloads`
    after = _digests(home)
    assert {k: after[k] for k in before} == before
    assert set(after) - set(before) == {
        "configs/tiny.json", "layer_metrics/tiny_extra.py",
        *(f"traffic/{n}.json" for n in TINY_TRAFFIC)}
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        for old, new in zip(original[key], doc[key]):
            strip = lambda e: {k: v for k, v in e.items() if k != "workloads"}
            assert strip(old) == strip(new)
            assert new.get("workloads", [])[:len(old.get("workloads", []))] \
                == old.get("workloads", [])
    return root


def _rehearse(root, cell, *, trace):
    t0 = time.perf_counter()
    doc, correct, attempted, failed, obs, info = bench_run.run_cell(
        root, cell, seed=11, seconds=1.2, trace=trace, t_start=t0,
        require_chip=False)
    out = bench_run.result(doc, obs, correct=correct, attempted=attempted,
                           failed=failed, trace=trace)
    json.dumps(out)                      # the last line must serialise
    return obs, info, out


@pytest.mark.parametrize("cell", sorted(TINY_CELLS))
def test_cell_rehearses_correct_on_cpu(tiny_root, cell):
    obs, info, out = _rehearse(tiny_root, cell, trace=False)
    assert out["correct"] is True, info
    assert out["attempted"] > 0 and out["failed"] == 0
    assert obs.counters["compiles_in_window"] == 0
    assert set(out) == {"correct", "attempted", "failed", "metrics", "device"}
    assert set(out["metrics"]) == {m["name"] for m in obs.cell.end_to_end}
    assert "setup_s" in out["metrics"] and len(out["metrics"]) >= 2
    assert all(v["value"] > 0 for v in out["metrics"].values())
    assert out["device"]["platform"] == "cpu"
    if cell == "tiny_train4":
        assert obs.counters["chips"] == 4
        assert info["rel_first"] < 1e-5 and info["rel_after"] < 1e-5
    if cell == "tiny_sat":               # above capacity: the queue is cut
        assert info["cut_at_close"] > 0 and info["queue_depth_end"] > 0
    if cell == "tiny_chat":
        assert info["check"]["checked_streams"] == 3
        assert info["check"]["kv_pages_held"] == 0


@pytest.mark.parametrize("cell", ["tiny_train1", "tiny_chat"])
def test_traced_run_reports_the_cells_per_layer_metrics(tiny_root, cell):
    obs, info, out = _rehearse(tiny_root, cell, trace=True)
    assert out["correct"] is True, info
    wanted = {m["name"] for m in obs.cell.per_layer}
    # the CPU backend has no device plane and no memory counter: readers
    # that find nothing to read leave their metric out
    assert set(out["metrics"]) <= wanted
    assert f"compiles_in_window.{cell == 'tiny_chat' and 'chat' or 'train'}" \
        in out["metrics"]
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
    assert out["device"]["window_s"] > 0 and "busy_s" in out["device"]
    if cell == "tiny_chat":
        # the metric this test added, found by the part of its name before
        # the dot, in a file that was not there
        assert out["metrics"]["tiny_extra.chat"]["value"] > 0
        assert out["metrics"]["decode_step_ms_p50"]["value"] > 0
        assert "kv_alloc_failures.chat" in out["metrics"]


def test_the_command_has_no_cpu_mode():
    """Without a TPU: another exit code than 0, and no result line."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", "train_2k", "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert not [ln for ln in proc.stdout.splitlines()
                if ln.lstrip().startswith("{")]
    assert "TPU" in proc.stderr
