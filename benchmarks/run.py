#!/usr/bin/env python3
"""Run one cell of the benchmark once, in this process, on this machine's TPU.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout.  The cell is an entry of `workloads` in
BENCHMARK.json; its configuration, traffic mix and per-layer metrics are files
found by name (benchmarks/harness/spec.py).  The run checks the device (no TPU,
or fewer chips than the cell asks for: a non-zero exit and no result), builds
the weights on the device from --seed, warms the cell's shapes (set-up: from
the process's start to the window's, less the seconds the TPU runtime took to
start), measures for --seconds, checks the outputs against the configuration's plain reference outside the window, and
prints ONE JSON object as its last line: `correct`, `attempted`, `failed`,
`metrics` (the cell's end-to-end metrics, or with --trace 1 its per-layer
metrics), `device`, and with --trace 1 `breakdown`.  Sample counts and the
checks' readings are on the `[bench]` lines before it.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()       # the process's start: set-up counts from here

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from typing import Any  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


@dataclasses.dataclass
class RunContext:
    cell: Any
    family: Any
    seed: int
    seconds: float
    trace: bool
    trace_dir: str
    t_start: float        # the process started
    runtime_start_s: float    # seconds inside the first `jax.devices()`
    device: dict
    peaks: Any
    marks: dict = dataclasses.field(default_factory=dict)

    def mark(self, name: str) -> None:
        """Seconds since the process started, at a point of set-up."""
        self.marks[name] = round(time.perf_counter() - self.t_start, 3)

    def setup_s(self) -> float:
        """`setup_s`, read as the window opens: everything since the
        process started — interpreter, imports, weights, engine, warm-up,
        compilation — except the seconds inside the first `jax.devices()`,
        the TPU runtime's own start.  Process start to device ready took
        7.7-15.5 s in PR 22's chip runs, rising from run to run (jax warns
        that the runtime starts slowly without transparent hugepages); where
        the call itself was timed it was 9.3-9.5 s of 12.0-12.4 s.  That
        moved the median set-up of `train_2k` by 21 % from one set of six
        runs to the next with no code changed, and it is neither the
        program's work nor the benchmark's.  It is on the info line as
        `tpu_runtime_start`."""
        return time.perf_counter() - self.t_start - self.runtime_start_s


def say(label: str, doc: dict) -> None:
    print(f"[bench] {label}: {json.dumps(doc, default=str)}", flush=True)


def run_cell(root: str, workload: str, *, seed: int, seconds: float,
             trace: bool, t_start: float, require_chip: bool = True):
    """Run the cell and return (correct, attempted, failed, observations,
    info).  `require_chip=False` is for the CPU rehearsal in the tests,
    which checks `correct` and reports no metric."""
    from benchmarks.harness import device as dev
    from benchmarks.harness import serve, spec, trace_reduce, train

    doc = spec.load(root)
    cell = doc.cell(workload)
    import jax

    t_devices = time.perf_counter()
    if require_chip:
        device = dev.require_tpu(cell.chips)
        peaks = dev.peaks(device["kind"])
    else:
        device, peaks = dev.describe(jax.devices()), None
    runtime_start_s = time.perf_counter() - t_devices
    trace_dir = os.path.join(root, ".bench_out", "trace", workload)
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        os.makedirs(trace_dir)
    ctx = RunContext(
        cell=cell, family=spec.family(cell.config, doc.home), seed=seed,
        seconds=seconds, trace=trace, trace_dir=trace_dir, t_start=t_start,
        runtime_start_s=runtime_start_s, device=device, peaks=peaks)
    ctx.mark("device_ready")
    ctx.marks["tpu_runtime_start"] = round(runtime_start_s, 3)
    runner = {"train": train.run, "serve": serve.run}[cell.traffic["kind"]]
    correct, attempted, failed, obs, info = runner(ctx)
    info["setup_marks_s"] = ctx.marks
    if trace:
        obs.trace = trace_reduce.build(trace_reduce.events_from_xplane(
            trace_reduce.find_xplane(trace_dir)))
    return doc, correct, attempted, failed, obs, info


def result(doc, obs, *, correct, attempted, failed, trace: bool) -> dict:
    """The object of the last line."""
    from benchmarks.harness import trace_reduce

    cell = obs.cell
    metrics = {}
    if trace:
        for m in cell.per_layer:
            value = doc.reader(m["name"])(obs)
            if value is not None:
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
    else:
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": float(obs.e2e[m["name"]]),
                                  "unit": m["unit"]}
    device = {**obs.device,
              "memory_peak_bytes": int(obs.counters["memory_peak_bytes"])}
    out = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed), "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = trace_reduce.busy_seconds(obs.trace)
        device["window_s"] = trace_reduce.window_seconds(
            obs.trace, obs.trace_wall_s)
        out["breakdown"] = {
            "device_ops": trace_reduce.top_ops(obs.trace, 10),
            "idle_gaps": trace_reduce.idle_gaps(obs.trace, 10),
        }
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    # the program persists only programs that took over a second to
    # compile; every run is a new process, so everything it compiles is
    # worth keeping (its own knob, set in this process's environment only)
    os.environ.setdefault("DL4J_TPU_CACHE_MIN_COMPILE_SECS", "0")
    # crash artifacts of the program stay out of the tree's top level
    os.environ.setdefault("DL4JTPU_CRASH_DIR",
                          os.path.join(ROOT, ".bench_out", "crash"))

    doc, correct, attempted, failed, obs, info = run_cell(
        ROOT, args.workload, seed=args.seed, seconds=args.seconds,
        trace=bool(args.trace), t_start=T_START)
    say("info", info)
    say("end_to_end", obs.e2e)
    say("counters", obs.counters)
    print(json.dumps(result(doc, obs, correct=correct, attempted=attempted,
                            failed=failed, trace=bool(args.trace))),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
