#!/usr/bin/env python3
"""Find the knee of a serving mix: the highest arrival rate the system
sustains.  Run ONCE when a cell is defined, on the chip:

    python3 benchmarks/tools/knee_sweep.py --workload serve_chat \
        --rates 1 2 3 4 5 --seconds 30 --seed 1 --out chiprun_out/knee.json

One process, one engine, one window per rate (each drained before the next).
A rate is SUSTAINED when no request failed or was refused, the engine's
queue is no deeper over the last third of the window than over the first
(+1), and no more requests are unanswered at the close than there are
slots.  The knee is the highest sustained rate of the sweep, and
`saturated_completed_per_s` is the most the system completed per second in a
window it did not sustain: its capacity.  One short window of a fresh
Poisson draw can fail the rule on a burst alone (4.0 req/s did in PR 22's
long-prompt sweep, well under capacity), so read capacity from windows of
the cell's own length.  The cell's traffic file then fixes its rate — 0.8 x
the knee where tails are judged, 1.25 x the saturated completion rate where
completed tokens per second are — and names the saved records.  The
benchmark itself never searches for a rate.
"""

from __future__ import annotations

import argparse
import copy
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def thirds(depths, seconds: float) -> list:
    out = []
    for i in range(3):
        part = [d for t, d in depths
                if i * seconds / 3 <= t < (i + 1) * seconds / 3]
        out.append(sum(part) / len(part) if part else 0.0)
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    os.environ.setdefault("DL4J_TPU_CACHE_MIN_COMPILE_SECS", "0")

    import jax

    from benchmarks.harness import device as dev
    from benchmarks.harness import serve, spec
    from benchmarks.harness import traffic as tg

    doc = spec.load(ROOT)
    cell = doc.cell(args.workload)
    device = dev.require_tpu(cell.chips)
    t_start = time.perf_counter()
    rig = serve.ServeRig(cell, spec.family(cell.config, doc.home), args.seed)
    rows = []
    try:
        rig.warm()
        setup_s = time.perf_counter() - t_start
        slots = int(cell.traffic["engine"]["slots"])
        for rate in args.rates:
            traffic = copy.deepcopy(cell.traffic)
            traffic["arrivals"]["rate_per_s"] = rate
            requests = tg.serve_requests(traffic, rig.vocab, args.seed,
                                         args.seconds)
            w = rig.window(requests, args.seconds)
            s = serve.summarize(w["streams"], w["t0"], w["close"])
            depth = thirds(w["queue_depths"], args.seconds)
            unanswered = sum(1 for x in w["streams"]
                             if not x.stamps_by(w["close"]))
            row = {
                "rate_per_s": rate, **s,
                "queue_depth_mean_by_third": depth,
                "unanswered_at_close": unanswered,
                "kv_pages_peak": w["pages_peak"],
                "kv_alloc_failures": rig.engine.kv.stats()["alloc_failures"],
                "sustained": bool(s["failed"] == 0
                                  and depth[2] <= depth[0] + 1.0
                                  and unanswered <= slots),
            }
            rows.append(row)
            print(json.dumps(row), flush=True)
    finally:
        rig.close()
    sustained = [r["rate_per_s"] for r in rows if r["sustained"]]
    out = {
        "workload": args.workload, "seed": args.seed,
        "seconds": args.seconds, "device": device,
        "memory_peak_bytes": dev.memory_peak_bytes(jax.devices()[:1]),
        "setup_s": setup_s, "engine": cell.traffic["engine"],
        "knee_rate_per_s": max(sustained) if sustained else None,
        "saturated_completed_per_s": max(
            (r["completed_per_s"] for r in rows if not r["sustained"]),
            default=None),
        "rows": rows,
    }
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps({"knee_rate_per_s": out["knee_rate_per_s"]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
