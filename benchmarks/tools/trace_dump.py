#!/usr/bin/env python3
"""Look at a profiler trace by hand before trusting code that reduces it.

    python3 benchmarks/tools/trace_dump.py <log_dir or .xplane.pb> <out.json> \
        [--events N]

Writes, for every plane and line of the trace: its name, how many events it
holds, the first few events with ALL their stats, and the names that took
most time — so one can see which planes are devices, which lines hold the
executed ops, and how the kernels are named today.  With --events N it also
writes `<out>.events.json`: the first N device-op events of the first
device and the async spans, programs and host events beside them, in the form
`trace_reduce.events_from_json` reads (the recorded traces the tests check the
reduction against were cut from such files).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import warnings

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("trace")
    ap.add_argument("out")
    ap.add_argument("--events", type=int, default=0)
    args = ap.parse_args()
    from jax.profiler import ProfileData

    from benchmarks.harness import trace_reduce as tr

    path = args.trace if args.trace.endswith(".pb") else tr.find_xplane(
        args.trace)
    warnings.simplefilter("ignore", DeprecationWarning)
    planes = []
    for plane in ProfileData.from_file(path).planes:
        lines = []
        for line in plane.lines:
            events = list(line.events)
            total: dict = {}
            for e in events:
                total[e.name] = total.get(e.name, 0.0) + e.duration_ns
            lines.append({
                "line": line.name, "events": len(events),
                "first": [{"name": e.name, "start_ns": e.start_ns,
                           "dur_ns": e.duration_ns,
                           "stats": {k: str(v)[:300] for k, v in e.stats}}
                          for e in events[:4]],
                "top_by_time": sorted(((k, v * 1e-9) for k, v in
                                       total.items()),
                                      key=lambda kv: -kv[1])[:25],
            })
        planes.append({"plane": plane.name, "lines": lines})
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump({"xplane": path,
                   "bytes": os.path.getsize(path), "planes": planes}, f,
                  indent=1)
    if args.events:
        events = tr.events_from_xplane(path)
        trace = tr.build(events)
        ops = tr.first_device(trace)[: args.events]
        if ops:
            lo, hi = ops[0].start_ns, ops[-1].end_ns
            beside = (tr.first_device(trace, "async_ops")
                      + tr.first_device(trace, "modules") + trace.host)
            keep = ops + [e for e in beside
                          if e.end_ns >= lo and e.start_ns <= hi]
            tr.events_to_json(keep, args.out + ".events.json")
    return 0


if __name__ == "__main__":
    sys.exit(main())
