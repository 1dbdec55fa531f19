#!/usr/bin/env python3
"""Spread of each end-to-end metric over repeated runs, as the driver reads
it: the distance between the quartiles over the median.

    python3 benchmarks/tools/spread.py <dir> [<dir> ...]

Each directory is one SET of runs of the same code: files `<cell>_s<seed>.log`
holding the standard output of `benchmarks/run.py --trace 0`.  A bound is set
to about five times the widest spread over the cells and sets, and never under
1 %; the driver refuses one under twice or over eight times what it reads.
"""

from __future__ import annotations

import collections
import glob
import json
import os
import re
import statistics
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmarks.harness.stats import spread  # noqa: E402


def main() -> int:
    for folder in sys.argv[1:]:
        values = collections.defaultdict(lambda: collections.defaultdict(list))
        for path in sorted(glob.glob(os.path.join(folder, "*_s[0-9]*.log"))):
            cell = re.sub(r"_s\d+\.log$", "", os.path.basename(path))
            with open(path) as f:
                last = [ln for ln in f if ln.startswith("{")]
            if not last:
                print(f"no result line in {path}")
                continue
            doc = json.loads(last[-1])
            if not doc["correct"] or doc["failed"]:
                print(f"{path}: correct={doc['correct']} "
                      f"failed={doc['failed']}")
            for name, m in doc["metrics"].items():
                values[cell][name].append(m["value"])
        for cell, metrics in values.items():
            for name, vs in metrics.items():
                if len(vs) >= 2:
                    print(f"{os.path.basename(folder):10s} {cell:22s} "
                          f"{name:20s} n={len(vs)} "
                          f"median={statistics.median(vs):12.3f} "
                          f"spread={100 * spread(vs):6.2f}%  "
                          f"min={min(vs):.3f} max={max(vs):.3f}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
