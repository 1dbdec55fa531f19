"""How unevenly the held experts were loaded: over the series of
`dl4jtpu_moe_expert_assignments_total{layer, expert}`, the busiest expert's
assignments over the mean of its layer's, the worst layer taken.  1.0 is
even routing; the grouped product's time follows the total, its tail the
largest group."""

import re

from benchmarks.layer_metrics import program_counts as pc


def read(obs):
    got = pc.series(pc.MOE_EXPERT)
    if not got:
        return None
    by_layer: dict = {}
    for labels, v in got.items():
        m = re.search(r'layer="([^"]*)"', labels)
        by_layer.setdefault(m.group(1) if m else "", []).append(v)
    held = int(obs.cell.config["n_routed_experts"])
    worst = 0.0
    for loads in by_layer.values():
        mean = sum(loads) / held          # an expert never chosen has no series
        if mean:
            worst = max(worst, max(loads) / mean)
    return worst or None
