"""The program's own process-total counters, for the readers beside this
file (it is not a reader: no metric is named after it).

`series(name)` is {label text: value} of one counter family of the
program's metrics registry after its pull collectors ran, `{"": value}` for
a family without labels — or None where the program does not declare the
family (a program from before the PR that added it), so that a reader
returns None and the line leaves its metric out.  Process totals: the
warm-up's requests are in them (one stream per prefill bucket before a
window of tens), which a share of like over like does not mind.
"""

from __future__ import annotations

DSA_SCORED = "dl4jtpu_dsa_rows_scored_total"
DSA_SELECTED = "dl4jtpu_dsa_rows_selected_total"
MOE_ASSIGNMENTS = "dl4jtpu_moe_assignments_total"
MOE_EXPERT = "dl4jtpu_moe_expert_assignments_total"


def series(name: str):
    try:
        from deeplearning4j_tpu.observe.metrics import registry
    except ImportError:
        return None
    reg = registry()
    reg.collect()
    family = reg.get(name)
    if family is None:
        return None
    snap = family.snapshot()
    return dict(snap["series"]) if "series" in snap else {"": snap["value"]}


def total(name: str):
    got = series(name)
    return None if got is None else float(sum(got.values()))


def held_share():
    """Share of the expert assignments that fell on experts held on this
    chip, or None where nothing was counted."""
    got = series(MOE_ASSIGNMENTS)
    if not got:
        return None
    held = sum(v for k, v in got.items() if 'held="true"' in k)
    every = sum(got.values())
    return held / every if every else None
