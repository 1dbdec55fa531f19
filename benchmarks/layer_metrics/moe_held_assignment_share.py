"""Share of the expert assignments (rows x experts per token) that fell on
experts held on this chip: `dl4jtpu_moe_assignments_total{held}`, counted on
the device.  With 16 of 256 experts held and even routing it is 6.25 %; it
is the share of the routed experts' work that this chip does, and what the
rest of the deployment would do for these rows."""

from benchmarks.layer_metrics import program_counts as pc


def read(obs):
    share = pc.held_share()
    return None if share is None else 100.0 * share
