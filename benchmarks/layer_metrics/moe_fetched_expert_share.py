"""Share of the held experts whose weights a decode step's grouped expert
products fetched: `dl4jtpu_moe_experts_fetched_total` (the held experts
with at least one row, summed over the expert layers of every decode step,
counted on the device) over the held experts times
`dl4jtpu_moe_expert_layer_steps_total`.  A product that streams every held
expert reads 100 %; one that fetches only the experts with rows reads what
the routing gives them.  None where the program does not count it."""

from benchmarks.layer_metrics import program_counts as pc

FETCHED = "dl4jtpu_moe_experts_fetched_total"
LAYER_STEPS = "dl4jtpu_moe_expert_layer_steps_total"


def held_experts(config: dict):
    """The experts one chip holds, under either family's key."""
    for key in ("num_experts", "n_routed_experts"):
        if key in config:
            return int(config[key])
    return None


def read(obs):
    fetched, steps = pc.total(FETCHED), pc.total(LAYER_STEPS)
    held = held_experts(obs.cell.config)
    if fetched is None or not steps or not held:
        return None
    return 100.0 * fetched / (held * steps)
