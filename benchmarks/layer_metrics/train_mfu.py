"""Model FLOP/s utilization: the rate `fit()` achieved times the FLOPs the
forward and backward passes require per unit (the family's analytic count;
recomputation not counted), over the benchmark's own bf16 peak per chip."""


def read(obs):
    rate = obs.e2e.get("train_rate_per_chip")
    if rate is None or obs.peaks is None or obs.counters.get("unit") != "tokens":
        return None
    flops = obs.family.train_flops_per_token(obs.cell.config,
                                             obs.counters["seq_len"])
    return 100.0 * rate * flops / obs.peaks[0]
