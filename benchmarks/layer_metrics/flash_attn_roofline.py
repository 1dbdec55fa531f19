"""The flash-attention kernels' share of their roofline over the traced
steps: the least time the chip could take for the attention those steps
require (the larger of FLOPs / bf16 peak and bytes / HBM peak, from the
family's analytic counts for the cell's shapes, forward + backward) over the
summed device time of the kernels.  At T 2048 and head size 128 the compute
bound is the larger by far.

The kernels carry no name yet: they are the Pallas custom calls of the train
step (three per layer per step: forward, dQ, dK+dV), so the number of steps
traced is their count over 3 x layers — partial steps at the slice's edges
count for the kernels they hold."""

from benchmarks.harness import trace_reduce as tr


def read(obs):
    if obs.trace is None or obs.peaks is None:
        return None
    pallas = lambda e: e.is_pallas
    seconds = tr.op_seconds(obs.trace, pallas)
    calls = tr.op_count(obs.trace, pallas)
    if not seconds:
        return None
    c, cfg, fam = obs.counters, obs.cell.config, obs.family
    steps = calls / (3.0 * int(cfg["n_layer"]))
    per_chip_batch = c["batch"] // c["chips"]
    flops = fam.flash_train_flops(cfg, per_chip_batch, c["seq_len"])
    nbytes = fam.flash_train_bytes(cfg, per_chip_batch, c["seq_len"])
    least = max(flops / obs.peaks[0], nbytes / obs.peaks[1])
    return 100.0 * steps * least / seconds
