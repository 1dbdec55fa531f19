"""The `latent_paged_attn` kernel's share of its roofline: the least time the
chip could take for what a decode step's calls of it must read and compute,
over the time they take in the traced slice.

Rows: the program's own device count of the rows the full latent layers'
decode attention read, `dl4jtpu_latent_rows_attended_total{kind="full"}`
(seq_len + 1 per live slot per step and full layer, summed), over
`dl4jtpu_decode_steps_total` and the family's `full_layers` (one call per
full layer per step): the rows of one call, on average.  Bytes and FLOPs of
a call are the family's `latent_paged_attn_bytes` (each row's latent row
once at 2 bytes an element — the counted width, not the lane-padded width
the pool stores — plus every slot's queries and outputs) and
`latent_paged_attn_flops` of those rows; the larger of bytes over the HBM
peak and FLOPs over the bf16 peak is the bound (the bytes).  Kernel time:
the Pallas calls of the slice named `latent_paged_attn`.  A program without
the counter or the kernel reads nothing."""

from benchmarks.harness import trace_reduce as tr
from benchmarks.layer_metrics import engine_thread
from benchmarks.layer_metrics import program_counts as pc

KERNEL = "latent_paged_attn"
ROWS = "dl4jtpu_latent_rows_attended_total"


def kernel_calls(obs):
    """The Pallas events of the slice that are `latent_paged_attn` calls."""
    return [e for e in tr.first_device(obs.trace)
            if e.kind not in tr.CONTAINERS and e.is_pallas
            and KERNEL in e.name]


def read(obs):
    fam = obs.family
    if (obs.trace is None or obs.peaks is None
            or not hasattr(fam, "latent_paged_attn_bytes")):
        return None
    totals = engine_thread.decode_counts()
    rows = pc.series(ROWS)
    calls = kernel_calls(obs)
    seconds = sum(e.dur_ns for e in calls) * 1e-9
    if totals is None or not rows or not calls or not seconds:
        return None
    full = sum(v for k, v in rows.items() if 'kind="full"' in k)
    cfg = obs.cell.config
    per_call = full / totals[engine_thread.STEPS] / fam.full_layers(cfg)
    slots = int(obs.cell.traffic["engine"]["slots"])
    least = max(fam.latent_paged_attn_bytes(cfg, per_call, slots)
                / obs.peaks[1],
                fam.latent_paged_attn_flops(cfg, per_call) / obs.peaks[0])
    return 100.0 * least * len(calls) / seconds
