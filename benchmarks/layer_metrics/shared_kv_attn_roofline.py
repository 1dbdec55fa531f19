"""The `shared_kv_attn` kernel's share of its roofline: the least time the
chip could take for what a decode step's calls of it must read and compute,
over the time they take per step in the traced slice.

Rows: the engine's own count, `dl4jtpu_shared_kv_rows_attended_total` /
`dl4jtpu_decode_steps_total` (process totals; seq_len + 1 per live slot per
step).  Bytes and FLOPs per step are the family's `shared_kv_bytes` and
`shared_kv_flops` of those rows — every reader layer (the full layer and
each cross layer, `shared_kv_readers`) reads each row's keys and values
once, at 2 bytes an element (bf16, the engine's compute type) — and the
larger of bytes over the HBM peak and FLOPs over the bf16 peak is the bound
(the bytes, by far).  Kernel time per step: the `shared_kv_attn` Pallas
calls of the slice (every Pallas call where the trace does not name them),
`shared_kv_readers` calls a step.  A program without the counter or the
kernel reads nothing."""

from benchmarks.harness import trace_reduce as tr
from benchmarks.layer_metrics import engine_thread
from benchmarks.layer_metrics import program_counts as pc

KERNEL = "shared_kv_attn"
ROWS = "dl4jtpu_shared_kv_rows_attended_total"


def kernel_calls(obs):
    """The Pallas events of the slice that are `shared_kv_attn` calls."""
    pallas = [e for e in tr.first_device(obs.trace)
              if e.kind not in tr.CONTAINERS and e.is_pallas]
    named = [e for e in pallas if KERNEL in e.name]
    return named or pallas


def read(obs):
    fam = obs.family
    if (obs.trace is None or obs.peaks is None
            or not hasattr(fam, "shared_kv_bytes")):
        return None
    totals = engine_thread.decode_counts()
    rows = pc.total(ROWS)
    calls = kernel_calls(obs)
    seconds = sum(e.dur_ns for e in calls) * 1e-9
    if totals is None or not rows or not calls or not seconds:
        return None
    cfg = obs.cell.config
    per_step = rows / totals[engine_thread.STEPS]
    least = max(fam.shared_kv_bytes(cfg, per_step) / obs.peaks[1],
                fam.shared_kv_flops(cfg, per_step) / obs.peaks[0])
    steps = len(calls) / fam.shared_kv_readers(cfg)
    return 100.0 * least * steps / seconds
