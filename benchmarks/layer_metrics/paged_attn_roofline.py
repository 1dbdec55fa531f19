"""The paged-attention kernel's share of its roofline: the least time the
chip could take for the KV rows a decode step attends, over the time the
kernel takes per step in the traced slice.

Rows: the engine's own count, `dl4jtpu_decode_rows_attended_total` /
`dl4jtpu_decode_steps_total` (process totals; the row a step writes counts).
Each row is read once per layer as K and as V: rows x n_layer x 2 x heads x
head size x B bytes over the HBM peak, against rows x n_layer x heads x 4 x
head size FLOPs (q.k and p.v, a multiply and an add each) over the bf16 peak;
the larger is the bound, the bytes by far at one query row per slot.  B is 1
where the traffic file's `engine.kv_dtype` is `int8`, else 2 — bf16, the
engine's compute type — whatever the pool stores: the same work for every
pool, so a float32 pool tops out at 50 % and nothing reads over 100 %.

Kernel time per step: the Pallas custom calls of the slice are the decode
step's paged kernel (prefill under 2048 tokens takes the dense path), one
call per layer per step, so the steps traced are calls / n_layer."""

from benchmarks.harness import trace_reduce as tr
from benchmarks.layer_metrics import engine_thread


def read(obs):
    if obs.trace is None or obs.peaks is None:
        return None
    totals = engine_thread.decode_counts()
    pallas = lambda e: e.is_pallas
    seconds = tr.op_seconds(obs.trace, pallas)
    calls = tr.op_count(obs.trace, pallas)
    if totals is None or not seconds or not calls:
        return None
    cfg = obs.cell.config
    layers, heads = int(cfg["n_layer"]), int(cfg["n_head"])
    head_size = int(cfg["n_embd"]) // heads
    engine = obs.cell.traffic.get("engine", {})
    elem_bytes = 1 if engine.get("kv_dtype") == "int8" else 2
    rows = totals[engine_thread.ROWS] / totals[engine_thread.STEPS]
    per_row = layers * heads * head_size
    least = max(rows * per_row * 2 * elem_bytes / obs.peaks[1],
                rows * per_row * 4 / obs.peaks[0])
    return 100.0 * least * (calls / layers) / seconds
