"""Mean number of live slots per decode step:
`dl4jtpu_decode_slot_steps_total` / `dl4jtpu_decode_steps_total`, the
engine's own counts made where each step is built.  Process totals, so the
warm-up's steps are in them: one two-token stream per prefill bucket, at most
5 one-slot steps against the thousands of a window.

Above the knee (`.sat`) higher is better: the slots are the capacity.  Below
it (`.chat`) it follows step time x arrival rate x answer length — a faster
step holds fewer streams at once — so there lower is better."""

from benchmarks.layer_metrics import engine_thread


def read(obs):
    totals = engine_thread.decode_counts()
    if totals is None:
        return None
    return totals[engine_thread.SLOT_STEPS] / totals[engine_thread.STEPS]
