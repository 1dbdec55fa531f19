"""What the generation engine's own thread says of itself, for the readers
beside this file (it is not a reader: no metric is named after it).

Since PR 25 the phases of the engine's loop are recorder spans, and a
recorder span is a `jax.profiler` annotation: in a traced run they are events
of "/host:CPU" named `generation.<phase>`, on the clock of the device planes.
Each decode step also adds to three process-total counters where it is built
(`dl4jtpu_decode_steps_total`, `..._slot_steps_total`,
`..._rows_attended_total`), bridged to the program's metrics registry by a
pull collector.  A program from before PR 25 has neither: `spans` is then
empty and `decode_counts` None, and the readers return None.
"""

from __future__ import annotations

PREFIX = "generation."
STEPS = "dl4jtpu_decode_steps_total"
SLOT_STEPS = "dl4jtpu_decode_slot_steps_total"
ROWS = "dl4jtpu_decode_rows_attended_total"


def spans(obs, phase: str) -> list:
    """Seconds of every `generation.<phase>` span the traced slice holds
    whole (a span open when the session starts or stops is not recorded)."""
    if obs.trace is None:
        return []
    name = PREFIX + phase
    return [e.dur_ns * 1e-9 for e in obs.trace.host if e.name == name]


def decode_counts():
    """{family: process total} of the three step counters, or None where
    the program does not declare them or no step has run.  Process totals:
    the warm-up's steps are in them (one two-token stream per prefill
    bucket, so at most 5 one-slot steps before a window of thousands)."""
    try:
        from deeplearning4j_tpu.observe.metrics import registry
    except ImportError:
        return None
    reg = registry()
    reg.collect()
    families = {name: reg.get(name) for name in (STEPS, SLOT_STEPS, ROWS)}
    if any(f is None for f in families.values()):
        return None
    totals = {name: f.value() for name, f in families.items()}
    return totals if totals[STEPS] > 0 else None
