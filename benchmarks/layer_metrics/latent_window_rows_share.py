"""Share of the latent rows a decode step's layers attended that the window
layers read: `dl4jtpu_latent_rows_attended_total{kind="window"}` over all
kinds (counted on the device by the step programs; process totals).  A
window layer reads its ring's min(seq_len + 1, window) rows, a full layer
the whole context, so at contexts of thousands the share sits far under
the window layers' share of the layers; a program whose window layers read
their whole context would read near it."""

from benchmarks.layer_metrics import program_counts as pc

ROWS = "dl4jtpu_latent_rows_attended_total"


def read(obs):
    rows = pc.series(ROWS)
    every = sum(rows.values()) if rows else 0
    if not every:
        return None
    window = sum(v for k, v in rows.items() if 'kind="window"' in k)
    return 100.0 * window / every
