"""Share of the prompt rows that a hybrid stack's prefill did NOT run
through its cross-decoder (the full layer's attention and every layer after
it, which only the prompt's last row needs):
`dl4jtpu_prefill_rows_skipped_total{part="cross"}` over
`dl4jtpu_prefill_rows_total` (process totals, the warm-up's prompts in
them).  The engine counts the skipped rows as each prompt's length less the
rows its prefill program reports the cross-decoder ran, so a program that
ran it over every row of a chunk would read near 0 here.  1 - 1 / prompt
length per prompt: 99.9 % and more at prompts of thousands of rows."""

from benchmarks.layer_metrics import program_counts as pc

SKIPPED = "dl4jtpu_prefill_rows_skipped_total"
ROWS = "dl4jtpu_prefill_rows_total"


def read(obs):
    skipped = pc.series(SKIPPED)
    rows = pc.total(ROWS)
    if not skipped or not rows:
        return None
    cross = sum(v for k, v in skipped.items() if 'part="cross"' in k)
    return 100.0 * cross / rows
