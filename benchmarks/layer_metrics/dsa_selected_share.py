"""Rows the sparse-attention indexers selected, as a share of the rows they
scored: `dl4jtpu_dsa_rows_selected_total` / `dl4jtpu_dsa_rows_scored_total`,
the engine's own counts from the lengths (per query row per indexer layer:
its whole prefix scored, the top `index_topk` of it kept), prefill and
decode.  100 % while every context is shorter than `index_topk`; the lower,
the more of the cache the attention leaves unread."""

from benchmarks.layer_metrics import program_counts as pc


def read(obs):
    scored, selected = pc.total(pc.DSA_SCORED), pc.total(pc.DSA_SELECTED)
    if not scored or selected is None:
        return None
    return 100.0 * selected / scored
