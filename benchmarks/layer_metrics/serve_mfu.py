"""Share of the chip's bf16 peak that the serving path turned into REQUIRED
model operations: the family's `request_flops` of every request completed in
the window — the active matrices at the counted share of held expert
assignments, attention over min(t + 1, index_topk) rows and the indexers
over t + 1, the head once per generated token — over the span `serve_tok_s`
divides by (window start to the last completion), over the benchmark's own
peak.  It is the share of the whole path, prefill and decode and every gap
between them, and bounds what a later change to one part can claim here.
Host clock and counts; it cannot pass 100 %: nothing recomputed, padded or
speculated is counted."""

from benchmarks.layer_metrics import program_counts as pc


def read(obs):
    flops_of = getattr(obs.family, "request_flops", None)
    share = pc.held_share()
    rate = obs.e2e.get("serve_tok_s")
    if flops_of is None or share is None or obs.peaks is None or not rate:
        return None
    done = [s for s in obs.streams
            if s.completed_by(obs.close) and not s.failed]
    if not done:
        return None
    # the span `serve_tok_s` divided the same requests' tokens by
    span = sum(s.prompt_len + s.max_new for s in done) / rate
    flops = sum(flops_of(obs.cell.config, s.prompt_len, s.max_new, share)
                for s in done)
    return 100.0 * flops / span / obs.peaks[0]
