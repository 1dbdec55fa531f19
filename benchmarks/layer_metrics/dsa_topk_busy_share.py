"""Share of the device's busy time spent in the sparse attention's
SELECTION: the operations that turn indexer scores into each row's exact
top `index_topk`.  An op event's name is its HLO text; where that text
carries the program's scope (`dsa_topk`, from `jax.named_scope`) the ops are
found by it.  Where it does not, they are found by opcode and shape: `sort`
and top-k custom calls whose rows are not the vocabulary's (the sampler's
sort is `[slots, vocab]`) — the decode step's selection; the prefill's
counting passes are plain fusions and are then missed, so the number reads
low and says so by being under the scoped one."""

import re

from benchmarks.harness import trace_reduce as tr

SCOPE = "dsa_topk"


def read(obs):
    if obs.trace is None:
        return None
    busy = tr.busy_seconds(obs.trace)
    ops = [e for e in tr.first_device(obs.trace)
           if e.kind not in tr.CONTAINERS]
    if not busy or not ops:
        return None
    scoped = [e for e in ops if SCOPE in e.name]
    if not scoped:
        vocab = str(int(obs.cell.config["vocab_size"]))

        def selection(e):
            if e.kind != "sort" and "topk" not in e.name.lower():
                return False
            shape = re.search(r"\[([\d,]*)\]", tr.parse_op(e.name)[1])
            return bool(shape) and shape.group(1).split(",")[-1] != vocab

        scoped = [e for e in ops if selection(e)]
    if not scoped:
        return None
    inside = sum(e - s for s, e in tr.merged_intervals(scoped)) * 1e-9
    return 100.0 * inside / busy
