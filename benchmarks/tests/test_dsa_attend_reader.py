"""The `dsa_attend_busy_share` reader against hand-made traces: it reads the
`dsa_prefill_attn` calls by name and no other Pallas call, and nothing where
the trace has none."""

import json
import os

import pytest

from benchmarks.harness import spec
from benchmarks.harness import trace_reduce as tr
from benchmarks.harness.observe import Observations
from benchmarks.layer_metrics import dsa_attend_busy_share

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
with open(os.path.join(ROOT, "benchmarks", "configs",
                       "glm-5.2-ep16-l5.json")) as f:
    CFG = json.load(f)
PALLAS = ('%custom-call.{i} = bf16[2048,16384] custom-call(), '
          'custom_call_target="tpu_custom_call", kernel_name="{name}"')


def _obs(calls):
    """calls: (kernel name, start ns, duration ns) of Pallas events, and a
    plain fusion of 4 ms after them."""
    events = [tr.Event("/device:TPU:0", tr.OP_LINE,
                       PALLAS.format(i=i, name=name), start, dur)
              for i, (name, start, dur) in enumerate(calls)]
    events.append(tr.Event("/device:TPU:0", tr.OP_LINE,
                           "%fusion.1 = f32[8] fusion()", 1e9, 4e6))
    cell = spec.Cell(name="glm52_longdoc_sat", chips=1, config=CFG,
                     traffic={}, end_to_end=(), per_layer=())
    return Observations(cell=cell, family=spec.family(CFG), device={},
                        peaks=(197.0e12, 8.19e11, 16.0e9),
                        trace=tr.build(events))


def test_reads_only_the_named_kernel():
    # two dsa_prefill_attn calls of 2 and 1 ms and a 3 ms paged_attn call:
    # busy 10 ms, of which the named kernel 3 ms
    obs = _obs([("dsa_prefill_attn", 0, 2e6), ("paged_attn", 1e7, 3e6),
                ("dsa_prefill_attn", 2e7, 1e6)])
    assert dsa_attend_busy_share.read(obs) == pytest.approx(30.0)


def test_no_named_call_reads_none():
    assert dsa_attend_busy_share.read(
        _obs([("paged_attn", 0, 3e6)])) is None
    assert dsa_attend_busy_share.read(_obs([])) is None
