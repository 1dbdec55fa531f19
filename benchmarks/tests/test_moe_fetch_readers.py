"""The readers of the grouped expert products against hand-made counters and
traces: `moe_fetched_expert_share` (experts fetched over held experts x
expert layer-steps, nothing on a program without the counters) and
`moe_expert_busy_share` (XLA's `ragged-dot` and the `held_gmm` kernel alike,
no other op)."""

import json
import os

import pytest

from benchmarks.harness import spec
from benchmarks.harness import trace_reduce as tr
from benchmarks.harness.observe import Observations
from benchmarks.layer_metrics import moe_expert_busy_share
from benchmarks.layer_metrics import moe_fetched_expert_share
from benchmarks.layer_metrics import program_counts as pc

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _config(name):
    with open(os.path.join(ROOT, "benchmarks", "configs", name)) as f:
        return json.load(f)


MOTIF = _config("motif-3-beta-ep8-l5.json")
GLM = _config("glm-5.2-ep16-l5.json")
RAGGED = ('%ragged-dot-none.{i} = f32[256,1280]{{1,0}} custom-call('
          'bf16[256,4096]{{1,0}} %p0, bf16[48,4096,1280]{{2,1,0}} %p1, '
          's32[48]{{0}} %p2), custom_call_target="ragged-dot"')
PALLAS = ('%{name}.{i} = f32[256,1280] custom-call(), '
          'custom_call_target="tpu_custom_call", kernel_name="{name}"')


def _obs(events=(), config=MOTIF, cell="motif3_mixed_sat"):
    c = spec.Cell(name=cell, chips=1, config=config, traffic={},
                  end_to_end=(), per_layer=())
    return Observations(cell=c, family=spec.family(config), device={},
                        peaks=(197.0e12, 8.19e11, 16.0e9),
                        trace=tr.build(list(events)) if events else None)


def _counters(monkeypatch, got):
    monkeypatch.setattr(pc, "series", lambda name: got.get(name))


@pytest.mark.parametrize("config,cell,fetched,steps,want", [
    # 100 steps of 4 expert layers, 23.6 of 48 held experts fetched each
    (MOTIF, "motif3_mixed_sat", 9440.0, 400.0, 100.0 * 23.6 / 48),
    # every held expert fetched: what streaming them all reads
    (MOTIF, "motif3_mixed_sat", 48 * 400.0, 400.0, 100.0),
    # GLM holds 16 (its key is n_routed_experts): 4 of them a layer-step
    (GLM, "glm52_longdoc_sat", 4 * 250.0, 250.0, 25.0),
])
def test_fetched_share_against_a_hand_count(monkeypatch, config, cell,
                                            fetched, steps, want):
    _counters(monkeypatch, {moe_fetched_expert_share.FETCHED: {"": fetched},
                            moe_fetched_expert_share.LAYER_STEPS:
                                {"": steps}})
    got = moe_fetched_expert_share.read(_obs(config=config, cell=cell))
    assert got == pytest.approx(want, rel=1e-12)


@pytest.mark.parametrize("got", [
    {},                                            # a parent: no families
    {moe_fetched_expert_share.FETCHED: {"": 0.0},
     moe_fetched_expert_share.LAYER_STEPS: {"": 0.0}},   # no decode step
])
def test_fetched_share_reads_none_without_counts(monkeypatch, got):
    _counters(monkeypatch, got)
    assert moe_fetched_expert_share.read(_obs()) is None


def test_busy_share_reads_ragged_dot_and_held_gmm_alike():
    # a ragged-dot of 3 ms, two held_gmm calls of 1 ms, another kernel of
    # 2 ms and a fusion of 4 ms: busy 11 ms, the grouped products 5 ms
    events = [
        tr.Event("/device:TPU:0", tr.OP_LINE, RAGGED.format(i=1), 0, 3e6),
        tr.Event("/device:TPU:0", tr.OP_LINE,
                 PALLAS.format(name="held_gmm", i=1), 1e7, 1e6),
        tr.Event("/device:TPU:0", tr.OP_LINE,
                 PALLAS.format(name="latent_paged_attn", i=2), 2e7, 2e6),
        tr.Event("/device:TPU:0", tr.OP_LINE,
                 PALLAS.format(name="held_gmm", i=3), 3e7, 1e6),
        tr.Event("/device:TPU:0", tr.OP_LINE,
                 "%fusion.1 = f32[8] fusion()", 4e7, 4e6),
    ]
    assert moe_expert_busy_share.read(_obs(events)) == pytest.approx(
        100.0 * 5 / 11)


def test_busy_share_reads_none_without_grouped_products():
    fusion = tr.Event("/device:TPU:0", tr.OP_LINE,
                      "%fusion.1 = f32[8] fusion()", 0, 4e6)
    assert moe_expert_busy_share.read(_obs([fusion])) is None
    assert moe_expert_busy_share.read(_obs()) is None
