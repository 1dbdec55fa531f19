"""The `phi4flash` family's counts — parameters, the `shared_kv_attn` kernel's
bytes and operations — against hand counts, and its roofline reader against
a hand-made trace: a kernel that took exactly the least time the chip could
take reads 100 %, never more (a share over 100 % would mean bytes counted too
high or kernel time left out)."""

import json
import os

import pytest

from benchmarks.harness import spec
from benchmarks.harness import trace_reduce as tr
from benchmarks.harness.observe import Observations
from benchmarks.layer_metrics import cross_prefill_skip_share
from benchmarks.layer_metrics import engine_thread
from benchmarks.layer_metrics import program_counts as pc
from benchmarks.layer_metrics import shared_kv_attn_busy_share
from benchmarks.layer_metrics import shared_kv_attn_roofline

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
with open(os.path.join(ROOT, "benchmarks", "configs",
                       "phi-4-mini-flash-reasoning.json")) as f:
    CFG = json.load(f)
FAMILY = spec.family(CFG)
PEAKS = (197.0e12, 8.19e11, 16.0e9)
PALLAS = ('%custom-call.{i} = bf16[32,10,16,128] custom-call(), '
          'custom_call_target="tpu_custom_call", kernel_name="{name}"')


def test_whole_model_is_the_published_size():
    # 9 Mamba (41.2 M), 9 attention (19.7 M), 7 GMU (26.2 M), 7 cross
    # (13.1 M), 32 FFN (78.6 M), the tied embedding (512 M), the norms
    n = FAMILY.param_count(CFG)
    assert n == pytest.approx(3.85e9, rel=0.01)
    d, e = 2560, 5120
    mamba = d * 2 * e + 4 * e + e + e * 192 + 160 * e + e + e * 16 + e + e * d
    attn = d * 2560 + 2 * d * 1280 + 2560 * d + 4 * 64 + 128
    cross = d * 2560 + 2560 * d + 4 * 64 + 128
    gmu = 2 * d * e
    ffn = 3 * d * 10240
    norms = 32 * 4 * d + 2 * d
    assert n == (9 * mamba + 9 * attn + 7 * gmu + 7 * cross + 32 * ffn
                 + 200064 * d + norms)


def test_shared_kv_counts_against_a_hand_count():
    # the full layer and the seven cross layers read each row once: 20 KV
    # heads x 64 for K and for V, 2 bytes each
    assert FAMILY.shared_kv_readers(CFG) == 8
    assert FAMILY.shared_kv_bytes(CFG, 1000) == 1000 * 8 * 2 * 20 * 64 * 2
    assert FAMILY.shared_kv_bytes(CFG, 10, elem_bytes=4) == 10 * 8 * 5120 * 2
    # per row and reader: 40 query halves, a 64-wide score and a 128-wide
    # weighted value row, a multiply and an add each
    assert FAMILY.shared_kv_flops(CFG, 1000) == 2 * 1000 * 8 * 40 * (64 + 128)


def _obs(call_ns, calls, busy_ns=None, name="shared_kv_attn"):
    events = [tr.Event("/device:TPU:0", tr.OP_LINE,
                       PALLAS.format(i=i, name=name), i * 1e6, call_ns)
              for i in range(calls)]
    if busy_ns:
        events.append(tr.Event("/device:TPU:0", tr.OP_LINE,
                               "%fusion.1 = f32[8] fusion()", 1e9, busy_ns))
    cell = spec.Cell(name="phi4flash_reason_sat", chips=1, config=CFG,
                     traffic={}, end_to_end=(), per_layer=())
    return Observations(cell=cell, family=FAMILY, device={}, peaks=PEAKS,
                        trace=tr.build(events))


@pytest.fixture
def counted(monkeypatch):
    """Process totals as a run would leave them: 100 steps that attended
    144,000 shared rows (32 slots at a mean context of 4,500)."""
    monkeypatch.setattr(engine_thread, "decode_counts", lambda: {
        engine_thread.STEPS: 100.0, engine_thread.SLOT_STEPS: 3200.0,
        engine_thread.ROWS: 144000.0})
    monkeypatch.setattr(pc, "total", lambda name: {
        shared_kv_attn_roofline.ROWS: 14.4e6}.get(name))


def test_roofline_reads_100_percent_at_the_least_time_and_never_more(
        counted):
    # 144,000 rows a step x 8 readers x 5,120 B = 5.90 GB: 7.20 ms at 819 GB/s
    least = 144000 * 8 * 5120 / 8.19e11
    per_call_ns = least / 8 * 1e9
    exact = shared_kv_attn_roofline.read(_obs(per_call_ns, 16))   # 2 steps
    assert exact == pytest.approx(100.0, rel=1e-9)
    slower = shared_kv_attn_roofline.read(_obs(2 * per_call_ns, 16))
    assert slower == pytest.approx(50.0, rel=1e-9)


def test_roofline_finds_the_kernel_by_name_among_other_kernels(counted):
    least_ns = 144000 * 8 * 5120 / 8.19e11 / 8 * 1e9
    obs = _obs(least_ns, 8)
    other = [tr.Event("/device:TPU:0", tr.OP_LINE,
                      PALLAS.format(i=100 + i, name="paged_attn"),
                      5e9 + i * 1e6, 10 * least_ns) for i in range(8)]
    obs.trace = tr.build(tr.first_device(obs.trace) + other)
    assert shared_kv_attn_roofline.read(obs) == pytest.approx(100.0)


def test_busy_share_and_skip_share(counted, monkeypatch):
    obs = _obs(1e6, 4, busy_ns=6e6)
    assert shared_kv_attn_busy_share.read(obs) == pytest.approx(40.0)
    monkeypatch.setattr(pc, "series", lambda name: {
        cross_prefill_skip_share.SKIPPED: {'{part="cross"}': 4095.0 + 2047}
    }.get(name))
    monkeypatch.setattr(pc, "total", lambda name: {
        cross_prefill_skip_share.ROWS: 4096.0 + 2048}.get(name))
    assert cross_prefill_skip_share.read(obs) == pytest.approx(
        100.0 * 6142 / 6144)


def test_readers_find_nothing_in_a_program_without_the_counters(monkeypatch):
    monkeypatch.setattr(pc, "series", lambda name: None)
    monkeypatch.setattr(pc, "total", lambda name: None)
    obs = _obs(1e6, 8)
    assert shared_kv_attn_roofline.read(obs) is None
    assert cross_prefill_skip_share.read(obs) is None
