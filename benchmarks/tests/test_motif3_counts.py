"""The `motif3` family's counts — parameters, the `latent_paged_attn`
kernel's bytes and operations, one request's required FLOPs — against hand
counts, and its readers against hand-made traces and counters: a kernel that
took exactly the least time the chip could take reads 100 %, never more (a
share over 100 % would mean bytes counted too high or kernel time left
out)."""

import json
import os

import numpy as np
import pytest

from benchmarks.harness import spec
from benchmarks.harness import trace_reduce as tr
from benchmarks.harness.observe import Observations
from benchmarks.layer_metrics import engine_thread
from benchmarks.layer_metrics import latent_paged_attn_busy_share
from benchmarks.layer_metrics import latent_paged_attn_roofline
from benchmarks.layer_metrics import latent_window_rows_share
from benchmarks.layer_metrics import program_counts as pc

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
with open(os.path.join(ROOT, "benchmarks", "configs",
                       "motif-3-beta-ep8-l5.json")) as f:
    CFG = json.load(f)
FAMILY = spec.family(CFG)
PEAKS = (197.0e12, 8.19e11, 16.0e9)
PALLAS = ('%custom-call.{i} = f32[32,80,512] custom-call(), '
          'custom_call_target="tpu_custom_call", kernel_name="{name}"')
D = 4096


def test_the_cut_counts_its_stated_parameters():
    # per layer: attention 91.8 M (W_qa, W_qb, W_kva, W_kvb, W_lam, W_gate,
    # W_o and three norms), mixing 2 x (16,384 x 24 + 16,384 + 3 + 24)
    attn = (D * 1024 + 1024 * 80 * 192 + D * 576 + 512 * 16 * 256
            + D * 64 + D * 8192 + 8192 * D + 1024 + 512)
    mix = 2 * (16384 * 24 + 16384 + 3 + 24)
    expert = 3 * D * 1280 + 3 + 1
    dense = 3 * D * 12288 + 3 + 1
    router = D * 384 + 384
    layer1 = attn + mix + 2 * D + dense
    moe_layer = attn + mix + 2 * D + router + 49 * expert
    vocab = 27520 * D
    n = FAMILY.param_count(CFG)
    assert n == layer1 + 4 * moe_layer + 2 * vocab + D
    assert n == pytest.approx(3.93e9, rel=0.01)
    assert [k for _, k in FAMILY.layer_kinds(CFG)] == [128, 128, 0, 128, 128]
    assert FAMILY.full_layers(CFG) == 1


def test_latent_paged_attn_counts_against_a_hand_count():
    # a row: 512 + 64 values at 2 bytes, read once; per slot 80 queries of
    # 576 at 2 bytes in and 80 outputs of 512 at 4 bytes out
    assert FAMILY.latent_paged_attn_bytes(CFG, 1000, 32) == (
        1000 * 1152 + 32 * 80 * (1152 + 2048))
    # per row and head a 576-wide score and a 512-wide weighted row
    assert FAMILY.latent_paged_attn_flops(CFG, 1000) == (
        2 * 1000 * 80 * (576 + 512))


def test_request_flops_of_one_request_by_hand():
    t_p, n, share = 300, 5, 0.125
    rows = t_p + n - 1
    attn = (D * 1024 + 1024 * 80 * 192 + D * 576 + 512 * 16 * 256
            + D * 64 + D * 8192 + 8192 * D)
    mix = 2 * 16384 * 24
    per_token = (5 * (attn + mix) + 3 * D * 12288
                 + 4 * (D * 384 + 3 * D * 1280 * (1 + 8 * share)))
    ctx = np.arange(1, rows + 1)
    per_key = 2 * 80 * (128 + 64 + 128)
    # four window layers attend min(t + 1, 128), the full one t + 1
    attention = per_key * (4 * np.minimum(ctx, 128).sum() + ctx.sum())
    want = rows * 2 * per_token + attention + n * 2 * D * 27520
    assert FAMILY.request_flops(CFG, t_p, n, share) == pytest.approx(want)


def _obs(call_ns, calls, busy_ns=None, name="latent_paged_attn"):
    events = [tr.Event("/device:TPU:0", tr.OP_LINE,
                       PALLAS.format(i=i, name=name), i * 1e6, call_ns)
              for i in range(calls)]
    if busy_ns:
        events.append(tr.Event("/device:TPU:0", tr.OP_LINE,
                               "%fusion.1 = f32[8] fusion()", 1e9, busy_ns))
    cell = spec.Cell(name="motif3_mixed_sat", chips=1, config=CFG,
                     traffic={"engine": {"slots": 32}}, end_to_end=(),
                     per_layer=())
    return Observations(cell=cell, family=FAMILY, device={}, peaks=PEAKS,
                        trace=tr.build(events))


@pytest.fixture
def counted(monkeypatch):
    """Process totals as a run would leave them: 100 steps whose one full
    layer attended 24,000,000 rows (32 slots at a mean context of 7,500)
    and whose four window layers 1,638,400 (every ring full)."""
    monkeypatch.setattr(engine_thread, "decode_counts", lambda: {
        engine_thread.STEPS: 100.0, engine_thread.SLOT_STEPS: 3200.0,
        engine_thread.ROWS: 24.0e6})
    monkeypatch.setattr(pc, "series", lambda name: {
        latent_paged_attn_roofline.ROWS: {'{kind="full"}': 24.0e6,
                                          '{kind="window"}': 1638400.0},
    }.get(name))


def test_roofline_reads_100_percent_at_the_least_time_and_never_more(
        counted):
    # 240,000 rows a call: 276.5 MB of rows + 8.2 MB of queries and
    # outputs at 819 GB/s (the FLOPs, 41.8 G, take 0.21 ms at 197 TF/s)
    least = (240000 * 1152 + 32 * 80 * 3200) / 8.19e11
    assert least > 2 * 240000 * 80 * 1088 / 197e12
    exact = latent_paged_attn_roofline.read(_obs(least * 1e9, 4))
    assert exact == pytest.approx(100.0, rel=1e-9)
    slower = latent_paged_attn_roofline.read(_obs(2 * least * 1e9, 4))
    assert slower == pytest.approx(50.0, rel=1e-9)


def test_roofline_counts_only_the_named_kernel(counted):
    least_ns = (240000 * 1152 + 32 * 80 * 3200) / 8.19e11 * 1e9
    obs = _obs(least_ns, 4)
    other = [tr.Event("/device:TPU:0", tr.OP_LINE,
                      PALLAS.format(i=100 + i, name="dsa_prefill_attn"),
                      5e9 + i * 1e6, 10 * least_ns) for i in range(4)]
    obs.trace = tr.build(tr.first_device(obs.trace) + other)
    assert latent_paged_attn_roofline.read(obs) == pytest.approx(100.0)


def test_busy_share_and_window_share(counted):
    obs = _obs(1e6, 4, busy_ns=6e6)
    assert latent_paged_attn_busy_share.read(obs) == pytest.approx(40.0)
    assert latent_window_rows_share.read(obs) == pytest.approx(
        100.0 * 1638400 / (24.0e6 + 1638400))


def test_readers_find_nothing_in_a_program_without_the_counters(monkeypatch):
    monkeypatch.setattr(pc, "series", lambda name: None)
    monkeypatch.setattr(engine_thread, "decode_counts", lambda: None)
    obs = _obs(1e6, 8)
    assert latent_paged_attn_roofline.read(obs) is None
    assert latent_window_rows_share.read(obs) is None
    assert latent_paged_attn_busy_share.read(_obs(1e6, 0, busy_ns=6e6)) is None
