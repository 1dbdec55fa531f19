#!/usr/bin/env python
"""Benchmark harness — prints ONE JSON line with the headline metric.

Headline (BASELINE.json primary metric): **ResNet-50 GraphModel `fit()`
samples/sec on one TPU chip** (BASELINE config 2), with an MFU estimate.
All four single-chip BASELINE configs are measured and recorded in the
headline line's `extra.configs`:

  1. LeNet MNIST SequentialModel       (BASELINE config 1)
  2. ResNet-50 GraphModel, 224x224x3   (BASELINE config 2 — headline)
  3. GravesLSTM char-RNN, TBPTT        (BASELINE config 3)
  4. BERT-base-shaped transformer step (BASELINE config 4 architecture;
     built through the config DSL rather than TF import so the bench has
     no TensorFlow runtime dependency on the TPU host)

Protocol follows BASELINE.md: warm up past XLA compile, then report
steady-state samples/sec over timed iterations (PerformanceListener is the
reference's instrument; here we time the fit_batch loop directly and
block_until_ready before reading the clock).

FLOPs/MFU: forward-pass FLOPs come from XLA's own cost analysis of the
compiled forward (jit(...).lower().compile().cost_analysis()); training-step
FLOPs are estimated as 3x forward (the standard fwd+bwd accounting).  MFU is
against the chip's bf16 peak (models run bf16 compute on TPU by default).

vs_baseline: BASELINE.json carries no published reference numbers
(`published: {}` — see BASELINE.md provenance).  The north-star statement is
"match nd4j-cuda A100 samples/sec per chip"; DL4J never published A100
ResNet-50 numbers, so we normalize against a DOCUMENTED ASSUMPTION: a
well-tuned cuDNN-backed framework trains ResNet-50 at ~400 samples/sec/A100
(fp32, batch 128; mixed-precision pushes 2-3x higher).  vs_baseline =
ours / 400.  The assumption is recorded in the output.

Set BENCH_QUICK=1 for a fast smoke run (tiny shapes, few iterations) —
useful on CPU; numbers from quick mode are not comparable.
"""

from __future__ import annotations

import functools
import json
import math
import os
import sys
import time

ASSUMED_RESNET50_A100_SAMPLES_PER_SEC = 400.0
QUICK = os.environ.get("BENCH_QUICK", "") not in ("", "0")

# Floor on warmup steps excluded from every timed window (compile +
# first-dispatch noise must not leak into steady-state rates).  CLI:
# --warmup-steps N; env: BENCH_WARMUP_STEPS.
WARMUP_STEPS = int(os.environ.get("BENCH_WARMUP_STEPS", "3"))

def _peak_flops() -> tuple[float | None, str]:
    """(bf16 peak FLOP/s per chip, device kind) from the ONE peaks table
    (`observe.cost.PEAKS_BY_DEVICE_KIND`).  A TPU the table does not
    list raises; a non-TPU device has no MFU (peak None)."""
    import jax

    from deeplearning4j_tpu.observe.cost import peaks

    d0 = jax.devices()[0]
    kind = str(getattr(d0, "device_kind", d0.platform))
    if d0.platform != "tpu":
        return None, kind
    return peaks()[0] / jax.local_device_count(), kind


def _cost_flops(jitted, *args) -> float | None:
    """FLOPs of one call of `jitted(*args)` per XLA cost analysis."""
    try:
        cost = jitted.lower(*args).compile().cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0]
        return float(cost["flops"])
    except Exception:
        return None


def _fwd_flops_sequential(model, feats) -> float | None:
    """Per-EXAMPLE forward FLOPs (XLA counts the whole batch; divide out)."""
    import jax

    def f(params, net_state, x):
        out = model._forward(params, net_state, x, training=False, rng=None)
        return out[0]

    total = _cost_flops(jax.jit(f), model.params, model.net_state, feats)
    return total / feats.shape[0] if total else None


def _fwd_flops_graph(model, feats: tuple) -> float | None:
    """Per-EXAMPLE forward FLOPs (XLA counts the whole batch; divide out)."""
    import jax

    def f(params, net_state, features):
        inputs = dict(zip(model.conf.network_inputs, features))
        outs, _ = model._forward(params, net_state, inputs, training=False, rng=None)
        return outs

    total = _cost_flops(jax.jit(f), model.params, model.net_state, feats)
    return total / feats[0].shape[0] if total else None


def _lstm_fwd_flops(vocab: int, hidden: int, seq: int, n_layers: int = 2) -> float:
    """Analytic per-example forward FLOPs of the char-RNN stack.  XLA's
    cost_analysis counts a lax.scan body ONCE (not x trip count), so the
    recurrent matmuls — the dominant term — vanish from its total; count
    them by hand instead.  Gate width 4H (LSTM family)."""
    f = seq * (2 * vocab * 4 * hidden + 2 * hidden * 4 * hidden)  # layer 0
    f += (n_layers - 1) * seq * (2 * hidden * 4 * hidden) * 2     # stack
    f += seq * 2 * hidden * vocab                                 # output
    return float(f)


def _transformer_fwd_flops(vocab: int, d: int, seq: int, n_layers: int,
                           causal: bool) -> float:
    """Analytic per-example forward FLOPs of a pre-LN transformer LM.
    Needed because XLA cannot see through the Pallas flash-attention call.
    Per layer: QKVO projections 8*T*d^2, attention score+value 4*T^2*d
    (halved for causal — flash skips the masked blocks), MLP (4x) 16*T*d^2;
    plus the vocab head 2*T*d*V."""
    attn_td2 = 8 * seq * d * d
    attn_t2d = 4 * seq * seq * d * (0.5 if causal else 1.0)
    mlp = 16 * seq * d * d
    return float(n_layers * (attn_td2 + attn_t2d + mlp) + 2 * seq * d * vocab)


def _timed_chunks(run_chunk, *, chunks: int = 4) -> tuple[float, dict]:
    """Timing engine shared by every config: `chunks` equal chunks of
    work, each fully synced.

    run_chunk() runs a fixed amount of work and returns the sample count;
    it must wait for the device (`block_until_ready` on the result)
    before returning.  Returns (median chunk rate, meta); meta carries
    every chunk's rate, the whole-run mean and the max/min spread, so a
    reader sees how self-consistent the number is."""
    import statistics

    rates: list[float] = []
    total_samples = 0
    total_time = 0.0
    for _ in range(chunks):
        t0 = time.perf_counter()
        samples = run_chunk()
        dt = time.perf_counter() - t0
        rates.append(samples / dt)
        total_samples += samples
        total_time += dt
    meta = {
        "samples_per_sec_mean": round(total_samples / total_time, 1),
        "chunks": len(rates),
        "chunk_rates": [round(r, 1) for r in rates],
        "rate_spread": round(max(rates) / max(min(rates), 1e-9) - 1, 4),
    }
    return statistics.median(rates), meta


def _stage(batches):
    """Pre-place batches on device.  The bench measures TRAINING throughput
    (the PerformanceListener metric); host->device staging is the async
    prefetch pipeline's job (AsyncDataSetIterator overlaps it in real
    runs)."""
    import jax

    from deeplearning4j_tpu.data.dataset import DataSet

    return [
        DataSet(jax.device_put(b.features), jax.device_put(b.labels))
        for b in batches
    ]


def _timed_fit(model, batches, warmup: int, iters: int,
               spe: int = 1) -> tuple[float, dict]:
    """Steady-state samples/sec of fit_batch via the chunk engine
    (_timed_chunks); `iters` sets the per-chunk work (iters/4 steps per
    chunk).

    spe (steps_per_execution) > 1 groups that many optimizer steps into
    one compiled program (fit(steps_per_execution=k)'s engine) — used for
    configs whose single step is smaller than the per-dispatch latency.

    Sync: block_until_ready on the params plus the last step's loss
    readback (`score_value`) before the clock is read."""
    import jax

    warmup = max(warmup, WARMUP_STEPS)

    def _sync():
        jax.block_until_ready(model.params)
        model.score_value          # scalar readback of the last loss

    batches = _stage(batches)
    n = len(batches)

    tbptt = (
        getattr(model.conf, "backprop_type", "") == "tbptt"
        and getattr(model.conf, "tbptt_length", 0) > 0
    )
    if spe > 1:
        # the grouped path bypasses fit()'s compatibility guards; assert
        # the same preconditions so a future config switch can't silently
        # train wrong-but-plausibly
        assert getattr(model, "_batch_sharding", None) is None
        assert not getattr(model, "_grad_compression", None)
        assert getattr(model, "_pipeline_schedule", "gpipe") != "1f1b"
        if tbptt:
            assert batches[0].features.shape[1] % model.conf.tbptt_length == 0
        model._multi_iter_dev = None

    state = {"i": 0}

    def run(count):
        samples = 0
        i = state["i"]
        if spe > 1:
            grouped = (
                model._run_steps_grouped_tbptt if tbptt
                else model._run_steps_grouped
            )
            for _ in range(count // spe):
                group = [batches[(i + j) % n] for j in range(spe)]
                grouped(group)
                samples += sum(b.num_examples for b in group)
                i += spe
        else:
            for _ in range(count):
                b = batches[i % n]
                model.fit_batch(b)
                samples += b.num_examples
                i += 1
        state["i"] = i
        return samples

    run(warmup)
    _sync()
    per = max(iters // 4, spe)

    def chunk():
        samples = run(per)
        _sync()
        return samples

    if QUICK or iters < 8:
        t0 = time.perf_counter()
        samples = chunk()
        return samples / (time.perf_counter() - t0), {"chunks": 1}
    return _timed_chunks(chunk)


def _metrics_snapshot():
    """Telemetry-spine snapshot for a result row: the compile / ETL-wait /
    cache / step counters from `observe.metrics` (cumulative since
    process start — rows later in the run include earlier configs'
    taxes; the per-row DELTA is the difference between consecutive
    rows).  BENCH_*.json therefore carries the feed-and-compile evidence
    alongside the throughput it explains."""
    try:
        from deeplearning4j_tpu.observe.metrics import registry

        return registry().snapshot(prefixes=(
            "dl4jtpu_compile_", "dl4jtpu_etl_", "dl4jtpu_data_cache_",
            "dl4jtpu_train_steps", "dl4jtpu_health_",
        ))
    except Exception:
        return None


def _env_provenance():
    """Environment identity stamped into every bench row so the perf
    trajectory stays comparable across regenerations: jax/jaxlib
    versions, the devices the numbers came from, and the runtime flags
    that change the measured path."""
    try:
        import jax
        import jaxlib

        from deeplearning4j_tpu.runtime.flags import environment
        from deeplearning4j_tpu.version import __version__

        devs = jax.devices()
        env = environment()
        return {
            "version": __version__,
            "jax": jax.__version__,
            "jaxlib": jaxlib.__version__,
            "platform": devs[0].platform,
            "device_kind": str(getattr(devs[0], "device_kind", "")),
            "device_count": len(devs),
            "flags": {
                "bf16_compute": env.use_bfloat16_compute,
                "sequence_bucket_size": env.sequence_bucket_size,
                "prefetch_depth": env.prefetch_depth,
                "device_decode": env.device_decode,
                "watchdog_enabled": env.watchdog_enabled,
            },
        }
    except Exception as e:
        # provenance is evidence, never a bench failure
        return {"error": f"{type(e).__name__}: {e}"}


def _entry(name, sps, fwd_flops_per_example, peak, batch, note=None,
           timing=None, **extra):
    train_flops = 3.0 * fwd_flops_per_example if fwd_flops_per_example else None
    mfu = (
        round(sps * train_flops / peak, 4)
        if (train_flops and peak)
        else None
    )
    e = {
        "config": name,
        "samples_per_sec": round(sps, 1),
        "batch": batch,
        "fwd_flops_per_example": fwd_flops_per_example,
        "train_flops_per_example_est": train_flops,
        "mfu_vs_bf16_peak": mfu,
        "metrics": _metrics_snapshot(),
        "env": _env_provenance(),
    }
    if timing:
        e["timing"] = timing
    if note:
        e["note"] = note
    e.update(extra)
    return e


def bench_lenet(peak):
    import numpy as np

    from deeplearning4j_tpu.data.builtin import MnistDataSetIterator
    from deeplearning4j_tpu.zoo.lenet import LeNet

    batch = 64 if QUICK else 512
    train = MnistDataSetIterator(batch_size=batch, train=True,
                                 num_examples=batch * 8 if QUICK else 30000)
    model = LeNet().init_model()
    batches = list(train)[: (4 if QUICK else 40)]
    x0 = np.asarray(batches[0].features)
    flops = _fwd_flops_sequential(model, x0)
    # a LeNet step is far smaller than the per-dispatch latency: run 10
    # optimizer steps per compiled execution (fit(steps_per_execution=10))
    spe = 2 if QUICK else int(os.environ.get("BENCH_LENET_SPE", "50"))
    sps, timing = _timed_fit(model, batches, warmup=4 if QUICK else 2 * spe,
                             iters=10 if QUICK else 20 * spe, spe=spe)
    acc = None
    try:
        test = MnistDataSetIterator(batch_size=1000, train=False,
                                    num_examples=2000 if QUICK else 5000)
        acc = round(model.evaluate(test).accuracy(), 4)
    except Exception:
        pass
    return _entry("lenet_mnist_mln", sps, flops, peak, batch,
                  final_accuracy=acc, synthetic_data=train.is_synthetic,
                  steps_per_execution=spe, timing=timing)


def bench_resnet50(peak):
    import numpy as np

    from deeplearning4j_tpu.data.dataset import DataSet
    from deeplearning4j_tpu.zoo.resnet import ResNet50

    if QUICK:
        batch, hw, n_classes = 8, 64, 10
    else:
        # batch 256 measured faster per chip than round-2/3's 128 (higher
        # arithmetic intensity amortizes the HBM-bound tail — PROFILE.md
        # round-4 A/B); BASELINE pins no batch (north star is sps/chip)
        batch = int(os.environ.get("BENCH_RESNET_BATCH", "256"))
        hw, n_classes = 224, 1000
    model = ResNet50(num_classes=n_classes, height=hw, width=hw).init_model()
    rng = np.random.default_rng(0)
    batches = [
        DataSet(
            rng.normal(0, 1, (batch, hw, hw, 3)).astype(np.float32),
            np.eye(n_classes, dtype=np.float32)[
                rng.integers(0, n_classes, batch)
            ],
        )
        for _ in range(2 if QUICK else 4)
    ]
    flops = _fwd_flops_graph(model, (np.asarray(batches[0].features),))
    # spe=16 measured faster than 8 at equal health (r5 A/B: 2073 vs
    # ~2012 sps — deeper step-grouping shaves the residual dispatch tax)
    spe = 1 if QUICK else int(os.environ.get("BENCH_RESNET_SPE", "16"))
    sps, timing = _timed_fit(model, batches, warmup=2 if QUICK else 3 * spe,
                             iters=4 if QUICK else 15 * spe, spe=spe)
    return _entry("resnet50_cg", sps, flops, peak, batch,
                  image=f"{hw}x{hw}x3 synthetic", num_classes=n_classes,
                  steps_per_execution=spe, timing=timing)


def _etl_config():
    if QUICK:
        return 8, 64, 4, 64          # batch, hw, n_classes, n_img
    return (int(os.environ.get("BENCH_RESNET_BATCH", "256")), 224, 4, 1024)


def _etl_corpus(n_img: int, n_classes: int) -> str:
    """One-time synthetic JPEG corpus (typical ImageNet source size);
    shared by the etl_fed and etl_fed_cached benches."""
    import os as _os
    import tempfile

    import numpy as np

    root = _os.path.join(tempfile.gettempdir(), f"dl4jtpu_etl_{n_img}")
    marker = _os.path.join(root, "c3", f"img_{n_img - 1:05d}.jpg")
    if not _os.path.exists(marker):
        from PIL import Image

        rng = np.random.default_rng(0)
        gx = np.linspace(0, 255, 500)[None, :] * np.ones((375, 1))
        gy = np.linspace(0, 255, 375)[:, None] * np.ones((1, 500))
        for i in range(n_img):
            cls = i % n_classes
            d = _os.path.join(root, f"c{cls}")
            _os.makedirs(d, exist_ok=True)
            img = np.stack([
                (gx + 40 * cls) % 256,
                (gy * 0.7 + rng.integers(0, 64)) % 256,
                rng.integers(0, 255, (375, 500)),
            ], -1).astype(np.uint8)
            Image.fromarray(img).save(
                _os.path.join(d, f"img_{i:05d}.jpg"), quality=85)
    return root


def bench_resnet50_etl(peak):
    """BASELINE config 2 with a REAL image input pipeline (VERDICT r4):
    JPEGs on disk -> native libjpeg batch decode (ImageRecordReader fast
    path) -> RecordReaderDataSetIterator -> AsyncDataSetIterator ->
    fit().  Reports the raw ETL rate and the ETL-fed training rate next
    to the synthetic number so the input tier is measured, not assumed.
    The decode tier is threaded per core; this host's core count is
    recorded alongside (a 1-vCPU dev host caps the decode rate far below
    a real TPU-VM's 100+ cores)."""
    import os as _os

    import numpy as np

    from deeplearning4j_tpu.data.iterator import AsyncDataSetIterator
    from deeplearning4j_tpu.datavec import (
        ImageRecordReader,
        RecordReaderDataSetIterator,
    )
    from deeplearning4j_tpu.zoo.resnet import ResNet50

    batch, hw, n_classes, n_img = _etl_config()
    root = _etl_corpus(n_img, n_classes)

    # uint8 WIRE format: decoded bytes cross the host->device link at 1/4
    # the f32 size and cast to the compute dtype inside the jitted step
    # (h2d_mb_per_s below records the link rate this run saw)
    reader = ImageRecordReader(hw, hw, 3, shuffle_seed=0, dtype="uint8")
    reader.initialize(root)

    # raw ETL rate: full decode pipeline, no device in the loop
    t0 = time.perf_counter()
    it = RecordReaderDataSetIterator(reader, batch, label_index=1,
                                     num_classes=n_classes, drop_last=True)
    n_fed = sum(b.num_examples for b in it)
    etl_rate = n_fed / (time.perf_counter() - t0)

    model = ResNet50(num_classes=n_classes, height=hw, width=hw).init_model()

    # ETL-fed training: async producer overlaps decode with device steps
    it.reset()
    feed = AsyncDataSetIterator(it, queue_size=4)
    warm = 1 if QUICK else 2
    for i, b in enumerate(feed):
        if i >= warm:
            break
        model.fit_batch(b)
    t0 = time.perf_counter()
    samples = 0
    it.reset()
    last = None
    for b in AsyncDataSetIterator(it, queue_size=4):
        last = model.fit_batch(b)
        samples += b.num_examples
    model.score_value
    sps = samples / (time.perf_counter() - t0)

    # decompose the synthetic-vs-ETL gap: host->device transfer rate of
    # one real batch
    import jax

    one = next(iter(AsyncDataSetIterator(it, queue_size=1,
                                         device_put=False)))
    feats = np.asarray(one.features)
    t0 = time.perf_counter()
    jax.block_until_ready(jax.device_put(feats))
    h2d_s = time.perf_counter() - t0
    h2d_mb_s = feats.nbytes / 1e6 / h2d_s
    return _entry(
        "resnet50_etl_fed", sps, None, peak, batch,
        etl_images_per_sec=round(etl_rate, 1),
        wire_dtype="uint8",
        h2d_mb_per_s=round(h2d_mb_s, 1),
        host_cpus=_os.cpu_count(),
        n_images=n_img, num_classes=n_classes,
        source_size="500x375 JPEG q85",
        note="real-image pipeline (uint8 wire): disk JPEG -> native "
             "decode -> async prefetch -> fit.  The gap vs the synthetic "
             "resnet50_cg entry decomposes into JPEG decode (CPU-bound; "
             "measure scaling with bench.py --decode-scaling) and "
             "host->device transfer (h2d_mb_per_s; the uint8 wire puts a "
             "224px image at ~0.147 MB — 4x under f32)",
    )


def bench_resnet50_etl_cached(peak):
    """The cached-batch ETL tier (ExistingMiniBatchDataSetIterator role):
    epoch 1 decodes JPEGs and writes device-format uint8 batches to disk
    via CachedDataSetIterator; the TIMED epoch mmaps those batches and
    feeds fit() with zero decode work.  The row quantifies the re-decode
    tax the plain etl_fed row pays every epoch — on decode-bound hosts
    the cached rate approaches the synthetic headline."""
    import os as _os
    import shutil
    import tempfile

    from deeplearning4j_tpu.data.cached import CachedDataSetIterator
    from deeplearning4j_tpu.data.iterator import AsyncDataSetIterator
    from deeplearning4j_tpu.datavec import (
        ImageRecordReader,
        RecordReaderDataSetIterator,
    )
    from deeplearning4j_tpu.zoo.resnet import ResNet50

    batch, hw, n_classes, n_img = _etl_config()
    root = _etl_corpus(n_img, n_classes)

    reader = ImageRecordReader(hw, hw, 3, shuffle_seed=0, dtype="uint8")
    reader.initialize(root)
    base = RecordReaderDataSetIterator(reader, batch, label_index=1,
                                       num_classes=n_classes, drop_last=True)
    cache_dir = tempfile.mkdtemp(prefix="dl4jtpu_batch_cache_")
    try:
        cached = CachedDataSetIterator(base, cache_dir)
        # epoch 1: decode + persist (the one-time cost the cache amortizes)
        t0 = time.perf_counter()
        n_fed = sum(b.num_examples for b in cached)
        populate_s = time.perf_counter() - t0
        assert cached.is_cached
        # raw replay rate: mmap -> batches, no decode, no device
        t0 = time.perf_counter()
        n_replay = sum(b.num_examples for b in cached)
        replay_rate = n_replay / (time.perf_counter() - t0)

        model = ResNet50(num_classes=n_classes, height=hw, width=hw).init_model()
        warm = 1 if QUICK else 2
        for i, b in enumerate(AsyncDataSetIterator(cached, queue_size=4)):
            if i >= warm:
                break
            model.fit_batch(b)
        t0 = time.perf_counter()
        samples = 0
        for b in AsyncDataSetIterator(cached, queue_size=4):
            model.fit_batch(b)
            samples += b.num_examples
        model.score_value
        sps = samples / (time.perf_counter() - t0)
        cache_bytes = sum(
            _os.path.getsize(_os.path.join(cache_dir, f))
            for f in _os.listdir(cache_dir)
        )
    finally:
        shutil.rmtree(cache_dir, ignore_errors=True)
    return _entry(
        "etl_fed_cached", sps, None, peak, batch,
        cache_populate_s=round(populate_s, 2),
        cache_replay_images_per_sec=round(replay_rate, 1),
        cache_mb=round(cache_bytes / 1e6, 1),
        wire_dtype="uint8",
        host_cpus=_os.cpu_count(),
        n_images=n_img, num_classes=n_classes,
        note="cached-batch ETL tier: epoch 1 decodes and persists uint8 "
             "batches (cache_populate_s), the timed epoch mmaps them — "
             "the gap between this row and resnet50_etl_fed is the "
             "per-epoch re-decode tax CachedDataSetIterator eliminates",
    )


def bench_lstm(peak):
    import numpy as np

    from deeplearning4j_tpu.data.dataset import DataSet
    from deeplearning4j_tpu.zoo.textgen import TextGenerationLSTM

    vocab = 77
    if QUICK:
        batch, seq, hidden = 8, 32, 64
    else:
        # BASELINE config 3 pins neither batch nor hidden (VERDICT r3);
        # batch 1024 raises per-scan-step arithmetic intensity 16x over
        # round 3's 64 — the recurrent matmuls at batch 64 left the MXU
        # ~99% idle (measured r4 A/B: b64 ~8k sps, b512/spe8 27.6k,
        # b1024/spe8 35.3k)
        batch = int(os.environ.get("BENCH_LSTM_BATCH", "1024"))
        seq, hidden = 200, 200
    model = TextGenerationLSTM(vocab_size=vocab, hidden=hidden,
                               tbptt_length=50).init_model()
    rng = np.random.default_rng(1)
    batches = []
    for _ in range(2 if QUICK else 4):
        ids = rng.integers(0, vocab, (batch, seq))
        x = np.eye(vocab, dtype=np.float32)[ids]          # one-hot chars
        y = np.eye(vocab, dtype=np.float32)[np.roll(ids, -1, axis=1)]
        batches.append(DataSet(x, y))
    flops = _lstm_fwd_flops(vocab, hidden, seq)
    spe = 1 if QUICK else int(os.environ.get("BENCH_LSTM_SPE", "8"))
    sps, timing = _timed_fit(model, batches, warmup=2 if QUICK else 2 * spe,
                             iters=4 if QUICK else 10 * spe, spe=spe)
    return _entry("graveslstm_charnn", sps, flops, peak, batch,
                  seq_len=seq, tbptt=50, hidden=hidden,
                  steps_per_execution=spe, timing=timing,
                  flops_source="analytic (XLA cost_analysis counts scan "
                               "bodies once, dropping the recurrent matmuls)")


def bench_bert(peak):
    """BASELINE config 4 — SameDiff BERT-base fine-tune via ACTUAL TF
    import: a frozen BERT-base-shaped classifier GraphDef is synthesized
    through the self-contained codec (real BERT-base weights are ~440MB —
    not a committable fixture — and the bench host has no TensorFlow;
    tests/test_tf_import_goldens.py proves real TF executes these bytes
    identically), imported with trainable=True, and fine-tuned on
    BertIterator WordPiece batches."""
    import numpy as np

    from deeplearning4j_tpu.autodiff.samediff import TrainingConfig
    from deeplearning4j_tpu.modelimport._tf.synthetic import (
        build_bert_classifier_graphdef,
    )
    from deeplearning4j_tpu.modelimport.tensorflow import import_graph
    from deeplearning4j_tpu.nlp.wordpiece import (
        BertIterator,
        BertWordPieceTokenizer,
    )
    from deeplearning4j_tpu.nn.updaters import Adam

    if QUICK:
        vocab, d, heads, layers, batch, seq = 128, 32, 2, 2, 4, 16
    else:
        vocab, d, heads, layers, batch, seq = 30522, 768, 12, 12, 32, 128
    n_classes = 2

    raw = build_bert_classifier_graphdef(
        vocab=vocab, d_model=d, n_layers=layers, n_heads=heads,
        seq_len=seq, batch=batch, n_classes=n_classes, seed=4,
    )
    graph_mb = round(len(raw) / 1e6, 1)
    sd = import_graph(raw, trainable=True)
    labels = sd.placeholder("labels")
    loss = sd.loss.softmax_cross_entropy(sd["logits"], labels, name="loss")
    sd.set_loss(loss)
    sd.set_training_config(
        TrainingConfig(updater=Adam(2e-5), bf16_compute=True)
    )

    # SST-2-style sentences through the real WordPiece pipeline
    words = ["the", "movie", "was", "great", "terrible", "plot", "acting",
             "boring", "brilliant", "slow", "fun", "a", "it", "felt",
             "script", "ending"]
    pieces = {t: i + 5 for i, t in enumerate(words)}
    tok = BertWordPieceTokenizer(
        {"[PAD]": 0, "[UNK]": 1, "[CLS]": 2, "[SEP]": 3, "[MASK]": 4,
         **pieces}
    )
    rng = np.random.default_rng(2)
    n_sent = batch * 4
    sentences = [
        " ".join(rng.choice(words, rng.integers(6, seq // 2)))
        for _ in range(n_sent)
    ]
    it = BertIterator(tok, sentences, rng.integers(0, n_classes, n_sent),
                      num_classes=n_classes, batch_size=batch, max_len=seq)
    feeds = [
        {"ids": b.features.astype(np.int32), "labels": b.labels}
        for b in it
    ]

    warmup, iters = (2, 4) if QUICK else (6, 24)
    for i in range(warmup):
        sd.fit_batch(feeds[i % len(feeds)])
    state = {"step": warmup}
    per = max(iters // 4, 1)

    def chunk():
        last = None
        for _ in range(per):
            # sync=False pipelines the steps; the end-of-chunk float()
            # readback waits for the device
            last = sd.fit_batch(feeds[state["step"] % len(feeds)], sync=False)
            state["step"] += 1
        _ = float(last)
        return per * batch

    if QUICK:
        t0 = time.perf_counter()
        n = chunk()
        best, timing = n / (time.perf_counter() - t0), {"chunks": 1}
    else:
        best, timing = _timed_chunks(chunk)

    # analytic fwd FLOPs (non-causal attention + classifier head)
    flops = float(
        layers * (24 * seq * d * d + 4 * seq * seq * d)
        + 2 * d * n_classes
    )
    return _entry(
        "bert_base_tf_import_finetune", best, flops, peak, batch,
        seq_len=seq, d_model=d, n_layers=layers, timing=timing,
        tf_import=True, frozen_graph_mb=graph_mb,
        note="frozen BERT-base-shaped GraphDef imported via "
             "modelimport.tensorflow (trainable=True) and fine-tuned with "
             "BertIterator; graph synthesized by the self-contained codec "
             "(no TF on the bench host)",
    )


def bench_longctx(peak):
    """Long-context causal LM step: Pallas flash attention (O(block)
    memory — dense logits would be (B,H,T,T)) + chunked vocab loss.
    Reported as tokens/sec (the long-context unit of work)."""
    import numpy as np

    from deeplearning4j_tpu.data.dataset import DataSet
    from deeplearning4j_tpu.zoo.transformer import TransformerEncoder

    if QUICK:
        vocab, d, heads, layers, batch, seq = 128, 64, 4, 2, 2, 256
    else:
        # r4: d=1024/8-layer flagship (r3's d=512/4-layer was judged
        # sub-scale; bigger matmuls more than double the measured MFU:
        # 13.3% -> 33.6% on-chip with the Pallas fwd+bwd flash kernels)
        vocab, d, heads, layers, batch, seq = 32000, 1024, 8, 8, 4, 2048
    if not QUICK:
        # pick the fastest flash block config for this shape ONCE (eager
        # timing, cached; trace-time dispatch reads the cache)
        from deeplearning4j_tpu.ops.flash_attention import flash_autotune

        blocks = flash_autotune(seq_len=seq, n_heads=heads,
                                head_dim=d // heads, batch=batch,
                                causal=True)
        print(f"[bench] longctx flash blocks: {blocks}", file=sys.stderr)
    model = TransformerEncoder(
        vocab_size=vocab, d_model=d, n_heads=heads, n_layers=layers,
        causal=True, chunked_vocab_loss=True, vocab_chunk=8192,
    ).init_model()
    rng = np.random.default_rng(3)
    batches = []
    for _ in range(2 if QUICK else 3):
        ids = rng.integers(0, vocab, (batch, seq))
        batches.append(DataSet(ids.astype(np.float32),
                               np.roll(ids, -1, axis=1).astype(np.float32)))
    sps, timing = _timed_fit(model, batches, warmup=2 if QUICK else 6,
                             iters=4 if QUICK else 24)
    return _entry(
        "longctx_flash_chunked_lm", sps,
        _transformer_fwd_flops(vocab, d, seq, layers, causal=True),
        peak, batch,
        seq_len=seq, d_model=d, n_layers=layers, vocab=vocab,
        tokens_per_sec=round(sps * seq, 1), timing=timing,
        note="flash attention + chunked vocab loss",
        flops_source="analytic (XLA cost analysis cannot see through the "
                     "Pallas flash-attention call)",
    )


def bench_longctx_quant() -> None:
    """bench.py --longctx: the long-context transformer's INFERENCE
    path, f32 vs int8-quantized (quant/ptq.py) -> BENCH_LONGCTX_QUANT
    .json.  Quantization covers the embedding table, every block's
    attention projections + FFN weights, and the LM head; the flash-
    attention core and norms stay f32.  Rows: tokens/sec both ways,
    the measured speedup, prediction agreement (random weights — the
    TRAINED-model parity gates live in tests/test_quant.py), bytes
    saved, and which dequant-matmul impl the quantized programs
    selected.  Quick mode shrinks shapes and does not rewrite the
    committed table."""
    import jax

    jax.config.update(
        "jax_platforms", os.environ.get("BENCH_SERVING_PLATFORM", "cpu")
    )
    import numpy as np

    from deeplearning4j_tpu.observe.metrics import registry
    from deeplearning4j_tpu.quant import (
        parity_check, quantize, quantized_bytes,
    )
    from deeplearning4j_tpu.zoo.transformer import TransformerEncoder

    if QUICK:
        vocab, d, heads, layers, batch, seq = 256, 64, 4, 2, 2, 128
        reps = 4
    else:
        vocab, d, heads, layers, batch, seq = 8192, 512, 8, 4, 2, 1024
        reps = 10
    model = TransformerEncoder(
        vocab_size=vocab, d_model=d, n_heads=heads, n_layers=layers,
        causal=True,
    ).init_model()
    qmodel = quantize(model)
    qb = quantized_bytes(qmodel.params)
    rng = np.random.default_rng(3)
    ids = rng.integers(0, vocab, (batch, seq)).astype(np.float32)

    impl_counts_before = {
        impl: registry().counter(
            "dl4jtpu_quant_dequant_matmul_total"
        ).value(impl=impl)
        for impl in ("pallas", "blocked", "xla")
    }

    def tokens_per_sec(m):
        ms = _time_jitted(
            lambda x: m.output(x), ids, reps=reps,
        )
        return batch * seq / (ms / 1000.0)

    f32_tps = tokens_per_sec(model)
    q_tps = tokens_per_sec(qmodel)
    impls = {
        impl: registry().counter(
            "dl4jtpu_quant_dequant_matmul_total"
        ).value(impl=impl) - impl_counts_before[impl]
        for impl in ("pallas", "blocked", "xla")
    }
    agreement = parity_check(
        model, qmodel, rng.integers(0, vocab, (2, seq)).astype(
            np.float32
        ),
    )
    doc = {
        "schema": "bench-longctx-quant/1",
        "platform": jax.default_backend(),
        "env": _env_provenance(),
        "quick": QUICK,
        "config": {
            "vocab": vocab, "d_model": d, "n_heads": heads,
            "n_layers": layers, "batch": batch, "seq": seq,
        },
        "f32_tokens_per_sec": round(f32_tps, 1),
        "int8_tokens_per_sec": round(q_tps, 1),
        "speedup_vs_f32": round(q_tps / f32_tps, 3),
        "bytes": qb,
        "dequant_matmul_lowerings": impls,
        "prediction_agreement": agreement["top1_agreement"],
        "note": (
            "random-weight agreement; the trained-model parity gates "
            "(top-1 <= 1%, F1 <= 0.02) are asserted in tier-1 "
            "(tests/test_quant.py)"
        ),
    }
    if not QUICK:
        path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "BENCH_LONGCTX_QUANT.json")
        with open(path, "w") as f:
            json.dump(doc, f, indent=1)
        print(f"[bench] longctx quant table -> {path}", file=sys.stderr)
    print(json.dumps(doc))


def bench_resnet_ab() -> None:
    """ResNet batch/spe A/B matrix (VERDICT r5 ask 7 measurement aid):
    runs the headline config across (batch, spe) pairs in one process,
    printing one JSON line per pair.
    Pairs via BENCH_AB_PAIRS="256:8,256:16,384:8,512:8" (default).
    Run:  python bench.py --resnet-ab"""
    if QUICK or os.environ.get("BENCH_FORCE_CPU", "") not in ("", "0"):
        # quick mode hardcodes batch 8 / spe 1 (every pair would measure
        # the SAME config under its requested label — false data), and a
        # full-size ResNet matrix on a host CPU runs for hours; this mode
        # is a chip measurement aid, not a plumbing check
        print(json.dumps({"metric": "resnet50 batch/spe A-B",
                          "error": "requires a real device run "
                                   "(unset BENCH_QUICK/BENCH_FORCE_CPU)"}))
        return
    peak, kind = _peak_flops()
    pairs = [
        tuple(int(v) for v in p.split(":"))
        for p in os.environ.get(
            "BENCH_AB_PAIRS", "256:8,256:16,384:16,512:16").split(",")
    ]
    out = []
    for batch, spe in pairs:
        os.environ["BENCH_RESNET_BATCH"] = str(batch)
        os.environ["BENCH_RESNET_SPE"] = str(spe)
        try:
            r = bench_resnet50(peak)
        except Exception as exc:
            r = {"error": f"{type(exc).__name__}: {exc}"}
        t = r.get("timing", {})
        row = {
            "batch": batch, "spe": spe,
            "samples_per_sec": r.get("samples_per_sec"),
            "mfu": r.get("mfu_vs_bf16_peak"),
            "rate_spread": t.get("rate_spread"),
            "error": r.get("error"),
        }
        out.append({k: v for k, v in row.items() if v is not None})
        print(f"[ab] {json.dumps(out[-1])}", file=sys.stderr)
    print(json.dumps({"metric": "resnet50 batch/spe A-B",
                      "device_kind": kind, "rows": out}))


def bench_decode_scaling() -> None:
    """Measured decode-throughput-vs-worker-count table (VERDICT r4 weak
    #3: "scales per core" must be a measurement, not an assertion).  Runs
    the native libjpeg batch decode over n_threads in {1, 2, 4, ...,
    2*cores} on a synthetic JPEG corpus and prints one JSON line; paste
    the rows into PROFILE.md when re-run on a new host.  The C decode
    loop holds no GIL, so throughput should track physical cores — on a
    1-vCPU host the table comes out flat, which is the honest result
    there.  Run:  python bench.py --decode-scaling
    """
    import os as _os
    import tempfile

    import numpy as np
    from PIL import Image

    from deeplearning4j_tpu.runtime import native

    if not native.has_jpeg():
        print(json.dumps({"metric": "jpeg decode scaling",
                          "error": "native jpeg unavailable"}))
        return
    n_img, hw = (96 if QUICK else 512), 224
    root = _os.path.join(tempfile.gettempdir(), f"dl4jtpu_dec_{n_img}")
    marker = _os.path.join(root, f"img_{n_img - 1:05d}.jpg")
    if not _os.path.exists(marker):
        rng = np.random.default_rng(0)
        _os.makedirs(root, exist_ok=True)
        base = rng.integers(0, 255, (375, 500, 3)).astype(np.uint8)
        for i in range(n_img):
            Image.fromarray(np.roll(base, i * 7, axis=1)).save(
                _os.path.join(root, f"img_{i:05d}.jpg"), quality=85)
    paths = sorted(
        _os.path.join(root, f) for f in _os.listdir(root)
        if f.endswith(".jpg"))
    cores = _os.cpu_count() or 1
    threads = sorted({1, 2, 4, 8, cores, 2 * cores})
    # warm the page cache over the FULL corpus so the first timed row
    # (the speedup baseline) isn't measured partly cold-cache
    native.jpeg_batch_decode(paths, hw, hw, 3, dtype=np.uint8)
    rows = []
    for nt in threads:
        t0 = time.perf_counter()
        native.jpeg_batch_decode(paths, hw, hw, 3, n_threads=nt,
                                 dtype=np.uint8)
        dt = time.perf_counter() - t0
        rows.append({"n_threads": nt,
                     "images_per_sec": round(len(paths) / dt, 1)})
        print(f"[decode] {rows[-1]}", file=sys.stderr)
    base_rate = rows[0]["images_per_sec"]
    for r in rows:
        r["speedup_vs_1"] = round(r["images_per_sec"] / base_rate, 2)
    print(json.dumps({
        "metric": "native libjpeg batch decode images/sec vs n_threads",
        "host_cpus": cores, "n_images": len(paths),
        "source_size": "500x375 JPEG q85", "target": f"{hw}x{hw}x3 uint8",
        "rows": rows,
    }))


def bench_scaling() -> None:
    """BASELINE row 5 readiness: DP scaling — per-chip samples/sec at
    1..N devices plus host-input-pipeline overlap.  On a multi-chip TPU
    host it measures DP ResNet-50 on the real devices; on anything else it
    exercises the identical distribute() path on a virtual CPU mesh with a
    LeNet proxy (numbers validate the MECHANISM and the efficiency table,
    not absolute TPU throughput).  Run:  python bench.py --scaling
    """
    n_target = int(os.environ.get("BENCH_SCALING_DEVICES", "8"))
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={n_target}"
    ).strip()
    import jax

    # The platform must be decided BEFORE anything initializes a backend
    # (probing jax.devices() first would lock it in).  Default: virtual CPU
    # mesh — exercises the real distribute()/GSPMD path on any host.  On a
    # genuine multi-chip TPU slice set BENCH_SCALING_TPU=1 for real-device
    # numbers.  (config update, not JAX_PLATFORMS: experimental PJRT
    # plugins ignore the env var.)
    if os.environ.get("BENCH_SCALING_TPU", "") in ("", "0"):
        jax.config.update("jax_platforms", "cpu")
    devices = jax.devices()
    on_tpu = devices[0].platform == "tpu"
    n_max = min(len(devices), n_target)

    import numpy as np

    from deeplearning4j_tpu.data.dataset import DataSet
    from deeplearning4j_tpu.data.iterator import (
        AsyncDataSetIterator,
        NumpyDataSetIterator,
    )
    from deeplearning4j_tpu.parallel import ParallelConfig, distribute

    # single source of truth for the sweep config (per-chip batch, input
    # shape, classes) — make_model() only builds the matching model
    if on_tpu:
        per_chip_batch, in_shape, n_cls = 128, (224, 224, 3), 1000
    else:
        per_chip_batch, in_shape, n_cls = 64, (28, 28, 1), 10

    def make_model():
        if on_tpu:
            from deeplearning4j_tpu.zoo.resnet import ResNet50

            return ResNet50(num_classes=n_cls).init_model(), per_chip_batch, in_shape, n_cls
        from deeplearning4j_tpu.zoo.lenet import LeNet

        return LeNet().init_model(), per_chip_batch, in_shape, n_cls

    sizes = []
    n = 1
    while n <= n_max:
        sizes.append(n)
        n *= 2
    if sizes[-1] != n_max:
        sizes.append(n_max)

    rng = np.random.default_rng(0)

    def measure(n: int, batch: int) -> float:
        model, _, hw, n_classes = make_model()
        batches = [
            DataSet(
                rng.normal(0, 1, (batch,) + hw).astype(np.float32),
                np.eye(n_classes, dtype=np.float32)[
                    rng.integers(0, n_classes, batch)
                ],
            )
            for _ in range(2)
        ]
        distribute(model, ParallelConfig(data=n), devices=devices[:n])
        warm, iters = (2, 6) if not on_tpu else (8, 30)
        sps, _meta = _timed_fit(model, batches, warmup=warm, iters=iters)
        return sps

    rows = []
    for n in sizes:
        batch = per_chip_batch * n
        sps = measure(n, batch)
        rows.append(
            {
                "devices": n,
                "global_batch": batch,
                "samples_per_sec": round(sps, 1),
                "per_chip": round(sps / n, 1),
            }
        )
        print(f"[scaling] {rows[-1]}", file=sys.stderr)
    base = rows[0]["per_chip"]
    for r in rows:
        r["efficiency"] = round(r["per_chip"] / base, 3)

    # fixed-work variant (VERDICT weak #5): the weak-scaling table above
    # grows the aggregate work with n, so on VIRTUAL devices sharing one
    # host's cores its efficiency column conflates GSPMD overhead with
    # plain core oversubscription (per-chip rate falls ~1/n at perfect
    # mechanism scaling).  Holding the GLOBAL batch constant keeps the
    # aggregate FLOPs fixed no matter how many virtual devices split it,
    # so samples/sec(n) / samples/sec(1) isolates the partitioning +
    # collective overhead — ~1.0 means distribute() itself is free; the
    # shortfall is the mechanism's cost.  (On real TPU devices this is a
    # strong-scaling table: per-device work shrinks as 1/n.)
    import math as _math

    # the constant global batch must shard evenly over EVERY row's data
    # axis (BENCH_SCALING_DEVICES=6 -> sizes [1,2,4,6]); round up to a
    # common multiple so non-power-of-2 meshes don't crash the sweep
    fixed_batch = per_chip_batch
    common = _math.lcm(*sizes)
    fixed_batch = ((fixed_batch + common - 1) // common) * common
    fixed_rows = []
    for n in sizes:
        sps = measure(n, fixed_batch)
        fixed_rows.append(
            {
                "devices": n,
                "global_batch": fixed_batch,
                "samples_per_sec": round(sps, 1),
            }
        )
        print(f"[scaling fixed-work] {fixed_rows[-1]}", file=sys.stderr)
    fbase = fixed_rows[0]["samples_per_sec"]
    for r in fixed_rows:
        r["mechanism_efficiency"] = round(
            r["samples_per_sec"] / fbase, 3
        ) if fbase else None

    # pipelined column (PR 5): the fixed-work rows above feed
    # PRE-STAGED device batches through fit_batch — they isolate the
    # step program but hide the input pipeline entirely.  These
    # measurements run the REAL fit() loop against a decode-per-next()
    # host feed, once with flags.prefetch_depth=2 (PrefetchIterator
    # stages batch N+1 while step N computes) and once with depth=0
    # (serial pull -> stage -> dispatch), so the delta is exactly the
    # software-pipelining win on an ETL-fed loop.
    from deeplearning4j_tpu.data.iterator import DataSetIterator
    from deeplearning4j_tpu.runtime.flags import environment
    from deeplearning4j_tpu.train.listeners import PerformanceListener

    class _RawWireFeed(DataSetIterator):
        """Undecoded uint8 camera-wire batches + int class ids — the
        raw-byte base feed the device-compiled decode path pulls (the
        host's per-batch job is ONE array slice)."""

        def __init__(self, raw, ids, batch, n_batches):
            self._raw, self._ids = raw, ids
            self._batch, self._n = batch, n_batches

        @property
        def batch_size(self):
            return self._batch

        def reset(self):
            pass

        def __iter__(self):
            for i in range(self._n):
                lo = (i * self._batch) % len(self._raw)
                sl = slice(lo, lo + self._batch)
                yield DataSet(self._raw[sl], self._ids[sl])

    class _DecodeFeed(DataSetIterator):
        """uint8 camera-wire batches (224x224x3) decoded on every
        next(): cast + normalize + mean-pool resize down to the model's
        input shape + label one-hot — the JPEG-decode/augment-shaped
        host cost the prefetch pipeline exists to hide."""

        WIRE = (224, 224, 3)

        def __init__(self, raw, ids, batch, n_classes, n_batches, hw):
            self._raw, self._ids = raw, ids
            self._batch, self._ncls = batch, n_classes
            self._n = n_batches
            self._hw = hw

        @property
        def batch_size(self):
            return self._batch

        def reset(self):
            pass

        def __iter__(self):
            for i in range(self._n):
                lo = (i * self._batch) % len(self._raw)
                sl = slice(lo, lo + self._batch)
                x = self._raw[sl].astype(np.float32)
                x = (x - 127.5) / 127.5
                if self._hw != self.WIRE:
                    # decode-resize: 8x8 mean pool + channel collapse,
                    # (B,224,224,3) -> (B,28,28,1)
                    B = x.shape[0]
                    x = x.reshape(B, 28, 8, 28, 8, 3).mean(
                        axis=(2, 4, 5), dtype=np.float32
                    )[..., None]
                x = np.ascontiguousarray(x)
                y = np.eye(self._ncls, dtype=np.float32)[self._ids[sl]]
                yield DataSet(x, y)

    def measure_fit(n: int, batch: int, depth: int,
                    fused: bool = False) -> dict:
        """Steady-state fit() throughput at prefetch_depth=depth.
        fused=True feeds the SAME wire bytes through a
        DeviceTransformIterator so fit() lowers the decode chain into
        the step program and stages raw uint8 — the device-compiled
        data pipeline row."""
        from deeplearning4j_tpu.observe.metrics import registry

        model, _, hw, n_classes = make_model()
        distribute(model, ParallelConfig(data=n), devices=devices[:n])
        warm = max(WARMUP_STEPS, 3)
        iters = (warm + 6) if QUICK else (warm + 16)
        raw = rng.integers(
            0, 256, (batch * 4,) + _DecodeFeed.WIRE
        ).astype(np.uint8)
        ids = rng.integers(0, n_classes, batch * 4)
        if fused:
            from deeplearning4j_tpu.datavec.device import (
                DeviceTransformIterator, MeanPool, OneHot, Scale,
                TransformChain,
            )

            specs = [Scale(1 / 127.5, -1.0)]
            if hw != _DecodeFeed.WIRE:
                # decode-resize to the model input, same math as
                # _DecodeFeed's host mean-pool
                specs.append(MeanPool((8, 8), collapse_channels=True))
            feed = DeviceTransformIterator(
                _RawWireFeed(raw, ids, batch, iters),
                TransformChain(tuple(specs), (OneHot(n_classes),)),
            )
        else:
            feed = _DecodeFeed(raw, ids, batch, n_classes, iters, hw)
        perf = PerformanceListener(frequency=10 ** 9,
                                   warmup_iterations=warm)
        model.set_listeners(perf)
        reg = registry()
        h2d = reg.counter("dl4jtpu_h2d_bytes_total")
        dec_secs = reg.counter("dl4jtpu_device_decode_seconds_total")
        dec_batches = reg.counter("dl4jtpu_device_decode_batches_total")
        h0 = h2d.value(feed="raw") + h2d.value(feed="decoded")
        s0, b0 = dec_secs.value(), dec_batches.value()
        env = environment()
        saved = env.prefetch_depth
        saved_dd = env.device_decode
        env.prefetch_depth = depth
        if fused:
            # pin the flag: an inherited DL4J_TPU_DEVICE_DECODE=0 would
            # silently record host-path numbers in the fused columns
            env.device_decode = True
        try:
            model.fit(feed, epochs=1)
        finally:
            env.prefetch_depth = saved
            env.device_decode = saved_dd
        import jax as _jax

        _jax.block_until_ready(model.params)
        sps = perf.samples_per_sec()
        bps = perf.batches_per_sec()
        h2d_bytes = (h2d.value(feed="raw") + h2d.value(feed="decoded")
                     - h0)
        dec_n = dec_batches.value() - b0
        # performance attribution (observe/cost.py): the train program's
        # XLA-analyzed model FLOPs, the MFU that throughput achieves
        # against the n-device peak, and the program's roofline class
        from deeplearning4j_tpu.observe import cost as _cost

        flops = mfu = roofline = None
        train_recs = [r for r in _cost.analyze_model(model)
                      if r.kind.startswith("train")]
        if train_recs:
            rec = max(train_recs, key=lambda r: r.dispatches)
            flops = rec.flops
            roofline = rec.roofline()
            if flops and bps:
                pk_f, _pk_b = _cost.peaks()
                per_dev = pk_f / max(1, _jax.local_device_count())
                mfu = round(flops * bps / (per_dev * n), 4)
        return {
            "samples_per_sec": round(sps, 1),
            "step_latency_ms": round(1000.0 / bps, 3) if bps else None,
            "etl_wait_fraction": round(perf.etl_wait_fraction(), 3),
            "h2d_mb_per_step": round(h2d_bytes / iters / 1e6, 3),
            "device_decode_ms": (
                round((dec_secs.value() - s0) / dec_n * 1000.0, 3)
                if dec_n else None
            ),
            "model_flops_per_step": flops,
            "mfu": mfu,
            "roofline": roofline,
        }

    for r in fixed_rows:
        n = r["devices"]
        piped = measure_fit(n, fixed_batch, depth=2)
        serial = measure_fit(n, fixed_batch, depth=0)
        fused = measure_fit(n, fixed_batch, depth=2, fused=True)
        r["pipelined"] = piped["samples_per_sec"]
        r["pipelined_step_latency_ms"] = piped["step_latency_ms"]
        r["serial_fit"] = serial["samples_per_sec"]
        r["serial_step_latency_ms"] = serial["step_latency_ms"]
        r["serial_etl_wait_fraction"] = serial["etl_wait_fraction"]
        r["pipelined_etl_wait_fraction"] = piped["etl_wait_fraction"]
        r["pipelined_speedup"] = (
            round(piped["samples_per_sec"] / serial["samples_per_sec"], 3)
            if serial["samples_per_sec"] else None
        )
        # device-compiled decode columns: the host's per-batch job is a
        # raw-byte slice; normalize/resize/one-hot run inside the step
        # program (datavec/device.py)
        r["fused"] = fused["samples_per_sec"]
        r["fused_step_latency_ms"] = fused["step_latency_ms"]
        r["fused_etl_wait_fraction"] = fused["etl_wait_fraction"]
        r["fused_speedup_vs_pipelined"] = (
            round(fused["samples_per_sec"] / piped["samples_per_sec"], 3)
            if piped["samples_per_sec"] else None
        )
        r["h2d_mb_per_step"] = fused["h2d_mb_per_step"]
        r["h2d_mb_per_step_host_decoded"] = piped["h2d_mb_per_step"]
        r["device_decode_ms"] = fused["device_decode_ms"]
        # where the FLOPs go: the train program's XLA model FLOPs, the
        # MFU the pipelined row achieves, and its roofline class
        r["model_flops_per_step"] = piped["model_flops_per_step"]
        r["mfu"] = piped["mfu"]
        r["roofline"] = piped["roofline"]
        print(f"[scaling pipelined] devices={n} "
              f"pipelined={r['pipelined']} serial={r['serial_fit']} "
              f"speedup={r['pipelined_speedup']} fused={r['fused']} "
              f"fused_vs_pipelined={r['fused_speedup_vs_pipelined']}",
              file=sys.stderr)

    # ZeRO-1 sharded weight update columns (ISSUE 10): opt state + the
    # update computation sharded over the data axis vs the classic
    # replicated DP update, at every mesh width.  The proxy is an MLP
    # whose dims divide every sweep width (784/512/256) — ZeRO-1
    # shards only evenly-divisible dims (parallel/strategy
    # .zero1_spec_for_leaf), and LeNet's conv shapes divide nothing.
    from deeplearning4j_tpu.nn import Adam as _Adam
    from deeplearning4j_tpu.nn.activations import Activation as _Act
    from deeplearning4j_tpu.nn.conf import (
        Dense as _Dense,
        InputType as _InputType,
        NeuralNetConfiguration as _NNConf,
        OutputLayer as _OutputLayer,
    )
    from deeplearning4j_tpu.nn.losses import Loss as _Loss
    from deeplearning4j_tpu.parallel import zero as zero_mod

    def make_zero_model():
        conf = (
            _NNConf.builder()
            .seed(7)
            .updater(_Adam(1e-3))
            .activation(_Act.RELU)
            .list()
            .layer(_Dense(n_out=512))
            .layer(_Dense(n_out=256))
            .layer(_OutputLayer(n_out=n_cls, loss=_Loss.MCXENT,
                                activation=_Act.SOFTMAX))
            .set_input_type(_InputType.convolutional(*in_shape))
            .build()
        )
        from deeplearning4j_tpu.models import SequentialModel

        return SequentialModel(conf).init()

    def measure_zero(n: int, batch: int) -> dict:
        out = {}
        zbatches = [
            DataSet(
                rng.normal(0, 1, (batch,) + in_shape).astype(np.float32),
                np.eye(n_cls, dtype=np.float32)[
                    rng.integers(0, n_cls, batch)
                ],
            )
            for _ in range(2)
        ]
        for mode, stage in (("replicated", 0), ("zero1", 1)):
            model = make_zero_model()
            distribute(model, ParallelConfig(data=n, zero=stage),
                       devices=devices[:n])
            warm, iters = (2, 6) if QUICK else (3, 16)
            sps, _meta = _timed_fit(model, zbatches, warmup=warm,
                                    iters=iters)
            out[mode] = {
                "samples_per_sec": sps,
                "opt_bytes": zero_mod.opt_state_bytes_per_replica(
                    model.opt_state
                ),
                "update_ms": zero_mod.measure_update_seconds(
                    model, iters=2 if QUICK else 5
                ) * 1e3,
            }
        return out

    for r in fixed_rows:
        n = r["devices"]
        zres = measure_zero(n, fixed_batch)
        rep_m, z_m = zres["replicated"], zres["zero1"]
        r["zero1_samples_per_sec"] = round(z_m["samples_per_sec"], 1)
        r["replicated_samples_per_sec"] = round(
            rep_m["samples_per_sec"], 1
        )
        r["zero1_speedup"] = (
            round(z_m["samples_per_sec"] / rep_m["samples_per_sec"], 3)
            if rep_m["samples_per_sec"] else None
        )
        r["peak_opt_state_bytes_per_replica"] = z_m["opt_bytes"]
        r["peak_opt_state_bytes_per_replica_replicated"] = rep_m[
            "opt_bytes"
        ]
        r["update_time_ms"] = round(z_m["update_ms"], 3)
        r["update_time_ms_replicated"] = round(rep_m["update_ms"], 3)
        print(f"[scaling zero1] devices={n} "
              f"opt_bytes {rep_m['opt_bytes']}→{z_m['opt_bytes']} "
              f"update_ms {r['update_time_ms_replicated']}→"
              f"{r['update_time_ms']} speedup={r['zero1_speedup']}",
              file=sys.stderr)

    # host-input overlap: can the async host pipeline feed faster than the
    # device consumes?  (AsyncDataSetIterator producer-thread rate vs the
    # measured step rate at full mesh width.)
    model, per_chip_batch, hw, n_classes = make_model()
    batch = per_chip_batch * n_max
    x = rng.normal(0, 1, (batch * 8,) + hw).astype(np.float32)
    y = np.eye(n_classes, dtype=np.float32)[
        rng.integers(0, n_classes, batch * 8)
    ]
    feed = AsyncDataSetIterator(
        NumpyDataSetIterator(x, y, batch_size=batch), device_put=False
    )
    t0 = time.perf_counter()
    fed = sum(b.num_examples for b in feed)
    feed_rate = fed / (time.perf_counter() - t0)
    step_rate = rows[-1]["samples_per_sec"]

    out = {
        # schema 2 (ISSUE 8): fixed-work rows grew model_flops_per_step /
        # mfu / roofline (XLA cost analysis via observe/cost.py) and the
        # document carries environment provenance
        # schema 3 (ISSUE 10): fixed-work rows grew the ZeRO-1 columns
        # (peak_opt_state_bytes_per_replica[_replicated] /
        # update_time_ms[_replicated] / zero1_speedup)
        "schema": "bench-scaling/3",
        "metric": "DP scaling: per-chip samples/sec at 1..N devices",
        "env": _env_provenance(),
        "note": None if on_tpu else (
            "virtual CPU devices share one host's cores, so per-chip rate "
            "FALLS with n — this run validates the distribute()/GSPMD "
            "mechanism and the efficiency table, not hardware scaling"
        ),
        "platform": devices[0].platform,
        "device_kind": str(getattr(devices[0], "device_kind", "")),
        "model": "resnet50_cg" if on_tpu else "lenet_mnist_mln (CPU proxy)",
        "rows": rows,
        "fixed_work_rows": fixed_rows,
        "fixed_work_note": (
            "global batch held constant across n: aggregate work is fixed, "
            "so mechanism_efficiency = sps(n)/sps(1) isolates the "
            "distribute()/GSPMD partitioning+collective overhead — "
            "meaningful even when virtual devices share one host's cores "
            "(the weak-scaling rows' efficiency is not, there)"
        ),
        "pipelined_note": (
            "pipelined/serial_fit columns run the REAL fit() loop over a "
            "decode-per-next() host feed with flags.prefetch_depth=2 "
            "(PrefetchIterator overlaps pull+stage with compute; donated "
            "step buffers) vs 0 (serial) — pipelined_speedup is the "
            "software-pipelining win; the base fixed-work rows pre-stage "
            "batches and hide the input pipeline entirely"
        ),
        "fused_note": (
            "fused columns feed the SAME camera-wire bytes through the "
            "device-compiled data pipeline (datavec/device.py): the "
            "transform chain (normalize + mean-pool resize + one-hot) "
            "is lowered INTO the step program, the host stages raw "
            "uint8, and the per-step host decode cost disappears — "
            "fused_speedup_vs_pipelined is the win over merely HIDING "
            "the decode (PR 5), largest where the producer thread has "
            "no spare core; device_decode_ms is the calibrated "
            "standalone cost of the decode stage, h2d_mb_per_step the "
            "raw-byte transfer vs h2d_mb_per_step_host_decoded"
        ),
        "zero1_note": (
            "zero1 columns compare distribute(zero=1) — opt state and "
            "the weight update sharded over the data axis "
            "(reduce-scatter grads -> per-shard update -> all-gather "
            "params, parallel/zero.py) — against the replicated DP "
            "update on an MLP proxy whose dims divide every sweep "
            "width; peak_opt_state_bytes_per_replica is the per-chip "
            "opt-state footprint (sharded ~1/n of replicated), "
            "update_time_ms the calibrated standalone update-epilogue "
            "cost, zero1_speedup the whole-step throughput ratio"
        ),
        "flops_note": (
            "model_flops_per_step is the train step program's XLA "
            "cost_analysis flops (forward + param grads + updater; "
            "dead-coded input grads excluded by XLA); mfu is the "
            "pipelined row's achieved FLOP/s over the n-device peak "
            "from observe/cost.py's device-kind table (CPU peak is a "
            "nominal); roofline "
            "classifies the program's arithmetic intensity against the "
            "machine ridge point"
        ),
        "warmup_steps": WARMUP_STEPS,
        "input_pipeline": {
            "async_feed_samples_per_sec": round(feed_rate, 1),
            "step_samples_per_sec": step_rate,
            "feed_covers_step": feed_rate > step_rate,
        },
    }
    if not QUICK:
        # quick smoke runs (the tier-1 gate) must not clobber the
        # committed full-run table with low-iteration numbers
        path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "BENCH_SCALING.json")
        with open(path, "w") as f:
            json.dump(out, f, indent=1)
    print(json.dumps(out))


def _spearman(xs, ys) -> float | None:
    """Spearman rank correlation (Pearson on ranks, average ties) —
    the predicted-vs-measured plan-quality statistic, stdlib-only."""
    n = len(xs)
    if n < 2 or len(ys) != n:
        return None

    def ranks(vs):
        order = sorted(range(n), key=lambda i: vs[i])
        r = [0.0] * n
        i = 0
        while i < n:
            j = i
            while j + 1 < n and vs[order[j + 1]] == vs[order[i]]:
                j += 1
            avg = (i + j) / 2.0 + 1.0
            for k in range(i, j + 1):
                r[order[k]] = avg
            i = j + 1
        return r

    rx, ry = ranks(xs), ranks(ys)
    mx = sum(rx) / n
    my = sum(ry) / n
    num = sum((a - mx) * (b - my) for a, b in zip(rx, ry))
    dx = sum((a - mx) ** 2 for a in rx) ** 0.5
    dy = sum((b - my) ** 2 for b in ry) ** 0.5
    if dx == 0 or dy == 0:
        return None
    return num / (dx * dy)


def bench_plan() -> None:
    """bench.py --plan: plan-quality table for the autosharding planner
    (parallel/planner.py).  At each mesh width n the planner prices its
    candidate set DISPATCH-FREE (compile-stats-asserted: zero backend
    compiles, zero step executions during planning), then every priced
    candidate is actually measured on the fixed-work MLP — the table
    records the planner's pick vs the best and worst hand config, the
    predicted-vs-measured rank correlation, and the ZeRO-2 grad+opt
    state bytes/replica.  Run:  python bench.py --plan
    """
    n_target = int(os.environ.get("BENCH_PLAN_DEVICES", "8"))
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={n_target}"
    ).strip()
    import jax

    if os.environ.get("BENCH_PLAN_TPU", "") in ("", "0"):
        jax.config.update("jax_platforms", "cpu")
    devices = jax.devices()
    n_max = min(len(devices), n_target)

    import numpy as np

    from deeplearning4j_tpu.data.dataset import DataSet
    from deeplearning4j_tpu.nn import Adam
    from deeplearning4j_tpu.nn.activations import Activation
    from deeplearning4j_tpu.nn.conf import (
        Dense,
        InputType,
        NeuralNetConfiguration,
        OutputLayer,
    )
    from deeplearning4j_tpu.nn.losses import Loss
    from deeplearning4j_tpu.observe import cost
    from deeplearning4j_tpu.parallel import distribute, plan
    from deeplearning4j_tpu.parallel import zero as zero_mod
    from deeplearning4j_tpu.runtime import compile_stats

    n_in, n_cls = 64, 8
    fixed_batch = 256          # divides every width in the sweep

    def make_model():
        conf = (
            NeuralNetConfiguration.builder()
            .seed(7)
            .updater(Adam(1e-3))
            .activation(Activation.RELU)
            .list()
            .layer(Dense(n_out=512))
            .layer(Dense(n_out=256))
            .layer(OutputLayer(n_out=n_cls, loss=Loss.MCXENT,
                               activation=Activation.SOFTMAX))
            .set_input_type(InputType.feed_forward(n_in))
            .build()
        )
        from deeplearning4j_tpu.models import SequentialModel

        return SequentialModel(conf).init()

    rng = np.random.default_rng(0)
    batches = [
        DataSet(
            rng.normal(0, 1, (fixed_batch, n_in)).astype(np.float32),
            np.eye(n_cls, dtype=np.float32)[
                rng.integers(0, n_cls, fixed_batch)
            ],
        )
        for _ in range(2)
    ]

    widths = []
    n = 1
    while n <= n_max:
        widths.append(n)
        n *= 2
    if QUICK:
        widths = widths[:2]

    def measure(config, devs) -> tuple[float, object]:
        m = make_model()
        distribute(m, config, devices=devs)
        # plan quality is a RANKING claim — under-warmed measurements
        # (first-dispatch tax, cold thread pools) reorder close
        # candidates, so even quick mode pays for steady state
        warm, iters = (3, 10) if QUICK else (6, 32)
        sps, _meta = _timed_fit(m, batches, warmup=warm, iters=iters)
        return fixed_batch / sps, m      # measured step seconds, model

    rows = []
    for n in widths:
        planner_model = make_model()
        before = compile_stats.snapshot()
        report = plan(planner_model, n_devices=n,
                      batch_size=fixed_batch)
        spent = compile_stats.snapshot() - before
        # the dispatch-free contract, asserted: planning lowered the
        # step program abstractly — no backend compile, no execution
        assert spent.backend_compiles == 0, (
            f"planning compiled: {spent.backend_compiles}"
        )
        plan_dispatches = sum(
            r.dispatches for r in cost.registry().programs()
            if r.owner_ref() is planner_model
        )
        assert plan_dispatches == 0, (
            f"planning dispatched {plan_dispatches} programs"
        )

        measured = []
        for cand in report.priced:
            step_s, m = measure(cand.config, devices[:cand.devices_used])
            entry = {
                "config": cand.label(),
                "zero": cand.config.zero or 0,
                "data": cand.config.data,
                "devices_used": cand.devices_used,
                "predicted_ms": round(
                    cand.predicted_step_seconds * 1e3, 3
                ),
                "measured_ms": round(step_s * 1e3, 3),
            }
            if (cand.config.zero or 0) == 2:
                entry["opt_bytes_per_replica"] = (
                    zero_mod.opt_state_bytes_per_replica(m.opt_state)
                )
                entry["grad_bytes_per_replica"] = (
                    zero_mod.grad_state_bytes_per_replica(m)
                )
            measured.append(entry)

        pick_label = report.pick_candidate().label()
        picked = next(e for e in measured if e["config"] == pick_label)
        best = min(measured, key=lambda e: e["measured_ms"])
        worst = max(measured, key=lambda e: e["measured_ms"])
        corr = _spearman(
            [e["predicted_ms"] for e in measured],
            [e["measured_ms"] for e in measured],
        )
        z2 = next((e for e in measured
                   if e["zero"] == 2 and e["data"] == n), None)
        rep0 = next((e for e in measured
                     if e["zero"] == 0 and e["data"] == n
                     and e["devices_used"] == n), None)
        rep_model = None
        if z2 is not None:
            # the 1/n claim needs the replicated footprint at the same
            # width next to it
            from deeplearning4j_tpu.parallel import ParallelConfig

            rep_model = make_model()
            distribute(rep_model,
                       ParallelConfig(data=n, zero=0),
                       devices=devices[:n])
        row = {
            "devices": n,
            "global_batch": fixed_batch,
            "candidates": measured,
            "pick": pick_label,
            "pick_measured_ms": picked["measured_ms"],
            "pick_predicted_ms": picked["predicted_ms"],
            "best_config": best["config"],
            "best_measured_ms": best["measured_ms"],
            "worst_config": worst["config"],
            "worst_measured_ms": worst["measured_ms"],
            "pick_vs_best": round(
                picked["measured_ms"] / best["measured_ms"], 3
            ) if best["measured_ms"] else None,
            "rank_correlation": round(corr, 3) if corr is not None else None,
            "zero2_opt_bytes_per_replica": (
                z2["opt_bytes_per_replica"] if z2 else None
            ),
            "zero2_grad_bytes_per_replica": (
                z2["grad_bytes_per_replica"] if z2 else None
            ),
            "replicated_opt_bytes_per_replica": (
                zero_mod.opt_state_bytes_per_replica(rep_model.opt_state)
                if rep_model is not None else None
            ),
            "replicated_grad_bytes_per_replica": (
                zero_mod.grad_state_bytes_per_replica(rep_model)
                if rep_model is not None else None
            ),
            "replicated_measured_ms": (
                rep0["measured_ms"] if rep0 else None
            ),
            "planning": {
                "plan_seconds": round(report.plan_seconds, 4),
                "priced": len(report.priced),
                "rejected": len(report.rejected),
                "backend_compiles": spent.backend_compiles,
                "step_dispatches": plan_dispatches,
            },
        }
        rows.append(row)
        print(
            f"[plan] n={n} pick={pick_label!r} "
            f"{picked['measured_ms']}ms best={best['config']!r} "
            f"{best['measured_ms']}ms worst={worst['config']!r} "
            f"{worst['measured_ms']}ms corr={row['rank_correlation']} "
            f"plan={report.plan_seconds * 1e3:.0f}ms",
            file=sys.stderr,
        )

    out = {
        "schema": "bench-plan/1",
        "metric": ("autosharding plan quality: planner pick vs "
                   "best/worst hand config per mesh width"),
        "env": _env_provenance(),
        "model": "mlp_fixed_work (64->512->256->8, Adam)",
        "global_batch": fixed_batch,
        "rows": rows,
        "note": (
            "fixed global batch across widths; on the virtual CPU mesh "
            "devices share one host's cores, so the planner's capacity "
            "model holds the aggregate peak constant across widths and "
            "narrow meshes win — more virtual devices buy collective + "
            "partition overhead, not compute.  On real TPU chips the "
            "per-device peaks are independent and the trade flips to "
            "wide meshes.  rank_correlation is Spearman between the "
            "planner's predicted step seconds and the measured step "
            "latency over the priced candidate set; planning is "
            "dispatch-free (backend_compiles/step_dispatches asserted "
            "zero).  zero2_*_bytes_per_replica are the persistently "
            "sharded grad accumulator + inner opt state next to their "
            "replicated twins (~1/n)"
        ),
        "quick": QUICK,
    }
    if not QUICK:
        # quick smoke runs (the tier-1 gate) must not clobber the
        # committed full-run table with low-iteration numbers
        path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "BENCH_PLAN.json")
        with open(path, "w") as f:
            json.dump(out, f, indent=1)
    print(json.dumps(out))


def _headline_value(kind, measured):
    """The canonical `value` field carries a genuine TPU measurement or
    null — NEVER a CPU number (a scoreboard that can silently swap in
    CPU numbers will eventually be read wrong).  The BENCH_FORCE_CPU
    plumbing mode's rate stays available under extra.*."""
    return measured if "tpu" in str(kind).lower() else None


def bench_chaos() -> None:
    """bench.py --chaos: one fixed fit under a composite seeded fault
    plan — a simulated hang (device.sync delay), a decode failure
    (data.decode raise) and a NaN-poisoned batch (data.decode corrupt)
    — with the full self-healing stack attached (StepWatchdog +
    RecoveryPolicy over a CheckpointStore).  Records steps-to-recover
    and the recovered-step fraction into BENCH_CHAOS.json.

    Runs on CPU by default (the subject is recovery control flow, not
    device throughput); BENCH_CHAOS_PLATFORM overrides."""
    import tempfile

    import jax

    jax.config.update(
        "jax_platforms", os.environ.get("BENCH_CHAOS_PLATFORM", "cpu")
    )
    import numpy as np

    from deeplearning4j_tpu.data.dataset import DataSet
    from deeplearning4j_tpu.data.iterator import DataSetIterator
    from deeplearning4j_tpu.models import SequentialModel
    from deeplearning4j_tpu.nn.conf import (
        Dense, InputType, NeuralNetConfiguration, OutputLayer,
    )
    from deeplearning4j_tpu.observe.metrics import registry
    from deeplearning4j_tpu.runtime import faults
    from deeplearning4j_tpu.runtime.flags import environment
    from deeplearning4j_tpu.train.checkpoint import CheckpointStore
    from deeplearning4j_tpu.train.listeners import TrainingListener
    from deeplearning4j_tpu.train.recovery import RecoveryPolicy

    total_batches = 28
    save_every = 4
    plan = ("device.sync:delay:nth=6,secs=0.4;"
            "data.decode:raise:nth=10,exc=runtime;"
            "data.decode:corrupt:nth=16")

    tmp = tempfile.mkdtemp(prefix="dl4jtpu-chaos-")
    os.environ.setdefault("DL4JTPU_CRASH_DIR", os.path.join(tmp, "crash"))
    env = environment()
    floor_before = env.watchdog_floor_s
    env.watchdog_floor_s = 0.06      # the 0.4s injected hang must escalate

    conf = (
        NeuralNetConfiguration.builder().seed(7).list()
        .layer(Dense(n_out=32)).layer(OutputLayer(n_out=4))
        .set_input_type(InputType.feed_forward(16)).build()
    )
    model = SequentialModel(conf).init()
    store = CheckpointStore(os.path.join(tmp, "ckpts"), keep_last=3)

    class _Saver(TrainingListener):
        def iteration_done(self, model, iteration, epoch, score):
            if iteration and iteration % save_every == 0:
                store.save(model, step=iteration)

    model.add_listener(_Saver())
    policy = RecoveryPolicy(
        store, skip_window=2, quarantine_dir=os.path.join(tmp, "quarantine"),
    ).attach(model)

    class _Feed(DataSetIterator):
        def __init__(self, n, seed=11):
            self.n, self.seed = n, seed

        def reset(self):
            pass

        def __iter__(self):
            rng = np.random.default_rng(self.seed)
            for _ in range(self.n):
                x = rng.normal(size=(16, 16)).astype(np.float32)
                y = np.eye(4, dtype=np.float32)[rng.integers(0, 4, 16)]
                yield DataSet(x, y)

    reg = registry()
    # warmup fit BEFORE arming: the watchdog's latency EWMA must decay
    # past the compile-step spike so the injected 0.4s hang actually
    # blows the deadline (same reason every bench floors warmup steps)
    warmup_batches = max(16, WARMUP_STEPS)
    model.fit(_Feed(warmup_batches, seed=5), epochs=1)
    warmup_iters = int(model.iteration)
    t0 = time.time()
    faults.arm(plan)
    try:
        model.fit(_Feed(total_batches), epochs=1)
    finally:
        faults.disarm()
        env.watchdog_floor_s = floor_before
    wall = time.time() - t0
    # fresh process: the post-fit totals ARE the chaos run's totals
    metrics = {
        name: reg.counter(name).snapshot()
        for name in (
            "dl4jtpu_watchdog_stalls_total",
            "dl4jtpu_quarantined_batches_total",
            "dl4jtpu_recovery_events_total",
        )
    }

    rollback = next(
        (e for e in policy.events if e["kind"] == "rollback"), None
    )
    steps_to_recover = (
        rollback["from_iteration"] - rollback["restored_iteration"]
        + rollback["skip_window"] if rollback else None
    )
    final_score = float(model.score_value)
    # finite means NaN AND Inf screened: an Inf score is just as
    # diverged, and json.dump would write it as the non-standard
    # `Infinity` literal strict parsers reject
    score_ok = math.isfinite(final_score)
    row = {
        "bench": "chaos",
        "plan": plan,
        "total_batches": total_batches,
        "final_iteration": int(model.iteration),
        "final_score": final_score if score_ok else None,
        "completed": score_ok,
        "rollbacks": policy.rollbacks,
        "quarantined": policy.quarantined,
        "lr_scale": policy.lr_scale,
        "steps_to_recover": steps_to_recover,
        # unique optimizer steps retained / batches fed — the cost of
        # chaos in lost work (skips + rollback rewind + quarantines)
        "recovered_step_fraction": round(
            (model.iteration - warmup_iters) / total_batches, 3
        ),
        "watchdog_events": [
            (e["stage"], e["stalled_s"])
            for e in (model._watchdog.events if model._watchdog else [])
        ],
        "metrics": metrics,
        "wall_s": round(wall, 2),
    }
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "BENCH_CHAOS.json")
    with open(path, "w") as f:
        json.dump(row, f, indent=1)
    print(f"[bench] chaos row -> {path}", file=sys.stderr)
    print(json.dumps({
        "metric": "chaos fit recovered-step fraction "
                  "(hang + NaN step + poison batch, seeded plan)",
        "value": row["recovered_step_fraction"],
        "unit": "fraction",
        "extra": {k: row[k] for k in (
            "completed", "rollbacks", "quarantined", "steps_to_recover",
            "lr_scale", "wall_s",
        )},
    }))


def _serving_closed_loop(target, clients, duration_s, deadline_s, n_in):
    """Closed-loop load against anything speaking ``infer(x,
    deadline_s=...)`` — an `InferenceServer` or a `ServingFleet` front
    door.  Every request's outcome is recorded from the CLIENT side:
    ok/shed/error/timeout must add up to issued, which is the
    no-silent-drops proof shared by --serving and --serving-fleet."""
    import threading

    import numpy as np

    from deeplearning4j_tpu.serving import (
        ServingError, ServingRejected, ServingTimeout,
    )

    stop = threading.Event()
    lock = threading.Lock()
    tally = {"issued": 0, "ok": 0, "errors": 0, "timeouts": 0}
    shed: dict = {}
    lats: list = []

    def client(cid):
        rng = np.random.default_rng(cid)
        local_lats = []
        while not stop.is_set():
            x = rng.normal(size=(n_in,)).astype(np.float32)
            t0 = time.monotonic()
            outcome, reason = "ok", None
            try:
                target.infer(x, deadline_s=deadline_s)
                local_lats.append(time.monotonic() - t0)
            except ServingRejected as e:
                outcome, reason = "shed", e.reason
            except ServingTimeout:
                outcome = "timeouts"
            except ServingError:
                outcome = "errors"
            with lock:
                tally["issued"] += 1
                if outcome == "ok":
                    tally["ok"] += 1
                elif outcome == "shed":
                    shed[reason] = shed.get(reason, 0) + 1
                else:
                    tally[outcome] += 1
        with lock:
            lats.extend(local_lats)

    threads = [
        threading.Thread(target=client, args=(i,)) for i in range(clients)
    ]
    t0 = time.time()
    for t in threads:
        t.start()
    time.sleep(duration_s)
    stop.set()
    for t in threads:
        t.join(30)
    wall = time.time() - t0
    lats.sort()

    def pct(p):
        return (
            round(lats[min(len(lats) - 1, int(p * len(lats)))] * 1000, 3)
            if lats else None
        )

    return {
        **tally,
        "shed_by_reason": shed,
        "shed": sum(shed.values()),
        "achieved_rps": round(tally["ok"] / wall, 1),
        "p50_ms": pct(0.50),
        "p99_ms": pct(0.99),
        "wall_s": round(wall, 2),
    }


def _time_jitted(fn, *args, reps=15):
    """ms/call of a jitted callable, post-compile."""
    import jax

    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps * 1000.0


def _bench_serving_quantized(run_loop) -> dict:
    """Phase 5 of --serving: int8 PTQ vs f32 on the SAME serving-shaped
    MLP — measured throughput at equal client counts, the
    evaluation-parity gate, the per-shape dequant-matmul kernel table
    (pallas/blocked vs the XLA dequantize-then-dot baseline), and the
    roofline-MODELED TPU speedup.

    The measured CPU rows are honest and therefore modest: weight-only
    int8 pays on memory-bandwidth-bound accelerators, and on this CPU
    XLA's dequantize materialization gives back what the smaller
    weights save (sustained random access is DRAM-latency-bound — see
    docs/quantization.md "What int8 buys, where").  The ≥1.2x serving
    claim is carried by the modeled row, computed from the cost
    registry's int8-adjusted params bytes against the published TPU
    v5e peaks, and must be re-measured when this bench runs on real
    TPU hardware (BENCH_SERVING_PLATFORM=tpu)."""
    import numpy as np

    from deeplearning4j_tpu.models import SequentialModel
    from deeplearning4j_tpu.nn.conf import (
        Dense, InputType, NeuralNetConfiguration, OutputLayer,
    )
    from deeplearning4j_tpu.observe.cost import PEAKS_BY_DEVICE_KIND
    from deeplearning4j_tpu.ops.dequant_matmul import (
        dequant_matmul, select_impl,
    )
    from deeplearning4j_tpu.quant import (
        parity_check, quantize, quantized_bytes,
    )
    from deeplearning4j_tpu.quant.qtensor import quantize_array
    from deeplearning4j_tpu.serving import InferenceServer, ServingConfig

    from deeplearning4j_tpu.nn.updaters import Adam

    n_in, hidden, n_out = (64, 256, 8) if QUICK else (256, 1024, 8)
    conf = (
        NeuralNetConfiguration.builder().seed(14).updater(Adam(5e-3))
        .list()
        .layer(Dense(n_out=hidden)).layer(Dense(n_out=hidden))
        .layer(OutputLayer(n_out=n_out))
        .set_input_type(InputType.feed_forward(n_in)).build()
    )
    f32_model = SequentialModel(conf).init()
    # brief fit on separable blobs: the parity gate (top-1 delta <= 1%)
    # is a statement about models with real decision margins — argmax
    # of random-init logits flips on rounding noise and gates nothing
    rng = np.random.default_rng(14)
    from deeplearning4j_tpu.data.dataset import DataSet

    y_tr = rng.integers(0, n_out, 512)
    x_tr = rng.normal(0, 0.4, (512, n_in)).astype(np.float32)
    x_tr[:, :n_out] += np.eye(n_out, dtype=np.float32)[y_tr] * 2.0
    oh = np.eye(n_out, dtype=np.float32)[y_tr]
    for _ in range(1 if QUICK else 3):
        for i in range(0, 512, 64):
            f32_model.fit_batch(DataSet(x_tr[i:i + 64], oh[i:i + 64]))
    q_model = quantize(f32_model)
    y_ev = rng.integers(0, n_out, 128 if QUICK else 512)
    x_ev = rng.normal(0, 0.4, (len(y_ev), n_in)).astype(np.float32)
    x_ev[:, :n_out] += np.eye(n_out, dtype=np.float32)[y_ev] * 2.0
    parity = parity_check(f32_model, q_model, x_ev, labels=y_ev)
    qb = quantized_bytes(q_model.params)

    # measured: same client counts against both servers
    example = np.zeros((n_in,), np.float32)
    window = 0.6 if QUICK else 2.5
    curve = []
    for clients in ((2,) if QUICK else (4, 8)):
        rows = {}
        for label, model in (("f32", f32_model), ("int8", q_model)):
            srv = InferenceServer(model, ServingConfig(
                max_batch=8, max_queue=64, linger_s=0.001,
            ))
            srv.warm_start(example)
            srv.start()
            rows[label] = run_loop(srv, clients, window, 2.0, n_in)
            srv.stop()
        curve.append({
            "clients": clients,
            "f32_rps": rows["f32"]["achieved_rps"],
            "int8_rps": rows["int8"]["achieved_rps"],
            "f32_p99_ms": rows["f32"]["p99_ms"],
            "int8_p99_ms": rows["int8"]["p99_ms"],
            "speedup_vs_f32": (
                round(rows["int8"]["achieved_rps"]
                      / rows["f32"]["achieved_rps"], 3)
                if rows["f32"]["achieved_rps"] else None
            ),
        })

    # per-shape kernel table: every impl vs the XLA baseline
    import jax
    import jax.numpy as jnp

    shapes = (
        ((8, 256, 256),) if QUICK
        else ((8, 512, 512), (8, 2048, 2048), (1, 4096, 4096))
    )
    kernel_rows = []
    for (m, k, n) in shapes:
        x = jnp.asarray(
            rng.standard_normal((m, k)).astype(np.float32)
        )
        w = rng.standard_normal((k, n)).astype(np.float32)
        qt = quantize_array(w)
        wj = jnp.asarray(w)
        f32_ms = _time_jitted(jax.jit(lambda a, b: a @ b), x, wj)
        row = {
            "shape": [m, k, n],
            "f32_matmul_ms": round(f32_ms, 4),
            "selected": select_impl(m, k, n),
        }
        for impl in ("xla", "blocked", "pallas"):
            if impl == "pallas" and (m, k, n) != shapes[0]:
                continue       # interpret mode: numerics-speed only,
                               # time the smallest shape as evidence
            fn = jax.jit(
                functools.partial(dequant_matmul, impl=impl)
            )
            row[f"{impl}_ms"] = round(
                _time_jitted(fn, x, qt.q, qt.scale), 4
            )
        kernel_rows.append(row)

    # roofline-modeled TPU speedup off the int8-adjusted params bytes:
    # serving inference at small batch is weights-bandwidth-bound on
    # TPU (AI far below the ridge), so dispatch time ~ bytes / membw
    peak_flops, peak_bw = PEAKS_BY_DEVICE_KIND["TPU v5e"]
    batch = 8
    flops = 2.0 * batch * (n_in * hidden + hidden * hidden
                           + hidden * n_out)
    bytes_f32 = float(qb["f32_equiv_bytes"])
    bytes_int8 = float(qb["quantized_bytes"])
    t_f32 = max(flops / peak_flops, bytes_f32 / peak_bw)
    t_int8 = max(flops / peak_flops, bytes_int8 / peak_bw)
    modeled = {
        "reference_chip": "TPU v5e",
        "peak_flops": peak_flops,
        "peak_membw_bytes_per_s": peak_bw,
        "batch": batch,
        "flops_per_dispatch": flops,
        "weight_bytes_f32": bytes_f32,
        "weight_bytes_int8": bytes_int8,
        "arithmetic_intensity_f32": round(flops / bytes_f32, 3),
        "ridge_point": round(peak_flops / peak_bw, 1),
        "modeled_speedup": round(t_f32 / t_int8, 3),
        "note": "bandwidth-bound regime: dispatch ~ weight bytes / "
                "membw; int8+scales cut the streamed bytes ~3.9x",
    }

    return {
        "model": f"dense{hidden}x2-out{n_out} (in={n_in})",
        "scheme": "int8-perchannel-symmetric/1",
        "parity": parity,
        "bytes": qb,
        "curve": curve,
        "kernel_bench": kernel_rows,
        "modeled_tpu": modeled,
        "measured_platform_note": (
            "CPU rows measure the full serving path honestly; "
            "weight-only int8 is ~parity on this host (dequantize "
            "materialization ~cancels the byte savings; random access "
            "is latency-bound).  The >=1.2x serving economics claim "
            "is the modeled_tpu row until this bench runs on TPU."
        ),
    }


def bench_serving() -> None:
    """bench.py --serving: the serving plane under load and under chaos
    -> BENCH_SERVING.json.

    Three phases over one small model:

      1. **curve** — closed-loop throughput-vs-latency at increasing
         client counts (achieved rps, p50/p99, batch occupancy, sheds);
      2. **warm start** — a FRESH replica warm-starts its bucket set,
         and its first request must land within 1.5x of steady-state
         (the AOT-at-boot acceptance);
      3. **chaos** — a seeded fault plan injects admit delays, a burst
         of infer hangs (blowing the per-batch watchdog deadline and
         tripping the breaker) and a torn hot-swap push, under an
         overload of short-deadline clients against a small queue.  The
         server must complete the run: every overloaded request is shed
         with an explicit rejection (client-side accounting proves no
         silent drops), the breaker trips AND recovers, a good swap
         installs after the torn one rolls back, and post-chaos p99
         returns to within 2x of the unfaulted baseline.

    CPU by default (the subject is the serving control plane, not
    device throughput); BENCH_SERVING_PLATFORM overrides.  Quick mode
    (BENCH_QUICK=1) shrinks the windows and does NOT rewrite the
    committed BENCH_SERVING.json."""
    import tempfile
    import threading

    import jax

    jax.config.update(
        "jax_platforms", os.environ.get("BENCH_SERVING_PLATFORM", "cpu")
    )
    import numpy as np

    from deeplearning4j_tpu.models import SequentialModel
    from deeplearning4j_tpu.nn.conf import (
        Dense, InputType, NeuralNetConfiguration, OutputLayer,
    )
    from deeplearning4j_tpu.runtime import faults
    from deeplearning4j_tpu.serving import (
        InferenceServer, ServingConfig, weights_checksum,
    )

    os.environ.setdefault(
        "DL4JTPU_CRASH_DIR",
        os.path.join(tempfile.mkdtemp(prefix="dl4jtpu-serving-"), "crash"),
    )
    n_in, n_out = 16, 4
    conf = (
        NeuralNetConfiguration.builder().seed(7).list()
        .layer(Dense(n_out=32)).layer(OutputLayer(n_out=n_out))
        .set_input_type(InputType.feed_forward(n_in)).build()
    )
    example = np.zeros((n_in,), np.float32)

    def make_server(max_queue=64):
        model = SequentialModel(conf).init()
        return InferenceServer(model, ServingConfig(
            max_batch=8, max_queue=max_queue, linger_s=0.001,
            breaker_threshold=3, breaker_probe_after_s=0.2,
        ))

    def run_load(srv, clients, duration_s, deadline_s):
        return _serving_closed_loop(srv, clients, duration_s, deadline_s,
                                    n_in)

    window = 0.6 if QUICK else 2.5
    client_points = (2, 8) if QUICK else (1, 2, 4, 8, 16)

    # -- phase 1: throughput-vs-latency curve ------------------------------
    srv = make_server()
    srv.warm_start(example)
    srv.start()
    curve = []
    for clients in client_points:
        srv.reset_latency_window()
        row = run_load(srv, clients, window, deadline_s=2.0)
        row["clients"] = clients
        row["batch_occupancy"] = srv.stats()["batch_occupancy"]
        curve.append(row)
        print(f"[bench] serving curve clients={clients}: "
              f"{json.dumps(row)}", file=sys.stderr)

    # -- phase 2: AOT warm start on a FRESH replica ------------------------
    replica = make_server()
    warmed = replica.warm_start(example)
    replica.start()
    t0 = time.monotonic()
    replica.infer(example, deadline_s=30.0)
    first_ms = (time.monotonic() - t0) * 1000.0
    steady = []
    for _ in range(40 if QUICK else 200):
        t0 = time.monotonic()
        replica.infer(example, deadline_s=30.0)
        steady.append((time.monotonic() - t0) * 1000.0)
    steady.sort()
    steady_p50 = steady[len(steady) // 2]
    warm_row = {
        "warmed_programs": len(warmed),
        "first_request_ms": round(first_ms, 3),
        "steady_p50_ms": round(steady_p50, 3),
        "first_request_ratio": round(first_ms / steady_p50, 3),
    }
    replica.stop()
    print(f"[bench] serving warm start: {json.dumps(warm_row)}",
          file=sys.stderr)

    # -- phase 3: chaos ----------------------------------------------------
    # a burst of three CONSECUTIVE infer hangs (nth clauses share the
    # site's consult counter) blows the shrunken per-batch deadline and
    # trips the threshold-3 breaker; admit delays slow the front door;
    # the first hot-swap push is torn and must roll back
    hang_at = 8 if QUICK else 20
    plan = (
        "serving.admit:delay:every=5,secs=0.01;"
        f"serving.infer:delay:nth={hang_at},secs=0.3;"
        f"serving.infer:delay:nth={hang_at + 1},secs=0.3;"
        f"serving.infer:delay:nth={hang_at + 2},secs=0.3;"
        "serving.hotswap:truncate:nth=1"
    )
    chaos_srv = make_server(max_queue=8)
    chaos_srv.warm_start(example)
    chaos_srv.start()
    baseline = run_load(chaos_srv, 4, window, deadline_s=2.0)
    model = chaos_srv.model
    good_params = jax.tree.map(lambda a: a + 0.01, model.params)
    chaos_srv.config.dispatch_timeout_s = 0.05
    chaos_srv._watchdog.floor_s = 0.05
    faults.arm(plan)
    swap_results = {}
    try:
        # overload: 12 short-deadline clients against a queue of 8
        loader = threading.Thread(
            target=lambda: swap_results.update(
                chaos_window=run_load(
                    chaos_srv, 12, window * 2, deadline_s=0.08,
                )
            )
        )
        loader.start()
        time.sleep(window * 0.5)
        swap_results["torn_push_installed"] = chaos_srv.push_weights(
            jax.tree.map(lambda a: a * 2.0, model.params)
        )
        loader.join(120)
    finally:
        faults.disarm()
        chaos_srv.config.dispatch_timeout_s = 10.0
        chaos_srv._watchdog.floor_s = 10.0
    # after the storm: a clean push must install...
    swap_results["good_push_installed"] = chaos_srv.push_weights(
        good_params, checksum=weights_checksum(good_params),
    )
    # ...the breaker must close (ride through the probe window)...
    recover_deadline = time.time() + 30
    while (chaos_srv.breaker.state != "closed"
           and time.time() < recover_deadline):
        try:
            chaos_srv.infer(example, deadline_s=2.0)
        except Exception:
            time.sleep(0.05)
    # ...and p99 must return to within 2x of the unfaulted baseline
    chaos_srv.reset_latency_window()
    post = run_load(chaos_srv, 4, window, deadline_s=2.0)
    breaker = chaos_srv.breaker.stats()
    stats = chaos_srv.stats()
    cw = swap_results.get("chaos_window", {})
    accounted = (
        cw.get("issued", 0)
        == cw.get("ok", 0) + cw.get("shed", 0)
        + cw.get("errors", 0) + cw.get("timeouts", 0)
    )
    p99_ratio = (
        round(post["p99_ms"] / baseline["p99_ms"], 3)
        if post["p99_ms"] and baseline["p99_ms"] else None
    )
    chaos_row = {
        "plan": plan,
        "baseline": baseline,
        "chaos_window": cw,
        "post": post,
        "p99_post_ratio": p99_ratio,
        "all_requests_accounted": accounted,
        "breaker_tripped": breaker["trips"] >= 1,
        "breaker_recovered": (
            breaker["recoveries"] >= 1 and breaker["state"] == "closed"
        ),
        "hotswap_rolled_back": not swap_results["torn_push_installed"],
        "hotswap_installed_after": swap_results["good_push_installed"],
        "weights_generation": chaos_srv.generation,
        "wedged_batches": stats["wedged_batches"],
        "watchdog_events": [
            (e["stage"], e["stalled_s"])
            for e in chaos_srv._watchdog.events
        ],
        "completed": bool(
            accounted
            and breaker["trips"] >= 1
            and breaker["state"] == "closed"
            and not swap_results["torn_push_installed"]
            and swap_results["good_push_installed"]
            and post["ok"] > 0
            and (p99_ratio is not None and p99_ratio <= 2.0)
        ),
    }
    chaos_srv.stop()
    srv.stop()

    # -- phase 4: request tracing + SLO burn alert (ISSUE 13) --------------
    # 4a: the chaos-plan request — its first try raises (-> one counted
    # cross-replica retry), the retried try is slowed past hedge_after
    # (-> one hedge), the hedge wins.  The whole journey must land in
    # ONE causally-linked trace whose spans account for >= 95% of the
    # client-observed latency.
    from deeplearning4j_tpu.observe import (
        chain_coverage, chain_is_causal, registry, tracer,
    )
    from deeplearning4j_tpu.serving import RouterConfig, ServingFleet

    fleet = ServingFleet(
        lambda: SequentialModel(conf).init(), n_replicas=2,
        config=ServingConfig(max_batch=8, linger_s=0.001),
        router_config=RouterConfig(retry_budget=2, hedge_after_s=0.05),
    )
    fleet.warm_start(example)
    fleet.start()
    rec = tracer()
    rec.enable()
    rec.clear()
    faults.arm("serving.infer:raise:nth=1;"
               "serving.infer:delay:nth=2,secs=0.2")
    t0 = time.monotonic()
    fleet.infer(example, deadline_s=10.0)
    client_wall_s = time.monotonic() - t0
    faults.disarm()
    time.sleep(0.4)        # the discarded hedge loser finishes its batch
    traced = [s for s in list(rec._spans) if s[5] and "trace" in s[5]]
    trace_ids = sorted({s[5]["trace"] for s in traced})
    chain = rec.trace_chain(trace_ids[0]) if trace_ids else []
    span_names: dict = {}
    for s in chain:
        span_names[s["name"]] = span_names.get(s["name"], 0) + 1
    coverage = chain_coverage(chain)
    rstats = fleet.router.stats()
    trace_row = {
        "plan": "serving.infer:raise:nth=1 (retry) + "
                "delay:nth=2,secs=0.2 (hedge)",
        "client_wall_ms": round(client_wall_s * 1000.0, 3),
        "trace_ids": len(trace_ids),
        "spans": len(chain),
        "span_names": span_names,
        "causal": chain_is_causal(chain),
        "coverage": round(coverage, 4) if coverage is not None else None,
        "retries": rstats["retries"],
        "hedges": rstats["hedges"],
    }
    rec.disable()
    rec.clear()
    fleet.stop()
    print(f"[bench] serving request trace: {json.dumps(trace_row)}",
          file=sys.stderr)

    # 4b: induced overload must fire the fast-window burn alert within
    # its window, and the alert must clear after recovery.  Real clock,
    # shrunken windows (the engine's clock is injectable; the bench
    # proves it on wall time).
    from deeplearning4j_tpu.observe.slo import (
        BurnWindow, SLObjective, SLOEngine,
    )

    fast_w, slow_w = (0.5, 2.0) if QUICK else (1.0, 4.0)
    engine = SLOEngine(
        [SLObjective.availability("availability", target=0.99)],
        windows=(BurnWindow(fast_w, 4.0), BurnWindow(slow_w, 1.0)),
    )
    slo_srv = make_server()
    slo_srv.warm_start(example)
    slo_srv.start()
    stop_load = threading.Event()

    def _slo_client():
        import numpy as _np

        rng = _np.random.default_rng(0)
        while not stop_load.is_set():
            try:
                slo_srv.infer(
                    rng.normal(size=(n_in,)).astype(_np.float32),
                    deadline_s=2.0,
                )
            except Exception:
                pass

    load_threads = [threading.Thread(target=_slo_client)
                    for _ in range(4)]
    for t in load_threads:
        t.start()
    engine.sample()
    time.sleep(fast_w)                      # healthy baseline window
    faults.arm("serving.infer:raise:every=2")
    t_overload = time.monotonic()
    fired_after_s = None
    deadline = time.monotonic() + fast_w * 6
    while time.monotonic() < deadline:
        if engine.sample()["availability"]["alert"]:
            fired_after_s = time.monotonic() - t_overload
            break
        time.sleep(0.05)
    faults.disarm()
    t_recover = time.monotonic()
    cleared_after_s = None
    deadline = time.monotonic() + fast_w * 6
    while time.monotonic() < deadline:
        if not engine.sample()["availability"]["alert"]:
            cleared_after_s = time.monotonic() - t_recover
            break
        time.sleep(0.05)
    stop_load.set()
    for t in load_threads:
        t.join(10)
    slo_srv.stop()
    slo_state = engine.state()["availability"]
    slo_row = {
        "objective": {"name": "availability", "target": 0.99},
        "windows": {"fast_s": fast_w, "slow_s": slow_w,
                    "fast_threshold": 4.0, "slow_threshold": 1.0},
        "alert_fired": fired_after_s is not None,
        "fired_after_s": (round(fired_after_s, 3)
                          if fired_after_s is not None else None),
        "fired_within_fast_window": (
            fired_after_s is not None and fired_after_s <= fast_w * 2
        ),
        "alert_cleared": cleared_after_s is not None,
        "cleared_after_s": (round(cleared_after_s, 3)
                            if cleared_after_s is not None else None),
        "alerts_total": slo_state["alerts_total"],
        "final_burn": slo_state["burn"],
    }
    # meta-observability: one full scrape, then read its self-timing
    reg = registry()
    reg.to_prometheus_text()
    slo_row["scrape_seconds"] = reg.gauge("dl4jtpu_scrape_seconds").value()
    slo_row["registry_series"] = reg.gauge("dl4jtpu_registry_series").value()
    print(f"[bench] serving slo: {json.dumps(slo_row)}", file=sys.stderr)

    # -- phase 5: int8 quantized serving (ISSUE 14) ------------------------
    quant_row = _bench_serving_quantized(run_loop=_serving_closed_loop)
    print(f"[bench] serving quantized: "
          f"{json.dumps({k: v for k, v in quant_row.items() if k != 'kernel_bench'})}",
          file=sys.stderr)

    doc = {
        "schema": "bench-serving/3",
        "platform": jax.default_backend(),
        "env": _env_provenance(),
        "quick": QUICK,
        "config": {
            "max_batch": 8, "linger_s": 0.001, "breaker_threshold": 3,
            "model": f"dense32-out{n_out} (in={n_in})",
        },
        "curve": curve,
        "warm_start": warm_row,
        "chaos": chaos_row,
        "request_trace": trace_row,
        "slo": slo_row,
        "quantized": quant_row,
    }
    if not QUICK:
        path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "BENCH_SERVING.json")
        with open(path, "w") as f:
            json.dump(doc, f, indent=1)
        print(f"[bench] serving table -> {path}", file=sys.stderr)
    print(json.dumps(doc))


def bench_serving_fleet() -> None:
    """bench.py --serving-fleet: N replicas behind the Router front door
    -> BENCH_SERVING_FLEET.json.

    Four phases over one small model:

      1. **scale** — closed-loop throughput at replica counts 1/2/4
         (achieved rps, p50/p99, client-side accounting: zero silent
         drops at every width);
      2. **deploy** — p99 during a rolling canary weight deploy vs the
         steady state on the same fleet: the deploy must install
         fleet-wide while traffic keeps flowing;
      3. **chaos** — one replica HARD-KILLED mid-traffic plus one torn
         canary deploy (``serving.canary:corrupt``) under concurrent
         load: every client request accounted (served / explicitly
         shed / retried-then-served), the torn deploy rolls back with
         at most ONE replica ever on the pushed weights, a clean
         deploy installs on the survivors after the storm, and
         post-chaos p99 returns to within 2x of baseline;
      4. **generation** — a 2-replica DISAGGREGATED fleet (prefill |
         decode) under routed token streams: TTFT/tokens-per-s
         percentiles with per-stream cross-replica trace chains, then
         an induced decode stall that must fire (and clear) the TTFT
         burn-rate alert and snapshot the serving flight recorder.

    CPU by default (the subject is the fleet control plane);
    BENCH_SERVING_PLATFORM overrides.  Quick mode (BENCH_QUICK=1)
    shrinks windows/widths and does NOT rewrite the committed table."""
    import threading

    import jax

    jax.config.update(
        "jax_platforms", os.environ.get("BENCH_SERVING_PLATFORM", "cpu")
    )
    import tempfile

    import numpy as np

    from deeplearning4j_tpu.models import SequentialModel
    from deeplearning4j_tpu.nn.conf import (
        Dense, InputType, NeuralNetConfiguration, OutputLayer,
    )
    from deeplearning4j_tpu.runtime import faults
    from deeplearning4j_tpu.serving import (
        RouterConfig, ServingConfig, ServingFleet,
    )

    os.environ.setdefault(
        "DL4JTPU_CRASH_DIR",
        os.path.join(tempfile.mkdtemp(prefix="dl4jtpu-fleet-"), "crash"),
    )
    n_in, n_out = 16, 4
    conf = (
        NeuralNetConfiguration.builder().seed(7).list()
        .layer(Dense(n_out=32)).layer(OutputLayer(n_out=n_out))
        .set_input_type(InputType.feed_forward(n_in)).build()
    )
    example = np.zeros((n_in,), np.float32)

    def make_fleet(n, **router_kw):
        router_kw.setdefault("retry_budget", 1)
        router_kw.setdefault("eject_threshold", 2)
        router_kw.setdefault("try_timeout_s", 0.25)
        router_kw.setdefault("probation_s", 30.0)
        fleet = ServingFleet(
            lambda: SequentialModel(conf).init(), n_replicas=n,
            config=ServingConfig(
                max_batch=8, max_queue=64, linger_s=0.001,
                breaker_threshold=3, breaker_probe_after_s=0.2,
            ),
            router_config=RouterConfig(**router_kw),
            golden_inputs=[example],
        )
        fleet.warm_start(example)
        return fleet.start()

    def run_load(fleet, clients, duration_s, deadline_s):
        # the shared closed loop drives the FRONT DOOR: its accounting
        # covers routing, retries and hedges too
        return _serving_closed_loop(fleet, clients, duration_s,
                                    deadline_s, n_in)

    window = 0.6 if QUICK else 2.5
    widths = (1, 2) if QUICK else (1, 2, 4)

    # -- phase 1: throughput vs replica count ------------------------------
    scale = []
    for n in widths:
        fleet = make_fleet(n)
        row = run_load(fleet, clients=8, duration_s=window,
                       deadline_s=2.0)
        row["replicas"] = n
        rstats = fleet.router.stats()
        row["router"] = {
            k: rstats[k] for k in ("retries", "hedges", "ejections")
        }
        fleet.stop()
        scale.append(row)
        print(f"[bench] fleet scale n={n}: {json.dumps(row)}",
              file=sys.stderr)

    # -- phase 2: p99 during a rolling deploy vs steady state --------------
    n_deploy = 2 if QUICK else 4
    fleet = make_fleet(n_deploy)
    steady = run_load(fleet, clients=6, duration_s=window,
                      deadline_s=2.0)
    model = fleet.replicas[0].model
    new_params = jax.tree.map(lambda a: a + 0.01, model.params)
    deploy_result = {}
    loader = threading.Thread(
        target=lambda: deploy_result.update(
            window=run_load(fleet, clients=6, duration_s=window * 2,
                            deadline_s=2.0)
        )
    )
    loader.start()
    time.sleep(window * 0.5)
    res = fleet.deployer.deploy(new_params, source="bench-rolling")
    loader.join(120)
    dw = deploy_result.get("window", {})
    deploy_row = {
        "replicas": n_deploy,
        "steady": steady,
        "during_deploy": dw,
        "deploy_installed": res["installed"],
        "replicas_updated": res["replicas_updated"],
        "deploy_generation": fleet.deployer.generation,
        "p99_deploy_ratio": (
            round(dw["p99_ms"] / steady["p99_ms"], 3)
            if dw.get("p99_ms") and steady.get("p99_ms") else None
        ),
    }
    fleet.stop()
    print(f"[bench] fleet deploy: {json.dumps(deploy_row)}",
          file=sys.stderr)

    # -- phase 3: chaos -----------------------------------------------------
    # one replica hard-killed mid-traffic + one torn canary deploy (the
    # canary's observed outputs are corrupted -> golden mismatch -> the
    # whole deploy rolls back, at most ONE replica ever on the pushed
    # weights) under concurrent load
    n_chaos = 2 if QUICK else 3
    fleet = make_fleet(n_chaos)
    baseline = run_load(fleet, clients=6, duration_s=window,
                        deadline_s=2.0)
    model = fleet.replicas[0].model
    good_params = jax.tree.map(lambda a: a + 0.005, model.params)
    chaos_result = {}
    faults.arm("serving.canary:corrupt:nth=1")
    torn_res = {}
    try:
        loader = threading.Thread(
            target=lambda: chaos_result.update(
                window=run_load(fleet, clients=8,
                                duration_s=window * 2, deadline_s=1.0)
            )
        )
        loader.start()
        time.sleep(window * 0.4)
        fleet.kill_replica(0)
        time.sleep(window * 0.3)
        torn_res.update(fleet.deployer.deploy(
            jax.tree.map(lambda a: a * 2.0, model.params),
            source="bench-torn-canary",
        ))
        loader.join(120)
    finally:
        faults.disarm()
    # after the storm: a clean deploy must install on the survivors
    good_res = fleet.deployer.deploy(good_params, source="bench-good")
    post = run_load(fleet, clients=6, duration_s=window, deadline_s=2.0)
    cw = chaos_result.get("window", {})
    accounted = (
        cw.get("issued", 0)
        == cw.get("ok", 0) + cw.get("shed", 0)
        + cw.get("errors", 0) + cw.get("timeouts", 0)
    )
    p99_ratio = (
        round(post["p99_ms"] / baseline["p99_ms"], 3)
        if post.get("p99_ms") and baseline.get("p99_ms") else None
    )
    router_stats = fleet.router.stats()
    chaos_row = {
        "replicas": n_chaos,
        "plan": "kill r0 mid-traffic + serving.canary:corrupt:nth=1",
        "baseline": baseline,
        "chaos_window": cw,
        "post": post,
        "p99_post_ratio": p99_ratio,
        "all_requests_accounted": accounted,
        "replica_killed": "r0",
        "ejections": router_stats["ejections"],
        "retries": router_stats["retries"],
        "torn_deploy_rolled_back": not torn_res["installed"],
        "replicas_ever_on_bad_weights": torn_res["rolled_back"],
        "good_deploy_installed_after": good_res["installed"],
        "deploy_generation": fleet.deployer.generation,
        "completed": bool(
            accounted
            and cw.get("ok", 0) > 0
            and router_stats["ejections"] >= 1
            and not torn_res["installed"]
            and torn_res["rolled_back"] <= 1
            and good_res["installed"]
            and post.get("ok", 0) > 0
            and (p99_ratio is not None and p99_ratio <= 2.0)
        ),
    }
    fleet.stop()
    print(f"[bench] fleet chaos: {json.dumps(chaos_row)}",
          file=sys.stderr)

    # -- phase 4: generation plane (ISSUE 17) ------------------------------
    # a 2-replica DISAGGREGATED fleet (r0 prefill | r1 decode) driven
    # through the routed front door: (a) healthy TTFT/tokens-per-s with
    # tracing on — every stream must land as ONE causal chain whose
    # spans cover both replicas' work (router picks, prefill, kv
    # handoff, decode steps); (b) an induced decode stall must fire the
    # TTFT burn-rate alert within its windows, the alert's rising edge
    # must snapshot the flight recorder, and the alert must clear after
    # recovery; (c) the flight ring must account for every settled
    # stream.
    from collections import Counter

    from deeplearning4j_tpu.observe import chain_is_causal, tracer
    from deeplearning4j_tpu.observe.slo import (
        BurnWindow, SLOEngine, generation_objectives,
    )
    from deeplearning4j_tpu.serving import GenerationConfig
    from deeplearning4j_tpu.zoo.transformer import TransformerEncoder

    gen_fleet = ServingFleet(
        lambda: TransformerEncoder(
            vocab_size=31, d_model=16, n_heads=2, n_layers=2,
            causal=True, seed=5,
        ).init_model(),
        n_replicas=2, roles=["prefill", "decode"],
        generation_config=GenerationConfig(
            slots=4, page_size=8, num_pages=64, max_pages_per_seq=4,
            max_queue=32, default_max_new=8,
        ),
    ).start()
    eng_dec = gen_fleet.engines[gen_fleet.handles[1].name]
    gen_lock = threading.Lock()
    ttfts: list = []
    walls: list = []           # (tokens, wall_s) per completed stream
    gen_out = {"ok": 0, "error": 0}
    prompt_seq = iter(range(10_000))

    def _one_stream(max_new=8):
        rng = np.random.default_rng(1000 + next(prompt_seq))
        prompt = rng.integers(0, 31, 6).astype(np.int32)
        marks: dict = {}
        t0 = time.monotonic()

        def _tok(_tok_id, _idx):
            marks.setdefault("ttft", time.monotonic() - t0)

        try:
            out = gen_fleet.generate(prompt, max_new, timeout=120.0,
                                     on_token=_tok)
            wall = time.monotonic() - t0
            with gen_lock:
                gen_out["ok"] += 1
                if "ttft" in marks:
                    ttfts.append(marks["ttft"])
                walls.append((len(out) - len(prompt), wall))
        except Exception:
            with gen_lock:
                gen_out["error"] += 1

    _one_stream()                       # compile warm-up, untraced
    rec = tracer()
    rec.enable()
    rec.clear()
    n_streams = 8 if QUICK else 24
    t_healthy0 = time.monotonic()
    threads = [
        threading.Thread(target=lambda k=i: [_one_stream()
                                             for _ in range(k)])
        for i in [n_streams // 4] * 4
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(300)
    healthy_s = time.monotonic() - t_healthy0
    with gen_lock:
        healthy_tok = sum(n for n, _ in walls)
        ttfts_ms = sorted(t * 1000.0 for t in ttfts)

    def _pct(xs, p):
        return (round(xs[min(len(xs) - 1, int(p * len(xs)))], 3)
                if xs else None)

    # every healthy stream = one causal cross-replica chain
    chains = [rec.trace_chain(tid) for tid in rec.trace_ids()]
    need = {"generation.stream", "router.pick", "generation.admit",
            "generation.prefill", "generation.kv_handoff",
            "generation.decode_step"}
    complete = sum(
        1 for c in chains
        if chain_is_causal(c) and need <= {s["name"] for s in c}
    )
    span_names = Counter(s["name"] for c in chains for s in c)
    rec.disable()
    rec.clear()

    # SLO: baseline -> decode stall -> alert fires (and dumps the
    # flight ring) -> recovery -> alert clears
    fast_w, slow_w = (0.5, 2.0) if QUICK else (1.0, 4.0)
    healthy_rate = healthy_tok / max(healthy_s, 1e-9)
    floor = max(5.0, round(healthy_rate * 0.25, 1))
    gen_engine = SLOEngine(
        generation_objectives(ttft_threshold_s=0.25,
                              tokens_floor_per_s=floor),
        windows=(BurnWindow(fast_w, 4.0), BurnWindow(slow_w, 1.0)),
    )
    dumps_before = eng_dec.flight.dumps_written
    stop_gen = threading.Event()

    def _gen_client():
        while not stop_gen.is_set():
            _one_stream()

    gen_threads = [threading.Thread(target=_gen_client)
                   for _ in range(3)]
    for t in gen_threads:
        t.start()
    gen_engine.sample()
    time.sleep(fast_w)                  # healthy baseline window
    faults.arm("serving.decode:delay:every=1,secs=0.3")
    t_stall = time.monotonic()
    gen_fired_after = None
    deadline = time.monotonic() + fast_w * 10
    while time.monotonic() < deadline:
        if gen_engine.sample()["generation_ttft_p95"]["alert"]:
            gen_fired_after = time.monotonic() - t_stall
            break
        time.sleep(0.05)
    faults.disarm()
    t_recover = time.monotonic()
    gen_cleared_after = None
    deadline = time.monotonic() + fast_w * 10
    while time.monotonic() < deadline:
        if not gen_engine.sample()["generation_ttft_p95"]["alert"]:
            gen_cleared_after = time.monotonic() - t_recover
            break
        time.sleep(0.05)
    stop_gen.set()
    for t in gen_threads:
        t.join(300)
    estats = eng_dec.stats()
    flight_records = eng_dec.flight.snapshot()
    dump_path = (eng_dec.flight.dump_paths[-1]
                 if eng_dec.flight.dump_paths else None)
    dump_doc = {}
    if dump_path:
        with open(dump_path) as f:
            dump_doc = json.load(f)
    settled = estats["streams"]["settled"]
    gen_state = gen_engine.state()
    gen_row = {
        "replicas": 2,
        "roles": ["prefill", "decode"],
        "plan": "healthy window + serving.decode:delay:every=1,secs=0.3 stall",
        "streams": dict(gen_out),
        "outcomes": estats["streams"]["outcomes"],
        "ttft_ms": {"p50": _pct(ttfts_ms, 0.50),
                    "p95": _pct(ttfts_ms, 0.95),
                    "p99": _pct(ttfts_ms, 0.99),
                    "n": len(ttfts_ms)},
        "healthy_tokens_per_s": round(healthy_rate, 2),
        "latency_breakdown": estats["latency_breakdown"],
        "trace": {
            "streams_traced": len(chains),
            "complete_causal_chains": complete,
            "span_names": dict(span_names),
        },
        "slo": {
            "objectives": {
                n: {"alert": s["alert"], "burn": s["burn"],
                    "alerts_total": s["alerts_total"],
                    **({"rate_per_s": s["rate_per_s"]}
                       if "rate_per_s" in s else {})}
                for n, s in gen_state.items()
            },
            "tokens_floor_per_s": floor,
            "ttft_alert_fired": gen_fired_after is not None,
            "fired_after_s": (round(gen_fired_after, 3)
                              if gen_fired_after is not None else None),
            "ttft_alert_cleared": gen_cleared_after is not None,
            "cleared_after_s": (round(gen_cleared_after, 3)
                                if gen_cleared_after is not None
                                else None),
        },
        "flight": {
            "records": len(flight_records),
            "streams_settled": settled,
            "all_settled_recorded": (
                settled <= 256 and len(flight_records) == settled
            ),
            "dumps_written": eng_dec.flight.dumps_written,
            "slo_alert_dumped": (
                eng_dec.flight.dumps_written > dumps_before
            ),
            "last_dump": {
                "trigger": dump_doc.get("trigger"),
                "schema": dump_doc.get("schema"),
                "records": len(dump_doc.get("records", ())),
            } if dump_doc else None,
        },
        "completed": bool(
            gen_out["ok"] > 0
            and complete == len(chains) > 0
            and gen_fired_after is not None
            and gen_cleared_after is not None
            and eng_dec.flight.dumps_written > dumps_before
        ),
    }
    gen_fleet.stop()
    print(f"[bench] fleet generation: {json.dumps(gen_row)}",
          file=sys.stderr)

    doc = {
        "schema": "bench-serving-fleet/1",
        "platform": jax.default_backend(),
        "env": _env_provenance(),
        "quick": QUICK,
        "config": {
            "max_batch": 8, "max_queue": 64, "retry_budget": 1,
            "eject_threshold": 2, "try_timeout_s": 0.25,
            "model": f"dense32-out{n_out} (in={n_in})",
        },
        "scale": scale,
        "deploy": deploy_row,
        "chaos": chaos_row,
        "generation": gen_row,
    }
    if not QUICK:
        path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "BENCH_SERVING_FLEET.json")
        with open(path, "w") as f:
            json.dump(doc, f, indent=1)
        print(f"[bench] fleet table -> {path}", file=sys.stderr)
    print(json.dumps(doc))


def bench_generate() -> None:
    """bench.py --generate: token-level continuous-batching generation
    vs request-at-a-time serving -> BENCH_GENERATE.json.

    Five phases over one small causal transformer:

      1. **curve** — the same mixed-length prompt set served two ways at
         1/2/4/8 concurrent streams: request-at-a-time (the dense
         `ops.generation.generate` fused scan, one request after
         another — the strongest honest baseline, since it pays ZERO
         per-token dispatch) vs the continuous-batching
         `GenerationEngine` (all streams submitted at once).  Each row
         records aggregate generated tokens/sec, TTFT distribution, and
         greedy token-parity between the two paths.
      2. **compile stability** — `compile_stats` delta across the whole
         measured window after bucket warm-up must show zero fresh
         backend compiles (the bounded-program-set acceptance).
      3. **int8 KV residency** — `PagedKVCache.bytes_per_token()` f32
         vs int8 plus measured greedy token agreement on the int8-KV
         engine (gated like PR 13: agreement is evidence, the residency
         ratio is the claim).
      4. **speculative decoding** — draft-k/verify-once (n-gram
         drafter, spec_k=4) vs the same engine shape decoding plain on
         a long-decode workload: interleaved best-of-3 rounds, byte
         parity asserted per round, acceptance rate and tokens/dispatch
         from the engine's own counters, plus a chaos run with EVERY
         draft corrupted (parity must hold, zero KV pages may leak) and
         a compile-stats gate over the verify program.  This is a
         MEASURED CPU speedup — speculation amortizes the per-dispatch
         fixed cost that dominates CPU decode.
      5. **modeled TPU speedup** — the >=2x continuous-batching claim,
         rooflined against TPU v5e peaks.  Decode is weights-bandwidth
         bound at serving batch sizes: a batched decode step streams
         the weights ONCE for all live streams, request-at-a-time
         streams them once PER stream-token, so the modeled speedup is
         B*(W+kv)/(W+B*kv).

    The measured CPU rows are honest and therefore modest: on CPU the
    dense scan baseline is compute-bound (a batch-8 matmul costs ~8x a
    batch-1 matmul) and already fuses the whole generation into one XLA
    program, so continuous batching buys little wall-clock — its
    measured CPU win is TTFT (prefills are admitted concurrently
    instead of queueing behind whole generations).  The >=2x aggregate
    throughput claim is carried by the modeled row until this bench
    runs on real TPU hardware (BENCH_SERVING_PLATFORM=tpu), exactly
    like BENCH_SERVING.json's quantized phase.

    CPU by default; BENCH_SERVING_PLATFORM overrides.  Quick mode
    (BENCH_QUICK=1) shrinks the model and does NOT rewrite the
    committed BENCH_GENERATE.json."""
    import jax

    jax.config.update(
        "jax_platforms", os.environ.get("BENCH_SERVING_PLATFORM", "cpu")
    )
    import numpy as np

    from deeplearning4j_tpu.observe.cost import PEAKS_BY_DEVICE_KIND
    from deeplearning4j_tpu.ops.generation import generate
    from deeplearning4j_tpu.runtime import compile_stats
    from deeplearning4j_tpu.serving.generation import (
        GenerationConfig, GenerationEngine,
    )
    from deeplearning4j_tpu.zoo.transformer import TransformerEncoder

    if QUICK:
        vocab, d, heads, layers, max_new = 128, 64, 4, 2, 6
        stream_points = (2, 4)
    else:
        vocab, d, heads, layers, max_new = 1024, 512, 8, 4, 24
        stream_points = (1, 2, 4, 8)
    model = TransformerEncoder(
        vocab_size=vocab, d_model=d, n_heads=heads, n_layers=layers,
        causal=True, seed=16,
    ).init_model()

    # mixed prompt lengths spanning the 8- and 16-row buckets; prompt +
    # max_new stays inside page_size * max_pages_per_seq = 64 positions
    lens = [5, 9, 13, 6, 11, 7, 15, 8]
    rng = np.random.default_rng(16)
    prompts = [rng.integers(0, vocab, n).astype(np.int32) for n in lens]
    max_streams = max(stream_points)

    def engine_config(**over):
        kw = dict(slots=max_streams, page_size=8, num_pages=256,
                  max_pages_per_seq=8, max_queue=64,
                  default_max_new=max_new)
        kw.update(over)
        return GenerationConfig(**kw)

    # -- request-at-a-time reference: warm every (prompt-len, max_new)
    # program first, then serve the arrived-at-t0 queue sequentially.
    # The dense path returns the whole sequence at once, so a request's
    # TTFT under this discipline is its completion time.
    dense_out = {}
    for i, p in enumerate(prompts):
        dense_out[i] = np.asarray(generate(model, p[None], max_new))[0]

    def dense_row(n_streams):
        t0 = time.perf_counter()
        ttfts, outs = [], []
        for p in prompts[:n_streams]:
            outs.append(np.asarray(generate(model, p[None], max_new))[0])
            ttfts.append(time.perf_counter() - t0)
        wall = time.perf_counter() - t0
        return outs, {
            "wall_s": round(wall, 4),
            "tokens_per_s": round(n_streams * max_new / wall, 1),
            "ttft_mean_s": round(float(np.mean(ttfts)), 4),
            "ttft_max_s": round(float(np.max(ttfts)), 4),
        }

    # -- continuous-batching engine: one engine for the whole curve;
    # warm both prefill buckets + the decode step, then snapshot
    # compile stats so the ENTIRE measured window proves program-set
    # closure
    eng = GenerationEngine(model=model, config=engine_config()).start()
    eng.generate(prompts[0], 2, timeout=300.0)     # 8-bucket + step
    eng.generate(prompts[2], 2, timeout=300.0)     # 16-bucket
    snap = compile_stats.snapshot()

    def engine_row(n_streams):
        t0 = time.perf_counter()
        reqs = [eng.submit(p, max_new) for p in prompts[:n_streams]]
        outs = [np.asarray(r.result(300.0)) for r in reqs]
        wall = time.perf_counter() - t0
        ttfts = [r.ttft_s for r in reqs]
        return outs, {
            "wall_s": round(wall, 4),
            "tokens_per_s": round(n_streams * max_new / wall, 1),
            "ttft_mean_s": round(float(np.mean(ttfts)), 4),
            "ttft_max_s": round(float(np.max(ttfts)), 4),
        }

    curve = []
    for n in stream_points:
        d_outs, d_row = dense_row(n)
        e_outs, e_row = engine_row(n)
        parity = all(
            np.array_equal(e, d) for e, d in zip(e_outs, d_outs)
        )
        row = {
            "streams": n,
            "request_at_a_time": d_row,
            "engine": e_row,
            "speedup": round(
                e_row["tokens_per_s"] / d_row["tokens_per_s"], 3),
            "ttft_speedup": round(
                d_row["ttft_mean_s"] / e_row["ttft_mean_s"], 3)
                if e_row["ttft_mean_s"] else None,
            "greedy_parity": parity,
        }
        curve.append(row)
        print(f"[bench] generate curve streams={n}: {json.dumps(row)}",
              file=sys.stderr)

    delta = (compile_stats.snapshot() - snap).as_dict()
    kv_f32_bpt = eng.kv.bytes_per_token()
    eng.stop()
    compile_row = {
        "window": f"all curve points after bucket warm-up "
                  f"(streams {list(stream_points)})",
        "fresh_backend_compiles": delta["fresh_backend_compiles"],
        "delta": delta,
    }
    print(f"[bench] generate compile stability: {json.dumps(compile_row)}",
          file=sys.stderr)

    # -- int8 KV: residency ratio is the claim, measured greedy
    # agreement vs the dense f32 reference is the gate evidence
    eng8 = GenerationEngine(
        model=model, config=engine_config(kv_dtype="int8")).start()
    agree = []
    for i, p in enumerate(prompts[:max_streams]):
        out = np.asarray(eng8.generate(p, max_new, timeout=300.0))
        gen, ref = out[len(p):], dense_out[i][len(p):]
        agree.append(float(np.mean(gen == ref)))
    kv_int8_bpt = eng8.kv.bytes_per_token()
    eng8.stop()
    int8_row = {
        "bytes_per_token_f32": kv_f32_bpt,
        "bytes_per_token_int8": kv_int8_bpt,
        "residency_ratio": round(kv_int8_bpt / kv_f32_bpt, 4),
        "greedy_agreement_mean": round(float(np.mean(agree)), 4),
        "greedy_agreement_min": round(float(np.min(agree)), 4),
    }
    print(f"[bench] generate int8 kv: {json.dumps(int8_row)}",
          file=sys.stderr)

    # -- speculative decoding: draft-k/verify-once (ISSUE 20) vs the
    # SAME engine shape decoding plain, on a long-decode workload where
    # the n-gram drafter earns its keep (greedy decode settles into
    # short cycles, which prompt-lookup drafts near-perfectly).  Both
    # engines measured interleaved, best-of-N rounds after steady-state
    # warm-up; byte parity between them is asserted per round — the
    # speedup is only meaningful because the outputs are identical.
    from deeplearning4j_tpu.runtime import faults as _faults

    spec_k = 4
    spec_max_new = 8 if QUICK else 100
    spec_rounds = 2 if QUICK else 3
    spec_cfg = dict(slots=max_streams, page_size=8, num_pages=256,
                    max_pages_per_seq=16, max_queue=64,
                    default_max_new=spec_max_new)
    eng_plain = GenerationEngine(
        model=model, config=GenerationConfig(**spec_cfg, spec_k=0),
    ).start()
    eng_spec = GenerationEngine(
        model=model, config=GenerationConfig(**spec_cfg, spec_k=spec_k),
    ).start()

    def spec_run(eng):
        t0 = time.perf_counter()
        reqs = [eng.submit(p, spec_max_new) for p in prompts]
        outs = [np.asarray(r.result(600.0)) for r in reqs]
        wall = time.perf_counter() - t0
        return outs, len(prompts) * spec_max_new / wall

    for e in (eng_plain, eng_spec):
        e.generate(prompts[0], 2, timeout=300.0)
        e.generate(prompts[2], 2, timeout=300.0)
        spec_run(e)                      # steady-state warm-up
    snap_spec = compile_stats.snapshot()
    sd0 = eng_spec.stats()["speculative"]
    best_plain = best_spec = 0.0
    p_outs = s_outs = None
    parity = True
    for _ in range(spec_rounds):
        p_outs, tps = spec_run(eng_plain)
        best_plain = max(best_plain, tps)
        s_outs, tps = spec_run(eng_spec)
        best_spec = max(best_spec, tps)
        parity = parity and all(
            np.array_equal(a, b) for a, b in zip(p_outs, s_outs))
    sd1 = eng_spec.stats()["speculative"]
    drafted = sd1["drafted"] - sd0["drafted"]
    accepted = sd1["accepted"] - sd0["accepted"]
    emitted = accepted + (sd1["bonus"] - sd0["bonus"])
    dispatches = (sd1["verify_dispatches"] - sd0["verify_dispatches"]
                  + sd1["plain_dispatches"] - sd0["plain_dispatches"])
    # chaos: corrupt EVERY draft — rejection sampling must shrug the
    # garbage off with byte-identical output and zero page leaks
    _faults.arm("serving.draft:corrupt:every=1")
    c_outs, _ = spec_run(eng_spec)
    _faults.disarm()
    chaos_parity = all(
        np.array_equal(a, b) for a, b in zip(p_outs, c_outs))
    leak = eng_spec.kv.leak_check()
    leaked_pages = eng_spec.kv.used_pages
    spec_compiles = (compile_stats.snapshot() - snap_spec).as_dict()
    eng_plain.stop()
    eng_spec.stop()
    spec_row = {
        "spec_k": spec_k,
        "drafter": "ngram",
        "streams": max_streams,
        "max_new_tokens": spec_max_new,
        "plain_tokens_per_s": round(best_plain, 1),
        "spec_tokens_per_s": round(best_spec, 1),
        "spec_speedup": round(best_spec / best_plain, 3)
            if best_plain else None,
        "acceptance_rate": round(accepted / drafted, 4) if drafted
            else 0.0,
        "tokens_per_dispatch": round(
            emitted / max(1, sd1["verify_dispatches"]
                          - sd0["verify_dispatches"]), 2),
        "dispatches_per_stream_token": round(
            dispatches / (len(prompts) * spec_max_new * spec_rounds), 4),
        "greedy_parity": parity,
        "measurement": f"best of {spec_rounds} interleaved rounds "
                       f"after steady-state warm-up",
        "chaos": {
            "plan": "serving.draft:corrupt:every=1",
            "greedy_parity": chaos_parity,
            "leak_check": leak,
            "leaked_pages": int(leaked_pages),
        },
        "fresh_backend_compiles":
            spec_compiles["fresh_backend_compiles"],
    }
    print(f"[bench] generate speculative: {json.dumps(spec_row)}",
          file=sys.stderr)

    # -- modeled TPU speedup: decode at serving batch is bandwidth
    # bound (AI ~ 2 FLOPs/byte, far under the v5e ridge), so a decode
    # step costs ~ streamed bytes / membw.  Request-at-a-time streams
    # the weights once per stream-token; the batched step streams them
    # once for all B live streams and adds B KV residencies.
    peak_flops, peak_bw = PEAKS_BY_DEVICE_KIND["TPU v5e"]
    weight_bytes = float(sum(
        np.asarray(leaf).size * np.asarray(leaf).dtype.itemsize
        for leaf in jax.tree_util.tree_leaves(model.params)
    ))
    mean_ctx = float(np.mean(lens)) + max_new / 2.0
    kv_bytes = kv_f32_bpt * mean_ctx
    batch = max_streams
    t_seq_token = (weight_bytes + kv_bytes) / peak_bw
    t_batch_step = (weight_bytes + batch * kv_bytes) / peak_bw
    flops_per_token = 2.0 * weight_bytes / 4.0   # 2 FLOPs per f32 param
    modeled = {
        "reference_chip": "TPU v5e",
        "peak_flops": peak_flops,
        "peak_membw_bytes_per_s": peak_bw,
        "batch": batch,
        "weight_bytes_f32": weight_bytes,
        "kv_bytes_per_stream": round(kv_bytes, 1),
        "arithmetic_intensity": round(
            flops_per_token / (weight_bytes + kv_bytes), 3),
        "ridge_point": round(peak_flops / peak_bw, 1),
        "modeled_speedup": round(
            batch * t_seq_token / t_batch_step, 3),
        "note": "bandwidth-bound decode: batched step streams weights "
                "once per step for all B streams vs once per "
                "stream-token; speedup = B*(W+kv)/(W+B*kv)",
    }
    print(f"[bench] generate modeled tpu: {json.dumps(modeled)}",
          file=sys.stderr)

    doc = {
        "schema": "bench-generate/2",
        "platform": jax.default_backend(),
        "env": _env_provenance(),
        "quick": QUICK,
        "config": {
            "model": f"transformer d{d}x{layers}L{heads}H-v{vocab}",
            "max_new_tokens": max_new,
            "prompt_lens": lens[:max_streams],
            "slots": max_streams, "page_size": 8, "num_pages": 256,
            "max_pages_per_seq": 8,
        },
        "curve": curve,
        "compile_stability": compile_row,
        "int8_kv": int8_row,
        "speculative": spec_row,
        "modeled_tpu": modeled,
        "measured_platform_note": (
            "CPU rows measure both serving disciplines honestly; the "
            "dense request-at-a-time baseline is ONE fused scan with "
            "zero per-token dispatch and this CPU is compute-bound at "
            "batch 8, so measured aggregate speedup is ~1x and the "
            "measured CPU win is TTFT (concurrent prefill admission). "
            "The >=2x aggregate tokens/s claim is the modeled_tpu row "
            "until this bench runs on TPU (BENCH_SERVING_PLATFORM=tpu). "
            "The speculative row IS a measured CPU speedup: "
            "draft-k/verify-once amortizes the per-dispatch fixed cost "
            "that dominates CPU decode, with byte-identical output."
        ),
    }
    if not QUICK:
        path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "BENCH_GENERATE.json")
        with open(path, "w") as f:
            json.dump(doc, f, indent=1)
        print(f"[bench] generate table -> {path}", file=sys.stderr)
    print(json.dumps(doc))


def main() -> int:
    """Run every single-chip config; returns the process exit code.

    Fails (non-zero, no benches run) when the device is not a TPU —
    a CPU run is never a speed.  BENCH_FORCE_CPU=1 is the one
    exception: a plumbing mode (tiny CPU shapes, CI) whose headline
    value is null.  A config that raises is recorded with its error,
    the others still run, and the exit code is non-zero."""
    import traceback

    global QUICK
    t_start = time.time()
    forced_cpu = os.environ.get("BENCH_FORCE_CPU", "") not in ("", "0")
    import jax

    if forced_cpu:
        print("[bench] BENCH_FORCE_CPU=1: CPU quick mode (headline value "
              "will be null — CPU numbers live in extra/details only)",
              file=sys.stderr)
        QUICK = True
        jax.config.update("jax_platforms", "cpu")
    elif jax.devices()[0].platform != "tpu":
        print(f"[bench] no TPU: jax.devices()[0] is {jax.devices()[0]!r}; "
              "bench.py measures on the chip only (BENCH_FORCE_CPU=1 runs "
              "the CPU plumbing mode, value null)", file=sys.stderr)
        return 1
    peak, kind = _peak_flops()

    results = {}
    failed = []
    for name, fn in [
        ("lenet", bench_lenet),
        ("resnet50", bench_resnet50),
        ("resnet50_etl", bench_resnet50_etl),
        ("resnet50_etl_cached", bench_resnet50_etl_cached),
        ("lstm", bench_lstm),
        ("bert", bench_bert),
        ("longctx", bench_longctx),
    ]:
        t0 = time.time()
        try:
            results[name] = fn(peak)
        except Exception as exc:  # one config's failure must not hide the
            # others' rows; it is recorded here and fails the run below
            traceback.print_exc()
            results[name] = {"config": name,
                             "error": f"{type(exc).__name__}: {exc}"}
            failed.append(name)
            continue
        results[name]["bench_wall_s"] = round(time.time() - t0, 1)
        print(f"[bench] {name}: {json.dumps(results[name])}",
              file=sys.stderr)

    headline = results.get("resnet50", {})
    # missing -> None, not 0.0: an errored-out headline bench on a live
    # chip must surface as null, not "the chip measured 0"
    measured = headline.get("samples_per_sec")
    value = _headline_value(kind, measured) if measured is not None else None
    h_timing = headline.get("timing", {})

    # Per-config detail goes to a FILE — the final stdout line stays <1KB
    # so a tail window can always parse it.
    details = {
        "device_kind": kind,
        "peak_bf16_flops": peak,
        "quick_mode": QUICK,
        "forced_cpu": forced_cpu,
        "wall_s": round(time.time() - t_start, 1),
        "baseline_assumption": (
            "cuDNN A100 fp32 ResNet-50 ~400 samples/sec "
            "(no published DL4J number; BASELINE.json published={})"
        ),
        "configs": results,
    }
    details_path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "BENCH_DETAILS.json")
    try:
        with open(details_path, "w") as f:
            json.dump(details, f, indent=1)
        print(f"[bench] per-config detail -> {details_path}", file=sys.stderr)
    except OSError as exc:
        print(f"[bench] could not write {details_path}: {exc}", file=sys.stderr)

    extra = {
        "device_kind": kind,
        "non_tpu_samples_per_sec": measured if value is None else None,
        "batch": headline.get("batch"),
        "mfu_vs_bf16_peak": headline.get("mfu_vs_bf16_peak"),
        "window": {
            k: h_timing.get(k)
            for k in ("chunks", "rate_spread", "samples_per_sec_mean")
        } if h_timing else None,
        "etl_fed_sps": results.get("resnet50_etl", {}).get(
            "samples_per_sec"),
        "etl_images_per_sec": results.get("resnet50_etl", {}).get(
            "etl_images_per_sec"),
        "etl_cached_sps": results.get("resnet50_etl_cached", {}).get(
            "samples_per_sec"),
        "lstm_sps": results.get("lstm", {}).get("samples_per_sec"),
        "bert_sps": results.get("bert", {}).get("samples_per_sec"),
        "bert_mfu": results.get("bert", {}).get("mfu_vs_bf16_peak"),
        "longctx_tokens_per_sec": results.get("longctx", {}).get(
            "tokens_per_sec"),
        "quick_mode": QUICK,
        "forced_cpu": forced_cpu or None,
        "failed_configs": failed or None,
        "detail_file": "BENCH_DETAILS.json",
    }
    line = json.dumps(
        {
            "metric": "ResNet-50 GraphModel fit() samples/sec "
                      "(1 chip, 224x224, steady-state)",
            "value": value,
            "unit": "samples/sec",
            "vs_baseline": (
                round(value / ASSUMED_RESNET50_A100_SAMPLES_PER_SEC, 3)
                if value is not None else None
            ),
            # null-valued extras are pruned to keep the line inside a
            # 1KB tail window
            "extra": {k: v for k, v in extra.items() if v is not None},
        }
    )
    assert len(line) < 1024, f"headline line too long ({len(line)}B)"
    print(line)
    if failed:
        print(f"[bench] FAILED configs: {failed}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    if "--warmup-steps" in sys.argv:
        _i = sys.argv.index("--warmup-steps")
        if _i + 1 >= len(sys.argv) or not sys.argv[_i + 1].isdigit():
            sys.exit("usage: bench.py --warmup-steps N [--scaling ...]")
        WARMUP_STEPS = int(sys.argv[_i + 1])
        del sys.argv[_i:_i + 2]
    if "--chaos" in sys.argv:
        sys.exit(bench_chaos())
    if "--serving-fleet" in sys.argv:
        sys.exit(bench_serving_fleet())
    if "--generate" in sys.argv:
        sys.exit(bench_generate())
    if "--serving" in sys.argv:
        sys.exit(bench_serving())
    if "--longctx" in sys.argv:
        sys.exit(bench_longctx_quant())
    if "--plan" in sys.argv:
        sys.exit(bench_plan())
    if "--scaling" in sys.argv:
        sys.exit(bench_scaling())
    if "--decode-scaling" in sys.argv:
        sys.exit(bench_decode_scaling())
    if "--resnet-ab" in sys.argv:
        sys.exit(bench_resnet_ab())
    sys.exit(main())
