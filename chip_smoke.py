#!/usr/bin/env python
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the LM train-and-serve path once on a TPU, through the entry points
a user calls, at the full width of the long-context LM (vocab 32000,
d_model 1024, 8 heads x 128, 8 layers, batch 4 x 2048; random weights from
a seed), and checks what comes out.  ONE process: a chip belongs to one
process at a time, so nothing here starts a child that needs it.

Phases (each printed with its wall time; any failure -> non-zero exit and
no result line):

  device     jax must report a TPU — there is no CPU mode in this file
  train      zoo TransformerEncoder + public model.fit(): finite falling
             loss, bf16 compute, the flash kernel (not dense O(T^2))
  serve      the same model behind GenerationEngine + InferenceServer +
             ServingHTTPServer: concurrent POST /v1/generate over two
             prefill buckets, leak-free KV pool, zero compiles after
             warm-up, the paged-attention impl the device selects, and
             every emitted token within a logit tolerance of the dense
             path's arg-max
  kernels    every Pallas kernel the package picks on TPU, compiled
             (interpret=False), against its XLA reference at "highest"
             matmul precision
  multichip  (>= 4 devices) the train phase under data=4 with the batch
             spread over four distinct devices

The last stdout line of a passing run is exactly one JSON object,
`{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}`,
the device as jax reports it.  The `summary:` line before it carries the
per-phase status and wall time, the versions and the compile-cache
counts.  Neither carries a rate, utilization or tokens/s — this script
measures nothing.

The phase bodies take their sizes as arguments so tests/test_chip_smoke.py
can run them tiny on the CPU (interpret-mode kernels); `python
chip_smoke.py` itself always runs the full width and fails without a TPU.
"""

from __future__ import annotations

import json
import sys
import time
import traceback

# -- tolerances, with their reasons ------------------------------------------
#
# paged attention: the kernel is f32 VPU arithmetic end to end (int8 pages
# dequantize exactly), so against the f32 "highest" reference only the
# summation order differs.
PAGED_ATOL = 1e-4
# flash attention: q/k/v/p ride the MXU as bf16 (8 significand bits,
# eps 2^-8) with f32 accumulation; a few roundings compound, so outputs
# and gradients must sit within 2^-6 (1.6%) of the reference's largest
# magnitude.  An 8-bit float (eps 2^-4) in place of bf16 would fail this.
FLASH_REL = 2.0 ** -6
# dequant-matmul: the int8 weights are exact in bf16 but the f32
# activations are rounded to bf16 by Mosaic's default dot precision, so
# the bound is again a small multiple of 2^-8 of the largest output.
DEQUANT_REL = 2.0 ** -7
# serving vs the dense forward: both compute in bf16, in different
# operation orders (paged f32 attention over an f32 cache vs dense bf16
# attention), and the logits themselves are bf16 values.  Token identity
# is NOT asserted — with random weights the arg-max flips on rounding.
# Instead every token the engine emitted must be a near-arg-max of the
# dense path's logits at that position: within SERVE_LOGIT_REL of the
# largest |logit|.  A wrong attention output moves logits by their full
# scale, two orders of magnitude beyond this.
SERVE_LOGIT_REL = 2.0 ** -5
# data-parallel first-step loss vs the one-chip loss on the same global
# batch: same bf16 arithmetic per example, only the cross-device mean's
# reduction order differs.
MULTICHIP_LOSS_REL = 1e-2

LM = dict(vocab_size=32000, d_model=1024, n_heads=8, n_layers=8,
          vocab_chunk=8192)


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


# -- device ------------------------------------------------------------------

def phase_device() -> dict:
    """Fail unless jax's first device is a TPU; report its identity."""
    import importlib.metadata as md

    import jax

    d0 = jax.devices()[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(jax.devices())}
    log(f"device: platform={d0.platform} device_kind={d0.device_kind!r} "
        f"count={device['count']}")
    if d0.platform != "tpu":
        raise SystemExit(
            f"chip_smoke: no TPU — jax.devices()[0] is {d0!r}; this script "
            "has no CPU mode")
    versions = {pkg: md.version(pkg) for pkg in ("jax", "jaxlib", "libtpu")}
    log(f"versions: {versions}")
    return {"device": device, "versions": versions}


# -- train -------------------------------------------------------------------

def make_lm(*, vocab_size, d_model, n_heads, n_layers, vocab_chunk,
            seed=123):
    from deeplearning4j_tpu.zoo.transformer import TransformerEncoder

    return TransformerEncoder(
        vocab_size=vocab_size, d_model=d_model, n_heads=n_heads,
        n_layers=n_layers, causal=True, chunked_vocab_loss=True,
        vocab_chunk=vocab_chunk, seed=seed,
    ).init_model()


def lm_batches(vocab_size: int, batch: int, seq: int, n: int, seed: int):
    """`n` seeded next-token batches of shape (batch, seq)."""
    import numpy as np

    from deeplearning4j_tpu.data.dataset import DataSet

    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n):
        ids = rng.integers(0, vocab_size, (batch, seq))
        out.append(DataSet(ids.astype(np.float32),
                           np.roll(ids, -1, axis=1).astype(np.float32)))
    return out


class _FlashSpy:
    """Records every `flash_eligible` decision `ops.attention.mha` takes
    while a program traces (mha resolves the name at call time, so
    wrapping the module attribute observes the real dispatch)."""

    def __enter__(self):
        from deeplearning4j_tpu.ops import flash_attention as fa

        self._fa, self._orig, self.calls = fa, fa.flash_eligible, []

        def spy(q, k, mask, **kw):
            ok = self._orig(q, k, mask, **kw)
            self.calls.append((tuple(q.shape), bool(ok)))
            return ok

        fa.flash_eligible = spy
        return self

    def __exit__(self, *exc):
        self._fa.flash_eligible = self._orig
        return False


def phase_train(lm: dict, *, batch: int, seq: int, n_batches: int = 2,
                epochs: int = 5, expect_bf16: bool = True,
                data_parallel: int = 1,
                reference_first_loss: float | None = None) -> dict:
    """Public `fit()` over a few repeated seeded batches.  Returns the
    trained model and its per-step losses.  With `data_parallel` > 1 the
    model is `distribute()`d first and the batch placement is checked."""
    import jax
    import numpy as np

    from deeplearning4j_tpu.train.listeners import CollectScoresListener

    model = make_lm(**lm)
    batches = lm_batches(lm["vocab_size"], batch, seq, n_batches, seed=3)
    if data_parallel > 1:
        from deeplearning4j_tpu.parallel import ParallelConfig, distribute
        from deeplearning4j_tpu.parallel.data_parallel import place_batch

        devs = jax.devices()[:data_parallel]
        distribute(model, ParallelConfig(data=data_parallel), devices=devs)
        placed = place_batch(model, np.asarray(batches[0].features))
        on = {s.device for s in placed.addressable_shards}
        assert len(on) == data_parallel, (
            f"batch shards sit on {len(on)} device(s), want {data_parallel}")
    scores = CollectScoresListener()
    model.set_listeners(scores)
    with _FlashSpy() as spy:
        model.fit(batches, epochs=epochs)
    losses = [s for _, s in scores.scores]
    steps = n_batches * epochs
    assert len(losses) == steps, f"{len(losses)} losses for {steps} steps"
    assert steps >= 5, "need >= 4 steps after the compiling one"
    assert all(np.isfinite(losses)), f"non-finite loss: {losses}"
    assert losses[-1] < losses[0], f"loss did not fall: {losses}"
    assert model._bf16 == expect_bf16, (
        f"compute dtype is {'bf16' if model._bf16 else 'f32'}")
    assert spy.calls and all(ok for _, ok in spy.calls), (
        f"dense O(T^2) attention ran instead of flash: {spy.calls}")
    if data_parallel > 1:
        for d in devs:
            stats = d.memory_stats()     # None only on the CPU backend
            assert stats is not None or d.platform == "cpu", d
            assert stats is None or stats["bytes_in_use"] > 0, (
                f"{d} holds no memory after the sharded fit")
    if reference_first_loss is not None:
        rel = abs(losses[0] - reference_first_loss) / abs(reference_first_loss)
        assert rel <= MULTICHIP_LOSS_REL, (
            f"first-step loss {losses[0]} vs one-chip "
            f"{reference_first_loss} (rel {rel:.2e})")
    log(f"  losses: {[round(x, 4) for x in losses]}  "
        f"flash calls: {len(spy.calls)}")
    return {"model": model, "losses": losses}


def one_chip_first_loss(lm: dict, *, batch: int, seq: int, parts: int) -> float:
    """The first-step loss of global batch `batch` on ONE chip, as the
    mean over `parts` equal slices — each the first `fit()` step of a
    fresh same-seed model, because the whole batch does not fit one
    chip's memory in a single step."""
    import numpy as np

    from deeplearning4j_tpu.data.dataset import DataSet
    from deeplearning4j_tpu.train.listeners import CollectScoresListener

    whole = lm_batches(lm["vocab_size"], batch, seq, 1, seed=3)[0]
    per = batch // parts
    firsts = []
    for i in range(parts):
        model = make_lm(**lm)
        scores = CollectScoresListener()
        model.set_listeners(scores)
        sl = slice(i * per, (i + 1) * per)
        model.fit([DataSet(whole.features[sl], whole.labels[sl])], epochs=1)
        firsts.append(scores.scores[0][1])
        del model
    return float(np.mean(firsts))


# -- serve -------------------------------------------------------------------

PAGED_IMPL_LABELS = ("pallas", "pallas_int8", "xla", "xla_int8",
                     "xla_chunk", "xla_chunk_int8")


def _paged_counts() -> dict:
    from deeplearning4j_tpu.observe.metrics import registry

    c = registry().counter("dl4jtpu_paged_attention_total")
    return {lab: c.value(impl=lab) for lab in PAGED_IMPL_LABELS}


def _post_generate(url: str, payload: dict) -> tuple[int, dict]:
    import urllib.error
    import urllib.request

    req = urllib.request.Request(
        url + "v1/generate", json.dumps(payload).encode(),
        {"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=600) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


def phase_serve(model, *, prompt_lens, max_new: int, page_size: int,
                prefill_quantum: int) -> dict:
    """`model` behind GenerationEngine + InferenceServer + HTTP: warm each
    prefill bucket, then answer len(prompt_lens) concurrent requests."""
    from concurrent.futures import ThreadPoolExecutor

    import numpy as np

    from deeplearning4j_tpu.ops.paged_attention import select_impl
    from deeplearning4j_tpu.runtime import compile_stats
    from deeplearning4j_tpu.runtime.flags import bucket_length
    from deeplearning4j_tpu.serving.generation import (
        GenerationConfig, GenerationEngine,
    )
    from deeplearning4j_tpu.serving.http import ServingHTTPServer
    from deeplearning4j_tpu.serving.server import InferenceServer

    vocab = int(model.conf.layers[0].n_in)
    n = len(prompt_lens)
    buckets = sorted({bucket_length(t, prefill_quantum) for t in prompt_lens})
    assert len(buckets) >= 2, f"prompts cover one prefill bucket: {buckets}"
    pages_per_seq = -(-(max(buckets) + max_new) // page_size)
    rng = np.random.default_rng(11)
    prompts = [rng.integers(0, vocab, t).astype(np.int32) for t in prompt_lens]

    counts0 = _paged_counts()
    srv = InferenceServer(model)
    eng = GenerationEngine(server=srv, config=GenerationConfig(
        slots=n, page_size=page_size, num_pages=n * pages_per_seq + 1,
        max_pages_per_seq=pages_per_seq, prefill_quantum=prefill_quantum,
        default_max_new=max_new,
    )).start()
    http = ServingHTTPServer(srv).start()
    try:
        # warm-up: one short stream per prefill bucket compiles that
        # bucket's prefill program and (once) the decode step
        for b in buckets:
            code, doc = _post_generate(http.url, {
                "prompt": rng.integers(0, vocab, b).tolist(),
                "max_new_tokens": 2})
            assert code == 200, f"warm-up bucket {b}: {code} {doc}"
        snap = compile_stats.snapshot()
        with ThreadPoolExecutor(n) as pool:
            replies = list(pool.map(
                lambda p: _post_generate(http.url, {
                    "prompt": p.tolist(), "max_new_tokens": max_new}),
                prompts))
        delta = compile_stats.snapshot() - snap
        rows = []
        for p, (code, doc) in zip(prompts, replies):
            assert code == 200 and "error" not in doc, f"{code} {doc}"
            toks = np.asarray(doc["tokens"], np.int32)
            assert toks.shape == (len(p) + max_new,), toks.shape
            assert np.array_equal(toks[:len(p)], p), "prompt not echoed"
            assert ((0 <= toks) & (toks < vocab)).all(), "token out of range"
            rows.append(toks)
        assert delta.fresh_backend_compiles == 0, (
            f"compiles after warm-up: {delta.as_dict()}")
        assert eng.kv.leak_check() is None, eng.kv.leak_check()
        assert eng.kv.used_pages == 0, f"{eng.kv.used_pages} pages held"
    finally:
        http.stop()
        eng.stop()
        srv.stop()
    ran = {k for k, v in _paged_counts().items() if v > counts0[k]}
    assert ran == {select_impl()}, (
        f"paged attention ran {sorted(ran)}, select_impl() says "
        f"{select_impl()!r}")
    gap = _dense_logit_gap(model, rows, [len(p) for p in prompts])
    log(f"  {n} requests over prefill buckets {buckets}; paged impl "
        f"{sorted(ran)}; worst near-arg-max gap {gap['worst_rel']:.2e} "
        f"of max|logit| {gap['logit_absmax']:.3f}")
    assert gap["worst_rel"] <= SERVE_LOGIT_REL, (
        f"an emitted token sits {gap['worst_rel']:.3e} x max|logit| below "
        f"the dense path's arg-max (tolerance {SERVE_LOGIT_REL:.3e})")
    return {"requests": n, "buckets": buckets, "paged_impl": sorted(ran)}


def _dense_logit_gap(model, rows, prompt_lens) -> dict:
    """Teacher-force each served row through the dense forward
    (`model.output` + the head's logits) and measure, at every generated
    position, how far the emitted token's logit sits below the dense
    arg-max."""
    import numpy as np

    head = model.conf.layers[-1]
    width = max(len(r) for r in rows) - 1
    ids = np.zeros((len(rows), width), np.float32)
    for i, r in enumerate(rows):
        ids[i, :len(r) - 1] = r[:-1]     # causal: the pad tail is inert
    hidden = model.output(ids)
    logits = np.asarray(
        head.logits(model.params[head.name], hidden), np.float32)
    absmax = float(np.max(np.abs(logits)))
    worst = 0.0
    for i, (r, t_p) in enumerate(zip(rows, prompt_lens)):
        pos = np.arange(t_p - 1, len(r) - 1)      # logits that chose r[t_p:]
        z = logits[i, pos]
        chosen = z[np.arange(len(pos)), r[t_p:]]
        worst = max(worst, float(np.max(z.max(axis=-1) - chosen)))
    return {"worst_rel": worst / absmax, "logit_absmax": absmax}


# -- kernels -----------------------------------------------------------------

def phase_kernels(*, flash_shape, paged, dequant_kn, dequant_ms=(1, 8, 256),
                  chunk: int = 5, interpret: bool = False) -> None:
    """Each Pallas kernel against its XLA reference under "highest"
    matmul precision.  `flash_shape` = (B, T, H, D); `paged` = dict(slots,
    heads, head_dim, page_size, pages_per_seq, num_pages)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deeplearning4j_tpu.ops import dequant_matmul as dm
    from deeplearning4j_tpu.ops import flash_attention as fa
    from deeplearning4j_tpu.ops import paged_attention as pa
    from deeplearning4j_tpu.serving.kv_cache import quantize_page_rows

    def highest(f, *args):
        with jax.default_matmul_precision("highest"):
            return jax.jit(f)(*args)

    def close(name, got, want, *, rel=None, atol=None):
        got = np.asarray(got, np.float32)
        want = np.asarray(want, np.float32)
        assert np.isfinite(got).all(), f"{name}: non-finite output"
        bound = atol if atol is not None else rel * float(np.max(np.abs(want)))
        err = float(np.max(np.abs(got - want)))
        assert err <= bound, f"{name}: max error {err:.3e} > {bound:.3e}"
        log(f"  {name}: max error {err:.2e} (bound {bound:.2e})")

    rng = np.random.default_rng(0)

    # flash attention forward and backward, bf16
    b, t, h, d = flash_shape
    q, k, v = (jnp.asarray(rng.normal(size=flash_shape), jnp.bfloat16)
               for _ in range(3))

    def dense(q, k, v):
        q, k, v = (a.astype(jnp.float32) for a in (q, k, v))
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(d)
        s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)

    def flash(q, k, v):
        return fa.flash_attention(q, k, v, causal=True,
                                  interpret=interpret).astype(jnp.float32)

    close("flash fwd", jax.jit(flash)(q, k, v), highest(dense, q, k, v),
          rel=FLASH_REL)
    grads = jax.jit(jax.grad(lambda *a: jnp.sum(flash(*a) ** 2), (0, 1, 2)))
    want = highest(jax.grad(lambda *a: jnp.sum(dense(*a) ** 2), (0, 1, 2)),
                   q, k, v)
    for name, g, w in zip("qkv", grads(q, k, v), want):
        close(f"flash grad d{name}", g, w, rel=FLASH_REL)

    # paged attention: f32 pages, int8 pages, and the C-token chunk route
    s_, ps, mp, n_pg = (paged["slots"], paged["page_size"],
                        paged["pages_per_seq"], paged["num_pages"])
    hp, dh = paged["heads"], paged["head_dim"]
    cap = mp * ps
    pq = jnp.asarray(rng.normal(size=(s_, hp, dh)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(n_pg, ps, hp, dh)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(n_pg, ps, hp, dh)), jnp.float32)
    tbl = jnp.asarray(rng.integers(1, n_pg, (s_, mp)), jnp.int32)
    # idle slot, one row, page boundaries either side, a full table
    lens = jnp.asarray(
        ([0, 1, ps, ps + 1, cap] + list(rng.integers(1, cap, s_)))[:s_],
        jnp.int32)

    def paged_kernel(*a, **kw):
        return pa.paged_attention(*a, impl="pallas", interpret=interpret,
                                  **kw)

    # each case twice: the (P, ps, H, Dh) pool, and the form the serving
    # step uses — layer 1 of a whole (L, P, ps, H, Dh) stack, read in
    # place (layer 0 holds the OTHER pool's values)
    def stacked(a, b):
        return jnp.stack([b, a])

    want = highest(pa._xla_paged_attention, pq, kp, vp, tbl, lens)
    close("paged f32", jax.jit(paged_kernel)(pq, kp, vp, tbl, lens), want,
          atol=PAGED_ATOL)
    close("paged f32 layer-indexed",
          jax.jit(lambda *a: paged_kernel(*a, layer=1))(
              pq, stacked(kp, vp), stacked(vp, kp), tbl, lens),
          want, atol=PAGED_ATOL)
    (kq, ks), (vq, vs) = quantize_page_rows(kp), quantize_page_rows(vp)

    def int8_kernel(q, k, v, t, n, a, b, **kw):
        return paged_kernel(q, k, v, t, n, k_scale=a, v_scale=b, **kw)

    want = highest(pa._xla_paged_attention, pq, kq, vq, tbl, lens, ks, vs)
    close("paged int8",
          jax.jit(int8_kernel)(pq, kq, vq, tbl, lens, ks, vs), want,
          atol=PAGED_ATOL)
    close("paged int8 layer-indexed",
          jax.jit(lambda *a: int8_kernel(*a, layer=1))(
              pq, stacked(kq, vq), stacked(vq, kq), tbl, lens,
              stacked(ks, vs), stacked(vs, ks)),
          want, atol=PAGED_ATOL)
    cq = jnp.asarray(rng.normal(size=(s_, chunk, hp, dh)), jnp.float32)
    attend = jnp.where(
        lens[:, None] > 0,
        jnp.minimum(lens[:, None] + jnp.arange(chunk)[None, :] + 1, cap), 0)

    def chunk_kernel(*a, **kw):
        return pa.paged_attention_chunk(*a, impl="pallas",
                                        interpret=interpret, **kw)

    want = highest(pa._xla_paged_attention_chunk, cq, kp, vp, tbl, attend)
    close(f"paged chunk C={chunk}",
          jax.jit(chunk_kernel)(cq, kp, vp, tbl, attend), want,
          atol=PAGED_ATOL)
    close(f"paged chunk C={chunk} layer-indexed",
          jax.jit(lambda *a: chunk_kernel(*a, layer=1))(
              cq, stacked(kp, vp), stacked(vp, kp), tbl, attend),
          want, atol=PAGED_ATOL)

    # fused dequant-matmul
    kk, nn = dequant_kn
    w = jnp.asarray(rng.integers(-127, 128, (kk, nn)), jnp.int8)
    sc = jnp.asarray(rng.uniform(1e-3, 1e-2, (nn,)), jnp.float32)
    for m in dequant_ms:
        x = jnp.asarray(rng.normal(size=(m, kk)), jnp.float32)
        close(f"dequant M={m}",
              jax.jit(lambda x, w, sc: dm.dequant_matmul(
                  x, w, sc, impl="pallas", interpret=interpret))(x, w, sc),
              highest(dm._xla_dequant_dot, x, w, sc), rel=DEQUANT_REL)


# -- main --------------------------------------------------------------------

def main() -> int:
    t_all = time.perf_counter()
    info = phase_device()      # exits non-zero here without a TPU
    import jax

    from deeplearning4j_tpu.runtime import compile_stats
    from deeplearning4j_tpu.runtime.backend import init_compile_cache

    cache_dir = init_compile_cache()
    log(f"compile cache: {cache_dir}")
    n_dev = len(jax.devices())
    phases: dict[str, dict] = {}

    def run(name: str, fn):
        """Run one phase and return what it returned (None when it
        failed); a failure is recorded and fails the script."""
        t0 = time.perf_counter()
        out = None
        try:
            out = fn()
            status = "ok"
        except Exception as exc:      # recorded below; exit code is non-zero
            traceback.print_exc()
            status = f"FAILED: {type(exc).__name__}: {exc}"
        wall = round(time.perf_counter() - t0, 1)
        phases[name] = {"status": status, "wall_s": wall}
        log(f"{name}: {status} ({wall}s)")
        return out

    def skip(name: str, why: str) -> None:
        phases[name] = {"status": f"not run ({why})", "wall_s": 0.0}
        log(f"{name}: not run ({why})")

    trained = run("train", lambda: phase_train(LM, batch=4, seq=2048))
    if trained is not None:
        run("serve", lambda: phase_serve(
            trained["model"],
            prompt_lens=(64, 200, 256, 100, 300, 400, 480, 512),
            max_new=32, page_size=16, prefill_quantum=256))
    else:
        skip("serve", "train failed")
    trained = None                    # free the model before the kernels
    run("kernels", lambda: phase_kernels(
        flash_shape=(4, 2048, 8, 128),
        paged=dict(slots=8, heads=8, head_dim=128, page_size=16,
                   pages_per_seq=34, num_pages=300),
        dequant_kn=(1024, 4096)))
    if n_dev >= 4:
        run("multichip", lambda: phase_train(
            LM, batch=16, seq=2048, data_parallel=4,
            reference_first_loss=one_chip_first_loss(
                LM, batch=16, seq=2048, parts=4)))
    else:
        skip("multichip", f"{n_dev} device")

    cs = compile_stats.snapshot()
    summary = {
        "ok": all(p["status"] == "ok" or p["status"].startswith("not run")
                  for p in phases.values()),
        "device": info["device"],
        "versions": info["versions"],
        "phases": phases,
        "compile_cache": {
            "dir": cache_dir,
            "persistent_hits": cs.persistent_cache_hits,
            "persistent_puts": cs.persistent_cache_puts,
            "backend_compiles": cs.backend_compiles,
            "fresh_backend_compiles": cs.fresh_backend_compiles,
        },
        "wall_s": round(time.perf_counter() - t_all, 1),
    }
    if not summary["ok"]:
        print(json.dumps(summary), file=sys.stderr, flush=True)
        return 1
    log("summary: " + json.dumps(summary))
    print(result_line(info["device"]), flush=True)
    return 0


def result_line(device: dict) -> str:
    """The last stdout line of a run in which every phase passed: exactly
    `ok` and the device as jax reports it, nothing else (the per-phase
    detail is the `summary:` line before it)."""
    return json.dumps({"ok": True, "device": {
        "platform": str(device["platform"]), "kind": str(device["kind"]),
        "count": int(device["count"])}})


if __name__ == "__main__":
    sys.exit(main())
