"""The `phi4flash` stack (SambaY: Mamba-1 and sliding-window layers, one
full-attention layer whose keys and values the cross layers read, gated
memory units, a tied head) on the serving path, against the family's plain
float32 reference — at a small size on the CPU with the published layer
pattern: d 64, 8 query heads and 4 key/value heads of 8 (4 and 2 pairs), a
window of 8, Mamba state 4 x 128, and 8 layers (mamba, swa, mamba, swa,
mamba, full, gmu, cross).

What is held to what: the engine (`GenerationEngine` over `PagedKVCache`'s
slot pools and its one shared row pool: chunked prefill that carries and
stops the state, then decode) and the DSL layer's whole-sequence `apply` to
`benchmarks/families/phi4flash.py`'s reference, which imports nothing from
the package.  Logits, not tokens: every comparison reads how far below the
reference's arg-max logit the engine's token lies.  On the CPU both sides
compute in float32 (`tests/conftest.py` pins matmuls to it), so a right
engine lands within rounding of the reference — 1e-4 of logits of size
30-50 — and every fault these tests look for moves the logits by their
scale.
"""

import json
import os
import shutil
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import run as bench_run
from benchmarks.harness import spec
from deeplearning4j_tpu.nn.conf import HybridDecoder
from deeplearning4j_tpu.ops import generation as dense
from deeplearning4j_tpu.ops import shared_kv_attention as skv
from deeplearning4j_tpu.serving import generation as serving
from deeplearning4j_tpu.serving.generation import (
    GenerationConfig, GenerationEngine,
)
from deeplearning4j_tpu.serving.kv_cache import PagedKVCache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FAMILY = spec.family({"family": "phi4flash"})

TINY = {
    "family": "phi4flash", "hidden_size": 64, "num_attention_heads": 8,
    "num_key_value_heads": 4, "intermediate_size": 96, "sliding_window": 8,
    "num_hidden_layers": 8, "mb_per_layer": 2, "mamba_d_state": 4,
    "mamba_d_conv": 4, "mamba_expand": 2, "mamba_dt_rank": 8,
    "vocab_size": 96, "layer_norm_eps": 1e-5,
}
ENGINE = dict(slots=3, page_size=8, num_pages=40, max_pages_per_seq=12,
              prefill_quantum=16, kv_dtype="f32")
VOCAB = TINY["vocab_size"]
#: a right engine: f32 against f32, rounding only (logits are 30-50)
GAP = 1e-4


def _model(seed=3):
    model = FAMILY.build_model(TINY)
    model.params = jax.jit(
        lambda key: FAMILY._init_tree(model, key, jnp.float32))(
            jax.random.key(seed))
    model.net_state, model.opt_state = {}, None
    return model


@pytest.fixture(scope="module")
def model():
    return _model()


@pytest.fixture(scope="module")
def reference():
    return FAMILY.make_reference_logits(TINY)


@pytest.fixture(scope="module")
def engine(model):
    eng = GenerationEngine(model=model,
                           config=GenerationConfig(**ENGINE)).start()
    yield eng
    eng.stop()


def _prompt(seed, t_p):
    return np.random.default_rng(seed).integers(0, VOCAB, t_p,
                                                dtype=np.int32)


def _gap(reference, model, row, t_p):
    """How far each emitted token's reference logit sits below the
    reference's arg-max at its position, over the generated part."""
    z = np.asarray(reference(model.params, jnp.asarray(row[:-1])))[t_p - 1:]
    return float(np.max(z.max(-1) - z[np.arange(len(z)), row[t_p:]]))


# -- the stack ------------------------------------------------------------------

def test_plan_states_what_each_layer_keeps(model):
    stack = dense._plan(model)
    assert isinstance(stack.final, HybridDecoder) and stack.head is stack.final
    assert [b.kind for b in stack.blocks] == FAMILY.layer_kinds(TINY)
    assert [dense.cache_rows(b) for b in stack.blocks] == [
        {}, {}, {}, {}, {}, {"kv": (64,)}, {}, {}]
    mamba = {"ssm": ((128, 4), "f32"), "conv": ((3, 128), "f32")}
    ring = {"ring": ((8, 64), "kv")}
    assert [dense.slot_rows(b) for b in stack.blocks] == [
        mamba, ring, mamba, ring, mamba, {}, {}, {}]
    assert [b.tap for b in stack.blocks] == [False] * 4 + [True] + [False] * 3
    with pytest.raises(ValueError, match="GenerationEngine"):
        dense.generate(model, np.zeros((1, 4), np.int32), 2)
    with pytest.raises(ValueError, match="has none"):
        dense.prompt_forward(stack, model.params, np.zeros((1, 4), np.int32),
                             jnp.float32)


def test_plan_error_names_the_three_stacks():
    from deeplearning4j_tpu.models.sequential import SequentialModel
    from deeplearning4j_tpu.nn.conf import (
        Embedding, InputType, NeuralNetConfiguration, RnnOutputLayer,
    )
    from deeplearning4j_tpu.nn.conf.layers import Dense

    conf = (NeuralNetConfiguration.builder().list()
            .layer(Embedding(n_in=8, n_out=4))
            .layer(Dense(n_out=4))
            .layer(RnnOutputLayer(n_out=8))
            .set_input_type(InputType.recurrent(1)).build())
    with pytest.raises(ValueError) as err:
        dense._plan(SequentialModel(conf))
    for stack in ("TransformerEncoderBlock*", "LatentSparseDecoder",
                  "HybridDecoder"):
        assert stack in str(err.value)


@pytest.mark.parametrize("kinds, says", [
    (("mamba", "swa", "gmu"), "exactly one"),
    (("mamba", "full", "swa"), "come before"),
    (("swa", "full", "gmu"), "memory of a mamba"),
])
def test_decoder_refuses_a_pattern_it_cannot_run(kinds, says):
    with pytest.raises(ValueError, match=says):
        HybridDecoder(d_model=8, n_heads=2, n_kv_heads=2, head_dim=4,
                      layer_types=kinds)


def test_dsl_output_is_the_reference(model, reference):
    """The model's own whole-prompt forward (the DSL layer's `apply`: every
    layer over every row from position 0, the scan unchunked) against the
    reference, logits through the tied head."""
    ids = np.random.default_rng(1).integers(0, VOCAB, (2, 40)).astype(
        np.int32)
    hidden = np.asarray(model.output(ids))
    emb = np.asarray(model.params["embed"]["W"])
    for b in range(2):
        z = np.asarray(reference(model.params, jnp.asarray(ids[b])))
        assert np.abs(hidden[b] @ emb.T - z).max() < GAP


def test_the_window_changes_the_reference_and_the_tap_feeds_the_gmu(model):
    """The reference is sensitive to what the controls below break: a
    window over the whole context, and the memory the GMUs read."""
    ids = jnp.asarray(_prompt(2, 30))
    with jax.default_matmul_precision("highest"):
        base = FAMILY.reference_hidden(TINY, model.params, ids)
        wide = FAMILY.reference_hidden(TINY, model.params, ids, window=10 ** 6)
    # the first `window` rows see the same keys either way
    assert float(jnp.max(jnp.abs(base[:8] - wide[:8]))) < 1e-5
    assert float(jnp.max(jnp.abs(base[8:] - wide[8:]))) > 0.1


# -- the shared K/V kernel ---------------------------------------------------------

def _kernel_case(seed=0, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    s, p, kp, hd, ps, n_pages, mp = 4, 4, 2, 8, 8, 30, 6
    pool = jnp.asarray(rng.standard_normal((1, n_pages, ps, 128)), dtype)
    tbl = jnp.asarray(rng.permutation(np.arange(1, n_pages))[:s * mp]
                      .reshape(s, mp).astype(np.int32))
    lens = jnp.asarray(np.array([0, 5, 17, 48], np.int32))
    q = jnp.asarray(rng.standard_normal((s, p, 2, hd)), dtype)
    return q, pool, tbl, lens, kp


def test_shared_kv_kernel_is_the_gather_reference():
    """The Pallas kernel (interpret mode) against its XLA form, over an idle
    slot, a slot inside one page, one ending mid-page past a turn, and one
    filling its table: idle slots write zeros."""
    q, pool, tbl, lens, kp = _kernel_case()
    want = skv.shared_kv_attention(q, pool, tbl, lens, kv_pairs=kp,
                                   impl="xla")
    got = skv.shared_kv_attention(q, pool, tbl, lens, kv_pairs=kp,
                                  impl="pallas", interpret=True)
    assert got.shape == (4, 4, 2, 16)
    assert float(jnp.max(jnp.abs(got - want))) < 1e-5
    assert float(jnp.max(jnp.abs(got[0]))) == 0.0


def test_shared_kv_kernel_ignores_what_lies_past_a_slots_length():
    q, pool, tbl, lens, kp = _kernel_case(1)
    before = skv.shared_kv_attention(q, pool, tbl, lens, kv_pairs=kp,
                                     impl="pallas", interpret=True)
    # slot 1 attends 5 rows of its first page: poison the rest of it
    page = int(tbl[1, 0])
    poisoned = pool.at[0, page, 5:].set(jnp.nan)
    after = skv.shared_kv_attention(q, poisoned, tbl, lens, kv_pairs=kp,
                                    impl="pallas", interpret=True)
    assert bool(jnp.all(jnp.isfinite(after)))
    assert float(jnp.max(jnp.abs(after - before))) == 0.0


# -- the pools ---------------------------------------------------------------------

def test_slot_pools_live_beside_the_row_pool_and_are_reported():
    kv = PagedKVCache(num_pages=6, page_size=8, kv_dtype="bf16",
                      rows={"kv": (1, (128,))},
                      slot_rows={"ssm": (2, 3, (16, 4), "f32"),
                                 "ring": (1, 3, (8, 128), "bf16")})
    pages, ssm, ring = kv.pool()
    assert pages.shape == (1, 6, 8, 128) and pages.dtype == jnp.bfloat16
    assert ssm.shape == (2, 3, 16, 4) and ssm.dtype == jnp.float32
    assert ring.shape == (1, 3, 8, 128) and ring.dtype == jnp.bfloat16
    st = kv.stats()
    assert st["slot_rows"] == {"ssm": [2, 3, 16, 4], "ring": [1, 3, 8, 128]}
    assert st["slot_pool_bytes"] == 2 * 3 * 64 * 4 + 3 * 8 * 128 * 2
    assert st["bytes_per_token"] == 256 and st["slots_held"] == 0
    kv.alloc("a", 2)
    kv.claim_slot("a", 1)
    assert kv.stats()["slots_held"] == 1 and kv.leak_check() is None
    kv.claim_slot("ghost", 2)                 # a slot without pages
    assert "holds no page" in kv.leak_check()
    kv.release("ghost")
    kv.alloc("b", 1)
    kv.claim_slot("b", 1)
    assert kv.leak_check() == "slot held by two streams"
    kv.release("b")
    assert kv.release("a") == 2 and kv.leak_check() is None
    assert kv.stats()["slots_held"] == 0
    # a failed dispatch that consumed the pool: every pool is made anew
    ssm.delete()
    assert kv.revive() and kv.stats()["pool_rebuilds"] == 1
    assert [a.shape for a in kv.pool()] == [(1, 6, 8, 128), (2, 3, 16, 4),
                                            (1, 3, 8, 128)]


def test_slot_pools_need_row_pools():
    with pytest.raises(ValueError, match="beside row pools"):
        PagedKVCache(n_layers=1, n_heads=2, head_dim=8, num_pages=4,
                     page_size=8, slot_rows={"ssm": (1, 2, (4, 4), "f32")})


def test_engine_sizes_the_pools_from_the_stack(engine):
    st = engine.kv.stats()
    assert st["rows"] == {"kv": [1, 128]}          # 64 wide, stored 128
    assert st["slot_rows"] == {"ssm": [3, 3, 128, 4], "conv": [3, 3, 3, 128],
                               "ring": [2, 3, 8, 64]}


# -- the engine against the reference ----------------------------------------------

@pytest.mark.parametrize("t_p, n_new", [
    (1, 6),      # length 1: the conv and the ring hold one position
    (37, 6),     # not a multiple of the 16-row chunk: 11 pad rows in chunk 2
    (20, 4),     # longer than the window of 8: the ring has wrapped
    (5, 12),     # crosses the window during decode
    (48, 5),     # three whole chunks, no pad
])
def test_prefill_then_decode_through_the_pools_is_the_reference(
        engine, model, reference, t_p, n_new):
    row = engine.generate(_prompt(t_p, t_p), n_new, timeout=300)
    assert len(row) == t_p + n_new
    assert _gap(reference, model, row, t_p) < GAP
    assert engine.kv.used_pages == 0 and engine.kv.leak_check() is None
    assert engine.kv.stats()["slots_held"] == 0


def test_streams_of_unequal_length_share_a_step(engine, model, reference):
    prompts = [_prompt(9, 41), _prompt(10, 12), _prompt(11, 3)]
    reqs = [engine.submit(p, 10) for p in prompts]
    for p, r in zip(prompts, reqs):
        assert _gap(reference, model, r.result(timeout=300), len(p)) < GAP


def test_a_reused_slot_starts_from_a_reset_state(model):
    """One slot, two streams in turn: the second gives the same tokens as a
    fresh engine (the first chunk writes the slot's state from scratch,
    whatever the stream before left in it)."""
    cfg = GenerationConfig(**{**ENGINE, "slots": 1})
    second = _prompt(21, 27)
    eng = GenerationEngine(model=model, config=cfg).start()
    try:
        eng.generate(_prompt(20, 45), 9, timeout=300)
        reused = eng.generate(second, 9, timeout=300)
    finally:
        eng.stop()
    fresh_eng = GenerationEngine(model=model, config=cfg).start()
    try:
        fresh = fresh_eng.generate(second, 9, timeout=300)
    finally:
        fresh_eng.stop()
    assert (reused == fresh).all()


def test_recurrent_state_refuses_a_drafter(model):
    with pytest.raises(ValueError, match="recurrent state"):
        GenerationEngine(model=model, config=GenerationConfig(
            **ENGINE, spec_k=2, spec_drafter="ngram"))


def test_quantum_must_tile_the_window():
    wide = FAMILY.build_model({**TINY, "sliding_window": 16})
    with pytest.raises(ValueError, match="window 16"):
        GenerationEngine(model=wide, config=GenerationConfig(
            **{**ENGINE, "prefill_quantum": 24}))


def test_engine_counts_what_the_state_was_read_for(model):
    """Queued before `start()`, the two streams are admitted by one refill:
    prompts 20 and 7 (19 + 6 rows skipped by the cross layers), then decode
    steps that each read seq_len + 1 shared rows and min(that, 8) ring rows
    per live slot."""
    eng = GenerationEngine(model=model, config=GenerationConfig(**ENGINE))
    reqs = [eng.submit(_prompt(30, 20), 4), eng.submit(_prompt(31, 7), 6)]
    eng.start()
    try:
        for r in reqs:
            r.result(timeout=300)
        st = eng.stats()
    finally:
        eng.stop()
    # decode rows: stream a at positions 20..22, stream b at 7..11
    shared = sum(p + 1 for p in range(20, 23)) + sum(
        p + 1 for p in range(7, 12))
    window = sum(min(p + 1, 8) for p in range(20, 23)) + sum(
        min(p + 1, 8) for p in range(7, 12))
    assert st["hybrid"] == {"shared_kv_rows_attended": shared,
                            "window_rows_attended": window,
                            "prefill_rows": 27,
                            "prefill_rows_skipped_cross": 25}


# -- the controls: each fault the comparison must see ------------------------------

def test_control_pad_rows_advancing_the_state_fails(model, reference,
                                                    monkeypatch):
    """Pad rows that advance the scan, the conv and the rings (every row of
    a chunk taken for the prompt's): a prompt of 37 in 16-row chunks has 11
    pad rows, and the decode that follows must leave the reference."""
    monkeypatch.setattr(serving, "_real_rows",
                        lambda prompt_len, start, c_rows: c_rows)
    eng = GenerationEngine(model=model,
                           config=GenerationConfig(**ENGINE)).start()
    try:
        row = eng.generate(_prompt(37, 37), 6, timeout=300)
    finally:
        eng.stop()
    z = np.asarray(reference(model.params, jnp.asarray(row[:-1])))
    assert _gap(reference, model, row, 37) > 2 ** -5 * np.abs(z).max()


def test_control_window_over_the_whole_context_fails(engine, model):
    """A reference whose window layers attend the whole context: the
    engine's rows, right by `GAP`, sit far below its arg-max."""
    wide = FAMILY.make_reference_logits(TINY, window=10 ** 6)
    row = engine.generate(_prompt(40, 30), 8, timeout=300)
    z = np.asarray(wide(model.params, jnp.asarray(row[:-1])))
    assert _gap(wide, model, row, 30) > 2 ** -5 * np.abs(z).max()


# -- the family's counts -------------------------------------------------------------

def test_request_flops_against_a_hand_count():
    d, e, hd, ff, w = 64, 128, 8, 96, 8
    mamba = d * 2 * e + e * (8 + 2 * 4) + 8 * e + e * d
    attn = d * 64 + 2 * d * 32 + 64 * d
    per_key = 2 * 8 * 3 * hd
    scan = 2 * e * (2 * 4 + 4)
    ffn = 3 * d * ff

    def row(kind, ctx):
        m = {"mamba": mamba, "swa": attn, "full": attn, "gmu": 2 * d * e,
             "cross": d * 64 + 64 * d}[kind]
        f = 2 * (m + ffn)
        if kind == "swa":
            f += per_key * min(ctx, w)
        if kind in ("full", "cross"):
            f += per_key * ctx
        if kind == "mamba":
            f += scan
        return f

    kinds = FAMILY.layer_kinds(TINY)
    kv = 2 * 2 * d * 32
    t_p, n = 10, 3
    want = (sum(row(k, t) for t in range(1, t_p + 1) for k in kinds[:5])
            + (t_p - 1) * kv + sum(row(k, t_p) for k in kinds[5:])
            + sum(row(k, t) for t in range(t_p + 1, t_p + n)
                  for k in kinds)
            + n * 2 * d * VOCAB)
    assert FAMILY.request_flops(TINY, t_p, n) == pytest.approx(want)


# -- the benchmark's cell, rehearsed --------------------------------------------------

@pytest.fixture(scope="module")
def tiny_bench(tmp_path_factory):
    """A throw-away copy of the benchmark with a tiny twin of
    `phi4flash_reason_sat` ADDED: a configuration, a traffic mix, a cell."""
    root = str(tmp_path_factory.mktemp("bench"))
    home = os.path.join(root, "benchmarks")
    shutil.copytree(os.path.join(ROOT, "benchmarks"), home,
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        doc = json.load(f)
    with open(os.path.join(home, "traffic", "phi4flash_reason_sat.json")) as f:
        traffic = json.load(f)
    traffic["arrivals"]["rate_per_s"] = 30.0
    traffic["classes"][0]["prompt_len"].update(min=17, max=40)
    traffic["classes"][0]["output_len"].update(min=3, max=8)
    traffic["engine"] = {**ENGINE, "slots": 2, "max_queue": 1024}
    traffic["trace_seconds"] = 0.3
    with open(os.path.join(home, "traffic", "tiny_phi.json"), "w") as f:
        json.dump(traffic, f)
    with open(os.path.join(home, "configs", "tiny_phi.json"), "w") as f:
        json.dump(TINY, f)
    doc["configs"].append({"name": "tiny_phi", "source": "none",
                           "reduced": [], "why": "test preset",
                           "file": "benchmarks/configs/tiny_phi.json"})
    doc["workloads"].append({"name": "tiny_phi", "config": "tiny_phi",
                             "traffic": "tiny_phi", "chips": 1,
                             "why": "test"})
    for m in doc["end_to_end"] + doc["per_layer"]:
        if "phi4flash_reason_sat" in m.get("workloads", ()):
            m["workloads"].append("tiny_phi")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(doc, f)
    return root


def _run(root, seed, trace=False):
    return bench_run.run_cell(root, "tiny_phi", seed=seed, seconds=1.5,
                              trace=trace, t_start=time.perf_counter(),
                              require_chip=False)


@pytest.mark.parametrize("trace", [False, True])
def test_the_cell_rehearses_correct_on_cpu(tiny_bench, trace):
    doc, correct, attempted, failed, obs, info = _run(
        tiny_bench, 2 ** 31 + 17, trace)
    out = bench_run.result(doc, obs, correct=correct, attempted=attempted,
                           failed=failed, trace=trace)
    json.dumps(out)
    assert out["correct"] is True, info
    assert attempted > 0 and failed == 0
    assert info["check"]["checked_streams"] == 3
    assert info["check"]["worst_rel_gap"] < 1e-4
    assert obs.counters["compiles_in_window"] == 0
    if not trace:
        assert set(out["metrics"]) == {"serve_tok_s", "setup_s"}
        return
    # no device plane on the CPU: readers of the trace leave their metric
    # out; the program's counters read the same here as on the chip
    got = out["metrics"]
    assert set(got) <= {m["name"] for m in obs.cell.per_layer}
    # 1 - 1 / prompt length per prompt, prompts of 16-40 rows
    assert 90 < got["cross_prefill_skip_share.phi4f"]["value"] < 97
    assert got["kv_alloc_failures.phi4f"]["value"] == 0


@pytest.mark.parametrize("control", ["window", "pads", "fp8"])
def test_the_cells_check_fails_each_control(tiny_bench, monkeypatch,
                                            control):
    """The harness's own check, unchanged, must say not correct: against a
    reference whose window layers attend the whole context, with the
    engine's pad rows advancing the slot state, and against the reference
    computed in float8, the type below bfloat16."""
    fam = spec.family(TINY, os.path.join(tiny_bench, "benchmarks"))
    real = fam.make_reference_gap
    if control == "window":
        monkeypatch.setattr(fam, "make_reference_gap",
                            lambda cfg: real(cfg, window=10 ** 6))
    elif control == "fp8":
        monkeypatch.setattr(fam, "make_reference_gap",
                            lambda cfg: real(cfg, rounding=fam.round_fp8))
    else:
        monkeypatch.setattr(serving, "_real_rows",
                            lambda prompt_len, start, c_rows: c_rows)
    _, correct, attempted, failed, _, info = _run(tiny_bench, 2 ** 31 + 18)
    assert attempted > 0 and failed == 0
    assert correct is False
    check = info["check"]
    assert check["checked_streams"] == 3 and check["kv_leak"] is None
    assert check["worst_rel_gap"] > check["tolerance"]
