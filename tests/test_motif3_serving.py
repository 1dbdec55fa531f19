"""The `motif3` block (grouped differential attention over latent keys and
values, 128-row latent rings on the window layers beside one paged latent
pool, a 4-stream mHC residual, PolyNorm experts as one chip's share) on the
serving path, against the family's plain float32 reference — at a small size
on the CPU: d 64, 10 heads (8 signal + 2 noise) over 2 key/value groups, a
window of 8, 8 routed experts of which this "chip" holds 4, and the
benchmark cut's five-layer pattern (dense/window, experts/window,
experts/full, experts/window x 2).

What is held to what: the engine (`GenerationEngine` over `PagedKVCache`:
chunked prefill writing the rings and the full layer's pages, then decode
through both) and the DSL layer's whole-sequence `apply` to
`benchmarks/families/motif3.py`'s reference, which imports nothing from the
package; the expert layer's shares to the uncut layer.  Every comparison is
float32 on both sides, so an emitted token's reference logit sits at the
reference's arg-max to rounding: tolerances of 1e-4 (absolute, logits of
order 1) leave room for summation order and nothing for a wrong row.
"""

import copy
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
from deeplearning4j_tpu.nn.conf.attention import LATENT_RING
from deeplearning4j_tpu.ops import generation as dense
from deeplearning4j_tpu.ops import moe
from deeplearning4j_tpu.serving.generation import (
    GenerationConfig, GenerationEngine,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(ROOT, "benchmarks", "configs",
                       "motif-3-beta-ep8-l5.json")) as _f:
    FULL = json.load(_f)
FAMILY = spec.family(FULL)

TINY = copy.deepcopy(FULL)
TINY.update({
    "hidden_size": 64, "num_attention_heads": 10, "num_key_value_heads": 2,
    "num_noise_heads": 2, "q_lora_rank": 32, "kv_lora_rank": 16,
    "head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 8,
    "intermediate_size": 64, "moe_intermediate_size": 16, "num_experts": 4,
    "experts_top_k": 2, "sliding_window": 8, "vocab_size": 96})
TINY["rope_scaling"] = dict(FULL["rope_scaling"], factor=4,
                            original_max_position_embeddings=16)
TINY["deployment"] = dict(FULL["deployment"], num_experts_published=8,
                          held_experts=[2, 6])
TINY["system"] = dict(FULL["system"], vocab_chunk=64)
ENGINE = dict(slots=3, page_size=8, num_pages=40, max_pages_per_seq=12,
              prefill_quantum=16, kv_dtype="f32")
VOCAB = TINY["vocab_size"]
WINDOW = TINY["sliding_window"]


def _model(cfg=TINY, seed=3):
    model = FAMILY.build_model(cfg)
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
def sound_gap():
    return FAMILY.make_reference_gap(TINY, tie=0)


@pytest.fixture(scope="module")
def engine(model):
    eng = GenerationEngine(model=model,
                           config=GenerationConfig(**ENGINE)).start()
    yield eng
    eng.stop()


#: the reference runs on every row padded to this length (it is causal: the
#: pad changes no logit before it), so it compiles once
T_REF = 64


def _gap(reference, model, row, t_p):
    """How far each emitted token's reference logit sits below the
    reference's arg-max at its position, over the generated part."""
    tokens = np.zeros(T_REF, np.int32)
    tokens[:len(row) - 1] = row[:-1]
    z = np.asarray(reference(model.params, jnp.asarray(tokens)))[
        t_p - 1:len(row) - 1]
    return float(np.max(z.max(-1) - z[np.arange(len(z)), row[t_p:]]))


def _prompt(t_p, seed=None):
    return np.random.default_rng(t_p if seed is None else seed).integers(
        0, VOCAB, t_p, dtype=np.int32)


# -- the stack ------------------------------------------------------------------------

def test_plan_keeps_rings_for_window_layers_and_pages_for_the_full_one(
        model, engine):
    stack = dense._plan(model)
    assert [dense.cache_rows(b) for b in stack.blocks] == [
        {}, {}, {"latent": (24,)}, {}, {}]
    assert [dense.slot_rows(b) for b in stack.blocks] == [
        {LATENT_RING: ((WINDOW, 24), "kv")}] * 2 + [{}] + [
        {LATENT_RING: ((WINDOW, 24), "kv")}] * 2
    st = engine.kv.stats()
    assert st["rows"] == {"latent": [1, 128]}      # 24 wide, stored 128
    assert st["slot_rows"] == {LATENT_RING: [4, 3, WINDOW, 24]}
    assert st["slot_pool_bytes"] == 4 * 3 * WINDOW * 24 * 4
    with pytest.raises(ValueError, match="ring"):
        GenerationEngine(model=model, config=GenerationConfig(
            **ENGINE, spec_k=2, spec_drafter="ngram"))


def test_dsl_output_is_the_reference(model, reference):
    ids = np.random.default_rng(1).integers(0, VOCAB, (2, 40)).astype(
        np.int32)
    hidden = np.asarray(model.output(ids))
    head = np.asarray(model.params["head"]["W"])
    for b in range(2):
        z = np.asarray(reference(model.params, jnp.asarray(ids[b])))
        assert np.abs(hidden[b] @ head - z).max() < 1e-4


# -- the engine against the reference ---------------------------------------------------

@pytest.mark.parametrize("t_p, n_new", [
    (1, 6),       # one row
    (5, 2),       # under the window
    (12, 4),      # over the window, one chunk
    (5, 9),       # crosses the window while decoding
    (37, 6),      # not a multiple of the chunk (16)
    (50, 9),      # four chunks: rings carried from chunk to chunk
])
def test_prefill_then_decode_through_rings_and_pool_is_the_reference(
        engine, model, reference, t_p, n_new):
    prompt = _prompt(t_p)
    row = engine.generate(prompt, n_new, timeout=300)
    assert len(row) == t_p + n_new and (row[:t_p] == prompt).all()
    assert _gap(reference, model, row, t_p) < 1e-4
    assert engine.kv.used_pages == 0 and engine.kv.leak_check() is None


def test_a_reused_slots_ring_is_written_anew(model, reference):
    """One slot: a long stream fills the rings, then a short one is seated
    in the same slot and must read none of its rows."""
    eng = GenerationEngine(model=model, config=GenerationConfig(
        **{**ENGINE, "slots": 1})).start()
    try:
        eng.generate(_prompt(30), 5, timeout=300)
        short = _prompt(3, seed=99)
        row = eng.generate(short, 8, timeout=300)
    finally:
        eng.stop()
    assert _gap(reference, model, row, 3) < 1e-4


def test_engine_counts_the_rows_each_kind_of_layer_attended(model):
    """One stream queued before the start: its decode steps attend, per
    step at position p, p + 1 rows of the full layer's pool and min(p + 1,
    8) rows of each of the four rings."""
    t_p, n = 20, 6
    eng = GenerationEngine(model=model, config=GenerationConfig(**ENGINE))
    req = eng.submit(_prompt(t_p), n)
    eng.start()
    try:
        req.result(timeout=300)
        st = eng.stats()
    finally:
        eng.stop()
    seen = np.arange(t_p, t_p + n - 1) + 1
    assert st["latent"]["full"]["rows_attended"] == int(seen.sum())
    assert st["latent"]["window"]["rows_attended"] == 4 * int(
        np.minimum(seen, WINDOW).sum())
    held, away = st["moe"]["assignments_held"], st["moe"][
        "assignments_elsewhere"]
    assert held + away == (t_p + n - 1) * 2 * 4     # rows x top-k x layers


def _tiles_by_hand(t_p, c=16, b=8):
    """[run, skipped] of the prefill kernel over one prompt, per kind: the
    full layer's (c, context) causal plane in b x b tiles; a window layer's
    (c, window + c) band over its ring and the chunk; two head groups (one
    per key/value group)."""
    full, window = np.zeros(2, int), np.zeros(2, int)
    for ci in range(-(-t_p // c)):
        pos = ci * c + np.arange(c)
        keys = np.arange((ci + 1) * c)
        live = (keys[None, :] <= pos[:, None]).reshape(
            c // b, b, -1, b).any(axis=(1, 3))
        full += [live.sum(), live.size - live.sum()]
        keys = ci * c - WINDOW + np.arange(WINDOW + c)
        back = pos[:, None] - keys[None, :]
        keep = (back >= 0) & (back < WINDOW) & (keys >= 0)[None, :]
        live = keep.reshape(c // b, b, -1, b).any(axis=(1, 3))
        window += [live.sum(), live.size - live.sum()]
    return 2 * full, 2 * 4 * window


def test_kernels_emit_the_xla_paths_tokens_and_count_their_tiles(
        engine, model, reference):
    """Both kernels in interpret mode inside the engine's programs: the
    grouped, windowed ``dsa_prefill_attn`` in every chunk and
    ``latent_paged_attn`` in every decode step; two streams of unequal
    length emit the tokens of the ``xla`` path and of the reference, and
    the engine reports the tiles run and skipped as counted by hand."""
    prompts = [_prompt(t, seed=11 + t) for t in (21, 30)]
    eng = GenerationEngine(model=model, config=GenerationConfig(
        **ENGINE, attention_impl="pallas", attention_interpret=True)).start()
    try:
        rows = [r.result(timeout=300)
                for r in [eng.submit(p, 6) for p in prompts]]
        got = eng.stats()["latent"]
        assert eng.drain(timeout=60)
        assert eng.kv.used_pages == 0 and eng.kv.leak_check() is None
    finally:
        eng.stop()
    for p, row in zip(prompts, rows):
        assert (row == engine.generate(p, 6, timeout=300)).all()
        assert _gap(reference, model, row, len(p)) < 1e-4
    full, window = np.sum([_tiles_by_hand(len(p)) for p in prompts], axis=0)
    assert [got["full"]["tiles_run"], got["full"]["tiles_skipped"]] == (
        full.tolist())
    assert [got["window"]["tiles_run"], got["window"]["tiles_skipped"]] == (
        window.tolist())
    assert window[1] > window[0] / 4       # the band leaves tiles dead


# -- the expert layer -----------------------------------------------------------------

def _moe_params(key, d=16, f=8, n=8):
    ks = jax.random.split(key, 12)
    mat = lambda k, *shape: jax.random.normal(k, shape) / np.sqrt(shape[-2])
    poly = lambda k, *lead: {
        "poly_w": 1 / 3 + 0.1 * jax.random.normal(k, lead + (3,)),
        "poly_b": 0.2 * jax.random.normal(jax.random.fold_in(k, 1), lead)}
    return {"router": mat(ks[0], d, n), "router_bias": jnp.zeros(n),
            "experts": {"Wg": mat(ks[1], n, d, f), "Wu": mat(ks[2], n, d, f),
                        "Wd": mat(ks[3], n, f, d), **poly(ks[7], n)},
            "shared": {"Wg": mat(ks[4], d, f), "Wu": mat(ks[5], d, f),
                       "Wd": mat(ks[6], f, d), **poly(ks[8])}}


@pytest.mark.parametrize("row_tile", [None, 8])
def test_polynorm_expert_shares_of_all_chips_plus_one_shared_are_the_layer(
        row_tile):
    act = moe.PolyNorm(0.5, 0.5, 1e-5)
    lp = _moe_params(jax.random.key(0))
    h = jax.random.normal(jax.random.key(1), (12, 16))
    whole, counts, _ = moe.moe_ffn(h, lp, first=0, top_k=2, scale=2.0,
                                   act=act, row_tile=row_tile)
    shared = moe.act_ffn(h, lp["shared"], act)
    routed = sum(moe.moe_ffn(
        h, dict(lp, experts={k: w[first:first + 2]
                             for k, w in lp["experts"].items()}),
        first=first, top_k=2, scale=2.0, act=act, row_tile=row_tile)[0]
                 - shared for first in range(0, 8, 2))
    assert float(jnp.max(jnp.abs(routed + shared - whole))) < 1e-4
    assert int(counts[:-1].sum()) == 24 and int(counts[-1]) == 0
    # and each held expert's part is its PolyNorm FFN at its gate
    s = jax.nn.sigmoid(h @ lp["router"])
    _, ids = jax.lax.top_k(s, 2)
    gates = 2.0 * jnp.take_along_axis(s, ids, -1) / jnp.sum(
        jnp.take_along_axis(s, ids, -1), -1, keepdims=True)
    want = shared
    for e in range(8):
        g = jnp.sum(jnp.where(ids == e, gates, 0.0), -1)
        fe = {k: w[e] for k, w in lp["experts"].items()}
        want = want + g[:, None] * moe.act_ffn(h, fe, act)
    assert float(jnp.max(jnp.abs(whole - want))) < 1e-4


# -- the controls of the comparison ----------------------------------------------------

@pytest.mark.parametrize("control", [
    {"window": 10 ** 6},            # window layers over the whole context
    {"mhc_identity": True},         # H_res = I: no Sinkhorn, no mixing
    {"no_lambda": True},            # the noise heads taken away at 0
])
def test_each_mechanism_left_out_of_the_reference_fails_the_check(
        engine, model, sound_gap, control):
    """The harness's comparison (`make_reference_gap`, the emitted token's
    logit under the reference's arg-max over the largest |logit|) on the
    engine's own streams: within 1e-4 of the sound reference, past the
    cell's 2^-5 under each control — the mechanism shows in the logits."""
    t_pad = T_REF
    worst = {"sound": 0.0, "control": 0.0}
    fns = {"sound": sound_gap,
           "control": FAMILY.make_reference_gap(TINY, tie=0, **control)}
    for t_p in (40, 47):
        row = engine.generate(_prompt(t_p, seed=t_p + 500), 12, timeout=300)
        tokens = np.zeros(t_pad, np.int32)
        tokens[:len(row) - 1] = row[:-1]
        rows = np.arange(t_p - 1, len(row) - 1, dtype=np.int32)
        for name, fn in fns.items():
            gaps, top = fn(model.params, jnp.asarray(tokens),
                           jnp.asarray(rows), jnp.asarray(row[t_p:]))
            worst[name] = max(worst[name], float(np.max(gaps) / top))
    assert worst["sound"] < 1e-4
    assert worst["control"] > 2.0 ** -5


# -- the benchmark's cell, rehearsed --------------------------------------------------

@pytest.fixture(scope="module")
def tiny_bench(tmp_path_factory):
    """A throw-away copy of the benchmark with a tiny twin of
    `motif3_mixed_sat` ADDED: a configuration, a traffic mix, a cell."""
    root = str(tmp_path_factory.mktemp("bench"))
    home = os.path.join(root, "benchmarks")
    shutil.copytree(os.path.join(ROOT, "benchmarks"), home,
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        doc = json.load(f)
    with open(os.path.join(home, "traffic", "motif3_mixed_sat.json")) as f:
        traffic = json.load(f)
    traffic["arrivals"]["rate_per_s"] = 30.0
    short, long_ = traffic["classes"]
    short["prompt_len"].update(min=4, max=20)
    long_["prompt_len"].update(min=24, max=40)
    for c in (short, long_):
        c["output_len"].update(min=3, max=8)
    traffic["engine"] = {**ENGINE, "slots": 2, "max_queue": 1024}
    traffic["trace_seconds"] = 0.3
    with open(os.path.join(home, "traffic", "tiny_motif.json"), "w") as f:
        json.dump(traffic, f)
    with open(os.path.join(home, "configs", "tiny_motif.json"), "w") as f:
        json.dump(TINY, f)
    doc["configs"].append({"name": "tiny_motif", "source": "none",
                           "reduced": [], "why": "test preset",
                           "file": "benchmarks/configs/tiny_motif.json"})
    doc["workloads"].append({"name": "tiny_motif", "config": "tiny_motif",
                             "traffic": "tiny_motif", "chips": 1,
                             "why": "test"})
    for m in doc["end_to_end"] + doc["per_layer"]:
        if "motif3_mixed_sat" in m.get("workloads", ()):
            m["workloads"].append("tiny_motif")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(doc, f)
    return root


@pytest.mark.parametrize("trace", [False, True])
def test_the_cell_rehearses_correct_on_cpu(tiny_bench, trace):
    doc, correct, attempted, failed, obs, info = bench_run.run_cell(
        tiny_bench, "tiny_motif", seed=2 ** 31 + 17, seconds=1.5,
        trace=trace, t_start=time.perf_counter(), require_chip=False)
    out = bench_run.result(doc, obs, correct=correct, attempted=attempted,
                           failed=failed, trace=trace)
    json.dumps(out)
    assert out["correct"] is True, info
    assert attempted > 0 and failed == 0
    assert info["check"]["checked_streams"] == 3
    # the rig serves the family's bfloat16 weights and every product takes
    # the matrices' type, so these are the chip's numerics: an emitted
    # token sits within bf16 rounding of the arg-max (0.004 here on a few
    # seeds, 0.0007-0.0018 on the chip), half the harness's 2^-5 limit
    assert info["check"]["worst_rel_gap"] < 2.0 ** -6
    assert obs.counters["compiles_in_window"] == 0
    if not trace:
        assert set(out["metrics"]) == {"serve_tok_s", "setup_s"}
        return
    # no device plane on the CPU: readers of the trace leave their metric
    # out; the program's counters read the same here as on the chip
    got = out["metrics"]
    assert set(got) <= {m["name"] for m in obs.cell.per_layer}
    # four rings of at most 8 rows against one context of 4-48 rows
    assert 10 < got["latent_window_rows_share.motif3"]["value"] < 90
    assert 30 < got["moe_held_assignment_share.motif3"]["value"] < 70
    assert got["kv_alloc_failures.motif3"]["value"] == 0
