"""The `glm_moe_dsa` block (latent attention, the DSA indexer with shared
selections, sigmoid-routed experts as one chip's share) on the serving path,
against the family's plain float32 reference — at a small size on the CPU:
d 64, 4 heads, index_topk 8, 8 routed experts of which this "chip" holds 4,
and the five-layer pattern of the benchmark's cut (dense/full, expert/shared
x 3, expert/full).

What is held to what: the engine (`InferenceServer`-less `GenerationEngine`
over `PagedKVCache` row pools: chunked prefill, then decode through the
paged latent pool) and the DSL layer's whole-sequence `apply` to
`benchmarks/families/glm_moe_dsa.py`'s reference, which imports nothing from
the package; the expert layer's shares to the uncut layer.
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
from deeplearning4j_tpu.nn.conf import LatentSparseDecoder
from deeplearning4j_tpu.ops import generation as dense
from deeplearning4j_tpu.ops import latent, moe
from deeplearning4j_tpu.serving.generation import (
    GenerationConfig, GenerationEngine,
)
from deeplearning4j_tpu.serving.kv_cache import PagedKVCache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FAMILY = spec.family({"family": "glm_moe_dsa"})

TINY = {
    "family": "glm_moe_dsa", "hidden_size": 64, "num_attention_heads": 4,
    "q_lora_rank": 32, "kv_lora_rank": 16, "qk_nope_head_dim": 8,
    "qk_rope_head_dim": 8, "v_head_dim": 16,
    "rope_parameters": {"rope_theta": 8000000, "rope_type": "default"},
    "rms_norm_eps": 1e-5, "index_n_heads": 2, "index_head_dim": 16,
    "index_topk": 8,
    "indexer_types": ["full", "full", "full", "shared", "shared", "shared",
                      "full"],
    "mlp_layer_types": ["dense", "dense", "dense", "sparse", "sparse",
                        "sparse", "sparse"],
    "intermediate_size": 128, "moe_intermediate_size": 32,
    "n_routed_experts": 4, "num_experts_per_tok": 2,
    "routed_scaling_factor": 2.5, "num_hidden_layers": 5, "vocab_size": 96,
    "system": {"layers_run": [2, 3, 4, 5, 6], "vocab_chunk": 64},
    "deployment": {"n_routed_experts_published": 8, "held_experts": [2, 6]},
}
ENGINE = dict(slots=3, page_size=8, num_pages=40, max_pages_per_seq=12,
              prefill_quantum=16, kv_dtype="f32")
VOCAB = TINY["vocab_size"]


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
def engine(model):
    eng = GenerationEngine(model=model,
                           config=GenerationConfig(**ENGINE)).start()
    yield eng
    eng.stop()


def _gap(reference, model, row, t_p):
    """How far each emitted token's reference logit sits below the
    reference's arg-max at its position, over the generated part."""
    z = np.asarray(reference(model.params, jnp.asarray(row[:-1])))[t_p - 1:]
    return float(np.max(z.max(-1) - z[np.arange(len(z)), row[t_p:]]))


# -- the selection ------------------------------------------------------------------

@pytest.mark.parametrize("k", [1, 8, 40, 64])
def test_topk_mask_is_lax_top_k_with_ties_to_the_lower_index(k):
    rng = np.random.default_rng(k)
    s = rng.standard_normal((6, 40)).astype(np.float32)
    s[0, :12] = 1.5                       # a run of ties across the k-th
    s[1] = 0.25                           # every entry tied
    lens = np.array([40, 40, 3, 8, 20, 1])
    valid = np.arange(40)[None, :] < lens[:, None]
    got = np.asarray(latent.topk_mask(jnp.asarray(s), jnp.asarray(valid), k))
    want = np.zeros_like(valid)
    for i in range(6):
        masked = np.where(valid[i], s[i], -np.inf)
        best, where = jax.lax.top_k(jnp.asarray(masked), min(k, 40))
        want[i, np.asarray(where)[np.asarray(best) > -np.inf]] = True
    assert (got == want).all()
    assert (got.sum(-1) == np.minimum(lens, k)).all()


# -- the expert layer ---------------------------------------------------------------

def _moe_params(key, d=16, f=8, n=8):
    ks = jax.random.split(key, 8)
    mat = lambda k, *shape: jax.random.normal(k, shape) / np.sqrt(shape[-2])
    return {"router": mat(ks[0], d, n), "router_bias": jnp.zeros(n),
            "experts": {"Wg": mat(ks[1], n, d, f), "Wu": mat(ks[2], n, d, f),
                        "Wd": mat(ks[3], n, f, d)},
            "shared": {"Wg": mat(ks[4], d, f), "Wu": mat(ks[5], d, f),
                       "Wd": mat(ks[6], f, d)}}


def _share(lp, first, n):
    return dict(lp, experts={k: w[first:first + n]
                             for k, w in lp["experts"].items()})


def test_expert_shares_of_all_chips_plus_one_shared_expert_are_the_layer():
    lp = _moe_params(jax.random.key(0))
    h = jax.random.normal(jax.random.key(1), (12, 16))
    whole, counts, _ = moe.moe_ffn(h, lp, first=0, top_k=2, scale=2.5)
    shared = moe.gated_ffn(h, lp["shared"]["Wg"], lp["shared"]["Wu"],
                           lp["shared"]["Wd"])
    routed = sum(moe.moe_ffn(h, _share(lp, first, 2), first=first, top_k=2,
                             scale=2.5)[0] - shared
                 for first in range(0, 8, 2))
    assert float(jnp.max(jnp.abs(routed + shared - whole))) < 1e-4
    assert int(counts[:-1].sum()) == 24 and int(counts[-1]) == 0


@pytest.mark.parametrize("row_tile", [None, 4, 16])
def test_expert_layer_is_dropless_when_the_router_sends_every_row_to_one(
        row_tile):
    lp = _moe_params(jax.random.key(2))
    lp["router_bias"] = jnp.zeros(8).at[5].set(10.0).at[1].set(5.0)
    h = jax.random.normal(jax.random.key(3), (24, 16))
    held = _share(lp, 4, 2)                           # holds experts 4, 5
    y, counts, _ = moe.moe_ffn(h, held, first=4, top_k=2, scale=2.5,
                               row_tile=row_tile)
    # every row chose experts 5 (held) and 1 (elsewhere): all 24 on one
    assert counts.tolist() == [0, 24, 24]
    s = jax.nn.sigmoid(h @ lp["router"])
    gate = 2.5 * s[:, 5] / (s[:, 5] + s[:, 1])
    ex = lp["experts"]
    want = (gate[:, None] * moe.gated_ffn(h, ex["Wg"][5], ex["Wu"][5],
                                          ex["Wd"][5])
            + moe.gated_ffn(h, lp["shared"]["Wg"], lp["shared"]["Wu"],
                            lp["shared"]["Wd"]))
    assert float(jnp.max(jnp.abs(y - want))) < 1e-4


def test_expert_layer_in_tiles_is_the_single_pass_and_counts_marked_rows():
    lp = _share(_moe_params(jax.random.key(4)), 2, 4)
    h = jax.random.normal(jax.random.key(5), (20, 16))
    marked = jnp.arange(20) < 13
    one, c_one, _ = moe.moe_ffn(h, lp, first=2, top_k=2, scale=2.5,
                                count_rows=marked)
    tiled, c_tiled, _ = moe.moe_ffn(h, lp, first=2, top_k=2, scale=2.5,
                                    count_rows=marked, row_tile=8)
    assert float(jnp.max(jnp.abs(one - tiled))) < 1e-5
    assert c_one.tolist() == c_tiled.tolist()
    assert int(c_one.sum()) == 13 * 2


# -- the pool -----------------------------------------------------------------------

def test_row_pools_hold_a_stated_width_per_layer_kind():
    kv = PagedKVCache(num_pages=6, page_size=8, kv_dtype="bf16",
                      rows={"latent": (5, (24,)), "index_key": (2, (16,))})
    latent_pages, key_pages = kv.pool()
    assert latent_pages.shape == (5, 6, 8, 24) and key_pages.shape == (
        2, 6, 8, 16)
    assert latent_pages.dtype == jnp.bfloat16
    assert kv.bytes_per_token() == (5 * 24 + 2 * 16) * 2
    st = kv.stats()
    assert st["rows"] == {"latent": [5, 24], "index_key": [2, 16]}
    assert st["kv_dtype"] == "bf16" and st["bytes_per_token"] == 304
    kv.alloc("r", 2)
    # no hand-off into row pools: the programs write them in place
    with pytest.raises(ValueError, match="write them in place|in place"):
        kv.write_prefill("r", jnp.ones((5, 16, 24)), None)
    assert kv.release("r") == 2 and kv.leak_check() is None


def test_keys_and_values_stated_as_rows_are_the_kv_pool():
    short = PagedKVCache(n_layers=3, n_heads=2, head_dim=8, num_pages=4,
                         page_size=8, kv_dtype="int8")
    stated = PagedKVCache(num_pages=4, page_size=8, kv_dtype="int8",
                          rows={"k": (3, (2, 8)), "v": (3, (2, 8))})
    assert stated.kv_layout and short.kv_layout
    assert short.rows == stated.rows
    assert [(a.shape, a.dtype) for a in stated.pool()] == [
        (a.shape, a.dtype) for a in short.pool()]
    assert stated.stats()["rows"] == {"k": [3, 2, 8], "v": [3, 2, 8]}
    assert stated.bytes_per_token() == short.bytes_per_token()


@pytest.mark.parametrize("kwargs, says", [
    (dict(n_layers=1, n_heads=2, head_dim=8, kv_dtype="bf16"), "row pools"),
    (dict(rows={"latent": (1, (8,))}, kv_dtype="int8"), "K/V pools only"),
])
def test_kv_dtype_error_names_what_each_pool_kind_holds(kwargs, says):
    with pytest.raises(ValueError, match=says):
        PagedKVCache(num_pages=4, page_size=8, **kwargs)


# -- the stack ------------------------------------------------------------------------

def test_plan_states_what_each_layer_caches(model):
    stack = dense._plan(model)
    assert isinstance(stack.final, LatentSparseDecoder) and stack.pos is None
    rows = [dense.cache_rows(b) for b in stack.blocks]
    full = {"latent": (24,), "index_key": (16,)}
    assert rows == [full, {"latent": (24,)}, {"latent": (24,)},
                    {"latent": (24,)}, full]
    held = model.params["decoder"]
    assert ["indexer" in held[f"layer{i:02d}"] for i in range(5)] == [
        True, False, False, False, True]
    with pytest.raises(ValueError, match="GenerationEngine"):
        dense.generate(model, np.zeros((1, 4), np.int32), 2)


def test_shared_layers_attend_the_preceding_full_layers_selection(model):
    """A shared layer is handed no indexer, and gets the mask object the
    full layer before it left."""
    seen = []
    inner = latent.sequence_attend()

    def attend(cfg, q, rows, index, wkvb):
        seen.append((cfg.indexer, index is None))
        return inner(cfg, q, rows, index, wkvb)

    stack = dense._plan(model)
    x = jnp.asarray(model.params["embed"]["W"])[jnp.arange(40)]
    for cfg in stack.blocks:
        x = dense.block(cfg, dense.block_params(model.params, cfg), x,
                        latent.LatentRows(attend, jnp.arange(40)))
    assert seen == [("full", False), ("shared", True), ("shared", True),
                    ("shared", True), ("full", False)]


def test_dsl_output_is_the_reference_and_a_selection_is_sparse(model,
                                                              reference):
    ids = np.random.default_rng(1).integers(0, VOCAB, (2, 40)).astype(
        np.int32)
    hidden = np.asarray(model.output(ids))
    head = np.asarray(model.params["head"]["W"])
    for b in range(2):
        z = np.asarray(reference(model.params, jnp.asarray(ids[b])))
        assert np.abs(hidden[b] @ head - z).max() < 1e-4
    picks = []
    with jax.default_matmul_precision("highest"):
        FAMILY.reference_hidden(TINY, model.params, jnp.asarray(ids[0]),
                                selections=picks)
    assert len(picks) == 2                       # the two full layers
    assert (np.asarray(picks[0]).sum(-1)
            == np.minimum(np.arange(40) + 1, 8)).all()


def test_a_small_fit_lowers_the_loss():
    from deeplearning4j_tpu.data.dataset import DataSet

    model = _model(seed=5)
    model.opt_state = model._tx.init(model.params)
    ids = np.random.default_rng(2).integers(0, VOCAB, (4, 24)).astype(
        np.int32)
    data = DataSet(ids, np.roll(ids, -1, axis=1))
    before = float(model.score(data))
    model.fit(data, epochs=8)
    assert float(model.score(data)) < before


# -- the engine against the reference -------------------------------------------------

@pytest.mark.parametrize("t_p, n_new", [(37, 6), (16, 4), (50, 9), (33, 3),
                                        (5, 12)])
def test_prefill_then_decode_through_the_pool_is_the_reference(
        engine, model, reference, t_p, n_new):
    """Prompts several times index_topk (8), across page (8) and chunk (16)
    boundaries: chunked prefill, then decode over the paged latent pool."""
    prompt = np.random.default_rng(t_p).integers(0, VOCAB, t_p,
                                                 dtype=np.int32)
    row = engine.generate(prompt, n_new, timeout=300)
    assert len(row) == t_p + n_new and (row[:t_p] == prompt).all()
    assert _gap(reference, model, row, t_p) < 1e-4
    assert engine.kv.used_pages == 0 and engine.kv.leak_check() is None


def test_two_streams_of_unequal_length_share_a_step(engine, model,
                                                    reference):
    rng = np.random.default_rng(9)
    prompts = [rng.integers(0, VOCAB, t, dtype=np.int32) for t in (41, 12)]
    steps = engine.stats()["decode_slot_steps"], engine.stats()[
        "decode_steps"]
    reqs = [engine.submit(p, 10) for p in prompts]
    rows = [r.result(timeout=300) for r in reqs]
    for p, row in zip(prompts, rows):
        assert _gap(reference, model, row, len(p)) < 1e-4
    st = engine.stats()
    # some step served both: more slot-steps than steps
    assert (st["decode_slot_steps"] - steps[0]) > (st["decode_steps"]
                                                   - steps[1])


def _tiles_by_hand(model, prompt, c=16, b=8):
    """[run, skipped] of the prefill kernel over one prompt: per chunk and
    layer, the b x b tiles of the chunk's (c, context) selection that keep a
    pair — the selections are the reference's own, over the prompt padded
    to whole chunks as the engine pads it; layer 0 and the three shared
    layers after it use the first full layer's, layer 4 its own; one head
    group (4 heads)."""
    t_b = -(-len(prompt) // c) * c
    padded = np.zeros(t_b, np.int32)
    padded[:len(prompt)] = prompt
    picks = []
    with jax.default_matmul_precision("highest"):
        FAMILY.reference_hidden(TINY, model.params, jnp.asarray(padded),
                                selections=picks)
    run = skipped = 0
    for ci in range(t_b // c):
        for full in (0, 0, 0, 0, 1):
            sel = np.asarray(picks[full])[ci * c:(ci + 1) * c,
                                          :(ci + 1) * c]
            live = sel.reshape(c // b, b, (ci + 1) * c // b, b).any(
                axis=(1, 3))
            run += int(live.sum())
            skipped += int(live.size - live.sum())
    return run, skipped


def test_prefill_kernel_emits_the_xla_paths_tokens_and_counts_its_tiles(
        engine, model, reference):
    """The ``dsa_prefill_attn`` kernel (interpret mode) in the engine's
    chunk programs: two streams of unequal length, each over two chunks,
    emit the tokens of the ``xla`` path and of the reference, and the
    engine reports the tiles the kernel ran and skipped as counted by
    hand from the reference's selections."""
    rng = np.random.default_rng(11)
    prompts = [rng.integers(0, VOCAB, t, dtype=np.int32) for t in (21, 30)]
    eng = GenerationEngine(model=model, config=GenerationConfig(
        **ENGINE, attention_impl="pallas", attention_interpret=True)).start()
    try:
        before = eng.stats()["dsa_prefill_tiles"]
        reqs = [eng.submit(p, 6) for p in prompts]
        rows = [r.result(timeout=300) for r in reqs]
        after = eng.stats()["dsa_prefill_tiles"]
        assert eng.drain(timeout=60)
        assert eng.kv.used_pages == 0 and eng.kv.leak_check() is None
    finally:
        eng.stop()
    for p, row in zip(prompts, rows):
        assert (row == engine.generate(p, 6, timeout=300)).all()
        assert _gap(reference, model, row, len(p)) < 1e-4
    by_hand = np.sum([_tiles_by_hand(model, p) for p in prompts], axis=0)
    assert [after[s] - before[s] for s in ("run", "skipped")] == (
        by_hand.tolist())
    # each chunk of each layer has one causal-dead tile; the selection
    # leaves some more empty
    assert by_hand[1] > 2 * 5 * 2
    # the xla path runs no kernel and counts nothing
    assert engine.stats()["dsa_prefill_tiles"] == {"run": 0, "skipped": 0}


def test_speculative_verify_chunk_emits_the_plain_tokens(model, engine):
    prompt = np.tile(np.arange(7, dtype=np.int32), 6)          # 42, repeats
    plain = engine.generate(prompt, 8, timeout=300)
    spec_eng = GenerationEngine(model=model, config=GenerationConfig(
        **ENGINE, spec_k=2, spec_drafter="ngram")).start()
    try:
        assert (spec_eng.generate(prompt, 8, timeout=300) == plain).all()
        assert spec_eng.stats()["speculative"]["verify_dispatches"] > 0
    finally:
        spec_eng.stop()


def test_pool_is_leak_free_after_a_cancel(engine):
    prompt = np.random.default_rng(4).integers(0, VOCAB, 30, dtype=np.int32)
    req = engine.submit(prompt, 60)
    while not req.tokens_so_far():
        time.sleep(0.01)
    req.cancel()
    assert engine.drain(timeout=60)
    assert engine.kv.used_pages == 0 and engine.kv.leak_check() is None


def test_engine_counts_the_selection_and_the_assignments(model):
    eng = GenerationEngine(model=model,
                           config=GenerationConfig(**ENGINE)).start()
    try:
        eng.generate(np.arange(20, dtype=np.int32), 4, timeout=300)
        st = eng.stats()
    finally:
        eng.stop()
    # rows through the layers: 20 prompt rows + 3 decode rows (the fourth
    # token is never fed back); each scores its prefix in 2 full layers
    contexts = np.arange(1, 24)
    assert st["dsa"] == {
        "rows_scored": 2 * int(contexts.sum()),
        "rows_selected": 2 * int(np.minimum(contexts, 8).sum())}
    held, away = st["moe"]["assignments_held"], st["moe"][
        "assignments_elsewhere"]
    assert held + away == 23 * 2 * 4                 # rows x top-k x layers
    assert np.sum(st["moe"]["expert_assignments"]) == held


# -- the family's operation counts ----------------------------------------------------

def test_flop_functions_against_a_hand_count():
    d, h, dq, lk, dn, dr, dv = 64, 4, 32, 16, 8, 8, 16
    attention = d * dq + dq * h * (dn + dr) + d * (lk + dr) + lk * h * (
        dn + dv) + h * dv * d
    indexer = dq * 2 * 16 + d * 16 + d * 2
    expert = 3 * d * 32
    per_token = 2 * (5 * attention + 2 * indexer + 3 * d * 128
                     + 4 * (d * 8 + expert * (1 + 2 * 0.5)))
    assert FAMILY.token_matmul_flops(TINY, 0.5) == per_token
    # one row at a context of 20: attends 8 rows in 5 layers, scores 20 in 2
    row = 2 * 5 * h * (dn + dr + dv) * 8 + 2 * 2 * 2 * (16 + 1) * 20
    assert FAMILY.row_attention_flops(TINY, 20) == row
    whole = FAMILY.request_flops(TINY, 10, 3, 0.5)
    by_rows = sum(FAMILY.row_attention_flops(TINY, t) for t in range(1, 13))
    assert whole == pytest.approx(12 * per_token + by_rows
                                  + 3 * 2 * d * VOCAB)


# -- the benchmark's cell, rehearsed ---------------------------------------------------

@pytest.fixture(scope="module")
def tiny_bench(tmp_path_factory):
    """A throw-away copy of the benchmark with a tiny twin of
    `glm52_longdoc_sat` ADDED: a configuration, a traffic mix, a cell."""
    root = str(tmp_path_factory.mktemp("bench"))
    home = os.path.join(root, "benchmarks")
    shutil.copytree(os.path.join(ROOT, "benchmarks"), home,
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        doc = json.load(f)
    with open(os.path.join(home, "traffic", "glm52_longdoc_sat.json")) as f:
        traffic = json.load(f)
    traffic["arrivals"]["rate_per_s"] = 40.0
    traffic["classes"][0]["prompt_len"].update(min=33, max=60)
    traffic["classes"][0]["output_len"].update(min=3, max=6)
    traffic["engine"] = {**ENGINE, "slots": 2, "max_queue": 1024}
    traffic["trace_seconds"] = 0.3
    with open(os.path.join(home, "traffic", "tiny_glm.json"), "w") as f:
        json.dump(traffic, f)
    with open(os.path.join(home, "configs", "tiny_glm.json"), "w") as f:
        json.dump(TINY, f)
    doc["configs"].append({"name": "tiny_glm", "source": "none",
                           "reduced": [], "why": "test preset",
                           "file": "benchmarks/configs/tiny_glm.json"})
    doc["workloads"].append({"name": "tiny_glm", "config": "tiny_glm",
                             "traffic": "tiny_glm", "chips": 1,
                             "why": "test"})
    for m in doc["end_to_end"] + doc["per_layer"]:
        if "glm52_longdoc_sat" in m.get("workloads", ()):
            m["workloads"].append("tiny_glm")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(doc, f)
    return root


def test_a_row_is_left_out_only_where_a_held_experts_score_is_near_tied():
    """`ROUTER_TIE`: rows whose basis vector picks a router row, so the
    scores are written down.  Held: experts 2-5; top-2."""
    z = np.full((4, 8), -4.0, np.float32)
    z[0, [0, 1]] = 3.0, 2.0                 # far from every held expert
    z[1, [0, 2, 1]] = 3.0, 1.001, 1.0       # a held one barely chosen
    z[2, [0, 1, 5]] = 3.0, 1.001, 1.0       # a held one barely left out
    z[3, [0, 1, 7]] = 3.0, 1.001, 1.0       # a near-tie between two absent
    router = np.zeros((64, 8), np.float32)
    router[:4] = z
    mat = lambda *shape: jnp.full(shape, 0.01, jnp.float32)
    f = {"router": jnp.asarray(router), "router_bias": jnp.zeros(8),
         "experts": {"Wg": mat(4, 64, 32), "Wu": mat(4, 64, 32),
                     "Wd": mat(4, 32, 64)},
         "shared": {"Wg": mat(64, 32), "Wu": mat(64, 32), "Wd": mat(32, 64)}}
    _, margin, took = FAMILY._reference_moe(TINY, jnp.eye(4, 64), f)
    assert took.tolist() == [[False] * 4, [True, False, False, False],
                             [False] * 4, [False] * 4]
    tied = np.asarray(FAMILY.near_tied([(margin, took)], FAMILY.ROUTER_TIE))
    assert tied.tolist() == [False, True, True, False]
    sig = lambda a: 1 / (1 + np.exp(-a))
    np.testing.assert_allclose(np.asarray(margin)[1:3],
                               sig(1.001) - sig(1.0), rtol=1e-3)
    assert margin[0] > 0.5 and margin[3] > 0.5


@pytest.mark.parametrize("trace", [False, True])
def test_the_cell_rehearses_correct_on_cpu(tiny_bench, trace):
    t0 = time.perf_counter()
    doc, correct, attempted, failed, obs, info = bench_run.run_cell(
        tiny_bench, "tiny_glm", seed=2 ** 31 + 17, seconds=1.5, trace=trace,
        t_start=t0, require_chip=False)
    out = bench_run.result(doc, obs, correct=correct, attempted=attempted,
                           failed=failed, trace=trace)
    json.dumps(out)
    assert out["correct"] is True, info
    assert attempted > 0 and failed == 0
    assert info["check"]["checked_streams"] == 3
    assert info["check"]["worst_rel_gap"] < 1e-3
    assert obs.counters["compiles_in_window"] == 0
    assert obs.counters["kv_alloc_failures"] == 0
    if not trace:
        assert set(out["metrics"]) == {"serve_tok_s", "setup_s"}
        return
    # no device plane on the CPU: readers of the trace leave their metric
    # out; the program's counters read the same here as on the chip
    got = out["metrics"]
    assert set(got) <= {m["name"] for m in obs.cell.per_layer}
    assert 0 < got["dsa_selected_share.glm52"]["value"] < 60
    assert 30 < got["moe_held_assignment_share.glm52"]["value"] < 70
    assert got["moe_expert_load_max_over_mean.glm52"]["value"] >= 1.0
    assert "compiles_in_window.glm52" in got


def test_a_wrong_expert_in_the_routed_path_fails_the_cells_check(
        tiny_bench, monkeypatch):
    """The control of the comparison: the system weights every held
    expert's output with its neighbour's gate (the held range shifted by
    one); the harness's own check, unchanged, must say not correct — with
    the near-tied rows left out, which a right system passes."""
    real = latent.moe_ffn
    monkeypatch.setattr(
        latent, "moe_ffn",
        lambda h, lp, *, first, **kw: real(h, lp, first=first + 1, **kw))
    _, correct, attempted, failed, _, info = bench_run.run_cell(
        tiny_bench, "tiny_glm", seed=2 ** 31 + 18, seconds=1.5, trace=False,
        t_start=time.perf_counter(), require_chip=False)
    assert attempted > 0 and failed == 0
    assert correct is False
    check = info["check"]
    assert check["checked_streams"] == 3 and check["kv_leak"] is None
    assert check["worst_rel_gap"] > 3 * check["tolerance"]
