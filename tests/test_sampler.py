"""The serving sampler (`serving.generation._sample_tokens`): one batched
sampler for every compiled serving program that never sorts the
vocabulary and runs only the work the batch's sampling parameters ask for.

What is held to what: its tokens to the sort-based per-row sampler it
replaced (kept here as `_sort_sampler`), bit for bit under the same keys,
over temperatures, top-k values (past the vocabulary too), ties at the k-th
value and zeros of both signs; the compiled engine programs to having no
sort over the vocabulary; one executable to serving greedy and sampled
batches alike; and ``dl4jtpu_decode_sampler_steps_total{branch}`` to the
branch each decode step ran."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.ops import latent
from deeplearning4j_tpu.ops.generation import generate
from deeplearning4j_tpu.runtime import compile_stats
from deeplearning4j_tpu.serving.generation import (
    GenerationConfig,
    GenerationEngine,
    _sample_tokens,
)
from deeplearning4j_tpu.zoo.transformer import TransformerEncoder

pytestmark = pytest.mark.generation

V = 37
TEMPS = (0.0, 0.7, 1.3)
TOP_KS = (0, 1, 5, V - 1, V, V + 7)


def _sort_sampler(logits, temp, top_k, key):
    """The per-row sampler the engine ran before: a descending sort of the
    whole vocabulary for the k-th largest, a draw for every row, and the
    greedy arg-max picked afterwards where ``temp <= 0``."""
    logits = logits.astype(jnp.float32)
    v = logits.shape[-1]
    greedy = jnp.argmax(logits).astype(jnp.int32)
    t = jnp.where(temp > 0.0, temp, 1.0)
    scaled = logits / t
    order = jnp.sort(scaled)[::-1]
    kth = jnp.where(top_k > 0, order[jnp.clip(top_k - 1, 0, v - 1)],
                    -jnp.inf)
    masked = jnp.where(scaled < kth, -jnp.inf, scaled)
    samp = jax.random.categorical(key, masked).astype(jnp.int32)
    return jnp.where(temp <= 0.0, greedy, samp)


_reference = jax.jit(jax.vmap(_sort_sampler))
_batched = jax.jit(_sample_tokens)


def _rows(seed=0, copies=32):
    """(4 * copies, V) logits: plain normals; values rounded to halves, so
    the k-th largest is tied with its neighbours; zeros of both signs among
    a few values; one value throughout.  Each pattern ``copies`` times,
    so that each is drawn with several keys."""
    rng = np.random.default_rng(seed)
    plain = rng.standard_normal(V) * 3.0
    tied = np.round(rng.standard_normal(V) * 2.0) / 2.0
    zeros = np.where(rng.random(V) < 0.5, 0.0, -0.0) * np.ones(V)
    zeros[:5] = (1.5, -2.0, 0.25, 1.5, -0.75)
    flat = np.full(V, 0.5)
    pats = np.stack([plain, tied, zeros, flat]).astype(np.float32)
    return np.repeat(pats, copies, axis=0)


def _keys(n):
    return jax.vmap(lambda s: jax.random.fold_in(jax.random.key(s), 3))(
        jnp.arange(n, dtype=jnp.uint32) + 11)


def _both(logits, temps, top_ks):
    keys = _keys(len(logits))
    args = (jnp.asarray(logits), jnp.asarray(temps, jnp.float32),
            jnp.asarray(top_ks, jnp.int32))
    return (np.asarray(_batched(*args, keys)),
            np.asarray(_reference(*args, keys)))


@pytest.mark.parametrize("top_k", TOP_KS)
@pytest.mark.parametrize("temp", TEMPS)
def test_same_tokens_as_the_sort_under_the_same_keys(temp, top_k):
    logits = _rows(seed=1)
    n = len(logits)
    got, want = _both(logits, [temp] * n, [top_k] * n)
    np.testing.assert_array_equal(got, want)


#: batches that mix what their rows ask for (temperature, top-k per row)
MIXES = {
    "greedy_and_sampled": [(0.0, 0), (0.7, 0), (0.0, 5), (1.3, 0)],
    "greedy_and_top_k": [(0.0, 0), (0.7, 5), (0.0, 0), (1.3, V - 1)],
    "sampled_and_top_k": [(0.7, 0), (1.3, 1), (0.7, V + 7), (1.3, 0)],
    "all_three": [(0.0, 3), (0.7, 0), (1.3, 5), (0.7, V)],
}


@pytest.mark.parametrize("mix", list(MIXES))
def test_mixed_batches_are_the_sort_row_by_row(mix):
    logits = _rows(seed=2)
    temps, top_ks = zip(*(MIXES[mix] * (len(logits) // len(MIXES[mix]))))
    got, want = _both(logits, temps, top_ks)
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("top_k", [0, 5])
def test_all_greedy_batch_is_the_arg_max_of_the_unscaled_logits(top_k):
    logits = _rows(seed=3)
    n = len(logits)
    got, want = _both(logits, [0.0] * n, [top_k] * n)
    np.testing.assert_array_equal(got, np.argmax(logits, axis=-1))
    np.testing.assert_array_equal(got, want)


def test_one_row_is_the_unbatched_sort():
    """The prefill programs sample one row, with a key of their own."""
    logits = _rows(seed=4, copies=1)
    for i, (temp, top_k) in enumerate([(0.0, 0), (0.7, 0), (1.3, 5),
                                       (0.7, V + 7)]):
        key = jax.random.fold_in(jax.random.key(5 + i), 0)
        got = _batched(jnp.asarray(logits[i:i + 1]),
                       jnp.float32([temp]), jnp.int32([top_k]), key[None])
        want = jax.jit(_sort_sampler)(jnp.asarray(logits[i]),
                                      jnp.float32(temp), jnp.int32(top_k),
                                      key)
        assert int(got[0]) == int(want)


@pytest.mark.parametrize("pattern", ["plain", "tied", "zeros", "flat"])
def test_kth_largest_equals_the_descending_sort(pattern):
    x = _rows(seed=6, copies=1)[["plain", "tied", "zeros",
                                 "flat"].index(pattern)]
    xs = np.repeat(x[None], V, axis=0)
    got = np.asarray(latent.kth_largest(jnp.asarray(xs),
                                        jnp.arange(1, V + 1)))
    want = np.sort(x)[::-1]
    np.testing.assert_array_equal(got == want, np.ones(V, bool))


# -- the compiled programs ------------------------------------------------------

KV_VOCAB = 29
KV_ENGINE = dict(slots=4, page_size=8, num_pages=32, max_pages_per_seq=4,
                 max_queue=16)


@pytest.fixture(scope="module")
def kv_model():
    return TransformerEncoder(vocab_size=KV_VOCAB, d_model=16, n_heads=2,
                              n_layers=2, causal=True, seed=5).init_model()


def _aval(a):
    return None if a is None else jax.ShapeDtypeStruct(a.shape, a.dtype)


def _step_avals(eng, c):
    s, mp = eng.config.slots, eng.config.max_pages_per_seq

    def vec(dtype, *tail):
        return jax.ShapeDtypeStruct((s,) + tail, dtype)

    return (jax.tree.map(_aval, eng.model.params),
            *[_aval(a) for a in eng._program_state()],
            vec(jnp.int32, mp), vec(jnp.int32),
            vec(jnp.int32) if c == 1 else vec(jnp.int32, c),
            vec(jnp.uint32), vec(jnp.int32), vec(jnp.float32),
            vec(jnp.int32))


def _scalar(dtype):
    return jax.ShapeDtypeStruct((), dtype)


#: one instruction of an HLO module: its name, shape, opcode and operands
_INSTR = re.compile(r"%([\w.\-]+) = (.*?) ([a-z][a-z\-]*)\((.*?)\)")
_SHAPE = re.compile(r"\b[a-z]+\d*\[([\d,]*)\]")


def _sorted_minor_dims(compiled_text):
    """The minor dimension of every operand of every sort and top-k in a
    compiled module (instruction names are unique in a module)."""
    shapes, sorting = {}, []
    for line in compiled_text.splitlines():
        m = _INSTR.search(line)
        if m is None:
            continue
        name, shape, op, operands = m.groups()
        shapes[name] = shape
        if op in ("sort", "topk") or 'custom_call_target="TopK"' in line:
            sorting += re.findall(r"%([\w.\-]+)", operands)
    return [int(d.split(",")[-1]) for o in sorting
            for d in _SHAPE.findall(shapes[o]) if d]


def _kv_program(eng, program):
    if program == "prefill":
        return eng._make_prefill(8).lower(
            jax.tree.map(_aval, eng.model.params),
            jax.ShapeDtypeStruct((1, 8), jnp.int32), _scalar(jnp.int32),
            _scalar(jnp.uint32), _scalar(jnp.float32), _scalar(jnp.int32))
    c = 1 if program == "step" else eng.spec_k + 1
    return eng._make_step(c).lower(*_step_avals(eng, c))


@pytest.mark.parametrize("program", ["step", "verify", "prefill"])
def test_no_kv_program_sorts_the_vocabulary(kv_model, program):
    eng = GenerationEngine(model=kv_model,
                           config=GenerationConfig(**KV_ENGINE, spec_k=2))
    text = _kv_program(eng, program).compile().as_text()
    assert KV_VOCAB not in _sorted_minor_dims(text)


@pytest.fixture(scope="module")
def latent_engine():
    from test_latent_serving import _model

    # a page table of 10 pages of 8 rows: the DSA selection sorts rows
    # of 80, not of the vocabulary's 96
    return GenerationEngine(model=_model(), config=GenerationConfig(
        slots=2, page_size=8, num_pages=24, max_pages_per_seq=10,
        prefill_quantum=16, kv_dtype="f32"))


@pytest.mark.parametrize("program", ["step", "prefill_chunk"])
def test_no_latent_program_sorts_the_vocabulary(latent_engine, program):
    eng = latent_engine
    vocab = eng._vocab
    if program == "step":
        lowered = eng._make_step(1).lower(*_step_avals(eng, 1))
    else:
        lowered = eng._make_prefill_chunk(1).lower(
            jax.tree.map(_aval, eng.model.params),
            *[_aval(a) for a in eng._program_state()],
            jax.ShapeDtypeStruct((eng.config.max_pages_per_seq,), jnp.int32),
            jax.ShapeDtypeStruct((eng._quantum,), jnp.int32),
            _scalar(jnp.int32), _scalar(jnp.uint32), _scalar(jnp.float32),
            _scalar(jnp.int32))
    dims = _sorted_minor_dims(lowered.compile().as_text())
    assert vocab not in dims
    if program == "step":
        # the DSA selection's top-k over the page table's rows is there
        assert eng.config.max_pages_per_seq * eng.kv.page_size in dims


def test_greedy_and_sampled_batches_share_one_executable(kv_model):
    eng = GenerationEngine(model=kv_model,
                           config=GenerationConfig(**KV_ENGINE)).start()
    rng = np.random.default_rng(9)
    prompts = [rng.integers(0, KV_VOCAB, 5).astype(np.int32)
               for _ in range(3)]
    sampled = dict(temperature=0.9, top_k=4, seed=21)
    want = np.asarray(generate(kv_model, prompts[2][None], 6, **sampled))[0]
    try:
        for p in prompts[:2]:                  # warm the bucket, greedy
            eng.generate(p, 6, timeout=120.0)
        snap = compile_stats.snapshot()
        got = eng.generate(prompts[2], 6, timeout=120.0, **sampled)
        delta = compile_stats.snapshot() - snap
        np.testing.assert_array_equal(np.asarray(got), want)
        assert delta.backend_compiles == 0
        assert eng._step_fns[1]._cache_size() == 1
    finally:
        eng.stop()


# -- the counter ------------------------------------------------------------------

@pytest.mark.parametrize("top_k,branch", [(4, "top_k"), (0, "sampled")])
def test_steps_are_counted_by_the_branch_they_ran(kv_model, top_k, branch):
    """A greedy stream alone counts greedy steps; a sampling stream beside
    it counts its branch while it is live, and greedy follows once it
    ends.  Streams queued before the loop starts: one refill admits both,
    so the schedule is fixed by the lengths alone."""
    from deeplearning4j_tpu.observe.metrics import registry

    rng = np.random.default_rng(10)
    p, q = (rng.integers(0, KV_VOCAB, 6).astype(np.int32) for _ in range(2))
    kw = dict(temperature=0.9, top_k=top_k, seed=4)
    refs = [np.asarray(generate(kv_model, p[None], 20))[0],
            np.asarray(generate(kv_model, q[None], 5, **kw))[0]]
    eng = GenerationEngine(model=kv_model, config=GenerationConfig(
        **KV_ENGINE))
    alone = eng.submit(p, 3)
    eng.start()
    try:
        alone.result(120.0)
        assert eng.drain(timeout=30.0)
        assert eng.stats()["decode_sampler_steps"] == {
            "greedy": 2, "sampled": 0, "top_k": 0}
    finally:
        eng.stop()
    eng = GenerationEngine(model=kv_model, config=GenerationConfig(
        **KV_ENGINE))
    reqs = [eng.submit(p, 20), eng.submit(q, 5, **kw)]
    eng.start()
    try:
        for r, ref in zip(reqs, refs):
            np.testing.assert_array_equal(np.asarray(r.result(120.0)), ref)
        assert eng.drain(timeout=30.0)
        st = eng.stats()
    finally:
        eng.stop()
    # 19 steps for the long stream; the sampling one is live in the 4
    # that make its tokens 2 to 5
    counts = {"greedy": 15, "sampled": 0, "top_k": 0}
    counts[branch] = 4
    assert st["decode_steps"] == 19
    assert st["decode_sampler_steps"] == counts
    reg = registry()
    reg.collect()
    text = reg.to_prometheus_text()
    for b in ("greedy", branch):
        assert f'dl4jtpu_decode_sampler_steps_total{{branch="{b}"}}' in text
