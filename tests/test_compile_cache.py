"""The recompile/re-decode tax elimination layer (ISSUE 1 tentpole):
sequence bucketing bounds step compiles, the persistent XLA cache
warm-starts fresh processes, CachedDataSetIterator replays byte-identical
batches without re-decoding, and the new counters prove each claim."""

import json
import math
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from deeplearning4j_tpu.data.dataset import DataSet
from deeplearning4j_tpu.data.iterator import DataSetIterator
from deeplearning4j_tpu.nlp import BertIterator, BertWordPieceTokenizer
from deeplearning4j_tpu.runtime import compile_stats
from deeplearning4j_tpu.runtime.flags import bucket_length

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

VOCAB = {t: i for i, t in enumerate(
    ["[PAD]", "[UNK]", "[CLS]", "[SEP]", "the", "fox", "dog", "jump"]
)}


# -- bucket_length helper --------------------------------------------------

def test_bucket_length_rounds_up_to_quantum():
    assert bucket_length(1, 32) == 32
    assert bucket_length(32, 32) == 32
    assert bucket_length(33, 32) == 64
    assert bucket_length(70, 32) == 96
    assert bucket_length(0, 32) == 32          # degenerate length still 1 bucket


def test_bucket_length_default_quantum_from_environment():
    from deeplearning4j_tpu.runtime.flags import environment

    q = environment().sequence_bucket_size
    assert bucket_length(1) == q


def test_bucket_length_rejects_bad_quantum():
    with pytest.raises(ValueError):
        bucket_length(10, 0)


# -- BertIterator bucketing ------------------------------------------------

def _mixed_corpus(tok, max_len=128):
    """Sentences spanning >= 6 distinct tokenized lengths under max_len."""
    sents, labels = [], []
    for i, words in enumerate([3, 12, 40, 60, 75, 100, 120, 24]):
        # words + [CLS]/[SEP] special tokens; 2 examples per length
        for j in range(2):
            sents.append(" ".join(["the"] * words))
            labels.append((i + j) % 2)
    return sents, labels


def test_bert_iterator_bucketing_shapes_and_coverage():
    tok = BertWordPieceTokenizer(VOCAB)
    sents, labels = _mixed_corpus(tok)
    max_len, q = 128, 32
    it = BertIterator(tok, sents, labels, num_classes=2, batch_size=4,
                      max_len=max_len, dynamic_seq_len=True, bucket_size=q)
    batches = list(it)
    seq_lens = {b.features.shape[1] for b in batches}
    assert all(L % q == 0 and L <= max_len for L in seq_lens)
    assert len(seq_lens) <= math.ceil(max_len / q)
    # every example appears exactly once across buckets
    total = sum(int(b.labels_mask.sum()) for b in batches)
    assert total == len(sents)
    # masks carry validity: real token count survives the re-layout
    static = BertIterator(tok, sents, labels, num_classes=2, batch_size=4,
                          max_len=max_len)
    want_tokens = sum(int(b.features_mask.sum()) for b in static)
    got_tokens = sum(int(b.features_mask.sum()) for b in batches)
    assert got_tokens == want_tokens
    # batch shape stays static per bucket (tail examples padded + masked)
    assert all(b.features.shape[0] == 4 for b in batches)


def test_bert_iterator_bucketing_saves_padding():
    tok = BertWordPieceTokenizer(VOCAB)
    sents = [" ".join(["the"] * 3)] * 8      # all-short corpus
    it = BertIterator(tok, sents, [0] * 8, num_classes=2, batch_size=4,
                      max_len=128, dynamic_seq_len=True, bucket_size=32)
    for b in it:
        assert b.features.shape[1] == 32      # not 128


def _tiny_seq_classifier(vocab_size, max_len, num_classes=2):
    from deeplearning4j_tpu.models import SequentialModel
    from deeplearning4j_tpu.nn import Adam
    from deeplearning4j_tpu.nn.activations import Activation
    from deeplearning4j_tpu.nn.conf import (
        Embedding, GlobalPooling, InputType, NeuralNetConfiguration,
        OutputLayer, PoolingType,
    )

    conf = (
        NeuralNetConfiguration.builder().seed(1).updater(Adam(1e-3))
        .list()
        .layer(Embedding(n_in=vocab_size, n_out=8))
        .layer(GlobalPooling(pooling=PoolingType.AVG))
        .layer(OutputLayer(n_out=num_classes, activation=Activation.SOFTMAX))
        .set_input_type(InputType.recurrent(1, max_len))
        .build()
    )
    return SequentialModel(conf).init()


def test_mixed_length_corpus_compiles_at_most_n_buckets():
    """THE acceptance criterion: >= 6 distinct lengths, quantum 32 ->
    at most ceil(max_len/32) compiled step programs, asserted by the new
    recompile counter (Model.compile_stats)."""
    tok = BertWordPieceTokenizer(VOCAB)
    sents, labels = _mixed_corpus(tok)
    max_len, q = 128, 32
    it = BertIterator(tok, sents, labels, num_classes=2, batch_size=4,
                      max_len=max_len, dynamic_seq_len=True, bucket_size=q)
    # precondition: the corpus genuinely mixes >= 6 distinct lengths
    it._encode_all()
    assert len({int(x) for x in it._lengths}) >= 6
    m = _tiny_seq_classifier(len(VOCAB), max_len)
    before = compile_stats.snapshot()
    m.fit(it, epochs=2)                      # epoch 2: all programs cached
    spent = compile_stats.snapshot() - before
    n_buckets = math.ceil(max_len / q)
    assert m.compile_stats()["step_programs"] <= n_buckets
    # and the global counter agrees the fit actually traced something
    assert spent.jit_cache_misses >= 1


def test_compile_stats_counts_fresh_traces():
    import jax
    import jax.numpy as jnp

    before = compile_stats.snapshot()
    f = jax.jit(lambda x: x * 2 + 1)
    f(jnp.ones((3,))).block_until_ready()
    mid = compile_stats.snapshot() - before
    assert mid.jit_cache_misses >= 1
    f(jnp.ones((3,))).block_until_ready()    # cached: no new trace
    again = compile_stats.snapshot() - before
    assert again.jit_cache_misses == mid.jit_cache_misses


# -- persistent compile cache (subprocess warm start) ----------------------

_WARMSTART_SCRIPT = r"""
import json, os, sys
import jax
jax.config.update("jax_platforms", "cpu")
# record every later config update: with JAX_COMPILATION_CACHE_DIR set the
# package must leave jax_compilation_cache_dir to jax
_updates = []
_orig_update = jax.config.update
def _recording_update(name, value):
    _updates.append(name)
    return _orig_update(name, value)
jax.config.update = _recording_update
import numpy as np
from deeplearning4j_tpu.data.dataset import DataSet
from deeplearning4j_tpu.models import SequentialModel
from deeplearning4j_tpu.nn import Sgd
from deeplearning4j_tpu.nn.activations import Activation
from deeplearning4j_tpu.nn.conf import (
    Dense, InputType, NeuralNetConfiguration, OutputLayer,
)
from deeplearning4j_tpu.runtime import compile_stats, init_compile_cache

assert init_compile_cache() == os.environ["JAX_COMPILATION_CACHE_DIR"]
assert "jax_compilation_cache_dir" not in _updates, _updates
conf = (
    NeuralNetConfiguration.builder().seed(0).updater(Sgd(0.1))
    .list()
    .layer(Dense(n_out=16, activation=Activation.RELU))
    .layer(OutputLayer(n_out=4, activation=Activation.SOFTMAX))
    .set_input_type(InputType.feed_forward(12))
    .build()
)
m = SequentialModel(conf).init()
x = np.random.default_rng(0).normal(size=(8, 12)).astype(np.float32)
y = np.eye(4, dtype=np.float32)[np.arange(8) % 4]
m.fit_batch(DataSet(x, y))
assert np.isfinite(m.score_value)
print(json.dumps(compile_stats.snapshot().as_dict()))
"""


def test_second_process_warm_starts_from_persistent_cache(tmp_path):
    """Acceptance: a second Python process reusing the persistent cache
    compiles the same model with ZERO fresh XLA compilations — every
    compile request is served from disk."""
    cache = str(tmp_path / "xla_cache")
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "JAX_COMPILATION_CACHE_DIR": cache,
        # persist EVERYTHING: the threshold exists for prod hygiene, the
        # test needs determinism
        "DL4J_TPU_CACHE_MIN_COMPILE_SECS": "0",
    })

    def run():
        proc = subprocess.run(
            [sys.executable, "-c", _WARMSTART_SCRIPT],
            capture_output=True, text=True, timeout=300, env=env, cwd=REPO,
        )
        assert proc.returncode == 0, proc.stderr[-2000:]
        return json.loads(proc.stdout.strip().splitlines()[-1])

    cold = run()
    assert cold["fresh_backend_compiles"] > 0       # actually compiled
    assert cold["persistent_cache_puts"] > 0        # ...and persisted
    warm = run()
    assert warm["backend_compiles"] > 0             # same programs needed
    assert warm["fresh_backend_compiles"] == 0      # all served from disk
    assert warm["persistent_cache_hits"] == warm["backend_compiles"]
    assert os.listdir(cache)                        # ...from THAT directory


# -- where the cache lives (placed from outside) ----------------------------

@pytest.fixture
def restore_cache_config():
    """These tests run `init_compile_cache`'s body (the public function is
    memoized per process) against a chosen jax-level setting; put the
    process's own setting back afterwards."""
    import jax

    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_unset_cache_dir_defaults_to_the_checkout(restore_cache_config):
    """Nothing configured -> `<checkout>/.jax_cache`, resolved from the
    package's own path: never `~`, XDG_CACHE_HOME, a tempdir, a pid or a
    time (the path is part of the cache key, so a directory that moves
    never hits)."""
    import jax

    from deeplearning4j_tpu.runtime import init_compile_cache

    jax.config.update("jax_compilation_cache_dir", None)
    want = os.path.join(REPO, ".jax_cache")
    assert init_compile_cache.__wrapped__() == want
    assert jax.config.jax_compilation_cache_dir == want
    assert os.path.isdir(want)


def test_configured_cache_dir_is_left_to_jax(restore_cache_config, tmp_path,
                                             monkeypatch):
    """A directory set from outside (JAX_COMPILATION_CACHE_DIR lands in
    this very config value at import) is used as is: the package performs
    NO `jax_compilation_cache_dir` update of its own."""
    import jax

    from deeplearning4j_tpu.runtime import init_compile_cache

    outside = str(tmp_path / "placed_from_outside")
    jax.config.update("jax_compilation_cache_dir", outside)
    updates = []
    real_update = jax.config.update
    monkeypatch.setattr(
        jax.config, "update",
        lambda name, value: (updates.append(name), real_update(name, value)))
    assert init_compile_cache.__wrapped__() == outside
    monkeypatch.undo()
    assert "jax_compilation_cache_dir" not in updates
    assert jax.config.jax_compilation_cache_dir == outside


# -- CachedDataSetIterator -------------------------------------------------

class _CountingUint8Iterator(DataSetIterator):
    """Stand-in for the decode pipeline: uint8 wire-format batches, with
    a pull counter standing in for 'JPEGs decoded'."""

    def __init__(self, n_batches=4, batch=3):
        rng = np.random.default_rng(7)
        self._batches = [
            DataSet(
                rng.integers(0, 255, (batch, 8, 8, 3)).astype(np.uint8),
                np.eye(2, dtype=np.float32)[rng.integers(0, 2, batch)],
            )
            for _ in range(n_batches)
        ]
        self.pulls = 0

    @property
    def batch_size(self):
        return self._batches[0].num_examples

    def reset(self):
        pass

    def __iter__(self):
        for b in self._batches:
            self.pulls += 1
            yield b


def test_cached_iterator_round_trips_byte_identical(tmp_path):
    from deeplearning4j_tpu.data.cached import CachedDataSetIterator

    base = _CountingUint8Iterator()
    it = CachedDataSetIterator(base, str(tmp_path / "cache"))
    assert not it.is_cached
    epoch1 = list(it)
    assert it.is_cached and base.pulls == 4
    epoch2 = list(it)
    assert base.pulls == 4                    # decode path skipped
    assert it.cache_hits == 4
    assert len(epoch2) == len(epoch1) == 4
    for a, b in zip(epoch1, epoch2):
        bf = np.asarray(b.features)
        assert bf.dtype == np.uint8           # wire format preserved
        assert np.asarray(a.features).tobytes() == bf.tobytes()
        assert np.asarray(a.labels).tobytes() == np.asarray(b.labels).tobytes()
        assert b.features_mask is None and b.labels_mask is None


def test_cached_iterator_fresh_instance_reuses_disk_cache(tmp_path):
    from deeplearning4j_tpu.data.cached import CachedDataSetIterator

    cache = str(tmp_path / "cache")
    base = _CountingUint8Iterator()
    list(CachedDataSetIterator(base, cache))
    # a NEW process/instance with no base at all replays the same bytes
    it2 = CachedDataSetIterator(None, cache)
    assert it2.is_cached and it2.batch_size == 3
    replay = list(it2)
    assert len(replay) == 4
    for a, b in zip(base._batches, replay):
        assert np.asarray(a.features).tobytes() == np.asarray(b.features).tobytes()


def test_cached_iterator_incomplete_cache_not_trusted(tmp_path):
    from deeplearning4j_tpu.data.cached import CachedDataSetIterator

    cache = str(tmp_path / "cache")
    base = _CountingUint8Iterator()
    it = CachedDataSetIterator(base, cache)
    next(iter(it))                            # abandon mid-population
    assert not it.is_cached
    it2 = CachedDataSetIterator(_CountingUint8Iterator(), cache)
    assert not it2.is_cached                  # no manifest -> re-decode
    assert len(list(it2)) == 4
    assert it2.is_cached


def test_cached_iterator_requires_base_or_cache(tmp_path):
    from deeplearning4j_tpu.data.cached import CachedDataSetIterator

    with pytest.raises(ValueError, match="no complete cache"):
        CachedDataSetIterator(None, str(tmp_path / "nothing"))


def test_cached_iterator_trains_a_model(tmp_path):
    """End-to-end: the uint8 replay feeds fit() exactly like the live
    decode pipeline (the models cast uint8 inside the compiled step)."""
    from deeplearning4j_tpu.data.cached import CachedDataSetIterator
    from deeplearning4j_tpu.models import SequentialModel
    from deeplearning4j_tpu.nn import Sgd
    from deeplearning4j_tpu.nn.activations import Activation
    from deeplearning4j_tpu.nn.conf import (
        Dense, InputType, NeuralNetConfiguration, OutputLayer,
    )

    conf = (
        NeuralNetConfiguration.builder().seed(0).updater(Sgd(0.01))
        .list()
        .layer(Dense(n_out=8, activation=Activation.RELU))
        .layer(OutputLayer(n_out=2, activation=Activation.SOFTMAX))
        .set_input_type(InputType.convolutional(8, 8, 3))
        .build()
    )
    m = SequentialModel(conf).init()
    it = CachedDataSetIterator(_CountingUint8Iterator(), str(tmp_path / "c"))
    m.fit(it, epochs=2)
    assert np.isfinite(m.score_value)


# -- SequenceRecordReaderDataSetIterator bucketing -------------------------

def test_sequence_record_reader_iterator_buckets_ragged_lengths():
    from deeplearning4j_tpu.datavec import SequenceRecordReaderDataSetIterator

    # ragged sequences: [f0, f1, label] per timestep
    def seq(t, cls):
        return [[float(i), float(i) * 0.5, cls] for i in range(t)]

    seqs = [seq(t, t % 2) for t in (2, 3, 5, 9, 2, 3, 11, 7)]
    it = SequenceRecordReaderDataSetIterator(
        seqs, batch_size=2, label_index=2, num_classes=2, bucket_size=4,
    )
    batches = list(it)
    lens = {b.features.shape[1] for b in batches}
    assert all(L % 4 == 0 for L in lens)
    assert len(lens) <= math.ceil(11 / 4)
    total_steps = sum(int(b.features_mask.sum()) for b in batches)
    assert total_steps == sum(len(s) for s in seqs)
    for b in batches:
        assert b.features.shape[0] == 2       # static batch dim, tail padded
        assert b.labels.shape[:2] == b.features.shape[:2]
        assert b.labels.shape[2] == 2
        # labels one-hot only on real steps
        np.testing.assert_array_equal(
            b.labels.sum(-1), b.labels_mask
        )


def test_sequence_record_reader_iterator_names_empty_sequence():
    from deeplearning4j_tpu.datavec import SequenceRecordReaderDataSetIterator

    seqs = [[[1.0, 2.0, 0.0]] * 3, []]        # upstream ETL artifact
    it = SequenceRecordReaderDataSetIterator(
        seqs, batch_size=2, label_index=2, num_classes=2, bucket_size=4,
    )
    with pytest.raises(ValueError, match="sequence 1 has zero timesteps"):
        list(it)


def test_sequence_record_reader_iterator_regression_and_unlabeled():
    from deeplearning4j_tpu.datavec import SequenceRecordReaderDataSetIterator

    seqs = [[[1.0, 2.0, 0.5]] * 3, [[3.0, 4.0, 1.5]] * 5]
    reg = SequenceRecordReaderDataSetIterator(
        seqs, batch_size=2, label_index=2, regression=True, bucket_size=4,
    )
    batches = list(reg)
    assert all(b.labels.shape[2] == 1 for b in batches)
    unl = SequenceRecordReaderDataSetIterator(
        seqs, batch_size=2, bucket_size=4,
    )
    for b in unl:
        assert b.labels.shape[1] == 0


# -- ETL-wait metric + listener surfaces -----------------------------------

class _SlowIterator(DataSetIterator):
    def __init__(self, batches, delay=0.01):
        self._batches = batches
        self._delay = delay

    @property
    def batch_size(self):
        return self._batches[0].num_examples

    def reset(self):
        pass

    def __iter__(self):
        for b in self._batches:
            time.sleep(self._delay)
            yield b


def test_etl_wait_metric_and_listener_surfaces(tmp_path):
    from deeplearning4j_tpu.models import SequentialModel
    from deeplearning4j_tpu.nn import Sgd
    from deeplearning4j_tpu.nn.activations import Activation
    from deeplearning4j_tpu.nn.conf import (
        Dense, InputType, NeuralNetConfiguration, OutputLayer,
    )
    from deeplearning4j_tpu.train.listeners import PerformanceListener
    from deeplearning4j_tpu.ui.stats import InMemoryStatsStorage, StatsListener

    conf = (
        NeuralNetConfiguration.builder().seed(0).updater(Sgd(0.1))
        .list()
        .layer(Dense(n_out=4, activation=Activation.RELU))
        .layer(OutputLayer(n_out=2, activation=Activation.SOFTMAX))
        .set_input_type(InputType.feed_forward(6))
        .build()
    )
    m = SequentialModel(conf).init()
    rng = np.random.default_rng(0)
    batches = [
        DataSet(rng.normal(size=(4, 6)).astype(np.float32),
                np.eye(2, dtype=np.float32)[rng.integers(0, 2, 4)])
        for _ in range(3)
    ]
    perf = PerformanceListener(frequency=1, warmup_iterations=1)
    storage = InMemoryStatsStorage()
    stats = StatsListener(storage, session_id="etl_test")
    m.set_listeners(perf, stats)
    m.fit(_SlowIterator(batches), epochs=2)

    assert m.etl_wait_s > 0.0                       # the sleeps were charged
    assert perf.etl_wait_seconds() > 0.0
    cs = perf.compile_stats()
    assert cs["jit_cache_misses"] >= 1              # the step fn traced
    assert cs["compile_secs"] > 0.0
    rec = storage.latest("etl_test")
    assert rec["etl_wait_s"] > 0.0
    assert rec["compile"]["jit_cache_misses"] >= 1
    # model-level counter: one program for the one batch shape
    assert m.compile_stats()["step_programs"] == 1
