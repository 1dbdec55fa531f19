"""Test configuration: force an 8-device virtual CPU platform.

The reference tests multi-node without a cluster via Spark local[N] and
Aeron loopback (SURVEY.md §4.2); our equivalent is
xla_force_host_platform_device_count=8 on the CPU plugin, so every sharding
test runs on a real 8-way Mesh with real XLA collectives, no TPU needed.
These env vars MUST be set before jax initializes its backends — hence here,
at conftest import time, before any test module imports jax.
"""

import os

prev = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in prev:
    os.environ["XLA_FLAGS"] = (prev + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")
# Test runs place their compile cache from outside the checkout, in the
# per-user directory: a fresh checkout (CI, a driver's clone) then starts
# warm instead of recompiling every >1 s program (measured: 1,114 s warm
# vs 1,359 s cold for tier-1).  The package itself never picks this
# location; unset, it uses <checkout>/.jax_cache
# (runtime/backend.init_compile_cache).  Set before jax is imported, and
# inherited by the subprocess tests.
os.environ.setdefault(
    "JAX_COMPILATION_CACHE_DIR",
    os.path.join(os.path.expanduser("~"), ".cache", "deeplearning4j_tpu", "xla"),
)

import numpy as np  # noqa: E402
import pytest  # noqa: E402
import jax  # noqa: E402

# Tests force the CPU: they must run (and mean the same) on a host that
# has an accelerator, whatever JAX_PLATFORMS says.
jax.config.update("jax_platforms", "cpu")

# This XLA CPU build defaults to low-precision matmul (bf16-sized error on a
# plain f32 matmul); pin to float32 so numeric assertions are meaningful.
jax.config.update("jax_default_matmul_precision", "float32")


@pytest.fixture
def rng():
    return np.random.default_rng(42)


@pytest.fixture(autouse=True)
def _crash_artifacts_dir(tmp_path, monkeypatch):
    """Crash artifacts (hang reports, serving flight-recorder dumps) go
    to tmp, never the repo cwd — watchdog aborts and SLO alerts write
    post-mortem dumps by design now, including from tests that induce
    them."""
    monkeypatch.setenv("DL4JTPU_CRASH_DIR", str(tmp_path / "crash"))


class HostProfile:
    """A CPU `jax.profiler` session around a block, read back as the
    host plane's lines: ``with host_profile() as prof: ...`` then
    `prof.lines` is a list (one entry per thread line of "/host:CPU") of
    event dicts ``{"name", "start", "end", "stats"}`` (nanoseconds),
    sorted by start.  Runtime TraceMe events only, as the benchmark's
    traced runs record them (`python_tracer_level = 0`)."""

    def __init__(self, log_dir: str):
        self.log_dir = log_dir
        self.lines: list = []

    def __enter__(self):
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.log_dir, profiler_options=opts)
        return self

    def __exit__(self, *exc):
        import glob
        import warnings

        jax.profiler.stop_trace()
        path = sorted(glob.glob(os.path.join(
            self.log_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
        with warnings.catch_warnings():
            # this jaxlib's stats iterator warns about its own type
            warnings.simplefilter("ignore", DeprecationWarning)
            for plane in jax.profiler.ProfileData.from_file(path).planes:
                if not plane.name.startswith("/host:CPU"):
                    continue
                for line in plane.lines:
                    self.lines.append(sorted(
                        ({"name": e.name, "start": e.start_ns,
                          "end": e.start_ns + e.duration_ns,
                          "stats": dict(e.stats)} for e in line.events),
                        key=lambda e: e["start"]))
        return False

    def line_with(self, name: str) -> list:
        """The one thread line that holds events called `name`."""
        found = [ln for ln in self.lines
                 if any(e["name"] == name for e in ln)]
        assert len(found) == 1, (name, len(found))
        return found[0]


@pytest.fixture
def host_profile(tmp_path):
    return lambda: HostProfile(str(tmp_path / "profile"))


def learned_position_lm(vocab=31, d=16, heads=2, layers=2, *,
                        max_length=32, bf16=None, seed=5):
    """The serving benchmark's stack at toy widths, initialised: Embedding,
    a LEARNED position table, causal blocks and the chunked-vocab head.
    ``bf16=True`` forces the activation type a TPU picks by default."""
    from deeplearning4j_tpu.models.sequential import SequentialModel
    from deeplearning4j_tpu.nn.conf import (
        ChunkedSoftmaxOutputLayer, Embedding, InputType,
        NeuralNetConfiguration,
    )
    from deeplearning4j_tpu.nn.conf.attention import (
        PositionalEncoding, TransformerEncoderBlock,
    )

    b = NeuralNetConfiguration.builder().seed(seed)
    if bf16 is not None:
        b = b.bf16_compute(bf16)
    b = (b.list()
         .layer(Embedding(n_in=vocab, n_out=d))
         .layer(PositionalEncoding(learned=True, max_length=max_length)))
    for _ in range(layers):
        b.layer(TransformerEncoderBlock(d_model=d, n_heads=heads,
                                        causal=True))
    b.layer(ChunkedSoftmaxOutputLayer(n_out=vocab, chunk=16))
    return SequentialModel(
        b.set_input_type(InputType.recurrent(1)).build()).init()
