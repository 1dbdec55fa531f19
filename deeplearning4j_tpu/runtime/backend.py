"""Backend discovery — the `Nd4jBackend` SPI role, TPU-native.

The reference selects an execution backend (nd4j-native CPU vs nd4j-cuda)
by classpath service discovery and routes every op through that backend's
OpExecutioner (SURVEY.md §1 L2, §2.2).  Here the "backend" is a PJRT
platform reported by JAX; ops never route through a host-side executioner —
whole computations are compiled — so the backend object only carries
identity, capability and preferred-dtype information.
"""

from __future__ import annotations

import dataclasses
import functools
import logging
import os

import jax
import numpy as np

log = logging.getLogger("deeplearning4j_tpu")


@dataclasses.dataclass(frozen=True)
class Backend:
    """Identity + capabilities of the active PJRT platform."""

    platform: str                 # "tpu" | "cpu" | "gpu"
    device_kind: str              # e.g. "TPU v5 lite"
    num_devices: int
    supports_bfloat16_matmul: bool

    @property
    def is_tpu(self) -> bool:
        return self.platform == "tpu"

    @property
    def compute_dtype(self):
        """Preferred matmul/conv dtype: bf16 on TPU (MXU-native), f32 on CPU."""
        return np.dtype("bfloat16") if self.supports_bfloat16_matmul else np.dtype("float32")


#: default cache directory: ``<checkout>/.jax_cache`` (listed in
#: ``.gitignore``), resolved from this file's own location.  The path is
#: part of the cache key, so it must not depend on the user, the process
#: or the time — a sealed machine that gets a copy of the checkout finds
#: what an earlier process on that copy compiled.
DEFAULT_COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))),
    ".jax_cache",
)


@functools.cache
def init_compile_cache() -> str | None:
    """Enable the persistent XLA compile cache by default (idempotent).

    Every user process otherwise recompiles its models from scratch —
    seconds to minutes of pure tax for programs XLA already built
    yesterday.

    The cache directory is placed from OUTSIDE the program: when the
    standard ``JAX_COMPILATION_CACHE_DIR`` is set (or the caller already
    configured ``jax_compilation_cache_dir``), jax owns the setting and
    this function performs no ``jax.config.update`` of it at all.  Only
    when nothing is configured does it install the default,
    `DEFAULT_COMPILE_CACHE_DIR`.

    ``DL4J_TPU_CACHE_MIN_COMPILE_SECS`` overrides jax's persist
    threshold (default 1.0s: tiny programs recompile faster than disk
    round-trips; set 0 to persist everything, as the warm-start tests
    do).  Returns the active cache dir, or None when the default
    directory cannot be created.  Hit/miss counts are observable via
    `runtime.compile_stats`.
    """
    from deeplearning4j_tpu.runtime import compile_stats

    compile_stats.install()          # count hits/misses from the first jit
    path = jax.config.jax_compilation_cache_dir
    if not path:
        path = DEFAULT_COMPILE_CACHE_DIR
        try:
            os.makedirs(path, exist_ok=True)
        except OSError as exc:        # read-only checkout — never fatal
            log.warning("persistent compile cache disabled (%s): %s",
                        path, exc)
            return None
        jax.config.update("jax_compilation_cache_dir", path)
    min_secs = os.environ.get("DL4J_TPU_CACHE_MIN_COMPILE_SECS")
    if min_secs is not None:
        jax.config.update(
            "jax_persistent_cache_min_compile_time_secs", float(min_secs)
        )
    log.info("persistent XLA compile cache: %s", path)
    return path


@functools.cache
def backend() -> Backend:
    init_compile_cache()
    devs = jax.devices()
    d0 = devs[0]
    return Backend(
        platform=d0.platform,
        device_kind=str(getattr(d0, "device_kind", d0.platform)),
        num_devices=len(devs),
        supports_bfloat16_matmul=d0.platform == "tpu",
    )


def devices():
    return jax.devices()


def device_count() -> int:
    return len(jax.devices())


def platform() -> str:
    return backend().platform
