"""Configuration of the benchmark's own tests: `python -m pytest benchmarks/tests`
from the root of the repo.  As `tests/conftest.py` does for the package's: the
CPU, eight virtual devices (the data-parallel rehearsal takes four), float32
matmuls, and a compile cache outside the checkout.  These tests are NOT under
`tests/`, so the tier-1 run neither collects nor schedules them: it stays what
it was (two full tier-1 runs with these files under `tests/` each tripped a
different timing-marginal multi-process elastic test; two without were clean).
"""

import os

prev = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in prev:
    os.environ["XLA_FLAGS"] = (
        prev + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault(
    "JAX_COMPILATION_CACHE_DIR",
    os.path.join(os.path.expanduser("~"), ".cache", "deeplearning4j_tpu",
                 "xla"))

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_default_matmul_precision", "float32")
