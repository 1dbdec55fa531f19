"""Multi-host runtime bootstrap — the cluster story's data plane.

Reference role: DL4J scales past one box with Spark driver/executor
orchestration plus an Aeron UDP mesh for gradient traffic
(`SparkDl4jMultiLayer`, `SharedTrainingMaster`, `ModelParameterServer` —
SURVEY.md §2.2, §3.5).  TPU-native, the data plane is jax.distributed: every
host process runs the SAME SPMD program, `jax.devices()` spans all hosts,
and GSPMD inserts cross-host collectives that ride ICI within a slice and
DCN across slices.  There is no parameter server and no gossip — sync
full-precision AllReduce replaces the threshold-encoded async exchange by
design (SURVEY.md §5.8).

The control plane (membership, heartbeat, elastic restart orchestration —
the Spark-driver/MeshOrganizer role) lives in
`deeplearning4j_tpu.runtime.coordinator`; this module owns only the JAX
runtime bring-up.

Multi-node-without-a-cluster (SURVEY.md §4.2): N local processes, CPU
platform, gloo collectives — the Spark-`local[N]`/Aeron-loopback analog.
`DistributedConfig(local_device_count=k, platform="cpu")` makes one host
process simulate a k-device worker; the test-suite drives whole worker
fleets this way.
"""

from __future__ import annotations

import dataclasses
import logging
import os
from typing import Optional

log = logging.getLogger("deeplearning4j_tpu")

ENV_COORDINATOR = "DL4JTPU_COORDINATOR"       # host:port of process 0
ENV_NUM_PROCESSES = "DL4JTPU_NUM_PROCESSES"
ENV_PROCESS_ID = "DL4JTPU_PROCESS_ID"
ENV_LOCAL_DEVICES = "DL4JTPU_LOCAL_DEVICES"   # CPU simulation only
ENV_PLATFORM = "DL4JTPU_PLATFORM"             # "cpu" to force the simulator


@dataclasses.dataclass(frozen=True)
class DistributedConfig:
    """How this process joins the cluster.

    All-None (on Cloud TPU) lets jax.distributed auto-detect the slice
    topology from the TPU metadata server.  For explicit clusters (and for
    the CPU simulator) give coordinator_address + num_processes +
    process_id.
    """

    coordinator_address: Optional[str] = None
    num_processes: Optional[int] = None
    process_id: Optional[int] = None
    # CPU-simulation knobs (multi-node-without-a-cluster):
    local_device_count: Optional[int] = None
    platform: Optional[str] = None
    # data-plane failure-detection latency (None = jax default, 100s)
    heartbeat_timeout_seconds: Optional[int] = None

    @staticmethod
    def from_env() -> "DistributedConfig":
        def _int(name):
            v = os.environ.get(name)
            return int(v) if v not in (None, "") else None

        return DistributedConfig(
            coordinator_address=os.environ.get(ENV_COORDINATOR) or None,
            num_processes=_int(ENV_NUM_PROCESSES),
            process_id=_int(ENV_PROCESS_ID),
            local_device_count=_int(ENV_LOCAL_DEVICES),
            platform=os.environ.get(ENV_PLATFORM) or None,
        )


_initialized = False


def initialize(config: DistributedConfig | None = None) -> None:
    """Join (or form) the multi-host JAX runtime.

    Must run before any other JAX call in the process (backend
    initialization is one-shot).  Safe to call when the process is the
    whole cluster (num_processes in (None, 1) with no coordinator):
    becomes a no-op so single-host scripts run unchanged.
    """
    global _initialized
    import jax

    if _initialized:
        return
    config = config or DistributedConfig.from_env()

    # ONE truth for "will this process be part of a multi-process world":
    # the gloo-collectives config below and the world-formation skip must
    # agree, or a formed world ends up without cross-process collectives.
    # num_processes == 1 is single even WITH a coordinator address (an
    # elastic world that shrank to one worker): forming a one-process
    # distributed runtime buys no collectives and adds a shutdown barrier
    # that can hang on exit.
    single_process = config.num_processes == 1 or (
        config.num_processes is None and config.coordinator_address is None
    )
    multiprocess = not single_process

    if config.platform == "cpu" or config.local_device_count:
        jax.config.update("jax_platforms", "cpu")
        if config.local_device_count:
            jax.config.update(
                "jax_num_cpu_devices", int(config.local_device_count)
            )
        # cross-process CPU collectives need an explicit implementation —
        # but ONLY in a real multi-process world: the gloo factory needs a
        # distributed client, and a single-process world (which skips
        # jax.distributed bring-up below) would crash at backend creation
        if multiprocess:
            jax.config.update("jax_cpu_collectives_implementation", "gloo")

    if not multiprocess:
        _initialized = True  # single-process: nothing to form
        return

    kwargs = {}
    if config.heartbeat_timeout_seconds is not None:
        kwargs["heartbeat_timeout_seconds"] = config.heartbeat_timeout_seconds
    jax.distributed.initialize(
        coordinator_address=config.coordinator_address,
        num_processes=config.num_processes,
        process_id=config.process_id,
        **kwargs,
    )
    _initialized = True


def shutdown() -> None:
    global _initialized
    import jax

    if _initialized:
        try:
            jax.distributed.shutdown()
        except Exception as e:
            # best-effort teardown (peers may already be gone), but a
            # silent failure here has masked wedged-barrier bugs before
            log.debug("jax.distributed.shutdown failed: %s", e)
    _initialized = False


def is_initialized() -> bool:
    return _initialized


def process_index() -> int:
    import jax

    return jax.process_index()


def process_count() -> int:
    import jax

    return jax.process_count()


def is_chief() -> bool:
    """True on the process that owns cluster-singleton work (checkpoint
    writes, stats export) — the Spark-driver role."""
    return process_index() == 0


def barrier(name: str = "dl4jtpu") -> None:
    """Block until every process reaches this point (device-level sync)."""
    from jax.experimental import multihost_utils

    multihost_utils.sync_global_devices(name)


def put_global(arr, sharding, *, full_value: bool = False):
    """Assemble a global jax.Array from this process's host data.

    Single-process: plain device_put.  Multi-process, full_value=False:
    each process passes its LOCAL portion of a batch-sharded array (per-host
    input pipelines feed disjoint shards — the RDD-partition role) and the
    global shape is inferred by concatenation.  full_value=True: every
    process passes the SAME complete array (param placement), so the global
    shape is the array's own shape regardless of how the spec shards it —
    without this, a cross-host-sharded param would get a wrongly inflated
    inferred global shape.
    """
    import jax

    if arr is None:
        return None
    if jax.process_count() == 1:
        return jax.device_put(arr, sharding)
    import numpy as np

    arr = np.asarray(arr)
    if full_value:
        return jax.make_array_from_process_local_data(
            sharding, arr, global_shape=arr.shape
        )
    return jax.make_array_from_process_local_data(sharding, arr)


def fetch_global(arr):
    """Bring a (possibly non-addressable) global array fully to this host —
    the allgather needed before single-writer checkpoint/serialization of
    cross-host-sharded values."""
    import jax
    import numpy as np

    if getattr(arr, "is_fully_addressable", True):
        return np.asarray(arr)
    from jax.experimental import multihost_utils

    return np.asarray(multihost_utils.process_allgather(arr, tiled=True))


from deeplearning4j_tpu.data.iterator import DataSetIterator as _DataSetIterator


class DistributedDataSetIterator(_DataSetIterator):
    """Rank-strided view of a DataSetIterator: process k of N yields
    batches k, N+k, 2N+k, ... — the RDD-partition role for multi-host
    input pipelines (each host reads DISJOINT data; `put_global` then
    assembles the global batch from per-host shards).

    A ragged tail (total batches not divisible by world size) is DROPPED
    on every rank: each fit_batch is a cross-host collective, so unequal
    per-host step counts would wedge the slice on the last step.

    Wrap the SAME underlying iterator construction on every host:

        it = DistributedDataSetIterator(CsvIterator(...))
        model.fit(it)            # each host consumes its stride
    """

    def __init__(self, inner, rank: int | None = None,
                 world_size: int | None = None):
        self.inner = inner
        self.rank = process_index() if rank is None else rank
        self.world = process_count() if world_size is None else world_size
        self._consumed = False
        if not (0 <= self.rank < self.world):
            raise ValueError(f"rank {self.rank} outside world {self.world}")

    @property
    def batch_size(self):
        return getattr(self.inner, "batch_size", None)

    def _one_shot(self) -> bool:
        """True when the inner can serve exactly one pass (a generator:
        its own iterator, no reset)."""
        return not hasattr(self.inner, "reset") and iter(self.inner) is self.inner

    def __iter__(self):
        # a one-shot inner serves exactly ONE (possibly partial) pass;
        # starting a second would silently yield zero batches — or worse,
        # resume mid-stream after a partial pass
        if self._consumed and self._one_shot():
            raise NotImplementedError(
                f"{type(self.inner).__name__} is a one-shot iterator; wrap "
                "a resettable DataSetIterator (or a list) for multi-epoch use"
            )
        self._consumed = True          # armed at START: partial passes count
        # yield only from COMPLETE stride groups so every rank sees the
        # same step count (works for streaming inners of unknown length)
        group = []
        for batch in self.inner:
            group.append(batch)
            if len(group) == self.world:
                yield group[self.rank]
                group = []

    def reset(self) -> None:
        # fit() resets after EVERY epoch incl. the last; only an actual
        # second pass over a ONE-SHOT inner is an error (see __iter__)
        if hasattr(self.inner, "reset"):
            self.inner.reset()
            self._consumed = False
        elif not self._one_shot():     # re-iterable (e.g. a list)
            self._consumed = False
