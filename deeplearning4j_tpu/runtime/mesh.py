"""Device-mesh construction — the scale-out topology substrate.

The reference organizes multi-device work as replica threads
(ParallelWrapper) and a UDP tree mesh (MeshOrganizer in
nd4j-parameter-server — SURVEY.md §2.3, §5.8).  TPU-native, topology is a
`jax.sharding.Mesh` with named axes and scale-out is sharding over those
axes; XLA inserts the collectives.  Axis-name conventions used throughout
the framework:

    "data"   — data parallel (batch dim)
    "model"  — tensor/model parallel (feature/head dims)
    "pipe"   — pipeline-parallel stage axis
    "seq"    — sequence/context parallel (ring attention axis)
    "expert" — expert parallel (MoE)

A MeshSpec names the axes present and their sizes; `make_mesh` lays the
available devices out accordingly.  On CPU, `virtual_cpu_devices` documents
the XLA_FLAGS trick used by the test-suite (the TPU-build analog of the
reference's "Spark local[N] / Aeron loopback" multi-node-without-a-cluster
patterns, SURVEY.md §4.2).
"""

from __future__ import annotations

import dataclasses
import math
from collections.abc import Sequence

import jax
import numpy as np
from jax.sharding import Mesh

DATA_AXIS = "data"
MODEL_AXIS = "model"
PIPE_AXIS = "pipe"
SEQ_AXIS = "seq"
EXPERT_AXIS = "expert"


@dataclasses.dataclass(frozen=True)
class MeshSpec:
    """Named axis layout for a device mesh.

    Axis sizes of -1 mean "fill with all remaining devices" (at most one
    axis may be -1).  Axes of size 1 are kept: a size-1 axis lets the same
    pjit-ted step run unchanged at any scale.
    """

    axes: tuple[tuple[str, int], ...] = ((DATA_AXIS, -1),)

    @staticmethod
    def data_parallel() -> "MeshSpec":
        return MeshSpec(((DATA_AXIS, -1),))

    @staticmethod
    def of(**axis_sizes: int) -> "MeshSpec":
        return MeshSpec(tuple(axis_sizes.items()))

    @property
    def axis_names(self) -> tuple[str, ...]:
        return tuple(name for name, _ in self.axes)

    def resolve(self, n_devices: int) -> tuple[tuple[str, int], ...]:
        sizes = [s for _, s in self.axes]
        wild = [i for i, s in enumerate(sizes) if s == -1]
        if len(wild) > 1:
            raise ValueError(f"at most one axis may be -1, got {self.axes}")
        fixed = math.prod(s for s in sizes if s != -1)
        if wild:
            if n_devices % fixed:
                raise ValueError(
                    f"{n_devices} devices not divisible by fixed axes product {fixed}"
                )
            sizes[wild[0]] = n_devices // fixed
        elif fixed != n_devices:
            raise ValueError(
                f"mesh axes {self.axes} need {fixed} devices, have {n_devices}"
            )
        return tuple((name, size) for (name, _), size in zip(self.axes, sizes))


def make_mesh(
    spec: MeshSpec | None = None,
    devices: Sequence[jax.Device] | None = None,
) -> Mesh:
    """Build a Mesh from the spec over the given (default: all) devices."""
    spec = spec or MeshSpec.data_parallel()
    devs = list(devices) if devices is not None else jax.devices()
    resolved = spec.resolve(len(devs))
    shape = tuple(size for _, size in resolved)
    names = tuple(name for name, _ in resolved)
    arr = np.asarray(devs, dtype=object).reshape(shape)
    return Mesh(arr, axis_names=names)


def shard_map(f, *, mesh: Mesh, in_specs, out_specs, axis_names=None,
              check_vma=None):
    """The ONE entry point the framework (and its tests) use for
    per-shard SPMD bodies: `jax.shard_map`, with the optional arguments
    forwarded only when given so jax's own defaults apply otherwise.
    ``axis_names`` restricts which mesh axes the body is manual over;
    the rest stay GSPMD-auto around it."""
    kwargs = {}
    if axis_names is not None:
        kwargs["axis_names"] = axis_names
    if check_vma is not None:
        kwargs["check_vma"] = bool(check_vma)
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, **kwargs)


def virtual_cpu_devices(n: int) -> str:
    """The env-var incantation for an n-device virtual CPU platform.

    Must be set BEFORE jax initializes its backends (the test conftest does
    this).  Returned as a string for documentation/subprocess use.
    """
    return f"--xla_force_host_platform_device_count={n}"


def single_device_mesh(axis: str = DATA_AXIS) -> Mesh:
    """1-device mesh so sharded code paths run unchanged on one chip."""
    return Mesh(np.asarray(jax.devices()[:1], dtype=object).reshape((1,)), (axis,))


# -- active-mesh context ----------------------------------------------------
# Layer `apply()` functions are traced deep inside a model's jitted step and
# have a fixed signature; layers whose lowering depends on the mesh (e.g.
# SelfAttentionLayer with seq_parallel="ring" wrapping its core in shard_map)
# read the mesh from this trace-time context, which the models set around
# their compiled-step invocations (distribute() stores the mesh on the model).

_ACTIVE_MESH: Mesh | None = None


class active_mesh_scope:
    """Context manager installing `mesh` as the active mesh for layer
    tracing.  Reentrant; None is a valid (no-mesh) value."""

    def __init__(self, mesh: Mesh | None):
        self._mesh = mesh
        self._prev: Mesh | None = None

    def __enter__(self):
        global _ACTIVE_MESH
        self._prev = _ACTIVE_MESH
        _ACTIVE_MESH = self._mesh
        return self._mesh

    def __exit__(self, *exc):
        global _ACTIVE_MESH
        _ACTIVE_MESH = self._prev
        return False


def active_mesh() -> Mesh | None:
    return _ACTIVE_MESH
