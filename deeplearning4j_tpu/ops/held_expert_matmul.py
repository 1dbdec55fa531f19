"""A grouped product that fetches only the experts with rows — the kernel
``held_gmm``.

`held_gmm(lhs, rhs, sizes)` is what ``lax.ragged_dot(lhs, rhs, sizes)``
computes for rows sorted by group: rows ``[s_g, s_g + sizes[g])`` of
``lhs (m, k)`` times ``rhs[g] (k, n)``, with ``s_g`` the sum of the sizes
before g, accumulated in f32 -> (m, n) in ``preferred_element_type`` (or
lhs's type).  Rows past the last group come out zero.

Why a kernel: in the decode step of an expert layer a chip holds many
experts (48 in `motif3_mixed_sat`) and a step's few held assignments (≈ 32
rows) give rows to about half of them; ``lax.ragged_dot`` at those shapes
streams every held expert's matrix from HBM on every call (0.61 ms for
48 x 4,096 x 1,280 bf16, the weight read at the HBM rate), whatever the
sizes say.  Here the experts with rows are listed first, in the program,
from ``sizes``:

- ``ids`` the non-empty groups in order, ``E = min(G, m)`` long (a static
  bound: m rows fill at most m groups), padded by repeating the last one;
- ``n_active`` their count; ``starts`` and ``counts`` each one's first row
  and row count.

All four reach the kernel as scalar-prefetch operands.  Grid ``(n / tn,
E)``, output tiles outer and experts inner: the output block ``(m, tn)``
stays in VMEM over the experts and is zeroed at the first; the weight block
``(k, tn)`` of grid step ``(j, e)`` is expert ``ids[e]``'s.  A step past
``n_active`` names the block the step before it named (the padding repeats
the last id), so the pipeline issues no copy for it, and its body does
nothing.  The rows ``(m, k)`` are one block, fetched once.  An expert's
rows are taken `_row_block` rows at a time from its start rounded down to
that block, multiplied on the MXU against its weight block and written
into the output under a mask of the expert's own rows.

So a call reads ``n_active x k x n`` weights, the bytes the mathematics
needs, instead of ``G x k x n``.  ``tn`` comes from the shapes
(`_out_tile`): the widest lane-aligned divisor of n whose weight block
stays within `_WEIGHT_BLOCK_BYTES`.

Tests run it on the CPU with ``interpret=True`` (the default off a TPU).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: the most VMEM one weight block may take (the pipeline holds two)
_WEIGHT_BLOCK_BYTES = 4 << 20
_LANES = 128


def _out_tile(k: int, n: int, itemsize: int) -> int:
    """The widest multiple of 128 that divides n and keeps a (k, tn) weight
    block within `_WEIGHT_BLOCK_BYTES`; n itself when n is not a multiple
    of 128 (one block, as the layout requires)."""
    if n % _LANES:
        return n
    fit = max(1, _WEIGHT_BLOCK_BYTES // (k * itemsize * _LANES))
    return _LANES * max(d for d in range(1, n // _LANES + 1)
                        if (n // _LANES) % d == 0 and d <= fit)


def _row_block(dtype) -> int:
    """Rows a product takes at a time: the sublanes of one tile of lhs's
    type (8 for 32 bits, 16 for bf16)."""
    return 8 * max(1, 4 // jnp.dtype(dtype).itemsize)


def expert_metadata(sizes, e_max: int):
    """``sizes (G,)`` -> ``(ids (E,), n_active (1,), starts (E,), counts
    (E,))`` int32, ``E = e_max``: the non-empty groups in order, padded by
    repeating the last one (group 0 when every group is empty), their
    number, and each one's first row and row count (0 in the padding)."""
    sizes = sizes.astype(jnp.int32)
    nonempty = sizes > 0
    n_active = jnp.sum(nonempty, dtype=jnp.int32)
    ids = jnp.argsort(~nonempty, stable=True)[:e_max].astype(jnp.int32)
    e = jnp.arange(e_max)
    ids = jnp.where(e < n_active, ids, ids[jnp.maximum(n_active - 1, 0)])
    starts = (jnp.cumsum(sizes) - sizes)[ids]
    counts = jnp.where(e < n_active, sizes[ids], 0)
    return ids, n_active[None], starts.astype(jnp.int32), counts


def _kernel(ids_ref, n_ref, start_ref, count_ref, x_ref, w_ref, o_ref, *,
            rb: int):
    e = pl.program_id(1)

    @pl.when(e == 0)
    def _zero():
        o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)

    @pl.when(e < n_ref[0])
    def _expert():
        start, count = start_ref[e], count_ref[e]
        first = lax.div(start, rb) * rb

        def block(b, _):
            r0 = pl.multiple_of(first + b * rb, rb)
            y = jnp.dot(x_ref[pl.ds(r0, rb), :], w_ref[...],
                        preferred_element_type=jnp.float32)
            row = lax.broadcasted_iota(jnp.int32, (rb, 1), 0) + r0
            mine = (row >= start) & (row < start + count)
            o_ref[pl.ds(r0, rb), :] = jnp.where(
                mine, y.astype(o_ref.dtype), o_ref[pl.ds(r0, rb), :])
            return 0

        lax.fori_loop(0, lax.div(start + count - first + rb - 1, rb), block,
                      0)


@functools.partial(jax.jit, static_argnames=("out_dtype", "interpret"))
def _pallas(lhs, rhs, sizes, *, out_dtype, interpret: bool):
    m, k = lhs.shape
    g, _, n = rhs.shape
    rb = _row_block(lhs.dtype)
    m_pad = -(-m // rb) * rb
    x = jnp.pad(lhs, ((0, m_pad - m), (0, 0)))
    tn = _out_tile(k, n, rhs.dtype.itemsize)
    e_max = min(g, m)
    kwargs = {}
    if not interpret:
        x_bytes = m_pad * k * lhs.dtype.itemsize
        w_bytes = k * tn * rhs.dtype.itemsize
        o_bytes = m_pad * tn * jnp.dtype(out_dtype).itemsize
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=2 * (x_bytes + w_bytes + o_bytes) + (16 << 20))
    out = pl.pallas_call(
        functools.partial(_kernel, rb=rb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(n // tn, e_max),
            in_specs=[
                pl.BlockSpec((m_pad, k), lambda j, e, *meta: (0, 0)),
                pl.BlockSpec((None, k, tn),
                             lambda j, e, ids, *meta: (ids[e], 0, j)),
            ],
            out_specs=pl.BlockSpec((m_pad, tn), lambda j, e, *meta: (0, j))),
        out_shape=jax.ShapeDtypeStruct((m_pad, n), out_dtype),
        interpret=interpret,
        name="held_gmm",
        **kwargs,
    )(*expert_metadata(sizes, e_max), x, rhs)
    return out[:m]


def held_gmm(lhs, rhs, sizes, preferred_element_type=None, *,
             interpret: bool | None = None):
    """lhs (m, k) rows sorted by group; rhs (G, k, n) of lhs's type; sizes
    (G,) rows per group -> (m, n) in ``preferred_element_type`` or lhs's
    type, as ``lax.ragged_dot`` gives it for the rows inside the groups
    (zeros past them).  ``interpret`` None: compiled on a TPU, interpreted
    elsewhere."""
    if interpret is None:
        from deeplearning4j_tpu.runtime.backend import backend

        interpret = not backend().is_tpu
    out_dtype = jnp.dtype(preferred_element_type or lhs.dtype)
    return _pallas(lhs, rhs, sizes, out_dtype=out_dtype, interpret=interpret)
