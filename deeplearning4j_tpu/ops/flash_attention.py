"""Flash attention — Pallas TPU kernels for the dense attention core.

Role: the cuDNN-fused-attention tier the reference reaches through
`platform/cudnn` helpers (SURVEY.md §2.1 "Platform-accelerated impls"),
built TPU-native instead: a FlashAttention-2-style forward kernel
(`pl.pallas_call`) that streams KV blocks through VMEM with online-softmax
accumulation — O(block) memory instead of the O(T^2) logits tensor — and
a Pallas backward of two kernels (dQ; dK+dV) that recompute the
probabilities from the saved logsumexp, wired up with `jax.custom_vjp`.
The blockwise `lax.scan` backward further down is their REFERENCE.

The wrapper hands the kernels what the MXU multiplies: `q * 1/sqrt(D)`,
k, v and dO already cast to bf16 (folded into the (B,T,H,D) -> (BH,T,D)
copy every call makes anyway); accumulators, softmax statistics, `lse`
and `delta` stay f32 and the outputs keep the caller's dtype.  Blocks
follow the shape (`_default_blocks`), and under a causal mask a skipped
grid step names the block already resident, so nothing is fetched for it.

`mha()` in ops/attention.py dispatches here automatically on TPU for
unmasked shapes that tile cleanly (sequence divisible by 128);
everything else keeps the fused-XLA dense path.  Force the choice with
DL4JTPU_FLASH=1/0.  CPU tests run the same kernels with interpret=True.
"""

from __future__ import annotations

import functools
import logging
import os

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

log = logging.getLogger("deeplearning4j_tpu")

MIN_BLOCK = 128                     # the TPU lane width: the smallest tile
BLOCK_LADDER = (1024, 512, 256, MIN_BLOCK)
ENV_FLASH = "DL4JTPU_FLASH"

# VMEM: what Mosaic gives a kernel unasked, and what a tiling may ask
# for through `vmem_limit_bytes` (a v5e core has 128 MiB)
_VMEM_SCOPED_DEFAULT = 16 * 2**20
_VMEM_BUDGET = 32 * 2**20

_NEG_INF = -1e30        # large-negative instead of -inf: keeps exp() exact
                        # zero without generating nan via inf-inf

_NT = (((1,), (1,)), ((), ()))      # a @ b.T
_NN = (((1,), (0,)), ((), ()))      # a @ b


def _last_k_block(qi, block_q: int, block_k: int):
    """The last KV block that Q block `qi` attends to under the causal
    mask.  (`lax.div`, not `//`: the operands are never negative, and an
    index map is lowered once per operand per call — `//` on a tracer
    traces a dozen jitted helpers each time, seconds of a step's set-up.)"""
    return lax.div((qi + 1) * block_q - 1, jnp.int32(block_k))


def _first_q_block(kj, block_q: int, block_k: int):
    """The first Q block that attends to KV block `kj` under the causal
    mask."""
    return lax.div(kj * block_k, jnp.int32(block_q))


def _for_live_block(block, causal: bool, qi, kj, block_q: int, block_k: int):
    """Run `block(masked)` for the (qi, kj) tile of the score plane: not
    at all where the causal mask leaves nothing of it (the classic ~2x
    flash-causal win), with the mask only where the tile straddles the
    diagonal, plain everywhere else."""
    if not causal:
        block(False)
        return
    q_lo, k_lo = qi * block_q, kj * block_k
    live = k_lo <= q_lo + (block_q - 1)
    straddles = k_lo + (block_k - 1) > q_lo
    pl.when(jnp.logical_and(live, straddles))(lambda: block(True))
    pl.when(jnp.logical_and(live, jnp.logical_not(straddles)))(
        lambda: block(False))


def _causal_keep(shape, q_dim: int, q_lo, k_lo):
    """Keep-mask of a score tile whose `q_dim` axis runs over queries
    from `q_lo` and whose other axis over keys from `k_lo`."""
    qpos = lax.broadcasted_iota(jnp.int32, shape, q_dim)
    kpos = lax.broadcasted_iota(jnp.int32, shape, 1 - q_dim)
    return qpos - kpos >= k_lo - q_lo


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                acc_ref, m_ref, l_ref, *, n_k: int, block_k: int,
                causal: bool):
    """Grid (BH, n_q, n_k): one KV block per program; the online-softmax
    accumulators live in VMEM scratch, persisting across the (sequential)
    innermost KV dimension — VMEM stays O(block) at any sequence length.
    q arrives scaled; q, k, v arrive in the dtype the MXU multiplies."""
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    bq = q_ref.shape[1]

    @pl.when(kj == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    def block(masked):
        v = v_ref[0]
        s = lax.dot_general(q_ref[0], k_ref[0], _NT,
                            preferred_element_type=jnp.float32)
        if masked:
            keep = _causal_keep(s.shape, 0, qi * bq, kj * block_k)
            s = jnp.where(keep, s, _NEG_INF)
        m_prev, l_prev = m_ref[...], l_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        pv = lax.dot_general(p.astype(v.dtype), v, _NN,
                             preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * alpha + pv
        m_ref[...] = m_new

    _for_live_block(block, causal, qi, kj, bq, block_k)

    @pl.when(kj == n_k - 1)
    def _finish():
        o_ref[0] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)
        # logsumexp residual for the backward recompute, broadcast over 8
        # sublanes — Mosaic requires trailing block dims of (8k, 128k)
        lse = (m_ref[...] + jnp.log(l_ref[...]))[:, 0]
        lse_ref[0, 0] = jnp.broadcast_to(lse[None, :], (8, bq))


def _compiler_params(interpret: bool, vmem_bytes: int) -> dict:
    """`pallas_call` keywords of a compiled kernel: BH and the outer
    sequence axis are independent, the inner one is the reduction; more
    VMEM than Mosaic's scoped default is asked for only where the tiling
    needs it."""
    if interpret:
        return {}
    limit = {}
    if vmem_bytes > _VMEM_SCOPED_DEFAULT:
        limit["vmem_limit_bytes"] = max(vmem_bytes, _VMEM_BUDGET)
    return {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"), **limit)}


def _kv_index_map(causal: bool, block_q: int, block_k: int):
    """(b, i, j) -> the KV block of grid step (i, j), Q outer.  Under the
    causal mask a step above the diagonal names the last block row `i`
    needs — the one already resident — so Pallas issues no copy for it."""
    if not causal:
        return lambda b, i, j: (b, j, 0)
    return lambda b, i, j: (
        b, lax.min(j, _last_k_block(i, block_q, block_k)), 0)


# jitted, so that a model's layers — the same call at the same shapes —
# trace and lower each kernel once between them, not once per layer
@functools.partial(jax.jit, static_argnames=(
    "causal", "block_q", "block_k", "interpret", "out_dtype"))
def _flash_fwd_bhtd(q, k, v, *, causal: bool, block_q: int, block_k: int,
                    interpret: bool, out_dtype):
    """(BH, T, D) MXU operands (q scaled) -> (out in `out_dtype`, lse)."""
    bh, t_q, d = q.shape
    t_k = k.shape[1]
    n_q, n_k = t_q // block_q, t_k // block_k
    kv_map = _kv_index_map(causal, block_q, block_k)
    out, lse8 = pl.pallas_call(
        functools.partial(_fwd_kernel, n_k=n_k, block_k=block_k,
                          causal=causal),
        grid=(bh, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), kv_map),
            pl.BlockSpec((1, block_k, d), kv_map),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, 1, 8, block_q), lambda b, i, j: (b, i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, t_q, d), out_dtype),
            jax.ShapeDtypeStruct((bh, n_q, 8, block_q), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),      # acc
            pltpu.VMEM((block_q, 1), jnp.float32),      # running max
            pltpu.VMEM((block_q, 1), jnp.float32),      # running denom
        ],
        interpret=interpret,
        name="flash_fwd",
        **_compiler_params(interpret, _vmem_bytes(
            block_q, block_k, d, q.dtype.itemsize,
            jnp.dtype(out_dtype).itemsize)),
    )(q, k, v)
    return out, lse8[:, :, 0, :].reshape(bh, t_q)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref, dq_ref,
                   dq_acc, *, n_k: int, block_k: int, causal: bool,
                   sm_scale: float):
    """dQ pass: grid (BH, n_q, n_k), KV innermost; dq accumulates in VMEM.
        P = exp(QK^T*scale - lse);  dP = g V^T;  dS = P*(dP - delta)
        dQ = dS K * scale"""
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    bq = q_ref.shape[1]

    @pl.when(kj == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    def block(masked):
        k = k_ref[0]
        s = lax.dot_general(q_ref[0], k, _NT,
                            preferred_element_type=jnp.float32)
        if masked:
            keep = _causal_keep(s.shape, 0, qi * bq, kj * block_k)
            s = jnp.where(keep, s, _NEG_INF)
        p = jnp.exp(s - lse_ref[0, 0][:, None])
        dp = lax.dot_general(g_ref[0], v_ref[0], _NT,
                             preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0, 0][:, None])
        dq_acc[...] += lax.dot_general(ds.astype(k.dtype), k, _NN,
                                       preferred_element_type=jnp.float32)

    _for_live_block(block, causal, qi, kj, bq, block_k)

    @pl.when(kj == n_k - 1)
    def _finish():
        dq_ref[0] = (dq_acc[...] * sm_scale).astype(dq_ref.dtype)


def _bwd_dkdv_kernel(q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref,
                     dk_ref, dv_ref, dk_acc, dv_acc, *, n_q: int,
                     block_q: int, causal: bool):
    """dK/dV pass: grid (BH, n_k, n_q), Q innermost; dk/dv in VMEM scratch.
    The score tile is built transposed (keys down, queries across), so
    every matmul is in the MXU's own orientation and lse / delta broadcast
    along sublanes as the rows they are stored as:
        P^T = exp(K (Q*scale)^T - lse);  dV += P^T g
        dS^T = P^T * (V g^T - delta);    dK += dS^T (Q*scale)"""
    kj = pl.program_id(1)
    qi = pl.program_id(2)
    bk = k_ref.shape[1]

    @pl.when(qi == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def block(masked):
        q, g = q_ref[0], g_ref[0]
        st = lax.dot_general(k_ref[0], q, _NT,
                             preferred_element_type=jnp.float32)
        if masked:
            keep = _causal_keep(st.shape, 1, qi * block_q, kj * bk)
            st = jnp.where(keep, st, _NEG_INF)
        pt = jnp.exp(st - lse_ref[0, 0:1, :])
        dv_acc[...] += lax.dot_general(pt.astype(g.dtype), g, _NN,
                                       preferred_element_type=jnp.float32)
        dpt = lax.dot_general(v_ref[0], g, _NT,
                              preferred_element_type=jnp.float32)
        dst = (pt * (dpt - delta_ref[0, 0:1, :])).astype(q.dtype)
        dk_acc[...] += lax.dot_general(dst, q, _NN,
                                       preferred_element_type=jnp.float32)

    _for_live_block(block, causal, qi, kj, block_q, bk)

    @pl.when(qi == n_q - 1)
    def _finish():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "causal", "block_q", "block_k", "interpret"))
def _flash_bwd_pallas(q, k, v, o, lse, g, *, causal: bool, block_q: int,
                      block_k: int, interpret: bool):
    """Pallas flash backward: two kernels (dQ; dK+dV), each O(block)
    VMEM, every matmul on the MXU, nothing O(T^2) materialized.  q (scaled),
    k, v are the forward's MXU operands; `o` and `g` are in the caller's
    dtype, which the three gradients take."""
    bh, t_q, d = q.shape
    t_k = k.shape[1]
    n_q, n_k = t_q // block_q, t_k // block_k
    delta = jnp.sum(
        g.astype(jnp.float32) * o.astype(jnp.float32), axis=-1
    )                                                          # (BH, Tq)
    g = g.astype(q.dtype)
    # Mosaic requires trailing block dims of (8k, 128k): residual rows ride
    # broadcast over 8 sublanes, same trick as the forward's lse output
    lse8 = jnp.broadcast_to(lse[:, None, :], (bh, 8, t_q))
    delta8 = jnp.broadcast_to(delta[:, None, :], (bh, 8, t_q))
    params = _compiler_params(interpret, _vmem_bytes(
        block_q, block_k, d, q.dtype.itemsize, o.dtype.itemsize))
    kv_map = _kv_index_map(causal, block_q, block_k)
    dq = pl.pallas_call(
        functools.partial(
            _bwd_dq_kernel, n_k=n_k, block_k=block_k, causal=causal,
            sm_scale=1.0 / (d**0.5),
        ),
        grid=(bh, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),   # q
            pl.BlockSpec((1, block_k, d), kv_map),                      # k
            pl.BlockSpec((1, block_k, d), kv_map),                      # v
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),   # g
            pl.BlockSpec((1, 8, block_q), lambda b, i, j: (b, 0, i)),   # lse
            pl.BlockSpec((1, 8, block_q), lambda b, i, j: (b, 0, i)),   # delta
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, t_q, d), o.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
        name="flash_bwd_dq",
        **params,
    )(q, k, v, g, lse8, delta8)
    # Q inner: under the causal mask a step above the diagonal names the
    # first Q block that column j needs, the one the next live step reads
    # (the last block where keys outnumber queries and no row needs j)
    def q_of(j, i):
        if not causal:
            return i
        first = _first_q_block(j, block_q, block_k)
        return lax.max(i, lax.min(first, jnp.int32(n_q - 1)))

    dk, dv = pl.pallas_call(
        functools.partial(
            _bwd_dkdv_kernel, n_q=n_q, block_q=block_q, causal=causal,
        ),
        grid=(bh, n_k, n_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d),
                         lambda b, j, i: (b, q_of(j, i), 0)),           # q
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),   # k
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),   # v
            pl.BlockSpec((1, block_q, d),
                         lambda b, j, i: (b, q_of(j, i), 0)),           # g
            pl.BlockSpec((1, 8, block_q),
                         lambda b, j, i: (b, 0, q_of(j, i))),           # lse
            pl.BlockSpec((1, 8, block_q),
                         lambda b, j, i: (b, 0, q_of(j, i))),           # delta
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, t_k, d), o.dtype),
            jax.ShapeDtypeStruct((bh, t_k, d), o.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        interpret=interpret,
        name="flash_bwd_dkdv",
        **params,
    )(q, k, v, g, lse8, delta8)
    return dq, dk, dv


def _flash_bwd_bhtd(q, k, v, o, lse, g, *, causal: bool, block_k: int):
    """Blockwise flash backward (recompute from lse), O(block) memory.

    Standard FlashAttention backward math:
        P_ij = exp(q_i k_j^T * scale - lse_i)
        dV  += P^T g ;  dP = g V^T ;  dS = P * (dP - rowsum(g*o))
        dQ  += dS K * scale ;  dK += dS^T Q * scale
    Implemented as a lax.scan over KV blocks in plain jnp — kept as the
    REFERENCE backward for the Pallas kernels' parity tests (and the
    DL4JTPU_FLASH_BWD=xla escape hatch).  Takes what the kernels take:
    q already scaled, gradients in `o`'s dtype."""
    d = q.shape[-1]
    sm_scale = 1.0 / (d**0.5)
    qf = q.astype(jnp.float32)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    gf = g.astype(jnp.float32)
    delta = jnp.sum(gf * o.astype(jnp.float32), axis=-1)       # (BH, Tq)
    t_k = k.shape[1]
    n_k = t_k // block_k
    t_q = q.shape[1]

    def body(carry, j):
        dq = carry
        ks = lax.dynamic_slice_in_dim(kf, j * block_k, block_k, axis=1)
        vs = lax.dynamic_slice_in_dim(vf, j * block_k, block_k, axis=1)
        s = jnp.einsum("bqd,bkd->bqk", qf, ks)
        if causal:
            qpos = jnp.arange(t_q)[:, None]
            kpos = j * block_k + jnp.arange(block_k)[None, :]
            s = jnp.where(qpos >= kpos, s, _NEG_INF)
        p = jnp.exp(s - lse[:, :, None])                       # (BH, Tq, bk)
        dv_j = jnp.einsum("bqk,bqd->bkd", p, gf)
        dp = jnp.einsum("bqd,bkd->bqk", gf, vs)
        ds = p * (dp - delta[:, :, None])
        dq = dq + jnp.einsum("bqk,bkd->bqd", ds, ks) * sm_scale
        dk_j = jnp.einsum("bqk,bqd->bkd", ds, qf)   # qf already carries scale
        return dq, (dk_j, dv_j)

    dq0 = jnp.zeros_like(qf)
    dq, (dk_blocks, dv_blocks) = lax.scan(body, dq0, jnp.arange(n_k))
    dk = jnp.moveaxis(dk_blocks, 0, 1).reshape(k.shape)
    dv = jnp.moveaxis(dv_blocks, 0, 1).reshape(v.shape)
    return dq.astype(o.dtype), dk.astype(o.dtype), dv.astype(o.dtype)


def _mxu_operands(q, k, v, mxu_f32: bool):
    """What the kernels multiply: q scaled in f32 and then, like k and v,
    rounded to bf16 once (f32 kept under `mxu_f32`).  XLA folds this into
    the copy that makes the (BH, T, D) layout."""
    dtype = jnp.float32 if mxu_f32 else jnp.bfloat16
    sm_scale = 1.0 / (q.shape[-1]**0.5)
    return ((q.astype(jnp.float32) * sm_scale).astype(dtype),
            k.astype(dtype), v.astype(dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_core(q, k, v, causal, block_q, block_k, interpret, mxu_f32):
    """(BH, T, D) q, k, v of ONE dtype -> attention output in that dtype."""
    return _flash_core_fwd(q, k, v, causal, block_q, block_k, interpret,
                           mxu_f32)[0]


def _flash_core_fwd(q, k, v, causal, block_q, block_k, interpret, mxu_f32):
    qs, km, vm = _mxu_operands(q, k, v, mxu_f32)
    out, lse = _flash_fwd_bhtd(qs, km, vm, causal=causal, block_q=block_q,
                               block_k=block_k, interpret=interpret,
                               out_dtype=q.dtype)
    return out, (qs, km, vm, out, lse)


def _flash_core_bwd(causal, block_q, block_k, interpret, mxu_f32, res, g):
    q, k, v, out, lse = res
    if os.environ.get("DL4JTPU_FLASH_BWD", "").strip() == "xla":
        return _flash_bwd_bhtd(q, k, v, out, lse, g, causal=causal,
                               block_k=block_k)
    return _flash_bwd_pallas(q, k, v, out, lse, g, causal=causal,
                             block_q=block_q, block_k=block_k,
                             interpret=interpret)


_flash_core.defvjp(_flash_core_fwd, _flash_core_bwd)


# (T_q, T_k, D, causal) -> (block_q, block_k), filled by flash_autotune()
# or the DL4JTPU_FLASH_BLOCK="bq,bk" env override; consulted statically at
# trace time.
_BLOCK_CACHE: dict = {}


def _vmem_bytes(block_q: int, block_k: int, d: int, operand_bytes: int,
                out_bytes: int) -> int:
    """VMEM working set of the hungriest kernel (dK+dV) at a tiling: the
    four f32 score-plane tiles (s, p, dp, ds) with the MXU copies of p and
    ds, the double-buffered operand and output blocks, the accumulators."""
    tiles = block_q * block_k * (4 * 4 + 2 * operand_bytes)
    operands = 2 * 2 * (block_q + block_k) * d * operand_bytes
    rows = 2 * 2 * 8 * block_q * 4                      # lse, delta
    outputs = 2 * 2 * block_k * d * out_bytes
    accumulators = 2 * block_k * d * 4
    return tiles + operands + rows + outputs + accumulators


def _default_blocks(t_q: int, t_k: int, d: int, operand_bytes: int,
                    out_bytes: int) -> tuple:
    """Blocks from the shape: per axis the largest rung of the ladder that
    divides the sequence (a sequence shorter than 128 is one block), then
    the larger block halved while the working set is over the VMEM budget
    and a smaller rung still divides."""
    def rungs(t):
        return [b for b in BLOCK_LADDER if t % b == 0] or [min(MIN_BLOCK, t)]

    q_rungs, k_rungs = rungs(t_q), rungs(t_k)
    iq = ik = 0
    while _vmem_bytes(q_rungs[iq], k_rungs[ik], d, operand_bytes,
                      out_bytes) > _VMEM_BUDGET:
        shrink_q = iq + 1 < len(q_rungs) and (
            q_rungs[iq] >= k_rungs[ik] or ik + 1 == len(k_rungs))
        if shrink_q:
            iq += 1
        elif ik + 1 < len(k_rungs):
            ik += 1
        else:
            break
    return q_rungs[iq], k_rungs[ik]


def _block_choice(t_q, t_k, d, causal, block_q, block_k, *,
                  operand_bytes: int = 2, out_bytes: int = 4):
    """Resolve block sizes: explicit caller choice > env override >
    autotune cache > the shape's default.  Invalid (non-tiling /
    malformed) env values fall through with a warning instead of crashing
    mid-trace."""
    if block_q is not None or block_k is not None:
        bq = block_q if block_q is not None else MIN_BLOCK
        bk = block_k if block_k is not None else MIN_BLOCK
        return min(bq, t_q), min(bk, t_k)
    env = os.environ.get("DL4JTPU_FLASH_BLOCK", "").strip()
    if env:
        try:
            bq, bk = (int(x) for x in env.split(","))
            bq, bk = min(bq, t_q), min(bk, t_k)
            if t_q % bq == 0 and t_k % bk == 0:
                return bq, bk
            log.warning(
                "DL4JTPU_FLASH_BLOCK=%s does not tile (Tq=%d, Tk=%d); "
                "ignoring", env, t_q, t_k)
        except ValueError:
            log.warning(
                "DL4JTPU_FLASH_BLOCK=%s is not 'bq,bk'; ignoring", env)
    cached = _BLOCK_CACHE.get((t_q, t_k, d, causal))
    if cached:
        return cached
    return _default_blocks(t_q, t_k, d, operand_bytes, out_bytes)


def flash_autotune(*, seq_len: int, n_heads: int, head_dim: int,
                   batch: int = 1, causal: bool = True,
                   candidates=((128, 128), (256, 128), (128, 256),
                               (256, 256), (256, 512), (512, 256),
                               (512, 512)),
                   reps: int = 3) -> tuple:
    """Measure fwd+bwd wall time for candidate block sizes EAGERLY (outside
    jit) on the current default device and cache the winner; later
    flash_attention() calls with the same (Tq, Tk, D, causal) pick it up
    statically at trace time.  Call once before building a model (bench.py
    does for the long-context config).  Returns the winning (bq, bk).  A
    candidate the compiler refuses is logged and skipped; when NONE
    compiles this raises — there is no silent default."""
    import logging
    import time as _time

    log = logging.getLogger("deeplearning4j_tpu")
    t = seq_len
    bh = batch * n_heads
    d = head_dim
    key = jax.random.key(0)
    q = jax.random.normal(key, (bh, t, d), jnp.float32)
    best = None
    refused = []
    for bq, bk in candidates:
        if t % min(bq, t) or t % min(bk, t):
            continue

        def loss(qq, kk, vv, _bq=min(bq, t), _bk=min(bk, t)):
            out = _flash_core(qq, kk, vv, causal, _bq, _bk, False, False)
            return jnp.sum(out.astype(jnp.float32))

        try:
            f = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
            g = f(q, q, q)
            float(jnp.sum(g[0]))            # compile + sync
        except Exception as exc:    # the compiler refusing a block shape
            # (VMEM, layout) disqualifies that candidate, not the search
            log.warning("flash_autotune: blocks (%d, %d) refused: %s: %s",
                        bq, bk, type(exc).__name__, exc)
            refused.append((bq, bk))
            continue
        t0 = _time.perf_counter()
        for _ in range(reps):
            g = f(q, q, q)
        float(jnp.sum(g[0]))
        dt = _time.perf_counter() - t0
        if best is None or dt < best[0]:
            best = (dt, (min(bq, t), min(bk, t)))
    if best is None:
        raise RuntimeError(
            f"flash_autotune: no candidate block shape compiled and ran for "
            f"T={t}, D={d} (refused: {refused}) — the kernel cannot serve "
            "this shape on this device"
        )
    _BLOCK_CACHE[(t, t, d, causal)] = best[1]
    return best[1]


def _count_tiling(block_q: int, block_k: int, causal: bool) -> None:
    """Trace-time telemetry: which tiling a flash-attention site lowered
    with.  Never raises into a trace."""
    try:
        from deeplearning4j_tpu.observe.metrics import registry

        registry().counter("dl4jtpu_flash_attention_total").inc(
            block_q=str(block_q), block_k=str(block_k),
            causal=str(bool(causal)).lower())
    except Exception as e:
        log.debug("flash-attention tiling metric failed: %s", e)


def flash_attention(q, k, v, *, causal: bool = False,
                    block_q: int | None = None,
                    block_k: int | None = None,
                    interpret: bool = False,
                    mxu_f32: bool = False) -> jax.Array:
    """FlashAttention over (B, T, H, D) tensors (same contract as mha()
    minus masks).  Sequence lengths must divide the block sizes.
    block_q/block_k=None (default) resolves via DL4JTPU_FLASH_BLOCK, then
    the flash_autotune cache, then the shape (`_default_blocks`); explicit
    values always win.  mxu_f32=True runs the in-kernel matmuls in full
    f32 (exactness tests); the default bf16-input/f32-accumulate matches
    the dense TPU path.  Output and gradients come in q's dtype."""
    b, t_q, h, d = q.shape
    t_k = k.shape[1]
    bq, bk = _block_choice(
        t_q, t_k, d, causal, block_q, block_k,
        operand_bytes=4 if mxu_f32 else 2, out_bytes=q.dtype.itemsize)
    _count_tiling(bq, bk, causal)
    qr = q.transpose(0, 2, 1, 3).reshape(b * h, t_q, d)
    kr = k.astype(q.dtype).transpose(0, 2, 1, 3).reshape(b * h, t_k, d)
    vr = v.astype(q.dtype).transpose(0, 2, 1, 3).reshape(b * h, t_k, d)
    out = _flash_core(qr, kr, vr, causal, bq, bk, interpret, mxu_f32)
    return out.reshape(b, h, t_q, d).transpose(0, 2, 1, 3)


def flash_eligible(q, k, mask) -> bool:
    """Can the flash kernel serve this mha() call?

    DL4JTPU_FLASH=1 forces it (CPU runs interpret mode — tests), =0
    disables; default: TPU only, no key mask, sequence lengths the
    smallest block tiles (a multiple of 128, or shorter than 128), and
    sequences long enough that the O(T^2) materialization actually hurts.
    """
    env = os.environ.get(ENV_FLASH, "").strip()
    if env == "0":
        return False
    if mask is not None:
        return False
    t_q, t_k = q.shape[1], k.shape[1]
    tileable = (t_q % min(MIN_BLOCK, t_q) == 0
                and t_k % min(MIN_BLOCK, t_k) == 0)
    if env == "1":
        return tileable
    from deeplearning4j_tpu.runtime.backend import backend

    # default threshold: flash wins the MEMORY ceiling (no O(Tq*Tk)
    # logits tensor) from the length at which that tensor starts to hurt
    return tileable and backend().is_tpu and t_q >= 2048 and t_k >= 2048
