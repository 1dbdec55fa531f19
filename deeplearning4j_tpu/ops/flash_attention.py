"""Flash attention — a Pallas TPU kernel for the dense attention core.

Role: the cuDNN-fused-attention tier the reference reaches through
`platform/cudnn` helpers (SURVEY.md §2.1 "Platform-accelerated impls"),
built TPU-native instead: a FlashAttention-2-style forward kernel
(`pl.pallas_call`) that streams KV blocks through VMEM with online-softmax
accumulation — O(block) memory instead of the O(T^2) logits tensor — plus
a blockwise `lax.scan` backward (recompute-from-logsumexp, the standard
flash backward math) wired up with `jax.custom_vjp`.

`mha()` in ops/attention.py dispatches here automatically on TPU for
unmasked shapes that tile cleanly (sequence divisible by the block size);
everything else keeps the fused-XLA dense path.  Force the choice with
DL4JTPU_FLASH=1/0.  CPU tests run the same kernel with interpret=True.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
ENV_FLASH = "DL4JTPU_FLASH"

_NEG_INF = -1e30        # large-negative instead of -inf: keeps exp() exact
                        # zero without generating nan via inf-inf


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                acc_ref, m_ref, l_ref, *, n_k: int, block_k: int,
                causal: bool, sm_scale: float, mxu_dtype):
    """Grid (BH, n_q, n_k): one KV block per program; the online-softmax
    accumulators live in VMEM scratch, persisting across the (sequential)
    innermost KV dimension — VMEM stays O(block) at any sequence length."""
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    bq = q_ref.shape[1]

    @pl.when(kj == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # causal: a KV block strictly above the diagonal contributes nothing —
    # skip its compute entirely (the classic ~2x flash-causal win)
    needed = (
        kj * block_k <= qi * bq + (bq - 1) if causal else kj >= 0
    )

    @pl.when(needed)
    def _block():
        # mxu_dtype=bf16 (TPU default): the same matmul precision the
        # dense XLA path uses, ~4x the f32 MXU throughput; softmax
        # statistics and accumulation stay f32.  f32 for exact tests.
        q = (q_ref[0].astype(jnp.float32) * sm_scale).astype(mxu_dtype)
        k = k_ref[0].astype(mxu_dtype)
        v = v_ref[0].astype(mxu_dtype)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        if causal:
            qpos = qi * bq + lax.broadcasted_iota(jnp.int32, s.shape, 0)
            kpos = kj * block_k + lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(qpos >= kpos, s, _NEG_INF)
        m_prev, l_prev = m_ref[...], l_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(mxu_dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        acc_ref[...] = acc_ref[...] * alpha + pv
        m_ref[...] = m_new
        l_ref[...] = l_new

    @pl.when(kj == n_k - 1)
    def _finish():
        o_ref[0] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)
        # logsumexp residual for the backward recompute, broadcast over 8
        # sublanes — Mosaic requires trailing block dims of (8k, 128k)
        lse = (m_ref[...] + jnp.log(l_ref[...]))[:, 0]
        lse_ref[0, 0] = jnp.broadcast_to(lse[None, :], (8, bq))


def _flash_fwd_bhtd(q, k, v, *, causal: bool, block_q: int, block_k: int,
                    interpret: bool, mxu_f32: bool):
    """(BH, T, D) inputs -> (out, lse)."""
    from jax.experimental.pallas import tpu as pltpu

    bh, t_q, d = q.shape
    t_k = k.shape[1]
    sm_scale = 1.0 / (d**0.5)
    n_q, n_k = t_q // block_q, t_k // block_k
    kernel = functools.partial(
        _fwd_kernel, n_k=n_k, block_k=block_k, causal=causal,
        sm_scale=sm_scale,
        mxu_dtype=jnp.float32 if mxu_f32 else jnp.bfloat16,
    )
    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        )
    out, lse8 = pl.pallas_call(
        kernel,
        grid=(bh, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, 1, 8, block_q), lambda b, i, j: (b, i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, t_q, d), q.dtype),
            jax.ShapeDtypeStruct((bh, n_q, 8, block_q), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),      # acc
            pltpu.VMEM((block_q, 1), jnp.float32),      # running max
            pltpu.VMEM((block_q, 1), jnp.float32),      # running denom
        ],
        interpret=interpret,
        **kwargs,
    )(q, k, v)
    return out, lse8[:, :, 0, :].reshape(bh, t_q)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref, dq_ref,
                   dq_acc, *, n_k: int, block_k: int, causal: bool,
                   sm_scale: float, mxu_dtype):
    """dQ pass: grid (BH, n_q, n_k), KV innermost; dq accumulates in VMEM.
        P = exp(QK^T*scale - lse);  dP = g V^T;  dS = P*(dP - delta)
        dQ = dS K * scale"""
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    bq = q_ref.shape[1]

    @pl.when(kj == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    needed = (
        kj * block_k <= qi * bq + (bq - 1) if causal else kj >= 0
    )

    @pl.when(needed)
    def _block():
        q = (q_ref[0].astype(jnp.float32) * sm_scale).astype(mxu_dtype)
        k = k_ref[0].astype(mxu_dtype)
        v = v_ref[0].astype(mxu_dtype)
        g = g_ref[0].astype(mxu_dtype)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        if causal:
            qpos = qi * bq + lax.broadcasted_iota(jnp.int32, s.shape, 0)
            kpos = kj * block_k + lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(qpos >= kpos, s, _NEG_INF)
        p = jnp.exp(s - lse_ref[0, 0][:, None])
        dp = jax.lax.dot_general(
            g, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta_ref[0, 0][:, None])
        dq_acc[...] += jax.lax.dot_general(
            ds.astype(mxu_dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * sm_scale

    @pl.when(kj == n_k - 1)
    def _finish():
        dq_ref[0] = dq_acc[...].astype(dq_ref.dtype)


def _bwd_dkdv_kernel(q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref,
                     dk_ref, dv_ref, dk_acc, dv_acc, *, n_q: int,
                     block_q: int, causal: bool, sm_scale: float, mxu_dtype):
    """dK/dV pass: grid (BH, n_k, n_q), Q innermost; dk/dv in VMEM scratch.
        dV += P^T g ;  dK += dS^T (Q*scale)"""
    kj = pl.program_id(1)
    qi = pl.program_id(2)
    bk = k_ref.shape[1]

    @pl.when(qi == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    needed = (
        qi * block_q + (block_q - 1) >= kj * bk if causal else qi >= 0
    )

    @pl.when(needed)
    def _block():
        q = (q_ref[0].astype(jnp.float32) * sm_scale).astype(mxu_dtype)
        k = k_ref[0].astype(mxu_dtype)
        v = v_ref[0].astype(mxu_dtype)
        g = g_ref[0].astype(mxu_dtype)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        if causal:
            qpos = qi * block_q + lax.broadcasted_iota(jnp.int32, s.shape, 0)
            kpos = kj * bk + lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(qpos >= kpos, s, _NEG_INF)
        p = jnp.exp(s - lse_ref[0, 0][:, None])
        dv_acc[...] += jax.lax.dot_general(
            p.astype(mxu_dtype), g, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dp = jax.lax.dot_general(
            g, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = (p * (dp - delta_ref[0, 0][:, None])).astype(mxu_dtype)
        dk_acc[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(qi == n_q - 1)
    def _finish():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _flash_bwd_pallas(q, k, v, o, lse, g, *, causal: bool, block_q: int,
                      block_k: int, interpret: bool, mxu_f32: bool):
    """Pallas flash backward: two kernels (dQ; dK+dV), each O(block)
    VMEM, every matmul on the MXU, nothing O(T^2) materialized."""
    from jax.experimental.pallas import tpu as pltpu

    bh, t_q, d = q.shape
    t_k = k.shape[1]
    sm_scale = 1.0 / (d**0.5)
    n_q, n_k = t_q // block_q, t_k // block_k
    mxu_dtype = jnp.float32 if mxu_f32 else jnp.bfloat16
    delta = jnp.sum(
        g.astype(jnp.float32) * o.astype(jnp.float32), axis=-1
    )                                                          # (BH, Tq)
    # Mosaic requires trailing block dims of (8k, 128k): residual rows ride
    # broadcast over 8 sublanes, same trick as the forward's lse output
    lse8 = jnp.broadcast_to(lse[:, None, :], (bh, 8, t_q))
    delta8 = jnp.broadcast_to(delta[:, None, :], (bh, 8, t_q))
    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        )
    dq = pl.pallas_call(
        functools.partial(
            _bwd_dq_kernel, n_k=n_k, block_k=block_k, causal=causal,
            sm_scale=sm_scale, mxu_dtype=mxu_dtype,
        ),
        grid=(bh, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),   # q
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),   # k
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),   # v
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),   # g
            pl.BlockSpec((1, 8, block_q), lambda b, i, j: (b, 0, i)),   # lse
            pl.BlockSpec((1, 8, block_q), lambda b, i, j: (b, 0, i)),   # delta
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, t_q, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
        **kwargs,
    )(q, k, v, g, lse8, delta8)
    dk, dv = pl.pallas_call(
        functools.partial(
            _bwd_dkdv_kernel, n_q=n_q, block_q=block_q, causal=causal,
            sm_scale=sm_scale, mxu_dtype=mxu_dtype,
        ),
        grid=(bh, n_k, n_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, j, i: (b, i, 0)),   # q
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),   # k
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),   # v
            pl.BlockSpec((1, block_q, d), lambda b, j, i: (b, i, 0)),   # g
            pl.BlockSpec((1, 8, block_q), lambda b, j, i: (b, 0, i)),   # lse
            pl.BlockSpec((1, 8, block_q), lambda b, j, i: (b, 0, i)),   # delta
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, t_k, d), k.dtype),
            jax.ShapeDtypeStruct((bh, t_k, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        interpret=interpret,
        **kwargs,
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
    DL4JTPU_FLASH_BWD=xla escape hatch)."""
    d = q.shape[-1]
    sm_scale = 1.0 / (d**0.5)
    qf = q.astype(jnp.float32) * sm_scale
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
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_core(q, k, v, causal, block_q, block_k, interpret, mxu_f32):
    out, _ = _flash_fwd_bhtd(q, k, v, causal=causal, block_q=block_q,
                             block_k=block_k, interpret=interpret,
                             mxu_f32=mxu_f32)
    return out


def _flash_core_fwd(q, k, v, causal, block_q, block_k, interpret, mxu_f32):
    out, lse = _flash_fwd_bhtd(q, k, v, causal=causal, block_q=block_q,
                               block_k=block_k, interpret=interpret,
                               mxu_f32=mxu_f32)
    return out, (q, k, v, out, lse)


def _flash_core_bwd(causal, block_q, block_k, interpret, mxu_f32, res, g):
    q, k, v, out, lse = res
    if os.environ.get("DL4JTPU_FLASH_BWD", "").strip() == "xla":
        return _flash_bwd_bhtd(q, k, v, out, lse, g, causal=causal,
                               block_k=block_k)
    return _flash_bwd_pallas(q, k, v, out, lse, g, causal=causal,
                             block_q=block_q, block_k=block_k,
                             interpret=interpret, mxu_f32=mxu_f32)


_flash_core.defvjp(_flash_core_fwd, _flash_core_bwd)


# (T_q, T_k, D, causal) -> (block_q, block_k), filled by flash_autotune()
# or the DL4JTPU_FLASH_BLOCK="bq,bk" env override; consulted statically at
# trace time.
_BLOCK_CACHE: dict = {}


def _block_choice(t_q, t_k, d, causal, block_q, block_k):
    """Resolve block sizes: explicit caller choice > env override >
    autotune cache > defaults.  Invalid (non-tiling / malformed) env
    values fall through with a warning instead of crashing mid-trace."""
    if block_q is not None or block_k is not None:
        bq = block_q if block_q is not None else DEFAULT_BLOCK_Q
        bk = block_k if block_k is not None else DEFAULT_BLOCK_K
        return min(bq, t_q), min(bk, t_k)
    env = os.environ.get("DL4JTPU_FLASH_BLOCK", "").strip()
    if env:
        import logging

        try:
            bq, bk = (int(x) for x in env.split(","))
            bq, bk = min(bq, t_q), min(bk, t_k)
            if t_q % bq == 0 and t_k % bk == 0:
                return bq, bk
            logging.getLogger(__name__).warning(
                "DL4JTPU_FLASH_BLOCK=%s does not tile (Tq=%d, Tk=%d); "
                "ignoring", env, t_q, t_k)
        except ValueError:
            logging.getLogger(__name__).warning(
                "DL4JTPU_FLASH_BLOCK=%s is not 'bq,bk'; ignoring", env)
    cached = _BLOCK_CACHE.get((t_q, t_k, d, causal))
    if cached:
        return cached
    return min(DEFAULT_BLOCK_Q, t_q), min(DEFAULT_BLOCK_K, t_k)


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


def flash_attention(q, k, v, *, causal: bool = False,
                    block_q: int | None = None,
                    block_k: int | None = None,
                    interpret: bool = False,
                    mxu_f32: bool = False) -> jax.Array:
    """FlashAttention over (B, T, H, D) tensors (same contract as mha()
    minus masks).  Sequence lengths must divide the block sizes.
    block_q/block_k=None (default) resolves via DL4JTPU_FLASH_BLOCK, then
    the flash_autotune cache, then 128/128; explicit values always win.
    mxu_f32=True runs the in-kernel matmuls in full f32 (exactness tests);
    the default bf16-input/f32-accumulate matches the dense TPU path."""
    b, t_q, h, d = q.shape
    t_k = k.shape[1]
    bq, bk = _block_choice(t_q, t_k, d, causal, block_q, block_k)
    qr = q.transpose(0, 2, 1, 3).reshape(b * h, t_q, d)
    kr = k.transpose(0, 2, 1, 3).reshape(b * h, t_k, d)
    vr = v.transpose(0, 2, 1, 3).reshape(b * h, t_k, d)
    out = _flash_core(qr, kr, vr, causal, bq, bk, interpret, mxu_f32)
    return out.reshape(b, h, t_q, d).transpose(0, 2, 1, 3)


def flash_eligible(q, k, mask, *, block_q: int = DEFAULT_BLOCK_Q,
                   block_k: int = DEFAULT_BLOCK_K) -> bool:
    """Can the flash kernel serve this mha() call?

    DL4JTPU_FLASH=1 forces it (CPU runs interpret mode — tests), =0
    disables; default: TPU only, no key mask, block-tileable sequence
    lengths, and sequences long enough that the O(T^2) materialization
    actually hurts.
    """
    env = os.environ.get(ENV_FLASH, "").strip()
    if env == "0":
        return False
    if mask is not None:
        return False
    t_q, t_k = q.shape[1], k.shape[1]
    bq, bk = min(block_q, t_q), min(block_k, t_k)
    tileable = t_q % bq == 0 and t_k % bk == 0
    if env == "1":
        return tileable
    from deeplearning4j_tpu.runtime.backend import backend

    # default threshold: flash wins the MEMORY ceiling (no O(Tq*Tk)
    # logits tensor) and, measured on v5e in round 4, beats the fused
    # dense path on wall clock from T=2048 up (12.2 vs 20.6 ms/iter
    # fwd+bwd at B=4 H=8 dh=64 with autotuned blocks)
    return tileable and backend().is_tpu and t_q >= 2048 and t_k >= 2048
