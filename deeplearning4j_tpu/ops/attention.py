"""Attention ops: dense MHA + sequence-parallel ring / Ulysses variants.

The reference exposes attention only as the `multi_head_dot_product_attention`
custom op + SelfAttentionLayer, single-device O(T^2) (SURVEY.md §5.7).  The
TPU build makes long-context first-class:

- `mha`: standard fused attention for one device (XLA fuses the softmax
  chain; the two matmuls ride the MXU).
- `ring_attention`: Q stays put, KV blocks rotate around the `seq` mesh
  axis via ppermute with flash-style ONLINE SOFTMAX accumulation (running
  rowmax m, normalizer l, weighted values o) — exact attention over the
  full sequence with per-device memory O(T_local^2-ish), communication
  overlapped with compute by XLA.
- `ulysses_attention`: all_to_all scatters heads / gathers sequence, runs
  dense local attention on H/P heads of the FULL sequence, then the
  inverse all_to_all — cheaper collectives when H >= P.

Shapes: (B, T, H, D) batch, time, heads, head_dim.  All functions are pure
and differentiable; the ring/ulysses versions must run inside
shard_map/pjit with the named `axis` present in the mesh.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax



def _scale(d: int) -> float:
    return 1.0 / (d**0.5)


def _flash_per_shard(flash, q, k, v):
    """Run the flash kernel on each device's own shard when the step is
    traced under a >1-device mesh.

    GSPMD cannot partition a Mosaic kernel ("Mosaic kernels cannot be
    automatically partitioned" — found on four real chips; interpret-mode
    CPU runs lower to plain XLA ops and never hit it), so every mesh axis
    that is still GSPMD-auto here goes manual around the call: batch
    split over "data" and heads over "model" where they divide (attention
    is independent per example and per head), replicated over any other
    axis.  Axes an enclosing shard_map already made manual (a pipeline
    stage, ulysses' "seq") are left alone."""
    from jax.sharding import PartitionSpec as P

    from deeplearning4j_tpu.runtime.mesh import (
        DATA_AXIS, MODEL_AXIS, active_mesh, shard_map,
    )

    mesh = active_mesh()
    if mesh is None:
        return flash(q, k, v)
    manual = set(jax.sharding.get_abstract_mesh().manual_axes)
    auto = {a for a in mesh.axis_names
            if mesh.shape[a] > 1 and a not in manual}
    if not auto:
        return flash(q, k, v)

    def over(axis, dim):
        return axis if axis in auto and dim % mesh.shape[axis] == 0 else None

    spec = P(over(DATA_AXIS, q.shape[0]), None, over(MODEL_AXIS, q.shape[2]),
             None)
    return shard_map(flash, mesh=mesh, in_specs=(spec, spec, spec),
                     out_specs=spec, axis_names=auto, check_vma=False)(q, k, v)


def mha(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    mask: jax.Array | None = None,
    q_offset=0,
    kv_offset=0,
) -> jax.Array:
    """Dense attention. q,k,v: (B, Tq|Tk, H, D) -> (B, Tq, H, D).

    q_offset/kv_offset: global position offsets (used by ring attention for
    cross-shard causal masking); scalars or traced ints.

    On TPU, unmasked offset-free calls with tileable sequence lengths
    dispatch to the Pallas flash kernel (ops/flash_attention.py) — O(block)
    memory instead of the O(Tq*Tk) logits tensor.
    """
    if (
        isinstance(q_offset, int)
        and q_offset == 0
        and isinstance(kv_offset, int)
        and kv_offset == 0
    ):
        from deeplearning4j_tpu.ops.flash_attention import (
            flash_attention,
            flash_eligible,
        )

        if flash_eligible(q, k, mask):
            from deeplearning4j_tpu.runtime.backend import backend

            return _flash_per_shard(
                functools.partial(flash_attention, causal=causal,
                                  interpret=not backend().is_tpu),
                q, k, v,
            )
    d = q.shape[-1]
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * _scale(d)
    logits = logits.astype(jnp.float32)
    if causal:
        qi = jnp.arange(q.shape[1]) + q_offset
        ki = jnp.arange(k.shape[1]) + kv_offset
        cmask = qi[:, None] >= ki[None, :]
        logits = jnp.where(cmask[None, None], logits, -jnp.inf)
    if mask is not None:
        # mask: (B, Tk) keep-mask over keys
        logits = jnp.where(mask[:, None, None, :] > 0, logits, -jnp.inf)
    # guard fully-masked rows (softmax of all -inf)
    w = jax.nn.softmax(logits, axis=-1)
    w = jnp.where(jnp.isnan(w), 0.0, w)
    return jnp.einsum("bhqk,bkhd->bqhd", w.astype(q.dtype), v)


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    axis: str,
    causal: bool = False,
    mask: jax.Array | None = None,
    block_size: int | None = 512,
) -> jax.Array:
    """Exact attention with KV rotating around the `axis` ring.

    Called under shard_map with the sequence dim sharded over `axis`:
    q,k,v are the LOCAL (B, T_local, H, D) shards.  Returns the local
    output shard.  mask: local (B, T_local) keep-mask over this shard's
    keys (rotates with KV).

    Blockwise + scan-based: the ring walk is a `lax.scan` over the mesh
    axis (program size independent of mesh size), and within each held KV
    shard the logits are materialized one `block_size` chunk at a time via
    an inner scan — peak logits memory is O(B*H*T_local*block) instead of
    O(B*H*T_local*T_local).  block_size=None disables inner chunking.
    """
    n = lax.axis_size(axis)
    idx = lax.axis_index(axis)
    b, t_local, h, d = q.shape
    scale = _scale(d)

    q32 = q.astype(jnp.float32)
    qi = jnp.arange(t_local) + idx * t_local  # global query positions

    # inner KV chunk: largest divisor of t_local <= block_size
    if block_size is None or block_size >= t_local:
        bs = t_local
    else:
        bs = max(s for s in range(1, block_size + 1) if t_local % s == 0)
    n_blocks = t_local // bs

    has_mask = mask is not None
    mb0 = mask.astype(jnp.float32) if has_mask else jnp.ones((b, t_local), jnp.float32)

    def process_block(carry, blk):
        """Online-softmax update (running rowmax m, normalizer l, weighted
        values o) for one (B, bs, H, D) KV chunk at global key offset k0."""
        o, m, l = carry
        kb, vb, mbk, k0 = blk
        logits = jnp.einsum("bqhd,bkhd->bhqk", q32, kb.astype(jnp.float32)) * scale
        if causal:
            ki = jnp.arange(kb.shape[1]) + k0
            cmask = qi[:, None] >= ki[None, :]
            logits = jnp.where(cmask[None, None], logits, -jnp.inf)
        if has_mask:
            logits = jnp.where(mbk[:, None, None, :] > 0, logits, -jnp.inf)
        m_new = jnp.maximum(m, jnp.max(logits, axis=-1))
        # guard: rows with no unmasked key yet keep m=-inf; exp(-inf - -inf)
        safe_m = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
        p = jnp.exp(logits - safe_m[..., None])
        p = jnp.where(jnp.isneginf(logits), 0.0, p)
        corr = jnp.where(jnp.isneginf(m), 0.0, jnp.exp(m - safe_m))
        l_new = l * corr + jnp.sum(p, axis=-1)
        o_new = o * corr[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", p, vb.astype(jnp.float32)
        )
        return (o_new, m_new, l_new), None

    perm = [(i, (i + 1) % n) for i in range(n)]

    def ring_step(carry, j):
        o, m, l, kb, vb, mbk = carry
        src = (idx - j) % n  # rank whose KV shard we currently hold
        k_base = src * t_local
        if n_blocks == 1:
            (o, m, l), _ = process_block((o, m, l), (kb, vb, mbk, k_base))
        else:
            kc = jnp.moveaxis(kb.reshape(b, n_blocks, bs, h, d), 1, 0)
            vc = jnp.moveaxis(vb.reshape(b, n_blocks, bs, h, d), 1, 0)
            mc = jnp.moveaxis(mbk.reshape(b, n_blocks, bs), 1, 0)
            offs = k_base + jnp.arange(n_blocks) * bs
            (o, m, l), _ = lax.scan(process_block, (o, m, l), (kc, vc, mc, offs))
        # rotate KV (and its mask) to the next rank for the following step
        kb = lax.ppermute(kb, axis, perm)
        vb = lax.ppermute(vb, axis, perm)
        if has_mask:
            mbk = lax.ppermute(mbk, axis, perm)
        return (o, m, l, kb, vb, mbk), None

    # the accumulators depend on this rank's q, so they VARY over the manual
    # axis — scan requires carry in/out types (incl. vma) to match
    def _vary(x):
        return lax.pcast(x, (axis,), to="varying")

    o0 = _vary(jnp.zeros((b, h, t_local, d), jnp.float32))
    m0 = _vary(jnp.full((b, h, t_local), -jnp.inf, jnp.float32))
    l0 = _vary(jnp.zeros((b, h, t_local), jnp.float32))
    (o, m, l, _, _, _), _ = lax.scan(
        ring_step, (o0, m0, l0, k, v, mb0), jnp.arange(n)
    )
    l = jnp.maximum(l, 1e-20)
    out = (o / l[..., None]).astype(q.dtype)
    return jnp.einsum("bhqd->bqhd", out)


def ulysses_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    axis: str,
    causal: bool = False,
    mask: jax.Array | None = None,
) -> jax.Array:
    """DeepSpeed-Ulysses style: all_to_all heads<->sequence, dense local
    attention over the FULL sequence on H/P heads, inverse all_to_all.

    Under shard_map with seq sharded on `axis`; requires H % axis_size == 0.
    q,k,v local: (B, T_local, H, D) -> returns (B, T_local, H, D).
    mask: local (B, T_local) keep-mask (all-gathered internally).
    """
    n = lax.axis_size(axis)
    h = q.shape[2]
    if h % n:
        raise ValueError(f"ulysses needs heads ({h}) divisible by axis size ({n})")

    def scatter_heads(x):
        # (B, T_local, H, D) -> (B, T_full, H/P, D)
        return lax.all_to_all(x, axis, split_axis=2, concat_axis=1, tiled=True)

    def gather_heads(x):
        return lax.all_to_all(x, axis, split_axis=1, concat_axis=2, tiled=True)

    qf, kf, vf = scatter_heads(q), scatter_heads(k), scatter_heads(v)
    mf = None
    if mask is not None:
        mf = lax.all_gather(mask, axis, axis=1, tiled=True)  # (B, T_full)
    out = mha(qf, kf, vf, causal=causal, mask=mf)
    return gather_heads(out)
