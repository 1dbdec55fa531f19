"""Attention of a latent (MLA) prefill chunk under the DSA selection — the
kernel ``dsa_prefill_attn``.

A chunk's queries ``q (C, H, d)`` attend the stream's whole context, the
cached latent rows ``latents (ctx, >= latent width)`` expanded into
per-head keys and values by ``wkvb``, under the exact top-k ``mask (C,
ctx)`` a full layer's indexer left (`ops/latent.topk_mask`; the shared
layers after it reuse it, as `carry` made it once):

    out[t, h] = softmax_j(q[t, h] . k[j, h] * scale | mask[t, j]) @ v[j, h]

-> (C, H * dv) in q's dtype.

- ``xla`` — `ops/latent.expand_latents` and `ops/latent.attend_expanded`
  in blocks of `XLA_QUERY_BLOCK` queries (`ops/latent.blocked`): f32
  scores ``(H, block, ctx)`` written to memory and read back by the mask,
  the max, the exp and the sum.  The CPU path and the kernel's reference.
- ``pallas`` — the keys and values expanded HEAD-MAJOR (`_expand`: one
  product ``(H, ctx, nope + v)``, its nope columns joined with the one
  rotary key repeated for every head), then the kernel: grid (query block
  i, head group g, key block j), square tiles of ``b = min(512, C / 2)``
  rows (`tile_rows`) and `HEAD_GROUP` heads a step: per head one product of the tile's queries against its
  keys on the MXU (bf16 operands, f32 scores), the mask tile applied with
  a finite stand-in for -inf, an online softmax in f32 carried over the key
  blocks in VMEM scratch, the weights cast to the values' dtype for the
  second product, and one normalisation at the last key block.  No score
  leaves VMEM.  The mask goes in as int8 and one tile of it serves the
  group's heads.

Dead tiles.  `live_tiles` reduces the mask, once per full layer (`carry`,
which also casts it to int8 once), to a bitmap ``(C / b, ctx / b)``: does
the tile keep any pair?  It reaches the kernel as a scalar-prefetch operand; a dead step runs nothing and names the
key, value and mask blocks already resident (`_resident`), so no copy is
issued for it.  Every tile above the causal diagonal is dead, and so is any
tile the selection leaves empty.  `tile_counts` counts what the kernel ran
and skipped.

Tiling at the `glm52_longdoc_sat` shapes (C 2,048, H 64, d = dv = 256,
bf16): b = 512, 8 heads a group, so a grid step holds q (512, 8 x 256) and
the output block (512, 8 x 256), 2 MiB each, keys and values (8, 512, 256)
2 MiB each, the mask tile (512, 512) int8 0.25 MiB, all double-buffered;
f32 scratch (8, 512, 256) 4 MiB and running max and sum (8, 512, 1) 2 MiB
each (lane-padded), and one head's f32 temporaries: 32.5 MiB by
`_vmem_bytes`, 39.6 MiB as Mosaic allocates it (offline v5e compile), so
``vmem_limit_bytes`` asks for the estimate + 16 MiB.
Bytes per layer and chunk at ctx 12,288: keys and values 0.81 GB, read once
per query block (4 x) less the dead tiles; the mask 25 MB read once per
head group (8 x), 1/16 of the key and value bytes.

Selection (``impl=None``): ``pallas`` on a TPU where the shapes tile
(`select_impl`), ``xla`` elsewhere; `carry` makes it and its result says
it (a bitmap, or None).  Tests run the kernel on the CPU with
``interpret=True``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deeplearning4j_tpu.ops.latent import attend_expanded, blocked, expand_latents

#: finite stand-in for -inf inside the kernel (see ops/paged_attention.py)
_MASK = -1e30
#: rows of a square tile, at most; a chunk has at least two query blocks
_TILE = 512
#: heads one grid step attends, sharing one mask tile
HEAD_GROUP = 8
#: queries per block of the ``xla`` path
XLA_QUERY_BLOCK = 256
#: the TPU's lanes: a compiled kernel's tile and head widths are multiples
_LANES = 128
_VMEM_SCOPED_DEFAULT = 16 * 2**20
_NT = (((1,), (1,)), ((), ()))      # a @ b.T


def tile_rows(c: int) -> int:
    """Rows of a square tile for a chunk of ``c`` queries."""
    return max(1, min(_TILE, c // 2))


def head_group(heads: int) -> int:
    return math.gcd(heads, HEAD_GROUP)


def select_impl(cfg, c: int, ctx: int, impl: str | None = None) -> str:
    """``impl`` where given, else ``pallas`` on a TPU where a chunk of ``c``
    queries over ``ctx`` rows tiles for the compiled kernel and ``xla``
    elsewhere."""
    if impl:
        return impl
    from deeplearning4j_tpu.runtime.backend import backend

    b = tile_rows(c)
    d = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
    tiles = (c % b == 0 and ctx % b == 0 and b % _LANES == 0
             and d % _LANES == 0 and cfg.v_head_dim % _LANES == 0)
    return "pallas" if backend().is_tpu and tiles else "xla"


def carry(cfg, mask, impl: str | None = None):
    """A full layer's selection ``mask (C, ctx)`` as the chosen path reads
    it, made once and carried to the shared layers after it: ``(mask as
    int8, live_tiles(mask))`` for the kernel, ``(mask, None)`` for the
    ``xla`` form."""
    if select_impl(cfg, *mask.shape, impl) == "xla":
        return mask, None
    return mask.astype(jnp.int8), live_tiles(mask)


def live_tiles(mask):
    """mask (C, ctx) -> int32 (C / b, ctx / b): 1 where the tile keeps any
    pair, 0 where it keeps none."""
    c, ctx = mask.shape
    b = tile_rows(c)
    return jnp.any(mask.reshape(c // b, b, ctx // b, b),
                   axis=(1, 3)).astype(jnp.int32)


def tile_counts(live, heads: int):
    """The (tile, head group) steps of one call that the kernel runs and
    skips: int32 ``[run, skipped]``."""
    groups = heads // head_group(heads)
    run = jnp.sum(live, dtype=jnp.int32)
    return jnp.stack([run, live.size - run]) * groups


def _resident(live):
    """(nq, nk) -> the key block each grid step names: its own where the
    tile is live; where dead, the last live block before it in the row (or
    the first after it), the block already in VMEM."""
    nk = live.shape[1]
    j = jnp.arange(nk, dtype=jnp.int32)[None, :]
    last = lax.cummax(jnp.where(live > 0, j, -1), axis=1)
    first = jnp.min(jnp.where(live > 0, j, nk - 1), axis=1, keepdims=True)
    return jnp.where(last >= 0, last, first).astype(jnp.int32)


def _kernel(live_ref, at_ref, q_ref, k_ref, v_ref, mask_ref, o_ref,
            acc_ref, m_ref, l_ref, *, heads: int, d: int, dv: int,
            scale: float, n_k: int):
    i, j = pl.program_id(0), pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _MASK)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(live_ref[i, j] > 0)
    def _tile():
        keep = mask_ref[...].astype(jnp.int32) > 0
        for h in range(heads):
            s = lax.dot_general(q_ref[:, h * d:(h + 1) * d], k_ref[h], _NT,
                                preferred_element_type=jnp.float32)
            # a row that keeps nothing here while its max is still the
            # stand-in adds exp(0) per entry; the first row entry it keeps
            # scales all of that by exp(_MASK - m) = 0
            s = jnp.where(keep, s * scale, _MASK)
            m_prev = m_ref[h]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_ref[h] = alpha * l_ref[h] + jnp.sum(p, axis=1, keepdims=True)
            acc_ref[h] = acc_ref[h] * alpha + jnp.dot(
                p.astype(v_ref.dtype), v_ref[h],
                preferred_element_type=jnp.float32)
            m_ref[h] = m_new

    @pl.when(j == n_k - 1)
    def _finish():
        for h in range(heads):
            o_ref[:, h * dv:(h + 1) * dv] = (
                acc_ref[h] / l_ref[h]).astype(o_ref.dtype)


def _vmem_bytes(b: int, hg: int, d: int, dv: int, itemsize: int) -> int:
    """Double-buffered blocks, scratch, and one head's f32 temporaries
    (scores, weights, the mask widened to int32, and what Mosaic keeps of
    them beside each other)."""
    blocks = b * hg * (2 * d + 2 * dv) * itemsize + b * b       # q k v o mask
    scratch = hg * b * (dv + 2 * _LANES) * 4
    return 2 * blocks + scratch + 8 * b * b * 4


def _expand(cfg, latents, wkvb):
    """`ops/latent.expand_latents` head-major: ``(k (H, n, nope + rope),
    v (H, n, v))``, the layout the kernel's blocks read."""
    dn, dv, lk = cfg.qk_nope_head_dim, cfg.v_head_dim, cfg.kv_lora_rank
    n, h_ = latents.shape[0], cfg.n_heads
    with jax.named_scope("dsa_attend"):
        kv = jnp.einsum("nc,chd->hnd", latents[:, :lk],
                        wkvb.astype(latents.dtype).reshape(lk, h_, dn + dv))
        k_r = latents[None, :, lk:lk + cfg.qk_rope_head_dim]
        k = jnp.concatenate(
            [kv[..., :dn], jnp.broadcast_to(k_r, (h_,) + k_r.shape[1:])],
            axis=-1)
    return k, kv[..., dn:]


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def _pallas(q, k, v, mask, live, *, scale: float, interpret: bool):
    c, heads, d = q.shape
    ctx, dv = k.shape[1], v.shape[2]
    b, hg = tile_rows(c), head_group(heads)
    if c % b or ctx % b:
        raise ValueError(f"a chunk of {c} over a context of {ctx} does not "
                         f"tile into {b} x {b}")
    nq, nk = c // b, ctx // b
    kv_block = lambda w: pl.BlockSpec(
        (hg, b, w), lambda i, g, j, live, at: (g, at[i, j], 0))
    kwargs = {}
    if not interpret:
        need = _vmem_bytes(b, hg, d, dv, k.dtype.itemsize)
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            **({"vmem_limit_bytes": need + (16 << 20)}
               if need > _VMEM_SCOPED_DEFAULT else {}))
    return pl.pallas_call(
        functools.partial(_kernel, heads=hg, d=d, dv=dv, scale=scale, n_k=nk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(nq, heads // hg, nk),
            in_specs=[
                pl.BlockSpec((b, hg * d),
                             lambda i, g, j, live, at: (i, g)),
                kv_block(d),
                kv_block(dv),
                pl.BlockSpec((b, b), lambda i, g, j, live, at: (i, at[i, j])),
            ],
            out_specs=pl.BlockSpec((b, hg * dv),
                                   lambda i, g, j, live, at: (i, g)),
            scratch_shapes=[
                pltpu.VMEM((hg, b, dv), jnp.float32),       # acc
                pltpu.VMEM((hg, b, 1), jnp.float32),        # running max
                pltpu.VMEM((hg, b, 1), jnp.float32),        # running sum
            ]),
        out_shape=jax.ShapeDtypeStruct((c, heads * dv), q.dtype),
        interpret=interpret,
        name="dsa_prefill_attn",
        **kwargs,
    )(live, _resident(live), q.reshape(c, heads * d), k, v, mask)


def dsa_prefill_attention(cfg, q, latents, wkvb, mask, live, *,
                          interpret: bool | None = None):
    """q (C, H, d); latents (ctx, >= latent width); ``(mask, live)`` as
    `carry` made them: the ``xla`` form where ``live`` is None, else the
    kernel -> (C, H * dv) in q's dtype, scores scaled by
    ``cfg.softmax_scale``."""
    if live is None:
        k, v = expand_latents(cfg, latents, wkvb)
        return blocked(lambda qb, mb: attend_expanded(cfg, qb, k, v, mb),
                       XLA_QUERY_BLOCK, q, mask)
    if interpret is None:
        from deeplearning4j_tpu.runtime.backend import backend

        interpret = not backend().is_tpu
    return _pallas(q, *_expand(cfg, latents, wkvb), mask, live,
                   scale=float(cfg.softmax_scale), interpret=interpret)
