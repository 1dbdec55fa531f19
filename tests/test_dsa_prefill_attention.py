"""The latent prefill's attention kernel ``dsa_prefill_attn``
(`ops/dsa_prefill_attention.py`) in interpret mode against its ``xla`` form
(`ops/latent.attend_expanded` in query blocks) on the CPU, under the masks a
chunk meets: the exact top-k with ties, a tile the selection leaves empty,
every causal-dead tile, a row that keeps only itself — and the tile bitmap
and the counts of tiles run and skipped against hand counts.

Shapes: a chunk of C = 256 queries at chunk index 0 (context 256) or 2
(context 768, queries at positions 512..767), heads of nope 192 + rope 64
over latent rows of 128 + 64, values 256 wide, so the tiles are 128 x 128:
two query blocks by 2 or 6 key blocks.
"""

import types

import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.ops import dsa_prefill_attention as dpa
from deeplearning4j_tpu.ops import latent

C, D, LK = 256, 256, 128


def _cfg(heads):
    return types.SimpleNamespace(
        n_heads=heads, qk_nope_head_dim=192, qk_rope_head_dim=64,
        v_head_dim=256, kv_lora_rank=LK, softmax_scale=D ** -0.5)


def _causal(ci):
    pos = ci * C + np.arange(C)
    return np.arange((ci + 1) * C)[None, :] <= pos[:, None]


def _topk_with_ties(ci, rng):
    """The indexer's exact top-k (k = 64) over scores rounded to a coarse
    grid, so that many rows tie across the k-th value."""
    scores = np.round(rng.standard_normal((C, (ci + 1) * C)) * 2) / 2
    valid = _causal(ci)
    return np.array(latent.topk_mask(jnp.asarray(scores, jnp.float32),
                                     jnp.asarray(valid), 64))


def _empty_tile(ci, rng):
    """Causal, less every pair of query block 1 with key block 0."""
    mask = _causal(ci)
    mask[128:, :128] = False
    return mask


def _all_causal(ci, rng):
    """Every pair the causal mask allows: only the causal-dead tiles."""
    return _causal(ci)


def _self_only(ci, rng):
    """The top-k, and row 5 keeps only its own position."""
    mask = _topk_with_ties(ci, rng)
    mask[5] = False
    mask[5, ci * C + 5] = True
    return mask


# (mask, chunk index, heads, dtype, bitmap, [run, skipped]) — the bitmap by
# hand: key block j of query block i is dead where the causal mask leaves it
# nothing (j * 128 > the block's last position) or the case empties it;
# counts are tiles x head groups of 8 heads
CASES = {
    "topk_ties_chunk0": (_topk_with_ties, 0, 4, jnp.bfloat16,
                         [[1, 0], [1, 1]], [3, 1]),
    "topk_ties_chunk2": (_topk_with_ties, 2, 4, jnp.bfloat16,
                         [[1, 1, 1, 1, 1, 0], [1, 1, 1, 1, 1, 1]], [11, 1]),
    "empty_tile_chunk2": (_empty_tile, 2, 4, jnp.bfloat16,
                          [[1, 1, 1, 1, 1, 0], [0, 1, 1, 1, 1, 1]], [10, 2]),
    "causal_chunk0_two_groups": (_all_causal, 0, 16, jnp.bfloat16,
                                 [[1, 0], [1, 1]], [6, 2]),
    "self_only_chunk2_f32": (_self_only, 2, 4, jnp.float32,
                             [[1, 1, 1, 1, 1, 0], [1, 1, 1, 1, 1, 1]],
                             [11, 1]),
}


@pytest.mark.parametrize("case", list(CASES))
def test_kernel_is_the_xla_form_and_counts_its_tiles(case):
    make, ci, heads, dtype, bitmap, counts = CASES[case]
    rng = np.random.default_rng(len(case))
    ctx = (ci + 1) * C
    mask = make(ci, rng)
    # every query row keeps at least one pair, so no output row is empty
    assert mask.any(axis=1).all()
    if case.startswith("self_only"):
        assert mask[5].sum() == 1 and mask[5, ci * C + 5]
    cfg = _cfg(heads)
    q = jnp.asarray(rng.standard_normal((C, heads, D)), dtype)
    # small integers, so that every key and value is exact in bf16 too
    lat = jnp.asarray(rng.integers(-1, 2, (ctx, LK + 64)), dtype)
    wkvb = jnp.asarray(rng.integers(-2, 3, (LK, heads * (192 + 256))) / 8,
                       dtype)
    m = jnp.asarray(mask)

    mask8, live = dpa.carry(cfg, m, "pallas")
    assert mask8.dtype == jnp.int8
    assert np.asarray(live).tolist() == bitmap
    assert np.asarray(dpa.tile_counts(live, heads)).tolist() == counts

    got = np.asarray(dpa.dsa_prefill_attention(
        cfg, q, lat, wkvb, mask8, live, interpret=True).astype(jnp.float32))
    # the reference in f32 on the same operands: the kernel's own roundings
    # (the weights to the values' dtype, the output to q's) are what differ
    f32 = lambda a: a.astype(jnp.float32)
    assert dpa.carry(cfg, m, "xla")[1] is None
    want = np.asarray(dpa.dsa_prefill_attention(
        cfg, f32(q), f32(lat), f32(wkvb), *dpa.carry(cfg, m, "xla")))
    assert got.shape == (C, heads * D)
    assert not np.isnan(got).any()
    assert np.abs(got - want).max() <= 2.0 ** -8 * np.abs(want).max()
    if case.startswith("self_only"):
        # the row that keeps only itself is its own value row, every head
        kv = np.asarray(f32(lat))[ci * C + 5, :LK] @ np.asarray(f32(wkvb))
        own = kv.reshape(heads, 192 + 256)[:, 192:].reshape(-1)
        assert np.abs(got[5] - own).max() <= 2.0 ** -8 * np.abs(own).max()


# -- grouped keys and values (GDLA), a causal plane and a window band ------------------

def _grouped_cfg():
    """10 query heads (8 signal + 2 noise) over 2 key/value groups, heads of
    nope 48 + rope 16 (padded to 128 lanes inside the kernel), values 128."""
    return types.SimpleNamespace(
        n_heads=10, groups=2, signal_heads=8, n_noise_heads=2,
        qk_nope_head_dim=48, qk_rope_head_dim=16, v_head_dim=128,
        kv_lora_rank=LK, softmax_scale=64 ** -0.5)


def _window_context(ci, w=64):
    """A window layer's context as the engine lays it out: the ring padded
    in front to whole tiles (128 - w rows that no query keeps), the ring's
    w rows (positions ci C - w ...), then the chunk's C rows; -> the key
    positions and the (C, 128 + C) keep mask of a w-row window."""
    pad = 128 - w
    keys = np.concatenate([np.full(pad, -1), ci * C - w + np.arange(w),
                           ci * C + np.arange(C)])
    back = (ci * C + np.arange(C))[:, None] - keys[None, :]
    return (back >= 0) & (back < w) & (keys >= 0)[None, :]


# (mask, chunk index, bitmap, [run, skipped]) — counts are tiles x 2 groups
GROUPED = {
    "causal_chunk2": (lambda ci: _causal(ci), 2,
                      [[1, 1, 1, 1, 1, 0], [1, 1, 1, 1, 1, 1]], [22, 2]),
    # block 0 holds the pad and the ring: query block 0 (positions 0-127 of
    # the chunk) reads it and itself, block 1 only itself and block 0 of
    # the chunk's rows
    "window_chunk1": (lambda ci: _window_context(ci), 1,
                      [[1, 1, 0], [0, 1, 1]], [8, 4]),
    "window_chunk0": (lambda ci: _window_context(ci), 0,
                      [[0, 1, 0], [0, 1, 1]], [6, 6]),
}


@pytest.mark.parametrize("case", list(GROUPED))
def test_grouped_kernel_is_the_xla_form_and_counts_its_tiles(case):
    make, ci, bitmap, counts = GROUPED[case]
    cfg = _grouped_cfg()
    rng = np.random.default_rng(len(case) + 7)
    mask = make(ci)
    assert mask.any(axis=1).all()
    q = jnp.asarray(rng.standard_normal((C, 10, 64)), jnp.bfloat16)
    lat = jnp.asarray(rng.integers(-1, 2, (mask.shape[1], LK + 16)),
                      jnp.bfloat16)
    wkvb = jnp.asarray(rng.integers(-2, 3, (LK, 2 * (48 + 128))) / 8,
                       jnp.bfloat16)
    m = jnp.asarray(mask)
    mask8, live = dpa.carry(cfg, m, "pallas")
    assert np.asarray(live).tolist() == bitmap
    assert np.asarray(dpa.tile_counts(live, 10, 2)).tolist() == counts
    got = np.asarray(dpa.dsa_prefill_attention(
        cfg, q, lat, wkvb, mask8, live, interpret=True).astype(jnp.float32))
    f32 = lambda a: a.astype(jnp.float32)
    want = np.asarray(dpa.dsa_prefill_attention(
        cfg, f32(q), f32(lat), f32(wkvb), *dpa.carry(cfg, m, "xla")))
    assert got.shape == (C, 10 * 128) and not np.isnan(got).any()
    assert np.abs(got - want).max() <= 2.0 ** -8 * np.abs(want).max()
    # by hand: noise head 8 and signal head 3 read group 0's key and value,
    # signal head 4 group 1's
    lat32, w32, q32 = (np.asarray(f32(a)) for a in (lat, wkvb, q))
    for head, g in ((8, 0), (3, 0), (4, 1)):
        kv = lat32[:, :LK] @ w32[:, g * 176:(g + 1) * 176]
        k = np.concatenate([kv[:, :48], lat32[:, LK:LK + 16]], -1)
        s = np.where(mask, q32[:, head] @ k.T * 64 ** -0.5, -np.inf)
        p = np.exp(s - s.max(-1, keepdims=True))
        o = (p / p.sum(-1, keepdims=True)) @ kv[:, 48:]
        np.testing.assert_allclose(want[:, head * 128:(head + 1) * 128], o,
                                   atol=1e-4)
