"""The decode step's latent attention kernel ``latent_paged_attn``
(`ops/latent_paged_attention.py`) in interpret mode against its ``xla``
form (gather the slot's pages, `ops/latent.attend_latent_rows`) on the CPU,
over ragged live pages: an idle slot, a slot of one row, one that ends
inside a page, one that fills its pages, one that spans several loop turns;
f32 and bf16 pools, one turn a slot and several.

Shapes: absorbed queries of 16 heads over latent rows of 128 + 64 stored
256 wide (the pool pads rows to whole lanes), pages of 16 rows, a table of 8
pages a slot over a pool of two layers.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.ops import latent
from deeplearning4j_tpu.ops import latent_paged_attention as lpa

S, H, LK, DR, WIDTH, PS, MP, P = 5, 16, 128, 64, 256, 16, 8, 48
LENS = [0, 1, 37, 64, 128]


def _case(dtype, seed=0):
    rng = np.random.default_rng(seed)
    pool = rng.standard_normal((2, P, PS, WIDTH)).astype(np.float32)
    pool[..., LK + DR:] = 0.0                       # the row's lane padding
    # every slot's own pages, none shared, page 0 left to the scratch
    tbl = (1 + rng.permutation(P - 1)[:S * MP]).reshape(S, MP).astype(
        np.int32)
    qc = rng.standard_normal((S, H, LK + DR)).astype(np.float32) * 0.1
    return (jnp.asarray(qc, dtype), jnp.asarray(pool, dtype),
            jnp.asarray(tbl), jnp.asarray(LENS, jnp.int32))


@pytest.mark.parametrize("dtype, tol", [(jnp.float32, 1e-5),
                                        (jnp.bfloat16, 2e-2)])
@pytest.mark.parametrize("turn_rows", [512, 32], ids=["one_turn", "turns"])
def test_kernel_is_the_gather_form_over_ragged_live_pages(
        dtype, tol, turn_rows, monkeypatch):
    monkeypatch.setattr(lpa, "_TURN_ROWS", turn_rows)
    qc, pool, tbl, lens = _case(dtype)
    got = np.asarray(lpa.latent_paged_attention(
        qc, pool, tbl, lens, layer=1, lk=LK, impl="pallas", interpret=True))
    want = np.asarray(lpa.latent_paged_attention(
        qc, pool, tbl, lens, layer=1, lk=LK, impl="xla"))
    assert got.shape == (S, H, LK) and got.dtype == np.float32
    assert np.abs(got[0]).max() == 0.0              # idle: zeros
    np.testing.assert_allclose(got, want, atol=tol, rtol=tol)


def test_gather_form_is_the_softmax_over_the_slots_rows():
    """The reference itself, by hand: slot 2 attends its first 37 rows of
    layer 0, a row at a time through the page table."""
    qc, pool, tbl, lens = _case(jnp.float32, seed=1)
    got = np.asarray(lpa.latent_paged_attention(
        qc, pool, tbl, lens, layer=0, lk=LK, impl="xla"))
    p, t = np.asarray(pool[0]), np.asarray(tbl[2])
    rows = np.stack([p[t[i // PS], i % PS] for i in range(37)])
    s = np.asarray(qc[2]) @ rows[:, :LK + DR].T
    w = np.exp(s - s.max(-1, keepdims=True))
    w /= w.sum(-1, keepdims=True)
    np.testing.assert_allclose(got[2], w @ rows[:, :LK], atol=1e-5)


def test_one_row_per_slot_is_the_absorbed_form_of_the_expanded_heads():
    """`absorb_queries` + the kernel's softmax + `absorbed_values` is the
    per-head attention of `attend_groups` over keys and values made from
    the same rows: W_kvb folded into the query and the output."""
    import types

    cfg = types.SimpleNamespace(
        n_heads=10, groups=2, signal_heads=8, n_noise_heads=2,
        qk_nope_head_dim=24, qk_rope_head_dim=8, v_head_dim=16,
        kv_lora_rank=32, softmax_scale=32 ** -0.5)
    rng = np.random.default_rng(3)
    rows = jnp.asarray(rng.standard_normal((20, 40)), jnp.float32)
    q = jnp.asarray(rng.standard_normal((1, 10, 32)), jnp.float32)
    wkvb = jnp.asarray(rng.standard_normal((32, 2 * 40)) / 6, jnp.float32)
    with jax.default_matmul_precision("highest"):
        qc = latent.absorb_queries(cfg, q, wkvb, jnp.float32)
        o_lat = latent.attend_latent_rows(qc, rows[None],
                                          jnp.ones((1, 20), bool), 32)
        got = latent.absorbed_values(cfg, o_lat, wkvb)
        k, v = latent.expand_groups(cfg, rows, wkvb)
        want = latent.attend_groups(cfg, q, k, v, jnp.ones((1, 20), bool))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
