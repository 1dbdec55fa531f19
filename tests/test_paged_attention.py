"""ISSUE 16 — paged-attention kernel parity.

The paged pools + page table are the serving plane's KV layout; this
file holds the three implementations to each other and to the dense
`cache_row_attention` numerics: the XLA gather reference IS the contract,
the Pallas online-softmax kernel (interpret mode on CPU) must match it to
float tolerance, and the fused int8 path must match dequantize-then-
attend exactly (the dequant is algebraically hoisted, not
approximated).  Masking is load-bearing: garbage rows past ``seq_len``
and idle slots (seq_len 0 parked on the scratch page) must never leak
into an output.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.ops.paged_attention import (
    IMPLS,
    paged_attention,
    paged_attention_chunk,
    select_impl,
)
from deeplearning4j_tpu.serving.kv_cache import quantize_page_rows

pytestmark = pytest.mark.generation

S, H, DH = 4, 2, 8          # slots, heads, head_dim
P, PS, MAXP = 24, 4, 5      # pool pages, page size, table width


def _case(seed=0, seq_lens=(7, 1, 13, 4)):
    """One random decode step: q rows, full pools, a page table whose
    entries are distinct pages, and per-slot live lengths."""
    rng = np.random.default_rng(seed)
    q = rng.standard_normal((S, H, DH)).astype(np.float32)
    k_pages = rng.standard_normal((P, PS, H, DH)).astype(np.float32)
    v_pages = rng.standard_normal((P, PS, H, DH)).astype(np.float32)
    tbl = rng.permutation(np.arange(1, P))[: S * MAXP].reshape(S, MAXP)
    return (jnp.asarray(q), jnp.asarray(k_pages), jnp.asarray(v_pages),
            jnp.asarray(tbl.astype(np.int32)),
            jnp.asarray(np.array(seq_lens, np.int32)))


def _dense_reference(q, k_pages, v_pages, tbl, seq_lens):
    """Per-slot dense softmax attention over the gathered live rows —
    `ops.generation.cache_row_attention`'s numerics, computed
    independently."""
    q, kp, vp = map(np.asarray, (q, k_pages, v_pages))
    tbl, seq_lens = np.asarray(tbl), np.asarray(seq_lens)
    out = np.zeros_like(q)
    for s in range(S):
        n = int(seq_lens[s])
        if n == 0:
            continue
        rows_k = np.concatenate([kp[p] for p in tbl[s]], axis=0)[:n]
        rows_v = np.concatenate([vp[p] for p in tbl[s]], axis=0)[:n]
        for h in range(H):
            scores = rows_k[:, h] @ q[s, h] / np.sqrt(DH)
            p = np.exp(scores - scores.max())
            p /= p.sum()
            out[s, h] = p @ rows_v[:, h]
    return out


class TestF32Parity:
    def test_xla_matches_dense_reference(self):
        q, kp, vp, tbl, lens = _case()
        got = np.asarray(
            paged_attention(q, kp, vp, tbl, lens, impl="xla"))
        np.testing.assert_allclose(
            got, _dense_reference(q, kp, vp, tbl, lens),
            rtol=1e-5, atol=1e-5)

    def test_pallas_matches_xla(self):
        q, kp, vp, tbl, lens = _case(seed=1)
        ref = np.asarray(paged_attention(q, kp, vp, tbl, lens, impl="xla"))
        got = np.asarray(paged_attention(
            q, kp, vp, tbl, lens, impl="pallas", interpret=True))
        np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-6)

    def test_garbage_rows_past_seq_len_are_masked(self):
        """Poisoning every row past each slot's live length (the exact
        rows a recycled page carries) must not move any output."""
        q, kp, vp, tbl, lens = _case(seed=2)
        kp2, vp2 = np.asarray(kp).copy(), np.asarray(vp).copy()
        for s in range(S):
            n = int(np.asarray(lens)[s])
            for j, p in enumerate(np.asarray(tbl)[s]):
                for r in range(PS):
                    if j * PS + r >= n:
                        kp2[p, r] = 1e4
                        vp2[p, r] = -1e4
        for impl, kw in (("xla", {}), ("pallas", {"interpret": True})):
            a = np.asarray(paged_attention(q, kp, vp, tbl, lens,
                                           impl=impl, **kw))
            b = np.asarray(paged_attention(
                q, jnp.asarray(kp2), jnp.asarray(vp2), tbl, lens,
                impl=impl, **kw))
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6,
                                       err_msg=impl)

    def test_idle_slot_is_finite(self):
        """seq_len 0 (an idle decode slot on the scratch page) must
        produce FINITE output — a plain softmax would nan a fully
        masked row, and one nan row would poison the whole fused step.
        The engine discards idle rows via its active mask, so the two
        impls may differ in the garbage VALUE (xla zeros it, pallas's
        online softmax leaves uniform-weight garbage); live slots must
        still agree exactly."""
        q, kp, vp, tbl, lens = _case(seed=3, seq_lens=(0, 5, 0, 2))
        outs = {}
        for impl, kw in (("xla", {}), ("pallas", {"interpret": True})):
            out = np.asarray(paged_attention(q, kp, vp, tbl, lens,
                                             impl=impl, **kw))
            assert np.isfinite(out).all(), impl
            outs[impl] = out
        np.testing.assert_allclose(outs["xla"][0], 0.0, atol=1e-6)
        np.testing.assert_allclose(outs["xla"][2], 0.0, atol=1e-6)
        for s in (1, 3):                      # the live slots
            np.testing.assert_allclose(
                outs["pallas"][s], outs["xla"][s], rtol=1e-5, atol=1e-6)


class TestInt8Parity:
    def _quantized(self, kp, vp):
        kq = np.zeros(np.asarray(kp).shape, np.int8)
        ks = np.ones(np.asarray(kp).shape[:-1], np.float32)
        vq, vs = kq.copy(), ks.copy()
        for p in range(P):
            kq[p], ks[p] = map(np.asarray, quantize_page_rows(kp[p]))
            vq[p], vs[p] = map(np.asarray, quantize_page_rows(vp[p]))
        return (jnp.asarray(kq), jnp.asarray(ks),
                jnp.asarray(vq), jnp.asarray(vs))

    def test_fused_matches_dequantize_then_attend(self):
        """The int8 kernels must equal attention over explicitly
        dequantized pools — fusion is a layout change, not a numerics
        change."""
        q, kp, vp, tbl, lens = _case(seed=4)
        kq, ks, vq, vs = self._quantized(kp, vp)
        deq_k = jnp.asarray(kq, jnp.float32) * ks[..., None]
        deq_v = jnp.asarray(vq, jnp.float32) * vs[..., None]
        ref = np.asarray(paged_attention(q, deq_k, deq_v, tbl, lens,
                                         impl="xla"))
        for impl, kw in (("xla", {}), ("pallas", {"interpret": True})):
            got = np.asarray(paged_attention(
                q, kq, vq, tbl, lens, k_scale=ks, v_scale=vs,
                impl=impl, **kw))
            np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-5,
                                       err_msg=impl)

    def test_int8_tracks_f32_within_quant_error(self):
        q, kp, vp, tbl, lens = _case(seed=5)
        kq, ks, vq, vs = self._quantized(kp, vp)
        f32 = np.asarray(paged_attention(q, kp, vp, tbl, lens, impl="xla"))
        i8 = np.asarray(paged_attention(
            q, kq, vq, tbl, lens, k_scale=ks, v_scale=vs, impl="xla"))
        assert np.max(np.abs(f32 - i8)) < 0.15

    def test_scales_must_come_in_pairs(self):
        q, kp, vp, tbl, lens = _case()
        ks = jnp.ones((P, PS, H), jnp.float32)
        with pytest.raises(ValueError, match="BOTH"):
            paged_attention(q, kp, vp, tbl, lens, k_scale=ks)


class TestLayerIndexedPool:
    """The serving step hands the kernel the WHOLE (L, P, ps, H, Dh)
    stack plus a static ``layer``: every route must read exactly what
    the 4-D call reads on ``pool[layer]`` — same gather, same blocks,
    one more index."""

    L = 3

    def _stack(self, kv_dtype, seed):
        """L distinct layers of `_case`'s pools (other layers hold other
        values, so reading the wrong layer cannot pass)."""
        rng = np.random.default_rng(100 + seed)
        shape = (self.L, P, PS, H, DH)
        k5 = rng.standard_normal(shape).astype(np.float32)
        v5 = rng.standard_normal(shape).astype(np.float32)
        if kv_dtype == "f32":
            return jnp.asarray(k5), jnp.asarray(v5), None, None
        kq, ks = quantize_page_rows(k5)
        vq, vs = quantize_page_rows(v5)
        return kq, vq, ks, vs

    @pytest.mark.parametrize("kv_dtype", ["f32", "int8"])
    @pytest.mark.parametrize("impl", ["xla", "pallas"])
    def test_step_equals_the_4d_call_on_that_layer(self, impl, kv_dtype):
        q, _, _, tbl, lens = _case(seed=6)
        k5, v5, ks5, vs5 = self._stack(kv_dtype, seed=6)
        for li in (0, self.L - 1):
            sc4 = ({} if ks5 is None
                   else dict(k_scale=ks5[li], v_scale=vs5[li]))
            sc5 = ({} if ks5 is None
                   else dict(k_scale=ks5, v_scale=vs5))
            ref = np.asarray(paged_attention(
                q, k5[li], v5[li], tbl, lens, impl=impl, interpret=True,
                **sc4))
            got = np.asarray(paged_attention(
                q, k5, v5, tbl, lens, layer=li, impl=impl, interpret=True,
                **sc5))
            np.testing.assert_array_equal(got, ref, err_msg=f"layer {li}")

    @pytest.mark.parametrize("kv_dtype", ["f32", "int8"])
    @pytest.mark.parametrize("impl", ["xla", "pallas"])
    def test_chunk_equals_the_4d_call_on_that_layer(self, impl, kv_dtype):
        c = 3
        _, _, _, tbl, lens = _case(seed=7)
        rng = np.random.default_rng(7)
        q = jnp.asarray(
            rng.standard_normal((S, c, H, DH)).astype(np.float32))
        attend = jnp.minimum(lens[:, None] + jnp.arange(c)[None, :] + 1,
                             MAXP * PS).astype(jnp.int32)
        k5, v5, ks5, vs5 = self._stack(kv_dtype, seed=7)
        li = 1
        sc4 = {} if ks5 is None else dict(k_scale=ks5[li], v_scale=vs5[li])
        sc5 = {} if ks5 is None else dict(k_scale=ks5, v_scale=vs5)
        ref = np.asarray(paged_attention_chunk(
            q, k5[li], v5[li], tbl, attend, impl=impl, interpret=True,
            **sc4))
        got = np.asarray(paged_attention_chunk(
            q, k5, v5, tbl, attend, layer=li, impl=impl, interpret=True,
            **sc5))
        np.testing.assert_array_equal(got, ref)

    @pytest.mark.parametrize("layer,rank", [(None, 5), (1, 4)])
    def test_rank_and_layer_must_agree(self, layer, rank):
        q, kp, vp, tbl, lens = _case()
        if rank == 5:
            kp, vp = kp[None], vp[None]
        with pytest.raises(ValueError, match="layer="):
            paged_attention(q, kp, vp, tbl, lens, layer=layer, impl="xla")


class TestLivePageLoop:
    """The kernel's iteration space is the slot's LIVE pages, several a
    loop turn: every boundary of a page and of a turn, both element
    types, the pool given alone and as a stack with ``layer``, always
    through a shuffled (non-contiguous) page table — against the `xla`
    route.  Pages of 16 rows make a turn of 4 pages, 64 rows, in a
    table of 10."""

    PS, MAXP, L = 16, 10, 3
    TURN = 4 * PS
    LENS = {"empty": 0, "one_row": 1, "page_less_one": PS - 1,
            "one_page": PS, "page_plus_one": PS + 1, "one_turn": TURN,
            "turn_plus_one": TURN + 1, "whole_span": MAXP * PS}

    def _case(self, seq_len, kv_dtype, seed=0):
        """Three slots — the length under test between an idle slot and
        one of another length — over a pool whose other layers and pages
        hold other values."""
        rng = np.random.default_rng(seed)
        lens = np.array([0, seq_len, 37], np.int32)
        n_pool = 3 * self.MAXP + 1
        tbl = (rng.permutation(np.arange(1, n_pool))
               .reshape(3, self.MAXP).astype(np.int32))
        shape = (self.L, n_pool, self.PS, H, DH)
        k = rng.standard_normal(shape).astype(np.float32)
        v = rng.standard_normal(shape).astype(np.float32)
        q = jnp.asarray(rng.standard_normal((3, H, DH)).astype(np.float32))
        pools = [k, v]
        if kv_dtype == "int8":
            kq, ks = quantize_page_rows(jnp.asarray(k))
            vq, vs = quantize_page_rows(jnp.asarray(v))
            pools = [np.array(a) for a in (kq, vq, ks, vs)]
        return q, pools, tbl, lens

    _programs = {}

    @classmethod
    def _call(cls, q, pools, tbl, lens, layer, **kw):
        """One jitted program per route, element type and layer serves
        every length: ``seq_lens`` is data, which is the point."""
        if layer is None:                    # the 4-D pool, alone
            pools = [a[1] for a in pools]
        key = (len(pools), layer, tuple(sorted(kw.items())))
        if key not in cls._programs:
            def f(q, tbl, lens, k, v, ks=None, vs=None):
                return paged_attention(q, k, v, tbl, lens, k_scale=ks,
                                       v_scale=vs, layer=layer, **kw)
            cls._programs[key] = jax.jit(f)
        return np.asarray(cls._programs[key](
            q, jnp.asarray(tbl), jnp.asarray(lens),
            *(jnp.asarray(a) for a in pools)))

    def test_turn_follows_the_shapes(self):
        from deeplearning4j_tpu.ops.paged_attention import _pages_per_turn

        assert _pages_per_turn(self.PS, self.PS * H * DH * 4,
                               self.MAXP) * self.PS == self.TURN
        # the cell's pages: 16 rows of 16 x 128 f32
        assert _pages_per_turn(16, 16 * 16 * 128 * 4, 80) == 4
        # a page that alone fills the buffers, and a table of one page
        assert _pages_per_turn(16, 8 << 20, 80) == 1
        assert _pages_per_turn(4, 256, 1) == 1

    @pytest.mark.parametrize("layer", [None, 1], ids=["pool", "stack"])
    @pytest.mark.parametrize("kv_dtype", ["f32", "int8"])
    @pytest.mark.parametrize("seq_len", list(LENS.values()),
                             ids=list(LENS))
    def test_matches_xla(self, seq_len, kv_dtype, layer):
        q, pools, tbl, lens = self._case(seq_len, kv_dtype, seed=seq_len)
        ref = self._call(q, pools, tbl, lens, layer, impl="xla")
        got = self._call(q, pools, tbl, lens, layer, impl="pallas",
                         interpret=True)
        np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-5)
        # an idle slot writes zeros, whatever its table row names
        np.testing.assert_array_equal(got[0], 0.0)
        if seq_len == 0:
            np.testing.assert_array_equal(got[1], 0.0)

    @pytest.mark.parametrize("kv_dtype", ["f32", "int8"])
    @pytest.mark.parametrize("poison", [np.nan, np.inf], ids=["nan", "inf"])
    def test_nothing_dead_reaches_the_output(self, poison, kv_dtype):
        """Every page the table does not make live, and every row past
        ``seq_len`` in the last live page, holds nan or inf (an int8
        pool: in its scales): the output is finite and the reference's
        over the clean pool.  0 x garbage is not 0."""
        seq_len = self.TURN + self.PS + 3    # a turn, a page, three rows
        q, pools, tbl, lens = self._case(seq_len, kv_dtype, seed=11)
        ref = self._call(q, pools, tbl, lens, 1, impl="xla")
        live = np.zeros(pools[0].shape[:3], bool)       # (L, P, ps)
        for s, n in enumerate(lens):
            for j in range(-(-int(n) // self.PS)):
                live[:, tbl[s, j], :min(self.PS, n - j * self.PS)] = True
        # an int8 row cannot hold a nan: its scale does
        for a in (pools[2:] if kv_dtype == "int8" else pools):
            a[~live] = poison
        got = self._call(q, pools, tbl, lens, 1, impl="pallas",
                         interpret=True)
        assert np.isfinite(got).all()
        np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-5)


class TestSelection:
    def test_env_override_wins(self, monkeypatch):
        from deeplearning4j_tpu.ops import paged_attention as pa

        monkeypatch.setenv(pa.ENV_KERNEL, "xla")
        assert select_impl() == "xla"
        monkeypatch.setenv(pa.ENV_KERNEL, "pallas")
        assert select_impl() == "pallas"

    def test_cpu_defaults_to_xla(self, monkeypatch):
        from deeplearning4j_tpu.ops import paged_attention as pa

        monkeypatch.delenv(pa.ENV_KERNEL, raising=False)
        assert select_impl() in IMPLS

    def test_selection_metric_counts(self):
        from deeplearning4j_tpu.observe.metrics import registry

        q, kp, vp, tbl, lens = _case()
        before = registry().counter(
            "dl4jtpu_paged_attention_total").value(impl="xla")
        paged_attention(q, kp, vp, tbl, lens, impl="xla")
        after = registry().counter(
            "dl4jtpu_paged_attention_total").value(impl="xla")
        assert after == before + 1
