"""Pallas flash-attention tests (interpret mode on the CPU platform):
forward/gradient parity vs the dense reference, dispatch gating, and the
DSL attention layer riding the kernel."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.ops.attention import mha
from deeplearning4j_tpu.ops import flash_attention as fa

RNG = np.random.default_rng(3)


def qkv(b=2, t=256, h=2, d=64, dtype=np.float32):
    def one():
        return jnp.asarray(RNG.normal(0, 1, (b, t, h, d)).astype(dtype))

    return one(), one(), one()


class TestForwardParity:
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_dense(self, causal):
        q, k, v = qkv()
        dense = mha(q, k, v, causal=causal)
        flash = fa.flash_attention(q, k, v, causal=causal, interpret=True, mxu_f32=True)
        np.testing.assert_allclose(
            np.asarray(flash), np.asarray(dense), rtol=2e-4, atol=2e-5
        )

    def test_cross_attention_lengths(self):
        q, _, _ = qkv(t=128)
        _, k, v = qkv(t=384)
        dense = mha(q, k, v)
        flash = fa.flash_attention(q, k, v, interpret=True, mxu_f32=True)
        np.testing.assert_allclose(
            np.asarray(flash), np.asarray(dense), rtol=2e-4, atol=2e-5
        )

    def test_small_sequence_uses_whole_block(self):
        q, k, v = qkv(t=64)
        dense = mha(q, k, v, causal=True)
        flash = fa.flash_attention(q, k, v, causal=True, interpret=True, mxu_f32=True)
        np.testing.assert_allclose(
            np.asarray(flash), np.asarray(dense), rtol=2e-4, atol=2e-5
        )

    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.parametrize("blocks", [(128, 256), (256, 128), (128, 512),
                                        (128, 128)])
    def test_unequal_and_several_blocks(self, causal, blocks):
        """T = 512 in 128- or 256-row blocks: several blocks a side, so the
        clamped index maps, the skipped steps and the diagonal-only mask
        all run; block_q != block_k moves the diagonal through a tile."""
        q, k, v = qkv(b=1, t=512, h=2, d=32)
        dense = mha(q, k, v, causal=causal)
        flash = fa.flash_attention(q, k, v, causal=causal, interpret=True,
                                   mxu_f32=True, block_q=blocks[0],
                                   block_k=blocks[1])
        np.testing.assert_allclose(
            np.asarray(flash), np.asarray(dense), rtol=2e-4, atol=2e-5
        )

    def test_length_only_the_smallest_rung_divides(self, monkeypatch):
        """640 = 5 x 128: 1024, 512 and 256 do not divide it, so it falls
        down the ladder to 128 — and stays in flash when forced."""
        assert fa._block_choice(640, 640, 32, True, None, None) == (128, 128)
        q, k, v = qkv(b=1, t=640, h=2, d=32)
        monkeypatch.setenv(fa.ENV_FLASH, "1")
        assert fa.flash_eligible(q, k, None)
        monkeypatch.setenv(fa.ENV_FLASH, "0")
        dense = mha(q, k, v, causal=True)
        flash = fa.flash_attention(q, k, v, causal=True, interpret=True,
                                   mxu_f32=True)
        np.testing.assert_allclose(
            np.asarray(flash), np.asarray(dense), rtol=2e-4, atol=2e-5
        )


class TestBf16Default:
    def test_bf16_kernel_within_bf16_tolerance(self):
        q, k, v = qkv(t=256)
        dense = mha(q, k, v, causal=True)
        flash = fa.flash_attention(q, k, v, causal=True, interpret=True)
        np.testing.assert_allclose(
            np.asarray(flash), np.asarray(dense), rtol=3e-2, atol=3e-2
        )


    @pytest.mark.parametrize("causal", [False, True])
    def test_f32_inputs_holding_bf16_values_equal_bf16_inputs(self, causal):
        """The wrapper's cast moved no rounding point: f32 inputs that hold
        bf16-representable values give, once cast to bf16, exactly what
        the same values passed as bf16 give."""
        qb, kb, vb = (x.astype(jnp.bfloat16) for x in qkv(b=1, t=256, d=32))
        q32, k32, v32 = (x.astype(jnp.float32) for x in (qb, kb, vb))
        kw = dict(causal=causal, interpret=True, block_q=128, block_k=128)
        out_bf16 = fa.flash_attention(qb, kb, vb, **kw)
        out_f32 = fa.flash_attention(q32, k32, v32, **kw)
        assert out_bf16.dtype == jnp.bfloat16 and out_f32.dtype == jnp.float32
        np.testing.assert_array_equal(
            np.asarray(out_f32.astype(jnp.bfloat16).astype(jnp.float32)),
            np.asarray(out_bf16.astype(jnp.float32)))

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("mxu_f32", [False, True])
    def test_output_and_gradient_dtypes_are_the_inputs(self, dtype, mxu_f32):
        q, k, v = (x.astype(dtype) for x in qkv(b=1, t=128, h=1, d=32))

        def loss(q, k, v):
            out = fa.flash_attention(q, k, v, causal=True, interpret=True,
                                     mxu_f32=mxu_f32)
            assert out.dtype == dtype
            return jnp.sum(out.astype(jnp.float32) ** 2)

        grads = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        assert [g.dtype for g in grads] == [dtype] * 3
        assert all(np.all(np.isfinite(np.asarray(g, np.float32)))
                   for g in grads)


def assert_grads_match_dense(q, k, v, *, causal, **flash_kw):
    """d(sum(out^2)) through the f32-MXU kernels against the dense path."""
    def loss_flash(q, k, v):
        return jnp.sum(fa.flash_attention(q, k, v, causal=causal,
                                          interpret=True, mxu_f32=True,
                                          **flash_kw) ** 2)

    def loss_dense(q, k, v):
        return jnp.sum(mha(q, k, v, causal=causal) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gf, gd, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=3e-3, atol=3e-4,
            err_msg=f"d{name}",
        )


class TestGradientParity:
    @pytest.mark.parametrize("causal", [False, True])
    def test_grads_match_dense(self, causal):
        q, k, v = qkv(b=1, t=128, h=2, d=32)
        assert_grads_match_dense(q, k, v, causal=causal)

    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.parametrize("blocks", [(128, 256), (256, 128), (128, 128),
                                        None])
    def test_grads_over_several_blocks(self, causal, blocks):
        """dQ (KV inner, clamped to the last block a row needs) and dK+dV
        (Q inner, clamped to the first block a column needs, tile built
        transposed) over a 4 x 2 / 2 x 4 / 4 x 4 grid and at the default
        (one 512 block)."""
        q, k, v = qkv(b=1, t=512, h=1, d=32)
        bq, bk = blocks or (None, None)
        assert_grads_match_dense(q, k, v, causal=causal, block_q=bq,
                                 block_k=bk)

    @pytest.mark.parametrize("t_q, t_k", [(256, 512), (512, 256)])
    def test_causal_grads_with_unequal_lengths(self, t_q, t_k):
        """Keys beyond the last query belong to no row (their dK, dV are
        zero and their Q index stays inside the array); queries beyond
        the last key attend to every key."""
        q, _, _ = qkv(b=1, t=t_q, h=1, d=32)
        _, k, v = qkv(b=1, t=t_k, h=1, d=32)
        assert_grads_match_dense(q, k, v, causal=True, block_q=128,
                                 block_k=128)

    def test_grads_at_the_length_only_128_divides(self):
        q, k, v = qkv(b=1, t=640, h=1, d=32)
        assert_grads_match_dense(q, k, v, causal=True)

    def test_cross_attention_grads_with_unequal_lengths(self):
        q, _, _ = qkv(b=1, t=256, h=1, d=32)
        _, k, v = qkv(b=1, t=384, h=1, d=32)
        assert fa._block_choice(256, 384, 32, False, None, None) == (256, 128)
        assert_grads_match_dense(q, k, v, causal=False)


class TestDispatch:
    def test_eligibility_rules(self, monkeypatch):
        q, k, v = qkv(t=256)
        monkeypatch.delenv(fa.ENV_FLASH, raising=False)
        # CPU default: not eligible (TPU-only heuristic)
        assert not fa.flash_eligible(q, k, None)
        monkeypatch.setenv(fa.ENV_FLASH, "1")
        assert fa.flash_eligible(q, k, None)
        assert not fa.flash_eligible(q, k, jnp.ones((2, 256)))   # masked
        monkeypatch.setenv(fa.ENV_FLASH, "0")
        assert not fa.flash_eligible(q, k, None)

    def test_eligible_lengths_are_what_they_were(self, monkeypatch):
        """Forced: any multiple of 128 (or a length under 128); on a TPU:
        any multiple of 128 from 2048 up.  The larger default blocks
        changed neither set."""
        import importlib

        bk = importlib.import_module("deeplearning4j_tpu.runtime.backend")

        def admitted():
            out = set()
            for t in list(range(32, 4352, 32)) + [100, 2049, 3000]:
                x = jax.ShapeDtypeStruct((1, t, 1, 128), jnp.float32)
                if fa.flash_eligible(x, x, None):
                    out.add(t)
            return out

        lengths = set(range(32, 4352, 32)) | {100, 2049, 3000}
        monkeypatch.setenv(fa.ENV_FLASH, "1")
        assert admitted() == {t for t in lengths if t % min(128, t) == 0}
        monkeypatch.delenv(fa.ENV_FLASH)
        assert admitted() == set()                      # the CPU
        tpu = bk.Backend(platform="tpu", device_kind="TPU v5 lite",
                         num_devices=1, supports_bfloat16_matmul=True)
        monkeypatch.setattr(bk, "backend", lambda: tpu)
        assert admitted() == {t for t in lengths
                              if t % 128 == 0 and t >= 2048}
        # 2176 = 17 x 128 stays in flash: it falls down the ladder to 128
        assert 2176 in admitted()
        assert fa._block_choice(2176, 2176, 128, True, None, None) == (128, 128)

    def test_trace_time_counter_carries_the_blocks(self):
        """`dl4jtpu_flash_attention_total{block_q, block_k, causal}` counts
        a traced call once, under the tiling its program got."""
        from deeplearning4j_tpu.observe.metrics import registry

        x = jax.ShapeDtypeStruct((1, 2048, 2, 128), jnp.float32)
        bq, bk = fa._block_choice(2048, 2048, 128, True, None, None)
        assert (bq, bk) == (1024, 1024)
        counter = registry().counter("dl4jtpu_flash_attention_total")
        labels = dict(block_q=str(bq), block_k=str(bk), causal="true")
        before = counter.value(**labels)
        total = counter.sum_series()
        jax.eval_shape(
            lambda q, k, v: fa.flash_attention(q, k, v, causal=True,
                                               interpret=True), x, x, x)
        assert counter.value(**labels) == before + 1
        assert counter.sum_series() == total + 1
        jax.eval_shape(
            lambda q, k, v: fa.flash_attention(q, k, v, block_q=256,
                                               block_k=512, interpret=True),
            x, x, x)
        assert counter.value(block_q="256", block_k="512",
                             causal="false") >= 1

    def test_mha_routes_to_flash_when_forced(self, monkeypatch):
        calls = {}
        orig = fa.flash_attention

        def spy(*args, **kw):
            calls["hit"] = True
            return orig(*args, **kw)

        monkeypatch.setattr(fa, "flash_attention", spy)
        monkeypatch.setenv(fa.ENV_FLASH, "1")
        q, k, v = qkv(t=256)
        out = mha(q, k, v, causal=True)
        assert calls.get("hit")
        monkeypatch.setenv(fa.ENV_FLASH, "0")
        dense = mha(q, k, v, causal=True)
        # forced path runs the bf16-MXU default kernel
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(dense), rtol=3e-2, atol=3e-2
        )

    def test_attention_layer_rides_flash(self, monkeypatch):
        from deeplearning4j_tpu.nn.conf.attention import SelfAttentionLayer
        from deeplearning4j_tpu.nn.conf.input_type import InputType

        calls = {}
        orig = fa.flash_attention

        def spy(*args, **kw):
            calls["hit"] = True
            return orig(*args, **kw)

        monkeypatch.setattr(fa, "flash_attention", spy)
        monkeypatch.setenv(fa.ENV_FLASH, "1")
        layer = SelfAttentionLayer(n_out=32, n_heads=2, causal=True)
        itype = InputType.recurrent(32, 256)
        params, _ = layer.init(jax.random.key(0), itype)
        x = jnp.asarray(RNG.normal(0, 1, (2, 256, 32)).astype(np.float32))
        y, _ = layer.apply(params, {}, x)
        assert calls.get("hit")
        assert np.all(np.isfinite(np.asarray(y)))


class TestPallasBackward:
    """Round-4: the backward is a Pallas kernel pair (dQ; dK+dV), not a
    lax.scan — these pin the kernels against the blockwise-XLA reference
    backward and the autotune block cache."""

    @pytest.mark.parametrize("causal", [False, True])
    def test_pallas_bwd_matches_xla_bwd(self, causal, monkeypatch):
        import jax
        import jax.numpy as jnp

        from deeplearning4j_tpu.ops import flash_attention as fa

        rng = np.random.default_rng(0)
        b, t, h, d = 2, 256, 2, 32
        q, k, v = (
            jnp.asarray(rng.normal(0, 1, (b, t, h, d)).astype(np.float32))
            for _ in range(3)
        )

        def loss(q, k, v):
            out = fa.flash_attention(q, k, v, causal=causal, interpret=True,
                                     mxu_f32=True)
            return jnp.sum(out * (1 + jnp.arange(d, dtype=jnp.float32)))

        g_pallas = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        monkeypatch.setenv("DL4JTPU_FLASH_BWD", "xla")
        g_xla = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        for gp, gx, name in zip(g_pallas, g_xla, "qkv"):
            np.testing.assert_allclose(
                np.asarray(gp), np.asarray(gx), atol=2e-4, rtol=1e-3,
                err_msg=f"d{name} pallas/xla backward drift",
            )

    def test_block_cache_consulted(self):
        from deeplearning4j_tpu.ops import flash_attention as fa

        fa._BLOCK_CACHE[(128, 128, 16, False)] = (64, 64)
        try:
            assert fa._block_choice(128, 128, 16, False, None, None) == (64, 64)
            # other shapes unaffected: the shape's own default, here the
            # largest rung that divides 256
            assert fa._block_choice(256, 256, 16, False, None, None) == (256, 256)
            # explicit caller blocks always beat the cache
            assert fa._block_choice(128, 128, 16, False, 128, 128) == (128, 128)
        finally:
            fa._BLOCK_CACHE.clear()

    def test_env_block_override(self, monkeypatch):
        from deeplearning4j_tpu.ops import flash_attention as fa

        monkeypatch.setenv("DL4JTPU_FLASH_BLOCK", "64,32")
        assert fa._block_choice(512, 512, 64, True, None, None) == (64, 32)
        # non-tiling or malformed env values fall through, never crash
        # — to the shape's default: one 512 block a side
        monkeypatch.setenv("DL4JTPU_FLASH_BLOCK", "96,96")
        assert fa._block_choice(512, 512, 64, True, None, None) == (512, 512)
        monkeypatch.setenv("DL4JTPU_FLASH_BLOCK", "256")
        assert fa._block_choice(512, 512, 64, True, None, None) == (512, 512)

    @pytest.mark.parametrize("shape, want", [
        # (t_q, t_k, d, operand bytes, out bytes) -> (block_q, block_k)
        ((2048, 2048, 128, 2, 4), (1024, 1024)),    # the train cells' call
        ((2048, 2048, 128, 2, 2), (1024, 1024)),    # bf16 activations
        ((2560, 2560, 128, 2, 4), (512, 512)),      # 1024 does not divide
        ((640, 640, 128, 2, 4), (128, 128)),        # only 128 divides
        ((2048, 3072, 128, 2, 4), (1024, 1024)),    # cross lengths
        ((256, 384, 64, 2, 4), (256, 128)),         # each axis its own rung
        ((64, 64, 64, 2, 4), (64, 64)),             # under 128: one block
        ((2048, 2048, 512, 2, 4), (512, 1024)),     # wide heads: VMEM budget
        ((2048, 2048, 256, 4, 4), (512, 1024)),     # f32 MXU operands: ditto
    ])
    def test_default_blocks_follow_the_shape(self, shape, want):
        got = fa._default_blocks(*shape)
        assert got == want
        assert shape[0] % got[0] == 0 and shape[1] % got[1] == 0
        assert fa._vmem_bytes(*got, *shape[2:]) <= fa._VMEM_BUDGET

    def test_autotune_raises_when_no_candidate_compiles(self, caplog):
        """The compiled (non-interpret) kernel cannot build on the CPU
        backend: every candidate is refused, each refusal is logged, and
        the search raises instead of returning default blocks."""
        import logging

        from deeplearning4j_tpu.ops import flash_attention as fa

        with caplog.at_level(logging.WARNING, logger="deeplearning4j_tpu"):
            with pytest.raises(RuntimeError, match="no candidate"):
                fa.flash_autotune(seq_len=128, n_heads=1, head_dim=16,
                                  candidates=((128, 128),), reps=1)
        assert sum("refused" in r.message for r in caplog.records) == 1
        assert (128, 128, 16, True) not in fa._BLOCK_CACHE
