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


class TestBf16Default:
    def test_bf16_kernel_within_bf16_tolerance(self):
        q, k, v = qkv(t=256)
        dense = mha(q, k, v, causal=True)
        flash = fa.flash_attention(q, k, v, causal=True, interpret=True)
        np.testing.assert_allclose(
            np.asarray(flash), np.asarray(dense), rtol=3e-2, atol=3e-2
        )


class TestGradientParity:
    @pytest.mark.parametrize("causal", [False, True])
    def test_grads_match_dense(self, causal):
        q, k, v = qkv(b=1, t=128, h=2, d=32)

        def loss_flash(q, k, v):
            return jnp.sum(
                fa.flash_attention(q, k, v, causal=causal, interpret=True,
                                   mxu_f32=True) ** 2
            )

        def loss_dense(q, k, v):
            return jnp.sum(mha(q, k, v, causal=causal) ** 2)

        gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gf, gd):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=3e-3, atol=3e-4
            )


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
            # other shapes unaffected
            assert fa._block_choice(256, 256, 16, False, None, None) == (128, 128)
            # explicit caller blocks always beat the cache
            assert fa._block_choice(128, 128, 16, False, 128, 128) == (128, 128)
        finally:
            fa._BLOCK_CACHE.clear()

    def test_env_block_override(self, monkeypatch):
        from deeplearning4j_tpu.ops import flash_attention as fa

        monkeypatch.setenv("DL4JTPU_FLASH_BLOCK", "64,32")
        assert fa._block_choice(512, 512, 64, True, None, None) == (64, 32)
        # non-tiling or malformed env values fall through, never crash
        monkeypatch.setenv("DL4JTPU_FLASH_BLOCK", "96,96")
        assert fa._block_choice(512, 512, 64, True, None, None) == (128, 128)
        monkeypatch.setenv("DL4JTPU_FLASH_BLOCK", "256")
        assert fa._block_choice(512, 512, 64, True, None, None) == (128, 128)

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
