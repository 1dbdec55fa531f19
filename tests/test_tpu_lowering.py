"""Cross-lower every Pallas kernel for TPU from the CPU, at the full-width
shapes `chip_smoke.py` runs.

`jax.export.export(jax.jit(f), platforms=["tpu"])` runs Pallas' Mosaic
LOWERING without a chip.  It is the check that found the paged-attention
kernel's batched in-kernel einsum (no TPU lowering) while that kernel had
only ever run in interpret mode; it keeps a later kernel change from
shipping something the lowering refuses.  It proves lowering only — the
Mosaic compile inside libtpu (VMEM limits, layouts) and the numerics are
`chip_smoke.py`'s kernels phase, on the chip.
"""

import jax
import jax.numpy as jnp
import pytest

from deeplearning4j_tpu.ops import dequant_matmul as dm
from deeplearning4j_tpu.ops import dsa_prefill_attention as dpa
from deeplearning4j_tpu.ops import flash_attention as fa
from deeplearning4j_tpu.ops import paged_attention as pa
from deeplearning4j_tpu.ops import shared_kv_attention as skv

# the smoke's shapes: flash (B, T, H, D); paged S slots of H x Dh heads
# over 16-row pages, 34 pages/seq; dequant (M, K) @ (K, N)
B, T, H, D = 4, 2048, 8, 128
S, PS, MP, P = 8, 16, 34, 300
K, N = 1024, 4096


def lower_for_tpu(f, *specs):
    exported = jax.export.export(jax.jit(f), platforms=["tpu"])(*specs)
    # a Pallas TPU kernel reaches the module as a Mosaic custom call
    assert "tpu_custom_call" in exported.mlir_module()
    return exported


def sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def test_flash_forward_bf16():
    q = sds((B, T, H, D), jnp.bfloat16)
    lower_for_tpu(lambda q, k, v: fa.flash_attention(q, k, v, causal=True),
                  q, q, q)


def test_flash_backward_bf16():
    q = sds((B, T, H, D), jnp.bfloat16)

    def loss(q, k, v):
        out = fa.flash_attention(q, k, v, causal=True)
        return jnp.sum(out.astype(jnp.float32))

    lower_for_tpu(jax.grad(loss, argnums=(0, 1, 2)), q, q, q)


@pytest.mark.parametrize("t, dtype", [
    (2048, jnp.float32),        # the train cells' call: blocks 1024 x 1024
    (2560, jnp.float32),        # 1024 does not divide: 512 x 512
    (2176, jnp.bfloat16),       # 17 x 128: the bottom of the ladder
])
def test_flash_step_is_three_kernels_in_the_callers_dtype(t, dtype):
    """Forward, dQ and dK+dV: three Mosaic calls per attention layer (the
    benchmark's roofline reader counts steps by them), whatever the
    tiling; bf16 goes in as operands, the caller's dtype comes out."""
    q = sds((2, t, H, D), dtype)

    def loss(q, k, v):
        out = fa.flash_attention(q, k, v, causal=True)
        return jnp.sum(out.astype(jnp.float32))

    exported = lower_for_tpu(jax.grad(loss, argnums=(0, 1, 2)), q, q, q)
    assert exported.mlir_module().count("@tpu_custom_call") == 3
    assert [a.dtype for a in exported.out_avals] == [dtype] * 3


@pytest.mark.parametrize("kv_dtype", ["f32", "int8"])
def test_paged_attention(kv_dtype):
    store = jnp.int8 if kv_dtype == "int8" else jnp.float32
    specs = [sds((S, H, D), jnp.float32), sds((P, PS, H, D), store),
             sds((P, PS, H, D), store), sds((S, MP), jnp.int32),
             sds((S,), jnp.int32)]
    if kv_dtype == "int8":
        specs += [sds((P, PS, H), jnp.float32)] * 2

    def f(q, k, v, tbl, lens, ks=None, vs=None):
        return pa.paged_attention(q, k, v, tbl, lens, k_scale=ks, v_scale=vs,
                                  impl="pallas", interpret=False)

    lower_for_tpu(f, *specs)


def test_paged_attention_chunk_c5():
    """The speculative verify route: C=5 queries per slot ride the decode
    kernel by pseudo-slot expansion."""
    c = 5

    def f(q, k, v, tbl, attend):
        return pa.paged_attention_chunk(q, k, v, tbl, attend, impl="pallas",
                                        interpret=False)

    lower_for_tpu(f, sds((S, c, H, D), jnp.float32),
                  sds((P, PS, H, D), jnp.float32),
                  sds((P, PS, H, D), jnp.float32),
                  sds((S, MP), jnp.int32), sds((S, c), jnp.int32))


LAYERS = 3


@pytest.mark.parametrize("kv_dtype", ["f32", "int8"])
def test_paged_attention_layer_indexed(kv_dtype):
    """What the serving step calls: the whole (L, P, ps, H, Dh) stack is
    the kernel's operand and the static layer is a block index."""
    store = jnp.int8 if kv_dtype == "int8" else jnp.float32
    specs = [sds((S, H, D), jnp.float32), sds((LAYERS, P, PS, H, D), store),
             sds((LAYERS, P, PS, H, D), store), sds((S, MP), jnp.int32),
             sds((S,), jnp.int32)]
    if kv_dtype == "int8":
        specs += [sds((LAYERS, P, PS, H), jnp.float32)] * 2

    def f(q, k, v, tbl, lens, ks=None, vs=None):
        return pa.paged_attention(q, k, v, tbl, lens, k_scale=ks, v_scale=vs,
                                  layer=LAYERS - 1, impl="pallas",
                                  interpret=False)

    lower_for_tpu(f, *specs)


def test_paged_attention_chunk_c5_layer_indexed():
    c = 5

    def f(q, k, v, tbl, attend):
        return pa.paged_attention_chunk(q, k, v, tbl, attend, layer=1,
                                        impl="pallas", interpret=False)

    lower_for_tpu(f, sds((S, c, H, D), jnp.float32),
                  sds((LAYERS, P, PS, H, D), jnp.float32),
                  sds((LAYERS, P, PS, H, D), jnp.float32),
                  sds((S, MP), jnp.int32), sds((S, c), jnp.int32))


@pytest.mark.parametrize("c", [1, 5], ids=["step", "verify_c5"])
def test_paged_call_is_one_kernel_at_serve_chat(c):
    """The `serve_chat` cell's call — 16 slots x 80 pages over the whole
    (24, 650, 16, 16, 128) f32 pool, a middle layer — is ONE Mosaic call,
    the plain step's and the c = 5 verify chunk's alike: the benchmark's
    roofline reader counts decode steps as Pallas calls / n_layer."""
    slots, pages = 16, 80
    pool = sds((24, 650, 16, 16, 128), jnp.float32)

    def f(q, k, v, tbl, attend):
        return pa.paged_attention_chunk(q, k, v, tbl, attend, layer=11,
                                        impl="pallas", interpret=False)

    exported = lower_for_tpu(
        f, sds((slots, c, 16, 128), jnp.float32), pool, pool,
        sds((slots, pages), jnp.int32), sds((slots, c), jnp.int32))
    assert exported.mlir_module().count("@tpu_custom_call") == 1


def test_shared_kv_attn_is_one_kernel_at_phi4flash_reason_sat():
    """The `phi4flash_reason_sat` cell's call — 32 slots of 20 query pairs x
    64 over the shared (1, 3585, 64, 2560) bf16 pool of 10 key pairs and
    their values, 112 pages a slot — is ONE Mosaic call named
    `shared_kv_attn` (the roofline reader counts decode steps as its calls
    over the layers that read the pool)."""
    exported = lower_for_tpu(
        lambda q, pool, tbl, lens: skv.shared_kv_attention(
            q, pool, tbl, lens, kv_pairs=10, impl="pallas",
            interpret=False),
        sds((32, 20, 2, 64), jnp.bfloat16),
        sds((1, 3585, 64, 2560), jnp.bfloat16),
        sds((32, 112), jnp.int32), sds((32,), jnp.int32))
    text = exported.mlir_module()
    assert text.count("@tpu_custom_call") == 1
    assert "shared_kv_attn" in text
    assert [a.shape for a in exported.out_avals] == [(32, 20, 2, 128)]


@pytest.mark.parametrize("ci", [0, 5])
def test_dsa_prefill_attn_is_one_kernel_at_glm52_longdoc_sat(ci):
    """The `glm52_longdoc_sat` cell's prefill chunks 0 and 5 — 2,048
    queries of 64 heads x (192 + 64) over a context of 2,048 or 12,288
    latent rows (stored 640 wide), under the (2,048, context) selection —
    attend in ONE Mosaic call named `dsa_prefill_attn` a layer (the
    busy-share reader finds it by that name), bf16 in and out."""
    import types

    ctx = (ci + 1) * 2048
    cfg = types.SimpleNamespace(
        n_heads=64, qk_nope_head_dim=192, qk_rope_head_dim=64,
        v_head_dim=256, kv_lora_rank=512, softmax_scale=256 ** -0.5)
    exported = lower_for_tpu(
        lambda q, lat, wkvb, mask: dpa.dsa_prefill_attention(
            cfg, q, lat, wkvb, *dpa.carry(cfg, mask, "pallas"),
            interpret=False),
        sds((2048, 64, 256), jnp.bfloat16), sds((ctx, 640), jnp.bfloat16),
        sds((512, 64 * 448), jnp.bfloat16), sds((2048, ctx), jnp.bool_))
    text = exported.mlir_module()
    assert text.count("@tpu_custom_call") == 1
    assert "dsa_prefill_attn" in text
    assert [(a.shape, a.dtype) for a in exported.out_avals] == [
        ((2048, 64 * 256), jnp.bfloat16)]


def _motif3_layer():
    """The `motif3_mixed_sat` cell's attention widths: 80 query heads (64
    signal + 16 noise) over 16 key/value groups, nope 128 + rope 64,
    values 128, latent 512."""
    import types

    return types.SimpleNamespace(
        n_heads=80, groups=16, signal_heads=64, n_noise_heads=16,
        qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
        kv_lora_rank=512, softmax_scale=192 ** -0.5)


@pytest.mark.parametrize("ctx", [2048, 32768, 2560],
                         ids=["full_chunk0", "full_chunk15", "window"])
def test_grouped_dsa_prefill_attn_is_one_kernel_at_motif3_mixed_sat(ctx):
    """The cell's prefill chunks: 2,048 queries over the full layer's
    context (chunk 0 and chunk 15, rows stored 640 wide) and over a window
    layer's ring padded to a tile plus the chunk (2,560 rows of 576), each
    in ONE Mosaic call named `dsa_prefill_attn` (the busy-share reader
    finds it by that name), bf16 in and out, back in head order."""
    cfg = _motif3_layer()
    width = 576 if ctx == 2560 else 640
    exported = lower_for_tpu(
        lambda q, lat, wkvb, mask: dpa.dsa_prefill_attention(
            cfg, q, lat, wkvb, *dpa.carry(cfg, mask, "pallas"),
            interpret=False),
        sds((2048, 80, 192), jnp.bfloat16), sds((ctx, width), jnp.bfloat16),
        sds((512, 16 * 256), jnp.bfloat16), sds((2048, ctx), jnp.bool_))
    text = exported.mlir_module()
    assert text.count("@tpu_custom_call") == 1
    assert "dsa_prefill_attn" in text
    assert [(a.shape, a.dtype) for a in exported.out_avals] == [
        ((2048, 80 * 128), jnp.bfloat16)]


def test_latent_paged_attn_is_one_kernel_at_motif3_mixed_sat():
    """The cell's decode step: 32 slots of 80 absorbed queries (576 wide)
    over the full layer's latent pool (16,897 pages of 64 rows stored 640
    wide) in ONE Mosaic call named `latent_paged_attn`, f32 latent outputs."""
    from deeplearning4j_tpu.ops import latent_paged_attention as lpa

    exported = lower_for_tpu(
        lambda qc, pool, tbl, lens: lpa.latent_paged_attention(
            qc, pool, tbl, lens, layer=0, lk=512, impl="pallas",
            interpret=False),
        sds((32, 80, 576), jnp.bfloat16),
        sds((1, 16897, 64, 640), jnp.bfloat16), sds((32, 528), jnp.int32),
        sds((32,), jnp.int32))
    text = exported.mlir_module()
    assert text.count("@tpu_custom_call") == 1
    assert "latent_paged_attn" in text
    assert [(a.shape, a.dtype) for a in exported.out_avals] == [
        ((32, 80, 512), jnp.float32)]


@pytest.mark.parametrize("m, g, k, n, out", [
    (256, 48, 4096, 1280, jnp.float32),    # motif3: gate and up
    (256, 48, 1280, 4096, jnp.float32),    # motif3: down
    (64, 16, 6144, 2048, None),            # glm52: gate and up, bf16 out
    (64, 16, 2048, 6144, None),            # glm52: down
], ids=["motif3_up", "motif3_down", "glm52_up", "glm52_down"])
def test_held_gmm_is_one_kernel_at_the_decode_steps(m, g, k, n, out):
    """A decode step's grouped expert product (32 or 8 slots x top-8 rows
    over the held experts' stack) in ONE Mosaic call named `held_gmm`."""
    from deeplearning4j_tpu.ops import held_expert_matmul as hgm

    exported = lower_for_tpu(
        lambda x, w, s: hgm.held_gmm(x, w, s, out, interpret=False),
        sds((m, k), jnp.bfloat16), sds((g, k, n), jnp.bfloat16),
        sds((g,), jnp.int32))
    text = exported.mlir_module()
    assert text.count("@tpu_custom_call") == 1
    assert "held_gmm" in text
    assert [(a.shape, a.dtype) for a in exported.out_avals] == [
        ((m, n), out or jnp.bfloat16)]


@pytest.mark.parametrize("m", [1, 8, 256])
def test_dequant_matmul(m):
    lower_for_tpu(
        lambda x, q, s: dm.dequant_matmul(x, q, s, impl="pallas",
                                          interpret=False),
        sds((m, K), jnp.float32), sds((K, N), jnp.int8),
        sds((N,), jnp.float32))


def test_flash_under_a_data_mesh_runs_per_shard():
    """GSPMD cannot partition a Mosaic kernel: a data-parallel step that
    reaches the flash kernel must wrap it per shard (found on four real
    chips — the interpret-mode CPU runs lower to plain XLA ops and never
    hit it).  The bare kernel under sharded inputs is refused at
    lowering; `ops.attention`'s per-shard wrapper lowers."""
    import functools

    from jax.sharding import NamedSharding, PartitionSpec as P

    from deeplearning4j_tpu.ops.attention import _flash_per_shard
    from deeplearning4j_tpu.runtime.mesh import (
        MeshSpec, active_mesh_scope, make_mesh,
    )

    mesh = make_mesh(MeshSpec.of(data=4), jax.devices()[:4])
    q = jax.ShapeDtypeStruct((16, T, H, D), jnp.bfloat16,
                             sharding=NamedSharding(mesh, P("data")))
    flash = functools.partial(fa.flash_attention, causal=True,
                              interpret=False)
    with pytest.raises(NotImplementedError, match="partitioned"):
        jax.export.export(jax.jit(flash), platforms=["tpu"])(q, q, q)
    def loss(q, k, v):
        return jnp.sum(_flash_per_shard(flash, q, k, v).astype(jnp.float32))

    with active_mesh_scope(mesh):
        exported = lower_for_tpu(jax.grad(loss, argnums=(0, 1, 2)), q, q, q)
    assert exported.nr_devices == 4
