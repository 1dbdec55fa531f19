"""Attention layers for the config DSL — the reference's attention surface
(`org.deeplearning4j.nn.conf.layers.SelfAttentionLayer`,
`LearnedSelfAttentionLayer`, and the `multi_head_dot_product_attention`
custom op underneath, SURVEY.md §5.7) made first-class and long-context
capable.

The reference runs attention single-device with O(T^2) memory.  Here every
attention layer carries a `seq_parallel` knob ({"none", "ring", "ulysses"},
the SURVEY §5.7 config-knob requirement): when the model was distribute()'d
onto a mesh with a "seq" axis, the attention core lowers to
`ops/attention.py`'s ring (ppermute KV rotation with online softmax) or
Ulysses (all_to_all head scatter) kernel inside a partial-manual shard_map
(manual over "seq", auto over everything else — GSPMD still handles
data/tensor parallelism around it).  On a single chip or a mesh without a
"seq" axis the same layer lowers to dense fused attention; the config is
scale-portable.

Also here: TransformerEncoderBlock, a pre-LN encoder block (MHA + FFN with
residuals) so a DSL-built transformer is a first-class citizen of the zoo;
and LatentSparseDecoder, the `glm_moe_dsa` family's decoder (latent
attention, a learned sparse selection shared between layers, sigmoid-routed
experts) as ONE layer, because the selection crosses its blocks.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from deeplearning4j_tpu.nn.activations import Activation
from deeplearning4j_tpu.nn.conf.input_type import InputType
from deeplearning4j_tpu.nn.conf.layers import (
    LayerConfig,
    LayerNorm,
    _coerce_enum,
    _dropout,
)
from deeplearning4j_tpu.nn.weights import WeightInit
from deeplearning4j_tpu.quant import functional as quantf
from deeplearning4j_tpu.ops.attention import mha, ring_attention, ulysses_attention
from deeplearning4j_tpu.runtime.mesh import SEQ_AXIS, active_mesh, shard_map
from deeplearning4j_tpu.utils import serde

_SEQ_MODES = ("none", "ring", "ulysses")


def _seq_axis_active(mesh) -> bool:
    return (
        mesh is not None
        and SEQ_AXIS in mesh.axis_names
        and mesh.shape[SEQ_AXIS] > 1
    )


def _attend(q, k, v, *, causal: bool, mask, seq_parallel: str):
    """Dispatch the attention core: dense on one shard, ring/ulysses under a
    partial-manual shard_map when a "seq" mesh axis is active.

    q,k,v: (B, T, H, Dh).  mask: (B, T) keep-mask over keys or None.
    """
    if seq_parallel not in _SEQ_MODES:
        raise ValueError(
            f"seq_parallel={seq_parallel!r}; options: {_SEQ_MODES}"
        )
    mesh = active_mesh()
    if seq_parallel == "none" or not _seq_axis_active(mesh):
        return mha(q, k, v, causal=causal, mask=mask)

    n = mesh.shape[SEQ_AXIS]
    if q.shape[1] % n:
        raise ValueError(
            f"sequence length {q.shape[1]} not divisible by seq axis size {n}"
        )
    if seq_parallel == "ulysses" and q.shape[2] % n:
        raise ValueError(
            f"ulysses needs heads ({q.shape[2]}) divisible by seq axis ({n})"
        )
    core = ring_attention if seq_parallel == "ring" else ulysses_attention
    spec = P(None, SEQ_AXIS)
    if mask is not None:
        fn = lambda q, k, v, m: core(q, k, v, axis=SEQ_AXIS, causal=causal, mask=m)
        return shard_map(
            fn,
            mesh=mesh,
            in_specs=(spec, spec, spec, spec),
            out_specs=spec,
            axis_names={SEQ_AXIS},
            check_vma=False,
        )(q, k, v, mask)
    fn = lambda q, k, v: core(q, k, v, axis=SEQ_AXIS, causal=causal)
    return shard_map(
        fn,
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        axis_names={SEQ_AXIS},
        check_vma=False,
    )(q, k, v)


def resolve_head_size(n_out: int, n_heads: int, head_size) -> int:
    """Explicit head_size wins; otherwise n_out must split evenly over
    heads.  Shared by SelfAttentionLayer / LearnedSelfAttentionLayer /
    AttentionVertex so head-size semantics can't drift between them."""
    if head_size is not None:
        return head_size
    if n_out % n_heads:
        raise ValueError(f"n_out {n_out} not divisible by n_heads {n_heads}")
    return n_out // n_heads


def init_qkv_params(key, wi: WeightInit, n_in_q: int, n_in_k: int, n_in_v: int,
                    hd: int, n_out: int) -> dict:
    """Wq/Wk/Wv projections into n_heads*head_size (=hd) + Wo back out —
    shared by SelfAttentionLayer and AttentionVertex."""
    kq, kk, kv, ko = jax.random.split(key, 4)
    return {
        "Wq": wi.init(kq, (n_in_q, hd), fan_in=n_in_q, fan_out=hd),
        "Wk": wi.init(kk, (n_in_k, hd), fan_in=n_in_k, fan_out=hd),
        "Wv": wi.init(kv, (n_in_v, hd), fan_in=n_in_v, fan_out=hd),
        "Wo": wi.init(ko, (hd, n_out), fan_in=hd, fan_out=n_out),
    }


def apply_qkv_attention(params, xq, xk, xv, *, n_heads: int, head_size: int,
                        project_input: bool, causal: bool, mask,
                        seq_parallel: str):
    """Project (when project_input), attend, merge heads, project out.
    xq/xk/xv: (B, T*, F) — identical arrays for self-attention."""
    b, tq = xq.shape[0], xq.shape[1]
    h, dh = n_heads, head_size
    dt = xq.dtype
    if project_input:
        q = quantf.matmul(xq, params["Wq"]).reshape(b, tq, h, dh)
        k = quantf.matmul(xk, params["Wk"]).reshape(b, xk.shape[1], h, dh)
        v = quantf.matmul(xv, params["Wv"]).reshape(b, xv.shape[1], h, dh)
    else:
        q = xq.reshape(b, tq, h, dh)
        k = xk.reshape(b, xk.shape[1], h, dh)
        v = xv.reshape(b, xv.shape[1], h, dh)
    out = _attend(q, k, v, causal=causal, mask=mask, seq_parallel=seq_parallel)
    out = out.reshape(b, tq, h * dh)
    if project_input:
        out = quantf.matmul(out, params["Wo"])
    return out


@serde.register
@dataclasses.dataclass(frozen=True)
class SelfAttentionLayer(LayerConfig):
    """Multi-head self-attention over a sequence (SelfAttentionLayer role).

    project_input=True (the useful case): learned Wq/Wk/Wv projections into
    n_heads*head_size, attention, then Wo back out to n_out.
    project_input=False mirrors the reference's constraint: the input is
    used directly as q=k=v, requiring n_in == n_heads*head_size == n_out.
    """

    n_out: int = 0
    n_heads: int = 1
    head_size: Optional[int] = None       # default: n_out // n_heads
    project_input: bool = True
    causal: bool = False
    seq_parallel: str = "none"            # none | ring | ulysses

    EXPECTS = "rnn"
    ACCEPTS_MASK = True
    REGULARIZED = ("Wq", "Wk", "Wv", "Wo")

    def _head_size(self) -> int:
        return resolve_head_size(self.n_out, self.n_heads, self.head_size)

    def output_type(self, itype: InputType) -> InputType:
        if not self.project_input and itype.size != self.n_out:
            raise ValueError(
                "project_input=False requires n_in == n_out "
                f"(got {itype.size} vs {self.n_out})"
            )
        return InputType.recurrent(self.n_out, itype.shape[0])

    def init(self, key, itype):
        if not self.project_input:
            if itype.size != self.n_heads * self._head_size():
                raise ValueError(
                    "project_input=False requires n_in == n_heads*head_size "
                    f"(got {itype.size} vs {self.n_heads}*{self._head_size()})"
                )
            return {}, {}
        n_in, hd = itype.size, self.n_heads * self._head_size()
        wi = self._winit(WeightInit.XAVIER)
        return init_qkv_params(key, wi, n_in, n_in, n_in, hd, self.n_out), {}

    def apply(self, params, state, x, *, training=False, rng=None, mask=None):
        x = _dropout(x, self.dropout_rate or 0.0, training, rng)
        out = apply_qkv_attention(
            params, x, x, x,
            n_heads=self.n_heads,
            head_size=self._head_size(),
            project_input=self.project_input,
            causal=self.causal,
            mask=mask,
            seq_parallel=self.seq_parallel,
        )
        return self._act()(out), state


@serde.register
@dataclasses.dataclass(frozen=True)
class LearnedSelfAttentionLayer(LayerConfig):
    """Attention with n_queries LEARNED query vectors
    (LearnedSelfAttentionLayer role): output is (B, n_queries, n_out),
    independent of input length — a trainable sequence-pooling layer.

    Sequence parallelism does not apply (queries are a small learned set,
    not a sharded sequence); keys/values are consumed dense.
    """

    n_out: int = 0
    n_heads: int = 1
    n_queries: int = 1
    head_size: Optional[int] = None

    EXPECTS = "rnn"
    ACCEPTS_MASK = True
    REGULARIZED = ("Wk", "Wv", "Wo", "Q")

    def _head_size(self) -> int:
        return resolve_head_size(self.n_out, self.n_heads, self.head_size)

    def output_type(self, itype: InputType) -> InputType:
        return InputType.recurrent(self.n_out, self.n_queries)

    def init(self, key, itype):
        n_in, hd = itype.size, self.n_heads * self._head_size()
        kq, kk, kv, ko = jax.random.split(key, 4)
        wi = self._winit(WeightInit.XAVIER)
        return {
            "Q": wi.init(kq, (self.n_queries, hd), fan_in=hd, fan_out=hd),
            "Wk": wi.init(kk, (n_in, hd), fan_in=n_in, fan_out=hd),
            "Wv": wi.init(kv, (n_in, hd), fan_in=n_in, fan_out=hd),
            "Wo": wi.init(ko, (hd, self.n_out), fan_in=hd, fan_out=self.n_out),
        }, {}

    def apply(self, params, state, x, *, training=False, rng=None, mask=None):
        x = _dropout(x, self.dropout_rate or 0.0, training, rng)
        b, t = x.shape[0], x.shape[1]
        h, dh = self.n_heads, self._head_size()
        q = jnp.broadcast_to(
            params["Q"].astype(x.dtype).reshape(1, self.n_queries, h, dh),
            (b, self.n_queries, h, dh),
        )
        k = (x @ params["Wk"].astype(x.dtype)).reshape(b, t, h, dh)
        v = (x @ params["Wv"].astype(x.dtype)).reshape(b, t, h, dh)
        out = mha(q, k, v, mask=mask)
        out = out.reshape(b, self.n_queries, h * dh) @ params["Wo"].astype(x.dtype)
        return self._act()(out), state


@serde.register
@dataclasses.dataclass(frozen=True)
class PositionalEncoding(LayerConfig):
    """Additive position information for attention stacks: sinusoidal
    (parameterless, any length) or learned (max_length x d table)."""

    learned: bool = False
    max_length: int = 0                 # required when learned=True

    EXPECTS = "rnn"
    REGULARIZED = ()

    @property
    def HAS_PARAMS(self):  # type: ignore[override]
        return self.learned

    def init(self, key, itype):
        if not self.learned:
            return {}, {}
        if self.max_length <= 0:
            raise ValueError("learned PositionalEncoding requires max_length")
        d = itype.size
        wi = self._winit(WeightInit.NORMAL)
        return {"P": wi.init(key, (self.max_length, d), fan_in=d, fan_out=d)}, {}

    def apply(self, params, state, x, *, training=False, rng=None):
        t, d = x.shape[1], x.shape[2]
        if self.learned:
            if t > self.max_length:
                raise ValueError(
                    f"sequence length {t} exceeds max_length {self.max_length}"
                )
            return x + params["P"][:t].astype(x.dtype), state
        pos = jnp.arange(t, dtype=jnp.float32)[:, None]
        div = jnp.exp(
            jnp.arange(0, d, 2, dtype=jnp.float32) * (-jnp.log(10000.0) / d)
        )
        pe = jnp.zeros((t, d), jnp.float32)
        pe = pe.at[:, 0::2].set(jnp.sin(pos * div))
        pe = pe.at[:, 1::2].set(jnp.cos(pos * div[: d // 2]))
        return x + pe.astype(x.dtype), state


@serde.register
@dataclasses.dataclass(frozen=True)
class TransformerEncoderBlock(LayerConfig):
    """Pre-LN transformer encoder block:
    x + MHA(LN(x)), then x + FFN(LN(x)) — the standard composition the
    reference could only express op-by-op in SameDiff.  One DSL layer here
    so zoo transformers stack cleanly; inherits the seq_parallel knob.
    """

    d_model: int = 0
    n_heads: int = 1
    d_ff: int = 0                        # default 4*d_model
    causal: bool = False
    seq_parallel: str = "none"
    ffn_activation: Activation = Activation.GELU

    EXPECTS = "rnn"
    ACCEPTS_MASK = True
    REGULARIZED = ()

    def __post_init__(self):
        super().__post_init__()
        object.__setattr__(
            self, "ffn_activation", _coerce_enum(self.ffn_activation, Activation)
        )

    def _attn(self) -> SelfAttentionLayer:
        return SelfAttentionLayer(
            n_out=self.d_model,
            n_heads=self.n_heads,
            causal=self.causal,
            seq_parallel=self.seq_parallel,
            weight_init=self.weight_init,
        )

    def _dff(self) -> int:
        return self.d_ff if self.d_ff > 0 else 4 * self.d_model

    def output_type(self, itype: InputType) -> InputType:
        if itype.size != self.d_model:
            raise ValueError(
                f"TransformerEncoderBlock d_model={self.d_model} but input "
                f"feature size is {itype.size}"
            )
        return InputType.recurrent(self.d_model, itype.shape[0])

    def init(self, key, itype):
        k_attn, k1, k2 = jax.random.split(key, 3)
        ln = LayerNorm()
        attn_p, _ = self._attn().init(k_attn, itype)
        ln1_p, _ = ln.init(None, itype)
        ln2_p, _ = ln.init(None, itype)
        d, dff = self.d_model, self._dff()
        wi = self._winit(WeightInit.XAVIER)
        return {
            "attn": attn_p,
            "ln1": ln1_p,
            "ln2": ln2_p,
            "W1": wi.init(k1, (d, dff), fan_in=d, fan_out=dff),
            "b1": jnp.zeros((dff,), jnp.float32),
            "W2": wi.init(k2, (dff, d), fan_in=dff, fan_out=d),
            "b2": jnp.zeros((d,), jnp.float32),
        }, {}

    def regularizable_params(self, lp):
        out = [lp[p] for p in ("W1", "W2") if p in lp]
        attn = lp.get("attn", {})
        out.extend(attn[p] for p in ("Wq", "Wk", "Wv", "Wo") if p in attn)
        return out

    def apply(self, params, state, x, *, training=False, rng=None, mask=None):
        ln = LayerNorm()
        attn = self._attn()
        r1, r2 = (jax.random.split(rng) if rng is not None else (None, None))
        h, _ = ln.apply(params["ln1"], {}, x)
        h, _ = attn.apply(params["attn"], {}, h, training=training, rng=r1, mask=mask)
        x = x + h
        h, _ = ln.apply(params["ln2"], {}, x)
        h = _dropout(h, self.dropout_rate or 0.0, training, r2)
        h = self.ffn_activation(
            quantf.matmul(h, params["W1"]) + params["b1"].astype(x.dtype)
        )
        h = quantf.matmul(h, params["W2"]) + params["b2"].astype(x.dtype)
        return x + h, state


#: the slot pool of the window layers' latent rings
LATENT_RING = "latent_ring"

#: what a `LatentSparseDecoder` layer's ``indexer_types`` entry may say: it
#: holds an indexer and selects (``full``), attends the selection of the
#: nearest full layer before it (``shared``), or attends every row of its
#: causal window with no selection (``none``)
INDEXER_KINDS = ("full", "shared", "none")

@dataclasses.dataclass(frozen=True)
class LatentBlock:
    """One layer of a `LatentSparseDecoder`, as `ops/latent.latent_block`
    and the serving engine read it: a view with the decoder's widths, this
    layer's kinds and where its parameters sit (``path`` into the model's
    tree).  Not a DSL layer of its own — the selection of a ``"full"``
    layer serves the ``"shared"`` ones after it, so the layers only exist
    together."""

    name: str
    path: tuple
    indexer: str                  # "full" | "shared"
    ffn: str                      # "dense" | "sparse"
    d_model: int
    n_heads: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    rope_theta: float
    rms_eps: float
    index_n_heads: int
    index_head_dim: int
    index_topk: int
    top_k: int
    routed_scale: float
    held_first: int
    causal: bool = True
    window: int = 0               # 0: every row before; else the last n
    n_kv_groups: int = 0          # 0: one key/value group per head
    n_noise_heads: int = 0
    output_gate: bool = False
    ffn_act: str = "silu"         # "silu" | "polynorm"
    polynorm_scale: float = 1.0
    polynorm_clamp: float = 0.5
    residual: str = "plain"       # "plain" | "mhc"
    mhc_streams: int = 1
    mhc_iters: int = 0
    hidden_clamp: float = 0.0
    rope_factor: float = 1.0      # > 1: YaRN frequencies on the full layers
    rope_original: int = 0
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    swa_rope_theta: float = 0.0   # the window layers' theta (0: rope_theta)

    @property
    def softmax_scale(self) -> float:
        return (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5

    @property
    def latent_width(self) -> int:
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def groups(self) -> int:
        """Key/value groups: ``W_kvb`` makes one key and one value each."""
        return self.n_kv_groups or self.n_heads

    @property
    def signal_heads(self) -> int:
        """Query heads whose outputs go on to ``W_o``; the noise heads
        after them are only taken away from them."""
        return self.n_heads - self.n_noise_heads

    @property
    def cache_rows(self) -> dict:
        """What one token caches in this layer's pages: pool name -> row
        width.  A window layer keeps its rows in a ring per stream
        instead (`slot_rows`)."""
        if self.window:
            return {}
        rows = {"latent": self.latent_width}
        if self.indexer == "full":
            rows["index_key"] = self.index_head_dim
        return rows

    @property
    def slot_rows(self) -> dict:
        """What a window layer keeps per stream: a ring of its last
        ``window`` latent rows, row ``p mod window`` holding position p."""
        if not self.window:
            return {}
        return {LATENT_RING: ((self.window, self.latent_width), "kv")}


@serde.register
@dataclasses.dataclass(frozen=True)
class LatentSparseDecoder(LayerConfig):
    """The decoder of the `glm_moe_dsa` family (GLM-5.x; DeepSeek-V3.2's
    attention): per layer ``x += MLA(norm1 x)``, ``x += FFN(norm2 x)`` with
    RMSNorm, no biases, rotary positions on ``qk_rope_head_dim`` of each
    query head and on the one shared rotary key; after the last layer
    ``norm_f``.  Equations and names: `ops/latent.py`, `ops/moe.py`.

    ``indexer_types[i]`` is ``"full"`` (the layer holds an indexer and
    selects each query's ``index_topk`` rows), ``"shared"`` (it attends
    the selection of the nearest full layer before it) or ``"none"`` (no
    selection: every row of its causal window); ``mlp_types[i]`` is
    ``"dense"`` (gated FFN of width ``d_ff``) or ``"sparse"``
    (``n_routed`` sigmoid-routed experts of width ``moe_d_ff``, ``top_k`` a
    token, plus one shared expert).  ``n_held`` > 0 makes this the share
    of ONE chip of an expert-parallel deployment: the router keeps all
    ``n_routed`` outputs and the layer holds, and computes, experts
    ``[held_first, held_first + n_held)`` only.

    The rest of the block's parts are data too, each off by default (the
    `glm_moe_dsa` block): ``windows[i]`` > 0 gives layer i a causal window
    of that many rows (its rows then live in a per-stream ring, not in
    pages); ``n_kv_groups`` makes ``W_kvb`` one key and value per group of
    query heads; ``n_noise_heads`` (one per group) makes the attention
    differential, ``O_i - sigmoid(h W_lam)_i O_noise(group i)``;
    ``output_gate`` multiplies the heads' outputs by ``sigmoid(h W_gate)``
    before ``W_o``; ``ffn_act`` is ``"silu"`` or ``"polynorm"``; ``residual
    "mhc"`` carries ``mhc_streams`` residual streams mixed around every
    sublayer (manifold-constrained hyper-connections, ``mhc_iters``
    Sinkhorn iterations); ``rope_factor`` > 1 gives the full layers YaRN
    frequencies and ``swa_rope_theta`` the window layers their own theta.

    `apply` is the whole-sequence form in plain differentiable
    `jax.numpy` (the selection is a constant of the backward pass);
    the serving engine runs the same blocks against its paged latent
    pool (`ops/generation.block`)."""

    d_model: int = 0
    n_heads: int = 1
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    rope_theta: float = 10000.0
    rms_eps: float = 1e-5
    index_n_heads: int = 1
    index_head_dim: int = 0
    index_topk: int = 0
    indexer_types: tuple[str, ...] = ()
    mlp_types: tuple[str, ...] = ()
    d_ff: int = 0
    moe_d_ff: int = 0
    n_routed: int = 0
    top_k: int = 1
    routed_scale: float = 1.0
    held_first: int = 0
    n_held: int = 0                      # 0: every routed expert
    windows: tuple[int, ...] = ()        # per layer; () or 0: no window
    n_kv_groups: int = 0
    n_noise_heads: int = 0
    output_gate: bool = False
    ffn_act: str = "silu"
    polynorm_scale: float = 1.0
    polynorm_clamp: float = 0.5
    residual: str = "plain"
    mhc_streams: int = 1
    mhc_iters: int = 0
    hidden_clamp: float = 0.0
    rope_factor: float = 1.0
    rope_original: int = 0
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    swa_rope_theta: float = 0.0

    EXPECTS = "rnn"
    REGULARIZED = ()

    def __post_init__(self):
        super().__post_init__()
        for f in ("indexer_types", "mlp_types", "windows"):
            object.__setattr__(self, f, tuple(getattr(self, f)))
        if len(self.indexer_types) != len(self.mlp_types):
            raise ValueError("indexer_types and mlp_types name the same "
                             "layers: their lengths differ")
        if self.windows and len(self.windows) != len(self.indexer_types):
            raise ValueError("windows names the same layers as "
                             "indexer_types: their lengths differ")
        for i, ix in enumerate(self.indexer_types):
            if ix == "shared" and "full" not in self.indexer_types[:i]:
                raise ValueError(
                    f"layer {i} is \"shared\": a shared layer attends the "
                    "selection of a \"full\" layer before it, and there is "
                    "none")
        bad = (set(self.indexer_types) - set(INDEXER_KINDS)
               | set(self.mlp_types) - {"dense", "sparse"}
               | {self.ffn_act} - {"silu", "polynorm"}
               | {self.residual} - {"plain", "mhc"})
        if bad:
            raise ValueError(f"unknown layer kinds {sorted(bad)}")
        if self.qk_rope_head_dim % 2 or ("full" in self.indexer_types and (
                self.index_head_dim < self.qk_rope_head_dim)):
            raise ValueError("qk_rope_head_dim must be even and at most "
                             "index_head_dim")
        g = self.n_kv_groups or self.n_heads
        if (self.n_heads - self.n_noise_heads) % g or (
                self.n_noise_heads not in (0, g)):
            raise ValueError(
                f"{self.n_heads} query heads, {self.n_noise_heads} of them "
                f"noise heads, over {g} key/value groups: the signal heads "
                "must split evenly over the groups, with one noise head "
                "per group or none")
        if any(w and ix != "none" for w, ix in zip(self.windows,
                                                   self.indexer_types)):
            raise ValueError("a window layer selects nothing: its "
                             "indexer_types entry must be \"none\"")

    def _held(self) -> int:
        return self.n_held or self.n_routed

    @property
    def stream_width(self) -> int:
        """Width of a row between the layers: the residual streams side by
        side (``mhc``), or the model width."""
        return self.d_model * (self.mhc_streams if self.residual == "mhc"
                               else 1)

    def blocks(self) -> tuple:
        shared = {f.name: getattr(self, f.name)
                  for f in dataclasses.fields(LatentBlock)
                  if f.name in {g.name for g in dataclasses.fields(self)}
                  and f.name != "name"}
        windows = self.windows or (0,) * len(self.indexer_types)
        return tuple(
            LatentBlock(name=f"{self.name}.layer{i:02d}",
                        path=(self.name, f"layer{i:02d}"),
                        indexer=ix, ffn=mlp, window=int(w), **shared)
            for i, (ix, mlp, w) in enumerate(zip(
                self.indexer_types, self.mlp_types, windows)))

    def output_type(self, itype: InputType) -> InputType:
        if itype.size != self.d_model:
            raise ValueError(
                f"LatentSparseDecoder d_model={self.d_model} but input "
                f"feature size is {itype.size}")
        return InputType.recurrent(self.d_model, itype.shape[0])

    def init(self, key, itype):
        wi = self._winit(WeightInit.LECUN_NORMAL)
        d, h_ = self.d_model, self.n_heads
        dq, lk = self.q_lora_rank, self.kv_lora_rank
        dn, dr, dv = (self.qk_nope_head_dim, self.qk_rope_head_dim,
                      self.v_head_dim)
        hi, di = self.index_n_heads, self.index_head_dim
        ones = lambda n: jnp.ones((n,), jnp.float32)

        def mat(k, *shape):
            return wi.init(k, shape, fan_in=shape[-2], fan_out=shape[-1])

        g_, hs = self.n_kv_groups or h_, h_ - self.n_noise_heads

        def ffn(k, width, lead=()):
            kg, ku, kd = jax.random.split(k, 3)
            out = {"Wg": mat(kg, *lead, d, width),
                   "Wu": mat(ku, *lead, d, width),
                   "Wd": mat(kd, *lead, width, d)}
            if self.ffn_act == "polynorm":
                # PolyNorm's weights start at 1/3 each, its bias at 0
                out["poly_w"] = jnp.full(lead + (3,), 1 / 3, jnp.float32)
                out["poly_b"] = jnp.zeros(lead, jnp.float32)
            return out

        def mhc(k):
            s = self.mhc_streams
            w, m = s * d, s * (2 + s)
            return {"gamma": ones(w), "phi": mat(k, w, m),
                    "alpha": ones(3), "b": jnp.zeros((m,), jnp.float32)}

        params = {"norm_f": ones(d)}
        for cfg, k in zip(self.blocks(),
                          jax.random.split(key, len(self.mlp_types))):
            ka, ki, kf = jax.random.split(k, 3)
            k1, k2, k3, k4, k5 = jax.random.split(ka, 5)
            lp = {"norm1": ones(d), "norm2": ones(d), "attn": {
                "Wqa": mat(k1, d, dq), "q_norm": ones(dq),
                "Wqb": mat(k2, dq, h_ * (dn + dr)),
                "Wkva": mat(k3, d, lk + dr), "kv_norm": ones(lk),
                "Wkvb": mat(k4, lk, g_ * (dn + dv)),
                "Wo": mat(k5, hs * dv, d)}}
            # the parts GLM's block lacks draw from keys of their own, so
            # its weights are the same whether they exist or not
            kx = jax.random.split(jax.random.fold_in(k, 1), 4)
            if self.n_noise_heads:
                lp["attn"]["W_lam"] = mat(kx[0], d, hs)
            if self.output_gate:
                lp["attn"]["W_gate"] = mat(kx[1], d, hs * dv)
            if self.residual == "mhc":
                lp["mhc_attn"], lp["mhc_ffn"] = mhc(kx[2]), mhc(kx[3])
            if cfg.indexer == "full":
                k1, k2, k3 = jax.random.split(ki, 3)
                lp["indexer"] = {
                    "Wq": mat(k1, dq, hi * di), "Wk": mat(k2, d, di),
                    "k_gamma": ones(di),
                    "k_beta": jnp.zeros((di,), jnp.float32),
                    "Ww": mat(k3, d, hi)}
            if cfg.ffn == "dense":
                lp["ffn"] = ffn(kf, self.d_ff)
            else:
                kr, ke, ks = jax.random.split(kf, 3)
                lp["ffn"] = {
                    "router": mat(kr, d, self.n_routed),
                    "router_bias": jnp.zeros((self.n_routed,), jnp.float32),
                    "experts": ffn(ke, self.moe_d_ff, (self._held(),)),
                    "shared": ffn(ks, self.moe_d_ff)}
            params[cfg.path[-1]] = lp
        return params, {}

    def apply(self, params, state, x, *, training=False, rng=None):
        from deeplearning4j_tpu.ops.latent import (
            LatentRows, final_rows, first_rows, latent_block,
            sequence_attend,
        )

        def one(x):
            rows = LatentRows(sequence_attend(), jnp.arange(x.shape[0]))
            x = first_rows(self, x)
            for cfg in self.blocks():
                x = latent_block(cfg, params[cfg.path[-1]], x, rows)
            return final_rows(self, params, x)

        # one sequence at a time: the grouped product has no batch rule
        return jax.lax.map(one, x), state
