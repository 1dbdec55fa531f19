"""The decoder-hybrid-decoder of the `phi4flash` family (SambaY), as ONE layer
of the config DSL: its layers read each other's state (a gated memory unit
reads the memory a Mamba layer left, a cross-attention layer reads the one
full-attention layer's keys and values), so they only exist together, as
`LatentSparseDecoder`'s do.

Per layer ``l``: ``x += mixer_l(LN1 x)``; ``x += W2 (silu(g) * u)`` with
``[g; u] = LN2(x) W1``; after the last layer ``LN_f``, and the logits are
``LN_f(h) E^T`` with the embedding's own matrix (the head is tied).  Every
norm is a LayerNorm with a bias, no projection has one.  The mixers, by
``layer_types[l]``:

- ``"mamba"``: Mamba-1 (selective state space) — `ops/hybrid.py`;
- ``"swa"``: differential attention with grouped keys and values over the
  last ``window`` positions;
- ``"full"``: the same over the whole causal context — the only keys and
  values the model keeps for every position;
- ``"gmu"``: a gated memory unit, ``(M * silu(h W_1)) W_2``, M the memory
  of the last Mamba layer before the full one (``y * silu(z)``, its output
  projection's input);
- ``"cross"``: differential attention of its own queries over the full
  layer's keys and values.

Mamba and window layers come before the full layer (the self-decoder), the
gated memory units and cross-attention layers after it (the cross-decoder).
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn.conf.input_type import InputType
from deeplearning4j_tpu.nn.conf.layers import LayerConfig
from deeplearning4j_tpu.nn.weights import WeightInit
from deeplearning4j_tpu.utils import serde

HYBRID_KINDS = ("mamba", "swa", "full", "gmu", "cross")
_SELF, _CROSS = ("mamba", "swa"), ("gmu", "cross")
#: the serving pools a hybrid stack's state lives in, by name: per stream a
#: Mamba layer's scan state and conv inputs and a window layer's ring; per
#: position the full layer's keys and values (`ops/hybrid.py` reads and
#: writes them)
SSM, CONV, RING, SHARED_KV = "ssm", "conv", "ring", "kv"


@dataclasses.dataclass(frozen=True)
class HybridBlock:
    """One layer of a `HybridDecoder`, as `ops/hybrid.hybrid_block` and the
    serving engine read it: the decoder's widths, this layer's kind, its
    index in the stack (the differential attention's lambda schedule) and
    where its parameters sit (``path`` into the model's tree)."""

    name: str
    path: tuple
    kind: str
    index: int
    tap: bool                     # the Mamba layer whose memory the GMUs read
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    window: int
    d_inner: int
    d_state: int
    d_conv: int
    dt_rank: int
    eps: float

    @property
    def lambda_init(self) -> float:
        return 0.8 - 0.6 * math.exp(-0.3 * self.index)

    @property
    def kv_width(self) -> int:
        """A position's keys and values side by side: ``n_kv_heads x
        head_dim`` each."""
        return 2 * self.n_kv_heads * self.head_dim

    @property
    def cache_rows(self) -> dict:
        """What one token caches in this layer in the paged pool, name ->
        row width: the full layer its keys and values (read by every cross
        layer too); no other layer caches per position."""
        return {SHARED_KV: self.kv_width} if self.kind == "full" else {}

    @property
    def slot_rows(self) -> dict:
        """What this layer keeps per STREAM, whatever its length: name ->
        (shape, "f32" | "kv"), "kv" meaning the pool's own row type.  A
        Mamba layer its scan state and the conv's last inputs, a window
        layer a ring of its last ``window`` positions' keys and values."""
        if self.kind == "mamba":
            return {SSM: ((self.d_inner, self.d_state), "f32"),
                    CONV: ((self.d_conv - 1, self.d_inner), "f32")}
        if self.kind == "swa":
            return {RING: ((self.window, self.kv_width), "kv")}
        return {}


@serde.register
@dataclasses.dataclass(frozen=True)
class HybridDecoder(LayerConfig):
    """The `phi4flash` decoder (module docstring).  The LAST layer of its
    stack, ``[Embedding, HybridDecoder]``: `apply` returns ``LN_f(h)``, and
    the logits are those rows times the embedding's matrix transposed
    (`ops/generation._head_logits`).  `apply` is the whole-sequence form in
    plain `jax.numpy`; the serving engine runs the same blocks against its
    slot pools and its paged pool (`ops/generation.block`).  Serving only:
    a training loss over the tied head is not implemented."""

    d_model: int = 0
    n_heads: int = 1
    n_kv_heads: int = 1
    head_dim: int = 0
    d_ff: int = 0
    window: int = 0
    d_state: int = 16
    d_conv: int = 4
    expand: int = 2
    dt_rank: int = 0
    eps: float = 1e-5
    layer_types: tuple[str, ...] = ()

    EXPECTS = "rnn"
    REGULARIZED = ()

    def __post_init__(self):
        super().__post_init__()
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        kinds = self.layer_types
        bad = set(kinds) - set(HYBRID_KINDS)
        if bad:
            raise ValueError(f"unknown layer kinds {sorted(bad)}")
        if kinds.count("full") != 1:
            raise ValueError("a hybrid decoder has exactly one \"full\" "
                             "layer: its keys and values are what the "
                             "cross layers read")
        at = kinds.index("full")
        if not set(kinds[:at]) <= set(_SELF) or not set(
                kinds[at + 1:]) <= set(_CROSS):
            raise ValueError("mamba and swa layers come before the full "
                             "layer, gmu and cross layers after it")
        if "gmu" in kinds and "mamba" not in kinds[:at]:
            raise ValueError("a gmu layer reads the memory of a mamba "
                             "layer before the full one")
        if self.n_heads % 2 or self.n_kv_heads % 2 or (
                self.n_heads % self.n_kv_heads):
            raise ValueError("differential attention pairs the heads: "
                             "n_heads and n_kv_heads must be even, and "
                             "n_heads a multiple of n_kv_heads")

    @property
    def d_inner(self) -> int:
        return self.expand * self.d_model

    def blocks(self) -> tuple:
        at = self.layer_types.index("full")
        tap = max((i for i, k in enumerate(self.layer_types[:at])
                   if k == "mamba"), default=-1)
        return tuple(
            HybridBlock(
                name=f"{self.name}.layer{i:02d}", path=(self.name,
                                                        f"layer{i:02d}"),
                kind=kind, index=i, tap=i == tap, d_model=self.d_model,
                n_heads=self.n_heads, n_kv_heads=self.n_kv_heads,
                head_dim=self.head_dim, d_ff=self.d_ff, window=self.window,
                d_inner=self.d_inner, d_state=self.d_state,
                d_conv=self.d_conv, dt_rank=self.dt_rank, eps=self.eps)
            for i, kind in enumerate(self.layer_types))

    def output_type(self, itype: InputType) -> InputType:
        if itype.size != self.d_model:
            raise ValueError(
                f"HybridDecoder d_model={self.d_model} but input feature "
                f"size is {itype.size}")
        return InputType.recurrent(self.d_model, itype.shape[0])

    def init(self, key, itype):
        wi = self._winit(WeightInit.LECUN_NORMAL)
        d, e, n = self.d_model, self.d_inner, self.d_state
        hd, r = self.head_dim, self.dt_rank
        ones = lambda k: jnp.ones((k,), jnp.float32)
        norm = lambda: {"gamma": ones(d),
                        "beta": jnp.zeros((d,), jnp.float32)}

        def mat(k, *shape):
            return wi.init(k, shape, fan_in=shape[-2], fan_out=shape[-1])

        def attention(k, cross):
            ks = jax.random.split(k, 5)
            lam = 0.1 * jax.random.normal(ks[4], (4, hd), jnp.float32)
            p = {"Wq": mat(ks[0], d, self.n_heads * hd),
                 "Wo": mat(ks[3], self.n_heads * hd, d),
                 "lq1": lam[0], "lk1": lam[1], "lq2": lam[2], "lk2": lam[3],
                 "subln": ones(2 * hd)}
            if not cross:
                p["Wk"] = mat(ks[1], d, self.n_kv_heads * hd)
                p["Wv"] = mat(ks[2], d, self.n_kv_heads * hd)
            return p

        def mamba(k):
            ks = jax.random.split(k, 6)
            # Mamba-1's initialisation: A = -(1..N) per channel, and the
            # time step's bias the inverse softplus of a log-uniform draw
            # in [1e-3, 1e-1]
            dt = jnp.exp(jax.random.uniform(
                ks[5], (e,), jnp.float32, math.log(1e-3), math.log(1e-1)))
            return {
                "W_in": mat(ks[0], d, 2 * e),
                "conv_w": jax.random.normal(ks[1], (self.d_conv, e),
                                            jnp.float32)
                * self.d_conv ** -0.5,
                "conv_b": jnp.zeros((e,), jnp.float32),
                "W_x": mat(ks[2], e, r + 2 * n),
                "W_dt": mat(ks[3], r, e),
                "b_dt": dt + jnp.log(-jnp.expm1(-dt)),
                "A_log": jnp.log(jnp.broadcast_to(
                    jnp.arange(1, n + 1, dtype=jnp.float32), (e, n))),
                "D": ones(e),
                "W_out": mat(ks[4], e, d)}

        params = {"norm_f": norm()}
        for cfg, k in zip(self.blocks(),
                          jax.random.split(key, len(self.layer_types))):
            km, k1, k2 = jax.random.split(k, 3)
            if cfg.kind == "mamba":
                mixer = mamba(km)
            elif cfg.kind == "gmu":
                k3, k4 = jax.random.split(km)
                mixer = {"W_1": mat(k3, d, e), "W_2": mat(k4, e, d)}
            else:
                mixer = attention(km, cfg.kind == "cross")
            params[cfg.path[-1]] = {
                "ln1": norm(), "ln2": norm(), "mixer": mixer,
                "ffn": {"W_in": mat(k1, d, 2 * self.d_ff),
                        "W_out": mat(k2, self.d_ff, d)}}
        return params, {}

    def apply(self, params, state, x, *, training=False, rng=None):
        from deeplearning4j_tpu.ops.hybrid import sequence_forward

        # one sequence at a time: the scan carries one stream's state
        return jax.lax.map(
            lambda xs: sequence_forward(self, params, xs), x), state

    def compute_loss(self, *args, **kwargs):
        raise NotImplementedError(
            "HybridDecoder is served, not trained: a loss over its tied "
            "head and a training path for its scans are not implemented")
