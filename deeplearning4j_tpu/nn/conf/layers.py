"""Layer configuration dataclasses + their pure forward implementations.

The reference splits layer *config* (org.deeplearning4j.nn.conf.layers.*)
from layer *runtime* (org.deeplearning4j.nn.layers.*) because runtime
layers hold mutable INDArray state.  TPU-native there is no mutable layer
object: each config owns three pure functions —

    output_type(input_type)          static shape inference
    init(key, input_type)            -> (params pytree, state pytree)
    apply(params, state, x, ...)     -> (y, new_state)

`apply` is traced into the model's single compiled train/inference step, so
"layers" cost nothing at runtime; XLA fuses across them.  There is no
backpropGradient anywhere — jax.grad differentiates the whole step
(replacing the reference's per-layer hand-written backward passes).

Layout: NHWC / seq-major (B, T, F) — see input_type.py for why.
"""

from __future__ import annotations

import dataclasses
import enum
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from deeplearning4j_tpu.nn.activations import Activation
from deeplearning4j_tpu.nn.losses import Loss
from deeplearning4j_tpu.nn.conf.input_type import InputType
from deeplearning4j_tpu.nn.weights import WeightInit
from deeplearning4j_tpu.quant import functional as quantf
from deeplearning4j_tpu.utils import serde

# Reserved key in a layer's returned state: an auxiliary loss the compiled
# training step adds to the objective (MoE load balancing etc.).  Aux
# entries are popped before state is carried — see models/_common.py
# pop_aux_losses.
AUX_LOSS_KEY = "__aux_loss__"


class PoolingType(str, enum.Enum):
    MAX = "max"
    AVG = "avg"
    SUM = "sum"
    PNORM = "pnorm"


def _pair(v) -> tuple[int, int]:
    if isinstance(v, (tuple, list)):
        return (int(v[0]), int(v[1]))
    return (int(v), int(v))


def _coerce_enum(v, enum_cls):
    """Accept an enum member, its value ("relu"), its NAME ("RELU"), or an
    alias from the enum's optional _ALIASES_ table."""
    if isinstance(v, enum_cls):
        return v
    s = str(v).lower()
    s = getattr(enum_cls, "_ALIASES_", {}).get(s, s)
    try:
        return enum_cls(s)
    except ValueError:
        pass
    try:
        return enum_cls[str(v).upper()]
    except KeyError:
        raise ValueError(
            f"{v!r} is not a valid {enum_cls.__name__}; "
            f"options: {[e.value for e in enum_cls]}"
        ) from None


def _dropout(x, rate: float, training: bool, rng):
    """Inverted dropout on the layer input (reference semantics: dropOut
    applies to a layer's input activations)."""
    if not training or rate <= 0.0 or rng is None:
        return x
    keep = 1.0 - rate
    mask = jax.random.bernoulli(rng, keep, x.shape)
    return jnp.where(mask, x / keep, 0.0).astype(x.dtype)


@dataclasses.dataclass(frozen=True)
class LayerConfig:
    """Base layer config.

    Fields that default to None are filled from the model-level
    NeuralNetConfiguration defaults at build time (the reference's
    global-config-with-layer-override pattern).
    """

    name: Optional[str] = None
    activation: Optional[Activation] = None
    weight_init: Optional[WeightInit] = None
    l1: Optional[float] = None
    l2: Optional[float] = None
    dropout_rate: Optional[float] = None   # probability of dropping (NOT retain prob)
    frozen: bool = False                   # FrozenLayer role: excluded from updates

    # Which input kind apply() expects; the model auto-inserts reshapes
    # (the reference's InputPreProcessor role) when kinds mismatch.
    EXPECTS = "any"
    HAS_PARAMS = True
    # Layers that consume the (B, T) sequence mask declare this; the model
    # threads features_mask into their apply(mask=...) kwarg.
    ACCEPTS_MASK = False

    def __post_init__(self):
        # User-facing coercions: plain strings are accepted everywhere the
        # reference accepts an enum (Activation.RELU vs "relu"), and padding
        # is case-insensitive — "SAME" must not silently diverge from "same"
        # in output_type's shape math.
        if self.activation is not None:
            object.__setattr__(self, "activation", _coerce_enum(self.activation, Activation))
        if self.weight_init is not None:
            object.__setattr__(self, "weight_init", _coerce_enum(self.weight_init, WeightInit))
        pad = getattr(self, "padding", None)
        if isinstance(pad, str):
            object.__setattr__(self, "padding", pad.lower())
        loss = getattr(self, "loss", None)
        if loss is not None:
            object.__setattr__(self, "loss", _coerce_enum(loss, Loss))
        pooling = getattr(self, "pooling", None)
        if pooling is not None:
            object.__setattr__(self, "pooling", _coerce_enum(pooling, PoolingType))

    def output_type(self, itype: InputType) -> InputType:
        return itype

    def init(self, key: jax.Array, itype: InputType) -> tuple[dict, dict]:
        return {}, {}

    def apply(self, params, state, x, *, training=False, rng=None):
        raise NotImplementedError

    # regularization hook: which param names are penalized by l1/l2
    REGULARIZED = ("W",)

    def regularizable_params(self, lp: dict) -> list:
        """Arrays the l1/l2 penalty applies to (wrappers with nested param
        dicts override this)."""
        return [lp[p] for p in self.REGULARIZED if p in lp]

    def regularization_terms(self, lp: dict) -> list:
        """(l1, l2, array) triples — wrappers override to surface their
        inner layer's own coefficients."""
        l1, l2 = self.l1 or 0.0, self.l2 or 0.0
        if not l1 and not l2:
            return []
        return [(l1, l2, w) for w in self.regularizable_params(lp)]

    def _act(self, default=Activation.IDENTITY) -> Activation:
        return self.activation if self.activation is not None else default

    def _winit(self, default=WeightInit.XAVIER) -> WeightInit:
        return self.weight_init if self.weight_init is not None else default


# ---------------------------------------------------------------------------
# Feed-forward layers
# ---------------------------------------------------------------------------

@serde.register
@dataclasses.dataclass(frozen=True)
class Dense(LayerConfig):
    """Fully connected layer (DenseLayer role). nIn is inferred."""

    n_out: int = 0
    has_bias: bool = True

    EXPECTS = "ff"

    def output_type(self, itype: InputType) -> InputType:
        return InputType.feed_forward(self.n_out)

    def init(self, key, itype):
        n_in = itype.size
        w = self._winit().init(key, (n_in, self.n_out), fan_in=n_in, fan_out=self.n_out)
        params = {"W": w}
        if self.has_bias:
            params["b"] = jnp.zeros((self.n_out,), jnp.float32)
        return params, {}

    def apply(self, params, state, x, *, training=False, rng=None):
        x = _dropout(x, self.dropout_rate or 0.0, training, rng)
        # quantf.matmul: `x @ W` for f32 weights, the fused
        # dequant-matmul (int8 weights, f32 accumulate) after quantize()
        y = quantf.matmul(x, params["W"])
        if self.has_bias:
            y = y + params["b"].astype(x.dtype)
        return self._act()(y), state


@serde.register
@dataclasses.dataclass(frozen=True)
class OutputLayer(Dense):
    """Dense + declared loss (the reference's OutputLayer).  apply() returns
    PRE-activation logits; the model fuses activation into the loss for
    training and applies it for output()/predict."""

    loss: Loss = Loss.MCXENT

    def apply(self, params, state, x, *, training=False, rng=None):
        x = _dropout(x, self.dropout_rate or 0.0, training, rng)
        y = quantf.matmul(x, params["W"])
        if self.has_bias:
            y = y + params["b"].astype(x.dtype)
        return y, state   # logits; activation fused into loss / applied at output()


@serde.register
@dataclasses.dataclass(frozen=True)
class LossLayer(LayerConfig):
    """Parameterless output: attaches a loss to whatever precedes it."""

    loss: Loss = Loss.MCXENT
    HAS_PARAMS = False
    REGULARIZED = ()

    def apply(self, params, state, x, *, training=False, rng=None):
        return x, state


@serde.register
@dataclasses.dataclass(frozen=True)
class ActivationLayer(LayerConfig):
    HAS_PARAMS = False
    REGULARIZED = ()
    # slope/scale override for the parameterized activations (Keras
    # LeakyReLU carries alpha=0.3 by default vs this enum's 0.01; ELU
    # carries a scale) — None keeps the enum's canonical constant
    alpha: Optional[float] = None

    def apply(self, params, state, x, *, training=False, rng=None):
        if self.alpha is not None:
            if self.activation == Activation.LEAKYRELU:
                return jax.nn.leaky_relu(x, self.alpha), state
            if self.activation == Activation.ELU:
                return jax.nn.elu(x, self.alpha), state
        return self._act()(x), state


@serde.register
@dataclasses.dataclass(frozen=True)
class ScaleShift(LayerConfig):
    """Fixed elementwise `x * scale + shift` (the ScaleVertex role, as a
    sequential layer).  Primary use: device-side image normalization for
    the uint8 ETL wire path — `ScaleShift(scale=1/255.)` first in the
    stack replaces a host-side ImagePreProcessingScaler, so batches cross
    the host->device link as bytes and the scaling fuses into the jitted
    step (zero extra HBM traffic; XLA folds it into the following conv's
    input read)."""

    scale: float = 1.0
    shift: float = 0.0
    HAS_PARAMS = False
    REGULARIZED = ()

    def apply(self, params, state, x, *, training=False, rng=None):
        y = x * jnp.asarray(self.scale, x.dtype) + jnp.asarray(
            self.shift, x.dtype)
        return self._act()(y), state


@serde.register
@dataclasses.dataclass(frozen=True)
class Dropout(LayerConfig):
    """Standalone dropout layer (DropoutLayer role)."""

    rate: float = 0.5
    HAS_PARAMS = False
    REGULARIZED = ()

    def apply(self, params, state, x, *, training=False, rng=None):
        return _dropout(x, self.rate, training, rng), state


@serde.register
@dataclasses.dataclass(frozen=True)
class Embedding(LayerConfig):
    """EmbeddingLayer/EmbeddingSequenceLayer role: int ids -> vectors.

    Accepts (B,) -> (B, n_out) [ff] or (B, T) -> (B, T, n_out) [rnn].
    """

    n_in: int = 0
    n_out: int = 0
    EXPECTS = "any"
    REGULARIZED = ("W",)

    def output_type(self, itype: InputType) -> InputType:
        if itype.kind == InputType.KIND_RNN:
            return InputType.recurrent(self.n_out, itype.shape[0])
        return InputType.feed_forward(self.n_out)

    def init(self, key, itype):
        n_in = self.n_in
        if n_in <= 0:
            raise ValueError("Embedding.n_in (vocab size) must be set explicitly")
        w = self._winit().init(key, (n_in, self.n_out), fan_in=n_in, fan_out=self.n_out)
        return {"W": w}, {}

    def apply(self, params, state, x, *, training=False, rng=None):
        ids = x.astype(jnp.int32)
        # quantized tables gather int8 ROWS and dequantize only those —
        # the lookup touches 1 byte/weight instead of 4
        y = quantf.embedding_lookup(params["W"], ids)
        return self._act()(y), state


# ---------------------------------------------------------------------------
# Convolutional layers (NHWC)
# ---------------------------------------------------------------------------

@serde.register
@dataclasses.dataclass(frozen=True)
class Conv2D(LayerConfig):
    """2D convolution (ConvolutionLayer role).

    The reference lowers conv to im2col+gemm in libnd4j or cuDNN
    (SURVEY.md §3.1); here it is one lax.conv_general_dilated that XLA maps
    directly onto the MXU.  Kernel layout HWIO, feature-map layout NHWC.
    """

    n_out: int = 0
    kernel: tuple[int, int] = (3, 3)
    stride: tuple[int, int] = (1, 1)
    padding: str = "valid"             # "same" | "valid"
    dilation: tuple[int, int] = (1, 1)
    groups: int = 1                    # n_in groups => depthwise
    has_bias: bool = True

    EXPECTS = "cnn"

    def _out_hw(self, h: int, w: int) -> tuple[int, int]:
        kh, kw = _pair(self.kernel)
        sh, sw = _pair(self.stride)
        dh, dw = _pair(self.dilation)
        ekh, ekw = (kh - 1) * dh + 1, (kw - 1) * dw + 1
        if self.padding == "same":
            return -(-h // sh), -(-w // sw)
        return (h - ekh) // sh + 1, (w - ekw) // sw + 1

    def output_type(self, itype: InputType) -> InputType:
        h, w, _ = itype.shape
        oh, ow = self._out_hw(h, w)
        return InputType.convolutional(oh, ow, self.n_out)

    def init(self, key, itype):
        c_in = itype.channels
        kh, kw = _pair(self.kernel)
        if c_in % self.groups:
            raise ValueError(f"channels {c_in} not divisible by groups {self.groups}")
        shape = (kh, kw, c_in // self.groups, self.n_out)
        fan_in = kh * kw * (c_in // self.groups)
        fan_out = kh * kw * self.n_out // self.groups
        w = self._winit(WeightInit.RELU).init(key, shape, fan_in=fan_in, fan_out=fan_out)
        params = {"W": w}
        if self.has_bias:
            params["b"] = jnp.zeros((self.n_out,), jnp.float32)
        return params, {}

    def apply(self, params, state, x, *, training=False, rng=None):
        x = _dropout(x, self.dropout_rate or 0.0, training, rng)
        # conv_weight: plain dtype cast, or dequantized int8 kernel (the
        # cast+scale fuse into the conv's weight read)
        w = quantf.conv_weight(params["W"], x.dtype)
        y = lax.conv_general_dilated(
            x,
            w,
            window_strides=_pair(self.stride),
            padding=self.padding.upper(),
            rhs_dilation=_pair(self.dilation),
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            feature_group_count=self.groups,
        ).astype(x.dtype)
        if self.has_bias:
            y = y + params["b"].astype(x.dtype)
        return self._act(Activation.IDENTITY)(y), state


@serde.register
@dataclasses.dataclass(frozen=True)
class SeparableConv2D(LayerConfig):
    """Depthwise + pointwise conv (SeparableConvolution2D role)."""

    n_out: int = 0
    kernel: tuple[int, int] = (3, 3)
    stride: tuple[int, int] = (1, 1)
    padding: str = "valid"
    depth_multiplier: int = 1
    has_bias: bool = True

    EXPECTS = "cnn"

    def output_type(self, itype: InputType) -> InputType:
        h, w, _ = itype.shape
        dummy = Conv2D(n_out=self.n_out, kernel=self.kernel, stride=self.stride, padding=self.padding)
        oh, ow = dummy._out_hw(h, w)
        return InputType.convolutional(oh, ow, self.n_out)

    def init(self, key, itype):
        c_in = itype.channels
        kh, kw = _pair(self.kernel)
        k1, k2 = jax.random.split(key)
        wi = self._winit(WeightInit.RELU)
        depth = wi.init(k1, (kh, kw, 1, c_in * self.depth_multiplier), fan_in=kh * kw, fan_out=self.depth_multiplier)
        point = wi.init(
            k2,
            (1, 1, c_in * self.depth_multiplier, self.n_out),
            fan_in=c_in * self.depth_multiplier,
            fan_out=self.n_out,
        )
        params = {"depthW": depth, "pointW": point}
        if self.has_bias:
            params["b"] = jnp.zeros((self.n_out,), jnp.float32)
        return params, {}

    REGULARIZED = ("depthW", "pointW")

    def apply(self, params, state, x, *, training=False, rng=None):
        x = _dropout(x, self.dropout_rate or 0.0, training, rng)
        c_in = x.shape[-1]
        y = lax.conv_general_dilated(
            x,
            quantf.conv_weight(params["depthW"], x.dtype),
            window_strides=_pair(self.stride),
            padding=self.padding.upper(),
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            feature_group_count=c_in,
        ).astype(x.dtype)
        y = lax.conv_general_dilated(
            y,
            quantf.conv_weight(params["pointW"], x.dtype),
            window_strides=(1, 1),
            padding="VALID",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
        ).astype(x.dtype)
        if self.has_bias:
            y = y + params["b"].astype(x.dtype)
        return self._act()(y), state


@serde.register
@dataclasses.dataclass(frozen=True)
class Deconv2D(LayerConfig):
    """Transposed convolution (Deconvolution2D role)."""

    n_out: int = 0
    kernel: tuple[int, int] = (2, 2)
    stride: tuple[int, int] = (2, 2)
    padding: str = "valid"
    has_bias: bool = True

    EXPECTS = "cnn"

    def output_type(self, itype: InputType) -> InputType:
        h, w, _ = itype.shape
        kh, kw = _pair(self.kernel)
        sh, sw = _pair(self.stride)
        if self.padding == "same":
            oh, ow = h * sh, w * sw
        else:
            # matches lax.conv_transpose VALID: h*s + max(k-s, 0)
            oh, ow = h * sh + max(kh - sh, 0), w * sw + max(kw - sw, 0)
        return InputType.convolutional(oh, ow, self.n_out)

    def init(self, key, itype):
        c_in = itype.channels
        kh, kw = _pair(self.kernel)
        w = self._winit(WeightInit.RELU).init(
            key, (kh, kw, c_in, self.n_out), fan_in=kh * kw * c_in, fan_out=kh * kw * self.n_out
        )
        params = {"W": w}
        if self.has_bias:
            params["b"] = jnp.zeros((self.n_out,), jnp.float32)
        return params, {}

    def apply(self, params, state, x, *, training=False, rng=None):
        x = _dropout(x, self.dropout_rate or 0.0, training, rng)
        y = lax.conv_transpose(
            x,
            params["W"].astype(x.dtype),
            strides=_pair(self.stride),
            padding=self.padding.upper(),
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
        ).astype(x.dtype)
        if self.has_bias:
            y = y + params["b"].astype(x.dtype)
        return self._act()(y), state


@serde.register
@dataclasses.dataclass(frozen=True)
class Subsampling(LayerConfig):
    """Pooling layer (SubsamplingLayer role)."""

    pooling: PoolingType = PoolingType.MAX
    kernel: tuple[int, int] = (2, 2)
    stride: tuple[int, int] = (2, 2)
    padding: str = "valid"
    pnorm: int = 2

    EXPECTS = "cnn"
    HAS_PARAMS = False
    REGULARIZED = ()

    def output_type(self, itype: InputType) -> InputType:
        h, w, c = itype.shape
        kh, kw = _pair(self.kernel)
        sh, sw = _pair(self.stride)
        if self.padding == "same":
            oh, ow = -(-h // sh), -(-w // sw)
        else:
            oh, ow = (h - kh) // sh + 1, (w - kw) // sw + 1
        return InputType.convolutional(oh, ow, c)

    def apply(self, params, state, x, *, training=False, rng=None):
        kh, kw = _pair(self.kernel)
        sh, sw = _pair(self.stride)
        dims = (1, kh, kw, 1)
        strides = (1, sh, sw, 1)
        pad = self.padding.upper()
        if self.pooling is PoolingType.MAX:
            y = lax.reduce_window(x, -jnp.inf, lax.max, dims, strides, pad)
        elif self.pooling is PoolingType.SUM:
            y = lax.reduce_window(x, 0.0, lax.add, dims, strides, pad)
        elif self.pooling is PoolingType.AVG:
            s = lax.reduce_window(x, 0.0, lax.add, dims, strides, pad)
            if pad == "SAME":
                ones = jnp.ones(x.shape[:1] + x.shape[1:], x.dtype)
                cnt = lax.reduce_window(ones, 0.0, lax.add, dims, strides, pad)
                y = s / cnt
            else:
                y = s / (kh * kw)
        elif self.pooling is PoolingType.PNORM:
            p = float(self.pnorm)
            s = lax.reduce_window(jnp.abs(x) ** p, 0.0, lax.add, dims, strides, pad)
            y = s ** (1.0 / p)
        else:
            raise ValueError(f"unhandled pooling {self.pooling}")
        return y, state


@serde.register
@dataclasses.dataclass(frozen=True)
class GlobalPooling(LayerConfig):
    """GlobalPoolingLayer role: collapse spatial (CNN) or time (RNN) dims."""

    pooling: PoolingType = PoolingType.AVG
    HAS_PARAMS = False
    REGULARIZED = ()
    ACCEPTS_MASK = True

    def output_type(self, itype: InputType) -> InputType:
        if itype.kind == InputType.KIND_CNN:
            return InputType.feed_forward(itype.channels)
        if itype.kind == InputType.KIND_RNN:
            return InputType.feed_forward(itype.size)
        return itype

    def apply(self, params, state, x, *, training=False, rng=None, mask=None):
        axes = tuple(range(1, x.ndim - 1))
        m = None
        if mask is not None:
            # (B, T) sequence mask broadcast over features; every pooling
            # type must exclude padded steps (the reference masks all four)
            m = mask.astype(x.dtype)
            while m.ndim < x.ndim:
                m = m[..., None]
        if self.pooling is PoolingType.MAX:
            if m is not None:
                x = jnp.where(m > 0, x, jnp.asarray(-jnp.inf, x.dtype))
            return jnp.max(x, axis=axes), state
        if self.pooling is PoolingType.SUM:
            if m is not None:
                x = x * m
            return jnp.sum(x, axis=axes), state
        if self.pooling is PoolingType.PNORM:
            p = 2.0
            if m is not None:
                x = x * m
            return jnp.sum(jnp.abs(x) ** p, axis=axes) ** (1 / p), state
        if m is not None:
            denom = jnp.maximum(jnp.sum(m, axis=axes), 1.0)
            return jnp.sum(x * m, axis=axes) / denom, state
        return jnp.mean(x, axis=axes), state


@serde.register
@dataclasses.dataclass(frozen=True)
class SpaceToDepth(LayerConfig):
    """Space-to-depth (the reference's SpaceToDepthLayer; YOLO2's
    'passthrough' reorg).  (B, H, W, C) -> (B, H/b, W/b, C*b^2)."""

    block: int = 2
    EXPECTS = "cnn"
    HAS_PARAMS = False
    REGULARIZED = ()

    def output_type(self, itype: InputType) -> InputType:
        h, w, c = itype.shape
        b = self.block
        if h % b or w % b:
            raise ValueError(f"spatial dims ({h},{w}) not divisible by block {b}")
        return InputType.convolutional(h // b, w // b, c * b * b)

    def apply(self, params, state, x, *, training=False, rng=None):
        n, h, w, c = x.shape
        b = self.block
        y = x.reshape(n, h // b, b, w // b, b, c)
        y = jnp.transpose(y, (0, 1, 3, 2, 4, 5)).reshape(n, h // b, w // b, c * b * b)
        return y, state


@serde.register
@dataclasses.dataclass(frozen=True)
class ZeroPadding2D(LayerConfig):
    padding: tuple[int, int, int, int] = (1, 1, 1, 1)   # top, bottom, left, right
    EXPECTS = "cnn"
    HAS_PARAMS = False
    REGULARIZED = ()

    def output_type(self, itype: InputType) -> InputType:
        h, w, c = itype.shape
        t, b, l, r = self.padding
        return InputType.convolutional(h + t + b, w + l + r, c)

    def apply(self, params, state, x, *, training=False, rng=None):
        t, b, l, r = self.padding
        return jnp.pad(x, ((0, 0), (t, b), (l, r), (0, 0))), state


@serde.register
@dataclasses.dataclass(frozen=True)
class Upsampling2D(LayerConfig):
    size: tuple[int, int] = (2, 2)
    EXPECTS = "cnn"
    HAS_PARAMS = False
    REGULARIZED = ()

    def output_type(self, itype: InputType) -> InputType:
        h, w, c = itype.shape
        return InputType.convolutional(h * self.size[0], w * self.size[1], c)

    def apply(self, params, state, x, *, training=False, rng=None):
        y = jnp.repeat(jnp.repeat(x, self.size[0], axis=1), self.size[1], axis=2)
        return y, state


# ---------------------------------------------------------------------------
# Normalization layers
# ---------------------------------------------------------------------------

@serde.register
@dataclasses.dataclass(frozen=True)
class BatchNorm(LayerConfig):
    """BatchNormalization role.

    Running mean/var live in layer STATE (the functional analog of the
    reference's mutable running stats); training returns updated state from
    inside the compiled step.  Under data-parallel sharding the batch mean
    is a global mean — GSPMD inserts the cross-replica reduction, which is
    exactly synchronized ("sync BN") semantics.
    """

    epsilon: float = 1e-5
    decay: float = 0.9        # running-stat momentum (reference default 0.9)
    lock_gamma_beta: bool = False

    HAS_PARAMS = True
    REGULARIZED = ()

    def init(self, key, itype):
        c = itype.shape[-1]
        params = {}
        if not self.lock_gamma_beta:
            params = {"gamma": jnp.ones((c,), jnp.float32), "beta": jnp.zeros((c,), jnp.float32)}
        state = {"mean": jnp.zeros((c,), jnp.float32), "var": jnp.ones((c,), jnp.float32)}
        return params, state

    def apply(self, params, state, x, *, training=False, rng=None):
        axes = tuple(range(x.ndim - 1))
        if training:
            xf = x.astype(jnp.float32)
            mean = jnp.mean(xf, axis=axes)
            var = jnp.var(xf, axis=axes)
            new_state = {
                "mean": self.decay * state["mean"] + (1 - self.decay) * mean,
                "var": self.decay * state["var"] + (1 - self.decay) * var,
            }
        else:
            mean, var = state["mean"], state["var"]
            new_state = state
        inv = lax.rsqrt(var + self.epsilon)
        scale = params.get("gamma", 1.0) * inv
        shift = params.get("beta", 0.0) - mean * scale
        y = (x.astype(jnp.float32) * scale + shift).astype(x.dtype)
        return self._act()(y), new_state


@serde.register
@dataclasses.dataclass(frozen=True)
class LayerNorm(LayerConfig):
    """Layer normalization over the feature (last) dim."""

    epsilon: float = 1e-5
    HAS_PARAMS = True
    REGULARIZED = ()

    def init(self, key, itype):
        c = itype.shape[-1]
        return {"gamma": jnp.ones((c,), jnp.float32), "beta": jnp.zeros((c,), jnp.float32)}, {}

    def apply(self, params, state, x, *, training=False, rng=None):
        xf = x.astype(jnp.float32)
        mean = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        y = (xf - mean) * lax.rsqrt(var + self.epsilon)
        y = y * params["gamma"] + params["beta"]
        return self._act()(y.astype(x.dtype)), state


@serde.register
@dataclasses.dataclass(frozen=True)
class LocalResponseNormalization(LayerConfig):
    """LRN role (AlexNet-era)."""

    k: float = 2.0
    n: int = 5
    alpha: float = 1e-4
    beta: float = 0.75
    EXPECTS = "cnn"
    HAS_PARAMS = False
    REGULARIZED = ()

    def apply(self, params, state, x, *, training=False, rng=None):
        sq = x.astype(jnp.float32) ** 2
        half = self.n // 2
        # sum over a window along the channel axis
        padded = jnp.pad(sq, ((0, 0), (0, 0), (0, 0), (half, half)))
        windows = [padded[..., i : i + x.shape[-1]] for i in range(self.n)]
        s = sum(windows)
        y = x.astype(jnp.float32) / (self.k + self.alpha * s) ** self.beta
        return y.astype(x.dtype), state


@serde.register
@dataclasses.dataclass(frozen=True)
class CenterLossOutputLayer(LayerConfig):
    """Softmax + center loss output (reference
    org.deeplearning4j.nn.conf.layers.CenterLossOutputLayer [U], the
    FaceNetNN4Small2 training head): pulls each example's embedding
    toward its class center while the cross-entropy separates classes.

    TPU-native design: the class centers are ordinary trainable params
    inside the compiled step — the center term's gradient wrt `centers`
    IS the center update (scaled by `alpha` against the main loss), so
    no out-of-graph bookkeeping exists.  `apply()` emits
    `concat([logits, embedding])`; use `split_output()` to separate
    them (the embedding half is the face-recognition feature vector).
    """

    n_out: int = 0            # number of classes
    alpha: float = 0.1        # center learning-rate multiplier
    lambda_coeff: float = 2e-4  # weight of the center-distance term
    has_bias: bool = True

    EXPECTS = "ff"

    def output_type(self, itype: InputType) -> InputType:
        return InputType.feed_forward(self.n_out + itype.size)

    def init(self, key, itype):
        n_in = itype.size
        w = self._winit().init(key, (n_in, self.n_out), fan_in=n_in,
                               fan_out=self.n_out)
        params = {"W": w, "centers": jnp.zeros((self.n_out, n_in), jnp.float32)}
        if self.has_bias:
            params["b"] = jnp.zeros((self.n_out,), jnp.float32)
        return params, {}

    def apply(self, params, state, x, *, training=False, rng=None):
        x = _dropout(x, self.dropout_rate or 0.0, training, rng)
        logits = x @ params["W"].astype(x.dtype)
        if self.has_bias:
            logits = logits + params["b"].astype(x.dtype)
        return jnp.concatenate([logits, x], axis=-1), state

    def split_output(self, out):
        """(logits, embedding) halves of apply()'s concatenated output."""
        return out[..., : self.n_out], out[..., self.n_out :]

    def evaluation_output(self, lp, out):
        """Class probabilities for Evaluation (argmax over the raw concat
        output would land in the embedding half)."""
        logits, _ = self.split_output(out)
        return jax.nn.softmax(logits.astype(jnp.float32), axis=-1)

    def compute_loss_with_params(self, lp, preds, labels, mask=None):
        logits, emb = self.split_output(preds.astype(jnp.float32))
        labels = labels.astype(jnp.float32)
        logp = jax.nn.log_softmax(logits, axis=-1)
        per = -jnp.sum(labels * logp, axis=-1)
        # class center per example; alpha scales the gradient that flows
        # into the centers (the reference's center update rate)
        centers = lp["centers"]
        centers = (
            centers * self.alpha + jax.lax.stop_gradient(centers) * (1 - self.alpha)
        )
        c = labels @ centers.astype(jnp.float32)
        center_term = 0.5 * jnp.sum((emb - c) ** 2, axis=-1)
        per = per + self.lambda_coeff * center_term
        if mask is not None:
            m = mask.astype(jnp.float32)
            return jnp.sum(per * m) / jnp.maximum(jnp.sum(m), 1.0)
        return jnp.mean(per)


@serde.register
@dataclasses.dataclass(frozen=True)
class ChunkedSoftmaxOutputLayer(LayerConfig):
    """LM output head whose softmax cross-entropy streams the vocab in
    chunks (ops/chunked_xent.py) — the (N, vocab) logits tensor, the
    largest activation in a large-vocab training step, never
    materializes.  No reference counterpart (the reference always
    buffers dense logits through LossMCXENT); this is TPU HBM headroom
    the dense path cannot offer.

    `apply()` passes hidden states through UNPROJECTED; the loss owns
    the (n_in, vocab) projection.  Labels may be int class ids
    ((B,) / (B,T), the memory-sane form) or one-hot (converted via
    argmax).  For inference, `logits(params, h)` materializes the
    projection densely (generation usually wants top-k of one step,
    not a training batch of logits).
    """

    n_out: int = 0          # vocab size
    chunk: int = 8192
    has_bias: bool = True

    EXPECTS = "any"

    def output_type(self, itype: InputType) -> InputType:
        return itype            # hidden states pass through; loss projects

    def init(self, key, itype):
        n_in = itype.size
        w = self._winit().init(key, (n_in, self.n_out), fan_in=n_in,
                               fan_out=self.n_out)
        params = {"W": w}
        if self.has_bias:
            params["b"] = jnp.zeros((self.n_out,), jnp.float32)
        return params, {}

    def apply(self, params, state, x, *, training=False, rng=None):
        return _dropout(x, self.dropout_rate or 0.0, training, rng), state

    def logits(self, params, h):
        """Dense projection for inference/generation."""
        y = quantf.matmul(h, params["W"])
        if self.has_bias:
            y = y + params["b"].astype(h.dtype)
        return y

    def evaluation_output(self, lp, out):
        """Class probabilities for Evaluation: project the hidden states
        densely (evaluate() batches are inference-sized)."""
        return jax.nn.softmax(self.logits(lp, out).astype(jnp.float32), axis=-1)

    def compute_loss_with_params(self, lp, preds, labels, mask=None):
        from deeplearning4j_tpu.ops.chunked_xent import chunked_softmax_xent

        d = preds.shape[-1]
        h = preds.reshape(-1, d)
        labels = jnp.asarray(labels)
        # disambiguate by ELEMENT COUNT, not trailing-dim match: when the
        # sequence length equals the vocab size, (B, T) int ids would
        # otherwise be misread as (B, V) one-hot
        if labels.size == h.shape[0] * self.n_out:
            labels = jnp.argmax(
                labels.reshape(h.shape[0], self.n_out), axis=-1
            )                                            # one-hot fallback
        elif labels.size != h.shape[0]:
            raise ValueError(
                f"labels with {labels.size} elements fit neither int ids "
                f"({h.shape[0]}) nor one-hot ({h.shape[0]}x{self.n_out})"
            )
        ids = labels.reshape(-1).astype(jnp.int32)
        if mask is not None:
            w = jnp.asarray(mask).reshape(-1).astype(jnp.float32)
        else:
            w = jnp.ones((h.shape[0],), jnp.float32)
        b = lp.get("b", jnp.zeros((self.n_out,), jnp.float32))
        return chunked_softmax_xent(h, lp["W"], b, ids, w, self.chunk)
