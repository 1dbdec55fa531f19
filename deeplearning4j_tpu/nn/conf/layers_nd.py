"""1-D / 3-D conv-family layers, croppings, and PReLU.

Reference parity (SURVEY.md §2.2 "DL4J-NN config DSL"): Convolution1D,
Convolution3D, Subsampling1DLayer, Subsampling3DLayer,
Cropping1D/2D/3D, PReLULayer.  Same pure init/apply contract as
layers.py; sequence (1-D) layers ride the RNN input kind (B, T, C) — the
TPU layout keeps channels last at every rank so every conv contraction
feeds the MXU lanes directly.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from deeplearning4j_tpu.nn.activations import Activation
from deeplearning4j_tpu.nn.conf.input_type import InputType
from deeplearning4j_tpu.nn.conf.layers import LayerConfig, PoolingType
from deeplearning4j_tpu.nn.weights import WeightInit
from deeplearning4j_tpu.quant import functional as quantf
from deeplearning4j_tpu.utils import serde


def _triple(v) -> tuple[int, int, int]:
    if isinstance(v, int):
        return (v, v, v)
    t = tuple(int(x) for x in v)
    if len(t) != 3:
        raise ValueError(f"need an int or 3-tuple, got {v}")
    return t


def _out_len(size: int, k: int, s: int, padding: str, d: int = 1) -> int:
    eff = (k - 1) * d + 1
    if padding == "same":
        return -(-size // s)
    return -(-(size - eff + 1) // s)


@serde.register
@dataclasses.dataclass(frozen=True)
class Conv1D(LayerConfig):
    """Temporal convolution over (B, T, C) — `Convolution1DLayer`."""

    n_out: int = 0
    kernel: int = 3
    stride: int = 1
    padding: str = "same"
    dilation: int = 1
    has_bias: bool = True

    EXPECTS = "rnn"

    def output_type(self, itype: InputType) -> InputType:
        t = itype.shape[0]
        t_out = (
            -1 if t < 0
            else _out_len(t, self.kernel, self.stride, self.padding, self.dilation)
        )
        return InputType.recurrent(self.n_out, t_out)

    def init(self, key, itype):
        c_in = itype.size
        fan_in = self.kernel * c_in
        w = self._winit(WeightInit.RELU).init(
            key, (self.kernel, c_in, self.n_out),
            fan_in=fan_in, fan_out=self.kernel * self.n_out,
        )
        params = {"W": w}
        if self.has_bias:
            params["b"] = jnp.zeros((self.n_out,), jnp.float32)
        return params, {}

    def apply(self, params, state, x, *, training=False, rng=None):
        y = lax.conv_general_dilated(
            x, quantf.conv_weight(params["W"], x.dtype),
            window_strides=(self.stride,),
            padding=self.padding.upper(),
            rhs_dilation=(self.dilation,),
            dimension_numbers=("NWC", "WIO", "NWC"),
        )
        if self.has_bias:
            y = y + params["b"].astype(x.dtype)
        return self._act()(y), state


@serde.register
@dataclasses.dataclass(frozen=True)
class Conv3D(LayerConfig):
    """Volumetric convolution over (B, D, H, W, C) — `Convolution3D`."""

    n_out: int = 0
    kernel: tuple[int, int, int] = (3, 3, 3)
    stride: tuple[int, int, int] = (1, 1, 1)
    padding: str = "same"
    has_bias: bool = True

    EXPECTS = "cnn3d"

    def output_type(self, itype: InputType) -> InputType:
        d, h, w, _ = itype.shape
        kd, kh, kw = _triple(self.kernel)
        sd, sh, sw = _triple(self.stride)
        return InputType.convolutional3d(
            _out_len(d, kd, sd, self.padding),
            _out_len(h, kh, sh, self.padding),
            _out_len(w, kw, sw, self.padding),
            self.n_out,
        )

    def init(self, key, itype):
        c_in = itype.channels
        kd, kh, kw = _triple(self.kernel)
        fan_in = kd * kh * kw * c_in
        w = self._winit(WeightInit.RELU).init(
            key, (kd, kh, kw, c_in, self.n_out),
            fan_in=fan_in, fan_out=kd * kh * kw * self.n_out,
        )
        params = {"W": w}
        if self.has_bias:
            params["b"] = jnp.zeros((self.n_out,), jnp.float32)
        return params, {}

    def apply(self, params, state, x, *, training=False, rng=None):
        y = lax.conv_general_dilated(
            x, quantf.conv_weight(params["W"], x.dtype),
            window_strides=_triple(self.stride),
            padding=self.padding.upper(),
            dimension_numbers=("NDHWC", "DHWIO", "NDHWC"),
        )
        if self.has_bias:
            y = y + params["b"].astype(x.dtype)
        return self._act()(y), state


def _pool_nd(x, kind: PoolingType, window, strides, padding: str,
             pnorm: float = 2.0):
    """All four reference pooling kinds (mirrors the 2D Subsampling)."""
    dims = (1, *window, 1)
    strd = (1, *strides, 1)
    pad = padding.upper()
    if kind == PoolingType.MAX:
        return lax.reduce_window(x, -jnp.inf, lax.max, dims, strd, pad)
    if kind == PoolingType.SUM:
        return lax.reduce_window(x, 0.0, lax.add, dims, strd, pad)
    if kind == PoolingType.AVG:
        s = lax.reduce_window(x, 0.0, lax.add, dims, strd, pad)
        if pad == "SAME":
            cnt = lax.reduce_window(
                jnp.ones_like(x), 0.0, lax.add, dims, strd, pad
            )
            return s / cnt
        denom = 1
        for w in window:
            denom *= w
        return s / denom
    if kind == PoolingType.PNORM:
        p = float(pnorm)
        s = lax.reduce_window(jnp.abs(x) ** p, 0.0, lax.add, dims, strd, pad)
        return s ** (1.0 / p)
    raise ValueError(f"unhandled pooling {kind}")


@serde.register
@dataclasses.dataclass(frozen=True)
class Subsampling1D(LayerConfig):
    """Temporal pooling over (B, T, C) — `Subsampling1DLayer`."""

    kernel: int = 2
    stride: int = 2
    padding: str = "valid"
    pooling: PoolingType = PoolingType.MAX
    pnorm: float = 2.0

    EXPECTS = "rnn"
    HAS_PARAMS = False

    def output_type(self, itype: InputType) -> InputType:
        t = itype.shape[0]
        t_out = -1 if t < 0 else _out_len(t, self.kernel, self.stride, self.padding)
        return InputType.recurrent(itype.size, t_out)

    def apply(self, params, state, x, *, training=False, rng=None):
        return _pool_nd(x, self.pooling, (self.kernel,), (self.stride,),
                        self.padding, self.pnorm), state


@serde.register
@dataclasses.dataclass(frozen=True)
class Subsampling3D(LayerConfig):
    """Volumetric pooling over (B, D, H, W, C) — `Subsampling3DLayer`."""

    kernel: tuple[int, int, int] = (2, 2, 2)
    stride: tuple[int, int, int] = (2, 2, 2)
    padding: str = "valid"
    pooling: PoolingType = PoolingType.MAX
    pnorm: float = 2.0

    EXPECTS = "cnn3d"
    HAS_PARAMS = False

    def output_type(self, itype: InputType) -> InputType:
        d, h, w, c = itype.shape
        kd, kh, kw = _triple(self.kernel)
        sd, sh, sw = _triple(self.stride)
        return InputType.convolutional3d(
            _out_len(d, kd, sd, self.padding),
            _out_len(h, kh, sh, self.padding),
            _out_len(w, kw, sw, self.padding),
            c,
        )

    def apply(self, params, state, x, *, training=False, rng=None):
        return _pool_nd(x, self.pooling, _triple(self.kernel),
                        _triple(self.stride), self.padding, self.pnorm), state


def _crop2(v) -> tuple[int, int]:
    if isinstance(v, int):
        return (v, v)
    t = tuple(int(x) for x in v)
    return (t[0], t[1]) if len(t) == 2 else (t[0], t[0])


@serde.register
@dataclasses.dataclass(frozen=True)
class Cropping1D(LayerConfig):
    """Trim (begin, end) timesteps — `Cropping1D`."""

    cropping: tuple[int, int] = (0, 0)

    EXPECTS = "rnn"
    HAS_PARAMS = False

    def output_type(self, itype: InputType) -> InputType:
        t = itype.shape[0]
        a, b = _crop2(self.cropping)
        return InputType.recurrent(itype.size, t if t < 0 else t - a - b)

    def apply(self, params, state, x, *, training=False, rng=None):
        a, b = _crop2(self.cropping)
        return x[:, a : x.shape[1] - b, :], state


@serde.register
@dataclasses.dataclass(frozen=True)
class Cropping2D(LayerConfig):
    """Trim ((top, bottom), (left, right)) pixels — `Cropping2D`."""

    cropping: tuple = ((0, 0), (0, 0))

    EXPECTS = "cnn"
    HAS_PARAMS = False

    def _hw(self):
        c = self.cropping
        if isinstance(c, int):
            return (c, c), (c, c)
        c = tuple(c)
        if isinstance(c[0], int):
            return (c[0], c[0]), (c[1], c[1])
        return _crop2(c[0]), _crop2(c[1])

    def output_type(self, itype: InputType) -> InputType:
        h, w, ch = itype.shape
        (t, b), (l, r) = self._hw()
        return InputType.convolutional(h - t - b, w - l - r, ch)

    def apply(self, params, state, x, *, training=False, rng=None):
        (t, b), (l, r) = self._hw()
        return x[:, t : x.shape[1] - b, l : x.shape[2] - r, :], state


@serde.register
@dataclasses.dataclass(frozen=True)
class Cropping3D(LayerConfig):
    """Trim ((d0,d1),(h0,h1),(w0,w1)) voxels — `Cropping3D`."""

    cropping: tuple = ((0, 0), (0, 0), (0, 0))

    EXPECTS = "cnn3d"
    HAS_PARAMS = False

    def _dhw(self):
        c = self.cropping
        if isinstance(c, int):
            return ((c, c),) * 3
        c = tuple(c)
        if isinstance(c[0], int):
            return tuple((v, v) for v in _triple(c))
        return tuple(_crop2(v) for v in c)

    def output_type(self, itype: InputType) -> InputType:
        d, h, w, ch = itype.shape
        (d0, d1), (h0, h1), (w0, w1) = self._dhw()
        return InputType.convolutional3d(d - d0 - d1, h - h0 - h1, w - w0 - w1, ch)

    def apply(self, params, state, x, *, training=False, rng=None):
        (d0, d1), (h0, h1), (w0, w1) = self._dhw()
        return (
            x[:, d0 : x.shape[1] - d1, h0 : x.shape[2] - h1,
              w0 : x.shape[3] - w1, :],
            state,
        )


@serde.register
@dataclasses.dataclass(frozen=True)
class PReLU(LayerConfig):
    """Parametric ReLU with a learnable per-channel slope — `PReLULayer`."""

    alpha_init: float = 0.25

    EXPECTS = "any"
    REGULARIZED = ()            # slopes are not weight-decayed (reference
                                # behavior: decay pulls them to dead ReLU)

    def _n_channels(self, itype: InputType) -> int:
        if itype.kind in (InputType.KIND_CNN, InputType.KIND_CNN3D):
            return itype.channels
        return itype.size

    def init(self, key, itype):
        return {
            "alpha": jnp.full((self._n_channels(itype),), self.alpha_init,
                              jnp.float32)
        }, {}

    def apply(self, params, state, x, *, training=False, rng=None):
        a = params["alpha"].astype(x.dtype)
        return jnp.where(x >= 0, x, a * x), state


@serde.register
@dataclasses.dataclass(frozen=True)
class Upsampling1D(LayerConfig):
    """Nearest-neighbor upsampling along the time axis (Upsampling1D
    role): (B, T, C) -> (B, T*size, C)."""

    size: int = 2
    EXPECTS = "rnn"
    HAS_PARAMS = False
    REGULARIZED = ()

    def output_type(self, itype: InputType) -> InputType:
        t = itype.shape[0]
        return InputType.recurrent(itype.size, t if t < 0 else t * self.size)

    def apply(self, params, state, x, *, training=False, rng=None):
        return jnp.repeat(x, self.size, axis=1), state


@serde.register
@dataclasses.dataclass(frozen=True)
class Upsampling3D(LayerConfig):
    """Nearest-neighbor volumetric upsampling (Upsampling3D role):
    (B, D, H, W, C) -> each spatial dim repeated by its factor."""

    size: tuple = (2, 2, 2)
    EXPECTS = "cnn3d"
    HAS_PARAMS = False
    REGULARIZED = ()

    def __post_init__(self):
        super().__post_init__()
        s = self.size
        if isinstance(s, int):
            s = (s, s, s)
        object.__setattr__(self, "size", tuple(int(v) for v in s))

    def output_type(self, itype: InputType) -> InputType:
        d, h, w, c = itype.shape
        sd, sh, sw = self.size
        return InputType.convolutional3d(d * sd, h * sh, w * sw, c)

    def apply(self, params, state, x, *, training=False, rng=None):
        sd, sh, sw = self.size
        y = jnp.repeat(x, sd, axis=1)
        y = jnp.repeat(y, sh, axis=2)
        return jnp.repeat(y, sw, axis=3), state


@serde.register
@dataclasses.dataclass(frozen=True)
class MaskZeroLayer(LayerConfig):
    """Zero out padded timesteps (MaskZeroLayer role): activations at
    mask==0 positions become `mask_value` so downstream layers never see
    padding garbage.  The reference wraps an inner layer; here masking is
    its own stack element (the wrapped layer simply precedes it)."""

    mask_value: float = 0.0
    EXPECTS = "rnn"
    HAS_PARAMS = False
    ACCEPTS_MASK = True
    REGULARIZED = ()

    def apply(self, params, state, x, *, training=False, rng=None, mask=None):
        if mask is None:
            return x, state
        keep = mask.astype(x.dtype)[:, :, None]
        return x * keep + (1.0 - keep) * self.mask_value, state
