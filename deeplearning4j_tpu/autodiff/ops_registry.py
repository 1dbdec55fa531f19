"""Op registry for the autodiff graph — named, serializable op set.

The reference maps each SameDiff op onto a libnd4j opNum executed one JNI
call at a time (SURVEY.md §3.3).  Here each op name maps to a pure jnp
function; a recorded graph stores op NAMES (strings) + attrs, so graphs
serialize/deserialize without pickling code, and execution traces the
whole graph into ONE XLA computation.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def _conv2d(x, w, *, stride=(1, 1), padding="SAME", dilation=(1, 1)):
    return jax.lax.conv_general_dilated(
        x, w, window_strides=tuple(stride), padding=padding,
        rhs_dilation=tuple(dilation),
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )


def _max_pool2d(x, *, kernel=(2, 2), stride=(2, 2), padding="VALID"):
    return jax.lax.reduce_window(
        x, -jnp.inf, jax.lax.max,
        (1, *kernel, 1), (1, *stride, 1), padding,
    )


def _avg_pool2d(x, *, kernel=(2, 2), stride=(2, 2), padding="VALID"):
    dims, strides = (1, *kernel, 1), (1, *stride, 1)
    s = jax.lax.reduce_window(x, 0.0, jax.lax.add, dims, strides, padding)
    if padding == "SAME":
        # divide by the per-window count of REAL elements, not kernel area
        cnt = jax.lax.reduce_window(
            jnp.ones_like(x), 0.0, jax.lax.add, dims, strides, padding
        )
        return s / cnt
    return s / (kernel[0] * kernel[1])


def _layer_norm(x, gamma, beta, *, epsilon=1e-5):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + epsilon) * gamma + beta


def _softmax_cross_entropy(logits, labels):
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.sum(labels * logp, axis=-1))


def _sparse_softmax_cross_entropy(logits, labels):
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, labels[..., None].astype(jnp.int32), axis=-1)
    return -jnp.mean(picked)


def _sigmoid_cross_entropy(logits, labels):
    per = jnp.maximum(logits, 0) - logits * labels + jnp.log1p(jnp.exp(-jnp.abs(logits)))
    return jnp.mean(per)


def _conv1d(x, w, *, stride=1, padding="SAME"):
    """x: (N, T, C), w: (K, C, O)."""
    return jax.lax.conv_general_dilated(
        x, w, window_strides=(stride,), padding=padding,
        dimension_numbers=("NWC", "WIO", "NWC"),
    )


def _conv3d(x, w, *, stride=(1, 1, 1), padding="SAME"):
    """x: (N, D, H, W, C), w: (Kd, Kh, Kw, C, O)."""
    return jax.lax.conv_general_dilated(
        x, w, window_strides=tuple(stride), padding=padding,
        dimension_numbers=("NDHWC", "DHWIO", "NDHWC"),
    )


def _depthwise_conv2d(x, w, *, stride=(1, 1), padding="SAME", dilation=(1, 1)):
    """w: (Kh, Kw, C, M) -> per-channel conv with multiplier M."""
    c = x.shape[-1]
    return jax.lax.conv_general_dilated(
        x, w.reshape(w.shape[0], w.shape[1], 1, -1),
        window_strides=tuple(stride), padding=padding,
        rhs_dilation=tuple(dilation),
        dimension_numbers=("NHWC", "HWIO", "NHWC"), feature_group_count=c,
    )


def _deconv2d(x, w, *, stride=(2, 2), padding="SAME"):
    """Transposed conv; w: (Kh, Kw, I, O)."""
    return jax.lax.conv_transpose(
        x, w, strides=tuple(stride), padding=padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )


def _onnx_slice(x, *, starts, ends, axes):
    big = 2**31 - 1
    sl = [slice(None)] * x.ndim
    for s, e, a in zip(starts, ends, axes):
        sl[a % x.ndim] = slice(s, None if e >= big else e)
    return x[tuple(sl)]


def _rationaltanh(x):
    from deeplearning4j_tpu.nn.activations import _rational_tanh

    return _rational_tanh(x)


def _mhdpa(q, k, v, *, causal=False):
    from deeplearning4j_tpu.ops.attention import mha

    return mha(q, k, v, causal=causal)


def _batch_norm(x, mean, var, gamma, beta, *, epsilon=1e-5):
    return (x - mean) * jax.lax.rsqrt(var + epsilon) * gamma + beta


def _lstm_cell(x, h, c, w, r, b):
    """Single LSTM step. x:(N,I) h,c:(N,H) w:(I,4H) r:(H,4H) b:(4H,).
    Gate order i,f,g,o (input, forget, cell, output)."""
    z = x @ w + h @ r + b
    i, f, g, o = jnp.split(z, 4, axis=-1)
    c_new = jax.nn.sigmoid(f) * c + jax.nn.sigmoid(i) * jnp.tanh(g)
    h_new = jax.nn.sigmoid(o) * jnp.tanh(c_new)
    return jnp.stack([h_new, c_new])


def _gru_cell(x, h, w, r, b):
    """Single GRU step. x:(N,I) h:(N,H) w:(I,3H) r:(H,3H) b:(3H,).
    Gate order r,z,n (reset, update, candidate)."""
    zx = x @ w + b
    zr = h @ r
    rx, ux, nx = jnp.split(zx, 3, axis=-1)
    rr, ur, nr = jnp.split(zr, 3, axis=-1)
    reset = jax.nn.sigmoid(rx + rr)
    update = jax.nn.sigmoid(ux + ur)
    cand = jnp.tanh(nx + reset * nr)
    return (1.0 - update) * cand + update * h


def _resize(x, *, size, method="bilinear"):
    """x: (N, H, W, C) -> (N, size[0], size[1], C)."""
    n, _, _, c = x.shape
    return jax.image.resize(x, (n, size[0], size[1], c), method=method)


def _crop(x, *, offset, size):
    """Static crop: x[:, oh:oh+h, ow:ow+w, :]."""
    oh, ow = offset
    h, w = size
    return x[:, oh : oh + h, ow : ow + w, :]


def _adjust_contrast(x, *, factor):
    mean = jnp.mean(x, axis=(-3, -2), keepdims=True)
    return (x - mean) * factor + mean


def _rgb_to_grayscale(x):
    w = jnp.asarray([0.2989, 0.5870, 0.1140], x.dtype)
    return jnp.sum(x * w, axis=-1, keepdims=True)


def _moments(x, *, axis=None, keepdims=False):
    """Stacked [mean, variance] (reference's moments op returns both)."""
    return jnp.stack(
        [jnp.mean(x, axis=_ax(axis), keepdims=keepdims),
         jnp.var(x, axis=_ax(axis), keepdims=keepdims)]
    )


def _entropy(x, *, axis=None):
    p = jnp.clip(x, 1e-12, 1.0)
    return -jnp.sum(p * jnp.log(p), axis=_ax(axis))


def _reverse_sequence(x, lengths, *, seq_axis=1, batch_axis=0):
    """Reverse the first `lengths[b]` elements of each row along seq_axis
    (reference reverse_sequence / TF ReverseSequence)."""
    T = x.shape[seq_axis]
    idx = jnp.arange(T)
    lengths = lengths.astype(jnp.int32)

    def one(row, n):
        rev = jnp.where(idx < n, n - 1 - idx, idx)
        return jnp.take(row, rev, axis=seq_axis - 1 if seq_axis > batch_axis else seq_axis)

    return jax.vmap(one, in_axes=(batch_axis, 0), out_axes=batch_axis)(x, lengths)


def _sequence_mask(lengths, *, maxlen):
    return (
        jnp.arange(maxlen)[None, :] < lengths.astype(jnp.int32)[..., None]
    ).astype(jnp.float32)


def _scatter(op_name):
    def fn(ref, indices, updates):
        at = jnp.asarray(ref).at[jnp.asarray(indices).astype(jnp.int32)]
        return getattr(at, op_name)(updates)

    return fn


def _gather_nd(x, indices):
    idx = jnp.asarray(indices).astype(jnp.int32)
    return jnp.asarray(x)[tuple(jnp.moveaxis(idx, -1, 0))]


def _scatter_nd(indices, updates, *, shape):
    idx = indices.astype(jnp.int32)
    return jnp.zeros(tuple(shape), updates.dtype).at[
        tuple(jnp.moveaxis(idx, -1, 0))
    ].add(updates)


def _rand(kind):
    def fn(*, shape, seed=0, **kw):
        key = jax.random.key(seed)
        if kind == "normal":
            return kw.get("mean", 0.0) + kw.get("std", 1.0) * jax.random.normal(
                key, tuple(shape)
            )
        if kind == "uniform":
            return jax.random.uniform(
                key, tuple(shape), minval=kw.get("minval", 0.0),
                maxval=kw.get("maxval", 1.0),
            )
        if kind == "bernoulli":
            return jax.random.bernoulli(key, kw.get("p", 0.5), tuple(shape)).astype(
                jnp.float32
            )
        if kind == "exponential":
            return jax.random.exponential(key, tuple(shape)) / kw.get("rate", 1.0)
        if kind == "gamma":
            return jax.random.gamma(key, kw.get("alpha", 1.0), tuple(shape)) / kw.get(
                "beta", 1.0
            )
        if kind == "poisson":
            return jax.random.poisson(key, kw.get("lam", 1.0), tuple(shape)).astype(
                jnp.float32
            )
        if kind == "truncated_normal":
            return kw.get("mean", 0.0) + kw.get("std", 1.0) * jax.random.truncated_normal(
                key, -2.0, 2.0, tuple(shape)
            )
        raise ValueError(kind)

    return fn


def _random_shuffle(x, *, seed=0, axis=0):
    return jax.random.permutation(jax.random.key(seed), x, axis=axis)


# -- signal / audio family (the reference's audio declarable ops) -----------

def _frame(x, *, frame_length, frame_step):
    """Overlapping frames over the LAST axis: (..., T) ->
    (..., n_frames, frame_length); tail samples that don't fill a frame
    are dropped (TF signal.frame pad_end=False semantics)."""
    T = x.shape[-1]
    n = 1 + (T - frame_length) // frame_step
    idx = (
        jnp.arange(n)[:, None] * frame_step + jnp.arange(frame_length)[None, :]
    )
    return x[..., idx]


def _stft(x, *, frame_length, frame_step, fft_length=None, window="hann"):
    """Short-time Fourier transform over the last axis -> complex
    (..., n_frames, fft_length//2 + 1).  Periodic (TF-semantics) window."""
    fft_length = fft_length or frame_length
    frames = _frame(x, frame_length=frame_length, frame_step=frame_step)
    w = _window(window, frame_length, x.dtype)
    return jnp.fft.rfft(frames * w, n=fft_length, axis=-1)


def _istft(s, *, frame_length, frame_step, fft_length=None, window="hann"):
    """Inverse STFT by windowed overlap-add with COLA normalization.
    The window name validates exactly like _stft's — a silent rectangular
    fallback would desynchronize the analysis and synthesis windows."""
    fft_length = fft_length or frame_length
    frames = jnp.fft.irfft(s, n=fft_length, axis=-1)[..., :frame_length]
    w = _window(window, frame_length, frames.dtype)
    n_frames = s.shape[-2]
    T = frame_length + (n_frames - 1) * frame_step
    idx = (
        jnp.arange(n_frames)[:, None] * frame_step
        + jnp.arange(frame_length)[None, :]
    ).reshape(-1)
    flat = (frames * w).reshape(s.shape[:-2] + (-1,))
    out = jnp.zeros(s.shape[:-2] + (T,), flat.dtype).at[..., idx].add(flat)
    norm = jnp.zeros((T,), flat.dtype).at[idx].add(jnp.tile(w * w, n_frames))
    return out / jnp.maximum(norm, 1e-12)


def _window(kind, length, dtype=jnp.float32, periodic=True):
    """TF-semantics windows: tf.signal.*_window defaults to PERIODIC
    (denominator N), unlike numpy's symmetric (N-1) forms — goldens
    against TF graphs depend on this."""
    n = jnp.arange(length, dtype=jnp.float32)
    d = float(length if periodic else max(length - 1, 1))
    if kind == "hann":
        w = 0.5 - 0.5 * jnp.cos(2.0 * jnp.pi * n / d)
    elif kind == "hamming":
        w = 0.54 - 0.46 * jnp.cos(2.0 * jnp.pi * n / d)
    elif kind == "blackman":
        w = (
            0.42
            - 0.5 * jnp.cos(2.0 * jnp.pi * n / d)
            + 0.08 * jnp.cos(4.0 * jnp.pi * n / d)
        )
    elif kind in (None, "none"):
        w = jnp.ones((length,), jnp.float32)
    else:
        raise ValueError(f"unknown window {kind!r}")
    return w.astype(dtype)


def _histogram_fixed_width(x, *, lo, hi, nbins):
    edges = jnp.linspace(lo, hi, nbins + 1)
    b = jnp.clip(jnp.searchsorted(edges, x.reshape(-1), side="right") - 1, 0, nbins - 1)
    return jnp.zeros((nbins,), jnp.int32).at[b].add(1)


def _image_gradients(img):
    """dy, dx of (B,H,W,C) images stacked on a leading axis of 2 (TF's
    tf.image.image_gradients returns the pair; a single tensor keeps the
    registry's one-output contract)."""
    dy = jnp.concatenate(
        [img[:, 1:] - img[:, :-1], jnp.zeros_like(img[:, :1])], axis=1
    )
    dx = jnp.concatenate(
        [img[:, :, 1:] - img[:, :, :-1], jnp.zeros_like(img[:, :, :1])], axis=2
    )
    return jnp.stack([dy, dx])


def _sobel_edges(img):
    """(B,H,W,C) -> (2,B,H,W,C): vertical/horizontal Sobel responses."""
    ky = jnp.array([[-1, -2, -1], [0, 0, 0], [1, 2, 1]], img.dtype)
    kx = ky.T
    B, H, W, C = img.shape
    x = jnp.moveaxis(img, -1, 1).reshape(B * C, 1, H, W)
    pad = jnp.pad(x, ((0, 0), (0, 0), (1, 1), (1, 1)), mode="reflect")

    def conv(k):
        out = jax.lax.conv_general_dilated(
            pad, k[None, None], (1, 1), "VALID",
            dimension_numbers=("NCHW", "OIHW", "NCHW"),
        )
        return jnp.moveaxis(out.reshape(B, C, H, W), 1, -1)

    return jnp.stack([conv(ky), conv(kx)])


def _total_variation(img):
    dv = jnp.abs(img[:, 1:] - img[:, :-1]).sum(axis=(1, 2, 3))
    dh = jnp.abs(img[:, :, 1:] - img[:, :, :-1]).sum(axis=(1, 2, 3))
    return dv + dh


def _psnr(a, b, *, max_val=1.0):
    mse = jnp.mean(jnp.square(a - b), axis=(-3, -2, -1))
    return 10.0 * jnp.log10(max_val * max_val / jnp.maximum(mse, 1e-12))


def _ssim(a, b, *, max_val=1.0):
    """Global-statistics SSIM per image (windowless simplification of the
    reference's ssim op; exact for the constant-window limit)."""
    axes = (-3, -2, -1)
    mu_a = jnp.mean(a, axis=axes)
    mu_b = jnp.mean(b, axis=axes)
    va = jnp.var(a, axis=axes)
    vb = jnp.var(b, axis=axes)
    cov = jnp.mean(a * b, axis=axes) - mu_a * mu_b
    c1 = (0.01 * max_val) ** 2
    c2 = (0.03 * max_val) ** 2
    return ((2 * mu_a * mu_b + c1) * (2 * cov + c2)) / (
        (mu_a**2 + mu_b**2 + c1) * (va + vb + c2)
    )


def _grayscale_to_rgb(x):
    if x.shape[-1] != 1:
        raise ValueError(
            f"grayscale_to_rgb expects a single channel, got {x.shape[-1]} "
            "(TF semantics: non-1-channel input is an error, not a repeat)"
        )
    return jnp.repeat(x, 3, axis=-1)


def _central_crop(x, fraction):
    """Center-crop the H/W axes of (..., H, W, C) to the given fraction."""
    if not 0.0 < fraction <= 1.0:
        raise ValueError(
            f"central_crop fraction must be in (0, 1], got {fraction}"
        )
    h, w = x.shape[-3], x.shape[-2]
    ch = max(int(round(h * fraction)), 1)
    cw = max(int(round(w * fraction)), 1)
    top, left = (h - ch) // 2, (w - cw) // 2
    return x[..., top : top + ch, left : left + cw, :]


def _fake_quant(x, *, min_val=-6.0, max_val=6.0, num_bits=8):
    """Quantize-dequantize with a straight-through gradient (the
    fake_quant_with_min_max_args role — QAT's core op)."""
    n = 2**num_bits - 1
    scale = (max_val - min_val) / n
    clipped = jnp.clip(x, min_val, max_val)
    q = jnp.round((clipped - min_val) / scale) * scale + min_val
    # straight-through: forward quantized, gradient of the clip
    return clipped + jax.lax.stop_gradient(q - clipped)


def _huber_loss(pred, target, *, delta=1.0):
    d = jnp.abs(pred - target)
    return jnp.mean(jnp.where(d <= delta, 0.5 * d * d, delta * (d - 0.5 * delta)))


def _kl_divergence(p, q):
    """KL(p || q) for distributions on the last axis (stable at p=0)."""
    p = jnp.clip(p, 1e-12, 1.0)
    q = jnp.clip(q, 1e-12, 1.0)
    return jnp.mean(jnp.sum(p * (jnp.log(p) - jnp.log(q)), axis=-1))


def _matrix_band_part(x, *, lower, upper):
    m, n = x.shape[-2], x.shape[-1]
    i = jnp.arange(m)[:, None]
    j = jnp.arange(n)[None, :]
    keep = jnp.ones((m, n), bool)
    if lower >= 0:
        keep &= (i - j) <= lower
    if upper >= 0:
        keep &= (j - i) <= upper
    return jnp.where(keep, x, jnp.zeros((), x.dtype))


def _matrix_set_diag(x, diag):
    x, diag = jnp.asarray(x), jnp.asarray(diag)
    m, n = x.shape[-2], x.shape[-1]
    idx = jnp.arange(min(m, n))
    return x.at[..., idx, idx].set(diag[..., : min(m, n)])


def _matrix_diag(diag):
    diag = jnp.asarray(diag)
    k = diag.shape[-1]
    out = jnp.zeros(diag.shape[:-1] + (k, k), diag.dtype)
    idx = jnp.arange(k)
    return out.at[..., idx, idx].set(diag)


def _rgb_to_hsv(x):
    r, g, b = x[..., 0], x[..., 1], x[..., 2]
    mx = jnp.maximum(jnp.maximum(r, g), b)
    mn = jnp.minimum(jnp.minimum(r, g), b)
    diff = mx - mn
    safe = jnp.where(diff == 0, 1.0, diff)
    h = jnp.where(
        mx == r, (g - b) / safe % 6.0,
        jnp.where(mx == g, (b - r) / safe + 2.0, (r - g) / safe + 4.0),
    ) / 6.0
    h = jnp.where(diff == 0, 0.0, h)
    s = jnp.where(mx == 0, 0.0, diff / jnp.where(mx == 0, 1.0, mx))
    return jnp.stack([h, s, mx], axis=-1)


def _hsv_to_rgb(x):
    h, s, v = x[..., 0] * 6.0, x[..., 1], x[..., 2]
    i = jnp.floor(h)
    f = h - i
    p, q, t = v * (1 - s), v * (1 - s * f), v * (1 - s * (1 - f))
    i = i.astype(jnp.int32) % 6
    r = jnp.select([i == 0, i == 1, i == 2, i == 3, i == 4, i == 5], [v, q, p, p, t, v])
    g = jnp.select([i == 0, i == 1, i == 2, i == 3, i == 4, i == 5], [t, v, v, q, p, p])
    b = jnp.select([i == 0, i == 1, i == 2, i == 3, i == 4, i == 5], [p, p, t, v, v, q])
    return jnp.stack([r, g, b], axis=-1)


def _adjust_hue(x, *, delta):
    hsv = _rgb_to_hsv(x)
    return _hsv_to_rgb(hsv.at[..., 0].set((hsv[..., 0] + delta) % 1.0))


def _adjust_saturation(x, *, factor):
    hsv = _rgb_to_hsv(x)
    return _hsv_to_rgb(hsv.at[..., 1].set(jnp.clip(hsv[..., 1] * factor, 0.0, 1.0)))


def _crop_and_resize(img, boxes, box_ind, *, crop_size):
    """Bilinear crop-and-resize from normalized (y1,x1,y2,x2) boxes
    (reference CropAndResize declarable op / TF semantics)."""
    img = jnp.asarray(img)
    H, W = img.shape[1], img.shape[2]
    ch, cw = crop_size

    def sample(image, ys, xs):
        y0 = jnp.clip(jnp.floor(ys).astype(jnp.int32), 0, H - 1)
        x0 = jnp.clip(jnp.floor(xs).astype(jnp.int32), 0, W - 1)
        y1 = jnp.clip(y0 + 1, 0, H - 1)
        x1 = jnp.clip(x0 + 1, 0, W - 1)
        wy = (ys - y0)[:, None, None]
        wx = (xs - x0)[None, :, None]
        g = lambda yy, xx: image[yy][:, xx]
        return (
            g(y0, x0) * (1 - wy) * (1 - wx)
            + g(y0, x1) * (1 - wy) * wx
            + g(y1, x0) * wy * (1 - wx)
            + g(y1, x1) * wy * wx
        )

    def one(box, bi):
        y1, x1, y2, x2 = box[0], box[1], box[2], box[3]
        ys = y1 * (H - 1) + (y2 - y1) * (H - 1) * jnp.linspace(0.0, 1.0, ch)
        xs = x1 * (W - 1) + (x2 - x1) * (W - 1) * jnp.linspace(0.0, 1.0, cw)
        return sample(img[bi], ys, xs)

    return jax.vmap(one)(boxes, box_ind.astype(jnp.int32))


def _iou(a, b):
    """IoU of two (4,) boxes y1,x1,y2,x2."""
    yy1 = jnp.maximum(a[0], b[0])
    xx1 = jnp.maximum(a[1], b[1])
    yy2 = jnp.minimum(a[2], b[2])
    xx2 = jnp.minimum(a[3], b[3])
    inter = jnp.maximum(yy2 - yy1, 0) * jnp.maximum(xx2 - xx1, 0)
    area = lambda z: jnp.maximum(z[2] - z[0], 0) * jnp.maximum(z[3] - z[1], 0)
    union = area(a) + area(b) - inter
    return jnp.where(union > 0, inter / union, 0.0)


def _non_max_suppression(boxes, scores, *, max_output_size, iou_threshold=0.5,
                         score_threshold=-jnp.inf):
    """Greedy NMS with a STATIC output size (padded with -1) — the
    data-dependent-shape reference op recast for XLA: a lax.fori_loop
    picks the best remaining box `max_output_size` times."""
    boxes, scores = jnp.asarray(boxes), jnp.asarray(scores)
    n = boxes.shape[0]
    alive = scores > score_threshold

    def body(i, st):
        sel, alive = st
        masked = jnp.where(alive, scores, -jnp.inf)
        best = jnp.argmax(masked)
        ok = masked[best] > -jnp.inf
        sel = sel.at[i].set(jnp.where(ok, best, -1))
        ious = jax.vmap(lambda b: _iou(boxes[best], b))(boxes)
        alive = alive & (ious <= iou_threshold) & (jnp.arange(n) != best)
        alive = jnp.where(ok, alive, jnp.zeros_like(alive))
        return sel, alive

    sel0 = jnp.full((max_output_size,), -1, jnp.int32)
    sel, _ = jax.lax.fori_loop(0, max_output_size, body, (sel0, alive))
    return sel


def _space_to_batch(x, *, block, paddings=((0, 0), (0, 0))):
    x = jnp.pad(x, ((0, 0), tuple(paddings[0]), tuple(paddings[1]), (0, 0)))
    n, h, w, c = x.shape
    x = x.reshape(n, h // block, block, w // block, block, c)
    return x.transpose(2, 4, 0, 1, 3, 5).reshape(
        n * block * block, h // block, w // block, c
    )


def _batch_to_space(x, *, block, crops=((0, 0), (0, 0))):
    nb, h, w, c = x.shape
    n = nb // (block * block)
    x = x.reshape(block, block, n, h, w, c).transpose(2, 3, 0, 4, 1, 5)
    x = x.reshape(n, h * block, w * block, c)
    (ct, cb), (cl, cr) = crops
    return x[:, ct : x.shape[1] - cb or None, cl : x.shape[2] - cr or None, :]


def _confusion_matrix(labels, preds, *, num_classes):
    idx = labels.astype(jnp.int32) * num_classes + preds.astype(jnp.int32)
    return jnp.bincount(idx, length=num_classes * num_classes).reshape(
        num_classes, num_classes
    ).astype(jnp.float32)


def _percentile(x, *, q, axis=None):
    return jnp.percentile(x, q, axis=_ax(axis))


def _standardize(x, *, axis=-1, epsilon=1e-5):
    mean = jnp.mean(x, axis=_ax(axis), keepdims=True)
    var = jnp.var(x, axis=_ax(axis), keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + epsilon)


def _lrn(x, *, size=5, alpha=1e-4, beta=0.75, bias=2.0):
    """Local response normalization across the TRAILING (channel) axis
    (channels-last; the ONNX/reference op normalizes across C)."""
    sq = jnp.square(x)
    # ONNX window: [c - floor((size-1)/2), c + ceil((size-1)/2)] — the
    # extra element of an even window goes RIGHT
    half = (size - 1) // 2
    pad = [(0, 0)] * (x.ndim - 1) + [(half, size - 1 - half)]
    cs = jnp.cumsum(jnp.pad(sq, pad), axis=-1)
    cs = jnp.pad(cs, [(0, 0)] * (x.ndim - 1) + [(1, 0)])
    win = cs[..., size:] - cs[..., :-size]
    return x / (bias + (alpha / size) * win) ** beta


def _clip_by_norm(x, *, clip_norm, axis=None):
    n = jnp.sqrt(jnp.sum(jnp.square(x), axis=_ax(axis), keepdims=True))
    return jnp.where(n > clip_norm, x * clip_norm / jnp.maximum(n, 1e-12), x)


OPS: dict[str, callable] = {
    # elementwise arithmetic
    "add": jnp.add,
    "sub": jnp.subtract,
    "mul": jnp.multiply,
    "div": jnp.divide,
    "pow": jnp.power,
    "neg": jnp.negative,
    "abs": jnp.abs,
    "exp": jnp.exp,
    "log": jnp.log,
    "sqrt": jnp.sqrt,
    "square": jnp.square,
    "rsqrt": jax.lax.rsqrt,
    "sign": jnp.sign,
    "floor": jnp.floor,
    "ceil": jnp.ceil,
    "clip": lambda x, *, lo, hi: jnp.clip(x, lo, hi),
    "maximum": jnp.maximum,
    "minimum": jnp.minimum,
    # comparisons / selection
    "greater": lambda a, b: (a > b).astype(jnp.float32),
    "less": lambda a, b: (a < b).astype(jnp.float32),
    "equal": lambda a, b: (a == b).astype(jnp.float32),
    "where": jnp.where,
    # linalg
    "matmul": jnp.matmul,
    "transpose": lambda x, *, axes=None: jnp.transpose(x, axes),
    "einsum": lambda *xs, equation: jnp.einsum(equation, *xs),
    "tensordot": lambda a, b, *, axes=2: jnp.tensordot(a, b, axes=axes),
    # shape
    "reshape": lambda x, *, shape: jnp.reshape(x, shape),
    # ONNX Reshape semantics: 0 = copy the input's dim at that position
    "onnx_reshape": lambda x, *, shape: jnp.reshape(
        x, tuple(x.shape[i] if s == 0 else s for i, s in enumerate(shape))
    ),
    # ONNX Slice semantics: negative starts/ends/axes count from the end
    # (Python's exact slicing rules); INT64_MAX-ish ends mean "to the end"
    "onnx_slice": _onnx_slice,
    "concat": lambda *xs, axis=-1: jnp.concatenate(xs, axis=axis),
    "stack": lambda *xs, axis=0: jnp.stack(xs, axis=axis),
    "squeeze": lambda x, *, axis: jnp.squeeze(x, axis=axis),
    "expand_dims": lambda x, *, axis: jnp.expand_dims(x, axis),
    # static slice; size -1 = "to end of dim" (TF convention)
    "slice": lambda x, *, begin, size: x[
        tuple(slice(b, None if s == -1 else b + s) for b, s in zip(begin, size))
    ],
    "gather": lambda x, idx, *, axis=0: jnp.take(x, idx.astype(jnp.int32), axis=axis),
    "one_hot": lambda x, *, depth, on_value=1.0, off_value=0.0, axis=-1: (
        jax.nn.one_hot(x.astype(jnp.int32), depth, axis=axis) * (on_value - off_value)
        + off_value
    ),
    "tile": lambda x, *, reps: jnp.tile(x, reps),
    "pad": lambda x, *, paddings, constant_values=0.0: jnp.pad(
        x, paddings, constant_values=constant_values
    ),
    # reductions
    "sum": lambda x, *, axis=None, keepdims=False: jnp.sum(x, axis=_ax(axis), keepdims=keepdims),
    "mean": lambda x, *, axis=None, keepdims=False: jnp.mean(x, axis=_ax(axis), keepdims=keepdims),
    "max": lambda x, *, axis=None, keepdims=False: jnp.max(x, axis=_ax(axis), keepdims=keepdims),
    "min": lambda x, *, axis=None, keepdims=False: jnp.min(x, axis=_ax(axis), keepdims=keepdims),
    "prod": lambda x, *, axis=None, keepdims=False: jnp.prod(x, axis=_ax(axis), keepdims=keepdims),
    "var": lambda x, *, axis=None, keepdims=False: jnp.var(x, axis=_ax(axis), keepdims=keepdims),
    "std": lambda x, *, axis=None, keepdims=False: jnp.std(x, axis=_ax(axis), keepdims=keepdims),
    "argmax": lambda x, *, axis=-1: jnp.argmax(x, axis=axis),
    "argmin": lambda x, *, axis=-1: jnp.argmin(x, axis=axis),
    "norm2": lambda x, *, axis=None: jnp.sqrt(jnp.sum(jnp.square(x), axis=_ax(axis))),
    "cumsum": lambda x, *, axis=0: jnp.cumsum(x, axis=axis),
    # activations
    "relu": jax.nn.relu,
    "relu6": jax.nn.relu6,
    "leaky_relu": lambda x, *, alpha=0.01: jax.nn.leaky_relu(x, alpha),
    "elu": jax.nn.elu,
    "selu": jax.nn.selu,
    "gelu": jax.nn.gelu,
    "silu": jax.nn.silu,
    "sigmoid": jax.nn.sigmoid,
    "tanh": jnp.tanh,
    "softmax": lambda x, *, axis=-1: jax.nn.softmax(x, axis=axis),
    "log_softmax": lambda x, *, axis=-1: jax.nn.log_softmax(x, axis=axis),
    "softplus": jax.nn.softplus,
    "sin": jnp.sin,
    "cos": jnp.cos,
    # trig / hyperbolic family
    "tan": jnp.tan,
    "asin": jnp.arcsin,
    "acos": jnp.arccos,
    "atan": jnp.arctan,
    "sinh": jnp.sinh,
    "cosh": jnp.cosh,
    "asinh": jnp.arcsinh,
    "acosh": jnp.arccosh,
    "atanh": jnp.arctanh,
    # rounding / checks
    "round": jnp.round,
    "trunc": jnp.trunc,
    "is_nan": lambda x: jnp.isnan(x).astype(jnp.float32),
    "is_inf": lambda x: jnp.isinf(x).astype(jnp.float32),
    "is_finite": lambda x: jnp.isfinite(x).astype(jnp.float32),
    "log1p": jnp.log1p,
    "expm1": jnp.expm1,
    "erfc": jax.scipy.special.erfc,
    "cube": lambda x: x * x * x,
    "softsign": jax.nn.soft_sign,
    "hard_sigmoid": jax.nn.hard_sigmoid,
    "hard_tanh": lambda x: jnp.clip(x, -1.0, 1.0),
    # the DSL activation's exact rational-polynomial form (a graph op and a
    # layer activation with the same name must not disagree)
    "rationaltanh": _rationaltanh,
    "logsumexp": lambda x, *, axis=None, keepdims=False: (
        jax.scipy.special.logsumexp(x, axis=_ax(axis), keepdims=keepdims)
    ),
    "cumprod": lambda x, *, axis=0: jnp.cumprod(x, axis=axis),
    # ordering / selection
    "sort": lambda x, *, axis=-1, descending=False: (
        -jnp.sort(-x, axis=axis) if descending else jnp.sort(x, axis=axis)
    ),
    "argsort": lambda x, *, axis=-1: jnp.argsort(x, axis=axis),
    "top_k_values": lambda x, *, k: jax.lax.top_k(x, k)[0],
    "top_k_indices": lambda x, *, k: jax.lax.top_k(x, k)[1],
    # segment reductions (static num_segments for XLA shapes)
    "segment_sum": lambda x, ids, *, num_segments: jax.ops.segment_sum(
        x, ids.astype(jnp.int32), num_segments=num_segments
    ),
    "segment_max": lambda x, ids, *, num_segments: jax.ops.segment_max(
        x, ids.astype(jnp.int32), num_segments=num_segments
    ),
    "segment_min": lambda x, ids, *, num_segments: jax.ops.segment_min(
        x, ids.astype(jnp.int32), num_segments=num_segments
    ),
    "segment_mean": lambda x, ids, *, num_segments: (
        jax.ops.segment_sum(x, ids.astype(jnp.int32), num_segments=num_segments)
        / jnp.maximum(
            jax.ops.segment_sum(
                jnp.ones_like(x), ids.astype(jnp.int32),
                num_segments=num_segments,
            ),
            1.0,
        )
    ),
    "reverse": lambda x, *, axis: jnp.flip(x, axis=axis),
    "roll": lambda x, *, shift, axis: jnp.roll(x, shift, axis=axis),
    # TF-import primitives
    "identity": lambda x: x,
    "stop_gradient": jax.lax.stop_gradient,
    "erf": jax.scipy.special.erf,
    "cast": lambda x, *, dtype: x.astype(dtype),
    "squared_difference": lambda a, b: jnp.square(a - b),
    "greater_equal": lambda a, b: (a >= b).astype(jnp.float32),
    "less_equal": lambda a, b: (a <= b).astype(jnp.float32),
    "not_equal": lambda a, b: (a != b).astype(jnp.float32),
    "logical_and": lambda a, b: jnp.logical_and(a > 0, b > 0).astype(jnp.float32),
    "logical_or": lambda a, b: jnp.logical_or(a > 0, b > 0).astype(jnp.float32),
    "logical_not": lambda a: jnp.logical_not(a > 0).astype(jnp.float32),
    "reciprocal": lambda x: 1.0 / x,
    "floor_div": lambda a, b: jnp.floor_divide(a, b),
    "mod": jnp.mod,
    "atan2": jnp.arctan2,
    # attention — the reference's multi_head_dot_product_attention custom op
    # (q,k,v: (B,T,H,D); flash-dispatched on TPU for long sequences)
    "multi_head_dot_product_attention": _mhdpa,
    # nn composite
    "conv2d": _conv2d,
    "max_pool2d": _max_pool2d,
    "avg_pool2d": _avg_pool2d,
    "layer_norm": _layer_norm,
    "bias_add": lambda x, b: x + b,
    "dropout": lambda x, *, rate=0.5, seed=0: x,  # inference identity; fit wires real rng
    # losses
    "softmax_cross_entropy": _softmax_cross_entropy,
    "sparse_softmax_cross_entropy": _sparse_softmax_cross_entropy,
    "sigmoid_cross_entropy": _sigmoid_cross_entropy,
    "mse_loss": lambda pred, lab: jnp.mean(jnp.square(pred - lab)),
    "l1_loss": lambda pred, lab: jnp.mean(jnp.abs(pred - lab)),
    # cnn extras (sd.cnn namespace; conv2d/pooling above)
    "conv1d": _conv1d,
    "conv3d": _conv3d,
    "depthwise_conv2d": _depthwise_conv2d,
    "deconv2d": _deconv2d,
    "batch_norm": _batch_norm,
    "im2col": lambda x, *, kernel, stride=(1, 1): jax.lax.conv_general_dilated_patches(
        x, filter_shape=tuple(kernel), window_strides=tuple(stride), padding="VALID",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    ),
    "space_to_depth": lambda x, *, block: x.reshape(
        x.shape[0], x.shape[1] // block, block, x.shape[2] // block, block, x.shape[3]
    ).transpose(0, 1, 3, 2, 4, 5).reshape(
        x.shape[0], x.shape[1] // block, x.shape[2] // block, block * block * x.shape[3]
    ),
    "depth_to_space": lambda x, *, block: x.reshape(
        x.shape[0], x.shape[1], x.shape[2], block, block, x.shape[3] // (block * block)
    ).transpose(0, 1, 3, 2, 4, 5).reshape(
        x.shape[0], x.shape[1] * block, x.shape[2] * block, x.shape[3] // (block * block)
    ),
    # rnn cells (sd.rnn namespace; reference lstmLayer/gruCell declarable ops)
    "lstm_cell": _lstm_cell,
    "gru_cell": _gru_cell,
    # image ops (sd.image namespace)
    "resize": _resize,
    "crop": _crop,
    "flip_lr": lambda x: x[:, :, ::-1, :],
    "flip_ud": lambda x: x[:, ::-1, :, :],
    "adjust_brightness": lambda x, *, delta: x + delta,
    "adjust_contrast": _adjust_contrast,
    "rgb_to_grayscale": _rgb_to_grayscale,
    "normalize_image": lambda x, mean, std: (x - mean) / std,
    # linalg (sd.linalg namespace)
    "inv": jnp.linalg.inv,
    "det": jnp.linalg.det,
    "cholesky": jnp.linalg.cholesky,
    "solve": jnp.linalg.solve,
    "svd": lambda x: jnp.linalg.svd(x, compute_uv=False),
    "qr": lambda x: jnp.linalg.qr(x)[0],
    "matrix_trace": lambda x: jnp.trace(x, axis1=-2, axis2=-1),
    "diag": jnp.diag,
    "diag_part": lambda x: jnp.diagonal(x, axis1=-2, axis2=-1),
    "matrix_transpose": lambda x: jnp.swapaxes(x, -1, -2),
    "lstsq": lambda a, b: jnp.linalg.lstsq(a, b)[0],
    "triu": lambda x, *, k=0: jnp.triu(x, k),
    "tril": lambda x, *, k=0: jnp.tril(x, k),
    # bitwise (sd.bitwise namespace; integer inputs)
    "bitwise_and": lambda a, b: jnp.bitwise_and(a.astype(jnp.int32), b.astype(jnp.int32)),
    "bitwise_or": lambda a, b: jnp.bitwise_or(a.astype(jnp.int32), b.astype(jnp.int32)),
    "bitwise_xor": lambda a, b: jnp.bitwise_xor(a.astype(jnp.int32), b.astype(jnp.int32)),
    "bitwise_not": lambda a: jnp.bitwise_not(a.astype(jnp.int32)),
    "left_shift": lambda a, *, bits: jnp.left_shift(a.astype(jnp.int32), bits),
    "right_shift": lambda a, *, bits: jnp.right_shift(a.astype(jnp.int32), bits),
    # reduce3 family (reference legacy_ops reduce3: pairwise distances)
    "dot": lambda a, b, *, axis=None: jnp.sum(a * b, axis=_ax(axis)),
    "cosine_similarity": lambda a, b, *, axis=-1: jnp.sum(a * b, axis=_ax(axis))
    / jnp.maximum(
        jnp.linalg.norm(a, axis=_ax(axis)) * jnp.linalg.norm(b, axis=_ax(axis)),
        1e-12,
    ),
    "cosine_distance": lambda a, b, *, axis=-1: 1.0
    - OPS["cosine_similarity"](a, b, axis=axis),
    "euclidean_distance": lambda a, b, *, axis=None: jnp.sqrt(
        jnp.sum(jnp.square(a - b), axis=_ax(axis))
    ),
    "manhattan_distance": lambda a, b, *, axis=None: jnp.sum(
        jnp.abs(a - b), axis=_ax(axis)
    ),
    "hamming_distance": lambda a, b, *, axis=None: jnp.sum(
        (a != b).astype(jnp.float32), axis=_ax(axis)
    ),
    "jaccard_distance": lambda a, b, *, axis=None: 1.0
    - jnp.sum(jnp.minimum(a, b), axis=_ax(axis))
    / jnp.maximum(jnp.sum(jnp.maximum(a, b), axis=_ax(axis)), 1e-12),
    # reduction breadth (reference reduce float/same families)
    "norm1": lambda x, *, axis=None, keepdims=False: jnp.sum(
        jnp.abs(x), axis=_ax(axis), keepdims=keepdims
    ),
    "norm_max": lambda x, *, axis=None, keepdims=False: jnp.max(
        jnp.abs(x), axis=_ax(axis), keepdims=keepdims
    ),
    "squared_norm": lambda x, *, axis=None, keepdims=False: jnp.sum(
        jnp.square(x), axis=_ax(axis), keepdims=keepdims
    ),
    "count_nonzero": lambda x, *, axis=None: jnp.sum(
        (x != 0).astype(jnp.float32), axis=_ax(axis)
    ),
    "count_zero": lambda x, *, axis=None: jnp.sum(
        (x == 0).astype(jnp.float32), axis=_ax(axis)
    ),
    "amean": lambda x, *, axis=None: jnp.mean(jnp.abs(x), axis=_ax(axis)),
    "amax": lambda x, *, axis=None: jnp.max(jnp.abs(x), axis=_ax(axis)),
    "amin": lambda x, *, axis=None: jnp.min(jnp.abs(x), axis=_ax(axis)),
    "entropy": _entropy,
    "shannon_entropy": lambda x, *, axis=None: _entropy(x, axis=axis) / jnp.log(2.0),
    "log_entropy": lambda x, *, axis=None: jnp.log(
        jnp.maximum(_entropy(x, axis=axis), 1e-12)
    ),
    "moments": _moments,
    "percentile": _percentile,
    "median": lambda x, *, axis=None: jnp.median(x, axis=_ax(axis)),
    # indexreduce family
    "iamax": lambda x, *, axis=-1: jnp.argmax(jnp.abs(x), axis=axis),
    "iamin": lambda x, *, axis=-1: jnp.argmin(jnp.abs(x), axis=axis),
    # -1 when no element matches (reference index-accumulation semantics)
    "first_index_nonzero": lambda x, *, axis=-1: jnp.where(
        jnp.any(x != 0, axis=axis),
        jnp.argmax((x != 0).astype(jnp.int32), axis=axis),
        -1,
    ),
    "last_index_nonzero": lambda x, *, axis=-1: jnp.where(
        jnp.any(x != 0, axis=axis),
        x.shape[axis]
        - 1
        - jnp.argmax(jnp.flip((x != 0).astype(jnp.int32), axis=axis), axis=axis),
        -1,
    ),
    # scatter family (reference scatter_add/upd/max/min declarable ops)
    "scatter_add": _scatter("add"),
    "scatter_sub": lambda ref, idx, upd: _scatter("add")(ref, idx, -upd),
    "scatter_mul": _scatter("multiply"),
    "scatter_update": _scatter("set"),
    "scatter_max": _scatter("max"),
    "scatter_min": _scatter("min"),
    "gather_nd": _gather_nd,
    "scatter_nd": _scatter_nd,
    # random family (seed is a static attr -> deterministic, jit-safe)
    "random_normal": _rand("normal"),
    "random_uniform": _rand("uniform"),
    "random_bernoulli": _rand("bernoulli"),
    "random_exponential": _rand("exponential"),
    # creation
    "zeros_like": jnp.zeros_like,
    "ones_like": jnp.ones_like,
    "full_like": lambda x, *, value: jnp.full_like(x, value),
    "eye": lambda *, n, m=None: jnp.eye(n, m),
    "linspace": lambda *, start, stop, num: jnp.linspace(start, stop, num),
    "range": lambda *, start, limit, delta=1: jnp.arange(start, limit, delta,
                                                         dtype=jnp.float32),
    "fill": lambda *, shape, value: jnp.full(tuple(shape), value, jnp.float32),
    # sequence ops
    "reverse_sequence": _reverse_sequence,
    "sequence_mask": _sequence_mask,
    # matrix structure
    "matrix_band_part": _matrix_band_part,
    "matrix_diag": _matrix_diag,
    "matrix_set_diag": _matrix_set_diag,
    # image breadth
    "rgb_to_hsv": _rgb_to_hsv,
    "hsv_to_rgb": _hsv_to_rgb,
    "adjust_hue": _adjust_hue,
    "adjust_saturation": _adjust_saturation,
    "crop_and_resize": _crop_and_resize,
    "non_max_suppression": _non_max_suppression,
    "space_to_batch": _space_to_batch,
    "batch_to_space": _batch_to_space,
    "broadcast_to": lambda x, *, shape: jnp.broadcast_to(x, tuple(shape)),
    "lrn": _lrn,
    # nn / misc breadth
    "prelu": lambda x, alpha: jnp.where(x >= 0, x, alpha * x),
    "thresholded_relu": lambda x, *, theta=1.0: jnp.where(x > theta, x, 0.0),
    "log_sigmoid": jax.nn.log_sigmoid,
    "mish": lambda x: x * jnp.tanh(jax.nn.softplus(x)),
    "swish": jax.nn.silu,
    "standardize": _standardize,
    "clip_by_norm": _clip_by_norm,
    "xw_plus_b": lambda x, w, b: x @ w + b,
    "confusion_matrix": _confusion_matrix,
    # special math (reference transform-strict family)
    "lgamma": jax.scipy.special.gammaln,
    "digamma": jax.scipy.special.digamma,
    "igamma": jax.scipy.special.gammainc,
    "igammac": jax.scipy.special.gammaincc,
    "zeta": jax.scipy.special.zeta,
    "polygamma": lambda x, *, n: jax.scipy.special.polygamma(n, x),
    "betainc": jax.scipy.special.betainc,
    "truncate_div": lambda a, b: jnp.trunc(a / b),
    "floor_mod": jnp.mod,
    # signal / audio family (reference audio declarable ops); periodic=True
    # matches tf.signal defaults (goldens vs TF graphs depend on it)
    "hann_window": lambda *, length, periodic=True: _window(
        "hann", length, periodic=periodic
    ),
    "hamming_window": lambda *, length, periodic=True: _window(
        "hamming", length, periodic=periodic
    ),
    "blackman_window": lambda *, length, periodic=True: _window(
        "blackman", length, periodic=periodic
    ),
    "frame": _frame,
    "stft": _stft,
    "istft": _istft,
    "fft": lambda x, *, n=None: jnp.fft.fft(x, n=n, axis=-1),
    "ifft": lambda x, *, n=None: jnp.fft.ifft(x, n=n, axis=-1),
    "rfft": lambda x, *, n=None: jnp.fft.rfft(x, n=n, axis=-1),
    "irfft": lambda x, *, n=None: jnp.fft.irfft(x, n=n, axis=-1),
    "fft2": lambda x: jnp.fft.fft2(x),
    "ifft2": lambda x: jnp.fft.ifft2(x),
    "real": jnp.real,
    "imag": jnp.imag,
    "complex_abs": lambda x: jnp.abs(x),
    "angle": jnp.angle,
    # exotic reductions tail
    "all": lambda x, *, axis=None, keepdims=False: jnp.all(
        x != 0, axis=_ax(axis), keepdims=keepdims
    ).astype(jnp.float32),
    "any": lambda x, *, axis=None, keepdims=False: jnp.any(
        x != 0, axis=_ax(axis), keepdims=keepdims
    ).astype(jnp.float32),
    "cumulative_logsumexp": lambda x, *, axis=-1: jax.lax.cumlogsumexp(
        x, axis=axis % x.ndim
    ),
    "segment_prod": lambda x, ids, *, num_segments: jax.ops.segment_prod(
        x, ids.astype(jnp.int32), num_segments
    ),
    # set / bucketing ops (static output sizes: XLA needs them)
    "unique_with_pad": lambda x, *, size, fill=0: jnp.unique(
        x, size=size, fill_value=fill
    ),
    "bincount": lambda x, *, length: jnp.bincount(
        x.astype(jnp.int32).reshape(-1), length=length
    ),
    "searchsorted": lambda sorted_seq, values, *, side="left": jnp.searchsorted(
        sorted_seq, values, side=side
    ),
    "invert_permutation": lambda x: jnp.argsort(x.astype(jnp.int32)),
    "histogram_fixed_width": _histogram_fixed_width,
    "nan_to_num": lambda x, *, nan=0.0, posinf=None, neginf=None: jnp.nan_to_num(
        x, nan=nan, posinf=posinf, neginf=neginf
    ),
    # linalg tail
    "eigh_values": lambda x: jnp.linalg.eigvalsh(x),
    "eigh_vectors": lambda x: jnp.linalg.eigh(x)[1],
    "logdet": lambda x: jnp.linalg.slogdet(x)[1],
    "slogdet_sign": lambda x: jnp.linalg.slogdet(x)[0],
    "pinv": jnp.linalg.pinv,
    "triangular_solve": lambda a, b, *, lower=True: (
        jax.scipy.linalg.solve_triangular(a, b, lower=lower)
    ),
    "matrix_power": lambda x, *, n: jnp.linalg.matrix_power(x, n),
    "kron": jnp.kron,
    "matrix_rank": lambda x: jnp.linalg.matrix_rank(x).astype(jnp.float32),
    "expm": jax.scipy.linalg.expm,
    # loss-function tail (reference ILossFunction family)
    "huber_loss": _huber_loss,
    "hinge_loss": lambda pred, target: jnp.mean(
        jnp.maximum(0.0, 1.0 - target * pred)
    ),
    "log_loss": lambda pred, target: -jnp.mean(
        target * jnp.log(jnp.clip(pred, 1e-7, 1.0))
        + (1.0 - target) * jnp.log(jnp.clip(1.0 - pred, 1e-7, 1.0))
    ),
    "absolute_difference": lambda pred, target: jnp.mean(jnp.abs(pred - target)),
    "poisson_loss": lambda pred, target: jnp.mean(
        pred - target * jnp.log(jnp.clip(pred, 1e-7, None))
    ),
    "kl_divergence": _kl_divergence,
    "cosine_proximity_loss": lambda pred, target: -jnp.mean(
        jnp.sum(pred * target, -1)
        / jnp.maximum(
            jnp.linalg.norm(pred, axis=-1) * jnp.linalg.norm(target, axis=-1),
            1e-12,
        )
    ),
    # random tail
    "random_gamma": _rand("gamma"),
    "random_poisson": _rand("poisson"),
    "random_truncated_normal": _rand("truncated_normal"),
    "random_shuffle": _random_shuffle,
    "random_categorical": lambda logits, *, num_samples, seed=0: jnp.moveaxis(
        jax.random.categorical(
            jax.random.key(seed), logits,
            shape=(num_samples,) + logits.shape[:-1],
        ),
        0, -1,
    ),
    "random_laplace": lambda *, shape, seed=0: jax.random.laplace(
        jax.random.key(seed), tuple(shape)
    ),
    "random_cauchy": lambda *, shape, seed=0: jax.random.cauchy(
        jax.random.key(seed), tuple(shape)
    ),
    "random_rademacher": lambda *, shape, seed=0: jax.random.rademacher(
        jax.random.key(seed), tuple(shape)
    ).astype(jnp.float32),
    "random_beta": lambda *, shape, a=1.0, b=1.0, seed=0: jax.random.beta(
        jax.random.key(seed), a, b, tuple(shape)
    ),
    # activation tail
    "hard_swish": jax.nn.hard_swish,
    "celu": lambda x, *, alpha=1.0: jax.nn.celu(x, alpha),
    "glu": lambda x, *, axis=-1: jax.nn.glu(x, axis=axis),
    "softshrink": lambda x, *, lambd=0.5: jnp.sign(x) * jnp.maximum(
        jnp.abs(x) - lambd, 0.0
    ),
    "hardshrink": lambda x, *, lambd=0.5: jnp.where(jnp.abs(x) > lambd, x, 0.0),
    "tanhshrink": lambda x: x - jnp.tanh(x),
    # elementwise tail (reference transform-same/strict stragglers)
    "rint": jnp.rint,
    "heaviside": lambda x, *, value=0.5: jnp.heaviside(x, value),
    "copysign": jnp.copysign,
    "nextafter": jnp.nextafter,
    "deg2rad": jnp.deg2rad,
    "rad2deg": jnp.rad2deg,
    "sinc": jnp.sinc,
    "logaddexp": jnp.logaddexp,
    "logaddexp2": jnp.logaddexp2,
    "hypot": jnp.hypot,
    "signbit": lambda x: jnp.signbit(x).astype(jnp.float32),
    "ldexp": lambda x, *, exp: jnp.ldexp(x, exp),
    "logit": jax.scipy.special.logit,
    "erfinv": jax.scipy.special.erfinv,
    "ndtr": jax.scipy.special.ndtr,
    "ndtri": jax.scipy.special.ndtri,
    "lerp": lambda a, b, *, weight: a + weight * (b - a),
    # NOTE: without jax_enable_x64 the widest integer is int32, so counts
    # are exact only for values representable in the input's jnp dtype
    "popcount": lambda x: jnp.bitwise_count(jnp.asarray(x)).astype(jnp.int32),
    "isclose": lambda a, b, *, rtol=1e-5, atol=1e-8: jnp.isclose(
        a, b, rtol=rtol, atol=atol
    ).astype(jnp.float32),
    # NaN-aware / range reductions
    "nansum": lambda x, *, axis=None, keepdims=False: jnp.nansum(
        x, axis=_ax(axis), keepdims=keepdims
    ),
    "nanmean": lambda x, *, axis=None, keepdims=False: jnp.nanmean(
        x, axis=_ax(axis), keepdims=keepdims
    ),
    "nanmax": lambda x, *, axis=None, keepdims=False: jnp.nanmax(
        x, axis=_ax(axis), keepdims=keepdims
    ),
    "nanmin": lambda x, *, axis=None, keepdims=False: jnp.nanmin(
        x, axis=_ax(axis), keepdims=keepdims
    ),
    "nanstd": lambda x, *, axis=None, keepdims=False: jnp.nanstd(
        x, axis=_ax(axis), keepdims=keepdims
    ),
    "ptp": lambda x, *, axis=None: jnp.ptp(x, axis=_ax(axis)),
    "cummax": lambda x, *, axis=-1: jax.lax.cummax(x, axis=axis % x.ndim),
    "cummin": lambda x, *, axis=-1: jax.lax.cummin(x, axis=axis % x.ndim),
    # linalg tail 2
    # scipy lu_factor semantics: combined LU in one matrix (pivots are
    # implementation detail; permute_l form would silently DROP U)
    "lu_factor": lambda x: jax.scipy.linalg.lu_factor(x)[0],
    "outer": jnp.outer,
    "cross": lambda a, b, *, axis=-1: jnp.cross(a, b, axis=axis),
    "vander": lambda x, *, n: jnp.vander(x, n),
    "diagflat": jnp.diagflat,
    "matrix_norm": lambda x, *, ord="fro": jnp.linalg.norm(
        x, ord=ord, axis=(-2, -1)
    ),
    "cond_number": lambda x: jnp.linalg.cond(x),
    # image tail
    "image_gradients": _image_gradients,
    "sobel_edges": _sobel_edges,
    "total_variation": _total_variation,
    "psnr": _psnr,
    "ssim": _ssim,
    "rot90": lambda x, *, k=1: jnp.rot90(x, k, axes=(-3, -2)),
    "grayscale_to_rgb": lambda x: _grayscale_to_rgb(x),
    "central_crop": lambda x, *, fraction: _central_crop(x, fraction),
    # quantization
    "fake_quant": _fake_quant,
    # loss tail 2
    "weighted_cross_entropy_with_logits": lambda logits, labels, *, pos_weight: (
        jnp.mean(
            (1 - labels) * logits
            + (1 + (pos_weight - 1) * labels)
            * jnp.log1p(jnp.exp(-jnp.abs(logits)))
            + jnp.maximum(-logits, 0.0) * (1 + (pos_weight - 1) * labels)
        )
    ),
    # stable form: log(cosh(d)) = |d| + softplus(-2|d|) - log(2) — the
    # direct cosh overflows f32 (inf/NaN grads) beyond |d| ~ 89
    "log_cosh_loss": lambda pred, target: jnp.mean(
        jnp.abs(pred - target)
        + jax.nn.softplus(-2.0 * jnp.abs(pred - target))
        - jnp.log(2.0)
    ),
}

OPS["extract_image_patches"] = OPS["im2col"]
# jax.ops.segment_* are unsorted-safe (indices_are_sorted=False default),
# so TF's unsorted_segment_* names alias the same implementations —
# except max/min, where TF fills EMPTY segments with the dtype's finite
# lowest/highest while jax yields -inf/+inf (inf * 0 downstream would
# produce NaN where TF produces 0)
for _k in ("sum", "mean", "prod"):
    OPS[f"unsorted_segment_{_k}"] = OPS[f"segment_{_k}"]


def _unsorted_segment_minmax(kind):
    def fn(x, ids, *, num_segments):
        ids = ids.astype(jnp.int32)
        base = jax.ops.segment_max if kind == "max" else jax.ops.segment_min
        out = base(x, ids, num_segments)
        cnt = jax.ops.segment_sum(
            jnp.ones((x.shape[0],), jnp.float32), ids, num_segments
        )
        if jnp.issubdtype(x.dtype, jnp.floating):
            info = jnp.finfo(x.dtype)
        else:
            info = jnp.iinfo(x.dtype)
        fill = info.min if kind == "max" else info.max
        shape = (num_segments,) + (1,) * (x.ndim - 1)
        return jnp.where(cnt.reshape(shape) > 0, out, fill)

    return fn


OPS["unsorted_segment_max"] = _unsorted_segment_minmax("max")
OPS["unsorted_segment_min"] = _unsorted_segment_minmax("min")


def _ax(axis):
    if axis is None:
        return None
    if isinstance(axis, (list, tuple)):
        return tuple(axis)
    return axis


def get_op(name: str):
    if name not in OPS:
        raise KeyError(f"unknown autodiff op {name!r}; known: {sorted(OPS)}")
    return OPS[name]


# ---------------------------------------------------------------------------
# Round-4 op tail — pushes the registry toward the reference's ~500
# declarable ops (SURVEY.md §2.1).  Everything here is static-shape,
# jit-safe, and differentiable where the reference's op is.


def _ctc_loss(logits, labels, *, logit_lengths=None, label_lengths=None,
              blank=0):
    """Connectionist temporal classification loss (reference `ctc_loss`,
    speech stacks).  logits (B,T,C) unnormalized; labels (B,S) int ids.
    Standard log-alpha forward recursion over the blank-interleaved label
    string, as one lax.scan — differentiable, so the gradient is the full
    CTC posterior (no custom backward needed)."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    B, T, C = logits.shape
    S = labels.shape[1]
    labels = labels.astype(jnp.int32)
    if logit_lengths is None:
        logit_lengths = jnp.full((B,), T, jnp.int32)
    if label_lengths is None:
        label_lengths = jnp.full((B,), S, jnp.int32)
    logit_lengths = logit_lengths.astype(jnp.int32)
    label_lengths = label_lengths.astype(jnp.int32)
    L = 2 * S + 1
    ext = jnp.full((B, L), blank, jnp.int32).at[:, 1::2].set(labels)
    NEG = jnp.float32(-1e30)

    # skip transition s-2 -> s allowed when ext[s] is a label differing
    # from ext[s-2]
    if L >= 3:
        prev2 = jnp.pad(ext[:, :-2], ((0, 0), (2, 0)), constant_values=-1)
    else:
        prev2 = jnp.full_like(ext, -1)
    can_skip = (ext != blank) & (ext != prev2)

    emit0 = jnp.take_along_axis(logp[:, 0], ext, axis=-1)      # (B, L)
    pos = jnp.arange(L)[None, :]
    alpha = jnp.where(pos <= 1, emit0, NEG)
    if S == 0:
        alpha = jnp.where(pos == 0, emit0, NEG)

    def lse(a, b):
        m = jnp.maximum(a, b)
        return m + jnp.log1p(jnp.exp(jnp.minimum(a, b) - m))

    def step(alpha, inp):
        logp_t, t = inp
        shift1 = jnp.pad(alpha[:, :-1], ((0, 0), (1, 0)), constant_values=NEG)
        # L<3 (empty label string): no skip transitions exist, and the
        # pad-by-2 would widen the scan carry from (B,1) to (B,2)
        shift2 = (
            jnp.pad(alpha[:, :-2], ((0, 0), (2, 0)), constant_values=NEG)
            if L >= 3 else jnp.full_like(alpha, NEG)
        )
        acc = lse(alpha, shift1)
        acc = jnp.where(can_skip, lse(acc, shift2), acc)
        emit = jnp.take_along_axis(logp_t, ext, axis=-1)
        new = acc + emit
        # past each example's input length the recursion freezes
        live = (t < logit_lengths)[:, None]
        return jnp.where(live, new, alpha), None

    ts = jnp.arange(1, T)
    alpha, _ = jax.lax.scan(step, alpha, (jnp.swapaxes(logp, 0, 1)[1:], ts))
    last = 2 * label_lengths - 1                                # final label
    final = lse(
        jnp.take_along_axis(alpha, jnp.maximum(last, 0)[:, None], axis=1)[:, 0],
        jnp.take_along_axis(alpha, (last + 1)[:, None], axis=1)[:, 0],
    )
    # degenerate empty-label case: all-blank path only
    final = jnp.where(label_lengths == 0, alpha[:, 0], final)
    return jnp.mean(-final)


def _ctc_greedy_decode(logits, *, blank=0, pad=-1):
    """Best-path decode: argmax per frame, collapse repeats, drop blanks.
    Static shapes: returns (B,T) padded with `pad`; pair with
    ctc_greedy_decode_lengths."""
    ids = jnp.argmax(logits, axis=-1).astype(jnp.int32)          # (B,T)
    prev = jnp.pad(ids[:, :-1], ((0, 0), (1, 0)), constant_values=-1)
    keep = (ids != blank) & (ids != prev)
    pos = jnp.cumsum(keep.astype(jnp.int32), axis=1) - 1
    B, T = ids.shape
    out = jnp.full((B, T), pad, jnp.int32)
    rows = jnp.broadcast_to(jnp.arange(B)[:, None], (B, T))
    # masked scatter: dead slots all write (harmlessly) to column 0 of a
    # dummy row appended then dropped
    safe_pos = jnp.where(keep, pos, T)
    out = jnp.pad(out, ((0, 0), (0, 1)), constant_values=pad)
    out = out.at[rows, safe_pos].set(jnp.where(keep, ids, pad))
    return out[:, :T]


def _max_pool_patches(x, kernel, stride, padding):
    """(values, flat_spatial_index) window stacks via static slicing."""
    B, H, W, C = x.shape
    kh, kw = kernel
    sh, sw = stride
    if padding == "SAME":
        oh, ow = -(-H // sh), -(-W // sw)
        ph = max((oh - 1) * sh + kh - H, 0)
        pw = max((ow - 1) * sw + kw - W, 0)
        x = jnp.pad(x, ((0, 0), (ph // 2, ph - ph // 2),
                        (pw // 2, pw - pw // 2), (0, 0)),
                    constant_values=-jnp.inf)
        off_h, off_w = -(ph // 2), -(pw // 2)
    else:
        oh, ow = (H - kh) // sh + 1, (W - kw) // sw + 1
        off_h = off_w = 0
    vals, idxs = [], []
    for i in range(kh):
        for j in range(kw):
            sub = x[:, i:i + (oh - 1) * sh + 1:sh,
                    j:j + (ow - 1) * sw + 1:sw, :]
            vals.append(sub)
            y = jnp.arange(oh) * sh + i + off_h
            z = jnp.arange(ow) * sw + j + off_w
            flat = y[:, None] * W + z[None, :]
            idxs.append(jnp.broadcast_to(flat[None, :, :, None],
                                         sub.shape))
    return jnp.stack(vals), jnp.stack(idxs), (B, oh, ow, C)


def _max_pool_with_argmax_indices(x, *, kernel=(2, 2), stride=(2, 2),
                                  padding="VALID",
                                  include_batch_in_index=False):
    """TF-convention flat indices of the max: ((b*H+)y*W + x)*C + c."""
    B, H, W, C = x.shape
    vals, idxs, _ = _max_pool_patches(x, kernel, stride, padding)
    best = jnp.argmax(vals, axis=0)
    spatial = jnp.take_along_axis(idxs, best[None], axis=0)[0]
    c = jnp.arange(C)[None, None, None, :]
    flat = spatial * C + c
    if include_batch_in_index:
        flat = flat + (jnp.arange(B) * H * W * C)[:, None, None, None]
    return flat.astype(jnp.int32)


def _dilation2d(x, filt, *, stride=(1, 1), padding="SAME"):
    """Grayscale morphological dilation (reference `dilation2d`):
    out = max_{ij} x[..y+i, x+j..] + filt[i,j,c]."""
    B, H, W, C = x.shape
    kh, kw, _ = filt.shape
    sh, sw = stride
    if padding == "SAME":
        oh, ow = -(-H // sh), -(-W // sw)
        ph = max((oh - 1) * sh + kh - H, 0)
        pw = max((ow - 1) * sw + kw - W, 0)
        x = jnp.pad(x, ((0, 0), (ph // 2, ph - ph // 2),
                        (pw // 2, pw - pw // 2), (0, 0)),
                    constant_values=-jnp.inf)
    else:
        oh, ow = (H - kh) // sh + 1, (W - kw) // sw + 1
    acc = None
    for i in range(kh):
        for j in range(kw):
            sub = x[:, i:i + (oh - 1) * sh + 1:sh,
                    j:j + (ow - 1) * sw + 1:sw, :] + filt[i, j]
            acc = sub if acc is None else jnp.maximum(acc, sub)
    return acc


def _erosion2d(x, filt, *, stride=(1, 1), padding="SAME"):
    return -_dilation2d(-x, filt[::-1, ::-1], stride=stride, padding=padding)


def _col2im(cols, *, input_shape, kernel, stride=(1, 1)):
    """Adjoint of im2col: overlap-add patches back to the image — exactly
    the linear transpose of the patch extraction XLA already knows."""
    x0 = jnp.zeros(tuple(input_shape), cols.dtype)
    f = lambda img: OPS["im2col"](img, kernel=tuple(kernel),
                                  stride=tuple(stride))
    (out,) = jax.linear_transpose(f, x0)(cols)
    return out


def _iou_matrix(a, b):
    """Pairwise IoU of (N,4) and (M,4) [y1,x1,y2,x2] boxes -> (N,M)."""
    area = lambda z: jnp.maximum(z[:, 2] - z[:, 0], 0) * jnp.maximum(
        z[:, 3] - z[:, 1], 0)
    tl = jnp.maximum(a[:, None, :2], b[None, :, :2])
    br = jnp.minimum(a[:, None, 2:], b[None, :, 2:])
    wh = jnp.maximum(br - tl, 0.0)
    inter = wh[..., 0] * wh[..., 1]
    union = area(a)[:, None] + area(b)[None, :] - inter
    return inter / jnp.maximum(union, 1e-9)


def _instance_norm(x, gamma, beta, *, epsilon=1e-5):
    axes = tuple(range(1, x.ndim - 1))
    mu = jnp.mean(x, axis=axes, keepdims=True)
    var = jnp.var(x, axis=axes, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + epsilon) * gamma + beta


def _group_norm(x, gamma, beta, *, groups, epsilon=1e-5):
    shp = x.shape
    C = shp[-1]
    g = x.reshape(shp[:-1] + (groups, C // groups))
    axes = tuple(range(1, x.ndim - 1)) + (x.ndim,)
    mu = jnp.mean(g, axis=axes, keepdims=True)
    var = jnp.var(g, axis=axes, keepdims=True)
    g = (g - mu) * jax.lax.rsqrt(var + epsilon)
    return g.reshape(shp) * gamma + beta


def _lrn(x, *, depth_radius=5, bias=1.0, alpha=1.0, beta=0.5):
    sq = jnp.square(x)
    pad = jnp.pad(sq, [(0, 0)] * (x.ndim - 1) + [(depth_radius, depth_radius)])
    window = sum(
        pad[..., i:i + x.shape[-1]] for i in range(2 * depth_radius + 1)
    )
    return x / jnp.power(bias + alpha * window, beta)


def _dot_product_attention(q, k, v, *, mask=None, causal=False):
    d = q.shape[-1]
    s = jnp.einsum("...qd,...kd->...qk", q, k) / jnp.sqrt(
        jnp.asarray(d, q.dtype))
    if causal:
        T, S = s.shape[-2], s.shape[-1]
        cm = jnp.tril(jnp.ones((T, S), bool))
        s = jnp.where(cm, s, jnp.asarray(-1e30, s.dtype))
    if mask is not None:
        s = jnp.where(mask.astype(bool), s, jnp.asarray(-1e30, s.dtype))
    return jnp.einsum("...qk,...kd->...qd", jax.nn.softmax(s, axis=-1), v)


def _multi_head_attention(x, wq, wk, wv, wo, *, heads, causal=False):
    B, T, D = x.shape
    dh = D // heads
    split = lambda z: z.reshape(B, T, heads, dh).transpose(0, 2, 1, 3)
    q, k, v = split(x @ wq), split(x @ wk), split(x @ wv)
    o = _dot_product_attention(q, k, v, causal=causal)
    return o.transpose(0, 2, 1, 3).reshape(B, T, D) @ wo


def _mixture_density_loss(params, target, *, components):
    """Negative log likelihood of an isotropic gaussian mixture (the
    reference's LossMixtureDensity).  params (B, K*(2D+1)) packed as
    [logit_pi(K), mu(K*D), log_sigma(K*D)]; target (B, D)."""
    B, D = target.shape
    K = components
    logit_pi = params[:, :K]
    mu = params[:, K:K + K * D].reshape(B, K, D)
    log_sig = params[:, K + K * D:].reshape(B, K, D)
    log_pi = jax.nn.log_softmax(logit_pi, axis=-1)
    z = (target[:, None, :] - mu) * jnp.exp(-log_sig)
    comp = (
        -0.5 * jnp.sum(jnp.square(z), axis=-1)
        - jnp.sum(log_sig, axis=-1)
        - 0.5 * D * jnp.log(2 * jnp.pi)
    )
    return jnp.mean(-jax.scipy.special.logsumexp(log_pi + comp, axis=-1))


# HOST-side constants: a module-level jnp.array would initialize the
# device backend at import time (importing a module must not open the
# chip).  The cast to device happens inside the op.
_RGB_YIQ = np.array([[0.299, 0.587, 0.114],
                     [0.59590059, -0.27455667, -0.32134392],
                     [0.21153661, -0.52273617, 0.31119955]], np.float32)
_RGB_YUV = np.array([[0.299, 0.587, 0.114],
                     [-0.14714119, -0.28886916, 0.43601035],
                     [0.61497538, -0.51496512, -0.10001026]], np.float32)


def _colorspace(mat):
    def fwd(x):
        return x @ jnp.asarray(mat.T, x.dtype)

    return fwd


def _resize(method):
    def fn(x, *, size):
        shape = (x.shape[0], int(size[0]), int(size[1]), x.shape[3])
        return jax.image.resize(x, shape, method=method)

    return fn


OPS.update({
    # --- CTC family (speech; SURVEY §2.1 declarable-op tail) ---
    "ctc_loss": _ctc_loss,
    "ctc_greedy_decode": _ctc_greedy_decode,
    "ctc_greedy_decode_lengths": lambda logits, *, blank=0: jnp.sum(
        (jnp.argmax(logits, -1) != blank)
        & (jnp.argmax(logits, -1) != jnp.pad(
            jnp.argmax(logits, -1)[:, :-1], ((0, 0), (1, 0)),
            constant_values=-1)),
        axis=1,
    ).astype(jnp.int32),
    # --- morphology / argmax pooling ---
    "dilation2d": _dilation2d,
    "erosion2d": _erosion2d,
    "max_pool_with_argmax": lambda x, *, kernel=(2, 2), stride=(2, 2),
    padding="VALID": jnp.max(
        _max_pool_patches(x, tuple(kernel), tuple(stride), padding)[0],
        axis=0,
    ),
    "max_pool_with_argmax_indices": _max_pool_with_argmax_indices,
    # --- image tail 2 ---
    "rgb_to_yiq": _colorspace(_RGB_YIQ),
    "yiq_to_rgb": _colorspace(np.linalg.inv(_RGB_YIQ)),
    "rgb_to_yuv": _colorspace(_RGB_YUV),
    "yuv_to_rgb": _colorspace(np.linalg.inv(_RGB_YUV)),
    "resize_bilinear": _resize("bilinear"),
    "resize_nearest": _resize("nearest"),
    "resize_bicubic": _resize("bicubic"),
    "mirror_pad": lambda x, *, paddings, mode="REFLECT": jnp.pad(
        x, [tuple(p) for p in paddings],
        mode="reflect" if str(mode).upper() == "REFLECT" else "symmetric",
    ),
    "upsampling2d": lambda x, *, factor=(2, 2): jnp.repeat(
        jnp.repeat(x, factor[0], axis=1), factor[1], axis=2
    ),
    "iou": _iou_matrix,
    "col2im": _col2im,
    "random_crop": lambda x, *, size, seed=0: jax.lax.dynamic_slice(
        x,
        tuple(
            jax.random.randint(
                jax.random.key(seed), (len(size),), 0,
                jnp.array([d - s + 1 for d, s in zip(x.shape, size)]),
            )
        ),
        tuple(size),
    ),
    # --- activations / nn tail ---
    "hardswish": lambda x: x * jnp.clip(x + 3.0, 0.0, 6.0) / 6.0,
    "softmin": lambda x, *, axis=-1: jax.nn.softmax(-x, axis=_ax(axis)),
    "rectifiedtanh": lambda x: jnp.maximum(jnp.tanh(x), 0.0),
    "relu_layer": lambda x, w, b: jax.nn.relu(x @ w + b),
    "alpha_dropout": lambda x, *, rate=0.5, seed=0: (
        # SELU-preserving dropout (reference AlphaDropout): affine fixup
        # keeps self-normalizing mean/var
        (lambda keep, a_: (
            (jnp.where(keep, x, a_)
             * (1.0 / jnp.sqrt((1 - rate) * (1 + rate * a_ ** 2))))
            + (-(1.0 / jnp.sqrt((1 - rate) * (1 + rate * a_ ** 2)))
               * rate * a_)
        ))(
            jax.random.bernoulli(jax.random.key(seed), 1.0 - rate, x.shape),
            -1.7580993408473766,
        )
    ),
    # --- norms ---
    "instance_norm": _instance_norm,
    "group_norm": _group_norm,
    "local_response_normalization": _lrn,
    "l2_normalize": lambda x, *, axis=-1, epsilon=1e-12: x * jax.lax.rsqrt(
        jnp.maximum(jnp.sum(jnp.square(x), axis=_ax(axis), keepdims=True),
                    epsilon)
    ),
    "normalize_moments": lambda count, mean_ss, var_ss, *, shift=0.0: (
        jnp.stack([
            mean_ss / count + shift,
            var_ss / count - jnp.square(mean_ss / count),
        ])
    ),
    "clip_by_avg_norm": lambda x, *, clip_norm: x * jnp.minimum(
        1.0,
        # TF/libnd4j "average norm" is l2/N, NOT the RMS l2/sqrt(N)
        clip_norm / jnp.maximum(
            jnp.sqrt(jnp.sum(jnp.square(x))) / x.size, 1e-12),
    ),
    # --- attention ---
    "dot_product_attention": _dot_product_attention,
    "multi_head_attention": _multi_head_attention,
    # --- loss-function parity (reference LossFunctions) ---
    "mae_loss": lambda pred, lab: jnp.mean(jnp.abs(pred - lab)),

    "mape_loss": lambda pred, lab: jnp.mean(
        jnp.abs((lab - pred) / jnp.maximum(jnp.abs(lab), 1e-8))) * 100.0,
    "msle_loss": lambda pred, lab: jnp.mean(
        jnp.square(jnp.log1p(jnp.maximum(pred, -1 + 1e-7))
                   - jnp.log1p(jnp.maximum(lab, -1 + 1e-7)))),
    "squared_hinge_loss": lambda pred, lab: jnp.mean(
        jnp.square(jnp.maximum(0.0, 1.0 - lab * pred))),
    "kld_loss": lambda pred, lab: jnp.mean(jnp.sum(
        lab * (jnp.log(jnp.maximum(lab, 1e-12))
               - jnp.log(jnp.maximum(pred, 1e-12))), axis=-1)),
    "wasserstein_loss": lambda pred, lab: jnp.mean(pred * lab),
    "multi_label_loss": lambda logits, labels: jnp.mean(
        jnp.maximum(logits, 0) - logits * labels
        + jnp.log1p(jnp.exp(-jnp.abs(logits)))),
    "fmeasure_loss": lambda pred, lab, *, beta=1.0: 1.0 - (
        (1 + beta ** 2) * jnp.sum(pred * lab)
        / jnp.maximum(
            beta ** 2 * jnp.sum(lab) + jnp.sum(pred), 1e-8)
    ),
    "focal_loss": lambda logits, labels, *, gamma=2.0, alpha=0.25: jnp.mean(
        -labels * alpha
        * jnp.power(1 - jax.nn.sigmoid(logits), gamma)
        * jax.nn.log_sigmoid(logits)
        - (1 - labels) * (1 - alpha)
        * jnp.power(jax.nn.sigmoid(logits), gamma)
        * jax.nn.log_sigmoid(-logits)
    ),
    "dice_loss": lambda pred, lab, *, smooth=1.0: 1.0 - (
        (2.0 * jnp.sum(pred * lab) + smooth)
        / (jnp.sum(jnp.square(pred)) + jnp.sum(jnp.square(lab)) + smooth)
    ),
    "log_poisson_loss": lambda logits, targets, *, compute_full_loss=False: (
        jnp.mean(
            jnp.exp(logits) - targets * logits
            # Stirling term only where it approximates log(target!) at all
            # (TF zeroes it for targets <= 1, where log(0!) = log(1!) = 0)
            + (jnp.where(
                targets > 1.0,
                targets * jnp.log(jnp.maximum(targets, 1e-12)) - targets
                + 0.5 * jnp.log(2 * jnp.pi * jnp.maximum(targets, 1e-12)),
                0.0,
            ) if compute_full_loss else 0.0)
        )
    ),
    "mean_pairwise_squared_error": lambda pred, lab: (
        # TF defn per example over the n per-element deltas d:
        # mean_{i<j}(d_i-d_j)^2 = 2*(n*sum d^2 - (sum d)^2) / (n*(n-1))
        (lambda d: (lambda n: jnp.mean(
            2.0 * (n * jnp.sum(jnp.square(d), axis=-1)
                   - jnp.square(jnp.sum(d, axis=-1)))
            / jnp.maximum(n * (n - 1), 1.0)
        ))(jnp.asarray(d.shape[1], jnp.float32)))
        ((pred - lab).reshape(pred.shape[0], -1))
    ),
    "cosine_embedding_loss": lambda a, b, y, *, margin=0.0: jnp.mean(
        jnp.where(
            y > 0,
            1.0 - OPS["cosine_similarity"](a, b, axis=-1),
            jnp.maximum(0.0, OPS["cosine_similarity"](a, b, axis=-1)
                        - margin),
        )
    ),
    "margin_ranking_loss": lambda x1, x2, y, *, margin=0.0: jnp.mean(
        jnp.maximum(0.0, -y * (x1 - x2) + margin)),
    "triplet_margin_loss": lambda anchor, pos, neg, *, margin=1.0: jnp.mean(
        jnp.maximum(
            0.0,
            jnp.sqrt(jnp.sum(jnp.square(anchor - pos), -1) + 1e-12)
            - jnp.sqrt(jnp.sum(jnp.square(anchor - neg), -1) + 1e-12)
            + margin,
        )
    ),
    "nll_loss": lambda logp, labels: -jnp.mean(
        jnp.take_along_axis(logp, labels[..., None].astype(jnp.int32),
                            axis=-1)),
    "mixture_density_loss": _mixture_density_loss,
    # --- math / array tail ---
    "erfcinv": lambda x: jax.scipy.special.erfinv(1.0 - x),
    "fmod": jnp.fmod,
    "trace": lambda x: jnp.trace(x, axis1=-2, axis2=-1),
    "matrix_diag_part": lambda x: jnp.diagonal(x, axis1=-2, axis2=-1),
    "choose": lambda idx, x: jnp.choose(idx.astype(jnp.int32), x,
                                        mode="clip"),
    "nth_element": lambda x, *, n, reverse=False: (
        jnp.sort(x, axis=-1)[..., x.shape[-1] - 1 - n]
        if reverse else jnp.sort(x, axis=-1)[..., n]
    ),
    "kth_value": lambda x, *, k: jnp.sort(x, axis=-1)[..., k - 1],
    "in_top_k": lambda predictions, targets, *, k: (
        # TF tie semantics: only STRICTLY greater entries spend the budget
        jnp.sum(
            (predictions
             > jnp.take_along_axis(
                 predictions, targets[:, None].astype(jnp.int32), axis=-1
             )).astype(jnp.int32),
            axis=-1,
        ) < k
    ),
    "embedding_lookup": lambda table, ids: jnp.take(
        table, ids.astype(jnp.int32), axis=0),
    "tensor_scatter_update": lambda x, indices, updates: jnp.asarray(x).at[
        tuple(jnp.moveaxis(jnp.asarray(indices, jnp.int32), -1, 0))
    ].set(updates),
    "tensor_scatter_add": lambda x, indices, updates: jnp.asarray(x).at[
        tuple(jnp.moveaxis(jnp.asarray(indices, jnp.int32), -1, 0))
    ].add(updates),
    "matmul_transpose": lambda a, b, *, transpose_a=False, transpose_b=False:
        jnp.matmul(
            jnp.swapaxes(a, -1, -2) if transpose_a else a,
            jnp.swapaxes(b, -1, -2) if transpose_b else b,
        ),
    "flatten_2d": lambda x: x.reshape(x.shape[0], -1),
    "reshape_as": lambda x, ref: x.reshape(ref.shape),
    "meshgrid_x": lambda x, y: jnp.meshgrid(x, y, indexing="xy")[0],
    "meshgrid_y": lambda x, y: jnp.meshgrid(x, y, indexing="xy")[1],
    "population_count": lambda x: jax.lax.population_count(
        x.astype(jnp.uint32)).astype(jnp.int32),
    "bitcast": lambda x, *, dtype: jax.lax.bitcast_convert_type(
        x, jnp.dtype(dtype)),
    # --- complex support (XLA complex64) ---
    "complex": jax.lax.complex,
    "conj": jnp.conj,
})

OPS["softmax_cross_entropy_with_logits"] = OPS["softmax_cross_entropy"]
OPS["mean_squared_error"] = OPS["mse_loss"]
OPS["batch_matmul"] = OPS["matmul"]
OPS["truncated_normal"] = OPS["random_truncated_normal"]
OPS["cross_entropy_loss"] = OPS["sparse_softmax_cross_entropy"]
OPS["histogram"] = OPS["histogram_fixed_width"]
OPS["top_k"] = OPS["top_k_values"]
OPS["cyclic_shift"] = OPS["roll"]
OPS["squared_hinge"] = OPS["squared_hinge_loss"]

OPS.update({
    "matrix_inverse": jnp.linalg.inv,
    "log2": jnp.log2,
    "log10": jnp.log10,
    "exp2": jnp.exp2,
    "frac": lambda x: x - jnp.trunc(x),
    "remainder": jnp.remainder,
    "gcd": jnp.gcd,
    "lcm": jnp.lcm,
    "swapaxes": lambda x, *, axis1, axis2: jnp.swapaxes(x, axis1, axis2),
    "moveaxis": lambda x, *, source, destination: jnp.moveaxis(
        x, source, destination),
    "flip_left_right": lambda x: jnp.flip(x, axis=-2),
    "flip_up_down": lambda x: jnp.flip(x, axis=-3),
    "adjust_gamma": lambda x, *, gamma=1.0, gain=1.0: gain * jnp.power(
        jnp.maximum(x, 0.0), gamma),
    "take_along_axis": lambda x, idx, *, axis=-1: jnp.take_along_axis(
        x, idx.astype(jnp.int32), axis=axis),
    "put_along_axis": lambda x, idx, vals, *, axis=-1: jnp.put_along_axis(
        x, idx.astype(jnp.int32), vals, axis=axis, inplace=False),
    "array_equal": lambda a, b: jnp.all(a == b),
})


def _strided_slice(x, *, begin, end, strides, begin_mask=0, end_mask=0,
                   ellipsis_mask=0, new_axis_mask=0, shrink_axis_mask=0):
    """TF StridedSlice semantics (static spec): per-dim python slices with
    the five TF bit masks."""
    idx = []
    for i in range(len(begin)):
        if ellipsis_mask & (1 << i):
            idx.append(Ellipsis)
        elif new_axis_mask & (1 << i):
            idx.append(None)
        elif shrink_axis_mask & (1 << i):
            idx.append(int(begin[i]))
        else:
            b = None if begin_mask & (1 << i) else int(begin[i])
            e = None if end_mask & (1 << i) else int(end[i])
            idx.append(slice(b, e, int(strides[i])))
    return x[tuple(idx)]


OPS.update({
    "strided_slice": _strided_slice,
    "l2_loss": lambda x: 0.5 * jnp.sum(jnp.square(x)),
})


# ---------------------------------------------------------------------------
# Round-4 tail 2: numpy-parity math, linalg, signal and statistics families
# (SURVEY §2.1 — the reference's declarable-op library spans the same
# ground: legacy *_bp grad ops, summary statistics, windows/FFT helpers,
# distance/correlation kernels).


def _spearman(a, b):
    def ranks(x):
        # AVERAGE ranks for ties (the standard definition): midpoint of
        # the first/last positions of each value in sorted order
        s = jnp.sort(x)
        lo = jnp.searchsorted(s, x, side="left")
        hi = jnp.searchsorted(s, x, side="right")
        return (lo + hi - 1).astype(jnp.float32) / 2.0

    return OPS["pearson_corr"](ranks(a.reshape(-1)), ranks(b.reshape(-1)))


def _pearson(a, b):
    a = a.astype(jnp.float32).reshape(-1)
    b = b.astype(jnp.float32).reshape(-1)
    ac = a - jnp.mean(a)
    bc = b - jnp.mean(b)
    return jnp.sum(ac * bc) / jnp.maximum(
        jnp.sqrt(jnp.sum(ac * ac) * jnp.sum(bc * bc)), 1e-12)


def _detrend(x):
    """Remove the least-squares linear fit along the last axis."""
    n = x.shape[-1]
    t = jnp.arange(n, dtype=jnp.float32)
    tc = t - t.mean()
    xm = jnp.mean(x, axis=-1, keepdims=True)
    slope = jnp.sum((x - xm) * tc, axis=-1, keepdims=True) / jnp.sum(tc * tc)
    return x - xm - slope * tc


def _medfilt(x, *, kernel=3):
    k = int(kernel)
    if k % 2 != 1:
        raise ValueError("medfilt kernel must be odd")
    pad = k // 2
    xp = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(pad, pad)], mode="edge")
    stacked = jnp.stack(
        [xp[..., i:i + x.shape[-1]] for i in range(k)], axis=0)
    return jnp.median(stacked, axis=0)


def _mel_filterbank(*, n_mels, n_fft_bins, sample_rate, fmin=0.0, fmax=None):
    """HTK-style triangular mel filterbank matrix (n_mels, n_fft_bins) —
    the spectrogram->mel projection behind MFCC pipelines."""
    fmax = fmax or sample_rate / 2.0
    mel = lambda f: 2595.0 * jnp.log10(1.0 + f / 700.0)
    imel = lambda m: 700.0 * (10.0 ** (m / 2595.0) - 1.0)
    pts = imel(jnp.linspace(mel(jnp.asarray(fmin)), mel(jnp.asarray(fmax)),
                            n_mels + 2))
    freqs = jnp.linspace(0.0, sample_rate / 2.0, n_fft_bins)
    lo, ctr, hi = pts[:-2, None], pts[1:-1, None], pts[2:, None]
    up = (freqs[None] - lo) / jnp.maximum(ctr - lo, 1e-9)
    down = (hi - freqs[None]) / jnp.maximum(hi - ctr, 1e-9)
    return jnp.clip(jnp.minimum(up, down), 0.0, 1.0)


def _confusion_counts(pred, lab):
    pred = pred.astype(bool).reshape(-1)
    lab = lab.astype(bool).reshape(-1)
    tp = jnp.sum(pred & lab).astype(jnp.float32)
    fp = jnp.sum(pred & ~lab).astype(jnp.float32)
    fn = jnp.sum(~pred & lab).astype(jnp.float32)
    tn = jnp.sum(~pred & ~lab).astype(jnp.float32)
    return tp, fp, fn, tn


def _f1(pred, lab):
    tp, fp, fn, _ = _confusion_counts(pred, lab)
    return 2 * tp / jnp.maximum(2 * tp + fp + fn, 1e-12)


def _mcc(pred, lab):
    tp, fp, fn, tn = _confusion_counts(pred, lab)
    denom = jnp.sqrt((tp + fp) * (tp + fn) * (tn + fp) * (tn + fn))
    return (tp * tn - fp * fn) / jnp.maximum(denom, 1e-12)


def _cohen_kappa(pred, lab):
    tp, fp, fn, tn = _confusion_counts(pred, lab)
    n = tp + fp + fn + tn
    po = (tp + tn) / n
    pe = ((tp + fp) * (tp + fn) + (fn + tn) * (fp + tn)) / (n * n)
    return (po - pe) / jnp.maximum(1.0 - pe, 1e-12)


def _ensure_shape(x, *, shape):
    """Identity that VALIDATES the static shape (TF semantics) — None/-1
    entries are wildcards; a mismatch raises instead of re-laying-out."""
    shape = tuple(shape)
    if len(shape) != x.ndim or any(
        s not in (None, -1) and int(s) != d for s, d in zip(shape, x.shape)
    ):
        raise ValueError(
            f"ensure_shape: got {tuple(x.shape)}, expected {shape}"
        )
    return x


OPS.update({
    # --- numpy-parity math/array tail ---
    "diff": lambda x, *, n=1, axis=-1: jnp.diff(x, n=n, axis=axis),
    "ediff1d": lambda x: jnp.ediff1d(x),
    "trapz": lambda y, *, dx=1.0, axis=-1: getattr(
        jnp, "trapezoid", getattr(jnp, "trapz", None))(y, dx=dx, axis=axis),
    "gradient_1d": lambda x: jnp.gradient(x),
    "interp": lambda x, xp, fp: jnp.interp(x, xp, fp),
    "unwrap": lambda x, *, axis=-1: jnp.unwrap(x, axis=axis),
    "polyval": lambda coeffs, x: jnp.polyval(coeffs, x),
    "polyder": lambda coeffs, *, m=1: jnp.polyder(coeffs, m=m),
    "polyint": lambda coeffs, *, m=1: jnp.polyint(coeffs, m=m),
    "convolve_1d": lambda a, v, *, mode="full": jnp.convolve(a, v, mode=mode),
    "correlate_1d": lambda a, v, *, mode="full": jnp.correlate(
        a, v, mode=mode),
    "partition": lambda x, *, kth, axis=-1: jnp.partition(x, kth, axis=axis),
    "argpartition": lambda x, *, kth, axis=-1: jnp.argpartition(
        x, kth, axis=axis),
    "lexsort": lambda *keys: jnp.lexsort(keys),
    "repeat": lambda x, *, repeats, axis=None: jnp.repeat(
        x, repeats, axis=axis),
    "take": lambda x, idx, *, axis=None: jnp.take(
        x, idx.astype(jnp.int32), axis=axis),
    "compress": lambda cond, x, *, axis=None, size, fill=0: jnp.compress(
        cond.astype(bool), x, axis=axis, size=size, fill_value=fill),
    "fill_diagonal": lambda x, *, value: jnp.asarray(x).at[
        ..., jnp.arange(min(x.shape[-2], x.shape[-1])),
        jnp.arange(min(x.shape[-2], x.shape[-1]))].set(value),
    "digitize": lambda x, bins: jnp.digitize(x, bins),
    "float_power": jnp.float_power,
    "fix": jnp.trunc,   # numpy fix == trunc toward zero
    "positive": jnp.positive,
    "cbrt": jnp.cbrt,
    "fabs": jnp.fabs,
    # --- linalg tail 2 ---
    "norm_fro": lambda x: jnp.linalg.norm(x, ord="fro", axis=(-2, -1)),
    "inner": jnp.inner,
    "vdot": jnp.vdot,
    "multi_dot": lambda *ms: jnp.linalg.multi_dot(ms),
    "cholesky_inverse": lambda L: jax.scipy.linalg.cho_solve(
        (L, True), jnp.eye(L.shape[-1], dtype=L.dtype)),
    "diag_embed": lambda x: x[..., None] * jnp.eye(x.shape[-1], dtype=x.dtype),
    "block_diag": lambda *ms: jax.scipy.linalg.block_diag(*ms),
    "toeplitz": lambda c, r=None: jax.scipy.linalg.toeplitz(
        c, r if r is not None else c),
    "adjoint": lambda x: jnp.conj(jnp.swapaxes(x, -1, -2)),
    # --- signal tail 2 ---
    "bartlett_window": lambda *, length: jnp.bartlett(length),
    "kaiser_window": lambda *, length, beta=12.0: jnp.kaiser(length, beta),
    "fft2d": lambda x: jnp.fft.fft2(x.astype(jnp.complex64)),
    "ifft2d": lambda x: jnp.fft.ifft2(x),
    "mel_filterbank": _mel_filterbank,
    "power_to_db": lambda s, *, ref=1.0, amin=1e-10: 10.0 * (
        jnp.log10(jnp.maximum(s, amin)) - jnp.log10(jnp.maximum(ref, amin))),
    "db_to_power": lambda db, *, ref=1.0: ref * jnp.power(10.0, db / 10.0),
    "rms": lambda x, *, axis=None: jnp.sqrt(
        jnp.mean(jnp.square(x.astype(jnp.float32)), axis=_ax(axis))),
    # (x >= 0) transitions count crossings THROUGH exact zeros too
    # (sign(0)=0 would silently drop them)
    "zero_crossings": lambda x: jnp.sum(
        jnp.abs(jnp.diff((x >= 0).astype(jnp.int32), axis=-1)), axis=-1),
    "autocorr": lambda x, *, lag=1: _pearson(
        x[..., :-lag].reshape(-1), x[..., lag:].reshape(-1)),
    "detrend": _detrend,
    "medfilt": _medfilt,
    # --- statistics / metrics tail (reference summary-stats + eval ops) ---
    "covariance": lambda a, b: jnp.mean(
        (a.astype(jnp.float32) - jnp.mean(a))
        * (b.astype(jnp.float32) - jnp.mean(b))),
    "pearson_corr": _pearson,
    "spearman_corr": _spearman,
    "skewness": lambda x: (lambda c, s: jnp.mean(c ** 3) / jnp.maximum(
        s ** 3, 1e-12))(x.astype(jnp.float32) - jnp.mean(x), jnp.std(x)),
    "kurtosis": lambda x: (lambda c, s: jnp.mean(c ** 4) / jnp.maximum(
        s ** 4, 1e-12) - 3.0)(x.astype(jnp.float32) - jnp.mean(x),
                              jnp.std(x)),
    "quantile": lambda x, *, q, axis=None: jnp.quantile(x, q, axis=_ax(axis)),
    "iqr": lambda x: jnp.quantile(x, 0.75) - jnp.quantile(x, 0.25),
    "mad": lambda x: jnp.median(jnp.abs(x - jnp.median(x))),
    "zscore": lambda x, *, axis=None, epsilon=1e-12: (
        (x - jnp.mean(x, axis=_ax(axis), keepdims=True))
        / (jnp.std(x, axis=_ax(axis), keepdims=True) + epsilon)),
    "weighted_mean": lambda x, w: jnp.sum(x * w) / jnp.maximum(
        jnp.sum(w), 1e-12),
    "ema": lambda x, *, alpha: jnp.moveaxis(
        jax.lax.scan(
            lambda c, v: ((1 - alpha) * c + alpha * v,) * 2,
            x[..., 0], jnp.moveaxis(x, -1, 0),
        )[1], 0, -1),
    "sma": lambda x, *, window: jnp.convolve(
        x, jnp.ones(window) / window, mode="valid"),
    "f1_score": _f1,
    "matthews_corrcoef": _mcc,
    "cohen_kappa": _cohen_kappa,
    "r2_score": lambda pred, lab: 1.0 - jnp.sum(jnp.square(lab - pred))
        / jnp.maximum(jnp.sum(jnp.square(lab - jnp.mean(lab))), 1e-12),
    "explained_variance": lambda pred, lab: 1.0 - jnp.var(lab - pred)
        / jnp.maximum(jnp.var(lab), 1e-12),
    "rmse": lambda pred, lab: jnp.sqrt(jnp.mean(jnp.square(pred - lab))),
    # --- legacy *_bp grad ops (the reference ships these as declarable
    # backward ops; useful for hand-built backward graphs) ---
    "sigmoid_bp": lambda x, g: g * jax.nn.sigmoid(x)
        * (1.0 - jax.nn.sigmoid(x)),
    "tanh_bp": lambda x, g: g * (1.0 - jnp.square(jnp.tanh(x))),
    "relu_bp": lambda x, g: g * (x > 0).astype(g.dtype),
    "softmax_bp": lambda x, g, *, axis=-1: (lambda s: s * (
        g - jnp.sum(g * s, axis=axis, keepdims=True)))(
        jax.nn.softmax(x, axis=axis)),
    "ensure_shape": _ensure_shape,
})

OPS["split_part"] = (
    # one output of an even split — shapes resolve at trace time, so the
    # importer doesn't need shape inference (TF Split -> one op per output)
    lambda x, *, index, num, axis=0: jnp.split(x, num, axis=axis)[index]
)
OPS["slice_axis"] = (
    lambda x, *, begin, size, axis=0: jax.lax.slice_in_dim(
        x, begin, begin + size, axis=axis)
)

OPS["matrix_exp"] = OPS["expm"]
OPS["log_matrix_determinant"] = OPS["logdet"]


# ---------------------------------------------------------------------------
# CTC prefix beam search (the reference's ctc_beam declarable op) — fully
# static shapes: fixed beam width, fixed per-frame symbol top-k pruning,
# candidate merge by prefix equality, one lax.scan over time.


def _ctc_beam_search(logits, *, beam_width=8, blank=0, symbol_topk=8,
                     pad=-1):
    """Returns (prefixes (B, W, T), lengths (B, W), log_probs (B, W)),
    beams sorted best-first.  Standard CTC prefix beam search: per beam a
    (p_blank, p_nonblank) pair; per frame the beam extends with the top-k
    symbols, equal prefixes merge by probability sum, and the best W
    survive — every step fixed-shape, so the whole decode jits."""
    NEG = jnp.float32(-1e30)
    B, T, C = logits.shape
    W = int(beam_width)
    K = min(int(symbol_topk), C)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)

    def decode_one(lp_seq):
        prefixes0 = jnp.full((W, T), pad, jnp.int32)
        lengths0 = jnp.zeros((W,), jnp.int32)
        pb0 = jnp.full((W,), NEG).at[0].set(0.0)
        pnb0 = jnp.full((W,), NEG)

        def step(state, lp):
            prefixes, lengths, pb, pnb = state
            top_v, top_i = jax.lax.top_k(lp, K)

            last = jnp.take_along_axis(
                prefixes,
                jnp.maximum(lengths - 1, 0)[:, None], axis=1,
            )[:, 0]
            lp_last = jnp.where(lengths > 0, lp[jnp.maximum(last, 0)], NEG)

            # stay candidates (same prefix): blank path + repeat collapse
            stay_pb = jnp.logaddexp(pb, pnb) + lp[blank]
            stay_pnb = pnb + lp_last
            # extension candidates: (W, K)
            is_rep = top_i[None, :] == last[:, None]        # repeat after blank
            base = jnp.where(
                is_rep & (lengths > 0)[:, None],
                pb[:, None],                                # only the blank path
                jnp.logaddexp(pb, pnb)[:, None],
            )
            ext_pnb = base + top_v[None, :]
            ext_pnb = jnp.where(
                (top_i[None, :] == blank) | (lengths >= T)[:, None],
                NEG, ext_pnb,
            )
            # candidate tensors: M = W + W*K
            ext_prefix = jnp.repeat(prefixes, K, axis=0)
            pos = jnp.repeat(lengths, K)
            ext_prefix = ext_prefix.at[
                jnp.arange(W * K), jnp.minimum(pos, T - 1)
            ].set(jnp.tile(top_i, W))
            cand_prefix = jnp.concatenate([prefixes, ext_prefix], axis=0)
            cand_len = jnp.concatenate(
                [lengths, jnp.minimum(pos + 1, T)], axis=0)
            cand_pb = jnp.concatenate(
                [stay_pb, jnp.full((W * K,), NEG)], axis=0)
            cand_pnb = jnp.concatenate([stay_pnb, ext_pnb.reshape(-1)],
                                       axis=0)

            # merge candidates with EQUAL prefixes (prob mass adds)
            eq = (
                jnp.all(cand_prefix[:, None, :] == cand_prefix[None, :, :],
                        axis=-1)
                & (cand_len[:, None] == cand_len[None, :])
            )
            canon = jnp.argmax(eq, axis=1)          # first equal candidate
            M = cand_pb.shape[0]
            owns = canon[None, :] == jnp.arange(M)[:, None]   # (M slots, M)
            merged_pb = jax.nn.logsumexp(
                jnp.where(owns, cand_pb[None, :], NEG), axis=1)
            merged_pnb = jax.nn.logsumexp(
                jnp.where(owns, cand_pnb[None, :], NEG), axis=1)
            is_canon = canon == jnp.arange(M)
            score = jnp.where(
                is_canon, jnp.logaddexp(merged_pb, merged_pnb), NEG)

            _, keep = jax.lax.top_k(score, W)
            return (
                cand_prefix[keep], cand_len[keep],
                merged_pb[keep], merged_pnb[keep],
            ), None

        (prefixes, lengths, pb, pnb), _ = jax.lax.scan(
            step, (prefixes0, lengths0, pb0, pnb0), lp_seq)
        score = jnp.logaddexp(pb, pnb)
        order = jnp.argsort(-score)
        return prefixes[order], lengths[order], score[order]

    return jax.vmap(decode_one)(logp)


# Public triple-return entry: EAGER callers should use this (one search);
# the three registry ops below are graph-building conveniences — inside a
# single jitted computation XLA CSE collapses their identical subgraphs,
# so only eager triple-fetch would pay 3x.
ctc_beam_search = _ctc_beam_search

OPS.update({
    "ctc_beam_decode": lambda logits, **kw: _ctc_beam_search(logits, **kw)[0],
    "ctc_beam_decode_lengths": lambda logits, **kw: _ctc_beam_search(
        logits, **kw)[1],
    "ctc_beam_decode_log_probs": lambda logits, **kw: _ctc_beam_search(
        logits, **kw)[2],
})
