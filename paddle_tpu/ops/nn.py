"""Neural-net ops: conv, pooling, normalization, embedding, losses.

Reference: fluid's cuDNN-backed kernels (``operators/conv_op.*``,
``operators/conv_cudnn_op.cu.cc``, ``softmax_op``, ``layer_norm_op``,
``batch_norm_op``, ``cross_entropy_op``, ``dropout_op``,
``lookup_table_op``, ``operators/math/pooling.*``).

TPU-first decisions:
- Layout is NHWC (TPU conv-native); fluid's default is NCHW. ``data_format``
  accepts both; internal compute is NHWC so XLA maps convs onto the MXU
  without transposes.
- Dropout takes an explicit PRNG ``key`` (functional; no global RNG state —
  fluid threads a seed attribute through the op).
- lookup_table's sparse-grad path (SelectedRows) is unnecessary: XLA
  scatter-add handles embedding grads; beyond-HBM tables live in
  paddle_tpu.parallel.embedding.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.core.registry import register_op


def _pair(v):
    if isinstance(v, (list, tuple)):
        return tuple(v)
    return (v, v)


def _to_nhwc(x, data_format):
    if data_format == "NCHW":
        return jnp.transpose(x, (0, 2, 3, 1))
    return x


def _from_nhwc(x, data_format):
    if data_format == "NCHW":
        return jnp.transpose(x, (0, 3, 1, 2))
    return x


@register_op("conv2d")
def conv2d(x, weight, bias=None, stride=1, padding=0, dilation=1, groups=1,
           data_format="NHWC"):
    """2-D convolution (fluid conv2d / cudnn conv -> XLA conv on MXU).

    weight layout: HWIO (filter_h, filter_w, in_channels/groups, out_channels).
    padding: int, pair, or "SAME"/"VALID".
    """
    x = _to_nhwc(x, data_format)
    stride = _pair(stride)
    dilation = _pair(dilation)
    if isinstance(padding, str):
        pad = padding
    else:
        ph, pw = _pair(padding)
        pad = ((ph, ph), (pw, pw))
    out = jax.lax.conv_general_dilated(
        x, weight,
        window_strides=stride,
        padding=pad,
        rhs_dilation=dilation,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=groups,
    )
    if bias is not None:
        out = out + bias
    return _from_nhwc(out, data_format)


@register_op("conv2d_transpose")
def conv2d_transpose(x, weight, bias=None, stride=1, padding=0,
                     data_format="NHWC"):
    """Transposed conv (fluid conv2d_transpose_op). weight: HWIO.

    Fluid semantics: out = (H-1)*stride + k - 2*padding (deconv = gradient of
    conv w.r.t. input). Implemented as input-dilated conv with explicit pads
    k-1-p and a spatially-flipped kernel, which is exactly that gradient.
    """
    x = _to_nhwc(x, data_format)
    sh, sw = _pair(stride)
    kh, kw = weight.shape[0], weight.shape[1]
    ph, pw = _pair(padding)
    out = jax.lax.conv_general_dilated(
        x, jnp.flip(weight, (0, 1)),
        window_strides=(1, 1),
        padding=((kh - 1 - ph, kh - 1 - ph), (kw - 1 - pw, kw - 1 - pw)),
        lhs_dilation=(sh, sw),
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    if bias is not None:
        out = out + bias
    return _from_nhwc(out, data_format)


@register_op("depthwise_conv2d")
def depthwise_conv2d(x, weight, bias=None, stride=1, padding=0, dilation=1,
                     data_format="NHWC"):
    """Depthwise conv (fluid depthwise_conv2d, math/depthwise_conv.cu).
    weight: HWI1 with groups == in_channels."""
    channels = weight.shape[2]
    w = weight.reshape(weight.shape[0], weight.shape[1], 1,
                       channels * weight.shape[3])
    return conv2d(x, w, bias, stride, padding, dilation, groups=channels,
                  data_format=data_format)


@register_op("pool2d")
def pool2d(x, kernel=2, stride=None, padding=0, pool_type="max",
           ceil_mode=False, data_format="NHWC", global_pooling=False):
    """Max/avg pooling (fluid pool2d_op, operators/math/pooling.*)."""
    x = _to_nhwc(x, data_format)
    if global_pooling:
        kernel = (x.shape[1], x.shape[2])
        stride, padding = kernel, 0
    kh, kw = _pair(kernel)
    sh, sw = _pair(stride if stride is not None else kernel)
    ph, pw = _pair(padding)
    dims = (1, kh, kw, 1)
    strides = (1, sh, sw, 1)
    pads = ((0, 0), (ph, ph), (pw, pw), (0, 0))
    if pool_type == "max":
        init = -jnp.inf if jnp.issubdtype(x.dtype, jnp.floating) else jnp.iinfo(x.dtype).min
        out = jax.lax.reduce_window(x, init, jax.lax.max, dims, strides, pads)
    elif pool_type == "avg":
        summed = jax.lax.reduce_window(x, 0.0, jax.lax.add, dims, strides, pads)
        if ph == 0 and pw == 0:
            out = summed / (kh * kw)
        else:
            # count_include_pad=False parity: divide by true window size
            ones = jnp.ones(x.shape[:3] + (1,), x.dtype)
            counts = jax.lax.reduce_window(ones, 0.0, jax.lax.add, dims, strides, pads)
            out = summed / counts
    else:
        raise ValueError(f"unknown pool_type {pool_type!r}")
    return _from_nhwc(out, data_format)


@register_op("adaptive_pool2d")
def adaptive_pool2d(x, output_size, pool_type="avg", data_format="NHWC"):
    x = _to_nhwc(x, data_format)
    oh, ow = _pair(output_size)
    n, h, w, c = x.shape
    if h % oh == 0 and w % ow == 0:
        x = x.reshape(n, oh, h // oh, ow, w // ow, c)
        out = x.max(axis=(2, 4)) if pool_type == "max" else x.mean(axis=(2, 4))
    else:
        raise NotImplementedError("adaptive pool requires divisible sizes")
    return _from_nhwc(out, data_format)


def _np_softmax(x, axis=-1):
    e = np.exp(x - np.max(x, axis=axis, keepdims=True))
    return e / np.sum(e, axis=axis, keepdims=True)


@register_op("softmax", reference=_np_softmax)
def softmax(x, axis=-1):
    """Numerically-stable softmax (fluid softmax_op / cudnn softmax)."""
    return jax.nn.softmax(x, axis=axis)


@register_op("log_softmax", reference=lambda x, axis=-1: np.log(_np_softmax(x, axis)))
def log_softmax(x, axis=-1):
    return jax.nn.log_softmax(x, axis=axis)


def _np_layer_norm(x, scale=None, bias=None, epsilon=1e-5, begin_norm_axis=1):
    axes = tuple(range(begin_norm_axis, np.ndim(x)))
    mean = np.mean(x, axis=axes, keepdims=True)
    var = np.var(x, axis=axes, keepdims=True)
    out = (x - mean) / np.sqrt(var + epsilon)
    if scale is not None:
        out = out * np.reshape(scale, x.shape[begin_norm_axis:])
    if bias is not None:
        out = out + np.reshape(bias, x.shape[begin_norm_axis:])
    return out


@register_op("layer_norm", reference=_np_layer_norm)
def layer_norm(x, scale=None, bias=None, epsilon=1e-5, begin_norm_axis=-1):
    """Layer normalization (fluid layer_norm_op; a Pallas fused variant lives
    in paddle_tpu.ops.pallas.layer_norm for the hot path)."""
    if begin_norm_axis < 0:
        begin_norm_axis = x.ndim + begin_norm_axis
    axes = tuple(range(begin_norm_axis, x.ndim))
    mean = jnp.mean(x, axis=axes, keepdims=True)
    var = jnp.var(x, axis=axes, keepdims=True)
    out = (x - mean) * jax.lax.rsqrt(var + epsilon)
    if scale is not None:
        out = out * scale.reshape(x.shape[begin_norm_axis:])
    if bias is not None:
        out = out + bias.reshape(x.shape[begin_norm_axis:])
    return out


@register_op("batch_norm")
def batch_norm(x, scale, bias, mean, variance, epsilon=1e-5, momentum=0.9,
               training=False, data_format="NHWC"):
    """Batch normalization (fluid batch_norm_op.cc).

    Returns (out, new_mean, new_variance). In inference mode the running
    stats pass through unchanged. Channel dim is last for NHWC, 1 for NCHW.
    """
    caxis = 1 if data_format == "NCHW" else x.ndim - 1
    shape = [1] * x.ndim
    shape[caxis] = x.shape[caxis]
    axes = tuple(i for i in range(x.ndim) if i != caxis)
    if training:
        batch_mean = jnp.mean(x, axis=axes)
        batch_var = jnp.var(x, axis=axes)
        new_mean = momentum * mean + (1 - momentum) * batch_mean
        new_var = momentum * variance + (1 - momentum) * batch_var
        use_mean, use_var = batch_mean, batch_var
    else:
        new_mean, new_var = mean, variance
        use_mean, use_var = mean, variance
    inv = jax.lax.rsqrt(use_var + epsilon) * scale
    out = (x - use_mean.reshape(shape)) * inv.reshape(shape) + bias.reshape(shape)
    return out, new_mean, new_var


def keep_mask(key, keep, shape):
    """Boolean mask of ``shape``: each element True independently with
    probability ``keep``, compared at 32 bits. The package's one way to
    draw a dropout mask.

    The bits come from XLA's ``RngBitGenerator``, which a TPU serves from
    its hardware generator; Threefry would hash every element in integer
    arithmetic on the VPU. The generator's 128-bit state is ``key``'s
    words repeated, as ``jax.random``'s own ``rbg`` seeding does; ``key``
    is a typed key or raw ``uint32`` key data, and splitting and
    ``fold_in`` upstream stay the caller's (Threefry). Same key and shape
    give the same mask on one backend; the bits differ between backends
    (a CPU expands Philox in XLA) and from ``jax.random.bernoulli``'s.
    Under ``vmap`` over keys JAX draws the whole batch from the first
    key's stream: rows stay independent of each other.
    """
    if jnp.issubdtype(key.dtype, jax.dtypes.prng_key):
        key = jax.random.key_data(key)
    state = jnp.concatenate([key] * (4 // key.shape[0]))
    _, bits = jax.lax.rng_bit_generator(state, shape, dtype=jnp.uint32)
    return bits < np.uint32(min(int(keep * 2 ** 32), 2 ** 32 - 1))


@register_op("dropout")
def dropout(x, key, rate=0.5, training=True):
    """Dropout with explicit PRNG key (fluid dropout_op; upscale_in_train).

    The mask is :func:`keep_mask`'s: the chip's bit generator, seeded from
    ``key``."""
    if not training or rate == 0.0:
        return x
    keep = 1.0 - rate
    mask = keep_mask(key, keep, x.shape)
    return jnp.where(mask, x / keep, 0.0).astype(x.dtype)


@register_op("lookup_table", has_grad=True)
def embedding(ids, table, padding_idx=None):
    """Embedding lookup (fluid lookup_table_op). Grad is an XLA scatter-add;
    the reference's SelectedRows sparse-grad machinery is unneeded."""
    out = jnp.take(table, ids, axis=0)
    if padding_idx is not None:
        mask = (ids != padding_idx)[..., None]
        out = out * mask.astype(out.dtype)
    return out


@register_op("one_hot", has_grad=False,
             reference=lambda ids, depth: np.eye(depth)[np.asarray(ids)])
def one_hot(ids, depth):
    return jax.nn.one_hot(ids, depth)


# -- losses ----------------------------------------------------------------

def _np_cross_entropy(logp_or_probs, label, soft_label=False):
    x = np.asarray(logp_or_probs)
    if soft_label:
        return -np.sum(label * np.log(x), axis=-1, keepdims=True)
    lbl = np.asarray(label).reshape(-1)
    flat = x.reshape(-1, x.shape[-1])
    picked = flat[np.arange(flat.shape[0]), lbl]
    return -np.log(picked).reshape(x.shape[:-1] + (1,))


@register_op("cross_entropy", reference=_np_cross_entropy)
def cross_entropy(probs, label, soft_label=False, epsilon=1e-12):
    """CE over probabilities (fluid cross_entropy_op; pair with softmax)."""
    logp = jnp.log(jnp.clip(probs, epsilon, 1.0))
    if soft_label:
        return -jnp.sum(label * logp, axis=-1, keepdims=True)
    lbl = label.astype(jnp.int32)
    if lbl.ndim == probs.ndim:  # fluid (N, 1) hard-label convention
        lbl = lbl.squeeze(-1)
    picked = jnp.take_along_axis(logp, lbl[..., None], axis=-1)
    return -picked


@register_op("softmax_with_cross_entropy")
def softmax_with_cross_entropy(logits, label, soft_label=False,
                               return_softmax=False, ignore_index=None):
    """Fused softmax+CE (fluid softmax_with_cross_entropy_op.cu — the fused
    CUDA kernel; on TPU XLA fuses logsumexp+gather into one pass)."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    if soft_label:
        loss = -jnp.sum(label * logp, axis=-1, keepdims=True)
    else:
        lbl = label.astype(jnp.int32)
        if lbl.ndim == logits.ndim:
            lbl = lbl.squeeze(-1)
        picked = jnp.take_along_axis(logp, lbl[..., None], axis=-1)
        loss = -picked
        if ignore_index is not None:
            loss = jnp.where(lbl[..., None] == ignore_index, 0.0, loss)
    if return_softmax:
        return loss, jnp.exp(logp)
    return loss


@register_op("sigmoid_cross_entropy_with_logits")
def sigmoid_cross_entropy_with_logits(x, label):
    """max(x,0) - x*z + log(1+exp(-|x|)) (fluid op of the same name)."""
    return jnp.maximum(x, 0) - x * label + jnp.log1p(jnp.exp(-jnp.abs(x)))


@register_op("square_error_cost",
             reference=lambda x, y: np.square(np.asarray(x) - np.asarray(y)))
def square_error_cost(input, label):
    return jnp.square(input - label)


@register_op("smooth_l1", reference=None)
def smooth_l1(x, y, sigma=1.0):
    diff = jnp.abs(x - y)
    s2 = sigma * sigma
    return jnp.where(diff < 1.0 / s2, 0.5 * s2 * diff * diff, diff - 0.5 / s2)


@register_op("kldiv_loss")
def kldiv_loss(x, target, reduction="mean"):
    loss = target * (jnp.log(jnp.clip(target, 1e-12)) - x)
    if reduction == "mean":
        return jnp.mean(loss)
    if reduction == "sum":
        return jnp.sum(loss)
    return loss


@register_op("margin_rank_loss")
def margin_rank_loss(label, left, right, margin=0.1):
    return jnp.maximum(0.0, -label * (left - right) + margin)


@register_op("huber_loss")
def huber_loss(input, label, delta=1.0):
    diff = jnp.abs(label - input)
    return jnp.where(diff <= delta, 0.5 * diff * diff,
                     delta * (diff - 0.5 * delta))


# -- misc nn ---------------------------------------------------------------

@register_op("label_smooth")
def label_smooth(label, epsilon=0.1):
    k = label.shape[-1]
    return label * (1 - epsilon) + epsilon / k


@register_op("pad", reference=lambda x, paddings, pad_value=0.0:
             np.pad(x, paddings, constant_values=pad_value))
def pad(x, paddings, pad_value=0.0):
    return jnp.pad(x, paddings, constant_values=pad_value)


@register_op("interpolate", has_grad=True)
def interpolate(x, size, method="nearest", data_format="NHWC"):
    """Image resize (fluid interpolate/image_resize ops)."""
    x = _to_nhwc(x, data_format)
    oh, ow = _pair(size)
    out = jax.image.resize(x, (x.shape[0], oh, ow, x.shape[3]), method=method)
    return _from_nhwc(out, data_format)


@register_op("grid_sampler", has_grad=True)
def grid_sampler(x, grid, data_format="NCHW"):
    """Bilinear grid sampling (fluid grid_sampler_op, used by STN-style
    detection heads). x: (N, C, H, W) NCHW (fluid layout; NHWC accepted
    via data_format); grid: (N, Ho, Wo, 2) normalized (x, y) in [-1, 1],
    align_corners=True mapping (-1 -> 0, 1 -> size-1), zero padding for
    samples outside the image — fluid 1.5 semantics. Fully differentiable
    w.r.t. both x and grid (gathers + lerps)."""
    nchw = data_format == "NCHW"
    if nchw:
        x = jnp.transpose(x, (0, 2, 3, 1))  # -> NHWC
    n, h, w, c = x.shape

    gx = (grid[..., 0] + 1.0) * 0.5 * (w - 1)   # (N, Ho, Wo)
    gy = (grid[..., 1] + 1.0) * 0.5 * (h - 1)
    x0 = jnp.floor(gx)
    y0 = jnp.floor(gy)
    wx = gx - x0
    wy = gy - y0

    def gather(img, yi, xi):
        """img (H,W,C); yi/xi int grids; zero outside bounds."""
        inb = (yi >= 0) & (yi < h) & (xi >= 0) & (xi < w)
        ys = jnp.clip(yi, 0, h - 1)
        xs = jnp.clip(xi, 0, w - 1)
        vals = img[ys, xs]                       # (Ho, Wo, C)
        return jnp.where(inb[..., None], vals, 0.0)

    def sample_one(img, x0, y0, wx, wy):
        xi0 = x0.astype(jnp.int32)
        yi0 = y0.astype(jnp.int32)
        v00 = gather(img, yi0, xi0)
        v01 = gather(img, yi0, xi0 + 1)
        v10 = gather(img, yi0 + 1, xi0)
        v11 = gather(img, yi0 + 1, xi0 + 1)
        wxe = wx[..., None]
        wye = wy[..., None]
        return (v00 * (1 - wye) * (1 - wxe) + v01 * (1 - wye) * wxe
                + v10 * wye * (1 - wxe) + v11 * wye * wxe)

    out = jax.vmap(sample_one)(x, x0, y0, wx, wy)  # (N, Ho, Wo, C)
    if nchw:
        out = jnp.transpose(out, (0, 3, 1, 2))
    return out


# -- nn long tail (root-op breadth) -----------------------------------------

@register_op("group_norm")
def group_norm(x, scale=None, bias=None, groups=32, epsilon=1e-5,
               data_format="NHWC"):
    """group_norm_op. x: (N, H, W, C) NHWC (reference is NCHW; the TPU
    layout is channel-last — pass data_format='NCHW' for parity shims)."""
    x = _to_nhwc(x, data_format)
    n, h, w, c = x.shape
    g = x.reshape(n, h, w, groups, c // groups)
    mean = g.mean(axis=(1, 2, 4), keepdims=True)
    var = g.var(axis=(1, 2, 4), keepdims=True)
    g = (g - mean) * jax.lax.rsqrt(var + epsilon)
    out = g.reshape(n, h, w, c)
    if scale is not None:
        out = out * scale
    if bias is not None:
        out = out + bias
    return _from_nhwc(out, data_format)


@register_op("instance_norm")
def instance_norm(x, scale=None, bias=None, epsilon=1e-5,
                  data_format="NHWC"):
    """instance_norm_op: per-(sample, channel) spatial normalization."""
    x = _to_nhwc(x, data_format)
    mean = x.mean(axis=(1, 2), keepdims=True)
    var = x.var(axis=(1, 2), keepdims=True)
    out = (x - mean) * jax.lax.rsqrt(var + epsilon)
    if scale is not None:
        out = out * scale
    if bias is not None:
        out = out + bias
    return _from_nhwc(out, data_format)


@register_op("lrn")
def lrn(x, n=5, k=1.0, alpha=1e-4, beta=0.75, data_format="NHWC"):
    """lrn_op (AlexNet local response norm) across channels."""
    x = _to_nhwc(x, data_format)
    sq = x * x
    half = n // 2
    pads = [(0, 0)] * 3 + [(half, n - 1 - half)]
    sq = jnp.pad(sq, pads)
    window = sum(sq[..., i:i + x.shape[-1]] for i in range(n))
    out = x / jnp.power(k + alpha * window, beta)
    return _from_nhwc(out, data_format)


@register_op("maxout")
def maxout(x, groups, axis=-1):
    """maxout_op: channel dim C -> C/groups by max over each group."""
    c = x.shape[axis]
    axis = axis % x.ndim
    new_shape = x.shape[:axis] + (c // groups, groups) + x.shape[axis + 1:]
    return jnp.max(x.reshape(new_shape), axis=axis + 1)


@register_op("pad2d")
def pad2d(x, paddings, mode="constant", pad_value=0.0,
          data_format="NHWC"):
    """pad2d_op: spatial padding (constant/reflect/edge).
    paddings: (top, bottom, left, right)."""
    x = _to_nhwc(x, data_format)
    t, b, l, r = paddings
    cfg = ((0, 0), (t, b), (l, r), (0, 0))
    if mode == "constant":
        out = jnp.pad(x, cfg, constant_values=pad_value)
    else:
        out = jnp.pad(x, cfg, mode={"reflect": "reflect",
                                    "edge": "edge"}[mode])
    return _from_nhwc(out, data_format)


@register_op("affine_grid")
def affine_grid(theta, out_shape):
    """affine_grid_op (STN, pairs with grid_sampler): theta (N, 2, 3) ->
    normalized sampling grid (N, H, W, 2) with align_corners semantics."""
    n, h, w = out_shape[0], out_shape[-2], out_shape[-1]
    ys = jnp.linspace(-1.0, 1.0, h)
    xs = jnp.linspace(-1.0, 1.0, w)
    gy, gx = jnp.meshgrid(ys, xs, indexing="ij")
    ones = jnp.ones_like(gx)
    base = jnp.stack([gx, gy, ones], axis=-1).reshape(1, h * w, 3)
    grid = jnp.einsum("bnk,bjk->bnj", jnp.broadcast_to(
        base, (n, h * w, 3)), theta)            # (N, HW, 2)
    return grid.reshape(n, h, w, 2)


@register_op("affine_channel")
def affine_channel(x, scale, bias, data_format="NHWC"):
    """affine_channel_op: per-channel y = scale * x + bias (frozen-BN
    form used by detection backbones)."""
    x = _to_nhwc(x, data_format)
    return _from_nhwc(x * scale + bias, data_format)


@register_op("log_loss", reference=lambda pred, label, epsilon=1e-4:
             -label * np.log(pred + epsilon)
             - (1 - label) * np.log(1 - pred + epsilon))
def log_loss(pred, label, epsilon=1e-4):
    return -label * jnp.log(pred + epsilon) \
        - (1.0 - label) * jnp.log(1.0 - pred + epsilon)


@register_op("rank_loss", reference=lambda label, left, right:
             np.log1p(np.exp(-np.abs(left - right)))
             + np.maximum(left - right, 0) - label * (left - right))
def rank_loss(label, left, right):
    """rank_loss_op (RankNet pairwise). softplus form: log1p(exp(d))
    overflows for d > ~88 in f32 and poisons grads with NaN."""
    return jax.nn.softplus(left - right) - label * (left - right)


@register_op("hinge_loss", reference=lambda logits, label:
             np.maximum(0.0, 1.0 - (2.0 * label - 1.0) * logits))
def hinge_loss(logits, label):
    return jnp.maximum(0.0, 1.0 - (2.0 * label - 1.0) * logits)


@register_op("cos_sim")
def cos_sim(x, y, epsilon=1e-12):
    """cos_sim_op: row-wise cosine similarity (B, D) -> (B, 1)."""
    nx = jnp.linalg.norm(x, axis=-1, keepdims=True)
    ny = jnp.linalg.norm(y, axis=-1, keepdims=True)
    return (x * y).sum(-1, keepdims=True) / jnp.maximum(nx * ny, epsilon)


@register_op("bilinear_tensor_product")
def bilinear_tensor_product(x, y, weight, bias=None):
    """bilinear_tensor_product_op: out[:, k] = x W_k y^T.
    x (B, M), y (B, N), weight (K, M, N) -> (B, K)."""
    out = jnp.einsum("bm,kmn,bn->bk", x, weight, y)
    if bias is not None:
        out = out + bias
    return out


# ---------------------------------------------------------------------------
# loss long tail (mse_loss, dice_loss, bpr_loss, npair_loss, center_loss,
# teacher_student_sigmoid_loss, sampled_softmax, nce, hsigmoid — fluid
# layers/nn.py + loss_op family)
# ---------------------------------------------------------------------------

@register_op("mse_loss")
def mse_loss(input, label):
    """mse_loss: mean squared error."""
    return jnp.mean((input - label) ** 2)


@register_op("dice_loss")
def dice_loss(input, label, epsilon=1e-5):
    """dice_loss (segmentation): 1 - 2|X∩Y| / (|X|+|Y|). ``input`` (N, C)
    probabilities, ``label`` (N,) int or (N, C) one-hot."""
    if label.ndim == input.ndim - 1:
        label = jax.nn.one_hot(label, input.shape[-1], dtype=input.dtype)
    reduce_dims = tuple(range(1, input.ndim))
    inter = (input * label).sum(reduce_dims)
    union = input.sum(reduce_dims) + label.sum(reduce_dims)
    return (1.0 - (2.0 * inter + epsilon) / (union + epsilon)).mean()


@register_op("bpr_loss")
def bpr_loss(input, label):
    """bpr_loss (Bayesian personalized ranking, session-based recs):
    -mean log sigmoid(score[label] - score[j]) over the other columns.
    ``input`` (N, C) scores, ``label`` (N,) int."""
    n, c = input.shape
    pos = jnp.take_along_axis(input, label[:, None], -1)      # (N, 1)
    diff = pos - input                                        # (N, C)
    logsig = jax.nn.log_sigmoid(diff)
    mask = jnp.arange(c)[None, :] != label[:, None]
    return -(logsig * mask).sum() / (n * (c - 1))


@register_op("npair_loss")
def npair_loss(anchor, positive, labels, l2_reg=0.002):
    """npair_loss (metric learning): softmax CE over anchor·positiveᵀ
    with same-label targets + L2 on embeddings."""
    labels = labels.reshape(-1)
    sim = anchor @ positive.T                                 # (N, N)
    same = (labels[:, None] == labels[None, :]).astype(anchor.dtype)
    targets = same / same.sum(-1, keepdims=True)
    logp = jax.nn.log_softmax(sim, -1)
    ce = -(targets * logp).sum(-1).mean()
    l2 = (anchor ** 2).sum(-1).mean() + (positive ** 2).sum(-1).mean()
    return ce + l2_reg * 0.25 * l2


@register_op("center_loss")
def center_loss(features, label, centers, alpha=0.1):
    """center_loss_op: pull features toward per-class centers. Returns
    (loss (N,), updated centers) — the reference updates centers in-place;
    functionally the new centers come back to the caller."""
    picked = centers[label]                                   # (N, D)
    diff = features - picked
    loss = 0.5 * (diff ** 2).sum(-1)
    # center update: c_y -= alpha * mean over batch members of class y
    counts = jnp.zeros((centers.shape[0],), features.dtype
                       ).at[label].add(1.0)
    sums_ = jnp.zeros_like(centers).at[label].add(diff)
    new_centers = centers + alpha * sums_ / jnp.maximum(
        counts[:, None], 1.0)
    return loss, jax.lax.stop_gradient(new_centers)


@register_op("teacher_student_sigmoid_loss")
def teacher_student_sigmoid_loss(x, label, soft_max_up_bound=15.0,
                                 soft_max_lower_bound=-15.0):
    """teacher_student_sigmoid_loss_op (CTR distillation): log(1+exp(x)) -
    x*z + sigmoid-CE against the teacher's soft score."""
    x = jnp.clip(x, soft_max_lower_bound, soft_max_up_bound)
    return sigmoid_cross_entropy_with_logits(x, label).mean()


@register_op("sampled_softmax_with_cross_entropy", has_grad=True)
def sampled_softmax_with_cross_entropy(logits_fn, label, key, *,
                                       num_samples, num_classes):
    """sampled_softmax_with_cross_entropy_op: CE over {true class} ∪
    uniform negative samples. ``logits_fn(ids) -> (N, len(ids))`` computes
    logits only for the sampled columns (the point of sampling: never
    materialize the full vocab)."""
    neg = jax.random.randint(key, (num_samples,), 0, num_classes)
    ids = jnp.concatenate([label.reshape(-1), neg])            # (N + S,)
    logits = logits_fn(ids)                                    # (N, N+S)
    n = label.shape[0]
    tgt = jnp.arange(n)                                        # true col i
    # remove accidental hits (reference remove_accidental_hits=True):
    # any column whose id equals the row's true label, other than the
    # row's own column, must not appear in the denominator
    hit = (ids[None, :] == label.reshape(-1)[:, None]) & \
        (jnp.arange(ids.shape[0])[None, :] != tgt[:, None])
    logits = jnp.where(hit, -jnp.inf, logits)
    logp = jax.nn.log_softmax(logits, -1)
    return -jnp.take_along_axis(logp, tgt[:, None], -1).mean()


@register_op("nce")
def nce(emb, weight, bias, label, key, *, num_neg, num_classes):
    """nce_op (noise-contrastive estimation, uniform noise): binary
    logistic on the true class + ``num_neg`` uniform negatives.
    ``emb`` (N, D); ``weight`` (C, D); ``bias`` (C,)."""
    n = emb.shape[0]
    neg = jax.random.randint(key, (n, num_neg), 0, num_classes)
    pos_logit = (emb * weight[label]).sum(-1) + bias[label]    # (N,)
    neg_logit = jnp.einsum("nd,nkd->nk", emb, weight[neg]) + bias[neg]
    log_q = -jnp.log(float(num_classes))                       # uniform
    pos = jax.nn.log_sigmoid(pos_logit - log_q)
    negl = jax.nn.log_sigmoid(-(neg_logit - log_q)).sum(-1)
    return -(pos + negl).mean()


@register_op("hsigmoid")
def hsigmoid(x, weight, bias, label, *, num_classes):
    """hsigmoid_op (hierarchical sigmoid over the default complete binary
    tree, like the reference's non-custom-tree path): the label's root-to-
    leaf path is decoded from its binary representation; loss is the sum
    of binary logistic losses at the (num_classes-1) internal nodes on
    the path. ``weight`` (num_classes - 1, D); ``bias`` (num_classes-1,)."""
    # complete-binary-tree paths: node ids 1..C-1 heap-style; leaf for
    # class y is node (C + y); walk ancestors.
    c = num_classes
    depth = int(np.ceil(np.log2(c))) if c > 1 else 1
    leaf = label + c                                           # (N,)
    codes = []
    nodes = []
    cur = leaf
    for _ in range(depth):
        bit = cur % 2                                          # left/right
        cur = cur // 2
        nodes.append(cur)                                      # ancestor
        codes.append(bit)
    nodes = jnp.stack(nodes, -1)                               # (N, depth)
    codes = jnp.stack(codes, -1).astype(x.dtype)
    valid = nodes >= 1
    idx = jnp.clip(nodes - 1, 0, c - 2)                        # weight row
    logits = jnp.einsum("nd,nkd->nk", x, weight[idx]) + bias[idx]
    # code 1 -> target 1, code 0 -> target 0 (sign convention of the op)
    bce = sigmoid_cross_entropy_with_logits(logits, codes)
    return (bce * valid).sum(-1).mean()


# ---------------------------------------------------------------------------
# normalization / misc nn tail
# ---------------------------------------------------------------------------

@register_op("data_norm")
def data_norm(x, batch_size, batch_sum, batch_square_sum, epsilon=1e-4):
    """data_norm_op (CTR): normalize by running sum statistics kept as
    plain tensors (means the caller accumulates them — the reference
    stores them as persistable params updated per batch). Returns
    (normalized x, new_size, new_sum, new_square_sum)."""
    mean = batch_sum / batch_size
    var = batch_square_sum / batch_size - mean ** 2
    out = (x - mean) / jnp.sqrt(var + epsilon)
    n = x.shape[0]
    return (out,
            batch_size + n,
            batch_sum + x.sum(0),
            batch_square_sum + (x ** 2).sum(0))


@register_op("spectral_norm")
def spectral_norm(weight, u, *, power_iters=1, epsilon=1e-12):
    """spectral_norm_op: W / sigma_max(W) via power iteration. ``u``
    (rows,) is the persistent left singular vector estimate; returns
    (normalized weight, new_u)."""
    w = weight.reshape(weight.shape[0], -1)

    def it(u, _):
        v = w.T @ u
        v = v / jnp.maximum(jnp.linalg.norm(v), epsilon)
        u = w @ v
        u = u / jnp.maximum(jnp.linalg.norm(u), epsilon)
        return u, v

    u, v = jax.lax.scan(it, u, None, length=power_iters)
    sigma = u @ w @ v[-1]          # scan stacks v: last iterate is v[-1]
    return weight / sigma, jax.lax.stop_gradient(u)


@register_op("add_position_encoding")
def add_position_encoding(x, alpha=1.0, beta=1.0):
    """add_position_encoding_op: x*alpha + beta*sinusoid (B, T, D)."""
    b, t, d = x.shape
    pos = jnp.arange(t, dtype=jnp.float32)[:, None]
    i = jnp.arange(d // 2, dtype=jnp.float32)[None, :]
    angle = pos / jnp.power(10000.0, 2 * i / d)
    pe = jnp.concatenate([jnp.sin(angle), jnp.cos(angle)], -1)
    return x * alpha + beta * pe[None, :, :].astype(x.dtype)


@register_op("mean_iou", has_grad=False)
def mean_iou(pred, label, num_classes):
    """mean_iou_op: mean intersection-over-union over classes present."""
    pred = pred.reshape(-1)
    label = label.reshape(-1)
    inter = jnp.zeros((num_classes,)).at[
        jnp.where(pred == label, pred, num_classes - 1)].add(
        (pred == label).astype(jnp.float32))
    area_p = jnp.zeros((num_classes,)).at[pred].add(1.0)
    area_l = jnp.zeros((num_classes,)).at[label].add(1.0)
    union = area_p + area_l - inter
    present = union > 0
    iou = jnp.where(present, inter / jnp.maximum(union, 1.0), 0.0)
    return iou.sum() / jnp.maximum(present.sum(), 1)


@register_op("row_conv")
def row_conv(x, weight):
    """row_conv_op (lookahead conv, Deep Speech 2): out[t] = sum_{k}
    x[t+k] * w[k] with future context only. ``x`` (B, T, D); ``weight``
    (K, D)."""
    k = weight.shape[0]
    pads = [(0, 0), (0, k - 1), (0, 0)]
    xp = jnp.pad(x, pads)
    return sum(xp[:, i:i + x.shape[1], :] * weight[i]
               for i in range(k))


@register_op("im2sequence", has_grad=True)
def im2sequence(x, filter_size, stride=1, padding=0):
    """im2sequence_op (OCR): slide a window over NHWC images; each window
    flattens to one timestep. Returns (B, out_h*out_w, fh*fw*C)."""
    fh, fw = _pair(filter_size)
    sh, sw = _pair(stride)
    ph, pw = _pair(padding)
    patches = jax.lax.conv_general_dilated_patches(
        x, (fh, fw), (sh, sw), ((ph, ph), (pw, pw)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    b, oh, ow, _ = patches.shape
    return patches.reshape(b, oh * ow, -1)


@register_op("similarity_focus", has_grad=False)
def similarity_focus(x, axis, indexes):
    """similarity_focus_op: binary attention mask — for each selected
    channel index along ``axis``, mark the argmax positions of every
    other (row, col) slice. Simplified faithful variant: mask where the
    selected slice attains its per-sample spatial max."""
    masks = []
    for idx in indexes:
        sl = jax.lax.index_in_dim(x, idx, axis, keepdims=True)
        spatial_axes = tuple(i for i in range(1, x.ndim) if i != axis)
        m = sl == sl.max(axis=spatial_axes, keepdims=True)
        masks.append(jnp.broadcast_to(m, x.shape))
    out = masks[0]
    for m in masks[1:]:
        out = out | m
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# 3-D conv/pool family (conv3d_op, pool3d_op — video/volumetric)
# ---------------------------------------------------------------------------

def _triple(v):
    return (v, v, v) if isinstance(v, int) else tuple(v)


@register_op("conv3d")
def conv3d(x, weight, bias=None, stride=1, padding=0, dilation=1,
           groups=1):
    """conv3d_op: NDHWC; weight DHWIO."""
    stride = _triple(stride)
    dilation = _triple(dilation)
    if isinstance(padding, str):
        pad = padding
    else:
        pd, ph, pw = _triple(padding)
        pad = ((pd, pd), (ph, ph), (pw, pw))
    out = jax.lax.conv_general_dilated(
        x, weight, stride, pad, rhs_dilation=dilation,
        dimension_numbers=("NDHWC", "DHWIO", "NDHWC"),
        feature_group_count=groups)
    if bias is not None:
        out = out + bias
    return out


@register_op("conv3d_transpose")
def conv3d_transpose(x, weight, bias=None, stride=1, padding=0):
    """conv3d_transpose_op via lhs dilation. Integer/tuple padding only
    (string modes would silently mean something else here)."""
    if isinstance(padding, str):
        raise ValueError(
            "conv3d_transpose takes integer/tuple padding, not "
            f"{padding!r} (SAME/VALID are ambiguous for deconv)")
    stride = _triple(stride)
    pd, ph, pw = _triple(padding)
    kd, kh, kw = weight.shape[:3]
    pad = ((kd - 1 - pd, kd - 1 - pd), (kh - 1 - ph, kh - 1 - ph),
           (kw - 1 - pw, kw - 1 - pw))
    out = jax.lax.conv_general_dilated(
        x, jnp.flip(weight, (0, 1, 2)),
        (1, 1, 1), pad, lhs_dilation=stride,
        dimension_numbers=("NDHWC", "DHWIO", "NDHWC"))
    if bias is not None:
        out = out + bias
    return out


@register_op("pool3d")
def pool3d(x, kernel=2, stride=None, padding=0, pool_type="max"):
    """pool3d_op: NDHWC max/avg pooling."""
    kd, kh, kw = _triple(kernel)
    stride = _triple(stride if stride is not None else kernel)
    pd, ph, pw = _triple(padding)
    dims = (1, kd, kh, kw, 1)
    strides = (1,) + stride + (1,)
    pads = ((0, 0), (pd, pd), (ph, ph), (pw, pw), (0, 0))
    if pool_type == "max":
        init = -jnp.inf
        out = jax.lax.reduce_window(x, init, jax.lax.max, dims, strides,
                                    pads)
    else:
        out = jax.lax.reduce_window(x, 0.0, jax.lax.add, dims, strides,
                                    pads)
        cnt = jax.lax.reduce_window(jnp.ones_like(x), 0.0, jax.lax.add,
                                    dims, strides, pads)
        out = out / cnt
    return out


@register_op("adaptive_pool3d")
def adaptive_pool3d(x, output_size, pool_type="avg"):
    """adaptive_pool3d_op: divisible sizes only (static shapes)."""
    od, oh, ow = _triple(output_size)
    b, d, h, w, c = x.shape
    if d % od or h % oh or w % ow:
        raise NotImplementedError(
            "adaptive_pool3d needs divisible spatial dims on TPU "
            f"(got {(d, h, w)} -> {(od, oh, ow)})")
    xr = x.reshape(b, od, d // od, oh, h // oh, ow, w // ow, c)
    if pool_type == "max":
        return xr.max(axis=(2, 4, 6))
    return xr.mean(axis=(2, 4, 6))


# --- image-resize aliases (image_resize/resize_* fluid layers) ------------

def resize_bilinear(x, size, data_format="NHWC"):
    """resize_bilinear (bilinear_interp_op)."""
    return interpolate(x, size, method="bilinear",
                       data_format=data_format)


def resize_nearest(x, size, data_format="NHWC"):
    """resize_nearest (nearest_interp_op)."""
    return interpolate(x, size, method="nearest",
                       data_format=data_format)


def image_resize(x, size, method="bilinear", data_format="NHWC"):
    """layers.image_resize."""
    return interpolate(x, size, method=method, data_format=data_format)


def image_resize_short(x, short_len, method="bilinear"):
    """layers.image_resize_short: scale so the short side == short_len."""
    h, w = x.shape[1], x.shape[2]
    if h <= w:
        oh, ow = short_len, int(round(w * short_len / h))
    else:
        oh, ow = int(round(h * short_len / w)), short_len
    return interpolate(x, (oh, ow), method=method)


@register_op("resize_trilinear")
def resize_trilinear(x, size):
    """trilinear_interp_op: NDHWC volumetric resize."""
    od, oh, ow = _triple(size) if not isinstance(size, tuple) else size
    return jax.image.resize(
        x, (x.shape[0], od, oh, ow, x.shape[4]), method="trilinear")


@register_op("cvm")
def continuous_value_model(x, *, use_cvm=True):
    """cvm_op (CTR): embeddings arrive with leading (show, click)
    counters per feature; with ``use_cvm`` they become
    (log(show+1), log(click+1) - log(show+1)) — otherwise the two
    counter slots are dropped. ``x`` (B, D), D >= 2."""
    show = jnp.log(x[:, :1] + 1.0)
    click = jnp.log(x[:, 1:2] + 1.0) - show
    if use_cvm:
        return jnp.concatenate([show, click, x[:, 2:]], -1)
    return x[:, 2:]


@register_op("filter_by_instag", has_grad=False)
def filter_by_instag(ins, ins_tags, filter_tags):
    """filter_by_instag_op (CTR multi-task): keep rows whose tag set
    intersects ``filter_tags``. Static shapes: returns (rows reordered
    kept-first, keep_mask, index mapping) instead of the reference's
    dynamically-sized output. ``ins_tags`` (B, T) padded with -1;
    ``filter_tags`` (K,)."""
    # a -1-padded filter_tags entry must never match -1-padded ins tags
    match = (ins_tags[:, :, None] == filter_tags[None, None, :]) \
        & (filter_tags[None, None, :] >= 0)
    hit = match.any((1, 2))
    order = jnp.argsort(~hit)                  # kept rows first, stable
    return ins[order], hit[order], order
