"""Shared model-zoo pieces (the LayerHelper-style glue every classifier
repeats in the reference's PaddleCV zoo)."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from paddle_tpu.ops import nn as ops_nn


def classification_loss(logits, label):
    """Softmax cross-entropy + top-1 accuracy — the standard image-
    classification loss head (softmax_with_cross_entropy + accuracy op)."""
    loss = ops_nn.softmax_with_cross_entropy(
        logits, label[:, None]).mean()
    acc = (logits.argmax(-1) == label).mean()
    return loss, {"acc": acc}


def rms_norm(x, scale, eps):
    """``x * rsqrt(mean(x^2) + eps) * scale`` over the last axis, in
    float32, handed back in ``x``'s type."""
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def rope(x, positions, theta, rotary_dim=None):
    """Rotary positions over the last axis, rotate-half pairing ``(i, i +
    r/2)`` inside its first ``rotary_dim`` = r entries (the whole axis
    where None); the rest passes through. ``x`` (S, C, heads, d) or (S,
    C, d); ``positions`` (S, C)."""
    if rotary_dim is not None and rotary_dim != x.shape[-1]:
        return jnp.concatenate(
            [rope(x[..., :rotary_dim], positions, theta),
             x[..., rotary_dim:]], -1)
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions.astype(jnp.float32)[..., None] * inv       # (S,C,d/2)
    if x.ndim == 4:
        ang = ang[:, :, None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x32 = x.astype(jnp.float32)
    a, b = x32[..., :d // 2], x32[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           -1).astype(x.dtype)


#: elements of one piece when a large matrix is drawn (the float32 draw of
#: a whole embedding would not fit beside the model)
_INIT_PIECE = 1 << 25


def normal_init(key, shape, dtype, std=0.02):
    """``std * N(0, 1)`` made in ``dtype``; a large matrix a piece of its
    leading axis at a time."""
    lead, size = shape[0], 1
    for n in shape:
        size *= n
    pieces = next(n for n in range(1, lead + 1)
                  if lead % n == 0 and size // n <= _INIT_PIECE)
    if pieces == 1:
        return (std * jax.random.normal(key, shape, jnp.float32)
                ).astype(dtype)
    piece = (lead // pieces,) + tuple(shape[1:])
    out = jax.lax.map(
        lambda k: (std * jax.random.normal(k, piece, jnp.float32)
                   ).astype(dtype), jax.random.split(key, pieces))
    return out.reshape(shape)


def matmul_precision(dtype):
    """``HIGHEST`` for float32 operands, the default for narrower ones
    (said outright: under a process-wide default of "highest" None would
    ask for fp32 passes over bf16 operands)."""
    return jax.lax.Precision.HIGHEST if jnp.dtype(dtype) == jnp.float32 \
        else jax.lax.Precision.DEFAULT


def project(x, w):
    """``x @ w`` with operands of the weight's type, summed in float32."""
    return jnp.matmul(x.astype(w.dtype), w,
                      precision=matmul_precision(w.dtype),
                      preferred_element_type=jnp.float32)
