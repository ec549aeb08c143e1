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


def rope(x, positions, theta):
    """Rotary positions over the whole last axis, rotate-half pairing
    ``(i, i + d/2)``. ``x`` (S, C, heads, d) or (S, C, d); ``positions``
    (S, C)."""
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
