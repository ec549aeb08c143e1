"""The plain reference of ``models/latent_conv_moe_lm.py`` for tier-1: the
equations as written down (the model's docstring, ISSUE 35), float32
``jax.numpy``, whole sequence; the two convs and the value shift written
as shifts of the sequence, the experts as a dense sum over a one-hot
choice; no cache, no tails, no kernel, no chunks, no batching, no
blocking. ``benchmark/families/zaya.py`` holds the same reference
computed in blocks for the chip's sizes;
``test_latent_conv_moe_serving.py`` holds the two to each other.

It reads the program's parameter tree and its config's published keys, and
shares no code with it. Call it under
``jax.default_matmul_precision("highest")``.
"""

import math

import jax.numpy as jnp
from jax.scipy.special import erf


def _f32(a):
    return a.astype(jnp.float32)


def _rms(u, g, eps):
    return g * u / jnp.sqrt(jnp.mean(u * u, -1, keepdims=True) + eps)


def _silu(u):
    return u / (1.0 + jnp.exp(-u))


def _gelu(u):
    return 0.5 * u * (1.0 + erf(u / math.sqrt(2.0)))


def _softmax(s):
    e = jnp.exp(s - s.max(-1, keepdims=True))
    return e / e.sum(-1, keepdims=True)


def _before(a):
    """Row ``t`` holds row ``t - 1``; row 0 zeros."""
    return jnp.concatenate([jnp.zeros_like(a[:1]), a[:-1]])


def _rope(u, theta, rotary):
    """The first ``rotary`` entries of each head rotated, pairing ``(i, i
    + rotary/2)``; ``u`` (N, heads, d), row ``t`` at position ``t``."""
    n, half = u.shape[0], rotary // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / rotary)
    ang = (jnp.arange(n, dtype=jnp.float32)[:, None] * freq)[:, None, :]
    lo, hi, rest = u[..., :half], u[..., half:rotary], u[..., rotary:]
    return jnp.concatenate([lo * jnp.cos(ang) - hi * jnp.sin(ang),
                            hi * jnp.cos(ang) + lo * jnp.sin(ang), rest], -1)


def reference_logits(params, ids, cfg, value_shift=True):
    """(N,) ids -> (N, V) float32 logits. ``value_shift=False`` is a
    control: every V head from the token itself."""
    n = ids.shape[0]
    d = cfg.head_dim
    h, g = cfg.num_attention_heads, cfg.num_key_value_heads
    eps = cfg.rms_norm_eps
    rotary = int(d * cfg.partial_rotary_factor)
    emb = _f32(params["embed"]["weight"])
    x = emb[ids]
    causal = jnp.tril(jnp.ones((n, n), bool))
    r_before = None
    for i in range(cfg.num_hidden_layers):
        lp = {k: v for k, v in params["layers"][str(i)].items()}
        w = lambda name: _f32(lp[name]["weight"])            # noqa: E731
        u = _rms(x, _f32(lp["attn_norm"]["scale"]), eps)
        z = jnp.concatenate([u @ w("q_proj"), u @ w("k_proj")], -1)
        w0, b0 = w("conv0"), _f32(lp["conv0"]["bias"])
        c = b0 + w0[:, 0] * _before(z) + w0[:, 1] * z
        w1, b1 = w("conv1"), _f32(lp["conv1"]["bias"])      # (2,H+G,d,d)
        per_head = lambda a: a.reshape(n, h + g, d)          # noqa: E731
        s = b1 + (jnp.einsum("nhi,hio->nho", per_head(_before(c)), w1[0])
                  + jnp.einsum("nhi,hio->nho", per_head(c), w1[1])
                  ).reshape(n, -1)
        zq, zk = z[:, :h * d].reshape(n, h, d), z[:, h * d:].reshape(n, g, d)
        sq, sk = s[:, :h * d].reshape(n, h, d), s[:, h * d:].reshape(n, g, d)
        group = h // g
        q = sq + 0.5 * (zq + jnp.repeat(zk, group, axis=1))
        k = sk + 0.5 * (zq.reshape(n, g, group, d).mean(2) + zk)
        norm = lambda a: jnp.sqrt(jnp.sum(a * a, -1, keepdims=True))  # noqa
        q = math.sqrt(d) * q / norm(q)
        k = math.sqrt(d) * _f32(lp["temperature"])[:, None] * k / norm(k)
        q = _rope(q, cfg.rope_theta, rotary)
        k = _rope(k, cfg.rope_theta, rotary)
        late = u @ w("v_shift_proj")
        v = jnp.concatenate(
            [u @ w("v_proj"), _before(late) if value_shift else late],
            -1).reshape(n, g, d)
        scores = jnp.einsum("qhd,khd->hqk", q, jnp.repeat(k, group, axis=1))
        scores = jnp.where(causal, scores / math.sqrt(d), -jnp.inf)
        att = jnp.einsum("hqk,khd->qhd", _softmax(scores),
                         jnp.repeat(v, group, axis=1)).reshape(n, h * d)
        res = lp["attn_residual"]
        x = _f32(res["keep"]) * x + _f32(res["add"]) * (att @ w("o_proj"))

        rp = lp["router"]
        t = _rms(x, _f32(lp["ffn_norm"]["scale"]), eps)
        r = t @ _f32(rp["in_proj"]["weight"]) + _f32(rp["in_proj"]["bias"])
        if r_before is not None:
            r = r + _f32(rp["carry_scale"]) * r_before
        r_before = r
        hid = _rms(r, _f32(rp["norm"]["scale"]), eps)
        hid = _gelu(_gelu(hid @ _f32(rp["fc1"]["weight"]))
                    @ _f32(rp["fc2"]["weight"]))
        p = _softmax(hid @ _f32(rp["out_proj"]["weight"]))
        pick = jnp.argmax(p + _f32(rp["balance_bias"]), -1)  # ties: lower e
        y = jnp.zeros_like(x)
        ex = lp["experts"]
        for e in range(cfg.num_experts):
            coef = jnp.where(pick == e, p[:, e], 0.0)
            hidden = _silu(t @ _f32(ex["gate"][e]).T) * (
                t @ _f32(ex["up"][e]).T)
            y = y + coef[:, None] * (hidden @ _f32(ex["down"][e]))
        res = lp["ffn_residual"]
        x = _f32(res["keep"]) * x + _f32(res["add"]) * y
    return _rms(x, _f32(params["final_norm"]["scale"]), eps) @ emb.T
