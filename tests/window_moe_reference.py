"""The plain reference of ``models/window_moe_lm.py`` (ISSUE 40's
equations): float32 ``jax.numpy``, no cache, no kernels, no chunks, no
batching; the window written as a mask over dense causal scores, the
experts as a dense weighted sum over every routed expert. It reads the
program's parameter tree and shares no code with it. Call it under
``jax.default_matmul_precision("highest")``.

``sizes`` holds the published keys (``hidden_size``, ``layer_types``,
``sliding_window``, ``mlp_layer_types``, ``num_experts_per_tok``,
``routed_scaling_factor`` ...). The tree's experts are those of
``sizes["expert_offset"]`` (0 where absent) on, as many as it holds: all
of the router's (the uncut layer), or a chip's share, whose part of the
sum this then computes. Departures from the published description: none
known; what the config does not settle is listed in ISSUE 40 (pre-norm
residuals, per-head QK norm, rotary embedding on the window layers only,
the window counted with the token itself, SwiGLU gate order).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def sizes_of(cfg, **over):
    """The published keys the reference reads, from a program config."""
    sizes = {k: getattr(cfg, k) for k in (
        "hidden_size", "num_hidden_layers", "num_attention_heads",
        "num_key_value_heads", "head_dim", "rms_norm_eps", "sliding_window",
        "num_experts_per_tok", "routed_scaling_factor", "norm_topk_prob")}
    sizes.update(layer_types=list(cfg.layer_types),
                 mlp_layer_types=list(cfg.mlp_layer_types),
                 rope_parameters={"rope_theta": cfg.rope_theta},
                 expert_offset=cfg.expert_offset, **over)
    return sizes


def _f32(a):
    return a.astype(jnp.float32)


def _rms(u, g, eps):
    return _f32(g) * u / jnp.sqrt(jnp.mean(u * u, -1, keepdims=True) + eps)


def _silu(u):
    return u / (1.0 + jnp.exp(-u))


def _rope(u, pos, theta):
    """Every entry of each head rotated, pairing ``(i, i + d/2)``; ``u``
    (N, heads, d), ``pos`` (N,)."""
    half = u.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = (pos.astype(jnp.float32)[:, None] * freq)[:, None, :]
    lo, hi = u[..., :half], u[..., half:]
    return jnp.concatenate([lo * jnp.cos(ang) - hi * jnp.sin(ang),
                            hi * jnp.cos(ang) + lo * jnp.sin(ang)], -1)


def _swiglu(t, p):
    return (_silu(t @ _f32(p["gate"]["weight"]))
            * (t @ _f32(p["up"]["weight"]))) @ _f32(p["down"]["weight"])


def reference_ffn(lp, t, sizes, shared=True):
    """What the MLP half of a layer adds for normed input ``t`` (N, D):
    the dense MLP, or the routed experts this tree holds (a dense
    weighted sum, the weight 0 where the router did not pick the expert)
    and, with ``shared``, the shared expert."""
    if "mlp" in lp:
        return _swiglu(t, lp["mlp"])
    k = sizes["num_experts_per_tok"]
    s = 1.0 / (1.0 + jnp.exp(-(t @ _f32(lp["router"]["weight"]))))
    sel = s + _f32(lp["router"]["selection_bias"])
    # the k largest, ties to the lower index: rank by (value, -index)
    order = jnp.argsort(-sel, axis=-1, stable=True)[:, :k]
    picked = jnp.zeros_like(s, bool).at[
        jnp.arange(s.shape[0])[:, None], order].set(True)
    top = jnp.where(picked, s, 0.0)
    if sizes.get("norm_topk_prob", True):
        top = top / top.sum(-1, keepdims=True)
    w = sizes["routed_scaling_factor"] * top                 # (N, routed)
    ex, off = lp["experts"], sizes.get("expert_offset", 0)
    y = jnp.zeros_like(t)
    for e in range(ex["gate"].shape[0]):
        hidden = _silu(t @ _f32(ex["gate"][e]).T) * (t @ _f32(ex["up"][e]).T)
        y = y + w[:, off + e, None] * (hidden @ _f32(ex["down"][e]))
    return y + _swiglu(t, lp["shared"]) if shared else y


def reference_logits(params, ids, sizes):
    """(N,) ids -> (N, V) float32 logits over the rows of the vocabulary
    the tree holds."""
    n = ids.shape[0]
    h, g, d = (sizes["num_attention_heads"], sizes["num_key_value_heads"],
               sizes["head_dim"])
    eps = sizes["rms_norm_eps"]
    theta = float(sizes["rope_parameters"]["rope_theta"])
    pos = jnp.arange(n)
    x = _f32(params["embed"]["weight"][ids])
    for i in range(sizes["num_hidden_layers"]):
        lp = params["layers"][str(i)]
        a = _rms(x, lp["attn_norm"]["scale"], eps)
        q = (a @ _f32(lp["q_proj"]["weight"])).reshape(n, h, d)
        k = (a @ _f32(lp["k_proj"]["weight"])).reshape(n, g, d)
        v = (a @ _f32(lp["v_proj"]["weight"])).reshape(n, g, d)
        q = _rms(q, lp["q_norm"]["scale"], eps)
        k = _rms(k, lp["k_norm"]["scale"], eps)
        seen = pos[None, :] <= pos[:, None]
        if sizes["layer_types"][i] == "sliding_attention":
            q, k = _rope(q, pos, theta), _rope(k, pos, theta)
            seen = seen & (pos[None, :] > pos[:, None]
                           - sizes["sliding_window"])
        kk = jnp.repeat(k, h // g, axis=1)      # query head j reads j // 8
        vv = jnp.repeat(v, h // g, axis=1)
        sc = jnp.einsum("qhd,nhd->hqn", q, kk) / math.sqrt(d)
        att = jax.nn.softmax(jnp.where(seen[None], sc, -jnp.inf), -1)
        o = jnp.einsum("hqn,nhd->qhd", att, vv).reshape(n, h * d)
        x = x + o @ _f32(lp["o_proj"]["weight"])
        x = x + reference_ffn(
            lp, _rms(x, lp["ffn_norm"]["scale"], eps), sizes)
    x = _rms(x, params["final_norm"]["scale"], eps)
    return x @ _f32(params["head"]["weight"]).T
