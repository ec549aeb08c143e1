"""The plain reference of the MiMo-V2-Flash block that
``models/window_moe_lm.py`` runs under its options (ISSUE 49's equations):
float32 ``jax.numpy``, no cache, no kernels, no chunks, no batching; the
window written as a mask over dense causal scores, the sink as one more
column of the scores that sums no value, the experts as a dense weighted
sum over every routed expert. It reads the program's parameter tree and
shares no code with it. Call it under
``jax.default_matmul_precision("highest")``.

``sizes`` holds the published keys (``hybrid_layer_pattern``: 1 a window
layer; ``moe_layer_freq``: 1 a routed MLP; ``swa_num_key_value_heads``,
``v_head_dim``, ``partial_rotary_factor``, ``swa_rope_theta``,
``attention_value_scale``, ``add_swa_attention_sink_bias`` ...). The
tree's experts are those of ``sizes["expert_offset"]`` (0 where absent)
on, as many as it holds: all of the router's (the uncut layer), or a
chip's share, whose part of the sum this then computes. What the config
does not settle is listed in ISSUE 49 (pre-norm residuals, no QK norm, the
value scale on V before it is cached, the sink in the denominator alone,
the window counted with the token itself, 0 in the pattern a full layer).

Each of ``leave_out`` drops one piece, for the controls that a comparison
must fail: ``"sink"``, ``"value_scale"``, ``"partial_rotary"`` (the whole
head is rotated), ``"window"`` (every layer full).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def sizes_of(cfg, **over):
    """The published keys the reference reads, from a program config."""
    sizes = dict(
        hidden_size=cfg.hidden_size, num_hidden_layers=cfg.num_hidden_layers,
        num_attention_heads=cfg.num_attention_heads,
        num_key_value_heads=cfg.num_key_value_heads,
        swa_num_key_value_heads=cfg.swa_num_key_value_heads
        or cfg.num_key_value_heads,
        head_dim=cfg.head_dim, v_head_dim=cfg.v_head_dim or cfg.head_dim,
        layernorm_epsilon=cfg.rms_norm_eps, rope_theta=cfg.rope_theta,
        swa_rope_theta=cfg.swa_rope_theta,
        partial_rotary_factor=cfg.partial_rotary_factor,
        sliding_window=cfg.sliding_window,
        attention_value_scale=cfg.attention_value_scale,
        add_swa_attention_sink_bias=cfg.add_swa_attention_sink_bias,
        add_full_attention_sink_bias=cfg.add_full_attention_sink_bias,
        hybrid_layer_pattern=[int(t == "sliding_attention")
                              for t in cfg.layer_types],
        moe_layer_freq=[int(t == "sparse") for t in cfg.mlp_layer_types],
        num_experts_per_tok=cfg.num_experts_per_tok,
        norm_topk_prob=cfg.norm_topk_prob,
        routed_scaling_factor=cfg.routed_scaling_factor,
        expert_offset=cfg.expert_offset)
    sizes.update(over)
    return sizes


def _f32(a):
    return a.astype(jnp.float32)


def _rms(u, g, eps):
    return _f32(g) * u / jnp.sqrt(jnp.mean(u * u, -1, keepdims=True) + eps)


def _silu(u):
    return u / (1.0 + jnp.exp(-u))


def _rope(u, pos, theta, r):
    """Entries ``[0, r)`` of each head rotated, pairing ``(n, n + r/2)``;
    entries ``[r, d)`` as they are. ``u`` (N, heads, d), ``pos`` (N,)."""
    half = r // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = (pos.astype(jnp.float32)[:, None] * freq)[:, None, :]
    lo, hi, rest = u[..., :half], u[..., half:r], u[..., r:]
    return jnp.concatenate([lo * jnp.cos(ang) - hi * jnp.sin(ang),
                            hi * jnp.cos(ang) + lo * jnp.sin(ang), rest], -1)


def _swiglu(t, p):
    return (_silu(t @ _f32(p["gate"]["weight"]))
            * (t @ _f32(p["up"]["weight"]))) @ _f32(p["down"]["weight"])


def reference_ffn(lp, t, sizes: dict, routed: bool = True):
    """What layer ``lp``'s MLP adds for the normed rows ``t`` (N, D): the
    dense SwiGLU, or the weighted sum over the routed experts the tree
    holds (there is no shared expert)."""
    if not routed:
        return _swiglu(t, lp["mlp"])
    n = t.shape[0]
    top_k, off = sizes["num_experts_per_tok"], sizes.get("expert_offset", 0)
    s = 1.0 / (1.0 + jnp.exp(-(t @ _f32(lp["router"]["weight"]))))
    sel = s + _f32(lp["router"]["selection_bias"])
    # the top_k largest, ties to the lower index
    order = jnp.argsort(-sel, axis=-1, stable=True)[:, :top_k]
    picked = jnp.zeros_like(s, bool).at[
        jnp.arange(n)[:, None], order].set(True)
    top = jnp.where(picked, s, 0.0)
    if sizes.get("norm_topk_prob", True):
        top = top / top.sum(-1, keepdims=True)
    coef = float(sizes.get("routed_scaling_factor") or 1.0) * top
    ex = lp["experts"]
    y = jnp.zeros_like(t)
    for e in range(ex["gate"].shape[0]):
        hidden = _silu(t @ _f32(ex["gate"][e]).T) * (t @ _f32(ex["up"][e]).T)
        y = y + coef[:, off + e, None] * (hidden @ _f32(ex["down"][e]))
    return y


def reference_logits(params, ids, sizes: dict, leave_out=()):
    """(N,) ids -> (N, V) float32 logits."""
    n = ids.shape[0]
    h, dk, dv = (sizes["num_attention_heads"], sizes["head_dim"],
                 sizes["v_head_dim"])
    eps = sizes["layernorm_epsilon"]
    r = dk if "partial_rotary" in leave_out \
        else int(dk * sizes["partial_rotary_factor"])
    scale = 1.0 if "value_scale" in leave_out \
        else sizes["attention_value_scale"]
    pos = jnp.arange(n)
    t_q, t_k = pos[:, None], pos[None, :]
    x = _f32(params["embed"]["weight"][ids])
    for i in range(sizes["num_hidden_layers"]):
        lp = params["layers"][str(i)]
        w = lambda name: _f32(lp[name]["weight"])            # noqa: E731
        windowed = bool(sizes["hybrid_layer_pattern"][i])
        g = sizes["swa_num_key_value_heads" if windowed
                  else "num_key_value_heads"]
        theta = float(sizes["swa_rope_theta" if windowed else "rope_theta"])
        a = _rms(x, lp["attn_norm"]["scale"], eps)
        q = _rope((a @ w("q_proj")).reshape(n, h, dk), pos, theta, r)
        k = _rope((a @ w("k_proj")).reshape(n, g, dk), pos, theta, r)
        v = scale * (a @ w("v_proj")).reshape(n, g, dv)
        k = jnp.repeat(k, h // g, axis=1)        # head i reads i // (h / g)
        v = jnp.repeat(v, h // g, axis=1)
        seen = t_k <= t_q
        if windowed and "window" not in leave_out:
            seen = seen & (t_k > t_q - sizes["sliding_window"])
        sc = jnp.where(seen[None], jnp.einsum("qhd,khd->hqk", q, k)
                       / math.sqrt(dk), -jnp.inf)
        sink = sizes["add_swa_attention_sink_bias" if windowed
                     else "add_full_attention_sink_bias"]
        if sink and "sink" not in leave_out:
            column = jnp.broadcast_to(_f32(lp["sinks"])[:, None, None],
                                      (h, n, 1))
            att = jax.nn.softmax(jnp.concatenate([sc, column], -1), -1)[
                ..., :n]
        else:
            att = jax.nn.softmax(sc, -1)
        x = x + jnp.einsum("hqk,khd->qhd", att, v).reshape(n, h * dv) \
            @ w("o_proj")

        x = x + reference_ffn(lp, _rms(x, lp["ffn_norm"]["scale"], eps),
                              sizes, bool(sizes["moe_layer_freq"][i]))
    x = _rms(x, params["final_norm"]["scale"], eps)
    return x @ _f32(params["head"]["weight"]).T
