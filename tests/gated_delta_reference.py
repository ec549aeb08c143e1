"""The plain reference of ``models/gated_delta_moe_lm.py`` for tier-1: the
equations as ISSUE 57 writes them down (PERF.md section 4), float32
``jax.numpy``, the delta rule as a ``lax.scan`` over tokens; no chunks, no
cache, no kernel, no batching, no blocking, every routed expert computed
densely. ``benchmark/families/qwen3_next.py`` holds the same reference
computed in blocks for the chip's sizes; ``test_gated_delta_serving.py``
holds the two to each other.

It reads the program's parameter tree and its config's published keys, and
shares no code with it. Departures from the published ``qwen3_next``
module, all of LAYOUT: weights are ``(in, out)``; ``q_proj``'s columns are
every head's query, then every head's gate; the conv's weight is
``(channels, taps)``; a head's state is ``(dk, dv)``; the experts are three
arrays ``(E, F, D)`` of which ``cfg.num_experts`` from
``cfg.expert_offset`` are held (the others' terms are another chip's).
Call it under ``jax.default_matmul_precision("highest")``.
"""

import jax
import jax.numpy as jnp


def _f32(a):
    return a.astype(jnp.float32)


def _rms1(u, w, eps):
    """The zero-centred norm: scale ``1 + w``."""
    return u / jnp.sqrt(jnp.mean(u * u, -1, keepdims=True) + eps) \
        * (1.0 + _f32(w))


def _silu(u):
    return u / (1.0 + jnp.exp(-u))


def _sigmoid(u):
    return 1.0 / (1.0 + jnp.exp(-u))


def _l2(y):
    return y / jnp.sqrt(jnp.sum(y * y, -1, keepdims=True) + 1e-6)


def _rope(u, theta, rot):
    """Rotate-half pairing inside the first ``rot`` entries of each head,
    the rest as it is; ``u`` (N, heads, d), row ``t`` at position ``t``."""
    n = u.shape[0]
    freq = theta ** (-jnp.arange(rot // 2, dtype=jnp.float32) * 2.0 / rot)
    ang = (jnp.arange(n, dtype=jnp.float32)[:, None] * freq)[:, None, :]
    lo, hi, rest = u[..., :rot // 2], u[..., rot // 2:rot], u[..., rot:]
    return jnp.concatenate([lo * jnp.cos(ang) - hi * jnp.sin(ang),
                            hi * jnp.cos(ang) + lo * jnp.sin(ang), rest], -1)


def delta_layer(lp, h, cfg, decay=True, beta_one=False):
    """A gated delta-rule layer over a whole sequence from a zero state:
    ``h`` (N, D) the block's normed input -> (N, D). ``decay=False`` (g =
    0) and ``beta_one`` are controls."""
    n = h.shape[0]
    hk, hv = cfg.linear_num_key_heads, cfg.linear_num_value_heads
    dk, dv, r = cfg.linear_key_head_dim, cfg.linear_value_head_dim, hv // hk
    taps = cfg.linear_conv_kernel_dim
    qkvz = (h @ _f32(lp["in_proj_qkvz"]["weight"])).reshape(
        n, hk, 2 * dk + 2 * r * dv)
    ba = (h @ _f32(lp["in_proj_ba"]["weight"])).reshape(n, hk, 2 * r)
    q, k = qkvz[..., :dk], qkvz[..., dk:2 * dk]
    v = qkvz[..., 2 * dk:2 * dk + r * dv].reshape(n, hv, dv)
    z = qkvz[..., 2 * dk + r * dv:].reshape(n, hv, dv)
    b, a = ba[..., :r].reshape(n, hv), ba[..., r:].reshape(n, hv)
    mixed = jnp.concatenate([q.reshape(n, -1), k.reshape(n, -1),
                             v.reshape(n, -1)], -1)
    padded = jnp.concatenate([jnp.zeros((taps - 1, mixed.shape[1])), mixed])
    w = _f32(lp["conv1d"]["weight"])                        # (channels, taps)
    u = _silu(sum(w[:, j] * padded[j:j + n] for j in range(taps)))
    q = _l2(u[:, :hk * dk].reshape(n, hk, dk)) / jnp.sqrt(float(dk))
    k = _l2(u[:, hk * dk:2 * hk * dk].reshape(n, hk, dk))
    v = u[:, 2 * hk * dk:].reshape(n, hv, dv)
    q, k = jnp.repeat(q, r, axis=1), jnp.repeat(k, r, axis=1)   # i: i // r
    beta = jnp.ones_like(b) if beta_one else _sigmoid(b)
    g = -jnp.exp(lp["A_log"]) * jnp.log1p(jnp.exp(a + lp["dt_bias"]))
    if not decay:
        g = jnp.zeros_like(g)

    def token(state, t):
        g_t, beta_t, q_t, k_t, v_t = t
        state = jnp.exp(g_t)[:, None, None] * state             # (Hv,dk,dv)
        d = beta_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", state, k_t))
        state = state + k_t[:, :, None] * d[:, None, :]
        return state, jnp.einsum("hkv,hk->hv", state, q_t)

    _, o = jax.lax.scan(token, jnp.zeros((hv, dk, dv), jnp.float32),
                        (g, beta, q, k, v))
    o = _f32(lp["norm"]["weight"]) * o / jnp.sqrt(
        jnp.mean(o * o, -1, keepdims=True) + cfg.rms_norm_eps) * _silu(z)
    return o.reshape(n, hv * dv) @ _f32(lp["out_proj"]["weight"])


def attention_layer(ap, h, cfg, gate=True):
    """A gated full-attention layer: ``h`` (N, D) -> (N, D). ``gate=
    False`` (the output gate left out) is a control."""
    n = h.shape[0]
    hq, g, d = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    eps, rot = cfg.rms_norm_eps, int(d * cfg.partial_rotary_factor)
    qg = h @ _f32(ap["q_proj"]["weight"])
    q = qg[:, :hq * d].reshape(n, hq, d)
    out_gate = qg[:, hq * d:]
    q = _rope(_rms1(q, ap["q_norm"]["weight"], eps), cfg.rope_theta, rot)
    k = _rope(_rms1((h @ _f32(ap["k_proj"]["weight"])).reshape(n, g, d),
                    ap["k_norm"]["weight"], eps), cfg.rope_theta, rot)
    v = (h @ _f32(ap["v_proj"]["weight"])).reshape(n, g, d)
    k, v = jnp.repeat(k, hq // g, axis=1), jnp.repeat(v, hq // g, axis=1)
    s = jnp.einsum("qhd,nhd->hqn", q, k) / jnp.sqrt(float(d))
    t = jnp.arange(n)
    s = jnp.where((t[None, :] <= t[:, None])[None], s, -jnp.inf)
    o = jnp.einsum("hqn,nhd->qhd", jax.nn.softmax(s, -1), v).reshape(n, -1)
    if gate:
        o = o * _sigmoid(out_gate)
    return o @ _f32(ap["o_proj"]["weight"])


def _swiglu(t, g_w, u_w, d_w):
    return (_silu(t @ g_w) * (t @ u_w)) @ d_w


def moe_layer(mp, t, cfg, shared=True):
    """Router over all the routed experts, the held experts' terms, the
    gated shared expert: ``t`` (N, D) -> (N, D)."""
    n = t.shape[0]
    p = jax.nn.softmax(t @ _f32(mp["gate"]["weight"]), -1)
    order = jnp.argsort(-p, axis=-1, stable=True)[:, :cfg.num_experts_per_tok]
    picked = jnp.zeros_like(p, bool).at[jnp.arange(n)[:, None],
                                        order].set(True)
    top = jnp.where(picked, p, 0.0)
    if cfg.norm_topk_prob:
        top = top / top.sum(-1, keepdims=True)
    ex, off = mp["experts"], cfg.expert_offset
    y = jnp.zeros_like(t)
    for e in range(ex["gate"].shape[0]):
        y = y + top[:, off + e, None] * _swiglu(
            t, _f32(ex["gate"][e]).T, _f32(ex["up"][e]).T,
            _f32(ex["down"][e]))
    if shared:
        sp = mp["shared_expert"]
        y = y + _sigmoid(t @ _f32(mp["shared_expert_gate"]["weight"])) \
            * _swiglu(t, _f32(sp["gate_proj"]["weight"]),
                      _f32(sp["up_proj"]["weight"]),
                      _f32(sp["down_proj"]["weight"]))
    return y


def reference_logits(params, ids, cfg, shared=True, **controls):
    """(N,) ids -> (N, V) float32 logits. ``controls``: ``decay``,
    ``beta_one`` (the delta layers'), ``gate`` (the full layers')."""
    eps = cfg.rms_norm_eps
    delta_kw = {k: v for k, v in controls.items() if k != "gate"}
    x = _f32(params["embed_tokens"]["weight"][ids])
    for i in range(cfg.num_hidden_layers):
        lp = params["layers"][str(i)]
        h = _rms1(x, lp["input_layernorm"]["weight"], eps)
        if (i + 1) % cfg.full_attention_interval:
            x = x + delta_layer(lp["linear_attn"], h, cfg, **delta_kw)
        else:
            x = x + attention_layer(lp["self_attn"], h, cfg,
                                    controls.get("gate", True))
        x = x + moe_layer(lp["mlp"], _rms1(
            x, lp["post_attention_layernorm"]["weight"], eps), cfg, shared)
    x = _rms1(x, params["norm"]["weight"], eps)
    return x @ _f32(params["lm_head"]["weight"]).T
