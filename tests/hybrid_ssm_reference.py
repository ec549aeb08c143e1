"""The plain reference of ``models/hybrid_ssm_lm.py`` for tier-1: the
equations as written down (PERF.md section 4), float32 ``jax.numpy``, the
recurrence as a ``lax.scan`` over tokens; no chunks, no cache, no kernel,
no batching, no blocking. ``benchmark/families/falcon_h1.py`` holds the
same reference computed in blocks for the chip's sizes;
``test_hybrid_ssm_serving.py`` holds the two to each other.

It reads the program's parameter tree and its config's published keys, and
shares no code with it. Call it under
``jax.default_matmul_precision("highest")``.
"""

import jax
import jax.numpy as jnp


def _f32(a):
    return a.astype(jnp.float32)


def _rms(u, g, eps):
    return g * u / jnp.sqrt(jnp.mean(u * u, -1, keepdims=True) + eps)


def _silu(u):
    return u / (1.0 + jnp.exp(-u))


def _rope(u, theta):
    """Rotate-half pairing ``(i, i + d/2)``; ``u`` (N, heads, d), row
    ``t`` at position ``t``."""
    n, d = u.shape[0], u.shape[-1]
    freq = theta ** (-jnp.arange(d // 2, dtype=jnp.float32) * 2.0 / d)
    ang = (jnp.arange(n, dtype=jnp.float32)[:, None] * freq[None, :]
           )[:, None, :]
    lo, hi = u[..., :d // 2], u[..., d // 2:]
    return jnp.concatenate([lo * jnp.cos(ang) - hi * jnp.sin(ang),
                            hi * jnp.cos(ang) + lo * jnp.sin(ang)], -1)


def mixer(lp, u, cfg):
    """The state-space half of a block over a whole sequence from a zero
    state: ``u`` (N, D) the block's normed input -> (N, D)."""
    n = u.shape[0]
    hm, p, g, ns = (cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_n_groups,
                    cfg.mamba_d_state)
    d_ssm, taps = cfg.mamba_d_ssm, cfg.mamba_d_conv
    m = cfg.ssm_multipliers
    proj = (u * cfg.ssm_in_multiplier) @ _f32(lp["in_proj"]["weight"])
    z = proj[:, :d_ssm] * m[0]
    x_in = proj[:, d_ssm:2 * d_ssm] * m[1]
    b_in = proj[:, 2 * d_ssm:2 * d_ssm + g * ns] * m[2]
    c_in = proj[:, 2 * d_ssm + g * ns:2 * d_ssm + 2 * g * ns] * m[3]
    dt = proj[:, 2 * d_ssm + 2 * g * ns:] * m[4]
    xbc = jnp.concatenate([x_in, b_in, c_in], -1)
    padded = jnp.concatenate([jnp.zeros((taps - 1, xbc.shape[1])), xbc])
    w = _f32(lp["conv"]["weight"])                          # (channels, taps)
    conv = _f32(lp["conv"]["bias"]) + sum(
        w[:, j] * padded[j:j + n] for j in range(taps))
    conv = _silu(conv)
    x = conv[:, :d_ssm].reshape(n, hm, p)
    bm = conv[:, d_ssm:d_ssm + g * ns].reshape(n, g, ns)
    cm = conv[:, d_ssm + g * ns:].reshape(n, g, ns)
    bm, cm = (jnp.repeat(t, hm // g, axis=1) for t in (bm, cm))   # (N,H,Ns)
    dt = jnp.log1p(jnp.exp(dt + lp["dt_bias"]))            # softplus
    decay = jnp.exp(-dt * jnp.exp(lp["A_log"]))             # (N, H)

    def token(state, t):
        a_t, dt_t, x_t, b_t, c_t = t
        state = a_t[:, None, None] * state + (
            dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]    # (H,P,Ns)
        return state, jnp.einsum("hpn,hn->hp", state, c_t)

    _, y = jax.lax.scan(token, jnp.zeros((hm, p, ns)),
                        (decay, dt, x, bm, cm))
    y = y + lp["D"][None, :, None] * x
    y = y.reshape(n, d_ssm) * _silu(z)
    y = _rms(y.reshape(n, g, -1), _f32(lp["mixer_norm"]["scale"]).reshape(
        g, -1), cfg.rms_norm_eps).reshape(n, d_ssm)
    return (y @ _f32(lp["out_proj"]["weight"])) * cfg.ssm_out_multiplier


def reference_logits(params, ids, cfg):
    """(N,) ids -> (N, V) float32 logits of every position."""
    n = ids.shape[0]
    h, kv, dh = (cfg.num_attention_heads, cfg.num_key_value_heads,
                 cfg.head_dim)
    eps, theta = cfg.rms_norm_eps, float(cfg.rope_theta)
    causal = jnp.tril(jnp.ones((n, n), bool))
    x = _f32(params["embed"]["weight"][ids]) * cfg.embedding_multiplier
    for i in range(cfg.num_hidden_layers):
        lp = params["layers"][str(i)]
        w = lambda name: _f32(lp[name]["weight"])            # noqa: E731
        u = _rms(x, _f32(lp["input_norm"]["scale"]), eps)
        a = u * cfg.attention_in_multiplier
        q = _rope((a @ w("q_proj")).reshape(n, h, dh), theta)
        k = _rope(((a @ w("k_proj")) * cfg.key_multiplier).reshape(
            n, kv, dh), theta)
        v = (a @ w("v_proj")).reshape(n, kv, dh)
        kk = jnp.repeat(k, h // kv, axis=1)     # query head j reads j // g
        vv = jnp.repeat(v, h // kv, axis=1)
        s = jnp.einsum("qhd,nhd->hqn", q, kk) / jnp.sqrt(float(dh))
        s = jnp.where(causal[None], s, -jnp.inf)
        o = jnp.einsum("hqn,nhd->qhd", jax.nn.softmax(s, -1), vv)
        att = (o.reshape(n, h * dh) @ w("o_proj")) \
            * cfg.attention_out_multiplier
        x = x + att + mixer(lp, u, cfg)
        b = _rms(x, _f32(lp["ff_norm"]["scale"]), eps)
        hidden = (b @ w("up_proj")) * _silu(
            (b @ w("gate_proj")) * cfg.mlp_multipliers[0])
        x = x + (hidden @ w("down_proj")) * cfg.mlp_multipliers[1]
    x = _rms(x, _f32(params["final_norm"]["scale"]), eps)
    return (x @ _f32(params["head"]["weight"]).T) * cfg.lm_head_multiplier
