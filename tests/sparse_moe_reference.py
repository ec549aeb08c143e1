"""The plain reference of ``models/sparse_moe_lm.py`` for tier-1: the
equations as written down (PERF.md section 4), float32 ``jax.numpy``, no
kernel, no cache, no batching, no blocking. ``benchmark/families/
keye_vl2.py`` holds the same reference computed in blocks for the chip's
sizes; ``test_sparse_moe_serving.py`` holds the two to each other.

It reads the program's parameter tree and shares no code with it. Call it
under ``jax.default_matmul_precision("highest")``.
"""

import jax
import jax.numpy as jnp


def _f32(a):
    return a.astype(jnp.float32)


def _rms(u, g, eps):
    return g * u / jnp.sqrt(jnp.mean(u * u, -1, keepdims=True) + eps)


def _layer_norm(u, p, eps=1e-6):
    mu = u.mean(-1, keepdims=True)
    var = ((u - mu) ** 2).mean(-1, keepdims=True)
    return (u - mu) / jnp.sqrt(var + eps) * _f32(p["scale"]) + _f32(p["bias"])


def _rope(u, theta):
    """Rotate-half pairing ``(i, i + d/2)``; ``u`` (N, ..., d), row ``t``
    at position ``t``."""
    n, d = u.shape[0], u.shape[-1]
    freq = theta ** (-jnp.arange(d // 2, dtype=jnp.float32) * 2.0 / d)
    ang = jnp.arange(n, dtype=jnp.float32)[:, None] * freq[None, :]
    ang = ang.reshape((n,) + (1,) * (u.ndim - 2) + (d // 2,))
    lo, hi = u[..., :d // 2], u[..., d // 2:]
    return jnp.concatenate([lo * jnp.cos(ang) - hi * jnp.sin(ang),
                            hi * jnp.cos(ang) + lo * jnp.sin(ang)], -1)


def index_scores(lp, a, cfg):
    """(N, N) index scores ``I[t, s]`` of a layer from its normed input."""
    n = a.shape[0]
    j, di = cfg.indexer_num_heads, cfg.indexer_head_dim
    q_i = _rope((a @ _f32(lp["idx_q"]["weight"])).reshape(n, j, di),
                cfg.rope_theta)
    k_i = _rope(_layer_norm(a @ _f32(lp["idx_k"]["weight"]),
                            lp["idx_k_norm"]), cfg.rope_theta)
    w_i = a @ _f32(lp["idx_w"]["weight"])
    dots = jnp.maximum(jnp.einsum("tjd,sd->tjs", q_i, k_i), 0.0)
    return (j * di) ** -0.5 * jnp.einsum("tj,tjs->ts", w_i, dots)


def selection(scores, topk):
    """(N, N) bool ``S_t``: all ``s <= t`` while ``t + 1 <= topk``, else
    the ``topk`` positions ``s <= t`` of largest score (ties: lower s)."""
    n = scores.shape[0]
    t = jnp.arange(n)
    seen = t[None, :] <= t[:, None]
    if n <= topk:
        return seen
    masked = jnp.where(seen, scores, -jnp.inf)
    order = jnp.argsort(-masked, axis=-1, stable=True)      # best first
    rank = jnp.argsort(order, axis=-1)                      # rank of each s
    return seen & ((t[:, None] + 1 <= topk) | (rank < topk))


def reference_logits(params, ids, cfg, return_scores=False):
    """(N,) ids -> (N, V) float32 logits (and each layer's index scores
    and selection where asked)."""
    n = ids.shape[0]
    h, kv, dh = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    eps = cfg.rms_norm_eps
    x = _f32(params["embed"]["weight"])[ids]
    seen = []
    for i in range(cfg.num_hidden_layers):
        lp = params["layers"][str(i)]
        a = _rms(x, _f32(lp["attn_norm"]["scale"]), eps)
        q = _rope(_rms((a @ _f32(lp["q_proj"]["weight"])).reshape(n, h, dh),
                       _f32(lp["q_norm"]["scale"]), eps), cfg.rope_theta)
        k = _rope(_rms((a @ _f32(lp["k_proj"]["weight"])).reshape(n, kv, dh),
                       _f32(lp["k_norm"]["scale"]), eps), cfg.rope_theta)
        v = (a @ _f32(lp["v_proj"]["weight"])).reshape(n, kv, dh)
        scores = index_scores(lp, a, cfg)
        keep = selection(scores, cfg.indexer_topk)
        seen.append((scores, keep))
        heads = []
        for hh in range(h):
            g = hh // (h // kv)
            s = (q[:, hh] @ k[:, g].T) / jnp.sqrt(float(dh))
            p = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1)
            heads.append(p @ v[:, g])
        x = x + jnp.concatenate(heads, -1) @ _f32(lp["o_proj"]["weight"])

        b = _rms(x, _f32(lp["ffn_norm"]["scale"]), eps)
        r = jax.nn.softmax(b @ _f32(lp["router"]["weight"]), axis=-1)
        top = jnp.argsort(-r, axis=-1)[:, :cfg.num_experts_per_tok]
        y = jnp.zeros_like(x)
        ex = lp["experts"]
        for t in range(n):                  # a plain loop: token by token
            r_t = r[t, top[t]]
            c_t = r_t / r_t.sum() if cfg.norm_topk_prob else r_t
            for c_e, e in zip(c_t, top[t]):
                g_ = _f32(ex["gate"][e]) @ b[t]
                hidden = g_ * jax.nn.sigmoid(g_) * (_f32(ex["up"][e]) @ b[t])
                y = y.at[t].add(c_e * (hidden @ _f32(ex["down"][e])))
        x = x + y
    x = _rms(x, _f32(params["final_norm"]["scale"]), eps)
    logits = x @ _f32(params["head"]["weight"]).T
    return (logits, seen) if return_scores else logits
