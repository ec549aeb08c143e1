"""Plain reference for ``models/mla_moe_lm.py``: the whole forward pass in
the EXPANDED form (every head's keys and values formed from the latent),
float32 at ``HIGHEST``, no cache, no kernel, no absorption, the experts a
dense weighted sum over every routed expert of which the held ones' terms
are kept. It reads the program's parameter tree and a dict of the
published keys, and imports nothing from the model.

``query_scale=False`` (``a_t`` left at 1), ``rotate_key=False`` (the
shared rotary key cached unrotated) and ``row_dtype`` (the cached row
``[c | k_rope]`` rounded to that type) are CONTROLS: a comparison that
passes them checks nothing.
"""

import math

import jax
import jax.numpy as jnp

_HI = jax.lax.Precision.HIGHEST


def _f32(a):
    return a.astype(jnp.float32)


def _mm(a, b):
    return jnp.matmul(a, _f32(b), precision=_HI)


def _rms(u, g, eps):
    return _f32(g) * u / jnp.sqrt(jnp.mean(u * u, -1, keepdims=True) + eps)


def _silu(u):
    return u / (1.0 + jnp.exp(-u))


def _m(f, a):
    return 0.1 * a * math.log(f) + 1.0 if f > 1 else 1.0


def yarn_omega(d_r, rp):
    theta, f, l0 = rp["rope_theta"], rp["factor"], \
        rp["original_max_position_embeddings"]
    cd = lambda r: d_r * math.log(l0 / (2 * math.pi * r)) \
        / (2 * math.log(theta))                             # noqa: E731
    low = max(math.floor(cd(rp["beta_fast"])), 0)
    high = min(math.ceil(cd(rp["beta_slow"])), d_r - 1)
    omega = []
    for i in range(d_r // 2):
        phi = theta ** (-2.0 * i / d_r)
        ramp = min(max((i - low) / max(high - low, 1e-3), 0.0), 1.0)
        omega.append(phi * (1 - ramp) + phi / f * ramp)
    return jnp.asarray(omega, jnp.float32)


def _rope(u, pos, omega, trig_scale):
    """Adjacent pairs rotated; ``u`` (N, ..., d_r), ``pos`` (N,)."""
    ang = _f32(pos)[:, None] * omega
    ang = ang.reshape((u.shape[0],) + (1,) * (u.ndim - 2) + (-1,))
    cos, sin = jnp.cos(ang) * trig_scale, jnp.sin(ang) * trig_scale
    even, odd = u[..., 0::2], u[..., 1::2]
    out = jnp.zeros_like(u)
    out = out.at[..., 0::2].set(even * cos - odd * sin)
    return out.at[..., 1::2].set(odd * cos + even * sin)


def _swiglu(t, p):
    return _mm(_silu(_mm(t, p["gate"]["weight"]))
               * _mm(t, p["up"]["weight"]), p["down"]["weight"])


def reference_logits(params, ids, sizes, *, query_scale=True,
                     rotate_key=True, row_dtype=None):
    """(N,) ids -> (N, V) float32 logits."""
    n = ids.shape[0]
    h = sizes["num_attention_heads"]
    dc, dn, dr, dv = (sizes["kv_lora_rank"], sizes["qk_nope_head_dim"],
                      sizes["qk_rope_head_dim"], sizes["v_head_dim"])
    eps, rp = sizes["rms_norm_eps"], sizes["rope_parameters"]
    omega = yarn_omega(dr, rp)
    trig = _m(rp["factor"], rp["mscale"]) \
        / _m(rp["factor"], rp["mscale_all_dim"])
    sigma = (dn + dr) ** -0.5 * _m(rp["factor"], rp["mscale_all_dim"]) ** 2
    pos = jnp.arange(n)
    a_t = 1.0 + rp["llama_4_scaling_beta"] * jnp.log(
        1.0 + jnp.floor(pos / rp["original_max_position_embeddings"]))
    if not query_scale:
        a_t = jnp.ones_like(a_t)
    causal = pos[None, :] <= pos[:, None]
    top_k, off = sizes["num_experts_per_tok"], sizes.get("expert_offset", 0)

    x = _f32(params["embed"]["weight"][ids])
    for i in range(sizes["num_hidden_layers"]):
        lp = params["layers"][str(i)]
        hid = _rms(x, lp["attn_norm"]["scale"], eps)
        c_q = _rms(_mm(hid, lp["q_a_proj"]["weight"]),
                   lp["q_a_norm"]["scale"], eps)
        q = _mm(c_q, lp["q_b_proj"]["weight"]).reshape(n, h, dn + dr)
        q_nope, q_rope = q[..., :dn], _rope(q[..., dn:], pos, omega, trig)
        kv = _mm(hid, lp["kv_a_proj"]["weight"])
        c = _rms(kv[:, :dc], lp["kv_a_norm"]["scale"], eps)
        k_rope = _rope(kv[:, dc:], pos, omega, trig) if rotate_key \
            else kv[:, dc:]
        if row_dtype is not None:                   # control: a lossy cache
            c, k_rope = (_f32(u.astype(row_dtype)) for u in (c, k_rope))
        kvb = _mm(c, lp["kv_b_proj"]["weight"]).reshape(n, h, dn + dv)
        k_nope, v = kvb[..., :dn], kvb[..., dn:]
        score = (jnp.einsum("thd,shd->hts", q_nope, k_nope, precision=_HI)
                 + jnp.einsum("thd,sd->hts", q_rope, k_rope, precision=_HI))
        score = score * (a_t * sigma)[None, :, None]
        p = jax.nn.softmax(jnp.where(causal[None], score, -jnp.inf), -1)
        o = jnp.einsum("hts,shd->thd", p, v, precision=_HI)
        x = x + _mm(o.reshape(n, h * dv), lp["o_proj"]["weight"])

        t = _rms(x, lp["ffn_norm"]["scale"], eps)
        s = jax.nn.softmax(_mm(t, lp["router"]["weight"]), -1)
        # the top_k largest, ties to the lower index
        order = jnp.argsort(-s, axis=-1, stable=True)[:, :top_k]
        picked = jnp.zeros_like(s, bool).at[
            jnp.arange(n)[:, None], order].set(True)
        top = jnp.where(picked, s, 0.0)
        if sizes.get("norm_topk_prob", True):
            top = top / top.sum(-1, keepdims=True)
        coef = sizes.get("routed_scaling_factor", 1.0) * top
        ex = lp["experts"]
        y = _swiglu(t, lp["shared"])
        for e in range(ex["gate"].shape[0]):        # the experts held here
            hidden = _silu(_mm(t, ex["gate"][e].T)) * _mm(t, ex["up"][e].T)
            y = y + coef[:, off + e, None] * _mm(hidden, ex["down"][e])
        x = x + y
    x = _rms(x, params["final_norm"]["scale"], eps)
    return _mm(x, params["head"]["weight"].T)
