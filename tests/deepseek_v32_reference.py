"""The plain reference of ``models/mla_moe_lm.py`` as DeepSeek-V3.2 sets it
(ISSUE 55) for tier-1: the equations as written down, float32
``jax.numpy``, the EXPANDED attention (every head's keys and values formed
from the latent; nothing absorbed), the selection by sorting, the experts a
dense weighted sum of which the held ones' terms are kept; no kernel, no
cache, no chunk, no batch, no block. ``benchmark/families/deepseek_v32.py``
holds the same reference computed in blocks for the chip's sizes;
``test_deepseek_v32_serving.py`` holds the two to each other.

It reads the program's parameter tree and shares no code with it. Call it
under ``jax.default_matmul_precision("highest")``. The keyword arguments
are CONTROLS (each leaves one stated mechanism out): a comparison that
passes one checks nothing.
"""

import math

import jax
import jax.numpy as jnp


def _f32(a):
    return a.astype(jnp.float32)


def _rms(u, g, eps):
    return _f32(g) * u / jnp.sqrt(jnp.mean(u * u, -1, keepdims=True) + eps)


def _layer_norm(u, p, eps):
    mu = u.mean(-1, keepdims=True)
    var = ((u - mu) ** 2).mean(-1, keepdims=True)
    return (u - mu) / jnp.sqrt(var + eps) * _f32(p["scale"]) + _f32(p["bias"])


def _silu(u):
    return u / (1.0 + jnp.exp(-u))


def _m(f, a):
    return 0.1 * a * math.log(f) + 1.0 if f > 1 else 1.0


def yarn_omega(d_r, rope):
    theta, f = float(rope["rope_theta"]), float(rope["factor"])
    l0 = rope["original_max_position_embeddings"]
    cd = lambda r: d_r * math.log(l0 / (2 * math.pi * r)) \
        / (2 * math.log(theta))                             # noqa: E731
    low = max(math.floor(cd(rope["beta_fast"])), 0)
    high = min(math.ceil(cd(rope["beta_slow"])), d_r - 1)
    omega = []
    for i in range(d_r // 2):
        phi = theta ** (-2.0 * i / d_r)
        ramp = min(max((i - low) / max(high - low, 1e-3), 0.0), 1.0)
        omega.append(phi * (1.0 - ramp) + phi / f * ramp)
    return jnp.asarray(omega, jnp.float32)


def _rope(u, omega, trig):
    """The ADJACENT pairs ``(u_2i, u_2i+1)`` of the last axis of ``u`` (N,
    ..., d) rotated by ``t * omega_i``, ``t`` the row."""
    n = u.shape[0]
    ang = (jnp.arange(n, dtype=jnp.float32)[:, None] * omega).reshape(
        (n,) + (1,) * (u.ndim - 2) + (-1,))
    cos, sin = trig * jnp.cos(ang), trig * jnp.sin(ang)
    even, odd = u[..., 0::2], u[..., 1::2]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                     -1).reshape(u.shape)


def _swiglu(t, p):
    return (_silu(t @ _f32(p["gate"]["weight"]))
            * (t @ _f32(p["up"]["weight"]))) @ _f32(p["down"]["weight"])


def selected(index, topk):
    """(N, N) index scores -> (N, N) bool: query ``t`` attends to every
    ``s <= t`` while ``t + 1 <= topk``, else to the ``topk`` of largest
    score among them, ties to the lower position."""
    n = index.shape[0]
    t = jnp.arange(n)
    seen = t[None, :] <= t[:, None]
    if n <= topk:
        return seen
    masked = jnp.where(seen, index, -jnp.inf)
    thr = jnp.sort(masked, axis=-1)[:, n - topk][:, None]
    above, ties = masked > thr, masked == thr
    need = topk - above.sum(-1, keepdims=True)
    chosen = above | (ties & (jnp.cumsum(ties, axis=-1) <= need))
    return seen & jnp.where((t + 1 <= topk)[:, None], True, chosen)


def routed(s, bias, sizes, group_limit=True):
    """Sigmoid scores ``s`` (N, E) -> (N, E) weights, 0 off the chosen
    ``num_experts_per_tok``: picked by ``s + bias`` inside the
    ``topk_group`` groups whose two best add up highest (ties to the lower
    index), weighed by ``s``."""
    n, e = s.shape
    groups, kept, k = (sizes["n_group"], sizes["topk_group"],
                       sizes["num_experts_per_tok"])
    pick = s + bias
    if group_limit and groups > 1:
        grouped = pick.reshape(n, groups, e // groups)
        g = jnp.sort(grouped, axis=-1)[..., -2:].sum(-1)        # (N, groups)
        best = jnp.argsort(-g, axis=-1, stable=True)[:, :kept]
        allowed = jnp.zeros((n, groups), bool).at[
            jnp.arange(n)[:, None], best].set(True)
        pick = jnp.where(allowed[:, :, None], grouped, -jnp.inf).reshape(n, e)
    order = jnp.argsort(-pick, axis=-1, stable=True)[:, :k]
    chosen = jnp.zeros((n, e), bool).at[jnp.arange(n)[:, None],
                                        order].set(True)
    top = jnp.where(chosen, s, 0.0)
    if sizes.get("norm_topk_prob", True):
        top = top / top.sum(-1, keepdims=True)
    return sizes.get("routed_scaling_factor", 1.0) * top


def reference_logits(params, ids, sizes, selection=True, group_limit=True,
                     index_rope=True, scale_m2=True, with_selected=False):
    """(N,) ids -> (N, V) float32 logits; ``with_selected``: also (L, N,
    N) bool, what each query attends to in each layer."""
    n = ids.shape[0]
    h = sizes["num_attention_heads"]
    dc, dn, dr, dv = (sizes["kv_lora_rank"], sizes["qk_nope_head_dim"],
                      sizes["qk_rope_head_dim"], sizes["v_head_dim"])
    j, di, topk = (sizes["index_n_heads"], sizes["index_head_dim"],
                   sizes["index_topk"])
    eps, rope = sizes["rms_norm_eps"], sizes["rope_parameters"]
    f = float(rope["factor"])
    omega = yarn_omega(dr, rope)
    trig = _m(f, rope["mscale"]) / _m(f, rope["mscale_all_dim"])
    sigma = (dn + dr) ** -0.5 * (
        _m(f, rope["mscale_all_dim"]) ** 2 if scale_m2 else 1.0)
    off = sizes.get("expert_offset", 0)
    causal = jnp.arange(n)[None, :] <= jnp.arange(n)[:, None]

    def partly_rotated(u):
        if not index_rope:
            return u
        return jnp.concatenate([_rope(u[..., :dr], omega, trig),
                                u[..., dr:]], -1)

    x = _f32(params["embed"]["weight"][ids])
    kept = []
    for i in range(sizes["num_hidden_layers"]):
        lp = params["layers"][str(i)]
        w = lambda name: _f32(lp[name]["weight"])            # noqa: E731
        a = _rms(x, lp["attn_norm"]["scale"], eps)
        c_q = _rms(a @ w("q_a_proj"), lp["q_a_norm"]["scale"], eps)
        q = (c_q @ w("q_b_proj")).reshape(n, h, dn + dr)
        q = jnp.concatenate(
            [q[..., :dn], _rope(q[..., dn:], omega, trig)], -1)
        kv = a @ w("kv_a_proj")
        c = _rms(kv[:, :dc], lp["kv_a_norm"]["scale"], eps)
        k_rope = _rope(kv[:, dc:], omega, trig)             # one a token
        up = (c @ w("kv_b_proj")).reshape(n, h, dn + dv)
        k = jnp.concatenate(
            [up[..., :dn], jnp.broadcast_to(k_rope[:, None], (n, h, dr))], -1)
        v = up[..., dn:]

        q_i = partly_rotated((c_q @ w("idx_q")).reshape(n, j, di))
        k_i = partly_rotated(_layer_norm(a @ w("idx_k"), lp["idx_k_norm"],
                                         eps))
        w_i = j ** -0.5 * (a @ w("idx_w"))
        index = di ** -0.5 * jnp.einsum(
            "tj,tjs->ts", w_i,
            jnp.maximum(jnp.einsum("tjd,sd->tjs", q_i, k_i), 0.0))
        keep = selected(index, topk) if selection else causal
        kept.append(keep)

        score = sigma * jnp.einsum("thd,shd->hts", q, k)
        p = jax.nn.softmax(jnp.where(keep[None], score, -jnp.inf), -1)
        x = x + jnp.einsum("hts,shv->thv", p, v).reshape(n, -1) @ w("o_proj")

        t = _rms(x, lp["ffn_norm"]["scale"], eps)
        if i < sizes.get("first_k_dense_replace", 0):
            x = x + _swiglu(t, lp["mlp"])
            continue
        coef = routed(1.0 / (1.0 + jnp.exp(-(t @ w("router")))),
                      _f32(lp["router_bias"]), sizes, group_limit)
        y = _swiglu(t, lp["shared"])
        ex = lp["experts"]
        for e in range(ex["gate"].shape[0]):                # the held ones
            hidden = _silu(t @ _f32(ex["gate"][e]).T) \
                * (t @ _f32(ex["up"][e]).T)
            y = y + coef[:, off + e, None] * (hidden @ _f32(ex["down"][e]))
        x = x + y
    x = _rms(x, params["final_norm"]["scale"], eps)
    logits = x @ _f32(params["head"]["weight"]).T
    return (logits, jnp.stack(kept)) if with_selected else logits
