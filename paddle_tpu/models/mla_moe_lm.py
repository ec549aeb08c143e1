"""Decoder-only language model with multi-head latent attention over a
softmax-routed expert layer with a shared expert, of which a chip may hold
a share, for the paged serving engine.

Layer ``l``, float32 stream ``x`` (``D`` wide; ``H`` heads; ``d_n`` =
``qk_nope_head_dim``, ``d_r`` = ``qk_rope_head_dim``, ``d_v`` =
``v_head_dim``, ``d_c`` = ``kv_lora_rank``), positions ``p``::

    h    = rms(x; g1)
    c_q  = rms(h W_qa; g_q)                       q_lora_rank wide
    [q_nope_i (d_n) | q_rope_i (d_r)] = (c_q W_qb)_i        head i
    [c' (d_c) | k_r (d_r)] = h W_kva
    c    = rms(c'; g_kv) ;  k_rope = rope(k_r, p)  ONE rotary key a token
    [k_nope_i (d_n) | v_i (d_v)] = c W_kvb,i ,  W_kvb,i = [W_UK,i | W_UV,i]
    score_i(t, s) = a_t sigma (q_nope_i,t . k_nope_i,s
                               + rope(q_rope_i,t, p_t) . k_rope_s)   s <= t
    x'   = x + concat_i(softmax(score_i) v_i) W_o
    h2   = rms(x'; g2)
    s    = softmax(h2 W_r)                        float32, all routed experts
    S    = the K largest of s                     ties to the lower index
    w_e  = scale * s_e / sum_{j in S} s_j
    x''  = x' + sum_{e in S, e held here} w_e FFN_e(h2) + FFN_shared(h2)

``rope`` rotates the ADJACENT pairs ``(u_2j, u_2j+1)`` by ``p omega_j``
(``rope_interleave``) with YaRN's frequencies: ``phi_j = theta^(-2j/d_r)``,
``omega_j = phi_j (1 - ramp_j) + (phi_j / f) ramp_j``, ``ramp`` rising from
0 to 1 between the pairs that turn ``beta_fast`` and ``beta_slow`` times in
``L0`` = ``original_max_position_embeddings`` positions; cos and sin times
``m(f, mscale) / m(f, mscale_all_dim)``, ``m(f, a) = 0.1 a ln f + 1``.
``sigma = (d_n + d_r)^(-1/2) m(f, mscale_all_dim)^2`` and ``a_t = 1 +
llama_4_scaling_beta ln(1 + floor(p_t / L0))``, the position-dependent
query scale.

**What is cached, and the form that runs.** A token's row a layer is ``r
= [c | k_rope]``, ``d_c + d_r`` values, shared by every head; K and V
heads are never formed. ``W_UK`` is folded into the query and ``W_UV``
into the output (the absorbed form, the same mathematics)::

    qt_i,t = a_t sigma [ q_nope_i,t W_UK,i^T (d_c) | rope(q_rope_i,t) (d_r) ]
    score  = qt_i,t . r_s ;  u_i,t = sum_s P_ts c_s ;  o_i,t = u_i,t W_UV,i

``attn_in`` returns ``qt`` and the row's two parts, the engine's latent
kernels return ``u``, ``attn_out`` applies ``W_UV`` and ``W_o``.

**A token selection over the latent cache** (``index_topk``: DeepSeek-V3.2's
lightning indexer). Each layer also caches an index key a token and every
query attends to the ``index_topk`` cached tokens it scores best::

    qI[t, j] = [rope(u_j[:d_r], p_t) | u_j[d_r:]],  u = c_q,t W_qI   j < J
    kI[s]    = [rope(z[:d_r], p_s) | z[d_r:]],      z = layernorm(h_s W_kI)
    w[t]     = J^(-1/2) h_t W_w                     J head weights, float32
    I[t, s]  = Di^(-1/2) sum_j w[t, j] relu(qI[t, j] . kI[s])      s <= t
    S_t      = every s <= t while t + 1 <= index_topk, else the
               index_topk largest I[t, s], ties to the lower position

and the softmax above runs over ``s in S_t``. The indexer's rotary part is
its first ``d_r`` lanes, the same adjacent pairs and YaRN frequencies as
the rotary key's. The row a token caches is then ``[c | k_rope]`` plus
``kI``; ``attn_in`` hands ``(qI, w)`` back as ``index``.

**Leading dense layers and the group-limited sigmoid router**
(``first_k_dense_replace``, ``scoring_func="sigmoid"``, ``n_group``,
``topk_group``: DeepSeek-V3's ``noaux_tc``). The first
``first_k_dense_replace`` layers' FFN is one SwiGLU of
``intermediate_size``. A sparse layer under the sigmoid rule::

    s    = sigmoid(h2 W_r)                        float32, all routed experts
    g_k  = sum of the 2 largest of (s + b) in group k       n_group groups
    G    = the topk_group groups of largest g_k   ties to the lower index
    S    = the K largest of (s + b) over the experts of G
    w_e  = scale * s_e / sum_{j in S} s_j

``b`` the selection bias (``router_bias``, float32): it picks, it does not
weigh.

The router is as wide as the model's routed experts and picks
``num_experts_per_tok`` of them; the layer holds ``n_routed_experts`` of
them from ``expert_offset`` on and computes their part of the sum (the
shared expert is whole on every chip). The vocabulary is the rows held
here. Untied head. The residual stream, the norms and the router are
float32 whatever the weights' type; projections and experts take operands
of the weights' type. The config's key names are those of the published
``config.json`` of this family (DeepSeek-V3's keys: ``kv_lora_rank``,
``qk_rope_head_dim``, ``n_routed_experts`` ...).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional

import jax
import jax.numpy as jnp

from paddle_tpu.models.common import (matmul_precision, normal_init, project,
                                      rms_norm)
from paddle_tpu.ops.attention import NEG_INF
from paddle_tpu.ops.grouped_ffn import grouped_expert_ffn
from paddle_tpu.serving.program import ServingSpec

_HI = jax.lax.Precision.HIGHEST

_STATS = ("moe_routed_pairs", "moe_assignments", "moe_experts_touched",
          "moe_expert_slots")


@dataclasses.dataclass
class MLAMoELMConfig:
    vocab_size: int = 131072
    hidden_size: int = 4096
    num_hidden_layers: int = 36
    num_attention_heads: int = 32
    q_lora_rank: int = 1024
    kv_lora_rank: int = 256
    qk_nope_head_dim: int = 64
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rms_norm_eps: float = 1e-6
    max_position_embeddings: int = 1048576
    rope_theta: float = 10000.0
    #: YaRN: the context is ``factor`` times the ``original`` one
    rope_factor: float = 128.0
    original_max_position_embeddings: int = 8192
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 1.0
    llama_4_scaling_beta: float = 0.1
    #: routed experts held here, of ``num_routed_experts`` (None: all of
    #: them) from ``expert_offset`` on
    n_routed_experts: int = 128
    num_routed_experts: Optional[int] = None
    expert_offset: int = 0
    num_experts_per_tok: int = 4
    moe_intermediate_size: int = 2048
    n_shared_experts: int = 1
    routed_scaling_factor: float = 1.0
    norm_topk_prob: bool = True
    #: leading layers whose FFN is one dense SwiGLU ``intermediate_size``
    #: wide
    first_k_dense_replace: int = 0
    intermediate_size: Optional[int] = None
    #: "softmax", or "sigmoid" with a selection bias, picked inside the
    #: ``topk_group`` best of ``n_group`` groups of experts
    scoring_func: str = "softmax"
    n_group: int = 1
    topk_group: int = 1
    #: the lightning indexer: None, no selection
    index_topk: Optional[int] = None
    index_n_heads: int = 0
    index_head_dim: int = 0
    #: which body the expert kernel runs: "auto" (Pallas on a TPU, XLA
    #: elsewhere), "pallas", "pallas_interpret", "lax"
    kernel_impl: str = "auto"

    def __post_init__(self):
        if self.num_routed_experts is None:
            self.num_routed_experts = self.n_routed_experts
        if not 0 <= self.expert_offset <= \
                self.num_routed_experts - self.n_routed_experts:
            raise ValueError("the experts held lie within the router's")
        if self.qk_rope_head_dim % 2:
            raise ValueError("the rotary key is rotated in pairs")
        if self.scoring_func not in ("softmax", "sigmoid"):
            raise ValueError(f"scoring_func={self.scoring_func!r}: the "
                             "router scores by 'softmax' or 'sigmoid'")
        if self.num_routed_experts % self.n_group or not \
                1 <= self.topk_group <= self.n_group:
            raise ValueError("the routed experts fall into n_group equal "
                             "groups of which topk_group are kept")
        if self.n_group > 1 and (
                self.scoring_func != "sigmoid"
                or self.num_experts_per_tok > self.topk_group
                * (self.num_routed_experts // self.n_group)):
            raise ValueError("the group limit goes with the sigmoid rule "
                             "and leaves num_experts_per_tok to pick")
        if self.first_k_dense_replace and not self.intermediate_size:
            raise ValueError("a dense layer is intermediate_size wide")
        if self.index_topk is not None and (
                self.index_n_heads < 1
                or self.index_head_dim < self.qk_rope_head_dim):
            raise ValueError("the indexer has heads of at least the "
                             "rotary key's width")

    @property
    def holds_all_experts(self) -> bool:
        return self.n_routed_experts == self.num_routed_experts

    @property
    def row_dim(self) -> int:
        """Values a token caches a layer: the latent and the rotary key."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    def is_dense(self, i: int) -> bool:
        return i < self.first_k_dense_replace

    @classmethod
    def tiny_selecting(cls, **kw):
        """:meth:`tiny` with the indexer (16 of up to 64 cached tokens),
        one leading dense layer of three and the group-limited sigmoid
        router (4 of 16 experts a token inside 2 of 4 groups; 4 held, from
        4 on)."""
        for k, v in dict(num_hidden_layers=3, first_k_dense_replace=1,
                         intermediate_size=48, scoring_func="sigmoid",
                         n_group=4, topk_group=2, n_routed_experts=4,
                         expert_offset=4, routed_scaling_factor=2.5,
                         llama_4_scaling_beta=0.0, index_topk=16,
                         index_n_heads=2, index_head_dim=16).items():
            kw.setdefault(k, v)
        return cls.tiny(**kw)

    @classmethod
    def tiny(cls, **kw):
        """Positions up to 64 cross ``L0`` = 16 three times; of the four
        rotary pairs the first keeps its frequency and the rest are
        interpolated (both sides of YaRN's ramp)."""
        for k, v in dict(vocab_size=96, hidden_size=64, num_hidden_layers=2,
                         num_attention_heads=4, q_lora_rank=32,
                         kv_lora_rank=16, qk_nope_head_dim=8,
                         qk_rope_head_dim=8, v_head_dim=16,
                         max_position_embeddings=256, rope_factor=4.0,
                         original_max_position_embeddings=16,
                         n_routed_experts=2, num_routed_experts=16,
                         num_experts_per_tok=4,
                         moe_intermediate_size=32).items():
            kw.setdefault(k, v)
        return cls(**kw)


def _f32(a):
    return a.astype(jnp.float32)


def _m(factor, a):
    return 0.1 * a * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_frequencies(c: MLAMoELMConfig):
    """``omega`` (d_r / 2,) float32: a pair's angle a position."""
    d, theta, l0 = (c.qk_rope_head_dim, c.rope_theta,
                    c.original_max_position_embeddings)

    def pair_turning(turns):
        return d * math.log(l0 / (2 * math.pi * turns)) \
            / (2 * math.log(theta))

    low = max(math.floor(pair_turning(c.beta_fast)), 0)
    high = min(math.ceil(pair_turning(c.beta_slow)), d - 1)
    j = jnp.arange(d // 2, dtype=jnp.float32)
    phi = theta ** (-2.0 * j / d)
    ramp = jnp.clip((j - low) / max(high - low, 1e-3), 0.0, 1.0)
    return phi * (1.0 - ramp) + phi / c.rope_factor * ramp


def _layer_norm(u, p, eps):
    """LayerNorm with a scale and a bias over the last axis, float32."""
    mu = u.mean(-1, keepdims=True)
    var = ((u - mu) ** 2).mean(-1, keepdims=True)
    return (u - mu) * jax.lax.rsqrt(var + eps) * _f32(p["scale"]) \
        + _f32(p["bias"])


def _swiglu(h, p):
    """``(silu(h W_g) * (h W_u)) W_d``, operands of the weights' type."""
    gate = project(h, p["gate"]["weight"])
    up = project(h, p["up"]["weight"])
    return project(jax.nn.silu(gate) * up, p["down"]["weight"])


class MLAMoELM:
    def __init__(self, cfg: MLAMoELMConfig):
        self.cfg = cfg
        c = cfg
        self._omega = yarn_frequencies(c)
        self._trig_scale = _m(c.rope_factor, c.mscale) \
            / _m(c.rope_factor, c.mscale_all_dim)
        #: the softmax scale
        self.sigma = (c.qk_nope_head_dim + c.qk_rope_head_dim) ** -0.5 \
            * _m(c.rope_factor, c.mscale_all_dim) ** 2

    # -- parameters -------------------------------------------------------

    def init(self, key, dtype=jnp.float32):
        """Seeded parameters, made in ``dtype``: normal of std 0.02, the
        norms at 1. The router is drawn like the rest: over a normed
        stream of ``D`` channels its logits spread by ``0.02 sqrt(D)``
        (1.28 at 4096), so the ``K`` largest of 128 are no ties."""
        c = self.cfg
        d, f, h = c.hidden_size, c.moe_intermediate_size, \
            c.num_attention_heads
        qk = c.qk_nope_head_dim + c.qk_rope_head_dim
        e = c.n_routed_experts
        ones = lambda n: {"scale": jnp.ones((n,), dtype)}       # noqa: E731
        lin = lambda k, a, b: {                                 # noqa: E731
            "weight": normal_init(k, (a, b), dtype)}

        def mlp(k, width):
            k = jax.random.split(k, 3)
            return {"gate": lin(k[0], d, width), "up": lin(k[1], d, width),
                    "down": lin(k[2], width, d)}

        keys = jax.random.split(key, c.num_hidden_layers + 2)
        layers = {}
        for i in range(c.num_hidden_layers):
            k = jax.random.split(keys[i], 10)
            layer = layers[str(i)] = {
                "attn_norm": ones(d),
                "q_a_proj": lin(k[0], d, c.q_lora_rank),
                "q_a_norm": ones(c.q_lora_rank),
                "q_b_proj": lin(k[1], c.q_lora_rank, h * qk),
                "kv_a_proj": lin(k[2], d, c.row_dim),
                "kv_a_norm": ones(c.kv_lora_rank),
                # head i's columns: [W_UK,i (d_n) | W_UV,i (d_v)]
                "kv_b_proj": lin(k[3], c.kv_lora_rank,
                                 h * (c.qk_nope_head_dim + c.v_head_dim)),
                "o_proj": lin(k[4], h * c.v_head_dim, d),
                "ffn_norm": ones(d),
                "router": lin(k[5], d, c.num_routed_experts),
                # (E, F, D) each: a block of hidden units is one
                # contiguous piece of every expert's three matrices
                "experts": {"gate": normal_init(k[6], (e, f, d), dtype),
                            "up": normal_init(k[7], (e, f, d), dtype),
                            "down": normal_init(k[8], (e, f, d), dtype)},
                "shared": mlp(k[9], f * c.n_shared_experts),
            }
            if c.is_dense(i):
                for name in ("router", "experts", "shared"):
                    del layer[name]
                layer["mlp"] = mlp(k[9], c.intermediate_size)
            elif c.scoring_func == "sigmoid":
                layer["router_bias"] = jnp.zeros((c.num_routed_experts,),
                                                 jnp.float32)
            if c.index_topk is not None:
                ki = jax.random.split(jax.random.fold_in(keys[i], 1), 3)
                di = c.index_head_dim
                layer.update(
                    idx_q=lin(ki[0], c.q_lora_rank, c.index_n_heads * di),
                    idx_k=lin(ki[1], d, di),
                    idx_k_norm={"scale": jnp.ones((di,), dtype),
                                "bias": jnp.zeros((di,), dtype)},
                    idx_w=lin(ki[2], d, c.index_n_heads))
        return {"embed": lin(keys[-2], c.vocab_size, d),
                "layers": layers, "final_norm": ones(d),
                "head": lin(keys[-1], c.vocab_size, d)}

    # -- the block, shared by forward() and the serving program -----------

    def embed(self, params, tokens, positions):
        del positions                       # rotary: applied in attn_in
        return _f32(params["embed"]["weight"][tokens])

    def rope(self, u, positions):
        """Adjacent pairs of the last axis rotated by ``positions *
        omega``; ``u`` (S, C, d_r) or (S, C, H, d_r) float32."""
        ang = _f32(positions)[..., None] * self._omega          # (S,C,d/2)
        if u.ndim == 4:
            ang = ang[:, :, None, :]
        cos = jnp.cos(ang) * self._trig_scale
        sin = jnp.sin(ang) * self._trig_scale
        a, b = u[..., 0::2], u[..., 1::2]
        return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                         -1).reshape(u.shape)

    def query_scale(self, positions):
        """``a_t sigma`` (S, C) float32: the softmax scale times the
        position-dependent query factor."""
        c = self.cfg
        return self.sigma * (1.0 + c.llama_4_scaling_beta * jnp.log1p(
            _f32(positions // c.original_max_position_embeddings)))

    def _up(self, lp):
        """``W_UK`` (d_c, H, d_n), ``W_UV`` (d_c, H, d_v) out of the one
        published matrix."""
        c = self.cfg
        w = lp["kv_b_proj"]["weight"].reshape(
            c.kv_lora_rank, c.num_attention_heads, -1)
        return w[..., :c.qk_nope_head_dim], w[..., c.qk_nope_head_dim:]

    def attn_in(self, params, i, x, positions):
        """-> (qt (S, H, C, d_c + d_r) scaled, (c (S, C, d_c), k_rope (S,
        C, d_r)), None): the absorbed queries and the row to cache; where
        the model selects, the rows end with ``kI`` (S, C, Di) and the
        third result is ``(qI (S, C, J, Di), h W_w (S, C, J) float32)``,
        the indexer's queries and head weights (``J^(-1/2) Di^(-1/2)`` is
        the scale their scores are given)."""
        c, lp = self.cfg, params["layers"][str(i)]
        s, n, _ = x.shape
        dn, dc = c.qk_nope_head_dim, c.kv_lora_rank
        w_uk, _ = self._up(lp)
        a = rms_norm(x, lp["attn_norm"]["scale"], c.rms_norm_eps)
        c_q = rms_norm(project(a, lp["q_a_proj"]["weight"]),
                       lp["q_a_norm"]["scale"], c.rms_norm_eps)
        q = project(c_q, lp["q_b_proj"]["weight"]).reshape(
            s, n, c.num_attention_heads, -1)
        kv = project(a, lp["kv_a_proj"]["weight"])
        latent = rms_norm(kv[..., :dc], lp["kv_a_norm"]["scale"],
                          c.rms_norm_eps)
        k_rope = self.rope(kv[..., dc:], positions)
        q_latent = jnp.einsum(
            "schd,lhd->schl", q[..., :dn].astype(w_uk.dtype), w_uk,
            precision=matmul_precision(w_uk.dtype),
            preferred_element_type=jnp.float32)
        qt = jnp.concatenate(
            [q_latent, self.rope(q[..., dn:], positions)], -1) \
            * self.query_scale(positions)[:, :, None, None]
        if c.index_topk is None:
            return (qt.astype(w_uk.dtype).transpose(0, 2, 1, 3),
                    (latent, k_rope), None)
        dr = c.qk_rope_head_dim

        def partly_rotated(u):
            return jnp.concatenate(
                [self.rope(u[..., :dr], positions), u[..., dr:]], -1)

        q_idx = partly_rotated(project(c_q, lp["idx_q"]["weight"]).reshape(
            s, n, c.index_n_heads, c.index_head_dim))
        k_idx = partly_rotated(_layer_norm(
            project(a, lp["idx_k"]["weight"]), lp["idx_k_norm"],
            c.rms_norm_eps))
        w_idx = jnp.matmul(_f32(a), _f32(lp["idx_w"]["weight"]),
                           precision=_HI)
        return (qt.astype(w_uk.dtype).transpose(0, 2, 1, 3),
                (latent, k_rope, k_idx),
                (q_idx.astype(w_uk.dtype), w_idx))

    def attn_out(self, params, i, x, att):
        """``att`` (S, C, H, d_c): each head's weighted sum of latents."""
        lp = params["layers"][str(i)]
        s, n = att.shape[:2]
        _, w_uv = self._up(lp)
        o = jnp.einsum("schl,lhv->schv", att.astype(w_uv.dtype), w_uv,
                       precision=matmul_precision(w_uv.dtype),
                       preferred_element_type=jnp.float32)
        return x + project(o.reshape(s, n, -1), lp["o_proj"]["weight"])

    def route(self, params, i, flat):
        """The router of layer ``i`` over ``flat`` (T, D) float32: ->
        (ids (T, K) int32 of all the routed experts, weights (T, K)
        float32)."""
        c = self.cfg
        if c.scoring_func == "sigmoid":
            return self._route_in_groups(params["layers"][str(i)], flat)
        score = jax.nn.softmax(jnp.matmul(
            flat, _f32(params["layers"][str(i)]["router"]["weight"]),
            precision=_HI), axis=-1)
        top, ids = jax.lax.top_k(score, c.num_experts_per_tok)
        if c.norm_topk_prob:
            top = top / top.sum(-1, keepdims=True)
        return ids.astype(jnp.int32), c.routed_scaling_factor * top

    def _route_in_groups(self, lp, flat):
        """The sigmoid rule: scores with the selection bias pick, inside
        the ``topk_group`` groups whose two best add up highest; the
        scores themselves weigh."""
        c = self.cfg
        t = flat.shape[0]
        score = jax.nn.sigmoid(jnp.matmul(
            flat, _f32(lp["router"]["weight"]), precision=_HI))
        pick = score + lp["router_bias"]
        if c.n_group > 1:
            grouped = pick.reshape(t, c.n_group, -1)
            best = jax.lax.top_k(grouped, 2)[0].sum(-1)         # (T, groups)
            _, kept = jax.lax.top_k(best, c.topk_group)
            allowed = jnp.zeros((t, c.n_group), bool).at[
                jnp.arange(t)[:, None], kept].set(True)
            pick = jnp.where(allowed[:, :, None], grouped,
                             -jnp.inf).reshape(t, -1)
        _, ids = jax.lax.top_k(pick, c.num_experts_per_tok)
        top = jnp.take_along_axis(score, ids, axis=1)
        if c.norm_topk_prob:
            top = top / top.sum(-1, keepdims=True)
        return ids.astype(jnp.int32), c.routed_scaling_factor * top

    def ffn(self, params, i, x, valid):
        c, lp = self.cfg, params["layers"][str(i)]
        s, n, d = x.shape
        b = rms_norm(x, lp["ffn_norm"]["scale"], c.rms_norm_eps)
        if c.is_dense(i):
            zero = jnp.zeros((), jnp.int32)
            return x + _swiglu(b, lp["mlp"]), dict.fromkeys(_STATS, zero)
        flat = b.reshape(s * n, d)
        ids, coef = self.route(params, i, flat)
        ex = lp["experts"]
        held = None if c.holds_all_experts else (c.expert_offset,
                                                 c.num_routed_experts)
        live = valid.reshape(s * n)
        y, sizes = grouped_expert_ffn(
            flat.astype(ex["gate"].dtype), ids, coef, live, ex["gate"],
            ex["up"], ex["down"], impl=c.kernel_impl, held=held)
        stats = {"moe_routed_pairs": live.sum() * c.num_experts_per_tok,
                 "moe_assignments": sizes.sum(),
                 "moe_experts_touched": (sizes > 0).sum(),
                 "moe_expert_slots": c.n_routed_experts}
        return x + y.reshape(s, n, d) + _swiglu(b, lp["shared"]), stats

    def head(self, params, x):
        w = params["head"]["weight"]
        x = rms_norm(x, params["final_norm"]["scale"], self.cfg.rms_norm_eps)
        return jnp.einsum("...d,vd->...v", x.astype(w.dtype), w,
                          precision=matmul_precision(w.dtype),
                          preferred_element_type=jnp.float32)

    # -- whole-sequence pass ------------------------------------------------

    def forward(self, params, ids):
        """(B, S) ids -> (B, S, V) float32 logits: the absorbed block over
        dense causal scores against every token's row, no cache."""
        b, n = ids.shape
        dc = self.cfg.kv_lora_rank
        pos = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32), (b, n))
        valid = jnp.ones((b, n), bool)
        x = self.embed(params, ids, pos)
        t = jnp.arange(n)
        causal = t[None, :] <= t[:, None]
        for i in range(self.cfg.num_hidden_layers):
            qt, rows, index = self.attn_in(params, i, x, pos)
            row = jnp.concatenate(rows[:2], -1)
            att = jnp.einsum("bhqd,bkd->bhqk", _f32(qt), row, precision=_HI)
            seen = causal
            if index is not None:
                seen = self._selected(index, rows[2], t + 1)[:, None] > 0
            att = jax.nn.softmax(jnp.where(seen, att, NEG_INF), axis=-1)
            u = jnp.einsum("bhqk,bkl->bqhl", att, row[..., :dc],
                           precision=_HI)
            x = self.attn_out(params, i, x, u)
            x, _ = self.ffn(params, i, x, valid)
        return self.head(params, x)

    def _selected(self, index, k_idx, n):
        """(B, S, S) float32, 1 where query ``t`` attends to token ``s``:
        the rule by sorting, over index scores made in float32."""
        from paddle_tpu.serving.sparse_attention import selected_by_sort
        q_idx, w_idx = index
        dots = jnp.einsum("bqjd,bkd->bqjk", _f32(q_idx), _f32(k_idx),
                          precision=_HI)
        c = self.cfg
        scores = (c.index_n_heads * c.index_head_dim) ** -0.5 * jnp.einsum(
            "bqj,bqjk->bqk", w_idx, jnp.maximum(dots, 0.0), precision=_HI)
        return selected_by_sort(scores, jnp.broadcast_to(n, scores.shape[:2]),
                                c.index_topk)

    # -- the paged serving engine's view ------------------------------------

    def serving(self, **unsupported):
        """This model's block as the paged serving engine runs it. It
        takes none of the engine's build options (``spec.supports`` holds
        prefix sharing alone, which needs none)."""
        if unsupported:
            raise ValueError(f"MLAMoELM.serving() takes no options yet, "
                             f"got {sorted(unsupported)}")
        return MLAMoEServing(self)


class MLAMoEServing:
    """:mod:`paddle_tpu.serving.program` for :class:`MLAMoELM`: one latent
    row cached a token and layer (``latent_row``), the absorbed queries
    against it, the expert share's counts handed back. A latent page is a
    page like any other, so prompts share their prefixes; nothing that
    quantizes, shards, snapshots, ships or speculates carries a one-row
    page yet."""

    def __init__(self, model: MLAMoELM):
        c = model.cfg
        self.model = model
        self.embed, self.attn_in = model.embed, model.attn_in
        self.attn_out, self.ffn, self.head = (model.attn_out, model.ffn,
                                              model.head)
        selects = {} if c.index_topk is None else dict(
            extra_rows=(("index_k", c.index_head_dim),),
            select_topk=c.index_topk)
        self.spec = ServingSpec(
            num_layers=c.num_hidden_layers,
            num_heads=c.num_attention_heads, kv_heads=1,
            head_dim=c.row_dim, vocab_size=c.vocab_size,
            max_position=c.max_position_embeddings, stats=_STATS,
            latent_row=(c.kv_lora_rank, c.qk_rope_head_dim),
            supports=frozenset({"prefix_sharing"}), **selects)

    def param_dtype(self, params):
        return params["embed"]["weight"].dtype
