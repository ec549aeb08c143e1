"""Decoder-only language model whose layers mix window and full attention
over a sigmoid-routed expert layer (with or without a shared expert), of
which a chip may hold a share, for the paged serving engine.

Layer ``l``, float32 stream ``x`` (``D`` wide; ``H`` query heads over ``G``
KV heads of ``d``, head ``i`` reads KV head ``i // (H / G)``)::

    h    = rms(x; g1)
    q, k, v = h W_q, h W_k, h W_v                 no biases
    q_i  = rms_d(q_i; gq) ;  k_j = rms_d(k_j; gk)   per head
    window layer ("sliding_attention"):
           q, k = rope(q), rope(k)                the whole head, rotate-half
           token t attends s with t - window < s <= t
    full layer ("full_attention"): no rotary embedding; s <= t
    x'   = x + softmax(q k^T / sqrt(d)) v W_o

which the options below turn, each alone, into the family whose two kinds
of layer differ in more than the mask (MiMo-V2: none of them set gives the
block above, parameter for parameter):

- ``swa_num_key_value_heads``: the window layers' KV heads where they are
  not the full layers' (``G_l``: 8 beside 4);
- ``v_head_dim``: values narrower than keys (``d`` = 192 for q and k, 128
  for v and the heads that reach ``W_o``);
- ``partial_rotary_factor`` < 1: the rotary embedding on the first
  ``int(d x factor)`` entries of a head, the rest as they are;
  ``full_attention_rope``: on the full layers too, base ``rope_theta``
  there and ``swa_rope_theta`` on the window layers;
- ``qk_norm=False``: no per-head norm (and no ``gq``, ``gk``);
- ``attention_value_scale``: ``v`` multiplied by it before it is cached;
- ``add_swa_attention_sink_bias`` / ``add_full_attention_sink_bias``: a
  learned logit ``b_i`` a query head (``sinks``) that joins that kind of
  layer's softmax denominator and nothing else, ``p_is = exp(a_is) /
  (exp(b_i) + sum_s' exp(a_is'))``;
- ``num_shared_experts`` 0: no shared expert (and no ``shared``).
    h2   = rms(x'; g2)
    dense layer:  x'' = x' + (silu(h2 W_g) * (h2 W_u)) W_d
    sparse layer: s   = sigmoid(h2 W_r)           float32, all routed experts
                  S   = the K largest of s + b    ties to the lower index
                  w_e = scale * s_e / sum_{j in S} s_j
                  x'' = x' + sum_{e in S, e held here} w_e FFN_e(h2)
                           + FFN_shared(h2)       every FFN SwiGLU

The router is as wide as the model's routed experts (``num_routed_experts``)
and picks ``num_experts_per_tok`` of them; the layer holds ``num_experts``
of them, from ``expert_offset`` on, and computes their part of the sum:
what the experts held on other chips would add is theirs, and this chip's
partial result goes on (the shared expert is whole on every chip). The
vocabulary is the rows held here. Untied head.

The residual stream, the norms and the router are float32 whatever the
weights' type; projections and experts take operands of the weights'
type. The config's key names are those of the published ``config.json``
of this family (EXAONE-MoE: ``layer_types``, ``mlp_layer_types``,
``sliding_window``, ``routed_scaling_factor`` ...), so a configuration
file's numbers can be passed straight in. ``forward`` is the
whole-sequence pass (dense scores, the window as a mask); ``serving()`` is
the same block as the paged engine runs it, a window layer's K and V in
the cache's ring (``ServingSpec.layer_windows``).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from paddle_tpu.models.common import (matmul_precision, normal_init, project,
                                      rms_norm, rope)
from paddle_tpu.ops.attention import NEG_INF
from paddle_tpu.ops.grouped_ffn import (grouped_expert_ffn, held_pairs,
                                        tile_rows)
from paddle_tpu.serving.program import ServingSpec

_HI = jax.lax.Precision.HIGHEST

_STATS = ("moe_routed_pairs", "moe_assignments", "moe_experts_touched",
          "moe_expert_slots", "moe_max_expert_tokens", "moe_tile_rows")


@dataclasses.dataclass
class WindowMoELMConfig:
    vocab_size: int = 153600
    hidden_size: int = 6144
    intermediate_size: int = 18432
    num_hidden_layers: int = 48
    num_attention_heads: int = 64
    num_key_value_heads: int = 8
    head_dim: int = 128
    rms_norm_eps: float = 1e-5
    rope_theta: float = 1e6
    max_position_embeddings: int = 262144
    #: one entry a layer: "sliding_attention" | "full_attention"
    layer_types: Tuple[str, ...] = ()
    sliding_window: int = 128
    #: one entry a layer: "dense" | "sparse"
    mlp_layer_types: Tuple[str, ...] = ()
    #: routed experts held here, of ``num_routed_experts`` (None: all of
    #: them) from ``expert_offset`` on
    num_experts: int = 128
    num_routed_experts: Optional[int] = None
    expert_offset: int = 0
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 2048
    num_shared_experts: int = 1
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    #: the window layers' KV heads (None: ``num_key_value_heads``)
    swa_num_key_value_heads: Optional[int] = None
    #: the width of a head's values (None: ``head_dim``)
    v_head_dim: Optional[int] = None
    #: the share of a head's entries, from the first, that are rotated
    partial_rotary_factor: float = 1.0
    #: the rotary embedding on the full layers too (base ``rope_theta``)
    full_attention_rope: bool = False
    #: the window layers' base (None: ``rope_theta``)
    swa_rope_theta: Optional[float] = None
    qk_norm: bool = True
    attention_value_scale: float = 1.0
    add_swa_attention_sink_bias: bool = False
    add_full_attention_sink_bias: bool = False
    #: which body the kernels run: "auto" (Pallas on a TPU, XLA
    #: elsewhere), "pallas", "pallas_interpret", "lax"
    kernel_impl: str = "auto"

    def __post_init__(self):
        n = self.num_hidden_layers
        self.layer_types = tuple(self.layer_types) or \
            ("sliding_attention", "sliding_attention", "sliding_attention",
             "full_attention") * n
        self.mlp_layer_types = tuple(self.mlp_layer_types) or \
            ("dense",) + ("sparse",) * n
        # a stage builds the first layers of the published lists
        self.layer_types = self.layer_types[:n]
        self.mlp_layer_types = self.mlp_layer_types[:n]
        if len(self.layer_types) != n or len(self.mlp_layer_types) != n \
                or set(self.layer_types) - {"sliding_attention",
                                            "full_attention"} \
                or set(self.mlp_layer_types) - {"dense", "sparse"}:
            raise ValueError("layer_types / mlp_layer_types name every "
                             "layer: sliding_attention | full_attention, "
                             "dense | sparse")
        if self.num_routed_experts is None:
            self.num_routed_experts = self.num_experts
        if not 0 <= self.expert_offset <= \
                self.num_routed_experts - self.num_experts:
            raise ValueError("the experts held lie within the router's")
        if any(self.num_attention_heads % g for g in self.layer_kv_heads):
            raise ValueError("query heads in whole groups over the KV heads")

    @property
    def layer_windows(self):
        return tuple(self.sliding_window if t == "sliding_attention" else None
                     for t in self.layer_types)

    @property
    def layer_kv_heads(self):
        """The KV heads of each layer, by its kind."""
        swa = self.swa_num_key_value_heads or self.num_key_value_heads
        return tuple(swa if t == "sliding_attention"
                     else self.num_key_value_heads for t in self.layer_types)

    @property
    def sink_layers(self):
        """Which layers' softmax carries a learned sink."""
        return tuple(self.add_swa_attention_sink_bias
                     if t == "sliding_attention"
                     else self.add_full_attention_sink_bias
                     for t in self.layer_types)

    @property
    def value_dim(self) -> int:
        return self.v_head_dim or self.head_dim

    @property
    def rotary_dim(self) -> int:
        return int(self.head_dim * self.partial_rotary_factor)

    def layer_rope_theta(self, i: int) -> Optional[float]:
        """The rotary base of layer ``i``; None: it has no rotary
        embedding."""
        if self.layer_types[i] == "sliding_attention":
            return self.rope_theta if self.swa_rope_theta is None \
                else self.swa_rope_theta
        return self.rope_theta if self.full_attention_rope else None

    @property
    def holds_all_experts(self) -> bool:
        return self.num_experts == self.num_routed_experts

    @classmethod
    def tiny(cls, **kw):
        for k, v in dict(vocab_size=96, hidden_size=64, intermediate_size=96,
                         num_hidden_layers=5, num_attention_heads=4,
                         num_key_value_heads=2, head_dim=16,
                         max_position_embeddings=256, sliding_window=8,
                         num_experts=2, num_routed_experts=8,
                         num_experts_per_tok=3,
                         moe_intermediate_size=32).items():
            kw.setdefault(k, v)
        return cls(**kw)


def _f32(a):
    return a.astype(jnp.float32)


def _swiglu(h, p):
    """``(silu(h W_g) * (h W_u)) W_d``, operands of the weights' type."""
    gate = project(h, p["gate"]["weight"])
    up = project(h, p["up"]["weight"])
    return project(jax.nn.silu(gate) * up, p["down"]["weight"])


class WindowMoELM:
    def __init__(self, cfg: WindowMoELMConfig):
        self.cfg = cfg

    # -- parameters -------------------------------------------------------

    def init(self, key, dtype=jnp.float32):
        """Seeded parameters, made in ``dtype``: normal of std 0.02, the
        norms at 1, the router's selection bias at 0. The router is drawn
        like the rest: over a normed stream of ``D`` channels its logits
        then spread by ``0.02 sqrt(D)`` (1.57 at 6144), so the sigmoid
        scores lie between 0.05 and 0.95 and the ``K`` largest are no
        ties (neighbours near the ``K``-th of 128 lie about 0.1 apart in
        the logit).

        A layer's sinks are drawn so that they MATTER: ``b = log(window) +
        (0.0004 D)^2 / 2 + log(2/3) + 0.3 n``, ``n`` standard normal a
        head. A window's scores over normal(0.02) projections of a normed
        stream spread by ``0.0004 D`` (1.64 at 4096), so its ``window``
        terms ``exp(a)`` sum to about ``window x exp(var / 2)`` (490 at
        128 and 4096) and the sink then takes about two fifths of the
        softmax's mass, more where a head's draw is high; a sink at 0
        would take 0.2% and a program that dropped it would pass every
        comparison."""
        c = self.cfg
        d, dh, f = c.hidden_size, c.head_dim, c.moe_intermediate_size
        h, e, dv = c.num_attention_heads, c.num_experts, c.value_dim
        ones = lambda n: {"scale": jnp.ones((n,), dtype)}       # noqa: E731

        def mlp(k, width):
            k = jax.random.split(k, 3)
            return {"gate": {"weight": normal_init(k[0], (d, width), dtype)},
                    "up": {"weight": normal_init(k[1], (d, width), dtype)},
                    "down": {"weight": normal_init(k[2], (width, d), dtype)}}

        keys = jax.random.split(key, c.num_hidden_layers + 2)
        layers = {}
        for i in range(c.num_hidden_layers):
            k = jax.random.split(keys[i], 10)
            g = c.layer_kv_heads[i]
            lp = {
                "attn_norm": ones(d),
                "q_proj": {"weight": normal_init(k[0], (d, h * dh), dtype)},
                "k_proj": {"weight": normal_init(k[1], (d, g * dh), dtype)},
                "v_proj": {"weight": normal_init(k[2], (d, g * dv), dtype)},
                "o_proj": {"weight": normal_init(k[3], (h * dv, d), dtype)},
                "ffn_norm": ones(d),
            }
            if c.qk_norm:
                lp.update(q_norm=ones(dh), k_norm=ones(dh))
            if c.sink_layers[i]:
                lp["sinks"] = (
                    math.log(c.sliding_window * 2 / 3) + (0.0004 * d) ** 2 / 2
                    + 0.3 * jax.random.normal(
                        jax.random.fold_in(keys[i], 1), (h,), jnp.float32))
            if c.mlp_layer_types[i] == "dense":
                lp["mlp"] = mlp(k[4], c.intermediate_size)
            else:
                lp["router"] = {
                    "weight": normal_init(
                        k[5], (d, c.num_routed_experts), dtype),
                    "selection_bias": jnp.zeros((c.num_routed_experts,),
                                                dtype)}
                # (E, F, D) each: a block of hidden units is one
                # contiguous piece of every expert's three matrices
                lp["experts"] = {
                    "gate": normal_init(k[6], (e, f, d), dtype),
                    "up": normal_init(k[7], (e, f, d), dtype),
                    "down": normal_init(k[8], (e, f, d), dtype)}
                if c.num_shared_experts:
                    lp["shared"] = mlp(k[9], f * c.num_shared_experts)
            layers[str(i)] = lp
        return {"embed": {"weight": normal_init(
                    keys[-2], (c.vocab_size, d), dtype)},
                "layers": layers, "final_norm": ones(d),
                "head": {"weight": normal_init(
                    keys[-1], (c.vocab_size, d), dtype)}}

    # -- the block, shared by forward() and the serving program -----------

    def embed(self, params, tokens, positions):
        del positions                       # rotary: applied at q and k
        return _f32(params["embed"]["weight"][tokens])

    def attn_in(self, params, i, x, positions):
        """-> (q (S, H, C, d), (K rows (S, C, G d), V rows (S, C, G dv)),
        the layer's sinks (H,) float32 or None)."""
        c, lp = self.cfg, params["layers"][str(i)]
        s, n, _ = x.shape
        h, g, dh = c.num_attention_heads, c.layer_kv_heads[i], c.head_dim
        a = rms_norm(x, lp["attn_norm"]["scale"], c.rms_norm_eps)
        q = project(a, lp["q_proj"]["weight"]).reshape(s, n, h, dh)
        k = project(a, lp["k_proj"]["weight"]).reshape(s, n, g, dh)
        v = project(a, lp["v_proj"]["weight"])
        if c.qk_norm:
            q = rms_norm(q, lp["q_norm"]["scale"], c.rms_norm_eps)
            k = rms_norm(k, lp["k_norm"]["scale"], c.rms_norm_eps)
        theta = c.layer_rope_theta(i)
        if theta is not None:
            q = rope(q, positions, theta, c.rotary_dim)
            k = rope(k, positions, theta, c.rotary_dim)
        if c.attention_value_scale != 1.0:
            v = v * c.attention_value_scale
        q = q.astype(lp["q_proj"]["weight"].dtype)
        sinks = _f32(lp["sinks"]) if c.sink_layers[i] else None
        return q.transpose(0, 2, 1, 3), (k.reshape(s, n, g * dh), v), sinks

    def attn_out(self, params, i, x, att):
        lp = params["layers"][str(i)]
        s, n = att.shape[:2]
        return x + project(att.reshape(s, n, -1), lp["o_proj"]["weight"])

    def route(self, params, i, flat):
        """The router of sparse layer ``i`` over ``flat`` (T, D) float32:
        -> (ids (T, K) int32 of all the routed experts, weights (T, K)
        float32)."""
        c, rp = self.cfg, params["layers"][str(i)]["router"]
        score = jax.nn.sigmoid(jnp.matmul(flat, _f32(rp["weight"]),
                                          precision=_HI))
        _, ids = jax.lax.top_k(score + _f32(rp["selection_bias"]),
                               c.num_experts_per_tok)
        top = jnp.take_along_axis(score, ids, axis=-1)
        if c.norm_topk_prob:
            top = top / top.sum(-1, keepdims=True)
        return ids.astype(jnp.int32), c.routed_scaling_factor * top

    def ffn(self, params, i, x, valid):
        c, lp = self.cfg, params["layers"][str(i)]
        s, n, d = x.shape
        b = rms_norm(x, lp["ffn_norm"]["scale"], c.rms_norm_eps)
        if c.mlp_layer_types[i] == "dense":
            zero = jnp.zeros((), jnp.int32)
            return x + _swiglu(b, lp["mlp"]), {name: zero for name in _STATS}
        flat = b.reshape(s * n, d)
        ids, coef = self.route(params, i, flat)
        ex = lp["experts"]
        held = None if c.holds_all_experts else (c.expert_offset,
                                                 c.num_routed_experts)
        live = valid.reshape(s * n)
        y, sizes = grouped_expert_ffn(
            flat.astype(ex["gate"].dtype), ids, coef, live, ex["gate"],
            ex["up"], ex["down"], impl=c.kernel_impl, held=held)
        k = c.num_experts_per_tok
        tm = tile_rows(held_pairs(s * n * k, c.num_experts, held),
                       c.num_experts)
        stats = {"moe_routed_pairs": live.sum() * k,
                 "moe_assignments": sizes.sum(),
                 "moe_experts_touched": (sizes > 0).sum(),
                 "moe_expert_slots": c.num_experts,
                 "moe_max_expert_tokens": sizes.max(),
                 "moe_tile_rows": (-(-sizes // tm)).sum() * tm}
        x = x + y.reshape(s, n, d)
        if c.num_shared_experts:
            x = x + _swiglu(b, lp["shared"])
        return x, stats

    def head(self, params, x):
        w = params["head"]["weight"]
        x = rms_norm(x, params["final_norm"]["scale"], self.cfg.rms_norm_eps)
        return jnp.einsum("...d,vd->...v", x.astype(w.dtype), w,
                          precision=matmul_precision(w.dtype),
                          preferred_element_type=jnp.float32)

    # -- whole-sequence pass ------------------------------------------------

    def forward(self, params, ids):
        """(B, S) ids -> (B, S, V) float32 logits: dense causal scores,
        the window as a mask, no cache."""
        c = self.cfg
        b, n = ids.shape
        pos = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32), (b, n))
        valid = jnp.ones((b, n), bool)
        x = self.embed(params, ids, pos)
        t = jnp.arange(n)
        causal = t[None, :] <= t[:, None]
        for i, win in enumerate(c.layer_windows):
            q, (k, v), sinks = self.attn_in(params, i, x, pos)
            group = c.num_attention_heads // c.layer_kv_heads[i]
            kh = jnp.repeat(k.reshape(b, n, -1, c.head_dim), group, axis=2)
            vh = jnp.repeat(v.reshape(b, n, -1, c.value_dim), group, axis=2)
            seen = causal if win is None else \
                causal & (t[None, :] > t[:, None] - win)
            att = jnp.einsum("bhqd,bkhd->bhqk", _f32(q), kh, precision=_HI)
            att = jnp.where(seen, att * c.head_dim ** -0.5, NEG_INF)
            if sinks is None:
                att = jax.nn.softmax(att, axis=-1)
            else:       # one more column a head, which sums no value
                att = jax.nn.softmax(jnp.concatenate(
                    [att, jnp.broadcast_to(sinks[None, :, None, None],
                                           att.shape[:3] + (1,))], -1),
                    axis=-1)[..., :-1]
            o = jnp.einsum("bhqk,bkhd->bqhd", att, vh, precision=_HI)
            x = self.attn_out(params, i, x, o)
            x, _ = self.ffn(params, i, x, valid)
        return self.head(params, x)

    # -- the paged serving engine's view ------------------------------------

    def serving(self, **unsupported):
        """This model's block as the paged serving engine runs it. It
        takes none of the engine's options yet (``spec.supports`` is
        empty, so the engine refuses them before asking)."""
        if unsupported:
            raise ValueError(f"WindowMoELM.serving() takes no options yet, "
                             f"got {sorted(unsupported)}")
        return WindowMoEServing(self)


class WindowMoEServing:
    """:mod:`paddle_tpu.serving.program` for :class:`WindowMoELM`: K and V
    cached a token and layer, a window layer's in the cache's ring
    (``layer_windows``), each kind of layer at its own KV heads and the
    values at their own width (``layer_kv_heads``, ``value_dim``), the
    sinks of the layers that have them handed over as ``attn_in``'s third
    result (``sink_layers``), the expert share's counts handed back. No option
    that shares, snapshots, ships or speculates carries two kinds of layer
    yet (a borrower of a prefix would need the window layers' last tokens
    of it), so ``supports`` is empty."""

    def __init__(self, model: WindowMoELM):
        c = model.cfg
        self.model = model
        self.embed, self.attn_in = model.embed, model.attn_in
        self.attn_out, self.ffn, self.head = (model.attn_out, model.ffn,
                                              model.head)
        self.spec = ServingSpec(
            num_layers=c.num_hidden_layers,
            num_heads=c.num_attention_heads,
            kv_heads=c.num_key_value_heads, head_dim=c.head_dim,
            vocab_size=c.vocab_size,
            max_position=c.max_position_embeddings,
            stats=_STATS, layer_windows=c.layer_windows,
            layer_kv_heads=c.layer_kv_heads, value_dim=c.value_dim,
            sink_layers=c.sink_layers, supports=frozenset())

    def param_dtype(self, params):
        return params["embed"]["weight"].dtype
