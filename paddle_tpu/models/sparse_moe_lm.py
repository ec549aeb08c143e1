"""Decoder-only language model with learned sparse attention and sparse
experts, for the paged serving engine.

Every layer: RMSNorm, grouped-query attention (per-head RMSNorm on q and
k, rotary positions over the whole head, rotate-half pairing) in which a
**lightning indexer** chooses the ``topk`` cached tokens a query attends
to once it can see more than ``topk``, RMSNorm, and a routed SwiGLU expert
layer: softmax router in float32, the ``num_experts_per_tok`` best
experts a token with their probabilities renormalised
(``norm_topk_prob``), no capacity and no dropped token, no shared expert.
Untied output head. The residual stream is float32 whatever the
weights' type (a lane of a serving step is a few thousand numbers): the
router, the norms and the indexer's head weights then see what a float32
model would up to the rounding of each block's own matmul inputs, not the
rounding of the stream itself, so fewer tokens fall on the other side of a
routing or selection threshold than their float32 reference.

Indexer, for token ``t`` against an earlier token ``s``::

    qI[t, j] = rope(W_qI^j a_t)          j < indexer_num_heads
    kI[s]    = rope(layernorm(W_kI a_s)) one indexer key a token
    I[t, s]  = (J * Di) ** -0.5 * sum_j (W_w a_t)[j] * relu(qI[t, j] . kI[s])

with ``a`` the layer's normed input. ``kI`` is cached beside K and V.

The config's key names are those of the published ``config.json`` files of
this family (Qwen3-MoE keys plus an ``sa_config`` group), so a
configuration file's numbers can be passed straight in. ``forward`` is
the whole-sequence pass (dense scores, the selection as a mask);
``serving()`` is the same block as the paged engine runs it.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from paddle_tpu.models.common import rms_norm as _rms_norm
from paddle_tpu.models.common import rope as _rope
from paddle_tpu.ops.attention import NEG_INF
from paddle_tpu.ops.grouped_ffn import grouped_expert_ffn
from paddle_tpu.serving.program import ServingSpec
from paddle_tpu.serving.sparse_attention import selected_by_sort

_HI = jax.lax.Precision.HIGHEST


@dataclasses.dataclass
class SparseMoELMConfig:
    vocab_size: int = 151936
    hidden_size: int = 2048
    num_hidden_layers: int = 48
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1e7
    max_position_embeddings: int = 262144
    num_experts: int = 128
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 768
    norm_topk_prob: bool = True
    indexer_num_heads: int = 16
    indexer_head_dim: int = 64
    indexer_topk: int = 2048
    #: which body the kernels run: "auto" (Pallas on a TPU, XLA
    #: elsewhere), "pallas", "pallas_interpret", "lax"
    kernel_impl: str = "auto"

    @classmethod
    def tiny(cls, **kw):
        for k, v in dict(vocab_size=96, hidden_size=64, num_hidden_layers=2,
                         num_attention_heads=4, num_key_value_heads=2,
                         head_dim=16, max_position_embeddings=256,
                         num_experts=8, num_experts_per_tok=2,
                         moe_intermediate_size=32, indexer_num_heads=2,
                         indexer_head_dim=8, indexer_topk=16).items():
            kw.setdefault(k, v)
        return cls(**kw)


def _layer_norm(x, p, eps=1e-6):
    x32 = x.astype(jnp.float32)
    mu = x32.mean(-1, keepdims=True)
    var = ((x32 - mu) ** 2).mean(-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return (y * p["scale"].astype(jnp.float32)
            + p["bias"].astype(jnp.float32)).astype(x.dtype)


class SparseMoELM:
    def __init__(self, cfg: SparseMoELMConfig):
        self.cfg = cfg

    # -- parameters -------------------------------------------------------

    def init(self, key, dtype=jnp.float32):
        """Seeded parameters, made in ``dtype`` (a served model's experts
        in float32 first would not fit beside themselves)."""
        c = self.cfg
        d, dh = c.hidden_size, c.head_dim
        h, kv = c.num_attention_heads, c.num_key_value_heads
        j, di = c.indexer_num_heads, c.indexer_head_dim
        e, f = c.num_experts, c.moe_intermediate_size

        def normal(k, shape, std=0.02):
            return (std * jax.random.normal(k, shape, jnp.float32)
                    ).astype(dtype)

        ones = lambda n: {"scale": jnp.ones((n,), dtype)}      # noqa: E731
        keys = jax.random.split(key, c.num_hidden_layers + 2)
        layers = {}
        for i in range(c.num_hidden_layers):
            k = jax.random.split(keys[i], 11)
            layers[str(i)] = {
                "attn_norm": ones(d),
                "q_proj": {"weight": normal(k[0], (d, h * dh))},
                "k_proj": {"weight": normal(k[1], (d, kv * dh))},
                "v_proj": {"weight": normal(k[2], (d, kv * dh))},
                "o_proj": {"weight": normal(k[3], (h * dh, d))},
                "q_norm": ones(dh), "k_norm": ones(dh),
                "idx_q": {"weight": normal(k[4], (d, j * di))},
                "idx_k": {"weight": normal(k[5], (d, di))},
                "idx_w": {"weight": normal(k[6], (d, j))},
                "idx_k_norm": {"scale": jnp.ones((di,), dtype),
                               "bias": jnp.zeros((di,), dtype)},
                "ffn_norm": ones(d),
                "router": {"weight": normal(k[7], (d, e))},
                # (E, F, D) each: a block of hidden units is one
                # contiguous piece of every expert's three matrices
                "experts": {"gate": normal(k[8], (e, f, d)),
                            "up": normal(k[9], (e, f, d)),
                            "down": normal(k[10], (e, f, d))},
            }
        return {"embed": {"weight": normal(keys[-2], (c.vocab_size, d))},
                "layers": layers, "final_norm": ones(d),
                "head": {"weight": normal(keys[-1], (c.vocab_size, d))}}

    # -- the block, shared by forward() and the serving program -----------

    def embed(self, params, tokens, positions):
        del positions                       # rotary: applied at q and k
        return params["embed"]["weight"][tokens].astype(jnp.float32)

    def attn_in(self, params, i, x, positions):
        c, lp = self.cfg, params["layers"][str(i)]
        s, n, _ = x.shape
        h, kv, dh = c.num_attention_heads, c.num_key_value_heads, c.head_dim
        a = _rms_norm(x, lp["attn_norm"]["scale"], c.rms_norm_eps).astype(
            lp["q_proj"]["weight"].dtype)
        q = jnp.matmul(a, lp["q_proj"]["weight"]).reshape(s, n, h, dh)
        k = jnp.matmul(a, lp["k_proj"]["weight"]).reshape(s, n, kv, dh)
        v = jnp.matmul(a, lp["v_proj"]["weight"])
        q = _rope(_rms_norm(q, lp["q_norm"]["scale"], c.rms_norm_eps),
                  positions, c.rope_theta)
        k = _rope(_rms_norm(k, lp["k_norm"]["scale"], c.rms_norm_eps),
                  positions, c.rope_theta)
        q_idx = _rope(jnp.matmul(a, lp["idx_q"]["weight"]).reshape(
            s, n, c.indexer_num_heads, c.indexer_head_dim),
            positions, c.rope_theta)
        k_idx = _rope(_layer_norm(jnp.matmul(a, lp["idx_k"]["weight"]),
                                  lp["idx_k_norm"]), positions, c.rope_theta)
        w_idx = jnp.matmul(a, lp["idx_w"]["weight"],
                           preferred_element_type=jnp.float32)
        return (q.transpose(0, 2, 1, 3),
                (k.reshape(s, n, kv * dh), v, k_idx), (q_idx, w_idx))

    def attn_out(self, params, i, x, att):
        lp = params["layers"][str(i)]
        s, n = att.shape[:2]
        w = lp["o_proj"]["weight"]
        return x + jnp.matmul(att.reshape(s, n, -1).astype(w.dtype), w,
                              preferred_element_type=jnp.float32)

    def ffn(self, params, i, x, valid):
        c, lp = self.cfg, params["layers"][str(i)]
        s, n, d = x.shape
        b = _rms_norm(x, lp["ffn_norm"]["scale"], c.rms_norm_eps)
        flat = b.reshape(s * n, d)
        logits = jnp.matmul(flat, lp["router"]["weight"].astype(jnp.float32),
                            precision=_HI)
        probs = jax.nn.softmax(logits, axis=-1)
        top, ids = jax.lax.top_k(probs, c.num_experts_per_tok)
        coef = top / top.sum(-1, keepdims=True) if c.norm_topk_prob else top
        ex = lp["experts"]
        y, sizes = grouped_expert_ffn(
            flat.astype(ex["gate"].dtype), ids.astype(jnp.int32), coef,
            valid.reshape(s * n), ex["gate"], ex["up"], ex["down"],
            impl=c.kernel_impl)
        stats = {"moe_assignments": sizes.sum(),
                 "moe_experts_touched": (sizes > 0).sum(),
                 "moe_expert_slots": c.num_experts,
                 "moe_max_expert_tokens": sizes.max()}
        return x + y.reshape(s, n, d), stats

    def head(self, params, x):
        w = params["head"]["weight"]
        x = _rms_norm(x, params["final_norm"]["scale"], self.cfg.rms_norm_eps)
        return jnp.einsum("...d,vd->...v", x.astype(w.dtype), w,
                          preferred_element_type=jnp.float32)

    # -- whole-sequence pass ------------------------------------------------

    def forward(self, params, ids):
        """(B, S) ids -> (B, S, V) float32 logits: dense causal scores
        with each query's selection as a mask, no cache. The selection
        is the sort's (``lax.top_k``), not the engine's counting kernel:
        the pass the engine is judged by shares no code with it."""
        c = self.cfg
        b, n = ids.shape
        pos = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32), (b, n))
        x = self.embed(params, ids, pos)
        g = c.num_attention_heads // c.num_key_value_heads
        for i in range(c.num_hidden_layers):
            q, (k, v, k_idx), (q_idx, w_idx) = self.attn_in(params, i, x, pos)
            dots = jnp.einsum("bqjd,bkd->bqjk", q_idx, k_idx, precision=_HI,
                              preferred_element_type=jnp.float32)
            scale = (c.indexer_num_heads * c.indexer_head_dim) ** -0.5
            scores = scale * jnp.einsum("bqj,bqjk->bqk", w_idx,
                                        jnp.maximum(dots, 0.0),
                                        precision=_HI)
            keep = selected_by_sort(scores, pos + 1,
                                    min(c.indexer_topk, n)) > 0
            kh = jnp.repeat(k.reshape(b, n, -1, c.head_dim), g, axis=2)
            vh = jnp.repeat(v.reshape(b, n, -1, c.head_dim), g, axis=2)
            att = jnp.einsum("bhqd,bkhd->bhqk", q, kh, precision=_HI,
                             preferred_element_type=jnp.float32)
            att = jnp.where(keep[:, None], att * c.head_dim ** -0.5, NEG_INF)
            att = jax.nn.softmax(att, axis=-1)
            o = jnp.einsum("bhqk,bkhd->bqhd", att, vh.astype(jnp.float32),
                           precision=_HI)
            x = self.attn_out(params, i, x, o)
            x, _ = self.ffn(params, i, x, jnp.ones((b, n), bool))
        return self.head(params, x)

    # -- the paged serving engine's view ------------------------------------

    def serving(self, **unsupported):
        """This model's block as the paged serving engine runs it. It
        shares prompt prefixes and takes none of the engine's other options
        yet (``spec.supports``, so the engine refuses them before asking)."""
        if unsupported:
            raise ValueError(f"SparseMoELM.serving() takes no options yet, "
                             f"got {sorted(unsupported)}")
        return SparseMoEServing(self)


class SparseMoEServing:
    """:mod:`paddle_tpu.serving.program` for :class:`SparseMoELM`: K, V
    and the indexer key cached a token and layer, selection past
    ``indexer_topk`` cached tokens, expert-load counts handed back."""

    def __init__(self, model: SparseMoELM):
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
            extra_rows=(("indexer_key", c.indexer_head_dim),),
            select_topk=c.indexer_topk,
            stats=("moe_assignments", "moe_experts_touched",
                   "moe_expert_slots", "moe_max_expert_tokens"),
            supports=frozenset({"prefix_sharing"}))

    def param_dtype(self, params):
        return params["embed"]["weight"].dtype
