"""Decoder-only language model whose attention runs in a compressed
latent that reads the tokens before it, over a top-1 routed expert layer
whose router carries its state from layer to layer, for the paged serving
engine.

Layer ``l``, token ``t``, float32 stream ``x`` (``D`` wide; ``H`` query
heads over ``G`` KV heads of ``d``, head ``h`` reads KV head ``h // (H /
G)``)::

    u_t  = rms(x_t)
    z_t  = [u_t W_q ; u_t W_k]                    (H + G) d latent channels
    c_t  = b0 + w0[:, 0] z_{t-1} + w0[:, 1] z_t   depthwise, 2 taps
    s_t  = b1 + W1[0] c_{t-1} + W1[1] c_t         2 taps, a d x d block a head
    q_h  = s^q_h + (q~_h + k~_g(h)) / 2           q~, k~: the halves of z_t
    k_g  = s^k_g + (mean_{h in g} q~_h + k~_g) / 2
    q_h  = sqrt(d) q_h / |q_h| ;  k_g = sqrt(d) tau_g k_g / |k_g|
    q, k = rope(q), rope(k)                       first half of each head
    v_t  = [u_t W_v ; u_{t-1} W_vs]               half the V heads a token late
    x'   = a x + b (softmax-attention over k, v) W_o
    w_t  = rms(x'_t)
    r_l  = w_t W_r + b_r + gamma r_{l-1}          the same token, layer before
    p    = softmax(W_3 gelu(W_2 gelu(W_1 rms(r_l))))
    e    = argmax(p + beta) ;  y = p_e expert_e(w_t)        SwiGLU, no capacity
    x''  = c x' + d y

``z_{-1}``, ``c_{-1}`` and ``u_{-1}`` are zeros: each conv pads its own
input. The head is the embedding (tied). What a sequence carries from
token to token beside K and V is, a layer, ``z``, ``c`` and ``u W_vs`` of
its last token: the serving engine keeps them a slot
(``ServingSpec.slot_state``, read by ``attn_in``:
``slot_state_reader``), and ``r_l`` rides from layer to layer beside the
residual stream (``ServingSpec.layer_carry``).

The residual stream, the convs, the norms and the router are float32
whatever the weights' type; projections and experts take operands of the
weights' type. The config's key names are those of the published
``config.json`` of this family (ZAYA1), so a configuration file's
numbers can be passed straight in. ``forward`` is the whole-sequence pass
(dense causal scores, zero tails); ``serving()`` is the same block as the
paged engine runs it.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from paddle_tpu.models.common import (matmul_precision, normal_init, project,
                                      rms_norm, rope)
from paddle_tpu.ops.attention import NEG_INF
from paddle_tpu.ops.grouped_ffn import grouped_expert_ffn, tile_rows
from paddle_tpu.serving.program import ServingSpec

_HI = jax.lax.Precision.HIGHEST
#: what ``init`` draws the router's last matrix with, times
#: ``router_hidden_size ** -0.5``: the spread of its logits
_ROUTER_LOGIT_SCALE = 3.0


@dataclasses.dataclass
class LatentConvMoELMConfig:
    vocab_size: int = 262272
    hidden_size: int = 2048
    num_hidden_layers: int = 40
    num_attention_heads: int = 8
    num_key_value_heads: int = 2
    head_dim: int = 128
    rms_norm_eps: float = 1e-5
    rope_theta: float = 5e6
    partial_rotary_factor: float = 0.5
    max_position_embeddings: int = 131072
    cca_time0: int = 2
    cca_time1: int = 2
    num_experts: int = 16
    num_experts_per_tok: int = 1
    moe_intermediate_size: int = 2048
    router_hidden_size: int = 256
    #: which body the kernels run: "auto" (Pallas on a TPU, XLA
    #: elsewhere), "pallas", "pallas_interpret", "lax"
    kernel_impl: str = "auto"

    def __post_init__(self):
        if (self.cca_time0, self.cca_time1) != (2, 2):
            raise ValueError("only two taps a conv are written: a slot "
                             "keeps one token's tail")
        if self.num_experts_per_tok != 1:
            raise ValueError("the router picks one expert a token")
        if self.num_attention_heads % self.num_key_value_heads \
                or self.num_key_value_heads % 2:
            raise ValueError("query heads in whole groups over an even "
                             "number of KV heads (half of V is shifted)")

    @property
    def latent_channels(self) -> int:
        return (self.num_attention_heads + self.num_key_value_heads) \
            * self.head_dim

    @property
    def rotary_dim(self) -> int:
        return int(self.head_dim * self.partial_rotary_factor)

    @property
    def shifted_value_dim(self) -> int:
        """Lanes of a token's V row that are the token before's."""
        return self.num_key_value_heads // 2 * self.head_dim

    @classmethod
    def tiny(cls, **kw):
        for k, v in dict(vocab_size=96, hidden_size=64, num_hidden_layers=2,
                         num_attention_heads=4, num_key_value_heads=2,
                         head_dim=16, max_position_embeddings=256,
                         num_experts=8, moe_intermediate_size=32,
                         router_hidden_size=16).items():
            kw.setdefault(k, v)
        return cls(**kw)


def _gelu(u):
    return jax.nn.gelu(u, approximate=False)


def _f32(a):
    return a.astype(jnp.float32)


class LatentConvMoELM:
    def __init__(self, cfg: LatentConvMoELMConfig):
        self.cfg = cfg

    # -- parameters -------------------------------------------------------

    def init(self, key, dtype=jnp.float32):
        """Seeded parameters, made in ``dtype``: normal of std 0.02 and
        norms, residual scales and temperatures at 1, but for what the
        block's own mathematics hangs on: the two convs uniform in
        +-1/sqrt(fan in) as torch's ``Conv1d`` draws them (fan in 2 and 2
        ``head_dim``), the router's matrices at unit gain with the last at
        ``_ROUTER_LOGIT_SCALE`` (a trained router is decisive: at 0.02 all
        its probabilities are 1 / ``num_experts`` to three digits) and the
        two after a GELU with columns that sum to zero (ASSUMED: a served
        router is even; a GELU's mean through a plain normal draw gives
        the same few experts a third of every batch), its depth carry
        ``gamma`` uniform in 0.75..1."""
        c = self.cfg
        d, dh, f, e = (c.hidden_size, c.head_dim, c.moe_intermediate_size,
                       c.num_experts)
        h, g, r, ch = (c.num_attention_heads, c.num_key_value_heads,
                       c.router_hidden_size, c.latent_channels)

        def centred(k, shape, std):
            """Normal, then every column's mean over its inputs taken
            off: a GELU's output has a mean, and through a matrix whose
            columns sum to zero that mean moves no unit."""
            w = std * jax.random.normal(k, shape, jnp.float32)
            return (w - w.mean(0, keepdims=True)).astype(dtype)

        def uniform(k, shape, bound, lo=None):
            return jax.random.uniform(
                k, shape, jnp.float32, -bound if lo is None else lo,
                bound).astype(dtype)

        ones = lambda *n: jnp.ones(n, dtype)                    # noqa: E731
        keys = jax.random.split(key, c.num_hidden_layers + 1)
        layers = {}
        for i in range(c.num_hidden_layers):
            k = jax.random.split(keys[i], 19)
            b0, b1 = 2.0 ** -0.5, (2.0 * dh) ** -0.5
            layers[str(i)] = {
                "attn_norm": {"scale": ones(d)},
                "q_proj": {"weight": normal_init(k[0], (d, h * dh), dtype)},
                "k_proj": {"weight": normal_init(k[1], (d, g * dh), dtype)},
                "v_proj": {"weight": normal_init(
                    k[2], (d, g * dh - c.shifted_value_dim), dtype)},
                "v_shift_proj": {"weight": normal_init(
                    k[3], (d, c.shifted_value_dim), dtype)},
                "conv0": {"weight": uniform(k[4], (ch, 2), b0),
                          "bias": uniform(k[5], (ch,), b0)},
                # (tap, head, in, out): a head's d x d block a tap
                "conv1": {"weight": uniform(k[6], (2, h + g, dh, dh), b1),
                          "bias": uniform(k[7], (ch,), b1)},
                "temperature": ones(g),
                "o_proj": {"weight": normal_init(k[8], (h * dh, d), dtype)},
                "attn_residual": {"keep": ones(d), "add": ones(d)},
                "ffn_norm": {"scale": ones(d)},
                "router": {
                    "in_proj": {
                        "weight": normal_init(k[9], (d, r), dtype, d ** -0.5),
                        "bias": normal_init(k[10], (r,), dtype)},
                    "carry_scale": uniform(k[11], (r,), 1.0, lo=0.75),
                    "norm": {"scale": ones(r)},
                    "fc1": {"weight": normal_init(
                        k[12], (r, r), dtype, (2.0 / r) ** 0.5)},
                    "fc2": {"weight": centred(
                        k[13], (r, r), (2.0 / r) ** 0.5)},
                    "out_proj": {"weight": centred(
                        k[14], (r, e), _ROUTER_LOGIT_SCALE * r ** -0.5)},
                    "balance_bias": normal_init(k[15], (e,), dtype)},
                # (E, F, D) each: a block of hidden units is one
                # contiguous piece of every expert's three matrices
                "experts": {"gate": normal_init(k[16], (e, f, d), dtype),
                            "up": normal_init(k[17], (e, f, d), dtype),
                            "down": normal_init(k[18], (e, f, d), dtype)},
                "ffn_residual": {"keep": ones(d), "add": ones(d)},
            }
        return {"embed": {"weight": normal_init(
                    keys[-1], (c.vocab_size, d), dtype)},
                "layers": layers, "final_norm": {"scale": ones(d)}}

    # -- the block, shared by forward() and the serving program -----------

    def embed(self, params, tokens, positions):
        del positions                       # rotary: applied at q and k
        return _f32(params["embed"]["weight"][tokens])

    def attn_in(self, params, i, x, positions, state, rows, fresh, valid):
        """The latent projections of block ``i`` over ``C`` tokens a lane
        (one decode token: ``C`` = 1). ``state`` the pools ``(z tails (R,
        channels), c tails (R, channels), shifted-value tails (R,
        lanes))``, lane ``s`` holding row ``rows[s]`` (0: the null row)
        and starting from zeros where ``fresh[s]``; ``valid`` (S, C) marks
        a lane's real tokens, which come first. Returns (q (S, H, C, d),
        (K rows, V rows) (S, C, G d), None, the pools with every lane's
        row advanced to its last valid token)."""
        c, lp = self.cfg, params["layers"][str(i)]
        s, n, _ = x.shape
        h, g, dh = c.num_attention_heads, c.num_key_value_heads, c.head_dim
        u = rms_norm(x, lp["attn_norm"]["scale"], c.rms_norm_eps)
        z = jnp.concatenate([project(u, lp["q_proj"]["weight"]),
                             project(u, lp["k_proj"]["weight"])], -1)
        v_late = project(u, lp["v_shift_proj"]["weight"])

        def with_tail(pool, now):
            """(S, 1 + C, W): the slot's tail, then the call's tokens."""
            tail = jnp.where((fresh > 0)[:, None], 0.0, _f32(pool[rows]))
            return jnp.concatenate([tail[:, None], now], axis=1)

        n_valid = valid.sum(-1).astype(jnp.int32)

        def advanced(pool, seq):
            """The pool with each lane's row at its last valid token
            (entry ``n_valid`` of ``seq``; the tail itself where none)."""
            last = jnp.take_along_axis(seq, n_valid[:, None, None], axis=1)
            return pool.at[rows].set(last[:, 0].astype(pool.dtype))

        z_pool, c_pool, v_pool = state
        z_seq = with_tail(z_pool, z)
        w0 = _f32(lp["conv0"]["weight"])
        conv = _f32(lp["conv0"]["bias"]) + w0[:, 0] * z_seq[:, :-1] \
            + w0[:, 1] * z_seq[:, 1:]                            # (S,C,CH)
        c_seq = with_tail(c_pool, conv)
        w1 = _f32(lp["conv1"]["weight"])
        heads = c_seq.reshape(s, n + 1, h + g, dh)
        mixed = _f32(lp["conv1"]["bias"]) + sum(
            jnp.einsum("snhi,hio->snho", heads[:, j:j + n], w1[j],
                       precision=_HI) for j in range(2)).reshape(s, n, -1)
        v_seq = with_tail(v_pool, v_late)
        state = (advanced(z_pool, z_seq), advanced(c_pool, c_seq),
                 advanced(v_pool, v_seq))

        zq = z[..., :h * dh].reshape(s, n, g, h // g, dh)
        zk = z[..., h * dh:].reshape(s, n, g, 1, dh)
        q = mixed[..., :h * dh].reshape(zq.shape) + 0.5 * (zq + zk)
        k = mixed[..., h * dh:].reshape(zk.shape) + 0.5 * (
            zq.mean(3, keepdims=True) + zk)

        def unit(a):                        # sqrt(d) a / |a|
            return a * jax.lax.rsqrt(jnp.mean(a * a, -1, keepdims=True))

        q = unit(q).reshape(s, n, h, dh)
        k = unit(k).reshape(s, n, g, dh) * _f32(lp["temperature"])[:, None]
        q = rope(q, positions, c.rope_theta, c.rotary_dim).astype(
            lp["q_proj"]["weight"].dtype)
        k = rope(k, positions, c.rope_theta, c.rotary_dim)
        v = jnp.concatenate([project(u, lp["v_proj"]["weight"]),
                             v_seq[:, :-1]], -1)
        return (q.transpose(0, 2, 1, 3), (k.reshape(s, n, g * dh), v), None,
                state)

    def attn_out(self, params, i, x, att):
        lp = params["layers"][str(i)]
        s, n = att.shape[:2]
        res = lp["attn_residual"]
        return _f32(res["keep"]) * x + _f32(res["add"]) * project(
            att.reshape(s, n, -1), lp["o_proj"]["weight"])

    def ffn(self, params, i, x, valid, carry):
        """The routed half of block ``i``; ``carry`` = (the router
        representation the same tokens left the layer before with (S, C,
        R),), zeros into the first layer. Returns (x, counts, carry)."""
        c, lp = self.cfg, params["layers"][str(i)]
        s, n, d = x.shape
        rp = lp["router"]
        w = rms_norm(x, lp["ffn_norm"]["scale"], c.rms_norm_eps)
        flat = w.reshape(s * n, d)

        def dense(a, name):
            return jnp.matmul(a, _f32(rp[name]["weight"]), precision=_HI)

        r = dense(flat, "in_proj") + _f32(rp["in_proj"]["bias"]) \
            + _f32(rp["carry_scale"]) * carry[0].reshape(s * n, -1)
        hidden = rms_norm(r, rp["norm"]["scale"], c.rms_norm_eps)
        hidden = _gelu(dense(_gelu(dense(hidden, "fc1")), "fc2"))
        probs = jax.nn.softmax(dense(hidden, "out_proj"), axis=-1)
        ids = jnp.argmax(probs + _f32(rp["balance_bias"]), -1)[:, None]
        coef = jnp.take_along_axis(probs, ids, axis=-1)
        ex = lp["experts"]
        y, sizes = grouped_expert_ffn(
            flat.astype(ex["gate"].dtype), ids.astype(jnp.int32), coef,
            valid.reshape(s * n), ex["gate"], ex["up"], ex["down"],
            impl=c.kernel_impl)
        tm = tile_rows(s * n, c.num_experts)
        stats = {"moe_assignments": sizes.sum(),
                 "moe_experts_touched": (sizes > 0).sum(),
                 "moe_expert_slots": c.num_experts,
                 "moe_max_expert_tokens": sizes.max(),
                 "moe_tile_rows": (-(-sizes // tm)).sum() * tm}
        res = lp["ffn_residual"]
        x = _f32(res["keep"]) * x + _f32(res["add"]) * y.reshape(s, n, d)
        return x, stats, (r.reshape(s, n, -1),)

    def head(self, params, x):
        w = params["embed"]["weight"]                   # tied
        x = rms_norm(x, params["final_norm"]["scale"], self.cfg.rms_norm_eps)
        return jnp.einsum("...d,vd->...v", x.astype(w.dtype), w,
                          precision=matmul_precision(w.dtype),
                          preferred_element_type=jnp.float32)

    def slot_state(self):
        """``ServingSpec.slot_state`` of one layer: the tails of the
        slot's last cached token."""
        c = self.cfg
        return (("z_tail", (c.latent_channels,)),
                ("conv_tail", (c.latent_channels,)),
                ("value_tail", (c.shifted_value_dim,)))

    def layer_carry(self):
        return (("router_state", self.cfg.router_hidden_size),)

    # -- whole-sequence pass ------------------------------------------------

    def forward(self, params, ids):
        """(B, S) ids -> (B, S, V) float32 logits: dense causal scores,
        zero tails, no cache."""
        c = self.cfg
        b, n = ids.shape
        pos = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32), (b, n))
        valid = jnp.ones((b, n), bool)
        x = self.embed(params, ids, pos)
        group = c.num_attention_heads // c.num_key_value_heads
        rows = jnp.arange(1, b + 1, dtype=jnp.int32)
        fresh = jnp.ones((b,), jnp.int32)
        state = tuple(jnp.zeros((b + 1,) + shape, jnp.float32)
                      for _name, shape in self.slot_state())
        causal = jnp.tril(jnp.ones((n, n), bool))
        carry = tuple(jnp.zeros((b, n, width), jnp.float32)
                      for _name, width in self.layer_carry())
        for i in range(c.num_hidden_layers):
            q, (k, v), _, _ = self.attn_in(params, i, x, pos, state, rows,
                                           fresh, valid)
            kh = jnp.repeat(k.reshape(b, n, -1, c.head_dim), group, axis=2)
            vh = jnp.repeat(v.reshape(b, n, -1, c.head_dim), group, axis=2)
            att = jnp.einsum("bhqd,bkhd->bhqk", _f32(q), kh, precision=_HI)
            att = jax.nn.softmax(jnp.where(
                causal, att * c.head_dim ** -0.5, NEG_INF), axis=-1)
            o = jnp.einsum("bhqk,bkhd->bqhd", att, vh, precision=_HI)
            x = self.attn_out(params, i, x, o)
            x, _, carry = self.ffn(params, i, x, valid, carry)
        return self.head(params, x)

    # -- the paged serving engine's view ------------------------------------

    def serving(self, **unsupported):
        """This model's block as the paged serving engine runs it. It
        takes none of the engine's options yet (``spec.supports`` is
        empty, so the engine refuses them before asking)."""
        if unsupported:
            raise ValueError(f"LatentConvMoELM.serving() takes no options "
                             f"yet, got {sorted(unsupported)}")
        return LatentConvMoEServing(self)


class LatentConvMoEServing:
    """:mod:`paddle_tpu.serving.program` for :class:`LatentConvMoELM`: K
    and V cached a token and layer in the latent's widths, the conv and
    value tails kept a slot and layer and read by ``attn_in``, the
    router's representation carried from layer to layer. Every option
    that snapshots, shares, ships or speculates reads K and V only and
    would lose the tails, so ``supports`` is empty."""

    def __init__(self, model: LatentConvMoELM):
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
            stats=("moe_assignments", "moe_experts_touched",
                   "moe_expert_slots", "moe_max_expert_tokens",
                   "moe_tile_rows"),
            slot_state=model.slot_state(),
            slot_state_reader="attn_in",
            layer_carry=model.layer_carry(),
            supports=frozenset())

    def param_dtype(self, params):
        return params["embed"]["weight"].dtype
