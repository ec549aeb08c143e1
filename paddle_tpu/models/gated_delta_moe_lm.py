"""Decoder-only language model whose layers are gated delta-rule
linear-attention layers with one gated full-attention layer in every
``full_attention_interval``, each over a softmax-routed expert layer with
a gated shared expert, of which a chip may hold a share, for the paged
serving engine.

Float32 stream ``x`` (``D`` wide). ``rms1(x; w) = x / sqrt(mean(x^2) +
eps) * (1 + w)`` (the family's zero-centred weight) for the two layer
norms, the final norm and the per-head q and k norms; ``rmsg(o, z; w) = w
* o / sqrt(mean(o^2) + eps) * silu(z)`` (the weight as it is) for the
linear layer's output. A layer is ``x' = x + mix(rms1(x; w_1))``, ``x'' =
x' + moe(rms1(x'; w_2))``.

Linear layer (``Hk`` key heads and ``Hv`` value heads of ``dk`` / ``dv``,
``r = Hv / Hk``; value head ``i`` reads key head ``i // r``), ``h`` the
normed input::

    [q | k | v | z] = h W_qkvz        a key head: q, k (dk each), v, z (r dv)
    [b | a]         = h W_ba          a key head: r values each
    u    = silu(conv(q | k | v))      depthwise causal, ``taps`` taps, no
                                      bias, all q, then all k, then all v
    q~_i = l2(u_q[i // r]) / sqrt(dk),  k~_i = l2(u_k[i // r])
    beta = sigmoid(b),   g = -exp(A_log) softplus(a + dt_bias)   float32
    S_i <- exp(g_i) S_i ;  d = beta_i (v_i - S_i^T k~_i) ;  S_i <- S_i + k~_i (x) d
    o_i  = S_i^T q~_i                 S_i (dk, dv), zeros at position 0
    mix  = concat_i rmsg(o_i, z_i; w_n) W_out

What a sequence carries from token to token is, a layer, the last ``taps -
1`` conv inputs and ``S``: a fixed size whatever the length, kept by the
serving engine in a pool row a slot (``ServingSpec.slot_state``). Such a
layer caches NO rows a token: it is a **state layer**
(``ServingSpec.state_layers``), its ``mixer`` the block's only token mixer.

Full layer (``H`` query heads over ``G`` KV heads of ``d``)::

    [q_i | gate_i] = (h W_q)_i
    q_i = rope(rms1(q_i; w_qn)),  k_j = rope(rms1((h W_k)_j; w_kn)),  v_j
    mix = concat_i (softmax_causal(q_i k^T / sqrt(d)) v * sigmoid(gate_i)) W_o

with the rotary embedding over the first ``d x partial_rotary_factor``
entries of a head (rotate-half pairing inside them).

Experts: ``p = softmax(h' W_r)`` over all the routed experts in float32,
the ``K`` largest, ``c_e = p_e / sum_top p`` (``norm_topk_prob``); ``moe =
sum_{e held here} c_e E_e(h') + sigmoid(h' . w_sg) E_shared(h')``, every
``E`` SwiGLU. The router is as wide as the model's routed experts
(``num_routed_experts``); the layer holds ``num_experts`` of them from
``expert_offset`` on and computes their part of the sum (the shared expert
is whole on every chip). Untied head over the rows of the vocabulary held
here.

The parameter tree's names are the published module's (``linear_attn.
in_proj_qkvz``, ``self_attn.q_proj``, ``mlp.shared_expert_gate`` ...) and
the config's keys those of the published ``config.json`` (Qwen3-Next).
Departures in LAYOUT, none in mathematics: a weight is ``(in, out)``; the
experts are three arrays ``(E, F, D)``; ``q_proj``'s columns are every
head's query and then every head's gate (the published matrix interleaves
them a head), so that ``attn_in`` and ``attn_out`` each multiply their
half; the conv's weight is ``(channels, taps)``; a head's state tile is
``(dk, dv)``. ``forward`` is the whole-sequence pass (dense causal scores,
the recurrence from a zero state); ``serving()`` is the same block as the
paged engine runs it.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from paddle_tpu.models.common import (matmul_precision, normal_init, project,
                                      rms_norm, rope)
from paddle_tpu.ops.attention import NEG_INF
from paddle_tpu.ops.gated_delta import (DELTA_TILE, gated_delta_chunk_scan,
                                        gated_delta_decode_update)
from paddle_tpu.ops.grouped_ffn import (grouped_expert_ffn, held_pairs,
                                        tile_rows)
from paddle_tpu.serving.program import ServingSpec

_HI = jax.lax.Precision.HIGHEST

_STATS = ("moe_routed_pairs", "moe_assignments", "moe_experts_touched",
          "moe_expert_slots", "moe_max_expert_tokens", "moe_tile_rows")


@dataclasses.dataclass
class GatedDeltaMoELMConfig:
    vocab_size: int = 151936
    hidden_size: int = 2048
    num_hidden_layers: int = 48
    num_attention_heads: int = 16
    num_key_value_heads: int = 2
    head_dim: int = 256
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1e7
    partial_rotary_factor: float = 0.25
    max_position_embeddings: int = 262144
    #: layer ``i`` is a full-attention layer where ``(i + 1) %
    #: full_attention_interval == 0`` and a linear layer otherwise
    full_attention_interval: int = 4
    linear_conv_kernel_dim: int = 4
    linear_key_head_dim: int = 128
    linear_value_head_dim: int = 128
    linear_num_key_heads: int = 16
    linear_num_value_heads: int = 32
    #: routed experts held here, of ``num_routed_experts`` (None: all of
    #: them) from ``expert_offset`` on
    num_experts: int = 512
    num_routed_experts: Optional[int] = None
    expert_offset: int = 0
    num_experts_per_tok: int = 10
    moe_intermediate_size: int = 512
    shared_expert_intermediate_size: int = 512
    norm_topk_prob: bool = True
    #: what ``init`` draws the heads' time scales from: ``A`` uniform and
    #: ``dt`` log-uniform in these ranges (``dt_bias`` its inverse softplus)
    a_init_range: Tuple[float, float] = (1.0, 16.0)
    dt_init_range: Tuple[float, float] = (1e-3, 1e-1)
    #: which body the kernels run: "auto" (Pallas on a TPU, XLA
    #: elsewhere), "pallas", "pallas_interpret", "lax"
    kernel_impl: str = "auto"

    def __post_init__(self):
        if self.num_routed_experts is None:
            self.num_routed_experts = self.num_experts
        if not 0 <= self.expert_offset <= \
                self.num_routed_experts - self.num_experts:
            raise ValueError("the experts held lie within the router's")
        if self.linear_num_value_heads % self.linear_num_key_heads \
                or self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("value heads in whole groups over the key "
                             "heads, query heads over the KV heads")
        if self.full_attention_interval < 1:
            raise ValueError("full_attention_interval is at least 1")

    @property
    def state_layers(self) -> Tuple[bool, ...]:
        """Which layers are linear layers: state a slot, no rows a token."""
        return tuple((i + 1) % self.full_attention_interval != 0
                     for i in range(self.num_hidden_layers))

    @property
    def key_dim(self) -> int:
        return self.linear_num_key_heads * self.linear_key_head_dim

    @property
    def value_dim(self) -> int:
        return self.linear_num_value_heads * self.linear_value_head_dim

    @property
    def conv_dim(self) -> int:
        return 2 * self.key_dim + self.value_dim

    @property
    def rotary_dim(self) -> int:
        return int(self.head_dim * self.partial_rotary_factor)

    @property
    def holds_all_experts(self) -> bool:
        return self.num_experts == self.num_routed_experts

    @classmethod
    def tiny(cls, **kw):
        for k, v in dict(vocab_size=96, hidden_size=64, num_hidden_layers=4,
                         num_attention_heads=4, num_key_value_heads=2,
                         head_dim=16, max_position_embeddings=512,
                         linear_key_head_dim=16, linear_value_head_dim=16,
                         linear_num_key_heads=2, linear_num_value_heads=4,
                         num_experts=2, num_routed_experts=8,
                         num_experts_per_tok=3, moe_intermediate_size=32,
                         shared_expert_intermediate_size=32).items():
            kw.setdefault(k, v)
        return cls(**kw)


def _f32(a):
    return a.astype(jnp.float32)


def _rms1(x, w, eps):
    """The family's zero-centred norm: the scale is ``1 + w``."""
    return rms_norm(x, 1.0 + _f32(w), eps)


def _l2(y):
    return y * jax.lax.rsqrt(jnp.sum(y * y, -1, keepdims=True) + 1e-6)


def _swiglu(h, p):
    """``(silu(h W_g) * (h W_u)) W_d``, operands of the weights' type."""
    gate = project(h, p["gate_proj"]["weight"])
    up = project(h, p["up_proj"]["weight"])
    return project(jax.nn.silu(gate) * up, p["down_proj"]["weight"])


class GatedDeltaMoELM:
    def __init__(self, cfg: GatedDeltaMoELMConfig):
        self.cfg = cfg

    # -- parameters -------------------------------------------------------

    def init(self, key, dtype=jnp.float32):
        """Seeded parameters, made in ``dtype``: normal of std 0.02, the
        zero-centred norms at 0 and the linear layer's output norm at 1,
        the depthwise conv uniform in +-1/sqrt(taps) (torch ``Conv1d``'s
        own draw). What the recurrence's time scales hang on is float32
        and drawn as the gated delta rule's reference layer draws it:
        ``A`` uniform in ``a_init_range``, ``dt`` log-uniform in
        ``dt_init_range`` kept as ``dt_bias``, its inverse softplus. (The
        published module's own ``dt_bias`` of ones with ``A`` in 0..16
        decays a seeded state by ``exp(-10)`` a token: nothing would be
        carried, and no comparison would see a lost state.)"""
        c = self.cfg
        d, f = c.hidden_size, c.moe_intermediate_size
        h, g, dh = c.num_attention_heads, c.num_key_value_heads, c.head_dim
        hk, hv = c.linear_num_key_heads, c.linear_num_value_heads
        e, taps = c.num_experts, c.linear_conv_kernel_dim
        zeros = lambda n: {"weight": jnp.zeros((n,), dtype)}    # noqa: E731
        lin = lambda k, a, b: {"weight": normal_init(k, (a, b), dtype)}  # noqa

        keys = jax.random.split(key, c.num_hidden_layers + 3)
        layers = {}
        for i in range(c.num_hidden_layers):
            k = jax.random.split(keys[i], 16)
            lp = {"input_layernorm": zeros(d),
                  "post_attention_layernorm": zeros(d)}
            if c.state_layers[i]:
                dt = jnp.exp(jax.random.uniform(
                    k[0], (hv,), jnp.float32, *jnp.log(jnp.asarray(
                        c.dt_init_range, jnp.float32))))
                bound = taps ** -0.5
                lp["linear_attn"] = {
                    "in_proj_qkvz": lin(k[1], d, 2 * c.key_dim
                                        + 2 * c.value_dim),
                    "in_proj_ba": lin(k[2], d, 2 * hv),
                    "conv1d": {"weight": jax.random.uniform(
                        k[3], (c.conv_dim, taps), jnp.float32, -bound,
                        bound).astype(dtype)},
                    # float32 whatever the weights' type: a head's time scale
                    "A_log": jnp.log(jax.random.uniform(
                        k[4], (hv,), jnp.float32, *c.a_init_range)),
                    "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                    "norm": {"weight": jnp.ones(
                        (c.linear_value_head_dim,), dtype)},
                    "out_proj": lin(k[5], c.value_dim, d)}
            else:
                lp["self_attn"] = {
                    "q_proj": lin(k[6], d, 2 * h * dh),
                    "k_proj": lin(k[7], d, g * dh),
                    "v_proj": lin(k[8], d, g * dh),
                    "o_proj": lin(k[9], h * dh, d),
                    "q_norm": zeros(dh), "k_norm": zeros(dh)}
            fs = c.shared_expert_intermediate_size
            lp["mlp"] = {
                "gate": lin(k[10], d, c.num_routed_experts),
                # (E, F, D) each: a block of hidden units is one
                # contiguous piece of every expert's three matrices
                "experts": {
                    "gate": normal_init(k[11], (e, f, d), dtype),
                    "up": normal_init(k[12], (e, f, d), dtype),
                    "down": normal_init(k[13], (e, f, d), dtype)},
                "shared_expert": {
                    "gate_proj": lin(jax.random.fold_in(k[14], 0), d, fs),
                    "up_proj": lin(jax.random.fold_in(k[14], 1), d, fs),
                    "down_proj": lin(jax.random.fold_in(k[14], 2), fs, d)},
                "shared_expert_gate": lin(k[15], d, 1)}
            layers[str(i)] = lp
        return {"embed_tokens": {"weight": normal_init(
                    keys[-3], (c.vocab_size, d), dtype)},
                "layers": layers, "norm": zeros(d),
                "lm_head": {"weight": normal_init(
                    keys[-2], (c.vocab_size, d), dtype)}}

    # -- the block, shared by forward() and the serving program -----------

    def embed(self, params, tokens, positions):
        del positions                       # rotary: applied at q and k
        return _f32(params["embed_tokens"]["weight"][tokens])

    def _normed(self, params, i, x):
        return _rms1(x, params["layers"][str(i)]["input_layernorm"]["weight"],
                     self.cfg.rms_norm_eps)

    def attn_in(self, params, i, x, positions):
        """A full layer's -> (q (S, H, C, d), (K rows, V rows (S, C, G
        d)), None)."""
        c, ap = self.cfg, params["layers"][str(i)]["self_attn"]
        s, n, _ = x.shape
        h, g, dh = c.num_attention_heads, c.num_key_value_heads, c.head_dim
        a = self._normed(params, i, x)
        wq = ap["q_proj"]["weight"]
        q = project(a, wq[:, :h * dh]).reshape(s, n, h, dh)
        k = project(a, ap["k_proj"]["weight"]).reshape(s, n, g, dh)
        v = project(a, ap["v_proj"]["weight"])
        q = _rms1(q, ap["q_norm"]["weight"], c.rms_norm_eps)
        k = _rms1(k, ap["k_norm"]["weight"], c.rms_norm_eps)
        q = rope(q, positions, c.rope_theta, c.rotary_dim).astype(wq.dtype)
        k = rope(k, positions, c.rope_theta, c.rotary_dim)
        return q.transpose(0, 2, 1, 3), (k.reshape(s, n, g * dh), v), None

    def attn_out(self, params, i, x, att):
        """A full layer's heads through their output gate (a sigmoid of
        the second half of ``q_proj`` over the layer's normed input) and
        the output projection."""
        c, ap = self.cfg, params["layers"][str(i)]["self_attn"]
        s, n = att.shape[:2]
        hd = c.num_attention_heads * c.head_dim
        gate = project(self._normed(params, i, x),
                       ap["q_proj"]["weight"][:, hd:])
        return x + project(_f32(att).reshape(s, n, hd)
                           * jax.nn.sigmoid(gate), ap["o_proj"]["weight"])

    def mixer(self, params, i, x, state, rows, fresh, valid):
        """Linear layer ``i`` over ``C`` tokens a lane (one decode token:
        ``C`` = 1): ``x`` (S, C, D) the block's input, ``state`` the pools
        ``(conv windows (R, (taps - 1) * channels), head states (R, Hv,
        dk, dv))``, lane ``s`` holding row ``rows[s]`` (0: the null row)
        and starting from zeros where ``fresh[s]``; ``valid`` (S, C) marks
        a lane's real tokens, which come first. Returns (output (S, C, D)
        float32 to add to the residual stream, the pools with every
        lane's row advanced past its valid tokens)."""
        c, lp = self.cfg, params["layers"][str(i)]["linear_attn"]
        conv_pool, s_pool = state
        s, n, _ = x.shape
        hk, hv = c.linear_num_key_heads, c.linear_num_value_heads
        dk, dv, r = c.linear_key_head_dim, c.linear_value_head_dim, hv // hk
        taps = c.linear_conv_kernel_dim
        h = self._normed(params, i, x)
        qkvz = project(h, lp["in_proj_qkvz"]["weight"]).reshape(
            s, n, hk, 2 * dk + 2 * r * dv)
        ba = project(h, lp["in_proj_ba"]["weight"]).reshape(s, n, hk, 2 * r)
        z = qkvz[..., 2 * dk + r * dv:].reshape(s, n, hv, dv)
        b, a = ba[..., :r].reshape(s, n, hv), ba[..., r:].reshape(s, n, hv)
        # all q, then all k, then all v: the conv's channels
        mixed = jnp.concatenate([
            qkvz[..., :dk].reshape(s, n, -1),
            qkvz[..., dk:2 * dk].reshape(s, n, -1),
            qkvz[..., 2 * dk:2 * dk + r * dv].reshape(s, n, -1)], -1)
        # depthwise causal conv over the window the slot kept and the
        # chunk; the window it keeps next: its last taps-1 valid inputs
        window = jnp.where((fresh > 0)[:, None, None], 0.0, _f32(conv_pool[
            rows]).reshape(s, taps - 1, c.conv_dim))
        seq = jnp.concatenate([window, mixed], axis=1)      # (S,taps-1+C,CH)
        w = _f32(lp["conv1d"]["weight"])
        conv = sum(w[:, j] * seq[:, j:j + n] for j in range(taps))
        n_valid = valid.sum(-1).astype(jnp.int32)
        keep = n_valid[:, None] + jnp.arange(taps - 1, dtype=jnp.int32)
        conv_pool = conv_pool.at[rows].set(jnp.take_along_axis(
            seq, keep[:, :, None], axis=1).reshape(s, -1).astype(
                conv_pool.dtype))
        u = conv * jax.nn.sigmoid(conv)
        q = _l2(u[..., :c.key_dim].reshape(s, n, hk, dk)) * dk ** -0.5
        k = _l2(u[..., c.key_dim:2 * c.key_dim].reshape(s, n, hk, dk))
        v = u[..., 2 * c.key_dim:].reshape(s, n, hv, dv)
        live = valid[..., None]
        beta = jnp.where(live, jax.nn.sigmoid(b), 0.0)
        g = jnp.where(live, -jnp.exp(lp["A_log"])
                      * jax.nn.softplus(a + lp["dt_bias"]), 0.0)
        if n == 1:
            o, s_pool = gated_delta_decode_update(
                q[:, 0], k[:, 0], v[:, 0], jnp.exp(g[:, 0]), beta[:, 0],
                s_pool, jnp.where(valid[:, 0], rows, 0), impl=c.kernel_impl)
            o = o[:, None]
        else:
            o, s_pool = gated_delta_chunk_scan(
                q, k, v, g, beta, s_pool, rows, fresh, impl=c.kernel_impl)
        o = rms_norm(o, lp["norm"]["weight"], c.rms_norm_eps) \
            * (z * jax.nn.sigmoid(z))
        return project(o.reshape(s, n, hv * dv), lp["out_proj"]["weight"]), \
            (conv_pool, s_pool)

    def route(self, params, i, flat):
        """The router of layer ``i`` over ``flat`` (T, D) float32: -> (ids
        (T, K) int32 of all the routed experts, weights (T, K) float32)."""
        c, mp = self.cfg, params["layers"][str(i)]["mlp"]
        prob = jax.nn.softmax(jnp.matmul(flat, _f32(mp["gate"]["weight"]),
                                         precision=_HI), axis=-1)
        top, ids = jax.lax.top_k(prob, c.num_experts_per_tok)
        if c.norm_topk_prob:
            top = top / top.sum(-1, keepdims=True)
        return ids.astype(jnp.int32), top

    def ffn(self, params, i, x, valid):
        c, lp = self.cfg, params["layers"][str(i)]
        mp = lp["mlp"]
        s, n, d = x.shape
        b = _rms1(x, lp["post_attention_layernorm"]["weight"],
                  c.rms_norm_eps)
        flat = b.reshape(s * n, d)
        ids, coef = self.route(params, i, flat)
        ex = mp["experts"]
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
        shared = jax.nn.sigmoid(project(
            b, mp["shared_expert_gate"]["weight"])) \
            * _swiglu(b, mp["shared_expert"])
        return x + y.reshape(s, n, d) + shared, stats

    def head(self, params, x):
        w = params["lm_head"]["weight"]
        x = _rms1(x, params["norm"]["weight"], self.cfg.rms_norm_eps)
        return jnp.einsum("...d,vd->...v", x.astype(w.dtype), w,
                          precision=matmul_precision(w.dtype),
                          preferred_element_type=jnp.float32)

    def slot_state(self):
        """``ServingSpec.slot_state`` of one linear layer."""
        c = self.cfg
        # the window's taps folded into one lane-dense row a slot (a
        # second-minor axis of 3 the chip would pad or re-lay out)
        return (("conv_window", ((c.linear_conv_kernel_dim - 1)
                                 * c.conv_dim,)),
                ("delta_state", (c.linear_num_value_heads,
                                 c.linear_key_head_dim,
                                 c.linear_value_head_dim)))

    # -- whole-sequence pass ------------------------------------------------

    def forward(self, params, ids):
        """(B, S) ids -> (B, S, V) float32 logits: dense causal scores,
        the recurrence from a zero state, no cache."""
        c = self.cfg
        b, n = ids.shape
        pad = -n % DELTA_TILE if n > DELTA_TILE else 0
        ids = jnp.pad(ids, ((0, 0), (0, pad)))
        m = n + pad
        pos = jnp.broadcast_to(jnp.arange(m, dtype=jnp.int32), (b, m))
        valid = pos < n
        x = self.embed(params, ids, pos)
        group = c.num_attention_heads // c.num_key_value_heads
        rows = jnp.arange(1, b + 1, dtype=jnp.int32)
        fresh = jnp.ones((b,), jnp.int32)
        causal = jnp.tril(jnp.ones((m, m), bool))
        for i, is_state in enumerate(c.state_layers):
            if is_state:
                state = tuple(jnp.zeros((b + 1,) + shape, jnp.float32)
                              for _name, shape in self.slot_state())
                mixed, _ = self.mixer(params, i, x, state, rows, fresh,
                                      valid)
                x = x + mixed
            else:
                q, (k, v), _ = self.attn_in(params, i, x, pos)
                kh = jnp.repeat(k.reshape(b, m, -1, c.head_dim), group,
                                axis=2)
                vh = jnp.repeat(v.reshape(b, m, -1, c.head_dim), group,
                                axis=2)
                att = jnp.einsum("bhqd,bkhd->bhqk", _f32(q), _f32(kh),
                                 precision=_HI)
                att = jax.nn.softmax(jnp.where(
                    causal, att * c.head_dim ** -0.5, NEG_INF), axis=-1)
                o = jnp.einsum("bhqk,bkhd->bqhd", att, _f32(vh),
                               precision=_HI)
                x = self.attn_out(params, i, x, o)
            x, _ = self.ffn(params, i, x, valid)
        return self.head(params, x)[:, :n]

    # -- the paged serving engine's view ------------------------------------

    def serving(self, **unsupported):
        """This model's block as the paged serving engine runs it. It
        takes none of the engine's options yet (``spec.supports`` is
        empty, so the engine refuses them before asking)."""
        if unsupported:
            raise ValueError(f"GatedDeltaMoELM.serving() takes no options "
                             f"yet, got {sorted(unsupported)}")
        return GatedDeltaMoEServing(self)


class GatedDeltaMoEServing:
    """:mod:`paddle_tpu.serving.program` for :class:`GatedDeltaMoELM`: a
    linear layer keeps a conv window and the heads' states a slot and
    caches no rows (``state_layers``: its ``mixer`` is the block's token
    mixer and no page is its), a full layer caches K and V a token and
    keeps no state; the expert share's counts handed back. Nothing that
    snapshots, shares, ships or speculates reads the state yet, so
    ``supports`` is empty."""

    def __init__(self, model: GatedDeltaMoELM):
        c = model.cfg
        self.model = model
        self.embed, self.attn_in = model.embed, model.attn_in
        self.attn_out, self.ffn, self.head = (model.attn_out, model.ffn,
                                              model.head)
        self.mixer = model.mixer
        self.spec = ServingSpec(
            num_layers=c.num_hidden_layers,
            num_heads=c.num_attention_heads,
            kv_heads=c.num_key_value_heads, head_dim=c.head_dim,
            vocab_size=c.vocab_size,
            max_position=c.max_position_embeddings, stats=_STATS,
            slot_state=model.slot_state(),
            slot_state_dtype="float32",     # the published recurrence's
            state_layers=c.state_layers, supports=frozenset())

    def param_dtype(self, params):
        return params["embed_tokens"]["weight"].dtype
