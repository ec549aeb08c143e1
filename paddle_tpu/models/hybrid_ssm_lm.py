"""Decoder-only language model whose every block runs grouped-query
attention AND a selective state-space mixer side by side, for the paged
serving engine.

A block feeds ONE normed input to both and adds both outputs to the
residual stream, then a SwiGLU MLP::

    u  = rms(x)
    x' = x + attn(u * attention_in_multiplier) * attention_out_multiplier
           + mixer(u * ssm_in_multiplier) * ssm_out_multiplier
    x''= x' + mlp(rms(x'))

Attention: rotary positions over the whole head (rotate-half pairing),
keys scaled by ``key_multiplier``, no bias. The mixer is the Mamba-2
form: one input projection to gate ``z``, conv input ``x|B|C`` and step
``dt`` (each part scaled by its ``ssm_multipliers`` entry), a depthwise
causal conv of ``mamba_d_conv`` taps with bias and SiLU, the recurrence
``S_t = exp(-dt_t e^{A_log}) S_{t-1} + dt_t x_t (x) B_t``, ``y_t = S_t C_t
+ D x_t`` per head (``mamba_n_groups`` groups share ``B`` and ``C``), the
gate ``y * silu(z)``, an RMSNorm over each group's channels, the output
projection. What a sequence carries from token to token is, a layer, the
last ``mamba_d_conv - 1`` conv inputs and the heads' states: a fixed size
whatever the length, kept by the serving engine in a pool row a slot
(``ServingSpec.slot_state``) beside the KV pages.

The residual stream is float32 whatever the weights' type, as in
``sparse_moe_lm``; projections take operands of the weights' type; the
recurrence, its decay and the conv are float32.

The config's key names are those of the published ``config.json`` files
of this family (Falcon-H1), so a configuration file's numbers can be
passed straight in. ``forward`` is the whole-sequence pass (dense causal
scores, the recurrence from a zero state); ``serving()`` is the same
block as the paged engine runs it.
"""

from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.models.common import matmul_precision as _precision
from paddle_tpu.models.common import normal_init as _normal
from paddle_tpu.models.common import project as _project, rms_norm, rope
from paddle_tpu.ops.attention import NEG_INF
from paddle_tpu.ops.ssm_scan import (SCAN_TILE, ssd_chunk_scan,
                                     ssm_decode_update)
from paddle_tpu.serving.program import ServingSpec

_HI = jax.lax.Precision.HIGHEST


@dataclasses.dataclass
class HybridSSMLMConfig:
    vocab_size: int = 261120
    hidden_size: int = 5120
    num_hidden_layers: int = 72
    num_attention_heads: int = 20
    num_key_value_heads: int = 4
    head_dim: int = 128
    intermediate_size: int = 21504
    rms_norm_eps: float = 1e-5
    rope_theta: float = 1e11
    max_position_embeddings: int = 262144
    mamba_d_ssm: int = 4096
    mamba_n_heads: int = 32
    mamba_d_head: int = 128
    mamba_n_groups: int = 2
    mamba_d_state: int = 256
    mamba_d_conv: int = 4
    mamba_chunk_size: int = 128
    mamba_conv_bias: bool = True
    mamba_rms_norm: bool = True
    mamba_norm_before_gate: bool = False
    embedding_multiplier: float = 5.656854249492381
    lm_head_multiplier: float = 0.0078125
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 0.0375
    key_multiplier: float = 0.011048543456039804
    ssm_in_multiplier: float = 0.25
    ssm_out_multiplier: float = 0.08838834764831845
    ssm_multipliers: Tuple[float, ...] = (
        0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
        0.3535533905932738)
    mlp_multipliers: Tuple[float, ...] = (0.1767766952966369,
                                          0.011160714285714284)
    #: what ``init`` draws the heads' time scales from: ``A`` uniform and
    #: ``dt`` log-uniform in these ranges (the Mamba-2 module's own)
    a_init_range: Tuple[float, float] = (1.0, 16.0)
    dt_init_range: Tuple[float, float] = (1e-3, 1e-1)
    #: type of the per-slot recurrent state and conv window when served
    state_dtype: str = "float32"
    #: which body the kernels run: "auto" (Pallas on a TPU, XLA
    #: elsewhere), "pallas", "pallas_interpret", "lax"
    kernel_impl: str = "auto"

    def __post_init__(self):
        if self.mamba_n_heads * self.mamba_d_head != self.mamba_d_ssm:
            raise ValueError("mamba_n_heads * mamba_d_head must be "
                             "mamba_d_ssm")
        if self.mamba_chunk_size != SCAN_TILE:
            raise ValueError(f"the scan's tile is {SCAN_TILE} tokens")
        if not (self.mamba_rms_norm and not self.mamba_norm_before_gate
                and self.mamba_conv_bias):
            raise ValueError("only the gate-then-grouped-norm mixer with a "
                             "conv bias is written")

    @property
    def conv_dim(self) -> int:
        return self.mamba_d_ssm + 2 * self.mamba_n_groups * self.mamba_d_state

    @classmethod
    def tiny(cls, **kw):
        for k, v in dict(vocab_size=96, hidden_size=64, num_hidden_layers=2,
                         num_attention_heads=4, num_key_value_heads=2,
                         head_dim=16, intermediate_size=96,
                         max_position_embeddings=512, mamba_d_ssm=64,
                         mamba_n_heads=4, mamba_d_head=16, mamba_n_groups=2,
                         mamba_d_state=16).items():
            kw.setdefault(k, v)
        return cls(**kw)


class HybridSSMLM:
    def __init__(self, cfg: HybridSSMLMConfig):
        self.cfg = cfg
        c = cfg
        gn = c.mamba_n_groups * c.mamba_d_state
        m = c.ssm_multipliers
        #: the projection's parts z | x | B | C | dt and their multipliers
        self._parts = (c.mamba_d_ssm, c.mamba_d_ssm, gn, gn, c.mamba_n_heads)
        self._mup = np.concatenate([
            np.full((n,), v, np.float32) for n, v in zip(self._parts, m)])

    # -- parameters -------------------------------------------------------

    def init(self, key, dtype=jnp.float32):
        """Seeded parameters, made in ``dtype``: normal of std 0.02 and
        norms at 1, but for what the recurrence's time scales hang on,
        drawn as the Mamba-2 module draws them: ``A`` uniform in
        ``a_init_range`` (1..16), ``dt`` log-uniform in ``dt_init_range``
        (0.001..0.1; kept as its inverse softplus), ``D`` ones, the
        depthwise conv uniform in +-1/sqrt(taps)."""
        c = self.cfg
        d, dh, f = c.hidden_size, c.head_dim, c.intermediate_size
        h, kv, hm = (c.num_attention_heads, c.num_key_value_heads,
                     c.mamba_n_heads)
        k_taps = c.mamba_d_conv
        ones = lambda n: {"scale": jnp.ones((n,), dtype)}      # noqa: E731
        keys = jax.random.split(key, c.num_hidden_layers + 2)
        layers = {}
        for i in range(c.num_hidden_layers):
            k = jax.random.split(keys[i], 13)
            dt = jnp.exp(jax.random.uniform(
                k[9], (hm,), jnp.float32, *jnp.log(jnp.asarray(
                    c.dt_init_range, jnp.float32))))
            bound = k_taps ** -0.5
            layers[str(i)] = {
                "input_norm": ones(d),
                "q_proj": {"weight": _normal(k[0], (d, h * dh), dtype)},
                "k_proj": {"weight": _normal(k[1], (d, kv * dh), dtype)},
                "v_proj": {"weight": _normal(k[2], (d, kv * dh), dtype)},
                "o_proj": {"weight": _normal(k[3], (h * dh, d), dtype)},
                "in_proj": {"weight": _normal(
                    k[4], (d, sum(self._parts)), dtype)},
                "conv": {
                    "weight": jax.random.uniform(
                        k[5], (c.conv_dim, k_taps), jnp.float32, -bound,
                        bound).astype(dtype),
                    "bias": jax.random.uniform(
                        k[6], (c.conv_dim,), jnp.float32, -bound,
                        bound).astype(dtype)},
                # float32 whatever the weights' type: a head's time scale
                "A_log": jnp.log(jax.random.uniform(
                    k[8], (hm,), jnp.float32, *c.a_init_range)),
                "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                "D": jnp.ones((hm,), jnp.float32),
                "mixer_norm": ones(c.mamba_d_ssm),
                "out_proj": {"weight": _normal(
                    k[7], (c.mamba_d_ssm, d), dtype)},
                "ff_norm": ones(d),
                "gate_proj": {"weight": _normal(k[10], (d, f), dtype)},
                "up_proj": {"weight": _normal(k[11], (d, f), dtype)},
                "down_proj": {"weight": _normal(k[12], (f, d), dtype)},
            }
        return {"embed": {"weight": _normal(keys[-2], (c.vocab_size, d),
                                            dtype)},
                "layers": layers, "final_norm": ones(d),
                "head": {"weight": _normal(keys[-1], (c.vocab_size, d),
                                           dtype)}}

    # -- the block, shared by forward() and the serving program -----------

    def embed(self, params, tokens, positions):
        del positions                       # rotary: applied at q and k
        return params["embed"]["weight"][tokens].astype(jnp.float32) \
            * self.cfg.embedding_multiplier

    def _normed(self, params, i, x):
        return rms_norm(x, params["layers"][str(i)]["input_norm"]["scale"],
                        self.cfg.rms_norm_eps)

    def attn_in(self, params, i, x, positions):
        c, lp = self.cfg, params["layers"][str(i)]
        s, n, _ = x.shape
        h, kv, dh = c.num_attention_heads, c.num_key_value_heads, c.head_dim
        a = self._normed(params, i, x) * c.attention_in_multiplier
        q = _project(a, lp["q_proj"]["weight"]).reshape(s, n, h, dh)
        k = (_project(a, lp["k_proj"]["weight"])
             * c.key_multiplier).reshape(s, n, kv, dh)
        v = _project(a, lp["v_proj"]["weight"])
        q = rope(q, positions, c.rope_theta).astype(
            lp["q_proj"]["weight"].dtype)
        k = rope(k, positions, c.rope_theta)
        return q.transpose(0, 2, 1, 3), (k.reshape(s, n, kv * dh), v), None

    def attn_out(self, params, i, x, att):
        lp = params["layers"][str(i)]
        s, n = att.shape[:2]
        return x + _project(att.reshape(s, n, -1), lp["o_proj"]["weight"]) \
            * self.cfg.attention_out_multiplier

    def mixer(self, params, i, x, state, rows, fresh, valid):
        """The state-space half of block ``i`` over ``C`` tokens a lane
        (one decode token: ``C`` = 1): ``x`` (S, C, D) the block's input,
        ``state`` the pools ``(conv windows (R, (taps - 1) * channels), head
        states (R, H, N, P))``, lane ``s`` holding row ``rows[s]`` (0: the
        null row) and starting from zeros where ``fresh[s]``; ``valid``
        (S, C) marks a lane's real tokens, which come first. Returns
        (output (S, C, D) float32 to add to the residual stream, the
        pools with every lane's row advanced past its valid tokens)."""
        c, lp = self.cfg, params["layers"][str(i)]
        conv_pool, ssm_pool = state
        s, n, _ = x.shape
        taps = c.mamba_d_conv
        u = self._normed(params, i, x) * c.ssm_in_multiplier
        zxbcdt = _project(u, lp["in_proj"]["weight"]) * self._mup
        d_ssm, gn = c.mamba_d_ssm, c.mamba_n_groups * c.mamba_d_state
        z = zxbcdt[..., :d_ssm]
        xbc = zxbcdt[..., d_ssm:d_ssm + c.conv_dim]
        dt = zxbcdt[..., d_ssm + c.conv_dim:]
        # depthwise causal conv over the window the slot kept and the
        # chunk; the window it keeps next: its last taps-1 valid inputs
        window = jnp.where((fresh > 0)[:, None, None], 0.0, conv_pool[
            rows].astype(jnp.float32).reshape(s, taps - 1, c.conv_dim))
        seq = jnp.concatenate([window, xbc], axis=1)        # (S,taps-1+C,CH)
        w = lp["conv"]["weight"].astype(jnp.float32)
        conv = lp["conv"]["bias"].astype(jnp.float32) + sum(
            w[:, j] * seq[:, j:j + n] for j in range(taps))
        n_valid = valid.sum(-1).astype(jnp.int32)
        keep = n_valid[:, None] + jnp.arange(taps - 1, dtype=jnp.int32)
        conv_pool = conv_pool.at[rows].set(jnp.take_along_axis(
            seq, keep[:, :, None], axis=1).reshape(s, -1).astype(
                conv_pool.dtype))
        xbc = conv * jax.nn.sigmoid(conv)
        xs, bm, cm = (xbc[..., :d_ssm], xbc[..., d_ssm:d_ssm + gn],
                      xbc[..., d_ssm + gn:])
        dt = jnp.where(valid[..., None],
                       jax.nn.softplus(dt + lp["dt_bias"]), 0.0)
        a = -jnp.exp(lp["A_log"])
        if n == 1:
            y, ssm_pool = ssm_decode_update(
                xs[:, 0], dt[:, 0], a, bm[:, 0], cm[:, 0], ssm_pool,
                jnp.where(valid[:, 0], rows, 0),
                n_groups=c.mamba_n_groups, impl=c.kernel_impl)
            y = y[:, None]
        else:
            y, ssm_pool = ssd_chunk_scan(
                xs, dt, a, bm, cm, ssm_pool, rows, fresh,
                n_groups=c.mamba_n_groups, impl=c.kernel_impl)
        y = y + jnp.repeat(lp["D"], c.mamba_d_head) * xs
        y = (y * (z * jax.nn.sigmoid(z))).reshape(
            s, n, c.mamba_n_groups, -1)
        y = rms_norm(y, lp["mixer_norm"]["scale"].reshape(
            c.mamba_n_groups, -1), c.rms_norm_eps).reshape(s, n, d_ssm)
        return _project(y, lp["out_proj"]["weight"]) \
            * c.ssm_out_multiplier, (conv_pool, ssm_pool)

    def ffn(self, params, i, x, valid):
        del valid
        c, lp = self.cfg, params["layers"][str(i)]
        b = rms_norm(x, lp["ff_norm"]["scale"], c.rms_norm_eps)
        g = _project(b, lp["gate_proj"]["weight"]) * c.mlp_multipliers[0]
        hidden = _project(b, lp["up_proj"]["weight"]) * (
            g * jax.nn.sigmoid(g))
        return x + _project(hidden, lp["down_proj"]["weight"]) \
            * c.mlp_multipliers[1], None

    def head(self, params, x):
        w = params["head"]["weight"]
        x = rms_norm(x, params["final_norm"]["scale"], self.cfg.rms_norm_eps)
        return jnp.einsum("...d,vd->...v", x.astype(w.dtype), w,
                          precision=_precision(w.dtype),
                          preferred_element_type=jnp.float32) \
            * self.cfg.lm_head_multiplier

    def slot_state(self):
        """``ServingSpec.slot_state`` of one layer."""
        c = self.cfg
        # the window's taps folded into one lane-dense row a slot (a
        # second-minor axis of 3 the chip would pad or re-lay out)
        return (("conv_window", ((c.mamba_d_conv - 1) * c.conv_dim,)),
                ("ssm_state", (c.mamba_n_heads, c.mamba_d_state,
                               c.mamba_d_head)))

    # -- whole-sequence pass ------------------------------------------------

    def forward(self, params, ids):
        """(B, S) ids -> (B, S, V) float32 logits: dense causal scores,
        the recurrence from a zero state, no cache."""
        c = self.cfg
        b, n = ids.shape
        pad = -n % SCAN_TILE if n > SCAN_TILE else 0
        ids = jnp.pad(ids, ((0, 0), (0, pad)))
        m = n + pad
        pos = jnp.broadcast_to(jnp.arange(m, dtype=jnp.int32), (b, m))
        valid = pos < n
        x = self.embed(params, ids, pos)
        g = c.num_attention_heads // c.num_key_value_heads
        rows = jnp.arange(1, b + 1, dtype=jnp.int32)
        fresh = jnp.ones((b,), jnp.int32)
        causal = jnp.tril(jnp.ones((m, m), bool))
        for i in range(c.num_hidden_layers):
            q, (k, v), _ = self.attn_in(params, i, x, pos)
            kh = jnp.repeat(k.reshape(b, m, -1, c.head_dim), g, axis=2)
            vh = jnp.repeat(v.reshape(b, m, -1, c.head_dim), g, axis=2)
            att = jnp.einsum("bhqd,bkhd->bhqk", q.astype(jnp.float32), kh,
                             precision=_HI)
            att = jax.nn.softmax(jnp.where(
                causal, att * c.head_dim ** -0.5, NEG_INF), axis=-1)
            o = jnp.einsum("bhqk,bkhd->bqhd", att, vh, precision=_HI)
            state = tuple(jnp.zeros((b + 1,) + shape, jnp.float32)
                          for _name, shape in self.slot_state())
            mixed, _ = self.mixer(params, i, x, state, rows, fresh, valid)
            x = self.attn_out(params, i, x, o) + mixed
            x, _ = self.ffn(params, i, x, valid)
        return self.head(params, x)[:, :n]

    # -- the paged serving engine's view ------------------------------------

    def serving(self, **unsupported):
        """This model's block as the paged serving engine runs it. It
        takes none of the engine's options yet (``spec.supports`` is
        empty, so the engine refuses them before asking)."""
        if unsupported:
            raise ValueError(f"HybridSSMLM.serving() takes no options yet, "
                             f"got {sorted(unsupported)}")
        return HybridSSMServing(self)


class HybridSSMServing:
    """:mod:`paddle_tpu.serving.program` for :class:`HybridSSMLM`: K and V
    cached a token and layer, a conv window and the heads' states kept a
    slot and layer. Nothing that snapshots, shares, ships or speculates
    reads that state yet, so ``supports`` is empty."""

    def __init__(self, model: HybridSSMLM):
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
            max_position=c.max_position_embeddings,
            slot_state=model.slot_state(),
            slot_state_dtype=c.state_dtype,
            supports=frozenset())

    def param_dtype(self, params):
        return params["embed"]["weight"].dtype
