"""Mistral-Small-4-119B-2603 (Mistral AI, 2026-03): the program's model from
the published ``config.json`` keys, a plain reference forward pass, and
what the ``serve_lm`` runner asks a family for.

36 layers of multi-head latent attention (32 heads; queries through a
latent of 1024, keys and values through one of 256 with ONE shared rotary
key of 64 a token; YaRN frequencies, a position-dependent query scale)
over 128 softmax-routed experts of 2048 that take 4 a token, renormalised,
beside one shared expert. A configuration may hold a chip's SHARE of each
layer's experts and of the vocabulary (``sizes["n_routed_experts"]`` of
``sizes["published"]["n_routed_experts"]`` from
``sizes["expert_share"]["offset"]`` on): the router keeps its width, the
layer computes its own experts' part, and the reference below is given the
same share.

The reference follows ISSUE 42's equations and nothing of the program:
float32 ``jax.numpy``, the EXPANDED form (every head's keys and values
formed from the latent; nothing absorbed), no kernel, no cache, no chunks,
no batching; the experts a dense weighted sum over every routed expert of
which the held ones' terms are kept. It reads the program's parameter tree
and shares no code with it. At the cell's sizes it works in blocks (one
head's keys and values at a time, its queries ``query_block`` at a time
against every key; one expert at a time; the vocabulary in pieces, the
logits of the rows asked for only) so that a 17152-token request fits
beside the served weights and the pages. Departures from the published
description: none known; what the config does not settle is in the
configuration file's ``assumed``. Call it under
``jax.default_matmul_precision("highest")``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

#: the kernels whose dispatches decide ``correct``: each must have run on
#: its Pallas body and never on its ``lax`` form
KERNELS = ("latent_paged_prefill", "latent_paged_decode", "moe_grouped_ffn")

#: the published keys the program's config takes under the same name
_KEYS = ("vocab_size", "hidden_size", "num_hidden_layers",
         "num_attention_heads", "q_lora_rank", "kv_lora_rank",
         "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
         "rms_norm_eps", "max_position_embeddings", "n_routed_experts",
         "num_experts_per_tok", "moe_intermediate_size", "n_shared_experts",
         "routed_scaling_factor", "norm_topk_prob")

#: ``rope_parameters`` key -> the program config's field
_ROPE = {"rope_theta": "rope_theta", "factor": "rope_factor",
         "original_max_position_embeddings":
             "original_max_position_embeddings",
         "beta_fast": "beta_fast", "beta_slow": "beta_slow",
         "mscale": "mscale", "mscale_all_dim": "mscale_all_dim",
         "llama_4_scaling_beta": "llama_4_scaling_beta"}


def _routed(sizes: dict) -> int:
    """The router's width: the published count of routed experts."""
    return sizes.get("published", {}).get("n_routed_experts",
                                          sizes["n_routed_experts"])


def _offset(sizes: dict) -> int:
    return sizes.get("expert_share", {}).get("offset", 0)


def model_config(sizes: dict, **kw):
    from paddle_tpu.models.mla_moe_lm import MLAMoELMConfig
    for flag, must in (("hidden_act", "silu"), ("first_k_dense_replace", 0),
                       ("tie_word_embeddings", False), ("n_group", 1),
                       ("topk_group", 1), ("rope_interleave", True),
                       ("attention_bias", False), ("mlp_bias", False),
                       ("sliding_window", None)):
        if sizes.get(flag, must) != must:
            raise ValueError(f"the program is written for {flag}={must!r}")
    rope = sizes["rope_parameters"]
    if rope.get("rope_type", "yarn") != "yarn":
        raise ValueError("the program is written for YaRN frequencies")
    given = {k: sizes[k] for k in _KEYS if k in sizes}
    return MLAMoELMConfig(
        num_routed_experts=_routed(sizes), expert_offset=_offset(sizes),
        **{field: float(rope[key]) if key != "original_max_position_embeddings"
           else int(rope[key]) for key, field in _ROPE.items()},
        **given, **kw)


def sizes_of(cfg) -> dict:
    """The published keys the reference reads, from a program config
    (:func:`model_config` the other way round)."""
    sizes = {k: getattr(cfg, k) for k in _KEYS}
    sizes.update(
        rope_parameters={key: getattr(cfg, field)
                         for key, field in _ROPE.items()},
        published={"n_routed_experts": cfg.num_routed_experts},
        expert_share={"offset": cfg.expert_offset})
    return sizes


def build(sizes: dict, *, interpret: bool = False):
    """The program's model for the published ``sizes``."""
    from paddle_tpu.models.mla_moe_lm import MLAMoELM
    return MLAMoELM(model_config(
        sizes, kernel_impl="pallas_interpret" if interpret else "pallas"))


def positions(sizes: dict) -> int:
    return sizes["max_position_embeddings"]


def vocabulary(sizes: dict) -> int:
    """The rows of the vocabulary held here: the traffic draws its ids
    from them."""
    return sizes["vocab_size"]


def round_weights(params, dtype):
    """Every parameter rounded to ``dtype`` and back: a CONTROL (the
    reference in a precision below the one the configuration states).
    Applied to the tree before any jitted call, so that no compiler takes
    the two casts for nothing."""
    return jax.tree.map(
        lambda a: a.astype(jnp.dtype(dtype)).astype(a.dtype), params)


# -- the plain reference ------------------------------------------------------

def _f32(a):
    return a.astype(jnp.float32)


def _rms(u, g, eps):
    return _f32(g) * u / jnp.sqrt(jnp.mean(u * u, -1, keepdims=True) + eps)


def _silu(u):
    return u / (1.0 + jnp.exp(-u))


def _m(f, a):
    return 0.1 * a * math.log(f) + 1.0 if f > 1 else 1.0


def _yarn(d_r, rope):
    """``omega`` (d_r / 2,): a pair's angle a position."""
    theta, f = float(rope["rope_theta"]), float(rope["factor"])
    l0 = rope["original_max_position_embeddings"]
    cd = lambda r: d_r * math.log(l0 / (2 * math.pi * r)) \
        / (2 * math.log(theta))                             # noqa: E731
    low = max(math.floor(cd(rope["beta_fast"])), 0)
    high = min(math.ceil(cd(rope["beta_slow"])), d_r - 1)
    i = jnp.arange(d_r // 2, dtype=jnp.float32)
    phi = theta ** (-2.0 * i / d_r)
    ramp = jnp.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    return phi * (1.0 - ramp) + phi / f * ramp


def _rope(u, pos, omega, trig):
    """The ADJACENT pairs ``(u_2i, u_2i+1)`` of the last axis rotated by
    ``pos * omega_i``; ``u`` (N, ..., d_r), ``pos`` (N,)."""
    ang = (_f32(pos)[:, None] * omega).reshape(
        (u.shape[0],) + (1,) * (u.ndim - 2) + (-1,))
    cos, sin = trig * jnp.cos(ang), trig * jnp.sin(ang)
    even, odd = u[..., 0::2], u[..., 1::2]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                     -1).reshape(u.shape)


def _pieces(n: int, limit: int) -> int:
    """The fewest equal pieces of ``n`` of at most ``limit`` each."""
    return next(k for k in range(1, n + 1) if n % k == 0 and n // k <= limit)


def _swiglu(t, p):
    return (_silu(t @ _f32(p["gate"]["weight"]))
            * (t @ _f32(p["up"]["weight"]))) @ _f32(p["down"]["weight"])


def _attend(q, k, v, scale, query_block):
    """One head: (N, d) queries over (N, d) keys and (N, d_v) values,
    causal, ``scale`` (N,) a query; a block of queries at a time against
    every key."""
    n = q.shape[0]
    nq = _pieces(n, query_block)
    qb = n // nq
    s_pos = jnp.arange(n)[None, :]

    def block(i):
        lo = i * qb
        qs = jax.lax.dynamic_slice_in_dim(q, lo, qb, 0)
        sc = jax.lax.dynamic_slice_in_dim(scale, lo, qb, 0)
        seen = s_pos <= lo + jnp.arange(qb)[:, None]
        score = (qs @ k.T) * sc[:, None]
        return jax.nn.softmax(jnp.where(seen, score, -jnp.inf), -1) @ v

    return jax.lax.map(block, jnp.arange(nq)).reshape(n, -1)


def reference_hidden(params, ids, sizes: dict, query_block: int = 128,
                     query_scale: bool = True, scale_m2: bool = True):
    """(N,) ids -> (N, D) float32 residual stream after the last layer.
    ``query_scale=False`` (``a_t`` left at 1) and ``scale_m2=False`` (the
    softmax scale without YaRN's ``m^2``) are CONTROLS the cell's limits
    were set against, as :func:`round_weights` is: a comparison that
    passes them checks nothing."""
    n = ids.shape[0]
    h = sizes["num_attention_heads"]
    dc, dn, dr, dv = (sizes["kv_lora_rank"], sizes["qk_nope_head_dim"],
                      sizes["qk_rope_head_dim"], sizes["v_head_dim"])
    eps, rope = sizes["rms_norm_eps"], sizes["rope_parameters"]
    f = float(rope["factor"])
    omega = _yarn(dr, rope)
    trig = _m(f, rope["mscale"]) / _m(f, rope["mscale_all_dim"])
    sigma = (dn + dr) ** -0.5 * (
        _m(f, rope["mscale_all_dim"]) ** 2 if scale_m2 else 1.0)
    pos = jnp.arange(n)
    a_t = 1.0 + rope["llama_4_scaling_beta"] * jnp.log(1.0 + _f32(
        pos // rope["original_max_position_embeddings"]))
    scale = sigma * (a_t if query_scale else jnp.ones_like(a_t))
    top_k, off = sizes["num_experts_per_tok"], _offset(sizes)

    x = _f32(params["embed"]["weight"][ids])
    for i in range(sizes["num_hidden_layers"]):
        lp = params["layers"][str(i)]
        w = lambda name: _f32(lp[name]["weight"])            # noqa: E731
        a = _rms(x, lp["attn_norm"]["scale"], eps)
        c_q = _rms(a @ w("q_a_proj"), lp["q_a_norm"]["scale"], eps)
        q = (c_q @ w("q_b_proj")).reshape(n, h, dn + dr)
        q = jnp.concatenate(
            [q[..., :dn], _rope(q[..., dn:], pos, omega, trig)], -1)
        kv = a @ w("kv_a_proj")
        c = _rms(kv[:, :dc], lp["kv_a_norm"]["scale"], eps)
        k_rope = _rope(kv[:, dc:], pos, omega, trig)        # one a token

        def head(y, hw, q=q, c=c, k_rope=k_rope):
            q_i, up_i, out_i = hw           # (N, dn+dr) (dc, dn+dv) (dv, D)
            kv_i = c @ up_i                 # this head's keys and values
            k_i = jnp.concatenate([kv_i[:, :dn], k_rope], -1)
            o_i = _attend(q_i, k_i, kv_i[:, dn:], scale, query_block)
            return y + o_i @ out_i, None

        x, _ = jax.lax.scan(head, x, (
            q.transpose(1, 0, 2),
            w("kv_b_proj").reshape(dc, h, dn + dv).transpose(1, 0, 2),
            w("o_proj").reshape(h, dv, -1)))

        t = _rms(x, lp["ffn_norm"]["scale"], eps)
        s = jax.nn.softmax(t @ w("router"), -1)
        # the top_k largest, ties to the lower index
        order = jnp.argsort(-s, axis=-1, stable=True)[:, :top_k]
        picked = jnp.zeros_like(s, bool).at[
            jnp.arange(n)[:, None], order].set(True)
        top = jnp.where(picked, s, 0.0)
        if sizes.get("norm_topk_prob", True):
            top = top / top.sum(-1, keepdims=True)
        coef = sizes.get("routed_scaling_factor", 1.0) * top  # (N, routed)
        ex = lp["experts"]
        held = ex["gate"].shape[0]

        def expert(y, ew, t=t):
            c_e, g_w, u_w, d_w = ew                           # one expert
            hidden = _silu(t @ _f32(g_w).T) * (t @ _f32(u_w).T)
            return y + c_e[:, None] * (hidden @ _f32(d_w)), None

        y, _ = jax.lax.scan(
            expert, _swiglu(t, lp["shared"]),
            (coef[:, off:off + held].T, ex["gate"], ex["up"], ex["down"]))
        x = x + y
    return x


def reference_logits(params, ids, sizes: dict, lo=0, rows=None,
                     query_block: int = 128, vocab_block: int = 8192,
                     probe=None, **controls):
    """(1, N) ids -> (1, rows, V) float32 logits of positions ``lo .. lo
    + rows`` (all of them by default; ``lo`` may be traced) over the rows
    of the vocabulary held here, ``vocab_block`` rows of the head at a
    time into one buffer. With ``probe`` (what ``serve_lm`` passes every
    family): (logits, selections), the selections empty: this family's
    attention selects nothing and the runner reads none. ``controls``:
    :func:`reference_hidden`'s."""
    ids = ids[0]
    rows = ids.shape[0] if rows is None else rows
    x = reference_hidden(params, ids, sizes, query_block, **controls)
    x = jax.lax.dynamic_slice_in_dim(x, lo, rows, axis=0)
    x = _rms(x, params["final_norm"]["scale"], sizes["rms_norm_eps"])
    head = params["head"]["weight"]
    k = _pieces(head.shape[0], vocab_block)
    width = head.shape[0] // k

    def write(i, logits):
        piece = _f32(jax.lax.dynamic_slice_in_dim(head, i * width, width, 0))
        return jax.lax.dynamic_update_slice_in_dim(
            logits, x @ piece.T, i * width, axis=1)

    logits = jax.lax.fori_loop(
        0, k, write, jnp.zeros((rows, k * width), jnp.float32))[None]
    return logits if probe is None else (logits,
                                         jnp.zeros((0,), jnp.bool_))


# -- what the traced window's kernels had to do -------------------------------

def kernel_needs(sizes: dict, itemsize: int, layers: int, traced: dict,
                 live_token_steps: float, selected_token_steps: float) -> dict:
    """Nominal operations and bytes of the grouped expert kernel and the
    two latent kernels at this family's shapes over the traced part of
    the window. ``traced``: the program's counters over that part (they
    already count layers).

    - experts: every touched expert's three matrices read once a layer
      and call, 6 D F operations a token-expert pair computed here;
    - latent attention, a phase: every row the kernel had to read once,
      ``d_c + d_r`` values (640 bytes in bfloat16, however the pool lays
      them out: padding would show as a lower share, not as more work),
      and for each (query token, row) pair every head's score over ``d_c
      + d_r`` and weighted sum over ``d_c``, ``2 (2 d_c + d_r)``
      operations a head (1152): the engine's own counts,
      ``serving_latent_rows_read_total`` and ``serving_latent_pairs_total``
      by ``phase``."""
    del layers, live_token_steps, selected_token_steps
    d, f = sizes["hidden_size"], sizes["moe_intermediate_size"]
    dc, dr = sizes["kv_lora_rank"], sizes["qk_rope_head_dim"]
    pair_flops = sizes["num_attention_heads"] * 2.0 * (2 * dc + dr)
    needs = {
        "moe_ffn_needed_bytes": traced.get(
            "serving_moe_experts_touched_total", 0.0) * 3 * d * f * itemsize,
        "moe_ffn_needed_flops": traced.get(
            "serving_moe_assignments_total", 0.0) * 6.0 * d * f,
    }
    for phase in ("decode", "prefill"):
        needs[f"latent_{phase}_needed_bytes"] = traced.get(
            f'serving_latent_rows_read_total{{phase="{phase}"}}', 0.0) \
            * (dc + dr) * itemsize
        needs[f"latent_{phase}_needed_flops"] = traced.get(
            f'serving_latent_pairs_total{{phase="{phase}"}}', 0.0) \
            * pair_flops
    return needs
