"""MiMo-V2-Flash (Xiaomi, 2025-12): the program's model from the published
``config.json`` keys, a plain reference forward pass, and what the
``serve_lm`` runner asks a family for.

48 layers; where ``hybrid_layer_pattern`` says 0 a layer attends to every
token before it over 4 KV heads (rotary base ``rope_theta``), where it says
1 to the last 128 over 8 KV heads (base ``swa_rope_theta``) with a learned
sink a query head in the softmax's denominator; 64 query heads, keys of
192 a head of which the first ``int(192 x 0.334)`` = 64 entries are
rotated, values of 128 scaled by 0.707, no QK norm. The first layer's MLP
is dense (``moe_layer_freq`` 0), every other a sigmoid router over 256
experts that takes 8 a token, renormalised, no shared expert. A
configuration may hold a chip's SHARE of each layer's experts and of the
vocabulary (``sizes["n_routed_experts"]`` of ``sizes["published"]
["n_routed_experts"]`` from ``sizes["expert_share"]["offset"]`` on): the
router keeps its width, the layer computes its own experts' part, and the
reference below is given the same share.

The reference follows ISSUE 49's equations and nothing of the program:
float32 ``jax.numpy``, no kernel, no cache, no ring, no chunks, no
batching; the window as a mask, the sink as one more column of the scores
that sums no value, the experts as a dense weighted sum over every routed
expert of which the held ones' terms are kept. It reads the program's
parameter tree and shares no code with it. At the cell's sizes it works in
blocks (queries ``query_block`` at a time, a window layer's against the
keys its windows can reach; one matrix cast to float32 at a time, the dense
MLP's hidden units in pieces, one expert at a time, the vocabulary in
pieces, the logits of the rows asked for only) so that a 9216-token
request fits beside the served weights and the pages. Departures from the
published description: none known; what the config does not settle is in
the configuration file's ``assumed``. The three multi-token-prediction
layers are not served. Call it under
``jax.default_matmul_precision("highest")``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

#: the kernels whose dispatches decide ``correct``: each must have run on
#: its Pallas body and never on its ``lax`` form
KERNELS = ("ragged_paged_prefill", "ragged_paged_decode", "moe_grouped_ffn")

#: the published keys the program's config takes under the same name
_SAME = ("vocab_size", "hidden_size", "intermediate_size",
         "num_hidden_layers", "num_attention_heads", "num_key_value_heads",
         "swa_num_key_value_heads", "head_dim", "v_head_dim",
         "max_position_embeddings", "sliding_window", "num_experts_per_tok",
         "moe_intermediate_size", "norm_topk_prob", "partial_rotary_factor",
         "attention_value_scale", "add_swa_attention_sink_bias",
         "add_full_attention_sink_bias")


def _routed(sizes: dict) -> int:
    """The router's width: the published count of routed experts."""
    return sizes.get("published", {}).get("n_routed_experts",
                                          sizes["n_routed_experts"])


def _offset(sizes: dict) -> int:
    return sizes.get("expert_share", {}).get("offset", 0)


def _scale(sizes: dict) -> float:
    """``routed_scaling_factor``: null in the published file, which is 1."""
    return float(sizes.get("routed_scaling_factor") or 1.0)


def _windowed(sizes: dict, i: int) -> bool:
    return bool(sizes["hybrid_layer_pattern"][i])


def model_config(sizes: dict, **kw):
    from paddle_tpu.models.window_moe_lm import WindowMoELMConfig
    for flag, must in (("scoring_func", "sigmoid"), ("hidden_act", "silu"),
                       ("tie_word_embeddings", False), ("n_group", 1),
                       ("topk_group", 1), ("attention_bias", False),
                       ("n_shared_experts", None)):
        if sizes.get(flag, must) != must:
            raise ValueError(f"the program is written for {flag}={must!r}")
    for key in ("swa_head_dim", "swa_v_head_dim", "swa_num_attention_heads"):
        kind = key[len("swa_"):]
        if sizes.get(key, sizes[kind]) != sizes[kind]:
            raise ValueError(f"the program is written for {key} = {kind}")
    n = sizes["num_hidden_layers"]
    return WindowMoELMConfig(
        rms_norm_eps=sizes["layernorm_epsilon"],
        rope_theta=float(sizes["rope_theta"]),
        swa_rope_theta=float(sizes["swa_rope_theta"]),
        full_attention_rope=True, qk_norm=False,
        layer_types=tuple("sliding_attention" if w else "full_attention"
                          for w in sizes["hybrid_layer_pattern"][:n]),
        mlp_layer_types=tuple("sparse" if m else "dense"
                              for m in sizes["moe_layer_freq"][:n]),
        num_experts=sizes["n_routed_experts"],
        num_routed_experts=_routed(sizes), expert_offset=_offset(sizes),
        num_shared_experts=0, routed_scaling_factor=_scale(sizes),
        **{k: sizes[k] for k in _SAME if k in sizes}, **kw)


def sizes_of(cfg) -> dict:
    """The published keys the reference reads, from a program config
    (:func:`model_config` the other way round)."""
    sizes = {k: getattr(cfg, k) for k in _SAME}
    sizes.update(
        swa_num_key_value_heads=cfg.swa_num_key_value_heads
        or cfg.num_key_value_heads,
        v_head_dim=cfg.value_dim, layernorm_epsilon=cfg.rms_norm_eps,
        rope_theta=cfg.rope_theta, swa_rope_theta=cfg.swa_rope_theta,
        hybrid_layer_pattern=[int(t == "sliding_attention")
                              for t in cfg.layer_types],
        moe_layer_freq=[int(t == "sparse") for t in cfg.mlp_layer_types],
        n_routed_experts=cfg.num_experts,
        routed_scaling_factor=cfg.routed_scaling_factor,
        published={"n_routed_experts": cfg.num_routed_experts},
        expert_share={"offset": cfg.expert_offset})
    return sizes


def build(sizes: dict, *, interpret: bool = False):
    """The program's model for the published ``sizes``."""
    from paddle_tpu.models.window_moe_lm import WindowMoELM
    return WindowMoELM(model_config(
        sizes, kernel_impl="pallas_interpret" if interpret else "pallas"))


def positions(sizes: dict) -> int:
    return sizes["max_position_embeddings"]


def vocabulary(sizes: dict) -> int:
    """The rows of the vocabulary held here: the traffic draws its ids
    from them."""
    return sizes["vocab_size"]


# -- the plain reference ------------------------------------------------------

def _f32(a):
    return a.astype(jnp.float32)


def _rms(u, g, eps):
    return _f32(g) * u / jnp.sqrt(jnp.mean(u * u, -1, keepdims=True) + eps)


def _silu(u):
    return u / (1.0 + jnp.exp(-u))


def _rope(u, pos, theta, r):
    """The first ``r`` entries of each head rotated, pairing ``(n, n +
    r/2)``, the rest as they are; ``u`` (N, heads, d), ``pos`` (N,)."""
    half = r // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = (pos.astype(jnp.float32)[:, None] * freq)[:, None, :]
    lo, hi = u[..., :half], u[..., half:r]
    return jnp.concatenate([lo * jnp.cos(ang) - hi * jnp.sin(ang),
                            hi * jnp.cos(ang) + lo * jnp.sin(ang),
                            u[..., r:]], -1)


def _pieces(n: int, limit: int) -> int:
    """The fewest equal pieces of ``n`` of at most ``limit`` each."""
    return next(k for k in range(1, n + 1) if n % k == 0 and n // k <= limit)


def _swiglu(t, p, hidden_block):
    """``(silu(t W_g) * (t W_u)) W_d``, the hidden units ``hidden_block``
    at a time (one piece of each matrix in float32 at once)."""
    d, f = p["gate"]["weight"].shape
    k = _pieces(f, hidden_block)
    width = f // k

    def piece(i, y):
        cols = lambda w: _f32(jax.lax.dynamic_slice_in_dim(   # noqa: E731
            w, i * width, width, 1))
        hidden = _silu(t @ cols(p["gate"]["weight"])) \
            * (t @ cols(p["up"]["weight"]))
        return y + hidden @ _f32(jax.lax.dynamic_slice_in_dim(
            p["down"]["weight"], i * width, width, 0))

    return jax.lax.fori_loop(0, k, piece, jnp.zeros_like(t))


def _attend(q, k, v, window, sinks, query_block):
    """(N, H, dk) queries over (N, G, dk) keys and (N, G, dv) values, head
    ``i`` reading KV head ``i // (H / G)``, causal, the last ``window``
    tokens where given; ``sinks`` (H,) where given: one more column of the
    scores, which sums no value. A block of queries at a time, against
    every key (full) or the ``query_block + window`` keys its windows can
    reach."""
    n, h, dk = q.shape
    g = k.shape[1]
    nq = _pieces(n, query_block)
    qb = n // nq
    span = n if window is None else min(n, qb + window)

    def block(i):
        lo = i * qb
        k_lo = 0 if window is None else jnp.clip(lo + qb - span, 0, n - span)
        qs = jax.lax.dynamic_slice_in_dim(q, lo, qb, 0).reshape(
            qb, g, h // g, dk)
        ks = jax.lax.dynamic_slice_in_dim(k, k_lo, span, 0)
        vs = jax.lax.dynamic_slice_in_dim(v, k_lo, span, 0)
        t = lo + jnp.arange(qb)[:, None]
        s = k_lo + jnp.arange(span)[None, :]
        seen = s <= t
        if window is not None:
            seen = seen & (s > t - window)
        sc = jnp.einsum("qgud,ngd->guqn", qs, ks) / math.sqrt(dk)
        sc = jnp.where(seen[None, None], sc, -jnp.inf)
        if sinks is not None:
            column = jnp.broadcast_to(
                sinks.reshape(g, h // g, 1, 1), sc.shape[:3] + (1,))
            sc = jnp.concatenate([sc, column], -1)
        att = jax.nn.softmax(sc, -1)[..., :span]
        return jnp.einsum("guqn,ngd->qgud", att, vs)

    return jax.lax.map(block, jnp.arange(nq)).reshape(n, -1)


def reference_hidden(params, ids, sizes: dict, query_block: int = 128,
                     hidden_block: int = 4096, ignore_window: bool = False,
                     sinks: bool = True, value_scale=None,
                     rotary: str = "published"):
    """(N,) ids -> (N, D) float32 residual stream after the last layer.
    The CONTROLS the cell's limits were set against (a comparison that
    passes one checks nothing): ``sinks=False`` (the window layers'
    softmax without its sink), ``value_scale=1.0`` (the values unscaled),
    ``rotary="whole"`` (every entry of a head rotated) or ``"swapped"``
    (the two kinds of layer take each other's base), ``ignore_window``
    (every layer attends to every token before)."""
    n = ids.shape[0]
    h, dk, dv = (sizes["num_attention_heads"], sizes["head_dim"],
                 sizes["v_head_dim"])
    eps = sizes["layernorm_epsilon"]
    r = dk if rotary == "whole" else int(dk * sizes["partial_rotary_factor"])
    thetas = (float(sizes["rope_theta"]), float(sizes["swa_rope_theta"]))
    if rotary == "swapped":
        thetas = thetas[::-1]
    scale = sizes["attention_value_scale"] if value_scale is None \
        else value_scale
    top_k, off = sizes["num_experts_per_tok"], _offset(sizes)
    pos = jnp.arange(n)
    x = _f32(params["embed"]["weight"][ids])
    for i in range(sizes["num_hidden_layers"]):
        lp = params["layers"][str(i)]
        w = lambda name: _f32(lp[name]["weight"])            # noqa: E731
        windowed = _windowed(sizes, i)
        g = sizes["swa_num_key_value_heads" if windowed
                  else "num_key_value_heads"]
        a = _rms(x, lp["attn_norm"]["scale"], eps)
        q = _rope((a @ w("q_proj")).reshape(n, h, dk), pos,
                  thetas[windowed], r)
        k = _rope((a @ w("k_proj")).reshape(n, g, dk), pos,
                  thetas[windowed], r)
        v = scale * (a @ w("v_proj")).reshape(n, g, dv)
        has_sink = sizes["add_swa_attention_sink_bias" if windowed
                         else "add_full_attention_sink_bias"]
        att = _attend(
            q, k, v,
            sizes["sliding_window"] if windowed and not ignore_window
            else None,
            _f32(lp["sinks"]) if has_sink and sinks else None, query_block)
        x = x + att @ w("o_proj")

        t = _rms(x, lp["ffn_norm"]["scale"], eps)
        if not sizes["moe_layer_freq"][i]:
            x = x + _swiglu(t, lp["mlp"], hidden_block)
            continue
        s = 1.0 / (1.0 + jnp.exp(-(t @ _f32(lp["router"]["weight"]))))
        sel = s + _f32(lp["router"]["selection_bias"])
        # the top_k largest, ties to the lower index
        order = jnp.argsort(-sel, axis=-1, stable=True)[:, :top_k]
        picked = jnp.zeros_like(s, bool).at[
            jnp.arange(n)[:, None], order].set(True)
        top = jnp.where(picked, s, 0.0)
        if sizes.get("norm_topk_prob", True):
            top = top / top.sum(-1, keepdims=True)
        coef = _scale(sizes) * top                           # (N, routed)
        ex = lp["experts"]
        held = ex["gate"].shape[0]

        def expert(y, ew, t=t):
            c_e, g_w, u_w, d_w = ew                           # one expert
            hidden = _silu(t @ _f32(g_w).T) * (t @ _f32(u_w).T)
            return y + c_e[:, None] * (hidden @ _f32(d_w)), None

        y, _ = jax.lax.scan(
            expert, jnp.zeros_like(x),
            (coef[:, off:off + held].T, ex["gate"], ex["up"], ex["down"]))
        x = x + y
    return x


def reference_logits(params, ids, sizes: dict, lo=0, rows=None,
                     query_block: int = 128, vocab_block: int = 9536,
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
    x = _rms(x, params["final_norm"]["scale"], sizes["layernorm_epsilon"])
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

def _by_kind(traced: dict, name: str) -> dict:
    return {kind: traced.get(f'{name}{{layers="{kind}"}}', 0.0)
            for kind in ("full", "window")}


def kernel_needs(sizes: dict, itemsize: int, layers: int, traced: dict,
                 live_token_steps: float, selected_token_steps: float) -> dict:
    """Nominal operations and bytes of the grouped expert kernel and the
    two dense paged kernels at this family's shapes over the traced part
    of the window. ``traced``: the program's counters over that part (they
    already count layers).

    - experts: every touched expert's three matrices read once a layer
      and call, 6 D F operations a token-expert pair computed here;
    - paged decode: the K and V rows a decode token step has to read, the
      engine's own count, ``serving_decode_kv_bytes_total{kind="live"}``:
      a full layer's every cached token at 4 x (192 + 128) values, a
      window layer's last 128 at most at 8 x (192 + 128);
    - paged prefill: ``serving_prefill_attn_pairs_total{layers}`` (query
      token, key token) pairs, each 64 heads x 2 x (192 + 128) operations
      (the score and the weighted value), and
      ``serving_prefill_kv_rows_total{layers}`` rows read once, each the
      kind's KV heads x (192 + 128) values. Where the program has no such
      counters (the parent's) the prefill needs are left out."""
    del layers, live_token_steps, selected_token_steps
    d, f = sizes["hidden_size"], sizes["moe_intermediate_size"]
    touched = traced.get("serving_moe_experts_touched_total", 0.0)
    pairs = traced.get("serving_moe_assignments_total", 0.0)
    needs = {
        "moe_ffn_needed_bytes": touched * 3 * d * f * itemsize,
        "moe_ffn_needed_flops": pairs * 6.0 * d * f,
        "paged_decode_needed_bytes": traced.get(
            'serving_decode_kv_bytes_total{kind="live"}', 0.0),
    }
    scored = _by_kind(traced, "serving_prefill_attn_pairs_total")
    read = _by_kind(traced, "serving_prefill_kv_rows_total")
    if any(scored.values()):
        wide = sizes["head_dim"] + sizes["v_head_dim"]
        heads = {"full": sizes["num_key_value_heads"],
                 "window": sizes["swa_num_key_value_heads"]}
        needs["paged_prefill_needed_flops"] = sum(scored.values()) \
            * sizes["num_attention_heads"] * 2.0 * wide
        needs["paged_prefill_needed_bytes"] = sum(
            read[kind] * heads[kind] * wide * itemsize for kind in read)
    return needs
