"""K-EXAONE-236B-A23B (LG AI Research, 2026-01): the program's model from
the published ``config.json`` keys, a plain reference forward pass, and
what the ``serve_lm`` runner asks a family for.

48 layers, three of window attention (the last 128 tokens, rotary
positions) to one of full attention (no rotary embedding); the first
layer's MLP dense, every other a sigmoid router over 128 experts that
takes 8 a token, renormalised and scaled by 2.5, beside one shared expert.
A configuration may hold a chip's SHARE of each layer's experts and of the
vocabulary (``sizes["num_experts"]`` of ``sizes["published"]
["num_experts"]`` from ``sizes["expert_share"]["offset"]`` on): the router
keeps its width, the layer computes its own experts' part, and the
reference below is given the same share.

The reference follows ISSUE 40's equations and nothing of the program:
float32 ``jax.numpy``, no kernel, no cache, no ring, no chunks, no
batching; the window as a mask, the experts as a dense weighted sum over
every routed expert of which the held ones' terms are kept. It reads the
program's parameter tree and shares no code with it. At the cell's sizes
it works in blocks (queries ``query_block`` at a time, a window layer's
against the keys its windows can reach; one matrix cast to float32 at a
time, the dense MLP's hidden units in pieces, one expert at a time, the
vocabulary in pieces, the logits of the rows asked for only) so that a
9216-token request fits beside the served weights and the pages.
Departures from the published description: none known; what the config
does not settle is in the configuration file's ``assumed``. Call it under
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
_KEYS = ("vocab_size", "hidden_size", "intermediate_size",
         "num_hidden_layers", "num_attention_heads", "num_key_value_heads",
         "head_dim", "rms_norm_eps", "max_position_embeddings",
         "sliding_window", "num_experts", "num_experts_per_tok",
         "moe_intermediate_size", "num_shared_experts",
         "routed_scaling_factor", "norm_topk_prob")


def _routed(sizes: dict) -> int:
    """The router's width: the published count of routed experts."""
    return sizes.get("published", {}).get("num_experts",
                                          sizes["num_experts"])


def _offset(sizes: dict) -> int:
    return sizes.get("expert_share", {}).get("offset", 0)


def model_config(sizes: dict, **kw):
    from paddle_tpu.models.window_moe_lm import WindowMoELMConfig
    for flag, must in (("scoring_func", "sigmoid"), ("hidden_act", "silu"),
                       ("tie_word_embeddings", False), ("n_group", 1),
                       ("topk_group", 1)):
        if sizes.get(flag, must) != must:
            raise ValueError(f"the program is written for {flag}={must!r}")
    if sizes.get("num_nextn_predict_layers", 0):
        raise ValueError("the multi-token-prediction layer is not served "
                         "(num_nextn_predict_layers must be 0)")
    given = {k: sizes[k] for k in _KEYS if k in sizes}
    return WindowMoELMConfig(
        rope_theta=float(sizes["rope_parameters"]["rope_theta"]),
        layer_types=tuple(sizes["layer_types"]),
        mlp_layer_types=tuple(sizes["mlp_layer_types"]),
        num_routed_experts=_routed(sizes), expert_offset=_offset(sizes),
        **given, **kw)


def sizes_of(cfg) -> dict:
    """The published keys the reference reads, from a program config
    (:func:`model_config` the other way round)."""
    sizes = {k: getattr(cfg, k) for k in _KEYS}
    sizes.update(
        layer_types=list(cfg.layer_types),
        mlp_layer_types=list(cfg.mlp_layer_types),
        rope_parameters={"rope_theta": cfg.rope_theta},
        published={"num_experts": cfg.num_routed_experts},
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


def _rope(u, pos, theta):
    """Every entry of each head rotated, pairing ``(i, i + d/2)``; ``u``
    (N, heads, d), ``pos`` (N,)."""
    half = u.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = (pos.astype(jnp.float32)[:, None] * freq)[:, None, :]
    lo, hi = u[..., :half], u[..., half:]
    return jnp.concatenate([lo * jnp.cos(ang) - hi * jnp.sin(ang),
                            hi * jnp.cos(ang) + lo * jnp.sin(ang)], -1)


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


def _attend(q, k, v, window, query_block):
    """(N, H, d) queries over (N, H, d) keys and values, causal, the
    last ``window`` tokens where given: a block of queries at a time,
    against every key (full) or the ``query_block + window`` keys its
    windows can reach."""
    n, h, d = q.shape
    nq = _pieces(n, query_block)
    qb = n // nq
    span = n if window is None else min(n, qb + window)

    def block(i):
        lo = i * qb
        k_lo = 0 if window is None else jnp.clip(lo + qb - span, 0, n - span)
        qs = jax.lax.dynamic_slice_in_dim(q, lo, qb, 0)
        ks = jax.lax.dynamic_slice_in_dim(k, k_lo, span, 0)
        vs = jax.lax.dynamic_slice_in_dim(v, k_lo, span, 0)
        t = lo + jnp.arange(qb)[:, None]
        s = k_lo + jnp.arange(span)[None, :]
        seen = s <= t
        if window is not None:
            seen = seen & (s > t - window)
        sc = jnp.einsum("qhd,nhd->hqn", qs, ks) / math.sqrt(d)
        att = jax.nn.softmax(jnp.where(seen[None], sc, -jnp.inf), -1)
        return jnp.einsum("hqn,nhd->qhd", att, vs)

    return jax.lax.map(block, jnp.arange(nq)).reshape(n, h * d)


def reference_hidden(params, ids, sizes: dict, query_block: int = 128,
                     hidden_block: int = 4608, ignore_window: bool = False,
                     shared: bool = True):
    """(N,) ids -> (N, D) float32 residual stream after the last layer.
    ``ignore_window`` (every layer attends to every token before) and
    ``shared=False`` (the shared expert left out) are the CONTROLS the
    cell's limits were set against: a comparison that passes them checks
    nothing."""
    n = ids.shape[0]
    h, g, d = (sizes["num_attention_heads"], sizes["num_key_value_heads"],
               sizes["head_dim"])
    eps = sizes["rms_norm_eps"]
    theta = float(sizes["rope_parameters"]["rope_theta"])
    top_k, off = sizes["num_experts_per_tok"], _offset(sizes)
    pos = jnp.arange(n)
    x = _f32(params["embed"]["weight"][ids])
    for i in range(sizes["num_hidden_layers"]):
        lp = params["layers"][str(i)]
        w = lambda name: _f32(lp[name]["weight"])            # noqa: E731
        a = _rms(x, lp["attn_norm"]["scale"], eps)
        q = _rms((a @ w("q_proj")).reshape(n, h, d),
                 lp["q_norm"]["scale"], eps)
        k = _rms((a @ w("k_proj")).reshape(n, g, d),
                 lp["k_norm"]["scale"], eps)
        v = (a @ w("v_proj")).reshape(n, g, d)
        window = None
        if sizes["layer_types"][i] == "sliding_attention":
            q, k = _rope(q, pos, theta), _rope(k, pos, theta)
            window = None if ignore_window else sizes["sliding_window"]
        att = _attend(q, jnp.repeat(k, h // g, axis=1),  # head j reads j // 8
                      jnp.repeat(v, h // g, axis=1), window, query_block)
        x = x + att @ w("o_proj")

        t = _rms(x, lp["ffn_norm"]["scale"], eps)
        if sizes["mlp_layer_types"][i] == "dense":
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
        coef = sizes["routed_scaling_factor"] * top          # (N, routed)
        ex = lp["experts"]
        held = ex["gate"].shape[0]

        def expert(y, ew, t=t):
            c_e, g_w, u_w, d_w = ew                           # one expert
            hidden = _silu(t @ _f32(g_w).T) * (t @ _f32(u_w).T)
            return y + c_e[:, None] * (hidden @ _f32(d_w)), None

        y, _ = jax.lax.scan(
            expert, jnp.zeros_like(x),
            (coef[:, off:off + held].T, ex["gate"], ex["up"], ex["down"]))
        if shared:
            y = y + _swiglu(t, lp["shared"], hidden_block)
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
    dense paged decode kernel at this family's shapes over the traced
    part of the window. ``traced``: the program's counters over that
    part (they already count layers).

    - experts: every touched expert's three matrices read once a layer
      and call, 6 D F operations a token-expert pair computed here;
    - paged decode: the K and V rows a decode token step has to read: a
      full layer's every cached token, a window layer's last 128 at most
      (the window's pages, not the slot's): the engine's own count,
      ``serving_decode_kv_bytes_total{kind="live"}``, which splits the
      layers by kind; the driver's ``live_token_steps`` would count
      every layer as full."""
    del layers, live_token_steps, selected_token_steps
    d, f = sizes["hidden_size"], sizes["moe_intermediate_size"]
    touched = traced.get("serving_moe_experts_touched_total", 0.0)
    pairs = traced.get("serving_moe_assignments_total", 0.0)
    return {
        "moe_ffn_needed_bytes": touched * 3 * d * f * itemsize,
        "moe_ffn_needed_flops": pairs * 6.0 * d * f,
        "paged_decode_needed_bytes": traced.get(
            'serving_decode_kv_bytes_total{kind="live"}', 0.0),
    }
