"""Qwen3-Next-80B-A3B (Qwen, 2025-09): the program's model from the
published ``config.json`` keys, a plain reference forward pass, and what
the ``serve_lm`` runner asks a family for.

48 layers, three gated delta-rule (Gated DeltaNet) linear-attention layers
to one gated full-attention layer (16 query heads over 2 KV heads of 256,
rotary over the first 64 entries, an output gate); every layer's
feed-forward part a softmax router over 512 experts of 512 that takes 10 a
token, renormalised, beside one shared expert behind a sigmoid gate. A
configuration may hold a chip's SHARE of each layer's experts and of the
vocabulary (``sizes["num_experts"]`` of ``sizes["published"]
["num_experts"]`` from ``sizes["expert_share"]["offset"]`` on): the router
keeps its width, the layer computes its own experts' part, and the
reference below is given the same share.

The reference follows ISSUE 57's equations (PERF.md section 4) and nothing
of the program: float32 ``jax.numpy``, no kernel, no cache, no chunks, no
batching; dense causal scores; the delta rule as a ``lax.scan`` over
tokens from a zero state; the experts as a dense weighted sum over every
routed expert of which the held ones' terms are kept. It reads the
program's parameter tree and shares no code with it. At the cell's sizes
it works in blocks (queries ``query_block`` at a time, one matrix cast to
float32 at a time, one expert at a time, the vocabulary in pieces, the
logits of the rows asked for only) so that a 5120-token request fits
beside the served weights, the state pool and the pages. Departures from
the published ``qwen3_next`` module are of layout only and listed in
``models/gated_delta_moe_lm.py``; what the config does not settle is in the
configuration file's ``assumed``. Call it under
``jax.default_matmul_precision("highest")``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

#: the kernels whose dispatches decide ``correct``: each must have run on
#: its Pallas body and never on its ``lax`` form
KERNELS = ("ragged_paged_prefill", "ragged_paged_decode", "moe_grouped_ffn",
           "gated_delta_chunk_scan", "gated_delta_decode_update")

#: the mechanisms ``benchmark/controls.py`` takes out of the reference, one
#: at a time, each of which the cell's two limits must call NOT correct:
#: what :func:`reference_logits` is handed, from the position of the first
#: checked row (the prompt's last token) and the engine's prefill chunk
CONTROLS = {
    "state_lost_at_last_chunk": lambda lo, chunk: dict(
        lose_state_at=lo // chunk * chunk),
    "no_decay": lambda lo, chunk: dict(decay=False),
    "beta_one": lambda lo, chunk: dict(beta_one=True),
    "no_output_gate": lambda lo, chunk: dict(gate=False),
}

#: what the benchmark's seeded weights draw the heads' time scales from
#: (``configs/qwen3_next_80b_a3b.json``, ``assumed.time_scales``): ``A``
#: uniform in 1..16 and ``dt`` log-uniform in 0.001..0.1, the gated delta
#: rule's reference draw; ``dt_bias`` is dt's inverse softplus
A_INIT_RANGE = (1.0, 16.0)
DT_INIT_RANGE = (1e-3, 1e-1)

#: the published keys the program's config takes under the same name
_KEYS = ("vocab_size", "hidden_size", "num_hidden_layers",
         "num_attention_heads", "num_key_value_heads", "head_dim",
         "rms_norm_eps", "partial_rotary_factor", "max_position_embeddings",
         "full_attention_interval", "linear_conv_kernel_dim",
         "linear_key_head_dim", "linear_value_head_dim",
         "linear_num_key_heads", "linear_num_value_heads", "num_experts",
         "num_experts_per_tok", "moe_intermediate_size",
         "shared_expert_intermediate_size", "norm_topk_prob")


def _routed(sizes: dict) -> int:
    """The router's width: the published count of routed experts."""
    return sizes.get("published", {}).get("num_experts",
                                          sizes["num_experts"])


def _offset(sizes: dict) -> int:
    return sizes.get("expert_share", {}).get("offset", 0)


def model_config(sizes: dict, **kw):
    from paddle_tpu.models.gated_delta_moe_lm import GatedDeltaMoELMConfig
    for flag, must in (("hidden_act", "silu"), ("tie_word_embeddings", False),
                       ("rope_scaling", None), ("use_sliding_window", False),
                       ("decoder_sparse_step", 1), ("mlp_only_layers", [])):
        if sizes.get(flag, must) != must:
            raise ValueError(f"the program is written for {flag}={must!r}")
    given = {k: sizes[k] for k in _KEYS if k in sizes}
    if "rope_theta" in sizes:
        given["rope_theta"] = float(sizes["rope_theta"])
    return GatedDeltaMoELMConfig(
        num_routed_experts=_routed(sizes), expert_offset=_offset(sizes),
        **given, **kw)


def sizes_of(cfg) -> dict:
    """The published keys the reference reads, from a program config
    (:func:`model_config` the other way round)."""
    sizes = {k: getattr(cfg, k) for k in _KEYS}
    sizes.update(rope_theta=cfg.rope_theta,
                 published={"num_experts": cfg.num_routed_experts},
                 expert_share={"offset": cfg.expert_offset})
    return sizes


def build(sizes: dict, *, interpret: bool = False):
    """The program's model for the published ``sizes``."""
    from paddle_tpu.models.gated_delta_moe_lm import GatedDeltaMoELM
    return GatedDeltaMoELM(model_config(
        sizes, a_init_range=A_INIT_RANGE, dt_init_range=DT_INIT_RANGE,
        kernel_impl="pallas_interpret" if interpret else "pallas"))


def positions(sizes: dict) -> int:
    return sizes["max_position_embeddings"]


def vocabulary(sizes: dict) -> int:
    """The rows of the vocabulary held here: the traffic draws its ids
    from them."""
    return sizes["vocab_size"]


# -- the plain reference ------------------------------------------------------

def _f32(a):
    return a.astype(jnp.float32)


def _rms1(u, w, eps):
    """The family's zero-centred norm: the scale is ``1 + w``."""
    return u / jnp.sqrt(jnp.mean(u * u, -1, keepdims=True) + eps) \
        * (1.0 + _f32(w))


def _silu(u):
    return u / (1.0 + jnp.exp(-u))


def _sigmoid(u):
    return 1.0 / (1.0 + jnp.exp(-u))


def _l2(y):
    return y / jnp.sqrt(jnp.sum(y * y, -1, keepdims=True) + 1e-6)


def _rope(u, pos, theta, rot):
    """Rotate-half pairing inside the first ``rot`` entries of each head,
    the rest as it is; ``u`` (N, heads, d), ``pos`` (N,)."""
    freq = theta ** (-jnp.arange(rot // 2, dtype=jnp.float32) * 2.0 / rot)
    ang = (pos.astype(jnp.float32)[:, None] * freq)[:, None, :]
    lo, hi, rest = u[..., :rot // 2], u[..., rot // 2:rot], u[..., rot:]
    return jnp.concatenate([lo * jnp.cos(ang) - hi * jnp.sin(ang),
                            hi * jnp.cos(ang) + lo * jnp.sin(ang), rest], -1)


def _pieces(n: int, limit: int) -> int:
    """The fewest equal pieces of ``n`` of at most ``limit`` each."""
    return next(k for k in range(1, n + 1) if n % k == 0 and n // k <= limit)


def _delta(lp, h, sizes, decay, beta_one, lose_state_at):
    """A gated delta-rule layer over the whole sequence from a zero
    state, token by token: ``h`` (N, D) normed input -> (N, D)."""
    n = h.shape[0]
    hk, hv = sizes["linear_num_key_heads"], sizes["linear_num_value_heads"]
    dk, dv = sizes["linear_key_head_dim"], sizes["linear_value_head_dim"]
    r, taps = hv // hk, sizes["linear_conv_kernel_dim"]
    qkvz = (h @ _f32(lp["in_proj_qkvz"]["weight"])).reshape(
        n, hk, 2 * dk + 2 * r * dv)
    ba = (h @ _f32(lp["in_proj_ba"]["weight"])).reshape(n, hk, 2 * r)
    z = qkvz[..., 2 * dk + r * dv:].reshape(n, hv, dv)
    b, a = ba[..., :r].reshape(n, hv), ba[..., r:].reshape(n, hv)
    mixed = jnp.concatenate([
        qkvz[..., :dk].reshape(n, -1), qkvz[..., dk:2 * dk].reshape(n, -1),
        qkvz[..., 2 * dk:2 * dk + r * dv].reshape(n, -1)], -1)
    padded = jnp.concatenate([jnp.zeros((taps - 1, mixed.shape[1])), mixed])
    w = _f32(lp["conv1d"]["weight"])                        # (channels, taps)
    u = _silu(sum(w[:, j] * padded[j:j + n] for j in range(taps)))
    q = _l2(u[:, :hk * dk].reshape(n, hk, dk)) / jnp.sqrt(float(dk))
    k = _l2(u[:, hk * dk:2 * hk * dk].reshape(n, hk, dk))
    v = u[:, 2 * hk * dk:].reshape(n, hv, dv)
    q, k = jnp.repeat(q, r, axis=1), jnp.repeat(k, r, axis=1)   # i: i // r
    beta = jnp.ones_like(b) if beta_one else _sigmoid(b)
    g = -jnp.exp(lp["A_log"]) * jnp.log1p(jnp.exp(a + lp["dt_bias"]))
    if not decay:
        g = jnp.zeros_like(g)
    lost = jnp.arange(n) == (-1 if lose_state_at is None else lose_state_at)

    def token(state, t):
        g_t, beta_t, q_t, k_t, v_t, lost_t = t
        state = jnp.where(lost_t, 0.0, state)
        state = jnp.exp(g_t)[:, None, None] * state             # (Hv,dk,dv)
        d = beta_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", state, k_t))
        state = state + k_t[:, :, None] * d[:, None, :]
        return state, jnp.einsum("hkv,hk->hv", state, q_t)

    _, o = jax.lax.scan(token, jnp.zeros((hv, dk, dv), jnp.float32),
                        (g, beta, q, k, v, lost))
    o = _f32(lp["norm"]["weight"]) * o / jnp.sqrt(
        jnp.mean(o * o, -1, keepdims=True) + sizes["rms_norm_eps"]) \
        * _silu(z)
    return o.reshape(n, hv * dv) @ _f32(lp["out_proj"]["weight"])


def _attention(ap, h, pos, sizes, gate, query_block):
    """A gated full-attention layer, a block of queries at a time against
    every key: ``h`` (N, D) -> (N, D)."""
    n = h.shape[0]
    hq, g, d = (sizes["num_attention_heads"], sizes["num_key_value_heads"],
                sizes["head_dim"])
    eps, theta = sizes["rms_norm_eps"], float(sizes["rope_theta"])
    rot = int(d * sizes["partial_rotary_factor"])
    qg = h @ _f32(ap["q_proj"]["weight"])       # every query, then every gate
    q = _rope(_rms1(qg[:, :hq * d].reshape(n, hq, d), ap["q_norm"]["weight"],
                    eps), pos, theta, rot)
    k = _rope(_rms1((h @ _f32(ap["k_proj"]["weight"])).reshape(n, g, d),
                    ap["k_norm"]["weight"], eps), pos, theta, rot)
    v = (h @ _f32(ap["v_proj"]["weight"])).reshape(n, g, d)
    kk = jnp.repeat(k, hq // g, axis=1)         # query head i reads i // 8
    vv = jnp.repeat(v, hq // g, axis=1)
    nq = _pieces(n, query_block)

    def attend(block):
        qh, p = block
        s = jnp.einsum("qhd,nhd->hqn", qh, kk) / jnp.sqrt(float(d))
        s = jnp.where((pos[None, :] <= p[:, None])[None], s, -jnp.inf)
        return jnp.einsum("hqn,nhd->qhd", jax.nn.softmax(s, -1), vv)

    o = jax.lax.map(attend, (q.reshape(nq, n // nq, hq, d),
                             pos.reshape(nq, n // nq))).reshape(n, hq * d)
    if gate:
        o = o * _sigmoid(qg[:, hq * d:])
    return o @ _f32(ap["o_proj"]["weight"])


def _moe(mp, t, sizes, shared):
    """Softmax over all the routed experts, the ten largest renormalised,
    the held experts' terms one expert at a time, the gated shared
    expert: ``t`` (N, D) -> (N, D)."""
    n = t.shape[0]
    p = jax.nn.softmax(t @ _f32(mp["gate"]["weight"]), -1)
    # the top_k largest, ties to the lower index
    order = jnp.argsort(-p, axis=-1, stable=True)[
        :, :sizes["num_experts_per_tok"]]
    picked = jnp.zeros_like(p, bool).at[jnp.arange(n)[:, None],
                                        order].set(True)
    coef = jnp.where(picked, p, 0.0)
    if sizes.get("norm_topk_prob", True):
        coef = coef / coef.sum(-1, keepdims=True)
    ex, off = mp["experts"], _offset(sizes)
    held = ex["gate"].shape[0]

    def expert(y, ew):
        c_e, g_w, u_w, d_w = ew                               # one expert
        hidden = _silu(t @ _f32(g_w).T) * (t @ _f32(u_w).T)
        return y + c_e[:, None] * (hidden @ _f32(d_w)), None

    y, _ = jax.lax.scan(
        expert, jnp.zeros_like(t),
        (coef[:, off:off + held].T, ex["gate"], ex["up"], ex["down"]))
    if shared:
        sp = mp["shared_expert"]
        hidden = _silu(t @ _f32(sp["gate_proj"]["weight"])) \
            * (t @ _f32(sp["up_proj"]["weight"]))
        y = y + _sigmoid(t @ _f32(mp["shared_expert_gate"]["weight"])) \
            * (hidden @ _f32(sp["down_proj"]["weight"]))
    return y


def reference_hidden(params, ids, sizes: dict, query_block: int = 256,
                     decay: bool = True, beta_one: bool = False,
                     gate: bool = True, shared: bool = True,
                     lose_state_at=None):
    """(N,) ids -> (N, D) float32 residual stream after the last layer.
    The CONTROLS the cell's limits were set against, each alone: ``decay=
    False`` (g = 0), ``beta_one`` (beta at 1), ``gate=False`` (the full
    layers' output gate left out), ``lose_state_at`` (every delta layer's
    state zeroed before that position, as a chunk that forgot to read its
    slot's row would), ``shared=False``: a comparison that passes them
    checks nothing."""
    eps = sizes["rms_norm_eps"]
    pos = jnp.arange(ids.shape[0])
    x = _f32(params["embed_tokens"]["weight"][ids])
    for i in range(sizes["num_hidden_layers"]):
        lp = params["layers"][str(i)]
        h = _rms1(x, lp["input_layernorm"]["weight"], eps)
        if (i + 1) % sizes["full_attention_interval"]:
            x = x + _delta(lp["linear_attn"], h, sizes, decay, beta_one,
                           lose_state_at)
        else:
            x = x + _attention(lp["self_attn"], h, pos, sizes, gate,
                               query_block)
        x = x + _moe(lp["mlp"], _rms1(
            x, lp["post_attention_layernorm"]["weight"], eps), sizes, shared)
    return x


def reference_logits(params, ids, sizes: dict, lo=0, rows=None,
                     query_block: int = 256, vocab_block: int = 8192,
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
    x = _rms1(x, params["norm"]["weight"], sizes["rms_norm_eps"])
    head = params["lm_head"]["weight"]
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
    """Nominal operations and bytes of the two delta-rule kernels, the
    grouped expert kernel and the two dense paged kernels at this family's
    shapes over the traced part of the window, from the program's counters
    over that part (they already count layers): what the work needs,
    whatever computes it.

    - a token of a value head: decay, ``S^T k``, the rank-one update and
      ``S^T q`` are 7 operations a state element (``dk dv``); it reads
      ``v`` and writes ``o`` (dv each), reads its key head's ``q`` and
      ``k`` (dk each, shared by ``r`` value heads) and ``g``, ``beta``,
      float32;
    - decode update: every live slot's state tiles (Hv dk dv float32)
      read once and written once a token step;
    - chunk scan: the tokens' rows as above and the state tiles the
      prefill calls read and wrote: the engine's state bytes less the
      decode steps' share (a lane reads its tiles once a chunk unless its
      prompt starts there, and writes them once), of which the tiles are
      all but the conv window's share; the factors XLA prepares around
      the kernel are in neither;
    - experts: every touched expert's three matrices read once a layer
      and call, 6 D F operations a token-expert pair computed here;
    - paged decode: the K and V rows the decode token steps of the two
      full layers had to read, the engine's own count
      (``serving_decode_kv_bytes_total{kind="live"}``: the state layers
      add nothing to it);
    - paged prefill: the full layers'
      ``serving_prefill_attn_pairs_total`` (query token, key token)
      pairs, each 16 heads x 2 x (256 + 256) operations (the score and the
      weighted value), and ``serving_prefill_kv_rows_total`` rows read
      once, each 2 KV heads x (256 + 256) values. Where the program has
      no such counters the prefill needs are left out."""
    del layers, live_token_steps, selected_token_steps
    hk, hv = sizes["linear_num_key_heads"], sizes["linear_num_value_heads"]
    dk, dv = sizes["linear_key_head_dim"], sizes["linear_value_head_dim"]
    tiles = 4.0 * hv * dk * dv
    window = 4.0 * (sizes["linear_conv_kernel_dim"] - 1) * (
        2 * hk * dk + hv * dv)
    token_rows = 4.0 * (2 * hv * dv + 2 * hk * dk + 2 * hv)
    token_flops = 7.0 * hv * dk * dv
    steps = traced.get("serving_ssm_decode_slot_steps_total", 0.0)
    tokens = traced.get("serving_ssm_prefill_tokens_total", 0.0)
    moved = sum(v for k, v in traced.items()
                if k.startswith("serving_ssm_state_bytes_total"))
    prefill_tiles = max(moved - 2.0 * steps * (tiles + window), 0.0) \
        * tiles / (tiles + window)
    d, f = sizes["hidden_size"], sizes["moe_intermediate_size"]
    touched = traced.get("serving_moe_experts_touched_total", 0.0)
    pairs = traced.get("serving_moe_assignments_total", 0.0)
    needs = {
        "delta_decode_needed_bytes": steps * (2.0 * tiles + token_rows),
        "delta_decode_needed_flops": steps * token_flops,
        "delta_chunk_needed_bytes": tokens * token_rows + prefill_tiles,
        "delta_chunk_needed_flops": tokens * token_flops,
        "moe_ffn_needed_bytes": touched * 3 * d * f * itemsize,
        "moe_ffn_needed_flops": pairs * 6.0 * d * f,
        "paged_decode_needed_bytes": traced.get(
            'serving_decode_kv_bytes_total{kind="live"}', 0.0),
    }
    scored = traced.get('serving_prefill_attn_pairs_total{layers="full"}', 0.0)
    if scored:
        wide = 2.0 * sizes["head_dim"]
        needs["paged_prefill_needed_flops"] = scored \
            * sizes["num_attention_heads"] * 2.0 * wide
        needs["paged_prefill_needed_bytes"] = traced.get(
            'serving_prefill_kv_rows_total{layers="full"}', 0.0) \
            * sizes["num_key_value_heads"] * wide * itemsize
    return needs
