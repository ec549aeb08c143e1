"""DeepSeek-V3.2 (DeepSeek-AI, 2025-12; the ``-Exp`` release carries the
same config): the program's model from the published ``config.json`` keys,
a plain reference forward pass, and what the ``serve_lm`` runner asks a
family for.

61 layers of multi-head latent attention (128 heads; queries through a
latent of 1536, keys and values through one of 512 with ONE shared rotary
key of 64 a token; YaRN frequencies) whose queries attend to the 2048
cached tokens a lightning indexer (64 heads of 128, fed by the query latent)
scores best; 3 leading dense layers of 18432, then 256 sigmoid-routed
experts of 2048 that take 8 a token inside 4 of 8 groups, renormalised and
scaled by 2.5, beside one shared expert. The model CLASS is
``models/mla_moe_lm.py``, the one ``families/mistral4.py`` builds for
Mistral Small 4: two families, one class (this family maps
``first_k_dense_replace``, ``n_group``, ``topk_group``, ``scoring_func``
and the indexer's keys onto it, where that one refuses them). A
configuration may hold a chip's SHARE of each layer's experts and of the
vocabulary, as Mistral's does.

The reference follows ISSUE 55's equations and nothing of the program:
float32 ``jax.numpy``, the EXPANDED form (every head's keys and values
formed from the latent; nothing absorbed), the selection by sorting over
masked index scores, no kernel, no cache, no chunks, no batching; the
experts a dense weighted sum over every routed expert of which the held
ones' terms are kept. It reads the program's parameter tree and shares no
code with it. At the cell's sizes it works in blocks so that a
33.4k-token request fits beside 9.3 GB of served weights and the pages,
and leaves out what no answer needs, so that a checked request takes the
chip about 17 s and not 44 (PERF.md section 6, PR 55: the driver stops a
run at 360 s): the stream is the one (N, D) array; projections and the FFN go
over it ``row_block`` rows at a time, in place; each query's selection is
kept as one BIT a key; a layer's queries go in runs, a run against
the keys up to its own end, which are all it can see; one head's keys and
values at a time, its queries ``query_block`` at a time, eight
heads' outputs side by side into one product with their rows of W_o; a
dense layer ``moe_intermediate_size`` hidden units at a time, one expert at
a time over the rows routed to it; the LAST layer for the rows asked for
only; weights are cut while still in their served type; the vocabulary in
pieces. Call it under
``jax.default_matmul_precision("highest")``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

#: the kernels whose dispatches decide ``correct``: each must have run on
#: its Pallas body and never on its ``lax`` form
KERNELS = ("sparse_latent_prefill", "sparse_latent_decode",
           "lightning_indexer", "moe_grouped_ffn")

#: the published keys the program's config takes under the same name
_KEYS = ("vocab_size", "hidden_size", "num_hidden_layers",
         "num_attention_heads", "q_lora_rank", "kv_lora_rank",
         "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
         "rms_norm_eps", "max_position_embeddings", "n_routed_experts",
         "num_experts_per_tok", "moe_intermediate_size", "n_shared_experts",
         "routed_scaling_factor", "norm_topk_prob", "first_k_dense_replace",
         "intermediate_size", "scoring_func", "n_group", "topk_group",
         "index_n_heads", "index_head_dim", "index_topk")

#: ``rope_scaling`` key -> the program config's field
_ROPE = {"factor": "rope_factor",
         "original_max_position_embeddings":
             "original_max_position_embeddings",
         "beta_fast": "beta_fast", "beta_slow": "beta_slow",
         "mscale": "mscale", "mscale_all_dim": "mscale_all_dim"}


def _routed(sizes: dict) -> int:
    """The router's width: the published count of routed experts."""
    return sizes.get("published", {}).get("n_routed_experts",
                                          sizes["n_routed_experts"])


def _offset(sizes: dict) -> int:
    return sizes.get("expert_share", {}).get("offset", 0)


def _rope_of(sizes: dict) -> dict:
    """The rotary keys the reference reads: ``rope_scaling`` and
    ``rope_theta`` under one roof."""
    return dict(sizes["rope_scaling"], rope_theta=sizes["rope_theta"])


def model_config(sizes: dict, **kw):
    from paddle_tpu.models.mla_moe_lm import MLAMoELMConfig
    for flag, must in (("hidden_act", "silu"), ("scoring_func", "sigmoid"),
                       ("topk_method", "noaux_tc"),
                       ("tie_word_embeddings", False),
                       ("attention_bias", False),
                       ("num_nextn_predict_layers", 0)):
        if sizes.get(flag, must) != must:
            raise ValueError(f"the program is written for {flag}={must!r}")
    rope = sizes["rope_scaling"]
    if rope.get("type", "yarn") != "yarn":
        raise ValueError("the program is written for YaRN frequencies")
    given = {k: sizes[k] for k in _KEYS if k in sizes}
    return MLAMoELMConfig(
        num_routed_experts=_routed(sizes), expert_offset=_offset(sizes),
        rope_theta=float(sizes["rope_theta"]), llama_4_scaling_beta=0.0,
        **{field: float(rope[key]) if key != "original_max_position_embeddings"
           else int(rope[key]) for key, field in _ROPE.items()},
        **given, **kw)


def sizes_of(cfg) -> dict:
    """The published keys the reference reads, from a program config
    (:func:`model_config` the other way round)."""
    sizes = {k: getattr(cfg, k) for k in _KEYS}
    sizes.update(
        rope_theta=cfg.rope_theta,
        rope_scaling=dict({key: getattr(cfg, field)
                           for key, field in _ROPE.items()}, type="yarn"),
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


def _layer_norm(u, p, eps):
    mu = u.mean(-1, keepdims=True)
    var = ((u - mu) ** 2).mean(-1, keepdims=True)
    return (u - mu) / jnp.sqrt(var + eps) * _f32(p["scale"]) + _f32(p["bias"])


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


def _cut(w, start, size, axis):
    """``size`` rows or columns of a weight from ``start`` on, cut in the
    type it is served in, then float32."""
    return _f32(jax.lax.dynamic_slice_in_dim(w, start, size, axis))


def _rows(i, width, *arrays):
    return tuple(jax.lax.dynamic_slice_in_dim(a, i * width, width, 0)
                 for a in arrays)


def _selection(index, q_pos, topk):
    """(Q, N) index scores of queries at ``q_pos`` -> (Q, N) bool: every
    position a query sees while those are at most ``topk``, else the
    ``topk`` of largest score, ties to the lower position. By sorting,
    counting and a running count of the ties."""
    n = index.shape[-1]
    seen = jnp.arange(n)[None, :] <= q_pos[:, None]
    if n <= topk:
        return seen
    masked = jnp.where(seen, index, -jnp.inf)
    thr = jnp.sort(masked, axis=-1)[:, n - topk][:, None]
    above, ties = masked > thr, masked == thr
    need = topk - above.sum(-1, keepdims=True)
    chosen = above | (ties & (jnp.cumsum(ties, axis=-1) <= need))
    return seen & jnp.where((q_pos + 1 <= topk)[:, None], True, chosen)


_BITS = 32

#: the most runs a layer's queries go in (9 runs hold 56% of the square of
#: queries and keys, 29 would hold 52% and compile three times as long)
_RUNS = 9
#: heads whose outputs meet W_o in one product (a head at a time the
#: stream is read and written 128 times a layer)
_HEAD_GROUP = 8


def _pack(keep):
    """(Q, N) bool -> (Q, ceil(N / 32)) uint32, key ``s`` at bit ``s %
    32`` of word ``s // 32``."""
    q, n = keep.shape
    pad = -n % _BITS
    words = jnp.pad(keep, ((0, 0), (0, pad))).reshape(q, -1, _BITS)
    return jnp.sum(words.astype(jnp.uint32)
                   << jnp.arange(_BITS, dtype=jnp.uint32), -1,
                   dtype=jnp.uint32)


def _unpack(words, n):
    bits = (words[:, :, None] >> jnp.arange(_BITS, dtype=jnp.uint32)) & 1
    return bits.reshape(words.shape[0], -1)[:, :n] > 0


def _routed_weights(s, bias, sizes, group_limit):
    """Sigmoid scores ``s`` (T, E) -> (T, E) weights, 0 off the chosen."""
    t, e = s.shape
    groups, kept, k = (sizes["n_group"], sizes["topk_group"],
                       sizes["num_experts_per_tok"])
    pick = s + bias
    if group_limit and groups > 1:
        grouped = pick.reshape(t, groups, e // groups)
        g = jnp.sort(grouped, axis=-1)[..., -2:].sum(-1)
        best = jnp.argsort(-g, axis=-1, stable=True)[:, :kept]
        allowed = jnp.zeros((t, groups), bool).at[
            jnp.arange(t)[:, None], best].set(True)
        pick = jnp.where(allowed[:, :, None], grouped, -jnp.inf).reshape(t, e)
    order = jnp.argsort(-pick, axis=-1, stable=True)[:, :k]
    chosen = jnp.zeros((t, e), bool).at[jnp.arange(t)[:, None],
                                        order].set(True)
    top = jnp.where(chosen, s, 0.0)
    if sizes.get("norm_topk_prob", True):
        top = top / top.sum(-1, keepdims=True)
    return sizes.get("routed_scaling_factor", 1.0) * top


def reference_hidden(params, ids, sizes: dict, query_block: int = 128,
                     index_block: int = 32, row_block: int = 2048,
                     probe=None, lo=None, rows=None, expert_room: int = 4,
                     selection: bool = True, group_limit: bool = True,
                     index_rope: bool = True, scale_m2: bool = True):
    """(N,) ids -> (N, D) float32 residual stream after the last layer, or
    with ``rows`` the (rows, D) of positions ``lo .. lo + rows`` alone
    (``lo`` may be traced): the last layer then computes those queries
    only, against every key. With ``probe`` (Q,) query positions also (L,
    Q, N) bool, the positions each of those queries selects in each layer.
    A layer's queries go in at most ``_RUNS`` equal runs of whole query
    blocks, a run held against the keys up to its own end (no query sees a
    later one). ``selection=False`` (every query attends to all it sees),
    ``group_limit=False`` (the 8 best of all 256), ``index_rope=False``
    (the indexer's queries and keys not rotated) and ``scale_m2=False``
    (the softmax scale without YaRN's ``m^2``) are CONTROLS the cell's
    limits were set against, as :func:`round_weights` is."""
    n = ids.shape[0]
    h = sizes["num_attention_heads"]
    dc, dn, dr, dv = (sizes["kv_lora_rank"], sizes["qk_nope_head_dim"],
                      sizes["qk_rope_head_dim"], sizes["v_head_dim"])
    j, di, topk = (sizes["index_n_heads"], sizes["index_head_dim"],
                   sizes["index_topk"])
    f_moe = sizes["moe_intermediate_size"]
    eps, rope = sizes["rms_norm_eps"], _rope_of(sizes)
    f = float(rope["factor"])
    omega = _yarn(dr, rope)
    trig = _m(f, rope["mscale"]) / _m(f, rope["mscale_all_dim"])
    sigma = (dn + dr) ** -0.5 * (
        _m(f, rope["mscale_all_dim"]) ** 2 if scale_m2 else 1.0)
    off = _offset(sizes)
    pos = jnp.arange(n)
    rb = n // _pieces(n, row_block)
    blocks = _pieces(n, query_block)
    run = n // max(k for k in range(1, _RUNS + 1) if blocks % k == 0)
    hg = h // _pieces(h, _HEAD_GROUP)
    layers = sizes["num_hidden_layers"]

    def partly_rotated(u, p):
        if not index_rope:
            return u
        return jnp.concatenate([_rope(u[..., :dr], p, omega, trig),
                                u[..., dr:]], -1)

    x = _f32(params["embed"]["weight"][ids])
    selections = []
    for i in range(layers):
        lp = params["layers"][str(i)]
        w = lambda name: _f32(lp[name]["weight"])            # noqa: E731

        # what the attention reads of every token, a block of rows a time
        def project(k, x=x, lp=lp, w=w):
            xs, ps = _rows(k, rb, x, pos)
            a = _rms(xs, lp["attn_norm"]["scale"], eps)
            c_q = _rms(a @ w("q_a_proj"), lp["q_a_norm"]["scale"], eps)
            kv = a @ w("kv_a_proj")
            return (c_q, _rms(kv[:, :dc], lp["kv_a_norm"]["scale"], eps),
                    _rope(kv[:, dc:], ps, omega, trig),
                    partly_rotated(_layer_norm(a @ w("idx_k"),
                                               lp["idx_k_norm"], eps), ps),
                    j ** -0.5 * (a @ w("idx_w")))

        c_q, c, k_rope, k_i, w_i = (
            t.reshape((n,) + t.shape[2:])
            for t in jax.lax.map(project, jnp.arange(n // rb)))

        def index_scores(c_q_rows, w_rows, p, keys, lp=lp, k_i=k_i):
            q_i = partly_rotated(
                (c_q_rows @ _f32(lp["idx_q"]["weight"])).reshape(-1, j, di),
                p)
            dots = jnp.maximum(jnp.einsum("qjd,nd->qjn", q_i, k_i[:keys]),
                               0.0)
            return di ** -0.5 * jnp.einsum("qj,qjn->qn", w_rows, dots)

        if probe is not None:
            selections.append(_selection(
                index_scores(c_q[probe], w_i[probe], probe, n), probe, topk))

        # this layer's queries, and its spans (first query, queries, keys)
        if rows is not None and i == layers - 1:
            x, c_q, w_i, q_pos = (
                jax.lax.dynamic_slice_in_dim(t, lo, rows, 0)
                for t in (x, c_q, w_i, pos))
            spans = [(0, rows, n)]
        else:
            q_pos = pos
            spans = [(s, run, s + run) for s in range(0, n, run)]

        # each query's selection, one bit a key it can see
        def selected(first, count, keys, c_q=c_q, w_i=w_i, q_pos=q_pos,
                     index_scores=index_scores):
            ib = count // _pieces(count, index_block)

            def some(k):
                cs, ws, ps = _rows(k, ib, *(t[first:first + count]
                                            for t in (c_q, w_i, q_pos)))
                if not selection:
                    return _pack(jnp.arange(keys)[None, :] <= ps[:, None])
                return _pack(_selection(index_scores(cs, ws, ps, keys), ps,
                                        topk))

            keep = jax.lax.map(some, jnp.arange(count // ib))
            return keep.reshape(count, keep.shape[-1])

        keeps = [selected(*span) for span in spans]

        def head(g, o, lp=lp, c_q=c_q, c=c, k_rope=k_rope, q_pos=q_pos,
                 spans=spans, keeps=keeps):
            q_g = c_q @ _cut(lp["q_b_proj"]["weight"], g * (dn + dr),
                             dn + dr, 1)
            q_g = jnp.concatenate(
                [q_g[:, :dn], _rope(q_g[:, dn:], q_pos, omega, trig)], -1)
            kv_g = c @ _cut(lp["kv_b_proj"]["weight"], g * (dn + dv),
                            dn + dv, 1)         # this head's keys and values
            k_g = jnp.concatenate([kv_g[:, :dn], k_rope], -1)
            v_g = kv_g[:, dn:]

            def span_out(first, count, keys, keep):
                qb = count // _pieces(count, query_block)

                def block(k):
                    qs, bits = _rows(k, qb, q_g[first:first + count], keep)
                    score = sigma * (qs @ k_g[:keys].T)
                    return jax.nn.softmax(
                        jnp.where(_unpack(bits, keys), score, -jnp.inf),
                        -1) @ v_g[:keys]

                return jax.lax.map(block, jnp.arange(count // qb)).reshape(
                    count, dv)

            o_g = jnp.concatenate([span_out(*span, keep)
                                   for span, keep in zip(spans, keeps)])
            return jax.lax.dynamic_update_slice_in_dim(
                o, o_g, g % hg * dv, 1)

        # ``hg`` heads' outputs side by side, then their rows of W_o
        def heads(k, x, lp=lp, head=head):
            o = jax.lax.fori_loop(k * hg, (k + 1) * hg, head,
                                  jnp.zeros((x.shape[0], hg * dv)))
            return x + o @ _cut(lp["o_proj"]["weight"], k * hg * dv,
                                hg * dv, 0)

        x = jax.lax.fori_loop(0, h // hg, heads, x)

        # the FFN, a block of rows at a time, in place
        dense = i < sizes.get("first_k_dense_replace", 0)
        fb = x.shape[0] // _pieces(x.shape[0], row_block)

        def ffn(k, x, lp=lp, dense=dense, fb=fb):
            xs, = _rows(k, fb, x)
            t = _rms(xs, lp["ffn_norm"]["scale"], eps)

            def swiglu(p, width, t=t):
                """SwiGLU ``width`` wide, at most ``f_moe`` hidden units
                at a time."""
                m = width // _pieces(width, f_moe)

                def some(k, y):
                    hidden = _silu(t @ _cut(p["gate"]["weight"], k * m, m,
                                            1)) \
                        * (t @ _cut(p["up"]["weight"], k * m, m, 1))
                    return y + hidden @ _cut(p["down"]["weight"], k * m, m,
                                             0)
                return jax.lax.fori_loop(0, width // m, some,
                                         jnp.zeros_like(t))

            if dense:
                y = swiglu(lp["mlp"], sizes["intermediate_size"])
            else:
                coef = _routed_weights(
                    1.0 / (1.0 + jnp.exp(-(t @ _f32(
                        lp["router"]["weight"])))),
                    _f32(lp["router_bias"]), sizes, group_limit)
                ex = lp["experts"]
                held = ex["gate"].shape[0]

                # an expert's tokens first (its weight is positive where
                # it was chosen): ``room`` rows hold them all, ``expert_room`` times
                # what the router sends it of a block on average; a block
                # that sends it more goes through it whole
                room = max(fb * sizes["num_experts_per_tok"] * expert_room
                           // _routed(sizes), 1)

                def expert(y, ew, t=t, room=room):
                    c_e, g_w, u_w, d_w = ew                   # one expert

                    def through(ts):
                        return (_silu(ts @ _f32(g_w).T)
                                * (ts @ _f32(u_w).T)) @ _f32(d_w)

                    def few():
                        at = jnp.argsort(-c_e)[:room]
                        return y.at[at].add(c_e[at, None] * through(t[at]))

                    return jax.lax.cond(
                        (c_e > 0).sum() <= room, few,
                        lambda: y + c_e[:, None] * through(t)), None

                y, _ = jax.lax.scan(
                    expert,
                    swiglu(lp["shared"],
                           f_moe * sizes.get("n_shared_experts", 1)),
                    (coef[:, off:off + held].T, ex["gate"], ex["up"],
                     ex["down"]))
            return jax.lax.dynamic_update_slice_in_dim(x, xs + y, k * fb, 0)

        x = jax.lax.fori_loop(0, x.shape[0] // fb, ffn, x)
    return x if probe is None else (x, jnp.stack(selections))


def reference_logits(params, ids, sizes: dict, lo=0, rows=None,
                     query_block: int = 128, vocab_block: int = 8192,
                     probe=None, **more):
    """(1, N) ids -> (1, rows, V) float32 logits of positions ``lo .. lo
    + rows`` (all of them by default; ``lo`` may be traced) over the rows
    of the vocabulary held here, ``vocab_block`` rows of the head at a
    time into one buffer. With ``probe`` (Q,) query positions: (logits,
    (L, Q, N) bool selections of those queries). ``more``: the block sizes
    and controls of :func:`reference_hidden`."""
    hidden = reference_hidden(params, ids[0], sizes, query_block,
                              probe=probe, lo=lo, rows=rows, **more)
    x, selections = hidden if probe is not None else (hidden, None)
    rows = x.shape[0]
    x = _rms(x, params["final_norm"]["scale"], sizes["rms_norm_eps"])
    head = params["head"]["weight"]
    k = _pieces(head.shape[0], vocab_block)
    width = head.shape[0] // k

    def write(i, logits):
        return jax.lax.dynamic_update_slice_in_dim(
            logits, x @ _cut(head, i * width, width, 0).T, i * width, axis=1)

    logits = jax.lax.fori_loop(
        0, k, write, jnp.zeros((rows, k * width), jnp.float32))[None]
    return logits if probe is None else (logits, selections)


# -- what the traced window's kernels had to do -------------------------------

def kernel_needs(sizes: dict, itemsize: int, layers: int, traced: dict,
                 live_token_steps: float, selected_token_steps: float) -> dict:
    """Nominal operations and bytes of the expert kernel, the indexer and
    the two selecting latent kernels at this family's shapes over the
    traced part of the window, from the program's counters over that part
    (they already count layers): what the mathematics needs, whatever
    implements it.

    - experts: every touched expert's three matrices read once a layer
      and call, 6 D F operations a token-expert pair computed here;
    - selecting latent attention, a phase: for each (query token,
      SELECTED row) pair every head's score over ``d_c + d_r`` and
      weighted sum over ``d_c``, ``2 (2 d_c + d_r)`` operations a head
      (2,176; ``serving_latent_pairs_total`` by ``phase``). Operations
      alone: the least bytes are each DISTINCT selected row once a token
      step and layer, and which rows the slots of one document select in
      common only the device knows (the selection never reaches the
      host); at 242 operations a byte for the chip's 240 the operations
      are the binding side wherever no row is shared, so the share
      cannot read high for want of the bytes;
    - indexer: every index-key row it had to score
      (``serving_index_rows_scored_total``: a decode token step every
      live row of every slot, a prefill call each lane's rows once),
      ``Di`` values each, and ``2 J Di + 2 J`` operations a (query, row)
      pair: the decode steps' pairs are the rows, a chunk's its tokens
      times its lane's rows, which the counters do not give, so the
      operations are the decode steps' and a lower bound."""
    del layers, selected_token_steps
    d, f = sizes["hidden_size"], sizes["moe_intermediate_size"]
    dc, dr = sizes["kv_lora_rank"], sizes["qk_rope_head_dim"]
    j, di = sizes["index_n_heads"], sizes["index_head_dim"]
    pair_flops = sizes["num_attention_heads"] * 2.0 * (2 * dc + dr)
    needs = {
        "moe_ffn_needed_bytes": traced.get(
            "serving_moe_experts_touched_total", 0.0) * 3 * d * f * itemsize,
        "moe_ffn_needed_flops": traced.get(
            "serving_moe_assignments_total", 0.0) * 6.0 * d * f,
        "indexer_needed_bytes": traced.get(
            "serving_index_rows_scored_total", 0.0) * di * itemsize,
        "indexer_needed_flops": live_token_steps * sizes[
            "num_hidden_layers"] * (2.0 * j * di + 2.0 * j),
    }
    for phase in ("decode", "prefill"):
        needs[f"sparse_latent_{phase}_needed_flops"] = traced.get(
            f'serving_latent_pairs_total{{phase="{phase}"}}', 0.0) \
            * pair_flops
    return needs
