"""Keye-VL-2.0-30B-A3B's language model (Kwai-Keye, 2026-06): the
program's model from the published ``text_config`` / ``sa_config`` keys,
a plain reference forward pass, and what the ``serve_lm`` runner asks a
family for.

The reference follows the equations of PERF.md section 4 and nothing of
the program: float32 ``jax.numpy``, no kernel, no cache, no batching;
dense causal scores with each query's selection as a mask; every expert
computed for every token and weighted by the token's routing coefficient
(0 where the token did not choose it). It reads the program's parameter
tree and shares no code with it. At the cell's sizes it works in blocks
(queries ``query_block`` at a time, one expert's weights cast to float32
at a time, the vocabulary in pieces) so that it fits beside the served
weights. Call it under ``jax.default_matmul_precision("highest")``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

#: the kernels whose dispatches decide ``correct``: each must have run on
#: its Pallas body and never on its ``lax`` form
KERNELS = ("ragged_paged_prefill", "lightning_indexer",
           "sparse_paged_decode", "sparse_paged_prefill", "moe_grouped_ffn")


def model_config(sizes: dict, **kw):
    from paddle_tpu.models.sparse_moe_lm import SparseMoELMConfig
    sa = sizes["sa_config"]
    return SparseMoELMConfig(
        vocab_size=sizes["vocab_size"], hidden_size=sizes["hidden_size"],
        num_hidden_layers=sizes["num_hidden_layers"],
        num_attention_heads=sizes["num_attention_heads"],
        num_key_value_heads=sizes["num_key_value_heads"],
        head_dim=sizes["head_dim"], rms_norm_eps=sizes["rms_norm_eps"],
        rope_theta=float(sizes["rope_theta"]),
        max_position_embeddings=sizes["max_position_embeddings"],
        num_experts=sizes["num_experts"],
        num_experts_per_tok=sizes["num_experts_per_tok"],
        moe_intermediate_size=sizes["moe_intermediate_size"],
        norm_topk_prob=sizes["norm_topk_prob"],
        indexer_num_heads=sa["indexer_num_heads"],
        indexer_head_dim=sa["indexer_head_dim"], indexer_topk=sa["topk"],
        **kw)


def sizes_of(cfg) -> dict:
    """The published keys the reference reads, from a program config
    (:func:`model_config` the other way round)."""
    return dict(
        vocab_size=cfg.vocab_size, hidden_size=cfg.hidden_size,
        num_hidden_layers=cfg.num_hidden_layers,
        num_attention_heads=cfg.num_attention_heads,
        num_key_value_heads=cfg.num_key_value_heads, head_dim=cfg.head_dim,
        rms_norm_eps=cfg.rms_norm_eps, rope_theta=cfg.rope_theta,
        max_position_embeddings=cfg.max_position_embeddings,
        num_experts=cfg.num_experts,
        num_experts_per_tok=cfg.num_experts_per_tok,
        moe_intermediate_size=cfg.moe_intermediate_size,
        norm_topk_prob=cfg.norm_topk_prob,
        sa_config=dict(indexer_num_heads=cfg.indexer_num_heads,
                       indexer_head_dim=cfg.indexer_head_dim,
                       topk=cfg.indexer_topk))


def build(sizes: dict, *, interpret: bool = False):
    """The program's model for the published ``sizes``."""
    from paddle_tpu.models.sparse_moe_lm import SparseMoELM
    return SparseMoELM(model_config(
        sizes, kernel_impl="pallas_interpret" if interpret else "pallas"))


def positions(sizes: dict) -> int:
    return sizes["max_position_embeddings"]


def vocabulary(sizes: dict) -> int:
    return sizes["vocab_size"]


# -- the plain reference ------------------------------------------------------

def _f32(a):
    return a.astype(jnp.float32)


def _rms(u, g, eps):
    return g * u / jnp.sqrt(jnp.mean(u * u, -1, keepdims=True) + eps)


def _layer_norm(u, p, eps=1e-6):
    mu = u.mean(-1, keepdims=True)
    var = ((u - mu) ** 2).mean(-1, keepdims=True)
    return (u - mu) / jnp.sqrt(var + eps) * _f32(p["scale"]) + _f32(p["bias"])


def _rope(u, pos, theta):
    """Rotate-half pairing ``(i, i + d/2)`` over the whole last axis;
    ``u`` (N, ..., d), ``pos`` (N,)."""
    d = u.shape[-1]
    freq = theta ** (-jnp.arange(d // 2, dtype=jnp.float32) * 2.0 / d)
    ang = pos.astype(jnp.float32)[:, None] * freq[None, :]
    ang = ang.reshape((u.shape[0],) + (1,) * (u.ndim - 2) + (d // 2,))
    lo, hi = u[..., :d // 2], u[..., d // 2:]
    return jnp.concatenate([lo * jnp.cos(ang) - hi * jnp.sin(ang),
                            hi * jnp.cos(ang) + lo * jnp.sin(ang)], -1)


def _selection(index_scores, q_pos, topk):
    """(Q, N) index scores of queries at ``q_pos`` -> (Q, N) bool: the
    positions each may attend to. All it can see while that is at most
    ``topk``; else the ``topk`` of largest score, ties to the lower
    position. By sorting, counting and a running count of the ties (the
    program uses ``lax.top_k``)."""
    n = index_scores.shape[-1]
    seen = jnp.arange(n)[None, :] <= q_pos[:, None]
    if n <= topk:
        return seen
    masked = jnp.where(seen, index_scores, -jnp.inf)
    thr = jnp.sort(masked, axis=-1)[:, n - topk][:, None]
    above = masked > thr
    ties = masked == thr
    need = topk - above.sum(-1, keepdims=True)
    chosen = above | (ties & (jnp.cumsum(ties, axis=-1) <= need))
    return seen & jnp.where((q_pos + 1 <= topk)[:, None], True, chosen)


def _query_block(n: int, limit: int) -> int:
    return max(b for b in range(1, min(n, limit) + 1) if n % b == 0)


def reference_hidden(params, ids, sizes: dict, query_block: int = 256,
                     probe=None):
    """(N,) ids -> (N, D) float32 residual stream after the last layer;
    with ``probe`` (Q,) query positions also (L, Q, N) bool, the positions
    each of those queries selects in each layer."""
    sa = sizes["sa_config"]
    n = ids.shape[0]
    h, kv, dh = (sizes["num_attention_heads"], sizes["num_key_value_heads"],
                 sizes["head_dim"])
    j, di, topk = sa["indexer_num_heads"], sa["indexer_head_dim"], sa["topk"]
    eps, theta = sizes["rms_norm_eps"], float(sizes["rope_theta"])
    n_top = sizes["num_experts_per_tok"]
    pos = jnp.arange(n)
    qb = _query_block(n, query_block)
    x = _f32(params["embed"]["weight"][ids])
    selections = []
    for i in range(sizes["num_hidden_layers"]):
        lp = params["layers"][str(i)]
        w = lambda name: _f32(lp[name]["weight"])            # noqa: E731
        a = _rms(x, _f32(lp["attn_norm"]["scale"]), eps)
        q = _rope(_rms((a @ w("q_proj")).reshape(n, h, dh),
                       _f32(lp["q_norm"]["scale"]), eps), pos, theta)
        k = _rope(_rms((a @ w("k_proj")).reshape(n, kv, dh),
                       _f32(lp["k_norm"]["scale"]), eps), pos, theta)
        v = (a @ w("v_proj")).reshape(n, kv, dh)
        q_i = _rope((a @ w("idx_q")).reshape(n, j, di), pos, theta)
        k_i = _rope(_layer_norm(a @ w("idx_k"), lp["idx_k_norm"]), pos, theta)
        w_i = a @ w("idx_w")                                  # (N, J)
        if probe is not None:
            dots = jnp.maximum(
                jnp.einsum("qjd,nd->qjn", q_i[probe], k_i), 0.0)
            selections.append(_selection(
                (j * di) ** -0.5 * jnp.einsum("qj,qjn->qn", w_i[probe], dots),
                probe, topk))

        kk = jnp.repeat(k, h // kv, axis=1)       # query head i reads i // 8
        vv = jnp.repeat(v, h // kv, axis=1)

        def attend(block, kk=kk, vv=vv, k_i=k_i):
            qh, qi, wi, p = block                             # a query block
            dots = jnp.maximum(jnp.einsum("qjd,nd->qjn", qi, k_i), 0.0)
            index = (j * di) ** -0.5 * jnp.einsum("qj,qjn->qn", wi, dots)
            keep = _selection(index, p, topk)                 # (Q, N)
            s = jnp.einsum("qhd,nhd->hqn", qh, kk) / jnp.sqrt(float(dh))
            s = jnp.where(keep[None], s, -jnp.inf)
            return jnp.einsum("hqn,nhd->qhd", jax.nn.softmax(s, -1), vv)

        blocks = tuple(t.reshape((n // qb, qb) + t.shape[1:])
                       for t in (q, q_i, w_i, pos))
        o = jax.lax.map(attend, blocks).reshape(n, h * dh)
        x = x + o @ w("o_proj")

        b = _rms(x, _f32(lp["ffn_norm"]["scale"]), eps)
        r = jax.nn.softmax(b @ w("router"), axis=-1)          # (N, E)
        nth = jnp.sort(r, axis=-1)[:, -n_top][:, None]
        chosen = r >= nth
        coef = jnp.where(chosen, r, 0.0)
        if sizes["norm_topk_prob"]:
            coef = coef / coef.sum(-1, keepdims=True)

        def expert(y, ew, b=b):
            c_e, g_w, u_w, d_w = ew                           # one expert
            g = b @ _f32(g_w).T
            hidden = g * jax.nn.sigmoid(g) * (b @ _f32(u_w).T)
            return y + c_e[:, None] * (hidden @ _f32(d_w)), None

        ex = lp["experts"]
        y, _ = jax.lax.scan(expert, jnp.zeros_like(x),
                            (coef.T, ex["gate"], ex["up"], ex["down"]))
        x = x + y
    return x if probe is None else (x, jnp.stack(selections))


def reference_logits(params, ids, sizes: dict, lo=0, rows=None,
                     query_block: int = 256, vocab_pieces: int = 8,
                     probe=None):
    """(1, N) ids -> (1, rows, V) float32 logits of positions ``lo ..
    lo + rows`` (all of them by default; ``lo`` may be traced). With
    ``probe`` (Q,) query positions: (logits, (L, Q, N) bool selections of
    those queries, :func:`reference_hidden`)."""
    hidden = reference_hidden(params, ids[0], sizes, query_block, probe)
    x, selections = hidden if probe is not None else (hidden, None)
    rows = x.shape[0] if rows is None else rows
    x = jax.lax.dynamic_slice_in_dim(x, lo, rows, axis=0)
    x = _rms(x, _f32(params["final_norm"]["scale"]), sizes["rms_norm_eps"])
    head = params["head"]["weight"]
    v = head.shape[0]
    if v % vocab_pieces:
        logits = (x @ _f32(head).T)[None]
    else:
        pieces = jax.lax.map(
            lambda wp: x @ _f32(wp).T,
            head.reshape(vocab_pieces, v // vocab_pieces, -1))
        logits = jnp.moveaxis(pieces, 0, 1).reshape(rows, v)[None]
    return logits if probe is None else (logits, selections)


# -- what the traced window's kernels had to do -------------------------------

def kernel_needs(sizes: dict, itemsize: int, layers: int, traced: dict,
                 live_token_steps: float, selected_token_steps: float) -> dict:
    """Nominal operations and bytes of each new kernel over the traced
    part of the window. ``traced``: the program's counters over that
    part; ``live_token_steps`` / ``selected_token_steps``: summed over
    every decode token step of every slot, the tokens cached and the
    tokens attended (the driver's count, as for ``paged_decode_bytes``).

    - experts: every touched expert's three matrices read once a layer
      and token step, 6 D F operations a token-expert pair;
    - indexer: decode only (a chunk's queries share one read of the keys
      and are left out, so the share is a lower bound): the indexer keys
      of every cached token, 2 J Di + 2 J operations a scored pair;
    - sparse decode: K and V rows of the selected tokens, 4 H Dh
      operations a query-token pair."""
    sa = sizes["sa_config"]
    d, f = sizes["hidden_size"], sizes["moe_intermediate_size"]
    j, di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    h, kv, dh = (sizes["num_attention_heads"], sizes["num_key_value_heads"],
                 sizes["head_dim"])
    touched = traced.get("serving_moe_experts_touched_total", 0.0)
    pairs = traced.get("serving_moe_assignments_total", 0.0)
    return {
        "moe_ffn_needed_bytes": touched * 3 * d * f * itemsize,
        "moe_ffn_needed_flops": pairs * 6.0 * d * f,
        "indexer_needed_bytes": live_token_steps * layers * di * itemsize,
        "indexer_needed_flops": live_token_steps * layers
        * (2.0 * j * di + 2.0 * j),
        "sparse_decode_needed_bytes": selected_token_steps * layers * 2
        * kv * dh * itemsize,
        "sparse_decode_needed_flops": selected_token_steps * layers * 4.0
        * h * dh,
    }
