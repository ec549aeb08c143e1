"""Sparse paged attention: a lightning indexer scores a slot's cached
tokens, the ``topk`` best are selected, and attention runs over those
tokens only (the DeepSeek-V3.2-style "lightning indexer + top-k token
selection", here over the serving engine's page pool).

Beside a layer's K and V pages the pool keeps one more row per token, the
**indexer key** ``kI`` (``Di`` values), stored a page at a time as
``(P, Di, page_size)``: tokens along the lanes. A page's scores are then
one ``(J, Di) @ (Di, page_size)`` product per query with nothing padded.
``Di`` = 64 is half a lane tile: token-major ``(P, page_size, Di)`` rows
the chip's compiler keeps page_size-minor as an entry parameter, and
copies the whole key pool to the kernel's layout and back in every call
(12 pool-sized copies in a 6-layer decode block). Tokens along the lanes
it keeps as stored; the engine then writes a token by rewriting its
page's ``(Di, page_size)`` tile whole (``ServingEngine._write_rows``),
because a scatter along the lane axis brings the same copies back
(PERF.md section 6, PR 28).

Index score of query ``t`` against cached token ``s``::

    I[t, s] = scale * sum_j w[t, j] * relu(qI[t, j] . kI[s])

Three kernels register with the shared kernel layer:

``lightning_indexer`` — the scores of ``C`` queries a slot against every
  cached token of the slot's pages, ``(S, C, mp * page_size)`` float32
  (positions at or past ``extent[s]`` read 0). Pallas: grid ``(S, mp /
  pb)``, block-table scalar prefetch, ``pb`` whole key pages a step.
``sparse_paged_decode`` — one query a slot over ``topk`` selected
  tokens: the selected K and V rows are gathered out of the pool (XLA
  gather, 2 x ``topk`` rows a slot where dense decode streams every
  page) into a contiguous run of ``topk / page_size`` pages a slot, and
  the paged decode body folds those. ``lax.top_k`` returns the selection
  best first, so the live ones are a prefix and the run needs no mask
  beyond a length.
``sparse_paged_prefill`` — a chunk of queries, each with its own
  selection: the paged prefill body with the selection as one more
  streamed input, applied beside the causal test inside the fold.

Selection itself is XLA (``lax.top_k``: ties go to the lower position).
A query at position ``p`` with ``p + 1 <= topk`` attends to everything
it can see, as the model defines.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.serving import decode_attention as DA

_FP32_DOT = jax.lax.Precision.HIGHEST


def _dot_precision(dtype):
    """True fp32 products for fp32 operands (Mosaic's default is one
    bf16 pass); bf16 operands multiply exactly into the fp32 sum."""
    return _FP32_DOT if jnp.dtype(dtype) == jnp.float32 \
        else jax.lax.Precision.DEFAULT


# ---------------------------------------------------------------------------
# lightning indexer
# ---------------------------------------------------------------------------

def _index_weights(w_idx, scale):
    """``(S, C, J)`` head weights -> ``(S, C, C*J)`` block-diagonal rows:
    row ``c`` holds ``scale * w[c, :]`` at columns ``c*J .. (c+1)*J``, so
    the weighted head sum of every query is ONE matmul against the
    ``(C*J, page_size)`` relu'd products."""
    s, c, j = w_idx.shape
    eye = jnp.eye(c, dtype=jnp.float32)
    w = w_idx.astype(jnp.float32) * scale
    return (eye[None, :, :, None] * w[:, None, :, :]).reshape(s, c, c * j)


def _indexer_lax(q_idx, w_idx, ik_pages, block_tables, extent, scale):
    s, c, j, di = q_idx.shape
    mp = block_tables.shape[1]
    ps = ik_pages.shape[-1]
    kg = ik_pages[block_tables]                        # (S, mp, Di, ps)
    dots = jnp.einsum("scjd,smdt->scjmt", q_idx, kg,
                      precision=_dot_precision(q_idx.dtype),
                      preferred_element_type=jnp.float32)
    w = w_idx.astype(jnp.float32) * scale
    scores = jnp.einsum("scj,scjmt->scmt", w, jnp.maximum(dots, 0.0),
                        precision=_FP32_DOT)
    scores = scores.reshape(s, c, mp * ps)
    tok = jnp.arange(mp * ps, dtype=jnp.int32)
    return jnp.where(tok[None, None, :] < extent[:, None, None], scores, 0.0)


def _indexer_kernel(bt_ref, ext_ref, q_ref, w_ref, *refs, page_size,
                    pages_per_block):
    pb = pages_per_block
    k_refs, o_ref = refs[:pb], refs[pb]
    sl, pj = pl.program_id(0), pl.program_id(1)
    extent = ext_ref[sl]
    rows = o_ref.shape[1]

    @pl.when(pj * pb * page_size >= extent)
    def _dead():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(pj * pb * page_size < extent)
    def _live():
        q = q_ref[0]                                   # (C*J, Di)
        w = w_ref[0]                                   # (C, C*J) f32
        for t in range(pb):
            dots = jax.lax.dot_general(
                q, k_refs[t][0], (((1,), (0,)), ((), ())),
                precision=_dot_precision(q.dtype),
                preferred_element_type=jnp.float32)    # (C*J, ps)
            sc = jax.lax.dot_general(
                w, jnp.maximum(dots, 0.0), (((1,), (0,)), ((), ())),
                precision=_FP32_DOT,
                preferred_element_type=jnp.float32)    # (C, ps)
            tok = (pj * pb + t) * page_size + jax.lax.broadcasted_iota(
                jnp.int32, (rows, page_size), 1)
            o_ref[0, :, t * page_size:(t + 1) * page_size] = jnp.where(
                tok < extent, sc, 0.0)


def _indexer_pallas(q_idx, w_idx, ik_pages, block_tables, extent, scale,
                    interpret, pages_per_block=1):
    s, c, j, di = q_idx.shape
    mp = block_tables.shape[1]
    ps = ik_pages.shape[-1]
    pb = max(1, min(int(pages_per_block), mp))
    while mp % pb:                      # whole blocks only: widths are pow2
        pb -= 1
    q2 = q_idx.reshape(s, c * j, di)
    wmat = _index_weights(w_idx, scale)

    def k_spec(t):
        def index(si, pj, bt, _ext):
            return (bt[si, pj * pb + t], 0, 0)
        return pl.BlockSpec((1, di, ps), index)

    def row_index(si, pj, *_prefetch):
        return (si, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(s, mp // pb),
        in_specs=[pl.BlockSpec((1, c * j, di), row_index),
                  pl.BlockSpec((1, c, c * j), row_index),
                  *[k_spec(t) for t in range(pb)]],
        out_specs=pl.BlockSpec((1, c, pb * ps),
                               lambda si, pj, *_prefetch: (si, 0, pj)),
    )
    kernel = functools.partial(_indexer_kernel, page_size=ps,
                               pages_per_block=pb)
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((s, c, mp * ps), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ) if not interpret else None,
        interpret=interpret, name="lightning_indexer",
    )(block_tables.astype(jnp.int32), extent.astype(jnp.int32), q2, wmat,
      *([ik_pages] * pb))


def lightning_index_scores(q_idx, w_idx, ik_pages, block_tables, extent, *,
                           scale=None, impl: str = "auto"):
    """Index scores of every query against its slot's cached tokens.

    ``q_idx`` (S, C, J, Di) indexer queries, ``w_idx`` (S, C, J) head
    weights, ``ik_pages`` (P, Di, page_size) cached indexer keys,
    ``block_tables`` (S, mp), ``extent`` (S,) tokens cached a slot.
    Returns (S, C, mp * page_size) float32, 0 at and past ``extent``.
    ``scale`` defaults to ``(J * Di) ** -0.5``."""
    from paddle_tpu import kernels
    return kernels.dispatch("lightning_indexer", q_idx, w_idx, ik_pages,
                            block_tables, extent, impl=impl, scale=scale)


def _indexer_scale(q_idx, scale):
    return (q_idx.shape[-2] * q_idx.shape[-1]) ** -0.5 if scale is None \
        else scale


def _indexer_kernel_pallas(q_idx, w_idx, ik_pages, block_tables, extent, *,
                           block_sizes, interpret, scale=None):
    return _indexer_pallas(q_idx, w_idx, ik_pages, block_tables, extent,
                           _indexer_scale(q_idx, scale), interpret,
                           block_sizes.get("pages_per_block", 1))


def _indexer_kernel_lax(q_idx, w_idx, ik_pages, block_tables, extent, *,
                        scale=None):
    return _indexer_lax(q_idx, w_idx, ik_pages, block_tables, extent,
                        _indexer_scale(q_idx, scale))


def _indexer_kernel_reference(q_idx, w_idx, ik_pages, block_tables, extent,
                              *, scale=None):
    """NumPy, a slot and a query at a time."""
    import numpy as np
    q = np.asarray(q_idx, np.float64)
    w = np.asarray(w_idx, np.float64)
    ik = np.asarray(ik_pages, np.float64)
    bt, ext = np.asarray(block_tables), np.asarray(extent)
    s, c, j, di = q.shape
    mp, ps = bt.shape[1], ik.shape[-1]
    scale = _indexer_scale(q_idx, scale)
    out = np.zeros((s, c, mp * ps))
    for sl in range(s):
        keys = np.concatenate([ik[p] for p in bt[sl]], axis=1)  # (Di, T)
        n = int(ext[sl])
        for r in range(c):
            dots = np.maximum(q[sl, r] @ keys[:, :n], 0.0)      # (J, n)
            out[sl, r, :n] = scale * (w[sl, r] @ dots)
    return jnp.asarray(out, jnp.float32)


def _make_indexer_sample(seed):
    import numpy as np
    s, c, j, di, ps, mp = ((3, 1, 2, 8, 8, 4), (2, 8, 4, 16, 8, 4),
                           (4, 4, 2, 8, 16, 2))[seed % 3]
    rng = np.random.default_rng(seed)
    num_pages = s * mp + 1
    q = jnp.asarray(rng.standard_normal((s, c, j, di)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((s, c, j)), jnp.float32)
    ik = jnp.asarray(rng.standard_normal((num_pages, di, ps)), jnp.float32)
    bt = jnp.asarray((rng.permutation(num_pages - 1)[:s * mp] + 1)
                     .reshape(s, mp), jnp.int32)
    ext = jnp.asarray(rng.integers(0, mp * ps + 1, s), jnp.int32)
    return (q, w, ik, bt, ext), {}


def _indexer_vmem_estimate(args, kwargs, blocks):
    q, _w, ik = args[0], args[1], args[2]
    s, c, j, di = q.shape
    ps = ik.shape[-1]
    pb = blocks.get("pages_per_block", 1)
    pad = lambda n, m: -(-n // m) * m                       # noqa: E731
    keys = pb * pad(di, 16) * pad(ps, 128) * ik.dtype.itemsize
    out = pad(c, 8) * pad(pb * ps, 128) * 4
    qw = pad(c * j, 16) * pad(di, 128) * q.dtype.itemsize \
        + pad(c, 8) * pad(c * j, 128) * 4
    temps = 2 * pad(c * j, 8) * pad(ps, 128) * 4
    return 2 * (keys + out + qw) + temps


# ---------------------------------------------------------------------------
# selection (XLA)
# ---------------------------------------------------------------------------

def select_decode(scores, lengths, topk):
    """Decode's selection: ``scores`` (S, T) of one query a slot over
    ``lengths[s]`` live tokens -> (indices (S, topk) best first, n (S,)
    how many of them are live). A slot of at most ``topk`` tokens selects
    all of them."""
    tok = jnp.arange(scores.shape[-1], dtype=jnp.int32)
    masked = jnp.where(tok[None, :] < lengths[:, None], scores, -jnp.inf)
    _vals, idx = jax.lax.top_k(masked, topk)
    return idx.astype(jnp.int32), jnp.minimum(lengths, topk)


def select_prefill(scores, chunk_starts, n_valid, topk):
    """Chunked prefill's selection: ``scores`` (S, C, T), query ``c`` of
    slot ``s`` at position ``chunk_starts[s] + c`` -> (S, C, T) float32,
    1 where that query may attend (beside the causal test, which the
    attention applies again). Ties at the threshold go to the lower
    position, as ``lax.top_k`` orders them."""
    s, c, t = scores.shape
    tok = jnp.arange(t, dtype=jnp.int32)
    pos = chunk_starts[:, None] + jnp.arange(c, dtype=jnp.int32)   # (S, C)
    seen = tok[None, None, :] <= pos[:, :, None]
    masked = jnp.where(seen, scores, -jnp.inf)
    vals, idx = jax.lax.top_k(masked, topk)
    thr = vals[..., -1:]                                           # (S,C,1)
    last_tie = jnp.max(jnp.where(vals == thr, idx, -1), axis=-1,
                       keepdims=True)
    chosen = (masked > thr) | ((masked == thr)
                               & (tok[None, None, :] <= last_tie))
    everything = (pos + 1 <= topk)[:, :, None]
    return (seen & (everything | chosen)).astype(jnp.float32)


# ---------------------------------------------------------------------------
# sparse decode: gather the selected rows, fold them with the paged body
# ---------------------------------------------------------------------------

def _gather_selected(k_pages, v_pages, block_tables, sel_idx):
    """The selected tokens' K and V rows as a pool of their own: slot
    ``s`` owns pages ``s*n .. (s+1)*n`` (``n = topk / page_size``), in
    selection order. An XLA row gather out of the ``(P*ps, lanes)`` view
    of the pool (the view moves nothing)."""
    p, ps, lanes = k_pages.shape
    s, topk = sel_idx.shape
    # a token's page out of the block table as a one-hot product (exact
    # in float32 for any page number below 2**24): the chip does a
    # scalar gather of 65536 table entries in 0.5 ms a layer
    onehot = jax.nn.one_hot(sel_idx // ps, block_tables.shape[1],
                            dtype=jnp.float32)
    page = jnp.einsum("stp,sp->st", onehot, block_tables.astype(
        jnp.float32), precision=_FP32_DOT).astype(jnp.int32)
    rows = (page * ps + sel_idx % ps).reshape(-1)
    n = topk // ps
    ks = k_pages.reshape(p * ps, lanes)[rows].reshape(s * n, ps, lanes)
    vs = v_pages.reshape(p * ps, lanes)[rows].reshape(s * n, ps, lanes)
    bt = jnp.arange(s * n, dtype=jnp.int32).reshape(s, n)
    return ks, vs, bt


def _sparse_decode_pallas(q, k_pages, v_pages, block_tables, sel_idx, n_sel,
                          *, block_sizes, interpret, scale=None):
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    ks, vs, bt = _gather_selected(k_pages, v_pages, block_tables, sel_idx)
    return DA._paged_decode_pallas(
        q, ks, vs, bt, n_sel, scale, interpret,
        pages_per_block=block_sizes.get("pages_per_block", 1),
        name="sparse_paged_decode")


def _sparse_decode_lax(q, k_pages, v_pages, block_tables, sel_idx, n_sel, *,
                       scale=None):
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    ks, vs, bt = _gather_selected(k_pages, v_pages, block_tables, sel_idx)
    return DA._paged_decode_lax(q, ks, vs, bt, n_sel, scale)


def _sparse_decode_reference(q, k_pages, v_pages, block_tables, sel_idx,
                             n_sel, *, scale=None):
    import numpy as np
    s, h, dh = q.shape
    if scale is None:
        scale = 1.0 / math.sqrt(dh)
    ps = k_pages.shape[1]
    kv = k_pages.shape[-1] // dh
    qn = np.asarray(q, np.float64)
    kp = np.asarray(k_pages, np.float64).reshape(-1, ps, kv, dh)
    vp = np.asarray(v_pages, np.float64).reshape(-1, ps, kv, dh)
    bt, idx, ns = (np.asarray(a) for a in (block_tables, sel_idx, n_sel))
    out = np.zeros((s, h, dh))
    for sl in range(s):
        toks = idx[sl, :int(ns[sl])]
        if not len(toks):
            continue
        k = kp[bt[sl, toks // ps], toks % ps]              # (n, kv, dh)
        v = vp[bt[sl, toks // ps], toks % ps]
        for hh in range(h):
            g = hh // (h // kv)
            sc = k[:, g] @ qn[sl, hh] * scale
            pr = np.exp(sc - sc.max())
            out[sl, hh] = (pr / pr.sum()) @ v[:, g]
    return jnp.asarray(out).astype(q.dtype)


def _make_sparse_decode_sample(seed):
    import numpy as np
    s, h, kv, dh, ps, mp, topk = ((3, 4, 2, 16, 4, 6, 8),
                                  (2, 8, 2, 32, 8, 4, 16),
                                  (4, 2, 2, 16, 4, 8, 8))[seed % 3]
    rng = np.random.default_rng(seed)
    num_pages = s * mp + 1
    q = jnp.asarray(rng.standard_normal((s, h, dh)), jnp.float32)
    kp = jnp.asarray(rng.standard_normal((num_pages, ps, kv * dh)),
                     jnp.float32)
    vp = jnp.asarray(rng.standard_normal((num_pages, ps, kv * dh)),
                     jnp.float32)
    bt = jnp.asarray((rng.permutation(num_pages - 1)[:s * mp] + 1)
                     .reshape(s, mp), jnp.int32)
    lengths = jnp.asarray(rng.integers(0, mp * ps + 1, s), jnp.int32)
    scores = jnp.asarray(rng.standard_normal((s, mp * ps)), jnp.float32)
    idx, n = select_decode(scores, lengths, topk)
    return (q, kp, vp, bt, idx, n), {}


def sparse_paged_decode_attention(q, k_pages, v_pages, block_tables,
                                  sel_idx, n_sel, *, scale=None,
                                  impl: str = "auto"):
    """One decode step of attention over each slot's SELECTED tokens.
    ``sel_idx`` (S, topk) cache positions, the first ``n_sel[s]`` live;
    ``topk`` a multiple of the page size. Returns (S, H, Dh)."""
    from paddle_tpu import kernels
    return kernels.dispatch("sparse_paged_decode", q, k_pages, v_pages,
                            block_tables, sel_idx, n_sel, impl=impl,
                            scale=scale)


# ---------------------------------------------------------------------------
# sparse chunked prefill: the paged prefill body under a per-query selection
# ---------------------------------------------------------------------------

def _sparse_prefill_pallas(q, k_pages, v_pages, block_tables, chunk_starts,
                           n_valid, selected, *, block_sizes, interpret,
                           scale=None):
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    return DA._paged_prefill_pallas(
        q, k_pages, v_pages, block_tables, chunk_starts, n_valid, scale,
        interpret, pages_per_block=block_sizes.get("pages_per_block", 1),
        selected=selected, name="sparse_paged_prefill")


def _sparse_prefill_lax(q, k_pages, v_pages, block_tables, chunk_starts,
                        n_valid, selected, *, scale=None):
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    return DA._paged_prefill_lax(q, k_pages, v_pages, block_tables,
                                 chunk_starts, n_valid, scale,
                                 selected=selected)


def _sparse_prefill_reference(q, k_pages, v_pages, block_tables,
                              chunk_starts, n_valid, selected, *,
                              scale=None):
    import numpy as np
    s, c, h, dh = q.shape
    if scale is None:
        scale = 1.0 / math.sqrt(dh)
    mp, ps = block_tables.shape[1], k_pages.shape[1]
    kv = k_pages.shape[-1] // dh
    qn = np.asarray(q, np.float64)
    kp = np.asarray(k_pages, np.float64)
    vp = np.asarray(v_pages, np.float64)
    bt, st, nv = (np.asarray(a) for a in (block_tables, chunk_starts,
                                          n_valid))
    sel = np.asarray(selected) > 0
    out = np.zeros((s, c, h, dh))
    for sl in range(s):
        k = kp[bt[sl]].reshape(mp * ps, kv, dh)
        v = vp[bt[sl]].reshape(mp * ps, kv, dh)
        for r in range(int(nv[sl])):
            keep = sel[sl, r] & (np.arange(mp * ps) <= int(st[sl]) + r)
            if not keep.any():
                continue
            for hh in range(h):
                g = hh // (h // kv)
                sc = k[keep, g] @ qn[sl, r, hh] * scale
                pr = np.exp(sc - sc.max())
                out[sl, r, hh] = (pr / pr.sum()) @ v[keep, g]
    return jnp.asarray(out).astype(q.dtype)


def _make_sparse_prefill_sample(seed):
    import numpy as np
    s, c, h, kv, dh, ps, mp, topk = ((3, 4, 4, 2, 16, 4, 6, 8),
                                     (2, 8, 8, 2, 32, 8, 4, 8),
                                     (4, 4, 2, 2, 16, 4, 8, 12))[seed % 3]
    rng = np.random.default_rng(seed)
    num_pages = s * mp + 1
    q = jnp.asarray(rng.standard_normal((s, c, h, dh)), jnp.float32)
    kp = jnp.asarray(rng.standard_normal((num_pages, ps, kv * dh)),
                     jnp.float32)
    vp = jnp.asarray(rng.standard_normal((num_pages, ps, kv * dh)),
                     jnp.float32)
    bt = jnp.asarray((rng.permutation(num_pages - 1)[:s * mp] + 1)
                     .reshape(s, mp), jnp.int32)
    starts = jnp.asarray(rng.integers(0, (mp - 1) * ps, s), jnp.int32)
    n_valid = jnp.asarray(rng.integers(0, c + 1, s), jnp.int32)
    scores = jnp.asarray(rng.standard_normal((s, c, mp * ps)), jnp.float32)
    selected = select_prefill(scores, starts, n_valid, topk)
    return (q, kp, vp, bt, starts, n_valid, selected), {}


def sparse_paged_prefill_attention(q, k_pages, v_pages, block_tables,
                                   chunk_starts, n_valid, selected, *,
                                   scale=None, impl: str = "auto"):
    """Batched chunked prefill where query ``c`` of slot ``s`` attends
    only to the cache positions ``selected[s, c]`` marks (and can see
    causally). Returns (S, C, H, Dh)."""
    from paddle_tpu import kernels
    return kernels.dispatch("sparse_paged_prefill", q, k_pages, v_pages,
                            block_tables, chunk_starts, n_valid, selected,
                            impl=impl, scale=scale)


# ---------------------------------------------------------------------------
# what the engine calls
# ---------------------------------------------------------------------------

def indexed_decode_attention(q, k_pages, v_pages, ik_pages, block_tables,
                             lengths, q_idx, w_idx, topk, *,
                             impl: str = "auto"):
    """Score, select, attend for one decode token a slot: ``q`` (S, H,
    Dh), ``q_idx`` (S, J, Di), ``w_idx`` (S, J), ``lengths`` the live
    tokens INCLUDING this one. Returns (attention (S, H, Dh), selected
    (S,) tokens attended a slot)."""
    idx, n_sel = indexed_decode_selection(ik_pages, block_tables, lengths,
                                          q_idx, w_idx, topk, impl=impl)
    att = sparse_paged_decode_attention(q, k_pages, v_pages, block_tables,
                                        idx, n_sel, impl=impl)
    return att, n_sel


def indexed_decode_selection(ik_pages, block_tables, lengths, q_idx, w_idx,
                             topk, *, impl: str = "auto"):
    """The first half of :func:`indexed_decode_attention`, for whoever
    wants to see the selection itself: (token indices (S, topk) best
    first, how many of them are live (S,))."""
    scores = lightning_index_scores(
        q_idx[:, None], w_idx[:, None], ik_pages, block_tables, lengths,
        impl=impl)[:, 0]
    return select_decode(scores, lengths, topk)


def indexed_prefill_attention(q, k_pages, v_pages, ik_pages, block_tables,
                              chunk_starts, n_valid, q_idx, w_idx, topk, *,
                              impl: str = "auto"):
    """Score, select, attend for a chunk of queries a slot: ``q`` (S, C,
    H, Dh), ``q_idx`` (S, C, J, Di), ``w_idx`` (S, C, J). Returns (S, C,
    H, Dh)."""
    scores = lightning_index_scores(q_idx, w_idx, ik_pages, block_tables,
                                    chunk_starts + n_valid, impl=impl)
    selected = select_prefill(scores, chunk_starts, n_valid, topk)
    return sparse_paged_prefill_attention(
        q, k_pages, v_pages, block_tables, chunk_starts, n_valid, selected,
        impl=impl)


# ---------------------------------------------------------------------------
# kernel-registry entries
# ---------------------------------------------------------------------------

def _sparse_tune_signature(args, kwargs):
    return DA._paged_sig(args[0], args[1], args[3]) \
        + (("topk", args[4].shape[-1]),)


def _register():
    from paddle_tpu import kernels
    kernels.register(kernels.KernelSpec(
        name="lightning_indexer",
        contract=kernels.KernelContract(
            version=1,
            arg_layouts={"q_idx": "(S,C,J,Di)", "w_idx": "(S,C,J)",
                         "ik_pages": "(P,Di,ps)",
                         "block_tables": "(S,mp) i32",
                         "extent": "(S,) i32"},
            out_layout="(S,C,mp*ps) f32",
            grid="(S, mp/pages_per_block) whole key pages, block-table "
                 "scalar prefetch, dead-block skip",
            block_candidates={"pages_per_block": (1, 2, 4, 8, 16)},
            atol=2e-5, rtol=2e-5),
        pallas_fn=_indexer_kernel_pallas,
        lax_fn=_indexer_kernel_lax,
        reference_fn=_indexer_kernel_reference,
        sample_inputs=_make_indexer_sample,
        pallas_sites=(
            "paddle_tpu.serving.sparse_attention:_indexer_pallas",),
        tune_signature=lambda args, kwargs: (
            ("s", args[0].shape[0]), ("c", args[0].shape[1]),
            ("j", args[0].shape[2]), ("d", args[0].shape[3]),
            ("ps", args[2].shape[-1]), ("mp", args[3].shape[1])),
        vmem_estimate=_indexer_vmem_estimate))
    pb_candidates = {"pages_per_block": (1, 2, 4)}
    kernels.register(kernels.KernelSpec(
        name="sparse_paged_decode",
        contract=kernels.KernelContract(
            version=1,
            arg_layouts={"q": "(S,H,Dh)", "k_pages": "(P,ps,KV*Dh)",
                         "v_pages": "(P,ps,KV*Dh)",
                         "block_tables": "(S,mp) i32",
                         "sel_idx": "(S,topk) i32", "n_sel": "(S,) i32"},
            out_layout="(S,H,Dh)",
            grid="XLA row gather of the selected tokens, then the paged "
                 "decode body over (S, topk/ps/pages_per_block)",
            block_candidates=pb_candidates, atol=2e-5, rtol=2e-5),
        pallas_fn=_sparse_decode_pallas,
        lax_fn=_sparse_decode_lax,
        reference_fn=_sparse_decode_reference,
        sample_inputs=_make_sparse_decode_sample,
        pallas_sites=(
            "paddle_tpu.serving.decode_attention:_paged_attend_pallas",),
        tune_signature=_sparse_tune_signature))
    kernels.register(kernels.KernelSpec(
        name="sparse_paged_prefill",
        contract=kernels.KernelContract(
            version=1,
            arg_layouts={"q": "(S,C,H,Dh)", "k_pages": "(P,ps,KV*Dh)",
                         "v_pages": "(P,ps,KV*Dh)",
                         "block_tables": "(S,mp) i32",
                         "chunk_starts": "(S,) i32",
                         "n_valid": "(S,) i32",
                         "selected": "(S,C,mp*ps) f32"},
            out_layout="(S,C,H,Dh)",
            grid="the paged prefill body, the selection streamed beside "
                 "the pages",
            block_candidates=pb_candidates, atol=2e-5, rtol=2e-5),
        pallas_fn=_sparse_prefill_pallas,
        lax_fn=_sparse_prefill_lax,
        reference_fn=_sparse_prefill_reference,
        sample_inputs=_make_sparse_prefill_sample,
        pallas_sites=(
            "paddle_tpu.serving.decode_attention:_paged_attend_pallas",),
        tune_signature=lambda args, kwargs: DA._paged_sig(
            args[0], args[1], args[3]),
        vmem_estimate=DA._paged_vmem_estimate))


_register()
