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
page's ``(Di, page_size)`` tile whole (``layer_kinds._write_lane_rows``),
because a scatter along the lane axis brings the same copies back
(PERF.md section 6, PR 28).

Index score of query ``t`` against cached token ``s``::

    I[t, s] = scale * sum_j w[t, j] * relu(qI[t, j] . kI[s])

Six kernels register with the shared kernel layer:

``lightning_indexer`` — the scores of ``C`` queries a slot against every
  cached token of the slot's pages, ``(S, C, mp * page_size)`` float32
  (positions at or past ``extent[s]`` read 0). Pallas: grid ``(S, mp /
  pb)``, block-table scalar prefetch, ``pb`` whole key pages a step.
``sparse_paged_decode`` — one query a slot over ``topk`` selected
  tokens. The kernel reads the K and V pools themselves: it walks whole
  pages (a selected row cannot be fetched cheaper than the tile it lies
  in, and nearly every tile of a long context holds one) and takes the
  selection as a mask on the scores, ``(S, mp * page_size)`` float32,
  as the prefill kernel does. No copy of the selected rows is made.
  Slots whose block tables open with the same pages (requests over one
  published document) are folded into ONE walk of those pages, their
  queries stacked against one copy of each, and each slot's own pages
  are walked from the state that walk left it: two Pallas calls under
  the kernel's one name, the groups made on the host from the tables
  (``decode_attention.decode_groups``).
``sparse_paged_prefill`` — a chunk of queries, each with its own
  selection: the paged prefill body with the selection as one more
  streamed input, applied beside the causal test inside the fold.

``sparse_latent_decode`` / ``sparse_latent_prefill`` — the same over a
  LATENT cache (one row a token that every head reads, so only selected
  rows are folded): both walk whole pages as ``sparse_paged_decode``
  does, a group's shared ones once (a group: decoding slots over one
  document, or eight chunk tokens of a prefill lane), and compact each
  member's selected rows out of a page in VMEM by a one-hot product
  before they fold them (further down).

``topk_selection_mask`` — the selection, one rule for decode and
  prefill, as the mask both attention kernels take: ``(R, T)`` scores and
  how many of them each row sees -> ``(R, T)`` float32, 1 at the
  ``topk`` best (ties to the lower position), at all it sees where those
  are no more (a query at position ``p`` with ``p + 1 <= topk`` attends
  to everything it can see, as the model defines). No sort: a mask wants
  the VALUE of the ``topk``-th largest score, which 32 compare-and-count
  passes over a row's order-preserving integer keys give exactly, and 14
  more the last position taken at it. Pallas: a block of rows whole in
  VMEM, read once, written once. The reference forward, the selection
  replay (:func:`select_decode`) and the tests state the same rule with
  ``lax.top_k`` (:func:`selected_by_sort`), and share no code with it.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

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

def _index_weights(w_idx, scale, block=None):
    """``(S, C, J)`` head weights -> ``(S, C, C*J)`` block-diagonal rows:
    row ``c`` holds ``scale * w[c, :]`` at columns ``c*J .. (c+1)*J``, so
    the weighted head sum of every query is ONE matmul against the
    ``(C*J, page_size)`` relu'd products. ``block``: the same for every
    ``block`` queries of their own, ``(S, C, block*J)``, row ``c``'s
    weights at columns ``(c % block)*J ..``."""
    s, c, j = w_idx.shape
    w = w_idx.astype(jnp.float32) * scale
    if block is None:
        eye = jnp.eye(c, dtype=jnp.float32)
        return (eye[None, :, :, None] * w[:, None, :, :]).reshape(
            s, c, c * j)
    eye = jnp.eye(block, dtype=jnp.float32)
    return (eye[None, None, :, :, None]
            * w.reshape(s, c // block, 1, block, j)).reshape(
                s, c, block * j)


#: rows of one product of the indexer, ``C * J``: a chunk with more sums
#: its heads ``_QUERY_BLOCK`` queries at a time (256 queries of 64 heads
#: against ONE block-diagonal would be a 16.8 MB float32 operand a lane
#: and 256 times the useful products)
_ONE_PRODUCT_ROWS = 4096
_QUERY_BLOCK = 8
#: rows of a product up to which a block's key pages are joined into one
#: (the rows of the matrix unit)
_JOINED_ROWS = 128


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
                    pages_per_block, query_block=None, joined=False):
    pb = pages_per_block
    k_refs, o_ref = refs[:pb], refs[pb]
    sl, pj = pl.program_id(0), pl.program_id(1)
    extent = ext_ref[sl]
    rows = o_ref.shape[1]

    def scores(q, w, keys):
        dots = jax.lax.dot_general(
            q, keys, (((1,), (0,)), ((), ())),
            precision=_dot_precision(q.dtype),
            preferred_element_type=jnp.float32)
        return jax.lax.dot_general(
            w, jnp.maximum(dots, 0.0), (((1,), (0,)), ((), ())),
            precision=_FP32_DOT, preferred_element_type=jnp.float32)

    def blocked():
        """``query_block`` queries at a time: their heads' products with a
        page, the head sum against their own small block-diagonal."""
        qb = query_block
        hj = w_ref.shape[2]                           # query_block * J
        for t in range(pb):
            keys = k_refs[t][0]

            def some(b, _, t=t, keys=keys):
                at = pl.multiple_of(b * qb, qb)
                sc = scores(q_ref[0, pl.ds(pl.multiple_of(b * hj, hj), hj)],
                            w_ref[0, pl.ds(at, qb)], keys)
                tok = (pj * pb + t) * page_size \
                    + jax.lax.broadcasted_iota(jnp.int32, sc.shape, 1)
                o_ref[0, pl.ds(at, qb),
                      t * page_size:(t + 1) * page_size] = jnp.where(
                          tok < extent, sc, 0.0)

            jax.lax.fori_loop(0, rows // qb, some, None)

    @pl.when(pj * pb * page_size >= extent)
    def _dead():
        o_ref[...] = jnp.zeros_like(o_ref)

    def one_product():
        """The block's key pages side by side, ONE product for all of
        them: a decode step's few rows (64 heads of one query) leave the
        matrix unit idle through a product a page, and it is the count of
        products, not the bytes, that the walk then waits for (4.4 ms a
        layer of 64 slots x 261 pages at 9 pages a step; PR 55)."""
        keys = jnp.concatenate([k_refs[t][0] for t in range(pb)], axis=1)
        sc = scores(q_ref[0], w_ref[0], keys)
        tok = pj * pb * page_size + jax.lax.broadcasted_iota(
            jnp.int32, sc.shape, 1)
        o_ref[0] = jnp.where(tok < extent, sc, 0.0)

    if query_block is not None or joined:
        pl.when(pj * pb * page_size < extent)(
            one_product if joined else blocked)
        return

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
    # whole blocks only: a width that is no multiple (the engine's widest
    # bucket, a slot's own page count) is padded with the null page, whose
    # tokens lie past every extent
    width = mp
    block_tables = jnp.pad(block_tables, ((0, 0), (0, -mp % pb)))
    mp = block_tables.shape[1]
    q2 = q_idx.reshape(s, c * j, di)
    qb = _QUERY_BLOCK if c * j > _ONE_PRODUCT_ROWS and c % _QUERY_BLOCK == 0 \
        else None
    wmat = _index_weights(w_idx, scale, qb)
    # few rows against key pages that are whole lane tiles: the pages of
    # a block joined into one product (a chunk's many rows fill the
    # matrix unit a page at a time as they are)
    joined = qb is None and pb > 1 and c * j <= _JOINED_ROWS \
        and di % 128 == 0 and ps % 128 == 0

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
                  pl.BlockSpec((1, c, wmat.shape[2]), row_index),
                  *[k_spec(t) for t in range(pb)]],
        out_specs=pl.BlockSpec((1, c, pb * ps),
                               lambda si, pj, *_prefetch: (si, 0, pj)),
    )
    kernel = functools.partial(
        _indexer_kernel, page_size=ps, pages_per_block=pb,
        **({} if qb is None else {"query_block": qb}),
        **({"joined": True} if joined else {}))
    limit = {} if qb is None else {"vmem_limit_bytes": DA._WIDE_VMEM_LIMIT}
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((s, c, mp * ps), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"), **limit,
        ) if not interpret else None,
        interpret=interpret, name="lightning_indexer",
    )(block_tables.astype(jnp.int32), extent.astype(jnp.int32), q2, wmat,
      *([ik_pages] * pb))[:, :, :width * ps]


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
    # the heads' products of one block-diagonal: all the chunk's, or
    # ``_QUERY_BLOCK`` queries' where the chunk is summed in blocks
    rows = c * j if c * j <= _ONE_PRODUCT_ROWS or c % _QUERY_BLOCK \
        else _QUERY_BLOCK * j
    qw = pad(c * j, 16) * pad(di, 128) * q.dtype.itemsize \
        + pad(c, 8) * pad(rows, 128) * 4
    temps = 2 * pad(rows, 8) * pad(ps, 128) * 4
    return 2 * (keys + out + qw) + temps


# ---------------------------------------------------------------------------
# selection
# ---------------------------------------------------------------------------
#
# The rule, for a row of scores of which the first ``n`` are visible: all
# of them while ``n <= topk``; else the ``topk`` of largest score, ``-0.0``
# and ``+0.0`` one value, ties to the lower position: exactly ``topk``.
# It is stated twice. By sorting (``lax.top_k``: :func:`select_decode`,
# :func:`selected_by_sort`), for the reference forward, the selection
# replay and the tests. And by counting (:func:`_bisect`), for the engine:
# the kernels want a mask, never an order, so the value of the
# ``topk``-th largest score is built a bit at a time from 32 counts over
# the row, the last position taken at that value from 14 more, and the
# mask is one comparison with the two. Scores are finite.

_KEY_MIN = jnp.iinfo(jnp.int32).min


def _visible_scores(scores, n):
    """``scores`` (..., T), ``n`` (...,) -> (the positions, which of them
    each row sees, its scores with ``-inf`` at the others). ``lax.top_k``
    orders ``-0.0`` below ``+0.0``; here they are one value."""
    tok = jnp.arange(scores.shape[-1], dtype=jnp.int32)
    seen = tok < n[..., None]
    return tok, seen, jnp.where(
        seen, jnp.where(scores == 0.0, 0.0, scores), -jnp.inf)


def select_decode(scores, lengths, topk):
    """Decode's selection by sorting: ``scores`` (S, T) of one query a
    slot over ``lengths[s]`` live tokens -> (indices (S, topk) best
    first, n (S,) how many of them are live). A slot of at most ``topk``
    tokens selects all of them."""
    _tok, _seen, masked = _visible_scores(scores, lengths)
    _vals, idx = jax.lax.top_k(masked, topk)
    return idx.astype(jnp.int32), jnp.minimum(lengths, topk)


def selected_by_sort(scores, n, topk):
    """The rule by sorting, the reference statement of it: ``scores``
    (..., T), ``n`` (...,) visible -> (..., T) float32, 1 at the ``topk``
    positions ``lax.top_k`` returns (above the value of the last of them,
    or at it and no later than the last position taken there), at every
    visible position where ``n <= topk``."""
    tok, seen, masked = _visible_scores(scores, n)
    vals, idx = jax.lax.top_k(masked, topk)
    thr = vals[..., -1:]
    last_tie = jnp.max(jnp.where(vals == thr, idx, -1), axis=-1,
                       keepdims=True)
    chosen = (masked > thr) | ((masked == thr) & (tok <= last_tie))
    return (seen & ((n <= topk)[..., None] | chosen)).astype(jnp.float32)


def _order_keys(scores, seen):
    """float32 scores -> int32 keys of the same order: the bit pattern,
    the sign folded; ``-0.0`` (the one pattern that lands on -1) given
    ``+0.0``'s key, as IEEE ``==`` has them; below every score where the
    row cannot see."""
    bits = jax.lax.bitcast_convert_type(scores, jnp.int32)
    key = bits ^ ((bits >> 31) & 0x7FFFFFFF)
    return jnp.where(seen, jnp.where(key == -1, 0, key), _KEY_MIN)


def _bisect(count, rows, topk, t):
    """The key of the ``topk``-th largest entry of each of ``rows`` rows
    and the last position taken at that key, (rows, 1) int32 each.
    ``count(hit)`` -> (rows, 1) int32, how many of a row's entries
    ``hit(keys, positions)`` holds for. A row that sees fewer than
    ``topk`` gets the lowest key."""
    def key_bit(i, thr):
        # bit 31 first: the lowest key has it set, and clearing it is
        # the one step up that a set bit is for the other 31
        cand = thr ^ (jnp.int32(1) << (31 - i))
        enough = count(lambda key, pos: key >= cand) >= topk
        return jnp.where(enough, cand, thr)

    thr = jax.lax.fori_loop(0, 32, key_bit,
                            jnp.full((rows, 1), _KEY_MIN, jnp.int32))
    # what the entries above the threshold leave goes to the lowest
    # positions that hold it: the largest position with fewer before it
    need = topk - count(lambda key, pos: key > thr)
    bits = max(t - 1, 1).bit_length()

    def pos_bit(i, last):
        cand = last | (jnp.int32(1) << (bits - 1 - i))
        before = count(lambda key, pos: (key == thr) & (pos < cand))
        return jnp.where(before < need, cand, last)

    return thr, jax.lax.fori_loop(0, bits, pos_bit, jnp.zeros_like(thr))


def _selected(key, pos, n, thr, last, topk):
    seen = pos < n
    return (seen & ((n <= topk) | (key > thr)
                    | ((key == thr) & (pos <= last)))).astype(jnp.float32)


def _selection_lax(scores, n, *, topk):
    t = scores.shape[1]
    pos = jnp.arange(t, dtype=jnp.int32)[None, :]
    n = n.astype(jnp.int32)[:, None]
    key = _order_keys(scores, pos < n)

    def count(hit):
        return jnp.sum(hit(key, pos), axis=1, keepdims=True,
                       dtype=jnp.int32)

    thr, last = _bisect(count, scores.shape[0], topk, t)
    return _selected(key, pos, n, thr, last, topk)


def _lane_chunk(t):
    """The lanes one count adds up at a time: several registers a row
    block, so that the adds of a pass are no one chain."""
    return next((w for w in (512, 256, 128) if t % w == 0), t)


def _selection_kernel(n_ref, x_ref, o_ref, key_ref, *, topk):
    """A block of rows, whole: ``x_ref`` (rows, T) scores read once into
    ``key_ref`` (rows, T) int32 keys, every pass of :func:`_bisect` over
    those, ``o_ref`` (rows, T) written once. ``n_ref`` (rows, 1)."""
    rows, t = x_ref.shape
    w = _lane_chunk(t)
    n = n_ref[...]

    def chunk(c):
        lanes = slice(c * w, (c + 1) * w)
        return lanes, c * w + jax.lax.broadcasted_iota(
            jnp.int32, (rows, w), 1)

    for c in range(t // w):
        lanes, pos = chunk(c)
        key_ref[:, lanes] = _order_keys(x_ref[:, lanes], pos < n)

    def count(hit):
        acc = jnp.zeros((rows, w), jnp.int32)
        for c in range(t // w):
            lanes, pos = chunk(c)
            acc += hit(key_ref[:, lanes], pos).astype(jnp.int32)
        return jnp.sum(acc, axis=1, keepdims=True)

    thr, last = _bisect(count, rows, topk, t)
    for c in range(t // w):
        lanes, pos = chunk(c)
        o_ref[:, lanes] = _selected(key_ref[:, lanes], pos, n, thr, last,
                                    topk)


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _selection_pallas(scores, n, topk, interpret, rows_per_block):
    """Jitted, as the sparse decode's call is: a step program traces and
    lowers the body once and calls it from every layer."""
    r, t = scores.shape
    # whole sublane tiles of rows a grid step, no more than there are
    rows = min(rows_per_block, r + -r % 8)
    pad = -r % rows
    scores = jnp.pad(scores.astype(jnp.float32), ((0, pad), (0, 0)))
    n = jnp.pad(n.astype(jnp.int32), (0, pad))[:, None]

    def block(width):
        return pl.BlockSpec((rows, width), lambda i: (i, 0))

    out = pl.pallas_call(
        functools.partial(_selection_kernel, topk=topk),
        grid=((r + pad) // rows,),
        in_specs=[block(1), block(t)],
        out_specs=block(t),
        out_shape=jax.ShapeDtypeStruct((r + pad, t), jnp.float32),
        scratch_shapes=[pltpu.VMEM((rows, t), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)) if not interpret else None,
        interpret=interpret, name="topk_selection_mask",
    )(n, scores)
    return out[:r]


def _selection_kernel_pallas(scores, n, *, block_sizes, interpret, topk):
    return _selection_pallas(scores, n, topk, interpret,
                             block_sizes.get("rows_per_block", 8))


def _selection_vmem_estimate(args, kwargs, blocks):
    """A block of scores and one of the mask, double-buffered, and the
    keys between them."""
    return 5 * blocks.get("rows_per_block", 8) * args[0].shape[1] * 4


def _make_selection_sample(seed):
    """Three shapes by ``seed % 3``; scores of a few values, so that ties
    cross the threshold, with zeros of both signs; rows of every kind of
    ``n``: none, fewer than ``topk``, ``topk``, one more, all, past the
    row's end."""
    import numpy as np
    r, t, topk = ((5, 48, 8), (9, 256, 32), (16, 640, 100))[seed % 3]
    rng = np.random.default_rng(seed)
    scores = rng.choice(
        np.asarray([-0.0, 0.0, 0.25, 0.5, 1.0, -1.0], np.float32), (r, t))
    scores[::2] += (rng.standard_normal((-(-r // 2), t)) * 0.1).astype(
        np.float32) * (rng.random((-(-r // 2), t)) < 0.5)
    n = rng.integers(0, t + 1, r)
    n[:5] = (0, topk - 1, topk, topk + 1, t + 3)[:r]
    return (jnp.asarray(scores), jnp.asarray(n, jnp.int32)), {"topk": topk}


def select_decode_mask(scores, lengths, topk, *, impl: str = "auto"):
    """The rule by counting, what the engine's kernels take: ``scores``
    (S, T) of one query a slot over ``lengths[s]`` live tokens -> (S, T)
    float32, 1 at the tokens the slot's query attends to, none at or past
    ``lengths[s]``: element for element what :func:`selected_by_sort`
    marks."""
    from paddle_tpu import kernels
    return kernels.dispatch("topk_selection_mask", scores, lengths,
                            impl=impl, topk=topk)


def select_prefill(scores, chunk_starts, n_valid, topk, *,
                   impl: str = "auto"):
    """Chunked prefill's selection: ``scores`` (S, C, T), query ``c`` of
    slot ``s`` at position ``chunk_starts[s] + c`` sees that many tokens
    and itself -> (S, C, T) float32, 1 where that query may attend
    (beside the causal test, which the attention applies again)."""
    s, c, t = scores.shape
    n = chunk_starts[:, None] + jnp.arange(1, c + 1, dtype=jnp.int32)
    return select_decode_mask(scores.reshape(s * c, t), n.reshape(s * c),
                              topk, impl=impl).reshape(s, c, t)


# ---------------------------------------------------------------------------
# sparse decode: walk whole pages of the pools under the selection
# ---------------------------------------------------------------------------
#
# A row of the pool cannot be fetched cheaper than the tile it lies in,
# and a bf16 pool's tiles of 16 rows nearly all hold a selected row (2048
# of 15.7k tokens: 89% of them). So the body copies WHOLE pages out of
# the K and V pools, as dense decode does (``decode_attention
# ._paged_decode_walk_kernel``), and the selection is a mask on the
# scores, as the sparse prefill body takes it. It is two calls under one
# name, laid out as the latent decode's are: the pages that the tables of
# several decoding slots open with are walked ONCE a group of those slots
# (Part A, one grid step a group), and each slot's own pages a slot from
# the state Part A handed it (Part B, one grid step a slot); a slot in no
# group is Part B's alone, over all its pages. Who shares what comes from
# the host (``decode_attention.decode_groups``).
#
# The queries meet a block a KV HEAD at a time: the rows of one product
# are the ``Hq / kv`` query heads of that KV head (padded to 8), of every
# member of the group one under the other, against the head's 128-lane
# slice of the block. No row is pushed through another head's lanes, and
# the slice is whole lane tiles of the buffer as it lies.

#: what a member's rows in a KV head's product of Part A are padded to
#: (the query heads of one KV head; a float32 sublane tile)
_MEMBER_ROWS = 8


def _fold_selected(q, k, v, live, m_ref, l_ref, acc_ref):
    """One softmax update of one KV head's rows with a block of its
    tokens: ``q`` (rows, Dh), ``k`` / ``v`` (width, Dh) the head's lanes
    of the block, ``live`` (rows, width) which tokens each row attends
    to. Scores, maximum, sum and accumulator float32; the weights go into
    ``P V`` as their three bf16 terms (``_exact_page_dot``). A row
    that has met no live token yet sums under ``m = NEG_INF`` what the
    first live one wipes with ``alpha = 0``."""
    s = DA._exact_page_dot(q, k, 1)                          # (rows, width)
    s = jnp.where(live, s, DA.NEG_INF)
    m = m_ref[...]
    m_next = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m - m_next)                              # (rows, 128)
    p = jnp.exp(s - m_next[:, :1])
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    m_ref[...] = m_next
    acc_ref[...] = acc_ref[...] * alpha[:, :1] \
        + DA._exact_page_dot(p, v, 0)                        # (rows, Dh)


def _fold_block(q_ref, k_buf, v_buf, buf, live, m_scr, l_scr, acc_scr, *,
                width):
    """:func:`_fold_selected` of every KV head with the first ``width``
    tokens of block ``buf``: ``q_ref`` (kv, rows, Dh), the state ``(kv,
    rows, .)``, a head's lanes a slice of whole lane tiles."""
    kv, _, dh = q_ref.shape

    def one_kv_head(g, _):
        lanes = pl.ds(pl.multiple_of(g * dh, dh), dh)
        _fold_selected(q_ref[g], k_buf[buf, :width, lanes],
                       v_buf[buf, :width, lanes], live,
                       m_scr.at[g], l_scr.at[g], acc_scr.at[g])

    jax.lax.fori_loop(0, kv, one_kv_head, None)


def _selection_rows(sel_ref, first_page, n_pages, rows):
    """``sel_ref`` (1, mp, ps) a slot's selection a page a row -> (rows,
    n_pages * ps) bool: pages ``first_page ..`` side by side, every row
    the same."""
    ps = sel_ref.shape[-1]
    return jnp.concatenate(
        [jnp.broadcast_to(sel_ref[0, pl.ds(first_page + t, 1), :],
                          (rows, ps)) for t in range(n_pages)], axis=1) > 0


def _kv_moves(k_hbm, v_hbm, k_buf, v_buf, page_size):
    def moves(page, buf, t):
        rows = pl.ds(t * page_size, page_size)
        return ((k_hbm.at[page], k_buf.at[buf, rows]),
                (v_hbm.at[page], v_buf.at[buf, rows]))
    return moves


def _reset(m_scr, l_scr, acc_scr):
    m_scr[...] = jnp.full_like(m_scr, DA.NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)


def _sparse_decode_shared_kernel(bt_ref, gs_ref, gp_ref, q_ref, *refs,
                                 page_size, pages_per_block, members):
    """Part A: grid ``(groups,)``, one step a group of up to ``members``
    slots whose tables open with the same ``gp_ref[g]`` pages, whole
    blocks of them. ``q_ref`` (1, kv, members*8, Dh) holds the members'
    queries a KV head, a member's 8 rows under the member's before;
    ``refs`` opens with the members' selections (``members`` blocks (1,
    mp, ps), found by ``gs_ref``, as ``q_ref``'s rows were). Every row of
    a shared page is live for every member, so the mask is the selection
    alone. A member the group lacks folds some slot's rows, which nobody
    reads. The members' unnormalised float32 states ``[acc | m | l]``
    leave as the group's block (1, kv, members*8, Dh + 256)."""
    g, ps, pb = members, page_size, pages_per_block
    sel_refs = refs[:g]
    (k_hbm, v_hbm, st_ref, k_buf, v_buf, sems, first_buf, m_scr, l_scr,
     acc_scr) = refs[g:]
    dh = q_ref.shape[-1]
    member_rows = q_ref.shape[2] // g
    grp = pl.program_id(0)

    def walk_of(of):
        return jnp.maximum(gs_ref[of * g], 0), 0, gp_ref[of]

    def fold(block, buf, _pages):
        live = jnp.concatenate(
            [_selection_rows(sel, block * pb, pb, member_rows)
             for sel in sel_refs], axis=0)
        _fold_block(q_ref.at[0], k_buf, v_buf, buf, live, m_scr, l_scr,
                    acc_scr, width=pb * ps)

    pl.when(gp_ref[grp] > 0)(
        functools.partial(_reset, m_scr, l_scr, acc_scr))
    DA._page_walk(walk_of, fold, bt_ref,
                  _kv_moves(k_hbm, v_hbm, k_buf, v_buf, ps), sems,
                  first_buf, pages_per_block=pb)

    @pl.when(gp_ref[grp] > 0)
    def _hand_over():
        st_ref[0, :, :, :dh] = acc_scr[...]
        st_ref[0, :, :, dh:dh + 128] = m_scr[...]
        st_ref[0, :, :, dh + 128:] = l_scr[...]


def _sparse_decode_own_kernel(bt_ref, ext_ref, sp_ref, row_ref, q_ref,
                              sel_ref, st_ref, k_hbm, v_hbm, o_ref, k_buf,
                              v_buf, sems, first_buf, m_scr, l_scr, acc_scr,
                              *, page_size, pages_per_block, spare):
    """Part B: grid ``(S,)``, one step a slot. ``q_ref`` (1, kv, rows,
    Dh) the slot's queries a KV head (``rows``: the heads of one, padded
    to whole bf16 tiles), ``sel_ref`` (1, mp, ps) its selection. The
    state starts from what Part A left the slot (``st_ref`` (1, kv, 1, 8,
    Dh + 256), block ``row_ref[slot]`` of the states) where
    ``sp_ref[slot]`` of its pages were folded with its group's, from
    nothing where its block is the ``spare`` one; the walk goes over its
    own pages from there to the one that holds token ``ext_ref[slot] -
    1``, exactly the fetched pages folded, and the state is normalised
    into ``o_ref`` (1, kv, rows, Dh). The selection marks no token at or
    past the slot's extent, so it is the whole mask here too."""
    ps, pb = page_size, pages_per_block
    sl = pl.program_id(0)
    rows, dh = q_ref.shape[2:]

    def walk_of(of):
        n = (ext_ref[of] + ps - 1) // ps
        shared = jnp.minimum(sp_ref[of], n)
        return of, shared, n - shared

    def fold(block, buf, pages):
        first = walk_of(sl)[1] + block * pb

        def fold_pages(n_pages):
            _fold_block(q_ref.at[0], k_buf, v_buf, buf,
                        _selection_rows(sel_ref, first, n_pages, rows),
                        m_scr, l_scr, acc_scr, width=n_pages * ps)

        for n_pages in range(1, pb + 1):
            pl.when(pages == n_pages)(
                functools.partial(fold_pages, n_pages))

    _reset(m_scr, l_scr, acc_scr)

    @pl.when(row_ref[sl] != spare)
    def _from_the_group():
        own = slice(0, st_ref.shape[3])
        acc_scr[:, own] = st_ref[0, :, 0, :, :dh]
        m_scr[:, own] = st_ref[0, :, 0, :, dh:dh + 128]
        l_scr[:, own] = st_ref[0, :, 0, :, dh + 128:]

    DA._page_walk(walk_of, fold, bt_ref,
                  _kv_moves(k_hbm, v_hbm, k_buf, v_buf, ps), sems,
                  first_buf, pages_per_block=pb)
    denom = l_scr[...][:, :, :1]
    denom = jnp.where(denom == 0.0, 1.0, denom)
    alive = m_scr[...][:, :, :1] > DA.NEG_INF / 2
    o_ref[0] = jnp.where(alive, acc_scr[...] / denom, 0.0).astype(
        o_ref.dtype)


@functools.partial(jax.jit, static_argnums=(9, 10))
def _sparse_decode_pallas(q, k_pages, v_pages, block_tables, selected,
                          extent, group_slots, group_pages, shared_pages,
                          interpret, pages_per_block):
    """The two ``pallas_call``s of ``sparse_paged_decode``, Part A a
    group and Part B a slot, both under the kernel's one name. ``q`` is
    already scaled. Jitted, so that a step program traces and lowers the
    bodies once and calls them from every layer."""
    s_slots, h, dh = q.shape
    ps, hd = k_pages.shape[1:]
    mp = block_tables.shape[1]
    kv = hd // dh
    heads = h // kv                              # query heads a KV head
    n_groups, g = group_slots.shape
    # the bodies copy a page out of each pool as it lies and slice a KV
    # head's lanes out of the copy, which the chip's compiler does only
    # where both are whole tiles
    if not interpret and (dh % 128 or ps % (32 // k_pages.dtype.itemsize)):
        raise ValueError(
            f"sparse_paged_decode copies whole pages out of the pools: "
            f"pages of {ps} tokens, heads of {dh} lanes are not whole tiles")
    # rows of a KV head's product: a member's in Part A, a slot's in
    # Part B, which stacks nothing and pads to whole bf16 tiles itself
    member_rows = heads + -heads % _MEMBER_ROWS
    rows = heads + -heads % DA._HEAD_ROWS
    if (g * member_rows) % DA._HEAD_ROWS:
        raise ValueError(f"sparse_paged_decode stacks whole tiles of rows: "
                         f"groups of {g} x {member_rows} rows are none")
    pb = max(1, min(int(pages_per_block), mp))
    block_tables = block_tables.astype(jnp.int32)
    extent = extent.astype(jnp.int32)
    group_slots = group_slots.astype(jnp.int32).reshape(-1)
    # Part A folds whole blocks: what is left of a group's pages is walked
    # a slot (nothing, where the groups are ``decode_groups``')
    group_pages = group_pages.astype(jnp.int32) // pb * pb
    shared_pages = shared_pages.astype(jnp.int32) // pb * pb
    selected = selected.astype(jnp.float32).reshape(s_slots, mp, ps)
    # a slot's queries a KV head: (S, kv, heads, Dh), padded with zero
    # queries to the rows of each part
    q = q.reshape(s_slots, kv, heads, dh)
    q_own = jnp.pad(q, ((0, 0), (0, 0), (0, rows - heads), (0, 0)))
    members = jnp.maximum(group_slots, 0).reshape(n_groups, g)
    q_grp = jnp.pad(q, ((0, 0), (0, 0), (0, member_rows - heads), (0, 0))
                    )[members]                   # (groups, g, kv, 8, Dh)
    q_grp = q_grp.transpose(0, 2, 1, 3, 4).reshape(
        n_groups, kv, g * member_rows, dh)
    params = pltpu.CompilerParams(
        dimension_semantics=("arbitrary",)) if not interpret else None

    def scratch(n_rows):
        return [pltpu.VMEM((2, pb * ps, hd), k_pages.dtype),
                pltpu.VMEM((2, pb * ps, hd), v_pages.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.SMEM((1,), jnp.int32),
                pltpu.VMEM((kv, n_rows, 128), jnp.float32),
                pltpu.VMEM((kv, n_rows, 128), jnp.float32),
                pltpu.VMEM((kv, n_rows, dh), jnp.float32)]

    pools = [pl.BlockSpec(memory_space=pl.ANY),
             pl.BlockSpec(memory_space=pl.ANY)]
    width = dh + DA._STATE_LANES

    # Part A. A member's selection comes from its slot's block (a member
    # a group lacks reads slot 0's); a group's states go to the group's
    # block, those of every group without pages to one spare block
    def member_selection(j):
        return pl.BlockSpec(
            (1, mp, ps),
            lambda grp, _bt, gs, _gp: (jnp.maximum(gs[grp * g + j], 0), 0, 0))

    states = pl.pallas_call(
        functools.partial(_sparse_decode_shared_kernel, page_size=ps,
                          pages_per_block=pb, members=g),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(n_groups,),
            in_specs=[pl.BlockSpec((1, kv, g * member_rows, dh),
                                   lambda grp, *_prefetch: (grp, 0, 0, 0))]
            + [member_selection(j) for j in range(g)] + pools,
            out_specs=pl.BlockSpec(
                (1, kv, g * member_rows, width),
                lambda grp, _bt, _gs, gp: (
                    jnp.where(gp[grp] > 0, grp, n_groups), 0, 0, 0)),
            scratch_shapes=scratch(g * member_rows)),
        out_shape=jax.ShapeDtypeStruct(
            (n_groups + 1, kv, g * member_rows, width), jnp.float32),
        compiler_params=params,
        interpret=interpret,
        name="sparse_paged_decode",
    )(block_tables, group_slots, group_pages, q_grp, *[selected] * g,
      k_pages, v_pages)

    # Part B, from the state rows Part A left: member j of group grp at
    # block (grp, j) of the states seen a member a block
    spare = n_groups * g
    state_rows = DA._group_state_rows(group_slots, shared_pages, extent,
                                      spare)

    def slot_block(*shape):
        return pl.BlockSpec((1,) + shape,
                            lambda s, *_prefetch: (s,) + (0,) * len(shape))

    out = pl.pallas_call(
        functools.partial(_sparse_decode_own_kernel, page_size=ps,
                          pages_per_block=pb, spare=spare),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(s_slots,),
            in_specs=[slot_block(kv, rows, dh), slot_block(mp, ps),
                      pl.BlockSpec(
                          (1, kv, 1, member_rows, width),
                          lambda s, _bt, _ext, _sp, row: (
                              row[s] // g, 0, row[s] % g, 0, 0))]
            + pools,
            out_specs=slot_block(kv, rows, dh),
            scratch_shapes=scratch(rows)),
        out_shape=jax.ShapeDtypeStruct((s_slots, kv, rows, dh), q.dtype),
        compiler_params=params,
        interpret=interpret,
        name="sparse_paged_decode",
    )(block_tables, extent, shared_pages, state_rows, q_own, selected,
      states.reshape(n_groups + 1, kv, g, member_rows, width),
      k_pages, v_pages)
    return out[:, :, :heads].reshape(s_slots, h, dh)


def _sparse_decode_kernel_pallas(q, k_pages, v_pages, block_tables,
                                 selected, extent, group_slots, group_pages,
                                 shared_pages, *, block_sizes, interpret,
                                 scale=None):
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    return _sparse_decode_pallas(
        q * jnp.asarray(scale, q.dtype), k_pages, v_pages, block_tables,
        selected, extent, group_slots, group_pages, shared_pages, interpret,
        block_sizes.get("pages_per_block", 1))


def _sparse_decode_lax(q, k_pages, v_pages, block_tables, selected, extent,
                       *_groups, scale=None):
    """Attention a slot over the tokens its selection marks; which slots
    share pages changes no result."""
    s, h, dh = q.shape
    if scale is None:
        scale = 1.0 / math.sqrt(dh)
    kg = DA._gather_pages(k_pages, block_tables, h, dh)
    vg = DA._gather_pages(v_pages, block_tables, h, dh)
    scores = jnp.einsum("shd,smthd->shmt", q.astype(jnp.float32),
                        kg.astype(jnp.float32)) * scale
    p = DA._latent_softmax(scores.reshape(s, h, -1),
                           selected[:, None, :] > 0).reshape(scores.shape)
    return jnp.einsum("shmt,smthd->shd", p,
                      vg.astype(jnp.float32)).astype(q.dtype)


def _sparse_decode_reference(q, k_pages, v_pages, block_tables, selected,
                             extent, *_groups, scale=None):
    """NumPy, a slot and a head at a time over the slot's selected
    tokens: independent of both impls and of which slots share pages."""
    import numpy as np
    s, h, dh = q.shape
    if scale is None:
        scale = 1.0 / math.sqrt(dh)
    ps = k_pages.shape[1]
    kv = k_pages.shape[-1] // dh
    qn = np.asarray(q, np.float64)
    kp = np.asarray(k_pages, np.float64).reshape(-1, ps, kv, dh)
    vp = np.asarray(v_pages, np.float64).reshape(-1, ps, kv, dh)
    bt, sel = np.asarray(block_tables), np.asarray(selected) > 0
    out = np.zeros((s, h, dh))
    for sl in range(s):
        toks = np.flatnonzero(sel[sl])
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
    """Three shapes by ``seed % 3``: float32 pools of pages scattered
    over the pool, slots of every length from empty to full, some of
    them opening with the same pages (a pair; three and a pair; a whole
    group of eight), grouped as the engine groups them."""
    import numpy as np
    s, h, kv, dh, ps, mp, topk, sharers = (
        (3, 4, 2, 16, 4, 12, 8, (([0, 1], 8),)),
        (6, 8, 2, 32, 8, 10, 16, (([0, 1, 2], 9), ([3, 5], 8))),
        (9, 2, 2, 16, 4, 18, 8, ((list(range(8)), 16),)))[seed % 3]
    rng = np.random.default_rng(seed)
    num_pages = s * mp + 1
    q = jnp.asarray(rng.standard_normal((s, h, dh)), jnp.float32)
    kp = jnp.asarray(rng.standard_normal((num_pages, ps, kv * dh)),
                     jnp.float32)
    vp = jnp.asarray(rng.standard_normal((num_pages, ps, kv * dh)),
                     jnp.float32)
    tables = (rng.permutation(num_pages - 1)[:s * mp] + 1).reshape(
        s, mp).astype(np.int32)
    lengths = rng.integers(0, mp * ps + 1, s).astype(np.int32)
    for slots, k in sharers:
        tables[slots, :k] = tables[slots[0], :k]
        lengths[slots] = rng.integers(k * ps, mp * ps + 1, len(slots))
    scores = jnp.asarray(rng.standard_normal((s, mp * ps)), jnp.float32)
    selected = select_decode_mask(scores, jnp.asarray(lengths), topk)
    groups = DA.decode_groups(tables, lengths, np.arange(s), ps)
    return (q, kp, vp, jnp.asarray(tables), selected, jnp.asarray(lengths)
            ) + tuple(map(jnp.asarray, groups)), {}


def sparse_paged_decode_attention(q, k_pages, v_pages, block_tables,
                                  sel_idx, n_sel, *, scale=None,
                                  impl: str = "auto", groups=None):
    """One decode step of attention over each slot's SELECTED tokens,
    for whoever holds a selection as indices (the engine's own path hands
    the kernel a mask: :func:`indexed_decode_attention`). ``sel_idx`` (S,
    topk) cache positions, the first ``n_sel[s]`` live. ``groups`` as
    :func:`selected_decode_attention` takes them. Returns (S, H, Dh).

    A LATENT entry's first two pools (the latent and the rotary key, both
    token-major: ``layer_kinds.SelectingLatent``) are taken here too, and
    told from K and V by one rule: the queries are wider than the first
    pool's rows (``Dl + Dr > Dl``; a K pool's ``G x Dh`` lanes are never
    narrower than a query head). They go to ``sparse_latent_decode``,
    ``q`` the absorbed queries already scaled; returns (S, H, Dl)."""
    if q.shape[-1] > k_pages.shape[-1]:
        return sparse_latent_decode_attention(
            q, k_pages, v_pages, block_tables, sel_idx, n_sel, impl=impl)
    selected, extent = _mask_of_positions(
        sel_idx, n_sel, block_tables.shape[1] * k_pages.shape[1])
    return selected_decode_attention(q, k_pages, v_pages, block_tables,
                                     selected, extent, groups, scale=scale,
                                     impl=impl)


def selected_decode_attention(q, k_pages, v_pages, block_tables, selected,
                              extent, groups=None, *, scale=None,
                              impl: str = "auto"):
    """One decode step of attention over the tokens ``selected`` (S, mp *
    page_size) marks, 1 for a token the slot's query attends to and 0 for
    every other, none at or past ``extent[s]`` (S,): the kernel walks a
    slot's pages up to the one that holds token ``extent[s] - 1``.
    ``groups``: ``(group_slots, group_pages, shared_pages)`` as
    ``decode_attention.decode_groups`` makes them (groups of 8, as the
    latent decode's: at the docs cell's geometry groups of 4 read 0.64 ms
    a call for 0.53, PERF.md section 6, PR 44), the slots whose tables
    open with the same pages: the kernel reads those pages once a group;
    None: every slot walked alone. The result is attention a slot either
    way. Returns (S, H, Dh)."""
    from paddle_tpu import kernels
    if groups is None:      # the shapes of a table that groups no slot
        groups = DA.decode_groups(block_tables, extent, (), 1)
    return kernels.dispatch("sparse_paged_decode", q, k_pages, v_pages,
                            block_tables, selected, extent, *groups,
                            impl=impl, scale=scale)


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
# selection over a latent cache: whole pages read once a group of queries,
# each member's selected rows compacted in VMEM and folded there
# ---------------------------------------------------------------------------
#
# A latent row is read by EVERY head (128 of them at the published widths),
# so a row folded under a mask costs as much as a selected one: walking
# whole pages as ``sparse_paged_decode`` does would push 33k rows through
# 128 heads to keep 2048 (16 times the products; PERF.md section 6, PR 55).
# So both phases fold the selected rows of the latent pool and of the
# rotary-key pool only, BOTH token-major (a row of 1 KB and one of 256
# bytes a token; the rotary pool's rows are whole lane tiles, the key in
# their first ``Dr`` lanes: a token-major pool of 64 lanes the chip's
# compiler keeps page-minor and re-lays whole, 40 MB a layer).
#
# Both phases take the selection as the MASK (``select_decode_mask``) and
# make no copy of the selected rows in HBM: XLA's gather of them is bound by
# rows (30 ns a row of 1 KB, 4% of the HBM peak: PERF.md section 6, PR 55),
# whole pages by bytes, and the queries that ask about one document (the
# slots over a published one in decode, the tokens of a lane's chunk in
# prefill) read the same pages. So the walk is ``sparse_paged_decode``'s
# (Part A a group of queries over the pages their tables open with, Part B
# a query over its own from the state Part A left it,
# ``decode_attention._page_walk``), and what is new is between the copy and
# the fold: a page in VMEM is not folded under the mask (every one of its
# 128 rows would meet all 128 heads to keep 8) but COMPACTED first. A
# member's mask of the page gives each selected row its rank among the
# page's selected rows (one product with a triangle a block), the one-hot
# ``(members x width, page_size)`` of ranks ``first .. first + width``
# times the page ``(page_size, Dl | Dr)`` is every member's selected rows
# of those ranks, bit for bit (a row times 1, the others times 0), and a
# page where some member selects more than ``width`` rows takes further
# passes of the same product over the next ranks, so nothing is dropped. A
# block's first passes (one a page, straight-line code) fill a member's
# ``pages_per_block x width`` compacted rows, which are folded against all
# its heads in one softmax update (scores, maximum, sums float32; the
# weights meet the latents in the pool's type: one bf16 pass, a float32
# pool at ``HIGHEST``), the rows past a page's count masked; the further
# passes collect in a second block of the same shape, from block to block,
# folded when it is full and at the walk's end.

#: rows a member's selection of one page is compacted into a pass: a bf16
#: tile (2048 of 33k tokens select a mean of 7.85 of a page's 128)
_COMPACT_ROWS = 16


def _page_ranks(sel):
    """``sel`` (n, ps) float32, 1 at a page's selected tokens -> ((n, ps)
    float32: a selected token's 1-based rank among its page's selected
    ones, 0 at every other; (n, 1) how many the page has). One product
    with a triangle, exact (counts of at most ``ps`` in float32)."""
    ps = sel.shape[1]
    upto = (jax.lax.broadcasted_iota(jnp.int32, (ps, ps), 0)
            <= jax.lax.broadcasted_iota(jnp.int32, (ps, ps), 1))
    running = DA._exact_page_dot(sel.astype(jnp.bfloat16),
                                 upto.astype(jnp.bfloat16), 0)
    return running * sel, running[:, ps - 1:]


def _one_hot_of_ranks(rank_rows, first, width, dtype):
    """``rank_rows``: a member's ranks of one page each, (1, ps) as
    :func:`_page_ranks` gives them -> (members * width, ps) in ``dtype``:
    row ``m * width + r`` is 1 at member ``m``'s selected token of rank
    ``first + r + 1`` and 0 elsewhere (all 0 where it has no such)."""
    ps = rank_rows[0].shape[1]
    want = (jax.lax.broadcasted_iota(jnp.int32, (width, ps), 0)
            + first + 1).astype(jnp.float32)
    return jnp.concatenate(
        [(jnp.broadcast_to(row, (width, ps)) == want).astype(jnp.float32)
         for row in rank_rows], axis=0).astype(dtype)


def _compact(one_hot, page):
    """The rows of ``page`` (ps, D) that ``one_hot`` (n, ps) marks, one a
    row, exactly: a product in the page's type whose every sum has one
    term."""
    return DA._exact_page_dot(one_hot, page, 0).astype(page.dtype)


class _Compaction(NamedTuple):
    """The scratch between a walk's copies and its folds, ``g`` members
    of ``H`` heads, a row ``D = Dl + Drl`` lanes (the latent, then the
    rotary key's): a block's FIRST pass of every page lands in ``rows``
    (page ``t`` of the block in rows ``t * width ..``) and is folded with
    the block; the further passes of the pages that need them collect in
    ``more``, as many passes a fold, from block to block (``n = pb *
    width`` rows a member either way)."""
    ranks: object       # (g * pb, ps) f32: the block's ranks, rows (m, t)
    counts: object      # (g * pb, n) f32: a page's count, a lane
    most: object        # (pb, 128) f32: the most any member selects of it
    rows: object        # (g, n, D): the first passes' rows
    live: object        # (g, n) f32: a page's count, a lane
    more: object        # (g, n, D): the further passes' rows
    more_live: object   # (g, n) f32: rows a pass holds, a lane
    filled: object      # SMEM (1,): further passes since their last fold
    scores: object      # (g, H, n) f32: a fold's scores, then
    weights: object     # (g, H, n): its weights in the pool's type, and
    decay: object       # (g, H, 128) f32: what its maxima wipe of the state


def _compaction_scratch(g, h, pb, ps, d, dtype, width):
    n = pb * width
    return [pltpu.VMEM((g * pb, ps), jnp.float32),
            pltpu.VMEM((g * pb, n), jnp.float32),
            pltpu.VMEM((pb, 128), jnp.float32),
            pltpu.VMEM((g, n, d), dtype),
            pltpu.VMEM((g, n), jnp.float32),
            pltpu.VMEM((g, n, d), dtype),
            pltpu.VMEM((g, n), jnp.float32),
            pltpu.SMEM((1,), jnp.int32),
            pltpu.VMEM((g, h, n), jnp.float32),
            pltpu.VMEM((g, h, n), dtype),
            pltpu.VMEM((g, h, 128), jnp.float32)]


def _clear_compaction(cm):
    """Before a call's first walk: no further pass waiting, and no row of
    the compacted blocks left as the scratch came (a dead row meets a
    weight of 0, which does not wipe a NaN)."""
    for ref in (cm.rows, cm.more, cm.more_live):
        ref[...] = jnp.zeros_like(ref)
    cm.filled[0] = 0


def _across(x, lanes):
    """A ``(rows, 128)`` statistic, every lane its row's value, against
    ``lanes`` columns: itself side by side where those are whole lane
    tiles (no lane moves: ``decode_attention._row_values``), else its
    first column."""
    if lanes % x.shape[1]:
        return x[:, :1]
    return jnp.concatenate([x] * (lanes // x.shape[1]), axis=1)


def _fold_compacted(q_ref, rows, live, cm, m_scr, l_scr, acc_scr, *, width):
    """One softmax update of every member's heads with its compacted
    rows ``rows`` (g, n, D): ``q_ref`` (g * H, D) the members' absorbed
    queries, the state ``(g * H, .)``, ``acc_scr`` ``Dl`` wide; row ``j``
    of a member is live where ``j % width`` is under ``live[m, j]``.
    Three passes over the members in straight-line code, each member's
    step independent of the others': every member's scores, then every
    member's softmax, then every member's weighted sum, so that one
    member's products run under another's softmax (a member alone is a
    chain of product, reduction, exponential, product; a member the group
    lacks folds some slot's queries, which nobody reads)."""
    g, n, _ = rows.shape
    h, dl = q_ref.shape[0] // g, acc_scr.shape[1]
    rank = (jax.lax.broadcasted_iota(jnp.int32, (1, n), 1) % width).astype(
        jnp.float32)
    of = [slice(m * h, (m + 1) * h) for m in range(g)]
    for m in range(g):
        s = DA._pool_dot(q_ref[of[m]], rows[m], 1)               # (H, n)
        cm.scores[m] = jnp.where(rank < live[m:m + 1, :], s,
                                        DA.NEG_INF)
    for m in range(g):
        s = cm.scores[m]
        m_prev = m_scr[of[m]]
        m_next = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_next)                         # (H, 128)
        p = jnp.where(rank < live[m:m + 1, :],
                      jnp.exp(s - _across(m_next, n)), 0.0)
        l_scr[of[m]] = l_scr[of[m]] * alpha \
            + jnp.sum(p, axis=1, keepdims=True)
        m_scr[of[m]] = m_next
        cm.decay[m] = alpha
        cm.weights[m] = p.astype(cm.weights.dtype)
    for m in range(g):
        acc_scr[of[m]] = acc_scr[of[m]] * _across(cm.decay[m], dl) \
            + DA._exact_page_dot(cm.weights[m], rows[m, :, :dl], 0)


def _compact_block(sel_refs, first, pages, buf, kv_buf, cm, fold, *,
                   page_size, pages_per_block, width, whole):
    """Block ``buf`` of a walk, ``pages`` pages from table place ``first``
    on (``whole``: always a whole block). Every member's selected rows of
    ranks up to ``width`` of every page are compacted and folded, in
    straight-line code (``fold(rows, live)``); where a member selects
    more of some page, that page's further ranks are compacted ``width``
    a pass into the passes waiting, which are folded whenever a block's
    worth of them are. ``sel_refs``: a member's selection each, (1, mp, ps)."""
    g, ps, pb = len(sel_refs), page_size, pages_per_block
    n = pb * width
    page_of_row = jax.lax.broadcasted_iota(jnp.int32, (pb, 1), 0)
    unit_of = jax.lax.broadcasted_iota(jnp.int32, (1, n), 1) // width
    most = None
    for m, sel in enumerate(sel_refs):
        marks = sel[0, pl.ds(pl.multiple_of(first, pb), pb), :]
        if not whole:       # past the walk's end lies no page's selection
            marks = jnp.where(page_of_row < pages, marks, 0.0)
        ranks, counts = _page_ranks(marks)
        cm.ranks[m * pb:(m + 1) * pb] = ranks
        cm.counts[m * pb:(m + 1) * pb] = jnp.broadcast_to(counts, (pb, n))
        # page t's count along the lanes of its rows
        cm.live[m:m + 1] = jnp.sum(
            jnp.where(unit_of == page_of_row, counts, 0.0), axis=0,
            keepdims=True)
        most = counts if most is None else jnp.maximum(most, counts)
    cm.most[...] = jnp.broadcast_to(most, cm.most.shape)

    def compacted(t, k):
        marks = _one_hot_of_ranks(
            [cm.ranks[pl.ds(m * pb + t, 1), :] for m in range(g)],
            k * width, width, kv_buf.dtype)
        return _compact(
            marks, kv_buf[buf, pl.ds(pl.multiple_of(t * ps, ps), ps)])

    def first_pass(t):
        x = compacted(t, 0)
        for m in range(g):
            cm.rows[m, t * width:(t + 1) * width] = \
                x[m * width:(m + 1) * width]

    for t in range(pb):
        if whole:
            first_pass(t)
        else:
            pl.when(t < pages)(functools.partial(first_pass, t))
    fold(cm.rows, cm.live)

    def further(t, _):
        need = jnp.max(cm.most[pl.ds(t, 1), :]).astype(jnp.int32)

        def one_pass(k, _):
            u = cm.filled[0]
            x = compacted(t, k)
            at = pl.ds(pl.multiple_of(u * width, width), width)
            for m in range(g):
                cm.more[m, at] = x[m * width:(m + 1) * width]
                cm.more_live[pl.ds(m, 1), :] = jnp.where(
                    unit_of == u,
                    cm.counts[pl.ds(m * pb + t, 1), :]
                    - (k * width).astype(jnp.float32),
                    cm.more_live[pl.ds(m, 1), :])
            cm.filled[0] = u + 1
            pl.when(u + 1 == pb)(
                functools.partial(_fold_further, cm, fold))

        jax.lax.fori_loop(1, (need + width - 1) // width, one_pass, None)

    pl.when(jnp.max(most) > width)(
        lambda: jax.lax.fori_loop(0, pages, further, None))


def _fold_further(cm, fold):
    """The passes waiting folded, and none waiting any more."""
    fold(cm.more, cm.more_live)
    cm.more_live[...] = jnp.zeros_like(cm.more_live)
    cm.filled[0] = 0


def _row_moves(c_hbm, r_hbm, kv_buf, page_size):
    """A page of each pool into ONE buffer, the rotary key's lanes behind
    the latent's: a token's row is then one row of the block."""
    dl = c_hbm.shape[-1]

    def moves(page, buf, t):
        rows = pl.ds(t * page_size, page_size)
        return ((c_hbm.at[page], kv_buf.at[buf, rows, pl.ds(0, dl)]),
                (r_hbm.at[page],
                 kv_buf.at[buf, rows, pl.ds(dl, r_hbm.shape[-1])]))
    return moves


def _sparse_latent_shared_kernel(bt_ref, gs_ref, gp_ref, *refs, page_size,
                                 pages_per_block, members, width):
    """Part A: grid ``(groups,)``, one step a group of up to ``members``
    slots whose tables open with the same ``gp_ref[g]`` pages, whole
    blocks of them, walked ONCE. ``refs`` opens with the members' queries
    (``members`` blocks (1, H, D)) and their selections ((1, mp, ps)
    each), found by ``gs_ref``; a member the group lacks reads some
    slot's, which is compacted and folded with the others' and read by
    nobody. The members' unnormalised float32 states ``[acc | m | l]``
    leave as the group's block (1, members * H, Dl + 256). A group
    without pages does nothing."""
    g, ps, pb = members, page_size, pages_per_block
    q_refs, sel_refs = refs[:g], refs[g:2 * g]
    (c_hbm, r_hbm, st_ref, kv_buf, sems, first_buf, q_scr, m_scr, l_scr,
     acc_scr) = refs[2 * g:2 * g + 10]
    cm = _Compaction(*refs[2 * g + 10:])
    h, dl = q_refs[0].shape[1], c_hbm.shape[-1]
    grp = pl.program_id(0)

    def walk_of(of):
        return jnp.maximum(gs_ref[of * g], 0), 0, gp_ref[of]

    def fold(rows, live):
        _fold_compacted(q_scr, rows, live, cm, m_scr, l_scr, acc_scr,
                        width=width)

    def block(b, buf, pages):
        _compact_block(sel_refs, b * pb, pages, buf, kv_buf, cm, fold,
                       page_size=ps, pages_per_block=pb, width=width,
                       whole=True)

    pl.when(grp == 0)(functools.partial(_clear_compaction, cm))

    @pl.when(gp_ref[grp] > 0)
    def _stack():
        for j in range(g):
            q_scr[j * h:(j + 1) * h] = q_refs[j][0]
        _reset(m_scr, l_scr, acc_scr)

    DA._page_walk(walk_of, block, bt_ref,
                  _row_moves(c_hbm, r_hbm, kv_buf, ps), sems, first_buf,
                  pages_per_block=pb)
    pl.when(cm.filled[0] > 0)(functools.partial(_fold_further, cm, fold))

    @pl.when(gp_ref[grp] > 0)
    def _hand_over():
        st_ref[0, :, :dl] = acc_scr[...]
        st_ref[0, :, dl:dl + 128] = m_scr[...]
        st_ref[0, :, dl + 128:] = l_scr[...]


def _sparse_latent_own_kernel(bt_ref, ext_ref, sp_ref, row_ref, q_ref,
                              sel_ref, st_ref, c_hbm, r_hbm, o_ref, kv_buf,
                              sems, first_buf, m_scr, l_scr, acc_scr,
                              *compaction, page_size, pages_per_block, width,
                              spare):
    """Part B: grid ``(S,)``, one step a slot, the same compaction and
    fold with one member. The state starts from what Part A left the slot
    (``st_ref`` (1, H, Dl + 256), block ``row_ref[slot]`` of the states)
    where ``sp_ref[slot]`` of its pages were walked with its group's,
    from nothing where its block is the ``spare`` one (a slot in no group:
    all its pages are walked here); the walk goes over its own pages from
    there to the one that holds token ``ext_ref[slot] - 1``, the current
    token's row among them, and the state is normalised into ``o_ref``
    (1, H, Dl). The selection marks no token at or past the slot's
    extent, so it is the whole mask."""
    ps, pb = page_size, pages_per_block
    cm = _Compaction(*compaction)
    sl = pl.program_id(0)
    dl = o_ref.shape[2]

    def walk_of(of):
        n = (ext_ref[of] + ps - 1) // ps
        shared = jnp.minimum(sp_ref[of], n)
        return of, shared, n - shared

    def fold(rows, live):
        _fold_compacted(q_ref.at[0], rows, live, cm, m_scr, l_scr, acc_scr,
                        width=width)

    def block(b, buf, pages):
        _compact_block((sel_ref,), walk_of(sl)[1] + b * pb, pages, buf,
                       kv_buf, cm, fold, page_size=ps, pages_per_block=pb,
                       width=width, whole=False)

    pl.when(sl == 0)(functools.partial(_clear_compaction, cm))
    _reset(m_scr, l_scr, acc_scr)

    @pl.when(row_ref[sl] != spare)
    def _from_the_group():
        acc_scr[...] = st_ref[0, :, :dl]
        m_scr[...] = st_ref[0, :, dl:dl + 128]
        l_scr[...] = st_ref[0, :, dl + 128:]

    DA._page_walk(walk_of, block, bt_ref,
                  _row_moves(c_hbm, r_hbm, kv_buf, ps), sems, first_buf,
                  pages_per_block=pb)
    pl.when(cm.filled[0] > 0)(functools.partial(_fold_further, cm, fold))
    denom = l_scr[...][:, :1]
    denom = jnp.where(denom == 0.0, 1.0, denom)
    alive = m_scr[...][:, :1] > DA.NEG_INF / 2
    o_ref[0] = jnp.where(alive, acc_scr[...] / denom, 0.0).astype(
        o_ref.dtype)


@functools.partial(jax.jit, static_argnums=(9, 10, 11, 12))
def _sparse_latent_decode_pallas(q, c_pages, r_pages, block_tables,
                                 selected, extent, group_slots, group_pages,
                                 shared_pages, interpret, pages_per_block,
                                 width, name):
    """The two ``pallas_call``s of ``sparse_latent_decode``, Part A a
    group and Part B a slot, both under the one ``name`` (prefill, whose
    chunk tokens are this kernel's slots, gives its own). ``q`` is
    absorbed and scaled. Jitted, so that a step program traces and lowers
    the bodies once and calls them from every layer."""
    s_slots, h, _ = q.shape
    ps, dl = c_pages.shape[1:]
    drl = r_pages.shape[-1]
    d = dl + drl
    mp = block_tables.shape[1]
    n_groups, g = group_slots.shape
    # the bodies copy a page out of each pool as it lies and compact it by
    # products whose rows are bf16 tiles, which the chip's compiler takes
    # only where every piece is whole tiles
    if not interpret and (ps % 128 or dl % 128 or drl % 128
                          or width % (32 // c_pages.dtype.itemsize)):
        raise ValueError(
            f"{name} copies whole pages out of the pools: "
            f"pages of {ps} tokens, rows of {dl} + {drl} lanes, {width} "
            f"compacted rows a pass are not whole tiles")
    pb = max(1, min(int(pages_per_block), mp))
    rows = h + -h % DA._HEAD_ROWS
    # a head's query over a cached row's lanes: the latent's, the rotary
    # key's, and zeros over the lanes that pad the key
    q = jnp.pad(q.astype(c_pages.dtype),
                ((0, 0), (0, rows - h), (0, d - q.shape[-1])))
    block_tables = block_tables.astype(jnp.int32)
    extent = extent.astype(jnp.int32)
    group_slots = group_slots.astype(jnp.int32).reshape(-1)
    # Part A walks whole blocks: what is left of a group's pages is walked
    # a slot (nothing, where the groups are ``decode_groups``')
    group_pages = group_pages.astype(jnp.int32) // pb * pb
    shared_pages = shared_pages.astype(jnp.int32) // pb * pb
    # a block of the selection is whole blocks of pages: the rows past the
    # table's width are no page's (nothing reads what lies there)
    selected = selected.astype(jnp.float32).reshape(s_slots, mp, ps)
    sel_pages = mp + -mp % pb
    params = pltpu.CompilerParams(
        dimension_semantics=("arbitrary",),
        vmem_limit_bytes=DA._WIDE_VMEM_LIMIT) if not interpret else None

    def scratch(members):
        return [pltpu.VMEM((2, pb * ps, d), c_pages.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.SMEM((1,), jnp.int32)], [
                pltpu.VMEM((members * rows, 128), jnp.float32),
                pltpu.VMEM((members * rows, 128), jnp.float32),
                pltpu.VMEM((members * rows, dl), jnp.float32)
                ] + _compaction_scratch(members, rows, pb, ps, d,
                                        c_pages.dtype, width)

    pools = [pl.BlockSpec(memory_space=pl.ANY),
             pl.BlockSpec(memory_space=pl.ANY)]
    state = dl + DA._STATE_LANES

    # Part A. A member's queries and selection come from its slot's block
    # (a member a group lacks reads slot 0's); a group's states go to the
    # group's block, those of every group without pages to one spare block
    def member(j, *shape):
        return pl.BlockSpec(
            (1,) + shape,
            lambda grp, _bt, gs, _gp: (jnp.maximum(gs[grp * g + j], 0), 0, 0))

    walk, fold = scratch(g)
    states = pl.pallas_call(
        functools.partial(_sparse_latent_shared_kernel, page_size=ps,
                          pages_per_block=pb, members=g, width=width),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(n_groups,),
            in_specs=[member(j, rows, d) for j in range(g)]
            + [member(j, sel_pages, ps) for j in range(g)] + pools,
            out_specs=pl.BlockSpec(
                (1, g * rows, state),
                lambda grp, _bt, _gs, gp: (
                    jnp.where(gp[grp] > 0, grp, n_groups), 0, 0)),
            scratch_shapes=walk + [
                pltpu.VMEM((g * rows, d), c_pages.dtype)] + fold),
        out_shape=jax.ShapeDtypeStruct((n_groups + 1, g * rows, state),
                                       jnp.float32),
        compiler_params=params,
        interpret=interpret,
        name=name,
    )(block_tables, group_slots, group_pages, *[q] * g, *[selected] * g,
      c_pages, r_pages)

    # Part B, from the state rows Part A left
    spare = n_groups * g
    state_rows = DA._group_state_rows(group_slots, shared_pages, extent,
                                      spare)

    def slot_block(*shape):
        return pl.BlockSpec((1,) + shape, lambda s, *_prefetch: (s, 0, 0))

    walk, fold = scratch(1)
    out = pl.pallas_call(
        functools.partial(_sparse_latent_own_kernel, page_size=ps,
                          pages_per_block=pb, width=width, spare=spare),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(s_slots,),
            in_specs=[slot_block(rows, d), slot_block(sel_pages, ps),
                      pl.BlockSpec(
                          (1, rows, state),
                          lambda s, _bt, _ext, _sp, row: (row[s], 0, 0))]
            + pools,
            out_specs=slot_block(rows, dl),
            scratch_shapes=walk + fold),
        out_shape=jax.ShapeDtypeStruct((s_slots, rows, dl), q.dtype),
        compiler_params=params,
        interpret=interpret,
        name=name,
    )(block_tables, extent, shared_pages, state_rows, q, selected,
      states.reshape(spare + g, rows, state), c_pages, r_pages)
    return out[:, :h]


def _sparse_latent_decode_kernel_pallas(q, c_pages, r_pages, block_tables,
                                        selected, extent, group_slots,
                                        group_pages, shared_pages, *,
                                        block_sizes, interpret):
    return _sparse_latent_decode_pallas(
        q, c_pages, r_pages, block_tables, selected, extent, group_slots,
        group_pages, shared_pages, interpret,
        block_sizes.get("pages_per_block", DA.GROUP_SHARED_PAGES),
        block_sizes.get("rows_a_pass", _COMPACT_ROWS), "sparse_latent_decode")


def _sparse_latent_decode_lax(q, c_pages, r_pages, block_tables, selected,
                              extent, *_groups):
    """Attention a slot over the rows its selection marks, every row of
    its table scored; which slots share pages changes no result."""
    s, mp = block_tables.shape
    dl = c_pages.shape[-1]
    cg = c_pages[block_tables].reshape(s, mp * c_pages.shape[1], dl)
    rg = r_pages[block_tables].reshape(s, cg.shape[1], -1)
    qf, cf = q.astype(jnp.float32), cg.astype(jnp.float32)
    scores = jnp.einsum("shd,std->sht", qf[..., :dl], cf,
                        precision=_FP32_DOT) \
        + jnp.einsum("shd,std->sht", qf[..., dl:],
                     rg[..., :q.shape[-1] - dl].astype(jnp.float32),
                     precision=_FP32_DOT)
    p = DA._latent_softmax(scores, selected[:, None, :] > 0)
    return jnp.einsum("sht,std->shd", p, cf,
                      precision=_FP32_DOT).astype(q.dtype)


def _mask_of_positions(sel_idx, n_sel, t):
    """A selection as positions, the first ``n_sel[s]`` of ``sel_idx``
    (S, K) live -> (the mask (S, t) float32, the extent (S,) one past the
    last position marked)."""
    live = jnp.arange(sel_idx.shape[1])[None, :] < n_sel[:, None]
    slot = jnp.arange(sel_idx.shape[0])[:, None]
    # a dead entry lands past the row's end and is dropped
    selected = jnp.zeros(sel_idx.shape[:1] + (t,), jnp.float32).at[
        slot, jnp.where(live, sel_idx, t)].set(1.0, mode="drop")
    return selected, jnp.max(jnp.where(live, sel_idx + 1, 0), axis=1)


def _sparse_latent_reference(q, c_pages, r_pages, block_tables, selected):
    """NumPy, a query row at a time over the rows its mask marks: ``q``
    (S, H, D) with ``selected`` (S, T), or a chunk a lane, (S, C, H, D)
    with (S, C, T)."""
    import numpy as np
    qn = np.asarray(q, np.float64)
    chunked = qn.ndim == 4
    if not chunked:
        qn = qn[:, None]
    s, c, h, _ = qn.shape
    cp, rp = np.asarray(c_pages, np.float64), np.asarray(r_pages, np.float64)
    ps, dl = cp.shape[1:]
    bt = np.asarray(block_tables)
    sel = (np.asarray(selected) > 0).reshape(s, c, -1)
    out = np.zeros((s, c, h, dl))
    for sl in range(s):
        for t in range(c):
            toks = np.flatnonzero(sel[sl, t])
            if not len(toks):
                continue
            rows = np.concatenate(
                [cp[bt[sl, toks // ps], toks % ps],
                 rp[bt[sl, toks // ps], toks % ps][:, :qn.shape[-1] - dl]],
                1)
            sc = qn[sl, t] @ rows.T
            pr = np.exp(sc - sc.max(-1, keepdims=True))
            out[sl, t] = (pr / pr.sum(-1, keepdims=True)) @ rows[:, :dl]
    out = out if chunked else out[:, 0]
    return jnp.asarray(out).astype(q.dtype)


def _sparse_latent_decode_reference(q, c_pages, r_pages, block_tables,
                                    selected, extent, *_groups):
    """:func:`_sparse_latent_reference`: independent of both impls and of
    which slots share pages."""
    return _sparse_latent_reference(q, c_pages, r_pages, block_tables,
                                    selected)


def _make_sparse_latent_decode_sample(seed):
    """Three shapes by ``seed % 3``: float32 pools of pages scattered over
    the pool, the rotary keys in rows wider than the key (seed 2: as
    wide); slots of every length from empty to full, some of them opening
    with the same pages (a pair; three and a pair; a whole group of eight
    and a slot alone), grouped as the engine groups them; selections by
    the engine's rule, of a few rows a page, of none and of whole pages."""
    import numpy as np
    s, h, dl, dr, ps, mp, topk, sharers = (
        (3, 2, 16, 8, 8, 12, 24, (([0, 1], 8),)),
        (6, 4, 32, 8, 8, 10, 40, (([0, 1, 2], 9), ([3, 5], 8))),
        (9, 4, 64, 16, 16, 18, 96, ((list(range(8)), 16),)))[seed % 3]
    rng = np.random.default_rng(seed)
    num_pages = s * mp + 1
    q = jnp.asarray((dl + dr) ** -0.5 * rng.standard_normal(
        (s, h, dl + dr)), jnp.float32)
    c_pages = jnp.asarray(rng.standard_normal((num_pages, ps, dl)),
                          jnp.float32)
    r_pages = jnp.asarray(rng.standard_normal(
        (num_pages, ps, dr if seed % 3 == 2 else 2 * dr)), jnp.float32)
    tables = (rng.permutation(num_pages - 1)[:s * mp] + 1).reshape(
        s, mp).astype(np.int32)
    lengths = rng.integers(0, mp * ps + 1, s).astype(np.int32)
    for slots, k in sharers:
        tables[slots, :k] = tables[slots[0], :k]
        lengths[slots] = rng.integers(k * ps, mp * ps + 1, len(slots))
    # scores that crowd a slot's selection into a few pages, whole ones
    # among them, and leave others with none
    scores = rng.standard_normal((s, mp * ps)) \
        + 3.0 * np.repeat(rng.standard_normal((s, mp)), ps, axis=1)
    selected = select_decode_mask(jnp.asarray(scores, jnp.float32),
                                  jnp.asarray(lengths), topk)
    groups = DA.decode_groups(tables, lengths, np.arange(s), ps)
    return (q, c_pages, r_pages, jnp.asarray(tables), selected,
            jnp.asarray(lengths)) + tuple(map(jnp.asarray, groups)), {}


def _compacting_vmem(heads, c_pages, r_pages, mp, g, blocks):
    """VMEM working set of one grid step of the selecting latent kernels,
    the larger of their two parts, a group's of ``g`` members of
    ``heads`` heads: two buffers of ``pb`` pages (a row the latent's
    lanes and the rotary key's), the members' queries and selections and
    the group's state block double-buffered by the pipeline, the queries
    stacked, the state, the compacted rows of the first and of the
    further passes, a fold's scores, weights and decay, and one pass's
    one-hot and product."""
    ps, dl = c_pages.shape[1:]
    isz = c_pages.dtype.itemsize
    pb = min(blocks.get("pages_per_block", DA.GROUP_SHARED_PAGES), mp)
    width = blocks.get("rows_a_pass", _COMPACT_ROWS)
    pad = lambda n, m: -(-n // m) * m                       # noqa: E731
    h = pad(heads, 16)
    rows = pad(pb * width, 16)
    n = pad(rows, 128)
    lanes = pad(dl, 128) + pad(r_pages.shape[-1], 128)
    pages = 2 * pb * pad(ps, 16) * lanes * isz
    queries = g * h * lanes * isz
    state = g * h * (pad(dl, 128) + 256) * 4
    io = 2 * (queries + g * pad(mp, pb) * pad(ps, 128) * 4 + state)
    compacted = 2 * g * rows * lanes * isz \
        + (2 * g * pb + pb + 2 * g) * pad(max(ps, n), 128) * 4
    fold = g * h * (n * (4 + isz) + 128 * 4)
    one_pass = g * pad(width, 16) * (pad(ps, 128) * (4 + isz)
                                     + lanes * (4 + isz))
    return pages + io + queries + state + compacted + fold + one_pass


def _sparse_latent_decode_vmem_estimate(args, kwargs, blocks):
    q, c_pages, r_pages, bt = args[:4]
    return _compacting_vmem(q.shape[-2], c_pages, r_pages, bt.shape[1],
                            args[6].shape[1], blocks)


def sparse_latent_decode_attention(q, c_pages, r_pages, block_tables,
                                   sel_idx, n_sel, *, impl: str = "auto"):
    """One decode step of latent attention over each slot's SELECTED
    rows, for whoever holds a selection as indices (the engine's own path
    hands the kernel a mask: :func:`selected_latent_decode_attention`):
    ``sel_idx`` (S, K) cache positions of which the first ``n_sel[s]``
    are live. Returns (S, H, Dl)."""
    selected, extent = _mask_of_positions(
        sel_idx, n_sel, block_tables.shape[1] * c_pages.shape[1])
    return selected_latent_decode_attention(
        q, c_pages, r_pages, block_tables, selected, extent, impl=impl)


def selected_latent_decode_attention(q, c_pages, r_pages, block_tables,
                                     selected, extent, groups=None, *,
                                     impl: str = "auto"):
    """One decode step of latent attention over the rows ``selected`` (S,
    mp * page_size) marks, 1 for a row the slot's query attends to and 0
    for every other, none at or past ``extent[s]`` (S,): ``q`` (S, H, Dl
    + Dr) absorbed queries, already scaled, ``c_pages`` (P, ps, Dl) and
    ``r_pages`` (P, ps, >= Dr, the key in the first ``Dr`` lanes of a
    row) the token-major latent and rotary-key pools. ``groups`` as
    :func:`selected_decode_attention` takes them: the kernel reads the
    pages a group's tables open with once for the group, and compacts
    each member's selected rows out of them on the chip; None: every slot
    walked alone, by the same code. Returns (S, H, Dl)."""
    from paddle_tpu import kernels
    if groups is None:      # the shapes of a table that groups no slot
        groups = DA.decode_groups(block_tables, extent, (), 1)
    return kernels.dispatch("sparse_latent_decode", q, c_pages, r_pages,
                            block_tables, selected, extent, *groups,
                            impl=impl)


# ---------------------------------------------------------------------------
# selecting latent prefill: a chunk's tokens through the same two parts,
# eight rows of a lane a walk
# ---------------------------------------------------------------------------
#
# A chunk token is to the kernel above what a decoding slot is: a query row
# ``(H, Dl + Dr)`` with a mask of its own, its lane's table and an extent
# (``chunk_starts[s] + c + 1`` rows, itself the last; 0 for a pad token,
# whose walk is empty). Eight rows of a lane in a row are a group whose
# members share EVERY page under the first one's extent, so Part A walks
# those once for the eight (whole blocks of ``GROUP_SHARED_PAGES``) and
# Part B each row's tail from there to its own extent: a page or two over
# a published document, up to nine pages while a document is published.
# A lane's rows are padded to whole groups, so that a group's members are
# one lane's (one table) and its dead rows its last; ``q_rows`` rows are
# one call of the two parts, a call none of whose rows is live skipped.

#: query rows a call of the two parts: 8 groups of ``DECODE_GROUP`` (a
#: call's masks are 8.5 MB at the published widths)
_LATENT_ROWS_A_CALL = 64


def _chunk_extents(chunk_starts, n_valid, c):
    """(S, c) int32: the rows chunk token ``c`` of lane ``s`` sees, itself
    the last (``chunk_starts[s] + c + 1``); 0 for a pad token."""
    tok = jnp.arange(c, dtype=jnp.int32)[None, :]
    return jnp.where(tok < n_valid[:, None], chunk_starts[:, None] + tok + 1,
                     0)


def _by_live_blocks(fn, n, arrays, row, rows_a_call=_LATENT_ROWS_A_CALL):
    """``fn(n, *arrays)`` of ``n`` (R,) and ``arrays`` (R, ...),
    ``rows_a_call`` rows at a time, a row of the result as ``row``
    (``ShapeDtypeStruct``) describes it; a block in which every ``n`` is
    0 (pad tokens) is skipped and reads zeros."""
    r = n.shape[0]
    if r <= rows_a_call:
        return fn(n, *arrays)
    pad = -r % rows_a_call
    blocks = tuple(
        jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1)).reshape(
            (-1, rows_a_call) + a.shape[1:]) for a in (n,) + tuple(arrays))
    out = jax.lax.map(lambda b: jax.lax.cond(
        jnp.any(b[0] > 0), lambda: fn(*b),
        lambda: jnp.zeros((rows_a_call,) + row.shape, row.dtype)), blocks)
    return out.reshape((-1,) + row.shape)[:r]


def _sparse_latent_prefill(q, c_pages, block_tables, chunk_starts, n_valid,
                           selected, attend, rows_a_call):
    """The chunk tokens as rows, a lane's padded to whole groups:
    ``attend(extent (R,), q (R, H, D), tables (R, mp), selected (R, T))``
    -> (R, H, Dl), ``rows_a_call`` rows at a time."""
    s, c = q.shape[:2]
    g = DA.DECODE_GROUP
    if rows_a_call % g:
        raise ValueError(f"sparse_latent_prefill walks groups of {g} rows: "
                         f"{rows_a_call} rows a call are no whole groups")
    pad = ((0, 0), (0, -c % g))
    extent = _chunk_extents(chunk_starts, n_valid, c + pad[1][1])
    rows = tuple(a.reshape((-1,) + a.shape[2:]) for a in (
        extent, jnp.pad(q, pad + ((0, 0), (0, 0))),
        jnp.repeat(block_tables[:, None], extent.shape[1], axis=1),
        jnp.pad(selected, pad + ((0, 0),))))
    out = _by_live_blocks(
        attend, rows[0], rows[1:],
        jax.ShapeDtypeStruct((q.shape[2], c_pages.shape[-1]), q.dtype),
        rows_a_call)
    return out.reshape((s, -1) + out.shape[1:])[:, :c]


def _sparse_latent_prefill_pallas(q, c_pages, r_pages, block_tables,
                                  chunk_starts, n_valid, selected, *,
                                  block_sizes, interpret):
    g, ps = DA.DECODE_GROUP, c_pages.shape[1]
    pb = block_sizes.get("pages_per_block", DA.GROUP_SHARED_PAGES)

    def attend(extent, qb, tables, marks):
        # a group's shared pages: the whole blocks under its first row's
        # extent (none where that row is dead: the whole group is)
        shared = extent.reshape(-1, g)[:, 0] // ps \
            // DA.GROUP_SHARED_PAGES * DA.GROUP_SHARED_PAGES
        return _sparse_latent_decode_pallas(
            qb, c_pages, r_pages, tables, marks, extent,
            jnp.arange(extent.shape[0], dtype=jnp.int32).reshape(-1, g),
            shared, jnp.repeat(shared, g), interpret, pb,
            block_sizes.get("rows_a_pass", _COMPACT_ROWS),
            "sparse_latent_prefill").astype(q.dtype)

    return _sparse_latent_prefill(
        q, c_pages, block_tables, chunk_starts, n_valid, selected, attend,
        block_sizes.get("q_rows", _LATENT_ROWS_A_CALL))


def _sparse_latent_prefill_lax(q, c_pages, r_pages, block_tables,
                               chunk_starts, n_valid, selected):
    """The masked form :func:`_sparse_latent_decode_lax` is, a chunk token
    a row: every row of its lane's table scored."""
    def attend(extent, qb, tables, marks):
        seen = jnp.arange(marks.shape[1])[None, :] < extent[:, None]
        return _sparse_latent_decode_lax(qb, c_pages, r_pages, tables,
                                         jnp.where(seen, marks, 0.0), extent)

    return _sparse_latent_prefill(
        q, c_pages, block_tables, chunk_starts, n_valid, selected, attend,
        _LATENT_ROWS_A_CALL)


def _sparse_latent_prefill_reference(q, c_pages, r_pages, block_tables,
                                     chunk_starts, n_valid, selected):
    """:func:`_sparse_latent_reference` over what each live chunk token's
    mask marks of the rows it sees."""
    import numpy as np
    c = q.shape[1]
    tok = np.arange(c)[None, :]
    extent = np.where(tok < np.asarray(n_valid)[:, None],
                      np.asarray(chunk_starts)[:, None] + tok + 1, 0)
    sel = np.asarray(selected) > 0
    seen = np.arange(sel.shape[-1])[None, None, :] < extent[..., None]
    return _sparse_latent_reference(q, c_pages, r_pages, block_tables,
                                    sel & seen)


def _make_sparse_latent_prefill_sample(seed):
    """Three shapes by ``seed % 3``: float32 pools of pages scattered over
    the pool, the rotary keys in rows wider than the key (seed 2: as
    wide); chunks of 4, 8 and 24 tokens (half a group, one, three); lanes
    that start at 0 (no shared page), deep in their tables (whole blocks
    shared) and not at all (dead: seed 1 has more rows than a call takes,
    whole calls of them dead); selections by the engine's rule, crowded
    into a few pages, the pad tokens' rows marked like any other (the
    kernel reads none of them)."""
    import numpy as np
    s, c, h, dl, dr, ps, mp, topk = (
        (3, 4, 2, 16, 8, 8, 12, 12), (11, 8, 4, 32, 8, 8, 11, 20),
        (2, 24, 4, 64, 16, 16, 10, 48))[seed % 3]
    rng = np.random.default_rng(seed)
    num_pages = s * mp + 1
    q = jnp.asarray((dl + dr) ** -0.5 * rng.standard_normal(
        (s, c, h, dl + dr)), jnp.float32)
    c_pages = jnp.asarray(rng.standard_normal((num_pages, ps, dl)),
                          jnp.float32)
    r_pages = jnp.asarray(rng.standard_normal(
        (num_pages, ps, dr if seed % 3 == 2 else 2 * dr)), jnp.float32)
    bt = jnp.asarray((rng.permutation(num_pages - 1)[:s * mp] + 1)
                     .reshape(s, mp), jnp.int32)
    starts = rng.integers(0, mp * ps - c + 1, s).astype(np.int32)
    n_valid = rng.integers(1, c + 1, s).astype(np.int32)
    starts[:2] = (0, mp * ps - c)
    n_valid[:2] = (c, c - 1)
    if seed % 3 == 1:
        n_valid[2:] = 0
        n_valid[-1] = 3                 # dead calls between live ones
    scores = rng.standard_normal((s, c, mp * ps)) \
        + 3.0 * np.repeat(rng.standard_normal((s, 1, mp)), ps, axis=2)
    starts, n_valid = jnp.asarray(starts), jnp.asarray(n_valid)
    selected = select_prefill(jnp.asarray(scores, jnp.float32), starts,
                              n_valid, topk)
    return (q, c_pages, r_pages, bt, starts, n_valid, selected), {}


def _sparse_latent_prefill_vmem_estimate(args, kwargs, blocks):
    q, c_pages, r_pages, bt = args[:4]
    return _compacting_vmem(q.shape[-2], c_pages, r_pages, bt.shape[1],
                            DA.DECODE_GROUP, blocks)


def sparse_latent_prefill_attention(q, c_pages, r_pages, block_tables,
                                    chunk_starts, n_valid, selected, *,
                                    impl: str = "auto"):
    """The same for a chunk of queries a lane, each with a selection of
    its own: ``q`` (S, C, H, Dl + Dr), ``selected`` (S, C, mp *
    page_size), query ``c`` of lane ``s`` attending to the rows its mask
    marks, none at or past ``chunk_starts[s] + c + 1``; the first
    ``n_valid[s]`` queries of a lane are live, the others read zeros.
    Returns (S, C, H, Dl)."""
    from paddle_tpu import kernels
    return kernels.dispatch("sparse_latent_prefill", q, c_pages, r_pages,
                            block_tables, chunk_starts, n_valid, selected,
                            impl=impl)


# ---------------------------------------------------------------------------
# what the engine calls
# ---------------------------------------------------------------------------

def indexed_decode_attention(q, k_pages, v_pages, ik_pages, block_tables,
                             lengths, q_idx, w_idx, topk, *, groups=None,
                             impl: str = "auto"):
    """Score, select, attend for one decode token a slot: ``q`` (S, H,
    Dh), ``q_idx`` (S, J, Di), ``w_idx`` (S, J), ``lengths`` the live
    tokens INCLUDING this one, ``groups`` which slots' tables open with
    the same pages (:func:`selected_decode_attention`). The selection
    reaches the kernel as a mask (:func:`select_decode_mask`). Returns
    (attention (S, H, Dh), selected (S,) tokens attended a slot)."""
    scores = lightning_index_scores(
        q_idx[:, None], w_idx[:, None], ik_pages, block_tables, lengths,
        impl=impl)[:, 0]
    selected = select_decode_mask(scores, lengths, topk, impl=impl)
    att = selected_decode_attention(q, k_pages, v_pages, block_tables,
                                    selected, lengths, groups, impl=impl)
    return att, jnp.minimum(lengths, topk)


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
    selected = select_prefill(scores, chunk_starts, n_valid, topk, impl=impl)
    return sparse_paged_prefill_attention(
        q, k_pages, v_pages, block_tables, chunk_starts, n_valid, selected,
        impl=impl)


def latent_indexed_decode_attention(q, c_pages, r_pages, ik_pages,
                                    block_tables, lengths, q_idx, w_idx,
                                    topk, *, groups=None,
                                    impl: str = "auto"):
    """Score, select, attend for one decode token a slot over a LATENT
    cache: ``q`` (S, H, Dl + Dr) absorbed and scaled, ``lengths`` the
    live tokens INCLUDING this one, ``groups`` which slots' tables open
    with the same pages (:func:`selected_latent_decode_attention`). The
    selection reaches the kernel as a mask (:func:`select_decode_mask`);
    a table of at most ``topk`` tokens selects every live one (no scores
    are made). Returns (attention (S, H, Dl), tokens attended a slot
    (S,))."""
    t = block_tables.shape[1] * c_pages.shape[1]
    if t <= topk:
        selected = (jnp.arange(t, dtype=jnp.int32)[None, :]
                    < lengths[:, None]).astype(jnp.float32)
    else:
        scores = lightning_index_scores(
            q_idx[:, None], w_idx[:, None], ik_pages, block_tables, lengths,
            impl=impl)[:, 0]
        selected = select_decode_mask(scores, lengths, topk, impl=impl)
    att = selected_latent_decode_attention(
        q, c_pages, r_pages, block_tables, selected, lengths, groups,
        impl=impl)
    return att, jnp.minimum(lengths, topk)


def latent_indexed_prefill_attention(q, c_pages, r_pages, ik_pages,
                                     block_tables, chunk_starts, n_valid,
                                     q_idx, w_idx, topk, *,
                                     impl: str = "auto"):
    """The same for a chunk of queries a lane, ``q`` (S, C, H, Dl + Dr):
    query ``c`` of lane ``s`` sees ``chunk_starts[s] + c + 1`` tokens and
    selects among them; the selection reaches the kernel as a mask too,
    made ``_LATENT_ROWS_A_CALL`` rows at a time where any of them is live
    (a pad token sees nothing and marks nothing). Returns (S, C, H, Dl)."""
    s, c = q.shape[:2]
    t = block_tables.shape[1] * c_pages.shape[1]
    n = _chunk_extents(chunk_starts, n_valid, c)
    if t <= topk:
        selected = (jnp.arange(t, dtype=jnp.int32) < n[..., None]).astype(
            jnp.float32)
    else:
        scores = lightning_index_scores(q_idx, w_idx, ik_pages, block_tables,
                                        chunk_starts + n_valid, impl=impl)
        selected = _by_live_blocks(
            lambda nb, sc: select_decode_mask(sc, nb, topk, impl=impl),
            n.reshape(s * c), (scores.reshape(s * c, t),),
            jax.ShapeDtypeStruct((t,), jnp.float32)).reshape(s, c, t)
    return sparse_latent_prefill_attention(
        q, c_pages, r_pages, block_tables, chunk_starts, n_valid, selected,
        impl=impl)


# ---------------------------------------------------------------------------
# kernel-registry entries
# ---------------------------------------------------------------------------

def _sparse_tune_signature(args, kwargs):
    return DA._paged_sig(args[0], args[1], args[3]) \
        + (("g", args[6].shape[1]),)


def _sparse_decode_vmem_estimate(args, kwargs, blocks):
    """VMEM working set of one grid step of the sparse decode, tiles
    padded as the chip lays them out; the larger of its two parts, a
    group's: two buffers of ``pb`` pages of each pool, the members'
    queries and selections and the group's state block double-buffered,
    the state, and one KV head's update ``pb`` pages wide for every
    member's rows (the selection's rows, float32 scores and weights, the
    weights' three bf16 terms and their product)."""
    q, k_pages, bt = args[0], args[1], args[3]
    ps, hd = k_pages.shape[1:]
    dh, mp = q.shape[-1], bt.shape[1]
    kv, g = hd // dh, args[6].shape[1]
    isz = k_pages.dtype.itemsize
    pb = blocks.get("pages_per_block", 1)

    def tiled(lead, sub, lane, itemsize):
        tile = 32 // itemsize
        return (lead * -(-sub // tile) * tile * -(-lane // 128) * 128
                * itemsize)

    heads = q.shape[-2] // kv
    rows, width = g * (heads + -heads % _MEMBER_ROWS), pb * ps
    pages = 2 * 2 * tiled(1, width, hd, isz)
    io = 2 * (tiled(kv, rows, dh, q.dtype.itemsize) + g * tiled(1, mp, ps, 4)
              + tiled(kv, rows, dh + DA._STATE_LANES, 4))
    state = 2 * tiled(kv, rows, 128, 4) + tiled(kv, rows, dh, 4)
    fold = (3 * tiled(1, rows, width, 4) + tiled(1, 3 * rows, width, 2)
            + tiled(1, 3 * rows, dh, 4))
    return pages + io + state + fold


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
    kernels.register(kernels.KernelSpec(
        name="topk_selection_mask",
        contract=kernels.KernelContract(
            version=1,
            arg_layouts={"scores": "(R,T) f32", "n": "(R,) i32"},
            out_layout="(R,T) f32",
            grid="(R/rows_per_block,) a block of rows whole: the scores "
                 "read once into int32 keys in VMEM, 32 + log2(T) "
                 "compare-and-count passes over those, the mask written "
                 "once",
            block_candidates={"rows_per_block": (8, 16, 32)},
            atol=0.0, rtol=0.0),
        pallas_fn=_selection_kernel_pallas,
        lax_fn=_selection_lax,
        reference_fn=selected_by_sort,
        sample_inputs=_make_selection_sample,
        pallas_sites=(
            "paddle_tpu.serving.sparse_attention:_selection_pallas",),
        tune_signature=lambda args, kwargs: (
            ("r", args[0].shape[0]), ("t", args[0].shape[1])),
        vmem_estimate=_selection_vmem_estimate))
    pb_candidates = {"pages_per_block": (1, 2, 4)}
    kernels.register(kernels.KernelSpec(
        name="sparse_paged_decode",
        contract=kernels.KernelContract(
            version=2,
            arg_layouts={"q": "(S,H,Dh)", "k_pages": "(P,ps,KV*Dh)",
                         "v_pages": "(P,ps,KV*Dh)",
                         "block_tables": "(S,mp) i32",
                         "selected": "(S,mp*ps) f32",
                         "extent": "(S,) i32",
                         "group_slots": "(S//2,G) i32",
                         "group_pages": "(S//2,) i32",
                         "shared_pages": "(S,) i32"},
            out_layout="(S,H,Dh)",
            grid="two calls, pools left in HBM. (S//2,) one step a group "
                 "of up to G slots whose tables open with the same "
                 "group_pages pages (group_slots, -1 for no member): the "
                 "members' queries stacked a KV head against ONE copy of "
                 "each shared page, each member's rows masked by its "
                 "selection, their float32 states [acc|m|l] handed on; "
                 "then (S,) one step a slot, from that state over its own "
                 "pages from shared_pages on (shared_pages * ps <= "
                 "extent; 0: the slot is walked alone) under its "
                 "selection. Either body copies whole pages of both pools "
                 "itself, pages_per_block side by side into one of two "
                 "VMEM buffers while it folds the other; a block one "
                 "softmax update a KV head",
            block_candidates={"pages_per_block": (1, 2, 4, 8)},
            atol=2e-5, rtol=2e-5),
        pallas_fn=_sparse_decode_kernel_pallas,
        lax_fn=_sparse_decode_lax,
        reference_fn=_sparse_decode_reference,
        sample_inputs=_make_sparse_decode_sample,
        pallas_sites=(
            "paddle_tpu.serving.sparse_attention:_sparse_decode_pallas",),
        tune_signature=_sparse_tune_signature,
        vmem_estimate=_sparse_decode_vmem_estimate))
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
    kernels.register(kernels.KernelSpec(
        name="sparse_latent_decode",
        contract=kernels.KernelContract(
            version=2,
            arg_layouts={"q": "(S,H,Dl+Dr)", "c_pages": "(P,ps,Dl)",
                         "r_pages": "(P,ps,>=Dr)",
                         "block_tables": "(S,mp) i32",
                         "selected": "(S,mp*ps) f32",
                         "extent": "(S,) i32",
                         "group_slots": "(S//2,G) i32",
                         "group_pages": "(S//2,) i32",
                         "shared_pages": "(S,) i32"},
            out_layout="(S,H,Dl)",
            grid="two calls, pools left in HBM. (S//2,) one step a group "
                 "of up to G slots whose tables open with the same "
                 "group_pages pages (group_slots, -1 for no member), "
                 "walked once; then (S,) one step a slot, from the state "
                 "its group left it over its own pages from shared_pages "
                 "on (0: the slot is walked alone). Either body copies "
                 "whole pages of both token-major pools itself, "
                 "pages_per_block side by side into one of two VMEM "
                 "buffers while it works on the other, COMPACTS each "
                 "member's selected rows of a page by a one-hot product "
                 "(rows_a_pass ranks a pass, as many passes as the page's "
                 "fullest member needs) and folds 8 passes' rows a member "
                 "against all its heads in one softmax update; no copy "
                 "of the selected rows in HBM",
            # (32 rows a pass read 1.96 ms a layer for 1.46 at the long-
            # document cell's geometry, selections spread evenly, and 3.63
            # for 3.79 crowded into a fifth of the pages: PERF.md, PR 56)
            block_candidates={"pages_per_block": (8,),
                              "rows_a_pass": (16,)},
            atol=2e-5, rtol=2e-5),
        pallas_fn=_sparse_latent_decode_kernel_pallas,
        lax_fn=_sparse_latent_decode_lax,
        reference_fn=_sparse_latent_decode_reference,
        sample_inputs=_make_sparse_latent_decode_sample,
        pallas_sites=("paddle_tpu.serving.sparse_attention:"
                      "_sparse_latent_decode_pallas",),
        tune_signature=lambda args, kwargs: (
            ("s", args[0].shape[0]), ("h", args[0].shape[1]),
            ("dl", args[1].shape[-1]),
            ("dr", args[0].shape[-1] - args[1].shape[-1]),
            ("ps", args[1].shape[1]), ("mp", args[3].shape[1]),
            ("g", args[6].shape[1])),
        vmem_estimate=_sparse_latent_decode_vmem_estimate))
    kernels.register(kernels.KernelSpec(
        name="sparse_latent_prefill",
        contract=kernels.KernelContract(
            version=2,
            arg_layouts={"q": "(S,C,H,Dl+Dr)",
                         "c_pages": "(P,ps,Dl)", "r_pages": "(P,ps,>=Dr)",
                         "block_tables": "(S,mp) i32",
                         "chunk_starts": "(S,) i32",
                         "n_valid": "(S,) i32",
                         "selected": "(S,C,mp*ps) f32"},
            out_layout="(S,C,H,Dl)",
            grid="sparse_latent_decode's two calls under this kernel's "
                 "name, a chunk token a slot (its lane's table, its own "
                 "mask, extent chunk_starts + c + 1; 0 for a pad token), "
                 "q_rows rows a pair of calls, a pair with no live row "
                 "skipped: (q_rows/8,) one step a group of 8 rows of a "
                 "lane over the whole blocks of pages under the first "
                 "one's extent, walked once; then (q_rows,) one step a "
                 "row over its own pages from there to its extent. The "
                 "selected rows compacted out of whole pages in VMEM and "
                 "folded there, no positions and no copy of them in HBM",
            # (the members a walk are decode_attention.DECODE_GROUP, as
            # decode's: PERF.md section 6, PR 58)
            block_candidates={"q_rows": (64,), "pages_per_block": (8,),
                              "rows_a_pass": (16,)},
            atol=2e-5, rtol=2e-5),
        pallas_fn=_sparse_latent_prefill_pallas,
        lax_fn=_sparse_latent_prefill_lax,
        reference_fn=_sparse_latent_prefill_reference,
        sample_inputs=_make_sparse_latent_prefill_sample,
        pallas_sites=("paddle_tpu.serving.sparse_attention:"
                      "_sparse_latent_decode_pallas",),
        tune_signature=lambda args, kwargs: (
            ("s", args[0].shape[0]), ("c", args[0].shape[1]),
            ("h", args[0].shape[2]), ("dl", args[1].shape[-1]),
            ("dr", args[0].shape[-1] - args[1].shape[-1]),
            ("ps", args[1].shape[1]), ("mp", args[3].shape[1])),
        vmem_estimate=_sparse_latent_prefill_vmem_estimate))


_register()
