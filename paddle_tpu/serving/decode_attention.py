"""Ragged paged attention kernels: the serving engine's hot path.

One fixed-shape call attends every slot's query token(s) over only that
slot's *live* KV pages — the "Ragged Paged Attention" TPU serving
pattern (PAPERS.md): sequences of wildly different lengths batch into
one step, and work/HBM traffic scale with live tokens, not with
``batch × max_len`` padding. Two kernels share the layout, the grid,
the scalar-prefetched block table and the online-softmax sequence; each
has the fold its query shape wants (below):

``ragged_paged_decode_attention`` — one query token per slot:
  q            (S, H, Dh)        one query token per decode slot
  k/v pages    (P, ps, H*Dh)     fixed-size pages, token-major; a
                                 token's heads folded into the lane
                                 axis, head-major (head ``h`` is
                                 lanes ``h*Dh .. (h+1)*Dh``)
  block_tables (S, max_pages)    page ids per slot (page 0 = null page)
  lengths      (S,)              live tokens per slot (0 = inactive slot)

``ragged_paged_prefill_attention`` — a CHUNK of C query tokens per slot
(the batched multi-request chunked-prefill step, ISSUE 6): queries sit
at absolute positions ``chunk_starts[s] + c`` and attend causally over
everything the slot has cached, including this chunk's own causal
prefix (whose K/V the caller writes before attending). Lanes past
``n_valid[s]`` (and whole inactive slots, ``n_valid == 0``) emit exact
zeros.

Each has two implementations with identical numerics:

- ``impl="lax"``: XLA gather + masked softmax (CPU/debug reference).
- ``impl="pallas"`` / ``"pallas_interpret"``: a Pallas kernel that
  scalar-prefetches the block table so each kv block's HBM address is
  known before it is needed (the PrefetchScalarGridSpec pattern), moves
  WHOLE pages (every head), does online-softmax accumulation over
  pages, and skips pages past the slot's live extent entirely. Chunked
  prefill and the int8 twins lay a grid ``(S, cdiv(max_pages,
  pages_per_block))`` over the block table's width and let the
  ``BlockSpec`` pipeline stream the pages; dense decode
  (``ragged_paged_decode``) has grid ``(S,)`` and copies a slot's live
  pages itself (below), as the latent and the sparse decode do. The
  interpret path runs the REAL kernel on CPU, so tier-1 tests exercise
  it.

The bodies differ in how a page meets the queries:

- **chunked prefill** (``_paged_prefill_kernel``): a chunk fills the
  MXU's rows with the C queries of ONE head, so the body loops over the
  heads and takes head ``h`` as a static lane slice of the page block
  (a wide chunk of grouped-query heads: over the KV heads, all the
  query heads of one at once). Its two products follow the rule below
  (``_exact_page_dot``), a float32 operand's three terms stacked only
  where the fold's rows leave the MXU room for them, and its row
  statistics meet a 128-lane row of scores or values as they lie;
  ``paged_prefill_lowerings_total`` counts which form a trace took
  (``_prefill_fold_form``);
- **decode** (``_paged_decode_kernel``): one query a head would leave
  those rows empty, so a page is folded ONCE for every head: the slot's
  queries are one block-structured matrix over the page's whole lane
  width (row ``i`` = query head ``i`` in the lanes of its KV head), the
  scores of all heads are one product, the softmax update one, the
  weighted values one. **The product rule of every body here**: the
  operands go to the MXU in the dtype the page is stored in — a bf16
  (or int8) page takes one bf16 pass with float32 accumulation, bf16
  queries as they are, and what is float32 (the softmax weights;
  queries, if the caller's are) goes in as its three bf16 terms, so
  nothing is rounded that ``HIGHEST`` would not round; a float32 pool
  multiplies at ``HIGHEST``. Only live pages are moved: a page operand past the
  slot's extent repeats its last index. This is the int8 twin's body
  and that of a pool whose pages are not whole tiles;
- **dense decode** (``_paged_decode_walk_kernel``, PR 39): the same
  all-heads products on the same operands, but the body walks the
  slot's live pages itself: the pools stay in HBM, one grid step a
  slot copies ``pages_per_block`` live pages side by side into one of
  two VMEM buffers while the other is folded, and a block is ONE
  softmax update (one score product, one maximum, one exponential, one
  product with ``V``) where the pipelined body makes one a page. No
  grid step, index map or DMA check exists for a page that does not
  move, and a slot's first block is on its way while the slot before
  still folds. Exactly the pages that were copied are folded, so
  nothing a buffer held before reaches an output.

No test holds the bodies to bit equality with each other, only each to
its reference; the pipelined bodies also to themselves across
``pages_per_block`` (the per-page accumulation order is identical),
the dense decode body at every setting to the float64 reference within
2e-5 (a block is one update, so the order of the sums follows the
setting).

The kernels register with the shared kernel layer
(:mod:`paddle_tpu.kernels`): the public entry points dispatch through
the registry, the ``pages_per_block`` tunable (how many of a slot's
pages one grid step streams, or one buffer of the dense decode body
holds) resolves from the shared autotuner at trace time, and the
registry's parity battery + graph-lint contract rule cover all.

**Dequant-attend int8 variants** (ISSUE 13):
``ragged_paged_decode_int8_attention`` and
``ragged_paged_prefill_int8_attention`` attend over an INT8 page pool
with per-token-row fp32 scales (``paged_cache.quantize_kv``'s layout).
The Pallas bodies stream the int8 pages through the SAME folds as
the fp pools, with the scale broadcast fused into the QK and PV
products — no dequantized fp page is ever materialized, HBM
traffic per attended token halves (the bytes-per-token lever the cost
model gates in CI). Registered like the fp kernels: lax fallbacks with
identical scale-after-dot numerics, independent dense references,
contracts with donation-safe pages AND scales, and the shared
``pages_per_block`` tunable.

**Tensor parallel** (ISSUE 15): there is no tp kernel. The engine
shards its whole step over the mesh's "tp" axis and each shard calls
these kernels on its own heads: the pool's lanes are head-major, so a
shard's ``(P, ps, (H/tp)*Dh)`` slice is its own whole heads, and heads
are independent, so a head shard through the plain kernel equals those
heads of the whole call (``tests/test_kernels.py``,
``TestHeadShardsAreIndependent``). The one attention-output collective
lives at the model's row-sharded output projection.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.observability import registry as _obs_registry
from paddle_tpu.ops.attention import NEG_INF


# ---------------------------------------------------------------------------
# lax reference path
# ---------------------------------------------------------------------------

def _gather_pages(pages, block_tables, h, dh):
    """A slot batch's pages out of the folded pool, heads unfolded:
    ``(S, mp, ps, H, Dh)`` (row-major, so the reshape moves nothing).
    A pool of fewer KV heads than the ``h`` query heads (grouped-query
    attention: query head ``i`` reads KV head ``i // (h / kv)``) is
    repeated up to ``h`` here, on the lax path only."""
    g = pages[block_tables]
    s, mp, ps, hd = g.shape                        # a 3-D pool only
    kv = hd // dh
    g = g.reshape(s, mp, ps, kv, dh)
    return g if kv == h else jnp.repeat(g, h // kv, axis=3)


def _value_dim(q, k_pages, v_pages):
    """The width of a head's values: the V pool's lanes over the KV heads
    the K pool's lanes hold at the queries' width (``Dv``; ``Dk`` where
    the two pools are alike)."""
    return v_pages.shape[-1] // (k_pages.shape[-1] // q.shape[-1])


def _sink_softmax(scores, sinks):
    """Softmax over the last axis of masked ``scores`` with one more term
    in the denominator, ``exp(sinks)`` (broadcast against the rows): a
    learned sink a head, which takes its share of a row's mass and sums
    no value. A row with every key masked gives zeros."""
    m = jnp.maximum(jnp.max(scores, axis=-1, keepdims=True), sinks)
    e = jnp.exp(scores - m)
    return e / (jnp.sum(e, axis=-1, keepdims=True) + jnp.exp(sinks - m))


def _paged_decode_lax(q, k_pages, v_pages, block_tables, lengths, scale,
                      window=None, sinks=None):
    s_slots, h, dh = q.shape
    mp = block_tables.shape[1]
    ps = k_pages.shape[1]
    # contract straight against the gathered 5-D (S, mp, ps, H, Dh)
    # layout — reshaping the gather to token-major would materialize a
    # full extra copy of every slot's K and V per call
    kg = _gather_pages(k_pages, block_tables, h, dh)
    vg = _gather_pages(v_pages, block_tables, h,
                       _value_dim(q, k_pages, v_pages))
    scores = jnp.einsum("shd,smthd->shmt", q.astype(jnp.float32),
                        kg.astype(jnp.float32)) * scale
    scores = scores.reshape(s_slots, h, mp * ps)
    tok = jnp.arange(mp * ps, dtype=jnp.int32)
    valid = tok[None, None, :] < lengths[:, None, None]
    if window is not None:      # the last ``window`` tokens only
        valid = valid & (tok[None, None, :]
                         >= lengths[:, None, None] - window)
    scores = jnp.where(valid, scores, NEG_INF)
    if sinks is not None:
        p = _sink_softmax(scores, sinks.astype(jnp.float32)[None, :, None])
    else:
        p = jax.nn.softmax(scores, axis=-1)
        # length-0 slots: every key masked -> emit 0, not a uniform mean
        # of v
        alive = jnp.max(scores, axis=-1, keepdims=True) > NEG_INF / 2
        p = jnp.where(alive, p, 0.0)
    p = p.reshape(s_slots, h, mp, ps)
    out = jnp.einsum("shmt,smthd->shd", p, vg.astype(jnp.float32))
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# Pallas kernels: grid (S, cdiv(max_pages, pb)), block-table scalar prefetch
# ---------------------------------------------------------------------------
#
# Block shapes the TPU compiler accepts (the last two dims of every block
# equal the array's own, or are (8k, 128k)-aligned), with the stored page
# layout (P, ps, H*Dh) left alone. The pool is stored the way the kernels
# read it: with (ps, H*Dh) as the two minor dims XLA keeps an entry
# parameter row-major, so no step relays the pool out around the kernel
# (with (H, Dh) minor it keeps the pool page_size-minor, and every call
# copies it whole, padded (H, Dh) -> (16, 128), in and out):
#
#   pages    block (1, ps, H*Dh)    one WHOLE page, every head — the HBM
#                                   tiles of a page move to VMEM as they
#                                   lie; head h is the static lane slice
#                                   [h*Dh, (h+1)*Dh) of the block
#   q / out  block (1, H, C, Dh)    chunked prefill: head-major; the
#                                   wrappers transpose the small
#                                   activations, never the pool
#   q        block (1, rows, H*Dh)  decode: the slot's queries as one
#                                   block-structured matrix over the
#                                   page's lanes (rows = heads, padded
#                                   to 16); out block (1, rows, Dh)
#   scales   block (8, ps)          the 8-row group holding the page's
#                                   scale row; the body picks row
#                                   ``page % 8`` (a (1, ps) block over
#                                   (P, ps) is refused: 1 is neither 8-
#                                   aligned nor the full P). Where P is
#                                   no multiple of 8 the last group runs
#                                   past the array (with P < 8, the only
#                                   group does): those rows are padding
#                                   no page number ever picks
#
# One grid step folds ``pb`` pages into EVERY head's (m, l, acc) state:
# chunked prefill head by head, state per head in (H, C, .) scratch;
# decode all heads at once, state (rows, .) over the page's lanes.

_SCALE_ROWS = 8     # f32 sublane tile: scale rows stream in groups of 8
_WIDE_VMEM_LIMIT = 64 << 20

#: a float32 pool's dots run in true fp32. Mosaic's DEFAULT for fp32
#: operands is a single bf16 pass (~3e-3 abs error at GPT-2 widths,
#: measured on a v5e), which is outside every paged contract's tolerance
#: — the interpreter never shows it.
_FP32_DOT = jax.lax.Precision.HIGHEST


def _bf16_terms(x):
    """float32 ``x (rows, n)`` as the exact sum of three bfloat16 terms
    (8 + 8 + 8 significand bits). One bf16 MXU pass a term multiplies
    every bit of ``x``: what ``Precision.HIGHEST`` does in six passes,
    three of them on the zero low halves of a bf16 page."""
    terms, rest = [], x
    for _ in range(3):
        term = rest.astype(jnp.bfloat16)
        terms.append(term)
        rest = rest - term.astype(jnp.float32)
    return tuple(terms)


def _exact_page_dot(x, page, contract_page_dim, stack=True):
    """``x (rows, .)`` times a page block (or a head's lanes of one) in
    the dtype the page is stored in, float32 out, no operand rounded: the
    one product rule of the decode bodies and of both chunked-prefill
    folds. A float32 page (the CPU tests) multiplies at ``HIGHEST``; a
    bf16 page, or an int8 one (int8 -> bf16 is exact), takes ONE bf16
    pass a term of ``x``: a bf16 ``x`` is its own one term, a float32
    ``x`` (the softmax weights; queries, if the caller's are) goes in as
    its three. ``stack``: the terms as ONE product, stacked along the
    rows, its row blocks summed (decode: a head's few rows leave the
    MXU's rows to spare); else a product a term, which saves the copy
    into the stack (a chunked-prefill fold whose rows fill the MXU a
    term: :func:`_prefill_fold_form`)."""
    dims = (((1,), (contract_page_dim,)), ((), ()))
    if page.dtype == jnp.float32:
        return jax.lax.dot_general(
            x.astype(jnp.float32), page, dims, precision=_FP32_DOT,
            preferred_element_type=jnp.float32)

    page = page.astype(jnp.bfloat16)         # once, whatever the terms

    def one_pass(x):
        return jax.lax.dot_general(
            x, page, dims, precision=jax.lax.Precision.DEFAULT,
            preferred_element_type=jnp.float32)

    if x.dtype == jnp.bfloat16:
        return one_pass(x)
    terms = _bf16_terms(x.astype(jnp.float32))
    if stack:
        rows = x.shape[0]
        out = one_pass(jnp.concatenate(terms, axis=0))
        blocks = (out[i * rows:(i + 1) * rows] for i in range(len(terms)))
    else:
        blocks = (one_pass(term) for term in terms)
    return functools.reduce(jnp.add, blocks)


def _row_values(x, lanes):
    """A ``(rows, 128)`` statistic of the fold's state, every lane its
    row's value, against ``lanes`` columns: as it lies where a row of
    scores or values is those 128 lanes wide (a page of 128 tokens, a
    head of 128 values: no lane moves), else its first column, which
    broadcasts. The same values either way; re-broadcasting a column
    costs the chip's cross-lane unit an operation a row group, and at
    128 wide those bounded the whole fold (PERF.md section 6, PR 51)."""
    return x if x.shape[1] == lanes else x[:, :1]


#: heads x queries of a chunk from which the chunked-prefill body folds a
#: page once a KV head, for the whole group of query heads that read it
#: (:func:`_online_softmax_group_fold`), and not once a query head
_GROUP_FOLD_MIN_ROWS = 4096
_MXU_ROWS = 128     # rows of the matrix unit (v5e)

_LOWERINGS = _obs_registry.counter(
    "paged_prefill_lowerings_total",
    "chunked-prefill bodies traced, by fold (a page once a query head or "
    "once a KV head), operands (to the MXU as the pool stores them, or a "
    "float32 pool at HIGHEST) and terms (a float32 operand's three bf16 "
    "terms as one stacked product or a product each: the fold's row "
    "count decides, whether or not the pool's type makes terms); counted "
    "where the shapes and the dtypes decide, at trace time")


class _FoldForm(NamedTuple):
    """The form a chunked-prefill call's folds take: all of it static,
    read off the call's shapes and dtypes (:func:`_prefill_fold_form`)."""
    by_group: bool      # a page folded once a KV head, not once a head
    stored: bool        # operands to the MXU as the pool stores them
    stack: bool         # three bf16 terms as one stacked product

    def count(self):
        _LOWERINGS.inc(fold="group" if self.by_group else "head",
                       operands="stored" if self.stored else "float32",
                       terms="stacked" if self.stack else "each")


def _prefill_fold_form(n_heads, rows, kv, dh, dv, page_dtype, selected):
    """What a chunked-prefill call of ``n_heads`` query heads over ``kv``
    KV heads (``dh`` key lanes, ``dv`` value lanes a head) and ``rows``
    queries a head does with a page, for the kernel and for its VMEM
    estimate. A wide chunk of grouped-query heads over a plain pool folds
    a page once a KV head (keys wider than values only where the KV heads
    come in whole spans of 128-lane tiles). The three terms of a float32
    operand stay one stacked product only where three times the fold's
    rows still fit the MXU's: a chunk that fills them a term saves the
    copy into the stack (the scheduled code of a 32-row, a 64-row and a
    2,048-row fold either way: PERF.md section 6, PR 51)."""
    span_heads = 128 // math.gcd(dh, 128)
    by_group = (n_heads > kv and n_heads * rows >= _GROUP_FOLD_MIN_ROWS
                and page_dtype != jnp.int8 and not selected
                and (dh == dv or kv % span_heads == 0))
    fold_rows = rows * (n_heads // kv if by_group else 1)
    return _FoldForm(by_group, page_dtype != jnp.float32,
                     3 * fold_rows <= _MXU_ROWS)


def _online_softmax_page_fold(q, k, v, mask, m_scr, l_scr, acc_scr, h,
                              stack, k_scale=None, v_scale=None):
    """Fold ONE head's (ps, Dh) slice of a kv page into head ``h``'s
    running (m, l, acc) online-softmax state: the chunked-prefill fold
    (a chunk fills the MXU's rows with the queries of one head; decode,
    with one query a head, folds a page for all heads at once:
    :func:`_paged_decode_kernel`). ``mask`` (rows, ps) marks live score
    entries; masked entries go to NEG_INF and contribute exact zeros.

    ``q``, ``k`` and ``v`` come as they are stored and go to the MXU so
    (:func:`_exact_page_dot`: no page block is cast to float32, and a
    bf16 query over a bf16 page is one pass), the float32 weights ``p``
    as their three bf16 terms.

    ``k_scale``/``v_scale`` (1, ps) are the int8 page pool's
    per-token-row dequant scales (None on the fp path): the scale
    broadcast is fused INTO the QK and PV products — the int8 page goes
    straight into the dot and the per-token scale multiplies the
    (rows, ps) score/weight matrix (the weights before they are split
    into terms), so no dequantized fp page is ever
    materialized (the TPP fused-microkernel shape). The m/l/acc update
    sequence is identical either way, so the int8 kernels inherit the
    same per-page accumulation-order contract."""
    s = _exact_page_dot(q, k, 1, stack)                # (rows, ps)
    if k_scale is not None:
        s = s * k_scale
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[h]                                  # (rows, 128)
    l_prev = l_scr[h]
    m_cur = jnp.max(s, axis=1, keepdims=True)          # (rows, 1)
    m_next = jnp.maximum(m_prev, m_cur)                # lanes broadcast
    alpha = jnp.exp(m_prev - m_next)
    p = jnp.exp(s - _row_values(m_next, s.shape[1]))   # (rows, ps)
    l_scr[h] = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
    m_scr[h] = m_next
    if v_scale is not None:
        p = p * v_scale
    pv = _exact_page_dot(p, v, 0, stack)               # (rows, Dh)
    acc_scr[h] = acc_scr[h] * _row_values(alpha, pv.shape[1]) + pv


def _online_softmax_group_fold(q, k, v, mask, m_scr, l_scr, acc_scr, heads,
                               stack):
    """:func:`_online_softmax_page_fold` for ALL the query heads of one KV
    head at once: ``q`` ``(group * rows, Dh)`` holds the group's heads one
    after the other, ``heads`` is their slice of the ``(H, rows, .)``
    state, ``mask`` ``(group * rows, ps)``. The same ``m / l / acc``
    sequence a row, so a row's sums are the per-head fold's; what changes
    is the unrolling: the body LOOPS over the KV heads (``lax.fori_loop``,
    the group's lanes a dynamic slice of whole 128-lane tiles) where the
    per-head body unrolls a fold a query head. 64 query heads over 8 KV
    heads of 128 queries: a call compiles in 2 s for the chip and lowers
    in 0.3 s where the per-head body took 11 s and 8 s, in every one of
    a cell's 35 prefill programs (PERF.md section 6, PR 40). The
    operands go to the MXU as the per-head fold's do, as they are
    stored."""
    group, rows = m_scr[heads].shape[:2]

    def flat(ref):
        a = ref[heads]
        return a.reshape(group * rows, a.shape[-1])

    s = _exact_page_dot(q, k, 1, stack)                # (G*rows, ps)
    s = jnp.where(mask, s, NEG_INF)
    m_prev, l_prev = flat(m_scr), flat(l_scr)
    m_next = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_next)
    p = jnp.exp(s - _row_values(m_next, s.shape[1]))
    l_scr[heads] = (l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
                    ).reshape(group, rows, -1)
    m_scr[heads] = m_next.reshape(group, rows, -1)
    pv = _exact_page_dot(p, v, 0, stack)               # (G*rows, Dh)
    acc_scr[heads] = (flat(acc_scr) * _row_values(alpha, pv.shape[1])
                      + pv).reshape(group, rows, -1)


def _split_kv_refs(rest, pb, quantized):
    """Unpack a paged kernel's trailing refs: ``pb`` k blocks, ``pb`` v
    blocks, (quantized only) ``pb`` k-scale + ``pb`` v-scale row groups,
    then the output ref and the three online-softmax scratch buffers.
    ONE unpacking convention for the fp and int8 variants of both
    kernels."""
    k_refs = rest[:pb]
    v_refs = rest[pb:2 * pb]
    if quantized:
        ks_refs = rest[2 * pb:3 * pb]
        vs_refs = rest[3 * pb:4 * pb]
        base = 4 * pb
    else:
        ks_refs = vs_refs = (None,) * pb
        base = 2 * pb
    o_ref = rest[base]
    m_scr, l_scr, acc_scr = rest[base + 1:]
    return k_refs, v_refs, ks_refs, vs_refs, o_ref, m_scr, l_scr, acc_scr


def _paged_prefill_kernel(bt_ref, start_ref, nv_ref, q_ref, *rest,
                          page_size, pages_per_block, quantized=False,
                          selected=False, window=None, sink=False):
    """The chunked-prefill body: online-softmax over a slot's pages,
    ``pages_per_block`` pages per grid step (the shared autotuner's
    tunable: fewer grid iterations, deeper DMA pipelining; the per-page
    accumulation ORDER is identical to pages_per_block=1, so outputs
    are bit-equal for any setting), every head of the page folded in
    turn: C query rows per head, row ``r`` live iff ``r < n_valid[s]``,
    attending causally to ``tok <= chunk_starts[s] + r``.

    ``quantized`` is ONE static flag, not a second kernel: the int8
    page blocks ride with their per-token scale rows and the scales
    fuse into the fold — grid, ragged skip, and finish logic cannot
    diverge between the fp and dequant-attend variants.

    Grouped-query heads: a page block holds ``kv`` heads of ``Dh`` lanes
    and the query block ``n_heads``, a multiple of it; query head ``h``
    folds KV head ``h // (n_heads / kv)``.

    ``selected``: one more input, ``(1, C, pb * ps)`` marks per query
    row which cache positions it may attend to beside the causal test:
    sparse attention's per-query selection applied inside the streamed
    fold.

    ``window``: a query attends to the last ``window`` tokens only, itself
    counted (``tok > chunk_starts[s] + r - window``), masked in float32
    like the causal test; a block wholly behind the window of the chunk's
    first query does nothing, and its page operands stay at the window's
    first page (:func:`_paged_page_index`). A row may meet a page that
    holds none of its tokens before one that does: its ``m`` is still
    ``NEG_INF`` there, what it sums is wiped by ``alpha = 0`` at the first
    page that holds one, and a live row's own token always is one.

    Keys wider than values: the queries and a KV head's K lanes are
    ``Dk`` wide (``q_ref``), its V lanes and the output ``Dv``
    (``o_ref``). Where ``Dk`` is no whole number of 128-lane tiles (192)
    a KV head's K lanes do not start on a tile boundary, so the group
    fold loads the fewest KV heads whose lanes together do (a span: two
    heads, 384 lanes) at a tile boundary and takes each head's lanes as
    a static slice of what it loaded.

    ``sink``: one more input, ``(H, 1, 128)`` float32, a learned logit a
    head that joins the softmax's denominator and sums no value: the
    state starts at ``m = sink, l = 1`` where it starts at ``m = NEG_INF,
    l = 0`` without one, and nothing else changes (a row that folds
    nothing still gives ``acc / l = 0``)."""
    pb = pages_per_block
    sel_ref = sink_ref = None
    if selected:
        sel_ref, rest = rest[0], rest[1:]
    if sink:
        sink_ref, rest = rest[0], rest[1:]
    (k_refs, v_refs, ks_refs, vs_refs, o_ref, m_scr, l_scr,
     acc_scr) = _split_kv_refs(rest, pb, quantized)
    sl = pl.program_id(0)
    pj = pl.program_id(1)
    npg = pl.num_programs(1)
    n_heads, rows, dh = q_ref.shape[1:]
    dv = o_ref.shape[-1]
    kv = k_refs[0].shape[-1] // dh
    group = n_heads // kv                          # query heads a KV head
    mp = bt_ref.shape[1]
    # KV heads whose K lanes together are whole tiles
    span_heads = 128 // math.gcd(dh, 128)
    form = _prefill_fold_form(n_heads, rows, kv, dh, dv, k_refs[0].dtype,
                              selected)
    form.count()
    by_group = form.by_group

    @pl.when(pj == 0)
    def _init():
        if sink:
            m_scr[...] = jnp.broadcast_to(sink_ref[...], m_scr.shape)
            l_scr[...] = jnp.ones_like(l_scr)
        else:
            m_scr[...] = jnp.full_like(m_scr, NEG_INF)
            l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    start, nv = start_ref[sl], nv_ref[sl]
    extent = start + nv                      # tokens this chunk can see
    has_work = (nv > 0) & (pj * pb * page_size < extent)
    if window is not None:
        has_work = has_work & ((pj + 1) * pb * page_size > start - window + 1)

    def _body_by_group():
        shape = (group * rows, page_size)
        row = jax.lax.rem(jax.lax.broadcasted_iota(jnp.int32, shape, 0),
                          rows)
        for t in range(pb):
            tok = (pj * pb + t) * page_size + jax.lax.broadcasted_iota(
                jnp.int32, shape, 1)
            ok = (tok <= start + row) & (row < nv)      # causal + live
            if window is not None:
                ok = ok & (tok > start + row - window)
            def one_kv_head(g, _, t=t, ok=ok, k_span=None, at=0):
                heads = pl.ds(g * group, group)
                lanes = pl.ds(pl.multiple_of(g * dv, dv), dv)
                _online_softmax_group_fold(
                    q_ref[0, heads].reshape(group * rows, dh),
                    k_refs[t][0, :, lanes]
                    if k_span is None else k_span[:, at * dh:(at + 1) * dh],
                    v_refs[t][0, :, lanes],
                    ok, m_scr, l_scr, acc_scr, heads, form.stack)

            def one_span(j, _, t=t, ok=ok):
                width = span_heads * dh
                k_span = k_refs[t][0, :, pl.ds(
                    pl.multiple_of(j * width, width), width)]
                for at in range(span_heads):
                    one_kv_head(j * span_heads + at, None, t, ok, k_span,
                                at)

            if dh == dv:
                jax.lax.fori_loop(0, kv, one_kv_head, None)
            else:
                jax.lax.fori_loop(0, kv // span_heads, one_span, None)

    def _body():
        for t in range(pb):
            # tokens at/after the slot's live extent (incl. whole tail
            # pages of this block, and the clamped duplicate page when
            # pb does not divide max_pages) mask to NEG_INF ->
            # exact-zero contributions to l and acc
            tok = (pj * pb + t) * page_size + jax.lax.broadcasted_iota(
                jnp.int32, (rows, page_size), 1)
            row = jax.lax.broadcasted_iota(
                jnp.int32, (rows, page_size), 0)
            ok = (tok <= start + row) & (row < nv)      # causal + live
            if window is not None:
                ok = ok & (tok > start + row - window)
            if selected:
                ok = ok & (sel_ref[0, :, t * page_size:
                                   (t + 1) * page_size] > 0)
            k_scale = v_scale = None
            if quantized:
                # the page's scale row inside its streamed 8-row group
                page = bt_ref[sl, jnp.minimum(pj * pb + t, mp - 1)]
                r = page % _SCALE_ROWS
                k_scale = ks_refs[t][pl.ds(r, 1), :]    # (1, ps)
                v_scale = vs_refs[t][pl.ds(r, 1), :]
            for h in range(n_heads):
                g = h // group
                _online_softmax_page_fold(
                    q_ref[0, h],                                # (R, Dk)
                    k_refs[t][0, :, g * dh:(g + 1) * dh],       # (ps, Dk)
                    v_refs[t][0, :, g * dv:(g + 1) * dv],       # (ps, Dv)
                    ok, m_scr, l_scr, acc_scr, h, form.stack,
                    k_scale=k_scale, v_scale=v_scale)

    # ragged skip: blocks wholly past the slot's live extent do nothing
    pl.when(has_work)(_body_by_group if by_group else _body)

    @pl.when(pj == npg - 1)
    def _finish():
        if by_group:                         # every head in one pass
            denom = l_scr[...][:, :, :1]
            denom = jnp.where(denom == 0.0, 1.0, denom)
            alive = m_scr[...][:, :, :1] > NEG_INF / 2
            o_ref[0] = jnp.where(alive, acc_scr[...] / denom, 0.0).astype(
                o_ref.dtype)
            return
        for h in range(n_heads):
            denom = l_scr[h][:, :1]
            denom = jnp.where(denom == 0.0, 1.0, denom)
            alive = m_scr[h][:, :1] > NEG_INF / 2
            o_ref[0, h] = jnp.where(
                alive, acc_scr[h] / denom, 0.0).astype(o_ref.dtype)


# -- decode: a page folded ONCE for every head ------------------------------
#
# One query row a head leaves the MXU's rows empty and a head's 64 lanes
# are half a tile: a fold a (head, page) costs decode twelve times what
# the page's bytes take to arrive. So the decode body lays a slot's
# queries out as ONE block-structured matrix ``Q (Hq, kv*Dh)``: row ``i``
# holds query head ``i`` in the lanes of its KV head and zeros elsewhere.
# ``S = Q x K_page^T`` is every head's scores in one product over the
# page block's whole lane width (no lane slice, no cast of the page);
# one softmax update runs on ``(Hq, ps)``; ``A += P x V_page`` is every
# head's weighted values, of which row ``i`` keeps its own KV head's
# lanes at the end. The zeros cost MXU flops the unit has to spare.
# Grouped-query heads are the same algorithm with ``Hq / kv`` rows a
# KV head.

_HEAD_ROWS = 16     # Q's rows pad to whole (16, 128) bf16 tiles


def _decode_finish(o_ref, m_scr, l_scr, acc_scr, group):
    """A slot's output from its finished ``m / l / acc`` state (both
    decode bodies): ``acc / l``, row ``i`` keeping the ``Dv`` lanes of its
    own KV head ``i // group``; a slot that folded nothing gives zeros
    (under a sink too: ``acc`` is zero over ``l = 1``)."""
    rows, dh = o_ref.shape[1:]
    denom = l_scr[...][:, :1]
    denom = jnp.where(denom == 0.0, 1.0, denom)
    alive = m_scr[...][:, :1] > NEG_INF / 2
    row = jax.lax.broadcasted_iota(jnp.int32, (rows, dh), 0)
    out = jnp.zeros((rows, dh), jnp.float32)
    for g in range(acc_scr.shape[1] // dh):
        mine = (row >= g * group) & (row < (g + 1) * group)
        out = out + jnp.where(mine, acc_scr[:, g * dh:(g + 1) * dh], 0.0)
    o_ref[0] = jnp.where(alive, out / denom, 0.0).astype(o_ref.dtype)


def _decode_start(sink_ref, m_scr, l_scr, acc_scr):
    """A slot's ``m / l / acc`` state before its first page (both decode
    bodies): nothing summed, or only the heads' sinks (``m = sink, l =
    1``: the sink's own term ``exp(sink - m)``)."""
    if sink_ref is None:
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
    else:
        m_scr[...] = sink_ref[...]
        l_scr[...] = jnp.ones_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)


def _decode_page(bt, lens, s, j, t, *, page_size, pages_per_block):
    """The pool page that page operand ``t`` of grid step ``(s, j)``
    holds: page ``j*pb + t`` of the slot while that page is live, then
    the operand's own last live page again (an index that repeats moves
    nothing), and the null page 0 where the slot has no live page for
    the operand at all (it stays there through every such slot). Only
    live pages move; the body folds only live pages."""
    pb = pages_per_block
    n_live = (lens[s] + page_size - 1) // page_size
    own_last = t + jnp.maximum(n_live - 1 - t, 0) // pb * pb
    page = jnp.minimum(jnp.minimum(j * pb + t, own_last), bt.shape[1] - 1)
    return jnp.where(t < n_live, bt[s, page], 0)


def _paged_decode_kernel(bt_ref, len_ref, q_ref, *rest, page_size,
                         pages_per_block, n_heads, quantized=False,
                         window=None, sink=False):
    """The decode body: online softmax over a slot's live pages, a page
    folded once for all heads (above). ``q_ref`` ``(1, rows, kv*Dh)`` is
    the block-structured query matrix (``rows`` = ``n_heads`` padded to
    whole tiles; the padding rows are zero queries whose output nobody
    reads), the state is ``m, l (rows, 128)`` and ``acc (rows, kv*Dh)``
    float32, the output ``(1, rows, Dh)``. Per page the ``m / l / alpha``
    sequence of the prefill fold, so the per-page accumulation order is
    independent of ``pages_per_block`` (outputs bit-equal for any
    setting). ``quantized``: the int8 page goes into the products as
    bf16 and its scale rows multiply the ``(rows, ps)`` scores and
    weights.

    A grid step folds exactly the live pages of its block, all of them
    in ONE region (one per count of live pages): a page's chain (scores,
    row maximum, exponentials, weighted values) is a string of latencies,
    and inside one region the scheduler runs the chains of neighbouring
    pages beside each other; a region a page read 0.70 us a page at the
    docs cell's geometry, this 0.42 (my chip runs, PR 29).

    ``window``: the last ``window`` tokens only, by the mask alone (this
    body serves dense decode only where a pool's pages are not whole
    tiles, sizes of a test: the pages behind the window still move; a
    page wholly behind it sums under ``m = NEG_INF`` what the first page
    with a live token wipes with ``alpha = 0``).

    Keys wider than values: ``q_ref`` spans the K page's lanes (``kv*Dk``),
    ``acc`` the V page's (``kv*Dv``), the output is ``Dv`` wide.
    ``sink``: one more input, ``(rows, 128)`` float32, a learned logit a
    query head (row) in the softmax's denominator: the state starts at
    ``m = sink, l = 1``."""
    pb = pages_per_block
    sink_ref = None
    if sink:
        sink_ref, rest = rest[0], rest[1:]
    (k_refs, v_refs, ks_refs, vs_refs, o_ref, m_scr, l_scr,
     acc_scr) = _split_kv_refs(rest, pb, quantized)
    sl = pl.program_id(0)
    pj = pl.program_id(1)
    npg = pl.num_programs(1)
    rows = q_ref.shape[1]
    kv = acc_scr.shape[-1] // o_ref.shape[-1]
    group = n_heads // kv
    extent = len_ref[sl]

    @pl.when(pj == 0)
    def _init():
        _decode_start(sink_ref, m_scr, l_scr, acc_scr)

    def _fold(n_pages):
        m, l, acc = m_scr[...], l_scr[...], acc_scr[...]
        q = q_ref[0]
        # every page's scores first: they depend on no state
        scores = [_exact_page_dot(q, k_refs[t][0], 1)        # (rows, ps)
                  for t in range(n_pages)]
        for t, s in enumerate(scores):
            if quantized:
                # the page's scale row inside its streamed 8-row group
                r = _decode_page(bt_ref, len_ref, sl, pj, t,
                                 page_size=page_size,
                                 pages_per_block=pb) % _SCALE_ROWS
                s = s * ks_refs[t][pl.ds(r, 1), :]
            tok = (pj * pb + t) * page_size + jax.lax.broadcasted_iota(
                jnp.int32, (rows, page_size), 1)
            live = tok < extent
            if window is not None:
                live = live & (tok >= extent - window)
            s = jnp.where(live, s, NEG_INF)
            m_next = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m - m_next)                      # (rows, 128)
            p = jnp.exp(s - m_next[:, :1])                   # (rows, ps)
            l = l * alpha + jnp.sum(p, axis=1, keepdims=True)
            m = m_next
            if quantized:
                p = p * vs_refs[t][pl.ds(r, 1), :]
            acc = acc * alpha[:, :1] + _exact_page_dot(
                p, v_refs[t][0], 0)                          # (rows, kv*Dh)
        m_scr[...], l_scr[...], acc_scr[...] = m, l, acc

    # ragged skip, page by page: a page at or past the slot's live
    # extent is neither moved (`_decode_page`) nor folded
    live_here = jnp.clip(
        (extent - pj * pb * page_size + page_size - 1) // page_size, 0, pb)
    for n_pages in range(1, pb + 1):
        pl.when(live_here == n_pages)(functools.partial(_fold, n_pages))

    pl.when(pj == npg - 1)(functools.partial(
        _decode_finish, o_ref, m_scr, l_scr, acc_scr, group))


def _paged_page_index(ps, mp, pb, t, decode, window=None):
    """Page operand ``t``'s pool page at grid step ``(s, j)``, as a
    function of the grid ids and the scalar-prefetch refs (the block
    table first). Chunked prefill: page ``j*pb + t`` of the slot,
    clamped to its last (the clamped duplicate is fully masked by the
    token test in the body) and, under a ``window``, to the page that
    holds the first token of the chunk's first window (pages behind it
    do not move). Decode: :func:`_decode_page`."""
    if decode:
        def page(s, j, bt, lens):
            return _decode_page(bt, lens, s, j, t, page_size=ps,
                                pages_per_block=pb)
    elif window is not None:
        def page(s, j, bt, starts, *_rest):
            first = jnp.maximum(starts[s] - window + 1, 0) // ps
            return bt[s, jnp.clip(j * pb + t, jnp.minimum(first, mp - 1),
                                  mp - 1)]
    else:
        def page(s, j, bt, *_rest):
            return bt[s, jnp.minimum(j * pb + t, mp - 1)]
    return page


def _paged_kv_specs(ps, hd, mp, pb, decode=False, window=None, hdv=None):
    """``pb`` (k, v) BlockSpec pairs per grid step: WHOLE pages of the
    slot's block table, all heads folded into their ``hd = H*Dh``
    lanes (``hdv``: the V pool's, where its heads are not as wide)."""
    def kv_spec(t, lanes):
        page = _paged_page_index(ps, mp, pb, t, decode, window)
        return pl.BlockSpec((1, ps, lanes),
                            lambda *ids_and_refs: (page(*ids_and_refs), 0, 0))
    ks = [kv_spec(t, hd) for t in range(pb)]
    vs = [kv_spec(t, hd if hdv is None else hdv) for t in range(pb)]
    return ks, vs


def _paged_scale_specs(ps, mp, pb, decode=False):
    """``pb`` (k_scale, v_scale) BlockSpec pairs — the 8-row group that
    holds the streamed page's (ps,) scale row, indexed by the SAME
    block-table entry as the page itself, so a page and its dequant
    scales always arrive together (the body reads row ``page % 8``)."""
    def sc_spec(t):
        page = _paged_page_index(ps, mp, pb, t, decode)
        return pl.BlockSpec(
            (_SCALE_ROWS, ps),
            lambda *ids_and_refs: (page(*ids_and_refs) // _SCALE_ROWS, 0))
    ks = [sc_spec(t) for t in range(pb)]
    vs = [sc_spec(t) for t in range(pb)]
    return ks, vs


def _block_structured_queries(q, kv):
    """``q (S, Hq, Dh)`` -> ``(S, rows, kv*Dh)``: row ``i`` holds query
    head ``i`` in the lanes of its KV head ``i // (Hq / kv)`` and zeros
    elsewhere; ``rows`` is ``Hq`` padded with zero queries to whole
    tiles. Written as ONE select over a broadcast of the queries laid
    side by side along the lanes (for one query head a KV head that is
    the ``(S, H*Dh)`` row the projection gave, so XLA has nothing to
    re-lay out: a select that unfolds ``(H, Dh)`` first costs five
    small ops and two relayouts a layer)."""
    s_slots, h, dh = q.shape
    group = h // kv
    # whole tiles of rows in whole KV heads
    kv_rows = kv + -kv % (_HEAD_ROWS // math.gcd(group, _HEAD_ROWS))
    side_by_side = q.reshape(s_slots, kv, group, dh).transpose(
        0, 2, 1, 3).reshape(s_slots, 1, group, kv * dh)
    mine = (jnp.arange(kv * dh)[None, :] // dh
            == jnp.arange(kv_rows)[:, None])                 # (kv_rows, hd)
    qb = jnp.where(mine[None, :, None, :], side_by_side,
                   jnp.zeros((), q.dtype))         # (S, kv_rows, group, hd)
    return qb.reshape(s_slots, kv_rows * group, kv * dh)


def _sink_rows(sinks, rows):
    """``sinks`` (H,) as the decode bodies take them: ``(rows, 128)``
    float32, row ``i`` head ``i``'s logit along the lanes (the shape of
    ``m``), the padding rows at 0 (zero queries whose output nobody
    reads)."""
    sinks = sinks.astype(jnp.float32)
    return jnp.broadcast_to(
        jnp.pad(sinks, (0, rows - sinks.shape[0]))[:, None], (rows, 128))


@functools.partial(jax.jit, static_argnums=(5, 6),
                   static_argnames=("name", "window"))
def _paged_attend_pallas(q, k_pages, v_pages, block_tables, geometry,
                         interpret, pages_per_block, k_scales, v_scales,
                         selected=None, name=None, window=None, sinks=None):
    """The one ``pallas_call`` behind the pipelined paged kernels (all
    but dense decode). Jitted, so
    that a step program traces and lowers the kernel body once and calls
    it from every layer (same shapes, same static arguments: JAX reuses
    the inner function's jaxpr and XLA inlines the calls), where each of
    a model's layers used to trace its own copy: most of a signature's
    warm-up time. ``q`` is already scaled: ``(S, H, Dh)`` for decode,
    head-major ``(S, H, C, Dh)`` for chunked prefill; ``geometry`` is
    the scalar-prefetch tail after the block table — ``(lengths,)`` for
    decode, ``(chunk_starts, n_valid)`` for chunked prefill.
    ``k_scales``/``v_scales`` given = the dequant-attend variant;
    ``selected`` (S, C, mp*ps) given = chunked prefill under a per-query
    selection; ``name`` renames the call for a device trace (sparse
    prefill runs this body under its own name); ``sinks`` (H,) given = a
    learned logit a head in the softmax's denominator. The V pool's heads
    may be narrower than the K pool's (``Dv``; the output's width)."""
    quantized = k_scales is not None
    chunked = len(geometry) == 2
    s_slots, h = q.shape[:2]
    dh = q.shape[-1]
    mp = block_tables.shape[1]
    ps, hd = k_pages.shape[1:]
    hdv = v_pages.shape[-1]
    dv = _value_dim(q, k_pages, v_pages)
    pb = max(1, min(int(pages_per_block), mp))
    if chunked:
        rows = q.shape[2]
        q_block, out_block = (1, h, rows, dh), (1, h, rows, dv)
        state, acc = (h, rows, 128), (h, rows, dv)
        kernel = functools.partial(_paged_prefill_kernel,
                                   selected=selected is not None)
    else:
        # the queries of a slot as ONE matrix over the page's lanes
        q = _block_structured_queries(q, hd // dh)
        rows = q.shape[1]
        q_block, out_block = (1, rows, hd), (1, rows, dv)
        state, acc = (rows, 128), (rows, hdv)
        kernel = functools.partial(_paged_decode_kernel, n_heads=h)
    if window is not None:
        kernel = functools.partial(kernel, window=window)

    k_specs, v_specs = _paged_kv_specs(ps, hd, mp, pb, decode=not chunked,
                                       window=window, hdv=hdv)
    sel_specs, sel_args = [], []
    if selected is not None:
        sel_specs = [pl.BlockSpec(
            (1, rows, pb * ps), lambda s, j, *_prefetch: (s, 0, j))]
        sel_args = [selected]
    if sinks is not None:
        kernel = functools.partial(kernel, sink=True)
        sink = jnp.broadcast_to(
            sinks.astype(jnp.float32)[:, None, None], (h, 1, 128)) \
            if chunked else _sink_rows(sinks, rows)
        sel_specs.append(pl.BlockSpec(
            sink.shape, lambda s, j, *_prefetch: (0,) * sink.ndim))
        sel_args.append(sink)
    sc_specs, sc_args = [], []
    if quantized:
        ks_specs, vs_specs = _paged_scale_specs(ps, mp, pb,
                                                decode=not chunked)
        sc_specs = [*ks_specs, *vs_specs]
        sc_args = [*([k_scales] * pb), *([v_scales] * pb)]

    def slot_block(shape):
        return pl.BlockSpec(shape, lambda s, j, *_prefetch:
                            (s,) + (0,) * (len(shape) - 1))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1 + len(geometry),
        grid=(s_slots, pl.cdiv(mp, pb)),
        in_specs=[
            slot_block(q_block),
            *sel_specs,
            *k_specs,
            *v_specs,
            *sc_specs,
        ],
        out_specs=slot_block(out_block),
        scratch_shapes=[
            pltpu.VMEM(state, jnp.float32),
            pltpu.VMEM(state, jnp.float32),
            pltpu.VMEM(acc, jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(kernel, page_size=ps, pages_per_block=pb,
                          quantized=quantized),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((s_slots,) + out_block[1:], q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            # a chunk of grouped-query heads unrolls (heads x pages)
            # folds whose fp32 temporaries the compiler stacks side by
            # side: 33 MB at 32 heads x 64 queries x 4 pages against the
            # default scoped limit of 16 MB (v5e has 128 MB of VMEM)
            vmem_limit_bytes=_WIDE_VMEM_LIMIT if chunked and (
                selected is not None or h * dh != hd)
            else None,
        ) if not interpret else None,
        interpret=interpret,
        name=name or ("ragged_paged_prefill" if chunked
                      else "ragged_paged_decode"),
    )(block_tables.astype(jnp.int32),
      *(g.astype(jnp.int32) for g in geometry),
      q, *sel_args, *([k_pages] * pb), *([v_pages] * pb), *sc_args)
    return out if chunked else out[:, :h]


def _paged_decode_pallas(q, k_pages, v_pages, block_tables, lengths, scale,
                         interpret, pages_per_block=1, k_scales=None,
                         v_scales=None, window=None, sinks=None):
    """The pipelined decode call: a pool whose pages are not whole tiles
    and, with ``k_scales``/``v_scales`` given, the dequant-attend
    variant: same grid and BlockSpecs plus one scale-row group per
    streamed page, multiplied into the all-heads scores and weights
    inside the one body. Grouped-query heads are read off the shapes (the
    page's lanes hold ``kv <= H`` heads of ``Dh``). Dense decode over
    whole tiles has a body of its own, :func:`_paged_decode_walk_pallas`,
    and so has sparse decode (``sparse_attention._sparse_decode_pallas``)."""
    return _paged_attend_pallas(
        q * jnp.asarray(scale, q.dtype), k_pages, v_pages, block_tables,
        (lengths,), interpret, pages_per_block, k_scales, v_scales,
        window=window, sinks=sinks)


# ---------------------------------------------------------------------------
# dense decode: the kernel walks a slot's live pages itself
# ---------------------------------------------------------------------------
#
# A ``BlockSpec`` pipeline lays a fixed grid over the block table's
# width: every grid step runs the index maps and DMA checks of its page
# operands whether or not a page moves, and the body meets a page at a
# time, one softmax chain each. A slot of the serving cells holds 3 to 9
# live pages of 64 to 192 KB, so those fixed costs were most of the
# call (1.4 us of bytes in 5.75 us a slot at a page row of 256 lanes:
# PERF.md section 6, PR 39). Here the grid is ``(S,)``, the pools stay
# in HBM, and the body copies the slot's LIVE pages side by side into
# one of two VMEM buffers (``pages_per_block`` pages each) while it
# folds the other, so a block of pages is ONE product against ``Q``,
# one softmax update, one product with ``V``. The last fold of a slot
# runs beside the copies of the next slot's first block. Exactly the
# fetched pages are folded (one region a count of pages, as in the
# pipelined body), so nothing a buffer held before reaches an output.
# The int8 twin and a pool whose pages are not whole tiles keep the
# pipelined body above.

def _paged_decode_walk_kernel(bt_ref, len_ref, q_ref, *refs, page_size,
                              pages_per_block, n_heads, window=None,
                              sink=False):
    """The dense decode body: grid ``(S,)``, one step a slot. ``q_ref``,
    ``o_ref`` and the ``m / l / acc`` state are
    :func:`_paged_decode_kernel`'s; ``k_hbm`` / ``v_hbm`` are the whole
    pools in HBM, ``k_buf`` / ``v_buf`` ``(2, pb*ps, kv*Dh)`` the two
    blocks in VMEM, ``sems`` ``(2, 2)`` one DMA semaphore a (pool,
    buffer), ``first_buf`` the buffer that holds the slot's first block
    (it alternates block by block across slots, so a slot's first block
    can be on its way while the slot before still folds).

    ``window``: a query attends to the slot's last ``window`` tokens only.
    The walk then starts at the page that holds the first of them (block
    ``b`` of a slot is its pages ``first + b*pb ..``), so it copies and
    folds ``pages_for(window) + 1`` pages at most whatever the length,
    and the rows of that page before the window are masked in float32
    as the rows past the extent are.

    ``sink``: ``(rows, 128)`` float32 comes after ``q_ref``, as in
    :func:`_paged_decode_kernel`; ``k_buf`` spans the K pool's lanes
    (``kv*Dk``) and ``v_buf`` the V pool's (``kv*Dv``)."""
    sink_ref = None
    if sink:
        sink_ref, refs = refs[0], refs[1:]
    (k_hbm, v_hbm, o_ref, k_buf, v_buf, sems, first_buf, m_scr, l_scr,
     acc_scr) = refs
    ps, pb = page_size, pages_per_block
    sl = pl.program_id(0)
    n_slots = pl.num_programs(0)
    rows = q_ref.shape[1]
    group = n_heads * o_ref.shape[-1] // acc_scr.shape[-1]  # heads a KV head

    def live_pages(slot):
        return (len_ref[slot] + ps - 1) // ps

    def walked(slot, page):
        """Page ``page`` of the slot's walk as a page of its table: the
        walk starts at page 0, or at the window's first page."""
        if window is None:
            return page
        return jnp.maximum(len_ref[slot] - window, 0) // ps + page

    extent = len_ref[sl]
    # the live pages from the walk's first on
    n_live = live_pages(sl) if window is None \
        else live_pages(sl) - walked(sl, 0)
    n_blocks = (n_live + pb - 1) // pb

    def copies(slot, block, buf, start):
        """Start, or wait for, the copies of one block of a slot: its
        live page ``block*pb + t`` out of each pool into rows ``t*ps ..``
        of that pool's buffer ``buf``, all of a buffer's on its one DMA
        semaphore. A wait rebuilds the descriptors its start was made
        from."""
        n = live_pages(slot)
        for t in range(pb):
            p = walked(slot, block * pb + t)
            # a dead page's table entry is never read past the table
            page = bt_ref[slot, jnp.minimum(p, bt_ref.shape[1] - 1)]

            @pl.when(p < n)
            def _live_page():
                for i, (pool, vmem) in enumerate(((k_hbm, k_buf),
                                                  (v_hbm, v_buf))):
                    copy = pltpu.make_async_copy(
                        pool.at[page], vmem.at[buf, pl.ds(t * ps, ps)],
                        sems.at[i, buf])
                    copy.start() if start else copy.wait()

    @pl.when(sl == 0)
    def _first_slot():
        first_buf[0] = 0

    # the slot before started this slot's first block beside its own
    # last fold; an empty one folded nothing, and nobody precedes slot 0
    @pl.when((sl == 0) | (live_pages(jnp.maximum(sl - 1, 0)) == 0))
    def _own_first_block():
        copies(sl, 0, first_buf[0], start=True)

    _decode_start(sink_ref, m_scr, l_scr, acc_scr)

    def fold(block, buf, n_pages):
        """Block ``block``'s ``n_pages`` fetched pages as ONE update."""
        width = n_pages * ps
        q = q_ref[0]
        s = _exact_page_dot(q, k_buf[buf, :width], 1)       # (rows, width)
        start = block * (pb * ps) if window is None \
            else walked(sl, block * pb) * ps
        tok = start + jax.lax.broadcasted_iota(
            jnp.int32, (rows, width), 1)
        live = tok < extent
        if window is not None:
            live = live & (tok >= extent - window)
        s = jnp.where(live, s, NEG_INF)
        m = m_scr[...]
        m_next = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m - m_next)                          # (rows, 128)
        p = jnp.exp(s - m_next[:, :1])                       # (rows, width)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        m_scr[...] = m_next
        acc_scr[...] = acc_scr[...] * alpha[:, :1] + _exact_page_dot(
            p, v_buf[buf, :width], 0)                        # (rows, kv*Dh)

    def walk(block, _):
        buf = (first_buf[0] + block) % 2
        last = block == n_blocks - 1
        # what arrives while this block is folded: the slot's next block,
        # or after its last the first block of the slot that follows
        nxt_slot = jnp.where(last, jnp.minimum(sl + 1, n_slots - 1), sl)

        @pl.when(~last | (sl + 1 < n_slots))
        def _next_block():
            copies(nxt_slot, jnp.where(last, 0, block + 1), 1 - buf,
                   start=True)

        copies(sl, block, buf, start=False)
        here = jnp.minimum(n_live - block * pb, pb)
        for n_pages in range(1, pb + 1):
            pl.when(here == n_pages)(
                functools.partial(fold, block, buf, n_pages))

    jax.lax.fori_loop(0, n_blocks, walk, None)
    first_buf[0] = (first_buf[0] + n_blocks) % 2
    _decode_finish(o_ref, m_scr, l_scr, acc_scr, group)


@functools.partial(jax.jit, static_argnums=(5, 6),
                   static_argnames=("window",))
def _paged_decode_walk_pallas(q, k_pages, v_pages, block_tables, lengths,
                              interpret, pages_per_block, window=None,
                              sinks=None):
    """The ``pallas_call`` of the dense decode entry
    (``ragged_paged_decode``), jitted like :func:`_paged_attend_pallas`
    so that a step program traces and lowers the body once. ``q`` is
    already scaled."""
    s_slots, h, dh = q.shape
    ps, hd = k_pages.shape[1:]
    hdv = v_pages.shape[-1]
    dv = _value_dim(q, k_pages, v_pages)
    pb = max(1, min(int(pages_per_block), block_tables.shape[1]))
    q = _block_structured_queries(q, hd // dh)
    rows = q.shape[1]
    static = {} if window is None else {"window": window}
    sink_specs, sink_args = [], []
    if sinks is not None:
        static["sink"] = True
        sink_specs = [pl.BlockSpec((rows, 128), lambda s, *_prefetch: (0, 0))]
        sink_args = [_sink_rows(sinks, rows)]

    def slot_block(shape):
        return pl.BlockSpec(shape, lambda s, *_prefetch: (s, 0, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(s_slots,),
        in_specs=[slot_block((1, rows, hd)),
                  *sink_specs,
                  pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=slot_block((1, rows, dv)),
        scratch_shapes=[
            pltpu.VMEM((2, pb * ps, hd), k_pages.dtype),
            pltpu.VMEM((2, pb * ps, hdv), v_pages.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SMEM((1,), jnp.int32),
            pltpu.VMEM((rows, 128), jnp.float32),
            pltpu.VMEM((rows, 128), jnp.float32),
            pltpu.VMEM((rows, hdv), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_paged_decode_walk_kernel, page_size=ps,
                          pages_per_block=pb, n_heads=h, **static),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((s_slots, rows, dv), q.dtype),
        # one slot after the other: a slot's last fold runs beside the
        # copies it started for the next
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)) if not interpret else None,
        interpret=interpret,
        name="ragged_paged_decode",
    )(block_tables.astype(jnp.int32), lengths.astype(jnp.int32), q,
      *sink_args, k_pages, v_pages)
    return out[:, :h]


# ---------------------------------------------------------------------------
# int8 dequant-attend decode: same grid, scales fused into QK/PV
# ---------------------------------------------------------------------------

def _paged_decode_int8_lax(q, k_pages, v_pages, k_scales, v_scales,
                           block_tables, lengths, scale):
    """Lax fallback of the dequant-attend decode kernel: gather the INT8
    pages (half the HBM bytes of bf16) and fold the per-token-row scales
    into the score and weight matrices — structurally the same
    scale-after-dot order as the Pallas body, so numerics agree. The
    int8 pools pass through :func:`slim.int8_resident` so a frozen
    graph that bakes them as constants cannot be constant-folded to fp
    (the keep-quantized idiom, shared with weight PTQ)."""
    from paddle_tpu import slim
    k_pages = slim.int8_resident(k_pages)
    v_pages = slim.int8_resident(v_pages)
    s_slots, h, dh = q.shape
    mp = block_tables.shape[1]
    ps = k_pages.shape[1]
    kg = _gather_pages(k_pages, block_tables, h, dh)    # int8
    vg = _gather_pages(v_pages, block_tables, h, dh)
    ksg = k_scales[block_tables]                # (S, mp, ps) f32
    vsg = v_scales[block_tables]
    scores = jnp.einsum("shd,smthd->shmt", q.astype(jnp.float32),
                        kg.astype(jnp.float32)) * scale
    scores = scores * ksg[:, None]              # dequant fused post-dot
    scores = scores.reshape(s_slots, h, mp * ps)
    tok = jnp.arange(mp * ps, dtype=jnp.int32)
    valid = tok[None, None, :] < lengths[:, None, None]
    scores = jnp.where(valid, scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    alive = jnp.max(scores, axis=-1, keepdims=True) > NEG_INF / 2
    p = jnp.where(alive, p, 0.0).reshape(s_slots, h, mp, ps)
    p = p * vsg[:, None]                        # dequant fused pre-PV
    out = jnp.einsum("shmt,smthd->shd", p, vg.astype(jnp.float32))
    return out.astype(q.dtype)


def _paged_decode_int8_pallas(q, k_pages, v_pages, k_scales, v_scales,
                              block_tables, lengths, scale, interpret,
                              pages_per_block=1):
    """The dequant-attend decode entry: the SAME kernel body as the fp
    path with ``quantized=True`` — per-page scale rows ride as ``pb``
    extra blocks beside the pages, fused into the QK/PV products inside
    the fold (no materialized fp page)."""
    return _paged_decode_pallas(q, k_pages, v_pages, block_tables,
                                lengths, scale, interpret,
                                pages_per_block=pages_per_block,
                                k_scales=k_scales, v_scales=v_scales)


# ---------------------------------------------------------------------------
# batched chunked prefill: lax reference + Pallas kernel
# ---------------------------------------------------------------------------

def _paged_prefill_lax(q, k_pages, v_pages, block_tables, chunk_starts,
                       n_valid, scale, selected=None, window=None,
                       sinks=None):
    """``selected`` (S, C, mp*ps), where given, marks the cache positions
    each query may attend to beside the causal test (sparse attention:
    the indexer's choice). ``sinks`` (H,): a learned term a head in the
    softmax's denominator (:func:`_sink_softmax`)."""
    s_slots, c, h, dh = q.shape
    mp = block_tables.shape[1]
    ps = k_pages.shape[1]
    kg = _gather_pages(k_pages, block_tables, h, dh)
    vg = _gather_pages(v_pages, block_tables, h,
                       _value_dim(q, k_pages, v_pages))
    scores = jnp.einsum("schd,smthd->shcmt", q.astype(jnp.float32),
                        kg.astype(jnp.float32)) * scale
    scores = scores.reshape(s_slots, h, c, mp * ps)
    tok = jnp.arange(mp * ps, dtype=jnp.int32)
    pos = chunk_starts[:, None] + jnp.arange(c, dtype=jnp.int32)  # (S, C)
    causal = tok[None, None, None, :] <= pos[:, None, :, None]
    row_ok = (jnp.arange(c) < n_valid[:, None])[:, None, :, None]
    ok = causal & row_ok
    if window is not None:      # the last ``window`` tokens, itself counted
        ok = ok & (tok[None, None, None, :] > pos[:, None, :, None] - window)
    if selected is not None:
        ok = ok & (selected[:, None] > 0)
    scores = jnp.where(ok, scores, NEG_INF)
    if sinks is not None:
        p = _sink_softmax(scores,
                          sinks.astype(jnp.float32)[None, :, None, None])
    else:
        p = jax.nn.softmax(scores, axis=-1)
        # masked rows (padding lanes / inactive slots) emit exact zeros
        alive = jnp.max(scores, axis=-1, keepdims=True) > NEG_INF / 2
        p = jnp.where(alive, p, 0.0)
    p = p.reshape(s_slots, h, c, mp, ps)
    out = jnp.einsum("shcmt,smthd->schd", p, vg.astype(jnp.float32))
    return out.astype(q.dtype)


def _paged_prefill_pallas(q, k_pages, v_pages, block_tables, chunk_starts,
                          n_valid, scale, interpret, pages_per_block=1,
                          k_scales=None, v_scales=None, selected=None,
                          name=None, window=None, sinks=None):
    """Chunked-prefill analog of :func:`_paged_decode_pallas`: the same
    call site with the chunked body, same ``pages_per_block`` tunable,
    outputs bit-equal for any setting of it. ``q`` (S, C, H, Dh) is handed to the
    kernel head-major; the engine holds it head-major already, so XLA
    cancels this transpose against the caller's."""
    qs = (q * jnp.asarray(scale, q.dtype)).transpose(0, 2, 1, 3)
    out = _paged_attend_pallas(qs, k_pages, v_pages, block_tables,
                               (chunk_starts, n_valid), interpret,
                               pages_per_block, k_scales, v_scales,
                               selected=selected, name=name, window=window,
                               sinks=sinks)
    return out.transpose(0, 2, 1, 3)                        # (S,C,H,Dv)


# ---------------------------------------------------------------------------
# int8 dequant-attend prefill
# ---------------------------------------------------------------------------

def _paged_prefill_int8_lax(q, k_pages, v_pages, k_scales, v_scales,
                            block_tables, chunk_starts, n_valid, scale):
    """Lax fallback of the dequant-attend prefill kernel (the int8 twin
    of :func:`_paged_prefill_lax`; same scale-after-dot order as the
    Pallas body, int8 pools barriered against constant folding)."""
    from paddle_tpu import slim
    k_pages = slim.int8_resident(k_pages)
    v_pages = slim.int8_resident(v_pages)
    s_slots, c, h, dh = q.shape
    mp = block_tables.shape[1]
    ps = k_pages.shape[1]
    kg = _gather_pages(k_pages, block_tables, h, dh)    # int8
    vg = _gather_pages(v_pages, block_tables, h, dh)
    ksg = k_scales[block_tables]                # (S, mp, ps) f32
    vsg = v_scales[block_tables]
    scores = jnp.einsum("schd,smthd->shcmt", q.astype(jnp.float32),
                        kg.astype(jnp.float32)) * scale
    scores = scores * ksg[:, None, None]        # dequant fused post-dot
    scores = scores.reshape(s_slots, h, c, mp * ps)
    tok = jnp.arange(mp * ps, dtype=jnp.int32)
    pos = chunk_starts[:, None] + jnp.arange(c, dtype=jnp.int32)  # (S, C)
    causal = tok[None, None, None, :] <= pos[:, None, :, None]
    row_ok = (jnp.arange(c) < n_valid[:, None])[:, None, :, None]
    scores = jnp.where(causal & row_ok, scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    alive = jnp.max(scores, axis=-1, keepdims=True) > NEG_INF / 2
    p = jnp.where(alive, p, 0.0).reshape(s_slots, h, c, mp, ps)
    p = p * vsg[:, None, None]                  # dequant fused pre-PV
    out = jnp.einsum("shcmt,smthd->schd", p, vg.astype(jnp.float32))
    return out.astype(q.dtype)


def _paged_prefill_int8_pallas(q, k_pages, v_pages, k_scales, v_scales,
                               block_tables, chunk_starts, n_valid,
                               scale, interpret, pages_per_block=1):
    """The dequant-attend prefill entry: the SAME kernel body as the fp
    path with ``quantized=True`` (see :func:`_paged_decode_int8_pallas`
    for the convention)."""
    return _paged_prefill_pallas(q, k_pages, v_pages, block_tables,
                                 chunk_starts, n_valid, scale, interpret,
                                 pages_per_block=pages_per_block,
                                 k_scales=k_scales, v_scales=v_scales)


# ---------------------------------------------------------------------------
# public entry points
# ---------------------------------------------------------------------------

# ---------------------------------------------------------------------------
# latent rows: every head reads ONE cached row a token, values are part of it
# ---------------------------------------------------------------------------
#
# Multi-head latent attention in its absorbed form: a token caches one
# row ``[c (Dl) | k_rope (Dr)]`` a layer, every head's query ``(Dl + Dr)``
# scores against that row, and what a head sums under its softmax weights
# is the row's first ``Dl`` values (the up-projections are folded into the
# queries and into the output projection by the model). The pool keeps the
# two parts where each is read without a relayout and in whole tiles:
#
#   c_pages  (P, ps, Dl)   token-major, the shape the dense pools have:
#                          ``q_c c^T`` contracts the lanes, ``P c`` the rows
#   r_pages  (P, Dr, ps)   the rotary key with the tokens along the lanes
#                          (as ``extra_rows`` are kept): ``q_r k_rope^T``
#                          is a plain product, and 64 rows of 128 tokens
#                          are whole tiles where ``(ps, 64)`` would be half
#
# so a row is ``(Dl + Dr) * itemsize`` bytes and nothing is padded. The
# page of ``c`` is read ONCE for both products. Queries come already
# scaled. Decode walks live pages itself, as dense decode does
# (:func:`_paged_decode_walk_kernel`): two VMEM buffers of
# ``pages_per_block`` pages, a block one softmax update for all rows. It
# is two calls: the pages that the tables of several decoding slots open
# with are walked ONCE a group of those slots, their queries stacked
# against one copy of each page (Part A, one grid step a group), and each
# slot's own pages a slot from the state Part A handed it (Part B, one
# grid step a slot); a slot that shares nothing is Part B's alone. Who
# shares what comes from the host, which holds the tables
# (:func:`decode_groups`). Chunked prefill stacks ``q_rows`` (heads x
# queries) of a lane as the rows of one matrix and streams the lane's pages
# past it through the ``BlockSpec`` pipeline, grid ``(S, query tiles, page
# blocks)``.

def _latent_gather(c_pages, r_pages, block_tables):
    """A slot batch's rows out of the two pools, token-major:
    ``(S, mp*ps, Dl)`` and ``(S, mp*ps, Dr)`` float32 (lax path only)."""
    s, mp = block_tables.shape
    cg = c_pages[block_tables].astype(jnp.float32)         # (S,mp,ps,Dl)
    rg = r_pages[block_tables].astype(jnp.float32)         # (S,mp,Dr,ps)
    return (cg.reshape(s, mp * cg.shape[2], -1),
            rg.transpose(0, 1, 3, 2).reshape(s, mp * cg.shape[2], -1))


def _latent_softmax(scores, ok):
    """Masked float32 softmax over the last axis; a row with nothing to
    attend to gives zeros."""
    scores = jnp.where(ok, scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    alive = jnp.max(scores, axis=-1, keepdims=True) > NEG_INF / 2
    return jnp.where(alive, p, 0.0)


def _latent_decode_lax(q, c_pages, r_pages, block_tables, lengths,
                       *_groups):
    """Attention a slot; which slots share pages changes no result."""
    dl = c_pages.shape[-1]
    cg, rg = _latent_gather(c_pages, r_pages, block_tables)
    qf = q.astype(jnp.float32)
    scores = jnp.einsum("shd,std->sht", qf[..., :dl], cg,
                        precision=_FP32_DOT) \
        + jnp.einsum("shd,std->sht", qf[..., dl:], rg, precision=_FP32_DOT)
    tok = jnp.arange(cg.shape[1], dtype=jnp.int32)
    p = _latent_softmax(scores, tok[None, None, :] < lengths[:, None, None])
    return jnp.einsum("sht,std->shd", p, cg,
                      precision=_FP32_DOT).astype(q.dtype)


def _latent_prefill_lax(q, c_pages, r_pages, block_tables, chunk_starts,
                        n_valid):
    dl, c = c_pages.shape[-1], q.shape[1]
    cg, rg = _latent_gather(c_pages, r_pages, block_tables)
    qf = q.astype(jnp.float32)
    scores = jnp.einsum("schd,std->shct", qf[..., :dl], cg,
                        precision=_FP32_DOT) \
        + jnp.einsum("schd,std->shct", qf[..., dl:], rg, precision=_FP32_DOT)
    tok = jnp.arange(cg.shape[1], dtype=jnp.int32)
    pos = chunk_starts[:, None] + jnp.arange(c, dtype=jnp.int32)  # (S, C)
    ok = (tok[None, None, None, :] <= pos[:, None, :, None]) \
        & (jnp.arange(c) < n_valid[:, None])[:, None, :, None]
    p = _latent_softmax(scores, ok)
    return jnp.einsum("shct,std->schd", p, cg,
                      precision=_FP32_DOT).astype(q.dtype)


def _pool_dot(x, page, contract_page_dim):
    """``x (rows, .)`` times a page block with both operands in the
    pool's type, float32 out: one pass over a bf16 pool (``x`` ROUNDED to
    it, where :func:`_exact_page_dot` would split it into three
    terms), ``HIGHEST`` over a float32 one (the CPU tests)."""
    return _exact_page_dot(x.astype(page.dtype), page, contract_page_dim)


#: slots of one group: how many decoding slots whose tables open with the
#: same pages are stacked against ONE copy of those pages. A block's cost
#: on the chip is its bytes once and its products a member (PERF.md
#: section 6, PR 43): 8 folds nearly every document of the sessions cell
#: into one walk, and a group pays for the members it has
DECODE_GROUP = 8

#: a group's shared pages come in multiples of this many: every
#: ``pages_per_block`` the kernel may be given divides it, so Part A
#: folds whole blocks only and the pages left over are each slot's own
GROUP_SHARED_PAGES = 8

#: lanes of a slot's unnormalised state behind its accumulator: the
#: running maximum and the running sum, a lane tile each
_STATE_LANES = 256


def _latent_fold(qc_ref, qr_ref, c_buf, r_buf, buf, m_scr, l_scr, acc_scr,
                 *, width, first_token=None, extent=None):
    """One softmax update of the rows' state (``m_scr / l_scr / acc_scr``)
    with the first ``width`` tokens of block ``buf``: ``S = q_c C^T + q_r
    R`` for every row at once, one maximum, one exponential, ``A += P C``
    with the ``C`` the scores were taken from. ``qc_ref`` ``(rows, Dl)``
    and ``qr_ref`` ``(rows, Dr)`` are absorbed queries, a head (of a
    slot) a row. Tokens from ``extent`` on are masked, the block's first
    being token ``first_token``; no ``extent``: every token is live."""
    c = c_buf[buf, :width]                                   # (width, Dl)
    s = _exact_page_dot(qc_ref[...], c, 1) \
        + _exact_page_dot(qr_ref[...], r_buf[buf, :, :width], 0)
    if extent is not None:
        tok = first_token + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(tok < extent, s, NEG_INF)
    m = m_scr[...]
    m_next = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m - m_next)                              # (rows, 128)
    p = jnp.exp(s - m_next[:, :1])                           # (rows, width)
    l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    m_scr[...] = m_next
    acc_scr[...] = acc_scr[...] * alpha[:, :1] \
        + _exact_page_dot(p, c, 0)                           # (rows, Dl)


def _page_walk(walk_of, fold, bt_ref, moves, sems, first_buf, *,
               pages_per_block):
    """One grid step's walk of a decode body that copies its pages
    itself, laid out as :func:`_paged_decode_walk_kernel` is:
    ``walk_of(step)`` gives (table row, first page of the row, pages) of
    a step's walk; the pages are copied ``pages_per_block`` side by side
    out of the pools into one of two VMEM buffers a pool while the other
    is folded, a step's first block started by the step before.
    ``moves(page, buf, t)`` names the copies of pool page ``page`` into
    place ``t`` of buffer ``buf``, one ``(source, destination)`` a pool;
    ``sems`` ``(pools, 2)`` one DMA semaphore a (pool, buffer).
    ``fold(block, buf, pages)`` folds block ``block`` of the step's walk,
    which lies in buffer ``buf`` and holds ``pages`` pages."""
    pb = pages_per_block
    step = pl.program_id(0)
    n_steps = pl.num_programs(0)
    n_live = walk_of(step)[2]
    n_blocks = (n_live + pb - 1) // pb

    def copies(of, block, buf, start):
        """Start, or wait for, the copies of one block of a step's walk:
        its page ``block*pb + t`` out of each pool into place ``t`` of
        that pool's buffer ``buf``."""
        row, first_page, n = walk_of(of)
        for t in range(pb):
            p = block * pb + t
            page = bt_ref[row, jnp.minimum(first_page + p,
                                           bt_ref.shape[1] - 1)]

            @pl.when(p < n)
            def _live_page():
                for i, (src, dst) in enumerate(moves(page, buf, t)):
                    copy = pltpu.make_async_copy(src, dst, sems.at[i, buf])
                    copy.start() if start else copy.wait()

    @pl.when(step == 0)
    def _first_step():
        first_buf[0] = 0

    @pl.when((step == 0) | (walk_of(jnp.maximum(step - 1, 0))[2] == 0))
    def _own_first_block():
        copies(step, 0, first_buf[0], start=True)

    def walk(block, _):
        buf = (first_buf[0] + block) % 2
        last = block == n_blocks - 1
        nxt = jnp.where(last, jnp.minimum(step + 1, n_steps - 1), step)

        @pl.when(~last | (step + 1 < n_steps))
        def _next_block():
            copies(nxt, jnp.where(last, 0, block + 1), 1 - buf, start=True)

        copies(step, block, buf, start=False)
        fold(block, buf, jnp.minimum(n_live - block * pb, pb))

    jax.lax.fori_loop(0, n_blocks, walk, None)
    first_buf[0] = (first_buf[0] + n_blocks) % 2


def _latent_walk(walk_of, fold, bt_ref, c_hbm, r_hbm, c_buf, r_buf, sems,
                 first_buf, *, page_size, pages_per_block):
    """:func:`_page_walk` over the two latent pools: ``c_buf`` ``(2,
    pb*ps, Dl)``, ``r_buf`` ``(2, Dr, pb*ps)``: a page's rotary keys land
    in the lanes of its tokens."""
    ps, dr = page_size, r_buf.shape[1]

    def moves(page, buf, t):
        return ((c_hbm.at[page], c_buf.at[buf, pl.ds(t * ps, ps)]),
                (r_hbm.at[page],
                 r_buf.at[buf, pl.ds(0, dr), pl.ds(t * ps, ps)]))

    _page_walk(walk_of, fold, bt_ref, moves, sems, first_buf,
               pages_per_block=pages_per_block)


def _latent_decode_shared_kernel(bt_ref, gs_ref, gp_ref, *refs, page_size,
                                 pages_per_block, members):
    """Part A of the latent decode: grid ``(groups,)``, one step a group
    of up to ``members`` slots whose tables open with the same
    ``gp_ref[g]`` pages, whole blocks of them. The members' queries
    (``members`` blocks ``(1, rows, Dl)`` then as many ``(1, rows, Dr)``,
    found by ``gs_ref``, a group's members first) are stacked as the rows
    of one matrix against ONE copy of each shared page
    (:func:`_latent_walk` over the first member's table; every row of a
    shared page is live for every member, so nothing is masked; a block
    is folded for the rows of the members the group has), and the
    members' unnormalised float32 states ``[acc | m | l]`` leave as the
    group's block ``(1, members*rows, Dl + 256)``. A group without pages
    does nothing."""
    g, ps, pb = members, page_size, pages_per_block
    qc_refs, qr_refs = refs[:g], refs[g:2 * g]
    (c_hbm, r_hbm, st_ref, c_buf, r_buf, sems, first_buf, qc_scr, qr_scr,
     m_scr, l_scr, acc_scr) = refs[2 * g:]
    rows, dl = qc_refs[0].shape[1:]
    grp = pl.program_id(0)
    held = sum((gs_ref[grp * g + j] >= 0).astype(jnp.int32)
               for j in range(g))

    def walk_of(of):
        return jnp.maximum(gs_ref[of * g], 0), 0, gp_ref[of]

    def fold(_block, buf, _pages):
        for n in range(2, g + 1):       # a group of one is folded as two
            head = pl.ds(0, n * rows)
            pl.when(jnp.maximum(held, 2) == n)(functools.partial(
                _latent_fold, qc_scr.at[head], qr_scr.at[head], c_buf,
                r_buf, buf, m_scr.at[head], l_scr.at[head],
                acc_scr.at[head], width=pb * ps))

    @pl.when(gp_ref[grp] > 0)
    def _stack():
        for j in range(g):
            qc_scr[j * rows:(j + 1) * rows] = qc_refs[j][0]
            qr_scr[j * rows:(j + 1) * rows] = qr_refs[j][0]
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    _latent_walk(walk_of, fold, bt_ref, c_hbm, r_hbm, c_buf, r_buf, sems,
                 first_buf, page_size=ps, pages_per_block=pb)

    @pl.when(gp_ref[grp] > 0)
    def _hand_over():
        st_ref[0, :, :dl] = acc_scr[...]
        st_ref[0, :, dl:dl + 128] = m_scr[...]
        st_ref[0, :, dl + 128:] = l_scr[...]


def _latent_decode_own_kernel(bt_ref, len_ref, sp_ref, row_ref, qc_ref,
                              qr_ref, st_ref, c_hbm, r_hbm, o_ref, c_buf,
                              r_buf, sems, first_buf, m_scr, l_scr, acc_scr,
                              *, page_size, pages_per_block, spare):
    """Part B of the latent decode: grid ``(S,)``, one step a slot. The
    slot's state starts from what Part A left it (``st_ref`` ``(1, rows,
    Dl + 256)``, block ``row_ref[slot]`` of the states) where
    ``sp_ref[slot]`` of its pages were folded with its group's, empty
    where its block is the ``spare`` one; :func:`_latent_walk` goes over
    its own live pages from there on, and the state is normalised into
    ``o_ref``."""
    ps, pb = page_size, pages_per_block
    sl = pl.program_id(0)
    dl = o_ref.shape[2]

    def walk_of(of):
        n = (len_ref[of] + ps - 1) // ps
        shared = jnp.minimum(sp_ref[of], n)
        return of, shared, n - shared

    def fold(block, buf, pages):
        for n_pages in range(1, pb + 1):
            pl.when(pages == n_pages)(functools.partial(
                _latent_fold, qc_ref.at[0], qr_ref.at[0], c_buf, r_buf, buf,
                m_scr, l_scr, acc_scr, width=n_pages * ps,
                first_token=(walk_of(sl)[1] + block * pb) * ps,
                extent=len_ref[sl]))

    folded = row_ref[sl] != spare

    @pl.when(folded)
    def _from_the_group():
        acc_scr[...] = st_ref[0, :, :dl]
        m_scr[...] = st_ref[0, :, dl:dl + 128]
        l_scr[...] = st_ref[0, :, dl + 128:]

    @pl.when(~folded)
    def _from_nothing():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    _latent_walk(walk_of, fold, bt_ref, c_hbm, r_hbm, c_buf, r_buf, sems,
                 first_buf, page_size=ps, pages_per_block=pb)
    denom = l_scr[...][:, :1]
    denom = jnp.where(denom == 0.0, 1.0, denom)
    alive = m_scr[...][:, :1] > NEG_INF / 2
    o_ref[0] = jnp.where(alive, acc_scr[...] / denom, 0.0).astype(o_ref.dtype)


def _group_state_rows(group_slots, shared_pages, lengths, spare):
    """Where Part B of a folded decode finds each slot's state:
    ``group_slots`` (groups * G,) flat, member ``j`` of group ``grp`` at
    row ``grp * G + j`` of the states seen a member a row; a slot that is
    in no group, had no whole block folded or is dead at row ``spare``
    (the spare group's first)."""
    mine = group_slots[None, :] == jnp.arange(lengths.shape[0])[:, None]
    return jnp.where(mine.any(1) & (shared_pages > 0) & (lengths > 0),
                     jnp.argmax(mine, 1), spare).astype(jnp.int32)


@functools.partial(jax.jit, static_argnums=(8, 9))
def _latent_decode_pallas(q, c_pages, r_pages, block_tables, lengths,
                          group_slots, group_pages, shared_pages,
                          interpret, pages_per_block):
    """The two ``pallas_call``s of ``latent_paged_decode``, Part A a
    group and Part B a slot, both under the kernel's one name."""
    s_slots, h, _ = q.shape
    ps, dl = c_pages.shape[1:]
    dr = r_pages.shape[1]
    n_groups, g = group_slots.shape
    # the body copies a page out of each pool as it lies, which the chip's
    # compiler does only where a page is whole tiles
    if not interpret and (dl % 128 or ps % 128
                          or dr % (32 // r_pages.dtype.itemsize)):
        raise ValueError(
            f"latent_paged_decode copies whole pages out of the pools: "
            f"pages of {ps} tokens, rows of {dl} + {dr} are not whole tiles")
    pb = max(1, min(int(pages_per_block), block_tables.shape[1]))
    rows = h + -h % _HEAD_ROWS
    q = q.astype(c_pages.dtype)
    if rows != h:
        q = jnp.pad(q, ((0, 0), (0, rows - h), (0, 0)))
    qc, qr = q[..., :dl], q[..., dl:]
    block_tables = block_tables.astype(jnp.int32)
    group_slots = group_slots.astype(jnp.int32).reshape(-1)
    # Part A folds whole blocks: what is left of a group's pages is walked
    # a slot (nothing, where the groups are :func:`decode_groups`')
    group_pages = group_pages.astype(jnp.int32) // pb * pb
    shared_pages = shared_pages.astype(jnp.int32) // pb * pb
    params = pltpu.CompilerParams(
        dimension_semantics=("arbitrary",)) if not interpret else None

    def buffers(n_rows):
        return [pltpu.VMEM((2, pb * ps, dl), c_pages.dtype),
                pltpu.VMEM((2, dr, pb * ps), r_pages.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.SMEM((1,), jnp.int32)], [
                pltpu.VMEM((n_rows, 128), jnp.float32),
                pltpu.VMEM((n_rows, 128), jnp.float32),
                pltpu.VMEM((n_rows, dl), jnp.float32)]

    pools = [pl.BlockSpec(memory_space=pl.ANY),
             pl.BlockSpec(memory_space=pl.ANY)]

    # Part A. A member's queries come from its slot's block (a member a
    # group lacks reads slot 0's); a group's states go to the group's
    # block, those of every group without pages to one spare block
    def member(j, width):
        return pl.BlockSpec(
            (1, rows, width),
            lambda grp, _bt, gs, _gp: (jnp.maximum(gs[grp * g + j], 0), 0, 0))

    walk, state = buffers(g * rows)
    width = dl + _STATE_LANES
    states = pl.pallas_call(
        functools.partial(_latent_decode_shared_kernel, page_size=ps,
                          pages_per_block=pb, members=g),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(n_groups,),
            in_specs=[member(j, dl) for j in range(g)]
            + [member(j, dr) for j in range(g)] + pools,
            out_specs=pl.BlockSpec(
                (1, g * rows, width),
                lambda grp, _bt, _gs, gp: (
                    jnp.where(gp[grp] > 0, grp, n_groups), 0, 0)),
            scratch_shapes=walk + [
                pltpu.VMEM((g * rows, dl), c_pages.dtype),
                pltpu.VMEM((g * rows, dr), c_pages.dtype)] + state),
        out_shape=jax.ShapeDtypeStruct((n_groups + 1, g * rows, width),
                                       jnp.float32),
        compiler_params=params,
        interpret=interpret,
        name="latent_paged_decode",
    )(block_tables, group_slots, group_pages, *[qc] * g, *[qr] * g,
      c_pages, r_pages)

    # Part B, from the state rows Part A left
    spare = n_groups * g
    lengths = lengths.astype(jnp.int32)
    state_rows = _group_state_rows(group_slots, shared_pages, lengths, spare)

    def slot_block(width):
        return pl.BlockSpec((1, rows, width), lambda s, *_prefetch: (s, 0, 0))

    walk, state = buffers(rows)
    out = pl.pallas_call(
        functools.partial(_latent_decode_own_kernel, page_size=ps,
                          pages_per_block=pb, spare=spare),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(s_slots,),
            in_specs=[slot_block(dl), slot_block(dr),
                      pl.BlockSpec(
                          (1, rows, width),
                          lambda s, _bt, _len, _sp, row: (row[s], 0, 0))]
            + pools,
            out_specs=slot_block(dl),
            scratch_shapes=walk + state),
        out_shape=jax.ShapeDtypeStruct((s_slots, rows, dl), q.dtype),
        compiler_params=params,
        interpret=interpret,
        name="latent_paged_decode",
    )(block_tables, lengths, shared_pages, state_rows, qc, qr,
      states.reshape(spare + g, rows, width), c_pages, r_pages)
    return out[:, :h]


def _latent_prefill_kernel(bt_ref, start_ref, nv_ref, qc_ref, qr_ref, *rest,
                           page_size, pages_per_block, n_heads):
    """The latent chunked-prefill body: grid ``(S, query tiles, page
    blocks)``. A tile's rows are ``n_heads`` rows a query, queries in
    order (row ``r`` is query ``r // n_heads`` of the tile); ``rest``:
    ``pb`` pages of ``c``, ``pb`` of the rotary keys, the output tile,
    the ``m / l / acc`` state of the tile. Row ``r`` attends causally to
    ``tok <= chunk_starts[s] + query``, rows of queries past ``n_valid``
    give zeros, and a block wholly past the tile's last live query does
    nothing (its page operands stay where they were)."""
    ps, pb = page_size, pages_per_block
    c_refs, r_refs = rest[:pb], rest[pb:2 * pb]
    o_ref, m_scr, l_scr, acc_scr = rest[2 * pb:]
    sl, qi, pj = (pl.program_id(a) for a in range(3))
    rows = qc_ref.shape[1]
    per_tile = rows // n_heads

    @pl.when(pj == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    start, nv = start_ref[sl], nv_ref[sl]
    first = qi * per_tile                       # the tile's first query
    # tokens the tile's last live query can see
    extent = start + jnp.minimum(first + per_tile, nv)
    has_work = (first < nv) & (pj * pb * ps < extent)

    @pl.when(has_work)
    def _body():
        shape = (rows, ps)
        query = first + jax.lax.broadcasted_iota(
            jnp.int32, shape, 0) // n_heads
        for t in range(pb):
            tok = (pj * pb + t) * ps + jax.lax.broadcasted_iota(
                jnp.int32, shape, 1)
            ok = (tok <= start + query) & (query < nv)
            c = c_refs[t][0]                                 # (ps, Dl)
            s = _pool_dot(qc_ref[0], c, 1) \
                + _pool_dot(qr_ref[0], r_refs[t][0], 0)      # (rows, ps)
            s = jnp.where(ok, s, NEG_INF)
            m = m_scr[...]
            m_next = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m - m_next)
            p = jnp.exp(s - m_next[:, :1])
            l_scr[...] = l_scr[...] * alpha \
                + jnp.sum(p, axis=1, keepdims=True)
            m_scr[...] = m_next
            acc_scr[...] = acc_scr[...] * alpha[:, :1] + _pool_dot(p, c, 0)

    @pl.when(pj == pl.num_programs(2) - 1)
    def _finish():
        denom = l_scr[...][:, :1]
        denom = jnp.where(denom == 0.0, 1.0, denom)
        alive = m_scr[...][:, :1] > NEG_INF / 2
        o_ref[0] = jnp.where(alive, acc_scr[...] / denom,
                             0.0).astype(o_ref.dtype)


def _latent_queries_a_tile(c, h, q_rows):
    """Queries a tile of at most ``q_rows`` rows holds: the most that
    divide the chunk."""
    return max(n for n in range(1, c + 1)
               if c % n == 0 and (n * h <= q_rows or n == 1))


@functools.partial(jax.jit, static_argnums=(6, 7, 8))
def _latent_prefill_pallas(q, c_pages, r_pages, block_tables, chunk_starts,
                           n_valid, interpret, pages_per_block, q_rows):
    """The ``pallas_call`` of ``latent_paged_prefill``: ``q`` (S, C, H, Dl
    + Dr) goes in as ``(S, C*H, .)``, a query's heads side by side, which
    is the order it comes in: no transpose either way."""
    s_slots, c, h, _ = q.shape
    mp = block_tables.shape[1]
    ps, dl = c_pages.shape[1:]
    dr = r_pages.shape[1]
    pb = max(1, min(int(pages_per_block), mp))
    per_tile = _latent_queries_a_tile(c, h, q_rows)
    rows = per_tile * h
    q = q.astype(c_pages.dtype).reshape(s_slots, c * h, dl + dr)

    def tile_block(width):
        return pl.BlockSpec((1, rows, width),
                            lambda s, i, j, *_prefetch: (s, i, 0))

    def page_spec(t, block):
        def page(s, i, j, bt, starts, nv):
            # the last page the tile's last live query sees: later blocks
            # repeat it and move nothing
            seen = starts[s] + jnp.clip(
                jnp.minimum((i + 1) * per_tile, nv[s]), 1, None)
            last = jnp.minimum((seen - 1) // ps, mp - 1)
            return bt[s, jnp.minimum(j * pb + t, last)]
        return pl.BlockSpec(block, lambda *a: (page(*a), 0, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(s_slots, c // per_tile, pl.cdiv(mp, pb)),
        in_specs=[tile_block(dl), tile_block(dr),
                  *(page_spec(t, (1, ps, dl)) for t in range(pb)),
                  *(page_spec(t, (1, dr, ps)) for t in range(pb))],
        out_specs=tile_block(dl),
        scratch_shapes=[
            pltpu.VMEM((rows, 128), jnp.float32),
            pltpu.VMEM((rows, 128), jnp.float32),
            pltpu.VMEM((rows, dl), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_latent_prefill_kernel, page_size=ps,
                          pages_per_block=pb, n_heads=h),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((s_slots, c * h, dl), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_WIDE_VMEM_LIMIT) if not interpret else None,
        interpret=interpret,
        name="latent_paged_prefill",
    )(block_tables.astype(jnp.int32), chunk_starts.astype(jnp.int32),
      n_valid.astype(jnp.int32), q[..., :dl], q[..., dl:],
      *([c_pages] * pb), *([r_pages] * pb))
    return out.reshape(s_slots, c, h, dl)


def ragged_paged_decode_attention(q, k_pages, v_pages, block_tables,
                                  lengths, *, scale: Optional[float] = None,
                                  impl: str = "auto",
                                  window: Optional[int] = None,
                                  sinks=None):
    """One decode step of attention for every slot at once.

    ``q`` (S, H, Dh); ``k_pages``/``v_pages`` (P, page_size, H*Dh),
    a token's heads folded head-major into the last axis;
    ``block_tables`` (S, max_pages) int32; ``lengths`` (S,) int32 valid
    tokens per slot. Returns (S, H, Dh). ``impl``: "auto" (pallas on
    TPU, lax elsewhere), "lax", "pallas", "pallas_interpret". ``window``
    (static): the query attends to the slot's last ``window`` tokens
    only, itself counted; None: to all of them.

    The pools may hold ``KV <= H`` heads (query head ``i`` reads KV head
    ``i // (H / KV)``, any whole group), and values narrower than keys:
    ``k_pages`` (P, page_size, KV*Dh) with ``Dh`` the queries' width,
    ``v_pages`` (P, page_size, KV*Dv); the result is then (S, H, Dv).
    ``sinks`` (H,): a learned logit a head that joins the softmax's
    denominator and nothing else, ``p = exp(a) / (exp(sink) + sum
    exp(a))``; None: no such term.
    """
    from paddle_tpu import kernels
    kw = {} if window is None else {"window": int(window)}
    if sinks is not None:
        kw["sinks"] = sinks
    return kernels.dispatch("ragged_paged_decode", q, k_pages, v_pages,
                            block_tables, lengths, impl=impl, scale=scale,
                            **kw)


def ragged_paged_prefill_attention(q, k_pages, v_pages, block_tables,
                                   chunk_starts, n_valid, *,
                                   scale: Optional[float] = None,
                                   impl: str = "auto",
                                   window: Optional[int] = None,
                                   sinks=None):
    """One batched chunked-prefill step of attention for every slot.

    ``q`` (S, C, H, Dh) — a chunk of C query tokens per slot, the first
    ``n_valid[s]`` real (rest padding), at absolute positions
    ``chunk_starts[s] + c``; keys/values are read from each slot's pages
    via ``block_tables`` (S, max_pages). Each live query attends
    causally to all cache positions ``<= chunk_starts[s] + c`` (earlier
    chunks, shared prefix pages, and this chunk's causal prefix — whose
    K/V the caller has already written). Padding lanes and inactive
    slots (``n_valid == 0``) emit exact zeros. Returns (S, C, H, Dh).
    ``impl``: "auto" (pallas on TPU, lax elsewhere), "lax", "pallas",
    "pallas_interpret". ``window`` (static): each query attends to the
    last ``window`` cache positions up to its own only; None: to all.
    Grouped-query pools, values narrower than keys (the result is then
    (S, C, H, Dv)) and ``sinks`` (H,) as
    :func:`ragged_paged_decode_attention` takes them.
    """
    from paddle_tpu import kernels
    kw = {} if window is None else {"window": int(window)}
    if sinks is not None:
        kw["sinks"] = sinks
    return kernels.dispatch("ragged_paged_prefill", q, k_pages, v_pages,
                            block_tables, chunk_starts, n_valid,
                            impl=impl, scale=scale, **kw)


def ragged_paged_decode_int8_attention(q, k_pages, v_pages, k_scales,
                                       v_scales, block_tables, lengths, *,
                                       scale: Optional[float] = None,
                                       impl: str = "auto"):
    """Dequant-attend decode over an INT8 page pool (ISSUE 13).

    Same contract as :func:`ragged_paged_decode_attention` with
    ``k_pages``/``v_pages`` int8 and per-token-row fp32
    ``k_scales``/``v_scales`` (P, page_size) — dequantization
    (``q_int * scale``) is fused into the QK and PV products inside the
    online-softmax page fold, so HBM moves int8 pages, never a
    materialized fp copy. Returns (S, H, Dh) in ``q.dtype``.
    """
    from paddle_tpu import kernels
    return kernels.dispatch("ragged_paged_decode_int8", q, k_pages,
                            v_pages, k_scales, v_scales, block_tables,
                            lengths, impl=impl, scale=scale)


def ragged_paged_prefill_int8_attention(q, k_pages, v_pages, k_scales,
                                        v_scales, block_tables,
                                        chunk_starts, n_valid, *,
                                        scale: Optional[float] = None,
                                        impl: str = "auto"):
    """Dequant-attend batched chunked prefill over an INT8 page pool —
    the int8 twin of :func:`ragged_paged_prefill_attention` (and the
    fixed-shape verify step speculative decoding rides on). Returns
    (S, C, H, Dh) in ``q.dtype``.
    """
    from paddle_tpu import kernels
    return kernels.dispatch("ragged_paged_prefill_int8", q, k_pages,
                            v_pages, k_scales, v_scales, block_tables,
                            chunk_starts, n_valid, impl=impl, scale=scale)


def decode_groups(block_tables, lengths, slots, page_size,
                  members=DECODE_GROUP):
    """The groups a decode kernel that reads shared pages once folds
    (``latent_paged_decode``, ``sparse_paged_decode``), made on the host
    (NumPy) from what the tables say and nothing else: among ``slots`` (the
    decoding ones) those whose tables open with the same page ids, a set
    cut into groups of at most ``members``, a group's shared pages the
    longest run that is common to its members and whole in each
    (``lengths // page_size``: shared pages are read-only, a slot grows in
    pages of its own, so a group holds while its members' tables do), in
    whole multiples of ``GROUP_SHARED_PAGES``.
    Fixed shapes whatever is shared: ``group_slots`` (S // 2, members)
    int32, -1 where a group has no such member; ``group_pages`` (S // 2,)
    int32, 0 for a group that is none; ``shared_pages`` (S,) int32, the
    leading pages of a slot that its group's walk covers, 0 for a slot
    walked alone."""
    import numpy as np
    n_slots = np.shape(block_tables)[0]
    group_slots = np.full((max(n_slots // 2, 1), members), -1, np.int32)
    group_pages = np.zeros((group_slots.shape[0],), np.int32)
    shared_pages = np.zeros((n_slots,), np.int32)
    slots = np.asarray(slots, np.int64)
    if len(slots) < 2:
        return group_slots, group_pages, shared_pages
    bt = np.asarray(block_tables)
    slots = slots[np.argsort(bt[slots, 0], kind="stable")]
    rows = bt[slots]
    at = np.arange(len(slots))
    # a set is a run of one first page, a group `members` of a set in a
    # row; ``head``: where a slot's group begins
    begins = np.r_[True, rows[1:, 0] != rows[:-1, 0]]
    seat = (at - np.maximum.accumulate(np.where(begins, at, 0))) % members
    head = at - seat
    starts = np.flatnonzero(seat == 0)
    sizes = np.diff(np.r_[starts, len(slots)])
    common = (rows == rows[head]).cumprod(1).sum(1)
    pages = np.minimum.reduceat(
        np.minimum(common, np.asarray(lengths)[slots] // page_size), starts)
    pages -= pages % GROUP_SHARED_PAGES
    # the groups kept, in order, and each slot's place among them
    kept = (sizes >= 2) & (pages > 0)
    place = np.repeat(np.where(kept, np.cumsum(kept) - 1, -1), sizes)
    held = place >= 0
    group_slots[place[held], seat[held]] = slots[held]
    group_pages[:kept.sum()] = pages[kept]
    shared_pages[slots[held]] = group_pages[place[held]]
    return group_slots, group_pages, shared_pages


def latent_paged_decode_attention(q, c_pages, r_pages, block_tables,
                                  lengths, groups=None, *,
                                  impl: str = "auto"):
    """One decode step of attention over latent rows, every slot at once.

    ``q`` (S, H, Dl + Dr): each head's absorbed query, already scaled;
    ``c_pages`` (P, page_size, Dl) the cached latents, which are also the
    values; ``r_pages`` (P, Dr, page_size) the shared rotary keys, tokens
    along the lanes; ``block_tables`` (S, max_pages) int32; ``lengths``
    (S,) int32 live tokens a slot. Every head scores the same row
    ``[c | k_rope]`` of a token and sums its ``c``. ``groups``:
    ``(group_slots, group_pages, shared_pages)`` as
    :func:`decode_groups` makes them, the slots whose tables open
    with the same pages: the kernel reads those pages once a group, the
    members' queries stacked against them; None: every slot walked alone.
    The result is attention a slot either way. Returns (S, H, Dl).
    """
    from paddle_tpu import kernels
    if groups is None:      # the shapes of a table that groups no slot
        groups = decode_groups(block_tables, lengths, (), 1)
    return kernels.dispatch("latent_paged_decode", q, c_pages, r_pages,
                            block_tables, lengths, *groups, impl=impl)


def latent_paged_prefill_attention(q, c_pages, r_pages, block_tables,
                                   chunk_starts, n_valid, *,
                                   impl: str = "auto"):
    """One batched chunked-prefill step of attention over latent rows.

    ``q`` (S, C, H, Dl + Dr), the first ``n_valid[s]`` queries of a lane
    real, at positions ``chunk_starts[s] + c``, each attending causally
    to every row cached up to its own (the caller has written the
    chunk's). Pools as :func:`latent_paged_decode_attention`. Padding
    rows and lanes give zeros. Returns (S, C, H, Dl).
    """
    from paddle_tpu import kernels
    return kernels.dispatch("latent_paged_prefill", q, c_pages, r_pages,
                            block_tables, chunk_starts, n_valid, impl=impl)


# ---------------------------------------------------------------------------
# kernel-registry entries (paddle_tpu.kernels)
# ---------------------------------------------------------------------------

def _decode_kernel_pallas(q, k_pages, v_pages, block_tables, lengths, *,
                          block_sizes, interpret, scale=None, window=None,
                          sinks=None):
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    pb = block_sizes.get("pages_per_block", 1)
    ps, hd = k_pages.shape[1:]
    # the body that walks the pages copies a page out of the pool as it
    # lies: the chip's compiler takes that slice only where a page is
    # whole tiles (rows of whole 128-lane tiles, whole sublane tiles of
    # them). A pool that is not (a tp = 4 shard of GPT-2's heads is 192
    # lanes) goes through the pipelined body, whose blocks span the row
    if hd % 128 or v_pages.shape[-1] % 128 \
            or ps % (32 // k_pages.dtype.itemsize):
        return _paged_decode_pallas(q, k_pages, v_pages, block_tables,
                                    lengths, scale, interpret,
                                    pages_per_block=pb, window=window,
                                    sinks=sinks)
    return _paged_decode_walk_pallas(
        q * jnp.asarray(scale, q.dtype), k_pages, v_pages, block_tables,
        lengths, interpret, pb, window=window, sinks=sinks)


def _decode_kernel_lax(q, k_pages, v_pages, block_tables, lengths, *,
                       scale=None, window=None, sinks=None):
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    return _paged_decode_lax(q, k_pages, v_pages, block_tables, lengths,
                             scale, window, sinks)


def _attend_np(q, k, v, scale, sinks):
    """NumPy attention of ``q`` (H, Dk) over the tokens ``k`` (T, KV, Dk)
    and ``v`` (T, KV, Dv), query head ``i`` reading KV head ``i // (H /
    KV)``; ``sinks`` (H,) or None: one more term a head in the
    denominator. -> (H, Dv)."""
    import numpy as np
    group = q.shape[0] // k.shape[1]
    k, v = (np.repeat(a, group, axis=1) for a in (k, v))
    s = np.einsum("hd,thd->ht", q, k) * scale
    m = s.max(-1, keepdims=True)
    if sinks is not None:
        m = np.maximum(m, sinks[:, None])
    p = np.exp(s - m)
    denom = p.sum(-1, keepdims=True)
    if sinks is not None:
        denom = denom + np.exp(sinks[:, None] - m)
    return np.einsum("ht,thd->hd", p / denom, v)


def _pools_np(q, k_pages, v_pages):
    """The pools as NumPy ``(P, ps, KV, Dk)`` and ``(P, ps, KV, Dv)``."""
    import numpy as np
    dh = q.shape[-1]
    kv = k_pages.shape[-1] // dh
    kp = np.asarray(k_pages, np.float32)
    vp = np.asarray(v_pages, np.float32)
    return (kp.reshape(kp.shape[:2] + (kv, dh)),
            vp.reshape(vp.shape[:2] + (kv, -1)))


def _decode_kernel_reference(q, k_pages, v_pages, block_tables, lengths,
                             *, scale=None, window=None, sinks=None):
    """NumPy per-slot dense attention — independent of both impls."""
    import numpy as np
    s_slots, h, dh = q.shape
    if scale is None:
        scale = 1.0 / math.sqrt(dh)
    mp, ps = block_tables.shape[1], k_pages.shape[1]
    qn = np.asarray(q, np.float32)
    kp, vp = _pools_np(q, k_pages, v_pages)
    sinks = None if sinks is None else np.asarray(sinks, np.float32)
    bt = np.asarray(block_tables)
    ln = np.asarray(lengths)
    outs = np.zeros((s_slots, h, vp.shape[-1]), np.float32)
    for sl in range(s_slots):
        n = int(ln[sl])
        if n == 0:
            continue
        lo = 0 if window is None else max(n - window, 0)
        k = kp[bt[sl]].reshape((mp * ps,) + kp.shape[2:])[lo:n]
        v = vp[bt[sl]].reshape((mp * ps,) + vp.shape[2:])[lo:n]
        outs[sl] = _attend_np(qn[sl], k, v, scale, sinks)
    return jnp.asarray(outs).astype(q.dtype)


def _make_paged_sample(seed, *, chunked):
    """Seeds 0-2: three shapes; seeds 3-5: the same shapes under a window
    that is no multiple of the page (``{"window": ...}``)."""
    import numpy as np
    kwargs = {"window": (11, 21, 40)[seed % 3]} if seed % 6 >= 3 else {}
    s_slots, h, dh, ps, mp = (
        (4, 2, 16, 8, 3), (6, 4, 32, 16, 4), (8, 4, 64, 16, 6))[seed % 3]
    c = ps  # prefill chunk = one page of queries
    num_pages = s_slots * mp + 1
    rng = np.random.default_rng(seed)
    # drawn per (page, token, head, dim) and folded: the same numbers
    # the 4-D pool held, in the layout the pool stores
    k_pages = jnp.asarray(
        rng.standard_normal((num_pages, ps, h, dh)), jnp.float32
    ).reshape(num_pages, ps, h * dh)
    v_pages = jnp.asarray(
        rng.standard_normal((num_pages, ps, h, dh)), jnp.float32
    ).reshape(num_pages, ps, h * dh)
    perm = rng.permutation(num_pages - 1)[:s_slots * mp] + 1
    block_tables = jnp.asarray(perm.reshape(s_slots, mp), jnp.int32)
    if not chunked:
        q = jnp.asarray(rng.standard_normal((s_slots, h, dh)),
                        jnp.float32)
        lengths = jnp.asarray(
            rng.integers(0, mp * ps + 1, s_slots), jnp.int32)
        return (q, k_pages, v_pages, block_tables, lengths), kwargs
    q = jnp.asarray(rng.standard_normal((s_slots, c, h, dh)), jnp.float32)
    starts = jnp.asarray(
        rng.integers(0, (mp - 1) * ps, s_slots), jnp.int32)
    n_valid = jnp.asarray(rng.integers(0, c + 1, s_slots), jnp.int32)
    return (q, k_pages, v_pages, block_tables, starts, n_valid), kwargs


def _paged_sig(q, k_pages, bt):
    """Tune-key dims of one paged call; the head count comes from ``q``
    (the folded pool does not carry it)."""
    sig = [("s", q.shape[0]), ("h", q.shape[-2]),
           ("d", q.shape[-1]), ("ps", k_pages.shape[1]),
           ("mp", bt.shape[1])]
    if q.ndim == 4:                      # prefill: chunk width matters
        sig.insert(1, ("c", q.shape[1]))
    kv = k_pages.shape[-1] // q.shape[-1]
    if kv != q.shape[-2]:                # grouped-query heads
        sig.append(("kv", kv))
    return tuple(sig)


def _paged_tune_signature(args, kwargs):
    sig = _paged_sig(args[0], args[1], args[3])
    dv = _value_dim(*args[:3])
    if dv != args[0].shape[-1]:          # values narrower than keys
        sig += (("dv", dv),)
    if kwargs.get("sinks") is not None:
        sig += (("sink", 1),)
    return sig


def _paged_vmem_estimate(args, kwargs, blocks, walk=False):
    """VMEM working set of one grid step, as the TPU lays it out: the
    streamed blocks are whole pages (every head, folded into the lane
    axis: ``(ps, H*Dh)`` tiles with no per-head padding), tiles pad the
    last two dims to (32/itemsize, 128), and the pipeline double-buffers
    every in/out block. One estimate for the fp and int8 kernels — the
    page dtype and the scale rows are read off the arguments — and for
    the three bodies: chunked prefill (4-D ``q``) holds per-head state
    and the temporaries of one head fold, pipelined decode the all-heads
    state over the page's lanes and the temporaries of one page fold,
    and the dense decode body that walks the pages itself (``walk``)
    its own two buffers of ``pb`` pages each for K and V (what the
    pipeline's double-buffered page blocks came to) with the
    temporaries of a fold ``pb`` pages wide."""
    q, k_pages = args[0], args[1]
    ps, hd = k_pages.shape[1:]
    v_pages = args[2] if len(args) > 2 else k_pages
    hdv = v_pages.shape[-1]
    h, dh = q.shape[-2:]
    dv = _value_dim(q, k_pages, v_pages)
    pb = blocks.get("pages_per_block", 1)

    def tiled(lead, sub, lane, itemsize):
        tile = 32 // itemsize
        return (lead * -(-sub // tile) * tile * -(-lane // 128) * 128
                * itemsize)

    streamed = pb * (tiled(1, ps, hd, k_pages.dtype.itemsize)
                     + tiled(1, ps, hdv, k_pages.dtype.itemsize))
    if k_pages.dtype.itemsize == 1:              # int8: + scale groups
        streamed += 2 * pb * tiled(1, _SCALE_ROWS, ps, 4)
    if q.ndim == 4:                              # chunked prefill
        rows = q.shape[1]
        streamed += (tiled(h, rows, dh, q.dtype.itemsize)
                     + tiled(h, rows, dv, q.dtype.itemsize))
        scratch = 2 * tiled(h, rows, 128, 4) + tiled(h, rows, dv, 4)
        # one fold's temporaries (a head's, or a whole group of query
        # heads' where the chunk is wide; a selecting call, which folds
        # by head, is priced as the wider): fp32 scores, weights and
        # product with V; over a pool that is not float32 the weights'
        # three bf16 terms and a product a term, the head's K and V
        # lanes cast to bf16 (int8 pools), and the terms of fp32 queries
        # with a score product each
        form = _prefill_fold_form(h, rows, hd // dh, dh, dv, k_pages.dtype,
                                  False)
        n = rows * (h // (hd // dh) if form.by_group else 1)
        fold = 2 * tiled(1, n, ps, 4) + tiled(1, n, dv, 4)
        if form.stored:
            fold += 3 * tiled(1, n, ps, 2) + 2 * tiled(1, n, dv, 4)
            if k_pages.dtype.itemsize == 1:
                fold += tiled(1, ps, dh, 2) + tiled(1, ps, dv, 2)
            if q.dtype.itemsize == 4:
                fold += 3 * tiled(1, n, dh, 2) + 3 * tiled(1, n, ps, 4)
    else:
        rows = h + -h % _HEAD_ROWS
        streamed += (tiled(1, rows, hd, q.dtype.itemsize)
                     + tiled(1, rows, dv, q.dtype.itemsize))
        scratch = 2 * tiled(1, rows, 128, 4) + tiled(1, rows, hdv, 4)
        # one softmax update (a page; the walk's is a block of pages):
        # fp32 scores and weights, the weights' three bf16 terms and
        # their products with V; a page cast to bf16 (int8 pools) and
        # the terms of fp32 queries where those apply
        width = pb * ps if walk else ps
        fold = (2 * tiled(1, rows, width, 4) + tiled(1, 3 * rows, width, 2)
                + tiled(1, 3 * rows, hdv, 4))
        if k_pages.dtype.itemsize == 1:
            fold += 2 * tiled(1, ps, hd, 2)
        if q.dtype.itemsize == 4:
            fold += tiled(1, 3 * rows, hd, 2) + tiled(1, 3 * rows, width, 4)
    return 2 * streamed + scratch + fold


def _decode_donation_probe():
    (q, k_pages, v_pages, block_tables, lengths), _ = \
        _make_paged_sample(0, chunked=False)

    def step(kp, vp, q, bt, lens):
        # the engine's real pattern: write this step's token K/V into
        # the pages, attend THROUGH THE PALLAS BODY (interpret lowering
        # — the structure XLA aliases, incl. the pages-passed-
        # pages_per_block-times operand shape), hand the pages back
        kp = kp.at[1, 0].set(q[0].reshape(-1))
        vp = vp.at[1, 0].set(q[0].reshape(-1))
        out = _decode_kernel_pallas(
            q, kp, vp, bt, lens,
            block_sizes={"pages_per_block": 4}, interpret=True)
        return out, kp, vp

    args = tuple(jax.ShapeDtypeStruct(a.shape, a.dtype)
                 for a in (k_pages, v_pages, q, block_tables, lengths))
    return step, args, (0, 1)


def _prefill_kernel_pallas(q, k_pages, v_pages, block_tables,
                           chunk_starts, n_valid, *, block_sizes,
                           interpret, scale=None, window=None, sinks=None):
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    return _paged_prefill_pallas(
        q, k_pages, v_pages, block_tables, chunk_starts, n_valid, scale,
        interpret, pages_per_block=block_sizes.get("pages_per_block", 1),
        window=window, sinks=sinks)


def _prefill_kernel_lax(q, k_pages, v_pages, block_tables, chunk_starts,
                        n_valid, *, scale=None, window=None, sinks=None):
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    return _paged_prefill_lax(q, k_pages, v_pages, block_tables,
                              chunk_starts, n_valid, scale, window=window,
                              sinks=sinks)


def _prefill_kernel_reference(q, k_pages, v_pages, block_tables,
                              chunk_starts, n_valid, *, scale=None,
                              window=None, sinks=None):
    """NumPy per-slot, per-row causal attention over the slot's pages."""
    import numpy as np
    s_slots, c, h, dh = q.shape
    if scale is None:
        scale = 1.0 / math.sqrt(dh)
    mp, ps = block_tables.shape[1], k_pages.shape[1]
    qn = np.asarray(q, np.float32)
    kp, vp = _pools_np(q[0], k_pages, v_pages)
    sinks = None if sinks is None else np.asarray(sinks, np.float32)
    bt = np.asarray(block_tables)
    st = np.asarray(chunk_starts)
    nv = np.asarray(n_valid)
    outs = np.zeros((s_slots, c, h, vp.shape[-1]), np.float32)
    for sl in range(s_slots):
        k = kp[bt[sl]].reshape((mp * ps,) + kp.shape[2:])
        v = vp[bt[sl]].reshape((mp * ps,) + vp.shape[2:])
        for r in range(int(nv[sl])):
            limit = int(st[sl]) + r + 1          # causal horizon
            lo = 0 if window is None else max(limit - window, 0)
            outs[sl, r] = _attend_np(qn[sl, r], k[lo:limit], v[lo:limit],
                                     scale, sinks)
    return jnp.asarray(outs).astype(q.dtype)


def _prefill_donation_probe():
    (q, k_pages, v_pages, block_tables, starts, n_valid), _ = \
        _make_paged_sample(0, chunked=True)

    def step(kp, vp, q, bt, st, nv):
        kp = kp.at[1, 0].set(q[0, 0].reshape(-1))
        vp = vp.at[1, 0].set(q[0, 0].reshape(-1))
        out = _prefill_kernel_pallas(
            q, kp, vp, bt, st, nv,
            block_sizes={"pages_per_block": 4}, interpret=True)
        return out, kp, vp

    args = tuple(jax.ShapeDtypeStruct(a.shape, a.dtype)
                 for a in (k_pages, v_pages, q, block_tables, starts,
                           n_valid))
    return step, args, (0, 1)


# -- int8 dequant-attend registry plumbing ----------------------------------

def _decode_int8_kernel_pallas(q, k_pages, v_pages, k_scales, v_scales,
                               block_tables, lengths, *, block_sizes,
                               interpret, scale=None):
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    return _paged_decode_int8_pallas(
        q, k_pages, v_pages, k_scales, v_scales, block_tables, lengths,
        scale, interpret,
        pages_per_block=block_sizes.get("pages_per_block", 1))


def _decode_int8_kernel_lax(q, k_pages, v_pages, k_scales, v_scales,
                            block_tables, lengths, *, scale=None):
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    return _paged_decode_int8_lax(q, k_pages, v_pages, k_scales, v_scales,
                                  block_tables, lengths, scale)


def _dequant_pages_np(k_pages, v_pages, k_scales, v_scales):
    """Host-side dequant for the dense references — independent of the
    fused in-kernel path (the parity battery's whole point)."""
    import numpy as np
    kf = np.asarray(k_pages, np.float32) \
        * np.asarray(k_scales, np.float32)[:, :, None]
    vf = np.asarray(v_pages, np.float32) \
        * np.asarray(v_scales, np.float32)[:, :, None]
    return jnp.asarray(kf), jnp.asarray(vf)


def _decode_int8_kernel_reference(q, k_pages, v_pages, k_scales, v_scales,
                                  block_tables, lengths, *, scale=None):
    kf, vf = _dequant_pages_np(k_pages, v_pages, k_scales, v_scales)
    return _decode_kernel_reference(q, kf, vf, block_tables, lengths,
                                    scale=scale)


def _prefill_int8_kernel_pallas(q, k_pages, v_pages, k_scales, v_scales,
                                block_tables, chunk_starts, n_valid, *,
                                block_sizes, interpret, scale=None):
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    return _paged_prefill_int8_pallas(
        q, k_pages, v_pages, k_scales, v_scales, block_tables,
        chunk_starts, n_valid, scale, interpret,
        pages_per_block=block_sizes.get("pages_per_block", 1))


def _prefill_int8_kernel_lax(q, k_pages, v_pages, k_scales, v_scales,
                             block_tables, chunk_starts, n_valid, *,
                             scale=None):
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    return _paged_prefill_int8_lax(q, k_pages, v_pages, k_scales,
                                   v_scales, block_tables, chunk_starts,
                                   n_valid, scale)


def _prefill_int8_kernel_reference(q, k_pages, v_pages, k_scales,
                                   v_scales, block_tables, chunk_starts,
                                   n_valid, *, scale=None):
    kf, vf = _dequant_pages_np(k_pages, v_pages, k_scales, v_scales)
    return _prefill_kernel_reference(q, kf, vf, block_tables,
                                     chunk_starts, n_valid, scale=scale)


def _make_paged_int8_sample(seed, *, chunked):
    """The fp sample's pages quantized per token row — THROUGH
    :func:`paged_cache.quantize_kv` itself, so the registry's parity
    and tuning samples can never drift from the convention the engine
    actually stores."""
    from paddle_tpu.serving.paged_cache import quantize_kv
    args, kwargs = _make_paged_sample(seed, chunked=chunked)
    q, k_pages, v_pages = args[0], args[1], args[2]
    rest = args[3:]
    kq, ks = quantize_kv(k_pages, (2,))            # scales (P, ps)
    vq, vs = quantize_kv(v_pages, (2,))
    return (q, kq, vq, ks, vs) + rest, kwargs


def _paged_int8_tune_signature(args, kwargs):
    return _paged_sig(args[0], args[1], args[5])


def _decode_int8_donation_probe():
    (q, k_pages, v_pages, k_scales, v_scales, block_tables, lengths), _ \
        = _make_paged_int8_sample(0, chunked=False)

    def step(kp, vp, ks, vs, q, bt, lens):
        # the engine's real pattern: quantize this step's token K/V into
        # the int8 pages + scale rows, attend THROUGH THE PALLAS BODY,
        # hand all four buffers back (pages AND scales must alias)
        from paddle_tpu.serving.paged_cache import quantize_kv
        kq, ksc = quantize_kv(q[:1].reshape(1, -1), (1,))
        kp = kp.at[1, 0].set(kq[0])
        vp = vp.at[1, 0].set(kq[0])
        ks = ks.at[1, 0].set(ksc[0])
        vs = vs.at[1, 0].set(ksc[0])
        out = _decode_int8_kernel_pallas(
            q, kp, vp, ks, vs, bt, lens,
            block_sizes={"pages_per_block": 4}, interpret=True)
        return out, kp, vp, ks, vs

    args = tuple(jax.ShapeDtypeStruct(a.shape, a.dtype)
                 for a in (k_pages, v_pages, k_scales, v_scales, q,
                           block_tables, lengths))
    return step, args, (0, 1, 2, 3)


def _prefill_int8_donation_probe():
    (q, k_pages, v_pages, k_scales, v_scales, block_tables, starts,
     n_valid), _ = _make_paged_int8_sample(0, chunked=True)

    def step(kp, vp, ks, vs, q, bt, st, nv):
        from paddle_tpu.serving.paged_cache import quantize_kv
        kq, ksc = quantize_kv(q[:1, 0].reshape(1, -1), (1,))
        kp = kp.at[1, 0].set(kq[0])
        vp = vp.at[1, 0].set(kq[0])
        ks = ks.at[1, 0].set(ksc[0])
        vs = vs.at[1, 0].set(ksc[0])
        out = _prefill_int8_kernel_pallas(
            q, kp, vp, ks, vs, bt, st, nv,
            block_sizes={"pages_per_block": 4}, interpret=True)
        return out, kp, vp, ks, vs

    args = tuple(jax.ShapeDtypeStruct(a.shape, a.dtype)
                 for a in (k_pages, v_pages, k_scales, v_scales, q,
                           block_tables, starts, n_valid))
    return step, args, (0, 1, 2, 3)


def _tp_local_sample(seed, *, tp, chunked, quantized=False):
    """The fp/int8 sample with its head axis cut to ONE tp shard's
    slice — the per-shard shapes a tp engine's sharded step dispatches
    the kernel at. ``--seed`` tunes these buckets so a tp mesh resolves
    from the committed manifest instead of a cold prior. None when this
    seed's head count is not divisible by ``tp``."""
    maker = _make_paged_int8_sample if quantized else _make_paged_sample
    args, kwargs = maker(seed, chunked=chunked)
    q, k_pages, v_pages = args[0], args[1], args[2]
    h, dh = q.shape[-2:]
    if h % tp:
        return None
    hl = h // tp
    q = q[:, :, :hl] if q.ndim == 4 else q[:, :hl]
    return (q, k_pages[:, :, :hl * dh],
            v_pages[:, :, :hl * dh]) + args[3:], kwargs


# -- latent rows: registry plumbing ------------------------------------------

#: rows (heads x queries) of a chunked-prefill tile
_LATENT_Q_ROWS = (256, 1024)


def _latent_decode_kernel_pallas(q, c_pages, r_pages, block_tables, lengths,
                                 group_slots, group_pages, shared_pages, *,
                                 block_sizes, interpret):
    return _latent_decode_pallas(
        q, c_pages, r_pages, block_tables, lengths, group_slots,
        group_pages, shared_pages, interpret,
        block_sizes.get("pages_per_block", 1))


def _latent_prefill_kernel_pallas(q, c_pages, r_pages, block_tables,
                                  chunk_starts, n_valid, *, block_sizes,
                                  interpret):
    return _latent_prefill_pallas(
        q, c_pages, r_pages, block_tables, chunk_starts, n_valid, interpret,
        block_sizes.get("pages_per_block", 1),
        block_sizes.get("q_rows", _LATENT_Q_ROWS[-1]))


def _latent_rows_np(c_pages, r_pages, table):
    """A slot's cached rows ``[c | k_rope]`` in token order (NumPy)."""
    import numpy as np
    c = np.asarray(c_pages, np.float32)[table]              # (mp, ps, Dl)
    r = np.asarray(r_pages, np.float32)[table]              # (mp, Dr, ps)
    return np.concatenate(
        [c.reshape(-1, c.shape[-1]),
         r.transpose(0, 2, 1).reshape(-1, r.shape[1])], axis=1)


def _latent_attend_np(q, rows, dl):
    """``q`` (H, Dl + Dr) over ``rows`` (T, Dl + Dr): softmax of the
    scores against the whole row, weighted sum of its first ``dl``."""
    import numpy as np
    s = q @ rows.T
    p = np.exp(s - s.max(-1, keepdims=True))
    return (p / p.sum(-1, keepdims=True)) @ rows[:, :dl]


def _latent_decode_reference(q, c_pages, r_pages, block_tables, lengths,
                             *_groups):
    """NumPy per-slot attention over the rows: independent of both impls
    and of which slots share pages."""
    import numpy as np
    dl = c_pages.shape[-1]
    qn, bt = np.asarray(q, np.float32), np.asarray(block_tables)
    out = np.zeros(q.shape[:2] + (dl,), np.float32)
    for sl, n in enumerate(np.asarray(lengths)):
        if n:
            out[sl] = _latent_attend_np(
                qn[sl], _latent_rows_np(c_pages, r_pages, bt[sl])[:n], dl)
    return jnp.asarray(out).astype(q.dtype)


def _latent_prefill_reference(q, c_pages, r_pages, block_tables,
                              chunk_starts, n_valid):
    import numpy as np
    dl = c_pages.shape[-1]
    qn, bt = np.asarray(q, np.float32), np.asarray(block_tables)
    st, nv = np.asarray(chunk_starts), np.asarray(n_valid)
    out = np.zeros(q.shape[:3] + (dl,), np.float32)
    for sl in range(q.shape[0]):
        rows = _latent_rows_np(c_pages, r_pages, bt[sl])
        for r in range(int(nv[sl])):
            out[sl, r] = _latent_attend_np(
                qn[sl, r], rows[:int(st[sl]) + r + 1], dl)
    return jnp.asarray(out).astype(q.dtype)


def _make_latent_sample(seed, *, chunked):
    """Three shapes by ``seed % 3``: float32 pools of pages scattered
    over the pool, slots of every length from empty to full. Decode:
    tables long enough for some slots' to open with the same pages (a
    pair; ten, which is a whole group and a pair; three and two), grouped
    as the engine groups them."""
    import numpy as np
    s_slots, h, dl, dr, ps, mp = (
        (4, 2, 16, 8, 8, 3), (6, 4, 32, 8, 16, 4), (8, 4, 64, 16, 16, 6)
    )[seed % 3] if chunked else (
        (4, 2, 16, 8, 8, 10), (12, 4, 32, 8, 8, 11), (8, 4, 64, 16, 16, 12)
    )[seed % 3]
    c = ps
    num_pages = s_slots * mp + 1
    rng = np.random.default_rng(seed)
    c_pages = jnp.asarray(rng.standard_normal((num_pages, ps, dl)),
                          jnp.float32)
    r_pages = jnp.asarray(rng.standard_normal((num_pages, dr, ps)),
                          jnp.float32)
    perm = rng.permutation(num_pages - 1)[:s_slots * mp] + 1
    tables = perm.reshape(s_slots, mp).astype(np.int32)
    scale = (dl + dr) ** -0.5           # the caller's: queries come scaled
    if not chunked:
        q = jnp.asarray(scale * rng.standard_normal((s_slots, h, dl + dr)),
                        jnp.float32)
        lengths = rng.integers(0, mp * ps + 1, s_slots).astype(np.int32)
        for sharers, k in ((([0, 1], 8),), ((list(range(10)), 9),),
                           (([0, 1, 2], 10), ([3, 4], 8)))[seed % 3]:
            tables[sharers, :k] = tables[sharers[0], :k]
            lengths[sharers] = rng.integers(k * ps, mp * ps + 1,
                                            len(sharers))
        groups = decode_groups(tables, lengths, np.arange(s_slots), ps)
        return (q, c_pages, r_pages, jnp.asarray(tables),
                jnp.asarray(lengths)) + tuple(map(jnp.asarray, groups)), {}
    block_tables = jnp.asarray(tables)
    q = jnp.asarray(scale * rng.standard_normal((s_slots, c, h, dl + dr)),
                    jnp.float32)
    starts = jnp.asarray(rng.integers(0, (mp - 1) * ps, s_slots), jnp.int32)
    n_valid = jnp.asarray(rng.integers(0, c + 1, s_slots), jnp.int32)
    return (q, c_pages, r_pages, block_tables, starts, n_valid), {}


def _latent_tune_signature(args, kwargs):
    q, c_pages, r_pages, bt = args[:4]
    sig = [("s", q.shape[0]), ("h", q.shape[-2]), ("dl", c_pages.shape[-1]),
           ("dr", r_pages.shape[1]), ("ps", c_pages.shape[1]),
           ("mp", bt.shape[1])]
    if q.ndim == 4:
        sig.insert(1, ("c", q.shape[1]))
    else:
        sig.append(("g", args[5].shape[1]))
    return tuple(sig)


def _latent_vmem_estimate(args, kwargs, blocks):
    """VMEM working set of one grid step of the latent bodies, tiles
    padded as the chip lays them out. Decode, the larger of its two
    parts, a group's: its own two buffers of ``pb`` pages of each pool,
    the members' queries double-buffered and stacked, the group's state
    block double-buffered, the state, and one update ``pb`` pages wide
    for every member's rows (float32 scores and weights, the weights'
    three bf16 terms and their product). Chunked prefill: ``pb`` pages
    of each pool, the query tile and the output tile double-buffered by
    the pipeline, the tile's state, and one page's scores and weights."""
    q, c_pages, r_pages = args[:3]
    ps, dl = c_pages.shape[1:]
    dr = r_pages.shape[1]
    isz = c_pages.dtype.itemsize
    pb = blocks.get("pages_per_block", 1)

    def tiled(sub, lane, itemsize):
        tile = 32 // itemsize
        return -(-sub // tile) * tile * -(-lane // 128) * 128 * itemsize

    pages = pb * (tiled(ps, dl, isz) + tiled(dr, ps, isz))
    if q.ndim == 3:
        rows = (q.shape[1] + -q.shape[1] % _HEAD_ROWS) * args[5].shape[1]
        width = pb * ps
        io = 3 * (tiled(rows, dl, isz) + tiled(rows, dr, isz)) \
            + 2 * tiled(rows, dl + _STATE_LANES, 4)
        state = 2 * tiled(rows, 128, 4) + tiled(rows, dl, 4)
        fold = (2 * tiled(rows, width, 4) + tiled(3 * rows, width, 2)
                + tiled(3 * rows, dl, 4))
        return 2 * pages + io + state + fold
    c, h = q.shape[1:3]
    rows = h * _latent_queries_a_tile(
        c, h, blocks.get("q_rows", _LATENT_Q_ROWS[-1]))
    io = 2 * (2 * tiled(rows, dl, isz) + tiled(rows, dr, isz))
    state = 2 * tiled(rows, 128, 4) + tiled(rows, dl, 4)
    fold = 3 * tiled(rows, ps, 4) + tiled(rows, dl, 4)
    return 2 * pages + io + state + fold


def _latent_donation_probe(chunked):
    def probe():
        args, _ = _make_latent_sample(0, chunked=chunked)
        q, c_pages, r_pages = args[:3]
        kernel = _latent_prefill_kernel_pallas if chunked \
            else _latent_decode_kernel_pallas

        def step(cp, rp, q, *geometry):
            # the engine's pattern: a token's row written, then attended
            # through the Pallas body, the pools handed back
            one = q[0, 0] if chunked else q[0]
            cp = cp.at[1, 0].set(one[0, :cp.shape[-1]])
            rp = rp.at[1, :, 0].set(one[0, cp.shape[-1]:])
            out = kernel(q, cp, rp, *geometry,
                         block_sizes={"pages_per_block": 2}, interpret=True)
            return out, cp, rp

        shapes = tuple(jax.ShapeDtypeStruct(a.shape, a.dtype)
                       for a in (c_pages, r_pages, q) + args[3:])
        return step, shapes, (0, 1)
    return probe


def _register_paged_kernels():
    from paddle_tpu import kernels
    pb_candidates = {"pages_per_block": (1, 2, 4)}
    # decode: the most pages a softmax update (dense), or a slot's whole
    # width in ONE grid step so that the next slot's pages arrive while
    # this one's are folded (pipelined: int8)
    decode_pb_candidates = {"pages_per_block": (1, 2, 4, 8)}
    kernels.register(kernels.KernelSpec(
        name="ragged_paged_decode",
        contract=kernels.KernelContract(
            version=1,
            arg_layouts={"q": "(S,H,Dh)", "k_pages": "(P,ps,H*Dh)",
                         "v_pages": "(P,ps,H*Dh)",
                         "block_tables": "(S,mp) i32",
                         "lengths": "(S,) i32"},
            out_layout="(S,H,Dh)",
            donatable=("k_pages", "v_pages"),
            grid="(S,) one step a slot, pools left in HBM: the body copies "
                 "the slot's live pages itself, pages_per_block side by "
                 "side into one of two VMEM buffers while it folds the "
                 "other; a block folded once for all heads (block-"
                 "structured Q over the page's lanes) as one softmax "
                 "update, block-table + lengths scalar prefetch, only "
                 "live pages copied and folded",
            block_candidates=decode_pb_candidates,
            atol=2e-5, rtol=2e-5),
        pallas_fn=_decode_kernel_pallas,
        lax_fn=_decode_kernel_lax,
        reference_fn=_decode_kernel_reference,
        sample_inputs=lambda seed: _make_paged_sample(seed, chunked=False),
        pallas_sites=(
            "paddle_tpu.serving.decode_attention:"
            "_paged_decode_walk_pallas",
            # a pool whose pages are not whole tiles
            "paddle_tpu.serving.decode_attention:_paged_attend_pallas"),
        tune_signature=_paged_tune_signature,
        vmem_estimate=functools.partial(_paged_vmem_estimate, walk=True),
        donation_probe=_decode_donation_probe,
        # per-shard (H/tp) buckets a tp engine dispatches this kernel at
        tune_sample_variants=(
            lambda s: _tp_local_sample(s, tp=2, chunked=False),
            lambda s: _tp_local_sample(s, tp=4, chunked=False))))
    kernels.register(kernels.KernelSpec(
        name="ragged_paged_prefill",
        contract=kernels.KernelContract(
            version=1,
            arg_layouts={"q": "(S,C,H,Dh)", "k_pages": "(P,ps,H*Dh)",
                         "v_pages": "(P,ps,H*Dh)",
                         "block_tables": "(S,mp) i32",
                         "chunk_starts": "(S,) i32",
                         "n_valid": "(S,) i32"},
            out_layout="(S,C,H,Dh)",
            donatable=("k_pages", "v_pages"),
            grid="(S, cdiv(mp,pages_per_block)) whole-page blocks, head "
                 "loop in the body, block-table scalar "
                 "prefetch, causal + live-lane mask",
            block_candidates=pb_candidates,
            atol=2e-5, rtol=2e-5),
        pallas_fn=_prefill_kernel_pallas,
        lax_fn=_prefill_kernel_lax,
        reference_fn=_prefill_kernel_reference,
        sample_inputs=lambda seed: _make_paged_sample(seed, chunked=True),
        pallas_sites=(
            "paddle_tpu.serving.decode_attention:_paged_attend_pallas",),
        tune_signature=_paged_tune_signature,
        vmem_estimate=_paged_vmem_estimate,
        donation_probe=_prefill_donation_probe,
        tune_sample_variants=(
            lambda s: _tp_local_sample(s, tp=2, chunked=True),
            lambda s: _tp_local_sample(s, tp=4, chunked=True))))
    kernels.register(kernels.KernelSpec(
        name="ragged_paged_decode_int8",
        contract=kernels.KernelContract(
            version=1,
            arg_layouts={"q": "(S,H,Dh)", "k_pages": "(P,ps,H*Dh) i8",
                         "v_pages": "(P,ps,H*Dh) i8",
                         "k_scales": "(P,ps) f32",
                         "v_scales": "(P,ps) f32",
                         "block_tables": "(S,mp) i32",
                         "lengths": "(S,) i32"},
            out_layout="(S,H,Dh)",
            donatable=("k_pages", "v_pages", "k_scales", "v_scales"),
            grid="(S, cdiv(mp,pages_per_block)) whole-page blocks, a page "
                 "folded once for all heads (block-structured Q over the "
                 "page's lanes), block-table + lengths scalar prefetch, "
                 "only live pages moved and folded, scales fused into "
                 "QK/PV",
            block_candidates=decode_pb_candidates,
            atol=5e-5, rtol=5e-5),
        pallas_fn=_decode_int8_kernel_pallas,
        lax_fn=_decode_int8_kernel_lax,
        reference_fn=_decode_int8_kernel_reference,
        sample_inputs=lambda seed: _make_paged_int8_sample(seed,
                                                           chunked=False),
        # the pipelined paged kernels run THROUGH the one pallas_call
        # site (static chunked/quantized flags)
        pallas_sites=(
            "paddle_tpu.serving.decode_attention:_paged_attend_pallas",),
        tune_signature=_paged_int8_tune_signature,
        vmem_estimate=_paged_vmem_estimate,
        donation_probe=_decode_int8_donation_probe,
        tune_sample_variants=(
            lambda s: _tp_local_sample(s, tp=2, chunked=False,
                                       quantized=True),
            lambda s: _tp_local_sample(s, tp=4, chunked=False,
                                       quantized=True))))
    kernels.register(kernels.KernelSpec(
        name="ragged_paged_prefill_int8",
        contract=kernels.KernelContract(
            version=1,
            arg_layouts={"q": "(S,C,H,Dh)", "k_pages": "(P,ps,H*Dh) i8",
                         "v_pages": "(P,ps,H*Dh) i8",
                         "k_scales": "(P,ps) f32",
                         "v_scales": "(P,ps) f32",
                         "block_tables": "(S,mp) i32",
                         "chunk_starts": "(S,) i32",
                         "n_valid": "(S,) i32"},
            out_layout="(S,C,H,Dh)",
            donatable=("k_pages", "v_pages", "k_scales", "v_scales"),
            grid="(S, cdiv(mp,pages_per_block)) whole-page blocks, head "
                 "loop in the body, block-table scalar "
                 "prefetch, causal + live-lane mask, scales fused into "
                 "QK/PV",
            block_candidates=pb_candidates,
            atol=5e-5, rtol=5e-5),
        pallas_fn=_prefill_int8_kernel_pallas,
        lax_fn=_prefill_int8_kernel_lax,
        reference_fn=_prefill_int8_kernel_reference,
        sample_inputs=lambda seed: _make_paged_int8_sample(seed,
                                                           chunked=True),
        pallas_sites=(
            "paddle_tpu.serving.decode_attention:_paged_attend_pallas",),
        tune_signature=_paged_int8_tune_signature,
        vmem_estimate=_paged_vmem_estimate,
        donation_probe=_prefill_int8_donation_probe,
        tune_sample_variants=(
            lambda s: _tp_local_sample(s, tp=2, chunked=True,
                                       quantized=True),
            lambda s: _tp_local_sample(s, tp=4, chunked=True,
                                       quantized=True))))

    latent_layouts = {"c_pages": "(P,ps,Dl)", "r_pages": "(P,Dr,ps)",
                      "block_tables": "(S,mp) i32"}
    kernels.register(kernels.KernelSpec(
        name="latent_paged_decode",
        contract=kernels.KernelContract(
            version=2,
            arg_layouts={"q": "(S,H,Dl+Dr)", **latent_layouts,
                         "lengths": "(S,) i32",
                         "group_slots": "(S//2,G) i32",
                         "group_pages": "(S//2,) i32",
                         "shared_pages": "(S,) i32"},
            out_layout="(S,H,Dl)",
            donatable=("c_pages", "r_pages"),
            grid="two calls, pools left in HBM. (S//2,) one step a group "
                 "of up to G slots whose tables open with the same "
                 "group_pages pages (group_slots, -1 for no member): the "
                 "members' queries stacked against ONE copy of each "
                 "shared page, their float32 states [acc|m|l] handed on; "
                 "then (S,) one step a slot, from that state over its own "
                 "pages from shared_pages on (shared_pages * ps <= "
                 "lengths; 0: the slot is walked alone). Either body "
                 "copies the live pages of both pools itself, "
                 "pages_per_block side by side into one of two VMEM "
                 "buffers while it folds the other; a block one softmax "
                 "update for all rows, the page of c read once for the "
                 "scores and the weighted sum",
            block_candidates=decode_pb_candidates,
            atol=2e-5, rtol=2e-5),
        pallas_fn=_latent_decode_kernel_pallas,
        lax_fn=_latent_decode_lax,
        reference_fn=_latent_decode_reference,
        sample_inputs=lambda seed: _make_latent_sample(seed, chunked=False),
        pallas_sites=(
            "paddle_tpu.serving.decode_attention:_latent_decode_pallas",),
        tune_signature=_latent_tune_signature,
        vmem_estimate=_latent_vmem_estimate,
        donation_probe=_latent_donation_probe(False)))
    kernels.register(kernels.KernelSpec(
        name="latent_paged_prefill",
        contract=kernels.KernelContract(
            version=1,
            arg_layouts={"q": "(S,C,H,Dl+Dr)", **latent_layouts,
                         "chunk_starts": "(S,) i32", "n_valid": "(S,) i32"},
            out_layout="(S,C,H,Dl)",
            donatable=("c_pages", "r_pages"),
            grid="(S, C*H/rows, cdiv(mp,pages_per_block)): a tile of "
                 "q_rows heads x queries against whole-page blocks of both "
                 "pools, block-table scalar prefetch, causal + live-row "
                 "mask, blocks past a tile's last query not moved",
            block_candidates={**pb_candidates, "q_rows": _LATENT_Q_ROWS},
            atol=2e-5, rtol=2e-5),
        pallas_fn=_latent_prefill_kernel_pallas,
        lax_fn=_latent_prefill_lax,
        reference_fn=_latent_prefill_reference,
        sample_inputs=lambda seed: _make_latent_sample(seed, chunked=True),
        pallas_sites=(
            "paddle_tpu.serving.decode_attention:_latent_prefill_pallas",),
        tune_signature=_latent_tune_signature,
        vmem_estimate=_latent_vmem_estimate,
        donation_probe=_latent_donation_probe(True)))

_register_paged_kernels()
