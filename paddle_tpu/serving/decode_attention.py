"""Ragged paged attention kernels: the serving engine's hot path.

One fixed-shape call attends every slot's query token(s) over only that
slot's *live* KV pages — the "Ragged Paged Attention" TPU serving
pattern (PAPERS.md): sequences of wildly different lengths batch into
one step, and work/HBM traffic scale with live tokens, not with
``batch × max_len`` padding. Two kernels share the layout and the
online-softmax structure (the reusable-kernel argument of Tensor
Processing Primitives — prefill is a chunk-sized variant of decode, not
a fourth bespoke module):

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
- ``impl="pallas"`` / ``"pallas_interpret"``: a Pallas kernel, grid
  ``(S, cdiv(max_pages, pages_per_block))``, that scalar-prefetches
  the block table so each kv block's HBM address is known before the
  body runs (the PrefetchScalarGridSpec pattern), streams WHOLE pages
  (every head; the head loop is inside the body and takes head ``h`` as
  a static lane slice of the page block), does online-softmax
  accumulation over pages, and skips pages past the slot's live extent
  entirely. The interpret path runs the REAL kernel on CPU, so tier-1
  tests exercise it.

Both kernels register with the shared kernel layer
(:mod:`paddle_tpu.kernels`): the public entry points dispatch through
the registry, the ``pages_per_block`` tunable (how many of a slot's
pages one grid step streams — bit-equal output for any setting, the
accumulation order is identical) resolves from the shared autotuner at
trace time, and the registry's parity battery + graph-lint contract
rule cover both.

**Dequant-attend int8 variants** (ISSUE 13):
``ragged_paged_decode_int8_attention`` and
``ragged_paged_prefill_int8_attention`` attend over an INT8 page pool
with per-token-row fp32 scales (``paged_cache.quantize_kv``'s layout).
The Pallas bodies stream the int8 pages through the SAME
``_online_softmax_page_fold`` with the scale broadcast fused into the
QK and PV products — no dequantized fp page is ever materialized, HBM
traffic per attended token halves (the bytes-per-token lever the cost
model gates in CI). Registered like the fp kernels: lax fallbacks with
identical scale-after-dot numerics, independent dense references,
contracts with donation-safe pages AND scales, and the shared
``pages_per_block`` tunable.

**Tensor-parallel variants** (ISSUE 15):
``ragged_paged_{decode,prefill}[_int8]_tp_attention`` run the
single-device kernels per head shard under ``shard_map`` — pages and
queries sharded ``H/tp`` over the mesh's "tp" axis (head-major folding
keeps a shard's heads contiguous: each holds ``(P, ps, (H/tp)*Dh)``),
block-table
geometry (and int8 scale rows) replicated. Heads are independent, so
each shard's output is BIT-identical to the tp=1 kernel; the one
attention-output collective lives at the caller's row-sharded output
projection, not in the kernel. Registered as mesh contracts
(``requires_mesh``) with their own parity battery and engine-shaped
donation probes that the kernel-contract lint lowers to verify
per-shard aliasing AND the declared ``("all_reduce",)`` collective set.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

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


def _paged_decode_lax(q, k_pages, v_pages, block_tables, lengths, scale):
    s_slots, h, dh = q.shape
    mp = block_tables.shape[1]
    ps = k_pages.shape[1]
    # contract straight against the gathered 5-D (S, mp, ps, H, Dh)
    # layout — reshaping the gather to token-major would materialize a
    # full extra copy of every slot's K and V per call
    kg = _gather_pages(k_pages, block_tables, h, dh)
    vg = _gather_pages(v_pages, block_tables, h, dh)
    scores = jnp.einsum("shd,smthd->shmt", q.astype(jnp.float32),
                        kg.astype(jnp.float32)) * scale
    scores = scores.reshape(s_slots, h, mp * ps)
    tok = jnp.arange(mp * ps, dtype=jnp.int32)
    valid = tok[None, None, :] < lengths[:, None, None]
    scores = jnp.where(valid, scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    # length-0 slots: every key masked -> emit 0, not a uniform mean of v
    alive = jnp.max(scores, axis=-1, keepdims=True) > NEG_INF / 2
    p = jnp.where(alive, p, 0.0).reshape(s_slots, h, mp, ps)
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
#   q / out  block (1, H, R, Dh)    head-major, R = 1 (decode) or C
#                                   (prefill); the wrappers transpose the
#                                   small activations, never the pool
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
# The head loop is inside the body: one grid step folds ``pb`` pages into
# EVERY head's (m, l, acc) state, kept per head in (H, R, .) scratch.

_SCALE_ROWS = 8     # f32 sublane tile: scale rows stream in groups of 8
_WIDE_VMEM_LIMIT = 64 << 20

#: the fold's dots run in true fp32. Mosaic's DEFAULT for fp32 operands
#: is a single bf16 pass (~3e-3 abs error at GPT-2 widths, measured on a
#: v5e), which is outside every paged contract's tolerance — the
#: interpreter never shows it.
_FP32_DOT = jax.lax.Precision.HIGHEST


def _online_softmax_page_fold(q, k, v, mask, m_scr, l_scr, acc_scr, h,
                              k_scale=None, v_scale=None):
    """Fold ONE head's (ps, Dh) slice of a kv page into head ``h``'s
    running (m, l, acc) online-softmax state. ``mask`` (rows, ps) marks
    live score entries; masked entries go to NEG_INF and contribute
    exact zeros. Shared by the decode and prefill kernels — the
    accumulation order here IS the byte-parity contract, so it must not
    diverge between them.

    ``k_scale``/``v_scale`` (1, ps) are the int8 page pool's
    per-token-row dequant scales (None on the fp path): the scale
    broadcast is fused INTO the QK and PV products — the int8 page goes
    straight into the dot and the per-token scale multiplies the
    (rows, ps) score/weight matrix, so no dequantized fp page is ever
    materialized (the TPP fused-microkernel shape). The m/l/acc update
    sequence is identical either way, so the int8 kernels inherit the
    same per-page accumulation-order contract."""
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), precision=_FP32_DOT,
        preferred_element_type=jnp.float32)            # (rows, ps)
    if k_scale is not None:
        s = s * k_scale
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[h]                                  # (rows, 128)
    l_prev = l_scr[h]
    m_cur = jnp.max(s, axis=1, keepdims=True)          # (rows, 1)
    m_next = jnp.maximum(m_prev, m_cur)                # lanes broadcast
    alpha = jnp.exp(m_prev - m_next)
    p = jnp.exp(s - m_next[:, :1])                     # (rows, ps)
    l_scr[h] = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
    m_scr[h] = m_next
    if v_scale is not None:
        p = p * v_scale
    pv = jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), precision=_FP32_DOT,
        preferred_element_type=jnp.float32)            # (rows, Dh)
    acc_scr[h] = acc_scr[h] * alpha[:, :1] + pv


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


def _paged_attend_kernel(bt_ref, *refs, page_size, pages_per_block,
                         chunked, quantized=False, selected=False):
    """THE paged-attention body: online-softmax over a slot's pages,
    ``pages_per_block`` pages per grid step (the shared autotuner's
    tunable: fewer grid iterations, deeper DMA pipelining; the per-page
    accumulation ORDER is identical to pages_per_block=1, so outputs
    are bit-equal for any setting), every head of the page folded in
    turn. Decode and chunked prefill are ONE body with a static
    ``chunked`` flag — they differ only in their scalar-prefetch
    geometry and the mask built from it:

    - decode (``len_ref``): one query row per head, key ``tok`` live
      iff ``tok < lengths[s]``;
    - prefill (``start_ref``, ``nv_ref``): C query rows per head, row
      ``r`` live iff ``r < n_valid[s]``, attending causally to
      ``tok <= chunk_starts[s] + r``.

    ``quantized`` is likewise ONE static flag, not a second kernel: the
    int8 page blocks ride with their per-token scale rows and the
    scales fuse into the shared fold — grid, ragged skip, and finish
    logic cannot diverge between the fp and dequant-attend variants.

    Grouped-query heads: a page block holds ``kv`` heads of ``Dh`` lanes
    and the query block ``n_heads``, a multiple of it; query head ``h``
    folds KV head ``h // (n_heads / kv)``. (Decode hands a group's
    queries in as the ROWS of its KV head instead, so there ``n_heads``
    is ``kv`` and a fold is one ``(group, Dh) x (Dh, ps)`` product.)

    ``selected`` (chunked only): one more input, ``(1, C, pb * ps)``
    marks per query row which cache positions it may attend to beside
    the causal test: sparse attention's per-query selection applied
    inside the streamed fold."""
    pb = pages_per_block
    n_geo = 2 if chunked else 1
    geo, q_ref, rest = refs[:n_geo], refs[n_geo], refs[n_geo + 1:]
    sel_ref = None
    if selected:
        sel_ref, rest = rest[0], rest[1:]
    (k_refs, v_refs, ks_refs, vs_refs, o_ref, m_scr, l_scr,
     acc_scr) = _split_kv_refs(rest, pb, quantized)
    sl = pl.program_id(0)
    pj = pl.program_id(1)
    npg = pl.num_programs(1)
    n_heads, rows, dh = q_ref.shape[1:]
    group = n_heads * dh // k_refs[0].shape[-1]    # query heads a KV head
    mp = bt_ref.shape[1]

    @pl.when(pj == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    if chunked:
        start, nv = geo[0][sl], geo[1][sl]
        extent = start + nv                  # tokens this chunk can see
        has_work = (nv > 0) & (pj * pb * page_size < extent)
    else:
        extent = geo[0][sl]
        has_work = pj * pb * page_size < extent

    def _body():
        for t in range(pb):
            # tokens at/after the slot's live extent (incl. whole tail
            # pages of this block, and the clamped duplicate page when
            # pb does not divide max_pages) mask to NEG_INF ->
            # exact-zero contributions to l and acc
            tok = (pj * pb + t) * page_size + jax.lax.broadcasted_iota(
                jnp.int32, (rows, page_size), 1)
            if chunked:
                row = jax.lax.broadcasted_iota(
                    jnp.int32, (rows, page_size), 0)
                ok = (tok <= start + row) & (row < nv)  # causal + live
                if selected:
                    ok = ok & (sel_ref[0, :, t * page_size:
                                       (t + 1) * page_size] > 0)
            else:
                ok = tok < extent
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
                    q_ref[0, h].astype(jnp.float32),            # (R, Dh)
                    k_refs[t][0, :, g * dh:(g + 1) * dh].astype(
                        jnp.float32),                           # (ps, Dh)
                    v_refs[t][0, :, g * dh:(g + 1) * dh].astype(
                        jnp.float32),
                    ok, m_scr, l_scr, acc_scr, h,
                    k_scale=k_scale, v_scale=v_scale)

    # ragged skip: blocks wholly past the slot's live extent do nothing
    pl.when(has_work)(_body)

    @pl.when(pj == npg - 1)
    def _finish():
        for h in range(n_heads):
            denom = l_scr[h][:, :1]
            denom = jnp.where(denom == 0.0, 1.0, denom)
            alive = m_scr[h][:, :1] > NEG_INF / 2
            o_ref[0, h] = jnp.where(
                alive, acc_scr[h] / denom, 0.0).astype(o_ref.dtype)


def _paged_kv_specs(ps, hd, mp, pb):
    """``pb`` (k, v) BlockSpec pairs per grid step: the WHOLE page
    ``j*pb + t`` of the slot's block table, all heads folded into its
    ``hd = H*Dh`` lanes (clamped to the
    last page — the clamped duplicate is fully masked by the token test
    in the kernel body). The index maps take the scalar-prefetch refs
    after the grid ids; the block table is always the first of them."""
    def kv_spec(t):
        def index(s, j, bt, *_rest):
            return (bt[s, jnp.minimum(j * pb + t, mp - 1)], 0, 0)
        return pl.BlockSpec((1, ps, hd), index)
    ks = [kv_spec(t) for t in range(pb)]
    vs = [kv_spec(t) for t in range(pb)]
    return ks, vs


def _paged_scale_specs(ps, mp, pb):
    """``pb`` (k_scale, v_scale) BlockSpec pairs — the 8-row group that
    holds the streamed page's (ps,) scale row, indexed by the SAME
    block-table entry as the page itself, so a page and its dequant
    scales always arrive together (the body reads row ``page % 8``)."""
    def sc_spec(t):
        def index(s, j, bt, *_rest):
            return (bt[s, jnp.minimum(j * pb + t, mp - 1)]
                    // _SCALE_ROWS, 0)
        return pl.BlockSpec((_SCALE_ROWS, ps), index)
    ks = [sc_spec(t) for t in range(pb)]
    vs = [sc_spec(t) for t in range(pb)]
    return ks, vs


@functools.partial(jax.jit, static_argnums=(5, 6), static_argnames=("name",))
def _paged_attend_pallas(q, k_pages, v_pages, block_tables, geometry,
                         interpret, pages_per_block, k_scales, v_scales,
                         selected=None, name=None):
    """The one ``pallas_call`` behind all four paged kernels. Jitted, so
    that a step program traces and lowers the kernel body once and calls
    it from every layer (same shapes, same static arguments: JAX reuses
    the inner function's jaxpr and XLA inlines the calls), where each of
    a model's layers used to trace its own copy: most of a signature's
    warm-up time. ``q`` is
    head-major ``(S, H, R, Dh)`` and already scaled; ``geometry`` is the
    scalar-prefetch tail after the block table — ``(lengths,)`` for
    decode, ``(chunk_starts, n_valid)`` for chunked prefill.
    ``k_scales``/``v_scales`` given = the dequant-attend variant;
    ``selected`` (S, C, mp*ps) given = chunked prefill under a per-query
    selection; ``name`` renames the call for a device trace (the sparse
    entries run this body under their own names)."""
    quantized = k_scales is not None
    chunked = len(geometry) == 2
    s_slots, h, rows, dh = q.shape
    mp = block_tables.shape[1]
    ps = k_pages.shape[1]
    pb = max(1, min(int(pages_per_block), mp))
    k_specs, v_specs = _paged_kv_specs(ps, k_pages.shape[-1], mp, pb)
    sel_specs, sel_args = [], []
    if selected is not None:
        sel_specs = [pl.BlockSpec(
            (1, rows, pb * ps), lambda s, j, *_prefetch: (s, 0, j))]
        sel_args = [selected]
    sc_specs, sc_args = [], []
    if quantized:
        ks_specs, vs_specs = _paged_scale_specs(ps, mp, pb)
        sc_specs = [*ks_specs, *vs_specs]
        sc_args = [*([k_scales] * pb), *([v_scales] * pb)]

    def q_index(s, j, *_prefetch):
        return (s, 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1 + len(geometry),
        grid=(s_slots, pl.cdiv(mp, pb)),
        in_specs=[
            pl.BlockSpec((1, h, rows, dh), q_index),
            *sel_specs,
            *k_specs,
            *v_specs,
            *sc_specs,
        ],
        out_specs=pl.BlockSpec((1, h, rows, dh), q_index),
        scratch_shapes=[
            pltpu.VMEM((h, rows, 128), jnp.float32),
            pltpu.VMEM((h, rows, 128), jnp.float32),
            pltpu.VMEM((h, rows, dh), jnp.float32),
        ],
    )
    kernel = functools.partial(_paged_attend_kernel, page_size=ps,
                               pages_per_block=pb, chunked=chunked,
                               quantized=quantized,
                               selected=selected is not None)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            # a chunk of grouped-query heads unrolls (heads x pages)
            # folds whose fp32 temporaries the compiler stacks side by
            # side: 33 MB at 32 heads x 64 queries x 4 pages against the
            # default scoped limit of 16 MB (v5e has 128 MB of VMEM)
            vmem_limit_bytes=_WIDE_VMEM_LIMIT if chunked and (
                selected is not None or h * dh != k_pages.shape[-1])
            else None,
        ) if not interpret else None,
        interpret=interpret,
        name=name or ("ragged_paged_prefill" if chunked
                      else "ragged_paged_decode"),
    )(block_tables.astype(jnp.int32),
      *(g.astype(jnp.int32) for g in geometry),
      q, *sel_args, *([k_pages] * pb), *([v_pages] * pb), *sc_args)


def _paged_decode_pallas(q, k_pages, v_pages, block_tables, lengths, scale,
                         interpret, pages_per_block=1, k_scales=None,
                         v_scales=None, name=None):
    """``k_scales``/``v_scales`` given = the dequant-attend variant:
    same grid and BlockSpecs plus one scale-row group per streamed
    page, fused into the shared fold inside the ONE kernel body.
    Grouped-query heads: the ``H / kv`` queries of a KV head go in as
    that head's rows, ``(S, kv, H/kv, Dh)`` (one query row when every
    head has its own K and V)."""
    s_slots, h, dh = q.shape
    kv = k_pages.shape[-1] // dh
    qs = (q * jnp.asarray(scale, q.dtype)).reshape(s_slots, kv, h // kv, dh)
    out = _paged_attend_pallas(qs, k_pages, v_pages, block_tables,
                               (lengths,), interpret, pages_per_block,
                               k_scales, v_scales, name=name)
    return out.reshape(s_slots, h, dh)


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
    extra scalar-prefetched blocks, fused into the QK/PV products
    inside the shared fold (no materialized fp page)."""
    return _paged_decode_pallas(q, k_pages, v_pages, block_tables,
                                lengths, scale, interpret,
                                pages_per_block=pages_per_block,
                                k_scales=k_scales, v_scales=v_scales)


# ---------------------------------------------------------------------------
# batched chunked prefill: lax reference + Pallas kernel
# ---------------------------------------------------------------------------

def _paged_prefill_lax(q, k_pages, v_pages, block_tables, chunk_starts,
                       n_valid, scale, selected=None):
    """``selected`` (S, C, mp*ps), where given, marks the cache positions
    each query may attend to beside the causal test (sparse attention:
    the indexer's choice)."""
    s_slots, c, h, dh = q.shape
    mp = block_tables.shape[1]
    ps = k_pages.shape[1]
    kg = _gather_pages(k_pages, block_tables, h, dh)
    vg = _gather_pages(v_pages, block_tables, h, dh)
    scores = jnp.einsum("schd,smthd->shcmt", q.astype(jnp.float32),
                        kg.astype(jnp.float32)) * scale
    scores = scores.reshape(s_slots, h, c, mp * ps)
    tok = jnp.arange(mp * ps, dtype=jnp.int32)
    pos = chunk_starts[:, None] + jnp.arange(c, dtype=jnp.int32)  # (S, C)
    causal = tok[None, None, None, :] <= pos[:, None, :, None]
    row_ok = (jnp.arange(c) < n_valid[:, None])[:, None, :, None]
    ok = causal & row_ok
    if selected is not None:
        ok = ok & (selected[:, None] > 0)
    scores = jnp.where(ok, scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    # masked rows (padding lanes / inactive slots) emit exact zeros
    alive = jnp.max(scores, axis=-1, keepdims=True) > NEG_INF / 2
    p = jnp.where(alive, p, 0.0).reshape(s_slots, h, c, mp, ps)
    out = jnp.einsum("shcmt,smthd->schd", p, vg.astype(jnp.float32))
    return out.astype(q.dtype)


def _paged_prefill_pallas(q, k_pages, v_pages, block_tables, chunk_starts,
                          n_valid, scale, interpret, pages_per_block=1,
                          k_scales=None, v_scales=None, selected=None,
                          name=None):
    """Chunked-prefill analog of :func:`_paged_decode_pallas`: the SAME
    kernel body (``chunked=True``), same ``pages_per_block`` tunable and
    bit-equal accumulation order. ``q`` (S, C, H, Dh) is handed to the
    kernel head-major; the engine holds it head-major already, so XLA
    cancels this transpose against the caller's."""
    qs = (q * jnp.asarray(scale, q.dtype)).transpose(0, 2, 1, 3)
    out = _paged_attend_pallas(qs, k_pages, v_pages, block_tables,
                               (chunk_starts, n_valid), interpret,
                               pages_per_block, k_scales, v_scales,
                               selected=selected, name=name)
    return out.transpose(0, 2, 1, 3)                        # (S,C,H,Dh)


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

def ragged_paged_decode_attention(q, k_pages, v_pages, block_tables,
                                  lengths, *, scale: Optional[float] = None,
                                  impl: str = "auto"):
    """One decode step of attention for every slot at once.

    ``q`` (S, H, Dh); ``k_pages``/``v_pages`` (P, page_size, H*Dh),
    a token's heads folded head-major into the last axis;
    ``block_tables`` (S, max_pages) int32; ``lengths`` (S,) int32 valid
    tokens per slot. Returns (S, H, Dh). ``impl``: "auto" (pallas on
    TPU, lax elsewhere), "lax", "pallas", "pallas_interpret".
    """
    from paddle_tpu import kernels
    return kernels.dispatch("ragged_paged_decode", q, k_pages, v_pages,
                            block_tables, lengths, impl=impl, scale=scale)


def ragged_paged_prefill_attention(q, k_pages, v_pages, block_tables,
                                   chunk_starts, n_valid, *,
                                   scale: Optional[float] = None,
                                   impl: str = "auto"):
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
    "pallas_interpret".
    """
    from paddle_tpu import kernels
    return kernels.dispatch("ragged_paged_prefill", q, k_pages, v_pages,
                            block_tables, chunk_starts, n_valid,
                            impl=impl, scale=scale)


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


def ragged_paged_decode_tp_attention(q, k_pages, v_pages, block_tables,
                                     lengths, *,
                                     scale: Optional[float] = None,
                                     impl: str = "auto", mesh=None):
    """Tensor-parallel ragged paged decode (ISSUE 15): same contract as
    :func:`ragged_paged_decode_attention` with ``q`` (S, H, Dh) and the
    page pool sharded ``H/tp`` over the mesh's "tp" axis (the folded
    ``H*Dh`` axis cut into ``tp`` runs of whole heads), block tables
    and lengths replicated. Runs the single-device kernel per head
    shard under ``shard_map`` — heads are independent, so the sharded
    output is BIT-identical to the tp=1 kernel on the same pages; the
    attention-output collective lives at the caller's row-sharded
    output projection, not here. Returns (S, H, Dh) sharded like
    ``q``. Must run under a mesh (``mesh_context`` or ``mesh=``)."""
    from paddle_tpu import kernels
    return kernels.dispatch("ragged_paged_decode_tp", q, k_pages,
                            v_pages, block_tables, lengths, impl=impl,
                            scale=scale, mesh=mesh)


def ragged_paged_prefill_tp_attention(q, k_pages, v_pages, block_tables,
                                      chunk_starts, n_valid, *,
                                      scale: Optional[float] = None,
                                      impl: str = "auto", mesh=None):
    """Tensor-parallel batched chunked prefill — the tp twin of
    :func:`ragged_paged_prefill_attention` (``q`` (S, C, H, Dh) and the
    pages sharded ``H/tp``, chunk geometry replicated). Same
    head-independence argument as the decode variant: bit-identical to
    tp=1 per head shard, zero collectives inside the kernel."""
    from paddle_tpu import kernels
    return kernels.dispatch("ragged_paged_prefill_tp", q, k_pages,
                            v_pages, block_tables, chunk_starts, n_valid,
                            impl=impl, scale=scale, mesh=mesh)


def ragged_paged_decode_int8_tp_attention(q, k_pages, v_pages, k_scales,
                                          v_scales, block_tables,
                                          lengths, *,
                                          scale: Optional[float] = None,
                                          impl: str = "auto", mesh=None):
    """Tensor-parallel dequant-attend decode: int8 pages sharded
    ``H/tp``, per-token-row fp32 scales REPLICATED (a token's scale is
    computed over all heads — see ``quantize_kv``'s ``psum_axis`` — so
    every shard dequantizes its head slice with the same row)."""
    from paddle_tpu import kernels
    return kernels.dispatch("ragged_paged_decode_int8_tp", q, k_pages,
                            v_pages, k_scales, v_scales, block_tables,
                            lengths, impl=impl, scale=scale, mesh=mesh)


def ragged_paged_prefill_int8_tp_attention(q, k_pages, v_pages, k_scales,
                                           v_scales, block_tables,
                                           chunk_starts, n_valid, *,
                                           scale: Optional[float] = None,
                                           impl: str = "auto",
                                           mesh=None):
    """Tensor-parallel dequant-attend batched chunked prefill (the int8
    twin of :func:`ragged_paged_prefill_tp_attention`)."""
    from paddle_tpu import kernels
    return kernels.dispatch("ragged_paged_prefill_int8_tp", q, k_pages,
                            v_pages, k_scales, v_scales, block_tables,
                            chunk_starts, n_valid, impl=impl, scale=scale,
                            mesh=mesh)


def paged_prefill_attention(q, k_pages, v_pages, block_table_row,
                            positions, *, scale: Optional[float] = None):
    """Chunked-prefill attention for ONE slot.

    ``q`` (C, H, Dh) — a chunk of query tokens at absolute ``positions``
    (C,) int32; keys/values are read from the slot's pages via
    ``block_table_row`` (max_pages,). Each query attends causally to all
    cache positions ``<= positions[c]`` (earlier chunks + the causal
    prefix of this chunk, whose K/V the caller has already written).
    Padded queries (positions past the chunk's valid length) produce
    garbage rows the caller discards. XLA-composed: prefill is a few
    calls per request, the per-step hot path is the decode kernel.
    """
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    mp = block_table_row.shape[0]
    ps = k_pages.shape[1]
    h, dh = q.shape[1], q.shape[2]
    k = k_pages[block_table_row].reshape(mp * ps, h, dh)
    v = v_pages[block_table_row].reshape(mp * ps, h, dh)
    scores = jnp.einsum("chd,thd->hct", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    tok = jnp.arange(mp * ps, dtype=jnp.int32)
    causal = tok[None, None, :] <= positions[None, :, None]
    scores = jnp.where(causal, scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    alive = jnp.max(scores, axis=-1, keepdims=True) > NEG_INF / 2
    p = jnp.where(alive, p, 0.0)
    out = jnp.einsum("hct,thd->chd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# kernel-registry entries (paddle_tpu.kernels)
# ---------------------------------------------------------------------------

def _decode_kernel_pallas(q, k_pages, v_pages, block_tables, lengths, *,
                          block_sizes, interpret, scale=None):
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    return _paged_decode_pallas(
        q, k_pages, v_pages, block_tables, lengths, scale, interpret,
        pages_per_block=block_sizes.get("pages_per_block", 1))


def _decode_kernel_lax(q, k_pages, v_pages, block_tables, lengths, *,
                       scale=None):
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    return _paged_decode_lax(q, k_pages, v_pages, block_tables, lengths,
                             scale)


def _decode_kernel_reference(q, k_pages, v_pages, block_tables, lengths,
                             *, scale=None):
    """NumPy per-slot dense attention — independent of both impls."""
    import numpy as np
    s_slots, h, dh = q.shape
    if scale is None:
        scale = 1.0 / math.sqrt(dh)
    mp, ps = block_tables.shape[1], k_pages.shape[1]
    qn = np.asarray(q, np.float32)
    kp = np.asarray(k_pages, np.float32)
    vp = np.asarray(v_pages, np.float32)
    bt = np.asarray(block_tables)
    ln = np.asarray(lengths)
    outs = np.zeros((s_slots, h, dh), np.float32)
    for sl in range(s_slots):
        n = int(ln[sl])
        if n == 0:
            continue
        k = kp[bt[sl]].reshape(mp * ps, h, dh)[:n]
        v = vp[bt[sl]].reshape(mp * ps, h, dh)[:n]
        s = np.einsum("hd,thd->ht", qn[sl], k) * scale
        s = s - s.max(-1, keepdims=True)
        p = np.exp(s)
        p = p / p.sum(-1, keepdims=True)
        outs[sl] = np.einsum("ht,thd->hd", p, v)
    return jnp.asarray(outs).astype(q.dtype)


def _make_paged_sample(seed, *, chunked):
    import numpy as np
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
        return (q, k_pages, v_pages, block_tables, lengths), {}
    q = jnp.asarray(rng.standard_normal((s_slots, c, h, dh)), jnp.float32)
    starts = jnp.asarray(
        rng.integers(0, (mp - 1) * ps, s_slots), jnp.int32)
    n_valid = jnp.asarray(rng.integers(0, c + 1, s_slots), jnp.int32)
    return (q, k_pages, v_pages, block_tables, starts, n_valid), {}


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
    return _paged_sig(args[0], args[1], args[3])


def _paged_vmem_estimate(args, kwargs, blocks):
    """VMEM working set of one grid step, as the TPU lays it out: the
    streamed blocks are whole pages (every head, folded into the lane
    axis: ``(ps, H*Dh)`` tiles with no per-head padding), tiles pad the
    last two dims to (32/itemsize, 128), and the pipeline double-buffers
    every in/out block. One estimate for the fp and int8 kernels — the
    page dtype and the scale rows are read off the arguments."""
    q, k_pages = args[0], args[1]
    ps = k_pages.shape[1]
    h, dh = q.shape[-2:]
    rows = q.shape[1] if q.ndim == 4 else 1
    pb = blocks.get("pages_per_block", 1)

    def tiled(lead, sub, lane, itemsize):
        tile = 32 // itemsize
        return (lead * -(-sub // tile) * tile * -(-lane // 128) * 128
                * itemsize)

    page = tiled(1, ps, k_pages.shape[-1], k_pages.dtype.itemsize)
    qo = tiled(h, rows, dh, q.dtype.itemsize)
    streamed = 2 * pb * page + 2 * qo
    if k_pages.dtype.itemsize == 1:              # int8: + scale groups
        streamed += 2 * pb * tiled(1, _SCALE_ROWS, ps, 4)
    scratch = 2 * tiled(h, rows, 128, 4) + tiled(h, rows, dh, 4)
    # fp32 temporaries of one head fold: k, v, scores, weights
    fold = 2 * tiled(1, ps, dh, 4) + 2 * tiled(1, rows, ps, 4)
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
                           interpret, scale=None):
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    return _paged_prefill_pallas(
        q, k_pages, v_pages, block_tables, chunk_starts, n_valid, scale,
        interpret, pages_per_block=block_sizes.get("pages_per_block", 1))


def _prefill_kernel_lax(q, k_pages, v_pages, block_tables, chunk_starts,
                        n_valid, *, scale=None):
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    return _paged_prefill_lax(q, k_pages, v_pages, block_tables,
                              chunk_starts, n_valid, scale)


def _prefill_kernel_reference(q, k_pages, v_pages, block_tables,
                              chunk_starts, n_valid, *, scale=None):
    """NumPy per-slot, per-row causal attention over the slot's pages."""
    import numpy as np
    s_slots, c, h, dh = q.shape
    if scale is None:
        scale = 1.0 / math.sqrt(dh)
    mp, ps = block_tables.shape[1], k_pages.shape[1]
    qn = np.asarray(q, np.float32)
    kp = np.asarray(k_pages, np.float32)
    vp = np.asarray(v_pages, np.float32)
    bt = np.asarray(block_tables)
    st = np.asarray(chunk_starts)
    nv = np.asarray(n_valid)
    outs = np.zeros((s_slots, c, h, dh), np.float32)
    for sl in range(s_slots):
        k = kp[bt[sl]].reshape(mp * ps, h, dh)
        v = vp[bt[sl]].reshape(mp * ps, h, dh)
        for r in range(int(nv[sl])):
            limit = int(st[sl]) + r + 1          # causal horizon
            s = np.einsum("hd,thd->ht", qn[sl, r], k[:limit]) * scale
            s = s - s.max(-1, keepdims=True)
            p = np.exp(s)
            p = p / p.sum(-1, keepdims=True)
            outs[sl, r] = np.einsum("ht,thd->hd", p, v[:limit])
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


def _register_paged_kernels():
    from paddle_tpu import kernels
    pb_candidates = {"pages_per_block": (1, 2, 4)}
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
            grid="(S, cdiv(mp,pages_per_block)) whole-page blocks, head "
                 "loop in the body, block-table scalar "
                 "prefetch, dead-page skip",
            block_candidates=pb_candidates,
            atol=2e-5, rtol=2e-5),
        pallas_fn=_decode_kernel_pallas,
        lax_fn=_decode_kernel_lax,
        reference_fn=_decode_kernel_reference,
        sample_inputs=lambda seed: _make_paged_sample(seed, chunked=False),
        pallas_sites=(
            "paddle_tpu.serving.decode_attention:_paged_attend_pallas",),
        tune_signature=_paged_tune_signature,
        vmem_estimate=_paged_vmem_estimate,
        donation_probe=_decode_donation_probe,
        # per-shard (H/tp) buckets the tp wrappers dispatch this kernel
        # at — lambdas so the late-defined helper resolves at call time
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
            grid="(S, cdiv(mp,pages_per_block)) whole-page blocks, head "
                 "loop in the body, block-table scalar "
                 "prefetch, dead-page skip, scales fused into QK/PV",
            block_candidates=pb_candidates,
            atol=5e-5, rtol=5e-5),
        pallas_fn=_decode_int8_kernel_pallas,
        lax_fn=_decode_int8_kernel_lax,
        reference_fn=_decode_int8_kernel_reference,
        sample_inputs=lambda seed: _make_paged_int8_sample(seed,
                                                           chunked=False),
        # all four paged kernels run THROUGH the one pallas_call site
        # (one body, static chunked/quantized flags)
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


_register_paged_kernels()


# ---------------------------------------------------------------------------
# tensor-parallel wrappers (ISSUE 15): heads sharded H/tp over "tp"
# ---------------------------------------------------------------------------

from jax.sharding import PartitionSpec as _P  # noqa: E402

#: the canonical tp specs: pages/queries sharded on the HEAD axis (the
#: pool's folded H*Dh axis is head-major, so a shard's slice is its own
#: whole heads), block-table geometry (and int8 scale rows) replicated
_TP_KV_SPEC = _P(None, None, "tp")                # (P, ps, H*Dh)
_TP_Q_DECODE = _P(None, "tp", None)               # (S, H, Dh)
_TP_Q_PREFILL = _P(None, None, "tp", None)        # (S, C, H, Dh)


def _tp_mesh(mesh):
    from paddle_tpu.core import mesh as mesh_lib
    mesh = mesh or mesh_lib.current_mesh()
    if mesh is None:
        raise ValueError("tp paged attention requires a mesh "
                         "(use mesh_context or pass mesh=)")
    return mesh


def _tp_run(inner_name, args, specs, *, inner_impl, block_sizes,
            scale, mesh):
    """Run the single-device kernel ``inner_name`` per head shard under
    shard_map. The inner dispatch resolves its block sizes from the
    shared autotuner at the LOCAL (H/tp) shapes — trace-time host code,
    so the tp wrappers stay recompile-safe; ``--seed`` keeps the
    committed manifest covering those buckets (tune_sample_variants)."""
    mesh = _tp_mesh(mesh)
    from paddle_tpu.core.compat import shard_map

    def body(*local):
        from paddle_tpu import kernels
        return kernels.dispatch(inner_name, *local, impl=inner_impl,
                                block_sizes=block_sizes or None,
                                scale=scale)

    out_spec = specs[0]       # output sharded like q
    return shard_map(body, mesh=mesh, in_specs=specs,
                     out_specs=out_spec, check_vma=False)(*args)


def _make_tp_fns(inner_name, specs):
    """(pallas_fn, lax_fn) pair for one tp wrapper spec."""
    def pallas_fn(*args, block_sizes, interpret, scale=None, mesh=None):
        if scale is None:
            scale = 1.0 / math.sqrt(args[0].shape[-1])
        impl = "pallas_interpret" if interpret else "pallas"
        return _tp_run(inner_name, args, specs, inner_impl=impl,
                       block_sizes=block_sizes, scale=scale, mesh=mesh)

    def lax_fn(*args, scale=None, mesh=None):
        if scale is None:
            scale = 1.0 / math.sqrt(args[0].shape[-1])
        return _tp_run(inner_name, args, specs, inner_impl="lax",
                       block_sizes=None, scale=scale, mesh=mesh)

    return pallas_fn, lax_fn


def _tp_parity_mesh():
    """Largest dp×(tp=2) mesh covering every device — tp=2 divides all
    sample head counts; None when the box cannot host one."""
    n = len(jax.devices())
    if n < 2 or n % 2:
        return None
    from paddle_tpu.core.mesh import MeshConfig, make_mesh
    return make_mesh(MeshConfig(dp=n // 2, tp=2))


def _make_tp_parity_fn(name, inner_name, sample_fn, reference_fn,
                       quantized=False):
    """Mesh-orchestrated battery for one tp wrapper: lax and
    pallas-interpret through the sharded dispatch vs the dense
    reference, PLUS the bit-equality pin — the tp lax path must equal
    the single-device lax kernel exactly (heads are independent). The
    int8 variants pin to 1e-6 instead: XLA's codegen for the fused
    cast-dequant dot reassociates differently at different head counts,
    so the per-shard dequant einsum can drift a last ulp from the
    full-head one (the engine-level acceptance — greedy tokens
    identical to tp=1 — is pinned exactly in tests/test_serving_tp.py
    and the serving_tp bench)."""
    def parity(seed):
        import numpy as np
        mesh = _tp_parity_mesh()
        if mesh is None:
            return {}
        args, kwargs = sample_fn(seed)
        from paddle_tpu import kernels
        contract = kernels.get(name).contract
        ref = np.asarray(reference_fn(*args, **kwargs), np.float32)
        from paddle_tpu.core.mesh import mesh_context
        errs = {}
        with mesh_context(mesh):
            for impl in ("lax", "pallas_interpret"):
                out = np.asarray(jax.jit(
                    lambda *a, _i=impl: kernels.dispatch(
                        name, *a, impl=_i, mesh=mesh, **kwargs))(*args),
                    np.float32)
                np.testing.assert_allclose(
                    out, ref, atol=contract.atol, rtol=contract.rtol,
                    err_msg=f"{name}[{impl}] diverged from the dense "
                            "reference")
                errs[impl] = float(np.max(np.abs(out - ref)))
            tp_lax = np.asarray(jax.jit(
                lambda *a: kernels.dispatch(
                    name, *a, impl="lax", mesh=mesh, **kwargs))(*args))
            tp1 = np.asarray(kernels.dispatch(inner_name, *args,
                                              impl="lax", **kwargs))
            if quantized:
                np.testing.assert_allclose(
                    tp_lax, tp1, rtol=1e-6, atol=1e-6,
                    err_msg=f"{name} tp output drifted from the "
                            f"single-device {inner_name} kernel")
            else:
                np.testing.assert_array_equal(
                    tp_lax, tp1,
                    err_msg=f"{name} tp output is not bit-identical to "
                            f"the single-device {inner_name} kernel")
        return errs
    return parity


def _tp_probe_mesh():
    devs = jax.devices()
    if len(devs) < 2:
        return None
    from paddle_tpu.core.mesh import MeshConfig, make_mesh
    return make_mesh(MeshConfig(tp=2), devices=devs[:2])


def _tp_local_sample(seed, *, tp, chunked, quantized=False):
    """The fp/int8 sample with its head axis cut to ONE tp shard's
    slice — the per-shard shapes the tp wrappers dispatch the inner
    kernel at. ``--seed`` tunes these buckets so a tp mesh resolves
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


def _tp_donation_probe(*, chunked, quantized):
    """Engine-shaped donation probe for one tp wrapper: write this
    step's K/V into the PER-SHARD pages (quantized: int8 rows + the
    replicated scale rows, with the pmax-completed global scale), attend
    through the sharded kernel, then the row-sharded output projection
    with THE one attention-output psum — and hand every pool buffer
    back. Lowered by the kernel-contract lint: per-shard aliasing
    (``jax.buffer_donor`` under SPMD) and exactly the contract's
    ``("all_reduce",)`` collective kind. None when the box cannot host
    a tp=2 mesh."""
    mesh = _tp_probe_mesh()
    if mesh is None:
        return None
    from paddle_tpu.core.compat import shard_map
    if quantized:
        (q, kp, vp, ks, vs, *rest), _ = _make_paged_int8_sample(
            0, chunked=chunked)
    else:
        (q, kp, vp, *rest), _ = _make_paged_sample(0, chunked=chunked)
    h, dh = q.shape[-2:]
    d_model = h * dh
    wo = jnp.zeros((h, dh, d_model), jnp.float32)
    inner = ("ragged_paged_prefill" if chunked else "ragged_paged_decode")
    inner += "_int8" if quantized else ""
    q_spec = _TP_Q_PREFILL if chunked else _TP_Q_DECODE
    geo_specs = tuple(_P() for _ in rest)

    if quantized:
        def local(kp, vp, ks, vs, q, wo, *geo):
            from paddle_tpu import kernels
            from paddle_tpu.serving.paged_cache import quantize_kv
            tok = (q[:1, 0] if chunked else q[:1]).reshape(1, -1)
            kq, ksc = quantize_kv(tok, (1,), psum_axis="tp")
            kp = kp.at[1, 0].set(kq[0])
            vp = vp.at[1, 0].set(kq[0])
            ks = ks.at[1, 0].set(ksc[0])
            vs = vs.at[1, 0].set(ksc[0])
            att = kernels.dispatch(inner, q, kp, vp, ks, vs, *geo,
                                   impl="lax")
            part = (jnp.einsum("schk,hkd->scd", att, wo) if chunked
                    else jnp.einsum("shk,hkd->sd", att, wo))
            out = jax.lax.psum(part, "tp")
            return out, kp, vp, ks, vs

        fn = shard_map(
            local, mesh=mesh,
            in_specs=(_TP_KV_SPEC, _TP_KV_SPEC, _P(), _P(), q_spec,
                      _P("tp", None, None)) + geo_specs,
            out_specs=(_P(), _TP_KV_SPEC, _TP_KV_SPEC, _P(), _P()),
            check_vma=False)
        arrs = (kp, vp, ks, vs, q, wo) + tuple(rest)
        donate = (0, 1, 2, 3)
    else:
        def local(kp, vp, q, wo, *geo):
            from paddle_tpu import kernels
            tok = (q[0, 0] if chunked else q[0]).reshape(-1)
            kp = kp.at[1, 0].set(tok)
            vp = vp.at[1, 0].set(tok)
            att = kernels.dispatch(inner, q, kp, vp, *geo, impl="lax")
            part = (jnp.einsum("schk,hkd->scd", att, wo) if chunked
                    else jnp.einsum("shk,hkd->sd", att, wo))
            out = jax.lax.psum(part, "tp")
            return out, kp, vp

        fn = shard_map(
            local, mesh=mesh,
            in_specs=(_TP_KV_SPEC, _TP_KV_SPEC, q_spec,
                      _P("tp", None, None)) + geo_specs,
            out_specs=(_P(), _TP_KV_SPEC, _TP_KV_SPEC),
            check_vma=False)
        arrs = (kp, vp, q, wo) + tuple(rest)
        donate = (0, 1)
    args = tuple(jax.ShapeDtypeStruct(a.shape, a.dtype) for a in arrs)
    return fn, args, donate


def _register_tp_kernels():
    from paddle_tpu import kernels
    grid = "shard_map over tp: inner kernel per H/tp head shard; the " \
           "attention-output collective lives at the caller's " \
           "row-sharded projection"
    defs = (
        ("ragged_paged_decode_tp", "ragged_paged_decode", False, False,
         {"q": "(S,H,Dh) H/tp", "k_pages": "(P,ps,H*Dh) H/tp",
          "v_pages": "(P,ps,H*Dh) H/tp", "block_tables": "(S,mp) i32",
          "lengths": "(S,) i32"}, "(S,H,Dh) H/tp"),
        ("ragged_paged_prefill_tp", "ragged_paged_prefill", True, False,
         {"q": "(S,C,H,Dh) H/tp", "k_pages": "(P,ps,H*Dh) H/tp",
          "v_pages": "(P,ps,H*Dh) H/tp", "block_tables": "(S,mp) i32",
          "chunk_starts": "(S,) i32", "n_valid": "(S,) i32"},
         "(S,C,H,Dh) H/tp"),
        ("ragged_paged_decode_int8_tp", "ragged_paged_decode_int8",
         False, True,
         {"q": "(S,H,Dh) H/tp", "k_pages": "(P,ps,H*Dh) i8 H/tp",
          "v_pages": "(P,ps,H*Dh) i8 H/tp",
          "k_scales": "(P,ps) f32 replicated",
          "v_scales": "(P,ps) f32 replicated",
          "block_tables": "(S,mp) i32", "lengths": "(S,) i32"},
         "(S,H,Dh) H/tp"),
        ("ragged_paged_prefill_int8_tp", "ragged_paged_prefill_int8",
         True, True,
         {"q": "(S,C,H,Dh) H/tp", "k_pages": "(P,ps,H*Dh) i8 H/tp",
          "v_pages": "(P,ps,H*Dh) i8 H/tp",
          "k_scales": "(P,ps) f32 replicated",
          "v_scales": "(P,ps) f32 replicated",
          "block_tables": "(S,mp) i32", "chunk_starts": "(S,) i32",
          "n_valid": "(S,) i32"}, "(S,C,H,Dh) H/tp"),
    )
    for name, inner, chunked, quantized, layouts, out_layout in defs:
        q_spec = _TP_Q_PREFILL if chunked else _TP_Q_DECODE
        n_geo = len(layouts) - (5 if quantized else 3)
        specs = (q_spec, _TP_KV_SPEC, _TP_KV_SPEC)
        if quantized:
            specs += (_P(), _P())             # scale rows replicated
        specs += tuple(_P() for _ in range(n_geo))
        pallas_fn, lax_fn = _make_tp_fns(inner, specs)
        sample_fn = (
            (lambda s, _c=chunked: _make_paged_int8_sample(s, chunked=_c))
            if quantized else
            (lambda s, _c=chunked: _make_paged_sample(s, chunked=_c)))
        inner_spec = kernels.get(inner)
        kernels.register(kernels.KernelSpec(
            name=name,
            contract=kernels.KernelContract(
                version=1,
                arg_layouts=layouts,
                out_layout=out_layout,
                donatable=inner_spec.contract.donatable,
                grid=grid,
                collectives=("all_reduce",),
                atol=inner_spec.contract.atol,
                rtol=inner_spec.contract.rtol),
            pallas_fn=pallas_fn,
            lax_fn=lax_fn,
            reference_fn=None,        # parity_fn orchestrates the mesh
            sample_inputs=sample_fn,
            pallas_sites=(),          # reuses the inner kernel's sites
            requires_mesh=True,
            parity_fn=_make_tp_parity_fn(name, inner, sample_fn,
                                         inner_spec.reference_fn,
                                         quantized=quantized),
            donation_probe=functools.partial(
                _tp_donation_probe, chunked=chunked,
                quantized=quantized)))


_register_tp_kernels()
