"""What a layer's kind of attention is, and how its rows are cached.

Decided HERE and nowhere else: :func:`build` reads the program's
:class:`~paddle_tpu.serving.program.ServingSpec` and the cache's geometry
once and returns one kind a layer (layers that are alike share one
object). The page cache asks a kind what the layer's pool entry is; the
engine's two loops ask it where a call's tokens go, have it write the rows
and attend; the host has it count. The kinds call the paged kernels
(:mod:`~paddle_tpu.serving.decode_attention`,
:mod:`~paddle_tpu.serving.sparse_attention`) and import neither the engine
nor a model.

The kinds, and what selects each:

==================  =====================================================
:class:`Paged`      K and V a token, pages of the shared pool under the
                    slot's block table (the default, and the base class)
:class:`PagedInt8`  the same in int8 with a scale a token row
                    (``dtype=int8``)
:class:`Ring`       K and V in a ring of pages a slot, the window's and
                    room for the run of chunks one prefill call may carry
                    (``spec.layer_windows[i]`` is a window)
:class:`Latent`     one latent row a token that every head reads, in two
                    pools (``spec.latent_row``)
:class:`Selecting`  K and V plus index rows, each query attending to its
                    ``select_topk`` best tokens once it sees more
                    (``spec.select_topk``, ``spec.extra_rows``)
:class:`SelectingLatent`  a latent row plus an index row a token, each
                    query's heads reading the ``select_topk`` latent rows
                    it selects (``spec.latent_row`` with the two above)
:class:`State`      NO rows a token: the layer's whole memory is the
                    program's slot state, its ``mixer`` the token mixer;
                    no pool, no page, never asked to place, write or
                    attend (``spec.state_layers[i]``)
==================  =====================================================

A kind's **geometry** is its own: :func:`build` hands each the KV heads of
ITS layers (``spec.layer_kv_heads``: full layers of 4 KV heads beside
window layers of 8 are two kinds with pools of their own widths), the
width of a head's keys and of its values (``spec.head_dim``,
``spec.value_dim``: a K pool of ``heads * 192`` lanes beside a V pool of
``heads * 128``, each stored at its own width, nothing padded), and
whether its layers' softmax carries a learned sink a query head
(``spec.sink_layers``: the logits come from ``attn_in`` with the queries
and go to the paged kernels as one more operand). Every byte the host
counts comes from the kind's two widths.

What a kind answers is the methods of :class:`Paged`: its pool entry;
traced, inside the engine's steps: where a call's tokens go and what the
kernel is handed, the writes, the attention, what its steps count; on the
host: the groups a folding decode takes, its series, and one counting call
a decode round and one a prefill call.

And how long a **run** it can take (``prefill_run``): a lane of a prefill
call is a (slot, chunk) pair, and consecutive lanes may be consecutive
chunks of ONE slot's prompt, because a call writes every lane's rows of a
layer before that layer attends. A plain pool takes any run (lane k + 1
finds lane k's rows under the same table); a ring takes as many chunks as
it has room for beside its window; a kind that selects or reads a latent
row by chunk, or writes a page tile at a time, answers 1 until it is
shown to take more. The engine takes the least over a program's kinds.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.serving import decode_attention as DA
from paddle_tpu.serving import sparse_attention as SA

#: abs-max floor so an all-zero token row gets a harmless tiny scale
#: instead of a division by zero (dequant of its zero int8 row is 0)
KV_SCALE_FLOOR = 1e-8


def quantize_kv(x, reduce_axes: Tuple[int, ...], psum_axis=None):
    """Symmetric per-token int8 quantization of a K/V slab.

    ``x`` carries one K (or V) vector per token over its TRAILING
    ``reduce_axes`` (decode writes ``(S, H*Dh)`` with axes ``(1,)``;
    prefill writes ``(S, C, H*Dh)`` with axes ``(2,)``). Returns
    ``(q int8, scale f32)`` with ``scale = max(|x|) / 127`` per token —
    the row the page pool stores next to the page so dequantization is
    ``q * scale`` inside the attend kernel. Per-token granularity keeps
    incremental page writes append-stable: a new token never forces a
    requantization of rows already stored (a single per-page scalar
    would), which is what lets shared/published int8 pages stay
    bit-stable under prefix sharing and CoW.

    ``psum_axis`` (tensor parallel): inside ``shard_map`` each shard
    holds only its own ``H/tp`` heads of ``x``, so the per-token abs-max
    is completed with a ``pmax`` over the named mesh axis BEFORE the
    scale divides — every shard then quantizes its head slice with the
    all-head scale the tp=1 engine computes (max is exact; deeper layers'
    inputs carry the psum's last-ulp noise, which the rounding absorbs:
    greedy parity is pinned at the token level)."""
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=reduce_axes)
    if psum_axis is not None:
        amax = jax.lax.pmax(amax, psum_axis)
    scale = jnp.maximum(amax, KV_SCALE_FLOOR) / 127.0
    exp = scale.reshape(scale.shape + (1,) * len(reduce_axes))
    q = jnp.clip(jnp.round(xf / exp), -127, 127).astype(jnp.int8)
    return q, scale


@dataclasses.dataclass(frozen=True)
class Geometry:
    """The cache's side of a kind: what :func:`build` is told."""
    num_slots: int
    page_size: int
    num_pages: int
    heads: int                  # heads of K and V a token caches
    head_dim: int               # the width of a head's keys (and queries)
    dtype: object
    tp: int = 1
    impl: str = "auto"          # the engine's ``attn_impl``
    value_dim: Optional[int] = None     # of its values; None: ``head_dim``
    #: the program's query heads where some of its layers' softmax carries
    #: a sink: every kind of such a program counts the query rows it
    #: attends for; 0: none does
    query_heads: int = 0

    @property
    def k_lanes(self) -> int:
        return self.heads * self.head_dim

    @property
    def v_lanes(self) -> int:
        return self.heads * (self.value_dim or self.head_dim)


# -- where tokens fall under the slots' tables (traced) ----------------------

def under_table(block_tables, positions, live, slot_ids, page_size, base):
    """Where tokens at ``positions`` fall under the slots' tables: decode,
    one a slot at ``lengths`` (S,); prefill, a chunk a lane (S, C) -> (the
    page each is written to, 0 where it is not ``live``; its row in the
    page; the table; ``base``, the lengths or the chunks' starts the
    kernel is handed with it). Every kind's placement starts from this
    one."""
    pages = jnp.where(
        live,
        block_tables[slot_ids, jnp.minimum(positions // page_size,
                                           block_tables.shape[1] - 1)],
        0)
    return pages, positions % page_size, block_tables, base


def _write_lane_rows(pool, row, page_idx, off):
    """Tokens into a ``(P, width, page_size)`` pool, whose lanes are
    the tokens of a page: every page a call touches is read, the
    call's tokens placed in their lanes, and the tile written back
    whole. (A scatter along the lane axis makes the chip's compiler
    re-lay the whole pool out and back around it.) ``row`` (S, C,
    width) or (S, width) with one ``page_idx`` / ``off`` a token;
    ``page_idx`` 0 marks a token that is not written. A lane's
    tokens are consecutive positions, so they touch at most
    ``(C - 1) // page_size + 2`` pages, in order."""
    s = row.shape[0]
    width, ps = pool.shape[1], pool.shape[2]
    row = row.reshape(s, -1, width).astype(pool.dtype)      # (S,C,W)
    page_idx, off = page_idx.reshape(s, -1), off.reshape(s, -1)
    c = row.shape[1]
    live = page_idx > 0
    # which of the lane's touched pages a token lands in: it moves
    # on where ``off`` wraps
    nth = jnp.cumsum(jnp.concatenate(
        [jnp.zeros((s, 1), jnp.int32),
         (off[:, 1:] < off[:, :-1]).astype(jnp.int32)], axis=1), axis=1)
    lanes = jnp.arange(ps, dtype=jnp.int32)
    for k in range((c - 1) // ps + 2 if c > 1 else 1):
        here = live & (nth == k)                            # (S,C)
        page = jnp.max(jnp.where(here, page_idx, 0), axis=1)  # (S,)
        put = (here[:, :, None]
               & (off[:, :, None] == lanes)).astype(pool.dtype)  # (S,C,ps)
        tile = jnp.einsum("scw,scp->swp", row, put)     # one-hot: exact
        written = jnp.any(put > 0, axis=1)[:, None, :]      # (S,1,ps)
        pool = pool.at[page].set(
            jnp.where(written, tile, pool[page]))
    return pool


def _write_rows(ent, rows, place, major):
    """Land one call's rows in a float entry, each where ``place`` says
    (one index a token: ``(S,)`` for decode, ``(S, C)`` for a chunk): the
    first ``major`` pools are token-major ``(P, page_size, lanes)``, the
    rest ``(P, width, page_size)``, tokens along the lanes, written a
    page tile at a time."""
    pages, off = place[0], place[1]
    out = [pool.at[pages, off].set(row.astype(pool.dtype))
           for pool, row in zip(ent[:major], rows[:major])]
    for pool, row in zip(ent[major:], rows[major:]):
        out.append(_write_lane_rows(pool, row, pages, off))
    return tuple(out)


# -- who shares what in a decode block (host) --------------------------------

def _rows_held_twice(tables, lens, page_size, num_pages):
    """The live rows of slots with block tables ``tables`` and
    ``lens`` tokens that a second of them holds too: a page some slot
    holds whole counts whole, however many hold it; the page a slot
    is filling counts as far as the longest of its holders goes."""
    ps = page_size
    whole = lens // ps
    held = np.bincount(
        tables[np.arange(tables.shape[1])[None, :] < whole[:, None]],
        minlength=num_pages) > 0
    filling = tables[np.arange(len(lens)),
                     np.minimum(whole, tables.shape[1] - 1)]
    part = (lens > whole * ps) & ~held[filling]
    ids, rows = filling[part], (lens - whole * ps)[part]
    order = np.argsort(ids, kind="stable")
    distinct = ps * int(held.sum()) + (int(np.maximum.reduceat(
        rows[order], np.flatnonzero(np.diff(ids[order], prepend=-1))
    ).sum()) if len(ids) else 0)
    return int(lens.sum()) - distinct


class _Groups:
    """Who shares what in a decode block of a kind whose decode folds the
    pages that several slots' tables open with, from the decoding slots'
    tables alone. Shared pages are whole and read-only and a slot grows in
    pages of its own, so it holds for every token step of the block, and
    from block to block while the decoding slots and their tables stay:
    ``kept`` is (slots, their table rows, the three arrays
    :func:`decode_attention.decode_groups` makes, on the device; the live
    rows a token step that a second decoding slot holds too; the rows the
    groups' walks do not copy twice)."""

    def __init__(self, geo: Geometry, counts_twice: bool):
        self.geo, self.counts_twice = geo, counts_twice
        self.kept = None

    def of(self, block_tables, lengths, dslots):
        """-> (the arrays, rows held twice, rows spared)."""
        ps = self.geo.page_size
        tables = block_tables[dslots]
        kept = self.kept
        if kept is not None and np.array_equal(kept[0], dslots) \
                and np.array_equal(kept[1], tables):
            return kept[2:]
        groups = DA.decode_groups(block_tables, lengths, dslots, ps)
        spared = int(((groups[0] >= 0).sum(1) - 1).clip(0)
                     @ groups[1].astype(np.int64)) * ps
        twice = _rows_held_twice(
            tables, lengths[dslots], ps,
            self.geo.num_pages) if self.counts_twice else 0
        self.kept = (np.asarray(dslots).copy(), tables,
                     tuple(jnp.asarray(a) for a in groups), twice, spared)
        return self.kept[2:]


def _add_attr(span, name: str, n: int):
    """``n`` more on the span's attribute ``name`` (several kinds of one
    program feed the same round's or call's span)."""
    if span is not None:
        span.set_attrs(**{name: span.attrs.get(name, 0) + n})


def _run_heads(starts, heads):
    """The starts of the lanes that open their slot's run."""
    return starts if heads is None else np.asarray(starts)[heads]


def _attended(lens, n):
    """Tokens ``n`` decode token steps attend over, a layer: step j of a
    slot holding L tokens attends over L + j + 1."""
    return n * int(lens.sum()) + len(lens) * n * (n + 1) // 2


# -- the kinds ---------------------------------------------------------------

class Kind:
    """What the cache and the engine ask of every layer's kind, whether
    it caches rows (:class:`Paged` and its subclasses) or none
    (:class:`State`): ``layers`` layers of one program in one cache of
    geometry ``geo``. ``label``: the kind's name in the series that split
    a pool by kind, None where the program's layers are all of one kind.
    ``state``: the layers keep the program's slot state, where it declares
    one (False for the attention layers of a program that names its state
    layers). ``pools``: ``(shape, dtype, the axes a tp mesh shards as a
    PartitionSpec's entries)`` a pool array of a layer's entry, before its
    slot state."""

    #: the layer caches rows: the engine has it place, write and attend
    paged: bool
    quantized = False       # pages carry scale rows
    by_slot = False         # a prefill lane's placement needs its slot
    #: consecutive chunks of one slot a prefill call may carry as lanes of
    #: their own; None: as many as the call has lanes
    prefill_run: Optional[int] = None
    #: its step programs are compiled at ONE table width, a slot's whole
    #: table, and not at every power of two below it (what a narrow table
    #: saves its calls is not worth a compile of both programs a width)
    whole_table = False
    stat_names: Tuple[str, ...] = ()    # counts its steps add on the device
    pools: tuple = ()
    page_bytes = 0          # bytes one page id commits in a layer
    slot_bytes = 0          # a layer's bytes a slot holds at any length

    def __init__(self, geo: Geometry, layers: int,
                 label: Optional[str] = None, state: bool = True):
        self.geo, self.layers, self.label = geo, layers, label
        self.state = state

    def check(self, ent, lengths):
        """The kind's part of the cache's self-check (tests): ``ent``,
        the layer's pool arrays, are what the kind lays out."""
        assert [(a.shape, a.dtype) for a in ent] == [
            (shape, jnp.dtype(dtype)) for shape, dtype, _ in self.pools], \
            f"a layer's entry is not what {type(self).__name__} lays out"

    def step_counts(self, context, selected):
        """The values of ``stat_names`` for one layer-call."""
        return ()

    def copy_page(self, ent, src, dst):
        """Page ``src`` of the entry duplicated into ``dst`` (the
        copy-on-write of a borrowed tail page)."""
        return tuple(a.at[dst].set(a[src]) for a in ent)

    # -- host --

    def decode_groups(self, block_tables, lengths, dslots) -> tuple:
        """``(groups,)`` for the decode step where the kind's decode
        folds the pages that several tables open with; ``()``: it walks
        every slot alone and its step takes none."""
        return ()

    def bind(self, reg):
        """Bind the kind's series in ``reg``, once."""

    def count_decode(self, span, block_tables, lengths, dslots, keeps,
                     n: int, width: int):
        """One decode round of ``n`` token steps at gather width ``width``
        over ``dslots``, from the tables and lengths BEFORE it (``keeps``:
        the tokens each slot keeps). Feeds the kind's series and the
        span's attribute; returns its share of
        ``serving_decode_kv_bytes_total`` (live, a row of its table)."""
        return 0, 0

    def count_prefill(self, span, starts, ns, heads=None):
        """One prefill call: lanes at ``starts`` computing ``ns`` tokens.
        ``heads``: which lanes open their slot's run (a mask; None: every
        lane is a slot's only one)."""


class Paged(Kind):
    """One kind of attention layer; this one is the plain kind, and the
    others subclass it for what they answer differently. K and V a token,
    each ``(num_pages, page_size, heads * head_dim)`` (a token's heads
    folded head-major into the lanes), pages of the shared pool mapped by
    the slot's block table; then one pool ``(num_pages, width,
    page_size)`` for each of the program's ``extra_rows`` (tokens along
    the lanes), allocated, shared, copied on write and freed with its
    page (K and V's sharded axis: the folded head axis). :class:`Latent`
    always names itself (``label``). ``sink``: the layers' softmax carries
    a learned logit a query head, which ``attn_in`` hands over as
    ``index``."""

    paged = True
    groups = None           # a :class:`_Groups` where its decode folds
    _c_resident = None      # bound where the program's pool is split by kind
    _c_rows = _c_sink_rows = None   # bound where the program has sinks

    def __init__(self, geo: Geometry, layers: int,
                 label: Optional[str] = None, extra_rows=(),
                 sink: bool = False, state: bool = True):
        super().__init__(geo, layers, label, state)
        self.sink = sink
        #: K and V of one token in one layer, each at its own width
        self.token_bytes = (geo.k_lanes + geo.v_lanes) \
            * np.dtype(geo.dtype).itemsize
        self.pools = self._kv_pools(geo.num_pages, (None, None, "tp")) \
            + tuple(((geo.num_pages, width, geo.page_size), geo.dtype, ())
                    for _name, width in extra_rows)
        if extra_rows:
            # a pool with the tokens along its lanes is written a page
            # tile at a time: two lanes of one slot in one page would
            # each write the tile back whole
            self.prefill_run = 1

    def _kv_pools(self, pages: int, axes, dtype=None):
        """The K pool and the V pool of ``pages`` pages: a token's heads
        folded head-major into the lanes, each pool its own width."""
        geo = self.geo
        return tuple(((pages, geo.page_size, lanes), dtype or geo.dtype, axes)
                     for lanes in (geo.k_lanes, geo.v_lanes))

    def _sinks(self, index) -> dict:
        """The kernels' ``sinks=`` where the layers' softmax has one."""
        return {"sinks": index} if self.sink else {}

    # -- the pool entry --

    @property
    def row_bytes(self) -> int:
        """Bytes of one page (its leading index) across the entry."""
        return sum(int(np.prod(shape[1:])) * np.dtype(dtype).itemsize
                   for shape, dtype, _ in self.pools)

    @property
    def page_bytes(self) -> int:
        """Bytes one page id of the shared pool commits in a layer."""
        return self.row_bytes

    # -- traced --

    def place_decode(self, under, writable, slot_ids):
        """Where one decode token a slot goes and what the kernel is
        handed, from :func:`under_table`'s ``under``."""
        return under

    def place_prefill(self, under, positions, valid, lane_rows):
        """The same for a chunk a lane;
        ``lane_rows``: the lanes' slots + 1 where a kind is ``by_slot``."""
        return under

    def write(self, ent, rows, place):
        """Land the rows ``attn_in`` wants cached in the entry ``ent``
        where ``place`` says; returns the entry."""
        return _write_rows(ent, rows, place, 2)

    def attend_decode(self, q, ent, place, index, groups):
        """One decode token a slot, ``q`` (S, H, Dh), over ``ent`` as just
        written -> (heads, tokens attended a slot (S,))."""
        lengths = place[3] + 1
        return DA.ragged_paged_decode_attention(
            q, ent[0], ent[1], place[2], lengths,
            impl=self.geo.impl, **self._sinks(index)), lengths

    def attend_prefill(self, q, ent, place, n_valid, index):
        """A chunk of queries a lane, ``q`` (S, C, H, Dh), causally over
        ``ent`` as just written -> heads."""
        return DA.ragged_paged_prefill_attention(
            q, ent[0], ent[1], place[2], place[3], n_valid,
            impl=self.geo.impl, **self._sinks(index))

    def attends_prefill(self, seen, block_tables):
        """Tokens each query of a chunk attends to, of the ``seen`` (S, C)
        it can see, in a bucket as wide as ``block_tables``."""
        return seen

    # -- host --

    def decode_groups(self, block_tables, lengths, dslots) -> tuple:
        if self.groups is None:
            return ()
        return (self.groups.of(block_tables, lengths, dslots)[0],)

    def bind(self, reg):
        if self.label is not None:
            self._bind_by_kind(reg)
        if self.geo.query_heads:
            self._bind_rows(reg)

    def _bind_by_kind(self, reg):
        self._c_resident = reg.counter(
            "serving_kv_resident_bytes_total",
            "K/V bytes the slots of a decode round or prefill call hold "
            "when it is dispatched, whole pages, by layer kind: a full "
            "layer every page of the slot's tokens, a window layer its "
            "ring's pages at most").child(layers=self.label)
        self._c_pairs = reg.counter(
            "serving_prefill_attn_pairs_total",
            "(query token, key token) pairs x layers the real tokens of "
            "the prefill calls score, by layer kind: causal, and inside "
            "the window on a window layer").child(layers=self.label)
        self._c_prefill_rows = reg.counter(
            "serving_prefill_kv_rows_total",
            "cached K/V rows x layers the prefill calls have to read "
            "once, by layer kind: a lane's context and chunk on a full "
            "layer, what the chunk's windows reach on a window layer"
            ).child(layers=self.label)
        self._set_pool_bytes(reg)

    def _bind_rows(self, reg):
        """The series of a program some of whose layers' softmax carries
        a sink: the query rows every kind attends for, and of those the
        rows of the layers with one."""
        self._c_rows = reg.counter(
            "serving_attn_rows_total",
            "query rows x heads x layers attended for, prefill tokens and "
            "decode token steps (a program with sink layers only)").child()
        if self.sink:
            self._c_sink_rows = reg.counter(
                "serving_attn_sink_rows_total",
                "query rows x heads x layers whose softmax carried a "
                "learned sink: prefill tokens and decode token steps of "
                "the layers that have one").child()

    def _count_rows(self, span, tokens: int):
        """``tokens`` query tokens attended for in each of the layers."""
        if self._c_rows is None:
            return
        rows = tokens * self.geo.query_heads * self.layers
        self._c_rows.inc(rows)
        if self._c_sink_rows is not None:
            self._c_sink_rows.inc(rows)
            _add_attr(span, "sink_rows", rows)

    def _seen_prefill(self, starts, ns):
        """-> ((query, key) pairs, K/V rows read) of one prefill call a
        layer: chunk token j of a lane at ``start`` sees its context and
        the chunk's tokens up to itself."""
        return (int((starts * ns + ns * (ns + 1) // 2).sum()),
                int((starts + ns)[ns > 0].sum()))

    def _count_prefill_attention(self, span, starts, ns):
        if self._c_rows is None and self._c_resident is None:
            return
        starts, ns = (np.asarray(a, np.int64) for a in (starts, ns))
        self._count_rows(span, int(ns.sum()))
        if self._c_resident is not None:
            pairs, rows = self._seen_prefill(starts, ns)
            self._c_pairs.inc(pairs * self.layers)
            self._c_prefill_rows.inc(rows * self.layers)
            _add_attr(span, "attn_pairs", pairs * self.layers)

    def _set_pool_bytes(self, reg):
        reg.gauge("serving_kv_pool_bytes", "bytes of the K/V pools by layer "
                  "kind, null pages included").set(self.layers * sum(
                      int(np.prod(shape)) * np.dtype(dtype).itemsize
                      for shape, dtype, _ in self.pools), layers=self.label)

    def _count_resident(self, before):
        """What the slots hold going in (``before`` tokens each, one entry
        a slot), where the program's pool is split by kind."""
        if self._c_resident is not None:
            pages = -(-np.asarray(before, np.int64) // self.geo.page_size)
            self._c_resident.inc(
                int(self._held(pages).sum()) * self.geo.page_size
                * self.token_bytes * self.layers)

    def _held(self, pages):
        return pages

    def _kv_bytes(self, live: int, width: int):
        each = self.token_bytes * self.layers
        return live * each, width * each

    def count_decode(self, span, block_tables, lengths, dslots, keeps,
                     n: int, width: int):
        lens = lengths[dslots]
        live = _attended(lens, n)
        self._count_own(span, block_tables, lengths, dslots, live, n, width)
        self._count_resident(lens)
        self._count_rows(span, n * len(lens))
        return self._kv_bytes(live, width)

    def _count_own(self, span, block_tables, lengths, dslots, live, n,
                   width):
        """What the kind alone counts of a decode round attending over
        ``live`` tokens a layer."""

    def count_prefill(self, span, starts, ns, heads=None):
        # what a slot holds going in is counted once a slot, at its first
        # lane
        self._count_resident(_run_heads(starts, heads))
        self._count_prefill_attention(span, starts, ns)


class PagedInt8(Paged):
    """K and V in int8 with one symmetric abs-max scale a token row
    (:func:`quantize_kv`): ``(k, v, k_scales, v_scales)``, the scales fp32
    ``(num_pages, page_size)``, page-major so that scales travel WITH
    their pages wherever pages go. Dequantization happens inside the
    dequant-attend kernels. Under tp K and V are sharded as
    :class:`Paged`'s, the scales replicated (a token's scale is over ALL
    heads: the abs-max is completed over the shards)."""

    quantized = True
    prefill_run = 1         # not shown to take a run yet

    def __init__(self, geo, layers):
        super().__init__(geo, layers)
        sc = ((geo.num_pages, geo.page_size), jnp.float32, ())
        self.pools = self._kv_pools(geo.num_pages, (None, None, "tp"),
                                    jnp.int8) + (sc, sc)
        self.psum_axis = "tp" if geo.tp > 1 else None

    def write(self, ent, rows, place):
        kp, vp, ksc, vsc = ent
        k_tok, v_tok = rows
        pages, off = place[0], place[1]
        ax = (k_tok.ndim - 1,)
        kq, k_s = quantize_kv(k_tok, ax, psum_axis=self.psum_axis)
        vq, v_s = quantize_kv(v_tok, ax, psum_axis=self.psum_axis)
        return (kp.at[pages, off].set(kq), vp.at[pages, off].set(vq),
                ksc.at[pages, off].set(k_s), vsc.at[pages, off].set(v_s))

    def attend_decode(self, q, ent, place, index, groups):
        lengths = place[3] + 1
        return DA.ragged_paged_decode_int8_attention(
            q, *ent, place[2], lengths, impl=self.geo.impl), lengths

    def attend_prefill(self, q, ent, place, n_valid, index):
        return DA.ragged_paged_prefill_int8_attention(
            q, *ent, place[2], place[3], n_valid, impl=self.geo.impl)


class Ring(Paged):
    """K and V of a layer whose queries attend to the last ``window``
    tokens (themselves counted): a **ring** of ``ring_pages`` =
    ``pages_for(window) + room`` pages a slot, in a pool of its own,
    ``(num_slots * ring + 1, page_size, lanes)`` (page 0 the null page).
    Token ``t`` of slot ``s`` lives in ring page ``1 + s * ring + (t //
    page_size) % ring``, row ``t % page_size``, so the page a slot writes
    next is the one whose tokens have all fallen behind the window
    (**recycled**), whatever the slot's length. No table, no allocation,
    no free: the ring is the slot's.

    ``room`` is the length of a run (``prefill_run``): a call writes
    every lane's rows before the layer attends, so a slot that gives the
    call ``room`` consecutive chunks of at most a page each (:func:`build`
    holds ``prefill_chunk`` to that) writes, and its queries read, a span
    of fewer than ``window + room * page_size`` tokens: under
    ``ring * page_size``, so no token of it lands on a row another
    query of the same call still reads. A run one chunk longer could
    (``check_run`` says so by name). Each lane still attends through a
    table of its own window's span, ``pages_for(window) + 2`` columns
    from its own first page, and a decode token through
    ``pages_for(window) + 1``: the room widens the pool, not a table.
    Admission reckons without the ring (``page_bytes`` 0). Never shared,
    copied on write, published, spilled or shipped."""

    by_slot = True

    def __init__(self, geo, layers, window: int, sink: bool = False,
                 room: int = 1):
        super().__init__(geo, layers, "window", sink=sink)
        self.window = window
        #: chunks of one slot a call may carry: pages of room in the ring
        self.prefill_run = room
        #: the pages a window spans where it ends with its last page
        self.window_pages = -(-window // geo.page_size)
        #: the window's own pages and those a call's run writes
        self.ring_pages = self.window_pages + room
        self.pools = self._kv_pools(
            geo.num_slots * self.ring_pages + 1, ())

    page_bytes = 0

    @property
    def slot_bytes(self) -> int:
        return self.ring_pages * self.row_bytes

    def page_of(self, slot: int, seq_page: int) -> int:
        """The ring page that holds page ``seq_page`` of ``slot``'s
        sequence (while it is held)."""
        return 1 + slot * self.ring_pages + seq_page % self.ring_pages

    def recycled(self, before, after) -> int:
        """Ring pages written over, all the kind's layers, as slots
        advance from ``before`` to ``after`` tokens (arrays, one entry a
        slot): a page is recycled when the slot enters a page of its
        sequence past the ring's first lap."""
        before, after = (-(-np.asarray(a, np.int64) // self.geo.page_size)
                         for a in (before, after))
        return self.layers * int(
            (np.maximum(after - self.ring_pages, 0)
             - np.maximum(before - self.ring_pages, 0)).sum())

    def check(self, ent, lengths):
        super().check(ent, lengths)
        ps, ring = self.geo.page_size, self.ring_pages
        for slot, length in enumerate(int(n) for n in lengths):
            # the pages that hold the slot's window are distinct pages of
            # the slot's own ring
            mine = [self.page_of(slot, p) for p in range(
                max(length - self.window, 0) // ps,
                max(length - 1, 0) // ps + 1)]
            assert len(set(mine)) == len(mine) and all(
                slot * ring < p <= (slot + 1) * ring for p in mine), \
                "a window's pages collide or leave the slot's ring"

    def check_run(self, start: int, tokens: int):
        """A slot at ``start`` tokens may give one call a run of
        ``tokens`` more: what the run writes and what its queries read,
        ``[start - window + 1, start + tokens)``, fits the ring without
        a token landing on another's row. Raises ValueError by name."""
        span = start + tokens - max(start - self.window + 1, 0)
        if span > self.ring_pages * self.geo.page_size:
            raise ValueError(
                f"a run of {tokens} tokens at {start} spans {span} tokens "
                f"with its window of {self.window}: more than the ring's "
                f"{self.ring_pages} pages of {self.geo.page_size} "
                f"(prefill_run={self.prefill_run}) hold at once")

    def _pages(self, slots, first_page, width):
        """-> (pages (S,) or (S, C) of the tokens of sequence page
        ``first_page`` (same shape), the table (S, ``width``) of the pages
        ``first_page[s] ..`` as the paged kernels take one)."""
        ring = self.ring_pages
        base = 1 + slots * ring

        def pages(seq_page):
            return base.reshape(base.shape + (1,) * (seq_page.ndim - 1)) \
                + seq_page % ring
        return pages, pages(first_page[:, None]
                            + jnp.arange(width, dtype=jnp.int32))

    def place_decode(self, under, writable, slot_ids):
        """-> (the ring page the token is written to, its row, the table
        of the pages its window spans, the tokens in them up to and with
        this one, the slots' lengths)."""
        _, off, _, lengths = under
        ps = self.geo.page_size
        first = jnp.maximum(lengths + 1 - self.window, 0) // ps
        pages, table = self._pages(slot_ids, first, self.window_pages + 1)
        return (jnp.where(writable, pages(lengths // ps), 0), off, table,
                lengths + 1 - first * ps, lengths)

    def place_prefill(self, under, positions, valid, lane_rows):
        """-> (the ring pages the chunk's tokens are written to (S, C),
        their rows, the table of the pages the chunk's windows span, the
        chunk's start in them). A chunk of at most a page of tokens spans
        its window's pages, one more and, where it starts inside a page,
        one more again: ``window_pages + 2`` columns from the lane's own
        first page, whatever the ring's room (a lane further on in its
        slot's run starts further on). Where the first and the last of
        them are one ring page on two laps, the stale rows of either lap
        lie outside every query's window or past it. A lane's ring is
        its slot's: pool row less one (a pad lane writes nothing and
        attends to nothing)."""
        _, off, _, starts = under
        slots = jnp.maximum(lane_rows - 1, 0)
        ps = self.geo.page_size
        first = jnp.maximum(starts - self.window + 1, 0) // ps
        pages, table = self._pages(slots, first, self.window_pages + 2)
        return (jnp.where(valid, pages(positions // ps), 0), off, table,
                starts - first * ps)

    def attend_decode(self, q, ent, place, index, groups):
        att = DA.ragged_paged_decode_attention(
            q, ent[0], ent[1], place[2], place[3], impl=self.geo.impl,
            window=self.window, **self._sinks(index))
        return att, jnp.minimum(place[4] + 1, self.window)

    def attend_prefill(self, q, ent, place, n_valid, index):
        return DA.ragged_paged_prefill_attention(
            q, ent[0], ent[1], place[2], place[3], n_valid,
            impl=self.geo.impl, window=self.window, **self._sinks(index))

    def copy_page(self, ent, src, dst):
        return ent              # no page of a ring is ever shared

    def bind(self, reg):
        super().bind(reg)
        self._c_recycled = reg.counter(
            "serving_window_pages_recycled_total",
            "ring pages of window layers written over as slots advanced "
            "past them (pages x window layers)").child()

    def _held(self, pages):
        return np.minimum(pages, self.ring_pages)

    def _count(self, span, before, after, held=None):
        """Slots advancing from ``before`` to ``after`` tokens (a prefill
        lane each, where a slot gives a call a run; ``held``: what each
        slot holds going in, once a slot)."""
        self._count_resident(before if held is None else held)
        recycled = self.recycled(before, after)
        self._c_recycled.inc(recycled)
        _add_attr(span, "window_pages", recycled)

    def count_decode(self, span, block_tables, lengths, dslots, keeps,
                     n, width):
        # a window layer's read is its window's: token step j of a slot
        # holding L tokens attends over min(L + j + 1, window), from a
        # table as wide as its window's span
        lens = lengths[dslots]
        self._count(span, lens, lens + keeps)
        self._count_rows(span, n * len(lens))
        return self._kv_bytes(
            int(sum(np.minimum(lens + j + 1, self.window).sum()
                    for j in range(n))), self.window_pages + 1)

    def _seen_prefill(self, starts, ns):
        # chunk token j of a lane at ``start`` sees the last ``window``
        # tokens up to itself; the chunk reads from its first window on
        w = self.window
        short = np.clip(w - 1 - starts, 0, ns)  # queries that see < window
        pairs = short * starts + short * (short + 1) // 2 + (ns - short) * w
        return (int(pairs.sum()), int(
            (starts + ns - np.maximum(starts - w + 1, 0))[ns > 0].sum()))

    def count_prefill(self, span, starts, ns, heads=None):
        self._count(span, starts, starts + ns, _run_heads(starts, heads))
        self._count_prefill_attention(span, starts, ns)


class Latent(Paged):
    """One row a token that every head reads (multi-head latent attention
    in its absorbed form), ``(latent_dim, rope_dim)`` wide: the entry is
    ``(c_pages (num_pages, page_size, latent_dim), r_pages (num_pages,
    rope_dim, page_size))``, the latent, token-major as K is, and the
    shared rotary key with the tokens along the lanes. There is no V pool
    (attention sums the latents themselves): whole tiles of both arrays
    and nothing padded. Both are page pools like any other: allocated,
    refcounted, published, copied on write and freed under one page id.
    Its decode reads the pages that a group of slots' tables open with
    once for the group. Not quantized, sharded, spilled or shipped yet."""

    prefill_run = 1         # the rotary pool is written a page tile a lane

    def __init__(self, geo, layers, latent_row):
        super().__init__(geo, layers, "latent")
        latent, rope = latent_row
        self.pools = (
            ((geo.num_pages, geo.page_size, latent), geo.dtype, ()),
            ((geo.num_pages, rope, geo.page_size), geo.dtype, ()))
        # a latent row is cached once: its values are a part of it
        self.token_bytes //= 2
        self.groups = _Groups(geo, counts_twice=True)

    def write(self, ent, rows, place):
        return _write_rows(ent, rows, place, 1)

    def attend_decode(self, q, ent, place, index, groups):
        lengths = place[3] + 1
        return DA.latent_paged_decode_attention(
            q, ent[0], ent[1], place[2], lengths, groups,
            impl=self.geo.impl), lengths

    def attend_prefill(self, q, ent, place, n_valid, index):
        return DA.latent_paged_prefill_attention(
            q, ent[0], ent[1], place[2], place[3], n_valid,
            impl=self.geo.impl)

    def bind(self, reg):
        rows = reg.counter(
            "serving_latent_rows_read_total",
            "cached latent rows x layers the latent kernels HAD to read, "
            "the least any kernel could: a decode token step each "
            "DISTINCT live row of the decoding slots once (a page that "
            "several slots' tables hold counts once), a prefill call "
            "each lane's context and chunk")
        fetched = reg.counter(
            "serving_latent_rows_fetched_total",
            "cached latent rows x layers the latent kernels' walks "
            "copied: a decode token step a group's shared rows once a "
            "group and every slot's own rows, a prefill call what it "
            "has to read (a selecting program: the rows every chunk "
            "token sees, the whole blocks of pages under the first of "
            "each eight tokens of a lane once for the eight)")
        pairs = reg.counter(
            "serving_latent_pairs_total",
            "(query token, cached row) pairs x layers the latent kernels "
            "scored, every head each: a decode token step one query a "
            "slot (equal to the rows), a prefill call each chunk token "
            "against the rows up to its own")
        self._c = {ph: tuple(c.child(phase=ph)
                             for c in (rows, pairs, fetched))
                   for ph in ("decode", "prefill")}
        self._set_pool_bytes(reg)

    def _count(self, span, phase, rows: int, pairs: int, fetched: int):
        """One round's or call's latent rows, pairs and rows fetched
        (each of ONE layer), and the span's ``latent_rows``."""
        for child, n in zip(self._c[phase], (rows, pairs, fetched)):
            child.inc(n * self.layers)
        if span is not None:
            span.set_attrs(latent_rows=rows * self.layers)

    def _count_own(self, span, block_tables, lengths, dslots, live, n,
                   width):
        _, twice, spared = self.groups.of(block_tables, lengths, dslots)
        self._count(span, "decode", live - n * twice, live,
                    live - n * spared)

    def count_prefill(self, span, starts, ns, heads=None):
        # chunk token j of a lane sees its context and the chunk's tokens
        # up to itself
        rows = int(starts.sum() + ns.sum())
        self._count(span, "prefill", rows,
                    int((starts * ns + ns * (ns + 1) // 2).sum()), rows)


class Selecting(Paged):
    """K and V as :class:`Paged`'s plus the index rows the program caches
    beside them (``extra_rows``), each query attending to the ``topk``
    cached tokens its index scores best. Whether a call selects is a
    static fact of its bucket: up to ``topk`` cached tokens every query
    attends to all it sees, so a table no wider than that takes the dense
    kernels. A decode that selects walks whole pages under the selection
    and reads the pages that a group of slots' tables open with once for
    the group."""

    stat_names = ("attn_context_tokens", "attn_selected_tokens")
    prefill_run = 1         # selects by chunk; index rows a page tile a lane

    def __init__(self, geo, layers, label, extra_rows, topk: int):
        super().__init__(geo, layers, label, extra_rows)
        self.topk = topk
        self.groups = _Groups(geo, counts_twice=False)

    def _selects(self, width: int) -> bool:
        return width * self.geo.page_size > self.topk

    def attend_decode(self, q, ent, place, index, groups):
        if self._selects(place[2].shape[1]):
            return SA.indexed_decode_attention(
                q, *ent, place[2], place[3] + 1, index[0][:, 0],
                index[1][:, 0], self.topk, groups=groups,
                impl=self.geo.impl)
        return super().attend_decode(q, ent, place, index, groups)

    def attend_prefill(self, q, ent, place, n_valid, index):
        if self._selects(place[2].shape[1]):
            return SA.indexed_prefill_attention(
                q, *ent, place[2], place[3], n_valid, index[0], index[1],
                self.topk, impl=self.geo.impl)
        return super().attend_prefill(q, ent, place, n_valid, index)

    def attends_prefill(self, seen, block_tables):
        return jnp.minimum(seen, self.topk) \
            if self._selects(block_tables.shape[1]) else seen

    def step_counts(self, context, selected):
        return context, selected

    def bind(self, reg):
        super().bind(reg)
        self._c_fetched = reg.counter(
            "serving_sparse_rows_fetched_total",
            "cached K/V rows x layers the sparse decode's walks copied, a "
            "token step of a bucket that selects: a group's shared rows "
            "once a group, every slot's own rows once").child()
        self._c_held = reg.counter(
            "serving_sparse_rows_held_total",
            "live cached K/V rows x layers of the decoding slots, a slot "
            "at a time, a token step of a bucket that selects: what the "
            "walks copy where every slot is walked alone").child()

    def _count_own(self, span, block_tables, lengths, dslots, live, n,
                   width):
        if self._selects(width):
            _, _, spared = self.groups.of(block_tables, lengths, dslots)
            self._c_fetched.inc((live - n * spared) * self.layers)
            self._c_held.inc(live * self.layers)


class SelectingLatent(Latent):
    """A latent row a token that every head reads AND an index row beside
    it, each query attending to the ``topk`` cached tokens its index
    scores best (DeepSeek-V3.2's selection over multi-head latent
    attention). The entry is three pools under one page id, allocated,
    published, borrowed, copied on write and freed together: ``(c_pages
    (num_pages, page_size, latent_dim), r_pages (num_pages, page_size,
    rope_lanes), ik_pages (num_pages, index_dim, page_size))``. The
    latent and the rotary key are BOTH token-major, because the attention
    folds the selected tokens' rows only (a row read by 128 heads costs
    as much under a mask as selected): both phases take the selection as
    a mask and compact the selected rows out of whole pages on the chip
    (``sparse_attention.sparse_latent_decode`` and, a chunk token a slot,
    ``sparse_latent_prefill``), and the rotary key lies in the first
    lanes of a row of whole lane tiles (``rope_lanes``: 128 for a key of
    64; a token-major pool of 64 lanes the chip's compiler keeps
    page-minor and re-lays whole); the index keys lie tokens along the
    lanes, as :class:`Selecting`'s. One path for every bucket: a table
    of at most ``topk`` tokens selects all a query sees, through the
    same kernel. Its decode reads the pages that a group of slots'
    tables open with once for the group, as :class:`Selecting`'s does,
    and its prefill the pages under a lane's chunk once for eight of the
    chunk's tokens (the indexer's walk of a slot's keys does not: it
    waits for its products, not for their bytes)."""

    stat_names = Selecting.stat_names
    #: a call attends to ``topk`` rows whatever the table's width and the
    #: indexer skips the pages past a lane's tokens: a narrow table saves
    #: the selection's pass over the scores and nothing else, a few
    #: microseconds a query row (PERF.md section 6, PR 55)
    whole_table = True

    def __init__(self, geo, layers, latent_row, extra_rows, topk: int):
        super().__init__(geo, layers, latent_row)
        latent, rope = latent_row
        (_name, index_dim), = extra_rows
        self.topk = topk
        self.groups = _Groups(geo, counts_twice=False)
        self.pools = (
            ((geo.num_pages, geo.page_size, latent), geo.dtype, ()),
            ((geo.num_pages, geo.page_size, rope + -rope % 128), geo.dtype,
             ()),
            ((geo.num_pages, index_dim, geo.page_size), geo.dtype, ()))
        #: what a token caches a layer, the rotary key's padding counted
        self.token_bytes = self.row_bytes // geo.page_size

    _selects = Selecting._selects

    def write(self, ent, rows, place):
        c, k_rope, k_idx = rows
        pad = ent[1].shape[-1] - k_rope.shape[-1]
        k_rope = jnp.pad(k_rope, ((0, 0),) * (k_rope.ndim - 1) + ((0, pad),))
        return _write_rows(ent, (c, k_rope, k_idx), place, 2)

    def attend_decode(self, q, ent, place, index, groups):
        return SA.latent_indexed_decode_attention(
            q, *ent, place[2], place[3] + 1, index[0][:, 0], index[1][:, 0],
            self.topk, groups=groups, impl=self.geo.impl)

    def attend_prefill(self, q, ent, place, n_valid, index):
        return SA.latent_indexed_prefill_attention(
            q, *ent, place[2], place[3], n_valid, index[0], index[1],
            self.topk, impl=self.geo.impl)

    attends_prefill = Selecting.attends_prefill
    step_counts = Selecting.step_counts

    def bind(self, reg):
        super().bind(reg)
        held = reg.counter(
            "serving_latent_rows_held_total",
            "live cached latent rows x layers of the queries attended for, "
            "a query at a time: a decode token step every live row of "
            "every decoding slot, a prefill call what each chunk token "
            "sees (a selecting latent program only: what its kernels "
            "would read without the selection)")
        self._c_held = {ph: held.child(phase=ph)
                        for ph in ("decode", "prefill")}
        self._c_index = tuple(reg.counter(name, text).child() for name, text
                              in (
            ("serving_index_rows_scored_total",
             "cached index-key rows x layers the indexer HAD to score, a "
             "call of a bucket that selects: a decode token step every "
             "live row of every decoding slot (each slot's query scores "
             "them all), a prefill call each lane's context and chunk"),
            ("serving_index_rows_fetched_total",
             "cached index-key rows x layers the indexer's walks copied: "
             "a slot or lane at a time, so what it has to score (a walk "
             "that read a document's pages once for its slots would copy "
             "fewer)")))

    def _count_index(self, rows: int):
        for child in self._c_index:
            child.inc(rows * self.layers)

    def _count_own(self, span, block_tables, lengths, dslots, live, n,
                   width):
        # token step j of a slot holding L tokens reads and scores the
        # min(L + j + 1, topk) rows it selects; the walks copy every live
        # row, a group's shared ones once a group
        lens = lengths[dslots]
        rows = int(sum(np.minimum(lens + j + 1, self.topk).sum()
                       for j in range(n)))
        _, _, spared = self.groups.of(block_tables, lengths, dslots)
        self._count(span, "decode", rows, rows, live - n * spared)
        self._c_held["decode"].inc(live * self.layers)
        if self._selects(width):
            self._count_index(live)

    def count_prefill(self, span, starts, ns, heads=None):
        # chunk token j of a lane sees start + j + 1 rows and reads the
        # topk it selects of them; the walks copy every row it sees, the
        # whole blocks of pages under the first token of its group of
        # ``DECODE_GROUP`` once for the group
        starts, ns = (np.asarray(a, np.int64) for a in (starts, ns))
        j = np.arange(int(ns.max()) if len(ns) else 0)[None, :]
        live = j < ns[:, None]
        seen = starts[:, None] + j + 1
        rows = int(np.where(live, np.minimum(seen, self.topk), 0).sum())
        held = int(np.where(live, seen, 0).sum())
        block = DA.GROUP_SHARED_PAGES * self.geo.page_size
        first = j % DA.DECODE_GROUP == 0
        shared = (starts[:, None] + j - j % DA.DECODE_GROUP + 1) \
            // block * block
        self._count(span, "prefill", rows, rows, held - int(
            np.where(live & ~first, shared, 0).sum()))
        self._c_held["prefill"].inc(held * self.layers)
        if len(starts) and self._selects(
                -(-int((starts + ns).max()) // self.geo.page_size)):
            self._count_index(int((starts + ns)[ns > 0].sum()))


class State(Kind):
    """A layer that caches nothing a token (a linear-attention layer: a
    gated or delta rule over a matrix state a head): its whole memory is
    the program's slot state, the same bytes a slot and layer whatever
    the length. No pool, and a page id commits no byte of it: the
    engine's loops never have it place, write or attend, they call the
    program's ``mixer`` as the block's token mixer and thread the state
    through (what the state kernels move is the engine's to count, as for
    every program with slot state). ``page_layers``: the program's other
    layers, for the spans."""

    paged = False
    prefill_run = 1         # a slot's state row is one lane's to advance

    def __init__(self, geo: Geometry, layers: int, page_layers: int):
        super().__init__(geo, layers, "state")
        self.page_layers = page_layers

    def _layers_touched(self, span):
        """How many of the step's layers touched state, and how many pages."""
        _add_attr(span, "state_layers", self.layers)
        _add_attr(span, "page_layers", self.page_layers)

    def count_decode(self, span, block_tables, lengths, dslots, keeps,
                     n: int, width: int):
        self._layers_touched(span)
        return 0, 0

    def count_prefill(self, span, starts, ns, heads=None):
        self._layers_touched(span)


# -- the one decision --------------------------------------------------------

def build(spec, *, num_slots: int, page_size: int, num_pages: int, dtype,
          share_prefix: bool, tp: int = 1, impl: str = "auto",
          prefill_chunk: Optional[int] = None,
          prefill_room: int = 1) -> Tuple[Kind, ...]:
    """One kind a layer of the program ``spec`` describes, in a cache of
    this geometry (layers alike share one object: the same window, KV
    heads and sink). The only reader of ``spec.extra_rows``,
    ``spec.select_topk``, ``spec.layer_windows``, ``spec.latent_row``,
    ``spec.layer_kv_heads``, ``spec.value_dim``, ``spec.sink_layers``,
    ``spec.state_layers`` and the cache's dtype, and the one place where
    what does not combine yet is refused (what a ``ServingSpec`` refuses
    of itself, state layers beside window, selecting or latent layers
    among it, never gets here). ``prefill_chunk``: the engine's, where an
    engine asks; ``prefill_room``: the pages of room a ring gets, which is the
    longest run of one slot's chunks a prefill call may carry through
    the window layers (the engine's to choose: ``ServingEngine``'s
    ``_LANE_STEP`` or the lanes its budget buys, whichever is less);
    left out: one page, a call that carries one chunk a slot. Each page
    of room is ``num_slots`` x window layers x a page of K and V
    (``Ring.slot_bytes``)."""
    def geo(heads):
        return Geometry(num_slots=num_slots, page_size=page_size,
                        num_pages=num_pages, heads=heads,
                        head_dim=spec.head_dim, dtype=dtype, tp=tp,
                        impl=impl, value_dim=spec.value_dim,
                        query_heads=spec.num_heads if spec.sink_layers
                        else 0)

    int8 = jnp.dtype(dtype) == jnp.dtype(jnp.int8)
    windows = spec.layer_windows or (None,) * spec.num_layers
    ringed = [w for w in windows if w is not None]
    own = [name for name in ("layer_kv_heads", "value_dim", "sink_layers")
           if getattr(spec, name)]
    if own:
        other = [what for what, there in (
            ("int8 pages", int8), (f"tp={tp}", tp > 1),
            ("prefix sharing", share_prefix),
            ("select_topk", spec.select_topk is not None),
            ("extra_rows", bool(spec.extra_rows))) if there]
        if other:
            raise ValueError(
                f"a program that declares {', '.join(own)} (a layer's own "
                f"KV heads, values narrower than keys, a sink in the "
                f"softmax) does not combine with {', '.join(other)} yet: "
                "the int8, sharded and sparse kernels and the prefix, "
                "spill and migration formats take K and V of one width "
                "and one head count")
    if spec.select_topk is not None and spec.select_topk % page_size:
        raise ValueError(
            f"select_topk={spec.select_topk} must be a multiple of "
            f"page_size={page_size}: the selected tokens are folded "
            "as whole pages")
    if spec.latent_row is not None and int8:
        raise ValueError(
            "a pool of latent rows is not quantized and carries no "
            "slot state or window layers yet")
    if ringed:
        if int8 or share_prefix:
            raise ValueError(
                "a pool with window layers is not quantized and shares "
                "no prefixes yet: a borrower would need the window "
                "layers' last tokens of the prefix")
        if prefill_chunk is not None and prefill_chunk > page_size:
            raise ValueError(
                f"prefill_chunk={prefill_chunk} > page_size="
                f"{page_size}: a window layer's ring holds its window and "
                "a page of room for each chunk of a call's run, so a "
                "chunk is at most a page of tokens")
    if spec.extra_rows and int8:
        raise ValueError("an int8 pool carries no extra rows yet")
    if spec.slot_state and int8:
        raise ValueError("an int8 pool carries no slot state yet")
    if spec.slot_state and share_prefix:
        raise ValueError(
            "a pool with slot state cannot share prefixes: a prefix "
            "hit would skip the tokens that built the state")
    if tp > 1:
        if spec.kv_heads % tp:
            raise ValueError(
                f"tp={tp} must divide num_heads={spec.kv_heads}")
        if spec.extra_rows or spec.slot_state or ringed or spec.latent_row:
            raise ValueError("a tp-sharded pool carries no extra "
                             "rows, no slot state, no window layers "
                             "and no latent rows yet")
    label = "full" if ringed or spec.state_layers else None
    stateful = spec.state_layers or (False,) * spec.num_layers

    def kind(window, heads, sink, is_state, layers):
        if is_state:
            return State(geo(heads), layers, spec.num_layers - layers)
        if window is not None:
            return Ring(geo(heads), layers, window, sink, prefill_room)
        if spec.latent_row is not None and spec.select_topk is not None:
            return SelectingLatent(geo(heads), layers, spec.latent_row,
                                   spec.extra_rows, spec.select_topk)
        if spec.latent_row is not None:
            return Latent(geo(heads), layers, spec.latent_row)
        if int8:
            return PagedInt8(geo(heads), layers)
        if spec.select_topk is not None:
            return Selecting(geo(heads), layers, label, spec.extra_rows,
                             spec.select_topk)
        # beside state layers an attention layer keeps no slot state
        return Paged(geo(heads), layers, label, spec.extra_rows, sink,
                     state=not spec.state_layers)

    alike = list(zip(
        windows, spec.layer_kv_heads or (spec.kv_heads,) * spec.num_layers,
        spec.sink_layers or (False,) * spec.num_layers, stateful))
    kinds = {key: kind(*key, alike.count(key))
             for key in dict.fromkeys(alike)}
    return tuple(kinds[key] for key in alike)
