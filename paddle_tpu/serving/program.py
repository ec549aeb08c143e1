"""What a model supplies to the paged serving engine.

The engine owns slots, pages, block tables, buckets, warm-up and
scheduling, and the call into paged attention; it knows no model's block.
A model hands it a **serving program** (``model.serving(...)``): an
object with a :class:`ServingSpec` as ``spec`` and these pure functions
over the parameter tree, each called inside the engine's jitted steps on
``S`` lanes of ``C`` tokens (decode: ``C`` = 1)::

    embed(params, tokens (S,C), positions (S,C))          -> x (S,C,D)
    attn_in(params, i, x, positions)                      -> q, rows, index
    attn_out(params, i, x, att (S,C,H,Dh))                -> x
    ffn(params, i, x, valid (S,C) bool[, carry])          -> x, stats[, carry]
    head(params, x (..., D))                              -> logits (..., V)
    mixer(params, i, x, state, rows (S,), fresh (S,), valid (S,C))
                                                          -> y (S,C,D), state

``attn_in`` returns the layer's queries ``q`` (S, H, C, Dh), the ``rows``
to cache for every token, a tuple of (S, C, lanes) arrays: K and V with
their KV heads folded head-major into the lanes, then one array for each
of ``spec.extra_rows``, and ``index``: None, or ``(q_idx (S,C,J,Di),
w_idx (S,C,J))`` where the model selects the tokens a query attends to
(``spec.select_topk``). The engine writes the rows where the slot's page
table says, runs attention over the pool, and hands the heads back to
``attn_out``. ``attn_out`` and ``ffn`` return the residual stream with
their block added. ``ffn`` may return a dict of scalar counts (names from
``spec.stats``) about the tokens ``valid`` marks, or None.

``mixer`` is called only for a program that declares ``spec.slot_state``:
state of a fixed size that a layer keeps a SLOT, not a token (a
recurrence's state, a conv window). The engine keeps one pool array a
layer and entry, ``(num_slots + 1,) + shape``, row 0 the null row, beside
the layer's K and V pools, and calls ``mixer`` with the layer's input
``x`` (the one ``attn_in`` gets), the pools as ``state``, the pool row of
every lane (0 for a pad lane or a slot that is not decoding), ``fresh``
where a lane's prompt starts in this call (its row's content is another
request's: start from zeros) and ``valid`` marking a lane's real tokens,
which come first. It returns what the block adds to the residual stream
beside ``attn_out``'s and the pools with every lane's row advanced past
its valid tokens; rows of other slots stay as they were, bit for bit.

Where the state is the attention projections' own (a query, key or value
that reads the tokens before it), the program declares
``spec.slot_state_reader = "attn_in"``: it has no ``mixer``, and the
engine hands ``attn_in`` those same four arguments after ``positions``
and takes the pools back as a fourth result::

    attn_in(params, i, x, positions, state, rows (S,), fresh (S,),
            valid (S,C))                        -> q, rows, index, state

A program that declares ``spec.layer_carry`` passes arrays from one
layer's ``ffn`` to the next layer's, per token, beside the residual
stream: the engine starts a pass through the layers with a tuple of
zeros ``(S, C, width)`` float32, one for each entry, hands it to ``ffn``
as a fifth argument, takes it back as a third result, and drops it after
the last layer. It is neither cached nor state: it lives for one pass.

A program whose layers are not all of one kind of attention declares
``spec.layer_windows``, one entry a layer: None where a token attends to
every token before it, ``w`` where it attends to the last ``w`` (itself
counted: ``t - w < s <= t``). The cache then keeps a window layer's K and
V in a ring of pages a slot (:mod:`~paddle_tpu.serving.paged_cache`: the
pages behind the window are written over as the slot advances, whatever
its length), the loops address it by the slot and the position alone (no
table) and hand the window to the paged kernels, and the program's own
functions stay as above: ``attn_in`` of layer ``i`` returns that layer's
rows whatever its kind.

A program whose heads all read ONE cached row a token declares
``spec.latent_row = (latent_dim, rope_dim)`` (multi-head latent attention
in its absorbed form). ``attn_in`` then returns ``q`` (S, H, C,
latent_dim + rope_dim), every head's query against the whole row, already
scaled, and ``rows = (c (S, C, latent_dim), k_rope (S, C, rope_dim))``: the
token's latent, which is also what attention sums, and the rotary key the
heads share. The cache keeps the two as one page pool each and nothing
else (``latent_dim + rope_dim`` values a token and layer; there is no V
pool: the values are the row's first ``latent_dim``), the engine attends
through the latent kernels (:mod:`~paddle_tpu.serving.decode_attention`),
and ``attn_out`` gets heads ``(S, C, H, latent_dim)``: the weighted sum of
latents, to which the program applies its value up-projection and its
output projection. Such a spec says ``kv_heads = 1`` and ``head_dim =
latent_dim + rope_dim``: one row, as wide as a query.

What a program cannot do yet it leaves out of ``spec.supports``; the
engine refuses, by name, an option that needs it.

A program that lists ``"tp"`` is built for its degree
(``model.serving(tp=N, ...)``: the functions above are then one head
shard's, run under the engine's ``shard_map`` with the program's own
collectives on the mesh's ``"tp"`` axis) and adds the layout that goes
with it; the engine names no parameter of any model::

    tp_params(params)  -> the tree the sharded steps take
    tp_plan()          -> parallel.plan.ShardingPlan of that tree
"""

from __future__ import annotations

import dataclasses
from typing import FrozenSet, Optional, Tuple

#: engine features a serving program may support
FEATURES = frozenset({
    "tp",               # heads sharded over a mesh's "tp" axis
    "int8_pages",       # int8 page pool with per-token scales
    "draft",            # speculative decoding (as target or as draft)
    "host_spill",       # cold published pages paged out to the host
    "migration",        # slot snapshot / restore, micro-checkpoints
    "tiers",            # prefill / decode disaggregation with handoff
    "prefix_export",    # published prefix pages shipped between replicas
    "prefix_sharing",   # published prompt pages mapped instead of prefilled
})


@dataclasses.dataclass(frozen=True)
class ServingSpec:
    num_layers: int
    num_heads: int                  # query heads
    kv_heads: int                   # heads of K and V a token caches
    head_dim: int
    vocab_size: int
    max_position: int
    #: further rows cached per token and layer beside K and V, ``(name,
    #: width)`` each, kept a page at a time as ``(P, width, page_size)``
    extra_rows: Tuple[Tuple[str, int], ...] = ()
    #: tokens a query attends to once it can see more (None: all)
    select_topk: Optional[int] = None
    #: counts ``ffn`` hands back, summed into ``serving_<name>_total``
    stats: Tuple[str, ...] = ()
    #: state kept per SLOT and layer beside the pages, ``(name, shape)``
    #: each: one pool array a layer, ``(num_slots + 1,) + shape``, read
    #: and written by ``mixer``; never shared, copied on write or shipped
    slot_state: Tuple[Tuple[str, Tuple[int, ...]], ...] = ()
    slot_state_dtype: str = "float32"
    #: which function reads and advances ``slot_state``: ``"mixer"``, a
    #: branch beside attention, or ``"attn_in"``, the attention
    #: projections themselves (such a program has no ``mixer``)
    slot_state_reader: str = "mixer"
    #: arrays a token carries from layer to layer beside the residual
    #: stream, ``(name, width)`` each, float32, through ``ffn``
    layer_carry: Tuple[Tuple[str, int], ...] = ()
    #: a layer's kind of attention, one entry a layer: None (every token
    #: before) or the window, the tokens a query attends to with itself
    #: counted; empty: every layer is full
    layer_windows: Tuple[Optional[int], ...] = ()
    #: ``(latent_dim, rope_dim)`` where a layer caches ONE row a token that
    #: every head reads, its first ``latent_dim`` values also the values
    #: attention sums; None: K and V heads
    latent_row: Optional[Tuple[int, int]] = None
    supports: FrozenSet[str] = FEATURES

    def __post_init__(self):
        if self.latent_row is not None:
            if (self.kv_heads, self.head_dim) != (1, sum(self.latent_row)):
                raise ValueError(
                    f"latent_row={self.latent_row!r}: one row a token as "
                    "wide as a query (kv_heads 1, head_dim latent_dim + "
                    "rope_dim)")
            mixed = [name for name in ("extra_rows", "select_topk",
                                       "slot_state", "layer_windows")
                     if getattr(self, name)]
            if mixed:
                raise ValueError(f"a latent row is cached alone, without "
                                 f"{mixed}")
        if self.layer_windows and all(w is None for w in self.layer_windows):
            object.__setattr__(self, "layer_windows", ())    # all full
        if self.layer_windows and (
                len(self.layer_windows) != self.num_layers or any(
                    w is not None and w < 1 for w in self.layer_windows)):
            raise ValueError(
                f"layer_windows={self.layer_windows!r}: one entry a layer "
                f"({self.num_layers}), None or a window of at least 1")
        if self.slot_state_reader not in ("mixer", "attn_in"):
            raise ValueError(
                f"slot_state_reader={self.slot_state_reader!r}: the engine "
                "hands slot state to 'mixer' or to 'attn_in'")
