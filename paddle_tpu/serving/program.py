"""What a model supplies to the paged serving engine: one contract.

The engine owns slots, pages, block tables, buckets, warm-up and
scheduling; what a layer caches and how it attends is its **kind**'s
(:mod:`~paddle_tpu.serving.layer_kinds`); neither knows a model's block. A
model hands the engine a **serving program** (``model.serving(...)``): an
object with a :class:`ServingSpec` as ``spec`` and the hooks below.

**What a program declares**, and the kind of layer each field selects
(the cache's dtype selects between ``Paged`` and ``PagedInt8``):

==================  ======================================================
(none of these)     K and V, ``kv_heads`` x ``head_dim`` a token, under
                    the block table: ``Paged``
``layer_windows``   one entry a layer: None (a token attends to every
                    token before it) or ``w`` (to the last ``w``, itself
                    counted: ``t - w < s <= t``): that layer is a ``Ring``
``latent_row``      ``(latent_dim, rope_dim)``: every head reads ONE
                    cached row a token (multi-head latent attention,
                    absorbed): ``Latent``. ``attn_in`` returns ``q`` (S, H,
                    C, latent_dim + rope_dim), already scaled, and ``rows
                    = (c (S, C, latent_dim), k_rope (S, C, rope_dim))``;
                    ``attn_out`` gets heads ``(S, C, H, latent_dim)``, the
                    weighted sum of latents, and applies its own value
                    up-projection. ``kv_heads`` 1, ``head_dim`` the row's
                    width, and none of the other three
``select_topk``,    each query attends to its ``select_topk`` best tokens
``extra_rows``      once it sees more, scored against the index rows
                    cached beside K and V: ``Selecting``
``latent_row`` with the selection over the latent rows, one index row a
both of those       token cached beside them: ``SelectingLatent``. ``rows
                    = (c, k_rope, k_index)`` and ``index`` as a selecting
                    program's
``state_layers``    one bool a layer: True, the layer is a **state layer**
                    (``State``): it caches NO rows a token, holds no page
                    and is never asked for queries; its whole memory is
                    the program's ``slot_state``, and ``mixer`` is the
                    block's only token mixer (``attn_in`` / ``attn_out``
                    are not called for it). False: an attention layer of
                    the kind the other fields select, which then keeps
                    NO slot state. Needs ``slot_state`` read by
                    ``"mixer"``. Empty: every layer attends, and keeps
                    the state too where ``slot_state`` is declared
==================  ======================================================

A layer's **geometry** is its own too. Three fields say where a layer's
differs from the program's ``kv_heads`` x ``head_dim`` (they go with
``Paged`` and ``Ring`` layers in a float pool on one chip, without prefix
sharing: what else they would combine with is refused by name):

==================  ======================================================
``layer_kv_heads``  one entry a layer: the KV heads that layer caches
                    (full layers of 4 beside window layers of 8); every
                    entry divides ``num_heads``. Empty: ``kv_heads``
``value_dim``       the width of a head's VALUES where keys are wider
                    (keys ``head_dim`` = 192, values 128): a layer's K
                    pool is ``heads * head_dim`` lanes and its V pool
                    ``heads * value_dim``; ``attn_in`` returns V rows of
                    that width and ``attn_out`` gets heads ``(S, C, H,
                    value_dim)``. None: ``head_dim``
``sink_layers``     one bool a layer: its softmax carries a learned sink
                    a query head, ``p = exp(a) / (exp(b) + sum exp(a))``.
                    ``attn_in`` hands the layer's sink logits ``b`` (H,)
                    back as its third result (``index``), which reaches
                    the kind with the queries. Empty: no layer does
==================  ======================================================

Beside the kind: ``slot_state`` (below), ``layer_carry`` (arrays a token
carries from one layer's ``ffn`` to the next, zeros ``(S, C, width)``
float32 into the first layer, dropped after the last: neither cached nor
state), ``stats`` (the scalar counts ``ffn`` hands back) and ``supports``
(the engine features the program carries, :data:`FEATURES`; the engine
refuses, by name, an option that needs one it leaves out).

**The hooks**: pure functions over the parameter tree, each called inside
the engine's jitted steps on ``S`` lanes of ``C`` tokens (decode: ``C`` =
1). Two of them have a second signature, chosen by the spec::

    embed(params, tokens (S,C), positions (S,C))          -> x (S,C,D)
    attn_in(params, i, x, positions)                      -> q, rows, index
    attn_in(params, i, x, positions, state, rows (S,), fresh (S,),
            valid (S,C))                        -> q, rows, index, state
                                 # where slot_state_reader == "attn_in"
    attn_out(params, i, x, att (S,C,H,Dh))                -> x
    mixer(params, i, x, state, rows (S,), fresh (S,), valid (S,C))
                                                          -> y (S,C,D), state
                 # only where slot_state is declared and read by "mixer"
    ffn(params, i, x, valid (S,C) bool)                   -> x, stats
    ffn(params, i, x, valid (S,C) bool, carry)            -> x, stats, carry
                                          # where layer_carry is declared
    head(params, x (..., D))                              -> logits (..., V)

``attn_in`` returns the layer's queries ``q`` (S, H, C, Dh), the ``rows``
to cache for every token, a tuple of (S, C, lanes) arrays: K and V with
their KV heads folded head-major into the lanes, then one array for each
of ``spec.extra_rows``, and ``index``: None, or ``(q_idx (S,C,J,Di),
w_idx (S,C,J))`` where the model selects, or the layer's sink logits (H,)
float32 where ``spec.sink_layers`` says it has them; layer ``i``'s rows
whatever its kind, K at ``layer_kv_heads[i] * head_dim`` lanes and V at
``layer_kv_heads[i] * value_dim`` where the program declares those. The kind writes the rows where it places them and attends over the
pool; the engine hands the heads to ``attn_out``. ``attn_out`` and ``ffn``
return the residual stream with their block added. ``ffn`` may return a
dict of scalar counts (names from ``spec.stats``) about the tokens
``valid`` marks, or None.

``slot_state`` is state of a fixed size a layer keeps a SLOT, not a token
(a recurrence's state, a conv window): one pool array a layer and entry
(a STATE layer and entry where the program declares ``state_layers``: its
attention layers then have none),
``(num_slots + 1,) + shape``, row 0 the null row, read and advanced by
``mixer``, or by ``attn_in`` where ``slot_state_reader`` says the state is
the attention projections' own. ``state`` is the layer's pools, ``rows``
the pool row of every lane (0 for a pad lane or a slot that is not
decoding), ``fresh`` where a lane's prompt starts in this call (its row's
content is another request's: start from zeros) and ``valid`` marks a
lane's real tokens, which come first. ``mixer`` gets the layer's input
``x`` and returns what the block adds to the residual stream beside
``attn_out``'s (alone, in a state layer); either reader returns the pools with every lane's row
advanced past its valid tokens, rows of other slots as they were, bit for
bit.

A program that lists ``"tp"`` is built for its degree
(``model.serving(tp=N, ...)``: the hooks are then one head shard's, run
under the engine's ``shard_map`` with the program's own collectives on the
mesh's ``"tp"`` axis) and adds ``tp_params(params)``, the tree the sharded
steps take, and ``tp_plan()``, its ``parallel.plan.ShardingPlan``; the
engine names no parameter of any model.
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
    # (what each field below selects: the module's docstring)
    #: ``(name, width)`` each, a pool ``(P, width, page_size)`` a row
    extra_rows: Tuple[Tuple[str, int], ...] = ()
    #: tokens a query attends to once it can see more (None: all)
    select_topk: Optional[int] = None
    #: counts ``ffn`` hands back, summed into ``serving_<name>_total``
    stats: Tuple[str, ...] = ()
    #: ``(name, shape)`` each, a pool ``(num_slots + 1,) + shape`` a layer
    slot_state: Tuple[Tuple[str, Tuple[int, ...]], ...] = ()
    slot_state_dtype: str = "float32"
    #: ``"mixer"``, or ``"attn_in"`` (such a program has no ``mixer``)
    slot_state_reader: str = "mixer"
    #: ``(name, width)`` each, float32, through ``ffn``
    layer_carry: Tuple[Tuple[str, int], ...] = ()
    #: one entry a layer, None or the window; empty: every layer is full
    layer_windows: Tuple[Optional[int], ...] = ()
    #: ``(latent_dim, rope_dim)``; None: K and V heads
    latent_row: Optional[Tuple[int, int]] = None
    #: one entry a layer, its KV heads; empty: ``kv_heads`` every layer
    layer_kv_heads: Tuple[int, ...] = ()
    #: the width of a head's values; None: ``head_dim``, as its keys
    value_dim: Optional[int] = None
    #: one bool a layer, its softmax carries a sink; empty: none does
    sink_layers: Tuple[bool, ...] = ()
    #: one bool a layer, it caches no rows and ``mixer`` is its token
    #: mixer; empty: every layer attends
    state_layers: Tuple[bool, ...] = ()
    supports: FrozenSet[str] = FEATURES

    def __post_init__(self):
        if self.latent_row is not None:
            if (self.kv_heads, self.head_dim) != (1, sum(self.latent_row)):
                raise ValueError(
                    f"latent_row={self.latent_row!r}: one row a token as "
                    "wide as a query (kv_heads 1, head_dim latent_dim + "
                    "rope_dim)")
            mixed = [name for name in ("slot_state", "layer_windows",
                                       "layer_kv_heads", "value_dim",
                                       "sink_layers", "state_layers")
                     if getattr(self, name)]
            if mixed:
                raise ValueError(f"a latent row is cached alone or beside "
                                 f"the index rows of a selection, without "
                                 f"{mixed}")
            if bool(self.extra_rows) != (self.select_topk is not None) \
                    or len(self.extra_rows) > 1:
                raise ValueError(
                    f"latent_row with extra_rows={self.extra_rows!r}, "
                    f"select_topk={self.select_topk!r}: beside a latent "
                    "row goes ONE index row a token and the selection "
                    "that reads it, both or neither")
        if self.layer_windows and all(w is None for w in self.layer_windows):
            object.__setattr__(self, "layer_windows", ())    # all full
        if self.layer_windows and (
                len(self.layer_windows) != self.num_layers or any(
                    w is not None and w < 1 for w in self.layer_windows)):
            raise ValueError(
                f"layer_windows={self.layer_windows!r}: one entry a layer "
                f"({self.num_layers}), None or a window of at least 1")
        if self.layer_kv_heads and set(self.layer_kv_heads) == {
                self.kv_heads}:
            object.__setattr__(self, "layer_kv_heads", ())   # all alike
        if self.value_dim == self.head_dim:
            object.__setattr__(self, "value_dim", None)
        if self.sink_layers and not any(self.sink_layers):
            object.__setattr__(self, "sink_layers", ())
        if self.layer_kv_heads and (
                len(self.layer_kv_heads) != self.num_layers or any(
                    g < 1 or self.num_heads % g
                    for g in self.layer_kv_heads)):
            raise ValueError(
                f"layer_kv_heads={self.layer_kv_heads!r}: one entry a "
                f"layer ({self.num_layers}), each dividing num_heads="
                f"{self.num_heads}")
        if self.sink_layers and len(self.sink_layers) != self.num_layers:
            raise ValueError(
                f"sink_layers={self.sink_layers!r}: one bool a layer "
                f"({self.num_layers})")
        if self.value_dim is not None and self.value_dim < 1:
            raise ValueError(f"value_dim={self.value_dim!r}: at least 1")
        if self.state_layers and not any(self.state_layers):
            object.__setattr__(self, "state_layers", ())
        if self.state_layers:
            if len(self.state_layers) != self.num_layers:
                raise ValueError(
                    f"state_layers={self.state_layers!r}: one bool a layer "
                    f"({self.num_layers})")
            if not self.slot_state or self.slot_state_reader != "mixer":
                raise ValueError(
                    "state_layers: a state layer's whole memory is the "
                    "program's slot_state, read and advanced by 'mixer'")
            mixed = [name for name in ("layer_windows", "select_topk",
                                       "extra_rows", "layer_kv_heads",
                                       "value_dim", "sink_layers",
                                       "layer_carry")
                     if getattr(self, name)]
            if mixed:
                raise ValueError(
                    f"state layers beside layers that declare {mixed} are "
                    "not served yet: window, selecting or latent layers, "
                    "a layer's own geometry or a carry beside state layers "
                    "each wait for an architecture that has them")
        if self.slot_state_reader not in ("mixer", "attn_in"):
            raise ValueError(
                f"slot_state_reader={self.slot_state_reader!r}: the engine "
                "hands slot state to 'mixer' or to 'attn_in'")
