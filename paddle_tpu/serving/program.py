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
    ffn(params, i, x, valid (S,C) bool)                   -> x, stats
    head(params, x (..., D))                              -> logits (..., V)

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
    supports: FrozenSet[str] = FEATURES
