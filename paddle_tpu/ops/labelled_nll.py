"""Negative log-likelihood over a vocabulary, computed where there is a
label and nowhere else.

A language model's loss is ``sum_p mask[p] * (logsumexp(z[p]) -
z[p, label[p]])`` with ``z[p] = head(hidden[p]) @ table.T + bias``. A row
whose mask is zero adds exactly zero to it and to every gradient, and
masked-language-model pre-training labels about a seventh of the
positions: :func:`labelled_nll` never forms the ``(B, S, vocab)`` logits.

1. **Compact.** Each sequence's positions are put in a stable order with
   the labelled ones (``mask != 0``) first. The batch axis is left alone,
   so under a data-parallel mesh the reordering stays inside a shard.
2. **Walk.** Columns of that order are taken ``chunk`` at a time,
   ``(B, chunk)`` positions: ``head`` (a model's transform before its
   decoder), the decoder product with float32 accumulation, the float32
   log-sum-exp, the label's logit, the row's mask. The walk is a
   ``lax.while_loop`` that ends at the largest count of labels any
   sequence has, read from the mask on the device: a mask of ones walks
   every column and is the dense loss, a mask of zeros walks none.
3. **Backward.** A ``jax.custom_vjp`` walks the same chunks again,
   recomputes a chunk's logits against the saved log-sum-exp, and
   accumulates the gradients of the table, the bias and ``head``'s
   parameters in float32 over the chunks. The hidden states' gradient is
   written a chunk at a time and put back in place by the inverse order
   (a gather, not a scatter).

There is nothing to set: no capacity, no dropped label. ``chunk`` follows
from the shapes (:func:`chunk_columns`).

Under a mesh (``core.mesh.mesh_context``) whose batch axes divide ``B``
every accumulator carries a leading axis of one entry a batch shard, so
a chunk's parameter gradients stay where their rows are and are summed
over the shards once, after the walk; a vocabulary sharded over ``tp``
reduces a chunk's log-sum-exp and label logit over ``tp`` as the dense
log-softmax does.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

#: rows (positions) a chunk aims at on one batch shard: enough that the
#: decoder product fills the matrix unit and the float32 accumulators
#: are passed over few times, few enough that a chunk's float32 logits
#: stay near 100 MB at a 30k vocabulary
_CHUNK_ROWS = 1024


def chunk_columns(shard_batch: int, seq: int) -> int:
    """Columns of the order one chunk holds: the largest divisor of
    ``seq`` that keeps a chunk on one batch shard at ``_CHUNK_ROWS`` rows
    or fewer (16 at 48 x 512)."""
    want = min(seq, max(1, _CHUNK_ROWS // shard_batch))
    return max(d for d in range(1, want + 1) if seq % d == 0)


class _Walk(NamedTuple):
    """The static facts of one walk."""
    head: Optional[Callable]     # (head_params, rows) -> rows, or None
    chunk: int                   # columns a chunk
    groups: int                  # batch shards of the mesh (1: no mesh)
    axes: Tuple[str, ...]        # the mesh axes those shards lie over


def _batch_shards(batch: int) -> Tuple[int, Tuple[str, ...]]:
    """How many shards the current mesh cuts a batch of ``batch`` into,
    and over which axes: (1, ()) with no mesh, inside a ``shard_map``,
    or where the axes do not divide the batch."""
    from paddle_tpu.core import mesh as mesh_lib
    mesh = mesh_lib.current_mesh()
    if mesh is None or jax.sharding.get_abstract_mesh().manual_axes:
        return 1, ()
    axes = tuple(a for a in mesh_lib.BATCH_AXES if a in mesh.shape)
    groups = math.prod(mesh.shape[a] for a in axes)
    if groups == 1 or batch % groups:
        return 1, ()
    return groups, axes


def _by_shard(walk: _Walk, x):
    """Pin the leading (group) axis of an accumulator or of the rows to
    the batch shards; every other axis is the partitioner's to choose."""
    if walk.groups == 1:
        return x
    return lax.with_sharding_constraint(
        x, P(walk.axes, *[P.UNCONSTRAINED] * (x.ndim - 1)))


def _grouped(walk: _Walk, x):
    """``(B, ...)`` -> ``(groups, B / groups, ...)``."""
    return _by_shard(walk, x.reshape((walk.groups, -1) + x.shape[1:]))


def _columns(walk: _Walk, x, j):
    """Chunk ``j`` of a ``(groups, b, S, ...)`` array."""
    return lax.dynamic_slice_in_dim(x, j * walk.chunk, walk.chunk, axis=2)


def _hidden_rows(walk: _Walk, head_params, rows):
    return rows if walk.head is None else walk.head(head_params, rows)


def _logits(h, table, bias):
    z = jnp.einsum("gbcd,vd->gbcv", h, table,
                   preferred_element_type=jnp.float32)
    return z + bias.astype(jnp.float32)


def _is_label(z, labels):
    return lax.broadcasted_iota(jnp.int32, z.shape, z.ndim - 1) \
        == labels[..., None]


def _forward_walk(walk: _Walk, head_params, table, bias, hidden, order,
                  labels, weights, needed):
    """-> (sum of the weighted negative log-likelihoods, the log-sum-exp
    of every row walked ``(groups, b, S)``, the ordered hidden rows)."""
    rows = _grouped(walk, jnp.take_along_axis(hidden, order[..., None],
                                              axis=1))

    def chunk(carry):
        j, total, lses = carry
        z = _logits(_hidden_rows(walk, head_params, _columns(walk, rows, j)),
                    table, bias)
        lse = jax.nn.logsumexp(z, axis=-1)
        picked = jnp.where(_is_label(z, _columns(walk, labels, j)),
                           z, 0.0).sum(-1)
        nll = (lse - picked) * _columns(walk, weights, j)
        lses = lax.dynamic_update_slice_in_dim(lses, lse, j * walk.chunk,
                                               axis=2)
        return j + 1, total + nll.sum(), lses

    _, total, lses = lax.while_loop(
        lambda carry: carry[0] < needed, chunk,
        (jnp.zeros((), jnp.int32), jnp.zeros((), jnp.float32),
         _by_shard(walk, jnp.zeros(labels.shape, jnp.float32))))
    return total, lses, rows


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _nll_sum(walk, head_params, table, bias, hidden, order, labels, weights,
             needed):
    return _forward_walk(walk, head_params, table, bias, hidden, order,
                         labels, weights, needed)[0]


def _nll_sum_fwd(walk, head_params, table, bias, hidden, order, labels,
                 weights, needed):
    total, lses, rows = _forward_walk(walk, head_params, table, bias, hidden,
                                      order, labels, weights, needed)
    return total, (head_params, table, bias, rows, order, labels, weights,
                   needed, lses)


def _nll_sum_bwd(walk, residuals, g):
    head_params, table, bias, rows, order, labels, weights, needed, lses = \
        residuals

    def zeros(x):   # one float32 accumulator a batch shard
        return _by_shard(walk, jnp.zeros((walk.groups,) + x.shape,
                                         jnp.float32))

    def head_grads(rows_g, dh_g):      # one batch shard's
        _, pull = jax.vjp(functools.partial(_hidden_rows, walk),
                          head_params, rows_g)
        return pull(dh_g)

    def chunk(carry):
        j, d_rows, d_head, d_table, d_bias = carry
        x = _columns(walk, rows, j)
        h = _hidden_rows(walk, head_params, x)
        z = _logits(h, table, bias)
        p = jnp.exp(z - _columns(walk, lses, j)[..., None])
        dz = (p - _is_label(z, _columns(walk, labels, j))) \
            * (_columns(walk, weights, j) * g)[..., None]
        dz_c = dz.astype(h.dtype)
        dh = jnp.einsum("gbcv,vd->gbcd", dz_c, table,
                        preferred_element_type=jnp.float32).astype(h.dtype)
        d_table = d_table + jnp.einsum(
            "gbcv,gbcd->gvd", dz_c, h, preferred_element_type=jnp.float32)
        d_bias = d_bias + dz.sum((1, 2))
        dp, dx = jax.vmap(head_grads)(x, dh)
        d_head = jax.tree_util.tree_map(
            lambda acc, d: acc + d.astype(jnp.float32), d_head, dp)
        d_rows = lax.dynamic_update_slice_in_dim(
            d_rows, dx.astype(d_rows.dtype), j * walk.chunk, axis=2)
        return j + 1, d_rows, d_head, d_table, d_bias

    _, d_rows, d_head, d_table, d_bias = lax.while_loop(
        lambda carry: carry[0] < needed, chunk,
        (jnp.zeros((), jnp.int32), jnp.zeros_like(rows),
         jax.tree_util.tree_map(zeros, head_params), zeros(table),
         zeros(bias)))

    def summed(acc, like):      # over the batch shards, once
        return acc.sum(0).astype(like.dtype)

    d_hidden = jnp.take_along_axis(
        d_rows.reshape((-1,) + d_rows.shape[2:]),
        jnp.argsort(order, axis=1)[..., None], axis=1)
    return (jax.tree_util.tree_map(summed, d_head, head_params),
            summed(d_table, table), summed(d_bias, bias), d_hidden,
            None, None, jnp.zeros_like(weights), None)


_nll_sum.defvjp(_nll_sum_fwd, _nll_sum_bwd)


def labelled_nll(hidden, table, bias, labels, mask, *,
                 head: Optional[Callable] = None, head_params: Any = None):
    """Masked sum of ``-log softmax(head(hidden) @ table.T + bias)[label]``
    over the positions whose ``mask`` is not zero.

    ``hidden`` ``(B, S, D)``; ``table`` ``(V, D)``; ``bias`` ``(V,)``;
    ``labels`` ``(B, S)`` integers; ``mask`` ``(B, S)``, a row's weight
    (``1.0`` where labelled). ``head(head_params, rows)`` maps hidden rows
    ``(..., D)`` to the decoder's input rows (BERT: dense, GELU,
    LayerNorm); left out, the hidden rows are the decoder's input.

    Returns ``(nll_sum, count, rows_share)``: the weighted sum in float32,
    ``mask.sum()`` in float32, and the share of the ``B x S`` rows the
    walk computed (whole chunks up to the largest count of any sequence;
    a device scalar, 1.0 for a mask of ones, 0.0 for a mask of zeros).
    The value and the gradients with respect to ``hidden``, ``table``,
    ``bias`` and ``head_params`` are those of the dense formula for any
    mask; the mask itself is data and gets no gradient.
    """
    b, s = labels.shape
    groups, axes = _batch_shards(b)
    walk = _Walk(head, chunk_columns(b // groups, s), groups, axes)
    weights = mask.astype(jnp.float32)
    labelled = weights != 0
    # a stable sort of False before True: the labelled positions first,
    # in their own order
    order = jnp.argsort(~labelled, axis=1, stable=True)
    needed = (labelled.sum(1).max() + walk.chunk - 1) // walk.chunk
    nll_sum = _nll_sum(
        walk, head_params, table, bias, hidden, order,
        _grouped(walk, jnp.take_along_axis(labels, order, axis=1)),
        _grouped(walk, jnp.take_along_axis(weights, order, axis=1)),
        needed.astype(jnp.int32))
    rows_share = (needed * walk.chunk).astype(jnp.float32) / s
    return nll_sum, weights.sum(), rows_share
