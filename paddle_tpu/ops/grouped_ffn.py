"""No-drop routed expert feed-forward: sort by expert, one grouped kernel.

``y[t] = sum_k coef[t, k] * W_down[e] (silu(W_gate[e] x[t]) * W_up[e] x[t])``
with ``e = expert_ids[t, k]``, for every token and every one of its
experts: no capacity, nothing dropped (``nn/moe.py``'s Switch layer drops
what overflows a capacity, which changes a served token's logits).

The token-expert pairs are sorted by expert and laid out in row tiles of
``tm`` rows, each tile holding pairs of ONE expert (an expert's last tile
is padded with zero rows; an expert with no pair has no tile). One Pallas
call, ``moe_grouped_ffn``, walks the tiles: the tile's expert comes from a
scalar-prefetched table, so only the weights of experts that have tokens
are read, once a tile; tiles past the last used one repeat its block
index (no DMA) and skip the arithmetic. The three weights are stored
``(E, F, D)`` so that a block of ``tf`` hidden units is one contiguous
piece of each. Routing, the sort and the gather / weighted sum around the
kernel are plain XLA.

A chip that holds a SHARE of a layer's experts (``held_offset``: experts
``held_offset .. held_offset + E`` of those the router picks from, the
weights ``(E, F, D)``) lays out the pairs of its own experts
only: a pair whose expert is held elsewhere gets no row and adds nothing
here, the tile table is sized for the held experts and the pairs that can
land on them, and ``sizes`` counts the held experts. Nothing stands in
for the other chips: their part of the sum is theirs.
"""

from __future__ import annotations


import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_FP32_DOT = jax.lax.Precision.HIGHEST

#: candidate widths of a hidden block, widest first; the widest that
#: divides F and keeps the double-buffered weight blocks under the
#: budget is taken (F itself where none divides it)
_BLOCK_F = (1024, 768, 512, 384, 256, 128)
_WEIGHT_VMEM_BUDGET = 10 << 20


def _precision(dtype):
    # said outright: under a process-wide default of "highest" None would
    # ask Mosaic for fp32 passes over bf16 operands, which it refuses
    return _FP32_DOT if jnp.dtype(dtype) == jnp.float32 \
        else jax.lax.Precision.DEFAULT


def tile_rows(n_pairs: int, n_experts: int) -> int:
    """Rows a tile: 16 (one bf16 sublane tile) while an expert sees a
    handful of tokens (a decode batch), 32 once it sees about a tile's
    worth (a prefill chunk), so that most experts still fit one tile and
    their weights are read once."""
    return 16 if n_pairs <= 8 * n_experts else 32


def held_pairs(n_pairs: int, n_experts: int, held=None) -> int:
    """The pairs an even router sends to the ``n_experts`` held here of
    ``held = (offset, n_routed)``; all of them where every expert is
    held (None)."""
    return n_pairs if held is None else n_pairs * n_experts // held[1]


def _block_f(f: int, d: int, itemsize: int) -> int:
    for tf in _BLOCK_F:
        if f % tf == 0 and 2 * 3 * tf * d * itemsize <= _WEIGHT_VMEM_BUDGET:
            return tf
    # none fits (rows of 7168: 128 of them are 11 MB): the least that
    # divides, never the whole expert (88 MB a matrix at those widths)
    return min((tf for tf in _BLOCK_F if f % tf == 0), default=f)


def route_tiles(expert_ids, valid, n_experts: int, tm: int,
                held_offset=None):
    """The tile layout of a batch of token-expert pairs.

    ``expert_ids`` (T, K) int32, ``valid`` (T,) bool (pairs of an invalid
    token get no row). Returns ``(src (Mp,) token of every padded row or
    -1, dest (T, K) padded row of every pair (0 for a pair without a
    row), tile_expert (n_tiles,), n_used (1,) tiles in use, sizes (E,)
    pairs an expert)`` with ``Mp = n_tiles * tm`` and ``n_tiles = E + T*K
    // tm``, the most that any routing can need.

    ``held_offset``: ``expert_ids`` name experts of a wider router, of
    which ``held_offset .. held_offset + n_experts`` are held here:
    validity is then a PAIR's (a pair of an expert held elsewhere gets no
    row), ``tile_expert`` and ``sizes`` index the held experts from 0,
    and the table is sized for the pairs that can land here, ``n_tiles =
    E + T * min(K, E) // tm`` (a token's experts are distinct)."""
    t, k = expert_ids.shape
    m = t * k
    live_pair = valid[:, None]
    if held_offset is None:
        n_tiles = n_experts + m // tm
    else:
        expert_ids = expert_ids - held_offset
        live_pair = live_pair & (expert_ids >= 0) & (expert_ids < n_experts)
        n_tiles = n_experts + t * min(k, n_experts) // tm
    flat = jnp.where(live_pair, expert_ids, n_experts).reshape(m)
    order = jnp.argsort(flat, stable=True)                  # pairs by expert
    sizes_all = jnp.zeros((n_experts + 1,), jnp.int32).at[flat].add(1)
    sizes = sizes_all[:n_experts]
    tiles_e = -(-sizes // tm)                               # tiles an expert
    tile_end = jnp.cumsum(tiles_e)
    row_start = (tile_end - tiles_e) * tm                   # padded start
    pair_start = jnp.cumsum(sizes_all) - sizes_all          # sorted start
    sorted_e = flat[order]
    rank = jnp.arange(m, dtype=jnp.int32) - pair_start[sorted_e]
    live = sorted_e < n_experts
    row_sorted = jnp.where(
        live, row_start[jnp.minimum(sorted_e, n_experts - 1)] + rank,
        n_tiles * tm)                                       # dropped by mode
    src = jnp.full((n_tiles * tm,), -1, jnp.int32).at[row_sorted].set(
        (order // k).astype(jnp.int32), mode="drop")
    dest = jnp.zeros((m,), jnp.int32).at[order].set(
        jnp.where(live, row_sorted, 0).astype(jnp.int32)).reshape(t, k)
    n_used = tile_end[-1]
    tile_ids = jnp.arange(n_tiles, dtype=jnp.int32)
    tile_expert = jnp.searchsorted(tile_end, jnp.minimum(
        tile_ids, jnp.maximum(n_used - 1, 0)), side="right")
    tile_expert = jnp.minimum(tile_expert, n_experts - 1).astype(jnp.int32)
    return src, dest, tile_expert, n_used.reshape(1).astype(jnp.int32), sizes


# ---------------------------------------------------------------------------
# the grouped kernel
# ---------------------------------------------------------------------------

def _grouped_kernel(te_ref, nu_ref, x_ref, wg_ref, wu_ref, wd_ref, o_ref,
                    acc_ref):
    i, f = pl.program_id(0), pl.program_id(1)
    nf = pl.num_programs(1)
    used = i < nu_ref[0]

    @pl.when(f == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(used)
    def _tile():
        x = x_ref[...]                                       # (tm, D)
        prec = _precision(x.dtype)
        nt = (((1,), (1,)), ((), ()))                        # x @ w^T
        g = jax.lax.dot_general(x, wg_ref[0], nt, precision=prec,
                                preferred_element_type=jnp.float32)
        u = jax.lax.dot_general(x, wu_ref[0], nt, precision=prec,
                                preferred_element_type=jnp.float32)
        h = (g * jax.nn.sigmoid(g) * u).astype(x.dtype)      # (tm, tf)
        acc_ref[...] += jax.lax.dot_general(
            h, wd_ref[0], (((1,), (0,)), ((), ())), precision=prec,
            preferred_element_type=jnp.float32)              # (tm, D)

    @pl.when(f == nf - 1)
    def _store():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _grouped_pallas(x_pad, tile_expert, n_used, w_gate, w_up, w_down, *,
                    block_sizes, interpret):
    mp, d = x_pad.shape
    e, f, _ = w_gate.shape
    n_tiles = tile_expert.shape[0]
    tm = mp // n_tiles
    tf = _block_f(f, d, w_gate.dtype.itemsize)
    nf = f // tf

    def w_index(i, j, te, nu):
        # a tile past the last used one keeps the previous block index:
        # nothing is fetched for it
        return (te[i], jnp.where(i < nu[0], j, nf - 1), 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_tiles, nf),
        in_specs=[pl.BlockSpec((tm, d), lambda i, j, *_p: (i, 0)),
                  pl.BlockSpec((1, tf, d), w_index),
                  pl.BlockSpec((1, tf, d), w_index),
                  pl.BlockSpec((1, tf, d), w_index)],
        out_specs=pl.BlockSpec((tm, d), lambda i, j, *_p: (i, 0)),
        scratch_shapes=[pltpu.VMEM((tm, d), jnp.float32)],
    )
    return pl.pallas_call(
        _grouped_kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((mp, d), x_pad.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
        ) if not interpret else None,
        interpret=interpret, name="moe_grouped_ffn",
    )(tile_expert.astype(jnp.int32), n_used.astype(jnp.int32), x_pad,
      w_gate, w_up, w_down)


def _grouped_lax(x_pad, tile_expert, n_used, w_gate, w_up, w_down):
    """The same tiles through XLA: every tile's expert weights gathered
    (sizes of a CPU test, not of a served model)."""
    mp, d = x_pad.shape
    n_tiles = tile_expert.shape[0]
    tm = mp // n_tiles
    prec = _precision(x_pad.dtype)
    xt = x_pad.reshape(n_tiles, tm, d)
    g = jnp.einsum("nmd,nfd->nmf", xt, w_gate[tile_expert], precision=prec,
                   preferred_element_type=jnp.float32)
    u = jnp.einsum("nmd,nfd->nmf", xt, w_up[tile_expert], precision=prec,
                   preferred_element_type=jnp.float32)
    h = (g * jax.nn.sigmoid(g) * u).astype(x_pad.dtype)
    y = jnp.einsum("nmf,nfd->nmd", h, w_down[tile_expert], precision=prec,
                   preferred_element_type=jnp.float32)
    used = jnp.arange(n_tiles)[:, None, None] < n_used[0]
    return jnp.where(used, y, 0.0).astype(x_pad.dtype).reshape(mp, d)


def _grouped_reference(x_pad, tile_expert, n_used, w_gate, w_up, w_down):
    import numpy as np
    x = np.asarray(x_pad, np.float64)
    te, nu = np.asarray(tile_expert), int(np.asarray(n_used)[0])
    wg, wu, wd = (np.asarray(w, np.float64) for w in (w_gate, w_up, w_down))
    n_tiles = te.shape[0]
    tm = x.shape[0] // n_tiles
    out = np.zeros_like(x)
    for i in range(nu):
        rows = x[i * tm:(i + 1) * tm]
        g = rows @ wg[te[i]].T
        h = g / (1.0 + np.exp(-g)) * (rows @ wu[te[i]].T)
        out[i * tm:(i + 1) * tm] = h @ wd[te[i]]
    return jnp.asarray(out).astype(x_pad.dtype)


def _make_grouped_sample(seed):
    import numpy as np
    t, k, e, d, f = ((6, 2, 4, 16, 32), (16, 2, 8, 32, 16),
                     (5, 3, 6, 16, 24))[seed % 3]
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((t, d)), jnp.float32)
    ids = jnp.asarray(np.stack([rng.permutation(e)[:k] for _ in range(t)]),
                      jnp.int32)
    valid = jnp.asarray(rng.uniform(size=t) < 0.8)
    tm = 8
    src, _dest, tile_expert, n_used, _sizes = route_tiles(ids, valid, e, tm)
    x_pad = jnp.where((src >= 0)[:, None], x[jnp.maximum(src, 0)], 0.0)
    w = [jnp.asarray(rng.standard_normal((e, f, d)) * d ** -0.5,
                     jnp.float32) for _ in range(3)]
    return (x_pad, tile_expert, n_used, *w), {}


def grouped_expert_ffn(x, expert_ids, coef, valid, w_gate, w_up, w_down, *,
                       impl: str = "auto", held=None):
    """Every token through every one of its experts, nothing dropped.

    ``x`` (T, D); ``expert_ids`` / ``coef`` (T, K) the experts of a token
    and their weights; ``valid`` (T,) tokens that count (the others get
    zeros and touch no expert); weights ``(E, F, D)``. Returns ``(y (T,
    D) float32, sizes (E,) int32 pairs computed an expert)``.

    ``held=(offset, n_routed)``: the weights are those of experts
    ``offset .. offset + E`` of the ``n_routed`` the router picks from; a
    pair of another expert adds nothing here (:func:`route_tiles`,
    ``held_offset``), ``y`` is this chip's part of the sum, and a tile's rows go
    by the pairs an even router sends here (:func:`held_pairs`)."""
    from paddle_tpu import kernels
    t, k = expert_ids.shape
    e = w_gate.shape[0]
    tm = tile_rows(held_pairs(t * k, e, held), e)
    offset = None if held is None else held[0]
    src, dest, tile_expert, n_used, sizes = route_tiles(
        expert_ids, valid, e, tm, held_offset=offset)
    x_pad = jnp.where((src >= 0)[:, None], x[jnp.maximum(src, 0)],
                      jnp.zeros((), x.dtype))
    y_pad = kernels.dispatch("moe_grouped_ffn", x_pad, tile_expert, n_used,
                             w_gate, w_up, w_down, impl=impl)
    picked = y_pad[dest].astype(jnp.float32)                 # (T, K, D)
    c = jnp.where(valid[:, None], coef, 0.0).astype(jnp.float32)
    if held is not None:        # a pair held elsewhere points at row 0
        c = jnp.where((expert_ids >= offset) & (expert_ids < offset + e),
                      c, 0.0)
    y = jnp.einsum("tk,tkd->td", c, picked, precision=_FP32_DOT)
    return y, sizes


def _register():
    from paddle_tpu import kernels
    kernels.register(kernels.KernelSpec(
        name="moe_grouped_ffn",
        contract=kernels.KernelContract(
            version=1,
            arg_layouts={"x_pad": "(Mp,D)", "tile_expert": "(N,) i32",
                         "n_used": "(1,) i32", "w_gate": "(E,F,D)",
                         "w_up": "(E,F,D)", "w_down": "(E,F,D)"},
            out_layout="(Mp,D)",
            grid="(row tiles, F/tf): a tile's expert from a scalar-"
                 "prefetched table, hidden blocks accumulated in fp32",
            atol=2e-5, rtol=2e-5),
        pallas_fn=_grouped_pallas,
        lax_fn=_grouped_lax,
        reference_fn=_grouped_reference,
        sample_inputs=_make_grouped_sample,
        pallas_sites=("paddle_tpu.ops.grouped_ffn:_grouped_pallas",)))


_register()
