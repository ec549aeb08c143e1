"""The sparse family's selection and kernels on their own, beside the
engine cases of ``tests/test_sparse_moe_serving.py`` (a file of its own for
``--dist loadfile``): the selected set against the reference's, as a mask
by counting, as a mask by sorting and as indices, the decode walk over
shared pages, the expert layer."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import kernels
from paddle_tpu.models.sparse_moe_lm import SparseMoELM, SparseMoELMConfig
from paddle_tpu.ops.grouped_ffn import grouped_expert_ffn
from paddle_tpu.serving import decode_attention as DA
from paddle_tpu.serving import sparse_attention as SA

import sparse_moe_reference as ref

#: an index score nearer than this to the selection threshold may fall on
#: either side of it (reordered float32 sums of ~1e-1 terms); the sets
#: are compared only for queries with no score that near
SCORE_EPS = 1e-5


def test_selected_set_is_the_references():
    """Layer 0's index scores and selections from the program's indexer
    (its keys laid out in shuffled pages, the Pallas body interpreted)
    against the reference's, for a decode query and for a chunk of
    queries, wherever no score lies within SCORE_EPS of the threshold."""
    model = SparseMoELM(SparseMoELMConfig.tiny(kernel_impl="lax"))
    params, cfg = model.init(jax.random.PRNGKey(5)), model.cfg
    n, page, topk = 56, 4, cfg.indexer_topk
    prompt = np.random.default_rng(9).integers(
        0, cfg.vocab_size, n).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        _, seen = ref.reference_logits(params, jnp.asarray(prompt), cfg,
                                       return_scores=True)
        x = model.embed(params, jnp.asarray(prompt)[None], None)
        pos = jnp.arange(n, dtype=jnp.int32)[None]
        _, (_k, _v, k_idx), (q_idx, w_idx) = model.attn_in(params, 0, x, pos)
    scores, keep = (np.asarray(a) for a in seen[0])
    pages = n // page
    order = np.random.default_rng(0).permutation(pages) + 1
    pool = np.zeros((pages + 1, cfg.indexer_head_dim, page), np.float32)
    pool[order] = np.asarray(k_idx[0]).reshape(pages, page, -1).transpose(
        0, 2, 1)
    bt = jnp.asarray(order[None], jnp.int32)

    def no_near_tie(t):
        # an exact tie is no hazard: it is broken by position on both
        # sides (with two indexer heads a quarter of the scores are
        # exactly 0, every head's product negative)
        row = np.sort(scores[t, :t + 1])[::-1]
        gap = row[topk - 1] - row[topk] if t + 1 > topk else np.inf
        return gap == 0 or gap > SCORE_EPS

    # a decode query: the last token against all n
    t = n - 1
    got = np.asarray(SA.lightning_index_scores(
        q_idx[:, t:], w_idx[:, t:], jnp.asarray(pool), bt,
        jnp.asarray([n]), impl="pallas_interpret"))[0, 0]
    np.testing.assert_allclose(got, scores[t], atol=SCORE_EPS)
    idx, n_sel = SA.select_decode(jnp.asarray(got[None]), jnp.asarray([n]),
                                  topk)
    assert int(n_sel[0]) == topk and no_near_tie(t)
    assert set(np.asarray(idx[0]).tolist()) \
        == set(np.nonzero(keep[t])[0].tolist())
    # a chunk of queries straddling topk: positions 12 .. 23
    lo, c = 12, 12
    got = np.asarray(SA.lightning_index_scores(
        q_idx[:, lo:lo + c], w_idx[:, lo:lo + c], jnp.asarray(pool), bt,
        jnp.asarray([lo + c]), impl="pallas_interpret"))
    chosen = np.asarray(SA.select_prefill(
        jnp.asarray(got), jnp.asarray([lo]), jnp.asarray([c]), topk))[0] > 0
    checked = 0
    for r in range(c):
        if no_near_tie(lo + r):
            checked += 1
            assert (chosen[r] == keep[lo + r]).all(), lo + r
    assert checked >= c - 2


SELECTIONS = {
    # (scores of one slot's T = 12 tokens, its length): ties at the
    # threshold go to the lower position; a slot of at most topk tokens
    # selects them all; a dead slot nothing
    "distinct_scores": ([.9, .1, .8, .2, .7, .3, .6, .4, .5, .0, .95, .05], 12),
    "ties_at_the_threshold": ([.9, .5, .8, .5, .5, .3, .5, .4, .5, .0, .5, .5],
                              12),
    "every_score_the_same": ([.5] * 12, 11),
    "a_tie_past_the_length": ([.9, .1, .8, .2, .7, .3, .6, .9, .9, .9, .9, .9],
                              7),
    "exactly_topk_tokens": ([.9, .1, .8, .2, .7, .3, .6, .4, .5, .0, .9, .9],
                            4),
    "fewer_than_topk_tokens": ([.9, .1, .8, .2, .7, .3, .6, .4, .5, .0, .9,
                                .9], 3),
    "one_token": ([.0] * 12, 1),
    "a_dead_slot": ([.9, .1, .8, .2, .7, .3, .6, .4, .5, .0, .9, .9], 0),
}


@pytest.mark.parametrize("case", sorted(SELECTIONS))
def test_the_mask_from_the_scores_is_the_scatter_of_the_selected_indices(
        case):
    """What the decode step hands the kernel (``select_decode_mask``:
    scores against the value of the ``topk``-th largest, found by
    counting, ties to the lower position, by the rule ``select_prefill``
    has) marks, element for element, the tokens the sort's indices name
    (``select_decode``), and what ``select_prefill`` marks for a query at
    position ``length - 1``."""
    topk = 4
    row, n = SELECTIONS[case]
    scores = jnp.asarray([row, row[::-1]], jnp.float32)
    lengths = jnp.asarray([n, n], jnp.int32)
    idx, n_sel = SA.select_decode(scores, lengths, topk)
    want = np.zeros(scores.shape, np.float32)
    for sl in range(2):
        want[sl, np.asarray(idx[sl, :int(n_sel[sl])])] = 1.0
    got = np.asarray(SA.select_decode_mask(scores, lengths, topk))
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(got, np.asarray(SA.select_prefill(
        scores[:, None], lengths - 1, None, topk)[:, 0]))
    assert want.sum(1).tolist() == [min(n, topk)] * 2


def _score_rows(kind, rows, t, seed=0):
    """Rows of ``t`` index scores: drawn; of a handful of values, so that
    ties cross the threshold and outnumber the places left; weighted sums
    of relu'd products, 70% of them exact zeros of either sign; one
    value."""
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((rows, t)).astype(np.float32)
    if kind == "a_handful_of_values":
        x = np.round(x)
    elif kind == "zeros_of_both_signs":
        x = np.maximum(x, 0.0) * (rng.random((rows, t)) < 0.3)
        x = np.where(rng.random((rows, t)) < 0.5, -x, x)
        assert (x == 0).mean() > 0.6 and np.signbit(x[x == 0]).any() \
            and not np.signbit(x[x == 0]).all()
    elif kind == "every_score_the_same":
        x = np.full_like(x, -0.75)
    return jnp.asarray(x)


@pytest.mark.parametrize("shape", ["decode", "prefill"])
@pytest.mark.parametrize("kind", [
    "drawn", "a_handful_of_values", "zeros_of_both_signs",
    "every_score_the_same"])
def test_the_mask_by_counting_is_the_mask_by_sorting(kind, shape):
    """The engine's selection (``topk_selection_mask``: the value of the
    ``topk``-th score by bisection, its Pallas body interpreted and the
    same in ``jnp``) marks, element for element, what the ``lax.top_k``
    statement of the rule marks: rows of one call that see nothing, fewer
    than ``topk``, ``topk``, one more, the whole row and anything
    between, as decode's ``(S, T)`` and as prefill's ``(S, C, T)``; a row
    that sees more than ``topk`` gets exactly ``topk``, zeros of either
    sign being one value."""
    topk, t = 24, 200
    if shape == "decode":
        n = np.asarray([0, 1, topk - 1, topk, topk + 1, t, 77, 150, t - 1,
                        t + 5, 31], np.int32)
        scores = _score_rows(kind, len(n), t)
        got = {impl: np.asarray(SA.select_decode_mask(
            scores, jnp.asarray(n), topk, impl=impl))
            for impl in ("lax", "pallas_interpret")}
    else:
        starts = np.asarray([0, topk - 3, t - 6, 90], np.int32)
        c = 6
        scores = _score_rows(kind, len(starts) * c, t).reshape(
            len(starts), c, t)
        got = {impl: np.asarray(SA.select_prefill(
            scores, jnp.asarray(starts), None, topk, impl=impl))
            for impl in ("lax", "pallas_interpret")}
        n = starts[:, None] + np.arange(1, c + 1)
    want = np.asarray(SA.selected_by_sort(scores, jnp.asarray(n), topk))
    for impl, mask in got.items():
        np.testing.assert_array_equal(mask, want, err_msg=impl)
    np.testing.assert_array_equal(want.sum(-1), np.minimum(n, topk))
    assert not want[np.arange(t) >= n[..., None]].any()


# -- the decode body: whole pages of the pools, walked under the selection ---

D_H, D_KV, D_DH, D_PS, D_TOPK = 4, 2, 16, 4, 24


def _documents(n_slots, sharers, shared, lengths, pool, mp, seed=0):
    """``n_slots`` slots over one pool of pages of 4 tokens, the first
    ``sharers`` of them opening with the same ``shared`` pages; float32
    queries holding bf16 values, so a bf16 pool's products are exact."""
    rng = np.random.default_rng(seed)
    num_pages = n_slots * mp + 1
    dtype = jnp.bfloat16 if pool == "bf16" else jnp.float32
    q = jnp.asarray(rng.standard_normal((n_slots, D_H, D_DH)),
                    jnp.bfloat16).astype(jnp.float32)
    kp, vp = (jnp.asarray(rng.standard_normal(
        (num_pages, D_PS, D_KV * D_DH)), jnp.bfloat16).astype(dtype)
        for _ in range(2))
    tables = (1 + rng.permutation(num_pages - 1)[:n_slots * mp]).reshape(
        n_slots, mp).astype(np.int32)
    tables[:sharers, :shared] = tables[0, :shared]
    lengths = np.asarray(lengths, np.int32)
    scores = jnp.asarray(rng.standard_normal((n_slots, mp * D_PS)),
                         jnp.float32)
    selected = SA.select_decode_mask(scores, jnp.asarray(lengths), D_TOPK)
    return (q, kp, vp, jnp.asarray(tables), selected,
            jnp.asarray(lengths)), tables, lengths


def _one_group(members, pages, n_slots):
    """A group written out, as the engine's grouping would never make it
    (a group of one, pages that are no whole block, a dead member)."""
    group_slots = np.full((max(n_slots // 2, 1), DA.DECODE_GROUP), -1,
                          np.int32)
    group_slots[0, :len(members)] = members
    group_pages = np.zeros((group_slots.shape[0],), np.int32)
    group_pages[0] = pages
    shared_pages = np.zeros((n_slots,), np.int32)
    shared_pages[list(members)] = pages
    return group_slots, group_pages, shared_pages


# name: (slots, how many of them open with the same pages, that many
# pages, lengths, the table's width, the pool, pages a block, a group by
# hand (members, pages) or None for the engine's grouping, the members a
# group of the engine's then has)
WALKS = {
    "a_group_of_one": (3, 1, 8, [40, 33, 48], 12, "f32", 4, ([0], 8), None),
    "a_pair": (3, 2, 8, [32, 33, 48], 12, "f32", 4, None, [2]),
    "four_of_a_document": (5, 4, 8, [32, 33, 48, 41, 17], 12, "f32", 8,
                           None, [4]),
    "eight": (9, 8, 8, [32, 33, 48, 41, 37, 45, 36, 44, 48], 12, "f32", 2,
              None, [8]),
    # the ninth sharer is a group of one to the engine: walked alone
    "nine_is_eight_and_one_alone": (
        9, 9, 8, [32, 33, 48, 41, 37, 45, 36, 44, 39], 12, "f32", 4, None,
        [8]),
    "nothing_shared": (3, 0, 0, [32, 33, 48], 12, "f32", 4, None, []),
    # seven pages are no whole block of the engine's: nothing is grouped
    "seven_shared_pages_are_walked_alone": (3, 3, 7, [32, 33, 48], 12,
                                            "f32", 4, None, []),
    # by hand the kernel folds the whole blocks of 4 among them and walks
    # the other three a slot
    "seven_shared_pages_by_hand": (3, 3, 7, [32, 33, 48], 12, "f32", 4,
                                   ([0, 1, 2], 7), None),
    "a_dead_member": (4, 4, 8, [40, 0, 48, 33], 12, "f32", 4,
                      ([0, 1, 2, 3], 8), None),
    "a_long_document": (3, 3, 120, [480, 481, 496], 124, "f32", 8, None,
                        [3]),
    "a_bf16_pool": (5, 4, 8, [32, 33, 48, 41, 17], 12, "bf16", 8, None,
                    [4]),
}


@pytest.mark.parametrize("case", sorted(WALKS))
def test_sparse_decode_walks_shared_pages_once_a_group(case):
    """``sparse_paged_decode``'s Pallas bodies (interpreted) against
    attention a slot over its selected tokens in NumPy: groups of 1, 2,
    4, 8 and 9 slots of one document, shared runs of 0, 7, 8 and 120
    pages, lengths at a page's end, one past it and the whole table,
    float32 and bf16 pools. A folded member's output is the same slot's
    walked alone within the contract's tolerance, and neither fallback
    asks who shares what."""
    (n_slots, sharers, shared, lengths, mp, pool, pb, by_hand,
     members) = WALKS[case]
    args, tables, lengths = _documents(n_slots, sharers, shared, lengths,
                                       pool, mp)
    spec = kernels.get("sparse_paged_decode")
    if by_hand is None:
        groups = DA.decode_groups(tables, lengths,
                                         np.flatnonzero(lengths), D_PS)
        held = (groups[0] >= 0).sum(1)
        assert sorted(held[held > 0]) == members
        assert set(groups[1][held > 0]) <= {shared}
    else:
        groups = _one_group(*by_hand, n_slots)
    alone = _one_group([], 0, n_slots)
    run = lambda g: np.asarray(kernels.dispatch(               # noqa: E731
        "sparse_paged_decode", *args, *map(jnp.asarray, g),
        impl="pallas_interpret", block_sizes={"pages_per_block": pb}))
    want = np.asarray(spec.reference_fn(*args))
    tol = dict(atol=spec.contract.atol, rtol=spec.contract.rtol)
    folded = run(groups)
    np.testing.assert_allclose(folded, want, **tol)
    if np.any(groups[1]):
        np.testing.assert_allclose(folded, run(alone), **tol)
    assert not folded[lengths == 0].any()
    for fn in (spec.lax_fn, spec.reference_fn):
        np.testing.assert_array_equal(
            np.asarray(fn(*args, *map(jnp.asarray, groups))),
            np.asarray(fn(*args, *map(jnp.asarray, alone))))
    np.testing.assert_allclose(np.asarray(spec.lax_fn(*args)), want, **tol)


def test_selection_as_indices_is_the_selection_as_a_mask():
    """``sparse_paged_decode_attention`` keeps its signature for whoever
    holds a selection as indices (the benchmark's selection replay): the
    mask it scatters them into and the extent it reads off them give the
    attention the mask from the scores gives."""
    args, _tables, _lengths = _documents(4, 0, 0, [0, 1, 30, 48], "f32", 12)
    q, kp, vp, bt, _selected, ln = args
    scores = jnp.asarray(np.random.default_rng(0).standard_normal(
        (4, 48)), jnp.float32)
    idx, n_sel = SA.select_decode(scores, ln, D_TOPK)
    selected = SA.select_decode_mask(scores, ln, D_TOPK)
    for impl in ("lax", "pallas_interpret"):
        np.testing.assert_array_equal(
            np.asarray(SA.sparse_paged_decode_attention(
                q, kp, vp, bt, idx, n_sel, impl=impl)),
            np.asarray(SA.selected_decode_attention(
                q, kp, vp, bt, selected, ln, impl=impl)))


@pytest.mark.parametrize("impl", ["lax", "pallas_interpret"])
def test_expert_layer_drops_nothing_under_a_skewed_router(impl):
    """One expert takes half the tokens: every token-expert pair is
    computed (a capacity would have dropped most of that expert's)."""
    rng = np.random.default_rng(0)
    t, k, e, d, f = 32, 2, 8, 32, 16
    x = rng.standard_normal((t, d)).astype(np.float32)
    ids = np.stack([rng.permutation(np.arange(1, e))[:k] for _ in range(t)])
    ids[::2, 0] = 0                          # expert 0: every second token
    coef = rng.uniform(0.1, 1.0, (t, k)).astype(np.float32)
    wg, wu, wd = (rng.standard_normal((e, f, d)).astype(np.float32)
                  * d ** -0.5 for _ in range(3))
    valid = np.ones(t, bool)
    with jax.default_matmul_precision("highest"):
        y, sizes = grouped_expert_ffn(
            jnp.asarray(x), jnp.asarray(ids, jnp.int32), jnp.asarray(coef),
            jnp.asarray(valid), *(jnp.asarray(w) for w in (wg, wu, wd)),
            impl=impl)
    want = np.zeros((t, d))
    for ti in range(t):                      # the per-token loop
        for kk in range(k):
            g = wg[ids[ti, kk]] @ x[ti].astype(np.float64)
            h = g / (1.0 + np.exp(-g)) * (wu[ids[ti, kk]] @ x[ti])
            want[ti] += coef[ti, kk] * (h @ wd[ids[ti, kk]])
    sizes = np.asarray(sizes)
    assert sizes.sum() == t * k and sizes[0] == t // 2
    # float32 sums of ~32 products of O(1) terms against float64
    np.testing.assert_allclose(np.asarray(y), want, atol=2e-5)
