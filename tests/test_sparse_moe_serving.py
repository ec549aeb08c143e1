"""The sparse-attention / sparse-expert language model through the paged
serving engine, against its plain float32 reference.

Sizes: hidden 64, 4 query heads over 2 KV heads of 16, 2 indexer heads of
8, ``topk`` 16, 8 experts with 2 per token, page 4, 2 layers. Weights are
seeded float32, so what separates the engine from the reference is the
order of float32 sums (the paged kernels fold a page at a time, the
grouped expert kernel a tile at a time) and nothing else.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import inference
from paddle_tpu import observability as obs
from paddle_tpu.models.sparse_moe_lm import SparseMoELM, SparseMoELMConfig
from paddle_tpu import kernels
from paddle_tpu.ops.grouped_ffn import grouped_expert_ffn
from paddle_tpu.serving import decode_attention as DA
from paddle_tpu.serving import sparse_attention as SA

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import sparse_moe_reference as ref  # noqa: E402

#: float32 on both sides, sums in another order: logits of magnitude ~0.5
#: agree to a few 1e-6; 2e-5 leaves room for two layers of reordering
LOGIT_ATOL = 2e-5
#: an index score nearer than this to the selection threshold may fall on
#: either side of it (reordered float32 sums of ~1e-1 terms); the sets
#: are compared only for queries with no score that near
SCORE_EPS = 1e-5

PAGE, CHUNK, TOPK = 4, 12, 16


@pytest.fixture(scope="module")
def model_and_params():
    cfg = SparseMoELMConfig.tiny(kernel_impl="lax")
    model = SparseMoELM(cfg)
    return model, model.init(jax.random.PRNGKey(5))


class _Tap:
    """A serving program whose ``head`` also hands every call's logits
    to the host, in order."""

    def __init__(self, program, sink):
        self._p, self._sink = program, sink
        self.spec = program.spec
        for name in ("embed", "attn_in", "attn_out", "ffn", "param_dtype"):
            setattr(self, name, getattr(program, name))

    def head(self, params, x):
        logits = self._p.head(params, x)
        jax.debug.callback(lambda a: self._sink.append(np.asarray(a)),
                           logits, ordered=True)
        return logits


def _engine(model, params, impl="lax", **kw):
    model = SparseMoELM(SparseMoELMConfig.tiny(kernel_impl=impl))
    reg = obs.MetricsRegistry()
    eng = inference.make_serving_engine(
        model, params, num_slots=2, page_size=PAGE, prefill_chunk=CHUNK,
        max_tokens_per_slot=96, decode_block=2, attn_impl=impl,
        registry=reg, **kw)
    sink = []
    eng.program = _Tap(eng.program, sink)
    return eng, sink, reg


def _serve(eng, sink, prompt, n_new):
    """One request alone in the engine: its tokens and the logits of
    positions ``len(prompt) - 1 .. len(prompt) + n_new - 2``."""
    del sink[:]
    rid = eng.submit(prompt, n_new)
    slot = None
    while not eng.scheduler.idle():
        eng.step()
        for i in eng.scheduler.active_slots():
            slot = i
    jax.effects_barrier()
    out = eng.result(rid)
    # prefill calls hand (lanes, V): the lone request is lane 0, and the
    # call that finished the prompt is the last of them; decode token
    # steps hand (slots, V)
    s_tot = eng.scheduler.num_slots
    calls = list(sink)
    last_prefill = max(i for i, a in enumerate(calls)
                       if a.shape[0] != s_tot or i == 0)
    logits = [calls[last_prefill][0]]
    logits += [a[slot if slot is not None else 0]
               for a in calls[last_prefill + 1:]]
    return out, np.stack(logits[:n_new])


def _reference_rows(model, params, prompt, out):
    ids = jnp.asarray(np.concatenate([prompt, out]))
    with jax.default_matmul_precision("highest"):
        logits = np.asarray(ref.reference_logits(params, ids, model.cfg))
    n0 = len(prompt)
    return logits[n0 - 1:n0 - 1 + len(out)]


CASES = {
    # total tokens stay at or below topk: every query attends to all
    "below_topk": (10, 5),
    # the second chunk (positions 12..23) holds the first query that
    # selects (position 16): both rules inside one chunk
    "straddles_topk_inside_a_chunk": (21, 4),
    # four times topk: every decode step selects 16 of 60+
    "well_past_topk": (61, 8),
}


@pytest.mark.parametrize("impl", ["lax", "pallas_interpret"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_prefill_then_decode_logits_match_the_reference(
        case, impl, model_and_params):
    model, params = model_and_params
    n0, n_new = CASES[case]
    prompt = np.random.default_rng(n0).integers(
        0, model.cfg.vocab_size, n0).astype(np.int32)
    eng, sink, _ = _engine(model, params, impl)
    out, got = _serve(eng, sink, prompt, n_new)
    want = _reference_rows(model, params, prompt, out)
    np.testing.assert_allclose(got, want, atol=LOGIT_ATOL, rtol=0)
    assert (want.argmax(-1) == out).all()


def test_shared_prefix_and_copy_on_write_carry_the_indexer_keys(
        model_and_params):
    """A second request that shares full pages with the first, and a
    verbatim repeat whose borrowed tail page is copied on write: their
    queries score indexer keys that another request's prefill wrote."""
    model, params = model_and_params
    rng = np.random.default_rng(3)
    base = rng.integers(0, model.cfg.vocab_size, 42).astype(np.int32)
    other = np.concatenate([base[:32], rng.integers(
        0, model.cfg.vocab_size, 9).astype(np.int32)])
    eng, sink, reg = _engine(model, params)
    for prompt in (base, other, base):
        out, got = _serve(eng, sink, prompt, 6)
        want = _reference_rows(model, params, prompt, out)
        np.testing.assert_allclose(got, want, atol=LOGIT_ATOL, rtol=0)
    assert eng.cache.cow_copies_total > 0
    snap = reg.snapshot()
    assert snap["serving_prefill_tokens_total"] \
        < snap["serving_prompt_tokens_total"]
    eng.cache.check_invariants()


def test_selected_set_is_the_references(model_and_params):
    """Layer 0's index scores and selections from the program's indexer
    (its keys laid out in shuffled pages, the Pallas body interpreted)
    against the reference's, for a decode query and for a chunk of
    queries, wherever no score lies within SCORE_EPS of the threshold."""
    model, params = model_and_params
    cfg = model.cfg
    n = 56
    prompt = np.random.default_rng(9).integers(
        0, cfg.vocab_size, n).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        _, seen = ref.reference_logits(params, jnp.asarray(prompt), cfg,
                                       return_scores=True)
        x = model.embed(params, jnp.asarray(prompt)[None], None)
        pos = jnp.arange(n, dtype=jnp.int32)[None]
        _, (_k, _v, k_idx), (q_idx, w_idx) = model.attn_in(params, 0, x, pos)
    scores, keep = (np.asarray(a) for a in seen[0])
    pages = n // PAGE
    order = np.random.default_rng(0).permutation(pages) + 1
    pool = np.zeros((pages + 1, cfg.indexer_head_dim, PAGE), np.float32)
    pool[order] = np.asarray(k_idx[0]).reshape(pages, PAGE, -1).transpose(
        0, 2, 1)
    bt = jnp.asarray(order[None], jnp.int32)

    def no_near_tie(t):
        # an exact tie is no hazard: it is broken by position on both
        # sides (with two indexer heads a quarter of the scores are
        # exactly 0, every head's product negative)
        row = np.sort(scores[t, :t + 1])[::-1]
        gap = row[TOPK - 1] - row[TOPK] if t + 1 > TOPK else np.inf
        return gap == 0 or gap > SCORE_EPS

    # a decode query: the last token against all n
    t = n - 1
    got = np.asarray(SA.lightning_index_scores(
        q_idx[:, t:], w_idx[:, t:], jnp.asarray(pool), bt,
        jnp.asarray([n]), impl="pallas_interpret"))[0, 0]
    np.testing.assert_allclose(got, scores[t], atol=SCORE_EPS)
    idx, n_sel = SA.select_decode(jnp.asarray(got[None]), jnp.asarray([n]),
                                  TOPK)
    assert int(n_sel[0]) == TOPK and no_near_tie(t)
    assert set(np.asarray(idx[0]).tolist()) \
        == set(np.nonzero(keep[t])[0].tolist())
    # a chunk of queries straddling topk: positions 12 .. 23
    lo, c = 12, 12
    got = np.asarray(SA.lightning_index_scores(
        q_idx[:, lo:lo + c], w_idx[:, lo:lo + c], jnp.asarray(pool), bt,
        jnp.asarray([lo + c]), impl="pallas_interpret"))
    chosen = np.asarray(SA.select_prefill(
        jnp.asarray(got), jnp.asarray([lo]), jnp.asarray([c]), TOPK))[0] > 0
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
    scores against the value of the last of the top-k, ties to the lower
    position, by the rule ``select_prefill`` has) marks, element for
    element, the tokens ``select_decode``'s indices name, and what
    ``select_prefill`` marks for a query at position ``length - 1``."""
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


def test_requests_over_a_published_document_decode_folded(model_and_params):
    """Two requests that open with the 40 tokens a third published decode
    as one group over ONE copy of its first eight pages (the Pallas
    bodies, interpreted; a group's pages are whole blocks of eight), and
    emit the tokens they emit when the cache shares nothing (the same
    engine, its cache told not to share: the step programs are the ones
    already compiled). The two counters, fed from the tables and lengths
    the host holds: the walks copy the group's 32 shared rows once a
    token step and layer where the slots hold them twice; with nothing
    shared they copy what the slots hold."""
    import dataclasses
    model, params = model_and_params
    eng, _sink, reg = _engine(model, params, "pallas_interpret")
    rng = np.random.default_rng(44)
    draw = lambda n: rng.integers(                               # noqa: E731
        0, model.cfg.vocab_size, n).astype(np.int32)
    document = draw(40)
    eng.generate_many([np.concatenate([document, draw(2)])],
                      max_new_tokens=2)
    asks = [np.concatenate([document, draw(n)]) for n in (3, 6)]
    names = ["serving_sparse_rows_fetched_total",
             "serving_sparse_rows_held_total"]

    def serve(sharing):
        shares = eng.cache.config
        eng.cache.config = dataclasses.replace(shares, share_prefix=sharing)
        before = reg.snapshot()
        try:
            outs = eng.generate_many(asks, max_new_tokens=5)
        finally:
            eng.cache.config = shares
        snap = reg.snapshot()
        return [list(o) for o in outs], [
            int(snap[k] - before.get(k, 0)) for k in names]

    folded, (fetched, held) = serve(True)
    kept = eng.cache.config.kinds[0].groups
    _slots, _tables, groups, _twice, spared = kept.kept
    assert np.asarray(groups[1]).tolist() == [8] and spared == 8 * PAGE
    assert sorted(np.asarray(groups[0])[0, :2]) == [0, 1]
    # blocks of 2 token steps, 2 layers: 32 rows spared each
    assert held > fetched > 0 and (held - fetched) % (32 * 2 * 2) == 0
    alone, (fetched, held) = serve(False)
    assert alone == folded
    assert fetched == held > 0
    assert not np.asarray(kept.kept[2][1]).any()


def test_blocked_benchmark_reference_is_the_plain_one(model_and_params):
    """``benchmark/families/keye_vl2.py`` computes the same reference in
    blocks; here both at one small size, the selection active."""
    model, params = model_and_params
    cfg = model.cfg
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark"))
    from families import keye_vl2
    sizes = keye_vl2.sizes_of(cfg)
    ids = jnp.asarray(np.random.default_rng(1).integers(
        0, cfg.vocab_size, 48), jnp.int32)
    with jax.default_matmul_precision("highest"):
        plain = np.asarray(ref.reference_logits(params, ids, cfg))
        blocked = np.asarray(keye_vl2.reference_logits(
            params, ids[None], sizes, lo=40, rows=8, query_block=16))[0]
        whole = np.asarray(model.forward(params, ids[None]))[0]
    np.testing.assert_allclose(blocked, plain[40:48], atol=LOGIT_ATOL)
    np.testing.assert_allclose(whole, plain, atol=LOGIT_ATOL)


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


def test_step_counters_and_span_attributes(model_and_params):
    model, params = model_and_params
    from paddle_tpu.observability import tracing
    tracer = tracing.Tracer(enabled=True)
    reg = obs.MetricsRegistry()
    eng = inference.make_serving_engine(
        model, params, num_slots=2, page_size=PAGE, prefill_chunk=CHUNK,
        max_tokens_per_slot=96, decode_block=2, attn_impl="lax",
        registry=reg, tracer=tracer)
    prompt = np.random.default_rng(2).integers(
        0, model.cfg.vocab_size, 40).astype(np.int32)
    eng.submit(prompt, 6)
    while not eng.scheduler.idle():
        eng.step()
    snap = reg.snapshot()
    layers, e, k = 2, model.cfg.num_experts, model.cfg.num_experts_per_tok
    # 40 prompt tokens + 5 decode token steps (the 6th token needs no
    # step), rounded up to whole blocks of 2: 6 token steps computed
    tokens = 40 + 6
    assert snap["serving_moe_assignments_total"] == tokens * k * layers
    assert 0 < snap["serving_moe_experts_touched_total"] \
        <= snap["serving_moe_expert_slots_total"]
    assert snap["serving_moe_expert_slots_total"] % (e * layers) == 0
    assert snap["serving_moe_max_expert_tokens_total"] > 0
    seen = sum(range(1, 41)) + sum(range(41, 47))
    assert snap["serving_attn_context_tokens_total"] == seen * layers
    sel = sum(min(p, TOPK) for p in range(1, 47))
    assert snap["serving_attn_selected_tokens_total"] == sel * layers
    # the rounds that dispatched a block (the last only settles one)
    rounds = [s for s in tracer.spans() if s.name == "serving.decode_round"
              and s.attrs["slots_live"]]
    assert rounds and all(
        s.attrs["selected"] == 2 * TOPK * layers
        and s.attrs["experts_touched"] > 0 for s in rounds)


REFUSALS = {
    "tp": dict(tp=2),
    "int8_pages": dict(cache_dtype=jnp.int8),
    "draft": "draft",
    "host_spill": dict(host_spill_pages=4),
    "migration": dict(snapshot_every_blocks=2),
    "tiers": dict(tier="prefill"),
}


@pytest.mark.parametrize("feature", sorted(REFUSALS))
def test_engine_refuses_by_name_what_the_family_does_not_carry(
        feature, model_and_params):
    model, params = model_and_params
    kw = REFUSALS[feature]
    if kw == "draft":
        kw = dict(draft_model=model, draft_params=params)
    with pytest.raises(ValueError, match=repr(feature)):
        inference.make_serving_engine(model, params, num_slots=2,
                                      page_size=PAGE, **kw)


def test_engine_refuses_migration_and_prefix_export_calls(model_and_params):
    model, params = model_and_params
    eng = inference.make_serving_engine(model, params, num_slots=2,
                                        page_size=PAGE, attn_impl="lax")
    with pytest.raises(ValueError, match="'migration'"):
        eng.snapshot_slot(0)
    with pytest.raises(ValueError, match="'migration'"):
        eng.restore_slot({})
    with pytest.raises(ValueError, match="'prefix_export'"):
        eng.export_prefix_pages([1])
    with pytest.raises(ValueError, match="'prefix_export'"):
        eng.import_prefix_pages({})
    assert ("page_read",) not in eng.warmup_plan()
    with pytest.raises(ValueError, match="multiple of page_size"):
        inference.make_serving_engine(model, params, num_slots=2,
                                      page_size=3)


def test_benchmark_configuration_holds_the_published_keys_twice():
    """``configs/keye_vl2_30b_a3b.json`` carries the catalog's numbers at
    its top level (where the driver compares them) and under ``sizes``
    (where the runner reads them): the same, but for the cut depth."""
    import json
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "keye_vl2_30b_a3b.json")) as f:
        cfg = json.load(f)
    for key, value in cfg["sizes"].items():
        assert cfg[key] == value, key
    assert cfg["reduced"] == ["num_hidden_layers"]
    assert cfg["sizes"]["num_hidden_layers"] == 6
    assert cfg["sizes"]["num_experts"] == 128
    assert cfg["sizes"]["vocab_size"] == 151936
