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
from paddle_tpu.models.sparse_moe_lm import SparseMoELM, SparseMoELMConfig

import sparse_moe_reference as ref  # noqa: E402
from serving_taps import (assert_refused, benchmark_config,  # noqa: E402
                          FEATURE_OPTIONS, moved, serve_alone,
                          shared_engines, tapped_engine, traced)

#: float32 on both sides, sums in another order: logits of magnitude ~0.5
#: agree to a few 1e-6; 2e-5 leaves room for two layers of reordering
LOGIT_ATOL = 2e-5

PAGE, CHUNK, TOPK = 4, 12, 16


@pytest.fixture(scope="module")
def model_and_params():
    cfg = SparseMoELMConfig.tiny(kernel_impl="lax")
    model = SparseMoELM(cfg)
    return model, model.init(jax.random.PRNGKey(5))


def _engine(params, impl="lax", **kw):
    return tapped_engine(
        SparseMoELM(SparseMoELMConfig.tiny(kernel_impl=impl)), params,
        num_slots=2, page_size=PAGE, prefill_chunk=CHUNK, attn_impl=impl, **kw)


@pytest.fixture(scope="module")
def engines(model_and_params):
    """``get(impl="lax") -> (engine, its head calls' logits, registry)``,
    one engine an ``impl`` for the module: what a case may assume of it
    (the pages earlier cases published stay mapped) is in
    ``tests/serving_taps.py``."""
    return shared_engines(
        lambda *a, **kw: _engine(model_and_params[1], *a, **kw))


def _reference_rows(model, params, prompt, out):
    # (un-jitted: its few lengths cost less op by op than its compile)
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
        case, impl, model_and_params, engines):
    model, params = model_and_params
    n0, n_new = CASES[case]
    prompt = np.random.default_rng(n0).integers(
        0, model.cfg.vocab_size, n0).astype(np.int32)
    eng, sink, _ = engines(impl)
    out, got = serve_alone(eng, sink, prompt, n_new)
    want = _reference_rows(model, params, prompt, out)
    np.testing.assert_allclose(got, want, atol=LOGIT_ATOL, rtol=0)
    assert (want.argmax(-1) == out).all()


def test_shared_prefix_and_copy_on_write_carry_the_indexer_keys(
        model_and_params, engines):
    """A second request that shares full pages with the first, and a
    verbatim repeat whose borrowed tail page is copied on write: their
    queries score indexer keys that another request's prefill wrote."""
    model, params = model_and_params
    rng = np.random.default_rng(3)
    base = rng.integers(0, model.cfg.vocab_size, 42).astype(np.int32)
    other = np.concatenate([base[:32], rng.integers(
        0, model.cfg.vocab_size, 9).astype(np.int32)])
    eng, sink, reg = engines("lax")
    before, copies = reg.snapshot(), eng.cache.cow_copies_total
    for prompt in (base, other, base):
        out, got = serve_alone(eng, sink, prompt, 6)
        want = _reference_rows(model, params, prompt, out)
        np.testing.assert_allclose(got, want, atol=LOGIT_ATOL, rtol=0)
    assert eng.cache.cow_copies_total > copies
    snap = moved(reg, before)
    assert snap["serving_prefill_tokens_total"] \
        < snap["serving_prompt_tokens_total"]
    eng.cache.check_invariants()


def test_requests_over_a_published_document_decode_folded(model_and_params,
                                                          engines):
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
    model, _ = model_and_params
    eng, _sink, reg = engines("pallas_interpret")
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


def test_step_counters_and_span_attributes(model_and_params, engines):
    model, _ = model_and_params
    eng, _sink, reg = engines("lax")
    prompt = np.random.default_rng(2).integers(
        0, model.cfg.vocab_size, 40).astype(np.int32)
    before = reg.snapshot()
    with traced(eng) as tracer:
        eng.generate_many([prompt], max_new_tokens=6)
    snap = moved(reg, before)
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


@pytest.mark.parametrize("feature", sorted(
    set(FEATURE_OPTIONS) - {"prefix_sharing", "prefix_export"}))
def test_engine_refuses_by_name_what_the_family_does_not_carry(
        feature, model_and_params):
    assert_refused(*model_and_params, feature, repr(feature),
                   page_size=PAGE, attn_impl="auto")


def test_engine_refuses_migration_and_prefix_export_calls(model_and_params,
                                                          engines):
    model, params = model_and_params
    eng = engines("lax")[0]
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
    cfg = benchmark_config("keye_vl2_30b_a3b", 6)
    assert cfg["sizes"]["num_experts"] == 128
    assert cfg["sizes"]["vocab_size"] == 151936
