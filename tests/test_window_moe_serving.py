"""Window and full attention layers over a sigmoid-routed expert share,
through the paged serving engine, against the plain float32 reference
(ISSUE 40).

Sizes: hidden 64, 4 query heads over 2 KV heads of 16, five layers
(window, window, window, full, window; the first MLP dense, then 8 routed
experts of 32 with 3 a token, of which 2 are held, beside a shared one),
window 8, page 4, chunk 4: a window layer's ring is its window's 2 pages
and 2 of room a slot (two slots, so a budget of two chunks a step: one
prompt alone gives a call a run of two).
Weights are seeded float32 as ``init`` draws them, so what separates the
engine from the reference is the order of float32 sums (the paged
kernels' page folds, the grouped expert kernel's tiles) and nothing else.
ONE engine an ``impl`` serves the cases that take this geometry
(``engines``, module-scoped: a case then compiles only the widths no
earlier one met), a request at a time; a slot's rings hold what its last
request left, as they do in a serving process.
"""

import dataclasses
import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import inference
from paddle_tpu import observability as obs
from paddle_tpu.models import WindowMoELM, WindowMoELMConfig
from paddle_tpu.serving.program import FEATURES

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
import window_moe_reference as ref  # noqa: E402
from serving_taps import (assert_close, assert_refused,  # noqa: E402
                          FEATURE_OPTIONS, moved,
                          reference_rows, runs_against_one_chunk_a_slot,
                          serve_alone, shared_engines, tapped_engine, traced)
from serving_taps import prompt as _prompt  # noqa: E402

#: float32 on both sides, sums in another order: 2e-5 OF THE LARGEST
#: LOGIT (the logits are of magnitude 0.5). Sound runs read under 2e-6 of
#: it; a window off by one token, a ring page too few or the shared
#: expert left out each read over 1e-3 (the controls below)
LOGIT_RTOL = 2e-5
_assert_close = functools.partial(assert_close, rtol=LOGIT_RTOL)

PAGE, CHUNK, WINDOW = 4, 4, 8


@pytest.fixture(scope="module")
def model_and_params():
    model = WindowMoELM(WindowMoELMConfig.tiny(kernel_impl="lax"))
    return model, model.init(jax.random.PRNGKey(5))


def _engine(params, impl="lax", slots=2, **kw):
    return tapped_engine(
        WindowMoELM(WindowMoELMConfig.tiny(kernel_impl=impl)), params,
        **{**dict(num_slots=slots, page_size=PAGE, prefill_chunk=CHUNK,
                  attn_impl=impl), **kw})


@pytest.fixture(scope="module")
def engines(model_and_params):
    """``get(impl="lax") -> (engine, its head calls' logits, registry)``,
    one engine an ``impl`` for the module: what a case may assume of it
    is in ``tests/serving_taps.py``."""
    return shared_engines(
        lambda *a, **kw: _engine(model_and_params[1], *a, **kw))


_rows = reference_rows(ref.reference_logits)


def _reference_rows(model, params, prompt, out, **over):
    return _rows(params, prompt, out, ref.sizes_of(model.cfg, **over))


CASES = {
    # every token of the request inside one window and one ring lap
    "inside_the_window": (3, 4),
    # the prompt ends a token short of the window; decode crosses it
    "decode_crosses_the_window": (WINDOW - 1, 5),
    # a prompt of one page and a token: the second chunk reads the first's
    # page; decode crosses a page boundary
    "crosses_a_page": (PAGE + 1, 6),
    # 10 + 9 tokens: the ring's 4 pages (2 of room: two lanes a call) hold
    # 16, so decode writes over the page of tokens 0-3 (a recycled page)
    "decode_recycles_pages": (10, 9),
    # the prompt itself laps the ring twice (29 tokens, 8 chunks), ends
    # inside a page; 11 new tokens lap it again
    "prompt_laps_the_ring": (29, 11),
    # the prompt ends on a chunk and page edge
    "ends_on_a_page_edge": (2 * PAGE, 5),
}


@pytest.mark.parametrize("case, impl", [
    ("inside_the_window", "lax"), ("decode_crosses_the_window", "lax"),
    ("crosses_a_page", "pallas_interpret"), ("decode_recycles_pages", "lax"),
    ("decode_recycles_pages", "pallas_interpret"),
    ("prompt_laps_the_ring", "pallas_interpret"),
    ("prompt_laps_the_ring", "lax"), ("ends_on_a_page_edge", "lax")])
def test_prefill_then_decode_through_the_cache_gives_the_references_logits(
        case, impl, model_and_params, engines):
    model, params = model_and_params
    n_prompt, n_new = CASES[case]
    eng, sink, _ = engines(impl)
    prompt = _prompt(n_prompt)
    out, logits = serve_alone(eng, sink, prompt, n_new)
    assert len(out) == n_new
    _assert_close(logits, _reference_rows(model, params, prompt, out))


@pytest.fixture(scope="module")
def served(engines):
    """One request served once for the controls: (prompt, tokens,
    logits)."""
    eng, sink, _ = engines("lax")
    prompt = _prompt(21)
    return (prompt,) + serve_alone(eng, sink, prompt, 7)


@pytest.mark.parametrize("control", ["window_one_longer", "every_layer_full",
                                     "no_shared_expert"])
def test_the_tolerance_tells_a_wrong_window_or_a_missing_expert(
        control, model_and_params, served):
    """What ``LOGIT_RTOL`` must refuse: the reference with the window a
    token longer, with every layer full, or without the shared expert."""
    model, params = model_and_params
    prompt, out, logits = served
    over, tree = {}, params
    if control == "window_one_longer":
        over = {"sliding_window": WINDOW + 1}
    elif control == "every_layer_full":
        # (the rotary embedding stays where it was: only the mask goes)
        over = {"sliding_window": 10 ** 6}
    else:
        zero = jax.tree_util.tree_map(jnp.zeros_like, params)
        tree = jax.tree_util.tree_map(lambda a: a, params)
        for i, lp in tree["layers"].items():
            if "shared" in lp:
                lp["shared"] = zero["layers"][i]["shared"]
    want = _reference_rows(model, tree, prompt, out, **over)
    worst = np.abs(logits - want).max() / np.abs(want).max()
    assert worst > 50 * LOGIT_RTOL, worst


def test_two_requests_side_by_side_keep_to_their_own_rings(
        model_and_params, engines):
    """Two slots of different lengths in one batch: each slot's window
    layers read its own ring (a slot's pages are found by the slot)."""
    model, params = model_and_params
    eng = engines("pallas_interpret")[0]
    prompts = [_prompt(17), _prompt(6)]
    outs = eng.generate_many(prompts, max_new_tokens=9)
    for prompt, out in zip(prompts, outs):
        want = _reference_rows(model, params, prompt, out)
        assert (want.argmax(-1) == out).all()


def test_engine_decodes_window_layers_through_the_body_that_walks_pages():
    """Pages of whole tiles (128 lanes, 8 float32 rows): the window
    layers' decode goes through the walking body, a ring of 4 pages (2 of
    room) that 40 tokens lap. (The one case where an engine's ring places what that
    body walks: ``tests/test_window_moe_kernels.py`` holds the body alone
    to the reference under a window, ``tests/test_kernels_registry.py`` an
    engine to it without one; the engines above, with pages of 4 rows,
    interpret the older pipelined body.)"""
    cfg = WindowMoELMConfig.tiny(
        hidden_size=128, num_attention_heads=4, num_key_value_heads=2,
        head_dim=64, sliding_window=16, kernel_impl="pallas_interpret")
    model = WindowMoELM(cfg)
    params = model.init(jax.random.PRNGKey(1))
    eng = inference.make_serving_engine(
        model, params, num_slots=2, page_size=8, prefill_chunk=8,
        max_tokens_per_slot=64, attn_impl="pallas_interpret",
        decode_block=2, registry=obs.MetricsRegistry())
    prompt = _prompt(19)
    out = eng.generate_many([prompt], max_new_tokens=21)[0]
    ids = jnp.asarray(np.concatenate([prompt, out]))
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref.reference_logits(params, ids, ref.sizes_of(cfg)))
    assert (want[18:18 + 21].argmax(-1) == out).all()


# -- a call that carries runs (ISSUE 54) -----------------------------------------

#: prompt lengths, served together in four slots under a budget of three
#: chunks a step: a ring of the window's 2 pages and 3 of room
RUN_CASES = {
    # 19 tokens: a run of three chunks, then one of two that ends inside
    # a page (the lane that ends the prompt gives the first token)
    "ends_inside_a_page": (4 * PAGE + 3,),
    # a prompt shorter than a page beside one of two runs
    "shorter_than_a_page": (3, 5 * PAGE + 1),
    # four prompts for three lanes: the nearest its first token first
    "more_slots_than_lanes": (9, 14, 6, 21),
    # 43 tokens lap the ring of 20 twice, a run at a time
    "laps_the_ring": (43, 2),
}


@pytest.fixture(scope="module")
def run_engine(model_and_params):
    model, params = model_and_params
    return inference.make_serving_engine(
        model, params, num_slots=4, page_size=PAGE, prefill_chunk=CHUNK,
        prefill_budget=3 * CHUNK, max_tokens_per_slot=64, decode_block=2,
        attn_impl="lax", registry=obs.MetricsRegistry())


@pytest.mark.parametrize("case", sorted(RUN_CASES))
def test_calls_that_carry_runs_give_the_tokens_of_one_chunk_a_slot(
        case, run_engine):
    """The same requests on the same programs, calls formed as the engine
    forms them and held to one chunk a slot: token for token the same,
    every ring sound after every step (``Ring.check``)."""
    eng = run_engine
    ring = next(k for k in eng.cache.config.kinds if k.by_slot)
    assert (ring.prefill_run, ring.ring_pages, eng._run_limit) == (3, 5, 3)
    prompts = [_prompt(n, seed=540 + n) for n in RUN_CASES[case]]
    (got, calls), (want, plain) = runs_against_one_chunk_a_slot(eng, prompts)
    assert got == want and all(len(t) == 5 for t in got)
    longest = max(c[4] for step in calls for c in step)
    assert longest == min(3, -(-max(RUN_CASES[case]) // CHUNK))
    # fewer calls for the same prompt tokens, and never two in a step
    # where the second would be a few lanes beside the first
    assert sum(c[3] for step in calls for c in step) \
        == sum(c[3] for step in plain for c in step) == sum(RUN_CASES[case])
    assert sum(map(len, calls)) <= sum(map(len, plain))
    assert all(len(step) == 1 for step in calls)


def test_warmed_buckets_cover_every_call_a_round_can_form(run_engine):
    """After ``warmup()`` a served run whose calls carry runs compiles
    nothing: every (width, lanes) a round forms is in the plan."""
    from paddle_tpu.analysis.hlo_lint import serving_bucket_coverage
    model, params = run_engine.model, run_engine.params
    eng = inference.make_serving_engine(
        model, params, num_slots=4, page_size=PAGE, prefill_chunk=CHUNK,
        prefill_budget=3 * CHUNK, max_tokens_per_slot=32, decode_block=2,
        attn_impl="lax", registry=obs.MetricsRegistry())
    assert serving_bucket_coverage(eng) == []
    assert sorted({sig[2] for sig in eng.warmup_plan()
                   if sig[0] == "prefill"}) == [1, 2, 4]
    eng.warmup(cost_gauges=False)
    det = obs.RecompileDetector("runs", warmup=0,
                                registry=obs.MetricsRegistry())
    outs = eng.generate_many([_prompt(n, seed=n) for n in (21, 3, 14, 9, 17)],
                             max_new_tokens=6)
    det.check()
    assert det.recompiles == 0 and all(len(o) == 6 for o in outs)
    served = {("prefill", c[2], c[1]) for r in eng.anatomy.records()
              for c in r.get("prefill_calls", ())}
    assert served and served <= eng.warmed_signatures
    assert max(c[5] for r in eng.anatomy.records()
               for c in r.get("prefill_calls", ())) == 3


# -- the engine ---------------------------------------------------------------

@pytest.mark.parametrize("feature", sorted(
    set(FEATURE_OPTIONS) - {"prefix_export"}))
def test_every_option_the_program_does_not_carry_is_refused_by_name(
        feature, model_and_params):
    model, params = model_and_params
    assert feature in FEATURES and not model.serving().spec.supports
    assert_refused(model, params, feature, f"WindowMoELM.*{feature!r}",
                   page_size=PAGE, prefill_chunk=CHUNK, attn_impl="auto")


def test_a_chunk_wider_than_a_page_is_refused(model_and_params):
    model, params = model_and_params
    with pytest.raises(ValueError, match="prefill_chunk=8 > page_size=4"):
        inference.make_serving_engine(model, params, num_slots=2,
                                      page_size=PAGE, prefill_chunk=8)


def test_counters_and_spans_of_the_two_layer_kinds(engines):
    """One request of 10 + 9 tokens alone: every series of ISSUE 40 from
    the lengths the host holds, one read-back a block."""
    eng, _sink, reg = engines("lax")
    before = reg.snapshot()
    with traced(eng) as tracer:
        eng.generate_many([_prompt(10)], max_new_tokens=9)
    snap, gauges = moved(reg, before), reg.snapshot()
    row = 2 * 2 * 16 * 4                     # K and V of a token, a layer
    page = PAGE * row
    # pools: 4 window layers of 2 x 4 + 1 pages, one full of 49
    assert gauges['serving_kv_pool_bytes{layers="window"}'] == 4 * 9 * page
    assert gauges['serving_kv_pool_bytes{layers="full"}'] \
        == eng.cache.config.num_pages * page
    # prefill calls at 0 (a run of two chunks) and 8 tokens held, what
    # a slot holds counted once a call; decode blocks of 2 from 10 on
    held = [0, 8] + [10, 12, 14, 16]
    pages = [-(-n // PAGE) for n in held]
    assert snap['serving_kv_resident_bytes_total{layers="full"}'] \
        == sum(pages) * page
    assert snap['serving_kv_resident_bytes_total{layers="window"}'] \
        == sum(min(p, 4) for p in pages) * 4 * page
    # the ring's first lap is 16 tokens: tokens 16-18 enter 1 more page
    assert snap["serving_window_pages_recycled_total"] == 1 * 4
    # a decode token step at L tokens held reads L + 1 rows of the full
    # layer and min(L + 1, 8) of each window layer
    steps = range(10, 18)
    assert snap['serving_decode_kv_bytes_total{kind="live"}'] == row * sum(
        (n + 1) + 4 * min(n + 1, WINDOW) for n in steps)
    assert snap["serving_moe_routed_pairs_total"] \
        == (10 + len(steps)) * 3 * 4          # tokens x K x sparse layers
    assert 0 < snap["serving_moe_assignments_total"] \
        < snap["serving_moe_routed_pairs_total"]
    assert snap['serving_device_readbacks_total{phase="decode"}'] \
        == snap["serving_steps_total"] - 1
    assert snap.get('serving_device_readbacks_total{phase="prefill"}', 0) == 0
    spans = tracer.spans()
    rounds = [s for s in spans if s.name == "serving.decode_round"
              and s.attrs.get("slots_live")]
    calls = [s for s in spans if s.name == "serving.prefill_call"]
    assert sum(s.attrs["window_pages"] for s in rounds + calls) == 4
    assert [(s.attrs["lanes_live"], s.attrs["slots"]) for s in calls] \
        == [(2, 1), (1, 1)]
    assert all("pairs_held" in s.attrs for s in rounds)


def test_a_program_of_one_layer_kind_binds_none_of_the_new_series():
    from paddle_tpu.models.gpt import GPT, GPTConfig
    model = GPT(GPTConfig.tiny())
    reg = obs.MetricsRegistry()
    eng = inference.make_serving_engine(
        model, model.init(jax.random.PRNGKey(0)), num_slots=2, page_size=8,
        prefill_chunk=16, attn_impl="lax", registry=reg)
    eng.generate_many([_prompt(5)], max_new_tokens=4)
    assert not [k for k in reg.snapshot()
                if "window" in k or "resident" in k or "pool_bytes" in k
                or "routed_pairs" in k or "latent" in k]
    assert eng.cache.bytes_per_slot() == 0
    assert eng.cache.capacity_bytes() \
        == eng.cache.bytes_per_page() * (eng.cache.config.num_pages - 1)


# -- the benchmark's copy -------------------------------------------------------

@pytest.fixture(scope="module")
def family():
    sys.path.insert(0, os.path.join(ROOT, "benchmark"))
    from families import k_exaone
    return k_exaone


def test_benchmark_reference_is_the_plain_reference(model_and_params,
                                                    family):
    """``families/k_exaone.py`` computes the same pass in blocks (queries
    8 at a time against the keys their windows reach, the dense MLP's
    hidden units and the vocabulary in pieces, the rows asked for only):
    held to the plain one here, with the chip's share of the experts."""
    model, params = model_and_params
    ids = jnp.asarray(_prompt(40))
    sizes = family.sizes_of(model.cfg)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref.reference_logits(params, ids,
                                               ref.sizes_of(model.cfg)))
        got, sel = family.reference_logits(
            params, ids[None], sizes, lo=jnp.asarray(7), rows=24,
            query_block=8, vocab_block=32, probe=jnp.zeros((8,), jnp.int32),
            hidden_block=32)
    assert sel.shape == (0,)
    np.testing.assert_allclose(np.asarray(got)[0], want[7:31], rtol=0,
                               atol=2e-6 * np.abs(want).max())
    built = family.build(sizes, interpret=True).cfg
    assert dataclasses.replace(built, kernel_impl="lax") == model.cfg
    assert family.vocabulary(sizes) == 96


@pytest.mark.parametrize("control", ["ignore_window", "shared"])
def test_the_benchmark_references_controls_move_the_logits(
        control, model_and_params, family):
    model, params = model_and_params
    ids = jnp.asarray(_prompt(40))[None]
    sizes = family.sizes_of(model.cfg)
    with jax.default_matmul_precision("highest"):
        sound = family.reference_logits(params, ids, sizes, query_block=8)
        moved = family.reference_logits(
            params, ids, sizes, query_block=8,
            **{control: control == "ignore_window"})
    assert float(jnp.abs(sound - moved).max()) \
        > 1e-3 * float(jnp.abs(sound).max())


def test_kernel_needs_counts_a_window_layers_read_as_its_windows(family):
    sizes = dict(hidden_size=6144, moe_intermediate_size=2048)
    needs = family.kernel_needs(sizes, 2, 5, {
        "serving_moe_experts_touched_total": 64.0,
        "serving_moe_assignments_total": 512.0,
        'serving_decode_kv_bytes_total{kind="live"}': 1.5e9,
        'serving_decode_kv_bytes_total{kind="gathered"}': 9e9}, 1e6, 0.0)
    assert needs["paged_decode_needed_bytes"] == 1.5e9
    assert needs["moe_ffn_needed_bytes"] == 64 * 3 * 6144 * 2048 * 2
    assert needs["moe_ffn_needed_flops"] == 512 * 6.0 * 6144 * 2048
    # the parent's counters hold no such series: nothing, no raise
    assert family.kernel_needs(sizes, 2, 5, {}, 1e6, 0.0) == {
        "moe_ffn_needed_bytes": 0.0, "moe_ffn_needed_flops": 0.0,
        "paged_decode_needed_bytes": 0.0}


