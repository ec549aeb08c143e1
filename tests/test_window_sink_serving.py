"""The window/full expert model under the options that make it
MiMo-V2-Flash's block (ISSUE 49), through the paged serving engine,
against the plain float32 reference (``tests/mimo_v2_flash_reference.py``).

Sizes: hidden 64, 4 query heads, seven layers (full, window x 4, full,
window; the first MLP dense, then 8 routed experts of 32 with 3 a token,
of which 2 are held, no shared expert); a full layer caches 1 KV head and
a window layer 2; keys of 24 a head of which the first ``int(24 x 0.334)``
= 8 entries are rotated (base 5e6 on a full layer, 1e4 on a window layer),
values of 16 scaled by 0.707, a learned sink a query head in the window
layers' softmax; window 8, page 4, chunk 4: a window layer's ring is 4
pages a slot (its window's 2 and 2 of room: two slots, two lanes a call). Weights are seeded float32 as ``init`` draws them (the sinks
so that they take about a quarter of a window's mass), so what separates
the engine from the reference is the order of float32 sums and nothing
else. ONE engine an ``impl`` serves the cases (``engines``, module-scoped).
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
from paddle_tpu.serving import layer_kinds
from paddle_tpu.serving.program import FEATURES

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
import mimo_v2_flash_reference as ref  # noqa: E402
from serving_taps import (assert_close, assert_refused,  # noqa: E402
                          FEATURE_OPTIONS, moved, reference_rows,
                          runs_against_one_chunk_a_slot, serve_alone,
                          shared_engines, tapped_engine, traced)
from serving_taps import prompt as _prompt  # noqa: E402

#: float32 on both sides, sums in another order: 2e-5 OF THE LARGEST
#: LOGIT. Sound runs read under 2e-6 of it; the sink, the value scale, the
#: partial rotary embedding or the window left out each read over 5e-3
#: (the controls below)
LOGIT_RTOL = 2e-5
_assert_close = functools.partial(assert_close, rtol=LOGIT_RTOL)

PAGE, CHUNK, WINDOW = 4, 4, 8
HEADS, KV_FULL, KV_WINDOW, DK, DV = 4, 1, 2, 24, 16
LAYERS = ("full_attention",) + ("sliding_attention",) * 4 \
    + ("full_attention", "sliding_attention")


def tiny_config(**kw):
    return WindowMoELMConfig.tiny(**{**dict(
        num_hidden_layers=7, layer_types=LAYERS,
        num_attention_heads=HEADS, num_key_value_heads=KV_FULL,
        swa_num_key_value_heads=KV_WINDOW, head_dim=DK, v_head_dim=DV,
        partial_rotary_factor=0.334, full_attention_rope=True,
        rope_theta=5e6, swa_rope_theta=1e4, qk_norm=False,
        attention_value_scale=0.707, add_swa_attention_sink_bias=True,
        num_shared_experts=0, routed_scaling_factor=1.0,
        kernel_impl="lax"), **kw})


@pytest.fixture(scope="module")
def model_and_params():
    model = WindowMoELM(tiny_config())
    return model, model.init(jax.random.PRNGKey(5))


def _engine(params, impl="lax", slots=2, **kw):
    return tapped_engine(
        WindowMoELM(tiny_config(kernel_impl=impl)), params,
        **{**dict(num_slots=slots, page_size=PAGE, prefill_chunk=CHUNK,
                  attn_impl=impl), **kw})


@pytest.fixture(scope="module")
def engines(model_and_params):
    return shared_engines(
        lambda *a, **kw: _engine(model_and_params[1], *a, **kw))


_rows = reference_rows(
    lambda params, ids, sizes: ref.reference_logits(
        params, ids, {k: v for k, v in sizes.items() if k != "leave_out"},
        leave_out=sizes.get("leave_out", ())))


def _reference_rows(model, params, prompt, out, **over):
    return _rows(params, prompt, out, ref.sizes_of(model.cfg, **over))


# -- the model ----------------------------------------------------------------

def test_forward_is_the_plain_reference(model_and_params):
    model, params = model_and_params
    ids = jnp.asarray(_prompt(40))
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref.reference_logits(params, ids,
                                               ref.sizes_of(model.cfg)))
        got = np.asarray(model.forward(params, ids[None]))[0]
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=2e-6 * np.abs(want).max())


def test_no_option_set_is_the_block_it_was():
    """The config without the new options draws the parameters it drew
    (per-head norms, a shared expert, K and V of one width, no sinks) and
    declares none of the new fields."""
    model = WindowMoELM(WindowMoELMConfig.tiny(kernel_impl="lax"))
    lp = jax.eval_shape(model.init, jax.random.PRNGKey(0))["layers"]["1"]
    assert {"q_norm", "k_norm", "shared"} <= set(lp) and "sinks" not in lp
    assert lp["k_proj"]["weight"].shape == lp["v_proj"]["weight"].shape
    spec = model.serving().spec
    assert (spec.layer_kv_heads, spec.value_dim, spec.sink_layers) \
        == ((), None, ())


def test_the_seeded_sinks_take_a_real_share_of_a_windows_mass(
        model_and_params):
    """``init`` draws the sinks around ``log(window / 3) + var / 2``: of a
    full window's softmax mass a sink takes a tenth to a half on average,
    so a program that dropped it cannot pass."""
    model, params = model_and_params
    c = model.cfg
    ids = jnp.asarray(_prompt(40))[None]
    pos = jnp.broadcast_to(jnp.arange(40, dtype=jnp.int32), (1, 40))
    x = model.embed(params, ids, pos)
    q, (k, _v), sinks = model.attn_in(params, 1, x, pos)
    assert sinks.shape == (HEADS,) and sinks.dtype == jnp.float32
    kh = jnp.repeat(k.reshape(40, KV_WINDOW, DK), HEADS // KV_WINDOW, axis=1)
    sc = jnp.einsum("hqd,khd->hqk", q[0].astype(jnp.float32), kh) / DK ** 0.5
    t = jnp.arange(40)
    seen = (t[None, :] <= t[:, None]) & (t[None, :] > t[:, None] - WINDOW)
    denom = jnp.where(seen, jnp.exp(sc), 0.0).sum(-1) \
        + jnp.exp(sinks)[:, None]
    share = float((jnp.exp(sinks)[:, None] / denom)[:, WINDOW:].mean())
    assert 0.1 < share < 0.5, share
    assert c.sink_layers == (False, True, True, True, True, False, True)


# -- prefill then decode through the cache -------------------------------------

CASES = {
    # every token of the request inside one window and one ring lap
    "inside_the_window": (3, 4),
    # the prompt ends a token short of the window; decode crosses it
    "decode_crosses_the_window": (WINDOW - 1, 5),
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
    ("decode_recycles_pages", "lax"),
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


@pytest.mark.parametrize("left_out", ["sink", "value_scale",
                                      "partial_rotary", "window"])
def test_the_tolerance_tells_each_piece_left_out(left_out, model_and_params,
                                                 served):
    """What ``LOGIT_RTOL`` must refuse: the reference without the sink,
    with the values unscaled, with the whole head rotated, or with every
    layer full."""
    model, params = model_and_params
    prompt, out, logits = served
    want = _reference_rows(model, params, prompt, out, leave_out=(left_out,))
    worst = np.abs(logits - want).max() / np.abs(want).max()
    assert worst > 50 * LOGIT_RTOL, worst


def test_two_requests_side_by_side_keep_to_their_own_rings(
        model_and_params, engines):
    """Two slots of different lengths in one batch, one of them past its
    window: each slot's window layers read its own ring."""
    model, params = model_and_params
    eng = engines("pallas_interpret")[0]
    prompts = [_prompt(17), _prompt(6)]
    outs = eng.generate_many(prompts, max_new_tokens=9)
    for prompt, out in zip(prompts, outs):
        want = _reference_rows(model, params, prompt, out)
        assert (want.argmax(-1) == out).all()


# -- a call that carries runs (ISSUE 54) -----------------------------------------

#: prompt lengths, served together in four slots under a budget of four
#: chunks a step (the rehearsal's): rings of the window's 2 pages and 4 of
#: room beside full layers that take any run
RUN_CASES = {
    # 22 tokens: a run of four chunks, then one of two that ends inside a
    # page (the lane that ends the prompt gives the first token)
    "ends_inside_a_page": (5 * PAGE + 2,),
    # a prompt shorter than a page beside one of several runs
    "shorter_than_a_page": (2, 9 * PAGE + 1),
    # five prompts for four slots and four lanes
    "more_slots_than_lanes": (9, 14, 6, 21, 11),
    # 51 tokens lap the ring of 24 twice, a run at a time
    "laps_the_ring": (51, 3),
}


@pytest.fixture(scope="module")
def run_engine(model_and_params):
    model, params = model_and_params
    return inference.make_serving_engine(
        model, params, num_slots=4, page_size=PAGE, prefill_chunk=CHUNK,
        prefill_budget=4 * CHUNK, max_tokens_per_slot=64, decode_block=2,
        attn_impl="lax", registry=obs.MetricsRegistry())


@pytest.mark.parametrize("case", sorted(RUN_CASES))
def test_calls_that_carry_runs_give_the_tokens_of_one_chunk_a_slot(
        case, run_engine):
    """Layers that differ in KV heads, key and value width and sinks: the
    same requests on the same programs, calls formed as the engine forms
    them and held to one chunk a slot, give the same tokens, and every
    ring is sound after every step (``Ring.check``)."""
    eng = run_engine
    full, ring = eng.cache.config.kinds[0], eng.cache.config.kinds[1]
    assert (full.prefill_run, ring.prefill_run, ring.ring_pages,
            eng._run_limit) == (None, 4, 6, 4)
    prompts = [_prompt(n, seed=549 + n) for n in RUN_CASES[case]]
    (got, calls), (want, plain) = runs_against_one_chunk_a_slot(eng, prompts)
    assert got == want and all(len(t) == 5 for t in got)
    # (with more slots than lanes the nearest its first token leaves the
    # others fewer lanes than their runs)
    assert 1 < max(c[4] for step in calls for c in step) \
        <= min(4, -(-max(RUN_CASES[case]) // CHUNK))
    assert sum(c[3] for step in calls for c in step) \
        == sum(c[3] for step in plain for c in step) == sum(RUN_CASES[case])
    assert sum(map(len, calls)) <= sum(map(len, plain))
    assert all(len(step) == 1 for step in calls)


@pytest.mark.parametrize("case", [
    "alone_on_an_idle_engine_it_advances_a_budget_a_step",
    "beside_a_slot_that_decodes_it_gives_a_step_one_run",
    "held_to_one_chunk_a_slot_it_advances_a_budget_a_step"])
def test_a_lone_long_prompt_and_the_steps_further_calls(
        case, model_and_params):
    """A ring's room (8 pages: ``engine._LANE_STEP``) under a call of 12
    lanes and a budget of 16 chunks, and one prompt of 15 chunks. Where
    no slot decodes nothing waits behind a call, and the step spends its
    budget in calls of one run (8 + 7 lanes: the first token after one
    step, as with one chunk a slot a call); where a slot decodes the
    prompt gives a step one run, and what it could still give leads the
    next step's call (the break-even is set by hand to a bf16 stage's at
    a chunk of 128: this toy's own is 120 lanes)."""
    model, params = model_and_params
    eng = inference.make_serving_engine(
        model, params, num_slots=12, page_size=PAGE, prefill_chunk=CHUNK,
        prefill_budget=16 * CHUNK, max_tokens_per_slot=72, decode_block=2,
        attn_impl="lax", registry=obs.MetricsRegistry())
    ring = eng.cache.config.kinds[1]
    assert (eng._lane_cap, ring.prefill_run, eng._run_limit) == (12, 8, 8)
    eng._second_call_lanes = 2
    if case.startswith("held"):
        eng._run_limit = 1
    long = _prompt(15 * CHUNK, seed=541)
    beside = case.startswith("beside")
    if beside:
        # a short request that decodes all the while
        eng.submit(_prompt(3, seed=542), 40)
        eng.step()
    n0 = eng.anatomy.summary()["steps"]
    rid = eng.submit(long, 2)
    out = {}
    while rid not in out:
        out.update(eng.step())
        eng.cache.check_invariants()
    recs = eng.anatomy.records()[-(eng.anatomy.summary()["steps"] - n0):]
    calls = [[c[0] for c in r["prefill_calls"]] for r in recs
             if r.get("prefill_calls")]
    assert calls == {"alone": [[8, 7]], "besid": [[8], [7]],
                     "held_": [[1] * 15]}[case[:5]]
    assert all(sum(c[3] for c in r.get("prefill_calls", ()))
               <= 16 * CHUNK for r in recs)
    got = np.asarray(out[rid])
    assert (_reference_rows(model, params, long, got).argmax(-1) == got).all()
    while not eng.scheduler.idle():
        eng.step()


def _rehearsal_engine(config):
    """The engine ``benchmark/run.py --rehearse`` builds for a
    configuration file: its rehearsal sizes and geometry, nothing run."""
    import importlib
    import json
    bench = os.path.join(ROOT, "benchmark")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    with open(os.path.join(bench, "configs", config + ".json")) as f:
        cfg = json.load(f)
    family = importlib.import_module(f"families.{cfg['family']}")
    model = family.build({**cfg["sizes"], **cfg["rehearsal"]["sizes"]},
                         interpret=True)
    ekw = {**cfg["engine"], **cfg["rehearsal"]["engine"]}
    ekw["cache_dtype"] = jnp.dtype(ekw["cache_dtype"])
    return inference.make_serving_engine(
        model, model.init(jax.random.PRNGKey(0), dtype=jnp.bfloat16),
        attn_impl="lax", registry=obs.MetricsRegistry(), **ekw)


@pytest.mark.parametrize("config", ["mimo_v2_flash", "k_exaone_236b_a23b"])
def test_rehearsal_engines_warm_every_call_a_round_can_form(config):
    """Both window models' rehearsal engines (4 slots, a budget of four
    chunks): the plan covers what the step side can ask for, lane counts
    up to what the budget buys and no further, and the round's runs are
    as long as the rings have room."""
    from paddle_tpu.analysis.hlo_lint import serving_bucket_coverage
    eng = _rehearsal_engine(config)
    assert serving_bucket_coverage(eng) == []
    assert set(eng.warmup_plan()) == eng.reachable_signatures()
    lanes = sorted({sig[2] for sig in eng.warmup_plan()
                    if sig[0] == "prefill"})
    assert lanes == [1, 2, 4] == sorted(
        {eng._pow2_count(n) for n in range(1, eng._lane_cap + 1)})
    rings = [k for k in eng.cache.config.kinds if k.by_slot]
    assert rings and {k.prefill_run for k in rings} == {4}
    assert eng._run_limit == 4 and eng._lane_cap == 4


# -- the engine ----------------------------------------------------------------

def test_each_kind_of_layer_has_its_own_geometry(engines):
    """Full layers of 1 KV head and window layers of 2 in one cache, each
    with a K pool of 24 a head and a V pool of 16, bytes from the two
    widths."""
    eng = engines("lax")[0]
    kinds = eng.cache.config.kinds
    full, ring = kinds[0], kinds[1]
    assert [type(k) for k in kinds] == [
        layer_kinds.Ring if t == "sliding_attention" else layer_kinds.Paged
        for t in LAYERS]
    assert len(set(kinds)) == 2 and kinds[5] is full and kinds[6] is ring
    assert (full.geo.heads, ring.geo.heads) == (KV_FULL, KV_WINDOW)
    assert (full.sink, ring.sink) == (False, True)
    pages = eng.cache.config.num_pages
    assert [shape for shape, _, _ in full.pools] == [
        (pages, PAGE, KV_FULL * DK), (pages, PAGE, KV_FULL * DV)]
    assert [shape for shape, _, _ in ring.pools] == [
        (2 * 4 + 1, PAGE, KV_WINDOW * DK), (2 * 4 + 1, PAGE, KV_WINDOW * DV)]
    # the window's 2 pages and 2 of room: two lanes a call
    assert (ring.window_pages, ring.prefill_run, ring.ring_pages) == (2, 2, 4)
    assert full.prefill_run is None and eng._run_limit == 2
    assert full.token_bytes == KV_FULL * (DK + DV) * 4
    assert ring.token_bytes == KV_WINDOW * (DK + DV) * 4
    assert eng.cache.bytes_per_page() == 2 * PAGE * full.token_bytes
    assert eng.cache.bytes_per_slot() == 5 * 4 * PAGE * ring.token_bytes
    eng.cache.check_invariants()


@pytest.mark.parametrize("feature", sorted(
    set(FEATURE_OPTIONS) - {"prefix_export"}))
def test_every_option_the_program_does_not_carry_is_refused_by_name(
        feature, model_and_params):
    model, params = model_and_params
    assert feature in FEATURES and not model.serving().spec.supports
    assert_refused(model, params, feature, f"WindowMoELM.*{feature!r}",
                   page_size=PAGE, prefill_chunk=CHUNK, attn_impl="auto")


def test_counters_and_spans_of_layers_of_two_widths(engines):
    """One request of 10 + 9 tokens alone: every series of ISSUE 49 from
    the lengths the host holds, each kind's bytes from its own widths."""
    eng, _sink, reg = engines("lax")
    before = reg.snapshot()
    with traced(eng) as tracer:
        eng.generate_many([_prompt(10)], max_new_tokens=9)
    snap, gauges = moved(reg, before), reg.snapshot()
    full_row = KV_FULL * (DK + DV) * 4          # K and V of a token, a layer
    ring_row = KV_WINDOW * (DK + DV) * 4
    assert gauges['serving_kv_pool_bytes{layers="window"}'] \
        == 5 * 9 * PAGE * ring_row
    assert gauges['serving_kv_pool_bytes{layers="full"}'] \
        == 2 * eng.cache.config.num_pages * PAGE * full_row
    # prefill calls at 0 (a run of two chunks) and 8 tokens held, what
    # a slot holds counted once a call; decode blocks of 2 from 10 on
    held = [0, 8] + [10, 12, 14, 16]
    pages = [-(-n // PAGE) for n in held]
    assert snap['serving_kv_resident_bytes_total{layers="full"}'] \
        == sum(pages) * 2 * PAGE * full_row
    assert snap['serving_kv_resident_bytes_total{layers="window"}'] \
        == sum(min(p, 4) for p in pages) * 5 * PAGE * ring_row
    # a decode token step at L tokens held reads L + 1 rows of each full
    # layer and min(L + 1, 8) of each window layer
    steps = range(10, 18)
    assert snap['serving_decode_kv_bytes_total{kind="live"}'] == sum(
        2 * full_row * (n + 1) + 5 * ring_row * min(n + 1, WINDOW)
        for n in steps)
    # prefill: token t scores t + 1 pairs on a full layer, min(t + 1, 8)
    # on a window layer; calls of 4, 4, 2 tokens read 4 + 8 + 10 rows on a
    # full layer and 4 + 8 + min(10, 2 + 7) on a window layer
    assert snap['serving_prefill_attn_pairs_total{layers="full"}'] \
        == 2 * sum(t + 1 for t in range(10))
    assert snap['serving_prefill_attn_pairs_total{layers="window"}'] \
        == 5 * sum(min(t + 1, WINDOW) for t in range(10))
    assert snap['serving_prefill_kv_rows_total{layers="full"}'] \
        == 2 * (4 + 8 + 10)
    assert snap['serving_prefill_kv_rows_total{layers="window"}'] \
        == 5 * (4 + 8 + 9)
    # 10 prompt tokens and 8 decode token steps, 4 heads, 7 layers of
    # which 5 have a sink
    assert snap["serving_attn_rows_total"] == (10 + 8) * HEADS * 7
    assert snap["serving_attn_sink_rows_total"] == (10 + 8) * HEADS * 5
    spans = tracer.spans()
    rounds = [s for s in spans if s.name == "serving.decode_round"
              and s.attrs.get("slots_live")]
    calls = [s for s in spans if s.name == "serving.prefill_call"]
    assert sum(s.attrs["sink_rows"] for s in rounds + calls) \
        == snap["serving_attn_sink_rows_total"]
    assert sum(s.attrs["attn_pairs"] for s in calls) == sum(
        snap[f'serving_prefill_attn_pairs_total{{layers="{k}"}}']
        for k in ("full", "window"))


# -- the benchmark's copy -------------------------------------------------------

@pytest.fixture(scope="module")
def family():
    sys.path.insert(0, os.path.join(ROOT, "benchmark"))
    from families import mimo_v2_flash
    return mimo_v2_flash


def test_benchmark_reference_is_the_plain_reference(model_and_params,
                                                    family):
    """``families/mimo_v2_flash.py`` computes the same pass in blocks
    (queries 8 at a time against the keys their windows reach, the dense
    MLP's hidden units and the vocabulary in pieces, the rows asked for
    only): held to the plain one here, with the chip's share of the
    experts."""
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


@pytest.mark.parametrize("control", [
    dict(sinks=False), dict(value_scale=1.0), dict(rotary="whole"),
    dict(rotary="swapped"), dict(ignore_window=True)],
    ids=lambda c: "-".join(f"{k}={v}" for k, v in c.items()))
def test_the_benchmark_references_controls_move_the_logits(
        control, model_and_params, family):
    model, params = model_and_params
    ids = jnp.asarray(_prompt(40))[None]
    sizes = family.sizes_of(model.cfg)
    with jax.default_matmul_precision("highest"):
        sound = family.reference_logits(params, ids, sizes, query_block=8)
        moved_ = family.reference_logits(params, ids, sizes, query_block=8,
                                         **control)
    assert float(jnp.abs(sound - moved_).max()) \
        > 1e-3 * float(jnp.abs(sound).max())


def test_kernel_needs_counts_each_kind_at_its_own_width(family):
    sizes = dict(hidden_size=4096, moe_intermediate_size=2048, head_dim=192,
                 v_head_dim=128, num_attention_heads=64,
                 num_key_value_heads=4, swa_num_key_value_heads=8)
    needs = family.kernel_needs(sizes, 2, 7, {
        "serving_moe_experts_touched_total": 64.0,
        "serving_moe_assignments_total": 512.0,
        'serving_decode_kv_bytes_total{kind="live"}': 1.5e9,
        'serving_prefill_attn_pairs_total{layers="full"}': 1000.0,
        'serving_prefill_attn_pairs_total{layers="window"}': 500.0,
        'serving_prefill_kv_rows_total{layers="full"}': 100.0,
        'serving_prefill_kv_rows_total{layers="window"}': 50.0}, 1e6, 0.0)
    assert needs["paged_decode_needed_bytes"] == 1.5e9
    assert needs["moe_ffn_needed_bytes"] == 64 * 3 * 4096 * 2048 * 2
    assert needs["moe_ffn_needed_flops"] == 512 * 6.0 * 4096 * 2048
    assert needs["paged_prefill_needed_flops"] == 1500 * 64 * 2.0 * 320
    assert needs["paged_prefill_needed_bytes"] \
        == 100 * 4 * 320 * 2 + 50 * 8 * 320 * 2
    # the parent's counters hold no such series: nothing, no raise
    assert family.kernel_needs(sizes, 2, 7, {}, 1e6, 0.0) == {
        "moe_ffn_needed_bytes": 0.0, "moe_ffn_needed_flops": 0.0,
        "paged_decode_needed_bytes": 0.0}
