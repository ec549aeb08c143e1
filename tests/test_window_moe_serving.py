"""Window and full attention layers over a sigmoid-routed expert share,
through the paged serving engine, against the plain float32 reference
(ISSUE 40).

Sizes: hidden 64, 4 query heads over 2 KV heads of 16, five layers
(window, window, window, full, window; the first MLP dense, then 8 routed
experts of 32 with 3 a token, of which 2 are held, beside a shared one),
window 8, page 4, chunk 4: a window layer's ring is 3 pages a slot.
Weights are seeded float32 as ``init`` draws them, so what separates the
engine from the reference is the order of float32 sums (the paged
kernels' page folds, the grouped expert kernel's tiles) and nothing else.
ONE engine an ``impl`` serves the cases that take this geometry
(``engines``, module-scoped: a case then compiles only the widths no
earlier one met), a request at a time; a slot's rings hold what its last
request left, as they do in a serving process.
"""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import inference, kernels
from paddle_tpu import observability as obs
from paddle_tpu.models import WindowMoELM, WindowMoELMConfig
from paddle_tpu.ops import grouped_ffn
from paddle_tpu.serving import layer_kinds
from paddle_tpu.serving.paged_cache import (PageOverflowError,
                                            PagedCacheConfig, PagedKVCache)
from paddle_tpu.serving.program import FEATURES, ServingSpec

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import window_moe_reference as ref  # noqa: E402

#: float32 on both sides, sums in another order: 2e-5 OF THE LARGEST
#: LOGIT (the logits are of magnitude 0.5). Sound runs read under 2e-6 of
#: it; a window off by one token, a ring page too few or the shared
#: expert left out each read over 1e-3 (the controls below)
LOGIT_RTOL = 2e-5

PAGE, CHUNK, WINDOW = 4, 4, 8


def _sizes(cfg, **over):
    """The published keys the reference reads, from a program config."""
    sizes = {k: getattr(cfg, k) for k in (
        "hidden_size", "num_hidden_layers", "num_attention_heads",
        "num_key_value_heads", "head_dim", "rms_norm_eps", "sliding_window",
        "num_experts_per_tok", "routed_scaling_factor", "norm_topk_prob")}
    sizes.update(layer_types=list(cfg.layer_types),
                 mlp_layer_types=list(cfg.mlp_layer_types),
                 rope_parameters={"rope_theta": cfg.rope_theta},
                 expert_offset=cfg.expert_offset, **over)
    return sizes


@pytest.fixture(scope="module")
def model_and_params():
    model = WindowMoELM(WindowMoELMConfig.tiny(kernel_impl="lax"))
    return model, model.init(jax.random.PRNGKey(5))


class _Tap:
    """A serving program whose ``head`` also hands every call's logits to
    the host, in order."""

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


def _engine(params, impl="lax", slots=2, **kw):
    model = WindowMoELM(WindowMoELMConfig.tiny(kernel_impl=impl))
    reg = obs.MetricsRegistry()
    kw.setdefault("decode_block", 2)
    kw.setdefault("prefill_chunk", CHUNK)
    eng = inference.make_serving_engine(
        model, params, num_slots=slots, page_size=PAGE,
        max_tokens_per_slot=96, attn_impl=impl, registry=reg, **kw)
    sink = []
    eng.program = _Tap(eng.program, sink)
    return eng, sink, reg


@pytest.fixture(scope="module")
def engines(model_and_params):
    """``impl -> (engine, the logits its head calls made, registry)``,
    built at first use and kept for the module."""
    built = {}

    def get(impl):
        if impl not in built:
            built[impl] = _engine(model_and_params[1], impl)
        return built[impl]
    return get


def _serve(eng, sink, prompt, n_new):
    """One request alone in the engine: its tokens and the logits of
    positions ``len(prompt) - 1 .. len(prompt) + n_new - 2``."""
    del sink[:]
    rid = eng.submit(prompt, n_new)
    slot = None
    while not eng.scheduler.idle():
        eng.step()
        eng.cache.check_invariants()
        for i in eng.scheduler.active_slots():
            slot = i
    jax.effects_barrier()
    out = eng.result(rid)
    s_tot = eng.scheduler.num_slots
    calls = list(sink)
    last_prefill = max(i for i, a in enumerate(calls)
                       if a.shape[0] != s_tot or i == 0)
    logits = [calls[last_prefill][0]]
    logits += [a[slot if slot is not None else 0]
               for a in calls[last_prefill + 1:]]
    return out, np.stack(logits[:n_new])


def _prompt(n, seed=None):
    return np.random.default_rng(n if seed is None else seed).integers(
        0, 96, n).astype(np.int32)


def _reference_rows(model, params, prompt, out, **over):
    ids = jnp.asarray(np.concatenate([prompt, out]))
    with jax.default_matmul_precision("highest"):
        logits = np.asarray(ref.reference_logits(
            params, ids, _sizes(model.cfg, **over)))
    n0 = len(prompt)
    return logits[n0 - 1:n0 - 1 + len(out)]


def _assert_close(got, want):
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=LOGIT_RTOL * np.abs(want).max())


CASES = {
    # every token of the request inside one window and one ring lap
    "inside_the_window": (3, 4),
    # the prompt ends a token short of the window; decode crosses it
    "decode_crosses_the_window": (WINDOW - 1, 5),
    # a prompt of one page and a token: the second chunk reads the first's
    # page; decode crosses a page boundary
    "crosses_a_page": (PAGE + 1, 6),
    # 10 + 9 tokens: the ring's 3 pages hold 12, so decode writes over
    # the page of tokens 0-3 and then 4-7 (recycled pages)
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
    out, logits = _serve(eng, sink, prompt, n_new)
    assert len(out) == n_new
    _assert_close(logits, _reference_rows(model, params, prompt, out))


@pytest.fixture(scope="module")
def served(engines):
    """One request served once for the controls: (prompt, tokens,
    logits)."""
    eng, sink, _ = engines("lax")
    prompt = _prompt(21)
    return (prompt,) + _serve(eng, sink, prompt, 7)


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


# -- the share ----------------------------------------------------------------

@pytest.mark.parametrize("impl", ["lax", "pallas_interpret"])
def test_the_shares_add_up_to_the_uncut_layer(impl):
    """Four chips' shares of 2 experts each, the shared expert counted
    once, add up to what the uncut reference gives for the whole layer."""
    uncut = WindowMoELMConfig.tiny(num_experts=8, kernel_impl=impl)
    whole = WindowMoELM(uncut).init(jax.random.PRNGKey(2))
    layer = 2                                           # a sparse layer
    lp = whole["layers"][str(layer)]
    # (a small stream: ``y - x`` below then keeps the layer's digits)
    x = 0.01 * jax.random.normal(jax.random.PRNGKey(3), (3, 5, 64),
                                 jnp.float32)
    valid = jnp.ones((3, 5), bool)
    with jax.default_matmul_precision("highest"):
        b = ref._rms(x.reshape(15, 64), lp["ffn_norm"]["scale"], 1e-5)
        want = ref.reference_ffn(lp, b, _sizes(uncut))
        shared = ref._swiglu(b, lp["shared"])
        total, pairs = shared, 0
        for offset in range(0, 8, 2):
            cfg = dataclasses.replace(uncut, num_experts=2,
                                      num_routed_experts=8,
                                      expert_offset=offset)
            tree = jax.tree_util.tree_map(lambda a: a, whole)
            tree["layers"][str(layer)]["experts"] = {
                k: w[offset:offset + 2] for k, w in lp["experts"].items()}
            y, stats = WindowMoELM(cfg).ffn(tree, layer, x, valid)
            total = total + (y - x).reshape(15, 64) - shared
            pairs += int(stats["moe_assignments"])
            assert int(stats["moe_routed_pairs"]) == 15 * 3
            assert int(stats["moe_expert_slots"]) == 2
    assert pairs == 15 * 3          # every pair is some chip's, once
    np.testing.assert_allclose(total, want, rtol=0,
                               atol=2e-5 * float(jnp.abs(want).max()))


@pytest.mark.parametrize("offset", [0, 4, 6])
def test_route_tiles_gives_no_row_to_a_pair_held_elsewhere(offset):
    rng = np.random.default_rng(offset)
    t, k, routed, held, tm = 11, 3, 8, 2, 8
    ids = jnp.asarray(np.stack([rng.permutation(routed)[:k]
                                for _ in range(t)]), jnp.int32)
    valid = jnp.asarray(rng.uniform(size=t) < 0.8)
    src, dest, tile_expert, n_used, sizes = grouped_ffn.route_tiles(
        ids, valid, held, tm, held_offset=offset)
    mine = np.asarray(valid)[:, None] & (np.asarray(ids) >= offset) \
        & (np.asarray(ids) < offset + held)
    # the table is sized for the held experts and the pairs that can land
    # on them: a token's experts are distinct, so min(k, held) of them
    n_tiles = held + t * min(k, held) // tm
    assert tile_expert.shape == (n_tiles,) and src.shape == (n_tiles * tm,)
    assert int((np.asarray(src) >= 0).sum()) == int(mine.sum())
    np.testing.assert_array_equal(
        np.asarray(sizes), [int((mine & (np.asarray(ids) == offset + e)
                                 ).sum()) for e in range(held)])
    rows = np.asarray(dest)[mine]
    assert len(set(rows.tolist())) == len(rows)          # a row a pair
    np.testing.assert_array_equal(np.asarray(src)[rows],
                                  np.nonzero(mine)[0])
    # a live row's tile belongs to its pair's expert, counted from 0
    np.testing.assert_array_equal(
        np.asarray(tile_expert)[rows // tm],
        np.asarray(ids)[mine] - offset)
    assert int(n_used[0]) == int((-(-np.asarray(sizes) // tm)).sum())


# -- the cache ----------------------------------------------------------------

def _kinds(windows, layers=None, slots=3, num_pages=25, dtype=jnp.float32,
           share_prefix=False):
    """The kinds of a program with these windows, through the one function
    that decides them."""
    spec = ServingSpec(num_layers=layers or len(windows), num_heads=2,
                       kv_heads=2, head_dim=16, vocab_size=8,
                       max_position=256, layer_windows=windows)
    return layer_kinds.build(spec, num_slots=slots, page_size=PAGE,
                             num_pages=num_pages, dtype=dtype,
                             share_prefix=share_prefix)


def _cache(num_pages=25, slots=3, windows=(8, 8, 8, None, 8)):
    return PagedKVCache(PagedCacheConfig(
        num_layers=len(windows), num_heads=2, head_dim=16, num_slots=slots,
        page_size=PAGE, num_pages=num_pages, max_pages_per_slot=24,
        share_prefix=False,
        kinds=_kinds(windows, slots=slots, num_pages=num_pages)))


def _rings(cache):
    """(layer, its kind) of the window layers; one object for them all."""
    return [(i, k) for i, k in enumerate(cache.config.kinds)
            if isinstance(k, layer_kinds.Ring)]


def test_a_window_layer_holds_a_ring_a_slot_whatever_the_length():
    cache = _cache()
    ring = _rings(cache)[0][1].ring_pages
    assert ring == 3 and len({id(k) for _, k in _rings(cache)}) == 1
    page = PAGE * 2 * 16 * 4 * 2                        # K and V, float32
    assert [ent[0].shape[0] for ent in cache.pages] == [10, 10, 10, 25, 10]
    assert cache.bytes_per_page() == page               # the full layer's
    assert cache.bytes_per_slot() == 4 * ring * page
    assert cache.capacity_bytes() == 24 * page + 3 * 4 * ring * page
    cache.reserve(1, 80)
    assert cache.live_bytes() == 20 * page + 4 * ring * page
    for n in (0, 3, 8, 12, 13, 57, 80):
        cache.lengths[1] = n
        cache.check_invariants()
    for _layer, kind in _rings(cache):      # what a ring holds of a slot
        assert kind.ring_pages * PAGE == WINDOW + PAGE
        assert kind.slot_bytes == ring * page and kind.page_bytes == 0
    cache.free_slot(1)
    assert cache.live_bytes() == 0


def test_a_long_request_is_admitted_where_every_layer_paged_alike_could_not():
    """80 tokens are 20 pages of the full layer; the four window layers
    hold their rings whatever the length. The same bytes as ONE pool
    paged alike (every layer every token) hold 20 pages x 5 layers only
    with 100 page rows: this pool's 24 + 3 x 4 x 3 = 60 could not."""
    cache = _cache()
    assert cache.can_reserve(80)
    alike = PagedKVCache(PagedCacheConfig(
        num_layers=5, num_heads=2, head_dim=16, num_slots=3, page_size=PAGE,
        num_pages=cache.capacity_bytes()
        // (5 * cache.bytes_per_page()) + 1, max_pages_per_slot=24,
        share_prefix=False))
    assert alike.capacity_bytes() <= cache.capacity_bytes()
    assert not alike.can_reserve(80)
    # admitted or refused whole: a second long request finds 4 pages free
    cache.reserve(0, 80)
    assert not cache.can_reserve(17) and cache.can_reserve(16)
    with pytest.raises(PageOverflowError):
        cache.reserve(1, 17)
    cache.check_invariants()
    assert cache.pages_in_use == 20 and not cache.slot_pages(1)


def test_a_recycled_page_is_never_one_a_live_slot_still_reads():
    """The page a slot writes next holds no token of its own window, and
    is no other slot's."""
    cache = _cache()
    kind = _rings(cache)[0][1]
    for slot in range(3):
        for n in range(0, 60):          # tokens held before the write
            writes = kind.page_of(slot, n // PAGE)
            still_read = {kind.page_of(slot, t // PAGE)
                          for t in range(max(n - WINDOW + 1, 0), n)
                          if t // PAGE != n // PAGE}
            assert writes not in still_read
            assert all(kind.page_of(other, p) != writes
                       for other in range(3) if other != slot
                       for p in range(3))
    # (all four window layers: the kind counts for the layers it stands for)
    assert kind.layers == 4
    assert kind.recycled(np.array([0, 11]), np.array([12, 13])) == 4
    assert kind.recycled(np.array([12]), np.array([21])) == 4 * 3


def test_a_pool_with_window_layers_shares_no_prefix_and_is_not_quantized():
    with pytest.raises(ValueError, match="window layers"):
        _kinds((8, None), share_prefix=True)
    with pytest.raises(ValueError, match="window layers"):
        _kinds((8, None), dtype=jnp.int8)
    with pytest.raises(ValueError, match="every layer or none"):
        PagedCacheConfig(num_layers=3, num_heads=2, head_dim=16, num_slots=2,
                         page_size=4, share_prefix=False,
                         kinds=_kinds((8, None)))
    with pytest.raises(ValueError, match="one entry a layer"):
        ServingSpec(num_layers=3, num_heads=2, kv_heads=2, head_dim=16,
                    vocab_size=8, max_position=64, layer_windows=(8, None))
    spec = ServingSpec(num_layers=2, num_heads=2, kv_heads=2, head_dim=16,
                       vocab_size=8, max_position=64,
                       layer_windows=(None, None))
    assert spec.layer_windows == ()                     # all full


# -- the kernels --------------------------------------------------------------

@pytest.mark.parametrize("seed", [3, 4, 5])
@pytest.mark.parametrize("name", ["ragged_paged_decode",
                                  "ragged_paged_prefill"])
def test_windowed_kernel_parity_in_the_harness(name, seed):
    """The harness's own samples under a window (seeds 3-5): the Pallas
    body interpreted and the ``lax`` form against the dense reference."""
    args, kwargs = kernels.get(name).sample_inputs(seed)
    assert kwargs["window"] % args[1].shape[1]       # no multiple of a page
    errs = kernels.parity_check(name, seed)
    assert set(errs) == {"lax", "pallas_interpret"}


@pytest.mark.parametrize("pb", [1, 2, 4, 8])
def test_the_windowed_walk_starts_at_the_windows_first_page(pb):
    """The dense decode body under a window at every ``pages_per_block``:
    the walk starts at the page of the window's first token, so a slot of
    any length folds ``pages_for(window) + 1`` pages at most."""
    spec = kernels.get("ragged_paged_decode")
    args, kwargs = spec.sample_inputs(5)     # 256 lanes, pages of 16: walks
    want = np.asarray(spec.reference_fn(*args, **kwargs))
    got = kernels.dispatch("ragged_paged_decode", *args,
                           impl="pallas_interpret",
                           block_sizes={"pages_per_block": pb}, **kwargs)
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-5, rtol=2e-5)
    # the same pages under a window as wide as the table: every token
    full = kernels.dispatch("ragged_paged_decode", *args,
                            impl="pallas_interpret",
                            block_sizes={"pages_per_block": pb},
                            window=10 ** 6)
    np.testing.assert_allclose(
        np.asarray(full), np.asarray(spec.reference_fn(*args)), atol=2e-5,
        rtol=2e-5)


def test_engine_decodes_window_layers_through_the_body_that_walks_pages():
    """Pages of whole tiles (128 lanes, 8 float32 rows): the window
    layers' decode goes through the walking body, a ring of 3 pages."""
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
        want = np.asarray(ref.reference_logits(params, ids, _sizes(cfg)))
    assert (want[18:18 + 21].argmax(-1) == out).all()


# -- the engine ---------------------------------------------------------------

@pytest.mark.parametrize("feature, option", [
    ("tp", dict(tp=2)), ("int8_pages", dict(cache_dtype=jnp.int8)),
    ("draft", dict(draft_model="self")), ("host_spill",
                                          dict(host_spill_pages=4)),
    ("migration", dict(snapshot_every_blocks=2)),
    ("tiers", dict(tier="prefill")),
    ("prefix_sharing", dict(prefix_sharing=True))])
def test_every_option_the_program_does_not_carry_is_refused_by_name(
        feature, option, model_and_params):
    model, params = model_and_params
    assert feature in FEATURES and not model.serving().spec.supports
    if option.get("draft_model") == "self":
        option = dict(draft_model=model, draft_params=params)
    with pytest.raises(ValueError, match=f"WindowMoELM.*{feature!r}"):
        inference.make_serving_engine(model, params, num_slots=2,
                                      page_size=PAGE, prefill_chunk=CHUNK,
                                      **option)


def test_a_chunk_wider_than_a_page_is_refused(model_and_params):
    model, params = model_and_params
    with pytest.raises(ValueError, match="prefill_chunk=8 > page_size=4"):
        inference.make_serving_engine(model, params, num_slots=2,
                                      page_size=PAGE, prefill_chunk=8)


def test_counters_and_spans_of_the_two_layer_kinds(model_and_params):
    """One request of 10 + 9 tokens alone: every series of ISSUE 40 from
    the lengths the host holds, one read-back a block."""
    _model, params = model_and_params
    tracer = obs.Tracer(enabled=True)
    eng, _sink, reg = _engine(params, tracer=tracer)
    eng.generate_many([_prompt(10)], max_new_tokens=9)
    snap = reg.snapshot()
    row = 2 * 2 * 16 * 4                     # K and V of a token, a layer
    page = PAGE * row
    # pools: 4 window layers of 2 x 3 + 1 pages, one full of 49
    assert snap['serving_kv_pool_bytes{layers="window"}'] == 4 * 7 * page
    assert snap['serving_kv_pool_bytes{layers="full"}'] \
        == eng.cache.config.num_pages * page
    # prefill calls at 0, 4, 8 tokens held; decode blocks of 2 from 10 on
    held = [0, 4, 8] + [10, 12, 14, 16]
    pages = [-(-n // PAGE) for n in held]
    assert snap['serving_kv_resident_bytes_total{layers="full"}'] \
        == sum(pages) * page
    assert snap['serving_kv_resident_bytes_total{layers="window"}'] \
        == sum(min(p, 3) for p in pages) * 4 * page
    # the ring's first lap is 12 tokens: tokens 12-18 enter 2 more pages
    assert snap["serving_window_pages_recycled_total"] == 2 * 4
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
    assert sum(s.attrs["window_pages"] for s in rounds + calls) == 8
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
                                               _sizes(model.cfg)))
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


@pytest.mark.parametrize("seed", [1, 2, 4, 5])
@pytest.mark.parametrize("kv_of", [1, 2], ids=["one_kv_head", "two_a_kv_head"])
def test_wide_grouped_chunks_fold_a_page_once_a_kv_head(seed, kv_of,
                                                        monkeypatch):
    """The chunked-prefill body's group fold (taken from 4096 heads x
    queries on: 64 heads of 128 queries; forced here at the harness's
    sizes) against the dense reference on grouped-query pools, with and
    without a window, at every ``pages_per_block``."""
    from paddle_tpu.serving import decode_attention as DA
    monkeypatch.setattr(DA, "_GROUP_FOLD_MIN_ROWS", 0)
    spec = kernels.get("ragged_paged_prefill")
    args, kw = spec.sample_inputs(seed)
    q, kp, vp = args[:3]
    h, dh = q.shape[-2:]
    kv = 1 if kv_of == 1 else h // 2
    pools = [p[:, :, :kv * dh] for p in (kp, vp)]
    whole = [jnp.repeat(p.reshape(p.shape[:2] + (kv, dh)), h // kv,
                        axis=2).reshape(p.shape[:2] + (h * dh,))
             for p in pools]
    want = np.asarray(spec.reference_fn(q, *whole, *args[3:], **kw))
    for pb in (1, 2, 4):
        got = kernels.dispatch(
            "ragged_paged_prefill", q, *pools, *args[3:],
            impl="pallas_interpret", block_sizes={"pages_per_block": pb},
            **kw)
        np.testing.assert_allclose(np.asarray(got), want, atol=2e-5,
                                   rtol=2e-5)
