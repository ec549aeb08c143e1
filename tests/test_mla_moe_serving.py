"""Multi-head latent attention over a softmax-routed expert share, through
the paged serving engine, against the plain float32 reference in the
expanded form (ISSUE 42).

Sizes: hidden 64, 4 heads, ``q_lora_rank`` 32, ``kv_lora_rank`` 16, nope 8,
rope 8, values 16, two layers, 16 routed experts of 32 with 4 a token of
which 2 are held beside a shared one; ``L0`` 16 and factor 4, so the
requests below cross ``L0`` twice (the query scale ``a_t`` takes three
values) and the four rotary pairs lie on both sides of YaRN's ramp; page 8,
chunk 8. A token's cached row is 16 + 8 = 24 values a layer. Weights are
seeded float32 as ``init`` draws them but for the two projections the
scores are made of, which are drawn larger (``_params``). ONE engine an
``impl`` serves every case of this file (module-scoped), a request at a
time.
"""

import dataclasses
import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import kernels
from paddle_tpu import observability as obs
from paddle_tpu.models.mla_moe_lm import MLAMoELM, MLAMoELMConfig
from paddle_tpu.serving import decode_attention as DA
from paddle_tpu.serving import layer_kinds
from paddle_tpu.serving.paged_cache import PagedCacheConfig, PagedKVCache
from paddle_tpu.serving.program import FEATURES, ServingSpec

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
import mla_moe_reference as ref  # noqa: E402
from serving_taps import (assert_close, assert_refused,  # noqa: E402
                          FEATURE_OPTIONS, serve_alone,
                          shared_engines, tapped_engine)
from serving_taps import prompt as _prompt  # noqa: E402

#: float32 on both sides, the absorbed sums in another order than the
#: expanded ones: 2e-5 OF THE LARGEST LOGIT. Sound runs read under 3e-6 of
#: it; ``a_t`` left at 1, an unrotated key or a float8 row each read over
#: 1e-3 (the controls below)
LOGIT_RTOL = 2e-5
_assert_close = functools.partial(assert_close, rtol=LOGIT_RTOL)

PAGE, CHUNK, ROW, LAYERS = 8, 8, 24, 2


def _sizes(cfg):
    """The published keys the reference reads, from a program config."""
    sizes = {k: getattr(cfg, k) for k in (
        "num_hidden_layers", "num_attention_heads", "kv_lora_rank",
        "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "rms_norm_eps",
        "num_experts_per_tok", "routed_scaling_factor", "norm_topk_prob",
        "expert_offset")}
    sizes["rope_parameters"] = {
        "rope_theta": cfg.rope_theta, "factor": cfg.rope_factor,
        "original_max_position_embeddings":
            cfg.original_max_position_embeddings,
        "beta_fast": cfg.beta_fast, "beta_slow": cfg.beta_slow,
        "mscale": cfg.mscale, "mscale_all_dim": cfg.mscale_all_dim,
        "llama_4_scaling_beta": cfg.llama_4_scaling_beta}
    return sizes


def _params(model, seed=5):
    """``init``'s draw with the query and row projections 8 and 4 times
    as large: at width 64 a draw of 0.02 leaves every score near 0.02, the
    softmax uniform, and no control below would move a logit. At the
    published widths the scores are of order 1 as drawn."""
    params = model.init(jax.random.PRNGKey(seed))
    for lp in params["layers"].values():
        lp["q_b_proj"]["weight"] = 8.0 * lp["q_b_proj"]["weight"]
        lp["kv_a_proj"]["weight"] = 4.0 * lp["kv_a_proj"]["weight"]
    return params


@pytest.fixture(scope="module")
def model_and_params():
    model = MLAMoELM(MLAMoELMConfig.tiny(kernel_impl="lax"))
    return model, _params(model)


def _engine(params, impl="lax"):
    eng, sink, reg = tapped_engine(
        MLAMoELM(MLAMoELMConfig.tiny(kernel_impl=impl)), params, num_slots=2,
        page_size=PAGE, prefill_chunk=CHUNK, attn_impl=impl,
        tracer=obs.Tracer(enabled=True))
    return eng, sink, reg, eng.tracer


@pytest.fixture(scope="module")
def engines(model_and_params):
    """``get(impl) -> (engine, its head calls' logits, registry,
    tracer)``, one engine an ``impl`` for the module: what a case may
    assume of it is in ``tests/serving_taps.py``."""
    return shared_engines(lambda impl: _engine(model_and_params[1], impl))


_REFERENCE = {}


def _reference(model, params, ids, **controls):
    """The plain reference's logits of ``ids``, computed at ONE padded
    length (the pass is causal: what follows a token does not reach it),
    so that a set of controls compiles once for the whole file."""
    key = tuple(sorted((k, str(v)) for k, v in controls.items()))
    if key not in _REFERENCE:
        def plain_reference(p, i):      # (named: no ``jit__lambda`` stays
            return ref.reference_logits(    # loaded for the module's life)
                p, i, _sizes(model.cfg), **controls)
        _REFERENCE[key] = jax.jit(plain_reference)
    padded = np.zeros((48,), np.int32)
    padded[:len(ids)] = ids
    return np.asarray(_REFERENCE[key](params, jnp.asarray(padded)))[:len(ids)]


def _reference_rows(model, params, prompt, out, **controls):
    logits = _reference(model, params, np.concatenate([prompt, out]),
                        **controls)
    n0 = len(prompt)
    return logits[n0 - 1:n0 - 1 + len(out)]


CASES = {
    # one chunk, decode crosses the first page edge
    "one_chunk": (5, 6),
    # four chunks (8, 8, 8, 5): the prompt crosses L0 = 16 once, decode
    # crosses a page edge and L0 again at 32
    "decode_crosses_l0": (29, 9),
    # the prompt crosses L0 twice and ends on a page and chunk edge
    "prompt_crosses_l0_twice": (40, 5),
}


@pytest.mark.parametrize("case, impl", [
    ("one_chunk", "lax"), ("one_chunk", "pallas_interpret"),
    ("decode_crosses_l0", "lax"), ("decode_crosses_l0", "pallas_interpret"),
    ("prompt_crosses_l0_twice", "lax"),
    ("prompt_crosses_l0_twice", "pallas_interpret")])
def test_prefill_then_decode_through_the_cache_gives_the_references_logits(
        case, impl, model_and_params, engines):
    model, params = model_and_params
    n_prompt, n_new = CASES[case]
    eng, sink = engines(impl)[:2]
    prompt = _prompt(n_prompt)
    out, logits = serve_alone(eng, sink, prompt, n_new)
    assert len(out) == n_new
    _assert_close(logits, _reference_rows(model, params, prompt, out))


@pytest.fixture(scope="module")
def served(model_and_params, engines):
    """One request that crosses ``L0`` twice, served once for the
    controls: (prompt, tokens, logits)."""
    eng, sink = engines("lax")[:2]
    prompt = _prompt(27, seed=77)
    return (prompt,) + serve_alone(eng, sink, prompt, 11)


@pytest.mark.parametrize("control", [
    dict(query_scale=False), dict(rotate_key=False),
    dict(row_dtype=jnp.float8_e4m3fn)],
    ids=["a_t_left_at_1", "key_unrotated", "float8_rows"])
def test_the_tolerance_refuses_a_control(control, model_and_params, served):
    model, params = model_and_params
    prompt, out, logits = served
    _assert_close(logits, _reference_rows(model, params, prompt, out))
    want = _reference_rows(model, params, prompt, out, **control)
    worst = np.abs(logits - want).max() / np.abs(want).max()
    assert worst > 50 * LOGIT_RTOL, worst


def test_forward_is_the_reference(model_and_params):
    model, params = model_and_params
    ids = _prompt(37)
    got = np.asarray(jax.jit(model.forward)(params, jnp.asarray(ids)[None]))[0]
    _assert_close(got, _reference(model, params, ids))


def test_yarn_frequencies_lie_on_both_sides_of_the_ramp():
    from paddle_tpu.models.mla_moe_lm import yarn_frequencies
    tiny = MLAMoELMConfig.tiny()
    phi = tiny.rope_theta ** (-2.0 * np.arange(4) / 8)
    omega = np.asarray(yarn_frequencies(tiny))
    np.testing.assert_allclose(omega[0], phi[0], rtol=1e-6)     # kept
    np.testing.assert_allclose(omega[1:], phi[1:] / 4, rtol=1e-6)
    # the published rope: the ramp runs from pair 12 to pair 25
    pub = np.asarray(yarn_frequencies(MLAMoELMConfig()))
    phi = 10000.0 ** (-2.0 * np.arange(32) / 64)
    np.testing.assert_allclose(pub[:13], phi[:13], rtol=1e-6)
    np.testing.assert_allclose(pub[25:], phi[25:] / 128, rtol=1e-6)
    assert (pub[13:25] < phi[13:25]).all() \
        and (pub[13:25] > phi[13:25] / 128).all()
    assert abs(MLAMoELM(MLAMoELMConfig()).sigma - 0.194969) < 1e-6


# -- the kernels ----------------------------------------------------------------

@pytest.mark.parametrize("impl", ["lax", "pallas_interpret"])
def test_absorbed_decode_is_expanded_attention(impl):
    """The kernel on its own: queries with ``W_UK`` folded in against the
    rows, ``W_UV`` applied to what it hands back, equal to attention over
    every head's expanded keys and values."""
    rng = np.random.default_rng(0)
    s, h, dc, dn, dr, dv, ps, mp = 3, 4, 16, 8, 8, 16, 8, 4
    f = lambda *shape: rng.standard_normal(shape).astype(np.float32)  # noqa
    w_uk, w_uv = f(dc, h, dn), f(dc, h, dv)
    q_nope, q_rope = 0.3 * f(s, h, dn), 0.3 * f(s, h, dr)
    c_pages, r_pages = f(s * mp + 1, ps, dc), f(s * mp + 1, dr, ps)
    table = (1 + rng.permutation(s * mp)).reshape(s, mp).astype(np.int32)
    lengths = np.asarray([mp * ps, 11, 0], np.int32)
    qt = np.concatenate([np.einsum("shd,lhd->shl", q_nope, w_uk), q_rope],
                        -1)
    u = np.asarray(DA.latent_paged_decode_attention(
        jnp.asarray(qt), jnp.asarray(c_pages), jnp.asarray(r_pages),
        jnp.asarray(table), jnp.asarray(lengths), impl=impl))
    got = np.einsum("shl,lhv->shv", u, w_uv)
    for sl, n in enumerate(lengths):
        c = c_pages[table[sl]].reshape(-1, dc)[:n]
        k_rope = r_pages[table[sl]].transpose(0, 2, 1).reshape(-1, dr)[:n]
        if not n:
            assert not got[sl].any()
            continue
        k_nope = np.einsum("tl,lhd->thd", c, w_uk)
        v = np.einsum("tl,lhv->thv", c, w_uv)
        score = np.einsum("hd,thd->ht", q_nope[sl], k_nope) \
            + q_rope[sl] @ k_rope.T
        p = np.exp(score - score.max(-1, keepdims=True))
        want = np.einsum("ht,thv->hv", p / p.sum(-1, keepdims=True), v)
        np.testing.assert_allclose(got[sl], want, atol=2e-5)


@pytest.mark.parametrize("blocks", [
    dict(pages_per_block=1, q_rows=8), dict(pages_per_block=2, q_rows=16),
    dict(pages_per_block=4, q_rows=1024)], ids=str)
@pytest.mark.parametrize("name", ["latent_paged_prefill",
                                  "latent_paged_decode"])
def test_latent_kernels_at_every_block_size(name, blocks):
    """Query tiles of 2, 4 and all the queries of a chunk; page blocks
    that divide the table and that do not."""
    spec = kernels.get(name)
    args, kw = spec.sample_inputs(2)
    got = kernels.dispatch(name, *args, impl="pallas_interpret",
                           block_sizes=blocks, **kw)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(spec.reference_fn(*args, **kw)),
        atol=spec.contract.atol, rtol=spec.contract.rtol)


@pytest.fixture(scope="module")
def shared_pool():
    """Twelve slots over one float32 pool, pages of 8 tokens: slots 0-9
    open with the same ten pages, slots 10 and 11 share nothing."""
    rng = np.random.default_rng(43)
    s, h, dl, dr, ps, mp = 12, 2, 16, 8, 8, 12
    f = lambda *shape: rng.standard_normal(shape).astype(np.float32)  # noqa
    tables = (1 + rng.permutation(s * mp)).reshape(s, mp).astype(np.int32)
    tables[:10, :10] = tables[0, :10]
    return dict(q=0.2 * f(s, h, dl + dr), c_pages=f(s * mp + 1, ps, dl),
                r_pages=f(s * mp + 1, dr, ps), tables=tables, ps=ps)


def _groups_by_hand(members, pages, slots=12):
    """One group, written out: what the engine's grouping would never
    make (a group of one, a dead member, pages that are no whole block)."""
    group_slots = np.full((slots // 2, DA.DECODE_GROUP), -1, np.int32)
    group_slots[0, :len(members)] = members
    group_pages = np.zeros((slots // 2,), np.int32)
    group_pages[0] = pages
    shared_pages = np.zeros((slots,), np.int32)
    shared_pages[list(members)] = pages
    return group_slots, group_pages, shared_pages


FOLDS = {
    # (lengths of slots 0-11, the groups: None = the engine's own)
    "a_full_group": ([96, 90, 81, 96, 70, 88, 65, 93, 3, 0, 40, 96], None),
    "a_set_cut_in_two": ([96, 90, 81, 96, 70, 88, 65, 93, 77, 80, 40, 9],
                         None),
    "own_parts_of_every_length": ([64, 65, 72, 73, 96, 0, 0, 0, 0, 0, 1, 0],
                                  None),
    "a_group_of_one": ([96, 90, 81, 96, 70, 88, 65, 93, 77, 80, 40, 9],
                       _groups_by_hand([3], 8)),
    "a_dead_slot_in_a_group": ([96, 0, 81, 0, 70, 88, 65, 93, 77, 80, 40, 9],
                               _groups_by_hand([0, 1, 2, 3], 8)),
    "pages_left_over_a_block": ([96, 90, 81, 96, 85, 88, 85, 93, 83, 80, 40,
                                 9], _groups_by_hand([0, 1, 2, 3, 4], 10)),
    "nothing_shared": ([96, 90, 81, 96, 70, 88, 65, 93, 77, 80, 40, 9],
                       _groups_by_hand([], 0)),
}


@pytest.mark.parametrize("case", list(FOLDS))
def test_slots_folded_over_shared_pages_attend_as_each_would_alone(
        case, shared_pool):
    """``latent_paged_decode`` with the members of a group stacked against
    one copy of their shared pages, against per-slot attention in NumPy:
    eight sharers; ten, cut into eight and two; own parts from nothing (a
    slot that ends on the shared pages' edge) to four pages; and groups
    only a caller could write: of one, with a dead slot inside, over pages
    that are no whole block (the kernel rounds them down and walks the
    rest a slot). With nothing grouped every slot is walked alone, to the
    same tolerance (the groups left out:
    ``test_absorbed_decode_is_expanded_attention``)."""
    pool = shared_pool
    lengths = np.asarray(FOLDS[case][0], np.int32)
    groups = FOLDS[case][1]
    if groups is None:
        groups = DA.decode_groups(
            pool["tables"], lengths, np.flatnonzero(lengths), pool["ps"])
        members = (groups[0] >= 0).sum(1)
        assert sorted(members[members > 0]) == {
            "a_full_group": [8], "a_set_cut_in_two": [2, 8],
            "own_parts_of_every_length": [5]}[case]
        assert set(groups[1][members > 0]) == {8}
    spec = kernels.get("latent_paged_decode")
    args = tuple(jnp.asarray(pool[k]) for k in (
        "q", "c_pages", "r_pages", "tables")) + (jnp.asarray(lengths),)
    got = np.asarray(kernels.dispatch(
        "latent_paged_decode", *args, *map(jnp.asarray, groups),
        impl="pallas_interpret", block_sizes={"pages_per_block": 4}))
    want = np.asarray(spec.reference_fn(*args))
    np.testing.assert_allclose(got, want, atol=spec.contract.atol,
                               rtol=spec.contract.rtol)
    assert not got[lengths == 0].any()


def test_vmem_estimates_at_the_published_widths():
    """128 slots of 32 heads over rows of 256 + 64 in pages of 128: the
    decode body's buffers of 8 pages a pool beside one update for a
    group's 8 x 32 stacked rows fit the chip's 16 MiB default scope
    twice, the prefill body's tile of 1024 rows fits it."""
    sds = jax.ShapeDtypeStruct
    pools = (sds((4993, 128, 256), jnp.bfloat16),
             sds((4993, 64, 128), jnp.bfloat16))
    decode = kernels.get("latent_paged_decode").vmem_estimate(
        (sds((128, 32, 320), jnp.bfloat16),) + pools
        + (sds((128, 134), jnp.int32), sds((128,), jnp.int32),
           sds((64, DA.DECODE_GROUP), jnp.int32)), {},
        {"pages_per_block": 8})
    prefill = kernels.get("latent_paged_prefill").vmem_estimate(
        (sds((8, 256, 32, 320), jnp.bfloat16),) + pools, {},
        {"pages_per_block": 4, "q_rows": 1024})
    page = 128 * 320 * 2                    # nothing padded: whole tiles
    assert 2 * 8 * page < decode < 8 << 20
    assert 2 * 4 * page < prefill < 16 << 20


# -- the cache ------------------------------------------------------------------

def test_a_latent_page_is_one_row_a_token(engines):
    """``bytes_per_page``, ``capacity_bytes``, ``live_bytes`` and the
    gauge: rows x 24 values x itemsize x layers, nothing padded, no V."""
    eng, sink, reg, _ = engines("lax")
    cache = eng.cache
    page = PAGE * ROW * 4 * LAYERS
    assert [a.shape[1:] for a in cache.pages[0]] == [(PAGE, 16), (8, PAGE)]
    assert cache.bytes_per_page() == page
    assert cache.capacity_bytes() == page * (cache.config.num_pages - 1)
    assert reg.snapshot()['serving_kv_pool_bytes{layers="latent"}'] \
        == page * cache.config.num_pages
    rid = eng.submit(_prompt(13, seed=901), 4)      # 17 tokens: 3 pages
    eng.step()
    assert cache.live_bytes() == 3 * page
    cache.check_invariants()
    while not eng.scheduler.idle():
        eng.step()
    assert eng.result(rid) is not None


def test_a_latent_pool_is_alone_in_its_entry():
    """A latent row goes with nothing but ONE index row and the selection
    that reads it (``tests/test_deepseek_v32_serving.py``): slot state,
    window layers, a layer's own KV heads, values narrower than keys and
    a sink in the softmax are still refused beside it, by name; so are
    int8 pages; and without the index row the entry is the two pools."""
    spec = dict(num_layers=1, num_heads=4, vocab_size=8, max_position=8)
    latent = dict(spec, kv_heads=1, head_dim=24, latent_row=(16, 8))
    geo = dict(num_slots=2, page_size=8, num_pages=5)

    def kinds(dtype=jnp.float32, share_prefix=True, **more):
        return layer_kinds.build(ServingSpec(**latent, **more), dtype=dtype,
                                 share_prefix=share_prefix, **geo)
    cache = PagedKVCache(PagedCacheConfig(
        num_layers=1, num_heads=1, head_dim=24, kinds=kinds(), **geo))
    cache.check_invariants()
    assert type(cache.config.kinds[0]) is layer_kinds.Latent
    assert len(cache.config.kinds[0].pools) == len(cache.pages[0]) == 2
    with pytest.raises(ValueError, match="latent rows"):
        kinds(dtype=jnp.int8)
    # (what a spec may not declare beside a latent row, the spec refuses)
    for name, extra in (
            ("slot_state", dict(slot_state=(("s", (2,)),),
                                share_prefix=False)),
            ("layer_windows", dict(layer_windows=(8,), share_prefix=False)),
            ("layer_kv_heads", dict(layer_kv_heads=(2,))),
            ("value_dim", dict(value_dim=8)),
            ("sink_layers", dict(sink_layers=(True,)))):
        with pytest.raises(ValueError, match=f"cached alone.*{name}"):
            kinds(**extra)
    with pytest.raises(ValueError, match="one row a token"):
        ServingSpec(**spec, kv_heads=4, head_dim=24, latent_row=(16, 8))
    # an index row without its selection, or the other way round
    for half in (dict(select_topk=8), dict(extra_rows=(("idx", 4),))):
        with pytest.raises(ValueError, match="both or neither"):
            ServingSpec(**latent, **half)


def test_a_borrower_of_published_pages_reads_what_a_fresh_prefill_writes(
        model_and_params, engines):
    """The first request publishes its prompt's pages (two full, one part
    filled); the second opens with the same 21 tokens, maps the full pages,
    takes the part-filled one copy-on-write and appends into its copy; the
    third leaves the first's prompt inside that page, after 18 tokens, and
    writes over the rest of its copy. Each gives the reference's logits,
    which know no cache."""
    model, params = model_and_params
    eng, sink = engines("pallas_interpret")[:2]
    first = _prompt(21, seed=500)
    shared0, cow0 = eng.cache.shared_tokens_total, eng.cache.cow_copies_total
    for prompt, shared in ((first, 0),
                           (np.concatenate([first, _prompt(9, 501)]), 21),
                           (np.concatenate([first[:18], _prompt(7, 502)]),
                            18)):
        before = eng.cache.shared_tokens_total
        out, logits = serve_alone(eng, sink, prompt, 6)
        assert eng.cache.shared_tokens_total - before == shared
        _assert_close(logits, _reference_rows(model, params, prompt, out))
    assert eng.cache.shared_tokens_total - shared0 == 39
    assert eng.cache.cow_copies_total - cow0 == 2


# -- the share ------------------------------------------------------------------

@pytest.mark.parametrize("impl", ["lax", "pallas_interpret"])
def test_the_eight_shares_add_up_to_the_uncut_layer(impl):
    """Eight chips' shares of 2 experts each, the shared expert counted
    once, add up to what the uncut layer gives."""
    uncut = MLAMoELMConfig.tiny(n_routed_experts=16, kernel_impl=impl)
    whole = MLAMoELM(uncut).init(jax.random.PRNGKey(2))
    lp = whole["layers"]["1"]
    x = 0.01 * jax.random.normal(jax.random.PRNGKey(3), (3, 5, 64),
                                 jnp.float32)
    valid = jnp.ones((3, 5), bool)
    want, _ = MLAMoELM(dataclasses.replace(uncut, kernel_impl="lax")).ffn(
        whole, 1, x, valid)
    b = ref._rms(x.reshape(15, 64), lp["ffn_norm"]["scale"], 1e-6)
    shared = ref._swiglu(b, lp["shared"]).reshape(3, 5, 64)
    total, pairs = shared, 0
    for offset in range(0, 16, 2):
        cfg = dataclasses.replace(uncut, n_routed_experts=2,
                                  num_routed_experts=16,
                                  expert_offset=offset)
        tree = jax.tree_util.tree_map(lambda a: a, whole)
        tree["layers"]["1"]["experts"] = {
            k: w[offset:offset + 2] for k, w in lp["experts"].items()}
        y, stats = MLAMoELM(cfg).ffn(tree, 1, x, valid)
        total = total + (y - x) - shared
        pairs += int(stats["moe_assignments"])
        assert int(stats["moe_routed_pairs"]) == 15 * 4
        assert int(stats["moe_expert_slots"]) == 2
    assert pairs == 15 * 4          # every pair is some chip's, once
    np.testing.assert_allclose(total, want - x, rtol=0,
                               atol=2e-5 * float(jnp.abs(want - x).max()))


# -- the engine -----------------------------------------------------------------

@pytest.mark.parametrize("feature", sorted(
    set(FEATURE_OPTIONS) - {"prefix_sharing", "prefix_export"}))
def test_every_option_the_program_does_not_carry_is_refused_by_name(
        feature, model_and_params):
    model, params = model_and_params
    assert feature in FEATURES
    assert model.serving().spec.supports == {"prefix_sharing"}
    assert_refused(model, params, feature, f"MLAMoELM.*{feature!r}",
                   page_size=PAGE, prefill_chunk=CHUNK, attn_impl="auto")


def test_pages_never_leave_the_engine(engines):
    """The seventh option, ``prefix_export``, is a call: refused by name
    like the calls of the others."""
    eng = engines("lax")[0]
    for call in (lambda: eng.export_prefix_pages([1]),
                 lambda: eng.snapshot_slot(0)):
        with pytest.raises(ValueError, match="MLAMoELM.*(prefix_export|"
                                             "migration)"):
            call()
    assert not [s for s in eng.warmup_plan() if s[0].startswith("page_")]


def test_counters_and_spans_of_the_latent_rows(engines):
    """One request of 13 + 7 tokens alone: the two counters, the span
    attribute and the live bytes from lengths the host holds; one
    read-back a block."""
    eng, _sink, reg, tracer = engines("lax")
    before, n_spans = reg.snapshot(), len(tracer.spans())
    eng.generate_many([_prompt(13, seed=902)], max_new_tokens=7)
    snap = {k: v - before.get(k, 0) for k, v in reg.snapshot().items()}
    # prefill calls of 8 and 5 tokens at 0 and 8 held
    assert snap['serving_latent_rows_read_total{phase="prefill"}'] \
        == (8 + 13) * LAYERS
    assert snap['serving_latent_pairs_total{phase="prefill"}'] \
        == (sum(range(1, 9)) + sum(range(9, 14))) * LAYERS
    # decode blocks of 2 from 13 tokens on: the first token is prefill's,
    # so 6 more are three blocks; step j of a block at L held reads L+j+1
    steps = range(13, 19)
    rows = sum(n + 1 for n in steps) * LAYERS
    assert snap['serving_latent_rows_read_total{phase="decode"}'] == rows
    assert snap['serving_latent_pairs_total{phase="decode"}'] == rows
    assert snap['serving_decode_kv_bytes_total{kind="live"}'] \
        == rows * ROW * 4
    assert snap["serving_moe_routed_pairs_total"] \
        == (13 + len(steps)) * 4 * LAYERS
    assert 0 < snap["serving_moe_assignments_total"] \
        < snap["serving_moe_routed_pairs_total"]
    assert snap['serving_device_readbacks_total{phase="decode"}'] \
        == snap["serving_steps_total"] - 1
    assert snap.get('serving_device_readbacks_total{phase="prefill"}', 0) == 0
    spans = tracer.spans()[n_spans:]
    rounds = [s for s in spans if s.name == "serving.decode_round"
              and s.attrs.get("slots_live")]
    calls = [s for s in spans if s.name == "serving.prefill_call"]
    assert sum(s.attrs["latent_rows"] for s in rounds) == rows
    assert [s.attrs["latent_rows"] for s in calls] == [8 * LAYERS,
                                                       13 * LAYERS]


def _decode_blocks_of(eng, run):
    """``run()`` with every decode block's decoding slots, tables and
    lengths as dispatch found them and the groups it made: [(slots,
    tables, lengths, (group_slots, group_pages, shared_pages))]."""
    seen, dispatch = [], eng._dispatch_block

    def watched(dslots, w, rnd):
        before = (list(dslots), eng.cache.block_tables.copy(),
                  eng.cache.lengths.copy())
        blk = dispatch(dslots, w, rnd)
        seen.append(before + (tuple(
            np.asarray(a) for a in
            eng.cache.config.kinds[0].groups.kept[2]),))
        return blk
    eng._dispatch_block = watched
    try:
        run()
    finally:
        del eng._dispatch_block
    return seen


def _rows_by_hand(blocks, n_steps):
    """What the three decode counters of one layer must add up to over
    ``blocks``: (the distinct (page, row) the decoding slots hold, token
    step by token step; every slot's rows; what the walks copy: a group's
    shared pages once, a slot's rows behind its shared pages)."""
    distinct = pairs = fetched = 0
    for slots, tables, lengths, (_, group_pages, shared_pages) in blocks:
        for j in range(1, n_steps + 1):
            held = set()
            for i in slots:
                n = int(lengths[i]) + j
                held |= {(int(tables[i, t // PAGE]), t % PAGE)
                         for t in range(n)}
                pairs += n
                fetched += n - int(shared_pages[i]) * PAGE
            distinct += len(held)
            fetched += int(group_pages.sum()) * PAGE
    return distinct, pairs, fetched


def test_requests_over_a_published_prefix_decode_folded(engines):
    """Two requests that open with the 64 tokens a third published are
    decoded as one group over ONE copy of those eight pages (the Pallas
    body, interpreted), and emit the tokens they emit when the cache
    shares nothing (the same engine, its cache told not to share: the
    step programs are the ones already compiled). The decode counters:
    rows read are the distinct (page, row) of the decoding slots by
    brute force, rows fetched the walks' own sum, pairs every slot's
    rows. Two that share only two pages are no group (a group's pages
    are whole blocks of eight): each walk copies its own, and the rows
    that HAD to be read are still fewer. With nothing shared all three
    are the same number."""
    eng, _sink, reg, _ = engines("pallas_interpret")
    n_new, n_steps = 5, eng.decode_block
    names = [f'serving_latent_{kind}_total{{phase="decode"}}'
             for kind in ("rows_read", "pairs", "rows_fetched")]

    def serve(asks, sharing=True):
        shares = eng.cache.config
        eng.cache.config = dataclasses.replace(shares, share_prefix=sharing)
        before, outs = reg.snapshot(), []
        try:
            blocks = _decode_blocks_of(eng, lambda: outs.extend(
                eng.generate_many(asks, max_new_tokens=n_new)))
        finally:
            eng.cache.config = shares
        snap = reg.snapshot()
        counts = [int(snap[k] - before.get(k, 0)) // LAYERS for k in names]
        assert tuple(counts) == _rows_by_hand(blocks, n_steps)
        return ([list(o) for o in outs], counts,
                [b[3] for b in blocks if len(b[0]) == 2])

    def asks_over(n_prefix, seed):
        """Two requests over one prefix, after a third published it."""
        prefix = _prompt(n_prefix, seed=seed)
        eng.generate_many([np.concatenate([prefix, _prompt(2, seed=seed)])],
                          max_new_tokens=2)
        return [np.concatenate([prefix, _prompt(n, seed=seed + n)])
                for n in (3, 6)]

    asks = asks_over(64, 640)
    shared0 = eng.cache.shared_tokens_total
    folded, (rows, pairs, fetched), both = serve(asks)
    assert eng.cache.shared_tokens_total - shared0 == 2 * 64
    assert both and all(
        pages.tolist() == [8] and shared.tolist() == [8, 8]
        and sorted(slots[0][:2]) == [0, 1] for slots, pages, shared in both)
    # a group of two copies the prefix once: 64 rows a token step spared
    assert rows == fetched == pairs - 64 * n_steps * len(both)

    alone, (rows, pairs, fetched), both = serve(asks, sharing=False)
    assert alone == folded
    assert both and not any(pages.any() or shared.any()
                            for _, pages, shared in both)
    assert rows == pairs == fetched

    _, (rows, pairs, fetched), both = serve(asks_over(16, 160))
    assert both and not any(pages.any() for _, pages, _ in both)
    assert rows == pairs - 16 * n_steps * len(both) and fetched == pairs


# -- the benchmark's copy ---------------------------------------------------------

@pytest.fixture(scope="module")
def family():
    sys.path.insert(0, os.path.join(ROOT, "benchmark"))
    from families import mistral4
    return mistral4


def test_benchmark_reference_is_the_plain_reference(model_and_params,
                                                    family):
    """``families/mistral4.py`` computes the same pass in blocks (queries
    8 at a time, one head's keys and values at a time, the vocabulary in
    pieces, the rows asked for only): held to the plain one here, with the
    chip's share of the experts."""
    model, params = model_and_params
    ids = jnp.asarray(_prompt(40))
    sizes = family.sizes_of(model.cfg)
    want = _reference(model, params, np.asarray(ids))
    with jax.default_matmul_precision("highest"):
        got, sel = family.reference_logits(
            params, ids[None], sizes, lo=jnp.asarray(7), rows=24,
            query_block=8, vocab_block=32, probe=jnp.zeros((8,), jnp.int32))
    assert sel.shape == (0,)
    np.testing.assert_allclose(np.asarray(got)[0], want[7:31], rtol=0,
                               atol=2e-6 * np.abs(want).max())
    built = family.build(sizes, interpret=True).cfg
    assert dataclasses.replace(built, kernel_impl="lax") == model.cfg
    assert family.vocabulary(sizes) == 96 and family.positions(sizes) == 256


@pytest.mark.parametrize("control", ["query_scale", "scale_m2",
                                     "float8_weights"])
def test_the_benchmark_references_controls_move_the_logits(
        control, model_and_params, family):
    model, params = model_and_params
    ids = jnp.asarray(_prompt(40))[None]
    sizes = family.sizes_of(model.cfg)
    with jax.default_matmul_precision("highest"):
        sound = family.reference_logits(params, ids, sizes, query_block=8)
        if control == "float8_weights":
            moved = family.reference_logits(
                family.round_weights(params, "float8_e4m3fn"), ids, sizes,
                query_block=8)
        else:
            moved = family.reference_logits(params, ids, sizes,
                                            query_block=8, **{control: False})
    assert float(jnp.abs(sound - moved).max()) \
        > 1e-3 * float(jnp.abs(sound).max())


def test_kernel_needs_reads_the_two_counters_by_phase(family):
    sizes = family.sizes_of(MLAMoELMConfig())
    needs = family.kernel_needs(sizes, 2, 6, {
        'serving_latent_rows_read_total{phase="decode"}': 1000.0,
        'serving_latent_pairs_total{phase="decode"}': 1000.0,
        'serving_latent_rows_read_total{phase="prefill"}': 300.0,
        'serving_latent_pairs_total{phase="prefill"}': 7000.0,
        "serving_moe_experts_touched_total": 5.0,
        "serving_moe_assignments_total": 40.0}, 0.0, 0.0)
    assert needs["latent_decode_needed_bytes"] == 1000 * 320 * 2
    assert needs["latent_decode_needed_flops"] == 1000 * 32 * 1152
    assert needs["latent_prefill_needed_bytes"] == 300 * 320 * 2
    assert needs["latent_prefill_needed_flops"] == 7000 * 32 * 1152
    assert needs["moe_ffn_needed_bytes"] == 5 * 3 * 4096 * 2048 * 2
    assert needs["moe_ffn_needed_flops"] == 40 * 6.0 * 4096 * 2048
    assert family.kernel_needs(sizes, 2, 6, {}, 0.0, 0.0)[
        "latent_decode_needed_bytes"] == 0
