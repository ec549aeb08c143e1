"""A token selection over a latent cache, leading dense layers and the
group-limited sigmoid router (DeepSeek-V3.2, ISSUE 55), through the paged
serving engine, against the plain float32 reference in the expanded form.

Sizes (``MLAMoELMConfig.tiny_selecting``): hidden 64, 4 heads,
``q_lora_rank`` 32, ``kv_lora_rank`` 16, nope 8, rope 8, values 16; an
indexer of 2 heads of 16 taking 16 tokens; three layers, the first dense
(48 wide), then 16 sigmoid-routed experts of 32 in 4 groups of which 2 are
kept, 4 a token, 4 held from 4 on beside a shared one; page 8, chunk 8.
The requests below hold 29-51 tokens, so the selection binds in prefill
(from the third chunk on) AND in decode. A token's cached row is 16 + 8
values and 16 of index key a layer. Weights are seeded float32 as ``init``
draws them but for the projections the scores, the index and the router
are made of, which are drawn larger (``_params``). ONE engine an ``impl``
serves every case of this file (module-scoped), a request at a time.
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
from paddle_tpu.kernels import autotune
from paddle_tpu import observability as obs
from paddle_tpu.models.mla_moe_lm import MLAMoELM, MLAMoELMConfig
from paddle_tpu.serving import layer_kinds
from paddle_tpu.serving import sparse_attention as SA
from paddle_tpu.serving.program import FEATURES, ServingSpec

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
import deepseek_v32_reference as ref  # noqa: E402
from serving_taps import (assert_close, assert_refused,  # noqa: E402
                          FEATURE_OPTIONS, moved, serve_alone,
                          shared_engines, tapped_engine, traced)
from serving_taps import prompt as _prompt  # noqa: E402

#: float32 on both sides, the absorbed sums over gathered rows in another
#: order than the expanded, masked ones: 2e-5 OF THE LARGEST LOGIT. Every
#: control below reads over 1e-3 of it
LOGIT_RTOL = 2e-5
_assert_close = functools.partial(assert_close, rtol=LOGIT_RTOL)

PAGE, CHUNK, LAYERS, TOPK = 8, 8, 3, 16


def _sizes(cfg):
    """The published keys the reference reads, from a program config."""
    sizes = {k: getattr(cfg, k) for k in (
        "num_hidden_layers", "num_attention_heads", "kv_lora_rank",
        "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "rms_norm_eps",
        "num_experts_per_tok", "routed_scaling_factor", "norm_topk_prob",
        "expert_offset", "n_group", "topk_group", "first_k_dense_replace",
        "index_n_heads", "index_head_dim", "index_topk")}
    sizes["rope_parameters"] = {
        "rope_theta": cfg.rope_theta, "factor": cfg.rope_factor,
        "original_max_position_embeddings":
            cfg.original_max_position_embeddings,
        "beta_fast": cfg.beta_fast, "beta_slow": cfg.beta_slow,
        "mscale": cfg.mscale, "mscale_all_dim": cfg.mscale_all_dim}
    return sizes


def _params(model, seed=5):
    """``init``'s draw with the query, row, index-query and router
    projections 8, 4, 8 and 8 times as large: at width 64 a draw of 0.02
    leaves every score near 0.02, the softmax, the index and the sigmoid
    scores flat, and no control below would move a logit."""
    params = model.init(jax.random.PRNGKey(seed))
    for lp in params["layers"].values():
        lp["q_b_proj"]["weight"] = 8.0 * lp["q_b_proj"]["weight"]
        lp["kv_a_proj"]["weight"] = 4.0 * lp["kv_a_proj"]["weight"]
        lp["idx_q"]["weight"] = 8.0 * lp["idx_q"]["weight"]
        if "router" in lp:
            lp["router"]["weight"] = 8.0 * lp["router"]["weight"]
    return params


@pytest.fixture(scope="module")
def model_and_params():
    model = MLAMoELM(MLAMoELMConfig.tiny_selecting(kernel_impl="lax"))
    return model, _params(model)


def _engine(params, impl="lax"):
    eng, sink, reg = tapped_engine(
        MLAMoELM(MLAMoELMConfig.tiny_selecting(kernel_impl=impl)), params,
        num_slots=2, page_size=PAGE, prefill_chunk=CHUNK, attn_impl=impl,
        tracer=obs.Tracer(enabled=False))
    return eng, sink, reg


@pytest.fixture(scope="module")
def engines(model_and_params):
    return shared_engines(lambda impl: _engine(model_and_params[1], impl))


_REFERENCE = {}


def _reference(model, params, ids, **controls):
    """The plain reference's logits of ``ids``, computed at ONE padded
    length (the pass is causal), so that a set of controls compiles once
    for the whole file."""
    key = tuple(sorted((k, str(v)) for k, v in controls.items()))
    if key not in _REFERENCE:
        def plain_reference(p, i):
            with jax.default_matmul_precision("highest"):
                return ref.reference_logits(p, i, _sizes(model.cfg),
                                            **controls)
        _REFERENCE[key] = jax.jit(plain_reference)
    padded = np.zeros((56,), np.int32)
    padded[:len(ids)] = ids
    return np.asarray(_REFERENCE[key](params, jnp.asarray(padded)))[:len(ids)]


def _reference_rows(model, params, prompt, out, **controls):
    logits = _reference(model, params, np.concatenate([prompt, out]),
                        **controls)
    n0 = len(prompt)
    return logits[n0 - 1:n0 - 1 + len(out)]


CASES = {
    # two chunks: every query sees at most 16 tokens until decode, which
    # selects from its second token on
    "selects_in_decode_only": (15, 6),
    # four chunks (8, 8, 8, 5): the third and fourth select; decode
    # crosses a page edge at 32
    "selects_in_prefill_and_decode": (29, 9),
    # five whole chunks, the prompt ends on a page and chunk edge; decode
    # crosses a page edge at 48
    "prompt_ends_on_an_edge": (40, 11),
}


@pytest.mark.parametrize("impl", ["lax", "pallas_interpret"])
@pytest.mark.parametrize("case", list(CASES))
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
    """One request whose selection binds in both phases, served once for
    the controls: (prompt, tokens, logits)."""
    eng, sink = engines("lax")[:2]
    prompt = _prompt(27, seed=77)
    return (prompt,) + serve_alone(eng, sink, prompt, 11)


def _float8(params):
    return jax.tree.map(
        lambda a: a.astype(jnp.float8_e4m3fn).astype(a.dtype), params)


@pytest.mark.parametrize("control", [
    "selection", "group_limit", "index_rope", "scale_m2", "float8_weights"])
def test_the_tolerance_refuses_a_control(control, model_and_params, served):
    """The selection left out, the group limit left out, the indexer's
    rotary part left out, the softmax scale without ``m^2``, weights
    rounded to float8: each moves the reference's logits by more than 50
    times the tolerance."""
    model, params = model_and_params
    prompt, out, logits = served
    _assert_close(logits, _reference_rows(model, params, prompt, out))
    if control == "float8_weights":
        want = _reference_rows(model, _float8(params), prompt, out)
    else:
        want = _reference_rows(model, params, prompt, out,
                               **{control: False})
    worst = np.abs(logits - want).max() / np.abs(want).max()
    assert worst > 50 * LOGIT_RTOL, worst


def test_forward_is_the_reference(model_and_params):
    model, params = model_and_params
    ids = _prompt(45)
    got = np.asarray(jax.jit(model.forward)(params, jnp.asarray(ids)[None]))[0]
    _assert_close(got, _reference(model, params, ids))


def test_mistrals_defaults_build_mistrals_program():
    """The fields this family added default to what leaves Mistral Small
    4's program as it was: softmax over all experts, no dense layer, no
    indexer, a latent row cached alone."""
    cfg = MLAMoELMConfig()
    assert (cfg.first_k_dense_replace, cfg.scoring_func, cfg.n_group,
            cfg.topk_group, cfg.index_topk) == (0, "softmax", 1, 1, None)
    spec = MLAMoELM(MLAMoELMConfig.tiny()).serving().spec
    assert spec.extra_rows == () and spec.select_topk is None
    tree = MLAMoELM(MLAMoELMConfig.tiny()).init(jax.random.PRNGKey(0))
    assert not [k for k in tree["layers"]["0"]
                if k.startswith("idx_") or k in ("mlp", "router_bias")]
    with pytest.raises(ValueError, match="group limit"):
        MLAMoELMConfig.tiny(n_group=4, topk_group=2)     # softmax
    with pytest.raises(ValueError, match="intermediate_size"):
        MLAMoELMConfig.tiny(first_k_dense_replace=1)


# -- the share ------------------------------------------------------------------

@pytest.mark.parametrize("impl", ["lax", "pallas_interpret"])
def test_the_shares_add_up_to_the_uncut_layer_group_limit_included(impl):
    """Four chips' shares of 4 experts each (a share is one GROUP of the
    router's four), the shared expert counted once, add up to what the
    uncut layer gives; and the uncut layer is the reference's, whose
    router keeps 2 groups of 4."""
    uncut = MLAMoELMConfig.tiny_selecting(
        n_routed_experts=16, expert_offset=0, kernel_impl=impl)
    model = MLAMoELM(dataclasses.replace(uncut, kernel_impl="lax"))
    whole = _params(model, seed=2)
    lp = whole["layers"]["1"]
    x = 0.01 * jax.random.normal(jax.random.PRNGKey(3), (3, 5, 64),
                                jnp.float32)
    valid = jnp.ones((3, 5), bool)
    want, _ = model.ffn(whole, 1, x, valid)
    t = ref._rms(x.reshape(15, 64), lp["ffn_norm"]["scale"], 1e-6)
    shared = ref._swiglu(t, lp["shared"]).reshape(3, 5, 64)
    with jax.default_matmul_precision("highest"):
        coef = ref.routed(
            1.0 / (1.0 + jnp.exp(-(t @ lp["router"]["weight"]))),
            lp["router_bias"], _sizes(uncut))
    # 4 a token, inside 2 of the 4 groups of 4
    assert ((coef > 0).sum(-1) == 4).all()
    assert ((coef.reshape(15, 4, 4) > 0).any(-1).sum(-1) <= 2).all()
    total, pairs = shared, 0
    for offset in range(0, 16, 4):
        cfg = dataclasses.replace(uncut, n_routed_experts=4,
                                  num_routed_experts=16,
                                  expert_offset=offset)
        tree = jax.tree_util.tree_map(lambda a: a, whole)
        tree["layers"]["1"]["experts"] = {
            k: w[offset:offset + 4] for k, w in lp["experts"].items()}
        y, stats = MLAMoELM(cfg).ffn(tree, 1, x, valid)
        total = total + (y - x) - shared
        pairs += int(stats["moe_assignments"])
        assert int(stats["moe_assignments"]) == int(
            (coef[:, offset:offset + 4] > 0).sum())
    assert pairs == 15 * 4          # every pair is some chip's, once
    np.testing.assert_allclose(total, want - x, rtol=0,
                               atol=2e-5 * float(jnp.abs(want - x).max()))
    # and the uncut layer is the reference's dense weighted sum
    hidden = jnp.stack([
        (ref._silu(t @ lp["experts"]["gate"][e].T)
         * (t @ lp["experts"]["up"][e].T)) @ lp["experts"]["down"][e]
        for e in range(16)], 1)                             # (15, 16, 64)
    plain = shared + jnp.einsum("te,ted->td", coef, hidden).reshape(3, 5, 64)
    np.testing.assert_allclose(want - x, plain, rtol=0,
                               atol=2e-5 * float(jnp.abs(plain).max()))


def test_the_dense_layer_counts_no_expert(model_and_params):
    model, params = model_and_params
    x = jnp.ones((1, 2, 64), jnp.float32)
    y, stats = model.ffn(params, 0, x, jnp.ones((1, 2), bool))
    assert y.shape == x.shape and not any(int(v) for v in stats.values())
    assert "mlp" in params["layers"]["0"] \
        and "experts" not in params["layers"]["0"]


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


def test_the_program_declares_a_latent_row_index_rows_and_a_selection(
        model_and_params, engines):
    model, _ = model_and_params
    spec = model.serving().spec
    assert (spec.latent_row, spec.extra_rows, spec.select_topk) == (
        (16, 8), (("index_k", 16),), TOPK)
    eng = engines("lax")[0]
    kind, = set(eng.cache.config.kinds)
    assert type(kind) is layer_kinds.SelectingLatent
    assert isinstance(kind, layer_kinds.Latent) and kind.prefill_run == 1
    # (latent, rotary key in a row of whole lane tiles, index key with the
    # tokens along the lanes), in the order the runner's replay unpacks
    assert [a.shape[1:] for a in eng.cache.pages[0]] == [
        (PAGE, 16), (PAGE, 128), (16, PAGE)]
    assert eng.cache.bytes_per_page() == PAGE * (16 + 128 + 16) * 4 * LAYERS
    eng.cache.check_invariants()
    # its step programs take a slot's whole table and no narrower one: a
    # call reads ``topk`` rows whatever the width
    widest = eng.cache.config.max_pages_per_slot
    assert kind.whole_table and not layer_kinds.Latent.whole_table
    assert {sig[1] for sig in eng.warmup_plan()
            if sig[0] in ("decode", "prefill")} == {widest}
    assert eng._pow2_width(1) == widest > 1
    assert eng.reachable_signatures() <= set(eng.warmup_plan())


def test_what_a_latent_row_goes_with_and_what_it_does_not():
    """A latent row is cached alone or beside ONE index row and the
    selection that reads it; slot state, window layers, a layer's own KV
    heads, narrower values and a sink stay refused by name."""
    spec = dict(num_layers=1, num_heads=4, vocab_size=8, max_position=8,
                kv_heads=1, head_dim=24, latent_row=(16, 8))
    ServingSpec(**spec)
    ServingSpec(**spec, extra_rows=(("index_k", 4),), select_topk=8)
    for half in (dict(select_topk=8), dict(extra_rows=(("index_k", 4),)),
                 dict(extra_rows=(("a", 4), ("b", 4)), select_topk=8)):
        with pytest.raises(ValueError, match="both or neither"):
            ServingSpec(**spec, **half)
    for name, more in (("slot_state", dict(slot_state=(("s", (2,)),))),
                       ("layer_windows", dict(layer_windows=(8,))),
                       ("layer_kv_heads", dict(layer_kv_heads=(2,))),
                       ("value_dim", dict(value_dim=8)),
                       ("sink_layers", dict(sink_layers=(True,)))):
        with pytest.raises(ValueError, match=f"cached alone.*{name}"):
            ServingSpec(**spec, **more)
    selecting = ServingSpec(**spec, extra_rows=(("index_k", 4),),
                            select_topk=8)
    geo = dict(num_slots=2, page_size=8, num_pages=5)
    with pytest.raises(ValueError, match="latent rows"):
        layer_kinds.build(selecting, dtype=jnp.int8, share_prefix=True,
                          **geo)
    with pytest.raises(ValueError, match="multiple of page_size"):
        layer_kinds.build(ServingSpec(**spec, extra_rows=(("i", 4),),
                                      select_topk=12),
                          dtype=jnp.float32, share_prefix=True, **geo)
    kinds = layer_kinds.build(selecting, dtype=jnp.float32,
                              share_prefix=True, **geo)
    assert type(kinds[0]) is layer_kinds.SelectingLatent


def test_a_borrower_of_published_pages_reads_what_a_fresh_prefill_writes(
        model_and_params, engines):
    """The first request publishes its prompt's pages (all three pools of
    a page under one id); the second opens with the same 37 tokens, maps
    the four full pages and takes the part-filled one copy-on-write; each
    gives the reference's logits, which know no cache, with the selection
    binding over borrowed rows."""
    model, params = model_and_params
    eng, sink = engines("pallas_interpret")[:2]
    first = _prompt(37, seed=500)
    shared0 = eng.cache.shared_tokens_total
    for prompt, shared in ((first, 0),
                           (np.concatenate([first, _prompt(9, 501)]), 37)):
        before = eng.cache.shared_tokens_total
        out, logits = serve_alone(eng, sink, prompt, 5)
        assert eng.cache.shared_tokens_total - before == shared
        _assert_close(logits, _reference_rows(model, params, prompt, out))
    assert eng.cache.shared_tokens_total - shared0 == 37


def test_counters_of_the_selected_rows(model_and_params, engines):
    """One request of 21 + 7 tokens alone: rows read and pairs are the
    SELECTED rows (min(seen, 16) a query), held what the queries see, the
    index rows those of the buckets that select; either phase fetches the
    rows its walks copy: every row a query sees, where no whole block of
    8 pages lies under a group of chunk tokens and the slot decodes
    alone."""
    eng, _sink, reg = engines("lax")
    before = reg.snapshot()
    with traced(eng) as tracer:
        eng.generate_many([_prompt(21, seed=902)], max_new_tokens=7)
    snap = moved(reg, before)
    # prefill calls of 8, 8 and 5 tokens at 0, 8 and 16 held
    seen = list(range(1, 22))
    read = sum(min(n, TOPK) for n in seen) * LAYERS
    for name in ("rows_read", "pairs"):
        assert snap[f'serving_latent_{name}_total{{phase="prefill"}}'] \
            == read
    for name in ("rows_held", "rows_fetched"):
        assert snap[f'serving_latent_{name}_total{{phase="prefill"}}'] \
            == sum(seen) * LAYERS
    # decode blocks of 2 from 21 tokens on: the first token is prefill's,
    # so 6 more; step j of a block at L held sees L + j + 1
    steps = range(21, 27)
    held = sum(n + 1 for n in steps) * LAYERS
    for name in ("rows_read", "pairs"):
        assert snap[f'serving_latent_{name}_total{{phase="decode"}}'] \
            == len(steps) * TOPK * LAYERS
    assert snap['serving_latent_rows_held_total{phase="decode"}'] == held
    assert snap['serving_latent_rows_fetched_total{phase="decode"}'] == held
    # the third chunk's table is 3 pages wide (bucket 4: 32 > 16 selects)
    # and every decode bucket selects: a lone slot fetches what it scores
    scored = (21 + sum(n + 1 for n in steps)) * LAYERS
    assert snap["serving_index_rows_scored_total"] == scored
    assert snap["serving_index_rows_fetched_total"] == scored
    (groups,) = eng._shared_groups(np.arange(2))
    assert not np.asarray(groups[1]).any()           # no slots are folded
    assert snap["serving_attn_context_tokens_total"] \
        == (sum(seen) + sum(n + 1 for n in steps)) * LAYERS
    assert snap["serving_attn_selected_tokens_total"] \
        == read + len(steps) * TOPK * LAYERS
    rounds = [s for s in tracer.spans() if s.name == "serving.decode_round"
              and s.attrs.get("slots_live")]
    assert sum(s.attrs["latent_rows"] for s in rounds) \
        == len(steps) * TOPK * LAYERS


def test_requests_over_a_published_document_decode_folded(model_and_params,
                                                          engines):
    """Two requests that open with the 64 tokens a third published decode
    as one group over ONE copy of its eight pages (the Pallas bodies,
    interpreted), each compacting its own selection out of them, and emit
    the tokens they emit when the cache shares nothing (the same engine,
    its cache told not to share). The counters, from the tables and
    lengths the host holds: the pairs are the selected rows either way;
    the walks copy the group's 64 shared rows once a token step and layer
    where the slots hold them twice, and what the slots hold where
    nothing is shared."""
    eng, _sink, reg = engines("pallas_interpret")
    document = _prompt(64, seed=910)
    eng.generate_many([np.concatenate([document, _prompt(2, 911)])],
                      max_new_tokens=2)
    asks = [np.concatenate([document, _prompt(n, 912 + n)]) for n in (3, 6)]
    names = [f'serving_latent_{name}_total{{phase="decode"}}'
             for name in ("rows_fetched", "rows_held", "pairs")]

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

    folded, (fetched, held, pairs) = serve(True)
    kept = eng.cache.config.kinds[0].groups
    _slots, _tables, groups, _twice, spared = kept.kept
    assert np.asarray(groups[1]).tolist() == [8] and spared == 8 * PAGE
    assert sorted(np.asarray(groups[0])[0, :2]) == [0, 1]
    # blocks of 2 token steps, 3 layers: 64 rows spared each
    assert held > fetched > 0 and (held - fetched) % (64 * 2 * LAYERS) == 0
    alone, (fetched, held, pairs_alone) = serve(False)
    assert alone == folded
    assert fetched == held > 0
    assert pairs == pairs_alone and pairs % (TOPK * LAYERS) == 0
    assert not np.asarray(kept.kept[2][1]).any()


def test_prefill_counts_what_its_walks_copy(engines):
    """``serving_latent_rows_fetched_total{phase="prefill"}`` from the
    ``starts`` and ``ns`` the host holds: eight chunk tokens of a lane are
    a group, the whole blocks of 8 pages under its first token copied
    once for the group and every token's rows from there to itself once
    for the token; held is what the tokens see."""
    eng, _sink, reg = engines("lax")
    kind = eng.cache.config.kinds[0]
    starts, ns = np.asarray([0, 70, 200, 127, 300]), \
        np.asarray([8, 5, 16, 3, 0])
    before = reg.snapshot()
    kind.count_prefill(None, starts, ns)
    snap = moved(reg, before)
    block = 8 * PAGE
    fetched = held = 0
    for start, n in zip(starts, ns):
        for first in range(0, n, 8):
            shared = (start + first + 1) // block * block
            seen = [start + j + 1 for j in range(first, min(first + 8, n))]
            fetched += shared + sum(e - shared for e in seen)
            held += sum(seen)
    assert (200 + 9) // block * block == 192 and held > fetched
    assert snap['serving_latent_rows_fetched_total{phase="prefill"}'] \
        == fetched * LAYERS
    assert snap['serving_latent_rows_held_total{phase="prefill"}'] \
        == held * LAYERS


# -- the kernels ----------------------------------------------------------------

@pytest.fixture
def released():
    """An interpreted walk-and-compact program maps about 1,500 memory
    regions of the 65,530 a process may hold (``vm.max_map_count``), and
    a test worker keeps every program it compiled: a case that compiles
    one of its own gives it back."""
    yield
    jax.clear_caches()


@pytest.fixture(scope="module", autouse=True)
def _programs_released_after_the_file():
    yield
    jax.clear_caches()



_WALK = dict(pages_per_block=8, rows_a_pass=16)


@pytest.mark.parametrize("name, blocks, seed", [
    (name, blocks, seed) for name, blocks, seeds in (
        ("sparse_latent_prefill",
         dict(q_rows=8, pages_per_block=2, rows_a_pass=4), (1, 2)),
        # (88 rows: the one sample that is more than a pair of calls of 64)
        ("sparse_latent_prefill", dict(q_rows=64, **_WALK), (1,)),
        ("sparse_latent_decode", _WALK, (0, 1, 2)),
        ("sparse_latent_decode", dict(pages_per_block=2, rows_a_pass=4),
         (0, 1, 2)),
        ("sparse_latent_decode", dict(pages_per_block=4, rows_a_pass=1),
         (0, 1, 2))) for seed in seeds], ids=str)
@pytest.mark.usefixtures("released")
def test_selecting_latent_kernels_at_every_block_size(name, blocks, seed):
    """Prefill: a group of rows a pair of calls (pairs none of whose rows
    is live are skipped) and up to 64, under blocks of 2 and 8 pages and 4
    and 16 compacted rows a pass. Decode: blocks of 8, 2 and 4
    pages (tables of 12, 10 and 18: ragged last blocks), 16, 4 and 1
    compacted rows a pass, so that pages of 8 and 16 tokens take further
    passes; a pair, three and a pair, a whole group and a slot alone."""
    spec = kernels.get(name)
    args, kw = spec.sample_inputs(seed)
    got = kernels.dispatch(name, *args, impl="pallas_interpret",
                           block_sizes=blocks, **kw)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(spec.reference_fn(*args, **kw)),
        atol=spec.contract.atol, rtol=spec.contract.rtol)


#: the grouped decode's cases below, one geometry (so one interpreted
#: program): 10 slots, 19 pages of 16 a table (16 a document's, in blocks
#: of 8, then 3 of a slot's own: a ragged last block), 4 compacted rows a
#: pass, so that a page's fifth selected row takes a second pass
GS, GH, GDL, GDR, GPS, GMP, GROWS = 10, 4, 32, 8, 16, 19, 4


def _grouped_case(case):
    """-> (q, c_pages, r_pages, tables, selected, extent, groups | None)."""
    rng = np.random.default_rng(7)
    t = GMP * GPS
    pages = GS * GMP + 1
    f = lambda *shape: rng.standard_normal(shape).astype(np.float32)  # noqa
    q = (GDL + GDR) ** -0.5 * f(GS, GH, GDL + GDR)
    c_pages, r_pages = f(pages, GPS, GDL), f(pages, GPS, 2 * GDR)
    tables = (1 + rng.permutation(pages - 1)[:GS * GMP]).reshape(
        GS, GMP).astype(np.int32)
    lengths = rng.integers(16 * GPS + 1, t + 1, GS).astype(np.int32)
    sharers = {"no_group": (), "a_slot_walked_alone": (),
               "groups_of_2": ([0, 1], [4, 7]),
               "a_group_of_8": (list(range(8)),),
               "a_missing_member": ([1, 2, 3, 5, 8],)}.get(
                   case, ([0, 1, 2],))
    for slots in sharers:
        tables[slots, :16] = tables[slots[0], :16]
    scores = f(GS, t)
    if case == "a_slot_walked_alone":
        lengths[3] = t                      # every page of its table
    selected = np.asarray(SA.select_decode_mask(
        jnp.asarray(scores), jnp.asarray(lengths), 40)).copy()
    if case == "a_member_selects_nothing":
        selected[1] = 0.0
    elif case == "an_overflowing_page":
        # 7 rows of shared page 2 and 13 of own page 17, for widths of 4
        selected[0, 2 * GPS:2 * GPS + 7] = 1.0
        lengths[2] = t
        selected[2, 17 * GPS + 3:18 * GPS] = 1.0
    elif case == "a_whole_page":
        selected[1, 5 * GPS:6 * GPS] = 1.0          # a shared one
        lengths[0] = 18 * GPS
        selected[0, 17 * GPS:18 * GPS] = 1.0        # and an own one
    elif case == "the_current_tokens_row":
        # the last live row, alone in a page of the slot's own
        lengths[:] = 17 * GPS + 1
        selected[:, 16 * GPS:] = 0.0
        selected[:, 17 * GPS] = 1.0
    groups = None if case == "no_group" else SA.DA.decode_groups(
        tables, lengths, np.arange(GS), GPS)
    return tuple(map(jnp.asarray, (q, c_pages, r_pages, tables, selected,
                                   lengths))) + (groups,)


@pytest.mark.parametrize("case", [
    "no_group", "groups_of_2", "a_group_of_8", "a_missing_member",
    "a_slot_walked_alone", "a_member_selects_nothing",
    "an_overflowing_page", "a_whole_page", "the_current_tokens_row"])
def test_grouped_decode_compacts_and_folds(case):
    """The two parts of ``sparse_latent_decode`` against the NumPy
    reference over the positions the mask marks, whoever shares what."""
    *args, groups = _grouped_case(case)
    if groups is not None:
        members = (np.asarray(groups[0]) >= 0).sum(1)
        want = {"groups_of_2": [2, 2], "a_group_of_8": [8],
                "a_missing_member": [5], "a_slot_walked_alone": []}.get(
                    case, [3])
        assert members[members > 0].tolist() == want
        assert set(np.asarray(groups[1])[:len(want)]) <= {16}
        groups = tuple(map(jnp.asarray, groups))
    got = kernels.dispatch(
        "sparse_latent_decode", *args,
        *(groups or SA.DA.decode_groups(args[3], args[5], (), 1)),
        impl="pallas_interpret",
        block_sizes=dict(pages_per_block=8, rows_a_pass=GROWS))
    want = SA._sparse_latent_decode_reference(*args)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)
    if case == "a_member_selects_nothing":
        assert not np.asarray(got)[1].any()


@pytest.mark.parametrize("impl", ["lax", "pallas_interpret"])
def test_a_table_no_wider_than_topk_selects_every_live_row(impl):
    """No scores are made: the mask is every position under the slot's
    length, whole pages of it, through the same kernel."""
    rng = np.random.default_rng(3)
    s, h, dl, dr, ps, mp = 3, 2, 16, 8, 8, 4
    f = lambda *shape: jnp.asarray(rng.standard_normal(shape),  # noqa: E731
                                   jnp.float32)
    q, c_pages, r_pages = 0.2 * f(s, h, dl + dr), f(s * mp + 1, ps, dl), \
        f(s * mp + 1, ps, 2 * dr)
    tables = jnp.asarray((1 + rng.permutation(s * mp)).reshape(s, mp),
                         jnp.int32)
    lengths = jnp.asarray([mp * ps, 11, 1], jnp.int32)
    got, n_sel = SA.latent_indexed_decode_attention(
        q, c_pages, r_pages, None, tables, lengths, None, None, mp * ps,
        impl=impl)
    np.testing.assert_array_equal(np.asarray(n_sel), np.asarray(lengths))
    want = SA._sparse_latent_reference(
        q, c_pages, r_pages, tables,
        np.arange(mp * ps)[None, :] < np.asarray(lengths)[:, None])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=str)
def test_the_compacted_rows_are_the_gathered_rows_bit_for_bit(dtype):
    """A page's selected rows by the one-hot product, 16 ranks a pass,
    equal the rows a gather takes, in order, every bit; the rows past a
    pass's count are zeros."""
    rng = np.random.default_rng(11)
    ps, d, width = 128, 640, 16
    page = jnp.asarray(rng.standard_normal((ps, d)) * 37.0, dtype)
    marks = np.zeros((3, ps), np.float32)
    marks[0, rng.permutation(ps)[:7]] = 1       # under a pass
    marks[1, rng.permutation(ps)[:41]] = 1      # three passes
    marks[2] = 1                                # the whole page: eight
    ranks, counts = SA._page_ranks(jnp.asarray(marks))
    np.testing.assert_array_equal(np.asarray(counts)[:, 0], [7, 41, 128])
    got = [[] for _ in marks]
    for k in range(ps // width):
        one_hot = SA._one_hot_of_ranks(
            [ranks[m:m + 1] for m in range(len(marks))], k * width, width,
            dtype)
        rows = np.asarray(SA._compact(one_hot, page).astype(jnp.float32))
        for m in range(len(marks)):
            got[m].append(rows[m * width:(m + 1) * width])
    for m, row in enumerate(marks):
        mine = np.concatenate(got[m])
        n = int(row.sum())
        np.testing.assert_array_equal(
            mine[:n], np.asarray(page.astype(jnp.float32))[
                np.flatnonzero(row)])
        assert not mine[n:].any()


@pytest.mark.parametrize("impl", ["lax", "pallas_interpret"])
def test_gathered_absorbed_decode_is_expanded_attention_over_the_selection(
        impl):
    """The index form the runner's replay calls, on a LATENT entry's first
    two pools, at sizes where a page is as wide as the rotary key: told
    from K and V by the queries being wider than the first pool's rows;
    ``W_UK`` folded into the queries and ``W_UV`` applied to what comes
    back equal attention over every head's expanded keys and values of
    the selected tokens."""
    rng = np.random.default_rng(0)
    s, h, dc, dn, dr, dv, ps, mp, k = 3, 4, 16, 8, 8, 16, 8, 4, 6
    f = lambda *shape: rng.standard_normal(shape).astype(np.float32)  # noqa
    w_uk, w_uv = f(dc, h, dn), f(dc, h, dv)
    q_nope, q_rope = 0.3 * f(s, h, dn), 0.3 * f(s, h, dr)
    c_pages, r_pages = f(s * mp + 1, ps, dc), f(s * mp + 1, ps, dr)
    table = (1 + rng.permutation(s * mp)).reshape(s, mp).astype(np.int32)
    idx = np.stack([rng.permutation(mp * ps)[:k] for _ in range(s)]
                   ).astype(np.int32)
    n_sel = np.asarray([k, 3, 0], np.int32)
    qt = np.concatenate([np.einsum("shd,lhd->shl", q_nope, w_uk), q_rope],
                        -1)
    u = np.asarray(SA.sparse_paged_decode_attention(
        jnp.asarray(qt), jnp.asarray(c_pages), jnp.asarray(r_pages),
        jnp.asarray(table), jnp.asarray(idx), jnp.asarray(n_sel), impl=impl))
    assert u.shape == (s, h, dc)
    got = np.einsum("shl,lhv->shv", u, w_uv)
    for sl, n in enumerate(n_sel):
        if not n:
            assert not got[sl].any()
            continue
        toks = idx[sl, :n]
        c = c_pages[table[sl, toks // ps], toks % ps]
        k_rope = r_pages[table[sl, toks // ps], toks % ps]
        k_nope = np.einsum("tl,lhd->thd", c, w_uk)
        v = np.einsum("tl,lhv->thv", c, w_uv)
        score = np.einsum("hd,thd->ht", q_nope[sl], k_nope) \
            + q_rope[sl] @ k_rope.T
        p = np.exp(score - score.max(-1, keepdims=True))
        want = np.einsum("ht,thv->hv", p / p.sum(-1, keepdims=True), v)
        np.testing.assert_allclose(got[sl], want, atol=2e-5)


#: the prefill's cases below, one geometry: 2 lanes of a chunk of 16 over
#: tables of 12 pages of 16, 16 rows a pair of calls (a lane a pair),
#: blocks of 4 pages, 4 compacted rows a pass, so that a page's fifth
#: selected row takes a second pass; (chunk_starts, n_valid) a case
PS_, PC, PH, PDL, PDR, PPS, PMP, PTOPK = 2, 16, 4, 32, 8, 16, 12, 40
PREFILL_CASES = {
    # tokens 150-165: the chunk's own rows lie on both sides of a page edge
    "a_chunk_straddles_a_page": ((150, 8), (16, 16)),
    # 11 and 5 live tokens: the second group of lane 0 and the one of
    # lane 1 are part dead, their dead rows last
    "a_partly_dead_group": ((130, 140), (11, 5)),
    # lane 0 carries nothing: its pair of calls is skipped, zeros
    "a_dead_lane_beside_a_live_one": ((96, 40), (0, 16)),
    # no page lies whole under a fresh lane's tokens: all Part B
    "no_shared_page": ((0, 0), (16, 9)),
    # the table's 192 tokens are no more than ``topk``: no scores made
    "every_visible_row": ((100, 176), (16, 16)),
    # a whole block of 8 pages under each lane, out of its own table
    "two_lanes_two_tables": ((130, 160), (16, 16)),
    # 7 rows of a shared page and 13 of an own one, for passes of 4
    "an_overflowing_page": ((144, 170), (16, 16)),
}


@functools.lru_cache(maxsize=None)
def _prefill_through(impl):
    """One jitted call an ``impl`` for every case (an eager call traces
    its ``lax.map`` anew and compiles it again)."""
    blocks = {} if impl == "lax" else {"block_sizes": dict(
        q_rows=16, pages_per_block=4, rows_a_pass=4)}
    return jax.jit(lambda *args: kernels.dispatch(
        "sparse_latent_prefill", *args, impl=impl, **blocks))


@pytest.mark.parametrize("impl", ["lax", "pallas_interpret"])
@pytest.mark.parametrize("case", list(PREFILL_CASES))
def test_prefill_walks_and_compacts(case, impl):
    """``sparse_latent_prefill`` against the NumPy reference over the rows
    each live token's mask marks of those it sees; a pad token reads
    zeros."""
    rng = np.random.default_rng(13)
    t = PMP * PPS
    pages = PS_ * PMP + 1
    f = lambda *shape: rng.standard_normal(shape).astype(np.float32)  # noqa
    q = jnp.asarray((PDL + PDR) ** -0.5 * f(PS_, PC, PH, PDL + PDR))
    c_pages, r_pages = jnp.asarray(f(pages, PPS, PDL)), \
        jnp.asarray(f(pages, PPS, 2 * PDR))
    tables = jnp.asarray((1 + rng.permutation(pages - 1)).reshape(
        PS_, PMP), jnp.int32)
    starts, n_valid = (jnp.asarray(a, jnp.int32)
                       for a in PREFILL_CASES[case])
    if case == "every_visible_row":
        got = jax.jit(lambda *a: SA.latent_indexed_prefill_attention(
            *a[:3], None, *a[3:], None, None, t, impl=impl))(
                q, c_pages, r_pages, tables, starts, n_valid)
        selected = jnp.ones((PS_, PC, t), jnp.float32)
    else:
        selected = np.asarray(SA.select_prefill(
            jnp.asarray(f(PS_, PC, t)), starts, n_valid, PTOPK)).copy()
        if case == "an_overflowing_page":
            selected[0, 3, 2 * PPS:2 * PPS + 7] = 1.0
            selected[1, 12, 10 * PPS + 3:11 * PPS] = 1.0
        selected = jnp.asarray(selected)
        got = _prefill_through(impl)(q, c_pages, r_pages, tables, starts,
                                     n_valid, selected)
    want = SA._sparse_latent_prefill_reference(
        q, c_pages, r_pages, tables, starts, n_valid, selected)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)
    dead = np.arange(PC)[None, :] >= np.asarray(n_valid)[:, None]
    assert not np.asarray(got)[dead].any()
    assert np.abs(np.asarray(got)[~dead]).min(-1).max() > 0


def test_few_rows_meet_a_blocks_key_pages_in_one_product():
    """One query of 2 heads of 128 a slot: the block's key pages (whole
    lane tiles) are joined side by side into one product; a table of 5
    pages under blocks of 2 is padded with the null page; slots of every
    length, an empty one among them. The ``lax`` form's scores."""
    rng = np.random.default_rng(2)
    s, j, di, ps, mp = 3, 2, 128, 128, 5
    f = lambda *shape: jnp.asarray(rng.standard_normal(shape),  # noqa: E731
                                   jnp.float32)
    q, w, ik = f(s, 1, j, di), f(s, 1, j), f(s * mp + 1, di, ps)
    bt = jnp.asarray((1 + rng.permutation(s * mp)).reshape(s, mp), jnp.int32)
    ext = jnp.asarray([mp * ps, 131, 0], jnp.int32)
    want = kernels.dispatch("lightning_indexer", q, w, ik, bt, ext,
                            impl="lax")
    for pb in (2, 4):
        got = kernels.dispatch("lightning_indexer", q, w, ik, bt, ext,
                               impl="pallas_interpret",
                               block_sizes={"pages_per_block": pb})
        assert got.shape == (s, 1, mp * ps) and not got[2].any()
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-4, rtol=2e-5)


def test_a_wide_chunk_sums_its_heads_a_few_queries_at_a_time():
    """80 queries of 64 heads are 5120 rows, more than one product takes:
    the body sums the heads 8 queries at a time against a block-diagonal
    of their own, over a table that is no multiple of the page block
    (padded with the null page), and gives the ``lax`` form's scores."""
    rng = np.random.default_rng(1)
    s, c, j, di, ps, mp = 2, 80, 64, 8, 8, 3
    assert c * j > SA._ONE_PRODUCT_ROWS
    f = lambda *shape: jnp.asarray(rng.standard_normal(shape),  # noqa: E731
                                   jnp.float32)
    q, w, ik = f(s, c, j, di), f(s, c, j), f(s * mp + 1, di, ps)
    bt = jnp.asarray((1 + rng.permutation(s * mp)).reshape(s, mp), jnp.int32)
    ext = jnp.asarray([mp * ps, 11], jnp.int32)
    want = kernels.dispatch("lightning_indexer", q, w, ik, bt, ext,
                            impl="lax")
    got = kernels.dispatch("lightning_indexer", q, w, ik, bt, ext,
                           impl="pallas_interpret",
                           block_sizes={"pages_per_block": 2})
    assert got.shape == (s, c, mp * ps)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5,
                               rtol=2e-5)


def test_vmem_estimates_at_the_published_widths():
    """A group of 8 queries' (decoding slots, or chunk tokens of a lane),
    states, selections and compacted rows beside two blocks of 8 pages
    fit the 64 MiB both selecting latent kernels ask for (more than the
    two blocks and the states alone), and the static prior is the one
    candidate each contract commits; the indexer's block of 16 key pages
    beside 256 x 64 query rows, summed 8 queries at a time, fits 16 MiB;
    so does the selection's block of 8 rows of 33408."""
    sds = jax.ShapeDtypeStruct
    pools = (sds((2433, 128, 512), jnp.bfloat16),
             sds((2433, 128, 128), jnp.bfloat16))
    prefill = kernels.get("sparse_latent_prefill")
    args = (sds((8, 256, 128, 576), jnp.bfloat16),) + pools + (
        sds((8, 261), jnp.int32), sds((8,), jnp.int32), sds((8,), jnp.int32),
        sds((8, 256, 261 * 128), jnp.float32))
    blocks = autotune.static_prior(prefill, args, {})
    assert blocks == {"q_rows": 64, "pages_per_block": 8, "rows_a_pass": 16}
    est = prefill.vmem_estimate(args, {}, blocks)
    assert 2 * 8 * 128 * 640 * 2 + 2 * 8 * 128 * 768 * 4 < est \
        < SA.DA._WIDE_VMEM_LIMIT
    decode = kernels.get("sparse_latent_decode")
    args = (sds((64, 128, 576), jnp.bfloat16),) + pools + (
        sds((64, 261), jnp.int32), sds((64, 261 * 128), jnp.float32),
        sds((64,), jnp.int32), sds((32, 8), jnp.int32),
        sds((32,), jnp.int32), sds((64,), jnp.int32))
    blocks = autotune.static_prior(decode, args, {})
    assert blocks == {"pages_per_block": 8, "rows_a_pass": 16}
    assert decode.vmem_estimate(args, {}, blocks) == est
    indexer = kernels.get("lightning_indexer").vmem_estimate(
        (sds((8, 256, 64, 128), jnp.bfloat16),
         sds((8, 256, 64), jnp.float32),
         sds((2433, 128, 128), jnp.bfloat16)), {}, {"pages_per_block": 16})
    assert 2 * 256 * 64 * 128 * 2 < indexer < 16 << 20
    selection = kernels.get("topk_selection_mask").vmem_estimate(
        (sds((64, 33408), jnp.float32), sds((64,), jnp.int32)), {},
        {"rows_per_block": 8})
    assert selection < 8 << 20


# -- the benchmark's copy ---------------------------------------------------------

@pytest.fixture(scope="module")
def family():
    sys.path.insert(0, os.path.join(ROOT, "benchmark"))
    from families import deepseek_v32
    return deepseek_v32


@pytest.mark.parametrize("expert_room", [4, 1])
def test_benchmark_reference_is_the_plain_reference(model_and_params,
                                                    family, expert_room):
    """``families/deepseek_v32.py`` computes the same pass in blocks (rows
    16 at a time, the selection packed one bit a key, queries 8 at a time,
    one head at a time, the vocabulary in pieces, the rows asked for
    only, a layer's queries in runs against the keys up to their end, an
    expert over the rows routed to it or, with room for a quarter of the
    average, over a whole block): held to the plain one here, with the
    chip's share of the experts, and its selections for the probe queries
    to the plain one's."""
    model, params = model_and_params
    ids = jnp.asarray(_prompt(48))
    sizes = family.sizes_of(model.cfg)
    probe = jnp.arange(40, 48)
    with jax.default_matmul_precision("highest"):
        want, kept = ref.reference_logits(params, ids, _sizes(model.cfg),
                                          with_selected=True)
        got, sel = family.reference_logits(
            params, ids[None], sizes, lo=jnp.asarray(7), rows=24,
            query_block=8, vocab_block=32, probe=probe, index_block=4,
            row_block=16, expert_room=expert_room)
    want = np.asarray(want)
    np.testing.assert_allclose(np.asarray(got)[0], want[7:31], rtol=0,
                               atol=2e-6 * np.abs(want).max())
    np.testing.assert_array_equal(np.asarray(sel), np.asarray(kept)[:, 40:])
    assert np.asarray(sel).sum(-1).min() == TOPK
    built = family.build(sizes, interpret=True).cfg
    assert dataclasses.replace(built, kernel_impl="lax") == model.cfg
    assert family.vocabulary(sizes) == 96 and family.positions(sizes) == 256


@pytest.mark.parametrize("control", ["selection", "group_limit",
                                     "index_rope", "scale_m2",
                                     "float8_weights"])
def test_the_benchmark_references_controls_move_the_logits(
        control, model_and_params, family):
    model, params = model_and_params
    ids = jnp.asarray(_prompt(40))[None]
    sizes = family.sizes_of(model.cfg)
    with jax.default_matmul_precision("highest"):
        sound = family.reference_logits(params, ids, sizes, query_block=8)
        if control == "float8_weights":
            moved_ = family.reference_logits(
                family.round_weights(params, "float8_e4m3fn"), ids, sizes,
                query_block=8)
        else:
            moved_ = family.reference_logits(params, ids, sizes,
                                             query_block=8,
                                             **{control: False})
    assert float(jnp.abs(sound - moved_).max()) \
        > 1e-3 * float(jnp.abs(sound).max())


def test_kernel_needs_counts_operations_of_the_selected_pairs(family):
    cfg = _published(family)
    needs = family.kernel_needs(cfg["sizes"], 2, 5, {
        'serving_latent_pairs_total{phase="decode"}': 2048.0,
        'serving_latent_pairs_total{phase="prefill"}': 10.0,
        "serving_index_rows_scored_total": 100.0,
        "serving_moe_assignments_total": 3.0,
        "serving_moe_experts_touched_total": 2.0}, 7.0, 0.0)
    assert needs["sparse_latent_decode_needed_flops"] == 2048 * 128 * 2176.0
    assert needs["sparse_latent_prefill_needed_flops"] == 10 * 128 * 2176.0
    assert "sparse_latent_decode_needed_bytes" not in needs
    assert needs["indexer_needed_bytes"] == 100 * 128 * 2
    assert needs["indexer_needed_flops"] == 7 * 5 * (2 * 64 * 128 + 128)
    assert needs["moe_ffn_needed_flops"] == 3 * 6.0 * 7168 * 2048
    assert needs["moe_ffn_needed_bytes"] == 2 * 3 * 7168 * 2048 * 2
    assert family.kernel_needs(cfg["sizes"], 2, 5, {}, 0.0, 0.0)[
        "sparse_latent_decode_needed_flops"] == 0.0


def _published(family):
    import json
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "deepseek_v3_2.json")) as f:
        return json.load(f)


def test_the_configuration_holds_the_catalogs_numbers_but_five(family):
    """Top level (where the driver compares) and ``sizes`` (where the
    runner reads) hold the same; exactly five keys are reduced, none a
    width; the program's config takes the published widths."""
    cfg = _published(family)
    for key, value in cfg["sizes"].items():
        assert cfg[key] == value, key
    assert cfg["reduced"] == ["num_hidden_layers", "first_k_dense_replace",
                              "n_routed_experts", "vocab_size",
                              "num_nextn_predict_layers"]
    assert [cfg[k] for k in cfg["reduced"]] == [5, 1, 16, 16160, 0]
    assert [cfg["published"][k] for k in cfg["reduced"]] \
        == [61, 3, 256, 129280, 1]
    c = family.model_config(cfg["sizes"])
    assert (c.hidden_size, c.num_attention_heads, c.q_lora_rank,
            c.kv_lora_rank, c.qk_nope_head_dim, c.qk_rope_head_dim,
            c.v_head_dim, c.index_n_heads, c.index_head_dim, c.index_topk,
            c.moe_intermediate_size, c.intermediate_size,
            c.num_experts_per_tok, c.n_group, c.topk_group,
            c.num_routed_experts, c.n_routed_experts) == (
        7168, 128, 1536, 512, 128, 64, 128, 64, 128, 2048, 2048, 18432, 8,
        8, 4, 256, 16)
    model = MLAMoELM(c)
    assert abs(model.sigma - 0.135234) < 1e-6
    assert c.llama_4_scaling_beta == 0.0          # a_t = 1
    shapes = jax.eval_shape(lambda k: model.init(k, dtype=jnp.bfloat16),
                            jax.random.PRNGKey(0))
    assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes)) \
        == cfg["bytes"]["parameters"]
    e = cfg["engine"]
    kind = layer_kinds.build(
        model.serving().spec, num_slots=e["num_slots"],
        page_size=e["page_size"], num_pages=e["num_pages"],
        dtype=jnp.bfloat16, share_prefix=True)[0]
    assert kind.row_bytes * e["num_pages"] * 5 == cfg["bytes"]["pool_bytes"]
    assert kind.token_bytes == cfg["bytes"][
        "cache_row_bytes_a_token_and_layer"]
