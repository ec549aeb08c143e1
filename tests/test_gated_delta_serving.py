"""The gated delta-rule / gated attention / routed expert family (Qwen3-
Next's block) through the paged serving engine, against its plain float32
reference (``gated_delta_reference.py``).

Sizes: hidden 64, 4 layers (three state layers and a full one), 2 key and 4
value heads of 16 in a state layer, 4 query heads over 2 KV heads of 16 in
the full one (rotary over the first 4), a router 8 wide taking 3 of which 2
are held, experts of 32, page 4, chunk 8. Weights are seeded float32, so
what separates the engine from the reference is the order of float32 sums
(the chunked form against the token-by-token recurrence, the paged kernels'
page folds, the grouped expert kernel) and nothing else.
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
from paddle_tpu.models.gated_delta_moe_lm import (GatedDeltaMoELM,
                                                  GatedDeltaMoELMConfig)
from paddle_tpu.observability import registry as obs_registry
from paddle_tpu.serving import layer_kinds
from paddle_tpu.serving.program import ServingSpec

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
import gated_delta_reference as ref  # noqa: E402
from serving_taps import (FEATURE_OPTIONS, assert_close,  # noqa: E402
                          assert_refused, moved, reference_rows, serve_alone,
                          serve_into_a_used_slot_and_alone,
                          serve_staggered_watching_state_rows,
                          shared_engines, tapped_engine, traced)
from serving_taps import prompt as _prompt  # noqa: E402

#: float32 on both sides, sums in another order: 2e-5 OF THE LARGEST LOGIT
#: (0.5-0.7 here); sound runs read 1e-6 of it, each control below 1e-2
LOGIT_RTOL = 2e-5
_assert_close = functools.partial(assert_close, rtol=LOGIT_RTOL)

PAGE, CHUNK = 4, 8
#: log decay -A softplus(a + dt_bias) of about -0.002..-0.14 a token: a
#: head remembers 7 to 500 tokens, so the state carries the layer's output
TIME_SCALES = dict(a_init_range=(0.02, 0.2), dt_init_range=(0.1, 0.7))


def _model(impl="lax", **kw):
    return GatedDeltaMoELM(GatedDeltaMoELMConfig.tiny(
        kernel_impl=impl, **{**TIME_SCALES, **kw}))


@pytest.fixture(scope="module")
def model_and_params():
    model = _model()
    return model, model.init(jax.random.PRNGKey(5))


def _engine(params, impl="lax", slots=2, **kw):
    return tapped_engine(_model(impl), params, num_slots=slots,
                         page_size=PAGE, prefill_chunk=CHUNK, attn_impl=impl,
                         **kw)


@pytest.fixture(scope="module")
def engines(model_and_params):
    return shared_engines(lambda impl: _engine(
        model_and_params[1], impl,
        **(dict(slots=4, prefill_budget=3 * CHUNK) if impl == "lax" else {})))


_rows = reference_rows(ref.reference_logits)


def _reference_rows(model, params, prompt, out, **controls):
    return _rows(params, prompt, out, model.cfg, **controls)


CASES = {
    # 21 = 2 chunks and 5 tokens: the prompt ends inside a chunk and
    # inside a page; 7 new tokens are 3 decode blocks and cross a page
    "ends_inside_a_chunk": (21, 7),
    "ends_on_a_chunk_edge": (16, 7),
    "ends_on_a_page_edge": (12, 9),
}


@pytest.mark.parametrize("case, impl", [
    ("ends_inside_a_chunk", "lax"),
    ("ends_inside_a_chunk", "pallas_interpret"),
    ("ends_on_a_chunk_edge", "pallas_interpret"),
    ("ends_on_a_page_edge", "lax")])
def test_prefill_then_decode_logits_match_the_reference(
        case, impl, model_and_params, engines):
    model, params = model_and_params
    n0, n_new = CASES[case]
    prompt = _prompt(n0)
    eng, sink, _ = engines(impl)
    out, got = serve_alone(eng, sink, prompt, n_new)
    want = _reference_rows(model, params, prompt, out)
    _assert_close(got, want)
    assert (want.argmax(-1) == out).all()


def test_whole_sequence_pass_is_the_reference(model_and_params):
    model, params = model_and_params
    ids = _prompt(70)           # past one tile of the scan: padded to two
    # (the jitted reference of the other cases: rows 0 .. 68)
    want = _reference_rows(model, params, ids[:1], ids[1:])
    got = jax.jit(model.forward)(params, jnp.asarray(ids)[None])
    _assert_close(np.asarray(got)[0, :-1], want)


@pytest.mark.parametrize("control", [dict(decay=False), dict(beta_one=True),
                                     dict(gate=False), dict(shared=False)],
                         ids=lambda c: next(iter(c)))
def test_a_reference_without_one_mechanism_fails_the_same_comparison(
        control, model_and_params, engines):
    """The decay left out, ``beta`` at 1, the full layer's output gate
    left out, no shared expert: each alone moves the logits past the
    bound the engine is held to."""
    model, params = model_and_params
    prompt = _prompt(21)
    eng, sink, _ = engines("lax")
    out, got = serve_alone(eng, sink, prompt, 7)
    with pytest.raises(AssertionError):
        _assert_close(got, _reference_rows(model, params, prompt, out,
                                           **control))


# -- what a state layer is to the cache and the steps ---------------------------

def test_a_state_layer_has_no_page_pool_and_a_full_layer_no_state(engines):
    eng, _, reg = engines("lax")
    cache = eng.cache
    kinds = cache.config.kinds
    assert [type(k).__name__ for k in kinds] == ["State"] * 3 + ["Paged"]
    state, full = kinds[0], kinds[3]
    assert state is kinds[1] is kinds[2] and state.layers == 3
    assert state.pools == () and state.page_bytes == 0 and not state.paged
    assert full.layers == 1 and full.paged and not full.state
    # no page of a state layer to copy on write, and the step programs take
    # a slot's whole table because the PROGRAM has state layers
    assert state.copy_page((), 1, 2) == () and not state.whole_table
    assert eng._whole_table and {
        sig[1] for sig in eng.warmup_plan() if sig[0] in ("decode", "prefill")
    } == {cache.config.max_pages_per_slot}
    slots = eng.scheduler.num_slots
    for ent in cache.pages[:3]:     # the conv window and the heads' states
        assert [a.shape for a in ent] == [(slots + 1, 3 * 128),
                                          (slots + 1, 4, 16, 16)]
    assert [a.shape[1:] for a in cache.pages[3]] == [(PAGE, 32), (PAGE, 32)]
    # a page id commits the ONE full layer's K and V; the state is the
    # three state layers'
    assert cache.bytes_per_page() == 2 * PAGE * 32 * 4
    assert cache.state_layers() == 3
    assert cache.state_bytes_per_slot() == 3 * 4 * (3 * 128 + 4 * 16 * 16)
    snap = reg.snapshot()
    assert snap["serving_ssm_state_pool_bytes"] \
        == cache.state_bytes_per_slot() * (slots + 1)
    assert snap['serving_kv_pool_bytes{layers="full"}'] \
        == 2 * cache.config.num_pages * PAGE * 32 * 4


def test_a_step_dispatches_no_attention_kernel_for_a_state_layer(
        model_and_params):
    """Tracing the decode block and a prefill call of a 4-layer program:
    ONE dispatch of each paged kernel (the full layer's), three of each
    delta kernel, and every page a request reserves is the full layer's."""
    _, params = model_and_params
    eng, _sink, _reg = _engine(params)
    c = obs_registry.counter("kernel_dispatch_total")
    names = ("ragged_paged_decode", "ragged_paged_prefill",
             "gated_delta_decode_update", "gated_delta_chunk_scan")
    before = {n: c.value(kernel=n, impl="lax") for n in names}
    eng.generate_many([_prompt(11)], max_new_tokens=3)
    ran = {n: c.value(kernel=n, impl="lax") - before[n] for n in names}
    # (each step program is traced once a signature: one prefill bucket,
    # one decode bucket here)
    assert ran["ragged_paged_decode"] == ran["ragged_paged_prefill"] > 0
    assert ran["gated_delta_decode_update"] \
        == 3 * ran["ragged_paged_decode"]
    assert ran["gated_delta_chunk_scan"] == 3 * ran["ragged_paged_prefill"]
    rid = eng.submit(_prompt(9), 4)
    eng.step()
    slot = eng.scheduler.active_slots()[0]
    assert len(eng.cache.slot_pages(slot)) == eng.cache.config.pages_for(13)
    assert eng.cache.live_bytes() == eng.cache.bytes_per_page() * 4
    while not eng.scheduler.idle():
        eng.step()
    assert len(eng.result(rid)) == 4


# -- continuous batching --------------------------------------------------------

def test_a_reused_slot_gives_what_the_request_gives_alone(model_and_params,
                                                          engines):
    """Two requests one after the other in slot 0: the second starts from
    zeros, not from what the first left in the slot's rows."""
    model, params = model_and_params
    second = _prompt(13, seed=77)
    out, got = serve_into_a_used_slot_and_alone(*engines("lax"), _prompt(19),
                                                second)
    _assert_close(got, _reference_rows(model, params, second, out))


def test_a_step_touches_only_the_rows_of_its_own_lanes(model_and_params,
                                                        engines):
    """Four slots under staggered traffic: every step leaves the state
    rows of slots outside its lanes bit for bit, and each request still
    reads the reference's argmax."""
    model, params = model_and_params
    eng, _sink, _ = engines("lax")
    prompts = [_prompt(n, seed=n) for n in (9, 30, 21, 27, 14)]
    rids = serve_staggered_watching_state_rows(eng, prompts)
    for rid, prompt in zip(rids, prompts):
        out = eng.result(rid)
        want = _reference_rows(model, params, prompt, out)
        assert (want.argmax(-1) == out).all()


# -- refusals -------------------------------------------------------------------

@pytest.mark.parametrize("feature", sorted(FEATURE_OPTIONS))
def test_engine_refuses_an_option_by_class_and_feature(feature,
                                                       model_and_params):
    assert_refused(*model_and_params, feature,
                   rf"GatedDeltaMoELM does not serve with '{feature}' yet")


_SPEC = dict(num_layers=2, num_heads=4, kv_heads=2, head_dim=16,
             vocab_size=96, max_position=64,
             slot_state=(("s", (4, 16, 16)),), state_layers=(True, False))


@pytest.mark.parametrize("extra, said", [
    (dict(layer_windows=(None, 8)), "layer_windows"),
    (dict(select_topk=8, extra_rows=(("idx", 8),)), "select_topk"),
    (dict(layer_kv_heads=(2, 4)), "layer_kv_heads"),
    (dict(sink_layers=(False, True)), "sink_layers"),
    (dict(layer_carry=(("c", 8),)), "layer_carry"),
    (dict(slot_state=()), "slot_state"),
    (dict(slot_state_reader="attn_in"), "read and advanced by 'mixer'"),
    (dict(state_layers=(True,)), "one bool a layer"),
    (dict(kv_heads=1, head_dim=24, latent_row=(16, 8)), "state_layers")],
    ids=lambda v: v if isinstance(v, str) else "")
def test_spec_refuses_what_state_layers_do_not_combine_with(extra, said):
    with pytest.raises(ValueError, match=said):
        ServingSpec(**{**_SPEC, **extra})


def test_build_refuses_quantized_sharded_and_shared_pools_by_name():
    spec = ServingSpec(**_SPEC)
    geo = dict(num_slots=2, page_size=4, num_pages=9)
    for kw, said in ((dict(dtype=jnp.int8, share_prefix=False),
                      "int8 pool carries no slot state"),
                     (dict(dtype=jnp.float32, share_prefix=True),
                      "cannot share prefixes"),
                     (dict(dtype=jnp.float32, share_prefix=False, tp=2),
                      "tp-sharded pool carries no")):
        with pytest.raises(ValueError, match=said):
            layer_kinds.build(spec, **geo, **kw)
    kinds = layer_kinds.build(spec, dtype=jnp.float32, share_prefix=False,
                              **geo)
    assert [type(k).__name__ for k in kinds] == ["State", "Paged"]
    assert kinds[1].label == "full" and not kinds[1].state


def test_an_all_false_state_layers_is_a_program_without(model_and_params):
    spec = ServingSpec(**{**_SPEC, "state_layers": (False, False)})
    assert spec.state_layers == ()
    kinds = layer_kinds.build(spec, num_slots=2, page_size=4, num_pages=9,
                              dtype=jnp.float32, share_prefix=False)
    assert kinds[0] is kinds[1] and kinds[0].state and kinds[0].paged


# -- the chip's share tied to the model -------------------------------------------

def test_eight_shares_and_one_shared_expert_add_up_to_the_uncut_layer():
    """A layer's 16 routed experts as 8 chips' shares of 2: each share's
    ``ffn`` adds its held experts' terms and the WHOLE shared expert; the
    eight partial results, the shared expert counted once, are the uncut
    reference layer (every expert dense, one softmax, one top-3)."""
    whole = _model(num_experts=16, num_routed_experts=16,
                   num_hidden_layers=1, full_attention_interval=1)
    params = whole.init(jax.random.PRNGKey(9))
    mp = params["layers"]["0"]["mlp"]
    x = jnp.asarray(np.random.default_rng(1).standard_normal(
        (2, 6, 64)), jnp.float32)
    valid = jnp.ones((2, 6), bool)
    with jax.default_matmul_precision("highest"):
        t = ref._rms1(x.reshape(12, 64), params["layers"]["0"][
            "post_attention_layernorm"]["weight"], whole.cfg.rms_norm_eps)
        want = ref.moe_layer(mp, t, whole.cfg)
        shared = want - ref.moe_layer(mp, t, whole.cfg, shared=False)
        total = jnp.zeros_like(want)
        for j in range(8):
            share = _model(num_experts=2, num_routed_experts=16,
                           expert_offset=2 * j, num_hidden_layers=1,
                           full_attention_interval=1)
            held = jax.tree.map(lambda a: a[2 * j:2 * j + 2], mp["experts"])
            p_j = {"layers": {"0": {**params["layers"]["0"],
                                    "mlp": {**mp, "experts": held}}}}
            y, stats = share.ffn(p_j, 0, x, valid)
            total = total + (y - x).reshape(12, 64)
            assert int(stats["moe_routed_pairs"]) == 12 * 3
        got = total - 7 * shared
    # each share's part is read off the stream it was added to (``y -
    # x`` with ``x`` of magnitude 4 and the part 1e-3: float32 keeps it to
    # 2^-22 |x|), eight times: the bound; a share left out or a shared
    # expert counted twice is off by 4e-4 or more
    assert float(jnp.abs(shared).max()) > 4e-4
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want),
        atol=8 * 2.0 ** -22 * float(jnp.abs(x).max()))


# -- counters and spans -----------------------------------------------------------

def test_counters_are_what_the_traffic_implies(engines):
    """A prompt of 21 tokens (3 chunks), 9 new tokens at 2 a block (the
    first from prefill, then 4 blocks), 3 state layers and a full one, one
    slot live of 4."""
    eng, _sink, reg = engines("lax")
    before = reg.snapshot()
    with traced(eng) as tracer:
        eng.generate_many([_prompt(21)], max_new_tokens=9)
    snap = moved(reg, before)
    layers, blocks, block = 3, 4, 2
    slot_bytes = eng.cache.state_bytes_per_slot()
    # the engine's slot-state series count the state layers only: a
    # decode token step reads and writes the slot's state, a prefill call
    # writes it and reads it unless the prompt starts there
    assert snap["serving_ssm_prefill_tokens_total"] == 21 * layers
    assert snap["serving_ssm_decode_slot_steps_total"] \
        == blocks * block * layers
    assert snap["serving_ssm_state_resets_total"] == 1
    assert snap['serving_ssm_state_bytes_total{kind="written"}'] \
        == slot_bytes * (blocks * block + 3)
    assert snap['serving_ssm_state_bytes_total{kind="read"}'] \
        == slot_bytes * (blocks * block + 2)
    # the full layer's K and V alone: token step j of a slot holding L
    # tokens attends over L + j + 1
    attended = sum(21 + 2 * b + j + 1 for b in range(blocks)
                   for j in range(block))
    assert snap['serving_decode_kv_bytes_total{kind="live"}'] \
        == attended * 2 * 32 * 4
    spans = tracer.spans()
    for name in ("serving.decode_round", "serving.prefill_call"):
        mine = [s for s in spans if s.name == name
                and s.attrs.get("slots_live", 1)]
        assert mine and all(s.attrs["state_layers"] == 3
                            and s.attrs["page_layers"] == 1 for s in mine)


# -- the benchmark's copy ---------------------------------------------------------

@pytest.fixture(scope="module")
def family():
    sys.path.insert(0, os.path.join(ROOT, "benchmark"))
    from families import qwen3_next
    return qwen3_next


def test_benchmark_reference_is_the_plain_reference(model_and_params,
                                                    family):
    """``families/qwen3_next.py`` computes the same pass in blocks (a
    block of queries at a time, one expert at a time, the vocabulary in
    pieces, the rows asked for only): held to the plain one here; its one
    control the plain reference lacks, a state lost at a position, moves
    the rows from that position on and no row before it."""
    model, params = model_and_params
    sizes = family.sizes_of(model.cfg)
    ids = _prompt(40)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref.reference_logits(params, jnp.asarray(ids),
                                               model.cfg))
        got, selections = family.reference_logits(
            params, jnp.asarray(ids)[None], sizes, lo=7, rows=24,
            probe=jnp.zeros((8,), jnp.int32))
        lost = family.reference_logits(
            params, jnp.asarray(ids)[None], sizes, lo=7, rows=24,
            lose_state_at=jnp.asarray(20))
    assert selections.size == 0
    _assert_close(np.asarray(got)[0], want[7:31])
    # a state lost before token 20 leaves the rows before it alone and
    # moves those from it on
    lost = np.asarray(lost)[0]
    _assert_close(lost[:13], want[7:20])
    with pytest.raises(AssertionError):
        _assert_close(lost[13:], want[20:31])
    built = family.build(sizes, interpret=True)
    assert built.cfg.kernel_impl == "pallas_interpret"
    assert built.cfg.a_init_range == family.A_INIT_RANGE
    assert built.serving().spec.state_layers == (True, True, True, False)
    assert set(family.KERNELS) == {
        "ragged_paged_prefill", "ragged_paged_decode", "moe_grouped_ffn",
        "gated_delta_chunk_scan", "gated_delta_decode_update"}
    assert all(kernels.get(k) for k in family.KERNELS)


def test_benchmark_configuration_holds_the_published_keys_twice(family):
    """``benchmark/configs/qwen3_next_80b_a3b.json``: the catalog's
    numbers at its top level and under ``sizes``, the same, but for the
    three reduced keys; the program config's defaults are the published
    numbers; the cell's engine is 256 slots whose state pools and pages
    are sized as the file says."""
    import json
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "qwen3_next_80b_a3b.json")) as f:
        cfg = json.load(f)
    default = GatedDeltaMoELMConfig()
    reduced = {"num_hidden_layers": 8, "num_experts": 64,
               "vocab_size": 18992}
    assert cfg["reduced"] == list(reduced)
    assert cfg["published"] == {"num_hidden_layers": 48, "num_experts": 512,
                                "vocab_size": 151936}
    for key, value in cfg["sizes"].items():
        assert cfg[key] == value, key
        if key in reduced:
            assert value == reduced[key]
            assert getattr(default, key) == cfg["published"][key]
        elif hasattr(default, key):
            assert getattr(default, key) == value, key
    built = family.model_config(cfg["sizes"])
    assert (built.num_experts, built.num_routed_experts,
            built.expert_offset) == (64, 512, 0)
    assert built.state_layers == (True, True, True, False) * 2
    # a slot's state: 6 layers x (3 x 8192 + 32 x 128 x 128) float32
    model = GatedDeltaMoELM(built)
    per_layer = 4 * sum(int(np.prod(shape))
                        for _n, shape in model.slot_state())
    assert per_layer == 4 * (3 * 8192 + 32 * 128 * 128) == 2195456
    assert dataclasses.asdict(built)["linear_num_value_heads"] == 32
    assert cfg["engine"]["num_slots"] == 256
    assert cfg["engine"]["max_tokens_per_slot"] == 4096 + 1024
