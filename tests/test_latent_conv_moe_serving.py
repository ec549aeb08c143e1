"""The latent-conv attention + carried-router expert model through the
paged serving engine, against its plain float32 reference.

Sizes: hidden 64, 4 query heads over 2 KV heads of 16 (80 q channels + 32
k channels in the latent), 8 experts of 32 with one a token, router hidden
16, page 4, chunk 8, 2 layers. Weights are seeded float32 as ``init``
draws them, so what separates the engine from the reference is the order
of float32 sums (the paged kernels' page folds, the grouped expert
kernel's tiles) and nothing else.
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
from paddle_tpu.models import LatentConvMoELM, LatentConvMoELMConfig
from paddle_tpu.models.common import rope
from paddle_tpu.ops import grouped_ffn

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
import latent_conv_moe_reference as ref  # noqa: E402
from serving_taps import (assert_close, assert_refused,  # noqa: E402
                          benchmark_config, FEATURE_OPTIONS,
                          moved, reference_rows, serve_alone,
                          serve_into_a_used_slot_and_alone,
                          serve_staggered_watching_state_rows,
                          shared_engines, tapped_engine, traced)
from serving_taps import prompt as _prompt  # noqa: E402

#: float32 on both sides, sums in another order: 2e-5 OF THE LARGEST
#: LOGIT (the logits are of magnitude 1). Sound runs read under 2e-6 of
#: it; each of the three controls below reads over 1e-2
LOGIT_RTOL = 2e-5
_assert_close = functools.partial(assert_close, rtol=LOGIT_RTOL)

PAGE, CHUNK = 4, 8


@pytest.fixture(scope="module")
def model_and_params():
    model = LatentConvMoELM(LatentConvMoELMConfig.tiny(kernel_impl="lax"))
    return model, model.init(jax.random.PRNGKey(5))


def _engine(params, impl="lax", slots=2, **kw):
    # (``control=``: ``attn_in`` / ``ffn`` may be replaced)
    return tapped_engine(
        LatentConvMoELM(LatentConvMoELMConfig.tiny(kernel_impl=impl)), params,
        num_slots=slots, page_size=PAGE, prefill_chunk=CHUNK, attn_impl=impl,
        **kw)


@pytest.fixture(scope="module")
def engines(model_and_params):
    """``get(impl) -> (engine, its head calls' logits, registry)``, one
    engine an ``impl`` for the module (``tests/serving_taps.py``). The
    ``lax`` one has the four slots and the budget (a chunk a step beside
    the decoding) of the staggered case; the others take it as it is."""
    return shared_engines(lambda impl: _engine(
        model_and_params[1], impl,
        **(dict(slots=4, prefill_budget=3 * CHUNK) if impl == "lax" else {})))


_rows = reference_rows(ref.reference_logits)


def _reference_rows(model, params, prompt, out, **kw):
    return _rows(params, prompt, out, model.cfg, **kw)


CASES = {
    # the first token's tails are all zeros; its one prefill call is fresh
    "one_token": (1, 5),
    # the second token reads the first's tails inside one call
    "two_tokens": (2, 5),
    # a chunk less one, a chunk, a chunk and one: the second call's first
    # token reads the row the first call left
    "chunk_less_one": (CHUNK - 1, 5),
    "ends_on_a_chunk_edge": (2 * CHUNK, 7),
    "chunk_and_one": (CHUNK + 1, 5),
    # 21 = 2 chunks and 5 tokens: the prompt ends inside a chunk and
    # inside a page; 7 new tokens are 3 decode blocks and cross a page
    "ends_inside_a_chunk": (21, 7),
}


@pytest.mark.parametrize("case, impl", [
    ("one_token", "lax"), ("two_tokens", "lax"), ("chunk_less_one", "lax"),
    ("ends_on_a_chunk_edge", "pallas_interpret"), ("chunk_and_one", "lax"),
    ("ends_inside_a_chunk", "lax"),
    ("ends_inside_a_chunk", "pallas_interpret")])
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
    ids = _prompt(40)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref.reference_logits(params, jnp.asarray(ids),
                                               model.cfg))
    _assert_close(np.asarray(model.forward(params, jnp.asarray(ids)[None]))[0],
                  want)


# -- three controls: each mechanism left out fails the same comparison ----------

def _tails_zeroed(program):
    """Every call starts from zero tails, as if nothing were kept."""
    def attn_in(params, i, x, positions, state, rows, fresh, valid):
        return program.attn_in(params, i, x, positions, state, rows,
                               jnp.ones_like(fresh), valid)
    return {"attn_in": attn_in}


def _carry_dropped(program):
    """Every layer's router sees zeros for the layer before."""
    def ffn(params, i, x, valid, carry):
        return program.ffn(params, i, x, valid,
                           tuple(jnp.zeros_like(a) for a in carry))
    return {"ffn": ffn}


@pytest.mark.parametrize("control", ["tails_zeroed", "carry_dropped",
                                     "value_shift_reads_the_token_itself"])
def test_a_mechanism_left_out_fails_the_same_comparison(control,
                                                        model_and_params,
                                                        engines):
    model, params = model_and_params
    prompt = _prompt(21)
    wrap = {"tails_zeroed": _tails_zeroed,
            "carry_dropped": _carry_dropped}.get(control)
    # a program with a hook replaced is another program: its own engine
    eng, sink, _ = _engine(params, control=wrap) if wrap else engines("lax")
    out, got = serve_alone(eng, sink, prompt, 7)
    # the third control is on the reference's side: its V heads all read
    # the token itself, the engine's are the program's own
    want = _reference_rows(model, params, prompt, out,
                           value_shift=wrap is not None)
    with pytest.raises(AssertionError):
        _assert_close(got, want)


# -- continuous batching --------------------------------------------------------

def test_a_reused_slot_gives_what_the_request_gives_alone(model_and_params,
                                                          engines):
    """Two requests one after the other in slot 0: the second starts from
    zero tails, not from what the first left in the slot's rows."""
    model, params = model_and_params
    second = _prompt(13, seed=77)
    out, got = serve_into_a_used_slot_and_alone(*engines("lax"), _prompt(19),
                                                second)
    _assert_close(got, _reference_rows(model, params, second, out))


def test_a_step_touches_only_the_rows_of_its_own_lanes(model_and_params,
                                                        engines):
    """Four slots under staggered traffic: every step leaves the tails of
    slots outside its lanes bit for bit (``serving_taps.
    serve_staggered_watching_state_rows``), and each request still reads
    the reference's logits' argmax."""
    model, params = model_and_params
    eng, _sink, _ = engines("lax")
    prompts = [_prompt(n, seed=n) for n in (9, 30, 21, 27, 14)]
    rids = serve_staggered_watching_state_rows(eng, prompts)
    for rid, prompt in zip(rids, prompts):
        out = eng.result(rid)
        want = _reference_rows(model, params, prompt, out)
        assert (want.argmax(-1) == out).all()


# -- refusals, declarations, counters ---------------------------------------------

@pytest.mark.parametrize("feature", sorted(FEATURE_OPTIONS))
def test_engine_refuses_an_option_by_class_and_feature(feature,
                                                       model_and_params):
    """The engine's one refusal sentence, for each of the eight options
    and calls that would read K and V and lose the tails."""
    assert_refused(*model_and_params, feature,
                   rf"LatentConvMoELM does not serve with '{feature}' yet")


def test_the_program_declares_what_the_loops_act_on(model_and_params,
                                                    engines):
    model, _ = model_and_params
    eng, _, _ = engines("lax")
    spec = eng.program.spec
    assert spec.slot_state_reader == "attn_in"
    assert not hasattr(model.serving(), "mixer")
    assert spec.slot_state == (("z_tail", (96,)), ("conv_tail", (96,)),
                               ("value_tail", (16,)))
    assert spec.layer_carry == (("router_state", 16),)
    assert spec.supports == frozenset()
    assert eng.cache.config.share_prefix is False
    # K, V, then the three tails, one row a slot and the null row
    assert [a.shape for a in eng.cache.pages[0][2:]] == [
        (5, 96), (5, 96), (5, 16)]
    assert all(a.dtype == jnp.float32 for a in eng.cache.pages[0][2:])
    with pytest.raises(ValueError, match="slot_state_reader='ffn'"):
        dataclasses.replace(spec, slot_state_reader="ffn")


def test_counters_are_what_the_traffic_implies(engines):
    """A prompt of 21 tokens (3 chunks), 9 new tokens at 2 a block (the
    first from prefill, then 4 blocks), 2 layers, one slot live of 4."""
    eng, _sink, reg = engines("lax")
    before = reg.snapshot()
    with traced(eng) as tracer:
        eng.generate_many([_prompt(21)], max_new_tokens=9)
    snap = moved(reg, before)
    layers, blocks, block = 2, 4, 2
    slot_bytes = eng.cache.state_bytes_per_slot()
    assert slot_bytes == layers * 4 * (96 + 96 + 16)
    assert snap["serving_ssm_prefill_tokens_total"] == 21 * layers
    assert snap["serving_ssm_decode_slot_steps_total"] \
        == blocks * block * layers
    assert snap["serving_ssm_state_resets_total"] == 1
    assert snap['serving_ssm_state_bytes_total{kind="written"}'] \
        == slot_bytes * (blocks * block + 3)
    assert snap['serving_ssm_state_bytes_total{kind="read"}'] \
        == slot_bytes * (blocks * block + 3 - 1)
    assert reg.snapshot()["serving_ssm_state_pool_bytes"] == slot_bytes * 5
    # one expert a token, no token dropped: 21 prompt tokens and the 8
    # tokens the blocks entered, a layer
    pairs = (21 + blocks * block) * layers
    assert snap["serving_moe_assignments_total"] == pairs
    # a tile is 16 rows and holds one expert's tokens: at least one tile
    # an expert touched, and room for every pair
    rows = snap["serving_moe_tile_rows_total"]
    assert rows == 16 * snap["serving_moe_experts_touched_total"] >= pairs
    spans = tracer.spans()
    for name in ("serving.decode_round", "serving.prefill_call"):
        mine = [s for s in spans if s.name == name
                and s.attrs.get("slots_live", 1)]
        assert mine and all(s.attrs["state_slots"] == 1 for s in mine)
        assert all(s.attrs["experts_touched"] >= 1 for s in mine)


# -- the pieces the program takes from elsewhere ----------------------------------

@pytest.mark.parametrize("impl", ["lax", "pallas_interpret"])
def test_grouped_expert_ffn_with_one_expert_a_token(impl):
    """``(T, 1)`` ids: the tiles, then the weighted sum over one pair a
    token, against the float64 tile reference and a direct sum."""
    t, e, d, f = 37, 8, 32, 48
    rng = np.random.default_rng(3)
    x = rng.standard_normal((t, d)).astype(np.float32)
    ids = rng.integers(0, e, (t, 1)).astype(np.int32)
    coef = rng.uniform(0.1, 1.0, (t, 1)).astype(np.float32)
    valid = rng.uniform(size=t) < 0.85
    w = [(rng.standard_normal((e, f, d)) * d ** -0.5).astype(np.float32)
         for _ in range(3)]
    y, sizes = grouped_ffn.grouped_expert_ffn(
        *(jnp.asarray(a) for a in (x, ids, coef, valid, *w)), impl=impl)
    assert int(sizes.sum()) == int(valid.sum())
    x64 = x.astype(np.float64)
    want = np.zeros((t, d))
    for row in np.nonzero(valid)[0]:
        g, u, dn = (m[ids[row, 0]].astype(np.float64) for m in w)
        gate = g @ x64[row]
        want[row] = coef[row, 0] * ((gate / (1 + np.exp(-gate))
                                     * (u @ x64[row])) @ dn)
    np.testing.assert_allclose(np.asarray(y), want, atol=2e-5, rtol=2e-5)
    tm = grouped_ffn.tile_rows(t, e)
    src, _dest, tile_expert, n_used, _ = grouped_ffn.route_tiles(
        jnp.asarray(ids), jnp.asarray(valid), e, tm)
    x_pad = jnp.where((src >= 0)[:, None], jnp.asarray(x)[jnp.maximum(
        src, 0)], 0.0)
    args = (x_pad, tile_expert, n_used, *(jnp.asarray(m) for m in w))
    np.testing.assert_allclose(
        np.asarray(kernels.dispatch("moe_grouped_ffn", *args, impl=impl)),
        np.asarray(grouped_ffn._grouped_reference(*args)), atol=2e-5,
        rtol=2e-5)


def test_partial_rope_rotates_the_first_half_and_passes_the_rest():
    """Against the rotation written out pair by pair, theta 5e6."""
    s, c, heads, d, rotary, theta = 2, 5, 3, 16, 8, 5e6
    rng = np.random.default_rng(4)
    x = rng.standard_normal((s, c, heads, d)).astype(np.float32)
    pos = rng.integers(0, 3000, (s, c)).astype(np.int32)
    got = np.asarray(rope(jnp.asarray(x), jnp.asarray(pos), theta, rotary))
    want = x.astype(np.float64)
    half = rotary // 2
    for i in range(half):
        ang = pos.astype(np.float64) * theta ** (-2.0 * i / rotary)
        a, b = x[..., i].astype(np.float64), x[..., i + half].astype(
            np.float64)
        want[..., i] = a * np.cos(ang)[..., None] - b * np.sin(ang)[..., None]
        want[..., i + half] = b * np.cos(ang)[..., None] \
            + a * np.sin(ang)[..., None]
    np.testing.assert_allclose(got, want, atol=2e-4)
    assert (got[..., rotary:] == x[..., rotary:]).all()
    whole = np.asarray(rope(jnp.asarray(x), jnp.asarray(pos), theta))
    assert (whole == np.asarray(rope(jnp.asarray(x), jnp.asarray(pos), theta,
                                     d))).all()


# -- the benchmark's copy ---------------------------------------------------------

@pytest.fixture(scope="module")
def family():
    sys.path.insert(0, os.path.join(ROOT, "benchmark"))
    from families import zaya
    return zaya


def test_benchmark_reference_is_the_plain_reference(model_and_params,
                                                    family):
    """``families/zaya.py`` computes the same pass in blocks (queries a
    block at a time, one expert upcast at a time, the vocabulary in
    pieces, the rows asked for only): held to the plain one here."""
    model, params = model_and_params
    sizes = family.sizes_of(model.cfg)
    ids = _prompt(40)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref.reference_logits(params, jnp.asarray(ids),
                                               model.cfg))
        got, selections = family.reference_logits(
            params, jnp.asarray(ids)[None], sizes, lo=7, rows=24,
            query_block=8, vocab_block=32,
            probe=jnp.zeros((8,), jnp.int32), follow=False)
    assert selections.size == 0
    _assert_close(np.asarray(got)[0], want[7:31])
    built = family.build(sizes, interpret=True)
    assert built.cfg.kernel_impl == "pallas_interpret"
    assert built.serving().spec.slot_state == model.slot_state()


def _loud_experts(params, scale=100.0):
    """``params`` with every expert's output ``scale`` times as large: at
    this file's sizes a token's own embedding decides its logits and one
    switched expert moves no chosen token; at the published widths the
    experts' outputs ARE the stream."""
    layers = {i: dict(lp, experts=dict(
        lp["experts"], down=lp["experts"]["down"] * scale))
        for i, lp in params["layers"].items()}
    return dict(params, layers=layers)


def _greedy(family, params, sizes, ids, forced=None):
    """The token chosen after every row by the reference's pass under
    ``forced``, and that pass's routing."""
    x, route = family.reference_hidden(params, ids, sizes, 8, forced)
    x, k, _width, piece = family._head_pieces(params, x, sizes, 32)
    logits = jnp.concatenate([x @ piece(i).T for i in range(k)], -1)
    return np.asarray(jnp.argmax(logits, -1)).astype(np.int32), route


@pytest.mark.parametrize("tie_holds", [True, False])
def test_benchmark_reference_follows_the_program_at_a_tie(model_and_params,
                                                          family, tie_holds):
    """One expert a token is a step: a program that takes the runner-up
    where two experts tie is as right as the reference. The "program"
    here is the float32 reference but for one routing event, where it
    takes the runner-up; ``follow_routing`` reads that off its tokens:
    with the event inside ``tie`` it takes the switch and every row is
    explained; with ``tie`` under the event's gap it may not, and the
    rows the switch moved stay short."""
    model, params = model_and_params
    params = _loud_experts(params)
    sizes = family.sizes_of(model.cfg)
    ids = jnp.asarray(_prompt(40))
    lo, rows = 4, 32
    with jax.default_matmul_precision("highest"):
        plain_tokens, route = _greedy(family, params, sizes, ids)
        none = jnp.full(route["pick"].shape, -1, jnp.int32)
        for event in ((layer, row) for row in range(8, 32)
                      for layer in range(model.cfg.num_hidden_layers)):
            tokens, _ = _greedy(family, params, sizes, ids, none.at[
                event].set(route["second"][event]))
            if (tokens != plain_tokens).any():
                break
        else:
            raise AssertionError("no switch moves a chosen token")
        width = float(route["gap"][event])
        chosen = jnp.asarray(tokens[lo:lo + rows])
        forced, counts = family.follow_routing(
            params, ids, sizes, lo, rows, query_block=8, vocab_block=32,
            tie=width * (2.0 if tie_holds else 0.5), explained=1e-4,
            rounds=3, chosen=chosen)
        x, _ = family.reference_hidden(params, ids, sizes, 8, forced)
        followed = np.asarray(family._shortfall(
            params, x[lo:lo + rows], chosen, sizes, 32))
    forced = np.asarray(forced)
    if tie_holds:
        assert forced[event] == int(route["second"][event])
        assert followed.max() <= 1e-4 and counts[2] >= 1
    else:
        assert forced[event] < 0
        assert followed.max() > 1e-2


def test_benchmark_reference_keeps_no_switch_that_explains_nothing(
        model_and_params, family):
    """Tokens that are NOT the program's (every row's LEAST likely one):
    every row is short, every tie is tried, and no switch is kept, since
    none brings a row within ``explained`` of the reference's best: a
    program whose stream is off by more than a tie is not excused."""
    model, params = model_and_params
    params = _loud_experts(params)
    sizes = family.sizes_of(model.cfg)
    ids = jnp.asarray(_prompt(40))
    with jax.default_matmul_precision("highest"):
        x, _ = family.reference_hidden(params, ids, sizes, 8)
        x, k, _width, piece = family._head_pieces(params, x, sizes, 32)
        chosen = jnp.argmin(jnp.concatenate(
            [x @ piece(i).T for i in range(k)], -1), -1)[4:36]
        forced, counts = family.follow_routing(
            params, ids, sizes, 4, 32, query_block=8, vocab_block=32,
            tie=0.5, explained=1e-4, rounds=3, chosen=chosen)
    assert counts[1] > 0 and counts[2] == 0 and counts[3] == 32
    assert (np.asarray(forced)[:, :4] < 0).all()
    assert (np.asarray(forced)[:, 36:] < 0).all()


def test_benchmark_configuration_holds_the_published_keys_twice():
    default = LatentConvMoELMConfig()
    cfg = benchmark_config("zaya1_8b", 10, default)
    assert default.rope_theta \
        == cfg["sizes"]["rope_parameters"]["hybrid"]["rope_theta"]
