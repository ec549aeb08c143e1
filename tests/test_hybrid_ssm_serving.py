"""The attention + state-space hybrid through the paged serving engine,
against its plain float32 reference.

Sizes: hidden 64, 4 query heads over 2 KV heads of 16, 4 mixer heads of 16
in 2 groups, state 16, conv of 4 taps, page 4, chunk 8, 2 layers, every
multiplier as published. Weights are seeded float32, so what separates the
engine from the reference is the order of float32 sums (the chunked scan
against the token-by-token recurrence, the paged kernels' page folds) and
nothing else.
"""

import functools
import hashlib
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import inference, kernels
from paddle_tpu import observability as obs
from paddle_tpu.models.hybrid_ssm_lm import HybridSSMLM, HybridSSMLMConfig
from paddle_tpu.ops import ssm_scan

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
import hybrid_ssm_reference as ref  # noqa: E402
from serving_taps import (assert_close, assert_refused,  # noqa: E402
                          benchmark_config, FEATURE_OPTIONS,
                          moved, reference_rows, serve_alone,
                          serve_into_a_used_slot_and_alone,
                          serve_staggered_watching_state_rows,
                          shared_engines, tapped_engine, traced)
from serving_taps import prompt as _prompt  # noqa: E402

#: float32 on both sides, sums in another order. With the published
#: multipliers (``lm_head_multiplier`` 1/128 on weights of std 0.02) the
#: logits are of magnitude 5e-3, so the bound is 2e-5 OF THE LARGEST
#: LOGIT: sound runs read 2e-7 of it, a bfloat16 state pool 1e-4
LOGIT_RTOL = 2e-5
_assert_close = functools.partial(assert_close, rtol=LOGIT_RTOL)

PAGE, CHUNK = 4, 8
#: decay per token exp(-A dt) with A dt in 0.002..0.14: a head remembers
#: 7 to 500 tokens and its state, not the skip, carries the mixer's
#: output. (With the module's own A in 1..16 a state of 16 dims carries 2%
#: of it at these sizes, and losing it moves a logit by 1e-5 of itself.)
TIME_SCALES = dict(a_init_range=(0.02, 0.2), dt_init_range=(0.1, 0.7))


@pytest.fixture(scope="module")
def model_and_params():
    """Seeded weights as ``init`` draws them, the heads' time scales from
    slower ranges than the module's own (``TIME_SCALES``)."""
    model = HybridSSMLM(HybridSSMLMConfig.tiny(kernel_impl="lax",
                                               **TIME_SCALES))
    return model, model.init(jax.random.PRNGKey(5))


def _engine(params, impl="lax", slots=2, state_dtype="float32", **kw):
    model = HybridSSMLM(HybridSSMLMConfig.tiny(
        kernel_impl=impl, state_dtype=state_dtype, **TIME_SCALES))
    return tapped_engine(model, params, num_slots=slots, page_size=PAGE,
                         prefill_chunk=CHUNK, attn_impl=impl, **kw)


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


def _reference_rows(model, params, prompt, out):
    return _rows(params, prompt, out, model.cfg)


CASES = {
    # 21 = 2 chunks and 5 tokens: the prompt ends inside a chunk and
    # inside a page; 7 new tokens are 3 decode blocks and cross a page
    "ends_inside_a_chunk": (21, 7),
    "ends_on_a_chunk_edge": (16, 7),
    # 12 tokens: a page edge inside the second chunk
    "ends_on_a_page_edge": (12, 9),
}


@pytest.mark.parametrize("case, impl", [
    ("ends_inside_a_chunk", "lax"), ("ends_inside_a_chunk",
                                     "pallas_interpret"),
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
    ids = _prompt(130)          # past one scan tile: padded to two
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref.reference_logits(params, jnp.asarray(ids),
                                               model.cfg))
    _assert_close(np.asarray(model.forward(params, jnp.asarray(ids)[None]))[0],
                  want)


def test_a_bfloat16_state_fails_the_same_comparison(model_and_params):
    """The state pool in bfloat16 and nothing else changed: the same
    request, the same bound."""
    model, params = model_and_params
    prompt = _prompt(21)
    eng, sink, _ = _engine(params, state_dtype="bfloat16")
    assert eng.cache.pages[0][-1].dtype == jnp.bfloat16
    out, got = serve_alone(eng, sink, prompt, 7)
    want = _reference_rows(model, params, prompt, out)
    with pytest.raises(AssertionError):
        _assert_close(got, want)


# -- the kernels against the token-by-token recurrence ------------------------

def _scan_case(seed=0, lanes=2, chunk=CHUNK):
    h, p, g, n = 4, 16, 2, 16
    rng = np.random.default_rng(seed)
    f = lambda *shape: rng.standard_normal(shape).astype(np.float32)  # noqa
    return dict(x=f(lanes, chunk, h * p),
                dt=np.log1p(np.exp(f(lanes, chunk, h))),
                a=-np.exp(0.5 * f(h)), bm=f(lanes, chunk, g * n),
                cm=f(lanes, chunk, g * n), pool=f(5, h, n, p)), g


@pytest.mark.parametrize("impl", ["lax", "pallas_interpret"])
def test_scan_is_the_recurrence_from_a_start_state_over_a_ragged_chunk(impl):
    """Lane 0 goes on from the state in its row, lane 1 starts fresh over
    the garbage in its own, and has 5 valid tokens of 8."""
    c, g = _scan_case()
    c["dt"][1, 5:] = 0.0
    rows, fresh = np.array([3, 1], np.int32), np.array([0, 1], np.int32)
    y, pool = ssm_scan.ssd_chunk_scan(
        *(jnp.asarray(c[k]) for k in ("x", "dt", "a", "bm", "cm", "pool")),
        jnp.asarray(rows), jnp.asarray(fresh), n_groups=g, impl=impl)
    for lane, start in ((0, c["pool"][3]), (1, np.zeros_like(c["pool"][1]))):
        want_y, want_st = ssm_scan._recurrence(
            c["x"][lane], c["dt"][lane], c["a"], c["bm"][lane],
            c["cm"][lane], start, g)
        np.testing.assert_allclose(np.asarray(y)[lane], want_y, atol=2e-4)
        np.testing.assert_allclose(np.asarray(pool)[rows[lane]], want_st,
                                   atol=2e-5)
    # 5 valid tokens leave what 5 tokens leave
    _, short = ssm_scan._recurrence(
        c["x"][1, :5], c["dt"][1, :5], c["a"], c["bm"][1, :5], c["cm"][1, :5],
        np.zeros_like(c["pool"][1]), g)
    np.testing.assert_allclose(np.asarray(pool)[1], short, atol=2e-5)
    for idle in (0, 2, 4):
        assert (np.asarray(pool)[idle] == c["pool"][idle]).all()


@pytest.mark.parametrize("impl", ["lax", "pallas_interpret"])
def test_two_chunks_chained_are_one_sequence(impl):
    c, g = _scan_case(seed=1, lanes=1, chunk=2 * CHUNK)
    rows = jnp.asarray([2], jnp.int32)
    pool = jnp.asarray(c["pool"])
    ys = []
    for k in range(2):
        part = slice(k * CHUNK, (k + 1) * CHUNK)
        y, pool = ssm_scan.ssd_chunk_scan(
            jnp.asarray(c["x"][:, part]), jnp.asarray(c["dt"][:, part]),
            jnp.asarray(c["a"]), jnp.asarray(c["bm"][:, part]),
            jnp.asarray(c["cm"][:, part]), pool, rows,
            jnp.asarray([1 - k], jnp.int32), n_groups=g, impl=impl)
        ys.append(np.asarray(y)[0])
    want_y, want_st = ssm_scan._recurrence(
        c["x"][0], c["dt"][0], c["a"], c["bm"][0], c["cm"][0],
        np.zeros_like(c["pool"][2]), g)
    np.testing.assert_allclose(np.concatenate(ys), want_y, atol=2e-4)
    np.testing.assert_allclose(np.asarray(pool)[2], want_st, atol=2e-5)


@pytest.mark.parametrize("impl", ["lax", "pallas_interpret"])
def test_decode_update_is_one_step_and_leaves_dead_slots_alone(impl):
    c, g = _scan_case(seed=2, lanes=3, chunk=1)
    rows = np.array([4, 0, 2], np.int32)        # the middle slot is dead
    y, pool = ssm_scan.ssm_decode_update(
        *(jnp.asarray(c[k][:, 0] if c[k].ndim == 3 else c[k])
          for k in ("x", "dt", "a", "bm", "cm")),
        jnp.asarray(c["pool"]), jnp.asarray(rows), n_groups=g, impl=impl)
    y, pool = np.asarray(y), np.asarray(pool)
    for lane in (0, 2):
        want_y, want_st = ssm_scan._recurrence(
            c["x"][lane], c["dt"][lane], c["a"], c["bm"][lane],
            c["cm"][lane], c["pool"][rows[lane]], g)
        np.testing.assert_allclose(y[lane], want_y[0], atol=2e-5)
        np.testing.assert_allclose(pool[rows[lane]], want_st, atol=2e-5)
    assert (y[1] == 0).all()
    for idle in (0, 1, 3):
        assert (pool[idle] == c["pool"][idle]).all()


# -- continuous batching --------------------------------------------------------

def test_a_reused_slot_gives_what_the_request_gives_alone(model_and_params,
                                                          engines):
    """Two requests one after the other in slot 0: the second starts from
    zeros, not from what the first left in the slot's row."""
    model, params = model_and_params
    second = _prompt(13, seed=77)
    out, got = serve_into_a_used_slot_and_alone(*engines("lax"), _prompt(19),
                                                second)
    _assert_close(got, _reference_rows(model, params, second, out))


def test_a_step_touches_only_the_rows_of_its_own_lanes(model_and_params,
                                                        engines):
    """Four slots under staggered traffic: every step leaves the rows of
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


# -- refusals -------------------------------------------------------------------

def _sparse_moe():
    from paddle_tpu.models.sparse_moe_lm import (SparseMoELM,
                                                 SparseMoELMConfig)
    model = SparseMoELM(SparseMoELMConfig.tiny(kernel_impl="lax"))
    return model, model.init(jax.random.PRNGKey(5))


def _hybrid():
    model = HybridSSMLM(HybridSSMLMConfig.tiny(kernel_impl="lax"))
    return model, model.init(jax.random.PRNGKey(5))


#: what each program that refuses leaves out of ``supports``
REFUSES = {"SparseMoELM": (_sparse_moe, sorted(set(FEATURE_OPTIONS)
                                               - {"prefix_sharing"})),
           "HybridSSMLM": (_hybrid, sorted(FEATURE_OPTIONS))}


@pytest.mark.parametrize("family, feature", [
    (family, feature) for family, (_make, features) in REFUSES.items()
    for feature in features])
def test_engine_refuses_an_option_by_class_and_feature(family, feature):
    """One sentence for every option and call a program does not carry:
    the model's class and the feature by name."""
    assert_refused(*REFUSES[family][0](), feature,
                   rf"{family} does not serve with '{feature}' yet")


def test_hybrid_engine_is_built_with_sharing_off(engines):
    eng, _, _ = engines("lax")
    assert eng.cache.config.share_prefix is False
    assert eng.program.spec.supports == frozenset()
    assert ("page_read",) not in eng.warmup_plan()
    same = _prompt(17)
    for _ in range(2):          # a verbatim repeat prefills every token
        eng.generate_many([same], max_new_tokens=2)
    assert eng.cache.shared_tokens_total == 0


# -- counters and the step programs ---------------------------------------------

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
    assert slot_bytes == layers * 4 * (3 * (64 + 2 * 2 * 16) + 4 * 16 * 16)
    assert snap["serving_ssm_prefill_tokens_total"] == 21 * layers
    assert snap["serving_ssm_decode_slot_steps_total"] \
        == blocks * block * layers
    assert snap["serving_ssm_state_resets_total"] == 1
    assert snap['serving_ssm_state_bytes_total{kind="written"}'] \
        == slot_bytes * (blocks * block + 3)
    assert snap['serving_ssm_state_bytes_total{kind="read"}'] \
        == slot_bytes * (blocks * block + 3 - 1)
    assert reg.snapshot()["serving_ssm_state_pool_bytes"] == slot_bytes * 5
    # one read-back a block, by the step after the one that sent it, none
    # in a prefill call
    assert snap['serving_device_readbacks_total{phase="decode"}'] \
        == snap["serving_steps_total"] - 1 == blocks
    assert snap.get('serving_device_readbacks_total{phase="prefill"}', 0) == 0
    spans = tracer.spans()
    for name in ("serving.decode_round", "serving.prefill_call"):
        # (the last round of the drain only settles the block in flight)
        mine = [s for s in spans if s.name == name
                and s.attrs.get("slots_live", 1)]
        assert mine and all(s.attrs["state_slots"] == 1 for s in mine)


def test_a_program_without_slot_state_lowers_to_the_steps_it_had():
    """GPT's decode block and prefill step trace to the jaxprs of the
    commit before slot state came (their sha256, taken there by this
    test's lines under this suite's conftest; a change that means to
    alter GPT's steps takes them anew): the new hook adds nothing to a
    program that does not declare it, and such an engine binds none of
    the new series."""
    from paddle_tpu.models.gpt import GPT, GPTConfig
    model = GPT(GPTConfig.tiny())
    params = model.init(jax.random.PRNGKey(0))
    reg = obs.MetricsRegistry()
    eng = inference.make_serving_engine(
        model, params, num_slots=4, page_size=8, prefill_chunk=16,
        attn_impl="lax", registry=reg)
    z = jnp.zeros((4,), jnp.int32)
    decode = jax.make_jaxpr(eng._decode_step_impl)(
        params, eng.cache.pages, jnp.zeros((4, 2), jnp.int32), z, z, z)
    prefill = jax.make_jaxpr(eng._prefill_step_impl)(
        params, eng.cache.pages, jnp.zeros((2, 2), jnp.int32), z[:2],
        jnp.zeros((2, 16), jnp.int32), z[:2])
    sha = lambda j: hashlib.sha256(str(j).encode()).hexdigest()  # noqa: E731
    assert sha(decode) == ("bf60eb05707606164c429e670e0faf8b"
                           "614a46453a81d392e5765a1874b091d3")
    assert sha(prefill) == ("0f83fd0423ae898e96602c595bd4c2a1"
                            "f45744bde9e34eac7744e2bf8f48ca64")
    assert not [k for k in reg.snapshot() if "ssm" in k]
    assert all(len(ent) == 2 for ent in eng.cache.pages)


def test_kernels_are_registered_with_both_forms():
    for name in ("ssd_chunk_scan", "ssm_decode_update"):
        spec = kernels.get(name)
        assert spec.lax_fn is not None and spec.pallas_fn is not None
        assert spec.contract.donatable == ("pool",)


# -- the benchmark's copy ---------------------------------------------------------

@pytest.fixture(scope="module")
def family():
    sys.path.insert(0, os.path.join(ROOT, "benchmark"))
    from families import falcon_h1
    return falcon_h1


def test_benchmark_reference_is_the_plain_reference(model_and_params,
                                                    family):
    """``families/falcon_h1.py`` computes the same pass in blocks (one
    matrix upcast at a time, the MLP's hidden units and the vocabulary in
    pieces, the rows asked for only): held to the plain one here."""
    model, params = model_and_params
    sizes = family.sizes_of(model.cfg)
    ids = _prompt(40)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref.reference_logits(params, jnp.asarray(ids),
                                               model.cfg))
        got, selections = family.reference_logits(
            params, jnp.asarray(ids)[None], sizes, lo=7, rows=24,
            probe=jnp.zeros((8,), jnp.int32))
    assert selections.size == 0
    _assert_close(np.asarray(got)[0], want[7:31])
    built = family.build(sizes, interpret=True)
    assert built.cfg.kernel_impl == "pallas_interpret"
    assert built.serving().spec.slot_state == model.slot_state()


def test_benchmark_configuration_holds_the_published_keys_twice():
    benchmark_config("falcon_h1_34b", 6, HybridSSMLMConfig())
