"""The pieces ISSUE 49 added on their own, beside the engine cases of
``tests/test_window_sink_serving.py``: the dense paged bodies where a KV
head's keys are wider than its values, query groups of 16, with and
without a learned sink and a window, against NumPy; a chip's share of a
layer with no shared expert. A file of its own for ``--dist loadfile``."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import kernels
from paddle_tpu.kernels import autotune
from paddle_tpu.models import WindowMoELM
from paddle_tpu.serving import decode_attention as DA

import mimo_v2_flash_reference as ref
from test_window_sink_serving import tiny_config

DK, DV, KV, GROUP = 192, 128, 2, 16     # K pool 384 lanes, V pool 256


def _sample(seed, chunked, page, sink, window):
    s, mp, h = 3, 4, KV * GROUP
    pages = s * mp + 1
    rng = np.random.default_rng(seed)
    pools = tuple(jnp.asarray(rng.standard_normal((pages, page, KV * d)),
                              jnp.float32) for d in (DK, DV))
    bt = jnp.asarray((rng.permutation(pages - 1)[:s * mp] + 1)
                     .reshape(s, mp), jnp.int32)
    kw = {}
    if sink:        # from far under the scores to over them
        kw["sinks"] = jnp.asarray(rng.standard_normal(h) * 4, jnp.float32)
    if window:
        kw["window"] = window
    if not chunked:
        q = jnp.asarray(rng.standard_normal((s, h, DK)), jnp.float32)
        lengths = jnp.asarray([0, mp * page, rng.integers(1, mp * page)],
                              jnp.int32)
        return (q, *pools, bt, lengths), kw
    q = jnp.asarray(rng.standard_normal((s, page, h, DK)), jnp.float32)
    starts = jnp.asarray(rng.integers(0, (mp - 1) * page, s), jnp.int32)
    n_valid = jnp.asarray([0, page, rng.integers(1, page)], jnp.int32)
    return (q, *pools, bt, starts, n_valid), kw


#: body -> (kernel, page size, the least rows of a group fold)
BODIES = {
    # whole tiles (384 and 256 lanes, pages of 8 float32 rows): the body
    # that walks a slot's live pages itself
    "decode_walk": ("ragged_paged_decode", 8, None),
    # pages of 4 rows are no whole tiles: the pipelined body
    "decode_pipelined": ("ragged_paged_decode", 4, None),
    # a fold a query head, K lanes a static slice at a multiple of 192
    "prefill_by_head": ("ragged_paged_prefill", 8, 10 ** 9),
    # a fold a KV head, two heads' K lanes (384) loaded at a tile boundary
    "prefill_by_group": ("ragged_paged_prefill", 8, 0),
}


@pytest.mark.parametrize("window", [None, 11], ids=["full", "window"])
@pytest.mark.parametrize("sink", [False, True], ids=["no_sink", "sink"])
@pytest.mark.parametrize("body", sorted(BODIES))
def test_paged_bodies_with_keys_wider_than_values(body, sink, window,
                                                  monkeypatch):
    """Each Pallas body, interpreted, and the ``lax`` form against the
    NumPy reference at ``Dk`` 192 beside ``Dv`` 128 and 16 query heads a
    KV head, at every ``pages_per_block``."""
    name, page, fold_rows = BODIES[body]
    if fold_rows is not None:
        monkeypatch.setattr(DA, "_GROUP_FOLD_MIN_ROWS", fold_rows)
    spec = kernels.get(name)
    args, kw = _sample(3, "prefill" in name, page, sink, window)
    want = np.asarray(spec.reference_fn(*args, **kw))
    assert want.shape == args[0].shape[:-1] + (DV,)
    got = kernels.dispatch(name, *args, impl="lax", **kw)
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-5, rtol=2e-5)
    for pb in (1, 2, 4):
        got = kernels.dispatch(name, *args, impl="pallas_interpret",
                               block_sizes={"pages_per_block": pb}, **kw)
        np.testing.assert_allclose(np.asarray(got), want, atol=2e-5,
                                   rtol=2e-5, err_msg=f"{body} pb={pb}")
    if sink:        # the sink moved the result: it is not a no-op
        bare = {k: v for k, v in kw.items() if k != "sinks"}
        assert np.abs(np.asarray(spec.reference_fn(*args, **bare))
                      - want).max() > 1e-2


def test_a_sink_far_below_the_scores_is_no_sink():
    """The same call with sinks at -1e9 gives what it gives without."""
    spec = kernels.get("ragged_paged_decode")
    args, kw = _sample(4, False, 8, True, None)
    none = kernels.dispatch("ragged_paged_decode", *args,
                            impl="pallas_interpret")
    far = kernels.dispatch(
        "ragged_paged_decode", *args, impl="pallas_interpret",
        sinks=jnp.full_like(kw["sinks"], -1e9))
    np.testing.assert_allclose(np.asarray(far), np.asarray(none), atol=1e-6)
    assert spec.contract.donatable == ("k_pages", "v_pages")


def test_tune_key_and_vmem_estimate_follow_the_two_widths():
    spec = kernels.get("ragged_paged_decode")
    sds = jax.ShapeDtypeStruct
    q = sds((64, 64, DK), jnp.bfloat16)
    table, lens = sds((64, 64), jnp.int32), sds((64,), jnp.int32)
    wide = (q, sds((4609, 128, 768), jnp.bfloat16),
            sds((4609, 128, 512), jnp.bfloat16), table, lens)
    alike = (q, wide[1], wide[1], table, lens)
    sink = {"sinks": sds((64,), jnp.float32)}
    assert spec.tune_signature(wide, {}) \
        == spec.tune_signature(alike, {}) + (("dv", DV),)
    assert spec.tune_signature(wide, sink) \
        == spec.tune_signature(wide, {}) + (("sink", 1),)
    assert autotune.tune_key(spec, wide, sink) \
        != autotune.tune_key(spec, wide, {})
    blocks = {"pages_per_block": 4}
    # a block of V pages is 512 lanes where K's is 768, and so is the
    # float32 accumulator and the products with V
    assert spec.vmem_estimate(wide, {}, blocks) \
        < spec.vmem_estimate(alike, {}, blocks)


# -- the share ----------------------------------------------------------------

@pytest.mark.parametrize("shares", [4, 2], ids=["four_of_2", "two_of_4"])
@pytest.mark.parametrize("impl", ["lax", "pallas_interpret"])
def test_the_shares_add_up_to_the_uncut_layer(impl, shares):
    """The chips' shares of 8 routed experts add up to what the uncut
    reference gives for the whole layer: nothing is counted twice (there
    is no shared expert) and every pair is some chip's."""
    uncut = tiny_config(num_experts=8, kernel_impl=impl)
    whole = WindowMoELM(uncut).init(jax.random.PRNGKey(2))
    layer, held = 2, 8 // shares
    lp = whole["layers"][str(layer)]
    assert "shared" not in lp
    x = 0.01 * jax.random.normal(jax.random.PRNGKey(3), (3, 5, 64),
                                 jnp.float32)
    valid = jnp.ones((3, 5), bool)
    with jax.default_matmul_precision("highest"):
        b = ref._rms(x.reshape(15, 64), lp["ffn_norm"]["scale"], 1e-5)
        want = ref.reference_ffn(lp, b, ref.sizes_of(uncut))
        total, pairs = 0.0, 0
        for offset in range(0, 8, held):
            cfg = dataclasses.replace(uncut, num_experts=held,
                                      num_routed_experts=8,
                                      expert_offset=offset)
            tree = jax.tree_util.tree_map(lambda a: a, whole)
            tree["layers"][str(layer)]["experts"] = {
                k: w[offset:offset + held] for k, w in lp["experts"].items()}
            y, stats = WindowMoELM(cfg).ffn(tree, layer, x, valid)
            total = total + (y - x).reshape(15, 64)
            pairs += int(stats["moe_assignments"])
            assert int(stats["moe_routed_pairs"]) == 15 * 3
    assert pairs == 15 * 3          # every pair is some chip's, once
    np.testing.assert_allclose(total, want, rtol=0,
                               atol=2e-5 * float(jnp.abs(want).max()))
