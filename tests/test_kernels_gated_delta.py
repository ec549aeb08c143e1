"""The two gated delta-rule kernels (``ops/gated_delta.py``) against the
token-by-token recurrence in float64, beyond the parity battery of
``tests/test_kernels.py`` (a file of its own for ``--dist loadfile``).

Tolerances: float32 on both sides with sums in another order (the WY form
of a tile against the recurrence): 2e-4 on the outputs, 2e-5 on the stored
state, the contracts' own."""

import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import kernels
from paddle_tpu.ops import gated_delta as gd

IMPLS = ["lax", "pallas_interpret"]
NAMES = ["gated_delta_chunk_scan", "gated_delta_decode_update"]


def _case(seed=0, lanes=2, chunk=8, hk=2, hv=4, dk=16, dv=16, rows=5):
    rng = np.random.default_rng(seed)
    f = lambda *shape: rng.standard_normal(shape).astype(np.float32)  # noqa

    def unit(a):
        return a / np.sqrt((a * a).sum(-1, keepdims=True) + 1e-6)

    return dict(
        q=unit(f(lanes, chunk, hk, dk)) * dk ** -0.5,
        # neighbouring tokens' keys share a direction, as a conv's do
        k=unit(f(lanes, chunk, hk, dk) + 0.7 * f(lanes, 1, hk, dk)),
        v=f(lanes, chunk, hv, dv),
        g=-np.exp(f(hv)) * 0.3 * np.log1p(np.exp(f(lanes, chunk, hv))),
        beta=1.0 / (1.0 + np.exp(-f(lanes, chunk, hv))),
        pool=f(rows, hv, dk, dv))


def _scan(c, rows, fresh, impl):
    return gd.gated_delta_chunk_scan(
        *(jnp.asarray(c[k]) for k in ("q", "k", "v", "g", "beta", "pool")),
        jnp.asarray(rows, jnp.int32), jnp.asarray(fresh, jnp.int32),
        impl=impl)


def _recur(c, lane, start, upto=None):
    sl = slice(0, upto)
    return gd._recurrence(c["q"][lane, sl], c["k"][lane, sl],
                          c["v"][lane, sl], np.exp(c["g"][lane, sl]),
                          c["beta"][lane, sl], start)


@pytest.mark.parametrize("impl", IMPLS)
def test_scan_is_the_recurrence_from_a_start_state_over_a_ragged_chunk(impl):
    """Lane 0 goes on from the state in its row, lane 1 starts fresh over
    the garbage in its own and has 5 valid tokens of 8; the rows no lane
    holds keep their bits."""
    c = _case()
    c["g"][1, 5:], c["beta"][1, 5:] = 0.0, 0.0
    rows, fresh = [3, 1], [0, 1]
    y, pool = (np.asarray(a) for a in _scan(c, rows, fresh, impl))
    for lane, start in ((0, c["pool"][3]), (1, np.zeros_like(c["pool"][1]))):
        want_y, want_st = _recur(c, lane, start)
        np.testing.assert_allclose(y[lane], want_y, atol=2e-4)
        np.testing.assert_allclose(pool[rows[lane]], want_st, atol=2e-5)
    # 5 valid tokens leave what 5 tokens leave
    _, short = _recur(c, 1, np.zeros_like(c["pool"][1]), upto=5)
    np.testing.assert_allclose(pool[1], short, atol=2e-5)
    for idle in (0, 2, 4):
        assert (pool[idle] == c["pool"][idle]).all()


@pytest.mark.parametrize("impl", IMPLS)
@pytest.mark.parametrize("chunk, tiles", [(8, 1), (128, 2)],
                         ids=["one-tile-a-chunk", "two-tiles-of-64"])
def test_chunks_chained_are_one_sequence(impl, chunk, tiles):
    """A chunk boundary inside a prompt: two calls, the second from the
    row the first left, are the recurrence over both (and a chunk of 128
    carries the state across its own two tiles of 64)."""
    assert chunk // gd._tile(chunk) == tiles
    c = _case(seed=1, lanes=1, chunk=2 * chunk)
    pool, ys = jnp.asarray(c["pool"]), []
    for part in range(2):
        piece = {k: (a[:, part * chunk:(part + 1) * chunk]
                     if k != "pool" else pool) for k, a in c.items()}
        y, pool = _scan(piece, [2], [1 - part], impl)
        ys.append(np.asarray(y)[0])
    want_y, want_st = _recur(c, 0, np.zeros_like(c["pool"][2]))
    np.testing.assert_allclose(np.concatenate(ys), want_y, atol=2e-4)
    np.testing.assert_allclose(np.asarray(pool)[2], want_st, atol=2e-5)


@pytest.mark.parametrize("impl", IMPLS)
def test_decode_update_is_one_step_and_leaves_dead_slots_alone(impl):
    c = _case(seed=2, lanes=3, chunk=1)
    rows = np.array([4, 0, 2], np.int32)        # the middle slot is dead
    o, pool = gd.gated_delta_decode_update(
        *(jnp.asarray(c[k][:, 0]) for k in ("q", "k", "v")),
        jnp.asarray(np.exp(c["g"][:, 0])), jnp.asarray(c["beta"][:, 0]),
        jnp.asarray(c["pool"]), jnp.asarray(rows), impl=impl)
    o, pool = np.asarray(o), np.asarray(pool)
    for lane in (0, 2):
        want_o, want_st = _recur(c, lane, c["pool"][rows[lane]])
        np.testing.assert_allclose(o[lane], want_o[0], atol=2e-5)
        np.testing.assert_allclose(pool[rows[lane]], want_st, atol=2e-5)
    assert (o[1] == 0).all()
    for idle in (0, 1, 3):
        assert (pool[idle] == c["pool"][idle]).all()


def test_a_token_subtracts_what_the_state_already_answers():
    """The delta rule, not a cumulative sum: writing the same key twice
    with ``beta`` 1 and no decay leaves the state answering the SECOND
    value for that key, where ``S + k (x) v`` would answer their sum."""
    dk = dv = 16
    k = np.zeros((1, 2, 1, dk), np.float32)
    k[..., 3] = 1.0
    v = np.random.default_rng(0).standard_normal((1, 2, 1, dv)).astype(
        np.float32)
    zeros, ones = np.zeros((1, 2, 1), np.float32), np.ones((1, 2, 1),
                                                          np.float32)
    o, pool = gd.gated_delta_chunk_scan(
        *(jnp.asarray(a) for a in (k, k, v, zeros, ones)),
        jnp.zeros((2, 1, dk, dv)), jnp.asarray([1]), jnp.asarray([1]),
        impl="lax")
    np.testing.assert_allclose(np.asarray(o)[0, 1, 0], v[0, 1, 0], atol=1e-6)
    np.testing.assert_allclose(np.asarray(pool)[1, 0, 3], v[0, 1, 0],
                               atol=1e-6)


@pytest.mark.parametrize("el", [16, 24, 64],
                         ids=["rows", "rows-ragged", "blocks-of-16"])
def test_forward_substitution_inverts_a_unit_lower_matrix(el):
    """A tile of 64 goes by diagonal blocks of 16 and the blocks below
    them; a tile that is no whole number of blocks row by row. Against
    float64's inverse, to 1e-6 of its largest entry."""
    rng = np.random.default_rng(3)
    a = 0.4 * np.tril(rng.standard_normal((2, 3, el, el)), -1).astype(
        np.float32)
    inv = np.asarray(gd._unit_lower_inverse(jnp.asarray(a)))
    want = np.linalg.inv(np.eye(el) + a.astype(np.float64))
    np.testing.assert_allclose(inv, want, atol=1e-6 * np.abs(want).max())
    assert (np.triu(inv, 1) == 0).all()


@pytest.mark.parametrize("impl", IMPLS)
@pytest.mark.parametrize("name", NAMES)
def test_a_bfloat16_pool_rounds_the_stored_state_only(name, impl):
    """The pool comes back in the type it came in; the outputs are float32
    and move only by what the START state lost when it was rounded."""
    args, kw = kernels.get(name).sample_inputs(1)
    rounded = args[5].astype(jnp.bfloat16)
    y32, p32 = kernels.dispatch(
        name, *args[:5], rounded.astype(jnp.float32), *args[6:], impl=impl,
        **kw)
    y16, p16 = kernels.dispatch(name, *args[:5], rounded, *args[6:],
                                impl=impl, **kw)
    assert p16.dtype == jnp.bfloat16 and y16.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(y16), np.asarray(y32), atol=1e-5)
    assert (np.asarray(p16) == np.asarray(p32.astype(jnp.bfloat16))).all()


def test_decode_grid_step_holds_whole_key_heads_under_the_budget():
    # the published layer: 32 tiles of (128, 128) float32, 2 MiB in and
    # out each: all of them in one step
    assert gd._head_block(32, 2, 128, 128) == 32
    assert gd._head_block(4, 2, 16, 16) == 4
    # tiles four times as large: the most that divide the heads, in whole
    # key heads, and stay under the budget
    assert gd._head_block(32, 2, 256, 256) == 8
    assert gd._head_block(24, 3, 256, 256) == 6
    args, kw = kernels.get("gated_delta_decode_update").sample_inputs(0)
    assert gd._decode_vmem_estimate(args, kw, {}) \
        == 4 * 4 * 16 * 16 * 4 + 2 * 16 * 16 * 4


def test_scan_tiles_a_long_chunk_and_refuses_a_ragged_one():
    assert gd._tile(8) == 8 and gd._tile(64) == 64
    assert gd._tile(256) == gd.DELTA_TILE
    with pytest.raises(ValueError, match="multiple of the delta rule's tile"):
        gd._tile(100)


@pytest.mark.parametrize("name", NAMES)
def test_dispatch_is_counted_by_kernel_and_impl(name):
    from paddle_tpu.observability import registry as obs_registry
    c = obs_registry.counter("kernel_dispatch_total")
    before = {i: c.value(kernel=name, impl=i)
              for i in ("lax", "pallas_interpret")}
    args, kw = kernels.get(name).sample_inputs(0)
    kernels.dispatch(name, *args, impl="pallas_interpret", **kw)
    assert c.value(kernel=name, impl="pallas_interpret") \
        == before["pallas_interpret"] + 1
    assert c.value(kernel=name, impl="lax") == before["lax"]


@pytest.mark.parametrize("name", NAMES)
def test_kernels_are_registered_with_both_forms(name):
    spec = kernels.get(name)
    assert spec.lax_fn is not None and spec.pallas_fn is not None
    assert spec.contract.donatable == ("pool",)
    assert spec.vmem_estimate is not None
