"""The window family's pieces on their own, beside the engine cases of
``tests/test_window_moe_serving.py``: one chip's share of the experts, a
cache whose window layers hold a ring of pages a slot, and the paged
kernels under a window. A file of its own for ``--dist loadfile``."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import kernels
from paddle_tpu.models import WindowMoELM, WindowMoELMConfig
from paddle_tpu.ops import grouped_ffn
from paddle_tpu.serving import layer_kinds
from paddle_tpu.serving.paged_cache import (PageOverflowError,
                                            PagedCacheConfig, PagedKVCache)
from paddle_tpu.serving.program import ServingSpec

import window_moe_reference as ref

PAGE, WINDOW = 4, 8


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
        want = ref.reference_ffn(lp, b, ref.sizes_of(uncut))
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
