"""The pages themselves, from ``tests/test_serving.py`` (ISSUE 4): the
allocator, the two ragged kernels against dense attention, and the wire
format of a page. A file of its own for ``--dist loadfile``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import serving
from paddle_tpu.serving.paged_cache import (PagedCacheConfig, PagedKVCache,
                                            PageOverflowError)

from serving_taps import prompts as _prompts, tiny_gpt as _model


class TestPagedKVCache:
    def _cache(self, **kw):
        kw.setdefault("num_layers", 1)
        kw.setdefault("num_heads", 2)
        kw.setdefault("head_dim", 4)
        kw.setdefault("num_slots", 3)
        kw.setdefault("page_size", 4)
        kw.setdefault("num_pages", 10)
        kw.setdefault("max_pages_per_slot", 4)
        return PagedKVCache(PagedCacheConfig(**kw))

    def test_reserve_free_roundtrip(self):
        c = self._cache()
        c.reserve(0, 9)     # 3 pages
        c.reserve(1, 4)     # 1 page
        assert c.pages_in_use == 4
        assert set(c.block_tables[0, :3]) & {0} == set()
        c.check_invariants()
        c.free_slot(0)
        assert c.pages_in_use == 1
        assert (c.block_tables[0] == 0).all()
        c.check_invariants()

    def test_pages_are_reused_after_free(self):
        c = self._cache()
        c.reserve(0, 16)
        first = set(c.slot_pages(0))
        c.free_slot(0)
        c.reserve(1, 16)
        assert set(c.slot_pages(1)) == first
        c.check_invariants()

    def test_overflow_refused_all_or_nothing(self):
        c = self._cache()
        c.reserve(0, 16)
        c.reserve(1, 16)
        free_before = c.free_pages
        assert not c.can_reserve(8)
        with pytest.raises(PageOverflowError):
            c.reserve(2, 8)
        assert c.free_pages == free_before  # nothing leaked
        with pytest.raises(PageOverflowError):
            c.reserve(2, 17)                # > max_pages_per_slot
        c.check_invariants()

    def test_null_page_never_allocated(self):
        c = self._cache()
        c.reserve(0, 16)
        c.reserve(1, 16)
        c.reserve(2, 4)
        assert 0 not in [p for s in range(3) for p in c.slot_pages(s)]

    def test_utilization_tracks_live_tokens(self):
        c = self._cache()
        assert c.utilization() == 0.0
        c.reserve(0, 8)
        c.lengths[0] = 8
        assert c.utilization() == pytest.approx(8 / (9 * 4))


class TestRaggedPagedDecodeAttention:
    def _setup(self, seed=0, s=4, h=2, dh=8, ps=4, mp=4, p=16):
        rng = np.random.default_rng(seed)
        q = jnp.asarray(rng.standard_normal((s, h, dh)), jnp.float32)
        # the pool's stored layout: a token's heads folded head-major
        kp = jnp.asarray(rng.standard_normal((p, ps, h, dh)),
                         jnp.float32).reshape(p, ps, h * dh)
        vp = jnp.asarray(rng.standard_normal((p, ps, h, dh)),
                         jnp.float32).reshape(p, ps, h * dh)
        bt = jnp.asarray(rng.integers(1, p, (s, mp)), jnp.int32)
        lens = jnp.asarray(rng.integers(0, mp * ps + 1, (s,)), jnp.int32)
        return q, kp, vp, bt, lens

    def test_lax_matches_dense_gather(self):
        q, kp, vp, bt, lens = self._setup()
        out = serving.ragged_paged_decode_attention(q, kp, vp, bt, lens,
                                                    impl="lax")
        dh = q.shape[-1]
        for s in range(q.shape[0]):
            n = int(lens[s])
            if n == 0:
                np.testing.assert_array_equal(np.asarray(out[s]), 0.0)
                continue
            k = kp[bt[s]].reshape(-1, *q.shape[1:])[:n]
            v = vp[bt[s]].reshape(-1, *q.shape[1:])[:n]
            sc = jnp.einsum("hd,thd->ht", q[s], k) / np.sqrt(dh)
            ref = jnp.einsum("ht,thd->hd", jax.nn.softmax(sc, -1), v)
            np.testing.assert_allclose(np.asarray(out[s]), np.asarray(ref),
                                       atol=1e-5, rtol=1e-5)

    def test_pallas_interpret_matches_lax(self):
        """The REAL kernel (interpret mode) against the lax fallback —
        including a length-0 (inactive) slot."""
        q, kp, vp, bt, _ = self._setup(seed=1)
        lens = jnp.asarray([0, 1, 7, 16], jnp.int32)
        out_l = serving.ragged_paged_decode_attention(q, kp, vp, bt, lens,
                                                      impl="lax")
        out_p = serving.ragged_paged_decode_attention(
            q, kp, vp, bt, lens, impl="pallas_interpret")
        np.testing.assert_allclose(np.asarray(out_p), np.asarray(out_l),
                                   atol=1e-5, rtol=1e-5)

    def test_stale_page_contents_ignored(self):
        """Poison every page a slot does NOT own plus its own dead tail:
        the output must only depend on the live prefix."""
        q, kp, vp, bt, _ = self._setup(seed=2, s=1)
        lens = jnp.asarray([6], jnp.int32)
        ref = serving.ragged_paged_decode_attention(q, kp, vp, bt, lens,
                                                    impl="lax")
        owned = set(np.asarray(bt[0, :2]).tolist())  # pages of tokens 0..7
        poison_k = np.asarray(kp).copy()
        poison_v = np.asarray(vp).copy()
        for pg in range(kp.shape[0]):
            if pg not in owned:
                poison_k[pg] = 1e6
                poison_v[pg] = 1e6
        # dead tail inside the second owned page (tokens 6..7)
        pg2 = int(bt[0, 1])
        poison_k[pg2, 2:] = 1e6
        poison_v[pg2, 2:] = 1e6
        out = serving.ragged_paged_decode_attention(
            q, jnp.asarray(poison_k), jnp.asarray(poison_v), bt, lens,
            impl="lax")
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5, rtol=1e-5)


class TestRaggedPagedPrefillAttention:
    """The batched chunked-prefill kernel (ISSUE 6): one call, every
    slot's next chunk, causal over pages."""

    def _setup(self, seed=0, s=3, c=4, h=2, dh=8, ps=4, mp=4, p=12):
        rng = np.random.default_rng(seed)
        q = jnp.asarray(rng.standard_normal((s, c, h, dh)), jnp.float32)
        kp = jnp.asarray(rng.standard_normal((p, ps, h, dh)),
                         jnp.float32).reshape(p, ps, h * dh)
        vp = jnp.asarray(rng.standard_normal((p, ps, h, dh)),
                         jnp.float32).reshape(p, ps, h * dh)
        bt = jnp.asarray(rng.integers(1, p, (s, mp)), jnp.int32)
        return q, kp, vp, bt

    def test_lax_matches_per_row_dense(self):
        q, kp, vp, bt = self._setup()
        starts = jnp.asarray([0, 5, 2], jnp.int32)
        nv = jnp.asarray([4, 3, 4], jnp.int32)
        out = serving.ragged_paged_prefill_attention(
            q, kp, vp, bt, starts, nv, impl="lax")
        dh = q.shape[-1]
        for s in range(q.shape[0]):
            k = kp[bt[s]].reshape(-1, *q.shape[2:])
            v = vp[bt[s]].reshape(-1, *q.shape[2:])
            for c in range(int(nv[s])):
                n = int(starts[s]) + c + 1        # causal horizon
                sc = jnp.einsum("hd,thd->ht", q[s, c], k[:n]) / np.sqrt(dh)
                ref = jnp.einsum("ht,thd->hd",
                                 jax.nn.softmax(sc, -1), v[:n])
                np.testing.assert_allclose(
                    np.asarray(out[s, c]), np.asarray(ref),
                    atol=1e-5, rtol=1e-5)

    def test_pad_lanes_and_inactive_slots_emit_zeros(self):
        q, kp, vp, bt = self._setup(seed=1)
        starts = jnp.asarray([0, 3, 0], jnp.int32)
        nv = jnp.asarray([2, 4, 0], jnp.int32)    # slot 2 inactive
        for impl in ("lax", "pallas_interpret"):
            out = serving.ragged_paged_prefill_attention(
                q, kp, vp, bt, starts, nv, impl=impl)
            np.testing.assert_array_equal(np.asarray(out[0, 2:]), 0.0)
            np.testing.assert_array_equal(np.asarray(out[2]), 0.0)

    def test_pallas_interpret_matches_lax(self):
        """The REAL kernel (interpret mode) against the lax fallback —
        mixed starts/valid counts including an idle lane."""
        q, kp, vp, bt = self._setup(seed=2)
        starts = jnp.asarray([7, 0, 2], jnp.int32)
        nv = jnp.asarray([4, 1, 0], jnp.int32)
        out_l = serving.ragged_paged_prefill_attention(
            q, kp, vp, bt, starts, nv, impl="lax")
        out_p = serving.ragged_paged_prefill_attention(
            q, kp, vp, bt, starts, nv, impl="pallas_interpret")
        np.testing.assert_allclose(np.asarray(out_p), np.asarray(out_l),
                                   atol=1e-5, rtol=1e-5)


class TestFoldedPoolWireFormat:
    """The pool stores a token's heads folded into the last axis, (P,
    ps, H*Dh); a page on the wire (migration shards, spilled payloads,
    prefix bundles) stays (2, L, ps, H, Dh). Both are the same row-major
    bytes, so payloads and their sha256 digests are what the 4-D pool
    gave. The digests below were recorded from the engine of the parent
    commit (4-D pool) for the same payload."""

    RECORDED = {
        "float32": "bffdbf5b5bd3b915f43ddbed1db74aeb"
                   "5c2815074d0b9d13db97bb5efd5211a2",
        "int8": "73df36a3e4484bce7fae70c5b312922c"
                "8fea986496cd442a07f457bf47efa163",
    }

    @pytest.mark.parametrize("dtype", ["float32", "int8"])
    def test_page_payload_and_digest_are_the_4d_pools(self, dtype):
        model, params = _model()
        eng = serving.ServingEngine(
            model, params, num_slots=2, page_size=4, attn_impl="lax",
            cache_dtype=jnp.int8 if dtype == "int8" else None)
        c = eng.cache.config
        shape = (2, c.num_layers, c.page_size, c.num_heads, c.head_dim)
        ramp = (np.arange(int(np.prod(shape))) * 7) % 251 - 125
        pid = jnp.asarray(3, jnp.int32)
        if eng.quantized:
            kv = ramp.astype(np.int8).reshape(shape)
            sc = ((np.arange(2 * c.num_layers * c.page_size) % 13 + 1)
                  / 16).astype(np.float32).reshape(shape[:3])
            eng.cache.pages = eng.write_page_step(
                eng.cache.pages, pid, jnp.asarray(kv), jnp.asarray(sc))
            page = eng.read_page_step(eng.cache.pages, pid)
            shard = (np.asarray(page[0]), np.asarray(page[1]))
            assert shard[1].tobytes() == sc.tobytes()
            kv_out = shard[0]
        else:
            kv = (ramp / 4).astype(np.float32).reshape(shape)
            eng.cache.pages = eng.write_page_step(
                eng.cache.pages, pid, jnp.asarray(kv))
            shard = kv_out = np.asarray(
                eng.read_page_step(eng.cache.pages, pid))
        # stored: each token row holds its heads one after the other
        for layer, ent in enumerate(eng.cache.pages):
            assert ent[0].shape == (c.num_pages, c.page_size,
                                    c.num_heads * c.head_dim)
            for side in (0, 1):
                np.testing.assert_array_equal(
                    np.asarray(ent[side][3]),
                    kv[side, layer].reshape(c.page_size, -1))
        assert kv_out.shape == shape and kv_out.tobytes() == kv.tobytes()
        assert eng._shard_digest(shard) == self.RECORDED[dtype]

    @pytest.mark.parametrize("impl", ["lax", "pallas_interpret"])
    def test_served_pages_read_back_as_the_unfolded_pool(self, impl):
        """Pages the engine itself wrote (prefill chunks and decode
        tokens): what ``read_page_step`` hands the wire is the stored
        page with its last axis unfolded, and a snapshot's manifest
        digests are those of exactly these arrays."""
        model, params = _model(seed=3)
        eng = serving.ServingEngine(model, params, num_slots=2,
                                    page_size=4, prefill_chunk=8,
                                    attn_impl=impl)
        rng = np.random.default_rng(11)
        for p in _prompts(rng, [10, 7]):
            eng.submit(p, 24)
        eng.step()
        eng.step()
        c = eng.cache.config
        slot = next(s for s in range(2) if eng.cache.lengths[s] > 0)
        n_live = c.pages_for(int(eng.cache.lengths[slot]))
        assert n_live >= 3
        snap = eng.snapshot_slot(slot)
        for k, pid in enumerate(eng.cache.block_tables[slot, :n_live]):
            page = np.asarray(eng.read_page_step(
                eng.cache.pages, jnp.asarray(int(pid), jnp.int32)))
            for layer, (kp, vp) in enumerate(eng.cache.pages):
                for side, pool in enumerate((kp, vp)):
                    np.testing.assert_array_equal(
                        page[side, layer],
                        np.asarray(pool[int(pid)]).reshape(
                            c.page_size, c.num_heads, c.head_dim))
            assert snap["manifest"][k]["sha256"] == eng._shard_digest(page)
            assert page.any()
