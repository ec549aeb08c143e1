"""Hierarchical KV (ISSUE 20): HBM->host spill of published prefix
pages with byte-identical restore (fp + int8), the randomized
two-tier allocator property battery, fleet-global prefix fetch over
hash-chained migration shards, fetch-under-churn degradation (drain /
crash / scale-in / corruption — never a lost or wrong request), and
the stale-affinity generation fix."""

import numpy as np
import jax.numpy as jnp
import pytest

from paddle_tpu import observability as obs
from paddle_tpu import serving
from paddle_tpu.serving import fleet
from paddle_tpu.serving.engine import (PREFIX_BUNDLE_FORMAT,
                                       SlotMigrationError)
from paddle_tpu.serving.fleet.faults import (ChaosReplica, ChaosSpec,
                                             FaultPolicy,
                                             ReplicaUnavailable)
from paddle_tpu.serving.paged_cache import (HostPagePool, SpilledPage,
                                            payload_digest,
                                            prompt_prefix_digests)

VOCAB = 64


@pytest.fixture(scope="module")
def model_params():
    from serving_taps import tiny_gpt
    return tiny_gpt()


def _engine(model_params, **kw):
    model, params = model_params
    kw.setdefault("num_slots", 2)
    kw.setdefault("page_size", 4)
    kw.setdefault("max_tokens_per_slot", 44)
    kw.setdefault("prefill_chunk", 4)
    return serving.ServingEngine(model, params, attn_impl="lax",
                                 registry=obs.MetricsRegistry(), **kw)


def _prefix(seed=1, n=16):
    return np.random.default_rng(seed).integers(1, VOCAB, n).astype(
        np.int32)


def _spill_schedule(eng, prefix, rng):
    """Publish ``prefix``, evict it to the host pool with filler
    pressure, then hit it again. Returns the three generated outputs."""
    outs = []
    p1 = np.concatenate([prefix, rng.integers(1, VOCAB, 3).astype(np.int32)])
    outs.append(eng.generate_many([p1], 6, max_steps=10_000)[0])
    filler = np.arange(1, 33, dtype=np.int32) % (VOCAB - 1) + 1
    outs.append(eng.generate_many([filler], 6, max_steps=10_000)[0])
    p2 = np.concatenate([prefix, rng.integers(1, VOCAB, 2).astype(np.int32)])
    outs.append(eng.generate_many([p2], 6, max_steps=10_000)[0])
    return outs


class TestHostPagePool:
    def _entry(self, key, fill):
        payload = (np.full((2, 1, 4, 2, 4), fill, np.int8),)
        return SpilledPage(key=key, tokens=np.arange(4, dtype=np.int32),
                           payload=payload,
                           sha256=payload_digest(payload),
                           nbytes=payload[0].nbytes)

    def test_capacity_drops_lru(self):
        pool = HostPagePool(2)
        for k in (1, 2, 3):
            pool.put(self._entry(k, k))
        assert pool.keys() == frozenset({2, 3})
        assert pool.dropped_total == 1
        assert pool.spilled_total == 3
        assert len(pool) == 2 <= pool.capacity

    def test_get_refreshes_lru_and_gen_tracks_drops(self):
        pool = HostPagePool(2)
        pool.put(self._entry(1, 1))
        pool.put(self._entry(2, 2))
        g = pool.gen
        assert pool.get(1) is not None      # 1 becomes hot
        pool.put(self._entry(3, 3))         # 2 is the LRU victim
        assert pool.keys() == frozenset({1, 3})
        assert pool.gen > g, "a dropped entry must bump the generation"

    def test_rejects_useless_capacity(self):
        with pytest.raises(ValueError):
            HostPagePool(0)


class TestSpillRestore:
    def test_restore_is_byte_identical(self, model_params):
        eng = _engine(model_params, num_pages=12, host_spill_pages=8)
        eng.warmup()
        rng = np.random.default_rng(0)
        prefix = _prefix()
        p1 = np.concatenate([prefix,
                             rng.integers(1, VOCAB, 3).astype(np.int32)])
        eng.generate_many([p1], 6, max_steps=10_000)
        # golden bytes of every published full prefix page, pre-spill
        golden = {}
        for key, pid in eng.cache._full_index.items():
            if eng.cache._page_pub.get(pid, (None,))[0] == "full":
                golden[key] = tuple(np.asarray(a).copy()
                                    for a in eng._spill_read(pid))
        filler = np.arange(1, 33, dtype=np.int32) % (VOCAB - 1) + 1
        eng.generate_many([filler], 6, max_steps=10_000)
        pool = eng.cache.spill_pool
        assert len(pool) > 0, "pressure did not spill any published page"
        for ent in pool.entries():
            assert payload_digest(ent.payload) == ent.sha256
        p2 = np.concatenate([prefix,
                             rng.integers(1, VOCAB, 2).astype(np.int32)])
        eng.generate_many([p2], 6, max_steps=10_000)
        assert pool.restored_total > 0, "prefix hit restored nothing"
        # restored device content must equal the pre-spill bytes
        checked = 0
        for key, want in golden.items():
            pid = eng.cache._full_index.get(key)
            if pid is None:
                continue
            got = eng._spill_read(pid)
            for w, g in zip(want, got):
                np.testing.assert_array_equal(np.asarray(w),
                                              np.asarray(g))
            checked += 1
        assert checked > 0
        eng.cache.check_invariants()

    @pytest.mark.slow
    @pytest.mark.parametrize("cache_dtype", [None, jnp.int8])
    def test_greedy_bit_identical_and_zero_recompiles(self, model_params,
                                                      cache_dtype):
        prefix = _prefix()
        base = _engine(model_params, num_pages=12, host_spill_pages=0,
                       cache_dtype=cache_dtype)
        base.warmup()
        outs_base = _spill_schedule(base, prefix, np.random.default_rng(0))
        eng = _engine(model_params, num_pages=12, host_spill_pages=8,
                      cache_dtype=cache_dtype)
        eng.warmup()
        outs = _spill_schedule(eng, prefix, np.random.default_rng(0))
        pool = eng.cache.spill_pool
        assert pool.spilled_total > 0 and pool.restored_total > 0
        if cache_dtype is jnp.int8:
            # int8 scale rows travel WITH their pages: two host arrays
            for ent in pool.entries():
                assert len(ent.payload) == 2
        for a, b in zip(outs_base, outs):
            np.testing.assert_array_equal(a, b)
        assert eng.health()["recompiles"] == 0, \
            "spill/restore must ride the warmed page_read/page_write"
        eng.cache.check_invariants()

    @pytest.mark.slow
    def test_spill_headroom_and_gauges(self, model_params):
        eng = _engine(model_params, num_pages=12, host_spill_pages=8)
        eng.warmup()
        assert eng.health()["headroom"]["spill"] == 1.0
        _spill_schedule(eng, _prefix(), np.random.default_rng(0))
        h = eng.health()
        assert 0.0 <= h["headroom"]["spill"] < 1.0
        assert h["headroom"]["spill_pages"] == len(eng.cache.spill_pool)
        assert eng._reg.gauge("serving_spill_pages").value() == \
            len(eng.cache.spill_pool)
        assert eng._reg.counter(
            "serving_spill_restored_pages_total").value() > 0

    def test_disabled_tier_has_no_pool(self, model_params):
        eng = _engine(model_params, num_pages=12)
        assert eng.cache.spill_pool is None
        assert eng.health()["headroom"]["spill"] == 1.0


class TestHierarchyProperty:
    pytestmark = pytest.mark.slow  # excluded from the quick CI gate

    @pytest.mark.parametrize("seed", [0, 1, 2])
    @pytest.mark.parametrize("cache_dtype", [None, jnp.int8])
    def test_randomized_two_tier_schedule(self, model_params, seed,
                                          cache_dtype):
        """Interleaved admit/publish/spill/restore/CoW/free under a
        tiny page pool hold the allocator invariants (which now include
        host-pool sha verification and device/host disjointness) and
        produce exactly the no-spill engine's greedy tokens."""
        rng = np.random.default_rng(seed)
        prefixes = [rng.integers(1, VOCAB, n).astype(np.int32)
                    for n in (8, 12, 16)]
        prompts = []
        for _ in range(12):
            roll = rng.random()
            if roll < 0.7:      # shared-prefix traffic (publish + CoW)
                pre = prefixes[rng.integers(len(prefixes))]
                tail = rng.integers(1, VOCAB,
                                    rng.integers(1, 5)).astype(np.int32)
                prompts.append(np.concatenate([pre, tail]))
            else:               # unique filler (eviction pressure)
                prompts.append(rng.integers(1, VOCAB, 24).astype(np.int32))
        want, got = [], []
        for spill in (0, 6):
            eng = _engine(model_params, num_pages=14,
                          host_spill_pages=spill, cache_dtype=cache_dtype)
            eng.warmup()
            outs = want if spill == 0 else got
            for i in range(0, len(prompts), 3):
                outs.extend(eng.generate_many(prompts[i:i + 3], 5,
                                              max_steps=10_000))
                eng.cache.check_invariants()
            if spill:
                assert eng.cache.spill_pool.spilled_total > 0
                assert eng.health()["recompiles"] == 0
        for a, b in zip(want, got):
            np.testing.assert_array_equal(a, b)


def _fleet(model_params, n, prefix_fetch=True, faults=None, chaos=None,
           **kw):
    kw.setdefault("num_pages", 24)
    kw.setdefault("host_spill_pages", 8)
    reps = []
    for i in range(n):
        r = fleet.LocalReplica(_engine(model_params, **kw),
                               name=f"r{i}").warmup()
        if chaos and i in chaos:
            r = ChaosReplica(r, chaos[i])
        reps.append(r)
    router = fleet.FleetRouter(reps, registry=obs.MetricsRegistry(),
                               tracer=obs.Tracer(enabled=False),
                               prefix_fetch=prefix_fetch, faults=faults)
    return router, reps


def _publish_and_drain(router, reps, prefix, rng):
    """Run one shared-prefix request, then drain whichever replica
    published the pages — the next same-prefix submit MUST route
    elsewhere. Returns (first_output, holder, miss_target)."""
    p1 = np.concatenate([prefix, rng.integers(1, VOCAB, 3).astype(np.int32)])
    f1 = router.submit(p1, 6)
    out1 = router.run_until_idle(2_000)[f1]
    holder = next(r for r in reps if r.prefix_digests())
    holder.draining = True
    other = next(r for r in reps if r is not holder)
    return out1, holder, other


class TestFleetPrefixFetch:
    @pytest.mark.slow
    def test_miss_fetches_from_holder_bit_identical(self, model_params):
        prefix = _prefix()
        outs = {}
        for pf in (False, True):
            rng = np.random.default_rng(0)
            router, reps = _fleet(model_params, 2, prefix_fetch=pf)
            out1, holder, other = _publish_and_drain(router, reps,
                                                     prefix, rng)
            p2 = np.concatenate([prefix,
                                 rng.integers(1, VOCAB, 2).astype(np.int32)])
            f2 = router.submit(p2, 6)
            out2 = router.run_until_idle(2_000)[f2]
            outs[pf] = (out1, out2)
            reg = router._reg
            fetched = reg.counter("fleet_prefix_fetch_pages_total").value()
            shared = other.engine._reg.counter(
                "serving_prefix_shared_tokens_total").value()
            if pf:
                assert fetched > 0, "miss did not fetch from the holder"
                assert shared >= 4 * fetched
                assert reg.counter("fleet_prefix_fetch_total").value(
                    src=holder.name, dst=other.name) == 1
                assert reg.counter(
                    "fleet_prefix_fetch_bytes_total").value() > 0
            else:
                assert fetched == 0 and shared == 0
            for r in reps:
                r.engine.cache.check_invariants()
                assert r.engine.health()["recompiles"] == 0
        for a, b in zip(outs[False], outs[True]):
            np.testing.assert_array_equal(a, b)

    def test_engine_export_import_roundtrip(self, model_params):
        src = _engine(model_params, num_pages=24, host_spill_pages=8)
        dst = _engine(model_params, num_pages=24, host_spill_pages=8)
        src.warmup(), dst.warmup()
        prefix = _prefix()
        rng = np.random.default_rng(0)
        p1 = np.concatenate([prefix,
                             rng.integers(1, VOCAB, 3).astype(np.int32)])
        src.generate_many([p1], 6, max_steps=10_000)
        digests = prompt_prefix_digests(p1, 4)
        bundle = src.export_prefix_pages(digests)
        assert bundle["format"] == PREFIX_BUNDLE_FORMAT
        assert len(bundle["pages"]) == len(digests)
        assert dst.import_prefix_pages(bundle) == len(digests)
        # idempotent: everything already held installs nothing
        assert dst.import_prefix_pages(bundle) == 0
        assert set(digests) <= dst.cache.advertised_digests()
        dst.cache.check_invariants()
        out_dst = dst.generate_many([p1], 6, max_steps=10_000)[0]
        out_src = src.generate_many([p1], 6, max_steps=10_000)[0]
        np.testing.assert_array_equal(out_src, out_dst)
        assert dst._reg.counter(
            "serving_prefix_shared_tokens_total").value() >= 16

    @pytest.mark.slow
    def test_export_covers_spilled_pages(self, model_params):
        """A host-spilled page is still exportable — the whole point of
        advertising the spill tier fleet-wide."""
        src = _engine(model_params, num_pages=12, host_spill_pages=8)
        src.warmup()
        prefix = _prefix()
        _spill_schedule(src, prefix, np.random.default_rng(0))
        digests = prompt_prefix_digests(prefix, 4)
        spilled = src.cache.spill_pool.keys()
        assert spilled, "schedule did not leave spilled pages"
        bundle = src.export_prefix_pages(digests)
        assert bundle is not None
        assert {int(p["key"]) for p in bundle["pages"]} >= set(
            d for d in digests if d in spilled)

    @pytest.mark.slow
    def test_holder_crash_mid_fetch_degrades(self, model_params):
        prefix = _prefix()
        rng = np.random.default_rng(0)
        chaos = None
        router, reps = _fleet(model_params, 2,
                              faults=FaultPolicy())
        out1, holder, other = _publish_and_drain(router, reps, prefix, rng)
        # the holder dies exactly when the fetch reaches for its pages
        idx = reps.index(holder)
        reps[idx] = ChaosReplica(holder, ChaosSpec(crash_on_export=True))
        reps[idx].draining = True   # the wrapper must stay draining too
        router.replicas[router.replicas.index(holder)] = reps[idx]
        p2 = np.concatenate([prefix,
                             rng.integers(1, VOCAB, 2).astype(np.int32)])
        f2 = router.submit(p2, 6)
        out2 = router.run_until_idle(2_000).get(f2)
        assert out2 is not None, "request lost to a mid-fetch crash"
        reg = router._reg
        assert reg.counter("fleet_prefix_fetch_failed_total").value(
            reason="transport") >= 1
        assert reg.counter(
            "fleet_prefix_fetch_degraded_total").value() >= 1
        assert reg.counter("fleet_prefix_fetch_pages_total").value() == 0
        # degraded = local re-prefill: bit-identical to a no-fetch fleet
        rng = np.random.default_rng(0)
        router2, reps2 = _fleet(model_params, 2, prefix_fetch=False)
        ref1, _h, _o = _publish_and_drain(router2, reps2, prefix, rng)
        p2r = np.concatenate([prefix,
                              rng.integers(1, VOCAB, 2).astype(np.int32)])
        fr2 = router2.submit(p2r, 6)
        ref2 = router2.run_until_idle(2_000)[fr2]
        np.testing.assert_array_equal(out1, ref1)
        np.testing.assert_array_equal(out2, ref2)

    @pytest.mark.slow
    def test_holder_scaled_in_mid_fetch_degrades(self, model_params):
        """The holder advertises, then vanishes (autoscaler scale-in)
        before the export lands: the fetch degrades with a structured
        marker and the request re-prefills locally."""
        prefix = _prefix()
        rng = np.random.default_rng(0)
        router, reps = _fleet(model_params, 2)
        out1, holder, other = _publish_and_drain(router, reps, prefix, rng)

        real_export = holder.export_prefix_pages

        def vanished(digests):
            raise ReplicaUnavailable("chaos: scaled in mid-fetch")

        holder.export_prefix_pages = vanished
        p2 = np.concatenate([prefix,
                             rng.integers(1, VOCAB, 2).astype(np.int32)])
        f2 = router.submit(p2, 6)
        out2 = router.run_until_idle(2_000).get(f2)
        assert out2 is not None
        reg = router._reg
        assert reg.counter(
            "fleet_prefix_fetch_degraded_total").value() >= 1
        assert reg.counter("fleet_prefix_fetch_pages_total").value() == 0
        holder.export_prefix_pages = real_export
        for r in reps:
            r.engine.cache.check_invariants()

    @pytest.mark.slow
    def test_corrupt_bundle_refused_not_installed(self, model_params):
        prefix = _prefix()
        rng = np.random.default_rng(0)
        router, reps = _fleet(model_params, 2)
        out1, holder, other = _publish_and_drain(router, reps, prefix, rng)

        real_export = holder.export_prefix_pages

        def tampered(digests):
            bundle = real_export(digests)
            shard = bundle["pages"][0]["shards"][0]
            kv = np.asarray(shard[0] if isinstance(shard, tuple)
                            else shard).copy()
            kv.view(np.uint8).flat[0] ^= 1  # one bit of KV rot
            if isinstance(shard, tuple):
                bundle["pages"][0]["shards"][0] = (kv, shard[1])
            else:
                bundle["pages"][0]["shards"][0] = kv
            return bundle

        holder.export_prefix_pages = tampered
        p2 = np.concatenate([prefix,
                             rng.integers(1, VOCAB, 2).astype(np.int32)])
        f2 = router.submit(p2, 6)
        out2 = router.run_until_idle(2_000).get(f2)
        assert out2 is not None, "request lost to a corrupt bundle"
        reg = router._reg
        assert reg.counter(
            "fleet_prefix_fetch_refused_total").value() == 1
        assert reg.counter(
            "fleet_prefix_fetch_degraded_total").value() >= 1
        # a refused bundle installs NOTHING via the fetch path (the
        # pages now advertised were published by serving p2 locally)
        assert reg.counter("fleet_prefix_fetch_pages_total").value() == 0
        other.engine.cache.check_invariants()
        holder.export_prefix_pages = real_export

    @pytest.mark.slow
    def test_unprovable_chain_refused(self, model_params):
        """A bundle whose keys do not hash-chain over its own token
        content is refused outright — shard hashes alone do not make
        pages trustworthy as PUBLISHED prefix state."""
        src = _engine(model_params, num_pages=24, host_spill_pages=8)
        dst = _engine(model_params, num_pages=24, host_spill_pages=8)
        src.warmup(), dst.warmup()
        prefix = _prefix()
        src.generate_many([np.concatenate([prefix, prefix[:3]])], 6,
                          max_steps=10_000)
        bundle = src.export_prefix_pages(prompt_prefix_digests(prefix, 4))
        bundle["pages"][0]["key"] = int(bundle["pages"][0]["key"]) ^ 1
        with pytest.raises(SlotMigrationError):
            dst.import_prefix_pages(bundle)
        dst.cache.check_invariants()

    @pytest.mark.slow
    def test_import_never_evicts_published_pages(self, model_params):
        """All-or-nothing capacity: a bundle larger than the idle free
        pool is refused instead of evicting local published pages."""
        src = _engine(model_params, num_pages=24, host_spill_pages=8)
        dst = _engine(model_params, num_pages=6, host_spill_pages=8)
        src.warmup(), dst.warmup()
        prefix = _prefix()
        src.generate_many([np.concatenate([prefix, prefix[:3]])], 6,
                          max_steps=10_000)
        bundle = src.export_prefix_pages(prompt_prefix_digests(prefix, 4))
        need = len(bundle["pages"])
        assert need > dst.cache.idle_free_pages or need > 0
        if need > dst.cache.idle_free_pages:
            with pytest.raises(SlotMigrationError):
                dst.import_prefix_pages(bundle)
            dst.cache.check_invariants()


class TestStaleAffinity:
    pytestmark = pytest.mark.slow  # excluded from the quick CI gate

    def test_prefix_gen_bumps_through_health(self, model_params):
        eng = _engine(model_params, num_pages=12, host_spill_pages=2)
        eng.warmup()
        rng = np.random.default_rng(0)
        prefix = _prefix()
        p1 = np.concatenate([prefix,
                             rng.integers(1, VOCAB, 3).astype(np.int32)])
        eng.generate_many([p1], 6, max_steps=10_000)
        rep = fleet.LocalReplica(eng, name="r0")
        g0 = rep.health()["prefix_gen"]
        adv0 = rep.prefix_digests()
        # pressure: published pages spill (pool holds 2, rest DROP)
        filler = np.arange(1, 33, dtype=np.int32) % (VOCAB - 1) + 1
        eng.generate_many([filler], 6, max_steps=10_000)
        g1 = rep.health()["prefix_gen"]
        assert g1 > g0, \
            "eviction/spill of a published page must bump prefix_gen"
        dropped = eng.cache.spill_pool.dropped_total
        assert dropped > 0, "tiny pool should have dropped spilled pages"
        # the filler published pages of its own, so compare what LEFT:
        # at least one of p1's advertised pages must be gone for good
        assert adv0 - rep.prefix_digests(), \
            "dropped pages must leave the advertisement"

    def test_affinity_miss_counter_on_stale_view(self, model_params):
        """A replica advertising pages it no longer holds gets the
        affinity route AND the miss counted — the regression signal the
        generation plumbing keeps at zero."""
        rng = np.random.default_rng(0)
        router, reps = _fleet(model_params, 2, prefix_fetch=False,
                              host_spill_pages=0, num_pages=12)
        prefix = _prefix()
        out1, holder, other = _publish_and_drain(router, reps, prefix, rng)
        holder.draining = False
        stale = holder.prefix_digests()
        assert stale
        # silently destroy the holder's pages, then freeze its
        # advertisement at the pre-eviction view
        filler = np.arange(1, 33, dtype=np.int32) % (VOCAB - 1) + 1
        holder.engine.generate_many([filler], 6, max_steps=10_000)
        assert not (set(stale) & holder.engine.cache.advertised_digests())
        holder.prefix_digests = lambda: stale
        p2 = np.concatenate([prefix,
                             rng.integers(1, VOCAB, 2).astype(np.int32)])
        f2 = router.submit(p2, 6)
        assert router.run_until_idle(2_000).get(f2) is not None
        assert router._reg.counter(
            "fleet_affinity_miss_total").value() == 1

    def test_no_miss_when_generation_propagates(self, model_params):
        """With live advertisements (the fix), the same eviction story
        routes by balance instead and the miss counter stays zero."""
        rng = np.random.default_rng(0)
        router, reps = _fleet(model_params, 2, prefix_fetch=False,
                              host_spill_pages=0, num_pages=12)
        prefix = _prefix()
        out1, holder, other = _publish_and_drain(router, reps, prefix, rng)
        holder.draining = False
        filler = np.arange(1, 33, dtype=np.int32) % (VOCAB - 1) + 1
        holder.engine.generate_many([filler], 6, max_steps=10_000)
        p2 = np.concatenate([prefix,
                             rng.integers(1, VOCAB, 2).astype(np.int32)])
        f2 = router.submit(p2, 6)
        assert router.run_until_idle(2_000).get(f2) is not None
        assert router._reg.counter(
            "fleet_affinity_miss_total").value() == 0
