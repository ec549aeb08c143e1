"""Online embedding serving tests (ISSUE 7): device hot-row cache over
host-KV backing, streaming trainer pushes, staleness bounds, load
shedding, persistence, and the zero-steady-state-recompile invariant.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import embedding_serving as es
from paddle_tpu import observability as obs
from paddle_tpu.models.deepfm import DeepFMHostKV
from paddle_tpu.parallel.host_kv import HostKVStore


def _store(dim=4, **kw):
    kw.setdefault("optimizer", "sgd")
    kw.setdefault("init_scale", 0.1)
    kw.setdefault("seed", 0)
    return HostKVStore(dim, **kw)


class TestDeviceEmbeddingCache:
    def test_install_gather_roundtrip(self):
        reg = obs.MetricsRegistry()
        c = es.DeviceEmbeddingCache(8, 3, min_gather_bucket=4,
                                    min_install_bucket=2, registry=reg)
        ids = np.array([10, 20, 30], np.int64)
        rows = np.arange(9, dtype=np.float32).reshape(3, 3)
        c.install(ids, rows)
        got = np.asarray(c.gather(ids))
        assert got.shape == (4, 3)          # pow2 bucket
        np.testing.assert_allclose(got[:3], rows)
        c.check_invariants()

    def test_refresh_reuses_slot(self):
        c = es.DeviceEmbeddingCache(4, 2, min_gather_bucket=2,
                                    registry=obs.MetricsRegistry())
        c.install(np.array([7]), np.ones((1, 2), np.float32))
        slot = c._slot_of[7]
        c.install(np.array([7]), np.full((1, 2), 9.0, np.float32))
        assert c._slot_of[7] == slot        # refreshed in place
        np.testing.assert_allclose(np.asarray(c.gather(np.array([7])))[0],
                                   9.0)
        c.check_invariants()

    def test_lru_evicts_least_recently_served(self):
        c = es.DeviceEmbeddingCache(3, 2, policy="lru",
                                    min_gather_bucket=2,
                                    registry=obs.MetricsRegistry())
        for i in (1, 2, 3):
            c.install(np.array([i]),
                      np.full((1, 2), float(i), np.float32))
        c.gather(np.array([1]))             # 1 becomes MRU
        c.install(np.array([4]), np.full((1, 2), 4.0, np.float32))
        assert not c.resident(2)            # oldest unserved went
        assert c.resident(1) and c.resident(3) and c.resident(4)
        c.check_invariants()

    def test_lfu_evicts_least_frequent(self):
        c = es.DeviceEmbeddingCache(3, 2, policy="lfu",
                                    min_gather_bucket=2,
                                    registry=obs.MetricsRegistry())
        for i in (1, 2, 3):
            c.install(np.array([i]),
                      np.full((1, 2), float(i), np.float32))
        for _ in range(3):
            c.gather(np.array([1, 3]))      # 2 stays at freq 0
        c.install(np.array([4]), np.full((1, 2), 4.0, np.float32))
        assert not c.resident(2)
        c.check_invariants()

    def test_protect_set_never_evicted(self):
        c = es.DeviceEmbeddingCache(2, 2, min_gather_bucket=2,
                                    registry=obs.MetricsRegistry())
        c.install(np.array([1, 2]), np.zeros((2, 2), np.float32))
        with pytest.raises(es.CacheCapacityError):
            c.install(np.array([3]), np.zeros((1, 2), np.float32),
                      protect=[1, 2, 3])
        c.check_invariants()

    def test_capacity_exceeded_raises(self):
        c = es.DeviceEmbeddingCache(2, 2, min_gather_bucket=2,
                                    registry=obs.MetricsRegistry())
        with pytest.raises(es.CacheCapacityError):
            c.install(np.arange(3, dtype=np.int64),
                      np.zeros((3, 2), np.float32))

    def test_stale_version_counts_as_miss(self):
        c = es.DeviceEmbeddingCache(4, 2, min_gather_bucket=2,
                                    registry=obs.MetricsRegistry())
        c.install(np.array([5]), np.ones((1, 2), np.float32),
                  versions={5: 1})
        hit, miss = c.split(np.array([5]), {5: 1})
        assert hit.all() and miss.size == 0
        hit, miss = c.split(np.array([5]), {5: 2})
        assert not hit.any() and list(miss) == [5]

    def test_zero_recompiles_after_warmup(self):
        reg = obs.MetricsRegistry()
        c = es.DeviceEmbeddingCache(64, 3, min_gather_bucket=4,
                                    min_install_bucket=4, registry=reg)
        c.warmup(32)
        det = obs.RecompileDetector("cache_warm", warmup=0, registry=reg)
        rng = np.random.default_rng(0)
        for n in (1, 3, 4, 7, 12, 29, 32):
            ids = rng.choice(10_000, size=n, replace=False).astype(np.int64)
            c.install(ids, rng.normal(size=(n, 3)).astype(np.float32))
            c.gather(ids)
        det.check()
        assert det.recompiles == 0
        c.check_invariants()

    def test_non_pow2_capacity_zero_recompiles(self):
        """A non-pow2 capacity must not mint a serve-time bucket width
        warmup never compiled: _pow2_bucket used to clamp to the raw
        capacity (100), so a 70-uniq batch gathered at width 100 while
        warmup compiled 64 and 128 — first steady-state serve
        retraced."""
        reg = obs.MetricsRegistry()
        c = es.DeviceEmbeddingCache(100, 3, min_gather_bucket=64,
                                    min_install_bucket=64, registry=reg)
        c.warmup(100)
        det = obs.RecompileDetector("cache_np2", warmup=0, registry=reg)
        rng = np.random.default_rng(1)
        for n in (70, 100, 65, 96):          # all between 64 and 100
            ids = rng.choice(10_000, size=n, replace=False).astype(np.int64)
            c.install(ids, rng.normal(size=(n, 3)).astype(np.float32))
            c.gather(ids)
        det.check()
        assert det.recompiles == 0
        c.check_invariants()


class TestRandomizedIdStream:
    """The cache-correctness property test: a randomized zipf-ish id
    stream with interleaved streaming pushes; after every served batch,
    each served row must equal the backing store's row as of the
    batch's submit (the staleness bound with a drained channel), slot
    index invariants must hold, and evicted-then-readmitted ids must
    serve fresh rows, never garbage."""

    def test_served_rows_match_store_within_bound(self):
        store = _store(dim=3)
        reg = obs.MetricsRegistry()
        ch = es.StreamingUpdateChannel(store, registry=reg)
        eng = es.EmbeddingServingEngine(
            store, capacity=32, min_bucket=8, channel=ch,
            max_lag_updates=0, registry=reg)
        rng = np.random.default_rng(42)
        for step in range(30):
            if step % 3 == 1:       # trainer pushes fresh values
                ids = rng.choice(40, size=4, replace=False)
                ch.push_rows(ids.astype(np.int64),
                             rng.normal(size=(4, 3)).astype(np.float32))
            # max_lag_updates=0 forces the staleness gate to drain the
            # channel at submit, so "within the bound" == exact match
            # against the store at submit time
            hot = rng.integers(0, 8, size=(3, 2))
            tail = rng.integers(8, 60, size=(3, 2))
            ids = np.where(rng.random((3, 2)) < 0.7, hot, tail)
            served = eng.serve(ids.astype(np.int64))
            uniq = np.unique(ids)
            expect = store.pull(uniq)
            np.testing.assert_allclose(served[:uniq.size], expect,
                                       rtol=1e-6, atol=1e-7,
                                       err_msg=f"step {step}")
            eng.cache.check_invariants()
        assert reg.counter("embedding_cache_evictions_total").value() > 0
        ch.stop()

    def test_eviction_never_serves_garbage(self):
        # capacity 4 with an 8-id working set: every batch churns slots;
        # a bad slot-reuse path would serve another id's row
        store = _store(dim=2)
        eng = es.EmbeddingServingEngine(store, capacity=4, min_bucket=4,
                                        registry=obs.MetricsRegistry())
        rng = np.random.default_rng(7)
        for _ in range(40):
            ids = np.sort(rng.choice(8, size=3, replace=False)
                          ).astype(np.int64)      # uniq order == sorted
            served = eng.serve(ids.reshape(1, 3))
            np.testing.assert_allclose(served[:3], store.pull(ids),
                                       rtol=1e-6)
            eng.cache.check_invariants()


class TestStreamingUpdates:
    def test_pushed_row_served_within_one_lookup(self):
        """The acceptance bound: a row pushed through the channel is
        served (cache refreshed) by the next lookup after the push
        applies."""
        store = _store(dim=3)
        reg = obs.MetricsRegistry()
        ch = es.StreamingUpdateChannel(store, registry=reg)
        eng = es.EmbeddingServingEngine(store, capacity=16, min_bucket=4,
                                        channel=ch, registry=reg)
        ids = np.array([[1, 2, 3]], np.int64)
        eng.serve(ids)                       # row 2 now cached
        new = np.array([[0.5, -1.0, 2.0]], np.float32)
        ch.push_rows(np.array([2]), new)
        ch.flush()                           # update applied to store
        served = eng.serve(ids)              # N = 1 lookup later
        np.testing.assert_allclose(served[1], new[0], rtol=1e-6)
        assert ch.version_of(2) == 1
        ch.stop()

    def test_staleness_bound_forces_drain(self):
        """With the bound at 0 lag-updates, a pending (unapplied) push
        cannot be outrun: submit flushes the channel first, so the
        served row ALWAYS reflects the push."""
        store = _store(dim=2)
        reg = obs.MetricsRegistry()
        ch = es.StreamingUpdateChannel(store, registry=reg)
        eng = es.EmbeddingServingEngine(store, capacity=8, min_bucket=2,
                                        channel=ch, max_lag_updates=0,
                                        registry=reg)
        eng.serve(np.array([[4]], np.int64))
        ch.push_rows(np.array([4]), np.full((1, 2), 3.5, np.float32))
        served = eng.serve(np.array([[4]], np.int64))   # no flush() call
        np.testing.assert_allclose(served[0], 3.5)
        ch.stop()

    def test_pushed_row_served_under_pipelined_load(self):
        """The staleness bound must hold for an id continuously
        referenced by in-flight batches: its slot cannot be freed
        (pending batches are about to gather it), so the gate records a
        version requirement and the next submit reclassifies it as a
        miss. A keep-deferral design kept such hot ids dirty forever —
        stale rows served indefinitely under pipelined load."""
        store = _store(dim=2)
        reg = obs.MetricsRegistry()
        ch = es.StreamingUpdateChannel(store, registry=reg)
        eng = es.EmbeddingServingEngine(store, capacity=16, min_bucket=2,
                                        max_pending=3, channel=ch,
                                        registry=reg)
        eng.serve(np.array([[7, 1]], np.int64))     # row 7 cached
        # two in-flight batches pin id 7 (no step between submits)
        eng.submit(np.array([[7, 2]], np.int64))
        eng.submit(np.array([[7, 3]], np.int64))
        ch.push_rows(np.array([7]), np.full((1, 2), 9.25, np.float32))
        ch.flush()                                  # applied; 7 dirty
        rid = eng.submit(np.array([[7, 4]], np.int64))
        assert eng._stale_req.get(7) == 1           # pinned, not freed
        out = {}
        while eng.pending():
            out.update(eng.step())
        got = out[rid]                              # (U_pad, dim) rows
        uniq = np.unique(np.array([7, 4]))
        np.testing.assert_allclose(
            got[list(uniq).index(7)], 9.25)         # fresh, not stale
        assert not eng._stale_req                   # requirement settled
        # and once nothing pins it, a plain hit serves the fresh row
        np.testing.assert_allclose(
            eng.serve(np.array([[7]], np.int64))[0], 9.25)
        eng.cache.check_invariants()
        ch.stop()

    def test_grad_push_applies_store_optimizer(self):
        store = _store(dim=2, optimizer="sgd", init_scale=0.0)
        ch = es.StreamingUpdateChannel(store,
                                       registry=obs.MetricsRegistry())
        g = np.ones((1, 2), np.float32)
        ch.push_grads(np.array([9]), g, lr=0.5)
        ch.flush()
        np.testing.assert_allclose(store.pull(np.array([9])), -0.5)
        assert ch.version_of(9) == 1
        ch.stop()

    def test_merge_last_writer_wins(self):
        store = _store(dim=2)
        ch = es.StreamingUpdateChannel(store, max_merge=8,
                                       registry=obs.MetricsRegistry())
        for v in (1.0, 2.0, 3.0):
            ch.push_rows(np.array([5]), np.full((1, 2), v, np.float32))
        ch.flush()
        np.testing.assert_allclose(store.pull(np.array([5])), 3.0)
        ch.stop()

    def test_worker_error_surfaces_at_flush(self):
        store = _store(dim=2)
        ch = es.StreamingUpdateChannel(store,
                                       registry=obs.MetricsRegistry())
        vals = np.zeros((1, 2), np.float32)
        ch.push_rows(np.array([1]), vals)
        ch.flush()
        store.close()            # dead backing store: applies now fail
        ch.push_rows(np.array([2]), vals)
        with pytest.raises(RuntimeError, match="streaming update"):
            ch.flush()           # worker error re-raised, not swallowed

    def test_lag_observability(self):
        store = _store(dim=2)
        ch = es.StreamingUpdateChannel(store,
                                       registry=obs.MetricsRegistry())
        assert ch.lag_seconds() == 0.0 and ch.lag_updates() == 0
        ch.push_rows(np.array([1]), np.zeros((1, 2), np.float32))
        ch.flush()
        assert ch.lag_seconds() == 0.0 and ch.lag_updates() == 0
        ch.stop()


class TestEngineServing:
    def _model(self, fields=3, dim=4):
        model = DeepFMHostKV(num_fields=fields, embed_dim=dim,
                             hidden=(8,))
        return model, model.init(jax.random.PRNGKey(0))

    def test_deepfm_forward_matches_direct(self):
        model, params = self._model()
        store = _store(dim=5)               # 1 + embed_dim
        eng = es.EmbeddingServingEngine(store, model, params,
                                        capacity=32, min_bucket=8,
                                        registry=obs.MetricsRegistry())
        ids = np.array([[3, 7, 7], [9, 3, 1]], np.int64)
        probs = eng.serve(ids)
        uniq, inv = np.unique(ids, return_inverse=True)
        rows = store.pull(uniq)
        pad = np.zeros((8, 5), np.float32)
        pad[:uniq.size] = rows
        expect = np.asarray(model.predict_proba(
            params, jnp.asarray(pad),
            jnp.asarray(inv.reshape(ids.shape).astype(np.int32))))
        np.testing.assert_allclose(probs, expect, rtol=1e-5)

    def test_pipeline_overlap_and_results(self):
        model, params = self._model()
        store = _store(dim=5)
        eng = es.EmbeddingServingEngine(store, model, params,
                                        capacity=64, min_bucket=8,
                                        max_pending=3,
                                        registry=obs.MetricsRegistry())
        rng = np.random.default_rng(0)
        rids = [eng.submit(rng.integers(0, 100, size=(2, 3)))
                for _ in range(3)]
        outs = {}
        while eng.pending():
            outs.update(eng.step())
        assert sorted(outs) == sorted(rids)
        for r in rids:
            got = eng.result(r)
            assert got is not None and got.shape == (2,)
            assert eng.result(r) is None     # pop-on-read

    def test_load_shed_structured(self):
        store = _store(dim=2)
        eng = es.EmbeddingServingEngine(store, capacity=16, min_bucket=2,
                                        max_pending=2,
                                        registry=obs.MetricsRegistry())
        eng.submit(np.array([[1]], np.int64))
        eng.submit(np.array([[2]], np.int64))
        with pytest.raises(es.EmbeddingLoadShedError) as ei:
            eng.submit(np.array([[3]], np.int64))
        rej = ei.value.reject
        assert rej.reason == "miss_queue_full"
        assert rej.queue_depth == 2
        assert rej.retry_after_s > 0
        while eng.pending():                 # drain unblocks submits
            eng.step()
        assert eng.submit(np.array([[3]], np.int64)) > 0
        eng.step()

    def test_capacity_pressure_degrades_not_crashes(self):
        """When the aggregate in-flight working set outgrows the table,
        step() must degrade (protect only its own batch, later batches
        re-pull evicted rows synchronously) — never crash the popped
        batch with CacheCapacityError or a gather KeyError."""
        store = _store(dim=2)
        eng = es.EmbeddingServingEngine(store, capacity=8, min_bucket=2,
                                        max_pending=2,
                                        registry=obs.MetricsRegistry())
        eng.serve(np.arange(10, 18, dtype=np.int64).reshape(1, 8))
        assert len(eng.cache) == 8                  # table full
        r1 = eng.submit(np.arange(0, 7, dtype=np.int64).reshape(1, 7))
        r2 = eng.submit(np.arange(10, 17, dtype=np.int64).reshape(1, 7))
        # r1's install wants 7 fresh slots but r1∪r2 protects 14 ids on
        # an 8-slot table; r2's hit-classified rows then get evicted
        out = {}
        while eng.pending():
            out.update(eng.step())
        for rid, ids in ((r1, np.arange(0, 7)), (r2, np.arange(10, 17))):
            np.testing.assert_allclose(
                out[rid][:7], store.pull(ids.astype(np.int64)),
                rtol=1e-6)
        eng.cache.check_invariants()

    def test_zero_steady_state_recompiles(self):
        """The acceptance invariant: after warmup, a steady serving
        loop (varying batches, misses, evictions, streaming refreshes)
        compiles nothing."""
        model, params = self._model(fields=4, dim=4)
        store = _store(dim=5)
        reg = obs.MetricsRegistry()
        ch = es.StreamingUpdateChannel(store, registry=reg)
        eng = es.EmbeddingServingEngine(store, model, params,
                                        capacity=64, min_bucket=8,
                                        channel=ch, max_lag_updates=0,
                                        registry=reg)
        eng.warmup((4, 4))
        det = obs.RecompileDetector("embed_steady", warmup=0,
                                    registry=reg)
        rng = np.random.default_rng(3)
        for i in range(12):
            if i % 4 == 2:
                ch.push_rows(rng.choice(200, 3, replace=False)
                             .astype(np.int64),
                             rng.normal(size=(3, 5)).astype(np.float32))
            eng.serve(rng.integers(0, 200, size=(4, 4)))
        det.check()
        assert det.recompiles == 0
        assert reg.gauge("embedding_serving_hit_rate").value() > 0
        ch.stop()

    def test_facade(self):
        from paddle_tpu import inference
        model, params = self._model()
        store = _store(dim=5)
        eng = inference.make_embedding_serving_engine(
            store, model, params, capacity=16, min_bucket=4,
            registry=obs.MetricsRegistry())
        assert isinstance(eng, es.EmbeddingServingEngine)
        assert eng.serve(np.array([[1, 2, 3]], np.int64)).shape == (1,)


class TestPersistence:
    def test_snapshot_restore_roundtrip(self, tmp_path):
        store = _store(dim=3)
        reg = obs.MetricsRegistry()
        ch = es.StreamingUpdateChannel(store, registry=reg)
        eng = es.EmbeddingServingEngine(store, capacity=8, min_bucket=2,
                                        channel=ch, registry=reg)
        eng.serve(np.array([[1, 2]], np.int64))
        ch.push_rows(np.array([2]), np.full((1, 3), 7.0, np.float32))
        ch.flush()
        d = os.path.join(tmp_path, "snaps")
        eng.snapshot(d, step=5)
        assert es.committed_steps(d) == [5]

        store2 = _store(dim=3, seed=99)
        ch2 = es.StreamingUpdateChannel(store2,
                                        registry=obs.MetricsRegistry())
        eng2 = es.EmbeddingServingEngine(store2, capacity=8,
                                         min_bucket=2, channel=ch2,
                                         registry=obs.MetricsRegistry())
        eng2.restore(d)
        ids = np.array([1, 2], np.int64)
        np.testing.assert_allclose(store2.pull(ids), store.pull(ids))
        assert ch2.version_of(2) == 1       # counters restored
        ch.stop(), ch2.stop()

    def test_torn_save_invisible_corrupt_refused(self, tmp_path):
        store = _store(dim=2)
        d = os.path.join(tmp_path, "s")
        es.save_kv_snapshot(store, d, 1)
        # torn save: payload without a manifest is invisible
        torn = os.path.join(d, "step_00000002")
        os.makedirs(torn)
        with open(os.path.join(torn, "table.kv"), "wb") as f:
            f.write(b"half a save")
        assert es.latest_valid_step(d) == 1
        # bit rot under a committed manifest: refused, falls back
        es.save_kv_snapshot(store, d, 3)
        with open(os.path.join(d, "step_00000003", "table.kv"),
                  "r+b") as f:
            f.seek(0)
            f.write(b"\xff\xff\xff\xff")
        assert es.latest_valid_step(d) == 1
        from paddle_tpu.resilience import SnapshotCorruptionError
        with pytest.raises(SnapshotCorruptionError):
            es.restore_kv_snapshot(_store(dim=2), d, step=3)

    def test_dim_mismatch_refused(self, tmp_path):
        d = os.path.join(tmp_path, "s")
        es.save_kv_snapshot(_store(dim=3), d, 1)
        from paddle_tpu.resilience import SnapshotCorruptionError
        with pytest.raises(SnapshotCorruptionError, match="dim"):
            es.restore_kv_snapshot(_store(dim=4), d)


class TestTeardownHardening:
    """ISSUE 7 satellite: KV teardown must be idempotent and must not
    spew AttributeErrors at interpreter exit when the native library
    failed to load."""

    def test_close_idempotent(self):
        s = _store(dim=2)
        s.push(np.array([1], np.int64), np.ones((1, 2), np.float32),
               lr=1.0, wait=False)
        s.close()
        s.close()                            # second close is a no-op
        s.__del__()                          # and so is del-after-close

    def test_del_safe_when_lib_load_fails(self, monkeypatch):
        from paddle_tpu.parallel import host_kv

        def boom():
            raise RuntimeError("native toolchain unavailable")

        monkeypatch.setattr(host_kv, "_lib", boom)
        with pytest.raises(RuntimeError, match="native toolchain"):
            host_kv.HostKVStore(4)
        # a half-built instance (as __init__ left it) must tear down
        # silently — this is the interpreter-exit path
        obj = host_kv.HostKVStore.__new__(host_kv.HostKVStore)
        obj.close()                          # no AttributeError
        obj.__del__()

    def test_server_stop_idempotent_and_safe(self, monkeypatch):
        from paddle_tpu.parallel import kv_server

        def boom():
            raise RuntimeError("native toolchain unavailable")

        monkeypatch.setattr(kv_server, "_lib", boom)
        with pytest.raises(RuntimeError, match="native toolchain"):
            kv_server.KVServer(4)
        obj = kv_server.KVServer.__new__(kv_server.KVServer)
        obj.stop()                           # no AttributeError
        obj.__del__()

    def test_server_real_stop_twice(self):
        from paddle_tpu.parallel.kv_server import KVServer
        srv = KVServer(3, port=0)
        assert srv.port > 0
        srv.stop()
        srv.stop()
        srv.__del__()
