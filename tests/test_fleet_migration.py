"""Live migration in the fleet of ``tests/test_fleet_serving.py`` (a file
of its own for ``--dist loadfile``): a drain mid-decode is byte-identical,
a forged or corrupt snapshot is refused before it touches a page, an
aborted drain restores everything. Its engines decode in blocks of 4."""

import numpy as np
import pytest

from paddle_tpu import observability as obs
from paddle_tpu import serving
from paddle_tpu.serving import fleet

from serving_taps import fleet_of as _fleet
from serving_taps import tiny_gpt, traced, warmed_engines

VOCAB = 64


@pytest.fixture(scope="module")
def model_params():
    return tiny_gpt()


@pytest.fixture(scope="module")
def warmed(model_params):
    """``get(peer=0, **options)``, as in ``tests/test_fleet_serving.py``."""
    return warmed_engines(model_params)


def _step_until_mid_decode(router, rep, cap, max_steps=1000):
    """Step the fleet until ``rep`` holds a mid-decode request (some
    tokens generated, more to go) — the deterministic drain window the
    migration tests need regardless of decode_block/cap timing."""
    eng = rep.engine
    for _ in range(max_steps):
        router.step()
        mid = [i for i in eng.scheduler.decode_slots()
               if 0 < len(eng.scheduler.slots[i].generated) < cap]
        if mid:
            return
    raise AssertionError("no mid-decode window reached")


class TestMigration:
    def test_drain_mid_decode_byte_identical(self, model_params, warmed):
        """ISSUE acceptance: greedy tokens through a mid-decode drain
        are byte-identical to an unmigrated run (of the same two engines:
        the second run finds each prompt's first page where the first run
        published it, and is routed as the first was)."""
        rng = np.random.default_rng(9)
        prompts = [rng.integers(1, VOCAB, int(n)).astype(np.int32)
                   for n in (5, 9, 6, 11)]
        ref_router, _ = _fleet(model_params, 2, seed=1, warmed=warmed,
                               decode_block=4)
        ref_frids = [ref_router.submit(p, 16) for p in prompts]
        ref_router.run_until_idle(max_steps=10_000)
        ref = [ref_router.result(f) for f in ref_frids]

        router, reps = _fleet(model_params, 2, seed=1, warmed=warmed,
                              decode_block=4)
        frids = [router.submit(p, 16) for p in prompts]
        _step_until_mid_decode(router, reps[1], 16)
        migrated = router.drain_replica(reps[1])
        assert migrated > 0
        assert len(router.replicas) == 1
        router.run_until_idle(max_steps=10_000)
        got = [router.result(f) for f in frids]
        for want, have in zip(ref, got):
            assert have is not None
            np.testing.assert_array_equal(want, have)
        assert router.migrations_total == migrated

    def test_excess_shard_refused_before_touching_pages(self, warmed):
        """A snapshot carrying more shards than its live length
        explains must be refused: the extra shard would index past the
        reserved block-table entries and overwrite the null page."""
        import hashlib
        eng = warmed(0, decode_block=4)
        eng.submit(np.arange(1, 8, dtype=np.int32), 24)
        for _ in range(2):
            eng.step()
        snap = eng.snapshot_slot(eng.scheduler.active_slots()[0])
        forged = np.zeros_like(snap["shards"][0])
        snap["shards"].append(forged)
        snap["manifest"].append({
            "index": len(snap["manifest"]),
            "sha256": hashlib.sha256(forged.tobytes()).hexdigest(),
            "bytes": forged.nbytes})        # hash-valid, count-invalid
        target = warmed(1, decode_block=4)
        with pytest.raises(serving.SlotMigrationError,
                           match="inconsistent"):
            target.restore_slot(snap)
        assert target.scheduler.active_slots() == []
        target.cache.check_invariants()

    def test_drain_queue_closes_request_bookkeeping(self, warmed):
        """Queued requests popped by a drain must not leak engine-side
        spans/maps: the root span finishes as 'requeued'."""
        eng = warmed(0, decode_block=4)
        rep = fleet.LocalReplica(eng, name="dq")
        with traced(eng) as tracer:
            rids = [eng.submit(np.arange(1, 6, dtype=np.int32), 4)
                    for _ in range(3)]      # queued, never stepped
            assert len(eng._req_spans) == 3
            popped = rep.drain_queue()
        assert [t[0] for t in popped] == rids
        assert eng._req_spans == {} and eng._phase_acc == {}
        closed = [s for s in tracer.spans()
                  if s.name == "serving.request"
                  and s.status == "requeued"]
        assert len(closed) == 3

    def test_corrupt_shard_refused(self, warmed):
        eng = warmed(0, decode_block=4)
        eng.submit(np.arange(1, 8, dtype=np.int32), 24)
        for _ in range(2):
            eng.step()
        snap = eng.snapshot_slot(eng.scheduler.active_slots()[0])
        flat = snap["shards"][0].reshape(-1).copy()
        flat[0] += 1                       # bit-flip one value
        snap["shards"][0] = flat.reshape(snap["shards"][0].shape)
        target = warmed(1, decode_block=4)
        with pytest.raises(serving.SlotMigrationError,
                           match="sha256 mismatch"):
            target.restore_slot(snap)
        # target untouched: nothing reserved, no slot installed
        assert target.scheduler.active_slots() == []
        target.cache.check_invariants()

    def test_drain_abort_restores_everything(self, model_params, warmed):
        """No peer capacity: the drain aborts, every snapshot goes back
        into the source, and every request still completes."""
        router, reps = _fleet(model_params, 2, num_slots=2, seed=2,
                              warmed=warmed, decode_block=4)
        rng = np.random.default_rng(3)
        # saturate BOTH replicas' slots so nothing can migrate
        frids = [router.submit(rng.integers(1, VOCAB, 5).astype(np.int32),
                               16) for _ in range(4)]
        _step_until_mid_decode(router, reps[1], 16)
        with pytest.raises(serving.SlotMigrationError, match="aborted"):
            router.drain_replica(reps[1])
        assert len(router.replicas) == 2
        assert not reps[1].draining
        out = router.run_until_idle(max_steps=10_000)
        assert set(out) == set(frids)

    def test_migration_trace_continuity(self, model_params):
        tracer = obs.Tracer(capacity=2048)
        router, reps = _fleet(model_params, 2, tracer=tracer, seed=4,
                              decode_block=4)
        rng = np.random.default_rng(4)
        frids = [router.submit(rng.integers(1, VOCAB, 6).astype(np.int32),
                               16) for _ in range(4)]
        _step_until_mid_decode(router, reps[1], 16)
        router.drain_replica(reps[1])
        router.run_until_idle(max_steps=10_000)
        spans = tracer.spans()
        req_tids = {s.trace_id for s in spans
                    if s.name == "serving.request"}
        route_tids = {s.trace_id for s in spans
                      if s.name == "router.route"}
        mig = [s for s in spans if s.name == "router.migrate"]
        assert mig, "no migrate spans"
        for s in mig:
            # the migrate span AND the restored request continuation
            # live on the original router-minted trace
            assert s.trace_id in req_tids
            assert s.trace_id in route_tids
            assert s.attrs["src"] == "r1"
            assert s.attrs["dst"] == "r0"
        migrated_in = [s for s in spans if s.name == "serving.request"
                       and s.attrs.get("migrated")]
        assert migrated_in
        for s in migrated_in:
            assert s.trace_id in route_tids

    def test_migrated_stats_and_counters(self, model_params, warmed):
        router, reps = _fleet(model_params, 2, seed=6, warmed=warmed,
                              decode_block=4)
        came_in = reps[0].engine.migrated_in_total
        went_out = reps[1].engine.migrated_out_total
        rng = np.random.default_rng(6)
        frids = [router.submit(rng.integers(1, VOCAB, 6).astype(np.int32),
                               16) for _ in range(4)]
        _step_until_mid_decode(router, reps[1], 16)
        n = router.drain_replica(reps[1])
        assert reps[0].engine.migrated_in_total - came_in == n
        assert reps[1].engine.migrated_out_total - went_out == n
        router.run_until_idle(max_steps=10_000)
        for f in frids:
            assert router.result(f) is not None


class _QueueFake(fleet.ReplicaHandle):
    """Interface-level fake: accepts (or sheds) submissions, hands its
    queue back on drain — lets the requeue paths be tested without
    engines."""

    def __init__(self, name, shed=False):
        self.name = name
        self.shed = shed
        self.accepted = []
        self._rids = iter(range(1, 1000))

    def page_size(self):
        return 4

    def prefix_digests(self):
        return frozenset()

    def health(self):
        return {"queue_depth": len(self.accepted),
                "requests_in_flight": 0, "slot_occupancy": 0.0,
                "page_utilization": 0.0, "free_slots": 4}

    def idle(self):
        return True

    def step(self):
        return {}

    def warmup(self):
        return self

    def submit(self, prompt, max_new_tokens, eos_id=None, *,
               lane="default", ttft_deadline_s=None, trace_id=None):
        if self.shed:
            from paddle_tpu.serving.scheduler import Reject
            raise serving.LoadShedError(
                Reject("queue_full", lane, 99, 1.0, 0.1))
        rid = next(self._rids)
        self.accepted.append((rid, prompt, max_new_tokens, eos_id,
                              lane, ttft_deadline_s))
        return rid

    def drain_queue(self):
        out, self.accepted = self.accepted, []
        return out

    def snapshot_inflight(self):
        return []

    def close(self):
        pass


class TestDrainRequeue:
    def test_requeue_retries_every_peer_before_shedding(self):
        victim = _QueueFake("victim")
        shedder = _QueueFake("shedder", shed=True)
        acceptor = _QueueFake("acceptor")
        # round_robin puts the first submit on the victim; the shedder
        # (load 0) is the first re-route target, the acceptor must
        # still get the request
        router = fleet.FleetRouter([victim, shedder, acceptor],
                                   policy="round_robin",
                                   registry=obs.MetricsRegistry())
        frid = router.submit(np.arange(1, 6, dtype=np.int32), 4)
        assert router._where[frid][0] is victim
        router._rr = 0      # pin the re-route's first pick to the shedder
        router.drain_replica(victim)
        assert len(acceptor.accepted) == 1, "retry never reached peer"
        assert router._where[frid][0] is acceptor

    def test_requeue_shed_everywhere_cleans_fleet_maps(self):
        victim = _QueueFake("victim")
        s1 = _QueueFake("s1", shed=True)
        s2 = _QueueFake("s2", shed=True)
        router = fleet.FleetRouter([victim, s1, s2],
                                   policy="round_robin",
                                   registry=obs.MetricsRegistry())
        frid = router.submit(np.arange(1, 6, dtype=np.int32), 4)
        router.drain_replica(victim)
        assert frid not in router._where, "stale mapping leaked"
        assert frid not in router._trace
