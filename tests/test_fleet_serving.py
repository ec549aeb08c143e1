"""Multi-replica serving fleet (ISSUE 11): prefix-affinity routing vs
round-robin, power-of-two-choices balance bounds, live request
migration byte-parity, concurrent health polling, router→replica
trace-id propagation, burn-rate autoscaling, and the voluntary-drain
exit code."""

import threading

import numpy as np
import pytest

from paddle_tpu import observability as obs
from paddle_tpu.serving import fleet
from paddle_tpu.serving.paged_cache import prompt_prefix_digests

from serving_taps import tiny_gpt, traced, warmed_engines
from serving_taps import fleet_engine as _engine, fleet_of as _fleet

VOCAB = 64


@pytest.fixture(scope="module")
def model_params():
    return tiny_gpt()


@pytest.fixture(scope="module")
def warmed(model_params):
    """``get(peer=0, **options) ->`` engine number ``peer`` of these
    options, warmed ONCE for the module and idle (``tests/serving_taps.py``:
    the pages earlier cases published stay mapped). A case that hands its
    engines a tracer builds its own."""
    return warmed_engines(model_params)


class TestPrefixDigests:
    def test_digests_match_published_index(self, warmed):
        eng = warmed()
        rng = np.random.default_rng(0)
        prompt = rng.integers(1, VOCAB, 13).astype(np.int32)
        eng.generate_many([prompt], 4, max_steps=10_000)
        want = prompt_prefix_digests(prompt, 4)
        assert len(want) == 3            # 13 tokens, limit 12 -> 3 pages
        held = eng.cache.published_digests()
        assert set(want) <= held, "published index missed prefix pages"

    def test_digest_cap_leaves_one_token(self):
        # a page-aligned prompt never digests its last page: at least
        # one token must prefill on whoever serves it
        p = np.arange(1, 9, dtype=np.int32)      # 8 tokens, ps=4
        assert len(prompt_prefix_digests(p, 4)) == 1

    def test_distinct_prompts_distinct_digests(self):
        a = prompt_prefix_digests(np.arange(1, 10, dtype=np.int32), 4)
        b = prompt_prefix_digests(np.arange(2, 11, dtype=np.int32), 4)
        assert a and b and a[0] != b[0]

    def test_published_digests_memoized_on_index_gen(self, warmed):
        eng = warmed()
        d0 = eng.cache.published_digests()
        assert eng.cache.published_digests() is d0   # no per-call build
        rng = np.random.default_rng(2)
        eng.generate_many([rng.integers(1, VOCAB, 13).astype(np.int32)],
                          4, max_steps=10_000)
        d1 = eng.cache.published_digests()
        assert d1 is not d0 and len(d1) > len(d0)    # refreshed on change


class TestExternalTraceId:
    def test_submit_adopts_router_trace_id(self, warmed):
        eng = warmed()
        with traced(eng) as tracer:
            rid = eng.submit(np.arange(1, 6, dtype=np.int32), 3,
                             trace_id=777)
            assert eng._req_spans[rid].trace_id == 777
            while not eng.scheduler.idle():
                eng.step()
        st = eng.request_stats(rid)
        assert st["trace_id"] == 777.0
        spans = [s for s in tracer.spans() if s.trace_id == 777]
        assert any(s.name == "serving.request" for s in spans)

    def test_trace_id_carried_with_tracing_off(self, warmed):
        eng = warmed()                    # its tracer is off
        rid = eng.submit(np.arange(1, 6, dtype=np.int32), 3,
                         trace_id=555)
        while not eng.scheduler.idle():
            eng.step()
        assert eng.request_stats(rid)["trace_id"] == 555.0


class TestConcurrentHealth:
    def test_health_poll_during_step_loop(self, warmed):
        """Satellite regression: a router thread hammers ``health()``
        while the engine thread runs ``step()`` — snapshot reads must
        never throw or return torn values."""
        eng = warmed()
        rng = np.random.default_rng(1)
        prompts = [rng.integers(1, VOCAB, int(n)).astype(np.int32)
                   for n in rng.integers(4, 12, 12)]
        errs = []
        stop = threading.Event()

        def poll():
            try:
                while not stop.is_set():
                    h = eng.health()
                    assert 0.0 <= h["slot_occupancy"] <= 1.0
                    assert h["queue_depth"] >= 0
                    assert 0.0 <= h["page_utilization"] <= 1.0
                    assert h["free_slots"] >= 0
                    assert h["requests_in_flight"] >= 0
            except Exception as e:          # pragma: no cover
                errs.append(e)

        t = threading.Thread(target=poll)
        t.start()
        try:
            eng.generate_many(prompts, 8, max_steps=100_000)
        finally:
            stop.set()
            t.join()
        assert not errs, errs
        h = eng.health()
        assert h["requests_in_flight"] == 0 and h["queue_depth"] == 0

    def test_snapshot_updates_on_submit_and_step(self, warmed):
        eng = warmed()
        assert eng.health()["queue_depth"] == 0
        eng.submit(np.arange(1, 6, dtype=np.int32), 2)
        assert eng.health()["queue_depth"] == 1
        while not eng.scheduler.idle():
            eng.step()
        assert eng.health()["queue_depth"] == 0
        assert eng.health()["steps"] >= 1


def _shared_prefix_traffic(rng, sys_prompt, n, tail=4):
    return [np.concatenate([sys_prompt,
                            rng.integers(1, VOCAB, tail).astype(np.int32)])
            for _ in range(n)]


class TestRouting:
    def _shared_tokens(self, router):
        return sum(int(r.engine._reg.counter(
            "serving_prefix_shared_tokens_total").value())
            for r in router.replicas)

    def _run_shared_traffic(self, model_params, policy, warmed, first):
        """-> the router and the tokens its two replicas (the module's
        engines ``first`` and ``first + 1``, which have not met this
        system prompt) shared in the run."""
        rng = np.random.default_rng(7)
        sysp = rng.integers(1, VOCAB, 13).astype(np.int32)
        # fleet prefix fetch would let round-robin import the pages it
        # missed — disable it to compare the ROUTING policies alone
        router, _ = _fleet(model_params, 2, policy=policy, seed=3,
                           prefix_fetch=False, warmed=warmed, first=first)
        before = self._shared_tokens(router)
        # wave 1 publishes the prefix on ONE replica
        router.submit(_shared_prefix_traffic(rng, sysp, 1)[0], 4)
        router.run_until_idle(max_steps=10_000)
        # wave 2: the affinity signal exists now
        for p in _shared_prefix_traffic(rng, sysp, 8):
            router.submit(p, 4)
        router.run_until_idle(max_steps=10_000)
        return router, self._shared_tokens(router) - before

    def test_affinity_beats_round_robin_on_shared_prefix(self, model_params,
                                                         warmed):
        aff, got_aff = self._run_shared_traffic(model_params, "affinity",
                                                warmed, 0)
        _rr, got_rr = self._run_shared_traffic(model_params, "round_robin",
                                               warmed, 2)
        # affinity keeps every wave-2 request on the publisher: all 8
        # share the 3-page prefix; round-robin spreads them, half land
        # on the replica that never saw the prefix (until its own
        # follower publishes — strictly fewer shared tokens)
        assert got_aff > got_rr, (got_aff, got_rr)
        assert aff.routed_affinity_total >= 8

    def test_p2c_imbalance_bounded_random_arrivals(self, model_params,
                                                   warmed):
        router, reps = _fleet(model_params, 4, policy="p2c", seed=11,
                              warmed=warmed)
        rng = np.random.default_rng(11)
        counts = {r.name: 0 for r in reps}
        for _ in range(64):
            p = rng.integers(1, VOCAB, int(rng.integers(4, 12))
                             ).astype(np.int32)
            frid = router.submit(p, 2)
            rep = router._where[frid][0]
            counts[rep.name] += 1
        vals = np.array(list(counts.values()), float)
        assert vals.min() > 0, counts      # no starved replica
        # power-of-two-choices keeps the spread tight even with a
        # queue-depth-only signal: max within 2x of mean
        assert vals.max() / vals.mean() <= 2.0, counts
        router.run_until_idle(max_steps=100_000)

    def test_round_robin_cycles(self, model_params, warmed):
        router, reps = _fleet(model_params, 2, policy="round_robin",
                              warmed=warmed)
        a = router.submit(np.arange(1, 6, dtype=np.int32), 2)
        b = router.submit(np.arange(1, 6, dtype=np.int32), 2)
        assert router._where[a][0] is not router._where[b][0]
        router.run_until_idle(max_steps=10_000)

    def test_fleet_results_and_stats_by_fleet_rid(self, model_params,
                                                  warmed):
        router, _ = _fleet(model_params, 2, warmed=warmed)
        rng = np.random.default_rng(5)
        prompts = [rng.integers(1, VOCAB, 6).astype(np.int32)
                   for _ in range(6)]
        frids = [router.submit(p, 5) for p in prompts]
        out = router.run_until_idle(max_steps=10_000)
        assert set(out) == set(frids)
        for f in frids:
            st = router.request_stats(f)
            assert st is not None and st["tokens"] == 5.0
            assert st["replica"].startswith("r")


class TestThreadedReplica:
    def test_background_loop_serves_and_health_polls(self, warmed):
        rep = fleet.LocalReplica(warmed(), name="bg")
        rep.start()
        try:
            rng = np.random.default_rng(8)
            rids = [rep.submit(rng.integers(1, VOCAB, 6).astype(np.int32),
                               4) for _ in range(6)]
            import time
            deadline = time.monotonic() + 60.0
            while not rep.idle():
                assert time.monotonic() < deadline, "replica stuck"
                h = rep.health()            # poll while it steps
                assert 0.0 <= h["slot_occupancy"] <= 1.0
            for r in rids:
                got = rep.result(r)
                assert got is not None and len(got) == 4
        finally:
            rep.stop()
        assert not rep.running()


class _FakeReplica(fleet.ReplicaHandle):
    def __init__(self, name, burn=0.0):
        self.name = name
        self.burn = burn
        self.closed = False
        self.warmed = False
        self.inflight = 0

    def page_size(self):
        return 4

    def prefix_digests(self):
        return frozenset()

    def health(self):
        return {"queue_depth": 0, "requests_in_flight": self.inflight,
                "slot_occupancy": 0.0, "page_utilization": 0.0,
                "free_slots": 4,
                "slo": {"burn_fast": self.burn,
                        "burn_slow": self.burn}}

    def idle(self):
        return True

    def step(self):
        return {}

    def warmup(self):
        self.warmed = True
        return self

    def drain_queue(self):
        return []

    def snapshot_inflight(self):
        return []

    def close(self):
        self.closed = True


class TestAutoscaler:
    def _scaler(self, spawn, **kw):
        kw.setdefault("min_replicas", 1)
        kw.setdefault("max_replicas", 3)
        kw.setdefault("scale_out_burn", 6.0)
        kw.setdefault("sustain_s", 2.0)
        kw.setdefault("idle_s", 5.0)
        kw.setdefault("cooldown_s", 3.0)
        clock = [0.0]
        a = fleet.FleetAutoscaler(spawn, registry=obs.MetricsRegistry(),
                                  clock=lambda: clock[0], **kw)
        return a, clock

    def test_sustained_burn_scales_out_prewarmed(self):
        spawned = []

        def spawn(i):
            r = _FakeReplica(f"auto{i}")
            spawned.append(r)
            return r

        a, clock = self._scaler(spawn)
        base = _FakeReplica("base", burn=20.0)
        router = fleet.FleetRouter([base], policy="p2c",
                                   registry=obs.MetricsRegistry(),
                                   autoscaler=a)
        assert a.tick() is None            # hot but not sustained yet
        clock[0] = 1.0
        assert a.tick() is None
        clock[0] = 2.5
        assert a.tick() == "scale_out"
        assert spawned and spawned[0].warmed, \
            "replica attached before warmup"
        assert len(router.replicas) == 2
        clock[0] = 4.0                     # cooldown holds
        assert a.tick() is None

    def test_spike_alone_never_scales(self):
        a, clock = self._scaler(lambda i: _FakeReplica(f"a{i}"))
        base = _FakeReplica("base")
        fleet.FleetRouter([base], policy="p2c",
                          registry=obs.MetricsRegistry(), autoscaler=a)
        base.burn = 20.0
        assert a.tick() is None
        base.burn = 0.0                    # pressure gone before sustain
        clock[0] = 2.5
        assert a.tick() is None
        assert a.scale_outs == 0

    def test_sustained_idle_scales_in_via_drain(self):
        a, clock = self._scaler(lambda i: _FakeReplica(f"a{i}"))
        r0, r1 = _FakeReplica("r0"), _FakeReplica("r1")
        router = fleet.FleetRouter([r0, r1], policy="p2c",
                                   registry=obs.MetricsRegistry(),
                                   autoscaler=a)
        assert a.tick() is None            # idle starts counting
        clock[0] = 5.5
        assert a.tick() == "scale_in"
        assert len(router.replicas) == 1
        assert r0.closed or r1.closed
        assert a.events[-1]["action"] == "scale_in"

    def test_never_below_min_replicas(self):
        a, clock = self._scaler(lambda i: _FakeReplica(f"a{i}"))
        base = _FakeReplica("base")
        router = fleet.FleetRouter([base], policy="p2c",
                                   registry=obs.MetricsRegistry(),
                                   autoscaler=a)
        clock[0] = 100.0
        assert a.tick() is None
        assert len(router.replicas) == 1

    def test_scale_in_abort_backs_off_instead_of_crashing(self,
                                                          model_params):
        """Both replicas saturated: the autoscaler's drain attempt
        aborts (no peer capacity), which must cool down — NOT raise
        out of router.step() — and every request still completes."""
        clock = [0.0]
        a = fleet.FleetAutoscaler(
            lambda i: (_ for _ in ()).throw(AssertionError()),
            min_replicas=1, max_replicas=2, idle_occupancy=1.0,
            idle_s=0.0, cooldown_s=1000.0,
            registry=obs.MetricsRegistry(), clock=lambda: clock[0])
        router, reps = _fleet(model_params, 2, num_slots=2, seed=20,
                              decode_block=4, autoscaler=a)
        rng = np.random.default_rng(20)
        frids = [router.submit(rng.integers(1, VOCAB, 5).astype(np.int32),
                               16) for _ in range(4)]
        out = router.run_until_idle(max_steps=10_000)   # must not raise
        assert set(out) == set(frids)
        assert a.scale_ins == 0
        aborted = [e for e in a.events
                   if e["action"] == "scale_in_aborted"]
        assert aborted, "drain abort never recorded"
        assert len(router.replicas) == 2

    def test_real_fleet_idle_scale_in_migrates(self, model_params, warmed):
        """Integration: a real 2-replica fleet with in-flight work on
        the drain victim — scale-in live-migrates, requests finish."""
        model, params = model_params

        def spawn(i):                      # pragma: no cover
            raise AssertionError("no scale-out expected")

        clock = [0.0]
        a = fleet.FleetAutoscaler(spawn, min_replicas=1, max_replicas=2,
                                  idle_occupancy=1.0, idle_s=0.0,
                                  cooldown_s=0.0,
                                  registry=obs.MetricsRegistry(),
                                  clock=lambda: clock[0])
        router, reps = _fleet(model_params, 2, seed=12, autoscaler=a,
                              warmed=warmed)
        rng = np.random.default_rng(12)
        frids = [router.submit(rng.integers(1, VOCAB, 5).astype(np.int32),
                               12) for _ in range(2)]
        # idle_occupancy=1.0 makes "idle" true despite in-flight work,
        # so the first tick (inside router.step) drains immediately —
        # exercising migration THROUGH the autoscaler path
        out = router.run_until_idle(max_steps=10_000)
        assert a.scale_ins == 1
        assert len(router.replicas) == 1
        assert set(out) == set(frids)


class TestDrainExitCode:
    class _Proc:
        def __init__(self, rc):
            self.returncode = None
            self._rc = rc
            self.killed = False

        def poll(self):
            self.returncode = self._rc
            return self._rc

        def kill(self):                    # pragma: no cover
            self.killed = True

        def wait(self):
            return self.returncode

    def test_drained_rank_retires_without_budget(self):
        from paddle_tpu import fleet as proc_fleet
        from paddle_tpu.resilience import EXIT_DRAINED
        rcs = {0: 0, 1: EXIT_DRAINED}
        spawned = []

        def spawn(rank, attempt):
            p = self._Proc(rcs[rank])
            spawned.append((rank, attempt))
            return p

        coord = proc_fleet.ElasticCoordinator(
            spawn, 2, max_restarts=1, poll_s=0.01, gang=False,
            log_fn=lambda *a: None)
        assert coord.run(timeout_s=10.0)
        assert coord.drained_exits == 1
        assert coord.restarts == 0
        assert coord.rank_restarts == [0, 0]
        assert coord.preemption_restarts == 0
        assert len(spawned) == 2           # nobody respawned

    def test_gang_restart_never_resurrects_drained_rank(self):
        """A gang respawn after a peer's crash must leave a drained
        rank retired — its work migrated away; respawning it would
        re-grow the fleet the autoscaler just shrank."""
        from paddle_tpu import fleet as proc_fleet
        from paddle_tpu.resilience import EXIT_DRAINED
        spawns = []

        def spawn(rank, attempt):
            spawns.append((rank, attempt))
            if rank == 0:
                return self._Proc(EXIT_DRAINED)
            # rank 1 crashes once, then succeeds after the gang restart
            return self._Proc(7 if attempt == 0 else 0)

        coord = proc_fleet.ElasticCoordinator(
            spawn, 2, max_restarts=1, poll_s=0.01, gang=True,
            log_fn=lambda *a: None)
        assert coord.run(timeout_s=10.0)
        assert coord.drained_exits == 1
        assert coord.restarts == 1
        assert spawns.count((0, 0)) == 1
        assert all(r != 0 for (r, a) in spawns if a > 0), \
            f"drained rank respawned: {spawns}"

    def test_gang_failure_same_window_still_retires_drained_rank(self):
        """Rank A crashes and rank B drains in the SAME poll window:
        the exit scan must record B's retirement before the gang
        respawn, or B gets resurrected."""
        from paddle_tpu import fleet as proc_fleet
        from paddle_tpu.resilience import EXIT_DRAINED
        spawns = []

        def spawn(rank, attempt):
            spawns.append((rank, attempt))
            if rank == 1:
                return self._Proc(EXIT_DRAINED)
            return self._Proc(7 if attempt == 0 else 0)

        coord = proc_fleet.ElasticCoordinator(
            spawn, 2, max_restarts=1, poll_s=0.01, gang=True,
            log_fn=lambda *a: None)
        assert coord.run(timeout_s=10.0)
        assert coord.drained_exits == 1
        assert all(r != 1 for (r, a) in spawns if a > 0), \
            f"drained rank respawned: {spawns}"

    def test_crash_still_consumes_budget(self):
        from paddle_tpu import fleet as proc_fleet
        calls = {"n": 0}

        def spawn(rank, attempt):
            calls["n"] += 1
            return self._Proc(7)           # always crashes

        coord = proc_fleet.ElasticCoordinator(
            spawn, 1, max_restarts=1, poll_s=0.01, gang=False,
            log_fn=lambda *a: None)
        assert not coord.run(timeout_s=10.0)
        assert coord.rank_restarts == [1]
        assert coord.drained_exits == 0


class TestFleetMonitorAndFacade:
    def test_monitor_aggregates_gauges(self, model_params):
        reg = obs.MetricsRegistry()
        tracer = obs.Tracer(enabled=False)
        reps = [fleet.LocalReplica(
            _engine(model_params, tracer=tracer, ttft_budget_s=4.0),
            name=f"m{i}").warmup() for i in range(2)]
        router = fleet.FleetRouter(reps, registry=reg, tracer=tracer)
        mon = fleet.FleetMonitor(router, registry=reg)
        rng = np.random.default_rng(13)
        router.submit(rng.integers(1, VOCAB, 6).astype(np.int32), 4)
        mon.collect()
        assert reg.gauge("fleet_replicas").value() == 2
        assert reg.gauge("fleet_queue_depth").value() >= 0
        assert reg.gauge("fleet_replica_queue_depth").value(
            replica="m0") >= 0
        router.run_until_idle(max_steps=10_000)
        h = mon.collect()
        assert h["requests_in_flight"] == 0
        # burn gauges exist because the engines armed SLO monitors
        assert reg.gauge("fleet_burn_rate_max").value() >= 0.0

    def test_make_serving_fleet_facade(self, model_params):
        from paddle_tpu import inference
        model, params = model_params
        router = inference.make_serving_fleet(
            model, params, num_replicas=2, num_slots=2, page_size=4,
            max_tokens_per_slot=32, prefill_chunk=4,
            registry=obs.MetricsRegistry())
        rng = np.random.default_rng(14)
        frids = [router.submit(rng.integers(1, VOCAB, 6).astype(np.int32),
                               4) for _ in range(4)]
        out = router.run_until_idle(max_steps=10_000)
        assert set(out) == set(frids)
        for rep in router.replicas:
            assert rep.engine.warmed_signatures  # facade pre-warmed

    def test_fleet_zero_steady_state_recompiles(self, model_params):
        # (replicas of the case's own, new from warm-up: on the module's,
        # a bucket that warm-up missed was compiled by an earlier case)
        router, _ = _fleet(model_params, 2, seed=15)
        det = obs.RecompileDetector("fleet_test", warmup=0,
                                    registry=obs.MetricsRegistry())
        rng = np.random.default_rng(15)
        for p in [rng.integers(1, VOCAB, int(n)).astype(np.int32)
                  for n in (5, 9, 6, 11, 7, 8)]:
            router.submit(p, 6)
        router.run_until_idle(max_steps=10_000)
        det.check()
        assert det.recompiles == 0, \
            "steady-state fleet traffic recompiled"


class TestWarmupCoverageWithMigration:
    def test_page_io_in_plan_and_reachable(self, warmed):
        eng = warmed()
        plan = set(eng.warmup_plan())
        assert ("page_read",) in plan and ("page_write",) in plan
        assert set(eng.reachable_signatures()) == plan

    def test_bucket_coverage_still_clean(self, warmed):
        from paddle_tpu import analysis
        eng = warmed()
        assert analysis.serving_bucket_coverage(eng) == []
