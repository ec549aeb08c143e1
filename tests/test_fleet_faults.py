"""Fleet fault tolerance (ISSUE 14): chaos injection, failure
detection, circuit breakers, and exactly-once request redrive.

The battery pins the ISSUE acceptance: with a ChaosReplica killed
mid-burst, every accepted request completes or sheds with a structured
reason (0 silently lost), redriven greedy outputs are byte-identical
to a failure-free run, the breaker visibly opens → half-opens →
closes, and the steady state compiles nothing with detection +
breakers armed."""

import time

import numpy as np
import pytest

from paddle_tpu import observability as obs
from paddle_tpu import serving
from paddle_tpu.serving import fleet
from paddle_tpu.serving.fleet.faults import BREAKER_GAUGE

from serving_taps import (fleet_engine, fleet_of, tiny_gpt,
                          warmed_engines)
from serving_taps import random_prompts as _prompts


class FakeClock:
    def __init__(self, t=1000.0):
        self.t = t

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


@pytest.fixture(scope="module")
def model_params():
    return tiny_gpt()


GEOMETRY = dict(num_slots=2, decode_block=2)


def _engine(model_params, tracer=None, **kw):
    return fleet_engine(model_params, tracer, **{**GEOMETRY, **kw})


@pytest.fixture(scope="module")
def warmed(model_params):
    """``get(peer=0, **options) ->`` engine number ``peer`` of these
    options, warmed ONCE for the module and idle (``serving_taps.py``): a
    chaos wrapper's crash, a hang and an ejection are the replica's; what
    they leave in the engine is served out before the next case has it."""
    return warmed_engines(model_params, **GEOMETRY)


def _fleet(model_params, n, faults=None, **kw):
    """``serving_taps.fleet_of`` at this file's geometry, its router quick
    to eject (one failure) unless given other ``faults``."""
    return fleet_of(model_params, n, engine=_engine, faults=faults or (
        fleet.FaultPolicy(max_consecutive_failures=1, probe_timeout_s=30.0)),
        **kw)


def _reference(warmed, prompts, max_new):
    """Failure-free reference: one of the module's engines, greedy
    decode."""
    return warmed().generate_many(prompts, max_new, max_steps=100_000)


def _drain_fleet(router, frids, max_steps=5000):
    """Run to idle; every accepted request must end with a result or a
    structured reject (the no-silent-loss contract)."""
    steps = 0
    while not router.idle():
        router.step()
        steps += 1
        assert steps < max_steps, "fleet did not converge"
    outs, rejects = {}, {}
    for f in frids:
        r = router.result(f)
        if r is not None:
            outs[f] = r
        else:
            rejects[f] = router.reject_reason(f)
            assert rejects[f] is not None, \
                f"request {f} silently lost (no result, no reject)"
    return outs, rejects


# ---------------------------------------------------------------------------
# unit: chaos wrapper


class _InnerFake(fleet.ReplicaHandle):
    name = "inner"

    def __init__(self):
        self.steps = 0
        self.submits = 0

    def step(self):
        self.steps += 1
        return {}

    def submit(self, *a, **k):
        self.submits += 1
        return self.submits

    def health(self):
        return {"queue_depth": 1, "requests_in_flight": 0,
                "heartbeat_age_s": 0.0}

    def idle(self):
        return False


class TestChaosReplica:
    def test_crash_on_step_then_dead_host(self):
        c = fleet.ChaosReplica(_InnerFake(), crash_on_step=3)
        assert c.step() == {} and c.step() == {}
        with pytest.raises(fleet.ReplicaCrashed):
            c.step()
        # dead-host semantics: EVERY later op raises, inner untouched
        for op in (c.step, c.health, c.idle, lambda: c.submit([1], 4)):
            with pytest.raises(fleet.ReplicaCrashed):
                op()
        assert c.inner.steps == 2

    def test_submit_failures_then_heal(self):
        c = fleet.ChaosReplica(_InnerFake(), submit_failures=2)
        for _ in range(2):
            with pytest.raises(fleet.ReplicaUnavailable):
                c.submit([1], 4)
        assert c.submit([1], 4) == 1         # healed

    def test_hang_reports_stale_heartbeat_no_progress(self):
        c = fleet.ChaosReplica(_InnerFake(), hang_after_step=1)
        assert c.step() == {}
        assert c.hung and c.inner.steps == 0     # never reached inner
        assert c.health()["heartbeat_age_s"] == float("inf")
        assert c.idle() is False                 # work never finishes

    def test_corrupt_health_then_heal(self):
        c = fleet.ChaosReplica(_InnerFake(), health_failures=1)
        with pytest.raises(fleet.ReplicaUnavailable):
            c.health()
        assert c.health()["queue_depth"] == 1

    def test_seeded_schedule_deterministic(self):
        a = fleet.chaos_schedule(7, 8)
        b = fleet.chaos_schedule(7, 8)
        assert a == b and len(a) == 8
        assert fleet.chaos_schedule(8, 8) != a


# ---------------------------------------------------------------------------
# unit: circuit breaker + detector


class TestCircuitBreaker:
    def test_full_cycle_closed_open_halfopen_closed(self):
        clk = FakeClock()
        b = fleet.CircuitBreaker(threshold=2, cooldown_s=5.0, clock=clk)
        assert b.allow() and b.state == "closed"
        b.record_failure()
        assert b.state == "closed"
        b.record_failure()
        assert b.state == "open" and not b.allow()
        clk.advance(4.9)
        assert not b.allow()                 # still cooling down
        clk.advance(0.2)
        assert b.allow() and b.state == "half_open"
        b.note_probe()
        assert not b.allow()                 # one probe at a time
        b.record_success()
        assert b.state == "closed" and b.allow()
        assert b.transitions == [("closed", "open"),
                                 ("open", "half_open"),
                                 ("half_open", "closed")]

    def test_probe_failure_reopens(self):
        clk = FakeClock()
        b = fleet.CircuitBreaker(threshold=1, cooldown_s=1.0, clock=clk)
        b.record_failure()
        clk.advance(1.1)
        assert b.allow()
        b.note_probe()
        b.record_failure()
        assert b.state == "open" and not b.allow()
        clk.advance(1.1)                     # cooldown restarted
        assert b.allow() and b.state == "half_open"

    def test_gauge_encoding_covers_states(self):
        assert set(BREAKER_GAUGE) == {"closed", "open", "half_open"}


class TestFailureDetector:
    def test_crash_is_immediately_terminal(self):
        d = fleet.FailureDetector(max_consecutive_failures=99)
        assert d.observe_failure("r", fleet.ReplicaCrashed("x")) == "crashed"

    def test_consecutive_threshold_with_reset(self):
        d = fleet.FailureDetector(max_consecutive_failures=3)
        e = fleet.ReplicaUnavailable("flake")
        assert d.observe_failure("r", e) is None
        assert d.observe_failure("r", e) is None
        d.observe_success("r")               # healed: count resets
        assert d.observe_failure("r", e) is None
        assert d.observe_failure("r", e) is None
        assert d.observe_failure("r", e) is not None

    def test_health_verdicts(self):
        d = fleet.FailureDetector(probe_timeout_s=5.0)
        assert d.check_health("r", {"failed": True,
                                    "last_error": "boom"}) is not None
        # stale heartbeat only matters while work is pending
        idle = {"heartbeat_age_s": 99.0, "queue_depth": 0,
                "requests_in_flight": 0}
        assert d.check_health("r", idle) is None
        busy = {"heartbeat_age_s": 99.0, "queue_depth": 1,
                "requests_in_flight": 0}
        assert d.check_health("r", busy) is not None


# ---------------------------------------------------------------------------
# integration: eject + exactly-once redrive


class TestEjectRedrive:
    def test_crash_mid_burst_zero_lost_bit_identical(self, model_params,
                                                     warmed):
        """The acceptance battery: kill a replica mid-burst; nothing is
        lost and every redriven output is byte-identical to a
        failure-free run — with zero steady-state recompiles while
        detection + breakers are armed."""
        cap = 10
        prompts = _prompts(6)
        ref = _reference(warmed, prompts, cap)
        tracer = obs.Tracer()
        router, reps = _fleet(model_params, 3, tracer=tracer,
                              wrap={1: {}})
        chaos = reps[1]
        det = obs.RecompileDetector("fleet_chaos", warmup=0,
                                    registry=obs.MetricsRegistry())
        frids = [router.submit(p, cap) for p in prompts]
        # run until the chaos replica holds mid-decode work, then kill
        for _ in range(500):
            router.step()
            eng = chaos.inner.engine
            if any(0 < len(eng.scheduler.slots[i].generated) < cap
                   for i in eng.scheduler.decode_slots()):
                break
        else:
            pytest.skip("chaos replica never held mid-decode work")
        chaos.dead = True
        outs, rejects = _drain_fleet(router, frids)
        assert not rejects, f"unexpected sheds: {rejects}"
        for f, want in zip(frids, ref):
            np.testing.assert_array_equal(outs[f], want)
        assert chaos not in router.replicas
        assert router.ejected_total == 1 and router.redrives_total >= 1
        det.check()
        assert det.recompiles == 0
        names = {s.name for s in tracer.spans()}
        assert "router.eject" in names and "router.redrive" in names

    def test_redrive_shares_original_trace_id(self, model_params, warmed):
        tracer = obs.Tracer()
        router, reps = _fleet(model_params, 2, tracer=tracer,
                              wrap={0: {}})
        frids = [router.submit(p, 6) for p in _prompts(3, lo=3, hi=5)]
        router.step()
        reps[0].dead = True
        _drain_fleet(router, frids)
        redrives = [s for s in tracer.spans()
                    if s.name == "router.redrive"]
        assert redrives
        req_tids = {s.trace_id for s in tracer.spans()
                    if s.name == "router.route"}
        assert all(s.trace_id in req_tids for s in redrives), \
            "redrive spans must ride the request's original trace"

    def test_queued_requests_reroute_on_eject(self, model_params, warmed):
        # more requests than the chaos replica can admit: its queue
        # must re-route (observed empty -> plain resubmit)
        router, reps = _fleet(model_params, 2, wrap={0: {}}, warmed=warmed)
        prompts = _prompts(8, lo=3, hi=5)
        ref = _reference(warmed, prompts, 6)
        frids = [router.submit(p, 6) for p in prompts]
        reps[0].dead = True                  # dies before a single step
        outs, rejects = _drain_fleet(router, frids)
        assert not rejects
        for f, want in zip(frids, ref):
            np.testing.assert_array_equal(outs[f], want)

    def test_redrive_budget_exhausted_sheds_structured(self,
                                                       model_params, warmed):
        router, reps = _fleet(
            model_params, 2,
            faults=fleet.FaultPolicy(max_consecutive_failures=1,
                                     max_redrives=0), warmed=warmed)
        frids = [router.submit(p, 6) for p in _prompts(2, lo=3, hi=5)]
        router.step()
        router.eject_replica(reps[0], reason="crashed")
        outs, rejects = _drain_fleet(router, frids)
        assert rejects, "budget 0 must shed the ejected replica's work"
        for rej in rejects.values():
            assert rej.reason == "redrive_budget"
        # reject is pop-on-read
        assert all(router.reject_reason(f) is None for f in rejects)

    def test_expired_deadline_redrive_sheds_structured(self,
                                                       model_params, warmed):
        clk = FakeClock()
        router, reps = _fleet(model_params, 2, clock=clk, warmed=warmed)
        # a queued-only request (no token observed) with a TTFT deadline
        frid = router.submit(_prompts(1)[0], 6, ttft_deadline_s=0.5)
        rep = router._where[frid][0]
        clk.advance(1.0)                     # deadline long gone
        router.eject_replica(rep, reason="crashed")
        rej = router.reject_reason(frid)
        assert rej is not None and rej.reason == "deadline_expired"
        reg = router._reg
        assert reg.counter("fleet_redrive_shed_total").value(
            reason="deadline_expired") == 1

    def test_engine_side_shed_surfaces_at_router(self, model_params, warmed):
        """A replica's OWN engine shedding a queued request (TTFT
        deadline expired before admission) must surface as a fleet
        reject — result XOR reject, never silence — and clean the
        replay record."""
        router, reps = _fleet(model_params, 1, warmed=warmed)
        # fill both slots so the probe request has to queue
        busy = [router.submit(p, 16) for p in _prompts(2, lo=3, hi=5)]
        router.step()
        doomed = router.submit(_prompts(1)[0], 8, ttft_deadline_s=0.01)
        time.sleep(0.05)                 # deadline passes while queued
        for _ in range(50):
            router.step()
            if doomed not in router._reqs:
                break
        rej = router.reject_reason(doomed)
        assert rej is not None and rej.reason == "deadline_expired"
        assert router.result(doomed) is None
        assert doomed not in router._reqs and doomed not in router._where
        assert router._reg.counter("fleet_replica_shed_total").value(
            reason="deadline_expired") == 1
        outs, rejects = _drain_fleet(router, busy)
        assert not rejects and len(outs) == 2

    def test_live_deadline_survives_redrive(self, model_params, warmed):
        clk = FakeClock()
        router, reps = _fleet(model_params, 2, clock=clk, warmed=warmed)
        prompts = _prompts(1)
        ref = _reference(warmed, prompts, 6)
        frid = router.submit(prompts[0], 6, ttft_deadline_s=60.0)
        rep = router._where[frid][0]
        router.eject_replica(rep, reason="crashed")
        outs, rejects = _drain_fleet(router, [frid])
        assert not rejects
        np.testing.assert_array_equal(outs[frid], ref[0])


class TestWarmRedrive:
    def test_micro_checkpoint_restores_on_peer(self, model_params, warmed):
        """With snapshot_every_blocks on, a crash redrives WARM: the
        newest checkpoint restores into a peer (bounded re-decode) and
        outputs stay byte-identical."""
        cap = 12
        prompts = _prompts(2, lo=3, hi=5)
        ref = _reference(warmed, prompts, cap)
        tracer = obs.Tracer()
        router, reps = _fleet(model_params, 2, tracer=tracer,
                              wrap={0: {}}, snapshot_every_blocks=1)
        chaos = reps[0]
        frids = [router.submit(p, cap) for p in prompts]
        for _ in range(500):
            router.step()
            if any(rec.checkpoint is not None
                   for rec in router._reqs.values()):
                break
        else:
            pytest.fail("no micro-checkpoint ever reached the router")
        chaos.dead = True
        outs, rejects = _drain_fleet(router, frids)
        assert not rejects
        for f, want in zip(frids, ref):
            np.testing.assert_array_equal(outs[f], want)
        warm = router._reg.counter("fleet_redrive_total").value(
            mode="warm")
        assert warm >= 1, "warm restore path never used"
        modes = {s.attrs.get("mode") for s in tracer.spans()
                 if s.name == "router.redrive"}
        assert "warm" in modes

    def test_engine_refuses_speculative_checkpoints(self, model_params):
        model, params = model_params
        with pytest.raises(ValueError):
            serving.ServingEngine(model, params, num_slots=2,
                                  page_size=4, max_tokens_per_slot=32,
                                  draft_model=model, draft_params=params,
                                  spec_k=2, snapshot_every_blocks=1,
                                  registry=obs.MetricsRegistry())


class TestHangDetection:
    def test_hung_replica_ejected_work_redriven(self, model_params, warmed):
        prompts = _prompts(4, lo=3, hi=5)
        ref = _reference(warmed, prompts, 6)
        router, reps = _fleet(
            model_params, 2, wrap={1: {"hang_after_step": 2}},
            faults=fleet.FaultPolicy(max_consecutive_failures=1,
                                     probe_timeout_s=5.0), warmed=warmed)
        frids = [router.submit(p, 6) for p in prompts]
        outs, rejects = _drain_fleet(router, frids)
        assert not rejects
        assert router.ejected_total == 1
        assert reps[1] not in router.replicas
        for f, want in zip(frids, ref):
            np.testing.assert_array_equal(outs[f], want)


class TestThreadDeathSurfaced:
    def test_background_loop_crash_marks_replica_failed(self,
                                                        model_params, warmed):
        """Satellite regression: a raising step() in the background
        loop must not die silently — last_error recorded, failed set,
        health()/running() see it."""
        rep = fleet.LocalReplica(warmed(), name="t0")

        def boom():
            raise RuntimeError("kaboom in step")

        rep.engine.step = boom
        try:
            rep.start(idle_sleep_s=0.001)
            rep.submit(_prompts(1)[0], 4)
            for _ in range(200):
                if rep.failed:
                    break
                time.sleep(0.01)
            assert rep.failed and "kaboom" in rep.last_error
            assert rep.running() is False
            h = rep.health()
            assert h["failed"] and "kaboom" in h["last_error"]
        finally:
            rep.stop()
            del rep.engine.step             # the class's own again
        with pytest.raises(RuntimeError):
            rep.start()                      # no zombie restarts

    def test_router_ejects_failed_thread_replica(self, model_params, warmed):
        prompts = _prompts(2, lo=3, hi=5)
        ref = _reference(warmed, prompts, 6)
        router, reps = _fleet(model_params, 2, warmed=warmed)
        bad = reps[0]
        frids = [router.submit(p, 6) for p in prompts]
        # simulate what the background loop records on a step crash
        bad.failed = True
        bad.last_error = "RuntimeError: kaboom in step"
        outs, rejects = _drain_fleet(router, frids)
        assert not rejects
        assert bad not in router.replicas
        assert router._reg.counter("fleet_ejected_total").value(
            reason="replica_failed") == 1
        for f, want in zip(frids, ref):
            np.testing.assert_array_equal(outs[f], want)


class TestDrainVsCrashRace:
    def test_crash_mid_drain_falls_through_to_redrive(self,
                                                      model_params, warmed):
        """A replica that dies after drain_queue but before migration
        completes must not lose its in-flight requests — they fall
        through to the redrive path."""
        cap = 10
        prompts = _prompts(4)
        ref = _reference(warmed, prompts, cap)
        router, reps = _fleet(model_params, 2,
                              wrap={1: {"crash_on_snapshot": True}},
                              warmed=warmed)
        chaos = reps[1]
        frids = [router.submit(p, cap) for p in prompts]
        for _ in range(500):
            router.step()
            eng = chaos.inner.engine
            if any(0 < len(eng.scheduler.slots[i].generated) < cap
                   for i in eng.scheduler.decode_slots()):
                break
        else:
            pytest.skip("no mid-decode window on the chaos replica")
        router.drain_replica(chaos)          # dies at snapshot time
        assert chaos not in router.replicas
        assert router._reg.counter("fleet_drain_crash_total").value() == 1
        outs, rejects = _drain_fleet(router, frids)
        assert not rejects
        for f, want in zip(frids, ref):
            np.testing.assert_array_equal(outs[f], want)


# ---------------------------------------------------------------------------
# integration: circuit breaker through the router


class TestBreakerThroughRouter:
    def test_open_halfopen_closed_visible(self, model_params, warmed):
        clk = FakeClock()
        tracer = obs.Tracer()
        router, reps = _fleet(
            model_params, 2, tracer=tracer, clock=clk,
            wrap={1: {"submit_failures": 2}},
            faults=fleet.FaultPolicy(max_consecutive_failures=10,
                                     breaker_threshold=2,
                                     breaker_cooldown_s=5.0))
        # enough submits that p2c hits the flaky replica twice: its
        # breaker opens; the caller never sees a failure (peer retry)
        frids = [router.submit(p, 4) for p in _prompts(6, lo=3, hi=5)]
        name = reps[1].name
        assert (name, "closed", "open") in router.breaker_transitions
        assert not router.is_routable(reps[1])
        assert router.routable_count() == 1
        h = router.health()
        assert h["degraded"] and h["breakers"][name]["state"] == "open"
        outs, rejects = _drain_fleet(router, frids)
        assert not rejects and len(outs) == 6
        # cooldown passes; the next submit is routed as the deliberate
        # half-open probe; the chaos replica has healed -> closed
        clk.advance(6.0)
        probe = router.submit(_prompts(1)[0], 4)
        assert (name, "open", "half_open") in router.breaker_transitions
        assert (name, "half_open", "closed") in router.breaker_transitions
        assert router._where[probe][0] is reps[1], \
            "half-open probe must be routed to the recovering replica"
        outs, rejects = _drain_fleet(router, [probe])
        assert not rejects
        states = [s.attrs["to"] for s in tracer.spans()
                  if s.name == "fleet.breaker"]
        assert states == ["open", "half_open", "closed"]
        g = router._reg.gauge("fleet_breaker_state")
        assert g.value(replica=name) == BREAKER_GAUGE["closed"]

    def test_transient_health_flap_quarantines_not_ejects(self,
                                                          model_params,
                                                          warmed):
        """A transiently flaky health endpoint must trip the breaker
        (quarantine, which also stops the probing) BEFORE the
        consecutive-failure count reaches the death verdict — the
        replica stays in the fleet and recovers through the half-open
        probe."""
        router, reps = _fleet(
            model_params, 2, wrap={0: {"health_failures": 3}},
            faults=fleet.FaultPolicy(max_consecutive_failures=5,
                                     breaker_threshold=3,
                                     breaker_cooldown_s=0.0), warmed=warmed)
        name = reps[0].name
        for _ in range(6):               # idle fleet: probes flake
            router.step()
        assert reps[0] in router.replicas, "flake must not eject"
        assert router.ejected_total == 0
        assert (name, "closed", "open") in router.breaker_transitions
        # endpoint healed: the next submit probes the breaker shut and
        # the replica serves again
        frid = router.submit(_prompts(1)[0], 4)
        outs, rejects = _drain_fleet(router, [frid])
        assert not rejects
        assert (name, "half_open", "closed") in router.breaker_transitions

    def test_disabled_policy_restores_pr9_behavior(self, model_params, warmed):
        router, reps = _fleet(model_params, 2,
                              faults=fleet.FaultPolicy(enabled=False),
                              wrap={0: {"crash_on_step": 1}}, warmed=warmed)
        # p2c balances, so a few submits guarantee the chaos replica
        # holds work and gets stepped (a lone request may land on the
        # healthy peer and never touch it)
        for p in _prompts(4, lo=3, hi=5):
            router.submit(p, 4)
        assert not reps[0].inner.engine.scheduler.idle()
        with pytest.raises(fleet.ReplicaCrashed):
            router.run_until_idle(max_steps=50)
        # PR 9 contract: with faults disabled, health errors surface
        # instead of degrading to error-dicts / infinite load
        with pytest.raises(fleet.ReplicaCrashed):
            router.health()
        with pytest.raises(fleet.ReplicaCrashed):
            router._load(reps[0])


# ---------------------------------------------------------------------------
# autoscaler: lost capacity -> replacement


class _HealthFake(fleet.ReplicaHandle):
    def __init__(self, name, occupancy=0.5):
        self.name = name
        self.draining = False
        self.warmed = 0
        self.occupancy = occupancy

    def health(self):
        return {"queue_depth": 0, "requests_in_flight": 0,
                "slot_occupancy": self.occupancy, "slo": {}}

    def idle(self):
        return True

    def warmup(self):
        self.warmed += 1
        return self


class TestAutoscalerReplace:
    def _make(self, clk, n=2, min_replicas=2, max_replicas=4,
              occupancy=0.5, **asc_kw):
        spawned = []

        def spawn(i):
            r = _HealthFake(f"spawn{i}", occupancy=occupancy)
            spawned.append(r)
            return r

        asc = fleet.FleetAutoscaler(spawn, min_replicas=min_replicas,
                                    max_replicas=max_replicas,
                                    cooldown_s=10.0,
                                    registry=obs.MetricsRegistry(),
                                    clock=clk, **asc_kw)
        router = fleet.FleetRouter(
            [_HealthFake(f"f{i}", occupancy=occupancy)
             for i in range(n)],
            registry=obs.MetricsRegistry(),
            tracer=obs.Tracer(enabled=False), autoscaler=asc, clock=clk)
        return router, asc, spawned

    def test_ejection_below_floor_spawns_warmed_replacement(self):
        clk = FakeClock()
        router, asc, spawned = self._make(clk)
        router.eject_replica(router.replicas[0], reason="crashed")
        assert asc.tick() == "replace"
        assert len(spawned) == 1 and spawned[0].warmed == 1
        assert spawned[0] in router.replicas
        assert asc.events[-1]["action"] == "replace"
        # cooldown: an immediate second loss does not flap-spawn
        router.eject_replica(router.replicas[0], reason="crashed")
        assert asc.tick() is None
        clk.advance(11.0)
        assert asc.tick() == "replace"

    def test_open_breaker_counts_as_lost_capacity(self):
        clk = FakeClock()
        router, asc, spawned = self._make(clk)
        b = router._breaker(router.replicas[0])
        for _ in range(b.threshold):
            b.record_failure()
        assert router.routable_count() == 1
        assert asc.tick() == "replace"
        assert len(spawned) == 1

    def test_scale_in_with_no_routable_victim_is_a_noop(self):
        """Fleet-wide breaker flap at max_replicas: _scale_in must find
        no victim and return None — never crash the serve loop with
        min() over an empty sequence."""
        clk = FakeClock()
        router, asc, spawned = self._make(clk, n=2, min_replicas=1,
                                          max_replicas=2, occupancy=0.0,
                                          idle_s=1.0)
        for rep in router.replicas:
            b = router._breaker(rep)
            for _ in range(b.threshold):
                b.record_failure()
        assert router.routable_count() == 0
        assert asc.tick() is None        # starts the idle clock
        clk.advance(2.0)
        assert asc.tick() is None        # idle long enough: no victim
        assert not spawned and len(router.replicas) == 2

    def test_drain_never_replaced(self):
        clk = FakeClock()
        router, asc, spawned = self._make(clk, n=3, min_replicas=1)
        # voluntary shrink: replicas drop to 2, routable 2 >= min 1
        router.replicas[0].draining = True
        router.replicas.remove(router.replicas[0])
        assert asc.tick() is None
        assert not spawned


# ---------------------------------------------------------------------------
# exposition: the fleet-breaker /healthz section


class TestHealthzFleetBreakers:
    def test_degraded_503_while_breaker_open(self, model_params, warmed):
        router, reps = _fleet(model_params, 2, warmed=warmed)
        monitor = fleet.FleetMonitor(router,
                                     registry=obs.MetricsRegistry())
        srv = obs.ExpositionServer(registry=monitor.reg,
                                   tracer=router.tracer)
        srv.add_health("fleet", monitor.collect)
        status, payload = srv.healthz()
        assert status == "ok"
        b = router._breaker(reps[0])
        for _ in range(b.threshold):
            b.record_failure()
        status, payload = srv.healthz()
        assert status == "degraded"
        sect = payload["providers"]["fleet"]
        assert sect["breakers"][reps[0].name]["state"] == "open"
        assert sect["routable"] == 1
        assert monitor.reg.gauge("fleet_routable_replicas").value() == 1
