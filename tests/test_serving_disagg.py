"""Prefill/decode disaggregation (ISSUE 19): a flops-bound prefill
tier streaming prefill-complete slots into a KV-bound decode tier as
sha256-verified shard manifests (the live-migration transfer format).

The battery pins the acceptance: disaggregated greedy outputs are
BIT-IDENTICAL to the colocated fleet (fp and int8, tp=1 and tp=2, via
real shard manifests), a corrupt shard is refused all-or-nothing, no
request is ever lost (decode-capacity abort falls back to
decode-in-place, prefill/decode crashes redrive bit-identically), both
tiers run zero steady-state recompiles with per-tier bucket coverage,
the router never routes a fresh prompt to a decode-only replica, the
per-tier autoscaler scales each tier on ITS binding resource under a
fake clock, and the handoff is observable end to end (tier labels,
handoff counters, ``router.handoff`` spans on the request's trace,
``prefill_done_s``/``handoff_s``/``decode_start_s`` stamps)."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from paddle_tpu import observability as obs
from paddle_tpu.serving import fleet
from paddle_tpu.serving.engine import SlotMigrationError

from serving_taps import random_prompts as _prompts
from serving_taps import tiny_gpt, warmed_engines
from serving_taps import fleet_engine as _engine


@pytest.fixture(scope="module")
def model_params():
    return tiny_gpt()


@pytest.fixture(scope="module")
def warmed(model_params):
    """``get(**options) ->`` the engine of these options (``tier=`` one of
    the two, or colocated), warmed ONCE for the module and idle (``tests/
    serving_taps.py``); a case that crashes a replica builds its own."""
    return warmed_engines(model_params)


def _disagg_fleet(model_params, tracer=None, pre_kw=None, dec_kw=None,
                  warmed=None, **kw):
    """1 prefill + 1 decode LocalReplica behind a FleetRouter: over the
    module's engines with ``warmed``, else over two of their own."""
    tracer = tracer or obs.Tracer(enabled=False)
    if warmed is not None:
        engines = [warmed(tier="prefill", **kw), warmed(tier="decode", **kw)]
    else:
        engines = [_engine(model_params, tracer=tracer, tier=tier,
                           **dict(kw, **(own or {})))
                   for tier, own in (("prefill", pre_kw), ("decode", dec_kw))]
        for eng in engines:
            eng.warmup()
    pre, dec = (fleet.LocalReplica(eng, name=name)
                for eng, name in zip(engines, ("p0", "d0")))
    router = fleet.FleetRouter([pre, dec], policy="p2c", seed=0,
                               registry=obs.MetricsRegistry(), tracer=tracer)
    return router, pre, dec


def _reference(warmed, prompts, max_new, **kw):
    """Failure-free colocated reference, from the module's colocated
    engine of these options."""
    return [np.asarray(t) for t in warmed(**kw).generate_many(
        prompts, max_new, eos_id=None)]


def _drain(router, max_steps=3000):
    out = {}
    for _ in range(max_steps):
        out.update(router.step())
        if router.idle():
            break
    else:
        raise AssertionError("fleet not idle")
    return out


class TestDisaggParity:
    """Greedy tokens through the prefill -> handoff -> decode pipeline
    must be BIT-IDENTICAL to a colocated run — the handoff is the
    hash-verified migration format, so nothing may drift."""

    def test_fp_parity_and_streaming(self, model_params, warmed):
        prompts = _prompts(6)
        ref = _reference(warmed, prompts, 8)
        router, pre, dec = _disagg_fleet(model_params, warmed=warmed)
        came_in = dec.engine.migrated_in_total
        frids = [router.submit(p, 8) for p in prompts]
        _drain(router)
        outs = [router.result(f) for f in frids]
        assert all(o is not None for o in outs)
        assert all(np.array_equal(o, r) for o, r in zip(outs, ref))
        # every request crossed the tier boundary
        assert router.handoffs_total == len(prompts)
        # decode happened on the decode tier, not in place
        assert dec.engine.migrated_in_total - came_in == len(prompts)

    def test_int8_parity(self, model_params, warmed):
        prompts = _prompts(4, rng=np.random.default_rng(7))
        ref = _reference(warmed, prompts, 6,
                         cache_dtype=jnp.int8, num_pages=65)
        router, _pre, _dec = _disagg_fleet(
            model_params, warmed=warmed, cache_dtype=jnp.int8, num_pages=65)
        frids = [router.submit(p, 6) for p in prompts]
        _drain(router)
        outs = [router.result(f) for f in frids]
        assert all(np.array_equal(o, r) for o, r in zip(outs, ref))
        assert router.handoffs_total == len(prompts)

    @pytest.mark.skipif(len(jax.devices()) < 4,
                        reason="tp tests need >= 4 (virtual) devices")
    def test_tp2_parity_real_shard_manifests(self, model_params, warmed):
        """tp=2 on both tiers: the prefill tier runs the REAL Megatron
        MLP shard (ffn column/row split, second psum) and the handoff
        carries per-(page, tp-shard) manifests; decode must still be
        bit-identical to the tp=1 colocated reference."""
        from paddle_tpu.core.mesh import MeshConfig, make_mesh
        prompts = _prompts(4, rng=np.random.default_rng(3))
        ref = _reference(warmed, prompts, 6)
        kw = dict(page_size=8, max_tokens_per_slot=64)

        def mesh():
            return make_mesh(MeshConfig(tp=2),
                             devices=jax.devices()[:2])

        router, pre, _dec = _disagg_fleet(
            model_params, pre_kw={"mesh": mesh()},
            dec_kw={"mesh": mesh()}, **kw)
        assert pre.engine._mlp_sharded, \
            "prefill tier must run the sharded MLP"
        frids = [router.submit(p, 6) for p in prompts]
        _drain(router)
        outs = [router.result(f) for f in frids]
        assert all(np.array_equal(o, r) for o, r in zip(outs, ref))
        assert router.handoffs_total == len(prompts)

    def test_corrupt_shard_refused_all_or_nothing(self, model_params, warmed):
        """A flipped bit in one page shard must fail the sha256 check
        BEFORE anything is written: the decode engine stays empty and
        the same snapshot restores cleanly elsewhere."""
        pre, dec = warmed(tier="prefill"), warmed(tier="decode")
        pre.submit(_prompts(1)[0], 8)
        handoffs = []
        for _ in range(50):
            pre.step()
            handoffs = pre.poll_handoffs()
            if handoffs:
                break
        (rid, snap), = handoffs
        evil = dict(snap, shards=[np.array(s, copy=True)
                                  for s in snap["shards"]])
        flat = evil["shards"][0].reshape(-1)
        flat[0] = flat[0] + 1
        with pytest.raises(SlotMigrationError):
            dec.restore_slot(evil)
        assert not dec.scheduler.active_slots(), \
            "corrupt restore must write NOTHING"
        # the pristine snapshot still restores (nothing was consumed)
        nrid = dec.restore_slot(snap)
        assert nrid in {st.request.rid
                        for s in dec.scheduler.active_slots()
                        for st in [dec.scheduler.slots[s]]}

    def test_decode_tier_mid_prefill_restore_refused(self, model_params,
                                                     warmed):
        """Decode-tier engines restore only prefill-COMPLETE slots."""
        src = _engine(model_params, prefill_budget=4)
        dec = warmed(tier="decode")
        p = np.arange(1, 17, dtype=np.int32)     # 16 tokens, chunk=4
        src.submit(p, 8)
        slot = None
        for _ in range(50):                      # stop mid-prefill
            src.step()
            mid = [s for s in src.scheduler.active_slots()
                   if not src.scheduler.slots[s].prefill_done]
            if mid:
                slot = mid[0]
                break
        assert slot is not None, "never observed a mid-prefill slot"
        snap = src.snapshot_slot(slot)
        with pytest.raises(SlotMigrationError, match="prefill-complete"):
            dec.restore_slot(snap)


class TestNoLostRequests:
    def test_decode_capacity_abort_decodes_in_place(self, model_params,
                                                    warmed):
        """Decode tier too small for the wave: the unplaceable handoff
        restores BACK into the prefill replica with the
        decode-in-place marker — every request still finishes with
        bit-identical tokens, none lost, no Reject needed."""
        prompts = _prompts(6)
        ref = _reference(warmed, prompts, 8)
        reg = obs.MetricsRegistry()
        # (decoding in place compiles decode programs on a prefill tier:
        # not on the module's, which must never compile after warm-up)
        pre = fleet.LocalReplica(
            _engine(model_params, tier="prefill"), name="p0").warmup()
        dec = fleet.LocalReplica(
            _engine(model_params, tier="decode", num_slots=2,
                    num_pages=17), name="d0").warmup()
        router = fleet.FleetRouter([pre, dec], policy="p2c",
                                   registry=reg, seed=0)
        frids = [router.submit(p, 8) for p in prompts]
        _drain(router)
        outs = [router.result(f) for f in frids]
        assert all(o is not None for o in outs), "request lost"
        assert all(np.array_equal(o, r) for o, r in zip(outs, ref))
        fb = reg.get("fleet_handoff_fallback_total")
        assert fb is not None and fb.value(replica="p0") > 0, \
            "expected at least one decode-in-place fallback"

    def test_prefill_crash_mid_handoff_redrives_bit_identical(
            self, model_params, warmed):
        """ChaosReplica kills the prefill replica exactly at
        poll_handoffs: in-flight requests redrive from the replay
        records onto the surviving colocated peer, outputs
        bit-identical, 0 lost."""
        prompts = _prompts(4)
        ref = _reference(warmed, prompts, 8)
        tracer = obs.Tracer(enabled=False)
        pre = fleet.ChaosReplica(
            fleet.LocalReplica(
                _engine(model_params, tier="prefill"),
                name="p0").warmup(),
            crash_on_handoff=True)
        # the survivor is colocated so redriven prompts can decode
        colo = fleet.LocalReplica(warmed(), name="c0")
        router = fleet.FleetRouter(
            [pre, colo], policy="p2c", registry=obs.MetricsRegistry(),
            tracer=tracer, seed=0,
            faults=fleet.FaultPolicy(max_consecutive_failures=1,
                                     probe_timeout_s=30.0))
        frids = [router.submit(p, 8) for p in prompts]
        _drain(router)
        done, shed = 0, 0
        for f, r in zip(frids, ref):
            out = router.result(f)
            if out is not None:
                assert np.array_equal(out, r), \
                    "redriven output diverged"
                done += 1
            else:
                assert router.reject_reason(f) is not None, \
                    f"request {f} silently lost"
                shed += 1
        assert done + shed == len(frids)
        assert done > 0
        assert pre not in router.replicas, "dead prefill not ejected"

    def test_decode_crash_mid_restore_no_lost(self, model_params, warmed):
        """ChaosReplica kills the decode replica at restore(): the
        handoff placement fails over (decode-in-place on the source),
        the dead replica is ejected, and every request completes or
        sheds with a structured reason."""
        prompts = _prompts(4)
        ref = _reference(warmed, prompts, 8)
        pre = fleet.LocalReplica(
            _engine(model_params, tier="prefill"), name="p0").warmup()
        dec = fleet.ChaosReplica(
            fleet.LocalReplica(
                _engine(model_params, tier="decode"),
                name="d0").warmup(),
            crash_on_restore=True)
        router = fleet.FleetRouter(
            [pre, dec], policy="p2c", registry=obs.MetricsRegistry(),
            seed=0,
            faults=fleet.FaultPolicy(max_consecutive_failures=1,
                                     probe_timeout_s=30.0))
        frids = [router.submit(p, 8) for p in prompts]
        _drain(router)
        done, shed = 0, 0
        for f, r in zip(frids, ref):
            out = router.result(f)
            if out is not None:
                assert np.array_equal(out, r)
                done += 1
            elif router.reject_reason(f) is not None:
                shed += 1
            else:
                raise AssertionError(f"request {f} silently lost")
        assert done + shed == len(frids)
        assert done > 0


class TestTierContracts:
    def test_decode_tier_refuses_fresh_prompts(self, model_params, warmed):
        eng = warmed(tier="decode")
        with pytest.raises(ValueError, match="restored slots"):
            eng.submit(_prompts(1)[0], 4)

    def test_router_never_routes_prompts_to_decode_tier(
            self, model_params, warmed):
        router, pre, dec = _disagg_fleet(model_params, warmed=warmed)
        for p in _prompts(6):
            router.submit(p, 4)
        # every submit landed on the prefill replica
        assert dec.engine.scheduler.queue_depth() == 0
        assert not dec.engine.scheduler.active_slots()
        assert pre.engine.scheduler.queue_depth() \
            + len(pre.engine.scheduler.active_slots()) == 6
        _drain(router)

    def test_decode_only_fleet_has_no_prompt_candidates(
            self, model_params, warmed):
        dec = fleet.LocalReplica(warmed(tier="decode"), name="d0")
        router = fleet.FleetRouter([dec], policy="p2c",
                                   registry=obs.MetricsRegistry())
        with pytest.raises(SlotMigrationError, match="no routable"):
            router.submit(_prompts(1)[0], 4)

    def test_tier_validation(self, model_params):
        with pytest.raises(ValueError, match="tier"):
            _engine(model_params, tier="frontend")

    def test_zero_recompiles_and_bucket_coverage_both_tiers(
            self, model_params):
        """Post-warmup steady state compiles NOTHING on either tier,
        and each tier's warmup plan covers exactly its reachable
        signatures (prefill never compiles decode buckets, decode
        never compiles prefill buckets). (Engines of the case's own,
        new from warm-up: on the module's, a bucket that warm-up missed
        would have been compiled by an earlier case.)"""
        router, pre, dec = _disagg_fleet(model_params)
        for eng, tier in ((pre.engine, "prefill"),
                          (dec.engine, "decode")):
            plan = set(eng.warmup_plan())
            reach = eng.reachable_signatures()
            assert plan >= reach, \
                f"{tier} coverage hole: {reach - plan}"
        kinds_pre = {s[0] for s in pre.engine.warmup_plan()}
        kinds_dec = {s[0] for s in dec.engine.warmup_plan()}
        assert "decode" not in kinds_pre and "prefill" in kinds_pre
        assert "prefill" not in kinds_dec and "decode" in kinds_dec
        frids = [router.submit(p, 8) for p in _prompts(6)]
        _drain(router)
        assert all(router.result(f) is not None for f in frids)
        assert pre.engine.recompile_detector.recompiles == 0, \
            "prefill tier recompiled in steady state"
        assert dec.engine.recompile_detector.recompiles == 0, \
            "decode tier recompiled in steady state"


class TestDisaggObservability:
    def test_health_tier_and_handoff_counters(self, model_params, warmed):
        router, pre, dec = _disagg_fleet(model_params, warmed=warmed)
        reg = router._reg
        frids = [router.submit(p, 6) for p in _prompts(4)]
        _drain(router)
        h = router.health()
        assert h["per_replica"]["p0"]["tier"] == "prefill"
        assert h["per_replica"]["d0"]["tier"] == "decode"
        assert h["handoffs_total"] == len(frids)
        assert reg.counter("fleet_handoff_total",
                           "x").value(src="p0", dst="d0") == len(frids)
        assert reg.counter("fleet_handoff_bytes_total",
                           "x").value(src="p0", dst="d0") > 0

    def test_colocated_health_has_no_tier_surprises(self, model_params,
                                                    warmed):
        """A colocated engine advertises tier="colocated" and the
        monitor's per-replica gauges keep their exact pre-tier label
        sets (no tier label) — dashboards stay byte-identical."""
        eng = warmed()
        assert eng.health()["tier"] == "colocated"
        rep = fleet.LocalReplica(eng, name="m0")
        reg = obs.MetricsRegistry()
        router = fleet.FleetRouter([rep], policy="p2c", registry=reg)
        mon = fleet.FleetMonitor(router, registry=reg)
        mon.collect()
        assert reg.get("fleet_replica_queue_depth") \
            .value(replica="m0") == 0.0

    def test_monitor_tier_labels_on_tiered_fleet(self, model_params, warmed):
        router, _pre, _dec = _disagg_fleet(model_params, warmed=warmed)
        reg = obs.MetricsRegistry()
        mon = fleet.FleetMonitor(router, registry=reg)
        mon.collect()
        g = reg.get("fleet_replica_slot_occupancy")
        assert g.value(replica="p0", tier="prefill") == 0.0
        assert g.value(replica="d0", tier="decode") == 0.0

    def test_handoff_span_and_phase_stamps(self, model_params,
                                           tmp_path):
        """The router.handoff span rides the request's ONE trace id,
        request_stats carries ordered prefill_done_s <= handoff_s <=
        decode_start_s, and the exported trace passes
        check_metrics_log --trace (which validates handoff spans)."""
        tracer = obs.Tracer(capacity=4096)
        router, _pre, _dec = _disagg_fleet(model_params, tracer=tracer)
        frid = router.submit(_prompts(1)[0], 6)
        tid = router.trace_id(frid)
        assert tid
        _drain(router)
        st = router.request_stats(frid)
        assert st is not None
        assert 0 < st["prefill_done_s"] <= st["handoff_s"] \
            <= st["decode_start_s"]
        spans = [s for s in tracer.spans()
                 if s.name == "router.handoff"]
        assert spans, "no router.handoff span recorded"
        assert all(s.trace_id == tid for s in spans)
        assert spans[0].attrs["src"] == "p0"
        assert spans[0].attrs["dst"] == "d0"
        assert spans[0].attrs["bytes"] > 0
        path = str(tmp_path / "trace.jsonl")
        tracer.export_jsonl(path)
        from paddle_tpu.observability.tracing import validate_trace_log
        assert validate_trace_log(path, require_spans=1) > 0

    def test_trace_validator_rejects_bad_handoff_span(self):
        from paddle_tpu.observability.tracing import \
            validate_trace_record
        good = {"kind": "span", "trace_id": 7, "span_id": 1,
                "parent_id": 0, "name": "router.handoff", "ts": 1.0,
                "dur_s": 0.0, "attrs": {"src": "p0", "dst": "d0"}}
        validate_trace_record(good)
        with pytest.raises(ValueError, match="src"):
            validate_trace_record(
                dict(good, attrs={"dst": "d0"}))
        with pytest.raises(ValueError, match="trace_id=0"):
            validate_trace_record(dict(good, trace_id=0))
        with pytest.raises(ValueError, match="dst"):
            validate_trace_record(dict(good, attrs={"src": "p0"}))
        # a fallback handoff span legitimately has no dst
        validate_trace_record(dict(good, attrs={"src": "p0"},
                                   status="decode_in_place"))


class _FakeTiered(fleet.ReplicaHandle):
    """Health-only fake for autoscaler decision tests: a tier plus the
    headroom plane the per-tier signals read."""

    def __init__(self, name, tier, *, flops=1.0, pages=1.0, slots=1.0,
                 queue=0):
        self.name = name
        self.tier = tier
        self.flops = flops
        self.pages = pages
        self.slots = slots
        self.queue = queue
        self.warmed = False
        self.closed = False

    def page_size(self):
        return 4

    def prefix_digests(self):
        return frozenset()

    def health(self):
        return {"tier": self.tier, "queue_depth": self.queue,
                "requests_in_flight": 0, "slot_occupancy": 0.0,
                "page_utilization": 0.0,
                "headroom": {"flops": self.flops, "pages": self.pages,
                             "slots": self.slots, "hbm": 1.0}}

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


class TestTieredAutoscaler:
    def _scaler(self, tiers, **kw):
        kw.setdefault("sustain_s", 2.0)
        kw.setdefault("idle_s", 5.0)
        kw.setdefault("cooldown_s", 3.0)
        clock = [0.0]
        a = fleet.FleetAutoscaler(lambda i: None, tiers=tiers,
                                  registry=obs.MetricsRegistry(),
                                  clock=lambda: clock[0], **kw)
        return a, clock

    def test_prefill_scales_on_queue_pressure_decode_untouched(self):
        spawned = []

        def spawn(i):
            r = _FakeTiered(f"p{i}", "prefill")
            spawned.append(r)
            return r

        tiers = {"prefill": {"spawn": spawn, "min": 1, "max": 3,
                             "queue_hot": 4},
                 "decode": {"spawn": lambda i: _FakeTiered(
                     f"d{i}", "decode"), "min": 1, "max": 3}}
        a, clock = self._scaler(tiers)
        pre = _FakeTiered("p0", "prefill", queue=8)
        dec = _FakeTiered("d0", "decode")
        router = fleet.FleetRouter([pre, dec], policy="p2c",
                                   registry=obs.MetricsRegistry(),
                                   autoscaler=a)
        assert a.tick() is None            # hot, not sustained
        clock[0] = 2.5
        assert a.tick() == "scale_out:prefill"
        assert spawned and spawned[0].warmed and spawned[0].tier == \
            "prefill"
        assert len(router.replicas) == 3
        clock[0] = 4.0                     # prefill cooldown holds
        assert a.tick() is None

    def test_decode_scales_on_kv_headroom(self):
        spawned = []

        def spawn(i):
            r = _FakeTiered(f"d{i}", "decode")
            spawned.append(r)
            return r

        tiers = {"decode": {"spawn": spawn, "min": 1, "max": 2,
                            "headroom_floor": 0.25}}
        a, clock = self._scaler(tiers)
        pre = _FakeTiered("p0", "prefill")
        dec = _FakeTiered("d0", "decode", pages=0.1)   # KV-starved
        router = fleet.FleetRouter([pre, dec], policy="p2c",
                                   registry=obs.MetricsRegistry(),
                                   autoscaler=a)
        assert a.tick() is None
        clock[0] = 2.5
        assert a.tick() == "scale_out:decode"
        assert len(router.replicas) == 3
        dec.pages = 0.9
        spawned[0].pages = 0.9
        # max reached: pressure again never exceeds the tier cap
        dec.pages = 0.1
        clock[0] = 10.0
        assert a.tick() is None
        clock[0] = 13.0
        assert a.tick() is None, "scaled past the decode tier max"

    def test_per_tier_scale_in_on_idle(self, monkeypatch):
        tiers = {"prefill": {"spawn": lambda i: None, "min": 1,
                             "max": 3},
                 "decode": {"spawn": lambda i: None, "min": 1,
                            "max": 3}}
        a, clock = self._scaler(tiers)
        p0, p1 = (_FakeTiered("p0", "prefill"),
                  _FakeTiered("p1", "prefill"))
        dec = _FakeTiered("d0", "decode")
        router = fleet.FleetRouter([p0, p1, dec], policy="p2c",
                                   registry=obs.MetricsRegistry(),
                                   autoscaler=a)
        drained = []
        monkeypatch.setattr(router, "drain_replica",
                            lambda rep, **kw: drained.append(rep) or 0)
        assert a.tick() is None            # idle starts counting
        clock[0] = 5.5
        assert a.tick() == "scale_in:prefill"
        assert drained and drained[0].tier == "prefill"
        # decode tier holds at its min=1 — never drained
        assert all(r.tier != "decode" for r in drained)

    def test_tier_replace_restores_lost_capacity(self):
        spawned = []

        def spawn(i):
            r = _FakeTiered(f"d{i}", "decode")
            spawned.append(r)
            return r

        tiers = {"decode": {"spawn": spawn, "min": 1, "max": 2}}
        a, clock = self._scaler(tiers)
        pre = _FakeTiered("p0", "prefill")
        dec = _FakeTiered("d0", "decode")
        router = fleet.FleetRouter([pre, dec], policy="p2c",
                                   registry=obs.MetricsRegistry(),
                                   autoscaler=a)
        dec.draining = True                # decode capacity gone
        assert a.tick() == "replace:decode"
        assert spawned and spawned[0].warmed
        assert a.events[-1]["action"] == "replace"
        assert a.events[-1]["tier"] == "decode"

    def test_tiers_config_validation(self):
        with pytest.raises(ValueError, match="unknown tier"):
            fleet.FleetAutoscaler(lambda i: None,
                                  tiers={"frontend": {"spawn":
                                                      lambda i: None}})
        with pytest.raises(ValueError, match="spawn"):
            fleet.FleetAutoscaler(lambda i: None,
                                  tiers={"prefill": {}})
