"""Step-time anatomy, resource headroom, crash flight recorder (ISSUE 16).

Three surfaces under test:

- :class:`StepAnatomy`: per-jitted-step wall-time decomposition (host
  gap / phase-split call wall time / host remainder / sampled
  collective-exposed time) with a bounded ring, schema validators, and
  the metrics/trace fan-out;
- the resource-headroom plane: ``engine.health()["headroom"]`` (flops /
  pages / slots / HBM), separable across prefill-heavy vs decode-heavy
  workloads, aggregated fleet-wide by :class:`FleetMonitor` (which must
  also DROP a vanished replica's labeled series — the stale-gauge
  regression);
- :class:`FlightRecorder`: the bounded black box whose postmortem
  bundles the router dumps on eject / breaker-open, trace-id-linked to
  the victim requests and schema-validated end to end (CLI included).
"""

import json
import os
import sys
import time
import urllib.request

import jax
import numpy as np
import pytest

from paddle_tpu import observability as obs
from paddle_tpu import serving
from paddle_tpu.observability import anatomy as anat
from paddle_tpu.observability import flight as flt
from paddle_tpu.serving import fleet
from paddle_tpu.serving.fleet.router import FleetMonitor
from paddle_tpu.models.gpt import GPT, GPTConfig

VOCAB = 64

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "tools"))


@pytest.fixture(scope="module")
def model_params():
    cfg = GPTConfig.tiny(vocab_size=VOCAB, hidden_size=16, num_layers=2,
                         num_heads=2, ffn_size=32, max_position=96,
                         dropout=0.0, attn_impl="xla")
    model = GPT(cfg)
    return model, model.init(jax.random.PRNGKey(0))


def _engine(model_params, tracer=None, **kw):
    model, params = model_params
    kw.setdefault("num_slots", 2)
    kw.setdefault("page_size", 4)
    # small on purpose: warmup compiles every reachable signature, and
    # this file builds four engines — keep the bucket set minimal
    kw.setdefault("max_tokens_per_slot", 16)
    kw.setdefault("prefill_chunk", 4)
    kw.setdefault("decode_block", 2)
    return serving.ServingEngine(model, params, attn_impl="lax",
                                 registry=obs.MetricsRegistry(),
                                 tracer=tracer, **kw)


# ---------------------------------------------------------------------------
# StepAnatomy: pure host-side unit surface
# ---------------------------------------------------------------------------

class TestStepAnatomy:
    def test_record_schema_metrics_and_spans(self):
        """A record's wall and phases are the ``serving.*`` phase spans'
        own clock reads; the anatomy records no span of its own."""
        reg = obs.MetricsRegistry()
        tracer = obs.Tracer(enabled=True)
        a = obs.StepAnatomy(registry=reg)
        with tracer.phase("serving.step", step=1) as step_ph:
            a.begin_step(1, t0=step_ph.start)
            with tracer.phase("serving.prefill.dispatch") as pre:
                time.sleep(0.001)
            a.add_phase("prefill", pre.start, pre.end)
            with tracer.phase("serving.decode.sync") as dec:
                time.sleep(0.0015)
            a.add_phase("decode", dec.start, dec.end)
        rec = a.end_step(tokens=3, t1=step_ph.end)
        anat.validate_anatomy_record(rec)
        assert rec["step"] == 1 and rec["tokens"] == 3
        spans = {s.name: s for s in tracer.spans()}
        assert set(spans) == {"serving.step", "serving.prefill.dispatch",
                              "serving.decode.sync"}
        assert rec["phases"]["decode"] == round(
            spans["serving.decode.sync"].duration_s, 9) >= 0.0015
        assert rec["wall_s"] == round(spans["serving.step"].duration_s, 9)
        assert reg.counter("anatomy_steps_total").value() == 1
        assert reg.histogram("anatomy_phase_seconds").summary(
            phase="decode")["count"] == 1

    def test_ring_bounded_under_10k_steps(self):
        """The black-box discipline: 10k steps leave the ring at its
        capacity, the flight recorder's snapshot ring at its capacity,
        and the whole-run summary still exact."""
        a = obs.StepAnatomy(capacity=256)
        fr = obs.FlightRecorder("r", anatomy=a, capacity=64,
                                snapshot_every=8)
        for i in range(10_000):
            a.begin_step(i + 1)
            t = a.now()
            a.add_phase("decode", t, t)     # zero-width: wall-safe
            a.end_step(tokens=1)
            fr.note({"queue_depth": i})
        assert len(a) == 256
        recs = a.records()
        assert anat.validate_anatomy_records(recs) == 256
        assert recs[-1]["step"] == 10_000
        s = a.summary()
        assert s["steps"] == 10_000 and s["tokens"] == 10_000
        assert len(fr.snapshots()) == 64
        # the bundle ring is bounded too
        for _ in range(3 * flt.MAX_BUNDLES_KEPT):
            fr.dump("test")
        assert len(fr.bundles()) == flt.MAX_BUNDLES_KEPT

    def test_cancel_step_keeps_host_gap_honest(self):
        """Idle engine ticks (begin then cancel) must not count the
        idle wait as host gap on the next real step."""
        a = obs.StepAnatomy()
        a.begin_step()
        a.end_step()
        for _ in range(5):      # idle ticks
            a.begin_step()
            time.sleep(0.002)
            a.cancel_step()
        a.begin_step()
        rec = a.end_step()
        assert rec["host_gap_s"] < 0.002
        assert a.summary()["steps"] == 2

    @pytest.mark.parametrize("call, kept", [
        ((2, 2, 4, 100, 0.003), [2, 2, 4, 100, 0.003]),
        ((6, 8, 4, 100, 0.003, 3), [6, 8, 4, 100, 0.003, 3])])
    def test_a_steps_record_keeps_each_calls_longest_run(self, call, kept):
        """``end_step(prefill_calls=)`` takes a call with or without its
        longest run and keeps what it was given."""
        a = obs.StepAnatomy()
        a.begin_step(1)
        a.end_step(parts={anat.OTHER_PART: 0.0}, prefill_calls=[call])
        rec = a.last()
        assert rec["prefill_calls"] == [kept]

    def test_validators_reject_malformed(self, tmp_path):
        a = obs.StepAnatomy()
        a.begin_step(5)
        good = a.end_step()
        bad_kind = dict(good, kind="step")
        with pytest.raises(ValueError, match="kind"):
            anat.validate_anatomy_record(bad_kind)
        with pytest.raises(ValueError, match="monotonic"):
            anat.validate_anatomy_record(good, prev_step=7)
        overfull = dict(good, phases={"decode": good["wall_s"] + 1.0})
        with pytest.raises(ValueError, match="exceeds wall"):
            anat.validate_anatomy_record(overfull)
        with pytest.raises(ValueError, match="negative|nonneg|>= 0"):
            anat.validate_anatomy_record(dict(good, host_gap_s=-1.0))
        # a prefill call of the step's own record: five fields, or six
        # with the call's longest run (PR 54), which lies in 1..lanes_live
        for call in ([3, 4, 2, 12, 0.001], [3, 4, 2, 12, 0.001, 1],
                     [3, 4, 2, 12, 0.001, 3]):
            anat.validate_anatomy_record(dict(good, prefill_calls=[call]))
        for call in ([3, 4, 2, 12, 0.001, 4], [3, 4, 2, 12, 0.001, 0],
                     [5, 4, 2, 12, 0.001, 1], [3, 4, 2, 12, 0.001, 1, 1]):
            with pytest.raises(ValueError, match="prefill_calls"):
                anat.validate_anatomy_record(
                    dict(good, prefill_calls=[call]))
        p = tmp_path / "anat.jsonl"
        a.export_jsonl(str(p))
        assert anat.validate_anatomy_log(str(p), require_steps=1) == 1
        with pytest.raises(ValueError):
            anat.validate_anatomy_log(str(p), require_steps=2)


# ---------------------------------------------------------------------------
# FlightRecorder: bundles, files, CLI
# ---------------------------------------------------------------------------

class TestFlightRecorder:
    def _bundle(self):
        reg = obs.MetricsRegistry()
        tracer = obs.Tracer(enabled=True)
        a = obs.StepAnatomy(registry=reg)
        fr = obs.FlightRecorder("rX", anatomy=a, registry=reg,
                                tracer=tracer, snapshot_every=1)
        for i in range(4):
            a.begin_step(i + 1)
            a.end_step(tokens=2)
            fr.note({"queue_depth": i, "requests_in_flight": 1,
                     "slot_occupancy": 0.5,
                     "headroom": {"flops": 0.5, "pages": 0.5,
                                  "slots": 0.5, "hbm": 0.5}})
        return fr.dump("eject", trace_ids=(7, 3, 7),
                       extra={"cause": "crashed"})

    def test_dump_roundtrip_and_validation(self, tmp_path):
        b = self._bundle()
        obs.validate_postmortem_bundle(b)
        assert b["schema"] == obs.POSTMORTEM_SCHEMA
        assert b["replica"] == "rX" and b["reason"] == "eject"
        assert b["trace_ids"] == [3, 7]         # deduped, sorted
        assert len(b["snapshots"]) == 4
        assert anat.validate_anatomy_records(b["anatomy"]) == 4
        p = str(tmp_path / "pm.json")
        obs.write_bundle(b, p)
        got = obs.validate_postmortem_file(p)
        assert got["trace_ids"] == [3, 7]
        with pytest.raises(ValueError, match="schema"):
            obs.validate_postmortem_bundle(dict(b, schema="nope"))
        with pytest.raises(ValueError, match="reason"):
            obs.validate_postmortem_bundle(dict(b, reason=""))

    def test_cli_anatomy_and_postmortem_modes(self, tmp_path):
        from check_metrics_log import main as check_main
        a = obs.StepAnatomy()
        for i in range(3):
            a.begin_step(i + 1)
            a.end_step()
        alog = str(tmp_path / "a.jsonl")
        a.export_jsonl(alog)
        assert check_main([alog, "--anatomy", "--require-steps", "3"]) == 0
        assert check_main([alog, "--anatomy", "--require-steps", "9"]) == 1
        p = str(tmp_path / "pm.json")
        obs.write_bundle(self._bundle(), p)
        assert check_main([p, "--postmortem"]) == 0
        with pytest.raises(SystemExit):    # exclusive modes fail fast
            check_main([p, "--postmortem", "--anatomy"])
        with pytest.raises(SystemExit):
            check_main([p, "--postmortem", "--require-steps", "1"])

    def test_offline_renderer(self, tmp_path, capsys):
        from postmortem import main as pm_main
        p = str(tmp_path / "pm.json")
        obs.write_bundle(self._bundle(), p)
        # NOT .json: directory mode below globs *.json as bundles
        trace_out = str(tmp_path / "trace.out")
        assert pm_main([p, "--trace-out", trace_out]) == 0
        out = capsys.readouterr().out
        assert "reason=eject" in out and "trace ids [3, 7]" in out
        obs.chrome_trace_valid(json.load(open(trace_out)))
        # a directory of bundles renders too; an invalid one fails
        assert pm_main([str(tmp_path)]) == 0
        with open(str(tmp_path / "bad.json"), "w") as f:
            json.dump({"schema": "nope"}, f)
        assert pm_main([str(tmp_path)]) == 1


# ---------------------------------------------------------------------------
# registry series removal (the FleetMonitor stale-gauge contract)
# ---------------------------------------------------------------------------

class TestSeriesRemoval:
    def test_remove_and_remove_matching(self):
        reg = obs.MetricsRegistry()
        g = reg.gauge("g", "h")
        g.set(1.0, replica="a", resource="pages")
        g.set(2.0, replica="a", resource="flops")
        g.set(3.0, replica="b", resource="pages")
        assert g.remove(replica="a", resource="flops") is True
        assert g.remove(replica="a", resource="flops") is False
        assert g.remove_matching(replica="a") == 1
        assert [dict(k)["replica"] for k in g.labels_seen()] == ["b"]
        assert g.remove_matching(replica="zzz") == 0


class TestAutoscalerHeadroomFloor:
    def _auto(self, floor, pages):
        a = fleet.FleetAutoscaler(lambda i: None, headroom_floor=floor,
                                  registry=obs.MetricsRegistry())

        class _R:
            replicas = [object()]

            @staticmethod
            def health():
                return {"queue_depth_total": 0,
                        "slot_occupancy_mean": 0.0,
                        "per_replica": {"r0": {"headroom": {
                            "pages": pages, "slots": 1.0, "hbm": 1.0}}}}

        a.bind(_R())
        return a

    def test_floor_vetoes_idle_scale_in(self):
        """A replica still pinning KV pages is not idle, however empty
        its occupancy reads — but only when the operator opted into the
        floor (default 0.0 keeps pure-occupancy scale-in timing)."""
        assert self._auto(0.5, pages=0.2)._fleet_idle() is False
        assert self._auto(0.5, pages=0.9)._fleet_idle() is True
        assert self._auto(0.0, pages=0.2)._fleet_idle() is True


# ---------------------------------------------------------------------------
# engine integration: anatomy + headroom on the real serving loop
# ---------------------------------------------------------------------------

class TestEngineAnatomy:
    @pytest.fixture(scope="class")
    def eng(self, model_params):
        e = _engine(model_params)
        e.warmup()              # cost gauges on: the flops plane is live
        return e

    def test_anatomy_records_and_report(self, model_params, eng):
        rng = np.random.default_rng(0)
        prompts = [rng.integers(1, VOCAB, n).astype(np.int32)
                   for n in (5, 9, 7)]
        outs = eng.generate_many(prompts, 6, eos_id=None)
        assert all(len(np.asarray(o)) == 6 for o in outs)
        recs = eng.anatomy.records()
        assert recs and anat.validate_anatomy_records(recs) >= 1
        s = eng.anatomy.summary()
        assert s["phase_s"].get("prefill", 0) > 0
        assert s["phase_s"].get("decode", 0) > 0
        assert 0.0 <= s["host_gap_frac"] <= 1.0
        assert eng.recompile_detector.recompiles == 0
        eng.health()        # refreshes the headroom gauges the report reads
        text = obs.report(eng._reg, eng.tracer)
        assert "-- anatomy --" in text
        assert "phase_split" in text and "headroom" in text

    def test_headroom_idle_vs_mid_flight(self, eng):
        """Mid-decode the page/slot/HBM headroom must read the held
        resources; at idle everything returns to 1.0 while the flops
        plane keeps its utilization estimate."""
        h = eng.health()["headroom"]
        assert h["pages"] == 1.0 and h["slots"] == 1.0 and h["hbm"] == 1.0
        assert h["flops_utilization"] > 0.0     # the busy run above
        assert 0.0 <= h["flops"] < 1.0
        rng = np.random.default_rng(1)
        rids = [eng.submit(rng.integers(1, VOCAB, 6).astype(np.int32), 8,
                           eos_id=None) for _ in range(2)]
        collected = {}
        for _ in range(200):
            collected.update(eng.step())
            if eng.scheduler.decode_slots():
                break
        mid = eng.health()["headroom"]
        assert mid["slots"] == 0.0              # both slots held
        assert mid["pages"] < 1.0 and mid["hbm"] < 1.0
        assert mid["hbm_live_bytes"] > 0
        assert mid["hbm_capacity_bytes"] == \
            eng.cache.capacity_bytes()
        reg_val = eng._reg.get("serving_headroom").value(resource="pages")
        assert reg_val == mid["pages"]
        while not eng.scheduler.idle():
            collected.update(eng.step())
        assert set(rids) <= set(collected)
        end = eng.health()["headroom"]
        assert end["pages"] == 1.0 and end["slots"] == 1.0 \
            and end["hbm"] == 1.0

    def test_phase_split_separates_workloads(self, eng):
        """Prefill-heavy traffic (long prompts, 1 new token) moves the
        phase split toward prefill; decode-heavy traffic (short prompt,
        long generation) moves it toward decode — the anatomy must make
        the two regimes distinguishable from its records alone. Held by
        what the records count (the steps a phase took part in, and the
        tokens the phases' counters saw), not by two sums of wall-clock
        time: under a loaded host one pause in either phase outweighs
        the few milliseconds this tiny engine computes."""
        rng = np.random.default_rng(2)

        def mark():
            snap = eng._reg.snapshot()
            return (eng.anatomy.summary()["steps"],
                    snap.get("serving_prefill_tokens_total", 0.0),
                    snap.get("serving_tokens_total", 0.0))

        def since(before):
            steps, pre_tok, dec_tok = (a - b for a, b in
                                       zip(mark(), before))
            recs = eng.anatomy.records()[-int(steps):]
            took_part = {ph: sum(1 for r in recs if r["phases"].get(ph, 0) > 0)
                         for ph in ("prefill", "decode")}
            assert all(s >= 0.0 for r in recs for s in r["phases"].values())
            return took_part, pre_tok, dec_tok

        before = mark()
        long_prompts = [rng.integers(1, VOCAB, 12).astype(np.int32)
                        for _ in range(4)]
        eng.generate_many(long_prompts, 1, eos_id=None)
        steps, pre_tok, dec_tok = since(before)
        assert steps["prefill"] > steps["decode"]
        assert pre_tok == 4 * 12 and pre_tok > dec_tok

        before = mark()
        short = [rng.integers(1, VOCAB, 4).astype(np.int32)
                 for _ in range(2)]
        eng.generate_many(short, 12, eos_id=None)
        steps, pre_tok, dec_tok = since(before)
        assert steps["decode"] > steps["prefill"]
        assert dec_tok == 2 * 12 and dec_tok > pre_tok

    def test_phase_seconds_are_the_call_wall_time_of_the_phase_spans(
            self, model_params):
        """ISSUE 25: ``anatomy_phase_seconds`` is the wall time of the
        jitted call (uploads, dispatch, sync), taken from the SAME clock reads as
        the ``serving.*`` phase spans and their counters, not a second
        pair beside them; the host figure is what is left of the step."""
        tracer = obs.Tracer(enabled=True, capacity=4096)
        eng = _engine(model_params, tracer=tracer)
        rng = np.random.default_rng(3)
        eng.generate_many([rng.integers(1, VOCAB, n).astype(np.int32)
                           for n in (5, 7)], 4, eos_id=None)
        snap = eng._reg.snapshot()

        def parts(phase, *names):
            return sum(snap['serving_step_part_seconds_total{part="%s",'
                            'phase="%s"}' % (n, phase)] for n in names)
        for phase in ("prefill", "decode"):
            # assemble.start .. the end of what the call waited for (its
            # sync, or its dispatch where it read nothing back): the
            # three parts plus the few microseconds between one phase's
            # exit and the next's entry
            call_s = parts(phase, "assemble", "dispatch", "sync")
            got = eng.anatomy.summary()["phase_s"][phase]
            assert call_s <= got + 1e-6 and got <= call_s + 5e-3
            assert snap['anatomy_phase_seconds_sum{phase="%s"}' % phase] \
                == pytest.approx(got, abs=1e-6)
        assert "dispatch + sync" in eng._reg.get("anatomy_phase_seconds").help
        # the spans in the ring carry the same stamps
        recs = eng.anatomy.records()
        asm = tracer.spans(name="serving.decode.assemble")
        sync = tracer.spans(name="serving.decode.sync")
        disp = tracer.spans(name="serving.decode.dispatch")
        rounds = [r["phases"]["decode"] for r in recs
                  if "decode" in r["phases"]]
        # ISSUE 34: a round's decode interval is what the host spent on
        # it: the new block's uploads and dispatch, then the wait for
        # the block before. The first round only dispatches, the last
        # step only waits
        assert len(asm) == len(sync) == len(rounds) - 1 >= 1
        assert rounds == [round(e - s, 9) for s, e in
                          [(asm[0].start, disp[0].end)]
                          + [(a.start, s.end) for a, s in zip(asm[1:], sync)]
                          + [(sync[-1].start, sync[-1].end)]]
        # ISSUE 31: no request here makes a prefill call read back, so a
        # call's interval ends where its dispatch returned; ISSUE 52: a
        # step's record holds each of its calls
        assert tracer.spans(name="serving.prefill.sync") == []
        asm = tracer.spans(name="serving.prefill.assemble")
        disp = tracer.spans(name="serving.prefill.dispatch")
        calls = [c for r in recs for c in r["prefill_calls"]]
        assert len(asm) == len(disp) == len(calls) >= 1
        assert [c[4] for c in calls] == [round(d.end - a.start, 9)
                                         for a, d in zip(asm, disp)]
        assert [(c[0], c[1], c[2], c[3]) for c in calls] == [
            (s.attrs["lanes_live"], s.attrs["lanes"], s.attrs["width"],
             s.attrs["tokens"])
            for s in tracer.spans(name="serving.prefill_call")]
        assert not any(s.name.startswith("anatomy.") for s in tracer.spans())
        # the step's wall time bounds its parts, sync included
        step_s = snap["serving_step_seconds_total"]
        assert 0 < sum(v for k, v in snap.items() if k.startswith(
            "serving_step_part_seconds_total")) <= step_s
        # and is the anatomy records' wall, from the same clock reads
        # (a record rounds to the nanosecond)
        steps = tracer.spans(name="serving.step")
        assert step_s == pytest.approx(sum(r["wall_s"] for r in recs),
                                       abs=1e-8 * len(recs))
        assert [r["wall_s"] for r in recs] == [
            round(s.end - s.start, 9) for s in steps
            if s.attrs["step"] in {r["step"] for r in recs}]


# ---------------------------------------------------------------------------
# fleet: crash -> postmortem with victim trace ids; stale series dropped;
# headroom aggregated; /debug/postmortem served
# ---------------------------------------------------------------------------

class TestFleetFlightRecorder:
    @pytest.fixture(scope="class")
    def crashed_fleet(self, model_params, tmp_path_factory):
        tracer = obs.Tracer(enabled=True)
        reps = [fleet.LocalReplica(
            _engine(model_params, tracer=tracer), name=f"r{i}").warmup()
            for i in range(2)]
        assert reps[0].engine.flight.name == "r0"
        chaos = fleet.ChaosReplica(reps[1], crash_on_step=3)
        reg = obs.MetricsRegistry()
        pm_dir = str(tmp_path_factory.mktemp("pm"))
        router = fleet.FleetRouter(
            [reps[0], chaos], registry=reg, tracer=tracer, seed=0,
            faults=fleet.FaultPolicy(max_consecutive_failures=1,
                                     probe_timeout_s=30.0),
            postmortem_dir=pm_dir)
        mon = FleetMonitor(router)
        rng = np.random.default_rng(4)
        frids = [router.submit(rng.integers(1, VOCAB, 6).astype(np.int32),
                               8) for _ in range(6)]
        tids = {router.trace_id(f) for f in frids}
        steps = 0
        while not router.idle():
            router.step()
            mon.collect()
            steps += 1
            assert steps < 5000, "fleet did not converge"
        return router, mon, reg, frids, tids, pm_dir

    def test_eject_ships_linked_postmortem(self, crashed_fleet):
        router, _mon, _reg, frids, tids, pm_dir = crashed_fleet
        assert router.ejected_total == 1
        bundles = router.postmortems()
        assert len(bundles) == 1
        b = bundles[0]
        obs.validate_postmortem_bundle(b)
        assert b["reason"] == "eject" and b["replica"] == "r1"
        assert b["extra"]["cause"].startswith("crashed")
        # the bundle's trace ids ARE the victims': every one was minted
        # by the router for a request that was on board at the crash
        assert b["trace_ids"] and set(b["trace_ids"]) <= tids
        # and the on-disk artifact validates standalone
        files = sorted(os.listdir(pm_dir))
        assert len(files) == 1 and "r1" in files[0]
        obs.validate_postmortem_file(os.path.join(pm_dir, files[0]))
        # no silent loss alongside: every request ends with a result
        for f in frids:
            assert router.result(f) is not None \
                or router.reject_reason(f) is not None

    def test_stale_replica_series_dropped(self, crashed_fleet):
        """The regression: after an eject the monitor must REMOVE the
        dead replica's labeled series, not freeze them at their last
        values."""
        _router, mon, reg, *_ = crashed_fleet
        mon.collect()
        for mname in FleetMonitor._PER_REPLICA_METRICS:
            m = reg.get(mname)
            if m is None:
                continue
            names = {dict(k).get("replica") for k in m.labels_seen()}
            assert "r1" not in names, (mname, names)
        # the survivor's series stay live
        occ = reg.get("fleet_replica_slot_occupancy")
        assert {dict(k)["replica"] for k in occ.labels_seen()} == {"r0"}

    def test_headroom_aggregated_and_served(self, crashed_fleet):
        router, mon, reg, *_ = crashed_fleet
        h = mon.collect()
        assert set(h["headroom"]) == {"flops", "pages", "slots", "hbm",
                                      "spill"}
        assert h["headroom"]["pages"] == 1.0        # fleet is idle now
        g = reg.get("fleet_headroom_min")
        assert g.value(resource="slots") == h["headroom"]["slots"]
        pr = reg.get("fleet_replica_headroom")
        assert pr.value(replica="r0", resource="pages") == 1.0
        assert router.health()["postmortems"] == 1
        srv = mon.start_exposition()
        try:
            payload = json.loads(urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/debug/postmortem",
                timeout=10).read())
            assert payload["count"] == 1
            obs.validate_postmortem_bundle(payload["bundles"][0])
            # ?replica filters by PROVIDER name (the fleet registers one
            # provider for the whole router)
            one = json.loads(urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}"
                "/debug/postmortem?replica=fleet&limit=1",
                timeout=10).read())
            assert one["count"] == 1
            none = json.loads(urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}"
                "/debug/postmortem?replica=nope",
                timeout=10).read())
            assert none["count"] == 0
        finally:
            srv.stop()
