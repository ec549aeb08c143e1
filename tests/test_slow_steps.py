"""The step that took too long (ISSUE 52): every engine step keeps its
own parts and prefill calls in its anatomy record, one fixed rule flags
the slow ones and names the part, and the compile listener counts what a
step can wait on that no part names (a retrace, the garbage collector).

- the rule on synthetic series (``anatomy.SlowStepRule``);
- a tiny engine on the CPU with a sleep injected into one read-back and,
  separately, into one prefill dispatch: the right ``{phase,part}``
  child moves by the sleep, the record is ``slow``, the flight bundle
  validates and ``tools/postmortem.py`` prints the step;
- the two prefill histograms against a scripted arrival pattern;
- every new series at 0 in a fresh engine's snapshot;
- ``jax.clear_caches()`` between two steps is counted as traces (last in
  the file: everything this worker compiled is compiled again after it).
"""

import glob
import os
import sys
import time

import jax
import numpy as np
import pytest

from paddle_tpu import observability as obs
from paddle_tpu import serving
from paddle_tpu.models.gpt import GPT, GPTConfig
from paddle_tpu.observability import anatomy as anat
from paddle_tpu.observability import recompile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "tools"))

VOCAB = 64
GAP, OTHER = anat.GAP_PART, anat.OTHER_PART


# ---------------------------------------------------------------------------
# the rule, on synthetic series
# ---------------------------------------------------------------------------

def _step(sync=0.010, prefill=0.0, gap=0.0002, other=0.0005, **more):
    """One backlog-like step's parts: 14 ms, most of it the wait."""
    parts = {"decode.assemble": 0.0015, "decode.dispatch": 0.0005,
             "decode.sync": sync, "decode.book": 0.001,
             "sched.book": 0.0003, "observe.book": 0.0002,
             "prefill.dispatch": prefill, OTHER: other, GAP: gap}
    parts.update(more)
    return parts


def _wall(parts):
    return sum(v for p, v in parts.items() if p != GAP)


def _run(rule, steps):
    return [rule.judge(_wall(p), p) for p in steps]


def _steady(n, rng=None, **kw):
    rng = rng or np.random.default_rng(0)
    return [{p: v * float(rng.uniform(0.9, 1.1)) for p, v in
             _step(**kw).items()} for _ in range(n)]


class TestSlowStepRule:
    def test_steady_steps_are_never_flagged(self):
        assert _run(anat.SlowStepRule(), _steady(400)) == [None] * 400

    @pytest.mark.parametrize("part", ["decode.sync", "decode.dispatch",
                                      "observe.book", OTHER])
    def test_a_pause_of_a_tenth_of_a_second_is_flagged_under_its_part(
            self, part):
        """The backlog cell's stall: 0.11 s inside one part of a 14 ms
        step, whichever part it is."""
        rule = anat.SlowStepRule()
        _run(rule, _steady(100))
        slow = _step()
        slow[part] += 0.110
        (name, excess), = _run(rule, [slow])
        assert name == part
        assert 0.110 - 3 * 0.011 < excess <= 0.111
        # and the steps after it are judged as before
        assert _run(rule, _steady(50)) == [None] * 50

    def test_one_part_eight_times_its_median_names_the_step(self):
        rule = anat.SlowStepRule()
        _run(rule, _steady(64))
        got, = _run(rule, [_step(sync=0.080)])
        assert got[0] == "decode.sync"
        assert got[1] == pytest.approx(0.080 - 3 * 0.010, rel=0.12)

    def test_a_wall_that_doubles_over_200_steps_is_not_flagged(self):
        steps = [{p: v * (1 + i / 200) for p, v in _step().items()}
                 for i in range(200)]
        assert _run(anat.SlowStepRule(), steps) == [None] * 200

    def test_steps_with_and_without_prefill_calls_are_not_flagged(self):
        """A part's median is over the steps in which it ran: a prefill
        call every other step is no excess in the steps that hold one,
        and neither is the longer wait behind it."""
        steps = [_step(prefill=0.004 * (i % 2), sync=0.010 + 0.012 * (i % 2))
                 for i in range(300)]
        assert _run(anat.SlowStepRule(), steps) == [None] * 300

    def test_a_longer_sync_that_held_more_calls_is_not_flagged(self):
        """The long-prompt cell: a step of 300 ms whose wait is half as
        long again because it held 18 prefill calls and not 12."""
        rule = anat.SlowStepRule()
        base = dict(sync=0.250, prefill=0.030, other=0.002)
        _run(rule, _steady(100, **base))
        assert _run(rule, [_step(sync=0.375, prefill=0.045, other=0.002)]) \
            == [None]

    def test_a_context_that_grows_and_starts_over_is_not_flagged(self):
        """The sessions cell's set-up: a document of 30 steps whose one
        prefill call waits 52 to 125 ms in ``prefill.dispatch`` as the
        context grows, then the next document from a short context."""
        steps = [_step(prefill=0.052 + 0.0025 * (i % 30), sync=0.0)
                 for i in range(600)]
        assert _run(anat.SlowStepRule(), steps) == [None] * 600

    def test_a_change_that_stays_is_flagged_once_at_its_first_step(self):
        """Dispatch returns in a millisecond until the runtime's queue is
        full and then waits a device call every step, longer as the
        context grows, and drains with each group of documents: measured
        by the median alone every waiting step of a group is "slow";
        held against the step before as well, the first one is."""
        group = [_step(prefill=0.001, sync=0.0)] * 39 + [
            _step(prefill=0.060 + 0.0026 * i, sync=0.0) for i in range(25)]
        got = _run(anat.SlowStepRule(), group * 4)
        flagged = [i for i, v in enumerate(got) if v is not None]
        assert flagged == [39, 64 + 39, 128 + 39, 192 + 39]
        assert {got[i][0] for i in flagged} == {"prefill.dispatch"}
        # a pause is flagged every time it comes, the step after one too
        # when it stalls in another part
        rule = anat.SlowStepRule()
        _run(rule, _steady(100))
        got = _run(rule, [_step(sync=0.120), _step(gap=0.115),
                          _step(), _step(sync=0.120)])
        assert [v and v[0] for v in got] == ["decode.sync", GAP, None,
                                             "decode.sync"]

    def test_no_verdict_before_32_steps(self):
        rule = anat.SlowStepRule()
        assert _run(rule, _steady(anat.SLOW_MIN_STEPS - 1)) \
            == [None] * (anat.SLOW_MIN_STEPS - 1)
        assert _run(rule, [_step(sync=0.5)]) == [None]
        # a part has no verdict before IT ran 32 times, however many
        # steps there were
        _run(rule, _steady(64))
        _run(rule, _steady(anat.SLOW_MIN_STEPS - 1, prefill=0.002))
        assert _run(rule, [_step(prefill=0.3)]) == [None]
        _run(rule, _steady(2, prefill=0.002))
        (name, _), = _run(rule, [_step(prefill=0.3)])
        assert name == "prefill.dispatch"

    def test_a_slow_caller_is_named_gap(self):
        rule = anat.SlowStepRule()
        _run(rule, _steady(64))
        (name, excess), = _run(rule, [_step(gap=0.2)])
        assert name == GAP and excess == pytest.approx(0.2, rel=0.01)

    def test_the_largest_excess_names_a_step_with_two(self):
        rule = anat.SlowStepRule()
        _run(rule, _steady(64))
        (name, excess), = _run(rule, [_step(sync=0.100, other=0.060)])
        assert name == "decode.sync"
        assert excess == pytest.approx(
            0.100 - 0.030 + 0.060 - 0.0015, rel=0.05)

    def test_a_step_that_cannot_be_slow_takes_no_median(self, monkeypatch):
        """The pre-test on what the step spent in all: steady steps sort
        nothing but the cached wall median, once every
        ``SLOW_MIN_STEPS`` steps."""
        rule = anat.SlowStepRule()
        _run(rule, _steady(64))
        sorts = []
        real = anat._Last.median
        monkeypatch.setattr(anat._Last, "median",
                            lambda self: sorts.append(1) or real(self))
        _run(rule, _steady(4 * anat.SLOW_MIN_STEPS))
        assert len(sorts) <= 4
        # and it has no parameter a caller could set
        with pytest.raises(TypeError):
            anat.SlowStepRule(128)
        with pytest.raises(TypeError):
            obs.StepAnatomy(slow_factor=2.0)


class TestRecordValidation:
    def _rec(self, **parts):
        a = obs.StepAnatomy(registry=obs.MetricsRegistry())
        a.begin_step(1, t0=10.0)
        a.end_step(t1=10.0 + _wall(parts), parts=parts,
                   prefill_calls=[(1, 2, 4, 100, 0.003)])
        return a.last()

    def test_records_with_and_without_the_step_parts_validate(self):
        rec = self._rec(**_step())
        assert anat.validate_anatomy_record(rec) == 1
        assert rec["prefill_calls"] == [[1, 2, 4, 100, 0.003]]
        assert "prefill.dispatch" not in rec["parts"]   # it did not run
        old = {k: v for k, v in rec.items()
               if k not in ("parts", "prefill_calls")}
        assert anat.validate_anatomy_record(old) == 1

    @pytest.mark.parametrize("field, bad, match", [
        ("parts", {"decode.sync": 9.0, OTHER: 0.0}, "parts sum"),
        ("parts", {"decode.sync": 0.001, OTHER: 0.0}, "parts sum"),
        ("parts", {"decode.sync": -1.0}, "bad 'parts'"),
        ("prefill_calls", [[3, 2, 4, 100, 0.003]], "prefill_calls"),
        ("prefill_calls", [[1, 2, 4, 100]], "prefill_calls"),
        ("slow", True, "slow_part"),
    ])
    def test_malformed_step_parts_are_refused(self, field, bad, match):
        rec = dict(self._rec(**_step()))
        rec[field] = bad
        with pytest.raises(ValueError, match=match):
            anat.validate_anatomy_record(rec)

    def test_only_the_newest_records_and_the_slow_ones_keep_their_parts(
            self):
        """A slow record holds its ``parts`` and ``prefill_calls`` for
        good; the others have theirs while they are among the newest
        ``PARTS_TAIL`` steps, and no record in the ring is written again
        once it is there."""
        a = obs.StepAnatomy(registry=obs.MetricsRegistry())
        held = []
        for i in range(anat.PARTS_TAIL + 100):
            a.begin_step(i + 1, t0=float(i))
            parts = _step(sync=0.5) if i == 60 else _step()
            held.append(a.end_step(t1=i + _wall(parts), parts=parts,
                                   prefill_calls=[(1, 1, 1, 4, 0.001)]))
        recs = a.records()
        assert anat.validate_anatomy_records(recs) == len(recs)
        full = [r["step"] for r in recs if "parts" in r]
        assert full == [61] + list(range(101, anat.PARTS_TAIL + 101))
        assert recs[60]["slow"] and recs[60]["prefill_calls"]
        assert recs[60] is held[60] and recs[0] is held[0]
        assert "prefill_calls" not in recs[0]
        assert a.last() == recs[-1] and a.last()["prefill_calls"]

    def test_the_committed_postmortem_bundles_still_validate(self):
        paths = glob.glob(os.path.join(ROOT, "BENCH_ROUTER.postmortems",
                                       "*.json"))
        assert paths
        for p in paths:
            bundle = obs.validate_postmortem_file(p)
            assert all("parts" not in r for r in bundle["anatomy"])


# ---------------------------------------------------------------------------
# an engine on the CPU
# ---------------------------------------------------------------------------

NEW_SERIES = [
    "serving_step_traces_total", "serving_step_gc_seconds_total",
    "serving_prefill_call_lanes_count", "serving_step_prefill_calls_count",
] + [f'{name}{{part="{pt}",phase="{ph}"}}'
     for name in ("serving_slow_steps_total",
                  "serving_slow_step_excess_seconds_total")
     for ph, pt in serving.engine._SLOW_PARTS]


def _model():
    cfg = GPTConfig.tiny(vocab_size=VOCAB, hidden_size=16, num_layers=2,
                         num_heads=2, ffn_size=32, max_position=96,
                         dropout=0.0, attn_impl="xla")
    model = GPT(cfg)
    return model, model.init(jax.random.PRNGKey(0))


def _engine(model_params, tracer=None):
    model, params = model_params
    return serving.ServingEngine(
        model, params, attn_impl="lax", registry=obs.MetricsRegistry(),
        tracer=tracer, num_slots=4, page_size=4, max_tokens_per_slot=24,
        prefill_chunk=4, prefill_budget=16, decode_block=2)


def _prompts(rng, *lengths):
    return [rng.integers(1, VOCAB, n).astype(np.int32) for n in lengths]


def _slow(snap, name="serving_slow_steps_total"):
    """-> {``phase.part``: the series' value} of the series that moved."""
    out = {}
    for (ph, pt) in serving.engine._SLOW_PARTS:
        v = snap.get(f'{name}{{part="{pt}",phase="{ph}"}}', 0.0)
        if v:
            out[f"{ph}.{pt}"] = v
    return out


def _flagged(recs, name="slow_part"):
    """What the counters must say of ``recs``: steps and excess by part.
    (A loaded host may pause this tiny engine for 25 ms anywhere, so the
    cases hold the counters to the records and the record that slept to
    its part, and do not assume that no other step was flagged.)"""
    steps, excess = {}, {}
    for r in recs:
        if r.get("slow"):
            steps[r[name]] = steps.get(r[name], 0) + 1
            excess[r[name]] = excess.get(r[name], 0.0) + r["excess_s"]
    return steps, excess


@pytest.fixture(scope="module")
def model_params():
    return _model()


@pytest.fixture(scope="module")
def eng(model_params):
    """Warmed, then served until every part that runs here has a median:
    what the cases below inject is judged against these steps."""
    e = _engine(model_params)
    e.warmup()
    rng = np.random.default_rng(0)
    for _ in range(20):
        e.generate_many(_prompts(rng, 5, 9, 7), 10, eos_id=None)
    assert all(ring.n >= anat.SLOW_MIN_STEPS
               for ring in e.anatomy.slow_rule._parts.values())
    return e


def _served_with_a_sleep(eng, attr, seconds=0.2, at_call=2):
    """Serve three requests with ONE call of ``eng.<attr>`` sleeping
    first; -> (registry delta, the records of those steps, the seconds
    it slept)."""
    real, calls = getattr(eng, attr), []

    def slept(*a, **kw):
        calls.append(0.0)
        if len(calls) == at_call:
            t0 = time.monotonic()
            time.sleep(seconds)
            calls[-1] = time.monotonic() - t0
        return real(*a, **kw)
    before, n0 = eng._reg.snapshot(), eng.anatomy.summary()["steps"]
    setattr(eng, attr, slept)
    try:
        # prompts no earlier case sent: a published prefix is not prefilled
        eng.generate_many(_prompts(np.random.default_rng(
            [eng.anatomy.summary()["steps"], len(attr)]), 5, 9, 7), 10,
            eos_id=None)
    finally:
        setattr(eng, attr, real)
    after = eng._reg.snapshot()
    assert len(calls) >= at_call
    recs = eng.anatomy.records()[-(eng.anatomy.summary()["steps"] - n0):]
    return ({k: v - before.get(k, 0.0) for k, v in after.items()}, recs,
            max(calls))


class TestEngineSlowSteps:
    def test_every_new_series_is_at_zero_in_a_fresh_engine(self,
                                                           model_params):
        snap = _engine(model_params)._reg.snapshot()
        assert [k for k in NEW_SERIES if snap.get(k) != 0] == []
        assert len(NEW_SERIES) == 4 + 2 * 13
        assert "serving_flops_utilization" not in snap
        reg = _engine(model_params)._reg
        assert reg.get("serving_prefill_call_lanes").buckets == \
            (1, 2, 4, 8, 16, 32, 64)
        assert reg.get("serving_step_prefill_calls").buckets == \
            (1, 2, 4, 8, 12, 16, 24, 32, 64)

    def test_sound_steps_keep_their_parts_and_flag_nothing(self, eng):
        recs = eng.anatomy.records()
        assert anat.validate_anatomy_records(recs) == len(recs)
        assert len(recs) <= anat.PARTS_TAIL      # every one has its parts
        for r in recs:
            inside = sum(s for p, s in r["parts"].items() if p != GAP)
            assert inside == pytest.approx(r["wall_s"], abs=1e-6)
            assert len(r["prefill_calls"]) == 0 or "prefill" in r["phases"]
        assert any(r["parts"].get(GAP, 0) > 0 for r in recs)
        # the first step of a drain is entered from an idle engine: the
        # caller's time before it is no part of it
        assert GAP not in recs[0]["parts"]
        snap = eng._reg.snapshot()
        # the counters say what the records say (nothing, on a quiet host)
        steps, excess = _flagged(recs)
        assert _slow(snap) == steps == eng.anatomy.summary()["slow_steps"]
        assert _slow(snap, "serving_slow_step_excess_seconds_total") \
            == pytest.approx(excess)
        assert all(r["excess_s"] > anat.SLOW_MIN_EXCESS_S
                   for r in eng.anatomy.slow_records())
        part_s = sum(v for k, v in snap.items()
                     if k.startswith("serving_step_part_seconds_total"))
        assert sum(s for r in recs for p, s in r["parts"].items()
                   if p not in (GAP, OTHER)) \
            == pytest.approx(part_s, abs=1e-6)

    @pytest.mark.parametrize("attr, phase, part", [
        ("_read_back", "decode", "sync"),
        ("prefill_step", "prefill", "dispatch")])
    def test_an_injected_sleep_is_booked_under_the_part_that_slept(
            self, eng, attr, phase, part, tmp_path, capsys):
        delta, recs, slept_s = _served_with_a_sleep(eng, attr)
        name = f"{phase}.{part}"
        # the step that slept is named after the part that slept, by the
        # sleep within a fifth (its three medians are a millisecond)
        slow, = [r for r in recs if r["parts"].get(name, 0) >= 0.2]
        assert slow.get("slow") and slow["slow_part"] == name
        assert slow["excess_s"] == pytest.approx(slept_s, rel=0.2)
        assert slow["excess_s"] >= 0.15
        assert sum(s for p, s in slow["parts"].items() if p != GAP) \
            == pytest.approx(slow["wall_s"], abs=1e-6)
        # the counters hold exactly the records' verdicts: that part's
        # children moved, and no other's unless another step was flagged
        steps, excess = _flagged(recs)
        assert _slow(delta) == steps and steps[name] >= 1
        assert _slow(delta, "serving_slow_step_excess_seconds_total") \
            == pytest.approx(excess)
        key = f'{{part="{part}",phase="{phase}"}}'
        assert delta["serving_step_part_seconds_total" + key] >= 0.2
        assert {"slots_live", "width", "admitted", "evicted", "traces",
                "gc_s"} <= set(slow)
        assert slow["traces"] == 0 and 0 <= slow["slots_live"] <= 4
        assert bool(slow["prefill_calls"]) == (phase == "prefill")
        # the flight recorder's bundle holds it, and the renderer prints
        # the slow steps first
        bundle = eng.flight.dump("test")
        obs.validate_postmortem_bundle(bundle)
        assert bundle["anatomy_summary"]["slow_steps"][
            slow["slow_part"]] >= 1
        from postmortem import main as pm_main
        path = str(tmp_path / "pm.json")
        obs.write_bundle(bundle, path)
        assert pm_main([path]) == 0
        out = capsys.readouterr().out.splitlines()
        assert out[1].strip().startswith("slow_steps ")
        assert f"{name}=" in out[1]
        assert any(ln.strip().startswith(f"step {slow['step']}: ")
                   and name in ln for ln in out)
        text = obs.report(eng._reg, eng.tracer, anatomy=eng.anatomy)
        digest = text[text.index("-- anatomy --"):].splitlines()
        assert digest[1].startswith("slow_steps ")
        assert f"step {slow['step']}: " in text
        assert any(ln.startswith("in_working_steps traces=0 gc=")
                   for ln in digest)

    def test_prefill_histograms_against_a_scripted_arrival_pattern(
            self, eng):
        """Budget 16, chunk 4, four slots, a plain pool (a run as long as
        the call): two prompts of 8 are one call of two runs of two; three
        prompts of 4 one call of three lanes in a bucket of four; one
        prompt of 12 one call whose three lanes are one run. The lanes, the
        runs and the pad are what a hand count gives (PR 54)."""
        rng = np.random.default_rng(2)
        before, n0 = eng._reg.snapshot(), eng.anatomy.summary()["steps"]
        for lengths in ((8, 8), (4, 4, 4), (12,)):
            eng.generate_many(_prompts(rng, *lengths), 3, eos_id=None)
        after = eng._reg.snapshot()
        d = {k: after[k] - before[k] for k in after
             if k.startswith(("serving_prefill_call_lanes",
                              "serving_step_prefill_calls",
                              "serving_prefill_calls_total",
                              "serving_prefill_run_chunks",
                              "serving_prefill_lanes_total"))}
        assert d["serving_prefill_calls_total"] == 3
        assert d["serving_prefill_call_lanes_count"] == 3
        assert d["serving_prefill_call_lanes_sum"] == 4 + 3 + 3
        assert d["serving_step_prefill_calls_count"] == 3
        assert d["serving_step_prefill_calls_sum"] == 3
        # a run is observed once a slot and call: 2 2, 1 1 1, 3
        assert d["serving_prefill_run_chunks_count"] == 6
        assert d["serving_prefill_run_chunks_sum"] == 2 + 2 + 1 + 1 + 1 + 3
        assert d['serving_prefill_lanes_total{kind="live"}'] == 4 + 3 + 3
        assert d['serving_prefill_lanes_total{kind="bucket"}'] == 4 + 4 + 4
        recs = eng.anatomy.records()[-(eng.anatomy.summary()["steps"] - n0):]
        # [lanes_live, lanes, width, tokens, (seconds,) longest run]
        calls = [[c[:4] + c[5:] for c in r["prefill_calls"]] for r in recs
                 if r["prefill_calls"]]
        assert calls == [[[4, 4, 2, 16, 2]],
                         [[3, 4, 1, 12, 1]],
                         [[3, 4, 4, 12, 3]]]
        # the lanes' fill is in the records: live lanes over the buckets'
        assert sum(c[0] for cs in calls for c in cs) == 10
        assert sum(c[1] for cs in calls for c in cs) == 12

    def test_a_slow_step_is_one_annotation_after_its_phase_closed(
            self, eng, monkeypatch):
        """The join key to ``serving.step`` in a profiler's trace: one
        ``serving.slow_step`` with the step's number, emitted once the
        step's own annotation has closed; sound steps emit none."""
        seen = []

        class Spy:
            def __init__(self, name, **attrs):
                self.name, self.attrs = name, attrs

            def __enter__(self):
                seen.append(("enter", self.name, self.attrs))

            def __exit__(self, *exc):
                seen.append(("exit", self.name, self.attrs))
        monkeypatch.setattr(serving.engine, "TraceAnnotation", Spy)
        monkeypatch.setattr(obs.tracing, "_Annotation", Spy)
        _, recs, _ = _served_with_a_sleep(eng, "_read_back")
        slow, = [r for r in recs if r["parts"].get("decode.sync", 0) >= 0.2]
        marks = [i for i, ev in enumerate(seen)
                 if ev[1] == "serving.slow_step"
                 and ev[2]["step"] == slow["step"]]
        assert [seen[i][0] for i in marks] == ["enter", "exit"]
        assert sum(ev[1] == "serving.slow_step" for ev in seen) \
            == 2 * sum(1 for r in recs if r.get("slow"))
        assert seen[marks[0]][2] == {
            "step": slow["step"], "part": "decode.sync",
            "excess_us": int(slow["excess_s"] * 1e6)}
        closed = seen[marks[0] - 1]
        assert closed[:2] == ("exit", "serving.step")
        assert closed[2]["step"] == slow["step"]

    def test_clearing_the_caches_between_two_steps_is_counted_as_traces(
            self, eng):
        """A step program traced again: the compile listener's one
        stream counts the traces beside the compiles, and the engine
        books those that ended inside a working step."""
        rng = np.random.default_rng(3)
        compiles = obs.default().counter("jax_compiles_total")
        for p in _prompts(rng, 6, 6):
            eng.submit(p, 8, eos_id=None)
        eng.step()
        eng.step()
        t0 = eng._c_step_traces.value()
        seen0 = (recompile.trace_count(), recompile.compile_count(),
                 compiles.value())
        jax.clear_caches()
        while not eng.scheduler.idle():
            eng.step()
        traced = eng._c_step_traces.value() - t0
        d_traces, d_compiles, d_counter = (
            b - a for a, b in zip(seen0, (recompile.trace_count(),
                                          recompile.compile_count(),
                                          compiles.value())))
        assert traced > 0 and d_compiles > 0
        assert d_counter == d_compiles          # one listener, one count
        assert 0 < traced <= d_traces
        assert sum(r.get("traces", 0) for r in eng.anatomy.slow_records()) \
            <= traced
        assert eng._c_step_gc.value() >= 0.0
        # the hook was installed once, with the one listener
        import gc
        assert gc.callbacks.count(recompile._on_gc) == 1
