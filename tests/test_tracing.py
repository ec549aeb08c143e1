"""Request-lifecycle tracing, live exposition, and SLO burn-rate tests
(ISSUE 10): span parentage across threads, ring-buffer memory bounds,
the zero-cost disabled path, exporter contracts (Chrome trace keys,
JSONL schema), the exposition endpoint round trip, burn-rate alerting,
and the full serving-engine lifecycle reconstruction — with the
zero-steady-state-recompile invariant re-asserted WITH tracing on.
"""

import gc
import json
import sys
import threading
import urllib.error
import urllib.request

import numpy as np
import pytest

from paddle_tpu import observability as obs
from paddle_tpu.observability import slo as slo_mod
from paddle_tpu.observability import tracing


# ---------------------------------------------------------------------------
class TestSpans:
    def test_nested_parentage_same_thread(self):
        tr = tracing.Tracer(capacity=64)
        with tr.span("outer", layer=1) as outer:
            with tr.span("inner") as inner:
                assert tr.current() is inner
            assert tr.current() is outer
        assert tr.current() is None
        inner_s, outer_s = tr.spans()
        assert inner_s.name == "inner" and outer_s.name == "outer"
        assert inner_s.parent_id == outer_s.span_id
        assert inner_s.trace_id == outer_s.trace_id
        assert outer_s.parent_id == 0
        assert outer_s.attrs == {"layer": 1}

    def test_sibling_roots_get_distinct_traces(self):
        tr = tracing.Tracer(capacity=8)
        with tr.span("a"):
            pass
        with tr.span("b"):
            pass
        a, b = tr.spans()
        assert a.trace_id != b.trace_id

    def test_threaded_spans_have_own_stacks(self):
        """A background thread's spans must NOT accidentally parent to
        the engine thread's current span (thread-local stacks)."""
        tr = tracing.Tracer(capacity=64)
        done = threading.Event()

        def worker():
            with tr.span("bg"):
                pass
            done.set()

        with tr.span("fg"):
            t = threading.Thread(target=worker)
            t.start()
            t.join()
        assert done.wait(1)
        bg = tr.spans(name="bg")[0]
        fg = tr.spans(name="fg")[0]
        assert bg.parent_id == 0            # own root, not under fg
        assert bg.trace_id != fg.trace_id
        assert bg.thread != fg.thread

    def test_explicit_parent_crosses_threads(self):
        """And when the caller WANTS cross-thread attribution (snapshot
        writer under its save), parent= ties the trace together."""
        tr = tracing.Tracer(capacity=64)
        root = tr.start_span("save")
        out = []

        def worker():
            out.append(tr.record_span("write", duration_s=0.01,
                                      parent=root))

        t = threading.Thread(target=worker)
        t.start()
        t.join()
        root.finish()
        child = out[0]
        assert child.parent_id == root.span_id
        assert child.trace_id == root.trace_id

    def test_exception_marks_span_error(self):
        tr = tracing.Tracer(capacity=8)
        with pytest.raises(ValueError):
            with tr.span("boom"):
                raise ValueError("x")
        (sp,) = tr.spans()
        assert sp.status == "error" and sp.end is not None

    def test_ring_buffer_bounded_under_10k_spans(self):
        tr = tracing.Tracer(capacity=1000)
        for i in range(10_000):
            tr.record_span(f"s{i}", duration_s=0.0)
        spans = tr.spans()
        assert len(spans) == 1000
        assert tr.dropped == 9_000
        # the ring keeps the NEWEST window
        assert spans[-1].name == "s9999" and spans[0].name == "s9000"

    def test_events_recorded_with_attrs(self):
        tr = tracing.Tracer(capacity=8)
        with tr.span("req") as sp:
            sp.add_event("admitted", slot=3)
        (s,) = tr.spans()
        t, name, attrs = s.events[0]
        assert name == "admitted" and attrs == {"slot": 3}
        assert s.start <= t <= s.end


class TestDisabledZeroCost:
    def test_disabled_span_is_shared_noop(self):
        tr = tracing.Tracer(enabled=False)
        s = tr.span("a", big_attr="x")
        assert s is tr.span("b") is tr.start_span("c") \
            is tracing.NOOP_SPAN
        # the no-op absorbs the whole span protocol
        with s as inner:
            inner.add_event("e", k=1).set_attrs(a=2)
        s.finish()
        assert tr.spans() == [] and tr.record_span("x") is None

    def test_disabled_hot_path_allocation_free(self):
        """The disabled path must not RETAIN any allocation: net
        allocated-block delta over 10k enter/exits stays ~zero, and the
        ring buffer stays empty."""
        tr = tracing.Tracer(enabled=False)
        for _ in range(100):        # warm any lazy caches
            with tr.span("hot"):
                pass
        gc.collect()
        base = sys.getallocatedblocks()
        for _ in range(10_000):
            with tr.span("hot"):
                pass
        gc.collect()
        delta = sys.getallocatedblocks() - base
        assert delta < 50, f"disabled span retained {delta} blocks"
        assert tr.spans() == []

    def test_enable_disable_round_trip(self):
        tr = tracing.Tracer(enabled=False)
        tr.enable(capacity=16)
        with tr.span("on"):
            pass
        tr.disable()
        with tr.span("off"):
            pass
        assert [s.name for s in tr.spans()] == ["on"]

    def test_enable_shrink_counts_evicted_as_dropped(self):
        tr = tracing.Tracer(capacity=32)
        for i in range(20):
            tr.record_span(f"s{i}", duration_s=0.0)
        tr.enable(capacity=8)            # evicts the 12 oldest
        assert len(tr.spans()) == 8
        assert tr.dropped == 12
        assert tr.spans()[-1].name == "s19"


class TestExporters:
    def _traced(self):
        tr = tracing.Tracer(capacity=64)
        with tr.span("outer", rid=1) as o:
            o.add_event("admitted", slot=0)
            with tr.span("inner"):
                pass
        return tr

    def test_chrome_trace_required_keys(self):
        tr = self._traced()
        trace = tr.to_chrome()
        assert tracing.chrome_trace_valid(trace, require_events=3) == 3
        for e in trace["traceEvents"]:
            for k in ("ph", "ts", "pid", "tid", "name"):
                assert k in e
        phs = {e["ph"] for e in trace["traceEvents"]}
        assert phs == {"X", "i"}     # spans + instant events
        x = [e for e in trace["traceEvents"] if e["ph"] == "X"]
        assert all("dur" in e for e in x)
        assert {e["name"] for e in x} == {"outer", "inner"}

    def test_chrome_trace_validator_rejects_bad(self):
        with pytest.raises(ValueError, match="missing traceEvents"):
            tracing.chrome_trace_valid({})
        with pytest.raises(ValueError, match="missing 'tid'"):
            tracing.chrome_trace_valid({"traceEvents": [
                {"ph": "i", "ts": 0, "pid": 1, "name": "x"}]})
        with pytest.raises(ValueError, match="X without dur"):
            tracing.chrome_trace_valid({"traceEvents": [
                {"ph": "X", "ts": 0, "pid": 1, "tid": 0, "name": "x"}]})

    def test_jsonl_round_trip_and_validation(self, tmp_path):
        tr = self._traced()
        p = str(tmp_path / "trace.jsonl")
        n = tr.export_jsonl(p)
        assert n == 2
        assert tracing.validate_trace_log(p, require_spans=2) == 2
        recs = [json.loads(x) for x in open(p)]
        assert recs[0]["kind"] == "trace_meta"
        spans = [r for r in recs if r["kind"] == "span"]
        byname = {r["name"]: r for r in spans}
        assert byname["inner"]["parent_id"] == byname["outer"]["span_id"]
        assert byname["outer"]["events"][0]["name"] == "admitted"
        # chrome conversion from the JSONL (offline tooling path)
        out = str(tmp_path / "trace.json")
        tracing.chrome_trace_from_jsonl(p, out)
        tracing.chrome_trace_valid(json.load(open(out)),
                                   require_events=2)

    def test_jsonl_partial_tail_tolerated(self, tmp_path):
        tr = self._traced()
        p = str(tmp_path / "trace.jsonl")
        tr.export_jsonl(p)
        with open(p, "a") as f:
            f.write('{"kind": "span", "trace')   # crash artifact
        assert tracing.validate_trace_log(p) == 2

    def test_validator_rejects_bad_records(self, tmp_path):
        p = str(tmp_path / "bad.jsonl")
        with open(p, "w") as f:
            f.write(json.dumps({"kind": "span", "trace_id": 1,
                                "span_id": 2, "parent_id": 2,
                                "name": "x", "ts": 0.0,
                                "dur_s": 0.1}) + "\n")
        with pytest.raises(ValueError, match="its own parent"):
            tracing.validate_trace_log(p)
        with open(p, "w") as f:
            f.write(json.dumps({"kind": "span", "trace_id": 1,
                                "span_id": 2, "parent_id": 0,
                                "ts": 0.0, "dur_s": 0.1}) + "\n")
        with pytest.raises(ValueError, match="'name'"):
            tracing.validate_trace_log(p)

    def test_check_metrics_log_cli_trace_mode(self, tmp_path):
        from tools import check_metrics_log
        tr = self._traced()
        p = str(tmp_path / "trace.jsonl")
        tr.export_jsonl(p)
        assert check_metrics_log.main([p, "--trace"]) == 0
        assert check_metrics_log.main(
            [p, "--trace", "--require-spans", "99"]) == 1

    def test_record_event_folds_into_timeline(self):
        from paddle_tpu import profiler
        tr = tracing.default()
        tr.clear()
        tr.enable()
        try:
            with tr.span("step"):
                with profiler.record_event("my_region"):
                    pass
            spans = {s.name: s for s in tr.spans()}
        finally:
            tr.disable()
            tr.clear()          # leave the process-default tracer clean
        assert "my_region" in spans
        assert spans["my_region"].parent_id == spans["step"].span_id


# ---------------------------------------------------------------------------
class TestExposition:
    def _get(self, url):
        with urllib.request.urlopen(url, timeout=5) as r:
            return r.read().decode()

    def test_endpoint_round_trip(self):
        reg = obs.MetricsRegistry()
        reg.counter("rt_total", "h").inc(7)
        reg.histogram("rt_seconds").observe(0.25)
        tr = tracing.Tracer(capacity=16)
        tr.record_span("x", duration_s=0.1)
        srv = obs.ExpositionServer(registry=reg, tracer=tr)
        srv.add_health("engine", lambda: {"queue_depth": 3})
        with srv:
            assert srv.port > 0          # ephemeral bind, port-0 default
            m = self._get(srv.url + "/metrics")
            assert "rt_total 7" in m
            assert "rt_seconds_count 1" in m
            assert m.count("# TYPE rt_seconds histogram") == 1
            hz = json.loads(self._get(srv.url + "/healthz"))
            # pinned healthz surface
            for k in ("status", "time", "uptime_s", "tracing_enabled",
                      "providers"):
                assert k in hz
            assert hz["status"] == "ok"
            assert hz["providers"]["engine"]["queue_depth"] == 3
            t = json.loads(self._get(srv.url + "/traces"))
            assert t["count"] == 1 and t["capacity"] == 16
            assert t["spans"][0]["name"] == "x"
            t2 = json.loads(self._get(srv.url + "/traces?limit=0"))
            assert t2["count"] == 0
            with pytest.raises(urllib.error.HTTPError) as ei:
                self._get(srv.url + "/traces?limit=abc")
            assert ei.value.code == 400  # caller error, not server fault
            with pytest.raises(urllib.error.HTTPError) as ei:
                self._get(srv.url + "/nope")
            assert ei.value.code == 404

    def test_degraded_provider_returns_503(self):
        srv = obs.ExpositionServer(registry=obs.MetricsRegistry(),
                                   tracer=tracing.Tracer(capacity=4))

        def bad():
            raise RuntimeError("engine gone")

        srv.add_health("bad", bad)
        with srv:
            with pytest.raises(urllib.error.HTTPError) as ei:
                self._get(srv.url + "/healthz")
            assert ei.value.code == 503
            body = json.loads(ei.value.read().decode())
            assert body["status"] == "degraded"
            assert "engine gone" in body["providers"]["bad"]["error"]

    def test_metrics_parse_as_prometheus(self):
        """Every exposition line must be '# ...' or 'name{...} value'."""
        reg = obs.MetricsRegistry()
        reg.counter("a_total").inc(labelled="va\"l", other="x\ny")
        reg.histogram("b_seconds").observe(1.0, route="/x")
        srv = obs.ExpositionServer(registry=reg)
        with srv:
            text = self._get(srv.url + "/metrics")
        for line in text.strip().split("\n"):
            if line.startswith("#"):
                assert line.split()[1] in ("HELP", "TYPE")
                continue
            name_part, value = line.rsplit(" ", 1)
            float(value)             # parses
            assert name_part[0].isalpha()


# ---------------------------------------------------------------------------
class TestBurnRate:
    def _setup(self, budget=0.5, objective=0.99, windows=(10.0, 50.0),
               **kw):
        reg = obs.MetricsRegistry()
        tr = tracing.Tracer(capacity=64)
        h = reg.histogram("lat_seconds", buckets=(0.1, 0.5, 1.0, 5.0))
        clock = [0.0]
        mon = slo_mod.BurnRateMonitor(
            "lat_seconds", budget, objective=objective, windows=windows,
            registry=reg, tracer=tr, clock=lambda: clock[0], **kw)
        return reg, tr, h, clock, mon

    def test_silent_under_budget(self):
        reg, tr, h, clock, mon = self._setup()
        for _ in range(100):
            h.observe(0.05)
        clock[0] = 5.0
        burn = mon.check()
        assert burn == {"fast": 0.0, "slow": 0.0}
        assert mon.alerts_total == 0 and mon.alerting() == []
        assert reg.gauge("slo_burn_rate").value(
            slo="lat_seconds", window="fast") == 0.0
        assert tr.spans(name="slo.alert") == []

    def test_alert_fires_on_breach_and_is_edge_triggered(self):
        reg, tr, h, clock, mon = self._setup()
        for _ in range(50):
            h.observe(0.05)
        for _ in range(50):
            h.observe(3.0)           # half the traffic breaches
        clock[0] = 5.0
        burn = mon.check()
        # violation frac 0.5 / error budget 0.01 = burn 50 >= page 14.4
        assert burn["fast"] == pytest.approx(50.0)
        assert burn["slow"] == pytest.approx(50.0)
        # firing page also marks the implied ticket band active (same
        # excursion — decay through it must not mint a second alert)
        assert mon.alerts_total == 1
        assert mon.alerting() == ["page", "ticket"]
        assert reg.counter("slo_alerts_total").value(
            slo="lat_seconds", severity="page") == 1
        # alert event lands in the trace with its context
        (alert,) = tr.spans(name="slo.alert")
        assert alert.attrs["severity"] == "page"
        assert alert.attrs["slo"] == "lat_seconds"
        # edge-triggered: still burning, but no second count
        clock[0] = 6.0
        mon.check()
        assert mon.alerts_total == 1

    def test_rearm_after_recovery(self):
        # single threshold: the recovery path must RE-ARM (a decaying
        # excursion is one alert, a fresh breach is a second)
        reg, tr, h, clock, mon = self._setup(
            windows=(2.0, 4.0), thresholds=(("page", 14.4),))
        for _ in range(10):
            h.observe(3.0)
        clock[0] = 1.0
        mon.check()
        assert mon.alerts_total == 1
        # healthy traffic only; the breach ages out of both windows
        for t in range(2, 8):
            for _ in range(100):
                h.observe(0.01)
            clock[0] = float(t)
            mon.check()
        assert mon.alerting() == []
        # a NEW breach fires a NEW alert
        for _ in range(200):
            h.observe(3.0)
        clock[0] = 8.0
        mon.check()
        assert mon.alerts_total == 2

    def test_fast_spike_alone_does_not_page(self):
        """Multi-window discipline: a burst that dominates the fast
        window but not the slow one (long healthy history) stays quiet
        — checks run at the engine's step cadence, so each second gets
        a sample and the windows resolve properly."""
        reg, tr, h, clock, mon = self._setup(windows=(2.0, 100.0))
        for t in range(1, 51):       # 50 s of healthy step-rate checks
            for _ in range(200):
                h.observe(0.05)
            clock[0] = float(t)
            mon.check()
        for _ in range(400):
            h.observe(3.0)           # brief violent spike
        clock[0] = 51.0
        burn = mon.check()
        assert burn["fast"] >= 14.4          # fast window screams
        assert burn["slow"] < 14.4           # slow window absorbs it
        assert mon.alerts_total == 0

    def test_decay_through_lower_band_does_not_realert(self):
        """One count per excursion: burn decaying from the page band
        into the ticket band must NOT mint a fresh ticket alert."""
        reg, tr, h, clock, mon = self._setup(windows=(2.0, 4.0))
        for _ in range(20):
            h.observe(3.0)
        for _ in range(100):
            h.observe(0.01)
        clock[0] = 1.0
        mon.check()                  # frac 20/120 -> burn 16.7: page
        assert mon.alerts_total == 1
        # ticket-band burn in both windows (fast ~7, slow ~10.6)
        for _ in range(14):
            h.observe(3.0)
        for _ in range(186):
            h.observe(0.01)
        clock[0] = 3.0
        burn = mon.check()
        assert 6.0 <= burn["fast"] < 14.4
        assert 6.0 <= burn["slow"] < 14.4
        assert mon.alerts_total == 1          # same excursion
        assert mon.alerting() == ["ticket"]

    def test_mid_bucket_budget_never_pages_on_compliant_traffic(self):
        """Conservative violation counting: a budget sitting inside a
        bucket must not count that bucket's (compliant) samples as
        violations — an interpolating count would page here."""
        # budget 0.3 is inside bucket (0.1, 0.5]; traffic at 0.2 meets
        # it; one real outlier keeps max above the budget
        reg, tr, h, clock, mon = self._setup(budget=0.3)
        for _ in range(100):
            h.observe(0.2)
        h.observe(20.0)
        clock[0] = 5.0
        burn = mon.check()
        assert burn["fast"] == pytest.approx((1 / 101) / 0.01)
        assert mon.alerts_total == 0
        assert h.count_over(0.3) == 1.0
        assert h.count_over(30.0) == 0.0
        assert h.count_over(0.01) == 101.0

    def test_burn_never_negative_across_count_regimes(self):
        """count_and_over reads EXACT while all traffic violates
        (min > budget) and degrades to conservative once an in-budget
        sample arrives — the falling 'over' must clamp, never publish
        a negative burn."""
        reg, tr, h, clock, mon = self._setup(budget=0.3)
        # all-violating traffic in the budget's own bucket (0.1, 0.5]
        for _ in range(10):
            h.observe(0.45)
        clock[0] = 1.0
        burn = mon.check()               # exact regime: all over
        assert burn["fast"] > 0
        h.observe(0.05)                  # min drops below the budget
        clock[0] = 2.0
        burn = mon.check()               # conservative regime: over=0
        assert burn["fast"] >= 0.0 and burn["slow"] >= 0.0
        assert reg.gauge("slo_burn_rate").value(
            slo="lat_seconds", window="fast") >= 0.0

    def test_count_le_interpolation(self):
        reg = obs.MetricsRegistry()
        h = reg.histogram("x_seconds", buckets=(1.0, 2.0, 4.0))
        for v in (0.5, 1.5, 1.5, 3.0, 9.0):
            h.observe(v)
        assert h.count_le(0.1) == 0.0
        assert h.count_le(10.0) == 5.0
        assert h.count_le(4.0) == pytest.approx(4.0)
        mid = h.count_le(2.0)
        assert 2.0 <= mid <= 4.0

    def test_bad_config_rejected(self):
        reg = obs.MetricsRegistry()
        with pytest.raises(ValueError, match="objective"):
            slo_mod.BurnRateMonitor("m", 1.0, objective=1.5, registry=reg)
        with pytest.raises(ValueError, match="budget_s"):
            slo_mod.BurnRateMonitor("m", 0.0, registry=reg)
        with pytest.raises(ValueError, match="window"):
            slo_mod.BurnRateMonitor("m", 1.0, windows=(60.0, 30.0),
                                    registry=reg)


# ---------------------------------------------------------------------------
def _tiny_engine(**kw):
    import jax

    from paddle_tpu import serving
    from paddle_tpu.models.gpt import GPT, GPTConfig

    cfg = GPTConfig.tiny(vocab_size=64, hidden_size=16, num_layers=2,
                         num_heads=2, ffn_size=32, max_position=64,
                         dropout=0.0, attn_impl="xla")
    model = GPT(cfg)
    params = model.init(jax.random.PRNGKey(0))
    kw.setdefault("num_slots", 2)
    kw.setdefault("page_size", 4)
    kw.setdefault("prefill_chunk", 8)
    kw.setdefault("attn_impl", "lax")
    eng = serving.ServingEngine(model, params, **kw)
    return eng


class TestServingLifecycleTrace:
    def test_request_trace_reconstructs_lifecycle(self):
        """ISSUE acceptance: one request's spans rebuild queue →
        admitted → N prefill chunks → M decode steps → finished, and
        the zero-recompile invariant holds WITH tracing enabled."""
        reg = obs.MetricsRegistry()
        tr = tracing.Tracer(capacity=2048)
        eng = _tiny_engine(registry=reg, tracer=tr)
        eng.warmup()
        det = obs.RecompileDetector("trace_test", warmup=0, registry=reg)
        prompt = np.arange(1, 13, dtype=np.int32)     # 12 tokens, chunk 8
        rid = eng.submit(prompt, 6)
        while not eng.scheduler.idle():
            eng.step()
        det.check()
        assert det.recompiles == 0     # tracing never touches jit
        stats = eng.request_stats(rid)
        trace_id = int(stats["trace_id"])
        assert trace_id > 0
        spans = tr.spans(trace_id=trace_id)
        (root,) = [s for s in spans if s.name == "serving.request"]
        events = [e[1] for e in root.events]
        assert events[0] == "submitted"
        assert "admitted" in events and "first_token" in events
        assert events[-1] == "finished"
        chunks = [s for s in spans if s.name == "serving.prefill_chunk"]
        blocks = [s for s in spans if s.name == "serving.decode_block"]
        assert len(chunks) == 2        # ceil(12 / 8)
        assert len(blocks) >= 1
        assert all(s.parent_id == root.span_id for s in chunks + blocks)
        # per-phase breakdown sourced from those spans
        assert stats["prefill_chunks"] == 2
        assert stats["decode_blocks"] == len(blocks)
        # (the two chunks are one call's run: its wall once)
        assert len({s.attrs["call"] for s in chunks}) == 1
        assert stats["prefill_compute_s"] == pytest.approx(
            chunks[0].duration_s)
        assert stats["decode_s"] == pytest.approx(
            sum(s.duration_s for s in blocks))
        # the whole thing exports as a valid Perfetto timeline
        tracing.chrome_trace_valid(tr.to_chrome(), require_events=4)

    def test_shed_request_trace_explains_why(self):
        """A deadline-expired shed leaves a finished span whose events
        carry the reason (satellite acceptance)."""
        clock = [0.0]
        reg = obs.MetricsRegistry()
        tr = tracing.Tracer(capacity=256)
        eng = _tiny_engine(registry=reg, tracer=tr)
        eng.scheduler._clock = lambda: clock[0]
        eng.warmup()
        # fill both slots so the victim has to queue
        r1 = eng.submit(np.arange(1, 5, dtype=np.int32), 8)
        r2 = eng.submit(np.arange(1, 5, dtype=np.int32), 8)
        victim = eng.submit(np.arange(1, 5, dtype=np.int32), 8,
                            lane="interactive", ttft_deadline_s=0.5)
        clock[0] = 1.0                 # deadline passes while queued
        eng.step()
        rej = eng.reject_reason(victim)
        assert rej is not None and rej.reason == "deadline_expired"
        roots = [s for s in tr.spans(name="serving.request")
                 if s.attrs.get("rid") == victim]
        (root,) = roots
        assert root.status == "shed"
        shed_events = [e for e in root.events if e[1] == "shed"]
        assert shed_events[0][2]["reason"] == "deadline_expired"

    def test_submit_shed_records_reason_span(self):
        reg = obs.MetricsRegistry()
        tr = tracing.Tracer(capacity=64)
        eng = _tiny_engine(registry=reg, tracer=tr, max_queue_depth=0)
        from paddle_tpu.serving import LoadShedError
        with pytest.raises(LoadShedError):
            eng.submit(np.arange(1, 5, dtype=np.int32), 4)
        (sp,) = tr.spans(name="serving.request")
        assert sp.status == "shed"
        assert sp.attrs["shed_reason"] == "queue_full"

    def test_scheduler_decisions_annotated(self):
        """sched_skip (page starvation) + sched_boost (EDF at-risk)
        events land on the affected request's span with reasons."""
        reg = obs.MetricsRegistry()
        tr = tracing.Tracer(capacity=256)
        # starved pool: 4 usable pages; one 16-token request takes all.
        # decode_block=2 keeps the first request running several steps,
        # so the starved one is skipped repeatedly while a slot is free
        eng = _tiny_engine(registry=reg, tracer=tr,
                           max_tokens_per_slot=16, num_pages=5,
                           prefill_chunk=4, decode_block=2)
        eng.warmup()
        p = np.arange(1, 9, dtype=np.int32)
        eng.submit(p, 8)
        eng.step()                         # admit: pool now exhausted
        # estimator >> deadline (the first request's real TTFT is in
        # the EWMA too, so push it well above the 1 s deadline)
        for _ in range(5):
            eng.scheduler.note_ttft(10.0)
        starved = eng.submit(p, 8, lane="interactive",
                             ttft_deadline_s=1.0)
        while not eng.scheduler.idle():
            eng.step()
        (root,) = [s for s in tr.spans(name="serving.request")
                   if s.attrs.get("rid") == starved]
        names = [e[1] for e in root.events]
        assert "sched_boost" in names
        assert "sched_skip" in names
        skip = next(e for e in root.events if e[1] == "sched_skip")
        assert skip[2]["reason"] == "no_capacity"
        assert "finished" in names          # still served eventually

    def test_tracing_disabled_engine_unaffected(self):
        reg = obs.MetricsRegistry()
        tr = tracing.Tracer(capacity=8, enabled=False)
        eng = _tiny_engine(registry=reg, tracer=tr)
        eng.warmup()
        rid = eng.submit(np.arange(1, 6, dtype=np.int32), 4)
        while not eng.scheduler.idle():
            eng.step()
        assert tr.spans() == []
        stats = eng.request_stats(rid)
        assert stats["trace_id"] == 0.0
        # phase accumulators still populate (cheap floats, not spans)
        assert stats["decode_blocks"] >= 1


class TestServingLiveEndpoints:
    def _get(self, url):
        with urllib.request.urlopen(url, timeout=5) as r:
            return r.read().decode()

    def test_metrics_healthz_traces_from_running_engine(self):
        """ISSUE acceptance: /metrics, /healthz, /traces served live
        from a running engine, and slo_alerts_total increments on a
        synthetic TTFT-budget breach."""
        reg = obs.MetricsRegistry()
        tr = tracing.Tracer(capacity=512)
        # 1us budget: every real TTFT is a synthetic breach
        eng = _tiny_engine(registry=reg, tracer=tr, ttft_budget_s=1e-6)
        eng.warmup()
        srv = eng.start_exposition()
        try:
            for _ in range(3):
                eng.submit(np.arange(1, 6, dtype=np.int32), 4)
            while not eng.scheduler.idle():
                eng.step()
                hz = json.loads(self._get(srv.url + "/healthz"))
                assert hz["status"] == "ok"
            s = hz["providers"]["serving"]
            for k in ("slot_occupancy", "queue_depth",
                      "page_utilization", "recompiles",
                      "requests_in_flight", "steps", "slo"):
                assert k in s, f"healthz serving payload missing {k}"
            assert s["recompiles"] == 0
            assert s["slo"]["alerts_total"] >= 1     # breach alerted
            m = self._get(srv.url + "/metrics")
            assert "serving_ttft_seconds_count" in m
            assert "slo_burn_rate" in m
            assert 'slo_alerts_total{severity="page"' in m
            t = json.loads(self._get(srv.url + "/traces"))
            assert t["count"] > 0
            assert any(sp["name"] == "serving.request"
                       for sp in t["spans"])
        finally:
            srv.stop()
        assert reg.counter("slo_alerts_total").value(
            slo="serving_ttft_seconds", severity="page") >= 1

    def test_generous_budget_stays_silent(self):
        reg = obs.MetricsRegistry()
        eng = _tiny_engine(registry=reg, ttft_budget_s=1e6)
        eng.warmup()
        eng.submit(np.arange(1, 6, dtype=np.int32), 4)
        while not eng.scheduler.idle():
            eng.step()
        assert eng.slo_monitor.alerts_total == 0
        assert eng.slo_monitor.burn["fast"] == 0.0


# ---------------------------------------------------------------------------
class TestBackgroundThreadSpans:
    def test_trainer_fit_steps_traced(self, tmp_path):
        import jax
        import jax.numpy as jnp

        from paddle_tpu.trainer import Trainer

        def train_step(state, x):
            return dict(state, step=state["step"] + 1), \
                {"loss": jnp.mean(x)}

        tr = tracing.default()
        tr.clear()
        tr.enable(capacity=256)
        try:
            t = Trainer(train_step,
                        {"step": jnp.asarray(0), "params": {}},
                        telemetry=False, log_every=0)
            t.fit([{"x": jnp.ones((2, 2))} for _ in range(3)])
            fit = tr.spans(name="trainer.fit")
            steps = tr.spans(name="trainer.step")
        finally:
            tr.disable()
            tr.clear()          # leave the process-default tracer clean
        assert len(fit) == 1 and len(steps) == 3
        assert all(s.parent_id == fit[0].span_id for s in steps)
        assert [s.attrs["step"] for s in steps] == [1, 2, 3]

    def test_snapshot_save_restore_spans_cross_thread(self, tmp_path):
        import jax.numpy as jnp

        from paddle_tpu.resilience import SnapshotEngine

        tr = tracing.default()
        tr.clear()
        tr.enable(capacity=256)
        try:
            eng = SnapshotEngine(str(tmp_path), process_index=0,
                                 process_count=1)
            state = {"w": jnp.arange(8.0)}
            eng.save(3, state, wait=True)
            eng.restore(3)
            eng.close()
            (blocking,) = tr.spans(name="snapshot.save_blocking")
            (write,) = tr.spans(name="snapshot.write")
            (restore,) = tr.spans(name="snapshot.restore")
        finally:
            tr.disable()
            tr.clear()          # leave the process-default tracer clean
        # the writer thread's span is parented to the caller's save —
        # explicit cross-thread attribution
        assert write.parent_id == blocking.span_id
        assert write.trace_id == blocking.trace_id
        assert write.thread != blocking.thread
        assert restore.attrs["step"] == 3

    def test_streaming_applier_spans(self):
        from paddle_tpu.embedding_serving import StreamingUpdateChannel

        class _Store:
            dim = 4

            def set_rows(self, ids, vals):
                pass

        tr = tracing.Tracer(capacity=64)
        ch = StreamingUpdateChannel(_Store(), registry=obs.MetricsRegistry(),
                                    tracer=tr)
        try:
            ch.push_rows(np.asarray([1, 2], np.int64),
                         np.ones((2, 4), np.float32))
            ch.flush()
        finally:
            ch.stop()
        applies = tr.spans(name="embed.stream_apply")
        assert applies and applies[0].attrs["rows"] == 2
        # applier thread's own trace — not parented to the pusher
        assert applies[0].parent_id == 0
        assert applies[0].thread != threading.current_thread().name


class TestEmbeddingServingTrace:
    def test_batch_lifecycle_spans(self):
        from paddle_tpu import embedding_serving as es
        from paddle_tpu.parallel.host_kv import HostKVStore

        store = HostKVStore(dim=4)
        try:
            tr = tracing.Tracer(capacity=256)
            eng = es.EmbeddingServingEngine(
                store, capacity=64, min_bucket=8,
                registry=obs.MetricsRegistry(), tracer=tr)
            ids = np.asarray([[1, 2], [3, 1]], np.int64)
            rid = eng.submit(ids)
            out = eng.step()
            assert rid in out
            (root,) = tr.spans(name="embed.request")
            events = [e[1] for e in root.events]
            assert "dedup" in events and "pull_issued" in events
            assert events[-1] == "finished"
            assert root.attrs["uniq"] == 3
            for child in ("embed.pull_wait", "embed.install",
                          "embed.gather_forward"):
                (sp,) = tr.spans(name=child)
                assert sp.parent_id == root.span_id
        finally:
            store.close()

    def test_failed_step_preserves_span_with_error_status(self):
        """An exception after the batch is popped must still land its
        root span in the ring (the failing request's trace is the one
        an operator needs most)."""
        from paddle_tpu import embedding_serving as es
        from paddle_tpu.parallel.host_kv import HostKVStore

        store = HostKVStore(dim=4)
        try:
            tr = tracing.Tracer(capacity=64)
            eng = es.EmbeddingServingEngine(
                store, capacity=64, min_bucket=8,
                registry=obs.MetricsRegistry(), tracer=tr)
            eng.submit(np.asarray([[1, 2]], np.int64))

            def boom(*a, **kw):
                raise RuntimeError("device gone")

            eng.cache.gather = boom
            with pytest.raises(RuntimeError, match="device gone"):
                eng.step()
            (root,) = tr.spans(name="embed.request")
            assert root.status == "error"
            assert root.events[-1][1] == "error"
        finally:
            store.close()


# ---------------------------------------------------------------------------
class TestReportIntegration:
    def test_report_includes_trace_and_slo_sections(self):
        reg = obs.MetricsRegistry()
        tr = tracing.Tracer(capacity=32)
        tr.record_span("serving.request", duration_s=0.2)
        tr.record_span("serving.request", duration_s=0.1)
        tr.record_span("embed.request", duration_s=0.05)
        h = reg.histogram("lat_seconds", buckets=(0.1, 1.0))
        clock = [0.0]
        mon = slo_mod.BurnRateMonitor("lat_seconds", 0.1, registry=reg,
                                      tracer=tr,
                                      clock=lambda: clock[0])
        for _ in range(10):
            h.observe(5.0)
        clock[0] = 1.0
        mon.check()
        text = obs.report(reg, tracer=tr)
        assert "-- trace spans --" in text
        assert "serving.request" in text
        assert "-- slo --" in text
        assert "burn_rate slo=lat_seconds window=fast" in text
        assert "alerts slo=lat_seconds severity=page 1" in text

    def test_default_report_unchanged_without_tracing(self):
        reg = obs.MetricsRegistry()
        reg.counter("c_total").inc()
        text = obs.report(reg, tracer=tracing.Tracer(capacity=4))
        assert "-- trace spans --" not in text
        assert "-- slo --" not in text


# ---------------------------------------------------------------------------
#: ISSUE 25: every phase span of one engine step and its parent
STEP_TREE = {
    "serving.step": None,
    "serving.shed": "serving.step",
    "serving.admit": "serving.step",
    "serving.evict": "serving.step",
    "serving.observe": "serving.step",
    "serving.prefill_round": "serving.step",
    "serving.prefill_call": "serving.prefill_round",
    "serving.prefill.assemble": "serving.prefill_call",
    "serving.prefill.cow_copy": "serving.prefill_call",
    "serving.prefill.dispatch": "serving.prefill_call",
    "serving.prefill.sync": "serving.prefill_call",
    "serving.prefill.book": "serving.prefill_call",
    "serving.decode_round": "serving.step",
    "serving.decode.assemble": "serving.decode_round",
    "serving.decode.dispatch": "serving.decode_round",
    "serving.decode.sync": "serving.decode_round",
    "serving.decode.book": "serving.decode_round",
}
#: tiny engine: 2 layers x 2 heads x 8 x float32, keys and values
TOKEN_BYTES = 2 * 2 * 8 * 4 * 2


def _drain(eng):
    while not eng.scheduler.idle():
        eng.step()


def _part_seconds(reg):
    return {k: v for k, v in reg.snapshot().items()
            if k.startswith("serving_step_part_seconds_total")}


class TestEngineStepPhases:
    @pytest.fixture(scope="class")
    def traced_run(self):
        """One warmed engine's traced run, read by three tests."""
        reg = obs.MetricsRegistry()
        tr = tracing.Tracer(capacity=8192)
        eng = _tiny_engine(registry=reg, tracer=tr)
        eng.warmup()
        tr.clear()
        det = obs.RecompileDetector("phase_test", warmup=0, registry=reg)
        prompt = np.arange(1, 11, dtype=np.int32)   # 2 full pages + tail
        eng.submit(prompt.copy(), 6)
        _drain(eng)
        # the same prompt again maps the published tail page: a CoW copy
        eng.submit(prompt.copy(), 6)
        eng.submit(np.arange(20, 27, dtype=np.int32), 5)
        _drain(eng)
        # a budget of one token: the step evicts on the first token, so
        # this prompt's last prefill call is one that reads back
        eng.submit(np.arange(30, 36, dtype=np.int32), 1)
        _drain(eng)
        det.check()
        return eng, reg, tr, det

    def test_span_tree_of_a_step_is_the_table(self, traced_run):
        eng, reg, tr, det = traced_run
        assert det.recompiles == 0          # spans never touch jit
        spans = tr.spans()
        by_id = {s.span_id: s for s in spans}
        seen = set()
        for s in spans:
            if s.name not in STEP_TREE:
                continue
            seen.add(s.name)
            want = STEP_TREE[s.name]
            if want is None:
                assert s.parent_id == 0, s.name
            else:
                assert by_id[s.parent_id].name == want, s.name
        assert seen == set(STEP_TREE)
        # nothing else of the engine's step is in the ring under another name
        others = {s.name for s in spans if s.name.startswith("serving.")}
        assert others - set(STEP_TREE) == {
            "serving.request", "serving.prefill_chunk",
            "serving.decode_block"}
        steps = [s.attrs["step"] for s in spans if s.name == "serving.step"]
        assert len(steps) >= 2 and steps == sorted(set(steps))
        call = next(s for s in spans if s.name == "serving.prefill_call")
        assert {"lanes", "width", "tokens"} <= set(call.attrs)
        # ISSUE 31: only a call whose first token the step needs at once
        # waits; every other call has no sync span, and a step waits once
        calls = [s for s in spans if s.name == "serving.prefill_call"]
        syncs = [s for s in spans if s.name == "serving.prefill.sync"]
        assert len(calls) >= 3 and len(syncs) == 1
        snap = reg.snapshot()
        assert snap['serving_device_readbacks_total{phase="prefill"}'] == 1
        assert snap['serving_device_readbacks_total{phase="decode"}'] == len(
            [s for s in spans if s.name == "serving.decode.sync"]) \
            == snap["serving_decode_rounds_total"]
        rnd = next(s for s in spans if s.name == "serving.decode_round")
        assert {"width", "slots_live"} <= set(rnd.attrs)

    def test_request_children_name_the_call_that_caused_them(self,
                                                             traced_run):
        eng, reg, tr, det = traced_run
        spans = tr.spans()
        calls = {s.span_id for s in spans if s.name == "serving.prefill_call"}
        rounds = {s.span_id for s in spans
                  if s.name == "serving.decode_round"}
        chunks = [s for s in spans if s.name == "serving.prefill_chunk"]
        blocks = [s for s in spans if s.name == "serving.decode_block"]
        assert chunks and blocks
        assert all(s.attrs["call"] in calls for s in chunks)
        assert all(s.attrs["call"] in rounds for s in blocks)

    def test_disabled_and_unprofiled_step_allocates_no_span(self,
                                                            monkeypatch):
        reg = obs.MetricsRegistry()
        tr = tracing.Tracer(capacity=8, enabled=False)
        eng = _tiny_engine(registry=reg, tracer=tr)
        eng.warmup()
        made = []
        init = tracing.Span.__init__
        monkeypatch.setattr(
            tracing.Span, "__init__",
            lambda self, *a, **k: (made.append(1), init(self, *a, **k))[1])
        eng.submit(np.arange(1, 6, dtype=np.int32), 4)
        _drain(eng)
        assert made == [] and tr.spans() == []
        # the counters at the same boundaries are on all the same
        assert sum(_part_seconds(reg).values()) > 0

    def test_parts_sum_to_at_most_the_step_seconds(self, traced_run):
        eng, reg, tr, det = traced_run
        snap = reg.snapshot()
        parts = _part_seconds(reg)
        assert len(parts) == 11 and all(v >= 0 for v in parts.values())
        for key in ('phase="prefill"', 'phase="decode"',
                    'phase="sched"', 'phase="observe"'):
            assert sum(v for k, v in parts.items() if key in k) > 0, key
        assert 0 < sum(parts.values()) <= snap["serving_step_seconds_total"]
        assert snap["serving_prefill_calls_total"] == len(
            tr.spans(name="serving.prefill_call"))
        # a round that had a slot to advance dispatched a block; the last
        # step of a drain only settles the block in flight (ISSUE 34)
        rounds = tr.spans(name="serving.decode_round")
        assert snap["serving_decode_rounds_total"] == len(
            [s for s in rounds if s.attrs["slots_live"]])
        assert len(rounds) - snap["serving_decode_rounds_total"] == 2
        # an idle tick is no step that did work
        before = snap["serving_step_seconds_total"]
        eng.step()
        assert reg.snapshot()["serving_step_seconds_total"] == before

    def test_kv_bytes_of_a_decode_round_match_a_hand_count(self):
        reg = obs.MetricsRegistry()
        eng = _tiny_engine(registry=reg, decode_block=4,
                           tracer=tracing.Tracer(enabled=False))
        eng.warmup()
        eng.submit(np.arange(1, 6, dtype=np.int32), 3)      # 5 tokens
        eng.submit(np.arange(1, 8, dtype=np.int32), 3)      # 7 tokens
        _drain(eng)
        snap = reg.snapshot()
        assert snap["serving_decode_rounds_total"] == 1
        # 4 token steps; slot lengths 5 and 7 before the round: token
        # step j attends over L + j + 1 tokens -> 4 * 12 + 2 * (1+2+3+4)
        live = (4 * (5 + 7) + 2 * 10) * TOKEN_BYTES
        # the grid visits 2 slots x 4 pages (11 tokens -> 3 pages of 4,
        # gathered at the pow2 width 4)
        gathered = 4 * 2 * 4 * 4 * TOKEN_BYTES
        assert snap['serving_decode_kv_bytes_total{kind="live"}'] == live
        assert snap['serving_decode_kv_bytes_total{kind="gathered"}'] \
            == gathered
        assert gathered >= live > 0

    def test_a_profiler_session_sees_the_step_and_its_children(self,
                                                               tmp_path):
        import glob

        import jax.profiler
        eng = _tiny_engine(registry=obs.MetricsRegistry(),
                           tracer=tracing.Tracer(enabled=False))
        eng.warmup()
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            eng.submit(np.arange(1, 11, dtype=np.int32), 5)
            eng.submit(np.arange(30, 36, dtype=np.int32), 1)   # reads back
            _drain(eng)
        finally:
            jax.profiler.stop_trace()
        (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                                / "*.xplane.pb"))
        events = [ev for plane in
                  jax.profiler.ProfileData.from_file(path).planes
                  for line in plane.lines for ev in line.events
                  if ev.name.startswith("serving.")]
        # attributes come back as stats, or inside the name after a "#"
        assert {ev.name.split("#")[0] for ev in events} >= set(
            STEP_TREE) - {"serving.prefill.cow_copy"}
        step = next(ev for ev in events
                    if ev.name.split("#")[0] == "serving.step")
        attrs = {k for k, _ in step.stats} | set(
            kv.split("=")[0] for kv in step.name.partition("#")[2]
            .strip("#").split(",") if kv)
        assert {"step", "t_mono_ns"} <= attrs

    def test_step_histograms_have_buckets_a_median_can_be_taken_from(self):
        reg = obs.MetricsRegistry()
        _tiny_engine(registry=reg)
        for name in ("serving_decode_step_seconds",
                     "serving_prefill_step_seconds"):
            b = reg.get(name).buckets
            assert b[0] == 1e-3 and 2.0 <= b[-1] < 2.1
            assert all(1.4 < hi / lo < 1.43 for lo, hi in zip(b, b[1:]))

    def test_warmup_seconds_split_by_part(self):
        reg = obs.MetricsRegistry()
        eng = _tiny_engine(registry=reg)
        eng.warmup(cost_gauges=False)
        snap = reg.snapshot()
        first = snap['serving_warmup_seconds_total{part="first_call"}']
        assert first > 0
        assert snap['serving_warmup_seconds_total{part="cost_gauges"}'] == 0
        eng.warmup()
        snap = reg.snapshot()
        assert snap['serving_warmup_seconds_total{part="cost_gauges"}'] > 0
        assert snap['serving_warmup_seconds_total{part="first_call"}'] \
            > first


class TestPhasePrimitive:
    def test_phase_feeds_ring_counter_and_clock_from_one_read_pair(self):
        reg = obs.MetricsRegistry()
        child = reg.counter("t_part_seconds_total").child(part="x")
        tr = tracing.Tracer(capacity=16)
        with tr.phase("outer", step=3) as outer:
            with tr.phase("inner", child, lanes=2) as inner:
                pass
        o, i = tr.spans(name="outer")[0], tr.spans(name="inner")[0]
        assert i.parent_id == o.span_id and o.parent_id == 0
        assert (i.start, i.end) == (inner.start, inner.end)
        assert inner.span_id == i.span_id and i.attrs == {"lanes": 2}
        assert child.value() == inner.end - inner.start
        assert outer.start <= inner.start <= inner.end <= outer.end

    def test_disabled_phase_still_times_and_counts(self):
        reg = obs.MetricsRegistry()
        child = reg.counter("t_part_seconds_total").child(part="x")
        tr = tracing.Tracer(capacity=16, enabled=False)
        with tr.phase("p", child, stamp=True) as ph:
            pass
        assert ph.span is None and ph.span_id == 0 and tr.spans() == []
        assert child.value() == ph.end - ph.start >= 0

    def test_phase_marks_the_ring_span_on_error(self):
        tr = tracing.Tracer(capacity=16)
        with pytest.raises(RuntimeError):
            with tr.phase("boom"):
                raise RuntimeError("x")
        assert tr.spans(name="boom")[0].status == "error"
        assert tr.current() is None
