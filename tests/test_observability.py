"""Profiler, metrics, debug (NaN checks), fleet role tests."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from paddle_tpu import debug, fleet, metrics, profiler


class TestProfiler:
    def test_record_event_and_summary(self, capsys):
        with profiler.profiler(summary=True):
            with profiler.record_event("fwd"):
                jnp.ones((8, 8)) @ jnp.ones((8, 8))
            with profiler.record_event("fwd"):
                pass
            with profiler.record_event("bwd"):
                pass
        out = capsys.readouterr().out
        assert "fwd" in out and "bwd" in out
        assert "Calls" in out
        # fwd appears with 2 calls
        fwd_line = next(l for l in out.splitlines() if l.startswith("fwd"))
        assert "2" in fwd_line

    def test_named_scope_traces(self):
        # record_event must be usable inside jit (named_scope is traceable)
        @jax.jit
        def f(x):
            with profiler.record_event("matmul"):
                return x @ x

        out = f(jnp.eye(4))
        np.testing.assert_allclose(np.asarray(out), np.eye(4))


class TestMetrics:
    def test_accuracy(self):
        m = metrics.Accuracy()
        m.update(np.array([[0.9, 0.1], [0.2, 0.8]]), np.array([0, 0]))
        assert m.eval() == pytest.approx(0.5)
        m.reset()
        assert m.eval() == 0.0

    def test_auc_perfect_and_random(self):
        m = metrics.Auc()
        probs = np.concatenate([np.random.RandomState(0).uniform(0.6, 1.0, 500),
                                np.random.RandomState(1).uniform(0.0, 0.4, 500)])
        labels = np.concatenate([np.ones(500), np.zeros(500)])
        m.update(probs, labels)
        assert m.eval() > 0.99
        m2 = metrics.Auc()
        rng = np.random.RandomState(2)
        m2.update(rng.uniform(size=2000), rng.randint(0, 2, 2000))
        assert 0.4 < m2.eval() < 0.6

    def test_precision_recall(self):
        m = metrics.PrecisionRecall()
        m.update(np.array([0.9, 0.8, 0.2, 0.1]), np.array([1, 0, 1, 0]))
        r = m.eval()
        assert r["precision"] == pytest.approx(0.5)
        assert r["recall"] == pytest.approx(0.5)

    def test_mean(self):
        m = metrics.MeanMetric()
        m.update(2.0).update(4.0)
        assert m.eval() == pytest.approx(3.0)


class TestDebug:
    def test_check_numerics_passes_clean(self):
        err, out = debug.checked(
            lambda x: debug.check_numerics({"x": x}, "t"))(jnp.ones(3))
        err.throw()  # no error

    def test_check_numerics_catches_nan(self):
        def f(x):
            return debug.check_numerics({"x": x / x}, "t")

        err, _ = debug.checked(f)(jnp.zeros(3))
        with pytest.raises(Exception, match="non-finite"):
            err.throw()

    def test_finite_or_zero(self):
        x = jnp.array([1.0, jnp.inf, jnp.nan])
        np.testing.assert_allclose(np.asarray(debug.finite_or_zero(x)),
                                   [1.0, 0.0, 0.0])


class TestFleet:
    def test_role_from_env(self, monkeypatch):
        monkeypatch.setenv("PADDLE_TRAINER_ID", "2")
        monkeypatch.setenv("PADDLE_TRAINERS_NUM", "4")
        role = fleet.RoleMaker.from_env()
        assert role.worker_index == 2
        assert role.worker_num == 4
        assert not role.is_first_worker()

    def test_single_process_init_noop(self):
        role = fleet.init(fleet.RoleMaker(0, 1))
        assert role.is_first_worker()
        assert fleet.worker_num() == 1

    def test_local_shard(self):
        batch = {"x": np.arange(8)}
        out = fleet.local_shard(batch, index=1, num=4)
        np.testing.assert_array_equal(out["x"], [2, 3])


# ---------------------------------------------------------------------------
# Runtime telemetry subsystem (paddle_tpu.observability)
# ---------------------------------------------------------------------------

from paddle_tpu import observability as obs


class TestRegistry:
    def test_counter_labels(self):
        r = obs.MetricsRegistry()
        c = r.counter("req_total", "requests")
        c.inc(model="a").inc(2, model="a").inc(model="b")
        assert c.value(model="a") == 3
        assert c.value(model="b") == 1
        assert c.value(model="zzz") == 0  # unseen series starts at 0
        with pytest.raises(ValueError):
            c.inc(-1)

    def test_gauge(self):
        r = obs.MetricsRegistry()
        g = r.gauge("mem")
        g.set(5.0)
        g.inc(2.5)
        assert g.value() == pytest.approx(7.5)

    def test_histogram_summary(self):
        r = obs.MetricsRegistry()
        h = r.histogram("lat", buckets=(0.1, 1.0))
        for v in (0.05, 0.5, 5.0):
            h.observe(v)
        s = h.summary()
        assert s["count"] == 3
        assert s["min"] == pytest.approx(0.05)
        assert s["max"] == pytest.approx(5.0)
        assert s["mean"] == pytest.approx((0.05 + 0.5 + 5.0) / 3)

    def test_histogram_quantiles(self):
        """Bucket-interpolated p50/p90/p99 (the SLO surface
        BENCH_SERVING reports): monotone in q, clamped to observed
        min/max, 0 when empty."""
        r = obs.MetricsRegistry()
        h = r.histogram("lat", buckets=(0.1, 0.5, 1.0, 5.0))
        assert h.quantile(0.99) == 0.0                 # empty
        for v in (0.2, 0.3, 0.4, 0.45, 0.6, 0.7, 0.8, 0.9, 0.95, 3.0):
            h.observe(v)
        p = h.percentiles(0.5, 0.9, 0.99)
        assert set(p) == {"p50", "p90", "p99"}
        assert 0.5 <= p["p50"] <= 1.0   # 5th/6th samples' bucket (0.5,1]
        assert p["p50"] <= p["p90"] <= p["p99"] <= 3.0  # clamped to max
        assert p["p99"] > 0.9
        h2 = r.histogram("one", buckets=(10.0,))
        h2.observe(2.0)
        # a single sample in a huge bucket must not report beyond it
        assert h2.quantile(0.99) == pytest.approx(2.0)
        # empty INTERIOR buckets must not drag the estimate below the
        # target bucket's lower edge (one fast outlier + a 4.0s cluster:
        # the median bucket is (3.0, 5.0], so p50 >= 3.0)
        h3 = r.histogram("gap", buckets=(0.005, 0.1, 1.0, 3.0, 5.0))
        h3.observe(0.003)
        for _ in range(99):
            h3.observe(4.0)
        assert 3.0 <= h3.quantile(0.5) <= 4.0
        with pytest.raises(ValueError):
            h.quantile(1.5)

    def test_type_conflict_raises(self):
        r = obs.MetricsRegistry()
        r.counter("x_total")
        with pytest.raises(TypeError):
            r.gauge("x_total")

    def test_same_name_same_object(self):
        r = obs.MetricsRegistry()
        assert r.counter("y_total") is r.counter("y_total")

    def test_snapshot_flattens(self):
        r = obs.MetricsRegistry()
        r.counter("c_total").inc(3, k="v")
        r.histogram("h").observe(2.0)
        snap = r.snapshot()
        assert snap['c_total{k="v"}'] == 3
        assert snap["h_count"] == 1
        assert snap["h_mean"] == pytest.approx(2.0)


class TestPrometheus:
    def test_exposition_format(self):
        r = obs.MetricsRegistry()
        r.counter("runs_total", "bench runs").inc(2, model="bert")
        r.gauge("mfu").set(0.41)
        r.histogram("step_s", buckets=(0.5, 1.0)).observe(0.7)
        text = r.render_prometheus()
        assert "# TYPE runs_total counter" in text
        assert 'runs_total{model="bert"} 2' in text
        assert "# HELP runs_total bench runs" in text
        assert "mfu 0.41" in text
        # histogram triplet: cumulative buckets + sum + count
        assert 'step_s_bucket{le="0.5"} 0' in text
        assert 'step_s_bucket{le="1.0"} 1' in text
        assert 'step_s_bucket{le="+Inf"} 1' in text
        assert "step_s_count 1" in text
        assert text.endswith("\n")

    def test_empty_registry_renders_empty(self):
        assert obs.MetricsRegistry().render_prometheus() == ""


class TestRunLog:
    def test_round_trip(self, tmp_path):
        p = str(tmp_path / "run.jsonl")
        with obs.RunLogWriter(p, meta={"job": "t"}) as w:
            for i in range(3):
                w.write({"step": i, "step_time_s": 0.1,
                         "examples_per_sec": 640.0,
                         "metrics": {"loss": 1.0 / (i + 1)}})
        recs = obs.read_run_log(p)
        assert recs[0]["kind"] == "run_meta" and recs[0]["job"] == "t"
        steps = [r for r in recs if r["kind"] == "step"]
        assert [r["step"] for r in steps] == [0, 1, 2]
        assert steps[2]["metrics"]["loss"] == pytest.approx(1 / 3)
        assert obs.validate_run_log(p, require_steps=3) == 3

    def test_partial_tail_dropped(self, tmp_path):
        p = str(tmp_path / "run.jsonl")
        with obs.RunLogWriter(p) as w:
            w.write({"step": 0, "step_time_s": 0.1,
                     "examples_per_sec": 1.0})
        with open(p, "a") as f:
            f.write('{"step": 1, "step_time')  # crash mid-record
        recs = obs.read_run_log(p)
        assert len(recs) == 1  # partial tail silently dropped

    def test_validator_rejects_bad_records(self, tmp_path):
        p = str(tmp_path / "bad.jsonl")
        with open(p, "w") as f:
            f.write('{"kind": "step", "ts": 1.0, "step": 0}\n')
        with pytest.raises(ValueError, match="step_time_s"):
            obs.validate_run_log(p)
        with open(p, "w") as f:
            f.write('{"kind": "nope", "ts": 1.0}\n')
        with pytest.raises(ValueError, match="unknown kind"):
            obs.validate_run_log(p)

    def test_validator_require_steps(self, tmp_path):
        p = str(tmp_path / "short.jsonl")
        with obs.RunLogWriter(p) as w:
            w.write({"step": 0, "step_time_s": 0.1,
                     "examples_per_sec": 1.0})
        with pytest.raises(ValueError, match="step records"):
            obs.validate_run_log(p, require_steps=5)


class TestRecompileDetector:
    def test_fires_on_shape_change_only(self):
        msgs = []
        det = obs.RecompileDetector("t", log_fn=msgs.append,
                                    registry=obs.MetricsRegistry())
        f = jax.jit(lambda x: x * 2)
        f(jnp.ones((4,)))
        det.check(step=1, feeds={"x": jnp.ones((4,))})
        assert det.recompiles == 0          # warmup compile: counted, no warn
        assert not msgs
        f(jnp.ones((4,)))                    # cache hit
        assert det.check(step=2, feeds={"x": jnp.ones((4,))}) == 0
        f(jnp.ones((6,)))                    # deliberate retrace
        assert det.check(step=3, feeds={"x": jnp.ones((6,))}) >= 1
        assert det.recompiles >= 1
        assert len(msgs) == 1
        assert "RECOMPILATION" in msgs[0]
        assert "float32[6]" in msgs[0]       # arg-shape signature included
        assert "step=3" in msgs[0]

    def test_shape_signature(self):
        sig = obs.shape_signature(
            {"b": jnp.ones((2, 3)), "a": jnp.zeros((4,), jnp.int32)})
        assert sig == "a:int32[4] b:float32[2,3]"
        assert obs.shape_signature(None) == "<no feeds>"


class TestAggregate:
    def test_single_process_noop(self):
        out = obs.aggregate({"step_time_s": 0.25, "eps": 100.0})
        assert out["step_time_s"]["min"] == 0.25
        assert out["step_time_s"]["max"] == 0.25
        assert out["step_time_s"]["mean"] == pytest.approx(0.25)
        assert out["eps"]["argmax"] == 0
        line = obs.format_aggregate(out)
        assert "step_time_s" in line and "host0" in line

    def test_empty(self):
        assert obs.aggregate({}) == {}


class TestReport:
    def test_unified_summary_includes_spans(self):
        from paddle_tpu import profiler as prof
        with prof.record_event("report_span_x"):
            pass
        obs.counter("report_demo_total").inc()
        text = obs.report()
        assert "record_event spans" in text
        assert "report_span_x" in text
        assert "report_demo_total" in text

    def test_fresh_registry_empty(self):
        assert "no metrics recorded" in obs.report(obs.MetricsRegistry())


class TestTrainerTelemetry:
    def _fit(self, tmp_path, shape_break=None, steps=10):
        from paddle_tpu import optimizer as opt
        from paddle_tpu.train import build_train_step, make_train_state
        from paddle_tpu.nn.layers import Linear
        from paddle_tpu.trainer import Trainer

        model = Linear(4, 2)
        optimizer = opt.SGD(learning_rate=0.1)
        state = make_train_state(model, optimizer, jax.random.PRNGKey(0))

        def loss_fn(params, x, y):
            pred = model(params, x)
            return jnp.mean((pred - y) ** 2)

        step = jax.jit(build_train_step(loss_fn, optimizer),
                       donate_argnums=0)
        rng = np.random.RandomState(0)

        def batches():
            for i in range(steps):
                n = 8 if i != shape_break else 4
                yield dict(x=jnp.asarray(rng.randn(n, 4), jnp.float32),
                           y=jnp.asarray(rng.randn(n, 2), jnp.float32))

        log = str(tmp_path / "run.jsonl")
        msgs = []
        tr = Trainer(step, state, log_every=0, run_log=log,
                     log_fn=msgs.append)
        tr.fit(batches())
        return log, msgs

    def test_jsonl_per_step(self, tmp_path):
        log, _ = self._fit(tmp_path)
        recs = obs.read_run_log(log)
        steps = [r for r in recs if r["kind"] == "step"]
        assert len(steps) == 10
        for i, r in enumerate(steps):
            assert r["step"] == i + 1
            assert r["step_time_s"] > 0
            assert r["examples_per_sec"] > 0
            assert "recompiles" in r and "data_wait_s" in r
        assert obs.validate_run_log(log, require_steps=10) == 10
        assert recs[-1]["kind"] == "summary"

    def test_forced_shape_change_detected(self, tmp_path):
        log, msgs = self._fit(tmp_path, shape_break=6)
        steps = [r for r in obs.read_run_log(log) if r["kind"] == "step"]
        assert steps[-1]["recompiles"] >= 1
        assert steps[2]["recompiles"] == 0   # steady prefix is clean
        warn = [m for m in msgs if "RECOMPILATION" in m]
        assert warn and "float32[4,4]" in warn[0]

    def test_telemetry_off(self, tmp_path):
        from paddle_tpu import optimizer as opt
        from paddle_tpu.train import build_train_step, make_train_state
        from paddle_tpu.nn.layers import Linear
        from paddle_tpu.trainer import Trainer

        model = Linear(2, 1)
        optimizer = opt.SGD(learning_rate=0.1)
        state = make_train_state(model, optimizer, jax.random.PRNGKey(0))
        step = jax.jit(build_train_step(
            lambda p, x, y: jnp.mean((model(p, x) - y) ** 2), optimizer),
            donate_argnums=0)
        tr = Trainer(step, state, telemetry=False, log_every=0)
        out = tr.fit([dict(x=jnp.ones((2, 2)), y=jnp.ones((2, 1)))])
        assert "loss" in out


class TestBenchTelemetry:
    def test_write_and_check_cli(self, script, tmp_path, monkeypatch):
        """bench.write_bench_telemetry writes the log, the Prometheus
        dump, and passes its own validator CLI."""
        bench = script("bench")
        log = str(tmp_path / "bench.jsonl")
        monkeypatch.setenv("PADDLE_TPU_METRICS_LOG", log)
        result = {"metric": "m", "value": 10.0, "vs_baseline": 1.0,
                  "_telemetry": {"steps": 4, "dt": 2.0,
                                 "examples_per_step": 32,
                                 "tokens_per_step": 64}}
        path = bench.write_bench_telemetry(result)
        assert path == log
        assert "_telemetry" not in result
        steps = [r for r in obs.read_run_log(log) if r["kind"] == "step"]
        assert len(steps) == 4
        assert steps[0]["examples_per_sec"] == pytest.approx(64.0)
        assert steps[0]["tokens_per_sec"] == pytest.approx(128.0)
        with open(log + ".prom") as f:
            assert 'bench_value{metric="m"} 10' in f.read()


class TestExecutorTelemetry:
    def test_train_from_dataset_run_log(self, tmp_path):
        from paddle_tpu.executor import Executor, Program

        def fn(state, x):
            return state, {"y": x.sum()}

        def dataset():
            for _ in range(12):
                yield np.ones(2, np.float32)

        log = str(tmp_path / "exec.jsonl")
        exe = Executor()
        state, fetches = exe.train_from_dataset(
            Program(fn, name="p"), dataset, None, batch_size=4,
            feed_builder=lambda samples: {"x": np.stack(samples)},
            run_log=log)
        steps = [r for r in obs.read_run_log(log) if r["kind"] == "step"]
        assert len(steps) == 3  # 12 samples / batch 4
        assert obs.validate_run_log(log, require_steps=3) == 3


class TestRegistryConcurrency:
    """Thread-safety audit regression (ISSUE 10 satellite): concurrent
    writers creating NEW label series (the serving step thread vs the
    streaming applier vs the snapshot writer pattern) must never lose
    updates, and concurrent readers must never see a torn exposition."""

    def test_concurrent_writers_and_readers_exact(self):
        import threading

        reg = obs.MetricsRegistry()
        c = reg.counter("conc_total")
        g = reg.gauge("conc_gauge")
        h = reg.histogram("conc_seconds", buckets=(0.1, 1.0, 10.0))
        n_threads, n_iter = 6, 400
        stop = threading.Event()
        render_errors = []

        def writer(tid):
            # distinct label values force label-map mutation under load
            child = c.child(thread=tid)      # lock-protected creation
            hchild = h.child(thread=tid)
            for i in range(n_iter):
                child.inc()
                c.inc(thread=tid, phase=str(i % 5))
                g.set(i, thread=tid)
                hchild.observe(0.5)
                h.observe(5.0, thread=tid, phase=str(i % 3))

        def reader():
            # a scraper hammering exposition mid-write: every render
            # must be internally consistent (+Inf bucket == _count)
            import re
            while not stop.is_set():
                text = reg.render_prometheus()
                reg.snapshot()
                counts = {}
                bucket_cum = {}
                for line in text.splitlines():
                    if line.startswith("conc_seconds_bucket"):
                        series, v = line.rsplit(" ", 1)
                        # strip the le label -> the series' own key;
                        # lines come in le order, keep the LAST (+Inf)
                        key = re.sub(r',le="[^"]*"}$', "}", series)
                        bucket_cum[key] = float(v)
                    elif line.startswith("conc_seconds_count"):
                        series, v = line.rsplit(" ", 1)
                        counts[series.replace("_count", "_bucket")] = \
                            float(v)
                # every count line must have a matching bucket series
                # AND agree with its +Inf cumulative value
                for key, total in counts.items():
                    if key not in bucket_cum:
                        render_errors.append(("missing", key))
                    elif bucket_cum[key] != total:
                        render_errors.append((key, bucket_cum[key],
                                              total))

        threads = [threading.Thread(target=writer, args=(t,))
                   for t in range(n_threads)]
        rthread = threading.Thread(target=reader)
        rthread.start()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        stop.set()
        rthread.join()
        assert not render_errors, f"torn renders: {render_errors[:3]}"
        # exact totals: no lost update under any interleaving
        for t in range(n_threads):
            assert c.value(thread=t) == n_iter          # child incs
            assert h.summary(thread=t)["count"] == n_iter
            per_phase = sum(c.value(thread=t, phase=str(p))
                            for p in range(5))
            assert per_phase == n_iter                  # labeled incs
        total = sum(c.value(**dict(k)) for k in c.labels_seen())
        assert total == 2 * n_threads * n_iter

    def test_render_cell_snapshot_is_lock_protected(self):
        """Deterministic pin of the torn-exposition fix: every field of
        the render snapshot must be read UNDER the metric lock. The pure
        race is a 2-bytecode window the GIL makes essentially
        unobservable in a stress test, so probe the locking discipline
        directly: a proxy cell records whether the lock was held at
        each field access."""
        from paddle_tpu.observability.registry import _label_key

        reg = obs.MetricsRegistry()
        h = reg.histogram("lk_seconds")
        h.observe(0.5)

        lock = h._lock
        real = h._series[_label_key({})]

        class ProbeCell:
            reads = []

            @property
            def counts(self):
                self.reads.append(lock.locked())
                return real.counts

            @property
            def count(self):
                self.reads.append(lock.locked())
                return real.count

            @property
            def sum(self):
                self.reads.append(lock.locked())
                return real.sum

        h._series[_label_key({})] = ProbeCell()
        counts, count, total = h._render_cell({})
        assert sum(counts) == count == 1 and total == 0.5
        assert ProbeCell.reads and all(ProbeCell.reads), \
            f"cell fields read outside the metric lock: {ProbeCell.reads}"

    def test_child_api_equivalence(self):
        reg = obs.MetricsRegistry()
        c = reg.counter("child_total")
        c.child(route="/a").inc(3)
        c.inc(2, route="/a")
        assert c.value(route="/a") == 5
        g = reg.gauge("child_gauge")
        gc_ = g.child()
        gc_.set(7)
        gc_.inc(1)
        assert g.value() == 8
        h = reg.histogram("child_seconds")
        h.child(op="x").observe(0.5)
        assert h.summary(op="x")["count"] == 1
        with pytest.raises(ValueError):
            c.child().inc(-1)
