"""Concurrency analysis tier (ISSUE 18): the @guarded_by lock-discipline
lint, the static lock-order graph + committed-manifest drift gate, the
runtime lock sanitizer, the conformance lints (ReplicaHandle interface,
Reject.reason vocabulary), and regression tests for the races the tier
found in the existing serving plane. Every rule gets a fire/clean-twin
pair; the threaded e2e proves observed ⊆ the committed static graph on
a real stepping fleet under sanitize()."""

import os
import threading
import time

import numpy as np
import pytest

from paddle_tpu import observability as obs
from paddle_tpu import serving
from paddle_tpu.analysis import concurrency as conc
from paddle_tpu.analysis import conformance
from paddle_tpu.analysis.findings import RULES
from paddle_tpu.serving import fleet
from paddle_tpu.serving.scheduler import REJECT_REASONS, Reject

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LOCK_ORDER = os.path.join(REPO, "tools", "lock_order.json")

VOCAB = 64


@pytest.fixture(scope="module")
def model_params():
    from serving_taps import tiny_gpt
    return tiny_gpt()


def _engine(model_params, **kw):
    model, params = model_params
    kw.setdefault("num_slots", 4)
    kw.setdefault("page_size", 4)
    kw.setdefault("max_tokens_per_slot", 32)
    kw.setdefault("prefill_chunk", 4)
    return serving.ServingEngine(model, params, attn_impl="lax",
                                 registry=obs.MetricsRegistry(), **kw)


def _rules(findings):
    return sorted({f.rule for f in findings})


# ---------------------------------------------------------------------------
# the annotation convention


class TestGuardedBy:
    def test_decorator_merges_and_stacks(self):
        @conc.guarded_by("_cv", "_a", "_b")
        @conc.guarded_by("_vlock", "_c")
        class C:
            pass

        assert C.__guarded_by__ == {"_a": "_cv", "_b": "_cv",
                                    "_c": "_vlock"}

    def test_subclass_gets_a_copy(self):
        @conc.guarded_by("_lk", "_x")
        class Base:
            pass

        @conc.guarded_by("_lk2", "_y")
        class Sub(Base):
            pass

        assert Base.__guarded_by__ == {"_x": "_lk"}
        assert Sub.__guarded_by__ == {"_x": "_lk", "_y": "_lk2"}

    def test_annotated_production_classes(self):
        """The contract the lint enforces is declared on the real
        serving-plane classes — a refactor that drops an annotation
        silently un-guards the field."""
        from paddle_tpu.observability.registry import MetricsRegistry
        from paddle_tpu.resilience.snapshot import SnapshotEngine
        from paddle_tpu.embedding_serving.streaming import \
            StreamingUpdateChannel
        from paddle_tpu.serving.engine import ServingEngine
        from paddle_tpu.serving.fleet.net.frontdoor import FrontDoor

        assert MetricsRegistry.__guarded_by__["_metrics"] == "_lock"
        assert ServingEngine.__guarded_by__["_health_snap"] == \
            "_health_lock"
        assert fleet.LocalReplica.__guarded_by__["engine"] == "_lock"
        assert fleet.FleetRouter.__guarded_by__["_postmortems"] == \
            "_view_lock"
        assert SnapshotEngine.__guarded_by__["_error"] == "_err_lock"
        assert StreamingUpdateChannel.__guarded_by__ == {
            "_pending": "_cv", "_oldest_pending_ts": "_cv",
            "_error": "_cv", "_versions": "_vlock", "_dirty": "_vlock"}
        assert FrontDoor.__guarded_by__ == {"_netlog": "_netlog_lock",
                                            "_frame": "_netlog_lock"}

    def test_rules_registered(self):
        for rule in ("unguarded-access", "lock-order-cycle",
                     "double-acquire", "lock-order-drift",
                     "sanitizer-violation", "interface-drift",
                     "reject-vocab-drift"):
            sev, _desc = RULES[rule]
            assert sev == "error"


# ---------------------------------------------------------------------------
# (a) lock-discipline lint: fire / clean-twin pairs


_DISCIPLINE_HDR = """
import threading
from paddle_tpu.analysis.concurrency import guarded_by

@guarded_by("_lk", "_x")
class C:
    def __init__(self):
        self._lk = threading.Lock()
        self._x = 0
"""


class TestLockDiscipline:
    def test_unguarded_read_fires(self):
        src = _DISCIPLINE_HDR + """
    def peek(self):
        return self._x
"""
        out = conc.lint_locks(src, filename="t.py")
        assert _rules(out) == ["unguarded-access"]
        assert "C.peek reads self._x" in out[0].message

    def test_guarded_read_clean_twin(self):
        src = _DISCIPLINE_HDR + """
    def peek(self):
        with self._lk:
            return self._x
"""
        assert conc.lint_locks(src, filename="t.py") == []

    def test_unguarded_write_via_helper_fires(self):
        # the helper writes unguarded; ONE of its two intra-class call
        # sites does not hold the lock, so propagation cannot excuse it
        src = _DISCIPLINE_HDR + """
    def _bump(self):
        self._x += 1
    def locked_path(self):
        with self._lk:
            self._bump()
    def sneak(self):
        self._bump()
"""
        out = conc.lint_locks(src, filename="t.py")
        assert _rules(out) == ["unguarded-access"]
        assert any("C.sneak" in f.message for f in out)

    def test_helper_clean_when_all_callers_hold(self):
        src = _DISCIPLINE_HDR + """
    def _bump(self):
        self._x += 1
    def a(self):
        with self._lk:
            self._bump()
    def b(self):
        with self._lk:
            self._bump()
"""
        assert conc.lint_locks(src, filename="t.py") == []

    def test_public_method_never_excused_by_callers(self):
        # public methods are reachable from outside the class, where no
        # caller can be assumed to hold an internal lock
        src = _DISCIPLINE_HDR + """
    def bump(self):
        self._x += 1
    def locked_path(self):
        with self._lk:
            self.bump()
"""
        out = conc.lint_locks(src, filename="t.py")
        assert _rules(out) == ["unguarded-access"]

    def test_init_exempt(self):
        assert conc.lint_locks(_DISCIPLINE_HDR, filename="t.py") == []

    def test_with_inside_except_handler_counts(self):
        # regression: ExceptHandler bodies are not ast.stmt nodes; an
        # earlier walker dropped their `with` scopes and flagged the
        # guarded write inside the handler
        src = _DISCIPLINE_HDR + """
    def ok(self):
        try:
            pass
        except Exception as e:
            with self._lk:
                self._x = 1
    def bad(self):
        try:
            pass
        except Exception as e:
            self._x = 1
"""
        out = conc.lint_locks(src, filename="t.py")
        assert len(out) == 1 and "C.bad" in out[0].message

    def test_nested_def_runs_with_empty_held_set(self):
        # a closure outlives the `with` it was defined in — another
        # thread may run it with no lock held
        src = _DISCIPLINE_HDR + """
    def spawn(self):
        with self._lk:
            def worker():
                return self._x
            return worker
"""
        out = conc.lint_locks(src, filename="t.py")
        assert _rules(out) == ["unguarded-access"]


# ---------------------------------------------------------------------------
# (b) lock-order graph: fire / clean-twin pairs + the committed manifest


_CYCLE_SRC = """
import threading

class A:
    def __init__(self):
        self._a = threading.Lock()
        self._b = threading.Lock()
    def fwd(self):
        with self._a:
            with self._b:
                pass
    def rev(self):
        with self._b:
            with self._a:
                pass
"""

_ACYCLIC_SRC = _CYCLE_SRC.replace("""    def rev(self):
        with self._b:
            with self._a:
                pass
""", "")


class TestLockOrderGraph:
    def test_cycle_fires(self):
        g = conc.extract_lock_graph({"a.py": _CYCLE_SRC})
        assert not g.acyclic()
        assert "lock-order-cycle" in _rules(g.findings())

    def test_acyclic_clean_twin(self):
        g = conc.extract_lock_graph({"a.py": _ACYCLIC_SRC})
        assert g.acyclic() and g.findings() == []
        assert ("A._a", "A._b") in g.edges

    def test_double_acquire_via_helper_fires(self):
        src = """
import threading

class D:
    def __init__(self):
        self._m = threading.Lock()
    def _inner(self):
        with self._m:
            pass
    def outer(self):
        with self._m:
            self._inner()
"""
        g = conc.extract_lock_graph({"d.py": src})
        assert g.double_acquires
        assert "double-acquire" in _rules(g.findings())

    def test_rlock_reacquire_clean_twin(self):
        src = """
import threading

class D:
    def __init__(self):
        self._m = threading.RLock()
    def _inner(self):
        with self._m:
            pass
    def outer(self):
        with self._m:
            self._inner()
"""
        g = conc.extract_lock_graph({"d.py": src})
        assert g.findings() == []

    def test_manifest_roundtrip_clean(self):
        g = conc.extract_lock_graph({"a.py": _ACYCLIC_SRC})
        m = conc.lock_order_manifest(g)
        assert conc.lock_order_diff(g, m) == []

    def test_missing_manifest_fires(self):
        g = conc.extract_lock_graph({"a.py": _ACYCLIC_SRC})
        out = conc.lock_order_diff(g, None)
        assert _rules(out) == ["lock-order-drift"]

    def test_new_edge_and_stale_lock_fire(self):
        g = conc.extract_lock_graph({"a.py": _ACYCLIC_SRC})
        m = conc.lock_order_manifest(g)
        m["edges"] = []                          # edge missing -> new
        m["locks"]["Ghost._lock"] = "lock"       # lock gone -> stale
        out = conc.lock_order_diff(g, m)
        msgs = " | ".join(f.message for f in out)
        assert _rules(out) == ["lock-order-drift"]
        assert "new acquisition edge" in msgs
        assert "stale manifest lock Ghost._lock" in msgs


class TestCommittedLockOrder:
    """The committed tools/lock_order.json must stay fresh, acyclic, and
    in sync with the package — the hermetic version of the CI gate."""

    def test_manifest_is_fresh(self):
        g = conc.extract_lock_graph(conc.package_sources())
        out = conc.lock_order_diff(g, conc.load_lock_order(LOCK_ORDER),
                                   path=LOCK_ORDER)
        assert out == [], "\n".join(f.message for f in out)

    def test_graph_acyclic_no_double_acquires(self):
        g = conc.extract_lock_graph(conc.package_sources())
        assert g.acyclic() and not g.double_acquires

    def test_known_cross_class_edge_extracted(self):
        # LocalReplica.step holds _lock while engine.step refreshes the
        # health snapshot under _health_lock — the one real nested
        # acquisition in the serving plane, resolved through the
        # annotated `engine: "ServingEngine"` attribute type
        g = conc.extract_lock_graph(conc.package_sources())
        assert ("LocalReplica._lock", "ServingEngine._health_lock") \
            in g.edges

    def test_package_lint_findings_all_triaged(self):
        """Every remaining finding on the real package is one of the
        five documented LocalReplica suppressions — anything else is an
        untriaged regression (run tools/graph_lint.py --concurrency)."""
        rep = conc.lint_concurrency(registry=False)
        benign = ("LocalReplica.health", "LocalReplica.page_size",
                  "LocalReplica.can_accept", "LocalReplica.postmortem")
        for f in rep.findings:
            assert f.rule == "unguarded-access" and \
                any(b in f.message for b in benign), f.message


# ---------------------------------------------------------------------------
# conformance lints (satellites 2 + 3)


class TestConformance:
    def test_interfaces_clean(self):
        assert conformance.lint_interfaces() == []

    def test_dispatch_ops_extraction(self):
        src = """
class S:
    def _dispatch(self, op, msg):
        if op == "hello":
            return {"name": self.name, "page_size": 4}
        if op == "submit":
            return 1
        if "health" == op:
            return {}
"""
        ops, hello_keys = conformance._dispatch_ops(src, "s.py")
        assert ops == {"hello", "submit", "health"}
        assert hello_keys == {"name", "page_size"}

    def test_sig_shape_detects_drift(self):
        import inspect

        def proto(self, rid, *, wait=False):
            pass

        def renamed(self, req_id, *, wait=False):
            pass

        def compatible(self, rid, *, wait=True):
            pass    # default VALUE may differ, shape may not

        shape = conformance._sig_shape
        assert shape(inspect.signature(proto)) != \
            shape(inspect.signature(renamed))
        assert shape(inspect.signature(proto)) == \
            shape(inspect.signature(compatible))

    def test_reject_vocab_clean(self):
        assert conformance.lint_reject_vocab() == []

    def test_unregistered_reason_fires(self, tmp_path):
        mod = tmp_path / "shed.py"
        mod.write_text(
            "from paddle_tpu.serving.scheduler import Reject\n"
            "def f(n):\n"
            "    return Reject('queue_full', 'default', n, 0.0, 0.1) "
            "if n else Reject('made_up', 'default', n, 0.0, 0.1)\n")
        out = conformance.lint_reject_vocab(str(tmp_path))
        fired = [f for f in out if "made_up" in f.message]
        assert fired and fired[0].rule == "reject-vocab-drift"
        assert not any("'queue_full'" in f.message and
                       "not registered" in f.message for f in out)

    def test_dead_vocab_fires(self, tmp_path):
        # a tree constructing no rejects leaves every registered reason
        # dead — drift in the other direction
        (tmp_path / "empty.py").write_text("x = 1\n")
        out = conformance.lint_reject_vocab(str(tmp_path))
        dead = {f.message.split("'")[1] for f in out
                if "constructed nowhere" in f.message}
        assert dead == set(REJECT_REASONS)

    def test_wire_rejects_unknown_reason(self):
        from paddle_tpu.serving.fleet.net import wire

        d = wire.reject_to_wire(
            Reject("queue_full", "default", 3, 0.0, 0.1))
        assert wire.reject_from_wire(dict(d)).reason == "queue_full"
        d["reason"] = "not_a_reason"
        with pytest.raises(wire.WireError, match="unknown Reject"):
            wire.reject_from_wire(d)

    def test_reasons_registry_shape(self):
        assert len(set(REJECT_REASONS)) == len(REJECT_REASONS)
        assert "queue_full" in REJECT_REASONS
        assert "slow_reader" in REJECT_REASONS


# ---------------------------------------------------------------------------
# (c) runtime lock sanitizer


class TestSanitizer:
    def test_double_acquire_raises_instead_of_deadlocking(self):
        with conc.sanitize(register_metrics=False) as mon:
            lk = threading.Lock()
            lk.acquire()
            with pytest.raises(conc.DoubleAcquireError):
                lk.acquire()
            lk.release()
        assert mon.double_acquires

    def test_rlock_reentry_clean_twin(self):
        with conc.sanitize(register_metrics=False) as mon:
            lk = threading.RLock()
            with lk:
                with lk:
                    pass
        assert not mon.double_acquires

    def test_locks_outside_context_untouched(self):
        before = threading.Lock()
        with conc.sanitize(register_metrics=False):
            inside = threading.Lock()
        after = threading.Lock()
        assert isinstance(inside, conc._SanitizedLock)
        assert not isinstance(before, conc._SanitizedLock)
        assert not isinstance(after, conc._SanitizedLock)

    def test_observes_the_real_nested_edge(self, model_params):
        # an idle engine step still publishes health: LocalReplica.step
        # acquires _lock, engine._refresh_health acquires _health_lock
        # inside it — the sanitizer must name both and record the edge
        with conc.sanitize(register_metrics=False) as mon:
            rep = fleet.LocalReplica(_engine(model_params), name="san0")
            rep.step()
        edge = ("LocalReplica._lock", "ServingEngine._health_lock")
        assert edge in mon.observed_edges()
        assert mon.acquisitions > 0

    def test_check_clean_against_committed_manifest(self, model_params):
        with conc.sanitize(register_metrics=False) as mon:
            rep = fleet.LocalReplica(_engine(model_params), name="san1")
            rep.step()
        assert mon.check(conc.load_lock_order(LOCK_ORDER)) == []

    def test_check_fires_on_unblessed_order(self, model_params):
        # same observation, checked against a manifest that ORDERS both
        # locks the other way round: the observed edge is an inversion
        with conc.sanitize(register_metrics=False) as mon:
            rep = fleet.LocalReplica(_engine(model_params), name="san2")
            rep.step()
        reversed_manifest = {"edges": [
            ["ServingEngine._health_lock", "LocalReplica._lock", "x"]]}
        out = mon.check(reversed_manifest)
        assert _rules(out) == ["sanitizer-violation"]
        assert "LocalReplica._lock -> ServingEngine._health_lock" \
            in out[0].message

    def test_check_ignores_unmodeled_leaf_locks(self, model_params):
        # locks the committed graph never orders (flight recorder,
        # metrics) are out of scope — only inversions among MODELED
        # locks can fire, so runtime-only leaf edges don't false-alarm
        with conc.sanitize(register_metrics=False) as mon:
            rep = fleet.LocalReplica(_engine(model_params), name="san3")
            rep.step()
        observed = mon.observed_edges()
        assert len(observed) > 1, "expected runtime-only leaf edges"
        assert mon.check(conc.load_lock_order(LOCK_ORDER)) == []

    def test_export_metrics(self, model_params):
        reg = obs.MetricsRegistry()
        with conc.sanitize(register_metrics=False) as mon:
            rep = fleet.LocalReplica(_engine(model_params), name="san4")
            rep.step()
        mon.export_metrics(reg)
        text = reg.render_prometheus()
        assert "concurrency_lock_acquisitions_total" in text
        assert "concurrency_lock_order_edges_total" in text

    def test_export_metrics_into_sanitized_registry(self):
        # regression: a registry built INSIDE the context guards itself
        # with a _SanitizedLock whose acquire re-enters the monitor —
        # export_metrics must not hold _mu across reg.counter() or the
        # exporting thread self-deadlocks (found driving the fleet
        # e2e: mon.export_metrics(fleet_registry) hung forever)
        with conc.sanitize(register_metrics=False) as mon:
            reg = obs.MetricsRegistry()
            with threading.Lock():
                pass
        done = []
        t = threading.Thread(
            target=lambda: done.append(mon.export_metrics(reg)),
            daemon=True)
        t.start()
        t.join(10)
        assert done, "export_metrics deadlocked on a sanitized registry"
        assert "concurrency_lock_acquisitions_total" \
            in reg.render_prometheus()


class TestThreadedE2E:
    def test_observed_subset_of_committed_graph(self, model_params):
        """The ISSUE's acceptance e2e: a stepping replica behind a
        router with a concurrent health-scraping reader, all built and
        run under sanitize() — every observed acquisition order among
        statically modeled locks must be blessed by the committed
        tools/lock_order.json."""
        committed = conc.load_lock_order(LOCK_ORDER)
        with conc.sanitize(register_metrics=False) as mon:
            rep = fleet.LocalReplica(_engine(model_params), name="e0")
            rep.warmup()
            router = fleet.FleetRouter(
                [rep], registry=obs.MetricsRegistry(),
                tracer=obs.Tracer(enabled=False))
            rep.start()
            stop = threading.Event()
            scrapes = []

            def scraper():
                while not stop.is_set():
                    h = router.health()
                    scrapes.append(h["requests_in_flight"])
                    router.postmortems()
                    time.sleep(0.001)

            reader = threading.Thread(target=scraper, daemon=True)
            reader.start()
            try:
                rng = np.random.default_rng(18)
                frids = [router.submit(
                    rng.integers(1, VOCAB, 6).astype(np.int32), 4)
                    for _ in range(6)]
                assert len(frids) == 6
                deadline = time.monotonic() + 120.0
                while not rep.idle():
                    assert time.monotonic() < deadline, "fleet stuck"
                    time.sleep(0.005)
            finally:
                stop.set()
                reader.join(timeout=10)
                rep.stop()
        violations = mon.check(committed)
        assert violations == [], "\n".join(f.message for f in violations)
        # non-vacuous: the committed edge really happened at runtime
        assert ("LocalReplica._lock", "ServingEngine._health_lock") \
            in mon.observed_edges()
        assert scrapes, "scraper never ran"


# ---------------------------------------------------------------------------
# regression tests for the races the tier found (satellite 1)


class TestRaceFixes:
    def test_snapshot_error_handoff_is_locked_and_one_shot(self, tmp_path):
        from paddle_tpu.resilience.snapshot import SnapshotEngine

        eng = SnapshotEngine.__new__(SnapshotEngine)
        eng._err_lock = threading.Lock()
        eng._error = RuntimeError("worker died")
        with pytest.raises(RuntimeError, match="worker died"):
            eng._raise_pending()
        eng._raise_pending()        # cleared exactly once, no re-raise

    def test_streaming_worker_failure_surfaces_under_cv(self):
        from paddle_tpu.embedding_serving.streaming import \
            StreamingUpdateChannel

        class BoomStore:
            dim = 4

            def set_rows(self, ids, vals):
                raise RuntimeError("store exploded")

        ch = StreamingUpdateChannel(BoomStore(), registry=obs
                                    .MetricsRegistry(),
                                    tracer=obs.Tracer(enabled=False))
        ch.push_rows(np.array([1]), np.ones((1, 4), np.float32))
        deadline = time.monotonic() + 30.0
        while ch.lag_updates() and time.monotonic() < deadline:
            time.sleep(0.01)
        with pytest.raises(RuntimeError, match="worker failed"):
            ch._raise_if_failed()
        ch._raise_if_failed()       # one-shot: cleared under _cv
        ch._stop.set()

    def test_netlog_lines_atomic_under_concurrent_writers(self, tmp_path):
        """The _netlog_lock regression: interleaved _log calls from
        multiple threads must still produce valid JSONL with strictly
        monotonic frame ids (the validator rejects torn interior lines
        and duplicate frames)."""
        from paddle_tpu.serving.fleet.net import frontdoor

        path = str(tmp_path / "netlog.jsonl")
        fd = frontdoor.FrontDoor(None, netlog_path=path,
                                 registry=obs.MetricsRegistry())
        try:
            def writer(i):
                for j in range(50):
                    fd._log("accept", rid=i * 1000 + j, conn=i)
                    fd._log("finished", rid=i * 1000 + j, conn=i)

            threads = [threading.Thread(target=writer, args=(i,))
                       for i in range(8)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
        finally:
            fd.close()
        counts = frontdoor.validate_netlog_file(path,
                                                require_requests=400)
        assert counts["accept"] == 400
        assert counts["finished"] == 400

    def test_router_health_during_membership_churn(self, model_params):
        """health() snapshots the replica list: scraping while replicas
        are added must never blow up mid-iteration."""
        rep = fleet.LocalReplica(_engine(model_params), name="m0")
        router = fleet.FleetRouter([rep],
                                   registry=obs.MetricsRegistry(),
                                   tracer=obs.Tracer(enabled=False))
        stop = threading.Event()
        errors = []

        def scraper():
            while not stop.is_set():
                try:
                    router.health()
                except Exception as e:   # pragma: no cover - the bug
                    errors.append(e)
                    return

        t = threading.Thread(target=scraper, daemon=True)
        t.start()
        try:
            for i in range(8):
                router.add_replica(fleet.LocalReplica(
                    _engine(model_params), name=f"m{i + 1}"))
                time.sleep(0.002)
        finally:
            stop.set()
            t.join(timeout=10)
        assert not errors
        assert router.health()["replicas"] == 9
