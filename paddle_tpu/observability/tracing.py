"""Request-lifecycle tracing: low-overhead spans in a bounded ring.

The aggregate registry (PR 1) answers *that* requests waited; spans
answer *where* — one trace per request, one span per phase (queue,
prefill chunk, decode block, KV pull, snapshot write), with events for
the point decisions in between (admitted, sched_skip, cow_copy, shed).
The design follows the serving discipline everywhere else in the repo:

- **Bounded memory.** Completed spans land in a ring buffer
  (``deque(maxlen=capacity)``); a month-long serving process keeps the
  most recent window, never an unbounded history.
- **Zero cost when disabled.** ``span()`` returns a process-wide no-op
  singleton (no allocation, no clock read); hot paths additionally
  guard their span fan-out behind the ``enabled`` flag so disabled
  tracing is one attribute read per step. Nothing here touches jitted
  code — all instrumentation is host-side around the fixed-shape calls,
  preserving the zero-steady-state-recompile invariant.
- **Thread-correct parentage.** The current-span stack is thread-local,
  so nested spans from the engine thread and background threads (the
  snapshot writer, the streaming applier) attribute to their own
  stacks; explicit ``parent=`` crosses threads when the caller *wants*
  a background span under a foreground one.

**Phases** (:meth:`Tracer.phase`) are the one context the serving
engine opens at every layer boundary. A phase is always a
``jax.profiler.TraceAnnotation`` of the same name — so whenever a
profiler session is running, whoever started it, the span lands in the
profiler's trace on the device's clock — plus one clock-read pair that
feeds the always-on work counters, and a ring span with thread-local
parentage when the tracer is enabled. Disabled and unprofiled it
allocates no ``Span`` and records nothing.

Two exporters share the buffer: crash-safe JSONL (one span per line,
flushed per record — the runlog discipline, validated by
:func:`validate_trace_log` / ``tools/check_metrics_log.py --trace``)
and Chrome trace-event JSON (:func:`chrome_trace` /
:meth:`Tracer.export_chrome`) loadable in Perfetto, with span events as
instant markers. ``profiler.record_event`` regions fold into the same
timeline automatically whenever the default tracer is enabled.
"""

from __future__ import annotations

import itertools
import json
import os
import threading
import time
from collections import deque
from typing import Any, Dict, Iterable, List, Optional

from jax.profiler import TraceAnnotation as _Annotation

TRACE_SCHEMA_VERSION = 1

# span/trace id 0 is reserved for "none" (the no-op span advertises it)
_NO_ID = 0


class Span:
    """One timed region. Also its own context manager: ``with
    tracer.span("x"):`` pushes/pops the thread-local stack; manual spans
    (``start_span`` … ``finish``) skip the stack for cross-step or
    cross-thread lifecycles (a serving request lives across many
    ``step()`` calls)."""

    __slots__ = ("tracer", "trace_id", "span_id", "parent_id", "name",
                 "start", "end", "attrs", "events", "thread", "status",
                 "_on_stack")

    def __init__(self, tracer: "Tracer", trace_id: int, span_id: int,
                 parent_id: int, name: str, start: float,
                 attrs: Optional[Dict[str, Any]]):
        self.tracer = tracer
        self.trace_id = trace_id
        self.span_id = span_id
        self.parent_id = parent_id
        self.name = name
        self.start = start
        self.end: Optional[float] = None
        self.attrs = attrs or {}
        self.events: List[tuple] = []      # (t, name, attrs)
        self.thread = threading.current_thread().name
        self.status = "ok"
        self._on_stack = False

    @property
    def duration_s(self) -> float:
        return (self.end if self.end is not None
                else self.tracer.now()) - self.start

    def set_attrs(self, **attrs) -> "Span":
        self.attrs.update(attrs)
        return self

    def add_event(self, name: str, **attrs) -> "Span":
        """Point annotation inside the span (scheduler decisions, CoW
        copies, SLO alerts); exported as Chrome instant events."""
        self.events.append((self.tracer.now(), name, attrs))
        return self

    def finish(self, status: Optional[str] = None,
               end: Optional[float] = None):
        """Complete the span and move it into the ring buffer. Safe to
        call once; a second call is ignored (exception paths)."""
        if self.end is not None:
            return
        self.end = self.tracer.now() if end is None else end
        if status is not None:
            self.status = status
        self.tracer._record(self)

    # -- context-manager protocol (stack-tracked spans) -------------------
    def __enter__(self) -> "Span":
        self.tracer._push(self)
        self._on_stack = True
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        if self._on_stack:
            self.tracer._pop(self)
            self._on_stack = False
        self.finish(status="error" if exc_type is not None else None)
        return False

    def to_record(self) -> Dict[str, Any]:
        """JSONL record (schema checked by :func:`validate_trace_record`)."""
        tr = self.tracer
        rec: Dict[str, Any] = {
            "kind": "span",
            "trace_id": self.trace_id,
            "span_id": self.span_id,
            "parent_id": self.parent_id,
            "name": self.name,
            "ts": tr.to_wall(self.start),
            "dur_s": round(self.duration_s, 9),
            "thread": self.thread,
            "status": self.status,
        }
        if self.attrs:
            rec["attrs"] = _jsonable_dict(self.attrs)
        if self.events:
            rec["events"] = [
                {"ts": tr.to_wall(t), "name": n,
                 **({"attrs": _jsonable_dict(a)} if a else {})}
                for t, n, a in self.events]
        return rec


class Phase:
    """One layer boundary, entered once: a profiler annotation (inert
    unless a profiler session runs), the clock-read pair ``start`` /
    ``end`` on the tracer's clock, an optional bound counter child that
    is credited the seconds in between, and — tracer enabled — a ring
    span parented from the thread-local stack (``span``, else None).
    ``stamp`` writes ``t_mono_ns`` (the tracer clock at entry) into the
    annotation, so a reader joins ring spans and anatomy records to the
    device timeline by id, with no clock arithmetic."""

    __slots__ = ("_tracer", "_ann", "_counter", "span", "start", "end")

    def __init__(self, tracer: "Tracer", name: str, counter, stamp: bool,
                 attrs: Dict[str, Any]):
        self._tracer = tracer
        self._counter = counter
        self.end: Optional[float] = None
        self.start = tracer.now()
        self.span = (tracer._make(name, None, attrs, start=self.start)
                     if tracer.enabled else None)
        if stamp:       # the ring span holds ``start`` itself
            attrs = dict(attrs, t_mono_ns=int(self.start * 1e9))
        self._ann = _Annotation(name, **attrs)

    @property
    def span_id(self) -> int:
        return self.span.span_id if self.span is not None else _NO_ID

    def __enter__(self) -> "Phase":
        self._ann.__enter__()
        if self.span is not None:
            self._tracer._push(self.span)
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        self.end = self._tracer.now()
        if self._counter is not None:
            self._counter.inc(self.end - self.start)
        sp = self.span
        if sp is not None:
            self._tracer._pop(sp)
            sp.finish(status="error" if exc_type is not None else None,
                      end=self.end)
        self._ann.__exit__(exc_type, exc, tb)
        return False


class _NoopSpan:
    """The disabled-mode span: a single shared instance whose every
    method is a no-op — ``tracer.span()`` while disabled allocates
    nothing (identity-tested in tests/test_tracing.py)."""

    __slots__ = ()
    trace_id = _NO_ID
    span_id = _NO_ID
    parent_id = _NO_ID
    name = ""
    status = "noop"
    events: List[tuple] = []
    attrs: Dict[str, Any] = {}
    duration_s = 0.0

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set_attrs(self, **attrs):
        return self

    def add_event(self, name, **attrs):
        return self

    def finish(self, status=None, end=None):
        pass


NOOP_SPAN = _NoopSpan()


class _Stack(threading.local):
    def __init__(self):
        self.spans: List[Span] = []


class Tracer:
    """Span factory + bounded ring buffer + exporters.

    The clock is ``time.monotonic`` (matching the engine's step timers);
    :meth:`to_wall` maps it onto unix time via an anchor taken at
    construction so exported records carry real timestamps.
    """

    now = staticmethod(time.monotonic)

    def __init__(self, capacity: int = 4096, enabled: bool = True):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.capacity = capacity
        self.enabled = bool(enabled)
        self._buf: "deque[Span]" = deque(maxlen=capacity)
        self._lock = threading.Lock()
        self._stack = _Stack()
        self._span_ids = itertools.count(1)
        self._trace_ids = itertools.count(1)
        self._mono0 = time.monotonic()
        self._wall0 = time.time()
        self.dropped = 0            # spans evicted by the ring bound

    # -- lifecycle --------------------------------------------------------
    def enable(self, capacity: Optional[int] = None):
        if capacity is not None and capacity != self.capacity:
            if capacity < 1:
                raise ValueError(f"capacity must be >= 1, got {capacity}")
            with self._lock:
                self.capacity = capacity
                evicted = max(len(self._buf) - capacity, 0)
                self.dropped += evicted     # shrinking evicts oldest
                self._buf = deque(self._buf, maxlen=capacity)
        self.enabled = True
        return self

    def disable(self):
        self.enabled = False
        return self

    def clear(self):
        with self._lock:
            self._buf.clear()
            self.dropped = 0

    def to_wall(self, t: float) -> float:
        return self._wall0 + (t - self._mono0)

    # -- span creation ----------------------------------------------------
    def span(self, name: str, parent: Optional[Span] = None, **attrs):
        """Stack-tracked span context manager. Disabled → the shared
        no-op (zero allocation). Parent defaults to this thread's
        current span; a root span starts a new trace."""
        if not self.enabled:
            return NOOP_SPAN
        return self._make(name, parent, attrs)

    def phase(self, name: str, counter=None, stamp: bool = False,
              **attrs) -> Phase:
        """The context for one layer boundary (see :class:`Phase`):
        ``with tracer.phase("serving.decode.sync", counter=child):``.
        Works the same enabled or disabled — only the ring span is
        conditional."""
        return Phase(self, name, counter, stamp, attrs)

    def start_span(self, name: str, parent: Optional[Span] = None,
                   trace_id: Optional[int] = None, **attrs):
        """Manual span — NOT pushed on the thread stack; the caller owns
        its lifetime and must ``finish()`` it (request-lifecycle roots
        that live across many engine steps, cross-thread children).
        ``trace_id`` adopts an externally minted trace id (a fleet
        router's, a remote caller's) instead of starting a fresh trace —
        the propagation hook that lets one timeline cross process
        boundaries where no parent ``Span`` object can travel."""
        if not self.enabled:
            return NOOP_SPAN
        return self._make(name, parent, attrs, trace_id=trace_id)

    def record_span(self, name: str, start: Optional[float] = None,
                    end: Optional[float] = None,
                    duration_s: Optional[float] = None,
                    parent: Optional[Span] = None,
                    status: Optional[str] = None,
                    trace_id: Optional[int] = None,
                    **attrs) -> Optional[Span]:
        """Record an already-measured interval as a completed span (the
        engine times its jitted calls anyway; this turns those stamps
        into timeline entries without a second clock read). Give either
        ``start``/``end`` in this tracer's clock, or ``duration_s``
        (ends now)."""
        if not self.enabled:
            return None
        if end is None:
            end = self.now()
        if start is None:
            start = end - (duration_s or 0.0)
        sp = self._make(name, parent, attrs, start=start,
                        trace_id=trace_id)
        sp.finish(status=status, end=end)
        return sp

    def _make(self, name, parent, attrs, start=None,
              trace_id=None) -> Span:
        if parent is None:
            st = self._stack.spans
            parent = st[-1] if st else None
        if parent is None or parent.span_id == _NO_ID:
            if trace_id is None:
                trace_id = next(self._trace_ids)
            parent_id = _NO_ID
        else:
            trace_id = parent.trace_id
            parent_id = parent.span_id
        return Span(self, trace_id, next(self._span_ids), parent_id,
                    name, self.now() if start is None else start, attrs)

    def current(self) -> Optional[Span]:
        st = self._stack.spans
        return st[-1] if st else None

    def _push(self, span: Span):
        self._stack.spans.append(span)

    def _pop(self, span: Span):
        st = self._stack.spans
        if st and st[-1] is span:
            st.pop()
        elif span in st:            # exception-skewed exit order
            st.remove(span)

    def _record(self, span: Span):
        with self._lock:
            if len(self._buf) == self._buf.maxlen:
                self.dropped += 1
            self._buf.append(span)

    # -- views ------------------------------------------------------------
    def spans(self, name: Optional[str] = None,
              trace_id: Optional[int] = None,
              limit: Optional[int] = None) -> List[Span]:
        """Snapshot of the ring (oldest → newest), optionally filtered."""
        with self._lock:
            out = list(self._buf)
        if name is not None:
            out = [s for s in out if s.name == name]
        if trace_id is not None:
            out = [s for s in out if s.trace_id == trace_id]
        if limit is not None:
            out = out[-limit:] if limit > 0 else []
        return out

    def summary(self) -> Dict[str, Dict[str, float]]:
        """Per-name span counts + total seconds (the report() table)."""
        agg: Dict[str, Dict[str, float]] = {}
        for s in self.spans():
            a = agg.setdefault(s.name, {"count": 0, "total_s": 0.0})
            a["count"] += 1
            a["total_s"] += s.duration_s
        return agg

    # -- exporters --------------------------------------------------------
    def export_jsonl(self, path: str) -> int:
        """Append every buffered span to a JSONL file, one flushed line
        per span (crash loses at most the partial final line — same
        contract as the metrics run log). Returns spans written."""
        d = os.path.dirname(os.path.abspath(path))
        os.makedirs(d, exist_ok=True)
        spans = self.spans()
        with open(path, "a", encoding="utf-8") as f:
            f.write(json.dumps(
                {"kind": "trace_meta",
                 "schema_version": TRACE_SCHEMA_VERSION,
                 "ts": time.time(), "capacity": self.capacity,
                 "dropped": self.dropped}) + "\n")
            f.flush()
            for s in spans:
                f.write(json.dumps(s.to_record(), sort_keys=True,
                                   default=str) + "\n")
                f.flush()
        return len(spans)

    def to_chrome(self) -> Dict[str, Any]:
        """Chrome trace-event JSON (Perfetto / chrome://tracing): spans
        as complete ("X") events, span events as instant ("i") markers,
        pid/tid from the recording process/thread. The ``args`` carry
        trace/span ids so one request's lifecycle is clickable."""
        out = records_to_chrome(s.to_record() for s in self.spans())
        out["otherData"] = {"tracer_capacity": self.capacity,
                            "dropped": self.dropped}
        return out

    def export_chrome(self, path: str) -> str:
        d = os.path.dirname(os.path.abspath(path))
        os.makedirs(d, exist_ok=True)
        with open(path, "w", encoding="utf-8") as f:
            json.dump(self.to_chrome(), f, default=str)
        return path


def _jsonable_dict(d: Dict[str, Any]) -> Dict[str, Any]:
    out = {}
    for k, v in d.items():
        if isinstance(v, (str, int, float, bool)) or v is None:
            out[k] = v
        else:
            try:
                out[k] = float(v)     # numpy/device scalars
            except Exception:
                out[k] = str(v)
    return out


# -- trace JSONL schema validation (check_metrics_log --trace) -------------

_SPAN_REQUIRED = {
    "trace_id": (int,),
    "span_id": (int,),
    "parent_id": (int,),
    "name": (str,),
    "ts": (int, float),
    "dur_s": (int, float),
}


def validate_trace_record(rec: Dict[str, Any], *, index: int = 0):
    """Schema-check one trace JSONL record; raises ValueError with a
    precise message (the runlog validate_record discipline)."""

    def fail(msg):
        raise ValueError(f"trace record {index}: {msg} (record={rec!r})")

    if not isinstance(rec, dict):
        fail("not a JSON object")
    kind = rec.get("kind")
    if kind == "trace_meta":
        if not isinstance(rec.get("schema_version"), int):
            fail("trace_meta missing integer 'schema_version'")
        return
    if kind != "span":
        fail(f"unknown kind {kind!r} (expected 'span' or 'trace_meta')")
    for field, types in _SPAN_REQUIRED.items():
        v = rec.get(field)
        if not isinstance(v, types) or isinstance(v, bool):
            fail(f"missing/mistyped span field {field!r}")
    if rec["dur_s"] < 0:
        fail(f"negative dur_s: {rec['dur_s']}")
    if rec["span_id"] == rec["parent_id"]:
        fail("span is its own parent")
    for ev in rec.get("events", ()):
        if not isinstance(ev, dict) or not isinstance(ev.get("name"), str) \
                or not isinstance(ev.get("ts"), (int, float)):
            fail(f"malformed event {ev!r}")
    if rec["name"] == "router.handoff":
        # disaggregation contract (ISSUE 19): a handoff span rides the
        # REQUEST's trace id (one Perfetto timeline from route through
        # handoff to decode) and names its source; a successfully
        # placed handoff also names the decode destination
        attrs = rec.get("attrs") or {}
        if not attrs.get("src"):
            fail("router.handoff span missing 'src' attr")
        if rec["trace_id"] == 0:
            fail("router.handoff span is off the request's trace "
                 "(trace_id=0)")
        if rec.get("status", "ok") == "ok" and not attrs.get("dst"):
            fail("placed router.handoff span missing 'dst' attr")


def validate_trace_log(path: str, *, require_spans: int = 0) -> int:
    """Validate every record of a span JSONL export; returns the span
    count. A trailing partial line (crash artifact) is tolerated."""
    from paddle_tpu.observability import runlog
    spans = 0
    for i, rec in enumerate(runlog.read_run_log(path)):
        validate_trace_record(rec, index=i)
        if rec.get("kind") == "span":
            spans += 1
    if spans < require_spans:
        raise ValueError(
            f"{path}: {spans} span records < required {require_spans}")
    return spans


def chrome_trace_valid(trace: Dict[str, Any], *, require_events: int = 0):
    """Assert the Chrome trace-event invariants Perfetto needs: a
    ``traceEvents`` list whose every entry carries ``ph``/``ts``/
    ``pid``/``tid`` (and ``dur`` for complete events). Raises ValueError;
    used by run_ci's bench-artifact pin and the tests."""
    evs = trace.get("traceEvents")
    if not isinstance(evs, list):
        raise ValueError("chrome trace: missing traceEvents list")
    for i, e in enumerate(evs):
        for k in ("ph", "ts", "pid", "tid", "name"):
            if k not in e:
                raise ValueError(f"chrome trace event {i}: missing {k!r}")
        if e["ph"] == "X" and "dur" not in e:
            raise ValueError(f"chrome trace event {i}: X without dur")
    if len(evs) < require_events:
        raise ValueError(f"chrome trace: {len(evs)} events < required "
                         f"{require_events}")
    return len(evs)


def records_to_chrome(records: Iterable[Dict[str, Any]]
                      ) -> Dict[str, Any]:
    """Span JSONL records (``Span.to_record`` shape) → Chrome trace-
    event JSON. The ONE builder behind :meth:`Tracer.to_chrome` and
    :func:`chrome_trace_from_jsonl`, so the live and offline exports
    can never drift out of the :func:`chrome_trace_valid` contract."""
    pid = os.getpid()
    tids: Dict[str, int] = {}
    events: List[Dict[str, Any]] = []
    recs = list(records)
    base = min((r["ts"] for r in recs), default=0.0)
    for r in recs:
        tid = tids.setdefault(r.get("thread", "main"), len(tids))
        args = dict(r.get("attrs", {}), trace_id=r["trace_id"],
                    span_id=r["span_id"], parent_id=r["parent_id"],
                    status=r.get("status", "ok"))
        events.append({"name": r["name"], "cat": "span", "ph": "X",
                       "ts": (r["ts"] - base) * 1e6,
                       "dur": max(r["dur_s"], 0.0) * 1e6,
                       "pid": pid, "tid": tid, "args": args})
        for ev in r.get("events", ()):
            events.append({"name": ev["name"], "cat": "event", "ph": "i",
                           "s": "t", "ts": (ev["ts"] - base) * 1e6,
                           "pid": pid, "tid": tid,
                           "args": dict(ev.get("attrs", {}),
                                        trace_id=r["trace_id"],
                                        span_id=r["span_id"])})
    return {"traceEvents": events, "displayTimeUnit": "ms"}


def chrome_trace_from_jsonl(path: str, out_path: str) -> str:
    """Offline conversion: span JSONL export → Chrome trace file."""
    from paddle_tpu.observability import runlog
    recs = [r for r in runlog.read_run_log(path) if r.get("kind") == "span"]
    with open(out_path, "w", encoding="utf-8") as f:
        json.dump(records_to_chrome(recs), f)
    return out_path


# -- process-wide default tracer (disabled until someone enables it) -------

_DEFAULT = Tracer(enabled=False)


def default() -> Tracer:
    return _DEFAULT


def enable(capacity: Optional[int] = None) -> Tracer:
    """Turn on the process-wide tracer (serving binaries call this at
    startup; tests enable around the region they assert on)."""
    return _DEFAULT.enable(capacity)


def disable() -> Tracer:
    return _DEFAULT.disable()
