"""Profiling/tracing: host+device timeline with the reference's contract.

Reference mapping (SURVEY.md §5.1): RAII ``RecordEvent`` wrapping every op
(operator.cc:180) + CUPTI ``DeviceTracer`` correlating device activity +
``tools/timeline.py`` Chrome-trace emission, driven by
``fluid.profiler.profiler`` context managers (python/paddle/fluid/
profiler.py). TPU-native: ``jax.profiler`` (XPlane → TensorBoard/Perfetto)
carries the device side; ``record_event``/named_scope annotate traced
regions so XLA ops correlate back to model code
(:func:`device_time_by_scope` books a session's device time by those
names, from the loaded programs' own ``op_name`` metadata); a lightweight
host-side event table reproduces the sorted per-op summary report.
"""

from __future__ import annotations

import contextlib
import threading
import time
from typing import Dict, List, Optional

import jax

from paddle_tpu import observability as _obs


class _Events(threading.local):
    def __init__(self):
        self.active: Optional[List] = None


_EVENTS = _Events()


@contextlib.contextmanager
def record_event(name: str):
    """Annotate a region: shows up in device traces (named_scope → XLA op
    metadata), in the host event table under :func:`profiler`, in the
    observability registry's span histogram (so ``observability.report()``
    covers record_event spans without a profiler context), and — when the
    default tracer is enabled — in the request-trace timeline, parented
    to the calling thread's current span. (Inside jit the span fires once
    per TRACE, not per execution — host spans measure host work.)"""
    t0 = time.perf_counter()
    with jax.named_scope(name):
        yield
    dt = time.perf_counter() - t0
    _obs.observe_span(name, dt)
    tr = _obs.tracing.default()
    if tr.enabled:
        # duration-only record: perf_counter and the tracer's monotonic
        # clock may differ in epoch, so let the tracer place the span at
        # its own "now" minus the measured duration
        tr.record_span(name, duration_s=dt, cat="record_event")
    if _EVENTS.active is not None:
        _EVENTS.active.append((name, dt, t0))


@contextlib.contextmanager
def _collect_events(out: list):
    """Install a fresh host-event buffer; restore the previous one and
    append (events, wall) to ``out`` on exit. Shared by every profiling
    context manager so the collection protocol lives in one place."""
    prev = _EVENTS.active
    _EVENTS.active = []
    t0 = time.perf_counter()
    try:
        yield
    finally:
        events = _EVENTS.active
        _EVENTS.active = prev
        out.append((events, time.perf_counter() - t0))


@contextlib.contextmanager
def profiler(output_dir: Optional[str] = None, *, summary: bool = True):
    """Profile a region. With ``output_dir``, captures a jax.profiler trace
    viewable in TensorBoard/XProf (device timeline ≙ CUPTI tracer + Chrome
    trace). Always collects host record_event stats; prints the sorted
    summary table on exit (EnableProfiler/DisableProfiler parity), and
    under it the session's device time by named scope
    (:func:`device_time_by_scope`) when it traced a device."""
    if output_dir:
        jax.profiler.start_trace(output_dir)
    res = []
    try:
        with _collect_events(res):
            yield
    finally:
        if output_dir:
            jax.profiler.stop_trace()
        events, wall = res[0]
        if summary and events:
            print(format_summary(events, wall))
        if summary and output_dir:
            by_scope = device_time_by_scope(output_dir)
            if by_scope:                # the session traced a device
                print(format_by_scope(by_scope))


def device_time_by_scope(logdir: str) -> Dict[str, float]:
    """Device self seconds of the profiler session under ``logdir`` by
    the ``jax.named_scope`` each instruction was traced under:
    ``{"forward/ffn": 0.41, "backward/ffn": 0.83, "optimizer/": ...,
    "attend": ..., "unattributed": ...}`` (``phase/scope``; the bare
    scope where a step has no phases; ``unattributed``: events no loaded
    program's table could key). The session's ``.xplane.pb`` is joined
    with :func:`paddle_tpu.observability.scopes.tables` by (module,
    instruction): the programs must still be loaded, or have been
    catalogued by the compile listener (``build_train_step``,
    ``shard_train_step`` and the serving engine install it)."""
    return _obs.scopes.device_time_by_scope(logdir)


def format_by_scope(by_scope: Dict[str, float]) -> str:
    """The by-scope table of :func:`device_time_by_scope`, sorted."""
    total = sum(by_scope.values())
    lines = [f"{'Scope (device self time)':<32}{'Total(s)':>12}{'Ratio':>8}"]
    for key, t in sorted(by_scope.items(), key=lambda kv: -kv[1]):
        lines.append(f"{key or '(no scope)':<32}{t:>12.4f}"
                     f"{t / max(total, 1e-12):>8.2%}")
    return "\n".join(lines)


def format_summary(events, wall: float) -> str:
    """Sorted per-event table (profiler.cc sorted summaries)."""
    agg: Dict[str, List[float]] = {}
    for name, dt, *_ in events:
        agg.setdefault(name, []).append(dt)
    rows = sorted(agg.items(), key=lambda kv: -sum(kv[1]))
    lines = [f"{'Event':<32}{'Calls':>8}{'Total(s)':>12}{'Avg(ms)':>12}"
             f"{'Ratio':>8}"]
    for name, ts in rows:
        tot = sum(ts)
        lines.append(f"{name:<32}{len(ts):>8}{tot:>12.4f}"
                     f"{1e3 * tot / len(ts):>12.3f}"
                     f"{tot / max(wall, 1e-9):>8.2%}")
    return "\n".join(lines)


def chrome_trace(events, path: str, *, pid: int = 0):
    """Write host events as a Chrome trace (``chrome://tracing`` /
    Perfetto) — ``tools/timeline.py:131`` ``_ChromeTraceFormatter`` parity
    for the host-side table. Device-side timelines come from the
    jax.profiler capture (XPlane → Perfetto) which subsumes the CUPTI
    path; this covers the reference's host-annotation stream."""
    import json

    if not events:
        trace = {"traceEvents": []}
    else:
        base = min(t0 for _, _, t0 in events)
        trace = {"traceEvents": [
            {"name": name, "ph": "X", "pid": pid, "tid": 0,
             "ts": (t0 - base) * 1e6, "dur": dt * 1e6,
             "cat": "host"}
            for name, dt, t0 in events]}
    with open(path, "w") as f:
        json.dump(trace, f)
    return path


@contextlib.contextmanager
def profile_to_chrome_trace(path: str, *, summary: bool = False):
    """Profile a region and dump the host event stream as a Chrome trace
    file (fluid.profiler.profiler(output='timeline') parity)."""
    res = []
    try:
        with _collect_events(res):
            yield
    finally:
        events, wall = res[0]
        chrome_trace(events, path)
        if summary and events:
            print(format_summary(events, wall))


def start_server(port: int = 9012):
    """Live profiling endpoint (jax.profiler server) for on-demand capture."""
    return jax.profiler.start_server(port)


@contextlib.contextmanager
def step_marker(step: int):
    """Mark a training step (XProf StepEvents)."""
    with jax.profiler.StepTraceAnnotation("train", step_num=step):
        yield
