"""Unified run summary: one ``report()`` call renders everything the
registry saw — counters, gauges, histograms, the profiler's
``record_event`` spans (which feed the same registry), the tracer's
ring-buffer spans per subsystem, and the SLO burn-rate/alert state — as
one text block. The reference's sorted profiler summary, generalized to
the whole telemetry surface.
"""

from __future__ import annotations

from typing import List, Optional

from paddle_tpu.observability import anatomy as _anatomy
from paddle_tpu.observability import registry as _registry
from paddle_tpu.observability import tracing as _tracing
from paddle_tpu.observability.registry import (Counter, Gauge, Histogram,
                                               _fmt_labels)

SPAN_METRIC = "record_event_span_seconds"


def report(reg: Optional[_registry.MetricsRegistry] = None,
           tracer: Optional[_tracing.Tracer] = None,
           anatomy: Optional[_anatomy.StepAnatomy] = None) -> str:
    """Render the unified observability summary. ``anatomy`` (an
    engine's ``eng.anatomy``): its newest slow steps are printed under
    their counts, which the registry alone gives."""
    reg = reg or _registry.default()
    tracer = tracer or _tracing.default()
    scalars: List[str] = []
    hists: List[str] = []
    spans: List[tuple] = []
    for m in reg.metrics():
        for key in m.labels_seen():
            labels = dict(key)
            if isinstance(m, Histogram):
                s = m.summary(**labels)
                if not s["count"]:
                    continue
                if m.name == SPAN_METRIC:
                    spans.append((labels.get("name", "?"), s))
                    continue
                hists.append(
                    f"{m.name}{_fmt_labels(key)}  count={s['count']} "
                    f"mean={s['mean']:.6g} min={s['min']:.6g} "
                    f"max={s['max']:.6g} sum={s['sum']:.6g}")
            else:
                kind = "c" if isinstance(m, Counter) else "g"
                scalars.append(f"{m.name}{_fmt_labels(key)} "
                               f"[{kind}] {m.value(**labels):.6g}")
    lines = ["== paddle_tpu observability report =="]
    if scalars:
        lines.append("-- counters / gauges --")
        lines.extend(sorted(scalars))
    if hists:
        lines.append("-- histograms --")
        lines.extend(sorted(hists))
    if spans:
        lines.append("-- record_event spans --")
        lines.append(f"{'Event':<32}{'Calls':>8}{'Total(s)':>12}"
                     f"{'Avg(ms)':>12}{'Max(ms)':>12}")
        for name, s in sorted(spans, key=lambda kv: -kv[1]["sum"]):
            lines.append(
                f"{name:<32}{s['count']:>8}{s['sum']:>12.4f}"
                f"{1e3 * s['mean']:>12.3f}{1e3 * s['max']:>12.3f}")
    trace_summary = tracer.summary()
    if trace_summary:
        lines.append("-- trace spans --")
        lines.append(f"{'Span':<32}{'Count':>8}{'Total(s)':>12}")
        for name, a in sorted(trace_summary.items(),
                              key=lambda kv: -kv[1]["total_s"]):
            lines.append(f"{name:<32}{a['count']:>8.0f}"
                         f"{a['total_s']:>12.4f}")
        if tracer.dropped:
            lines.append(f"(ring dropped {tracer.dropped} older spans)")
    anatomy_lines = _anatomy_lines(reg, anatomy)
    if anatomy_lines:
        lines.append("-- anatomy --")
        lines.extend(anatomy_lines)
    slo_lines = _slo_lines(reg)
    if slo_lines:
        lines.append("-- slo --")
        lines.extend(slo_lines)
    if len(lines) == 1:
        lines.append("(no metrics recorded)")
    return "\n".join(lines)


def _anatomy_lines(reg: _registry.MetricsRegistry,
                   anatomy: Optional[_anatomy.StepAnatomy] = None
                   ) -> List[str]:
    """Step-anatomy digest, when a StepAnatomy fed this registry: first
    the slow steps (``serving_slow_steps_total`` by part, then the ring's
    newest records) and the traces and collector seconds inside working
    steps, then the per-phase call-wall-time split,
    host-gap/host fractions, and the resource-headroom snapshot."""
    counts = {}
    slow = reg.get("serving_slow_steps_total")
    if isinstance(slow, Counter):
        for key in slow.labels_seen():
            labels = dict(key)
            counts[f"{labels.get('phase', '?')}.{labels.get('part', '?')}"] \
                = slow.value(**labels)
    out: List[str] = _anatomy.slow_step_lines(
        counts, anatomy.records() if anatomy is not None else ())
    traces = reg.get("serving_step_traces_total")
    gc_s = reg.get("serving_step_gc_seconds_total")
    if isinstance(traces, Counter) and isinstance(gc_s, Counter):
        # what a step can wait on that no part names
        out.append(f"in_working_steps traces={int(traces.value())} "
                   f"gc={gc_s.value() * 1e3:.2f}ms")
    phase_h = reg.get("anatomy_phase_seconds")
    if isinstance(phase_h, Histogram):
        sums = {}
        for key in phase_h.labels_seen():
            s = phase_h.summary(**dict(key))
            if s["count"]:
                sums[dict(key).get("phase", "?")] = s["sum"]
        busy = sum(sums.values())
        if busy > 0:
            split = " ".join(f"{p}={v / busy:.1%}"
                             for p, v in sorted(sums.items(),
                                                key=lambda kv: -kv[1]))
            out.append(f"phase_split {split} (busy={busy:.4g}s)")
    for gname, label in (("anatomy_host_gap_frac", "host_gap_frac"),
                         ("anatomy_host_frac", "host_frac")):
        g = reg.get(gname)
        if isinstance(g, Gauge) and g.labels_seen():
            out.append(f"{label} {g.value():.4g}")
    head = reg.get("serving_headroom")
    if isinstance(head, Gauge):
        parts = []
        for key in sorted(head.labels_seen()):
            labels = dict(key)
            parts.append(f"{labels.get('resource', '?')}="
                         f"{head.value(**labels):.3g}")
        if parts:
            out.append("headroom " + " ".join(parts))
    return out


def _slo_lines(reg: _registry.MetricsRegistry) -> List[str]:
    """Current burn rates + alert counts, when SLO monitoring ran."""
    out: List[str] = []
    burn = reg.get("slo_burn_rate")
    if isinstance(burn, Gauge):
        for key in sorted(burn.labels_seen()):
            labels = dict(key)
            out.append(f"burn_rate slo={labels.get('slo', '?')} "
                       f"window={labels.get('window', '?')} "
                       f"{burn.value(**labels):.4g}")
    alerts = reg.get("slo_alerts_total")
    if isinstance(alerts, Counter):
        for key in sorted(alerts.labels_seen()):
            labels = dict(key)
            out.append(f"alerts slo={labels.get('slo', '?')} "
                       f"severity={labels.get('severity', '?')} "
                       f"{alerts.value(**labels):.0f}")
    return out
