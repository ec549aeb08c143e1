"""Runtime telemetry subsystem: metrics, traces, exposition, anatomy.

Four pillars (ISSUE 1 + ISSUE 10 + ISSUE 16 / TensorFlow-paper-style
first-class telemetry):

1. **Metrics** (`registry.py`, `runlog.py`, `telemetry.py`,
   `recompile.py`, `aggregate.py`): process-wide named Counter / Gauge /
   Histogram with labels (thread-safe, with a lock-protected bound-child
   hot path); Prometheus text exposition; crash-safe JSONL run logs;
   the :class:`StepTelemetry` driver wired into ``Trainer.fit`` /
   ``Executor.train_from_dataset``; a :class:`RecompileDetector` over
   ``jax.monitoring`` compile events; cross-host min/mean/max skew.
2. **Traces** (`tracing.py`): request-lifecycle spans in a bounded ring
   buffer — thread-local span stacks, zero-cost-when-disabled no-op
   spans, JSONL + Chrome-trace (Perfetto) exporters — instrumenting the
   serving engines, scheduler decisions, Trainer steps, and snapshot
   save/restore. ``profiler.record_event`` regions fold into the same
   timeline.
3. **Live exposition + SLO monitoring** (`exposition.py`, `slo.py`):
   an opt-in stdlib HTTP endpoint serving ``/metrics`` / ``/healthz`` /
   ``/traces`` from a running process, and a multi-window burn-rate
   monitor over the latency histograms (``slo_burn_rate`` gauge,
   edge-triggered ``slo_alerts_total`` alerts into metrics AND trace).
4. **Step anatomy + crash flight recorder** (`anatomy.py`, `flight.py`):
   per-jitted-step wall-time decomposition (host gap, phase-split call
   wall time, host assembly) feeding histograms/gauges (the intervals
   themselves are the engine's ``serving.*`` phase spans);
   a bounded :class:`FlightRecorder` black box per replica that dumps
   schema-validated postmortem bundles (anatomy JSONL + Chrome trace +
   health trajectory) on eject / breaker-open / shed spikes, served
   live at ``/debug/postmortem`` and rendered by ``tools/postmortem.py``.

   **The step that took too long.** Each record of the anatomy ring
   (``eng.anatomy.records()``, always on, no tracer needed) holds that
   ONE step's ``parts`` (seconds by ``phase.part`` of
   ``serving_step_part_seconds_total``, with ``caller.gap``, the
   caller's time before the step, and ``step.other``, what no part
   names) and its ``prefill_calls`` (``[lanes_live, lanes, width,
   tokens, seconds, run]`` each; ``run``: the most consecutive chunks of
   one slot among the call's lanes). A fixed rule (``anatomy.SlowStepRule``; its
   constants are module-level names beside it, nothing can be set) marks
   a step ``slow`` with the part that made it so (``slow_part``,
   ``excess_s``) and what the engine knew of it (``slots_live``,
   ``width``, ``admitted``, ``evicted``, ``traces``, ``gc_s``). A part
   is held against its own median and against the step before, so a
   pause is flagged every time it comes and a change that stays (a
   queue to the device that has filled, a context that grows) once, at
   its first step. An
   operator's use: ``serving_slow_steps_total{phase,part}`` and
   ``serving_slow_step_excess_seconds_total{phase,part}`` on
   ``/metrics`` say how many such steps a replica met and where (0.0
   when none: under ``dispatch`` / ``sync`` the host waited for the
   device, under any other part or ``caller/gap`` the host itself stood
   still); beside them ``serving_step_traces_total`` (functions traced
   to a jaxpr inside working steps: anything above 0 after warm-up is a
   step program traced again, which no compile counter shows) and
   ``serving_step_gc_seconds_total`` (the collector's seconds inside
   working steps, to hold against ``serving_step_seconds_total``: the
   two a stall is first suspected of, ruled in or out without a
   profiler); ``report(reg, tracer, anatomy=eng.anatomy)`` and
   ``tools/postmortem.py BUNDLE.json`` print the slow steps first (the
   count by part, then the newest five records); in a profiler's trace a
   ``serving.slow_step`` annotation carries the ``step`` of the
   ``serving.step`` span it judged.

One :func:`report` call dumps a unified summary across all four.
"""

from paddle_tpu.observability import (anatomy, exposition, flight, recompile,
                                      scopes, slo, tracing)
from paddle_tpu.observability.anatomy import (StepAnatomy,
                                              validate_anatomy_log,
                                              validate_anatomy_record,
                                              validate_anatomy_records)
from paddle_tpu.observability.flight import (POSTMORTEM_SCHEMA,
                                             FlightRecorder,
                                             validate_postmortem_bundle,
                                             validate_postmortem_file,
                                             write_bundle)
from paddle_tpu.observability.registry import (Counter, Gauge, Histogram,
                                               MetricsRegistry, counter,
                                               default, gauge, histogram)
from paddle_tpu.observability.runlog import (RunLogWriter, read_run_log,
                                             validate_record,
                                             validate_run_log)
from paddle_tpu.observability.recompile import (RecompileDetector,
                                                compile_count,
                                                install_compile_listener,
                                                loaded_programs,
                                                shape_signature)
from paddle_tpu.observability.aggregate import aggregate, format_aggregate
from paddle_tpu.observability.telemetry import (StepTelemetry,
                                                device_memory_stats,
                                                record_memory_gauges)
from paddle_tpu.observability.report import SPAN_METRIC, report
from paddle_tpu.observability.tracing import (Span, Tracer,
                                              chrome_trace_valid,
                                              validate_trace_log)
from paddle_tpu.observability.exposition import ExpositionServer
from paddle_tpu.observability.slo import BurnRateMonitor


def render_prometheus(reg: MetricsRegistry = None) -> str:
    """Prometheus text-format exposition of ``reg`` (default registry)."""
    return (reg or default()).render_prometheus()


def snapshot(reg: MetricsRegistry = None) -> dict:
    """Flat scalar snapshot of ``reg`` (default registry)."""
    return (reg or default()).snapshot()


_SPAN_NAME_CAP = 256


def observe_span(name: str, seconds: float,
                 reg: MetricsRegistry = None):
    """Feed one profiler ``record_event`` span into the registry (the
    unified-summary bridge; called by ``paddle_tpu.profiler``).

    Cardinality-bounded: record_event names can be dynamic (per-shard,
    per-request), and the registry keeps one series per name for the
    process lifetime — beyond ``_SPAN_NAME_CAP`` distinct names, new
    ones lump into the ``__other__`` series instead of growing memory
    without bound."""
    h = (reg or default()).histogram(
        SPAN_METRIC, "host record_event span durations")
    seen = h.labels_seen()
    if len(seen) >= _SPAN_NAME_CAP and (("name", str(name)),) not in seen:
        name = "__other__"
    h.observe(seconds, name=name)


__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "counter",
    "default", "gauge", "histogram", "RunLogWriter", "read_run_log",
    "validate_record", "validate_run_log", "RecompileDetector",
    "compile_count", "install_compile_listener", "loaded_programs",
    "shape_signature",
    "aggregate", "format_aggregate", "StepTelemetry",
    "device_memory_stats", "record_memory_gauges", "SPAN_METRIC",
    "report", "render_prometheus", "snapshot", "observe_span",
    "Span", "Tracer", "validate_trace_log", "chrome_trace_valid",
    "ExpositionServer", "BurnRateMonitor",
    "StepAnatomy", "validate_anatomy_record", "validate_anatomy_records",
    "validate_anatomy_log", "FlightRecorder", "POSTMORTEM_SCHEMA",
    "validate_postmortem_bundle", "validate_postmortem_file",
    "write_bundle",
    "tracing", "exposition", "slo", "anatomy", "flight", "scopes",
]
