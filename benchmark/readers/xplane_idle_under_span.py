"""Device 0's idle gaps inside the traced window, shared out over the
PROGRAM's own ``serving.*`` phase spans (innermost wins), summed over
the spans whose name matches any of ``patterns``, as a share of the
window, in percent. What no program span covers is booked to
``(no host span)``, which a pattern can select like any span.

``TraceSummary`` keeps no gaps and the benchmark's own reduction loads
``bench.*`` spans only, so this loads the same file again (once a
process) with ``span_prefix="serving."`` and takes the window from the
``bench.traced_window`` span. A program without these spans, as before
they were added, gives nothing to read.

The device plane and the host plane of one trace are stamped by two
clocks that need not agree (PERF.md section 6, PR 25: 1.2-1.5 ms on a
v5e), so a gap's share between two spans that meet inside it is only as
good as that; a sum over spans that cover a gap from end to end is
exact."""

import dataclasses
import functools
import os
import re

import common
import trace_reduce

NO_SPAN = "(no host span)"


def _load(path, prefix):
    """``trace_reduce.load``; a file with no TPU plane is a rehearsal."""
    devices, host = trace_reduce.load(path, span_prefix=prefix)
    if not devices:
        devices, host = trace_reduce.load(
            path, span_prefix=prefix,
            device_plane=trace_reduce.REHEARSAL_PLANE,
            op_line=trace_reduce.REHEARSAL_OP_LINE)
    return devices, host


def split(device_events, spans, lo, hi):
    """-> {span name: idle seconds of the device inside [lo, hi]}. The
    innermost span wins; ``NO_SPAN`` takes what no span covers."""
    # an annotation's attributes may come back inside its name, after "#"
    spans = [dataclasses.replace(ev, name=ev.name.split("#")[0])
             for ev in trace_reduce.clip(spans, lo, hi)]
    gaps = trace_reduce.reduce_device(device_events, lo, hi).gaps
    return trace_reduce.attribute_gaps(gaps, spans)


@functools.lru_cache(maxsize=1)
def idle_by_span(path, mtime_ns):
    """-> (window seconds, {span name: idle seconds}, a note for the log)
    or None where the file holds no program span."""
    _, bench = _load(path, "bench.")
    window = [ev for ev in bench if ev.name == common.Profiler.WINDOW_SPAN]
    devices, spans = _load(path, "serving.")
    if len(window) != 1 or not devices or not spans:
        return None
    lo, hi = window[0].start, window[0].end
    by_span = split(devices[0], spans, lo, hi)
    note = "device idle under the program's spans, ms: " + ", ".join(
        f"{k} {v * 1e3:.2f}" for k, v in sorted(
            by_span.items(), key=lambda kv: -kv[1]))
    return hi - lo, by_span, note


def read(params, run):
    if run.trace is None:
        return None
    try:
        path = trace_reduce.find_xplane(common.TRACE_DIR)
    except FileNotFoundError:
        return None
    found = idle_by_span(path, os.stat(path).st_mtime_ns)
    if found is None:
        return None
    window_s, by_span, note = found
    if note not in run.notes:
        run.notes.append(note)
    regs = [re.compile(p) for p in params["patterns"]]
    idle = sum(t for name, t in by_span.items()
               if any(r.match(name) for r in regs))
    return 100.0 * idle / window_s
