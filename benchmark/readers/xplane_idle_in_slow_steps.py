"""Device 0's idle gaps inside the engine steps the PROGRAM called slow,
as a share of the traced window, in percent.

The engine judges every step by one fixed rule and, after a slow one,
emits a zero-length ``serving.slow_step`` annotation whose ``step`` is
that of the ``serving.step`` span it judged (``part`` and ``excess_us``
say which part of the step and by how much). This joins the two by that
number and lays the device's gaps against the flagged steps' intervals.
A step flagged ``caller.gap`` stood still BEFORE its span (the caller's
time between two ``step()`` calls), so its interval opens ``excess_us``
before the span does. Such an interval that opens before the traced
window does is left out: the window opens when the benchmark's profiler
has started, which holds the main thread 48-96 ms between two steps
(PERF.md section 6, PR 52), and that is the benchmark's pause and not the
program's (``engine.slow_step_time_pct`` leaves ``caller/gap`` out for
the same reason). A trace with ``serving.step`` spans and no flagged
step reads 0.0; a trace without ``serving.step`` spans (a program before
they existed) gives nothing to read. A stall is tens of milliseconds, so
the 1.2-1.5 ms by which the trace's device and host planes disagree
(PERF.md section 6, PR 25) is left alone.

ONE log note says what each flagged step was: its wall, ``part``,
``excess_us``, the device's busy share inside it, its three longest
device operations and the ``lanes_live``/``width``/``tokens`` of the
``serving.prefill_call`` spans inside it: "device idle, host in
decode.dispatch" and "device busy, one 32-lane prefill call" read
differently there. The note opens with the engine's own counters over
the WHOLE window (the registry's delta: slow steps and their excess by
part, traces and collector seconds inside working steps), which is where
a stall outside the traced part shows."""

import functools
import os
import re

import common
import trace_reduce
from readers import xplane_idle_under_span as under

STEP, SLOW, CALL = "serving.step", "serving.slow_step", "serving.prefill_call"
GAP = "caller.gap"      # paddle_tpu.observability.anatomy.GAP_PART
_ATTR = re.compile(r"([A-Za-z_]\w*)=([^,#]*)")


def parse(name):
    """``"serving.step#step=7,t_mono_ns=5#"`` -> ``("serving.step",
    {"step": "7", "t_mono_ns": "5"})``: on the chip an annotation's
    attributes come back inside its name, after ``#``."""
    base, _, rest = name.partition("#")
    return base, dict(_ATTR.findall(rest))


def load_spans(path):
    """The host planes' ``serving.*`` events of a profiler file as
    ``(Event named without its attributes, attributes)``. Where the
    attributes are not in the name they are the event's own stats (a
    CPU trace, which is what a rehearsal leaves), and
    ``trace_reduce.load`` keeps names only: hence this second walk."""
    import jax.profiler
    out = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if not ev.name.startswith("serving."):
                    continue
                base, attrs = parse(ev.name)
                if base in (STEP, SLOW, CALL):
                    attrs.update((k, str(v)) for k, v in ev.stats)
                s = ev.start_ns * 1e-9
                out.append((trace_reduce.Event(
                    base, s, s + ev.duration_ns * 1e-9), attrs))
    return out


def slow_steps(device_events, spans, lo, hi):
    """``spans``: ``(Event, attributes)`` pairs. -> (idle seconds of the
    device inside the flagged steps, clipped to [lo, hi]; one dict a
    flagged step for the note, its ``wall_ms`` from where its interval
    opens), or None where ``spans`` hold no ``serving.step``."""
    steps, flags, calls = {}, {}, []
    for ev, attrs in spans:
        base = ev.name
        if base == STEP and "step" in attrs:
            steps[attrs["step"]] = ev
        elif base == SLOW and "step" in attrs:
            flags[attrs["step"]] = attrs
        elif base == CALL:
            calls.append((ev, attrs))
    if not steps:
        return None
    # the gaps as ``trace_reduce.reduce_device`` defines them (the window
    # less the union of the operations), without its per-operation times
    device_events = trace_reduce.clip(device_events, lo, hi)
    gaps = trace_reduce.subtract([(lo, hi)], trace_reduce.merge(
        (ev.start, ev.end) for ev in device_events))
    idle, found = 0.0, []
    for number, attrs in sorted(flags.items(), key=lambda kv: int(kv[0])):
        ev = steps.get(number)
        if ev is None or ev.end <= lo or ev.start >= hi:
            continue        # flagged before the trace began, or after
        start = ev.start
        if attrs.get("part") == GAP:
            # the caller stood still before the step: ``excess_us`` of it
            start -= float(attrs.get("excess_us", 0)) * 1e-6
            if start < lo:
                continue    # the benchmark's profiler starting
        s, e = max(start, lo), min(ev.end, hi)
        gap_s = trace_reduce.total(trace_reduce.subtract(
            [(s, e)], trace_reduce.subtract([(s, e)], gaps)))
        idle += gap_s
        ops = sorted(trace_reduce.clip(device_events, s, e),
                     key=lambda op: op.start - op.end)[:3]
        found.append({
            "step": int(number), "wall_ms": (ev.end - start) * 1e3,
            "part": attrs.get("part", "?"),
            "excess_us": attrs.get("excess_us", "?"),
            "busy_pct": 100.0 * (1.0 - gap_s / (e - s)) if e > s else 0.0,
            "ops": [(op.group, (op.end - op.start) * 1e3) for op in ops],
            "calls": [(a.get("lanes_live", "?"), a.get("width", "?"),
                       a.get("tokens", "?")) for c, a in calls
                      if c.start >= start and c.end <= ev.end]})
    return idle, found


_SERIES = re.compile(r'^serving_slow_step(s|_excess_seconds)_total'
                     r'\{part="(\w+)",phase="(\w+)"\}$')


def window_note(delta):
    """The engine's counters over the whole window, from the registry's
    delta: slow steps and excess seconds by ``phase.part`` (those that
    moved), traces and collector seconds inside working steps."""
    steps, excess = {}, {}
    for key, value in delta.items():
        m = _SERIES.match(key)
        if m and value:
            (steps if m.group(1) == "s" else excess)[
                f"{m.group(3)}.{m.group(2)}"] = value
    return ("slow steps over the window: " + (" ".join(
        f"{p}={int(n)} ({excess.get(p, 0.0) * 1e3:.1f} ms over)"
        for p, n in sorted(steps.items())) or "none")
        + f"; traces {int(delta.get('serving_step_traces_total', 0))}, "
        f"gc {delta.get('serving_step_gc_seconds_total', 0.0) * 1e3:.2f} ms"
        f" inside {int(delta.get('serving_steps_total', 0))} steps")


def note(found):
    if not found:
        return "slow steps in the traced part: none"
    return "slow steps in the traced part: " + "; ".join(
        f"step {f['step']} wall {f['wall_ms']:.2f} ms part {f['part']} "
        f"excess_us {f['excess_us']}, device busy {f['busy_pct']:.1f}% "
        "inside it, longest ops " + (", ".join(
            f"{g} {ms:.2f} ms" for g, ms in f["ops"]) or "none")
        + f", {len(f['calls'])} prefill calls (lanes_live/width/tokens) "
        + " ".join("/".join(c) for c in f["calls"]) for f in found)


@functools.lru_cache(maxsize=1)
def idle_in_slow_steps(path, mtime_ns):
    """-> (window seconds, idle seconds inside flagged steps, the note)
    or None where the file holds no ``serving.step`` span."""
    devices, bench = under._load(path, "bench.")
    window = [ev for ev in bench if ev.name == common.Profiler.WINDOW_SPAN]
    if len(window) != 1 or not devices:
        return None
    lo, hi = window[0].start, window[0].end
    got = slow_steps(devices[0], load_spans(path), lo, hi)
    if got is None:
        return None
    return hi - lo, got[0], note(got[1])


def read(params, run):
    if run.trace is None:
        return None
    try:
        path = trace_reduce.find_xplane(common.TRACE_DIR)
    except FileNotFoundError:
        return None
    found = idle_in_slow_steps(path, os.stat(path).st_mtime_ns)
    if found is None:
        return None
    window_s, idle_s, text = found
    text = window_note(run.registry_delta) + ". " + text[0].upper() + text[1:]
    if text not in run.notes:
        run.notes.append(text)
    return 100.0 * idle_s / window_s
