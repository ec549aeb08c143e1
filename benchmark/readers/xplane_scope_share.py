"""Device trace x the program's scope tables: self time of the device's
operations whose ``phase/scope`` matches any of ``params.scopes``
(regular expressions, matched against the whole key), as a share of
device busy time, in percent.

The program opens ``jax.named_scope``s around its parts
(``forward/mlm_head``, ``optimizer/``, ``attend``, ``ffn``: PERF.md
section 3); they reach each compiled instruction's ``op_name`` metadata
and not the trace, so ``paddle_tpu.observability.scopes.tables()`` builds
``{instruction: Scope}`` from every program the process has loaded, and
this joins each traced event with it by (module, instruction). The
module of an event: its ``hlo_module`` / ``program_id`` stats where the
trace has them (a CPU rehearsal), else the event of the device plane's
``XLA Modules`` line that encloses it in time (a TPU). Several loaded
programs may share a module name (one a gather width), so the program's
``Tables.candidates`` narrows them by every instruction seen in that
execution and ``Tables.find`` books an event only where all that remain
agree; what cannot be keyed is ``unattributed``, never guessed. A fusion
is booked whole to its own metadata.

Like ``xplane_idle_under_span`` this loads the trace file again (once a
process): ``TraceSummary`` keeps neither an event's module nor its
window. Times are self times (:func:`self_times`) of every used device's
events inside ``bench.traced_window``, averaged over the devices as
``trace_reduce.summarize`` does. ``params.exclude_opcode`` (one opcode
or a list) and ``params.exclude_names`` (patterns on the instruction
group, matched from the start) leave events out of the numerator.
``run.notes`` gets ONE note with the whole table. A program without the
scope tables (before they were added) gives nothing to read."""

import bisect
import dataclasses
import functools
import os
import re
import time
from collections import defaultdict
from typing import Optional

import common
import trace_reduce

UNATTRIBUTED = "unattributed"
MODULE_LINE = "XLA Modules"


@dataclasses.dataclass(frozen=True)
class RunEvent(trace_reduce.Event):
    sig: Optional[int] = None   # the result shape's signature, if named
    run: str = ""               # the execution it ran in: "jit_step(<id>)"


def _scopes_module():
    try:
        from paddle_tpu.observability import scopes
    except ImportError:         # a program from before the tables
        return None
    return scopes


def load(path, parse_instruction):
    """-> (one list of :class:`RunEvent` a device in device-id order,
    (lo, hi) of the ``bench.traced_window`` span or None)."""
    import jax.profiler
    data = jax.profiler.ProfileData.from_file(path)
    tpu = any(re.match(r"^/device:TPU:\d+$", p.name) for p in data.planes)
    plane_re = r"^/device:TPU:\d+$" if tpu else trace_reduce.REHEARSAL_PLANE
    line_re = trace_reduce.OP_LINE if tpu else trace_reduce.REHEARSAL_OP_LINE
    devices, window = {}, []
    for plane in data.planes:
        if not re.match(r"^/device:", plane.name):
            for line in plane.lines:
                window += [(ev.start_ns * 1e-9,
                            (ev.start_ns + ev.duration_ns) * 1e-9)
                           for ev in line.events
                           if ev.name == common.Profiler.WINDOW_SPAN]
        if not re.match(plane_re, plane.name):
            continue
        runs, evs = [], []
        for line in plane.lines:
            if line.name == MODULE_LINE:
                runs = sorted((ev.start_ns, ev.start_ns + ev.duration_ns,
                               ev.name) for ev in line.events)
        starts = [r[0] for r in runs]
        for line in plane.lines:
            if not re.match(line_re, line.name):
                continue
            for ev in line.events:
                if ev.name.startswith("bench.") or not ev.duration_ns:
                    continue
                name, op = trace_reduce.parse_op(ev.name)
                parsed = parse_instruction(ev.name)
                run = ""
                if runs:
                    k = bisect.bisect_right(starts, ev.start_ns) - 1
                    if k >= 0 and ev.start_ns < runs[k][1]:
                        run = runs[k][2]
                else:
                    stats = dict(ev.stats)
                    if "hlo_module" in stats:
                        run = (f"{stats['hlo_module']}"
                               f"({stats.get('program_id', 0)})")
                    elif not tpu:
                        continue    # a host thread's own event, no op
                s = ev.start_ns * 1e-9
                evs.append(RunEvent(name, s, s + ev.duration_ns * 1e-9, op,
                                    parsed[2] if parsed else None, run))
        devices[plane.name] = evs
    ordered = [devices[k] for k in sorted(
        devices, key=lambda n: int(n.rsplit(":", 1)[1])
        if n.rsplit(":", 1)[1].isdigit() else 0)]
    return ordered, (window[0] if len(window) == 1 else None)


def self_times(events):
    """Each event with the seconds in which it is the event that started
    last: every instant of the device's busy time goes to one event, so
    the shares add up to 100. ``trace_reduce.self_times`` takes a nested
    event's time from the ONE event below it on its stack; where two
    operations of a loop body overlap (an async ``copy-done`` beside a
    fusion) the younger one's part past the elder's end is then taken
    from nobody, the enclosing ``while`` keeps it, and the serving cells'
    shares came to 101.7-103.1 (my chip runs, PR 38). Here that part is
    taken from whichever enclosing events still run."""
    out, stack = [], []
    for ev in sorted(events, key=lambda e: (e.start, e.start - e.end)):
        while stack and out[stack[-1]][0].end <= ev.start:
            stack.pop()
        covered = ev.start
        for k in reversed(stack):
            under = out[k]
            if under[0].end > covered:
                upto = min(ev.end, under[0].end)
                under[1] -= upto - covered
                covered = upto
                if covered >= ev.end:
                    break
        out.append([ev, ev.end - ev.start])
        stack.append(len(out) - 1)
    return [(ev, max(t, 0.0)) for ev, t in out]


def by_scope(device_events, tables, lo, hi):
    """-> (busy seconds, rows): the devices' self seconds inside
    ``[lo, hi]`` by (``phase/scope`` or ``unattributed``, opcode,
    instruction group, in a mixed-scope fusion), both averaged over the
    devices."""
    rows = defaultdict(float)
    busy = 0.0
    n = len(device_events)
    for events in device_events:
        evs = trace_reduce.clip(events, lo, hi)
        busy += trace_reduce.total(trace_reduce.merge(
            (ev.start, ev.end) for ev in evs)) / n
        seen = defaultdict(set)
        for ev in evs:
            seen[ev.run].add((ev.name, ev.sig))
        cands = {run: tables.candidates(re.sub(r"\(-?\d+\)$", "", run), names)
                 for run, names in seen.items()}
        for ev, t in self_times(evs):
            sc = tables.find(cands[ev.run], ev.name)
            key = UNATTRIBUTED if sc is None else sc.key
            rows[(key, ev.opcode, ev.group, bool(sc and sc.mixed))] += t / n
    return busy, dict(rows)


def select(rows, params):
    """Seconds of the rows a metric's ``params`` select."""
    regs = [re.compile(p) for p in params["scopes"]]
    ops = params.get("exclude_opcode", [])
    ops = [ops] if isinstance(ops, str) else ops
    names = [re.compile(p) for p in params.get("exclude_names", [])]
    return sum(t for (key, op, group, _), t in rows.items()
               if any(r.fullmatch(key) for r in regs) and op not in ops
               and not any(r.match(group) for r in names))


def _note(busy, rows):
    pct = lambda t: 100.0 * t / busy                       # noqa: E731
    by_key, bare, kernels = defaultdict(float), defaultdict(float), 0.0
    for (key, op, group, _), t in rows.items():
        by_key[key] += t
        if key in ("", "forward/", "backward/", UNATTRIBUTED):
            bare[group] += t
            kernels += t if op == "custom-call" else 0.0
    mixed = sum(t for (_, _, _, m), t in rows.items() if m)
    table = ", ".join(f"{k or '(no scope)'} {pct(t):.2f}" for k, t in sorted(
        by_key.items(), key=lambda kv: -kv[1]))
    top = ", ".join(f"{g} {pct(t):.2f}" for g, t in sorted(
        bare.items(), key=lambda kv: -kv[1])[:8])
    return (f"device time by scope, % of busy ({sum(map(pct, by_key.values())):.2f} "
            f"in all): {table}; in mixed-scope fusions {pct(mixed):.2f}; "
            f"custom calls under no scope {pct(kernels):.2f}; under no scope "
            f"or unattributed, by instruction group: {top}")


def _resident_bytes():
    try:
        with open("/proc/self/statm") as f:
            return int(f.read().split()[1]) * os.sysconf("SC_PAGE_SIZE")
    except (OSError, ValueError):
        return 0


@functools.lru_cache(maxsize=1)
def table(path, mtime_ns, n_devices):
    """-> (busy seconds, rows, a note for the log) over the first
    ``n_devices`` devices, or None where the program has no scope tables
    or the file no window."""
    scopes = _scopes_module()
    if scopes is None:
        return None
    t0, rss0 = time.perf_counter(), _resident_bytes()
    tables = scopes.tables()
    t1, rss1 = time.perf_counter(), _resident_bytes()
    devices, window = load(path, scopes.parse_instruction)
    if window is None or not devices:
        return None
    busy, rows = by_scope(devices[:n_devices], tables, *window)
    if busy <= 0:
        return None
    # what the instrumentation costs when it is ON (PERF.md section 6)
    cost = (f"; scope tables of {len(tables.programs)} programs, "
            f"{sum(len(p.scopes) for p in tables.programs)} instructions: "
            f"built in {t1 - t0:.2f} s, resident +{(rss1 - rss0) / 2**20:.0f} "
            f"MiB; trace read and joined in {time.perf_counter() - t1:.2f} s")
    return busy, rows, _note(busy, rows) + cost


def read(params, run):
    if run.trace is None:
        return None
    try:
        path = trace_reduce.find_xplane(common.TRACE_DIR)
    except FileNotFoundError:
        return None
    found = table(path, os.stat(path).st_mtime_ns, run.trace.n_devices)
    if found is None:
        return None
    busy, rows, note = found
    if note not in run.notes:
        run.notes.append(note)
    return 100.0 * select(rows, params) / busy
