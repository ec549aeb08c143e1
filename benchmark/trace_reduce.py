"""From a profiler trace (``.xplane.pb``) to device busy time, idle
gaps, per-operation time and exposed collective time.

The reduction works on plain lists of ``Event`` so that it can be
checked on a hand-built list (``tests/test_trace_reduce.py``); only
``load`` touches the profiler's file format.

Definitions (all within the traced window, per device, then averaged
over the devices used):

- busy: the union of the intervals of the device's operation events;
- self time of an operation: its duration minus the part covered by
  events nested inside it (a ``while`` contains its body's operations),
  so that self times add up to busy time and no operation is counted
  twice;
- collective exposed time: the part of the collectives' intervals in
  which no non-collective operation runs on that device;
- idle gaps: the complement of busy in the window, each gap shared out
  among the host spans (``bench.*`` annotations) it overlaps.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple


@dataclasses.dataclass(frozen=True)
class Event:
    name: str               # the instruction's name, "fusion.553"
    start: float            # seconds on the trace's clock
    end: float
    opcode: str = ""        # "fusion", "custom-call", "copy", ...

    @property
    def group(self) -> str:
        """The name without its instruction number: the twelve layers'
        ``ragged_paged_decode.132`` .. ``.143`` are one group."""
        return re.sub(r"\.\d+$", "", self.name)


_HLO_TEXT = re.compile(r"^%?(?P<name>[^\s=]+) = .*? (?P<op>[a-z][a-z0-9\-]*)\(")


def parse_op(text: str) -> Tuple[str, str]:
    """A TPU trace names an operation by its whole HLO text,
    ``%fusion.5 = bf16[..]{..} fusion(...), kind=...``; -> (name, opcode).
    Anything else is its own name."""
    m = _HLO_TEXT.match(text)
    if m:
        return m.group("name"), m.group("op")
    return text.lstrip("%"), ""


#: HLO collectives, by the opcode that leads the instruction's name
COLLECTIVE_RE = re.compile(
    r"^(all-reduce|all-gather|all-to-all|reduce-scatter|"
    r"collective-permute|collective-broadcast|send|recv)", re.I)

#: the line of a device plane that holds one event per executed operation
OP_LINE = r"^XLA Ops$"
#: a CPU rehearsal has no device plane; its operations are on the host
#: plane's XLA threads. Only for driving this code without a chip.
REHEARSAL_PLANE = r"^/host:CPU$"
REHEARSAL_OP_LINE = r"^tf_XLA"


def merge(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Sorted disjoint union of ``(start, end)`` intervals."""
    out: List[List[float]] = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def total(intervals: Sequence[Tuple[float, float]]) -> float:
    return sum(e - s for s, e in intervals)


def clip(events: Iterable[Event], lo: float, hi: float) -> List[Event]:
    return [dataclasses.replace(ev, start=max(ev.start, lo),
                                end=min(ev.end, hi))
            for ev in events if ev.end > lo and ev.start < hi]


def subtract(a: Sequence[Tuple[float, float]],
             b: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """``a`` minus ``b``; both sorted and disjoint."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def self_times(events: Sequence[Event]) -> List[Tuple[Event, float]]:
    """Each event with its self time: duration minus nested events."""
    order = sorted(events, key=lambda ev: (ev.start, -(ev.end - ev.start)))
    out: List[List] = []
    stack: List[int] = []
    for ev in order:
        while stack and out[stack[-1]][0].end <= ev.start:
            stack.pop()
        if stack:
            parent = out[stack[-1]]
            parent[1] -= min(ev.end, parent[0].end) - ev.start
        out.append([ev, ev.end - ev.start])
        stack.append(len(out) - 1)
    return [(ev, max(t, 0.0)) for ev, t in out]


def is_collective(ev: Event) -> bool:
    return bool(COLLECTIVE_RE.match(ev.name))


@dataclasses.dataclass
class DeviceSummary:
    busy_s: float
    by_name: Dict[str, float]
    collective_s: float
    collective_exposed_s: float
    gaps: List[Tuple[float, float]]


def reduce_device(events: Sequence[Event], lo: float, hi: float
                  ) -> DeviceSummary:
    evs = clip(events, lo, hi)
    busy = merge((ev.start, ev.end) for ev in evs)
    by_name: Dict[str, float] = defaultdict(float)
    for ev, t in self_times(evs):
        by_name[ev.group] += t
    coll = merge((ev.start, ev.end) for ev in evs if is_collective(ev))
    # compute: the non-collective operations that hold no other one (a
    # ``while`` spans its body, collectives included, and is not compute)
    leaves = [ev for ev, t in self_times(
        [ev for ev in evs if not is_collective(ev)])
        if t >= 0.999 * (ev.end - ev.start)]
    compute = merge((ev.start, ev.end) for ev in leaves)
    return DeviceSummary(
        busy_s=total(busy), by_name=dict(by_name),
        collective_s=total(coll),
        collective_exposed_s=total(subtract(coll, compute)),
        gaps=subtract([(lo, hi)], busy))


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    busy_s: float                       # mean over devices
    by_name: Dict[str, float]           # self seconds by group, mean over devices
    opcode: Dict[str, str]              # group -> opcode
    collective_s: float
    collective_exposed_s: float
    idle_by_host: Dict[str, float]      # device 0's gaps by host span
    n_devices: int

    def seconds_matching(self, patterns: Sequence[str],
                         opcode: Optional[str] = None) -> float:
        """Self seconds of the operation groups whose name matches any
        of ``patterns`` (regular expressions, matched from the start)
        and, when given, whose opcode is ``opcode``. Only an
        instruction's OWN name counts: its operands' names do not."""
        regs = [re.compile(p) for p in patterns]
        return sum(t for name, t in self.by_name.items()
                   if any(r.match(name) for r in regs)
                   and (opcode is None or self.opcode.get(name) == opcode))

    def top_ops(self, n: int = 10) -> List[List]:
        top = sorted(self.by_name.items(), key=lambda kv: -kv[1])[:n]
        return [[name, t] for name, t in top]

    def top_idle(self, n: int = 10) -> List[List]:
        top = sorted(self.idle_by_host.items(), key=lambda kv: -kv[1])[:n]
        return [[name, t] for name, t in top]


def attribute_gaps(gaps: Sequence[Tuple[float, float]],
                   host_spans: Sequence[Event]) -> Dict[str, float]:
    """Share each idle gap out among the host spans it overlaps; the
    innermost span wins where spans nest (self time of spans). What no
    span covers goes to ``(no host span)``."""
    out: Dict[str, float] = defaultdict(float)
    covered: List[Tuple[float, float]] = []
    # innermost first: a span's share is what its children left
    spans = sorted(host_spans, key=lambda ev: ev.end - ev.start)
    for g in gaps:
        left = [g]
        for sp in spans:
            if sp.end <= g[0] or sp.start >= g[1]:
                continue
            hit = subtract([(max(sp.start, g[0]), min(sp.end, g[1]))],
                           merge(subtract([g], left)))
            t = total(hit)
            if t > 0:
                out[sp.name] += t
                left = subtract(left, merge(hit))
        out["(no host span)"] += total(left)
    return {k: v for k, v in out.items() if v > 0}


def summarize(device_events: Sequence[Sequence[Event]],
              host_spans: Sequence[Event], lo: float, hi: float
              ) -> TraceSummary:
    if not device_events:
        raise ValueError("the trace holds no device plane")
    devs = [reduce_device(evs, lo, hi) for evs in device_events]
    n = len(devs)
    by_name: Dict[str, float] = defaultdict(float)
    for d in devs:
        for k, v in d.by_name.items():
            by_name[k] += v / n
    opcode = {}
    for evs in device_events:
        for ev in evs:
            opcode.setdefault(ev.group, ev.opcode)
    return TraceSummary(
        window_s=hi - lo, busy_s=sum(d.busy_s for d in devs) / n,
        by_name=dict(by_name), opcode=opcode,
        collective_s=sum(d.collective_s for d in devs) / n,
        collective_exposed_s=sum(d.collective_exposed_s for d in devs) / n,
        idle_by_host=attribute_gaps(devs[0].gaps, clip(host_spans, lo, hi)),
        n_devices=n)


# ---------------------------------------------------------------------------
# the profiler's file
# ---------------------------------------------------------------------------

def find_xplane(logdir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        logdir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return paths[-1]


def load(path: str, *, span_prefix: str = "bench.",
         device_plane: str = r"^/device:TPU:\d+$", op_line: str = OP_LINE):
    """-> (device_events, host_spans): one list of operation events per
    device plane, in the order of the device ids, and the host events
    whose name starts with ``span_prefix``."""
    import jax.profiler
    data = jax.profiler.ProfileData.from_file(path)
    devices, host = {}, []
    for plane in data.planes:
        if re.match(device_plane, plane.name):
            evs = []
            for line in plane.lines:
                if not re.match(op_line, line.name):
                    continue
                for ev in line.events:
                    if ev.name.startswith(span_prefix) or not ev.duration_ns:
                        continue
                    s = ev.start_ns * 1e-9
                    name, op = parse_op(ev.name)
                    evs.append(Event(name, s, s + ev.duration_ns * 1e-9, op))
            devices[plane.name] = evs
        if not re.match(r"^/device:", plane.name):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(span_prefix):
                        s = ev.start_ns * 1e-9
                        host.append(Event(ev.name, s,
                                          s + ev.duration_ns * 1e-9))
    ordered = [devices[k] for k in sorted(
        devices, key=lambda n: int(n.rsplit(":", 1)[1])
        if n.rsplit(":", 1)[1].isdigit() else 0)]
    return ordered, host


def describe(path: str, top: int = 40) -> str:
    """A plain-text survey of a trace file: planes, lines, event counts
    and the longest-running names on each line. For looking at a trace
    by hand before trusting a pattern."""
    import jax.profiler
    data = jax.profiler.ProfileData.from_file(path)
    rows = []
    for plane in data.planes:
        rows.append(f"PLANE {plane.name}")
        for line in plane.lines:
            agg: Dict[str, List[float]] = defaultdict(lambda: [0, 0.0])
            sample = {}
            n = 0
            for ev in line.events:
                n += 1
                a = agg[ev.name]
                a[0] += 1
                a[1] += ev.duration_ns * 1e-9
                if ev.name not in sample:
                    sample[ev.name] = [(k, str(v)[:160]) for k, v in ev.stats]
            rows.append(f"  LINE {line.name!r} events={n}")
            for name, (c, t) in sorted(agg.items(),
                                       key=lambda kv: -kv[1][1])[:top]:
                rows.append(f"    {t:10.6f}s x{c:<6d} {name[:120]}  "
                            f"{sample[name][:6]}")
    return "\n".join(rows)
