"""Step-time anatomy: where does one jitted serving step's wall time go.

The tracing ring (PR 8) shows request lifecycles; the registry shows
aggregate latencies. Neither answers the scheduling question ROADMAP
items 1/3/5 block on: per *step*, how much time is host gap between
device steps, how much is call wall time (uploads, dispatch + sync of
the jitted call, which holds the device's work but is read on the host's
clock)
split by phase (prefill / decode / draft / verify), and how much is the
host remainder. (What a tp engine's collectives leave exposed is a
device-trace reading, ``device.collective_exposed_pct``, not a host one.)

:class:`StepAnatomy` is the host-side accumulator the engine drives
around its fixed-shape calls — nothing here touches jitted code, so the
zero-steady-state-recompile invariant is untouched:

- ``begin_step()`` stamps the step start and the host gap since the
  previous step ended;
- ``add_phase(phase, start, end)`` records one call interval, dispatch
  through sync (the engine's ``tracer.phase`` spans already hold these
  stamps — no extra clock reads on the hot path);
- ``end_step(tokens=...)`` closes the record, pushes it into a bounded
  ring and publishes registry histograms/gauges. The intervals
  themselves are the engine's ``serving.step`` / ``serving.*`` phase
  spans (one Perfetto export shows them beside ``serving.request``);
  nothing is recorded a second time here.

**The step's own record (PR 52).** ``end_step(parts=, prefill_calls=)``
also keeps what THIS step cost, from the clock reads the engine's phases
took anyway: ``parts``, the seconds of each ``phase.part`` of
``serving_step_part_seconds_total`` inside this step, with
:data:`GAP_PART` (what the caller spent between the step before and this
one, outside the wall) and :data:`OTHER_PART` (what is left of the wall:
they sum to it), and ``prefill_calls``, one ``[lanes_live, lanes, width,
tokens, seconds, run]`` a batched prefill call (``run``, since PR 54: the
most consecutive chunks of one slot among its lanes; a caller that gives
five has five kept; the slow records hold the two for good, the others
while they are among the newest ``PARTS_TAIL``).
:class:`SlowStepRule` judges every such step against the steps before
it and marks a slow one (``slow``, ``slow_part``, ``excess_s`` and what
the engine knows of the step), so the flight recorder's bundle and
``tools/postmortem.py`` hold the stall that was met, with no tracer on.

Records are plain dicts (JSONL-exportable, crash-safe via the runlog
discipline) validated by :func:`validate_anatomy_record` /
:func:`validate_anatomy_log` — the schema ``tools/check_metrics_log.py
--anatomy`` enforces: monotonic step ids, non-negative times, phase
sums bounded by step wall time and, where a record has them, parts that
sum to it.
"""

from __future__ import annotations

import json
import os
import threading
import time
from collections import deque
from typing import Any, Callable, Dict, Iterable, List, Optional, Tuple

from paddle_tpu.observability import registry as _registry

ANATOMY_SCHEMA_VERSION = 1

# the engine's phase vocabulary; validation accepts these plus any
# future phase name (schema checks types, not the closed set)
PHASES = ("prefill", "decode", "draft", "verify")

# phase-time floats compare against wall time measured by separate
# clock reads; allow this much skew before calling the record corrupt
_EPS = 1e-6

#: how many of the newest steps' ``parts`` and ``prefill_calls`` are held
#: beside the ring (the flight recorder's ``anatomy_tail``: what a bundle
#: holds, the steps that led up to a slow one); a slow record holds its
#: own for good
PARTS_TAIL = 256

#: the part that is no part of the step's wall: what the caller spent
#: between the step before and this one while the engine held work
GAP_PART = "caller.gap"
#: what is left of the wall once the named parts are taken out (the
#: Python between two phases, a collection that fell there): with it a
#: record's parts sum to its wall
OTHER_PART = "step.other"

# -- the rule that flags a slow step ----------------------------------------
# One rule for every engine: module-level names, no constructor argument,
# nothing read from the environment.
#: working steps a median looks back over; a part's own median looks
#: back over the last this many working steps in which the part ran
SLOW_WINDOW = 128
#: no verdict from a median of fewer values than this
SLOW_MIN_STEPS = 32
#: a part's excess is what it took above this many times its median
SLOW_PART_FACTOR = 3.0
#: a step is slow when its parts' excess sums to more than this ...
SLOW_MIN_EXCESS_S = 0.025
#: ... and to more than this many times the median step wall
SLOW_WALL_FACTOR = 3.0


class _Last:
    """The last ``SLOW_WINDOW`` values of one series, written in place
    (``newest``: the last one pushed)."""

    __slots__ = ("values", "n", "at", "newest")

    def __init__(self):
        self.values = [0.0] * SLOW_WINDOW
        self.n = 0
        self.at = 0
        self.newest = 0.0

    def push(self, v: float) -> None:
        self.values[self.at] = self.newest = v
        self.at = (self.at + 1) % SLOW_WINDOW
        if self.n < SLOW_WINDOW:
            self.n += 1

    def median(self) -> Optional[float]:
        """None before ``SLOW_MIN_STEPS`` values: no verdict yet."""
        if self.n < SLOW_MIN_STEPS:
            return None
        return sorted(self.values[:self.n])[self.n // 2]


class SlowStepRule:
    """Says of each working step whether it was slow, and which part made
    it so: each part is compared with its own median over the last
    ``SLOW_WINDOW`` working steps in which it ran, or with what it took in
    the last of them where that was more; what it took above
    ``SLOW_PART_FACTOR`` x that is its *excess*; the step is slow when
    the excesses sum to more than ``SLOW_MIN_EXCESS_S`` and to more than
    ``SLOW_WALL_FACTOR`` x the median step wall; the part with the
    largest excess names it. A step is judged against the steps before
    it and then joins them, slow or not.

    The step before is in the rule because a sound load changes in steps
    that stay changed: a set-up that only prefills dispatches in a
    millisecond until the runtime's queue is full and then waits a whole
    device call in every ``prefill.dispatch``, longer as the context
    grows, and starts over with each document (the sessions cell's
    set-up, PERF.md section 6, PR 52: judged by the median alone, 120 of
    its steps were "slow"). Judged so, a change of regime is flagged
    ONCE, at its first step, and a ramp not at all; the price is that
    the second of two stalls in consecutive steps of one part goes
    uncounted unless it is three times the first.

    A step that cannot be slow costs two comparisons and the ring writes:
    the excesses cannot sum to more than the step spent in all (its wall
    and the caller's gap), so that sum is held against the floor and
    against the wall median as last computed before any median is taken.
    That cached median is taken again every ``SLOW_MIN_STEPS`` steps (one
    sort of ``SLOW_WINDOW`` floats) so that it follows a load whose steps
    shorten."""

    def __init__(self):
        self._parts: Dict[str, _Last] = {}
        self._wall = _Last()
        self._wall_median: Optional[float] = None
        self._stale = 0

    def judge(self, wall_s: float, parts: Dict[str, float]
              ) -> Optional[Tuple[str, float]]:
        """-> ``(part, excess seconds)`` of a slow step, else None."""
        verdict = None
        spent = wall_s + parts.get(GAP_PART, 0.0)
        self._stale += 1
        if self._wall_median is None or self._stale >= SLOW_MIN_STEPS:
            self._wall_median, self._stale = self._wall.median(), 0
        if (self._wall_median is not None and spent > SLOW_MIN_EXCESS_S
                and spent > SLOW_WALL_FACTOR * self._wall_median):
            verdict = self._judge_parts(parts)
        self._wall.push(wall_s)
        for name, v in parts.items():
            if v > 0.0:
                ring = self._parts.get(name)
                if ring is None:
                    ring = self._parts[name] = _Last()
                ring.push(v)
        return verdict

    def _judge_parts(self, parts):
        self._wall_median, self._stale = self._wall.median(), 0
        total, worst, worst_excess = 0.0, None, 0.0
        for name, v in parts.items():
            ring = self._parts.get(name)
            median = ring.median() if ring is not None and v > 0.0 else None
            if median is None:
                continue
            excess = v - SLOW_PART_FACTOR * max(median, ring.newest)
            if excess > 0.0:
                total += excess
                if excess > worst_excess:
                    worst, worst_excess = name, excess
        if total > SLOW_MIN_EXCESS_S \
                and total > SLOW_WALL_FACTOR * self._wall_median:
            return worst, total
        return None


class StepAnatomy:
    """Per-step wall-time decomposition with a bounded record ring.

    Single-writer (the engine step thread); reads (``records()``,
    ``summary()``, the flight recorder's dump) are lock-protected so
    exposition/monitor threads can snapshot mid-step.
    """

    now = staticmethod(time.monotonic)

    def __init__(self, registry: Optional[_registry.MetricsRegistry] = None,
                 capacity: int = 2048):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.registry = registry or _registry.default()
        self.capacity = capacity
        self.slow_rule = SlowStepRule()
        self._ring: "deque[Dict[str, Any]]" = deque(maxlen=capacity)
        # (step, its parts and prefill calls) of the newest steps that
        # were not slow: ``records()`` puts them back into their records
        self._tail: "deque[Tuple[int, Dict[str, Any]]]" = deque(
            maxlen=PARTS_TAIL)
        self._lock = threading.Lock()
        self._cur: Optional[Dict[str, Any]] = None
        self._last_end: Optional[float] = None
        self._step_seq = 0
        self._mono0 = time.monotonic()
        self._wall0 = time.time()
        # totals for summary() — cheap running sums, not ring-derived,
        # so the summary reflects the whole run even after ring wrap
        self._tot = {"steps": 0, "wall_s": 0.0, "gap_s": 0.0,
                     "host_s": 0.0, "tokens": 0}
        self._tot_phase: Dict[str, float] = {}
        self._tot_slow: Dict[str, int] = {}
        r = self.registry
        self._h_wall = r.histogram(
            "anatomy_step_wall_seconds",
            "serving step wall time (begin_step..end_step)")
        self._h_gap = r.histogram(
            "anatomy_host_gap_seconds",
            "host gap between consecutive steps")
        self._h_phase = r.histogram(
            "anatomy_phase_seconds",
            "call wall time (uploads + dispatch + sync) per step by phase")
        self._g_gap_frac = r.gauge(
            "anatomy_host_gap_frac",
            "fraction of timeline spent in host gaps between steps")
        self._g_host_frac = r.gauge(
            "anatomy_host_frac",
            "fraction of step wall spent in host assembly/data wait")
        self._c_steps = r.counter(
            "anatomy_steps_total", "anatomy records closed").child()
        self._phase_children: Dict[str, object] = {}

    def to_wall(self, t: float) -> float:
        return self._wall0 + (t - self._mono0)

    # -- step lifecycle ---------------------------------------------------
    def begin_step(self, step_id: Optional[int] = None,
                   t0: Optional[float] = None) -> None:
        """``t0`` / ``end_step``'s ``t1``: clock reads the caller already
        took around the step (the engine's ``serving.step`` phase), so
        that the record's wall is that span's, to the digit."""
        if t0 is None:
            t0 = self.now()
        gap = (t0 - self._last_end) if self._last_end is not None else 0.0
        if step_id is None:
            step_id = self._step_seq
        self._step_seq = step_id + 1
        self._cur = {"step": int(step_id), "t0": t0,
                     "gap_s": max(gap, 0.0), "phases": {}}

    def add_phase(self, phase: str, start: float, end: float) -> None:
        """Attribute one call interval, dispatch through sync (tracer-clock
        stamps the engine's phase spans already took), to ``phase``."""
        cur = self._cur
        if cur is None:
            return
        dur = max(end - start, 0.0)
        cur["phases"][phase] = cur["phases"].get(phase, 0.0) + dur

    def cancel_step(self) -> None:
        """Abandon the open step without recording it (an idle engine
        tick). The gap anchor still advances, so the next real step's
        host gap measures dispatch overhead, not queue-empty waiting."""
        if self._cur is not None:
            self._cur = None
            self._last_end = self.now()

    def end_step(self, tokens: int = 0, t1: Optional[float] = None,
                 parts: Optional[Dict[str, float]] = None,
                 prefill_calls: Optional[List[tuple]] = None,
                 slow_detail: Optional[Callable[[], Dict[str, Any]]] = None
                 ) -> Optional[Dict[str, Any]]:
        """``parts``: this step's seconds by ``phase.part``, ``GAP_PART``
        and ``OTHER_PART`` among them; ``prefill_calls``: its batched
        prefill calls, ``(lanes_live, lanes, width, tokens, seconds)``
        each, or with the call's longest run as a sixth. A step that gives its parts is judged by the slow-step
        rule; ``slow_detail()`` is asked only of a slow one, for what
        else its record should say. Returns the ring's record: a slow
        one holds its ``parts`` and ``prefill_calls`` itself, any other
        has them in ``records()`` / ``last()`` while it is among the
        newest ``PARTS_TAIL``."""
        cur = self._cur
        if cur is None:
            return None
        self._cur = None
        if t1 is None:
            t1 = self.now()
        wall = max(t1 - cur["t0"], 0.0)
        phases = {p: round(s, 9) for p, s in cur["phases"].items()}
        busy = sum(phases.values())
        host = max(wall - busy, 0.0)
        rec: Dict[str, Any] = {
            "kind": "anatomy",
            "schema_version": ANATOMY_SCHEMA_VERSION,
            "step": cur["step"],
            "ts": self.to_wall(cur["t0"]),
            "wall_s": round(wall, 9),
            "host_gap_s": round(cur["gap_s"], 9),
            "host_s": round(host, 9),
            "phases": phases,
            "tokens": int(tokens),
        }
        own = None
        if parts is not None:
            own = {
                "parts": {p: round(s, 9) for p, s in parts.items()
                          if s > 0.0 or p == OTHER_PART},
                "prefill_calls": [
                    [int(live), int(lanes), int(w), int(toks), round(s, 9)]
                    + [int(run) for run in more]
                    for live, lanes, w, toks, s, *more
                    in prefill_calls or ()]}
            verdict = self.slow_rule.judge(wall, parts)
            if verdict is not None:
                part, excess = verdict
                rec.update(own, slow=True, slow_part=part,
                           excess_s=round(excess, 9))
                own = None
                if slow_detail is not None:
                    rec.update(slow_detail())
                self._tot_slow[part] = self._tot_slow.get(part, 0) + 1
        self._publish(rec)
        self._last_end = t1
        with self._lock:
            self._ring.append(rec)
            if own is not None:
                self._tail.append((rec["step"], own))
        return rec

    def _publish(self, rec: Dict[str, Any]) -> None:
        wall = rec["wall_s"]
        self._h_wall.observe(wall)
        self._h_gap.observe(rec["host_gap_s"])
        for phase, s in rec["phases"].items():
            ch = self._phase_children.get(phase)
            if ch is None:
                ch = self._phase_children[phase] = \
                    self._h_phase.child(phase=phase)
            ch.observe(s)
        self._c_steps.inc()
        t = self._tot
        t["steps"] += 1
        t["wall_s"] += wall
        t["gap_s"] += rec["host_gap_s"]
        t["host_s"] += rec["host_s"]
        t["tokens"] += rec["tokens"]
        for phase, s in rec["phases"].items():
            self._tot_phase[phase] = self._tot_phase.get(phase, 0.0) + s
        timeline = t["wall_s"] + t["gap_s"]
        if timeline > 0:
            self._g_gap_frac.set(t["gap_s"] / timeline)
        if t["wall_s"] > 0:
            self._g_host_frac.set(t["host_s"] / t["wall_s"])

    # -- views ------------------------------------------------------------
    def records(self, limit: Optional[int] = None) -> List[Dict[str, Any]]:
        """Ring snapshot, oldest → newest, the newest steps' ``parts``
        and ``prefill_calls`` in their records."""
        with self._lock:
            out = list(self._ring)
            own = dict(self._tail)
        if limit is not None:
            out = out[-limit:] if limit > 0 else []
        return [dict(r, **own[r["step"]]) if r["step"] in own else r
                for r in out]

    def last(self) -> Optional[Dict[str, Any]]:
        recs = self.records(limit=1)
        return recs[0] if recs else None

    def __len__(self) -> int:
        with self._lock:
            return len(self._ring)

    def slow_records(self) -> List[Dict[str, Any]]:
        """The ring's slow steps, oldest → newest."""
        return [r for r in self.records() if r.get("slow")]

    def summary(self) -> Dict[str, Any]:
        """Whole-run aggregate (survives ring wrap): phase split,
        host-gap fraction and the slow steps met, by the part that named
        them."""
        t = dict(self._tot)
        steps = t["steps"]
        wall = t["wall_s"]
        timeline = wall + t["gap_s"]
        return {
            "steps": steps,
            "wall_s": wall,
            "tokens": t["tokens"],
            "host_gap_frac": (t["gap_s"] / timeline) if timeline else 0.0,
            "host_frac": (t["host_s"] / wall) if wall else 0.0,
            "phase_s": dict(self._tot_phase),
            "phase_frac": {p: (s / wall if wall else 0.0)
                           for p, s in self._tot_phase.items()},
            "slow_steps": dict(self._tot_slow),
        }

    def export_jsonl(self, path: str) -> int:
        """Append the ring to a JSONL file (one flushed line per record
        — the runlog crash-safety contract). Returns records written."""
        d = os.path.dirname(os.path.abspath(path))
        os.makedirs(d, exist_ok=True)
        recs = self.records()
        with open(path, "a", encoding="utf-8") as f:
            for rec in recs:
                f.write(json.dumps(rec, sort_keys=True) + "\n")
                f.flush()
        return len(recs)


#: how many of the newest slow steps a digest prints
SLOW_DIGEST = 5


def slow_step_lines(counts: Dict[str, Any], recs: Iterable[Dict[str, Any]]
                    ) -> List[str]:
    """The slow steps as ``observability.report`` and
    ``tools/postmortem.py`` print them, before anything else of the
    anatomy: how many by the part that named them (``counts``), then the
    newest ``SLOW_DIGEST`` of ``recs`` a line each."""
    counts = {p: int(n) for p, n in counts.items() if n}
    slow = [r for r in recs if r.get("slow")][-SLOW_DIGEST:]
    if not counts and not slow:
        return []
    out = [f"slow_steps {sum(counts.values())} " + " ".join(
        f"{p}={n}" for p, n in sorted(counts.items(), key=lambda kv: -kv[1]))]
    for r in slow:
        calls = r.get("prefill_calls") or []
        out.append(
            f"  step {r['step']}: wall={r['wall_s'] * 1e3:.2f}ms, "
            f"{r['slow_part']} {r['parts'].get(r['slow_part'], 0) * 1e3:.2f}"
            f"ms, excess={r['excess_s'] * 1e3:.2f}ms; slots_live="
            f"{r.get('slots_live', '?')} width={r.get('width', '?')} "
            f"admitted={r.get('admitted', '?')} evicted="
            f"{r.get('evicted', '?')} traces={r.get('traces', '?')} "
            f"gc={r.get('gc_s', 0.0) * 1e3:.2f}ms; prefill calls "
            f"{len(calls)}" + (" (lanes_live/lanes x width: " + " ".join(
                f"{c[0]}/{c[1]}x{c[2]}" for c in calls) + ")"
                if calls else ""))
    return out


# -- schema validation (check_metrics_log --anatomy) -----------------------

def validate_anatomy_record(rec: Dict[str, Any], *, index: int = 0,
                            prev_step: Optional[int] = None) -> int:
    """Schema-check one anatomy record; returns its step id so callers
    can thread the monotonicity check. Raises ValueError with a precise
    message (the runlog discipline)."""

    def fail(msg):
        raise ValueError(f"anatomy record {index}: {msg} (record={rec!r})")

    if not isinstance(rec, dict):
        fail("not a JSON object")
    if rec.get("kind") != "anatomy":
        fail(f"kind is {rec.get('kind')!r}, expected 'anatomy'")
    step = rec.get("step")
    if not isinstance(step, int) or isinstance(step, bool):
        fail("missing/mistyped integer 'step'")
    if prev_step is not None and step <= prev_step:
        fail(f"step ids not monotonic: {step} after {prev_step}")
    for field in ("wall_s", "host_gap_s", "host_s", "ts"):
        v = rec.get(field)
        if not isinstance(v, (int, float)) or isinstance(v, bool):
            fail(f"missing/mistyped numeric {field!r}")
        if field != "ts" and v < 0:
            fail(f"negative {field}: {v}")
    phases = rec.get("phases")
    if not isinstance(phases, dict):
        fail("missing 'phases' object")
    for p, s in phases.items():
        if not isinstance(p, str):
            fail(f"non-string phase key {p!r}")
        if not isinstance(s, (int, float)) or isinstance(s, bool) or s < 0:
            fail(f"phase {p!r} has bad duration {s!r}")
    if sum(phases.values()) > rec["wall_s"] + _EPS:
        fail(f"phase sum {sum(phases.values()):.9f} exceeds wall "
             f"{rec['wall_s']:.9f}")
    tok = rec.get("tokens", 0)
    if not isinstance(tok, int) or isinstance(tok, bool) or tok < 0:
        fail(f"bad tokens {tok!r}")

    def seconds(v):
        return isinstance(v, (int, float)) and not isinstance(v, bool) \
            and v >= 0

    # the step's own record: there since PR 52, absent in older bundles
    parts = rec.get("parts")
    if parts is not None:
        if not isinstance(parts, dict) or not all(
                isinstance(p, str) and seconds(s) for p, s in parts.items()):
            fail(f"bad 'parts' {parts!r}")
        inside = sum(s for p, s in parts.items() if p != GAP_PART)
        if inside > rec["wall_s"] + _EPS or (
                OTHER_PART in parts and inside < rec["wall_s"] - _EPS):
            fail(f"parts sum to {inside:.9f}, wall is {rec['wall_s']:.9f}")
    for i, call in enumerate(rec.get("prefill_calls", ())):
        if not isinstance(call, (list, tuple)) or len(call) not in (5, 6) \
                or not all(seconds(v) for v in call) or call[0] > call[1] \
                or (len(call) == 6 and not 1 <= call[5] <= call[0]):
            fail(f"prefill_calls[{i}] is {call!r}, want [lanes_live, "
                 "lanes, width, tokens, seconds] or with the longest run "
                 "as a sixth")
    if rec.get("slow"):
        if not isinstance(rec.get("slow_part"), str) \
                or not seconds(rec.get("excess_s")):
            fail("slow record without 'slow_part' / 'excess_s'")
    return step


def validate_anatomy_records(recs: Iterable[Dict[str, Any]]) -> int:
    """Validate an in-memory record sequence (monotonic step ids
    included); returns the record count."""
    prev: Optional[int] = None
    n = 0
    for i, rec in enumerate(recs):
        prev = validate_anatomy_record(rec, index=i, prev_step=prev)
        n += 1
    return n


def validate_anatomy_log(path: str, *, require_steps: int = 0) -> int:
    """Validate an anatomy JSONL export; returns the record count. A
    trailing partial line (crash artifact) is tolerated."""
    from paddle_tpu.observability import runlog
    prev: Optional[int] = None
    n = 0
    for i, rec in enumerate(runlog.read_run_log(path)):
        prev = validate_anatomy_record(rec, index=i, prev_step=prev)
        n += 1
    if n < require_steps:
        raise ValueError(
            f"{path}: {n} anatomy records < required {require_steps}")
    return n
