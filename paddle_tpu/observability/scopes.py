"""Device time by model part: from the ``jax.named_scope`` names the
step programs open to the instructions the device trace times.

A ``named_scope`` exists only while a function is traced: it adds no
operation, and ends up as the ``op_name`` in the ``metadata`` of every
HLO instruction the compiler made from what the scope held
(``jit(step)/transpose(jvp(forward))/mlm_head/dot_general``). The
profiler's device trace names an event by its instruction (on a TPU by
the instruction's text up to its operands, ``%fusion.7 = bf16[48,512]{..}
fusion(..)``) inside an event of the program that ran it
(``jit_step(<id>)`` on the ``XLA Modules`` line); what ``jax.profiler``'s
reader hands out of the file carries no ``op_name``. The join is
therefore made here, by the program, from its own programs:
``recompile.loaded_programs()`` lists every program the backend has
loaded (the compile listener holds the last few it saw, so that one a
caller has dropped can still be asked), and :func:`tables` reads each
one's HLO text ONCE, ON DEMAND, into
``{instruction name: Scope}``. The key is (module name, instruction
name); several loaded programs may share a module name (a serving engine
loads one a gather width), so the programs an execution can have been are
narrowed by every instruction seen in it, name and result shape, and an
instruction is booked only where all that remain agree
(:meth:`Tables.candidates`, :meth:`Tables.find`): what cannot be keyed is
``unattributed``, never guessed. Nothing in this module runs unless a
caller asks (``profiler.device_time_by_scope``, the benchmark's
``xplane_scope_share`` reader).

The scope names are a contract like the ``serving.*`` span names: each
is a module-level tuple beside the code that opens it (:func:`scope_names`
lists where), and PERF.md section 3 says what each wraps.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
import zlib
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from paddle_tpu.observability import recompile

#: what cannot be keyed: an event of no catalogued program, or an
#: instruction name that two programs of one module name book differently
UNATTRIBUTED = "unattributed"


@dataclasses.dataclass(frozen=True)
class Scope:
    """One ``op_name``, split. ``phase``: ``forward``, ``backward`` (under
    ``transpose(``), ``optimizer`` or ``""``; ``scope``: the first entry
    that is one of :func:`scope_names`, ``""`` if none; ``rest``: what
    follows that entry (with no scope: the phase's; with neither: all of
    it). ``mixed``: a fusion whose fused instructions
    carry more than one ``phase/scope`` (it is booked whole to its own
    ``metadata`` all the same)."""
    phase: str = ""
    scope: str = ""
    rest: str = ""
    mixed: bool = False

    @property
    def key(self) -> str:
        """``phase/scope``; the bare scope where there is no phase (a
        serving step), so ``""`` is an instruction under no name at all."""
        return f"{self.phase}/{self.scope}" if self.phase else self.scope


def scope_names() -> Tuple[frozenset, frozenset]:
    """-> (scope names, phase names), from the tuples kept beside the
    code that opens them."""
    from paddle_tpu import train
    from paddle_tpu.models import bert
    from paddle_tpu.nn import transformer
    from paddle_tpu.serving import engine
    return (frozenset(engine.STEP_SCOPES + transformer.BLOCK_SCOPES
                      + bert.MODEL_SCOPES),
            frozenset(train.PHASE_SCOPES))


_WRAPPED = re.compile(r"^(\w+)\((.*)\)$")
#: wrappers that name a called function, not a scope: ``jit(_take)``
_CALLS = frozenset(("jit", "pjit"))


def _unwrap(entry: str) -> Tuple[str, Tuple[str, ...]]:
    """``transpose(jvp(forward))`` -> (``forward``, (``transpose``,
    ``jvp``)): the name a transform wrapped, and the transforms."""
    wrappers = []
    while True:
        m = _WRAPPED.match(entry)
        if not m:
            return entry, tuple(wrappers)
        wrappers.append(m.group(1))
        entry = m.group(2)


def split_op_name(op_name: str, names: Optional[Tuple] = None) -> Scope:
    """An instruction's ``op_name`` -> :class:`Scope`."""
    scopes, phases = names or scope_names()
    entries = op_name.split("/") if op_name else []
    phase, scope, backward, after = "", "", False, 0
    for i, entry in enumerate(entries):
        core, wrappers = _unwrap(entry)
        backward = backward or "transpose" in wrappers
        if _CALLS.intersection(wrappers):
            continue
        if not phase and not scope and core in phases:
            phase, after = core, i + 1
        elif not scope and core in scopes:
            scope, after = core, i + 1
    if backward:
        phase = "backward"
    return Scope(phase, scope, "/".join(entries[after:]))


# -- one program's HLO text -> {instruction: Scope} -------------------------

_COMPUTATION = re.compile(r"^(?:ENTRY )?%?(?P<name>\S+) \(.*\{$")
#: an instruction as the HLO text AND a TPU trace's event name write it:
#: ``%name = <result shape> opcode(``
_INSTRUCTION = re.compile(
    r"^(?:ROOT )?%?(?P<name>[^\s=]+) = (?P<shape>.*?) "
    r"(?P<op>[a-z][a-z0-9\-]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLED = re.compile(r"calls=%?([^\s,}]+)")


def parse_instruction(text: str) -> Optional[Tuple[str, str, int]]:
    """``%fusion.5 = bf16[8,128]{1,0} fusion(...)`` -> (name, opcode,
    signature of the result shape as written); None for any other text
    (a CPU trace names an event by the instruction's name alone)."""
    m = _INSTRUCTION.match(text)
    if not m:
        return None
    return m.group("name"), m.group("op"), zlib.crc32(
        m.group("shape").encode())


@dataclasses.dataclass
class ProgramTable:
    """One loaded program: its module's name and, by instruction name,
    the scope each instruction is booked to and its result shape's
    signature."""
    module: str
    scopes: Dict[str, Scope]
    shapes: Dict[str, int]
    fusions: int = 0
    mixed_fusions: int = 0

    @property
    def mixed_share(self) -> float:
        """The share of this program's fusions that hold fused
        instructions of more than one scope: how blunt the split is."""
        return self.mixed_fusions / self.fusions if self.fusions else 0.0

    def has(self, seen: Iterable[Tuple[str, Optional[int]]]) -> bool:
        """Whether every (instruction, shape signature or None) of
        ``seen`` is an instruction of this program."""
        return all(self.shapes.get(name, -1) == sig if sig is not None
                   else name in self.shapes for name, sig in seen)


def parse_hlo(text: str, names: Optional[Tuple] = None) -> ProgramTable:
    """The text of one HLO module (``HloModule.to_string()``) ->
    :class:`ProgramTable`. A fusion is booked to its OWN metadata; the
    fused computation it calls decides ``mixed``, and the scope of a
    fusion the compiler left without metadata where every fused
    instruction that has some agrees (a pool's in-place row write)."""
    names = names or scope_names()
    split: Dict[str, Scope] = {}         # op_name -> Scope, each split once
    head = re.match(r"HloModule ([^\s,]+)", text)
    scopes: Dict[str, Scope] = {}
    shapes: Dict[str, int] = {}
    inside: Dict[str, Dict[str, Scope]] = {}    # computation -> its keys
    fusion_calls: List[Tuple[str, str]] = []
    body: Optional[Dict[str, Scope]] = None
    for line in text.splitlines():
        if not line.startswith(" "):
            m = _COMPUTATION.match(line)
            body = inside.setdefault(m.group("name"), {}) if m else None
            continue
        parsed = parse_instruction(line.lstrip())
        if parsed is None:
            continue
        name, opcode, shapes[name] = parsed
        found = _OP_NAME.search(line)
        op_name = found.group(1) if found else ""
        sc = split.get(op_name)
        if sc is None:
            sc = split[op_name] = split_op_name(op_name, names)
        scopes[name] = sc
        if body is not None and found:
            body.setdefault(sc.key, sc)
        if opcode == "fusion":
            called = _CALLED.search(line)
            if called:
                fusion_calls.append((name, called.group(1)))
    mixed = 0
    bare = split.get("")
    for name, called in fusion_calls:
        keys = inside.get(called, {})
        if len(keys) > 1:
            scopes[name] = dataclasses.replace(scopes[name], mixed=True)
            mixed += 1
        elif keys and scopes[name] is bare:
            (scopes[name],) = keys.values()
    return ProgramTable(head.group(1) if head else "", scopes, shapes,
                        len(fusion_calls), mixed)


# -- every loaded program ---------------------------------------------------

class Tables:
    """The tables of every catalogued program and the lookup the
    readers share."""

    def __init__(self, programs: Iterable[ProgramTable]):
        self.programs: List[ProgramTable] = list(programs)
        self._by_module: Dict[str, List[ProgramTable]] = defaultdict(list)
        for prog in self.programs:
            self._by_module[prog.module].append(prog)

    def candidates(self, module: str,
                   seen: Iterable[Tuple[str, Optional[int]]] = ()
                   ) -> List[ProgramTable]:
        """The programs named ``module`` that hold every instruction
        ``seen`` in one of the trace's executions: (name, shape signature
        from :func:`parse_instruction`, or None where the trace gives
        none)."""
        seen = list(seen)
        named = self._by_module.get(module, [])
        # by name and shape; by name alone where no program's text writes
        # a shape as the trace does; else every program of that name:
        # each step only widens what :meth:`find` needs to agree
        return ([p for p in named if p.has(seen)]
                or [p for p in named if p.has((n, None) for n, _ in seen)]
                or list(named))

    @staticmethod
    def find(candidates: Sequence[ProgramTable], instruction: str
             ) -> Optional[Scope]:
        """The scope of ``instruction`` where every one of ``candidates``
        books it to the same ``phase/scope``; else None (never guessed).
        ``mixed`` if it is in any."""
        found = [p.scopes.get(instruction) for p in candidates]
        if not found or None in found or len({s.key for s in found}) > 1:
            return None
        return max(found, key=lambda s: s.mixed)

    def mixed_share(self) -> Dict[str, float]:
        """By module name: mixed-scope fusions over fusions."""
        return {m: (sum(p.mixed_fusions for p in ps)
                    / max(sum(p.fusions for p in ps), 1))
                for m, ps in self._by_module.items()}


def tables() -> Tables:
    """The table of every program loaded now (what the process holds,
    and the last few the compile listener saw). Each program's text is
    read and parsed once, the first time this is called while it is
    loaded; a run that never calls this produces and parses no HLO
    text."""
    names = scope_names()
    out = []
    for prog in recompile.loaded_programs():
        if prog.table is None:
            prog.table = parse_hlo(
                prog.handle.hlo_modules()[0].to_string(), names)
        out.append(prog.table)
    return Tables(out)


# -- a profiler session's device events, booked -----------------------------

@dataclasses.dataclass(frozen=True)
class OpEvent:
    """One executed instruction of a device trace."""
    name: str               # "fusion.553"
    start: float            # seconds on the trace's clock
    end: float
    opcode: str = ""
    sig: Optional[int] = None   # result shape's signature, where given
    run: str = ""           # the execution's label: "jit_step(<id>)"


_TPU_PLANE = re.compile(r"^/device:TPU:\d+$")


def load_device_events(path: str) -> List[List[OpEvent]]:
    """One ``.xplane.pb`` -> a list of :class:`OpEvent` a device. A TPU
    plane's ``XLA Ops`` events are labelled with the event of its ``XLA
    Modules`` line that encloses them in time; with no TPU plane (a CPU
    run) the host plane's XLA threads are one device, and each event
    says its ``hlo_module`` / ``program_id`` itself."""
    import jax.profiler
    data = jax.profiler.ProfileData.from_file(path)
    devices: Dict[str, List[OpEvent]] = {}
    for plane in data.planes:
        if not _TPU_PLANE.match(plane.name):
            continue
        runs, raw = [], []
        for line in plane.lines:
            if line.name == "XLA Modules":
                runs = sorted((ev.start_ns, ev.start_ns + ev.duration_ns,
                               ev.name) for ev in line.events)
            elif line.name == "XLA Ops":
                raw = [ev for ev in line.events if ev.duration_ns]
        starts = [r[0] for r in runs]
        evs = []
        for ev in raw:
            parsed = parse_instruction(ev.name)
            name, op, sig = parsed or (ev.name.lstrip("%"), "", None)
            k = bisect.bisect_right(starts, ev.start_ns) - 1
            run = runs[k][2] if k >= 0 and ev.start_ns < runs[k][1] else ""
            evs.append(OpEvent(name, ev.start_ns * 1e-9,
                               (ev.start_ns + ev.duration_ns) * 1e-9,
                               op, sig, run))
        devices[plane.name] = evs
    if devices:
        return [devices[k] for k in sorted(
            devices, key=lambda n: int(n.rsplit(":", 1)[1]))]
    evs = []
    for plane in data.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            if not line.name.startswith("tf_XLA"):
                continue
            for ev in line.events:
                stats = dict(ev.stats)
                if "hlo_module" not in stats or not ev.duration_ns:
                    continue
                evs.append(OpEvent(
                    ev.name, ev.start_ns * 1e-9,
                    (ev.start_ns + ev.duration_ns) * 1e-9, run="{}({})".format(
                        stats["hlo_module"], stats.get("program_id", 0))))
    return [evs] if evs else []


def self_seconds(events: Sequence[OpEvent]) -> List[Tuple[OpEvent, float]]:
    """Each event with the seconds in which it is the event that started
    last (a ``while`` holds its body's instructions; two of them may
    overlap, an async copy beside a fusion): every instant of busy time
    goes to exactly one event."""
    out: List[List] = []
    stack: List[int] = []
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


def book(timed: Iterable[Tuple[OpEvent, float]], tabs: Tables
         ) -> List[Tuple[OpEvent, float, Optional[Scope]]]:
    """(event, seconds) -> (event, seconds, its Scope or None where it
    cannot be keyed). The programs an execution label can be are found
    once a label, from everything seen under it."""
    timed = list(timed)
    seen: Dict[str, set] = defaultdict(set)
    for ev, _ in timed:
        seen[ev.run].add((ev.name, ev.sig))
    # an execution's label is its module's name and the program's id
    cands = {run: tabs.candidates(re.sub(r"\(-?\d+\)$", "", run), names)
             for run, names in seen.items()}
    return [(ev, t, Tables.find(cands[ev.run], ev.name)) for ev, t in timed]


def find_xplane(logdir: str) -> str:
    """The newest ``.xplane.pb`` a profiler session left under
    ``logdir``."""
    paths = sorted(glob.glob(os.path.join(
        logdir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return paths[-1]


def device_time_by_scope(logdir: str) -> Dict[str, float]:
    """``{phase/scope: self seconds}`` of a profiler session's device
    events, averaged over its devices, plus ``unattributed``: what no
    catalogued program's table could key."""
    devices = load_device_events(find_xplane(logdir))
    tabs = tables()
    out: Dict[str, float] = defaultdict(float)
    for evs in devices:
        for ev, t, sc in book(self_seconds(evs), tabs):
            out[UNATTRIBUTED if sc is None else sc.key] += t / len(devices)
    return dict(out)
