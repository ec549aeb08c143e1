"""Recompilation detection: surface silent mid-training retraces.

The dominant TPU-side performance failure mode is a jitted step silently
recompiling every step (shape drift in the input pipeline, a weak-type
flip, a Python-hashable static arg changing). XLA gives no hot-path
signal — the step just takes seconds instead of milliseconds — so this
module listens to ``jax.monitoring``'s compile-duration events (emitted
once per backend compile, cache hits excluded), keeps a process-wide
count, and lets the Trainer snapshot it per step: a count increase after
warmup is a retrace, logged as a structured warning with the function
name and the offending batch's arg-shape signature.

The same listener keeps the process's CATALOGUE of loaded programs: at
every compile event, and at every program read from the persistent
compile cache, it looks at the handles the backend lists
(``live_executables()``) and holds the ones it has not seen, the last
``_RECENT`` of them within ``_HELD_CODE_BYTES`` of generated code.
Handles only: no HLO text is produced or parsed
until ``observability.scopes.tables()`` asks, so with tracing off the
cost is one list call an event. A held handle keeps its program loaded
after the caller dropped it; what that costs on the chip is in PERF.md
section 6 (PR 38).

The one listener also counts what a step can wait on that no compile
event shows (PR 52): every function TRACED to a jaxpr
(:func:`trace_count`; a program traced again and then found in a cache
compiles nothing and still costs its tracing), and, through one
``gc.callbacks`` hook installed with it, the seconds the garbage
collector ran (:func:`gc_seconds`). The serving engine takes both deltas
over each working step.
"""

from __future__ import annotations

import collections
import dataclasses
import gc
import itertools
import threading
import time
from typing import Any, Callable, Deque, Dict, List, Optional, Tuple

from paddle_tpu.observability import registry as _registry

# any of these firing == one backend compile happened in-process
_COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",)
# a program came from the persistent cache instead (JAX's own event for
# the read): no compile, but a program the backend has loaded
_CACHE_READ_EVENTS = ("/jax/compilation_cache/cache_retrieval_time_sec",)
# a function was traced to a jaxpr, whatever became of it afterwards
_TRACE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",)

_lock = threading.Lock()
_installed = False
_count = 0
_traces = 0
_gc_seconds = 0.0
_gc_began: Optional[float] = None


#: the programs the listener saw last, held so that one the caller has
#: dropped since can still be asked for its table. Bounded in number (a
#: loaded program keeps its code mapped, and a process that compiles
#: thousands, a test suite or a retracing trainer, must not keep them
#: all) and in bytes: a held program's generated code stays in device
#: memory (the BERT cell's two reference programs, 34 and 70 MB, raised
#: ``memory_peak_bytes`` by exactly that on a chip that has 0.3 GB to
#: spare: PERF.md section 6, PR 38), so a program larger than the budget
#: is not held at all. A serving engine lifts the budget
#: (:func:`hold_step_programs`): its step programs are 10-60 MB of code
#: each and loaded for as long as it serves, so holding them costs
#: nothing until it is dropped, and a trace of its steps is read after.
#: A trainer's step program is told apart from the one-off programs
#: around it where it is traced (:func:`expect_step_program`) and never
#: makes room for one of those: the BERT cell's evaluation program is
#: within a megabyte of the budget on either side from one PR to the
#: next (33.9 MB, then 32.9: PERF.md section 6, PR 41) and, once under
#: it, pushed the 29 MB step program out and stayed loaded itself. The
#: NEWEST step program is held whatever its size: it is loaded for as
#: long as the trainer runs it, and its size is the compiler's choice
#: (the BERT cell's is 29 MB compiled against a full chip and 133 MB
#: with 4 GB to spare: PERF.md section 6, PR 47).
_RECENT = 64
_HELD_CODE_BYTES = 32 << 20
_held_code_bytes: Optional[int] = _HELD_CODE_BYTES


_step_traced = False


def expect_step_program() -> None:
    """The program being traced is a step program: the next programs
    the listener meets are held in preference to any other (called by
    ``train.build_train_step``'s step while it is traced; nothing runs
    a step). What is loaded already is met first, unmarked: a listener
    installed after a trainer's set-up programs would otherwise meet
    them together with the step program and mark them all."""
    global _step_traced
    _step_traced = False
    _look()
    _step_traced = True


def hold_step_programs() -> None:
    """Hold the programs the listener sees whatever their size (the last
    ``_RECENT`` still): for a process whose large programs are its own
    step programs, alive while it runs (``ServingEngine.__init__``)."""
    global _held_code_bytes
    install_compile_listener()
    _held_code_bytes = None
_recent: Deque["LoadedProgram"] = collections.deque(maxlen=_RECENT)
#: (id(handle), its fingerprint) -> seq, of the LIVE programs
_seen: Dict[Tuple[int, Any], int] = {}
_tables: Dict[int, Any] = {}    # seq -> scope table, of the live programs
_seq = itertools.count(1)


@dataclasses.dataclass
class LoadedProgram:
    """One loaded program: the backend's handle, the order in which the
    listener first saw it, and (``table``) its scope table once
    ``scopes.tables()`` has built it, kept while the program is loaded."""
    handle: Any
    seq: int
    code_bytes: int = 0     # its generated code, as loaded on the device
    step: bool = False      # traced from a train step: the last to go

    @property
    def module(self) -> str:
        return self.handle.hlo_modules()[0].name

    @property
    def table(self) -> Any:
        return _tables.get(self.seq)

    @table.setter
    def table(self, value: Any) -> None:
        _tables[self.seq] = value


def _look(collect: bool = False) -> List[LoadedProgram]:
    """Look at what the backend lists: programs not seen before go into
    ``_recent``; what died is forgotten. A handle is the backend's own
    object, the same one every call, and takes no weak reference; its
    ``id`` may come back as another program's BEFORE a look has noticed
    the death (programs die and compile between two looks), so a program
    is keyed by its ``id`` and its fingerprint together: an ``id`` that
    comes back under another fingerprint is a program not seen before,
    and under the same one the same program compiled again, whose table
    is the dead one's word for word. With ``collect`` -> a record a live
    program."""
    try:
        import jax.extend.backend
        live = jax.extend.backend.get_backend().live_executables()
    except Exception:       # telemetry must never take a compile down
        return []
    global _step_traced
    out = []
    with _lock:
        seqs = {}
        step = _step_traced
        for handle in live:
            key = (id(handle), _fingerprint(handle))
            seq = _seen.get(key)
            if seq is None:
                seq = next(_seq)
                _hold(LoadedProgram(handle, seq, _code_bytes(handle), step))
                _step_traced = False    # the trace's programs are met
            seqs[key] = seq
            if collect:
                out.append(LoadedProgram(handle, seq))
        _seen.clear()
        _seen.update(seqs)
        for gone in set(_tables).difference(seqs.values()):
            del _tables[gone]
    return out


def _fingerprint(handle) -> Any:
    try:
        return handle.fingerprint
    except Exception:       # a backend that gives none: the id alone
        return None


def _code_bytes(handle) -> int:
    try:
        return int(handle.get_compiled_memory_stats()
                   .generated_code_size_in_bytes)
    except Exception:       # a backend that does not say: count it free
        return 0


def _hold(rec: LoadedProgram) -> None:
    """``rec`` into ``_recent``, the oldest out until the held programs'
    code fits the budget, where there is one (with ``_lock`` held):
    the programs that are no step's first, ``rec`` among them, so a
    one-off program never pushes a step program out; then the older
    step programs; never the newest one, whatever its size."""
    budget = _held_code_bytes
    if budget is not None and rec.code_bytes > budget and not rec.step:
        return
    _recent.append(rec)
    while budget is not None \
            and sum(r.code_bytes for r in _recent) > budget:
        may_go = [r for r in _recent if not r.step] \
            or [r for r in _recent if r.step][:-1]
        if not may_go:
            break
        _recent.remove(may_go[0])


def loaded_programs() -> List[LoadedProgram]:
    """Every program the backend has loaded now, oldest first: what the
    process holds itself, and the last ``_RECENT`` the compile listener
    saw (:func:`install_compile_listener`), whoever dropped them since."""
    return sorted(_look(collect=True), key=lambda rec: rec.seq)


def _on_duration(event: str, duration: float, **kw):
    global _count, _traces
    if event in _TRACE_EVENTS:
        with _lock:
            _traces += 1
        return
    if event in _COMPILE_EVENTS or event in _CACHE_READ_EVENTS:
        _look()
    if event in _COMPILE_EVENTS:
        with _lock:
            _count += 1
        _registry.counter(
            "jax_compiles_total",
            "backend compiles observed via jax.monitoring").inc()
        _registry.histogram(
            "jax_compile_seconds",
            "backend compile wall time").observe(duration)


def _on_gc(phase: str, info: Dict[str, Any]):
    """A ``gc.callbacks`` hook: sums the seconds of collections. It runs
    in whichever thread set the collection off, possibly one that holds
    ``_lock``, so it takes no lock: two floats under the interpreter's
    own."""
    global _gc_began, _gc_seconds
    if phase == "start":
        _gc_began = time.monotonic()
    elif _gc_began is not None:
        _gc_seconds += time.monotonic() - _gc_began
        _gc_began = None


def install_compile_listener():
    """Idempotently hook jax.monitoring's compile-duration stream, and
    the garbage collector's callbacks with it.

    Degrades gracefully: if this jax has no (or a renamed) monitoring
    API, detection stays silently off (compile_count() == 0 forever)
    rather than taking down the training loop — telemetry must never
    kill a run. One attempt per process either way."""
    global _installed
    with _lock:
        if _installed:
            return
        _installed = True  # one attempt per process, success or not
    gc.callbacks.append(_on_gc)
    try:
        import jax.monitoring
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
    except Exception as e:
        import warnings
        warnings.warn(
            f"[observability] jax.monitoring unavailable ({e}); "
            "recompile detection disabled", RuntimeWarning)


def compile_count() -> int:
    """Backend compiles observed in this process since the listener was
    installed (0 before :func:`install_compile_listener`)."""
    with _lock:
        return _count


def trace_count() -> int:
    """Functions traced to a jaxpr in this process since the listener was
    installed: every compile begins with one, and so does a call that
    misses ``jit``'s own cache and finds its program compiled already."""
    with _lock:
        return _traces


def gc_seconds() -> float:
    """Seconds the garbage collector has run since the listener was
    installed, every generation and thread together."""
    return _gc_seconds


def shape_signature(feeds: Optional[Dict[str, Any]]) -> str:
    """Stable ``name:dtype[shape]`` signature of a feed dict — the
    retrace warning's 'what changed' half."""
    if not feeds:
        return "<no feeds>"

    def one(v):
        shape = getattr(v, "shape", None)
        dtype = getattr(v, "dtype", None)
        if shape is None:
            return f"{type(v).__name__}"
        ds = getattr(dtype, "name", str(dtype))
        return f"{ds}[{','.join(map(str, shape))}]"

    return " ".join(f"{k}:{one(v)}" for k, v in sorted(feeds.items()))


class RecompileDetector:
    """Per-callsite retrace watcher around the global compile counter.

    Protocol (what Trainer.fit does):
      det = RecompileDetector("train_step")
      ... run step ...
      new = det.check(step=i, feeds=batch)   # compiles since last check
    The first ``warmup`` checks that see compiles are expected (initial
    trace) and counted but not warned about; any later increase fires a
    structured warning via ``log_fn`` and bumps the
    ``<name>_recompiles_total`` counter.
    """

    def __init__(self, name: str = "step",
                 *, warmup: int = 1,
                 registry: Optional[_registry.MetricsRegistry] = None,
                 log_fn: Callable[[str], None] = None):
        install_compile_listener()
        self.name = name
        self.warmup = warmup
        self._reg = registry or _registry.default()
        self._log = log_fn if log_fn is not None else _warn
        self._baseline = compile_count()
        self._last = self._baseline
        self._seen_seq = next(_seq)
        self._checks = 0
        self.compiles_cum = 0     # compiles since construction
        self.recompiles = 0       # compiles after warmup (true retraces)

    def check(self, *, step: Optional[int] = None,
              feeds: Optional[Dict[str, Any]] = None) -> int:
        """Call once per step AFTER the step ran. Returns the number of
        new compiles observed since the previous check."""
        now = compile_count()
        new = now - self._last
        self._last = now
        self._checks += 1
        self.compiles_cum = now - self._baseline
        appeared = []
        if new:         # a step without a compile event does no new work
            with _lock:
                appeared = [p for p in _recent if p.seq > self._seen_seq]
            self._seen_seq = next(_seq)
        if new and self._checks > self.warmup:
            self.recompiles += new
            self._reg.counter(
                f"{self.name}_recompiles_total",
                "post-warmup retraces (shape/dtype drift)").inc(new)
            at = f" step={step}" if step is not None else ""
            self._log(
                f"[observability] RECOMPILATION: fn={self.name}{at} "
                f"new_compiles={new} total_retraces={self.recompiles} "
                f"programs={[p.module for p in appeared]} — "
                f"arg signature: {shape_signature(feeds)} (a mid-training "
                "retrace usually means input shape/dtype drift; pad or "
                "bucket the batch)")
        return new


def _warn(msg: str):
    import warnings
    warnings.warn(msg, RuntimeWarning, stacklevel=3)
