"""What both runners share: the cell as loaded from its data files, the
log, the compile watch, the profiler window and the device facts."""

from __future__ import annotations

import contextlib
import dataclasses
import json
import os
import shutil
import time
from typing import Any, Dict, List, Optional

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))


def log(msg: str) -> None:
    print(f"[bench] {msg}", flush=True)


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    """One run of one cell: its data files and the command's arguments."""
    name: str
    chips: int
    config: dict            # configs/<config>.json
    traffic: dict           # traffic/<traffic>.json
    seed: int
    seconds: float
    trace: bool
    rehearse: bool          # tiny sizes on the CPU, kernels interpreted
    t_start: float          # perf_counter() at process start
    devices: list = dataclasses.field(default_factory=list)
    sweep: Optional[list] = None        # [(rate, seconds or None), ...]
    watch: Any = None                   # CompileWatch
    survey_path: Optional[str] = None   # where a traced run keeps a survey

    @property
    def seed32(self) -> int:
        """``--seed`` folded into what a 32-bit PRNG key takes."""
        return self.seed % 2147483647

    def sizes(self) -> dict:
        """The model's sizes: as published, or the rehearsal's tiny ones."""
        sizes = dict(self.config["sizes"])
        if self.rehearse:
            sizes.update(self.config["rehearsal"]["sizes"])
        return sizes

    def job(self) -> dict:
        """The traffic file, with its rehearsal overrides when rehearsing."""
        job = dict(self.traffic)
        if self.rehearse:
            job.update(self.traffic.get("rehearsal", {}))
        return job


@dataclasses.dataclass
class RunResult:
    """What a runner hands back; the readers take per-layer metrics
    from it and ``run.py`` prints the contract's last line from it."""
    correct: bool
    attempted: int
    failed: int
    values: Dict[str, float]                    # end-to-end metrics + facts
    registry_delta: Dict[str, float] = dataclasses.field(default_factory=dict)
    request_stats: List[dict] = dataclasses.field(default_factory=list)
    trace: Any = None                           # trace_reduce.TraceSummary
    notes: List[str] = dataclasses.field(default_factory=list)


# -- what JAX itself reports while it compiles (copied from chip_smoke.py) --

_EVENT_KEYS = {
    "/jax/compilation_cache/cache_hits": "hits",
    "/jax/compilation_cache/cache_misses": "misses",
    "/jax/core/compile/jaxpr_trace_duration": "trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
    "/jax/core/compile/backend_compile_duration": "backend_compile_s",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_read_s",
}


class CompileWatch:
    """Sums JAX's own compile events over the process: persistent-cache
    hits and misses, seconds tracing, lowering, compiling, reading."""

    def __init__(self):
        import jax.monitoring
        self.stats = {v: 0.0 for v in _EVENT_KEYS.values()}
        self.n_backend = 0      # backend compiles, cache loads included
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)

    def _on_event(self, event, **kw):
        if event in _EVENT_KEYS:
            self.stats[_EVENT_KEYS[event]] += 1

    def _on_duration(self, event, seconds, **kw):
        if event in _EVENT_KEYS:
            self.stats[_EVENT_KEYS[event]] += seconds
            self.n_backend += _EVENT_KEYS[event] == "backend_compile_s"

    def compiles(self) -> int:
        """Programs compiled or loaded from the cache so far: either
        inside the measured window is a fault."""
        return self.n_backend

    def line(self) -> str:
        return json.dumps({k: round(v, 1) for k, v in self.stats.items()})


# -- the profiler window -----------------------------------------------------

TRACE_DIR = os.path.join(BENCH_DIR, ".trace")


class Profiler:
    """``start()`` .. ``stop()`` around the last seconds of the window;
    ``summary()`` reduces the file afterwards, outside the window."""

    WINDOW_SPAN = "bench.traced_window"

    def __init__(self, rehearse: bool = False):
        self._ctx = None
        self._rehearse = rehearse

    def start(self):
        import jax.profiler
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0        # no per-call Python events
        opts.host_tracer_level = 2
        jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
        self._ctx = jax.profiler.TraceAnnotation(self.WINDOW_SPAN)
        self._ctx.__enter__()

    def stop(self):
        import jax.profiler
        self._ctx.__exit__(None, None, None)
        jax.profiler.stop_trace()

    def summary(self, n_devices: int, keep_survey: Optional[str] = None):
        import trace_reduce
        path = trace_reduce.find_xplane(TRACE_DIR)
        kw = dict(device_plane=trace_reduce.REHEARSAL_PLANE,
                  op_line=trace_reduce.REHEARSAL_OP_LINE) \
            if self._rehearse else {}
        devices, host = trace_reduce.load(path, **kw)
        if keep_survey:
            os.makedirs(os.path.dirname(keep_survey), exist_ok=True)
            with open(keep_survey, "w") as f:
                f.write(trace_reduce.describe(path))
        window = [ev for ev in host if ev.name == self.WINDOW_SPAN]
        if len(window) != 1:
            raise RuntimeError(f"expected one {self.WINDOW_SPAN} span in the "
                               f"trace, found {len(window)}")
        spans = [ev for ev in host if ev.name != self.WINDOW_SPAN]
        return trace_reduce.summarize(devices[:n_devices], spans,
                                      window[0].start, window[0].end)


@contextlib.contextmanager
def span(name: str, on: bool):
    """A host span on the profiler's clock, when tracing."""
    if not on:
        yield
        return
    import jax.profiler
    with jax.profiler.TraceAnnotation(name):
        yield


def annotate_methods(obj, names: Dict[str, str]) -> None:
    """Wrap ``obj.<attr>`` in a host span for a traced run, from the
    benchmark's side (the program's own spans are a later change).
    ``names`` maps attribute path -> span name; a path the program no
    longer has raises, so that a traced run never reports idle gaps
    with a phase silently missing."""
    import jax.profiler
    for path, label in names.items():
        owner = obj
        *head, attr = path.split(".")
        for h in head:
            owner = getattr(owner, h)
        fn = getattr(owner, attr)

        def wrapped(*a, _fn=fn, _label=label, **kw):
            with jax.profiler.TraceAnnotation(_label):
                return _fn(*a, **kw)
        setattr(owner, attr, wrapped)


def memory_peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest of ``devices``."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


def live_bytes(devices) -> int:
    """Bytes in use right now on the fullest of ``devices``."""
    return max(int((d.memory_stats() or {}).get("bytes_in_use", 0))
               for d in devices)


def percentile(values, q: float) -> float:
    """The ``q``-quantile (0..1) by linear interpolation."""
    import numpy as np
    return float(np.quantile(np.asarray(values, dtype=np.float64), q))


def now() -> float:
    return time.perf_counter()
