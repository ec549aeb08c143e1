"""The readers of the program's own spans and counters (ISSUE 25), on a
hand-built trace and a hand-built registry delta."""

import importlib
import json
import os
import re

import pytest

from conftest import BENCH

import common
import trace_reduce as tr

E = tr.Event
idle = importlib.import_module("readers.xplane_idle_under_span")
ratio = importlib.import_module("readers.registry_counter_sum_ratio")

# device busy [0,2] [4,5] [9,10]; window [0,10]: idle (2,4), (5,9) = 6 s
DEVICE = [E("fusion.1", 0.0, 2.0, "fusion"),
          E("ragged_paged_decode.3", 4.0, 5.0, "custom-call"),
          E("copy.2", 9.0, 10.0, "copy")]
# nested program spans; attributes may ride in the name after "#"
SPANS = [E("serving.step#step=7,t_mono_ns=5#", 1.0, 8.5),
         E("serving.decode_round#width=4#", 1.5, 6.0),
         E("serving.decode.assemble", 2.0, 3.0),
         E("serving.decode.dispatch", 3.0, 3.5),
         E("serving.decode.sync", 3.5, 5.5),
         E("serving.decode.book", 5.5, 6.0),
         E("serving.observe", 6.0, 8.0)]


def _metric(name):
    with open(os.path.join(BENCH, "layer_metrics", name + ".json")) as f:
        return json.load(f)["params"]


def test_gaps_are_shared_among_nested_spans_innermost_first():
    got = idle.split(DEVICE, SPANS, 0.0, 10.0)
    assert got == pytest.approx({
        "serving.decode.assemble": 1.0,      # (2,3)
        "serving.decode.dispatch": 0.5,      # (3,3.5)
        "serving.decode.sync": 1.0,          # (3.5,4) + (5,5.5)
        "serving.decode.book": 0.5,          # (5.5,6)
        "serving.observe": 2.0,              # (6,8)
        "serving.step": 0.5,                 # (8,8.5): the step's self time
        idle.NO_SPAN: 0.5})                  # (8.5,9): outside step()
    assert sum(got.values()) == pytest.approx(6.0)


def test_a_clock_lead_moves_idle_inside_the_call_share_only():
    """A pause as the backlog cell has them: the device stops inside one
    call's .sync and starts inside the next call's .dispatch. With the
    device plane stamped 0.4 early, idle moves from .dispatch to .sync;
    book and observe keep theirs (they lie further than that from either
    end of the pause), and so does the sum the call metric reads."""
    spans = [E("serving.decode.sync", 0.0, 2.0),
             E("serving.decode.book", 2.0, 3.0),
             E("serving.observe", 3.0, 4.0),
             E("serving.decode.assemble", 4.0, 5.0),
             E("serving.decode.dispatch", 5.0, 6.0),
             E("serving.decode.sync", 6.0, 9.0)]
    device = [E("fusion.1", -1.0, 1.5, "fusion"),
              E("fusion.2", 5.5, 8.5, "fusion")]
    early = [E(e.name, e.start - 0.4, e.end - 0.4, e.opcode) for e in device]
    true, got = (idle.split(d, spans, 0.0, 8.0) for d in (device, early))
    call = re.compile(_metric("device.idle_call_pct.backlog")["patterns"][0])

    def share(by_span):
        return sum(t for name, t in by_span.items() if call.match(name))
    assert (true["serving.decode.dispatch"], got["serving.decode.dispatch"]) \
        == pytest.approx((0.5, 0.1))
    assert (true["serving.decode.sync"], got["serving.decode.sync"]) \
        == pytest.approx((0.5, 0.9))
    for name in ("serving.decode.book", "serving.observe"):
        assert got[name] == pytest.approx(true[name]) == pytest.approx(1.0)
    assert share(got) == pytest.approx(share(true)) == pytest.approx(2.0)


class _Run:
    def __init__(self, trace=True):
        self.trace = object() if trace else None
        self.notes = []


def test_metrics_of_the_split_add_up_to_the_idle_share(monkeypatch, tmp_path):
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(b"")
    monkeypatch.setattr(tr, "find_xplane", lambda d: str(path))
    window = [E(common.Profiler.WINDOW_SPAN, 0.0, 10.0)]
    monkeypatch.setattr(idle, "_load", lambda p, prefix: (
        [DEVICE], window if prefix == "bench." else SPANS))
    idle.idle_by_span.cache_clear()
    run = _Run()
    parts = {k: idle.read(_metric(f"device.idle_{k}_pct.backlog"), run)
             for k in ("call", "book", "sched")}
    assert parts == pytest.approx({"call": 25.0, "book": 5.0, "sched": 25.0})
    uncovered = idle.read({"patterns": [r"\(no host span\)$"]}, run)
    assert uncovered == pytest.approx(5.0)
    # device.idle_pct.backlog of the same trace: 6 s of 10
    assert sum(parts.values()) + uncovered == pytest.approx(60.0)
    assert len(run.notes) == 1 and "serving.observe 2000.00" in run.notes[0]
    # untraced run, and a program that has no such spans (the parent)
    assert idle.read(_metric("device.idle_book_pct.backlog"),
                     _Run(trace=False)) is None
    monkeypatch.setattr(idle, "_load", lambda p, prefix: (
        [DEVICE], window if prefix == "bench." else []))
    idle.idle_by_span.cache_clear()
    assert idle.read(_metric("device.idle_book_pct.backlog"), run) is None
    idle.idle_by_span.cache_clear()


PART = 'serving_step_part_seconds_total{part="%s",phase="%s"}'


def test_counter_sums_by_label_over_a_denominator():
    class Run:
        registry_delta = {
            PART % ("assemble", "decode"): 0.010,
            PART % ("dispatch", "decode"): 0.004,
            PART % ("sync", "decode"): 1.600,
            PART % ("book", "decode"): 0.006,
            PART % ("assemble", "prefill"): 0.020,
            PART % ("cow_copy", "prefill"): 0.0,
            PART % ("dispatch", "prefill"): 0.010,
            PART % ("sync", "prefill"): 0.300,
            PART % ("book", "prefill"): 0.010,
            PART % ("book", "sched"): 0.005,
            PART % ("book", "observe"): 0.015,
            "serving_step_seconds_total": 2.0,
            "serving_decode_rounds_total": 10.0,
            "serving_prefill_calls_total": 20.0,
            'serving_decode_kv_bytes_total{kind="live"}': 3.0,
            'serving_decode_kv_bytes_total{kind="gathered"}': 8.0,
        }
    run = Run()
    assert ratio.read(_metric("engine.host_share_pct"), run) == \
        pytest.approx(100 * 0.080 / 2.0)
    assert ratio.read(_metric("engine.decode_host_ms"), run) == \
        pytest.approx(2.0)
    assert ratio.read(_metric("engine.prefill_host_ms"), run) == \
        pytest.approx(2.0)
    useful = importlib.import_module("readers.registry_counter_ratio")
    assert useful.read(_metric("engine.decode_gather_useful_pct"), run) == \
        pytest.approx(37.5)
    # a program without the counters (the parent): nothing to read
    Run.registry_delta = {"serving_tokens_total": 5.0}
    for name in ("engine.host_share_pct", "engine.decode_host_ms",
                 "engine.prefill_host_ms"):
        assert ratio.read(_metric(name), run) is None
    assert useful.read(_metric("engine.decode_gather_useful_pct"), run) is None
