"""The reader of the engine's ``serving.slow_step`` annotations (ISSUE
52): on a hand-built trace, and on a CPU profiler trace of a tiny engine
with one injected stall (a rehearsal's planes, read like the chip's)."""

import importlib
import json
import os
import time

import numpy as np
import pytest

from conftest import BENCH

import common
import trace_reduce as tr

E = tr.Event
slow = importlib.import_module("readers.xplane_idle_in_slow_steps")

# device busy [0,2] [4,5] [9,10]; window [0,10]: idle (2,4), (5,9)
DEVICE = [E("fusion.1", 0.0, 2.0, "fusion"),
          E("ragged_paged_decode.3", 4.0, 5.0, "custom-call"),
          E("copy.2", 9.0, 10.0, "copy")]
STEPS = [E("serving.step#step=6,t_mono_ns=1#", 0.0, 1.0),
         E("serving.step#step=7,t_mono_ns=5#", 1.0, 8.5),
         E("serving.prefill_call#lanes=4,width=2,tokens=96,lanes_live=3#",
           1.2, 1.4),
         E("serving.decode.sync", 3.5, 5.5),
         E("serving.step#step=8,t_mono_ns=9#", 8.6, 9.5)]
FLAG = E("serving.slow_step#step=7,part=decode.sync,excess_us=5400000#",
         8.5, 8.5)


def _pairs(events):
    """As ``load_spans`` hands them over: names split from attributes."""
    out = []
    for ev in events:
        base, attrs = slow.parse(ev.name)
        out.append((E(base, ev.start, ev.end), attrs))
    return out


def test_attributes_come_back_inside_the_name():
    assert slow.parse(FLAG.name) == ("serving.slow_step", {
        "step": "7", "part": "decode.sync", "excess_us": "5400000"})
    assert slow.parse("serving.decode.sync") == ("serving.decode.sync", {})


def test_idle_inside_the_flagged_step_only():
    idle_s, found = slow.slow_steps(DEVICE, _pairs(STEPS + [FLAG]), 0.0, 10.0)
    # step 7 is [1, 8.5]: idle (2,4) and (5,8.5)
    assert idle_s == pytest.approx(5.5)
    (f,) = found
    assert f["step"] == 7 and f["part"] == "decode.sync"
    assert f["wall_ms"] == pytest.approx(7500.0)
    assert f["busy_pct"] == pytest.approx(100 * 2.0 / 7.5)
    # the operations inside it, cut to it: fusion.1's last second too
    assert sorted(f["ops"]) == [("fusion", pytest.approx(1000.0)),
                                ("ragged_paged_decode", pytest.approx(1000.0))]
    assert f["calls"] == [("3", "2", "96")]
    text = slow.note(found)
    assert "step 7 wall 7500.00 ms part decode.sync excess_us 5400000" in text
    assert "1 prefill calls (lanes_live/width/tokens) 3/2/96" in text
    # a window that cuts the step cuts its idle time with it
    idle_s, _ = slow.slow_steps(DEVICE, _pairs(STEPS + [FLAG]), 3.0, 6.0)
    assert idle_s == pytest.approx(2.0)            # (3,4) + (5,6)
    # a flag whose step lies outside the trace is left out
    late = E("serving.slow_step#step=99,part=caller.gap,excess_us=1#", 9, 9)
    assert slow.slow_steps(DEVICE, _pairs(STEPS + [late]), 0.0, 10.0) == (0.0, [])


def test_a_slow_caller_stood_still_before_the_step():
    """``caller.gap``: the interval opens ``excess_us`` before the span,
    and one that opens before the window is the profiler's own start."""
    gap = E("serving.slow_step#step=8,part=caller.gap,excess_us=3000000#",
            9.5, 9.5)
    idle_s, (f,) = slow.slow_steps(DEVICE, _pairs(STEPS + [gap]), 0.0, 10.0)
    # step 8 is [8.6, 9.5]; with its gap [5.6, 9.5]: idle (5.6, 9)
    assert idle_s == pytest.approx(3.4)
    assert f["part"] == "caller.gap" and f["wall_ms"] == pytest.approx(3900.0)
    assert f["busy_pct"] == pytest.approx(100 * 0.5 / 3.9)
    assert slow.slow_steps(DEVICE, _pairs(STEPS + [gap]), 6.0, 10.0) \
        == (0.0, [])
    # a step flagged under a part inside it is clipped, not dropped
    idle_s, _ = slow.slow_steps(DEVICE, _pairs(STEPS + [FLAG]), 6.0, 10.0)
    assert idle_s == pytest.approx(2.5)


def test_no_flag_reads_zero_and_no_step_span_reads_nothing():
    assert slow.slow_steps(DEVICE, _pairs(STEPS), 0.0, 10.0) == (0.0, [])
    assert slow.note([]) == "slow steps in the traced part: none"
    assert slow.slow_steps(DEVICE, _pairs([STEPS[3]]), 0.0, 10.0) is None
    assert slow.slow_steps(DEVICE, [], 0.0, 10.0) is None


class _Run:
    registry_delta = {
        'serving_slow_steps_total{part="sync",phase="decode"}': 2.0,
        'serving_slow_steps_total{part="gap",phase="caller"}': 1.0,
        'serving_slow_steps_total{part="book",phase="decode"}': 0.0,
        'serving_slow_step_excess_seconds_total{part="sync",phase="decode"}':
            0.2204,
        'serving_slow_step_excess_seconds_total{part="gap",phase="caller"}':
            0.5,
        "serving_step_traces_total": 3.0,
        "serving_step_gc_seconds_total": 0.0125,
        "serving_steps_total": 1400.0,
        "serving_step_seconds_total": 20.0,
    }

    def __init__(self, trace=True):
        self.trace = object() if trace else None
        self.notes = []


def test_read_gives_a_share_of_the_window_and_one_note(monkeypatch, tmp_path):
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(b"")
    monkeypatch.setattr(tr, "find_xplane", lambda d: str(path))
    window = [E(common.Profiler.WINDOW_SPAN, 0.0, 10.0)]
    spans = {"flagged": STEPS + [FLAG], "sound": STEPS, "parent": [STEPS[3]]}
    which = ["flagged"]
    monkeypatch.setattr(slow.under, "_load",
                        lambda p, prefix: ([DEVICE], window))
    monkeypatch.setattr(slow, "load_spans",
                        lambda p: _pairs(spans[which[0]]))
    run = _Run()
    got = {}
    for which[0] in spans:
        slow.idle_in_slow_steps.cache_clear()
        got[which[0]] = slow.read({}, run)
        slow.read({}, run)                  # a second metric, the same note
    assert got == {"flagged": pytest.approx(55.0), "sound": 0.0,
                   "parent": None}
    assert len(run.notes) == 2 and "step 7 wall" in run.notes[0]
    assert run.notes[1].endswith("none")
    # the note opens with the engine's counters over the whole window
    assert run.notes[0].startswith(
        "slow steps over the window: caller.gap=1 (500.0 ms over) "
        "decode.sync=2 (220.4 ms over); traces 3, gc 12.50 ms inside 1400 "
        "steps. Slow steps in the traced part: step 7")
    # the share of step time leaves the caller's gap out
    ratio = importlib.import_module("readers.registry_counter_sum_ratio")
    with open(os.path.join(BENCH, "layer_metrics",
                           "engine.slow_step_time_pct.json")) as f:
        assert ratio.read(json.load(f)["params"], run) == \
            pytest.approx(100 * 0.2204 / 20.0)
    assert slow.read({}, _Run(trace=False)) is None
    slow.idle_in_slow_steps.cache_clear()
    with open(os.path.join(BENCH, "layer_metrics",
                           "device.idle_slow_step_pct.json")) as f:
        assert json.load(f)["reader"] == "xplane_idle_in_slow_steps"


def test_a_profiler_trace_of_a_tiny_engine_with_one_stall(monkeypatch,
                                                          tmp_path):
    """What a rehearsal's trace holds (no TPU plane: the sibling reader's
    fallback): the engine's own annotation and ``serving.step`` joined by
    number, the device idle through the injected wait."""
    import jax
    jax.config.update("jax_platforms", "cpu")
    from paddle_tpu import observability as obs
    from paddle_tpu import serving
    from paddle_tpu.models.gpt import GPT, GPTConfig
    cfg = GPTConfig.tiny(vocab_size=64, hidden_size=16, num_layers=2,
                         num_heads=2, ffn_size=32, max_position=96,
                         dropout=0.0, attn_impl="xla")
    model = GPT(cfg)
    eng = serving.ServingEngine(
        model, model.init(jax.random.PRNGKey(0)), attn_impl="lax",
        registry=obs.MetricsRegistry(), num_slots=4, page_size=4,
        max_tokens_per_slot=24, prefill_chunk=4, prefill_budget=16,
        decode_block=2)
    eng.warmup()
    rng = np.random.default_rng(0)

    def serve():
        eng.generate_many([rng.integers(1, 64, n).astype(np.int32)
                           for n in (5, 9, 7)], 10, eos_id=None)
    for _ in range(20):
        serve()
    monkeypatch.setattr(common, "TRACE_DIR", str(tmp_path / "trace"))
    real, calls = eng._read_back, []

    def slept(*a, **kw):
        calls.append(1)
        if len(calls) == 3:
            time.sleep(0.2)
        return real(*a, **kw)
    prof = common.Profiler(rehearse=True)
    prof.start()
    eng._read_back = slept
    t0 = time.perf_counter()
    serve()
    window_s = time.perf_counter() - t0
    prof.stop()
    flagged = [r for r in eng.anatomy.records()
               if r.get("slow") and r["parts"].get("decode.sync", 0) >= 0.2]
    assert len(flagged) == 1
    slow.idle_in_slow_steps.cache_clear()
    run = _Run()
    got = slow.read({}, run)
    slow.idle_in_slow_steps.cache_clear()
    # the device stood still through the 0.2 s the host slept
    assert 100 * 0.15 / window_s < got <= 100.0
    (text,) = run.notes
    assert text.startswith("slow steps over the window: ")
    assert f"step {flagged[0]['step']} wall" in text
    assert "part decode.sync" in text
    assert f"excess_us {int(flagged[0]['excess_s'] * 1e6)}" in text
