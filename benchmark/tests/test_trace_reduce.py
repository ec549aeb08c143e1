"""The trace reduction on a hand-built event list."""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import trace_reduce as tr  # noqa: E402

E = tr.Event


def test_merge_subtract():
    assert tr.merge([(0, 2), (1, 3), (5, 6), (6, 6)]) == [(0, 3), (5, 6)]
    assert tr.subtract([(0, 10)], [(1, 2), (4, 12)]) == [(0, 1), (2, 4)]
    assert tr.subtract([(0, 1), (2, 3)], []) == [(0, 1), (2, 3)]


def test_busy_idle_overlap_and_gap():
    # two overlapping ops, a gap, one more op; window [0, 10]
    evs = [E("fusion.1", 1.0, 3.0), E("fusion.2", 2.0, 4.0),
           E("ragged_paged_decode.7", 6.0, 7.0, "custom-call")]
    d = tr.reduce_device(evs, 0.0, 10.0)
    assert d.busy_s == pytest.approx(4.0)
    assert d.gaps == [(0.0, 1.0), (4.0, 6.0), (7.0, 10.0)]
    # clipped to a narrower window
    d = tr.reduce_device(evs, 2.5, 6.5)
    assert d.busy_s == pytest.approx(1.5 + 0.5)


def test_self_time_of_nested_while():
    evs = [E("while.1", 0.0, 10.0), E("fusion.1", 1.0, 4.0),
           E("custom-call.2", 5.0, 9.0)]
    d = tr.reduce_device(evs, 0.0, 10.0)
    assert d.busy_s == pytest.approx(10.0)
    assert d.by_name["while"] == pytest.approx(3.0)
    assert d.by_name["fusion"] == pytest.approx(3.0)
    assert sum(d.by_name.values()) == pytest.approx(d.busy_s)


def test_collective_hidden_and_exposed():
    # one all-reduce wholly under compute, one with half of it exposed
    evs = [E("fusion.1", 0.0, 4.0), E("all-reduce.1", 1.0, 2.0),
           E("all-reduce.2", 5.0, 7.0), E("fusion.2", 6.0, 8.0)]
    d = tr.reduce_device(evs, 0.0, 10.0)
    assert d.collective_s == pytest.approx(3.0)
    assert d.collective_exposed_s == pytest.approx(1.0)


def test_summary_patterns_and_gap_attribution():
    dev = [E("fusion.1", 0.0, 2.0, "fusion"),
           E("ragged_paged_decode.3", 4.0, 5.0, "custom-call")]
    host = [E("bench.step", 1.5, 4.5), E("bench.decode_round", 2.5, 3.5)]
    s = tr.summarize([dev, dev], host, 0.0, 6.0)
    assert s.n_devices == 2 and s.window_s == 6.0
    assert s.busy_s == pytest.approx(3.0)
    assert s.seconds_matching(["ragged_paged_decode$"]) == pytest.approx(1.0)
    assert s.seconds_matching(["ragged_paged"], "fusion") == 0.0
    assert s.seconds_matching(["fusion$"]) == pytest.approx(2.0)
    # gaps: (2,4) and (5,6). (2,4): decode_round 1.0, step the other 1.0;
    # (5,6): no span
    assert s.idle_by_host["bench.decode_round"] == pytest.approx(1.0)
    assert s.idle_by_host["bench.step"] == pytest.approx(1.0)
    assert s.idle_by_host["(no host span)"] == pytest.approx(1.0)
    assert s.top_ops(1)[0][0] == "fusion"


def test_parse_op_takes_the_instructions_own_name():
    text = ("%fusion.7 = (bf16[48,512]{1,0:T(8,128)(2,1)S(1)}, f32[8]{0}) "
            "fusion(bf16[64,12,1,64]{3,2,1,0:T(2,128)(2,1)} "
            "%ragged_paged_decode.141), kind=kLoop")
    assert tr.parse_op(text) == ("fusion.7", "fusion")
    text = ("%ragged_paged_decode.141 = bf16[64,12,1,64]{3,2,1,0:T(2,128)(2,1)"
            "S(1)} custom-call(s32[64,4]{1,0:T(8,128)S(1)} %get-tuple-element.6),"
            " custom_call_target=\"tpu_custom_call\"")
    assert tr.parse_op(text) == ("ragged_paged_decode.141", "custom-call")
    assert tr.parse_op("ThunkExecutor::Execute") == ("ThunkExecutor::Execute", "")
    assert E("all-reduce-start.3", 0, 1).group == "all-reduce-start"
