"""``readers/xplane_scope_share`` on a hand-built event list and table,
and a traced CPU rehearsal of a training and a serving cell printing the
by-scope metrics (ISSUE 38)."""

import json
import os
import re
import subprocess
import sys

import pytest

from conftest import BENCH, ROOT

from readers import xplane_scope_share as reader


class _Scope:
    def __init__(self, key, mixed=False):
        self.key, self.mixed = key, mixed


class _Tables:
    """What ``observability.scopes.Tables`` is to the reader: programs by
    module name, each ``{instruction: (scope key, mixed)}``."""

    def __init__(self, programs):
        self.programs = programs        # [(module, {instr: _Scope})]

    def candidates(self, module, seen):
        return [t for m, t in self.programs if m == module
                and all(name in t for name, _sig in seen)]

    @staticmethod
    def find(cands, instr):
        found = [t.get(instr) for t in cands]
        if not found or None in found or len({s.key for s in found}) > 1:
            return None
        return found[0]


def _ev(name, start, end, op="fusion", run="jit_step(1)"):
    return reader.RunEvent(name, start, end, op, None, run)


def test_by_scope_on_a_hand_built_trace():
    decode_w1 = {"while.1": _Scope(""), "fusion.7": _Scope("ffn"),
                 "ragged_paged_decode.3": _Scope("attend"),
                 "sort.2": _Scope("attend"),
                 "fusion.9": _Scope("head", mixed=True)}
    decode_w8 = {"while.1": _Scope(""), "fusion.7": _Scope("attn_in"),
                 "ragged_paged_decode.3": _Scope("attend"),
                 "fusion.8": _Scope("ffn")}
    tabs = _Tables([("jit_step", decode_w1), ("jit_step", decode_w8)])
    events = [
        # execution 1 is the w1 program (fusion.9 is only in it): a while
        # of 10 s holding 2 + 3 + 1 s of body, then the head
        _ev("while.1", 0.0, 10.0, "while"),
        _ev("fusion.7", 1.0, 3.0),
        _ev("ragged_paged_decode.3", 3.0, 6.0, "custom-call"),
        _ev("sort.2", 6.0, 7.0, "sort"),
        _ev("fusion.9", 10.0, 12.0),
        # execution 2 could be either program: fusion.7 is ffn in one and
        # attn_in in the other -> unattributed; the kernel agrees
        _ev("fusion.7", 20.0, 21.0, run="jit_step(2)"),
        _ev("ragged_paged_decode.3", 21.0, 23.0, "custom-call",
            run="jit_step(2)"),
        # an event of a program nobody catalogued, and one in no execution
        _ev("fusion.1", 30.0, 31.0, run="jit_other(3)"),
        _ev("copy.5", 31.0, 32.0, "copy", run=""),
        # outside the window
        _ev("fusion.7", 50.0, 60.0),
    ]
    busy, rows = reader.by_scope([events], tabs, 0.0, 40.0)
    assert busy == pytest.approx(17.0)
    by_key = {}
    for (key, _op, _group, _mixed), t in rows.items():
        by_key[key] = by_key.get(key, 0.0) + t
    assert by_key == pytest.approx({
        "": 4.0, "ffn": 2.0, "attend": 6.0, "head": 2.0,
        reader.UNATTRIBUTED: 3.0})
    assert sum(by_key.values()) == pytest.approx(busy)
    assert sum(t for (_, _, _, mixed), t in rows.items() if mixed) == 2.0

    def sel(**params):
        return reader.select(rows, params)
    assert sel(scopes=["attend"]) == pytest.approx(6.0)
    assert sel(scopes=["attend"], exclude_opcode="custom-call") \
        == pytest.approx(1.0)
    assert sel(scopes=["attend"], exclude_opcode=["custom-call", "sort"]) == 0
    assert sel(scopes=["", "unattributed"]) == pytest.approx(7.0)
    assert sel(scopes=["", "unattributed"], exclude_names=["copy"]) \
        == pytest.approx(6.0)
    assert sel(scopes=["ffn|head"]) == pytest.approx(4.0)
    assert sel(scopes=["f"]) == 0           # the whole key, not a prefix
    note = reader._note(busy, rows)
    assert "attend 35.29" in note and "unattributed 17.65" in note
    assert "(100.00 in all)" in note
    assert "in mixed-scope fusions 11.76" in note

    # two devices: seconds and busy are means over them
    busy2, rows2 = reader.by_scope([events, []], tabs, 0.0, 40.0)
    assert busy2 == pytest.approx(busy / 2)
    assert sum(rows2.values()) == pytest.approx(busy / 2)


def test_overlapping_siblings_leave_their_parent_only_what_is_its_own():
    """A loop body's operations may overlap (an async copy beside a
    fusion): every instant goes to the event that started last, so self
    times add up to busy time. ``trace_reduce.self_times`` leaves the
    ``while`` 3 s too many here."""
    import trace_reduce
    evs = [_ev("while.1", 0.0, 10.0, "while"), _ev("fusion.7", 1.0, 5.0),
           _ev("copy-done.2", 4.0, 8.0, "copy-done"),
           _ev("fusion.8", 7.0, 9.0)]
    got = {ev.name: t for ev, t in reader.self_times(evs)}
    assert got == pytest.approx({"while.1": 2.0, "fusion.7": 3.0,
                                 "copy-done.2": 3.0, "fusion.8": 2.0})
    assert sum(got.values()) == pytest.approx(10.0)
    old = {ev.name: t for ev, t in trace_reduce.self_times(evs)}
    assert old["while.1"] == pytest.approx(6.0)
    assert {k: v for k, v in old.items() if k != "while.1"} \
        == pytest.approx({k: v for k, v in got.items() if k != "while.1"})


TRAIN = ["train_step.mlm_head_time_pct", "train_step.optimizer_time_pct",
         "train_step.unscoped_time_pct"]
SERVE = ["serve_step.attend_xla_time_pct", "serve_step.attn_in_time_pct",
         "serve_step.ffn_time_pct", "serve_step.head_time_pct",
         "serve_step.unscoped_time_pct"]


@pytest.mark.parametrize("cell, new", [
    ("bert_base.pretrain_b48_s512", TRAIN),
    ("gpt2_small.serve_decode_backlog", SERVE)], ids=["train", "serve"])
def test_a_traced_rehearsal_prints_the_by_scope_metrics(cell, new):
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", cell,
         "--seed", "3800000017", "--seconds", "3", "--trace", "1",
         "--rehearse"],
        cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    lines = p.stdout.strip().splitlines()
    line = json.loads(lines[-1])
    assert line["correct"] is True and line["device"]["platform"] == "cpu"
    got = {k: v["value"] for k, v in line["metrics"].items()}
    assert set(new) <= set(got), sorted(got)
    assert all(0 <= got[k] <= 100 for k in new)
    # ONE note with the whole table; every scope of it together is all
    # of the device's busy time
    (note,) = [ln for ln in lines if "device time by scope" in ln]
    in_all = float(re.search(r"\(([\d.]+) in all\)", note).group(1))
    table = note.split("in all): ")[1].split(";")[0]
    shares = {k: float(v) for k, v in re.findall(
        r"(\(no scope\)|[\w/]+) ([\d.]+)", table)}
    assert sum(shares.values()) == pytest.approx(in_all, abs=0.1)
    assert in_all == pytest.approx(100.0, abs=0.05)
    assert shares.get("unattributed", 0.0) < 1.0
    if cell.startswith("bert"):
        assert got["train_step.mlm_head_time_pct"] == pytest.approx(
            shares["forward/mlm_head"] + shares["backward/mlm_head"],
            abs=0.02)
        assert got["train_step.optimizer_time_pct"] == pytest.approx(
            shares["optimizer/"], abs=0.02)
        assert shares["backward/ffn"] > 0 and shares["forward/add_norm"] > 0
    else:
        # the serving metrics, the scopes no metric reads and the kernels
        # the attend metric leaves out are the whole table
        kernels = shares["attend"] - got["serve_step.attend_xla_time_pct"]
        rest = sum(shares.get(k, 0.0) for k in ("embed", "stats"))
        assert sum(got[k] for k in new) + kernels + rest \
            == pytest.approx(in_all, abs=0.15)
        assert got["serve_step.ffn_time_pct"] > 0
        assert got["serve_step.head_time_pct"] > 0
