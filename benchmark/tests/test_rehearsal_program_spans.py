"""``run.py --rehearse --trace 1`` of the backlog cell prints every
per-layer metric that reads the program's own spans and counters
(ISSUE 25), and the idle split adds up to the device's idle share."""

import json
import os
import re
import subprocess
import sys

import pytest

from conftest import BENCH, ROOT

NEW = ["engine.host_share_pct", "engine.decode_host_ms",
       "engine.prefill_host_ms", "engine.decode_gather_useful_pct",
       "device.idle_call_pct.backlog", "device.idle_book_pct.backlog",
       "device.idle_sched_pct.backlog"]


def test_backlog_rehearsal_prints_the_program_span_metrics():
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "gpt2_small.serve_decode_backlog", "--seed", "3000000021",
         "--seconds", "3", "--trace", "1", "--rehearse"],
        cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    lines = p.stdout.strip().splitlines()
    line = json.loads(lines[-1])
    assert line["correct"] is True and line["device"]["platform"] == "cpu"
    got = {k: v["value"] for k, v in line["metrics"].items()}
    assert set(NEW) <= set(got)
    assert all(got[k] >= 0 for k in NEW)
    assert 0 < got["engine.decode_gather_useful_pct"] <= 100
    assert 0 < got["engine.host_share_pct"] < 100
    # the reader's note carries every span's share, the ones no metric
    # reports too: all of them together are the device's idle time
    (note,) = [ln for ln in lines if "device idle under the program" in ln]
    by_span = {k: float(v) for k, v in re.findall(
        r"(serving\.[\w.]+|\(no host span\)) ([\d.]+)", note)}
    window_ms = line["device"]["window_s"] * 1e3
    assert 100 * sum(by_span.values()) / window_ms == pytest.approx(
        got["device.idle_pct.backlog"], abs=0.02)
    three = sum(got[f"device.idle_{k}_pct.backlog"]
                for k in ("call", "book", "sched"))
    assert three + 100 * by_span.get("(no host span)", 0.0) / window_ms \
        == pytest.approx(got["device.idle_pct.backlog"], abs=0.05)
