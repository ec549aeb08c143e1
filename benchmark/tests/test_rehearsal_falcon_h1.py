"""The hybrid chat cell PR 32 added, rehearsed from ``BENCHMARK.json`` as
it stands: ``run.py --rehearse`` at tiny sizes on the CPU, kernels
interpreted.

The cell goes through ``runners/serve_lm.py`` and ``families/falcon_h1.py``:
nothing published, nothing shared, both paged kernels and both state-space
kernels on their Pallas bodies, the blocked float32 reference, the state
counters read."""

import json
import os
import subprocess
import sys

from conftest import BENCH, ROOT

ENV = dict(os.environ, JAX_PLATFORMS="cpu")
CELL = "falcon_h1_34b.serve_chat_backlog"


def _line(trace, seconds="3"):
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seed", "3000000019", "--seconds", seconds, "--trace", trace,
         "--rehearse"], cwd=ROOT, env=ENV, capture_output=True, text=True,
        timeout=900)
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1]), p.stdout


def test_chat_backlog_untraced_reports_its_end_to_end_metrics():
    line, out = _line("0")
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"serve_tokens_per_s", "tpot_p95_ms",
                                    "setup_s"}
    for kernel in ("ragged_paged_prefill", "ragged_paged_decode",
                   "ssd_chunk_scan", "ssm_decode_update"):
        assert f"'{kernel}[lax]': 0" in out
        assert f"'{kernel}[pallas_interpret]': 0" not in out
    assert "compiles in the window 0" in out
    assert "published" not in out


def test_chat_backlog_traced_reads_its_counters_and_kernel_shares():
    line, _ = _line("1", seconds="4")
    assert line["correct"] is True
    m = line["metrics"]
    # the two rooflines need the chip's peaks and are never made up here
    assert {"engine.decode_block_ms", "engine.host_share_pct",
            "engine.decode_host_ms", "engine.prefill_host_ms",
            "engine.readbacks_per_step", "device.idle_call_pct.backlog",
            "device.idle_book_pct.backlog", "device.idle_sched_pct.backlog",
            "kernel.ssm_time_pct.h1", "kernel.paged_attn_time_pct.h1",
            "device.idle_pct.h1", "ssm.state_share_pct.h1"} <= set(m)
    assert m["engine.readbacks_per_step"]["value"] == 1.0
    assert 50 < m["ssm.state_share_pct.h1"]["value"] < 100


def test_state_share_reads_nothing_where_the_program_feeds_no_state():
    """The one reader this PR adds, on a program without the counters
    (the parent, or any family without slot state): nothing, no raise."""
    sys.path.insert(0, BENCH)
    from readers import registry_counter_share

    class Run:
        registry_delta = {'serving_decode_kv_bytes_total{kind="live"}': 5.0}

    with open(os.path.join(BENCH, "layer_metrics",
                           "ssm.state_share_pct.h1.json")) as f:
        params = json.load(f)["params"]
    assert registry_counter_share.read(params, Run) is None
    Run.registry_delta = {
        'serving_ssm_state_bytes_total{kind="read"}': 10.0,
        'serving_ssm_state_bytes_total{kind="written"}': 20.0,
        'serving_decode_kv_bytes_total{kind="live"}': 10.0,
        'serving_decode_kv_bytes_total{kind="gathered"}': 99.0}
    assert registry_counter_share.read(params, Run) == 75.0
