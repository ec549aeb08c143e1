"""The reasoning backlog cell PR 35 added, rehearsed from ``BENCHMARK.json``
as it stands: ``run.py --rehearse`` at tiny sizes on the CPU, kernels
interpreted.

The cell goes through ``runners/serve_lm.py`` and ``families/zaya.py``:
nothing published, nothing shared, both paged kernels and the grouped
expert kernel on their Pallas bodies, the blocked float32 reference, the
tail and tile counters read by the metric files this PR adds, and
the accepted metrics of the same layers (idle, the two kernels' shares,
experts touched) reporting in this cell too."""

import json
import os
import subprocess
import sys

from conftest import BENCH, ROOT

ENV = dict(os.environ, JAX_PLATFORMS="cpu")
CELL = "zaya1_8b.serve_reasoning_backlog"
NEW_FILES = ("moe.hottest_expert_share_pct.zaya", "moe.tile_fill_pct.zaya",
             "cca.tail_share_pct.zaya")
#: accepted metrics whose readers find their layers in this cell too: the
#: cell is appended to their ``workloads``
SHARED = ("device.idle_pct.backlog", "kernel.moe_time_pct.docs",
          "kernel.paged_attn_time_pct.backlog",
          "moe.experts_touched_pct.docs")


def _line(trace, seconds="3"):
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seed", "3500000019", "--seconds", seconds, "--trace", trace,
         "--rehearse"], cwd=ROOT, env=ENV, capture_output=True, text=True,
        timeout=900)
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1]), p.stdout


def test_reasoning_backlog_untraced_reports_its_end_to_end_metrics():
    line, out = _line("0")
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"serve_tokens_per_s", "tpot_p95_ms",
                                    "setup_s"}
    for kernel in ("ragged_paged_prefill", "ragged_paged_decode",
                   "moe_grouped_ffn"):
        assert f"'{kernel}[lax]': 0" in out
        assert f"'{kernel}[pallas_interpret]': 0" not in out
    assert "compiles in the window 0" in out
    assert "published" not in out


def test_reasoning_backlog_traced_reads_every_new_metric_file():
    line, _ = _line("1", seconds="4")
    assert line["correct"] is True
    m = line["metrics"]
    # the two rooflines need the chip's peaks and are never made up here
    assert {"engine.decode_block_ms", "engine.host_share_pct",
            "engine.decode_host_ms", "engine.prefill_host_ms",
            "engine.readbacks_per_step", "engine.overlapped_blocks_pct",
            "device.idle_call_pct.backlog", "device.idle_book_pct.backlog",
            "device.idle_sched_pct.backlog"} | set(NEW_FILES) \
        | set(SHARED) <= set(m)
    assert m["engine.readbacks_per_step"]["value"] <= 1.0
    # one expert a token over 8 experts, 4 slots: a tile of 16 rows holds
    # a token or two
    assert 0 < m["moe.tile_fill_pct.zaya"]["value"] < 50
    assert 12.5 <= m["moe.hottest_expert_share_pct.zaya"]["value"] <= 100
    assert 0 < m["moe.experts_touched_pct.docs"]["value"] <= 100
    assert 0 < m["cca.tail_share_pct.zaya"]["value"] < 100


def test_new_counter_metrics_read_nothing_where_the_program_feeds_none():
    """The metric files over counters this PR adds, on a program without
    them (the parent, or a family that counts no tile rows and keeps no
    slot state): nothing, no raise."""
    sys.path.insert(0, BENCH)
    from readers import registry_counter_ratio, registry_counter_share

    class Run:
        registry_delta = {'serving_decode_kv_bytes_total{kind="live"}': 5.0,
                          "serving_moe_assignments_total": 7.0}

    def params(name):
        with open(os.path.join(BENCH, "layer_metrics",
                               name + ".json")) as f:
            return json.load(f)["params"]

    assert registry_counter_ratio.read(
        params("moe.tile_fill_pct.zaya"), Run) is None
    assert registry_counter_share.read(
        params("cca.tail_share_pct.zaya"), Run) is None
    Run.registry_delta = {
        "serving_moe_assignments_total": 12.0,
        "serving_moe_tile_rows_total": 48.0,
        "serving_moe_max_expert_tokens_total": 3.0,
        'serving_ssm_state_bytes_total{kind="read"}': 1.0,
        'serving_ssm_state_bytes_total{kind="written"}': 2.0,
        'serving_decode_kv_bytes_total{kind="live"}': 97.0}
    assert registry_counter_ratio.read(
        params("moe.tile_fill_pct.zaya"), Run) == 25.0
    assert registry_counter_ratio.read(
        params("moe.hottest_expert_share_pct.zaya"), Run) == 25.0
    assert registry_counter_share.read(
        params("cca.tail_share_pct.zaya"), Run) == 3.0
