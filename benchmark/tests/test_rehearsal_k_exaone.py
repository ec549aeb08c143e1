"""The mixed backlog cell PR 40 added, rehearsed from ``BENCHMARK.json`` as
it stands: ``run.py --rehearse`` at tiny sizes on the CPU, kernels
interpreted.

The cell goes through ``runners/serve_lm.py`` and ``families/k_exaone.py``:
window and full attention layers in one engine (window 8, page 4: a ring
of 3 pages a slot), 2 of 8 experts held beside a shared one, both paged
kernels and the grouped expert kernel on their Pallas bodies, the blocked
float32 reference given the same share, the two counter metrics this PR
adds, and the accepted metrics whose lists the cell joined."""

import json
import os
import subprocess
import sys

from conftest import BENCH, ROOT

ENV = dict(os.environ, JAX_PLATFORMS="cpu")
CELL = "k_exaone_236b_a23b.serve_mixed_backlog"
NEW_FILES = ("cache.window_resident_pct.mixed", "moe.held_pairs_pct.mixed")
#: accepted metrics whose readers find their layers in this cell too: the
#: cell is appended to their ``workloads`` (the two rooflines need the
#: chip's peaks and are never made up here)
SHARED = ("device.idle_pct.backlog", "kernel.moe_time_pct.docs",
          "kernel.paged_attn_time_pct.backlog",
          "moe.experts_touched_pct.docs", "engine.decode_block_ms",
          "engine.host_share_pct", "engine.decode_host_ms",
          "engine.prefill_host_ms", "engine.readbacks_per_step",
          "engine.overlapped_blocks_pct", "device.idle_call_pct.backlog",
          "device.idle_book_pct.backlog", "device.idle_sched_pct.backlog")


def _line(trace, seconds="3"):
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seed", "4000000019", "--seconds", seconds, "--trace", trace,
         "--rehearse"], cwd=ROOT, env=ENV, capture_output=True, text=True,
        timeout=1500)
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1]), p.stdout


def test_mixed_backlog_untraced_reports_its_end_to_end_metrics():
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


def test_mixed_backlog_traced_reads_every_metric_of_the_cell():
    line, out = _line("1", seconds="4")
    assert line["correct"] is True
    m = line["metrics"]
    assert set(NEW_FILES) | set(SHARED) <= set(m)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        listed = {e["name"] for e in json.load(f)["per_layer"]
                  if CELL in e.get("workloads", ())}
    # every metric the cell is listed under reports, but the two
    # rooflines (the chip's peaks) and the scopes of a device trace
    assert listed - set(m) <= {
        "kernel.paged_decode_roofline", "kernel.moe_ffn_roofline",
        "serve_step.attend_xla_time_pct", "serve_step.attn_in_time_pct",
        "serve_step.ffn_time_pct", "serve_step.head_time_pct",
        "serve_step.unscoped_time_pct"}
    assert m["engine.readbacks_per_step"]["value"] <= 1.0
    # 4 window layers of 5 with a ring of 3 pages of 4 tokens: under 80,
    # which every layer paged alike would read once slots pass 12 tokens
    assert 0 < m["cache.window_resident_pct.mixed"]["value"] < 80
    # 2 of 8 experts held: 25 for an even router; 20 tokens a step
    assert 5 < m["moe.held_pairs_pct.mixed"]["value"] < 50
    assert 0 < m["moe.experts_touched_pct.docs"]["value"] <= 100
    counters = json.loads(out.split("program counters over the window: ")[1]
                          .splitlines()[0].replace("'", '"'))
    assert counters["serving_moe_routed_pairs_total"] \
        > counters["serving_moe_assignments_total"] > 0


def test_new_counter_metrics_read_nothing_where_the_program_feeds_none():
    """The two metric files over counters this PR adds, on a program
    without them (the parent, or a family with one kind of layer that
    holds every expert): nothing, no raise."""
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
        params("moe.held_pairs_pct.mixed"), Run) is None
    assert registry_counter_share.read(
        params("cache.window_resident_pct.mixed"), Run) is None
    Run.registry_delta = {
        "serving_moe_assignments_total": 16.0,
        "serving_moe_routed_pairs_total": 128.0,
        'serving_kv_resident_bytes_total{layers="window"}': 30.0,
        'serving_kv_resident_bytes_total{layers="full"}': 70.0}
    assert registry_counter_ratio.read(
        params("moe.held_pairs_pct.mixed"), Run) == 12.5
    assert registry_counter_share.read(
        params("cache.window_resident_pct.mixed"), Run) == 30.0
