"""The long-prompt backlog cell PR 49 added, rehearsed from
``BENCHMARK.json`` as it stands: ``run.py --rehearse`` at tiny sizes on the
CPU, kernels interpreted.

The cell goes through ``runners/serve_lm.py`` and
``families/mimo_v2_flash.py``: full layers of 1 KV head beside window
layers of 2 in one engine, keys of 24 beside values of 16, the rotary
embedding on 8 of 24 entries, a sink a head in the window layers' softmax
(window 8, page 4: a ring of 3 pages a slot), 2 of 8 experts held and no
shared one, both paged kernels and the grouped expert kernel on their
Pallas bodies, the blocked float32 reference given the same share, the
counter metrics this PR adds, and the accepted metrics whose lists the
cell joined."""

import json
import os
import subprocess
import sys

from conftest import BENCH, ROOT

ENV = dict(os.environ, JAX_PLATFORMS="cpu")
CELL = "mimo_v2_flash.serve_long_prompt_backlog"
NEW_FILES = ("kernel.paged_prefill_roofline",
             "kernel.paged_prefill_time_pct.long",
             "attn.prefill_full_pairs_pct.long", "attn.sink_rows_pct.long")
#: accepted metrics whose readers find their layers in this cell too: the
#: cell is appended to their ``workloads`` (the rooflines need the chip's
#: peaks and are never made up here)
SHARED = ("device.idle_pct.backlog", "kernel.moe_time_pct.docs",
          "kernel.paged_attn_time_pct.backlog",
          "moe.experts_touched_pct.docs", "moe.held_pairs_pct.mixed",
          "cache.window_resident_pct.mixed", "engine.decode_block_ms",
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


def test_long_prompt_backlog_untraced_reports_its_end_to_end_metrics():
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


def test_long_prompt_backlog_traced_reads_every_metric_of_the_cell():
    line, out = _line("1", seconds="4")
    assert line["correct"] is True
    m = line["metrics"]
    assert set(NEW_FILES) - {"kernel.paged_prefill_roofline"} \
        | set(SHARED) <= set(m)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        listed = {e["name"] for e in json.load(f)["per_layer"]
                  if CELL in e.get("workloads", ())}
    assert set(NEW_FILES) <= listed
    # every metric the cell is listed under reports, but the three
    # rooflines (the chip's peaks) and the scopes of a device trace
    assert listed - set(m) <= {
        "kernel.paged_decode_roofline", "kernel.moe_ffn_roofline",
        "kernel.paged_prefill_roofline",
        "serve_step.attend_xla_time_pct", "serve_step.attn_in_time_pct",
        "serve_step.ffn_time_pct", "serve_step.head_time_pct",
        "serve_step.unscoped_time_pct"}
    assert m["engine.readbacks_per_step"]["value"] <= 1.0
    # 5 layers of 7 carry a sink, whatever the traffic
    assert abs(m["attn.sink_rows_pct.long"]["value"] - 500 / 7) < 1e-9
    # 2 full layers score every token before a query (28-40 of them), 5
    # window layers its last 8
    assert 40 < m["attn.prefill_full_pairs_pct.long"]["value"] < 70
    # 5 window layers of 7 with a ring of 3 pages of 4 tokens, twice the
    # KV heads of a full layer: under 5 x 2 / (5 x 2 + 2) = 83.3, which
    # every layer paged alike would read once slots pass 12 tokens
    assert 0 < m["cache.window_resident_pct.mixed"]["value"] < 83.3
    # 2 of 8 experts held: 25 for an even router
    assert 5 < m["moe.held_pairs_pct.mixed"]["value"] < 50
    assert 0 < m["moe.experts_touched_pct.docs"]["value"] <= 100
    counters = json.loads(out.split("program counters over the window: ")[1]
                          .splitlines()[0].replace("'", '"'))
    assert counters["serving_attn_rows_total"] * 5 \
        == counters["serving_attn_sink_rows_total"] * 7


def test_new_metric_files_read_nothing_where_the_program_feeds_none():
    """The metric files over counters this PR adds, on a program without
    them (the parent, or a family without sinks whose pool is not split by
    kind): nothing, no raise; and ``kernel_needs`` leaves the prefill
    needs out, so the new roofline reads nothing too."""
    sys.path.insert(0, BENCH)
    from families import mimo_v2_flash
    from readers import (registry_counter_ratio, registry_counter_share,
                         xplane_roofline)

    class Run:
        registry_delta = {'serving_decode_kv_bytes_total{kind="live"}': 5.0,
                          "serving_moe_assignments_total": 7.0}
        values = {}
        notes = []

        class trace:
            @staticmethod
            def seconds_matching(patterns, opcode=None):
                return 1.0

    def params(name):
        with open(os.path.join(BENCH, "layer_metrics",
                               name + ".json")) as f:
            return json.load(f)["params"]

    assert registry_counter_ratio.read(
        params("attn.sink_rows_pct.long"), Run) is None
    assert registry_counter_share.read(
        params("attn.prefill_full_pairs_pct.long"), Run) is None
    sizes = dict(hidden_size=64, moe_intermediate_size=32, head_dim=24,
                 v_head_dim=16, num_attention_heads=4,
                 num_key_value_heads=1, swa_num_key_value_heads=2)
    Run.values = mimo_v2_flash.kernel_needs(sizes, 2, 7, Run.registry_delta,
                                            0.0, 0.0)
    assert xplane_roofline.read(
        params("kernel.paged_prefill_roofline"), Run) is None
    Run.registry_delta = {
        "serving_attn_sink_rows_total": 50.0, "serving_attn_rows_total": 70.0,
        'serving_prefill_attn_pairs_total{layers="full"}': 30.0,
        'serving_prefill_attn_pairs_total{layers="window"}': 10.0}
    assert abs(registry_counter_ratio.read(
        params("attn.sink_rows_pct.long"), Run) - 500 / 7) < 1e-9
    assert registry_counter_share.read(
        params("attn.prefill_full_pairs_pct.long"), Run) == 75.0
