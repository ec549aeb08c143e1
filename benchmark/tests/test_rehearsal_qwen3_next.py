"""The long-answer cell PR 57 added, rehearsed from ``BENCHMARK.json`` as
it stands: ``run.py --rehearse`` at tiny sizes on the CPU, kernels
interpreted.

The cell goes through ``runners/serve_lm.py`` and ``families/
qwen3_next.py``: nothing published, nothing shared, three state layers to a
full one, both paged kernels, both delta-rule kernels and the grouped
expert kernel on their Pallas bodies, the blocked float32 reference, the
engine's slot-state counters read."""

import json
import os
import subprocess
import sys

from conftest import BENCH, ROOT

ENV = dict(os.environ, JAX_PLATFORMS="cpu")
CELL = "qwen3_next_80b_a3b.serve_long_answer_backlog"
KERNELS = ("ragged_paged_prefill", "ragged_paged_decode", "moe_grouped_ffn",
           "gated_delta_chunk_scan", "gated_delta_decode_update")


def _line(trace, seconds="3"):
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seed", "4000000057", "--seconds", seconds, "--trace", trace,
         "--rehearse"], cwd=ROOT, env=ENV, capture_output=True, text=True,
        timeout=1200)
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1]), p.stdout


def test_long_answer_backlog_untraced_reports_its_end_to_end_metrics():
    line, out = _line("0")
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"serve_tokens_per_s", "tpot_p95_ms",
                                    "setup_s"}
    for kernel in KERNELS:
        assert f"'{kernel}[lax]': 0" in out
        assert f"'{kernel}[pallas_interpret]': 0" not in out
    assert "compiles in the window 0" in out
    assert "published" not in out
    # ONE table width: a decode bucket and the prefill lanes' buckets
    assert "decode widths [16]" in out and "prefill widths [16]" in out


def test_long_answer_backlog_traced_reads_its_counters_and_kernel_shares():
    line, _ = _line("1", seconds="4")
    assert line["correct"] is True
    m = line["metrics"]
    # the four rooflines need the chip's peaks and are never made up here
    assert {"engine.decode_block_ms", "engine.host_share_pct",
            "engine.readbacks_per_step", "device.idle_pct.backlog",
            "device.idle_call_pct.backlog", "serve_step.mixer_time_pct",
            "serve_step.ffn_time_pct", "kernel.paged_attn_time_pct.backlog",
            "kernel.moe_time_pct.docs", "moe.experts_touched_pct.docs",
            "moe.held_pairs_pct.mixed", "kernel.delta_time_pct.answers",
            "kernel.paged_prefill_time_pct.long",
            "ssm.state_share_pct.h1"} <= set(m)
    # one read-back a step (a step that only admits reads nothing)
    assert 0.95 < m["engine.readbacks_per_step"]["value"] <= 1.0
    assert 50 < m["ssm.state_share_pct.h1"]["value"] < 100
    # an even router over 8 experts of which 2 are held: a quarter
    assert 10 < m["moe.held_pairs_pct.mixed"]["value"] < 45


def test_the_new_metrics_read_nothing_on_a_program_without_state_layers():
    """The two rooflines this PR adds, on a program that has neither the
    counters nor the kernels (the parent, or any other family): nothing,
    no raise."""
    sys.path.insert(0, BENCH)
    from readers import xplane_roofline

    class Run:
        registry_delta = {'serving_decode_kv_bytes_total{kind="live"}': 5.0}
        trace = None
        values = {}

    for name in ("kernel.delta_decode_roofline",
                 "kernel.delta_chunk_roofline"):
        with open(os.path.join(BENCH, "layer_metrics", name + ".json")) as f:
            assert xplane_roofline.read(json.load(f)["params"], Run) is None


def test_kernel_needs_counts_tiles_rows_and_experts():
    sys.path.insert(0, BENCH)
    from families import qwen3_next
    cfg = json.load(open(os.path.join(BENCH, "configs",
                                      "qwen3_next_80b_a3b.json")))
    tiles, window = 4 * 32 * 128 * 128, 4 * 3 * 8192
    counters = {
        "serving_ssm_decode_slot_steps_total": 6.0,
        "serving_ssm_prefill_tokens_total": 12.0,
        # six slot-steps read and write a layer's state; a prefill lane
        # that reads and writes it once more
        'serving_ssm_state_bytes_total{kind="read"}':
            7.0 * (tiles + window),
        'serving_ssm_state_bytes_total{kind="written"}':
            7.0 * (tiles + window),
        "serving_moe_experts_touched_total": 2.0,
        "serving_moe_assignments_total": 10.0,
        'serving_decode_kv_bytes_total{kind="live"}': 7.0}
    need = qwen3_next.kernel_needs(cfg["sizes"], 2, 8, counters, 0.0, 0.0)
    rows = 4 * (2 * 4096 + 2 * 2048 + 64)
    assert need["delta_decode_needed_bytes"] == 6 * (2 * tiles + rows)
    assert need["delta_decode_needed_flops"] == 6 * 7 * 32 * 128 * 128
    assert need["delta_chunk_needed_bytes"] == 12 * rows + 2 * tiles
    assert need["moe_ffn_needed_bytes"] == 2 * 3 * 2048 * 512 * 2
    assert need["moe_ffn_needed_flops"] == 10 * 6 * 2048 * 512
    assert need["paged_decode_needed_bytes"] == 7.0
    assert "paged_prefill_needed_flops" not in need
    # the two full layers' prefill: 16 heads of 256 over 2 KV heads
    need = qwen3_next.kernel_needs(cfg["sizes"], 2, 8, {
        **counters,
        'serving_prefill_attn_pairs_total{layers="full"}': 5.0,
        'serving_prefill_kv_rows_total{layers="full"}': 3.0}, 0.0, 0.0)
    assert need["paged_prefill_needed_flops"] == 5 * 16 * 2 * 512
    assert need["paged_prefill_needed_bytes"] == 3 * 2 * 512 * 2


def test_the_control_driver_decides_each_control_as_the_runner_would():
    """``benchmark/controls.py --rehearse``: one line a comparison from
    ``serve_lm._reference_check`` under the configuration's limits, the
    sound one correct (a tiny model's controls need not fail)."""
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "controls.py"), "--config",
         "qwen3_next_80b_a3b", "--seed", "4000000057", "--rehearse"],
        cwd=ROOT, env=ENV, capture_output=True, text=True, timeout=1200)
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    lines = [json.loads(line) for line in p.stdout.splitlines()
             if line.startswith("{")]
    assert [line["control"] for line in lines] == [
        "sound", "state_lost_at_last_chunk", "no_decay", "beta_one",
        "no_output_gate", "float8_reference_weights"]
    assert lines[0]["correct"] is True
    assert all(isinstance(line["correct"], bool)
               and line["tie_margin"] == 0.35
               and line["mean_shortfall_max"] == 0.01 for line in lines)
