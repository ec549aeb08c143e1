"""The cells PR 28 added, rehearsed from ``BENCHMARK.json`` as it stands:
``run.py --rehearse`` at tiny sizes on the CPU, kernels interpreted.

The long-document serving cell goes through ``runners/serve_lm.py`` and
``families/keye_vl2.py``: documents published in set-up, every new kernel
on its Pallas body, the blocked float32 reference, the selection counted.
The four-chip BERT cell runs on four virtual devices."""

import json
import os
import re
import subprocess
import sys

import pytest

from conftest import BENCH, ROOT

ENV = dict(os.environ, JAX_PLATFORMS="cpu")
DOCS = "keye_vl2_30b_a3b.serve_doc_sessions"
MESH = "bert_base.pretrain_dp2tp2"


def _line(cell, trace, seconds="3"):
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", cell,
         "--seed", "3000000019", "--seconds", seconds, "--trace", trace,
         "--rehearse"], cwd=ROOT, env=ENV, capture_output=True, text=True,
        timeout=900)
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1]), p.stdout


def test_doc_sessions_untraced_reports_its_end_to_end_metrics():
    line, out = _line(DOCS, "0")
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"serve_tokens_per_s", "tpot_p95_ms",
                                    "setup_s"}
    assert "published 2 documents" in out
    assert "'selected_topk_of_more': True" in out
    # 16 of 32 tokens, bf16 weights: a position or two at the threshold
    found = re.search(r"selection check: (\d) of (\d) .* by layer, ([\d.]+), "
                      r"([\d.]+) ", out)
    assert found and found.group(1) == found.group(2) == "3"
    assert min(float(found.group(3)), float(found.group(4))) >= 0.9


def test_doc_sessions_traced_reads_its_counters_and_kernel_shares():
    line, _ = _line(DOCS, "1", seconds="4")
    assert line["correct"] is True
    m = line["metrics"]
    # the rooflines need the chip's peaks and are never made up here
    assert {"engine.decode_block_ms", "device.idle_pct.docs",
            "kernel.sparse_attn_time_pct.docs", "kernel.moe_time_pct.docs",
            "kernel.xla_fusion_time_pct.docs",
            "moe.experts_touched_pct.docs", "attn.selected_share_pct.docs",
            "engine.prefix_hit_pct", "engine.host_share_pct",
            "engine.decode_host_ms", "engine.prefill_host_ms",
            "device.idle_call_pct.backlog", "device.idle_book_pct.backlog",
            "device.idle_sched_pct.backlog"} <= set(m)
    assert 0 < m["attn.selected_share_pct.docs"]["value"] < 100
    assert 0 < m["moe.experts_touched_pct.docs"]["value"] <= 100
    assert m["engine.prefix_hit_pct"]["value"] > 50


def test_dp2tp2_traced_reads_the_collective_share():
    line, _ = _line(MESH, "1", seconds="2")
    assert line["correct"] is True and line["device"]["count"] == 4
    assert {"device.collective_exposed_pct",
            "device.idle_pct.dp2tp2"} <= set(line["metrics"])


@pytest.mark.parametrize("name", ["keye_vl2_30b_a3b.json"])
def test_configuration_keeps_the_catalog_numbers(name):
    with open(os.path.join(BENCH, "configs", name)) as f:
        cfg = json.load(f)
    for key, value in cfg["sizes"].items():
        assert cfg[key] == value
    assert cfg["reduced"] == ["num_hidden_layers"]
