"""The long-shared-documents cell PR 55 added, rehearsed from
``BENCHMARK.json`` as it stands: ``run.py --rehearse`` at tiny sizes on the
CPU, kernels interpreted.

The cell goes through ``runners/serve_lm.py`` and
``families/deepseek_v32.py``: a latent row and an index row a token and
layer (16 + 8 values and 16, pages of 8), every query attending to the 16
cached tokens its index scores best, two documents published in set-up and
mapped by every request, a leading dense layer, 4 of 16 sigmoid-routed
experts held beside a shared one, the two selecting latent kernels, the
indexer and the grouped expert kernel on their Pallas bodies, the blocked
float32 reference in the expanded form given the same share, the runner's
selection replay over the three pools of a published page, the metrics this
PR adds and the accepted metrics whose lists the cell joined."""

import json
import os
import subprocess
import sys

from conftest import BENCH, ROOT

ENV = dict(os.environ, JAX_PLATFORMS="cpu")
CELL = "deepseek_v3_2.serve_long_doc_sessions"
NEW_FILES = ("kernel.sparse_latent_decode_roofline",
             "kernel.sparse_latent_prefill_roofline",
             "kernel.sparse_latent_attn_time_pct.longdocs",
             "device.idle_pct.longdocs",
             "attn.sparse_latent_fetch_pct.longdocs",
             "index.keys_fetched_pct.longdocs")
#: what a CPU rehearsal cannot read of the metrics the cell is listed
#: under: the rooflines (the chip's peaks are never made up here) and the
#: scopes of a device trace
CHIP_ONLY = {"kernel.sparse_latent_decode_roofline",
             "kernel.sparse_latent_prefill_roofline",
             "kernel.moe_ffn_roofline", "kernel.indexer_roofline",
             "serve_step.attend_xla_time_pct", "serve_step.attn_in_time_pct",
             "serve_step.ffn_time_pct", "serve_step.head_time_pct",
             "serve_step.unscoped_time_pct"}


def _line(trace, seconds="3"):
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seed", "4000000021", "--seconds", seconds, "--trace", trace,
         "--rehearse"], cwd=ROOT, env=ENV, capture_output=True, text=True,
        timeout=1500)
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1]), p.stdout


def test_long_docs_untraced_reports_its_end_to_end_metrics():
    line, out = _line("0")
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"serve_tokens_per_s", "tpot_p95_ms",
                                    "setup_s"}
    for kernel in ("sparse_latent_prefill", "sparse_latent_decode",
                   "lightning_indexer", "moe_grouped_ffn"):
        assert f"'{kernel}[lax]': 0" in out
        assert f"'{kernel}[pallas_interpret]': 0" not in out
    assert "compiles in the window 0" in out
    assert "published 2 documents of 32 tokens" in out
    assert "'selected_topk_of_more': True" in out
    assert "2 of 2 requests' documents found in the prefix index" in out


def test_long_docs_traced_reads_every_metric_of_the_cell():
    line, out = _line("1", seconds="4")
    assert line["correct"] is True
    m = line["metrics"]
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        listed = {e["name"] for e in json.load(f)["per_layer"]
                  if CELL in e.get("workloads", ())}
    assert set(NEW_FILES) <= listed and listed - set(m) <= CHIP_ONLY
    assert m["engine.readbacks_per_step"]["value"] <= 1.0
    # 32 of a prompt's 36-44 tokens are its document's
    assert 70 < m["engine.prefix_hit_pct"]["value"] < 95
    # 16 of 37-54 live rows a decode token step
    assert 25 < m["attn.sparse_latent_fetch_pct.longdocs"]["value"] < 50
    assert 25 < m["attn.selected_share_pct.docs"]["value"] < 60
    # a document's slots share its index keys' read where they decode
    # together; never more than scored
    assert 0 < m["index.keys_fetched_pct.longdocs"]["value"] <= 100
    # (a CPU trace names no custom call: the share reads 0 here)
    assert m["kernel.sparse_latent_attn_time_pct.longdocs"]["value"] >= 0
    # 4 of 16 experts held: 25 for an even router
    assert 5 < m["moe.held_pairs_pct.mixed"]["value"] < 60
    assert 0 < m["moe.experts_touched_pct.docs"]["value"] <= 100


def test_new_metrics_read_nothing_where_the_program_feeds_none():
    """The metric files over what this PR adds, on a program without it
    (the parent, or a family with no selection over a latent cache):
    nothing, no raise."""
    sys.path.insert(0, BENCH)
    from readers import registry_counter_sum_ratio, xplane_roofline

    def params(name):
        with open(os.path.join(BENCH, "layer_metrics",
                               name + ".json")) as f:
            return json.load(f)["params"]

    class Run:
        registry_delta = {'serving_decode_kv_bytes_total{kind="live"}': 5.0,
                          'serving_latent_rows_fetched_total'
                          '{phase="decode"}': 7.0}
        values = {"peak_bf16_flops_per_s": 197e12,
                  "peak_hbm_bytes_per_s": 819e9}
        notes = []

        class trace:
            @staticmethod
            def seconds_matching(patterns, opcode):
                return 0.0

    for name in ("attn.sparse_latent_fetch_pct.longdocs",
                 "index.keys_fetched_pct.longdocs"):
        assert registry_counter_sum_ratio.read(params(name), Run) is None
    for name in NEW_FILES[:2]:
        assert xplane_roofline.read(params(name), Run) is None
    Run.registry_delta = {
        'serving_latent_rows_fetched_total{phase="decode"}': 2048.0,
        'serving_latent_rows_fetched_total{phase="prefill"}': 99.0,
        'serving_latent_rows_held_total{phase="decode"}': 32768.0,
        "serving_index_rows_fetched_total": 30.0,
        "serving_index_rows_scored_total": 120.0}
    assert registry_counter_sum_ratio.read(
        params("attn.sparse_latent_fetch_pct.longdocs"), Run) == 6.25
    assert registry_counter_sum_ratio.read(
        params("index.keys_fetched_pct.longdocs"), Run) == 25.0
