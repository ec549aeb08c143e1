"""The shared-context sessions cell PR 42 added, rehearsed from
``BENCHMARK.json`` as it stands: ``run.py --rehearse`` at tiny sizes on the
CPU, kernels interpreted.

The cell goes through ``runners/serve_lm.py`` and ``families/mistral4.py``:
one latent row a token and layer (16 + 8 values, pages of 8), two documents
published in set-up and mapped by every request, 2 of 16 experts held
beside a shared one, both latent kernels and the grouped expert kernel on
their Pallas bodies, the blocked float32 reference in the expanded form
given the same share, the counter metric this PR adds, and the accepted
metrics whose lists the cell joined."""

import json
import os
import subprocess
import sys

from conftest import BENCH, ROOT

ENV = dict(os.environ, JAX_PLATFORMS="cpu")
CELL = "mistral_small_4_119b.serve_shared_context_sessions"
NEW_FILES = ("attn.latent_prefill_pairs_pct.sessions",
             "kernel.latent_attn_time_pct.sessions")
#: what a CPU rehearsal cannot read of the metrics the cell is listed
#: under: the rooflines (the chip's peaks are never made up here) and the
#: scopes of a device trace
CHIP_ONLY = {"kernel.latent_decode_roofline",
             "kernel.latent_prefill_roofline", "kernel.moe_ffn_roofline",
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


def test_sessions_untraced_reports_its_end_to_end_metrics():
    line, out = _line("0")
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"serve_tokens_per_s", "tpot_p95_ms",
                                    "setup_s"}
    for kernel in ("latent_paged_prefill", "latent_paged_decode",
                   "moe_grouped_ffn"):
        assert f"'{kernel}[lax]': 0" in out
        assert f"'{kernel}[pallas_interpret]': 0" not in out
    assert "compiles in the window 0" in out
    assert "published 2 documents of 32 tokens" in out


def test_sessions_traced_reads_every_metric_of_the_cell():
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
    assert 0 < m["attn.latent_prefill_pairs_pct.sessions"]["value"] < 100
    # (a CPU trace names no custom call: the share reads 0 here)
    assert m["kernel.latent_attn_time_pct.sessions"]["value"] >= 0
    # 2 of 16 experts held: 12.5 for an even router
    assert 2 < m["moe.held_pairs_pct.mixed"]["value"] < 40
    assert 0 < m["moe.experts_touched_pct.docs"]["value"] <= 100


def test_new_metrics_read_nothing_where_the_program_feeds_none():
    """The metric files over what this PR adds, on a program without it
    (the parent, or a family that caches K and V): nothing, no raise."""
    sys.path.insert(0, BENCH)
    from readers import registry_counter_share, xplane_roofline

    def params(name):
        with open(os.path.join(BENCH, "layer_metrics",
                               name + ".json")) as f:
            return json.load(f)["params"]

    class Run:
        registry_delta = {'serving_decode_kv_bytes_total{kind="live"}': 5.0}
        values = {"peak_bf16_flops_per_s": 197e12,
                  "peak_hbm_bytes_per_s": 819e9}
        notes = []

        class trace:
            @staticmethod
            def seconds_matching(patterns, opcode):
                return 0.0

    share = params("attn.latent_prefill_pairs_pct.sessions")
    assert registry_counter_share.read(share, Run) is None
    for name in ("kernel.latent_decode_roofline",
                 "kernel.latent_prefill_roofline"):
        assert xplane_roofline.read(params(name), Run) is None
    Run.registry_delta = {
        'serving_latent_pairs_total{phase="prefill"}': 30.0,
        'serving_latent_pairs_total{phase="decode"}': 70.0}
    assert registry_counter_share.read(share, Run) == 30.0
