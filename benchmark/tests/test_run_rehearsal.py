"""``run.py`` end to end at tiny sizes on the CPU with the kernels
interpreted: both runners, the training runner on a 2 x 2 mesh of four
virtual CPU devices, and the refusal to run without a TPU."""

import json
import os
import subprocess
import sys

import pytest

from conftest import BENCH, ROOT

ENV = dict(os.environ, JAX_PLATFORMS="cpu")


def _run(*args, timeout=900):
    return subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), *args],
        cwd=ROOT, env=ENV, capture_output=True, text=True, timeout=timeout)


def _last(proc):
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


CHAT = "gpt2_small.serve_chat_prompt_heavy"
#: the open-loop chat mix is not a cell of BENCHMARK.json yet (PERF.md,
#: open cells); the PR that adds it adds these entries
CHAT_ENTRIES = {
    "workloads": [{"name": CHAT, "config": "gpt2_small",
                   "traffic": "serve_chat_prompt_heavy", "chips": 1,
                   "why": "rehearsal"}],
    "end_to_end": [{"name": "ttft_p95_ms", "unit": "ms", "better": "lower",
                    "bound": 0.1, "source": "host_clock",
                    "workloads": [CHAT]}],
    "per_layer": [
        {"name": n, "unit": u, "better": b, "source": src,
         "layer": "serving engine", "moves": "ttft_p95_ms",
         "workloads": [CHAT]}
        for n, u, b, src in [
            ("engine.ttft_p95_ms", "ms", "lower", "host_clock"),
            ("engine.queue_wait_p95_ms", "ms", "lower", "program_span"),
            ("engine.prefix_hit_pct", "%", "higher", "program_counter"),
            ("device.idle_pct.chat", "%", "lower", "device_trace"),
            ("kernel.paged_attn_time_pct.chat", "%", "lower",
             "device_trace")]],
}


def _alt_benchmark(tmp_path, extra, joins=()):
    """BENCHMARK.json plus the entries of a cell it does not list yet;
    ``joins``: (metric, cell) pairs, the cell added to a listed metric."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        b = json.load(f)
    for key, entries in extra.items():
        b[key].extend(entries)
    for metric, cell in joins:
        next(m for m in b["end_to_end"] + b["per_layer"]
             if m["name"] == metric)["workloads"].append(cell)
    alt = tmp_path / "BENCHMARK.json"
    alt.write_text(json.dumps(b))
    return str(alt)


def test_without_a_tpu_nothing_is_printed():
    p = _run("--workload", "bert_base.pretrain_b48_s512", "--seed", "1",
             "--seconds", "1", "--trace", "0")
    assert p.returncode != 0
    assert "needs a TPU" in p.stderr
    assert not p.stdout.strip().startswith("{")


@pytest.mark.parametrize("cell,trace,metric", [
    ("bert_base.pretrain_b48_s512", "0", "train_tokens_per_s"),
    ("bert_base.pretrain_b48_s512", "1", "device.idle_pct.train"),
    ("gpt2_small.serve_decode_backlog", "0", "serve_tokens_per_s"),
    ("gpt2_small.serve_decode_backlog", "1", "engine.decode_block_ms"),
    (CHAT, "0", "ttft_p95_ms"),
    (CHAT, "1", "engine.prefix_hit_pct"),
])
def test_rehearsal_prints_the_contract_line(cell, trace, metric, tmp_path):
    alt = _alt_benchmark(tmp_path, CHAT_ENTRIES if cell == CHAT else {})
    line = _last(_run("--benchmark-json", alt, "--workload", cell,
                      "--seed", "3000000019", "--seconds", "3",
                      "--trace", trace, "--rehearse"))
    keys = {"correct", "attempted", "failed", "metrics", "device"}
    assert keys <= set(line) <= keys | {"breakdown"}
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert line["device"]["platform"] == "cpu"
    assert metric in line["metrics"]
    assert all(set(v) == {"value", "unit"} for v in line["metrics"].values())
    if trace == "1":
        assert line["device"]["busy_s"] > 0
        assert line["device"]["window_s"] > 0
        assert len(line["breakdown"]["device_ops"]) <= 10
        # a device metric that needs the chip's peaks is never made up
        assert "train_step.mfu_pct" not in line["metrics"]
    else:
        assert "setup_s" in line["metrics"]


def test_training_runner_on_a_2x2_mesh_of_virtual_devices(tmp_path):
    cell = "bert_base.pretrain_dp2tp2"
    alt = _alt_benchmark(tmp_path, {
        "workloads": [{"name": cell, "config": "bert_base",
                       "traffic": "pretrain_dp2tp2", "chips": 4,
                       "why": "rehearsal"}]},
        joins=[("train_tokens_per_s", cell)])
    line = _last(_run("--benchmark-json", alt, "--workload", cell,
                      "--seed", "7", "--seconds", "2", "--trace", "0",
                      "--rehearse"))
    assert line["correct"] is True
    assert line["device"]["platform"] == "cpu"
    assert line["device"]["count"] == 4


def test_sweep_takes_rates_and_window_lengths(tmp_path):
    alt = _alt_benchmark(tmp_path, CHAT_ENTRIES)
    p = _run("--benchmark-json", alt, "--workload", CHAT, "--seed", "5",
             "--seconds", "2", "--trace", "0", "--rehearse",
             "--sweep", "3,3@3")
    rows = _last(p)["sweep"]
    assert [(r["rate_per_s"], r["seconds"]) for r in rows] == \
        [(3.0, 2.0), (3.0, 3.0)]
    assert [r["due"] for r in rows] == [6, 9] and rows[0]["failed"] == 0
