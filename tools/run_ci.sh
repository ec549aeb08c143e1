#!/bin/sh
# CI entry (SURVEY §7 step 11: surface freeze + test gate).
# Runs on a virtual 8-device CPU mesh; no network, no TPU required.
#
# Tiers (≙ reference ctest labels in paddle/scripts/paddle_build.sh):
#   run_ci.sh --quick   surface freeze + quick suite (-m "not slow"),
#                       sized for a 1-CPU box (< ~5 min)
#   run_ci.sh           the merge gate: freeze + quick + the slow tier in
#                       two memory-bounded chunks + the multichip dryrun
set -e
cd "$(dirname "$0")/.."

MODE="${1:-full}"

echo "== api surface freeze =="
SPEC_NOW="$(mktemp)"   # unique per run: concurrent CI must not race
trap 'rm -f "$SPEC_NOW"' EXIT
python tools/gen_api_spec.py > "$SPEC_NOW"
diff -u api_spec.txt "$SPEC_NOW" || {
  echo "API surface changed: regenerate api_spec.txt in the same commit"
  exit 1
}

PYTEST="python -m pytest -q"
export XLA_FLAGS=--xla_force_host_platform_device_count=8
export JAX_PLATFORMS=cpu

echo "== quick tier =="
$PYTEST tests/ -m "not slow"

# bench-bitrot smoke: the TPU-session scripts must at least run end-to-end
# on CPU (round 5 lost its int8 hardware window to an import error here)
echo "== bench smoke (int8 dryrun) =="
python tools/int8_bench.py --dryrun > /dev/null

# serving-bench smoke: the continuous-batching engine + paged decode +
# batched prefill must run end-to-end on CPU and self-validate the
# BENCH_SERVING schema (incl. the zero-steady-state-recompiles invariant)
# before any TPU session; the python check pins the ISSUE 6 prefill
# metrics — TTFT percentiles vs the stated budget and the shared-prefix
# variant actually saving prefill work
echo "== bench smoke (serving dryrun) =="
SERVING_OUT="$(python bench.py --model serving --dryrun)"
if echo "$SERVING_OUT" | grep -q '"error"'; then
  echo "serving bench dryrun failed: $SERVING_OUT"
  exit 1
fi
echo "$SERVING_OUT" | python -c '
import json, sys
r = json.load(sys.stdin)
for k in ("ttft_p50_s", "ttft_p90_s", "ttft_p99_s", "ttft_budget_s",
          "queue_wait_p99_s", "admit_to_first_token_p99_s",
          "prefix_variant", "slo_burn_rate", "slo_alerts_total",
          "trace_json", "trace_spans", "tokens_per_hbm_byte",
          "tokens_per_hbm_byte_bf16", "quant_static_bytes_ratio",
          "quant_speedup", "quant_variant", "spec_accept_rate",
          "spec_variant"):
    assert k in r, f"BENCH_SERVING missing {k}"
assert r["ttft_slo_met"], "dryrun TTFT p99 blew the stated budget"
pv = r["prefix_variant"]
assert pv["prefill_tokens_computed"] < pv["prompt_tokens_submitted"], \
    "prefix sharing saved no prefill work"
assert pv["recompiles"] == 0 and r["decode_recompiles_after_warmup"] == 0
# ISSUE 13: the int8 paged cache must statically beat the bf16 pool by
# >= 1.8x tokens-per-HBM-byte (cost-model derived, deterministic), the
# speculative variant must be bit-exact vs non-speculative greedy, and
# neither new variant may recompile in steady state
assert r["quant_static_bytes_ratio"] >= 1.8, r["quant_static_bytes_ratio"]
assert r["spec_variant"]["exact_vs_nonspeculative"] is True
assert r["quant_variant"]["recompiles"] == 0
assert r["spec_variant"]["recompiles"] == 0
assert 0.0 <= r["spec_accept_rate"] <= 1.0
# the ISSUE 10 trace artifact: present, Perfetto-valid (every event
# carries ph/ts/pid/tid), and carrying the lifecycle + decision
# annotations the bench self-check pinned
from paddle_tpu.observability import tracing
trace = json.load(open(r["trace_json"]))
n = tracing.chrome_trace_valid(trace, require_events=r["trace_spans"])
names = {e["name"] for e in trace["traceEvents"]}
for needed in ("serving.request", "serving.prefill_chunk",
               "serving.decode_block", "prefix_shared", "sched_skip",
               "sched_boost"):
    assert needed in names, f"trace artifact missing {needed!r}"
assert r["trace_spans"] > 0, "empty trace ring"
print(f"serving dryrun prefill+SLO+trace metrics OK ({n} trace events)")
'

# router bench smoke: the multi-replica fleet (prefix-affinity router,
# live migration, burn-rate autoscaling signal) must run end-to-end on
# CPU and self-validate the BENCH_ROUTER schema — aggregate throughput
# scales across 1/2/4 replicas, a mid-decode drain migrates in-flight
# requests with byte-identical greedy outputs, zero recompiles
# fleet-wide, and the trace artifact shows one request crossing the
# fleet (router.route / serving.request / router.migrate share ids).
# The chaos stage (ISSUE 14) additionally kills a replica mid-burst and
# flakes another's transport: 0 requests silently lost, redriven
# outputs byte-identical, the circuit breaker completes a visible
# open -> half_open -> closed cycle, 0 recompiles with breakers armed
# (schema pinned by tools/check_metrics_log.py:validate_chaos_section).
echo "== bench smoke (router + chaos dryrun) =="
ROUTER_OUT="$(python bench.py --model router --dryrun)"
if echo "$ROUTER_OUT" | grep -q '"error"'; then
  echo "router bench dryrun failed: $ROUTER_OUT"
  exit 1
fi
echo "$ROUTER_OUT" | python -c '
import json, sys
sys.path.insert(0, "tools")
r = json.load(sys.stdin)
for k in ("replica_scaling", "scaling_2x", "scaling_4x",
          "ttft_interactive_p99_s", "ttft_slo_met", "migrations",
          "migration_parity_ok", "affinity_routed",
          "prefix_tokens_shared", "recompiles_after_warmup",
          "trace_json", "trace_spans", "chaos"):
    assert k in r, f"BENCH_ROUTER missing {k}"
assert set(r["replica_scaling"]) == {"1", "2", "4"}
assert r["migration_parity_ok"], "drained run diverged from clean run"
assert r["migrations"] >= 1, "migration leg migrated nothing"
assert r["recompiles_after_warmup"] == 0, "fleet recompiled"
assert r["affinity_routed"] >= 1, "prefix affinity never fired"
assert r["prefix_tokens_shared"] > 0, "affinity saved no prefill"
assert r["ttft_slo_met"], "interactive probe TTFT blew the budget"
from check_metrics_log import validate_chaos_section
validate_chaos_section(r["chaos"])
assert r["chaos"]["lost_requests"] == 0
assert r["chaos"]["redrive_parity"] is True
assert r["chaos"]["breaker_cycle_ok"] is True
assert r["chaos"]["recompiles"] == 0
# ISSUE 16: the resource-headroom plane (fleet bottleneck, min across
# replicas) and the crash flight recorder must both ship
assert set(r["headroom"]) == {"flops", "pages", "slots", "hbm",
                              "spill"}, r["headroom"]
for res, v in r["headroom"].items():
    assert 0.0 <= v <= 1.0, (res, v)
assert r["chaos"]["postmortems"] >= 1, "no postmortem bundle captured"
assert "eject" in r["chaos"]["postmortem_reasons"], \
    r["chaos"]["postmortem_reasons"]
assert r["chaos"]["postmortem_valid"] is True
from paddle_tpu.observability import tracing
trace = json.load(open(r["trace_json"]))
tracing.chrome_trace_valid(trace, require_events=1)
names = {e["name"] for e in trace["traceEvents"]}
for needed in ("router.route", "serving.request", "router.migrate",
               "migrated_in", "migrated_out", "router.eject",
               "router.redrive", "fleet.breaker", "router.postmortem"):
    assert needed in names, f"router trace missing {needed!r}"
print("router + chaos dryrun fleet metrics OK")
'
# the on-disk postmortem artifact must validate standalone (the
# flight-recorder acceptance: every chaos-bench ejection ships a
# schema-valid bundle the offline renderer can read)
PM_DIR=/tmp/BENCH_ROUTER.postmortems
test -d "$PM_DIR" || { echo "no postmortem dump dir at $PM_DIR"; exit 1; }
for pm in "$PM_DIR"/*.json; do
  python tools/check_metrics_log.py --postmortem "$pm"
done
python tools/postmortem.py "$PM_DIR" > /dev/null
echo "postmortem artifacts OK ($(ls "$PM_DIR" | wc -l) bundle(s))"

# embedding-serving bench smoke: the device-cached host-KV lookup engine
# must run end-to-end on CPU (cache hits/misses/evictions, streaming
# pushes, zero steady-state recompiles) and self-validate the
# BENCH_EMBED_SERVE schema before any TPU session
echo "== bench smoke (embedding serving dryrun) =="
EMBED_OUT="$(python bench.py --model embedding_serving --dryrun)"
if echo "$EMBED_OUT" | grep -q '"error"'; then
  echo "embedding serving bench dryrun failed: $EMBED_OUT"
  exit 1
fi
echo "$EMBED_OUT" | python -c '
import json, sys
r = json.load(sys.stdin)
for k in ("qps_cached", "qps_cold", "speedup_vs_cold", "lookup_p99_s",
          "hit_rate", "staleness_seconds", "streaming_rows_applied",
          "evictions", "recompiles_after_warmup"):
    assert k in r, f"BENCH_EMBED_SERVE missing {k}"
assert r["recompiles_after_warmup"] == 0, "steady-state recompile"
assert 0.0 < r["hit_rate"] <= 1.0, "hit-rate gauge not populated"
assert r["streaming_rows_applied"] > 0, "streaming updates dead"
assert r["speedup_vs_cold"] > 1.0, \
    "device cache slower than the cold full-table path"
print("embedding serving dryrun metrics OK")
'

# net_router bench smoke (ISSUE 17): the fleet split across REAL
# subprocesses behind the wire-protocol ReplicaHandle must run
# end-to-end on CPU — greedy outputs bit-identical to the in-process
# LocalReplica fleet (the interface contract survives the socket), the
# streaming front door delivers >=2 partial frames per request with a
# validating crash-safe netlog, and the socket-chaos leg (SIGSTOP
# breaker cycle + kill -9 eject/redrive over a real dead socket) loses
# 0 requests with bit-identical redriven outputs and client-side
# postmortems, 0 steady-state recompiles per replica process
echo "== bench smoke (net_router + socket chaos dryrun) =="
NET_OUT="$(python bench.py --model net_router --dryrun)"
if echo "$NET_OUT" | grep -q '"error"'; then
  echo "net_router bench dryrun failed: $NET_OUT"
  exit 1
fi
echo "$NET_OUT" | python -c '
import json, sys
r = json.load(sys.stdin)
for k in ("net_tokens_per_sec", "local_tokens_per_sec",
          "transport_overhead_ms_per_token", "transport_parity_ok",
          "wire_codec", "stream_partials_min", "stream_ttft_p99_s",
          "ttft_slo_met", "netlog_valid", "steady_state_recompiles",
          "chaos"):
    assert k in r, f"BENCH_NET missing {k}"
assert r["transport_parity_ok"] is True, \
    "net fleet outputs diverged from in-process"
assert r["steady_state_recompiles"] == 0, \
    "replica subprocess recompiled in steady state"
assert r["stream_partials_min"] >= 2, \
    "front door buffered instead of streaming"
assert r["ttft_slo_met"], "streamed TTFT blew the budget"
assert r["netlog_valid"]["accepted_requests"] >= 4
c = r["chaos"]
assert c["lost_requests"] == 0, "socket chaos lost requests"
assert c["redrive_parity"] is True
assert c["ejected"] >= 1 and c["redrives"] >= 1
assert c["breaker_cycle_ok"] is True, c["breaker_transitions"]
assert c["postmortems"] >= 1
assert "eject" in c["postmortem_reasons"], c["postmortem_reasons"]
assert c["postmortem_valid"] is True
print("net_router + socket chaos dryrun OK (overhead=%.3fms/token, "
      "codec=%s)" % (r["transport_overhead_ms_per_token"],
                     r["wire_codec"]))
'
# the front door netlog must validate standalone through the CLI (the
# crash-safe ledger CI replays: schema + monotonic frame ids + every
# accepted request terminated exactly once)
python tools/check_metrics_log.py --netlog /tmp/BENCH_NET.netlog.jsonl \
  --require-requests 4

# disaggregation bench smoke (ISSUE 19): the two-tier fleet (flops-bound
# prefill replicas streaming sha256 shard manifests into KV-bound decode
# replicas) must run the mixed burst end-to-end on CPU — interactive
# TTFT p99 at least 2x better than the colocated fleet, decode
# throughput within 10% by busy-time accounting, greedy outputs
# bit-identical, transfer bytes metered under the page-math budget, and
# zero steady-state recompiles on BOTH tiers (each tier warms only its
# own bucket plan)
echo "== bench smoke (disagg dryrun) =="
DISAGG_OUT="$(python bench.py --model disagg --dryrun)"
if echo "$DISAGG_OUT" | grep -q '"error"'; then
  echo "disagg bench dryrun failed: $DISAGG_OUT"
  exit 1
fi
echo "$DISAGG_OUT" | python -c '
import json, sys
r = json.load(sys.stdin)
for k in ("ttft_interactive_p99_s", "ttft_ratio",
          "decode_tokens_per_s_busy", "throughput_ratio",
          "greedy_identical", "recompiles_after_warmup", "handoffs",
          "transfer_bytes", "transfer_budget_bytes"):
    assert k in r, f"BENCH_DISAGG missing {k}"
assert r["greedy_identical"] is True, \
    "disaggregated greedy outputs diverged from colocated"
assert r["handoffs"] >= 1, "no prefill->decode handoff happened"
for tier in ("prefill", "decode", "colocated"):
    assert r["recompiles_after_warmup"][tier] == 0, \
        (tier, "recompiled in steady state")
assert 0 < r["transfer_bytes"] <= r["transfer_budget_bytes"], \
    "handoff transfer bytes unmetered or over the page-math budget"
assert r["ttft_ratio"] > 0 and r["throughput_ratio"] > 0
print("disagg dryrun OK (ttft %.2fx, throughput %.2fx, %d handoffs, "
      "%d transfer bytes)" % (r["ttft_ratio"], r["throughput_ratio"],
                              r["handoffs"], r["transfer_bytes"]))
'

# hierarchical-KV bench smoke (ISSUE 20): host-spilled cold pages plus
# fleet-global prefix fetch must run the churn script end-to-end on CPU
# — wave A publishes + spills, a fresh replica scales out, the holders
# drain (wave B fetches instead of re-prefilling) and scale in, wave C
# runs on the survivors — with greedy outputs bit-identical to the
# affinity-only fleet and zero steady-state recompiles in both legs
# (schema pinned by check_metrics_log.validate_prefix_fleet_section;
# the strictly-below prefill/served gate runs non-dryrun in the bench)
echo "== bench smoke (prefix_fleet dryrun) =="
PFLEET_OUT="$(python bench.py --model prefix_fleet --dryrun)"
if echo "$PFLEET_OUT" | grep -q '"error"'; then
  echo "prefix_fleet bench dryrun failed: $PFLEET_OUT"
  exit 1
fi
echo "$PFLEET_OUT" | python -c '
import json, sys
sys.path.insert(0, "tools")
r = json.load(sys.stdin)
from check_metrics_log import validate_prefix_fleet_section
validate_prefix_fleet_section(r)
assert r["churn"]["scale_out_replicas"] >= 1
assert r["churn"]["drained_holders"] is True
pps = r["prefill_per_served"]
print("prefix_fleet dryrun OK (prefill/served %.3f affinity-only vs "
      "%.3f hierarchical, %d pages fetched, %d spilled)"
      % (pps["affinity_only"], pps["hierarchical"],
         r["fetch"]["pages"], r["spill"]["spilled_pages"]))
'

# kernel-layer bench smoke: the shared autotuner must measure all three
# single-device Pallas kernels (flash, ragged decode, ragged prefill)
# across 3 shape buckets through ONE dispatch harness, hit its cache on
# re-resolution, and load the committed tools/kernel_tune.json with zero
# stale entries (a kernel contract-version bump without a reseed fails
# here, not in production)
echo "== bench smoke (kernels dryrun) =="
KERNELS_OUT="$(python bench.py --model kernels --dryrun)"
if echo "$KERNELS_OUT" | grep -q '"error"'; then
  echo "kernels bench dryrun failed: $KERNELS_OUT"
  exit 1
fi
echo "$KERNELS_OUT" | python -c '
import json, sys
r = json.load(sys.stdin)
for k in ("kernels", "tuner_cache_hits", "tuner_cache_misses",
          "tuner_stale_entries", "committed_cache_entries",
          "committed_cache_stale", "impl"):
    assert k in r, f"BENCH_KERNELS missing {k}"
ks = r["kernels"]
assert set(ks) == {"flash_attention", "ragged_paged_decode",
                   "ragged_paged_prefill", "ragged_paged_decode_int8",
                   "ragged_paged_prefill_int8"}, sorted(ks)
for name, buckets in ks.items():
    assert len(buckets) == 3, f"{name}: expected 3 shape buckets"
    for key, b in buckets.items():
        assert b["tuned_s"] <= b["default_s"] * 1.001, \
            f"{key}: tuner picked a slower config than the default"
assert r["tuner_cache_hits"] >= 3, "measured buckets did not cache-hit"
assert r["committed_cache_entries"] > 0, "committed tune cache empty"
assert r["committed_cache_stale"] == 0, "stale committed tune entries"
print("kernels dryrun OK (geomean %sx vs default blocks)" % r["value"])
'

# static self-lint: the zoo's step functions (LeNet/ResNet-18 train, GPT
# decode, VGG conv-group dropout, serving decode/prefill, embedding
# install/lookup) must be free of error-severity graph hazards (host
# syncs, key reuse, tracer branches); accepted warnings live in
# tools/graph_lint_suppressions.txt (stale entries are themselves an
# error). The preset now also runs the kernel-registry rule: every
# registered Pallas kernel's contract (layouts, donation aliasing in
# lowered HLO, zero collectives, autotuner blocks within candidates)
# is verified, and any pallas_call in ops/, parallel/ or serving/ that
# bypasses the registry fails the build unless allowlisted in
# tools/kernel_registry_allowlist.txt (stale allowlist entries are
# rejected like stale suppressions). The --cost tier adds the HLO rules
# — zero collectives in
# single-device serving steps, peak-HBM/flops under the committed
# budgets, warmup bucket-coverage proof — and --cost-diff fails the
# build when any surface's static flops / peak-HBM / collective bytes
# regress >10% vs tools/cost_budgets.json (a hardware-free perf gate;
# regenerate the manifest with --update-budgets when a regression is
# intentional and justify it in the PR). The --concurrency tier adds the
# host-thread rules: @guarded_by lock discipline over every package
# module, cycle/double-acquire detection on the static lock-acquisition
# graph plus the drift gate against the committed tools/lock_order.json
# (regenerate with --update-lock-order and review the order),
# ReplicaHandle/wire-dispatch interface conformance, and the
# single-source Reject.reason vocabulary check
echo "== graph self-lint + cost budgets (framework preset) =="
python tools/graph_lint.py --preset framework --cost --cost-diff --concurrency

if [ "$MODE" = "--quick" ]; then
  echo "CI OK (quick tier)"
  exit 0
fi

# slow tier in two sequential chunks so a 1-CPU box never holds the whole
# model zoo + pipeline graphs in one process; chunk 2 is "every slow test
# NOT in chunk 1", so new slow-marked files can never silently drop out
CHUNK1="tests/test_model_zoo_cv.py tests/test_detection_train.py \
        tests/test_resnet.py tests/test_faster_rcnn.py \
        tests/test_ocr_gan.py tests/test_zoo_trainer_detection.py \
        tests/test_crf_srl.py tests/test_ops_long_tail2.py"

echo "== slow tier (1/2: model zoo + detection) =="
$PYTEST $CHUNK1 -m slow

echo "== slow tier (2/2: everything else slow) =="
IGNORES=""
for f in $CHUNK1; do IGNORES="$IGNORES --ignore=$f"; done
$PYTEST tests/ -m slow $IGNORES

echo "== multichip dryrun =="
python -c "import __graft_entry__ as g; g.dryrun_multichip(8)"

echo "CI OK"
