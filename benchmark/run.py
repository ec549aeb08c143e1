"""One run of one cell of the benchmark.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one cell is data: ``BENCHMARK.json`` names
the cell's configuration and traffic mix, ``configs/<config>.json`` and
``traffic/<traffic>.json`` hold their parameters, the configuration
names its runner (``runners/<runner>.py``) and its model family
(``families/<family>.py``), and every per-layer metric is
``layer_metrics/<name>.json`` naming a reader (``readers/<reader>.py``).
The LAST line of stdout is the result object and nothing else. Without
a TPU (or with fewer chips than the cell asks for) the run exits
non-zero and prints no result; ``--rehearse`` is the one way to drive
the same code at tiny sizes on the CPU, and its last line says
``"platform": "cpu"`` and carries no device metric's value from a chip.
"""

from __future__ import annotations

import time

_T_START = time.perf_counter()      # before any heavy import: setup_s counts them

import argparse      # noqa: E402
import importlib     # noqa: E402
import json          # noqa: E402
import os            # noqa: E402
import sys           # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
sys.path.insert(0, BENCH_DIR)
sys.path.insert(1, ROOT)             # the program: ``import paddle_tpu``

import common        # noqa: E402
from common import log  # noqa: E402


def _find(entries, name, what):
    for e in entries:
        if e["name"] == name:
            return e
    raise SystemExit(f"run.py: no {what} named {name!r} in BENCHMARK.json")


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def _sweep_rows(text: str):
    """``"6,6,10@51"`` -> ``[(6.0, None), (6.0, None), (10.0, 51.0)]``."""
    rows = []
    for item in text.split(","):
        rate, _, seconds = item.partition("@")
        rows.append((float(rate), float(seconds) if seconds else None))
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on the CPU, kernels interpreted; "
                         "never a device measurement")
    ap.add_argument("--sweep", default=None,
                    help="open-loop serving only: comma-separated rates, "
                         "each RATE or RATE@SECONDS; prints a table, not "
                         "the contract's line")
    ap.add_argument("--benchmark-json", default=os.path.join(
        ROOT, "BENCHMARK.json"), help="rehearsals of a cell that "
        "BENCHMARK.json does not list yet give their own")
    ap.add_argument("--keep-survey", default=None,
                    help="with --trace 1: write a survey of the trace here")
    args = ap.parse_args(argv)

    bench = common.load_json(args.benchmark_json)
    wl = _find(bench["workloads"], args.workload, "workload")
    cfg_entry = _find(bench["configs"], wl["config"], "configuration")
    config = common.load_json(ROOT, cfg_entry["file"])
    traffic = common.load_json(BENCH_DIR, "traffic", wl["traffic"] + ".json")
    seconds = bench["run_seconds"] if args.seconds is None else args.seconds

    import jax
    if args.rehearse:
        jax.config.update("jax_platforms", "cpu")
        jax.config.update("jax_num_cpu_devices", max(wl["chips"], 1))
    found = jax.devices()
    platform = found[0].platform
    if platform != "tpu" and not args.rehearse:
        print(f"run.py: needs a TPU, JAX found {platform!r}; a device "
              "metric is never taken from another platform",
              file=sys.stderr)
        return 1
    if len(found) < wl["chips"]:
        print(f"run.py: {wl['name']} needs {wl['chips']} chip(s), JAX found "
              f"{len(found)}", file=sys.stderr)
        return 1
    devices = found[:wl["chips"]]

    # the persistent compile cache: where JAX_COMPILATION_CACHE_DIR says,
    # else <checkout>/.jax_cache (the program's own fixed path). Every
    # program is kept, however fast it compiled or however many there
    # are, so that a second run finds all of them.
    from paddle_tpu.core.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    # this installation caps the cache at 192 MiB with least-recently-used
    # eviction; one serving cell's step programs (5-12 MB each) come to
    # about that, so every run of it would evict what the next one needs
    jax.config.update("jax_compilation_cache_max_size", 4 << 30)
    watch = common.CompileWatch()
    log(f"cell={wl['name']} seed={args.seed} seconds={seconds} "
        f"trace={args.trace} device={devices[0].device_kind} "
        f"count={len(devices)} (JAX found {len(found)}) "
        f"compile cache={cache_dir}")

    cell = common.Cell(
        name=wl["name"], chips=wl["chips"], config=config, traffic=traffic,
        seed=args.seed, seconds=float(seconds), trace=bool(args.trace),
        rehearse=args.rehearse, t_start=_T_START, devices=devices,
        sweep=_sweep_rows(args.sweep) if args.sweep else None,
        watch=watch, survey_path=args.keep_survey)
    runner = importlib.import_module(f"runners.{config['runner']}")
    result = runner.run(cell)
    log(f"JAX compile events over the process: {watch.line()}")
    alloc_peak = common.memory_peak_bytes(devices)
    temp = int(result.values.get("program_temp_bytes", 0))
    live = int(result.values.get("live_bytes_at_window", 0))
    # Three separate facts. The allocator's peak counts buffers and leaves
    # out what a program takes while it runs (1.5 GB against 11 GB of
    # step temporaries in the BERT cell, PERF.md). The device line
    # carries what the chip really holds at its fullest: the buffers
    # live at the window plus the compiler's temporaries of the step
    # program, or the allocator's peak where that is larger.
    peak = max(alloc_peak, live + temp)
    log(f"memory: allocator peak_bytes_in_use={alloc_peak}; buffers live "
        f"at the window={live}; step program's temporaries (compiler's "
        f"temp_size_in_bytes)={temp}; device line memory_peak_bytes={peak}")

    if cell.sweep:
        print(json.dumps({"sweep": result.values["sweep"]}))
        return 0

    device = {"platform": platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    if not args.rehearse:
        import flops
        peaks = flops.peaks_for(devices[0].device_kind)
        result.values["peak_bf16_flops_per_s"] = peaks["bf16_flops_per_s"]
        result.values["peak_hbm_bytes_per_s"] = peaks["hbm_bytes_per_s"]

    metrics = {}
    if not args.trace:
        for m in bench["end_to_end"]:
            if _applies(m, cell.name):
                if m["name"] not in result.values:
                    log(f"end-to-end metric {m['name']} was not measured")
                    result.correct = False
                    continue
                metrics[m["name"]] = {"value": result.values[m["name"]],
                                      "unit": m["unit"]}
    else:
        for m in bench["per_layer"]:
            if not _applies(m, cell.name):
                continue
            spec = common.load_json(BENCH_DIR, "layer_metrics",
                                    m["name"] + ".json")
            reader = importlib.import_module(f"readers.{spec['reader']}")
            value = reader.read(spec.get("params", {}), result)
            if value is None:
                log(f"per-layer metric {m['name']}: nothing to read")
                continue
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        for note in result.notes:
            log(note)
    line = {"correct": bool(result.correct), "attempted": int(result.attempted),
            "failed": int(result.failed), "metrics": metrics, "device": device}
    if result.trace is not None:
        device["busy_s"] = result.trace.busy_s
        device["window_s"] = result.trace.window_s
        line["breakdown"] = {"device_ops": result.trace.top_ops(10),
                             "idle_gaps": result.trace.top_idle(10)}
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main())
