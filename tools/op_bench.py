"""Per-op micro-benchmark harness.

Reference mapping: ``paddle/fluid/operators/benchmark/op_tester.cc`` (run a
single op from a config, time it) and ``operators/jit/benchmark.cc`` (table
of kernel timings). TPU-native: each entry jits one op at sizes from a
config table, times steady-state device execution, and prints a table
sorted by achieved FLOPS (or GB/s for bandwidth-bound ops), comparing
implementations where there are two (flash vs composed attention; Pallas
ring step vs composed ring step).

Usage:
  python tools/op_bench.py                   # run, print table
  python tools/op_bench.py --record PATH     # also write JSON results
  python tools/op_bench.py --check PATH      # exit 1 on >25% regression
  python tools/op_bench.py --ops matmul,softmax
"""

from __future__ import annotations

import argparse
import functools
import json
import sys
import time

import jax
import jax.numpy as jnp

import os

# runnable as `python tools/<name>.py` from anywhere: repo root on path
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _time_fn(fn, *args, iters=20):
    jax.block_until_ready(fn(*args))  # compile + warm
    t0 = time.perf_counter()
    out = None
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)  # in-order stream: waits for all iters
    return (time.perf_counter() - t0) / iters


def _rand(key, shape, dtype):
    return jax.random.normal(jax.random.PRNGKey(key), shape, dtype)


def bench_matmul(dtype=jnp.bfloat16):
    rows = []
    for m, k, n in [(1024, 1024, 1024), (4096, 4096, 4096),
                    (8192, 2048, 8192)]:
        a = _rand(0, (m, k), dtype)
        b = _rand(1, (k, n), dtype)
        f = jax.jit(lambda a, b: a @ b)
        dt = _time_fn(f, a, b)
        rows.append({"op": f"matmul_{m}x{k}x{n}", "ms": dt * 1e3,
                     "gflops": 2 * m * k * n / dt / 1e9})
    return rows


def bench_layer_norm():
    from paddle_tpu.ops.nn import layer_norm

    rows = []
    for b, s, d in [(32, 512, 1024), (8, 4096, 4096)]:
        x = _rand(0, (b, s, d), jnp.float32)
        g = jnp.ones((d,))
        bb = jnp.zeros((d,))
        f = jax.jit(lambda x, g, bb: layer_norm(x, g, bb))
        dt = _time_fn(f, x, g, bb)
        rows.append({"op": f"layer_norm_{b}x{s}x{d}", "ms": dt * 1e3,
                     "gbps": 2 * x.nbytes / dt / 1e9})
    return rows


def bench_softmax():
    rows = []
    for b, h, s in [(32, 12, 512), (4, 16, 4096)]:
        x = _rand(0, (b, h, s, s), jnp.float32)
        f = jax.jit(lambda x: jax.nn.softmax(x, axis=-1))
        dt = _time_fn(f, x)
        rows.append({"op": f"softmax_{b}x{h}x{s}x{s}", "ms": dt * 1e3,
                     "gbps": 2 * x.nbytes / dt / 1e9})
    return rows


def _attn_flops(b, h, s, d):
    return 4 * b * h * s * s * d  # qk^T + pv, 2 FLOPs per MAC


def bench_attention():
    """Pallas flash kernel vs XLA-composed attention, fwd and fwd+bwd."""
    from paddle_tpu.ops import attention as A

    on_tpu = jax.devices()[0].platform == "tpu"
    rows = []
    for b, h, s, d in [(4, 12, 2048, 64), (1, 8, 8192, 128)]:
        q = _rand(0, (b, h, s, d), jnp.bfloat16)
        k = _rand(1, (b, h, s, d), jnp.bfloat16)
        v = _rand(2, (b, h, s, d), jnp.bfloat16)
        impls = {"xla": "xla"}
        if on_tpu:
            impls["flash"] = "flash"
        for name, impl in impls.items():
            f = jax.jit(functools.partial(
                A.dot_product_attention, causal=True, impl=impl))
            dt = _time_fn(f, q, k, v, iters=10)
            rows.append({"op": f"attn_{name}_fwd_{b}x{h}x{s}x{d}",
                         "ms": dt * 1e3,
                         "gflops": _attn_flops(b, h, s, d) / dt / 1e9})

            def loss(q, k, v, impl=impl):
                return A.dot_product_attention(
                    q, k, v, causal=True, impl=impl
                ).astype(jnp.float32).sum()

            g = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
            dt = _time_fn(g, q, k, v, iters=10)
            rows.append({"op": f"attn_{name}_fwdbwd_{b}x{h}x{s}x{d}",
                         "ms": dt * 1e3,
                         "gflops": 3.5 * _attn_flops(b, h, s, d) / dt / 1e9})
    return rows


def bench_ring_attention():
    """Composed vs Pallas-per-block ring step (single chip, sp=1 ring —
    measures the per-block kernel advantage that holds under sp>1)."""
    from paddle_tpu.core.mesh import MeshConfig, make_mesh, mesh_context
    from paddle_tpu.parallel.ring_attention import ring_attention

    on_tpu = jax.devices()[0].platform == "tpu"
    rows = []
    mesh = make_mesh(MeshConfig(), devices=jax.devices()[:1])
    for b, h, s, d in [(4, 12, 4096, 64)]:
        q = _rand(0, (b, h, s, d), jnp.bfloat16)
        k = _rand(1, (b, h, s, d), jnp.bfloat16)
        v = _rand(2, (b, h, s, d), jnp.bfloat16)
        impls = ["xla"] + (["flash"] if on_tpu else [])
        with mesh_context(mesh):
            for impl in impls:
                f = jax.jit(functools.partial(
                    ring_attention, causal=True, mesh=mesh, impl=impl))
                dt = _time_fn(f, q, k, v, iters=10)
                rows.append({"op": f"ring_{impl}_fwd_{b}x{h}x{s}x{d}",
                             "ms": dt * 1e3,
                             "gflops": _attn_flops(b, h, s, d) / dt / 1e9})
    return rows


BENCHES = {
    "matmul": bench_matmul,
    "layer_norm": bench_layer_norm,
    "softmax": bench_softmax,
    "attention": bench_attention,
    "ring_attention": bench_ring_attention,
}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--ops", default=",".join(BENCHES))
    ap.add_argument("--record", default=None)
    ap.add_argument("--check", default=None)
    args = ap.parse_args()

    from paddle_tpu.core.compile_cache import enable_compile_cache
    dev = jax.devices()[0]
    enable_compile_cache()
    print(f"# op bench on {getattr(dev, 'device_kind', dev.platform)}")
    rows = []
    for name in args.ops.split(","):
        rows.extend(BENCHES[name.strip()]())

    rows.sort(key=lambda r: -r.get("gflops", r.get("gbps", 0.0)))
    width = max(len(r["op"]) for r in rows) + 2
    for r in rows:
        rate = (f"{r['gflops']:10.1f} GFLOP/s" if "gflops" in r
                else f"{r['gbps']:10.1f} GB/s   ")
        print(f"{r['op']:<{width}} {r['ms']:9.3f} ms {rate}")

    if args.record:
        with open(args.record, "w") as f:
            json.dump({"device": getattr(dev, "device_kind", dev.platform),
                       "rows": rows}, f, indent=2)
        print(f"# recorded -> {args.record}")

    if args.check:
        with open(args.check) as f:
            baseline = json.load(f)
        base = {r["op"]: r for r in baseline["rows"]}
        here = getattr(dev, "device_kind", dev.platform)
        if baseline.get("device") != here:
            print(f"# WARNING: baseline device {baseline.get('device')!r}"
                  f" != current {here!r}; timings not comparable")
        bad = []
        for r in rows:
            b = base.get(r["op"])
            if b and r["ms"] > b["ms"] * 1.25:
                bad.append(f"{r['op']}: {b['ms']:.3f} -> {r['ms']:.3f} ms")
        # an op that VANISHED from a full run is a failure, not a pass
        # (crashed bench or silent rename would otherwise slip the gate)
        if set(args.ops.split(",")) == set(BENCHES):
            got = {r["op"] for r in rows}
            for op in sorted(set(base) - got):
                bad.append(f"{op}: present in baseline, missing from run")
        if bad:
            print("# REGRESSIONS:\n" + "\n".join(bad))
            sys.exit(1)
        print("# no regressions vs", args.check)


if __name__ == "__main__":
    main()
