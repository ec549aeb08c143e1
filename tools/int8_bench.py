"""int8-vs-float serving comparison on the current backend.

Measures, for a matmul-heavy serving graph (the int8 win case):
  - compiled artifact s8-buffer survival (the residency proof)
  - serve latency (median of N runs)
  - executable/device memory via memory_analysis()
Prints ONE JSON line; run inside the TPU session for the hardware
numbers (CPU run is labeled honestly).

``--dryrun`` shrinks everything to CPU-smoke size and self-validates the
output schema — tools/run_ci.sh runs it so bench bitrot is caught by CI,
not by a burning TPU session (round-5 lost its int8 window to an import
error this very file shipped with).
"""
import argparse
import json
import os
import statistics
import sys
import time

# run from anywhere: the repo root is this file's parent dir (round 5's
# crash was exactly this line missing — `python tools/int8_bench.py` has
# tools/ on sys.path, not the repo root)
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

_REQUIRED_KEYS = ("device", "float32", "int8", "int8_vs_float_latency",
                  "max_abs_diff")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dim", type=int, default=2048)
    ap.add_argument("--layers", type=int, default=8)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--iters", type=int, default=50)
    ap.add_argument("--dryrun", action="store_true",
                    help="tiny CPU smoke run + output-schema self-check")
    args = ap.parse_args()
    if args.dryrun:
        args.dim, args.layers, args.batch = 64, 2, 2
        args.iters = min(args.iters, 3)

    import jax
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu import slim
    from paddle_tpu.core.compile_cache import enable_compile_cache

    dev = jax.devices()[0]
    enable_compile_cache()
    rng = np.random.RandomState(0)
    params = {f"l{i}": {"w": rng.randn(args.dim, args.dim)
                        .astype(np.float32) * 0.03}
              for i in range(args.layers)}

    def net(p, x):
        for i in range(args.layers):
            x = jnp.tanh(x @ p[f"l{i}"]["w"])
        return x

    x = jnp.asarray(rng.randn(args.batch, args.dim), jnp.float32)
    q = slim.quantize_weights_int8(params)

    def f_float(x):
        return net(params, x)

    def f_int8(x):
        return net(slim.dequantize_weights(q, keep_int8_resident=True), x)

    out = {"device": str(dev), "dim": args.dim, "layers": args.layers,
           "batch": args.batch}
    results = {}
    for name, fn in (("float32", f_float), ("int8", f_int8)):
        c = jax.jit(fn).lower(x).compile()
        hlo = c.as_text()
        mem = c.memory_analysis()
        r = c(x)
        jax.block_until_ready(r)
        results[name] = r
        ts = []
        for _ in range(args.iters):
            t0 = time.perf_counter()
            jax.block_until_ready(c(x))
            ts.append(time.perf_counter() - t0)
        out[name] = {
            "latency_ms": statistics.median(ts) * 1e3,
            "s8_weight_bufs": hlo.count(f"s8[{args.dim},{args.dim}]") > 0,
            "generated_code_bytes": getattr(
                mem, "generated_code_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
        }
    fl = out["float32"]["latency_ms"]
    i8 = out["int8"]["latency_ms"]
    out["int8_vs_float_latency"] = i8 / fl
    # numerical sanity: int8 path tracks float within quantization error
    # (reuse the executables' outputs — no recompilation)
    d = float(jnp.max(jnp.abs(jnp.asarray(results["float32"]) -
                              jnp.asarray(results["int8"]))))
    out["max_abs_diff"] = d
    if args.dryrun:
        out["dryrun"] = True
        missing = [k for k in _REQUIRED_KEYS if k not in out]
        if missing:
            print(f"int8_bench dryrun: missing output keys {missing}",
                  file=sys.stderr)
            return 1
        if not (d == d and d < 1.0):   # NaN-safe sanity on the quant error
            print(f"int8_bench dryrun: implausible max_abs_diff {d}",
                  file=sys.stderr)
            return 1
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
