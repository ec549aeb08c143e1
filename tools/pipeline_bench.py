"""Wall-clock GPipe vs interleaved-1F1B-circular pipeline comparison.

The analytic bubble fractions (parallel/pipeline.py
pipeline_bubble_fraction) say circular with v chunks should win:
GPipe runs M+n-1 ticks of full-stage work, circular v*M+n-1 ticks of
1/v-size chunks, so per-device layer-applications are
  gpipe:    (M+n-1) * L/n
  circular: (v*M+n-1) * L/(n*v)
This script measures whether the structural win survives the traced
SPMD masked-tick implementation as actual step time (fwd+bwd+sgd).

Run on the 8-virtual-device CPU mesh (no multichip hardware) or on a
real mesh. Writes tools/PIPELINE_TIMING.json and prints a table.
"""
import argparse
import json
import os
import statistics
import sys
import time

# runnable as `python tools/<name>.py` from anywhere: repo root on path
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dim", type=int, default=512)
    ap.add_argument("--mb", type=int, default=8, help="microbatch rows")
    ap.add_argument("--M", type=int, default=8, help="num microbatches")
    ap.add_argument("--layers", type=int, default=8)
    ap.add_argument("--circuits", type=int, default=2)
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--platform", default="cpu")
    args = ap.parse_args()

    import jax
    if args.platform == "cpu":
        jax.config.update("jax_platforms", "cpu")
        jax.config.update("jax_num_cpu_devices", 8)
    import jax.numpy as jnp
    from paddle_tpu.core.mesh import MeshConfig, make_mesh, mesh_context
    from paddle_tpu.core.compile_cache import enable_compile_cache
    from paddle_tpu.parallel import pipeline as pl

    dev = jax.devices()[0]
    enable_compile_cache()
    results = {"device": str(dev), "dim": args.dim, "mb": args.mb,
               "M": args.M, "layers": args.layers,
               "circuits": args.circuits, "configs": []}

    def block(p, h, extra, mb):
        return jnp.tanh(h @ p["w"] + p["b"])

    for pp in (2, 4):
        n_other = 8 // pp
        mesh = make_mesh(MeshConfig(pp=pp, dp=n_other))
        key = jax.random.PRNGKey(0)
        layers = []
        for i in range(args.layers):
            k1, k2, key = jax.random.split(key, 3)
            layers.append({
                "w": jax.random.normal(k1, (args.dim, args.dim)) * 0.1,
                "b": jnp.zeros((args.dim,))})
        stacked = pl.stack_layer_params(layers)
        x = jax.random.normal(key, (args.M, args.mb, args.dim))
        y = jax.random.normal(jax.random.PRNGKey(9), (args.M, args.mb,
                                                      args.dim))

        def make_step(schedule):
            def loss_fn(sp, x, y):
                if schedule == "gpipe":
                    out = pl.gpipe(block, sp, x, mesh=mesh)
                else:
                    out = pl.circular_pipeline(
                        block, sp, x, num_circuits=args.circuits,
                        mesh=mesh, pre_interleaved=True)
                return jnp.mean((out - y) ** 2)

            def step(sp, x, y):
                loss, g = jax.value_and_grad(loss_fn)(sp, x, y)
                sp = jax.tree_util.tree_map(
                    lambda p, gg: p - 1e-3 * gg, sp, g)
                return sp, loss
            return jax.jit(step)

        for schedule in ("gpipe", "circular"):
            params = (pl.interleave_stack(stacked, pp, args.circuits)
                      if schedule == "circular" else stacked)
            with mesh_context(mesh):
                step = make_step(schedule)
                # warmup + compile
                t0 = time.perf_counter()
                p2, loss = step(params, x, y)
                jax.block_until_ready(loss)
                compile_s = time.perf_counter() - t0
                times = []
                for _ in range(args.iters):
                    t0 = time.perf_counter()
                    params, loss = step(params, x, y)
                    jax.block_until_ready(loss)
                    times.append(time.perf_counter() - t0)
            med = statistics.median(times)
            v = args.circuits if schedule == "circular" else 1
            rec = dict(
                pp=pp, schedule=schedule, step_ms=med * 1e3,
                min_ms=min(times) * 1e3,
                compile_s=compile_s,
                bubble_analytic=pl.pipeline_bubble_fraction(
                    pp, args.M, v),
                layer_apps_per_device=(
                    (args.M + pp - 1) * args.layers // pp if v == 1 else
                    (v * args.M + pp - 1) * args.layers // (pp * v)),
                loss=float(loss))
            results["configs"].append(rec)
            print(f"pp={pp} {schedule:9s} step={med * 1e3:8.2f}ms "
                  f"bubble={rec['bubble_analytic']:.3f} "
                  f"layer_apps={rec['layer_apps_per_device']} "
                  f"compile={compile_s:.1f}s", flush=True)

    # speedup summary
    for pp in (2, 4):
        g = next(r for r in results["configs"]
                 if r["pp"] == pp and r["schedule"] == "gpipe")
        c = next(r for r in results["configs"]
                 if r["pp"] == pp and r["schedule"] == "circular")
        sp = g["step_ms"] / c["step_ms"]
        results[f"speedup_pp{pp}"] = sp
        print(f"pp={pp}: circular/gpipe speedup = {sp:.3f}x "
              f"(analytic work ratio = "
              f"{g['layer_apps_per_device'] / c['layer_apps_per_device']:.3f})")

    import os
    out = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "PIPELINE_TIMING.json")
    with open(out, "w") as f:
        json.dump(results, f, indent=1)
    print("wrote", out)


if __name__ == "__main__":
    sys.exit(main())
