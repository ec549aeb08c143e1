"""BERT-base perf sweep on the attached TPU (round-2 record: 49.45% MFU,
135,812 tok/s at bs48/seq512). One JSON line per variant to find the
round-4 operating point in a single hardware session.

Variants: batch size, attention impl (xla composed vs pallas flash),
remat. Usage: python tools/bert_sweep.py [--quick]
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


def one(batch_size, attn_impl, remat=False, stacked=False, seq=512,
        steps=12):
    from bench import count_params, device_peak_flops
    from paddle_tpu import optimizer as opt
    from paddle_tpu.core import dtypes
    from paddle_tpu.models.bert import BertConfig, BertForPretraining
    from paddle_tpu.train import build_train_step, make_train_state

    cfg = BertConfig.base(dropout=0.0, attn_dropout=0.0,
                          attn_impl=attn_impl, stacked_layers=stacked)
    model = BertForPretraining(cfg)
    optimizer = opt.AdamW(learning_rate=1e-4)
    state = make_train_state(model, optimizer, jax.random.PRNGKey(0))

    def loss_fn(params, **batch):
        return model.loss(params, training=True, **batch)

    step = jax.jit(build_train_step(
        loss_fn, optimizer, policy=dtypes.get_policy("bf16"),
        remat=remat), donate_argnums=(0,))
    key = jax.random.PRNGKey(1)
    batch = dict(
        input_ids=jax.random.randint(key, (batch_size, seq), 0,
                                     cfg.vocab_size, jnp.int32),
        token_type_ids=jnp.zeros((batch_size, seq), jnp.int32),
        attention_mask=jnp.ones((batch_size, seq), bool),
        mlm_labels=jax.random.randint(key, (batch_size, seq), 0,
                                      cfg.vocab_size, jnp.int32),
        mlm_mask=(jax.random.uniform(key, (batch_size, seq)) < 0.15
                  ).astype(jnp.float32),
        nsp_labels=jnp.zeros((batch_size,), jnp.int32))
    for _ in range(2):
        state, m = step(state, **batch)
    jax.block_until_ready(state)
    t0 = time.perf_counter()
    for _ in range(steps):
        state, m = step(state, **batch)
    jax.block_until_ready(state)
    dt = time.perf_counter() - t0

    n_params = count_params(state["params"])
    fpt = 6 * n_params + 12 * cfg.num_layers * seq * cfg.hidden_size
    tps = batch_size * seq * steps / dt
    return {
        "variant": (f"bs{batch_size}_{attn_impl}"
                    + ("_remat" if remat else "")
                    + ("_stacked" if stacked else "")),
        "tokens_per_sec": round(tps, 1),
        "mfu": round(tps * fpt / device_peak_flops(jax.devices()[0]), 4),
        "step_ms": round(dt / steps * 1e3, 2),
    }


def main():
    from paddle_tpu.core.compile_cache import enable_compile_cache
    enable_compile_cache()
    quick = "--quick" in sys.argv
    grid = [
        dict(batch_size=48, attn_impl="xla"),
        dict(batch_size=48, attn_impl="flash"),
        dict(batch_size=64, attn_impl="flash"),
        dict(batch_size=96, attn_impl="flash", remat=True),
        dict(batch_size=64, attn_impl="xla"),
        dict(batch_size=48, attn_impl="flash", stacked=True),
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
