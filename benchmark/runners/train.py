"""A training cell: the program's train step on a seeded pool of
batches, steps dispatched without a host sync, the rate taken over the
whole window.

Set-up: the model and AdamW state made on the device in one jitted call
from ``--seed``; a pool of batches made on the device in one jitted
call; on a mesh (the job file names it) the step goes through
``parallel.api.shard_train_step`` with the model's own sharding hints,
as ``chip_smoke.py --chips 4`` does. Step ``i`` takes batch
``i % pool`` and the dropout key ``fold_in(key, i)``, both chosen on
the device from the state's step counter, so the host sends nothing per
step.
"""

from __future__ import annotations

import importlib
import math

import numpy as np

import common
import flops
from common import log

#: evaluation-mode loss, bf16 policy + flash kernel against the plain
#: float32 reference on the same parameters. bf16 keeps 8 bits of
#: mantissa (relative 4e-3 per rounding); the loss is a mean of ~600
#: masked tokens' log-probabilities of about 10-12, whose roundings
#: largely average out. A dropped residual, a wrong mask or attention
#: in the wrong order moves the loss by whole units. The first chip
#: run reads the real gap (PERF.md); this bound is several times it.
EVAL_LOSS_RTOL = 5e-3
#: steps the host may run ahead of the device before it waits for the
#: oldest: enough that the device never starves, few enough that the
#: window's last step is not dispatched seconds before it runs
RUN_AHEAD = 2


def _make_pool(job, vocab, key):
    """``pool`` batches on the device, in one jitted call."""
    import jax
    import jax.numpy as jnp
    n, b, s = job["pool"], job["batch"], job["seq"]

    def make(key):
        k1, k2, k3, k4 = jax.random.split(key, 4)
        return dict(
            input_ids=jax.random.randint(k1, (n, b, s), 0, vocab, jnp.int32),
            token_type_ids=jnp.zeros((n, b, s), jnp.int32),
            attention_mask=jnp.ones((n, b, s), bool),
            mlm_labels=jax.random.randint(k2, (n, b, s), 0, vocab,
                                          jnp.int32),
            mlm_mask=(jax.random.uniform(k3, (n, b, s))
                      < job["mlm_mask_share"]).astype(jnp.float32),
            nsp_labels=jax.random.randint(k4, (n, b), 0, 2, jnp.int32))
    return jax.jit(make)(key)


def _pool_step(step, pool_size):
    """``step(state, **batch)`` -> ``step(state, pool=, key=)``: batch and
    dropout key picked on the device from the state's own counter."""
    import jax

    def run(state, *, pool, key):
        i = state["step"]
        batch = jax.tree_util.tree_map(lambda x: x[i % pool_size], pool)
        return step(state, **batch, key=jax.random.fold_in(key, i))
    return run


def _spread_over(tree, devices) -> bool:
    """Some array of ``tree`` is sharded, and every sharded one has
    shards on ALL ``devices`` with no device holding the whole of it
    (after chip_smoke.py's ``_assert_spread``)."""
    import jax
    sharded = 0
    for x in jax.tree_util.tree_leaves(tree):
        if x.sharding.is_fully_replicated:
            continue
        sharded += 1
        if {s.device for s in x.addressable_shards} != set(devices):
            return False
        if any(math.prod(s.data.shape) >= math.prod(x.shape)
               for s in x.addressable_shards):
            return False
    log(f"{sharded} parameter arrays are sharded over {len(devices)} devices")
    return sharded > 0


def _dispatched(kernel, impl):
    from paddle_tpu.observability import registry
    return registry.counter("kernel_dispatch_total").value(
        kernel=kernel, impl=impl)


def run(cell: common.Cell) -> common.RunResult:
    import jax
    from paddle_tpu import optimizer as opt
    from paddle_tpu.core import dtypes
    from paddle_tpu.train import build_train_step, make_train_state

    cfg, job = cell.config, cell.job()
    family = importlib.import_module(f"families.{cfg['family']}")
    sizes = cell.sizes()
    impl = "pallas_interpret" if cell.rehearse else "pallas"
    flash_before = _dispatched("flash_attention", impl)
    flash_lax_before = _dispatched("flash_attention", "lax")
    model = family.build(sizes, interpret=cell.rehearse)
    optimizer = opt.AdamW(learning_rate=cfg["assumed"]["learning_rate"])
    policy = dtypes.get_policy(cfg["assumed"]["policy"])
    key = jax.random.PRNGKey(cell.seed32)
    k_state, k_pool, k_drop = jax.random.split(key, 3)

    state = jax.jit(lambda k: make_train_state(model, optimizer, k))(k_state)
    pool = _make_pool(job, sizes["vocab_size"], k_pool)
    n_params = sum(int(np.prod(x.shape))
                   for x in jax.tree_util.tree_leaves(state["params"]))

    def loss_fn(params, **batch):
        return model.loss(params, training=True, **batch)

    step = _pool_step(build_train_step(loss_fn, optimizer, policy=policy),
                      job["pool"])
    spread_ok = True
    mesh_cfg = job.get("mesh")
    if mesh_cfg:
        from paddle_tpu.core.mesh import MeshConfig, make_mesh, mesh_context
        from paddle_tpu.parallel import api as papi, plan as plan_lib
        from jax.sharding import PartitionSpec as P
        mesh = make_mesh(MeshConfig(**mesh_cfg["axes"]),
                         devices=cell.devices)
        plan = getattr(plan_lib, mesh_cfg["plan"])()
        batch_spec = {"pool": jax.tree_util.tree_map(
            lambda x: P(None, *papi.batch_specs(x[0])), pool),
            "key": P()}
        ctx = mesh_context(mesh)
        ctx.__enter__()
        sharded, state = papi.shard_train_step(
            step, mesh, state, plan=plan,
            hints=model.sharding_specs(state["params"]),
            batch_spec=batch_spec)
        # placed once: an unplaced pool would be resharded every step
        pool, k_mesh = jax.device_put(
            (pool, k_drop), (sharded.batch_sharding["pool"],
                             sharded.batch_sharding["key"]))
        compiled = sharded.lower(state, pool=pool, key=k_mesh).compile()

        def run_step(st):
            return compiled(st, {"pool": pool, "key": k_mesh})
    else:
        compiled = jax.jit(
            lambda st, pool, key: step(st, pool=pool, key=key),
            donate_argnums=(0,)).lower(state, pool, k_drop).compile()

        def run_step(st):
            return compiled(st, pool, k_drop)
    # the allocator's own peak leaves out what the program takes while
    # it runs (activations, logits): the compiler's figure for that
    temp_bytes = int(compiled.memory_analysis().temp_size_in_bytes)

    # -- once, in set-up: evaluation-mode loss against the plain reference,
    # through the parameters as they are placed (on a mesh: sharded)
    heads = sizes["num_attention_heads"]
    sample = {k: v[0, :job["reference_sequences"]] for k, v in pool.items()}
    got = float(jax.jit(lambda p, b: model.loss(
        policy.cast_to_compute(p), training=False, **b)[0])(
            state["params"], sample))
    with jax.default_matmul_precision("highest"):
        want = float(jax.jit(lambda p, b: family.reference_loss(
            p, b, heads))(state["params"], sample))
    ref_gap = abs(got - want) / abs(want)
    log(f"eval loss, bf16 policy + flash kernel {got:.6f} vs plain float32 "
        f"reference {want:.6f}: relative gap {ref_gap:.3e} "
        f"(tolerance {EVAL_LOSS_RTOL})")
    ref_ok = math.isfinite(got) and ref_gap < EVAL_LOSS_RTOL

    if mesh_cfg:
        spread_ok = _spread_over(state["params"], cell.devices)

    # -- warm-up: two optimizer steps (the first loads the program)
    warm_losses = []
    for _ in range(2):
        state, m = run_step(state)
        warm_losses.append(float(m["loss"]))
    flash = _dispatched("flash_attention", impl) - flash_before
    flash_lax = _dispatched("flash_attention", "lax") - flash_lax_before
    log(f"warm-up losses {[round(x, 4) for x in warm_losses]}; "
        f"flash_attention dispatches {impl}={int(flash)} lax={int(flash_lax)}")

    watch = cell.watch
    compiles_before = watch.compiles()
    prof = common.Profiler(cell.rehearse) if cell.trace else None
    trace_len = min(job["trace_seconds"], cell.seconds / 2)
    tokens_per_step = job["batch"] * job["seq"]

    # ------------------------------------------------------------------
    # the window
    # ------------------------------------------------------------------
    losses = []
    live_bytes = common.live_bytes(cell.devices)
    t0 = common.now()
    setup_s = t0 - cell.t_start
    t_end = t0 + cell.seconds
    tracing = False
    while True:
        t = common.now()
        if t >= t_end:
            break
        if prof and not tracing and t >= t_end - trace_len:
            prof.start()
            tracing = True
        with common.span("bench.dispatch", tracing):
            state, m = run_step(state)
        losses.append(m["loss"])
        if len(losses) > RUN_AHEAD:
            with common.span("bench.wait_oldest_step", tracing):
                losses[-RUN_AHEAD - 1].block_until_ready()
    with common.span("bench.wait_last_step", tracing):
        jax.block_until_ready(state)
    elapsed = common.now() - t0
    if tracing:
        prof.stop()
    # ------------------------------------------------------------------

    if mesh_cfg:
        ctx.__exit__(None, None, None)
    steps = len(losses)
    losses = [float(x) for x in losses]
    compiled = watch.compiles() - compiles_before
    finite = all(math.isfinite(x) for x in losses + warm_losses)
    tail = float(np.mean(losses[-min(8, steps):])) if steps else math.nan
    falling = steps > 0 and tail < warm_losses[0]
    tokens_per_s = steps * tokens_per_step / elapsed
    log(f"window: {steps} steps of {tokens_per_step} tokens in "
        f"{elapsed:.4f}s = {tokens_per_s:.1f} tokens/s; first loss "
        f"{warm_losses[0]:.4f}, mean of last {min(8, steps)} {tail:.4f}; "
        f"compiles inside the window {int(compiled)}")
    checks = {"reference": ref_ok, "params_spread": spread_ok, "finite": finite,
              "loss_fell": falling, "flash_on_kernel": flash > 0
              and flash_lax == 0, "no_compile_in_window": compiled == 0}
    log("checks " + str(checks))

    seq, b = job["seq"], job["batch"]
    attn = flops.flash_attention_train(
        b, heads, seq, sizes["hidden_size"] // heads,
        sizes["num_hidden_layers"])
    values = {
        "train_tokens_per_s": tokens_per_s,
        "setup_s": setup_s,
        "steps": float(steps),
        "steps_per_s": steps / elapsed,
        "flops_per_token": flops.train_flops_per_token(
            n_params, sizes["num_hidden_layers"], seq, sizes["hidden_size"]),
        "n_params": float(n_params),
        "chips": float(cell.chips),
        "flash_flops_per_step": attn["flops"],
        "flash_bytes_per_step": attn["bytes"],
        "eval_loss_rel_gap": ref_gap,
        "program_temp_bytes": float(temp_bytes),
        "live_bytes_at_window": float(live_bytes),
    }
    result = common.RunResult(
        correct=all(checks.values()), attempted=steps,
        failed=sum(not math.isfinite(x) for x in losses), values=values)
    if tracing:
        result.trace = prof.summary(cell.chips, cell.survey_path)
        # steps that ran inside the traced window, from its own length
        values["traced_steps"] = values["steps_per_s"] * result.trace.window_s
    return result
