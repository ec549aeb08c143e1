"""pjit-facing API: shard a train/eval step over a mesh.

TPU-native replacement of ``ParallelExecutor`` (``parallel_executor.cc:393``)
+ ``CompiledProgram.with_data_parallel`` (``compiler.py:138``): instead of
cloning the graph per device and scheduling SSA op handles, the ONE jitted
step function is given input/output shardings and XLA GSPMD partitions it,
inserting all-reduces/all-gathers where the SSA builder would have placed
op handles (``details/all_reduce_op_handle.cc:127``).

BuildStrategy knobs (``details/build_strategy.h``) map to arguments here:
  - reduce_strategy (AllReduce vs Reduce)  -> ShardingPlan choice
    (replicated vs fsdp: fsdp IS the "Reduce" mode — each shard owns a
    slice of params, ≙ ReduceSSAGraphBuilder ownership rotation)
  - fuse_all_reduce_ops          -> XLA all-reduce combiner (automatic)
  - memory_optimize / inplace    -> donate_argnums (buffer donation)
  - num_iteration_per_drop_scope -> unnecessary (no scopes)
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from paddle_tpu.core import mesh as mesh_lib
from paddle_tpu.observability.recompile import install_compile_listener
from paddle_tpu.parallel import plan as plan_lib


def batch_specs(batch: Any, *, seq_dim: Optional[int] = None) -> Any:
    """Per-leaf PartitionSpecs for a feed dict: dim 0 over (dp, fsdp); with
    ``seq_dim`` set, that dim of rank>=2 float/int arrays over "sp"
    (sequence parallelism). Rank-0/1 leaves shard only the batch dim."""

    def spec(x):
        ndim = getattr(x, "ndim", 0)
        if ndim == 0:
            return P()
        entries = [mesh_lib.BATCH_AXES] + [None] * (ndim - 1)
        if seq_dim is not None and ndim > seq_dim:
            entries[seq_dim] = "sp"
        return P(*entries)

    return jax.tree_util.tree_map(spec, batch)


def _to_shardings(mesh: Mesh, spec: Any) -> Any:
    """P-or-pytree-of-P -> NamedSharding pytree."""
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), spec,
        is_leaf=lambda x: isinstance(x, P))


def shard_train_step(
    step: Callable,
    mesh: Mesh,
    state: Any,
    *,
    plan: Optional[plan_lib.ShardingPlan] = None,
    hints: Any = None,
    batch_spec: P = P(mesh_lib.BATCH_AXES),
    donate_state: bool = True,
):
    """Compile ``step(state, **batch) -> (state, metrics)`` for the mesh.

    Returns ``(jitted_step, placed_state)`` where ``placed_state`` is the
    input state device_put onto its shardings (the analog of
    ``BCastParamsToDevices``, ``parallel_executor.cc:630`` — except sharded
    placement, not N full copies).
    """
    install_compile_listener()     # catalogue the program the caller loads
    plan = plan or plan_lib.replicated_plan()
    state_specs = plan.state_specs(state, hints)
    state_sh = plan_lib.named_shardings(mesh, state_specs)
    batch_sh = _to_shardings(mesh, batch_spec)

    def kw_step(state, batch):
        return step(state, **batch)

    jitted = jax.jit(
        kw_step,
        in_shardings=(state_sh, batch_sh),
        out_shardings=(state_sh, NamedSharding(mesh, P())),
        donate_argnums=(0,) if donate_state else (),
    )
    placed = jax.device_put(state, state_sh)

    def run(state, **batch):
        return jitted(state, batch)

    run.state_shardings = state_sh
    run.batch_sharding = batch_sh
    run.lower = lambda st, **batch: jitted.lower(st, batch)
    return run, placed


def shard_eval_step(
    fn: Callable,
    mesh: Mesh,
    params: Any,
    *,
    plan: Optional[plan_lib.ShardingPlan] = None,
    hints: Any = None,
    batch_spec: P = P(mesh_lib.BATCH_AXES),
):
    """Compile ``fn(params, **batch) -> out`` (out replicated)."""
    plan = plan or plan_lib.replicated_plan()
    pspecs = plan.params_specs(params, hints)
    p_sh = plan_lib.named_shardings(mesh, pspecs)
    batch_sh = _to_shardings(mesh, batch_spec)

    def kw_fn(params, batch):
        return fn(params, **batch)

    jitted = jax.jit(kw_fn, in_shardings=(p_sh, batch_sh))
    placed = jax.device_put(params, p_sh)

    def run(params, **batch):
        return jitted(params, batch)

    run.param_shardings = p_sh
    return run, placed


def with_sharding_constraint(x, spec: P):
    """Mid-function activation sharding hint (≙ the reference pinning a var
    to a Place; here a GSPMD constraint XLA propagates both ways)."""
    return jax.lax.with_sharding_constraint(x, spec)
