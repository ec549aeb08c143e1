"""Parallel subsystem tests on the virtual 8-device CPU mesh.

Mirrors the reference's collective-op tests (test_collective_base.py:34 —
subprocesses comparing each c_* op to a numpy reduction) and the
ParallelExecutor loss-parity tests (parallel_executor_test_base.py:32 —
single- vs multi-device training must match).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from paddle_tpu import nn, optimizer, train
from paddle_tpu.core.mesh import MeshConfig, make_mesh, mesh_context
from paddle_tpu.parallel import (ShardingPlan, collective, fsdp_plan,
                                 replicated_plan, shard_train_step)


@pytest.fixture(scope="module")
def dp_mesh():
    return make_mesh(MeshConfig(dp=8))


@pytest.fixture(scope="module")
def dp_tp_mesh():
    return make_mesh(MeshConfig(dp=2, tp=4))


# -- collectives (test_collective_base parity) ------------------------------

def test_all_reduce_sum(dp_mesh):
    x = jnp.arange(8.0)
    with mesh_context(dp_mesh):
        out = collective.all_reduce(x, "dp")
    np.testing.assert_allclose(out, x * 8)


@pytest.mark.parametrize("op,ref", [("max", np.max), ("min", np.min)])
def test_all_reduce_minmax(dp_mesh, op, ref):
    # replicated input: reduction over identical members is identity
    x = jnp.array([3.0, -1.0, 7.0])
    with mesh_context(dp_mesh):
        out = collective.all_reduce(x, "dp", op=op)
    np.testing.assert_allclose(out, x)


def test_all_gather_tiled(dp_mesh):
    x = jnp.ones((2, 3))
    with mesh_context(dp_mesh):
        out = collective.all_gather(x, "dp", concat_axis=0)
    assert out.shape == (16, 3)


def test_reduce_scatter(dp_mesh):
    x = jnp.ones((16, 4))
    with mesh_context(dp_mesh):
        out = collective.reduce_scatter(x, "dp", scatter_axis=0)
    assert out.shape == (16, 4)
    np.testing.assert_allclose(np.asarray(out), np.full((16, 4), 8.0))


def test_broadcast(dp_mesh):
    x = jnp.array([5.0, 6.0])
    with mesh_context(dp_mesh):
        out = collective.broadcast(x, "dp", root=0)
    np.testing.assert_allclose(out, x)


def test_barrier(dp_mesh):
    with mesh_context(dp_mesh):
        collective.barrier("dp")  # must not deadlock/crash


# -- sharding plans ---------------------------------------------------------

def test_plan_rule_precedence():
    plan = ShardingPlan([(r"dense/weight", P("fsdp", "tp"))])
    spec = plan.spec_for(("dense", "weight"), hint=P(None, "tp"),
                        shape=(128, 128))
    assert spec == P("fsdp", "tp")
    # no rule -> hint wins
    spec = plan.spec_for(("other", "weight"), hint=P(None, "tp"),
                        shape=(128, 128))
    assert spec == P(None, "tp")
    # nothing -> replicated
    assert plan.spec_for(("b",), hint=None, shape=(4,)) == P()


def test_fsdp_plan_shards_largest_dim():
    plan = fsdp_plan(min_size=16)
    spec = plan.spec_for(("w",), hint=None, shape=(8, 1024))
    assert spec == P(None, "fsdp")
    # small params stay replicated
    assert plan.spec_for(("b",), hint=None, shape=(4,)) == P()
    # hint with tp on dim1 -> fsdp goes to dim0 (largest unsharded)
    spec = plan.spec_for(("w2",), hint=P(None, "tp"), shape=(4096, 8))
    assert spec == P("fsdp", "tp")


# -- end-to-end loss parity (parallel_executor_test_base parity) -----------

def _make_model_and_batch(seed=0):
    model = nn.Sequential(
        nn.Linear(16, 32), nn.Sequential(), nn.Linear(32, 4, sharding=None),
    )
    rng = np.random.RandomState(seed)
    x = rng.randn(32, 16).astype(np.float32)
    y = rng.randint(0, 4, size=(32,))
    return model, {"x": jnp.asarray(x), "y": jnp.asarray(y)}


def _loss_fn(model):
    from paddle_tpu.ops import nn as ops_nn

    def loss_fn(params, x, y):
        logits = model(params, x)
        return ops_nn.softmax_with_cross_entropy(
            logits, y, return_softmax=False).mean()

    return loss_fn


def _run_steps(step_fn, state, batch, n=4):
    losses = []
    for _ in range(n):
        state, metrics = step_fn(state, **batch)
        losses.append(float(metrics["loss"]))
    return losses


@pytest.mark.parametrize("plan_name", ["replicated", "fsdp"])
def test_dp_loss_parity(dp_mesh, plan_name):
    model, batch = _make_model_and_batch()
    opt = optimizer.Momentum(learning_rate=0.1, momentum=0.9)
    loss_fn = _loss_fn(model)
    step = train.build_train_step(loss_fn, opt)

    # single-device baseline
    state0 = train.make_train_state(model, opt, jax.random.PRNGKey(0))
    base = _run_steps(jax.jit(lambda s, **b: step(s, **b)), state0, batch)

    # sharded run
    plan = replicated_plan() if plan_name == "replicated" else fsdp_plan(
        min_size=128)
    state1 = train.make_train_state(model, opt, jax.random.PRNGKey(0))
    with mesh_context(dp_mesh):
        run, placed = shard_train_step(
            step, dp_mesh, state1, plan=plan,
            hints={"params": None})
        got = _run_steps(run, placed, batch)

    np.testing.assert_allclose(base, got, rtol=2e-5, atol=2e-6)


def test_tp_loss_parity(dp_tp_mesh):
    model, batch = _make_model_and_batch()
    opt = optimizer.Adam(learning_rate=1e-2)
    loss_fn = _loss_fn(model)
    step = train.build_train_step(loss_fn, opt)

    state0 = train.make_train_state(model, opt, jax.random.PRNGKey(0))
    base = _run_steps(jax.jit(lambda s, **b: step(s, **b)), state0, batch)

    state1 = train.make_train_state(model, opt, jax.random.PRNGKey(0))
    hints = model.sharding_specs(state1["params"])
    with mesh_context(dp_tp_mesh):
        run, placed = shard_train_step(step, dp_tp_mesh, state1,
                                       hints=hints)
        got = _run_steps(run, placed, batch)

    np.testing.assert_allclose(base, got, rtol=2e-4, atol=1e-5)
