"""Train-step builder: backward + optimize + state updates as ONE jitted fn.

This is the TPU-native replacement for the reference's two-phase world
(``optimizer.minimize`` appending backward+optimize ops into a ProgramDesc,
then ``Executor``/``ParallelExecutor`` interpreting it — SURVEY.md §3.1/3.2).
Here the whole training step — forward, backward (jax.grad ≙ append_backward
``backward.py:933``), gradient accumulation (≙ BatchMergePass), AMP casts,
BN state updates, optimizer — is one traced function XLA compiles and fuses.

Data-parallel execution needs NO changes here: jit over a mesh with the
batch sharded on (dp, fsdp) makes XLA insert gradient all-reduces exactly
where AllReduceOpHandle (details/all_reduce_op_handle.cc:127) would sit.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

from paddle_tpu.core import dtypes
from paddle_tpu.nn.module import apply_state_updates, capture_state
from paddle_tpu.observability.recompile import (expect_step_program,
                                                install_compile_listener)

#: the ``jax.named_scope`` names :func:`build_train_step` opens: the
#: phases ``observability.scopes`` splits a step's device time by (the
#: backward inherits ``forward`` as ``transpose(jvp(forward))``)
PHASE_SCOPES = ("forward", "optimizer")


def make_train_state(model, optimizer, rng_key, sample_extra=None):
    """Initialize {params, opt, step} (+ user extras)."""
    params = model.init(rng_key)
    state = {
        "params": params,
        "opt": optimizer.init(params),
        "step": jnp.zeros((), jnp.int32),
    }
    if sample_extra:
        state.update(sample_extra)
    return state


def build_train_step(
    loss_fn: Callable,
    optimizer,
    *,
    policy: Optional[dtypes.Policy] = None,
    trainable_mask: Any = None,
    grad_accum_steps: int = 1,
    remat: bool = False,
) -> Callable:
    """Build ``step(state, **batch) -> (state, metrics)``.

    ``loss_fn(params, **batch)`` returns a scalar loss or ``(loss, aux_dict)``.
    AMP: params are cast per ``policy`` before the forward; grads arrive in
    param dtype (f32 master weights — fluid AMP keeps fp32 master copies).
    ``grad_accum_steps`` > 1 splits the batch into microbatches and
    accumulates grads in a lax.scan (≙ BatchMergePass,
    ir/multi_batch_merge_pass.h:34).
    """

    # the step's compile is the caller's: catalogue whatever it loads, so
    # that observability.scopes can book device time by the scopes below
    install_compile_listener()
    if remat:
        loss_fn = jax.checkpoint(loss_fn)

    def forward(params, batch):
        if policy:
            params = policy.cast_to_compute(params)
            batch = policy.cast_to_compute(batch)  # activations too: conv/dot
            # require matching operand dtypes
        # backward inherits the scope as transpose(jvp(forward))
        with capture_state() as tape, jax.named_scope("forward"):
            out = loss_fn(params, **batch)
        if isinstance(out, tuple):
            loss, aux = out
        else:
            loss, aux = out, {}
        return loss, (dict(tape.updates), aux)

    grad_fn = jax.value_and_grad(forward, has_aux=True)

    def single_step(state, batch):
        (loss, (updates, aux)), grads = grad_fn(state["params"], batch)
        return loss, updates, aux, grads

    def accum_step(state, batch):
        def micro(gsum, mb):
            loss, updates, aux, grads = single_step(state, mb)
            gsum = jax.tree_util.tree_map(jnp.add, gsum, grads)
            return gsum, (loss, aux, updates)

        micro_batches = jax.tree_util.tree_map(
            lambda x: x.reshape((grad_accum_steps, -1) + x.shape[1:]), batch)
        zeros = jax.tree_util.tree_map(jnp.zeros_like, state["params"])
        gsum, (losses, auxs, updates_seq) = jax.lax.scan(
            micro, zeros, micro_batches)
        grads = jax.tree_util.tree_map(lambda g: g / grad_accum_steps, gsum)
        loss = jnp.mean(losses)
        aux = jax.tree_util.tree_map(jnp.mean, auxs)
        # running-state (BN) updates: keep the last microbatch's values
        updates = jax.tree_util.tree_map(lambda u: u[-1], updates_seq)
        return loss, updates, aux, grads

    def step(state, **batch):
        expect_step_program()       # while traced: nothing runs a step
        if grad_accum_steps > 1:
            loss, updates, aux, grads = accum_step(state, batch)
        else:
            loss, updates, aux, grads = single_step(state, batch)
        with jax.named_scope("optimizer"):
            params, opt_state = optimizer.update(
                grads, state["opt"], state["params"], mask=trainable_mask)
        params = apply_state_updates(params, updates)
        new_state = dict(state)
        new_state.update(params=params, opt=opt_state, step=state["step"] + 1)
        metrics = {"loss": loss, **aux}
        return new_state, metrics

    return step


def build_eval_step(model_fn: Callable,
                    policy: Optional[dtypes.Policy] = None) -> Callable:
    def step(params, **batch):
        if policy:
            params = policy.cast_to_compute(params)
            batch = policy.cast_to_compute(batch)
        return model_fn(params, **batch)

    return step
