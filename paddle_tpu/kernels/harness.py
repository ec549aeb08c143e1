"""Shared dispatch / interpret / fallback harness for registered kernels.

Replaces the four private ``_on_tpu()`` + impl-string shims the flash,
ring, decode, and prefill kernels each carried. One entry point:

    out = dispatch("flash_attention", q, k, v, bias,
                   impl="auto", causal=True)

``impl`` is canonical across every kernel:

- ``"auto"``    — Pallas on a TPU backend, lax on any other (that is
  how the CPU tier-1 suite runs; an entry point that must be on the
  chip asserts what :func:`resolve_impl` gave it);
- ``"pallas"``  — the compiled Pallas body (TPU);
- ``"pallas_interpret"`` — the SAME Pallas body run by the interpreter
  (CPU tier-1 tests exercise the real kernel logic);
- ``"lax"``     — the XLA-composed fallback (identical numerics).

For Pallas impls the tunable block sizes resolve through the shared
autotuner (:func:`~paddle_tpu.kernels.autotune.default_tuner`) at trace
time — pure host code over abstract shapes, so an autotuner cache update
can never retrace a compiled steady-state step.

The parity battery (:func:`parity_check`) is the one harness every
registered kernel must pass: pallas-interpret vs lax fallback vs dense
reference on the kernel's own sample inputs, at the contract's declared
tolerances.
"""

from __future__ import annotations

from typing import Dict, Optional

import jax
import numpy as np

from paddle_tpu.kernels import autotune as _autotune
from paddle_tpu.kernels import registry as _registry
from paddle_tpu.observability import registry as _obs_registry

IMPLS = ("auto", "pallas", "pallas_interpret", "lax")

# what "auto" became is otherwise invisible: counted per trace (host
# code — a compiled steady-state step never comes back through here)
_DISPATCHED = _obs_registry.counter(
    "kernel_dispatch_total",
    "kernel dispatches by resolved impl, counted at trace time")


def on_tpu() -> bool:
    """THE TPU probe (was private in four modules). A backend that
    fails to initialise raises here — "no chip" is never read as "use
    the lax path"."""
    return jax.devices()[0].platform == "tpu"


def resolve_impl(impl: str) -> str:
    """Canonical impl name -> concrete backend for this process."""
    if impl not in IMPLS:
        raise ValueError(f"unknown impl {impl!r} (expected "
                         f"{'|'.join(IMPLS)})")
    if impl == "auto":
        return "pallas" if on_tpu() else "lax"
    return impl


def dispatch(name: str, *args, impl: str = "auto",
             block_sizes: Optional[Dict[str, int]] = None,
             tuner: Optional["_autotune.KernelTuner"] = None, **kwargs):
    """Run kernel ``name`` through its registered contract.

    ``block_sizes`` overrides the autotuner (bench sweeps); ``tuner``
    overrides the process-wide cache (tests)."""
    spec = _registry.get(name)
    concrete = resolve_impl(impl)
    _DISPATCHED.inc(kernel=name, impl=concrete)
    if concrete == "lax":
        return spec.lax_fn(*args, **kwargs)
    if block_sizes is None:
        block_sizes = (tuner or _autotune.default_tuner()).get(
            spec, args, kwargs)
    return spec.pallas_fn(*args, block_sizes=dict(block_sizes),
                          interpret=concrete == "pallas_interpret",
                          **kwargs)


def parity_check(name: str, seed: int = 0) -> Dict[str, float]:
    """Run one kernel's parity battery: pallas-interpret and the lax
    fallback against the dense reference on the kernel's sample inputs.
    Returns ``{impl: max_abs_err}``; raises AssertionError outside the
    contract's tolerances."""
    spec = _registry.get(name)
    if spec.parity_fn is not None:     # mesh kernels orchestrate themselves
        return spec.parity_fn(seed)
    args, kwargs = spec.sample_inputs(seed)
    ref = np.asarray(spec.reference_fn(*args, **kwargs), np.float32)
    errs: Dict[str, float] = {}
    for impl in ("lax", "pallas_interpret"):
        out = np.asarray(dispatch(name, *args, impl=impl, **kwargs),
                         np.float32)
        np.testing.assert_allclose(
            out, ref, atol=spec.contract.atol, rtol=spec.contract.rtol,
            err_msg=f"{name}[{impl}] diverged from the dense reference")
        errs[impl] = float(np.max(np.abs(out - ref))) if ref.size else 0.0
    return errs
