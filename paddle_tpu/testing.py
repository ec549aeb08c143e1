"""OpTest harness: numeric kernel + gradient checking.

Parity with the reference's backbone test infrastructure
(``python/paddle/fluid/tests/unittests/op_test.py:135`` — OpTest with
``check_output_with_place`` and finite-difference ``check_grad_with_place``).
TPU-native version: an op is a JAX function; outputs are compared against the
registered NumPy reference, and analytic grads (jax.grad) are compared
against central finite differences.
"""

from __future__ import annotations

from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np


def _is_traceable(a):
    if isinstance(a, (np.ndarray, jnp.ndarray)):
        return True
    if isinstance(a, (list, tuple)) and a and all(
            isinstance(e, (np.ndarray, jnp.ndarray)) for e in a):
        return True
    return False


def check_output(op_fn: Callable, reference: Callable, args, kwargs=None,
                 rtol=1e-5, atol=1e-6):
    """Run op under jit and compare against the NumPy reference.

    Array args are traced; everything else (shapes, axes, dtypes) stays
    static, as it would in real jitted code.
    """
    kwargs = kwargs or {}
    traced_idx = [i for i, a in enumerate(args) if _is_traceable(a)]

    def wrapper(*traced):
        full = list(args)
        for i, t in zip(traced_idx, traced):
            full[i] = t
        return op_fn(*full, **kwargs)

    got = jax.jit(wrapper)(*[args[i] for i in traced_idx])
    want = reference(*args, **kwargs)
    got_leaves = jax.tree_util.tree_leaves(got)
    want_leaves = jax.tree_util.tree_leaves(want)
    assert len(got_leaves) == len(want_leaves), (
        f"output arity {len(got_leaves)} vs reference {len(want_leaves)}")
    for g, w in zip(got_leaves, want_leaves):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=rtol, atol=atol)


def numeric_grad(f: Callable, args: Sequence, wrt: int = 0, eps=1e-3):
    """Central finite differences of sum(f(args)) w.r.t. args[wrt]
    (parity with op_test.py get_numeric_gradient)."""
    args = [np.asarray(a, np.float64) if hasattr(a, "dtype") and
            np.issubdtype(np.asarray(a).dtype, np.floating)
            else a for a in args]
    x = np.array(args[wrt], np.float64)
    grad = np.zeros_like(x)
    it = np.nditer(x, flags=["multi_index"])
    while not it.finished:
        idx = it.multi_index
        orig = x[idx]
        x[idx] = orig + eps
        args[wrt] = x
        hi = float(np.sum(np.asarray(f(*args), np.float64)))
        x[idx] = orig - eps
        args[wrt] = x
        lo = float(np.sum(np.asarray(f(*args), np.float64)))
        x[idx] = orig
        grad[idx] = (hi - lo) / (2 * eps)
        it.iternext()
    args[wrt] = x
    return grad


def check_grad(op_fn: Callable, args, wrt=(0,), kwargs=None, eps=1e-3,
               rtol=5e-3, atol=1e-3):
    """Compare jax.grad against finite differences for each input in wrt.

    Uses float64-on-CPU finite differences of the f32 op — tolerances sized
    accordingly (reference uses max_relative_error=0.005 typically).
    """
    kwargs = kwargs or {}

    def scalar_f(*a):
        return jnp.sum(op_fn(*a, **kwargs))

    for i in wrt:
        analytic = jax.grad(scalar_f, argnums=i)(*[jnp.asarray(a) for a in args])
        numeric = numeric_grad(lambda *a: op_fn(*a, **kwargs), list(args),
                               wrt=i, eps=eps)
        np.testing.assert_allclose(np.asarray(analytic), numeric,
                                   rtol=rtol, atol=atol,
                                   err_msg=f"grad mismatch wrt arg {i}")


def assert_trees_close(a, b, rtol=1e-5, atol=1e-6):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=rtol, atol=atol)


def subprocess_env(repo_on_path: bool = True):
    """Environment for worker subprocesses spawned by tests and
    launchers: the parent's, with the repo on PYTHONPATH."""
    import os

    env = dict(os.environ)
    if repo_on_path:
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        extra = env.get("PYTHONPATH", "")
        env["PYTHONPATH"] = repo + (os.pathsep + extra if extra else "")
    return env
