"""Thin wrappers over the installed JAX's public spellings.

There is one installation (the same JAX here and on the chip machine),
so these are not version shims: they pin the defaults this repo wants.
"""

from __future__ import annotations

import jax


def shard_map(f, *, mesh, in_specs, out_specs, check_vma: bool = False):
    """``jax.shard_map`` with ``check_vma=False`` by default: the
    callers take logically-replicated inputs whose axis-invariance the
    varying-axes checker cannot prove."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)


def axis_size(axis) -> int:
    """The STATIC size of a mapped mesh axis from inside shard_map —
    callers use it in Python control flow (``range(n)``), so it must be
    a concrete int, not a traced psum."""
    return jax.lax.axis_size(axis)
