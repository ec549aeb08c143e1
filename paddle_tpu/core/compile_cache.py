"""JAX's persistent compilation cache, placed from outside or at ONE
fixed path.

Called by the entry points that run on the chip (``chip_smoke.py``,
``bench.py``, the bench tools) — never at ``import paddle_tpu`` and
never under pytest. The cache directory is part of every entry's key,
so it must not move between runs: no temporary name, pid or timestamp.
"""

from __future__ import annotations

import os
from typing import Optional

import jax

#: the one in-checkout location (listed in .gitignore)
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_compile_cache() -> Optional[str]:
    """Turn the persistent compile cache on for a run on the chip and
    return its directory.

    ``JAX_COMPILATION_CACHE_DIR`` set: JAX already reads it into its own
    config — leave that alone, set nothing. Unset: the cache goes to
    :data:`DEFAULT_DIR` inside the checkout. On any backend but a TPU
    (the CPU smokes of ``tools/run_ci.sh``) nothing is switched on and
    None is returned: such a run compiles little, and what it would
    cache is of no use to the chip."""
    if jax.devices()[0].platform != "tpu":
        return None
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
