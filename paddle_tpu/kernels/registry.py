"""Kernel registry: one declared contract per Pallas kernel.

Before this layer existed, four hand-tuned Pallas kernels (flash
attention, ring attention, ragged paged decode, ragged paged prefill)
each carried a private block-size heuristic, a private interpret-mode
shim, and a private lax fallback — every new kernel variant (tensor-
parallel sharding, dequant-attend, speculative verify) would have become
a fifth bespoke module. Tensor Processing Primitives (PAPERS.md) argues
for exactly one microkernel-abstraction layer; TPU-MLIR's lowering
discipline motivates checking kernel contracts statically instead of by
convention. This module is that layer's spine:

- :class:`KernelContract` — the *declared* contract: layouts, donation-
  safety, grid/block constraints, tunable block parameters with their
  candidate sets, parity tolerances, and a version (bumped on any
  numerics or layout change — the autotuner rejects stale cache entries
  by it).
- :class:`KernelSpec` — one registered kernel: its Pallas body, its lax
  fallback (identical numerics, runs anywhere), a dense reference for
  the parity battery, a sample-input factory, and the source sites that
  are allowed to contain ``pallas_call`` (``tools/graph_lint.py``'s
  kernel-registry rule fails any Pallas call in ``ops/``, ``parallel/``
  or ``serving/`` outside these sites).
- :func:`register` / :func:`get` / :func:`names` / :func:`load_all` —
  the registry itself. Kernels register from their home modules at
  import time; :func:`load_all` imports every home module so tools and
  tests can iterate the full registry.

Dispatch lives in :mod:`~paddle_tpu.kernels.harness`; block-size
resolution in :mod:`~paddle_tpu.kernels.autotune`.
"""

from __future__ import annotations

import dataclasses
import importlib
from typing import Any, Callable, Dict, Mapping, Optional, Tuple


@dataclasses.dataclass(frozen=True)
class KernelContract:
    """The declared (statically checkable) contract of one kernel.

    ``version`` participates in every autotuner cache key: bump it when
    the kernel's numerics, layouts, or block semantics change and every
    persisted tuning entry for the old kernel becomes stale (detected,
    reported, and re-derived — never silently reused).
    """

    version: int
    #: arg name -> layout string, e.g. ``"(B,H,S,D)"`` / ``"(S,mp) i32"``
    arg_layouts: Mapping[str, str]
    out_layout: str
    #: args that must stay donation-safe through an update-then-attend
    #: step (the serving engine donates its KV pages INTO the jitted
    #: step that calls this kernel) — verified against the lowered HLO's
    #: ``tf.aliasing_output`` by the kernel-registry lint rule
    donatable: Tuple[str, ...] = ()
    grid: str = ""
    #: tunable block parameter -> candidate values. The static prior
    #: resolves the LARGEST-product candidate that fits the VMEM budget
    #: (smallest when nothing fits) — ordering within the tuple carries
    #: no default semantics; `default_blocks()` (first entry) exists for
    #: display/reference only.
    block_candidates: Mapping[str, Tuple[int, ...]] = \
        dataclasses.field(default_factory=dict)
    #: parity-battery tolerances (pallas-interpret vs lax vs reference)
    atol: float = 1e-5
    rtol: float = 1e-5

    def default_blocks(self) -> Dict[str, int]:
        return {k: v[0] for k, v in self.block_candidates.items()}


@dataclasses.dataclass
class KernelSpec:
    """One registered kernel behind the shared dispatch/fallback layer.

    ``pallas_fn(*args, block_sizes=..., interpret=..., **kw)`` runs the
    Pallas body (interpret mode reuses the SAME body on CPU);
    ``lax_fn(*args, **kw)`` is the XLA-composed fallback with identical
    numerics; ``reference_fn`` is the dense reference the parity battery
    compares both against. ``sample_inputs(seed)`` returns
    ``(args, kwargs)`` small enough for CPU CI.
    """

    name: str
    contract: KernelContract
    pallas_fn: Callable[..., Any]
    lax_fn: Callable[..., Any]
    reference_fn: Callable[..., Any]
    sample_inputs: Callable[[int], Tuple[tuple, dict]]
    #: ``"module:function"`` sites allowed to contain ``pallas_call``
    pallas_sites: Tuple[str, ...] = ()
    #: needs a device mesh (parity/lint run it under one; the bench may
    #: skip it on single-device boxes)
    requires_mesh: bool = False
    #: dims of the tuning key, derived from the call args:
    #: ``tune_signature(args, kwargs) -> ((label, int_dim), ...)``
    tune_signature: Optional[Callable[..., Tuple[Tuple[str, int], ...]]] = \
        None
    #: VMEM working-set estimate (bytes) for a candidate block config —
    #: the static prior picks the largest candidate that fits budget
    vmem_estimate: Optional[Callable[..., int]] = None
    #: optional ``() -> (fn, args, donate_argnums)`` probe lowered by the
    #: lint rule to verify the donation contract in real HLO. A probe
    #: may return None when the box cannot host it — the check is
    #: skipped, not failed
    donation_probe: Optional[Callable[[], Optional[Tuple[
        Callable, tuple, Tuple[int, ...]]]]] = None
    #: extra ``seed -> (args, kwargs) | None`` sample factories the
    #: offline ``--seed`` CLI tunes IN ADDITION to ``sample_inputs`` —
    #: the tp-sharded wrappers dispatch this kernel per shard at H/tp
    #: head counts, and these keep the committed manifest covering
    #: those buckets (None = the variant does not apply to that seed)
    tune_sample_variants: Tuple[Callable[[int], Optional[Tuple[
        tuple, dict]]], ...] = ()
    #: optional custom parity check ``(seed) -> {impl: max_abs_err}``
    #: (mesh kernels need their own orchestration)
    parity_fn: Optional[Callable[[int], Dict[str, float]]] = None


_REGISTRY: Dict[str, KernelSpec] = {}

#: home modules that register kernels at import time
_HOME_MODULES = (
    "paddle_tpu.ops.attention",
    "paddle_tpu.serving.decode_attention",
    "paddle_tpu.serving.sparse_attention",
    "paddle_tpu.ops.grouped_ffn",
    "paddle_tpu.ops.ssm_scan",
    "paddle_tpu.ops.gated_delta",
    "paddle_tpu.parallel.ring_attention",
)


def register(spec: KernelSpec) -> KernelSpec:
    """Idempotent (module reloads re-register the same spec)."""
    _REGISTRY[spec.name] = spec
    return spec


def get(name: str) -> KernelSpec:
    if name not in _REGISTRY:
        load_all()
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(f"no kernel {name!r} registered "
                       f"(have: {', '.join(sorted(_REGISTRY)) or 'none'})")


def names() -> Tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


def load_all() -> Tuple[str, ...]:
    """Import every kernel home module (registration is an import-time
    side effect there) and return the registered names."""
    for mod in _HOME_MODULES:
        importlib.import_module(mod)
    return names()


def all_pallas_sites() -> Dict[str, str]:
    """``"module:function" -> kernel name`` over the whole registry —
    the allow-set the kernel-registry lint rule checks Pallas call
    sites against."""
    sites: Dict[str, str] = {}
    for spec in _REGISTRY.values():
        for site in spec.pallas_sites:
            sites[site] = spec.name
    return sites
