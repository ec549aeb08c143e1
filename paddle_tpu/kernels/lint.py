"""Kernel-registry lint: contracts vs lowered HLO, and bypass detection.

Two rules, both wired into ``tools/graph_lint.py``'s framework preset
(so ``tools/run_ci.sh`` gates on them):

- ``kernel-contract`` — for every registered kernel, verify the
  *declared* contract against what actually lowers: the lax fallback
  and the Pallas body must agree on abstract output shape/dtype; sample
  inputs must match the declared layouts' ranks; kernels whose contract
  marks buffers donation-safe must really alias them in the lowered
  HLO (``tf.aliasing_output`` on the donation probe — the serving
  engine's page-donation contract, checked in real StableHLO, not by
  convention); single-device kernels must lower with ZERO collectives;
  and the autotuner's resolved blocks must come from the contract's
  candidate set.
- ``kernel-registry-bypass`` — an AST scan over ``paddle_tpu/ops``,
  ``paddle_tpu/parallel`` and ``paddle_tpu/serving``: every function
  containing a ``pallas_call`` must be a ``pallas_sites`` entry of some
  registered kernel. Deliberate exceptions live in
  ``tools/kernel_registry_allowlist.txt``; entries that match no
  Pallas site are themselves an error (stale allowlist entries rot
  exactly like stale suppressions).
"""

from __future__ import annotations

import ast
import os
from typing import List, Optional, Sequence

import jax

from paddle_tpu.analysis.findings import Finding, Report
from paddle_tpu.kernels import autotune as _autotune
from paddle_tpu.kernels import registry as _registry

_PKG_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEFAULT_SCAN_ROOTS = ("ops", "parallel", "serving")
DEFAULT_ALLOWLIST = os.path.join(os.path.dirname(_PKG_ROOT), "tools",
                                 "kernel_registry_allowlist.txt")


def _layout_rank(layout: str) -> Optional[int]:
    """``"(P,ps,H*Dh) i8" -> 3`` (dims are comma-separated; ``H*Dh`` is
    one folded dim); None when the layout is not dimensioned."""
    if "(" not in layout:
        return None
    body = layout[layout.index("(") + 1:layout.index(")")]
    return len([p for p in body.split(",") if p.strip()])


def _abstract(args):
    return tuple(jax.ShapeDtypeStruct(a.shape, a.dtype)
                 if hasattr(a, "shape") else a for a in args)


def contract_findings(spec, tuner=None) -> List[Finding]:
    """Verify one kernel's declared contract (see module docstring)."""
    out: List[Finding] = []
    loc = f"kernels/{spec.name}"

    def bad(msg, fix=""):
        out.append(Finding("kernel-contract", "error", msg, location=loc,
                           fix=fix, engine="plan"))

    args, kwargs = spec.sample_inputs(0)

    # 1. declared layouts vs sample-input ranks (insertion order)
    for (arg_name, layout), a in zip(spec.contract.arg_layouts.items(),
                                     args):
        rank = _layout_rank(layout)
        if rank is not None and hasattr(a, "ndim") and a.ndim != rank:
            bad(f"arg {arg_name!r} declared {layout} (rank {rank}) but "
                f"sample input has rank {a.ndim}",
                fix="fix the contract's arg_layouts or the kernel's "
                    "sample_inputs — they are the same declared surface")

    # 2. autotuner blocks must come from the declared candidate set
    blocks = (tuner or _autotune.KernelTuner(path=None)).get(
        spec, args, kwargs)
    for bname, bval in blocks.items():
        cands = spec.contract.block_candidates.get(bname)
        if cands is None or bval not in cands:
            bad(f"autotuner resolved {bname}={bval}, outside the "
                f"contract's candidates {cands}",
                fix="extend block_candidates or fix the prior")

    if spec.parity_fn is not None:
        # mesh kernels: the parity battery orchestrates the numerics;
        # the donation contract is still verified on the probe's lowering
        _donation_findings(spec, bad)
        return out

    # 3. lax fallback and Pallas body agree on abstract output
    abstract = _abstract(args)
    try:
        lax_shape = jax.eval_shape(
            lambda *a: spec.lax_fn(*a, **kwargs), *abstract)
        pal_shape = jax.eval_shape(
            lambda *a: spec.pallas_fn(*a, block_sizes=blocks,
                                      interpret=True, **kwargs),
            *abstract)
        lax_flat = [(s.shape, str(s.dtype))
                    for s in jax.tree_util.tree_leaves(lax_shape)]
        pal_flat = [(s.shape, str(s.dtype))
                    for s in jax.tree_util.tree_leaves(pal_shape)]
        if lax_flat != pal_flat:
            bad(f"lax fallback lowers to {lax_flat} but the Pallas body "
                f"lowers to {pal_flat}",
                fix="the two impls are one contract: align their "
                    "output layouts")
    except Exception as e:
        bad(f"abstract evaluation failed: {type(e).__name__}: {e}")

    # 4. single-device kernels must lower with zero collectives
    try:
        from paddle_tpu.analysis import estimate_cost
        cost = estimate_cost(lambda *a: spec.lax_fn(*a, **kwargs),
                             *abstract, name=spec.name)
        if cost.collectives:
            kinds = sorted(cost.collective_kinds())
            bad(f"single-device kernel lowers collectives {kinds}",
                fix="a kernel that syncs devices must be registered "
                    "requires_mesh with its own parity_fn")
    except Exception as e:
        bad(f"cost lowering failed: {type(e).__name__}: {e}")

    # 5. donation contract vs real HLO aliasing
    _donation_findings(spec, bad)
    return out


def _donation_findings(spec, bad):
    """Lower the kernel's donation probe and verify the contract's
    donatable buffers really alias in HLO — ``tf.aliasing_output`` on a
    single-device lowering, ``jax.buffer_donor`` under SPMD (the
    partitioner defers the aliasing decision, jax marks the donor). A
    probe returning None means the box cannot host it: skipped, not
    failed."""
    if spec.contract.donatable and spec.donation_probe is None:
        bad("contract declares donatable buffers but registers no "
            "donation_probe to verify them against lowered HLO")
    if spec.donation_probe is None:
        return
    try:
        probe = spec.donation_probe()
    except Exception as e:
        bad(f"donation probe construction failed: "
            f"{type(e).__name__}: {e}")
        return
    if probe is None:
        return
    fn, pargs, donate = probe
    try:
        txt = jax.jit(fn, donate_argnums=donate).lower(
            *pargs).as_text()
        aliased = (txt.count("tf.aliasing_output")
                   + txt.count("jax.buffer_donor"))
        if aliased < len(donate):
            bad(f"contract marks {spec.contract.donatable} "
                f"donation-safe but the lowered probe aliases only "
                f"{aliased}/{len(donate)} donated buffers",
                fix="something in the kernel breaks XLA's aliasing "
                    "(e.g. a dtype round-trip); fix it or drop the "
                    "donatable declaration")
    except Exception as e:
        bad(f"donation probe failed to lower: "
            f"{type(e).__name__}: {e}")


# ---------------------------------------------------------------------------
# pallas_call bypass scan
# ---------------------------------------------------------------------------

def _pallas_sites_in_file(path: str, module: str) -> List[str]:
    """``module:function`` for every function in ``path`` whose body
    contains a ``pallas_call`` invocation."""
    with open(path) as f:
        tree = ast.parse(f.read(), filename=path)
    sites = []

    class V(ast.NodeVisitor):
        def __init__(self):
            self.stack: List[str] = []

        def _visit_fn(self, node):
            self.stack.append(node.name)
            self.generic_visit(node)
            self.stack.pop()

        visit_FunctionDef = visit_AsyncFunctionDef = _visit_fn

        def visit_Call(self, node):
            fn = node.func
            name = (fn.attr if isinstance(fn, ast.Attribute)
                    else fn.id if isinstance(fn, ast.Name) else "")
            if name == "pallas_call" and self.stack:
                site = f"{module}:{self.stack[0]}"
                if site not in sites:
                    sites.append(site)
            self.generic_visit(node)

    V().visit(tree)
    return sites


def load_allowlist(path: str) -> List[str]:
    entries = []
    if path and os.path.exists(path):
        with open(path) as f:
            for line in f:
                line = line.split("#", 1)[0].strip()
                if line:
                    entries.append(line)
    return entries


def bypass_findings(roots: Sequence[str] = DEFAULT_SCAN_ROOTS,
                    allowlist_path: Optional[str] = None
                    ) -> List[Finding]:
    """Every pallas_call site under ``roots`` must be registered (a
    spec's ``pallas_sites`` entry) or deliberately allowlisted.
    ``allowlist_path=None`` uses the committed default."""
    allowlist_path = allowlist_path or DEFAULT_ALLOWLIST
    _registry.load_all()
    registered = _registry.all_pallas_sites()
    allow = load_allowlist(allowlist_path)
    used_allow: set = set()
    out: List[Finding] = []
    for root in roots:
        base = os.path.join(_PKG_ROOT, root)
        for dirpath, _dirs, files in os.walk(base):
            for fname in sorted(files):
                if not fname.endswith(".py"):
                    continue
                path = os.path.join(dirpath, fname)
                rel = os.path.relpath(path, os.path.dirname(_PKG_ROOT))
                module = rel[:-3].replace(os.sep, ".")
                for site in _pallas_sites_in_file(path, module):
                    if site in registered:
                        continue
                    if site in allow:
                        used_allow.add(site)
                        continue
                    out.append(Finding(
                        "kernel-registry-bypass", "error",
                        f"pallas_call in {site} bypasses the kernel "
                        "registry: no registered kernel claims this "
                        "site", location=site,
                        fix="register the kernel in paddle_tpu/kernels "
                            "(pallas_sites=...) or add a justified "
                            "entry to tools/"
                            "kernel_registry_allowlist.txt",
                        engine="ast"))
    for entry in allow:
        if entry not in used_allow:
            out.append(Finding(
                "kernel-registry-bypass", "error",
                f"stale allowlist entry {entry!r} matches no pallas_call "
                "site", location=allowlist_path,
                fix="delete it — dead entries would silently re-accept "
                    "a future bypass", engine="ast"))
    return out


def lint_registry(suppressions=None,
                  allowlist_path: Optional[str] = None) -> Report:
    """The full kernel-registry report: per-kernel contract checks +
    the bypass scan (``tools/graph_lint.py`` preset surface)."""
    _registry.load_all()
    report = Report("kernel_registry", suppressions=suppressions)
    tuner = _autotune.KernelTuner(path=None)
    for name in _registry.names():
        report.extend(contract_findings(_registry.get(name), tuner=tuner))
    # the COMMITTED manifest production dispatch resolves from must be
    # valid too: stale versions, unknown kernels, or out-of-candidate
    # blocks (get() refuses them at runtime, but CI should say so)
    committed = _autotune.KernelTuner(_autotune.DEFAULT_CACHE_PATH)
    for key in committed.stale_entries():
        report.add(Finding(
            "kernel-contract", "error",
            f"committed tune-cache entry {key!r} is dead (stale "
            "contract version, unknown kernel, or blocks outside the "
            "candidate set)", location=_autotune.DEFAULT_CACHE_PATH,
            fix="reseed: python -m paddle_tpu.kernels.autotune --seed",
            engine="plan"))
    report.extend(bypass_findings(allowlist_path=allowlist_path))
    report.count_into_registry()
    return report
