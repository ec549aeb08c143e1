"""HLO-level lint rules over a :class:`~paddle_tpu.analysis.cost_model.
CostReport`, plus the bucket-coverage proof for the serving engines.

The third tier of the static-analysis stack (AST → jaxpr → HLO): these
rules fire on hazards only visible in the *lowered* program —

- **unexpected-collective** — collectives outside a declared allowlist.
  A single-device serving decode/prefill step must contain zero; on a
  tensor-parallel mesh only the planned kinds (e.g. the tp all-reduce
  after sharded attention) are acceptable, and anything else is an
  implicit cross-device sync the sharding specs accidentally created.
- **resharding-churn** — adjacent sharding annotations that disagree on
  a large value's layout, forcing an implicit transpose/all-to-all
  between them (detected as constraint→constraint chains by the
  cost walker).
- **peak-hbm-budget** — the liveness-based peak-HBM estimate exceeds
  the preset's declared budget.
- **flops budget** (reported as ``cost-regression``) — static flops
  exceed the declared budget.
- **bucket-coverage** — the ahead-of-time half of the zero-recompile
  invariant: statically enumerate every pow2 bucket signature the
  engine's steady-state loop can request and prove ``warmup()``'s
  precompile plan covers it. The reachable set is derived from the
  *step-side* bucketing functions and the warmed set from the
  *warmup-side* plan — two independent derivations, so a drift in
  either fires the rule before the first mid-serving recompile.
"""

from __future__ import annotations

from typing import List, Optional, Sequence, Set, Tuple

from paddle_tpu.analysis.findings import Finding, RULES


def _mb(n: int) -> str:
    return f"{n / (1 << 20):.2f}MiB"


def lint_cost_report(cost, *,
                     collective_allowlist: Optional[Sequence[str]] = None,
                     hbm_budget_bytes: Optional[int] = None,
                     flops_budget: Optional[int] = None) -> List[Finding]:
    """Findings for one :class:`CostReport`.

    ``collective_allowlist``: ``None`` skips the collective check
    entirely; a sequence (possibly empty — the single-device serving
    contract) permits exactly those kinds. ``hbm_budget_bytes`` /
    ``flops_budget``: ``None`` skips that budget."""
    findings: List[Finding] = []
    if collective_allowlist is not None:
        allowed = set(collective_allowlist)
        for kind, nbytes in sorted(cost.collective_kinds().items()):
            if kind in allowed:
                continue
            sites = [c for c in cost.collectives if c.kind == kind]
            ax = sorted({c.axis for c in sites if c.axis})
            findings.append(Finding(
                "unexpected-collective", RULES["unexpected-collective"][0],
                f"{len(sites)} `{kind}` op(s) moving {_mb(nbytes)} "
                f"{'over axis ' + '/'.join(ax) + ' ' if ax else ''}"
                f"in the lowered program, outside the allowlist "
                f"{sorted(allowed) or '(none)'}",
                location=sites[0].location,
                fix="fix the sharding specs that force the implicit "
                    "collective, or declare it in the surface's "
                    "allowlist if the comm is intended",
                engine="hlo"))
    for site in cost.resharding:
        findings.append(Finding(
            "resharding-churn", RULES["resharding-churn"][0],
            f"a {_mb(site.bytes)} value is resharded "
            f"{site.src} -> {site.dst} between adjacent sharding "
            "annotations: the compiler inserts an implicit "
            "transpose/all-to-all here every step",
            location=site.location,
            fix="make the adjacent with_sharding_constraint specs "
                "agree, or reorder the computation so the layout "
                "changes once",
            engine="hlo"))
    if hbm_budget_bytes is not None and \
            cost.peak_hbm_bytes > hbm_budget_bytes:
        findings.append(Finding(
            "peak-hbm-budget", RULES["peak-hbm-budget"][0],
            f"static peak-HBM estimate {_mb(cost.peak_hbm_bytes)} "
            f"exceeds the declared budget {_mb(hbm_budget_bytes)}",
            location=cost.name,
            fix="donate the large buffers (cuts old+new copies), shrink "
                "the surface, or raise the committed budget with a "
                "rationale",
            engine="hlo"))
    if flops_budget is not None and cost.total_flops > flops_budget:
        findings.append(Finding(
            "cost-regression", RULES["cost-regression"][0],
            f"static flops {cost.total_flops:,} exceed the declared "
            f"budget {flops_budget:,}",
            location=cost.name,
            fix="profile what grew (CostReport.per_op names the op), or "
                "raise the committed budget with a rationale",
            engine="hlo"))
    return findings


# ---------------------------------------------------------------------------
# bucket coverage: reachable signatures vs the warmup plan
# ---------------------------------------------------------------------------

def _coverage_findings(reachable: Set[Tuple], warmed: Set[Tuple],
                       name: str, engine_kind: str) -> List[Finding]:
    findings = []
    for sig in sorted(reachable - warmed, key=str):
        findings.append(Finding(
            "bucket-coverage", RULES["bucket-coverage"][0],
            f"{engine_kind} bucket signature {sig} is statically "
            "reachable by the steady-state loop but missing from "
            "warmup's precompile plan: the first request hitting it "
            "recompiles mid-serving",
            location=f"{name}:{sig}",
            fix="align warmup()'s bucket enumeration with the step-side "
                "bucketing (warmup_plan() must cover every reachable "
                "signature)",
            engine="hlo"))
    return findings


def serving_bucket_coverage(engine, warmed: Optional[Set[Tuple]] = None,
                            name: str = "serving") -> List[Finding]:
    """Prove ``ServingEngine.warmup()`` precompiles every decode/prefill
    signature ``step()`` can request.

    Reachable signatures are enumerated from the *step-side* bucketing
    (``_pow2_width`` over every live page count, ``_pow2_count`` over
    every in-prefill slot count); the warmed set defaults to the
    *warmup-side* :meth:`ServingEngine.warmup_plan`. Pass ``warmed``
    explicitly to audit a doctored or partial warmup (the tests do)."""
    if warmed is None:
        warmed = set(engine.warmup_plan())
    return _coverage_findings(set(engine.reachable_signatures()),
                              set(warmed), name, "serving")


def embedding_bucket_coverage(cache, max_uniq: int,
                              warmed: Optional[Set[Tuple]] = None,
                              name: str = "embedding"
                              ) -> List[Finding]:
    """Prove ``DeviceEmbeddingCache.warmup(max_uniq)`` precompiles every
    gather/install width a batch with up to ``max_uniq`` unique ids can
    request (same two-sided derivation as the serving variant)."""
    if warmed is None:
        warmed = set(cache.warmup_plan(max_uniq))
    return _coverage_findings(set(cache.reachable_buckets(max_uniq)),
                              set(warmed), name, "embedding")


def check_bucket_coverage(engine, *, max_uniq: Optional[int] = None,
                          warmed: Optional[Set[Tuple]] = None,
                          name: Optional[str] = None) -> List[Finding]:
    """Dispatch on engine type: a token-serving engine (has
    ``reachable_signatures``) or an embedding cache/engine (needs
    ``max_uniq``)."""
    if hasattr(engine, "reachable_signatures"):
        return serving_bucket_coverage(engine, warmed,
                                       name or "serving")
    cache = getattr(engine, "cache", engine)
    if max_uniq is None:
        raise ValueError("embedding coverage needs max_uniq (the "
                         "warmup's per-batch unique-id bound)")
    return embedding_bucket_coverage(cache, max_uniq, warmed,
                                     name or "embedding")
