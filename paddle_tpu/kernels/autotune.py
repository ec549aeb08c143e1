"""Shared block-size autotuner: measure-and-cache per (kernel, bucket).

Every registered kernel's tunable block sizes resolve through one
:class:`KernelTuner`:

- **Key** — ``kernel|v<contract-version>|<shape bucket>|<dtype>|<device
  kind>``. Shape dims are bucketed to the next power of two, so one
  cache entry covers a whole serving bucket family and the key is a
  deterministic function of the *abstract* call signature (tracers
  only contribute shape/dtype — resolution happens at trace time and
  can never retrace a steady-state step).
- **Prior** — on a cache miss the tuner does NOT guess blindly: a
  static prior picks the largest candidate block config whose VMEM
  working set (``spec.vmem_estimate``) fits the per-core budget; the
  offline ``--seed`` CLI additionally lowers the kernel's lax fallback
  through the PR 7 static cost model (:func:`analysis.estimate_cost`)
  and stamps the entry with the measured flops / traffic bytes /
  arithmetic intensity, so the committed cache starts near-optimal and
  CI never tunes from scratch.
- **Measurement** — :meth:`KernelTuner.measure` times each candidate on
  the live backend (``bench.py --model kernels``) and caches the best.
- **Persistence** — ``tools/kernel_tune.json`` is committed the way
  ``api_spec.txt`` is: regenerate with
  ``python -m paddle_tpu.kernels.autotune --seed`` and commit alongside
  any PR that changes a kernel's contract version or candidate set.
  Entries whose ``contract_version`` no longer matches the registered
  kernel are *stale*: detected, counted, and ignored (a cold cache is
  correct, just slower to warm).
"""

from __future__ import annotations

import argparse
import itertools
import json
import os
import time
from typing import Any, Dict, Optional, Tuple

import jax

from paddle_tpu.kernels import registry as _registry

#: committed cache (kept beside api_spec/cost_budgets — tools/ is the
#: home of every frozen-artifact manifest)
DEFAULT_CACHE_PATH = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), "tools", "kernel_tune.json")

#: per-core VMEM budget the static prior fits blocks into; TPU cores
#: have ~16 MiB — leave headroom for double buffering
VMEM_BUDGET_BYTES = 12 << 20

_SCHEMA_VERSION = 1


def next_pow2(n: int) -> int:
    n = max(int(n), 1)
    return 1 << (n - 1).bit_length()


def device_kind() -> str:
    return jax.devices()[0].device_kind.replace(" ", "_")


def tune_key(spec, args, kwargs) -> str:
    """Deterministic cache key for one abstract call signature."""
    if spec.tune_signature is not None:
        dims = spec.tune_signature(args, kwargs)
    else:
        dims = tuple(
            (f"a{i}d{j}", d)
            for i, a in enumerate(args) if hasattr(a, "shape")
            for j, d in enumerate(a.shape))
    bucket = "x".join(f"{label}{next_pow2(d)}" for label, d in dims)
    dtypes = "-".join(sorted({str(a.dtype) for a in args
                              if hasattr(a, "dtype")}))
    return (f"{spec.name}|v{spec.contract.version}|{bucket}|{dtypes}|"
            f"{device_kind()}")


def candidate_grid(contract) -> Tuple[Dict[str, int], ...]:
    """Every block config in the contract's candidate cartesian."""
    names = sorted(contract.block_candidates)
    if not names:
        return ({},)
    return tuple(dict(zip(names, vals)) for vals in itertools.product(
        *(contract.block_candidates[n] for n in names)))


def static_prior(spec, args, kwargs,
                 budget_bytes: int = VMEM_BUDGET_BYTES) -> Dict[str, int]:
    """Largest candidate block config whose VMEM working set fits the
    budget — the 'start near-optimal' seed for the measured search.
    Host-side and abstract-shape-only, so it is safe at trace time."""
    if not spec.contract.block_candidates:
        return {}

    def score(cand):
        s = 1
        for v in cand.values():
            s *= int(v)
        return s

    grid = candidate_grid(spec.contract)
    fits = []
    for cand in grid:
        if spec.vmem_estimate is not None:
            try:
                vmem = int(spec.vmem_estimate(args, kwargs, cand))
            except Exception:
                continue  # broken estimator reads as does-NOT-fit: an
                # error must never promote the largest working set
            if vmem > budget_bytes:
                continue
        fits.append(cand)
    if fits:
        return dict(max(fits, key=score))
    # nothing fits the budget: take the SMALLEST working set, not the
    # default (which the kernels order largest-first) — when VMEM is the
    # problem, the biggest blocks are the worst possible guess
    return dict(min(grid, key=score))


class KernelTuner:
    """Measure-and-cache block sizes, persisted like api_spec.txt.

    ``path=None`` is a pure in-memory tuner (tests, bench measuring);
    :func:`default_tuner` wires the committed ``tools/kernel_tune.json``.
    """

    def __init__(self, path: Optional[str] = None):
        self.path = path
        self.entries: Dict[str, dict] = {}
        self.hits = 0
        self.misses = 0
        self.stale = 0
        if path and os.path.exists(path):
            self.load(path)

    # -- persistence --------------------------------------------------------
    def load(self, path: str):
        with open(path) as f:
            data = json.load(f)
        if int(data.get("schema_version", 0)) != _SCHEMA_VERSION:
            return  # incompatible manifest: treat as cold cache
        self.entries.update(data.get("entries", {}))

    def save(self, path: Optional[str] = None):
        path = path or self.path
        manifest = {
            "_comment": [
                "Committed block-size cache for the shared kernel "
                "autotuner (paddle_tpu/kernels/autotune.py).",
                "Regenerate: python -m paddle_tpu.kernels.autotune "
                "--seed   (static-cost priors, no hardware)",
                "or refresh measured entries via bench.py --model "
                "kernels on the target device.",
                "Keys are kernel|v<contract>|<pow2 bucket>|<dtype>|"
                "<device kind>; entries with a stale contract_version "
                "are ignored at load and should be deleted.",
            ],
            "schema_version": _SCHEMA_VERSION,
            "entries": {k: self.entries[k] for k in sorted(self.entries)},
        }
        with open(path, "w") as f:
            json.dump(manifest, f, indent=1, sort_keys=False)
            f.write("\n")

    # -- resolution (trace-time safe) --------------------------------------
    def get(self, spec, args=(), kwargs=None) -> Dict[str, int]:
        """Resolve block sizes for one call signature. Pure host code on
        abstract shapes: called during tracing, never from compiled
        code, so tuning can never cause a steady-state recompile."""
        kwargs = kwargs or {}
        if not spec.contract.block_candidates:
            return {}
        key = tune_key(spec, args, kwargs)
        ent = self.entries.get(key)
        if ent is not None:
            blocks = ent.get("blocks", {})
            valid = (
                int(ent.get("contract_version", -1)) ==
                spec.contract.version
                and all(blocks.get(b) in c for b, c in
                        spec.contract.block_candidates.items()))
            if valid:
                self.hits += 1
                return dict(blocks)
            # version bump OR out-of-candidate blocks (hand-edited /
            # corrupt manifest): the entry is dead — re-derive, never
            # run an out-of-contract block config
            self.stale += 1
        self.misses += 1
        blocks = static_prior(spec, args, kwargs)
        self.entries[key] = {
            "blocks": blocks,
            "source": "prior",
            "contract_version": spec.contract.version,
        }
        return dict(blocks)

    # -- measurement (bench-time only) --------------------------------------
    def measure(self, spec, args, kwargs=None, *, impl: str = "pallas",
                reps: int = 3, candidates=None) -> dict:
        """Time every candidate block config and cache the winner.
        Returns ``{"blocks", "timings_s", "default_blocks",
        "default_s", "best_s"}``. Never called from traced code."""
        from paddle_tpu.kernels import harness
        kwargs = dict(kwargs or {})
        key = tune_key(spec, args, kwargs)
        default = static_prior(spec, args, kwargs)
        timings: Dict[str, float] = {}
        best_blocks, best_t = default, float("inf")
        for cand in (candidates or candidate_grid(spec.contract)):
            t = _time_call(
                lambda: harness.dispatch(spec.name, *args, impl=impl,
                                         block_sizes=cand, **kwargs),
                reps=reps)
            timings[json.dumps(cand, sort_keys=True)] = t
            if t < best_t:
                best_blocks, best_t = dict(cand), t
        self.entries[key] = {
            "blocks": best_blocks,
            "source": "measured",
            "contract_version": spec.contract.version,
            "timings_s": {k: round(v, 6) for k, v in timings.items()},
        }
        return {"blocks": best_blocks, "timings_s": timings,
                "default_blocks": default,
                "default_s": timings.get(
                    json.dumps(default, sort_keys=True), best_t),
                "best_s": best_t}

    def stats(self) -> Dict[str, int]:
        return {"hits": self.hits, "misses": self.misses,
                "stale": self.stale, "entries": len(self.entries)}

    def stale_entries(self) -> list:
        """Keys that are dead: kernel unknown to the registry, recorded
        contract_version behind the registered contract, or blocks
        outside the contract's candidate set (hand-edited / corrupt
        manifest). THE validity rule — ``get()``, ``purge_stale``, the
        bench gate, and the registry lint all read it; don't re-derive
        it elsewhere."""
        _registry.load_all()
        dead = []
        for key, ent in self.entries.items():
            name = key.split("|", 1)[0]
            try:
                spec = _registry.get(name)
            except KeyError:
                dead.append(key)
                continue
            blocks = ent.get("blocks", {})
            if int(ent.get("contract_version", -1)) != \
                    spec.contract.version or \
                    not all(blocks.get(b) in c for b, c in
                            spec.contract.block_candidates.items()):
                dead.append(key)
        return dead

    def purge_stale(self) -> int:
        """Drop every stale entry (see :meth:`stale_entries`); returns
        how many were dropped. ``--seed`` calls this so a contract-
        version bump + reseed really clears the stale-entry CI gate
        (old-version keys would otherwise persist forever)."""
        dead = self.stale_entries()
        for key in dead:
            del self.entries[key]
        return len(dead)


def _time_call(fn, reps: int) -> float:
    out = fn()
    jax.block_until_ready(out)        # warmup compile excluded
    t0 = time.perf_counter()
    for _ in range(max(reps, 1)):
        out = fn()
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / max(reps, 1)


_DEFAULT: Optional[KernelTuner] = None


def default_tuner() -> KernelTuner:
    """Process-wide tuner over the committed cache."""
    global _DEFAULT
    if _DEFAULT is None:
        _DEFAULT = KernelTuner(DEFAULT_CACHE_PATH)
    return _DEFAULT


def set_default_tuner(tuner: Optional[KernelTuner]) -> Optional[KernelTuner]:
    """Swap the process-wide tuner (tests); returns the previous one."""
    global _DEFAULT
    prev, _DEFAULT = _DEFAULT, tuner
    return prev


# ---------------------------------------------------------------------------
# offline seeding: static-cost priors from the PR 7 cost model
# ---------------------------------------------------------------------------

def seed_entry(tuner: KernelTuner, spec, args, kwargs=None) -> str:
    """Seed one bucket's entry with the VMEM-fit prior, stamped with the
    lax fallback's static CostReport (flops / traffic bytes /
    arithmetic intensity) so the committed cache records WHY the prior
    was chosen. Lowering only — nothing executes."""
    kwargs = dict(kwargs or {})
    key = tune_key(spec, args, kwargs)
    existing = tuner.entries.get(key)
    if existing is not None and existing.get("source") == "measured" \
            and int(existing.get("contract_version", -1)) == \
            spec.contract.version:
        return key    # a current measured entry beats a re-derived prior
    blocks = static_prior(spec, args, kwargs)
    entry: Dict[str, Any] = {
        "blocks": blocks,
        "source": "prior",
        "contract_version": spec.contract.version,
    }
    try:
        from paddle_tpu import analysis
        abstract = tuple(
            jax.ShapeDtypeStruct(a.shape, a.dtype)
            if hasattr(a, "shape") else a for a in args)
        cost = analysis.estimate_cost(
            lambda *a: spec.lax_fn(*a, **kwargs), *abstract,
            name=spec.name)
        entry["cost_prior"] = {
            "flops": int(cost.total_flops),
            "traffic_bytes": int(cost.traffic_bytes),
            "arithmetic_intensity": round(
                cost.total_flops / max(cost.traffic_bytes, 1), 3),
        }
    except Exception as e:  # mesh kernels etc.: prior stands without cost
        entry["cost_prior"] = {"error": f"{type(e).__name__}: {e}"}
    tuner.entries[key] = entry
    return key


def seed_default_buckets(tuner: KernelTuner) -> Dict[str, str]:
    """Seed the canonical serving/training buckets for every registered
    kernel (the shapes the bench and the serving engine actually hit)."""
    _registry.load_all()
    seeded = {}
    for name in _registry.names():
        spec = _registry.get(name)
        if not spec.contract.block_candidates or spec.requires_mesh:
            continue               # mesh kernels inherit the inner kernel
        for seed in (0, 1, 2):     # 3 shape buckets per kernel
            args, kwargs = spec.sample_inputs(seed)
            seeded[seed_entry(tuner, spec, args, kwargs)] = name
        # tp-local twins: the tp-sharded wrappers dispatch THIS kernel
        # per shard at H/tp head counts — those buckets must resolve
        # from the committed manifest too, or every tp mesh starts on
        # an unseeded prior
        for variant in spec.tune_sample_variants:
            for seed in (0, 1, 2):
                sample = variant(seed)
                if sample is None:
                    continue       # head count not divisible by this tp
                v_args, v_kwargs = sample
                seeded[seed_entry(tuner, spec, v_args, v_kwargs)] = name
    return seeded


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="Seed/refresh the committed kernel-tune cache")
    ap.add_argument("--seed", action="store_true",
                    help="seed canonical buckets with static-cost priors")
    ap.add_argument("--out", default=DEFAULT_CACHE_PATH)
    args = ap.parse_args(argv)
    if not args.seed:
        ap.error("nothing to do (pass --seed)")
    jax.config.update("jax_platforms", "cpu")  # pure lowering, no TPU
    tuner = KernelTuner(args.out if os.path.exists(args.out) else None)
    tuner.path = args.out
    purged = tuner.purge_stale()
    seeded = seed_default_buckets(tuner)
    tuner.save(args.out)
    print(f"seeded {len(seeded)} bucket(s), purged {purged} stale "
          f"-> {args.out}")
    return 0


if __name__ == "__main__":  # pragma: no cover
    import sys
    sys.exit(main())
