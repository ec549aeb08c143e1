"""Jaxpr analyzer: static hazard detection over a traced step function.

Walks a closed jaxpr (recursing through pjit / scan / while / cond /
remat (``jax.checkpoint``) / custom-derivative sub-jaxprs — remat
bodies are stored as OPEN jaxprs and need their own unwrap) and emits
findings for the TPU failure
modes that are statically visible before a single step runs:

- **host-callback / debug-callback** — ``pure_callback`` / ``io_callback``
  / ``debug_callback`` equations: each is a device→host→device round trip
  in the compiled step (the reference's runtime ``PrintFetchVars`` world
  leaking into the hot path).
- **f64-promotion** — float64/complex128 avals anywhere in the program:
  TPUs emulate f64 in software, and the usual cause is an accidental
  weak-type promotion from a Python float / numpy scalar.
- **undonated-buffer** — large inputs with a same-shape/dtype output that
  are not donated: peak HBM holds both the old and new copy of every
  such buffer (the static face of ``donate_argnums``, parallel/api.py).
- **prng-key-reuse** — one key origin feeding >= 2 random draws with no
  ``split``/``fold_in`` in between (the static version of the
  ``distributions.sample()`` keyless-draw guard), including the
  loop-const variant: a key closed over by ``scan``/``while`` and drawn
  inside the body repeats the SAME stream every iteration.
- **replicated-large** — given a :class:`~paddle_tpu.parallel.plan.
  ShardingPlan`, large state leaves whose spec degenerates to fully
  replicated; plus in-graph ``sharding_constraint`` equations that pin a
  large intermediate to a fully-replicated sharding on a >1-device mesh.

Pure tracing — nothing here compiles or executes device code.
"""

from __future__ import annotations

import math
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
from jax.extend.core import Literal

from paddle_tpu.analysis.findings import Finding, RULES

HOST_CALLBACK_PRIMS = {"pure_callback", "io_callback"}
# jax.debug.callback binds `debug_callback`, jax.debug.print its own
# `debug_print`
DEBUG_CALLBACK_PRIMS = {"debug_callback", "debug_print"}
# primitives that DRAW from a key (consume its stream)
KEY_DRAW_PRIMS = {"random_bits", "threefry2x32", "rng_bit_generator"}
# primitives that DERIVE fresh independent keys (consuming is fine)
KEY_DERIVE_PRIMS = {"random_split", "random_fold_in", "random_seed",
                    "random_clone"}
# primitives whose output IS the same key as their input (aliasing)
KEY_ALIAS_PRIMS = {"random_wrap", "random_unwrap"}

_SLOW_DTYPES = ("float64", "complex128")


def _aval(v):
    return getattr(v, "aval", None)


def _nbytes(aval) -> int:
    shape = getattr(aval, "shape", None)
    dtype = getattr(aval, "dtype", None)
    if shape is None or dtype is None:
        return 0
    try:
        return int(math.prod(shape)) * dtype.itemsize
    except (TypeError, AttributeError):
        return 0


def _is_key_like(aval) -> bool:
    """True for new-style key arrays AND raw uint32[..., 2] key buffers."""
    dtype = getattr(aval, "dtype", None)
    if dtype is None:
        return False
    try:
        if jax.dtypes.issubdtype(dtype, jax.dtypes.prng_key):
            return True
    except (AttributeError, TypeError):
        pass
    shape = getattr(aval, "shape", ())
    return str(dtype) == "uint32" and tuple(shape)[-1:] == (2,)


def _src(eqn) -> str:
    """User-frame source location of an equation, best effort."""
    try:
        from jax.extend import source_info_util
        return source_info_util.summarize(eqn.source_info)
    except Exception:
        return ""


def _where(prefix: str, i: int, eqn) -> str:
    loc = f"{prefix}eqn[{i}] {eqn.primitive.name}"
    src = _src(eqn)
    return f"{loc} ({src})" if src else loc


def _sub_open(params: dict, *keys):
    """The inner (open) jaxpr under any of ``keys`` — accepts both
    ClosedJaxpr params (pjit's ``jaxpr``) and bare open Jaxprs
    (``remat2``/checkpoint store the body UNclosed, which the previous
    ClosedJaxpr-only probe silently skipped: every rule was blind
    inside ``jax.checkpoint`` scopes)."""
    for k in keys:
        v = params.get(k)
        if v is None:
            continue
        if hasattr(v, "jaxpr"):        # ClosedJaxpr
            return v.jaxpr
        if hasattr(v, "eqns"):         # open core.Jaxpr
            return v
    return None


class _KeyFlow:
    """Cross-scope PRNG dataflow state (origins are outer-most var ids)."""

    def __init__(self):
        self.counts: Dict[Any, int] = {}
        self.sites: Dict[Any, List[str]] = {}
        self.loop_reuse: List[Tuple[Any, str]] = []

    def draw(self, origin, where: str, in_loop_consts: bool):
        self.counts[origin] = self.counts.get(origin, 0) + 1
        self.sites.setdefault(origin, []).append(where)
        if in_loop_consts:
            self.loop_reuse.append((origin, where))


def analyze_jaxpr(
    closed_jaxpr,
    *,
    name: str = "fn",
    arg_labels: Optional[Sequence[Tuple[Any, str]]] = None,
    donated: Optional[Sequence[bool]] = None,
    donation_min_bytes: int = 1 << 16,
    plan=None,
    state_tree: Any = None,
    replicated_min_bytes: int = 1 << 20,
) -> List[Finding]:
    """Run every jaxpr rule over ``closed_jaxpr``; returns findings.

    ``arg_labels`` is ``[(invar, label), ...]`` for readable messages;
    ``donated`` is per-flat-input donation flags (None = unknown, skips
    the donation rule); ``plan``+``state_tree`` (abstract leaves) enable
    the replicated-large plan check.
    """
    findings: List[Finding] = []
    jaxpr = closed_jaxpr.jaxpr
    label_of = dict(arg_labels or ())
    flow = _KeyFlow()
    f64_sites: List[str] = []
    f64_seen = 0
    repl_sites: List[str] = []

    def walk(jx, env: Dict[Any, Any], prefix: str, loop_consts: set):
        nonlocal f64_seen

        def origin(v):
            if isinstance(v, Literal) or not hasattr(v, "aval"):
                return None
            if v in env:
                return env[v]
            if not _is_key_like(v.aval):
                return None
            # fresh origin: scope-qualified so a sub-jaxpr shared by two
            # call sites (jax caches traced subfunctions) does not merge
            # its internal keys' draw counts across the calls
            return (prefix, v) if prefix else v

        for i, eqn in enumerate(jx.eqns):
            prim = eqn.primitive.name
            # ---- host syncs ----
            if prim in HOST_CALLBACK_PRIMS:
                cb = eqn.params.get("callback", "")
                findings.append(Finding(
                    "host-callback", RULES["host-callback"][0],
                    f"`{prim}` reachable from the hot path"
                    + (f" (callback={cb})" if cb else ""),
                    location=_where(prefix, i, eqn),
                    fix="move host work out of the step; if data must "
                        "leave the device, fetch it AFTER dispatch from "
                        "the returned metrics instead"))
            elif prim in DEBUG_CALLBACK_PRIMS:
                findings.append(Finding(
                    "debug-callback", RULES["debug-callback"][0],
                    f"`{prim}` (jax.debug.print/callback) in the "
                    "traced step",
                    location=_where(prefix, i, eqn),
                    fix="strip jax.debug.* calls from production steps or "
                        "gate them behind a flag"))
            # ---- f64 ----
            for v in tuple(eqn.outvars) + tuple(eqn.invars):
                av = _aval(v)
                if av is not None and str(getattr(av, "dtype", "")) \
                        in _SLOW_DTYPES:
                    f64_seen += 1
                    if len(f64_sites) < 3:
                        site = _where(prefix, i, eqn)
                        if site not in f64_sites:
                            f64_sites.append(site)
                    break
            # ---- replicated sharding_constraint ----
            if prim == "sharding_constraint":
                sh = eqn.params.get("sharding")
                try:
                    big = _nbytes(_aval(eqn.invars[0])) >= \
                        replicated_min_bytes
                    multi = len(getattr(sh, "device_set", ())) > 1
                    if sh is not None and big and multi \
                            and sh.is_fully_replicated:
                        repl_sites.append(_where(prefix, i, eqn))
                except Exception:
                    pass
            # ---- PRNG dataflow ----
            if prim in KEY_ALIAS_PRIMS:
                o = origin(eqn.invars[0])
                if o is not None:
                    for ov in eqn.outvars:
                        env[ov] = o
            elif prim in KEY_DERIVE_PRIMS:
                pass                      # outputs are fresh origins
            elif prim == "concatenate":
                # one key repeated to fill a wider generator state
                # (ops.nn.keep_mask) is still that key
                origins = {origin(v) for v in eqn.invars}
                if len(origins) == 1 and None not in origins:
                    env[eqn.outvars[0]] = origins.pop()
            elif prim in KEY_DRAW_PRIMS:
                for v in eqn.invars:
                    o = origin(v)
                    if o is not None:
                        flow.draw(o, _where(prefix, i, eqn),
                                  o in loop_consts)
            # ---- recursion ----
            _recurse(eqn, env, origin, prefix, i, loop_consts, walk)

    def _recurse(eqn, env, origin, prefix, i, loop_consts, walk):
        prim = eqn.primitive.name
        params = eqn.params
        tag = f"{prefix}eqn[{i}]:{prim}/"
        if prim == "jit" or prim in ("closed_call", "core_call", "call",
                                      "remat", "remat2", "checkpoint",
                                      "custom_jvp_call", "custom_vjp_call",
                                      "custom_vjp_call_jaxpr"):
            inner = _sub_open(params, "jaxpr", "call_jaxpr", "fun_jaxpr")
            if inner is None:
                return
            sub_env = dict(zip(inner.invars,
                               (origin(v) for v in eqn.invars)))
            sub_env = {k: v for k, v in sub_env.items() if v is not None}
            walk(inner, sub_env, tag, loop_consts)
        elif prim == "cond":
            branches = params.get("branches", ())
            # each branch sees the same outer keys; one branch executes,
            # so counts merge by MAX, not sum
            base = dict(flow.counts)
            merged = dict(base)
            for b, sub in enumerate(branches):
                inner = sub.jaxpr
                sub_env = dict(zip(inner.invars,
                                   (origin(v) for v in eqn.invars[1:])))
                sub_env = {k: v for k, v in sub_env.items()
                           if v is not None}
                flow.counts = dict(base)
                walk(inner, sub_env, f"{tag}branch{b}/", loop_consts)
                for k, v in flow.counts.items():
                    if v > merged.get(k, 0):
                        merged[k] = v
            flow.counts = merged
        elif prim == "scan":
            sub = params.get("jaxpr")
            if sub is None:
                return
            inner = sub.jaxpr
            n_const = int(params.get("num_consts", 0))
            sub_env = {}
            sub_consts = set(loop_consts)
            for bind, outer in zip(inner.invars[:n_const],
                                   eqn.invars[:n_const]):
                o = origin(outer)
                if o is not None:
                    sub_env[bind] = o
                    sub_consts.add(o)
            walk(inner, sub_env, tag, sub_consts)
        elif prim == "while":
            for which, n_key in (("cond_jaxpr", "cond_nconsts"),
                                 ("body_jaxpr", "body_nconsts")):
                sub = params.get(which)
                if sub is None:
                    continue
                inner = sub.jaxpr
                n_const = int(params.get(n_key, 0))
                # while invars: [cond_consts, body_consts, carry]
                off = 0 if which == "cond_jaxpr" else \
                    int(params.get("cond_nconsts", 0))
                sub_env = {}
                sub_consts = set(loop_consts)
                for bind, outer in zip(inner.invars[:n_const],
                                       eqn.invars[off:off + n_const]):
                    o = origin(outer)
                    if o is not None:
                        sub_env[bind] = o
                        sub_consts.add(o)
                walk(inner, sub_env, f"{tag}{which}/", sub_consts)
        else:
            # unknown higher-order primitive: still scan nested programs
            # (fresh origins) so callbacks/f64 inside are not missed
            for v in params.values():
                for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                    if hasattr(sub, "jaxpr"):
                        walk(sub.jaxpr, {}, tag, set())

    walk(jaxpr, {}, "", set())

    # ---- key-reuse findings ----
    def _origin_label(o) -> str:
        if o in label_of:
            return f"key argument {label_of[o]}"
        return "an intermediate key"

    loop_reused = {o for o, _ in flow.loop_reuse}
    for o, where in flow.loop_reuse:
        findings.append(Finding(
            "prng-key-reuse", RULES["prng-key-reuse"][0],
            f"{_origin_label(o)} is closed over by a scan/while loop and "
            "drawn inside the body: every iteration replays the SAME "
            "random stream",
            location=where,
            fix="pass per-iteration keys through xs "
                "(jax.random.split(key, n)) or fold_in the loop index"))
    for o, n in flow.counts.items():
        if n >= 2 and o not in loop_reused:
            sites = "; ".join(flow.sites.get(o, [])[:4])
            findings.append(Finding(
                "prng-key-reuse", RULES["prng-key-reuse"][0],
                f"{_origin_label(o)} feeds {n} random draws with no "
                "split/fold_in between them — the draws are correlated "
                "(identical streams)",
                location=sites,
                fix="jax.random.split the key once per independent draw "
                    "(or fold_in a distinct integer per consumer)"))

    # ---- f64 finding ----
    if f64_seen:
        findings.append(Finding(
            "f64-promotion", RULES["f64-promotion"][0],
            f"{f64_seen} equation(s) carry float64/complex128 values "
            "(TPU executes f64 in software, ~10x slower)",
            location="; ".join(f64_sites),
            fix="drop jax_enable_x64 or cast explicitly to float32 / "
                "use weak-typed Python scalars"))

    # ---- donation finding ----
    if donated is not None:
        findings.extend(_donation_findings(
            jaxpr, donated, label_of, donation_min_bytes))

    # ---- replicated-large: plan check + constraint sites ----
    if plan is not None and state_tree is not None:
        findings.extend(_plan_findings(plan, state_tree,
                                       replicated_min_bytes))
    for site in repl_sites:
        findings.append(Finding(
            "replicated-large", RULES["replicated-large"][0],
            "a large intermediate is pinned to a fully-replicated "
            "sharding on a multi-device mesh",
            location=site,
            fix="give the with_sharding_constraint a partitioned spec "
                "(e.g. batch dim over ('dp','fsdp'))"))
    return findings


def _donation_findings(jaxpr, donated, label_of, min_bytes):
    """Inputs that COULD be donated (same shape+dtype as an output) but
    are not. Matching is a multiset walk: donated inputs consume their
    matching outputs first, so a partially-donated step only reports the
    leftovers."""
    out_pool: Dict[Tuple, int] = {}
    for ov in jaxpr.outvars:
        av = _aval(ov)
        if av is None:
            continue
        k = (tuple(getattr(av, "shape", ())), str(getattr(av, "dtype", "")))
        out_pool[k] = out_pool.get(k, 0) + 1

    def take(aval) -> bool:
        k = (tuple(getattr(aval, "shape", ())),
             str(getattr(aval, "dtype", "")))
        if out_pool.get(k, 0) > 0:
            out_pool[k] -= 1
            return True
        return False

    invars = jaxpr.invars
    flags = list(donated) + [False] * (len(invars) - len(donated))
    for v, d in zip(invars, flags):          # donated inputs consume first
        if d and v.aval is not None:
            take(v.aval)
    missed_bytes = 0
    examples = []
    for v, d in zip(invars, flags):
        av = _aval(v)
        if d or av is None or _nbytes(av) < min_bytes:
            continue
        if take(av):
            missed_bytes += _nbytes(av)
            if len(examples) < 3:
                examples.append(label_of.get(v, str(av)))
    if missed_bytes:
        return [Finding(
            "undonated-buffer", RULES["undonated-buffer"][0],
            f"{missed_bytes} bytes of inputs have same-shape outputs but "
            f"are not donated (e.g. {', '.join(examples)}): peak HBM "
            "holds the old AND new copy of each",
            fix="jit with donate_argnums covering the state argument "
                "(shard_train_step does this by default)")]
    return []


def _plan_findings(plan, state_tree, min_bytes):
    """Large state leaves whose plan spec degenerates to replicated."""
    try:
        specs = plan.state_specs(state_tree)
    except Exception:
        try:
            specs = plan.params_specs(state_tree)
        except Exception:
            return []
    from jax.sharding import PartitionSpec
    leaves_s = jax.tree_util.tree_flatten_with_path(
        specs, is_leaf=lambda x: isinstance(x, PartitionSpec))[0]
    leaves_v = dict(jax.tree_util.tree_flatten_with_path(state_tree)[0])
    findings = []
    for path, spec in leaves_s:
        val = leaves_v.get(path)
        if val is None or _nbytes(val) < min_bytes:
            continue
        entries = tuple(spec) if spec is not None else ()
        if all(e is None for e in entries):
            findings.append(Finding(
                "replicated-large", RULES["replicated-large"][0],
                f"state leaf {jax.tree_util.keystr(path)} "
                f"({_nbytes(val)} bytes) is fully replicated under the "
                "given sharding plan: HBM cost multiplies by mesh size",
                location=jax.tree_util.keystr(path),
                fix="add a plan rule or ParamSpec sharding hint for it "
                    "(or use fsdp_plan() to shard big params)"))
    return findings
