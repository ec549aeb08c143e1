"""Static HLO cost model: walk a lowered StableHLO module into a CostReport.

The jaxpr linter sees the *traced program*; this module sees what the
compiler was actually handed. ``estimate_cost``/``estimate_lowered``
lower a (jitted) function — reusing the same ``Lowered.args_info``
donation plumbing as :mod:`~paddle_tpu.analysis.api` — and walk the
StableHLO module's operations to produce a :class:`CostReport`:

- **per-op flops and bytes** — ``dot_general``/``convolution`` get real
  contraction math (2·B·M·N·K, 2·out·k_spatial·c_in), reductions count
  their input elements, elementwise ops their results, and pure data
  movement (reshape/transpose/slice/gather/...) counts bytes only;
- **peak-HBM estimate** — a liveness scan over each function body:
  every SSA value is live from its defining op to its last use,
  non-donated entry arguments live for the whole call (the caller still
  holds them), donated arguments die at their last use (XLA may alias
  them into outputs), and region-carrying ops (while/case/reduce) add
  their bodies' internal peak at the op's program point;
- **per-collective accounting** — every ``all_reduce`` / ``all_gather``
  / ``reduce_scatter`` / ``all_to_all`` / ``collective_permute`` /
  ``collective_broadcast`` op is recorded with its payload bytes and
  replica-group shape, attributed to a mesh axis when ``mesh_axes``
  (``{axis_name: size}``) disambiguates the group size;
- **resharding chains** — ``sdy.sharding_constraint`` sites whose result
  flows (through elementwise ops) into another constraint site with
  a *different* sharding: the implicit transpose/all-to-all churn the
  ``resharding-churn`` lint rule reports.

Numbers are *static*: loop bodies and called functions count once per
call site (a lower bound — trip counts are runtime values), and the
peak-HBM scan models the unfused lowering, so it upper-bounds what XLA's
fusion achieves. That is exactly what a budget gate wants: the numbers
are deterministic functions of the lowered module, so a committed
baseline (``tools/cost_budgets.json``) catches *regressions* in the
lowered program without any hardware in the loop.

Pure lowering — nothing here compiles or executes device code.
"""

from __future__ import annotations

import dataclasses
import functools
import math
import re
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax

# element-type token -> bits (MLIR spellings)
_ETYPE_BITS = {
    "f64": 64, "f32": 32, "f16": 16, "bf16": 16,
    "f8E4M3FN": 8, "f8E5M2": 8, "f8E4M3FNUZ": 8, "f8E5M2FNUZ": 8,
    "f8E4M3B11FNUZ": 8,
    "i64": 64, "ui64": 64, "i32": 32, "ui32": 32,
    "i16": 16, "ui16": 16, "i8": 8, "ui8": 8, "i4": 4, "ui4": 4,
    "i1": 8,        # XLA stores predicates one per byte
    "c64": 64, "c128": 128, "index": 64,
}

_TENSOR_RE = re.compile(r"tensor<([^<>]*?)>")

#: ops that move/alias data but do no arithmetic
_DATA_MOVEMENT = {
    "reshape", "transpose", "broadcast_in_dim", "broadcast", "slice",
    "concatenate", "constant", "iota", "pad", "reverse", "copy",
    "bitcast_convert", "tuple", "get_tuple_element",
    "optimization_barrier", "dynamic_slice", "dynamic_update_slice",
    "gather", "scatter", "after_all", "create_token", "return", "call",
    "while", "case", "if", "custom_call", "convert", "composite",
    "partition_id", "replica_id",
}

#: stablehlo collective op name (sans dialect prefix) -> canonical kind
COLLECTIVE_OPS = {
    "all_reduce": "all_reduce",
    "all_gather": "all_gather",
    "reduce_scatter": "reduce_scatter",
    "all_to_all": "all_to_all",
    "collective_permute": "collective_permute",
    "collective_broadcast": "collective_broadcast",
}

#: ops a sharding annotation flows through unchanged (for churn chains)
_RESHARD_PASSTHROUGH = {
    "add", "subtract", "multiply", "divide", "maximum", "minimum",
    "negate", "abs", "convert", "select", "tanh", "exponential", "log",
    "logistic", "sqrt", "rsqrt", "power", "optimization_barrier",
}

_TRANSCENDENTALS = {
    "exponential", "exponential_minus_one", "log", "log_plus_one",
    "logistic", "tanh", "sine", "cosine", "tan", "atan2", "power",
    "sqrt", "rsqrt", "cbrt", "erf", "erf_inv",
}


@functools.lru_cache(maxsize=4096)
def _type_counts(type_str: str) -> Tuple[int, int]:
    """(elements, bytes) summed over every ``tensor<...>`` in an MLIR
    type string (handles tuples/variadic renderings); unknown element
    types count zero. Cached — the walker parses each value's type for
    cost, flops, and liveness separately, and a module's type strings
    repeat massively."""
    elems = nbytes = 0
    for body in _TENSOR_RE.findall(str(type_str)):
        parts = body.split("x")
        etype = parts[-1].strip()
        bits = _ETYPE_BITS.get(etype)
        if bits is None:
            continue
        n = 1
        ok = True
        for d in parts[:-1]:
            d = d.strip()
            if not d.isdigit():     # dynamic dim / layout token
                ok = False
                break
            n *= int(d)
        if not ok:
            continue
        elems += n
        nbytes += n * ((bits + 7) // 8)
    return elems, nbytes


def _value_bytes(v) -> int:
    return _type_counts(str(v.type))[1]


def _value_elems(v) -> int:
    return _type_counts(str(v.type))[0]


def _short_loc(op) -> str:
    loc = str(getattr(op, "location", "")).strip()
    if loc.startswith("loc("):
        loc = loc[4:-1]
    loc = loc.strip('"')
    loc = loc.split('"(', 1)[0]     # drop the nested callsite chain
    return loc[:80] if loc and loc != "unknown" else ""


@dataclasses.dataclass
class OpCost:
    """Aggregate cost of every instance of one op kind."""
    count: int = 0
    flops: int = 0
    bytes: int = 0

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)


@dataclasses.dataclass
class Collective:
    """One collective op instance: payload + replica-group shape."""
    kind: str                 # all_reduce | all_gather | ...
    bytes: int
    groups: int = 1           # number of replica groups
    group_size: int = 1       # devices per group
    axis: str = ""            # mesh axis attribution (best effort)
    location: str = ""

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)


@dataclasses.dataclass
class ReshardSite:
    """A value resharded between two explicit sharding annotations."""
    bytes: int
    src: str                  # sdy sharding of the producer
    dst: str                  # sdy sharding of the consumer
    location: str = ""

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)


class CostReport:
    """Static cost of one lowered function (see module docstring)."""

    def __init__(self, name: str = "fn"):
        self.name = name
        self.per_op: Dict[str, OpCost] = {}
        self.collectives: List[Collective] = []
        self.resharding: List[ReshardSite] = []
        self.peak_hbm_bytes: int = 0
        self.arg_bytes: int = 0
        self.out_bytes: int = 0
        self.donated_bytes: int = 0

    # -- aggregates ---------------------------------------------------------
    @property
    def total_flops(self) -> int:
        return sum(c.flops for c in self.per_op.values())

    @property
    def traffic_bytes(self) -> int:
        """Sum of operand+result bytes over every op: the memory-traffic
        face of the cost (upper bound — fusion elides most of it)."""
        return sum(c.bytes for c in self.per_op.values())

    @property
    def collective_bytes(self) -> int:
        return sum(c.bytes for c in self.collectives)

    @property
    def n_ops(self) -> int:
        return sum(c.count for c in self.per_op.values())

    def collective_kinds(self) -> Dict[str, int]:
        """kind -> total bytes, for allowlist checks."""
        out: Dict[str, int] = {}
        for c in self.collectives:
            out[c.kind] = out.get(c.kind, 0) + c.bytes
        return out

    def summary(self) -> Dict[str, int]:
        """The budget-gate metrics (what ``tools/cost_budgets.json``
        commits and ``--cost-diff`` compares)."""
        return {
            "flops": int(self.total_flops),
            "peak_hbm_bytes": int(self.peak_hbm_bytes),
            "traffic_bytes": int(self.traffic_bytes),
            "collective_bytes": int(self.collective_bytes),
        }

    def as_dict(self) -> dict:
        return {
            "name": self.name,
            **self.summary(),
            "arg_bytes": self.arg_bytes,
            "out_bytes": self.out_bytes,
            "donated_bytes": self.donated_bytes,
            "n_ops": self.n_ops,
            "per_op": {k: v.as_dict()
                       for k, v in sorted(self.per_op.items())},
            "collectives": [c.as_dict() for c in self.collectives],
            "resharding": [r.as_dict() for r in self.resharding],
        }

    def render_text(self) -> str:
        def mb(n):
            return f"{n / (1 << 20):.2f}MiB"
        lines = [f"cost: {self.name} — {self.total_flops:,} flops, "
                 f"traffic {mb(self.traffic_bytes)}, peak HBM "
                 f"{mb(self.peak_hbm_bytes)} (args {mb(self.arg_bytes)}, "
                 f"out {mb(self.out_bytes)}, donated "
                 f"{mb(self.donated_bytes)}), "
                 f"{len(self.collectives)} collective(s)"]
        top = sorted(self.per_op.items(), key=lambda kv: -kv[1].flops)[:6]
        for op, c in top:
            if c.flops:
                lines.append(f"  {op:24s} x{c.count:<4d} "
                             f"{c.flops:,} flops  {mb(c.bytes)}")
        for c in self.collectives:
            ax = f" axis={c.axis}" if c.axis else ""
            lines.append(f"  collective {c.kind} {mb(c.bytes)} "
                         f"({c.groups}x{c.group_size}{ax})")
        for r in self.resharding:
            lines.append(f"  reshard {mb(r.bytes)} {r.src} -> {r.dst}")
        return "\n".join(lines)


# ---------------------------------------------------------------------------
# flops models for the structured ops
# ---------------------------------------------------------------------------

def _tensor_dims(v) -> List[int]:
    body = _TENSOR_RE.findall(str(v.type))
    if not body:
        return []
    parts = body[0].split("x")[:-1]
    return [int(p) for p in parts if p.strip().isdigit()]


def _dot_flops(op) -> int:
    attr = str(op.attributes["dot_dimension_numbers"]) \
        if "dot_dimension_numbers" in op.attributes else ""
    # the batching lists may be absent from the attr text entirely, so
    # each dimension list is pulled by its own name
    named = {}
    for key in ("lhs_batching_dimensions", "rhs_batching_dimensions",
                "lhs_contracting_dimensions",
                "rhs_contracting_dimensions"):
        m = re.search(key + r"\s*=\s*\[([\d,\s]*)\]", attr)
        named[key] = [int(x) for x in m.group(1).split(",") if x.strip()] \
            if m else []
    lhs = _tensor_dims(op.operands[0])
    rhs = _tensor_dims(op.operands[1])
    lb = named["lhs_batching_dimensions"]
    lc = named["lhs_contracting_dimensions"]
    rb = named["rhs_batching_dimensions"]
    rc = named["rhs_contracting_dimensions"]
    try:
        b = math.prod(lhs[i] for i in lb) if lb else 1
        k = math.prod(lhs[i] for i in lc) if lc else 1
        m_ = math.prod(d for i, d in enumerate(lhs) if i not in lb + lc)
        n_ = math.prod(d for i, d in enumerate(rhs) if i not in rb + rc)
    except IndexError:
        return 2 * _value_elems(op.results[0])
    return 2 * b * m_ * n_ * k


def _conv_flops(op) -> int:
    out = _value_elems(op.results[0])
    kernel = _tensor_dims(op.operands[1])
    attr = str(op.attributes["dimension_numbers"]) \
        if "dimension_numbers" in op.attributes else ""
    # "#stablehlo.conv<[b, f, 0, 1]x[o, i, 0, 1]->[b, f, 0, 1]>"
    m = re.search(r"x\[([^\]]*)\]", attr)
    if not m or not kernel:
        return 2 * out
    spec = [t.strip() for t in m.group(1).split(",")]
    try:
        i_pos = spec.index("i")
        spatial = [kernel[j] for j, t in enumerate(spec)
                   if t not in ("i", "o")]
        return 2 * out * kernel[i_pos] * math.prod(spatial or [1])
    except (ValueError, IndexError):
        return 2 * out


def _op_flops(op, kind: str) -> int:
    if kind == "dot_general":
        return _dot_flops(op)
    if kind == "convolution":
        return _conv_flops(op)
    if kind in ("reduce", "reduce_window", "sort", "select_and_scatter"):
        return sum(_value_elems(v) for v in op.operands)
    if kind in _DATA_MOVEMENT:
        return 0
    # elementwise / transcendental / compare / everything else: one op
    # per result element (transcendentals are several, but a stable 1x
    # convention keeps the budget numbers comparable across PRs)
    return sum(_value_elems(r) for r in op.results)


def _replica_groups(op) -> Tuple[int, int]:
    """(groups, group_size) from a collective's replica_groups attr."""
    if "replica_groups" not in op.attributes:
        return 1, 1
    attr = str(op.attributes["replica_groups"])
    m = re.search(r"tensor<(\d+)x(\d+)xi64>", attr)
    if m:
        return int(m.group(1)), int(m.group(2))
    return 1, 1


def _axis_for(group_size: int,
              mesh_axes: Optional[Dict[str, int]]) -> str:
    if not mesh_axes or group_size <= 1:
        return ""
    hits = [a for a, s in mesh_axes.items() if int(s) == group_size]
    return "|".join(hits)


# ---------------------------------------------------------------------------
# the walker
# ---------------------------------------------------------------------------

class _Walker:
    def __init__(self, module, *, mesh_axes=None,
                 resharding_min_bytes: int = 1 << 16):
        self.funcs: Dict[str, Any] = {}
        self.mesh_axes = mesh_axes
        self.resharding_min_bytes = resharding_min_bytes
        self._stack: set = set()
        self._users: Dict[Any, List[Any]] = {}
        self._shard_ops: List[Any] = []
        for op in module.body.operations:
            if "sym_name" in op.attributes:
                self.funcs[str(op.attributes["sym_name"]).strip('"')] = op

    # -- entry --------------------------------------------------------------
    def run(self, report: CostReport,
            donated: Optional[Sequence[bool]]) -> None:
        main = self.funcs.get("main")
        if main is None:                       # defensive: empty module
            return
        blk = main.regions[0].blocks[0]
        args = list(blk.arguments)
        flags = list(donated or [])
        flags += [False] * (len(args) - len(flags))
        report.arg_bytes = sum(_value_bytes(a) for a in args)
        report.donated_bytes = sum(
            _value_bytes(a) for a, d in zip(args, flags) if d)
        report.peak_hbm_bytes = self._walk_block(
            blk, report, donated_args=flags[:len(args)])
        # main's outputs: the func.return operand bytes
        for o in blk.operations:
            if o.name == "func.return":
                report.out_bytes = sum(_value_bytes(v) for v in o.operands)
        self._resharding_chains(report)

    # -- per-block liveness + cost ------------------------------------------
    def _walk_block(self, blk, report: CostReport, *,
                    donated_args: Optional[Sequence[bool]] = None,
                    count_args: bool = True) -> int:
        """Accumulate op costs for ``blk`` (recursing into regions and
        called functions) and return the block's liveness peak in bytes.

        ``count_args``: region blocks pass False — their block args are
        the enclosing op's operands, already live at the outer level."""
        ops = list(blk.operations)
        deaths: Dict[Any, int] = {}
        extra = [0] * len(ops)

        for idx, o in enumerate(ops):
            for v in o.operands:
                deaths[v] = idx

        live_delta = [0] * (len(ops) + 1)

        args = list(blk.arguments)
        dflags = list(donated_args or []) + [False] * len(args)
        for a, d in zip(args, dflags):
            if not count_args:
                continue
            nb = _value_bytes(a)
            live_delta[0] += nb
            if d:
                # donated: XLA may alias it into the consuming op's
                # output, so the old copy is gone AT its last use (the
                # in-place update the donation lint rule wants);
                # non-donated args get no decrement at all — the caller
                # still holds them, so they stay live to the end
                live_delta[max(deaths.get(a, 0), 0)] -= nb

        for idx, o in enumerate(ops):
            kind = o.name.split(".", 1)[-1]
            dialect = o.name.split(".", 1)[0]

            # ---- cost accounting ----
            if o.name not in ("func.return", "stablehlo.return"):
                oc = report.per_op.setdefault(kind, OpCost())
                oc.count += 1
                oc.flops += _op_flops(o, kind)
                oc.bytes += sum(_value_bytes(v) for v in o.operands) \
                    + sum(_value_bytes(r) for r in o.results)

            # ---- collectives ----
            if kind in COLLECTIVE_OPS:
                nb = max(sum(_value_bytes(v) for v in o.operands),
                         sum(_value_bytes(r) for r in o.results))
                groups, gsize = _replica_groups(o)
                report.collectives.append(Collective(
                    COLLECTIVE_OPS[kind], nb, groups, gsize,
                    _axis_for(gsize, self.mesh_axes), _short_loc(o)))

            # ---- sharding annotations (for churn chains) ----
            if _constraint_sharding(o) is not None:
                self._shard_ops.append(o)
            for v in o.operands:
                self._users.setdefault(v, []).append(o)

            # ---- recursion: called functions + regions ----
            if dialect == "func" and kind == "call" \
                    and "callee" in o.attributes:
                callee = str(o.attributes["callee"]).strip('"').lstrip("@")
                extra[idx] = max(extra[idx], self._walk_func(
                    callee, report))
            inner = 0
            for r in o.regions:
                for b in r.blocks:
                    inner = max(inner, self._walk_block(
                        b, report, count_args=False))
            extra[idx] = max(extra[idx], inner)

            # ---- liveness births ----
            for res in o.results:
                nb = _value_bytes(res)
                live_delta[idx] += nb
                end = deaths.get(res, idx)
                if end + 1 <= len(ops) - 1:
                    live_delta[end + 1] -= nb

        peak = running = 0
        for idx in range(len(ops)):
            running += live_delta[idx]
            peak = max(peak, running + extra[idx])
        return peak

    def _walk_func(self, name: str, report: CostReport) -> int:
        fn = self.funcs.get(name)
        if fn is None or name in self._stack:
            return 0
        self._stack.add(name)
        try:
            # callee peak: its args are the call's operands, live at the
            # caller already, so count only the body's intermediates
            return self._walk_block(fn.regions[0].blocks[0], report,
                                    count_args=False)
        finally:
            self._stack.discard(name)

    # -- resharding chains --------------------------------------------------
    def _resharding_chains(self, report: CostReport) -> None:
        """For every sharding-constraint site, follow its result forward
        through elementwise ops; a different constraint downstream on a
        large tensor is a resharding-churn site."""
        for src_op in self._shard_ops:
            src = _constraint_sharding(src_op)
            if _REPLICATED_SDY.fullmatch(src):
                continue
            nb = _value_bytes(src_op.results[0])
            if nb < self.resharding_min_bytes:
                continue
            seen: set = set()
            frontier = list(src_op.results)
            depth = 0
            while frontier and depth < 16:
                nxt = []
                for v in frontier:
                    for user in self._users.get(v, ()):
                        kind = user.name.split(".", 1)[-1]
                        if user in seen:
                            continue
                        seen.add(user)
                        dst = _constraint_sharding(user)
                        if dst is not None:
                            if dst != src:
                                report.resharding.append(ReshardSite(
                                    nb, src, dst, _short_loc(user)))
                            continue            # chain ends at a reshard
                        if kind in _RESHARD_PASSTHROUGH:
                            nxt.extend(user.results)
                frontier = nxt
                depth += 1


def _constraint_sharding(op) -> Optional[str]:
    """The sharding an ``sdy.sharding_constraint`` op pins (how
    ``with_sharding_constraint`` lowers under the Shardy partitioner),
    as text; None for any other op."""
    if op.name != "sdy.sharding_constraint":
        return None
    return str(op.attributes["sharding"])


#: a constraint that shards no dimension: ``<@mesh, [{}, {}]>``
_REPLICATED_SDY = re.compile(r"#sdy\.sharding<@\w+, \[(\{\??\}(, )?)*\]>")


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------

def analyze_module(module, *, name: str = "fn",
                   donated: Optional[Sequence[bool]] = None,
                   mesh_axes: Optional[Dict[str, int]] = None,
                   resharding_min_bytes: int = 1 << 16) -> CostReport:
    """Walk an MLIR/StableHLO module into a :class:`CostReport`."""
    report = CostReport(name)
    _Walker(module, mesh_axes=mesh_axes,
            resharding_min_bytes=resharding_min_bytes).run(report, donated)
    return report


def estimate_lowered(lowered, *, name: str = "fn",
                     donated: Optional[Sequence[bool]] = None,
                     mesh_axes: Optional[Dict[str, int]] = None,
                     resharding_min_bytes: int = 1 << 16) -> CostReport:
    """Cost-analyze a ``jax.stages.Lowered``. Donation flags default to
    the lowering's own ``args_info`` (the same plumbing the donation
    lint rule reads)."""
    if donated is None:
        try:
            donated = [a.donated
                       for a in jax.tree_util.tree_leaves(lowered.args_info)]
        except Exception:
            donated = None
    module = lowered.compiler_ir(dialect="stablehlo")
    return analyze_module(module, name=name, donated=donated,
                          mesh_axes=mesh_axes,
                          resharding_min_bytes=resharding_min_bytes)


def estimate_cost(fn, *args, name: Optional[str] = None,
                  donate_argnums=None,
                  mesh_axes: Optional[Dict[str, int]] = None,
                  resharding_min_bytes: int = 1 << 16,
                  **kwargs) -> CostReport:
    """Lower ``fn(*args, **kwargs)`` (jitting if it is not already a
    jit wrapper) and cost-analyze the StableHLO. Args may be concrete
    arrays or ``jax.ShapeDtypeStruct`` — nothing executes."""
    name = name or getattr(fn, "__name__", None) or type(fn).__name__
    if hasattr(fn, "lower"):
        lowered = fn.lower(*args, **kwargs)
    else:
        if donate_argnums is None:
            lowered = jax.jit(fn).lower(*args, **kwargs)
        else:
            lowered = jax.jit(fn, donate_argnums=donate_argnums).lower(
                *args, **kwargs)
    return estimate_lowered(lowered, name=name, mesh_axes=mesh_axes,
                            resharding_min_bytes=resharding_min_bytes)
