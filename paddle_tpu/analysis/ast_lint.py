"""AST linter for jit-reachable step functions.

The jaxpr analyzer sees what *traced*; this pass reads the Python
*source* of a step function and flags host-sync idioms that either crash
at trace time or silently sync the device every step:

- ``.item()`` / ``.tolist()`` / ``float()/int()/bool()`` on tracer values
  (device→host transfer per call);
- ``np.asarray`` / ``np.array`` / ``numpy.*`` materialization;
- ``time.time()`` / ``time.perf_counter()`` (trace-time constant — the
  compiled step bakes in ONE timestamp forever);
- bare stdlib ``random.*`` (same: one trace-time draw replayed forever);
- Python ``if``/``while`` on tracer-valued names (trace-time
  ``ConcretizationTypeError``, or a retrace per distinct value when the
  name is a weakly-typed scalar).

Tracer inference is a deliberate, shallow heuristic: the function's
parameters seed the tracer set (minus parameters whose defaults are
plain Python flags — ``training=False``, ``mode="train"``, ``key=None``
— which are static config by convention), and assignments propagate.
``x is None``-style comparisons and ``x.shape`` / ``x.ndim`` / ``x.dtype``
are static and never flagged. The lint
is per-function — callees are not followed; run it on the function you
``jit``.
"""

from __future__ import annotations

import ast
import inspect
import textwrap
from typing import List, Optional, Set

from paddle_tpu.analysis.findings import Finding, RULES

_NUMPY_MODULES = {"np", "numpy"}
_TIME_CALLS = {"time", "perf_counter", "monotonic", "process_time"}
_SYNC_METHODS = {"item", "tolist", "block_until_ready"}
_CAST_BUILTINS = {"float", "int", "bool", "complex"}


def _names_in(node: ast.AST) -> Set[str]:
    """Names ``node`` reads a value of (``x.shape[0]`` reads none of
    ``x``'s: a branch on it is a branch on a Python int)."""
    if isinstance(node, ast.Attribute) and node.attr in ("shape", "ndim",
                                                         "dtype"):
        return set()
    if isinstance(node, ast.Name):
        return {node.id}
    return set().union(*map(_names_in, ast.iter_child_nodes(node)))


def _is_none_compare(test: ast.AST) -> bool:
    """`x is None` / `x is not None` / `x == None` — static, never a sync."""
    if not isinstance(test, ast.Compare):
        return False
    return any(isinstance(c, ast.Constant) and c.value is None
               for c in test.comparators)


def _static_default(default: ast.AST) -> bool:
    """Defaults that mark a parameter as static config, not a tracer."""
    return isinstance(default, ast.Constant) and isinstance(
        default.value, (bool, str, int, float, type(None)))


class _FnLinter(ast.NodeVisitor):
    def __init__(self, fn_node: ast.FunctionDef, filename: str,
                 line_offset: int):
        self.filename = filename
        self.off = line_offset
        self.findings: List[Finding] = []
        self.tracers: Set[str] = set()
        args = fn_node.args
        pos = list(args.posonlyargs) + list(args.args)
        n_def = len(args.defaults)
        defaults = [None] * (len(pos) - n_def) + list(args.defaults)
        for a, d in zip(pos, defaults):
            if a.arg != "self" and (d is None or not _static_default(d)):
                self.tracers.add(a.arg)
        for a, d in zip(args.kwonlyargs, args.kw_defaults):
            if d is None or not _static_default(d):
                self.tracers.add(a.arg)
        if args.vararg:
            self.tracers.add(args.vararg.arg)
        if args.kwarg:
            self.tracers.add(args.kwarg.arg)

    # -- helpers ------------------------------------------------------------
    def _loc(self, node) -> str:
        return f"{self.filename}:{node.lineno + self.off}"

    def _tracer_expr(self, node: ast.AST) -> bool:
        return bool(_names_in(node) & self.tracers)

    def _add(self, rule: str, node: ast.AST, message: str, fix: str):
        self.findings.append(Finding(
            rule, RULES[rule][0], message, location=self._loc(node),
            fix=fix, engine="ast"))

    # -- dataflow -----------------------------------------------------------
    def visit_Assign(self, node: ast.Assign):
        if self._tracer_expr(node.value):
            for t in node.targets:
                for n in ast.walk(t):
                    if isinstance(n, ast.Name):
                        self.tracers.add(n.id)
        self.generic_visit(node)

    def visit_AugAssign(self, node: ast.AugAssign):
        if self._tracer_expr(node.value) and isinstance(node.target,
                                                        ast.Name):
            self.tracers.add(node.target.id)
        self.generic_visit(node)

    def visit_For(self, node: ast.For):
        if self._tracer_expr(node.iter):
            for n in ast.walk(node.target):
                if isinstance(n, ast.Name):
                    self.tracers.add(n.id)
        self.generic_visit(node)

    # -- rules --------------------------------------------------------------
    def visit_Call(self, node: ast.Call):
        fn = node.func
        # x.item() / x.tolist() / x.block_until_ready()
        if isinstance(fn, ast.Attribute) and fn.attr in _SYNC_METHODS \
                and self._tracer_expr(fn.value):
            self._add("ast-host-sync", node,
                      f"`.{fn.attr}()` on a tracer value: device->host "
                      "sync inside the step",
                      "return the array in the metrics dict and convert "
                      "on the host after dispatch")
        # np.asarray / np.array / numpy.*
        elif isinstance(fn, ast.Attribute) and \
                isinstance(fn.value, ast.Name) and \
                fn.value.id in _NUMPY_MODULES and \
                fn.attr in ("asarray", "array", "copy"):
            self._add("ast-host-sync", node,
                      f"`{fn.value.id}.{fn.attr}(...)` materializes a "
                      "host numpy array inside jit-reachable code",
                      "use jnp.asarray (stays on device) or hoist the "
                      "conversion out of the step")
        # time.time() family
        elif isinstance(fn, ast.Attribute) and \
                isinstance(fn.value, ast.Name) and \
                fn.value.id == "time" and fn.attr in _TIME_CALLS:
            self._add("ast-host-sync", node,
                      f"`time.{fn.attr}()` in jit-reachable code is a "
                      "trace-time constant: the compiled step replays ONE "
                      "timestamp forever",
                      "time on the host around the step call "
                      "(Trainer/StepTelemetry already does)")
        # bare stdlib random.*
        elif isinstance(fn, ast.Attribute) and \
                isinstance(fn.value, ast.Name) and fn.value.id == "random":
            self._add("ast-host-sync", node,
                      f"stdlib `random.{fn.attr}(...)` in jit-reachable "
                      "code: one trace-time draw, baked into the "
                      "compiled step",
                      "use jax.random with an explicit key")
        # float(x) / int(x) / bool(x) on a tracer
        elif isinstance(fn, ast.Name) and fn.id in _CAST_BUILTINS and \
                node.args and self._tracer_expr(node.args[0]):
            self._add("ast-host-sync", node,
                      f"`{fn.id}(...)` on a tracer value forces a "
                      "device->host sync (or a trace-time crash)",
                      "keep it as a jnp scalar; convert after the step "
                      "returns")
        self.generic_visit(node)

    def _check_branch(self, node, kind: str):
        if _is_none_compare(node.test):
            return
        if self._tracer_expr(node.test):
            names = sorted(_names_in(node.test) & self.tracers)
            self._add("ast-tracer-branch", node,
                      f"Python `{kind}` on tracer value(s) "
                      f"{', '.join(names)}: crashes at trace time under "
                      "jit (ConcretizationTypeError) or forces a retrace "
                      "per value",
                      "use jnp.where / lax.cond / lax.while_loop, or "
                      "hoist the decision out of the jitted function")

    def visit_If(self, node: ast.If):
        self._check_branch(node, "if")
        self.generic_visit(node)

    def visit_While(self, node: ast.While):
        self._check_branch(node, "while")
        self.generic_visit(node)


def lint_source(src: str, *, filename: str = "<src>",
                line_offset: int = 0) -> List[Finding]:
    """Lint already-extracted function source (first def found)."""
    tree = ast.parse(textwrap.dedent(src))
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            linter = _FnLinter(node, filename, line_offset)
            linter.visit(node)
            return linter.findings
    return []


def lint_callable(fn) -> List[Finding]:
    """Lint a function's source; silently returns [] when source is
    unavailable (builtins, jitted wrappers, REPL lambdas)."""
    inner = inspect.unwrap(getattr(fn, "__wrapped__", fn))
    try:
        src = inspect.getsource(inner)
        filename = inspect.getsourcefile(inner) or "<src>"
        _, first_line = inspect.getsourcelines(inner)
    except (OSError, TypeError):
        return []
    try:
        return lint_source(src, filename=filename,
                           line_offset=max(0, first_line - 1))
    except SyntaxError:
        return []
