"""The seam between the benchmark's serving runners and the engine.

``benchmark/runners/serve.py`` and ``serve_lm.py`` reach into the engine
(bucketing functions, the warm-up plan, the jitted steps, the program, the
page pool) and a PR that tidies the engine may not edit ``benchmark/``. A
rename there would otherwise show first as a failed cell on the chip; this
reads the runners' source and holds a tiny engine to every name they use.
"""

import ast
import os

import jax
import pytest

from paddle_tpu import observability as obs
from paddle_tpu import serving
from paddle_tpu.models.gpt import GPT, GPTConfig

RUNNERS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark", "runners")

#: names the issue that added this guard listed by hand: the collector
#: below must at least find these, or it has gone blind
KNOWN = {
    "serve.py": {"_pow2_width", "_pow2_count", "warmup_plan",
                 "warmed_signatures", "_step_params", "decode_step",
                 "cache.pages", "cache.lengths", "scheduler.num_slots"},
    "serve_lm.py": {"_pow2_width", "_pow2_count", "warmup_plan",
                    "warmed_signatures", "_step_params", "decode_step",
                    "program.spec", "attn_impl", "cache.pages"},
}


def _chain(node):
    """``a.b.c`` -> (root node, ("b", "c")); None when the chain passes
    through a call or a subscript."""
    attrs = []
    while isinstance(node, ast.Attribute):
        attrs.append(node.attr)
        node = node.value
    return node, tuple(reversed(attrs))


def _engine_path(node, aliases):
    """Attribute path from the engine for ``node``, or None. The runners
    name the engine ``eng`` (``self.eng`` / ``driver.eng`` on their
    drivers); a local bound to a path from it carries that path."""
    root, attrs = _chain(node)
    if not isinstance(root, ast.Name):
        return None
    if "eng" in attrs:                     # self.eng.x, driver.eng.x
        return attrs[attrs.index("eng") + 1:]
    if root.id == "eng":
        return attrs
    if root.id in aliases:
        return aliases[root.id] + attrs
    return None


def engine_paths(source):
    """Every dotted path the source reaches from the engine."""
    paths = set()
    tree = ast.parse(source)
    for fn in ast.walk(tree):
        if not isinstance(fn, ast.FunctionDef):
            continue
        # a helper that takes the engine's cache takes it as ``cache``
        aliases = {"cache": ("cache",)} if any(
            a.arg == "cache" for a in fn.args.args) else {}
        for node in ast.walk(fn):
            if not isinstance(node, ast.Assign):
                continue
            tgt, val = node.targets[0], node.value
            pairs = zip(tgt.elts, val.elts) if (
                isinstance(tgt, ast.Tuple) and isinstance(val, ast.Tuple)
            ) else [(tgt, val)]
            for t, v in pairs:
                path = _engine_path(v, aliases)
                if isinstance(t, ast.Name) and path:
                    aliases[t.id] = path
        for node in ast.walk(fn):
            if isinstance(node, ast.Attribute):
                path = _engine_path(node, aliases)
                if path:
                    paths.add(".".join(path))
    return paths


def span_paths(source):
    """The keys of the runner's ``ENGINE_SPANS`` (methods a traced run
    wraps in host spans)."""
    for node in ast.parse(source).body:
        if isinstance(node, ast.Assign) and any(
                isinstance(t, ast.Name) and t.id == "ENGINE_SPANS"
                for t in node.targets):
            return set(ast.literal_eval(node.value))
    return set()


@pytest.fixture(scope="module")
def tiny_engine():
    model = GPT(GPTConfig.tiny(num_heads=2, hidden_size=16,
                               max_position=32))
    return serving.ServingEngine(
        model, model.init(jax.random.PRNGKey(0)), num_slots=2, page_size=8,
        attn_impl="lax", registry=obs.MetricsRegistry())


@pytest.mark.parametrize("runner", sorted(KNOWN))
def test_engine_has_what_the_runner_reaches(runner, tiny_engine):
    with open(os.path.join(RUNNERS, runner)) as f:
        source = f.read()
    paths = engine_paths(source)
    assert KNOWN[runner] <= paths, sorted(KNOWN[runner] - paths)
    if runner == "serve.py":
        spans = span_paths(source)
        assert len(spans) == 4, spans
        paths |= spans
    missing = []
    for path in sorted(paths):
        obj = tiny_engine
        for attr in path.split("."):
            if not hasattr(obj, attr):
                missing.append(path)
                break
            obj = getattr(obj, attr)
    assert not missing, (
        f"benchmark/runners/{runner} reaches {missing} on the engine; a "
        "PR that may not edit benchmark/ must keep them")


LAYER_METRICS = os.path.join(os.path.dirname(RUNNERS), "layer_metrics")


def _series_names(name):
    """The registry series a counter-ratio metric file names."""
    import json
    with open(os.path.join(LAYER_METRICS, name)) as f:
        spec = json.load(f)
    if not spec["reader"].startswith("registry_counter"):
        return set()
    sides = (spec["params"]["numerator"], spec["params"]["denominator"])
    return {side["name"] if isinstance(side, dict) else side.split("{")[0]
            for side in sides}


#: the serving engine's metrics that read its registry by series name
COUNTER_METRICS = sorted(n for n in os.listdir(LAYER_METRICS)
                         if n.startswith("engine.") and _series_names(n))


@pytest.mark.parametrize("name", COUNTER_METRICS)
def test_engine_feeds_the_series_a_layer_metric_reads(name, tiny_engine):
    """A metric file names the engine's counters as data; a counter
    renamed in the engine would read as a metric gone quiet on the chip."""
    import numpy as np
    if not tiny_engine._reg.snapshot().get("serving_steps_total"):
        tiny_engine.generate_many([np.arange(1, 12, dtype=np.int32)], 4)
    have = {k.split("{")[0] for k in tiny_engine._reg.snapshot()}
    wanted = _series_names(name)
    assert wanted <= have, sorted(wanted - have)
    assert "engine.readbacks_per_step.json" in COUNTER_METRICS
