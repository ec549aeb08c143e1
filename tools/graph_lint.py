"""Graph lint CLI: static analysis over step functions / the model zoo.

CI self-lint (``tools/run_ci.sh``)::

    python tools/graph_lint.py --preset framework
    python tools/graph_lint.py --preset framework --cost --cost-diff

lints representative zoo step functions — LeNet train step, ResNet-18
train step, GPT (tiny) cached decode step, the VGG-style ImgConvGroup
dropout forward, the serving decode/prefill steps, and the embedding-
serving install/lookup steps — and exits 1 on any unsuppressed
error-severity finding. ``tools/graph_lint_suppressions.txt`` is the
committed allow-list for known-accepted warnings; entries that no
longer match any finding are themselves an error (stale suppressions
rot silently and would re-accept a future regression).

``--cost`` adds the HLO tier: every surface is lowered to StableHLO and
cost-analyzed (``analysis.cost_model``), then checked for unexpected
collectives (single-device serving steps must have ZERO), resharding
churn, and the peak-HBM/flops budgets committed in
``tools/cost_budgets.json``; plus the bucket-coverage proof that the
serving engines' ``warmup()`` plans precompile every statically
reachable pow2 signature. ``--cost-diff`` compares the measured static
flops / peak-HBM / collective-bytes against the committed baselines and
fails when any regresses beyond the manifest's tolerance — a perf-
regression gate that needs no hardware. ``--update-budgets`` rewrites
the manifest from the current measurements (commit it with the PR that
legitimately moved the numbers).

``--concurrency`` adds the host-thread tier (``analysis.concurrency`` +
``analysis.conformance``): the ``@guarded_by`` lock-discipline pass over
every package module, cycle/double-acquire detection on the extracted
static lock-acquisition graph, the drift gate against the committed
``tools/lock_order.json`` (regenerate with ``--update-lock-order``,
mirroring ``--update-budgets``), ReplicaHandle/wire-dispatch interface
conformance, and the single-source ``Reject.reason`` vocabulary check.

Everything here is abstract tracing and lowering: no weights are
trained, nothing is compiled or executed, so the whole preset runs in
seconds on CPU.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# the tp serving surfaces lower under a 2-device mesh: force virtual CPU
# devices (read at backend init, so setting it here still takes effect)
# the way tests/conftest.py does
if "xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
# pin the RNG lowering: partitionable threefry changes the op mix of
# dropout surfaces, and the committed cost budgets must be a
# deterministic function of the module regardless of caller env (the
# test suite runs with this flag on; it is also jax's forward default)
jax.config.update("jax_threefry_partitionable", True)

import jax.numpy as jnp  # noqa: E402

from paddle_tpu import analysis  # noqa: E402
from paddle_tpu.analysis import hlo_lint  # noqa: E402

DEFAULT_SUPPRESSIONS = os.path.join(os.path.dirname(__file__),
                                    "graph_lint_suppressions.txt")
DEFAULT_BUDGETS = os.path.join(os.path.dirname(__file__),
                               "cost_budgets.json")
DEFAULT_LOCK_ORDER = os.path.join(os.path.dirname(__file__),
                                  "lock_order.json")

#: metrics --cost-diff gates against the committed baseline
DIFF_METRICS = ("flops", "peak_hbm_bytes", "collective_bytes")

#: rules that only fire in their optional tier — the stale-suppression
#: gate is scoped to rules whose tier actually RAN this invocation, so
#: the plain `--preset framework` CI leg doesn't reject committed
#: entries that only the `--concurrency` / `--cost` legs can match
CONCURRENCY_RULES = frozenset({
    "unguarded-access", "lock-order-cycle", "double-acquire",
    "lock-order-drift", "sanitizer-violation", "interface-drift",
    "reject-vocab-drift"})
COST_RULES = frozenset({
    "unexpected-collective", "resharding-churn", "peak-hbm-budget",
    "bucket-coverage", "cost-regression"})


def _train_step_report(model, loss_fn, sample_batch, *, name,
                       suppressions, lr=1e-3, cost=False):
    from paddle_tpu import optimizer as opt
    from paddle_tpu.train import build_train_step, make_train_state

    optim = opt.Adam(learning_rate=lr)
    state = make_train_state(model, optim, jax.random.PRNGKey(0))
    step = jax.jit(build_train_step(loss_fn, optim), donate_argnums=0)
    return analysis.lint_train_step(step, state, sample_batch, name=name,
                                    suppressions=suppressions, cost=cost)


def lint_lenet(suppressions, cost=False):
    from paddle_tpu.models import LeNet
    from paddle_tpu.ops import nn as F

    model = LeNet()

    def loss_fn(params, image, label):
        logits = model(params, image)
        return jnp.mean(F.softmax_with_cross_entropy(logits, label))

    batch = {"image": jnp.zeros((8, 28, 28, 1), jnp.float32),
             "label": jnp.zeros((8, 1), jnp.int32)}
    return _train_step_report(model, loss_fn, batch, name="lenet_train",
                              suppressions=suppressions, cost=cost)


def lint_resnet18(suppressions, cost=False):
    from paddle_tpu.models import ResNet
    from paddle_tpu.ops import nn as F

    model = ResNet(depth=18, num_classes=10, in_ch=3)

    def loss_fn(params, image, label):
        logits = model(params, image, training=True)
        return jnp.mean(F.softmax_with_cross_entropy(logits, label))

    batch = {"image": jnp.zeros((4, 64, 64, 3), jnp.float32),
             "label": jnp.zeros((4, 1), jnp.int32)}
    return _train_step_report(model, loss_fn, batch,
                              name="resnet18_train",
                              suppressions=suppressions, cost=cost)


def lint_gpt_decode(suppressions, cost=False):
    """Cached single-token decode step, jitted WITHOUT cache donation —
    the undonated-cache warning this produces is a known-accepted entry
    in the suppression file (``generate()`` donates at its own jit
    boundary; a bare decode step kept for interactive use cannot, since
    callers may replay from an old cache)."""
    from paddle_tpu.models.gpt import GPT, GPTConfig

    cfg = GPTConfig.tiny()
    model = GPT(cfg)
    params = model.init(jax.random.PRNGKey(0))
    cache = model.init_cache(8, 256)     # serving-sized KV cache

    decode = jax.jit(model.decode_step)
    report = analysis.lint_fn(
        decode, analysis.abstractify(params),
        jax.ShapeDtypeStruct((8,), jnp.int32),
        jax.ShapeDtypeStruct((), jnp.int32),
        analysis.abstractify(cache),
        name="gpt_decode", ast_fn=model.decode_step,
        suppressions=suppressions, cost=cost)
    return report


def lint_convgroup(suppressions, cost=False):
    """VGG building block with per-layer fold_in dropout keys — the PRNG
    hygiene surface (must stay key-reuse clean)."""
    from paddle_tpu.nn import ImgConvGroup

    model = ImgConvGroup(3, [8, 8], pool_size=2, conv_with_batchnorm=True,
                         conv_batchnorm_drop_rate=0.3, conv_act="relu")
    params = model.init(jax.random.PRNGKey(0))

    def fwd(params, key, x):
        return model(params, x, training=True, dropout_key=key).sum()

    return analysis.lint_fn(
        fwd, analysis.abstractify(params),
        jax.random.PRNGKey(1),
        jax.ShapeDtypeStruct((2, 16, 16, 3), jnp.float32),
        name="vgg_convgroup", suppressions=suppressions, cost=cost)


_TINY_GPT = None


def _tiny_gpt():
    """One shared tiny GPT for every serving surface in the preset
    (model.init compiles and runs real computation — pay it once)."""
    global _TINY_GPT
    if _TINY_GPT is None:
        from paddle_tpu.models.gpt import GPT, GPTConfig
        model = GPT(GPTConfig.tiny())
        _TINY_GPT = (model, model.init(jax.random.PRNGKey(0)))
    return _TINY_GPT


def _tiny_serving_engine(**kw):
    from paddle_tpu import serving

    model, params = _tiny_gpt()
    kw.setdefault("num_slots", 4)
    kw.setdefault("page_size", 8)
    kw.setdefault("max_tokens_per_slot", 64)
    return serving.ServingEngine(model, params, attn_impl="lax", **kw)


def lint_serving_decode(suppressions, cost=False):
    """The serving engine's continuous-batching decode step — the hot
    path of ISSUE 4. Unlike the bare ``gpt_decode`` surface above, the
    engine IS the donating surface: its jitted step donates the KV cache
    pages (single-use by construction — the engine replaces its page
    handles every call), so this must lint clean with NO undonated-
    buffer suppression. Under ``--cost`` the single-device serving
    contract also applies: ZERO collectives in the lowered step."""
    import jax.numpy as jnp

    eng = _tiny_serving_engine()
    c = eng.cache.config
    return analysis.lint_fn(
        eng.decode_step, analysis.abstractify(eng.params),
        analysis.abstractify(eng.cache.pages),
        jax.ShapeDtypeStruct((c.num_slots, c.max_pages_per_slot),
                             jnp.int32),
        jax.ShapeDtypeStruct((c.num_slots,), jnp.int32),
        jax.ShapeDtypeStruct((c.num_slots,), jnp.int32),
        jax.ShapeDtypeStruct((c.num_slots,), jnp.int32),
        name="serving_decode", ast_fn=eng._decode_loop,
        suppressions=suppressions, cost=cost)


def lint_serving_prefill(suppressions, cost=False):
    """The batched chunked-prefill step (ISSUE 6) — the other jitted
    serving surface. Same contract as decode: the engine donates the KV
    cache pages into the step (single-use by construction), and nothing
    inside may sync to the host — so it must lint clean with NO
    undonated-buffer suppression (and zero collectives under --cost)."""
    import jax.numpy as jnp

    eng = _tiny_serving_engine()
    c = eng.cache.config
    return analysis.lint_fn(
        eng.prefill_step, analysis.abstractify(eng.params),
        analysis.abstractify(eng.cache.pages),
        jax.ShapeDtypeStruct((c.num_slots, c.max_pages_per_slot),
                             jnp.int32),
        jax.ShapeDtypeStruct((c.num_slots,), jnp.int32),
        jax.ShapeDtypeStruct((c.num_slots, eng.prefill_chunk), jnp.int32),
        jax.ShapeDtypeStruct((c.num_slots,), jnp.int32),
        name="serving_prefill", ast_fn=eng._prefill_loop,
        suppressions=suppressions, cost=cost)


def _tiny_int8_serving_engine(**kw):
    """The int8 lint/cost engine: same tiny GPT, quantized page pool
    sized KV-heavy (a big pool on a small model) so the int8-vs-bf16
    static-bytes gap is far outside the cost-diff tolerance — the
    committed budget then demonstrably FAILS if the dequant-attend path
    ever regresses to bf16-level bytes."""
    kw.setdefault("cache_dtype", jnp.int8)
    kw.setdefault("num_pages", 513)
    return _tiny_serving_engine(**kw)


def lint_serving_decode_int8(suppressions, cost=False):
    """The dequant-attend decode step (ISSUE 13): int8 pages + scale
    rows are all donated into the jitted step (the engine replaces
    every handle each call), so this must lint clean with NO
    undonated-buffer suppression; under ``--cost`` the single-device
    zero-collective contract and the int8 bytes budget apply."""
    import jax.numpy as jnp

    eng = _tiny_int8_serving_engine()
    c = eng.cache.config
    return analysis.lint_fn(
        eng.decode_step, analysis.abstractify(eng.params),
        analysis.abstractify(eng.cache.pages),
        jax.ShapeDtypeStruct((c.num_slots, c.max_pages_per_slot),
                             jnp.int32),
        jax.ShapeDtypeStruct((c.num_slots,), jnp.int32),
        jax.ShapeDtypeStruct((c.num_slots,), jnp.int32),
        jax.ShapeDtypeStruct((c.num_slots,), jnp.int32),
        name="serving_decode_int8", ast_fn=eng._decode_loop,
        suppressions=suppressions, cost=cost)


def lint_serving_prefill_int8(suppressions, cost=False):
    """The dequant-attend batched-prefill step — also the shape of the
    speculative VERIFY step (same jitted body, all-position argmax), so
    linting it covers both surfaces."""
    import jax.numpy as jnp

    eng = _tiny_int8_serving_engine()
    c = eng.cache.config
    return analysis.lint_fn(
        eng.prefill_step, analysis.abstractify(eng.params),
        analysis.abstractify(eng.cache.pages),
        jax.ShapeDtypeStruct((c.num_slots, c.max_pages_per_slot),
                             jnp.int32),
        jax.ShapeDtypeStruct((c.num_slots,), jnp.int32),
        jax.ShapeDtypeStruct((c.num_slots, eng.prefill_chunk), jnp.int32),
        jax.ShapeDtypeStruct((c.num_slots,), jnp.int32),
        name="serving_prefill_int8", ast_fn=eng._prefill_loop,
        suppressions=suppressions, cost=cost)


def _tiny_tp_engine(**kw):
    """A tp=2 twin of the preset's tiny serving engine over the first
    two virtual CPU devices (the tiny GPT has 2 heads — one per
    shard)."""
    from paddle_tpu.core.mesh import MeshConfig, make_mesh
    mesh = make_mesh(MeshConfig(tp=2), devices=jax.devices()[:2])
    return _tiny_serving_engine(mesh=mesh, **kw)


def lint_serving_decode_tp(suppressions, cost=False):
    """The tensor-parallel decode step (ISSUE 15): heads sharded H/tp
    under shard_map, per-shard page pools donated, and — under
    ``--cost`` — the sharded-step collective contract: the
    ``collective_allowlist`` committed in ``tools/cost_budgets.json``
    is exactly ``["all_reduce"]``, the one attention-output psum per
    layer (MLP/embeddings replicated emit nothing), with the
    collective BYTES budget-gated by ``--cost-diff``."""
    import jax.numpy as jnp

    eng = _tiny_tp_engine()
    c = eng.cache.config
    return analysis.lint_fn(
        eng.decode_step, analysis.abstractify(eng._step_params),
        analysis.abstractify(eng.cache.pages),
        jax.ShapeDtypeStruct((c.num_slots, c.max_pages_per_slot),
                             jnp.int32),
        jax.ShapeDtypeStruct((c.num_slots,), jnp.int32),
        jax.ShapeDtypeStruct((c.num_slots,), jnp.int32),
        jax.ShapeDtypeStruct((c.num_slots,), jnp.int32),
        name="serving_decode_tp", ast_fn=eng._decode_loop,
        suppressions=suppressions, cost=cost)


def lint_serving_prefill_tp(suppressions, cost=False):
    """The tensor-parallel batched-prefill step — same sharded-step
    contract as :func:`lint_serving_decode_tp` (one attention-output
    collective kind, budget-gated bytes)."""
    import jax.numpy as jnp

    eng = _tiny_tp_engine()
    c = eng.cache.config
    return analysis.lint_fn(
        eng.prefill_step, analysis.abstractify(eng._step_params),
        analysis.abstractify(eng.cache.pages),
        jax.ShapeDtypeStruct((c.num_slots, c.max_pages_per_slot),
                             jnp.int32),
        jax.ShapeDtypeStruct((c.num_slots,), jnp.int32),
        jax.ShapeDtypeStruct((c.num_slots, eng.prefill_chunk), jnp.int32),
        jax.ShapeDtypeStruct((c.num_slots,), jnp.int32),
        name="serving_prefill_tp", ast_fn=eng._prefill_loop,
        suppressions=suppressions, cost=cost)


def lint_serving_prefill_tp_mlp(suppressions, cost=False):
    """The prefill-TIER tensor-parallel batched-prefill step
    (ISSUE 19): a disaggregated prefill engine runs the real Megatron
    MLP shard (fc1 column-split, fc2 row-split) on top of the sharded
    attention, so its lowered step carries exactly TWO all_reduce
    psums per layer — attention output plus MLP row-parallel
    reduction. The ``collective_allowlist`` stays ``["all_reduce"]``
    and the extra collective BYTES are budget-gated by ``--cost-diff``;
    the colocated/decode surfaces above must stay byte-identical
    (+0.0%) because the shard is gated to ``tier="prefill"``."""
    import jax.numpy as jnp

    eng = _tiny_tp_engine(tier="prefill")
    c = eng.cache.config
    return analysis.lint_fn(
        eng.prefill_step, analysis.abstractify(eng._step_params),
        analysis.abstractify(eng.cache.pages),
        jax.ShapeDtypeStruct((c.num_slots, c.max_pages_per_slot),
                             jnp.int32),
        jax.ShapeDtypeStruct((c.num_slots,), jnp.int32),
        jax.ShapeDtypeStruct((c.num_slots, eng.prefill_chunk), jnp.int32),
        jax.ShapeDtypeStruct((c.num_slots,), jnp.int32),
        name="serving_prefill_tp_mlp", ast_fn=eng._prefill_loop,
        suppressions=suppressions, cost=cost)


#: a layer kind's methods that run inside the engine's jitted steps
TRACED_KIND_METHODS = ("place_decode", "place_prefill", "write",
                       "attend_decode", "attend_prefill", "attends_prefill",
                       "step_counts", "copy_page")


def lint_serving_layer_kinds(suppressions, cost=False):
    """The traced half of ``serving/layer_kinds.py``: where a call's
    tokens go, the row writes and the attention calls of every kind of
    layer. They run inside the serving steps the presets above lower (the
    jaxpr tier sees them there); the AST host-sync lint is per function,
    so it runs here on each of them as it runs on ``_decode_loop`` /
    ``_prefill_loop``, the bodies they were lifted out of."""
    from paddle_tpu.analysis import ast_lint
    from paddle_tpu.serving import layer_kinds

    report = analysis.Report("serving_layer_kinds",
                             suppressions=suppressions)
    lk = layer_kinds
    traced = [lk.under_table, lk._write_lane_rows, lk._write_rows,
              lk.quantize_kv, lk.Ring._pages] + [
        vars(kind)[name]
        for kind in (lk.Paged, lk.PagedInt8, lk.Ring, lk.Latent,
                     lk.Selecting)
        for name in TRACED_KIND_METHODS if name in vars(kind)]
    for fn in traced:
        report.extend(ast_lint.lint_callable(fn))
    report.count_into_registry()
    return report


def lint_embedding_install(suppressions, cost=False):
    """The embedding-serving cache's update step: the device hot-row
    table is DONATED into the bucketed scatter (the engine replaces its
    table handle every install — single-use by construction), so this
    must lint clean with NO undonated-buffer suppression."""
    from paddle_tpu.embedding_serving import DeviceEmbeddingCache

    cache = DeviceEmbeddingCache(64, 9, min_gather_bucket=8)
    return analysis.lint_fn(
        cache._install_fn, analysis.abstractify(cache.table),
        jax.ShapeDtypeStruct((8,), jnp.int32),
        jax.ShapeDtypeStruct((8, 9), jnp.float32),
        name="embedding_cache_install", suppressions=suppressions,
        cost=cost)


def lint_embedding_lookup(suppressions, cost=False):
    """The embedding-serving hot path: fixed-shape gather out of the
    (read-only) device table straight into the DeepFM forward. Nothing
    inside may sync to the host (no callbacks, no .item()) — misses are
    handled host-side BEFORE this step runs, which is exactly what
    keeps the jitted surface clean."""
    from paddle_tpu.embedding_serving import DeviceEmbeddingCache
    from paddle_tpu.models.deepfm import DeepFMHostKV

    cache = DeviceEmbeddingCache(64, 9, min_gather_bucket=8)
    model = DeepFMHostKV(num_fields=4, embed_dim=8, hidden=(16,))
    params = model.init(jax.random.PRNGKey(0))

    def serve(params, table, slots, inv):
        rows = jnp.take(table, slots, axis=0)
        return model.predict_proba(params, rows, inv)

    return analysis.lint_fn(
        jax.jit(serve), analysis.abstractify(params),
        analysis.abstractify(cache.table),
        jax.ShapeDtypeStruct((8,), jnp.int32),
        jax.ShapeDtypeStruct((4, 4), jnp.int32),
        name="embedding_lookup_serve", ast_fn=serve,
        suppressions=suppressions, cost=cost)


def bucket_coverage_report(suppressions):
    """The ahead-of-time zero-recompile proof (``--cost`` only): the
    serving engines' statically reachable pow2 bucket signatures must
    all be in their ``warmup()`` precompile plans. The coverage check
    itself is pure host math (no tracing, no compiles — engine
    construction reuses the preset's shared tiny GPT); includes
    deliberately non-pow2 configurations (the historical failure mode:
    a raw capacity clamp minting a width the warmup doubling loop never
    visits)."""
    from paddle_tpu.embedding_serving import DeviceEmbeddingCache

    report = analysis.Report("bucket_coverage", suppressions=suppressions)
    for slots, page, cap, tag in ((4, 8, 64, "pow2"),
                                  (6, 8, 72, "nonpow2")):
        eng = _tiny_serving_engine(num_slots=slots, page_size=page,
                                   max_tokens_per_slot=cap)
        report.extend(hlo_lint.serving_bucket_coverage(
            eng, name=f"serving_{tag}"))
    for capacity, max_uniq, tag in ((64, 48, "pow2"), (50, 50, "nonpow2")):
        cache = DeviceEmbeddingCache(capacity, 9, min_gather_bucket=8)
        report.extend(hlo_lint.embedding_bucket_coverage(
            cache, max_uniq, name=f"embedding_{tag}"))
    report.count_into_registry()
    return report


def lint_kernel_registry(suppressions, cost=False):
    """The kernel-layer contract surface (ISSUE 12): every registered
    Pallas kernel's declared contract (layouts, donation-safety via a
    lowered probe's ``tf.aliasing_output``, zero-collective lowering,
    autotuner blocks within the candidate set) is verified against what
    actually lowers, and every ``pallas_call`` in ``ops/``, ``parallel/``
    and ``serving/`` must belong to a registered kernel (deliberate
    exceptions: ``tools/kernel_registry_allowlist.txt``; stale entries
    are rejected like stale suppressions)."""
    from paddle_tpu import kernels
    return kernels.lint_registry(suppressions)


def concurrency_report(suppressions, *, lock_order):
    """The host-thread tier (``--concurrency``): lock discipline + the
    lock-order graph + drift gate, plus the conformance lints (interface
    drift, reject vocabulary) — one report on the shared spine."""
    from paddle_tpu.analysis import conformance

    report = analysis.lint_concurrency(lock_order=lock_order,
                                       suppressions=suppressions,
                                       registry=False)
    report.extend(conformance.lint_interfaces())
    report.extend(conformance.lint_reject_vocab())
    report.count_into_registry()
    return report


PRESETS = {
    "framework": [lint_lenet, lint_resnet18, lint_gpt_decode,
                  lint_convgroup, lint_serving_decode,
                  lint_serving_prefill, lint_serving_decode_int8,
                  lint_serving_prefill_int8, lint_serving_decode_tp,
                  lint_serving_prefill_tp, lint_serving_prefill_tp_mlp,
                  lint_serving_layer_kinds,
                  lint_embedding_install,
                  lint_embedding_lookup, lint_kernel_registry],
}


def _load_budgets(path: str) -> dict:
    if os.path.exists(path):
        with open(path) as f:
            return json.load(f)
    return {"tolerance": 0.10, "surfaces": {}}


def cost_diff(measured: dict, budgets: dict, *, out=print) -> int:
    """Compare measured static costs against the committed baselines;
    returns 1 when any gated metric regressed beyond tolerance (or a
    surface has no committed baseline)."""
    tol = float(budgets.get("tolerance", 0.10))
    surfaces = budgets.get("surfaces", {})
    rc = 0
    out(f"cost diff vs committed baselines (tolerance {tol:.0%}):")
    for name in sorted(measured):
        spec = surfaces.get(name)
        if spec is None:
            out(f"  FAIL {name}: no committed baseline — run "
                "--update-budgets and commit tools/cost_budgets.json")
            rc = 1
            continue
        for metric in DIFF_METRICS:
            base = int(spec.get(metric, 0))
            now = int(measured[name].get(metric, 0))
            limit = base * (1.0 + tol)
            delta = (now - base) / base if base else (1.0 if now else 0.0)
            flag = ""
            if now > limit:
                flag = f"  REGRESSION (> {tol:+.0%})"
                rc = 1
            elif base and now < base * (1.0 - tol):
                flag = "  (improved — refresh with --update-budgets)"
            out(f"  {name:24s} {metric:18s} {base:>14,d} -> {now:>14,d} "
                f"{delta:+7.1%}{flag}")
    gone = sorted(set(surfaces) - set(measured))
    for name in gone:
        out(f"  FAIL {name}: committed baseline has no measured surface "
            "(remove it from tools/cost_budgets.json)")
        rc = 1
    if rc:
        out("cost diff FAILED — a static cost metric regressed beyond "
            "tolerance (see above); if intended, regenerate the manifest "
            "with --update-budgets and justify it in the PR")
    return rc


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--preset", choices=sorted(PRESETS),
                    default="framework",
                    help="which set of zoo step functions to lint")
    ap.add_argument("--fail-on", choices=("error", "warning"),
                    default="error",
                    help="exit 1 when any unsuppressed finding is at or "
                         "above this severity")
    ap.add_argument("--suppressions", default=DEFAULT_SUPPRESSIONS,
                    help="suppression file (rule-id + substring per line);"
                         " 'none' disables")
    ap.add_argument("--json", action="store_true",
                    help="emit one JSON report per model instead of text")
    ap.add_argument("--list-rules", action="store_true",
                    help="print the rule registry and exit")
    ap.add_argument("--cost", action="store_true",
                    help="add the HLO cost tier: collective/resharding/"
                         "budget rules + the warmup bucket-coverage proof")
    ap.add_argument("--cost-diff", action="store_true",
                    help="fail when static flops/peak-HBM/collective "
                         "bytes regress beyond the committed tolerance")
    ap.add_argument("--budgets", default=DEFAULT_BUDGETS,
                    help="budget manifest (tools/cost_budgets.json)")
    ap.add_argument("--update-budgets", action="store_true",
                    help="rewrite the budget manifest from the current "
                         "measurements (commit it with the PR)")
    ap.add_argument("--concurrency", action="store_true",
                    help="add the host-thread tier: @guarded_by lock "
                         "discipline, lock-order graph + drift gate vs "
                         "tools/lock_order.json, interface conformance, "
                         "Reject.reason vocabulary")
    ap.add_argument("--lock-order", default=DEFAULT_LOCK_ORDER,
                    help="committed lock-order manifest "
                         "(tools/lock_order.json)")
    ap.add_argument("--update-lock-order", action="store_true",
                    help="rewrite the lock-order manifest from the "
                         "extracted graph (refuses while the graph is "
                         "cyclic; commit it with the PR)")
    args = ap.parse_args(argv)

    if args.list_rules:
        for rule, (sev, desc) in sorted(analysis.RULES.items()):
            print(f"{rule:20s} [{sev}] {desc}")
        return 0

    sup = None
    if args.suppressions and args.suppressions != "none" and \
            os.path.exists(args.suppressions):
        sup = analysis.Suppressions.load(args.suppressions)

    cost_mode = args.cost or args.cost_diff or args.update_budgets
    budgets = _load_budgets(args.budgets) if cost_mode else None
    tol = float(budgets.get("tolerance", 0.10)) if budgets else 0.10
    measured = {}

    rc = 0
    for build in PRESETS[args.preset]:
        report = build(sup, cost=cost_mode)
        if cost_mode and report.cost is not None:
            measured[report.name] = report.cost.summary()
            if args.cost:
                spec = budgets["surfaces"].get(report.name, {})
                report.extend(hlo_lint.lint_cost_report(
                    report.cost,
                    collective_allowlist=spec.get("collectives", []),
                    hbm_budget_bytes=int(
                        spec["peak_hbm_bytes"] * (1 + tol))
                    if "peak_hbm_bytes" in spec else None,
                    flops_budget=int(spec["flops"] * (1 + tol))
                    if "flops" in spec else None))
        print(report.render_json() if args.json else report.render_text())
        if not report.ok(args.fail_on):
            rc = 1

    if args.cost:
        report = bucket_coverage_report(sup)
        print(report.render_json() if args.json else report.render_text())
        if not report.ok(args.fail_on):
            rc = 1

    conc_mode = args.concurrency or args.update_lock_order
    if conc_mode:
        # when regenerating, skip the drift gate (it is the thing being
        # rewritten) but keep cycle/double-acquire/discipline findings —
        # a cyclic graph must never be blessed
        report = concurrency_report(
            sup, lock_order=None if args.update_lock_order
            else args.lock_order)
        print(report.render_json() if args.json else report.render_text())
        if not report.ok(args.fail_on):
            rc = 1
        if args.update_lock_order:
            from paddle_tpu.analysis import concurrency as _conc
            if not report.graph.acyclic():
                print("refusing to write a CYCLIC lock-order manifest — "
                      "fix the cycle first (see findings above)",
                      file=sys.stderr)
                rc = 1
            else:
                manifest = _conc.lock_order_manifest(report.graph)
                with open(args.lock_order, "w") as f:
                    json.dump(manifest, f, indent=1, sort_keys=True)
                    f.write("\n")
                print(f"wrote {args.lock_order} "
                      f"({len(manifest['locks'])} locks, "
                      f"{len(manifest['edges'])} edges)")

    if args.update_budgets:
        manifest = {
            "_comment": [
                "Static cost baselines for tools/graph_lint.py "
                "--cost/--cost-diff.",
                "Regenerate with: python tools/graph_lint.py --preset "
                "framework --update-budgets",
                "and commit alongside any PR that legitimately moves "
                "the numbers.",
                "'collectives' is the per-surface allowlist of "
                "permitted collective kinds",
                "(empty = the single-device contract: zero collectives "
                "in the lowered step).",
            ],
            "tolerance": tol,
            "surfaces": {
                name: {**vals,
                       "collectives": budgets["surfaces"]
                       .get(name, {}).get("collectives", [])}
                for name, vals in sorted(measured.items())
            },
        }
        with open(args.budgets, "w") as f:
            json.dump(manifest, f, indent=1, sort_keys=True)
            f.write("\n")
        print(f"wrote {args.budgets} ({len(measured)} surfaces)")
    elif args.cost_diff:
        rc = max(rc, cost_diff(measured, budgets))

    # stale-suppression gate: only meaningful after the FULL preset has
    # had the chance to match every committed entry — and scoped to the
    # tiers that actually ran (a concurrency-rule entry can only match
    # under --concurrency; judging it stale without running that tier
    # would make the plain CI leg reject legitimate committed entries)
    if sup is not None and args.preset == "framework":
        stale = sup.stale()
        if not conc_mode:
            stale = [e for e in stale if e[0] not in CONCURRENCY_RULES]
        if not cost_mode:
            stale = [e for e in stale if e[0] not in COST_RULES]
        if stale:
            for rule, pat in stale:
                print(f"stale suppression: `{rule}  {pat}` matched no "
                      "finding — delete it from "
                      f"{args.suppressions} (dead entries would "
                      "silently re-accept a future regression)",
                      file=sys.stderr)
            rc = 1

    if rc:
        print(f"graph lint FAILED (findings at >= {args.fail_on} "
              "severity; see above)", file=sys.stderr)
    return rc


if __name__ == "__main__":
    sys.exit(main())
