"""What the serving tests share: a tap on a program's head, one request
served alone through it, and engines kept for a module.

``make_serving_engine`` jits closures of its own, so a second engine of one
model at one geometry compiles every step program again. A test file
therefore builds ONE engine a geometry (a set of options), through
:func:`shared_engines`, and its cases serve through it one after the other.

**What a case may assume of a shared engine.** It is idle: ``get`` steps
it until the scheduler is, so nothing is queued, no slot is held and no
block is in flight (``eng._pending is None``). Nothing else is as built:

- a slot's state rows and the pool's pages hold what the last request left.
  The step that starts a prompt starts from zeros (the engine's own rule,
  which the reused-slot cases check); a case that needs the pools as a new
  engine has them calls :func:`wipe`;
- with prefix sharing on, pages that earlier cases published are still
  mapped: a prompt served before prefills fewer tokens, and gives the
  same tokens;
- the registry's counters run on from the cases before: read what a case
  moved with :func:`moved`; the engine's tracer (its own, built off) holds
  a case's spans where the case runs under :func:`traced`;
- the step programs met so far are compiled, a bucket that warm-up
  missed among them, and the engine's own ``recompile_detector`` has
  counted every compile the PROCESS made between two of its steps, other
  engines' too. A case that holds warm-up to "nothing compiles after it"
  therefore takes an engine no case has served on: one of its own, or a
  key no other case asks for, and then asserts that it is new
  (``eng.health()["steps"] == 0``: warm-up makes no step).

A case that hands the engine a tracer or a control of its own, substitutes
one of its methods for good, or leaves it broken (a corrupt restore, a
crash, a slot migrated away) builds an engine of its own.
"""

import contextlib
import json
import os

import jax
import numpy as np


class Tap:
    """A serving program whose ``head`` also hands every call's logits to
    ``sink`` on the host, in order. Every other hook is the program's own,
    whatever hooks it has, but for those ``replaced`` (the controls)."""

    def __init__(self, program, sink, **replaced):
        self._p, self._sink = program, sink
        self.__dict__.update(replaced)

    def __getattr__(self, name):        # (only what the instance lacks)
        return getattr(self._p, name)

    def head(self, params, x):
        logits = self._p.head(params, x)
        jax.debug.callback(lambda a: self._sink.append(np.asarray(a)),
                           logits, ordered=True)
        return logits


def tap(eng, **replaced):
    """Put a :class:`Tap` over ``eng``'s program (before its first step:
    a traced step keeps the program it was traced with); -> the sink."""
    sink = []
    eng.program = Tap(eng.program, sink, **replaced)
    return sink


def tapped_engine(model, params, control=None, **kw):
    """An engine of ``model`` under :func:`tap` (``control(program)``
    names the hooks to replace), a registry of its own, its tracer off,
    96 tokens a slot in blocks of 2 unless ``kw`` says otherwise:
    -> ``(engine, sink, registry)``."""
    from paddle_tpu import inference
    from paddle_tpu import observability as obs
    reg = obs.MetricsRegistry()
    eng = inference.make_serving_engine(model, params, **{**dict(
        max_tokens_per_slot=96, decode_block=2, registry=reg,
        tracer=obs.Tracer(enabled=False)), **kw})
    return eng, tap(eng, **(control(eng.program) if control else {})), reg


def drain(eng, max_steps=5000):
    """Step ``eng`` until its scheduler is idle; -> ``{rid: tokens}``. (A
    prefill tier parks a finished prompt's slot until it is polled: what
    it parks is polled and dropped.)"""
    out = {}
    for _ in range(max_steps):
        if eng.scheduler.idle():
            return out
        out.update(eng.step())
        if eng.tier == "prefill":
            eng.poll_handoffs()
    raise AssertionError(f"not idle after {max_steps} steps")


def serve_alone(eng, sink, prompt, n_new):
    """One request alone in the engine, the cache's invariants checked
    after every step: its tokens and the logits of positions
    ``len(prompt) - 1 .. len(prompt) + n_new - 2``."""
    del sink[:]
    rid = eng.submit(prompt, n_new)
    slot = None
    call, calls_made = eng._prefill_call, []

    def noted(lanes):
        calls_made.append(len(lanes))
        return call(lanes)
    eng._prefill_call = noted
    try:
        while not eng.scheduler.idle():
            eng.step()
            eng.cache.check_invariants()
            for i in eng.scheduler.active_slots():
                slot = i
    finally:
        del eng._prefill_call
    jax.effects_barrier()
    out = eng.result(rid)
    # prefill calls hand (lanes, V): the lone request's chunks are the
    # call's live lanes in order (a run of them where the engine forms
    # runs), the call that finished the prompt is the last prefill call
    # and the lane that ended it the last live one; decode token steps
    # hand (slots, V)
    calls = list(sink)
    last_prefill = len(calls_made) - 1
    logits = [calls[last_prefill][calls_made[-1] - 1]]
    logits += [a[slot if slot is not None else 0]
               for a in calls[last_prefill + 1:]]
    return out, np.stack(logits[:n_new])


def serve_noting_calls(eng, prompts, n_new=5):
    """``prompts`` submitted at once and served to the end, the cache's
    invariants (every ring's among them) checked after every step: ->
    (the requests' tokens in order, ``[[lanes_live, lanes, width, tokens,
    longest run] a call]`` a step that made any)."""
    n0 = eng.anatomy.summary()["steps"]
    rids = [eng.submit(p, n_new) for p in prompts]
    out = {}
    while not eng.scheduler.idle():
        out.update(eng.step())
        eng.cache.check_invariants()
    recs = eng.anatomy.records()[-(eng.anatomy.summary()["steps"] - n0):]
    return ([np.asarray(out[r]).tolist() for r in rids],
            [[c[:4] + c[5:] for c in r["prefill_calls"]] for r in recs
             if r.get("prefill_calls")])


def runs_against_one_chunk_a_slot(eng, prompts, n_new=5):
    """Serve ``prompts`` on ``eng`` as it forms its calls, then again
    held to a run of 1 (the call of one chunk a slot), on the same
    compiled programs: -> ((tokens, calls) with runs, (tokens, calls)
    without), the step's budget held to on both sides."""
    with_runs = serve_noting_calls(eng, prompts, n_new)
    limit, eng._run_limit = eng._run_limit, 1
    try:
        without = serve_noting_calls(eng, prompts, n_new)
    finally:
        eng._run_limit = limit
    most = max(eng.prefill_budget, eng.prefill_chunk)
    for _, calls in (with_runs, without):
        assert all(sum(c[3] for c in step) <= most for step in calls)
        assert all(c[0] <= c[1] and c[4] <= limit for step in calls
                   for c in step)
    assert all(c[4] == 1 for step in without[1] for c in step)
    return with_runs, without


def prompt(n, seed=None, vocab=96):
    """``n`` seeded tokens (the seed ``n`` itself unless given)."""
    return np.random.default_rng(n if seed is None else seed).integers(
        0, vocab, n).astype(np.int32)


def reference_rows(reference_logits, pad_to=48):
    """-> ``rows(params, prompt, out, cfg, **kw)``: the plain reference's
    logits of positions ``len(prompt) - 1 .. len(prompt) + len(out) - 2``
    of ``prompt + out``, float32 at the highest matmul precision, computed
    at a padded length (a multiple of ``pad_to``; the pass is causal: what
    follows a token does not reach it) and jitted, so that a file compiles
    its reference once a set of arguments and not once a length."""
    jitted = {}

    def rows(params, prompt, out, cfg, **kw):
        key = repr((cfg, sorted(kw.items())))
        if key not in jitted:
            def plain_reference(p, i):      # (named: no ``jit__lambda``)
                return reference_logits(p, i, cfg, **kw)
            jitted[key] = jax.jit(plain_reference)
        n0, n = len(prompt), len(prompt) + len(out)
        ids = np.zeros((-(-n // pad_to) * pad_to,), np.int32)
        ids[:n] = np.concatenate([prompt, out])
        with jax.default_matmul_precision("highest"):
            logits = np.asarray(jitted[key](params, jax.numpy.asarray(ids)))
        return logits[n0 - 1:n - 1]
    return rows


def assert_close(got, want, rtol=2e-5):
    """``got`` within ``rtol`` OF THE LARGEST of ``want``."""
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=rtol * np.abs(want).max())


def prompts(rng, lens, vocab=64):
    """A prompt of each of ``lens`` tokens, drawn from ``rng``."""
    return [rng.integers(1, vocab, n).astype(np.int32) for n in lens]


def serve_into_a_used_slot_and_alone(eng, sink, reg, first, second):
    """``first`` then ``second`` through slot 0 of an engine that keeps
    state a slot, then ``second`` with every pool zeroed as a new engine
    has them: the second starts from zeros (a reset a request), not from
    what the first left in the slot's rows, and gives bit for bit what it
    gives alone. -> its tokens and logits."""
    before = reg.snapshot()
    serve_alone(eng, sink, first, 5)
    state0 = len(eng.cache.config.kinds[0].pools)   # past the page pools
    for pool in eng.cache.pages[0][state0:]:
        assert np.asarray(pool[1]).any()        # what the first left
    out, got = serve_alone(eng, sink, second, 6)
    assert moved(reg, before)["serving_ssm_state_resets_total"] == 2
    wipe(eng)
    assert not any(np.asarray(a).any() for a in eng.cache.pages[0][state0:])
    out2, got2 = serve_alone(eng, sink, second, 6)
    assert (out == out2).all() and (got == got2).all()
    return out, got


def serve_staggered_watching_state_rows(eng, prompts, n_new=6):
    """Serve ``prompts`` staggered (three at once, the rest three steps
    later) through four slots whose budget prefills a chunk a step while
    others decode, every step held to the rows of its own lanes: a decode
    block leaves the slot-state rows of slots it does not decode (free, or
    in mid-prefill and owning live state) bit for bit, a prefill call those
    of slots outside its lanes, pad lanes included. -> the requests' ids."""
    seen = {"decode_kept": 0, "prefill_kept": 0, "pad_lanes": 0,
            "mid_prefill_during_decode": 0}

    paged = [len(kind.pools) for kind in eng.cache.config.kinds]

    def state_rows(pages):      # {(layer, entry): array}, on the host
        return {(i, k): np.asarray(a) for i, ent in enumerate(pages)
                for k, a in enumerate(ent[paged[i]:])}

    def watch(step, rows_of, kind):
        def run(params_, pages, *args):
            before = state_rows(pages)
            touched = set(rows_of(*args)) | {0}
            out, new_pages = step(params_, pages, *args)
            for key, was in before.items():
                now = np.asarray(new_pages[key[0]][paged[key[0]] + key[1]])
                for r in range(was.shape[0]):
                    if r not in touched:
                        assert (now[r] == was[r]).all(), (kind, key, r)
                        seen[f"{kind}_kept"] += 1
            return out, new_pages
        return run

    def decode_rows(_bt, _lengths, _tokens, active):
        live = np.nonzero(np.asarray(active))[0]
        busy = set(eng.scheduler.active_slots()) - set(live.tolist())
        seen["mid_prefill_during_decode"] += len(busy)
        return (live + 1).tolist()

    def prefill_rows(bt, _starts, _tokens, n_valid):
        rows = np.asarray(bt)[:, -1]
        seen["pad_lanes"] += int((np.asarray(n_valid) == 0).sum())
        assert (rows[np.asarray(n_valid) == 0] == 0).all()
        return rows.tolist()

    steps = eng.decode_step, eng.prefill_step
    eng.decode_step = watch(eng.decode_step, decode_rows, "decode")
    eng.prefill_step = watch(eng.prefill_step, prefill_rows, "prefill")
    try:
        rids = [eng.submit(p, n_new) for p in prompts[:3]]
        for _ in range(3):
            eng.step()
        rids += [eng.submit(p, n_new) for p in prompts[3:]]
        drain(eng)
    finally:
        eng.decode_step, eng.prefill_step = steps
    assert seen["decode_kept"] and seen["prefill_kept"]
    assert seen["pad_lanes"] and seen["mid_prefill_during_decode"]
    return rids


#: for every feature a program may leave out of ``supports``, an engine
#: option that needs it ("draft": the model as its own draft; "call": the
#: two calls of ``prefix_export``, made on an engine that was built)
FEATURE_OPTIONS = {
    "tp": dict(tp=2),
    "int8_pages": dict(cache_dtype=jax.numpy.int8),
    "draft": "draft",
    "host_spill": dict(host_spill_pages=4),
    "migration": dict(snapshot_every_blocks=2),
    "tiers": dict(tier="prefill"),
    "prefix_sharing": dict(prefix_sharing=True),
    "prefix_export": "call",
}


def assert_refused(model, params, feature, said, **base):
    """The engine refuses ``feature`` for ``model`` in a sentence that
    matches ``said`` (a ``ValueError``), at construction or at the call."""
    import pytest

    from paddle_tpu import inference
    base = {**dict(num_slots=2, page_size=4, attn_impl="lax"), **base}
    kw = FEATURE_OPTIONS[feature]
    if kw == "call":
        eng = inference.make_serving_engine(model, params, **base)
        for call, arg in ((eng.export_prefix_pages, [1]),
                          (eng.import_prefix_pages, {})):
            with pytest.raises(ValueError, match=said):
                call(arg)
        return
    if kw == "draft":
        kw = dict(draft_model=model, draft_params=params)
    with pytest.raises(ValueError, match=said):
        inference.make_serving_engine(model, params, **base, **kw)


def benchmark_config(name, depth, default=None):
    """``benchmark/configs/<name>.json`` carries the catalog's numbers at
    its top level (where the driver compares them) and under ``sizes``
    (where the runner reads them): the same, but for the depth, cut to
    ``depth``; and a program config's ``default`` s are those numbers. ->
    the file."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs", name + ".json")) as f:
        cfg = json.load(f)
    for key, value in cfg["sizes"].items():
        assert cfg[key] == value, key
        if hasattr(default, key) and key != "num_hidden_layers":
            got = getattr(default, key)
            assert (list(got) if isinstance(got, tuple) else got) == value, key
    assert cfg["reduced"] == ["num_hidden_layers"]
    assert cfg["num_hidden_layers"] == depth
    return cfg


def random_prompts(n, rng=None, lo=3, hi=9, vocab=64):
    """``n`` prompts of ``lo`` to ``hi - 1`` tokens (seed 0 unless given)."""
    rng = rng or np.random.default_rng(0)
    return [rng.integers(1, vocab, int(rng.integers(lo, hi)))
            .astype(np.int32) for _ in range(n)]


def tiny_gpt(seed=0, **kw):
    """The fleet, migration and quantisation batteries' model: ``(GPT,
    params)`` at vocabulary 64, width 16, two layers of two heads."""
    from paddle_tpu.models.gpt import GPT, GPTConfig
    model = GPT(GPTConfig.tiny(
        vocab_size=64, hidden_size=16, num_layers=2, num_heads=2,
        ffn_size=32, max_position=64, dropout=0.0, attn_impl="xla", **kw))
    return model, model.init(jax.random.PRNGKey(seed))


def dense_reference(model, params, prompt, max_new):
    """``model.generate``'s greedy tokens past ``prompt`` (no engine)."""
    out = model.generate(params, jax.numpy.asarray(prompt)[None],
                         max_new_tokens=max_new, use_cache=True)
    return np.asarray(out)[0, len(prompt):]


def fleet_engine(model_params, tracer=None, **kw):
    """A ``lax`` engine of :func:`tiny_gpt` at the fleet batteries'
    geometry (pages and chunks of 4, 32 tokens a slot), a registry of its
    own."""
    from paddle_tpu import observability as obs
    from paddle_tpu import serving
    kw = {**dict(num_slots=4, page_size=4, max_tokens_per_slot=32,
                 prefill_chunk=4), **kw}
    return serving.ServingEngine(*model_params, attn_impl="lax",
                                 registry=obs.MetricsRegistry(),
                                 tracer=tracer, **kw)


def warmed_engines(model_params, **defaults):
    """:func:`shared_engines` over :func:`fleet_engine`: ``get(peer=0,
    **options)`` is engine number ``peer`` of these options over
    ``defaults``, its tracer its own and off, warmed ONCE and idle."""
    from paddle_tpu import observability as obs

    def build(peer=0, **kw):
        eng = fleet_engine(model_params, obs.Tracer(capacity=2048,
                                                    enabled=False),
                           **{**defaults, **kw})
        eng.warmup()
        return eng
    return shared_engines(build)


def churn_a_prefix_pool(steps, **cache):
    """``steps`` random reserves (half), publications and frees over four
    slots, 14 pages and six recurring prompts (one a verbatim repeat, so
    prefixes overlap heavily), the cache's invariants checked after each:
    pages never leak, refcounts are the live mappings."""
    from paddle_tpu.serving.paged_cache import (PagedCacheConfig,
                                                PagedKVCache,
                                                PageOverflowError)
    rng = np.random.default_rng(22)
    c = PagedKVCache(PagedCacheConfig(
        num_layers=1, num_heads=2, head_dim=4, num_slots=4, page_size=4,
        num_pages=14, max_pages_per_slot=4, **cache))
    pool = [rng.integers(1, 9, n).astype(np.int32)
            for n in (6, 9, 10, 13, 10)]
    pool.append(pool[2].copy())
    live = {}
    for _step in range(steps):
        op = rng.random()
        free_slots = [s for s in range(4) if s not in live]
        if op < 0.5 and free_slots:
            slot = int(rng.choice(free_slots))
            prompt = pool[int(rng.integers(len(pool)))]
            total = len(prompt) + int(rng.integers(1, 4))
            try:
                shared = c.reserve(slot, total, prompt=prompt)
            except PageOverflowError:
                c.check_invariants()
                continue
            assert 0 <= shared < len(prompt)
            live[slot] = (prompt, shared)
        elif op < 0.7 and live:
            slot = int(rng.choice(list(live)))
            if c.pending_copy(slot) is not None:
                c.copy_done(slot)           # (an engine would copy it)
            prompt, shared = live[slot]
            upto = int(rng.integers(shared, len(prompt) + 1))
            if c.pending_copy(slot) is None:
                c.publish_prefix(slot, prompt, upto)
        elif live:
            slot = int(rng.choice(list(live)))
            c.free_slot(slot)
            del live[slot]
        c.check_invariants()
    for slot in list(live):
        c.free_slot(slot)
    c.check_invariants()
    assert c.pages_in_use == 0, "pages leaked"


def fleet_of(model_params, n, warmed=None, first=0, tracer=None, wrap=None,
             engine=fleet_engine, **kw):
    """``n`` warmed ``LocalReplica`` s ``r0..`` behind a ``FleetRouter``
    with a registry of its own: over the module's engines ``first..``
    with ``warmed`` (:func:`warmed_engines`), else over engines of their
    own from ``engine``, as a ``tracer`` shared with the router needs.
    ``wrap`` maps a replica's index to the ``ChaosSpec`` of a
    ``ChaosReplica`` around it; of ``kw`` the router takes its own
    options and the engines the rest. -> (router, replicas)"""
    from paddle_tpu import observability as obs
    from paddle_tpu.serving import fleet
    assert warmed is None or tracer is None
    tracer = tracer or obs.Tracer(enabled=False)
    router_kw = {k: kw.pop(k) for k in ("policy", "seed", "autoscaler",
                                        "faults", "clock", "prefix_fetch")
                 if k in kw}
    reps = [fleet.LocalReplica(warmed(first + i, **kw), name=f"r{i}")
            if warmed else fleet.LocalReplica(
                engine(model_params, tracer=tracer, **kw),
                name=f"r{i}").warmup() for i in range(n)]
    for i, spec in (wrap or {}).items():
        reps[i] = fleet.ChaosReplica(reps[i], **spec)
    return fleet.FleetRouter(reps, registry=obs.MetricsRegistry(),
                             tracer=tracer, **router_kw), reps


# -- the read-back battery (ISSUE 31 and 34: ``test_serving_readback*.py``,
# ``test_serving_overlap.py``) ------------------------------------------------

#: seven prompts over one 10-token prefix (the last repeats the second),
#: 10 tokens each; the same for every kind of engine
PARENT_TOKENS = [
    [89, 124, 124, 124, 124, 49, 124, 49, 49, 49],
    [39, 49, 120, 39, 120, 34, 120, 2, 39, 39],
    [49, 42, 49, 124, 39, 124, 49, 124, 39, 27],
    [36, 36, 36, 36, 36, 36, 36, 36, 36, 89],
    [124, 124, 124, 124, 124, 124, 49, 49, 49, 49],
    [27, 27, 42, 27, 60, 27, 60, 27, 60, 89],
    [39, 49, 120, 39, 120, 34, 120, 2, 39, 39],
]


def readback_gpt():
    """``(GPT, params)``: the tiny GPT at four heads."""
    from paddle_tpu.models.gpt import GPT, GPTConfig
    model = GPT(GPTConfig.tiny(num_heads=4, attn_impl="xla"))
    return model, model.init(jax.random.PRNGKey(5))


def readback_engine(model_params, **over):
    from paddle_tpu import inference
    from paddle_tpu import observability as obs
    kw = dict(num_slots=4, page_size=8, max_tokens_per_slot=56,
              prefill_chunk=8, decode_block=3, attn_impl="pallas_interpret",
              registry=obs.MetricsRegistry())
    kw.update(over)
    return inference.make_serving_engine(*model_params, **kw)


def readback_prompts(vocab):
    rng = np.random.default_rng(23)
    shared = rng.integers(0, vocab, 10)
    tails = [rng.integers(0, vocab, n) for n in (2, 19, 7, 30, 1, 12)]
    ps = [np.concatenate([shared, t]).astype(np.int32) for t in tails]
    return ps + [ps[1].copy()]


def readbacks(eng):
    return sum(v for k, v in eng._reg.snapshot().items()
               if k.startswith("serving_device_readbacks_total"))


def drain_counting(eng):
    """Step to idle; -> ({rid: tokens}, steps, most read-backs a step)."""
    out, steps, most = {}, 0, 0
    while not eng.scheduler.idle():
        before = readbacks(eng)
        out.update(eng.step())
        most = max(most, readbacks(eng) - before)
        steps += 1
    return out, steps, most


def once(build):
    """-> ``get(*key, **options)``: what ``build`` returned the first
    time it was asked for these (nothing is kept of a call that raised,
    or skipped)."""
    built = {}

    def get(*key, **options):
        at = (key, tuple(sorted(options.items())))
        if at not in built:
            built[at] = build(*key, **options)
        return built[at]
    return get


def shared_engines(build):
    """:func:`once` over a ``build`` that returns an engine, or a tuple
    that starts with one (``(engine, sink, registry)``): ``get`` hands it
    out idle. Call it once in a module-scoped fixture."""
    built = once(build)

    def get(*key, **options):
        got = built(*key, **options)
        eng = got[0] if isinstance(got, tuple) else got
        drain(eng)
        assert eng._pending is None and eng._owed == []
        return got
    return get


def moved(reg, before):
    """What each series of ``reg`` moved by since ``before`` (a
    ``reg.snapshot()``): a case's own counts on a shared engine."""
    return {k: v - before.get(k, 0) for k, v in reg.snapshot().items()}


@contextlib.contextmanager
def traced(eng):
    """``eng``'s tracer (one of its own, built off) emptied and on for
    the block: -> the tracer, whose ``spans()`` are the block's."""
    eng.tracer.clear()
    eng.tracer.enable()
    try:
        yield eng.tracer
    finally:
        eng.tracer.disable()


def wipe(eng):
    """Zero an idle engine's pools: every page and every slot's state row
    as a new engine has them (not where published pages stay mapped)."""
    assert eng.scheduler.idle() and eng._pending is None
    assert not eng.cache.config.share_prefix
    eng.cache.pages = jax.tree.map(jax.numpy.zeros_like, eng.cache.pages)
