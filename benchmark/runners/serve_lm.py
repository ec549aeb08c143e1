"""A serving cell of a model family that brings its own facts.

``runners/serve.py`` drives the paged engine for GPT-2 and hard-wires
what that family is: its key names, its two kernels, a reference that
returns every position's logits. This runner drives the same engine, the
same way (``Driver``, ``_window``, ``_latencies`` are ``serve.py``'s: the
same clocks, the same token count at both edges, the same ``failed``),
and asks the configuration's **family module** for the rest:

    build(sizes, interpret=)            the program's model
    positions(sizes), vocabulary(sizes)
    reference_logits(params, ids, sizes, lo, rows, probe=)
    KERNELS                             names whose dispatches decide correct
    kernel_needs(...)                   nominal work of each new kernel

Set-up differs in one step: where the traffic file says
``publish_documents``, each distinct shared prefix is submitted once (one
token of output) before the ramp, so that every request of ramp and
window finds its document's pages published.

``correct``: the window compiled nothing; every kernel of ``KERNELS`` ran
on its Pallas body and never on its ``lax`` form; the checked requests'
tokens, teacher-forced through the plain reference at the cell's own
lengths, lie within ``tie_margin`` of the reference's best, and fall short
of it by at most ``mean_shortfall_max`` on average (a routed model in bf16
crosses a routing threshold now and then where its float32 reference does
not, so the worst token alone says little; the two limits and the
readings they were set from are in the configuration file); and where the model
selects, some query in the window attended to fewer tokens than it could
see, every selection a whole multiple of ``topk``, and the selection
itself is the reference's: the last ``PROBE_QUERIES`` tokens of each
checked request's document, replayed through the program's layers over
the pages the engine published (its own indexer keys, K and V, its
indexer kernel and ``top_k``), select in every layer the positions the
reference selects for them: in the first layer, where both sides start
from the same embeddings, but for a share of at most ``1 -
selection_overlap_min``; in every layer at least
``selection_overlap_floor`` (the logits hardly see a coarser selection:
the tokens at the threshold carry little attention weight).
"""

from __future__ import annotations

import importlib

import numpy as np

import common
import traffic_gen
from common import log
from runners.serve import ENGINE_SPANS, Driver, _latencies, _window


class LMDriver(Driver):
    """``Driver`` that also sums, while tracing, the tokens each decode
    token step attended to under a selection of ``topk``."""

    def __init__(self, eng, topk):
        super().__init__(eng)
        self.topk = topk
        self.selected_token_steps = 0.0

    def step(self):
        done = super().step()
        if self.tracing and self.topk:
            n = self.eng.decode_block
            lens = self.eng.cache.lengths
            for i in self.eng.scheduler.decode_slots():
                before = int(lens[i]) - n
                self.selected_token_steps += sum(
                    min(before + j + 1, self.topk) for j in range(n))
        return done


class _CountingProfiler(common.Profiler):
    """The profiler window, with the program's counters read at both of
    its edges: what the traced kernels had to do comes from them."""

    def __init__(self, reg, rehearse):
        super().__init__(rehearse)
        self._reg = reg
        self.counters = {}

    def start(self):
        self._at_start = self._reg.snapshot()
        super().start()

    def stop(self):
        super().stop()
        now = self._reg.snapshot()
        self.counters = {k: v - self._at_start.get(k, 0.0)
                         for k, v in now.items()}


def _dispatch_counts(kernels, impl):
    from paddle_tpu.observability import registry
    c = registry.counter("kernel_dispatch_total")
    return {(k, i): c.value(kernel=k, impl=i)
            for k in kernels for i in (impl, "lax")}


def _warm(eng, job, publish_lanes):
    """``eng.warmup(cost_gauges=False)`` over the signatures this traffic
    reaches. With every request opening on a published document, prefill
    in ramp and window starts past the document and decode runs at the
    widths of the whole request; publishing the documents walks every
    prefill width once, at ``publish_lanes`` lanes."""
    c = eng.cache.config
    share = job.get("shared_prefix") or {}
    published = share.get("tokens", 0) if job.get("publish_documents") else 0
    prompt = job["prompt_tokens"]
    total_hi = prompt["hi"] + job["output_tokens"]["hi"]
    first_chunk_end = max(published, 0) + 1
    pre = {eng._pow2_width(p) for p in range(
        c.pages_for(first_chunk_end), c.pages_for(prompt["hi"]) + 1)}
    dec = {eng._pow2_width(p) for p in range(
        c.pages_for(prompt["lo"] + 1),
        c.pages_for(total_hi + eng.decode_block) + 1)}
    lane_cap = min(max(eng.prefill_budget // eng.prefill_chunk, 1),
                   eng.scheduler.num_slots)
    lanes = {eng._pow2_count(n) for n in range(1, lane_cap + 1)}
    pub = {eng._pow2_width(p) for p in range(1, c.pages_for(published) + 1)} \
        if published else set()
    full = eng.warmup_plan()
    keep = [sig for sig in full
            if (sig[0] == "decode" and sig[1] in dec)
            or (sig[0] == "prefill" and sig[1] in pre and sig[2] in lanes)
            or (sig[0] == "prefill" and sig[1] in pub
                and sig[2] == publish_lanes)
            or sig[0] not in ("decode", "prefill")]
    log(f"warm-up plan: {len(keep)} of the engine's {len(full)} signatures "
        f"(prefill widths {sorted(pre)} at lanes {sorted(lanes)}, publishing "
        f"widths {sorted(pub)} at {publish_lanes} lanes, decode widths "
        f"{sorted(dec)})")
    eng.warmup_plan = lambda: keep
    eng.warmup(cost_gauges=False)


def _documents(queues):
    """The distinct shared prefixes of every request the clients hold."""
    docs = {}
    for q in queues:
        for req in q:
            if req.shared_prefix:
                doc = req.prompt[:req.shared_prefix]
                docs.setdefault(doc.tobytes(), doc)
    return list(docs.values())


def _publish(driver, docs):
    """Submit every document once, one token of output, and run the
    engine dry: its full pages are then in the prefix index."""
    eng = driver.eng
    for doc in docs:
        eng.submit(doc, 1)
    while not eng.scheduler.idle():
        eng.step()


#: queries a checked request's selection is compared on: the last tokens
#: of its document
PROBE_QUERIES = 8


def _document_pages(cache, doc):
    """The pool pages that hold ``doc`` (whole pages of tokens), as the
    prefix index publishes them."""
    from paddle_tpu.serving.paged_cache import prompt_prefix_digests
    found = [cache.lookup_prefix_page(key) for key in
             prompt_prefix_digests(doc, cache.config.page_size)]
    if any(f is None or f[0] != "device" for f in found):
        return None
    return [f[1] for f in found]


def _selection_replay(eng):
    """One decode token a lane through the program's layers as
    ``_decode_loop`` runs them, nothing written (the engine cached these
    tokens' rows when it prefilled them): (params, pool, table (Q, W),
    lengths (Q,) cached tokens, the query the last of them, tokens (Q,))
    -> (L, Q, topk) token indices each layer selects, (Q,) how many of
    them are live."""
    import jax.numpy as jnp
    from paddle_tpu.serving import sparse_attention as SA
    program, spec = eng.program, eng.program.spec

    def replay(params, pool, table, lengths, tokens):
        pos = lengths - 1
        x = program.embed(params, tokens[:, None], pos[:, None])
        chosen = []
        for i in range(spec.num_layers):
            q, _rows, index = program.attn_in(params, i, x, pos[:, None])
            k_pages, v_pages, ik_pages = pool[i]
            idx, n_sel = SA.indexed_decode_selection(
                ik_pages, table, lengths, index[0][:, 0], index[1][:, 0],
                spec.select_topk, impl=eng.attn_impl)
            att = SA.sparse_paged_decode_attention(
                q[:, :, 0, :], k_pages, v_pages, table, idx, n_sel,
                impl=eng.attn_impl)
            x = program.attn_out(params, i, x, att[:, None])
            x, _ = program.ffn(params, i, x,
                               jnp.ones((tokens.shape[0], 1), bool))
            chosen.append(idx)
        return jnp.stack(chosen), n_sel

    return replay


def _program_selection(eng, pages, tokens, lengths):
    """What the program selects for query ``tokens[i]``, the last of
    ``lengths[i]`` cached tokens of pool pages ``pages``."""
    import jax
    import jax.numpy as jnp
    table = np.zeros((len(tokens), eng._pow2_width(len(pages))), np.int32)
    table[:, :len(pages)] = pages
    idx, n_sel = jax.jit(_selection_replay(eng))(
        eng._step_params, eng.cache.pages, jnp.asarray(table),
        jnp.asarray(lengths, jnp.int32), jnp.asarray(tokens, jnp.int32))
    return np.asarray(idx), np.asarray(n_sel)


def _selection_overlap(eng, doc, probe, reference):
    """Share of the program's selections for the ``probe`` positions of
    ``doc`` that ``reference`` (L, Q, N) bool selects too, a layer."""
    pages = _document_pages(eng.cache, doc)
    if pages is None:
        return None
    idx, n_sel = _program_selection(eng, pages, doc[probe], probe + 1)
    live = np.arange(idx.shape[-1])[None, :] < n_sel[:, None]      # (Q, K)
    idx = np.minimum(idx, reference.shape[-1] - 1)      # dead entries
    hit = np.take_along_axis(reference, idx, axis=-1) & live[None]
    return hit.sum((1, 2)) / live.sum()


def _reference_check(fwd, params, pad_to, rows, records, margin, mean_max,
                     eng, limits):
    """Teacher-forced: the engine's own tokens through the plain
    reference; every chosen token within ``margin`` of the best, and
    their mean shortfall at most ``mean_max``; and, where the program
    selects, its selections for the end of each request's document
    against the reference's (``limits``: the least overlap in the first
    layer and in any layer)."""
    import jax
    import jax.numpy as jnp
    all_gaps, overlaps = [], []
    with jax.default_matmul_precision("highest"):
        for rec in records:
            prompt, out = rec.req.prompt, rec.tokens
            n0, n = len(prompt), len(out)
            ids = np.zeros((1, pad_to), np.int32)
            ids[0, :n0] = prompt
            ids[0, n0:n0 + n] = out
            shared = rec.req.shared_prefix
            probe = np.arange(max(shared - PROBE_QUERIES, 0), shared) \
                if limits is not None else np.zeros((0,), np.int64)
            logits, selections = fwd(
                params, jnp.asarray(ids), jnp.asarray(n0 - 1, jnp.int32),
                jnp.asarray(np.resize(probe, PROBE_QUERIES), jnp.int32))
            got = np.asarray(logits)[0, :n].astype(np.float64)
            all_gaps.append(got.max(-1) - got[np.arange(n), out])
            if len(probe):
                # (the precision context above does not reach the replay:
                # its matmuls take bf16 operands or say their precision)
                overlaps.append(_selection_overlap(
                    eng, prompt[:shared], probe,
                    np.asarray(selections)[:, :len(probe)]))
    gaps = np.concatenate(all_gaps)
    worst, mean = float(gaps.max()), float(gaps.mean())
    log(f"reference check: {len(records)} requests, "
        f"{int((gaps == 0).sum())}/{len(gaps)} tokens are the reference's "
        f"own argmax, largest shortfall {worst:.4e} logits (margin "
        f"{margin}), mean shortfall {mean:.4e} (at most {mean_max}); share "
        f"of tokens short by more than "
        + ", ".join(f"{t}: {float((gaps > t).mean()):.4f}"
                    for t in (0.02, 0.05, 0.1, 0.2)))
    ok = worst < margin and mean <= mean_max
    overlap = None
    if limits is not None:
        found = [o for o in overlaps if o is not None]
        by_layer = np.mean(found, axis=0) if found else np.zeros((1,))
        overlap = float(by_layer[0])
        log(f"selection check: {len(found)} of {len(records)} requests' "
            f"documents found in the prefix index; of the program's "
            f"selections for their last {PROBE_QUERIES} tokens the reference "
            f"selects, by layer, "
            + ", ".join(f"{o:.5f}" for o in by_layer)
            + f" (the first, whose inputs are the reference's own, at least "
            f"{limits[0]}; every layer at least {limits[1]})")
        ok = (ok and len(found) == len(records) and overlap >= limits[0]
              and float(by_layer.min()) >= limits[1])
    return ok, worst, mean, overlap


def run(cell: common.Cell) -> common.RunResult:
    import jax
    import jax.numpy as jnp
    from paddle_tpu import inference
    from paddle_tpu import observability as obs

    cfg, job = cell.config, cell.job()
    family = importlib.import_module(f"families.{cfg['family']}")
    sizes = cell.sizes()
    impl = "pallas_interpret" if cell.rehearse else "pallas"
    model = family.build(sizes, interpret=cell.rehearse)
    served = jnp.dtype(cfg["assumed"]["weights_dtype"])
    params = jax.jit(lambda k: model.init(k, dtype=served))(
        jax.random.PRNGKey(cell.seed32))
    jax.block_until_ready(params)
    t_params = common.now() - cell.t_start

    ekw = dict(cfg["engine"])
    if cell.rehearse:
        ekw.update(cfg["rehearsal"]["engine"])
    ekw["attn_impl"] = impl
    ekw["cache_dtype"] = jnp.dtype(ekw["cache_dtype"])
    before = _dispatch_counts(family.KERNELS, impl)
    reg = obs.MetricsRegistry()
    eng = inference.make_serving_engine(model, params, registry=reg, **ekw)
    topk = eng.program.spec.select_topk
    lane_cap = max(eng.prefill_budget // eng.prefill_chunk, 1)
    t_w = common.now()
    max_total = min(ekw["max_tokens_per_slot"], family.positions(sizes))
    _warm(eng, job, eng._pow2_count(min(
        lane_cap, (job.get("shared_prefix") or {}).get("distinct", 1))))
    log(f"weights on the device after {t_params:.1f}s; warmup of "
        f"{len(eng.warmed_signatures)} signatures took "
        f"{common.now() - t_w:.1f}s; JAX reports {cell.watch.line()}")

    # the plain reference at one padded length, compiled in set-up: the
    # logits of ``rows`` positions from a traced start
    pad_to = job["reference_pad_to"]
    rows = min(job["output_tokens"]["hi"], pad_to)
    margin = cfg["assumed"]["tie_margin"]
    mean_max = cfg["assumed"]["mean_shortfall_max"]
    lim = cfg["rehearsal" if cell.rehearse else "assumed"]
    limits = (lim["selection_overlap_min"],
              lim["selection_overlap_floor"]) if topk else None
    ref_fwd = jax.jit(lambda p, ids, lo, probe: family.reference_logits(
        p, ids, sizes, lo, rows, probe=probe))
    with jax.default_matmul_precision("highest"):
        ref_fwd = ref_fwd.lower(
            params, jax.ShapeDtypeStruct((1, pad_to), jnp.int32),
            jax.ShapeDtypeStruct((), jnp.int32),
            jax.ShapeDtypeStruct((PROBE_QUERIES,), jnp.int32)).compile()

    if cell.trace:
        common.annotate_methods(eng, ENGINE_SPANS)
    driver = LMDriver(eng, topk)
    vocab = family.vocabulary(sizes)
    if job["loop"] != "closed":
        raise ValueError("serve_lm drives closed-loop traffic only")
    queues = traffic_gen.closed_loop(job, cell.seed, vocab, max_total)
    if job.get("publish_documents"):
        docs = _documents(queues)
        t_p = common.now()
        _publish(driver, docs)
        log(f"published {len(docs)} documents of {len(docs[0])} tokens in "
            f"{common.now() - t_p:.1f}s: "
            f"{len(eng.cache.published_digests())} pages in the prefix "
            f"index")
    state = {"queues": queues, "cursor": [0] * len(queues),
             "idle": list(range(len(queues)))}
    # ramp: until every client has finished one request
    t_ramp_end = common.now() + job["ramp_max_s"]
    finished_once = set()
    while len(finished_once) < len(queues) and common.now() < t_ramp_end:
        while state["idle"]:
            c = state["idle"].pop()
            q = queues[c]
            driver.submit(q[state["cursor"][c] % len(q)], c, common.now(),
                          False)
            state["cursor"][c] += 1
        for rec in driver.step():
            finished_once.add(rec.client)
            state["idle"].append(rec.client)
    log(f"ramp: {len(finished_once)}/{len(queues)} clients finished a "
        f"request")
    # what the widest decode program takes while it runs, which the
    # allocator's peak leaves out: the compiler's figure
    s_tot = eng.scheduler.num_slots
    z = jnp.zeros((s_tot,), jnp.int32)
    w_hi = max(sig[1] for sig in eng.warmed_signatures if sig[0] == "decode")
    temp_bytes = int(eng.decode_step.lower(
        eng._step_params, eng.cache.pages,
        jnp.zeros((s_tot, w_hi), jnp.int32), z, z,
        z).compile().memory_analysis().temp_size_in_bytes)
    live_bytes = common.live_bytes(cell.devices)
    det = obs.RecompileDetector("bench_serving", warmup=0, registry=reg)
    compiles_before = cell.watch.compiles()

    prof = _CountingProfiler(reg, cell.rehearse) if cell.trace else None
    trace_len = min(job["trace_seconds"], cell.seconds / 2)
    snap0 = reg.snapshot()
    setup_s = common.now() - cell.t_start
    w = _window(driver, job, cell.seconds, state, prof, trace_len)
    snap1 = reg.snapshot()
    det.check()
    compiled = cell.watch.compiles() - compiles_before
    delta = {k: v - snap0.get(k, 0.0) for k, v in snap1.items()}

    ran = {k: v - before[k]
           for k, v in _dispatch_counts(family.KERNELS, impl).items()}
    on_kernel = all(ran[(k, impl)] > 0 and ran[(k, "lax")] == 0
                    for k in family.KERNELS)
    fin = w["finished"]
    ttft, tpot = _latencies(fin)
    fits = [r for r in fin if len(r.req.prompt) - 1 + rows <= pad_to]
    rng = np.random.default_rng(cell.seed)
    picks = [fits[i] for i in
             rng.permutation(len(fits))[:job["check_requests"]]]
    ref_ok, worst, mean_short, overlap = (
        _reference_check(ref_fwd, params, pad_to, rows, picks, margin,
                         mean_max, eng, limits)
        if picks else (False, 0.0, 0.0, None))
    checks = {"no_compile_in_window": det.recompiles == 0 and compiled == 0,
              "kernels_on_pallas": on_kernel,
              "reference": ref_ok and len(picks) == job["check_requests"]}
    if topk:
        seen = delta.get("serving_attn_context_tokens_total", 0.0)
        sel = delta.get("serving_attn_selected_tokens_total", 0.0)
        checks["selected_topk_of_more"] = 0 < sel < seen and sel % topk == 0
    tokens_per_s = w["tokens"] / w["elapsed"]
    log(f"window: {w['elapsed']:.4f}s, {w['attempted']} requests due, "
        f"{len(fin)} finished, {w['failed']} failed; {w['tokens']} output "
        f"tokens = {tokens_per_s:.1f} tokens/s; queue at the end "
        f"{w['queue_at_end']}, deepest {driver.max_queue}; compiles in the "
        f"window {int(compiled)}; dispatches "
        + str({f"{k}[{i}]": int(c) for (k, i), c in ran.items()}))
    cache = eng.cache
    log(f"page pool: {cache.config.num_pages - 1} pages of "
        f"{cache.bytes_per_page() / 1e6:.2f} MB (nominal bytes); since the "
        f"ramp began at most {driver.max_pages_reserved} were mapped by "
        f"requests at once and {driver.max_pages_written} held tokens")
    log("program counters over the window: " + str({
        k: int(v) for k, v in sorted(delta.items())
        if k.startswith(("serving_moe_", "serving_attn_",
                         "serving_prefill_tokens", "serving_prompt_tokens",
                         "serving_prefix_cow"))}))
    log("checks " + str(checks))
    if tpot:
        log(f"ttft ms p50 {common.percentile(ttft, .5):.2f} p95 "
            f"{common.percentile(ttft, .95):.2f}; tpot ms p50 "
            f"{common.percentile(tpot, .5):.3f} p95 "
            f"{common.percentile(tpot, .95):.3f} over {len(tpot)} requests")

    values = {
        "setup_s": setup_s,
        "serve_tokens_per_s": tokens_per_s,
        "requests_per_s": len(fin) / w["elapsed"],
        "reference_shortfall": worst,
        "reference_mean_shortfall": mean_short,
        "chips": float(cell.chips),
        "program_temp_bytes": float(temp_bytes),
        "live_bytes_at_window": float(live_bytes),
    }
    if overlap is not None:
        values["selection_overlap"] = overlap
    if ttft:
        values["ttft_p95_ms"] = common.percentile(ttft, 0.95)
    if tpot:
        values["tpot_p95_ms"] = common.percentile(tpot, 0.95)
        values["tpot_p50_ms"] = common.percentile(tpot, 0.50)
    result = common.RunResult(
        correct=all(checks.values()), attempted=w["attempted"],
        failed=w["failed"], values=values, registry_delta=delta,
        request_stats=[r.stats for r in fin if r.stats])
    if w["traced"]:
        result.trace = prof.summary(cell.chips, cell.survey_path)
        c = cache.config
        values.update(family.kernel_needs(
            sizes, np.dtype(c.dtype).itemsize, c.num_layers, prof.counters,
            driver.live_token_steps, driver.selected_token_steps))
    return result
