"""A serving cell: the program's paged engine, driven from ONE thread.

Between engine steps the driver submits what is due (open loop: a
schedule made before the window; closed loop: a client's next request
once its last has finished), then calls ``eng.step()``. All times are
the host's clock at the END of an engine step, which ends in a sync
(``np.asarray`` of the step's tokens): the first token of a request is
seen at the end of the step that produced it.

Set-up (all counted in ``setup_s``): weights made on the device in one
jitted call from ``--seed`` in the served dtype, the engine, its
``warmup(cost_gauges=False)``, the plain reference program, and for a
closed loop a ramp until every client has finished one request (at most
``ramp_max_s``), so that the slots are out of step when the window
opens.
"""

from __future__ import annotations

import importlib
import time
from typing import Dict, List, Optional

import numpy as np

import common
import flops
import traffic_gen
from common import log

#: the engine's phases, wrapped in host spans for a traced run from the
#: benchmark's side (the program has no spans on the profiler's clock
#: yet); a name the engine no longer has fails the traced run
ENGINE_SPANS = {
    "scheduler.admit": "bench.admit",
    "_prefill_round": "bench.prefill_round",
    "_decode_round": "bench.decode_round",
    "_evict": "bench.evict",
}


class _InFlight:
    __slots__ = ("req", "client", "due", "first", "done", "tokens",
                 "in_window", "stats")

    def __init__(self, req, client, due, in_window):
        self.req, self.client, self.due = req, client, due
        self.in_window = in_window
        self.first = self.done = None
        self.tokens = self.stats = None


class Driver:
    """Submits, steps and keeps the books; one instance per engine."""

    def __init__(self, eng):
        self.eng = eng
        self.flight: Dict[int, _InFlight] = {}      # rid -> record
        self.awaiting_first: set = set()
        self.finished: List[_InFlight] = []
        self.rejected: List[_InFlight] = []
        self.done_tokens = 0            # tokens of finished requests
        self.tracing = False            # host spans + live-token count
        self.live_token_steps = 0.0     # see flops.paged_decode_bytes
        self.max_queue = 0
        self.max_pages_reserved = 0     # pages mapped to slots at once
        self.max_pages_written = 0      # of those, pages holding tokens

    def submit(self, req, client, due, in_window) -> Optional[int]:
        from paddle_tpu.serving.scheduler import LoadShedError
        rec = _InFlight(req, client, due, in_window)
        try:
            rid = self.eng.submit(req.prompt, req.max_new_tokens)
        except LoadShedError:
            self.rejected.append(rec)
            return None
        self.flight[rid] = rec
        self.awaiting_first.add(rid)
        return rid

    def produced(self) -> int:
        """Output tokens handed back so far, live slots included."""
        sch = self.eng.scheduler
        return self.done_tokens + sum(
            len(sch.slots[i].generated) for i in sch.active_slots())

    def step(self) -> List[_InFlight]:
        """One engine step; returns the requests that finished in it."""
        eng = self.eng
        with common.span("bench.step", self.tracing):
            out = eng.step()
        t = common.now()
        sch = eng.scheduler
        if self.awaiting_first:
            for i in sch.active_slots():
                st = sch.slots[i]
                rid = st.request.rid
                if rid in self.awaiting_first and st.generated:
                    self.flight[rid].first = t
                    self.awaiting_first.discard(rid)
        if self.tracing:
            n = eng.decode_block
            lens = eng.cache.lengths
            for i in sch.decode_slots():
                before = int(lens[i]) - n
                # token step j of the block attends over before + j + 1
                self.live_token_steps += n * before + n * (n + 1) / 2
        self.max_queue = max(self.max_queue, sch.queue_depth())
        cache = eng.cache
        self.max_pages_reserved = max(self.max_pages_reserved,
                                      cache.pages_in_use)
        self.max_pages_written = max(self.max_pages_written, int(
            -(-cache.lengths // cache.config.page_size).sum()))
        done = []
        for rid, toks in out.items():
            rec = self.flight.pop(rid)
            if rid in self.awaiting_first:      # first and last in one step
                rec.first = t
                self.awaiting_first.discard(rid)
            rec.done, rec.tokens = t, np.asarray(toks)
            self.done_tokens += len(rec.tokens)
            rec.stats = eng.request_stats(rid)
            self.finished.append(rec)
            done.append(rec)
        return done

    def idle(self) -> bool:
        return self.eng.scheduler.idle()


def _bf16(tree):
    import jax
    import jax.numpy as jnp
    return jax.tree_util.tree_map(
        lambda a: a.astype(jnp.bfloat16)
        if jnp.issubdtype(a.dtype, jnp.floating) else a, tree)


def _dispatch_counts(impl):
    from paddle_tpu.observability import registry
    c = registry.counter("kernel_dispatch_total")
    return {(k, i): c.value(kernel=k, impl=i)
            for k in ("ragged_paged_decode", "ragged_paged_prefill")
            for i in (impl, "lax")}


def _reference_check(fwd, params, pad_to, records, margin):
    """Teacher-forced: the engine's own tokens through the plain
    reference ``fwd``; every chosen token within ``margin`` of the best."""
    import jax
    import jax.numpy as jnp
    worst, exact, total_pos = 0.0, 0, 0
    with jax.default_matmul_precision("highest"):
        for rec in records:
            prompt, out = rec.req.prompt, rec.tokens
            n0, n = len(prompt), len(out)
            ids = np.zeros((1, pad_to), np.int32)
            ids[0, :n0] = prompt
            ids[0, n0:n0 + n] = out
            logits = np.asarray(fwd(params, jnp.asarray(ids)))[0]
            rows = logits[n0 - 1:n0 + n - 1].astype(np.float64)
            gaps = rows.max(-1) - rows[np.arange(n), out]
            worst = max(worst, float(gaps.max()))
            exact += int((gaps == 0).sum())
            total_pos += n
    log(f"reference check: {len(records)} requests, {exact}/{total_pos} "
        f"tokens are the reference's own argmax, largest shortfall "
        f"{worst:.4e} logits (margin {margin})")
    return worst < margin, worst


def _warm_reachable(eng, job, max_total):
    """``eng.warmup(cost_gauges=False)`` over the part of its plan that
    this cell's traffic can reach: prefill widths up to the longest
    prompt, decode widths up to the longest sequence, lane counts up to
    what ``prefill_budget`` lets one round hold. The engine has no way
    to warm a subset, so the plan it iterates is narrowed from outside;
    a signature wrongly left out compiles inside the window, which
    fails the run."""
    c = eng.cache.config
    prompt_hi = job["prompt_tokens"]["hi"]
    total_hi = min(max_total, prompt_hi + job["output_tokens"]["hi"])
    pre = {eng._pow2_width(p)
           for p in range(1, c.pages_for(prompt_hi) + 1)}
    dec = {eng._pow2_width(p) for p in range(
        1, c.pages_for(total_hi + eng.decode_block) + 1)}
    lane_cap = min(max(eng.prefill_budget // eng.prefill_chunk, 1),
                   eng.scheduler.num_slots)
    lanes = {eng._pow2_count(n) for n in range(1, lane_cap + 1)}
    full = eng.warmup_plan()
    keep = [sig for sig in full
            if (sig[0] == "decode" and sig[1] in dec)
            or (sig[0] == "prefill" and sig[1] in pre and sig[2] in lanes)
            or sig[0] not in ("decode", "prefill")]
    log(f"warm-up plan: {len(keep)} of the engine's {len(full)} signatures "
        f"(prefill widths {sorted(pre)}, decode widths {sorted(dec)}, "
        f"lanes {sorted(lanes)})")
    eng.warmup_plan = lambda: keep
    eng.warmup(cost_gauges=False)


def _window(driver: Driver, job, seconds, reqs, prof, trace_len):
    """Run one measured window; returns its facts. ``reqs``: open loop:
    the schedule (``due_s`` offsets); closed loop: per-client queues."""
    closed = job["loop"] == "closed"
    eng = driver.eng
    tracing = False
    late_max = 0.0
    n0_finished, n0_rejected = len(driver.finished), len(driver.rejected)
    nxt = 0
    t0 = common.now()
    t_end = t0 + seconds
    tok0 = driver.produced()
    if closed:
        cursor = reqs["cursor"]
        queues = reqs["queues"]
        idle_clients = reqs["idle"]
    while True:
        t = common.now()
        if t >= t_end:
            break
        if prof is not None and not tracing and t >= t_end - trace_len:
            prof.start()
            tracing = driver.tracing = True
        with common.span("bench.submit", tracing):
            if closed:
                while idle_clients:
                    c = idle_clients.pop()
                    q = queues[c]
                    driver.submit(q[cursor[c] % len(q)], c, t, True)
                    cursor[c] += 1
            else:
                while nxt < len(reqs) and t0 + reqs[nxt].due_s <= t:
                    due = t0 + reqs[nxt].due_s
                    driver.submit(reqs[nxt], None, due, True)
                    late_max = max(late_max, common.now() - due)
                    nxt += 1
        if not closed and driver.idle():
            # nothing in the engine: sleep to the next arrival
            wait = (t0 + reqs[nxt].due_s if nxt < len(reqs) else t_end) - t
            if wait > 0:
                with common.span("bench.no_request_due", tracing):
                    time.sleep(min(wait, 0.002))
                continue
        for rec in driver.step():
            if closed:
                idle_clients.append(rec.client)
    t1 = common.now()
    tok1 = driver.produced()
    if tracing:
        prof.stop()
        driver.tracing = False
    if not closed:
        # due inside the window while its last engine step still ran:
        # sent now, late, and timed from when they were due
        for req in reqs[nxt:]:
            due = t0 + req.due_s
            driver.submit(req, None, due, True)
            late_max = max(late_max, common.now() - due)
    # drain: no new requests; what is in flight may finish
    t_drain_end = common.now() + job["drain_max_s"]
    queue_at_end = eng.scheduler.queue_depth()
    while not driver.idle() and common.now() < t_drain_end:
        driver.step()
    mine = [r for r in driver.finished[n0_finished:] if r.in_window]
    shed = [r for r in driver.rejected[n0_rejected:] if r.in_window]
    unfinished = [r for r in driver.flight.values() if r.in_window]
    return dict(t0=t0, elapsed=t1 - t0, tokens=tok1 - tok0, finished=mine,
                failed=len(shed) + len(unfinished),
                attempted=len(mine) + len(shed) + len(unfinished),
                late_max=late_max, queue_at_end=queue_at_end,
                traced=tracing)


def _latencies(finished):
    ttft = [(r.first - r.due) * 1e3 for r in finished]
    tpot = [(r.done - r.first) * 1e3 / (len(r.tokens) - 1)
            for r in finished if len(r.tokens) > 1]
    return ttft, tpot


def run(cell: common.Cell) -> common.RunResult:
    import jax
    from paddle_tpu import inference
    from paddle_tpu import observability as obs

    cfg, job = cell.config, cell.job()
    family = importlib.import_module(f"families.{cfg['family']}")
    sizes = cell.sizes()
    impl = "pallas_interpret" if cell.rehearse else "pallas"
    model = family.build(sizes, interpret=cell.rehearse)
    served = cfg["assumed"]["weights_dtype"]
    init = (lambda k: _bf16(model.init(k))) if served == "bfloat16" \
        else model.init
    params = jax.jit(init)(jax.random.PRNGKey(cell.seed32))
    jax.block_until_ready(params)
    t_params = common.now() - cell.t_start

    ekw = dict(cfg["engine"])
    if cell.rehearse:
        ekw.update(cfg["rehearsal"]["engine"])
        ekw["attn_impl"] = "pallas_interpret"
    if "cache_dtype" in ekw:
        import jax.numpy as jnp
        ekw["cache_dtype"] = jnp.dtype(ekw["cache_dtype"])
    before = _dispatch_counts(impl)
    reg = obs.MetricsRegistry()
    eng = inference.make_serving_engine(model, params, registry=reg, **ekw)
    t_w = common.now()
    max_total = min(ekw["max_tokens_per_slot"], sizes["n_positions"])
    _warm_reachable(eng, job, max_total)
    log(f"weights on the device after {t_params:.1f}s; warmup of "
        f"{len(eng.warmed_signatures)} signatures took "
        f"{common.now() - t_w:.1f}s; JAX reports {cell.watch.line()}")

    # the plain reference at one padded length, compiled in set-up
    heads = sizes["n_head"]
    pad_to = job["reference_pad_to"]
    # teacher-forced check: the reference's logit of every token the
    # engine chose lies within this margin of the reference's best; the
    # configuration file states the margin and what it was set from
    margin = cfg["assumed"]["tie_margin"]
    ref_fwd = jax.jit(lambda p, ids: family.reference_logits(p, ids, heads))
    with jax.default_matmul_precision("highest"):
        jax.block_until_ready(ref_fwd(params, np.zeros((1, pad_to), np.int32)))

    if cell.trace:
        common.annotate_methods(eng, ENGINE_SPANS)
    driver = Driver(eng)
    vocab = sizes["vocab_size"]
    closed = job["loop"] == "closed"
    if closed:
        queues = traffic_gen.closed_loop(job, cell.seed, vocab, max_total)
        state = {"queues": queues, "cursor": [0] * len(queues),
                 "idle": list(range(len(queues)))}
        # ramp: until every client has finished one request
        t_ramp_end = common.now() + job["ramp_max_s"]
        finished_once = set()
        while len(finished_once) < len(queues) and common.now() < t_ramp_end:
            while state["idle"]:
                c = state["idle"].pop()
                q = queues[c]
                driver.submit(q[state["cursor"][c] % len(q)], c,
                              common.now(), False)
                state["cursor"][c] += 1
            for rec in driver.step():
                finished_once.add(rec.client)
                state["idle"].append(rec.client)
        log(f"ramp: {len(finished_once)}/{len(queues)} clients finished a "
            f"request")
    # what the decode program takes while it runs, which the allocator's
    # peak leaves out: the compiler's figure, from the narrowest decode
    # signature (the same at every width: a re-laid-out copy of the pool)
    import jax.numpy as jnp
    s_tot = eng.scheduler.num_slots
    z = jnp.zeros((s_tot,), jnp.int32)
    temp_bytes = int(eng.decode_step.lower(
        eng._step_params, eng.cache.pages, jnp.zeros((s_tot, 1), jnp.int32),
        z, z, z).compile().memory_analysis().temp_size_in_bytes)
    live_bytes = common.live_bytes(cell.devices)
    det = obs.RecompileDetector("bench_serving", warmup=0, registry=reg)
    compiles_before = cell.watch.compiles()

    if cell.sweep:
        return _sweep(cell, driver, job, vocab, max_total)

    reqs = state if closed else traffic_gen.open_loop(
        job, cell.seed, cell.seconds, vocab, max_total)
    prof = common.Profiler(cell.rehearse) if cell.trace else None
    trace_len = min(job["trace_seconds"], cell.seconds / 2)
    snap0 = reg.snapshot()
    setup_s = common.now() - cell.t_start
    w = _window(driver, job, cell.seconds, reqs, prof, trace_len)
    snap1 = reg.snapshot()
    det.check()
    compiled = cell.watch.compiles() - compiles_before

    ran = {k: v - before[k] for k, v in _dispatch_counts(impl).items()}
    on_kernel = all(ran[(k, impl)] > 0 and ran[(k, "lax")] == 0
                    for k in ("ragged_paged_decode", "ragged_paged_prefill"))
    fin = w["finished"]
    ttft, tpot = _latencies(fin)
    # the checked requests: the shortest-fitting ones, a seeded choice
    fits = [r for r in fin if len(r.req.prompt) + len(r.tokens) <= pad_to]
    rng = np.random.default_rng(cell.seed)
    picks = [fits[i] for i in rng.permutation(len(fits))[:job["check_requests"]]]
    ref_ok, worst = (_reference_check(ref_fwd, params, pad_to, picks, margin)
                     if picks else (False, 0.0))
    checks = {"no_compile_in_window": det.recompiles == 0 and compiled == 0,
              "paged_attention_on_kernel": on_kernel,
              "reference": ref_ok and len(picks) == job["check_requests"]}
    tokens_per_s = w["tokens"] / w["elapsed"]
    counter_tokens = snap1.get("serving_tokens_total", 0.0) \
        - snap0.get("serving_tokens_total", 0.0)
    log(f"window: {w['elapsed']:.4f}s, {w['attempted']} requests due, "
        f"{len(fin)} finished, {w['failed']} failed; {w['tokens']} output "
        f"tokens = {tokens_per_s:.1f} tokens/s (engine counter, drain "
        f"included: {counter_tokens:.0f}); queue at the end "
        f"{w['queue_at_end']}, deepest {driver.max_queue}; latest submit "
        f"{w['late_max'] * 1e3:.2f} ms late; compiles in the window "
        f"{int(compiled)}; dispatches "
        + str({f"{k}[{i}]": int(c) for (k, i), c in ran.items()}))
    cache = eng.cache
    mb = cache.bytes_per_page() / 1e6
    log(f"page pool: {cache.config.num_pages - 1} pages of {mb:.2f} MB "
        f"(nominal bytes); since the ramp began at most "
        f"{driver.max_pages_reserved} were reserved by requests at once "
        f"and {driver.max_pages_written} held tokens")
    log("checks " + str(checks))
    if ttft:
        log(f"ttft ms p50 {common.percentile(ttft, .5):.2f} p95 "
            f"{common.percentile(ttft, .95):.2f}; tpot ms p50 "
            f"{common.percentile(tpot, .5):.3f} p95 "
            f"{common.percentile(tpot, .95):.3f} over {len(tpot)} requests")

    values = {
        "setup_s": setup_s,
        "serve_tokens_per_s": tokens_per_s,
        "requests_per_s": len(fin) / w["elapsed"],
        "late_submit_ms": w["late_max"] * 1e3,
        "reference_shortfall": worst,
        "chips": float(cell.chips),
        "program_temp_bytes": float(temp_bytes),
        "live_bytes_at_window": float(live_bytes),
    }
    if ttft:
        values["ttft_p95_ms"] = common.percentile(ttft, 0.95)
        values["ttft_p50_ms"] = common.percentile(ttft, 0.50)
    if tpot:
        values["tpot_p95_ms"] = common.percentile(tpot, 0.95)
        values["tpot_p50_ms"] = common.percentile(tpot, 0.50)
    delta = {k: v - snap0.get(k, 0.0) for k, v in snap1.items()}
    result = common.RunResult(
        correct=all(checks.values()), attempted=w["attempted"],
        failed=w["failed"], values=values, registry_delta=delta,
        request_stats=[r.stats for r in fin if r.stats])
    if w["traced"]:
        result.trace = prof.summary(cell.chips, cell.survey_path)
        c = eng.cache.config
        values["paged_decode_needed_bytes"] = flops.paged_decode_bytes(
            driver.live_token_steps, c.num_layers, c.num_heads, c.head_dim,
            np.dtype(c.dtype).itemsize)
    return result


def _sweep(cell, driver, job, vocab, max_total) -> common.RunResult:
    """Windows at several rates (and lengths) in ONE process: the tables
    in PERF.md from which an open-loop mix's fixed rate is chosen. Every
    window sends the file's own sizes and arrivals; window k draws its
    tokens from ``--seed`` + k. Not what the driver runs."""
    rows = []
    for k, (rate, seconds) in enumerate(cell.sweep):
        seconds = seconds or cell.seconds
        reqs = traffic_gen.open_loop(job, cell.seed + k, seconds, vocab,
                                     max_total, rate=rate)
        driver.max_queue = 0
        w = _window(driver, job, seconds, reqs, None, 0.0)
        ttft, tpot = _latencies(w["finished"])
        row = {"rate_per_s": rate, "seconds": seconds, "due": w["attempted"],
               "failed": w["failed"], "queue_at_end": w["queue_at_end"],
               "queue_deepest": driver.max_queue,
               "tokens_per_s": w["tokens"] / w["elapsed"],
               "late_submit_ms": w["late_max"] * 1e3}
        if ttft:
            row.update(ttft_p50_ms=common.percentile(ttft, .5),
                       ttft_p95_ms=common.percentile(ttft, .95),
                       tpot_p50_ms=common.percentile(tpot, .5),
                       tpot_p95_ms=common.percentile(tpot, .95))
        rows.append(row)
        log("sweep " + str({k: (round(v, 3) if isinstance(v, float) else v)
                            for k, v in row.items()}))
        while not driver.idle():        # empty the engine between rates
            driver.step()
    return common.RunResult(correct=True, attempted=0, failed=0,
                            values={"sweep": rows})
