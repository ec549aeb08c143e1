"""Paged KV-cache serving engine: paged-vs-dense equivalence, scheduler
properties under randomized arrivals, prefix-sharing refcount/CoW
invariants, SLO scheduling, and steady-state recompile-freedom (ISSUE 4 +
ISSUE 6 acceptance surface). The allocator's invariants, the two ragged
attention kernels' parity and a page's wire format are in
``tests/test_kernels_paged.py``."""

import numpy as np
import pytest

from paddle_tpu import observability as obs
from paddle_tpu import serving
from paddle_tpu.serving.paged_cache import PagedCacheConfig, PagedKVCache

from serving_taps import churn_a_prefix_pool
from serving_taps import dense_reference as _dense_reference
from serving_taps import prompts as _prompts, tiny_gpt as _model


class TestPagedVsDense:
    """ISSUE 4 acceptance: identical greedy tokens, engine vs dense."""

    def test_mixed_length_batch_matches_dense(self):
        model, params = _model()
        rng = np.random.default_rng(3)
        prompts = _prompts(rng, [5, 9, 3, 12, 7])
        eng = serving.ServingEngine(model, params, num_slots=3,
                                    page_size=4, prefill_chunk=8,
                                    attn_impl="lax")
        outs = eng.generate_many(prompts, max_new_tokens=6, max_steps=200)
        for p, o in zip(prompts, outs):
            ref = _dense_reference(model, params, p, 6)
            np.testing.assert_array_equal(o, ref)
        eng.cache.check_invariants()
        assert eng.cache.pages_in_use == 0

    def test_engine_with_pallas_interpret_kernel(self):
        """End-to-end through the REAL decode kernel on CPU."""
        model, params = _model(seed=1)
        rng = np.random.default_rng(4)
        prompts = _prompts(rng, [4, 10])
        eng = serving.ServingEngine(model, params, num_slots=2,
                                    page_size=4, prefill_chunk=8,
                                    attn_impl="pallas_interpret")
        outs = eng.generate_many(prompts, max_new_tokens=5, max_steps=100)
        for p, o in zip(prompts, outs):
            np.testing.assert_array_equal(
                o, _dense_reference(model, params, p, 5))

    def test_early_eos_eviction_and_result(self):
        """A sequence hitting EOS stops early, frees its pages, and its
        tokens still match the dense decode truncated at EOS."""
        model, params = _model()
        rng = np.random.default_rng(5)
        prompt = _prompts(rng, [6])[0]
        full = _dense_reference(model, params, prompt, 12)
        eos = int(full[3])   # force an "EOS" a few tokens in
        stop = int(np.argmax(full == eos)) + 1   # first occurrence
        eng = serving.ServingEngine(model, params, num_slots=2,
                                    page_size=4, attn_impl="lax")
        out = eng.generate_many([prompt], max_new_tokens=12, eos_id=eos,
                                max_steps=100)[0]
        np.testing.assert_array_equal(out, full[:stop])
        assert eng.cache.pages_in_use == 0

    def test_submit_rejects_oversized_request(self):
        model, params = _model()
        eng = serving.ServingEngine(model, params, num_slots=2,
                                    page_size=4, max_tokens_per_slot=16,
                                    attn_impl="lax")
        with pytest.raises(ValueError):
            eng.submit(np.ones(10, np.int32), max_new_tokens=10)

    @pytest.mark.slow
    def test_via_inference_facade(self):
        from paddle_tpu import inference
        model, params = _model()
        rng = np.random.default_rng(6)
        prompts = _prompts(rng, [5, 8])
        eng = inference.make_serving_engine(model, params, num_slots=2,
                                            page_size=4, attn_impl="lax")
        outs = eng.generate_many(prompts, max_new_tokens=4, max_steps=100)
        for p, o in zip(prompts, outs):
            np.testing.assert_array_equal(
                o, _dense_reference(model, params, p, 4))


class TestSchedulerProperty:
    """Randomized arrival order / lengths: every request completes,
    outputs match single-request decode, pages never leak."""

    def test_randomized_arrivals_all_complete(self):
        model, params = _model(seed=2)
        rng = np.random.default_rng(7)
        eng = serving.ServingEngine(model, params, num_slots=2,
                                    page_size=4, prefill_chunk=8,
                                    num_pages=17, attn_impl="lax")
        n_req = 9
        lens = rng.integers(2, 14, n_req)
        max_news = rng.integers(1, 8, n_req)
        prompts = _prompts(rng, lens)
        rids = {}
        pending = list(range(n_req))
        rng.shuffle(pending)
        submitted = 0
        for _ in range(500):
            # trickle submissions in shuffled order, ~0-2 per step
            while submitted < n_req and rng.random() < 0.6:
                i = pending[submitted]
                rids[i] = eng.submit(prompts[i], int(max_news[i]))
                submitted += 1
            eng.step()
            if submitted == n_req and eng.scheduler.idle():
                break
        assert eng.scheduler.idle(), "requests left behind"
        for i in range(n_req):
            out = eng.result(rids[i])
            assert out is not None, f"request {i} never finished"
            ref = _dense_reference(model, params, prompts[i],
                                   int(max_news[i]))
            np.testing.assert_array_equal(out, ref)
        eng.cache.check_invariants()
        assert eng.cache.pages_in_use == 0

    def test_batch_admission_cannot_overcommit_pages(self):
        """Two requests each needing most of a down-sized pool, both
        admissible against the INITIAL free count: admission must
        reserve as it goes, admitting one and queueing the other — not
        crash mid-step with a PageOverflowError."""
        model, params = _model()
        eng = serving.ServingEngine(model, params, num_slots=4,
                                    page_size=4, num_pages=7,  # 6 usable
                                    max_tokens_per_slot=16,
                                    attn_impl="lax")
        rng = np.random.default_rng(9)
        prompts = _prompts(rng, [8, 8])
        outs = eng.generate_many(prompts, max_new_tokens=8,
                                 max_steps=200)  # 4 pages per request
        for p, o in zip(prompts, outs):
            np.testing.assert_array_equal(
                o, _dense_reference(model, params, p, 8))
        eng.cache.check_invariants()
        assert eng.cache.pages_in_use == 0

    def test_fifo_head_blocking_no_starvation(self):
        """A large request at the queue head waits for pages but is
        never overtaken — it runs as soon as capacity frees."""
        from paddle_tpu.serving.scheduler import (
            ContinuousBatchingScheduler, Request)
        big_ok = {"allowed": False}

        def can_admit(req: Request):
            return req.max_new_tokens < 10 or big_ok["allowed"]

        s = ContinuousBatchingScheduler(2, can_admit=can_admit)
        s.submit(np.ones(4, np.int32), 20)   # big, blocked
        s.submit(np.ones(4, np.int32), 2)    # small, behind it
        assert s.admit() == []               # head blocks the line
        big_ok["allowed"] = True
        assert s.admit() == [0, 1]           # big first, FIFO preserved
        assert s.slots[0].request.max_new_tokens == 20
        assert s.slots[1].request.max_new_tokens == 2


class TestServingObservability:
    def test_metrics_and_zero_steady_state_recompiles(self):
        model, params = _model()
        rng = np.random.default_rng(8)
        reg = obs.MetricsRegistry()
        eng = serving.ServingEngine(model, params, num_slots=2,
                                    page_size=4, attn_impl="lax",
                                    registry=reg)
        eng.warmup()   # compiles every gather bucket + the prefill chunk
        det = obs.RecompileDetector("serving_steady", warmup=0,
                                    registry=reg)
        eng.generate_many(_prompts(rng, [9, 4, 6]), max_new_tokens=4,
                          max_steps=100)
        det.check()
        assert det.recompiles == 0, "steady-state serving recompiled"
        snap = reg.snapshot()
        assert snap["serving_requests_total"] == 3
        assert snap["serving_tokens_total"] == 3 * 4
        assert any(k.startswith("serving_ttft_seconds") for k in snap)
        assert reg.get("serving_slot_occupancy") is not None
        assert reg.get("serving_page_utilization") is not None
        assert reg.get("serving_queue_wait_seconds") is not None

    def test_hbm_scales_with_live_tokens_not_horizon(self):
        """The paging claim: page-pool bytes for a tiny active set stay
        far below the dense cache's batch x max_len allocation."""
        model, params = _model()
        cfg = model.cfg
        eng = serving.ServingEngine(model, params, num_slots=8,
                                    page_size=4, num_pages=9,
                                    max_tokens_per_slot=32,
                                    attn_impl="lax")
        # dense cache for the same 8 slots at the engine's horizon:
        # 8 * H * 32 * Dh floats/layer/KV; the page pool holds 8 pages
        kp, _ = eng.cache.pages[0]
        dense = 8 * cfg.num_heads * 32 * (cfg.hidden_size // cfg.num_heads)
        assert kp.size < dense / 4

    def test_ttft_split_accounting(self):
        """ISSUE 6 satellite: submit->admit (queue wait) and
        admit->first-token (prefill cost) are separate histograms whose
        sum is the TTFT — scheduler effects no longer hide inside one
        conflated number."""
        model, params = _model()
        rng = np.random.default_rng(11)
        reg = obs.MetricsRegistry()
        eng = serving.ServingEngine(model, params, num_slots=2,
                                    page_size=4, attn_impl="lax",
                                    registry=reg)
        n = 5   # > num_slots so some requests genuinely queue
        eng.generate_many(_prompts(rng, [6] * n), max_new_tokens=3,
                          max_steps=200)
        qw = reg.histogram("serving_queue_wait_seconds").summary()
        a2f = reg.histogram(
            "serving_admit_to_first_token_seconds").summary()
        ttft = reg.histogram("serving_ttft_seconds").summary()
        assert qw["count"] == a2f["count"] == ttft["count"] == n
        # identical timestamps on both sides of the split: sums add up
        assert ttft["sum"] == pytest.approx(qw["sum"] + a2f["sum"],
                                            abs=5e-3)
        assert reg.histogram("serving_ttft_seconds").quantile(0.99) >= \
            reg.histogram("serving_ttft_seconds").quantile(0.5)

    def test_prefill_budget_caps_per_step_tokens(self):
        """The decode/prefill interleaving contract: one step() computes
        at most ``prefill_budget`` prompt tokens (a long-prompt burst
        cannot starve in-flight decodes), while a budget below one chunk
        still advances one lane per round (liveness)."""
        model, params = _model()
        rng = np.random.default_rng(13)
        reg = obs.MetricsRegistry()
        eng = serving.ServingEngine(model, params, num_slots=4,
                                    page_size=4, prefill_chunk=8,
                                    prefill_budget=8, attn_impl="lax",
                                    registry=reg)
        prompts = _prompts(rng, [30, 29, 27, 25])
        rids = [eng.submit(p, 2) for p in prompts]
        pf = reg.counter("serving_prefill_tokens_total")
        steps = 0
        while not eng.scheduler.idle():
            before = pf.value()
            eng.step()
            assert pf.value() - before <= 8, \
                "step() overshot the prefill budget"
            steps += 1
            assert steps < 500
        for r, p in zip(rids, prompts):
            assert np.array_equal(eng.result(r),
                                  _dense_reference(model, params, p, 2))

        reg2 = obs.MetricsRegistry()
        eng2 = serving.ServingEngine(model, params, num_slots=4,
                                     page_size=4, prefill_chunk=8,
                                     prefill_budget=2, attn_impl="lax",
                                     registry=reg2)
        pf2 = reg2.counter("serving_prefill_tokens_total")
        for p in prompts:
            eng2.submit(p, 2)
        steps = 0
        while not eng2.scheduler.idle():
            before = pf2.value()
            eng2.step()
            # sub-chunk budget: exactly one lane runs, so the overshoot
            # is bounded by a single chunk — never a full batched call
            assert pf2.value() - before <= 8
            steps += 1
            assert steps < 500


    @pytest.mark.parametrize("case", [
        "a_budget_of_three_lanes", "a_budget_under_a_chunk",
        "partial_chunks_leave_no_narrow_second_call",
        "prompts_admitted_behind_a_call_wait_for_the_next_step"])
    def test_a_round_that_carries_runs_keeps_to_the_steps_budget(self, case):
        """ISSUE 54, a plain pool (a run as long as the call): a step
        spends at most ``max(prefill_budget, prefill_chunk)`` prompt
        tokens, in ONE call; under a chunk of budget the liveness lane
        still moves a slot a step. Tokens are the dense reference's."""
        from serving_taps import serve_noting_calls
        slots, budget, lengths, n_new = {
            "a_budget_of_three_lanes": (4, 12, [30, 29, 27, 25], 2),
            "a_budget_under_a_chunk": (4, 2, [9, 6], 2),
            # three prompts of 4 + 1 tokens: a chunk each and the nearest's
            # second are four lanes and 13 tokens; the 3 left buy no lane,
            # and the two last tokens wait for the next step
            "partial_chunks_leave_no_narrow_second_call":
                (4, 16, [5, 5, 5], 2),
            # 24 slots of 2-token prompts that end on their first token:
            # the first call's 24 lanes spend 48 of 96 tokens and their
            # slots are free at once; the 12 prompts admitted behind them
            # lead the next step's call
            "prompts_admitted_behind_a_call_wait_for_the_next_step":
                (24, 96, [2] * 36, 1),
        }[case]
        model, params = _model()
        eng = serving.ServingEngine(
            model, params, num_slots=slots, page_size=4, prefill_chunk=4,
            prefill_budget=budget, attn_impl="lax",
            registry=obs.MetricsRegistry())
        prompts = _prompts(np.random.default_rng(54), lengths)
        tokens, steps = serve_noting_calls(eng, prompts, n_new=n_new)
        for p, toks in zip(prompts, tokens):
            assert np.array_equal(
                toks, _dense_reference(model, params, p, n_new))
        assert all(sum(c[3] for c in step) <= max(budget, 4)
                   for step in steps)
        assert sum(c[3] for step in steps for c in step) == sum(lengths)
        assert all(len(step) == 1 for step in steps)
        if case == "a_budget_of_three_lanes":
            # four prompts for three lanes: the three nearest, a chunk
            # each; runs form once fewer prompts than lanes are left
            assert steps[0] == [[3, 4, 1, 12, 1]]
            assert max(c[4] for step in steps for c in step) == 3
        elif case == "a_budget_under_a_chunk":
            assert eng._lane_cap == eng._run_limit == 1
            assert all([c[:2] for c in step] == [[1, 1]] for step in steps)
            assert len(steps) == 3 + 2              # a chunk a step
        elif case.startswith("partial"):
            assert [[c[0] for c in step] for step in steps] == [[4], [2]]
            assert steps[0][0][3] == 13 and steps[0][0][4] == 2
        else:
            assert [[c[:2] for c in step] for step in steps] \
                == [[[24, 24]], [[12, 16]]]


    @pytest.mark.parametrize("case, break_even, calls", [
        ("the_surplus_of_a_burst_goes_in_the_same_step", 2, [[8, 4]]),
        ("a_surplus_under_the_break_even_waits_a_step", 5, [[8], [4]]),
        ("held_to_one_chunk_a_slot_the_loop_is_as_it_was", None, [[8, 4]])])
    def test_a_further_call_is_for_the_slots_a_call_had_no_lane_for(
            self, case, break_even, calls):
        """ISSUE 54, the round's rule on a step's further calls, on a
        plain pool: 12 prompts of half a chunk for a call of 8 lanes and
        a budget of 8 chunks. The first call spends half the budget and
        its 8 slots decode from this step on; the 4 it had no lane for
        go in a second call of the same step where they reach the
        break-even in lanes (set by hand: this toy's own, float32 at a
        chunk of 4, is 120) and wait for the next step's where not.
        With the break-even of a bf16 stage at a chunk of 128 that is
        the call sequence of the loop of one chunk a slot."""
        from serving_taps import serve_noting_calls
        from paddle_tpu.serving import engine as E
        model, params = _model()
        eng = serving.ServingEngine(
            model, params, num_slots=12, page_size=4, prefill_chunk=4,
            prefill_budget=32, decode_block=2, attn_impl="lax",
            registry=obs.MetricsRegistry())
        assert eng._lane_cap == eng._run_limit == 8
        assert eng._second_call_lanes == E._break_even_lanes(4, 4) == 120
        if break_even is None:
            eng._run_limit = 1
        else:
            eng._second_call_lanes = break_even
        prompts = _prompts(np.random.default_rng(54), [2] * 12)
        tokens, steps = serve_noting_calls(eng, prompts, n_new=3)
        for p, toks in zip(prompts, tokens):
            assert np.array_equal(
                toks, _dense_reference(model, params, p, 3))
        assert [[c[0] for c in step] for step in steps] == calls
        assert all(sum(c[3] for c in step) <= 32 for step in steps)

    @pytest.mark.parametrize("dtype, chunk, lanes", [
        ("bfloat16", 128, 2), ("bfloat16", 64, 4), ("bfloat16", 32, 8),
        ("float32", 128, 4), ("float32", 4, 120), ("int8", 128, 1)])
    def test_the_break_even_follows_the_weights_bytes_and_the_chunk(
            self, dtype, chunk, lanes):
        """240 flops a byte: a bf16 weight's read is paid at 240 rows, so
        at 2 lanes of 128 (both window cells) and 8 of 32 (gpt2)."""
        from paddle_tpu.serving import engine as E
        assert E._FLOPS_PER_HBM_BYTE == 240
        import jax.numpy as jnp
        assert E._break_even_lanes(
            jnp.dtype(dtype).itemsize, chunk) == lanes


class TestPrefixSharing:
    """ISSUE 6: refcounted copy-on-write prefix/page sharing."""

    def test_shared_prefix_parity_and_savings(self):
        """Greedy tokens identical with sharing on/off; prefill tokens
        COMPUTED drop when prompts share a system prefix."""
        model, params = _model(seed=3)
        rng = np.random.default_rng(20)
        prefix = rng.integers(1, 64, 10).astype(np.int32)
        prompts = [np.concatenate([prefix,
                                   rng.integers(1, 64, t).astype(np.int32)])
                   for t in (3, 5, 2, 7, 4, 6)]

        def run(share):
            reg = obs.MetricsRegistry()
            eng = serving.ServingEngine(model, params, num_slots=2,
                                        page_size=4, prefill_chunk=8,
                                        attn_impl="lax",
                                        prefix_sharing=share, registry=reg)
            outs = eng.generate_many(prompts, max_new_tokens=5,
                                     max_steps=300)
            eng.cache.check_invariants()
            assert eng.cache.pages_in_use == 0
            return outs, reg.counter("serving_prefill_tokens_total").value()

        outs_off, computed_off = run(False)
        outs_on, computed_on = run(True)
        for a, b in zip(outs_off, outs_on):
            np.testing.assert_array_equal(a, b)
        assert computed_on < computed_off, "sharing computed no less"
        for p, o in zip(prompts, outs_on):
            np.testing.assert_array_equal(
                o, _dense_reference(model, params, p, 5))

    def test_identical_prompts_tail_cow_parity(self):
        """Identical prompts force the shared-TAIL case: followers map
        the published partial page and must copy-on-write before
        appending. Tokens stay exactly equal to the dense reference and
        the published source page is never mutated."""
        model, params = _model(seed=4)
        rng = np.random.default_rng(21)
        prompt = rng.integers(1, 64, 10).astype(np.int32)  # 2 full + tail
        ref = _dense_reference(model, params, prompt, 6)
        eng = serving.ServingEngine(model, params, num_slots=1,
                                    page_size=4, prefill_chunk=8,
                                    attn_impl="lax")
        # slot count 1 => strictly sequential: req 0 publishes, later
        # requests revive the pages from the CACHED pool and CoW the tail
        out0 = eng.generate_many([prompt.copy()], max_new_tokens=6,
                                 max_steps=100)[0]
        np.testing.assert_array_equal(out0, ref)
        shared_pages = np.asarray(sorted(eng.cache._page_pub))
        snap = {l: (np.asarray(kp[shared_pages]), np.asarray(vp[shared_pages]))
                for l, (kp, vp) in enumerate(eng.cache.pages)}
        tail_pid = next(iter(eng.cache._tail_index.values()))
        tail_tokens = len(eng.cache._page_tokens[tail_pid])
        for _ in range(2):
            out = eng.generate_many([prompt.copy()], max_new_tokens=6,
                                    max_steps=100)[0]
            np.testing.assert_array_equal(out, ref)
        assert eng.cache.cow_copies_total == 2
        assert eng.cache.shared_tokens_total == 2 * (len(prompt) - 1)
        for l, (kp, vp) in enumerate(eng.cache.pages):
            k_now = np.asarray(kp[shared_pages])
            v_now = np.asarray(vp[shared_pages])
            for j, pid in enumerate(shared_pages):
                # published content region must be byte-identical;
                # (a tail page's offsets >= its published count belong
                # to the owner and are masked for every sharer)
                t = tail_tokens if pid == tail_pid else None
                np.testing.assert_array_equal(k_now[j][:t], snap[l][0][j][:t])
                np.testing.assert_array_equal(v_now[j][:t], snap[l][1][j][:t])
        eng.cache.check_invariants()

    def test_randomized_admit_evict_refcount_invariants(self):
        """Allocator-level property test: randomized reserve / publish /
        CoW-resolve / free interleavings over a small pool of recurring
        prompts — pages never leak, never double-free, refcounts always
        equal the live mapping count."""
        churn_a_prefix_pool(400)

    def test_cow_src_survives_fresh_allocation_under_pressure(self):
        """Reserving against a matched tail when fresh allocation must
        evict from the cached pool: the CoW src page is pinned first —
        it must never be recycled as the borrower's own fresh page (the
        pending copy would read garbage). If pinning it leaves too few
        evictable pages, the tail share degrades to full pages only
        instead of refusing (or corrupting) the request."""
        from paddle_tpu.serving.paged_cache import (PagedCacheConfig,
                                                    PagedKVCache)

        def seeded(num_pages):
            c = PagedKVCache(PagedCacheConfig(
                num_layers=1, num_heads=2, head_dim=4, num_slots=2,
                page_size=4, num_pages=num_pages, max_pages_per_slot=3))
            p = np.arange(1, 7, dtype=np.int32)   # 1 full page + 2 tail
            c.reserve(0, 6, prompt=p)
            c.publish_prefix(0, p, 6)
            c.free_slot(0)                        # F,T idle in cached pool
            return c, p

        # roomy pool: tail shared, src pinned BEFORE fresh allocation
        c, p = seeded(5)
        assert c.reserve(1, 10, prompt=p.copy()) == 5
        src, dst = c.pending_copy(1)
        assert src in c._page_pub, "CoW src evicted by fresh allocation"
        assert src not in c._owned[1] and src != dst
        c.copy_done(1)
        c.check_invariants()

        # tight pool (3 usable pages, request needs 3): pinning the tail
        # would leave only 1 evictable page for 2 fresh — degrade
        c, p = seeded(4)
        assert c.can_reserve(10, prompt=p)
        assert c.reserve(1, 10, prompt=p.copy()) == 4  # full page only
        assert c.pending_copy(1) is None
        c.check_invariants()

    def test_cached_pages_evicted_when_pool_runs_dry(self):
        """Published-but-idle pages are reusable capacity, not a leak:
        the allocator evicts them (unpublishing) before refusing."""
        from paddle_tpu.serving.paged_cache import (PagedCacheConfig,
                                                    PagedKVCache)
        c = PagedKVCache(PagedCacheConfig(
            num_layers=1, num_heads=2, head_dim=4, num_slots=2,
            page_size=4, num_pages=5, max_pages_per_slot=4))
        prompt = np.arange(1, 9, dtype=np.int32)       # 2 full pages
        c.reserve(0, 10, prompt=prompt)                # 3 pages
        c.publish_prefix(0, prompt, 8)
        c.free_slot(0)                                 # all 3 idle, 2 cached
        assert c.pages_in_use == 0 and len(c._cached) == 2
        c.reserve(1, 16)                               # needs all 4 pages
        c.check_invariants()
        assert c.pages_in_use == 4
        assert not c._full_index, "evicted pages still published"


class TestSLOScheduler:
    """ISSUE 6: priority lanes, deadlines, anti-starvation, shedding."""

    def _sched(self, **kw):
        from paddle_tpu.serving.scheduler import SLOScheduler
        t = {"now": 0.0}
        kw.setdefault("clock", lambda: t["now"])
        return SLOScheduler(2, **kw), t

    def test_priority_lanes_order(self):
        s, _ = self._sched()
        s.submit(np.ones(4, np.int32), 4, lane="batch")
        s.submit(np.ones(4, np.int32), 4, lane="interactive")
        s.submit(np.ones(4, np.int32), 4, lane="default")
        s.admit()
        lanes = [s.slots[i].request.lane for i in range(2)]
        assert lanes == ["interactive", "default"]
        assert s.queue[0].lane == "batch"

    def test_no_head_blocking_but_bounded_skips(self):
        """A too-big head is skipped (no head-of-line blocking) until
        its skip budget runs out — then it blocks the line until it
        fits, so it can never starve."""
        from paddle_tpu.serving.scheduler import Request

        def can_admit(req: Request):
            return req.max_new_tokens < 10

        s, _ = self._sched(can_admit=can_admit, starvation_skips=2)
        big = s.submit(np.ones(4, np.int32), 20)
        s.submit(np.ones(4, np.int32), 2)
        assert len(s.admit()) == 1          # small slips past the big head
        assert s.slots[0].request.max_new_tokens == 2
        s.submit(np.ones(4, np.int32), 3)
        assert len(s.admit()) == 1          # skip 2 for big
        s.evict_finished()
        s.slots = [None] * 2
        s.submit(np.ones(4, np.int32), 4)
        assert s.admit() == []              # big exhausted its skips: blocks
        assert s.queue[0].rid == big

    def test_deadline_boost_is_edf(self):
        """At-risk deadlines jump every lane, earliest first."""
        s, t = self._sched()
        s.note_ttft(1.0)                    # estimator: ~1s to serve
        s.submit(np.ones(4, np.int32), 4, lane="interactive")
        a = s.submit(np.ones(4, np.int32), 4, lane="batch",
                     ttft_deadline_s=0.5)   # at risk NOW (est 1s > 0.5s)
        b = s.submit(np.ones(4, np.int32), 4, lane="batch",
                     ttft_deadline_s=0.3)
        s.admit()
        assert {s.slots[0].request.rid, s.slots[1].request.rid} == {a, b}
        assert s.slots[0].request.rid == b  # earlier deadline first

    def test_load_shed_queue_full_structured(self):
        from paddle_tpu.serving.scheduler import LoadShedError
        s, _ = self._sched(max_queue_depth=1)
        s.submit(np.ones(4, np.int32), 4)
        with pytest.raises(LoadShedError) as ei:
            s.submit(np.ones(4, np.int32), 4)
        r = ei.value.reject
        assert r.reason == "queue_full" and r.queue_depth == 1
        assert r.retry_after_s > 0
        assert s.shed_total == 1

    def test_load_shed_infeasible_deadline(self):
        from paddle_tpu.serving.scheduler import LoadShedError
        s, _ = self._sched()
        s.note_ttft(2.0)
        for _ in range(4):                  # queue up: est *= waves
            s.submit(np.ones(4, np.int32), 4)
        with pytest.raises(LoadShedError) as ei:
            s.submit(np.ones(4, np.int32), 4, ttft_deadline_s=0.1)
        assert ei.value.reject.reason == "deadline_infeasible"
        assert ei.value.reject.est_ttft_s > 0.1

    def test_shed_expired_deadline_in_queue(self):
        s, t = self._sched()
        s.submit(np.ones(4, np.int32), 4)
        rid = s.submit(np.ones(4, np.int32), 4, ttft_deadline_s=0.5)
        t["now"] = 1.0                      # deadline long gone
        dead = s.shed_expired()
        assert [r.rid for r in dead] == [rid]
        assert len(s.queue) == 1            # the deadline-free one stays

    def test_engine_reports_structured_rejects(self):
        """Engine surface: a shed request raises LoadShedError with the
        Reject payload, and the rejected counter ticks."""
        model, params = _model()
        reg = obs.MetricsRegistry()
        eng = serving.ServingEngine(model, params, num_slots=1,
                                    page_size=4, attn_impl="lax",
                                    max_queue_depth=2, registry=reg)
        eng.submit(np.ones(4, np.int32), 4)
        eng.submit(np.ones(4, np.int32), 4)   # queue depth now 2 == cap
        with pytest.raises(serving.LoadShedError) as ei:
            eng.submit(np.ones(4, np.int32), 4)
        assert ei.value.reject.reason == "queue_full"
        assert ei.value.reject.queue_depth == 2
        assert reg.counter("serving_rejected_total").value(
            reason="queue_full") == 1
        # drain so the engine ends idle
        while not eng.scheduler.idle():
            eng.step()

    def test_engine_fifo_policy_still_available(self):
        model, params = _model()
        rng = np.random.default_rng(23)
        eng = serving.ServingEngine(model, params, num_slots=2,
                                    page_size=4, attn_impl="lax",
                                    scheduler_policy="fifo")
        prompts = _prompts(rng, [5, 9, 3])
        outs = eng.generate_many(prompts, max_new_tokens=4, max_steps=200)
        for p, o in zip(prompts, outs):
            np.testing.assert_array_equal(
                o, _dense_reference(model, params, p, 4))
