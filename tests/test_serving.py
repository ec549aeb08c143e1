"""Paged KV-cache serving engine: paged-vs-dense equivalence,
allocator invariants, ragged decode/prefill-attention kernel parity,
scheduler properties under randomized arrivals, prefix-sharing
refcount/CoW invariants, SLO scheduling, and steady-state
recompile-freedom (ISSUE 4 + ISSUE 6 acceptance surface)."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from paddle_tpu import observability as obs
from paddle_tpu import serving
from paddle_tpu.models.gpt import GPT, GPTConfig
from paddle_tpu.serving.paged_cache import (PagedCacheConfig, PagedKVCache,
                                            PageOverflowError)


def _model(seed=0, **kw):
    cfg = GPTConfig.tiny(vocab_size=64, hidden_size=16, num_layers=2,
                         num_heads=2, ffn_size=32, max_position=64,
                         dropout=0.0, attn_impl="xla", **kw)
    model = GPT(cfg)
    return model, model.init(jax.random.PRNGKey(seed))


def _prompts(rng, lens):
    return [rng.integers(1, 64, n).astype(np.int32) for n in lens]


def _dense_reference(model, params, prompt, max_new):
    """Single-request greedy decode through the dense cached path."""
    out = model.generate(params, jnp.asarray(prompt)[None],
                         max_new_tokens=max_new, use_cache=True)
    return np.asarray(out)[0, len(prompt):]


class TestPagedKVCache:
    def _cache(self, **kw):
        kw.setdefault("num_layers", 1)
        kw.setdefault("num_heads", 2)
        kw.setdefault("head_dim", 4)
        kw.setdefault("num_slots", 3)
        kw.setdefault("page_size", 4)
        kw.setdefault("num_pages", 10)
        kw.setdefault("max_pages_per_slot", 4)
        return PagedKVCache(PagedCacheConfig(**kw))

    def test_reserve_free_roundtrip(self):
        c = self._cache()
        c.reserve(0, 9)     # 3 pages
        c.reserve(1, 4)     # 1 page
        assert c.pages_in_use == 4
        assert set(c.block_tables[0, :3]) & {0} == set()
        c.check_invariants()
        c.free_slot(0)
        assert c.pages_in_use == 1
        assert (c.block_tables[0] == 0).all()
        c.check_invariants()

    def test_pages_are_reused_after_free(self):
        c = self._cache()
        c.reserve(0, 16)
        first = set(c.slot_pages(0))
        c.free_slot(0)
        c.reserve(1, 16)
        assert set(c.slot_pages(1)) == first
        c.check_invariants()

    def test_overflow_refused_all_or_nothing(self):
        c = self._cache()
        c.reserve(0, 16)
        c.reserve(1, 16)
        free_before = c.free_pages
        assert not c.can_reserve(8)
        with pytest.raises(PageOverflowError):
            c.reserve(2, 8)
        assert c.free_pages == free_before  # nothing leaked
        with pytest.raises(PageOverflowError):
            c.reserve(2, 17)                # > max_pages_per_slot
        c.check_invariants()

    def test_null_page_never_allocated(self):
        c = self._cache()
        c.reserve(0, 16)
        c.reserve(1, 16)
        c.reserve(2, 4)
        assert 0 not in [p for s in range(3) for p in c.slot_pages(s)]

    def test_utilization_tracks_live_tokens(self):
        c = self._cache()
        assert c.utilization() == 0.0
        c.reserve(0, 8)
        c.lengths[0] = 8
        assert c.utilization() == pytest.approx(8 / (9 * 4))


class TestRaggedPagedDecodeAttention:
    def _setup(self, seed=0, s=4, h=2, dh=8, ps=4, mp=4, p=16):
        rng = np.random.default_rng(seed)
        q = jnp.asarray(rng.standard_normal((s, h, dh)), jnp.float32)
        # the pool's stored layout: a token's heads folded head-major
        kp = jnp.asarray(rng.standard_normal((p, ps, h, dh)),
                         jnp.float32).reshape(p, ps, h * dh)
        vp = jnp.asarray(rng.standard_normal((p, ps, h, dh)),
                         jnp.float32).reshape(p, ps, h * dh)
        bt = jnp.asarray(rng.integers(1, p, (s, mp)), jnp.int32)
        lens = jnp.asarray(rng.integers(0, mp * ps + 1, (s,)), jnp.int32)
        return q, kp, vp, bt, lens

    def test_lax_matches_dense_gather(self):
        q, kp, vp, bt, lens = self._setup()
        out = serving.ragged_paged_decode_attention(q, kp, vp, bt, lens,
                                                    impl="lax")
        dh = q.shape[-1]
        for s in range(q.shape[0]):
            n = int(lens[s])
            if n == 0:
                np.testing.assert_array_equal(np.asarray(out[s]), 0.0)
                continue
            k = kp[bt[s]].reshape(-1, *q.shape[1:])[:n]
            v = vp[bt[s]].reshape(-1, *q.shape[1:])[:n]
            sc = jnp.einsum("hd,thd->ht", q[s], k) / np.sqrt(dh)
            ref = jnp.einsum("ht,thd->hd", jax.nn.softmax(sc, -1), v)
            np.testing.assert_allclose(np.asarray(out[s]), np.asarray(ref),
                                       atol=1e-5, rtol=1e-5)

    def test_pallas_interpret_matches_lax(self):
        """The REAL kernel (interpret mode) against the lax fallback —
        including a length-0 (inactive) slot."""
        q, kp, vp, bt, _ = self._setup(seed=1)
        lens = jnp.asarray([0, 1, 7, 16], jnp.int32)
        out_l = serving.ragged_paged_decode_attention(q, kp, vp, bt, lens,
                                                      impl="lax")
        out_p = serving.ragged_paged_decode_attention(
            q, kp, vp, bt, lens, impl="pallas_interpret")
        np.testing.assert_allclose(np.asarray(out_p), np.asarray(out_l),
                                   atol=1e-5, rtol=1e-5)

    def test_stale_page_contents_ignored(self):
        """Poison every page a slot does NOT own plus its own dead tail:
        the output must only depend on the live prefix."""
        q, kp, vp, bt, _ = self._setup(seed=2, s=1)
        lens = jnp.asarray([6], jnp.int32)
        ref = serving.ragged_paged_decode_attention(q, kp, vp, bt, lens,
                                                    impl="lax")
        owned = set(np.asarray(bt[0, :2]).tolist())  # pages of tokens 0..7
        poison_k = np.asarray(kp).copy()
        poison_v = np.asarray(vp).copy()
        for pg in range(kp.shape[0]):
            if pg not in owned:
                poison_k[pg] = 1e6
                poison_v[pg] = 1e6
        # dead tail inside the second owned page (tokens 6..7)
        pg2 = int(bt[0, 1])
        poison_k[pg2, 2:] = 1e6
        poison_v[pg2, 2:] = 1e6
        out = serving.ragged_paged_decode_attention(
            q, jnp.asarray(poison_k), jnp.asarray(poison_v), bt, lens,
            impl="lax")
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5, rtol=1e-5)


class TestRaggedPagedPrefillAttention:
    """The batched chunked-prefill kernel (ISSUE 6): one call, every
    slot's next chunk, causal over pages."""

    def _setup(self, seed=0, s=3, c=4, h=2, dh=8, ps=4, mp=4, p=12):
        rng = np.random.default_rng(seed)
        q = jnp.asarray(rng.standard_normal((s, c, h, dh)), jnp.float32)
        kp = jnp.asarray(rng.standard_normal((p, ps, h, dh)),
                         jnp.float32).reshape(p, ps, h * dh)
        vp = jnp.asarray(rng.standard_normal((p, ps, h, dh)),
                         jnp.float32).reshape(p, ps, h * dh)
        bt = jnp.asarray(rng.integers(1, p, (s, mp)), jnp.int32)
        return q, kp, vp, bt

    def test_lax_matches_per_row_dense(self):
        q, kp, vp, bt = self._setup()
        starts = jnp.asarray([0, 5, 2], jnp.int32)
        nv = jnp.asarray([4, 3, 4], jnp.int32)
        out = serving.ragged_paged_prefill_attention(
            q, kp, vp, bt, starts, nv, impl="lax")
        dh = q.shape[-1]
        for s in range(q.shape[0]):
            k = kp[bt[s]].reshape(-1, *q.shape[2:])
            v = vp[bt[s]].reshape(-1, *q.shape[2:])
            for c in range(int(nv[s])):
                n = int(starts[s]) + c + 1        # causal horizon
                sc = jnp.einsum("hd,thd->ht", q[s, c], k[:n]) / np.sqrt(dh)
                ref = jnp.einsum("ht,thd->hd",
                                 jax.nn.softmax(sc, -1), v[:n])
                np.testing.assert_allclose(
                    np.asarray(out[s, c]), np.asarray(ref),
                    atol=1e-5, rtol=1e-5)

    def test_pad_lanes_and_inactive_slots_emit_zeros(self):
        q, kp, vp, bt = self._setup(seed=1)
        starts = jnp.asarray([0, 3, 0], jnp.int32)
        nv = jnp.asarray([2, 4, 0], jnp.int32)    # slot 2 inactive
        for impl in ("lax", "pallas_interpret"):
            out = serving.ragged_paged_prefill_attention(
                q, kp, vp, bt, starts, nv, impl=impl)
            np.testing.assert_array_equal(np.asarray(out[0, 2:]), 0.0)
            np.testing.assert_array_equal(np.asarray(out[2]), 0.0)

    def test_pallas_interpret_matches_lax(self):
        """The REAL kernel (interpret mode) against the lax fallback —
        mixed starts/valid counts including an idle lane."""
        q, kp, vp, bt = self._setup(seed=2)
        starts = jnp.asarray([7, 0, 2], jnp.int32)
        nv = jnp.asarray([4, 1, 0], jnp.int32)
        out_l = serving.ragged_paged_prefill_attention(
            q, kp, vp, bt, starts, nv, impl="lax")
        out_p = serving.ragged_paged_prefill_attention(
            q, kp, vp, bt, starts, nv, impl="pallas_interpret")
        np.testing.assert_allclose(np.asarray(out_p), np.asarray(out_l),
                                   atol=1e-5, rtol=1e-5)


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


class TestFoldedPoolWireFormat:
    """The pool stores a token's heads folded into the last axis, (P,
    ps, H*Dh); a page on the wire (migration shards, spilled payloads,
    prefix bundles) stays (2, L, ps, H, Dh). Both are the same row-major
    bytes, so payloads and their sha256 digests are what the 4-D pool
    gave. The digests below were recorded from the engine of the parent
    commit (4-D pool) for the same payload."""

    RECORDED = {
        "float32": "bffdbf5b5bd3b915f43ddbed1db74aeb"
                   "5c2815074d0b9d13db97bb5efd5211a2",
        "int8": "73df36a3e4484bce7fae70c5b312922c"
                "8fea986496cd442a07f457bf47efa163",
    }

    @pytest.mark.parametrize("dtype", ["float32", "int8"])
    def test_page_payload_and_digest_are_the_4d_pools(self, dtype):
        model, params = _model()
        eng = serving.ServingEngine(
            model, params, num_slots=2, page_size=4, attn_impl="lax",
            cache_dtype=jnp.int8 if dtype == "int8" else None)
        c = eng.cache.config
        shape = (2, c.num_layers, c.page_size, c.num_heads, c.head_dim)
        ramp = (np.arange(int(np.prod(shape))) * 7) % 251 - 125
        pid = jnp.asarray(3, jnp.int32)
        if eng.quantized:
            kv = ramp.astype(np.int8).reshape(shape)
            sc = ((np.arange(2 * c.num_layers * c.page_size) % 13 + 1)
                  / 16).astype(np.float32).reshape(shape[:3])
            eng.cache.pages = eng.write_page_step(
                eng.cache.pages, pid, jnp.asarray(kv), jnp.asarray(sc))
            page = eng.read_page_step(eng.cache.pages, pid)
            shard = (np.asarray(page[0]), np.asarray(page[1]))
            assert shard[1].tobytes() == sc.tobytes()
            kv_out = shard[0]
        else:
            kv = (ramp / 4).astype(np.float32).reshape(shape)
            eng.cache.pages = eng.write_page_step(
                eng.cache.pages, pid, jnp.asarray(kv))
            shard = kv_out = np.asarray(
                eng.read_page_step(eng.cache.pages, pid))
        # stored: each token row holds its heads one after the other
        for layer, ent in enumerate(eng.cache.pages):
            assert ent[0].shape == (c.num_pages, c.page_size,
                                    c.num_heads * c.head_dim)
            for side in (0, 1):
                np.testing.assert_array_equal(
                    np.asarray(ent[side][3]),
                    kv[side, layer].reshape(c.page_size, -1))
        assert kv_out.shape == shape and kv_out.tobytes() == kv.tobytes()
        assert eng._shard_digest(shard) == self.RECORDED[dtype]

    @pytest.mark.parametrize("impl", ["lax", "pallas_interpret"])
    def test_served_pages_read_back_as_the_unfolded_pool(self, impl):
        """Pages the engine itself wrote (prefill chunks and decode
        tokens): what ``read_page_step`` hands the wire is the stored
        page with its last axis unfolded, and a snapshot's manifest
        digests are those of exactly these arrays."""
        model, params = _model(seed=3)
        eng = serving.ServingEngine(model, params, num_slots=2,
                                    page_size=4, prefill_chunk=8,
                                    attn_impl=impl)
        rng = np.random.default_rng(11)
        for p in _prompts(rng, [10, 7]):
            eng.submit(p, 24)
        eng.step()
        eng.step()
        c = eng.cache.config
        slot = next(s for s in range(2) if eng.cache.lengths[s] > 0)
        n_live = c.pages_for(int(eng.cache.lengths[slot]))
        assert n_live >= 3
        snap = eng.snapshot_slot(slot)
        for k, pid in enumerate(eng.cache.block_tables[slot, :n_live]):
            page = np.asarray(eng.read_page_step(
                eng.cache.pages, jnp.asarray(int(pid), jnp.int32)))
            for layer, (kp, vp) in enumerate(eng.cache.pages):
                for side, pool in enumerate((kp, vp)):
                    np.testing.assert_array_equal(
                        page[side, layer],
                        np.asarray(pool[int(pid)]).reshape(
                            c.page_size, c.num_heads, c.head_dim))
            assert snap["manifest"][k]["sha256"] == eng._shard_digest(page)
            assert page.any()


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
        from paddle_tpu.serving.paged_cache import (PagedCacheConfig,
                                                    PagedKVCache,
                                                    PageOverflowError)
        rng = np.random.default_rng(22)
        c = PagedKVCache(PagedCacheConfig(
            num_layers=1, num_heads=2, head_dim=4, num_slots=4,
            page_size=4, num_pages=14, max_pages_per_slot=4))
        # small prompt pool => heavy prefix overlap
        pool = [rng.integers(1, 9, n).astype(np.int32)
                for n in (6, 9, 10, 13, 10)]
        pool.append(pool[2].copy())          # exact duplicate
        live = {}
        for _step in range(400):
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
                    c.copy_done(slot)        # engine would device-copy
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
