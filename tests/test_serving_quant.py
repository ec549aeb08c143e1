"""Int8 paged KV cache (ISSUE 13): dequant-attend kernel parity,
quantized-engine greedy parity vs the bf16/fp32 cache, prefix-sharing /
CoW scale consistency, fleet migration of int8 slots (hash-verified
shards include scales), zero steady-state recompiles, and the static
bytes-reduction gate (cost-diff demonstrably fails at bf16-level
bytes)."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from paddle_tpu import kernels
from paddle_tpu import observability as obs
from paddle_tpu import serving
from paddle_tpu.serving.paged_cache import (PagedCacheConfig, PagedKVCache,
                                            quantize_kv)

from serving_taps import dense_reference as _dense_reference
from serving_taps import prompts as _prompts, tiny_gpt as _model
from serving_taps import churn_a_prefix_pool, shared_engines


@pytest.fixture(scope="module")
def model_params():
    return _model(seed=5)


@pytest.fixture(scope="module")
def warmed_int8(model_params):
    """``get(peer) ->`` one of the int8 engines of one geometry, warmed
    ONCE for the module and idle (``tests/serving_taps.py``): a migration's
    source (0) and its peer (1), and the one that must not recompile
    (``"unserved"``: no other case takes it)."""
    model, params = model_params

    def build(peer):
        eng = serving.ServingEngine(
            model, params, num_slots=2, page_size=4, max_tokens_per_slot=48,
            attn_impl="lax", cache_dtype=jnp.int8, decode_block=2,
            registry=obs.MetricsRegistry())
        eng.warmup()
        return eng
    return shared_engines(build)


class TestQuantizeKV:
    def test_roundtrip_error_bounded(self):
        """Per-token abs-max int8: dequant error <= scale/2 per element
        (half an LSB), i.e. <= amax/254 — the quality budget the greedy
        parity rides on."""
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.standard_normal((5, 3, 8)), jnp.float32)
        q, scale = quantize_kv(x, (1, 2))
        assert q.dtype == jnp.int8 and scale.shape == (5,)
        deq = np.asarray(q, np.float32) * np.asarray(scale)[:, None, None]
        amax = np.max(np.abs(np.asarray(x)), axis=(1, 2))
        err = np.max(np.abs(deq - np.asarray(x)), axis=(1, 2))
        assert (err <= amax / 254.0 + 1e-7).all()

    def test_zero_row_harmless(self):
        q, scale = quantize_kv(jnp.zeros((2, 4)), (1,))
        assert (np.asarray(q) == 0).all()
        assert (np.asarray(scale) > 0).all()    # floored, no div-by-zero

    def test_quantized_pool_layout(self):
        c = PagedKVCache(PagedCacheConfig(
            num_layers=2, num_heads=2, head_dim=4, num_slots=2,
            page_size=4, num_pages=6, max_pages_per_slot=3,
            dtype=jnp.int8))
        assert all(kind.quantized for kind in c.config.kinds)
        kp, vp, ks, vs = c.pages[0]
        assert kp.dtype == jnp.int8 and vp.dtype == jnp.int8
        # heads folded into the last axis, like the fp pool; scale rows
        # stay one per token
        assert kp.shape == vp.shape == (6, 4, 2 * 4)
        assert ks.shape == (6, 4) and ks.dtype == jnp.float32
        # allocator state is dtype-agnostic: invariants hold untouched
        c.reserve(0, 9)
        c.check_invariants()
        c.free_slot(0)
        c.check_invariants()


class TestDequantAttendKernels:
    """The registered int8 kernels through the shared harness."""

    @pytest.mark.parametrize("name", ["ragged_paged_decode_int8",
                                      "ragged_paged_prefill_int8"])
    def test_parity_battery(self, name):
        for seed in (0, 1, 2):
            kernels.parity_check(name, seed)

    @pytest.mark.parametrize("name", ["ragged_paged_decode_int8",
                                      "ragged_paged_prefill_int8"])
    def test_pages_per_block_bit_equal(self, name):
        """The tunable streams N pages per grid step with an identical
        per-page accumulation order, so every setting is BIT-equal —
        tuning can never flip a greedy argmax (same contract as the fp
        kernels)."""
        spec = kernels.get(name)
        args, kwargs = spec.sample_inputs(1)
        ref = np.asarray(kernels.dispatch(
            name, *args, impl="pallas_interpret",
            block_sizes={"pages_per_block": 1}, **kwargs))
        for pb in (2, 4):
            out = np.asarray(kernels.dispatch(
                name, *args, impl="pallas_interpret",
                block_sizes={"pages_per_block": pb}, **kwargs))
            np.testing.assert_array_equal(out, ref)

    def test_stale_page_contents_ignored(self):
        """Poisoning pages (and scales) beyond the live extent must not
        change the int8 decode output."""
        spec = kernels.get("ragged_paged_decode_int8")
        (q, kp, vp, ks, vs, bt, _lens), _ = spec.sample_inputs(0)
        lens = jnp.asarray([3] + [0] * (q.shape[0] - 1), jnp.int32)
        ref = np.asarray(kernels.dispatch(
            "ragged_paged_decode_int8", q, kp, vp, ks, vs, bt, lens,
            impl="lax"))
        owned = int(bt[0, 0])
        pk, pv = np.asarray(kp).copy(), np.asarray(vp).copy()
        pks, pvs = np.asarray(ks).copy(), np.asarray(vs).copy()
        for pg in range(pk.shape[0]):
            if pg != owned:
                pk[pg] = 127
                pv[pg] = 127
                pks[pg] = 1e6
                pvs[pg] = 1e6
        pk[owned, 3:] = 127                   # dead tail of the live page
        pks[owned, 3:] = 1e6
        out = np.asarray(kernels.dispatch(
            "ragged_paged_decode_int8", q, jnp.asarray(pk),
            jnp.asarray(pv), jnp.asarray(pks), jnp.asarray(pvs), bt, lens,
            impl="lax"))
        np.testing.assert_allclose(out, ref, atol=1e-6, rtol=1e-6)


class TestInt8EngineParity:
    """ISSUE 13 quality gate: greedy tokens through the int8 cache
    match the bf16/fp32 cache on the serving parity battery. The pinned
    tolerance is EXACT token equality on this battery — per-token-row
    scales keep the dequant error around 0.4% of each row's abs-max,
    far inside the greedy argmax margins of these models."""

    def test_int8_matches_fp32_and_dense(self):
        model, params = _model()
        rng = np.random.default_rng(3)
        prompts = _prompts(rng, [5, 9, 3, 12, 7])

        def run(dtype):
            eng = serving.ServingEngine(
                model, params, num_slots=3, page_size=4, prefill_chunk=8,
                attn_impl="lax", cache_dtype=dtype)
            outs = eng.generate_many(prompts, max_new_tokens=6,
                                     max_steps=200)
            eng.cache.check_invariants()
            assert eng.cache.pages_in_use == 0
            return outs

        outs_fp = run(None)
        outs_bf = run(jnp.bfloat16)
        outs_q = run(jnp.int8)
        for p, fp, bf, q in zip(prompts, outs_fp, outs_bf, outs_q):
            ref = _dense_reference(model, params, p, 6)
            np.testing.assert_array_equal(fp, ref)
            np.testing.assert_array_equal(q, bf)
            np.testing.assert_array_equal(q, ref)

    def test_int8_through_interpret_kernels(self):
        """End-to-end through the REAL dequant-attend kernel bodies."""
        model, params = _model(seed=1)
        rng = np.random.default_rng(4)
        prompts = _prompts(rng, [4, 10])
        eng = serving.ServingEngine(model, params, num_slots=2,
                                    page_size=4, prefill_chunk=8,
                                    attn_impl="pallas_interpret",
                                    cache_dtype=jnp.int8)
        outs = eng.generate_many(prompts, max_new_tokens=5, max_steps=100)
        for p, o in zip(prompts, outs):
            np.testing.assert_array_equal(
                o, _dense_reference(model, params, p, 5))

    def test_zero_steady_state_recompiles(self, warmed_int8):
        rng = np.random.default_rng(8)
        eng = warmed_int8("unserved")
        # new from warm-up: no earlier case compiled what warm-up missed
        assert eng.health()["steps"] == 0
        det = obs.RecompileDetector("int8_steady", warmup=0,
                                    registry=eng._reg)
        eng.generate_many(_prompts(rng, [9, 4, 6]), max_new_tokens=4,
                          max_steps=100)
        det.check()
        assert det.recompiles == 0, "int8 steady state recompiled"

    def test_same_pool_hosts_twice_the_tokens(self):
        """The HBM claim: per-token page bytes roughly halve (int8 + a
        small scale overhead vs bf16)."""
        c8 = PagedKVCache(PagedCacheConfig(
            num_layers=1, num_heads=4, head_dim=32, num_slots=2,
            page_size=16, num_pages=8, max_pages_per_slot=4,
            dtype=jnp.int8))
        cb = PagedKVCache(PagedCacheConfig(
            num_layers=1, num_heads=4, head_dim=32, num_slots=2,
            page_size=16, num_pages=8, max_pages_per_slot=4,
            dtype=jnp.bfloat16))
        bytes8 = sum(a.size * a.dtype.itemsize for ent in c8.pages
                     for a in ent)
        bytesb = sum(a.size * a.dtype.itemsize for ent in cb.pages
                     for a in ent)
        assert bytes8 < 0.6 * bytesb


class TestInt8PrefixSharing:
    """Scales never diverge from their pages: sharing, CoW, and the
    cached pool all move (page, scale-rows) as one unit."""

    def test_identical_prompts_tail_cow_parity_int8(self):
        """The tail-CoW battery on an int8 engine: tokens stay exactly
        equal to the dense reference, the published source page AND its
        scale rows are never mutated by borrowers, and the CoW copy
        duplicates the scales with the page."""
        model, params = _model(seed=4)
        rng = np.random.default_rng(21)
        prompt = rng.integers(1, 64, 10).astype(np.int32)
        ref = _dense_reference(model, params, prompt, 6)
        eng = serving.ServingEngine(model, params, num_slots=1,
                                    page_size=4, prefill_chunk=8,
                                    attn_impl="lax", cache_dtype=jnp.int8)
        out0 = eng.generate_many([prompt.copy()], max_new_tokens=6,
                                 max_steps=100)[0]
        np.testing.assert_array_equal(out0, ref)
        shared_pages = np.asarray(sorted(eng.cache._page_pub))
        snap = {}
        for layer, (kp, vp, ks, vs) in enumerate(eng.cache.pages):
            snap[layer] = tuple(np.asarray(a[shared_pages])
                                for a in (kp, vp, ks, vs))
        tail_pid = next(iter(eng.cache._tail_index.values()))
        tail_tokens = len(eng.cache._page_tokens[tail_pid])
        for _ in range(2):
            out = eng.generate_many([prompt.copy()], max_new_tokens=6,
                                    max_steps=100)[0]
            np.testing.assert_array_equal(out, ref)
        assert eng.cache.cow_copies_total == 2
        for layer, (kp, vp, ks, vs) in enumerate(eng.cache.pages):
            now = tuple(np.asarray(a[shared_pages])
                        for a in (kp, vp, ks, vs))
            for j, pid in enumerate(shared_pages):
                t = tail_tokens if pid == tail_pid else None
                for a_now, a_snap in zip(now, snap[layer]):
                    np.testing.assert_array_equal(a_now[j][:t],
                                                  a_snap[j][:t])
        eng.cache.check_invariants()

    def test_randomized_refcount_invariants_int8(self):
        """The allocator property test on a quantized pool — refcounts,
        publication, and the free/cached/live partition are storage-
        dtype independent and must hold identically."""
        churn_a_prefix_pool(300, dtype=jnp.int8)


class TestInt8Migration:
    """Fleet drain of an int8 slot: shards carry scales, hashes cover
    both, restore is byte-identical."""

    def _step_to_mid_decode(self, eng, cap, max_steps=50):
        for _ in range(max_steps):
            eng.step()
            mid = [i for i in eng.scheduler.decode_slots()
                   if 0 < len(eng.scheduler.slots[i].generated) < cap]
            if mid:
                return mid[0]
        raise AssertionError("no mid-decode window reached")

    def test_mid_decode_migration_byte_identical(self, model_params,
                                                 warmed_int8):
        model, params = model_params
        prompt = np.arange(1, 8, dtype=np.int32)
        ref = _dense_reference(model, params, prompt, 16)

        src = warmed_int8(0)
        src.submit(prompt, 16)
        slot = self._step_to_mid_decode(src, 16)
        snap = src.snapshot_slot(slot)
        # quantized shards are (kv int8, scales f32) pairs, hashed as one
        kv, sc = snap["shards"][0]
        assert kv.dtype == np.int8 and sc.dtype == np.float32
        assert snap["geometry"]["dtype"] == "int8"

        dst = warmed_int8(1)
        rid = dst.restore_slot(snap)
        src.release_slot(slot)
        out = {}
        for _ in range(200):
            out.update(dst.step())
            if dst.scheduler.idle():
                break
        np.testing.assert_array_equal(out[rid], ref)
        # the restored pages + scales must be byte-identical: re-snapshot
        dst_slot_gone = dst.scheduler.active_slots() == []
        assert dst_slot_gone
        src.cache.check_invariants()
        dst.cache.check_invariants()

    def test_corrupt_scale_shard_refused(self, warmed_int8):
        """A bit-flip in the SCALES (not the int8 KV) must be refused,
        before anything lands: the digest covers both halves of the
        shard."""
        src = warmed_int8(0)
        src.submit(np.arange(1, 8, dtype=np.int32), 24)
        snap = src.snapshot_slot(self._step_to_mid_decode(src, 24))
        kv, sc = snap["shards"][0]
        sc = sc.copy()
        sc.reshape(-1)[0] += 0.25
        snap["shards"][0] = (kv, sc)
        dst = warmed_int8(1)
        with pytest.raises(serving.SlotMigrationError,
                           match="sha256 mismatch"):
            dst.restore_slot(snap)
        assert dst.scheduler.active_slots() == []
        dst.cache.check_invariants()

    def test_cross_dtype_restore_refused(self, model_params, warmed_int8):
        """An int8 snapshot cannot restore into a bf16 engine (geometry
        pins the dtype)."""
        src = warmed_int8(0)
        src.submit(np.arange(1, 8, dtype=np.int32), 24)
        snap = src.snapshot_slot(self._step_to_mid_decode(src, 24))
        dst = serving.ServingEngine(
            *model_params, num_slots=2, page_size=4, max_tokens_per_slot=48,
            attn_impl="lax", cache_dtype=jnp.bfloat16, decode_block=2)
        with pytest.raises(serving.SlotMigrationError,
                           match="geometry mismatch"):
            dst.restore_slot(snap)


class TestInt8StaticBytes:
    """The PR 7 cost model proves the bytes-per-decode-step reduction
    statically, and the committed budget gate demonstrably FAILS if the
    int8 path regresses to bf16-level bytes."""

    def _lower(self, dtype):
        from paddle_tpu import analysis
        model, params = _model()
        eng = serving.ServingEngine(
            model, params, num_slots=4, page_size=8,
            max_tokens_per_slot=64, num_pages=513, attn_impl="lax",
            cache_dtype=dtype)
        c = eng.cache.config
        args = (analysis.abstractify(eng.params),
                analysis.abstractify(eng.cache.pages),
                jax.ShapeDtypeStruct((c.num_slots, c.max_pages_per_slot),
                                     jnp.int32),
                jax.ShapeDtypeStruct((c.num_slots,), jnp.int32),
                jax.ShapeDtypeStruct((c.num_slots,), jnp.int32),
                jax.ShapeDtypeStruct((c.num_slots,), jnp.int32))
        return analysis.estimate_cost(eng.decode_step, *args,
                                      name=f"decode_{dtype}")

    def test_cost_diff_fails_at_bf16_bytes(self, graph_lint_cli):
        cost_diff = graph_lint_cli.cost_diff
        cost8 = self._lower(jnp.int8)
        costb = self._lower(jnp.bfloat16)
        # the real claim: on a KV-dominated pool the int8 step moves
        # meaningfully fewer static bytes than the bf16 step
        assert costb.traffic_bytes > 1.1 * cost8.traffic_bytes
        budgets = {"tolerance": 0.10,
                   "surfaces": {"serving_decode_int8": cost8.summary()}}
        ok = cost_diff({"serving_decode_int8": cost8.summary()}, budgets,
                       out=lambda *_a: None)
        assert ok == 0
        regressed = cost_diff({"serving_decode_int8": costb.summary()},
                              budgets, out=lambda *_a: None)
        assert regressed == 1, ("bf16-level bytes did not trip the "
                                "int8 budget gate")
