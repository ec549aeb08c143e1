"""Exact speculative decoding (ISSUE 13): a draft model proposes
``spec_k`` tokens per slot, the target verifies them in ONE fixed-shape
batched-prefill-shaped step, accept-prefix/rollback rewinds the write
cursors — and greedy outputs are BIT-EXACT vs non-speculative greedy
(the acceptance gate), under perfect drafts (long accepts), adversarial
drafts (constant rollback), int8 caches, and with zero steady-state
recompiles; the bucket-coverage lint extends to the verify buckets."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from paddle_tpu import observability as obs
from paddle_tpu import serving
from paddle_tpu.analysis import hlo_lint
from paddle_tpu.models.gpt import GPT, GPTConfig

from serving_taps import dense_reference as _dense_reference
from serving_taps import prompts as _prompts, tiny_gpt as _model
from serving_taps import moved, shared_engines


def _draft(seed=9):
    """A genuinely smaller draft sharing only the vocabulary — its
    random weights agree with the target almost never, so every round
    exercises the reject/rollback path."""
    cfg = GPTConfig.tiny(vocab_size=64, hidden_size=8, num_layers=1,
                         num_heads=2, ffn_size=16, max_position=64,
                         dropout=0.0, attn_impl="xla")
    model = GPT(cfg)
    return model, model.init(jax.random.PRNGKey(seed))


@pytest.fixture(scope="module")
def engines():
    """``get(self_draft) ->`` the battery's engine of ``_model()`` without
    a draft, or with itself as the draft (``spec_k`` 4), built once for
    the module and idle (``tests/serving_taps.py``)."""
    model, params = _model()

    def build(self_draft):
        kw = dict(draft_model=model, draft_params=params,
                  spec_k=4) if self_draft else {}
        return serving.ServingEngine(
            model, params, num_slots=3, page_size=4, prefill_chunk=8,
            attn_impl="lax", registry=obs.MetricsRegistry(), **kw)
    return shared_engines(build)


class TestSpeculativeParity:
    """The acceptance gate: speculative greedy == non-speculative
    greedy, bit for bit, on the serving parity battery."""

    def _run(self, model, params, prompts, max_new, eos_id=None, eng=None,
             **kw):
        eng = eng or serving.ServingEngine(model, params, num_slots=3,
                                           page_size=4, prefill_chunk=8,
                                           attn_impl="lax", **kw)
        outs = eng.generate_many(prompts, max_new_tokens=max_new,
                                 eos_id=eos_id, max_steps=500)
        eng.cache.check_invariants()
        assert eng.cache.pages_in_use == 0
        if eng.speculative:
            eng.draft_cache.check_invariants()
            assert eng.draft_cache.pages_in_use == 0
            # the draft's pool is folded like the target's, at its widths
            dc = eng.draft_cache.config
            assert eng.draft_cache.pages[0][0].shape == (
                dc.num_pages, dc.page_size, dc.num_heads * dc.head_dim)
        return outs

    def test_self_draft_bit_exact_long_accepts(self, engines):
        """draft == target: every proposal verifies, rounds accept the
        whole chunk — and outputs still exactly match non-speculative
        greedy AND the dense reference."""
        model, params = _model()
        rng = np.random.default_rng(3)
        prompts = _prompts(rng, [5, 9, 3, 12, 7])
        reg = engines(True)._reg
        before = reg.snapshot()
        base = self._run(model, params, prompts, 7, eng=engines(False))
        spec = self._run(model, params, prompts, 7, eng=engines(True))
        for p, b, s in zip(prompts, base, spec):
            np.testing.assert_array_equal(s, b)
            np.testing.assert_array_equal(
                s, _dense_reference(model, params, p, 7))
        snap = moved(reg, before)
        prop = snap["serving_spec_proposed_total"]
        acc = snap["serving_spec_accepted_total"]
        assert prop > 0 and acc == prop     # perfect draft: all accepted

    def test_weak_draft_bit_exact_constant_rollback(self, engines):
        """A random small draft never matches: every round rolls back
        to the single target token — exactness must survive the rewind
        (stale K/V behind the cursor, overwritten next round)."""
        model, params = _model()
        dmodel, dparams = _draft()
        rng = np.random.default_rng(5)
        prompts = _prompts(rng, [6, 11, 4])
        reg = obs.MetricsRegistry()
        base = self._run(model, params, prompts, 8, eng=engines(False))
        spec = self._run(model, params, prompts, 8, draft_model=dmodel,
                         draft_params=dparams, spec_k=4, registry=reg)
        for b, s in zip(base, spec):
            np.testing.assert_array_equal(s, b)
        prop = reg.counter("serving_spec_proposed_total").value()
        acc = reg.counter("serving_spec_accepted_total").value()
        assert prop > 0 and acc < prop      # rollback really happened

    def test_early_eos_truncates_accepted_run(self, engines):
        """EOS inside an accepted chunk stops the request exactly where
        sequential decoding would."""
        model, params = _model()
        rng = np.random.default_rng(6)
        prompt = _prompts(rng, [6])[0]
        full = _dense_reference(model, params, prompt, 12)
        eos = int(full[3])
        stop = int(np.argmax(full == eos)) + 1
        out = self._run(model, params, [prompt], 12, eos_id=eos,
                        eng=engines(True))[0]
        np.testing.assert_array_equal(out, full[:stop])

    def test_int8_cache_speculative_matches_int8_plain(self):
        """Quantization and speculation compose: both caches int8, and
        the speculative stream equals the plain int8 stream exactly."""
        model, params = _model()
        rng = np.random.default_rng(7)
        prompts = _prompts(rng, [9, 4, 6])
        plain = self._run(model, params, prompts, 5,
                          cache_dtype=jnp.int8, prefix_sharing=False)
        spec = self._run(model, params, prompts, 5,
                         cache_dtype=jnp.int8, draft_model=model,
                         draft_params=params, spec_k=3)
        for a, b in zip(plain, spec):
            np.testing.assert_array_equal(a, b)

    def test_speculation_disables_prefix_sharing(self):
        model, params = _model()
        eng = serving.ServingEngine(model, params, num_slots=2,
                                    page_size=4, attn_impl="lax",
                                    draft_model=model,
                                    draft_params=params)
        assert not eng.cache.config.share_prefix
        assert not eng.draft_cache.config.share_prefix

    def test_bad_configs_rejected(self):
        model, params = _model()
        dmodel, _ = _draft()
        with pytest.raises(ValueError, match="draft_params"):
            serving.ServingEngine(model, params, draft_model=model)
        with pytest.raises(ValueError, match="spec_k"):
            serving.ServingEngine(model, params, draft_model=model,
                                  draft_params=params, spec_k=1)
        other = GPT(GPTConfig.tiny(vocab_size=32))
        with pytest.raises(ValueError, match="vocabulary"):
            serving.ServingEngine(
                model, params, draft_model=other,
                draft_params=other.init(jax.random.PRNGKey(0)))


class TestSpeculativeObservability:
    def test_accept_rate_histogram_and_request_stats(self):
        model, params = _model()
        rng = np.random.default_rng(11)
        reg = obs.MetricsRegistry()
        eng = serving.ServingEngine(model, params, num_slots=2,
                                    page_size=4, prefill_chunk=8,
                                    attn_impl="lax", registry=reg,
                                    draft_model=model,
                                    draft_params=params, spec_k=4)
        rids = [eng.submit(p, 6) for p in _prompts(rng, [5, 8])]
        while not eng.scheduler.idle():
            eng.step()
        h = reg.histogram("serving_spec_accept_rate").summary()
        assert h["count"] > 0
        assert reg.counter("serving_spec_proposed_total").value() > 0
        for r in rids:
            stats = eng.request_stats(r)
            assert stats["spec_proposed"] >= stats["spec_accepted"] > 0
            assert stats["tokens"] == 6.0

    def test_zero_steady_state_recompiles_with_speculation(self):
        model, params = _model()
        dmodel, dparams = _draft()
        rng = np.random.default_rng(12)
        reg = obs.MetricsRegistry()
        eng = serving.ServingEngine(model, params, num_slots=2,
                                    page_size=4, attn_impl="lax",
                                    registry=reg, cache_dtype=jnp.int8,
                                    draft_model=dmodel,
                                    draft_params=dparams, spec_k=3)
        eng.warmup()
        det = obs.RecompileDetector("spec_steady", warmup=0, registry=reg)
        eng.generate_many(_prompts(rng, [9, 4, 6, 13]), max_new_tokens=5,
                          max_steps=200)
        det.check()
        assert det.recompiles == 0, \
            "speculative+quantized steady state recompiled"


class TestSpeculativeBucketCoverage:
    """warmup_plan()/bucket-coverage extend to the draft/verify buckets
    — the ahead-of-time zero-recompile proof covers speculation."""

    def _engine(self):
        model, params = _model()
        return serving.ServingEngine(model, params, num_slots=2,
                                     page_size=4,
                                     max_tokens_per_slot=32,
                                     attn_impl="lax", draft_model=model,
                                     draft_params=params, spec_k=4)

    def test_plan_covers_reachable_including_verify(self):
        eng = self._engine()
        plan = set(eng.warmup_plan())
        assert any(s[0] == "verify" for s in plan)
        assert any(s[0] == "draft" for s in plan)
        assert any(s[0] == "draft_prefill" for s in plan)
        assert not any(s[0] == "decode" for s in plan)
        assert hlo_lint.serving_bucket_coverage(eng) == []

    def test_missing_verify_bucket_fires(self):
        eng = self._engine()
        doctored = {s for s in eng.warmup_plan() if s[0] != "verify"}
        findings = hlo_lint.serving_bucket_coverage(eng, warmed=doctored)
        assert findings and all(f.severity == "error" for f in findings)
        assert any("verify" in f.message for f in findings)

    def test_warmup_executes_the_whole_plan(self):
        eng = self._engine()
        eng.warmup(cost_gauges=False)
        assert eng.warmed_signatures == set(eng.warmup_plan())


class TestSpeculativeMigrationGuard:
    def test_snapshot_and_restore_refused(self):
        model, params = _model()
        eng = serving.ServingEngine(model, params, num_slots=2,
                                    page_size=4, attn_impl="lax",
                                    draft_model=model,
                                    draft_params=params)
        # the guard fires before any slot/state lookup
        with pytest.raises(serving.SlotMigrationError,
                           match="speculative"):
            eng.snapshot_slot(0)
        with pytest.raises(serving.SlotMigrationError,
                           match="speculative"):
            eng.restore_slot({"format": "x"})
