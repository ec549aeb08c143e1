"""The kernel layer's bookkeeping, beside the parity battery of
``tests/test_kernels.py``: the tuner's cache, blocks resolved at trace
time (a serving engine through the tuned kernels never recompiles), and
the registry's lint. A file of its own for ``--dist loadfile``."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import kernels
from paddle_tpu.kernels import autotune, lint, registry

KERNEL_NAMES = kernels.load_all()


class TestTunerCache:
    def test_key_is_deterministic_and_bucketed(self):
        spec = kernels.get("flash_attention")
        args, kw = spec.sample_inputs(0)
        k1 = kernels.tune_key(spec, args, kw)
        k2 = kernels.tune_key(spec, args, kw)
        assert k1 == k2
        # abstract shapes produce the same key as concrete arrays
        # (resolution happens on tracers at trace time)
        abstract = tuple(jax.ShapeDtypeStruct(a.shape, a.dtype)
                         for a in args)
        assert kernels.tune_key(spec, abstract, kw) == k1
        # pow2 bucketing: a 65-token and a 128-token seq share an entry
        (q, k, v), _ = spec.sample_inputs(0)

        def with_seq(s):
            pad = ((0, 0), (0, 0), (0, s - q.shape[2]), (0, 0))
            return tuple(jnp.pad(a, pad) for a in (q, k, v))

        k65 = kernels.tune_key(spec, with_seq(65), kw)
        k128 = kernels.tune_key(spec, with_seq(128), kw)
        assert k65 == k128
        assert kernels.tune_key(spec, args, kw) != k65
        # dtype participates
        bf16 = tuple(a.astype(jnp.bfloat16) for a in args)
        assert kernels.tune_key(spec, bf16, kw) != k1

    def test_persisted_round_trip(self, tmp_path):
        spec = kernels.get("ragged_paged_decode")
        args, kw = spec.sample_inputs(0)
        t1 = kernels.KernelTuner(path=None)
        blocks = t1.get(spec, args, kw)
        assert t1.misses == 1
        path = str(tmp_path / "tune.json")
        t1.save(path)
        t2 = kernels.KernelTuner(path)
        assert t2.get(spec, args, kw) == blocks
        assert t2.hits == 1 and t2.misses == 0

    def test_stale_entry_detected_on_contract_version_bump(self):
        import dataclasses
        spec = kernels.get("ragged_paged_decode")
        args, kw = spec.sample_inputs(0)
        t = kernels.KernelTuner(path=None)
        t.get(spec, args, kw)
        bumped = dataclasses.replace(
            spec, contract=dataclasses.replace(spec.contract, version=99))
        key_old = kernels.tune_key(spec, args, kw)
        key_new = kernels.tune_key(bumped, args, kw)
        assert key_old != key_new        # version is part of the key
        # simulate a manifest written before the bump: entry sits under
        # the NEW key but carries the OLD contract_version
        t.entries[key_new] = dict(t.entries[key_old])
        t.entries[key_new]["contract_version"] = spec.contract.version
        stale_before = t.stale
        blocks = t.get(bumped, args, kw)
        assert t.stale == stale_before + 1
        assert blocks == autotune.static_prior(bumped, args, kw)

    def test_cold_cache_still_correct(self):
        """An empty tuner (no committed manifest) must still produce
        reference-correct outputs — cold is slower, never wrong."""
        prev = kernels.set_default_tuner(kernels.KernelTuner(path=None))
        try:
            kernels.parity_check("ragged_paged_prefill", 0)
        finally:
            kernels.set_default_tuner(prev)

    def test_committed_manifest_fresh_and_cost_seeded(self):
        """tools/kernel_tune.json loads, covers every tunable leaf
        kernel, and carries no stale contract versions."""
        t = kernels.KernelTuner(kernels.DEFAULT_CACHE_PATH)
        assert t.entries, "committed kernel_tune.json missing or empty"
        covered = set()
        for key, ent in t.entries.items():
            name = key.split("|", 1)[0]
            spec = kernels.get(name)
            assert int(ent["contract_version"]) == spec.contract.version, \
                f"stale committed entry {key} — reseed with " \
                "python -m paddle_tpu.kernels.autotune --seed"
            covered.add(name)
        for name in KERNEL_NAMES:
            spec = kernels.get(name)
            if spec.contract.block_candidates and not spec.requires_mesh:
                assert name in covered, f"{name} missing from manifest"

    def test_corrupt_blocks_entry_never_dispatched(self):
        """A hand-edited / corrupt manifest entry whose blocks fall
        outside the contract's candidate set must be refused at
        resolution (re-derived as a prior) and flagged stale — dispatch
        can never run an out-of-contract block config."""
        spec = kernels.get("flash_attention")
        args, kw = spec.sample_inputs(0)
        t = kernels.KernelTuner(path=None)
        t.get(spec, args, kw)
        key = kernels.tune_key(spec, args, kw)
        t.entries[key]["blocks"] = {"block_q": 1024, "block_k": 512}
        assert t.stale_entries() == [key]
        blocks = t.get(spec, args, kw)
        assert t.stale == 1
        assert blocks == autotune.static_prior(spec, args, kw)

    def test_purge_stale_clears_bumped_and_orphaned_entries(self):
        """The documented remediation loop: after a contract-version
        bump, ``--seed`` (via purge_stale) must actually delete the old
        entries — or the CI stale gate could never be cleared."""
        spec = kernels.get("flash_attention")
        args, kw = spec.sample_inputs(0)
        t = kernels.KernelTuner(path=None)
        t.get(spec, args, kw)
        key = kernels.tune_key(spec, args, kw)
        t.entries["gone_kernel|v1|x|float32|cpu"] = dict(t.entries[key])
        t.entries[key + "old"] = {**t.entries[key], "contract_version": 0}
        assert t.purge_stale() == 2
        assert set(t.entries) == {key}

    def test_seed_preserves_current_measured_entries(self):
        """Reseeding must not clobber a fresh measured winner with a
        re-derived prior (a TPU session's tuning would silently vanish
        on the next --seed)."""
        spec = kernels.get("ragged_paged_decode")
        args, kw = spec.sample_inputs(0)
        t = kernels.KernelTuner(path=None)
        res = t.measure(spec, args, kw, impl="pallas_interpret", reps=1)
        key = kernels.seed_entry(t, spec, args, kw)
        assert t.entries[key]["source"] == "measured"
        assert t.entries[key]["blocks"] == res["blocks"]

    def test_seed_entry_stamps_cost_prior(self, tmp_path):
        spec = kernels.get("flash_attention")
        args, kw = spec.sample_inputs(0)
        t = kernels.KernelTuner(path=None)
        key = kernels.seed_entry(t, spec, args, kw)
        ent = t.entries[key]
        assert ent["source"] == "prior"
        assert ent["cost_prior"]["flops"] > 0
        assert ent["cost_prior"]["traffic_bytes"] > 0

    def test_measure_caches_winner_and_hits(self):
        spec = kernels.get("ragged_paged_decode")
        args, kw = spec.sample_inputs(0)
        t = kernels.KernelTuner(path=None)
        res = t.measure(spec, args, kw, impl="pallas_interpret", reps=1)
        cands = spec.contract.block_candidates["pages_per_block"]
        assert cands == (1, 2, 4, 8)      # decode: up to the gather width
        assert res["blocks"]["pages_per_block"] in cands
        assert len(res["timings_s"]) == len(cands)  # every candidate timed
        hits = t.hits
        assert t.get(spec, args, kw) == res["blocks"]
        assert t.hits == hits + 1


class TestTraceTimeResolution:
    def test_tuner_update_never_retraces_steady_state(self):
        """Blocks resolve during tracing; a tuner-cache mutation between
        steady-state calls must NOT trigger a recompile (the jit cache
        keys on shapes, not on tuner state)."""
        from paddle_tpu import observability as obs
        obs.install_compile_listener()
        spec = kernels.get("ragged_paged_decode")
        (q, kp, vp, bt, lens), _ = spec.sample_inputs(0)
        tuner = kernels.KernelTuner(path=None)
        prev = kernels.set_default_tuner(tuner)
        try:
            step = jax.jit(lambda *a: kernels.dispatch(
                "ragged_paged_decode", *a, impl="pallas_interpret"))
            out1 = np.asarray(step(q, kp, vp, bt, lens))   # traces here
            det = obs.RecompileDetector("kernel_tuner_steady", warmup=0)
            # mid-serving tuning: the cache learns a "better" config
            key = kernels.tune_key(spec, (q, kp, vp, bt, lens), {})
            tuner.entries[key]["blocks"] = {"pages_per_block": 4}
            out2 = np.asarray(step(q, kp, vp, bt, lens))
            assert det.check(step=1) == 0, \
                "tuner mutation recompiled a steady-state step"
            np.testing.assert_array_equal(out1, out2)
        finally:
            kernels.set_default_tuner(prev)

    def test_engine_zero_recompiles_with_tuned_interpret_kernel(self):
        """End-to-end acceptance: the serving engine through the REAL
        decode/prefill kernels (interpret) with the autotuner resolving
        pages_per_block at trace time — greedy tokens match the dense
        reference AND a post-warmup detector stays at zero (the tuner
        can never recompile a steady-state step)."""
        from serving_taps import dense_reference as _dense_reference
        from serving_taps import prompts as _prompts, tiny_gpt as _model
        from paddle_tpu import observability as obs
        from paddle_tpu import serving
        model, params = _model(seed=2)
        rng = np.random.default_rng(7)
        prompts = _prompts(rng, [4, 9])
        eng = serving.ServingEngine(model, params, num_slots=2,
                                    page_size=4, prefill_chunk=8,
                                    attn_impl="pallas_interpret")
        eng.warmup()   # precompiles every decode+prefill bucket
        det = obs.RecompileDetector("kernel_engine_steady", warmup=0)
        outs = eng.generate_many(prompts, max_new_tokens=4, max_steps=100)
        det.check()
        assert det.recompiles == 0, \
            "steady-state serving recompiled with the autotuner active"
        for p, o in zip(prompts, outs):
            np.testing.assert_array_equal(
                o, _dense_reference(model, params, p, 4))


    def test_engine_decodes_through_the_body_that_walks_pages(
            self, monkeypatch):
        """The dense decode entry walks a slot's live pages itself where
        a pool page is whole tiles (128 lanes a row here, float32 pages
        of 8 rows) and only elsewhere falls back to the pipelined body:
        an engine at such widths never reaches the fallback, and its
        greedy tokens are the dense cached path's, over slots of one
        and of several pages."""
        from serving_taps import dense_reference as _dense_reference
        from serving_taps import prompts as _prompts
        from paddle_tpu import serving
        from paddle_tpu.models.gpt import GPT, GPTConfig
        from paddle_tpu.serving import decode_attention as DA

        def no_fallback(*_a, **_k):
            raise AssertionError("the pipelined decode body was traced")
        monkeypatch.setattr(DA, "_paged_decode_pallas", no_fallback)
        model = GPT(GPTConfig.tiny(
            vocab_size=64, hidden_size=128, num_heads=2, ffn_size=64,
            max_position=64, dropout=0.0, attn_impl="xla"))
        params = model.init(jax.random.PRNGKey(3))
        prompts = _prompts(np.random.default_rng(11), [4, 19, 9])
        eng = serving.ServingEngine(model, params, num_slots=2,
                                    page_size=8, prefill_chunk=8,
                                    attn_impl="pallas_interpret")
        outs = eng.generate_many(prompts, max_new_tokens=6, max_steps=100)
        for p, o in zip(prompts, outs):
            np.testing.assert_array_equal(
                o, _dense_reference(model, params, p, 6))


class TestRegistryLint:
    def test_full_registry_lints_clean(self):
        report = kernels.lint_registry()
        assert report.ok(), report.render_text()

    def test_all_pallas_sites_are_registered(self):
        """The bypass scan over ops/, parallel/, serving/ must come back
        empty against the real registry + committed allowlist."""
        assert lint.bypass_findings() == []

    def test_unregistered_pallas_call_is_a_bypass(self):
        """Deleting a spec turns its (real) pallas_call sites into
        bypass findings — the scan is live, not a fixture."""
        saved = dict(registry._REGISTRY)
        try:
            del registry._REGISTRY["flash_attention"]
            sites = {f.location for f in lint.bypass_findings()}
            assert "paddle_tpu.ops.attention:_flash_fwd" in sites
            assert "paddle_tpu.ops.attention:_flash_bwd" in sites
        finally:
            registry._REGISTRY.clear()
            registry._REGISTRY.update(saved)

    def test_allowlist_suppresses_and_stale_entry_fails(self, tmp_path):
        saved = dict(registry._REGISTRY)
        allow = tmp_path / "allow.txt"
        try:
            del registry._REGISTRY["flash_attention"]
            allow.write_text(
                "# deliberate exception for the test\n"
                "paddle_tpu.ops.attention:_flash_fwd\n"
                "paddle_tpu.ops.attention:_flash_bwd\n")
            assert lint.bypass_findings(allowlist_path=str(allow)) == []
        finally:
            registry._REGISTRY.clear()
            registry._REGISTRY.update(saved)
        # with the kernel registered again, those entries are now STALE
        # -> each one is its own error finding
        findings = lint.bypass_findings(allowlist_path=str(allow))
        assert len(findings) == 2
        assert all(f.rule == "kernel-registry-bypass" and
                   "stale" in f.message for f in findings)

    def test_contract_violation_is_reported(self):
        """A spec whose lax fallback and Pallas body disagree on output
        shape must produce a kernel-contract finding."""
        spec = kernels.get("flash_attention")
        import dataclasses
        broken = dataclasses.replace(
            spec, name="broken_flash",
            lax_fn=lambda q, k, v, bias=None, **kw:
                jnp.zeros((1,), jnp.float32))
        findings = lint.contract_findings(broken)
        assert any(f.rule == "kernel-contract" for f in findings)

    def test_donation_contract_verified_in_lowered_hlo(self):
        """The decode/prefill donation probes really lower with
        tf.aliasing_output on the page buffers."""
        for name in ("ragged_paged_decode", "ragged_paged_prefill"):
            spec = kernels.get(name)
            fn, args, donate = spec.donation_probe()
            txt = jax.jit(fn, donate_argnums=donate).lower(
                *args).as_text()
            assert txt.count("tf.aliasing_output") >= len(donate)

    def test_graph_lint_preset_includes_kernel_registry(self):
        path = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "tools", "graph_lint.py")
        src = open(path).read()
        assert "lint_kernel_registry" in src

    def test_dispatch_unknown_kernel_and_impl(self):
        with pytest.raises(KeyError):
            kernels.dispatch("no_such_kernel", jnp.zeros(1))
        with pytest.raises(ValueError):
            kernels.resolve_impl("cuda")
