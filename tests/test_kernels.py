"""The shared Pallas kernel layer (ISSUE 12): registry, autotuner,
fallback harness, and registry lint.

The parity battery here is THE acceptance surface for every registered
kernel: pallas-interpret (the real kernel body under the interpreter) vs
the lax fallback vs an independent dense reference, at each contract's
declared tolerances. Plus: byte parity against the pre-refactor call
paths, tuner-cache contracts (deterministic keys, persisted round trip,
stale-entry detection on contract-version bumps, cold-cache
correctness), and the zero-steady-state-recompile invariant with the
autotuner active (tuned blocks resolve at trace time, never mid-step).
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import kernels
from paddle_tpu.kernels import autotune, lint, registry

KERNEL_NAMES = kernels.load_all()
# the entries whose body folds a block of pages as ONE softmax update:
# bit-equal across ``pages_per_block`` no more, each setting held to the
# reference instead
ONE_UPDATE_A_BLOCK = ("ragged_paged_decode",)


# ---------------------------------------------------------------------------
# parity battery — every registered kernel, one harness
# ---------------------------------------------------------------------------

class TestParityBattery:
    @pytest.mark.parametrize("name", KERNEL_NAMES)
    @pytest.mark.parametrize("seed", [0, 1])
    def test_interpret_vs_lax_vs_reference(self, name, seed):
        errs = kernels.parity_check(name, seed)
        # parity_check asserts tolerances internally; a mesh kernel on a
        # single-device box returns {} (skipped), every other kernel
        # must have produced both comparisons
        if errs:
            assert set(errs) >= {"lax", "pallas_interpret"} or \
                set(errs) >= {"xla", "flash_interpret"}, errs


# ---------------------------------------------------------------------------
# head shards — what tensor-parallel serving leans on
# ---------------------------------------------------------------------------

class TestHeadShardsAreIndependent:
    """A tp engine shards its whole step and calls the plain paged
    kernels on each shard's heads (there is no tp kernel). That is sound
    because heads are independent and the pool's lanes are head-major: a
    head shard of the folded pool through the plain kernel is those
    heads of the whole call."""

    @pytest.mark.parametrize("name", [
        "ragged_paged_decode", "ragged_paged_prefill",
        "ragged_paged_decode_int8", "ragged_paged_prefill_int8"])
    @pytest.mark.parametrize("seed", [0, 1])
    def test_shard_equals_those_heads_of_the_whole_call(self, name, seed):
        spec = kernels.get(name)
        args, kw = spec.sample_inputs(seed)
        q, k_pages, v_pages, rest = args[0], args[1], args[2], args[3:]
        h, dh = q.shape[-2:]
        hl = h // 2                 # the two shards of tp=2
        whole = {impl: np.asarray(kernels.dispatch(
            name, *args, impl=impl, **kw))
            for impl in ("lax", "pallas_interpret")}
        for lo, hi in ((0, hl), (hl, h)):
            # scale rows (int8) and the block-table geometry go whole:
            # a token's scale is taken over all its heads
            shard = (q[..., lo:hi, :], k_pages[..., lo * dh:hi * dh],
                     v_pages[..., lo * dh:hi * dh]) + rest
            lax_out = np.asarray(kernels.dispatch(
                name, *shard, impl="lax", **kw))
            want = whole["lax"][..., lo:hi, :]
            if name.endswith("_int8"):
                # XLA's codegen for the fused cast-dequant dot
                # reassociates differently at different head counts, so
                # the per-shard dequant einsum can drift a last ulp from
                # the full-head one (greedy tokens identical to tp=1 are
                # pinned exactly in tests/test_serving_tp.py)
                np.testing.assert_allclose(lax_out, want,
                                           rtol=1e-6, atol=1e-6)
            else:
                np.testing.assert_array_equal(lax_out, want)
            np.testing.assert_allclose(
                np.asarray(kernels.dispatch(
                    name, *shard, impl="pallas_interpret", **kw)),
                whole["pallas_interpret"][..., lo:hi, :],
                atol=spec.contract.atol, rtol=spec.contract.rtol)


# ---------------------------------------------------------------------------
# byte parity vs the pre-refactor call paths
# ---------------------------------------------------------------------------

class TestByteParity:
    def test_flash_dispatch_equals_direct_kernel_call(self):
        """dispatch() with the tuner's default prior must reproduce the
        pre-refactor flash_attention(block=512) output BIT-FOR-BIT."""
        from paddle_tpu.ops.attention import flash_attention
        spec = kernels.get("flash_attention")
        (q, k, v), kw = spec.sample_inputs(0)
        via_registry = np.asarray(kernels.dispatch(
            "flash_attention", q, k, v, None, impl="pallas_interpret",
            tuner=kernels.KernelTuner(path=None), **kw))
        direct = np.asarray(flash_attention(
            q, k, v, None, kw["causal"], None, 512, 512, True))
        np.testing.assert_array_equal(via_registry, direct)

    @pytest.mark.parametrize("name", ["ragged_paged_decode",
                                      "ragged_paged_prefill"])
    def test_pages_per_block_bit_exact(self, name):
        """The pipelined bodies keep the per-page accumulation ORDER
        whatever ``pages_per_block``, so every setting is bit-equal. The
        dense decode body folds a block of pages as one softmax update:
        there every setting is within the contract's tolerance of the
        reference."""
        spec = kernels.get(name)
        args, kw = spec.sample_inputs(1)
        outs = [np.asarray(kernels.dispatch(
            name, *args, impl="pallas_interpret",
            block_sizes={"pages_per_block": pb}, **kw))
            for pb in (1, 2, 4)]
        if name in ONE_UPDATE_A_BLOCK:
            want = np.asarray(spec.reference_fn(*args, **kw))
            for o in outs:
                np.testing.assert_allclose(o, want, atol=spec.contract.atol,
                                           rtol=spec.contract.rtol)
            return
        for o in outs[1:]:
            np.testing.assert_array_equal(outs[0], o)

    def test_decode_dispatch_equals_private_lax(self):
        from paddle_tpu.serving.decode_attention import _paged_decode_lax
        spec = kernels.get("ragged_paged_decode")
        (q, kp, vp, bt, lens), _ = spec.sample_inputs(0)
        via_registry = np.asarray(kernels.dispatch(
            "ragged_paged_decode", q, kp, vp, bt, lens, impl="lax"))
        direct = np.asarray(_paged_decode_lax(
            q, kp, vp, bt, lens, 1.0 / np.sqrt(q.shape[-1])))
        np.testing.assert_array_equal(via_registry, direct)

    def test_flash_prior_is_the_historic_default(self):
        """The static prior must resolve to the pre-refactor 512/512 so
        auto-dispatched flash is byte-identical to the old hard-coded
        path on every bucket."""
        spec = kernels.get("flash_attention")
        for seed in (0, 1, 2):
            args, kw = spec.sample_inputs(seed)
            assert autotune.static_prior(spec, args, kw) == \
                {"block_q": 512, "block_k": 512}


# ---------------------------------------------------------------------------
# tuner cache
# ---------------------------------------------------------------------------

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


# ---------------------------------------------------------------------------
# zero-steady-state-recompile invariant with the autotuner active
# ---------------------------------------------------------------------------

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
        from test_serving import _dense_reference, _model, _prompts
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
        from test_serving import _dense_reference, _prompts
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


# ---------------------------------------------------------------------------
# registry + lint
# ---------------------------------------------------------------------------

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


# ---------------------------------------------------------------------------
# the folded page pool (P, ps, H*Dh): a relayout, never a change of result
# ---------------------------------------------------------------------------

PAGED = ("ragged_paged_decode", "ragged_paged_prefill",
         "ragged_paged_decode_int8", "ragged_paged_prefill_int8")


def _lax_on_the_unfolded_pool(name, args):
    """What the lax path computed when the pool was stored (P, ps, H,
    Dh): the 5-D gather contracted head by head, the decode and the
    prefill contraction each as it was, written out here so the folded
    path is held to something that never saw a fold."""
    from paddle_tpu.ops.attention import NEG_INF
    quantized, chunked = name.endswith("int8"), "prefill" in name
    q, kp, vp = args[:3]
    ks, vs = args[3:5] if quantized else (None, None)
    bt, *geo = args[5:] if quantized else args[3:]
    h, dh = q.shape[-2:]
    scale = 1.0 / np.sqrt(dh)
    p, ps = kp.shape[:2]
    kg = kp.reshape(p, ps, h, dh)[bt].astype(jnp.float32)
    vg = vp.reshape(p, ps, h, dh)[bt].astype(jnp.float32)
    s_slots, mp = bt.shape
    tok = jnp.arange(mp * ps, dtype=jnp.int32)
    qf = q.astype(jnp.float32)
    if chunked:
        starts, n_valid = geo
        c = q.shape[1]
        lead = (s_slots, h, c)
        scores = jnp.einsum("schd,smthd->shcmt", qf, kg) * scale
        if quantized:
            scores = scores * ks[bt][:, None, None]
        pos = starts[:, None] + jnp.arange(c, dtype=jnp.int32)
        live = (tok[None, None, None, :] <= pos[:, None, :, None]) & \
            (jnp.arange(c) < n_valid[:, None])[:, None, :, None]
    else:
        lead = (s_slots, h)
        scores = jnp.einsum("shd,smthd->shmt", qf, kg) * scale
        if quantized:
            scores = scores * ks[bt][:, None]
        live = tok[None, None, :] < geo[0][:, None, None]
    scores = jnp.where(live, scores.reshape(lead + (mp * ps,)), NEG_INF)
    w = jax.nn.softmax(scores, axis=-1)
    alive = jnp.max(scores, axis=-1, keepdims=True) > NEG_INF / 2
    w = jnp.where(alive, w, 0.0).reshape(lead + (mp, ps))
    if quantized:
        w = w * (vs[bt][:, None, None] if chunked else vs[bt][:, None])
    out = jnp.einsum("shcmt,smthd->schd" if chunked
                     else "shmt,smthd->shd", w, vg)
    return out.astype(q.dtype)


class TestFoldedPagePool:
    @pytest.mark.parametrize("name", PAGED)
    def test_samples_and_contracts_declare_the_folded_pool(self, name):
        spec = kernels.get(name)
        args, _ = spec.sample_inputs(2)
        q, kp, vp = args[:3]
        h, dh = q.shape[-2:]
        assert kp.ndim == vp.ndim == 3 and kp.shape[2] == h * dh
        for arg in ("k_pages", "v_pages"):
            layout = spec.contract.arg_layouts[arg]
            assert layout.startswith("(P,ps,H*Dh)"), layout
            assert lint._layout_rank(layout) == 3
        assert lint.contract_findings(spec) == []

    @pytest.mark.parametrize("name", PAGED)
    def test_lax_path_bit_equal_to_the_unfolded_pool(self, name):
        """Folding the heads into the lane axis is a reshape of
        row-major bytes: on the lax path it changes no bit."""
        spec = kernels.get(name)
        for seed in (0, 1, 2):
            args, _ = spec.sample_inputs(seed)
            folded = np.asarray(kernels.dispatch(name, *args, impl="lax"))
            np.testing.assert_array_equal(
                folded, np.asarray(_lax_on_the_unfolded_pool(name, args)))

    @pytest.mark.parametrize("name", PAGED)
    def test_kernel_takes_head_h_from_lanes_h_dh(self, name):
        """Head ``h`` is lanes ``[h*Dh, (h+1)*Dh)`` of a page block and
        nothing else: with the heads of the query and of the folded pool
        reordered alike, the Pallas body (interpreted) gives the same
        heads, reordered, bit for bit — and stays inside the contract's
        tolerance of the lax path fed the same folded pool."""
        spec = kernels.get(name)
        args, _ = spec.sample_inputs(2)
        q, kp, vp = args[:3]
        h, dh = q.shape[-2:]
        perm = np.random.default_rng(0).permutation(h)

        def reorder(pool):
            p, ps, _ = pool.shape
            return pool.reshape(p, ps, h, dh)[:, :, perm].reshape(p, ps, -1)

        out = np.asarray(kernels.dispatch(name, *args,
                                          impl="pallas_interpret"))
        moved = np.asarray(kernels.dispatch(
            name, q[..., perm, :], reorder(kp), reorder(vp), *args[3:],
            impl="pallas_interpret"))
        np.testing.assert_array_equal(moved, out[..., perm, :])
        np.testing.assert_allclose(
            out, np.asarray(kernels.dispatch(name, *args, impl="lax")),
            atol=spec.contract.atol, rtol=spec.contract.rtol)

    @pytest.mark.parametrize("name", PAGED)
    def test_tune_keys_still_hit_the_committed_manifest(self, name):
        """The head count in a tune key comes from ``q`` (the folded
        pool does not carry it); every bucket the offline seeding visits
        (the samples and their per-shard tp twins) must resolve from
        tools/kernel_tune.json — an entry, never a fresh static prior."""
        spec = kernels.get(name)
        tuner = kernels.KernelTuner(kernels.DEFAULT_CACHE_PATH)
        committed = set(tuner.entries)
        samples = [spec.sample_inputs(seed) for seed in (0, 1, 2)]
        samples += [v(seed) for v in spec.tune_sample_variants
                    for seed in (0, 1, 2)]
        for args, kw in filter(None, samples):
            assert kernels.tune_key(spec, args, kw) in committed
            tuner.get(spec, args, kw)
        assert tuner.misses == 0 and tuner.hits > 0

    @pytest.mark.parametrize("name", PAGED)
    def test_vmem_estimate_prices_a_page_block_as_it_is_tiled(self, name):
        """At the serving cell's widths (12 heads of 64, pages of 128) a
        page block is (128, 768): whole tiles in bf16 and in int8, no
        padding of heads."""
        spec = kernels.get(name)
        sds = jax.ShapeDtypeStruct
        quantized, chunked = name.endswith("int8"), "prefill" in name
        q = sds((64, 32, 12, 64) if chunked else (64, 12, 64), jnp.bfloat16)
        pool = sds((513, 128, 768), jnp.int8 if quantized else jnp.bfloat16)
        one = spec.vmem_estimate((q, pool), {}, {"pages_per_block": 1})
        two = spec.vmem_estimate((q, pool), {}, {"pages_per_block": 2})
        page_blocks = 128 * 768 * pool.dtype.itemsize      # unpadded
        scale_rows = 8 * 128 * 4 if quantized else 0
        # one more page a step = a K and a V block (+ scale groups),
        # double-buffered by the pipeline and once more by the estimate;
        # the dense decode body's own two buffers each come to the same,
        # and its one softmax update grows a page wider: 16 rows of
        # float32 scores and weights, the weights' three bf16 terms
        wider_fold = (2 * 16 * 128 * 4 + 48 * 128 * 2
                      if name in ONE_UPDATE_A_BLOCK else 0)
        assert two - one == 2 * 2 * (page_blocks + scale_rows) + wider_fold


# ---------------------------------------------------------------------------
# bench artifact
# ---------------------------------------------------------------------------

class TestBenchArtifact:
    def test_committed_bench_kernels_schema(self):
        path = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "BENCH_KERNELS.json")
        with open(path) as f:
            r = json.load(f)
        for k in ("metric", "value", "kernels", "tuner_cache_hits",
                  "committed_cache_entries", "committed_cache_stale"):
            assert k in r, f"BENCH_KERNELS.json missing {k}"
        assert r["committed_cache_stale"] == 0
        assert set(r["kernels"]) == {"flash_attention",
                                     "ragged_paged_decode",
                                     "ragged_paged_prefill"}
        for buckets in r["kernels"].values():
            assert len(buckets) == 3


# ---------------------------------------------------------------------------
# grouped-query heads in the paged kernels (query head h reads KV head
# h // (H / KV)): the Pallas body against the lax form and a dense NumPy
# reference that repeats nothing
# ---------------------------------------------------------------------------

def _gqa_sample(seed, chunked):
    s, h, kv, dh, ps, mp = ((3, 8, 2, 16, 8, 4), (4, 4, 1, 32, 4, 6))[seed]
    rng = np.random.default_rng(seed)
    num_pages = s * mp + 1
    kp = jnp.asarray(rng.standard_normal((num_pages, ps, kv * dh)),
                     jnp.float32)
    vp = jnp.asarray(rng.standard_normal((num_pages, ps, kv * dh)),
                     jnp.float32)
    bt = jnp.asarray((rng.permutation(num_pages - 1)[:s * mp] + 1)
                     .reshape(s, mp), jnp.int32)
    if not chunked:
        q = jnp.asarray(rng.standard_normal((s, h, dh)), jnp.float32)
        lengths = jnp.asarray(rng.integers(0, mp * ps + 1, s), jnp.int32)
        return (q, kp, vp, bt, lengths)
    c = ps
    q = jnp.asarray(rng.standard_normal((s, c, h, dh)), jnp.float32)
    starts = jnp.asarray(rng.integers(0, (mp - 1) * ps, s), jnp.int32)
    n_valid = jnp.asarray(rng.integers(0, c + 1, s), jnp.int32)
    return (q, kp, vp, bt, starts, n_valid)


def _gqa_reference(q, kp, vp, bt, *geometry, scale=None):
    q, kp, vp, bt = (np.asarray(a, np.float64) for a in (q, kp, vp, bt))
    bt = bt.astype(int)
    chunked = len(geometry) == 2
    if not chunked:
        q = q[:, None]                                  # (S, 1, H, Dh)
    s, c, h, dh = q.shape
    kv = kp.shape[-1] // dh
    ps = kp.shape[1]
    out = np.zeros_like(q)
    for sl in range(s):
        k = kp[bt[sl]].reshape(-1, kv, dh)
        v = vp[bt[sl]].reshape(-1, kv, dh)
        for r in range(c):
            if chunked:
                if r >= int(geometry[1][sl]):
                    continue
                limit = int(geometry[0][sl]) + r + 1
            else:
                limit = int(geometry[0][sl])
            if limit == 0:
                continue
            for hh in range(h):
                g = hh // (h // kv)
                sc = k[:limit, g] @ q[sl, r, hh] * (scale or dh ** -0.5)
                p = np.exp(sc - sc.max())
                out[sl, r, hh] = (p / p.sum()) @ v[:limit, g]
    del ps
    return out if chunked else out[:, 0]


class TestGroupedQueryHeads:
    @pytest.mark.parametrize("seed", [0, 1])
    @pytest.mark.parametrize("name", ["ragged_paged_decode",
                                      "ragged_paged_prefill"])
    @pytest.mark.parametrize("impl", ["lax", "pallas_interpret"])
    def test_against_dense_reference(self, name, seed, impl):
        args = _gqa_sample(seed, chunked=name.endswith("prefill"))
        want = _gqa_reference(*args)
        got = np.asarray(kernels.dispatch(name, *args, impl=impl))
        np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)

    @pytest.mark.parametrize("name", ["ragged_paged_decode",
                                      "ragged_paged_prefill"])
    def test_pages_per_block_bit_exact(self, name):
        args = _gqa_sample(0, chunked=name.endswith("prefill"))
        outs = [np.asarray(kernels.dispatch(
            name, *args, impl="pallas_interpret",
            block_sizes={"pages_per_block": pb})) for pb in (1, 2, 4)]
        if name in ONE_UPDATE_A_BLOCK:
            for o in outs:
                np.testing.assert_allclose(o, _gqa_reference(*args),
                                           atol=2e-5, rtol=2e-5)
            return
        for o in outs[1:]:
            np.testing.assert_array_equal(outs[0], o)


# ---------------------------------------------------------------------------
# the decode body folds a page ONCE for every head, on operands in the
# dtype they are stored in: bf16 (or int8) pages take one bf16 MXU pass,
# float32 queries and the float32 softmax weights go in as three bf16
# terms, so no operand is rounded. Held to a float64 reference computed
# from the bf16 VALUES, at a tolerance a single-term `P` fails.
# ---------------------------------------------------------------------------

_FOLD_SHAPES = {"mha-12x64": (12, 12, 64), "gqa-32-over-4x128": (32, 4, 128)}
_FOLD_PS, _FOLD_MP = 16, 8
# empty, one token, a page boundary, one past it, the full width, three
# live pages (no multiple of 2, 4 or 8), an empty slot between two live
# ones, six live pages (two blocks of 4, the second half full)
_FOLD_LENGTHS = (0, 1, _FOLD_PS, _FOLD_PS + 1, _FOLD_PS * _FOLD_MP, 37, 0,
                 5 * _FOLD_PS + 3)
_FOLD_PB = (1, 2, 4, 8)


def _fold_sample(shape, pool):
    """float32 queries holding bf16 values (so the output is float32)
    over a bf16, float32 or int8 pool; returns (kernel name, args,
    float64 K, V)."""
    h, kv, dh = _FOLD_SHAPES[shape]
    s = len(_FOLD_LENGTHS)
    rng = np.random.default_rng(h)
    num_pages = s * _FOLD_MP + 1
    q = jnp.asarray(rng.standard_normal((s, h, dh)),
                    jnp.bfloat16).astype(jnp.float32)
    kp, vp = (jnp.asarray(
        rng.standard_normal((num_pages, _FOLD_PS, kv * dh)), jnp.bfloat16)
        for _ in range(2))
    bt = jnp.asarray((rng.permutation(num_pages - 1)[:s * _FOLD_MP] + 1)
                     .reshape(s, _FOLD_MP), jnp.int32)
    lengths = jnp.asarray(_FOLD_LENGTHS, jnp.int32)
    if pool in ("bf16", "f32"):
        k64, v64 = (np.asarray(p.astype(jnp.float32), np.float64)
                    for p in (kp, vp))
        if pool == "f32":       # the same values, multiplied at HIGHEST
            kp, vp = kp.astype(jnp.float32), vp.astype(jnp.float32)
        return "ragged_paged_decode", (q, kp, vp, bt, lengths), k64, v64
    from paddle_tpu.serving.paged_cache import quantize_kv
    kq, ks = quantize_kv(kp.astype(jnp.float32), (2,))
    vq, vs = quantize_kv(vp.astype(jnp.float32), (2,))
    k64, v64 = (np.asarray(p, np.float64)
                * np.asarray(sc, np.float64)[:, :, None]
                for p, sc in ((kq, ks), (vq, vs)))
    return ("ragged_paged_decode_int8", (q, kq, vq, ks, vs, bt, lengths),
            k64, v64)


def _live_pages(bt, lengths, ps):
    """The pool pages that some slot's live extent covers."""
    bt, lengths = np.asarray(bt), np.asarray(lengths)
    return {int(p) for row, n in zip(bt, lengths)
            for p in row[:-(-int(n) // ps)]}


class TestDecodeFoldsAPageForAllHeads:
    TOL = 2e-5

    def _run(self, name, args, pb=2):
        return np.asarray(kernels.dispatch(
            name, *args, impl="pallas_interpret", scale=0.125,
            block_sizes={"pages_per_block": pb}))

    @pytest.mark.parametrize("pb", _FOLD_PB)
    @pytest.mark.parametrize("pool", ["bf16", "f32", "int8"])
    @pytest.mark.parametrize("shape", _FOLD_SHAPES)
    def test_against_float64_on_the_stored_values(self, shape, pool, pb):
        name, args, k64, v64 = _fold_sample(shape, pool)
        want = _gqa_reference(args[0], k64, v64, args[-2], args[-1],
                              scale=0.125)
        got = self._run(name, args, pb)
        assert got.dtype == np.float32
        np.testing.assert_allclose(got, want, atol=self.TOL, rtol=self.TOL)
        # the empty slots: zeros, the first and the one between two live
        assert not got[0].any() and not got[6].any()

    @pytest.mark.parametrize("pool", ["bf16", "int8"])
    @pytest.mark.parametrize("shape", _FOLD_SHAPES)
    def test_pages_per_block_bit_equal(self, shape, pool):
        """The int8 entry keeps the body that folds page by page: bit-
        equal for any setting. The dense entry's body folds a block as
        one update: every setting within the tolerance of float64."""
        name, args, k64, v64 = _fold_sample(shape, pool)
        outs = [self._run(name, args, pb) for pb in (1, 2, 4)]
        if name in ONE_UPDATE_A_BLOCK:
            want = _gqa_reference(args[0], k64, v64, args[-2], args[-1],
                                  scale=0.125)
            for o in outs:
                np.testing.assert_allclose(o, want, atol=self.TOL,
                                           rtol=self.TOL)
            return
        for o in outs[1:]:
            np.testing.assert_array_equal(outs[0], o)

    @pytest.mark.parametrize("pool", ["bf16", "f32"])
    @pytest.mark.parametrize("pb", _FOLD_PB)
    def test_only_live_pages_are_read(self, pb, pool):
        """The dense body copies a slot's live pages itself: with every
        pool page that no slot's live extent covers filled with NaN
        (the null page 0 among them) the outputs are the clean pool's."""
        name, args, _k, _v = _fold_sample("gqa-32-over-4x128", pool)
        q, kp, vp, bt, lengths = args
        dead = np.asarray(sorted(
            set(range(kp.shape[0])) - _live_pages(bt, lengths, _FOLD_PS)))
        assert 0 in dead and len(dead) > len(_FOLD_LENGTHS)
        poisoned = tuple(p.at[dead].set(jnp.nan) for p in (kp, vp))
        got = self._run(name, (q, *poisoned, bt, lengths), pb)
        assert np.isfinite(got).all()
        np.testing.assert_array_equal(got, self._run(name, args, pb))

    @pytest.mark.parametrize("pb", _FOLD_PB)
    def test_nothing_stale_is_folded(self, pb):
        """What a buffer held before reaches no output: a full-length
        slot of huge values (whose weights times a small value would
        still be seen), then a one-token slot, then a slot whose last
        block holds one live page, its dead table entries pointing at a
        NaN page."""
        h, kv, dh, ps, mp = 8, 2, 128, _FOLD_PS, 8
        rng = np.random.default_rng(pb)
        lengths = (mp * ps, 1, (pb if pb < mp else pb // 2) * ps + 1)
        num_pages = len(lengths) * mp + 2
        q = jnp.asarray(rng.standard_normal((len(lengths), h, dh)),
                        jnp.bfloat16).astype(jnp.float32)
        kp, vp = (jnp.asarray(
            rng.standard_normal((num_pages, ps, kv * dh)), jnp.bfloat16)
            for _ in range(2))
        bt = np.arange(1, 1 + len(lengths) * mp).reshape(len(lengths), mp)
        huge = jnp.asarray(3e38, jnp.bfloat16)
        kp = kp.at[bt[0]].multiply(huge)
        vp = vp.at[bt[0]].set(huge)
        nan_page = num_pages - 1
        for sl, n in enumerate(lengths):
            bt[sl, -(-n // ps):] = nan_page
        kp, vp = kp.at[nan_page].set(jnp.nan), vp.at[nan_page].set(jnp.nan)
        args = (q, kp, vp, jnp.asarray(bt, jnp.int32),
                jnp.asarray(lengths, jnp.int32))
        k64, v64 = (np.asarray(p.astype(jnp.float32), np.float64)
                    for p in (kp, vp))
        with np.errstate(all="ignore"):     # slot 0's own inf and NaN
            want = _gqa_reference(args[0], k64, v64, args[-2], args[-1],
                                  scale=0.125)
        got = self._run("ragged_paged_decode", args, pb)
        np.testing.assert_allclose(got[1:], want[1:], atol=self.TOL,
                                   rtol=self.TOL)

    @pytest.mark.parametrize("shape", _FOLD_SHAPES)
    def test_one_bf16_term_of_p_is_seen_and_fails(self, shape, monkeypatch):
        """The same body with the split removed (`P`, and a float32
        `q`, rounded to ONE bf16 term: what most flash kernels do) is a
        different result: it misses the tolerance by two orders, where
        the three-term fold sits two orders inside it. The scale is a
        power of two, so the queries stay bf16 values and only `P` is
        rounded."""
        from paddle_tpu.serving import decode_attention as DA
        name, args, k64, v64 = _fold_sample(shape, "bf16")
        want = _gqa_reference(args[0], k64, v64, args[-2], args[-1],
                              scale=0.125)
        split_err = np.abs(self._run(name, args) - want).max()
        monkeypatch.setattr(DA, "_bf16_terms",
                            lambda x: x.astype(jnp.bfloat16))
        DA._paged_decode_walk_pallas.clear_cache()   # traced with the split
        try:
            rounded_err = np.abs(self._run(name, args) - want).max()
        finally:
            DA._paged_decode_walk_pallas.clear_cache()
        assert split_err < self.TOL / 20
        assert rounded_err > self.TOL * 50

    def test_only_live_pages_move(self):
        """The pipelined body (the int8 and sparse entries): page
        operand ``t`` of a grid step holds page ``j*pb + t``
        while the slot has it, then its own last live page again (a
        repeated index moves nothing), and pool page 0 where the slot
        never gives it a live page."""
        from paddle_tpu.serving import decode_attention as DA
        ps, pb = 16, 2
        bt = np.arange(100, 108)[None]                       # one slot
        for n_tokens, want in ((0, [[0, 0], [0, 0], [0, 0], [0, 0]]),
                               (1, [[100, 0], [100, 0], [100, 0], [100, 0]]),
                               (ps * 3, [[100, 101], [102, 101],
                                         [102, 101], [102, 101]]),
                               (ps * 8, [[100, 101], [102, 103],
                                         [104, 105], [106, 107]])):
            lens = np.asarray([n_tokens])
            got = [[int(DA._decode_page(bt, lens, 0, j, t, page_size=ps,
                                        pages_per_block=pb))
                    for t in range(pb)] for j in range(4)]
            assert got == want, (n_tokens, got)


def _sparse_decode_cell_args():
    """``sparse_paged_decode`` at the docs cell's geometry: 32 slots, 32
    query heads over 4 KV heads of 128, tables of 128 pages of a
    1280-page bf16 pool, the selection as a mask, groups of 8."""
    sds = jax.ShapeDtypeStruct
    pages = sds((1280, 128, 4 * 128), jnp.bfloat16)
    return (sds((32, 32, 128), jnp.bfloat16), pages, pages,
            sds((32, 128), jnp.int32), sds((32, 128 * 128), jnp.float32),
            sds((32,), jnp.int32), sds((16, 8), jnp.int32),
            sds((16,), jnp.int32), sds((32,), jnp.int32))


def test_sparse_decode_traces_to_the_call_it_was():
    """``sparse_paged_decode`` at the docs cell's geometry traces, on its
    Pallas path, to the jaxpr it had when the body that walks the pools
    under the selection was written (PR 44; sha256 taken by these lines
    under this suite's conftest, source positions stripped; a change that
    means to alter the sparse decode call takes it anew): two calls
    under the kernel's one name, blocks of 8 pages."""
    import functools
    import hashlib
    import re
    spec = kernels.get("sparse_paged_decode")
    args = _sparse_decode_cell_args()
    blocks = autotune.static_prior(spec, args, {})
    assert blocks == {"pages_per_block": 8}
    text = str(jax.make_jaxpr(functools.partial(
        spec.pallas_fn, block_sizes=blocks, interpret=False))(*args))
    assert text.count("name=sparse_paged_decode") == 2
    text = re.sub(r" at [^\s\]]+:\d+", "", text)
    assert hashlib.sha256(text.encode()).hexdigest() == (
        "8df1db902acdd374b53cf4fe8056cd7d"
        "f6da3892bab925851d219babfdae37be")


def test_sparse_decode_vmem_estimate_at_the_published_widths():
    """A group's step at 8 members x 8 rows a KV head and blocks of 8
    pages: more than its four buffers of 8 pages (4 MB), under half the
    chip's 16 MiB default scope."""
    spec = kernels.get("sparse_paged_decode")
    need = spec.vmem_estimate(_sparse_decode_cell_args(), {},
                              {"pages_per_block": 8})
    assert 4 * 8 * 128 * 512 * 2 < need < 8 << 20


# ---------------------------------------------------------------------------
# the state-space kernels (ops/ssm_scan.py)
# ---------------------------------------------------------------------------

class TestStateSpaceKernels:
    """Beyond the parity battery above (both kernels, both outputs,
    against the token-by-token recurrence): how the pool's type, the
    scan's tile and the decode grid's head blocks behave."""

    NAMES = ["ssd_chunk_scan", "ssm_decode_update"]

    @pytest.mark.parametrize("impl", ["lax", "pallas_interpret"])
    @pytest.mark.parametrize("name", NAMES)
    def test_a_bfloat16_pool_rounds_the_stored_state_only(self, name, impl):
        """The pool comes back in the type it came in. ``y`` is float32
        and is computed from the float32 state of the step, so it moves
        only by what the START state lost when it was rounded; rows no
        lane holds keep their bits."""
        args, kw = kernels.get(name).sample_inputs(1)
        rounded = args[5].astype(jnp.bfloat16)
        y32, p32 = kernels.dispatch(
            name, *args[:5], rounded.astype(jnp.float32), *args[6:],
            impl=impl, **kw)
        y16, p16 = kernels.dispatch(name, *args[:5], rounded, *args[6:],
                                    impl=impl, **kw)
        assert p16.dtype == jnp.bfloat16 and y16.dtype == jnp.float32
        np.testing.assert_allclose(np.asarray(y16), np.asarray(y32),
                                   atol=1e-5)
        assert (np.asarray(p16) == np.asarray(
            p32.astype(jnp.bfloat16))).all()

    def test_decode_grid_step_holds_heads_of_one_group(self):
        from paddle_tpu.ops import ssm_scan
        # the published mixer: 16 heads a group, tiles of (256, 128)
        # float32: all 16 in one step, 2 MiB of state in and out each
        assert ssm_scan._head_block(32, 16, 256, 128) == 16
        assert ssm_scan._head_block(4, 2, 16, 16) == 2
        # a tile four times as large: the most that divide the group and
        # stay under the budget
        assert ssm_scan._head_block(32, 16, 1024, 128) == 4
        assert ssm_scan._head_block(24, 12, 1024, 128) == 4

    def test_scan_tiles_a_long_chunk_and_refuses_a_ragged_one(self):
        from paddle_tpu.ops import ssm_scan
        assert ssm_scan._tile(8) == 8 and ssm_scan._tile(128) == 128
        assert ssm_scan._tile(384) == ssm_scan.SCAN_TILE
        with pytest.raises(ValueError, match="multiple of the scan tile"):
            ssm_scan._tile(200)

    @pytest.mark.parametrize("name", NAMES)
    def test_dispatch_is_counted_by_kernel_and_impl(self, name):
        from paddle_tpu.observability import registry as obs_registry
        c = obs_registry.counter("kernel_dispatch_total")
        before = {i: c.value(kernel=name, impl=i)
                  for i in ("lax", "pallas_interpret")}
        args, kw = kernels.get(name).sample_inputs(0)
        kernels.dispatch(name, *args, impl="pallas_interpret", **kw)
        assert c.value(kernel=name, impl="pallas_interpret") \
            == before["pallas_interpret"] + 1
        assert c.value(kernel=name, impl="lax") == before["lax"]


# ---------------------------------------------------------------------------
# the flash bodies (ops/attention.py): a body holds only what its static
# shape needs, and the backward is one kernel
# ---------------------------------------------------------------------------

def _pallas_eqns(jaxpr):
    """Every ``pallas_call`` equation of a jaxpr, nested ones included."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append(eqn)
            continue
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found.extend(_pallas_eqns(sub))
    return found


def _kernel_ops(eqn):
    """(primitive name, result shape) of every operation of a Pallas
    kernel's body, branches of a ``cond`` included."""
    def walk(jaxpr):
        for e in jaxpr.eqns:
            for out in e.outvars[:1]:
                yield e.primitive.name, tuple(getattr(out.aval, "shape", ()))
            for sub in jax.core.jaxprs_in_params(e.params):
                yield from walk(sub)
    return list(walk(eqn.params["jaxpr"]))


class TestFlashBodies:
    # name: (sq, sk, block_q, block_k)
    GEOMETRY = {
        "one_pair": (64, 64, 64, 64),
        "one_pair_sq_lt_sk": (40, 56, 64, 64),
        "blocks": (64, 64, 32, 32),
        "one_key_block": (64, 32, 32, 32),
        "ragged_sq": (56, 64, 32, 32),
        "ragged_sk": (64, 56, 32, 32),
        "ragged_both_sq_lt_sk": (40, 72, 32, 32),
    }

    @staticmethod
    def _inputs(sq, sk, dtype, key_bias, b=2, h=2, d=32, seed=0):
        ks = jax.random.split(jax.random.PRNGKey(seed), 4)
        q = jax.random.normal(ks[0], (b, h, sq, d), jnp.float32).astype(dtype)
        k = jax.random.normal(ks[1], (b, h, sk, d), jnp.float32).astype(dtype)
        v = jax.random.normal(ks[2], (b, h, sk, d), jnp.float32).astype(dtype)
        g = jax.random.normal(ks[3], (b, h, sq, d), jnp.float32).astype(dtype)
        bias = None
        if key_bias:
            # batch 0: every key masked (all its rows are dead);
            # batch 1: the last third of the keys masked
            keep = jnp.stack([jnp.zeros(sk, bool),
                              jnp.arange(sk) < sk - sk // 3])
            from paddle_tpu.ops.attention import make_padding_bias
            bias = make_padding_bias(keep)
        return q, k, v, g, bias

    @staticmethod
    def _reference(q, k, v, g, bias, causal):
        """float32 composed attention on the same (rounded) inputs: out,
        lse, which rows have a key at all, and the three gradients."""
        from paddle_tpu.ops import attention as A
        q, k, v, g = (x.astype(jnp.float32) for x in (q, k, v, g))
        out, vjp = jax.vjp(
            lambda q, k, v: A.scaled_dot_product_attention(
                q, k, v, bias=bias, causal=causal), q, k, v)
        s = A._masked_scores(q, k, bias, scale=q.shape[-1] ** -0.5,
                             causal=causal)
        alive = jnp.max(s, axis=-1) > A.NEG_INF / 2
        return out, jax.nn.logsumexp(s, axis=-1), alive, vjp(g)

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                             ids=["f32", "bf16"])
    @pytest.mark.parametrize("key_bias", [False, True],
                             ids=["nobias", "keybias_dead_row"])
    @pytest.mark.parametrize("causal", [False, True],
                             ids=["full", "causal"])
    @pytest.mark.parametrize("geometry", sorted(GEOMETRY))
    def test_forward_lse_and_gradients_match_composed_float32(
            self, geometry, causal, key_bias, dtype):
        from paddle_tpu.ops import attention as A
        sq, sk, bq, bk = self.GEOMETRY[geometry]
        q, k, v, g, bias = self._inputs(sq, sk, dtype, key_bias)
        want_out, want_lse, alive, want_grads = self._reference(
            q, k, v, g, bias, causal)
        out, lse = A._flash_fwd(q, k, v, bias, scale=q.shape[-1] ** -0.5,
                                causal=causal, block_q=bq, block_k=bk,
                                interpret=True, return_lse=True)
        _, vjp = jax.vjp(lambda q, k, v: A.flash_attention(
            q, k, v, bias, causal, None, bq, bk, True), q, k, v)
        grads = vjp(g)
        tol = (dict(atol=2e-5, rtol=2e-5) if dtype == jnp.float32
               else dict(atol=3e-2, rtol=3e-2))
        gtol = (dict(atol=2e-4, rtol=2e-4) if dtype == jnp.float32
                else dict(atol=6e-2, rtol=6e-2))
        assert out.dtype == dtype and lse.dtype == jnp.float32
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(want_out), **tol)
        alive = np.asarray(alive)
        np.testing.assert_allclose(np.asarray(lse)[alive],
                                   np.asarray(want_lse)[alive], **tol)
        assert (np.asarray(lse)[~alive] <= A.NEG_INF / 2).all()
        for got, want in zip(grads, want_grads):
            assert got.dtype == dtype
            np.testing.assert_allclose(np.asarray(got, np.float32),
                                       np.asarray(want), **gtol)

    @pytest.mark.parametrize("causal", [False, True], ids=["full", "causal"])
    @pytest.mark.parametrize("blocks", [(64, 64), (32, 32)],
                             ids=["one_pair", "blocks"])
    def test_backward_against_a_foreign_lse(self, blocks, causal):
        """What ring attention hands ``_flash_bwd``: the logsumexp and
        the output of attention over MORE keys than the block it asks
        the gradients of. They are that block's share of the whole
        attention's gradients."""
        from paddle_tpu.ops import attention as A
        q, k, v, g, _ = self._inputs(64, 128, jnp.float32, False, seed=3)
        scale = q.shape[-1] ** -0.5
        # keys 0..63 are the block (the diagonal one under causal), keys
        # 64..127 a block every query sees whole, folded in by hand
        k1, k2, v1, v2 = k[:, :, :64], k[:, :, 64:], v[:, :, :64], v[:, :, 64:]

        def whole(q, k1, v1):
            s1 = A._masked_scores(q, k1, None, scale=scale, causal=causal)
            s2 = A._masked_scores(q, k2, None, scale=scale, causal=False)
            s = jnp.concatenate([s1, s2], axis=-1)
            p = jax.nn.softmax(s, axis=-1)
            out = jnp.einsum("bhqk,bhkd->bhqd", p,
                             jnp.concatenate([v1, v2], axis=2))
            return out, jax.nn.logsumexp(s, axis=-1)

        (out, lse), vjp = jax.vjp(whole, q, k1, v1)
        _, want_dk, want_dv = vjp((g, jnp.zeros_like(lse)))
        kw = dict(scale=scale, causal=causal)
        dq, dk, dv = A._flash_bwd(q, k1, v1, None, out, lse, g,
                                  block_q=blocks[0], block_k=blocks[1],
                                  interpret=True, **kw)
        want_dq = A._lax_flash_block_bwd(q, k1, v1, None, out, lse, g,
                                         **kw)[0]
        for got, want in ((dq, want_dq), (dk, want_dk), (dv, want_dv)):
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       atol=2e-4, rtol=2e-4)

    @staticmethod
    def _grad_jaxpr(sq, sk, bq, bk, causal, key_bias):
        from paddle_tpu.ops import attention as A
        # a head width no block has: a (bq, bk) shape names the scores
        q, k, v, _, bias = TestFlashBodies._inputs(sq, sk, jnp.float32,
                                                   key_bias, d=16)
        return jax.make_jaxpr(jax.grad(
            lambda q, k, v: A.flash_attention(
                q, k, v, bias, causal, None, bq, bk, True).sum(),
            argnums=(0, 1, 2)))(q, k, v).jaxpr

    @pytest.mark.parametrize("geometry", sorted(GEOMETRY))
    def test_gradient_is_two_pallas_calls(self, geometry):
        """The forward and ONE backward, whatever the blocking."""
        calls = _pallas_eqns(self._grad_jaxpr(*self.GEOMETRY[geometry],
                                              causal=False, key_bias=True))
        assert len(calls) == 2
        assert [len(c.outvars) for c in calls] == [2, 3]   # o, lse; dq dk dv

    @pytest.mark.parametrize("geometry, causal, masked", [
        ("one_pair", False, False), ("blocks", False, False),
        ("one_key_block", False, False), ("one_pair", True, True),
        ("blocks", True, True), ("ragged_sq", False, True),
        ("ragged_sk", False, True)])
    def test_masks_exist_only_where_the_shape_needs_them(
            self, geometry, causal, masked):
        sq, sk, bq, bk = self.GEOMETRY[geometry]
        bq, bk = min(bq, sq), min(bk, sk)
        calls = _pallas_eqns(self._grad_jaxpr(sq, sk, bq, bk, causal,
                                              key_bias=True))
        if geometry == "ragged_sq":
            # the forward's rows past seq_q are never written back; the
            # backward drops them through (bq, 1) columns and the
            # (bq, Dh) operands
            fwd_ops, bwd_ops = map(_kernel_ops, calls)
            assert "iota" not in [name for name, _ in fwd_ops]
            assert ("iota", (bq, 1)) in bwd_ops
            assert ("select_n", (bq, bk)) not in fwd_ops + bwd_ops
            return
        for ops in map(_kernel_ops, calls):
            assert ("iota" in [name for name, _ in ops]) == masked
            assert (("select_n", (bq, bk)) in ops) == masked

    @pytest.mark.parametrize("geometry, causal, fwd, bwd, masks", [
        ("one_pair", False, "single_block", "single_block", "none"),
        ("one_pair", True, "single_block", "single_block", "causal"),
        ("one_key_block", False, "single_block", "blocked", "none"),
        ("blocks", False, "blocked", "blocked", "none"),
        ("ragged_sk", False, "blocked", "blocked", "ragged"),
        ("ragged_sq", True, "blocked", "blocked", "causal")])
    def test_lowerings_are_counted_by_the_body_taken(
            self, geometry, causal, fwd, bwd, masks):
        from paddle_tpu.observability import registry as obs
        counter = obs.counter("flash_attention_lowerings_total")
        labels = [{"pass": "fwd", "body": fwd, "masks": masks},
                  {"pass": "bwd", "body": bwd, "masks": masks}]
        before = [counter.value(**lb) for lb in labels]
        total = sum(counter.value(**dict(lb)) for lb in counter.labels_seen())
        self._grad_jaxpr(*self.GEOMETRY[geometry], causal=causal,
                         key_bias=False)
        assert [counter.value(**lb) for lb in labels] == [
            n + 1 for n in before]
        assert sum(counter.value(**dict(lb))
                   for lb in counter.labels_seen()) == total + 2
