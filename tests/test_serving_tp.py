"""Tensor-parallel paged serving (ISSUE 15).

The acceptance battery for the tp-sharded engine: greedy tokens
IDENTICAL to the tp=1 engine (fp + int8, prefix sharing on/off), zero
steady-state recompiles with tp on, bucket-coverage proof for the
sharded warmup plan, per-shard migration byte-parity through a
mid-decode drain, and the mesh shape surfacing through ``health()`` and
the fleet router. The sharded step runs the plain paged kernels on each
shard's heads; that a head shard through them equals those heads of the
whole call is ``test_kernels.py::TestHeadShardsAreIndependent``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import observability as obs
from paddle_tpu import serving
from paddle_tpu.models.gpt import GPT, GPTConfig

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 4, reason="tp tests need >= 4 (virtual) devices")


@pytest.fixture(scope="module")
def tiny_model():
    cfg = GPTConfig.tiny(num_heads=4, hidden_size=32, max_position=128)
    model = GPT(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return model, params


@pytest.fixture(scope="module")
def prompts():
    rng = np.random.default_rng(0)
    return [rng.integers(1, 128, n).astype(np.int32)
            for n in (9, 17, 30, 5, 21)]


def make_engine(tiny_model, **kw):
    model, params = tiny_model
    kw.setdefault("num_slots", 4)
    kw.setdefault("page_size", 8)
    kw.setdefault("max_tokens_per_slot", 64)
    kw.setdefault("attn_impl", "lax")
    kw.setdefault("registry", obs.MetricsRegistry())
    return serving.ServingEngine(model, params, **kw)


def run_all(eng, prompts, cap=16, eos=7):
    return [np.asarray(t) for t in
            eng.generate_many(prompts, cap, eos_id=eos)]


# ---------------------------------------------------------------------------
# greedy parity: tp engine == tp=1 engine, token for token
# ---------------------------------------------------------------------------

class TestTpGreedyParity:
    def test_fp_tp2_and_tp4_match_tp1(self, tiny_model, prompts):
        base = run_all(make_engine(tiny_model), prompts)
        for tp in (2, 4):
            outs = run_all(make_engine(tiny_model, tp=tp), prompts)
            for a, b in zip(base, outs):
                np.testing.assert_array_equal(
                    a, b, err_msg=f"tp={tp} diverged from tp=1")

    @pytest.mark.slow
    def test_fp_tp2_sharing_off(self, tiny_model, prompts):
        base = run_all(make_engine(tiny_model, prefix_sharing=False),
                       prompts)
        outs = run_all(make_engine(tiny_model, tp=2,
                                   prefix_sharing=False), prompts)
        for a, b in zip(base, outs):
            np.testing.assert_array_equal(a, b)

    def test_fp_tp2_shared_prefix_traffic(self, tiny_model):
        # the prefix-sharing path (publication, mapping, CoW tails) must
        # stay exact over per-shard pools: a publisher wave commits the
        # shared system prompt's pages, then followers map them —
        # tp=2 vs tp=1
        rng = np.random.default_rng(3)
        sys_prompt = rng.integers(1, 128, 19).astype(np.int32)
        reqs = [np.concatenate([sys_prompt,
                                rng.integers(1, 128, n).astype(np.int32)])
                for n in (4, 9, 2, 6)]
        base_eng = make_engine(tiny_model)
        base = run_all(base_eng, [reqs[0]]) + run_all(base_eng, reqs[1:])
        tp_eng = make_engine(tiny_model, tp=2)
        outs = run_all(tp_eng, [reqs[0]]) + run_all(tp_eng, reqs[1:])
        for a, b in zip(base, outs):
            np.testing.assert_array_equal(a, b)
        # sharing actually engaged on the tp engine
        assert tp_eng.cache.shared_tokens_total > 0

    def test_int8_tp2_matches_int8_tp1(self, tiny_model, prompts):
        base_eng = make_engine(tiny_model, cache_dtype=jnp.int8)
        base = run_all(base_eng, prompts)
        tp_eng = make_engine(tiny_model, tp=2, cache_dtype=jnp.int8)
        outs = run_all(tp_eng, prompts)
        for a, b in zip(base, outs):
            np.testing.assert_array_equal(
                a, b, err_msg="int8 tp=2 diverged from int8 tp=1")
        # the pmax-completed per-token scales keep the STORED int8 rows
        # bit-identical; the scale rows agree to the last ulp (deeper
        # layers' inputs carry the psum's accumulation noise, which the
        # int8 rounding absorbs)
        for ent1, ent2 in zip(base_eng.cache.pages, tp_eng.cache.pages):
            np.testing.assert_array_equal(np.asarray(ent1[0]),
                                          np.asarray(ent2[0]))
            np.testing.assert_array_equal(np.asarray(ent1[1]),
                                          np.asarray(ent2[1]))
            for a1, a2 in zip(ent1[2:], ent2[2:]):
                np.testing.assert_allclose(np.asarray(a1),
                                           np.asarray(a2),
                                           rtol=1e-5, atol=1e-7)


# ---------------------------------------------------------------------------
# zero recompiles + bucket coverage + health, on ONE warmed tp engine
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def warmed_tp_engine(tiny_model):
    # small bucket plan (mp=4): the coverage/recompile proof is about
    # plan==reachable, not plan size
    eng = make_engine(tiny_model, tp=2, max_tokens_per_slot=32)
    eng.warmup()
    return eng


class TestTpSteadyState:
    def test_zero_recompiles_after_warmup(self, warmed_tp_engine,
                                          prompts):
        eng = warmed_tp_engine
        # requests sized to the fixture's 32-token slots
        run_all(eng, [p for p in prompts if len(p) <= 24], cap=8)
        assert eng.recompile_detector.recompiles == 0

    def test_bucket_coverage_plan_covers_reachable(self,
                                                   warmed_tp_engine):
        from paddle_tpu.analysis import hlo_lint
        assert hlo_lint.serving_bucket_coverage(warmed_tp_engine) == []
        # the proof has teeth: a doctored warmup plan missing one
        # decode bucket fires
        warmed = set(warmed_tp_engine.warmup_plan())
        dropped = next(s for s in warmed if s[0] == "decode")
        findings = hlo_lint.serving_bucket_coverage(
            warmed_tp_engine, warmed=warmed - {dropped})
        assert any(f.rule == "bucket-coverage" for f in findings)

    def test_health_reports_mesh_shape(self, warmed_tp_engine):
        h = warmed_tp_engine.health()
        assert h["tp"] == 2
        assert h["mesh_devices"] == 2

    def test_decode_step_lowers_one_all_reduce_a_layer(
            self, warmed_tp_engine, tiny_model):
        """The sharded decode step's whole collective set: the
        attention-output psum over the tp axis, once a layer (embedding
        and MLP are replicated and emit none)."""
        from paddle_tpu import analysis
        eng = warmed_tp_engine
        s_tot = eng.scheduler.num_slots
        zeros = jnp.zeros((s_tot,), jnp.int32)
        w = min(sig[1] for sig in eng.warmup_plan() if sig[0] == "decode")
        cost = analysis.estimate_cost(
            eng.decode_step, eng._step_params, eng.cache.pages,
            jnp.zeros((s_tot, w), jnp.int32), zeros, zeros, zeros,
            name="serving_decode_tp")
        assert [(c.kind, c.group_size) for c in cost.collectives] == [
            ("all_reduce", 2)] * tiny_model[0].cfg.num_layers

    def test_warmed_signatures_match_plan(self, warmed_tp_engine):
        assert warmed_tp_engine.warmed_signatures == set(
            warmed_tp_engine.warmup_plan())

    def test_pool_is_cut_between_heads_of_the_folded_axis(
            self, warmed_tp_engine, tiny_model):
        """The pool's folded H*Dh axis is head-major, so sharding it
        over tp hands each shard its own whole heads: shard ``t`` holds
        lanes ``[t*(H/tp)*Dh, (t+1)*(H/tp)*Dh)`` of every page."""
        from jax.sharding import PartitionSpec
        eng = warmed_tp_engine
        cfg = tiny_model[0].cfg
        c = eng.cache.config
        per_shard = (cfg.num_heads // 2) * c.head_dim
        for kp, vp in eng.cache.pages:
            for pool in (kp, vp):
                assert pool.shape == (c.num_pages, c.page_size,
                                      cfg.num_heads * c.head_dim)
                assert pool.sharding.spec == PartitionSpec(None, None, "tp")
                assert {s.data.shape for s in pool.addressable_shards} == {
                    (c.num_pages, c.page_size, per_shard)}
                for s in pool.addressable_shards:
                    assert s.index[2].start % per_shard == 0


# ---------------------------------------------------------------------------
# per-shard live migration
# ---------------------------------------------------------------------------

class TestTpMigration:
    def _mid_decode_snapshot(self, tiny_model, **kw):
        rng = np.random.default_rng(1)
        prompt = rng.integers(1, 128, 21).astype(np.int32)
        src = make_engine(tiny_model, num_slots=2,
                          max_tokens_per_slot=96, **kw)
        src.submit(prompt, 40)
        for _ in range(2):
            src.step()          # prefill + one decode block: mid-decode
        assert not src.scheduler.idle()
        return src, prompt

    def test_mid_decode_drain_byte_parity(self, tiny_model):
        src, prompt = self._mid_decode_snapshot(tiny_model, tp=2)
        snap = src.snapshot_slot(0)
        # shard-indexed manifest: one sha256 shard per (page, tp shard)
        assert sorted({m["tp_shard"] for m in snap["manifest"]}) == [0, 1]
        assert snap["geometry"]["tp"] == 2
        src.release_slot(0)
        dst = make_engine(tiny_model, num_slots=2,
                          max_tokens_per_slot=96, tp=2)
        nrid = dst.restore_slot(snap)
        done = {}
        while not dst.scheduler.idle():
            done.update(dst.step())
        clean = make_engine(tiny_model, num_slots=2,
                            max_tokens_per_slot=96,
                            tp=2).generate_many([prompt], 40)[0]
        np.testing.assert_array_equal(done[nrid], clean)

    def test_corrupt_and_cross_tp_restores_refused(self, tiny_model):
        src, _ = self._mid_decode_snapshot(tiny_model, tp=2)
        snap = src.snapshot_slot(0)
        # a tp=1 engine refuses the tp=2 shard layout outright
        dst1 = make_engine(tiny_model, num_slots=2,
                           max_tokens_per_slot=96)     # tp=1
        with pytest.raises(serving.SlotMigrationError,
                           match="geometry mismatch"):
            dst1.restore_slot(snap)
        # a corrupted per-shard chunk is refused by its own hash
        snap["shards"][1] = np.zeros_like(np.asarray(snap["shards"][1]))
        dst2 = make_engine(tiny_model, num_slots=2,
                           max_tokens_per_slot=96, tp=2)
        with pytest.raises(serving.SlotMigrationError,
                           match="sha256 mismatch"):
            dst2.restore_slot(snap)

    @pytest.mark.slow
    def test_int8_tp_migration_parity(self, tiny_model):
        src, prompt = self._mid_decode_snapshot(tiny_model, tp=2,
                                                cache_dtype=jnp.int8)
        snap = src.snapshot_slot(0)
        src.release_slot(0)
        dst = make_engine(tiny_model, num_slots=2,
                          max_tokens_per_slot=96, tp=2,
                          cache_dtype=jnp.int8)
        nrid = dst.restore_slot(snap)
        done = {}
        while not dst.scheduler.idle():
            done.update(dst.step())
        clean = make_engine(
            tiny_model, num_slots=2, max_tokens_per_slot=96, tp=2,
            cache_dtype=jnp.int8).generate_many([prompt], 40)[0]
        np.testing.assert_array_equal(done[nrid], clean)


# ---------------------------------------------------------------------------
# configuration contracts + probe mode + fleet surfacing
# ---------------------------------------------------------------------------

class TestTpConfig:
    def test_tp_must_divide_heads(self, tiny_model):
        with pytest.raises(ValueError, match="divide num_heads"):
            make_engine(tiny_model, tp=3)

    def test_tp_refuses_speculative(self, tiny_model):
        model, params = tiny_model
        with pytest.raises(ValueError, match="speculative"):
            make_engine(tiny_model, tp=2, draft_model=model,
                        draft_params=params)

    def test_mesh_tp_disagreement_refused(self, tiny_model):
        from paddle_tpu.core.mesh import MeshConfig, make_mesh
        mesh = make_mesh(MeshConfig(tp=2), devices=jax.devices()[:2])
        with pytest.raises(ValueError, match="disagrees"):
            make_engine(tiny_model, mesh=mesh, tp=4)

    def test_tp_plan_adds_no_signature_kind(self, tiny_model):
        plain = {sig[0] for sig in make_engine(tiny_model).warmup_plan()}
        eng = make_engine(tiny_model, tp=2)
        assert {sig[0] for sig in eng.warmup_plan()} <= plain
        assert set(eng.warmup_plan()) == eng.reachable_signatures()

    def test_one_pool_and_one_param_tree(self, tiny_model):
        """Every device array the tp engine holds is a leaf of its
        page pool or of its (sharded) parameter tree: no second pool,
        no second copy of a shard's weights."""
        eng = make_engine(tiny_model, tp=2)
        assert eng.params is eng._step_params
        own = {id(x) for x in jax.tree_util.tree_leaves(
            (eng._step_params, eng.cache.pages))}
        extra = [(name, x.shape) for name, v in vars(eng).items()
                 if name != "cache"
                 for x in jax.tree_util.tree_leaves(v)
                 if isinstance(x, jax.Array) and id(x) not in own]
        assert extra == []

    def test_tp_params_round_trip(self, tiny_model):
        model, params = tiny_model
        program = model.serving(tp=2)
        tree = program.tp_params(params)
        d = model.cfg.hidden_size
        for name, bp in params["blocks"].items():
            got, want = tree["blocks"][name]["attn"], bp["attn"]
            assert set(got) == {"qkv_tp", "out_tp"}
            np.testing.assert_array_equal(
                np.asarray(got["qkv_tp"]["weight"]).reshape(d, 3 * d),
                np.asarray(want["qkv_proj"]["weight"]))
            np.testing.assert_array_equal(
                np.asarray(got["qkv_tp"]["bias"]).reshape(3 * d),
                np.asarray(want["qkv_proj"]["bias"]))
            np.testing.assert_array_equal(
                np.asarray(got["out_tp"]["weight"]).reshape(d, d),
                np.asarray(want["out_proj"]["weight"]))
            np.testing.assert_array_equal(
                np.asarray(got["out_tp"]["bias"]),
                np.asarray(want["out_proj"]["bias"]))
            assert tree["blocks"][name]["mlp"] is bp["mlp"]
        # every leaf of the tree has a spec under the program's plan
        specs = program.tp_plan().params_specs(tree)
        assert jax.tree_util.tree_structure(specs) == \
            jax.tree_util.tree_structure(tree)

    def test_program_without_tp_refused_before_layout(self, tiny_model):
        """``supports`` decides, by name, before the engine asks the
        program for a tp layout it does not have."""
        import dataclasses
        model, params = tiny_model

        class NoTp:
            def __init__(self, prog):
                self.spec = dataclasses.replace(
                    prog.spec, supports=prog.spec.supports - {"tp"})

            def tp_params(self, params):
                raise AssertionError("asked for a tp layout")

        class Model:
            def serving(self, **kw):
                return NoTp(model.serving(**kw))

        with pytest.raises(ValueError, match="does not serve with 'tp'"):
            serving.ServingEngine(Model(), params, tp=2)

    def test_quantize_kv_psum_axis_matches_global(self):
        from paddle_tpu.core.compat import shard_map
        from paddle_tpu.core.mesh import MeshConfig, make_mesh
        from paddle_tpu.serving.paged_cache import quantize_kv
        mesh = make_mesh(MeshConfig(tp=2), devices=jax.devices()[:2])
        x = jax.random.normal(jax.random.PRNGKey(0), (3, 4, 8))
        qg, sg = quantize_kv(x, (1, 2))
        from jax.sharding import PartitionSpec as P
        qs, ss = shard_map(
            lambda xl: quantize_kv(xl, (1, 2), psum_axis="tp"),
            mesh=mesh, in_specs=P(None, "tp", None),
            out_specs=(P(None, "tp", None), P()),
            check_vma=False)(x)
        np.testing.assert_array_equal(np.asarray(qs), np.asarray(qg))
        np.testing.assert_array_equal(np.asarray(ss), np.asarray(sg))

    def test_fleet_health_reports_chips(self, tiny_model):
        from paddle_tpu.serving import fleet
        reg = obs.MetricsRegistry()
        reps = [fleet.LocalReplica(make_engine(tiny_model, tp=2),
                                   name="tp2"),
                fleet.LocalReplica(make_engine(tiny_model),
                                   name="plain")]
        router = fleet.FleetRouter(reps, registry=reg)
        h = router.health()
        assert h["chips_total"] == 3
        assert h["per_replica"]["tp2"]["mesh_devices"] == 2
        assert h["per_replica"]["plain"]["mesh_devices"] == 1
