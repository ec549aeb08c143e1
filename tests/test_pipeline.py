"""GPipe pipeline-parallel tests: schedule parity vs sequential stack.

Reference test analog: fluid pipeline tests run SectionWorkers over scope
queues; here the whole schedule is traced, so parity with the plain
sequential stack is exact.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
pytestmark = pytest.mark.slow  # excluded from the quick CI gate


from paddle_tpu.core.mesh import MeshConfig, make_mesh, mesh_context
from paddle_tpu.parallel.pipeline import (circular_pipeline, gpipe,
                                          microbatch,
                                          pipeline_bubble_fraction,
                                          stack_layer_params, unmicrobatch)


@pytest.fixture(scope="module")
def pp_mesh():
    return make_mesh(MeshConfig(pp=4, dp=2))


def _block(params, h, extra=None, mb_idx=None):
    h = jnp.tanh(h @ params["w"] + params["b"])
    if extra is not None:
        h = h + extra
    return h


def _make_layers(key, n_layers, dim):
    out = []
    for i in range(n_layers):
        k1, k2, key = jax.random.split(key, 3)
        out.append({"w": jax.random.normal(k1, (dim, dim)) * 0.3,
                    "b": jax.random.normal(k2, (dim,)) * 0.1})
    return out


class TestGPipe:
    def test_matches_sequential(self, pp_mesh):
        layers = _make_layers(jax.random.PRNGKey(0), 8, 16)
        stacked = stack_layer_params(layers)
        x = jax.random.normal(jax.random.PRNGKey(1), (12, 4, 16))  # M=12 mbs

        ref = x
        for p in layers:
            ref = _block(p, ref)

        with mesh_context(pp_mesh):
            out = jax.jit(lambda sp, x: gpipe(
                _block, sp, x, mesh=pp_mesh))(stacked, x)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5, rtol=1e-5)

    def test_grads_match_sequential(self, pp_mesh):
        layers = _make_layers(jax.random.PRNGKey(2), 4, 8)
        stacked = stack_layer_params(layers)
        x = jax.random.normal(jax.random.PRNGKey(3), (8, 2, 8))

        def loss_pipe(sp):
            return gpipe(_block, sp, x, mesh=pp_mesh).sum()

        def loss_seq(sp):
            def body(h, lp):
                return _block(lp, h), None
            h, _ = jax.lax.scan(body, x, sp)
            return h.sum()

        with mesh_context(pp_mesh):
            g_pipe = jax.jit(jax.grad(loss_pipe))(stacked)
        g_seq = jax.grad(loss_seq)(stacked)
        for a, b in zip(jax.tree_util.tree_leaves(g_pipe),
                        jax.tree_util.tree_leaves(g_seq)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-4, rtol=1e-4)

    def test_extras_ride_the_ring(self, pp_mesh):
        """Per-microbatch side inputs (attention-bias analog) must follow
        their microbatch through every stage."""
        layers = _make_layers(jax.random.PRNGKey(7), 4, 8)
        stacked = stack_layer_params(layers)
        x = jax.random.normal(jax.random.PRNGKey(8), (6, 2, 8))
        extra = jax.random.normal(jax.random.PRNGKey(9), (6, 2, 8))

        ref = x
        for p in layers:
            ref = _block(p, ref, extra)

        with mesh_context(pp_mesh):
            out = jax.jit(lambda sp, x, e: gpipe(
                _block, sp, x, extras=e, mesh=pp_mesh))(stacked, x, extra)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5, rtol=1e-5)

    def test_pp_sharded_extras_rejected(self, pp_mesh):
        """Extras are indexed locally and must be pp-replicated; a spec
        sharding them over the pp axis is a contract violation."""
        layers = _make_layers(jax.random.PRNGKey(7), 4, 8)
        stacked = stack_layer_params(layers)
        x = jnp.zeros((4, 2, 8))
        extra = jnp.zeros((4, 2, 8))
        from jax.sharding import PartitionSpec as P
        with mesh_context(pp_mesh):
            with pytest.raises(ValueError, match="pp-replicated"):
                gpipe(_block, stacked, x, extras=extra,
                      extras_spec=P("pp"), mesh=pp_mesh)

    def test_mb_idx_tracks_microbatch(self, pp_mesh):
        """The microbatch index delivered to the block must equal the true
        index of the microbatch being computed (dropout-PRNG contract)."""
        layers = _make_layers(jax.random.PRNGKey(0), 4, 4)
        stacked = stack_layer_params(layers)
        M = 6
        x = jnp.zeros((M, 1, 4))

        def block(p, h, extra, mb_idx):
            # write the index into the activation; every stage adds it, so
            # output = 4 * mb_idx if indices are delivered correctly
            return h + mb_idx.astype(h.dtype)

        with mesh_context(pp_mesh):
            out = jax.jit(lambda sp, x: gpipe(
                block, sp, x, mesh=pp_mesh))(stacked, x)
        expect = 4.0 * jnp.arange(M).reshape(M, 1, 1) * jnp.ones((M, 1, 4))
        np.testing.assert_allclose(np.asarray(out), np.asarray(expect))

    def test_circular_matches_gpipe_pp4(self, pp_mesh):
        """Interleaved 1F1B-circular schedule computes the same function
        as GPipe (pp=4, v=2, L=8, M=8)."""
        layers = _make_layers(jax.random.PRNGKey(10), 8, 16)
        stacked = stack_layer_params(layers)
        x = jax.random.normal(jax.random.PRNGKey(11), (8, 4, 16))

        with mesh_context(pp_mesh):
            ref = jax.jit(lambda sp, x: gpipe(
                _block, sp, x, mesh=pp_mesh))(stacked, x)
            out = jax.jit(lambda sp, x: circular_pipeline(
                _block, sp, x, num_circuits=2, mesh=pp_mesh))(stacked, x)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5, rtol=1e-5)

    def test_circular_matches_gpipe_pp2(self):
        mesh = make_mesh(MeshConfig(pp=2, dp=4))
        layers = _make_layers(jax.random.PRNGKey(12), 8, 8)
        stacked = stack_layer_params(layers)
        x = jax.random.normal(jax.random.PRNGKey(13), (8, 2, 8))
        with mesh_context(mesh):
            ref = jax.jit(lambda sp, x: gpipe(
                _block, sp, x, mesh=mesh))(stacked, x)
            out = jax.jit(lambda sp, x: circular_pipeline(
                _block, sp, x, num_circuits=4, mesh=mesh))(stacked, x)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5, rtol=1e-5)

    def test_circular_grads_match_sequential(self, pp_mesh):
        layers = _make_layers(jax.random.PRNGKey(14), 8, 8)
        stacked = stack_layer_params(layers)
        x = jax.random.normal(jax.random.PRNGKey(15), (8, 2, 8))

        def loss_circ(sp):
            return circular_pipeline(_block, sp, x, num_circuits=2,
                                     mesh=pp_mesh).sum()

        def loss_seq(sp):
            def body(h, lp):
                return _block(lp, h), None
            h, _ = jax.lax.scan(body, x, sp)
            return h.sum()

        with mesh_context(pp_mesh):
            g_circ = jax.jit(jax.grad(loss_circ))(stacked)
        g_seq = jax.grad(loss_seq)(stacked)
        for a, b in zip(jax.tree_util.tree_leaves(g_circ),
                        jax.tree_util.tree_leaves(g_seq)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-4, rtol=1e-4)

    def test_circular_extras_and_mb_idx(self, pp_mesh):
        """Extras and microbatch indices stay glued to their microbatch
        across all circuits of the ring."""
        layers = _make_layers(jax.random.PRNGKey(16), 8, 4)
        stacked = stack_layer_params(layers)
        M = 8
        x = jnp.zeros((M, 1, 4))
        extras = 100.0 * jnp.arange(M, dtype=jnp.float32)

        def block(p, h, extra, mb_idx):
            # every chunk-layer adds extra + mb; 8 layers total
            return h + extra + mb_idx.astype(h.dtype)

        with mesh_context(pp_mesh):
            out = jax.jit(lambda sp, x, e: circular_pipeline(
                block, sp, x, num_circuits=2, extras=e,
                mesh=pp_mesh))(stacked, x, extras)
        expect = (8.0 * (100.0 * jnp.arange(M) + jnp.arange(M))
                  ).reshape(M, 1, 1) * jnp.ones((M, 1, 4))
        np.testing.assert_allclose(np.asarray(out), np.asarray(expect))

    def test_interleave_roundtrip_and_pre_interleaved(self, pp_mesh):
        """interleave_stack/uninterleave_stack invert each other, and a
        pre-interleaved layout (the recommended no-reshuffle path) gives
        the same result as arranging inside the step."""
        from paddle_tpu.parallel.pipeline import (interleave_stack,
                                                  uninterleave_stack)
        layers = _make_layers(jax.random.PRNGKey(20), 8, 8)
        stacked = stack_layer_params(layers)
        arranged = interleave_stack(stacked, 4, 2)
        back = uninterleave_stack(arranged, 4, 2)
        for a, b in zip(jax.tree_util.tree_leaves(back),
                        jax.tree_util.tree_leaves(stacked)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

        x = jax.random.normal(jax.random.PRNGKey(21), (8, 2, 8))
        with mesh_context(pp_mesh):
            out1 = jax.jit(lambda sp, x: circular_pipeline(
                _block, sp, x, num_circuits=2, mesh=pp_mesh))(stacked, x)
            out2 = jax.jit(lambda sp, x: circular_pipeline(
                _block, sp, x, num_circuits=2, mesh=pp_mesh,
                pre_interleaved=True))(arranged, x)
        np.testing.assert_allclose(np.asarray(out1), np.asarray(out2),
                                   atol=1e-6, rtol=1e-6)

    def test_circular_rejects_short_streams(self, pp_mesh):
        layers = _make_layers(jax.random.PRNGKey(17), 8, 4)
        stacked = stack_layer_params(layers)
        with mesh_context(pp_mesh):
            with pytest.raises(ValueError, match="microbatches"):
                circular_pipeline(_block, stacked, jnp.zeros((2, 1, 4)),
                                  num_circuits=2, mesh=pp_mesh)

    def test_bubble_fraction_beats_gpipe(self):
        """The interleaved schedule's structural bubble is strictly below
        GPipe's for every v > 1."""
        for n, M in [(2, 8), (4, 8), (4, 16)]:
            g = pipeline_bubble_fraction(n, M, 1)
            for v in (2, 4):
                c = pipeline_bubble_fraction(n, M, v)
                assert c < g, (n, M, v, c, g)
        # exact values: pp=4, M=8 -> GPipe 3/11, circular v=2 -> 3/19
        assert abs(pipeline_bubble_fraction(4, 8, 1) - 3 / 11) < 1e-12
        assert abs(pipeline_bubble_fraction(4, 8, 2) - 3 / 19) < 1e-12

    def test_circular_ticks_are_cheaper_than_gpipe_ticks(self, pp_mesh):
        """Wall-clock check of the schedules (tools/PIPELINE_TIMING.md):
        circular ticks apply 1/v of a GPipe stage's layers, so measured
        per-tick time must be strictly lower — the robust wall-clock
        property on any backend (full circ-beats-gpipe step time needs
        per-tick overhead << chunk compute, true on ICI, not on the CPU
        thread-rendezvous backend; the model + measurements live in
        tools/pipeline_bench.py)."""
        import time
        n, v, L, M, dim, mb = 4, 2, 8, 8, 768, 8
        key = jax.random.PRNGKey(0)
        layers = _make_layers(key, L, dim)
        stacked = stack_layer_params(layers)
        x = jax.random.normal(jax.random.PRNGKey(1), (M, mb, dim))
        y = jax.random.normal(jax.random.PRNGKey(2), (M, mb, dim))

        def step_time(fn, params):
            def loss(sp, x, y):
                return jnp.mean((fn(sp, x) - y) ** 2)

            @jax.jit
            def step(sp, x, y):
                l, g = jax.value_and_grad(loss)(sp, x, y)
                return jax.tree_util.tree_map(
                    lambda p, gg: p - 1e-3 * gg, sp, g), l

            with mesh_context(pp_mesh):
                params, l = step(params, x, y)
                jax.block_until_ready(l)
                ts = []
                for _ in range(7):
                    t0 = time.perf_counter()
                    params, l = step(params, x, y)
                    jax.block_until_ready(l)
                    ts.append(time.perf_counter() - t0)
            return sorted(ts)[len(ts) // 2]

        t_g = step_time(
            lambda sp, x: gpipe(_block, sp, x, mesh=pp_mesh), stacked)
        from paddle_tpu.parallel.pipeline import interleave_stack
        t_c = step_time(
            lambda sp, x: circular_pipeline(
                _block, sp, x, num_circuits=v, mesh=pp_mesh,
                pre_interleaved=True),
            interleave_stack(stacked, n, v))
        ticks_g, ticks_c = M + n - 1, v * M + n - 1
        per_tick_g, per_tick_c = t_g / ticks_g, t_c / ticks_c
        # 5% slack: on heavily contended/low-core runners the per-tick
        # rendezvous overhead can eat most of the halved-compute margin
        assert per_tick_c < per_tick_g * 1.05, (
            f"circular per-tick {per_tick_c * 1e3:.2f}ms not below gpipe "
            f"{per_tick_g * 1e3:.2f}ms (steps: {t_c * 1e3:.1f} / "
            f"{t_g * 1e3:.1f}ms)")
        # and the full step must stay within the overhead-regime bound
        assert t_c < 2.0 * t_g

    def test_microbatch_roundtrip(self):
        batch = {"x": jnp.arange(24.0).reshape(12, 2)}
        mb = microbatch(batch, 4)
        assert mb["x"].shape == (4, 3, 2)
        back = unmicrobatch(mb)
        np.testing.assert_allclose(np.asarray(back["x"]),
                                   np.asarray(batch["x"]))

class TestBertPipelined:
    """BERT with the encoder run through gpipe over "pp", composed with
    dp+fsdp batch sharding — loss/grad parity vs the sequential encoder."""

    CFG = dict(vocab_size=64, hidden_size=16, num_layers=4, num_heads=2,
               ffn_size=32, max_position=32, dropout=0.0, attn_dropout=0.0,
               attn_impl="xla")

    def _models_and_batch(self):
        from paddle_tpu.models.bert import BertConfig, BertForPretraining

        m_ref = BertForPretraining(BertConfig.tiny(**self.CFG))
        # stacked_layers=False: these tests isolate the SCHEDULE by
        # feeding the same LayerList-layout params to both models (the
        # stacked layout has its own parity tests below)
        m_pp = BertForPretraining(BertConfig.tiny(
            **self.CFG, pipeline=True, pp_microbatches=4,
            stacked_layers=False))
        params = m_ref.init(jax.random.PRNGKey(0))
        b, s = 16, 16
        k1, k2 = jax.random.split(jax.random.PRNGKey(1))
        mask = jnp.arange(s)[None, :] < jax.random.randint(
            k2, (b, 1), s // 2, s + 1)           # ragged padding
        batch = dict(
            input_ids=jax.random.randint(k1, (b, s), 0, 64, jnp.int32),
            token_type_ids=jnp.zeros((b, s), jnp.int32),
            attention_mask=mask,
            mlm_labels=jnp.zeros((b, s), jnp.int32),
            mlm_mask=jnp.ones((b, s), jnp.float32),
            nsp_labels=jnp.zeros((b,), jnp.int32),
        )
        return m_ref, m_pp, params, batch

    def test_loss_and_grad_parity_pp_dp_fsdp(self):
        from paddle_tpu.core.mesh import MeshConfig, make_mesh

        mesh = make_mesh(MeshConfig(dp=2, fsdp=2, pp=2))
        m_ref, m_pp, params, batch = self._models_and_batch()

        def loss_ref(p):
            return m_ref.loss(p, training=False, **batch)[0]

        def loss_pp(p):
            return m_pp.loss(p, training=False, **batch)[0]

        l_ref, g_ref = jax.value_and_grad(loss_ref)(params)
        with mesh_context(mesh):
            l_pp, g_pp = jax.jit(jax.value_and_grad(loss_pp))(params)
        assert float(l_pp) == pytest.approx(float(l_ref), rel=1e-5)
        for a, b_ in zip(jax.tree_util.tree_leaves(g_pp),
                         jax.tree_util.tree_leaves(g_ref)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                       atol=2e-4, rtol=1e-3)

    def test_circular_schedule_loss_and_grad_parity(self):
        """BERT encoder through the interleaved 1F1B-circular schedule
        (pp=2, v=2, M=4) matches the sequential reference."""
        from paddle_tpu.core.mesh import MeshConfig, make_mesh
        from paddle_tpu.models.bert import BertConfig, BertForPretraining

        mesh = make_mesh(MeshConfig(dp=2, fsdp=2, pp=2))
        m_ref, _, params, batch = self._models_and_batch()
        m_circ = BertForPretraining(BertConfig.tiny(
            **self.CFG, pipeline=True, pp_microbatches=4,
            pp_schedule="circular", pp_circuits=2,
            stacked_layers=False))

        def loss_ref(p):
            return m_ref.loss(p, training=False, **batch)[0]

        def loss_circ(p):
            return m_circ.loss(p, training=False, **batch)[0]

        l_ref, g_ref = jax.value_and_grad(loss_ref)(params)
        with mesh_context(mesh):
            l_c, g_c = jax.jit(jax.value_and_grad(loss_circ))(params)
        assert float(l_c) == pytest.approx(float(l_ref), rel=1e-5)
        for a, b_ in zip(jax.tree_util.tree_leaves(g_c),
                         jax.tree_util.tree_leaves(g_ref)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                       atol=2e-4, rtol=1e-3)

    def test_circular_pre_interleaved_layout(self):
        """Stacked-layers BERT with params converted once via
        interleave_stack + pp_pre_interleaved=True (the no-per-step-
        reshuffle path) computes the same loss/grads as the in-step
        arrangement."""
        from paddle_tpu.core.mesh import MeshConfig, make_mesh
        from paddle_tpu.models.bert import BertConfig, BertForPretraining
        from paddle_tpu.parallel.pipeline import (interleave_stack,
                                                  uninterleave_stack)

        mesh = make_mesh(MeshConfig(dp=2, fsdp=2, pp=2))
        base = dict(self.CFG, pipeline=True, pp_microbatches=4,
                    pp_schedule="circular", pp_circuits=2)
        m = BertForPretraining(BertConfig.tiny(**base))
        m_pre = BertForPretraining(BertConfig.tiny(
            **base, pp_pre_interleaved=True))
        params = m.init(jax.random.PRNGKey(0))
        p_pre = dict(params)
        p_pre["bert"] = dict(params["bert"])
        p_pre["bert"]["encoder"] = interleave_stack(
            params["bert"]["encoder"], 2, 2)
        _, _, _, batch = self._models_and_batch()

        with mesh_context(mesh):
            l, g = jax.jit(jax.value_and_grad(
                lambda p: m.loss(p, training=False, **batch)[0]))(params)
            l2, g2 = jax.jit(jax.value_and_grad(
                lambda p: m_pre.loss(p, training=False, **batch)[0]))(p_pre)
        assert float(l2) == pytest.approx(float(l), rel=1e-5)
        g2["bert"]["encoder"] = uninterleave_stack(
            g2["bert"]["encoder"], 2, 2)
        for a, b_ in zip(jax.tree_util.tree_leaves(g2),
                         jax.tree_util.tree_leaves(g)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                       atol=2e-4, rtol=1e-3)

    def test_circular_pre_interleaved_dropout_keys(self):
        """training=True exercises the layer-key interleave branch: the
        pre-interleaved layout must sample the SAME dropout masks as the
        canonical layout (layer->key binding is layout-independent)."""
        from paddle_tpu.core.mesh import MeshConfig, make_mesh
        from paddle_tpu.models.bert import BertConfig, BertForPretraining
        from paddle_tpu.parallel.pipeline import interleave_stack

        mesh = make_mesh(MeshConfig(dp=2, fsdp=2, pp=2))
        base = dict(self.CFG, dropout=0.3, pipeline=True,
                    pp_microbatches=4, pp_schedule="circular",
                    pp_circuits=2)
        m = BertForPretraining(BertConfig.tiny(**base))
        m_pre = BertForPretraining(BertConfig.tiny(
            **base, pp_pre_interleaved=True))
        params = m.init(jax.random.PRNGKey(0))
        p_pre = dict(params)
        p_pre["bert"] = dict(params["bert"])
        p_pre["bert"]["encoder"] = interleave_stack(
            params["bert"]["encoder"], 2, 2)
        _, _, _, batch = self._models_and_batch()
        with mesh_context(mesh):
            l = jax.jit(lambda p, k: m.loss(
                p, training=True, key=k, **batch)[0])(
                    params, jax.random.PRNGKey(7))
            l2 = jax.jit(lambda p, k: m_pre.loss(
                p, training=True, key=k, **batch)[0])(
                    p_pre, jax.random.PRNGKey(7))
        assert float(l2) == pytest.approx(float(l), rel=1e-5)

    def test_pre_interleaved_rejected_under_gpipe(self):
        from paddle_tpu.core.mesh import MeshConfig, make_mesh
        from paddle_tpu.parallel.pipeline import gpipe_layer_stack

        mesh = make_mesh(MeshConfig(pp=2, dp=4))
        layers = _make_layers(jax.random.PRNGKey(30), 4, 4)
        with mesh_context(mesh):
            with pytest.raises(ValueError, match="wrong order"):
                gpipe_layer_stack(
                    lambda lp, h, e, k: _block(lp, h), layers,
                    jnp.zeros((8, 4)), num_microbatches=4,
                    schedule="gpipe", pre_interleaved=True)

    def test_dropout_under_pipeline(self):
        """training=True with dropout>0 exercises the per-layer key ride
        (fold_in of the microbatch index) inside the schedule."""
        from paddle_tpu.core.mesh import MeshConfig, make_mesh
        from paddle_tpu.models.bert import BertConfig, BertForPretraining

        cfg = dict(self.CFG, dropout=0.3)
        m = BertForPretraining(BertConfig.tiny(
            **cfg, pipeline=True, pp_microbatches=4,
            stacked_layers=False))
        params = m.init(jax.random.PRNGKey(0))
        _, _, _, batch = self._models_and_batch()
        mesh = make_mesh(MeshConfig(dp=2, fsdp=2, pp=2))
        with mesh_context(mesh):
            f = jax.jit(lambda p, k: m.loss(
                p, training=True, key=k, **batch)[0])
            l1 = float(f(params, jax.random.PRNGKey(1)))
            l2 = float(f(params, jax.random.PRNGKey(2)))
        assert np.isfinite(l1) and np.isfinite(l2)
        assert l1 != l2  # dropout really sampled

    def test_pp_composes_with_tp(self):
        """pp=2 x tp=2: stage params replicated over tp, attention/FFN
        constraints inert inside the shard_map — result must still match
        the sequential reference."""
        from paddle_tpu.core.mesh import MeshConfig, make_mesh

        mesh = make_mesh(MeshConfig(dp=2, tp=2, pp=2))
        m_ref, m_pp, params, batch = self._models_and_batch()

        def loss_ref(p):
            return m_ref.loss(p, training=False, **batch)[0]

        def loss_pp(p):
            return m_pp.loss(p, training=False, **batch)[0]

        l_ref = float(loss_ref(params))
        with mesh_context(mesh):
            l_pp = float(jax.jit(loss_pp)(params))
        assert l_pp == pytest.approx(l_ref, rel=1e-5)


class TestBertStackedLayers:
    """Scan-over-layers param layout (nn.module.StackedLayers): stacked
    (L, ...) leaves, pp-sharded from init."""

    CFG = dict(vocab_size=64, hidden_size=16, num_layers=4, num_heads=2,
               ffn_size=32, max_position=32, dropout=0.0, attn_dropout=0.0,
               attn_impl="xla")

    def test_stacked_forward_matches_layerlist(self):
        from paddle_tpu.models.bert import BertConfig, BertForPretraining

        m_list = BertForPretraining(BertConfig.tiny(**self.CFG))
        m_stk = BertForPretraining(BertConfig.tiny(
            **self.CFG, stacked_layers=True))
        from paddle_tpu.models.bert import stack_encoder_params
        params = m_list.init(jax.random.PRNGKey(0))
        sparams = stack_encoder_params(params, 4)
        ids = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, 64,
                                 jnp.int32)
        a = m_list(params, ids, training=False)
        b = m_stk(sparams, ids, training=False)
        for x, y in zip(a, b):
            np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                       atol=1e-5, rtol=1e-5)

    def test_stacked_init_shapes_and_shardings(self):
        from paddle_tpu.models.bert import BertConfig, BertForPretraining

        m = BertForPretraining(BertConfig.tiny(
            **self.CFG, stacked_layers=True))
        params = m.init(jax.random.PRNGKey(0))
        w = params["bert"]["encoder"]["ffn"]["fc1"]["weight"]
        assert w.shape[0] == 4                   # leading L dim
        specs = m.sharding_specs(params)
        s = specs["bert"]["encoder"]["ffn"]["fc1"]["weight"]
        assert tuple(s)[0] == "pp"               # stage axis from init
        assert "tp" in tuple(s)                  # template hint preserved

    def test_stacked_dropout_exact_parity_with_layerlist(self):
        """training=True with dropout: the scan path consumes keys[i+1]
        at step i exactly like the loop path, so outputs match EXACTLY
        given converted params (pins the key-ordering contract)."""
        from paddle_tpu.models.bert import (BertConfig, BertForPretraining,
                                            stack_encoder_params)

        cfg = dict(self.CFG, dropout=0.3)
        m_list = BertForPretraining(BertConfig.tiny(**cfg))
        m_stk = BertForPretraining(BertConfig.tiny(
            **cfg, stacked_layers=True))
        params = m_list.init(jax.random.PRNGKey(0))
        sparams = stack_encoder_params(params, 4)
        ids = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, 64,
                                 jnp.int32)
        key = jax.random.PRNGKey(7)
        a = m_list(params, ids, key=key, training=True)
        b = m_stk(sparams, ids, key=key, training=True)
        for x, y in zip(a, b):
            np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                       atol=1e-5, rtol=1e-5)
        # and dropout is actually live: different key -> different output
        c = m_stk(sparams, ids, key=jax.random.PRNGKey(8), training=True)
        assert not np.allclose(np.asarray(b[0]), np.asarray(c[0]))

    def test_unstack_roundtrip(self):
        from paddle_tpu.models.bert import (BertConfig, BertForPretraining,
                                            stack_encoder_params,
                                            unstack_encoder_params)

        m = BertForPretraining(BertConfig.tiny(**self.CFG))
        params = m.init(jax.random.PRNGKey(0))
        back = unstack_encoder_params(
            stack_encoder_params(params, 4), 4)
        for a, b in zip(jax.tree_util.tree_leaves(params),
                        jax.tree_util.tree_leaves(back)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_stacked_pipeline_trains_no_reshard(self):
        """Pipeline over natively pp-sharded stacked params: loss/grad
        parity vs the same params run sequentially (scan path)."""
        from paddle_tpu.core.mesh import MeshConfig, make_mesh
        from paddle_tpu.models.bert import BertConfig, BertForPretraining

        m_pp = BertForPretraining(BertConfig.tiny(
            **self.CFG, pipeline=True, pp_microbatches=4))
        m_seq = BertForPretraining(BertConfig.tiny(
            **self.CFG, stacked_layers=True))
        assert m_pp.cfg.stacked_layers        # defaults on with pipeline
        params = m_pp.init(jax.random.PRNGKey(0))
        b, s = 16, 16
        k1 = jax.random.PRNGKey(1)
        batch = dict(
            input_ids=jax.random.randint(k1, (b, s), 0, 64, jnp.int32),
            token_type_ids=jnp.zeros((b, s), jnp.int32),
            attention_mask=jnp.ones((b, s), bool),
            mlm_labels=jnp.zeros((b, s), jnp.int32),
            mlm_mask=jnp.ones((b, s), jnp.float32),
            nsp_labels=jnp.zeros((b,), jnp.int32),
        )
        mesh = make_mesh(MeshConfig(dp=2, fsdp=2, pp=2))
        l_seq, g_seq = jax.value_and_grad(
            lambda p: m_seq.loss(p, training=False, **batch)[0])(params)
        with mesh_context(mesh):
            l_pp, g_pp = jax.jit(jax.value_and_grad(
                lambda p: m_pp.loss(p, training=False, **batch)[0]))(params)
        assert float(l_pp) == pytest.approx(float(l_seq), rel=1e-5)
        for a, b_ in zip(jax.tree_util.tree_leaves(g_pp),
                         jax.tree_util.tree_leaves(g_seq)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                       atol=2e-4, rtol=1e-3)


class TestGPTPipelined:
    def test_gpt_pp_loss_and_grad_parity(self):
        """GPT with the block stack through gpipe (pp=2 x dp x fsdp) vs
        the sequential stack — loss/grad parity."""
        from paddle_tpu.core.mesh import MeshConfig, make_mesh
        from paddle_tpu.models.gpt import GPT, GPTConfig

        cfg = dict(vocab_size=64, hidden_size=16, num_layers=4,
                   num_heads=2, ffn_size=32, max_position=32,
                   dropout=0.0, attn_impl="xla")
        m_ref = GPT(GPTConfig.tiny(**cfg))
        m_pp = GPT(GPTConfig.tiny(**cfg, pipeline=True,
                                  pp_microbatches=4,
                                  stacked_layers=False))
        params = m_ref.init(jax.random.PRNGKey(0))
        ids = jax.random.randint(jax.random.PRNGKey(1), (16, 17), 0, 64,
                                 jnp.int32)
        mesh = make_mesh(MeshConfig(dp=2, fsdp=2, pp=2))

        l_ref, g_ref = jax.value_and_grad(
            lambda p: m_ref.loss(p, ids, training=False)[0])(params)
        with mesh_context(mesh):
            l_pp, g_pp = jax.jit(jax.value_and_grad(
                lambda p: m_pp.loss(p, ids, training=False)[0]))(params)
        assert float(l_pp) == pytest.approx(float(l_ref), rel=1e-5)
        for a, b in zip(jax.tree_util.tree_leaves(g_pp),
                        jax.tree_util.tree_leaves(g_ref)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=2e-4, rtol=1e-3)

    def test_gpt_stacked_pipeline_parity(self):
        """GPT with natively-stacked blocks through the pipeline vs the
        same stacked params run through the scan path."""
        from paddle_tpu.core.mesh import MeshConfig, make_mesh
        from paddle_tpu.models.gpt import GPT, GPTConfig

        cfg = dict(vocab_size=64, hidden_size=16, num_layers=4,
                   num_heads=2, ffn_size=32, max_position=32,
                   dropout=0.0, attn_impl="xla")
        m_pp = GPT(GPTConfig.tiny(**cfg, pipeline=True,
                                  pp_microbatches=4))
        m_seq = GPT(GPTConfig.tiny(**cfg, stacked_layers=True))
        assert m_pp.cfg.stacked_layers
        params = m_pp.init(jax.random.PRNGKey(0))
        assert params["blocks"]["attn"]["qkv_proj"]["weight"].shape[0] == 4
        ids = jax.random.randint(jax.random.PRNGKey(1), (16, 17), 0, 64,
                                 jnp.int32)
        l_seq = float(m_seq.loss(params, ids, training=False)[0])
        mesh = make_mesh(MeshConfig(dp=2, fsdp=2, pp=2))
        with mesh_context(mesh):
            l_pp = float(jax.jit(
                lambda p: m_pp.loss(p, ids, training=False)[0])(params))
        assert l_pp == pytest.approx(l_seq, rel=1e-5)

    def test_gpt_pp_trains_with_dropout(self):
        from paddle_tpu import optimizer as opt
        from paddle_tpu.core.mesh import MeshConfig, make_mesh
        from paddle_tpu.models.gpt import GPT, GPTConfig
        from paddle_tpu.train import build_train_step, make_train_state

        cfg = GPTConfig.tiny(num_layers=4, dropout=0.1, attn_impl="xla",
                             pipeline=True, pp_microbatches=2)
        model = GPT(cfg)
        optimizer = opt.Adam(learning_rate=3e-3)
        mesh = make_mesh(MeshConfig(dp=2, fsdp=2, pp=2))
        ids = jax.random.randint(jax.random.PRNGKey(1), (8, 17), 0,
                                 cfg.vocab_size, jnp.int32)
        with mesh_context(mesh):
            state = make_train_state(model, optimizer,
                                     jax.random.PRNGKey(0))
            step = jax.jit(build_train_step(
                lambda p, ids, dropout_key: model.loss(
                    p, ids, key=dropout_key, training=True)[0],
                optimizer))
            losses = []
            for i in range(8):
                state, m = step(state, ids=ids,
                                dropout_key=jax.random.key(i))
                losses.append(float(m["loss"]))
        assert np.isfinite(losses).all()
        assert losses[-1] < losses[0]


class TestGPipeTraining:
    def test_train_step_through_pipeline(self, pp_mesh):
        """End-to-end: pipelined MLP regression learns under jit."""
        layers = _make_layers(jax.random.PRNGKey(4), 4, 8)
        stacked = stack_layer_params(layers)
        x = jax.random.normal(jax.random.PRNGKey(5), (8, 4, 8))
        y = jax.random.normal(jax.random.PRNGKey(6), (8, 4, 8))

        def loss_fn(sp):
            out = gpipe(_block, sp, x, mesh=pp_mesh)
            return ((out - y) ** 2).mean()

        with mesh_context(pp_mesh):
            step = jax.jit(jax.value_and_grad(loss_fn))
            params = stacked
            losses = []
            for _ in range(10):
                loss, g = step(params)
                params = jax.tree_util.tree_map(
                    lambda p, gr: p - 0.1 * gr, params, g)
                losses.append(float(loss))
        assert losses[-1] < losses[0]


class TestTransformerPipelined:
    """Seq2seq Transformer with encoder AND decoder stacks pipelined
    over "pp" — loss/grad parity vs the sequential stacks."""

    CFG = dict(dropout=0.0, attn_dropout=0.0, max_len=16,
               attn_impl="xla", label_smoothing=0.1,
               num_encoder_layers=4, num_decoder_layers=4)

    def _setup(self, **pp_kw):
        from paddle_tpu.models.transformer import (Transformer,
                                                   TransformerConfig)
        m_ref = Transformer(TransformerConfig.tiny(**self.CFG))
        m_pp = Transformer(TransformerConfig.tiny(
            **self.CFG, pipeline=True, pp_microbatches=4, **pp_kw))
        params = m_ref.init(jax.random.PRNGKey(0))
        rng = np.random.RandomState(0)
        src = jnp.asarray(rng.randint(3, 64, (16, 12)), jnp.int32)
        tgt_in = jnp.asarray(rng.randint(3, 64, (16, 10)), jnp.int32)
        tgt_out = jnp.asarray(rng.randint(3, 64, (16, 10)), jnp.int32)
        return m_ref, m_pp, params, (src, tgt_in, tgt_out)

    @pytest.mark.parametrize("schedule", ["gpipe", "circular"])
    def test_loss_and_grad_parity(self, schedule):
        from paddle_tpu.core.mesh import MeshConfig, make_mesh

        mesh = make_mesh(MeshConfig(dp=2, fsdp=2, pp=2))
        m_ref, m_pp, params, batch = self._setup(
            pp_schedule=schedule,
            pp_circuits=2 if schedule == "circular" else 1)

        def loss_ref(p):
            return m_ref.loss(p, *batch, training=False)[0]

        def loss_pp(p):
            return m_pp.loss(p, *batch, training=False)[0]

        l_ref, g_ref = jax.value_and_grad(loss_ref)(params)
        with mesh_context(mesh):
            l_pp, g_pp = jax.jit(jax.value_and_grad(loss_pp))(params)
        assert float(l_pp) == pytest.approx(float(l_ref), rel=1e-5)
        for a, b in zip(jax.tree_util.tree_leaves(g_pp),
                        jax.tree_util.tree_leaves(g_ref)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=2e-4, rtol=1e-3)

    def test_dropout_trains_under_pipeline(self):
        from paddle_tpu.core.mesh import MeshConfig, make_mesh
        from paddle_tpu.models.transformer import (Transformer,
                                                   TransformerConfig)

        cfg = dict(self.CFG, dropout=0.2)
        m = Transformer(TransformerConfig.tiny(
            **cfg, pipeline=True, pp_microbatches=4))
        params = m.init(jax.random.PRNGKey(1))
        rng = np.random.RandomState(1)
        src = jnp.asarray(rng.randint(3, 64, (16, 8)), jnp.int32)
        tgt_in = jnp.asarray(rng.randint(3, 64, (16, 8)), jnp.int32)
        tgt_out = jnp.asarray(rng.randint(3, 64, (16, 8)), jnp.int32)
        mesh = make_mesh(MeshConfig(dp=2, fsdp=2, pp=2))
        with mesh_context(mesh):
            f = jax.jit(lambda p, k: m.loss(
                p, src, tgt_in, tgt_out, training=True, key=k)[0])
            l1 = float(f(params, jax.random.PRNGKey(2)))
            l2 = float(f(params, jax.random.PRNGKey(3)))
        assert np.isfinite(l1) and np.isfinite(l2)
        assert l1 != l2
