"""Aux-subsystem tail: recommender book model + movielens/uci_housing
loaders, chrome-trace export (tools/timeline.py parity), program printer
(debugger.py parity), QAT transform (slim QuantizationTransformPass
parity)."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest


class TestRecommender:
    def _batch(self, reader, n=64):
        rows = []
        for i, row in enumerate(reader()):
            rows.append(row)
            if i + 1 == n:
                break
        cols = list(zip(*rows))
        return [jnp.asarray(np.stack(c)) for c in cols]

    def test_trains_on_movielens_schema(self):
        from paddle_tpu.data.datasets import movielens
        from paddle_tpu.models.book import RecommenderSystem
        from paddle_tpu import optimizer as opt
        from paddle_tpu.train import build_train_step, make_train_state

        model = RecommenderSystem(n_users=101, n_movies=201, dim=16)
        uid, g, a, o, mid, cat, rating = self._batch(movielens())
        batch = dict(user_id=uid, gender=g, age=a, occupation=o,
                     movie_id=mid, categories=cat, rating=rating)
        optimizer = opt.Adam(learning_rate=1e-2)
        step = jax.jit(build_train_step(
            lambda p, **b: model.loss(p, **b), optimizer))
        state = make_train_state(model, optimizer, jax.random.PRNGKey(0))
        losses = []
        for _ in range(8):
            state, m = step(state, **batch)
            losses.append(float(m["loss"]))
        assert losses[-1] < losses[0]
        assert np.isfinite(losses).all()

    def test_movielens_real_format(self, tmp_path):
        (tmp_path / "users.dat").write_text(
            "1::F::1::10::48067\n2::M::56::16::70072\n")
        (tmp_path / "movies.dat").write_text(
            "1::Toy Story (1995)::Animation|Children's|Comedy\n"
            "2::Jumanji (1995)::Adventure\n")
        (tmp_path / "ratings.dat").write_text(
            "1::1::5::978300760\n2::2::3::978299026\n"
            "1::2::4::978301968\n2::1::1::978300275\n")
        from paddle_tpu.data.datasets import movielens
        rows = list(movielens(str(tmp_path), split="train")())
        assert len(rows) == 3          # 10% (>=1) held out
        uid, gender, age, occ, mid, cat, rating = rows[0]
        assert int(uid) == 1 and int(gender) == 1 and int(age) == 0
        assert cat.shape == (18,) and cat.sum() == 3
        assert rating == 5.0
        test_rows = list(movielens(str(tmp_path), split="test")())
        assert len(test_rows) == 1

    def test_uci_housing(self, tmp_path):
        rng = np.random.RandomState(0)
        data = rng.rand(50, 14)
        lines = "\n".join(" ".join(f"{v:.4f}" for v in row)
                          for row in data)
        (tmp_path / "housing.data").write_text(lines)
        from paddle_tpu.data.datasets import uci_housing
        rows = list(uci_housing(str(tmp_path), split="train")())
        t_rows = list(uci_housing(str(tmp_path), split="test")())
        assert len(rows) == 40 and len(t_rows) == 10
        x = np.stack([r[0] for r in rows + t_rows])
        assert x.shape == (50, 13)
        # synthetic fallback works without files
        assert len(list(uci_housing(None)())) > 100


class TestChromeTrace:
    def test_trace_file_valid(self, tmp_path):
        from paddle_tpu import profiler
        path = str(tmp_path / "trace.json")
        with profiler.profile_to_chrome_trace(path):
            with profiler.record_event("stepA"):
                jnp.ones((4, 4)).sum().block_until_ready()
            with profiler.record_event("stepB"):
                pass
        trace = json.load(open(path))
        names = [e["name"] for e in trace["traceEvents"]]
        assert names == ["stepA", "stepB"]
        for e in trace["traceEvents"]:
            assert e["ph"] == "X" and e["dur"] >= 0 and e["ts"] >= 0

    def test_summary_still_works(self, capsys):
        from paddle_tpu import profiler
        with profiler.profiler(summary=True):
            with profiler.record_event("x"):
                pass
        out = capsys.readouterr().out
        assert "x" in out and "Calls" in out


class TestProgramPrinter:
    def test_jaxpr_and_hlo(self, capsys):
        from paddle_tpu.debug import print_program
        f = lambda x: jnp.tanh(x) @ x
        text = print_program(f, jnp.ones((3, 3)))
        assert "tanh" in text and "dot_general" in text
        hlo = print_program(f, jnp.ones((3, 3)), stage="hlo")
        assert "stablehlo" in hlo or "HloModule" in hlo or "func" in hlo

    def test_dot_export(self):
        from paddle_tpu.debug import program_to_dot
        dot = program_to_dot(lambda x: jnp.tanh(x).sum(), jnp.ones((4,)))
        assert dot.startswith("digraph")
        assert "tanh" in dot and "->" in dot

    def test_stage_validation(self):
        from paddle_tpu.debug import print_program
        with pytest.raises(ValueError):
            print_program(lambda x: x, jnp.ones(()), stage="nope")


class TestQAT:
    def _setup(self):
        from paddle_tpu.models.lenet import LeNet
        from paddle_tpu.ops import nn as ops_nn
        model = LeNet(num_classes=4)
        params = model.init(jax.random.PRNGKey(0))
        rng = np.random.RandomState(0)
        batch = dict(
            image=jnp.asarray(rng.randn(4, 28, 28, 1).astype(np.float32)),
            label=jnp.asarray(rng.randint(0, 4, (4,))))

        def loss_fn(p, image, label):
            logits = model(p, image)
            return ops_nn.softmax_with_cross_entropy(
                logits, label[:, None]).mean(), {}

        return loss_fn, params, batch

    def test_qat_quantizes_forward_but_grads_flow(self):
        from paddle_tpu import slim
        loss_fn, params, batch = self._setup()
        qfn = slim.qat_transform(loss_fn, bit_length=8)
        (loss, _), grads = jax.value_and_grad(qfn, has_aux=True)(
            params, **batch)
        assert np.isfinite(float(loss))
        flat = jax.tree_util.tree_leaves(grads)
        assert all(np.isfinite(np.asarray(g)).all() for g in flat)
        assert sum(float(np.abs(np.asarray(g)).sum()) for g in flat) > 0

    def test_qat_matches_eval_on_converted_weights(self):
        from paddle_tpu import slim
        loss_fn, params, batch = self._setup()
        qparams = slim.qat_convert(params, bit_length=8)
        qat_loss, _ = slim.qat_transform(loss_fn, bit_length=8)(
            params, **batch)
        frozen_loss, _ = loss_fn(qparams, **batch)
        assert float(qat_loss) == pytest.approx(float(frozen_loss),
                                                rel=1e-5)

    def test_convert_changes_weights_to_grid(self):
        from paddle_tpu import slim
        _, params, _ = self._setup()
        q = slim.qat_convert(params, bit_length=8)
        leaf = np.asarray(params["conv_pool1"]["conv"]["weight"])
        qleaf = np.asarray(q["conv_pool1"]["conv"]["weight"])
        assert qleaf.shape == leaf.shape
        # values snapped to a 2^7-step grid of the abs-max scale
        scale = float(np.abs(leaf).max()) / 127.0
        steps = qleaf / scale
        np.testing.assert_allclose(steps, np.round(steps), atol=1e-4)


class TestReviewRegressions:
    def test_uci_housing_zero_test_fraction(self, tmp_path):
        rng = np.random.RandomState(0)
        lines = "\n".join(" ".join(f"{v:.4f}" for v in row)
                          for row in rng.rand(10, 14))
        (tmp_path / "housing.data").write_text(lines)
        from paddle_tpu.data.datasets import uci_housing
        rows = list(uci_housing(str(tmp_path), split="train",
                                test_fraction=0.0)())
        assert len(rows) == 10               # train keeps everything
        assert list(uci_housing(str(tmp_path), split="test",
                                test_fraction=0.0)()) == []

    def test_movielens_gzipped(self, tmp_path):
        import gzip
        with gzip.open(tmp_path / "users.dat.gz", "wt") as f:
            f.write("1::F::1::10::48067\n")
        with gzip.open(tmp_path / "movies.dat.gz", "wt") as f:
            f.write("1::Toy Story (1995)::Comedy\n")
        with gzip.open(tmp_path / "ratings.dat.gz", "wt") as f:
            f.write("1::1::5::978300760\n1::1::4::978300761\n")
        from paddle_tpu.data.datasets import movielens
        rows = list(movielens(str(tmp_path), split="train")())
        assert len(rows) == 1 and float(rows[0][-1]) == 5.0

    def test_qat_channel_wise_convert_matches_training_grid(self):
        from paddle_tpu import slim
        loss_fn, params, batch = self._qat_setup()
        q = slim.qat_convert(params, channel_wise=True)
        tr_loss, _ = slim.qat_transform(loss_fn, channel_wise=True)(
            params, **batch)
        frozen_loss, _ = loss_fn(q, **batch)
        assert float(tr_loss) == pytest.approx(float(frozen_loss),
                                               rel=1e-5)

    def _qat_setup(self):
        return TestQAT._setup(self)


class TestDebugTools:
    def test_op_frequency(self):
        from paddle_tpu.debug import op_frequency
        f = lambda x: jnp.tanh(x @ x).sum()
        freq = op_frequency(f, jnp.ones((4, 4)))
        assert freq["dot_general"] == 1 and freq["tanh"] == 1

    def test_op_frequency_nested(self):
        from paddle_tpu.debug import op_frequency

        def f(x):
            return jax.lax.scan(lambda c, _: (jnp.tanh(c), None), x,
                                None, length=3)[0]

        freq = op_frequency(f, jnp.ones((4,)))
        assert freq.get("tanh", 0) >= 1     # found inside the scan body

    def test_estimate_memory(self):
        from paddle_tpu.debug import estimate_memory
        m = estimate_memory(lambda x: (x @ x).sum(), jnp.ones((8, 8)))
        if m is not None:                   # backend-dependent
            assert m["argument_bytes"] == 8 * 8 * 4
            assert m["total_bytes"] > 0


class TestLSTMP:
    def test_projection_shapes_and_training(self):
        from paddle_tpu.nn.rnn import LSTMPCell, RNN
        cell = LSTMPCell(input_size=6, hidden_size=16, proj_size=4)
        rnn = RNN(cell)
        params = rnn.init(jax.random.PRNGKey(0))
        x = jnp.asarray(np.random.RandomState(0).randn(2, 5, 6),
                        jnp.float32)
        out, final = rnn(params, x)
        assert out.shape == (2, 5, 4)       # projected width
        r, c = final
        assert r.shape == (2, 4) and c.shape == (2, 16)
        g = jax.grad(lambda p: rnn(p, x)[0].sum())(params)
        flat = jax.tree_util.tree_leaves(g)
        assert all(np.isfinite(np.asarray(a)).all() for a in flat)


class TestInGraphMetricOps:
    def test_auc_matches_host_metric(self):
        from paddle_tpu.metrics import Auc
        from paddle_tpu.ops.metrics_ops import auc
        rng = np.random.RandomState(0)
        probs = rng.rand(500).astype(np.float32)
        labels = (probs + 0.3 * rng.randn(500) > 0.5).astype(np.float32)
        host = Auc(num_thresholds=511)
        host.update(probs, labels)
        k = 511
        a, pb, nb = jax.jit(auc)(jnp.asarray(probs), jnp.asarray(labels),
                                 jnp.zeros(k + 1), jnp.zeros(k + 1))
        assert float(a) == pytest.approx(host.eval(), abs=0.02)

    def test_auc_streaming_accumulates(self):
        from paddle_tpu.ops.metrics_ops import auc
        pb = nb = jnp.zeros(101)
        # perfect separation over two updates -> auc ~ 1
        a, pb, nb = auc(jnp.asarray([0.9, 0.1]), jnp.asarray([1.0, 0.0]),
                        pb, nb)
        a, pb, nb = auc(jnp.asarray([0.8, 0.2]), jnp.asarray([1.0, 0.0]),
                        pb, nb)
        assert float(a) > 0.95
        assert float(pb.sum()) == 2 and float(nb.sum()) == 2

    def test_precision_recall_stream(self):
        from paddle_tpu.ops.metrics_ops import precision_recall
        stats = jnp.zeros(3)
        (p, r, f1), stats = precision_recall(
            jnp.asarray([0.9, 0.8, 0.2]), jnp.asarray([1.0, 0.0, 1.0]),
            stats)
        assert float(p) == pytest.approx(0.5)
        assert float(r) == pytest.approx(0.5)
        (p2, r2, _), stats = precision_recall(
            jnp.asarray([0.9]), jnp.asarray([1.0]), stats)
        assert float(stats[0]) == 2.0     # tp accumulated


class TestAucDegenerate:
    def test_single_class_history_is_half(self):
        from paddle_tpu.ops.metrics_ops import auc
        a, pb, nb = auc(jnp.asarray([0.2, 0.4]), jnp.asarray([0.0, 0.0]),
                        jnp.zeros(65), jnp.zeros(65))
        assert float(a) == 0.5

    def test_lstmp_public_export(self):
        from paddle_tpu.nn import LSTMPCell
        assert LSTMPCell is not None


class TestExecutorDatasetPath:
    def _setup(self):
        from paddle_tpu import optimizer as opt
        from paddle_tpu.executor import Executor, Program
        from paddle_tpu.models.book import LinearRegression
        from paddle_tpu.train import build_train_step, make_train_state

        model = LinearRegression(in_features=13)
        optimizer = opt.SGD(learning_rate=0.05)
        step = build_train_step(
            lambda p, x, y: model.loss(p, x, y), optimizer)
        state = make_train_state(model, optimizer, jax.random.PRNGKey(0))
        prog = Program(fn=jax.jit(step), name="fit_a_line")
        return Executor(), prog, state

    def test_train_from_dataset_reader(self):
        from paddle_tpu.data.datasets import uci_housing
        exe, prog, state = self._setup()

        def feed_builder(samples):
            xs, ys = zip(*samples)
            return {"x": jnp.asarray(np.stack(xs)),
                    "y": jnp.asarray(np.stack(ys))}

        seen = []
        state, fetches = exe.train_from_dataset(
            prog, uci_housing(None), state, batch_size=32, epochs=2,
            feed_builder=feed_builder,
            fetch_handler=lambda i, f: seen.append(float(f["loss"])))
        assert len(seen) >= 20          # 404 rows / 32 * 2 epochs
        assert seen[-1] < seen[0]       # it actually trained

    def test_infer_from_dataset(self):
        from paddle_tpu.data.datasets import uci_housing
        from paddle_tpu.executor import Program
        from paddle_tpu.models.book import LinearRegression
        exe, prog, state = self._setup()

        def feed_builder(samples):
            xs, ys = zip(*samples)
            return {"x": jnp.asarray(np.stack(xs)),
                    "y": jnp.asarray(np.stack(ys))}

        outs = exe.infer_from_dataset(prog, uci_housing(None, "test"),
                                      state, batch_size=16,
                                      feed_builder=feed_builder)
        assert len(outs) >= 5
        assert all(np.isfinite(o[1]["loss"]) for o in
                   [(None, x) for x in outs])


class TestExecutorDatasetEdgeCases:
    def test_reader_without_feed_builder_rejected(self):
        from paddle_tpu.executor import _dataset_batches
        with pytest.raises(ValueError):
            list(_dataset_batches(lambda: iter([1, 2]), 2, None))

    def test_partial_tail_batch_kept_for_inference(self):
        from paddle_tpu.executor import _dataset_batches
        batches = list(_dataset_batches(
            lambda: iter(range(10)), 4, lambda s: {"n": len(s)}))
        assert [b["n"] for b in batches] == [4, 4, 2]
        dropped = list(_dataset_batches(
            lambda: iter(range(10)), 4, lambda s: {"n": len(s)},
            drop_last=True))
        assert [b["n"] for b in dropped] == [4, 4]


class TestTrainerPredict:
    def test_predict_collects_numpy(self):
        from paddle_tpu.models.lenet import LeNet
        from paddle_tpu.trainer import Trainer
        model = LeNet(num_classes=3)
        params = model.init(jax.random.PRNGKey(0))
        state = {"params": params}
        trainer = Trainer.__new__(Trainer)
        trainer.state = state
        step = jax.jit(lambda p, image: model(p, image))
        batches = [dict(image=jnp.zeros((2, 28, 28, 1))),
                   dict(image=jnp.ones((2, 28, 28, 1)))]
        outs = trainer.predict(step, batches)
        assert len(outs) == 2
        assert isinstance(outs[0], np.ndarray)
        assert outs[0].shape == (2, 3)
