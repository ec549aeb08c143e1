"""Sequence-op long tail + WMT loader tests (operators/sequence_ops/
breadth; python/paddle/dataset/wmt16 parse path)."""


import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import sequence as S


class TestSequenceConv:
    def test_matches_numpy(self):
        rng = np.random.default_rng(0)
        b, t, d, f, ctx = 3, 6, 4, 5, 3
        x = rng.normal(size=(b, t, d)).astype(np.float32)
        lengths = np.array([6, 4, 2])
        w = rng.normal(size=(ctx * d, f)).astype(np.float32)
        start = -1

        ref = np.zeros((b, t, f), np.float32)
        for bi in range(b):
            for ti in range(lengths[bi]):
                cat = []
                for j in range(ctx):
                    src = ti + start + j
                    if 0 <= src < lengths[bi]:
                        cat.append(x[bi, src])
                    else:
                        cat.append(np.zeros(d, np.float32))
                ref[bi, ti] = np.concatenate(cat) @ w
        out = S.sequence_conv(jnp.asarray(x), jnp.asarray(lengths),
                              jnp.asarray(w), context_start=start)
        np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-5,
                                   atol=1e-5)


class TestSequenceSlice:
    def test_2d(self):
        x = jnp.asarray([[1, 2, 3, 4, 5], [6, 7, 8, 0, 0]])
        lengths = jnp.asarray([5, 3])
        out, nl = S.sequence_slice(x, lengths, jnp.asarray([1, 0]),
                                   jnp.asarray([3, 2]))
        np.testing.assert_array_equal(np.asarray(out),
                                      [[2, 3, 4, 0, 0], [6, 7, 0, 0, 0]])
        np.testing.assert_array_equal(np.asarray(nl), [3, 2])

    def test_clamps_to_row_length(self):
        x = jnp.asarray([[1, 2, 3, 0]])
        out, nl = S.sequence_slice(x, jnp.asarray([3]), jnp.asarray([2]),
                                   jnp.asarray([4]))
        np.testing.assert_array_equal(np.asarray(out), [[3, 0, 0, 0]])
        assert int(nl[0]) == 1

    def test_3d(self):
        x = jnp.arange(24.0).reshape(2, 3, 4)
        out, nl = S.sequence_slice(x, jnp.asarray([3, 3]),
                                   jnp.asarray([1, 0]),
                                   jnp.asarray([2, 1]))
        np.testing.assert_allclose(np.asarray(out)[0, 0], np.asarray(x)[0, 1])
        assert np.allclose(np.asarray(out)[0, 2], 0.0)


class TestSequenceErase:
    def test_erase_and_compact(self):
        x = jnp.asarray([[2, 1, 2, 3, 0], [5, 5, 5, 0, 0]])
        lengths = jnp.asarray([4, 3])
        out, nl = S.sequence_erase(x, lengths, [2, 5])
        np.testing.assert_array_equal(np.asarray(out),
                                      [[1, 3, 0, 0, 0], [0, 0, 0, 0, 0]])
        np.testing.assert_array_equal(np.asarray(nl), [2, 0])

    def test_padding_not_counted(self):
        # pad value 0 is outside every valid prefix; erasing 0 is a no-op
        x = jnp.asarray([[1, 2, 0, 0]])
        out, nl = S.sequence_erase(x, jnp.asarray([2]), [0])
        np.testing.assert_array_equal(np.asarray(out), [[1, 2, 0, 0]])
        assert int(nl[0]) == 2


class TestSequenceEnumerate:
    def test_windows(self):
        x = jnp.asarray([[1, 2, 3, 4]])
        out = S.sequence_enumerate(x, jnp.asarray([3]), 2, pad_value=9)
        np.testing.assert_array_equal(
            np.asarray(out)[0], [[1, 2], [2, 3], [3, 9], [9, 9]])


class TestSequenceConcat:
    def test_ragged_concat(self):
        x = jnp.asarray([[1, 2, 0], [3, 0, 0]])
        y = jnp.asarray([[7, 8], [9, 0]])
        out, nl = S.sequence_concat(x, jnp.asarray([2, 1]), y,
                                    jnp.asarray([2, 1]))
        np.testing.assert_array_equal(np.asarray(out),
                                      [[1, 2, 7, 8, 0], [3, 9, 0, 0, 0]])
        np.testing.assert_array_equal(np.asarray(nl), [4, 2])


class TestWmtLoader:
    def test_parallel_reader(self, tmp_path):
        from paddle_tpu.data.datasets import wmt_build_dict, wmt_parallel

        (tmp_path / "train.en").write_text("a b c\nb c\n")
        (tmp_path / "train.de").write_text("x y\ny z w\n")
        reader = wmt_parallel(str(tmp_path))
        pairs = list(reader())
        assert len(pairs) == 2
        s0, t0 = pairs[0]
        assert s0.dtype == np.int64 and len(s0) == 3 and len(t0) == 2
        # vocab is frequency-sorted: 'b'/'c' (2x) before 'a' (1x)
        d = wmt_build_dict([str(tmp_path / "train.en")])
        assert d["b"] < d["a"] and d["c"] < d["a"]
        assert "<unk>" in d

    def test_missing_files(self, tmp_path):
        from paddle_tpu.data.datasets import wmt_parallel

        with pytest.raises(FileNotFoundError, match="stage"):
            wmt_parallel(str(tmp_path))
