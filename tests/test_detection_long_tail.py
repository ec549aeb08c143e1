"""Long-tail detection ops + real-format dataset loaders.

Reference parity targets: operators/detection/{grid_sampler, roi_pool,
anchor_generator}_op, multiclass_nms at reference-scale box counts, and
python/paddle/dataset/{mnist,cifar,imdb}.py parse paths (files staged
locally — zero egress).
"""

import gzip
import pickle
import struct
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import detection as D
from paddle_tpu.ops import nn as ops_nn


class TestGridSampler:
    def _numpy_ref(self, x, grid):
        """Plain-python bilinear ref: NCHW, align_corners, zero pad."""
        n, c, h, w = x.shape
        _, ho, wo, _ = grid.shape
        out = np.zeros((n, c, ho, wo), np.float32)
        for b in range(n):
            for i in range(ho):
                for j in range(wo):
                    gx = (grid[b, i, j, 0] + 1) * 0.5 * (w - 1)
                    gy = (grid[b, i, j, 1] + 1) * 0.5 * (h - 1)
                    x0, y0 = int(np.floor(gx)), int(np.floor(gy))
                    for dy in (0, 1):
                        for dx in (0, 1):
                            xi, yi = x0 + dx, y0 + dy
                            if 0 <= xi < w and 0 <= yi < h:
                                wgt = ((gx - x0 if dx else x0 + 1 - gx)
                                       * (gy - y0 if dy else y0 + 1 - gy))
                                out[b, :, i, j] += wgt * x[b, :, yi, xi]
        return out

    def test_matches_numpy_reference(self):
        rng = np.random.default_rng(0)
        x = rng.normal(size=(2, 3, 5, 7)).astype(np.float32)
        grid = rng.uniform(-1.2, 1.2, size=(2, 4, 6, 2)).astype(np.float32)
        out = ops_nn.grid_sampler(jnp.asarray(x), jnp.asarray(grid))
        np.testing.assert_allclose(np.asarray(out),
                                   self._numpy_ref(x, grid),
                                   rtol=1e-5, atol=1e-5)

    def test_identity_grid_reproduces_image(self):
        rng = np.random.default_rng(1)
        x = rng.normal(size=(1, 2, 8, 8)).astype(np.float32)
        ys, xs = np.meshgrid(np.linspace(-1, 1, 8), np.linspace(-1, 1, 8),
                             indexing="ij")
        grid = np.stack([xs, ys], -1)[None].astype(np.float32)
        out = ops_nn.grid_sampler(jnp.asarray(x), jnp.asarray(grid))
        np.testing.assert_allclose(np.asarray(out), x, rtol=1e-5,
                                   atol=1e-5)

    def test_differentiable_wrt_both(self):
        rng = np.random.default_rng(2)
        x = jnp.asarray(rng.normal(size=(1, 1, 4, 4)).astype(np.float32))
        grid = jnp.asarray(
            rng.uniform(-0.9, 0.9, size=(1, 2, 2, 2)).astype(np.float32))
        gx, gg = jax.grad(
            lambda x, g: ops_nn.grid_sampler(x, g).sum(),
            argnums=(0, 1))(x, grid)
        assert np.isfinite(np.asarray(gx)).all()
        assert np.isfinite(np.asarray(gg)).all()
        assert np.abs(np.asarray(gg)).sum() > 0  # grid really gets grads


class TestRoiPool:
    def test_whole_image_roi_is_global_max(self):
        rng = np.random.default_rng(0)
        feat = rng.normal(size=(8, 8, 3)).astype(np.float32)
        rois = jnp.asarray([[0.0, 0.0, 7.0, 7.0]])
        out = D.roi_pool(jnp.asarray(feat), rois, output_size=(1, 1))
        np.testing.assert_allclose(np.asarray(out)[0, 0, 0],
                                   feat.max(axis=(0, 1)), rtol=1e-6)

    def test_quadrants(self):
        feat = np.zeros((4, 4, 1), np.float32)
        feat[0, 0, 0] = 1.0   # top-left
        feat[0, 3, 0] = 2.0   # top-right
        feat[3, 0, 0] = 3.0   # bottom-left
        feat[3, 3, 0] = 4.0   # bottom-right
        out = D.roi_pool(jnp.asarray(feat),
                         jnp.asarray([[0.0, 0.0, 3.0, 3.0]]),
                         output_size=(2, 2))
        np.testing.assert_allclose(np.asarray(out)[0, :, :, 0],
                                   [[1, 2], [3, 4]])

    def test_spatial_scale(self):
        feat = np.arange(16.0, dtype=np.float32).reshape(4, 4, 1)
        # roi in image coords 8x8, scale 0.5 -> whole 4x4 feature
        out = D.roi_pool(jnp.asarray(feat),
                         jnp.asarray([[0.0, 0.0, 7.0, 7.0]]),
                         output_size=(1, 1), spatial_scale=0.5)
        assert float(out[0, 0, 0, 0]) == 15.0


class TestAnchorGenerator:
    def test_counts_and_geometry(self):
        anchors, var = D.anchor_generator(
            2, 3, anchor_sizes=(64, 128), aspect_ratios=(0.5, 1.0, 2.0),
            stride=(16.0, 16.0))
        assert anchors.shape == (2 * 3 * 6, 4)
        assert var.shape == anchors.shape
        a = np.asarray(anchors)
        # every anchor of size s has area ~s^2 regardless of ratio
        w = a[:, 2] - a[:, 0]
        h = a[:, 3] - a[:, 1]
        areas = (w * h).reshape(-1, 6)
        np.testing.assert_allclose(areas[:, :3], 64.0 ** 2, rtol=1e-5)
        np.testing.assert_allclose(areas[:, 3:], 128.0 ** 2, rtol=1e-5)
        # first cell centered at offset*stride = (8, 8)
        np.testing.assert_allclose((a[0, 0] + a[0, 2]) / 2, 8.0, atol=1e-4)
        np.testing.assert_allclose((a[0, 1] + a[0, 3]) / 2, 8.0, atol=1e-4)
        # aspect ratio honored: h/w == ratio
        np.testing.assert_allclose((h / w).reshape(-1, 6)[0, :3],
                                   [0.5, 1.0, 2.0], rtol=1e-5)


class TestNmsAtScale:
    def _numpy_nms(self, boxes, scores, iou_thr, max_out):
        order = np.argsort(-scores)
        keep = []
        while order.size and len(keep) < max_out:
            i = order[0]
            keep.append(i)
            xx1 = np.maximum(boxes[i, 0], boxes[order[1:], 0])
            yy1 = np.maximum(boxes[i, 1], boxes[order[1:], 1])
            xx2 = np.minimum(boxes[i, 2], boxes[order[1:], 2])
            yy2 = np.minimum(boxes[i, 3], boxes[order[1:], 3])
            w = np.maximum(0.0, xx2 - xx1)
            h = np.maximum(0.0, yy2 - yy1)
            inter = w * h
            a1 = ((boxes[i, 2] - boxes[i, 0])
                  * (boxes[i, 3] - boxes[i, 1]))
            a2 = ((boxes[order[1:], 2] - boxes[order[1:], 0])
                  * (boxes[order[1:], 3] - boxes[order[1:], 1]))
            iou = inter / np.maximum(a1 + a2 - inter, 1e-10)
            order = order[1:][iou < iou_thr]
        return keep

    def test_reference_scale_box_count(self):
        """4000 boxes (reference detection models feed thousands into
        multiclass_nms) — results match the numpy greedy reference and
        complete in sane time."""
        rng = np.random.default_rng(0)
        n = 4000
        centers = rng.uniform(0, 100, size=(n, 2))
        wh = rng.uniform(2, 12, size=(n, 2))
        boxes = np.concatenate([centers - wh / 2, centers + wh / 2],
                               -1).astype(np.float32)
        scores = rng.uniform(size=(n,)).astype(np.float32)

        f = jax.jit(lambda b, s: D.nms(b, s, iou_threshold=0.5,
                                       max_outputs=200))
        idxs, valid = f(jnp.asarray(boxes), jnp.asarray(scores))
        t0 = time.perf_counter()
        idxs, valid = f(jnp.asarray(boxes), jnp.asarray(scores))
        jax.block_until_ready(idxs)
        dt = time.perf_counter() - t0
        assert dt < 5.0, f"nms at 4000 boxes took {dt:.1f}s"

        got = np.asarray(idxs)[np.asarray(valid)]
        want = self._numpy_nms(boxes, scores, 0.5, 200)
        np.testing.assert_array_equal(got, want)


class TestBoxClip:
    def test_clip(self):
        boxes = jnp.asarray([[-5.0, -5.0, 50.0, 50.0],
                             [10.0, 10.0, 20.0, 20.0]])
        out = D.box_clip(boxes, (32, 40))   # h=32, w=40
        np.testing.assert_allclose(np.asarray(out),
                                   [[0, 0, 39, 31], [10, 10, 20, 20]])


class TestMatrixNms:
    def test_duplicate_suppressed_distinct_kept(self):
        boxes = jnp.asarray([
            [0.0, 0.0, 10.0, 10.0],
            [0.5, 0.5, 10.5, 10.5],    # near-duplicate of 0
            [50.0, 50.0, 60.0, 60.0],  # far away
        ])
        scores = jnp.asarray([0.9, 0.8, 0.7])
        idxs, new_scores, valid = D.matrix_nms(
            boxes, scores, keep_top_k=3, post_threshold=0.0)
        got = {int(i): float(s) for i, s, v in
               zip(idxs, new_scores, valid) if v}
        assert got[0] == pytest.approx(0.9)        # top box undecayed
        assert got[2] == pytest.approx(0.7)        # disjoint box undecayed
        assert got[1] < 0.25                       # duplicate crushed

    def test_gaussian_kernel_and_post_threshold(self):
        boxes = jnp.asarray([[0.0, 0.0, 10.0, 10.0],
                             [0.0, 0.0, 10.0, 10.0]])
        scores = jnp.asarray([0.9, 0.8])
        _, s, valid = D.matrix_nms(boxes, scores, keep_top_k=2,
                                   use_gaussian=True, gaussian_sigma=0.5,
                                   post_threshold=0.5)
        kept = np.asarray(s)[np.asarray(valid)]
        np.testing.assert_allclose(kept, [0.9])    # identical box killed

    def test_fixed_shapes_under_jit(self):
        rng = np.random.default_rng(0)
        boxes = jnp.asarray(rng.uniform(0, 100, (500, 4)).astype(np.float32))
        boxes = boxes.at[:, 2:].set(boxes[:, :2] + 5.0)
        scores = jnp.asarray(rng.uniform(size=(500,)).astype(np.float32))
        f = jax.jit(lambda b, s: D.matrix_nms(b, s, nms_top_k=200,
                                              keep_top_k=50))
        idxs, new_scores, valid = f(boxes, scores)
        assert idxs.shape == (50,) and valid.shape == (50,)
        assert bool(valid.any())


class TestDensityPriorBox:
    def test_counts_and_density_tiling(self):
        boxes = D.density_prior_box(
            2, 2, 64, 64, fixed_sizes=(8.0, 16.0), densities=(2, 1),
            fixed_ratios=(1.0,), clip=False)
        # A = 2^2 + 1^2 = 5 per cell
        assert boxes.shape == (2 * 2 * 5, 4)
        b = np.asarray(boxes) * 64.0
        w = b[:, 2] - b[:, 0]
        per_cell = w.reshape(4, 5)
        np.testing.assert_allclose(per_cell[:, :4], 8.0, rtol=1e-5)
        np.testing.assert_allclose(per_cell[:, 4], 16.0, rtol=1e-5)
        # density-2 sub-centers are distinct within the cell
        cx = (b[:, 0] + b[:, 2]) / 2
        cell0 = cx.reshape(4, 5)[0, :4]
        assert len(np.unique(np.round(cell0, 3))) == 2


class TestRealFormatLoaders:
    def test_mnist_idx_parsing(self, tmp_path):
        from paddle_tpu.data.datasets import mnist

        n = 5
        imgs = np.random.default_rng(0).integers(
            0, 256, size=(n, 28, 28)).astype(np.uint8)
        lbls = np.arange(n, dtype=np.uint8)
        with gzip.open(tmp_path / "train-images-idx3-ubyte.gz", "wb") as f:
            f.write(struct.pack(">IIII", 2051, n, 28, 28))
            f.write(imgs.tobytes())
        with open(tmp_path / "train-labels-idx1-ubyte", "wb") as f:
            f.write(struct.pack(">II", 2049, n))
            f.write(lbls.tobytes())

        samples = list(mnist(str(tmp_path), "train")())
        assert len(samples) == n
        img, lbl = samples[2]
        assert img.shape == (784,) and img.dtype == np.float32
        assert -1.0 <= img.min() and img.max() <= 1.0
        np.testing.assert_allclose(
            img, imgs[2].reshape(-1) / 255.0 * 2.0 - 1.0, rtol=1e-4)
        assert lbl == 2

    def test_mnist_missing_files_helpful_error(self, tmp_path):
        from paddle_tpu.data.datasets import mnist

        with pytest.raises(FileNotFoundError, match="synthetic"):
            mnist(str(tmp_path), "train")

    def test_cifar10_pickle_parsing(self, tmp_path):
        from paddle_tpu.data.datasets import cifar10

        d = tmp_path / "cifar-10-batches-py"
        d.mkdir()
        rng = np.random.default_rng(1)
        for i in range(1, 6):
            batch = {b"data": rng.integers(
                0, 256, size=(4, 3072)).astype(np.uint8),
                b"labels": list(range(4))}
            with open(d / f"data_batch_{i}", "wb") as f:
                pickle.dump(batch, f)
        samples = list(cifar10(str(tmp_path), "train")())
        assert len(samples) == 20
        img, lbl = samples[0]
        assert img.shape == (3072,) and 0.0 <= img.min() <= img.max() <= 1.0

    def test_imdb_tree_parsing(self, tmp_path):
        from paddle_tpu.data.datasets import imdb, imdb_build_dict

        for sub, texts in (("train/pos", ["good great good", "great fun"]),
                           ("train/neg", ["bad awful", "bad bad sad"])):
            d = tmp_path / sub
            d.mkdir(parents=True)
            for i, t in enumerate(texts):
                (d / f"{i}.txt").write_text(t)
        word_idx = imdb_build_dict(str(tmp_path), cutoff=0)
        assert "<unk>" in word_idx
        samples = list(imdb(str(tmp_path), word_idx, "train")())
        assert len(samples) == 4
        labels = sorted(int(lbl) for _, lbl in samples)
        assert labels == [0, 0, 1, 1]
        ids, lbl = samples[0]
        assert ids.dtype == np.int64 and len(ids) == 3
