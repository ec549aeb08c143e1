"""Training-side detection ops: matching, target assignment, SSD/YOLOv3/
focal losses, RPN/FPN proposal plumbing.

Mirrors the reference's OpTest strategy (op_test.py): every op is checked
against a plain-NumPy re-implementation of the documented semantics, plus
gradient flow where the op sits on the training path.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
pytestmark = pytest.mark.slow  # excluded from the quick CI gate


from paddle_tpu.ops import detection as D


def np_bipartite_match(dist, row_mask):
    d = np.where(row_mask[:, None], dist, -1.0).copy()
    g, p = d.shape
    col_to_row = np.full((p,), -1, np.int32)
    col_dist = np.zeros((p,), d.dtype)
    for _ in range(g):
        r, c = np.unravel_index(np.argmax(d), d.shape)
        if d[r, c] <= 0:
            break
        col_to_row[c] = r
        col_dist[c] = d[r, c]
        d[r, :] = -1.0
        d[:, c] = -1.0
    return col_to_row, col_dist


class TestBipartiteMatch:
    def test_matches_numpy_greedy(self):
        rng = np.random.RandomState(0)
        for trial in range(5):
            dist = rng.rand(4, 12).astype(np.float32)
            mask = np.array([True, True, True, trial % 2 == 0])
            idx, dval = D.bipartite_match(jnp.asarray(dist),
                                          jnp.asarray(mask))
            ref_idx, ref_d = np_bipartite_match(dist, mask)
            np.testing.assert_array_equal(np.asarray(idx), ref_idx)
            np.testing.assert_allclose(np.asarray(dval), ref_d, rtol=1e-6)

    def test_each_row_matched_once(self):
        rng = np.random.RandomState(1)
        dist = rng.rand(3, 10).astype(np.float32)
        idx, _ = D.bipartite_match(jnp.asarray(dist))
        matched = np.asarray(idx)[np.asarray(idx) >= 0]
        assert len(set(matched.tolist())) == len(matched)
        assert len(matched) == 3  # all 3 rows found a column

    def test_per_prediction_augmentation(self):
        # one gt, two anchors both overlapping > threshold: bipartite
        # matches one; per_prediction picks up the other
        gt = jnp.asarray([[0.0, 0.0, 1.0, 1.0]])
        anchors = jnp.asarray([[0.0, 0.0, 1.0, 0.9],
                               [0.0, 0.0, 0.9, 1.0],
                               [5.0, 5.0, 6.0, 6.0]])
        iou = D.box_iou(gt, anchors)
        m_idx, _ = D.match_boxes(iou, overlap_threshold=0.5)
        assert m_idx[0] == 0 and m_idx[1] == 0 and m_idx[2] == -1


class TestTargetAssign:
    def test_gather_and_weights(self):
        x = jnp.arange(12, dtype=jnp.float32).reshape(3, 4)
        idx = jnp.asarray([2, -1, 0, 1], jnp.int32)
        out, w = D.target_assign(x, idx, mismatch_value=-9.0)
        np.testing.assert_allclose(np.asarray(out[0]), np.arange(8, 12))
        np.testing.assert_allclose(np.asarray(out[1]), [-9.0] * 4)
        np.testing.assert_allclose(np.asarray(w), [1, 0, 1, 1])


class TestMineHardExamples:
    def test_ratio_and_ordering(self):
        # 2 positives -> 6 negatives allowed; pick the 6 largest losses
        p = 12
        loss = jnp.asarray(np.arange(p)[::-1].copy(), jnp.float32)
        match = jnp.full((p,), -1, jnp.int32).at[0].set(0).at[1].set(1)
        neg = np.asarray(D.mine_hard_examples(loss, match,
                                              neg_pos_ratio=3.0))
        assert neg.sum() == 6
        assert not neg[0] and not neg[1]          # positives excluded
        assert neg[2:8].all()                     # hardest negatives

    def test_no_positives_no_negatives(self):
        neg = D.mine_hard_examples(jnp.ones((5,)),
                                   jnp.full((5,), -1, jnp.int32))
        assert not np.asarray(neg).any()


class TestSSDLoss:
    def _data(self, b=2, p=16, c=4, g=3, seed=0):
        rng = np.random.RandomState(seed)
        anchors = np.sort(rng.rand(p, 2, 2), axis=1).reshape(p, 4)
        anchors = anchors.astype(np.float32)
        gt = np.sort(rng.rand(b, g, 2, 2), axis=2).reshape(b, g, 4)
        gt[..., 2:] = np.maximum(gt[..., 2:], gt[..., :2] + 0.1)
        labels = rng.randint(1, c, (b, g))
        mask = np.ones((b, g), bool)
        mask[:, -1] = False
        loc = rng.randn(b, p, 4).astype(np.float32) * 0.1
        conf = rng.randn(b, p, c).astype(np.float32)
        return (jnp.asarray(loc), jnp.asarray(conf), jnp.asarray(anchors),
                jnp.asarray(gt.astype(np.float32)),
                jnp.asarray(labels), jnp.asarray(mask))

    def test_finite_and_positive(self):
        loss = D.ssd_loss(*self._data())
        assert np.isfinite(float(loss)) and float(loss) > 0

    def test_gradients_flow(self):
        loc, conf, anchors, gt, labels, mask = self._data()
        gfn = jax.grad(lambda l, cf: D.ssd_loss(l, cf, anchors, gt,
                                                labels, mask), argnums=(0, 1))
        gl, gc = gfn(loc, conf)
        assert np.isfinite(np.asarray(gl)).all()
        assert np.isfinite(np.asarray(gc)).all()
        assert np.abs(np.asarray(gc)).sum() > 0

    def test_perfect_predictions_lower_loss(self):
        loc, conf, anchors, gt, labels, mask = self._data()
        loss_rand = float(D.ssd_loss(loc, conf, anchors, gt, labels, mask))
        # construct near-perfect conf: big logit on the matched class
        iou = jax.vmap(lambda g_, m_: D.box_iou(g_, anchors))(gt, mask)
        good_conf = []
        for i in range(loc.shape[0]):
            m_idx, _ = D.match_boxes(iou[i], mask[i])
            cls = jnp.where(m_idx >= 0,
                            labels[i][jnp.maximum(m_idx, 0)], 0)
            good_conf.append(10.0 * jax.nn.one_hot(cls, conf.shape[-1]))
        good_conf = jnp.stack(good_conf)
        loss_good = float(D.ssd_loss(loc, good_conf, anchors, gt, labels,
                                     mask))
        assert loss_good < loss_rand

    def test_jit_compiles(self):
        args = self._data()
        f = jax.jit(D.ssd_loss)
        assert np.isfinite(float(f(*args)))


class TestSigmoidFocalLoss:
    def test_matches_numpy(self):
        rng = np.random.RandomState(3)
        logits = rng.randn(6, 4).astype(np.float32)
        labels = np.array([0, 1, 2, 4, 3, 0])
        out = np.asarray(D.sigmoid_focal_loss(
            jnp.asarray(logits), jnp.asarray(labels),
            gamma=2.0, alpha=0.25))
        t = (labels[:, None] == np.arange(1, 5)[None, :]).astype(np.float32)
        p = 1.0 / (1.0 + np.exp(-logits))
        ce = -(t * np.log(p + 1e-12) + (1 - t) * np.log(1 - p + 1e-12))
        pt = p * t + (1 - p) * (1 - t)
        at = 0.25 * t + 0.75 * (1 - t)
        ref = at * (1 - pt) ** 2.0 * ce
        np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-5)

    def test_grad_flows(self):
        logits = jnp.asarray(np.random.RandomState(0).randn(5, 3),
                             jnp.float32)
        labels = jnp.asarray([1, 2, 0, 3, 1])
        g = jax.grad(lambda x: D.sigmoid_focal_loss(x, labels).sum())(logits)
        assert np.isfinite(np.asarray(g)).all()


class TestYolov3Loss:
    ANCHORS = [(10, 13), (33, 30), (62, 45), (116, 90)]

    def _head(self, b=2, a=2, c=3, h=4, w=4, seed=0):
        rng = np.random.RandomState(seed)
        return jnp.asarray(rng.randn(b, a * (5 + c), h, w).astype(
            np.float32) * 0.1)

    def test_finite_and_grad(self):
        x = self._head()
        gt = jnp.asarray([[[0.3, 0.3, 0.2, 0.2], [0.7, 0.6, 0.4, 0.3]],
                          [[0.5, 0.5, 0.3, 0.3], [0.0, 0.0, 0.0, 0.0]]],
                         jnp.float32)
        labels = jnp.asarray([[0, 2], [1, 0]])
        mask = jnp.asarray([[True, True], [True, False]])
        fn = lambda x_: D.yolov3_loss(
            x_, gt, labels, mask, anchors=self.ANCHORS,
            anchor_mask=[0, 1], class_num=3, downsample_ratio=8)
        loss = float(fn(x))
        assert np.isfinite(loss) and loss > 0
        g = jax.grad(lambda x_: fn(x_))(x)
        assert np.isfinite(np.asarray(g)).all()
        assert np.abs(np.asarray(g)).sum() > 0

    def test_gt_outside_head_anchor_mask_ignored(self):
        # gt whose best anchor is NOT owned by this head contributes no
        # positive; loss reduces to pure background objectness
        x = jnp.zeros((1, 2 * 8, 2, 2))
        big = jnp.asarray([[[0.5, 0.5, 0.9, 0.9]]], jnp.float32)  # huge box
        labels = jnp.zeros((1, 1), jnp.int32)
        mask = jnp.ones((1, 1), bool)
        # downsample 32 -> 64px input -> gt is 57.6px: best wh-IoU anchor
        # is (62,45) = index 2, NOT owned by this head's mask [0, 1]: no
        # positive terms; only the ignore-mask differs from the empty case,
        # which can only REMOVE background-objectness terms
        loss_with = float(D.yolov3_loss(
            x, big, labels, mask, anchors=self.ANCHORS,
            anchor_mask=[0, 1], class_num=3, downsample_ratio=32))
        loss_empty = float(D.yolov3_loss(
            x, big, labels, jnp.zeros((1, 1), bool),
            anchors=self.ANCHORS, anchor_mask=[0, 1], class_num=3,
            downsample_ratio=32))
        assert loss_with <= loss_empty + 1e-5


class TestRpnTargetAssign:
    def test_labels_partition(self):
        anchors = jnp.asarray(
            [[0, 0, 10, 10], [0, 0, 9, 10], [50, 50, 60, 60],
             [200, 200, 210, 210]], jnp.float32)
        gt = jnp.asarray([[0, 0, 10, 10]], jnp.float32)
        mask = jnp.ones((1,), bool)
        labels, tgt, fg, bg = D.rpn_target_assign(
            anchors, gt, mask, pos_threshold=0.7, neg_threshold=0.3)
        lab = np.asarray(labels)
        assert lab[0] == 1            # IoU 1.0
        assert lab[1] == 1            # IoU 0.9 ~ forced/pos
        assert lab[2] == 0 and lab[3] == 0
        # targets zero for non-fg
        assert np.allclose(np.asarray(tgt)[~np.asarray(fg)], 0.0)

    def test_fg_cap(self):
        n = 20
        anchors = jnp.tile(jnp.asarray([[0., 0., 10., 10.]]), (n, 1))
        gt = jnp.asarray([[0, 0, 10, 10]], jnp.float32)
        labels, _, fg, bg = D.rpn_target_assign(
            anchors, gt, jnp.ones((1,), bool),
            batch_size_per_im=8, fg_fraction=0.5)
        assert int(np.asarray(fg).sum()) <= 4


class TestProposalPath:
    def test_generate_proposals_shapes_and_validity(self):
        p = 32
        rng = np.random.RandomState(0)
        anchors, _ = D.anchor_generator(4, 8, anchor_sizes=(32,),
                                        aspect_ratios=(1.0,))
        scores = jnp.asarray(rng.rand(p).astype(np.float32))
        deltas = jnp.asarray(rng.randn(p, 4).astype(np.float32) * 0.1)
        rois, s, valid = D.generate_proposals(
            scores, deltas, anchors, jnp.asarray([64.0, 128.0]),
            pre_nms_top_n=16, post_nms_top_n=8, nms_thresh=0.7,
            min_size=4.0)
        assert rois.shape == (8, 4) and valid.dtype == bool
        v = np.asarray(valid)
        assert v.any()
        r = np.asarray(rois)[v]
        assert (r[:, 0] >= 0).all() and (r[:, 2] <= 127).all()
        assert (r[:, 3] <= 63).all()

    def test_fpn_distribute_and_collect(self):
        rois = jnp.asarray([[0, 0, 20, 20],       # small -> low level
                            [0, 0, 224, 224],     # refer scale -> level 4
                            [0, 0, 800, 800]],    # huge -> level 5
                           jnp.float32)
        lvl, masks = D.distribute_fpn_proposals(rois, min_level=2,
                                                max_level=5)
        lv = np.asarray(lvl)
        assert lv[0] == 2 and lv[1] == 4 and lv[2] == 5
        assert masks.shape == (4, 3)
        np.testing.assert_array_equal(np.asarray(masks).sum(0), [1, 1, 1])

        out_r, out_s, valid = D.collect_fpn_proposals(
            [rois, rois + 1.0], [jnp.asarray([0.1, 0.9, 0.5]),
                                 jnp.asarray([0.8, 0.2, 0.3])],
            post_nms_top_n=4)
        assert out_r.shape == (4, 4)
        assert np.asarray(valid).all()
        np.testing.assert_allclose(np.asarray(out_s),
                                   [0.9, 0.8, 0.5, 0.3], rtol=1e-6)

    def test_polygon_box_transform(self):
        x = jnp.zeros((1, 8, 2, 3))
        out = np.asarray(D.polygon_box_transform(x))
        # zero offsets -> absolute coords are 4*index
        np.testing.assert_allclose(out[0, 0, 0], [0, 4, 8])   # x channel
        np.testing.assert_allclose(out[0, 1, :, 0], [0, 4])   # y channel

    def test_retinanet_detection_output(self):
        rng = np.random.RandomState(1)
        anchors, _ = D.anchor_generator(2, 2, anchor_sizes=(32,),
                                        aspect_ratios=(1.0,))
        deltas = jnp.asarray(rng.randn(4, 4).astype(np.float32) * 0.05)
        scores = jax.nn.sigmoid(jnp.asarray(
            rng.randn(4, 3).astype(np.float32)))
        boxes, cls, s, valid = D.retinanet_detection_output(
            [deltas], [scores], [anchors], jnp.asarray([64.0, 64.0]),
            keep_top_k=5, score_threshold=0.05)
        assert boxes.shape == (5, 4)
        v = np.asarray(valid)
        assert v.any()
        sv = np.asarray(s)[v]
        assert (np.diff(sv) <= 1e-6).all()   # sorted desc


class TestReviewRegressions:
    """Regressions for the round-3 code-review findings."""

    def test_rpn_empty_image_is_all_background(self):
        anchors = jnp.asarray([[0, 0, 10, 10], [5, 5, 20, 20]],
                              jnp.float32)
        gt = jnp.zeros((1, 4), jnp.float32)
        labels, _, fg, bg = D.rpn_target_assign(
            anchors, gt, jnp.zeros((1,), bool), batch_size_per_im=4)
        assert not np.asarray(fg).any()
        assert np.asarray(bg).all()          # negatives, not ignored
        assert (np.asarray(labels) == 0).all()

    def test_collect_fpn_padding_never_outranks_real(self):
        # level 1: one real proposal with NEGATIVE score + one zero-pad
        rois = jnp.asarray([[1, 1, 2, 2], [0, 0, 0, 0]], jnp.float32)
        scores = jnp.asarray([-3.0, 0.0])
        valid = jnp.asarray([True, False])
        out_r, out_s, out_v = D.collect_fpn_proposals(
            [rois], [scores], [valid], post_nms_top_n=2)
        assert np.asarray(out_v)[0] and not np.asarray(out_v)[1]
        np.testing.assert_allclose(np.asarray(out_r)[0], [1, 1, 2, 2])

    def test_detection_map_ignores_hallucinated_class(self):
        from paddle_tpu.metrics import DetectionMAP
        m = DetectionMAP()
        gt = np.array([[0, 0, 10, 10]], np.float32)
        # perfect match on class 1 plus a prediction of class 7 (no gt)
        m.update(pred_boxes=np.array([[0, 0, 10, 10], [30, 30, 40, 40]],
                                     np.float32),
                 pred_scores=np.array([0.9, 0.8]),
                 pred_classes=np.array([1, 7]),
                 pred_valid=np.array([True, True]),
                 gt_boxes=gt, gt_classes=np.array([1]),
                 gt_mask=np.array([True]))
        # class 7 adds no zero term
        assert m.eval() == pytest.approx(1.0)

    def test_ssd_mismatched_aspect_ratio_sets(self):
        # no ar == 1.0 in the set: heads and priors must still agree
        from paddle_tpu.models.ssd import SSD, SSDConfig
        cfg = SSDConfig.tiny()
        cfg.aspect_ratios = (2.0, 0.5)
        model = SSD(cfg)
        params = model.init(jax.random.PRNGKey(0))
        loc, conf = model.forward(params, jnp.zeros((1, 64, 64, 3)))
        assert loc.shape[1] == model.anchors().shape[0]

    def test_rpn_zero_iou_gt_forces_nothing(self):
        # gt overlapping NO anchor must not force every anchor positive
        anchors = jnp.asarray([[0, 0, 10, 10], [20, 20, 30, 30],
                               [40, 40, 50, 50], [60, 60, 70, 70]],
                              jnp.float32)
        gt = jnp.asarray([[100, 100, 101, 101]], jnp.float32)
        labels, _, fg, bg = D.rpn_target_assign(
            anchors, gt, jnp.ones((1,), bool), batch_size_per_im=4)
        assert not np.asarray(fg).any()
        assert np.asarray(bg).all()

    def test_retinanet_pre_nms_topk_bounds_shape(self):
        rng = np.random.RandomState(2)
        anchors, _ = D.anchor_generator(4, 4, anchor_sizes=(16,),
                                        aspect_ratios=(1.0,))
        deltas = jnp.asarray(rng.randn(16, 4).astype(np.float32) * 0.05)
        scores = jax.nn.sigmoid(jnp.asarray(
            rng.randn(16, 2).astype(np.float32)))
        boxes, cls, s, valid = D.retinanet_detection_output(
            [deltas], [scores], [anchors], jnp.asarray([64.0, 64.0]),
            nms_top_k=8, keep_top_k=4, score_threshold=0.0)
        assert boxes.shape == (4, 4)
        assert np.asarray(valid).any()

    def test_rpn_im_shape_excludes_boundary_anchors(self):
        anchors = jnp.asarray([[0, 0, 10, 10],      # inside
                               [-5, 0, 5, 10],      # straddles left edge
                               [56, 56, 70, 70]],   # straddles right edge
                              jnp.float32)
        gt = jnp.asarray([[0, 0, 10, 10]], jnp.float32)
        labels, _, fg, bg = D.rpn_target_assign(
            anchors, gt, jnp.ones((1,), bool),
            im_shape=jnp.asarray([64.0, 64.0]))
        lab = np.asarray(labels)
        assert lab[0] == 1          # inside + perfect IoU
        assert lab[1] == -1         # boundary anchors are ignored
        assert lab[2] == -1
