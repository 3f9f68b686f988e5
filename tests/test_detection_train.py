"""Detection training ops: rpn_target_assign,
generate_proposals, ssd_loss, multi_box_head, deformable_conv.

Numerics pinned against numpy references built from the C++ kernels
(rpn_target_assign_op.cc, generate_proposals_op.cc bbox_util.h,
mine_hard_examples_op.cc) and invariance checks for deformable_conv
(zero offsets == plain conv; integer offsets == shifted sampling)."""

import numpy as np
import pytest

import paddle1_tpu as paddle
import paddle1_tpu.fluid as fluid
import paddle1_tpu.fluid.layers as L
from paddle1_tpu.core.tensor import to_tensor


def _np(t):
    return np.asarray(t.numpy())


class TestRpnTargetAssign:
    def _data(self, seed=0):
        rng = np.random.default_rng(seed)
        # a tiny grid of anchors
        ys, xs = np.meshgrid(np.arange(0, 32, 8), np.arange(0, 32, 8),
                             indexing="ij")
        a = np.stack([xs.ravel(), ys.ravel(), xs.ravel() + 7,
                      ys.ravel() + 7], axis=1).astype(np.float32)
        M = a.shape[0]
        N = 2
        bbox_pred = rng.standard_normal((N, M, 4)).astype(np.float32)
        cls_logits = rng.standard_normal((N, M, 1)).astype(np.float32)
        gt = np.zeros((N, 2, 4), np.float32)
        gt[0, 0] = [0, 0, 7, 7]       # exactly anchor 0
        gt[0, 1] = [8, 8, 15, 15]
        gt[1, 0] = [16, 0, 23, 7]
        gt_lens = np.array([2, 1], np.int64)
        crowd = np.zeros((N, 2), np.int64)
        im_info = np.tile(np.array([32.0, 32.0, 1.0], np.float32),
                          (N, 1))
        return a, bbox_pred, cls_logits, gt, gt_lens, crowd, im_info

    def test_perfect_anchor_is_fg_with_zero_delta(self):
        (a, bp, cl, gt, lens, crowd,
         info) = self._data()
        scores, locs, lbl, tbox, inw = L.rpn_target_assign(
            to_tensor(bp), to_tensor(cl), to_tensor(a), None,
            to_tensor(gt), to_tensor(crowd), to_tensor(info),
            gt_lengths=lens, rpn_batch_size_per_im=16,
            use_random=False)
        lbl_np, tb = _np(lbl).ravel(), _np(tbox)
        # fg targets exist and the exact-match anchors encode to 0
        n_fg = int((lbl_np == 1).sum())
        assert n_fg >= 3
        assert tb.shape[0] >= n_fg
        exact = np.abs(tb).sum(axis=1)
        assert (exact < 1e-5).sum() >= 3   # the 3 perfect anchors
        # shapes line up between scores and labels, locs and weights
        assert _np(scores).shape[0] == lbl_np.shape[0]
        assert _np(locs).shape == tb.shape == _np(inw).shape

    def test_batch_cap_and_label_balance(self):
        (a, bp, cl, gt, lens, crowd, info) = self._data(1)
        scores, locs, lbl, tbox, inw = L.rpn_target_assign(
            to_tensor(bp), to_tensor(cl), to_tensor(a), None,
            to_tensor(gt), to_tensor(crowd), to_tensor(info),
            gt_lengths=lens, rpn_batch_size_per_im=8,
            rpn_fg_fraction=0.5, use_random=False)
        lbl_np = _np(lbl).ravel()
        # per image at most batch_size samples
        assert lbl_np.shape[0] <= 2 * 8
        assert set(np.unique(lbl_np)) <= {0, 1}

    def test_gathered_predictions_carry_grad(self):
        (a, bp, cl, gt, lens, crowd, info) = self._data(2)
        bpt, clt = to_tensor(bp), to_tensor(cl)
        bpt.stop_gradient = False
        clt.stop_gradient = False
        scores, locs, lbl, tbox, inw = L.rpn_target_assign(
            bpt, clt, to_tensor(a), None, to_tensor(gt),
            to_tensor(crowd), to_tensor(info), gt_lengths=lens,
            use_random=False)
        loss = (locs * inw - tbox * inw).abs().sum() \
            + (scores ** 2).sum()
        loss.backward()
        assert np.abs(_np(bpt.grad)).sum() > 0
        assert np.abs(_np(clt.grad)).sum() > 0

    def test_zero_gt_image_is_all_background(self):
        (a, bp, cl, gt, lens, crowd, info) = self._data(4)
        lens0 = np.array([2, 0], np.int64)  # image 1 has no gt
        scores, locs, lbl, tbox, inw = L.rpn_target_assign(
            to_tensor(bp), to_tensor(cl), to_tensor(a), None,
            to_tensor(gt), to_tensor(crowd), to_tensor(info),
            gt_lengths=lens0, rpn_batch_size_per_im=8,
            use_random=False)
        lbl_np = _np(lbl).ravel()
        assert lbl_np.shape[0] > 0
        # the negative image contributed only background labels and
        # no regression targets beyond image 0's
        assert set(np.unique(lbl_np)) <= {0, 1}

    def test_crowd_gt_excluded(self):
        (a, bp, cl, gt, lens, crowd, info) = self._data(3)
        crowd2 = crowd.copy()
        crowd2[0, 0] = 1  # first gt of image 0 is crowd
        _, _, lbl_a, _, _ = L.rpn_target_assign(
            to_tensor(bp), to_tensor(cl), to_tensor(a), None,
            to_tensor(gt), to_tensor(crowd), to_tensor(info),
            gt_lengths=lens, use_random=False)
        _, _, lbl_b, _, _ = L.rpn_target_assign(
            to_tensor(bp), to_tensor(cl), to_tensor(a), None,
            to_tensor(gt), to_tensor(crowd2), to_tensor(info),
            gt_lengths=lens, use_random=False)
        assert (_np(lbl_b) == 1).sum() < (_np(lbl_a) == 1).sum()


class TestGenerateProposals:
    def test_decode_clip_nms(self):
        rng = np.random.default_rng(4)
        N, A, H, W = 1, 3, 4, 4
        anchors = np.zeros((H, W, A, 4), np.float32)
        for y in range(H):
            for x in range(W):
                for k in range(A):
                    s = 4 * (k + 1)
                    anchors[y, x, k] = [x * 8, y * 8, x * 8 + s,
                                        y * 8 + s]
        variances = np.full((H, W, A, 4), 1.0, np.float32)
        scores = rng.random((N, A, H, W)).astype(np.float32)
        deltas = (rng.standard_normal((N, 4 * A, H, W)) * 0.1).astype(
            np.float32)
        info = np.array([[32, 32, 1.0]], np.float32)
        rois, probs, lens = L.generate_proposals(
            to_tensor(scores), to_tensor(deltas), to_tensor(info),
            to_tensor(anchors), to_tensor(variances),
            pre_nms_top_n=40, post_nms_top_n=10, nms_thresh=0.7,
            min_size=1.0)
        r, p, ln = _np(rois), _np(probs), _np(lens)
        assert ln[0] == r.shape[0] <= 10
        assert p.shape == (r.shape[0], 1)
        # clipped to the image
        assert r[:, 0].min() >= 0 and r[:, 2].max() <= 31
        assert r[:, 1].min() >= 0 and r[:, 3].max() <= 31
        # scores sorted descending (NMS keeps order)
        assert (np.diff(p.ravel()) <= 1e-6).all()
        # zero-delta anchor decodes to itself
        z = np.zeros_like(deltas)
        rois2, probs2, _ = L.generate_proposals(
            to_tensor(scores), to_tensor(z), to_tensor(info),
            to_tensor(anchors), to_tensor(variances),
            pre_nms_top_n=40, post_nms_top_n=48, nms_thresh=1.01,
            min_size=1.0)
        r2 = _np(rois2)
        best = scores[0].transpose(1, 2, 0).reshape(-1).argmax()
        np.testing.assert_allclose(
            r2[0], anchors.reshape(-1, 4)[best], atol=1e-5)


class TestGenerateProposalsEdge:
    def test_all_filtered_emits_zero_box(self):
        """keep-the-graph-alive contract: an image whose proposals are
        all filtered still contributes one [0,0,0,0] roi, score 0."""
        anchors = np.zeros((1, 1, 1, 4), np.float32)
        anchors[0, 0, 0] = [0, 0, 0.5, 0.5]   # sub-min_size anchor
        variances = np.ones((1, 1, 1, 4), np.float32)
        scores = np.ones((1, 1, 1, 1), np.float32)
        deltas = np.zeros((1, 4, 1, 1), np.float32)
        info = np.array([[16, 16, 1.0]], np.float32)
        rois, probs, lens = L.generate_proposals(
            to_tensor(scores), to_tensor(deltas), to_tensor(info),
            to_tensor(anchors), to_tensor(variances), min_size=8.0)
        assert _np(lens).tolist() == [1]
        np.testing.assert_array_equal(_np(rois), [[0, 0, 0, 0]])
        np.testing.assert_array_equal(_np(probs), [[0.0]])


class TestSSDLoss:
    def _toy(self, seed=5):
        rng = np.random.default_rng(seed)
        N, P, C, G = 2, 8, 4, 2
        pb = np.zeros((P, 4), np.float32)
        for i in range(P):
            cx = (i % 4) * 0.25 + 0.125
            cy = (i // 4) * 0.5 + 0.25
            pb[i] = [cx - 0.1, cy - 0.15, cx + 0.1, cy + 0.15]
        loc = (rng.standard_normal((N, P, 4)) * 0.1).astype(np.float32)
        conf = rng.standard_normal((N, P, C)).astype(np.float32)
        gt = np.zeros((N, G, 4), np.float32)
        gt[0, 0] = pb[1] + 0.01
        gt[0, 1] = pb[6] - 0.01
        gt[1, 0] = pb[3] + 0.02
        gl = np.array([[1, 2], [3, 0]], np.int64)
        lens = np.array([2, 1], np.int64)
        return pb, loc, conf, gt, gl, lens

    def test_loss_shape_positive_and_grad(self):
        pb, loc, conf, gt, gl, lens = self._toy()
        lt, ct = to_tensor(loc), to_tensor(conf)
        lt.stop_gradient = False
        ct.stop_gradient = False
        loss = L.ssd_loss(lt, ct, to_tensor(gt), to_tensor(gl),
                          to_tensor(pb), gt_lengths=lens)
        lv = _np(loss)
        assert lv.shape == (2, 1) and (lv > 0).all()
        loss.sum().backward()
        assert np.abs(_np(lt.grad)).sum() > 0
        assert np.abs(_np(ct.grad)).sum() > 0

    def test_training_decreases_loss(self):
        pb, loc, conf, gt, gl, lens = self._toy(6)
        lay = paddle.nn.Layer()
        lt = lay.create_parameter(list(loc.shape))
        ct = lay.create_parameter(list(conf.shape))
        lt.set_value(loc)
        ct.set_value(conf)
        opt = paddle.optimizer.Adam(learning_rate=0.05,
                                    parameters=[lt, ct])
        losses = []
        for _ in range(15):
            loss = L.ssd_loss(lt, ct, to_tensor(gt), to_tensor(gl),
                              to_tensor(pb), gt_lengths=lens).sum()
            loss.backward()
            opt.step()
            opt.clear_grad()
            losses.append(float(loss.numpy()))
        assert losses[-1] < losses[0] * 0.7, losses

    def test_perfect_predictions_loss_small(self):
        """Predictions exactly matching the encoded targets and
        confident correct classes → near-zero loc loss part."""
        pb, loc, conf, gt, gl, lens = self._toy(7)
        zero_loc = np.zeros_like(loc)
        l1 = _np(L.ssd_loss(to_tensor(loc * 10), to_tensor(conf),
                            to_tensor(gt), to_tensor(gl),
                            to_tensor(pb), gt_lengths=lens))
        l2 = _np(L.ssd_loss(to_tensor(zero_loc), to_tensor(conf),
                            to_tensor(gt), to_tensor(gl),
                            to_tensor(pb), gt_lengths=lens))
        # targets are near-zero deltas (gt ≈ prior): zero predictions
        # give a smaller localization loss than large ones
        assert l2.sum() < l1.sum()


class TestMultiBoxHead:
    def test_shapes_and_consistency(self):
        rng = np.random.default_rng(8)
        img = to_tensor(rng.standard_normal((1, 3, 64, 64)).astype(
            np.float32))
        f1 = to_tensor(rng.standard_normal((1, 8, 8, 8)).astype(
            np.float32))
        f2 = to_tensor(rng.standard_normal((1, 16, 4, 4)).astype(
            np.float32))
        loc, conf, boxes, vars_ = L.multi_box_head(
            [f1, f2], img, base_size=64, num_classes=5,
            aspect_ratios=[[2.0], [2.0, 3.0]], min_ratio=20,
            max_ratio=90, offset=0.5, flip=True, name="mbh")
        M = _np(boxes).shape[0]
        assert _np(loc).shape == (1, M, 4)
        assert _np(conf).shape == (1, M, 5)
        assert _np(vars_).shape == (M, 4)
        bx = _np(boxes)
        assert (bx[:, 2] >= bx[:, 0]).all()

    def test_feeds_ssd_loss(self):
        rng = np.random.default_rng(9)
        img = to_tensor(rng.standard_normal((2, 3, 32, 32)).astype(
            np.float32))
        f1 = to_tensor(rng.standard_normal((2, 4, 4, 4)).astype(
            np.float32))
        loc, conf, boxes, vars_ = L.multi_box_head(
            [f1], img, base_size=32, num_classes=3,
            aspect_ratios=[[2.0]], min_sizes=[10.0], max_sizes=[20.0],
            name="mbh2")
        gt = np.array([[[0.1, 0.1, 0.4, 0.4]],
                       [[0.5, 0.5, 0.9, 0.9]]], np.float32)
        gl = np.array([[1], [2]], np.int64)
        loss = L.ssd_loss(loc, conf, to_tensor(gt), to_tensor(gl),
                          boxes, prior_box_var=vars_,
                          gt_lengths=np.array([1, 1], np.int64))
        assert (_np(loss) > 0).all()
        loss.sum().backward()  # grads reach the implicit conv heads


class TestDeformableConv:
    def _conv_ref(self, x, w, stride=1):
        """Plain valid conv via jax for the zero-offset check."""
        import jax
        return np.asarray(jax.lax.conv_general_dilated(
            x, w, (stride, stride), "VALID"))

    def test_zero_offset_equals_plain_conv(self):
        rng = np.random.default_rng(10)
        x = rng.standard_normal((2, 4, 8, 8)).astype(np.float32)
        off = np.zeros((2, 2 * 9, 6, 6), np.float32)
        mask = np.ones((2, 9, 6, 6), np.float32)
        out = L.deformable_conv(to_tensor(x), to_tensor(off),
                                to_tensor(mask), 5, 3, name="dcn1")
        w = _np(fluid.layers.implicit_parameters()[-2])
        assert w.shape == (5, 4, 3, 3)
        ref = self._conv_ref(x, w)
        b = _np(fluid.layers.implicit_parameters()[-1])
        np.testing.assert_allclose(_np(out),
                                   ref + b[None, :, None, None],
                                   rtol=2e-4, atol=2e-4)

    def test_integer_offset_shifts_sampling(self):
        rng = np.random.default_rng(11)
        x = rng.standard_normal((1, 2, 10, 10)).astype(np.float32)
        # every tap shifted by (+1, +1): equals plain conv on the
        # shifted input window (out is 8x8; the shifted ref covers 7x7)
        off = np.ones((1, 2 * 9, 8, 8), np.float32)
        mask = np.ones((1, 9, 8, 8), np.float32)
        out = L.deformable_conv(to_tensor(x), to_tensor(off),
                                to_tensor(mask), 3, 3,
                                bias_attr=False, name="dcn2")
        w = _np(fluid.layers.implicit_parameters()[-1])
        ref = self._conv_ref(x[:, :, 1:, 1:], w)
        np.testing.assert_allclose(_np(out)[:, :, :7, :7], ref,
                                   rtol=2e-4, atol=2e-4)

    def test_mask_modulates(self):
        rng = np.random.default_rng(12)
        x = rng.standard_normal((1, 2, 6, 6)).astype(np.float32)
        off = np.zeros((1, 18, 4, 4), np.float32)
        half = np.full((1, 9, 4, 4), 0.5, np.float32)
        full = np.ones((1, 9, 4, 4), np.float32)
        o_half = L.deformable_conv(to_tensor(x), to_tensor(off),
                                   to_tensor(half), 3, 3,
                                   bias_attr=False, name="dcn3")
        o_full = L.deformable_conv(to_tensor(x), to_tensor(off),
                                   to_tensor(full), 3, 3,
                                   bias_attr=False, name="dcn3")
        np.testing.assert_allclose(_np(o_half) * 2, _np(o_full),
                                   rtol=2e-4, atol=2e-4)

    def test_gradients_flow_to_offsets(self):
        rng = np.random.default_rng(13)
        x = to_tensor(rng.standard_normal((1, 2, 6, 6)).astype(
            np.float32))
        off = to_tensor((rng.standard_normal((1, 18, 4, 4)) * 0.3)
                        .astype(np.float32))
        mask = to_tensor(np.ones((1, 9, 4, 4), np.float32))
        x.stop_gradient = False
        off.stop_gradient = False
        out = L.deformable_conv(x, off, mask, 3, 3, name="dcn4")
        out.sum().backward()
        assert np.abs(_np(x.grad)).sum() > 0
        assert np.abs(_np(off.grad)).sum() > 0
