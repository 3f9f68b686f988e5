"""End-to-end two-stage detection training (the RCNN-family
composition the training ops exist for): backbone → RPN
(rpn_target_assign loss + generate_proposals) → proposal sampling
(generate_proposal_labels) → ROI head (prroi_pool + cls/reg losses)
→ mask head (generate_mask_labels + per-class mask loss). The whole
pipeline trains with decreasing loss on synthetic data — every
gradient flows through the traced gathers/pools while the
data-dependent assignment stays host-side, the reference's own
split."""

import numpy as np
import pytest

import paddle1_tpu as paddle
import paddle1_tpu.fluid.layers as L
import paddle1_tpu.nn.functional as F
from paddle1_tpu.core.tensor import to_tensor


def _np(t):
    return np.asarray(t.numpy())


class TinyTwoStage(paddle.nn.Layer):
    """8x8-anchor two-stage detector over a 32x32 image."""

    def __init__(self, num_classes=3):
        super().__init__()
        self.backbone = paddle.nn.Sequential(
            paddle.nn.Conv2D(3, 8, 3, stride=2, padding=1),
            paddle.nn.ReLU(),
            paddle.nn.Conv2D(8, 16, 3, stride=2, padding=1),
            paddle.nn.ReLU())                     # [N, 16, 8, 8]
        self.rpn_head = paddle.nn.Conv2D(16, 5, 1)  # 4 loc + 1 score
        self.roi_fc = paddle.nn.Linear(16 * 2 * 2, 32)
        self.cls_head = paddle.nn.Linear(32, num_classes)
        self.reg_head = paddle.nn.Linear(32, 4 * num_classes)
        self.mask_head = paddle.nn.Linear(16 * 2 * 2,
                                          num_classes * 4 * 4)
        self.num_classes = num_classes


def _anchors_8x8():
    ys, xs = np.meshgrid(np.arange(8), np.arange(8), indexing="ij")
    return np.stack([xs.ravel() * 4, ys.ravel() * 4,
                     xs.ravel() * 4 + 7, ys.ravel() * 4 + 7],
                    axis=1).astype(np.float32)


@pytest.fixture(scope="module")
def scene():
    gt = np.array([[[2, 2, 13, 13], [18, 16, 29, 30]]], np.float32)
    gtc = np.array([[1, 2]], np.int64)
    info = np.array([[32, 32, 1.0]], np.float32)
    m1 = np.zeros((32, 32), np.uint8)
    m1[2:14, 2:14] = 1
    m2 = np.zeros((32, 32), np.uint8)
    m2[16:31, 18:30] = 1
    rng = np.random.default_rng(0)
    img = rng.standard_normal((1, 3, 32, 32)).astype(np.float32) * 0.1
    img[:, 0, 2:14, 2:14] += 2.0     # class-1 object signal
    img[:, 1, 16:31, 18:30] += 2.0   # class-2 object signal
    return img, gt, gtc, info, [m1, m2]


def _train_step(model, img, gt, gtc, info, masks, anchors):
    feat = model.backbone(to_tensor(img))          # [1, 16, 8, 8]
    rpn_out = model.rpn_head(feat)                 # [1, 5, 8, 8]
    from paddle1_tpu.ops import manip_ops
    M = anchors.shape[0]
    rpn_flat = manip_ops.reshape(
        manip_ops.transpose(rpn_out, [0, 2, 3, 1]), [1, M, 5])
    bbox_pred = rpn_flat[:, :, :4]
    cls_logits = rpn_flat[:, :, 4:5]

    # --- RPN loss ---
    ps, pl, tl, tb, iw = L.rpn_target_assign(
        bbox_pred, cls_logits, to_tensor(anchors), None,
        to_tensor(gt), None, to_tensor(info),
        gt_lengths=np.array([2], np.int64),
        rpn_batch_size_per_im=32, rpn_positive_overlap=0.5,
        rpn_negative_overlap=0.3, use_random=False)
    lbl = to_tensor(_np(tl).astype(np.float32))
    rpn_cls_loss = F.binary_cross_entropy_with_logits(ps, lbl)
    rpn_reg_loss = (L.smooth_l1(pl, tb, inside_weight=iw,
                                outside_weight=iw)).mean()

    # --- proposals (host) + second-stage sampling ---
    sc_map = _np(cls_logits).reshape(1, 8, 8, 1).transpose(0, 3, 1, 2)
    bd_map = _np(bbox_pred).reshape(1, 8, 8, 4).transpose(0, 3, 1, 2)
    rois, probs, rlens = L.generate_proposals(
        to_tensor(sc_map), to_tensor(bd_map), to_tensor(info),
        to_tensor(anchors.reshape(8, 8, 1, 4)),
        to_tensor(np.ones((8, 8, 1, 4), np.float32)),
        pre_nms_top_n=64, post_nms_top_n=12, nms_thresh=0.7,
        min_size=2.0)
    srois, slabels, stgt, siw, sow, slens = L.generate_proposal_labels(
        rois, to_tensor(gtc), None, to_tensor(gt), to_tensor(info),
        rois_lengths=np.asarray(rlens.numpy()), batch_size_per_im=16,
        fg_thresh=0.5, bg_thresh_hi=0.5, class_nums=model.num_classes,
        use_random=False)

    # --- ROI head over prroi-pooled features ---
    pooled = L.prroi_pool(feat, srois, spatial_scale=8.0 / 32.0,
                          pooled_height=2, pooled_width=2)
    R = pooled.shape[0]
    flat = manip_ops.reshape(pooled, [R, -1])
    hid = F.relu(model.roi_fc(flat))
    cls_logit = model.cls_head(hid)
    reg_pred = model.reg_head(hid)
    cls_loss = F.softmax_with_cross_entropy(
        cls_logit, to_tensor(_np(slabels).astype(np.int64))).mean()
    reg_loss = ((reg_pred - stgt) ** 2 * siw).sum() / max(R, 1)

    # --- mask head on fg rois ---
    mrois, has, mtgt, mlens = L.generate_mask_labels(
        to_tensor(info), None, None, [masks], srois, slabels,
        num_classes=model.num_classes, resolution=4,
        rois_lengths=np.asarray(slens.numpy()))
    mask_loss = to_tensor(np.float32(0.0))
    if _np(mtgt).shape[0]:
        mp = L.prroi_pool(feat, mrois, spatial_scale=8.0 / 32.0,
                          pooled_height=2, pooled_width=2)
        mlogits = model.mask_head(
            manip_ops.reshape(mp, [mp.shape[0], -1]))
        tgt = _np(mtgt).astype(np.float32)
        w = (tgt >= 0).astype(np.float32)
        mask_loss = (F.binary_cross_entropy_with_logits(
            mlogits, to_tensor(np.clip(tgt, 0, 1)), reduction="none")
            * to_tensor(w)).sum() / max(w.sum(), 1)

    return rpn_cls_loss + rpn_reg_loss + cls_loss + 0.1 * reg_loss \
        + mask_loss


@pytest.mark.slow  # ~55s of convergence soaks; the per-op detection
# suites (test_detection_ops/test_detection_train) keep the stage math
# covered in-tier (CI heavy step)
class TestTwoStageE2E:
    def test_pipeline_trains(self, scene):
        img, gt, gtc, info, masks = scene
        paddle.seed(11)
        model = TinyTwoStage()
        anchors = _anchors_8x8()
        opt = paddle.optimizer.Adam(learning_rate=2e-3,
                                    parameters=model.parameters())
        losses = []
        for step in range(12):
            loss = _train_step(model, img, gt, gtc, info, masks,
                               anchors)
            loss.backward()
            if step == 0:
                # gradients reached every stage (checked BEFORE the
                # clear: a stage silently detached would show zeros)
                for p, name in [(model.backbone[0].weight, "backbone"),
                                (model.rpn_head.weight, "rpn"),
                                (model.roi_fc.weight, "roi_fc"),
                                (model.cls_head.weight, "cls"),
                                (model.reg_head.weight, "reg"),
                                (model.mask_head.weight, "mask")]:
                    assert p.grad is not None, name
                    assert np.abs(_np(p.grad)).sum() > 0, name
            opt.step()
            opt.clear_grad()
            losses.append(float(np.asarray(loss.numpy())))
        assert losses[-1] < losses[0] * 0.8, losses

    def test_proposals_converge_toward_gt(self, scene):
        """After training, the RPN's top proposal overlaps a gt box."""
        img, gt, gtc, info, masks = scene
        paddle.seed(12)
        model = TinyTwoStage()
        anchors = _anchors_8x8()
        opt = paddle.optimizer.Adam(learning_rate=5e-3,
                                    parameters=model.parameters())
        for _ in range(40):
            loss = _train_step(model, img, gt, gtc, info, masks,
                               anchors)
            loss.backward()
            opt.step()
            opt.clear_grad()
        feat = model.backbone(to_tensor(img))
        rpn_out = model.rpn_head(feat)
        sc_map = _np(rpn_out)[:, 4:5]
        bd_map = _np(rpn_out)[:, :4]
        rois, probs, _ = L.generate_proposals(
            to_tensor(sc_map), to_tensor(bd_map), to_tensor(info),
            to_tensor(anchors.reshape(8, 8, 1, 4)),
            to_tensor(np.ones((8, 8, 1, 4), np.float32)),
            pre_nms_top_n=64, post_nms_top_n=3, nms_thresh=0.7,
            min_size=2.0)
        tops = _np(rois)

        def iou(a, b):
            ix = max(0, min(a[2], b[2]) - max(a[0], b[0]) + 1)
            iy = max(0, min(a[3], b[3]) - max(a[1], b[1]) + 1)
            inter = ix * iy
            aa = (a[2] - a[0] + 1) * (a[3] - a[1] + 1)
            bb = (b[2] - b[0] + 1) * (b[3] - b[1] + 1)
            return inter / (aa + bb - inter)
        best = max(iou(t, g) for t in tops for g in gt[0])
        assert best > 0.2, (tops, best)
