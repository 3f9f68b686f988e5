"""YOLOv3 detection model (the PaddleDetection-era baseline the reference
ships ops for: yolo_box_op, yolov3_loss_op, multiclass_nms_op).

DarkNet-53 backbone + FPN-style neck + per-scale heads; postprocess =
vision.ops.yolo_box + multiclass_nms. Anchor config matches the standard
COCO setup. Training uses :func:`yolov3_loss` (dense per-cell targets —
the reference's yolov3_loss_op semantics, vectorized)."""

from __future__ import annotations

from typing import List, Sequence

import numpy as np

from ...nn.layer_base import Layer
from ...nn.layer_conv_pool import Conv2D
from ...nn.layer_norm_act import BatchNorm2D, LeakyReLU, Sequential

__all__ = ["DarkNet53", "YOLOv3", "yolov3", "yolov3_loss"]

_ANCHORS = [10, 13, 16, 30, 33, 23, 30, 61, 62, 45, 59, 119, 116, 90,
            156, 198, 373, 326]
_MASKS = [[6, 7, 8], [3, 4, 5], [0, 1, 2]]


class ConvBNLeaky(Layer):
    def __init__(self, cin, cout, k, stride=1):
        super().__init__()
        self.conv = Conv2D(cin, cout, k, stride=stride, padding=k // 2,
                           bias_attr=False)
        self.bn = BatchNorm2D(cout)
        self.act = LeakyReLU(0.1)

    def forward(self, x):
        return self.act(self.bn(self.conv(x)))


class DarkBlock(Layer):
    def __init__(self, ch):
        super().__init__()
        self.conv1 = ConvBNLeaky(ch, ch // 2, 1)
        self.conv2 = ConvBNLeaky(ch // 2, ch, 3)

    def forward(self, x):
        return x + self.conv2(self.conv1(x))


class DarkNet53(Layer):
    """Backbone emitting C3/C4/C5 (reference-era darknet.py)."""

    def __init__(self, depths=(1, 2, 8, 8, 4)):
        super().__init__()
        self.stem = ConvBNLeaky(3, 32, 3)
        chans = [64, 128, 256, 512, 1024]
        stages = []
        cin = 32
        for ch, n in zip(chans, depths):
            blocks = [ConvBNLeaky(cin, ch, 3, stride=2)]
            blocks += [DarkBlock(ch) for _ in range(n)]
            stages.append(Sequential(*blocks))
            cin = ch
        self.stage1, self.stage2, self.stage3, self.stage4, self.stage5 = \
            stages

    def forward(self, x):
        x = self.stem(x)
        x = self.stage1(x)
        x = self.stage2(x)
        c3 = self.stage3(x)
        c4 = self.stage4(c3)
        c5 = self.stage5(c4)
        return c3, c4, c5


class YoloDetBlock(Layer):
    def __init__(self, cin, ch):
        super().__init__()
        self.body = Sequential(
            ConvBNLeaky(cin, ch, 1), ConvBNLeaky(ch, ch * 2, 3),
            ConvBNLeaky(ch * 2, ch, 1), ConvBNLeaky(ch, ch * 2, 3),
            ConvBNLeaky(ch * 2, ch, 1))
        self.tip = ConvBNLeaky(ch, ch * 2, 3)

    def forward(self, x):
        route = self.body(x)
        return route, self.tip(route)


class YOLOv3(Layer):
    def __init__(self, num_classes=80, anchors=None, anchor_masks=None):
        super().__init__()
        self.num_classes = num_classes
        self.anchors = anchors or _ANCHORS
        self.anchor_masks = anchor_masks or _MASKS
        self.backbone = DarkNet53()
        out_ch = 3 * (5 + num_classes)
        self.block5 = YoloDetBlock(1024, 512)
        self.block4 = YoloDetBlock(512 + 256, 256)
        self.block3 = YoloDetBlock(256 + 128, 128)
        self.route5 = ConvBNLeaky(512, 256, 1)
        self.route4 = ConvBNLeaky(256, 128, 1)
        self.head5 = Conv2D(1024, out_ch, 1)
        self.head4 = Conv2D(512, out_ch, 1)
        self.head3 = Conv2D(256, out_ch, 1)

    def forward(self, x):
        from ...nn import functional as F
        c3, c4, c5 = self.backbone(x)
        r5, t5 = self.block5(c5)
        p5 = self.head5(t5)
        u5 = F.interpolate(self.route5(r5), scale_factor=2, mode="nearest")
        from ...ops import manip_ops
        r4, t4 = self.block4(manip_ops.concat([u5, c4], axis=1))
        p4 = self.head4(t4)
        u4 = F.interpolate(self.route4(r4), scale_factor=2, mode="nearest")
        r3, t3 = self.block3(manip_ops.concat([u4, c3], axis=1))
        p3 = self.head3(t3)
        return [p5, p4, p3]     # strides 32, 16, 8

    def postprocess(self, outputs, img_size, conf_thresh=0.01,
                    nms_thresh=0.45, keep_top_k=100):
        """Decode + NMS one batch (host-side; the compiled path stops at
        the head outputs, matching the reference's deploy split).

        Pinned to the host CPU backend when one coexists with an
        accelerator: the decode+NMS loop is hundreds of small eager
        ops on a few boxes, each its own device dispatch (its cost on
        the v5e: not measured)."""
        import jax as _jax
        try:
            _cpu = _jax.devices("cpu")[0]
        except RuntimeError:
            _cpu = None
        if _cpu is not None and _jax.default_backend() != "cpu":
            from ...core.tensor import Tensor as _T

            def _host(t):
                a = np.asarray(t.numpy() if isinstance(t, _T) else t)
                return _T(_jax.device_put(a, _cpu))
            with _jax.default_device(_cpu):
                return self._postprocess_impl(
                    [_host(o) for o in outputs], _host(img_size),
                    conf_thresh, nms_thresh, keep_top_k)
        return self._postprocess_impl(outputs, img_size, conf_thresh,
                                      nms_thresh, keep_top_k)

    def _postprocess_impl(self, outputs, img_size, conf_thresh,
                          nms_thresh, keep_top_k):
        from .. import ops as V
        from ...ops import manip_ops
        all_boxes, all_scores = [], []
        for out, mask, stride in zip(outputs, self.anchor_masks,
                                     (32, 16, 8)):
            sub_anchors = []
            for m in mask:
                sub_anchors += self.anchors[2 * m:2 * m + 2]
            b, s = V.yolo_box(out, img_size, sub_anchors, self.num_classes,
                              conf_thresh, stride)
            all_boxes.append(b)
            all_scores.append(s)
        boxes = manip_ops.concat(all_boxes, axis=1)
        scores = manip_ops.concat(all_scores, axis=1)
        results = []
        for bi in range(boxes.shape[0]):
            res = V.multiclass_nms(
                boxes[bi], manip_ops.transpose(scores[bi], [1, 0]),
                score_threshold=conf_thresh, nms_threshold=nms_thresh,
                keep_top_k=keep_top_k)
            results.append(res)
        return results


def yolov3(pretrained=False, num_classes=80, **kwargs):
    if pretrained:
        raise NotImplementedError(
            "pretrained weights are not bundled (no network egress)")
    return YOLOv3(num_classes=num_classes, **kwargs)


def yolov3_loss(outputs, gt_boxes, gt_labels, anchors=None,
                anchor_masks=None, num_classes=80, ignore_thresh=0.7,
                downsample_ratios=(32, 16, 8), gt_scores=None,
                use_label_smooth=False, scale_x_y=1.0):
    """YOLOv3 training loss (reference yolov3_loss_op), vectorized.

    gt_boxes: [B, G, 4] cxcywh normalized to [0,1]; gt_labels: [B, G]
    int (−1 pads). ``gt_scores`` [B, G] weights each gt's loss terms
    (mixup); ``use_label_smooth`` applies the op's
    min(1/C, 1/40) positive/negative smoothing; ``scale_x_y`` decodes
    x = s·sigmoid(tx) − (s−1)/2 (yolov3_loss_op.h:287-291,390).
    Returns scalar loss summing obj/cls/box terms.
    """
    import jax.numpy as jnp

    from ...autograd.engine import apply
    from ...core.tensor import Tensor
    anchors = np.asarray(anchors or _ANCHORS, np.float32).reshape(-1, 2)
    anchor_masks = anchor_masks or _MASKS
    if use_label_smooth:
        sw = min(1.0 / num_classes, 1.0 / 40)
        label_pos, label_neg = 1.0 - sw, sw
    else:
        label_pos, label_neg = 1.0, 0.0
    sxy = float(scale_x_y)

    def one_level(pred, gtb, gtl, gts, mask, ds):
        na = len(mask)
        b, _, h, w = pred.shape
        pred = pred.reshape(b, na, 5 + num_classes, h, w)
        tx, ty = pred[:, :, 0], pred[:, :, 1]
        tw, th = pred[:, :, 2], pred[:, :, 3]
        tobj = pred[:, :, 4]
        tcls = pred[:, :, 5:]
        sub = anchors[mask]                       # [na, 2]

        # build dense targets: for each gt, which cell/anchor owns it
        gx = gtb[:, :, 0] * w                     # [B, G]
        gy = gtb[:, :, 1] * h
        gw = gtb[:, :, 2]
        gh = gtb[:, :, 3]
        valid = (gtl >= 0) & (gw > 0)
        ci = jnp.clip(gx.astype(jnp.int32), 0, w - 1)
        cj = jnp.clip(gy.astype(jnp.int32), 0, h - 1)
        # best anchor per gt by wh-IoU against ALL anchors, then keep
        # those assigned to this level's mask
        gwh = jnp.stack([gw, gh], -1)[..., None, :] * jnp.asarray(
            [w * ds, h * ds], jnp.float32)        # pixels [B,G,1,2]
        awh = jnp.asarray(anchors, jnp.float32)[None, None]  # [1,1,A,2]
        inter = jnp.minimum(gwh, awh).prod(-1)
        union = gwh.prod(-1) + awh.prod(-1) - inter
        best = jnp.argmax(inter / jnp.maximum(union, 1e-10), axis=-1)
        mask_arr = jnp.asarray(mask)
        own = (best[..., None] == mask_arr[None, None, :])  # [B,G,na]
        sel = valid[..., None] & own

        # ignore_thresh (reference yolov3_loss_op): decode every predicted
        # box and drop the no-object penalty where its best IoU against
        # any gt exceeds the threshold — those cells are "almost right",
        # not negatives.
        gxn = jnp.arange(w, dtype=jnp.float32)[None, None, None, :]
        gyn = jnp.arange(h, dtype=jnp.float32)[None, None, :, None]
        sx = sxy * jax.nn.sigmoid(tx) - 0.5 * (sxy - 1.0)
        sy = sxy * jax.nn.sigmoid(ty) - 0.5 * (sxy - 1.0)
        pcx = (sx + gxn) / w
        pcy = (sy + gyn) / h
        paw = sub[:, 0][None, :, None, None]
        pah = sub[:, 1][None, :, None, None]
        pw_ = jnp.exp(jnp.clip(tw, -10, 10)) * paw / (w * ds)
        ph_ = jnp.exp(jnp.clip(th, -10, 10)) * pah / (h * ds)
        pred_box = jnp.stack([pcx - pw_ / 2, pcy - ph_ / 2,
                              pcx + pw_ / 2, pcy + ph_ / 2], -1)
        gt_xyxy = jnp.stack([gtb[:, :, 0] - gw / 2, gtb[:, :, 1] - gh / 2,
                             gtb[:, :, 0] + gw / 2, gtb[:, :, 1] + gh / 2],
                            -1)                          # [B,G,4]
        pb = pred_box.reshape(b, -1, 4)                  # [B,naHW,4]
        lt = jnp.maximum(pb[:, :, None, :2], gt_xyxy[:, None, :, :2])
        rb = jnp.minimum(pb[:, :, None, 2:], gt_xyxy[:, None, :, 2:])
        whi = jnp.clip(rb - lt, 0)
        inter_p = whi[..., 0] * whi[..., 1]
        area_p = ((pb[:, :, 2] - pb[:, :, 0]) *
                  (pb[:, :, 3] - pb[:, :, 1]))[:, :, None]
        area_g = (gw * gh)[:, None, :]
        iou_pg = inter_p / jnp.maximum(area_p + area_g - inter_p, 1e-10)
        iou_pg = jnp.where(valid[:, None, :], iou_pg, 0.0)
        best_iou = jnp.max(iou_pg, axis=2).reshape(b, na, h, w)

        obj_target = jnp.zeros((b, na, h, w))
        cls_target = jnp.zeros((b, na, num_classes, h, w))
        box_w = jnp.zeros((b, na, h, w))
        txt = jnp.zeros((b, na, h, w))
        tyt = jnp.zeros((b, na, h, w))
        twt = jnp.zeros((b, na, h, w))
        tht = jnp.zeros((b, na, h, w))
        bidx = jnp.arange(b)[:, None, None]
        aidx = jnp.arange(na)[None, None, :]
        bb = jnp.broadcast_to(bidx, sel.shape)
        aa = jnp.broadcast_to(aidx, sel.shape)
        jj = jnp.broadcast_to(cj[..., None], sel.shape)
        ii = jnp.broadcast_to(ci[..., None], sel.shape)
        selw = sel.astype(jnp.float32)
        # per-gt mixup score rides every positive contribution
        # (yolov3_loss_op.h:390 — score multiplies the gt's terms)
        selws = selw * jnp.broadcast_to(gts[..., None], sel.shape)
        obj_target = obj_target.at[bb, aa, jj, ii].max(selw)
        # with scale_x_y, the sigmoid target solves
        # s·sig(t) − (s−1)/2 = frac  →  sig(t) = (frac + (s−1)/2)/s
        fx = (gx - jnp.floor(gx) + 0.5 * (sxy - 1.0)) / sxy
        fy = (gy - jnp.floor(gy) + 0.5 * (sxy - 1.0)) / sxy
        txt = txt.at[bb, aa, jj, ii].add(
            selw * jnp.broadcast_to(
                jnp.clip(fx, 0.0, 1.0)[..., None], sel.shape))
        tyt = tyt.at[bb, aa, jj, ii].add(
            selw * jnp.broadcast_to(
                jnp.clip(fy, 0.0, 1.0)[..., None], sel.shape))
        aw = sub[:, 0][None, None, :]
        ah = sub[:, 1][None, None, :]
        twt = twt.at[bb, aa, jj, ii].add(
            selw * jnp.log(jnp.maximum(
                gw[..., None] * w * ds / aw, 1e-9)))
        tht = tht.at[bb, aa, jj, ii].add(
            selw * jnp.log(jnp.maximum(
                gh[..., None] * h * ds / ah, 1e-9)))
        box_w = box_w.at[bb, aa, jj, ii].max(selws)
        cls_oh = jax.nn.one_hot(jnp.clip(gtl, 0), num_classes)  # [B,G,C]
        smooth_oh = cls_oh * label_pos + (1.0 - cls_oh) * label_neg
        cls_target = cls_target.at[
            bb, aa, :, jj, ii].max(selw[..., None] *
                                   jnp.broadcast_to(
                                       smooth_oh[:, :, None],
                                       sel.shape + (num_classes,)))

        bce = lambda logit, tgt, wgt: jnp.sum(
            wgt * (jnp.maximum(logit, 0) - logit * tgt +
                   jnp.log1p(jnp.exp(-jnp.abs(logit)))))
        loss_xy = bce(tx, txt, box_w) + bce(ty, tyt, box_w)
        loss_wh = jnp.sum(box_w * ((tw - twt) ** 2 + (th - tht) ** 2)) * 0.5
        # objectness: positives count at their gt score; negatives only
        # where the best IoU vs gt stays below ignore_thresh
        obj_w = jnp.where(obj_target > 0, jnp.maximum(box_w, 1e-8),
                          (best_iou < ignore_thresh).astype(jnp.float32))
        loss_obj = bce(tobj, obj_target, obj_w)
        loss_cls = bce(tcls, cls_target,
                       jnp.broadcast_to(box_w[:, :, None], cls_target.shape))
        return loss_xy + loss_wh + loss_obj + loss_cls

    import jax

    def f(gtb, gtl, gts, *preds):
        total = 0.0
        for pred, mask, ds in zip(preds, anchor_masks, downsample_ratios):
            total = total + one_level(pred, gtb, gtl, gts, mask, ds)
        return total / preds[0].shape[0]
    from ...core.tensor import to_tensor as tt
    if gt_scores is None:
        gt_arr = (gt_boxes.numpy() if hasattr(gt_boxes, "numpy")
                  else gt_boxes)
        gt_scores = np.ones(np.asarray(gt_arr).shape[:2], np.float32)
    tensors = (gt_boxes, gt_labels, gt_scores) + tuple(outputs)
    return apply("yolov3_loss", f,
                 tuple(t if isinstance(t, Tensor) else tt(t)
                       for t in tensors))
