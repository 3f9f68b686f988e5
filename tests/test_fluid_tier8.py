"""Fluid tier 8: ctc_greedy_decoder,
similarity_focus, filter_by_instag, reorder_lod_tensor_by_rank,
load/read_file, inplace_abn, detection_output, box_decoder_and_assign,
collect_fpn_proposals, locality_aware_nms."""

import numpy as np
import pytest

import paddle1_tpu as paddle
import paddle1_tpu.fluid.layers as L
from paddle1_tpu.core.tensor import to_tensor


def _np(t):
    return np.asarray(t.numpy())


class TestCtcGreedyDecoder:
    def test_merge_repeats_drop_blanks(self):
        # logits whose argmax path is [b, 1, 1, b, 2, 2] -> [1, 2]
        path = [[0, 1, 1, 0, 2, 2], [3, 3, 0, 0, 0, 0]]
        C = 4
        x = np.full((2, 6, C), -5.0, np.float32)
        for b, row in enumerate(path):
            for t, tok in enumerate(row):
                x[b, t, tok] = 5.0
        dec, lens = L.ctc_greedy_decoder(to_tensor(x), blank=0)
        d, ln = _np(dec), _np(lens)
        assert ln.tolist() == [[2], [1]]
        assert d[0, :2].tolist() == [1, 2]
        assert d[1, :1].tolist() == [3]
        assert (d[1, 1:] == 0).all()  # padding_value default 0

    def test_input_length_truncates(self):
        x = np.full((1, 4, 3), -5.0, np.float32)
        for t, tok in enumerate([1, 2, 1, 2]):
            x[0, t, tok] = 5.0
        dec, lens = L.ctc_greedy_decoder(
            to_tensor(x), blank=0,
            input_length=np.array([2], np.int64))
        assert _np(lens).tolist() == [[2]]
        assert _np(dec)[0].tolist()[:2] == [1, 2]


class TestSimilarityFocus:
    def test_reference_docstring_example(self):
        x = np.array(
            [[[[0.8, 0.1], [0.4, 0.5]],
              [[0.9, 0.7], [0.9, 0.9]],
              [[0.8, 0.9], [0.1, 0.2]]],
             [[[0.2, 0.5], [0.3, 0.4]],
              [[0.9, 0.7], [0.8, 0.4]],
              [[0.0, 0.2], [0.4, 0.7]]]], np.float32)
        out = _np(L.similarity_focus(to_tensor(x), axis=1,
                                     indexes=[0]))
        ref0 = np.array([[1.0, 0.0], [0.0, 1.0]], np.float32)
        ref1 = np.array([[0.0, 1.0], [1.0, 0.0]], np.float32)
        for c in range(3):
            np.testing.assert_array_equal(out[0, c], ref0)
            np.testing.assert_array_equal(out[1, c], ref1)


class TestFilterByInstag:
    def test_reference_example(self):
        ins = np.arange(8, dtype=np.float32).reshape(4, 2)
        tags = [[0, 1], [1, 3], [0, 3], [2, 6]]
        out, w = L.filter_by_instag(to_tensor(ins), tags,
                                    to_tensor(np.array([1], np.int64)))
        np.testing.assert_array_equal(_np(out), ins[[0, 1]])
        np.testing.assert_array_equal(_np(w), np.ones((2, 1)))

    def test_empty_result_contract(self):
        ins = np.ones((2, 3), np.float32)
        out, w = L.filter_by_instag(
            to_tensor(ins), [[5], [6]],
            to_tensor(np.array([9], np.int64)), out_val_if_empty=7)
        assert (_np(out) == 7).all() and _np(out).shape == (1, 3)
        assert _np(w).tolist() == [[0.0]]

    def test_padded_array_tags(self):
        ins = np.eye(3, dtype=np.float32)
        tags = np.array([[1, -1], [2, 3], [4, -1]], np.int64)
        out, w = L.filter_by_instag(to_tensor(ins), tags,
                                    np.array([3, 4], np.int64))
        np.testing.assert_array_equal(_np(out), ins[[1, 2]])


class TestReorderByRank:
    def test_descending_length_order(self):
        x = np.arange(12, dtype=np.float32).reshape(4, 3)
        lens = np.array([2, 5, 3, 5], np.int64)
        out = _np(L.reorder_lod_tensor_by_rank(to_tensor(x), lens))
        np.testing.assert_array_equal(out, x[[1, 3, 2, 0]])  # stable


class TestLoadReadFile:
    def test_load_roundtrip(self, tmp_path):
        val = to_tensor(np.arange(6, dtype=np.float32).reshape(2, 3))
        p = str(tmp_path / "var.pd")
        paddle.save(val, p)
        out = to_tensor(np.zeros((2, 3), np.float32))
        L.load(out, p)
        np.testing.assert_array_equal(_np(out),
                                      np.arange(6).reshape(2, 3))

    def test_read_file(self, tmp_path):
        p = tmp_path / "blob.bin"
        p.write_bytes(bytes([1, 2, 250]))
        out = _np(L.read_file(str(p)))
        assert out.dtype == np.uint8
        assert out.tolist() == [1, 2, 250]


class TestInplaceAbn:
    def test_equals_bn_plus_activation(self):
        rng = np.random.default_rng(0)
        x = rng.standard_normal((2, 3, 4, 4)).astype(np.float32)
        a = L.inplace_abn(to_tensor(x), act="leaky_relu",
                          act_alpha=0.2, name="abn1")
        b = L.batch_norm(to_tensor(x), name="abn2")
        import paddle1_tpu.nn.functional as F
        ref = F.leaky_relu(b, negative_slope=0.2)
        np.testing.assert_allclose(_np(a), _np(ref), rtol=2e-5,
                                   atol=2e-6)

    def test_unsupported_act_teaches(self):
        with pytest.raises(Exception, match="leaky_relu"):
            L.inplace_abn(to_tensor(np.zeros((1, 2, 2, 2),
                                             np.float32)), act="relu")

    def test_is_test_uses_moving_stats(self):
        rng = np.random.default_rng(5)
        x1 = rng.standard_normal((4, 2, 3, 3)).astype(np.float32)
        # one training pass updates the moving stats
        L.inplace_abn(to_tensor(x1), name="abn_t")
        x2 = rng.standard_normal((4, 2, 3, 3)).astype(np.float32) + 3.0
        a = _np(L.inplace_abn(to_tensor(x2), is_test=True,
                              name="abn_t"))
        b = _np(L.inplace_abn(to_tensor(x2), is_test=False,
                              name="abn_t"))
        # eval normalizes with moving stats (mean≈0), not the shifted
        # batch stats — outputs must differ
        assert np.abs(a - b).max() > 0.1


class TestDetectionOutput:
    def test_decode_and_nms(self):
        # two priors, one clear detection per class
        pb = np.array([[0.1, 0.1, 0.3, 0.3], [0.6, 0.6, 0.9, 0.9]],
                      np.float32)
        pv = np.full((2, 4), 0.1, np.float32)
        loc = np.zeros((1, 2, 4), np.float32)  # decode to the priors
        scores = np.array([[[0.05, 0.9, 0.05], [0.05, 0.05, 0.9]]],
                          np.float32)
        outs = L.detection_output(to_tensor(loc), to_tensor(scores),
                                  to_tensor(pb), to_tensor(pv),
                                  background_label=0,
                                  score_threshold=0.5)
        assert isinstance(outs, list) and len(outs) == 1
        o = _np(outs[0])
        assert o.shape[0] == 2
        labels = set(o[:, 0].astype(int).tolist())
        assert labels == {1, 2}
        # decoded boxes equal the priors (zero deltas)
        row1 = o[o[:, 0] == 1][0]
        np.testing.assert_allclose(row1[2:], pb[0], atol=1e-5)


class TestBoxDecoderAndAssign:
    def test_assign_picks_argmax_class(self):
        pb = np.array([[0, 0, 9, 9]], np.float32)
        pv = np.ones((1, 4), np.float32)
        # class 0 deltas zero; class 1 shifts right by 1 width
        tb = np.array([[0, 0, 0, 0, 1.0, 0, 0, 0]], np.float32)
        sc = np.array([[0.2, 0.8]], np.float32)
        dec, assigned = L.box_decoder_and_assign(
            to_tensor(pb), to_tensor(pv), to_tensor(tb),
            to_tensor(sc), box_clip=4.135)
        d = _np(dec)
        np.testing.assert_allclose(d[0, :4], [0, 0, 9, 9], atol=1e-4)
        a = _np(assigned)
        np.testing.assert_allclose(a[0], d[0, 4:], atol=1e-5)


class TestCollectFpn:
    def test_topk_across_levels(self):
        r1 = np.array([[0, 0, 1, 1], [1, 1, 2, 2]], np.float32)
        r2 = np.array([[2, 2, 3, 3]], np.float32)
        s1 = np.array([[0.9], [0.1]], np.float32)
        s2 = np.array([[0.5]], np.float32)
        out = _np(L.collect_fpn_proposals([to_tensor(r1),
                                           to_tensor(r2)],
                                          [to_tensor(s1),
                                           to_tensor(s2)], 2, 3, 2))
        np.testing.assert_array_equal(out, np.stack([r1[0], r2[0]]))

    def test_batched_per_image_topk(self):
        # two images: level rows partitioned by per-level lengths —
        # the top-k must NOT mix images
        r1 = np.array([[0, 0, 1, 1], [9, 9, 10, 10]], np.float32)
        s1 = np.array([[0.9], [0.8]], np.float32)
        lens1 = np.array([1, 1], np.int64)
        r2 = np.array([[2, 2, 3, 3], [8, 8, 9, 9]], np.float32)
        s2 = np.array([[0.5], [0.95]], np.float32)
        lens2 = np.array([1, 1], np.int64)
        rois, out_lens = L.collect_fpn_proposals(
            [to_tensor(r1), to_tensor(r2)],
            [to_tensor(s1), to_tensor(s2)], 2, 3, 1,
            rois_lengths=[lens1, lens2])
        rv = _np(rois)
        assert _np(out_lens).tolist() == [1, 1]
        np.testing.assert_array_equal(rv[0], r1[0])  # img0 best: 0.9
        np.testing.assert_array_equal(rv[1], r2[1])  # img1 best: 0.95


class TestLocalityAwareNms:
    def test_adjacent_boxes_merge_weighted(self):
        b = np.array([[0, 0, 10, 10], [0.5, 0.5, 10.5, 10.5],
                      [50, 50, 60, 60]], np.float32)
        s = np.array([[0.8, 0.4, 0.9]], np.float32)
        out = _np(L.locality_aware_nms(to_tensor(b), to_tensor(s),
                                       score_threshold=0.1,
                                       nms_top_k=10, keep_top_k=10,
                                       nms_threshold=0.3))
        assert out.shape[0] == 2  # first two merged, third separate
        merged = out[np.argmax(out[:, 1])]
        # weighted average of the two overlapping boxes
        exp = (b[0] * 0.8 + b[1] * 0.4) / 1.2
        got_box = out[(out[:, 2] < 20)][0][2:]
        np.testing.assert_allclose(got_box, exp, atol=1e-4)


class TestMultivariateNormalDiag:
    def test_entropy_and_kl_closed_form(self):
        import math
        d1 = np.array([2.0, 3.0], np.float64)
        d2 = np.array([1.0, 1.5], np.float64)
        a = L.MultivariateNormalDiag(
            np.array([0.1, 0.2], np.float32),
            np.diag(d1).astype(np.float32))
        b = L.MultivariateNormalDiag(
            np.array([0.3, -0.1], np.float32),
            np.diag(d2).astype(np.float32))
        ent = float(_np(a.entropy()))
        ref_ent = 0.5 * (2 * (1 + math.log(2 * math.pi))
                         + math.log(d1.prod()))
        assert abs(ent - ref_ent) < 1e-5
        kl = float(_np(a.kl_divergence(b)))
        mu = np.array([0.3, -0.1]) - np.array([0.1, 0.2])
        ref_kl = 0.5 * ((d1 / d2).sum() + (mu ** 2 / d2).sum() - 2
                        + math.log(d2.prod() / d1.prod()))
        assert abs(kl - ref_kl) < 1e-5
