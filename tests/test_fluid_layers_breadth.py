"""fluid.layers breadth tier 2: namespace sweep
pinning coverage counts against the reference surface, plus functional
spot-checks of the newly mapped groups and the transpiler teaching
error."""

import os
import re

import numpy as np
import pytest

import paddle1_tpu.fluid as fluid
import paddle1_tpu.fluid.layers as L
from paddle1_tpu.core.tensor import to_tensor

REF = "/root/reference/python/paddle/fluid/layers"


def _reference_names():
    names = set()
    if not os.path.isdir(REF):
        return names
    for f in os.listdir(REF):
        if not f.endswith(".py") or f == "__init__.py":
            continue
        txt = open(os.path.join(REF, f), encoding="utf-8",
                   errors="replace").read()
        m = re.search(r"__all__\s*=\s*\[(.*?)\]", txt, re.S)
        if m:
            names.update(re.findall(r"['\"]([A-Za-z_0-9]+)['\"]",
                                    m.group(1)))
    return names


class TestNamespaceSweep:
    def test_coverage_counts(self):
        """Pin the classification like the 242-name top-level sweep:
        every reference name either resolves (mapped) or raises the
        teaching AttributeError — and the mapped share stays >= 200."""
        ref = _reference_names()
        if not ref:
            pytest.skip("reference tree unavailable")
        mapped, teaching = [], []
        for n in sorted(ref):
            try:
                getattr(L, n)
                mapped.append(n)
            except AttributeError as e:
                teaching.append(n)
                assert n in str(e), f"teaching error must name {n}"
        assert len(ref) >= 300            # surface didn't shrink
        assert len(mapped) >= 300, (len(mapped),
                                    "r5 mapping floor regressed")
        # the tier-2 groups are all mapped
        for n in """elementwise_max logical_and reduce_prod ones eye
                 linspace argsort gather_nd scatter squeeze stack split
                 where triu expand pad flatten transpose relu6
                 leaky_relu elu swish hard_sigmoid maxout prelu scale
                 l2_normalize label_smooth mse_loss huber_loss log_loss
                 kldiv_loss cos_sim sigmoid_cross_entropy_with_logits
                 dice_loss layer_norm group_norm instance_norm lrn
                 conv2d_transpose conv3d pool3d adaptive_pool2d
                 image_resize resize_bilinear pixel_shuffle grid_sampler
                 unfold yolo_box multiclass_nms prior_box box_coder
                 roi_align iou_similarity sequence_pad sequence_pool
                 sequence_softmax sequence_enumerate exponential_decay
                 piecewise_decay cosine_decay noam_decay linear_lr_warmup
                 rnn birnn GRUCell LSTMCell array_write array_read
                 tensor_array_to_tensor edit_distance""".split():
            assert n in mapped, n

    def test_still_teaching_by_design(self):
        """Block-based program-construction APIs stay loud teaching
        errors (py_reader became a real queue-backed reader in r5 —
        tests/test_fluid_reader.py)."""
        for n in ("StaticRNN", "DynamicRNN", "While", "Switch",
                  "IfElse"):
            with pytest.raises(AttributeError):
                getattr(L, n)


class TestMappedGroupsFunctional:
    def test_elementwise_compare_reduce(self):
        a = to_tensor(np.array([[1.0, 5.0], [3.0, 2.0]], np.float32))
        b = to_tensor(np.array([[2.0, 4.0], [3.0, 1.0]], np.float32))
        np.testing.assert_allclose(L.elementwise_max(a, b).numpy(),
                                   [[2, 5], [3, 2]])
        assert L.less_than(a, b).numpy().tolist() == [[True, False],
                                                      [False, False]]
        np.testing.assert_allclose(L.reduce_prod(a).numpy(), 30.0)
        assert bool(L.reduce_any(L.equal(a, b)).numpy())

    def test_creation_and_manipulation(self):
        e = L.eye(3)
        np.testing.assert_allclose(e.numpy(), np.eye(3, dtype=np.float32))
        r = L.range(0, 6, 2, "int64")
        assert r.numpy().tolist() == [0, 2, 4]
        x = to_tensor(np.arange(12, dtype=np.float32).reshape(3, 4))
        f = L.fill_constant_batch_size_like(x, [1, 2], "float32", 7.0)
        assert f.shape == [3, 2] and float(f.numpy()[0, 0]) == 7.0
        s = L.split(x, 2, dim=1)
        assert len(s) == 2 and s[0].shape == [3, 2]
        st = L.stack([x, x], axis=0)
        assert st.shape == [2, 3, 4]
        assert L.flatten(x, axis=2).shape == [12, 1]
        assert L.size(x).numpy() == 12

    def test_activations_and_scale(self):
        x = to_tensor(np.array([-2.0, 0.5, 9.0], np.float32))
        np.testing.assert_allclose(L.relu6(x).numpy(), [0, 0.5, 6.0])
        np.testing.assert_allclose(L.brelu(x, 0.0, 1.0).numpy(),
                                   [0, 0.5, 1.0])
        np.testing.assert_allclose(
            L.hard_sigmoid(x).numpy(),
            np.clip(np.array([-2, 0.5, 9]) * 0.2 + 0.5, 0, 1), rtol=1e-6)
        np.testing.assert_allclose(
            L.scale(x, scale=2.0, bias=1.0).numpy(), [-3, 2, 19])
        np.testing.assert_allclose(
            L.scale(x, scale=2.0, bias=1.0,
                    bias_after_scale=False).numpy(), [-2, 3, 20])

    def test_losses(self):
        p = to_tensor(np.array([[0.2], [0.8]], np.float32))
        y = to_tensor(np.array([[0.0], [1.0]], np.float32))
        ll = L.log_loss(p, y).numpy()
        np.testing.assert_allclose(
            ll, [[-np.log(0.8)], [-np.log(0.8)]], atol=2e-4)
        h = L.huber_loss(to_tensor(np.array([0.0, 3.0], np.float32)),
                         to_tensor(np.array([0.5, 0.0], np.float32)),
                         delta=1.0)
        np.testing.assert_allclose(h.numpy(), [0.125, 2.5], rtol=1e-6)
        d = L.edit_distance(
            to_tensor(np.array([[1, 2, 3]], np.int64)),
            to_tensor(np.array([[1, 3, 3]], np.int64)),
            normalized=False)
        assert float(d[0].numpy()[0, 0]) == 1.0

    def test_param_bearing_norm_layers_train(self):
        x = to_tensor(np.random.default_rng(0).standard_normal(
            (2, 4, 8)).astype(np.float32))
        out = L.layer_norm(x, begin_norm_axis=2)
        assert out.shape == [2, 4, 8]
        # normalized over the trailing axis
        np.testing.assert_allclose(np.asarray(out.numpy()).mean(-1),
                                   np.zeros((2, 4)), atol=1e-5)
        img = to_tensor(np.random.default_rng(1).standard_normal(
            (2, 6, 8, 8)).astype(np.float32))
        assert L.group_norm(img, groups=3).shape == [2, 6, 8, 8]
        assert L.instance_norm(img).shape == [2, 6, 8, 8]
        assert L.conv2d_transpose(img, 4, filter_size=3).shape[1] == 4

    def test_lr_decays_are_schedulers(self):
        from paddle1_tpu.optimizer.lr import LRScheduler
        import paddle1_tpu as paddle
        sched = L.exponential_decay(0.1, decay_steps=10, decay_rate=0.5)
        assert isinstance(sched, LRScheduler)
        m = paddle.nn.Linear(2, 2)
        opt = paddle.optimizer.SGD(learning_rate=L.piecewise_decay(
            [2], [0.1, 0.01]), parameters=m.parameters())
        assert abs(opt.get_lr() - 0.1) < 1e-9

    def test_rnn_runner(self):
        import paddle1_tpu as paddle
        cell = L.GRUCell(hidden_size=8)
        x = to_tensor(np.random.default_rng(0).standard_normal(
            (2, 5, 8)).astype(np.float32))
        out, state = L.rnn(cell, x)
        assert out.shape == [2, 5, 8]

    def test_tensor_array_ops(self):
        arr = L.create_array("float32")
        L.array_write(to_tensor(np.ones((2, 3), np.float32)), 0, arr)
        L.array_write(to_tensor(np.zeros((2, 3), np.float32)), 1, arr)
        assert int(L.array_length(arr).numpy()[0]) == 2
        assert L.array_read(arr, 1).numpy().sum() == 0
        t, sizes = L.tensor_array_to_tensor(arr, axis=0, use_stack=True)
        assert t.shape == [2, 2, 3]

    def test_detection_spotcheck(self):
        iou = L.iou_similarity(
            to_tensor(np.array([[0, 0, 10, 10]], np.float32)),
            to_tensor(np.array([[0, 0, 10, 10], [20, 20, 30, 30]],
                               np.float32)))
        np.testing.assert_allclose(iou.numpy(), [[1.0, 0.0]], atol=1e-6)

    def test_space_to_depth_and_shuffle_channel(self):
        x = to_tensor(np.arange(16, dtype=np.float32).reshape(1, 1, 4, 4))
        y = L.space_to_depth(x, 2)
        assert y.shape == [1, 4, 2, 2]
        c = to_tensor(np.arange(8, dtype=np.float32).reshape(1, 8, 1, 1))
        s = L.shuffle_channel(c, group=2)
        assert s.numpy().reshape(-1).tolist() == [0, 4, 1, 5, 2, 6, 3, 7]


class TestTranspilerSurface:
    # r5: the transpiler became a REAL mapping onto the PS runtime —
    # the e2e train flow is tests/test_transpiler_ps.py; here the
    # surface-level contracts
    def test_transpile_without_net_teaches(self, monkeypatch):
        from paddle1_tpu.core.errors import PreconditionNotMetError
        from paddle1_tpu.fluid import layers as fl
        # other tests in this file create implicit params; an empty
        # registry is the condition under test
        monkeypatch.setattr(fl, "_implicit_registry", {})
        t = fluid.DistributeTranspiler()
        with pytest.raises(PreconditionNotMetError, match="parameters"):
            t.transpile(trainer_id=0, pservers="127.0.0.1:6174",
                        trainers=2)

    def test_programs_require_transpile_first(self):
        from paddle1_tpu.core.errors import PreconditionNotMetError
        t = fluid.DistributeTranspiler()
        with pytest.raises(PreconditionNotMetError, match="transpile"):
            t.get_trainer_program()
        with pytest.raises(PreconditionNotMetError, match="transpile"):
            t.get_pserver_program("127.0.0.1:6174")

    def test_memory_optimize_noop(self):
        assert fluid.transpiler.memory_optimize() is None


class TestReviewRegressions:
    def test_elementwise_max_mid_axis_broadcast(self):
        x = to_tensor(np.zeros((2, 3, 4), np.float32))
        y = to_tensor(np.array([1.0, 2.0, 3.0], np.float32))
        out = L.elementwise_max(x, y, axis=1)  # [C] broadcasts on dim 1
        assert out.shape == [2, 3, 4]
        np.testing.assert_allclose(np.asarray(out.numpy())[0, :, 0],
                                   [1, 2, 3])

    def test_unique_returns_real_index_mapping(self):
        u, idx = L.unique(to_tensor(np.array([2, 3, 2], np.int64)))
        uv = np.asarray(u.numpy())
        iv = np.asarray(idx.numpy())
        np.testing.assert_array_equal(uv[iv],
                                      np.array([2, 3, 2]))
        u2, idx2, counts = L.unique_with_counts(
            to_tensor(np.array([5, 5, 7], np.int64)))
        assert np.asarray(counts.numpy()).tolist() == [2, 1]
        np.testing.assert_array_equal(np.asarray(u2.numpy())[
            np.asarray(idx2.numpy())], np.array([5, 5, 7]))

    def test_bpr_loss_excludes_self_term(self):
        # two classes, logits equal => only the self term and one
        # diff=0 term... construct: pos=class0, score diff pos-other = 1
        x = to_tensor(np.array([[2.0, 1.0]], np.float32))
        y = to_tensor(np.array([[0]], np.int64))
        loss = float(np.asarray(L.bpr_loss(x, y).numpy())[0, 0])
        expect = -np.log(1.0 / (1.0 + np.exp(-1.0)))  # only pos-vs-other
        assert abs(loss - expect) < 1e-5, (loss, expect)

    def test_sigmoid_still_layers_version(self):
        # the star import must not shadow layers.py's own definitions
        import paddle1_tpu.fluid.layers as LL
        import inspect
        assert "layers_ext" not in inspect.getsourcefile(LL.sigmoid)

    def test_lr_decay_staircase_semantics(self):
        sched = L.natural_exp_decay(0.1, decay_steps=1000,
                                    decay_rate=0.5, staircase=True)
        for _ in range(5):
            sched.step()
        assert abs(sched() - 0.1) < 1e-9  # still inside the first stair
        sched2 = L.inverse_time_decay(0.1, decay_steps=2,
                                      decay_rate=1.0, staircase=True)
        sched2.step(); sched2.step()  # step=2 -> floor(2/2)=1 -> lr/2
        assert abs(sched2() - 0.05) < 1e-9

    def test_cumsum_reverse_exclusive(self):
        x = to_tensor(np.array([1.0, 2.0, 3.0], np.float32))
        np.testing.assert_allclose(L.cumsum(x, reverse=True).numpy(),
                                   [6, 5, 3])
        np.testing.assert_allclose(L.cumsum(x, exclusive=True).numpy(),
                                   [0, 1, 3])
        np.testing.assert_allclose(
            L.cumsum(x, exclusive=True, reverse=True).numpy(), [5, 3, 0])

    def test_sum_single_tensor_passes_through(self):
        x = to_tensor(np.ones((2, 3), np.float32))
        assert L.sum(x).shape == [2, 3]
        assert float(L.sum([x, x]).numpy()[0, 0]) == 2.0

    def test_sequence_expand_as_needs_lengths(self):
        from paddle1_tpu.core.errors import InvalidArgumentError
        x = to_tensor(np.ones((2, 3), np.float32))
        with pytest.raises(InvalidArgumentError, match="lengths"):
            L.sequence_expand_as(x, x)

    def test_prelu_element_mode_teaches(self):
        from paddle1_tpu.core.errors import UnimplementedError
        x = to_tensor(np.ones((1, 2, 3), np.float32))
        with pytest.raises(UnimplementedError, match="element"):
            L.prelu(x, mode="element")


class TestTier3:
    def test_mean_iou_counts(self):
        pred = to_tensor(np.array([0, 0, 1, 1], np.int64))
        lab = to_tensor(np.array([0, 1, 1, 1], np.int64))
        miou, wrong, correct = L.mean_iou(pred, lab, 2)
        # class0: corr 1, union 2 -> 0.5; class1: corr 2, union 3 -> 2/3
        np.testing.assert_allclose(float(miou.numpy()),
                                   (0.5 + 2 / 3) / 2, rtol=1e-6)
        assert np.asarray(correct.numpy()).tolist() == [1, 2]
        assert np.asarray(wrong.numpy()).tolist() == [1, 0]

    def test_case_and_switch_case(self):
        t, f = to_tensor(np.array(True)), to_tensor(np.array(False))
        out = L.case([(f, lambda: 1), (t, lambda: 2)],
                     default=lambda: 3)
        assert out == 2
        assert L.switch_case(to_tensor(np.array(1)),
                             {0: lambda: "a", 1: lambda: "b"}) == "b"
        assert L.switch_case(to_tensor(np.array(9)),
                             {0: lambda: "a"},
                             default=lambda: "d") == "d"

    def test_assert_and_print(self):
        x = to_tensor(np.ones(3, np.float32))
        assert L.Print(x, message="dbg") is x
        L.Assert(to_tensor(np.array(True)))
        with pytest.raises(AssertionError):
            L.Assert(to_tensor(np.array(False)),
                     data=[to_tensor(np.arange(3))])

    def test_distributions(self):
        n = L.Normal(0.0, 1.0)
        s = n.sample([4])
        assert list(s.shape)[:1] == [4]
        u = L.Uniform(0.0, 2.0)
        vals = np.asarray(u.sample([100]).numpy())
        assert (vals >= 0).all() and (vals <= 2).all()
        c = L.Categorical(to_tensor(np.array([1.0, 1.0, 1.0],
                                             np.float32)))
        assert c is not None

    def test_auc_functional(self):
        scores = np.array([[0.1, 0.9], [0.8, 0.2], [0.3, 0.7],
                           [0.6, 0.4]], np.float32)
        labels = np.array([1, 0, 1, 0], np.int64)
        v, stat = L.auc(to_tensor(scores), to_tensor(labels))
        assert float(v.numpy()) == 1.0  # perfectly separable


class TestTier4:
    def test_hsigmoid_trains(self):
        x = to_tensor(np.random.default_rng(0).standard_normal(
            (4, 8)).astype(np.float32))
        y = to_tensor(np.array([0, 1, 2, 3], np.int64))
        loss = L.hsigmoid(x, y, num_classes=6)
        assert loss.shape[0] == 4
        loss.sum().backward()

    def test_bilinear_tensor_product(self):
        x = to_tensor(np.ones((2, 3), np.float32))
        y = to_tensor(np.ones((2, 5), np.float32))
        out = L.bilinear_tensor_product(x, y, size=4)
        assert out.shape == [2, 4]

    def test_fsp_matrix(self):
        a = to_tensor(np.ones((2, 3, 4, 4), np.float32))
        b = to_tensor(np.full((2, 5, 4, 4), 2.0, np.float32))
        out = L.fsp_matrix(a, b)
        assert out.shape == [2, 3, 5]
        np.testing.assert_allclose(np.asarray(out.numpy()), 2.0)

    def test_row_conv_lookahead(self):
        x = to_tensor(np.eye(4, dtype=np.float32).reshape(1, 4, 4))
        out = L.row_conv(x, future_context_size=1)
        assert out.shape == [1, 4, 4]

    def test_im2sequence_patches(self):
        x = to_tensor(np.arange(16, dtype=np.float32).reshape(1, 1, 4, 4))
        out = L.im2sequence(x, filter_size=2, stride=2)
        assert out.shape == [1, 4, 4]  # 4 patches of 1*2*2

    def test_center_loss_updates_centers(self):
        feats = to_tensor(np.ones((4, 3), np.float32))
        labels = to_tensor(np.zeros((4,), np.int64))
        losses = []
        for _ in range(2):  # same site across two passes
            l = L.center_loss(feats, labels, num_classes=2, alpha=0.5)
            losses.append(float(l.numpy().sum()))
            L.reset_parameter_pass()  # end of pass (no backward here)
        # centers moved toward the features: loss decreased
        assert losses[1] < losses[0], losses

    def test_sampling_id_range(self):
        probs = to_tensor(np.array([[0.0, 1.0, 0.0]] * 8, np.float32))
        ids = np.asarray(L.sampling_id(probs).numpy())
        assert (ids == 1).all()

    def test_anchor_generator_shapes(self):
        fmap = to_tensor(np.zeros((1, 8, 4, 6), np.float32))
        anchors, var = L.anchor_generator(
            fmap, anchor_sizes=[64.0], aspect_ratios=[1.0],
            stride=[16.0, 16.0])
        assert anchors.shape == [4, 6, 1, 4]
        a = np.asarray(anchors.numpy())
        # reference convention: center offset*(stride-1)=7.5, corners
        # +-(w-1)/2 with w = round(sqrt(256/1)) * 64/16 = 64
        np.testing.assert_allclose(a[0, 0, 0], [-24, -24, 39, 39])
        assert var.shape == [4, 6, 1, 4]

    def test_bipartite_match_greedy(self):
        d = to_tensor(np.array([[0.9, 0.1],
                                [0.8, 0.7]], np.float32))
        idx, dist = L.bipartite_match(d)
        iv = np.asarray(idx.numpy())[0]
        assert iv[0] == 0 and iv[1] == 1   # mutual-best then next-best
        np.testing.assert_allclose(np.asarray(dist.numpy())[0],
                                   [0.9, 0.7])

    def test_density_prior_box_counts(self):
        fmap = to_tensor(np.zeros((1, 3, 2, 2), np.float32))
        boxes, var = L.density_prior_box(
            fmap, densities=[2], fixed_sizes=[32.0],
            fixed_ratios=[1.0], steps=[16.0, 16.0], clip=True,
            flatten_to_2d=True)
        # 2x2 cells x density^2(4) boxes = 16
        assert boxes.shape == [16, 4]
        b = np.asarray(boxes.numpy())
        assert (b >= 0).all() and (b <= 1).all()

    def test_teacher_student_loss_runs(self):
        x = to_tensor(np.array([0.5, -0.5], np.float32))
        y = to_tensor(np.array([1.0, 0.0], np.float32))
        out = L.teacher_student_sigmoid_loss(x, y)
        assert out.shape == [2]

    def test_teacher_student_piecewise_values(self):
        x = np.array([1.0, 1.0, 1.0, 1.0], np.float32)
        y = np.array([-2.0, -0.5, 0.5, 2.0], np.float32)
        out = np.asarray(L.teacher_student_sigmoid_loss(
            to_tensor(x), to_tensor(y)).numpy())
        l1p = np.log1p(np.exp(1.0))
        np.testing.assert_allclose(
            out, [l1p, l1p - 1.0, 2 * l1p - 0.5, 2 * l1p - 2.0],
            rtol=1e-5)

    def test_row_conv_truncates_at_sequence_end(self):
        x = np.zeros((1, 4, 2), np.float32)
        x[0, 3] = 99.0                      # padding content
        out = np.asarray(L.row_conv(
            to_tensor(x), future_context_size=2,
            lengths=to_tensor(np.array([3], np.int64))).numpy())
        # valid positions must not see the padding frame at t=3
        assert np.isfinite(out).all() and (np.abs(out[0, :3]) < 50).all()

    def test_density_prior_box_clamps_unconditionally(self):
        fmap = to_tensor(np.zeros((1, 3, 2, 2), np.float32))
        boxes, _ = L.density_prior_box(
            fmap, densities=[1], fixed_sizes=[64.0],
            fixed_ratios=[1.0], steps=[16.0, 16.0], clip=False,
            flatten_to_2d=True)
        b = np.asarray(boxes.numpy())
        assert (b >= 0).all() and (b <= 1).all()

    def test_sampling_id_seeded_reproducible(self):
        probs = to_tensor(np.full((4, 3), 1 / 3, np.float32))
        a = np.asarray(L.sampling_id(probs, seed=7).numpy())
        b = np.asarray(L.sampling_id(probs, seed=7).numpy())
        np.testing.assert_array_equal(a, b)

    def test_center_loss_centers_not_in_autograd(self):
        feats = to_tensor(np.ones((2, 3), np.float32))
        feats.stop_gradient = False
        labels = to_tensor(np.zeros((2,), np.int64))
        loss = L.center_loss(feats, labels, num_classes=2, alpha=0.0,
                             update_center=False)
        loss.sum().backward()
        assert feats.grad is not None
        # the centers parameter got NO autograd gradient
        from paddle1_tpu.fluid.layers import _implicit_registry
        for st in _implicit_registry.values():
            for lay in st.layers:
                for pp in lay.parameters():
                    if tuple(pp.shape) == (2, 3):
                        assert pp.grad is None


class TestTier5:
    def test_gather_tree_backtrace(self):
        # T=3, B=1, beam=2; parent pointers trace the winning path
        ids = np.array([[[1, 2]], [[3, 4]], [[5, 6]]], np.int64)
        parents = np.array([[[0, 0]], [[0, 0]], [[1, 0]]], np.int64)
        out = np.asarray(L.gather_tree(to_tensor(ids),
                                       to_tensor(parents)).numpy())
        # beam 0 at t=2 came from parent 1 at t=1 (which came from 0)
        np.testing.assert_array_equal(out[:, 0, 0], [1, 4, 5])
        np.testing.assert_array_equal(out[:, 0, 1], [1, 3, 6])

    def test_add_position_encoding(self):
        x = np.zeros((1, 4, 6), np.float32)
        out = np.asarray(L.add_position_encoding(
            to_tensor(x), alpha=1.0, beta=1.0).numpy())
        np.testing.assert_allclose(out[0, 0, :3], 0.0, atol=1e-6)  # sin 0
        np.testing.assert_allclose(out[0, 0, 3:], 1.0, atol=1e-6)  # cos 0
        assert abs(out[0, 1, 0] - np.sin(1.0)) < 1e-5

    def test_affine_channel(self):
        x = to_tensor(np.ones((1, 2, 2, 2), np.float32))
        out = L.affine_channel(x, scale=np.array([2.0, 3.0], np.float32),
                               bias=np.array([1.0, 0.0], np.float32))
        o = np.asarray(out.numpy())
        assert o[0, 0, 0, 0] == 3.0 and o[0, 1, 0, 0] == 3.0

    def test_step_counter_increments(self):
        a = int(L.autoincreased_step_counter("t5c").numpy()[0])
        b = int(L.autoincreased_step_counter("t5c").numpy()[0])
        assert b == a + 1

    def test_selected_rows_bridges(self):
        from paddle1_tpu.core.indexed_slices import IndexedSlices
        import jax.numpy as jnp
        s = IndexedSlices(jnp.asarray([0, 0], jnp.int32),
                          jnp.ones((2, 3)), (4, 3))
        merged = L.merge_selected_rows(s)
        rows = L.get_tensor_from_selected_rows(merged)
        vals = np.asarray(rows.numpy())
        # reference semantics: the VALUES tensor [n_rows, dim], not a
        # zero-filled dense scatter
        assert vals.shape == (1, 3)
        np.testing.assert_allclose(vals[0], 2.0)  # duplicate rows merged

    def test_chunk_eval_iob(self):
        # 2 chunk types, IOB: tags B0=0 I0=1 B1=2 I1=3 O=4
        label = np.array([[0, 1, 4, 2, 3]], np.int64)
        pred = np.array([[0, 1, 4, 2, 4]], np.int64)
        p, r, f1, ni, nl, nc = L.chunk_eval(
            to_tensor(pred), to_tensor(label), "IOB", 2)
        assert int(nl.numpy()[0]) == 2
        assert int(ni.numpy()[0]) == 2
        assert int(nc.numpy()[0]) == 1          # chunk (0,2,type0) only
        assert abs(float(p.numpy()[0]) - 0.5) < 1e-6
        assert abs(float(f1.numpy()[0]) - 0.5) < 1e-6

    def test_polygon_box_transform_offsets(self):
        x = to_tensor(np.zeros((1, 2, 2, 2), np.float32))
        out = np.asarray(L.polygon_box_transform(x).numpy())
        # zero offsets -> absolute grid coords (x: 4*col, y: 4*row)
        np.testing.assert_allclose(out[0, 0], [[0, 4], [0, 4]])
        np.testing.assert_allclose(out[0, 1], [[0, 0], [4, 4]])

    def test_chunk_eval_iobes_malformed(self):
        # B0 E0 I0: E closes (0..2); the dangling I opens (2..3)
        # (reference ChunkEnd on E even for non-canonical sequences)
        # tags: B0=0 I0=1 E0=2 S0=3, O=8 (2 types x 4)
        pred = np.array([[0, 2, 1]], np.int64)
        label = np.array([[0, 2, 1]], np.int64)
        p, r, f1, ni, nl, nc = L.chunk_eval(
            to_tensor(pred), to_tensor(label), "IOBES", 2)
        assert int(ni.numpy()[0]) == 2
        assert int(nc.numpy()[0]) == 2
        assert float(f1.numpy()[0]) == 1.0

    def test_add_position_encoding_reference_divisor(self):
        x = np.zeros((1, 2, 6), np.float32)
        out = np.asarray(L.add_position_encoding(to_tensor(x)).numpy())
        # k=1 divisor is 10000^(1/(half-1)) = 10000^0.5 for half=3
        assert abs(out[0, 1, 1] - np.sin(1.0 / 10000 ** 0.5)) < 1e-6

    def test_rnncell_teaches_on_subclass(self):
        from paddle1_tpu.core.errors import UnimplementedError
        with pytest.raises(UnimplementedError, match="RNNCellBase"):
            class _C(L.RNNCell):
                pass

    def test_resize_short_and_linear_and_lod(self):
        img = to_tensor(np.zeros((1, 3, 8, 16), np.float32))
        out = L.image_resize_short(img, 4)
        assert out.shape == [1, 3, 4, 8]
        seq = to_tensor(np.zeros((1, 3, 6), np.float32))
        assert L.resize_linear(seq, out_shape=[12]).shape == [1, 3, 12]
        x, lens = L.lod_reset(to_tensor(np.zeros((2, 3), np.float32)),
                              target_lod=[2, 1])
        assert np.asarray(lens.numpy()).tolist() == [2, 1]

    def test_beam_search_step_and_decode(self):
        B, beam, V, end = 1, 2, 5, 0
        pre_ids = np.array([[3], [4]], np.int64)      # both alive
        pre_sc = np.array([[-0.5], [-1.0]], np.float32)
        # beam 0 strongly prefers token 2; beam 1 prefers token 1
        acc = np.full((2, V), -10.0, np.float32)
        acc[0, 2] = -0.6
        acc[0, 1] = -0.9
        acc[1, 1] = -1.1
        ids, sc, par = L.beam_search(pre_ids, pre_sc, None, acc,
                                     beam_size=beam, end_id=end,
                                     return_parent_idx=True)
        iv = np.asarray(ids.numpy()).reshape(-1)
        pv = np.asarray(par.numpy()).reshape(-1)
        assert iv.tolist() == [2, 1] and pv.tolist() == [0, 0]

        # finished beam keeps exactly its end candidate
        pre_ids2 = np.array([[0], [4]], np.int64)     # beam 0 finished
        ids2, sc2 = L.beam_search(pre_ids2, pre_sc, None, acc,
                                  beam_size=beam, end_id=end)
        i2 = np.asarray(ids2.numpy()).reshape(-1)
        s2 = np.asarray(sc2.numpy()).reshape(-1)
        assert 0 in i2.tolist()
        assert abs(s2[i2.tolist().index(0)] - (-0.5)) < 1e-6

        # decode: T=2 steps of (ids, parents)
        step_ids = np.array([[[2, 1]], [[0, 3]]], np.int64)
        step_par = np.array([[[0, 0]], [[0, 1]]], np.int64)
        seqs, _ = L.beam_search_decode(step_ids, None, beam, end,
                                       parents=step_par)
        sq = np.asarray(seqs.numpy())
        assert sq[:, 0, 0].tolist() == [2, 0]   # ends at end_id
        assert sq[:, 0, 1].tolist() == [1, 3]

    def test_beam_search_pruned_ids_path(self):
        # topk-pruned usage: scores [B*beam, K] with candidate vocab
        # ids in `ids` — selected tokens must be VOCAB ids
        pre_ids = np.array([[3], [4]], np.int64)
        pre_sc = np.array([[-0.5], [-1.0]], np.float32)
        cand_ids = np.array([[7, 9], [11, 13]], np.int64)   # K=2
        cand_sc = np.array([[-0.6, -0.9], [-1.1, -5.0]], np.float32)
        ids, sc, par = L.beam_search(pre_ids, pre_sc, cand_ids, cand_sc,
                                     beam_size=2, end_id=0,
                                     return_parent_idx=True)
        assert np.asarray(ids.numpy()).reshape(-1).tolist() == [7, 9]
        # finished beam in pruned mode: token forced to end_id
        pre_ids2 = np.array([[0], [4]], np.int64)
        ids2, _ = L.beam_search(pre_ids2, pre_sc, cand_ids, cand_sc,
                                beam_size=2, end_id=0)
        assert 0 in np.asarray(ids2.numpy()).reshape(-1).tolist()

    def test_beam_decode_fills_after_end(self):
        step_ids = np.array([[[5, 1]], [[0, 3]], [[7, 4]]], np.int64)
        step_par = np.array([[[0, 0]], [[0, 1]], [[0, 1]]], np.int64)
        seqs, _ = L.beam_search_decode(step_ids, None, 2, 0,
                                       parents=step_par)
        sq = np.asarray(seqs.numpy())
        assert sq[:, 0, 0].tolist() == [5, 0, 0]  # 7 after end -> end

    def test_image_resize_short_rounds(self):
        img = to_tensor(np.zeros((1, 1, 4, 6), np.float32))
        out = L.image_resize_short(img, 3)
        assert out.shape == [1, 1, 3, 5]  # 6*3/4=4.5 -> rounds to 5

    def test_lod_reset_y_dtype(self):
        x = to_tensor(np.zeros((2, 3), np.float32))
        _, l1 = L.lod_reset(x, y=[2, 1])
        _, l2 = L.lod_reset(x, target_lod=[2, 1])
        assert str(l1.dtype) == str(l2.dtype)


class TestTier6:
    def test_spectral_norm_unit_sigma(self):
        w = np.random.default_rng(0).standard_normal(
            (4, 6)).astype(np.float32) * 3.0
        out = L.spectral_norm(to_tensor(w), power_iters=20)
        o = np.asarray(out.numpy())
        s = np.linalg.svd(o, compute_uv=False)[0]
        assert abs(s - 1.0) < 0.05  # spectral radius normalized to ~1

    def test_batch_size_like_randoms(self):
        x = to_tensor(np.zeros((5, 2), np.float32))
        u = L.uniform_random_batch_size_like(x, [1, 3])
        assert u.shape == [5, 3]
        g = L.gaussian_random_batch_size_like(x, [1, 4])
        assert g.shape == [5, 4]

    def test_lstm_unit_step(self):
        x = to_tensor(np.ones((2, 3), np.float32))
        h = to_tensor(np.zeros((2, 4), np.float32))
        c = to_tensor(np.zeros((2, 4), np.float32))
        h2, c2 = L.lstm_unit(x, h, c)
        assert h2.shape == [2, 4] and c2.shape == [2, 4]
        # |h| = |tanh(c)*o| < 1 strictly
        assert np.abs(np.asarray(h2.numpy())).max() < 1.0

    def test_hash_buckets_stable(self):
        ids = to_tensor(np.array([[1], [2], [1]], np.int64))
        a = np.asarray(L.hash(ids, hash_size=1000, num_hash=2).numpy())
        b = np.asarray(L.hash(ids, hash_size=1000, num_hash=2).numpy())
        np.testing.assert_array_equal(a, b)       # deterministic
        # reference HashOutputSize: (..., num_hash, 1); the whole
        # last-dim row is ONE key
        assert a.shape == (3, 2, 1)
        assert (a >= 0).all() and (a < 1000).all()
        np.testing.assert_array_equal(a[0], a[2])  # same id same bucket
        bi = np.array([[1, 2]], np.int64)          # bigram row = one key
        hb = np.asarray(L.hash(to_tensor(bi), hash_size=1000).numpy())
        assert hb.shape == (1, 1, 1)
        assert hb.reshape(-1)[0] != a[0, 0, 0]     # row-key, not elementwise

    def test_target_assign(self):
        ent = np.arange(12, dtype=np.float32).reshape(1, 3, 4)
        matched = np.array([[2, -1, 0]], np.int64)
        out, w = L.target_assign(to_tensor(ent), to_tensor(matched),
                                 mismatch_value=-5.0)
        o = np.asarray(out.numpy())
        np.testing.assert_allclose(o[0, 0], ent[0, 2])
        np.testing.assert_allclose(o[0, 1], -5.0)
        np.testing.assert_allclose(np.asarray(w.numpy())[0, :, 0],
                                   [1, 0, 1])

    def test_target_assign_negatives_per_row(self):
        ent = np.ones((2, 2, 1), np.float32)
        matched = np.array([[0, 1], [1, 0]], np.int64)
        neg = np.array([[1], [0]], np.int64)   # DIFFERENT prior per row
        out, w = L.target_assign(to_tensor(ent), to_tensor(matched),
                                 negative_indices=to_tensor(neg),
                                 mismatch_value=0.0)
        o = np.asarray(out.numpy())
        wv = np.asarray(w.numpy())
        # row 0: negative at prior 1 only; row 1: at prior 0 only
        assert o[0, 1, 0] == 0.0 and o[1, 0, 0] == 0.0
        assert o[0, 0, 0] == 1.0 and o[1, 1, 0] == 1.0
        assert wv[0, 1, 0] == 1.0 and wv[1, 0, 0] == 1.0

    def test_lstm_unit_reference_gate_order_and_bias_attr(self):
        import paddle1_tpu as paddle
        x = to_tensor(np.ones((1, 2), np.float32))
        h = to_tensor(np.zeros((1, 3), np.float32))
        c = to_tensor(np.full((1, 3), 2.0, np.float32))
        L.reset_parameter_pass()
        h2, c2 = L.lstm_unit(x, h, c, forget_bias=100.0,
                             bias_attr=False)
        # forget gate (slot 1) saturated at 1: c2 = c + i*g in (1, 3)
        assert (np.asarray(c2.numpy()) > 1.0).all()
        L.reset_parameter_pass()
        _, c3 = L.lstm_unit(x, h, c, forget_bias=-100.0,
                            bias_attr=False)
        # forget gate saturated at 0: c3 = i*g in (-1, 1)
        assert (np.abs(np.asarray(c3.numpy())) < 1.0).all()

    def test_gaussian_batch_size_like_seeded(self):
        x = to_tensor(np.zeros((3, 2), np.float32))
        a = np.asarray(L.gaussian_random_batch_size_like(
            x, [1, 4], seed=11).numpy())
        b = np.asarray(L.gaussian_random_batch_size_like(
            x, [1, 4], seed=11).numpy())
        np.testing.assert_array_equal(a, b)

    def test_continuous_value_model(self):
        x = np.ones((2, 4), np.float32)
        sc = np.array([[3.0, 1.0], [0.0, 0.0]], np.float32)
        out = np.asarray(L.continuous_value_model(
            to_tensor(x), to_tensor(sc)).numpy())
        assert out.shape == (2, 4)
        np.testing.assert_allclose(out[0, 0], np.log(4.0), rtol=1e-6)
        np.testing.assert_allclose(out[0, 1],
                                   np.log(2.0) - np.log(4.0), rtol=1e-6)
        np.testing.assert_allclose(out[:, 2:], 1.0)
        out2 = np.asarray(L.continuous_value_model(
            to_tensor(x), to_tensor(sc), use_cvm=False).numpy())
        assert out2.shape == (2, 2)

    def test_data_norm_reference_formula(self):
        import jax.numpy as jnp
        from paddle1_tpu.fluid.layers import _implicit_registry
        L.reset_parameter_pass()
        rng = np.random.default_rng(0)
        x = rng.standard_normal((8, 3)).astype(np.float32)

        xt = to_tensor(x)
        xt.stop_gradient = False  # so backward() can drive the commit

        def dn(**kw):  # ONE call site -> one implicit stat holder
            return L.data_norm(xt, **kw)

        before = np.asarray(dn().numpy())
        assert before.shape == (8, 3)
        # locate the stat holder and pin known summaries: the output
        # must follow the reference math out = (x - sum/size) *
        # sqrt(size / square_sum) (data_norm_op.cc:302-303)
        holder = None
        for st in _implicit_registry.values():
            for lay in st.layers:
                if hasattr(lay, "batch_square_sum") and \
                        tuple(lay.batch_sum.shape) == (3,):
                    holder = lay
        assert holder is not None
        holder.batch_size._data = jnp.full((3,), 10.0)
        holder.batch_sum._data = jnp.full((3,), 20.0)     # mean 2
        holder.batch_square_sum._data = jnp.full((3,), 40.0)  # scale 0.5
        L.reset_parameter_pass()
        out = np.asarray(dn(update=False).numpy())
        np.testing.assert_allclose(out, (x - 2.0) * 0.5, rtol=1e-5)
        # updates are STAGED at forward and committed on backward-end
        # (the reference updates in the grad op): eval forwards leave
        # the stats untouched
        L.reset_parameter_pass()
        dn()
        np.testing.assert_allclose(
            np.asarray(holder.batch_size.numpy()), 10.0)
        L.reset_parameter_pass()
        y = dn()
        y.sum().backward()      # commit fires here
        assert float(np.asarray(holder.batch_size.numpy())[0]) > 10.0
