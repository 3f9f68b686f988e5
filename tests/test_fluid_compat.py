"""fluid compat shim (paddle1_tpu/fluid/): pre-2.0 scripts written
against `import paddle.fluid as fluid` run on the modern surface
(reference python/paddle/fluid/)."""

import numpy as np
import pytest

import paddle1_tpu.fluid as fluid


class TestFluidDygraphScript:
    def test_classic_training_script_shape(self):
        """The canonical fluid dygraph idiom: guard + to_variable +
        layers.fc + cross_entropy + backward + SGDOptimizer."""
        rng = np.random.default_rng(0)
        X = rng.standard_normal((32, 8)).astype(np.float32)
        Y = (X[:, 0] > 0).astype(np.int64)
        with fluid.dygraph.guard():
            losses = []
            params = None
            opt = None
            for step in range(25):
                x = fluid.dygraph.to_variable(X)
                label = fluid.dygraph.to_variable(Y)
                h = fluid.layers.fc(x, 16, act="relu")
                logits = fluid.layers.fc(h, 2, name="head")
                loss = fluid.layers.mean(
                    fluid.layers.softmax_with_cross_entropy(
                        logits, fluid.layers.reshape(label, [-1, 1])))
                loss.backward()
                if opt is None:
                    params = fluid.layers.implicit_parameters()
                    opt = fluid.optimizer.SGDOptimizer(
                        learning_rate=0.5, parameters=params)
                opt.step()
                opt.clear_grad()
                losses.append(float(loss.numpy()))
            assert losses[-1] < losses[0] * 0.8

    def test_layer_cache_reuses_weights(self):
        with fluid.dygraph.guard():
            x = fluid.dygraph.to_variable(
                np.ones((2, 4), np.float32))
            a = fluid.layers.fc(x, 3, name="shared")
            b = fluid.layers.fc(x, 3, name="shared")
            np.testing.assert_allclose(a.numpy(), b.numpy())

    def test_ops_subset(self):
        x = fluid.dygraph.to_variable(
            np.arange(6, dtype=np.float32).reshape(2, 3))
        np.testing.assert_allclose(
            fluid.layers.reduce_sum(x).numpy(), 15.0)
        assert fluid.layers.mul(
            x, fluid.dygraph.to_variable(
                np.ones((3, 2), np.float32))).shape == [2, 2]
        assert fluid.layers.elementwise_add(x, x).shape == [2, 3]
        assert fluid.layers.cast(x, "int32").dtype == "int32"
        assert fluid.layers.fill_constant([2], "float32", 3.0).shape == [2]
        oh = fluid.layers.one_hot(
            fluid.dygraph.to_variable(np.array([0, 2])), 3)
        np.testing.assert_allclose(oh.numpy(),
                                   [[1, 0, 0], [0, 0, 1]])

    def test_cross_entropy_is_prob_space(self):
        # fluid.layers.cross_entropy takes POST-softmax probabilities
        probs = fluid.dygraph.to_variable(
            np.array([[0.9, 0.1], [0.2, 0.8]], np.float32))
        label = fluid.dygraph.to_variable(np.array([0, 1]))
        ce = fluid.layers.cross_entropy(probs, label)
        np.testing.assert_allclose(
            ce.numpy().reshape(-1), [-np.log(0.9), -np.log(0.8)],
            rtol=1e-5)


class TestTeachingErrors:
    def test_moved_op_names_destination(self):
        # r5: the former teaching names are now real implementations
        assert callable(fluid.layers.dynamic_lstm)
        assert callable(fluid.layers.py_func)
        # r4 breadth tier 2: multiclass_nms is now MAPPED (vision.ops)
        assert callable(fluid.layers.multiclass_nms)

    def test_unknown_op_points_at_modern_namespace(self):
        with pytest.raises(AttributeError, match="MIGRATING"):
            fluid.layers.this_never_existed

    def test_disable_dygraph_teaches(self):
        with pytest.raises(RuntimeError, match="to_static"):
            fluid.disable_dygraph()

    def test_global_scope_is_real(self):
        # r5: the scope tree is real — find_var sees live parameters
        # and get_tensor() reads/writes them (reference scope.h idiom)
        import numpy as np
        import paddle1_tpu as paddle
        paddle.seed(0)
        lin = paddle.nn.Linear(3, 2)
        v = fluid.global_scope().find_var(lin.weight.name)
        assert v is not None
        t = v.get_tensor()
        assert np.array(t).shape == (3, 2)
        t.set(np.full((3, 2), 2.0, np.float32))
        np.testing.assert_allclose(np.asarray(lin.weight.numpy()), 2.0)
        # persistable buffers (BN stats) are scope-visible too
        bn = paddle.nn.BatchNorm1D(4)
        assert fluid.global_scope().find_var(bn._mean.name) is not None
        # scope TREE: child lookup falls through to the root
        kid = fluid.global_scope().new_scope()
        kid.var("local").get_tensor().set(
            np.float32(1.0).reshape(()))
        assert kid.find_var(lin.weight.name) is not None
        assert fluid.global_scope().find_var("local") is None
        assert "local" in kid.local_var_names()
        kid2 = kid.new_scope()
        assert kid2.find_var("local") is not None
        fluid.global_scope().drop_kids()
        # shape-mismatched writes are loud
        from paddle1_tpu.core.errors import InvalidArgumentError
        with pytest.raises(InvalidArgumentError, match="shape"):
            fluid.global_scope().find_var(lin.weight.name) \
                 .get_tensor().set(np.zeros((5, 5), np.float32))

    def test_scope_guard_switches_global(self):
        s = fluid.Scope()
        assert isinstance(s, fluid.Scope)   # the real class, lazily
        with fluid.scope_guard(s):
            assert fluid.global_scope() is s
        assert fluid.global_scope() is not s

    def test_fresh_scope_is_isolated(self):
        # review finding: only the global ROOT carries the live-model
        # bridge — a user Scope must be empty (scope_guard isolation)
        import numpy as np
        import paddle1_tpu as paddle
        lin = paddle.nn.Linear(2, 2)
        s = fluid.Scope()
        assert s.find_var(lin.weight.name) is None
        assert s.local_var_names() == []
        # and a fresh variable's first set() DEFINES shape/dtype
        # (reference LoDTensor.set on a new Variable)
        t = s.var("img").get_tensor()
        t.set(np.ones((3, 4), np.float32))
        assert np.array(t).shape == (3, 4)
        # subsequent sets enforce the established shape
        from paddle1_tpu.core.errors import InvalidArgumentError
        with pytest.raises(InvalidArgumentError, match="shape"):
            t.set(np.ones((2, 2), np.float32))

    def test_root_var_does_not_pin_params(self):
        # review finding: var() on a live param must not cache a strong
        # reference (GC pinning / staleness)
        import paddle1_tpu as paddle
        lin = paddle.nn.Linear(2, 2)
        name = lin.weight.name
        fluid.global_scope().var(name)
        assert name not in fluid.global_scope()._vars


class TestAliases:
    def test_optimizer_spellings(self):
        assert fluid.optimizer.SGDOptimizer is fluid.optimizer.SGD
        assert fluid.optimizer.AdamOptimizer is fluid.optimizer.Adam

    def test_places_and_static_shell(self):
        assert fluid.CUDAPlace is fluid.TPUPlace  # "the accelerator"
        assert fluid.Executor is not None
        spec = fluid.data("x", [None, 8])
        assert list(spec.shape) == [None, 8] or list(spec.shape) == [-1, 8]

    def test_initializer_spellings(self):
        assert fluid.initializer.ConstantInitializer \
            is fluid.initializer.Constant
        assert fluid.initializer.MSRAInitializer is not None

    def test_batch_norm_and_pool(self):
        x = fluid.dygraph.to_variable(
            np.random.default_rng(0).standard_normal(
                (2, 3, 8, 8)).astype(np.float32))
        y = fluid.layers.batch_norm(x, act="relu")
        assert y.shape == [2, 3, 8, 8]
        assert float(y.numpy().min()) >= 0.0
        p = fluid.layers.pool2d(x, pool_size=2, pool_type="max",
                                pool_stride=2)
        assert p.shape == [2, 3, 4, 4]
        g = fluid.layers.pool2d(x, global_pooling=True, pool_type="avg")
        assert g.shape == [2, 3, 1, 1]


class TestReviewRegressions:
    def test_distinct_fc_call_sites_do_not_weight_tie(self):
        x = fluid.dygraph.to_variable(
            np.random.default_rng(0).standard_normal(
                (2, 64)).astype(np.float32))
        h1 = fluid.layers.fc(x, 64)
        h2 = fluid.layers.fc(x, 64)  # different line: different weights
        assert not np.allclose(h1.numpy(), h2.numpy())

    def test_loop_call_site_reuses_weights(self):
        # training-shaped loop: backward() ends the pass, so the next
        # iteration reuses the same implicit parameters
        x = fluid.dygraph.to_variable(
            np.ones((1, 4), np.float32))
        outs = []
        for _ in range(2):
            y = fluid.layers.fc(x, 3)
            outs.append(y.numpy())
            y.sum().backward()
        np.testing.assert_allclose(outs[0], outs[1])

    def test_same_line_two_creations_train_distinct_params(self):
        # reference per-creation semantics: two
        # textual calls on ONE line are two parameter sets
        x = fluid.dygraph.to_variable(
            np.random.default_rng(3).standard_normal(
                (2, 16)).astype(np.float32))
        outs = []
        for _ in range(2):
            a = fluid.layers.fc(x, 16); b = fluid.layers.fc(x, 16)  # noqa: E702,E501
            outs.append((a.numpy(), b.numpy()))
            (a.sum() + b.sum()).backward()
        a1, b1 = outs[0]
        a2, b2 = outs[1]
        assert not np.allclose(a1, b1)  # two creations, distinct weights
        # second pass reuses both, in creation order
        np.testing.assert_allclose(a1, a2)
        np.testing.assert_allclose(b1, b2)

    def test_helper_called_for_two_branches_distinct(self):
        x = fluid.dygraph.to_variable(
            np.random.default_rng(4).standard_normal(
                (2, 8)).astype(np.float32))

        def branch():
            return fluid.layers.fc(x, 8)

        l, r = branch(), branch()
        assert not np.allclose(l.numpy(), r.numpy())
        (l.sum() + r.sum()).backward()
        l2, r2 = branch(), branch()
        np.testing.assert_allclose(l.numpy(), l2.numpy())
        np.testing.assert_allclose(r.numpy(), r2.numpy())

    def test_frozen_overrun_warns_and_reuses(self):
        import warnings as w
        x = fluid.dygraph.to_variable(np.ones((1, 4), np.float32))

        def call():
            return fluid.layers.fc(x, 5)

        y = call()
        y.sum().backward()  # freeze: one creation in the first pass
        with w.catch_warnings(record=True) as rec:
            w.simplefilter("always")
            y1 = call()
            y2 = call()  # overrun: collapses onto y1's weights
        np.testing.assert_allclose(y1.numpy(), y2.numpy())
        assert any("reuse existing weights" in str(r.message) for r in rec)

    def test_conv2d_dilation_not_shared(self):
        x = fluid.dygraph.to_variable(
            np.random.default_rng(0).standard_normal(
                (1, 2, 8, 8)).astype(np.float32))
        a = fluid.layers.conv2d(x, 4, 3, padding=1, dilation=1)
        b = fluid.layers.conv2d(x, 4, 3, padding=2, dilation=2)
        assert a.shape == b.shape == [1, 4, 8, 8]

    def test_elementwise_axis_broadcast(self):
        x = fluid.dygraph.to_variable(
            np.zeros((2, 3, 4, 5), np.float32))
        bias = fluid.dygraph.to_variable(
            np.arange(3, dtype=np.float32))
        out = fluid.layers.elementwise_add(x, bias, axis=1)
        assert out.shape == [2, 3, 4, 5]
        np.testing.assert_allclose(out.numpy()[0, :, 0, 0], [0, 1, 2])

    def test_cross_entropy_rank2_label(self):
        probs = fluid.dygraph.to_variable(
            np.array([[0.9, 0.1], [0.2, 0.8]], np.float32))
        label = fluid.dygraph.to_variable(
            np.array([[0], [1]]))  # the old mandatory [N, 1]
        ce = fluid.layers.cross_entropy(probs, label)
        np.testing.assert_allclose(ce.numpy().reshape(-1),
                                   [-np.log(0.9), -np.log(0.8)],
                                   rtol=1e-5)

    def test_accuracy_topk(self):
        probs = fluid.dygraph.to_variable(np.array(
            [[0.5, 0.3, 0.2], [0.1, 0.2, 0.7]], np.float32))
        label = fluid.dygraph.to_variable(np.array([[1], [0]]))
        acc5 = fluid.layers.accuracy(probs, label, k=3)
        np.testing.assert_allclose(float(acc5.numpy()), 1.0)

    def test_crf_cost_sign(self):
        # fluid's linear_chain_crf is a COST (negative log-likelihood)
        rng = np.random.default_rng(0)
        x = fluid.dygraph.to_variable(
            rng.standard_normal((2, 4, 3)).astype(np.float32))
        y = fluid.dygraph.to_variable(rng.integers(0, 3, (2, 4)))
        cost = fluid.layers.linear_chain_crf(x, y)
        assert float(cost.numpy().mean()) > 0  # -log p >= 0

    def test_rank3_input_rank2_label_cross_entropy(self):
        # sequence probs [B, T, C] with [B, T] labels keep working
        probs = fluid.dygraph.to_variable(
            np.full((2, 1, 2), 0.5, np.float32))
        label = fluid.dygraph.to_variable(np.array([[0], [1]]))
        ce = fluid.layers.cross_entropy(probs, label)
        np.testing.assert_allclose(ce.numpy().reshape(-1),
                                   [np.log(2.0)] * 2, rtol=1e-6)

    def test_same_line_fc_distinct_creations(self):
        # r4: per-creation semantics — one line, two creations, two
        # parameter sets (was a documented weight-tie before)
        x = fluid.dygraph.to_variable(np.ones((1, 4), np.float32))
        a, b = fluid.layers.fc(x, 3), fluid.layers.fc(x, 3)  # one line
        assert not np.allclose(a.numpy(), b.numpy())
        c = fluid.layers.fc(x, 3, name="other")
        assert not np.allclose(a.numpy(), c.numpy())

    def test_crf_heads_separable_by_name(self):
        rng = np.random.default_rng(0)
        x = fluid.dygraph.to_variable(
            rng.standard_normal((1, 3, 4)).astype(np.float32))
        y = fluid.dygraph.to_variable(rng.integers(0, 4, (1, 3)))
        fluid.layers.linear_chain_crf(x, y, param_attr="head_a")
        fluid.layers.linear_chain_crf(x, y, param_attr="head_b")
        from paddle1_tpu.fluid.layers import _crf_param
        assert ("named", "head_a") in _crf_param._params
        assert ("named", "head_b") in _crf_param._params

    def test_rank3_input_rank3_label_cross_entropy(self):
        # fluid's trailing-1 label applies at any rank: [B,T,1] labels
        probs = fluid.dygraph.to_variable(
            np.full((2, 2, 2), 0.5, np.float32))
        label = fluid.dygraph.to_variable(
            np.zeros((2, 2, 1), np.int64))
        ce = fluid.layers.cross_entropy(probs, label)
        np.testing.assert_allclose(np.asarray(ce.numpy()).reshape(-1),
                                   [np.log(2.0)] * 4, rtol=1e-6)
