"""distribution / auto-checkpoint / sysconfig / onnx-shim coverage."""

import os
import tempfile
import unittest

import numpy as np
import pytest

import paddle1_tpu as paddle


class TestDistribution(unittest.TestCase):
    def test_normal(self):
        d = paddle.distribution.Normal(0.0, 1.0)
        s = d.sample([2000])
        self.assertLess(abs(float(s.numpy().mean())), 0.15)
        lp = d.log_prob(paddle.to_tensor(0.0))
        self.assertAlmostEqual(float(lp), -0.9189385, places=5)
        ent = d.entropy()
        self.assertAlmostEqual(float(ent), 1.4189385, places=5)
        kl = d.kl_divergence(paddle.distribution.Normal(0.0, 2.0))
        self.assertGreater(float(kl), 0.0)

    def test_uniform(self):
        d = paddle.distribution.Uniform(1.0, 3.0)
        s = d.sample([1000]).numpy()
        self.assertTrue((s >= 1.0).all() and (s < 3.0).all())
        self.assertAlmostEqual(float(d.entropy()), np.log(2.0), places=5)
        self.assertAlmostEqual(float(d.log_prob(paddle.to_tensor(2.0))),
                               -np.log(2.0), places=5)
        self.assertEqual(float(d.log_prob(paddle.to_tensor(5.0))),
                         -np.inf)

    def test_categorical(self):
        logits = paddle.to_tensor(np.log(np.array([0.7, 0.2, 0.1],
                                                  np.float32)))
        d = paddle.distribution.Categorical(logits)
        s = d.sample([4000]).numpy()
        self.assertAlmostEqual((s == 0).mean(), 0.7, delta=0.06)
        lp = d.log_prob(paddle.to_tensor(np.array([0], np.int64)))
        self.assertAlmostEqual(float(lp), np.log(0.7), places=4)
        ent = float(d.entropy())
        self.assertAlmostEqual(ent, -(0.7 * np.log(0.7) + 0.2 * np.log(0.2)
                                      + 0.1 * np.log(0.1)), places=4)


class TestAutoCheckpoint(unittest.TestCase):
    def test_resume_cycle(self):
        from paddle1_tpu.incubate import train_epoch_range
        from paddle1_tpu.vision.models import LeNet
        with tempfile.TemporaryDirectory() as d:
            m = LeNet()
            opt = paddle.optimizer.SGD(learning_rate=0.1,
                                       parameters=m.parameters())
            seen = []
            for epoch in train_epoch_range(5, m, opt, name="t",
                                           checkpoint_dir=d):
                seen.append(epoch)
                if epoch == 2:
                    # simulated crash DURING epoch 2 (before its snapshot):
                    # epochs 0-1 are durable, epoch 2 must re-run
                    break
            self.assertEqual(seen, [0, 1, 2])
            # "restart": fresh objects, same dir → resumes at epoch 2
            m2 = LeNet()
            opt2 = paddle.optimizer.SGD(learning_rate=0.1,
                                        parameters=m2.parameters())
            seen2 = list(train_epoch_range(5, m2, opt2, name="t",
                                           checkpoint_dir=d))
            self.assertEqual(seen2, [2, 3, 4])
            # weights restored from snapshot
            a = m.state_dict()["features.0.weight"].numpy()
            b = m2.state_dict()["features.0.weight"].numpy()
            np.testing.assert_array_equal(a, b)

    def test_no_dir_passthrough(self):
        from paddle1_tpu.incubate import train_epoch_range
        os.environ.pop("PADDLE_CHECKPOINT_DIR", None)
        self.assertEqual(list(train_epoch_range(3)), [0, 1, 2])


class TestMisc(unittest.TestCase):
    def test_sysconfig(self):
        self.assertTrue(os.path.isdir(paddle.sysconfig.get_include()))
        self.assertTrue(os.path.isdir(paddle.sysconfig.get_lib()))

    def test_onnx_export_raises_for_onnx_suffix(self):
        from paddle1_tpu.vision.models import LeNet
        with self.assertRaises(NotImplementedError):
            paddle.onnx.export(LeNet(), "/tmp/x.onnx")


class TestPre20TopLevelCompat:
    """r3 namespace sweep vs reference python/paddle/__init__.py: the
    pre-2.0 top-level names old scripts touch."""

    def test_reader_pipeline(self):
        import paddle1_tpu as paddle

        def train():
            for i in range(10):
                yield np.float32([i]), i % 2

        r = paddle.batch(paddle.reader.shuffle(train, buf_size=4), 4)
        batches = list(r())
        assert [len(b) for b in batches] == [4, 4, 2]
        r2 = paddle.batch(train, 4, drop_last=True)
        assert [len(b) for b in list(r2())] == [4, 4]
        # decorators compose
        fn = paddle.reader.firstn(paddle.reader.cache(train), 3)
        assert len(list(fn())) == 3
        m = paddle.reader.map_readers(lambda s: s[1], train)
        assert list(m()) == [i % 2 for i in range(10)]

    def test_flags_and_modes(self):
        import paddle1_tpu as paddle
        # the real device probe (False on the CPU test sim, True on chip)
        assert isinstance(paddle.is_compiled_with_tpu(), (bool, np.bool_))
        assert not paddle.is_compiled_with_cuda()
        assert paddle.in_dygraph_mode() and paddle.in_dynamic_mode()
        assert paddle.get_cudnn_version() is None

    def test_tensor_utilities(self):
        import paddle1_tpu as paddle
        x = paddle.to_tensor(np.arange(12).reshape(3, 4))
        assert int(paddle.rank(x).numpy()) == 2
        assert paddle.tolist(paddle.to_tensor(np.array([1, 2]))) == [1, 2]
        assert not bool(paddle.is_empty(x).numpy())
        np.testing.assert_array_equal(
            paddle.reverse(paddle.to_tensor(np.array([1, 2, 3])),
                           0).numpy(), [3, 2, 1])
        np.testing.assert_array_equal(
            paddle.crop_tensor(x, shape=[2, 2],
                               offsets=[1, 1]).numpy(),
            [[5, 6], [9, 10]])

    def test_aliases_and_places(self):
        import paddle1_tpu as paddle
        assert paddle.VarBase is paddle.Tensor
        assert paddle.CUDAPlace is paddle.TPUPlace
        with pytest.raises(RuntimeError, match="TPU build"):
            paddle.NPUPlace(0)
        p = paddle.create_parameter([2, 3])
        assert p.shape == [2, 3]
        st = paddle.get_cuda_rng_state()
        paddle.set_cuda_rng_state(st)


class TestBoundedDifferentiableWhile(unittest.TestCase):
    """static.nn.while_loop(max_iter=N): bounded lax.scan lowering —
    the differentiable form of the traced while (a traced-bound while
    was forward-only)."""

    def test_matches_unbounded_result(self):
        import jax.numpy as jnp
        from paddle1_tpu import static
        from paddle1_tpu.core.tensor import to_tensor

        def run(**kw):
            i0 = to_tensor(np.int32(0))
            s0 = to_tensor(np.float32(0.0))
            i, s = static.nn.while_loop(
                lambda i, s: to_tensor((i.data < 5)),
                lambda i, s: (to_tensor(i.data + 1),
                              to_tensor(s.data + 2.0)),
                [i0, s0], **kw)
            return int(i.numpy()), float(s.numpy())

        self.assertEqual(run(), (5, 10.0))
        self.assertEqual(run(max_iter=8), (5, 10.0))  # freezes after 5

    def test_bounded_form_is_differentiable(self):
        import jax
        import jax.numpy as jnp
        from paddle1_tpu import static

        def loss(x):
            # s = x * 3 via three loop iterations, then squared
            def cond(i, s):
                return i < 3

            def body(i, s):
                return i + 1, s + x

            from paddle1_tpu.core.tensor import to_tensor
            i, s = static.nn.while_loop(
                cond, body, [jnp.int32(0), jnp.zeros(())], max_iter=5)
            s = s.data if hasattr(s, "data") else s
            return (s * s).sum()

        g = jax.grad(loss)(jnp.float32(2.0))
        # d/dx (3x)^2 = 18x = 36
        self.assertAlmostEqual(float(g), 36.0, places=4)

    def test_unbounded_form_still_forward_only(self):
        import jax
        import jax.numpy as jnp
        from paddle1_tpu import static

        def loss(x):
            i, s = static.nn.while_loop(
                lambda i, s: i < 3,
                lambda i, s: (i + 1, s + x),
                [jnp.int32(0), jnp.zeros(())])
            s = s.data if hasattr(s, "data") else s
            return (s * s).sum()

        # forward works; reverse mode specifically is what fails
        self.assertAlmostEqual(float(loss(jnp.float32(2.0))), 36.0,
                               places=4)
        with self.assertRaises(ValueError) as cm:
            jax.grad(loss)(jnp.float32(2.0))
        self.assertIn("while", str(cm.exception).lower())

    def test_bounded_grad_survives_unsafe_frozen_body(self):
        """Double-where regression: the dead body evaluation after
        termination (here x/(3-i) hitting i=3 -> x/0) must not poison
        the gradient with NaN."""
        import jax
        import jax.numpy as jnp
        from paddle1_tpu import static

        def loss(x):
            def cond(i, s):
                return i < 3

            def body(i, s):
                return i + 1, s + x / (3.0 - i.astype(jnp.float32))

            i, s = static.nn.while_loop(cond, body,
                                        [jnp.int32(0), jnp.zeros(())],
                                        max_iter=5)
            s = s.data if hasattr(s, "data") else s
            return s

        v = float(loss(jnp.float32(2.0)))
        self.assertAlmostEqual(v, 2 * (1 / 3 + 1 / 2 + 1.0), places=4)
        g = float(jax.grad(loss)(jnp.float32(2.0)))
        self.assertAlmostEqual(g, 1 / 3 + 1 / 2 + 1.0, places=4)

    def test_bounded_body_arity_mismatch_raises(self):
        import jax.numpy as jnp
        from paddle1_tpu import static
        with self.assertRaises(TypeError):
            static.nn.while_loop(
                lambda i, s: i < 2,
                lambda i, s: (i + 1, s, s),   # 3 outputs for 2 vars
                [jnp.int32(0), jnp.zeros(())], max_iter=4)

    def test_zero_iteration_loop_grad_clean(self):
        """cond false on entry: the body (x/0 on the initial state)
        must never execute, so both value and grad stay finite."""
        import jax
        import jax.numpy as jnp
        from paddle1_tpu import static

        def loss(x):
            i, s = static.nn.while_loop(
                lambda i, s: i < 0,
                lambda i, s: (i + 1,
                              s + x / (0.0 - i.astype(jnp.float32))),
                [jnp.int32(0), jnp.zeros(())], max_iter=3)
            s = s.data if hasattr(s, "data") else s
            return s

        self.assertEqual(float(loss(jnp.float32(2.0))), 0.0)
        self.assertEqual(float(jax.grad(loss)(jnp.float32(2.0))), 0.0)
