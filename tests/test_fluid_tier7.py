"""Fluid tier 7: py_func, random_crop,
conv3d_transpose, adaptive_pool3d, scatter_nd."""

import numpy as np
import pytest

import paddle1_tpu as paddle
import paddle1_tpu.fluid.layers as L
from paddle1_tpu.core.tensor import to_tensor


class TestPyFunc:
    def test_forward_numpy_roundtrip(self):
        x = to_tensor(np.arange(6, np.float32).reshape(2, 3)
                      if False else
                      np.arange(6, dtype=np.float32).reshape(2, 3))
        out = L.py_func(lambda a: a * 2 + 1, x)
        np.testing.assert_allclose(
            np.asarray(out.numpy()),
            np.arange(6, dtype=np.float32).reshape(2, 3) * 2 + 1)

    def test_multiple_inputs_outputs(self):
        a = to_tensor(np.ones((2, 2), np.float32))
        b = to_tensor(np.full((2, 2), 3.0, np.float32))
        s, p = L.py_func(lambda u, v: (u + v, u * v), [a, b])
        np.testing.assert_allclose(np.asarray(s.numpy()), 4.0)
        np.testing.assert_allclose(np.asarray(p.numpy()), 3.0)

    def test_backward_func_supplies_grad(self):
        x = to_tensor(np.array([[1.0, 2.0], [3.0, 4.0]], np.float32))
        x.stop_gradient = False

        def fwd(a):
            return np.tanh(a)

        def bwd(a, out, gout):
            return gout * (1 - out ** 2)
        y = L.py_func(fwd, x, backward_func=bwd)
        y.sum().backward()
        ref = 1 - np.tanh(np.asarray([[1, 2], [3, 4]], np.float32)) ** 2
        np.testing.assert_allclose(np.asarray(x.grad.numpy()), ref,
                                   rtol=1e-5)

    def test_skip_input_var(self):
        x = to_tensor(np.array([2.0, 3.0], np.float32))
        x.stop_gradient = False
        argc = {}

        def fwd(a):
            return a * a

        def bwd(*args):
            argc["n"] = len(args)
            return args[-1]
        y = L.py_func(fwd, x, backward_func=bwd,
                      skip_vars_in_backward_input=[x])
        y.sum().backward()
        # backward saw (out, gout) only — x was skipped
        assert argc["n"] == 2


class TestRandomCrop:
    def test_shapes_and_content(self):
        rng = np.random.default_rng(0)
        x = rng.standard_normal((4, 8, 9)).astype(np.float32)
        out = L.random_crop(to_tensor(x), [5, 6], seed=3)
        o = np.asarray(out.numpy())
        assert o.shape == (4, 5, 6)
        # every cropped instance is a contiguous window of its source
        for b in range(4):
            found = False
            for i in range(8 - 5 + 1):
                for j in range(9 - 6 + 1):
                    if np.allclose(o[b], x[b, i:i + 5, j:j + 6]):
                        found = True
            assert found, b

    def test_instances_draw_distinct_offsets(self):
        # identical content per instance: crops differ iff offsets do
        base = np.arange(100, dtype=np.float32).reshape(10, 10)
        x = np.tile(base, (16, 1, 1))
        out = np.asarray(L.random_crop(to_tensor(x), [4, 4],
                                       seed=11).numpy())
        assert not all(np.array_equal(out[0], out[b])
                       for b in range(1, 16))

    def test_bad_shape(self):
        with pytest.raises(Exception, match="non-batch"):
            L.random_crop(to_tensor(np.zeros((2, 4, 4), np.float32)),
                          [2])


class TestConv3DTranspose:
    def test_shape_and_grad(self):
        x = to_tensor(np.random.default_rng(1).standard_normal(
            (2, 3, 4, 4, 4)).astype(np.float32))
        out = L.conv3d_transpose(x, 5, filter_size=3, stride=2,
                                 name="c3t")
        assert tuple(out.shape) == (2, 5, 9, 9, 9)
        out.sum().backward()

    def test_needs_filter_size(self):
        with pytest.raises(Exception, match="filter_size"):
            L.conv3d_transpose(
                to_tensor(np.zeros((1, 2, 4, 4, 4), np.float32)), 3)


class TestAdaptivePool3D:
    def test_avg_matches_numpy(self):
        x = np.arange(2 * 2 * 4 * 4 * 4, dtype=np.float32).reshape(
            2, 2, 4, 4, 4)
        out = L.adaptive_pool3d(to_tensor(x), [2, 2, 2],
                                pool_type="avg")
        ref = x.reshape(2, 2, 2, 2, 2, 2, 2, 2).mean(axis=(3, 5, 7))
        np.testing.assert_allclose(np.asarray(out.numpy()), ref,
                                   rtol=1e-5)

    def test_max(self):
        x = np.random.default_rng(2).standard_normal(
            (1, 1, 6, 6, 6)).astype(np.float32)
        out = L.adaptive_pool3d(to_tensor(x), [3, 3, 3],
                                pool_type="max")
        ref = x.reshape(1, 1, 3, 2, 3, 2, 3, 2).max(axis=(3, 5, 7))
        np.testing.assert_allclose(np.asarray(out.numpy()), ref)


class TestScatterNd:
    def test_matches_numpy(self):
        idx = np.array([[1, 1], [0, 1], [1, 1]], np.int64)
        upd = np.array([9.0, 10.0, 11.0], np.float32)
        out = L.scatter_nd(to_tensor(idx), to_tensor(upd), [2, 3])
        ref = np.zeros((2, 3), np.float32)
        for i, u in zip(idx, upd):
            ref[tuple(i)] += u
        np.testing.assert_allclose(np.asarray(out.numpy()), ref)
