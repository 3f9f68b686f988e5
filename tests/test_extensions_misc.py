"""Optimizer wrappers (EMA / ModelAverage / LookAhead), the to_static
control-flow teaching error, the fs abstraction with checkpoint-to-remote,
and the custom-op extension API."""

import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle1_tpu as paddle
from paddle1_tpu.core.tensor import Tensor, to_tensor


def _linear_and_data(seed=0):
    rng = np.random.default_rng(seed)
    lin = paddle.nn.Linear(4, 4)
    x = rng.standard_normal((8, 4)).astype(np.float32)
    y = rng.standard_normal((8, 4)).astype(np.float32)
    return lin, x, y


def _step(lin, opt, x, y):
    loss = ((lin(to_tensor(x)) - to_tensor(y)) ** 2).mean()
    loss.backward()
    opt.step()
    opt.clear_grad()
    return float(loss.numpy())


class TestEMA:
    def test_ema_tracks_and_applies(self):
        from paddle1_tpu.incubate import ExponentialMovingAverage
        lin, x, y = _linear_and_data()
        opt = paddle.optimizer.SGD(learning_rate=0.1,
                                   parameters=lin.parameters())
        ema = ExponentialMovingAverage(lin.parameters(), decay=0.5)
        for _ in range(5):
            _step(lin, opt, x, y)
            ema.update()
        train_w = np.asarray(lin.weight.data).copy()
        with ema.apply():
            ema_w = np.asarray(lin.weight.data).copy()
            assert not np.allclose(ema_w, train_w)
        np.testing.assert_array_equal(np.asarray(lin.weight.data), train_w)

    def test_ema_bias_correction_first_step(self):
        from paddle1_tpu.incubate import ExponentialMovingAverage
        lin, _, _ = _linear_and_data()
        ema = ExponentialMovingAverage(lin.parameters(), decay=0.9)
        ema.update()
        w = np.asarray(lin.weight.data)
        with ema.apply():
            # after 1 update, corrected EMA == current params exactly
            np.testing.assert_allclose(np.asarray(lin.weight.data), w,
                                       rtol=1e-6)

    def test_apply_before_update_raises(self):
        """Review finding: apply() with zeroed EMA buffers must not
        silently wipe the parameters."""
        from paddle1_tpu.incubate import ExponentialMovingAverage
        from paddle1_tpu.core.errors import InvalidArgumentError
        lin, _, _ = _linear_and_data()
        ema = ExponentialMovingAverage(lin.parameters())
        with pytest.raises(InvalidArgumentError):
            ema.apply()

    def test_lookahead_state_roundtrip(self):
        """Review finding: set_state_dict must restore inner + slow
        weights, not delegate a wrong-shaped dict to the inner opt."""
        from paddle1_tpu.incubate import LookAhead
        lin, x, y = _linear_and_data(4)
        opt = LookAhead(paddle.optimizer.Adam(
            learning_rate=0.01, parameters=lin.parameters()), k=3)
        for _ in range(4):
            _step(lin, opt, x, y)
        state = opt.state_dict()
        params_snap = {k: np.asarray(v.data).copy()
                       for k, v in lin.state_dict().items()}

        # continue 3 steps from the snapshot
        l1 = [_step(lin, opt, x, y) for _ in range(3)]

        # rewind the SAME model+optimizer via the state dict and replay
        # (param names must match — the reference's state_dict contract)
        for k, v in lin.state_dict().items():
            v._data = jnp.asarray(params_snap[k])
        opt.set_state_dict(state)
        assert opt._step_count == 4
        l2 = [_step(lin, opt, x, y) for _ in range(3)]
        np.testing.assert_allclose(l1, l2, rtol=1e-5)

    def test_double_apply_raises(self):
        from paddle1_tpu.incubate import ExponentialMovingAverage
        from paddle1_tpu.core.errors import InvalidArgumentError
        lin, _, _ = _linear_and_data()
        ema = ExponentialMovingAverage(lin.parameters())
        ema.update()
        ema.apply(need_restore=False)
        with pytest.raises(InvalidArgumentError):
            ema.apply()
        ema.restore()


class TestModelAverage:
    def test_average_applies_and_restores(self):
        from paddle1_tpu.incubate import ModelAverage
        lin, x, y = _linear_and_data(1)
        opt = paddle.optimizer.SGD(learning_rate=0.1,
                                   parameters=lin.parameters())
        ma = ModelAverage(0.5, parameters=lin.parameters(),
                          min_average_window=2, max_average_window=10)
        snaps = []
        for _ in range(4):
            _step(lin, opt, x, y)
            ma.update()
            snaps.append(np.asarray(lin.weight.data).copy())
        cur = np.asarray(lin.weight.data).copy()
        with ma.apply():
            avg = np.asarray(lin.weight.data)
            np.testing.assert_allclose(avg, np.mean(snaps[-ma._n:], axis=0),
                                       rtol=1e-5)
        np.testing.assert_array_equal(np.asarray(lin.weight.data), cur)


class TestLookAhead:
    def test_slow_weights_interpolate(self):
        from paddle1_tpu.incubate import LookAhead
        lin, x, y = _linear_and_data(2)
        w0 = np.asarray(lin.weight.data).copy()
        inner = paddle.optimizer.SGD(learning_rate=0.05,
                                     parameters=lin.parameters())
        opt = LookAhead(inner, alpha=0.5, k=2)
        # one fast step: slow not applied yet
        _step(lin, opt, x, y)
        w1 = np.asarray(lin.weight.data)
        assert not np.allclose(w1, w0)
        # second step hits k: w = slow + 0.5*(fast - slow)
        lin_ref, _, _ = _linear_and_data(2)
        lin_ref.load_dict({k: v for k, v in lin.state_dict().items()})
        _step(lin, opt, x, y)
        w2 = np.asarray(lin.weight.data)
        # slow was w0; fast after 2 steps unknown, but w2 must lie midway
        # between w0 and the pure-fast trajectory — check pullback happened
        assert np.linalg.norm(w2 - w0) < np.linalg.norm(w1 - w0) * 2
        losses = [_step(lin, opt, x, y) for _ in range(6)]
        assert losses[-1] < losses[0]

    def test_validation(self):
        from paddle1_tpu.incubate import LookAhead
        from paddle1_tpu.core.errors import InvalidArgumentError
        lin, _, _ = _linear_and_data()
        inner = paddle.optimizer.SGD(learning_rate=0.1,
                                     parameters=lin.parameters())
        with pytest.raises(InvalidArgumentError):
            LookAhead(inner, alpha=2.0)
        with pytest.raises(InvalidArgumentError):
            LookAhead(inner, k=0)
        with pytest.raises(InvalidArgumentError):
            LookAhead(None)


class TestToStaticTeachingError:
    def test_early_return_tensor_if_now_converts(self):
        # r4: this exact pattern used to raise the teaching error; the
        # RETURN transformer now lowers it to lax.cond
        @paddle.jit.to_static
        def f(x):
            if (x > 0).all():        # tensor-dependent python branch
                return x + 1
            return x - 1

        np.testing.assert_allclose(
            np.asarray(f(to_tensor(np.ones(4, np.float32))).numpy()), 2.0)
        np.testing.assert_allclose(
            np.asarray(f(to_tensor(-np.ones(4, np.float32))).numpy()),
            -2.0)

    def test_unconvertible_loop_still_teaches(self):
        from paddle1_tpu.core.errors import InvalidArgumentError

        @paddle.jit.to_static
        def g(x):
            # break keeps the loop unconverted; the traced condition
            # then hits the actionable teaching error
            while (x > 0).all():
                x = x - 1
                if float(x.sum()) < -100:
                    break
            return x

        with pytest.raises((InvalidArgumentError, Exception)) as ei:
            g(to_tensor(np.ones(4, np.float32)))
        msg = str(ei.value)
        assert ("static.nn" in msg or "while_loop" in msg
                or "traced" in msg.lower() or "Tracer" in msg)

    def test_graph_native_cond_still_works(self):
        @paddle.jit.to_static
        def f(x):
            return paddle.static.nn.cond(
                (x.sum() > 0), lambda: x + 1, lambda: x - 1)

        out = f(to_tensor(np.ones(4, np.float32)))
        np.testing.assert_allclose(np.asarray(out.numpy()), 2.0)


class TestFS:
    def test_localfs_surface(self, tmp_path):
        from paddle1_tpu.distributed.fleet.utils import LocalFS
        fs = LocalFS()
        d = tmp_path / "a"
        fs.mkdirs(str(d))
        assert fs.is_dir(str(d)) and not fs.is_file(str(d))
        f = d / "x.txt"
        fs.touch(str(f))
        assert fs.is_file(str(f))
        dirs, files = fs.ls_dir(str(d))
        assert files == ["x.txt"] and dirs == []
        fs.mv(str(f), str(d / "y.txt"))
        assert fs.is_exist(str(d / "y.txt"))
        from paddle1_tpu.distributed.fleet.utils.fs import FSFileExistsError
        fs.touch(str(d / "z.txt"))
        with pytest.raises(FSFileExistsError):
            fs.mv(str(d / "z.txt"), str(d / "y.txt"))
        assert not fs.need_upload_download()
        fs.delete(str(d))
        assert not fs.is_exist(str(d))

    def test_hdfs_requires_cli(self):
        from paddle1_tpu.distributed.fleet.utils import HDFSClient
        from paddle1_tpu.core.errors import PreconditionNotMetError
        with pytest.raises(PreconditionNotMetError):
            HDFSClient(hadoop_home="/nonexistent")

    def test_checkpoint_to_remote_roundtrip(self, tmp_path):
        """Local training checkpoints replicate through the fs layer; a
        cold host restores from the remote copy (reference HDFS flow)."""
        from paddle1_tpu.distributed.fleet.utils import LocalFS
        from paddle1_tpu.incubate import train_epoch_range
        remote = tmp_path / "remote"
        fs = LocalFS()

        def run(local_dir, epochs_to_do):
            lin, x, y = _linear_and_data(3)
            opt = paddle.optimizer.SGD(learning_rate=0.1,
                                       parameters=lin.parameters())
            done = []
            for ep in train_epoch_range(
                    4, lin, opt, name="t", checkpoint_dir=str(local_dir),
                    fs=fs, remote_dir=str(remote)):
                _step(lin, opt, x, y)
                done.append(ep)
                if len(done) >= epochs_to_do:
                    break
            return done, lin

        done1, _ = run(tmp_path / "host1", 2)
        assert done1 == [0, 1]
        assert fs.is_exist(str(remote))
        # "new host": fresh local dir. Breaking out of the epoch loop
        # suspends the generator before epoch 1's save, so the durable
        # snapshot is epoch 0 → the cold host resumes at epoch 1.
        done2, _ = run(tmp_path / "host2", 10)
        assert done2 == [1, 2, 3], done2


class TestCustomOps:
    def test_register_and_run_eager_and_jit(self):
        from paddle1_tpu.utils import register_op, get_op

        @register_op("test_swish")
        def swish(x):
            return x * jax.nn.sigmoid(x)

        op = get_op("test_swish")
        x = np.random.default_rng(0).standard_normal(8).astype(np.float32)
        out = op(to_tensor(x))
        np.testing.assert_allclose(np.asarray(out.numpy()),
                                   x / (1 + np.exp(-x)), rtol=1e-5)
        # under jit
        f = jax.jit(lambda a: op(Tensor(a)).data)
        np.testing.assert_allclose(np.asarray(f(jnp.asarray(x))),
                                   x / (1 + np.exp(-x)), rtol=1e-5)

    def test_autograd_through_custom_op(self):
        from paddle1_tpu.utils import register_op
        op = register_op("test_square3", lambda x: 3.0 * x * x)
        t = to_tensor(np.array([2.0], np.float32))
        t.stop_gradient = False
        op(t).sum().backward()
        np.testing.assert_allclose(np.asarray(t.grad.data), [12.0],
                                   rtol=1e-6)

    def test_custom_bwd(self):
        from paddle1_tpu.utils import register_op

        def fwd(x):
            return x * 2.0, x.shape

        def bwd(res, g):
            return (jnp.full(res, 100.0),)  # deliberately wrong grad

        op = register_op("test_custom_bwd", fwd, bwd)
        t = to_tensor(np.ones(3, np.float32))
        t.stop_gradient = False
        op(t).sum().backward()
        np.testing.assert_allclose(np.asarray(t.grad.data), 100.0)

    def test_duplicate_registration_rejected(self):
        from paddle1_tpu.utils import register_op
        from paddle1_tpu.core.errors import InvalidArgumentError
        register_op("test_dup", lambda x: x)
        with pytest.raises(InvalidArgumentError):
            register_op("test_dup", lambda x: x)

    def test_cpp_extension_teaches(self):
        from paddle1_tpu.utils import cpp_extension
        from paddle1_tpu.core.errors import InvalidArgumentError
        with pytest.raises(InvalidArgumentError) as ei:
            cpp_extension.load(name="x", sources=["x.cc"])
        assert "Pallas" in str(ei.value)

    def test_load_c_op_library(self, tmp_path):
        """Host C kernel through jax.pure_callback (works under jit)."""
        src = tmp_path / "op.c"
        src.write_text(textwrap.dedent("""
            #include <stdint.h>
            void scale7(const float* in, float* out, int64_t n) {
              for (int64_t i = 0; i < n; ++i) out[i] = 7.0f * in[i];
            }
        """))
        so = tmp_path / "libop.so"
        r = subprocess.run(["gcc", "-O2", "-shared", "-fPIC", str(src),
                            "-o", str(so)], capture_output=True)
        if r.returncode != 0:
            pytest.skip("no C toolchain")
        from paddle1_tpu.utils import load_op_library
        op = load_op_library(str(so), "test_scale7", "scale7")
        x = np.arange(6, dtype=np.float32).reshape(2, 3)
        out = op(to_tensor(x))
        np.testing.assert_allclose(np.asarray(out.numpy()), 7 * x)
        f = jax.jit(lambda a: op(Tensor(a)).data)
        np.testing.assert_allclose(np.asarray(f(jnp.asarray(x))), 7 * x)
