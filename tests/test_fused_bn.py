"""Batch-norm tests. The given-statistics Pallas kernels (ISSUE 15) in
interpret mode on CPU, which exercises the same kernel code the TPU
executes: parity matrix fwd + bwd, fp32 + bf16, eval mode, with/without
residual-add and relu, kernel path vs the XLA lowering; plus the flag
gating, the SyncBatchNorm local-stats reuse, the collect_stat_updates
functionalization, and the eval-mode no-copy/no-retrace regressions
(ISSUE 15 satellite 6). The training-mode XLA composition (ISSUE 26),
the only training path, against a float64 numpy batch norm, and that no
value of ``fused_bn`` puts a kernel in it (ISSUE 29)."""

import warnings

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle1_tpu as paddle
import paddle1_tpu.nn.functional as F
from paddle1_tpu.core.flags import flags_guard
from paddle1_tpu.core.tensor import Tensor, to_tensor


def _data(rows_shape=(4, 8, 8), c=64, seed=0, dtype=np.float32):
    rng = np.random.default_rng(seed)
    n, h, w = rows_shape
    x = (rng.standard_normal((n, c, h, w)) * 2 + 1).astype(dtype)
    g = rng.standard_normal((c,)).astype(np.float32)
    b = rng.standard_normal((c,)).astype(np.float32)
    m = rng.standard_normal((c,)).astype(np.float32)
    v = (rng.standard_normal((c,)).astype(np.float32)) ** 2 + 0.5
    res = rng.standard_normal((n, c, h, w)).astype(dtype)
    return x, g, b, m, v, res


class TestKernelSupported:
    def test_supported_matrix(self):
        from paddle1_tpu.ops.pallas import fused_bn as pbn
        assert pbn.supported((256, 64))
        assert pbn.supported((4, 8, 8, 64))          # rows = 256
        assert not pbn.supported((256, 63))          # lane-unfriendly C
        assert not pbn.supported((7, 64))            # rows don't tile
        assert not pbn.supported((64,))              # no row dim
        # 16-bit compute needs a sublane-aligned row block
        assert pbn.supported((256, 64), jnp.bfloat16)

    def test_bad_act_typed(self):
        from paddle1_tpu.core.errors import InvalidArgumentError
        from paddle1_tpu.ops.pallas import fused_bn as pbn
        x = jnp.ones((64, 8), jnp.float32)
        with pytest.raises(InvalidArgumentError):
            pbn.fused_bn_norm(x, jnp.zeros(8), jnp.ones(8), jnp.ones(8),
                              jnp.zeros(8), 1e-5, act="gelu")
        with pytest.raises(InvalidArgumentError):
            F.fused_batch_norm_act(
                to_tensor(np.ones((2, 8, 4, 4), np.float32)),
                to_tensor(np.zeros(8, np.float32)),
                to_tensor(np.ones(8, np.float32)),
                to_tensor(np.ones(8, np.float32)),
                to_tensor(np.zeros(8, np.float32)), act="gelu")

    def test_requires_affine_and_matching_residual(self):
        from paddle1_tpu.core.errors import InvalidArgumentError
        x = to_tensor(np.ones((2, 8, 4, 4), np.float32))
        m = to_tensor(np.zeros(8, np.float32))
        v = to_tensor(np.ones(8, np.float32))
        with pytest.raises(InvalidArgumentError):
            F.fused_batch_norm_act(x, m, v, None, None)
        with pytest.raises(InvalidArgumentError):
            F.fused_batch_norm_act(
                x, m, v, to_tensor(np.ones(8, np.float32)),
                to_tensor(np.zeros(8, np.float32)),
                residual=to_tensor(np.ones((2, 8, 4, 2), np.float32)))


class TestFusedBnParity:
    """Given statistics (eval mode): kernel path vs XLA lowering through
    the public functional, tape backward included — the acceptance
    matrix."""

    def _run(self, fused, act, use_res, dtype):
        x, g, b, m0, v0, res = _data(dtype=dtype)
        xt = to_tensor(x)
        xt.stop_gradient = False
        rt = to_tensor(res)
        rt.stop_gradient = False
        m = to_tensor(m0.copy())
        v = to_tensor(v0.copy())
        gw = to_tensor(g)
        gw.stop_gradient = False
        bw = to_tensor(b)
        bw.stop_gradient = False
        # non-uniform cotangent: a plain .sum() makes dgamma a pure
        # cancellation (sum of xhat ~ 0) and the comparison noise. Under
        # relu it is zero where the pre-activation sits on the knife
        # edge, where a last-bit difference would flip the mask
        cot = np.random.default_rng(7).standard_normal(
            x.shape).astype(np.float32)
        if act == "relu":
            bs = (1, -1, 1, 1)
            pre = ((x.astype(np.float64) - m0.reshape(bs))
                   / np.sqrt(v0.reshape(bs) + 1e-5) * g.reshape(bs)
                   + b.reshape(bs))
            if use_res:
                pre = pre + res.astype(np.float64)
            cot = cot * (np.abs(pre) > 1e-3)
        with flags_guard(conv_nhwc="always", fused_bn=fused):
            if act == "identity" and not use_res:
                out = F.batch_norm(xt, m, v, gw, bw, training=False)
            else:
                out = F.fused_batch_norm_act(
                    xt, m, v, gw, bw, training=False, act=act,
                    residual=rt if use_res else None)
            if np.dtype(dtype).itemsize == 2:
                # normalize output-dtype semantics: the XLA lowering
                # promotes a bf16 input to f32 through the f32 buffers
                # where the kernel stays bf16-native — pin both paths
                # to bf16 so forward AND cotangent see one rounding
                out = out.astype("bfloat16")
            (out.astype("float32") * to_tensor(cot)).sum().backward()
        outs = [np.asarray(out.astype("float32").numpy()),
                np.asarray(xt.grad.astype("float32").numpy()),
                np.asarray(gw.grad.numpy()), np.asarray(bw.grad.numpy()),
                np.asarray(m.numpy()), np.asarray(v.numpy())]
        if use_res:
            outs.append(np.asarray(rt.grad.astype("float32").numpy()))
        return outs

    @pytest.mark.parametrize("act", ["identity", "relu"])
    @pytest.mark.parametrize("use_res", [False, True])
    def test_fp32_matrix(self, act, use_res):
        want = self._run("never", act, use_res, np.float32)
        got = self._run("always", act, use_res, np.float32)
        for i, (a, b) in enumerate(zip(got, want)):
            np.testing.assert_allclose(
                a, b, rtol=2e-5, atol=2e-5,
                err_msg=f"out {i} act={act} res={use_res}")

    @pytest.mark.parametrize("act", ["identity", "relu"])
    @pytest.mark.parametrize("use_res", [False, True])
    def test_bf16_matrix(self, act, use_res):
        import ml_dtypes
        dt = np.dtype(ml_dtypes.bfloat16)
        want = self._run("never", act, use_res, dt)
        got = self._run("always", act, use_res, dt)
        # both paths compute in f32 and round once: bf16 resolution
        for i, (a, b) in enumerate(zip(got, want)):
            np.testing.assert_allclose(
                a, b, rtol=3e-2, atol=3e-2,
                err_msg=f"out {i} act={act} res={use_res}")

    def test_running_stats_update_parity(self):
        x, g, b, m0, v0, _ = _data()
        m = to_tensor(m0.copy())
        v = to_tensor(v0.copy())
        with flags_guard(conv_nhwc="always"):
            F.batch_norm(to_tensor(x), m, v, to_tensor(g), to_tensor(b),
                         training=True, momentum=0.8)
        want = _bn_float64(x, g, b, None, np.zeros_like(x), 1e-5,
                           "identity", 1)
        np.testing.assert_allclose(
            m.numpy(), 0.8 * m0 + 0.2 * want["mean"], rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(
            v.numpy(), 0.8 * v0 + 0.2 * want["var"], rtol=1e-5, atol=1e-6)
        assert np.abs(np.asarray(m.numpy()) - m0).max() > 1e-3  # did move

    def test_unsupported_shape_falls_back(self):
        # C=63 can't take the given-stats kernel: the flag path must
        # silently use the XLA lowering and still be correct
        rng = np.random.default_rng(3)
        x = rng.standard_normal((2, 63, 4, 4)).astype(np.float32)
        g = rng.standard_normal(63).astype(np.float32)
        b = rng.standard_normal(63).astype(np.float32)
        outs = {}
        for fused in ("never", "always"):
            with flags_guard(conv_nhwc="always", fused_bn=fused):
                outs[fused] = np.asarray(F.batch_norm(
                    to_tensor(x), to_tensor(np.zeros(63, np.float32)),
                    to_tensor(np.ones(63, np.float32)), to_tensor(g),
                    to_tensor(b), training=False).numpy())
        np.testing.assert_allclose(outs["never"], outs["always"],
                                   rtol=1e-5, atol=1e-6)

    # what the flag resolves to for given statistics, on shapes either
    # side of the 4 MiB threshold that ``auto`` had (fused_bn_auto_mb,
    # never measured): (flag, shape, dtype) -> kernel?
    BIG, SMALL = (1024, 1024, 64), (8, 8, 64)    # 256 MiB / 16 KiB of f32

    @pytest.mark.parametrize("flag_value,shape,dtype,want", [
        ("always", BIG, "float32", True),
        ("always", SMALL, "float32", True),
        ("always", SMALL, "bfloat16", True),
        ("never", BIG, "float32", False),
        ("never", BIG, "bfloat16", False),
        ("auto", BIG, "float32", False),      # the backend here is the CPU
        ("auto", SMALL, "bfloat16", False),
    ])
    def test_flag_resolution(self, flag_value, shape, dtype, want):
        from paddle1_tpu.nn.functional.norm import fused_bn_active
        with flags_guard(fused_bn=flag_value):
            assert fused_bn_active(shape, jnp.dtype(dtype)) is want

    @pytest.mark.parametrize("shape", [BIG, SMALL])
    def test_auto_on_a_tpu_takes_kernels_for_given_stats_only(
            self, shape, monkeypatch):
        # auto asks the default backend: make it say "tpu"
        from paddle1_tpu.nn.functional.norm import fused_bn_active
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        with flags_guard(fused_bn="auto"):
            assert fused_bn_active(shape, jnp.float32)
        monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
        with flags_guard(fused_bn="auto"):
            assert not fused_bn_active(shape, jnp.float32)

    @pytest.mark.parametrize("name", ["fused_bn_auto_mb", "fused_bn_bwd",
                                      "fused_adam"])
    def test_the_flag_is_gone(self, name):
        from paddle1_tpu.core.errors import InvalidArgumentError
        from paddle1_tpu.core.flags import flag
        with pytest.raises(InvalidArgumentError):
            flag(name)


def _bn_float64(x, g, b, res, cot, eps, act, ch_axis):
    """Plain batch norm, forward and backward, in float64 numpy."""
    x, g, b, cot = (np.asarray(a, np.float64) for a in (x, g, b, cot))
    axes = tuple(i for i in range(x.ndim) if i != ch_axis)
    bs = [1] * x.ndim
    bs[ch_axis] = -1
    n = x.size // x.shape[ch_axis]
    mean = x.mean(axis=axes)
    var = x.var(axis=axes)
    rstd = 1.0 / np.sqrt(var + eps)
    xhat = (x - mean.reshape(bs)) * rstd.reshape(bs)
    pre = xhat * g.reshape(bs) + b.reshape(bs)
    if res is not None:
        pre = pre + np.asarray(res, np.float64)
    y = np.maximum(pre, 0.0) if act == "relu" else pre
    dy = cot * (pre > 0) if act == "relu" else cot
    dg = (dy * xhat).sum(axis=axes)
    db = dy.sum(axis=axes)
    dx = (g * rstd).reshape(bs) * (
        dy - db.reshape(bs) / n - xhat * dg.reshape(bs) / n)
    return {"y": y, "dx": dx, "dgamma": dg, "dbeta": db, "dres": dy,
            "mean": mean, "var": var}


class TestTrainComposition:
    """Training mode, through the public functional and the tape,
    against float64 numpy."""

    def _run(self, dtype, act, use_res, layout, fused="auto"):
        import ml_dtypes
        half = dtype == "bfloat16"
        dt = np.dtype(ml_dtypes.bfloat16) if half else np.dtype(np.float32)
        x, g, b, m0, v0, res = _data(dtype=dt)
        cot = np.random.default_rng(7).standard_normal(x.shape).astype(dt)
        g, b = g.astype(dt), b.astype(dt)
        ch_axis = 1
        if layout == "NHWC":
            x, res, cot = (np.ascontiguousarray(a.transpose(0, 2, 3, 1))
                           for a in (x, res, cot))
            ch_axis = 3
        ts = [to_tensor(a) for a in (x, g, b, res)]
        for t in ts:
            t.stop_gradient = False
        xt, gw, bw, rt = ts
        m, v = to_tensor(m0.copy()), to_tensor(v0.copy())
        with flags_guard(conv_nhwc="never", fused_bn=fused):
            if act == "identity" and not use_res:
                out = F.batch_norm(xt, m, v, gw, bw, training=True,
                                   data_format=layout)
            else:
                out = F.fused_batch_norm_act(
                    xt, m, v, gw, bw, training=True, act=act,
                    data_format=layout, residual=rt if use_res else None)
            assert out.data.dtype == x.dtype       # rounded once, to x's
            (out.astype("float32") * to_tensor(
                cot.astype(np.float32))).sum().backward()
        got = {"y": out, "dx": xt.grad, "dgamma": gw.grad, "dbeta": bw.grad}
        if use_res:
            got["dres"] = rt.grad
        got = {k: np.asarray(t.astype("float32").numpy(), np.float64)
               for k, t in got.items()}
        got["mean"] = (np.asarray(m.numpy(), np.float64) - 0.9 * m0) / 0.1
        got["var"] = (np.asarray(v.numpy(), np.float64) - 0.9 * v0) / 0.1
        want = _bn_float64(x, g, b, res if use_res else None, cot, 1e-5,
                           act, ch_axis)
        return got, want

    @pytest.mark.parametrize("layout", ["NHWC", "NCHW"])
    @pytest.mark.parametrize("use_res", [False, True])
    @pytest.mark.parametrize("act", ["identity", "relu"])
    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    def test_against_float64(self, dtype, act, use_res, layout):
        got, want = self._run(dtype, act, use_res, layout)
        # float32: sums of 256 terms; bfloat16: y, dx, dgamma and dbeta
        # are each rounded ONCE (2^-9 relative), everything before the
        # rounding is float32
        rtol, atol = (5e-3, 2e-3) if dtype == "bfloat16" else (2e-5, 2e-5)
        for k, a in got.items():
            tol = (2e-4, 2e-4) if k in ("mean", "var") else (rtol, atol)
            np.testing.assert_allclose(
                a, want[k], rtol=tol[0], atol=tol[1],
                err_msg=f"{k} {dtype} act={act} res={use_res} {layout}")

    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    def test_statistics_stay_float32(self, dtype):
        from paddle1_tpu.nn.functional.norm import collect_stat_updates
        x, g, b, m0, v0, _ = _data()

        def step(xa):
            return F.batch_norm(
                to_tensor(xa), to_tensor(m0.copy()), to_tensor(v0.copy()),
                to_tensor(g.astype(dtype)), to_tensor(b.astype(dtype)),
                training=True).data

        with flags_guard(conv_nhwc="always", fused_bn="auto"):
            with collect_stat_updates() as sink:
                y = jax.jit(step)(jnp.asarray(x, dtype))
        assert y.dtype == jnp.dtype(dtype)
        assert [u.rule for u in sink] == ["blend", "blend"]
        assert sink[0].value.dtype == jnp.float32      # the mean
        assert sink[1].value.dtype == jnp.float32      # the variance

    @pytest.mark.parametrize("dtype,centre,rtol", [
        # bf16 data at mean 300, spread 1: a bf16 mean is off by up to 1
        # and a bf16 sum of squares loses the variance altogether; the
        # float32 sums keep what the data had
        ("bfloat16", 300.0, 2e-2),
        # float32 data at mean 1000: E[x^2] - mean^2 in float32 keeps 4
        # of the variance's 24 bits, the centred second pass all
        ("float32", 1000.0, 1e-5),
    ])
    def test_variance_keeps_the_data_s_bits(self, dtype, centre, rtol):
        rng = np.random.default_rng(0)
        x = jnp.asarray(centre + rng.standard_normal((8, 4, 4, 16)), dtype)
        m, v = to_tensor(np.zeros(16, "f4")), to_tensor(np.zeros(16, "f4"))
        with flags_guard(fused_bn="auto"):
            F.batch_norm(to_tensor(x), m, v, to_tensor(np.ones(16, "f4")),
                         to_tensor(np.zeros(16, "f4")), training=True,
                         momentum=0.0, data_format="NHWC")
        x64 = np.asarray(x.astype(jnp.float32), np.float64)
        np.testing.assert_allclose(m.numpy(), x64.mean(axis=(0, 1, 2)),
                                   rtol=1e-6)
        np.testing.assert_allclose(v.numpy(), x64.var(axis=(0, 1, 2)),
                                   rtol=rtol, atol=rtol)

    def test_no_affine(self):
        x, _, _, m0, v0, _ = _data()
        xt = to_tensor(x)
        xt.stop_gradient = False
        with flags_guard(conv_nhwc="never", fused_bn="auto"):
            out = F.batch_norm(xt, to_tensor(m0.copy()),
                               to_tensor(v0.copy()), training=True)
            cot = np.random.default_rng(7).standard_normal(
                x.shape).astype(np.float32)
            (out * to_tensor(cot)).sum().backward()
        c = x.shape[1]
        want = _bn_float64(x, np.ones(c), np.zeros(c), None, cot, 1e-5,
                           "identity", 1)
        np.testing.assert_allclose(out.numpy(), want["y"], rtol=2e-5,
                                   atol=2e-5)
        np.testing.assert_allclose(xt.grad.numpy(), want["dx"], rtol=2e-5,
                                   atol=2e-5)


def _zero_fed_full_size_ops(jaxpr, full_size):
    """Census of a (closed) jaxpr, sub-jaxprs included: the ``mul`` /
    ``add`` / ``sub`` equations of ``full_size`` elements with an
    operand that is known to be all zeros (a zero literal, a broadcast
    of one, or elementwise / shape ops of such)."""
    hits = []

    def is_zero_literal(v):      # a Literal has a value, a Var has none
        return (hasattr(v, "val") and np.ndim(v.val) == 0
                and float(v.val) == 0.0)

    def walk(jp, zero_in):
        zero = set(zero_in)

        def z(v):
            return is_zero_literal(v) or (
                not hasattr(v, "val") and v in zero)

        for eqn in jp.eqns:
            name = eqn.primitive.name
            subs = [p for p in eqn.params.values()
                    if hasattr(p, "eqns") or hasattr(p, "jaxpr")]
            if subs:
                for sub in subs:
                    inner = getattr(sub, "jaxpr", sub)
                    if len(inner.invars) != len(eqn.invars):
                        walk(inner, ())
                        continue
                    walk(inner, [iv for iv, ov in zip(inner.invars,
                                                      eqn.invars) if z(ov)])
                continue
            ins = [z(v) for v in eqn.invars]
            size = int(np.prod(eqn.outvars[0].aval.shape)) \
                if eqn.outvars else 0
            if name in ("mul", "add", "sub", "add_any") and any(ins) \
                    and size >= full_size:
                hits.append(str(eqn))
            if (name in ("broadcast_in_dim", "reshape", "convert_element_type",
                         "squeeze", "expand_dims", "neg", "copy")
                    and all(ins)) or (name == "mul" and any(ins)) \
                    or (name == "zeros_like"):
                zero.update(eqn.outvars)
    walk(getattr(jaxpr, "jaxpr", jaxpr), ())
    return hits


class TestStatisticsCarryNoGradient:
    """The batch statistics feed the running averages alone: no term for
    their cotangents, zero or not, in the backward."""

    ROWS, C = (4, 8, 8), 64

    def _fn(self, fused):
        x, g, b, m0, v0, _ = _data(self.ROWS, self.C)
        from paddle1_tpu.autograd import engine as ae
        from paddle1_tpu.nn.functional.norm import collect_stat_updates

        def loss(xa, ga, ba, weight_of_stats=0.0):
            with flags_guard(conv_nhwc="always", fused_bn=fused), \
                    ae.no_grad(), collect_stat_updates() as sink:
                y = F.fused_batch_norm_act(
                    Tensor(xa), to_tensor(m0.copy()), to_tensor(v0.copy()),
                    Tensor(ga), Tensor(ba), training=True, act="relu").data
            stats = sink[0].value.sum() + sink[1].value.sum()
            return (y * y).sum() + weight_of_stats * stats, stats

        return loss, (jnp.asarray(x), jnp.asarray(g), jnp.asarray(b))

    def test_gradient_asked_of_the_statistics_is_zero(self):
        loss, args = self._fn("auto")
        grads = jax.grad(lambda *a: loss(*a)[1], argnums=(0, 1, 2))(*args)
        for gr in grads:
            assert not np.asarray(gr).any()
        # and weighting them into a loss changes no gradient
        a = jax.grad(lambda *a: loss(*a)[0])(*args)
        b = jax.grad(lambda *a: loss(*a, weight_of_stats=3.0)[0])(*args)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_no_full_size_op_on_a_zero_cotangent(self):
        full = int(np.prod(self.ROWS)) * self.C
        loss, args = self._fn("auto")
        jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2),
                                        has_aux=True))(*args)
        assert _zero_fed_full_size_ops(jaxpr, full) == []
        # the census sees what it looks for: a term for the statistics'
        # cotangents, (dmean + 2 dvar (x - mean)) / n with dmean = dvar
        # = zeros, as a custom_vjp rule is handed them
        jaxpr = jax.make_jaxpr(lambda x: (
            jnp.zeros((self.C,)) + 2.0 * jnp.zeros((self.C,)) * (x - 1.0))
            / full)(jnp.ones((full // self.C, self.C)))
        assert _zero_fed_full_size_ops(jaxpr, full)


def _pallas_calls(jaxpr):
    n = 0
    for eqn in jaxpr.eqns:
        n += eqn.primitive.name == "pallas_call"
        for p in eqn.params.values():
            inner = getattr(p, "jaxpr", p)
            if hasattr(inner, "eqns"):
                n += _pallas_calls(inner)
    return n


class TestBottleneckCensus:
    """A ResNet bottleneck's lowered training step reaches no
    ``pallas_call``, on any backend, whatever ``fused_bn`` says:
    ``always`` means nothing in training mode."""

    def _jaxpr(self, fused, backend, monkeypatch):
        from paddle1_tpu.autograd import engine as ae
        from paddle1_tpu.nn.functional.norm import collect_stat_updates
        from paddle1_tpu.vision.models.resnet import BottleneckBlock
        monkeypatch.setattr(jax, "default_backend", lambda: backend)
        paddle.seed(0)
        block = BottleneckBlock(64, 16)
        block.train()
        names = list(block.state_dict())
        params = {k: block.state_dict()[k].data for k in names}

        def loss(params, xa):
            with ae.no_grad(), block.load_functional_state(params), \
                    collect_stat_updates():
                return (block(Tensor(xa)).data ** 2).mean()

        x = jnp.zeros((4, 64, 8, 8), jnp.float32)
        # round the whole trace: a custom_vjp's backward rule is traced
        # after the forward function has returned
        with flags_guard(conv_nhwc="always", fused_bn=fused):
            return jax.make_jaxpr(jax.grad(loss))(params, x)

    @pytest.mark.parametrize("fused", ["auto", "always", "never"])
    @pytest.mark.parametrize("backend", ["cpu", "tpu"])
    def test_training_mode_reaches_no_pallas_call(self, backend, fused,
                                                  monkeypatch):
        assert _pallas_calls(self._jaxpr(fused, backend, monkeypatch)) == 0


class TestCompiledTrainerIntegration:
    """Training-mode batch norm under ParallelEngine: functionalized
    running stats, one trace a program, loss parity with an eager loop
    of the same steps."""

    K = 3

    def _setup(self):
        paddle.seed(0)
        np.random.seed(0)
        model = paddle.nn.Sequential(
            paddle.nn.Conv2D(3, 16, 3, padding=1, bias_attr=False),
            paddle.nn.BatchNorm2D(16),
            paddle.nn.ReLU(),
            paddle.nn.AdaptiveAvgPool2D(1),
            paddle.nn.Flatten(),
            paddle.nn.Linear(16, 4))
        opt = paddle.optimizer.Momentum(learning_rate=0.05,
                                        parameters=model.parameters())
        loss_fn = lambda m, b: \
            ((m(Tensor(b["x"])) - Tensor(b["y"])) ** 2).mean()
        rng = np.random.default_rng(0)
        batches = [
            {"x": rng.standard_normal((8, 3, 16, 16)).astype(np.float32),
             "y": rng.standard_normal((8, 4)).astype(np.float32)}
            for _ in range(self.K)]
        return model, opt, loss_fn, batches

    @staticmethod
    def _running_stats(model):
        return {k: np.asarray(v.data)
                for k, v in model.state_dict().items()
                if "_mean" in k or "_variance" in k}

    def _train_engine(self):
        from paddle1_tpu.distributed import ParallelEngine, build_mesh
        model, opt, loss_fn, batches = self._setup()
        mesh = build_mesh(dp=1, devices=jax.devices()[:1])
        with flags_guard(conv_nhwc="always"):
            eng = ParallelEngine(model, opt, loss_fn, mesh=mesh)
            losses = [float(eng.step(b)) for b in batches]
            many = [float(l) for l in eng.step_many(batches)]
            eng.sync_model()
        return losses + many, self._running_stats(model), eng.trace_count

    def _train_eager(self):
        model, opt, loss_fn, batches = self._setup()
        losses = []
        with flags_guard(conv_nhwc="always"):
            for b in batches + batches:
                loss = loss_fn(model, b)
                loss.backward()
                opt.step()
                opt.clear_grad()
                losses.append(float(loss))
        return losses, self._running_stats(model)

    def test_engine_parity_and_stat_functionalization(self):
        l1, s1 = self._train_eager()
        l2, s2, traces = self._train_engine()
        np.testing.assert_allclose(l1, l2, rtol=1e-5, atol=1e-6)
        for k in s1:
            np.testing.assert_allclose(s1[k], s2[k], rtol=1e-5,
                                       atol=1e-6)
            # running stats actually moved under the compiled step
            init = 0.0 if "_mean" in k else 1.0
            assert np.abs(s2[k] - init).max() > 1e-4, k
        assert traces == 2     # the step and the k-step scan, once each

    def test_collector_records_traced_stats(self):
        from paddle1_tpu.nn.functional.norm import collect_stat_updates
        x, g, b, m0, v0, _ = _data()
        with flags_guard(conv_nhwc="always"):
            with collect_stat_updates() as sink:
                def step(xa):
                    m = to_tensor(m0.copy())
                    v = to_tensor(v0.copy())
                    return F.batch_norm(to_tensor(xa), m, v,
                                        to_tensor(g), to_tensor(b),
                                        training=True).data
                jax.jit(step)(jnp.asarray(x))
        assert len(sink) == 2          # the mean's record and the variance's
        assert [u.momentum for u in sink] == [0.9, 0.9]


class TestSyncBatchNormFused:
    """SyncBatchNorm reuses the kernel's local-stats pass and keeps its
    cross-replica psum. Pallas calls carry no shard_map replication
    rule, so the fused variant runs under check_vma=False (any Pallas
    kernel does); grads go through the engine discipline (tape off,
    outer jax.grad)."""

    def _run(self, fused):
        from jax.sharding import Mesh, PartitionSpec as P
        from jax import shard_map
        from paddle1_tpu import nn
        from paddle1_tpu.distributed.env import spmd_axes
        from paddle1_tpu.autograd import engine as ae

        devs = jax.devices()[:4]
        mesh = Mesh(np.asarray(devs), ("data",))
        rng = np.random.default_rng(0)
        x = rng.standard_normal((8, 64, 4, 4)).astype(np.float32) * 2 + 1
        paddle.seed(0)
        sbn = nn.SyncBatchNorm(64)
        w, b = sbn.weight.data, sbn.bias.data

        def shard_fn(xs, w, b):
            with ae.no_grad(), spmd_axes(dp="data"), \
                    flags_guard(conv_nhwc="always", fused_bn=fused):
                with warnings.catch_warnings():
                    warnings.simplefilter("ignore")
                    return sbn(Tensor(xs)).data

        mapped = shard_map(shard_fn, mesh=mesh,
                           in_specs=(P("data"), P(), P()),
                           out_specs=P("data"), check_vma=False)
        y = jax.jit(mapped)(jnp.asarray(x), w, b)
        grads = jax.grad(lambda xs, w, b: (mapped(xs, w, b) ** 2).sum(),
                         argnums=(0, 1, 2))(jnp.asarray(x), w, b)
        return np.asarray(y), [np.asarray(g) for g in grads], sbn

    def test_matches_global_bn_and_xla_path(self):
        y, grads, sbn = self._run("always")
        # global-batch reference
        rng = np.random.default_rng(0)
        x = rng.standard_normal((8, 64, 4, 4)).astype(np.float32) * 2 + 1
        mean = x.mean(axis=(0, 2, 3), keepdims=True)
        var = x.var(axis=(0, 2, 3), keepdims=True)
        want = (x - mean) / np.sqrt(var + sbn._epsilon)
        np.testing.assert_allclose(y, want, rtol=2e-4, atol=2e-4)
        # and bit-for-bit-level parity with the XLA lowering
        y2, grads2, _ = self._run("never")
        np.testing.assert_allclose(y, y2, rtol=1e-5, atol=1e-5)
        for a, b in zip(grads, grads2):
            np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)


class TestEvalHotPathRegressions:
    """ISSUE 15 satellite 6: eval-mode BN must not defensively copy the
    running-stat buffers per call, round-trip the host per step, or
    retrace under repeated calls."""

    def _model(self):
        paddle.seed(0)
        m = paddle.nn.Sequential(
            paddle.nn.Conv2D(3, 16, 3, padding=1, bias_attr=False),
            paddle.nn.BatchNorm2D(16),
            paddle.nn.ReLU(),
            paddle.nn.Conv2D(16, 16, 3, padding=1, bias_attr=False),
            paddle.nn.BatchNorm2D(16))
        m.eval()
        return m

    def test_eval_no_buffer_copy_and_no_host_round_trip(self):
        m = self._model()
        bn = m[1]
        x = jnp.asarray(np.random.default_rng(0)
                        .standard_normal((2, 16, 8, 8)).astype(np.float32))
        mean_arr = bn._mean.data
        var_arr = bn._variance.data
        bn(Tensor(x))  # settle lazy constants (cached epsilon scalar)
        # the buffers ride straight through: same device arrays (no
        # defensive copy per call), and an eval BN forward moves
        # NOTHING host<->device once inputs are device-resident — the
        # per-call epsilon-constant transfer was the satellite-6 audit
        # finding, fixed by the cached weak-typed scalar
        with jax.transfer_guard("disallow"):
            bn(Tensor(x))
        assert bn._mean.data is mean_arr
        assert bn._variance.data is var_arr

    def test_running_stat_blend_no_host_round_trip(self):
        # the eager running-stat blend stays on device (momentum
        # scalars are cached). The train-mode FORWARD cannot be fully
        # transfer-free under the eager tape — jax's own jvp rules
        # (e.g. rsqrt's coefficient) lift fresh scalar constants per
        # linearize — but the compiled-trainer path runs the whole
        # step in-jit, where constants fold (TestCompiledTrainer...)
        from paddle1_tpu.nn.functional.norm import _update_running_stats
        m = to_tensor(np.zeros(16, np.float32))
        v = to_tensor(np.ones(16, np.float32))
        mean = to_tensor(np.full(16, 0.5, np.float32))
        var = to_tensor(np.full(16, 2.0, np.float32))
        _update_running_stats(m, v, mean, var, 0.9, "test")  # warm
        before = m.data
        with jax.transfer_guard("disallow"):
            _update_running_stats(m, v, mean, var, 0.9, "test")
        assert m.data is not before  # blended, on device

    def test_eval_forward_compiles_once(self):
        m = self._model()
        traces = [0]

        def fwd(xa):
            traces[0] += 1
            from paddle1_tpu.autograd import engine as ae
            with ae.no_grad():
                return m(Tensor(xa)).data

        j = jax.jit(fwd)
        x = jnp.asarray(np.random.default_rng(0)
                        .standard_normal((2, 3, 8, 8)).astype(np.float32))
        a = j(x)
        b = j(x)
        assert traces[0] == 1
        np.testing.assert_allclose(np.asarray(a), np.asarray(b))

    def test_eval_dispatch_count_stable(self):
        # BN-heavy eager eval: the per-forward op dispatch count must
        # not grow call over call (no per-step host work accreting)
        from paddle1_tpu.autograd import engine as ae
        m = self._model()
        x = Tensor(jnp.asarray(
            np.random.default_rng(0).standard_normal((2, 3, 8, 8))
            .astype(np.float32)))
        m(x)
        orig = ae._apply_impl
        seen = []
        try:
            def probe(*a, **k):
                seen.append(a[0])
                return orig(*a, **k)
            ae._apply_impl = probe
            m(x)
            first = len(seen)
            seen.clear()
            m(x)
            second = len(seen)
        finally:
            ae._apply_impl = orig
        assert first == second and first > 0
