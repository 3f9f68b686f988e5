"""LARS / Ftrl / AdaDelta numeric checks vs the reference kernel
formulas (lars_momentum_op.h, ftrl_op.h,
adadelta_op.h) + the fleet lars/lamb meta-optimizer toggles."""

import numpy as np
import pytest

import paddle1_tpu as paddle
from paddle1_tpu.core.tensor import to_tensor


def _param(shape, val, name_suffix=""):
    lay = paddle.nn.Layer()
    p = lay.create_parameter(list(shape))
    p.set_value(val)
    return p


def _step(opt, p, grad):
    p.clear_grad() if p.grad is not None else None
    (p * to_tensor(grad)).sum().backward()
    opt.step()
    opt.clear_grad()


class TestLars:
    def test_matches_kernel_formula(self):
        rng = np.random.default_rng(0)
        v0 = rng.standard_normal((4, 3)).astype(np.float32)
        g = rng.standard_normal((4, 3)).astype(np.float32)
        p = _param((4, 3), v0)
        lr, mu, coeff, wd, eps = 0.1, 0.9, 0.001, 0.0005, 1e-9
        opt = paddle.optimizer.Lars(learning_rate=lr, momentum=mu,
                                    parameters=[p], lars_coeff=coeff,
                                    lars_weight_decay=wd, epsilon=eps)
        vel = np.zeros_like(v0)
        pv = v0.copy()
        for _ in range(3):
            pn = np.sqrt((pv ** 2).sum())
            gn = np.sqrt((g ** 2).sum())
            local_lr = lr * coeff * pn / (gn + wd * pn + eps)
            vel = mu * vel + local_lr * (g + wd * pv)
            pv = pv - vel
            _step(opt, p, g)
        np.testing.assert_allclose(np.asarray(p.numpy()), pv,
                                   rtol=2e-5, atol=1e-6)

    def test_user_regularization_applies_before_lars(self):
        rng = np.random.default_rng(4)
        v0 = rng.standard_normal((4,)).astype(np.float32)
        g = rng.standard_normal((4,)).astype(np.float32)
        p = _param((4,), v0)
        lr, mu, coeff, wd, l2 = 0.1, 0.9, 0.001, 0.0005, 0.01
        opt = paddle.optimizer.Lars(learning_rate=lr, momentum=mu,
                                    parameters=[p], lars_coeff=coeff,
                                    lars_weight_decay=wd,
                                    weight_decay=l2, epsilon=1e-9)
        vel = np.zeros_like(v0)
        pv = v0.copy()
        for _ in range(2):
            greg = g + l2 * pv           # user L2 first
            pn = np.sqrt((pv ** 2).sum())
            gn = np.sqrt((greg ** 2).sum())
            local_lr = lr * coeff * pn / (gn + wd * pn + 1e-9)
            vel = mu * vel + local_lr * (greg + wd * pv)
            pv = pv - vel
            _step(opt, p, g)
        np.testing.assert_allclose(np.asarray(p.numpy()), pv,
                                   rtol=2e-5, atol=1e-6)

    def test_zero_weight_decay_degrades_to_momentum(self):
        rng = np.random.default_rng(1)
        v0 = rng.standard_normal((5,)).astype(np.float32)
        g = rng.standard_normal((5,)).astype(np.float32)
        p1 = _param((5,), v0)
        p2 = _param((5,), v0)
        lars = paddle.optimizer.Lars(learning_rate=0.1, momentum=0.9,
                                     parameters=[p1],
                                     lars_weight_decay=0.0)
        mom = paddle.optimizer.Momentum(learning_rate=0.1, momentum=0.9,
                                        parameters=[p2])
        for _ in range(2):
            _step(lars, p1, g)
            _step(mom, p2, g)
        np.testing.assert_allclose(np.asarray(p1.numpy()),
                                   np.asarray(p2.numpy()), rtol=1e-6)


class TestFtrl:
    @pytest.mark.parametrize("lr_power", [-0.5, -0.3])
    def test_matches_kernel_formula(self, lr_power):
        rng = np.random.default_rng(2)
        v0 = (rng.standard_normal((6,)) * 0.5).astype(np.float32)
        p = _param((6,), v0)
        lr, l1, l2 = 0.05, 0.1, 0.2
        opt = paddle.optimizer.Ftrl(learning_rate=lr, l1=l1, l2=l2,
                                    lr_power=lr_power, parameters=[p])
        l1k, l2k = l1 + 1e-10, l2 + 1e-10
        sq = np.zeros_like(v0)
        lin = np.zeros_like(v0)
        pv = v0.copy()
        for i in range(4):
            g = (rng.standard_normal(6) * 0.3).astype(np.float32)
            new_sq = sq + g * g
            if lr_power == -0.5:
                sigma = (np.sqrt(new_sq) - np.sqrt(sq)) / lr
                y = np.sqrt(new_sq) / lr + 2 * l2k
            else:
                sigma = (new_sq ** (-lr_power) - sq ** (-lr_power)) / lr
                y = new_sq ** (-lr_power) / lr + 2 * l2k
            lin = lin + g - sigma * pv
            x = l1k * np.sign(lin) - lin
            pv = np.where(np.abs(lin) > l1k, x / y, 0.0).astype(
                np.float32)
            sq = new_sq
            _step(opt, p, g)
        np.testing.assert_allclose(np.asarray(p.numpy()), pv,
                                   rtol=2e-4, atol=1e-6)

    def test_l1_shrinkage_produces_exact_zeros(self):
        p = _param((8,), np.full(8, 0.01, np.float32))
        opt = paddle.optimizer.Ftrl(learning_rate=0.1, l1=10.0, l2=0.0,
                                    parameters=[p])
        _step(opt, p, np.full(8, 0.001, np.float32))
        assert (np.asarray(p.numpy()) == 0.0).all()


class TestAdaDelta:
    def test_matches_kernel_formula(self):
        rng = np.random.default_rng(3)
        v0 = rng.standard_normal((5,)).astype(np.float32)
        p = _param((5,), v0)
        rho, eps = 0.95, 1e-6
        opt = paddle.optimizer.AdaDelta(learning_rate=1.0, rho=rho,
                                        epsilon=eps, parameters=[p])
        Eg = np.zeros_like(v0)
        Ex = np.zeros_like(v0)
        pv = v0.copy()
        for i in range(3):
            g = rng.standard_normal(5).astype(np.float32)
            Eg = rho * Eg + (1 - rho) * g * g
            upd = -np.sqrt((Ex + eps) / (Eg + eps)) * g
            Ex = rho * Ex + (1 - rho) * upd * upd
            pv = pv + upd
            _step(opt, p, g)
        np.testing.assert_allclose(np.asarray(p.numpy()), pv,
                                   rtol=2e-4, atol=1e-6)


class TestFleetToggles:
    def test_lars_swaps_momentum(self):
        from paddle1_tpu.distributed.fleet import DistributedStrategy
        from paddle1_tpu.distributed.fleet.meta_optimizers import \
            apply_optimizer_meta
        from paddle1_tpu.optimizer import Ftrl, Lamb, Lars
        p = _param((3,), np.zeros(3, np.float32))
        st = DistributedStrategy()
        st.lars = True
        st.lars_configs = {"lars_coeff": 0.002,
                           "lars_weight_decay": 0.001}
        mom = paddle.optimizer.Momentum(learning_rate=0.1,
                                        momentum=0.8, parameters=[p])
        out = apply_optimizer_meta(mom, st)
        assert isinstance(out, Lars)
        assert out._lars_coeff == 0.002
        assert out._momentum == 0.8
        assert out._parameter_list == [p]
        # a non-Momentum optimizer passes through
        adam = paddle.optimizer.Adam(parameters=[p])
        assert apply_optimizer_meta(adam, st) is adam

    def test_lamb_swaps_adam(self):
        from paddle1_tpu.distributed.fleet import DistributedStrategy
        from paddle1_tpu.distributed.fleet.meta_optimizers import \
            apply_optimizer_meta
        from paddle1_tpu.optimizer import Lamb
        p = _param((3,), np.zeros(3, np.float32))
        st = DistributedStrategy()
        st.lamb = True
        st.lamb_configs = {"lamb_weight_decay": 0.02}
        adam = paddle.optimizer.Adam(learning_rate=0.01,
                                     parameters=[p])
        out = apply_optimizer_meta(adam, st)
        assert isinstance(out, Lamb)
        assert out._lamb_wd == 0.02

    def test_fluid_legacy_spellings(self):
        import paddle1_tpu.fluid as fluid
        assert fluid.optimizer.LarsMomentumOptimizer \
            is paddle.optimizer.Lars
        assert fluid.optimizer.FtrlOptimizer is paddle.optimizer.Ftrl
        assert fluid.optimizer.AdadeltaOptimizer \
            is paddle.optimizer.AdaDelta
