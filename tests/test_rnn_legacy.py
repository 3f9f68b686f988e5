"""Fluid RNN-era ops: dynamic_lstm(p) /
dynamic_gru / gru_unit / lstm vs numpy references with the kernel's
gate orders (lstm: old-api [c,i,f,o], gru: [u,r,c])."""

import numpy as np
import pytest

import paddle1_tpu as paddle
import paddle1_tpu.fluid as fluid
import paddle1_tpu.fluid.layers as L
from paddle1_tpu.core.tensor import to_tensor

B, T, H, D = 3, 6, 5, 4


def _sig(v):
    return 1.0 / (1.0 + np.exp(-v))


def np_dynamic_lstm(x, w, b, lens, use_peep, reverse=False):
    Hh = x.shape[-1] // 4
    gb = b[0, :4 * Hh]
    if use_peep:
        cki, ckf, cko = (b[0, 4 * Hh:5 * Hh], b[0, 5 * Hh:6 * Hh],
                         b[0, 6 * Hh:7 * Hh])
    else:
        cki = ckf = cko = np.zeros(Hh, np.float32)
    hs = np.zeros(x.shape[:2] + (Hh,), np.float32)
    cs = np.zeros_like(hs)
    for bi in range(x.shape[0]):
        h = np.zeros(Hh, np.float32)
        c = np.zeros(Hh, np.float32)
        order = range(lens[bi])
        if reverse:
            order = reversed(list(order))
        for t in order:
            g = x[bi, t] + h @ w + gb
            gc, gi, gf, go = np.split(g, 4)
            i = _sig(gi + c * cki)
            f = _sig(gf + c * ckf)
            cn = f * c + i * np.tanh(gc)
            o = _sig(go + cn * cko)
            hn = o * np.tanh(cn)
            hs[bi, t], cs[bi, t] = hn, cn
            h, c = hn, cn
    return hs, cs


def np_dynamic_gru(x, w, b, lens, origin_mode, reverse=False):
    Dd = x.shape[-1] // 3
    hs = np.zeros(x.shape[:2] + (Dd,), np.float32)
    w_ur, w_c = w[:, :2 * Dd], w[:, 2 * Dd:]
    for bi in range(x.shape[0]):
        h = np.zeros(Dd, np.float32)
        order = range(lens[bi])
        if reverse:
            order = reversed(list(order))
        for t in order:
            g = x[bi, t] + b[0]
            ur = g[:2 * Dd] + h @ w_ur
            u, r = _sig(ur[:Dd]), _sig(ur[Dd:])
            c = np.tanh(g[2 * Dd:] + (r * h) @ w_c)
            h = u * h + (1 - u) * c if origin_mode \
                else (1 - u) * h + u * c
            hs[bi, t] = h
    return hs


def _set_params(rng, scale=0.4):
    """Fetch the just-created implicit (weight, bias) pair — the last
    two implicit parameters — and overwrite with known values."""
    ps = fluid.layers.implicit_parameters()[-2:]
    vals = []
    for p in ps:
        v = (rng.standard_normal(p.shape) * scale).astype(np.float32)
        p.set_value(v)
        vals.append(v)
    return vals


class TestDynamicLSTM:
    @pytest.mark.parametrize("peep", [False, True])
    @pytest.mark.parametrize("reverse", [False, True])
    def test_matches_numpy(self, peep, reverse):
        rng = np.random.default_rng(0)
        x = rng.standard_normal((B, T, 4 * H)).astype(np.float32)
        lens = np.array([6, 3, 5], np.int64)
        nm = f"dl_{peep}_{reverse}"
        L.dynamic_lstm(to_tensor(x), 4 * H, lengths=lens, name=nm,
                       use_peepholes=peep, is_reverse=reverse)
        w, b = _set_params(rng)
        hid, cell = L.dynamic_lstm(to_tensor(x), 4 * H, lengths=lens,
                                   name=nm, use_peepholes=peep,
                                   is_reverse=reverse)
        ref_h, ref_c = np_dynamic_lstm(x, w, b, lens, peep,
                                       reverse=reverse)
        np.testing.assert_allclose(np.asarray(hid.numpy()), ref_h,
                                   rtol=2e-4, atol=2e-5)
        np.testing.assert_allclose(np.asarray(cell.numpy()), ref_c,
                                   rtol=2e-4, atol=2e-5)
        # padded positions are exactly zero
        assert np.abs(np.asarray(hid.numpy())[1, 3:]).max() == 0

    def test_bad_shape_teaches(self):
        with pytest.raises(Exception, match="4\\*hidden"):
            L.dynamic_lstm(to_tensor(np.zeros((B, 4 * H),
                                              np.float32)), 4 * H)

    def test_gradients_flow(self):
        rng = np.random.default_rng(1)
        x = to_tensor(rng.standard_normal((B, T, 4 * H)).astype(
            np.float32))
        x.stop_gradient = False
        hid, cell = L.dynamic_lstm(x, 4 * H, name="dl_grad",
                                   use_peepholes=True)
        (hid.sum() + cell.sum()).backward()
        assert np.abs(np.asarray(x.grad.numpy())).sum() > 0


class TestDynamicLSTMP:
    def test_projection_shapes_and_numpy(self):
        rng = np.random.default_rng(2)
        P = 3
        x = rng.standard_normal((B, T, 4 * H)).astype(np.float32)
        lens = np.array([6, 4, 2], np.int64)
        L.dynamic_lstmp(to_tensor(x), 4 * H, P, lengths=lens,
                        name="dlp", use_peepholes=False)
        ps = fluid.layers.implicit_parameters()[-3:]
        w = (rng.standard_normal((P, 4 * H)) * 0.4).astype(np.float32)
        b = (rng.standard_normal((1, 4 * H)) * 0.4).astype(np.float32)
        pw = (rng.standard_normal((H, P)) * 0.4).astype(np.float32)
        # creation order: weight, bias, proj_weight
        ps[0].set_value(w)
        ps[1].set_value(b)
        ps[2].set_value(pw)
        proj, cell = L.dynamic_lstmp(to_tensor(x), 4 * H, P,
                                     lengths=lens, name="dlp",
                                     use_peepholes=False)
        assert tuple(proj.shape) == (B, T, P)
        assert tuple(cell.shape) == (B, T, H)
        # numpy twin with projection recurrence
        ref_p = np.zeros((B, T, P), np.float32)
        ref_c = np.zeros((B, T, H), np.float32)
        for bi in range(B):
            r = np.zeros(P, np.float32)
            c = np.zeros(H, np.float32)
            for t in range(lens[bi]):
                g = x[bi, t] + r @ w + b[0]
                gc, gi, gf, go = np.split(g, 4)
                i, f = _sig(gi), _sig(gf)
                cn = f * c + i * np.tanh(gc)
                o = _sig(go)
                hn = o * np.tanh(cn)
                rn = np.tanh(hn @ pw)
                ref_p[bi, t], ref_c[bi, t] = rn, cn
                r, c = rn, cn
        np.testing.assert_allclose(np.asarray(proj.numpy()), ref_p,
                                   rtol=2e-4, atol=2e-5)
        np.testing.assert_allclose(np.asarray(cell.numpy()), ref_c,
                                   rtol=2e-4, atol=2e-5)


class TestDynamicGRU:
    @pytest.mark.parametrize("origin", [False, True])
    def test_matches_numpy(self, origin):
        rng = np.random.default_rng(3)
        x = rng.standard_normal((B, T, 3 * D)).astype(np.float32)
        lens = np.array([6, 2, 4], np.int64)
        nm = f"dg_{origin}"
        L.dynamic_gru(to_tensor(x), D, lengths=lens, name=nm,
                      origin_mode=origin)
        w, b = _set_params(rng)
        hid = L.dynamic_gru(to_tensor(x), D, lengths=lens, name=nm,
                            origin_mode=origin)
        ref = np_dynamic_gru(x, w, b, lens, origin)
        np.testing.assert_allclose(np.asarray(hid.numpy()), ref,
                                   rtol=2e-4, atol=2e-5)
        assert np.abs(np.asarray(hid.numpy())[1, 2:]).max() == 0

    def test_reverse(self):
        rng = np.random.default_rng(4)
        x = rng.standard_normal((B, T, 3 * D)).astype(np.float32)
        lens = np.array([5, 6, 3], np.int64)
        L.dynamic_gru(to_tensor(x), D, lengths=lens, name="dgr",
                      is_reverse=True)
        w, b = _set_params(rng)
        hid = L.dynamic_gru(to_tensor(x), D, lengths=lens, name="dgr",
                            is_reverse=True)
        ref = np_dynamic_gru(x, w, b, lens, False, reverse=True)
        np.testing.assert_allclose(np.asarray(hid.numpy()), ref,
                                   rtol=2e-4, atol=2e-5)


class TestBiasAttr:
    def test_dynamic_lstm_rejects_bias_false(self):
        # reference rnn.py:2383 asserts the same
        with pytest.raises(Exception, match="bias_attr"):
            L.dynamic_lstm(to_tensor(np.zeros((B, T, 4 * H),
                                              np.float32)), 4 * H,
                           bias_attr=False)

    def test_dynamic_gru_without_bias(self):
        rng = np.random.default_rng(11)
        x = rng.standard_normal((B, T, 3 * D)).astype(np.float32)
        lens = np.array([6, 4, 5], np.int64)
        L.dynamic_gru(to_tensor(x), D, lengths=lens, name="dg_nb",
                      bias_attr=False)
        ps = fluid.layers.implicit_parameters()[-1:]
        w = (rng.standard_normal((D, 3 * D)) * 0.4).astype(np.float32)
        ps[0].set_value(w)
        hid = L.dynamic_gru(to_tensor(x), D, lengths=lens,
                            name="dg_nb", bias_attr=False)
        ref = np_dynamic_gru(x, w, np.zeros((1, 3 * D), np.float32),
                             lens, False)
        np.testing.assert_allclose(np.asarray(hid.numpy()), ref,
                                   rtol=2e-4, atol=2e-5)

    def test_gru_unit_without_bias(self):
        rng = np.random.default_rng(12)
        xt = rng.standard_normal((B, 3 * D)).astype(np.float32)
        h0 = rng.standard_normal((B, D)).astype(np.float32)
        L.gru_unit(to_tensor(xt), to_tensor(h0), 3 * D, name="gu_nb",
                   bias_attr=False)
        w = (rng.standard_normal((D, 3 * D)) * 0.4).astype(np.float32)
        fluid.layers.implicit_parameters()[-1].set_value(w)
        hn, rh, gate = L.gru_unit(to_tensor(xt), to_tensor(h0), 3 * D,
                                  name="gu_nb", bias_attr=False)
        ur = xt[:, :2 * D] + h0 @ w[:, :2 * D]
        u, r = _sig(ur[:, :D]), _sig(ur[:, D:])
        c = np.tanh(xt[:, 2 * D:] + (r * h0) @ w[:, 2 * D:])
        np.testing.assert_allclose(np.asarray(hn.numpy()),
                                   (1 - u) * h0 + u * c,
                                   rtol=2e-4, atol=2e-5)


class TestGRUUnit:
    def test_single_step_matches_numpy(self):
        rng = np.random.default_rng(5)
        xt = rng.standard_normal((B, 3 * D)).astype(np.float32)
        h0 = rng.standard_normal((B, D)).astype(np.float32)
        L.gru_unit(to_tensor(xt), to_tensor(h0), 3 * D, name="gu")
        w, b = _set_params(rng)
        hn, rh, gate = L.gru_unit(to_tensor(xt), to_tensor(h0), 3 * D,
                                  name="gu")
        g = xt + b[0]
        ur = g[:, :2 * D] + h0 @ w[:, :2 * D]
        u, r = _sig(ur[:, :D]), _sig(ur[:, D:])
        c = np.tanh(g[:, 2 * D:] + (r * h0) @ w[:, 2 * D:])
        ref_h = (1 - u) * h0 + u * c
        np.testing.assert_allclose(np.asarray(hn.numpy()), ref_h,
                                   rtol=2e-4, atol=2e-5)
        np.testing.assert_allclose(np.asarray(rh.numpy()), r * h0,
                                   rtol=2e-4, atol=2e-5)
        np.testing.assert_allclose(
            np.asarray(gate.numpy()),
            np.concatenate([u, r, c], axis=-1), rtol=2e-4, atol=2e-5)


class TestCudnnStyleLSTM:
    def test_shapes_and_determinism(self):
        rng = np.random.default_rng(6)
        x = rng.standard_normal((T, B, D)).astype(np.float32)
        nl = 2
        h0 = np.zeros((nl, B, H), np.float32)
        c0 = np.zeros((nl, B, H), np.float32)
        out, h, c = L.lstm(to_tensor(x), to_tensor(h0), to_tensor(c0),
                           T, H, nl, is_test=True, name="cu1")
        assert tuple(out.shape) == (T, B, H)
        assert tuple(h.shape) == (nl, B, H)
        out2, _, _ = L.lstm(to_tensor(x), to_tensor(h0), to_tensor(c0),
                            T, H, nl, is_test=True, name="cu1")
        np.testing.assert_array_equal(np.asarray(out.numpy()),
                                      np.asarray(out2.numpy()))

    def test_bidirec_doubles_width(self):
        x = np.zeros((T, B, D), np.float32)
        h0 = np.zeros((2, B, H), np.float32)
        c0 = np.zeros((2, B, H), np.float32)
        out, h, c = L.lstm(to_tensor(x), to_tensor(h0), to_tensor(c0),
                           T, H, 1, is_bidirec=True, is_test=True,
                           name="cu2")
        assert tuple(out.shape) == (T, B, 2 * H)
