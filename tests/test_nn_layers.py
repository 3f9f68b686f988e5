"""nn layer correctness: shapes, gradients, state_dict, hooks."""

import numpy as np

import paddle1_tpu as paddle
from paddle1_tpu import nn
from op_test import OpTest

F = nn.functional


class TestLinearConv(OpTest):
    def test_linear_matches_manual(self):
        paddle.seed(0)
        lin = nn.Linear(4, 3)
        x = np.random.randn(2, 4).astype(np.float32)
        out = lin(paddle.to_tensor(x))
        ref = x @ lin.weight.numpy() + lin.bias.numpy()
        np.testing.assert_allclose(out.numpy(), ref, rtol=1e-5, atol=1e-6)

    def test_conv2d_matches_torch_semantics(self):
        # reference semantics: NCHW, weight [out,in,kh,kw]
        import jax
        paddle.seed(0)
        conv = nn.Conv2D(3, 8, 3, stride=2, padding=1)
        x = np.random.randn(2, 3, 16, 16).astype(np.float32)
        out = conv(paddle.to_tensor(x))
        assert out.shape == [2, 8, 8, 8]
        out.sum().backward()
        assert conv.weight.grad is not None
        assert conv.bias.grad is not None

    def test_conv_grad_numeric(self):
        w = np.random.randn(2, 1, 3, 3).astype(np.float32) * 0.5
        x = np.random.randn(1, 1, 6, 6).astype(np.float32)
        self.check_grad(
            lambda xi, wi: F.conv2d(xi, wi, padding=1),
            [x, w], grad_input_idx=(0, 1), delta=1e-2, rtol=5e-2, atol=5e-3)

    def test_conv2d_transpose_shape(self):
        deconv = nn.Conv2DTranspose(4, 2, 3, stride=2, padding=1)
        x = paddle.to_tensor(np.random.randn(1, 4, 8, 8).astype(np.float32))
        out = deconv(x)
        assert out.shape == [1, 2, 15, 15], out.shape

    def test_depthwise_groups(self):
        conv = nn.Conv2D(4, 4, 3, groups=4, padding=1)
        x = paddle.to_tensor(np.random.randn(1, 4, 5, 5).astype(np.float32))
        assert conv(x).shape == [1, 4, 5, 5]


class TestNorms(OpTest):
    def test_layer_norm_stats(self):
        ln = nn.LayerNorm(16)
        x = np.random.randn(4, 16).astype(np.float32) * 3 + 1
        out = ln(paddle.to_tensor(x)).numpy()
        np.testing.assert_allclose(out.mean(-1), np.zeros(4), atol=1e-5)
        np.testing.assert_allclose(out.std(-1), np.ones(4), atol=1e-2)

    def test_batch_norm_train_and_eval(self):
        bn = nn.BatchNorm2D(3)
        x = np.random.randn(8, 3, 4, 4).astype(np.float32) * 2 + 5
        bn.train()
        y = bn(paddle.to_tensor(x)).numpy()
        np.testing.assert_allclose(y.mean((0, 2, 3)), np.zeros(3), atol=1e-4)
        # running stats moved toward batch stats
        assert not np.allclose(bn._mean.numpy(), np.zeros(3))
        bn.eval()
        y2 = bn(paddle.to_tensor(x))
        assert y2.shape == [8, 3, 4, 4]

    def test_group_norm(self):
        gn = nn.GroupNorm(2, 4)
        x = paddle.to_tensor(np.random.randn(2, 4, 5, 5).astype(np.float32))
        assert gn(x).shape == [2, 4, 5, 5]

    def test_rms_norm(self):
        rn = nn.RMSNorm(8)
        x = paddle.to_tensor(np.random.randn(3, 8).astype(np.float32))
        out = rn(x)
        assert out.shape == [3, 8]


class TestActivationsPooling(OpTest):
    def test_activations(self):
        x = np.linspace(-3, 3, 13).astype(np.float32)
        t = paddle.to_tensor(x)
        np.testing.assert_allclose(F.relu(t).numpy(), np.maximum(x, 0))
        np.testing.assert_allclose(F.sigmoid(t).numpy(),
                                   1 / (1 + np.exp(-x)), rtol=1e-5)
        np.testing.assert_allclose(
            F.softmax(paddle.to_tensor(x.reshape(1, -1))).numpy().sum(),
            1.0, rtol=1e-5)
        self.check_grad(F.gelu, [np.random.randn(5).astype(np.float32)])

    def test_pools(self):
        x = np.arange(16, dtype=np.float32).reshape(1, 1, 4, 4)
        out = F.max_pool2d(paddle.to_tensor(x), 2, 2)
        np.testing.assert_array_equal(out.numpy().reshape(2, 2),
                                      [[5, 7], [13, 15]])
        out = F.avg_pool2d(paddle.to_tensor(x), 2, 2)
        np.testing.assert_allclose(out.numpy().reshape(2, 2),
                                   [[2.5, 4.5], [10.5, 12.5]])

    def test_adaptive_pool(self):
        x = paddle.to_tensor(np.random.randn(1, 2, 7, 7).astype(np.float32))
        out = F.adaptive_avg_pool2d(x, 3)
        assert out.shape == [1, 2, 3, 3]


class TestEmbeddingDropout(OpTest):
    def test_embedding_lookup_and_grad(self):
        emb = nn.Embedding(10, 4)
        ids = paddle.to_tensor(np.array([[1, 2], [3, 1]], np.int64))
        out = emb(ids)
        assert out.shape == [2, 2, 4]
        out.sum().backward()
        g = emb.weight.grad.numpy()
        # row 1 used twice
        np.testing.assert_allclose(g[1], 2 * np.ones(4))
        np.testing.assert_allclose(g[5], np.zeros(4))

    def test_dropout_modes(self):
        paddle.seed(7)
        x = paddle.to_tensor(np.ones((1000,), np.float32))
        out = F.dropout(x, p=0.5, training=True)
        kept = out.numpy()
        frac = (kept != 0).mean()
        assert 0.4 < frac < 0.6
        np.testing.assert_allclose(kept[kept != 0], 2.0, rtol=1e-6)
        out_eval = F.dropout(x, p=0.5, training=False)
        np.testing.assert_allclose(out_eval.numpy(), 1.0)


class TestRNN(OpTest):
    def test_lstm_shapes(self):
        lstm = nn.LSTM(8, 16, num_layers=2)
        x = paddle.to_tensor(np.random.randn(4, 5, 8).astype(np.float32))
        out, (h, c) = lstm(x)
        assert out.shape == [4, 5, 16]
        assert h.shape == [2, 4, 16]
        assert c.shape == [2, 4, 16]
        out.sum().backward()
        assert lstm.weight_ih_l0.grad is not None

    def test_bidirectional_gru(self):
        gru = nn.GRU(4, 6, direction="bidirect")
        x = paddle.to_tensor(np.random.randn(2, 3, 4).astype(np.float32))
        out, h = gru(x)
        assert out.shape == [2, 3, 12]
        assert h.shape == [2, 2, 6]

    def test_lstm_cell(self):
        cell = nn.LSTMCell(4, 8)
        x = paddle.to_tensor(np.random.randn(2, 4).astype(np.float32))
        out, (h, c) = cell(x)
        assert out.shape == [2, 8]


class TestTransformer(OpTest):
    def test_mha_forward_backward(self):
        mha = nn.MultiHeadAttention(16, 4)
        x = paddle.to_tensor(np.random.randn(2, 6, 16).astype(np.float32))
        out = mha(x, x, x)
        assert out.shape == [2, 6, 16]
        out.sum().backward()
        assert mha.q_proj.weight.grad is not None

    def test_encoder_layer(self):
        enc_layer = nn.TransformerEncoderLayer(16, 4, 32, dropout=0.0)
        enc = nn.TransformerEncoder(enc_layer, 2)
        x = paddle.to_tensor(np.random.randn(2, 5, 16).astype(np.float32))
        out = enc(x)
        assert out.shape == [2, 5, 16]

    def test_full_transformer(self):
        model = nn.Transformer(d_model=16, nhead=4, num_encoder_layers=1,
                               num_decoder_layers=1, dim_feedforward=32,
                               dropout=0.0)
        src = paddle.to_tensor(np.random.randn(2, 4, 16).astype(np.float32))
        tgt = paddle.to_tensor(np.random.randn(2, 3, 16).astype(np.float32))
        out = model(src, tgt)
        assert out.shape == [2, 3, 16]


class TestLayerProtocol(OpTest):
    def test_state_dict_roundtrip(self):
        net = nn.Sequential(nn.Linear(4, 8), nn.ReLU(), nn.Linear(8, 2))
        sd = net.state_dict()
        assert set(sd) == {"0.weight", "0.bias", "2.weight", "2.bias"}
        net2 = nn.Sequential(nn.Linear(4, 8), nn.ReLU(), nn.Linear(8, 2))
        net2.set_state_dict(sd)
        for (k1, v1), (k2, v2) in zip(sorted(net.state_dict().items()),
                                      sorted(net2.state_dict().items())):
            np.testing.assert_allclose(v1.numpy(), v2.numpy())

    def test_forward_hooks(self):
        lin = nn.Linear(2, 2)
        calls = []
        h = lin.register_forward_post_hook(
            lambda layer, inp, out: calls.append(1))
        lin(paddle.to_tensor(np.zeros((1, 2), np.float32)))
        assert calls == [1]
        h.remove()
        lin(paddle.to_tensor(np.zeros((1, 2), np.float32)))
        assert calls == [1]

    def test_train_eval_propagates(self):
        net = nn.Sequential(nn.Linear(2, 2), nn.Dropout(0.5))
        net.eval()
        assert not net[1].training
        net.train()
        assert net[1].training

    def test_named_parameters_unique(self):
        shared = nn.Linear(3, 3)

        class Net(nn.Layer):
            def __init__(self):
                super().__init__()
                self.a = shared
                self.b = shared

            def forward(self, x):
                return self.b(self.a(x))

        net = Net()
        names = [n for n, _ in net.named_parameters()]
        assert len(names) == 2  # shared params counted once


class TestLosses(OpTest):
    def test_cross_entropy_matches_manual(self):
        logits = np.random.randn(4, 5).astype(np.float32)
        labels = np.array([0, 2, 1, 4], np.int64)
        out = F.cross_entropy(paddle.to_tensor(logits),
                              paddle.to_tensor(labels))
        # manual
        e = np.exp(logits - logits.max(-1, keepdims=True))
        p = e / e.sum(-1, keepdims=True)
        ref = -np.log(p[np.arange(4), labels]).mean()
        np.testing.assert_allclose(out.item(), ref, rtol=1e-5)

    def test_mse_and_l1(self):
        a = np.random.randn(6).astype(np.float32)
        b = np.random.randn(6).astype(np.float32)
        np.testing.assert_allclose(
            F.mse_loss(paddle.to_tensor(a), paddle.to_tensor(b)).item(),
            ((a - b) ** 2).mean(), rtol=1e-5)
        np.testing.assert_allclose(
            F.l1_loss(paddle.to_tensor(a), paddle.to_tensor(b)).item(),
            np.abs(a - b).mean(), rtol=1e-5)

    def test_bce_with_logits_stable(self):
        x = np.array([100.0, -100.0, 0.0], np.float32)
        y = np.array([1.0, 0.0, 1.0], np.float32)
        out = F.binary_cross_entropy_with_logits(
            paddle.to_tensor(x), paddle.to_tensor(y))
        assert np.isfinite(out.item())

    def test_ignore_index(self):
        logits = np.random.randn(3, 4).astype(np.float32)
        labels = np.array([1, -100, 2], np.int64)
        out = F.cross_entropy(paddle.to_tensor(logits),
                              paddle.to_tensor(labels), ignore_index=-100)
        mask = labels != -100
        e = np.exp(logits - logits.max(-1, keepdims=True))
        p = e / e.sum(-1, keepdims=True)
        ref = -np.log(p[np.arange(3), np.clip(labels, 0, None)])[mask].mean()
        np.testing.assert_allclose(out.item(), ref, rtol=1e-4)


class TestParitySweepNN:
    """r3 nn-surface parity sweep: hsigmoid_loss/HSigmoidLoss, diag_embed,
    elu_, RNN base classes (reference nn/functional/loss.py:312,
    nn/functional/extension.py diag_embed, nn/layer/rnn.py:134,844)."""

    def test_hsigmoid_is_a_distribution(self):
        # the binary-tree path losses must define a normalized
        # distribution: sum_l exp(-loss(l)) == 1 for any x
        import paddle1_tpu.nn.functional as F
        rng = np.random.default_rng(0)
        C, D = 11, 6
        x = paddle.to_tensor(rng.standard_normal((1, D)).astype(np.float32))
        w = paddle.to_tensor(rng.standard_normal((C - 1, D))
                             .astype(np.float32))
        b = paddle.to_tensor(rng.standard_normal((C - 1,))
                             .astype(np.float32))
        probs = []
        for label in range(C):
            l = paddle.to_tensor(np.array([label]))
            loss = F.hsigmoid_loss(x, l, C, w, bias=b)
            probs.append(np.exp(-float(loss.numpy()[0, 0])))
        np.testing.assert_allclose(sum(probs), 1.0, rtol=1e-5)

    def test_hsigmoid_layer_trains(self):
        import paddle1_tpu as paddle
        rng = np.random.default_rng(1)
        hs = paddle.nn.HSigmoidLoss(4, 6)
        opt = paddle.optimizer.SGD(learning_rate=0.5,
                                   parameters=hs.parameters())
        x = paddle.to_tensor(rng.standard_normal((8, 4)).astype(np.float32))
        y = paddle.to_tensor(np.arange(8, dtype=np.int64) % 6)
        first = None
        for _ in range(30):
            loss = hs(x, y).mean()
            loss.backward()
            opt.step()
            opt.clear_grad()
            first = first if first is not None else float(loss.numpy())
        assert float(loss.numpy()) < first * 0.7

    def test_hsigmoid_custom_path(self):
        import paddle1_tpu.nn.functional as F
        rng = np.random.default_rng(2)
        x = paddle.to_tensor(rng.standard_normal((2, 3)).astype(np.float32))
        w = paddle.to_tensor(rng.standard_normal((4, 3)).astype(np.float32))
        lab = paddle.to_tensor(np.array([0, 1]))
        table = paddle.to_tensor(np.array([[0, 1, -1], [0, 2, 3]],
                                          np.int64))
        code = paddle.to_tensor(np.array([[1.0, 0.0, 0.0],
                                          [0.0, 1.0, 1.0]], np.float32))
        loss = F.hsigmoid_loss(x, lab, 5, w, path_table=table,
                               path_code=code)
        assert loss.shape == [2, 1]
        assert np.isfinite(loss.numpy()).all()

    def test_diag_embed(self):
        import paddle1_tpu.nn.functional as F
        x = paddle.to_tensor(np.arange(6, dtype=np.float32).reshape(2, 3))
        d = F.diag_embed(x)
        assert d.shape == [2, 3, 3]
        np.testing.assert_allclose(np.asarray(d.numpy())[1],
                                   np.diag([3.0, 4.0, 5.0]))
        off = F.diag_embed(x, offset=1)
        assert off.shape == [2, 4, 4]
        np.testing.assert_allclose(np.asarray(off.numpy())[0],
                                   np.diag([0.0, 1.0, 2.0], k=1))

    def test_elu_inplace(self):
        import paddle1_tpu.nn.functional as F
        t = paddle.to_tensor(np.float32([-1.0, 2.0]))
        out = F.elu_(t)
        assert out is t
        np.testing.assert_allclose(t.numpy(), [np.expm1(-1.0), 2.0],
                                   rtol=1e-6)

    def test_rnn_base_classes_exported(self):
        assert isinstance(paddle.nn.LSTM(4, 8), paddle.nn.RNNBase)
        assert issubclass(paddle.nn.LSTMCell, paddle.nn.RNNCellBase)


class TestConvNHWCInternal(OpTest):
    """conv_nhwc flag (BASELINE conv-throughput candidate fix): the
    NHWC-internal path must be numerically identical to the NCHW path,
    forward and backward."""

    def test_flag_path_matches_nchw(self):
        import numpy as np
        from paddle1_tpu.core import flags as core_flags
        from paddle1_tpu.core.tensor import to_tensor
        import paddle1_tpu.nn.functional as F

        rng = np.random.default_rng(0)
        x = rng.standard_normal((2, 3, 8, 8)).astype(np.float32)
        w = rng.standard_normal((4, 3, 3, 3)).astype(np.float32)
        b = rng.standard_normal((4,)).astype(np.float32)

        def run():
            xt = to_tensor(x)
            xt.stop_gradient = False
            out = F.conv2d(xt, to_tensor(w), to_tensor(b), stride=2,
                           padding=1)
            out.sum().backward()
            return np.asarray(out.numpy()), np.asarray(xt.grad.numpy())

        with core_flags.flags_guard(conv_nhwc="never"):
            o1, g1 = run()
        with core_flags.flags_guard(conv_nhwc="always"):
            o2, g2 = run()
        np.testing.assert_allclose(o1, o2, rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(g1, g2, rtol=1e-5, atol=1e-5)

    def test_grouped_conv_flag_path(self):
        import numpy as np
        from paddle1_tpu.core import flags as core_flags
        from paddle1_tpu.core.tensor import to_tensor
        import paddle1_tpu.nn.functional as F
        rng = np.random.default_rng(1)
        x = rng.standard_normal((1, 4, 6, 6)).astype(np.float32)
        w = rng.standard_normal((8, 2, 3, 3)).astype(np.float32)
        with core_flags.flags_guard(conv_nhwc="never"):
            o1 = np.asarray(F.conv2d(to_tensor(x), to_tensor(w),
                                     groups=2, padding=1).numpy())
        with core_flags.flags_guard(conv_nhwc="always"):
            o2 = np.asarray(F.conv2d(to_tensor(x), to_tensor(w),
                                     groups=2, padding=1).numpy())
        np.testing.assert_allclose(o1, o2, rtol=1e-5, atol=1e-5)

    def test_pool_flag_path_matches_nchw(self):
        # pools join the channels-last region (_layout.py)
        import numpy as np
        from paddle1_tpu.core.flags import flags_guard
        from paddle1_tpu.core.tensor import to_tensor
        import paddle1_tpu.nn.functional as F
        rng = np.random.default_rng(2)
        x = rng.standard_normal((2, 3, 9, 9)).astype(np.float32)
        for fn, kw in [(F.max_pool2d, dict(kernel_size=3, stride=2,
                                           padding=1)),
                       (F.max_pool2d, dict(kernel_size=2, stride=2,
                                           ceil_mode=True)),
                       (F.avg_pool2d, dict(kernel_size=3, stride=2,
                                           padding=1, exclusive=True)),
                       (F.avg_pool2d, dict(kernel_size=3, stride=3,
                                           exclusive=False)),
                       (F.adaptive_avg_pool2d, dict(output_size=3))]:
            def run():
                xt = to_tensor(x)
                xt.stop_gradient = False
                out = fn(xt, **kw)
                out.sum().backward()
                return (np.asarray(out.numpy()),
                        np.asarray(xt.grad.numpy()))
            with flags_guard(conv_nhwc="never"):
                o1, g1 = run()
            with flags_guard(conv_nhwc="always"):
                o2, g2 = run()
            np.testing.assert_allclose(o1, o2, rtol=1e-5, atol=1e-5,
                                       err_msg=f"{fn.__name__} {kw}")
            np.testing.assert_allclose(g1, g2, rtol=1e-5, atol=1e-5,
                                       err_msg=f"{fn.__name__} {kw} grad")

    def test_batch_norm_flag_path_matches_nchw(self):
        import numpy as np
        from paddle1_tpu.core.flags import flags_guard
        from paddle1_tpu.core.tensor import to_tensor
        import paddle1_tpu.nn.functional as F
        rng = np.random.default_rng(3)
        x = rng.standard_normal((4, 5, 6, 6)).astype(np.float32)
        w = rng.standard_normal((5,)).astype(np.float32)
        b = rng.standard_normal((5,)).astype(np.float32)
        m = rng.standard_normal((5,)).astype(np.float32)
        v = rng.standard_normal((5,)).astype(np.float32) ** 2 + 0.5
        for training in (False, True):
            def run():
                xt = to_tensor(x)
                xt.stop_gradient = False
                out = F.batch_norm(xt, to_tensor(m.copy()),
                                   to_tensor(v.copy()), to_tensor(w),
                                   to_tensor(b), training=training)
                out.sum().backward()
                return (np.asarray(out.numpy()),
                        np.asarray(xt.grad.numpy()))
            with flags_guard(conv_nhwc="never"):
                o1, g1 = run()
            with flags_guard(conv_nhwc="always"):
                o2, g2 = run()
            np.testing.assert_allclose(o1, o2, rtol=1e-5, atol=1e-5,
                                       err_msg=f"training={training}")
            np.testing.assert_allclose(g1, g2, rtol=1e-5, atol=1e-5,
                                       err_msg=f"training={training} grad")

    def test_conv_1d_3d_flag_path_matches(self):
        # r5: the channels-last region generalized beyond 2-D — same
        # physics (channel dim must be the lane dim on this backend)
        import numpy as np
        from paddle1_tpu.core.flags import flags_guard
        from paddle1_tpu.core.tensor import to_tensor
        import paddle1_tpu.nn.functional as F
        rng = np.random.default_rng(5)
        cases = [
            (F.conv1d, rng.standard_normal((2, 3, 12)),
             rng.standard_normal((5, 3, 3)), dict(stride=2, padding=1)),
            (F.conv1d, rng.standard_normal((1, 4, 10)),
             rng.standard_normal((8, 2, 3)), dict(groups=2, padding=1)),
            (F.conv3d, rng.standard_normal((2, 3, 5, 6, 6)),
             rng.standard_normal((4, 3, 3, 3, 3)),
             dict(stride=2, padding=1)),
            (F.conv3d, rng.standard_normal((1, 4, 4, 5, 5)),
             rng.standard_normal((8, 2, 3, 3, 3)),
             dict(groups=2, padding=1, dilation=1)),
        ]
        for fn, x, w, kw in cases:
            x = x.astype(np.float32)
            w = (w * 0.3).astype(np.float32)

            def run():
                xt = to_tensor(x)
                xt.stop_gradient = False
                out = fn(xt, to_tensor(w), **kw)
                out.sum().backward()
                return (np.asarray(out.numpy()),
                        np.asarray(xt.grad.numpy()))
            with flags_guard(conv_nhwc="never"):
                o1, g1 = run()
            with flags_guard(conv_nhwc="always"):
                o2, g2 = run()
            np.testing.assert_allclose(o1, o2, rtol=2e-5, atol=2e-5,
                                       err_msg=f"{fn.__name__} {kw}")
            np.testing.assert_allclose(g1, g2, rtol=2e-5, atol=2e-5,
                                       err_msg=f"{fn.__name__} {kw} grad")

    def test_conv_transpose_flag_path_matches(self):
        import numpy as np
        from paddle1_tpu.core.flags import flags_guard
        from paddle1_tpu.core.tensor import to_tensor
        import paddle1_tpu.nn.functional as F
        rng = np.random.default_rng(6)
        cases = [
            (F.conv1d_transpose, rng.standard_normal((2, 4, 8)),
             rng.standard_normal((4, 3, 3)), dict(stride=2, padding=1)),
            (F.conv2d_transpose, rng.standard_normal((2, 4, 6, 6)),
             rng.standard_normal((4, 3, 3, 3)),
             dict(stride=2, padding=1, output_padding=1)),
            (F.conv2d_transpose, rng.standard_normal((1, 4, 5, 5)),
             rng.standard_normal((4, 2, 3, 3)), dict(groups=2)),
            (F.conv3d_transpose, rng.standard_normal((1, 3, 4, 4, 4)),
             rng.standard_normal((3, 2, 3, 3, 3)),
             dict(stride=2, padding=1)),
        ]
        for fn, x, w, kw in cases:
            x = x.astype(np.float32)
            w = (w * 0.3).astype(np.float32)

            def run():
                xt = to_tensor(x)
                xt.stop_gradient = False
                out = fn(xt, to_tensor(w), **kw)
                out.sum().backward()
                return (np.asarray(out.numpy()),
                        np.asarray(xt.grad.numpy()))
            with flags_guard(conv_nhwc="never"):
                o1, g1 = run()
            with flags_guard(conv_nhwc="always"):
                o2, g2 = run()
            np.testing.assert_allclose(o1, o2, rtol=2e-5, atol=2e-5,
                                       err_msg=f"{fn.__name__} {kw}")
            np.testing.assert_allclose(g1, g2, rtol=2e-5, atol=2e-5,
                                       err_msg=f"{fn.__name__} {kw} grad")

    def test_pool_1d_3d_and_bn_ranks_flag_path(self):
        import numpy as np
        from paddle1_tpu.core.flags import flags_guard
        from paddle1_tpu.core.tensor import to_tensor
        import paddle1_tpu.nn.functional as F
        rng = np.random.default_rng(7)
        pool_cases = [
            (F.max_pool1d, rng.standard_normal((2, 3, 11)),
             dict(kernel_size=3, stride=2, padding=1)),
            (F.avg_pool1d, rng.standard_normal((2, 3, 10)),
             dict(kernel_size=2, stride=2)),
            (F.max_pool3d, rng.standard_normal((2, 3, 6, 7, 7)),
             dict(kernel_size=2, stride=2, ceil_mode=True)),
            (F.avg_pool3d, rng.standard_normal((2, 3, 6, 6, 6)),
             dict(kernel_size=3, stride=2, padding=1)),
        ]
        for fn, x, kw in pool_cases:
            x = x.astype(np.float32)

            def run():
                xt = to_tensor(x)
                xt.stop_gradient = False
                out = fn(xt, **kw)
                out.sum().backward()
                return (np.asarray(out.numpy()),
                        np.asarray(xt.grad.numpy()))
            with flags_guard(conv_nhwc="never"):
                o1, g1 = run()
            with flags_guard(conv_nhwc="always"):
                o2, g2 = run()
            np.testing.assert_allclose(o1, o2, rtol=1e-5, atol=1e-5,
                                       err_msg=f"{fn.__name__} {kw}")
            np.testing.assert_allclose(g1, g2, rtol=1e-5, atol=1e-5,
                                       err_msg=f"{fn.__name__} {kw} grad")
        # batch norm at 3-D (NCL) and 5-D (NCDHW)
        for shape in [(4, 5, 7), (2, 5, 3, 4, 4)]:
            x = rng.standard_normal(shape).astype(np.float32)
            w = rng.standard_normal((5,)).astype(np.float32)
            b = rng.standard_normal((5,)).astype(np.float32)

            def run():
                xt = to_tensor(x)
                xt.stop_gradient = False
                out = F.batch_norm(xt, to_tensor(np.zeros(5, np.float32)),
                                   to_tensor(np.ones(5, np.float32)),
                                   to_tensor(w), to_tensor(b),
                                   training=True)
                out.sum().backward()
                return (np.asarray(out.numpy()),
                        np.asarray(xt.grad.numpy()))
            with flags_guard(conv_nhwc="never"):
                o1, g1 = run()
            with flags_guard(conv_nhwc="always"):
                o2, g2 = run()
            np.testing.assert_allclose(o1, o2, rtol=1e-5, atol=1e-5,
                                       err_msg=f"bn {shape}")
            np.testing.assert_allclose(g1, g2, rtol=1e-5, atol=1e-5,
                                       err_msg=f"bn {shape} grad")

    def test_small_cnn_end_to_end_flag_path(self):
        # conv+bn+pool+residual+fc: the full channels-last region in one
        # model, forward and parameter gradients identical to NCHW
        import numpy as np
        import paddle1_tpu as paddle
        from paddle1_tpu.core.flags import flags_guard
        from paddle1_tpu.core.tensor import Tensor

        def build_and_step(seed):
            np.random.seed(seed)
            paddle.seed(seed)
            m = paddle.nn.Sequential(
                paddle.nn.Conv2D(3, 8, 3, padding=1),
                paddle.nn.BatchNorm2D(8),
                paddle.nn.ReLU(),
                paddle.nn.MaxPool2D(2, 2),
                paddle.nn.Conv2D(8, 8, 3, padding=1),
                paddle.nn.AdaptiveAvgPool2D(1),
                paddle.nn.Flatten(),
                paddle.nn.Linear(8, 4))
            rng = np.random.default_rng(0)
            x = Tensor(rng.standard_normal((2, 3, 12, 12))
                       .astype(np.float32))
            y = Tensor(rng.integers(0, 4, (2,)).astype(np.int64))
            loss = paddle.nn.functional.cross_entropy(m(x), y)
            loss.backward()
            return (float(np.asarray(loss.data)),
                    [np.asarray(p.grad.numpy()) for p in m.parameters()
                     if p.grad is not None])
        with flags_guard(conv_nhwc="never"):
            l1, g1 = build_and_step(7)
        with flags_guard(conv_nhwc="always"):
            l2, g2 = build_and_step(7)
        assert abs(l1 - l2) < 1e-5, (l1, l2)
        assert len(g1) == len(g2) and len(g1) > 0
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)


class TestConvBlockLayoutStability(OpTest):
    """ISSUE 15: a conv -> BN -> act -> pool residual block must stay
    layout-stable end to end in the channels-last region — only the
    stem/head boundary transposes survive XLA's cancellation, whatever
    ``fused_bn`` says (training mode does not read it). This is the
    CPU-measurable face of copy/layout overhead in the step."""

    def _block_hlo_counts(self, fused):
        import warnings
        import jax.numpy as jnp
        import numpy as np
        import paddle1_tpu as paddle
        import paddle1_tpu.nn.functional as F
        from bench_utils import compiled_hlo_layout_census
        from paddle1_tpu.autograd import engine as ae
        from paddle1_tpu.core.flags import flags_guard
        from paddle1_tpu.core.tensor import Tensor

        paddle.seed(0)
        conv1 = paddle.nn.Conv2D(64, 64, 3, padding=1, bias_attr=False)
        bn1 = paddle.nn.BatchNorm2D(64)
        conv2 = paddle.nn.Conv2D(64, 64, 3, padding=1, bias_attr=False)
        bn2 = paddle.nn.BatchNorm2D(64)
        pool = paddle.nn.MaxPool2D(2, 2)

        def block(xa):
            with ae.no_grad():
                x = Tensor(xa)
                h = F.relu(bn1(conv1(x)))
                h = F.fused_batch_norm_act(
                    conv2(h), bn2._mean, bn2._variance, bn2.weight,
                    bn2.bias, training=True, act="relu", residual=x)
                return pool(h).data

        x = jnp.asarray(np.random.default_rng(0)
                        .standard_normal((8, 64, 16, 16))
                        .astype(np.float32))
        with flags_guard(conv_nhwc="always", fused_bn=fused), \
                warnings.catch_warnings():
            warnings.simplefilter("ignore")  # traced-stat warn-and-skip
            census = compiled_hlo_layout_census(block, x)
        return census["transposes"], census["copies"]

    def test_residual_block_transpose_free_interior(self):
        tr_xla, cp_xla = self._block_hlo_counts("never")
        # stem input + head output only: conv/BN/act/pool boundaries
        # all cancel. 3 allows one residual-edge transpose on some XLA
        # versions; the pre-fix layout-churn trace showed dozens.
        assert tr_xla <= 3, f"XLA path grew interior transposes: {tr_xla}"
        assert cp_xla <= 3, f"XLA path grew interior copies: {cp_xla}"
        assert self._block_hlo_counts("auto") == (tr_xla, cp_xla)


class TestSyncBatchNorm(OpTest):
    """Cross-replica BN (reference sync_batch_norm_op): stats psum'd
    over dp must equal GLOBAL-batch BN, in both layouts of the
    channels-last region (r5)."""

    def _run(self, conv_nhwc):
        import jax
        import jax.numpy as jnp
        import numpy as np
        from jax.sharding import Mesh, PartitionSpec as P
        from jax import shard_map
        from paddle1_tpu.core.flags import flags_guard
        from paddle1_tpu.core.tensor import Tensor
        from paddle1_tpu.distributed.env import spmd_axes

        devs = jax.devices()[:4]
        mesh = Mesh(np.asarray(devs), ("data",))
        rng = np.random.default_rng(0)
        x = rng.standard_normal((8, 3, 4, 4)).astype(np.float32) * 2 + 1

        paddle.seed(0)
        sbn = nn.SyncBatchNorm(3)
        w = sbn.weight.data
        b = sbn.bias.data

        def shard_fn(xs, w, b):
            with spmd_axes(dp="data"), flags_guard(conv_nhwc=conv_nhwc):
                y, = (sbn(Tensor(xs)).data,)
            return y

        y = jax.jit(shard_map(
            shard_fn, mesh=mesh,
            in_specs=(P("data"), P(), P()),
            out_specs=P("data")))(jnp.asarray(x), w, b)

        # global-batch reference
        mean = x.mean(axis=(0, 2, 3), keepdims=True)
        var = x.var(axis=(0, 2, 3), keepdims=True)
        want = (x - mean) / np.sqrt(var + sbn._epsilon)
        want = want * np.asarray(w).reshape(1, -1, 1, 1) + \
            np.asarray(b).reshape(1, -1, 1, 1)
        np.testing.assert_allclose(np.asarray(y), want, rtol=2e-4,
                                   atol=2e-4)

    def test_matches_global_bn_nchw_path(self):
        self._run("never")

    def test_matches_global_bn_channels_last_region(self):
        self._run("always")
