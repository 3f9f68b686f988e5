"""Sampled large-vocab losses: nce +
sampled_softmax_with_cross_entropy vs numpy references built from the
kernel formulas (nce_op.h cost loop; sample_logits_op + math/sampler.cc
probabilities)."""

import numpy as np
import pytest

import paddle1_tpu as paddle
import paddle1_tpu.fluid as fluid
import paddle1_tpu.fluid.layers as L
from paddle1_tpu.core.tensor import to_tensor

B, DIM, K = 4, 6, 20


def _sig(v):
    return 1.0 / (1.0 + np.exp(-v))


def np_nce_cost(x, w, bias, samples, num_true, q, n_neg, sw=None):
    o = _sig(np.einsum("bd,bsd->bs", x, w[samples])
             + (bias[samples, 0] if bias is not None else 0.0))
    bq = q * n_neg
    cost = np.where(np.arange(samples.shape[1])[None, :] < num_true,
                    -np.log(o / (o + bq)), -np.log(bq / (o + bq)))
    out = cost.sum(axis=1)
    if sw is not None:
        out = out * sw
    return out[:, None]


class TestNCE:
    def _setup(self, name, with_bias=True, num_true=1):
        rng = np.random.default_rng(0)
        x = rng.standard_normal((B, DIM)).astype(np.float32)
        lab = rng.integers(0, K, (B, num_true)).astype(np.int64)
        negs = [1, 3, 5, 7, 11]
        L.nce(to_tensor(x), to_tensor(lab), K, name=name,
              custom_neg_classes=negs,
              bias_attr=True if with_bias else False)
        ps = fluid.layers.implicit_parameters()[-(2 if with_bias else 1):]
        w = (rng.standard_normal((K, DIM)) * 0.5).astype(np.float32)
        ps[0].set_value(w)
        bias = None
        if with_bias:
            bias = (rng.standard_normal((K, 1)) * 0.5).astype(np.float32)
            ps[1].set_value(bias)
        return x, lab, negs, w, bias

    def test_uniform_custom_negs_matches_numpy(self):
        x, lab, negs, w, bias = self._setup("nce_u")
        cost = L.nce(to_tensor(x), to_tensor(lab), K, name="nce_u",
                     custom_neg_classes=negs, bias_attr=True)
        samples = np.concatenate(
            [lab, np.tile(negs, (B, 1))], axis=1)
        q = np.full(samples.shape, 1.0 / K, np.float32)
        ref = np_nce_cost(x, w, bias, samples, 1, q, len(negs))
        np.testing.assert_allclose(np.asarray(cost.numpy()), ref,
                                   rtol=2e-4, atol=2e-5)

    def test_log_uniform_probability_formula(self):
        x, lab, negs, w, bias = self._setup("nce_lu")
        cost = L.nce(to_tensor(x), to_tensor(lab), K, name="nce_lu",
                     custom_neg_classes=negs, sampler="log_uniform",
                     bias_attr=True)
        samples = np.concatenate([lab, np.tile(negs, (B, 1))], axis=1)
        q = (np.log((samples + 2.0) / (samples + 1.0))
             / np.log(K + 1.0)).astype(np.float32)
        ref = np_nce_cost(x, w, bias, samples, 1, q, len(negs))
        np.testing.assert_allclose(np.asarray(cost.numpy()), ref,
                                   rtol=2e-4, atol=2e-5)

    def test_sample_weight_and_no_bias(self):
        x, lab, negs, w, bias = self._setup("nce_sw", with_bias=False)
        sw = np.array([0.5, 1.0, 2.0, 0.0], np.float32)
        cost = L.nce(to_tensor(x), to_tensor(lab), K, name="nce_sw",
                     custom_neg_classes=negs, bias_attr=False,
                     sample_weight=to_tensor(sw[:, None]))
        samples = np.concatenate([lab, np.tile(negs, (B, 1))], axis=1)
        q = np.full(samples.shape, 1.0 / K, np.float32)
        ref = np_nce_cost(x, w, None, samples, 1, q, len(negs), sw=sw)
        np.testing.assert_allclose(np.asarray(cost.numpy()), ref,
                                   rtol=2e-4, atol=2e-5)
        assert float(np.asarray(cost.numpy())[3, 0]) == 0.0

    @pytest.mark.slow  # ~22s convergence soak; the NCE cost-parity
    # cases above stay in-tier (CI heavy step)
    def test_trains_word2vec_style(self):
        """The defining use: large-vocab binary logistic training —
        loss decreases and the gradient reaches input and weight."""
        paddle.seed(7)  # Embedding init draws from the global RNG
        rng = np.random.default_rng(7)
        emb = paddle.nn.Embedding(K, DIM)
        opt = paddle.optimizer.Adam(learning_rate=0.05,
                                    parameters=list(emb.parameters())
                                    + fluid.layers.implicit_parameters())
        ctx = rng.integers(0, K, (16,)).astype(np.int64)
        tgt = ((ctx + 1) % K)[:, None]
        losses = []
        for i in range(12):
            vec = emb(to_tensor(ctx))
            cost = L.nce(vec, to_tensor(tgt), K, name="nce_train",
                         num_neg_samples=5, seed=13 + i)
            loss = cost.mean()
            loss.backward()
            opt.step()
            opt.clear_grad()
            losses.append(float(loss.numpy()))
        assert losses[-1] < losses[0], losses

    def test_sampler_validation(self):
        with pytest.raises(Exception, match="custom_dist"):
            L.nce(to_tensor(np.zeros((2, DIM), np.float32)),
                  to_tensor(np.zeros((2, 1), np.int64)), K,
                  name="nce_bad", sampler="custom_dist")
        # same teaching error through the custom_neg_classes branch
        with pytest.raises(Exception, match="custom_dist"):
            L.nce(to_tensor(np.zeros((2, DIM), np.float32)),
                  to_tensor(np.zeros((2, 1), np.int64)), K,
                  name="nce_bad2", sampler="custom_dist",
                  custom_neg_classes=[1, 2])


class TestSampledSoftmax:
    def test_customized_samples_match_numpy(self):
        rng = np.random.default_rng(1)
        logits = rng.standard_normal((B, K)).astype(np.float32)
        lab = rng.integers(0, K, (B, 1)).astype(np.int64)
        S = 6
        neg = rng.integers(0, K, (B, S)).astype(np.int64)
        samples = np.concatenate([lab, neg], axis=1)
        probs = rng.random((B, S + 1)).astype(np.float32) * 0.1 + 0.01
        loss = L.sampled_softmax_with_cross_entropy(
            to_tensor(logits), to_tensor(lab), S,
            use_customized_samples=True,
            customized_samples=to_tensor(samples),
            customized_probabilities=to_tensor(probs),
            remove_accidental_hits=False)
        g = np.take_along_axis(logits, samples, axis=1) - np.log(probs)
        m = g - g.max(axis=1, keepdims=True)
        logp = m - np.log(np.exp(m).sum(axis=1, keepdims=True))
        ref = -logp[:, :1]
        np.testing.assert_allclose(np.asarray(loss.numpy()), ref,
                                   rtol=2e-4, atol=2e-5)

    def test_accidental_hits_are_masked(self):
        rng = np.random.default_rng(2)
        logits = rng.standard_normal((2, K)).astype(np.float32)
        lab = np.array([[4], [9]], np.int64)
        # negative column 0 hits the true label of row 0
        neg = np.array([[4, 5, 6], [1, 2, 3]], np.int64)
        samples = np.concatenate([lab, neg], axis=1)
        probs = np.full((2, 4), 0.1, np.float32)
        with_mask = L.sampled_softmax_with_cross_entropy(
            to_tensor(logits), to_tensor(lab), 3,
            use_customized_samples=True,
            customized_samples=to_tensor(samples),
            customized_probabilities=to_tensor(probs),
            remove_accidental_hits=True)
        without = L.sampled_softmax_with_cross_entropy(
            to_tensor(logits), to_tensor(lab), 3,
            use_customized_samples=True,
            customized_samples=to_tensor(samples),
            customized_probabilities=to_tensor(probs),
            remove_accidental_hits=False)
        wm = np.asarray(with_mask.numpy())
        wo = np.asarray(without.numpy())
        assert wm[0, 0] < wo[0, 0]          # hit removed -> lower loss
        np.testing.assert_allclose(wm[1], wo[1], rtol=1e-5)

    def test_num_true_soft_target(self):
        rng = np.random.default_rng(3)
        logits = rng.standard_normal((2, K)).astype(np.float32)
        lab = np.array([[1, 2], [3, 4]], np.int64)
        S = 4
        neg = rng.integers(10, K, (2, S)).astype(np.int64)
        samples = np.concatenate([lab, neg], axis=1)
        probs = np.full((2, S + 2), 0.05, np.float32)
        loss = L.sampled_softmax_with_cross_entropy(
            to_tensor(logits), to_tensor(lab), S, num_true=2,
            use_customized_samples=True,
            customized_samples=to_tensor(samples),
            customized_probabilities=to_tensor(probs),
            remove_accidental_hits=False)
        g = np.take_along_axis(logits, samples, axis=1) - np.log(probs)
        m = g - g.max(axis=1, keepdims=True)
        logp = m - np.log(np.exp(m).sum(axis=1, keepdims=True))
        ref = -(logp[:, :2].sum(axis=1) / 2)[:, None]
        np.testing.assert_allclose(np.asarray(loss.numpy()), ref,
                                   rtol=2e-4, atol=2e-5)

    def test_sampled_path_runs_and_backprops(self):
        rng = np.random.default_rng(4)
        logits = to_tensor(rng.standard_normal((B, K)).astype(
            np.float32))
        logits.stop_gradient = False
        lab = to_tensor(rng.integers(0, K, (B, 1)).astype(np.int64))
        loss = L.sampled_softmax_with_cross_entropy(
            logits, lab, num_samples=5, seed=11)
        assert tuple(loss.shape) == (B, 1)
        loss.sum().backward()
        assert np.abs(np.asarray(logits.grad.numpy())).sum() > 0
