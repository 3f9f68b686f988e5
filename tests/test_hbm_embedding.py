"""HBM-resident sharded embedding (heter_ps analog):
table row-sharded over the mesh in device memory, trained under jit,
matching the host-table result."""

import numpy as np
import jax
import pytest

import paddle1_tpu as paddle
from paddle1_tpu.core.tensor import Tensor, to_tensor
from paddle1_tpu.distributed import (HBMShardedEmbedding, ParallelEngine,
                                     build_mesh)
from paddle1_tpu.nn.layer_base import Layer


class _Model(Layer):
    def __init__(self, vocab, dim, axis_size):
        super().__init__()
        self.emb = HBMShardedEmbedding(vocab, dim, axis="sharding",
                                       axis_size=axis_size)
        self.head = paddle.nn.Linear(dim, 1)

    def forward(self, ids):
        return self.head(self.emb(ids).mean(axis=1))


class TestHBMShardedEmbedding:
    def test_eager_lookup_matches_plain_gather(self):
        emb = HBMShardedEmbedding(16, 4)
        ids = to_tensor(np.array([[1, 3], [15, 0]], np.int64))
        out = emb(ids)
        w = np.asarray(emb.weight.numpy())
        np.testing.assert_allclose(np.asarray(out.numpy()),
                                   w[np.array([[1, 3], [15, 0]])])

    def test_vocab_pads_to_shard_multiple(self):
        emb = HBMShardedEmbedding(10, 4, axis_size=4)
        assert emb.vocab_size == 12

    def test_sharded_training_matches_single_device(self):
        """The engine trains the row-sharded table in-graph; values must
        match the SAME model trained dp=1 (a host-table/dense-equivalent
        reference)."""
        n = len(jax.devices())
        if n < 8:
            pytest.skip("needs the 8-device CPU mesh")
        rng = np.random.default_rng(0)
        ids = rng.integers(0, 64, (16, 6)).astype(np.int64)
        y = rng.standard_normal((16, 1)).astype(np.float32)

        def run(degrees):
            paddle.seed(7)
            model = _Model(64, 8, axis_size=degrees.get("sharding", 1))
            opt = paddle.optimizer.SGD(learning_rate=0.1,
                                       parameters=model.parameters())
            eng = ParallelEngine(
                model, opt,
                lambda m, b: ((m(Tensor(b["ids"])) - Tensor(b["y"])) ** 2
                              ).mean(),
                mesh=build_mesh(**degrees,
                                devices=jax.devices()[:int(np.prod(
                                    list(degrees.values())))]),
                zero_stage=0)
            for _ in range(3):
                loss = eng.step({"ids": ids, "y": y})
            eng.sync_model()
            return (float(loss),
                    np.asarray(model.emb.weight.numpy()))

        loss1, w1 = run({"dp": 1})
        loss8, w8 = run({"dp": 2, "sharding": 4})
        assert abs(loss1 - loss8) < 1e-4, (loss1, loss8)
        np.testing.assert_allclose(w1, w8, rtol=2e-4, atol=1e-5)

    def test_service_surface_pull_push(self):
        emb = HBMShardedEmbedding(16, 4)
        rows = emb.pull([2, 5])
        assert rows.shape == (2, 4)
        g = np.ones((2, 4), np.float32)
        emb.push_grad([2, 5], g, lr=0.5)
        np.testing.assert_allclose(emb.pull([2, 5]), rows - 0.5,
                                   rtol=1e-6)
        from paddle1_tpu.core.errors import InvalidArgumentError
        with pytest.raises(InvalidArgumentError, match="host tier"):
            emb.pull([99])
        with pytest.raises(InvalidArgumentError, match="-1"):
            emb.pull([-1])  # negative ids must not wrap around
