"""Sparse embedding gradients (IndexedSlices / SelectedRows analog) and the
host-RAM embedding-table service (scoped PS analog);
reference selected_rows.h, adam_op.h SparseAdamFunctor,
distributed/table/common_sparse_table.h."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle1_tpu as paddle
from paddle1_tpu.core.indexed_slices import IndexedSlices
from paddle1_tpu.core.tensor import to_tensor
from paddle1_tpu.nn.layer_common import Embedding

VOCAB = 50_000  # big enough that a dense [vocab, dim] grad would be obvious
DIM = 16


class TestIndexedSlices:
    def test_merge_sums_duplicates(self):
        s = IndexedSlices([3, 1, 3], np.ones((3, 4), np.float32), (10, 4))
        m = s.merge()
        assert m.n_rows == 2
        rows = np.asarray(m.rows).tolist()
        vals = np.asarray(m.values)
        assert rows == [1, 3]
        np.testing.assert_allclose(vals[rows.index(3)], 2.0)
        np.testing.assert_allclose(vals[rows.index(1)], 1.0)

    def test_add_concats_and_to_dense(self):
        a = IndexedSlices([0], np.full((1, 2), 2.0, np.float32), (4, 2))
        b = IndexedSlices([0], np.full((1, 2), 3.0, np.float32), (4, 2))
        c = a + b
        assert c.n_rows == 2
        d = np.asarray(c.to_dense())
        np.testing.assert_allclose(d[0], 5.0)
        np.testing.assert_allclose(d[1:], 0.0)

    def test_dense_mix_and_scalar_mul(self):
        s = IndexedSlices([1], np.ones((1, 2), np.float32), (3, 2))
        dense = jnp.ones((3, 2))
        np.testing.assert_allclose(np.asarray(s + dense)[1], 2.0)
        np.testing.assert_allclose(np.asarray((2.0 * s).values), 2.0)

    def test_shape_mismatch_raises(self):
        a = IndexedSlices([0], np.ones((1, 2), np.float32), (4, 2))
        b = IndexedSlices([0], np.ones((1, 3), np.float32), (4, 3))
        with pytest.raises(ValueError):
            a + b


class TestSparseEmbeddingGrad:
    def _grads(self, sparse):
        emb = Embedding(VOCAB, DIM, sparse=sparse)
        ids = to_tensor(np.array([[3, 7], [3, 11]], np.int64))
        out = emb(ids)
        loss = (out * out).sum()
        loss.backward()
        return emb, emb.weight.grad

    def test_eager_grad_is_indexed_slices(self):
        emb, g = self._grads(sparse=True)
        assert isinstance(g.data, IndexedSlices)
        # memory: 4 touched rows, NOT vocab rows
        assert g.data.values.shape == (4, DIM)
        assert g.data.dense_shape == (VOCAB, DIM)

    def test_sparse_matches_dense_grad(self):
        rng_state = np.random.default_rng(0)
        w = rng_state.standard_normal((VOCAB, DIM)).astype(np.float32)
        ids = np.array([[3, 7], [3, 11]], np.int64)

        def run(sparse):
            emb = Embedding(VOCAB, DIM, sparse=sparse)
            emb.weight._data = jnp.asarray(w)
            out = emb(to_tensor(ids))
            ((out * out).sum()).backward()
            g = emb.weight.grad.data
            return np.asarray(g.to_dense() if isinstance(g, IndexedSlices)
                              else g)

        np.testing.assert_allclose(run(True), run(False), rtol=1e-5,
                                   atol=1e-6)

    def test_accumulation_two_backwards(self):
        emb = Embedding(VOCAB, DIM, sparse=True)
        for _ in range(2):
            out = emb(to_tensor(np.array([5], np.int64)))
            out.sum().backward()
        g = emb.weight.grad.data
        assert isinstance(g, IndexedSlices) and g.n_rows == 2
        merged = g.merge()
        assert merged.n_rows == 1
        np.testing.assert_allclose(np.asarray(merged.values), 2.0)

    def test_padding_idx_rows_zeroed(self):
        emb = Embedding(VOCAB, DIM, padding_idx=0, sparse=True)
        out = emb(to_tensor(np.array([0, 2], np.int64)))
        out.sum().backward()
        g = emb.weight.grad.data.merge()
        vals = np.asarray(g.values)
        rows = np.asarray(g.rows).tolist()
        np.testing.assert_allclose(vals[rows.index(0)], 0.0)
        assert np.abs(vals[rows.index(2)]).max() > 0

    def test_non_leaf_weight_densifies(self):
        """Review finding: a derived (non-leaf) weight cannot take the
        sparse path — its producer's jax.vjp expects array cotangents."""
        from paddle1_tpu.nn import functional as F
        base = to_tensor(
            np.random.default_rng(5).standard_normal((64, DIM))
            .astype(np.float32))
        base.stop_gradient = False
        w2 = base * 2.0  # non-leaf
        out = F.embedding(to_tensor(np.array([1, 2], np.int64)), w2,
                          sparse=True)
        out.sum().backward()  # must not crash
        g = base.grad.data
        assert not isinstance(g, IndexedSlices)
        assert np.asarray(g).shape == (64, DIM)
        assert np.abs(np.asarray(g)[1]).max() > 0

    def test_under_jit_densifies_but_works(self):
        """Functional path: sparse=True under trace falls back to the dense
        vjp (documented — scatter-add is the efficient jit lowering)."""
        emb = Embedding(64, DIM, sparse=True)
        params = emb.functional_state()
        ids = jnp.asarray([1, 2, 3])

        def loss_fn(params):
            with emb.load_functional_state(params):
                return (emb(to_tensor(ids)) ** 2).sum().data

        g = jax.grad(loss_fn)(params)
        leaf = jax.tree_util.tree_leaves(g)[0]
        assert leaf.shape == (64, DIM)
        assert np.isfinite(np.asarray(leaf)).all()


class TestSparseOptimizerUpdates:
    def _setup(self, vocab=100):
        rng = np.random.default_rng(1)
        w = rng.standard_normal((vocab, DIM)).astype(np.float32)
        ids = np.array([2, 9, 2], np.int64)
        return w, ids

    def _grad_slices(self, w, ids):
        emb = Embedding(w.shape[0], DIM, sparse=True)
        emb.weight._data = jnp.asarray(w)
        out = emb(to_tensor(ids))
        (out.sum()).backward()
        return emb

    def test_sgd_sparse_touches_only_rows(self):
        w, ids = self._setup()
        emb = self._grad_slices(w, ids)
        opt = paddle.optimizer.SGD(learning_rate=0.5,
                                   parameters=emb.parameters())
        opt.step()
        neww = np.asarray(emb.weight.data)
        untouched = [i for i in range(100) if i not in ids]
        np.testing.assert_array_equal(neww[untouched], w[untouched])
        # touched rows moved by -lr * summed grad (grad of sum = 1 per hit)
        np.testing.assert_allclose(neww[9], w[9] - 0.5, rtol=1e-6)
        np.testing.assert_allclose(neww[2], w[2] - 1.0, rtol=1e-6)

    def test_adam_lazy_matches_dense_on_touched_rows(self):
        w, ids = self._setup()
        emb_s = self._grad_slices(w, ids)
        opt_s = paddle.optimizer.Adam(learning_rate=0.1, lazy_mode=True,
                                      parameters=emb_s.parameters())
        opt_s.step()

        emb_d = Embedding(100, DIM, sparse=False)
        emb_d.weight._data = jnp.asarray(w)
        out = emb_d(to_tensor(ids))
        out.sum().backward()
        opt_d = paddle.optimizer.Adam(learning_rate=0.1,
                                      parameters=emb_d.parameters())
        opt_d.step()

        ws = np.asarray(emb_s.weight.data)
        wd = np.asarray(emb_d.weight.data)
        for r in set(ids.tolist()):
            np.testing.assert_allclose(ws[r], wd[r], rtol=1e-5, atol=1e-6)
        # lazy: untouched rows identical to start; dense Adam also leaves
        # them (zero grad, zero moments) — but lazy guarantees no compute
        untouched = [i for i in range(100) if i not in ids]
        np.testing.assert_array_equal(ws[untouched], w[untouched])

    def test_adam_nonlazy_densifies(self):
        w, ids = self._setup()
        emb = self._grad_slices(w, ids)
        opt = paddle.optimizer.Adam(learning_rate=0.1, lazy_mode=False,
                                    parameters=emb.parameters())
        opt.step()  # must not raise; falls back to densified update
        assert np.isfinite(np.asarray(emb.weight.data)).all()

    def test_global_norm_clip_with_sparse(self):
        w, ids = self._setup()
        emb = self._grad_slices(w, ids)
        clip = paddle.nn.ClipGradByGlobalNorm(1e-4)  # force clipping
        opt = paddle.optimizer.SGD(learning_rate=1.0, grad_clip=clip,
                                   parameters=emb.parameters())
        opt.step()
        delta = np.abs(np.asarray(emb.weight.data) - w).max()
        assert 0 < delta < 1e-3  # clipped hard, but an update happened


class TestEmbeddingService:
    def test_pull_creates_and_is_deterministic(self):
        from paddle1_tpu.distributed.ps import EmbeddingService
        svc = EmbeddingService(dim=8, num_shards=4)
        a = svc.pull([5, 9, 5])
        assert a.shape == (3, 8)
        np.testing.assert_array_equal(a[0], a[2])
        b = svc.pull([5])
        np.testing.assert_array_equal(a[0], b[0])
        assert len(svc) == 2

    def test_push_sgd_updates(self):
        from paddle1_tpu.distributed.ps import EmbeddingService
        svc = EmbeddingService(dim=4, num_shards=2, optimizer="sgd", lr=0.5)
        before = svc.pull([7]).copy()
        svc.push([7], np.ones((1, 4), np.float32))
        after = svc.pull([7])
        np.testing.assert_allclose(after, before - 0.5, rtol=1e-6)

    def test_adagrad_and_adam_slots(self):
        from paddle1_tpu.distributed.ps import EmbeddingService
        for optname in ("adagrad", "adam"):
            svc = EmbeddingService(dim=4, num_shards=1, optimizer=optname,
                                   lr=0.1)
            before = svc.pull([3]).copy()
            for _ in range(3):
                svc.push([3], np.ones((1, 4), np.float32))
            after = svc.pull([3])
            assert (after < before).all()

    def test_state_dict_roundtrip(self):
        from paddle1_tpu.distributed.ps import EmbeddingService
        svc = EmbeddingService(dim=4, num_shards=2)
        svc.pull([1, 2, 3])
        svc.push([1], np.ones((1, 4), np.float32))
        state = svc.state_dict()
        svc2 = EmbeddingService(dim=4, num_shards=2)
        svc2.load_state_dict(state)
        np.testing.assert_array_equal(svc.pull([1, 2, 3]),
                                      svc2.pull([1, 2, 3]))

    def test_distributed_embedding_trains(self):
        """End-to-end: embedding-heavy model, loss decreases, device-side
        memory independent of vocab (only unique rows pulled)."""
        from paddle1_tpu.distributed.ps import (DistributedEmbedding,
                                                EmbeddingService)
        svc = EmbeddingService(dim=DIM, num_shards=4, optimizer="adagrad",
                               lr=0.5)
        emb = DistributedEmbedding(svc)
        rng = np.random.default_rng(0)
        ids = rng.integers(0, 10_000_000, (8, 4))  # 10M-vocab table
        target = jnp.asarray(rng.standard_normal((8, 4, DIM))
                             .astype(np.float32))

        losses = []
        for _ in range(5):
            out = emb(to_tensor(ids))
            assert emb._last_pulled.data.shape[0] <= 32  # unique ids only
            loss = ((out - to_tensor(target)) ** 2).mean()
            loss.backward()
            losses.append(float(loss.numpy()))
        assert losses[-1] < losses[0] * 0.9
        assert len(svc) <= 32
