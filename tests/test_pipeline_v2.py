"""Pipeline parallelism v2.

* In-graph path: a real BERT (embeddings + blocks + tied MLM head) trains
  through ParallelEngine at pp=4 on the virtual mesh and matches pp=1
  numerically, reached via the fleet DistributedStrategy compiler.
* Eager path: the 1F1B scheduler runs heterogeneous PipelineLayer stages
  (embedding / blocks / head — different param shapes per stage) with the
  per-stage in-flight bound of the reference's SectionWorker, and matches
  plain sequential grad accumulation.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle1_tpu as paddle
from paddle1_tpu.core.tensor import Tensor, to_tensor
from paddle1_tpu.distributed import ParallelEngine, build_mesh
from paddle1_tpu.text.models import (BertForPretraining, BertModel,
                                     BertPretrainingCriterion)


def _tiny_bert():
    m = BertForPretraining(BertModel(
        vocab_size=128, hidden_size=32, num_hidden_layers=4,
        num_attention_heads=4, intermediate_size=64,
        max_position_embeddings=16, hidden_dropout_prob=0.0,
        attention_probs_dropout_prob=0.0))
    return m, BertPretrainingCriterion(128)


def _batch(rng, b=8, s=16, v=128):
    return {"ids": rng.integers(1, v, (b, s)).astype(np.int32),
            "mlm": rng.integers(0, v, (b, s)).astype(np.int32),
            "nsp": rng.integers(0, 2, (b,)).astype(np.int32)}


class TestInGraphPipelineEngine:
    def _run(self, sd0, batch, pp, steps=3, via_fleet=False,
             n_micro=4):
        m, crit = _tiny_bert()
        for k, t in m.state_dict().items():
            t._data = jnp.asarray(sd0[k])
        opt = paddle.optimizer.SGD(learning_rate=0.05,
                                   parameters=m.parameters())

        def loss_fn(mm, bb):
            s, r = mm(Tensor(bb["ids"]))
            return crit(s, r, Tensor(bb["mlm"]), Tensor(bb["nsp"]))

        if via_fleet:
            from paddle1_tpu.distributed.fleet.meta_optimizers import \
                compile_strategy
            from paddle1_tpu.distributed.fleet.strategy import \
                DistributedStrategy
            strat = DistributedStrategy()
            strat.hybrid_configs = {"pp_degree": pp, "dp_degree": 1,
                                    "mp_degree": 1}
            strat.pipeline = True
            strat.pipeline_configs = {"accumulate_steps": n_micro,
                                      "micro_batch_size": 2}
            kwargs = compile_strategy(strat, n_devices=pp)
            assert kwargs["degrees"]["pp"] == pp
            assert kwargs["pp_microbatches"] == n_micro
            mesh = build_mesh(**kwargs["degrees"],
                              devices=jax.devices()[:pp])
            engine = ParallelEngine(
                m, opt, loss_fn, mesh=mesh,
                zero_stage=kwargs["zero_stage"],
                grad_accum=kwargs["grad_accum"],
                amp_dtype=kwargs["amp_dtype"],
                pp_microbatches=kwargs["pp_microbatches"])
        else:
            mesh = build_mesh(pp=pp, dp=1, devices=jax.devices()[:pp])
            engine = ParallelEngine(
                m, opt, loss_fn, mesh=mesh,
                pp_microbatches=n_micro if pp > 1 else None)
        return [float(engine.step(batch)) for _ in range(steps)]

    def test_pp4_matches_pp1_via_fleet_strategy(self):
        m0, _ = _tiny_bert()
        sd0 = {k: np.asarray(t.data) for k, t in m0.state_dict().items()}
        batch = _batch(np.random.default_rng(0))
        l1 = self._run(sd0, batch, pp=1)
        l4 = self._run(sd0, batch, pp=4, via_fleet=True)
        np.testing.assert_allclose(l1, l4, rtol=2e-4)

    def test_pp2_with_dp2_composes(self):
        """pp manual axis + dp auto axis in one step function."""
        m0, _ = _tiny_bert()
        sd0 = {k: np.asarray(t.data) for k, t in m0.state_dict().items()}
        batch = _batch(np.random.default_rng(1))
        l1 = self._run(sd0, batch, pp=1)
        m, crit = _tiny_bert()
        for k, t in m.state_dict().items():
            t._data = jnp.asarray(sd0[k])
        opt = paddle.optimizer.SGD(learning_rate=0.05,
                                   parameters=m.parameters())

        def loss_fn(mm, bb):
            s, r = mm(Tensor(bb["ids"]))
            return crit(s, r, Tensor(bb["mlm"]), Tensor(bb["nsp"]))

        mesh = build_mesh(pp=2, dp=2, devices=jax.devices()[:4])
        engine = ParallelEngine(m, opt, loss_fn, mesh=mesh,
                                pp_microbatches=2)
        l = [float(engine.step(batch)) for _ in range(3)]
        np.testing.assert_allclose(l1, l, rtol=2e-4)

    def test_pp_without_pipelined_body_raises(self):
        from paddle1_tpu.core.errors import InvalidArgumentError
        lin = paddle.nn.Linear(4, 4)
        opt = paddle.optimizer.SGD(learning_rate=0.1,
                                   parameters=lin.parameters())
        mesh = build_mesh(pp=2, dp=1, devices=jax.devices()[:2])
        with pytest.raises(InvalidArgumentError):
            ParallelEngine(lin, opt, lambda m, b: (m(Tensor(b)) ** 2).sum(),
                           mesh=mesh)


class TestEager1F1B:
    """Heterogeneous stages through the eager SectionWorker-analog."""

    def _model_descs(self, vocab=64, hidden=16, n_blocks=4, classes=4):
        from paddle1_tpu.nn.layer_common import Embedding, Linear

        def mean_pool(x):
            from paddle1_tpu.ops import math_ops
            return math_ops.mean(x, axis=1)

        descs = [Embedding(vocab, hidden)]          # stage with [V,H] param
        for _ in range(n_blocks):
            descs.append(Linear(hidden, hidden))    # mid blocks
        descs.append(mean_pool)                     # fn layer
        descs.append(Linear(hidden, classes))       # head, [H,C]
        return descs

    def _loss_fn(self):
        def f(out, y):
            return paddle.nn.functional.cross_entropy(out, to_tensor(y))
        return f

    def _make(self, num_stages, seed=0):
        from paddle1_tpu.distributed.meta_parallel.pp_layers import \
            PipelineLayer
        np.random.seed(seed)
        descs = self._model_descs()
        model = PipelineLayer(descs, num_stages=num_stages,
                              loss_fn=self._loss_fn(),
                              seg_method="uniform")
        return model

    def _sync_weights(self, src, dst):
        s1, s2 = src.state_dict(), dst.state_dict()
        for k in s1:
            s2[k]._data = s1[k].data

    def test_1f1b_matches_sequential_accumulation(self):
        from paddle1_tpu.distributed.meta_parallel.pipeline_parallel import \
            PipelineParallel
        from paddle1_tpu.distributed import fleet
        from paddle1_tpu.distributed.fleet.strategy import \
            DistributedStrategy

        rng = np.random.default_rng(0)
        x = rng.integers(0, 64, (8, 6)).astype(np.int64)
        y = rng.integers(0, 4, (8,)).astype(np.int64)

        pp_model = self._make(num_stages=4)
        seq_model = self._make(num_stages=4)
        self._sync_weights(pp_model, seq_model)

        # reference: plain sequential micro-batch grad accumulation
        opt_r = paddle.optimizer.SGD(learning_rate=0.1,
                                     parameters=seq_model.parameters())
        tl = None
        for i in range(4):
            out = seq_model(to_tensor(x[i * 2:(i + 1) * 2]))
            l = self._loss_fn()(out, y[i * 2:(i + 1) * 2])
            (l / 4.0).backward()
            tl = l if tl is None else tl + l
        opt_r.step()
        opt_r.clear_grad()

        # 1F1B scheduled
        strat = DistributedStrategy()
        strat.pipeline_configs = {"accumulate_steps": 4,
                                  "micro_batch_size": 2}

        class _HCG:
            def get_data_parallel_group(self):
                from paddle1_tpu.distributed.collective import Group
                return Group(0, 1)

        runner = PipelineParallel(pp_model, _HCG(), strategy=strat)
        opt_p = paddle.optimizer.SGD(learning_rate=0.1,
                                     parameters=pp_model.parameters())
        loss = runner.train_batch([to_tensor(x), y], opt_p)

        np.testing.assert_allclose(float(loss.numpy()),
                                   float((tl / 4.0).numpy()), rtol=1e-5)
        for k, t in pp_model.state_dict().items():
            np.testing.assert_allclose(
                np.asarray(t.data),
                np.asarray(seq_model.state_dict()[k].data),
                rtol=1e-5, atol=1e-6,
                err_msg=f"param {k} diverged between 1F1B and sequential")

    def test_in_flight_bound(self):
        from paddle1_tpu.distributed.meta_parallel.pipeline_parallel import \
            PipelineParallel
        from paddle1_tpu.distributed.fleet.strategy import \
            DistributedStrategy

        rng = np.random.default_rng(1)
        x = rng.integers(0, 64, (16, 6)).astype(np.int64)
        y = rng.integers(0, 4, (16,)).astype(np.int64)
        model = self._make(num_stages=4, seed=1)
        strat = DistributedStrategy()
        strat.pipeline_configs = {"accumulate_steps": 8,
                                  "micro_batch_size": 2}

        class _HCG:
            def get_data_parallel_group(self):
                from paddle1_tpu.distributed.collective import Group
                return Group(0, 1)

        runner = PipelineParallel(model, _HCG(), strategy=strat)
        opt = paddle.optimizer.SGD(learning_rate=0.1,
                                   parameters=model.parameters())
        runner.train_batch([to_tensor(x), y], opt)
        S = 4
        for s in range(S):
            # SectionWorker bound: stage s holds at most S - s microbatches
            assert runner.last_max_in_flight[s] <= S - s, (
                s, runner.last_max_in_flight)
        # the schedule genuinely pipelined (stage 0 reached its bound)
        assert runner.last_max_in_flight[0] == S

    def test_int_boundary_no_deadlock(self):
        """Review finding: a non-differentiable (int) stage boundary must
        not starve the upstream grad queue."""
        from paddle1_tpu.distributed.meta_parallel.pp_layers import \
            PipelineLayer
        from paddle1_tpu.distributed.meta_parallel.pipeline_parallel import \
            PipelineParallel
        from paddle1_tpu.distributed.fleet.strategy import \
            DistributedStrategy
        from paddle1_tpu.nn.layer_common import Embedding, Linear

        def mean_pool(x):
            from paddle1_tpu.ops import math_ops
            return math_ops.mean(x, axis=1)

        # stage 0 = identity over INT ids; embedding only in stage 1
        model = PipelineLayer(
            [lambda x: x, Embedding(32, 8), mean_pool, Linear(8, 4)],
            num_stages=2, loss_fn=self._loss_fn(), seg_method="uniform")
        strat = DistributedStrategy()
        strat.pipeline_configs = {"accumulate_steps": 2,
                                  "micro_batch_size": 2}

        class _HCG:
            def get_data_parallel_group(self):
                from paddle1_tpu.distributed.collective import Group
                return Group(0, 1)

        runner = PipelineParallel(model, _HCG(), strategy=strat)
        opt = paddle.optimizer.SGD(learning_rate=0.1,
                                   parameters=model.parameters())
        rng = np.random.default_rng(3)
        x = rng.integers(0, 32, (4, 5)).astype(np.int64)
        y = rng.integers(0, 4, (4,)).astype(np.int64)
        loss = runner.train_batch([to_tensor(x), y], opt)  # must not hang
        assert np.isfinite(float(loss.numpy()))
        # embedding DID train (grad flowed within stage 1)
        emb = model.run_function[1]
        assert any(np.abs(np.asarray(p.data)).sum() > 0
                   for p in emb.parameters())

    def test_broadcast_mask_pipelined_encoder(self):
        """Review finding: a broadcastable ([1,1,S,S]) mask must work on
        the pipelined encoder path, as it does sequentially."""
        from paddle1_tpu.nn.layer_transformer import (TransformerEncoder,
                                                      TransformerEncoderLayer)
        from paddle1_tpu.distributed.topology import build_mesh as bm
        enc_layer = TransformerEncoderLayer(16, 2, 32, dropout=0.0)
        enc = TransformerEncoder(enc_layer, 4)
        rng = np.random.default_rng(4)
        x = rng.standard_normal((4, 8, 16)).astype(np.float32)
        causal = np.tril(np.ones((8, 8), bool))[None, None]

        seq = enc(to_tensor(x), to_tensor(causal))

        enc.pipeline_axis = "pp"
        enc.pipeline_mesh = bm(pp=4, dp=1, devices=jax.devices()[:4])
        enc.pipeline_microbatches = 2

        def fwd(xa):
            return enc(Tensor(xa), to_tensor(causal)).data

        piped = jax.jit(fwd)(jnp.asarray(x))
        np.testing.assert_allclose(np.asarray(seq.data), np.asarray(piped),
                                   rtol=2e-4, atol=2e-5)
        enc.pipeline_axis = None

    def test_tuple_activation_boundary(self):
        """Review finding: tuple activations crossing a stage boundary."""
        from paddle1_tpu.distributed.meta_parallel.pp_layers import \
            PipelineLayer
        from paddle1_tpu.distributed.meta_parallel.pipeline_parallel import \
            PipelineParallel
        from paddle1_tpu.distributed.fleet.strategy import \
            DistributedStrategy
        from paddle1_tpu.nn.layer_common import Embedding, Linear

        def split2(x):
            return x, x * 2.0

        def join2(a, b):
            from paddle1_tpu.ops import math_ops
            return math_ops.mean(a + b, axis=1)

        model = PipelineLayer(
            [Embedding(32, 8), split2, join2, Linear(8, 4)],
            num_stages=2, loss_fn=self._loss_fn(), seg_method="uniform")
        strat = DistributedStrategy()
        strat.pipeline_configs = {"accumulate_steps": 2,
                                  "micro_batch_size": 2}

        class _HCG:
            def get_data_parallel_group(self):
                from paddle1_tpu.distributed.collective import Group
                return Group(0, 1)

        runner = PipelineParallel(model, _HCG(), strategy=strat)
        opt = paddle.optimizer.SGD(learning_rate=0.1,
                                   parameters=model.parameters())
        rng = np.random.default_rng(5)
        x = rng.integers(0, 32, (4, 5)).astype(np.int64)
        y = rng.integers(0, 4, (4,)).astype(np.int64)
        loss = runner.train_batch([to_tensor(x), y], opt)
        assert np.isfinite(float(loss.numpy()))
        # grads crossed the tuple boundary into the embedding
        emb = model.run_function[0]
        assert emb.weight.grad is None  # cleared by clear_grad
        w_before = np.asarray(emb.weight.data).copy()
        runner.train_batch([to_tensor(x), y], opt)
        assert np.abs(np.asarray(emb.weight.data) - w_before).max() > 0

    def test_heterogeneous_partition_shapes(self):
        model = self._make(num_stages=4, seed=2)
        shapes = []
        for s in range(4):
            shapes.append(sorted(tuple(p.shape)
                                 for l in model.stage_layers(s)
                                 for p in l.parameters()))
        # embedding stage differs from block stages and head stage
        assert shapes[0] != shapes[1]
        assert shapes[-1] != shapes[1]
