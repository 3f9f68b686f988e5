"""The hybrid dp x mp x ZeRO-2 step must lower without GSPMD's
"involuntary full rematerialization" fallback:
grads reduce-scatter into the slot layout instead of replicate-and-
repartition. Reference intent: sharding_optimizer.py:146 "reduce rather
than allreduce"."""

import os
import re
import tempfile
import unittest

import numpy as np
import jax

import paddle1_tpu as paddle
from paddle1_tpu.core.tensor import Tensor
from paddle1_tpu.distributed import ParallelEngine, build_mesh
from paddle1_tpu.text.models import apply_megatron_sharding


def _tiny_bert():
    from paddle1_tpu.text.models import (BertForPretraining, BertModel,
                                         BertPretrainingCriterion)
    model = BertForPretraining(BertModel(
        vocab_size=128, hidden_size=32, num_hidden_layers=1,
        num_attention_heads=2, intermediate_size=64,
        max_position_embeddings=16, hidden_dropout_prob=0.0,
        attention_probs_dropout_prob=0.0))
    return model, BertPretrainingCriterion(128)


class _CaptureFd2:
    """Capture EVERYTHING written to fd 2 (XLA's C++ glog warnings bypass
    sys.stderr) for the duration of the with-block."""

    def __enter__(self):
        self._saved = os.dup(2)
        self._tmp = tempfile.TemporaryFile()
        os.dup2(self._tmp.fileno(), 2)
        return self

    def __exit__(self, *exc):
        os.dup2(self._saved, 2)
        os.close(self._saved)
        self._tmp.seek(0)
        self.text = self._tmp.read().decode(errors="replace")
        self._tmp.close()
        return False


@unittest.skipIf(len(jax.devices()) < 8, "needs the 8-device CPU mesh")
class TestHybridZero2Lowering(unittest.TestCase):
    def test_no_involuntary_remat_and_reduce_scatter_present(self):
        model, crit = _tiny_bert()
        apply_megatron_sharding(model)
        opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                     parameters=model.parameters())

        def loss_fn(m, batch):
            scores, rel = m(Tensor(batch["ids"]))
            return crit(scores, rel, Tensor(batch["mlm"]),
                        Tensor(batch["nsp"]))

        mesh = build_mesh(dp=2, mp=2, sharding=2, devices=jax.devices()[:8])
        engine = ParallelEngine(model, opt, loss_fn, mesh=mesh,
                                zero_stage=2, clip_global_norm=1.0)
        rng = np.random.default_rng(0)
        batch = {
            "ids": rng.integers(1, 128, (8, 16)).astype(np.int32),
            "mlm": rng.integers(0, 128, (8, 16)).astype(np.int32),
            "nsp": rng.integers(0, 2, (8,)).astype(np.int32),
        }
        placed = engine.shard_batch(batch)
        lowered = engine._jit.lower(engine.params, engine.opt_state, placed,
                                    jax.random.PRNGKey(0),
                                    np.float32(1e-4), engine.step_state)
        with _CaptureFd2() as cap:
            compiled = lowered.compile()
        self.assertNotIn("Involuntary full rematerialization", cap.text,
                         "GSPMD fell back to replicate-then-repartition:\n"
                         + cap.text[-2000:])

        hlo = compiled.as_text()
        # no all-to-all fallback in the grad path. (reduce-scatter itself
        # is not asserted: XLA:CPU never forms it — the
        # allreduce+slice→reduce-scatter reassociation is a TPU/GPU pass;
        # on CPU the grads lower to all-reduce + local slice.)
        self.assertNotIn("all-to-all", hlo)
        self.assertIn("all-reduce", hlo)  # the batch-axis grad reduction

        # and the step still trains
        loss = engine.step(batch)
        self.assertTrue(np.isfinite(float(loss)))
