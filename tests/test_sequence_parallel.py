"""Ring/Ulysses sequence parallelism vs dense attention on the virtual
mesh (capability extension — no reference counterpart, SURVEY §5)."""

import unittest

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map as _sm


def shard_map(f, mesh, in_specs, out_specs):
    return _sm(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs)

from paddle1_tpu.distributed.sequence_parallel import (ring_attention,
                                                       ulysses_attention)
from paddle1_tpu.nn.functional.attention import attention_ref


def _data(B=2, N=64, H=4, D=16, seed=0):
    rng = np.random.default_rng(seed)
    mk = lambda: jnp.asarray(rng.standard_normal((B, N, H, D)).astype(
        np.float32))
    return mk(), mk(), mk()


class TestSequenceParallel(unittest.TestCase):
    def setUp(self):
        self.mesh = Mesh(np.array(jax.devices()[:4]), ("sp",))
        self.spec = P(None, "sp")

    def _sp(self, fn, *args):
        return shard_map(fn, self.mesh, tuple(self.spec for _ in args),
                         self.spec)(*args)

    def test_ring_matches_dense(self):
        q, k, v = _data()
        for causal in (False, True):
            out = self._sp(lambda q, k, v, c=causal: ring_attention(
                q, k, v, "sp", causal=c), q, k, v)
            ref = attention_ref(q, k, v, is_causal=causal)
            np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                       atol=2e-5)

    def test_ulysses_matches_dense(self):
        q, k, v = _data()
        for causal in (False, True):
            out = self._sp(lambda q, k, v, c=causal: ulysses_attention(
                q, k, v, "sp", causal=c), q, k, v)
            ref = attention_ref(q, k, v, is_causal=causal)
            np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                       atol=2e-5)

    def test_ring_gradients(self):
        q, k, v = _data(N=32)

        def loss_sp(q, k, v):
            out = self._sp(lambda q, k, v: ring_attention(
                q, k, v, "sp", causal=True), q, k, v)
            return jnp.sum(out ** 2)

        def loss_ref(q, k, v):
            return jnp.sum(attention_ref(q, k, v, is_causal=True) ** 2)

        gs = jax.grad(loss_sp, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gs, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=5e-4)

    def test_ulysses_head_divisibility(self):
        q, k, v = _data(H=3)
        with self.assertRaises(Exception):
            self._sp(lambda q, k, v: ulysses_attention(q, k, v, "sp"),
                     q, k, v)


class TestFlashKernel(unittest.TestCase):
    def test_flash_vs_ref(self):
        from paddle1_tpu.ops.pallas import flash_attention as fa
        rng = np.random.default_rng(1)
        shape = (2, 256, 2, 64)
        q, k, v = (jnp.asarray(rng.standard_normal(shape, np.float32))
                   for _ in range(3))
        self.assertTrue(fa.supported(q.shape, k.shape))
        for causal in (False, True):
            out = fa.flash_attention(q, k, v, causal=causal)
            ref = attention_ref(q, k, v, is_causal=causal)
            np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                       atol=2e-5)

    def test_flash_grads(self):
        from paddle1_tpu.ops.pallas import flash_attention as fa
        rng = np.random.default_rng(2)
        shape = (1, 128, 2, 32)
        q, k, v = (jnp.asarray(rng.standard_normal(shape, np.float32))
                   for _ in range(3))
        gf = jax.grad(lambda q, k, v: jnp.sum(
            fa.flash_attention(q, k, v, causal=True) ** 2),
            argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(lambda q, k, v: jnp.sum(
            attention_ref(q, k, v, is_causal=True) ** 2),
            argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gf, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=5e-4)

    def test_unsupported_shapes_gated(self):
        from paddle1_tpu.ops.pallas import flash_attention as fa
        self.assertFalse(fa.supported((2, 100, 4, 64), (2, 100, 4, 64)))
        self.assertFalse(fa.supported((2, 128, 4, 257), (2, 128, 4, 257)))
