"""Tests for paddle1_tpu.distributed.collective — the simulated-mesh test
backend promised by that module's docstring.

Two modes, mirroring the module's two faces:

* **SPMD trace**: every collective under ``shard_map`` over the virtual
  8-device CPU mesh (conftest.py), checking the real multi-device lowering
  numerically — including ReduceOp.PROD's log-magnitude/sign/zero handling
  and the Megatron fwd/bwd pairs (_c_identity/_mp_allreduce).
* **Eager group mode**: world-size-1 no-ops, group bookkeeping, send/recv
  pairing, barrier/wait (reference test_collective_base.py:34,124 roles).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map

import paddle1_tpu.distributed.collective as C
from paddle1_tpu.core.errors import InvalidArgumentError
from paddle1_tpu.core.tensor import Tensor, to_tensor

N = 8


@pytest.fixture(scope="module")
def mesh():
    devs = jax.devices()
    assert len(devs) >= N, "conftest must provision the 8-device CPU mesh"
    return Mesh(np.array(devs[:N]), ("x",))


def _per_rank(shape=(N, 4), seed=0, signed=True):
    rng = np.random.default_rng(seed)
    a = rng.standard_normal(shape).astype(np.float32)
    if not signed:
        a = np.abs(a) + 0.1
    return jnp.asarray(a)


def _run(mesh, fn, x, in_spec=P("x"), out_spec=P("x")):
    """shard_map fn over the 'x' axis; fn sees this rank's shard."""
    return shard_map(fn, mesh=mesh, in_specs=(in_spec,),
                     out_specs=out_spec)(x)


class TestAllReduceTrace:
    def test_sum(self, mesh):
        x = _per_rank()

        def f(xs):
            t = Tensor(xs[0])
            C.all_reduce(t, op=C.ReduceOp.SUM, group="x")
            return t.data[None]

        out = _run(mesh, f, x)
        np.testing.assert_allclose(
            np.asarray(out), np.broadcast_to(np.asarray(x).sum(0), x.shape),
            rtol=1e-5, atol=1e-5)

    def test_max_min_avg(self, mesh):
        x = _per_rank(seed=1)
        for op, ref in ((C.ReduceOp.MAX, np.asarray(x).max(0)),
                        (C.ReduceOp.MIN, np.asarray(x).min(0)),
                        (C.ReduceOp.AVG, np.asarray(x).mean(0))):
            def f(xs):
                t = Tensor(xs[0])
                C.all_reduce(t, op=op, group="x")
                return t.data[None]

            out = _run(mesh, f, x)
            np.testing.assert_allclose(np.asarray(out)[0], ref,
                                       rtol=1e-5, atol=1e-5)

    def test_prod_signs(self, mesh):
        # mixed signs: even/odd negative counts per column
        x = np.ones((N, 4), np.float32) * 2.0
        x[0, 0] = -2.0                    # one negative → negative product
        x[0, 1] = -2.0
        x[1, 1] = -2.0                    # two negatives → positive
        x = jnp.asarray(x)

        def f(xs):
            t = Tensor(xs[0])
            C.all_reduce(t, op=C.ReduceOp.PROD, group="x")
            return t.data[None]

        out = np.asarray(_run(mesh, f, x))[0]
        np.testing.assert_allclose(out, np.asarray(x).prod(0), rtol=1e-4)
        assert out[0] < 0 and out[1] > 0

    def test_prod_zero(self, mesh):
        x = np.full((N, 3), 1.5, np.float32)
        x[3, 2] = 0.0                     # any zero → exact 0, not -inf/nan

        def f(xs):
            t = Tensor(xs[0])
            C.all_reduce(t, op=C.ReduceOp.PROD, group="x")
            return t.data[None]

        out = np.asarray(_run(mesh, f, jnp.asarray(x)))[0]
        np.testing.assert_allclose(out, np.asarray(x).prod(0), rtol=1e-4,
                                   atol=1e-7)
        assert out[2] == 0.0 and np.isfinite(out).all()


class TestRootedTrace:
    def test_reduce_masks_non_dst(self, mesh):
        x = _per_rank(seed=2)

        def f(xs):
            t = Tensor(xs[0])
            C.reduce(t, dst=3, op=C.ReduceOp.SUM, group="x")
            return t.data[None]

        out = np.asarray(_run(mesh, f, x))
        ref = np.asarray(x)
        np.testing.assert_allclose(out[3], ref.sum(0), rtol=1e-5, atol=1e-5)
        for r in range(N):
            if r != 3:
                np.testing.assert_allclose(out[r], ref[r], rtol=1e-6)

    def test_broadcast(self, mesh):
        x = _per_rank(seed=3)

        def f(xs):
            t = Tensor(xs[0])
            C.broadcast(t, src=5, group="x")
            return t.data[None]

        out = np.asarray(_run(mesh, f, x))
        for r in range(N):
            np.testing.assert_allclose(out[r], np.asarray(x)[5], rtol=1e-6)

    def test_scatter(self, mesh):
        x = _per_rank(shape=(N, N, 2), seed=4)  # per-rank list of N chunks

        def f(xs):
            chunks = [Tensor(xs[0, i]) for i in range(N)]
            t = Tensor(jnp.zeros_like(xs[0, 0]))
            C.scatter(t, chunks, src=2, group="x")
            return t.data[None]

        out = np.asarray(_run(mesh, f, x))
        for r in range(N):
            # each rank ends with chunk r of src-rank-2's list
            np.testing.assert_allclose(out[r], np.asarray(x)[2, r],
                                       rtol=1e-6)


class TestGatherScatterTrace:
    def test_all_gather_stacked_and_list(self, mesh):
        x = _per_rank(shape=(N, 3), seed=5)

        def f(xs):
            lst = []
            stacked = C.all_gather(lst, Tensor(xs[0]), group="x")
            assert len(lst) == N
            return stacked.data[None]

        out = np.asarray(_run(mesh, f, x))
        for r in range(N):
            np.testing.assert_allclose(out[r], np.asarray(x), rtol=1e-6)

    def test_reduce_scatter(self, mesh):
        x = _per_rank(shape=(N, N * 2), seed=6)  # each rank holds [N*2]

        def f(xs):
            t = Tensor(jnp.zeros((2,), jnp.float32))
            C.reduce_scatter(t, Tensor(xs[0]), group="x")
            return t.data[None]

        out = np.asarray(_run(mesh, f, x))
        ref = np.asarray(x).sum(0).reshape(N, 2)
        for r in range(N):
            np.testing.assert_allclose(out[r], ref[r], rtol=1e-5, atol=1e-5)

    def test_reduce_scatter_list_input(self, mesh):
        x = _per_rank(shape=(N, N, 2), seed=7)

        def f(xs):
            parts = [Tensor(xs[0, i]) for i in range(N)]
            t = Tensor(jnp.zeros((2,), jnp.float32))
            C.reduce_scatter(t, parts, group="x")
            return t.data[None]

        out = np.asarray(_run(mesh, f, x))
        ref = np.asarray(x).sum(0)  # [N, 2]
        for r in range(N):
            np.testing.assert_allclose(out[r], ref[r], rtol=1e-5, atol=1e-5)

    def test_alltoall(self, mesh):
        x = _per_rank(shape=(N, N, 2), seed=8)  # rank r sends x[r, j] to j

        def f(xs):
            outs = []
            C.alltoall([Tensor(xs[0, i]) for i in range(N)], outs,
                       group="x")
            assert len(outs) == N
            return jnp.stack([o.data for o in outs])[None]

        out = np.asarray(_run(mesh, f, x))
        ref = np.asarray(x)
        for r in range(N):
            for j in range(N):
                np.testing.assert_allclose(out[r, j], ref[j, r], rtol=1e-6)

    def test_all_to_all_alias(self):
        assert C.all_to_all is C.alltoall


class TestMegatronPairsTrace:
    def test_c_identity_fwd_bwd(self, mesh):
        x = _per_rank(shape=(N, 4), seed=9)

        def loss(xs):
            y = C._c_identity(Tensor(xs), group="x")
            return jnp.sum(y.data)

        def f(xs):
            v = loss(xs[0])
            g = jax.grad(loss)(xs[0])
            return v[None], g[None]

        val, grad = shard_map(f, mesh=mesh, in_specs=(P("x"),),
                              out_specs=(P("x"), P("x")))(x)
        # fwd identity: per-rank sum of own shard
        np.testing.assert_allclose(np.asarray(val),
                                   np.asarray(x).sum(-1), rtol=1e-5)
        # bwd psum: each grad element = N (sum of ones across ranks)
        np.testing.assert_allclose(np.asarray(grad),
                                   np.full((N, 4), float(N)), rtol=1e-6)

    def test_mp_allreduce_fwd_bwd(self, mesh):
        x = _per_rank(shape=(N, 4), seed=10)

        def loss(xs):
            y = C._mp_allreduce(Tensor(xs), group="x")
            return jnp.sum(y.data)

        def f(xs):
            v = loss(xs[0])
            g = jax.grad(loss)(xs[0])
            return v[None], g[None]

        val, grad = shard_map(f, mesh=mesh, in_specs=(P("x"),),
                              out_specs=(P("x"), P("x")))(x)
        # fwd psum: every rank's loss = total sum
        np.testing.assert_allclose(np.asarray(val),
                                   np.full(N, np.asarray(x).sum()),
                                   rtol=1e-4)
        # bwd identity: grads are ones (no double-psum)
        np.testing.assert_allclose(np.asarray(grad),
                                   np.ones((N, 4)), rtol=1e-6)

    def test_c_concat(self, mesh):
        x = _per_rank(shape=(N, 2, 3), seed=11)

        def f(xs):
            return C._c_concat(Tensor(xs[0]), group="x").data[None]

        out = np.asarray(_run(mesh, f, x))
        ref = np.concatenate([np.asarray(x)[r] for r in range(N)], axis=-1)
        for r in range(N):
            np.testing.assert_allclose(out[r], ref, rtol=1e-6)

    def test_c_split(self, mesh):
        x = jnp.broadcast_to(_per_rank(shape=(2, N * 3), seed=12),
                             (N, 2, N * 3))

        def f(xs):
            return C._c_split(Tensor(xs[0]), group="x").data[None]

        out = np.asarray(_run(mesh, f, x))
        full = np.asarray(x)[0]
        for r in range(N):
            np.testing.assert_allclose(out[r], full[:, r * 3:(r + 1) * 3],
                                       rtol=1e-6)

    def test_c_split_indivisible_raises(self, mesh):
        x = jnp.ones((N, 2, N * 3 + 1), jnp.float32)

        def f(xs):
            return C._c_split(Tensor(xs[0]), group="x").data[None]

        with pytest.raises(InvalidArgumentError):
            _run(mesh, f, x)

    def test_split_guards(self, mesh):
        with pytest.raises(InvalidArgumentError):
            C.split(to_tensor(np.ones((4, 8), np.float32)), N, axis=0)
        with pytest.raises(InvalidArgumentError):
            C.split(to_tensor(np.ones((4, 8), np.float32)), 3, axis=-1)

    def test_round_trip_identity_concat_split(self, mesh):
        """c_split(c_concat(x)) == x — the column↔row parallel seam."""
        x = _per_rank(shape=(N, 2, 4), seed=13)

        def f(xs):
            y = C._c_concat(Tensor(xs[0]), group="x")
            z = C._c_split(y, group="x")
            return z.data[None]

        out = np.asarray(_run(mesh, f, x))
        np.testing.assert_allclose(out, np.asarray(x), rtol=1e-6)


class TestEagerGroupMode:
    def setup_method(self, _):
        C.destroy_process_group()

    def test_world_size_1_noops(self):
        t = to_tensor(np.arange(6, dtype=np.float32).reshape(2, 3))
        ref = np.asarray(t.numpy()).copy()
        C.all_reduce(t)
        C.broadcast(t, src=0)
        C.reduce(t, dst=0)
        np.testing.assert_allclose(np.asarray(t.numpy()), ref)
        lst = []
        stacked = C.all_gather(lst, t)
        assert len(lst) == 1 and stacked.shape[0] == 1
        np.testing.assert_allclose(np.asarray(lst[0].numpy()), ref)

    def test_group_bookkeeping(self):
        assert not C.is_initialized()
        g0 = C.get_group(0)
        assert C.is_initialized()
        assert g0.world_size == C.get_world_size() == 1
        assert C.get_rank() == 0 and C.get_rank(g0) == 0
        g = C.new_group([0])
        assert g.id >= 1 and g.nranks == 1
        assert g.get_group_rank(0) == 0 and g.get_group_rank(7) == -1
        assert C.get_group(g.id) is g
        assert "Group(" in repr(g)
        C.destroy_process_group(g)
        from paddle1_tpu.core.errors import PreconditionNotMetError
        with pytest.raises(PreconditionNotMetError):
            C.get_group(g.id)
        C.destroy_process_group()
        assert not C.is_initialized()

    def test_send_recv_pairing(self):
        src = to_tensor(np.array([1.0, 2.0], np.float32))
        C.send(src, dst=0)
        dst = to_tensor(np.zeros(2, np.float32))
        C.recv(dst, src=0)
        np.testing.assert_allclose(np.asarray(dst.numpy()), [1.0, 2.0])
        # empty buffer: recv leaves tensor untouched
        dst2 = to_tensor(np.full(2, 7.0, np.float32))
        C.recv(dst2, src=0)
        np.testing.assert_allclose(np.asarray(dst2.numpy()), [7.0, 7.0])

    def test_isend_irecv_work(self):
        w = C.isend(to_tensor(np.ones(2, np.float32)), dst=0)
        assert w.is_completed() and w.wait() is None
        w2 = C.irecv(to_tensor(np.zeros(2, np.float32)), src=0)
        assert w2.is_completed()

    def test_barrier_and_wait(self):
        C.barrier()          # single process: returns without error
        C.wait(to_tensor(np.ones(2, np.float32)))

    def test_all_gather_object(self):
        objs = []
        C.all_gather_object(objs, {"k": 1})
        assert objs == [{"k": 1}]

    def test_reduce_op_constants(self):
        assert (C.ReduceOp.SUM, C.ReduceOp.MAX, C.ReduceOp.MIN,
                C.ReduceOp.PROD, C.ReduceOp.AVG) == (0, 1, 2, 3, 4)


class TestHierarchicalAllReduce:
    """Functional two-level collective (reference
    hierarchical_allreduce strategy)."""

    def test_matches_flat_psum_on_2x4_mesh(self):
        import jax
        import jax.numpy as jnp
        from jax.sharding import Mesh, PartitionSpec as P
        from paddle1_tpu.distributed.collective import (
            hierarchical_all_reduce)
        devs = np.asarray(jax.devices()[:8]).reshape(2, 4)
        mesh = Mesh(devs, ("dcn", "ici"))
        x = np.arange(8 * 4, dtype=np.float32).reshape(8, 4)

        @jax.jit
        def hier(v):
            return shard_map(
                lambda s: hierarchical_all_reduce(s, "ici", "dcn"),
                mesh=mesh, in_specs=P(("dcn", "ici")),
                out_specs=P(("dcn", "ici")))(v)

        @jax.jit
        def flat(v):
            return shard_map(
                lambda s: jax.lax.psum(jax.lax.psum(s, "ici"), "dcn"),
                mesh=mesh, in_specs=P(("dcn", "ici")),
                out_specs=P(("dcn", "ici")))(v)

        np.testing.assert_allclose(np.asarray(hier(x)),
                                   np.asarray(flat(x)), rtol=1e-6)

    def test_non_divisible_falls_back(self):
        import jax
        import jax.numpy as jnp
        from jax.sharding import Mesh, PartitionSpec as P
        from paddle1_tpu.distributed.collective import (
            hierarchical_all_reduce)
        devs = np.asarray(jax.devices()[:8]).reshape(2, 4)
        mesh = Mesh(devs, ("dcn", "ici"))
        x = np.arange(8 * 3, dtype=np.float32).reshape(8, 3)

        @jax.jit
        def hier(v):
            # local shard dim0 = 1 per device over the batch, then the
            # collective sees a [1,3] shard: 1 % 4 != 0 -> flat path
            return shard_map(
                lambda s: hierarchical_all_reduce(s, "ici", "dcn"),
                mesh=mesh, in_specs=P(("dcn", "ici")),
                out_specs=P(("dcn", "ici")))(v)

        expect = np.tile(x.sum(axis=0, keepdims=True) * 0 + x.sum(0),
                         (8, 1))
        np.testing.assert_allclose(np.asarray(hier(x)), expect,
                                   rtol=1e-6)
