"""Dense tables + async Communicator + geo-async SGD: the reference PS trains DENSE params asynchronously through
send/recv gradient queues (communicator.cc, common_dense_table.h) and
supports geo-async staleness (sparse_geo_table.h)."""

import os
import subprocess
import sys
import textwrap
import threading
import time

import numpy as np
import pytest

from paddle1_tpu.distributed.communicator import (AsyncCommunicator,
                                                  DenseEndpoint,
                                                  GeoCommunicator)
from paddle1_tpu.distributed.ps import DenseTable, SparseTable
from paddle1_tpu.distributed.ps_server import RemoteTable, TableServer

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class TestDenseTable:
    def test_sgd_update_math(self):
        t = DenseTable((3, 2), optimizer="sgd", lr=0.5, seed=1)
        v0 = t.pull_dense()
        g = np.ones((3, 2), np.float32)
        t.push_dense_grad(g)
        np.testing.assert_allclose(t.pull_dense(), v0 - 0.5, rtol=1e-6)
        assert t.get_version() == 1

    def test_adam_update_moves_against_grad(self):
        t = DenseTable((4,), optimizer="adam", lr=0.1, seed=2)
        v0 = t.pull_dense()
        for _ in range(3):
            t.push_dense_grad(np.ones(4, np.float32))
        assert (t.pull_dense() < v0).all()
        assert t.get_version() == 3

    def test_delta_merge_and_state_roundtrip(self):
        t = DenseTable((2, 2), seed=3)
        v0 = t.pull_dense()
        t.push_dense_delta(np.full((2, 2), 0.25, np.float32))
        np.testing.assert_allclose(t.pull_dense(), v0 + 0.25, rtol=1e-6)
        sd = t.state_dict()
        t2 = DenseTable((2, 2), seed=99)
        t2.load_state_dict(sd)
        np.testing.assert_allclose(t2.pull_dense(), t.pull_dense())
        assert t2.get_version() == t.get_version()

    def test_shape_mismatch_raises(self):
        t = DenseTable((2, 2))
        with pytest.raises(ValueError, match="shape"):
            t.push_dense_grad(np.ones((3, 3), np.float32))


class TestServedDense:
    def test_named_dense_tables_over_the_wire(self):
        dense = {"w": DenseTable((4, 3), lr=0.1, seed=0),
                 "b": DenseTable((3,), lr=0.1, seed=1)}
        srv = TableServer(SparseTable(dim=8), aux_tables=dense).start()
        try:
            rt = RemoteTable(srv.endpoint)
            assert rt.list_tables() == ["b", "w"]
            w0 = rt.table_call("w", "pull_dense")
            rt.table_call("w", "push_dense_grad", np.ones((4, 3),
                                                          np.float32))
            np.testing.assert_allclose(
                rt.table_call("w", "pull_dense"), w0 - 0.1, rtol=1e-6)
            # primary sparse table still serves on the same port
            assert rt.pull([1, 2]).shape == (2, 8)
            # unknown table / non-whitelisted method are loud errors
            from paddle1_tpu.core.errors import PreconditionNotMetError
            with pytest.raises(PreconditionNotMetError, match="no table"):
                rt.table_call("nope", "pull_dense")
            with pytest.raises(PreconditionNotMetError,
                               match="RPC_METHODS"):
                rt.table_call("w", "load_state_dict", {})
        finally:
            srv.stop()


class TestAsyncCommunicator:
    def test_merge_mean_applies_once(self):
        t = DenseTable((2,), optimizer="sgd", lr=1.0, seed=0)
        v0 = t.pull_dense()
        comm = AsyncCommunicator({"w": t}, merge_num=4,
                                 merge_mode="mean").start()
        try:
            for g in ([2.0, 0.0], [0.0, 2.0], [2.0, 2.0], [0.0, 0.0]):
                comm.send("w", np.asarray(g, np.float32))
            comm.flush()
            # mean of the four grads = [1, 1] applied with lr=1
            np.testing.assert_allclose(t.pull_dense(), v0 - 1.0,
                                       rtol=1e-5)
            np.testing.assert_allclose(comm.recv("w"), t.pull_dense())
        finally:
            comm.stop()

    def test_async_linear_regression_converges_two_threads(self):
        rng = np.random.default_rng(0)
        W_true = rng.standard_normal((5, 1)).astype(np.float32)
        # async SGD stability: staleness (steps between cache refreshes)
        # x lr must stay inside the contraction region, so small lr and a
        # fast pull interval
        t = DenseTable((5, 1), optimizer="sgd", lr=0.01, seed=1)
        comm = AsyncCommunicator({"w": t}, merge_num=2,
                                 pull_interval=0.005).start()

        def worker(seed):
            r = np.random.default_rng(seed)
            for _ in range(400):
                X = r.standard_normal((16, 5)).astype(np.float32)
                y = X @ W_true
                w = comm.recv("w")
                grad = 2.0 * X.T @ (X @ w - y) / len(X)
                comm.send("w", grad)
                time.sleep(0.001)

        try:
            ts = [threading.Thread(target=worker, args=(s,))
                  for s in (1, 2)]
            [th.start() for th in ts]
            [th.join() for th in ts]
            comm.flush()
            err = float(np.abs(t.pull_dense() - W_true).max())
            assert err < 0.05, err
            assert t.get_version() > 100  # many merged async updates
        finally:
            comm.stop()

    def test_send_before_start_raises(self):
        from paddle1_tpu.core.errors import PreconditionNotMetError
        comm = AsyncCommunicator({"w": DenseTable((2,))})
        with pytest.raises(PreconditionNotMetError):
            comm.send("w", np.zeros(2, np.float32))


class TestGeoAsync:
    def test_staleness_bounded_and_converges(self):
        rng = np.random.default_rng(0)
        W_true = rng.standard_normal((4,)).astype(np.float32) * 0.5
        table = DenseTable((4,), seed=1)
        geo = GeoCommunicator({"w": table}, geo_k=5)
        w = geo.register("w")
        versions_at_sync = []
        max_lag = 0
        for step in range(100):
            X = rng.standard_normal((8, 4)).astype(np.float32)
            y = X @ W_true
            grad = 2.0 * X.T @ (X @ w - y) / len(X)
            w = w - 0.05 * grad          # LOCAL update (no PS traffic)
            lag_before = geo.steps_since_sync("w")
            w = geo.step("w", w)
            max_lag = max(max_lag, lag_before + 1)
            if geo.steps_since_sync("w") == 0:
                versions_at_sync.append(table.get_version())
        assert max_lag <= 5               # bounded staleness: geo_k
        # the PS only heard from us every geo_k steps
        assert len(versions_at_sync) == 100 // 5
        assert float(np.abs(w - W_true).max()) < 0.05

    def test_two_workers_deltas_compose(self):
        table = DenseTable((2,), seed=0)
        v0 = table.pull_dense()
        a = GeoCommunicator({"w": table}, geo_k=1)
        b = GeoCommunicator({"w": table}, geo_k=1)
        wa, wb = a.register("w"), b.register("w")
        a.step("w", wa + np.float32(1.0))
        b.step("w", wb + np.float32(2.0))  # pushes vs its OWN base
        np.testing.assert_allclose(table.pull_dense(), v0 + 3.0,
                                   rtol=1e-6)


WORKER = textwrap.dedent("""
    import os, sys, time
    import numpy as np
    sys.path.insert(0, os.environ["REPO"])
    from paddle1_tpu.distributed.communicator import AsyncCommunicator
    from paddle1_tpu.distributed.ps_server import RemoteTable

    seed = int(sys.argv[1])
    rt = RemoteTable(os.environ["PS_ENDPOINT"])
    comm = AsyncCommunicator({"w": (rt, "w")}, merge_num=2,
                             pull_interval=0.01).start()
    rng = np.random.default_rng(seed)
    W_true = np.arange(1, 6, dtype=np.float32).reshape(5, 1) / 5.0
    emb_ids = [seed * 10 + 1, seed * 10 + 2]
    for step in range(300):
        X = rng.standard_normal((16, 5)).astype(np.float32)
        y = X @ W_true
        w = comm.recv("w")
        grad = 2.0 * X.T @ (X @ w - y) / len(X)
        comm.send("w", grad)
        rows = rt.pull(emb_ids)              # sparse path on same port
        rt.push(emb_ids, 0.1 * rows)         # in-table sgd step
        time.sleep(0.001)
    comm.stop()
    w = comm.recv("w")
    print("FINAL_ERR", float(np.abs(w - W_true).max()))
""")


class TestTwoProcessDownpourDense:
    def test_two_worker_processes_train_dense_and_sparse(self):
        """The 'done' criterion: two real worker PROCESSES
        training dense (async Communicator) + sparse (pull/push) params
        through one PS endpoint, converging."""
        dense = {"w": DenseTable((5, 1), optimizer="sgd", lr=0.02,
                                 seed=1)}
        sparse = SparseTable(dim=3, optimizer="sgd", lr=1.0)
        srv = TableServer(sparse, aux_tables=dense).start()
        env = {k: v for k, v in os.environ.items()}
        env.update({"REPO": REPO, "PS_ENDPOINT": srv.endpoint,
                    "JAX_PLATFORMS": "cpu"})
        try:
            procs = [subprocess.Popen([sys.executable, "-c", WORKER,
                                       str(s)],
                                      env=env, stdout=subprocess.PIPE,
                                      stderr=subprocess.PIPE)
                     for s in (1, 2)]
            outs = [p.communicate(timeout=240) for p in procs]
            for p, (out, errtxt) in zip(procs, outs):
                assert p.returncode == 0, (out.decode(), errtxt.decode())
                err = float(out.decode().split("FINAL_ERR")[1])
                assert err < 0.1, (err, out.decode())
            W_true = np.arange(1, 6, dtype=np.float32).reshape(5, 1) / 5.0
            assert float(np.abs(dense["w"].pull_dense()
                                - W_true).max()) < 0.1
            # both workers' sparse rows were trained in-table
            assert len(sparse) == 4
            # gradient-ascent-by-0.1 rows moved away from init
            assert dense["w"].get_version() > 50
        finally:
            srv.stop()


class TestReviewRegressions:
    def test_load_state_dict_validates_shape_and_optimizer(self):
        src = DenseTable((4, 2), optimizer="adam")
        sd = src.state_dict()
        with pytest.raises(ValueError, match="shape"):
            DenseTable((2, 2), optimizer="adam").load_state_dict(sd)
        with pytest.raises(ValueError, match="optimizer"):
            DenseTable((4, 2), optimizer="sgd").load_state_dict(sd)

    def test_send_surfaces_dead_send_thread(self):
        from paddle1_tpu.core.errors import PreconditionNotMetError

        class Broken:
            RPC_METHODS = DenseTable.RPC_METHODS

            def pull_dense(self):
                return np.zeros(2, np.float32)

            def push_dense_grad(self, g):
                raise ConnectionError("ps is gone")

            def get_version(self):
                return 0

        comm = AsyncCommunicator({"w": Broken()}, send_queue_size=1,
                                 send_interval=0.001)
        comm._max_retries = 2
        comm.start()
        try:
            deadline = time.time() + 10
            with pytest.raises(PreconditionNotMetError, match="down"):
                while time.time() < deadline:
                    comm.send("w", np.zeros(2, np.float32))
                    time.sleep(0.01)
                raise TimeoutError("send never surfaced the dead thread")
        finally:
            comm._stop.set()
            for t in comm._threads:
                t.join(timeout=5)


class TestFleetPersistables:
    """fleet.save/load_persistables + save_inference_model parity."""

    def test_roundtrip_dense_and_tables(self, tmp_path):
        import paddle1_tpu as paddle
        import paddle1_tpu.distributed.fleet as fleet
        fleet.init()
        fleet.fleet.init_server(dim=4, dense_tables={"w": (2, 2)})
        tbl = fleet.fleet._server_table
        tbl.pull([1, 2, 3])
        fleet.fleet._server_dense["w"].push_dense_grad(
            np.ones((2, 2), np.float32))
        model = paddle.nn.Linear(3, 2)
        d = str(tmp_path / "ckpt")
        fleet.fleet.save_persistables(dirname=d, model=model)

        # mutate, then restore
        w_after = fleet.fleet._server_dense["w"].pull_dense().copy()
        fleet.fleet._server_dense["w"].push_dense_grad(
            np.ones((2, 2), np.float32))
        tbl.push([1], np.ones((1, 4), np.float32))
        fleet.fleet.load_persistables(dirname=d, model=model)
        np.testing.assert_allclose(
            fleet.fleet._server_dense["w"].pull_dense(), w_after)
        assert len(fleet.fleet._server_table) == 3

    def test_save_inference_model_gates_and_writes(self, tmp_path):
        import os
        import paddle1_tpu as paddle
        import paddle1_tpu.distributed.fleet as fleet
        from paddle1_tpu.jit import InputSpec
        from paddle1_tpu.core.errors import PreconditionNotMetError
        fleet.init()
        with pytest.raises(PreconditionNotMetError, match="input_spec"):
            fleet.fleet.save_inference_model(dirname=str(tmp_path))
        m = paddle.nn.Linear(4, 2)
        fleet.fleet.save_inference_model(
            dirname=str(tmp_path / "sim"), model=m,
            input_spec=[InputSpec([1, 4], "float32", "x")])
        assert os.path.exists(str(tmp_path / "sim" / "model.pdmodel"))
