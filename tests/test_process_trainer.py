"""ProcessMultiTrainer: real process Hogwild workers over the shm arena
(the reference HogwildWorker is a parallel C++ thread,
device_worker.h:150)."""

import time

import numpy as np
import pytest

from paddle1_tpu.core import native

pytestmark = pytest.mark.skipif(not native.available(),
                                reason="needs the native shm arena")


# -- module-level factories (spawn-picklable) --------------------------------

def _model_fn():
    import paddle1_tpu as paddle
    return paddle.nn.Linear(16, 1)


def _optimizer_fn(model):
    import paddle1_tpu as paddle
    return paddle.optimizer.SGD(learning_rate=0.05,
                                parameters=model.parameters())


def _mse_loss(model, batch):
    from paddle1_tpu.core.tensor import to_tensor
    pred = model(to_tensor(batch["x"]))
    y = to_tensor(batch["y"])
    return ((pred - y) * (pred - y)).mean()


def _slot_loss(model, batch):
    """CPU-bound slot-file workload: GIL-heavy python feature hashing
    before the tiny model math (the work profile process workers exist
    for)."""
    import numpy as _np
    from paddle1_tpu.core.tensor import to_tensor
    feats = _np.zeros((len(batch["slots"]), 16), _np.float32)
    for i, line in enumerate(batch["slots"]):          # pure-Python parse
        for tok in line.split():
            h = 0
            for ch in tok:                              # GIL-bound hash
                h = (h * 131 + ord(ch)) & 0xFFFFFFFF
            feats[i, h % 16] += 1.0
    pred = model(to_tensor(feats))
    return (pred * pred).mean()


def _make_xy_batches(n_batches, batch=16, seed=0):
    rng = np.random.default_rng(seed)
    W = rng.standard_normal((16, 1)).astype(np.float32)
    out = []
    for _ in range(n_batches):
        X = rng.standard_normal((batch, 16)).astype(np.float32)
        out.append({"x": X, "y": X @ W})
    return out, W


def _make_slot_batches(n_batches, rows=512, tokens=120, seed=0):
    # one shared line pool: generation stays cheap, parse cost per batch
    # is rows*tokens*chars of pure-Python work (~130 ms)
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, 99999, (rows, tokens))
    lines = [" ".join(f"f{ids[r, j]}:{j}" for j in range(tokens))
             for r in range(rows)]
    return [{"slots": lines} for _ in range(n_batches)]


class TestProcessTrainerCorrectness:
    @pytest.mark.slow  # ~17s convergence soak; worker-error/arena/dead-
    # worker cases keep the mp machinery covered in-tier (CI heavy step)
    def test_two_process_regression_converges(self):
        from paddle1_tpu.distributed.fleet.process_trainer import (
            ProcessMultiTrainer)
        batches, W = _make_xy_batches(120)
        tr = ProcessMultiTrainer(process_num=2, publish_interval=2)
        out = tr.train_from_dataset(batches, _model_fn, _mse_loss,
                                    _optimizer_fn, batch_size=None)
        assert out["batches"] == 120
        assert out["updates"] == 120         # every grad applied once
        assert out["workers"] == 2
        # both workers actually trained
        assert all(s["batches"] > 0 for s in out["per_worker"].values())
        # the MASTER model converged to the generating weights
        from paddle1_tpu.core.tensor import to_tensor
        master = out["model"]
        X = np.random.default_rng(9).standard_normal(
            (64, 16)).astype(np.float32)
        pred = np.asarray(master(to_tensor(X)).numpy())
        mse = float(np.mean((pred - X @ W) ** 2))
        assert mse < 0.05, mse

    def test_worker_error_propagates(self):
        from paddle1_tpu.distributed.fleet.process_trainer import (
            ProcessMultiTrainer)
        batches, _ = _make_xy_batches(4)
        bad = [{"x": b["x"][:, :7], "y": b["y"]} for b in batches]  # shape
        tr = ProcessMultiTrainer(process_num=2)
        with pytest.raises(RuntimeError, match="hogwild worker"):
            tr.train_from_dataset(bad, _model_fn, _mse_loss,
                                  _optimizer_fn, batch_size=None)

    def test_arena_reset_barrier_under_pressure(self):
        """A small arena forces the drain-reset-republish path."""
        from paddle1_tpu.distributed.fleet.process_trainer import (
            ProcessMultiTrainer)
        batches, _ = _make_xy_batches(40, batch=64)
        tr = ProcessMultiTrainer(process_num=2, arena_size=1 << 18,
                                 publish_interval=2,
                                 arena_reset_fraction=0.4)
        out = tr.train_from_dataset(batches, _model_fn, _mse_loss,
                                    _optimizer_fn, batch_size=None)
        assert out["batches"] == 40
        assert out["updates"] == 40


class TestProcessTrainerThroughput:
    @pytest.mark.slow  # ~90s (3 interleaved rounds) and load-sensitive;
    # the scaling assertion runs on the CI heavy step where the box is
    # dedicated
    @pytest.mark.skipif(
        len(__import__("os").sched_getaffinity(0)) < 2,
        reason="throughput scaling needs >=2 CPU cores (this host has 1; "
               "the mechanism is exercised by the correctness tests, the "
               "scaling assertion runs on multi-core CI)")
    def test_two_processes_beat_one_on_slot_workload(self):
        """The point of process workers: GIL-bound slot parsing scales
        with processes.

        Scored as a best-of-N RATIO via ``bench_utils.best_of`` — this
        was the tier-1 suite's one chronic flake as a single-run
        wall-clock comparison: one multi-second scheduler stall landing
        on the 2-process run flipped the ratio. Interleaved rounds make
        both arms sample the same noise windows and the fastest round
        of each is the scaling signal."""
        from bench_utils import best_of
        from paddle1_tpu.distributed.fleet.process_trainer import (
            ProcessMultiTrainer)
        batches = _make_slot_batches(40)

        def run(n):
            def phase():
                tr = ProcessMultiTrainer(process_num=n)
                out = tr.train_from_dataset(batches, _model_fn,
                                            _slot_loss, _optimizer_fn,
                                            batch_size=None)
                assert out["batches"] == 40
            return phase

        one, two = best_of(3, run(1), run(2))
        speedup = one.best_s / two.best_s
        assert speedup > 1.2, (one.times, two.times, speedup)


def _exit_model_fn():
    import os
    if os.environ.get("P1T_HOGWILD_WORKER"):
        os._exit(3)  # dies before any error can be reported
    import paddle1_tpu as paddle
    return paddle.nn.Linear(16, 1)  # parent master builds fine


class TestDeadWorkerDetection:
    def test_silently_dead_worker_raises_not_hangs(self):
        from paddle1_tpu.distributed.fleet.process_trainer import (
            ProcessMultiTrainer)
        batches, _ = _make_xy_batches(4)
        tr = ProcessMultiTrainer(process_num=2)
        t0 = time.monotonic()
        with pytest.raises(RuntimeError, match="died without reporting"):
            tr.train_from_dataset(batches, _exit_model_fn, _mse_loss,
                                  _optimizer_fn, batch_size=None)
        assert time.monotonic() - t0 < 120


class TestExecutorEntry:
    """exe.train_from_dataset parity (reference executor.py:1113)."""

    def test_thread_route(self):
        import paddle1_tpu as paddle
        from paddle1_tpu.core.tensor import to_tensor
        from paddle1_tpu.static import Executor
        m = paddle.nn.Linear(4, 1)
        opt = paddle.optimizer.SGD(learning_rate=0.05,
                                   parameters=m.parameters())
        rng = np.random.default_rng(0)
        W = rng.standard_normal((4, 1)).astype(np.float32)
        data = []
        for _ in range(30):
            X = rng.standard_normal((8, 4)).astype(np.float32)
            data.append({"x": X, "y": X @ W})

        def loss_fn(b):
            d = m(to_tensor(b["x"])) - to_tensor(b["y"])
            return (d * d).mean()

        out = Executor().train_from_dataset(
            dataset=data, thread=2, loss_fn=loss_fn, optimizer=opt,
            batch_size=None)
        assert out["batches"] == 30

    def test_process_route(self):
        from paddle1_tpu.static import Executor
        batches, _ = _make_xy_batches(10)
        out = Executor().train_from_dataset(
            dataset=batches, process_num=2, model_fn=_model_fn,
            loss_fn=_mse_loss, optimizer_fn=_optimizer_fn,
            batch_size=None)
        assert out["batches"] == 10 and out["workers"] == 2

    def test_missing_args_teach(self):
        import pytest as _pytest
        from paddle1_tpu.core.errors import InvalidArgumentError
        from paddle1_tpu.static import Executor
        with _pytest.raises(InvalidArgumentError, match="loss_fn"):
            Executor().train_from_dataset(dataset=[1, 2])
        with _pytest.raises(InvalidArgumentError, match="picklable"):
            Executor().train_from_dataset(dataset=[1], process_num=2)


class TestTrainerDesc:
    """TrainerDesc/DeviceWorkerDesc factory parity (reference
    trainer_desc.proto + trainer_factory.cc)."""

    def test_routes_by_desc(self):
        import paddle1_tpu.distributed.fleet as fleet
        t = fleet.create_trainer(fleet.TrainerDesc(thread_num=3))
        assert isinstance(t, fleet.MultiTrainer) and t.thread_num == 3
        p = fleet.create_trainer(fleet.TrainerDesc(process_num=2))
        assert isinstance(p, fleet.ProcessMultiTrainer)
        assert p.process_num == 2

    def test_bad_worker_kind_teaches(self):
        import paddle1_tpu.distributed.fleet as fleet
        from paddle1_tpu.core.errors import InvalidArgumentError
        with pytest.raises(InvalidArgumentError, match="hogwild"):
            fleet.DeviceWorkerDesc("heter")
        with pytest.raises(InvalidArgumentError, match="PipelineParallel"):
            fleet.create_trainer(fleet.TrainerDesc(
                device_worker=fleet.DeviceWorkerDesc("section")))


class TestOrphanDetection:
    """PR 3 satellite: a worker whose leader died must exit promptly
    with a clear error instead of hanging on its queue gets (120s on
    the initial-param get, forever in the task loop)."""

    def test_dead_parent_raises_promptly(self, monkeypatch):
        import queue

        from paddle1_tpu.distributed.fleet import process_trainer as pt

        class _DeadParent:
            def is_alive(self):
                return False

        monkeypatch.setattr(pt.mp, "parent_process", lambda: _DeadParent())
        t0 = time.monotonic()
        with pytest.raises(RuntimeError, match="orphaned"):
            # timeout=None = the task-loop get that used to block forever
            pt._orphan_checked_get(queue.Queue(), None, "the next task")
        assert time.monotonic() - t0 < 10

    def test_finite_timeout_still_raises_empty(self):
        import queue

        from paddle1_tpu.distributed.fleet import process_trainer as pt

        # in the MAIN process parent_process() is None: no orphan check
        # applies and the plain-get timeout contract is preserved
        t0 = time.monotonic()
        with pytest.raises(queue.Empty):
            pt._orphan_checked_get(queue.Queue(), 0.2, "the initial params")
        dt = time.monotonic() - t0
        assert 0.15 < dt < 5

    def test_live_parent_delivers(self):
        import queue

        from paddle1_tpu.distributed.fleet import process_trainer as pt

        q = queue.Queue()
        q.put("payload")
        assert pt._orphan_checked_get(q, 5, "x") == "payload"
