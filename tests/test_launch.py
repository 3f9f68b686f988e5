"""Multi-node launch path: Cluster/Pod/Trainer model,
2-process rendezvous through jax.distributed, cross-process allreduce, and
fail-fast watch semantics. Reference launch_utils.py:58,141,452,559.

PR 3 adds the elastic supervision layer (distributed/supervisor): worker
heartbeats, hang detection with SIGABRT stack dumps, restart-from-
checkpoint and drain policies, worker-level chaos. The fast cases below
use plain-stdlib worker scripts (the heartbeat protocol is just a file
mtime) so they cost subprocess startup, not a jax import; the full
kill/restart training-parity soak is @slow (also `bench.py --elastic`).
"""

import os
import socket
import subprocess
import sys
import textwrap
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    p = s.getsockname()[1]
    s.close()
    return p


def _clean_env():
    env = {k: v for k, v in os.environ.items()
           if not k.startswith(("TPU_", "PADDLE_"))}
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    env["PYTHONPATH"] = REPO
    return env


class TestClusterModel:
    def test_get_cluster_two_hosts(self):
        from paddle1_tpu.distributed.launch_utils import get_cluster
        c = get_cluster(["10.0.0.1", "10.0.0.2"], 2, base_port=7000)
        assert c.world_size() == 4
        assert c.trainers_endpoints() == [
            "10.0.0.1:7000", "10.0.0.1:7001",
            "10.0.0.2:7000", "10.0.0.2:7001"]
        assert c.pod(1).trainers[0].rank == 2
        assert c.pod(1).addr == "10.0.0.2"

    def test_local_simulation_unique_ports(self):
        from paddle1_tpu.distributed.launch_utils import get_cluster
        c = get_cluster(["127.0.0.1", "127.0.0.1"], 2, base_port=7000)
        eps = c.trainers_endpoints()
        assert len(set(eps)) == 4  # every local rank gets its own port


WORKER_ALLREDUCE = textwrap.dedent("""
    import os, sys
    import numpy as np
    import paddle1_tpu.distributed as dist

    pe = dist.init_parallel_env()   # dials jax.distributed
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    assert jax.process_count() == 2, jax.process_count()
    devs = jax.devices()
    assert len(devs) == 2, devs     # 1 CPU device per process, global view

    rank = dist.get_rank()
    mesh = Mesh(np.array(devs), ("x",))
    sharding = NamedSharding(mesh, P("x"))
    local = np.full((1, 4), float(rank + 1), np.float32)
    garr = jax.make_array_from_process_local_data(sharding, local, (2, 4))
    summed = jax.jit(lambda a: jnp.sum(a, axis=0),
                     out_shardings=NamedSharding(mesh, P()))(garr)
    val = float(np.asarray(summed.addressable_shards[0].data)[0])
    print(f"RESULT rank={rank} endpoint="
          f"{os.environ['PADDLE_CURRENT_ENDPOINT']} sum={val}", flush=True)
    assert val == 3.0, val
""")

WORKER_ENGINE_DP = textwrap.dedent("""
    import os
    import numpy as np
    import paddle1_tpu.distributed as dist

    pe = dist.init_parallel_env()
    import jax
    import paddle1_tpu as paddle
    from paddle1_tpu.core.tensor import Tensor
    from paddle1_tpu.distributed import ParallelEngine, build_mesh

    rank = dist.get_rank()
    devs = jax.devices()
    assert len(devs) == 2

    # identical init on both ranks (fixed weights)
    lin = paddle.nn.Linear(4, 1)
    lin.weight._data = jax.numpy.asarray(
        np.arange(4, dtype=np.float32).reshape(4, 1) * 0.1)
    lin.bias._data = jax.numpy.zeros((1,), np.float32)
    opt = paddle.optimizer.SGD(learning_rate=0.1,
                               parameters=lin.parameters())

    def loss_fn(m, b):
        return ((m(Tensor(b["x"])) - Tensor(b["y"])) ** 2).mean()

    mesh = build_mesh(dp=2, devices=devs)
    engine = ParallelEngine(lin, opt, loss_fn, mesh=mesh, donate=False)

    # deterministic global batch [4, ...]; THIS process feeds rows
    # [2*rank : 2*rank+2] — its local data-parallel shard
    rng = np.random.default_rng(7)
    gx = rng.standard_normal((4, 4)).astype(np.float32)
    gy = rng.standard_normal((4, 1)).astype(np.float32)
    b = {"x": gx[2 * rank:2 * rank + 2], "y": gy[2 * rank:2 * rank + 2]}

    losses = [float(engine.step(b)) for _ in range(3)]
    print(f"ENGINE rank={rank} losses=" +
          ",".join(f"{l:.6f}" for l in losses), flush=True)
""")

WORKER_FAILFAST = textwrap.dedent("""
    import os, sys, time
    rank = int(os.environ["PADDLE_TRAINER_ID"])
    if rank == 1:
        sys.exit(7)
    time.sleep(300)   # rank 0 must be killed by the watcher
""")


class TestLauncher:
    def test_two_node_rendezvous_allreduce(self, tmp_path):
        worker = tmp_path / "worker.py"
        worker.write_text(WORKER_ALLREDUCE)
        logdir = tmp_path / "logs"
        port = _free_port()
        r = subprocess.run(
            [sys.executable, "-m", "paddle1_tpu.distributed.launch",
             "--nnodes", "2", "--nproc_per_node", "1",
             "--master", f"127.0.0.1:{port}",
             "--log_dir", str(logdir), str(worker)],
            env=_clean_env(), cwd=REPO, capture_output=True, timeout=300)
        logs = {i: (logdir / f"workerlog.{i}").read_text()
                for i in range(2)}
        assert r.returncode == 0, (r.stdout.decode(), r.stderr.decode(),
                                   logs)
        for i in range(2):
            assert f"RESULT rank={i}" in logs[i], logs
            assert "sum=3.0" in logs[i], logs
        # distinct endpoints per rank
        assert f":{port}" in logs[0] and f":{port + 1}" in logs[1]

    @pytest.mark.slow  # ~18s of double jax.distributed rendezvous; the
    # allreduce rendezvous test above keeps the two-node path in-tier
    # (CI heavy step runs this full training variant)
    def test_engine_dp_training_across_processes(self, tmp_path):
        """Full multi-host TRAINING path: 2 processes, each feeding its
        local dp shard into one ParallelEngine step over the global mesh;
        losses must agree across ranks AND match the single-process run
        on the concatenated batch."""
        worker = tmp_path / "worker.py"
        worker.write_text(WORKER_ENGINE_DP)
        logdir = tmp_path / "logs"
        port = _free_port()
        r = subprocess.run(
            [sys.executable, "-m", "paddle1_tpu.distributed.launch",
             "--nnodes", "2", "--nproc_per_node", "1",
             "--master", f"127.0.0.1:{port}",
             "--log_dir", str(logdir), str(worker)],
            env=_clean_env(), cwd=REPO, capture_output=True, timeout=300)
        logs = {i: (logdir / f"workerlog.{i}").read_text()
                for i in range(2)}
        assert r.returncode == 0, (r.stdout.decode(), r.stderr.decode(),
                                   logs)
        import re as _re
        per_rank = {}
        for i in range(2):
            m = _re.search(r"ENGINE rank=%d losses=([\d.,-]+)" % i,
                           logs[i])
            assert m, logs[i]
            per_rank[i] = [float(v) for v in m.group(1).split(",")]
        assert per_rank[0] == per_rank[1], per_rank  # replicated loss

        # single-process reference on the concatenated batch
        import numpy as np
        import jax.numpy as jnp
        import paddle1_tpu as paddle
        from paddle1_tpu.core.tensor import Tensor
        from paddle1_tpu.distributed import ParallelEngine, build_mesh
        import jax
        lin = paddle.nn.Linear(4, 1)
        lin.weight._data = jnp.asarray(
            np.arange(4, dtype=np.float32).reshape(4, 1) * 0.1)
        lin.bias._data = jnp.zeros((1,), np.float32)
        opt = paddle.optimizer.SGD(learning_rate=0.1,
                                   parameters=lin.parameters())
        rng = np.random.default_rng(7)
        gx = rng.standard_normal((4, 4)).astype(np.float32)
        gy = rng.standard_normal((4, 1)).astype(np.float32)
        engine = ParallelEngine(
            lin, opt, lambda m, b: ((m(Tensor(b["x"])) - Tensor(b["y"]))
                                    ** 2).mean(),
            mesh=build_mesh(dp=1, devices=jax.devices()[:1]), donate=False)
        ref = [float(engine.step({"x": gx, "y": gy})) for _ in range(3)]
        np.testing.assert_allclose(per_rank[0], ref, rtol=2e-4)

    def test_fail_fast_kills_pod(self, tmp_path):
        worker = tmp_path / "worker.py"
        worker.write_text(WORKER_FAILFAST)
        port = _free_port()
        t0 = time.time()
        r = subprocess.run(
            [sys.executable, "-m", "paddle1_tpu.distributed.launch",
             "--nnodes", "2", "--nproc_per_node", "1",
             "--master", f"127.0.0.1:{port}", str(worker)],
            env=_clean_env(), cwd=REPO, capture_output=True, timeout=120)
        dt = time.time() - t0
        assert r.returncode == 7, (r.returncode, r.stderr.decode())
        assert dt < 60, f"watcher failed to kill the sleeping rank ({dt}s)"


# -- elastic supervision (PR 3) ---------------------------------------------
# plain-stdlib workers: the heartbeat protocol is file mtime + the
# PADDLE_FT_* env vars, so supervision logic tests don't pay a jax import

BEATER = textwrap.dedent("""
    import os, sys, time
    hb = os.environ["PADDLE_FT_HEARTBEAT_FILE"]
    rank = int(os.environ.get("RANK", "0"))
    if rank == 1 and os.environ.get("RANK1_EXIT"):
        sys.exit(int(os.environ["RANK1_EXIT"]))
    for _ in range(3000):
        os.utime(hb, None)
        time.sleep(0.02)
""")

RESTART_RESUME = textwrap.dedent("""
    import os, sys, time
    hb = os.environ["PADDLE_FT_HEARTBEAT_FILE"]
    inc = int(os.environ["PADDLE_FT_WORKER_INCARNATION"])
    state = os.environ["STATE_FILE"]  # stands in for a checkpoint
    start = int(open(state).read()) if os.path.exists(state) else 0
    for step in range(start, 10):
        os.utime(hb, None)
        open(state, "w").write(str(step + 1))
        if inc == 0 and step == 4 and not os.environ.get("ALWAYS_DIE"):
            sys.exit(3)
        if os.environ.get("ALWAYS_DIE") and step == start + 2:
            sys.exit(3)   # deterministic fault: dies in EVERY life
        time.sleep(0.02)
""")

HANG_AFTER_3 = textwrap.dedent("""
    import faulthandler, os, time
    hb = os.environ["PADDLE_FT_HEARTBEAT_FILE"]
    faulthandler.enable(
        file=open(os.environ["PADDLE_FT_STACKDUMP_FILE"], "w"),
        all_threads=True)
    for _ in range(3):
        os.utime(hb, None)
        time.sleep(0.05)
    time.sleep(600)   # the wedge: stops beating, never exits
""")

DRAINER = textwrap.dedent("""
    import os, signal, sys, time
    hb = os.environ["PADDLE_FT_HEARTBEAT_FILE"]
    rank = int(os.environ.get("RANK", "0"))
    def on_term(s, f):   # "checkpoint" on the drain SIGTERM, exit clean
        open(os.environ["DRAIN_FILE"] + str(rank), "w").write("saved")
        sys.exit(0)
    signal.signal(signal.SIGTERM, on_term)
    for i in range(3000):
        os.utime(hb, None)
        if rank == 0 and i == 5:
            with open(hb + ".unhealthy", "w") as f:
                f.write("simulated sick worker")
        time.sleep(0.02)
""")


def _sup(tmp_path, **kw):
    from paddle1_tpu.distributed import Supervisor
    kw.setdefault("poll_s", 0.05)
    kw.setdefault("grace_s", 3.0)
    kw.setdefault("hang_timeout", 5.0)
    kw.setdefault("heartbeat_dir", str(tmp_path / "hb"))
    return Supervisor(**kw)


def _worker_file(tmp_path, body, name="worker.py"):
    p = tmp_path / name
    p.write_text(body)
    return str(p)


class TestSupervisor:
    def test_fail_fast_kills_pod_on_worker_exit(self, tmp_path):
        """Supervised fail_fast preserves watch_local_trainers
        semantics: rank 1 exits 7, rank 0 (alive and beating) is
        killed, the pod returns 7."""
        w = _worker_file(tmp_path, BEATER)
        sup = _sup(tmp_path, policy="fail_fast")
        for r in range(2):
            env = dict(os.environ, RANK=str(r), RANK1_EXIT="7")
            sup.add_worker(r, [sys.executable, "-u", w], env=env)
        t0 = time.time()
        assert sup.run() == 7
        assert time.time() - t0 < 30
        assert sup.report.failures[0].kind == "exit"

    def test_restart_policy_resumes_and_converges(self, tmp_path):
        """A rank SIGKILL-able worker dies mid-run (incarnation 0);
        restart relaunches it with the same env and it RESUMES from
        its persisted state (the checkpoint stand-in) and finishes."""
        w = _worker_file(tmp_path, RESTART_RESUME)
        state = tmp_path / "state"
        sup = _sup(tmp_path, policy="restart", max_restarts=2)
        sup.add_worker(0, [sys.executable, "-u", w],
                       env=dict(os.environ, STATE_FILE=str(state)),
                       log_path=str(tmp_path / "log.0"))
        assert sup.run() == 0
        assert sup.report.total_restarts == 1
        assert int(state.read_text()) == 10  # resumed 5..10, not 0..10

    @pytest.mark.slow  # tier-1 time budget: the core restart/hang/
    # drain/CLI cases above cover the policy matrix; these variants
    # ride the CI launcher-smoke step instead
    def test_restart_budget_exhausted_fails_pod(self, tmp_path):
        w = _worker_file(tmp_path, RESTART_RESUME)
        sup = _sup(tmp_path, policy="restart", max_restarts=1)
        sup.add_worker(0, [sys.executable, "-u", w],
                       env=dict(os.environ, ALWAYS_DIE="1",
                                STATE_FILE=str(tmp_path / "state")))
        assert sup.run() == 3      # deterministic fault: budget runs out
        assert sup.report.total_restarts == 1

    def test_hang_detected_within_timeout_and_stack_dumped(self, tmp_path):
        """A worker that stops beating is declared hung within
        ft_hang_timeout, SIGABRT'd for a faulthandler stack dump, and
        the pod fails instead of blocking forever."""
        w = _worker_file(tmp_path, HANG_AFTER_3)
        sup = _sup(tmp_path, policy="fail_fast", hang_timeout=1.0,
                   startup_grace_s=3.0, dump_wait_s=3.0)
        sup.add_worker(0, [sys.executable, "-u", w])
        t0 = time.time()
        assert sup.run() != 0
        assert time.time() - t0 < 20  # NOT the 600s the worker sleeps
        assert sup.report.hangs_detected == 1
        assert sup.report.failures[0].kind == "hang"
        assert sup.report.stack_dumps
        dump = open(sup.report.stack_dumps[0]).read()
        assert "time.sleep" in dump or "File" in dump, dump[:300]

    @pytest.mark.slow  # see test_restart_budget_exhausted_fails_pod
    def test_hung_rank_restarts(self, tmp_path):
        """restart policy also covers hangs: kill the wedged rank,
        relaunch, finish (second incarnation = RESTART_RESUME path)."""
        w = _worker_file(tmp_path, textwrap.dedent("""
            import os, sys, time
            hb = os.environ["PADDLE_FT_HEARTBEAT_FILE"]
            if int(os.environ["PADDLE_FT_WORKER_INCARNATION"]) == 0:
                os.utime(hb, None)
                time.sleep(600)   # wedge in the first life
            for _ in range(3):
                os.utime(hb, None)
                time.sleep(0.02)
        """))
        sup = _sup(tmp_path, policy="restart", max_restarts=1,
                   hang_timeout=0.8, startup_grace_s=2.0, dump_wait_s=2.0)
        sup.add_worker(0, [sys.executable, "-u", w])
        assert sup.run() == 0
        assert sup.report.hangs_detected == 1
        assert sup.report.total_restarts == 1

    def test_drain_checkpoints_every_worker(self, tmp_path):
        """An unhealthy report under drain: every rank gets the
        graceful SIGTERM, "checkpoints" (drain file), exits clean; the
        pod stops with rc 0 and report.drained."""
        w = _worker_file(tmp_path, DRAINER)
        sup = _sup(tmp_path, policy="drain")
        for r in range(2):
            env = dict(os.environ, RANK=str(r),
                       DRAIN_FILE=str(tmp_path / "drained."))
            sup.add_worker(r, [sys.executable, "-u", w], env=env)
        assert sup.run() == 0
        assert sup.report.drained
        assert sup.report.unhealthy_reports == 1
        assert (tmp_path / "drained.0").exists()
        assert (tmp_path / "drained.1").exists()

    @pytest.mark.slow  # see test_restart_budget_exhausted_fails_pod
    def test_unhealthy_report_restarts_rank(self, tmp_path):
        """Explicit unhealthy report under restart policy relaunches
        just that rank (second life takes the clean path)."""
        w = _worker_file(tmp_path, textwrap.dedent("""
            import os, time
            hb = os.environ["PADDLE_FT_HEARTBEAT_FILE"]
            first = int(os.environ["PADDLE_FT_WORKER_INCARNATION"]) == 0
            for i in range(4):
                os.utime(hb, None)
                if first and i == 2:
                    with open(hb + ".unhealthy", "w") as f:
                        f.write("broken")
                    time.sleep(60)   # sick: waits for the supervisor
                time.sleep(0.02)
        """))
        sup = _sup(tmp_path, policy="restart", max_restarts=1)
        sup.add_worker(0, [sys.executable, "-u", w])
        assert sup.run() == 0
        assert sup.report.unhealthy_reports == 1
        assert sup.report.total_restarts == 1


class TestSupervisedLaunchCLI:
    def test_launch_ft_supervise_restart_smoke(self, tmp_path):
        """The launcher end-to-end with --ft_supervise restart: the
        worker dies once mid-run, the supervisor relaunches it (same
        env), the relaunch resumes from its state file, rc 0. Also
        covers the no-execve single-proc supervised path."""
        worker = tmp_path / "worker.py"
        worker.write_text(RESTART_RESUME)
        env = _clean_env()
        env["STATE_FILE"] = str(tmp_path / "state")
        r = subprocess.run(
            [sys.executable, "-m", "paddle1_tpu.distributed.launch",
             "--ft_supervise", "restart", "--ft_max_worker_restarts", "2",
             "--log_dir", str(tmp_path / "logs"), str(worker)],
            env=env, cwd=REPO, capture_output=True, timeout=300)
        assert r.returncode == 0, (r.stdout.decode(), r.stderr.decode())
        assert b"relaunched" in r.stderr
        assert (tmp_path / "state").read_text() == "10"
        # the restarted rank's log APPENDS across incarnations
        log = (tmp_path / "logs" / "workerlog.0").read_text()
        assert "supervisor restart #1" in log


@pytest.mark.slow
class TestElasticTrainingParity:
    def test_kill_restart_final_param_parity(self):
        """The acceptance gate: a run whose worker is SIGKILLed
        mid-training (worker_kill chaos) and auto-restarted by the
        Supervisor produces final params equal to the uninterrupted
        run at 1e-6 (resume via ResilientTrainer.restore_latest)."""
        sys.path.insert(0, REPO)
        from bench import bench_elastic_soak
        bench_elastic_soak(on_tpu=False)  # raises unless parity holds


WORKER_PS = textwrap.dedent("""
    import os
    import numpy as np
    import jax
    jax.config.update("jax_platforms", "cpu")
    import paddle1_tpu as paddle
    import paddle1_tpu.distributed.fleet as fleet

    role = os.environ["TRAINING_ROLE"]
    if role == "PSERVER":
        fleet.init()
        fleet.fleet.init_server(dim=4)
        print("SERVER UP", os.environ["PADDLE_PORT"], flush=True)
        fleet.fleet.run_server()
    else:
        import time
        from paddle1_tpu.distributed import DistributedEmbedding, ps_server
        eps = os.environ["PADDLE_PSERVERS_IP_PORT_LIST"].split(",")
        svc = None
        for _ in range(60):   # wait for servers to bind
            try:
                svc = ps_server.remote_service(4, eps)
                break
            except OSError:
                time.sleep(0.5)
        assert svc is not None, "servers never came up"
        emb = DistributedEmbedding(svc)
        rank = int(os.environ["PADDLE_TRAINER_ID"])
        ids = np.array([0, 1, 2, 3]) + 4 * rank
        first = None
        for _ in range(20):
            v = emb(ids)
            loss = (v * v).mean()
            loss.backward()
            first = first if first is not None else float(loss.numpy())
        print(f"PSTRAIN rank={rank} first={first:.8f} "
              f"last={float(loss.numpy()):.8f}", flush=True)
        assert float(loss.numpy()) <= first
""")


class TestLauncherPSMode:
    def test_ps_job_one_server_two_trainers(self, tmp_path):
        worker = tmp_path / "worker.py"
        worker.write_text(WORKER_PS)
        logdir = tmp_path / "logs"
        port = _free_port()
        r = subprocess.run(
            [sys.executable, "-m", "paddle1_tpu.distributed.launch",
             "--server_num", "1", "--trainer_num", "2",
             "--master", f"127.0.0.1:{port}",
             "--log_dir", str(logdir), str(worker)],
            env=_clean_env(), cwd=REPO, capture_output=True, timeout=300)
        assert r.returncode == 0, (r.stdout.decode(), r.stderr.decode())
        slog = (logdir / "serverlog.0").read_text()
        tlogs = {i: (logdir / f"workerlog.{i}").read_text()
                 for i in range(2)}
        assert "SERVER UP" in slog
        for i in range(2):
            assert f"PSTRAIN rank={i}" in tlogs[i], tlogs
