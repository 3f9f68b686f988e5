"""CPU acceptance gates and soaks: fault injection, elasticity, the
input pipeline, serving, generation, the recommender, observability and
the cost observatory, one mode flag each (``python bench.py`` with no
mode lists them). Each mode prints JSON result lines that
``tools/bench_history.py`` reads. None of them is the speed benchmark:
that is ``python3 -m benchmarks.run`` (BENCHMARK.json; PERF.md
section 2), which the driver runs on the chip.

Runs on the backend JAX gives it and records that device (platform, kind,
count) in its JSON line. There is no probe child and no CPU fallback: on a
non-TPU backend it runs only when started with ``JAX_PLATFORMS=cpu`` (a
test's count run, whose metric name is suffixed ``@cpu_count_run``), and
fails otherwise.
"""

import argparse
import json
import statistics
import sys
import time

import numpy as np


def _peak_flops(device) -> float:
    """bf16 peak FLOP/s per chip by device kind — delegated to
    obs.costmodel's table (ISSUE 13): the bench's analytic MFU and the
    engine's cost-model MFU must divide by the SAME peak or the 15%
    cross-check would measure table drift, not attribution quality."""
    from paddle1_tpu.obs.costmodel import device_peak_flops
    return device_peak_flops(device)


def _read_back(x):
    """Fetch a result to host memory — a completion barrier that cannot
    return before the device has produced the value."""
    import jax
    for leaf in jax.tree_util.tree_leaves(x.data if hasattr(x, "data")
                                          else x):
        np.asarray(jax.device_get(leaf))


def _timed_steps(step_fn, n_steps):
    """Slope-timed stepping; returns (per-step-seconds estimates, last
    result).

    Wall-times a window of k chained steps ending in a host readback, for
    k = lo and k = n_steps, three trials; each trial contributes the slope
    (T_hi - T_lo)/(hi - lo). The readback cost and any fixed dispatch
    latency are identical in both windows and cancel; the
    dependency chain through the updated params serializes the steps on
    device, so the slope is the true per-step cost."""
    n_steps = max(2, n_steps)  # the slope needs two distinct window sizes
    lo = max(1, n_steps // 4)
    slopes, out = [], None
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(lo):
            out = step_fn()
        _read_back(out)
        t_lo = time.perf_counter() - t0
        t0 = time.perf_counter()
        for _ in range(n_steps):
            out = step_fn()
        _read_back(out)
        t_hi = time.perf_counter() - t0
        if t_hi > t_lo:
            slopes.append((t_hi - t_lo) / (n_steps - lo))
        # else: noise made the long window "faster" — reject the trial
        # rather than fabricate a number (honesty contract)
    if not slopes:
        raise AssertionError(
            "slope timing rejected all trials (t_hi <= t_lo every time): "
            "host too noisy for these window sizes — raise n_steps")
    return slopes, out


def _emit(metric, value, unit, vs_baseline, detail):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        # a CPU run counts and gates; its number is never a device metric
        metric += "@cpu_count_run"
    rec = {"metric": metric, "value": round(value, 2),
           "unit": unit, "vs_baseline": round(vs_baseline, 4),
           "device": {"platform": devs[0].platform,
                      "kind": devs[0].device_kind, "count": len(devs)},
           "detail": detail}
    print(json.dumps(rec))
    return rec


_RESULT_KEYS = ("metric", "value", "unit", "vs_baseline", "detail")


def parse_result_line(line):
    """Parse one bench JSON result line back into a dict, validating the
    schema the driver (and the tier-1 harness test) rely on. Raises
    ValueError on anything that is not a well-formed result line."""
    rec = json.loads(line)
    if not isinstance(rec, dict):
        raise ValueError(f"bench line is not an object: {line!r}")
    missing = [k for k in _RESULT_KEYS if k not in rec]
    if missing:
        raise ValueError(f"bench line missing keys {missing}: {line!r}")
    if not isinstance(rec["detail"], dict):
        raise ValueError("bench detail must be an object")
    return rec


def bench_chaos_soak(on_tpu, steps_override=None):
    """``--chaos``: fault-injection soak of the resilient runtime.

    Runs the same tiny-MLP training twice — once clean, once through
    ``ResilientTrainer`` with a poisoned batch, an injected
    checkpoint-write failure and a simulated preemption — and reports
    recovered throughput. ``vs_baseline`` is the recovery contract
    itself: 1.0 iff the chaos run's final params match the clean run to
    1e-6 AND the trainer's counters account for every injected fault.
    """
    import os
    import shutil
    import tempfile

    import jax
    import paddle1_tpu as paddle
    from paddle1_tpu.core import chaos
    from paddle1_tpu.core.tensor import Tensor
    from paddle1_tpu.distributed import (ParallelEngine, ResilientTrainer,
                                         build_mesh)

    steps = steps_override or (50 if on_tpu else 12)
    save_freq = max(steps // 6, 1)
    rng = np.random.default_rng(0)
    batches = [{"x": rng.standard_normal((8, 16)).astype(np.float32),
                "y": rng.standard_normal((8, 4)).astype(np.float32)}
               for _ in range(steps)]

    def make_engine():
        paddle.seed(0)
        model = paddle.nn.Sequential(
            paddle.nn.Linear(16, 32), paddle.nn.ReLU(),
            paddle.nn.Linear(32, 4))
        for i, p in enumerate(model.parameters()):
            p._data = jax.numpy.asarray(
                np.random.default_rng(7 + i)
                .standard_normal(p.shape).astype(np.float32) * 0.1)
        opt = paddle.optimizer.AdamW(learning_rate=1e-2,
                                     parameters=model.parameters())
        loss_fn = lambda m, b: \
            ((m(Tensor(b["x"])) - Tensor(b["y"])) ** 2).mean()
        mesh = build_mesh(dp=1, devices=jax.devices()[:1])
        return ParallelEngine(model, opt, loss_fn, mesh=mesh,
                              check_finite=True)

    tmp = tempfile.mkdtemp(prefix="p1t_chaos_")
    try:
        # clean reference run
        chaos.reset()
        clean = ResilientTrainer(make_engine(), os.path.join(tmp, "clean"),
                                 save_freq=save_freq,
                                 bad_step_policy="restore_last_good",
                                 backoff_base_s=0.0)
        clean.fit(lambda: list(batches), steps=steps)
        clean_params = {k: np.asarray(v)
                        for k, v in clean.engine.params.items()}

        # chaos run: NaN batch + failed checkpoint write + preemption
        chaos.configure(f"nan_batch@{save_freq + 1},ckpt_fail@2,"
                        f"preempt@{min(2 * save_freq + 1, steps)}")
        trainer = ResilientTrainer(make_engine(), os.path.join(tmp, "run"),
                                   save_freq=save_freq,
                                   bad_step_policy="restore_last_good",
                                   backoff_base_s=0.0)
        t0 = time.perf_counter()
        report = trainer.fit(lambda: list(batches), steps=steps)
        dt = time.perf_counter() - t0

        max_err = max(
            float(np.max(np.abs(clean_params[k] -
                                np.asarray(trainer.engine.params[k]))))
            for k in clean_params)
        recovered = (max_err <= 1e-6 and report.bad_steps >= 1
                     and report.retries >= 1 and report.preemptions >= 1
                     and report.restores >= 2)
        detail = dict(report.as_dict(), steps=steps, save_freq=save_freq,
                      max_param_err=max_err, elapsed_s=round(dt, 3),
                      device=getattr(jax.devices()[0], "device_kind",
                                     jax.devices()[0].platform))
        _emit("chaos_soak_recovered_steps_per_sec", steps / dt, "steps/s",
              1.0 if recovered else 0.0, detail)
        if not recovered:
            raise AssertionError(
                f"chaos soak did NOT recover: {json.dumps(detail)}")
    finally:
        chaos.reset()  # a failing soak must not leave faults armed
        shutil.rmtree(tmp, ignore_errors=True)


_ELASTIC_WORKER = '''\
"""bench --elastic worker: deterministic tiny-MLP training through
ResilientTrainer (checkpoints + resume), final params to npz."""
import os
import numpy as np
import jax
jax.config.update("jax_platforms", "cpu")
import paddle1_tpu as paddle
from paddle1_tpu.core.tensor import Tensor
from paddle1_tpu.distributed import (ParallelEngine, ResilientTrainer,
                                     build_mesh)

steps = int(os.environ["P1T_ELASTIC_STEPS"])
save_freq = int(os.environ["P1T_ELASTIC_SAVE_FREQ"])
paddle.seed(0)
model = paddle.nn.Sequential(
    paddle.nn.Linear(16, 32), paddle.nn.ReLU(), paddle.nn.Linear(32, 4))
for i, p in enumerate(model.parameters()):
    p._data = jax.numpy.asarray(
        np.random.default_rng(7 + i)
        .standard_normal(p.shape).astype(np.float32) * 0.1)
opt = paddle.optimizer.AdamW(learning_rate=1e-2,
                             parameters=model.parameters())
loss_fn = lambda m, b: ((m(Tensor(b["x"])) - Tensor(b["y"])) ** 2).mean()
engine = ParallelEngine(model, opt, loss_fn,
                        mesh=build_mesh(dp=1, devices=jax.devices()[:1]),
                        check_finite=True)
rng = np.random.default_rng(0)
batches = [{"x": rng.standard_normal((8, 16)).astype(np.float32),
            "y": rng.standard_normal((8, 4)).astype(np.float32)}
           for _ in range(steps)]
trainer = ResilientTrainer(engine, os.environ["P1T_ELASTIC_CKPT"],
                           save_freq=save_freq,
                           bad_step_policy="restore_last_good",
                           backoff_base_s=0.0)
report = trainer.fit(lambda: list(batches), steps=steps)
np.savez(os.environ["P1T_ELASTIC_OUT"],
         **{k.replace("/", "__"): np.asarray(v)
            for k, v in engine.params.items()})
print(f"ELASTIC final_step={report.final_step} "
      f"resumed_from={report.resumed_from}", flush=True)
'''


def bench_elastic_soak(on_tpu, steps_override=None):
    """``--elastic``: supervised kill-and-restart soak of the launcher.

    Trains the same deterministic tiny MLP twice under the Supervisor —
    once clean, once with ``worker_kill`` chaos SIGKILLing the worker
    mid-run (policy ``restart``: the supervisor relaunches the rank,
    which resumes from its last committed checkpoint). ``vs_baseline``
    is the elastic recovery contract: 1.0 iff the killed-and-restarted
    run's final params match the clean run to 1e-6 AND exactly one
    restart was performed.
    """
    import os
    import shutil
    import sys as _sys
    import tempfile

    from paddle1_tpu.distributed import Supervisor

    steps = steps_override or 12
    if steps < 4:
        raise SystemExit(
            f"--elastic needs --steps >= 4 (got {steps}): the kill is "
            "armed past a mid-run checkpoint commit and must land "
            "before the run ends")
    save_freq = max(steps // 6, 1)
    # worker_kill counts health BEATS, and ResilientTrainer beats ~3x
    # per step (loop + dispatch-retry + readback-retry) plus 2 per save
    # — aim for mid-run so the kill lands PAST mid-run commits and well
    # before the end; the resumed_from assertion below keeps this gate
    # honest if the per-step beat count ever changes
    kill_beat = (3 * steps + 2 * (steps // save_freq) + 2) // 2
    tmp = tempfile.mkdtemp(prefix="p1t_elastic_")
    worker_py = os.path.join(tmp, "worker.py")
    with open(worker_py, "w") as f:
        f.write(_ELASTIC_WORKER)

    def run_supervised(tag, chaos_spec):
        env = dict(os.environ)
        env.pop("FLAGS_ft_chaos", None)
        repo = os.path.dirname(os.path.abspath(__file__))
        env.update({
            # the worker script lives in the tmp dir: python puts the
            # script's dir (not our cwd) on sys.path
            "PYTHONPATH": repo + os.pathsep + env.get("PYTHONPATH", ""),
            "JAX_PLATFORMS": "cpu",
            "P1T_ELASTIC_STEPS": str(steps),
            "P1T_ELASTIC_SAVE_FREQ": str(save_freq),
            "P1T_ELASTIC_CKPT": os.path.join(tmp, tag, "ckpts"),
            "P1T_ELASTIC_OUT": os.path.join(tmp, tag, "params.npz"),
        })
        if chaos_spec:
            env["FLAGS_ft_chaos"] = chaos_spec
        os.makedirs(os.path.join(tmp, tag), exist_ok=True)
        sup = Supervisor(policy="restart", max_restarts=2,
                         heartbeat_dir=os.path.join(tmp, tag, "hb"),
                         poll_s=0.2, grace_s=5.0)
        sup.add_worker(0, [_sys.executable, "-u", worker_py], env=env,
                       log_path=os.path.join(tmp, tag, "workerlog.0"))
        rc = sup.run()
        log = open(os.path.join(tmp, tag, "workerlog.0")).read()
        if rc != 0:
            raise AssertionError(
                f"elastic soak {tag} run failed rc={rc}: {log[-2000:]}")
        import re
        m = re.findall(r"resumed_from=(\S+)", log)
        resumed_from = (int(m[-1]) if m and m[-1] != "None" else None)
        out = np.load(os.path.join(tmp, tag, "params.npz"))
        return {k: out[k] for k in out.files}, sup.report, resumed_from

    try:
        t0 = time.perf_counter()
        clean, _, _ = run_supervised("clean", "")
        faulted, report, resumed_from = run_supervised(
            "kill", f"worker_kill@{kill_beat}:0")
        dt = time.perf_counter() - t0
        max_err = max(float(np.max(np.abs(clean[k] - faulted[k])))
                      for k in clean)
        # resumed_from >= save_freq proves the restarted worker picked
        # up a MID-RUN commit (a step-0-baseline resume replays the
        # whole run and would pass parity trivially)
        recovered = (max_err <= 1e-6 and report.total_restarts == 1
                     and resumed_from is not None
                     and resumed_from >= save_freq)
        detail = dict(report.as_dict(), steps=steps, save_freq=save_freq,
                      kill_beat=kill_beat, resumed_from=resumed_from,
                      max_param_err=max_err, elapsed_s=round(dt, 3))
        _emit("elastic_soak_recovered_steps_per_sec", steps / dt,
              "steps/s", 1.0 if recovered else 0.0, detail)
        if not recovered:
            raise AssertionError(
                f"elastic soak did NOT recover: {json.dumps(detail)}")
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


_RESIZE_WORKER = '''\
"""bench --elastic-resize worker: a single-controller fleet — one host
process driving a W-virtual-device CPU mesh, W handed down by the
Supervisor's resize env overlay (PADDLE_ELASTIC_WORLD). Each life
recomputes its mesh from the latest checkpoint's manifest descriptor
via topology.plan_resize, so param/optimizer state arrives through the
manifest-driven resharding load path."""
import os
import time

W = int(os.environ["PADDLE_ELASTIC_WORLD"])
os.environ["XLA_FLAGS"] = (
    f"--xla_force_host_platform_device_count={W}")  # before jax import
import numpy as np
import jax
jax.config.update("jax_platforms", "cpu")
import paddle1_tpu as paddle
from paddle1_tpu.core.tensor import Tensor
from paddle1_tpu.distributed import (ParallelEngine, ResilientTrainer,
                                     build_mesh, plan_resize)
from paddle1_tpu.distributed import checkpoint as ckpt_mod
from paddle1_tpu.io import DataLoader, Dataset, DistributedBatchSampler

steps = int(os.environ["P1T_RESIZE_STEPS"])
save_freq = int(os.environ["P1T_RESIZE_SAVE_FREQ"])
G = int(os.environ["P1T_RESIZE_GLOBAL_BATCH"])
ck_dir = os.environ["P1T_RESIZE_CKPT"]
pace_s = float(os.environ.get("P1T_RESIZE_PACE_S", "0"))
inc = int(os.environ.get("PADDLE_FT_WORKER_INCARNATION", "0"))
assert len(jax.devices()) == W, (W, jax.devices())

paddle.seed(0)
model = paddle.nn.Sequential(
    paddle.nn.Linear(16, 48), paddle.nn.ReLU(), paddle.nn.Linear(48, 4))
for i, p in enumerate(model.parameters()):
    p._data = jax.numpy.asarray(
        np.random.default_rng(7 + i)
        .standard_normal(p.shape).astype(np.float32) * 0.1)
opt = paddle.optimizer.AdamW(learning_rate=1e-2,
                             parameters=model.parameters())
loss_fn = lambda m, b: ((m(Tensor(b["x"])) - Tensor(b["y"])) ** 2).mean()

# the elastic mesh: recomputed from the LATEST commit's manifest
# descriptor — the saved dp/sharding degrees remap onto the new world
latest = ckpt_mod.latest_step(ck_dir)
saved_mesh = (ckpt_mod.manifest_mesh(os.path.join(ck_dir, str(latest)))
              if latest is not None else None)
degrees = (plan_resize(saved_mesh, W) if saved_mesh is not None
           else {"sharding": W})
engine = ParallelEngine(model, opt, loss_fn, mesh=build_mesh(**degrees),
                        zero_stage=3, check_finite=True)


class _Synth(Dataset):
    """sample i -> deterministic (x, y); the sleep paces the run so
    mid-run membership events land deterministically."""

    def __len__(self):
        return (steps + 4) * G

    def __getitem__(self, i):
        if pace_s:
            time.sleep(pace_s)
        r = np.random.default_rng(1000 + i)
        return {"x": r.standard_normal(16).astype(np.float32),
                "y": r.standard_normal(4).astype(np.float32)}


ds = _Synth()
# world-invariant global stream: batch-major elastic layout, this host
# drives every mesh device so it consumes the whole global batch
sampler = DistributedBatchSampler(ds, batch_size=G // W, num_replicas=W,
                                  rank="all", shuffle=True, elastic=True)
loader = DataLoader(ds, batch_sampler=sampler)
trainer = ResilientTrainer(engine, ck_dir, save_freq=save_freq,
                           bad_step_policy="restore_last_good",
                           backoff_base_s=0.0)
report = trainer.fit(lambda: loader, steps=steps)
np.savez(os.environ["P1T_RESIZE_OUT"],
         **{k.replace("/", "__"): np.asarray(v)
            for k, v in engine.params.items()})
print(f"RESIZE life={inc} world={W} final_step={report.final_step} "
      f"resumed_from={report.resumed_from} "
      f"resharded={report.resharded_restores} "
      f"loader_resume={report.loader_resume} "
      f"consumed={loader.batches_consumed}", flush=True)
'''


def bench_elastic_resize(on_tpu, steps_override=None):
    """``--elastic-resize``: live 8→6→8 world-resize soak.

    Trains the same deterministic MLP twice under a ``resize``-policy
    Supervisor over an elastic single-controller fleet (one process
    driving a W-device CPU mesh, params + AdamW moments ZeRO-3-sharded
    W ways):

    * **clean** — fixed world 8, uninterrupted;
    * **resize** — ``worker_kill`` chaos SIGKILLs the fleet mid-run
      (an ungraceful preemption of 2 of the 8 "hosts"): the Supervisor
      shrinks to 6 — the relaunched life recomputes its mesh via
      ``plan_resize`` from the checkpoint manifest and restores through
      the resharding load path, resuming from a mid-run commit. Once
      the shrunken world commits past the grow mark, the bench calls
      ``request_resize(8)`` ("capacity returned"): survivors drain
      (graceful final commit), and the grown life reshards 6→8 and
      finishes.

    ``vs_baseline`` is the elasticity contract: 1.0 iff final params
    match the clean run to 1e-6 (the global batch is fixed, so the
    optimizer trajectory is world-size-invariant), both resized lives
    restored via the RESHARDING path, the kill resumed from a commit
    ``>= save_freq``, and sample accounting is exactly-once across the
    graceful resize (the grown life resumes at exactly the step the
    drained life committed, through the O(1) loader-state restore).
    """
    import os
    import re
    import shutil
    import sys as _sys
    import tempfile
    import threading

    from paddle1_tpu.distributed import Supervisor
    from paddle1_tpu.distributed import checkpoint as ckpt_mod

    steps = steps_override or 30
    if steps < 12:
        raise SystemExit(
            f"--elastic-resize needs --steps >= 12 (got {steps}): the "
            "kill, the shrunken-world commits and the grow must all "
            "land inside the run")
    save_freq = max(steps // 6, 1)
    grow_step = (2 * steps // 3) // save_freq * save_freq
    # worker_kill counts health BEATS (~3/step + 2/save); land the kill
    # around steps//3 — past mid-run commits, well before grow_step
    kill_step = max(steps // 3, save_freq + 1)
    kill_beat = 3 * kill_step + 2 * (kill_step // save_freq) + 2
    world, shrink_by = 8, 2
    tmp = tempfile.mkdtemp(prefix="p1t_resize_")
    worker_py = os.path.join(tmp, "worker.py")
    with open(worker_py, "w") as f:
        f.write(_RESIZE_WORKER)

    def run_supervised(tag, chaos_spec, with_grow):
        env = dict(os.environ)
        env.pop("FLAGS_ft_chaos", None)
        env.pop("XLA_FLAGS", None)  # the worker pins its own device count
        # The one cache directory made from a temporary name: this soak
        # counts cold against warm compiles across the lives of its CPU
        # children, so it needs a directory that starts empty. It never
        # runs on the chip, and a cache placed from outside must not
        # reach the children (it would override the flag below).
        env.pop("JAX_COMPILATION_CACHE_DIR", None)
        repo = os.path.dirname(os.path.abspath(__file__))
        ck_dir = os.path.join(tmp, tag, "ckpts")
        env.update({
            "PYTHONPATH": repo + os.pathsep + env.get("PYTHONPATH", ""),
            "JAX_PLATFORMS": "cpu",
            "PADDLE_ELASTIC_WORLD": str(world),
            "P1T_RESIZE_STEPS": str(steps),
            "P1T_RESIZE_SAVE_FREQ": str(save_freq),
            "P1T_RESIZE_GLOBAL_BATCH": "48",
            "P1T_RESIZE_CKPT": ck_dir,
            "P1T_RESIZE_OUT": os.path.join(tmp, tag, "params.npz"),
            "P1T_RESIZE_PACE_S": "0.004",
            # share one XLA cache across lives: a resized life pays the
            # retrace, a re-grown life hits the original world's cache
            "FLAGS_jit_cache_dir": os.path.join(tmp, "jitcache"),
        })
        if chaos_spec:
            env["FLAGS_ft_chaos"] = chaos_spec
        os.makedirs(os.path.join(tmp, tag), exist_ok=True)
        sup = Supervisor(policy="resize", world_size=world,
                         min_world=2, max_resizes=4,
                         shrink_target=lambda w, fails: w - shrink_by,
                         heartbeat_dir=os.path.join(tmp, tag, "hb"),
                         poll_s=0.05, grace_s=5.0, resize_grace_s=30.0)
        log_path = os.path.join(tmp, tag, "workerlog.0")
        sup.add_worker(0, [_sys.executable, "-u", worker_py], env=env,
                       log_path=log_path)
        rc_box = {}
        runner = threading.Thread(
            target=lambda: rc_box.update(rc=sup.run()), daemon=True)
        runner.start()
        if with_grow:
            # grow back once the SHRUNKEN world has committed past the
            # grow mark — "the preempted capacity came back"
            deadline = time.monotonic() + 240
            while time.monotonic() < deadline:
                if rc_box:  # completed before the grow could land
                    break
                if sup.report.resizes and \
                        (ckpt_mod.latest_step(ck_dir) or 0) >= grow_step:
                    sup.request_resize(world, "capacity restored")
                    break
                time.sleep(0.02)
        runner.join(timeout=300)
        if runner.is_alive():
            raise AssertionError(f"elastic-resize {tag} run wedged")
        rc = rc_box.get("rc")
        log = open(log_path).read()
        if rc != 0:
            raise AssertionError(
                f"elastic-resize {tag} run failed rc={rc}: {log[-2000:]}")
        lives = []
        for m in re.finditer(
                r"RESIZE life=(\d+) world=(\d+) final_step=(\d+) "
                r"resumed_from=(\S+) resharded=(\d+) "
                r"loader_resume=(\S+) consumed=(\d+)", log):
            lives.append({
                "life": int(m.group(1)), "world": int(m.group(2)),
                "final_step": int(m.group(3)),
                "resumed_from": (None if m.group(4) == "None"
                                 else int(m.group(4))),
                "resharded": int(m.group(5)),
                "loader_resume": m.group(6),
                "consumed": int(m.group(7))})
        out = np.load(os.path.join(tmp, tag, "params.npz"))
        return {k: out[k] for k in out.files}, sup.report, lives

    try:
        t0 = time.perf_counter()
        clean, _, _ = run_supervised("clean", "", with_grow=False)
        faulted, report, lives = run_supervised(
            "resize", f"worker_kill@{kill_beat}:0", with_grow=True)
        dt = time.perf_counter() - t0
        max_err = max(float(np.max(np.abs(clean[k] - faulted[k])))
                      for k in clean)
        sizes = [(r["from"], r["to"]) for r in report.resizes]
        kill_life = next((l for l in lives if l["world"] == world -
                          shrink_by), None)
        grow_life = next((l for l in lives
                          if l["world"] == world and l["life"] > 0), None)
        recovered = (
            max_err <= 1e-6
            and sizes == [(world, world - shrink_by),
                          (world - shrink_by, world)]
            and kill_life is not None and grow_life is not None
            # the ungraceful kill resumed from a MID-RUN commit through
            # the 8→6 resharding load path
            and kill_life["resumed_from"] is not None
            and kill_life["resumed_from"] >= save_freq
            and kill_life["resharded"] >= 1
            # exactly-once across the graceful resize: the grown life
            # resumes at exactly the step the drained life committed,
            # via the O(1) loader-state restore (no replay, no gap)
            and grow_life["resumed_from"] == kill_life["final_step"]
            and grow_life["resharded"] >= 1
            and grow_life["loader_resume"] == "state"
            and grow_life["final_step"] == steps)
        detail = dict(report.as_dict(), steps=steps, save_freq=save_freq,
                      kill_beat=kill_beat, grow_step=grow_step,
                      lives=lives, max_param_err=max_err,
                      elapsed_s=round(dt, 3))
        _emit("elastic_resize_recovered_steps_per_sec", steps / dt,
              "steps/s", 1.0 if recovered else 0.0, detail)
        if not recovered:
            raise AssertionError(
                f"elastic resize did NOT recover: {json.dumps(detail)}")
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def bench_loader_chaos(on_tpu, steps_override=None):
    """``--loader-chaos``: fault-injection soak of the input pipeline.

    Trains the same deterministic tiny MLP twice through
    ``ResilientTrainer`` over a ``num_workers=2`` DataLoader:

    * **faulted** — ``loader_worker_kill`` SIGKILLs worker 0 mid-epoch
      (recovered by re-spawn + task re-dispatch), ``corrupt_sample``
      poisons one of worker 1's sample fetches (quarantined under the
      ``quarantine`` policy), and a simulated preemption forces a
      mid-run rollback whose data stream comes back via the O(1)
      checkpointable-loader state restore;
    * **clean reference** — no chaos, but its dataset pre-excludes
      exactly the indices the faulted run quarantined (raising on them
      under the same policy), so both runs see the identical batch
      sequence IFF the faulted run skipped exactly what it logged.

    ``vs_baseline`` is the recovery contract: 1.0 iff final params
    match to 1e-6, every quarantined index appears exactly once, the
    worker restart/stall/preemption counters account for each injected
    fault, and the resume was a state restore (consumed-batch counter
    bounded by steps + save_freq — a replay fast-forward would consume
    ~steps + preempt_step)."""
    import os
    import shutil
    import tempfile

    import jax
    import paddle1_tpu as paddle
    from paddle1_tpu.core import chaos
    from paddle1_tpu.core.tensor import Tensor
    from paddle1_tpu.distributed import (ParallelEngine, ResilientTrainer,
                                         build_mesh)
    from paddle1_tpu.io import DataLoader

    steps = steps_override or 18
    if steps < 12:
        raise SystemExit(
            f"--loader-chaos needs --steps >= 12 (got {steps}): the "
            "kill/corrupt/preempt points are spread across the run and "
            "must all land before it ends")
    save_freq = max(steps // 3, 1)
    batch = 8
    n_samples = steps * batch  # exactly one epoch of data

    class _DetDS(paddle.io.Dataset):
        """Deterministic per-index samples; raises on ``bad`` indices
        (the clean reference's stand-in for the faulted run's
        quarantined records)."""

        def __init__(self, bad=()):
            self.bad = frozenset(int(b) for b in bad)

        def __len__(self):
            return n_samples

        def __getitem__(self, i):
            if i in self.bad:
                raise ValueError(f"pre-excluded corrupt record {i}")
            rng = np.random.default_rng(1000 + i)
            return (rng.standard_normal(16).astype(np.float32),
                    rng.standard_normal(4).astype(np.float32))

    def make_engine():
        paddle.seed(0)
        model = paddle.nn.Sequential(
            paddle.nn.Linear(16, 32), paddle.nn.ReLU(),
            paddle.nn.Linear(32, 4))
        for i, p in enumerate(model.parameters()):
            p._data = jax.numpy.asarray(
                np.random.default_rng(7 + i)
                .standard_normal(p.shape).astype(np.float32) * 0.1)
        opt = paddle.optimizer.AdamW(learning_rate=1e-2,
                                     parameters=model.parameters())
        loss_fn = lambda m, b: \
            ((m(Tensor(b[0])) - Tensor(b[1])) ** 2).mean()
        mesh = build_mesh(dp=1, devices=jax.devices()[:1])
        return ParallelEngine(model, opt, loss_fn, mesh=mesh,
                              check_finite=True)

    def run(tag, tmp, bad, spec):
        chaos.reset()
        if spec:
            chaos.configure(spec)
        dl = DataLoader(_DetDS(bad), batch_size=batch, num_workers=2,
                        bad_sample_policy="quarantine",
                        stall_timeout_s=30)
        trainer = ResilientTrainer(make_engine(), os.path.join(tmp, tag),
                                   save_freq=save_freq,
                                   bad_step_policy="restore_last_good",
                                   backoff_base_s=0.0)
        report = trainer.fit(lambda: dl, steps=steps)
        params = {k: np.asarray(v)
                  for k, v in trainer.engine.params.items()}
        return params, report, dl

    tmp = tempfile.mkdtemp(prefix="p1t_loaderchaos_")
    try:
        # corrupt fires on worker 1's 5th sample fetch (an early batch,
        # safely BELOW the first checkpoint so the preemption rollback
        # can never replay it); the kill hits worker 0 mid-epoch; the
        # preemption lands a few steps past a mid-run checkpoint commit
        spec = (f"corrupt_sample@5:1,loader_worker_kill@4:0,"
                f"preempt@{steps - 3}")

        def soak():
            faulted, report, fdl = run("faulted", tmp, (), spec)
            quarantined = [rec["index"] for rec in fdl.quarantine]
            clean, clean_report, cdl = run("clean", tmp, quarantined, "")
            return faulted, report, fdl, quarantined, clean, cdl

        from bench_utils import best_of
        # n=1: this soak's gate is recovery PARITY, not speed — best_of
        # is the shared timing plumbing (and the knob to repeat the
        # whole faulted+clean pair when diagnosing a flake)
        (bo,) = best_of(1, soak)
        faulted, report, fdl, quarantined, clean, cdl = bo.best_result
        dt = bo.best_s

        max_err = max(float(np.max(np.abs(clean[k] - faulted[k])))
                      for k in clean)
        # exactly-once accounting: no index quarantined twice (a
        # re-dispatched in-flight task must not double-log), and the
        # clean reference quarantined the same records
        exactly_once = (len(set(quarantined)) == len(quarantined)
                        and len(quarantined) >= 1)
        clean_q = [rec["index"] for rec in cdl.quarantine]
        recovered = (
            max_err <= 1e-6 and exactly_once
            and sorted(clean_q) == sorted(quarantined)
            and report.loader_worker_restarts == 1
            and report.bad_samples == len(quarantined)
            and report.samples_quarantined == len(quarantined)
            and report.preemptions == 1
            and report.loader_state_restores >= 1
            and report.loader_resume == "state"
            # the O(1)-resume contract: a replay fast-forward would
            # consume ~steps + preempt_step batches
            and fdl.batches_consumed <= steps + save_freq + 2
            and cdl.batches_consumed == steps)
        detail = dict(report.as_dict(), steps=steps, save_freq=save_freq,
                      chaos=spec, quarantined=quarantined,
                      clean_quarantined=clean_q,
                      batches_consumed=fdl.batches_consumed,
                      clean_batches_consumed=cdl.batches_consumed,
                      max_param_err=max_err, elapsed_s=round(dt, 3))
        _emit("loader_chaos_recovered_steps_per_sec", steps / dt,
              "steps/s", 1.0 if recovered else 0.0, detail)
        if not recovered:
            raise AssertionError(
                f"loader-chaos soak did NOT recover: {json.dumps(detail)}")
    finally:
        chaos.reset()  # a failing soak must not leave faults armed
        shutil.rmtree(tmp, ignore_errors=True)


def bench_serving(on_tpu, steps_override=None):
    """``--serving``: dynamic micro-batching throughput vs single-request
    dispatch.

    Serves N requests twice over the same MLP — once one-at-a-time
    through the bucketed engine (each request pays a full dispatch +
    readback), once through the Server's Batcher at ``max_batch`` 16 —
    and reports batched QPS. The two phases are INTERLEAVED for
    ``repeats`` rounds via ``bench_utils.best_of`` and the fastest run
    of each is scored: the gate compares serving designs, and on a
    shared box multi-ms scheduler stalls arrive in bursts (observed: an
    86ms stall inside one 0.4ms dispatch, and whole seconds-long slow
    windows) — interleaving makes both phases sample the same noise
    windows, and best-of-N dodges the bursts. ``vs_baseline`` is
    speedup/3.0: the acceptance gate asserts batched >= 3x sequential
    at batch 16 on CPU, batched outputs == sequential outputs to 1e-6
    on EVERY round, and exactly one compile per shape bucket (the
    engine's trace counters)."""
    import paddle1_tpu as paddle
    from bench_utils import SelfTimed, best_of
    from paddle1_tpu.serving import InferenceEngine, Server

    n_req = steps_override or 256
    max_batch = 16
    repeats = 5
    paddle.seed(0)
    # a model with REAL weight traffic (~8 MB): batch-1 inference is
    # memory-bound GEMV that re-reads every weight matrix per request,
    # batch-16 reads them once per 16 — the structural win batching
    # exists for. (A toy MLP here turns the gate into a pure
    # dispatch-overhead race, which this box's variable jax dispatch
    # cost — 80us to 600us between runs — decides arbitrarily.)
    # Output layer deliberately small-scale: bucket-1 and bucket-16 are
    # DIFFERENT XLA executables (GEMV vs tiled GEMM), so their outputs
    # legitimately differ by ~1 ulp relative (~1e-6 for this 2048-deep
    # f32 accumulation — measured 1.1e-6 rel, deterministic). The parity
    # gate is ABSOLUTE 1e-6 and exists to catch batcher scatter/pad bugs
    # (which are O(1) regardless of scale), so keep outputs at O(0.1) to
    # stay out of the rounding noise without weakening the gate.
    model = paddle.nn.Sequential(
        paddle.nn.Linear(512, 2048), paddle.nn.ReLU(),
        paddle.nn.Linear(2048, 512, weight_attr=paddle.ParamAttr(
            initializer=paddle.nn.initializer.Normal(std=1e-3))))
    model.eval()
    engine = InferenceEngine(model, buckets=(1, max_batch),
                             input_specs=[((512,), "float32")])
    engine.warm_up()  # both buckets compiled up front: the timed
    # sections below measure serving, not XLA

    rng = np.random.default_rng(0)
    reqs = [rng.standard_normal((1, 512)).astype(np.float32)
            for _ in range(n_req)]

    state = {}

    def seq_phase():
        # sequential: one dispatch + one readback per request (the
        # whole call is the critical section — plain external timing)
        return [engine.infer([r])[0] for r in reqs]

    def bat_phase():
        # batched: the same requests through the micro-batcher (a fresh
        # Server per round — its metrics/drain report must cover exactly
        # one pass; the engine and its compiled buckets are shared).
        # SelfTimed: construction/drain are per-round setup, the timed
        # section is submit -> result, matching the sequential phase.
        srv = Server(engine, max_batch=max_batch, batch_timeout_ms=50,
                     queue_depth=n_req + max_batch)
        srv.start()
        t0 = time.perf_counter()
        futs = [srv.submit(r) for r in reqs]
        bat_out = [f.result(timeout=120) for f in futs]
        dt = time.perf_counter() - t0
        state["srv"] = srv
        return SelfTimed(dt, (bat_out, srv.drain()))

    # best-of-N per phase, exactly as the docstring sells it: stalls on
    # this box arrive in bursts, so the fastest round of each phase is
    # the serving-design signal and anything slower is scheduler noise
    seq_bo, bat_bo = best_of(repeats, seq_phase, bat_phase)
    max_err = max(
        float(np.max(np.abs(s - b)))
        for seq_out, (bat_out, _) in zip(seq_bo.results, bat_bo.results)
        for s, b in zip(seq_out, bat_out))
    # accounting must hold on EVERY round, not just the fastest
    report = next((rep for _, rep in bat_bo.results
                   if rep["unaccounted"]), bat_bo.results[-1][1])
    t_seq = seq_bo.best_s
    t_bat = bat_bo.best_s
    speedup = t_seq / t_bat
    srv = state["srv"]
    occupancy = srv.metrics.histogram("batch_occupancy").summary()
    detail = {"requests": n_req, "max_batch": max_batch,
              "seq_qps": round(n_req / t_seq, 1),
              "batched_qps": round(n_req / t_bat, 1),
              "speedup": round(speedup, 2),
              "max_err": max_err,
              "batches": report["batches"],
              "mean_occupancy": occupancy["mean"],
              "compile_counts": {str(k): v for k, v in
                                 engine.compile_counts.items()},
              "dispatches": {str(k): v for k, v in
                             engine.dispatch_counts.items()},
              "p99_e2e_ms": srv.metrics.histogram("e2e_ms")
              .percentile(99),
              "unaccounted": report["unaccounted"]}
    ok = (max_err <= 1e-6 and speedup >= 3.0
          and all(v == 1 for v in engine.compile_counts.values())
          and report["unaccounted"] == 0)
    _emit("serving_batched_qps", n_req / t_bat, "req/s",
          speedup / 3.0, detail)
    if not ok:
        raise AssertionError(
            f"serving gate failed (need speedup>=3x, parity<=1e-6, one "
            f"compile per bucket, zero drops): {json.dumps(detail)}")


def bench_generate(on_tpu, steps_override=None):
    """``--generate``: continuous-batching decode throughput vs
    sequential eager ``dynamic_decode``.

    Decodes the same 16 greedy prompts twice over one small CausalLM —
    once one-sequence-at-a-time through the eager concat-cache
    ``nn.dynamic_decode`` loop (one host round trip per token per
    sequence: the pre-ISSUE-9 path), once through the
    ``GenerationServer``'s slot-batched jitted decode (ONE dispatch per
    token for the whole batch) — interleaved best-of-N
    (``bench_utils.best_of``) like every timing gate on this noisy box.
    ``vs_baseline`` is speedup/5.0. The acceptance gate asserts, on
    CPU:

    * batch-16 continuous-batching tokens/s >= 5x sequential eager;
    * greedy outputs == eager ``dynamic_decode`` outputs per prompt;
    * a STAGGERED run (requests joining the running batch mid-decode,
      half of them temperature/top-k sampled with per-request seeds)
      produces outputs bit-identical to each request decoded alone;
    * exactly ONE decode compile across all ragged arrivals/lengths
      (the trace counter);
    * a drain under load resolves every stream with request-level
      unaccounted == 0 AND token-level tokens_owed == 0.
    """
    import paddle1_tpu as paddle
    from bench_utils import best_of
    from paddle1_tpu.core.tensor import to_tensor
    from paddle1_tpu.nn import (BasicDecoder, GreedyEmbeddingHelper,
                                dynamic_decode)
    from paddle1_tpu.serving import (CausalLM, GenerationEngine,
                                     GenerationServer)

    n_req = 16
    max_new = steps_override or 24
    repeats = 3
    vocab, max_seq = 64, 64
    paddle.seed(0)
    lm = CausalLM(vocab_size=vocab, d_model=32, nhead=4,
                  dim_feedforward=64, num_layers=2, max_seq=max_seq)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, vocab, size=int(rng.integers(1, 9)))
               .tolist() for _ in range(n_req)]

    def eager_decode(prompt):
        # prefill through the concat cache, then dynamic_decode drives
        # the per-token loop (the eager baseline the ROADMAP names)
        cache = lm.empty_cache(1)
        logits, cache = lm(to_tensor(np.asarray(prompt, np.int64)[None]),
                           cache=cache)
        first = int(np.asarray(logits.numpy())[0, -1].argmax())

        def cell(inputs, states):
            lg, new_cache = lm(paddle.reshape(inputs, [1, 1]),
                               cache=states)
            return paddle.reshape(lg, [1, vocab]), new_cache
        helper = GreedyEmbeddingHelper(lambda ids: ids,
                                       np.asarray([first], np.int64),
                                       end_token=-1)  # run to max_step
        outs, _ = dynamic_decode(BasicDecoder(cell, helper),
                                 inits=cache, max_step_num=max_new - 2)
        return [first] + np.asarray(outs.sample_ids.numpy())[0].tolist()

    engine = GenerationEngine(lm, slots=n_req, max_seq=max_seq,
                              prefill_buckets=(8,))
    # pre-compile both paths once: the timed rounds measure decode
    # design, not XLA (the eager path warms its own traces in round 1,
    # so best-of-N with repeats >= 2 dodges that too)
    engine.warm_up()

    def seq_phase():
        return [eager_decode(p) for p in prompts]

    def gen_phase():
        srv = GenerationServer(engine, token_budget=max_new,
                               queue_depth=2 * n_req).start()
        streams = [srv.submit(p, max_new_tokens=max_new)
                   for p in prompts]
        outs = [s.result(timeout=300) for s in streams]
        rep = srv.drain()
        if rep["unaccounted"] or rep["tokens_owed"]:
            raise AssertionError(f"generate accounting broke: {rep}")
        return outs

    seq_bo, gen_bo = best_of(repeats, seq_phase, gen_phase)
    parity = all(a == b for seq_out, gen_out
                 in zip(seq_bo.results, gen_bo.results)
                 for a, b in zip(seq_out, gen_out))
    total_tokens = n_req * max_new
    tps_seq = total_tokens / seq_bo.best_s
    tps_gen = total_tokens / gen_bo.best_s
    speedup = tps_gen / tps_seq

    # staggered arrivals: half greedy, half seeded sampling; late
    # requests join the RUNNING batch — outputs must be bit-identical
    # to each request decoded alone on the same engine
    def kw_for(i):
        if i % 2:
            return dict(max_new_tokens=max_new, temperature=0.9,
                        top_k=8, seed=1000 + i)
        return dict(max_new_tokens=max_new)

    srv = GenerationServer(engine, token_budget=max_new,
                           queue_depth=2 * n_req).start()
    streams = []
    for i, p in enumerate(prompts):
        streams.append(srv.submit(p, **kw_for(i)))
        if i == n_req // 2:
            while len(streams[0].tokens) < max_new // 2:
                time.sleep(0.002)
    staggered = [s.result(timeout=300) for s in streams]
    srv.drain()
    alone_ok = True
    for i in (0, 1, n_req // 2 + 1, n_req - 1):
        srv = GenerationServer(engine, token_budget=max_new).start()
        alone = srv.submit(prompts[i], **kw_for(i)).result(timeout=300)
        srv.drain()
        alone_ok = alone_ok and alone == staggered[i]

    # drain under load: token-level unaccounted == 0
    srv = GenerationServer(engine, token_budget=max_new,
                           queue_depth=4 * n_req).start()
    load = [srv.submit(p, max_new_tokens=max_new) for p in prompts * 2]
    drain_rep = srv.drain(timeout=120)
    drain_ok = (all(s.done() for s in load)
                and drain_rep["unaccounted"] == 0
                and drain_rep["tokens_owed"] == 0)

    one_compile = engine.decode_compile_count == 1
    detail = {"requests": n_req, "max_new_tokens": max_new,
              "eager_tokens_per_s": round(tps_seq, 1),
              "batched_tokens_per_s": round(tps_gen, 1),
              "speedup": round(speedup, 2),
              "greedy_parity": parity,
              "staggered_bit_identical": alone_ok,
              "decode_compiles": engine.decode_compile_count,
              "prefill_compiles": {str(k): v for k, v in
                                   engine.prefill_compile_counts.items()},
              "drain_under_load": {
                  "unaccounted": drain_rep["unaccounted"],
                  "tokens_owed": drain_rep["tokens_owed"],
                  "completed": drain_rep["completed"]}}
    ok = (speedup >= 5.0 and parity and alone_ok and one_compile
          and drain_ok)
    _emit("generate_tokens_per_s", tps_gen, "tok/s", speedup / 5.0,
          detail)
    if not ok:
        raise AssertionError(
            "generate gate failed (need tokens/s>=5x eager, greedy "
            "parity, staggered bit-parity, one decode compile, clean "
            f"drain): {json.dumps(detail)}")
    _bench_generate_paged(lm, vocab, max_seq)
    _bench_generate_spec(vocab)


def _bench_generate_paged(lm, vocab, max_seq):
    """The decode-economics HBM arm (ISSUE 16): at the HBM budget of a
    FOUR-slot dense KV cache, the paged engine (16-token shared prefix
    + page-granular allocation) serves SIXTEEN concurrent requests —
    >= 4x the concurrency per byte — bit-identically, over one decode
    compile, owing zero pages at drain. Also emits the decode-density
    line ``generate_tokens_per_s_per_hbm_gib`` (tokens/s per KV-cache
    GiB, the metric the paged cache exists to move)."""
    from paddle1_tpu.quantization import quantize_weights_int8
    from paddle1_tpu.serving import GenerationEngine, GenerationServer

    ps, n_paged, budget_slots, max_new = 8, 16, 4, 6

    def kv_bytes(eng):
        return sum(k.size * k.dtype.itemsize + v.size * v.dtype.itemsize
                   for k, v in eng._kv)

    def timed_run(eng, prompts):
        t0 = time.perf_counter()
        srv = GenerationServer(eng, token_budget=max_new,
                               queue_depth=2 * len(prompts)).start()
        outs = [srv.submit(p, max_new_tokens=max_new) for p in prompts]
        outs = [s.result(timeout=300) for s in outs]
        rep = srv.drain()
        return outs, rep, time.perf_counter() - t0

    # the budget: every KV byte a 4-slot dense cache would hold, spent
    # on pages instead (parking page included — nothing hides off-book)
    n_pages = budget_slots * max_seq // ps
    paged_eng = GenerationEngine(lm, slots=n_paged, max_seq=max_seq,
                                 prefill_buckets=(24,), paged=True,
                                 page_size=ps, pages=n_pages,
                                 prefix_cache=8)
    dense16 = GenerationEngine(lm, slots=n_paged, max_seq=max_seq,
                               prefill_buckets=(24,))
    dense_budget_bytes = kv_bytes(dense16) * budget_slots // n_paged
    assert kv_bytes(paged_eng) <= dense_budget_bytes, \
        "paged pool exceeds the 4-slot dense HBM budget"

    prefix = [(7 * i) % vocab or 1 for i in range(1, 17)]
    prompts = [prefix + [1 + i % (vocab - 2), 1 + (3 * i) % (vocab - 2)]
               for i in range(n_paged)]
    paged_eng.warm_up()
    dense16.warm_up()
    outs, rep, elapsed = timed_run(paged_eng, prompts)
    # oracle: the same requests on the (budget-free) 16-slot dense
    # engine — paged serves 4x the slots per KV byte, bit-identically
    oracle, _orep, d_elapsed = timed_run(dense16, prompts)

    st = paged_eng.pool.stats()
    tps = n_paged * max_new / elapsed
    tps_dense = n_paged * max_new / d_elapsed
    gib = kv_bytes(paged_eng) / 2 ** 30
    dense_gib = kv_bytes(dense16) / 2 ** 30
    density, dense_density = tps / gib, tps_dense / dense_gib
    # int8 rides along: decode weight bytes after the artifact pass
    fs = lm.functional_state()
    q = quantize_weights_int8(fs)
    f32_b = sum(v.size * v.dtype.itemsize for v in fs.values())
    q_b = sum((v.q.size + v.scale.size * 4) if hasattr(v, "q")
              else v.size * v.dtype.itemsize for v in q.values())
    slots_ratio = n_paged / budget_slots
    detail = {"paged_slots": n_paged, "dense_budget_slots": budget_slots,
              "page_size": ps, "pages": n_pages,
              "kv_budget_bytes": dense_budget_bytes,
              "paged_kv_bytes": kv_bytes(paged_eng),
              "prefix_hit_pages": st["prefix_hit_pages"],
              "kv_pages_owed": rep["kv_pages_owed"],
              "bit_identical_to_dense": outs == oracle,
              "decode_compiles": paged_eng.decode_compile_count,
              "tokens_per_s": round(tps, 1),
              "int8_weight_bytes_ratio": round(q_b / f32_b, 3)}
    ok = (outs == oracle and rep["kv_pages_owed"] == 0
          and rep["unaccounted"] == 0
          and paged_eng.decode_compile_count == 1
          and slots_ratio >= 4.0)
    _emit("generate_paged_slots_at_hbm_budget", slots_ratio, "x",
          slots_ratio / 4.0, detail)
    _emit("generate_tokens_per_s_per_hbm_gib", density, "tok/s/GiB",
          density / dense_density / 1.0, {
              "paged_kv_gib": round(gib, 6),
              "dense_kv_gib": round(dense_gib, 6),
              "dense_tokens_per_s_per_hbm_gib": round(dense_density, 1)})
    if not ok:
        raise AssertionError(
            "paged-KV gate failed (need >= 4x slots at the dense HBM "
            "budget, bit-identical outputs, one decode compile, zero "
            f"pages owed): {json.dumps(detail)}")


def _bench_generate_spec(vocab):
    """The speculation arm (ISSUE 16): on the repetitive-text regime
    (a fixed-point model standing in for templated output), n-gram
    drafts verified in one dispatch must clear >= 70% acceptance and
    >= 1.8x tokens/s over the same engine decoding one token per
    dispatch — with BIT-identical greedy output."""
    import paddle1_tpu as paddle
    from bench_utils import best_of
    from paddle1_tpu.serving import GenerationEngine, NGramSpeculator

    n_tokens, spec_k, repeats, max_seq = 120, 4, 3, 256
    paddle.seed(0)
    from paddle1_tpu.serving import CausalLM
    lm = CausalLM(vocab_size=vocab, d_model=32, nhead=4,
                  dim_feedforward=64, num_layers=2, max_seq=max_seq)
    for _, t in lm.state_dict().items():
        t._data = t.data * 0          # fixed point -> cyclic output
    base = GenerationEngine(lm, slots=2, max_seq=max_seq,
                            prefill_buckets=(16,))
    spec = GenerationEngine(lm, slots=2, max_seq=max_seq,
                            prefill_buckets=(16,), spec_tokens=spec_k)
    base.warm_up()
    spec.warm_up()
    prompt = np.asarray([1, 2, 3, 4] * 4, np.int32)
    stats = {"proposed": 0, "accepted": 0, "dispatches": 0}

    def base_phase():
        out = [base.prefill(0, prompt, 0.0, 0, 1)]
        for _ in range(n_tokens - 1):
            toks, _f = base.decode(np.array([True, False]))
            out.append(int(toks[0, 0]))
        base.release(0)
        return out

    def spec_phase():
        out = [spec.prefill(0, prompt, 0.0, 0, 1)]
        sp = NGramSpeculator(prompt, spec_k, n=3)
        sp.observe(out[0])
        stats.update(proposed=0, accepted=0, dispatches=0)
        while len(out) < n_tokens:
            d = sp.propose()
            drafts = np.zeros([2, spec_k], np.int32)
            nd = np.zeros([2], np.int32)
            nd[0] = d.size
            drafts[0, :d.size] = d
            toks, flags = spec.decode(np.array([True, False]),
                                      drafts, nd)
            n = int(flags[0].sum())
            stats["proposed"] += int(nd[0])
            stats["accepted"] += max(n - 1, 0)
            stats["dispatches"] += 1
            for i in range(n):
                sp.observe(int(toks[0, i]))
                out.append(int(toks[0, i]))
        spec.release(0)
        return out[:n_tokens]

    base_bo, spec_bo = best_of(repeats, base_phase, spec_phase)
    parity = all(a == b for a, b in zip(base_bo.results[0],
                                        spec_bo.results[0]))
    tps_base = n_tokens / base_bo.best_s
    tps_spec = n_tokens / spec_bo.best_s
    speedup = tps_spec / tps_base
    accept = stats["accepted"] / max(stats["proposed"], 1)
    detail = {"tokens": n_tokens, "spec_tokens": spec_k,
              "base_tokens_per_s": round(tps_base, 1),
              "spec_tokens_per_s": round(tps_spec, 1),
              "speedup": round(speedup, 2),
              "accept_ratio": round(accept, 3),
              "dispatches": stats["dispatches"],
              "greedy_bit_identical": parity,
              "decode_compiles": spec.decode_compile_count}
    ok = (speedup >= 1.8 and accept >= 0.7 and parity
          and spec.decode_compile_count == 1)
    _emit("generate_spec_tokens_per_s", tps_spec, "tok/s",
          speedup / 1.8, detail)
    if not ok:
        raise AssertionError(
            "speculation gate failed (need >= 1.8x tokens/s at >= 70% "
            "acceptance with bit-identical greedy output, one decode "
            f"compile): {json.dumps(detail)}")


def bench_obs(on_tpu, steps_override=None):
    """``--obs``: observability acceptance gate (ISSUE 10), two parts.

    **Overhead** — the same tiny-MLP training loop (per-step readback:
    the worst case for instrumentation, every phase histogram AND the
    readback timer fire each step) is timed with observability fully
    off and with metrics+tracing fully on, interleaved best-of-3
    (bench_utils noise policy). Gates: enabled overhead < 5% of step
    time, and disabled cost ≈ 0 proven STRUCTURALLY — a disabled run
    touches neither the process registry nor the trace sink (zero
    metric families, zero span files), so the only possible residue is
    the flag checks themselves.

    **Cross-process trace** — a 2-replica ServingFleet soak with a
    ``replica_hang`` chaos point and a tight transport deadline: the
    wedged request fails over, and the merged chrome-trace export must
    show ONE request's spans across >= 3 processes (client/router in
    the fleet process, the wedged replica, the failover replica)
    linked by trace_id, with client -> router -> replica -> batcher
    span names and flow events. ``vs_baseline`` is 1.0 iff every gate
    holds; the metric is the enabled-overhead fraction."""
    import os
    import shutil
    import tempfile
    import urllib.request

    import jax
    import paddle1_tpu as paddle
    from bench_utils import best_of
    from paddle1_tpu import obs
    from paddle1_tpu.core import chaos
    from paddle1_tpu.core import flags as core_flags
    from paddle1_tpu.core.tensor import Tensor
    from paddle1_tpu.distributed import ParallelEngine, build_mesh
    from paddle1_tpu.obs import trace as obs_trace
    from paddle1_tpu.serving import ServingFleet

    steps = steps_override or (100 if on_tpu else 60)
    rng = np.random.default_rng(0)
    # a few-ms step (batch 256 MLP on CPU): small enough to iterate,
    # big enough that the gate measures instrumentation against a
    # realistic denominator — real training steps are ms-scale and up,
    # and the per-step obs cost is a fixed ~tens of us
    batches = [{"x": rng.standard_normal((256, 256)).astype(np.float32),
                "y": rng.standard_normal((256, 64)).astype(np.float32)}
               for _ in range(8)]

    paddle.seed(0)
    model = paddle.nn.Sequential(
        paddle.nn.Linear(256, 512), paddle.nn.ReLU(),
        paddle.nn.Linear(512, 64))
    opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                 parameters=model.parameters())
    loss_fn = lambda m, b: \
        ((m(Tensor(b["x"])) - Tensor(b["y"])) ** 2).mean()
    mesh = build_mesh(dp=1, devices=jax.devices()[:1])
    engine = ParallelEngine(model, opt, loss_fn, mesh=mesh)
    for _ in range(5):  # compile + settle outside every timed round
        float(engine.step(batches[0]))

    def run_steps():
        for i in range(steps):
            # per-step readback: the instrumentation worst case (each
            # step pays shard+dispatch histograms AND the readback
            # timer when enabled)
            float(engine.step(batches[i % len(batches)]))

    tmp = tempfile.mkdtemp(prefix="p1t_obsbench_")
    train_trace = os.path.join(tmp, "train_trace")
    try:
        # structural disabled-cost proof BEFORE anything ever enables
        # obs in this process (a fresh registry must stay untouched)
        obs.reset_process_registry()
        run_steps()
        disabled_clean = obs.process_registry().empty() and \
            not os.path.isdir(train_trace)

        def disabled_phase():
            run_steps()

        def enabled_phase():
            with core_flags.flags_guard(obs_metrics=True,
                                        obs_trace_dir=train_trace):
                run_steps()

        # best-of-5: the true overhead is ~tens of us/step (~1-2%) but
        # this shared box schedules ~10ms stalls into 200ms phases —
        # min-of-5 interleaved keeps the gate's noise floor well under
        # the 5% line (bench_utils noise policy)
        dis_bo, en_bo = best_of(5, disabled_phase, enabled_phase)
        overhead = en_bo.best_s / dis_bo.best_s - 1.0

        snap = obs.process_registry().snapshot()
        hists = snap["histograms"]
        metrics_ok = all(
            hists.get(h, {}).get("count", 0) >= steps
            for h in ("train_shard_seconds", "train_dispatch_seconds",
                      "train_readback_seconds"))
        train_span_names = {s["name"]
                           for s in obs_trace.read_spans(train_trace)}
        train_trace_ok = {"train/step", "train/shard",
                          "train/dispatch"} <= train_span_names

        # live telemetry endpoint smoke: the enabled run's families
        # must be scrapeable, and /healthz must answer
        tele = obs.TelemetryServer(port=0).start()
        page = urllib.request.urlopen(
            tele.url + "/metrics", timeout=10).read().decode()
        hz = json.loads(urllib.request.urlopen(
            tele.url + "/healthz", timeout=10).read())
        tele.stop()
        endpoint_ok = ("# TYPE p1t_train_dispatch_seconds summary"
                       in page and hz.get("ok") is True)

        # -- part B: one request's spans across >= 3 processes ----------
        fleet_trace = os.path.join(tmp, "fleet_trace")
        factory = os.path.join(tmp, "factory.py")
        with open(factory, "w") as f:
            f.write(_FLEET_FACTORY)
        chaos.reset()
        # replicas inherit the sink via env; this process via set_flags
        os.environ["FLAGS_obs_trace_dir"] = fleet_trace
        core_flags.set_flags({"obs_trace_dir": fleet_trace})
        try:
            fleet = ServingFleet(
                f"{factory}:make_model", replicas=2, version="v1",
                model_arg="v1", max_batch=8, buckets=(1, 8),
                batch_timeout_ms=2, input_specs=[((32,), "float32")],
                warmup=True, retry_max=2, replica_timeout_ms=2000,
                hang_timeout=30.0, poll_s=0.1, inflight_per_replica=2,
                chaos_spec="replica_hang@1:0",
                env={"JAX_PLATFORMS": "cpu"},
                work_dir=os.path.join(tmp, "fleet"))
            fleet.start()
            futs = [fleet.submit(
                rng.standard_normal((1, 32)).astype(np.float32))
                for _ in range(8)]
            for fut in futs:
                fut.result(timeout=120)
            freport = fleet.drain()
        finally:
            core_flags.set_flags({"obs_trace_dir": ""})
            os.environ.pop("FLAGS_obs_trace_dir", None)

        pids_by_trace = {}
        for s in obs_trace.read_spans(fleet_trace):
            if s.get("trace"):
                pids_by_trace.setdefault(s["trace"], set()).add(s["pid"])
        best_tid, best_pids = max(pids_by_trace.items(),
                                  key=lambda kv: len(kv[1]),
                                  default=(None, set()))
        merged = os.path.join(tmp, "fleet_request_trace.json")
        # the export's parent-aware filter also pulls in spans that
        # flow-link INTO the trace (a micro-batch dispatch span lists
        # every co-batched request as a parent)
        stats = obs_trace.export_chrome_trace(fleet_trace, merged,
                                              trace_id=best_tid)
        names = set(stats["names"])
        fleet_ok = (len(best_pids) >= 3 and stats["flows"] >= 3
                    and freport["unaccounted"] == 0
                    and {"client/submit", "fleet/dispatch",
                         "replica/recv", "replica/serve",
                         "serve/batch_dispatch"} <= names)

        ok = (disabled_clean and overhead < 0.05 and metrics_ok
              and train_trace_ok and endpoint_ok and fleet_ok)
        detail = {"steps": steps,
                  "disabled_s": round(dis_bo.best_s, 4),
                  "enabled_s": round(en_bo.best_s, 4),
                  "overhead_frac": round(overhead, 4),
                  "disabled_clean": disabled_clean,
                  "metrics_ok": metrics_ok,
                  "train_trace_ok": train_trace_ok,
                  "endpoint_ok": endpoint_ok,
                  "fleet_trace_pids": len(best_pids),
                  "fleet_flows": stats["flows"],
                  "fleet_span_names": sorted(names),
                  "fleet_unaccounted": freport["unaccounted"],
                  "chrome_trace": merged}
        _emit("obs_overhead_frac", max(overhead, 0.0), "fraction",
              1.0 if ok else 0.0, detail)
        if not ok:
            raise AssertionError(
                "obs gate failed (need disabled-cost ~0, enabled "
                "overhead < 5%, scrapeable endpoint, and one request "
                f"traced across >= 3 processes): {json.dumps(detail)}")
    finally:
        shutil.rmtree(tmp, ignore_errors=True)



_FLIGHT_CRASH_WORKER = '''\
"""bench --cost crash worker: train a tiny MLP with the flight
recorder armed, then die on an injected uncaught exception — the
parent asserts the dump holds the final K step records."""
import os
import numpy as np
import jax
jax.config.update("jax_platforms", "cpu")
import paddle1_tpu as paddle
from paddle1_tpu.core import flags as core_flags
from paddle1_tpu.core.tensor import Tensor
from paddle1_tpu.distributed import ParallelEngine, build_mesh

K = int(os.environ["P1T_FLIGHT_K"])
steps = int(os.environ["P1T_FLIGHT_STEPS"])
core_flags.set_flags({"obs_metrics": True, "obs_flight_steps": K,
                      "obs_flight_dir": os.environ["P1T_FLIGHT_DIR"]})
paddle.seed(0)
model = paddle.nn.Sequential(
    paddle.nn.Linear(16, 32), paddle.nn.ReLU(), paddle.nn.Linear(32, 4))
opt = paddle.optimizer.AdamW(learning_rate=1e-2,
                             parameters=model.parameters())
loss_fn = lambda m, b: ((m(Tensor(b["x"])) - Tensor(b["y"])) ** 2).mean()
engine = ParallelEngine(model, opt, loss_fn,
                        mesh=build_mesh(dp=1, devices=jax.devices()[:1]))
rng = np.random.default_rng(0)
b = {"x": rng.standard_normal((8, 16)).astype(np.float32),
     "y": rng.standard_normal((8, 4)).astype(np.float32)}
for i in range(steps):
    float(engine.step(b))
raise RuntimeError("injected crash (bench --cost flight gate)")
'''


def bench_cost(on_tpu, steps_override=None):
    """``--cost``: cost-observatory acceptance gate (ISSUE 13), four
    parts.

    **MFU cross-check** — the BERT-base step is slope-timed (the
    honesty contract's readback barrier) and its MFU computed twice
    from the SAME measured dt and peak table: once from the bench's
    hand-derived ``6 * matmul_params * tokens + attention`` formula,
    once from the engine's ``step_cost()`` (XLA cost analysis of the
    lowered executable). Gate: cost-model MFU within 15% of analytic,
    and the cost source is the real analysis, not the heuristic.

    **HBM census** — with the BERT engine live (params + AdamW moments
    + the Layer's master copy registered), ``obs.hbm.census()`` must
    cover >= 95% of device-reported live bytes — "every big consumer
    is tagged".

    **Flight recorder** — a subprocess trains with
    ``obs_flight_steps=K`` armed and dies on an injected uncaught
    exception; the dump must exist, say ``reason=crash``, and contain
    exactly the final K step records.

    **Overhead** — the tiny-MLP per-step-readback loop (worst case)
    with the full cost observatory on (metrics + cost gauges + census
    + leak detector + flight ring) vs fully off, interleaved best-of-5:
    enabled < 5%, disabled ≈ 0 proven structurally (fresh registry
    stays empty, no flight file)."""
    import os
    import shutil
    import subprocess
    import sys as _sys
    import tempfile

    import jax
    import paddle1_tpu as paddle
    from bench_utils import best_of
    from paddle1_tpu import obs
    from paddle1_tpu.core import flags as core_flags
    from paddle1_tpu.core.tensor import Tensor
    from paddle1_tpu.distributed import ParallelEngine, build_mesh
    from paddle1_tpu.obs import flight as obs_flight
    from paddle1_tpu.obs import hbm as obs_hbm
    from paddle1_tpu.text.models import (BertForPretraining,
                                         BertPretrainingCriterion,
                                         bert_base)

    dev = jax.devices()[0]
    batch, seq = (32, 128) if on_tpu else (4, 64)
    steps = steps_override or 3

    # -- part A: BERT MFU cross-check ----------------------------------
    model = BertForPretraining(bert_base(
        hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0))
    crit = BertPretrainingCriterion(model.bert.vocab_size)
    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters())

    def loss_fn(m, b):
        scores, rel = m(Tensor(b["ids"]))
        return crit(scores, rel, Tensor(b["mlm"]), Tensor(b["nsp"]))

    engine = ParallelEngine(model, opt, loss_fn,
                            mesh=build_mesh(dp=1, devices=[dev]),
                            amp_dtype="bfloat16" if on_tpu else None)
    rng = np.random.default_rng(0)
    v = model.bert.vocab_size
    b = {"ids": rng.integers(1, v, (batch, seq)).astype(np.int32),
         "mlm": rng.integers(0, v, (batch, seq)).astype(np.int32),
         "nsp": rng.integers(0, 2, (batch,)).astype(np.int32)}
    step_fn = lambda: engine.step(b)
    _read_back(step_fn())  # compile flushed outside the timed window
    times, _ = _timed_steps(step_fn, steps)
    dt = statistics.median(times)

    n_params = sum(int(np.prod(p.shape)) for p in model.parameters())
    cfg = model.bert
    lookup_only = (cfg.embeddings.position_embeddings.weight.size +
                   cfg.embeddings.token_type_embeddings.weight.size)
    attn_flops = 12 * cfg.num_hidden_layers * batch * seq * seq * \
        cfg.hidden_size
    analytic_flops = 6 * (n_params - int(lookup_only)) * batch * seq \
        + attn_flops
    peak = _peak_flops(dev)
    analytic_mfu = (analytic_flops / dt) / peak
    cost = engine.step_cost(b)
    cm_mfu = (cost.flops / dt) / peak
    mfu_ratio = cm_mfu / analytic_mfu if analytic_mfu else 0.0
    mfu_ok = cost.exact and abs(mfu_ratio - 1.0) <= 0.15

    # -- part B: HBM census coverage (BERT engine live) ----------------
    engine.drain()
    c = obs_hbm.census()
    coverage = c["coverage_ratio"]
    census_ok = coverage >= 0.95

    tmp = tempfile.mkdtemp(prefix="p1t_costbench_")
    try:
        # -- part C: injected crash -> flight dump with final K steps --
        K, crash_steps = 6, 15
        flight_dir = os.path.join(tmp, "flight")
        worker_py = os.path.join(tmp, "crash_worker.py")
        with open(worker_py, "w") as f:
            f.write(_FLIGHT_CRASH_WORKER)
        env = dict(os.environ)
        repo = os.path.dirname(os.path.abspath(__file__))
        env.update({
            "PYTHONPATH": repo + os.pathsep + env.get("PYTHONPATH", ""),
            "JAX_PLATFORMS": "cpu",
            "P1T_FLIGHT_K": str(K),
            "P1T_FLIGHT_STEPS": str(crash_steps),
            "P1T_FLIGHT_DIR": flight_dir,
        })
        r = subprocess.run([_sys.executable, "-u", worker_py], env=env,
                           capture_output=True, timeout=300)
        if r.returncode == 0:
            raise AssertionError(
                "flight crash worker was supposed to die on the "
                "injected exception but exited 0")
        bundles = [fn for fn in (os.listdir(flight_dir)
                                 if os.path.isdir(flight_dir) else [])
                   if fn.startswith("flight-")]
        flight_steps, flight_reason = [], None
        if bundles:
            recs = obs_flight.read_bundle(
                os.path.join(flight_dir, bundles[0]))
            flight_reason = next(
                (rec.get("reason") for rec in recs
                 if rec.get("kind") == "flight_header"), None)
            flight_steps = sorted(rec["step"] for rec in recs
                                  if rec.get("kind") == "step")
        flight_ok = (
            flight_reason == "crash"
            and flight_steps == list(range(crash_steps - K + 1,
                                           crash_steps + 1)))

        # -- part D: overhead off vs on (tiny-MLP worst case) ----------
        # drop the BERT engine first: its census registrations die
        # with it (weakref), so the overhead phase measures the
        # MLP-only process a real training job would be — and 1.7 GB
        # of params/moments stops skewing the host
        import gc
        del engine, model, opt, crit, step_fn
        gc.collect()
        paddle.seed(0)
        mlp = paddle.nn.Sequential(
            paddle.nn.Linear(256, 512), paddle.nn.ReLU(),
            paddle.nn.Linear(512, 64))
        mopt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                      parameters=mlp.parameters())
        mloss = lambda m, bb: \
            ((m(Tensor(bb["x"])) - Tensor(bb["y"])) ** 2).mean()
        meng = ParallelEngine(mlp, mopt, mloss,
                              mesh=build_mesh(dp=1,
                                              devices=jax.devices()[:1]))
        mb = {"x": rng.standard_normal((256, 256)).astype(np.float32),
              "y": rng.standard_normal((256, 64)).astype(np.float32)}
        for _ in range(5):
            float(meng.step(mb))
        n_steps = 60

        def run_steps():
            for _ in range(n_steps):
                float(meng.step(mb))

        # structural disabled-cost proof BEFORE anything enables the
        # observatory in this process
        obs.reset_process_registry()
        obs_flight.reset()
        run_steps()
        disabled_clean = (obs.process_registry().empty()
                          and obs_flight.recorder() is None)

        en_dir = os.path.join(tmp, "flight_en")

        def disabled_phase():
            obs_flight.reset()  # a prior enabled round's taps must
            # not bill the disabled run
            run_steps()

        def enabled_phase():
            with core_flags.flags_guard(obs_metrics=True,
                                        obs_flight_steps=K,
                                        obs_flight_dir=en_dir,
                                        obs_hbm_leak_steps=10 ** 6):
                run_steps()

        dis_bo, en_bo = best_of(5, disabled_phase, enabled_phase)
        overhead = en_bo.best_s / dis_bo.best_s - 1.0
        snap = obs.process_registry().snapshot()
        gauges_ok = all(k in snap["gauges"] for k in
                        ("train_step_flops", "train_step_bytes",
                         "hbm_params_bytes",
                         "hbm_census_bytes"))
        overhead_ok = disabled_clean and overhead < 0.05 and gauges_ok

        ok = mfu_ok and census_ok and flight_ok and overhead_ok
        detail = {
            "batch": batch, "seq_len": seq, "steps": steps,
            "step_ms_median": round(dt * 1e3, 2),
            "analytic_mfu": round(analytic_mfu, 5),
            "costmodel_mfu": round(cm_mfu, 5),
            "mfu_ratio": round(mfu_ratio, 4),
            "cost_source": cost.source,
            "census": {k: c[k] for k in
                       ("census_bytes", "device_bytes_in_use",
                        "device_source")},
            "census_coverage": round(coverage, 4),
            "flight_reason": flight_reason,
            "flight_steps": flight_steps,
            "flight_K": K,
            "disabled_s": round(dis_bo.best_s, 4),
            "enabled_s": round(en_bo.best_s, 4),
            "overhead_frac": round(overhead, 4),
            "disabled_clean": disabled_clean,
            "gauges_ok": gauges_ok,
            "device": getattr(dev, "device_kind", dev.platform)}
        _emit("cost_observatory_overhead_frac", max(overhead, 0.0),
              "fraction", 1.0 if ok else 0.0, detail)
        if not ok:
            raise AssertionError(
                "cost gate failed (need cost-model MFU within 15% of "
                "analytic, census >= 95% of device live bytes, crash "
                "dump with the final K steps, enabled overhead < 5%, "
                f"disabled structurally zero): {json.dumps(detail)}")
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


_FLEET_FACTORY = '''
"""bench --serving-fleet replica model: a deterministic MLP whose
weights are a pure function of the seed, so every replica process —
and the in-process reference engines — build bit-identical versions.
arg "v2" scales the output (a real model change the version-tag parity
check can see); arg "boom" raises (the failed-canary artifact)."""


def make_model(arg):
    import numpy as np
    import jax.numpy as jnp
    if arg == "boom":
        raise RuntimeError("broken artifact (failed-canary bench case)")
    rng = np.random.default_rng(0)
    W1 = (rng.standard_normal((32, 64)) * 0.1).astype(np.float32)
    b1 = np.zeros(64, np.float32)
    W2 = (rng.standard_normal((64, 8)) * 0.1).astype(np.float32)
    b2 = np.zeros(8, np.float32)
    scale = 2.0 if arg == "v2" else 1.0

    def fwd(x):
        h = jnp.maximum(x @ W1 + b1, 0)
        return (h @ W2 + b2) * scale
    return fwd
'''


def bench_serving_fleet(on_tpu, steps_override=None):
    """``--serving-fleet``: chaos soak of the multi-replica HA layer.

    Three replica Server subprocesses under the fleet's Supervisor,
    then the ISSUE 7 acceptance matrix in one run:

    * **kill failover** — ``replica_kill`` SIGKILLs replica 1 mid-soak;
      every accepted request still resolves *successfully* (the
      failover retries absorb the kill — zero client-visible failures,
      typed or not), and the Supervisor relaunches the rank.
    * **hot-swap under load** — a mid-soak ``deploy`` to model version
      v2 (canary + rolling swap) drops zero requests; every response is
      checked against the single-process InferenceEngine of the version
      its tag names, at 1e-6 — both populations of the mixed-version
      window verify.
    * **failed canary** — deploying a broken artifact raises typed
      DeployFailed, rolls back, and the fleet keeps serving.
    * **accounting** — the drain report proves unaccounted == 0 across
      the kill, the failovers, and the swap.

    ``vs_baseline`` is 1.0 iff every gate holds; the metric is fleet
    QPS (best-of-2 via ``bench_utils.best_of`` — shared-box noise
    policy)."""
    import importlib.util
    import os
    import shutil
    import tempfile
    import threading

    from bench_utils import best_of
    from paddle1_tpu.core import chaos
    from paddle1_tpu.serving import (DeployFailed, InferenceEngine,
                                     ServingFleet)

    n_req = steps_override or 300
    if n_req < 60:
        raise SystemExit(
            f"--serving-fleet needs --steps >= 60 (got {n_req}): the "
            "replica_kill lands on replica 1's 10th request and must "
            "hit while the soak is still in flight")
    tmp = tempfile.mkdtemp(prefix="p1t_fleetbench_")
    try:
        factory = os.path.join(tmp, "factory.py")
        with open(factory, "w") as f:
            f.write(_FLEET_FACTORY)

        # in-process reference engines: the acceptance wording is
        # "outputs match their single-process engines at 1e-6"
        spec = importlib.util.spec_from_file_location("_fleet_fac",
                                                      factory)
        fac = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(fac)
        refs = {"v1": InferenceEngine(fac.make_model("v1"),
                                      buckets=(1, 8)),
                "v2": InferenceEngine(fac.make_model("v2"),
                                      buckets=(1, 8))}
        rng = np.random.default_rng(0)
        reqs = [rng.standard_normal((1, 32)).astype(np.float32)
                for _ in range(n_req)]
        expected = {v: [e.infer([x])[0] for x in reqs]
                    for v, e in refs.items()}

        chaos.reset()
        chaos.configure("replica_kill@10:1")  # replica 1's 10th request
        fleet = ServingFleet(
            f"{factory}:make_model", replicas=3, version="v1",
            model_arg="v1", max_batch=8, buckets=(1, 8),
            batch_timeout_ms=2, input_specs=[((32,), "float32")],
            warmup=True, retry_max=3, hang_timeout=30.0, poll_s=0.1,
            replica_timeout_ms=60000,
            # small in-flight cap: the burst must spread across all 3
            # replicas so the rank-qualified kill deterministically
            # sees replica 1's 10th request
            inflight_per_replica=8,
            env={"JAX_PLATFORMS": "cpu"},
            work_dir=os.path.join(tmp, "fleet"))
        fleet.start()

        def check(i, fut, out):
            ref = expected[fut.version][i]
            return float(np.max(np.abs(ref - out)))

        # phase 1: kill soak — the burst keeps all 3 replicas loaded
        # while the armed kill fires on replica 1
        futs = [fleet.submit(x) for x in reqs]
        outs = [f.result(timeout=300) for f in futs]
        kill_err = max(check(i, f, o)
                       for i, (f, o) in enumerate(zip(futs, outs)))

        # phase 2: steady-state throughput metric (best-of-2)
        def pump():
            fs = [fleet.submit(x) for x in reqs]
            return [f.result(timeout=300) for f in fs]
        (qps_bo,) = best_of(2, pump)
        qps = n_req / qps_bo.best_s

        # phase 3: hot-swap under load, mixed-version parity
        stop = threading.Event()
        swap: dict = {"pairs": [], "failures": []}

        def bg_pump():
            i = 0
            while not stop.is_set():
                i = (i + 1) % n_req
                try:
                    fut = fleet.submit(reqs[i])
                    out = fut.result(timeout=300)
                    swap["pairs"].append((i, fut, out))
                except Exception as e:  # noqa: broad-except — ANY
                    # failure during the swap (typed or not) fails the
                    # zero-drops gate below
                    swap["failures"].append(repr(e))
        bg = threading.Thread(target=bg_pump)
        bg.start()
        fleet.deploy(f"{factory}:make_model", "v2", model_arg="v2",
                     canary=[np.zeros((1, 32), np.float32)])
        stop.set()
        bg.join(timeout=300)
        swap_err = max((check(i, f, o) for i, f, o in swap["pairs"]),
                       default=0.0)
        swap_versions = sorted({f.version for _, f, _ in swap["pairs"]})
        post = fleet.submit(reqs[0])
        post_out = post.result(timeout=300)
        post_v2 = (post.version == "v2"
                   and check(0, post, post_out) <= 1e-6)

        # phase 4: failed canary rolls back, fleet still serving
        canary_failed = False
        try:
            fleet.deploy(f"{factory}:make_model", "v3",
                         model_arg="boom", ready_timeout_s=60)
        except DeployFailed:
            canary_failed = True
        still = fleet.submit(reqs[1])
        still_ok = (float(np.max(np.abs(
            expected["v2"][1] - still.result(timeout=300)))) <= 1e-6)

        report = fleet.drain()
        detail = {
            "requests": n_req, "replicas": 3,
            "fleet_qps": round(qps, 1),
            "kill_max_err": kill_err,
            "swap_max_err": swap_err,
            "swap_requests": len(swap["pairs"]),
            "swap_failures": swap["failures"][:3],
            "swap_versions": swap_versions,
            "post_swap_v2": post_v2,
            "canary_failed_typed": canary_failed,
            "serving_after_rollback": still_ok,
            "restarts": report["replica_restarts"],
            "retries": report["retries"],
            "failovers": report["failovers"],
            "rollbacks": report["rollbacks"],
            "unaccounted": report["unaccounted"],
            "accepted": report["accepted"],
            "completed": report["completed"],
        }
        ok = (report["unaccounted"] == 0
              and report["replica_restarts"] >= 1
              and kill_err <= 1e-6 and swap_err <= 1e-6
              and not swap["failures"]
              and len(swap["pairs"]) >= 1
              and post_v2 and canary_failed and still_ok
              and report["errors"] == 0
              and report["rollbacks"] == 1)
        _emit("serving_fleet_qps", qps, "req/s",
              1.0 if ok else 0.0, detail)
        if not ok:
            raise AssertionError(
                f"serving-fleet gate failed: {json.dumps(detail)}")
    finally:
        chaos.reset()
        shutil.rmtree(tmp, ignore_errors=True)


def bench_traffic(on_tpu, steps_override=None):
    """``--traffic``: one compressed production day against the CLOSED
    control loop (ISSUE 18 acceptance).

    An open-loop :mod:`paddle1_tpu.serving.traffic` schedule — diurnal
    ramp, a 10x flash crowd, heavy-tail payload sizes, mixed priority
    classes — is offered to a 2-replica ServingFleet whose only
    capacity knob is the Autoscaler (SLO burn + queue-EWMA signals
    against a min=2/max=4 policy), chaos-composed with a
    ``replica_kill`` aimed at rank 2: the FIRST rank the autoscaler
    spawns, so the kill deterministically lands mid-flash on the
    replica the scale-out just added, and the Supervisor must heal it
    while the crowd is still arriving. Traffic rates are calibrated
    from the fleet's own measured steady capacity so the flash peak
    lands ~1.4x above it on any host — saturation by construction,
    not by tuning to one machine — and the day LENGTH is calibrated
    from the measured replica spawn+warmup cost, so the post-flash
    window always fits the spawn, the chaos kill + supervised
    restart, and the scale-in dwell, on slow hosts as on fast ones
    (``--steps`` overrides the day length in seconds). Gates:

    * **SLO held** — admitted-traffic p99 stays inside the declared
      ``p99(e2e_ms) < SLO`` through the flash and the kill (typed
      sheds are accounted back-pressure, not failures — the bounded
      fleet queue is what keeps admitted latency bounded while the
      crowd is shed).
    * **elastic, not greedy** — the ready-replica integral costs
      <= 2x the steady-state floor's replica-hours, and the loop both
      scaled OUT (>= 1) and back IN (>= 1): capacity returned after
      the crowd passed.
    * **zero client-visible failures** — no errored admitted request,
      no synchronous non-typed submit failure, and the drain report
      proves unaccounted == 0 with >= 1 supervised replica restart.
    * **journaled** — every applied scaling transition appears in the
      obs/events journal as an ``autoscale_decision`` record with a
      matching fleet-side ``fleet_scale`` record.
    * **cheap** — summed ``autoscale_decision_seconds`` < 1% of the
      day's wall clock, and the ``autoscale_*`` families are
      structurally ABSENT before the Autoscaler exists (proved by
      peek, which never materializes a family).

    Emits two ratchet lines: ``traffic_slo_headroom`` (declared SLO
    over observed admitted p99 — regresses DOWN) and
    ``traffic_replica_hours_frac`` (replica-hour integral over the
    steady-state floor — regresses UP). ``vs_baseline`` is 1.0 iff
    every gate holds."""
    import os
    import shutil
    import tempfile
    import threading

    from paddle1_tpu.obs import events as obs_events
    from paddle1_tpu.obs import slo as obs_slo
    from paddle1_tpu.serving import Autoscaler, ServingFleet, parse_policy
    from paddle1_tpu.serving import traffic as traffic_mod

    if steps_override is not None and float(steps_override) < 12:
        raise SystemExit(
            f"--traffic needs --steps >= 12 (got "
            f"{float(steps_override):g}): the day is --steps seconds "
            "long and must fit the flash crowd plus the scale-in "
            "dwell after it")
    slo_ms = 1000.0
    steady_replicas = 2
    queue_cap = 64
    tmp = tempfile.mkdtemp(prefix="p1t_trafficbench_")
    journal = os.path.join(tmp, "events.jsonl")
    prev_journal = os.environ.get(obs_events.EVENTS_ENV)
    os.environ[obs_events.EVENTS_ENV] = journal
    scaler = None
    try:
        factory = os.path.join(tmp, "factory.py")
        with open(factory, "w") as f:
            f.write(_FLEET_FACTORY)
        fleet = ServingFleet(
            f"{factory}:make_model", replicas=steady_replicas,
            version="v1", model_arg="v1", max_batch=8, buckets=(1, 8),
            batch_timeout_ms=2, input_specs=[((32,), "float32")],
            warmup=True, retry_max=3, hang_timeout=30.0, poll_s=0.05,
            replica_timeout_ms=60000, inflight_per_replica=8,
            fleet_queue_depth=queue_cap,
            # rank 2 does not exist yet: the kill can only fire on the
            # replica the autoscaler's first scale-out creates
            chaos_spec="replica_kill@20:2",
            env={"JAX_PLATFORMS": "cpu"},
            work_dir=os.path.join(tmp, "fleet"))
        fleet.start()
        rng = np.random.default_rng(0)
        xs = {r: rng.standard_normal((r, 32)).astype(np.float32)
              for r in range(1, 9)}
        t_warm = time.perf_counter()
        for r in (1, 8):
            fleet.submit(xs[r]).result(timeout=300)
        # the steady replicas spawned + warmed CONCURRENTLY behind
        # those first submits — this wall time is one replica's
        # spawn cost, the same latency the autoscaler's (parallel)
        # scale-out will pay mid-flash
        spawn_s = time.perf_counter() - t_warm

        # structural zero BEFORE any Autoscaler exists: peek (never
        # materialize) proves the disabled loop costs no families
        fams = ("autoscale_decisions_total", "autoscale_scale_out_total",
                "autoscale_scale_in_total", "autoscale_refusals_total",
                "autoscale_queue_ratio", "autoscale_burn_max_ratio",
                "autoscale_target_replicas",
                "autoscale_decision_seconds")
        disabled_zero = all(fleet.metrics.peek(n) is None for n in fams)

        # calibrate steady capacity: bounded-concurrency closed loop
        # (24 outstanding < queue_cap, so nothing sheds)
        cal_s, cal_done, cal_lock = 2.5, [0], threading.Lock()
        cal_stop = time.perf_counter() + cal_s

        def _cal(k):
            i = 0
            while time.perf_counter() < cal_stop:
                fleet.submit(xs[1 + (i + k) % 8]).result(timeout=300)
                with cal_lock:
                    cal_done[0] += 1
                i += 1
        ths = [threading.Thread(target=_cal, args=(k,))
               for k in range(24)]
        for t in ths:
            t.start()
        for t in ths:
            t.join()
        qps0 = cal_done[0] / cal_s

        # day length from the measured spawn cost: the flash lands at
        # 0.35*day, the (async-actuated, parallel) scale-out completes
        # ~spawn_s later under flash load, the chaos kill + supervised
        # restart ride on top, and the scale-in needs its dwell AFTER
        # all of that — 2*spawn + 12 keeps every phase inside the day
        # on any host; the cap bounds CI wall-clock
        dur = (float(steps_override) if steps_override is not None
               else max(20.0, min(48.0, round(2.0 * spawn_s + 12.0))))

        # steady at 1/8 capacity; the 10x flash peaks ~1.45x ABOVE
        # capacity (10 * 1.16 diurnal / 8) — pressure by construction
        model = traffic_mod.parse_traffic(
            f"rps={qps0 / 8.0:.1f};dur={dur:g};diurnal=0.2;"
            f"flash=10x@{0.35 * dur:g}+{0.2 * dur:g};"
            "tail=1.3;len=1:8;prio=0:0.7,1:0.2,2:0.1;seed=0")
        arrivals = traffic_mod.schedule(model)
        policy = parse_policy(
            f"min={steady_replicas};max=4;queue_hi=0.5;queue_lo=0.05;"
            "burn_hi=1.0;burn_lo=0.5;step=2;cooldown=2;"
            f"dwell={0.15 * dur:g};backoff=3;interval=0.25")
        slos = obs_slo.parse_slos(f"lat=p99(e2e_ms)<{slo_ms:g}")
        scaler = Autoscaler(fleet, policy, slos=slos).start()

        ready_samples: list = []

        def on_tick(now_s):
            ready_samples.append(fleet.ready_replicas())

        def submit(a):
            return fleet.submit(xs[min(8, max(1, a.length))],
                                priority=a.priority)

        t0 = time.perf_counter()
        stats = traffic_mod.run(arrivals, submit, tick_s=0.25,
                                on_tick=on_tick, result_timeout_s=120)
        wall = time.perf_counter() - t0
        scaler.stop()

        def _count(name):
            hit = fleet.metrics.peek(name)
            return int(hit[1].value) if hit else 0
        outs, ins = (_count("autoscale_scale_out_total"),
                     _count("autoscale_scale_in_total"))
        hit = fleet.metrics.peek("autoscale_decision_seconds")
        ticks, loop_s = hit[1].totals() if hit else (0, 0.0)
        overhead = loop_s / max(wall, 1e-9)

        events = obs_events.read_events(journal)
        dec_ev = [e for e in events
                  if e.get("event") == "autoscale_decision"]
        scale_ev = [e for e in events if e.get("event") == "fleet_scale"
                    and e.get("kind") == "serving"]
        journaled = (len(dec_ev) == outs + ins
                     and len(scale_ev) >= outs + ins)

        report = fleet.drain()
        p99 = stats["latency_ms"]["p99"]
        replica_s = 0.25 * sum(ready_samples)
        hours_frac = replica_s / (steady_replicas * dur)
        detail = {
            "day_s": dur, "spawn_s": round(spawn_s, 2),
            "calibrated_qps": round(qps0, 1),
            "steady_rps": round(qps0 / 8.0, 1),
            "offered": stats["offered"], "admitted": stats["admitted"],
            "shed_typed": stats["shed"],
            "submit_failed": stats["submit_failed"],
            "completed": stats["completed"], "errors": stats["errors"],
            "error_types": stats["error_types"],
            "admitted_p99_ms": p99, "slo_ms": slo_ms,
            "lateness_p99_ms": stats["lateness_p99_ms"],
            "scale_outs": outs, "scale_ins": ins,
            "refusals": _count("autoscale_refusals_total"),
            "decision_ticks": ticks,
            "loop_overhead_frac": round(overhead, 5),
            "disabled_structurally_zero": disabled_zero,
            "decision_events": len(dec_ev),
            "fleet_scale_events": len(scale_ev),
            "replica_hours_frac": round(hours_frac, 3),
            "restarts": report["replica_restarts"],
            "unaccounted": report["unaccounted"],
        }
        ok = (stats["errors"] == 0 and stats["submit_failed"] == 0
              and stats["admitted"] == stats["completed"]
              and 0.0 < p99 <= slo_ms
              and outs >= 1 and ins >= 1 and journaled
              and hours_frac <= 2.0
              and overhead < 0.01 and disabled_zero
              and report["replica_restarts"] >= 1
              and report["unaccounted"] == 0)
        _emit("traffic_slo_headroom", slo_ms / max(p99, 1e-6), "x",
              1.0 if ok else 0.0, detail)
        _emit("traffic_replica_hours_frac", hours_frac, "x",
              1.0 if ok else 0.0, detail)
        if not ok:
            # post-mortem: the decision journal says WHY the loop held
            tail = [f"{d.action}->{d.target}: {d.reason}"
                    for d in scaler.decisions()[-30:]]
            raise AssertionError(
                f"traffic gate failed: {json.dumps(detail)}\n"
                f"decision journal tail:\n  " + "\n  ".join(tail))
    finally:
        if scaler is not None:
            scaler.stop()
        if prev_journal is None:
            os.environ.pop(obs_events.EVENTS_ENV, None)
        else:
            os.environ[obs_events.EVENTS_ENV] = prev_journal
        shutil.rmtree(tmp, ignore_errors=True)


_GENFLEET_FACTORY = '''
"""bench --generate-fleet replica model: a tiny causal LM whose weights
are a pure function of the seed, so every replica process — and the
in-process reference server — decode bit-identical token streams.
arg "boom" raises (a broken artifact, unused here but kept symmetric
with the serving-fleet factory)."""


def make_model(arg):
    if arg == "boom":
        raise RuntimeError("broken artifact")
    import paddle1_tpu as paddle
    paddle.seed(0)
    return paddle.serving.CausalLM(
        vocab_size=32, d_model=16, nhead=2, dim_feedforward=32,
        num_layers=2, max_seq=64)
'''


def bench_generate_fleet(on_tpu, steps_override=None):
    """``--generate-fleet``: chaos soak of the fault-tolerant
    generative serving layer (ISSUE 17 acceptance).

    * **kill failover** — three GenerationServer replica subprocesses
      under the GenerationFleet; ``gen_replica_kill`` SIGKILLs replicas
      mid-stream (the pigeonhole over the armed frame count guarantees
      at least one fires); every accepted stream — greedy AND sampled —
      completes **bit-identical** to the uninterrupted single-process
      reference with zero client-visible failures, the drain ledger
      balances (``unaccounted == 0``), and each replica process
      compiled exactly one decode signature (failover replays ride the
      prefill buckets, never a new decode shape).
    * **KV-pressure preemption** — an in-process server over a tight
      paged pool with ``gen_page_pressure`` chaos claiming every free
      page mid-decode: the low-priority streams preempt (pages
      released, stream parked) and re-admit by replay, finishing
      bit-identical to a pressure-free run; ``KVPoolExhausted`` is
      never client-visible and the page ledger drains to zero.

    ``vs_baseline`` is 1.0 iff every gate holds; the metric is
    fleet-wide decode throughput through the kill soak (restart cost
    included — this is the availability number, not the happy path).
    """
    import os
    import shutil
    import tempfile

    from paddle1_tpu.core import chaos
    from paddle1_tpu.serving import (CausalLM, GenerationEngine,
                                     GenerationFleet, GenerationServer)
    import paddle1_tpu as paddle

    n_streams = steps_override or 8
    max_new = 12

    def specs(n):
        out = []
        for i in range(n):
            s = {"prompt": [2 + i % 20, 7, 1 + (i % 3), 9],
                 "max_new": max_new, "seed": 50 + i}
            if i % 2:  # half greedy, half sampled: parity must hold
                s.update(temperature=0.8, top_k=8)  # for both
            out.append(s)
        return out

    def reference(sp):
        paddle.seed(0)
        lm = CausalLM(vocab_size=32, d_model=16, nhead=2,
                      dim_feedforward=32, num_layers=2, max_seq=64)
        srv = GenerationServer(lm, slots=4, max_seq=64,
                               prefill_buckets=(8, 24)).start()
        try:
            return [srv.generate(s["prompt"],
                                 max_new_tokens=s["max_new"],
                                 temperature=s.get("temperature", 0.0),
                                 top_k=s.get("top_k", 0),
                                 seed=s["seed"])
                    for s in sp]
        finally:
            srv.drain()

    tmp = tempfile.mkdtemp(prefix="p1t_genfleetbench_")
    try:
        factory = os.path.join(tmp, "factory.py")
        with open(factory, "w") as f:
            f.write(_GENFLEET_FACTORY)
        sp = specs(n_streams)
        ref = reference(sp)

        # -- arm 1: kill failover, bit-identical mid-stream ----------
        chaos.reset()
        fleet = GenerationFleet(
            f"{factory}:make_model", replicas=3, version="v1",
            slots=4, max_seq=64, prefill_buckets=(8, 24), warmup=True,
            retry_max=5, streams_per_replica=4,
            hang_timeout=60.0, poll_s=0.1, ready_timeout_s=300.0,
            stream_timeout_ms=60000.0,
            chaos_spec="gen_replica_kill@10",
            env={"JAX_PLATFORMS": "cpu"},
            work_dir=os.path.join(tmp, "genfleet"))
        fleet.start()
        failures = []
        t0 = time.perf_counter()
        try:
            streams = [fleet.submit(s["prompt"],
                                    max_new_tokens=s["max_new"],
                                    temperature=s.get("temperature",
                                                      0.0),
                                    top_k=s.get("top_k", 0),
                                    seed=s["seed"]) for s in sp]
            outs = []
            for st in streams:
                try:
                    outs.append(st.result(timeout=300))
                except Exception as e:  # noqa: broad-except — ANY
                    # client-visible failure fails the zero-drops gate
                    failures.append(repr(e))
                    outs.append(None)
        finally:
            kill_dt = time.perf_counter() - t0
            rep = fleet.drain()
        kill_identical = outs == ref
        one_decode_sig = all(
            info.get("decode_compiles", 99) <= 1
            for info in rep["replicas"].values())
        pools_clean = all(
            (info.get("pool") or {}).get("pages_in_use", 0) == 0
            for info in rep["replicas"].values())
        tokens = sum(len(o) for o in outs if o is not None)
        tps = tokens / kill_dt if kill_dt > 0 else 0.0

        # -- arm 2: KV-pressure preemption, park + replay ------------
        def pressure_run(pressure):
            chaos.reset()
            if pressure:
                chaos.configure("gen_page_pressure@3")
            paddle.seed(0)
            lm = CausalLM(vocab_size=32, d_model=16, nhead=2,
                          dim_feedforward=32, num_layers=2, max_seq=64)
            eng = GenerationEngine(lm, slots=4, max_seq=64,
                                   prefill_buckets=(8, 24), paged=True,
                                   page_size=8, pages=16,
                                   prefix_cache=0)
            srv = GenerationServer(eng, preempt=True).start()
            try:
                sts = [srv.submit(s["prompt"], max_new_tokens=16,
                                  temperature=0.7, top_k=6,
                                  seed=s["seed"],
                                  # stream 0 is the high-priority one
                                  # the preemptor must never park
                                  priority=(0 if i == 0 else 2))
                       for i, s in enumerate(sp[:3])]
                res = [st.result(timeout=300) for st in sts]
            finally:
                prep = srv.drain()
            counters = srv.metrics.snapshot()["counters"]
            return res, prep, counters

        calm, calm_rep, _ = pressure_run(pressure=False)
        hot, hot_rep, hot_counters = pressure_run(pressure=True)
        preempt_identical = hot == calm
        preemptions = hot_counters.get("gen_preemptions_total", 0)
        readmits = hot_counters.get("gen_preempt_readmits_total", 0)

        detail = {
            "streams": n_streams, "replicas": 3, "max_new": max_new,
            "fleet_tokens_per_s": round(tps, 1),
            "kill_identical": kill_identical,
            "client_failures": failures[:3],
            "failovers": rep["failovers"],
            "retries": rep["retries"],
            "replica_restarts": rep["replica_restarts"],
            "dup_tokens_dropped": rep["dup_tokens_dropped"],
            "unaccounted": rep["unaccounted"],
            "one_decode_signature_per_replica": one_decode_sig,
            "replica_pools_drained": pools_clean,
            "preempt_identical": preempt_identical,
            "preemptions": preemptions,
            "preempt_readmits": readmits,
            "pressure_kv_pages_owed": hot_rep.get("kv_pages_owed", 0),
        }
        ok = (kill_identical and not failures
              and rep["unaccounted"] == 0
              and rep["errors"] == 0 and rep["stream_failed"] == 0
              and rep["failovers"] >= 1
              and rep["replica_restarts"] >= 1
              and one_decode_sig and pools_clean
              and preempt_identical
              and preemptions >= 1 and readmits >= 1
              and calm_rep["unaccounted"] == 0
              and hot_rep["unaccounted"] == 0
              and hot_rep.get("kv_pages_owed", 0) == 0)
        _emit("generate_fleet_tokens_per_s", tps, "tok/s",
              1.0 if ok else 0.0, detail)
        if not ok:
            raise AssertionError(
                f"generate-fleet gate failed: {json.dumps(detail)}")
    finally:
        chaos.reset()
        shutil.rmtree(tmp, ignore_errors=True)


_RECO_FACTORY = '''
"""bench --recommender serving replica: a raw embedding-row lookup
over the FULL logical vocab, zero-initialized — a served row is
non-zero only if the trainer's delta log delivered it, so the parity
check below exercises exactly the online-learning path."""


def make_model(arg):
    import jax.numpy as jnp
    import paddle1_tpu as paddle

    vocab, dim = (int(s) for s in arg.split("x"))

    class _Lookup(paddle.nn.Layer):
        def __init__(self):
            super().__init__()
            self.emb = paddle.nn.Embedding(vocab, dim)
            self.emb.weight._data = jnp.zeros((vocab, dim), jnp.float32)

        def forward(self, ids):
            return self.emb(ids)

    m = _Lookup()
    m.eval()
    return m
'''


def bench_recommender(on_tpu, steps_override=None):
    """``--recommender``: the ISSUE 19 sharded-embedding acceptance.

    A synthetic CTR model embeds a LOGICAL vocabulary ~50x larger than
    the hot device table (200k logical rows, a 4096-slot HBM table
    row-sharded over the mesh's 'sharding' axis with a 2048-row
    admission budget) through the ShardedEmbeddingEngine tier bridge:
    route() admits/demotes host-side between steps, the jitted step
    sees only fixed-shape slot gathers. Gates (vs_baseline 1.0 iff all
    hold):

    * **one dispatch per step** — ``dispatch_count == steps`` and at
      most one retrace after warmup, despite rows moving between tiers
      every step (the tentpole's fused-lookup claim).
    * **budgeted occupancy, exactly-once moves** — the census 'embed'
      bytes never exceed budget x row_bytes, residency never exceeds
      the budget, eviction actually happened (demote_total > 0), and
      the admit/demote ledger balances after every step.
    * **online-learning loop closed** — the trainer's drained delta
      (changed rows + version) lands on a LIVE ServingFleet replica
      through the delta log in < 5 s, and the served rows match the
      trainer's at 1e-6 (zeros before, trained values after — the
      click-feedback-to-serving path, no redeploy).

    Metric: trainer samples/s through the tiered table (route + step).
    """
    import os
    import shutil
    import tempfile

    import jax

    import paddle1_tpu as paddle
    from paddle1_tpu.core.tensor import Tensor
    from paddle1_tpu.distributed import (DeltaLog, EmbeddingService,
                                         HBMShardedEmbedding,
                                         ParallelEngine,
                                         ShardedEmbeddingEngine,
                                         build_mesh)
    from paddle1_tpu.nn import TieredEmbedding
    from paddle1_tpu.obs import MetricsRegistry
    from paddle1_tpu.obs import hbm as obs_hbm
    from paddle1_tpu.serving import ServingFleet

    steps = int(steps_override or 30)
    if steps < 10:
        raise SystemExit(
            f"--recommender needs --steps >= 10 (got {steps}): the "
            "working set must churn through the admission budget for "
            "the eviction gates to mean anything")
    VOCAB, DIM, CAP, BUDGET = 200_000, 16, 4096, 2048
    BATCH, FEATS = 64, 8
    shard_n = 4 if len(jax.devices()) >= 4 else 1
    mesh = build_mesh(sharding=shard_n,
                      devices=jax.devices()[:shard_n])

    paddle.seed(0)
    hbm = HBMShardedEmbedding(CAP, DIM, axis="sharding",
                              axis_size=shard_n)
    host = EmbeddingService(DIM, num_shards=4, optimizer="sgd", lr=0.1)
    metrics = MetricsRegistry()
    eng = ShardedEmbeddingEngine(hbm, host, hbm_row_budget=BUDGET,
                                 metrics=metrics)

    class _CTR(paddle.nn.Layer):
        def __init__(self):
            super().__init__()
            self.emb = TieredEmbedding(eng)
            self.head = paddle.nn.Linear(DIM, 1)

        def forward(self, slots):
            return self.head(self.emb(slots).mean(axis=1))

    model = _CTR()
    opt = paddle.optimizer.SGD(learning_rate=0.1,
                               parameters=model.parameters())
    peng = ParallelEngine(
        model, opt,
        lambda m, b: ((m(Tensor(b["slots"])) - Tensor(b["y"])) ** 2
                      ).mean(),
        mesh=mesh, zero_stage=0)
    eng.bind_engine(peng)

    rng = np.random.default_rng(0)

    def draw_ids():
        # production-shaped skew: 80% of lookups hit a 2k-row hot set,
        # 20% the full 200k logical tail — hits AND steady eviction
        hot = rng.integers(0, 2_000, (BATCH, FEATS))
        cold = rng.integers(0, VOCAB, (BATCH, FEATS))
        pick = rng.random((BATCH, FEATS)) < 0.8
        return np.where(pick, hot, cold).astype(np.int64)

    row_bytes = eng.row_bytes
    max_occ = 0
    ledger_ok = True
    t0 = time.perf_counter()
    for _ in range(steps):
        ids = draw_ids()
        slots = eng.route(ids)
        y = rng.random((BATCH, 1)).astype(np.float32)
        peng.step({"slots": slots, "y": y})
        occ = obs_hbm.registered_bytes()["embed"]
        max_occ = max(max_occ, occ)
        acc = eng.accounting()
        ledger_ok = ledger_ok and acc["balanced"] \
            and acc["resident"] <= BUDGET
    _read_back(peng.params)
    elapsed = time.perf_counter() - t0
    sps = steps * BATCH / elapsed
    acc = eng.accounting()
    eng.publish_gauges()

    dispatch_ok = (peng.dispatch_count == steps
                   and peng.trace_count <= 2)
    occupancy_ok = max_occ <= BUDGET * row_bytes and ledger_ok
    eviction_ok = acc["demote_total"] > 0 and acc["balanced"]

    # -- the online-learning loop against a LIVE fleet replica --------------
    tmp = tempfile.mkdtemp(prefix="p1t_recobench_")
    delta_ok = False
    delta_latency_s = float("inf")
    fleet = None
    try:
        factory = os.path.join(tmp, "factory.py")
        with open(factory, "w") as f:
            f.write(_RECO_FACTORY)
        delta_dir = os.path.join(tmp, "deltas")
        fleet = ServingFleet(
            f"{factory}:make_model", replicas=1, version="v1",
            model_arg=f"{VOCAB}x{DIM}", max_batch=8, buckets=(1, 8),
            batch_timeout_ms=2, input_specs=[((FEATS,), "int64")],
            delta_dir=delta_dir, delta_poll_ms=20,
            env={"JAX_PLATFORMS": "cpu"},
            work_dir=os.path.join(tmp, "fleet"))
        fleet.start()
        dirty_ids, dirty_rows = eng.drain_dirty()
        probe = dirty_ids[:FEATS]
        want = dirty_rows[:FEATS]
        # zeros before the delta: the rows can only arrive via the log
        pre = np.asarray(fleet.submit(
            probe[None, :]).result(timeout=300))
        t0 = time.perf_counter()
        DeltaLog(delta_dir).publish("emb.weight", dirty_ids, dirty_rows)
        while time.perf_counter() - t0 < 5.0:
            out = np.asarray(fleet.submit(
                probe[None, :]).result(timeout=300))
            if np.allclose(out[0], want, rtol=1e-6, atol=1e-6):
                delta_latency_s = time.perf_counter() - t0
                delta_ok = True
                break
            time.sleep(0.02)
        delta_ok = delta_ok and np.allclose(pre, 0.0)
    finally:
        if fleet is not None:
            fleet.drain()
        shutil.rmtree(tmp, ignore_errors=True)

    detail = {
        "steps": steps, "batch": BATCH, "feats": FEATS,
        "logical_vocab": VOCAB, "hbm_capacity": CAP,
        "hbm_row_budget": BUDGET,
        "logical_over_hot_ratio": round(VOCAB / CAP, 1),
        "mesh_sharding": shard_n,
        "dispatch_count": peng.dispatch_count,
        "trace_count": peng.trace_count,
        "max_embed_bytes": int(max_occ),
        "budget_bytes": BUDGET * row_bytes,
        "resident_rows": acc["resident"],
        "host_rows": len(host),
        "admit_total": acc["admit_total"],
        "demote_total": acc["demote_total"],
        "hit_rate": round(acc["hit_total"] / max(
            1, acc["hit_total"] + acc["miss_total"]), 3),
        "delta_rows": int(np.size(dirty_ids)),
        "delta_latency_s": (round(delta_latency_s, 3)
                            if delta_ok else None),
        "dispatch_ok": dispatch_ok, "occupancy_ok": occupancy_ok,
        "eviction_ok": eviction_ok, "delta_ok": delta_ok,
    }
    ok = dispatch_ok and occupancy_ok and eviction_ok and delta_ok
    _emit("recommender_samples_per_s", sps, "samples/s",
          1.0 if ok else 0.0, detail)
    if not ok:
        raise AssertionError(
            f"recommender gate failed: {json.dumps(detail)}")


def bench_recommender_chaos(on_tpu, steps_override=None):
    """``--recommender-chaos``: the durable-recommender acceptance.

    Runs the same deterministic tiered-embedding training loop twice —
    once clean, once faulted — against a REAL supervised table-server
    subprocess and a live in-process serving replica fed by the delta
    log. The faulted run composes every recommender fault in one life:

    * ``ps_kill`` mid-epoch — the table server is SIGKILLed after it
      applied+checkpointed a push but BEFORE the ack; the Supervisor
      restarts it from its own checkpoint and the client's retry is
      deduplicated by the push-epoch fence (exactly-once, no double
      apply).
    * a trainer preemption — every in-process object is discarded and
      rebuilt, then ``restore_latest`` reloads params/opt + the embed
      sidecar (admission ledger, LFU/TTL bookkeeping, host-tier rows)
      and overwrites the PS with the checkpoint-consistent state.
    * ``delta_corrupt`` + ``delta_gap`` on the live replica — a
      bit-flipped delta file is skipped+counted, a pruned-away version
      range surfaces as a typed gap, and the replica resyncs from the
      trainer's next full snapshot, then keeps applying deltas.

    vs_baseline is 1.0 iff the faulted run's final params AND the full
    logical table (demote_all + PS readback) match the clean run to
    1e-6, the admit/demote ledger balances with unaccounted == 0,
    exactly one PS restart happened with client retries > 0, the gap
    and resync counters fired, and the replica's served rows converge
    to the trainer's table at 1e-6.
    """
    import os
    import shutil
    import socket
    import sys
    import tempfile
    import threading

    import jax
    import paddle1_tpu as paddle
    from paddle1_tpu.core import chaos
    from paddle1_tpu.core.tensor import Tensor
    from paddle1_tpu.distributed import (DeltaLog, EmbeddingService,
                                         HBMShardedEmbedding,
                                         ParallelEngine, ResilientTrainer,
                                         ShardedEmbeddingEngine,
                                         build_mesh)
    from paddle1_tpu.distributed.embedding_delta import DeltaSubscriber
    from paddle1_tpu.distributed.ps_server import RemoteTable
    from paddle1_tpu.distributed.supervisor import Supervisor
    from paddle1_tpu.obs import MetricsRegistry
    from paddle1_tpu.obs import registry as obs_registry
    from paddle1_tpu.serving.engine import InferenceEngine

    steps = int(steps_override or 18)
    if steps < 12:
        raise SystemExit(
            f"--recommender-chaos needs --steps >= 12 (got {steps}): "
            "the faulted run must fit a checkpoint, a preemption AFTER "
            "it, and a snapshot-driven resync")
    SAVE = max(steps // 3, 1)          # trainer checkpoint cadence
    SNAP = max(steps // 3, 1)          # full-snapshot publish cadence
    PREEMPT = SAVE + max(SAVE // 2, 1)  # between the 1st and 2nd save
    KILL_REQ = 8                        # ~3rd step's PS traffic
    GAP_PUB = 4                         # prune at the 4th delta publish
    CORRUPT_PUB = 2                     # bit-flip the 2nd delta file
    VOCAB, DIM, CAP, BUDGET = 5_000, 8, 256, 128
    BATCH, FEATS = 32, 4

    rng = np.random.default_rng(0)

    def _draw():
        hot = rng.integers(0, 500, (BATCH, FEATS))
        cold = rng.integers(0, VOCAB, (BATCH, FEATS))
        pick = rng.random((BATCH, FEATS)) < 0.8
        return np.where(pick, hot, cold).astype(np.int64)

    # precomputed so a replayed step re-feeds the identical batch
    ids_seq = [_draw() for _ in range(steps)]
    ys = [rng.random((BATCH, 1)).astype(np.float32)
          for _ in range(steps)]

    repo = os.path.dirname(os.path.abspath(__file__))
    tmp = tempfile.mkdtemp(prefix="p1t_recochaos_")

    def _free_port():
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        p = s.getsockname()[1]
        s.close()
        return p

    def _logical_rows(eng, ids):
        """Current trainer-side values for logical ids, whichever tier
        holds them (the snapshot payload)."""
        rows = np.zeros((len(ids), DIM), np.float32)
        res, cold = [], []
        for k, i in enumerate(ids):
            (res if eng.tier_of(int(i)) == "hbm"
             else cold).append((k, int(i)))
        if res:
            got = eng.read_rows(np.asarray(
                [eng._slot_of[i] for _, i in res], np.int64))
            for (k, _), r in zip(res, got):
                rows[k] = r
        if cold:
            got = eng.host.pull(np.asarray([i for _, i in cold],
                                           np.int64))
            for (k, _), r in zip(cold, got):
                rows[k] = r
        return rows

    def run(tag, faulted):
        base = os.path.join(tmp, tag)
        os.makedirs(base, exist_ok=True)
        delta_dir = os.path.join(base, "deltas")
        os.makedirs(delta_dir, exist_ok=True)
        port = _free_port()
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
        env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
        if faulted:
            env["FLAGS_ft_chaos"] = f"ps_kill@{KILL_REQ}"
        sup = Supervisor(policy="restart", max_restarts=2,
                         hang_timeout=30.0,
                         heartbeat_dir=os.path.join(base, "hb"),
                         poll_s=0.1, grace_s=5.0)
        sup.add_worker(
            0, [sys.executable, "-m",
                "paddle1_tpu.distributed.ps_server",
                "--dim", str(DIM), "--port", str(port),
                "--optimizer", "sgd", "--lr", "0.1", "--init", "zeros",
                "--ckpt-dir", os.path.join(base, "ps-ckpt"),
                "--save-every", "1"],
            env=env, role="ps", essential=False,
            log_path=os.path.join(base, "ps.log"))
        sup.start()
        stop_evt = threading.Event()

        def _sweep():
            while not stop_evt.is_set():
                sup.supervise_once()
                stop_evt.wait(0.1)

        sweeper = threading.Thread(target=_sweep, daemon=True)
        sweeper.start()

        # the live replica: zero-init lookup fed only by the delta log
        class _Replica(paddle.nn.Layer):
            def __init__(self):
                super().__init__()
                self.emb = paddle.nn.Embedding(VOCAB, DIM)
                self.emb.weight._data = jax.numpy.zeros(
                    (VOCAB, DIM), jax.numpy.float32)

            def forward(self, ids):
                return self.emb(ids)

        reng = InferenceEngine(_Replica(), buckets=(1, 8))
        reg = MetricsRegistry()
        sub = DeltaSubscriber(delta_dir, reng.update_param_rows,
                              poll_s=0.02, metrics=reg).start()

        def build():
            paddle.seed(0)
            hbm = HBMShardedEmbedding(CAP, DIM)
            remote = RemoteTable(f"127.0.0.1:{port}", timeout=10.0,
                                 max_retries=40, backoff_base_s=0.02,
                                 backoff_max_s=0.25)
            host = EmbeddingService(DIM, shards=[remote])
            eng = ShardedEmbeddingEngine(hbm, host, hbm_row_budget=BUDGET)

            class _CTR(paddle.nn.Layer):
                def __init__(self):
                    super().__init__()
                    from paddle1_tpu.nn import TieredEmbedding
                    self.emb = TieredEmbedding(eng)
                    self.head = paddle.nn.Linear(DIM, 1)

                def forward(self, slots):
                    return self.head(self.emb(slots).mean(axis=1))

            model = _CTR()
            opt = paddle.optimizer.SGD(learning_rate=0.1,
                                       parameters=model.parameters())
            peng = ParallelEngine(
                model, opt,
                lambda m, b: ((m(Tensor(b["slots"])) - Tensor(b["y"]))
                              ** 2).mean(),
                mesh=build_mesh(dp=1, devices=jax.devices()[:1]),
                check_finite=True)
            eng.bind_engine(peng)
            tr = ResilientTrainer(peng, os.path.join(base, "ckpts"),
                                  save_freq=SAVE, backoff_base_s=0.0)
            tr.attach_embedding(eng)
            return eng, peng, tr

        chaos.reset()
        if faulted:
            chaos.configure(f"delta_corrupt@{CORRUPT_PUB},"
                            f"delta_gap@{GAP_PUB}")
        preg = obs_registry.process_registry()
        retries0 = preg.counter("ft_ps_retries_total").value
        eng, peng, tr = build()
        dlog = DeltaLog(delta_dir)
        resumed_from = None
        try:
            step = 0
            while step < steps:
                slots = eng.route(ids_seq[step], now=float(step))
                peng.step({"slots": slots, "y": ys[step]})
                d_ids, d_rows = eng.drain_dirty()
                if d_ids.size:
                    dlog.publish("emb.weight", d_ids, d_rows)
                step += 1
                if step % SAVE == 0:
                    tr.save(step)
                if step % SNAP == 0:
                    ever = sorted(eng._ever)
                    dlog.publish_snapshot(
                        "emb.weight", np.asarray(ever, np.int64),
                        _logical_rows(eng, ever))
                if faulted and resumed_from is None and step == PREEMPT:
                    # simulated preemption: every in-process object is
                    # lost; the rebuilt stack restores params + the
                    # embed sidecar and rolls the PS back with it
                    eng, peng, tr = build()
                    dlog = DeltaLog(delta_dir)
                    step = resumed_from = tr.restore_latest()
            peng.drain()
            params = {k: np.asarray(v) for k, v in peng.params.items()}
            acc = eng.accounting()
            eng.demote_all()
            tstate = eng.host.state_dict()
            table = {}
            for sd in tstate["shards"]:
                for i, r in sd["rows"].items():
                    table[int(i)] = np.asarray(r, np.float32)
            # replica convergence: every trained row arrived through
            # deltas (or the post-gap snapshot resync) — compare the
            # served bytes against the trainer's table
            trained = np.asarray(sorted(eng._ever), np.int64)
            want = np.stack([table[int(i)] for i in trained])
            replica_err = float("inf")
            deadline = time.perf_counter() + 15.0
            while time.perf_counter() < deadline:
                got = reng.param_rows("emb.weight", trained)
                replica_err = float(np.max(np.abs(got - want)))
                if replica_err <= 1e-6:
                    break
                time.sleep(0.05)
            stale = reg.gauge("embed_delta_staleness_seconds").value
            return {
                "params": params, "table": table, "acc": acc,
                "replica_err": replica_err,
                "staleness_s": float(stale),
                "resumed_from": resumed_from,
                "restarts": sup.report.total_restarts,
                "ps_retries": (preg.counter("ft_ps_retries_total").value
                               - retries0),
                "gaps": reg.counter("delta_gaps_total").value,
                "resyncs": reg.counter("delta_resyncs_total").value,
                "corrupt": reg.counter("delta_corrupt_total").value,
            }
        finally:
            chaos.reset()
            sub.stop()
            stop_evt.set()
            sweeper.join(timeout=5.0)
            try:
                sup.kill_worker(0)
            except Exception:
                pass

    try:
        t0 = time.perf_counter()
        clean = run("clean", faulted=False)
        faulted = run("faulted", faulted=True)
        dt = time.perf_counter() - t0

        max_err = max(
            float(np.max(np.abs(clean["params"][k] -
                                faulted["params"][k])))
            for k in clean["params"])
        table_err = 0.0
        table_ok = set(clean["table"]) == set(faulted["table"])
        if table_ok:
            for i in clean["table"]:
                table_err = max(table_err, float(np.max(np.abs(
                    clean["table"][i] - faulted["table"][i]))))
        acc = faulted["acc"]
        unaccounted = (acc["admit_total"] - acc["demote_total"]
                       - acc["resident"])
        recovered = (
            max_err <= 1e-6 and table_ok and table_err <= 1e-6
            and acc["balanced"] and unaccounted == 0
            and faulted["restarts"] == 1 and clean["restarts"] == 0
            and faulted["ps_retries"] > 0
            and faulted["resumed_from"] is not None
            and faulted["resumed_from"] >= SAVE
            and faulted["gaps"] >= 1 and faulted["resyncs"] >= 1
            and faulted["corrupt"] >= 1
            and clean["gaps"] == 0
            and faulted["replica_err"] <= 1e-6
            and clean["replica_err"] <= 1e-6)
        detail = {
            "steps": steps, "save_freq": SAVE, "snap_freq": SNAP,
            "preempt_step": PREEMPT, "kill_request": KILL_REQ,
            "gap_publish": GAP_PUB, "corrupt_publish": CORRUPT_PUB,
            "max_param_err": max_err, "table_err": table_err,
            "table_rows": len(faulted["table"]),
            "unaccounted": unaccounted,
            "ledger_balanced": acc["balanced"],
            "ps_restarts": faulted["restarts"],
            "ps_retries": faulted["ps_retries"],
            "resumed_from": faulted["resumed_from"],
            "delta_gaps": faulted["gaps"],
            "delta_resyncs": faulted["resyncs"],
            "delta_corrupt_skips": faulted["corrupt"],
            "replica_err_clean": clean["replica_err"],
            "replica_err_faulted": faulted["replica_err"],
            "clean_restarts": clean["restarts"],
            "clean_gaps": clean["gaps"],
            "staleness_s": faulted["staleness_s"],
            "elapsed_s": round(dt, 3),
        }
        _emit("recommender_chaos_recovered_steps_per_sec",
              2 * steps / dt, "steps/s",
              1.0 if recovered else 0.0, detail)
        if not recovered:
            raise AssertionError(
                f"recommender chaos soak did NOT recover: "
                f"{json.dumps(detail)}")
    finally:
        chaos.reset()
        shutil.rmtree(tmp, ignore_errors=True)


def main():
    import os
    ap = argparse.ArgumentParser()
    def _pos(v):
        v = int(v)
        if v <= 0:
            raise argparse.ArgumentTypeError("must be > 0")
        return v
    ap.add_argument("--steps", type=_pos, default=None)
    ap.add_argument("--elastic", action="store_true",
                    help="supervised kill/restart soak: SIGKILL the "
                         "worker mid-run via worker_kill chaos, let the "
                         "Supervisor relaunch it (resume from last "
                         "committed checkpoint); vs_baseline is 1.0 iff "
                         "final params match the clean run to 1e-6 with "
                         "exactly one restart")
    ap.add_argument("--elastic-resize", dest="elastic_resize",
                    action="store_true",
                    help="live world-resize soak: SIGKILL the fleet "
                         "mid-run (worker_kill chaos), shrink 8→6 with "
                         "a checkpoint-resharding resume, grow back to "
                         "8 on request; vs_baseline is 1.0 iff final "
                         "params match the uninterrupted fixed-global-"
                         "batch run to 1e-6 with exactly-once sample "
                         "accounting across the resize")
    ap.add_argument("--serving-fleet", dest="serving_fleet",
                    action="store_true",
                    help="multi-replica HA soak: 3 supervised replicas "
                         "under load through a replica_kill failover, "
                         "a mid-soak hot-swap to a second model "
                         "version (per-version parity 1e-6 vs the "
                         "single-process engines), and a failed-canary "
                         "rollback; vs_baseline is 1.0 iff zero "
                         "client-visible failures and unaccounted==0")
    ap.add_argument("--traffic", action="store_true",
                    help="production-day control-loop soak: an open-"
                         "loop traffic schedule (diurnal ramp, 10x "
                         "flash crowd, heavy-tail sizes, mixed "
                         "priorities) against a 2-replica fleet whose "
                         "only capacity knob is the SLO-driven "
                         "Autoscaler, chaos-composed with a "
                         "replica_kill on the first scaled-out rank; "
                         "vs_baseline is 1.0 iff admitted p99 holds "
                         "the declared SLO at <= 2x steady replica-"
                         "hours with zero client-visible failures, "
                         "unaccounted==0, every transition journaled, "
                         "and <1% loop overhead (--steps = seconds of "
                         "compressed day, default 20)")
    ap.add_argument("--generate-fleet", dest="generate_fleet",
                    action="store_true",
                    help="fault-tolerant generative serving soak: 3 "
                         "supervised GenerationServer replicas through "
                         "a gen_replica_kill mid-stream failover "
                         "(greedy AND sampled streams complete bit-"
                         "identical to the single-process reference, "
                         "zero client failures, unaccounted==0, one "
                         "decode signature per replica) plus a KV-"
                         "pressure arm where low-priority streams "
                         "preempt/park and re-admit bit-identically; "
                         "vs_baseline is 1.0 iff every gate holds")
    ap.add_argument("--recommender", action="store_true",
                    help="sharded-embedding gate: a synthetic CTR "
                         "model over a 200k-row logical vocab trains "
                         "through a 4096-slot HBM table (2048-row "
                         "admission budget) at ONE device dispatch "
                         "per step despite per-step tier churn; "
                         "census occupancy stays under budget with a "
                         "balanced admit/demote ledger, and the "
                         "trainer's drained delta lands on a live "
                         "ServingFleet replica in < 5 s at 1e-6; "
                         "vs_baseline is 1.0 iff every gate holds")
    ap.add_argument("--recommender-chaos", dest="recommender_chaos",
                    action="store_true",
                    help="durable-recommender soak: the tiered-"
                         "embedding loop vs a supervised table-server "
                         "subprocess through a ps_kill (restart-from-"
                         "own-checkpoint + fenced exactly-once retry), "
                         "a trainer preemption restored from the embed "
                         "checkpoint sidecar, and delta_corrupt + "
                         "delta_gap on a live replica healed by "
                         "snapshot resync; vs_baseline is 1.0 iff "
                         "final params AND the full logical table "
                         "match the clean run to 1e-6 with a balanced "
                         "ledger, unaccounted==0, exactly one PS "
                         "restart, and replica convergence at 1e-6")
    ap.add_argument("--serving", action="store_true",
                    help="dynamic micro-batching soak: serve N requests "
                         "sequentially and through the Batcher at batch "
                         "16; asserts batched >= 3x sequential "
                         "throughput, batched == sequential outputs to "
                         "1e-6, and exactly one compile per shape "
                         "bucket; vs_baseline = speedup/3")
    ap.add_argument("--generate", action="store_true",
                    help="generative serving soak: decode 16 prompts "
                         "through the slot-batched KV-cache engine vs "
                         "sequential eager dynamic_decode; asserts "
                         "tokens/s >= 5x, greedy parity, staggered-"
                         "arrival bit-parity, exactly one decode "
                         "compile, and token-level unaccounted==0 on "
                         "a drain under load; vs_baseline = speedup/5")
    ap.add_argument("--obs", action="store_true",
                    help="observability gate: instrumented training "
                         "loop overhead (metrics+tracing enabled < 5% "
                         "of step time, disabled ~0 proven "
                         "structurally), a scrapeable /metrics + "
                         "/healthz endpoint, and a fleet soak whose "
                         "merged chrome trace shows one request's "
                         "spans across >= 3 processes (client/router, "
                         "wedged replica, failover replica) linked by "
                         "trace_id with flow events")
    ap.add_argument("--cost", action="store_true",
                    help="cost-observatory gate: the engine's XLA-"
                         "cost-analysis MFU must land within 15% of "
                         "the bench's analytic BERT MFU (same dt, "
                         "same peak table), the HBM census must cover "
                         ">= 95% of device-reported live bytes, an "
                         "injected crash must leave a flight dump "
                         "holding the final K step records, and the "
                         "whole observatory costs < 5% enabled / "
                         "structurally zero disabled")
    ap.add_argument("--chaos", action="store_true",
                    help="fault-injection soak: run the ResilientTrainer "
                         "through a poisoned batch, a failed checkpoint "
                         "write and a simulated preemption; vs_baseline "
                         "is 1.0 iff final params match the clean run "
                         "to 1e-6 with accurate counters")
    ap.add_argument("--loader-chaos", action="store_true",
                    help="input-pipeline soak: train through a SIGKILLed "
                         "loader worker, a quarantined corrupt sample "
                         "and a preemption resumed via O(1) loader-state "
                         "restore; vs_baseline is 1.0 iff final params "
                         "match a clean run that pre-excludes exactly "
                         "the quarantined indices, to 1e-6")
    args = ap.parse_args()
    if not any(v is True for v in vars(args).values()):
        ap.error("give one mode flag (the speed benchmark is "
                 "`python3 -m benchmarks.run`)")

    import jax
    if os.environ.get("JAX_PLATFORMS") == "cpu":
        # a test asked for a CPU count run; the recommender needs a
        # multi-device mesh to smoke its path
        if args.recommender:
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "") +
                " --xla_force_host_platform_device_count=8")
    elif jax.devices()[0].platform != "tpu":
        sys.exit(f"bench.py: JAX found {jax.devices()[0].platform!r}, "
                 "not a TPU. A CPU count run is asked for with "
                 "JAX_PLATFORMS=cpu; it is never a fallback.")
    on_tpu = jax.devices()[0].platform == "tpu"
    if on_tpu:
        from paddle1_tpu.core import flags as core_flags
        core_flags.maybe_enable_compilation_cache(default_dir=os.path.join(
            os.path.dirname(os.path.abspath(__file__)), ".jax_cache"))

    if args.elastic:
        bench_elastic_soak(on_tpu, steps_override=args.steps)
    elif args.elastic_resize:
        bench_elastic_resize(on_tpu, steps_override=args.steps)
    elif args.serving_fleet:
        bench_serving_fleet(on_tpu, steps_override=args.steps)
    elif args.traffic:
        bench_traffic(on_tpu, steps_override=args.steps)
    elif args.generate_fleet:
        bench_generate_fleet(on_tpu, steps_override=args.steps)
    elif args.recommender:
        bench_recommender(on_tpu, steps_override=args.steps)
    elif args.recommender_chaos:
        bench_recommender_chaos(on_tpu, steps_override=args.steps)
    elif args.serving:
        bench_serving(on_tpu, steps_override=args.steps)
    elif args.generate:
        bench_generate(on_tpu, steps_override=args.steps)
    elif args.obs:
        bench_obs(on_tpu, steps_override=args.steps)
    elif args.cost:
        bench_cost(on_tpu, steps_override=args.steps)
    elif args.chaos:
        bench_chaos_soak(on_tpu, steps_override=args.steps)
    elif args.loader_chaos:
        bench_loader_chaos(on_tpu, steps_override=args.steps)


if __name__ == "__main__":
    sys.exit(main())
