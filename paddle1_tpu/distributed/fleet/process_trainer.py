"""Process-based Hogwild workers over the shared-memory arena.

The reference's HogwildWorker is a lock-free C++ thread
(/root/reference/paddle/fluid/framework/device_worker.h:150,
hogwild_worker.cc) — real parallel CPU throughput. The r3
:class:`~paddle1_tpu.distributed.fleet.trainer.MultiTrainer` runs
Python threads, which demonstrate the composition shape but serialize
on the GIL for the slot-parsing/feature work that dominates the CPU-PS
workload. This module is the throughput-bearing version:

* N worker **processes**, each with its own interpreter (no GIL
  sharing), built from a picklable ``model_fn``.
* Batches and gradients cross process boundaries as shared-memory
  descriptors over the :class:`~paddle1_tpu.core.native.ShmArena`
  (native.cc block allocator + refcounts) — numpy payloads are written
  once and read zero-copy; only tiny descriptor tuples travel through
  the queues.
* The **dense update stays serialized in the parent** (the reference
  Hogwild races updates benignly; here the parent applies each worker
  gradient to the master model through the real optimizer — the same
  slightly-stale async semantics without slot-state races), and fresh
  parameters broadcast back through the arena every
  ``publish_interval`` updates.
* The arena is a bump allocator (blocks reclaim on ``reset`` only), so
  the parent runs a drain-and-reset barrier when usage crosses a
  threshold: stop issuing tasks, absorb in-flight grads, reset,
  republish params.
* Sparse parameters compose unchanged: a ``DistributedEmbedding``
  inside ``model_fn``'s model pushes/pulls against the PS tables
  (process-safe TCP transport), exactly the Downpour split.
"""

from __future__ import annotations

import multiprocessing as mp
import os
import queue as pyqueue
import time
from typing import Callable, Iterable, Optional

import numpy as np

from ...core.errors import InvalidArgumentError

__all__ = ["ProcessMultiTrainer"]


def _orphan_checked_get(q, timeout, what):
    """``q.get`` that notices a dead leader. Workers block on
    ``param_q``/``task_q`` gets; if the parent died (SIGKILL skips the
    daemon-reaping atexit hook, orphaning spawn children), the plain
    get would hang 120s — or forever in the inner loops. Poll in short
    slices and check parent liveness between them; raises RuntimeError
    with the real cause instead. ``timeout=None`` blocks indefinitely
    (while the parent lives); a finite timeout re-raises ``Empty`` at
    its deadline, preserving the plain-get contract."""
    deadline = None if timeout is None else time.monotonic() + timeout
    while True:
        slice_s = 2.0
        if deadline is not None:
            slice_s = min(slice_s, max(0.05, deadline - time.monotonic()))
        try:
            return q.get(timeout=slice_s)
        except pyqueue.Empty:
            parent = mp.parent_process()
            if parent is not None and not parent.is_alive():
                raise RuntimeError(
                    f"hogwild worker orphaned: the leader process died "
                    f"while this worker waited for {what} — exiting "
                    "instead of hanging on the queue")
            if deadline is not None and time.monotonic() >= deadline:
                raise


# -- shm pytree transport ----------------------------------------------------

def _tree_put(arena, obj):
    """numpy-pytree → descriptor-pytree. ndarray payloads go through the
    arena; strings and plain scalars (slot lines, labels, meta) ride the
    descriptor itself."""
    if isinstance(obj, dict):
        return {"__d": {k: _tree_put(arena, v) for k, v in obj.items()}}
    if isinstance(obj, (list, tuple)):
        return {"__l": [_tree_put(arena, v) for v in obj]}
    if isinstance(obj, (str, bytes, int, float, bool, type(None))):
        return {"__v": obj}
    return {"__a": arena.put_array(np.asarray(obj))}


def _tree_get(arena, desc, decref=True):
    if "__d" in desc:
        return {k: _tree_get(arena, v, decref)
                for k, v in desc["__d"].items()}
    if "__l" in desc:
        return [_tree_get(arena, v, decref) for v in desc["__l"]]
    if "__v" in desc:
        return desc["__v"]
    arr = arena.get_array(desc["__a"])
    if decref:
        arena.decref(desc["__a"])
    return arr


def _worker_main(worker_id, arena_name, task_q, grad_q, param_q,
                 epoch, model_fn, loss_fn, env):
    """Worker process entry (module-level: spawn-picklable)."""
    os.environ.update(env)
    os.environ["P1T_HOGWILD_WORKER"] = "1"  # lets factories detect workers
    # the CPU-PS workload never touches the TPU; never let a worker
    # try to claim the chip its parent may hold
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax
    jax.config.update("jax_platforms", "cpu")

    from ...core import health, native
    from ...core.tensor import Tensor

    arena = native.ShmArena(arena_name, create=False)
    model = model_fn()
    # structured state_dict keys are replica-stable; Parameter.name uses a
    # process-global counter and need not agree between parent and worker
    tparams = {k: t for k, t in model.state_dict().items()
               if not t.stop_gradient}
    n_batches, losses = 0, []
    def adopt(msg):
        """Epoch-validated adoption: a message published before an arena
        reset points into reclaimed memory — discard it (the current
        params stay valid; the post-reset republish follows). The epoch
        is re-checked AFTER the copy-out to catch a reset racing the
        read."""
        ep, _ver, pdescs = msg
        if ep != epoch.value:
            return False
        # decref=False: a reset racing this read must see NO writes at
        # stale offsets (decref's fetch_sub would land inside freshly
        # allocated blocks); reset is the arena's only reclaimer anyway
        flat = _tree_get(arena, pdescs, decref=False)
        if ep != epoch.value:
            return False
        for name, p in tparams.items():
            p._data = Tensor(flat[name]).data
        return True

    version = 0
    try:
        # adopt the master's INITIAL params before any batch: per-process
        # model inits need not agree, and queue ordering across different
        # queues is not guaranteed
        while not adopt(_orphan_checked_get(param_q, 120,
                                            "the initial params")):
            pass
        while True:
            # supervisor liveness (no-op unless this worker tree runs
            # under a heartbeat channel) + worker-level chaos trigger
            health.beat()
            task = _orphan_checked_get(task_q, None, "the next task")
            if task is None:
                break
            # adopt the newest published params (drain to latest)
            newest = None
            while True:
                try:
                    newest = param_q.get_nowait()
                except pyqueue.Empty:
                    break
            if newest is not None:
                version = newest[1]
                adopt(newest)
            batch = _tree_get(arena, task)
            loss = loss_fn(model, batch)
            loss.backward()
            gdescs = {}
            for name, p in tparams.items():
                if p.grad is not None:
                    gdescs[name] = _tree_put(
                        arena, np.asarray(p.grad.numpy()))
                    p.clear_grad()
            losses.append(float(loss.numpy()))
            n_batches += 1
            grad_q.put(("grads", worker_id, gdescs, losses[-1], version))
    except BaseException as e:  # noqa: broad-except — surfaced to the
        # parent via the grad queue's error record; don't hang the join
        grad_q.put(("error", worker_id, repr(e), None, None))
        return
    finally:
        arena.close()
    grad_q.put(("exit", worker_id,
                {"batches": n_batches, "losses": losses}, None, None))


def _default_collate(buf):
    """Stack a list of samples: dict samples stack per key, tuple
    samples per position, array samples directly."""
    first = buf[0]
    if isinstance(first, dict):
        return {k: _default_collate([b[k] for b in buf]) for k in first}
    if isinstance(first, (list, tuple)):
        return type(first)(_default_collate([b[i] for b in buf])
                           for i in range(len(first)))
    if isinstance(first, str):
        return list(buf)
    return np.stack(buf)


def _tree_incref(arena, desc):
    if "__d" in desc:
        for v in desc["__d"].values():
            _tree_incref(arena, v)
    elif "__l" in desc:
        for v in desc["__l"]:
            _tree_incref(arena, v)
    elif "__a" in desc:
        arena.incref(desc["__a"])


def _batched(sample_iter: Iterable, batch_size, collate):
    buf = []
    for s in sample_iter:
        buf.append(s)
        if len(buf) == batch_size:
            yield collate(buf)
            buf = []
    if buf:
        yield collate(buf)


class ProcessMultiTrainer:
    """MultiTrainer with real process workers (reference HogwildWorker
    throughput semantics). ``model_fn``/``loss_fn`` must be picklable
    (module-level functions): each worker builds its own model replica;
    the parent holds the master copy and the optimizer."""

    def __init__(self, process_num: int = 2, arena_size: int = 1 << 27,
                 publish_interval: int = 4,
                 arena_reset_fraction: float = 0.6):
        if process_num < 1:
            raise InvalidArgumentError("process_num must be >= 1")
        self.process_num = int(process_num)
        self.arena_size = int(arena_size)
        self.publish_interval = int(publish_interval)
        self.arena_reset_fraction = float(arena_reset_fraction)

    def train_from_dataset(self, dataset, model_fn: Callable,
                           loss_fn: Callable, optimizer_fn: Callable,
                           batch_size: Optional[int] = 1,
                           collate: Optional[Callable] = None,
                           debug: bool = False) -> dict:
        """Drain ``dataset`` once across ``process_num`` worker
        processes. ``optimizer_fn(model) -> optimizer`` builds the
        parent-side optimizer over the master model."""
        from ...core import health, native
        from ...core.tensor import Tensor

        # the LEADER is the supervised process: adopt the heartbeat
        # channel now (beat() pops the PADDLE_FT_* env vars) so the
        # env snapshot below cannot leak it into the mp workers —
        # grandchildren beating the leader's file would mask a leader
        # hang from the supervisor
        health.beat()
        if not native.available():
            raise InvalidArgumentError(
                "ProcessMultiTrainer needs the native shm arena "
                "(core/native build); use MultiTrainer (threads) instead")
        if collate is None:
            collate = _default_collate
        batch_iter = iter(dataset) if batch_size is None else _batched(
            iter(dataset), batch_size, collate)

        master = model_fn()
        optimizer = optimizer_fn(master)
        tparams = {k: t for k, t in master.state_dict().items()
                   if not t.stop_gradient}

        arena_name = f"/p1t_hogwild_{os.getpid()}"
        lib = native._load()
        lib.shm_arena_unlink(arena_name.encode())
        arena = native.ShmArena(arena_name, self.arena_size)

        ctx = mp.get_context("spawn")
        task_q = ctx.Queue()
        grad_q = ctx.Queue()
        param_qs = [ctx.Queue() for _ in range(self.process_num)]
        epoch = ctx.Value("q", 0)  # arena-reset generation counter
        env = {k: v for k, v in os.environ.items()
               if k.startswith(("PADDLE_", "PYTHONPATH", "XLA_FLAGS"))}
        env["JAX_PLATFORMS"] = "cpu"
        procs = [ctx.Process(target=_worker_main,
                             args=(i, arena_name, task_q, grad_q,
                                   param_qs[i], epoch, model_fn, loss_fn,
                                   env),
                             daemon=True)
                 for i in range(self.process_num)]
        for p in procs:
            p.start()
        # exit-watching via the launcher's Supervisor (fail-fast,
        # detection only — check_failed() never takes policy action):
        # the mp workers are adopted through the Popen-shaped adapter
        from ..supervisor import MpProcessHandle, Supervisor
        watchdog = Supervisor(policy="fail_fast")
        for i, p in enumerate(procs):
            watchdog.attach(i, MpProcessHandle(p))

        def publish(version):
            # write the params into the arena ONCE; extra workers share
            # the blocks via incref (refcounted in native.cc)
            flat = {name: np.asarray(p.numpy())
                    for name, p in tparams.items()}
            descs = _tree_put(arena, flat)
            for q in param_qs[1:]:
                _tree_incref(arena, descs)
            ep = epoch.value
            for q in param_qs:
                q.put((ep, version, descs))

        stats: dict = {}
        outstanding = 0
        updates = 0
        version = 0
        exited = 0
        error = None
        draining = False

        def absorb(block):
            """Apply one grad message (or worker exit) from grad_q."""
            nonlocal outstanding, updates, version, exited, error
            deadline = 300
            while True:
                try:
                    kind, wid, payload, lossval, _v = grad_q.get(
                        timeout=5 if block else 0.001)
                    break
                except pyqueue.Empty:
                    if not block:
                        return False
                    # the leader is healthy while it waits here (its own
                    # 300s deadline tolerates slow workers) — keep the
                    # supervisor's hang detector fed
                    health.beat()
                    # a worker that died WITHOUT posting (unpicklable
                    # model_fn, missing __main__ guard in the caller's
                    # script, OOM-kill) would otherwise hang us forever
                    dead = watchdog.check_failed()
                    if len([p for p in procs if p.is_alive()]) + exited \
                            < self.process_num or dead:
                        raise RuntimeError(
                            "ProcessMultiTrainer: worker process died "
                            f"without reporting (exitcodes "
                            f"{[p.exitcode for p in procs]}). If your "
                            "script is the __main__ module, guard the "
                            "training call with if __name__ == "
                            "'__main__': (multiprocessing spawn "
                            "re-imports __main__)")
                    deadline -= 5
                    if deadline <= 0:
                        raise RuntimeError(
                            "ProcessMultiTrainer: no worker progress "
                            "in 300s")
            if kind == "error":
                error = RuntimeError(
                    f"hogwild worker {wid} failed: {payload}")
                exited += 1
                return True
            if kind == "exit":
                stats[wid] = payload
                exited += 1
                return True
            outstanding -= 1
            for name, gdesc in payload.items():
                g = _tree_get(arena, gdesc)
                tparams[name]._grad = Tensor(g)
            optimizer.step()
            optimizer.clear_grad()
            updates += 1
            if updates % self.publish_interval == 0 and not draining:
                # during the reset barrier the arena is near-full and a
                # fresh republish follows the reset anyway
                version += 1
                publish(version)
            return True

        try:
            publish(version)  # initial params
            while True:
                health.beat()  # leader liveness, once per dispatch round
                # memory barrier: drain in-flight, reset, republish
                if arena.used() > self.arena_size * self.arena_reset_fraction:
                    draining = True
                    while outstanding > 0 and error is None:
                        absorb(block=True)
                    draining = False
                    # bump the epoch FIRST: any pre-reset param message
                    # still in transit (mp.Queue feeder threads) is now
                    # stale and the workers discard it by epoch check
                    with epoch.get_lock():
                        epoch.value += 1
                    arena.reset()
                    version += 1
                    publish(version)
                if error is not None:
                    break
                batch = next(batch_iter, None)
                if batch is None:
                    break
                task_q.put(_tree_put(arena, batch))
                outstanding += 1
                while absorb(block=False):
                    pass
            for _ in procs:
                task_q.put(None)
            while exited < self.process_num:
                absorb(block=True)
        finally:
            for p in procs:
                p.join(timeout=30)
                if p.is_alive():
                    p.terminate()
            arena.close(unlink=True)
        if error is not None:
            raise error

        all_losses = [l for s in stats.values() for l in s["losses"]]
        out = {"workers": self.process_num,
               "batches": sum(s["batches"] for s in stats.values()),
               "updates": updates,
               "loss_mean": float(np.mean(all_losses)) if all_losses
               else float("nan"),
               "per_worker": stats,
               "model": master}  # the trained master (parent-side)
        if debug:
            print(f"ProcessMultiTrainer: {out['batches']} batches / "
                  f"{updates} dense updates over {self.process_num} "
                  f"processes, mean loss {out['loss_mean']:.6f}")
        return out
