"""Keras-like high-level Model.

Analog of /root/reference/python/paddle/hapi/model.py (Model:876, fit:1519,
evaluate/predict/save/load:1160; the dual static+dygraph adapters at
:294/:697 collapse into one eager path — jit compilation is applied inside
train_batch via to_static when beneficial).
"""

from __future__ import annotations

import collections
import os
from typing import List, Optional, Sequence

import numpy as np

from ..core.async_loss import LossFuture
from ..core.tensor import Tensor, to_tensor
from ..core.errors import InvalidArgumentError
from ..io import DataLoader, Dataset
from ..metric import Metric
from .callbacks import Callback, CallbackList, ProgBarLogger

__all__ = ["Model"]


def _as_list(x):
    if x is None:
        return []
    return list(x) if isinstance(x, (list, tuple)) else [x]


class Model:
    def __init__(self, network, inputs=None, labels=None):
        self.network = network
        self._inputs = inputs
        self._labels = labels
        self._optimizer = None
        self._loss = None
        self._metrics: List[Metric] = []
        self.stop_training = False

    # -- configuration ------------------------------------------------------

    def prepare(self, optimizer=None, loss=None, metrics=None,
                amp_configs=None):
        self._optimizer = optimizer
        self._loss = loss
        self._metrics = _as_list(metrics)
        return self

    # -- per-batch ops ------------------------------------------------------

    def train_batch(self, inputs, labels=None, update=True):
        self.network.train()
        inputs = _as_list(inputs)
        labels = _as_list(labels)
        outputs = self.network(*[_to_tensor(i) for i in inputs])
        losses = self._compute_loss(outputs, labels)
        total = losses[0] if len(losses) == 1 else _sum_losses(losses)
        total.backward()
        if update:
            self._optimizer.step()
            self._optimizer.clear_grad()
        metrics = self._update_metrics(outputs, labels)
        # Lazy handles, not floats: a blocking float(l.item()) here costs
        # a device→host readback EVERY batch, serializing the whole loop
        # on the host. The future reads back only when someone formats or
        # floats it (ProgBarLogger, or an explicit .item()).
        loss_vals = [LossFuture(l) for l in losses]
        if metrics:
            return loss_vals, metrics
        return loss_vals

    def eval_batch(self, inputs, labels=None):
        from ..autograd import engine
        self.network.eval()
        with engine.no_grad():
            inputs = _as_list(inputs)
            labels = _as_list(labels)
            outputs = self.network(*[_to_tensor(i) for i in inputs])
            losses = self._compute_loss(outputs, labels) if self._loss else []
            metrics = self._update_metrics(outputs, labels)
        loss_vals = [float(l.item()) for l in losses]
        if metrics:
            return loss_vals, metrics
        return loss_vals

    def predict_batch(self, inputs):
        from ..autograd import engine
        self.network.eval()
        with engine.no_grad():
            inputs = _as_list(inputs)
            out = self.network(*[_to_tensor(i) for i in inputs])
        return [o.numpy() for o in _as_list(out)]

    def _compute_loss(self, outputs, labels):
        if self._loss is None:
            return []
        outs = _as_list(outputs)
        loss = self._loss(*(outs + labels))
        return _as_list(loss)

    def _update_metrics(self, outputs, labels):
        res = {}
        outs = _as_list(outputs)
        for m in self._metrics:
            computed = m.compute(*(outs + labels))
            r = m.update(*(computed if isinstance(computed, (list, tuple))
                           else [computed]))
            res[m.name()] = r
        return res

    # -- loops --------------------------------------------------------------

    def fit(self, train_data=None, eval_data=None, batch_size=1, epochs=1,
            eval_freq=1, log_freq=10, save_dir=None, save_freq=1, verbose=2,
            drop_last=False, shuffle=True, num_workers=0, callbacks=None,
            accumulate_grad_batches=1, num_iters=None, resume=False):
        """Train for ``epochs`` epochs.

        Fault-tolerance knobs (the reference incubate/auto_checkpoint
        train_epoch_range role at the hapi level; engine-scale runs
        should use :class:`paddle1_tpu.distributed.ResilientTrainer`):
        ``save_dir`` + ``save_freq`` checkpoint network+optimizer every
        N epochs; ``resume=True`` picks the largest epoch checkpoint
        already under ``save_dir`` (non-numeric/partial entries are
        skipped), loads it, and continues from the NEXT epoch. When the
        train loader is a checkpointable :class:`~paddle1_tpu.io.
        DataLoader`, each epoch checkpoint also writes an
        ``<epoch>.pdloader`` sidecar (loader state + RNG stream) and
        ``resume=True`` restores it, so the resumed run's epoch
        ordering continues exactly where the interrupted run's would
        have — otherwise a one-time warning notes that ordering
        restarts.
        """
        start_epoch = 0
        latest = None
        if resume:
            if not save_dir:
                raise InvalidArgumentError(
                    "fit(resume=True) needs save_dir (the checkpoint "
                    "directory to resume from)")
            latest = _latest_saved_epoch(save_dir)
            if latest is not None:
                self.load(os.path.join(save_dir, str(latest)))
                start_epoch = latest + 1
        train_loader = self._to_loader(train_data, batch_size, shuffle,
                                       drop_last, num_workers)
        if latest is not None:
            _restore_loader_state(save_dir, latest, train_loader)
        eval_loader = self._to_loader(eval_data, batch_size, False, False,
                                      num_workers) if eval_data is not None \
            else None
        cbks = CallbackList((_as_list(callbacks) or []) +
                            [ProgBarLogger(log_freq, verbose)])
        cbks.set_model(self)
        try:
            steps = len(train_loader)
        except (RuntimeError, TypeError):
            steps = None
        cbks.set_params({"epochs": epochs, "steps": steps,
                         "verbose": verbose})
        self.stop_training = False
        cbks.on_train_begin()
        it = 0
        if start_epoch >= epochs:
            cbks.on_train_end()
            return
        # Bounded dispatch run-ahead: keep at most `window` batches of
        # un-synchronized loss futures outstanding, then block (device
        # sync, NOT a readback) on the oldest — dispatch runs ahead of
        # the device without unbounded live-buffer growth.
        window: collections.deque = collections.deque()
        window_size = 2
        for epoch in range(start_epoch, epochs):
            cbks.on_epoch_begin(epoch)
            for m in self._metrics:
                m.reset()
            logs = {}
            for step, batch in enumerate(train_loader):
                cbks.on_train_batch_begin(step)
                ins, labs = _split_batch(batch)
                update = (step + 1) % accumulate_grad_batches == 0
                res = self.train_batch(ins, labs, update=update)
                logs = _logs_from(res, self._metrics)
                for lv in logs.get("loss", []):
                    if isinstance(lv, LossFuture):
                        window.append(lv)
                while len(window) > window_size:
                    window.popleft().block()
                cbks.on_train_batch_end(step, logs)
                it += 1
                if (num_iters is not None and it >= num_iters) or \
                        self.stop_training:
                    break
            cbks.on_epoch_end(epoch, logs)
            if eval_loader is not None and (epoch + 1) % eval_freq == 0:
                self.evaluate(eval_loader, batch_size=batch_size,
                              verbose=verbose, callbacks=callbacks)
            if save_dir and (epoch + 1) % save_freq == 0:
                self.save(os.path.join(save_dir, str(epoch)))
                _save_loader_state(save_dir, epoch, train_loader)
            if self.stop_training or (num_iters is not None and
                                      it >= num_iters):
                break
        cbks.on_train_end()

    def evaluate(self, eval_data, batch_size=1, log_freq=10, verbose=2,
                 num_workers=0, callbacks=None, num_samples=None):
        loader = self._to_loader(eval_data, batch_size, False, False,
                                 num_workers)
        cbks = CallbackList((_as_list(callbacks) or []) +
                            [ProgBarLogger(log_freq, verbose)])
        cbks.set_model(self)
        cbks.set_params({})
        for m in self._metrics:
            m.reset()
        cbks.on_eval_begin()
        logs = {}
        for step, batch in enumerate(loader):
            ins, labs = _split_batch(batch)
            res = self.eval_batch(ins, labs)
            logs = _logs_from(res, self._metrics)
        final = {}
        if self._loss is not None and "loss" in logs:
            final["loss"] = logs["loss"]
        for m in self._metrics:
            final[m.name()] = m.accumulate()
        cbks.on_eval_end(final)
        return final

    def predict(self, test_data, batch_size=1, num_workers=0,
                stack_outputs=False, callbacks=None, verbose=1):
        loader = self._to_loader(test_data, batch_size, False, False,
                                 num_workers)
        outputs = []
        for batch in loader:
            ins, _ = _split_batch(batch, has_labels=False)
            outputs.append(self.predict_batch(ins))
        if stack_outputs:
            n_out = len(outputs[0])
            return [np.concatenate([o[i] for o in outputs])
                    for i in range(n_out)]
        return outputs

    # -- persistence --------------------------------------------------------

    def save(self, path, training=True):
        from ..framework.io import save as fsave
        fsave(self.network.state_dict(), path + ".pdparams")
        if training and self._optimizer is not None:
            fsave(self._optimizer.state_dict(), path + ".pdopt")

    def load(self, path, skip_mismatch=False, reset_optimizer=False):
        from ..framework.io import load as fload
        state = fload(path + ".pdparams")
        self.network.set_state_dict(state)
        opt_path = path + ".pdopt"
        if not reset_optimizer and self._optimizer is not None and \
                os.path.exists(opt_path):
            self._optimizer.set_state_dict(fload(opt_path))

    def parameters(self, *args, **kwargs):
        return self.network.parameters()

    def summary(self, input_size=None, dtype=None):
        from .model_summary import summary
        return summary(self.network, input_size, dtypes=dtype)

    def _to_loader(self, data, batch_size, shuffle, drop_last, num_workers):
        if data is None:
            return None
        if isinstance(data, DataLoader):
            return data
        if isinstance(data, Dataset):
            return DataLoader(data, batch_size=batch_size, shuffle=shuffle,
                              drop_last=drop_last, num_workers=num_workers)
        return data  # assume iterable of batches


def _loader_sidecar(save_dir, epoch):
    return os.path.join(save_dir, f"{epoch}.pdloader")


def _save_loader_state(save_dir, epoch, loader):
    """Write the ``<epoch>.pdloader`` sidecar: loader position + the
    global RNG stream (the next epoch's shuffle seed is drawn from it,
    so ordering parity needs both). Checkpointing must never fail the
    epoch that just trained — problems degrade to a warning."""
    import json
    import warnings
    from ..io import DataLoader
    if not isinstance(loader, DataLoader) or not loader.checkpointable():
        return
    from ..core.generator import get_rng_state
    try:
        doc = {"version": 1, "loader": loader.state_dict(),
               "rng": get_rng_state()}
        tmp = _loader_sidecar(save_dir, epoch) + ".tmp"
        with open(tmp, "w") as f:
            json.dump(doc, f)
        os.replace(tmp, _loader_sidecar(save_dir, epoch))
    except Exception as e:
        warnings.warn(f"loader state sidecar not written ({e}); "
                      "resume will restart epoch ordering")


_FALLBACK_WARNED = set()


def _restore_loader_state(save_dir, epoch, loader):
    """Apply the ``<epoch>.pdloader`` sidecar to a resumed fit's
    loader; warns ONCE per save_dir when it must fall back (missing
    sidecar / non-checkpointable loader) so the user knows the resumed
    run's data order restarts instead of continuing."""
    import json
    import warnings
    from ..io import DataLoader

    def fallback(why):
        if save_dir not in _FALLBACK_WARNED:
            _FALLBACK_WARNED.add(save_dir)
            warnings.warn(
                f"fit(resume=True): loader state not restored ({why}); "
                "epoch ordering restarts from scratch — pass a "
                "checkpointable io.DataLoader (built-in samplers) to "
                "resume the data stream exactly")

    path = _loader_sidecar(save_dir, epoch)
    if not isinstance(loader, DataLoader) or not loader.checkpointable():
        if os.path.exists(path):
            fallback("train loader is not checkpointable")
        return
    if not os.path.exists(path):
        fallback(f"no {os.path.basename(path)} sidecar — checkpoint "
                 "predates loader-state support")
        return
    try:
        with open(path) as f:
            doc = json.load(f)
        # loader state FIRST: it validates eagerly, so a corrupt
        # sidecar fails before the global RNG is touched — the
        # fallback's "ordering restarts from scratch" promise must
        # describe a process whose RNG stream really is untouched
        loader.set_state_dict(doc["loader"])
        from ..core.generator import set_rng_state
        if "rng" in doc:
            set_rng_state(doc["rng"])
    except Exception as e:
        fallback(f"unreadable sidecar: {e}")


def _latest_saved_epoch(save_dir):
    """Largest N with ``<save_dir>/<N>.pdparams`` present, or None.
    Non-numeric and partial entries (a ``.pdparams`` name that doesn't
    parse, or files from other tooling) are skipped, mirroring the
    hardened ``distributed.checkpoint.latest_step``."""
    import re
    if not os.path.isdir(save_dir):
        return None
    best = None
    for name in os.listdir(save_dir):
        m = re.fullmatch(r"(\d+)\.pdparams", name)
        if m is not None:
            n = int(m.group(1))
            best = n if best is None else max(best, n)
    return best


def _to_tensor(x):
    return x if isinstance(x, Tensor) else to_tensor(x)


def _split_batch(batch, has_labels=True):
    if isinstance(batch, (list, tuple)):
        if has_labels and len(batch) >= 2:
            return list(batch[:-1]), [batch[-1]]
        return list(batch), []
    return [batch], []


def _sum_losses(losses):
    total = losses[0]
    for l in losses[1:]:
        total = total + l
    return total


def _logs_from(res, metrics):
    logs = {}
    if isinstance(res, tuple):
        loss_vals, m = res
        logs["loss"] = loss_vals
        logs.update(m)
    else:
        logs["loss"] = res
    return logs
