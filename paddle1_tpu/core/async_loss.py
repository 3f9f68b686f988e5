"""Lazy loss handles — defer the device→host readback until the value is
actually formatted.

A device→host readback stalls the host until the device has caught up
(its cost on the v5e: not measured); a training loop that calls
``float(loss.item())`` every batch therefore serializes dispatch and
execution. :class:`LossFuture` keeps the loss as a device array
and only fetches it to host memory when someone *reads* it — ``float()``,
``.item()``, ``np.asarray`` (``__array__``), or string formatting. Until
then the only cost is the handle itself; XLA's async dispatch runs ahead.

``block()`` is the cheap synchronization point: it waits for the device
computation WITHOUT copying the value to host (no readback). The engine
and ``hapi.Model.fit`` use it to bound the in-flight dispatch window.

A module-level readback counter is the test hook for the "no per-batch
readback" acceptance criterion: every actual device→host materialization
increments it exactly once per handle (the fetched value is cached).
"""

from __future__ import annotations

import threading
import time
from typing import Any, Callable, Optional

import numpy as np

__all__ = ["LossFuture", "StepFuture", "readback_count",
           "reset_readback_count", "set_readback_observer"]

_lock = threading.Lock()
_readbacks = 0
# optional duration hook (seconds per materialization): obs wires the
# train_readback_seconds histogram through it when obs_metrics is on —
# None (the default) keeps the fetch path free of even a perf_counter
_observer: Optional[Callable[[float], None]] = None


def set_readback_observer(fn: Optional[Callable[[float], None]]) -> None:
    """Install (or clear, with None) a callable receiving each
    materialization's duration in seconds."""
    global _observer
    _observer = fn


def readback_count() -> int:
    """Total LossFuture device→host materializations (test hook)."""
    return _readbacks


def reset_readback_count() -> None:
    global _readbacks
    with _lock:
        _readbacks = 0


def _count_readback() -> None:
    global _readbacks
    with _lock:
        _readbacks += 1
    # the jit sanitizer's host-sync accounting (ISSUE 12): one module
    # bool test when the sanitizer never armed — attribution to the
    # engine step loop (or whatever hot_section the thread is in)
    # makes "this loop pays one readback per chunk" assertable
    from . import jit_sanitizer
    jit_sanitizer.note_host_sync("loss_readback")


def _read_back(arr) -> np.ndarray:
    """The one device→host fetch of a handle, inside a
    ``train/readback`` profiler annotation (a no-op outside a profiler
    session), counted, its duration handed to the observer."""
    from jax.profiler import TraceAnnotation
    obs = _observer
    t0 = time.perf_counter() if obs is not None else 0.0
    with TraceAnnotation("train/readback"):
        out = np.asarray(arr)
    _count_readback()
    if obs is not None:
        obs(time.perf_counter() - t0)
    return out


class LossFuture:
    """A loss value still living on device. Reads materialize it.

    Wraps a jax array (or Tensor); scalar losses behave like a float
    wherever one is expected (``float()``, ``f"{loss:.4f}"``, numpy
    coercion). ``step_many`` returns one future over the whole ``[k]``
    loss vector — ``np.asarray(fut)`` yields the k losses in one
    readback.
    """

    __slots__ = ("_arr", "_result")

    def __init__(self, value: Any):
        # Tensor → its backing array; plain floats/np pass through and
        # materialize for free.
        self._arr = value.data if hasattr(value, "data") else value
        self._result: Optional[np.ndarray] = None

    # -- device-side ------------------------------------------------------

    @property
    def data(self):
        """The underlying (device) array — no readback."""
        return self._arr

    def block(self) -> "LossFuture":
        """Wait for the device computation to finish WITHOUT fetching the
        value to host (bounds in-flight dispatch; not a readback)."""
        if self._result is None:
            try:
                import jax
                jax.block_until_ready(self._arr)
            except (ImportError, TypeError):
                pass
        return self

    @property
    def materialized(self) -> bool:
        return self._result is not None

    # -- host-side reads (each handle reads back at most once) -------------

    def numpy(self) -> np.ndarray:
        if self._result is None:
            self._result = _read_back(self._arr)
        return self._result

    def item(self) -> float:
        return float(np.ravel(self.numpy())[0]) if self.numpy().size == 1 \
            else self.numpy().item()

    def __float__(self) -> float:
        return self.item()

    def __int__(self) -> int:
        return int(self.item())

    def __array__(self, dtype=None):
        a = self.numpy()
        return a.astype(dtype) if dtype is not None else a

    # Minimal numeric protocol so code written against the old float
    # returns (`if loss < best:`, `total += loss`, `min(losses)`) keeps
    # working — each coerces through item()/numpy(), i.e. materializes.

    def __lt__(self, other):
        return self.item() < other

    def __le__(self, other):
        return self.item() <= other

    def __gt__(self, other):
        return self.item() > other

    def __ge__(self, other):
        return self.item() >= other

    def __eq__(self, other):
        if isinstance(other, LossFuture):
            other = other.item()
        return self.item() == other

    def __ne__(self, other):
        return not self.__eq__(other)

    __hash__ = object.__hash__

    def __add__(self, other):
        return self.item() + other

    __radd__ = __add__

    def __sub__(self, other):
        return self.item() - other

    def __rsub__(self, other):
        return other - self.item()

    def __mul__(self, other):
        return self.item() * other

    __rmul__ = __mul__

    def __truediv__(self, other):
        return self.item() / other

    def __rtruediv__(self, other):
        return other / self.item()

    def __neg__(self):
        return -self.item()

    def __abs__(self):
        return abs(self.item())

    def __format__(self, spec: str) -> str:
        a = self.numpy()
        if a.size == 1:
            return format(float(np.ravel(a)[0]), spec)
        return format(a, spec)

    def __repr__(self) -> str:
        if self._result is not None:
            return f"LossFuture({self._result!r})"
        return "LossFuture(<pending on device>)"

    def __len__(self):
        return len(self.numpy())

    def __iter__(self):
        return iter(self.numpy())


class StepFuture(LossFuture):
    """A LossFuture over a *packed* ``[..., 2]`` array of
    ``[loss, notfinite]`` pairs — the output of a ``check_finite``
    compiled train step.

    The bad-step flag is computed on device inside the step executable
    and packed next to the loss, so NaN/Inf detection costs no extra
    readback: one host fetch materializes both (and the readback counter
    increments once, same as a plain loss). All the float/format/numpy
    protocol of :class:`LossFuture` sees only the loss column —
    ``float(engine.step(b))`` behaves exactly as without detection —
    while :meth:`bad`, :meth:`bad_count` and :meth:`bad_mask` expose the
    flag side.
    """

    __slots__ = ("_raw",)

    def __init__(self, packed: Any):
        super().__init__(packed)
        self._raw: Optional[np.ndarray] = None

    def _fetch(self) -> np.ndarray:
        if self._raw is None:
            self._raw = _read_back(self._arr)
        return self._raw

    def numpy(self) -> np.ndarray:
        if self._result is None:
            self._result = np.asarray(self._fetch()[..., 0])
        return self._result

    def bad_mask(self) -> np.ndarray:
        """Per-step non-finite flags (bool; scalar for a single step,
        ``[k]`` for a ``step_many`` chunk)."""
        return np.asarray(self._fetch()[..., 1] > 0)

    def bad_count(self) -> int:
        return int(np.sum(self.bad_mask()))

    @property
    def bad(self) -> bool:
        """True when any step in this dispatch saw a non-finite loss or
        gradient (the update was skipped on device for those steps)."""
        return bool(np.any(self.bad_mask()))
