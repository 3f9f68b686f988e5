"""Device / Place abstraction.

Analog of the reference's Place variants and DeviceContextPool
(/root/reference/paddle/fluid/platform/place.h:26-95,
platform/device_context.h:107,795). On TPU the "device context" — streams,
library handles, per-device state — is owned by PJRT/XLA; Place here is a thin
identity wrapper over a ``jax.Device`` plus a process-global current-place,
which eager ops consult for output placement (the reference's
``DeviceContextPool::Get(place)`` pattern collapses into jax's default-device
machinery).
"""

from __future__ import annotations

import contextlib
import threading
from typing import Optional, Union

import jax

from .errors import InvalidArgumentError, UnavailableError

__all__ = [
    "Place", "CPUPlace", "TPUPlace", "set_device", "get_device",
    "device_guard", "is_compiled_with_tpu", "device_count",
]


class Place:
    """Device identity: (kind, index) resolving lazily to a jax.Device."""

    kind: str = "unknown"

    def __init__(self, index: int = 0):
        self.index = int(index)

    def _jax_backend(self) -> str:
        raise NotImplementedError

    def jax_device(self) -> jax.Device:
        try:
            devs = jax.devices(self._jax_backend())
        except RuntimeError as e:
            raise UnavailableError(
                f"No {self.kind} devices available: {e}") from None
        if self.index >= len(devs):
            raise InvalidArgumentError(
                f"{self.kind}:{self.index} out of range; "
                f"{len(devs)} device(s) present")
        return devs[self.index]

    def __eq__(self, other):
        return (isinstance(other, Place) and self.kind == other.kind
                and self.index == other.index)

    def __hash__(self):
        return hash((self.kind, self.index))

    def __repr__(self):
        return f"Place({self.kind}:{self.index})"


class CPUPlace(Place):
    kind = "cpu"

    def _jax_backend(self) -> str:
        return "cpu"


class TPUPlace(Place):
    """A single TPU chip/core. The reference's CUDAPlace analog."""
    kind = "tpu"

    def _jax_backend(self) -> str:
        # No TPU here: fall through to the default backend, which is what
        # lets reference-style scripts ask for an accelerator place on a
        # CPU-only host. A caller that needs the chip checks the platform
        # of the device it got, not the place it asked for.
        try:
            if jax.devices("tpu"):
                return "tpu"
        except RuntimeError:
            pass
        return jax.default_backend()


_tls = threading.local()


def _parse(device: Union[str, Place]) -> Place:
    if isinstance(device, Place):
        return device
    if not isinstance(device, str):
        raise InvalidArgumentError(f"Cannot parse device: {device!r}")
    dev = device.lower()
    if ":" in dev:
        kind, idx = dev.split(":", 1)
        idx = int(idx)
    else:
        kind, idx = dev, 0
    if kind in ("cpu",):
        return CPUPlace(idx)
    if kind in ("tpu", "xla", "gpu", "accelerator"):  # gpu accepted for compat
        return TPUPlace(idx)
    raise InvalidArgumentError(f"Unknown device kind: {device!r}")


def set_device(device: Union[str, Place]) -> Place:
    place = _parse(device)
    _tls.place = place
    jax.config.update("jax_default_device", place.jax_device())
    return place


def get_device() -> Place:
    place = getattr(_tls, "place", None)
    if place is None:
        # Default: accelerator if present else CPU.
        backend = jax.default_backend()
        place = CPUPlace(0) if backend == "cpu" else TPUPlace(0)
        _tls.place = place
    return place


@contextlib.contextmanager
def device_guard(device: Union[str, Place]):
    """Scoped device switch (reference framework.py:6021 device_guard)."""
    prev = get_device()
    set_device(device)
    try:
        yield
    finally:
        set_device(prev)


def is_compiled_with_tpu() -> bool:
    try:
        return len(jax.devices()) > 0 and jax.default_backend() != "cpu"
    except RuntimeError:
        return False


def device_count() -> int:
    return jax.device_count()
