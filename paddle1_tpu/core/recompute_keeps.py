"""What a recomputed segment keeps beside its inputs: the names.

``fleet.utils.recompute`` (``jax.checkpoint``) runs a segment's forward a
second time in the backward pass, so that the segment holds its inputs
and nothing it made. Some of what it made is dearer to make again than
to hold. The op or layer that makes such a value says so where it makes
it, :func:`keep_in_recompute` (``jax.ad_checkpoint.checkpoint_name``
through one helper), and the segment's policy (:func:`keeps`) keeps every
value named so. Outside a segment a name is inert: the value passes
through, eagerly and in a compiled step alike. A named value that
nothing in the backward pass reads is not held either (jax drops the
unread residual): naming costs memory only where it spares work.

**The rule is written, not set**: no flag, argument or budget chooses
what is kept. A value is named if making it again costs at least
:data:`MS_A_MB` (0.02) ms a MB kept on a TPU v5e, measured in a trace of
``rematted_computation`` and written beside the name. The arithmetic
that predicts it: a product ``[T, K] x [K, N]`` takes ``2TKN`` FLOP to
make ``2TN`` bytes of bf16, at the MXU's 197 TFLOP/s ``K x 5.08e-6`` ms a
MB whatever ``T`` and ``N`` are: a projection that narrows (``K`` 4096,
5632) is dear to run again and cheap to keep, one that widens (``K``
2048: 0.010) the reverse; a sort or a ``top_k`` makes a few integers a
row for milliseconds. PERF.md (PR 37) has the tables.

What a traced segment was given to keep is counted in the process
registry, ``recompute_kept_bytes_total{name}`` and
``recompute_kept_values_total{name}`` (by shape and dtype, when the
segment is traced, beside ``flash_tiles_total{kind}``): a name that no
longer matches anything after a refactoring reads 0 there.
"""

from __future__ import annotations

import contextlib
import threading

from jax.ad_checkpoint import checkpoint_name

__all__ = ["MS_A_MB", "keep_in_recompute", "kept_names", "keeps",
           "segment"]

MS_A_MB = 0.02      # the least a value costs to make again, to be named

_NAMES: set = set()
_tls = threading.local()


def kept_names() -> tuple:
    """Every name placed so far in this process, sorted."""
    return tuple(sorted(_NAMES))


def keeps(prim, *_, **params) -> bool:
    """The ``jax.checkpoint`` policy of a recomputed segment: keep what
    :func:`keep_in_recompute` named, and nothing else beside the inputs.
    Reads the names when a segment is differentiated, so one placed by a
    module that the segment's own trace imported is kept too."""
    return prim.name == "name" and params["name"] in _NAMES


def keep_in_recompute(value, name: str):
    """``value`` (a Tensor or an array) under ``name``: inside a
    recomputed segment the value the forward pass made is held for the
    backward pass, if that reads it, and what made it does not run
    again; anywhere else, ``value`` as it came. See the module's
    docstring for when a value earns a name."""
    from .tensor import Tensor
    if isinstance(value, Tensor):
        from ..autograd.engine import apply
        return apply("keep_in_recompute",
                     lambda v: keep_in_recompute(v, name), (value,))
    _NAMES.add(name)
    named = getattr(_tls, "named", None)
    if named is not None:
        named.append((name, value.size * value.dtype.itemsize))
    return checkpoint_name(value, name)


@contextlib.contextmanager
def segment():
    """Round the trace of one segment's forward: what is named inside is
    the segment's (an inner segment's its own), and is counted in the
    process registry when the trace ends."""
    outer, _tls.named = getattr(_tls, "named", None), []
    try:
        yield
        from ..obs.registry import process_group
        by_name = process_group("name")
        for name, size in _tls.named:
            child = by_name.child(name)
            child.counter("recompute_kept_bytes_total").inc(size)
            child.counter("recompute_kept_values_total").inc()
    finally:
        _tls.named = outer
