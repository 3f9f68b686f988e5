"""Activation recomputation (gradient checkpointing).

Analog of the reference's ``RecomputeFunction``
(python/paddle/distributed/fleet/utils/recompute.py:63): a PyLayer that
drops intermediate activations in forward and re-runs the segment (with the
saved RNG state) inside backward.

TPU-native: ``jax.checkpoint`` is exactly this transform, with XLA doing the
re-forward inside the compiled backward, so the implementation collapses to
wrapping the segment's pure function. RNG parity (reference saves/restores
CUDA seeds, recompute.py:88-114) comes for free: the segment's dropout keys
are explicit inputs, so the re-forward reuses identical keys.

Beside its inputs a segment keeps what costs more to make again than to
hold, by name: the op or layer that makes such a value names it where it
makes it (``core/recompute_keeps.py::keep_in_recompute``: the rule, 0.02
ms a MB kept on a v5e, and the registry of names), and the segment's
policy keeps every named value that its backward pass reads. Named so
far, each with its measured ms a MB beside it: the blockwise attention
kernel's ``out`` and ``lse`` (``ops/pallas/flash_attention.py``), the
output of a gated feed-forward's narrowing ``down_proj``
(``nn/layer_transformer.py``), the routed-expert layer's picks, pick
scores, sorted order and sorted rows' weights (``nn/layer_moe.py``), the
stream after attention in Kanana-2's and SDAR's pre-norm layers
(``text/models``). Nothing here chooses among them: no flag, no argument,
no budget.

State that the segment's forward writes (batch norm's running
statistics, the expert layer's load counts) cannot stay in the side list
it is recorded in (``nn/functional/norm.py::collect_stat_updates``): the
values are tracers of the segment's own trace. The segment collects them
itself, returns them as outputs that carry no gradient, and they are
recorded again outside, in whatever collector encloses the call.
"""

from __future__ import annotations

import functools
from typing import Any, Callable

import jax

from ....autograd.engine import apply, no_grad
from ....core import recompute_keeps
from ....core.generator import next_key, rng_scope
from ....core.tensor import Tensor
from ....nn.functional import norm as fnorm
from ....nn.layer_base import Layer

__all__ = ["recompute", "recompute_sequential"]


def recompute(function: Callable, *args, **kwargs):
    """Run ``function(*args)`` keeping its inputs and every value inside
    that carries a name of ``core/recompute_keeps.py`` and is read by the
    backward pass; backward re-executes the rest (reference
    recompute.py:162 recompute()). A segment that holds no named value
    (a block with XLA's dense attention and a plain feed-forward) keeps
    its inputs alone. What is kept is the value the forward pass made,
    to the bit. What it costs follows from the names inside: the
    attention kernel's ``out`` is one more tensor of the input's size a
    call at sequence >= 1024 (``lse`` 4 bytes x tokens x heads), a
    narrowing projection's output another;
    ``recompute_kept_bytes_total{name}`` in the process registry sums
    what each traced segment was given."""
    preserve = kwargs.pop("preserve_rng_state", True)
    use_reentrant = kwargs.pop("use_reentrant", True)
    if kwargs:
        raise TypeError(f"recompute got unexpected kwargs {list(kwargs)}")

    layer = function if isinstance(function, Layer) else None
    bound_self = getattr(function, "__self__", None)
    bound_method = None
    if layer is None and isinstance(bound_self, Layer):
        layer = bound_self      # bound method of a Layer: params threadable
        bound_method = function  # may be forward or any other method
    key = next_key()

    # split args into traced tensors and static (non-tensor) values,
    # preserving positions so the segment sees the original signature
    tensor_pos = [i for i, a in enumerate(args) if isinstance(a, Tensor)]
    tensor_args = [args[i] for i in tensor_pos]

    def _rebuild_args(arrays):
        full = list(args)
        for pos, arr in zip(tensor_pos, arrays):
            full[pos] = Tensor(arr, stop_gradient=True)
        return full

    # a Layer is called as a Layer: its hooks run and, in a traced step,
    # its scope is opened inside the segment
    fwd_callable = (bound_method if bound_method is not None else
                    layer if layer is not None else function)

    if layer is not None:
        names = list(layer.functional_state().keys())
        params = [layer.state_dict()[n] for n in names]
        # what the segment's forward wrote (batch norm's statistics, the
        # expert layer's counts), filled while ``seg`` is traced: the
        # record of each write without its value ("written"), how many of
        # the segment's outputs are the function's own ("outputs") and
        # whether it returned a tuple
        made: dict = {}

        @functools.partial(jax.checkpoint, policy=recompute_keeps.keeps)
        def seg(key, param_arrays, *input_arrays):
            # tape off: the segment is differentiated as a whole, by
            # jax, and an op's own vjp rule has to reach it unopened.
            # The collector is the segment's own: a value recorded here
            # is a tracer of this trace and leaves it as an output
            with rng_scope(key), no_grad(), layer.load_functional_state(
                    dict(zip(names, param_arrays))), \
                    fnorm.collect_stat_updates() as sink, \
                    recompute_keeps.segment():
                out = fwd_callable(*_rebuild_args(input_arrays))
            made["tuple"] = isinstance(out, (tuple, list))
            outs = tuple(t.data for t in out) if made["tuple"] \
                else (out.data,)
            made["outputs"] = len(outs)
            made["written"] = [(u.buffer, u.rule, u.momentum, u.what)
                               for u in sink]
            return outs + tuple(jax.lax.stop_gradient(u.value) for u in sink)

        def op(*flat):
            p = list(flat[:len(params)])
            x = flat[len(params):]
            outs = seg(key, p, *x)
            return outs[0] if len(outs) == 1 else outs

        flat = apply("recompute", op, tuple(params + tensor_args))
        flat = flat if isinstance(flat, tuple) else (flat,)
        # written again where the segment was called: the enclosing
        # collector's (the step's, an outer segment's), or the buffer
        # itself where nothing is being traced
        for (buffer, rule, momentum, what), value in zip(
                made["written"], flat[made["outputs"]:]):
            fnorm.record_state_update(buffer, value.data, rule, momentum,
                                      what)
        outs = flat[:made["outputs"]]
        return outs if made["tuple"] else outs[0]

    # Opaque callable: parameters it closes over cannot be threaded into
    # jax.checkpoint as differentiable inputs, and capturing them as trace
    # constants would SILENTLY drop their gradients. Run the segment on the
    # normal tape instead — correct grads, no memory saving — and say so.
    import warnings
    warnings.warn(
        "recompute() got an opaque callable; cannot prove it uses no layer "
        "parameters, so activations are NOT discarded (gradients stay "
        "correct). Pass the Layer itself (or its bound .forward) to get "
        "actual recomputation.", stacklevel=2)
    return function(*args)


def recompute_sequential(ctx: dict, functions, *args):
    """Recompute over a Sequential in ``segments`` chunks (reference
    recompute_sequential / recompute_hybrid)."""
    segments = ctx.get("segments", 1)
    layers = list(functions)
    per = max(1, len(layers) // segments)
    x = args[0] if len(args) == 1 else args
    for i in range(0, len(layers), per):
        chunk = layers[i:i + per]

        class _Seg(Layer):
            def __init__(self, ls):
                super().__init__()
                from ....nn.layer_norm_act import LayerList
                self.ls = LayerList(ls)

            def forward(self, x):
                for l in self.ls:
                    x = l(x)
                return x

        x = recompute(_Seg(chunk), x)
    return x
