"""Tape/graph autograd engine for eager mode.

Analog of the reference's dygraph autograd: ``Tracer::TraceOp`` records a
``GradOpNode`` per executed op (/root/reference/paddle/fluid/imperative/
tracer.cc:133,207), ``BasicEngine`` executes the reverse graph with dependency
counting (imperative/basic_engine.cc:39,235,305), ``GradientAccumulator`` sums
fan-in gradients (gradient_accumulator.h:27), and ``PartialGradEngine``
implements ``paddle.grad`` (partial_grad_engine.cc).

TPU-native design: instead of per-op hand-written grad kernels, each eager op
is a pure jax function; when gradients are required we run it under
``jax.vjp`` and store the returned vjp closure on the grad node. XLA thus
provides every backward rule; the engine only does graph bookkeeping
(dependency counts, accumulation, hooks) — which is exactly the part of the
reference's BasicEngine that is not kernel dispatch.
"""

from __future__ import annotations

import contextlib
import threading
import weakref
from collections import deque
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core import flags
from ..core.errors import (InvalidArgumentError, PreconditionNotMetError,
                           ResourceExhaustedError)
from ..core.tensor import Tensor

__all__ = ["apply", "apply_custom_vjp", "run_backward", "grad", "no_grad",
           "enable_grad", "is_grad_enabled", "set_grad_enabled", "GradNode",
           "traced_scopes", "scope"]

_tls = threading.local()


def is_grad_enabled() -> bool:
    return getattr(_tls, "grad_enabled", True)


def set_grad_enabled(mode: bool) -> None:
    _tls.grad_enabled = bool(mode)


class _GradCtx:
    def __init__(self, mode: bool):
        self._mode = mode

    def __enter__(self):
        self._prev = is_grad_enabled()
        set_grad_enabled(self._mode)
        return self

    def __exit__(self, *exc):
        set_grad_enabled(self._prev)
        return False

    def __call__(self, fn):
        import functools

        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            with _GradCtx(self._mode):
                return fn(*args, **kwargs)
        return wrapper


def no_grad(fn=None):
    """Context manager/decorator disabling tape recording (reference
    fluid/dygraph/base.py:207 no_grad)."""
    ctx = _GradCtx(False)
    return ctx(fn) if fn is not None else ctx


def enable_grad(fn=None):
    ctx = _GradCtx(True)
    return ctx(fn) if fn is not None else ctx


@contextlib.contextmanager
def traced_scopes():
    """Name the work while a step is traced: inside, ``Layer.__call__``
    and :func:`apply` enter ``jax.named_scope`` (the layer's name in its
    parent, the op's name), so that each instruction of the compiled
    program carries its layer path and op in ``op_name``. Scopes are
    trace-time metadata: the instructions themselves do not change.
    Outside, the eager path pays one attribute test."""
    was = getattr(_tls, "scopes", False)
    _tls.scopes = True
    try:
        yield
    finally:
        _tls.scopes = was


def scope(name: str):
    """``jax.named_scope(name)`` inside :func:`traced_scopes`, nothing
    outside: for a model that names a part of its forward pass which is no
    layer and no op of its own (a loop over shared layers, a group of
    heads)."""
    if getattr(_tls, "scopes", False):
        return jax.named_scope(name)
    return contextlib.nullcontext()


def _is_float(x) -> bool:
    return jnp.issubdtype(jnp.result_type(x), jnp.floating) or \
        jnp.issubdtype(jnp.result_type(x), jnp.complexfloating)


# live-GradNode census behind the eager_max_tape_len safety valve: an
# eager loop that records ops forever without ever running backward
# (the leak shape the flag exists for) fails loudly instead of growing
# host memory until the OOM killer picks a victim. A deque of tokens,
# not an int: append()/pop() are single C calls — atomic under the GIL
# from any thread AND from a GC-triggered __del__ interleaving with an
# in-progress update, where an `n += 1` read-modify-write would lose
# counts (and a lock could self-deadlock when __del__ fires inside the
# locked region of the same thread)
_live_nodes: deque = deque()


def _live_node_count() -> int:
    return len(_live_nodes)


class GradNode:
    """One reverse-graph node: the vjp closure of one executed op plus edges
    to producer nodes / leaf tensors."""

    __slots__ = ("name", "vjp_fn", "in_edges", "out_tensors", "n_outputs",
                 "out_float", "out_shapes", "_counted")

    def __init__(self, name: str, vjp_fn: Callable,
                 in_edges: List[Tuple[Optional["GradNode"], int,
                                      Optional[Tensor]]],
                 out_tensors: List[Tensor]):
        _live_nodes.append(None)
        self._counted = True
        if len(_live_nodes) > flags.flag("eager_max_tape_len"):
            _live_nodes.pop()
            self._counted = False
            raise ResourceExhaustedError(
                f"autograd graph exceeds eager_max_tape_len="
                f"{flags.flag('eager_max_tape_len')} live grad nodes — "
                "an eager loop recording ops without ever calling "
                ".backward() (or running under no_grad()) leaks the "
                "whole graph; wrap inference in no_grad(), call "
                "backward, or raise the flag")
        self.name = name
        self.vjp_fn = vjp_fn
        # Per differentiable input: (producer_node, producer_out_index,
        # leaf_tensor_or_hooked_tensor). producer_node None ⇒ leaf.
        self.in_edges = in_edges
        # weakrefs for hook firing / retain_grad on intermediate outputs
        self.out_tensors = [weakref.ref(t) for t in out_tensors]
        self.n_outputs = len(out_tensors)
        self.out_float = [_is_float(t.data) for t in out_tensors]
        self.out_shapes = [(t.data.shape, t.data.dtype) for t in out_tensors]

    def _uncount(self):
        if self._counted:
            self._counted = False
            try:
                _live_nodes.pop()
            except IndexError:  # pragma: no cover - cannot underflow
                pass            # unless census resets race teardown

    def release(self):
        self.vjp_fn = None
        self.in_edges = []
        self._uncount()

    def __del__(self):
        # a node GC'd without release() (its tensors simply died) must
        # leave the census too, or the valve trips on long well-behaved
        # eager runs
        if getattr(self, "_counted", False):
            self._uncount()


def apply(name: str, pure_fn: Callable, tensor_inputs: Sequence[Tensor],
          n_outputs: Optional[int] = None, **attrs) -> Any:
    """Execute one op eagerly, recording a grad node if needed.

    ``pure_fn`` takes raw jax arrays (same arity as ``tensor_inputs``) plus
    ``attrs`` and returns one array or a tuple of arrays. Inputs that are not
    Tensors are passed through as-is (static arguments). This is the single
    choke-point all eager ops go through — the TraceOp analog.
    """
    from .. import profiler as _prof
    scoped = getattr(_tls, "scopes", False)
    if not (scoped or _prof._enabled):
        return _apply_impl(name, pure_fn, tensor_inputs, n_outputs, **attrs)
    with contextlib.ExitStack() as stack:
        if scoped:
            # every instruction of a traced step carries its op's name
            # behind the layer path, whichever implementation runs
            stack.enter_context(jax.named_scope(name))
        if _prof._enabled:
            stack.enter_context(_prof.RecordEvent(name))
        return _apply_impl(name, pure_fn, tensor_inputs, n_outputs, **attrs)


def _apply_impl(name: str, pure_fn: Callable,
                tensor_inputs: Sequence[Tensor],
                n_outputs: Optional[int] = None, **attrs) -> Any:
    arrays = [t.data if isinstance(t, Tensor) else t for t in tensor_inputs]

    # AMP auto-cast (reference imperative/amp_auto_cast.cc): white-list ops
    # run in the amp dtype, black-list ops in f32.
    from ..amp import amp_state
    amp = amp_state()
    if amp is not None and amp.enabled:
        import jax.numpy as _jnp
        if name in amp.white:
            arrays = [a.astype(amp.dtype)
                      if hasattr(a, "dtype") and
                      _jnp.issubdtype(a.dtype, _jnp.floating) else a
                      for a in arrays]
        elif name in amp.black:
            arrays = [a.astype(_jnp.float32)
                      if hasattr(a, "dtype") and
                      _jnp.issubdtype(a.dtype, _jnp.floating) and
                      a.dtype != _jnp.float64 else a
                      for a in arrays]

    # Which inputs do we differentiate against?
    diff_idx = []
    if is_grad_enabled():
        for i, t in enumerate(tensor_inputs):
            if isinstance(t, Tensor) and not t.stop_gradient and _is_float(t.data):
                diff_idx.append(i)

    if not diff_idx:
        outs = pure_fn(*arrays, **attrs)
        return _wrap_outputs(name, outs, stop_gradient=True)

    # Close over non-differentiated inputs; vjp only over the float ones.
    def partial_fn(*diff_args):
        full = list(arrays)
        for k, i in enumerate(diff_idx):
            full[i] = diff_args[k]
        return pure_fn(*full, **attrs)

    diff_arrays = [arrays[i] for i in diff_idx]
    outs, vjp_fn = jax.vjp(partial_fn, *diff_arrays)

    out_list, single = _normalize_outputs(outs)
    out_tensors = [Tensor(o, stop_gradient=False) for o in out_list]

    in_edges = []
    for i in diff_idx:
        t = tensor_inputs[i]
        in_edges.append((t._node, t._output_index, t))
    node = GradNode(name, vjp_fn, in_edges, out_tensors)
    for j, ot in enumerate(out_tensors):
        ot._node = node
        ot._output_index = j

    if flags.flag("check_nan_inf"):
        for o in out_list:
            if _is_float(o) and not bool(jnp.all(jnp.isfinite(o))):
                raise PreconditionNotMetError(
                    f"NaN/Inf detected in output of op '{name}'")

    return out_tensors[0] if single else tuple(out_tensors)


def apply_custom_vjp(name: str, fwd_fn: Callable, bwd_fn: Callable,
                     tensor_inputs: Sequence[Tensor], **attrs) -> Any:
    """Execute an op with a *caller-supplied* backward rule.

    The extension point for ops whose cotangents are not plain arrays
    (e.g. embedding's IndexedSlices gradient) or whose backward should not
    be jax.vjp of the forward. ``fwd_fn(*arrays, **attrs)`` returns
    ``(outputs, residuals)``; ``bwd_fn(residuals, cotangents)`` returns one
    gradient per ``tensor_inputs`` entry (None / array / IndexedSlices) —
    the engine keeps only the ones that require grad. This is the analog of
    the reference's custom-operator registration
    (fluid/framework/custom_operator.cc) at the tape level.
    """
    arrays = [t.data if isinstance(t, Tensor) else t for t in tensor_inputs]
    if getattr(_tls, "scopes", False):
        with jax.named_scope(name):
            outs, residuals = fwd_fn(*arrays, **attrs)
    else:
        outs, residuals = fwd_fn(*arrays, **attrs)

    diff_idx = []
    if is_grad_enabled():
        for i, t in enumerate(tensor_inputs):
            if isinstance(t, Tensor) and not t.stop_gradient and \
                    _is_float(t.data):
                diff_idx.append(i)
    if not diff_idx:
        return _wrap_outputs(name, outs, stop_gradient=True)

    out_list, single = _normalize_outputs(outs)
    out_tensors = [Tensor(o, stop_gradient=False) for o in out_list]

    def vjp_fn(cotangents):
        all_grads = bwd_fn(residuals, cotangents)
        if not isinstance(all_grads, (tuple, list)):
            all_grads = (all_grads,)
        return tuple(all_grads[i] for i in diff_idx)

    in_edges = []
    for i in diff_idx:
        t = tensor_inputs[i]
        in_edges.append((t._node, t._output_index, t))
    node = GradNode(name, vjp_fn, in_edges, out_tensors)
    for j, ot in enumerate(out_tensors):
        ot._node = node
        ot._output_index = j
    return out_tensors[0] if single else tuple(out_tensors)


def _normalize_outputs(outs):
    if isinstance(outs, (tuple, list)):
        return list(outs), False
    return [outs], True


def _wrap_outputs(name, outs, stop_gradient):
    out_list, single = _normalize_outputs(outs)
    ts = [Tensor(o, stop_gradient=stop_gradient) for o in out_list]
    return ts[0] if single else tuple(ts)


# ---------------------------------------------------------------------------
# Backward execution (BasicEngine analog)
# ---------------------------------------------------------------------------


def _fire_hooks(tensor_ref, g):
    t = tensor_ref() if isinstance(tensor_ref, weakref.ref) else tensor_ref
    if t is None:
        return g
    for entry in t._hooks:
        hook = entry[0]
        if hook is None:
            continue
        res = hook(Tensor(g, stop_gradient=True))
        if res is not None:
            g = res.data if isinstance(res, Tensor) else jnp.asarray(res)
    return g


def _gadd(a, b):
    """Gradient accumulation that understands IndexedSlices fan-in
    (reference GradientAccumulator: SelectedRows+SelectedRows concatenates,
    SelectedRows+dense scatters — gradient_accumulator.cc MergeAdd)."""
    from ..core.indexed_slices import IndexedSlices
    if isinstance(a, IndexedSlices):
        return a + b if isinstance(b, IndexedSlices) else a.add_to_dense(b)
    if isinstance(b, IndexedSlices):
        return b.add_to_dense(a)
    return a + b


def _accumulate(tensor: Tensor, g) -> None:
    if tensor._grad is None:
        tensor._grad = Tensor(g, stop_gradient=True)
    else:
        tensor._grad = Tensor(_gadd(tensor._grad.data, g),
                              stop_gradient=True)


def run_backward(tensors: Sequence[Tensor],
                 grad_tensors: Sequence[Optional[Tensor]],
                 retain_graph: bool = False,
                 collect_for: Optional[Sequence[Tensor]] = None,
                 accumulate_leaves: bool = True,
                 allow_unused: bool = True) -> Optional[List[Optional[Tensor]]]:
    """Reverse pass with dependency counting.

    With ``collect_for`` set, behaves like PartialGradEngine (paddle.grad):
    returns grads for those tensors; ``accumulate_leaves=False`` leaves
    ``.grad`` untouched.
    """
    roots: List[Tuple[GradNode, int, Any]] = []
    leaf_seed: List[Tuple[Tensor, Any]] = []
    for t, g in zip(tensors, grad_tensors):
        if t.stop_gradient:
            raise PreconditionNotMetError(
                "backward() on a tensor with stop_gradient=True")
        if g is None:
            if t.size != 1:
                raise InvalidArgumentError(
                    "grad must be provided for non-scalar backward root "
                    f"(shape {t.shape})")
            garr = jnp.ones_like(t.data)
        else:
            garr = g.data if isinstance(g, Tensor) else jnp.asarray(g)
        if t._node is None:
            leaf_seed.append((t, garr))
        else:
            roots.append((t._node, t._output_index, garr))

    # Reachability + dependency counts (BasicEngine::PrepareDeps analog).
    deps: Dict[int, int] = {}
    nodes: Dict[int, GradNode] = {}
    stack = [n for n, _, _ in roots]
    seen = set()
    while stack:
        n = stack.pop()
        if id(n) in seen:
            continue
        seen.add(id(n))
        nodes[id(n)] = n
        for (pn, pout, _t) in n.in_edges:
            if pn is not None:
                deps[id(pn)] = deps.get(id(pn), 0) + 1
                if id(pn) not in seen:
                    stack.append(pn)

    # Pending output-cotangent buffers per node.
    pending: Dict[int, List[Any]] = {
        nid: [None] * n.n_outputs for nid, n in nodes.items()}
    ready = deque()
    root_ids = set()
    for n, oi, g in roots:
        buf = pending[id(n)]
        buf[oi] = g if buf[oi] is None else _gadd(buf[oi], g)
        root_ids.add(id(n))
    for nid in root_ids:
        if deps.get(nid, 0) == 0:
            ready.append(nid)
    # Nodes only reachable as producers start with their computed dep counts;
    # roots with outstanding consumers wait until those consumers run.

    collect: Dict[int, Any] = {}
    collect_ids = {id(t) for t in (collect_for or [])}

    executed = set()
    while ready:
        nid = ready.popleft()
        if nid in executed:
            continue
        executed.add(nid)
        node = nodes[nid]
        cotangents = []
        for j in range(node.n_outputs):
            g = pending[nid][j]
            if g is None:
                shape, dt = node.out_shapes[j]
                if node.out_float[j]:
                    g = jnp.zeros(shape, dt)
                else:
                    g = np.zeros(shape, jax.dtypes.float0)
            else:
                # fire hooks registered on the *output* tensor of this node
                g = _fire_hooks(node.out_tensors[j], g)
                ot = node.out_tensors[j]()
                if ot is not None and (ot._retain_grad or
                                       flags.flag("retain_grad_for_all")):
                    _accumulate(ot, g)
                if ot is not None and id(ot) in collect_ids:
                    prev = collect.get(id(ot))
                    collect[id(ot)] = g if prev is None else _gadd(prev, g)
            cotangents.append(g)
        outs = cotangents[0] if node.n_outputs == 1 else tuple(cotangents)
        # jax.vjp returned a tuple-cotangent function over the tuple output
        try:
            in_grads = node.vjp_fn(outs)
        except TypeError:
            in_grads = node.vjp_fn(tuple(cotangents))
        if not isinstance(in_grads, (tuple, list)):
            in_grads = (in_grads,)

        for (pn, pout, t), ig in zip(node.in_edges, in_grads):
            if ig is None or (hasattr(ig, "dtype") and
                              ig.dtype == jax.dtypes.float0):
                continue
            if pn is None:
                # Leaf: fire hooks then accumulate into .grad
                ig = _fire_hooks(t, ig)
                if id(t) in collect_ids:
                    prev = collect.get(id(t))
                    collect[id(t)] = ig if prev is None else _gadd(prev, ig)
                if accumulate_leaves:
                    _accumulate(t, ig)
            else:
                pid = id(pn)
                buf = pending[pid]
                buf[pout] = ig if buf[pout] is None else _gadd(buf[pout], ig)
                deps[pid] -= 1
                if deps[pid] == 0:
                    ready.append(pid)
        if not retain_graph:
            node.release()

    # Seeds that were themselves leaves.
    for t, g in leaf_seed:
        g = _fire_hooks(t, g)
        if id(t) in collect_ids:
            prev = collect.get(id(t))
            collect[id(t)] = g if prev is None else _gadd(prev, g)
        if accumulate_leaves:
            _accumulate(t, g)

    if collect_for is not None:
        out = []
        for t in collect_for:
            g = collect.get(id(t))
            if g is None and not allow_unused:
                raise InvalidArgumentError(
                    "One of the differentiated tensors appears unused in the "
                    "graph; pass allow_unused=True to return None for it")
            out.append(None if g is None else Tensor(g, stop_gradient=True))
        return out
    # A full backward (Tensor.backward, not paddle.grad) marks the end of
    # a forward pass — observers (e.g. fluid.layers implicit-parameter
    # pass tracking) hook here.
    for cb in list(_backward_end_callbacks):
        cb()
    return None


_backward_end_callbacks: List[Callable[[], None]] = []


def register_backward_end_callback(fn: Callable[[], None]) -> None:
    """Call ``fn`` after every completed full backward pass."""
    if fn not in _backward_end_callbacks:
        _backward_end_callbacks.append(fn)


def grad(outputs, inputs, grad_outputs=None, retain_graph=None,
         create_graph=False, only_inputs=True, allow_unused=False):
    """paddle.grad equivalent (reference fluid/dygraph/base.py:392 →
    PartialGradEngine). ``create_graph`` (double grad) is not yet supported —
    use the functional jax path for higher-order derivatives."""
    if create_graph:
        from ..core.errors import UnimplementedError
        raise UnimplementedError(
            "create_graph=True: use paddle1_tpu.incubate.functional.grad "
            "(jax.grad composition) for higher-order autodiff")
    outputs = outputs if isinstance(outputs, (list, tuple)) else [outputs]
    inputs = inputs if isinstance(inputs, (list, tuple)) else [inputs]
    if grad_outputs is None:
        grad_outputs = [None] * len(outputs)
    elif not isinstance(grad_outputs, (list, tuple)):
        grad_outputs = [grad_outputs]
    retain = bool(retain_graph) if retain_graph is not None else False
    return run_backward(outputs, grad_outputs, retain_graph=retain,
                        collect_for=inputs, accumulate_leaves=False,
                        allow_unused=allow_unused)
