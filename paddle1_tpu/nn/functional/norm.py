"""Functional normalization.

Analog of /root/reference/paddle/fluid/operators/{batch_norm_op,layer_norm_op,
group_norm_op,instance_norm_op}.cc and python/paddle/nn/functional/norm.py.
LayerNorm is the transformer hot path: the fused Pallas kernel in
ops/pallas/layer_norm.py is used under jit when shapes allow; this reference
implementation is the fallback and the numeric ground truth.
"""

from __future__ import annotations

import contextlib
import functools
import threading
from typing import Optional

import jax
import jax.numpy as jnp

from ...autograd.engine import apply
from ...core.errors import InvalidArgumentError
from ...core.tensor import Tensor, to_tensor

__all__ = ["batch_norm", "fused_batch_norm_act", "layer_norm",
           "instance_norm", "group_norm", "local_response_norm",
           "normalize", "collect_stat_updates"]


def _t(x):
    return x if isinstance(x, Tensor) else to_tensor(x)


# id-keyed weakrefs (not instance attributes: Tensor's __slots__ has no
# __dict__, and not a WeakSet: Tensor __eq__ is elementwise). The
# finalizer pops the entry so a recycled id can't suppress a NEW
# buffer's warning and the registry can't grow unboundedly.
_warned_stat_buffers: dict = {}

# State written in a forward, functionalized (ADVICE r5 medium; PR 3 only
# added the warning): a framework-owned compiled path (ParallelEngine's
# train step) opens a collector around the traced forward; a layer whose
# new state comes back as tracers (batch norm's batch statistics, the
# expert layer's load counts) RECORDS the update here instead of
# assigning a tracer into its buffer, the step builder folds it into the
# step's outputs by the record's rule, and the engine's normal flow
# (sync_model / checkpoints) assigns it outside the trace.
# ``fleet.utils.recompute`` opens a collector of its own inside its
# ``jax.checkpoint`` segment, hands what was recorded out as outputs of
# the segment and records it again outside: a record never holds a
# tracer of a trace that has ended. User-compiled fns (plain jax.jit /
# to_static) have no collector, so they keep the loud warn-and-skip
# path. This is the one channel: no second sink, no host callback.
_stat_sink = threading.local()


class StateUpdate:
    """One traced write to a buffer: the buffer's Tensor (its identity
    is the key into the compiled step's state: ``load_functional_state``
    swaps arrays inside the same Tensor), the traced value and the rule
    that folds it into the old value: ``blend`` (``momentum * old + (1 -
    momentum) * value``: a running statistic) or ``add`` (``old +
    value``: a counter; several writes of one forward sum)."""

    __slots__ = ("buffer", "value", "rule", "momentum", "what")

    def __init__(self, buffer, value, rule, momentum=None, what=None):
        self.buffer = buffer
        self.value = value
        self.rule = rule
        self.momentum = momentum
        self.what = what


@contextlib.contextmanager
def collect_stat_updates():
    """Arm the functionalized capture of state written in a forward for
    this thread's current trace; yields the list of :class:`StateUpdate`
    the step builder (or the recomputed segment) consumes."""
    prev = getattr(_stat_sink, "sink", None)
    sink: list = []
    _stat_sink.sink = sink
    try:
        yield sink
    finally:
        _stat_sink.sink = prev


def fold_state_update(old, value, rule, momentum=None):
    """``old`` after one recorded write, in ``old``'s dtype."""
    if rule == "add":
        return old + value.astype(old.dtype)
    cur = old.astype(jnp.float32)
    return (momentum * cur + (1 - momentum) * value).astype(old.dtype)


def record_state_update(buffer, value, rule, momentum=None,
                        what: Optional[str] = None) -> None:
    """A forward writes ``buffer`` (a Tensor) by ``rule``. A concrete
    ``value`` is folded in at once (the eager path). A traced one goes
    to the active collector; with none (a user-compiled fn) the write is
    skipped, with a warning where ``what`` names a statistic that eval
    forwards will miss, silently for a counter (``what`` None)."""
    if not isinstance(value, jax.core.Tracer):
        buffer._data = fold_state_update(buffer.data, value, rule, momentum)
        return
    sink = getattr(_stat_sink, "sink", None)
    if sink is not None:
        sink.append(StateUpdate(buffer, value, rule, momentum, what))
    elif what is not None:
        warn_traced_stats_skipped(buffer, what)


def _record_traced_stat_update(running_mean, running_var, mean_arr,
                               var_arr, momentum, what: str) -> None:
    """Batch stats arrived as tracers: functionalize under an active
    collector, else warn-and-skip (user-compiled fn)."""
    record_state_update(running_mean, mean_arr, "blend", momentum, what)
    # the mean's warning covers the pair
    record_state_update(running_var, var_arr, "blend", momentum)


def warn_traced_stats_skipped(buffer, what: str) -> None:
    """Warn (once per buffer) that a running-stat update was skipped
    because the batch stats are traced values (jit/shard_map).

    The reference updates running mean/var in-graph, so a migrated
    script trained entirely under jit keeps its INIT running stats
    (mean=0, var=1) and eval-mode forwards silently diverge. We cannot
    assign a tracer into the buffer (it would leak into eval forwards
    and state_dict), so the update is skipped — loudly. Workaround:
    after (or periodically during) compiled training, run one EAGER
    training-mode forward over a representative batch to refresh the
    running stats, or construct the layer/call with
    ``use_global_stats=True`` semantics in mind and load stats from a
    checkpoint that has them."""
    import weakref
    key = id(buffer)
    ref = _warned_stat_buffers.get(key)
    if ref is not None and ref() is buffer:
        return
    try:
        _warned_stat_buffers[key] = weakref.ref(
            buffer, lambda _, k=key: _warned_stat_buffers.pop(k, None))
    except TypeError:  # unweakrefable buffer type: warn every time
        pass
    import warnings
    warnings.warn(
        f"{what}: running mean/var update SKIPPED because the batch "
        "stats are traced (jit/shard_map) — the buffers keep their "
        "previous (possibly init) values, so eval-mode forwards after "
        "compiled-only training will use stale statistics. Refresh "
        "them with one eager training-mode forward after training "
        "(warned once per buffer).")


def fused_bn_active(shape, dtype) -> bool:
    """Resolve the ``fused_bn`` flag against a channels-LAST input whose
    statistics are GIVEN (eval mode, SyncBatchNorm's local halves):
    ``always`` / ``never`` are absolute, ``auto`` takes the Pallas
    kernels on a TPU backend (flag_active). Training-mode batch norm
    never asks: it is the XLA composition on every shape (PERF.md,
    PR 26)."""
    from ...core.flags import flag_active
    from ...ops.pallas import fused_bn as pbn
    return flag_active("fused_bn") and pbn.supported(shape, dtype)


# Cached weak-typed device scalars (epsilon, momentum, the relu zero).
# A python float inside an eager op body is lifted as a FRESH device
# constant on every call — one host->device transfer per BN layer per
# forward (the ISSUE 15 satellite-6 audit finding; measurable dispatch
# latency on TPU). A cached weak-typed jnp scalar is already device-
# resident and, being weak, does not promote bf16 compute to f32.
_scalar_cache: dict = {}


def _scalar(v: float):
    key = float(v)
    arr = _scalar_cache.get(key)
    if arr is None:
        arr = jnp.asarray(key)
        # under an active trace jnp.asarray yields a TRACED constant —
        # caching it would leak the tracer into later eager calls (and
        # inside a trace the constant folds into the jaxpr for free,
        # so there is nothing worth caching)
        if not isinstance(arr, jax.core.Tracer):
            _scalar_cache[key] = arr
    return arr


def _apply_act(y, act):
    if act == "relu":
        return jnp.maximum(y, _scalar(0.0))
    return y


def _bn_reduction(x, ch_axis):
    """-> (the axes batch norm reduces over, 1 / their element count as
    a cached scalar, the shape that broadcasts a [C] vector against x)."""
    axes = tuple(i for i in range(x.ndim) if i != ch_axis)
    n = 1
    for i in axes:
        n *= x.shape[i]
    bshape = [1] * x.ndim
    bshape[ch_axis] = -1
    return axes, _scalar(1.0 / n), bshape


def _bn_train_forward(x, gamma, beta, residual, eps, act, ch_axis):
    """Training-mode batch norm as one XLA composition, on the tensor as
    it comes (any channel axis, no reshape). For 16-bit ``x``:
    per-channel sum and sum of squares in ONE pass (a multi-output
    reduction the compiler hangs on the convolution that produces
    ``x``), mean / var / rstd in float32 with the count an exact
    constant and the variance clamped at 0; the normalise + affine
    (+ residual) + activation chain in float32, rounded once to ``x``'s
    dtype (the compiler fuses it into the convolution that consumes
    ``y``). Wider ``x`` takes a second, centred pass for the variance.
    -> (y, mean, var, rstd)."""
    axes, inv, bshape = _bn_reduction(x, ch_axis)
    ft = jnp.promote_types(x.dtype, jnp.float32)
    xf = x.astype(ft)
    mean = jnp.sum(xf, axis=axes) * inv
    xc = xf - mean.reshape(bshape)
    if jnp.dtype(x.dtype).itemsize <= 2:
        # 16-bit data: float32 sums carry 16 bits more than the data,
        # so E[x^2] - mean^2 costs nothing that the data had
        var = jnp.maximum(jnp.sum(xf * xf, axis=axes) * inv - mean * mean,
                          _scalar(0.0))
    else:
        # float32 data in float32 sums: E[x^2] - mean^2 loses
        # log2(E[x^2] / var) of the 24 bits (a 50-layer net leaves its
        # float32 reference by 3e-3 in the first gradient), so the
        # variance takes a second, centred pass
        var = jnp.sum(xc * xc, axis=axes) * inv
    rstd = jax.lax.rsqrt(var + _scalar(eps))
    y = xc * rstd.reshape(bshape)
    if gamma is not None:
        y = (y * gamma.astype(ft).reshape(bshape)
             + beta.astype(ft).reshape(bshape))
    if residual is not None:
        y = y + residual.astype(ft)
    return _apply_act(y, act).astype(x.dtype), mean, var, rstd


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _bn_train(x, gamma, beta, residual, eps, act, ch_axis):
    return _bn_train_forward(x, gamma, beta, residual, eps, act, ch_axis)[:3]


def _bn_train_fwd_rule(x, gamma, beta, residual, eps, act, ch_axis):
    y, mean, var, rstd = _bn_train_forward(x, gamma, beta, residual, eps,
                                           act, ch_axis)
    # y is kept for the activation's mask alone (the convolution that
    # consumes it keeps it anyway); of the residual only its dtype
    return (y, mean, var), (
        x, gamma, beta, mean, rstd, y if act == "relu" else None,
        None if residual is None else jnp.zeros((0,), residual.dtype))


def _bn_train_bwd_rule(eps, act, ch_axis, saved, cts):
    """dgamma = sum(dy * xhat), dbeta = sum(dy), one expression for dx,
    all in float32 inside whatever fusion the compiler builds. The
    statistics' cotangents are dropped: they feed the running averages
    only (the reference's SavedMean / SavedVariance are not
    differentiable outputs), and a term for them, zero or not, is a
    full pass over x."""
    x, gamma, beta, mean, rstd, y, res_proto = saved
    axes, inv, bshape = _bn_reduction(x, ch_axis)
    ft = mean.dtype
    dyf = cts[0].astype(ft)
    if act == "relu":
        dyf = jnp.where(y > 0, dyf, _scalar(0.0))
    xhat = (x.astype(ft) - mean.reshape(bshape)) * rstd.reshape(bshape)
    dg = jnp.sum(dyf * xhat, axis=axes)
    db = jnp.sum(dyf, axis=axes)
    scale = rstd if gamma is None else rstd * gamma.astype(ft)
    dx = scale.reshape(bshape) * (
        dyf - (db * inv).reshape(bshape) - xhat * (dg * inv).reshape(bshape))
    return (dx.astype(x.dtype),
            None if gamma is None else dg.astype(gamma.dtype),
            None if beta is None else db.astype(beta.dtype),
            None if res_proto is None else dyf.astype(res_proto.dtype))


_bn_train.defvjp(_bn_train_fwd_rule, _bn_train_bwd_rule)


def _update_running_stats(running_mean, running_var, mean, var, momentum,
                          what):
    if running_mean is None:
        return
    if isinstance(mean.data, jax.core.Tracer):
        # under jit/shard_map the batch stats are traced values —
        # assigning them into the buffer would leak a tracer (eval
        # forward / state_dict would then fail). Inside a
        # framework-owned compiled step the update is FUNCTIONALIZED
        # (collected here, blended into the step's output params,
        # assigned outside the trace); a user-compiled fn gets the
        # warn-and-skip (ADVICE r6 medium: the silence cost real
        # eval divergence).
        _record_traced_stat_update(_t(running_mean), _t(running_var),
                                   mean.data, var.data, momentum, what)
    else:
        rm = _t(running_mean)
        rv = _t(running_var)
        mom = _scalar(momentum)
        rem = _scalar(1 - momentum)
        rm._data = mom * rm.data + rem * mean.data
        rv._data = mom * rv.data + rem * var.data


def _batch_norm_impl(x, running_mean, running_var, weight, bias,
                     training, momentum, epsilon, data_format,
                     use_global_stats, act, residual, what):
    x = _t(x)
    channel_last = data_format in ("NHWC", "NLC", "NDHWC")
    # NCHW 4-D batch norm participates in the channels-last region
    # (_layout.py): computing with the channel axis last makes the
    # boundary transposes sit directly against the neighboring convs'
    # and pools', where XLA cancels them — and is what makes an
    # eval-mode input eligible for the Pallas kernels
    # (ops/pallas/fused_bn.py), which are NHWC-native.
    from ._layout import channels_last_region
    from ...ops.pallas import fused_bn as pbn
    nhwc_internal, to_internal, from_internal = channels_last_region(
        x.ndim, channel_last)
    eff_last = channel_last or nhwc_internal
    ch_axis = x.ndim - 1 if eff_last else 1
    use_stats = (not training) if use_global_stats is None else use_global_stats
    has_wb = weight is not None
    has_res = residual is not None

    def bshape(v, nd):
        shape = [1] * nd
        shape[ch_axis] = -1
        return v.reshape(shape)

    def split_rest(rest):
        wb = rest[:2] if has_wb else ()
        res = rest[-1] if has_res else None
        return wb, res

    res_args = (_t(residual),) if has_res else ()
    wb_args = (_t(weight), _t(bias)) if has_wb else ()

    if use_stats:
        def f(x, m, v, *rest):
            x = to_internal(x)
            wb, res = split_rest(rest)
            if res is not None:
                res = to_internal(res)
            if (has_wb and eff_last
                    and fused_bn_active(x.shape, x.dtype)):
                c = x.shape[-1]
                y2 = pbn.fused_bn_norm(
                    x.reshape(-1, c), m, v, wb[0], wb[1], epsilon,
                    act=act,
                    residual=None if res is None else res.reshape(-1, c))
                return from_internal(y2.reshape(x.shape))
            y = (x - bshape(m, x.ndim)) * jax.lax.rsqrt(
                bshape(v, x.ndim) + _scalar(epsilon))
            if wb:
                y = y * bshape(wb[0], x.ndim) + bshape(wb[1], x.ndim)
            if res is not None:
                y = y + res
            return from_internal(_apply_act(y, act))
        args = (x, _t(running_mean), _t(running_var)) + wb_args + res_args
        return apply(f"{what}_infer", f, args)

    # training: compute batch stats, update running stats in place
    def f(x, *rest):
        x = to_internal(x)
        wb, res = split_rest(rest)
        if res is not None:
            res = to_internal(res)
        y, mean, var = _bn_train(
            x, wb[0] if wb else None, wb[1] if wb else None, res,
            float(epsilon), act, ch_axis)
        return from_internal(y), mean, var

    args = (x,) + wb_args + res_args
    y, mean, var = apply(f"{what}_train", f, args, n_outputs=3)
    _update_running_stats(running_mean, running_var, mean, var, momentum,
                          what)
    return y


def batch_norm(x, running_mean, running_var, weight=None, bias=None,
               training=False, momentum=0.9, epsilon=1e-05,
               data_format="NCHW", use_global_stats=None, name=None):
    """Batch norm with running-stat update (reference batch_norm_op.cc).
    Running stats are updated in-place on the passed tensors, mirroring the
    reference's mutable mean/variance variables. Training mode is one
    XLA composition (``_bn_train_forward``: float32 statistics in one
    pass, one rounding) that the compiler fuses into the neighbouring
    convolutions; the Pallas kernels (ops/pallas/fused_bn.py) take a
    channels-last affine BN in eval mode under ``fused_bn=auto`` on a
    TPU."""
    return _batch_norm_impl(x, running_mean, running_var, weight, bias,
                            training, momentum, epsilon, data_format,
                            use_global_stats, "identity", None,
                            "batch_norm")


def fused_batch_norm_act(x, running_mean, running_var, weight, bias,
                         training=False, momentum=0.9, epsilon=1e-05,
                         data_format="NCHW", act="relu", residual=None,
                         use_global_stats=None, name=None):
    """``y = act(batch_norm(x) + residual)`` as ONE op — the analog of
    the reference's fused_bn_activation_op (act only) and
    fused_bn_add_activation_op (act + residual). The whole chain is
    one XLA composition in float32, rounded once (training mode:
    ``_bn_train_forward``), or in eval mode a single Pallas kernel where
    ``fused_bn`` resolves to it (see ``batch_norm``), with identical
    semantics (including the running-stat update and the
    ``collect_stat_updates`` functionalization under a compiled trainer
    step)."""
    from ...ops.pallas.fused_bn import ACTS
    if act not in ACTS:
        raise InvalidArgumentError(
            f"fused_batch_norm_act: act must be one of {ACTS}, got "
            f"{act!r} (the reference fused op supports these)")
    if weight is None or bias is None:
        raise InvalidArgumentError(
            "fused_batch_norm_act requires affine weight and bias (the "
            "reference fused_bn_activation_op takes Scale and Bias); "
            "use batch_norm for the affine-less form")
    if residual is not None:
        residual = _t(residual)
        if list(residual.shape) != list(_t(x).shape):
            raise InvalidArgumentError(
                "fused_batch_norm_act: residual shape "
                f"{list(residual.shape)} must match x shape "
                f"{list(_t(x).shape)} (fused_bn_add_activation_op adds "
                "elementwise before the activation)")
    return _batch_norm_impl(x, running_mean, running_var, weight, bias,
                            training, momentum, epsilon, data_format,
                            use_global_stats, act, residual,
                            "fused_bn_act")


def layer_norm(x, normalized_shape, weight=None, bias=None, epsilon=1e-05,
               name=None):
    x = _t(x)
    if isinstance(normalized_shape, int):
        normalized_shape = [normalized_shape]
    n_axes = len(list(normalized_shape))
    axes = tuple(range(x.ndim - n_axes, x.ndim))

    def f(x, *wb):
        if wb:
            from ...core.flags import flag_active
            from ...ops.pallas import layer_norm as pln
            if flag_active("fused_layer_norm") and pln.supported(
                    x.shape, n_axes):
                return pln.fused_layer_norm(x, wb[0], wb[1], epsilon)
        xf = x.astype(jnp.float32)  # stats in f32 even under bf16 AMP
        mean = jnp.mean(xf, axis=axes, keepdims=True)
        var = jnp.var(xf, axis=axes, keepdims=True)
        y = (xf - mean) * jax.lax.rsqrt(var + epsilon)
        y = y.astype(x.dtype)
        if wb:
            w = wb[0].reshape((1,) * (x.ndim - n_axes) + wb[0].shape)
            b = wb[1].reshape((1,) * (x.ndim - n_axes) + wb[1].shape)
            y = y * w + b
        return y

    args = (x,) + ((_t(weight), _t(bias)) if weight is not None else ())
    return apply("layer_norm", f, args)


def instance_norm(x, running_mean=None, running_var=None, weight=None,
                  bias=None, use_input_stats=True, momentum=0.9,
                  epsilon=1e-05, data_format="NCHW", name=None):
    x = _t(x)
    axes = tuple(range(2, x.ndim))  # per-sample, per-channel spatial stats

    def f(x, *wb):
        mean = jnp.mean(x, axis=axes, keepdims=True)
        var = jnp.var(x, axis=axes, keepdims=True)
        y = (x - mean) * jax.lax.rsqrt(var + epsilon)
        if wb:
            shape = (1, -1) + (1,) * (x.ndim - 2)
            y = y * wb[0].reshape(shape) + wb[1].reshape(shape)
        return y
    args = (x,) + ((_t(weight), _t(bias)) if weight is not None else ())
    return apply("instance_norm", f, args)


def group_norm(x, num_groups, epsilon=1e-05, weight=None, bias=None,
               data_format="NCHW", name=None):
    x = _t(x)
    channel_last = data_format in ("NHWC", "NLC", "NDHWC")

    def f(x, *wb):
        if channel_last:
            xm = jnp.moveaxis(x, -1, 1)
        else:
            xm = x
        n, c = xm.shape[0], xm.shape[1]
        g = num_groups
        grouped = xm.reshape(n, g, c // g, *xm.shape[2:])
        axes = tuple(range(2, grouped.ndim))
        mean = jnp.mean(grouped, axis=axes, keepdims=True)
        var = jnp.var(grouped, axis=axes, keepdims=True)
        y = ((grouped - mean) * jax.lax.rsqrt(var + epsilon)).reshape(xm.shape)
        if wb:
            shape = (1, -1) + (1,) * (xm.ndim - 2)
            y = y * wb[0].reshape(shape) + wb[1].reshape(shape)
        if channel_last:
            y = jnp.moveaxis(y, 1, -1)
        return y
    args = (x,) + ((_t(weight), _t(bias)) if weight is not None else ())
    return apply("group_norm", f, args)


def local_response_norm(x, size, alpha=1e-4, beta=0.75, k=1.0,
                        data_format="NCHW", name=None):
    def f(x):
        sq = jnp.square(x)
        half = size // 2
        pads = [(0, 0)] * x.ndim
        ch_axis = 1 if data_format.startswith("NC") else x.ndim - 1
        pads[ch_axis] = (half, size - half - 1)
        window = [1] * x.ndim
        window[ch_axis] = size
        summed = jax.lax.reduce_window(sq, 0.0, jax.lax.add, tuple(window),
                                       (1,) * x.ndim, pads)
        return x / (k + alpha * summed) ** beta
    return apply("local_response_norm", f, (_t(x),))


def normalize(x, p=2, axis=1, epsilon=1e-12, name=None):
    def f(x):
        if p == 2:
            n = jnp.sqrt(jnp.sum(jnp.square(x), axis=axis, keepdims=True))
        else:
            n = jnp.sum(jnp.abs(x) ** p, axis=axis, keepdims=True) ** (1.0 / p)
        return x / jnp.maximum(n, epsilon)
    return apply("normalize", f, (_t(x),))
