"""The gated short convolution of the LFM2 family (LiquidAI, ``model_type:
lfm2`` / ``lfm2_moe``): a depthwise causal convolution of a few taps
between two elementwise gates. No reference analog (the reference's
``conv1d`` lowers to cuDNN; ``nn/functional/conv.py::conv1d`` here to
``conv_general_dilated`` with ``feature_group_count``, which computes the
same sums as a convolution and leaves the gates to two more passes).

``bcx``: ``[batch, seq, 3 * channels]``, the gates ``b`` and ``c`` and the
input ``x`` side by side along the features, as the projection before the
op makes them; ``weight``: ``[channels, taps]``. With ``s_t = b_t * x_t``
and ``s_t = 0`` for ``t < 0``::

    y_t[ch]   = sum_{k < taps} weight[ch, k] * s_{t - (taps - 1) + k}[ch]
    out_t[ch] = c_t[ch] * y_t[ch]

(a cross-correlation: the last tap weighs the position itself). The op is
bound by memory: 16 KB a token at 2048 channels in bfloat16. Products and
sums are float32 whatever the operands arrive in (bfloat16 under AMP); the
result has ``bcx``'s dtype.

The backward pass is written out (``jax.custom_vjp``): it keeps ``bcx``
and the taps and makes ``s`` and ``y`` again, two multiplies, where
autodiff would store both (two more arrays of the output's size a call).
With ``g`` the output's gradient and ``dy = g * c``::

    dc_t = g_t * y_t
    ds_t = sum_k weight[:, k] * dy_{t + (taps - 1) - k}    (0 past the end)
    db_t = ds_t * x_t,   dx_t = ds_t * b_t
    dweight[ch, k] = sum_{batch, t} dy_t[ch] * s_{t - (taps - 1) + k}[ch]

the last a float32 reduction over every position of a channel.

**Two forms.** On a TPU, in a step that is one device's, at shapes of
whole tiles: the two Pallas kernels of ``ops/pallas/short_conv.py``, each
one pass over its operands. Anywhere else ``taps`` shifted multiply-adds
in ``jax.numpy``, which XLA fuses into a few passes (on the v5e it writes
``s`` and ``y`` to HBM in float32 between them: PERF.md section 6, PR 38).

**Counted where it is traced**: ``short_conv_bytes_total{pass}`` in the
process registry (beside ``flash_tiles_total{kind}``), the bytes a call's
forward (``pass="forward"``) or backward has to read and write by the
closed form of its shapes, :func:`traffic_bytes`, whichever form runs
(a whole multiple of one call's bytes: jax traces a call's forward more
than once under ``jax.checkpoint``). The benchmark's
roofline share of the op divides by a closed form of its own
(``benchmarks/model_flops/lfm2_24b_a2b.py::short_conv_bytes``) and a test
holds the two equal.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ...autograd.engine import apply
from ...core.flags import in_auto_partitioned_region
from ...core.tensor import Tensor, to_tensor
from ...ops.pallas import short_conv as kernels

__all__ = ["gated_short_conv"]


def traffic_bytes(shape, itemsize, taps, weight_itemsize=None) -> dict:
    """{"forward", "backward"} for ``bcx`` of ``shape``: the bytes one
    call has to move. Forward: ``b``, ``c``, ``x`` read and ``out``
    written, the taps read. Backward: ``b``, ``c``, ``x`` and the output's
    gradient read, three gradients written, the taps read and their
    gradient written."""
    channels = shape[-1] // 3
    whole = itemsize * channels
    for n in shape[:-1]:
        whole *= n
    taps_bytes = channels * taps * (weight_itemsize or itemsize)
    return {"forward": 4 * whole + taps_bytes,
            "backward": 7 * whole + 2 * taps_bytes}


def _count(which, bcx, weight):
    from ...obs.registry import process_group
    process_group("pass").child(which).counter(
        "short_conv_bytes_total").inc(traffic_bytes(
            bcx.shape, bcx.dtype.itemsize, weight.shape[1],
            weight.dtype.itemsize)[which])


def _use_kernels(bcx, weight) -> bool:
    return (jax.default_backend() == "tpu"
            and not in_auto_partitioned_region()
            and kernels.supported(bcx.shape, weight.shape[1]))


def _earlier(s, by):
    """``s`` [batch, seq, channels] -> its value ``by`` positions
    earlier, zeros before the start."""
    if by == 0:
        return s
    return jnp.pad(s, ((0, 0), (by, 0), (0, 0)))[:, :s.shape[1]]


def _later(s, by):
    """The value ``by`` positions later, zeros past the end."""
    if by == 0:
        return s
    return jnp.pad(s, ((0, 0), (0, by), (0, 0)))[:, by:]


def _taps_sum(s, weight, shifted):
    """``sum_k weight[:, k] * shifted(s, taps - 1 - k)``, float32."""
    taps = weight.shape[1]
    w = weight.astype(jnp.float32)
    total = w[:, taps - 1] * s
    for k in range(taps - 1):
        total = total + w[:, k] * shifted(s, taps - 1 - k)
    return total


def _parts(bcx):
    channels = bcx.shape[-1] // 3
    return tuple(bcx[..., k * channels:(k + 1) * channels].astype(jnp.float32)
                 for k in range(3))


def _forward(bcx, weight):
    _count("forward", bcx, weight)
    if _use_kernels(bcx, weight):
        return kernels.forward(bcx, weight)
    b, c, x = _parts(bcx)
    return (c * _taps_sum(b * x, weight, _earlier)).astype(bcx.dtype)


@jax.custom_vjp
def _gated_short_conv(bcx, weight):
    return _forward(bcx, weight)


def _fwd(bcx, weight):
    return _forward(bcx, weight), (bcx, weight)


def _bwd(res, g):
    bcx, weight = res
    _count("backward", bcx, weight)
    if _use_kernels(bcx, weight):
        d, dw = kernels.backward(bcx, weight, g)
        return d, dw.astype(weight.dtype)
    taps = weight.shape[1]
    b, c, x = _parts(bcx)
    g = g.astype(jnp.float32)
    s = b * x
    dy = g * c
    ds = _taps_sum(dy, weight, _later)
    dw = jnp.stack([jnp.sum(dy * _earlier(s, taps - 1 - k), axis=(0, 1))
                    for k in range(taps)], axis=1)
    d = jnp.concatenate([ds * x, g * _taps_sum(s, weight, _earlier), ds * b],
                        axis=-1)
    return d.astype(bcx.dtype), dw.astype(weight.dtype)


_gated_short_conv.defvjp(_fwd, _bwd)


def gated_short_conv(bcx, weight, name=None):
    """See the module's docstring. ``bcx``: [batch, seq, 3 * channels];
    ``weight``: [channels, taps] -> [batch, seq, channels]."""
    args = tuple(a if isinstance(a, Tensor) else to_tensor(a)
                 for a in (bcx, weight))
    return apply("gated_short_conv", _gated_short_conv, args)
