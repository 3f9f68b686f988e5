"""Functional convolutions.

Analog of /root/reference/paddle/fluid/operators/conv_op.cc (cuDNN-backed)
and python/paddle/nn/functional/conv.py:114. On TPU, conv lowers to XLA's
``conv_general_dilated`` which maps directly onto the MXU; NHWC is the
preferred layout (NCHW accepted for API parity and transposed internally —
XLA folds the transposes into the conv).
"""

from __future__ import annotations

from typing import Optional, Sequence, Union

import jax
import jax.numpy as jnp

from ...autograd.engine import apply
from ...core.tensor import Tensor, to_tensor
from ...core.errors import InvalidArgumentError

__all__ = ["conv1d", "conv2d", "conv3d", "conv1d_transpose",
           "conv2d_transpose", "conv3d_transpose", "unfold", "fold"]


def _t(x):
    return x if isinstance(x, Tensor) else to_tensor(x)


def _tuple(v, n):
    if isinstance(v, (list, tuple)):
        if len(v) == n:
            return tuple(int(x) for x in v)
        if len(v) == 1:
            return tuple(int(v[0]) for _ in range(n))
        return tuple(int(x) for x in v)
    return tuple(int(v) for _ in range(n))


def _padding(padding, n):
    """Normalize paddle padding spec → lax padding list or string."""
    if isinstance(padding, str):
        return padding.upper()  # SAME / VALID
    if isinstance(padding, int):
        return [(padding, padding)] * n
    padding = list(padding)
    if len(padding) == n and all(isinstance(p, int) for p in padding):
        return [(p, p) for p in padding]
    if len(padding) == 2 * n:
        return [(padding[2 * i], padding[2 * i + 1]) for i in range(n)]
    if all(isinstance(p, (list, tuple)) for p in padding):
        return [tuple(p) for p in padding]
    raise InvalidArgumentError(f"Bad padding spec: {padding!r}")


def _conv(x, weight, bias, stride, padding, dilation, groups, data_format,
          ndim, op_name):
    stride = _tuple(stride, ndim)
    dilation = _tuple(dilation, ndim)
    pad = _padding(padding, ndim)
    channel_last = data_format in ("NHWC", "NLC", "NDHWC")
    if ndim == 1:
        # lax uses single-char dims; W stands in for the L spatial dim
        dn_str = ("NWC", "OIW", "NWC") if channel_last else ("NCW", "OIW", "NCW")
    elif ndim == 2:
        dn_str = ("NHWC", "OIHW", "NHWC") if channel_last else \
            ("NCHW", "OIHW", "NCHW")
    else:
        dn_str = ("NDHWC", "OIDHW", "NDHWC") if channel_last else \
            ("NCDHW", "OIDHW", "NCDHW")

    # Channels-first-API convs run internally channels-last with
    # spatial-major weights when the region is active (see _layout.py;
    # the weight transpose is negligible next to the conv itself).
    from ._layout import (CONV_CL_SPEC, CONV_WEIGHT_PERM,
                          channels_last_region_for)
    nhwc_internal, _to_cl, _to_cf = channels_last_region_for(
        x, ndim, channel_last)
    _w_perm = CONV_WEIGHT_PERM[ndim]
    _cl_spec = CONV_CL_SPEC[ndim]

    def f(x, w, *maybe_b):
        if nhwc_internal:
            xi = _to_cl(x)
            wi = jnp.transpose(w, _w_perm)
            dn = jax.lax.conv_dimension_numbers(
                xi.shape, wi.shape, _cl_spec)
            out = jax.lax.conv_general_dilated(
                xi, wi, window_strides=stride, padding=pad,
                rhs_dilation=dilation, dimension_numbers=dn,
                feature_group_count=groups)
            if maybe_b:
                out = out + maybe_b[0].reshape(
                    (1,) * (out.ndim - 1) + (-1,))
            return _to_cf(out)
        dn = jax.lax.conv_dimension_numbers(x.shape, w.shape, dn_str)
        out = jax.lax.conv_general_dilated(
            x, w, window_strides=stride, padding=pad,
            rhs_dilation=dilation, dimension_numbers=dn,
            feature_group_count=groups)
        if maybe_b:
            b = maybe_b[0]
            if channel_last:
                out = out + b.reshape((1,) * (out.ndim - 1) + (-1,))
            else:
                out = out + b.reshape((1, -1) + (1,) * (out.ndim - 2))
        return out

    args = (_t(x), _t(weight)) + ((_t(bias),) if bias is not None else ())
    return apply(op_name, f, args)


def conv1d(x, weight, bias=None, stride=1, padding=0, dilation=1, groups=1,
           data_format="NCL", name=None):
    return _conv(x, weight, bias, stride, padding, dilation, groups,
                 data_format, 1, "conv1d")


def conv2d(x, weight, bias=None, stride=1, padding=0, dilation=1, groups=1,
           data_format="NCHW", name=None):
    return _conv(x, weight, bias, stride, padding, dilation, groups,
                 data_format, 2, "conv2d")


def conv3d(x, weight, bias=None, stride=1, padding=0, dilation=1, groups=1,
           data_format="NCDHW", name=None):
    return _conv(x, weight, bias, stride, padding, dilation, groups,
                 data_format, 3, "conv3d")


def _conv_transpose(x, weight, bias, stride, padding, output_padding,
                    dilation, groups, data_format, ndim, op_name,
                    output_size=None):
    stride = _tuple(stride, ndim)
    dilation = _tuple(dilation, ndim)
    out_padding = _tuple(output_padding, ndim)
    channel_last = data_format in ("NHWC", "NLC", "NDHWC")
    pad = _padding(padding, ndim)
    if isinstance(pad, str):
        if pad == "SAME":
            pad = [(0, 0)] * ndim  # resolved below via lax 'SAME'
            pad_str = "SAME"
        else:
            pad_str = "VALID"
    else:
        pad_str = None

    if ndim == 1:
        dn_str = ("NWC", "IOW", "NWC") if channel_last else ("NCW", "IOW", "NCW")
    elif ndim == 2:
        dn_str = ("NHWC", "IOHW", "NHWC") if channel_last else \
            ("NCHW", "IOHW", "NCHW")
    else:
        dn_str = ("NDHWC", "IODHW", "NDHWC") if channel_last else \
            ("NCDHW", "IODHW", "NCDHW")

    # transposed convs join the channels-last region too (_layout.py):
    # the lhs-dilated gradient-of-conv formulation below is still a
    # conv_general_dilated, with the same layout preference as the
    # forward convs
    from ._layout import (CONV_CL_SPEC, CONV_WEIGHT_PERM,
                          channels_last_region_for)
    nhwc_internal, _to_cl, _to_cf = channels_last_region_for(
        x, ndim, channel_last)
    _w_perm = CONV_WEIGHT_PERM[ndim]

    def f(x, w, *maybe_b):
        # Gradient-of-conv formulation: lhs-dilate input by stride.
        k = [(w.shape[2 + i] - 1) * dilation[i] + 1 for i in range(ndim)]
        if pad_str == "SAME":
            pads = []
            for i in range(ndim):
                total = k[i] - 1
                lo = total // 2
                pads.append((k[i] - 1 - lo, k[i] - 1 - (total - lo) +
                             out_padding[i]))
        elif pad_str == "VALID":
            pads = [(k[i] - 1, k[i] - 1 + out_padding[i]) for i in range(ndim)]
        else:
            pads = [(k[i] - 1 - pad[i][0], k[i] - 1 - pad[i][1] +
                     out_padding[i]) for i in range(ndim)]
        # weight layout paddle: [in_c, out_c/groups, *k]; flip spatial dims
        w_flip = jnp.flip(w, axis=tuple(range(2, 2 + ndim)))
        if groups > 1:
            ic, ocg = w.shape[0], w.shape[1]
            w_g = w_flip.reshape(groups, ic // groups, ocg, *w.shape[2:])
            w_g = jnp.swapaxes(w_g, 1, 2)  # [g, ocg, icg, *k]
            w_t = w_g.reshape(groups * ocg, ic // groups, *w.shape[2:])
        else:
            w_t = jnp.swapaxes(w_flip, 0, 1)
        if nhwc_internal:
            xi = _to_cl(x)
            wi = jnp.transpose(w_t, _w_perm)  # OI+k -> k+IO (HWIO-form)
            dn2 = jax.lax.conv_dimension_numbers(
                xi.shape, wi.shape, CONV_CL_SPEC[ndim])
            out = jax.lax.conv_general_dilated(
                xi, wi, window_strides=(1,) * ndim, padding=pads,
                lhs_dilation=stride, rhs_dilation=dilation,
                dimension_numbers=dn2, feature_group_count=groups)
            if maybe_b:
                out = out + maybe_b[0].reshape(
                    (1,) * (out.ndim - 1) + (-1,))
            return _to_cf(out)
        dn2 = jax.lax.conv_dimension_numbers(
            x.shape, w_t.shape,
            tuple(s.replace("IO", "OI") for s in dn_str))
        out = jax.lax.conv_general_dilated(
            x, w_t, window_strides=(1,) * ndim, padding=pads,
            lhs_dilation=stride, rhs_dilation=dilation,
            dimension_numbers=dn2, feature_group_count=groups)
        if maybe_b:
            b = maybe_b[0]
            if channel_last:
                out = out + b.reshape((1,) * (out.ndim - 1) + (-1,))
            else:
                out = out + b.reshape((1, -1) + (1,) * (out.ndim - 2))
        return out

    args = (_t(x), _t(weight)) + ((_t(bias),) if bias is not None else ())
    out = apply(op_name, f, args)
    if output_size is not None:
        pass  # output_padding derived sizes already handled by caller
    return out


def conv1d_transpose(x, weight, bias=None, stride=1, padding=0,
                     output_padding=0, groups=1, dilation=1,
                     output_size=None, data_format="NCL", name=None):
    return _conv_transpose(x, weight, bias, stride, padding, output_padding,
                           dilation, groups, data_format, 1,
                           "conv1d_transpose", output_size)


def conv2d_transpose(x, weight, bias=None, stride=1, padding=0,
                     output_padding=0, groups=1, dilation=1,
                     output_size=None, data_format="NCHW", name=None):
    return _conv_transpose(x, weight, bias, stride, padding, output_padding,
                           dilation, groups, data_format, 2,
                           "conv2d_transpose", output_size)


def conv3d_transpose(x, weight, bias=None, stride=1, padding=0,
                     output_padding=0, groups=1, dilation=1,
                     output_size=None, data_format="NCDHW", name=None):
    return _conv_transpose(x, weight, bias, stride, padding, output_padding,
                           dilation, groups, data_format, 3,
                           "conv3d_transpose", output_size)


def unfold(x, kernel_sizes, strides=1, paddings=0, dilations=1, name=None):
    """im2col (reference operators/math/im2col.cc). Output layout matches
    paddle: [N, C*prod(k), L]."""
    k = _tuple(kernel_sizes, 2)
    s = _tuple(strides, 2)
    d = _tuple(dilations, 2)
    p = _padding(paddings, 2)

    def f(x):
        n, c, h, w = x.shape
        patches = jax.lax.conv_general_dilated_patches(
            x, filter_shape=k, window_strides=s, padding=p,
            rhs_dilation=d, dimension_numbers=("NCHW", "OIHW", "NCHW"))
        # patches: [N, C*kh*kw, oh, ow]
        return patches.reshape(n, patches.shape[1], -1)
    return apply("unfold", f, (_t(x),))


def fold(x, output_sizes, kernel_sizes, strides=1, paddings=0, dilations=1,
         name=None):
    """col2im — the adjoint of unfold; computed as its vjp for exactness."""
    k = _tuple(kernel_sizes, 2)
    s = _tuple(strides, 2)
    d = _tuple(dilations, 2)
    p = _padding(paddings, 2)
    oh, ow = _tuple(output_sizes, 2)

    def f(cols):
        n = cols.shape[0]
        c = cols.shape[1] // (k[0] * k[1])

        def unfold_fn(img):
            patches = jax.lax.conv_general_dilated_patches(
                img, filter_shape=k, window_strides=s, padding=p,
                rhs_dilation=d, dimension_numbers=("NCHW", "OIHW", "NCHW"))
            return patches.reshape(n, patches.shape[1], -1)
        zero = jnp.zeros((n, c, oh, ow), cols.dtype)
        _, vjp = jax.vjp(unfold_fn, zero)
        return vjp(cols)[0]
    return apply("fold", f, (_t(x),))
