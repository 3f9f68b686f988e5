"""Functional pooling.

Analog of /root/reference/paddle/fluid/operators/pool_op.cc (cuDNN pooling)
and python/paddle/nn/functional/pooling.py. Lowers to
``lax.reduce_window`` which XLA fuses and vectorizes on the VPU.
"""

from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from ...autograd.engine import apply
from ...core.tensor import Tensor, to_tensor
from .conv import _padding, _tuple

__all__ = ["avg_pool1d", "avg_pool2d", "avg_pool3d", "max_pool1d",
           "max_pool2d", "max_pool3d", "adaptive_avg_pool1d",
           "adaptive_avg_pool2d", "adaptive_avg_pool3d",
           "adaptive_max_pool1d", "adaptive_max_pool2d",
           "adaptive_max_pool3d", "lp_pool2d", "max_unpool2d"]


def _t(x):
    return x if isinstance(x, Tensor) else to_tensor(x)


def _pool(x, ksize, stride, padding, ndim, mode, channel_last, ceil_mode,
          exclusive=True, op_name="pool"):
    k = _tuple(ksize, ndim)
    s = _tuple(stride if stride is not None else ksize, ndim)
    pad_cfg = _padding(padding, ndim)

    def f(x):
        # Channels-first-API pools join the channels-last region
        # (_layout.py), so a conv/pool stack stays channels-last
        # between its boundary transposes
        from ._layout import channels_last_region_for
        nhwc_internal, _to_cl, _to_cf = channels_last_region_for(
            x, ndim, channel_last)
        x = _to_cl(x)
        cl = channel_last or nhwc_internal
        if cl:
            window = (1,) + k + (1,)
            strides = (1,) + s + (1,)
            spatial = list(range(1, 1 + ndim))
        else:
            window = (1, 1) + k
            strides = (1, 1) + s
            spatial = list(range(2, 2 + ndim))
        if isinstance(pad_cfg, str):
            pads = pad_cfg
        else:
            full = [(0, 0)] * x.ndim
            for i, ax in enumerate(spatial):
                lo, hi = pad_cfg[i]
                if ceil_mode:
                    size = x.shape[ax]
                    out = -(-(size + lo + hi - k[i]) // s[i]) + 1
                    needed = (out - 1) * s[i] + k[i] - size - lo
                    hi = max(hi, needed)
                full[ax] = (lo, hi)
            pads = full
        if mode == "max":
            init = -jnp.inf if jnp.issubdtype(x.dtype, jnp.floating) else \
                jnp.iinfo(x.dtype).min
            out = jax.lax.reduce_window(x, init, jax.lax.max, window,
                                        strides, pads)
        else:
            summed = jax.lax.reduce_window(x, 0.0, jax.lax.add,
                                           window, strides, pads)
            if exclusive and pads != "VALID":
                ones = jnp.ones_like(x)
                counts = jax.lax.reduce_window(ones, 0.0, jax.lax.add,
                                               window, strides, pads)
                out = summed / counts
            else:
                out = summed / float(np.prod(k))
        return _to_cf(out)
    return apply(op_name, f, (_t(x),))


def max_pool1d(x, kernel_size, stride=None, padding=0, return_mask=False,
               ceil_mode=False, data_format="NCL", name=None):
    out = _pool(x, kernel_size, stride, padding, 1, "max",
                data_format == "NLC", ceil_mode, op_name="max_pool1d")
    if return_mask:
        return out, _pool_mask(x, out, kernel_size, stride, padding, 1,
                               data_format == "NLC")
    return out


def max_pool2d(x, kernel_size, stride=None, padding=0, return_mask=False,
               ceil_mode=False, data_format="NCHW", name=None):
    out = _pool(x, kernel_size, stride, padding, 2, "max",
                data_format == "NHWC", ceil_mode, op_name="max_pool2d")
    if return_mask:
        return out, _pool_mask(x, out, kernel_size, stride, padding, 2,
                               data_format == "NHWC")
    return out


def max_pool3d(x, kernel_size, stride=None, padding=0, return_mask=False,
               ceil_mode=False, data_format="NCDHW", name=None):
    out = _pool(x, kernel_size, stride, padding, 3, "max",
                data_format == "NDHWC", ceil_mode, op_name="max_pool3d")
    if return_mask:
        return out, _pool_mask(x, out, kernel_size, stride, padding, 3,
                               data_format == "NDHWC")
    return out


def avg_pool1d(x, kernel_size, stride=None, padding=0, exclusive=True,
               ceil_mode=False, data_format="NCL", name=None):
    return _pool(x, kernel_size, stride, padding, 1, "avg",
                 data_format == "NLC", ceil_mode, exclusive, "avg_pool1d")


def avg_pool2d(x, kernel_size, stride=None, padding=0, ceil_mode=False,
               exclusive=True, divisor_override=None, data_format="NCHW",
               name=None):
    return _pool(x, kernel_size, stride, padding, 2, "avg",
                 data_format == "NHWC", ceil_mode, exclusive, "avg_pool2d")


def avg_pool3d(x, kernel_size, stride=None, padding=0, ceil_mode=False,
               exclusive=True, divisor_override=None, data_format="NCDHW",
               name=None):
    return _pool(x, kernel_size, stride, padding, 3, "avg",
                 data_format == "NDHWC", ceil_mode, exclusive, "avg_pool3d")


def _pool_mask(x, out, ksize, stride, padding, ndim, channel_last):
    """Argmax indices for return_mask=True (flat spatial index, paddle
    convention)."""
    x = _t(x)
    k = _tuple(ksize, ndim)
    s = _tuple(stride if stride is not None else ksize, ndim)

    def f(x):
        spatial = x.shape[1:-1] if channel_last else x.shape[2:]
        flat_idx = jnp.arange(int(np.prod(spatial))).reshape(spatial)
        if channel_last:
            idx = jnp.broadcast_to(flat_idx[None, ..., None], x.shape)
            window = (1,) + k + (1,)
            strides = (1,) + s + (1,)
        else:
            idx = jnp.broadcast_to(flat_idx[None, None], x.shape)
            window = (1, 1) + k
            strides = (1, 1) + s

        def reducer(a, b):
            av, ai = a
            bv, bi = b
            take_b = bv > av
            return (jnp.where(take_b, bv, av), jnp.where(take_b, bi, ai))
        init = (jnp.array(-jnp.inf, x.dtype), jnp.array(0, jnp.int32))
        _, indices = jax.lax.reduce_window(
            (x, idx.astype(jnp.int32)), init, reducer, window, strides,
            "VALID")
        return indices.astype(jnp.int64)
    return apply("pool_mask", f, (x,))


def _adaptive(x, output_size, ndim, mode, channel_last, op_name,
              return_mask=False):
    x = _t(x)
    spatial = x.shape[1:-1] if channel_last else x.shape[2:]
    out_size = _tuple(output_size, ndim)
    out_size = tuple(o if o is not None else sp
                     for o, sp in zip(out_size, spatial))

    # Adaptive pooling with possibly-uneven windows: segment means/maxes per
    # output cell. When sizes divide evenly this is a plain strided pool.
    even = all(sp % o == 0 for sp, o in zip(spatial, out_size))
    if even:
        k = tuple(sp // o for sp, o in zip(spatial, out_size))
        return _pool(x, k, k, 0, ndim, mode, channel_last, False,
                     True, op_name)

    def f(x):
        y = x
        axis0 = 1 if channel_last else 2
        for i in range(ndim):
            ax = axis0 + i
            in_sz, out_sz = y.shape[ax], out_size[i]
            starts = (np.arange(out_sz) * in_sz) // out_sz
            ends = ((np.arange(out_sz) + 1) * in_sz + out_sz - 1) // out_sz
            segs = []
            for st, en in zip(starts, ends):
                sl = jax.lax.slice_in_dim(y, int(st), int(en), axis=ax)
                red = jnp.max(sl, axis=ax, keepdims=True) if mode == "max" \
                    else jnp.mean(sl, axis=ax, keepdims=True)
                segs.append(red)
            y = jnp.concatenate(segs, axis=ax)
        return y
    return apply(op_name, f, (x,))


def adaptive_avg_pool1d(x, output_size, name=None):
    return _adaptive(x, output_size, 1, "avg", False, "adaptive_avg_pool1d")


def adaptive_avg_pool2d(x, output_size, data_format="NCHW", name=None):
    return _adaptive(x, output_size, 2, "avg", data_format == "NHWC",
                     "adaptive_avg_pool2d")


def adaptive_avg_pool3d(x, output_size, data_format="NCDHW", name=None):
    return _adaptive(x, output_size, 3, "avg", data_format == "NDHWC",
                     "adaptive_avg_pool3d")


def adaptive_max_pool1d(x, output_size, return_mask=False, name=None):
    out = _adaptive(x, output_size, 1, "max", False, "adaptive_max_pool1d")
    return (out, None) if return_mask else out


def adaptive_max_pool2d(x, output_size, return_mask=False, name=None):
    out = _adaptive(x, output_size, 2, "max", False, "adaptive_max_pool2d")
    return (out, None) if return_mask else out


def adaptive_max_pool3d(x, output_size, return_mask=False, name=None):
    out = _adaptive(x, output_size, 3, "max", False, "adaptive_max_pool3d")
    return (out, None) if return_mask else out


def lp_pool2d(x, norm_type, kernel_size, stride=None, padding=0,
              ceil_mode=False, data_format="NCHW", name=None):
    p = float(norm_type)
    xp = apply("lp_pow", lambda x: jnp.abs(x) ** p, (_t(x),))
    pooled = _pool(xp, kernel_size, stride, padding, 2, "avg",
                   data_format == "NHWC", ceil_mode, False, "lp_pool2d")
    k = _tuple(kernel_size, 2)
    return apply("lp_root",
                 lambda y: (y * float(np.prod(k))) ** (1.0 / p),
                 (pooled,))


def max_unpool2d(x, indices, kernel_size, stride=None, padding=0,
                 data_format="NCHW", output_size=None, name=None):
    k = _tuple(kernel_size, 2)
    s = _tuple(stride if stride is not None else kernel_size, 2)

    def f(x, idx):
        n, c, h, w = x.shape
        if output_size is not None:
            oh, ow = _tuple(output_size, 2)[-2:]
        else:
            oh = (h - 1) * s[0] + k[0]
            ow = (w - 1) * s[1] + k[1]
        out = jnp.zeros((n, c, oh * ow), x.dtype)
        flat_idx = idx.reshape(n, c, -1)
        vals = x.reshape(n, c, -1)
        out = jax.vmap(jax.vmap(lambda o, i, v: o.at[i].set(v)))(
            out, flat_idx, vals)
        return out.reshape(n, c, oh, ow)
    return apply("max_unpool2d", f, (_t(x), _t(indices)))
