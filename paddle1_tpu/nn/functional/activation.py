"""Functional activations.

Analog of /root/reference/paddle/fluid/operators/activation_op.cc kernels and
python/paddle/nn/functional/activation.py. All lower to single fused XLA
elementwise HLO — no hand-written backward needed (jax.vjp supplies it).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ...autograd.engine import apply
from ...core.tensor import Tensor, to_tensor

__all__ = [
    "relu", "relu6", "relu_", "elu", "elu_", "selu", "celu", "gelu",
    "sigmoid",
    "hardsigmoid", "hardswish", "hardtanh", "hardshrink", "softshrink",
    "tanhshrink", "leaky_relu", "prelu", "rrelu", "log_sigmoid", "maxout",
    "silu", "swish", "mish", "softplus", "softsign", "tanh", "tanh_",
    "thresholded_relu", "log_softmax", "softmax", "softmax_", "glu",
    "swiglu", "gumbel_softmax",
]


def _t(x):
    return x if isinstance(x, Tensor) else to_tensor(x)


def _un(opname, fn):
    # the paddle-API `name=None` kwarg must not shadow the op name
    def op(x, name=None):
        return apply(opname, fn, (_t(x),))
    op.__name__ = opname
    return op


relu = _un("relu", jax.nn.relu)
relu6 = _un("relu6", jax.nn.relu6)
sigmoid = _un("sigmoid", jax.nn.sigmoid)
log_sigmoid = _un("log_sigmoid", jax.nn.log_sigmoid)
silu = _un("silu", jax.nn.silu)
mish = _un("mish", lambda x: x * jnp.tanh(jax.nn.softplus(x)))
softsign = _un("softsign", jax.nn.soft_sign)
tanh = _un("tanh", jnp.tanh)
tanhshrink = _un("tanhshrink", lambda x: x - jnp.tanh(x))


def _inplace(x, out):
    """In-place contract shared by the *_ variants: mutate a Tensor,
    gracefully return the out-of-place result for raw arrays (matching
    ops.manip_ops.flatten_ / math_ops.increment)."""
    from ...core.tensor import Tensor
    if isinstance(x, Tensor):
        x._replace_impl(out)
        return x
    return out


def relu_(x, name=None):
    return _inplace(x, relu(x))


def tanh_(x, name=None):
    return _inplace(x, tanh(x))


def elu(x, alpha=1.0, name=None):
    return apply("elu", lambda x: jax.nn.elu(x, alpha=alpha), (_t(x),))


def elu_(x, alpha=1.0, name=None):
    return _inplace(x, elu(x, alpha=alpha))


def selu(x, scale=1.0507009873554805, alpha=1.6732632423543772, name=None):
    return apply("selu",
                 lambda x: scale * jnp.where(x > 0, x,
                                             alpha * jnp.expm1(x)),
                 (_t(x),))


def celu(x, alpha=1.0, name=None):
    return apply("celu", lambda x: jax.nn.celu(x, alpha=alpha), (_t(x),))


def gelu(x, approximate=False, name=None):
    return apply("gelu",
                 lambda x: jax.nn.gelu(x, approximate=bool(approximate)),
                 (_t(x),))


def hardsigmoid(x, slope=0.1666667, offset=0.5, name=None):
    return apply("hardsigmoid",
                 lambda x: jnp.clip(slope * x + offset, 0.0, 1.0), (_t(x),))


def hardswish(x, name=None):
    return apply("hardswish",
                 lambda x: x * jnp.clip(x + 3.0, 0.0, 6.0) / 6.0, (_t(x),))


def hardtanh(x, min=-1.0, max=1.0, name=None):
    return apply("hardtanh", lambda x: jnp.clip(x, min, max), (_t(x),))


def hardshrink(x, threshold=0.5, name=None):
    return apply("hardshrink",
                 lambda x: jnp.where(jnp.abs(x) > threshold, x, 0.0),
                 (_t(x),))


def softshrink(x, threshold=0.5, name=None):
    return apply("softshrink",
                 lambda x: jnp.where(x > threshold, x - threshold,
                                     jnp.where(x < -threshold,
                                               x + threshold, 0.0)),
                 (_t(x),))


def leaky_relu(x, negative_slope=0.01, name=None):
    return apply("leaky_relu",
                 lambda x: jax.nn.leaky_relu(x, negative_slope), (_t(x),))


def prelu(x, weight, data_format="NCHW", name=None):
    def f(x, w):
        if w.size == 1:
            wb = w.reshape(())
        else:
            shape = [1] * x.ndim
            ch_axis = 1 if data_format == "NCHW" else x.ndim - 1
            shape[ch_axis] = w.size
            wb = w.reshape(shape)
        return jnp.where(x >= 0, x, wb * x)
    return apply("prelu", f, (_t(x), _t(weight)))


def rrelu(x, lower=1.0 / 8.0, upper=1.0 / 3.0, training=True, name=None):
    from ...core.generator import next_key
    x = _t(x)
    if training:
        import jax.random as jr
        slope = jr.uniform(next_key(), tuple(x.shape), x.data.dtype,
                           minval=lower, maxval=upper)
        return apply("rrelu", lambda x, s: jnp.where(x >= 0, x, s * x),
                     (x, to_tensor(slope)))
    mid = (lower + upper) / 2.0
    return apply("rrelu", lambda x: jnp.where(x >= 0, x, mid * x), (x,))


def maxout(x, groups, axis=1, name=None):
    def f(x):
        ax = axis % x.ndim
        c = x.shape[ax]
        new_shape = (x.shape[:ax] + (c // groups, groups) + x.shape[ax + 1:])
        return jnp.max(x.reshape(new_shape), axis=ax + 1)
    return apply("maxout", f, (_t(x),))


def swish(x, name=None):
    return silu(x)


def softplus(x, beta=1.0, threshold=20.0, name=None):
    return apply("softplus",
                 lambda x: jnp.where(beta * x > threshold, x,
                                     jax.nn.softplus(beta * x) / beta),
                 (_t(x),))


def thresholded_relu(x, threshold=1.0, value=0.0, name=None):
    return apply("thresholded_relu",
                 lambda x: jnp.where(x > threshold, x, value), (_t(x),))


def softmax(x, axis=-1, dtype=None, name=None):
    from ...core import dtype as dtypes

    def f(x):
        if dtype is not None:
            x = x.astype(dtypes.convert_dtype(dtype))
        from ...core.flags import flag_active
        from ...ops.pallas import softmax as psm
        if flag_active("fused_softmax") and psm.supported(x.shape, axis):
            return psm.fused_softmax(x)
        return jax.nn.softmax(x, axis=axis)
    return apply("softmax", f, (_t(x),))


def softmax_(x, axis=-1, dtype=None, name=None):
    return _inplace(x, softmax(x, axis, dtype))


def log_softmax(x, axis=-1, dtype=None, name=None):
    from ...core import dtype as dtypes

    def f(x):
        if dtype is not None:
            x = x.astype(dtypes.convert_dtype(dtype))
        return jax.nn.log_softmax(x, axis=axis)
    return apply("log_softmax", f, (_t(x),))


def glu(x, axis=-1, name=None):
    return apply("glu", lambda x: jax.nn.glu(x, axis=axis), (_t(x),))


def swiglu(gate, up, name=None):
    """``silu(gate) * up``: the gate of a SwiGLU feed-forward (Shazeer
    2020, arXiv:2002.05202) on its two projections."""
    return apply("swiglu", lambda g, u: jax.nn.silu(g) * u,
                 (_t(gate), _t(up)))


def gumbel_softmax(x, temperature=1.0, hard=False, axis=-1, name=None):
    from ...core.generator import next_key
    import jax.random as jr
    x = _t(x)
    g = jr.gumbel(next_key(), tuple(x.shape), x.data.dtype)

    def f(x, g):
        y = jax.nn.softmax((x + g) / temperature, axis=axis)
        if hard:
            idx = jnp.argmax(y, axis=axis, keepdims=True)
            y_hard = jnp.zeros_like(y)
            y_hard = jnp.put_along_axis(y_hard, idx, 1.0, axis=axis,
                                        inplace=False)
            # straight-through estimator
            y = y_hard + y - jax.lax.stop_gradient(y)
        return y
    return apply("gumbel_softmax", f, (x, to_tensor(g)))
