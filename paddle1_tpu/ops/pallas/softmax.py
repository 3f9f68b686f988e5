"""Fused softmax Pallas kernel.

TPU-native analog of the reference's fused softmax CUDA kernels
(/root/reference/paddle/fluid/operators/softmax_cudnn_op.cu and the
fused-attention softmax inside operators/fused/): one VMEM pass per row
block computes max, exp, sum, and the normalized output — no HBM
round-trips for the intermediates (BASELINE.md config 3 names this
kernel family explicitly).

Forward = Pallas kernel; backward = the closed-form softmax vjp
(dx = p * (dy - sum(dy * p))), which XLA fuses tightly. Interpret mode
runs the same kernel path on CPU for tests.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import _common
from ._common import block_rows as _block_rows

__all__ = ["fused_softmax", "supported"]


def supported(shape, axis: int) -> bool:
    """Last-axis softmax, lane-aligned non-empty rows tiling into VMEM."""
    nd = len(shape)
    if nd < 2 or axis not in (-1, nd - 1):
        return False
    h = shape[-1]
    rows = 1
    for s in shape[:-1]:
        rows *= s
    if h <= 0 or h % 128:
        return False
    return _block_rows(rows, h) > 0


def _softmax_kernel(x_ref, y_ref):
    x = x_ref[:].astype(jnp.float32)                  # [BR, H]
    m = jnp.max(x, axis=1, keepdims=True)
    e = jnp.exp(x - m)
    y_ref[:] = (e / jnp.sum(e, axis=1, keepdims=True)).astype(y_ref.dtype)


def _softmax_fwd(x2):
    rows, h = x2.shape
    br = _block_rows(rows, h)
    return pl.pallas_call(
        _softmax_kernel,
        grid=(rows // br,),
        in_specs=[pl.BlockSpec((br, h), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((br, h), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, h), x2.dtype),
        name="p1t_softmax_fwd",
        interpret=_common.interpret(),
    )(x2)


@jax.custom_vjp
def _sm(x2):
    return _softmax_fwd(x2)


def _sm_vjp_fwd(x2):
    p = _softmax_fwd(x2)
    return p, p


def _sm_vjp_bwd(p, dy):
    pf = p.astype(jnp.float32)
    dyf = dy.astype(jnp.float32)
    dx = pf * (dyf - jnp.sum(dyf * pf, axis=1, keepdims=True))
    return (dx.astype(p.dtype),)


_sm.defvjp(_sm_vjp_fwd, _sm_vjp_bwd)


def fused_softmax(x):
    """Softmax over the last axis. x: [..., H]."""
    h = x.shape[-1]
    return _sm(x.reshape(-1, h)).reshape(x.shape)
