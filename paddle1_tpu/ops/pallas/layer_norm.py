"""Fused LayerNorm Pallas kernel.

TPU-native analog of the reference's fused LayerNorm CUDA kernels
(/root/reference/paddle/fluid/operators/fused/fused_layernorm_* and
layer_norm_op.cu): one VMEM pass computes mean/rstd and the normalized,
affine-transformed output per row — no separate stats kernels, no HBM
round-trips for intermediates.

Forward = Pallas kernel; backward = XLA composition that recomputes the
(cheap, fusable) row stats — the same residual-free flash-style split used
by ops/pallas/flash_attention.py. Runs in interpreter mode off-TPU so tests
exercise the same code path.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import _common
from ._common import block_rows as _block_rows

__all__ = ["fused_layer_norm", "supported"]


def supported(shape, n_norm_axes: int) -> bool:
    """One trailing normalized axis, lane-aligned, rows sublane-aligned,
    and a row block that fits the VMEM budget at this h."""
    if n_norm_axes != 1 or len(shape) < 2:
        return False
    h = shape[-1]
    rows = 1
    for s in shape[:-1]:
        rows *= s
    if h % 128:
        return False
    return _block_rows(rows, h) > 0


def _ln_fwd_kernel(x_ref, w_ref, b_ref, y_ref, *, eps):
    x = x_ref[:].astype(jnp.float32)            # [BR, H]
    mean = jnp.mean(x, axis=1, keepdims=True)
    xc = x - mean
    var = jnp.mean(xc * xc, axis=1, keepdims=True)
    y = xc * jax.lax.rsqrt(var + eps)
    y = y * w_ref[:].astype(jnp.float32) + b_ref[:].astype(jnp.float32)
    y_ref[:] = y.astype(y_ref.dtype)


def _ln_fwd(x2, w, b, eps):
    rows, h = x2.shape
    br = _block_rows(rows, h)
    kernel = functools.partial(_ln_fwd_kernel, eps=eps)
    return pl.pallas_call(
        kernel,
        grid=(rows // br,),
        in_specs=[
            pl.BlockSpec((br, h), lambda i: (i, 0)),
            pl.BlockSpec((1, h), lambda i: (0, 0)),
            pl.BlockSpec((1, h), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((br, h), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, h), x2.dtype),
        name="p1t_layer_norm_fwd",
        interpret=_common.interpret(),
    )(x2, w.reshape(1, h), b.reshape(1, h))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _ln(x2, w, b, eps):
    return _ln_fwd(x2, w, b, eps)


def _ln_vjp_fwd(x2, w, b, eps):
    return _ln_fwd(x2, w, b, eps), (x2, w, b)


def _ln_vjp_bwd(eps, res, dy):
    x2, w, b = res
    xf = x2.astype(jnp.float32)
    dyf = dy.astype(jnp.float32)
    mean = jnp.mean(xf, axis=1, keepdims=True)
    xc = xf - mean
    var = jnp.mean(xc * xc, axis=1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    xhat = xc * rstd
    dyw = dyf * w.astype(jnp.float32)[None, :]
    dx = rstd * (dyw - jnp.mean(dyw, axis=1, keepdims=True)
                 - xhat * jnp.mean(dyw * xhat, axis=1, keepdims=True))
    dw = jnp.sum(dyf * xhat, axis=0)
    db = jnp.sum(dyf, axis=0)
    return (dx.astype(x2.dtype), dw.astype(w.dtype), db.astype(b.dtype))


_ln.defvjp(_ln_vjp_fwd, _ln_vjp_bwd)


def fused_layer_norm(x, weight, bias, epsilon: float = 1e-5):
    """LayerNorm over the last axis. x: [..., H]; weight/bias: [H]."""
    h = x.shape[-1]
    x2 = x.reshape(-1, h)
    y = _ln(x2, weight, bias, float(epsilon))
    return y.reshape(x.shape)
