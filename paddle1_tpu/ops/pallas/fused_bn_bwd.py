"""Fused batch-norm BACKWARD Pallas kernels.

One kernel produces dx, dgamma, dbeta (and dresidual) — the analog of
the reference's FusedBatchNormActGradKernel: the activation mask, the
two per-channel reductions (sum dy, sum dy*xhat) and the dx recurrence
never leave the kernel. On the v5e the training-mode kernel lost to
the XLA composition, which rides the dgrad and wgrad convolution
fusions (PERF.md, PR 26): it runs behind ``fused_bn=always`` only.

Training-mode dx couples every row to the batch reductions, so the
kernel mirrors the forward's two-phase sequential grid: phase 0
accumulates the f32 reduction outputs in VMEM, phase 1 streams dx
(and dresidual). Eval-mode dx is row-local, so its kernel is a single
phase that accumulates dgamma/dbeta while it streams.

The ``fused_bn_bwd`` flag picks between these kernels and
``*_bwd_xla`` — the jnp composition that is both the CPU/unaligned
fallback behind a kernel forward and the forward-only ablation arm. Same bf16 discipline as the
forward: reductions in f32, count exact, outputs cast at the edge.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import _common
from ._common import block_rows as _block_rows
from .fused_bn import supported

__all__ = ["train_bwd", "norm_bwd", "train_bwd_xla", "norm_bwd_xla"]


def _pallas_bwd_active(shape, dtype) -> bool:
    from ...core.flags import flag_active
    return flag_active("fused_bn_bwd") and supported(shape, dtype)


def _masked_dy(dy_ref, y_ref, act):
    dy = dy_ref[:].astype(jnp.float32)
    if act == "relu":
        # compare in f32: the v5e's vector unit has no bf16 comparison,
        # and Mosaic refuses the kernel rather than widen it
        dy = dy * (y_ref[:].astype(jnp.float32) > 0).astype(jnp.float32)
    return dy


# ---------------------------------------------------------------------------
# Training-mode backward (batch stats): two-phase grid
# ---------------------------------------------------------------------------


def _train_bwd_kernel(*refs, eps, act, inv_count, with_res):
    if with_res:
        (x_ref, g_ref, m_ref, v_ref, y_ref, dy_ref,
         dx_ref, dg_ref, db_ref, dr_ref) = refs
    else:
        (x_ref, g_ref, m_ref, v_ref, y_ref, dy_ref,
         dx_ref, dg_ref, db_ref) = refs
        dr_ref = None
    p = pl.program_id(0)
    i = pl.program_id(1)
    dy = _masked_dy(dy_ref, y_ref, act)
    rstd = jax.lax.rsqrt(v_ref[:] + eps)
    xhat = (x_ref[:].astype(jnp.float32) - m_ref[:]) * rstd

    @pl.when(p == 0)
    def _accumulate():
        sg = jnp.sum(dy * xhat, axis=0, keepdims=True)
        sb = jnp.sum(dy, axis=0, keepdims=True)

        @pl.when(i == 0)
        def _():
            dg_ref[:] = sg
            db_ref[:] = sb

        @pl.when(i != 0)
        def _():
            dg_ref[:] = dg_ref[:] + sg
            db_ref[:] = db_ref[:] + sb

    @pl.when(p == 1)
    def _stream():
        dx = g_ref[:].astype(jnp.float32) * rstd * (
            dy - db_ref[:] * inv_count - xhat * dg_ref[:] * inv_count)
        dx_ref[:] = dx.astype(dx_ref.dtype)
        if dr_ref is not None:
            dr_ref[:] = dy.astype(dr_ref.dtype)


def _train_bwd_pallas(x2, g, mean, var, y2, dy2, eps, act, with_res):
    rows, c = x2.shape
    br = _block_rows(rows, c)
    kernel = functools.partial(
        _train_bwd_kernel, eps=eps, act=act, inv_count=1.0 / rows,
        with_res=with_res)
    row_spec = pl.BlockSpec((br, c), lambda p, i: (i, 0))
    park_spec = pl.BlockSpec((br, c), lambda p, i: (p * i, 0))
    ch_spec = pl.BlockSpec((1, c), lambda p, i: (0, 0))
    out_specs = [park_spec, ch_spec, ch_spec]
    out_shape = [jax.ShapeDtypeStruct((rows, c), x2.dtype),
                 jax.ShapeDtypeStruct((1, c), jnp.float32),
                 jax.ShapeDtypeStruct((1, c), jnp.float32)]
    if with_res:
        out_specs.append(park_spec)
        out_shape.append(jax.ShapeDtypeStruct((rows, c), dy2.dtype))
    outs = pl.pallas_call(
        kernel,
        grid=(2, rows // br),
        in_specs=[row_spec, ch_spec, ch_spec, ch_spec, row_spec, row_spec],
        out_specs=out_specs,
        out_shape=out_shape,
        name="p1t_fused_bn_bwd_dx",
        interpret=_common.interpret(),
    )(x2, g.reshape(1, c), mean.astype(jnp.float32).reshape(1, c),
      var.astype(jnp.float32).reshape(1, c), y2, dy2)
    dx, dg, db = outs[0], outs[1].reshape(c), outs[2].reshape(c)
    if with_res:
        return dx, dg, db, outs[3]
    return dx, dg, db


def train_bwd_xla(x2, g, mean, var, y2, dy2, eps, act, with_res=False):
    """jnp composition of the training-mode backward — the fallback and
    the on-chip ablation arm for the Pallas kernel."""
    n = x2.shape[0]
    dy = dy2.astype(jnp.float32)
    if act == "relu":
        dy = dy * (y2 > 0).astype(jnp.float32)
    rstd = jax.lax.rsqrt(var.astype(jnp.float32) + eps)
    xhat = (x2.astype(jnp.float32) - mean[None, :]) * rstd[None, :]
    dg = jnp.sum(dy * xhat, axis=0)
    db = jnp.sum(dy, axis=0)
    dx = (g.astype(jnp.float32) * rstd)[None, :] * (
        dy - db[None, :] / n - xhat * dg[None, :] / n)
    dx = dx.astype(x2.dtype)
    if with_res:
        return dx, dg, db, dy.astype(dy2.dtype)
    return dx, dg, db


def train_bwd(x2, g, mean, var, y2, dy2, eps, act, with_res=False):
    """dx/dgamma/dbeta (+dresidual) for training-mode fused BN: the
    Pallas one-pass kernel when ``fused_bn_bwd`` resolves active, else
    the XLA composition."""
    if _pallas_bwd_active(x2.shape, x2.dtype):
        return _train_bwd_pallas(x2, g, mean, var, y2, dy2, float(eps),
                                 act, with_res)
    return train_bwd_xla(x2, g, mean, var, y2, dy2, float(eps), act,
                         with_res)


# ---------------------------------------------------------------------------
# Given-stats backward (eval / SyncBatchNorm normalize): single phase
# ---------------------------------------------------------------------------


def _norm_bwd_kernel(*refs, eps, act, with_res):
    if with_res:
        (x_ref, g_ref, m_ref, v_ref, y_ref, dy_ref,
         dx_ref, dg_ref, db_ref, dr_ref) = refs
    else:
        (x_ref, g_ref, m_ref, v_ref, y_ref, dy_ref,
         dx_ref, dg_ref, db_ref) = refs
        dr_ref = None
    i = pl.program_id(0)
    dy = _masked_dy(dy_ref, y_ref, act)
    rstd = jax.lax.rsqrt(v_ref[:] + eps)
    xhat = (x_ref[:].astype(jnp.float32) - m_ref[:]) * rstd
    sg = jnp.sum(dy * xhat, axis=0, keepdims=True)
    sb = jnp.sum(dy, axis=0, keepdims=True)

    @pl.when(i == 0)
    def _():
        dg_ref[:] = sg
        db_ref[:] = sb

    @pl.when(i != 0)
    def _():
        dg_ref[:] = dg_ref[:] + sg
        db_ref[:] = db_ref[:] + sb

    dx_ref[:] = (dy * g_ref[:].astype(jnp.float32) * rstd).astype(
        dx_ref.dtype)
    if dr_ref is not None:
        dr_ref[:] = dy.astype(dr_ref.dtype)


def _norm_bwd_pallas(x2, g, mean, var, y2, dy2, eps, act, with_res):
    rows, c = x2.shape
    br = _block_rows(rows, c)
    kernel = functools.partial(_norm_bwd_kernel, eps=eps, act=act,
                               with_res=with_res)
    row_spec = pl.BlockSpec((br, c), lambda i: (i, 0))
    ch_spec = pl.BlockSpec((1, c), lambda i: (0, 0))
    out_specs = [row_spec, ch_spec, ch_spec]
    out_shape = [jax.ShapeDtypeStruct((rows, c), x2.dtype),
                 jax.ShapeDtypeStruct((1, c), jnp.float32),
                 jax.ShapeDtypeStruct((1, c), jnp.float32)]
    if with_res:
        out_specs.append(row_spec)
        out_shape.append(jax.ShapeDtypeStruct((rows, c), dy2.dtype))
    outs = pl.pallas_call(
        kernel,
        grid=(rows // br,),
        in_specs=[row_spec, ch_spec, ch_spec, ch_spec, row_spec, row_spec],
        out_specs=out_specs,
        out_shape=out_shape,
        name="p1t_fused_bn_bwd_norm",
        interpret=_common.interpret(),
    )(x2, g.reshape(1, c), mean.astype(jnp.float32).reshape(1, c),
      var.astype(jnp.float32).reshape(1, c), y2, dy2)
    dx, dg, db = outs[0], outs[1].reshape(c), outs[2].reshape(c)
    if with_res:
        return dx, dg, db, outs[3]
    return dx, dg, db


def norm_bwd_xla(x2, g, mean, var, y2, dy2, eps, act, with_res=False):
    """jnp composition of the given-stats backward."""
    dy = dy2.astype(jnp.float32)
    if act == "relu":
        dy = dy * (y2 > 0).astype(jnp.float32)
    rstd = jax.lax.rsqrt(var.astype(jnp.float32) + eps)
    xhat = (x2.astype(jnp.float32) - mean[None, :]) * rstd[None, :]
    dg = jnp.sum(dy * xhat, axis=0)
    db = jnp.sum(dy, axis=0)
    dx = (dy * (g.astype(jnp.float32) * rstd)[None, :]).astype(x2.dtype)
    if with_res:
        return dx, dg, db, dy.astype(dy2.dtype)
    return dx, dg, db


def norm_bwd(x2, g, mean, var, y2, dy2, eps, act, with_res=False):
    """dx/dgamma/dbeta (+dresidual) for given-stats fused BN."""
    if _pallas_bwd_active(x2.shape, x2.dtype):
        return _norm_bwd_pallas(x2, g, mean, var, y2, dy2, float(eps),
                                act, with_res)
    return norm_bwd_xla(x2, g, mean, var, y2, dy2, float(eps), act,
                        with_res)
