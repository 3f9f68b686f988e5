"""Fused batch-norm Pallas kernels for GIVEN statistics.

TPU-native analog of the reference's fused BN CUDA ops
(/root/reference/paddle/fluid/operators/fused/fused_bn_activation_op.cu
and fused_bn_add_activation_op.cu) where the per-channel statistics
arrive from outside the kernel: eval mode, and ``SyncBatchNorm``, whose
statistics cross replicas between its two local halves.

- ``fused_bn_norm``: normalize + affine (+ residual) + activation in
  one kernel, forward, and one kernel for dx / dgamma / dbeta
  (+ dresidual) backward: the activation mask and the two per-channel
  reductions (sum dy, sum dy * xhat) never leave it. dx is row-local
  given the statistics, so the backward is a single phase that
  accumulates dgamma / dbeta in VMEM while it streams.
- ``local_moments``: per-channel (sum, sum of squares) in one f32 pass,
  the local half of ``SyncBatchNorm``'s statistics.

``fused_bn=auto`` takes these on a TPU; on the v5e they are not
measured. Training-mode batch norm has no kernel: it is the XLA
composition ``nn/functional/norm.py::_bn_train_forward``, which the
compiler fuses into the convolutions on either side of it, where a
``tpu_custom_call`` is a wall (PERF.md, PR 26, has the arms measured
on the v5e).

bf16 discipline (the one ``SyncBatchNorm`` documents): every reduction
accumulates in f32 regardless of the compute dtype, and outputs are
cast at the edge.

Inputs are channels-last ``[rows, C]`` (NHWC flattened), so under
``conv_nhwc=auto`` the conv/BN/act/pool residual block stays
layout-stable end to end. Interpret mode runs the same kernels on CPU
for tests.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import _common
from ._common import block_rows as _block_rows

__all__ = ["supported", "fused_bn_norm", "local_moments", "ACTS"]

ACTS = ("identity", "relu")


def _check_act(act: str) -> None:
    if act not in ACTS:
        from ...core.errors import InvalidArgumentError
        raise InvalidArgumentError(
            f"fused_bn activation must be one of {ACTS}, got {act!r}")


def supported(shape, dtype=None) -> bool:
    """Channels-last input ``[..., C]``: lane-friendly channel count,
    rows tiling into the shared VMEM row-block ladder (and a sublane-
    aligned block for 16-bit compute dtypes)."""
    if len(shape) < 2:
        return False
    c = shape[-1]
    rows = 1
    for s in shape[:-1]:
        rows *= s
    if c <= 0 or c % 8:
        return False
    br = _block_rows(rows, c)
    if br <= 0:
        return False
    if dtype is not None and jnp.dtype(dtype).itemsize == 2 and br % 16:
        return False
    return True


def _act_fwd(y, act: str):
    if act == "relu":
        return jnp.maximum(y, 0.0)
    return y


# ---------------------------------------------------------------------------
# Normalize kernels, forward and backward: given stats (eval mode /
# SyncBatchNorm post-psum)
# ---------------------------------------------------------------------------


def _bn_norm_kernel(*refs, eps, act, with_res):
    if with_res:
        x_ref, m_ref, v_ref, g_ref, b_ref, r_ref, y_ref = refs
    else:
        x_ref, m_ref, v_ref, g_ref, b_ref, y_ref = refs
        r_ref = None
    x = x_ref[:].astype(jnp.float32)
    y = (x - m_ref[:]) * jax.lax.rsqrt(v_ref[:] + eps)
    y = y * g_ref[:].astype(jnp.float32) + b_ref[:].astype(jnp.float32)
    if r_ref is not None:
        y = y + r_ref[:].astype(jnp.float32)
    y_ref[:] = _act_fwd(y, act).astype(y_ref.dtype)


def _norm_fwd(x2, m, v, g, b, res, eps, act):
    rows, c = x2.shape
    br = _block_rows(rows, c)
    kernel = functools.partial(_bn_norm_kernel, eps=eps, act=act,
                               with_res=res is not None)
    row_spec = pl.BlockSpec((br, c), lambda i: (i, 0))
    ch_spec = pl.BlockSpec((1, c), lambda i: (0, 0))
    in_specs = [row_spec, ch_spec, ch_spec, ch_spec, ch_spec]
    args = [x2, m.astype(jnp.float32).reshape(1, c),
            v.astype(jnp.float32).reshape(1, c),
            g.reshape(1, c), b.reshape(1, c)]
    if res is not None:
        in_specs.append(row_spec)
        args.append(res)
    return pl.pallas_call(
        kernel,
        grid=(rows // br,),
        in_specs=in_specs,
        out_specs=row_spec,
        out_shape=jax.ShapeDtypeStruct((rows, c), x2.dtype),
        name="p1t_fused_bn_fwd_norm",
        interpret=_common.interpret(),
    )(*args)


def _norm_bwd_kernel(*refs, eps, act, with_res):
    if with_res:
        (x_ref, g_ref, m_ref, v_ref, y_ref, dy_ref,
         dx_ref, dg_ref, db_ref, dr_ref) = refs
    else:
        (x_ref, g_ref, m_ref, v_ref, y_ref, dy_ref,
         dx_ref, dg_ref, db_ref) = refs
        dr_ref = None
    i = pl.program_id(0)
    dy = dy_ref[:].astype(jnp.float32)
    if act == "relu":
        # compare in f32: the v5e's vector unit has no bf16 comparison,
        # and Mosaic refuses the kernel rather than widen it
        dy = dy * (y_ref[:].astype(jnp.float32) > 0).astype(jnp.float32)
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


def _norm_bwd(x2, g, mean, var, y2, dy2, eps, act, with_res):
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


def _norm_stat_grads(g, var, dg, db, eps):
    """Channel-sized cotangents for the given stats: y depends on mean
    only through the shift and on var only through rstd."""
    gf = g.astype(jnp.float32)
    rstd = jax.lax.rsqrt(var.astype(jnp.float32) + eps)
    dm = -gf * rstd * db
    dv = -0.5 * gf * rstd * rstd * rstd * (dg / rstd)
    return dm, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _bn_norm(x2, m, v, g, b, eps, act):
    return _norm_fwd(x2, m, v, g, b, None, eps, act)


def _bn_norm_fwd_rule(x2, m, v, g, b, eps, act):
    y = _norm_fwd(x2, m, v, g, b, None, eps, act)
    return y, (x2, m, v, g, y)


def _bn_norm_bwd_rule(eps, act, resids, dy):
    x2, m, v, g, y = resids
    dx, dg, db = _norm_bwd(x2, g, m, v, y, dy, eps, act, False)
    dm, dv = _norm_stat_grads(g, v, dg, db, eps)
    return (dx, dm.astype(m.dtype), dv.astype(v.dtype),
            dg.astype(g.dtype), db.astype(g.dtype))


_bn_norm.defvjp(_bn_norm_fwd_rule, _bn_norm_bwd_rule)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _bn_norm_res(x2, m, v, g, b, res, eps, act):
    return _norm_fwd(x2, m, v, g, b, res, eps, act)


def _bn_norm_res_fwd_rule(x2, m, v, g, b, res, eps, act):
    y = _norm_fwd(x2, m, v, g, b, res, eps, act)
    return y, (x2, m, v, g, y, jnp.zeros((0,), res.dtype))


def _bn_norm_res_bwd_rule(eps, act, resids, dy):
    x2, m, v, g, y, res_proto = resids
    dx, dg, db, dres = _norm_bwd(x2, g, m, v, y, dy, eps, act, True)
    dm, dv = _norm_stat_grads(g, v, dg, db, eps)
    return (dx, dm.astype(m.dtype), dv.astype(v.dtype),
            dg.astype(g.dtype), db.astype(g.dtype),
            dres.astype(res_proto.dtype))


_bn_norm_res.defvjp(_bn_norm_res_fwd_rule, _bn_norm_res_bwd_rule)


def fused_bn_norm(x2, mean, var, gamma, beta, epsilon, act="identity",
                  residual=None):
    """Normalize ``x2: [rows, C]`` with GIVEN per-channel stats — the
    eval-mode kernel, and SyncBatchNorm's normalize after its
    cross-replica stat reduction (mean/var stay differentiable so the
    psum transpose sees their cotangents)."""
    _check_act(act)
    if residual is None:
        return _bn_norm(x2, mean, var, gamma, beta, float(epsilon), act)
    return _bn_norm_res(x2, mean, var, gamma, beta, residual,
                        float(epsilon), act)


# ---------------------------------------------------------------------------
# Local moments: SyncBatchNorm's per-replica stat pass
# ---------------------------------------------------------------------------


def _moments_kernel(x_ref, s_ref, ss_ref):
    i = pl.program_id(0)
    x = x_ref[:].astype(jnp.float32)
    s = jnp.sum(x, axis=0, keepdims=True)
    ss = jnp.sum(x * x, axis=0, keepdims=True)

    @pl.when(i == 0)
    def _():
        s_ref[:] = s
        ss_ref[:] = ss

    @pl.when(i != 0)
    def _():
        s_ref[:] = s_ref[:] + s
        ss_ref[:] = ss_ref[:] + ss


def _moments_fwd(x2):
    rows, c = x2.shape
    br = _block_rows(rows, c)
    s, ss = pl.pallas_call(
        _moments_kernel,
        grid=(rows // br,),
        in_specs=[pl.BlockSpec((br, c), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((1, c), lambda i: (0, 0)),
                   pl.BlockSpec((1, c), lambda i: (0, 0))],
        out_shape=[jax.ShapeDtypeStruct((1, c), jnp.float32),
                   jax.ShapeDtypeStruct((1, c), jnp.float32)],
        name="p1t_fused_bn_fwd_moments",
        interpret=_common.interpret(),
    )(x2)
    return s.reshape(c), ss.reshape(c)


@jax.custom_vjp
def _lm(x2):
    return _moments_fwd(x2)


def _lm_fwd_rule(x2):
    return _moments_fwd(x2), x2


def _lm_bwd_rule(x2, cts):
    ds, dss = cts
    dx = ds[None, :] + 2.0 * x2.astype(jnp.float32) * dss[None, :]
    return (dx.astype(x2.dtype),)


_lm.defvjp(_lm_fwd_rule, _lm_bwd_rule)


def local_moments(x2):
    """One f32 pass over ``x2: [rows, C]`` returning per-channel
    ``(sum, sum_of_squares)`` — the local half of SyncBatchNorm's
    cross-replica stats."""
    return _lm(x2)
