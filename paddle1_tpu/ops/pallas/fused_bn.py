"""Fused batch-norm Pallas kernels (forward family).

TPU-native analog of the reference's fused BN CUDA ops
(/root/reference/paddle/fluid/operators/fused/fused_bn_activation_op.cu
and fused_bn_add_activation_op.cu): ONE kernel owns the whole
stats + normalize + activation (+ residual-add) chain.

What the v5e said of the TRAINING kernels (PERF.md, PR 26;
ResNet-50, batch 128, bf16): the step is shorter without them. A
``tpu_custom_call`` is a wall to XLA: it cannot hang the statistics on
the convolution that produces x nor the normalize + ReLU on the one
that consumes y, and it copies convolution outputs into the row-major
layout the call demands; the plain composition
(``nn/functional/norm.py::_bn_train_forward``) has neither cost, so
``fused_bn=auto`` takes it in training mode and ``fused_bn_train``
runs under ``fused_bn=always`` only (tests, the ablation). The
given-stats kernels (``fused_bn_norm``, ``local_moments``) are still
what ``auto`` picks on a TPU; on the v5e they are not measured.

The training kernel is a two-pass-in-one-call design: a sequential
(2, row_blocks) grid whose first phase accumulates per-channel
sum / sum-of-squares into the f32 stat outputs resident in VMEM and
whose second phase finalizes mean/var once and streams the normalized,
affine-transformed, optionally residual-added and activated output.
No stat intermediate ever round-trips HBM, and the output (and
residual) windows ride a ``p * i`` index map so they stay parked on
block 0 through the stats phase — the data moves x twice, y and the
residual once.

bf16-safe exact-count discipline (the one ``SyncBatchNorm`` documents):
every reduction accumulates in f32 regardless of the compute dtype, and
the element count enters once as an exact host-side constant — a bf16
count is inexact past 256 and E[x^2]-mean^2 cancels catastrophically,
so the variance is clamped at 0 the same way ``sync_batch_norm_op``
does.

Inputs are channels-last ``[rows, C]`` (NHWC flattened), so under
``conv_nhwc=auto`` the conv/BN/act/pool residual block stays
layout-stable end to end. Backward lives in ``fused_bn_bwd.py``
(Pallas one-pass dx/dgamma/dbeta behind ``fused_bn_bwd``, with the XLA
composition as the reference/ablation path). Interpret mode runs the
same kernels on CPU for tests.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import _common
from ._common import block_rows as _block_rows

__all__ = ["supported", "fused_bn_train", "fused_bn_norm",
           "local_moments", "ACTS"]

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
# Training kernel: stats + normalize + act (+ residual) in one call
# ---------------------------------------------------------------------------


def _bn_train_kernel(*refs, eps, act, inv_count, with_res):
    if with_res:
        x_ref, g_ref, b_ref, r_ref, y_ref, mean_ref, var_ref = refs
    else:
        x_ref, g_ref, b_ref, y_ref, mean_ref, var_ref = refs
        r_ref = None
    p = pl.program_id(0)
    i = pl.program_id(1)
    x = x_ref[:].astype(jnp.float32)                      # [BR, C]

    @pl.when(p == 0)
    def _accumulate():
        s = jnp.sum(x, axis=0, keepdims=True)
        ss = jnp.sum(x * x, axis=0, keepdims=True)

        @pl.when(i == 0)
        def _():
            mean_ref[:] = s
            var_ref[:] = ss

        @pl.when(i != 0)
        def _():
            mean_ref[:] = mean_ref[:] + s
            var_ref[:] = var_ref[:] + ss

    @pl.when(p == 1)
    def _normalize():
        @pl.when(i == 0)
        def _finalize():
            m = mean_ref[:] * inv_count
            var_ref[:] = jnp.maximum(var_ref[:] * inv_count - m * m, 0.0)
            mean_ref[:] = m

        y = (x - mean_ref[:]) * jax.lax.rsqrt(var_ref[:] + eps)
        y = y * g_ref[:].astype(jnp.float32) + b_ref[:].astype(jnp.float32)
        if r_ref is not None:
            y = y + r_ref[:].astype(jnp.float32)
        y_ref[:] = _act_fwd(y, act).astype(y_ref.dtype)


def _train_fwd(x2, g, b, res, eps, act):
    rows, c = x2.shape
    br = _block_rows(rows, c)
    kernel = functools.partial(
        _bn_train_kernel, eps=eps, act=act, inv_count=1.0 / rows,
        with_res=res is not None)
    in_specs = [
        pl.BlockSpec((br, c), lambda p, i: (i, 0)),
        pl.BlockSpec((1, c), lambda p, i: (0, 0)),
        pl.BlockSpec((1, c), lambda p, i: (0, 0)),
    ]
    args = [x2, g.reshape(1, c), b.reshape(1, c)]
    if res is not None:
        # parked on block 0 through the stats phase (fetched once),
        # streamed in lockstep with x through the normalize phase
        in_specs.append(pl.BlockSpec((br, c), lambda p, i: (p * i, 0)))
        args.append(res)
    y, mean, var = pl.pallas_call(
        kernel,
        grid=(2, rows // br),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((br, c), lambda p, i: (p * i, 0)),
            pl.BlockSpec((1, c), lambda p, i: (0, 0)),
            pl.BlockSpec((1, c), lambda p, i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rows, c), x2.dtype),
            jax.ShapeDtypeStruct((1, c), jnp.float32),
            jax.ShapeDtypeStruct((1, c), jnp.float32),
        ],
        name="p1t_fused_bn_fwd_stats",
        interpret=_common.interpret(),
    )(*args)
    return y, mean.reshape(c), var.reshape(c)


def _stat_cotangent_terms(x2, mean, dmean, dvar, inv_count):
    """Fold cotangents that flow INTO the batch-stat outputs back into
    dx: mean = sum(x)/n, var = sum(x^2)/n - mean^2. Running-stat
    consumers detach the stats, so on the training path these are
    zeros, but a ``custom_vjp`` rule receives them as zero ARRAYS and
    XLA may not fold ``0 * (x - mean)`` in floating point: the compiled
    step keeps a full-size ``multiply`` by a zero constant, a read of
    x and a read and a write of dx for every norm (seen in the v5e's
    compiled text, PR 26). The composition that ``auto`` runs gives
    the statistics no gradient and has no such pass."""
    xf = x2.astype(jnp.float32)
    extra = (dmean[None, :]
             + 2.0 * dvar[None, :] * (xf - mean[None, :])) * inv_count
    return extra


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _bn_train(x2, g, b, eps, act):
    return _train_fwd(x2, g, b, None, eps, act)


def _bn_train_fwd_rule(x2, g, b, eps, act):
    y, mean, var = _train_fwd(x2, g, b, None, eps, act)
    return (y, mean, var), (x2, g, mean, var, y)


def _bn_train_bwd_rule(eps, act, resids, cts):
    x2, g, mean, var, y = resids
    dy, dmean, dvar = cts
    from .fused_bn_bwd import train_bwd
    dx, dg, db = train_bwd(x2, g, mean, var, y, dy, eps, act)
    extra = _stat_cotangent_terms(x2, mean, dmean, dvar, 1.0 / x2.shape[0])
    dx = (dx.astype(jnp.float32) + extra).astype(x2.dtype)
    return dx, dg.astype(g.dtype), db.astype(g.dtype)


_bn_train.defvjp(_bn_train_fwd_rule, _bn_train_bwd_rule)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _bn_train_res(x2, g, b, res, eps, act):
    return _train_fwd(x2, g, b, res, eps, act)


def _bn_train_res_fwd_rule(x2, g, b, res, eps, act):
    y, mean, var = _train_fwd(x2, g, b, res, eps, act)
    # zero-size carrier: residuals must be jax types, and bwd only
    # needs the residual's dtype
    return (y, mean, var), (x2, g, mean, var, y,
                            jnp.zeros((0,), res.dtype))


def _bn_train_res_bwd_rule(eps, act, resids, cts):
    x2, g, mean, var, y, res_proto = resids
    dy, dmean, dvar = cts
    from .fused_bn_bwd import train_bwd
    dx, dg, db, dres = train_bwd(x2, g, mean, var, y, dy, eps, act,
                                 with_res=True)
    extra = _stat_cotangent_terms(x2, mean, dmean, dvar, 1.0 / x2.shape[0])
    dx = (dx.astype(jnp.float32) + extra).astype(x2.dtype)
    return (dx, dg.astype(g.dtype), db.astype(g.dtype),
            dres.astype(res_proto.dtype))


_bn_train_res.defvjp(_bn_train_res_fwd_rule, _bn_train_res_bwd_rule)


def fused_bn_train(x2, gamma, beta, epsilon, act="identity", residual=None):
    """Training-mode fused BN over channels-last ``x2: [rows, C]``.

    Returns ``(y, batch_mean, batch_var)`` with the stats in f32 —
    ``y = act((x - mean) * rsqrt(var + eps) * gamma + beta [+ residual])``.
    """
    _check_act(act)
    if residual is None:
        return _bn_train(x2, gamma, beta, float(epsilon), act)
    return _bn_train_res(x2, gamma, beta, residual, float(epsilon), act)


# ---------------------------------------------------------------------------
# Normalize kernel: given stats (eval mode / SyncBatchNorm post-psum)
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
    from .fused_bn_bwd import norm_bwd
    dx, dg, db = norm_bwd(x2, g, m, v, y, dy, eps, act)
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
    from .fused_bn_bwd import norm_bwd
    dx, dg, db, dres = norm_bwd(x2, g, m, v, y, dy, eps, act,
                                with_res=True)
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
