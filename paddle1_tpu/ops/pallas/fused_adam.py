"""Fused Adam/AdamW update Pallas kernel.

TPU-native analog of the reference's fused optimizer CUDA kernels
(/root/reference/paddle/fluid/operators/optimizers/adam_op.cu — one kernel
reads p/g/m1/m2 and writes p/m1/m2): a single VMEM pass per block instead
of separate moment/param updates. The math is bit-identical to
optimizer.AdamW._update (decoupled decay; decay=0 + pre-adjusted grad
reproduces plain Adam).

Scalars (lr, bias corrections, decay) ride scalar-prefetch SMEM so `step`
stays a traced value. Runs in interpreter mode off-TPU for tests.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import _common

__all__ = ["fused_adam_update", "supported"]

_COLS = 1024
_ROWS = 8
_CHUNK = _COLS * _ROWS


def supported(n_elements: int) -> bool:
    # Tiny tensors (biases, norms) gain nothing; XLA fuses those fine.
    return n_elements >= _CHUNK


def _adam_kernel(s_ref, p_ref, g_ref, m1_ref, m2_ref,
                 po_ref, m1o_ref, m2o_ref, *, beta1, beta2, eps):
    lr, bc1, bc2, decay = s_ref[0], s_ref[1], s_ref[2], s_ref[3]
    g = g_ref[:].astype(jnp.float32)
    m1 = beta1 * m1_ref[:] + (1.0 - beta1) * g
    m2 = beta2 * m2_ref[:] + (1.0 - beta2) * g * g
    update = (m1 / bc1) / (jnp.sqrt(m2 / bc2) + eps)
    pf = p_ref[:].astype(jnp.float32) * (1.0 - lr * decay) - lr * update
    po_ref[:] = pf.astype(po_ref.dtype)
    m1o_ref[:] = m1
    m2o_ref[:] = m2


def fused_adam_update(p, g, m1, m2, lr, step, beta1, beta2, eps, decay):
    """One fused pass: returns (new_p, new_m1, new_m2).

    p: any shape/dtype; g same shape; m1/m2 f32. lr/step traced scalars;
    beta1/beta2/eps/decay python floats (decay may be traced).
    """
    shape, dtype = p.shape, p.dtype
    n = p.size
    pad = (-n) % _CHUNK
    rows = (n + pad) // _COLS

    def to2d(a, dt):
        flat = a.reshape(-1).astype(dt)
        if pad:
            flat = jnp.pad(flat, (0, pad))
        return flat.reshape(rows, _COLS)

    p2 = to2d(p, dtype)
    g2 = to2d(g, dtype)
    m12 = to2d(m1, jnp.float32)
    m22 = to2d(m2, jnp.float32)

    stepf = step.astype(jnp.float32) if hasattr(step, "astype") \
        else jnp.float32(step)
    scalars = jnp.stack([
        jnp.asarray(lr, jnp.float32),
        1.0 - beta1 ** stepf,
        1.0 - beta2 ** stepf,
        jnp.asarray(decay, jnp.float32),
    ])

    kernel = functools.partial(_adam_kernel, beta1=float(beta1),
                               beta2=float(beta2), eps=float(eps))
    # index maps under scalar-prefetch receive (grid_idx, scalar_ref)
    spec = pl.BlockSpec((_ROWS, _COLS), lambda i, s: (i, 0))
    new_p, new_m1, new_m2 = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(rows // _ROWS,),
            in_specs=[spec, spec, spec, spec],
            out_specs=[spec, spec, spec],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((rows, _COLS), dtype),
            jax.ShapeDtypeStruct((rows, _COLS), jnp.float32),
            jax.ShapeDtypeStruct((rows, _COLS), jnp.float32),
        ],
        name="p1t_fused_adam_update",
        interpret=_common.interpret(),
    )(scalars, p2, g2, m12, m22)

    unflat = lambda a, dt: a.reshape(-1)[:n].reshape(shape).astype(dt)
    return (unflat(new_p, dtype), unflat(new_m1, jnp.float32),
            unflat(new_m2, jnp.float32))
