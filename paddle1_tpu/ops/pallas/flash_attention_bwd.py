"""Pallas flash-attention BACKWARD kernels (FlashAttention-2 split).

The forward (flash_attention.py) recomputes probabilities in XLA for the
backward; these kernels do the recompute in VMEM instead — logits and
probabilities never touch HBM in either pass:

* ``_dkv_kernel``: grid over (batch·head, k-block); one pass over the
  q-blocks accumulates dK and dV for the resident k-block.
* ``_dq_kernel``: grid over (batch·head, q-block); one pass over the
  k-blocks accumulates dQ for the resident q-block.

Both consume the forward's LSE and ``delta = rowsum(dout * out)``
(computed in XLA — one cheap fused reduction). Scalar-per-row inputs
ride a trailing singleton dim ([bh, n, 1]) which satisfies Mosaic's
(8, 128)-or-equal tiling rule without lane broadcasting.

Gated by core flag ``flash_backward`` — default ``auto`` (engaged on
TPU): chip_smoke.py runs dq/dk/dv on the v5e against the XLA reference
and tests/test_chip_compile.py compiles them for it. ``never`` restores
the XLA recompute backward; interpret mode (``always`` off-TPU) does not
enforce the tiling rules (the forward's LSE layout bug only surfaced on
hardware).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import _common
from .flash_attention import BLOCK_K, BLOCK_Q, _NEG_INF

__all__ = ["flash_attention_bwd", "supported"]


def supported(q_shape, k_shape) -> bool:
    _, nq, _, d = q_shape
    _, nk, _, _ = k_shape
    if nq % BLOCK_Q or nk % BLOCK_K:
        return False
    if d % 8 or d > 256:
        return False
    # the dkv pass keeps FULL q+do rows resident; the dq pass keeps
    # full k+v. Measured scoped-VMEM cost (r5, on-chip compile report
    # at nq=nk=16384, d=64: 32.25 MiB vs the 16 MiB limit) is ~32
    # bytes per row-element — operands + accumulators + pipeline
    # double-buffering — so gate on that model with headroom. Shapes
    # rejected here take the chunked XLA recompute backward
    # (_bwd_xla), which is HBM-bounded instead.
    budget = 14 * 1024 * 1024
    if 32 * max(nq, nk) * d > budget:
        return False
    return True


def _masks(s_shape, q0, k0, nk, nq, causal, mask_ref):
    """Additive -inf mask for one [BQ, BK] logits tile."""
    add = None
    if causal:
        q_ids = (q0 + (nk - nq) +
                 jax.lax.broadcasted_iota(jnp.int32, s_shape, 0))
        k_ids = k0 + jax.lax.broadcasted_iota(jnp.int32, s_shape, 1)
        add = jnp.where(q_ids >= k_ids, 0.0, _NEG_INF)
    if mask_ref is not None:
        mk = mask_ref[0, pl.ds(k0, s_shape[1]), 0]        # [BK]
        pad = jnp.where(mk[None, :] > 0.5, 0.0, _NEG_INF)
        add = pad if add is None else add + pad
    return add


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, *, scale, causal, mask_ref=None):
    # k_ref/v_ref: [BLOCK_K, D] (resident); q/do: [N_q, D] full rows;
    # lse/delta: [N_q, 1]
    k_blk = pl.program_id(1)
    nq = q_ref.shape[0]
    nk = pl.num_programs(1) * BLOCK_K
    d = q_ref.shape[1]
    k = k_ref[:].astype(jnp.float32)
    v = v_ref[:].astype(jnp.float32)

    def body(i, carry):
        dk, dv = carry
        q = q_ref[pl.ds(i * BLOCK_Q, BLOCK_Q), :].astype(jnp.float32)
        do = do_ref[pl.ds(i * BLOCK_Q, BLOCK_Q), :].astype(jnp.float32)
        lse = lse_ref[pl.ds(i * BLOCK_Q, BLOCK_Q), 0]
        delta = delta_ref[pl.ds(i * BLOCK_Q, BLOCK_Q), 0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * scale
        add = _masks(s.shape, i * BLOCK_Q, k_blk * BLOCK_K, nk, nq,
                     causal, mask_ref)
        if add is not None:
            s = s + add
        # lse is +inf for fully-masked rows (remapped by the wrapper):
        # p underflows to an exact 0 there
        p = jnp.exp(s - lse[:, None])                     # [BQ, BK]
        dv = dv + jax.lax.dot_general(p, do,
                                      (((0,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None]) * scale
        dk = dk + jax.lax.dot_general(ds, q,
                                      (((0,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32)
        return dk, dv

    dk0 = jnp.zeros((BLOCK_K, d), jnp.float32)
    dv0 = jnp.zeros((BLOCK_K, d), jnp.float32)
    if causal:
        # q-blocks strictly before this k-block see none of it
        lo = jnp.maximum(
            (k_blk * BLOCK_K - (nk - nq)) // BLOCK_Q, 0)
    else:
        lo = 0
    dk, dv = jax.lax.fori_loop(lo, nq // BLOCK_Q, body, (dk0, dv0))
    dk_ref[:] = dk.astype(dk_ref.dtype)
    dv_ref[:] = dv.astype(dv_ref.dtype)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
               dq_ref, *, scale, causal, mask_ref=None):
    # q/do: [BLOCK_Q, D] resident; k/v full; lse/delta: [BLOCK_Q, 1]
    q_blk = pl.program_id(1)
    nk = k_ref.shape[0]
    nq = pl.num_programs(1) * BLOCK_Q
    d = q_ref.shape[1]
    q = q_ref[:].astype(jnp.float32)
    do = do_ref[:].astype(jnp.float32)
    lse = lse_ref[:, 0]
    delta = delta_ref[:, 0]

    def body(i, dq):
        k = k_ref[pl.ds(i * BLOCK_K, BLOCK_K), :].astype(jnp.float32)
        v = v_ref[pl.ds(i * BLOCK_K, BLOCK_K), :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * scale
        add = _masks(s.shape, q_blk * BLOCK_Q, i * BLOCK_K, nk, nq,
                     causal, mask_ref)
        if add is not None:
            s = s + add
        p = jnp.exp(s - lse[:, None])
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None]) * scale
        return dq + jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    dq0 = jnp.zeros((BLOCK_Q, d), jnp.float32)
    if causal:
        hi = pl.cdiv((q_blk + 1) * BLOCK_Q + (nk - nq), BLOCK_K)
        hi = jnp.minimum(hi, nk // BLOCK_K)
    else:
        hi = nk // BLOCK_K
    dq = jax.lax.fori_loop(0, hi, body, dq0)
    dq_ref[:] = dq.astype(dq_ref.dtype)


def flash_attention_bwd(q, k, v, out, lse, dout, scale, causal,
                        padding_mask=None):
    """(dq, dk, dv) in the paddle [B, N, H, D] layout — drop-in for
    flash_attention._bwd_xla."""
    b, nq, h, d = q.shape
    nk = k.shape[1]
    to_bhnd = lambda x: x.transpose(0, 2, 1, 3).reshape(b * h, -1, d)
    qh, kh, vh = to_bhnd(q), to_bhnd(k), to_bhnd(v)
    doh, oh = to_bhnd(dout), to_bhnd(out)

    # delta = rowsum(dout * out): one fused XLA reduction
    delta = jnp.sum(doh.astype(jnp.float32) * oh.astype(jnp.float32),
                    axis=-1, keepdims=True)               # [bh, nq, 1]
    # fully-padded rows carry the forward's FINITE sentinel LSE; remap to
    # +inf so exp(s - lse) is an exact 0 for every key (same guard as
    # _bwd_xla — exp(s - (-1e30)) would be exp(0) = 1, garbage grads)
    lse3 = lse.reshape(b * h, nq, 1).astype(jnp.float32)
    lse3 = jnp.where(lse3 > _NEG_INF * 0.1, lse3, jnp.inf)

    args = [qh, kh, vh, doh, lse3, delta]
    qspec = pl.BlockSpec((None, BLOCK_Q, d), lambda bh, i: (bh, i, 0))
    kfull = pl.BlockSpec((None, nk, d), lambda bh, i: (bh, 0, 0))
    qfull = pl.BlockSpec((None, nq, d), lambda bh, i: (bh, 0, 0))
    kspec = pl.BlockSpec((None, BLOCK_K, d), lambda bh, i: (bh, i, 0))
    row_q = pl.BlockSpec((None, BLOCK_Q, 1), lambda bh, i: (bh, i, 0))
    row_qfull = pl.BlockSpec((None, nq, 1), lambda bh, i: (bh, 0, 0))

    mask_arg, mask_specs = (), ()
    if padding_mask is not None:
        mk = padding_mask.astype(jnp.float32).reshape(b, 1, nk, 1)
        mask_arg = (mk,)
        mask_specs = (pl.BlockSpec((None, 1, nk, 1),
                                   lambda bh, i: (bh // h, 0, 0, 0)),)

    def with_mask(kern, n_outs):
        if padding_mask is None:
            return functools.partial(kern, scale=scale, causal=causal)

        def k2(*refs):
            *ins, m_ref = refs[:len(refs) - n_outs]
            outs = refs[len(refs) - n_outs:]
            kern(*ins, *outs, scale=scale, causal=causal,
                 mask_ref=m_ref)
        return k2

    # dkv pass
    dk, dv = pl.pallas_call(
        with_mask(_dkv_kernel, 2),
        grid=(b * h, nk // BLOCK_K),
        in_specs=[qfull, kspec, kspec, qfull, row_qfull, row_qfull,
                  *mask_specs],
        out_specs=[kspec, kspec],
        out_shape=[jax.ShapeDtypeStruct((b * h, nk, d), k.dtype),
                   jax.ShapeDtypeStruct((b * h, nk, d), v.dtype)],
        name="p1t_flash_attention_bwd_dkv",
        interpret=_common.interpret(),
    )(*args, *mask_arg)

    # dq pass
    dq = pl.pallas_call(
        with_mask(_dq_kernel, 1),
        grid=(b * h, nq // BLOCK_Q),
        in_specs=[qspec, kfull, kfull, qspec, row_q, row_q, *mask_specs],
        out_specs=qspec,
        out_shape=jax.ShapeDtypeStruct((b * h, nq, d), q.dtype),
        name="p1t_flash_attention_bwd_dq",
        interpret=_common.interpret(),
    )(*args, *mask_arg)

    back = lambda x: x.reshape(b, h, -1, d).transpose(0, 2, 1, 3)
    return back(dq), back(dk), back(dv)
