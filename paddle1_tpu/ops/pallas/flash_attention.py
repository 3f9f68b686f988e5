"""Flash attention Pallas kernel (TPU MXU/VMEM-native fused attention).

Replaces the reference's fused multihead attention CUDA kernels
(/root/reference/paddle/fluid/operators/fused/ attention ops) with the
TPU idiom: online-softmax blocking in VMEM, one pass over K/V per query
block, logits never materialized in HBM.

Layout: [B, N, H, D] (paddle layout, matching nn.functional.attention).
Forward = Pallas kernel (+ log-sum-exp residual); backward = XLA
recompute from the LSE (flash-style, no stored probabilities).
Runs in interpreter mode off-TPU so tests exercise the same code path.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import _common

BLOCK_Q = 128
BLOCK_K = 128
_LANES = 128  # Mosaic minor-dim tile: scalar-per-row outputs are stored
              # broadcast across one 128-lane register row
_NEG_INF = -1e30


def supported(q_shape, k_shape, causal: bool = False) -> bool:
    """Tile-aligned shapes only; everything else uses attention_ref."""
    if len(q_shape) != 4 or len(k_shape) != 4:
        return False
    _, nq, _, d = q_shape
    _, nk, _, _ = k_shape
    if nq % BLOCK_Q or nk % BLOCK_K:
        return False
    if causal and nq > nk:
        # bottom-right causal leaves leading queries with ZERO visible
        # keys; the zero-sumexp sentinel would poison the vjp — let
        # attention_ref handle this degenerate alignment
        return False
    if d % 8 or d > 256:
        return False
    # K+V rows for one (batch, head) must fit in VMEM comfortably.
    # ">=": nk=16384/d=64 lands EXACTLY on the 8 MiB boundary and the
    # real scoped-vmem cost (16.12 MiB vs the 16 MiB limit, r5 on-chip
    # compile report) makes it a coin flip across compile contexts —
    # boundary shapes must not pass
    if 2 * nk * d * 4 >= 8 * 1024 * 1024:
        return False
    return True


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, scale, causal,
                block_k, mask_ref=None):
    # q_ref: [BLOCK_Q, D]; k_ref/v_ref: [N_k, D]; o_ref: [BLOCK_Q, D]
    # mask_ref (optional): [1, N_k] f32, 1.0 = attend / 0.0 = padding.
    q_blk = pl.program_id(1)
    nk = k_ref.shape[0]
    nq = pl.num_programs(1) * BLOCK_Q
    d = q_ref.shape[1]
    q = q_ref[:].astype(jnp.float32) * scale

    def body(i, carry):
        m_prev, l_prev, acc = carry
        k = k_ref[pl.ds(i * block_k, block_k), :].astype(jnp.float32)
        v = v_ref[pl.ds(i * block_k, block_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)  # [BQ, BK]
        if mask_ref is not None:
            mk = mask_ref[0, pl.ds(i * block_k, block_k)]  # [BK]
            s = jnp.where(mk[None, :] > 0.5, s, _NEG_INF)
        if causal:
            # bottom-right alignment (query i attends keys j <= i + nk-nq),
            # matching attention_ref's tril(..., nk - nq)
            q_ids = (q_blk * BLOCK_Q + (nk - nq) +
                     jax.lax.broadcasted_iota(jnp.int32,
                                              (BLOCK_Q, block_k), 0))
            k_ids = (i * block_k +
                     jax.lax.broadcasted_iota(jnp.int32,
                                              (BLOCK_Q, block_k), 1))
            s = jnp.where(q_ids >= k_ids, s, _NEG_INF)
        m_cur = jnp.max(s, axis=1)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_new = l_prev * alpha + jnp.sum(p, axis=1)
        acc = acc * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l_new, acc

    m0 = jnp.full((BLOCK_Q,), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((BLOCK_Q,), jnp.float32)
    acc0 = jnp.zeros((BLOCK_Q, d), jnp.float32)
    n_blocks = nk // block_k
    if causal:
        # blocks strictly above the (aligned) diagonal contribute nothing
        hi = (q_blk + 1) * BLOCK_Q + (nk - nq)
        n_blocks_eff = jnp.minimum(n_blocks, pl.cdiv(hi, block_k))
        m, l, acc = jax.lax.fori_loop(0, n_blocks_eff, body, (m0, l0, acc0))
    else:
        m, l, acc = jax.lax.fori_loop(0, n_blocks, body, (m0, l0, acc0))
    # Rows with zero visible keys (fully-padded batch entry): m is still the
    # sentinel and p degenerated to exp(0)=1 per key inside the loop. Gate
    # those rows to zero output and sentinel LSE so the backward (which
    # keys p off the LSE) produces exact zero gradients for them.
    visible = m > _NEG_INF * 0.5
    l_safe = jnp.where(l == 0.0, 1.0, l)
    out = jnp.where(visible[:, None], acc / l_safe[:, None], 0.0)
    o_ref[:] = out.astype(o_ref.dtype)
    # [BLOCK_Q] → [BLOCK_Q, _LANES]: Mosaic requires the last two block dims
    # tile to (8, 128), so the per-row LSE is broadcast across one lane row
    # (same layout as jax's own TPU flash kernel's l/m outputs)
    lse = jnp.where(visible, m + jnp.log(l_safe), _NEG_INF)
    lse_ref[:] = jax.lax.broadcast_in_dim(
        lse.astype(jnp.float32), (BLOCK_Q, _LANES), (0,))


def _flash_fwd(q, k, v, scale, causal, padding_mask=None):
    b, nq, h, d = q.shape
    nk = k.shape[1]
    # [B, N, H, D] → [B*H, N, D]
    qh = q.transpose(0, 2, 1, 3).reshape(b * h, nq, d)
    kh = k.transpose(0, 2, 1, 3).reshape(b * h, nk, d)
    vh = v.transpose(0, 2, 1, 3).reshape(b * h, nk, d)

    kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                               block_k=BLOCK_K)
    in_specs = [
        pl.BlockSpec((None, BLOCK_Q, d), lambda bh, i: (bh, i, 0)),
        pl.BlockSpec((None, nk, d), lambda bh, i: (bh, 0, 0)),
        pl.BlockSpec((None, nk, d), lambda bh, i: (bh, 0, 0)),
    ]
    args = (qh, kh, vh)
    if padding_mask is not None:
        # [B, Nk] keep-mask as f32; each (batch, head) program reads its
        # batch row (index map folds bh → b).
        mk = padding_mask.astype(jnp.float32).reshape(b, 1, nk)
        in_specs.append(
            pl.BlockSpec((None, 1, nk), lambda bh, i: (bh // h, 0, 0)))
        args = args + (mk,)

        def kernel(q_r, k_r, v_r, m_r, o_r, l_r):
            _fwd_kernel(q_r, k_r, v_r, o_r, l_r, scale=scale, causal=causal,
                        block_k=BLOCK_K, mask_ref=m_r)
    out, lse = pl.pallas_call(
        kernel,
        grid=(b * h, nq // BLOCK_Q),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((None, BLOCK_Q, d), lambda bh, i: (bh, i, 0)),
            pl.BlockSpec((None, BLOCK_Q, _LANES), lambda bh, i: (bh, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, nq, d), q.dtype),
            jax.ShapeDtypeStruct((b * h, nq, _LANES), jnp.float32),
        ],
        name="p1t_flash_attention_fwd",
        interpret=_common.interpret(),
    )(*args)
    out = out.reshape(b, h, nq, d).transpose(0, 2, 1, 3)
    lse = lse[:, :, 0].reshape(b, h, nq)
    return out, lse


def _bwd_xla(q, k, v, out, lse, dout, scale, causal, padding_mask=None,
             q_chunk=None):
    """Flash-style backward in XLA: recompute P per (b,h) from the saved
    LSE; XLA blocks/fuses the einsums onto the MXU. Long sequences scan
    over query chunks so the transient [B,H,C,Nk] score block stays
    bounded (~512 MiB) instead of materializing the full [B,H,Nq,Nk]
    matrix — this is the memory-escape backward for shapes the Pallas
    kernels' VMEM model rejects (flash_attention_bwd.supported)."""
    qh = jnp.swapaxes(q, 1, 2).astype(jnp.float32)   # [B,H,Nq,D]
    kh = jnp.swapaxes(k, 1, 2).astype(jnp.float32)
    vh = jnp.swapaxes(v, 1, 2).astype(jnp.float32)
    doh = jnp.swapaxes(dout, 1, 2).astype(jnp.float32)
    oh = jnp.swapaxes(out, 1, 2).astype(jnp.float32)
    b, h, nq, d = qh.shape
    nk = kh.shape[2]
    # fully-masked rows carry the sentinel LSE from the forward: exp(s-lse)
    # would be exp(0)=1 per key there — gate p to zero instead so such rows
    # contribute no gradient (matching their zeroed forward output)
    lse = jnp.where(lse > _NEG_INF * 0.1, lse, jnp.inf)

    def block_grads(qs, dos, os_, lses, q0):
        """Gradient contributions of one query block [B,H,C,D]."""
        s = jnp.einsum("bhqd,bhkd->bhqk", qs, kh) * scale
        if padding_mask is not None:
            s = jnp.where(padding_mask[:, None, None, :] > 0.5, s,
                          _NEG_INF)
        if causal:
            c = qs.shape[2]
            q_ids = (q0 + (nk - nq) +
                     jax.lax.broadcasted_iota(jnp.int32, (c, nk), 0))
            k_ids = jax.lax.broadcasted_iota(jnp.int32, (c, nk), 1)
            s = jnp.where((q_ids >= k_ids)[None, None], s, _NEG_INF)
        p = jnp.exp(s - lses[..., None])              # [B,H,C,Nk]
        dv_c = jnp.einsum("bhqk,bhqd->bhkd", p, dos)
        dp = jnp.einsum("bhqd,bhkd->bhqk", dos, vh)
        delta = jnp.sum(dos * os_, axis=-1, keepdims=True)
        ds = p * (dp - delta) * scale
        dq_c = jnp.einsum("bhqk,bhkd->bhqd", ds, kh)
        dk_c = jnp.einsum("bhqk,bhqd->bhkd", ds, qs)
        return dq_c, dk_c, dv_c

    # chunk size: bound the f32 score block near 512 MiB, keep the
    # q dim a multiple that divides nq (nq is BLOCK_Q-aligned here);
    # q_chunk overrides for tests
    if q_chunk is not None:
        if nq % q_chunk:
            raise ValueError(
                f"q_chunk={q_chunk} must divide nq={nq} (a non-divisor "
                "would silently drop the tail rows' gradients)")
        chunk = q_chunk
    else:
        target = max(1, (512 * 1024 * 1024) // max(b * h * nk * 4, 1))
        # floor at 128 (nq is BLOCK_Q-aligned on every path that
        # reaches here): for the very largest workloads target drops
        # below every candidate, and falling back to chunk=nq would
        # materialize the full score matrix — the exact OOM this
        # chunking exists to prevent
        chunk = 128 if nq % 128 == 0 else nq
        for cand in (4096, 2048, 1024, 512, 256):
            if cand <= target and nq % cand == 0:
                chunk = cand
                break
    if chunk >= nq:
        dq, dk, dv = block_grads(qh, doh, oh, lse, 0)
    else:
        n_chunks = nq // chunk

        def body(carry, i):
            dk_acc, dv_acc = carry
            sl = lambda a: jax.lax.dynamic_slice_in_dim(
                a, i * chunk, chunk, axis=2)
            dq_c, dk_c, dv_c = block_grads(sl(qh), sl(doh), sl(oh),
                                           sl(lse), i * chunk)
            return (dk_acc + dk_c, dv_acc + dv_c), dq_c
        (dk, dv), dq_chunks = jax.lax.scan(
            body, (jnp.zeros_like(kh), jnp.zeros_like(vh)),
            jnp.arange(n_chunks))
        # [n_chunks, B, H, C, D] -> [B, H, Nq, D]
        dq = jnp.moveaxis(dq_chunks, 0, 2).reshape(b, h, nq, d)
    to = lambda x: jnp.swapaxes(x, 1, 2)
    return (to(dq).astype(q.dtype), to(dk).astype(k.dtype),
            to(dv).astype(v.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _flash(q, k, v, scale, causal):
    out, _ = _flash_fwd(q, k, v, scale, causal)
    return out


def _flash_vjp_fwd(q, k, v, scale, causal):
    out, lse = _flash_fwd(q, k, v, scale, causal)
    return out, (q, k, v, out, lse)


def _bwd_dispatch(q, k, v, out, lse, dout, scale, causal,
                  padding_mask=None):
    """XLA recompute backward by default; the Pallas backward kernels
    when the flash_backward flag allows (chip-smoked lowering only —
    see flash_attention_bwd.py)."""
    from ...core.flags import flag_active
    if flag_active("flash_backward"):
        from .flash_attention_bwd import flash_attention_bwd, supported
        if supported(q.shape, k.shape):
            return flash_attention_bwd(q, k, v, out, lse, dout, scale,
                                       causal, padding_mask=padding_mask)
    return _bwd_xla(q, k, v, out, lse, dout, scale, causal,
                    padding_mask=padding_mask)


def _flash_vjp_bwd(scale, causal, res, dout):
    q, k, v, out, lse = res
    return _bwd_dispatch(q, k, v, out, lse, dout, scale, causal)


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _flash_masked(q, k, v, padding_mask, scale, causal):
    out, _ = _flash_fwd(q, k, v, scale, causal, padding_mask=padding_mask)
    return out


def _flash_masked_vjp_fwd(q, k, v, padding_mask, scale, causal):
    out, lse = _flash_fwd(q, k, v, scale, causal, padding_mask=padding_mask)
    return out, (q, k, v, padding_mask, out, lse)


def _flash_masked_vjp_bwd(scale, causal, res, dout):
    q, k, v, padding_mask, out, lse = res
    dq, dk, dv = _bwd_dispatch(q, k, v, out, lse, dout, scale, causal,
                               padding_mask=padding_mask)
    # mask enters as f32 0/1 (see flash_attention), so a plain zero
    # cotangent is the right "non-differentiable" answer
    return dq, dk, dv, jnp.zeros_like(padding_mask)


_flash_masked.defvjp(_flash_masked_vjp_fwd, _flash_masked_vjp_bwd)


def flash_attention(q, k, v, causal: bool = False,
                    scale: Optional[float] = None, padding_mask=None):
    """Fused attention. ``padding_mask``: optional [B, Nk] keep-mask
    (bool/0-1); padded key positions are excluded from the softmax —
    the Pallas analog of the reference's additive attention-mask input
    (nn/layer/transformer.py MultiHeadAttention attn_mask)."""
    d = q.shape[-1]
    s = float(scale) if scale is not None else float(1.0 / (d ** 0.5))
    if padding_mask is None:
        return _flash(q, k, v, s, causal)
    pm = jnp.asarray(padding_mask)
    if pm.dtype == jnp.bool_:
        pm = pm.astype(jnp.float32)
    return _flash_masked(q, k, v, pm, s, causal)
