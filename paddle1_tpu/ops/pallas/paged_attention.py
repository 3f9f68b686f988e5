"""Paged-attention gather kernel (decode over the block-paged KV pool).

The paged decode cache (ISSUE 16) keeps K/V in per-layer global pools of
fixed-size pages — ``[pages, heads, page_size, dim]`` — with a
``[slots, max_pages_per_slot]`` int32 page table mapping each decode
slot's logical positions onto pool pages. Attention then needs a
*gather*: slot ``s``'s query window must read pages
``table[s, 0..ceil(len/page_size))``, scattered anywhere in the pool.

Two arms, same contract (used by nn.functional.paged_attention):

* :func:`paged_attention_ref` — XLA ``take`` composition. Materializes
  the gathered ``[slots, capacity, heads, dim]`` K/V, so it is the
  CPU/ablation arm and the numerics oracle.
* :func:`paged_attention` — the Pallas kernel. Scalar-prefetches the
  page table and per-slot base positions (PrefetchScalarGridSpec), so
  the BlockSpec index map itself chases ``table[s, j]``: each grid step
  DMAs exactly one page of K/V into VMEM and folds it into an
  online-softmax accumulator. The gathered cache never exists in HBM —
  the page table IS the gather.

Masking derives from position alone: query row ``i`` of slot ``s``
attends key positions ``<= base[s] + i`` (``base`` = the slot's length
before this window was written). Pages past the cursor — including the
reserved parking page that free slots' table rows point at — are fully
masked, so pool garbage never reaches the softmax of a live slot.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import _common

_LANES = 128  # Mosaic minor-dim tile (see flash_attention)
_NEG_INF = -1e30


def supported(q_shape, kp_shape) -> bool:
    """Tile-aligned shapes only; everything else uses the ref arm.
    ``q``: [slots, window, heads, dim]; ``kp``: [pages, heads,
    page_size, dim]."""
    if len(q_shape) != 4 or len(kp_shape) != 4:
        return False
    _, w, _, d = q_shape
    _, _, ps, _ = kp_shape
    if d % 8 or d > 256:
        return False
    if ps % 8:
        return False
    if w < 1 or w > 64:  # decode windows only (1 + spec_tokens)
        return False
    return True


def paged_attention_ref(q, kp, vp, table, base,
                        scale: Optional[float] = None):
    """XLA gather arm: materialize each slot's K/V via ``take`` over the
    page table, then masked softmax. q: [S, W, H, D]; kp/vp:
    [P, H, ps, D]; table: [S, mpps] int32; base: [S] int32 (slot length
    before this window). Returns [S, W, H, D]."""
    s_, w, h, d = q.shape
    ps = kp.shape[2]
    mpps = table.shape[1]
    cap = mpps * ps
    sc = float(scale) if scale is not None else float(1.0 / (d ** 0.5))
    flat = table.astype(jnp.int32).reshape(-1)

    def gather(pool):  # [S*mpps, H, ps, D] -> [S, cap, H, D]
        g = jnp.take(pool, flat, axis=0).reshape(s_, mpps, h, ps, d)
        return g.transpose(0, 1, 3, 2, 4).reshape(s_, cap, h, d)

    k, v = gather(kp), gather(vp)
    logits = jnp.einsum("swhd,skhd->shwk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * sc
    kpos = jnp.arange(cap, dtype=jnp.int32)
    qpos = base.astype(jnp.int32)[:, None] + jnp.arange(w, dtype=jnp.int32)
    mask = kpos[None, None, :] <= qpos[:, :, None]  # [S, W, cap]
    logits = jnp.where(mask[:, None], logits, _NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("shwk,skhd->swhd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)


def _kernel(table_ref, base_ref, q_ref, k_ref, v_ref, o_ref,
            m_ref, l_ref, acc_ref, *, scale, page_size):
    # grid (S, H, mpps); q_ref/o_ref: [W, D]; k_ref/v_ref: [ps, D] —
    # the page table already steered this block's DMA (index map), so
    # the kernel body only folds one page into the online softmax.
    s = pl.program_id(0)
    j = pl.program_id(2)
    nj = pl.num_programs(2)
    w = q_ref.shape[0]

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full(m_ref.shape, _NEG_INF, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    q = q_ref[...].astype(jnp.float32) * scale
    k = k_ref[...].astype(jnp.float32)
    sc = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # [W, ps]
    base = base_ref[s]
    kpos = j * page_size + jax.lax.broadcasted_iota(
        jnp.int32, (w, page_size), 1)
    rows = jax.lax.broadcasted_iota(jnp.int32, (w, page_size), 0)
    sc = jnp.where(kpos <= base + rows, sc, _NEG_INF)

    m_prev = m_ref[...][:, :1]  # [W, 1]; lanes hold copies
    l_prev = l_ref[...][:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(sc, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(sc - m_new)
    l_new = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, v_ref[...].astype(jnp.float32), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[...] = jax.lax.broadcast_in_dim(m_new[:, 0], m_ref.shape, (0,))
    l_ref[...] = jax.lax.broadcast_in_dim(l_new[:, 0], l_ref.shape, (0,))

    @pl.when(j == nj - 1)
    def _finalize():
        m = m_ref[...][:, :1]
        l = l_ref[...][:, :1]
        # a row with zero visible keys never happens for a live slot
        # (base >= 0 makes key 0 visible to every row), but free slots
        # ride the dispatch with parked tables — keep their output
        # finite instead of 0/0
        l_safe = jnp.where(l == 0.0, 1.0, l)
        out = jnp.where(m > _NEG_INF * 0.5, acc_ref[...] / l_safe, 0.0)
        o_ref[...] = out.astype(o_ref.dtype)


def paged_attention(q, kp, vp, table, base,
                    scale: Optional[float] = None):
    """Pallas gather arm, same contract as :func:`paged_attention_ref`.
    Grid (slots, heads, pages-per-slot); the scalar-prefetched table
    steers each step's K/V page DMA, scratch carries the online-softmax
    (m, l, acc) across the page axis."""
    s_, w, h, d = q.shape
    ps = kp.shape[2]
    mpps = table.shape[1]
    sc = float(scale) if scale is not None else float(1.0 / (d ** 0.5))
    kernel = functools.partial(_kernel, scale=sc, page_size=ps)
    # Mosaic takes a block whose last two dims are whole array dims (or
    # 8/128-aligned), so heads sit ahead of the rows in every operand:
    # the pools are stored that way, and the query window — a few rows
    # per slot — is transposed here and back.
    q = q.transpose(0, 2, 1, 3)  # [S, H, W, D]
    # index maps under scalar-prefetch receive (*grid_idx, *scalar_refs)
    qspec = pl.BlockSpec((None, None, w, d),
                         lambda s, hh, j, t, b: (s, hh, 0, 0))
    pspec = pl.BlockSpec((None, None, ps, d),
                         lambda s, hh, j, t, b: (t[s, j], hh, 0, 0))
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(s_, h, mpps),
            in_specs=[qspec, pspec, pspec],
            out_specs=qspec,
            scratch_shapes=[
                pltpu.VMEM((w, _LANES), jnp.float32),
                pltpu.VMEM((w, _LANES), jnp.float32),
                pltpu.VMEM((w, d), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((s_, h, w, d), q.dtype),
        name="p1t_paged_attention_fwd",
        interpret=_common.interpret(),
    )(table.astype(jnp.int32), base.astype(jnp.int32), q, kp, vp)
    return out.transpose(0, 2, 1, 3)
