"""Pallas flash-attention BACKWARD kernels (FlashAttention-2 split), on
the blocked grid of flash_attention.py: logits and probabilities never
touch HBM, no kernel keeps a whole row of any operand, and the blocks a
mask rule hides cost nothing.

* ``_dkv_kernel``: grid (batch·key head, k-block, q-block); a k-block
  stays resident while the q-blocks that see it pass (from the diagonal
  down under the causal rule), dK and dV accumulate in VMEM. Where a
  key head serves a group of query heads, the group's heads pass one
  after the other along the same sequential axis. The scores are
  computed transposed ([BK, BQ]) so that every product is a plain ``a @
  b`` or ``a @ b.T``.
* ``_dq_kernel``: grid (batch·head, q-block, k-block); a q-block stays
  resident while the k-blocks it sees pass.

Both consume the forward's LSE and ``delta = rowsum(dout * out)``
(computed in XLA, one fused reduction) as [batch·head, 1, Nq] rows, lane
dense; the dQ kernel turns its block of them into a column once.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import _common
from .flash_attention import (_LANES, _NEG_INF, _NN, _NT, _count_tiles,
                              _dot, _kv_head, _lanes, _layout, _run_tile,
                              _unlayout, split_blocks)
from .flash_attention import block_sizes as forward_block_sizes

__all__ = ["flash_attention_bwd", "block_sizes"]


def block_sizes(nq: int, nk: int, d: int, dtype) -> tuple:
    """((block_k, block_q, chunk) of dK/dV, (block_q, block_k, chunk) of
    dQ): resident block, block fetched a grid step, slice of it a pass of
    the body takes. From the same sweep as the forward's, and the same
    ladders; ``d`` is the wider of the key and the value head widths."""
    bq, bk, chunk_k = forward_block_sizes(nq, nk, d, dtype)
    _, bq_long, chunk_q = forward_block_sizes(nk, nq, d, dtype)
    return (chunk_k, bq_long, chunk_q), (bq, bk, chunk_k)


def _dkv_kernel(*refs, scale, rule, off, chunk, has_mask, steps):
    # k_ref [BK, D], v_ref [BK, Dv] (resident); q_ref [BQ, D], do_ref
    # [BQ, Dv]; lse_ref/delta_ref: [1, BQ]; mask_ref: [BK, 1]
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref = refs[:6]
    mask_ref = refs[6] if has_mask else None
    dk_ref, dv_ref, ks_ref, dk_acc, dv_acc = refs[6 + has_mask:]
    j, t = pl.program_id(1), pl.program_id(2)
    bq = q_ref.shape[0]
    bk = k_ref.shape[0]
    # a group's query heads pass one after the other, ``steps`` each
    i = t if steps is None else t % steps
    at, live = rule.query_blocks(j, i, bk, bq)

    @pl.when(t == 0)
    def _():
        ks_ref[...] = (k_ref[...] * scale).astype(ks_ref.dtype)
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def one(c, masked):
        qs = pl.ds(c * chunk, chunk)
        q, do = q_ref[qs, :], do_ref[qs, :]
        s = _dot(ks_ref[...], q, _NT)                        # [BK, C]
        if has_mask:
            s = jnp.where(mask_ref[...] > 0.5, s, _NEG_INF)
        if masked:
            s = jnp.where(
                rule.keep(s.shape, at * bq + c * chunk, j * bk, off, 1),
                s, _NEG_INF)
        # lse is +inf for fully-masked rows (remapped by the wrapper):
        # p underflows to an exact 0 there
        p = jnp.exp(s - lse_ref[:, qs])
        dv_acc[...] += _dot(p.astype(do.dtype), do, _NN)
        dp = _dot(v_ref[...], do, _NT)
        ds = p * (dp - delta_ref[:, qs])
        dk_acc[...] += _dot(ds.astype(q.dtype), q, _NN)

    for c in range(bq // chunk):
        _run_tile(functools.partial(one, c), rule, at * bq + c * chunk,
                  chunk, j * bk, bk, off, live)

    @pl.when(t == pl.num_programs(2) - 1)
    def _():
        dk_ref[...] = (dk_acc[...] * scale).astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)


def _column(row_ref):
    """A [1, N] block as a lane-replicated [N, 128] value."""
    col = jnp.expand_dims(row_ref[0], -1)
    return jnp.broadcast_to(col, (col.shape[0], _LANES))


def _dq_kernel(*refs, scale, rule, off, chunk, has_mask):
    # q_ref [BQ, D], do_ref [BQ, Dv] resident; k_ref [BK, D], v_ref
    # [BK, Dv]; lse_ref/delta_ref: [1, BQ]; mask_ref: [1, BK]
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref = refs[:6]
    mask_ref = refs[6] if has_mask else None
    dq_ref, qs_ref, lse_col, delta_col, dq_acc = refs[6 + has_mask:]
    i, j = pl.program_id(1), pl.program_id(2)
    bq = q_ref.shape[0]
    bk = k_ref.shape[0]
    at, live = rule.key_blocks(i, j, bq, bk)

    @pl.when(j == 0)
    def _():
        qs_ref[...] = (q_ref[...] * scale).astype(qs_ref.dtype)
        lse_col[...] = _column(lse_ref)
        delta_col[...] = _column(delta_ref)
        dq_acc[...] = jnp.zeros_like(dq_acc)

    def one(c, masked):
        ks = pl.ds(c * chunk, chunk)
        k = k_ref[ks, :]
        s = _dot(qs_ref[...], k, _NT)
        if has_mask:
            s = jnp.where(mask_ref[:, ks] > 0.5, s, _NEG_INF)
        if masked:
            s = jnp.where(
                rule.keep(s.shape, i * bq, at * bk + c * chunk, off, 0),
                s, _NEG_INF)
        p = jnp.exp(s - _lanes(lse_col[...], chunk))
        dp = _dot(do_ref[...], v_ref[ks, :], _NT)
        ds = p * (dp - _lanes(delta_col[...], chunk))
        dq_acc[...] += _dot(ds.astype(k.dtype), k, _NN)

    for c in range(bk // chunk):
        _run_tile(functools.partial(one, c), rule, i * bq, bq,
                  at * bk + c * chunk, chunk, off, live)

    @pl.when(j == pl.num_programs(2) - 1)
    def _():
        dq_ref[...] = (dq_acc[...] * scale).astype(dq_ref.dtype)


def flash_attention_bwd(q, k, v, out, lse, dout, scale, rule,
                        padding_mask=None, blocks=None):
    """(dq, dk, dv) in the paddle [B, N, H, D] layout (dk and dv with
    k's heads, dv as wide as v) from the forward's residuals; ``lse`` is
    [batch*heads, Nq]; ``rule``: a rule of ``mask_rules``. A
    ``jit`` of its own, as the forward and for its reason."""
    return _bwd_call(q, k, v, out, lse, dout, padding_mask, scale=scale,
                     rule=rule, blocks=blocks,
                     interpret=_common.interpret())


@functools.partial(jax.jit, static_argnames=("scale", "rule", "blocks",
                                             "interpret"))
def _bwd_call(q, k, v, out, lse, dout, padding_mask, *, scale, rule,
              blocks, interpret):
    b, nq, h, d = q.shape
    nk, h_kv, dv = k.shape[1], k.shape[2], v.shape[3]
    group = h // h_kv
    off = nk - nq
    defaults = block_sizes(*rule.sizes(nq, nk), max(d, dv), q.dtype)
    _, dkv, dq = split_blocks(blocks)
    kb_kv, qb_kv, c_kv = dkv or defaults[0]
    qb_q, kb_q, c_q = dq or defaults[1]
    qa, at = _layout(q)
    ka, at_k = _layout(k)
    va, at_v = _layout(v)       # v and dV: the value width's layout
    doa, at_do = _layout(dout)  # dout: q's heads, v's width
    kv = _kv_head(h, h_kv)

    # delta = rowsum(dout * out): one fused XLA reduction
    delta = jnp.sum(dout.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)                                  # [B, Nq, H]
    delta = delta.transpose(0, 2, 1).reshape(b * h, 1, nq)
    # fully-padded rows carry the forward's FINITE sentinel LSE; remap to
    # +inf so exp(s - lse) is an exact 0 for every key (exp(s - (-1e30))
    # would be exp(0) = 1, garbage grads)
    lse = lse.reshape(b * h, 1, nq).astype(jnp.float32)
    lse = jnp.where(lse > _NEG_INF * 0.1, lse, jnp.inf)

    args = [qa, ka, va, doa, lse, delta]
    has_mask = padding_mask is not None
    params = dict(scale=scale, rule=rule, off=off, has_mask=has_mask)
    semantics = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))

    # dK/dV: the query blocks that see key block j, from the first on
    # under the causal rule, the needed ones alone under another
    steps, qi = rule.query_map(nq, nk, kb_kv, qb_kv)
    if group == 1:
        head, step = (lambda g, t: g), (lambda t: t)
    else:
        # grid step t of key head g: query head t // steps of its group
        head = lambda g, t: g // h_kv * h + g % h_kv * group + t // steps
        step = lambda t: t % steps
    _count_tiles(rule, nq, nk, c_kv, kb_kv, b * h)
    rows = pl.BlockSpec(
        (None, qb_kv, d), lambda g, j, i: at(head(g, i), qi(j, step(i))))
    douts = pl.BlockSpec(
        (None, qb_kv, dv),
        lambda g, j, i: at_do(head(g, i), qi(j, step(i))))
    keys = pl.BlockSpec((None, kb_kv, d), lambda g, j, i: at_k(g, j))
    values = pl.BlockSpec((None, kb_kv, dv), lambda g, j, i: at_v(g, j))
    stat = pl.BlockSpec((None, 1, qb_kv),
                        lambda g, j, i: (head(g, i), 0, qi(j, step(i))))
    in_specs = [rows, keys, values, douts, stat, stat]
    if has_mask:
        in_specs.append(pl.BlockSpec((None, kb_kv, 1),
                                     lambda g, j, i: (g // h_kv, j, 0)))
        args.append(padding_mask.astype(jnp.float32).reshape(b, nk, 1))
    dk, dv_out = pl.pallas_call(
        functools.partial(_dkv_kernel, chunk=c_kv,
                          steps=None if group == 1 else steps, **params),
        grid=(b * h_kv, nk // kb_kv, group * steps),
        in_specs=in_specs,
        out_specs=[keys, values],
        out_shape=[jax.ShapeDtypeStruct(ka.shape, k.dtype),
                   jax.ShapeDtypeStruct(va.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((kb_kv, d), k.dtype),
                        pltpu.VMEM((kb_kv, d), jnp.float32),
                        pltpu.VMEM((kb_kv, dv), jnp.float32)],
        compiler_params=semantics,
        name="p1t_flash_attention_bwd_dkv",
        interpret=interpret,
    )(*args)

    # dQ: the key blocks that query block i sees
    steps, kj = rule.key_map(nq, nk, qb_q, kb_q)
    _count_tiles(rule, nq, nk, qb_q, c_q, b * h)
    rows = pl.BlockSpec((None, qb_q, d), lambda g, i, j: at(g, i))
    douts = pl.BlockSpec((None, qb_q, dv), lambda g, i, j: at_do(g, i))
    keys = pl.BlockSpec((None, kb_q, d),
                        lambda g, i, j: at_k(kv(g), kj(i, j)))
    values = pl.BlockSpec((None, kb_q, dv),
                          lambda g, i, j: at_v(kv(g), kj(i, j)))
    stat = pl.BlockSpec((None, 1, qb_q), lambda g, i, j: (g, 0, i))
    in_specs = [rows, keys, values, douts, stat, stat]
    if has_mask:
        in_specs.append(pl.BlockSpec(
            (None, 1, kb_q), lambda g, i, j: (g // h, 0, kj(i, j))))
        args[-1] = args[-1].reshape(b, 1, nk)
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, chunk=c_q, **params),
        grid=(b * h, nq // qb_q, steps),
        in_specs=in_specs,
        out_specs=rows,
        out_shape=jax.ShapeDtypeStruct(qa.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((qb_q, d), q.dtype),
                        pltpu.VMEM((qb_q, _LANES), jnp.float32),
                        pltpu.VMEM((qb_q, _LANES), jnp.float32),
                        pltpu.VMEM((qb_q, d), jnp.float32)],
        compiler_params=semantics,
        name="p1t_flash_attention_bwd_dq",
        interpret=interpret,
    )(*args)

    return (_unlayout(dq, b, h, d), _unlayout(dk, b, h_kv, d),
            _unlayout(dv_out, b, h_kv, dv))
