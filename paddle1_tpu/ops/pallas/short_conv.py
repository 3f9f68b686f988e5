"""The gated short convolution as two Pallas kernels (``nn/functional/
short_conv.py`` has the equations and the XLA form); no reference analog.

``bcx`` [batch, seq, 3 * channels] holds the gates ``b`` and ``c`` and the
input ``x`` side by side, as the projection before the op wrote them; the
kernels take it whole and window its three parts by column, so no part is
copied out first. A grid step holds ``rows`` positions of every channel.
The taps reach ``taps - 1`` positions back (the backward pass: forward),
across the block's edge: the ``_EDGE`` (16: a packed bfloat16 tile) rows
before the block (after it) come through a second window on the same
array, zeros at a row's start (end). A shift along the positions is a
rotation of the block with its edge rows on top (below), on the sublane
axis, and an aligned slice.

Forward: ``bcx`` read once, ``out`` written once: the op's roofline.
Backward: ``bcx`` and the output's gradient read once, ``d bcx`` written
once; the taps' gradient, a sum over every position of a channel, is
accumulated in float32 in an output block that stays resident over the
whole grid, which therefore runs in order.

XLA's fusion of the same sums (PERF.md section 6, PR 38) writes ``b * x``
and the tap sum to HBM in float32 between its passes. Runs in interpreter
mode off-TPU so tests exercise the same code path.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import _common

__all__ = ["supported", "forward", "backward"]

_LANES = 128
_EDGE = 16          # rows of the neighbouring block a grid step fetches
_VMEM_LIMIT = 64 * 1024 * 1024


def _rows(seq, channels, budget):
    """Positions a grid step: the most that divide ``seq`` whose float32
    [rows, channels] tile is ``budget`` bytes at most; 0 if none."""
    for r in (512, 256, 128, 64, 32, 16):
        if seq % r == 0 and r * channels * 4 <= budget:
            return r
    return 0


def supported(shape, taps) -> bool:
    """[batch, seq, 3 * channels] with channels in whole lanes, positions
    in whole edge tiles, taps that reach no further than an edge."""
    if len(shape) != 3 or shape[2] % 3:
        return False
    channels = shape[2] // 3
    return (channels % _LANES == 0 and shape[1] % _EDGE == 0
            and 1 <= taps <= _EDGE + 1
            and _rows(shape[1], channels, 1 << 20) > 0)


def _parts(ref, channels):
    """(b, c, x) of a [rows, 3 * channels] window, float32."""
    return tuple(ref[:, k * channels:(k + 1) * channels].astype(jnp.float32)
                 for k in range(3))


def _earlier(s, edge, by):
    """``s`` [rows, ch] ``by`` positions earlier, the rows before it from
    ``edge`` [_EDGE, ch]."""
    if by == 0:
        return s
    return pltpu.roll(jnp.concatenate([edge, s], axis=0), by, 0)[_EDGE:]


def _later(s, edge, by):
    """``by`` positions later, the rows after it from ``edge``."""
    if by == 0:
        return s
    whole = jnp.concatenate([s, edge], axis=0)
    return pltpu.roll(whole, whole.shape[0] - by, 0)[:s.shape[0]]


def _fwd_kernel(bcx_ref, before_ref, w_ref, o_ref, *, taps):
    channels = o_ref.shape[1]
    b, c, x = _parts(bcx_ref, channels)
    bb, _, xb = _parts(before_ref, channels)
    s = b * x
    edge = jnp.where(pl.program_id(1) > 0, bb * xb, 0.0)
    y = w_ref[taps - 1:taps, :] * s
    for k in range(taps - 1):
        y = y + w_ref[k:k + 1, :] * _earlier(s, edge, taps - 1 - k)
    o_ref[...] = (c * y).astype(o_ref.dtype)


def _bwd_kernel(bcx_ref, before_ref, after_ref, g_ref, g_after_ref, w_ref,
                d_ref, dw_ref, *, taps):
    channels = g_ref.shape[1]
    i, last = pl.program_id(1), pl.num_programs(1) - 1

    @pl.when((pl.program_id(0) == 0) & (i == 0))
    def _():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    b, c, x = _parts(bcx_ref, channels)
    bb, _, xb = _parts(before_ref, channels)
    _, ca, _ = _parts(after_ref, channels)
    g = g_ref[...].astype(jnp.float32)
    s = b * x
    s_edge = jnp.where(i > 0, bb * xb, 0.0)
    dy = g * c
    dy_edge = jnp.where(i < last, g_after_ref[...].astype(jnp.float32) * ca,
                        0.0)
    y = w_ref[taps - 1:taps, :] * s
    ds = w_ref[taps - 1:taps, :] * dy
    dw_ref[taps - 1:taps, :] += jnp.sum(dy * s, axis=0, keepdims=True)
    for k in range(taps - 1):
        by = taps - 1 - k
        earlier = _earlier(s, s_edge, by)
        y = y + w_ref[k:k + 1, :] * earlier
        ds = ds + w_ref[k:k + 1, :] * _later(dy, dy_edge, by)
        dw_ref[k:k + 1, :] += jnp.sum(dy * earlier, axis=0, keepdims=True)
    for k, part in enumerate((ds * x, g * y, ds * b)):
        d_ref[:, k * channels:(k + 1) * channels] = part.astype(d_ref.dtype)


def _windows(rows, width, edge_blocks):
    """Block specs of a [batch, seq, width] array: the block itself, the
    edge before it and the edge after it (clamped at a row's ends, where
    the kernels put zeros)."""
    per = rows // _EDGE
    return (
        pl.BlockSpec((None, rows, width), lambda n, i: (n, i, 0)),
        pl.BlockSpec((None, _EDGE, width),
                     lambda n, i: (n, jnp.maximum(i * per - 1, 0), 0)),
        pl.BlockSpec((None, _EDGE, width),
                     lambda n, i: (n, jnp.minimum((i + 1) * per,
                                                  edge_blocks - 1), 0)))


def forward(bcx, weight):
    """``bcx`` [batch, seq, 3 * channels], ``weight`` [channels, taps] ->
    [batch, seq, channels] in ``bcx``'s dtype."""
    batch, seq, width = bcx.shape
    channels, taps = width // 3, weight.shape[1]
    rows = _rows(seq, channels, 2 << 20)
    block, before, _ = _windows(rows, width, seq // _EDGE)
    w = weight.astype(jnp.float32).T
    return pl.pallas_call(
        functools.partial(_fwd_kernel, taps=taps),
        grid=(batch, seq // rows),
        in_specs=[block, before,
                  pl.BlockSpec((taps, channels), lambda n, i: (0, 0))],
        out_specs=pl.BlockSpec((None, rows, channels),
                               lambda n, i: (n, i, 0)),
        out_shape=jax.ShapeDtypeStruct((batch, seq, channels), bcx.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=_VMEM_LIMIT),
        name="p1t_gated_short_conv_fwd",
        interpret=_common.interpret(),
    )(bcx, bcx, w)


def backward(bcx, weight, g):
    """-> (``d bcx`` in ``bcx``'s dtype, ``d weight`` [channels, taps]
    float32) for the output's gradient ``g`` [batch, seq, channels]."""
    batch, seq, width = bcx.shape
    channels, taps = width // 3, weight.shape[1]
    rows = _rows(seq, channels, 1 << 20)
    block, before, after = _windows(rows, width, seq // _EDGE)
    g_block, _, g_after = _windows(rows, channels, seq // _EDGE)
    w = weight.astype(jnp.float32).T
    d, dw = pl.pallas_call(
        functools.partial(_bwd_kernel, taps=taps),
        grid=(batch, seq // rows),
        in_specs=[block, before, after, g_block, g_after,
                  pl.BlockSpec((taps, channels), lambda n, i: (0, 0))],
        out_specs=[pl.BlockSpec((None, rows, width), lambda n, i: (n, i, 0)),
                   pl.BlockSpec((taps, channels), lambda n, i: (0, 0))],
        out_shape=[jax.ShapeDtypeStruct(bcx.shape, bcx.dtype),
                   jax.ShapeDtypeStruct((taps, channels), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        name="p1t_gated_short_conv_bwd",
        interpret=_common.interpret(),
    )(bcx, bcx, bcx, g, g, w)
    return d, dw.T
