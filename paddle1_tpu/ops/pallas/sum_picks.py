"""Sum a token's picks from the rows that hold one: the expert layer's
combine, and the transpose of its dispatch (``nn/layer_moe.py``); no
reference analog.

``o`` [rows, hidden] holds one row for every pick this share computed, in
the grouped products' order; ``where`` [tokens * fan] names each pick's row,
or ``rows`` for a pick that has none (its expert is absent, or it lies
beyond the capacity). XLA's gather fetches a row for EVERY pick, 36.7 ns a
row from HBM whatever the row holds, and most picks have none (87% on one
share of eight). The kernel asks for the held rows alone: one DMA each
(4 KB at hidden 2048 in bf16) into a zeroed ``[fan, block, hidden]`` buffer
in VMEM, the next block of tokens fetched while this one is summed, the
sum over ``fan`` in float32 and cast once.

A DMA moves whole tiles, and in ``[rows, hidden]`` a tile is eight rows
deep; so the rows travel in a shape where a row IS whole tiles, its own
and in its own order (XLA makes the relayout: 0.46 ms for 151 MB in, 0.21
for 67 MB out, PERF.md section 6, PR 32). :func:`_row_shape` reads that
shape off the width and the dtype: ``[hidden / 128, 128]`` for whole ``(8,
128) x 32-bit`` tiles (hidden 2048 in bf16: Kanana-2, SDAR, LFM2), ``[depth,
hidden / depth]`` for any other whole number of 32-bit lane rows (PR 44;
SmallThinker's 2560 in bf16 is ten: ``[4, 640]``, one 5 KB DMA, nothing
padded). The kernel body is one; only the trailing shape of the operand,
the buffer and the output block differs. The scalar core walks held picks
only: XLA sorts each block's picks (held first, token-major) into one
int32 a pick, ``token << 23 | slot << 20 | row``, and counts them.

Runs in interpreter mode off-TPU so tests exercise the same code path.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import _common

__all__ = ["sum_picks", "supported"]

_LANES = 128
_ROW_BITS, _SLOT_BITS = 20, 3       # one int32 a pick: token | slot | row
_TOKENS = 8                         # tokens summed at once: a sublane tile
_BUFFER_BYTES = 8 * 1024 * 1024     # both halves of the row buffer
_PICKS_BYTES = 768 * 1024           # the picks in SMEM, which holds 1 MiB


def _block_tokens(tokens, fan, hidden, itemsize):
    """Tokens a grid step: the most that divide ``tokens`` whose two
    ``[fan, block, hidden]`` buffers fit; 0 if none."""
    for tb in (128, 64, 32, 16, 8):
        if tokens % tb == 0 and 2 * fan * tb * hidden * itemsize \
                <= _BUFFER_BYTES:
            return tb
    return 0


def _row_shape(hidden, itemsize):
    """The shape in which a row of ``hidden`` elements travels, every
    tile of it the row's own and the tiles in the row's order; () if it
    is no whole number of 32-bit lane rows.

    Whole ``(8, 128) x 32-bit`` tiles (hidden a multiple of 1024
    four-byte or 2048 two-byte elements) go as ``(hidden / 128, 128)``.
    Any other width goes ``depth`` deep, the most of 4, 2, 1 lane rows of
    32 bits that divide the row's: XLA and Mosaic tile a second-minor
    dimension under eight at its own depth, so ``(depth, hidden /
    depth)`` is ``hidden / depth / 128`` tiles side by side, each 512
    bytes x depth, and a vector register holds ``8 / depth`` of them
    (2,560 two-byte elements: 10 lane rows, ``(4, 640)``, five tiles of
    1 KB, two registers a token where ``(5, 4, 128)`` would take five a
    quarter full)."""
    packed = 4 // itemsize              # elements a 32-bit lane
    lane_rows, odd = divmod(hidden, _LANES * packed)
    if odd:
        return ()
    if lane_rows % 8 == 0:
        return (hidden // _LANES, _LANES)
    depth = packed * next(d for d in (4, 2, 1) if lane_rows % d == 0)
    return (depth, hidden // depth)


def supported(o, where, fan) -> bool:
    """Rows of a whole number of 32-bit lane rows (hidden a multiple of
    128 four-byte or 256 two-byte elements), a row and a slot that fit
    their bits, tokens in blocks of eight, and no more picks than SMEM
    holds (196,608: the v5e's compiler refuses 262,144)."""
    if o.ndim != 2 or o.dtype.itemsize not in (2, 4):
        return False
    rows, hidden = o.shape
    tokens = where.shape[0] // fan
    return (bool(_row_shape(hidden, o.dtype.itemsize))
            and rows < 1 << _ROW_BITS and fan <= 1 << _SLOT_BITS
            and where.shape[0] * 4 <= _PICKS_BYTES
            and _block_tokens(tokens, fan, hidden, o.dtype.itemsize) > 0)


def _kernel(picks_ref, counts_ref, o_hbm, y_ref, buf, sems, *, fan, tb):
    i, blocks = pl.program_id(0), pl.num_programs(0)

    def fetch(block, slot):
        """Zero this half of the buffer, then ask for the block's held
        rows: a DMA each, all on the half's semaphore."""
        buf[slot] = jnp.zeros(buf.shape[1:], buf.dtype)
        base = block * (tb * fan)

        def pick(k, c):
            v = picks_ref[base + k]
            t = v >> (_ROW_BITS + _SLOT_BITS)
            j = (v >> _ROW_BITS) & ((1 << _SLOT_BITS) - 1)
            row = v & ((1 << _ROW_BITS) - 1)
            pltpu.make_async_copy(o_hbm.at[row], buf.at[slot, j, t],
                                  sems.at[slot]).start()
            return c
        jax.lax.fori_loop(0, counts_ref[block], pick, 0)

    @pl.when(i == 0)
    def _():
        fetch(0, 0)
    slot = i % 2

    @pl.when(i + 1 < blocks)
    def _():
        fetch(i + 1, 1 - slot)

    def wait(_, c):          # every row is one size: one wait a row
        pltpu.make_async_copy(o_hbm.at[0], buf.at[slot, 0, 0],
                              sems.at[slot]).wait()
        return c
    jax.lax.fori_loop(0, counts_ref[i], wait, 0)

    def add(g, c):
        at = pl.ds(pl.multiple_of(g * _TOKENS, _TOKENS), _TOKENS)
        acc = buf[slot, 0, at].astype(jnp.float32)
        for j in range(1, fan):
            acc = acc + buf[slot, j, at].astype(jnp.float32)
        y_ref[at] = acc.astype(y_ref.dtype)
        return c
    jax.lax.fori_loop(0, tb // _TOKENS, add, 0)


def _held_first(where, rows, fan, tb):
    """-> (one int32 a pick, each block's held picks first in token
    order; held picks a block)."""
    local = jnp.arange(tb * fan, dtype=jnp.int32)
    tag = ((local // fan) << (_ROW_BITS + _SLOT_BITS)
           | (local % fan) << _ROW_BITS)
    w = where.reshape(-1, tb * fan)
    held = w < rows
    picks = jax.lax.sort(jnp.where(held, tag | w, jnp.int32(2 ** 31 - 1)),
                         dimension=1, is_stable=False)
    return picks.reshape(-1), jnp.sum(held, axis=1, dtype=jnp.int32)


def sum_picks(o, where, fan):
    """``o`` [rows, hidden], ``where`` [tokens * fan] int32 (``rows``: no
    row) -> [tokens, hidden]: the float32 sum of each token's held rows,
    in ``o``'s dtype. ``supported(o, where, fan)`` must hold."""
    rows, hidden = o.shape
    tokens = where.shape[0] // fan
    tb = _block_tokens(tokens, fan, hidden, o.dtype.itemsize)
    row = _row_shape(hidden, o.dtype.itemsize)
    picks, counts = _held_first(where, rows, fan, tb)
    y = pl.pallas_call(
        functools.partial(_kernel, fan=fan, tb=tb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(tokens // tb,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((tb,) + row,
                                   lambda i, picks, counts: (i, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, fan, tb) + row, o.dtype),
                pltpu.SemaphoreType.DMA((2,)),
            ]),
        out_shape=jax.ShapeDtypeStruct((tokens,) + row, o.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=_common.interpret(),
        name="p1t_sum_picks_fwd",
    )(picks, counts, o.reshape((rows,) + row))
    return y.reshape(tokens, hidden)
