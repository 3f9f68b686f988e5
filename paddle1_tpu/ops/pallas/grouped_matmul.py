"""A grouped matrix product whose tiles fit the widths it is given: the
expert layer's two products (``nn/layer_moe.py::grouped_matmul``) where
XLA's own grouped kernel would tile a width at 128; no reference analog.

``rows`` [m, k] are sorted by group, ``sizes`` [g] says how many each of
the ``g`` groups has, ``weights`` [g, k, n] holds a matrix a group: the
first ``sizes[0]`` rows go through ``weights[0]`` and so on -> [m, n], as
``jax.lax.ragged_dot`` gives (operands of one dtype, bf16 or float32;
float32 accumulation; the operands' dtype out). Rows past the groups are
not used and what stands in their output is not defined.

The TPU's compiler lowers ``lax.ragged_dot`` to a kernel of its own that
walks the (row tile of 512, group) pairs that hold a row, each width tiled
at :func:`xla_tile` of it: the largest power of two up to 512 that divides
the width, 128 where none of 128 does. At 512 x 512 x 512 that kernel runs
at the matrix unit's peak a pair; at Nemotron 3 Nano's 2688 x 1856 both
widths fall to 128, a pair is 21 x 15 grid steps of 17 MFLOP, and the
step's own overhead sets the time: 1.9 to 2.5 ms a call where the pairs'
work is 0.4 (PERF.md section 6, PR 51). :func:`supported` is true exactly
where XLA would tile a width under 256.

The same algorithm here with the tiles read off the shape
(:func:`_tiles`): k and n whole where the blocks fit the VMEM the call
asks for, else the largest multiples of 128 that divide them; the row tile
256, so that a weight block fetched once is used by 256 rows (the v5e's
ridge is 240 FLOP a byte). The table of visited pairs (:func:`_table`: each
step's group and row tile, and how many steps hold a row) is made on the
device from ``sizes`` and handed in by scalar prefetch. The grid has the
most steps a fill can need (``m / tm + g - 1``); **a step past the last
pair names the last pair's blocks again, so it fetches nothing, and
computes nothing**: the kernel's time follows ``sum(sizes)``, not ``m``.
With a width whole, a group's weight block keeps its index over the
group's consecutive row tiles and is fetched once a group. A row tile that
two groups share is visited once for each, the other's rows masked on the
store.

Three kernels under one ``jax.custom_vjp`` (:func:`grouped_matmul`):
``p1t_grouped_matmul_fwd`` (the product), ``p1t_grouped_matmul_bwd_dx`` (``dout``
[m, n] through each group's weights transposed -> [m, k]: the same kernel
body, the weight block read as it lies and contracted over its second
axis) and ``p1t_grouped_matmul_bwd_dw`` (``rows^T x dout`` a group -> [g, k,
n]: the rows of a visited tile that are not the group's are zeroed in both
operands, so a NaN past the groups stays out; a group without rows is
visited once and written zeros).

Runs in interpreter mode off-TPU so tests exercise the same code path.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import _common

__all__ = ["grouped_matmul", "supported", "xla_tile"]

F32 = jnp.float32
_LANES = 128
_ROW_TILE = 256                 # rows a weight block is used by
_VMEM_BLOCKS = 40 << 20         # what a call's blocks may take
_VMEM_SLACK = 16 << 20          # Mosaic's own temporaries beside them
_NN = (((1,), (0,)), ((), ()))  # a @ b
_NT = (((1,), (1,)), ((), ()))  # a @ b.T
_TN = (((0,), (0,)), ((), ()))  # a.T @ b


def xla_tile(width: int) -> int:
    """The tile XLA's grouped kernel gives a width: the largest power of
    two up to 512 that divides it, 128 (a last block part full) where
    none of 128 does. ``tests/test_chip_compile.py`` holds it to the
    ``ragged_dot_tiling`` of the compiled ``lax.ragged_dot``."""
    return next((t for t in (512, 256, 128) if width % t == 0), 128)


def _widths(width):
    """A width's tiles, widest first: itself, then the multiples of 128
    that divide it."""
    return [width] + [t for t in range(_LANES, width, _LANES)
                      if width % t == 0][::-1]


def _block_bytes(tm, tk, tn, itemsize, dw):
    """VMEM of a call's blocks: every operand and output block twice (the
    pipeline's two buffers), the float32 accumulator and one product
    beside it."""
    lhs, rhs, out = ((tm * tk, tm * tn, tk * tn) if dw
                     else (tm * tk, tk * tn, tm * tn))
    return 2 * (lhs + rhs + out) * itemsize + 2 * out * 4


def _tiles(m, k, n, itemsize, dw=False):
    """(tm, tk, tn) of a product ``[m, k] x [g, k, n]`` (``dw``: of
    ``[m, k]^T x [m, n]`` a group), from the shape alone, None where none
    fits: the row tile :data:`_ROW_TILE`, which has to divide ``m``; k and
    n whole where the blocks fit :data:`_VMEM_BLOCKS`, else the widest
    pair of their tiles that does; of two pairs as wide the one with
    more of the contraction whole (a weight block that spans k keeps its
    index from one row tile of a group to the next and is not fetched
    again)."""
    tm = _ROW_TILE
    if m % tm:
        return None
    pairs = sorted(((tk, tn) for tk in _widths(k) for tn in _widths(n)),
                   key=lambda p: (p[0] * p[1], p[0]), reverse=True)
    for tk, tn in pairs:
        if _block_bytes(tm, tk, tn, itemsize, dw) <= _VMEM_BLOCKS:
            return tm, tk, tn
    return None


def supported(rows, weights) -> bool:
    """Whether :func:`grouped_matmul` is the arm for these operands: XLA's
    own kernel would tile a width under 256 (``min(xla_tile(k),
    xla_tile(n)) < 256``) and has tiles to run by the dozen (both widths
    at least 256: a narrower one is two of XLA's tiles at most, as 1024
    is at 512), and the kernels here take them: ``rows`` [m, k] and
    ``weights`` [g, k, n] of one dtype, bf16 or float32, widths of whole
    half lane tiles (a multiple of 64), rows in whole row tiles, and
    tiles that fit for all three forms."""
    if rows.ndim != 2 or weights.ndim != 3 or rows.dtype != weights.dtype:
        return False
    if rows.dtype not in (jnp.bfloat16, jnp.float32):
        return False
    (m, k), (g, k2, n) = rows.shape, weights.shape
    if (k != k2 or min(xla_tile(k), xla_tile(n)) >= 256
            or min(k, n) < 2 * _LANES or k % 64 or n % 64):
        return False
    size = rows.dtype.itemsize
    return all((_tiles(m, k, n, size), _tiles(m, n, k, size),
                _tiles(m, k, n, size, dw=True)))


def _table(sizes, m, tm, every_group):
    """The (group, row tile) pairs a fill visits, in the rows' order ->
    (``bounds`` [g + 1]: each group's first row, and the last's end;
    ``group`` and ``tile`` [steps]: of each step; ``live`` [1]: steps that
    hold a row). ``steps = m / tm + g - 1`` is the most a fill can take
    (every boundary inside a tile); a step past ``live`` repeats the last
    live one. ``every_group``: a group without rows takes one step all
    the same (its ``dw`` is to be written)."""
    g, tiles_m = sizes.shape[0], m // tm
    sizes = sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    first = starts // tm
    tiles = jnp.where(sizes > 0, (ends - 1) // tm - first + 1,
                      int(every_group))
    stop = jnp.cumsum(tiles)
    live = stop[-1]
    step = jnp.minimum(jnp.arange(tiles_m + g - 1, dtype=jnp.int32),
                       jnp.maximum(live - 1, 0))
    group = jnp.minimum(
        jnp.sum(stop[None, :] <= step[:, None], axis=1, dtype=jnp.int32),
        g - 1)
    tile = jnp.minimum(first[group] + step - (stop - tiles)[group],
                       tiles_m - 1)
    bounds = jnp.concatenate([starts, ends[-1:]])
    return bounds, group, tile, live.reshape(1)


def _mine(bounds, group, tile, s, tm):
    """[tm, 1]: the rows of step ``s``'s tile that are its group's."""
    g = group[s]
    row = tile[s] * tm + jax.lax.broadcasted_iota(jnp.int32, (tm, 1), 0)
    return (row >= bounds[g]) & (row < bounds[g + 1])


def _dot(a, b, dims):
    precision = None if a.dtype == F32 else jax.lax.Precision.DEFAULT
    return jax.lax.dot_general(a, b, dims, precision=precision,
                               preferred_element_type=F32)


def _product_kernel(bounds, group, tile, live, x_ref, w_ref, o_ref, *acc,
                    tm, k_tiles, dims):
    s, kk = pl.program_id(1), pl.program_id(2)

    def store(total):
        # the other group's rows of a shared tile stay as they stand
        o_ref[...] = jnp.where(_mine(bounds, group, tile, s, tm),
                               total.astype(o_ref.dtype), o_ref[...])

    @pl.when(s < live[0])
    def _():
        part = _dot(x_ref[...], w_ref[...], dims)
        if k_tiles == 1:
            store(part)
            return
        acc_ref, = acc

        @pl.when(kk == 0)
        def _():
            acc_ref[...] = part

        @pl.when(kk > 0)
        def _():
            acc_ref[...] += part

        @pl.when(kk == k_tiles - 1)
        def _():
            store(acc_ref[...])


def _product(x, w, sizes, transposed):
    """``x`` [m, k] through each group's ``w[g]`` [k, n] (``transposed``:
    ``w[g]`` [n, k], contracted over its second axis) -> [m, n]."""
    m, k = x.shape
    g, n = w.shape[0], w.shape[1 if transposed else 2]
    tm, tk, tn = _tiles(m, k, n, x.dtype.itemsize)
    k_tiles = k // tk
    bounds, group, tile, live = _table(sizes, m, tm, every_group=False)

    def k_of(s, kk, live):      # a step past the pairs: the last block
        return jnp.where(s < live[0], kk, k_tiles - 1)

    def w_index(j, s, kk, bounds, group, tile, live):
        kk = k_of(s, kk, live)
        return (group[s], j, kk) if transposed else (group[s], kk, j)
    return pl.pallas_call(
        functools.partial(_product_kernel, tm=tm, k_tiles=k_tiles,
                          dims=_NT if transposed else _NN),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(n // tn, m // tm + g - 1, k_tiles),
            in_specs=[
                pl.BlockSpec((tm, tk), lambda j, s, kk, bounds, group, tile,
                             live: (tile[s], k_of(s, kk, live))),
                pl.BlockSpec((None, tn, tk) if transposed
                             else (None, tk, tn), w_index)],
            out_specs=pl.BlockSpec(
                (tm, tn),
                lambda j, s, kk, bounds, group, tile, live: (tile[s], j)),
            scratch_shapes=([pltpu.VMEM((tm, tn), F32)] if k_tiles > 1
                            else [])),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_block_bytes(tm, tk, tn, x.dtype.itemsize,
                                          False) + _VMEM_SLACK),
        interpret=_common.interpret(),
        # each a literal ``name="p1t_..."``: the tests find the tree's
        # kernels by it
        **(dict(name="p1t_grouped_matmul_bwd_dx") if transposed
           else dict(name="p1t_grouped_matmul_fwd")),
    )(bounds, group, tile, live, x, w)


def _dw_kernel(bounds, group, tile, live, x_ref, d_ref, o_ref, acc_ref, *,
               tm):
    s, steps = pl.program_id(2), pl.num_programs(2)
    g = group[s]
    opens = (s == 0) | (group[jnp.maximum(s - 1, 0)] != g)
    closes = (s == live[0] - 1) | (group[jnp.minimum(s + 1, steps - 1)] != g)

    @pl.when(s < live[0])
    def _():
        mine = _mine(bounds, group, tile, s, tm)
        part = _dot(jnp.where(mine, x_ref[...], 0),
                    jnp.where(mine, d_ref[...], 0), _TN)

        @pl.when(opens)
        def _():
            acc_ref[...] = part

        @pl.when(jnp.logical_not(opens))
        def _():
            acc_ref[...] += part

        @pl.when(closes)
        def _():
            o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _dw(x, d, sizes):
    """``x`` [m, k], ``d`` [m, n] -> [g, k, n]: ``x^T d`` over each
    group's rows, in the operands' dtype."""
    (m, k), n, g = x.shape, d.shape[1], sizes.shape[0]
    tm, tk, tn = _tiles(m, k, n, x.dtype.itemsize, dw=True)
    bounds, group, tile, live = _table(sizes, m, tm, every_group=True)
    return pl.pallas_call(
        functools.partial(_dw_kernel, tm=tm),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(k // tk, n // tn, m // tm + g - 1),
            in_specs=[
                pl.BlockSpec((tm, tk), lambda i, j, s, bounds, group, tile,
                             live: (tile[s], i)),
                pl.BlockSpec((tm, tn), lambda i, j, s, bounds, group, tile,
                             live: (tile[s], j))],
            out_specs=pl.BlockSpec(
                (None, tk, tn),
                lambda i, j, s, bounds, group, tile, live: (group[s], i, j)),
            scratch_shapes=[pltpu.VMEM((tk, tn), F32)]),
        out_shape=jax.ShapeDtypeStruct((g, k, n), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_block_bytes(tm, tk, tn, x.dtype.itemsize,
                                          True) + _VMEM_SLACK),
        interpret=_common.interpret(),
        name="p1t_grouped_matmul_bwd_dw",
    )(bounds, group, tile, live, x, d)


@jax.custom_vjp
def grouped_matmul(rows, weights, sizes):
    """``rows`` [m, k] x ``weights`` [g, k, n] by ``sizes`` [g] -> [m, n];
    ``supported(rows, weights)`` must hold."""
    return _product(rows, weights, sizes, transposed=False)


def _fwd(rows, weights, sizes):
    return (_product(rows, weights, sizes, transposed=False),
            (rows, weights, sizes))


def _bwd(res, dout):
    rows, weights, sizes = res
    return (_product(dout, weights, sizes, transposed=True),
            _dw(rows, dout, sizes), None)


grouped_matmul.defvjp(_fwd, _bwd)
