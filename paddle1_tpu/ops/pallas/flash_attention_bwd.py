"""Pallas flash-attention BACKWARD kernel, on the blocked grid of
flash_attention.py: one ``pallas_call`` makes a score tile's ``s``,
``p = exp(s - lse)``, ``dp = dout v^T`` and ``ds = p (dp - delta)`` once
and accumulates dQ, dK and dV from them (five score-shaped products and
one exponent a tile). Logits and probabilities never touch HBM, and the
blocks a mask rule hides cost nothing: three kinds of a tile, as the
forward's, **and a crossed one by sub-tile**. When a call is traced the
rule is asked again, with numpy, at :data:`_SUB` query rows x
:data:`_SUB` keys over every crossed tile of the call
(``mask_rules.subtile_patterns``): the sub-tiles' kinds fall into a few
layouts (the diagonal's under the causal rule; the diagonal's and the far
edge's under a window; the noisy copy's diagonal and the other
quadrants' under block diffusion), and each layout is straight code: a
strip of queries (lanes of the transposed scores, of LSE and of delta;
rows of q, dout and dQ) runs one pass over the contiguous keys (rows of k,
v, dK and dV) from its first needed sub-tile to its last, the rule's
element-wise keep on the sub-tiles it crosses alone, and a sub-tile
outside them runs nothing. A grid step finds its tile's layout by asking
``rule.tile`` of the one sub-tile or two that tell the layouts apart, on
the table's scalars (:func:`_run_tile`). A diagonal or far-edge tile
spares a quarter of its scores, a noisy-to-noisy tile of block diffusion
half (``flash_subtiles_total{kind}``, counted from the rule as the tiles
are). What is resident is sliced, nothing is added. (A loop over strips
that found its keys step by step was half again slower than the whole
tile on the v5e, every pass waiting for the one before; strips of 128
rows were no faster than of 256: PERF.md, PR 48.)

Grid (batch·key head, key range, query head of the group, needed pair):
the last axis walks the forward's table of needed (q-block, k-block)
pairs (``mask_rules.pair_table``, by scalar prefetch), query-major: a
q-block (with its dout, LSE and delta) stays resident while the k-blocks
it sees pass, as in the forward kernel, and dQ accumulates in a [BQ, D]
float32 scratch. **dK and dV of one key head stay resident in VMEM for
the whole of its sequence** ([Nk, D] + [Nk, Dv] float32, written at the
rows of the fetched chunk) while every q-block of every query head of
its group passes, and are stored once, after the last: no partial
gradient is written to HBM. The scores are computed transposed ([chunk of
keys, BQ]) so that dK, dV, ``s`` and ``dp`` are plain ``a @ b`` or ``a @
b.T`` and dQ alone contracts over the first axis of both operands, and so
that LSE and delta are read as the lane-dense [1, BQ] rows they arrive
as.

What is resident sets the scoped VMEM limit (:func:`_vmem_bytes`, a
reckoning from the shapes; the v5e has 128 MiB and the default is 16):
47.2 MiB at SDAR's 16,384 keys of 128 + 128, of which Mosaic uses 35.6
(the float32 dK and dV 16, the bf16 blocks they are stored through,
held twice by the pipeline, 16 more); 40.6 and 29.0 at Kanana-2's 8,192
x (192 + 128); 23.2 and 11.4 at Ouro's 4,096 (compiled for a described
v5e: ``tests/test_chip_compile.py``). A sequence whose gradients do not
fit :data:`_VMEM_CAP` takes the same kernel a key range at a time along
the grid's second axis: the range's rows resident, every q-block passing
once a range over a table of the range's own pairs, and dQ a float32
partial a range, summed in XLA. The ranges' tables are one length, so
the shorter end in steps that hold their last pair and run nothing
(``flash_grid_steps_total{kind="held"}``: 0 for every shape a model here
runs, which all take one range).

``delta = rowsum(dout * out)`` is computed in XLA (one fused reduction)
and arrives with the forward's LSE as [batch·head, 1, Nq] rows.
"""

from __future__ import annotations

import functools
import itertools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import _common
from .flash_attention import (_LANES, _NEG_INF, _NN, _NT, _count_kinds,
                              _count_pairs, _count_steps, _count_tiles,
                              _dot, _layout, _unlayout, block_sizes,
                              split_blocks)
from .flash_attention import _run_tile as _run_whole_tile
from .mask_rules import (FIRST, HELD, LAST, pair_table, subtile_counts,
                         subtile_patterns)

__all__ = ["flash_attention_bwd", "block_sizes"]

_TN = (((0,), (0,)), ((), ()))   # a.T @ b
# what one call may ask of the v5e's 128 MiB of VMEM: past it the keys
# are taken a range at a time
_VMEM_CAP = 96 << 20
_SUB = 256    # a crossed score tile is asked of its rule again in
              # sub-tiles of this many queries x this many keys
_LAYOUTS = 4  # a call whose crossed tiles lay their sub-tiles out in more
              # ways than this runs them whole: a layout is a body of code
# ``block_sizes`` (block_q, block_k, chunk: the resident query block, the
# key block fetched a grid step, the slice of it a pass of the body takes)
# is the forward's, from the same sweep and for its reasons; what it does
# not bound is the resident dK and dV: :func:`_vmem_bytes`.


def _vmem_bytes(span, bq, bk, chunk, d, dv, dtype):
    """The VMEM one call needs with ``span`` keys' gradients resident,
    reckoned from its shapes (a width takes whole 128-lane tiles, a
    [1, n] row 8 sublanes; what the pipeline fetches or writes back is
    held twice; a padding mask's column is counted whether there is one
    or not), and no less than the default 16 MiB."""
    size = jnp.dtype(dtype).itemsize
    wide = sum(-(-w // _LANES) * _LANES for w in (d, dv))
    resident = span * wide * 4                      # dk_acc, dv_acc
    written = 2 * span * wide * size                # the dK and dV blocks
    fetched = 2 * (bq + bk) * wide * size           # q, dout, k, v
    rows = 2 * 2 * 8 * bq * 4 + 2 * bk * _LANES * 4  # lse, delta; the mask
    query = bq * -(-d // _LANES) * _LANES * (3 * size + 4)  # qs, dQ, dq_acc
    tiles = 8 * chunk * bq * 4                      # s, p, dp, ds and casts
    need = resident + written + fetched + rows + query + tiles + (4 << 20)
    return max(need, 16 << 20)      # never under the compiler's default


def key_span(nk, bq, bk, chunk, d, dv, dtype) -> int:
    """The keys whose dK and dV one pass keeps resident: all ``nk`` where
    they fit :data:`_VMEM_CAP` (every shape a model here runs), else the
    largest halving of them that does and that whole fetched blocks
    make up."""
    span = nk
    while (_vmem_bytes(span, bq, bk, chunk, d, dv, dtype) > _VMEM_CAP
           and span % (2 * bk) == 0):
        span //= 2
    return span


def _run_tile(body, rule, q0, bq, k0, bk, off, layouts):
    """Call ``body(kind)`` as the kind of the score tile of queries [q0,
    q0+bq) x keys [k0, k0+bk) asks, or not at all: ``False`` for a tile
    the rule shows whole, and for a crossed one that one of ``layouts``
    (:func:`crossed_layouts`) which its sub-tiles match, or ``True``
    where there are none (the forward kernel's three kinds)."""
    if not layouts:
        return _run_whole_tile(body, rule, q0, bq, k0, bk, off)
    needed, full = rule.tile(q0, bq, k0, bk, off)   # some, every pair seen
    pl.when(full)(lambda: body(False))
    crossed = jnp.logical_and(needed, jnp.logical_not(full))
    sq, sk = bq // layouts[0].shape[0], bk // layouts[0].shape[1]
    probes = _probes(layouts)
    kinds = {}
    for j, t in probes:     # the rule again, at the sub-tile's grain
        some, every = rule.tile(q0 + j * sq, sq, k0 + t * sk, sk, off)
        kinds[j, t] = jnp.where(some, 1, 0) + jnp.where(every, 1, 0)
    for layout in layouts:
        match = crossed
        for j, t in probes:
            match = jnp.logical_and(match, kinds[j, t] == int(layout[j, t]))
        pl.when(match)(lambda layout=layout: body(layout))


def _probes(layouts):
    """The fewest sub-tiles (row, column) whose kinds tell ``layouts``
    apart (greedy): what a grid step asks its rule to find its own."""
    flat = np.stack([layout.reshape(-1) for layout in layouts])
    groups, chosen = [list(range(len(layouts)))], []
    while any(len(g) > 1 for g in groups):
        best = max(range(flat.shape[1]), key=lambda c: sum(
            len(set(flat[g, c])) for g in groups))
        chosen.append(divmod(best, layouts[0].shape[1]))
        groups = [[i for i in g if flat[i, best] == kind]
                  for g in groups for kind in sorted(set(flat[g, best]))]
    return chosen


def sub_grain(bq, chunk):
    """(query rows, keys) of a sub-tile of a crossed (bq x chunk) score
    tile; a side that :data:`_SUB` does not divide is taken whole."""
    return (bq if bq % _SUB else _SUB), (chunk if chunk % _SUB else _SUB)


def crossed_layouts(rule, nq, nk, bq, chunk):
    """How a call runs its crossed (bq x chunk) tiles: None for whole
    (a tile of one sub-tile; more layouts than :data:`_LAYOUTS`), else
    the layouts of sub-tile kinds that ``mask_rules.subtile_patterns``
    finds over them, each of which becomes straight code over the
    sub-tiles it runs."""
    sub = sub_grain(bq, chunk)
    layouts = sub != (bq, chunk) and subtile_patterns(rule, nq, nk, bq,
                                                      chunk, *sub)
    return layouts if layouts and len(layouts) <= _LAYOUTS else None


def _strips(layout):
    """(strip, first needed sub-tile, one past the last, their kinds) of
    every strip of query rows of ``layout`` that runs anything: one pass
    over the contiguous keys between."""
    for j, row in enumerate(layout):
        cols = np.flatnonzero(row)
        if cols.size:
            yield (j, int(cols[0]), int(cols[-1]) + 1,
                   row[cols[0]:cols[-1] + 1])


def _keep_crossed(rule, s, kinds, sk, q0, k0, off):
    """The transposed scores ``s`` of a strip's pass (keys from ``k0`` in
    sub-tiles of ``sk`` rows, of ``kinds``; queries from ``q0`` along the
    lanes) with the rule's keep on the sub-tiles it does not show
    whole."""
    at, pieces = 0, []
    for plain, run in itertools.groupby(kinds, lambda kind: kind == 2):
        n = len(list(run)) * sk
        piece = s[at:at + n]
        if not plain:
            piece = jnp.where(rule.keep(piece.shape, q0, k0 + at, off, 1),
                              piece, _NEG_INF)
        pieces.append(piece)
        at += n
    return pieces[0] if len(pieces) == 1 else jnp.concatenate(pieces)


def _count_subtiles(rule, nq, nk, bq, bk, layouts, calls):
    """``flash_subtiles_total{kind}``: the sub-tiles of the crossed score
    tiles alone of one lowered backward call x its ``calls`` (batch x
    heads), ``spared`` those the kernel does not run (a call that runs
    its crossed tiles whole, without ``layouts``, has one sub-tile a
    tile)."""
    sub = sub_grain(bq, bk) if layouts else (bq, bk)
    _count_kinds("flash_subtiles_total",
                 subtile_counts(rule, nq, nk, bq, bk, *sub), calls)


def _bwd_kernel(*refs, scale, rule, off, chunk, has_mask, span, held,
                layouts):
    # qb_ref/kb_ref/mark_ref: a range's pairs and marks (``pair_table``);
    # q_ref [BQ, D], do_ref [BQ, Dv], lse_ref/delta_ref [1, BQ] (resident);
    # k_ref [BK, D], v_ref [BK, Dv]; mask_ref [BK, 1]; dk_ref [span, D],
    # dv_ref [span, Dv] with their float32 accumulators
    qb_ref, kb_ref, mark_ref = refs[:3]
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref = refs[3:9]
    mask_ref = refs[9] if has_mask else None
    dq_ref, dk_ref, dv_ref, qs_ref, dq_acc, dk_acc, dv_acc = \
        refs[9 + has_mask:]
    # a group's query heads pass one after the other, each over the pairs
    r, head, step = pl.program_id(1), pl.program_id(2), pl.program_id(3)
    steps = pl.num_programs(3)
    bq = q_ref.shape[0]
    bk = k_ref.shape[0]
    entry = r * steps + step
    i, at, mark = qb_ref[entry], kb_ref[entry], mark_ref[entry]
    base = r * span             # the first key of this step's range
    first = mark & FIRST != 0
    last = mark & LAST != 0

    def rows_of(n):
        return pl.ds(pl.multiple_of(n * chunk, chunk), chunk)

    @pl.when((step == 0) & (head == 0))
    def _():
        def zero(n, _):
            dk_acc[rows_of(n), :] = jnp.zeros((chunk, dk_acc.shape[1]),
                                              jnp.float32)
            dv_acc[rows_of(n), :] = jnp.zeros((chunk, dv_acc.shape[1]),
                                              jnp.float32)
        jax.lax.fori_loop(0, span // chunk, zero, None)

    @pl.when(first)
    def _():
        qs_ref[...] = (q_ref[...] * scale).astype(qs_ref.dtype)
        dq_acc[...] = jnp.zeros_like(dq_acc)

    def grads(rows, lanes, first, size, keep):
        # queries ``rows`` of the resident block (``lanes`` of its LSE
        # and delta) x ``size`` keys from ``first`` of the fetched one;
        # ``keep(s)``: the scores under the rule, in a crossed tile
        ks = pl.ds(first, size)
        k, v = k_ref[ks, :], v_ref[ks, :]
        q, do = q_ref[rows], do_ref[rows]
        s = _dot(k, qs_ref[rows], _NT)                       # [keys, rows]
        if has_mask:
            s = jnp.where(mask_ref[ks, :] > 0.5, s, _NEG_INF)
        if keep:
            s = keep(s)
        # lse is +inf for fully-masked rows (remapped by the wrapper):
        # p underflows to an exact 0 there
        p = jnp.exp(s - lse_ref[lanes])
        dp = _dot(v, do, _NT)
        ds = (p * (dp - delta_ref[lanes])).astype(q.dtype)
        here = pl.ds(pl.multiple_of(at * bk + first - base,
                                    math.gcd(first, size, chunk)), size)
        dv_acc[here, :] += _dot(p.astype(do.dtype), do, _NN)
        dk_acc[here, :] += _dot(ds, q, _NN)
        dq_acc[rows] += _dot(ds, k, _TN)

    def one(c, kind):
        if isinstance(kind, np.ndarray):
            return by_sub_tile(c, kind)
        keep = kind and (lambda s: jnp.where(rule.keep(
            s.shape, i * bq, at * bk + c * chunk, off, 1), s, _NEG_INF))
        grads(..., ..., c * chunk, chunk, keep)

    def by_sub_tile(c, layout):
        # a crossed tile: a strip of queries is lanes of the transposed
        # scores, of LSE and of delta and rows of q, dout and dQ; it runs
        # one pass over the keys of its needed sub-tiles
        sq, sk = bq // layout.shape[0], chunk // layout.shape[1]
        for j, lo, hi, kinds in _strips(layout):
            first = c * chunk + lo * sk
            keep = functools.partial(
                _keep_crossed, rule, kinds=kinds, sk=sk, q0=i * bq + j * sq,
                k0=at * bk + first, off=off)
            grads((pl.ds(j * sq, sq), slice(None)),
                  (slice(None), pl.ds(j * sq, sq)), first, (hi - lo) * sk,
                  keep)

    def tiles():
        for c in range(bk // chunk):
            _run_tile(functools.partial(one, c), rule, i * bq, bq,
                      at * bk + c * chunk, chunk, off, layouts)
    if held:        # only a table in key ranges has steps that run nothing
        pl.when(mark & HELD == 0)(tiles)
    else:
        tiles()

    @pl.when(last)
    def _():
        dq_ref[...] = (dq_acc[...] * scale).astype(dq_ref.dtype)

    @pl.when((step == steps - 1) & (head == pl.num_programs(2) - 1))
    def _():
        def store(n, _):
            dk_ref[rows_of(n), :] = (dk_acc[rows_of(n), :]
                                     * scale).astype(dk_ref.dtype)
            dv_ref[rows_of(n), :] = dv_acc[rows_of(n), :].astype(
                dv_ref.dtype)
        jax.lax.fori_loop(0, span // chunk, store, None)


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
    # blocks: (block_q, block_k, chunk) and, for the tests, the keys
    # resident at a time as a fourth
    bq, bk, chunk, *span = split_blocks(blocks)[1] or block_sizes(
        *rule.sizes(nq, nk), max(d, dv), q.dtype)
    span, = span or (key_span(nk, bq, bk, chunk, d, dv, q.dtype),)
    ranges = nk // span
    has_mask = padding_mask is not None
    qa, at = _layout(q)
    ka, at_k = _layout(k)
    va, at_v = _layout(v)       # v and dV: the value width's layout
    doa, at_do = _layout(dout)  # dout: q's heads, v's width

    # delta = rowsum(dout * out): one fused XLA reduction
    delta = jnp.sum(dout.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)                                  # [B, Nq, H]
    delta = delta.transpose(0, 2, 1).reshape(b * h, 1, nq)
    # fully-padded rows carry the forward's FINITE sentinel LSE; remap to
    # +inf so exp(s - lse) is an exact 0 for every key (exp(s - (-1e30))
    # would be exp(0) = 1, garbage grads)
    lse = lse.reshape(b * h, 1, nq).astype(jnp.float32)
    lse = jnp.where(lse > _NEG_INF * 0.1, lse, jnp.inf)

    # key head g of the grid takes query head t of its group over the
    # range's needed (query block, key block) pairs: the windows follow
    # the table's columns
    table = pair_table(rule, nq, nk, bq, bk, ranges)
    steps = table.steps
    if group == 1:
        head = lambda g, t: g
    else:
        head = lambda g, t: g // h_kv * h + g % h_kv * group + t
    layouts = crossed_layouts(rule, nq, nk, bq, chunk)
    _count_tiles(rule, nq, nk, bq, chunk, b * h)
    _count_subtiles(rule, nq, nk, bq, chunk, layouts, b * h)
    _count_steps(table, b * h)
    _count_pairs(rule, nq, nk, b * h)
    _count_ranges(ranges)

    def spec(shape, index):
        """A window placed by (key head, query head, query block, key
        block, key range) of a grid step."""
        return pl.BlockSpec(shape, lambda g, r, t, s, qb, kb, _: index(
            g, head(g, t), qb[r * steps + s], kb[r * steps + s], r))
    rows = spec((None, bq, d), lambda g, hq, i, j, r: at(hq, i))
    douts = spec((None, bq, dv), lambda g, hq, i, j, r: at_do(hq, i))
    keys = spec((None, bk, d), lambda g, hq, i, j, r: at_k(g, j))
    values = spec((None, bk, dv), lambda g, hq, i, j, r: at_v(g, j))
    stat = spec((None, 1, bq), lambda g, hq, i, j, r: (hq, 0, i))
    in_specs = [rows, keys, values, douts, stat, stat]
    args = [qa, ka, va, doa, lse, delta]
    if has_mask:
        in_specs.append(spec(
            (None, bk, 1), lambda g, hq, i, j, r: (g // h_kv, j, 0)))
        args.append(padding_mask.astype(jnp.float32).reshape(b, nk, 1))
    dq, dk, dv_out = pl.pallas_call(
        functools.partial(
            _bwd_kernel, scale=scale, rule=rule, off=off, chunk=chunk,
            has_mask=has_mask, span=span, held=table.held > 0,
            layouts=layouts),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b * h_kv, ranges, group, steps),
            in_specs=in_specs,
            out_specs=[
                spec((None, None, bq, d),
                     lambda g, hq, i, j, r: (r,) + at(hq, i)),
                spec((None, span, d), lambda g, hq, i, j, r: at_k(g, r)),
                spec((None, span, dv), lambda g, hq, i, j, r: at_v(g, r)),
            ],
            scratch_shapes=[pltpu.VMEM((bq, d), q.dtype),
                            pltpu.VMEM((bq, d), jnp.float32),
                            pltpu.VMEM((span, d), jnp.float32),
                            pltpu.VMEM((span, dv), jnp.float32)]),
        out_shape=[
            # a partial a range is summed below: float32 until then
            jax.ShapeDtypeStruct((ranges,) + qa.shape,
                                 q.dtype if ranges == 1 else jnp.float32),
            jax.ShapeDtypeStruct(ka.shape, k.dtype),
            jax.ShapeDtypeStruct(va.shape, v.dtype),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary",
                                 "arbitrary"),
            vmem_limit_bytes=_vmem_bytes(span, bq, bk, chunk, d, dv,
                                         q.dtype)),
        # the name the dK/dV kernel had: the benchmark's reducers find the
        # backward's time by it (PERF.md section 7)
        name="p1t_flash_attention_bwd_dkv",
        interpret=interpret,
    )(table.q, table.k, table.mark, *args)
    dq = dq[0] if ranges == 1 else jnp.sum(dq, axis=0).astype(q.dtype)
    return (_unlayout(dq, b, h, d), _unlayout(dk, b, h_kv, d),
            _unlayout(dv_out, b, h_kv, dv))


def _count_ranges(ranges):
    """``flash_backward_ranges_total``: the key ranges of each traced
    backward call (1 where the resident gradients fit)."""
    from ...obs.registry import process_registry
    process_registry().counter("flash_backward_ranges_total").inc(ranges)
