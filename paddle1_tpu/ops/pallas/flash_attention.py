"""Blockwise attention Pallas kernels (TPU MXU/VMEM-native fused attention).

Replaces the reference's fused multihead attention CUDA kernels
(/root/reference/paddle/fluid/operators/fused/ attention ops) with the
TPU idiom: online-softmax blocking in VMEM, logits never in HBM.

Layout: [B, N, H, D] (paddle layout, matching nn.functional.attention).
This file holds the forward kernel and the entry point; the one
backward kernel (dQ, dK and dV from a score tile made once) is in
flash_attention_bwd.py. Both walk, a head, **the needed (query block,
key block) pairs and no other step**, query-major: one sequential grid
axis over a small int32 table (``mask_rules.pair_table``, made with
numpy from the mask rule when a call is traced) that reaches the index
maps and the bodies by scalar prefetch. A step's q / out windows follow
the table's query block, its k / v windows the key block, and two marks
say where a query block opens (set up what is resident with it) and
closes (write it out). A query block stays resident while the key blocks
it sees pass, accumulators in VMEM scratch, and while its last pair runs
the pipeline already fetches the next query block's operands: no step
runs nothing, so none leaves that fetch with nothing to hide behind (the
rectangular grid this replaced, query blocks x the hungriest one's key
steps, spent 38-47% of its steps so: PERF.md, PR 39). **Resident**: the
forward keeps a query block with its running max, sum and output
([BQ, D]; the row statistics below); the backward a query block with its
dout, LSE, delta and dQ,
and besides, for every query block and query head of its group, one key
head's whole dK and dV in float32 ([Nk, D] + [Nk, Dv]: 8 MiB at 4096
keys of 128, 32 at SDAR's 16,384, with the blocks they are written back
through), for which it raises its own scoped-VMEM limit. A grid step
takes its fetched block a chunk at a time, and under a mask rule
(``mask_rules.py``: bottom-right causal, query ``r`` sees keys ``<= r +
nk - nq``, or block diffusion's mask over a noisy and a clean copy of a
row) a score tile (resident block x chunk) is one of **three kinds, and
the backward kernel runs a crossed one by sub-tile**, by ``rule.tile`` on
the table's scalars:

* wholly hidden (a chunk of a needed block that the query block does not
  see): no work; a block of such is not in the table;
* wholly visible: the plain body, no iota, no select;
* crossed: the body with the rule's element-wise keep. The backward
  kernel asks the rule again inside such a tile and runs the sub-tiles it
  lets through alone (``flash_attention_bwd.crossed_layouts``); this
  kernel runs it whole, because its time goes with the rows a pass
  updates (a row's maximum across 128 lanes, its rescaled sum and
  accumulator), not with the scores it computes: by sub-tile it computed
  25-37% fewer of a crossed tile's scores in the same time at 256 rows a
  strip, and was slower at 128 (PERF.md, PR 48).

**A row's statistics** live in VMEM across one 128-lane register row,
``[BQ, 128]``: its running max replicated (a ``[BQ, 1]`` column would be
masked stores a pass), its running sum as **128 per-lane partial sums**.
The rescaling factor is equal across a row's lanes, so a pass adds the
128-lane slices of its probabilities to the lanes (VALU adds) and the
one reduction across lanes is made once a query block, at its ``LAST``
mark, where the replicated sum paid one a pass beside the maximum's: 1-2%
of a forward call at the cells' shapes (where a query block sees one
chunk, as [32, 128, 12, 64] without a mask, nothing hides the reduction
at ``LAST`` and a lone call reads 12% more: PERF.md, PR 49). The lanes
are a register convenience that stops at the edge of VMEM: what leaves
the kernel is the LSE as the lane-dense ``[B*H, 1, Nq]`` float32 row the
backward kernel reads (its ``stat`` block, ``[1, BQ]`` a step), 4 bytes a
(head, query), written once a query block through one transpose of the
``[BQ, 128]`` tile; ``_flash_fwd`` hands it on as ``[B*H, Nq]``. Written
as it is held, ``[B*H, Nq, 128]``, it was 512 bytes for every 4 that were
read and the largest thing the kernel moved under a short window (537 MB
a call beside q's and out's 268 at Laguna's 64 heads of 16,384 queries),
and XLA relaid the whole array out to take its first lane: 7.1 ms a
Laguna step, 0.8 to 4 in the other cells (PERF.md, PR 49;
``flash_stat_bytes_total{kind="lse"}`` counts the bytes).

k and v may have fewer heads than q (grouped-query attention: ``H_kv``
divides ``H``): the forward kernel reads key/value head ``h // (H /
H_kv)`` through the index map, the backward kernel's grid runs over the
key heads and passes a group's query heads one after the other, each
over the table, while that key head's dK and dV stay resident, and
writes ``H_kv`` heads. No copy of k or v per query head is made anywhere.

Precision is the caller's: the products take q/k/v/dout in the dtype
they arrive in (bf16 under AMP) and accumulate in float32; max, sum and
LSE are float32; the probabilities (and ``ds`` in the backward) are
rounded to the operand dtype once, before their products. The softmax
scale is folded into q once a resident block (and into dQ and dK as they
are stored).

q and k share one head width; v has its own (latent attention: keys
192 wide, values 128), which ``out``, ``dout``, ``delta`` and dV follow
while the scores, dQ and dK follow q / k. Each operand takes the layout
of its own width: where it is a multiple of 128 the kernels read and
write ``[B, N, H*D]`` with a ``(block, D)`` window at column ``h*D`` (a
free reshape of the paddle layout); otherwise the operand is transposed
to ``[B*H, N, D]`` in XLA on the way in and out (a 192-wide window would
straddle a 128-lane tile on every other head). Runs in interpreter mode
off-TPU so tests exercise the same code path.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...core.recompute_keeps import keep_in_recompute
from . import _common
from .mask_rules import (CAUSAL, FIRST, LAST, NO_MASK, pair_table,
                         tile_counts)

_LANES = 128  # Mosaic minor-dim tile: per-row statistics are kept
              # across one 128-lane register row
_NEG_INF = -1e30
_NT = (((1,), (1,)), ((), ()))   # a @ b.T
_NN = (((1,), (0,)), ((), ()))   # a @ b


def rule_of(causal: bool, mask):
    """The one mask rule of a call: ``mask`` (``mask_rules``), the causal
    rule where ``causal``, the rule that hides nothing where neither."""
    if causal and mask is not None and mask != CAUSAL:
        raise ValueError("causal=True beside another mask rule")
    return CAUSAL if causal else NO_MASK if mask is None else mask


def supported(q_shape, k_shape, causal: bool = False,
              v_shape=None, mask=None) -> bool:
    """Tile-aligned shapes only; everything else uses attention_ref.
    No VMEM gate: the forward keeps no more than a block of any operand,
    and the backward, which keeps a key head's dK and dV resident, takes
    the keys a range at a time where they would not fit
    (``flash_attention_bwd.key_span``).
    Assumes that q and k share one head width and that v (``v_shape``,
    k's where None) has one of its own, each a multiple of 8 up to 256;
    k's heads divide q's. ``mask``: a rule of ``mask_rules``; a rule
    whose alignment would leave a query with no key (bottom-right causal
    with more queries than keys: the zero-sumexp sentinel would poison
    the vjp) is attention_ref's."""
    if len(q_shape) != 4 or len(k_shape) != 4:
        return False
    _, nq, h, d = q_shape
    _, nk, h_kv, _ = k_shape
    dv = d if v_shape is None else v_shape[-1]
    if dv % 8 or dv > 256:
        return False
    if nq % _LANES or nk % _LANES:
        return False
    if h % h_kv or (v_shape is not None and v_shape[2] != h_kv):
        return False
    rule = rule_of(causal, mask)
    if not (rule.lengths_ok(nq, nk)
            and all(n % _LANES == 0 for n in rule.sizes(nq, nk))):
        return False
    if d % 8 or d > 256:
        return False
    return True


def block_sizes(nq: int, nk: int, d: int, dtype) -> tuple:
    """(block_q, block_k, chunk): the resident query block, the key
    block fetched a grid step, and the slice of it that one pass of the
    body takes. ``d`` is the wider of the key and
    the value head widths: the VMEM budget below is the widest operand's.
    The largest rungs that divide
    the lengths, of ladders set from the sweep on the v5e
    (tools/tpu_flash_crossover.py, PERF.md PR 28) at [2, 4096, 16, 128]
    bf16 causal. A 512 x 512 float32 score tile is what a pass handles
    best (256 is 25% slower, 1024 no faster). A grid step costs about
    0.2 us whatever it does, so a longer fetched block is faster (512 /
    1024 / 2048 rows: 1.88 / 1.76 / 1.64 ms a forward call), but every
    chunk of it is unrolled code: at 2048 rows the 96 kernel instances
    of Ouro's step make its executable 137.0 MiB in the compile cache
    against 129.7 at 1024 (the parent's dense step: 130.7), which a
    capped cache then fails to hold beside the other programs of a run.
    And no more than 512 KiB an operand: the forward fetches two, double
    buffered, beside a resident block, its output and the score tiles in
    the default 16 MiB of scoped VMEM (the backward, which takes these
    sizes too, sets its own limit: ``flash_attention_bwd._vmem_bytes``)."""
    rows = (512 << 10) // (d * jnp.dtype(dtype).itemsize)
    fetched = tuple(b for b in (1024, 512, 256, 128) if b <= rows)
    return (_rung(nq, (512, 256, 128)), _rung(nk, fetched),
            _rung(nk, (512, 256, 128)))


def _rung(n: int, ladder: tuple) -> int:
    return next(b for b in ladder if n % b == 0)


def _dot(a, b, dims):
    """``a`` x ``b`` in the dtype they arrive in, float32 accumulation.
    A bf16 product has one precision on the MXU, so it names it: a
    process-wide ``jax_default_matmul_precision`` of float32 would ask
    Mosaic for an fp32 contraction of bf16 operands, which it refuses."""
    precision = (None if a.dtype == jnp.float32
                 else jax.lax.Precision.DEFAULT)
    return jax.lax.dot_general(a, b, dims, precision=precision,
                               preferred_element_type=jnp.float32)


def _lanes(x, n: int):
    """A lane-replicated [rows, 128] statistic as [rows, n]."""
    if n % _LANES == 0:
        return x if n == _LANES else jnp.tile(x, (1, n // _LANES))
    if n < _LANES:
        return x[:, :n]
    return jnp.broadcast_to(x[:, :1], (x.shape[0], n))


def _lane_sums(p):
    """[rows, n] as [rows, 128] whose lanes sum to ``p``'s row sums: the
    128-lane slices added up (VALU adds), no reduction across lanes."""
    n = p.shape[1]
    if n % _LANES:
        p = jnp.pad(p, ((0, 0), (0, -n % _LANES)))
    return functools.reduce(
        jnp.add, (p[:, c:c + _LANES] for c in range(0, n, _LANES)))


def _layout(x):
    """[B, N, H, D] -> (the array a kernel windows, its index map)."""
    b, n, h, d = x.shape
    if d % _LANES == 0:
        return x.reshape(b, n, h * d), _window(h, d)
    return x.transpose(0, 2, 1, 3).reshape(b * h, n, d), _window(h, d)


def _window(h, d):
    """The index map of :func:`_layout`'s array of ``h`` heads ``d``
    wide: (batch*head index, row block) -> a block index."""
    if d % _LANES == 0:
        return lambda g, r: (g // h, r, g % h)
    return lambda g, r: (g, r, 0)


def _layout_shape(b, n, h, d):
    """The shape :func:`_layout` gives a [b, n, h, d] array."""
    return (b, n, h * d) if d % _LANES == 0 else (b * h, n, d)


def _unlayout(x, b, h, d):
    """Inverse of :func:`_layout` for a kernel's output."""
    if d % _LANES == 0:
        return x.reshape(b, -1, h, d)
    return x.reshape(b, h, -1, d).transpose(0, 2, 1, 3)


def _kv_head(h, h_kv):
    """batch*head index of q -> that of its key/value head."""
    if h == h_kv:
        return lambda g: g
    return lambda g: g // h * h_kv + g % h // (h // h_kv)


def _run_tile(body, rule, q0, bq, k0, bk, off):
    """Call ``body(masked)`` as the kind of the score tile of queries
    [q0, q0+bq) x keys [k0, k0+bk) asks, or not at all."""
    needed, full = rule.tile(q0, bq, k0, bk, off)   # some, every pair seen
    if full is True:        # the trace knows: a rule that hides nothing
        body(False)
        return
    pl.when(full)(lambda: body(False))
    pl.when(jnp.logical_and(needed, jnp.logical_not(full)))(
        lambda: body(True))


def _count_kinds(name, counts, calls):
    """Add ``counts`` ({kind: a number a batch x head}) x ``calls`` to the
    process's counters ``name{kind}``."""
    from ...obs.registry import process_group
    for kind, n in counts.items():
        process_group("kind").child(kind).counter(name).inc(n * calls)


def _count_tiles(rule, nq, nk, bq, bk, calls):
    """``flash_tiles_total{kind}``: the score tiles of one lowered kernel
    call over its ``calls`` (batch x heads) rows of the grid, by what the
    rule makes of them. A wrong rule shows as a count, not as a time."""
    _count_kinds("flash_tiles_total", tile_counts(rule, nq, nk, bq, bk),
                 calls)


def _count_steps(table, calls):
    """``flash_grid_steps_total{kind}``: the steps of one lowered kernel
    call's sequential axis over the ``calls`` times the grid walks it,
    ``working`` where a step runs its pair's tiles and ``held`` where it
    runs nothing (the padding of a backward call in key ranges: 0 for
    every shape a model here runs)."""
    _count_kinds("flash_grid_steps_total",
                 {"working": table.q.size - table.held, "held": table.held},
                 calls)


def _count_pairs(rule, nq, nk, calls):
    """``flash_pairs_total{rule}``: the (query, key) pairs the rule lets
    through (``rule.pairs``, a closed form) of one lowered kernel call x
    its ``calls`` (batch x heads), by the rule's name: the work a kernel
    is there to do, whatever tiles it takes to do it."""
    from ...obs.registry import process_group
    process_group("rule").child(rule.name).counter(
        "flash_pairs_total").inc(rule.pairs(nq, nk) * calls)


def _count_stat_bytes(shape, calls):
    """``flash_stat_bytes_total{kind="lse"}``: the float32 bytes of row
    statistics one lowered forward call writes to HBM (its LSE output, of
    ``shape`` a batch x head) x its ``calls``: 4 a (head, query), where
    the lane-replicated ``[Nq, 128]`` it once wrote reads 512."""
    _count_kinds("flash_stat_bytes_total", {"lse": 4 * math.prod(shape)},
                 calls)


def _fwd_kernel(*refs, scale, rule, off, chunk, has_mask):
    # q_ref/o_ref: [BQ, D]; k_ref/v_ref: [BK, D]; mask_ref: [1, BK] f32,
    # 1.0 = attend / 0.0 = padding; lse_ref: [1, BQ], the lane-dense row
    # the backward reads, written at LAST alone; m_ref: [BQ, 128], a
    # row's max in every lane; l_ref: [BQ, 128], a row's sum a lane's
    # share at a time (a pass adds its 128-lane slices of p, the lanes
    # are summed at LAST)
    # qb_ref/kb_ref/mark_ref: the step's pair and marks (``pair_table``)
    qb_ref, kb_ref, mark_ref, q_ref, k_ref, v_ref = refs[:6]
    mask_ref = refs[6] if has_mask else None
    o_ref, lse_ref, qs_ref, m_ref, l_ref, acc_ref = refs[6 + has_mask:]
    step = pl.program_id(1)
    i, at, mark = qb_ref[step], kb_ref[step], mark_ref[step]
    bq, dv = q_ref.shape[0], v_ref.shape[1]     # out is as wide as v
    bk = k_ref.shape[0]

    @pl.when(mark & FIRST != 0)
    def _():
        qs_ref[...] = (q_ref[...] * scale).astype(qs_ref.dtype)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def one(c, masked):
        ks = pl.ds(c * chunk, chunk)
        s = _dot(qs_ref[...], k_ref[ks, :], _NT)
        if has_mask:
            s = jnp.where(mask_ref[:, ks] > 0.5, s, _NEG_INF)
        if masked:
            s = jnp.where(
                rule.keep(s.shape, i * bq, at * bk + c * chunk, off, 0),
                s, _NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1)[:, None])
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - _lanes(m_new, chunk))
        l_ref[...] = alpha * l_ref[...] + _lane_sums(p)
        m_ref[...] = m_new
        acc_ref[...] = acc_ref[...] * _lanes(alpha, dv) + _dot(
            p.astype(v_ref.dtype), v_ref[ks, :], _NN)

    for c in range(bk // chunk):
        _run_tile(functools.partial(one, c), rule, i * bq, bq,
                  at * bk + c * chunk, chunk, off)

    @pl.when(mark & LAST != 0)
    def _():
        # Rows with zero visible keys (fully-padded batch entry): m is
        # still the sentinel and p degenerated to exp(0)=1 per key. Gate
        # those rows to zero output and sentinel LSE so the backward
        # (which keys p off the LSE) gives exact zero gradients for them.
        m = m_ref[...]
        # l_ref held a lane's share of a row's sum: the one sum across
        # lanes a query block
        l = jnp.broadcast_to(jnp.sum(l_ref[...], axis=1)[:, None], m.shape)
        visible = m > _NEG_INF * 0.5
        l_safe = jnp.where(l == 0.0, 1.0, l)
        inv = jnp.where(visible, 1.0 / l_safe, 0.0)
        o_ref[...] = (acc_ref[...] * _lanes(inv, dv)).astype(o_ref.dtype)
        # every lane of a row holds its LSE: a row of the transposed tile
        # is the query block's LSE lane-dense (64 vregs through the XLU
        # once a query block; a one-hot product on the MXU and the
        # diagonals summed down the sublanes read slower: PERF.md, PR 49)
        lse = jnp.where(visible, m + jnp.log(l_safe), _NEG_INF)
        lse_ref[...] = lse.T[:1]


def split_blocks(blocks):
    """``blocks`` as (forward, backward) triples: None, one (block_q,
    block_k, chunk) triple for both kernels, or two."""
    if blocks is None or isinstance(blocks[0], int):
        return blocks, blocks
    return tuple(blocks)


def _flash_fwd(q, k, v, scale, rule, padding_mask=None, blocks=None):
    """(out [B, Nq, H, Dv], lse [B*H, Nq]). One ``jit`` inside the
    caller's: a model's step calls this once a layer application, and
    the step's trace and lowering then take the kernel once a shape
    (Ouro's 48 calls cost its set-up 11 s otherwise).

    The kernel's two outputs carry names (``core/recompute_keeps.py``),
    outside that ``jit``: inert anywhere but in a recomputed segment
    (``fleet.utils.recompute``), where they spare the kernel's second
    run in the backward pass: 0.042 ms a MB kept on a v5e (PERF.md, PR
    30). One alone spares nothing, the kernel makes both or neither.
    ``out`` is named as the kernel wrote it, before :func:`_unlayout`:
    named as [B, Nq, H, D] the same bytes cost Ouro's step 48 more
    layout copies and a compile-cache entry of 131.8 MiB against 126.9
    (the step without the names: 129.7; PERF.md, PR 30).
    q, k and v are not named here: by the rule of that module a value is
    named where making it again costs 0.02 ms a MB, and what makes these
    is the caller's. Widening projections (K 2048) run again for 0.010
    to 0.012 ms a MB in all three cells; with a norm a head in float32
    behind them (SDAR) q still reads 0.012, because the norm's backward
    wants the product again whatever is kept; with rotary's float32
    passes Ouro's q and k read 0.025, and their 24 applications would
    hold 1.57 GiB, more than that chip has free (PERF.md, PR 37)."""
    (b, _, h, _), dv = q.shape, v.shape[3]
    out, lse = _fwd_call(q, k, v, padding_mask, scale=scale, rule=rule,
                         blocks=blocks, interpret=_common.interpret())
    out = keep_in_recompute(out, "flash_attention_out")
    return (_unlayout(out, b, h, dv),
            keep_in_recompute(lse, "flash_attention_lse"))


@functools.partial(jax.jit, static_argnames=("scale", "rule", "blocks",
                                             "interpret"))
def _fwd_call(q, k, v, padding_mask, *, scale, rule, blocks, interpret):
    b, nq, h, d = q.shape
    nk, h_kv, dv = k.shape[1], k.shape[2], v.shape[3]
    bq, bk, chunk = split_blocks(blocks)[0] or block_sizes(
        *rule.sizes(nq, nk), max(d, dv), q.dtype)
    off = nk - nq
    qa, at = _layout(q)
    ka, at_k = _layout(k)
    va, at_v = _layout(v)       # v and out take the layout of their width
    at_o = _window(h, dv)       # out: q's heads, v's width
    kv = _kv_head(h, h_kv)

    # a head walks the needed (query block, key block) pairs and no
    # other step: the windows follow the table's columns
    table = pair_table(rule, nq, nk, bq, bk)
    _count_tiles(rule, nq, nk, bq, chunk, b * h)
    _count_steps(table, b * h)
    _count_pairs(rule, nq, nk, b * h)
    # the LSE a head: one lane-dense row, the backward kernel's ``stat``
    lse_shape = (1, nq)
    _count_stat_bytes(lse_shape, b * h)

    def spec(shape, index):
        """A window placed by (batch x head, query block, key block) of
        a grid step."""
        return pl.BlockSpec(shape, lambda g, s, qb, kb, _: index(
            g, qb[s], kb[s]))
    in_specs = [
        spec((None, bq, d), lambda g, i, j: at(g, i)),
        spec((None, bk, d), lambda g, i, j: at_k(kv(g), j)),
        spec((None, bk, dv), lambda g, i, j: at_v(kv(g), j)),
    ]
    args = [qa, ka, va]
    if padding_mask is not None:
        # [B, Nk] keep-mask as f32; each (batch, head) program reads its
        # batch row (index map folds bh → b).
        in_specs.append(spec((None, 1, bk), lambda g, i, j: (g // h, 0, j)))
        args.append(padding_mask.astype(jnp.float32).reshape(b, 1, nk))
    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, rule=rule, off=off,
                          chunk=chunk, has_mask=padding_mask is not None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b * h, table.steps),
            in_specs=in_specs,
            out_specs=[
                spec((None, bq, dv), lambda g, i, j: at_o(g, i)),
                spec((None, 1, bq), lambda g, i, j: (g, 0, i)),
            ],
            scratch_shapes=[
                pltpu.VMEM((bq, d), q.dtype),
                pltpu.VMEM((bq, _LANES), jnp.float32),
                pltpu.VMEM((bq, _LANES), jnp.float32),
                pltpu.VMEM((bq, dv), jnp.float32),
            ]),
        out_shape=[
            jax.ShapeDtypeStruct(_layout_shape(b, nq, h, dv), q.dtype),
            jax.ShapeDtypeStruct((b * h,) + lse_shape, jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        name="p1t_flash_attention_fwd",
        interpret=interpret,
    )(table.q, table.k, table.mark, *args)
    return out, lse.reshape(b * h, nq)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _flash(q, k, v, padding_mask, scale, rule, blocks):
    return _flash_fwd(q, k, v, scale, rule, padding_mask, blocks)[0]


def _flash_vjp_fwd(q, k, v, padding_mask, scale, rule, blocks):
    # of the forward kernel the backward kernel needs ``out`` and ``lse``:
    # the two values _flash_fwd names for a recomputation to keep
    out, lse = _flash_fwd(q, k, v, scale, rule, padding_mask, blocks)
    return out, (q, k, v, padding_mask, out, lse)


def _flash_vjp_bwd(scale, rule, blocks, res, dout):
    from .flash_attention_bwd import flash_attention_bwd
    q, k, v, padding_mask, out, lse = res
    dq, dk, dv = flash_attention_bwd(q, k, v, out, lse, dout, scale, rule,
                                     padding_mask=padding_mask,
                                     blocks=blocks)
    # the mask enters as f32 0/1 (see flash_attention), so a plain zero
    # cotangent is the right "non-differentiable" answer
    dm = None if padding_mask is None else jnp.zeros_like(padding_mask)
    return dq, dk, dv, dm


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def flash_attention(q, k, v, causal: bool = False,
                    scale: Optional[float] = None, padding_mask=None,
                    blocks: Optional[tuple] = None, mask=None):
    """Fused attention. ``mask``: a rule of ``mask_rules`` (``causal`` is
    short for its ``CAUSAL``). ``padding_mask``: optional [B, Nk]
    keep-mask (bool/0-1); padded key positions are excluded from the
    softmax — the Pallas analog of the reference's additive
    attention-mask input (nn/layer/transformer.py MultiHeadAttention
    attn_mask). ``blocks`` overrides :func:`block_sizes` (tests and the
    sweep tool)."""
    d = q.shape[-1]             # the scale is the key width's, v has its own
    s = float(scale) if scale is not None else float(1.0 / (d ** 0.5))
    pm = padding_mask
    if pm is not None:
        pm = jnp.asarray(pm).astype(jnp.float32)
    return _flash(q, k, v, pm, s, rule_of(causal, mask), blocks)
