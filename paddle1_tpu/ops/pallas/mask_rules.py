"""Structured attention masks, as descriptions the blockwise kernels skip
by tile (``flash_attention.py``, ``flash_attention_bwd.py``) and
``attention_ref`` builds a dense mask from.

A rule is a small hashable value (it is a static argument of the kernels'
``jit``) that depends on positions alone, never on data, and answers two
questions for the forward and the backward kernel alike (both keep a
query block resident while key blocks pass):

* whether a score tile is wholly visible, crossed or hidden (``tile``);
* the element-wise keep of a crossed tile (``keep``).

A rule also gives the lengths the kernels' block sizes have to divide
(``sizes``), whether it describes given lengths at all (``lengths_ok``),
its dense mask and its count of visible pairs. It says nothing about a
grid: :func:`pair_table` asks ``tile`` of every (query block, fetched key
block) once, at trace time, and lists the needed pairs in the order the
kernels walk them; the table reaches their index maps and bodies by
scalar prefetch, so a grid has no step that fetches or runs nothing.

:data:`NO_MASK` hides nothing (every tile is plain: the kernels emit the
unmasked body alone), :data:`CAUSAL` is the bottom-right causal mask the
kernels have always had, :class:`SlidingWindow` the causal mask cut to a
band (a query sees itself and the ``window - 1`` keys before it: the
local layers of a model that mixes them with global ones),
:class:`BlockDiffusion` the training mask of block diffusion (BD3-LMs,
arXiv:2503.09573; SDAR, arXiv:2510.06303). A rule's ``name`` labels what
is counted by rule (``flash_pairs_total{rule}``).
Every function takes and gives arrays (numpy or jax, scalars included),
so the kernels call them on the table's scalars, and :func:`pair_table`,
:func:`tile_counts` and the tests on ``numpy.arange``s: one definition
for what runs and what is counted.
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["NO_MASK", "NoMask", "CAUSAL", "Causal", "SlidingWindow",
           "BlockDiffusion",
           "dense_mask", "tile_counts", "subtile_counts", "subtile_patterns",
           "visible_pairs", "pair_table",
           "PairTable", "FIRST", "LAST", "HELD"]


def _iota(shape, axis):
    return jax.lax.broadcasted_iota(jnp.int32, shape, axis)


@dataclasses.dataclass(frozen=True)
class NoMask:
    """Every query sees every key. The answers are Python's own ``True``,
    so that a kernel's trace decides them and holds no branch."""
    name = "none"

    def lengths_ok(self, nq, nk):
        return True

    def sizes(self, nq, nk):
        return nq, nk

    def tile(self, q0, bq, k0, bk, off=0):
        return True, True

    def dense(self, nq, nk):
        return np.ones((nq, nk), bool)

    def pairs(self, nq, nk):
        return nq * nk


NO_MASK = NoMask()


@dataclasses.dataclass(frozen=True)
class Causal:
    """Bottom-right aligned: query ``r`` sees keys ``<= r + nk - nq``
    (``off`` below is ``nk - nq``)."""
    name = "causal"

    def lengths_ok(self, nq, nk):
        # nq > nk leaves leading queries with ZERO visible keys; the
        # zero-sumexp sentinel would poison the vjp
        return nq <= nk

    def sizes(self, nq, nk):
        """The lengths a kernel's block sizes have to divide."""
        return nq, nk

    def tile(self, q0, bq, k0, bk, off):
        """(some pair visible, every pair visible) of the score tile of
        queries [q0, q0+bq) x keys [k0, k0+bk)."""
        return k0 <= q0 + bq - 1 + off, k0 + bk - 1 <= q0 + off

    def keep(self, shape, q0, k0, off, q_axis):
        """Keep-mask of one score tile whose ``q_axis`` runs over queries
        from q0 and whose other axis runs over keys from k0."""
        q_ids = q0 + off + _iota(shape, q_axis)
        k_ids = k0 + _iota(shape, 1 - q_axis)
        return q_ids >= k_ids

    def dense(self, nq, nk):
        return np.tril(np.ones((nq, nk), bool), nk - nq)

    def pairs(self, nq, nk):
        return nq * (nq + 1) // 2 + nq * (nk - nq)


CAUSAL = Causal()


@dataclasses.dataclass(frozen=True)
class SlidingWindow:
    """Causal, cut to a band: with ``d = r + nk - nq - c`` the distance of
    key ``c`` behind query ``r`` (bottom-right aligned as :class:`Causal`),
    the query sees the key iff ``0 <= d < window``: itself and the
    ``window - 1`` keys before it. A score tile holds every distance from
    its top-right corner's to its bottom-left corner's, so it can be
    crossed by the diagonal, by the band's far edge, or by both (a window
    shorter than a block); a window of ``nk`` or more is the causal
    mask."""
    window: int
    name = "window"

    def __post_init__(self):
        if self.window < 1:
            raise ValueError(f"window {self.window}: a query sees itself")

    def lengths_ok(self, nq, nk):
        return nq <= nk         # as the causal rule: no query without a key

    def sizes(self, nq, nk):
        return nq, nk

    def tile(self, q0, bq, k0, bk, off):
        nearest = q0 + off - (k0 + bk - 1)      # the top-right corner's d
        farthest = q0 + bq - 1 + off - k0       # the bottom-left corner's
        return ((farthest >= 0) & (nearest < self.window),
                (nearest >= 0) & (farthest < self.window))

    def keep(self, shape, q0, k0, off, q_axis):
        d = (q0 + off + _iota(shape, q_axis)) - (k0 + _iota(shape,
                                                            1 - q_axis))
        return (d >= 0) & (d < self.window)

    def dense(self, nq, nk):
        d = np.arange(nq)[:, None] + (nk - nq) - np.arange(nk)[None]
        return (d >= 0) & (d < self.window)

    def pairs(self, nq, nk):
        # the first queries see fewer than ``window`` keys: off + 1, ...
        off = nk - nq
        short = min(max(self.window - 1 - off, 0), nq)
        return (short * (off + 1) + short * (short - 1) // 2
                + (nq - short) * self.window)


@dataclasses.dataclass(frozen=True)
class BlockDiffusion:
    """A row of ``2 * length`` positions: a noisy copy and a clean copy of
    one sequence, the noisy one first where ``noisy_first``; blocks of
    ``block`` positions (a power of two that divides ``length``). With
    ``b(r) = (r mod length) // block``, query ``r`` sees key ``c`` iff

    * both noisy: ``b(c) == b(r)`` (both directions inside a block);
    * ``r`` noisy, ``c`` clean: ``b(c) < b(r)``;
    * ``r`` clean, ``c`` noisy: never;
    * both clean: ``b(c) <= b(r)``.

    ``length^2 + length * block`` of the ``(2 * length)^2`` pairs. The
    kernels' blocks divide ``length``, so a score tile lies in one copy on
    either axis and its quadrant is a scalar of the grid step."""
    length: int
    block: int
    noisy_first: bool = True
    name = "block_diffusion"

    def __post_init__(self):
        if self.block < 1 or self.block & (self.block - 1) \
                or self.length % self.block:
            raise ValueError(
                f"block {self.block} must be a power of two that divides "
                f"the length {self.length}")

    def lengths_ok(self, nq, nk):
        return nq == nk == 2 * self.length

    def sizes(self, nq, nk):
        """A copy's length: no tile may straddle two copies."""
        return self.length, self.length

    # -- where a tile lies

    def _place(self, r0):
        """-> (in the noisy copy, position within its copy)."""
        second = r0 >= self.length
        p0 = r0 - second.astype(r0.dtype) * self.length
        return (~second if self.noisy_first else second), p0

    def _floor(self, p):
        """The first position of ``p``'s block."""
        return p & -self.block

    def tile(self, q0, bq, k0, bk, off=0):
        qn, qp = self._place(q0)
        kn, kp = self._place(k0)
        qlo, qhi = self._floor(qp), self._floor(qp + bq - 1)
        klo, khi = self._floor(kp), self._floor(kp + bk - 1)
        needed = ((qn & kn & (klo <= qhi) & (khi >= qlo))
                  | (qn & ~kn & (klo < qhi)) | (~qn & ~kn & (klo <= qhi)))
        full = ((qn & kn & (klo == qhi) & (khi == qlo))
                | (qn & ~kn & (khi < qlo)) | (~qn & ~kn & (khi <= qlo)))
        return needed, full

    def keep(self, shape, q0, k0, off, q_axis):
        """Of a tile :meth:`tile` calls needed (so never clean queries
        against noisy keys): the keys' block less the queries' is 0 among
        the noisy, under 0 from noisy to clean, at most 0 among the
        clean."""
        qn, qp = self._place(q0)
        kn, kp = self._place(k0)
        apart = (self._floor(kp + _iota(shape, 1 - q_axis))
                 - self._floor(qp + _iota(shape, q_axis)))
        least = jnp.where(qn & kn, 0, -2 * self.length)
        most = jnp.where(qn & ~kn, -1, 0)
        return (apart >= least) & (apart <= most)

    def dense(self, nq, nk):
        r = np.arange(2 * self.length)
        noisy = (r < self.length) == self.noisy_first
        b = r % self.length // self.block
        qn, kn, qb, kb = noisy[:, None], noisy[None], b[:, None], b[None]
        return ((qn & kn & (kb == qb)) | (qn & ~kn & (kb < qb))
                | (~qn & ~kn & (kb <= qb)))

    def pairs(self, nq, nk):
        return self.length * (self.length + self.block)


def dense_mask(rule, nq, nk):
    """The [nq, nk] boolean keep-mask a rule describes (numpy): what
    ``attention_ref`` applies at sizes the kernels do not take."""
    if not rule.lengths_ok(nq, nk):
        raise ValueError(f"{rule} does not describe {nq} queries x {nk} keys")
    return rule.dense(nq, nk)


def visible_pairs(rule, nq, nk):
    """Pairs (query, key) a rule lets through, in closed form."""
    return rule.pairs(nq, nk)


def _tiles(rule, nq, nk, bq, bk):
    """(needed, full) of :meth:`tile` for every one of the (nq / bq) x
    (nk / bk) score tiles, as boolean matrices."""
    shape = nq // bq, nk // bk
    q0 = (np.arange(shape[0], dtype=np.int32) * bq)[:, None]
    k0 = (np.arange(shape[1], dtype=np.int32) * bk)[None]
    return tuple(np.broadcast_to(np.asarray(kind, bool), shape)
                 for kind in rule.tile(q0, bq, k0, bk, nk - nq))


def tile_counts(rule, nq, nk, bq, bk):
    """{"plain", "masked", "skipped"}: how many of the (nq / bq) x
    (nk / bk) score tiles a kernel runs without a mask, runs under the
    rule's element-wise keep, and does not run."""
    needed, full = _tiles(rule, nq, nk, bq, bk)
    plain = int(np.sum(full))
    masked = int(np.sum(needed & ~full))
    return {"plain": plain, "masked": masked,
            "skipped": needed.size - plain - masked}


def _subtiles(rule, nq, nk, bq, bk, sq, sk):
    """(crossed, needed, full): which (bq x bk) score tiles are crossed,
    and what ``rule.tile`` makes of each tile's (sq x sk) sub-tiles, as
    [query tiles, key tiles, sub-tile rows, sub-tile columns]."""
    needed, full = _tiles(rule, nq, nk, bq, bk)
    shape = nq // bq, bq // sq, nk // bk, bk // sk
    return (needed & ~full,) + tuple(
        kind.reshape(shape).transpose(0, 2, 1, 3)
        for kind in _tiles(rule, nq, nk, sq, sk))


def subtile_counts(rule, nq, nk, bq, bk, sq, sk):
    """{"plain", "masked", "spared"}: the (sq x sk) sub-tiles of the
    crossed (bq x bk) score tiles alone, by what the rule makes of them at
    their own grain: wholly visible, crossed, and hidden (what a kernel
    that runs a crossed tile by sub-tile does not run)."""
    crossed, needed, full = _subtiles(rule, nq, nk, bq, bk, sq, sk)
    needed, full = needed[crossed], full[crossed]
    return {"plain": int(np.sum(full)), "masked": int(np.sum(needed & ~full)),
            "spared": int(np.sum(~needed))}


def subtile_patterns(rule, nq, nk, bq, bk, sq, sk):
    """The distinct layouts of sub-tile kinds over the crossed tiles: a
    tuple of [bq / sq, bk / sk] int8 matrices, 0 where the rule hides the
    sub-tile, 1 where it crosses it, 2 where it shows every pair. One for
    the causal rule (the diagonal's), two for a window (the diagonal's
    and the far edge's), two for block diffusion in blocks shorter than a
    sub-tile (the noisy copy's own diagonal; the diagonal of the other
    two quadrants): made with numpy when a kernel call is traced, so that a kernel's
    crossed tile is straight code over the sub-tiles it runs."""
    crossed, needed, full = _subtiles(rule, nq, nk, bq, bk, sq, sk)
    kinds = (needed.astype(np.int8) + full)[crossed]
    if not len(kinds):
        return ()
    return tuple(np.unique(kinds.reshape(len(kinds), -1), axis=0).reshape(
        (-1,) + kinds.shape[1:]))


# marks of a step of :func:`pair_table`: the first / the last step of its
# query block (set up / write out what is resident with the block); a
# step that runs no tile
FIRST, LAST, HELD = 1, 2, 4


class PairTable(NamedTuple):
    """A step ``s`` of key range ``r`` is entry ``r * steps + s`` of the
    three int32 columns."""
    q: np.ndarray       # the query block
    k: np.ndarray       # the fetched key block
    mark: np.ndarray    # FIRST | LAST | HELD
    steps: int          # a range's steps

    @property
    def held(self) -> int:
        return int(np.sum(self.mark & HELD != 0))


def pair_table(rule, nq, nk, bq, bk, ranges=1) -> PairTable:
    """The (query block of ``bq``, key block of ``bk``) pairs that
    ``rule.tile`` calls needed, query-major with the key blocks ascending:
    the one sequential axis of the kernels' grids, made with numpy when a
    kernel call is traced. Every query block has a FIRST and a LAST step.

    With the keys in ``ranges`` equal ranges (the backward kernel where a
    head's dK and dV do not fit VMEM: no shape a model here runs), a table
    a range, of that range's key blocks alone and as long as the longest;
    the others end in steps that hold their last pair, and a query block
    that needs no key of a range has one step there, FIRST | LAST | HELD,
    so that its partial dQ of the range is written (zero)."""
    needed, _ = _tiles(rule, nq, nk, bq, bk)
    per = needed.shape[1] // ranges
    columns = []
    for lo in range(0, needed.shape[1], per):
        mine = needed[:, lo:lo + per]
        q, k = np.nonzero(mine)                 # row-major: query-major
        none = np.flatnonzero(~mine.any(axis=1))
        q, k = np.concatenate([q, none]), np.concatenate([k, 0 * none]) + lo
        mark = np.repeat([0, HELD], [q.size - none.size, none.size])
        order = np.lexsort((k, q))
        q, k, mark = q[order], k[order], mark[order]
        edge = np.flatnonzero(np.diff(q)) + 1   # where a query block begins
        mark[np.concatenate([[0], edge])] |= FIRST
        mark[np.concatenate([edge - 1, [q.size - 1]])] |= LAST
        columns.append((q, k, mark))
    steps = max(q.size for q, _, _ in columns)

    def column(parts, **how):       # every range padded to the longest
        return np.concatenate([np.pad(x, (0, steps - x.size), **how)
                               for x in parts]).astype(np.int32)
    qs, ks, marks = zip(*columns)
    return PairTable(column(qs, mode="edge"), column(ks, mode="edge"),
                     column(marks, constant_values=HELD), steps)
