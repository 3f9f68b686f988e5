"""Structured attention masks, as descriptions the blockwise kernels skip
by tile (``flash_attention.py``, ``flash_attention_bwd.py``) and
``attention_ref`` builds a dense mask from.

A rule is a small hashable value (it is a static argument of the kernels'
``jit``) that depends on positions alone, never on data, and answers three
questions for the forward and the backward kernel alike (both keep a
query block resident while key blocks pass):

* which fetched key blocks a resident query block needs at all
  (``key_blocks``): the index maps fetch nothing for the others;
* whether a score tile is wholly visible, crossed or hidden (``tile``);
* the element-wise keep of a crossed tile (``keep``).

A rule also gives the grid's inner axis its length and index map
(``key_map``), the lengths the kernels' block sizes have
to divide (``sizes``), whether it describes given lengths at all
(``lengths_ok``), its dense mask and its count of visible pairs.

:data:`NO_MASK` hides nothing (every tile is plain: the kernels emit the
unmasked body alone), :data:`CAUSAL` is the bottom-right causal mask the
kernels have always had, :class:`BlockDiffusion` the training mask of
block diffusion (BD3-LMs, arXiv:2503.09573; SDAR, arXiv:2510.06303).
Every function takes and gives arrays (numpy or jax, scalars included),
so the kernels call them on program ids, and :func:`tile_counts` and the
tests on ``numpy.arange``s: one definition for what runs and what is
counted.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["NO_MASK", "NoMask", "CAUSAL", "Causal", "BlockDiffusion",
           "dense_mask", "tile_counts", "visible_pairs"]


def _iota(shape, axis):
    return jax.lax.broadcasted_iota(jnp.int32, shape, axis)


def _xp(x):
    """numpy for numpy's arrays (the counts), jax.numpy for the rest (the
    program ids of a kernel or of an index map)."""
    return np if isinstance(x, (np.ndarray, np.generic)) else jnp


@dataclasses.dataclass(frozen=True)
class NoMask:
    """Every query sees every key. The answers are Python's own ``True``,
    so that a kernel's trace decides them and holds no branch."""

    def lengths_ok(self, nq, nk):
        return True

    def sizes(self, nq, nk):
        return nq, nk

    def key_map(self, nq, nk, bq, bk):
        return nk // bk, lambda i, j: j

    def key_blocks(self, i, step, bq, bk):
        return step, None

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

    def lengths_ok(self, nq, nk):
        # nq > nk leaves leading queries with ZERO visible keys; the
        # zero-sumexp sentinel would poison the vjp
        return nq <= nk

    def sizes(self, nq, nk):
        """The lengths a kernel's block sizes have to divide."""
        return nq, nk

    def key_map(self, nq, nk, bq, bk):
        """-> (steps of the inner axis of the kernels' grids,
        (query block i, grid step j) -> the key block to fetch): j, held
        at the last block that query block i sees, so that a step above
        the diagonal fetches nothing new."""
        off = nk - nq
        return nk // bk, lambda i, j: jnp.minimum(
            j, jnp.minimum((i * bq + bq - 1 + off) // bk, nk // bk - 1))

    def key_blocks(self, i, step, bq, bk):
        """-> (the block a grid step fetched as the kernels place it,
        whether the resident block needs it: None, the grid counts every
        block and the tiles are skipped by position)."""
        return step, None

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

    def key_map(self, nq, nk, bq, bk):
        """The inner axis counts the blocks the hungriest resident block
        needs, and no more."""
        return (self.key_steps(bq, bk),
                lambda i, j: self.key_blocks(i, j, bq, bk)[0])

    # -- where a tile lies

    def _place(self, r0):
        """-> (in the noisy copy, position within its copy)."""
        second = r0 >= self.length
        p0 = r0 - second.astype(r0.dtype) * self.length
        return (~second if self.noisy_first else second), p0

    def _floor(self, p):
        """The first position of ``p``'s block."""
        return p & -self.block

    def _base(self, noisy, blocks_a_copy):
        """The first block (of ``blocks_a_copy`` a copy) of a copy."""
        return 0 if noisy == self.noisy_first else blocks_a_copy

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

    # -- which key blocks a resident query block needs: two runs of
    # consecutive blocks, [a0, a0 + na) then [b0, b0 + nb)

    def _key_runs(self, i, bq, bk):
        per = self.length // bk
        qn, qp = self._place(i * bq)
        last = self._floor(qp + bq - 1)          # its last query's block
        clean, noisy = self._base(False, per), self._base(True, per)
        upto = (last + self.block - 1) // bk     # that block's last key's
        # a noisy query block: the clean keys before its last query's
        # block, then the noisy keys of its own blocks. A clean one: the
        # clean keys up to its last query's block
        first = self._floor(qp) // bk
        na = _xp(qn).where(qn, (last + bk - 1) // bk, upto + 1)
        nb = _xp(qn).where(qn, upto - first + 1, 0)
        return clean, na, noisy + first, nb

    @staticmethod
    def _pick(step, a0, na, b0, nb):
        """Grid step -> (the block to fetch, whether the step is one of
        the ``na + nb`` needed). A step past them holds the last block, so
        that nothing new is fetched."""
        xp = _xp(na)
        at = xp.minimum(step, na + nb - 1)
        return xp.where(at < na, a0 + at, b0 + at - na), step < na + nb

    def key_blocks(self, i, step, bq, bk):
        """Of query block ``i`` (of ``bq`` rows): the key block (of ``bk``)
        its ``step``-th grid step fetches, and whether it needs one."""
        return self._pick(step, *self._key_runs(i, bq, bk))

    def key_steps(self, bq, bk):
        """Grid steps along the keys that the hungriest query block
        needs: the inner axis of the kernels' grids."""
        _, na, _, nb = self._key_runs(
            np.arange(2 * self.length // bq, dtype=np.int32), bq, bk)
        return int(np.max(na + nb))

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


def tile_counts(rule, nq, nk, bq, bk):
    """{"plain", "masked", "skipped"}: how many of the (nq / bq) x
    (nk / bk) score tiles a kernel runs without a mask, runs under the
    rule's element-wise keep, and does not run."""
    total = (nq // bq) * (nk // bk)
    q0 = (np.arange(nq // bq, dtype=np.int32) * bq)[:, None]
    k0 = (np.arange(nk // bk, dtype=np.int32) * bk)[None]
    needed, full = rule.tile(q0, bq, k0, bk, nk - nq)
    needed, full = np.broadcast_to(needed, (nq // bq, nk // bk)), \
        np.broadcast_to(full, (nq // bq, nk // bk))
    plain = int(np.sum(full))
    masked = int(np.sum(needed & ~full))
    return {"plain": plain, "masked": masked,
            "skipped": total - plain - masked}
