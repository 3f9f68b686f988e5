"""The table of needed (query block, key block) pairs that the blockwise
attention kernels walk (ISSUE 39; ``ops/pallas/mask_rules.py::pair_table``)
against what ``rule.tile`` says pair by pair; the sub-tiles of the crossed
tiles, which the backward kernel runs or spares (ISSUE 48), in closed
form, on the counter and on the kernel; held steps (a backward call
in key ranges) change nothing; the counters of a lowered call's grid steps
and visible pairs; the sliding window's rule (ISSUE 43) against its dense
mask written out by hand, and on the kernels with query heads in groups
of 7. CPU; the kernels in interpreter mode."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from paddle1_tpu import obs  # noqa: E402
from paddle1_tpu.nn.functional.attention import attention_ref  # noqa: E402
from paddle1_tpu.obs.registry import process_group  # noqa: E402
from paddle1_tpu.ops.pallas import flash_attention as fa  # noqa: E402
from paddle1_tpu.ops.pallas import flash_attention_bwd as fb  # noqa: E402
from paddle1_tpu.ops.pallas.mask_rules import (CAUSAL, FIRST,  # noqa: E402
                                               HELD, LAST, NO_MASK,
                                               BlockDiffusion,
                                               SlidingWindow, dense_mask,
                                               pair_table, subtile_counts,
                                               subtile_patterns, tile_counts,
                                               visible_pairs)

# name -> (rule, queries, keys, resident block, fetched block)
SMALL = {
    "causal": (CAUSAL, 512, 512, 128, 128),
    "causal_two_chunks": (CAUSAL, 512, 512, 128, 256),
    "causal_fewer_queries": (CAUSAL, 256, 768, 128, 256),
    "no_mask": (NO_MASK, 512, 256, 128, 128),
    "one_block": (BlockDiffusion(256, 256), 512, 512, 128, 128),
    # a window shorter than a block (the diagonal's tile is crossed by
    # both edges), a block long, between one and two, and past every key
    "window_64": (SlidingWindow(64), 512, 512, 128, 128),
    "window_a_block": (SlidingWindow(128), 512, 512, 128, 256),
    "window_200": (SlidingWindow(200), 512, 512, 128, 128),
    "window_300_fewer_queries": (SlidingWindow(300), 256, 768, 128, 256),
    "window_past_the_keys": (SlidingWindow(4096), 512, 512, 128, 128),
}
SMALL.update({
    f"block_diffusion_{block}_{'noisy' if first else 'clean'}_first":
        (BlockDiffusion(512, block, first), 1024, 1024, 128, 256)
    for block in (1, 4, 32) for first in (True, False)})
# the four cells' attention calls at the shipped (512, 1024): the steps
# of a head of ISSUE 39's table
CELLS = {
    "sdar": (BlockDiffusion(8192, 4), 16384, 16384, 160),
    "kanana2": (CAUSAL, 8192, 8192, 72),
    "lfm2": (CAUSAL, 16384, 16384, 272),
    "ouro": (CAUSAL, 4096, 4096, 20),
    # a window layer of SmallThinker's (its global layer walks lfm2's
    # 272): a query block of 512 sees 4,096 keys in 5 blocks of 1,024
    # wherever it stands from the ninth on, the first eight 1, 1, 2, 2, 3,
    # 3, 4, 4
    "smallthinker": (SlidingWindow(4096), 16384, 16384, 24 * 5 + 20),
}
CASES = dict(SMALL, **{name: case[:3] + (512, 1024)
                       for name, case in CELLS.items()})


def _needed(rule, nq, nk, bq, bk):
    """{(query block, key block)} that ``rule.tile`` calls needed, asked
    one pair at a time."""
    return {(i, j) for i in range(nq // bq) for j in range(nk // bk)
            if rule.tile(np.int32(i * bq), bq, np.int32(j * bk), bk,
                         nk - nq)[0]}


@pytest.mark.parametrize("case", sorted(CASES))
def test_every_needed_pair_once_and_no_other(case):
    rule, nq, nk, bq, bk = CASES[case]
    table = pair_table(rule, nq, nk, bq, bk)
    pairs = list(zip(table.q.tolist(), table.k.tolist()))
    assert len(pairs) == table.steps == len(set(pairs))
    assert set(pairs) == _needed(rule, nq, nk, bq, bk)
    assert table.held == 0
    assert all(x.dtype == np.int32 for x in (table.q, table.k, table.mark))
    # a fetched block is needed where one of its chunks is
    chunk = min(bk, 512)
    by_chunk = _needed(rule, nq, nk, bq, chunk)
    assert set(pairs) == {(i, j * chunk // bk) for i, j in by_chunk}


@pytest.mark.parametrize("case", sorted(CASES))
def test_query_major_keys_ascending_and_marks_bracket_a_query_block(case):
    rule, nq, nk, bq, bk = CASES[case]
    table = pair_table(rule, nq, nk, bq, bk)
    pairs = list(zip(table.q.tolist(), table.k.tolist()))
    assert pairs == sorted(pairs)
    # every query block is there (none without a key), opened by its
    # first step and closed by its last, and by no step between
    assert sorted(set(table.q.tolist())) == list(range(nq // bq))
    opens = np.r_[True, np.diff(table.q) != 0]
    closes = np.r_[np.diff(table.q) != 0, True]
    np.testing.assert_array_equal(table.mark & FIRST != 0, opens)
    np.testing.assert_array_equal(table.mark & LAST != 0, closes)


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_a_cells_head_walks_the_steps_the_issue_counted(cell):
    rule, nq, nk, steps = CELLS[cell]
    assert pair_table(rule, nq, nk, 512, 1024).steps == steps
    # where the fetched block is the chunk, the table is the tiles run
    counts = tile_counts(rule, nq, nk, 512, 512)
    assert pair_table(rule, nq, nk, 512, 512).steps \
        == counts["plain"] + counts["masked"]
    # and the rectangle the parent's grid walked: the hungriest query
    # block's steps for every one of them
    most = int(np.bincount(pair_table(rule, nq, nk, 512, 1024).q).max())
    idle = (nq // 512) * most - steps
    assert idle == {"sdar": 128, "kanana2": 56, "lfm2": 240,
                    "ouro": 12, "smallthinker": 20}[cell]


RANGED = {
    "causal": (CAUSAL, 512, 512, 128, 128, 2),
    "causal_four_ranges": (CAUSAL, 512, 512, 128, 128, 4),
    "causal_fewer_queries": (CAUSAL, 256, 768, 128, 128, 3),
    "no_mask": (NO_MASK, 256, 512, 128, 128, 2),
    "block_diffusion": (BlockDiffusion(256, 4), 512, 512, 128, 128, 2),
    "block_diffusion_clean_first": (BlockDiffusion(256, 4, False), 512, 512,
                                    128, 128, 4),
    # every query block but the first two needs no key of the first range
    "window": (SlidingWindow(200), 512, 512, 128, 128, 2),
}


@pytest.mark.parametrize("case", sorted(RANGED))
def test_a_table_in_key_ranges_pads_with_steps_that_run_nothing(case):
    rule, nq, nk, bq, bk, ranges = RANGED[case]
    table = pair_table(rule, nq, nk, bq, bk, ranges)
    whole = pair_table(rule, nq, nk, bq, bk)
    assert table.q.size == ranges * table.steps
    per = nk // bk // ranges
    working = []
    for r in range(ranges):
        at = slice(r * table.steps, (r + 1) * table.steps)
        q, k, mark = table.q[at], table.k[at], table.mark[at]
        assert ((k >= r * per) & (k < (r + 1) * per)).all()
        assert (np.diff(q) >= 0).all()
        # every query block opens and closes once a range, so that its
        # partial dQ of the range is set up and written
        assert q[mark & FIRST != 0].tolist() == list(range(nq // bq))
        assert q[mark & LAST != 0].tolist() == list(range(nq // bq))
        live = mark & HELD == 0
        working += list(zip(q[live].tolist(), k[live].tolist()))
        # a held step between a block's first and last would be skipped
        # work: it is alone in its block, or after the range's last
        for s in np.flatnonzero(~live):
            alone = mark[s] == FIRST | LAST | HELD
            tail = mark[s] == HELD and (q[s:] == q[s]).all() \
                and (~live[s:]).all()
            assert alone or tail
    assert sorted(working) == list(zip(whole.q.tolist(), whole.k.tolist()))
    assert table.held == ranges * table.steps - whole.steps


# -- held steps change nothing ----------------------------------------------

# name -> (rule, sequence, heads, key/value heads, key width, value width)
HELD_CASES = {
    "causal": (CAUSAL, 512, 2, 2, 64, 64),
    "block_diffusion": (BlockDiffusion(256, 4), 512, 2, 2, 128, 128),
    "grouped_heads": (CAUSAL, 512, 8, 2, 64, 64),
    "keys_192_values_128": (CAUSAL, 512, 2, 2, 192, 128),
    "window": (SlidingWindow(200), 512, 2, 1, 128, 128),
}


@pytest.mark.parametrize("case", sorted(HELD_CASES))
def test_held_steps_change_nothing(case):
    """The backward at one key range and at two (whose padded table has
    steps that run nothing): dK and dV equal to the bit, dQ within
    float32's sum of two partials; and both within the tolerance the
    kernels' own tests hold them to against ``attention_ref``."""
    rule, s, h, h_kv, d, dv = HELD_CASES[case]
    keys = jax.random.split(jax.random.key(7), 4)
    q, k, v, dout = (jax.random.normal(kk, (1, s, heads, w), jnp.float32)
                     for kk, heads, w in zip(keys, (h, h_kv, h_kv, h),
                                             (d, d, dv, dv)))
    scale = d ** -0.5
    out, lse = fa._flash_fwd(q, k, v, scale, rule, blocks=(128, 128, 128))
    assert pair_table(rule, s, s, 128, 128, 2).held > 0
    one, two = (fb.flash_attention_bwd(
        q, k, v, out, lse, dout, scale, rule,
        blocks=(None, (128, 128, 128, span))) for span in (s, s // 2))
    np.testing.assert_array_equal(one[1], two[1], err_msg="dk")
    np.testing.assert_array_equal(one[2], two[2], err_msg="dv")
    np.testing.assert_allclose(one[0], two[0], rtol=0, atol=4e-7 * float(
        jnp.max(jnp.abs(one[0]))), err_msg="dq")

    def dense(q, k, v):
        k, v = (jnp.repeat(x, h // h_kv, axis=2) for x in (k, v))
        return attention_ref(q, k, v, mask_rule=rule)
    want, pull = jax.vjp(dense, q, k, v)
    for name, g, w in zip(("out", "dq", "dk", "dv"), (out,) + two,
                          (want,) + pull(dout)):
        np.testing.assert_allclose(g, w, rtol=2e-5, atol=2e-5 * float(
            jnp.max(jnp.abs(w))), err_msg=name)


# -- the counter ------------------------------------------------------------

@pytest.fixture
def _fresh_obs():
    """A call is counted when it is traced: no trace of another test's
    (this file's or, in one worker, another's) may answer for it."""
    def fresh():
        obs.reset_process_registry()
        fa._fwd_call.clear_cache()
        fb._bwd_call.clear_cache()
    fresh()
    yield
    fresh()


def _steps():
    kinds = process_group("kind")
    return {kind: kinds.child(kind).counter("flash_grid_steps_total").value
            for kind in ("working", "held")}


def _pairs():
    """``flash_pairs_total{rule}`` of every rule that has counted."""
    rules = process_group("rule")
    return {name: rules.child(name).counter("flash_pairs_total").value
            for name in rules.labels()}


@pytest.mark.parametrize("case", ["causal", "block_diffusion", "no_mask",
                                  "window"])
def test_a_lowered_call_counts_its_grid_steps(case, _fresh_obs):
    """``flash_grid_steps_total{kind}``: a lowered forward and backward
    call each walk the table once a batch x head; nothing is held.
    ``flash_pairs_total{rule}``: each counts the pairs its rule lets
    through a batch x head, under the rule's name and no other."""
    rule = {"causal": CAUSAL, "block_diffusion": BlockDiffusion(256, 4),
            "no_mask": NO_MASK, "window": SlidingWindow(200)}[case]
    q, k = (jax.ShapeDtypeStruct((2, 512, heads, 64), jnp.float32)
            for heads in (4, 2))
    jax.jit(jax.grad(lambda q, k, v: jnp.sum(fa.flash_attention(
        q, k, v, mask=rule, blocks=(128, 256, 128))),
        argnums=(0, 1, 2))).lower(q, k, k)
    pairs = pair_table(rule, 512, 512, 128, 256).steps
    assert _steps() == {"working": 2 * pairs * 2 * 4, "held": 0}
    seen = {"causal": 512 * 513 // 2, "block_diffusion": 256 * 260,
            "no_mask": 512 * 512,
            "window": 200 * 201 // 2 + 312 * 200}[case]
    assert _pairs() == {{"no_mask": "none"}.get(case, case):
                        2 * seen * 2 * 4}


def test_a_call_in_key_ranges_counts_its_held_steps(_fresh_obs):
    x = jax.ShapeDtypeStruct((1, 512, 2, 64), jnp.float32)
    jax.make_jaxpr(jax.grad(lambda q, k, v: jnp.sum(fa.flash_attention(
        q, k, v, causal=True, blocks=((128, 128, 128), (128, 128, 128, 256)))),
        argnums=(0, 1, 2)))(x, x, x)
    table = pair_table(CAUSAL, 512, 512, 128, 128, 2)
    whole = pair_table(CAUSAL, 512, 512, 128, 128).steps
    # forward: the whole table; backward: two ranges' steps, padded
    assert _steps() == {"working": 2 * 2 * whole,
                        "held": 2 * (2 * table.steps - whole)}
    assert table.held == 2 * table.steps - whole > 0


def test_at_the_cells_sizes_no_step_is_held(_fresh_obs):
    """A forward + backward call at each cell's shape, traced alone:
    ``held`` reads 0 and ``working`` the table's steps x batch x heads,
    where the parent's rectangle held 4,096 / 3,584 / 7,680 / 384 a call."""
    shapes = {"sdar": (1, 32, 4, 128, 128), "kanana2": (2, 32, 32, 192, 128),
              "lfm2": (1, 32, 8, 64, 64), "ouro": (2, 16, 16, 128, 128),
              "smallthinker": (1, 28, 4, 128, 128)}
    parents_idle = {}
    for cell, (b, h, h_kv, d, dv) in shapes.items():
        obs.reset_process_registry()
        rule, s, _, steps = CELLS[cell]
        q, k, v = (jax.ShapeDtypeStruct((b, s, heads, w), jnp.bfloat16)
                   for heads, w in ((h, d), (h_kv, d), (h_kv, dv)))
        jax.make_jaxpr(jax.grad(lambda q, k, v: jnp.sum(fa.flash_attention(
            q, k, v, mask=rule).astype(jnp.float32)),
            argnums=(0, 1, 2)))(q, k, v)
        assert _steps() == {"working": 2 * steps * b * h, "held": 0}, cell
        assert _pairs() == {rule.name: 2 * rule.pairs(s, s) * b * h}, cell
        most = int(np.bincount(pair_table(rule, s, s, 512, 1024).q).max())
        parents_idle[cell] = (s // 512 * most - steps) * b * h
    assert parents_idle == {"sdar": 4096, "kanana2": 3584, "lfm2": 7680,
                            "ouro": 384, "smallthinker": 560}


# -- the sliding window's rule ------------------------------------------------

def _band_by_hand(nq, nk, window):
    """Query ``r`` sees key ``c`` iff ``c`` is up to ``r``'s place among
    the keys (bottom-right aligned) and fewer than ``window`` behind it."""
    return np.array([[0 <= r + nk - nq - c < window for c in range(nk)]
                     for r in range(nq)])


# window, queries, keys, query block, key block: shorter than, as long as
# and longer than a block, more keys than queries, blocks of two sizes
WINDOWS = [(1, 64, 64, 16, 16), (5, 64, 64, 16, 16), (16, 64, 64, 16, 16),
           (17, 64, 64, 16, 32), (40, 64, 96, 16, 16), (64, 64, 64, 32, 16),
           (1000, 64, 128, 16, 16)]


@pytest.mark.parametrize("window,nq,nk,bq,bk", WINDOWS)
def test_the_window_rule_is_its_dense_band(window, nq, nk, bq, bk):
    """``dense``, ``pairs``, ``tile`` and ``keep`` of every tile against
    the band written out position by position; ``sizes`` and
    ``lengths_ok`` as the causal rule's."""
    rule = SlidingWindow(window)
    band = _band_by_hand(nq, nk, window)
    np.testing.assert_array_equal(dense_mask(rule, nq, nk), band)
    assert visible_pairs(rule, nq, nk) == band.sum()
    assert rule.sizes(nq, nk) == (nq, nk)
    assert rule.lengths_ok(nq, nk) and not rule.lengths_ok(nk + 16, nk)
    off, crossed_twice = nk - nq, 0
    by_hand = {"plain": 0, "masked": 0, "skipped": 0}
    for q0 in range(0, nq, bq):
        for k0 in range(0, nk, bk):
            tile = band[q0:q0 + bq, k0:k0 + bk]
            needed, full = rule.tile(np.int32(q0), bq, np.int32(k0), bk, off)
            assert (bool(needed), bool(full)) == (tile.any(), tile.all())
            by_hand["plain" if tile.all() else
                    "masked" if tile.any() else "skipped"] += 1
            for q_axis in (0, 1):       # the forward's tile, the backward's
                shape = (bq, bk) if q_axis == 0 else (bk, bq)
                keep = np.asarray(rule.keep(shape, q0, k0, off, q_axis))
                np.testing.assert_array_equal(
                    keep, tile if q_axis == 0 else tile.T)
            # the diagonal and the band's far edge through one tile
            crossed_twice += bool(tile[-1, 0] == 0 and tile[0, -1] == 0
                                  and tile.any())
    # a tile holds bq + bk - 1 distances: both edges fit in one whose
    # window is shorter than a block, in none where it is longer than that
    if window < min(bq, bk):
        assert crossed_twice > 0
    if window >= bq + bk - 1:
        assert crossed_twice == 0
    assert tile_counts(rule, nq, nk, bq, bk) == by_hand


def test_a_window_past_every_key_is_the_causal_rule():
    wide, nq, nk = SlidingWindow(768), 256, 768
    np.testing.assert_array_equal(wide.dense(nq, nk), CAUSAL.dense(nq, nk))
    assert wide.pairs(nq, nk) == CAUSAL.pairs(nq, nk)
    a, b = (pair_table(r, nq, nk, 128, 256) for r in (wide, CAUSAL))
    for x, y in zip(a[:3], b[:3]):
        np.testing.assert_array_equal(x, y)
    assert tile_counts(wide, nq, nk, 128, 128) \
        == tile_counts(CAUSAL, nq, nk, 128, 128)
    with pytest.raises(ValueError):
        SlidingWindow(0)
    with pytest.raises(ValueError):         # one rule a call
        fa.rule_of(True, SlidingWindow(8))


def test_the_cells_band_in_closed_form():
    """At SmallThinker's shape the table lists the band alone: 44% of a
    causal call's pairs in 51% of its steps, none held."""
    rule, s = SlidingWindow(4096), 16384
    assert rule.pairs(s, s) == 4096 * 4097 // 2 + 12288 * 4096 == 58722304
    assert CAUSAL.pairs(s, s) == 134225920
    table = pair_table(rule, s, s, 512, 1024)
    assert (table.steps, table.held) == (140, 0)
    # a query block's key blocks: those its first query's window reaches
    # back to, up to its last query's own
    for i in range(s // 512):
        mine = table.k[table.q == i]
        assert mine.tolist() == list(range(
            max(0, (512 * i - 4095) // 1024), (512 * i + 511) // 1024 + 1))
    counts = tile_counts(rule, s, s, 512, 512)
    # a query block from the ninth on: 7 whole tiles between 2 crossed
    assert counts == {"plain": 7 * 24 + sum(range(8)),
                      "masked": 2 * 24 + 8,
                      "skipped": 1024 - 9 * 24 - sum(range(1, 9))}


def test_a_window_of_one_query_block_has_no_whole_tile():
    """Laguna-XS.2's window layers (ISSUE 47): a window of 512 keys at the
    kernels' 512 resident query rows. A query block's band touches its
    own key chunk, crossed by the diagonal, and the chunk before, crossed
    by the window's far edge: two chunks a query block, none whole, so the
    kernels compute about two tiles' scores for every tile of visible
    pairs."""
    rule, s = SlidingWindow(512), 16384
    assert visible_pairs(rule, s, s) == rule.pairs(s, s) \
        == 16384 * 512 - 512 * 511 // 2 == 8257792
    chunks = pair_table(rule, s, s, 512, 512)
    assert (chunks.steps, chunks.held) == (2 * 32 - 1, 0)
    for i in range(s // 512):
        assert chunks.k[chunks.q == i].tolist() == list(
            range(max(0, i - 1), i + 1))
    assert tile_counts(rule, s, s, 512, 512) == {
        "plain": 0, "masked": 63, "skipped": 1024 - 63}
    # the tiles run hold 63 x 512 x 512 pairs: twice the visible ones
    assert 63 * 512 * 512 / rule.pairs(s, s) == pytest.approx(2.0, abs=0.001)
    # at the shipped 1,024 fetched keys: one block where both chunks lie
    # in it, two where the band crosses a block's edge
    table = pair_table(rule, s, s, 512, 1024)
    assert (table.steps, table.held) == (1 + 16 + 2 * 15, 0)
    for i in range(s // 512):
        assert table.k[table.q == i].tolist() == list(range(
            max(0, (512 * i - 511) // 1024), (512 * i + 511) // 1024 + 1))


# heads, key/value heads: SmallThinker's 28 / 4 and one group of 7
@pytest.mark.parametrize("heads,kv_heads", [(28, 4), (7, 1)])
def test_the_kernels_under_the_window_with_heads_in_groups_of_seven(
        heads, kv_heads):
    """Forward and backward kernels (interpreter mode) under a window that
    is no multiple of a block, against ``attention_ref`` with the key and
    value heads repeated: out and all three gradients; dK and dV sum a
    group's 7 query heads."""
    rule, s, d = SlidingWindow(200), 512, 128
    keys = jax.random.split(jax.random.key(43), 4)
    q, k, v, dout = (jax.random.normal(kk, (1, s, h, d), jnp.float32)
                     for kk, h in zip(keys, (heads, kv_heads, kv_heads,
                                             heads)))
    assert fa.supported(q.shape, k.shape, v_shape=v.shape, mask=rule)
    out, pull = jax.vjp(lambda q, k, v: fa.flash_attention(
        q, k, v, mask=rule, blocks=(128, 256, 128)), q, k, v)

    def dense(q, k, v):
        k, v = (jnp.repeat(x, heads // kv_heads, axis=2) for x in (k, v))
        return attention_ref(q, k, v, mask_rule=rule)
    want, want_pull = jax.vjp(dense, q, k, v)
    # a dense band of its own beside the rule's: head 0 by hand
    band = jnp.asarray(_band_by_hand(s, s, 200))
    scores = jnp.einsum("qd,kd->qk", q[0, :, 0], k[0, :, 0]) / d ** 0.5
    by_hand = jax.nn.softmax(jnp.where(band, scores, -jnp.inf), -1) \
        @ v[0, :, 0]
    np.testing.assert_allclose(out[0, :, 0], by_hand, rtol=2e-5, atol=2e-5)
    for name, g, w in zip(("out", "dq", "dk", "dv"), (out,) + pull(dout),
                          (want,) + want_pull(dout)):
        assert g.shape == w.shape, name
        np.testing.assert_allclose(g, w, rtol=2e-5, atol=2e-5 * float(
            jnp.max(jnp.abs(w))), err_msg=name)


# -- a crossed tile by sub-tile (ISSUE 48) ------------------------------------

# cell -> (rule, positions, crossed 512 x 512 tiles of a head, their sub-
# tiles plain / masked / spared at 128 x 128 (ISSUE 48's table) and at the
# backward kernel's 256 x 256; batch, heads, key/value heads)
SUBTILES = {
    "laguna_window": (SlidingWindow(512), 16384, 63, (378, 252, 378),
                      (63, 126, 63), 1, 64, 8),
    "ouro": (CAUSAL, 4096, 8, (48, 32, 48), (8, 16, 8), 2, 16, 16),
    "sdar": (BlockDiffusion(8192, 4), 16384, 48, (192, 192, 384),
             (32, 96, 64), 1, 32, 4),
    "smallthinker_window": (SlidingWindow(4096), 16384, 56, (336, 224, 336),
                            (56, 112, 56), 1, 28, 4),
    "kanana2": (CAUSAL, 8192, 16, (96, 64, 96), (16, 32, 16), 2, 32, 32),
    "laguna_global": (CAUSAL, 16384, 32, (192, 128, 192), (32, 64, 32),
                      1, 48, 8),
}
SUBTILE_KINDS = ("plain", "masked", "spared")


def _kinds(name, kinds):
    group = process_group("kind")
    return {kind: group.child(kind).counter(name).value for kind in kinds}


@pytest.mark.parametrize("cell", sorted(SUBTILES))
def test_a_cells_crossed_tiles_in_sub_tiles(cell, _fresh_obs):
    """From the rule alone: at 128 x 128 a diagonal or far-edge tile
    spares 6 of its 16 sub-tiles and masks 4, a noisy-to-noisy tile of
    block diffusion spares 12; at the backward kernel's 256 x 256 they
    spare 1 of 4 and 2 of 4. The crossed tiles lay their sub-tiles out in
    one way or two. A lowered forward and backward call add the backward's
    sub-tiles x batch x heads to ``flash_subtiles_total{kind}`` (the
    forward runs a crossed tile whole), and ``flash_tiles_total{kind}``
    reads what it read."""
    rule, s, crossed, fine, coarse, b, h, h_kv = SUBTILES[cell]
    tiles = tile_counts(rule, s, s, 512, 512)
    assert tiles["masked"] == crossed
    for grain, counts in ((128, fine), (256, coarse)):
        assert subtile_counts(rule, s, s, 512, 512, grain, grain) == dict(
            zip(SUBTILE_KINDS, counts)), grain
        assert sum(counts) == (512 // grain) ** 2 * crossed
        # the visible pairs lie in the plain tiles and the sub-tiles run
        whole = tiles["plain"] * 512 * 512 + counts[0] * grain * grain
        assert whole < rule.pairs(s, s) < whole + counts[1] * grain * grain
    assert fine[2] / sum(fine) == {"sdar": 0.5}.get(cell, 0.375)
    assert coarse[2] / sum(coarse) == {"sdar": 1 / 3}.get(cell, 0.25)
    # 0 hidden, 1 crossed, 2 whole: the diagonal's layout under every
    # rule, and a window's far edge or block diffusion's noisy copy
    assert fb.sub_grain(512, 512) == (256, 256)
    layouts = [layout.tolist()
               for layout in fb.crossed_layouts(rule, s, s, 512, 512)]
    other = {"laguna_window": [[1, 2], [0, 1]], "sdar": [[1, 0], [0, 1]],
             "smallthinker_window": [[1, 2], [0, 1]]}
    assert sorted(layouts) == sorted([[[1, 0], [2, 1]]] + (
        [other[cell]] if cell in other else []))
    q, k = (jax.ShapeDtypeStruct((b, s, heads, 128), jnp.bfloat16)
            for heads in (h, h_kv))
    jax.make_jaxpr(jax.grad(lambda q, k, v: jnp.sum(fa.flash_attention(
        q, k, v, mask=rule).astype(jnp.float32)), argnums=(0, 1, 2)))(q, k, k)
    assert _kinds("flash_subtiles_total", SUBTILE_KINDS) == {
        kind: n * b * h for kind, n in zip(SUBTILE_KINDS, coarse)}
    assert _kinds("flash_tiles_total", tiles) == {
        kind: 2 * n * b * h for kind, n in tiles.items()}


def test_no_rule_no_sub_tile_and_a_tile_of_one_sub_tile_is_itself(
        _fresh_obs):
    """``NO_MASK`` crosses no tile; at blocks of 128 a crossed tile is its
    one sub-tile, masked; a side that the grain does not divide is
    whole."""
    assert subtile_counts(NO_MASK, 512, 512, 256, 256, 128, 128) == dict(
        zip(SUBTILE_KINDS, (0, 0, 0)))
    assert subtile_patterns(NO_MASK, 512, 512, 256, 256, 128, 128) == ()
    assert fb.crossed_layouts(NO_MASK, 512, 512, 512, 512) is None
    assert fb.crossed_layouts(CAUSAL, 512, 512, 128, 128) is None
    assert fb.sub_grain(128, 128) == (128, 128)
    assert fb.sub_grain(512, 64) == (256, 64)
    assert subtile_counts(CAUSAL, 512, 512, 128, 128, 128, 128) == dict(
        zip(SUBTILE_KINDS, (0, 4, 0)))
    x = jax.ShapeDtypeStruct((1, 512, 2, 64), jnp.float32)
    jax.make_jaxpr(jax.grad(lambda q, k, v: jnp.sum(fa.flash_attention(
        q, k, v)), argnums=(0, 1, 2)))(x, x, x)
    assert _kinds("flash_subtiles_total", SUBTILE_KINDS) == dict(
        zip(SUBTILE_KINDS, (0, 0, 0)))
    assert _kinds("flash_tiles_total", ("plain",)) == {"plain": 2 * 2}


def test_a_step_finds_its_layout_by_the_sub_tiles_that_tell_them_apart(
        _fresh_obs, monkeypatch):
    """Three layouts under a window with fewer queries than keys: one
    sub-tile's kind tells them apart; a rule with more layouts than the
    kernel holds bodies for runs its crossed tiles whole, and the counter
    says so."""
    rule = SlidingWindow(300)
    monkeypatch.setattr(fb, "_SUB", 128)
    layouts = fb.crossed_layouts(rule, 256, 768, 256, 256)
    assert len(layouts) == 3 and len(fb._probes(layouts)) == 1
    (j, t), = fb._probes(layouts)
    assert len({int(layout[j, t]) for layout in layouts}) == 3
    assert fb._probes(fb.crossed_layouts(CAUSAL, 512, 512, 512, 512)) == []
    monkeypatch.setattr(fb, "_LAYOUTS", 2)
    assert fb.crossed_layouts(rule, 256, 768, 256, 256) is None
    fb._count_subtiles(rule, 256, 768, 256, 256, None, 1)
    assert _kinds("flash_subtiles_total", SUBTILE_KINDS) == dict(
        zip(SUBTILE_KINDS, (0, 3, 0)))


# rule, positions, (resident, fetched, chunk), the sub-tile's side: the
# diagonal through every strip at the kernel's grain and at 128; a
# window's two edges through one tile, then through two; block diffusion's
# quadrants
STRIPS = {
    "causal": (CAUSAL, 512, (512, 512, 512), 256),
    "causal_128": (CAUSAL, 512, (512, 512, 512), 128),
    "window_both_edges": (SlidingWindow(200), 512, (512, 512, 512), 128),
    "window_far_edge": (SlidingWindow(256), 1024, (512, 512, 512), 256),
    "block_diffusion": (BlockDiffusion(256, 32), 512, (256, 256, 256), 128),
}


@pytest.mark.parametrize("case", sorted(STRIPS))
def test_a_crossed_tile_by_sub_tile_gives_the_whole_tiles_gradients(
        case, _fresh_obs, monkeypatch):
    """The backward kernel's three gradients with a crossed tile run by
    sub-tile against the same kernel running it whole (the parent's body:
    no layouts) and the forward's ``out`` and LSE, which both are given,
    against the dense mask's softmax; some sub-tile is spared in every
    case."""
    rule, s, blocks, grain = STRIPS[case]
    keys = jax.random.split(jax.random.key(48), 4)
    q, k, v, dout = (jax.random.normal(kk, (1, s, 1, 64), jnp.float32)
                     for kk in keys)
    out, lse = fa._flash_fwd(q, k, v, 0.125, rule, blocks=blocks)
    scores = jnp.einsum("bqhd,bkd->bhqk", q, k[:, :, 0]) * 0.125
    scores = jnp.where(jnp.asarray(dense_mask(rule, s, s)), scores, -jnp.inf)
    np.testing.assert_allclose(
        lse, jax.nn.logsumexp(scores, -1).reshape(1, s), rtol=2e-5,
        atol=2e-5, err_msg="lse")
    np.testing.assert_allclose(
        out, jnp.einsum("bhqk,bkd->bqhd", jax.nn.softmax(scores, -1),
                        v[:, :, 0]), rtol=2e-5, atol=2e-5, err_msg="out")

    def grads(sub):
        monkeypatch.setattr(fb, "_SUB", sub)
        fb._bwd_call.clear_cache()
        try:
            return fb.flash_attention_bwd(q, k, v, out, lse, dout, 0.125,
                                          rule, blocks=blocks)
        finally:
            monkeypatch.undo()
            fb._bwd_call.clear_cache()
    got = grads(grain)
    assert _kinds("flash_subtiles_total", ("spared",))["spared"] > 0
    for name, g, w in zip(("dq", "dk", "dv"), got, grads(1024)):
        np.testing.assert_allclose(g, w, rtol=2e-5, atol=2e-5 * float(
            jnp.max(jnp.abs(w))), err_msg=name)
