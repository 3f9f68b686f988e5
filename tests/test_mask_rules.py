"""The table of needed (query block, key block) pairs that the blockwise
attention kernels walk (ISSUE 39; ``ops/pallas/mask_rules.py::pair_table``)
against what ``rule.tile`` says pair by pair; held steps (a backward call
in key ranges) change nothing; the counter of a lowered call's grid steps.
CPU; the kernels in interpreter mode."""

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
                                               BlockDiffusion, pair_table,
                                               tile_counts)

# name -> (rule, queries, keys, resident block, fetched block)
SMALL = {
    "causal": (CAUSAL, 512, 512, 128, 128),
    "causal_two_chunks": (CAUSAL, 512, 512, 128, 256),
    "causal_fewer_queries": (CAUSAL, 256, 768, 128, 256),
    "no_mask": (NO_MASK, 512, 256, 128, 128),
    "one_block": (BlockDiffusion(256, 256), 512, 512, 128, 128),
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
                    "ouro": 12}[cell]


RANGED = {
    "causal": (CAUSAL, 512, 512, 128, 128, 2),
    "causal_four_ranges": (CAUSAL, 512, 512, 128, 128, 4),
    "causal_fewer_queries": (CAUSAL, 256, 768, 128, 128, 3),
    "no_mask": (NO_MASK, 256, 512, 128, 128, 2),
    "block_diffusion": (BlockDiffusion(256, 4), 512, 512, 128, 128, 2),
    "block_diffusion_clean_first": (BlockDiffusion(256, 4, False), 512, 512,
                                    128, 128, 4),
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


@pytest.mark.parametrize("case", ["causal", "block_diffusion", "no_mask"])
def test_a_lowered_call_counts_its_grid_steps(case, _fresh_obs):
    """``flash_grid_steps_total{kind}``: a lowered forward and backward
    call each walk the table once a batch x head; nothing is held."""
    rule = {"causal": CAUSAL, "block_diffusion": BlockDiffusion(256, 4),
            "no_mask": NO_MASK}[case]
    q, k = (jax.ShapeDtypeStruct((2, 512, heads, 64), jnp.float32)
            for heads in (4, 2))
    jax.jit(jax.grad(lambda q, k, v: jnp.sum(fa.flash_attention(
        q, k, v, mask=rule, blocks=(128, 256, 128))),
        argnums=(0, 1, 2))).lower(q, k, k)
    pairs = pair_table(rule, 512, 512, 128, 256).steps
    assert _steps() == {"working": 2 * pairs * 2 * 4, "held": 0}


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
              "lfm2": (1, 32, 8, 64, 64), "ouro": (2, 16, 16, 128, 128)}
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
        most = int(np.bincount(pair_table(rule, s, s, 512, 1024).q).max())
        parents_idle[cell] = (s // 512 * most - steps) * b * h
    assert parents_idle == {"sdar": 4096, "kanana2": 3584, "lfm2": 7680,
                            "ouro": 384}
