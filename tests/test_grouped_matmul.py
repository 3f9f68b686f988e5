"""The repo's grouped matrix product (``ops/pallas/grouped_matmul.py``) in
interpret mode against ``jax.lax.ragged_dot`` and its autodiff: the
product, ``dx`` and ``dw`` over fills with empty groups, shared and
crossed row tiles and a NaN-poisoned tail; which operands it takes; the
arm ``nn/layer_moe.py::grouped_matmul`` counts."""

import functools
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle1_tpu.obs as obs
from decoder_cases import fresh_obs  # noqa: F401
from paddle1_tpu.core.flags import auto_partitioned_region
from paddle1_tpu.nn import layer_moe
from paddle1_tpu.ops.pallas import grouped_matmul as gm

BF16, F32, I32 = jnp.bfloat16, jnp.float32, jnp.int32
FORMS = ("product", "dx", "dw")

# name: (m, k, n, sizes, dtype, row tile, VMEM for the blocks or None)
FILLS = {
    # groups without rows first, in the middle and last; every row held
    "empty_groups_full": (512, 384, 320, (0, 200, 0, 0, 312, 0), F32, 64,
                          None),
    # a third of the rows held, the tail of every input NaN
    "a_third_nan_tail": (768, 384, 320, (100, 0, 30, 126), F32, 64, None),
    # one group over many row tiles, then several inside one
    "crossed_and_shared": (1024, 320, 384, (700, 9, 1, 20, 17, 150), F32, 64,
                           None),
    # nothing held but one row, in the last group
    "one_row": (256, 384, 320, (0, 0, 1), F32, 64, None),
    # the blocks' VMEM so small that k and n go in tiles of 128: the
    # accumulator over k, the weight block's index past the pairs
    "tiled_widths": (512, 384, 256, (130, 0, 200, 60), F32, 64, 400 << 10),
    "tiled_widths_bf16": (512, 384, 256, (130, 0, 200, 60), BF16, 64,
                          400 << 10),
    # nemotron's widths at the shipped row tile, bf16 as the cell runs them
    "nemotron_up": (512, 2688, 1856, (150, 0, 41, 120), BF16, 256, None),
    "nemotron_down": (512, 1856, 2688, (0, 300, 12, 100), BF16, 256, None),
    "nemotron_up_f32_full": (256, 2688, 1856, (100, 156), F32, 256, None),
}


@functools.lru_cache(maxsize=None)
def _both(fill):
    """((y, dx, dw) of the kernel, of ``lax.ragged_dot``, held rows): the
    kernel's inputs NaN past the groups, the reference's zero there."""
    m, k, n, sizes, dtype, tm, vmem = FILLS[fill]
    held = sum(sizes)
    keys = jax.random.split(jax.random.key(len(fill)), 3)
    x, d = (jax.random.normal(key, shape, F32).astype(dtype)
            for key, shape in zip(keys, ((m, k), (m, n))))
    w = (jax.random.normal(keys[2], (len(sizes), k, n), F32)
         * k ** -0.5).astype(dtype)
    sizes = jnp.asarray(sizes, I32)
    tail = (jnp.arange(m) >= held)[:, None]
    with mock.patch.multiple(gm, _ROW_TILE=tm,
                             _VMEM_BLOCKS=vmem or gm._VMEM_BLOCKS):
        assert gm._tiles(m, k, n, jnp.dtype(dtype).itemsize)
        poison = lambda a: jnp.where(tail, jnp.nan, a).astype(dtype)
        y, vjp = jax.vjp(lambda x, w: gm.grouped_matmul(x, w, sizes),
                         poison(x), w)
        got = (y,) + vjp(poison(d))
    clean = lambda a: jnp.where(tail, 0, a).astype(dtype)
    y, vjp = jax.vjp(lambda x, w: jax.lax.ragged_dot(x, w, sizes),
                     clean(x), w)
    want = (y,) + vjp(clean(d))
    return ([np.asarray(a, np.float32) for a in got],
            [np.asarray(a, np.float32) for a in want], held)


@pytest.mark.parametrize("form", FORMS)
@pytest.mark.parametrize("fill", sorted(FILLS))
def test_a_form_is_ragged_dots(fill, form):
    """Each form on the rows that hold a pick (all of ``dw``): finite
    whatever stands past the groups, and ``lax.ragged_dot``'s to the
    rounding of one more float32 sum (bf16: of the output's cast)."""
    got, want, held = _both(fill)
    i = FORMS.index(form)
    got, want = ((a[i] if form == "dw" else a[i][:held])
                 for a in (got, want))
    assert got.shape == want.shape and np.isfinite(got).all()
    bf16 = FILLS[fill][4] is BF16
    scale = np.abs(want).max() if want.size else 1.0
    np.testing.assert_allclose(got, want, rtol=2e-2 if bf16 else 2e-5,
                               atol=(1e-2 if bf16 else 2e-5) * scale)
    if form == "dw":        # a group without rows: zeros, written
        for g, size in enumerate(FILLS[fill][3]):
            assert size or not got[g].any()


@pytest.mark.parametrize("fill", ["tiled_widths", "crossed_and_shared"])
def test_the_table_visits_the_pairs_that_hold_a_row(fill):
    """``_table``: a step a (group, row tile) pair in the rows' order,
    ``live`` of them; the steps past repeat the last."""
    m, _, _, sizes, _, tm, _ = FILLS[fill]
    for every in (False, True):
        bounds, group, tile, live = (np.asarray(a) for a in gm._table(
            jnp.asarray(sizes, I32), m, tm, every))
        ends = np.cumsum(sizes)
        want = [(g, t) for g, (lo, hi) in enumerate(zip(ends - sizes, ends))
                for t in (range(lo // tm, (hi - 1) // tm + 1) if hi > lo
                          else [min(lo // tm, m // tm - 1)] if every else [])]
        assert live[0] == len(want) <= len(group) == m // tm + len(sizes) - 1
        assert list(zip(group, tile))[:len(want)] == want
        assert set(zip(group[len(want):], tile[len(want):])) <= {want[-1]}
        assert list(bounds) == [0] + list(ends)


def _operands(m, k, n, g, dtype=BF16, weights=None):
    return (jax.ShapeDtypeStruct((m, k), dtype),
            jax.ShapeDtypeStruct((g, k, n), weights or dtype))


# a cell's two products: rows, held experts, hidden, the first product's
# width out, an expert's width
CELLS = {
    "nemotron3_nano_30b_a3b": (9216, 8, 2688, 1856, 1856),
    "kanana2_30b_a3b": (36864, 16, 2048, 1536, 768),
    "sdar_30b_a3b": (49152, 16, 2048, 1536, 768),
    "lfm2_24b_a2b": (24576, 8, 2048, 3072, 1536),
    "smallthinker_21b_a3b": (36864, 8, 2560, 1536, 768),
    "laguna_xs2_33b_a3b": (24576, 16, 2048, 1024, 512),
}


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_supported_admits_nemotrons_products_and_no_other_cells(cell):
    rows, held, hidden, up, width = CELLS[cell]
    mine = cell.startswith("nemotron3")
    assert gm.supported(*_operands(rows, hidden, up, held)) == mine
    assert gm.supported(*_operands(rows, width, hidden, held)) == mine


def test_supported_reads_the_shape_and_the_dtype():
    ok = lambda *a, **kw: gm.supported(*_operands(*a, **kw))
    assert [gm.xla_tile(w) for w in (2688, 1856, 2048, 1536, 768, 2560, 1792,
                                     64)] \
        == [128, 128, 512, 512, 256, 512, 256, 128]
    assert ok(9216, 2688, 1856, 8, F32) and ok(512, 1856, 2688, 2)
    assert ok(9216, 2688, 2048, 8)          # one width at 128 is enough
    assert not ok(9216, 2560, 1792, 8)      # 512 and 256
    assert not ok(9216 + 128, 2688, 1856, 8)    # rows in no whole tile
    assert not ok(9216, 2688, 1856 + 32, 8)     # half a lane tile's half
    assert not ok(9216, 2688, 192, 8) and not ok(512, 64, 32, 4)  # narrow
    assert not ok(9216, 2688, 1856, 8, jnp.float16)
    assert not ok(9216, 2688, 1856, 8, BF16, weights=F32)
    # widths whose blocks fit in no tiles: float32 [256, k] x [k, 64 * 401]
    assert not ok(9216, 64 * 401, 64 * 401, 8, F32)
    assert not gm.supported(jax.ShapeDtypeStruct((8, 9216, 2688), BF16),
                            jax.ShapeDtypeStruct((8, 2688, 1856), BF16))


def test_the_tiles_come_from_the_shape():
    """k and n whole where the blocks fit, else the widest multiples of
    128 that divide them; the row tile 256."""
    assert gm._tiles(9216, 2688, 1856, 2) == (256, 2688, 1856)
    assert gm._tiles(9216, 1856, 2688, 2) == (256, 1856, 2688)
    assert gm._tiles(9216, 2688, 1856, 2, dw=True) == (256, 896, 1856)
    assert gm._tiles(9216, 1856, 2688, 2, dw=True) == (256, 1856, 896)
    assert gm._tiles(9216, 2688, 1856, 4)[0] == 256
    assert gm._widths(2688) == [2688, 896, 384, 128]
    assert gm._widths(1856) == [1856]
    assert gm._tiles(9216 + 8, 2688, 1856, 2) is None
    for dw in (False, True):       # float32: what fits, in tiles
        tiles = gm._tiles(9216, 2688, 1856, 4, dw=dw)
        assert tiles != (256, 2688, 1856)
        assert gm._block_bytes(*tiles, 4, dw) <= gm._VMEM_BLOCKS


def _arms():
    arms = obs.process_group("arm")
    return {arm: arms.child(arm).counter("moe_grouped_matmul_arm_total").value
            for arm in ("kernel", "xla")}


@pytest.mark.parametrize("cell,arm", [("nemotron3_nano_30b_a3b", "kernel"),
                                      ("kanana2_30b_a3b", "xla")])
def test_a_traced_product_counts_the_arm_it_took(fresh_obs, cell, arm):
    """``p1t_moe_grouped_matmul_arm_total{arm}``: one increment a traced
    ``grouped_matmul``, two an expert's feed-forward; the transposes are
    the arm's own and are not counted again."""
    rows, held, hidden, up, width = CELLS[cell]
    struct = lambda *shape: jax.ShapeDtypeStruct(shape, BF16)
    ffn = layer_moe.plain_expert_ffn if up == width else layer_moe.expert_ffn

    def loss(xs, first, down, sizes):
        return jnp.sum(ffn(xs, sizes, first, down, jax.nn.relu).astype(F32))
    text = str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(
        struct(rows, hidden), struct(held, hidden, up),
        struct(held, width, hidden), jax.ShapeDtypeStruct((held,), I32)))
    other = {"kernel": "xla", "xla": "kernel"}[arm]
    assert _arms() == {arm: 2, other: 0}
    assert f'p1t_moe_grouped_matmul_arm_total{{arm="{arm}"}} 2' \
        in obs.registry.render_process_groups()
    kernels = {name: text.count(f"name={name}\n") + text.count(
        f"name={name} ") for name in (
        "p1t_grouped_matmul_fwd", "p1t_grouped_matmul_bwd_dx",
        "p1t_grouped_matmul_bwd_dw")}
    assert kernels == dict.fromkeys(kernels, 2 if arm == "kernel" else 0)
    assert ("ragged_dot" in text) == (arm == "xla")


def test_a_step_xla_partitions_stays_with_xlas_kernel(fresh_obs):
    rows, held, hidden, up, _ = CELLS["nemotron3_nano_30b_a3b"]
    x, w = _operands(rows, hidden, up, held)
    with auto_partitioned_region():
        text = str(jax.make_jaxpr(layer_moe.grouped_matmul)(
            x, w, jax.ShapeDtypeStruct((held,), I32)))
    assert _arms() == {"kernel": 0, "xla": 1}
    assert "ragged_dot" in text and "p1t_grouped_matmul" not in text
