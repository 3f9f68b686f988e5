"""CPU tests of the shares of a peak that the benchmark reads of single
kernels: ``reducers/kernel_mxu_pct.py`` over a synthetic view of every cell
that lists one of its metrics (the reducer is told which cell runs and
takes the FLOPs of that cell's configuration and size), the one count of
the attention kernels' products behind them, and the names by which
``run.py`` watches a share. (The op's share of the HBM peak:
``test_lfm2_yardstick.py``, beside the scopes it reads.) Nothing here is a
device metric."""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import spec, traffic  # noqa: E402
from benchmarks.model_flops import attention_kernels  # noqa: E402
from benchmarks.reducers import kernel_mxu_pct  # noqa: E402

FILES = spec.layer_metrics()
ENTRIES = {m["name"]: m for m in spec.benchmark()["per_layer"]}
SHARES = ("kernel_mxu_pct", "op_hbm_pct", "step_mfu_pct")
# (metric, cell) for every cell that lists a metric of the reducer
LISTED = [(name, cell) for name, metric in sorted(FILES.items())
          if metric["reducer"] == "kernel_mxu_pct"
          for cell in ENTRIES[name].get("workloads",
                                        spec.names_in("workloads"))]
PEAK = 197e12
# backward FLOPs over forward FLOPs: 5 products to 2 where keys and
# values have one width, (3 x 192 + 2 x 128) to (192 + 128) at kanana2
RATIO = {"sdar_30b_a3b.blockdiff_s8192": 2.5,
         "lfm2_24b_a2b.pretrain_s16384": 2.5,
         "ouro_2p6b.pretrain_s4096": 2.5,
         "kanana2_30b_a3b.pretrain_s8192": 2.6}


def _view(names, ms, steps=4):
    """One chip's view whose every instruction ran ``ms`` in each step."""
    ops = {f"%{n} = bf16[8,128]{{1,0}} custom-call(%p.{i})": 1e-3 * ms * steps
           for i, n in enumerate(names)}
    return {"ops": ops, "step_s": [len(names) * 1e-3 * ms] * steps,
            "busy_s": len(names) * 1e-3 * ms * steps}


def test_the_four_attention_cells_list_the_kernels_share():
    assert {("attention_kernel_mxu_roofline", cell) for cell in RATIO} \
        <= set(LISTED)
    metric = FILES["attention_kernel_mxu_roofline"]
    assert metric["flops"] == "attention_kernel_flops"
    assert "cell" not in metric and "valid_in" not in metric


@pytest.mark.parametrize("name,cell_name", LISTED)
def test_a_kernels_share_of_the_matrix_unit_in_a_listed_cell(name, cell_name):
    """The FLOPs are the running cell's: ``ctx`` says which cell runs."""
    metric = FILES[name]
    cell = spec.cell(cell_name)
    cfg = spec.config(cell["config"])
    flops = getattr(spec.module("model_flops", cfg), metric["flops"])(
        cfg, traffic.environment(cfg, cell))
    assert all(v > 0 for v in flops.values())
    if cell_name in RATIO:
        assert set(flops) == set(attention_kernels.PRODUCTS)
        assert flops[attention_kernels.BACKWARD] \
            == RATIO[cell_name] * flops[attention_kernels.FORWARD]
    # the time the kernels would take at half the peak, spread over their
    # instructions, as the chip names them: a running number behind the
    # kernel's name, and what the program says more behind the family's
    seconds = sum(flops.values()) / (0.5 * PEAK)
    named = [k + tail for k, tail in zip(flops, (".16", "_dkv.17", ".3"))]
    view = _view(named + ["fusion.1", "p1t_sum_picks_fwd.2"],
                 1e3 * seconds / len(named))
    ctx = {"views": [view, view], "peak_flops_per_s": PEAK,
           "cell": cell, "config": cfg}
    assert kernel_mxu_pct.reduce(ctx, metric) == pytest.approx(50.0)
    per = kernel_mxu_pct.seconds_a_step(view, list(flops))
    assert all(s == pytest.approx(seconds / len(named)) for s in per.values())
    # a rehearsal has no peak, a run with no trace no view, a program
    # without the kernels (or one that took XLA's composition) no such
    # instruction: nothing, no raise
    assert kernel_mxu_pct.reduce({**ctx, "peak_flops_per_s": None},
                                 metric) is None
    assert kernel_mxu_pct.reduce({**ctx, "views": []}, metric) is None
    bare = _view(["fusion.1", "p1t_sum_picks_fwd.2"], 1.0)
    assert kernel_mxu_pct.reduce({**ctx, "views": [bare]}, metric) is None


def test_another_cells_trace_is_read_with_the_running_cells_flops():
    """What the metric's file named before PR 41 (one cell, whatever
    ran): the same view reads by the FLOPs of whichever cell ``ctx``
    names."""
    metric = FILES["attention_kernel_mxu_roofline"]
    view = _view(["p1t_flash_attention_fwd.1", "p1t_flash_attention_bwd.2"],
                 10.0)
    read = {}
    for name in RATIO:
        cell = spec.cell(name)
        cfg = spec.config(cell["config"])
        read[name] = kernel_mxu_pct.reduce(
            {"views": [view], "peak_flops_per_s": PEAK, "cell": cell,
             "config": cfg}, metric)
    assert len({round(v, 6) for v in read.values()}) == len(RATIO)
    # sdar's two kernels, 5 calls each, 7 products a visible pair
    pairs = 8192 * (8192 + 4)
    assert read["sdar_30b_a3b.blockdiff_s8192"] == pytest.approx(
        100 * 5 * 7 * 2 * 128 * 32 * pairs / 20e-3 / PEAK)
    # a configuration whose module counts no such kernels reads nothing
    bert = spec.cell("bert_base.pretrain_s128")
    assert kernel_mxu_pct.reduce(
        {"views": [view], "peak_flops_per_s": PEAK, "cell": bert,
         "config": spec.config("bert_base")}, metric) is None


def test_a_kernel_is_found_by_its_name_with_or_without_more_behind_it():
    names = ["p1t_flash_attention_fwd", "p1t_flash_attention_bwd"]
    view = _view(["p1t_flash_attention_fwd.26", "p1t_flash_attention_bwd.4",
                  "p1t_flash_attention_bwd_dkv.17",
                  "p1t_flash_attention_bwd_dq.12", "p1t_flash_attention.5",
                  "p1t_flash_attention_fwdx.6", "fusion.7"], 1.0)
    assert kernel_mxu_pct.seconds_a_step(view, names) == pytest.approx(
        {"p1t_flash_attention_fwd": 1e-3, "p1t_flash_attention_bwd": 3e-3})
    # the longer of two names that fit takes the instruction
    # (``chip_smoke.py`` asks by the name the program gives today)
    assert kernel_mxu_pct.seconds_a_step(
        view, ["p1t_flash_attention_bwd", "p1t_flash_attention_bwd_dkv"]) \
        == pytest.approx({"p1t_flash_attention_bwd": 2e-3,
                          "p1t_flash_attention_bwd_dkv": 1e-3})


def test_the_attention_kernels_products():
    """2 products forward, 5 in the one backward kernel (PR 35): the stale
    2 + 4 + 3 over three names read 9/7 of the work."""
    got = attention_kernels.flops(pairs=10, heads=3, key_width=128)
    assert got == {"p1t_flash_attention_fwd": 2 * 2 * 128 * 3 * 10,
                   "p1t_flash_attention_bwd": 5 * 2 * 128 * 3 * 10}
    wide = attention_kernels.flops(10, 3, 192, 128)
    assert wide == {"p1t_flash_attention_fwd": 2 * 10 * 3 * (192 + 128),
                    "p1t_flash_attention_bwd": 2 * 10 * 3 * (3 * 192
                                                             + 2 * 128)}
    assert sum(got.values()) * 9 == 7 * (2 + 4 + 3) * 2 * 128 * 3 * 10


@pytest.mark.parametrize("name", sorted(
    n for n, m in FILES.items() if m["reducer"] in SHARES))
def test_a_share_of_a_peak_has_a_name_the_harness_watches(name):
    """``run.py`` refuses a reading above 105% under a name that ends in
    ``_roofline`` or holds ``mfu``; a share of a peak has such a name, is
    in % and is better higher."""
    metric = FILES[name]
    assert name.endswith("_roofline") or "mfu" in name
    assert (metric["unit"], metric["better"]) == ("%", "higher")
