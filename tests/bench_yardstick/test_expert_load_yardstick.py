"""CPU tests of what PR 36 added to the yardstick: three per-layer metrics
that read the counts the routed-expert layers keep on the device
(``benchmarks/reducers/expert_load.py``), in the cells that have such
layers (kanana2's and sdar's since PR 36, lfm2's since PR 41). Nothing here
is a device metric."""

import argparse
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import spec  # noqa: E402
from benchmarks.reducers import expert_load  # noqa: E402

FILES = spec.layer_metrics()
CELLS = ["kanana2_30b_a3b.pretrain_s8192", "sdar_30b_a3b.blockdiff_s8192",
         "lfm2_24b_a2b.pretrain_s16384"]
NEW = {"moe_held_picks_pct": ("%", "samples_per_s", "held_picks_pct"),
       "moe_expert_rows_max": ("rows", "samples_per_s", "expert_rows_max"),
       "moe_late_picks": ("picks", "step_ms_p95", "late_picks")}


@pytest.mark.parametrize("name", sorted(NEW))
def test_the_metric_file_and_its_entry(name):
    unit, moves, read = NEW[name]
    metric = FILES[name]
    entry = {m["name"]: m for m in spec.benchmark()["per_layer"]}[name]
    assert (metric["unit"], metric["moves"], metric["read"]) == (
        unit, moves, read)
    assert metric["reducer"] == "expert_load"
    assert metric["source"] == entry["source"] == "program_counter"
    assert metric["better"] == entry["better"] == "lower"
    assert metric["layer"] == FILES["moe_route_ms"]["layer"]
    # the cells with a routed-expert layer list it, and beside it the
    # scope that holds the layer's time
    assert set(CELLS) <= set(entry["workloads"])
    for cell in entry["workloads"]:
        assert "moe_route_ms" in {m["name"] for m in spec.per_layer_for(cell)}


# two layers, 4 steps each, 1,000 picks made a layer a step
LOAD = {
    "layers.1.mlp.moe": {
        "rows": [400, 200, 120, 80], "held_picks": 820, "late_picks": 20,
        "late_steps": 1, "steps": 4, "picks_made_a_step": 1000,
        "capacity_rows": 512, "held": 4, "num_experts": 16},
    "layers.2.mlp.moe": {
        "rows": [100, 100, 100, 100], "held_picks": 400, "late_picks": 0,
        "late_steps": 0, "steps": 4, "picks_made_a_step": 1000,
        "capacity_rows": 512, "held": 4, "num_experts": 16},
}


def test_the_reducer_on_counters_made_by_hand():
    read = {n: expert_load.reduce({}, FILES[n], LOAD) for n in NEW}
    # (820 / 4000 + 400 / 4000) / 2 layers, in %
    assert read["moe_held_picks_pct"] == pytest.approx(15.25)
    # the fullest expert of either layer ran 400 rows in 4 steps
    assert read["moe_expert_rows_max"] == pytest.approx(100.0)
    # 20 late picks in 4 steps of one layer, none in the other
    assert read["moe_late_picks"] == pytest.approx(5.0)
    # a layer that has not run yet is left out of the means
    idle = dict(LOAD, idle={**LOAD["layers.2.mlp.moe"], "steps": 0,
                            "picks_made_a_step": None})
    assert expert_load.reduce({}, FILES["moe_held_picks_pct"], idle) \
        == pytest.approx(15.25)
    with pytest.raises(ValueError):
        expert_load.reduce({}, {"name": "x", "read": "nothing"}, LOAD)


def test_no_counters_no_metric(monkeypatch):
    """No engine has stepped in this process (or its model has no expert
    layer), or the program has no such reader at all (the parent's): the
    metric is left out of the line, and nothing is raised."""
    from paddle1_tpu.obs import costmodel
    monkeypatch.setattr(costmodel, "_stepping_engine", lambda: None)
    assert costmodel.step_expert_load() is None
    for name in NEW:
        assert expert_load.reduce({}, FILES[name]) is None
        assert expert_load.reduce({}, FILES[name], {}) is None
    monkeypatch.delattr(costmodel, "step_expert_load")
    assert expert_load.program_load() is None
    assert expert_load.reduce({}, FILES["moe_late_picks"]) is None


@pytest.mark.drives_a_run
@pytest.mark.parametrize("cell", CELLS)
def test_a_traced_rehearsal_prints_the_three_metrics(cell, capsys,
                                                      monkeypatch):
    """A CPU rehearsal of each expert cell with ``--trace 1``: the three
    metrics are on the line ``@cpu_rehearsal``, and they are what the
    engine's own counters give by the test's arithmetic."""
    from benchmarks import run
    from paddle1_tpu.obs import costmodel
    seen = {}
    real = run.first_steps

    def first_steps(*a, **kw):
        out = real(*a, **kw)
        seen["engine"] = out[0].engine
        return out

    monkeypatch.setattr(run, "first_steps", first_steps)
    result = run.run(argparse.Namespace(
        workload=cell, seed=2 ** 31 + 36, seconds=0.3, trace=1, rehearsal=1))
    capsys.readouterr()
    assert result["correct"] is True and result["failed"] == 0
    got = {n: result["metrics"][n + "@cpu_rehearsal"] for n in NEW}
    assert {n: v["unit"] for n, v in got.items()} == {
        n: NEW[n][0] for n in NEW}
    # the engine is gone from the run; its counters were read before
    load = seen.pop("engine").expert_load()
    assert load and costmodel.step_expert_load() == load
    steps = {c["steps"] for c in load.values()}
    # every step the engine ran: the set-up's too (10 at the cell's size)
    loop = spec.loop(spec.cell(cell), rehearsal=True)
    assert steps == {result["attempted"] + loop["check_steps"]
                     + loop["warm_steps"]}
    full = spec.loop(spec.cell(cell))
    assert full["check_steps"] + full["warm_steps"] == 10
    layers = list(load.values())
    held = [100.0 * c["held_picks"] / (c["steps"] * c["picks_made_a_step"])
            for c in layers]
    assert got["moe_held_picks_pct"]["value"] == pytest.approx(
        np.mean(held), rel=1e-12)
    assert got["moe_expert_rows_max"]["value"] == pytest.approx(
        max(max(c["rows"]) for c in layers) / steps.pop(), rel=1e-12)
    # every pick has a row at the rehearsal's size: none is late
    for c in layers:
        assert c["capacity_rows"] == c["picks_made_a_step"]
        assert sum(c["rows"]) == c["held_picks"] and c["late_picks"] == 0
        assert 0 < c["held_picks"] <= c["steps"] * c["picks_made_a_step"]
    assert got["moe_late_picks"]["value"] == 0.0
