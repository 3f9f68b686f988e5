"""Where the benchmark's data lives and how it is found by name.

``BENCHMARK.json`` (repo root) names the cells, configurations and
metrics; everything that belongs to one of them sits in a file of its own
under this directory, found by that name. Adding a cell, a configuration or
a per-layer metric adds files and entries and edits none.
"""

import importlib
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load_json(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def benchmark():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def names_in(directory, ext=".json"):
    return sorted(f[:-len(ext)] for f in os.listdir(os.path.join(HERE, directory))
                  if f.endswith(ext) and not f.startswith("_"))


def cell(name):
    """The cell's file, checked against its entry in BENCHMARK.json."""
    entry = {w["name"]: w for w in benchmark()["workloads"]}.get(name)
    if entry is None:
        raise SystemExit(f"benchmarks: no cell {name!r} in BENCHMARK.json")
    data = load_json("workloads", name + ".json")
    for key in ("name", "config", "traffic", "chips"):
        if data[key] != entry[key]:
            raise SystemExit(
                f"benchmarks: workloads/{name}.json says {key}="
                f"{data[key]!r}, BENCHMARK.json says {entry[key]!r}")
    return data


# The trainer's loop, the same in every cell unless its file says otherwise
# under ``loop``: host batches in the pool, a loss read back every so many
# steps, the steps the reference follows and the warm steps after them
# (together a multiple of ``readback_every``, so that the window opens on a
# drained device), and where in the window a traced run profiles.
LOOP = {"pool": 8, "readback_every": 10, "check_steps": 3, "warm_steps": 7,
        "trace_first_step": 20, "trace_steps": 20}
REHEARSAL_LOOP = {"pool": 8, "readback_every": 2, "check_steps": 3,
                  "warm_steps": 1, "trace_first_step": 2, "trace_steps": 4}


def loop(cell, rehearsal=False):
    if rehearsal:
        return dict(REHEARSAL_LOOP)
    return {**LOOP, **cell.get("loop", {})}


def config(name, rehearsal=False):
    cfg = load_json("configs", name + ".json")
    if rehearsal:
        cfg = {**cfg, **cfg["rehearsal"]}
    return cfg


_PACKAGES = {"reference": "reference", "program": "programs",
             "model_flops": "model_flops"}


def module(kind, cfg):
    """The module a configuration names under ``kind`` (reference,
    program, model_flops), or the one of its own name."""
    return importlib.import_module(
        f"benchmarks.{_PACKAGES[kind]}.{cfg.get(kind, cfg['name'])}")


def layer_metrics():
    return {n: load_json("layer_metrics", n + ".json")
            for n in names_in("layer_metrics")}


def reducer(name):
    return importlib.import_module(f"benchmarks.reducers.{name}").reduce


def end_to_end_for(cell_name):
    return [m for m in benchmark()["end_to_end"]
            if cell_name in m.get("workloads", [cell_name])]


def per_layer_for(cell_name):
    return [m for m in benchmark()["per_layer"]
            if cell_name in m.get("workloads", [cell_name])]


def limits(cfg_name, rehearsal=False):
    """The configuration's limits of ``correct``: ``limits/<name>.json``."""
    table = load_json("limits", cfg_name + ".json")
    return table["rehearsal" if rehearsal else "limits"]


def _by_kind(package, kind):
    return importlib.import_module(f"benchmarks.{package}.{kind}")


def lr_at(schedule, step):
    """The learning rate of the job's ``step``-th step (0-based from the
    start of the run): ``schedules/<kind>.py``."""
    return _by_kind("schedules", schedule["kind"]).lr_at(schedule, step)


def reference_optimizer(opt):
    """The reference's plain update rule: ``reference/optimizers/<kind>.py``."""
    return _by_kind("reference.optimizers", opt["kind"])


def draw(kind):
    """How a leaf of a batch is drawn: ``draws/<kind>.py``."""
    return _by_kind("draws", kind).draw
