"""A copy of the benchmark with what a later PR brings appended to it: a
configuration with limits of its own, a cell, a schedule, a draw and a
per-layer metric as new files, and their entries at the ends of
``BENCHMARK.json``'s lists. No file that was there is touched."""

import json
import os
import shutil

from benchmarks import spec

ROOT = spec.ROOT
CONFIG, CELL, METRIC = "probe", "probe.pretrain", "probe_ms"
LIKE = "bert_base.pretrain_s128"        # the cell the probe is made from


def make(root, chips=1, with_tests=False):
    """Write the copy under ``root`` (a ``pathlib.Path``): ``benchmarks/``,
    ``BENCHMARK.json`` and, ``with_tests``, the yardstick tests with the
    repo's ``tests/conftest.py`` above them. -> the files it added."""
    src = os.path.join(ROOT, "benchmarks")
    skip = shutil.ignore_patterns("__pycache__")
    shutil.copytree(src, root / "benchmarks", ignore=skip)
    if with_tests:
        shutil.copytree(os.path.join(ROOT, "tests", "bench_yardstick"),
                        root / "tests" / "bench_yardstick", ignore=skip)
        shutil.copy(os.path.join(ROOT, "tests", "conftest.py"),
                    root / "tests" / "conftest.py")
    cfg = spec.config("bert_base")
    cfg.update(name=CONFIG, optimizer={
        **cfg["optimizer"], "lr_schedule": {"kind": "probe_flat", "lr": 1e-5}})
    cell = spec.cell(LIKE)
    cell.update(name=CELL, config=CONFIG, traffic="pretrain", chips=chips)
    cell["fields"]["nsp"] = {"draw": "probe_ones", "shape": ["batch"]}
    metric = {**spec.load_json("layer_metrics", "attention_ms.json"),
              "name": METRIC, "match": "/probe(/|$)"}
    new = {
        "configs/probe.json": json.dumps(cfg),
        "limits/probe.json": json.dumps(spec.load_json("limits",
                                                       "bert_base.json")),
        "workloads/probe.pretrain.json": json.dumps(cell),
        "layer_metrics/probe_ms.json": json.dumps(metric),
        "schedules/probe_flat.py":
            "def lr_at(schedule, step):\n    return schedule['lr']\n",
        "draws/probe_ones.py":
            "import numpy as np\n\n\ndef draw(rng, field, resolve):\n"
            "    return np.ones([resolve(s) for s in field['shape']], "
            "np.int32)\n"}
    for rel, text in new.items():
        assert not os.path.exists(os.path.join(src, rel))
        (root / "benchmarks" / rel).write_text(text)
    bench = spec.benchmark()
    like = {c["name"]: c for c in bench["configs"]}["bert_base"]
    bench["configs"].append({**like, "name": CONFIG,
                             "file": "benchmarks/configs/probe.json"})
    bench["workloads"].append({k: cell[k] for k in (
        "name", "config", "traffic", "chips", "why")})
    for entry in bench["per_layer"]:
        if LIKE in entry.get("workloads", ()):
            entry["workloads"].append(CELL)
    bench["per_layer"].append({
        **{k: metric[k] for k in ("name", "unit", "better", "source",
                                  "layer", "moves")}, "workloads": [CELL]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return sorted(new)
