"""CPU tests of the benchmark's own yardstick (``benchmarks/``): the trace
reduction on a recorded trace, the FLOP functions, the plain references
against the framework, the data files a later PR adds to, and a rehearsal
of ``python -m benchmarks.run`` end to end. Nothing here loads the TPU's
library, and no number here is a device metric."""

import gzip
import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import spec, trace_reduce, traffic  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
BENCH = spec.benchmark()
CELLS = spec.names_in("workloads")
CONFIGS = spec.names_in("configs")
METRICS = spec.names_in("layer_metrics")
LIMITS = {"loss", "grad_worst", "grad_median", "grad_direction",
          "delta_worst"}


# -- the data files: a file a later PR adds is tested without an edit ------

@pytest.mark.parametrize("name", CONFIGS)
def test_config_file(name):
    cfg = spec.config(name)
    entry = {c["name"]: c for c in BENCH["configs"]}[name]
    assert cfg["name"] == name and NAME.match(name)
    assert entry["file"] == f"benchmarks/configs/{name}.json"
    assert sorted(cfg["reduced"]) == sorted(entry["reduced"])
    assert all(NAME.match(k) for k in cfg["reduced"])
    assert isinstance(cfg["assumed"], dict) and cfg["source"]
    assert cfg["precision"]["compute"] in ("bfloat16", "float32")
    for kind in ("reference", "program", "model_flops"):
        assert spec.module(kind, cfg) is not None
    # the rehearsal changes sizes only through keys the file already has
    assert set(cfg["rehearsal"]) <= set(cfg)
    # what belongs to the configuration alone is found by its name or by
    # the kinds it names: limits, update rule (both sides), schedule
    for rehearsal in (False, True):
        limits = spec.limits(name, rehearsal)
        assert LIMITS - {"grad_direction"} <= set(limits) <= LIMITS
        assert all(v > 0 for v in limits.values())
    opt = cfg["optimizer"]
    assert callable(spec.reference_optimizer(opt).update)
    assert opt["kind"] in spec.names_in("programs/optimizers", ".py")
    assert spec.lr_at(opt["lr_schedule"], 0) > 0
    from benchmarks.reference.numerics import PRECISIONS
    for block in (cfg, cfg["rehearsal"]):
        assert block["precision"]["control"] in PRECISIONS


@pytest.mark.parametrize("name", CELLS)
def test_workload_file(name):
    cell = spec.cell(name)              # checks it against BENCHMARK.json
    assert NAME.match(name) and NAME.match(cell["traffic"])
    assert cell["config"] in CONFIGS and cell["chips"] in (1, 4)
    assert 1 <= len(cell["why"]) <= 200 and "\n" not in cell["why"]
    for loop in (spec.loop(cell), spec.loop(cell, rehearsal=True)):
        assert set(loop) == set(spec.LOOP)
        assert (loop["check_steps"] + loop["warm_steps"]) \
            % loop["readback_every"] == 0
        assert loop["trace_steps"] % loop["readback_every"] == 0
    assert set(cell["rehearsal"]) == {"batch", "dims"}
    assert all(f["draw"] in spec.names_in("draws", ".py")
               for f in cell["fields"].values())
    cfg = spec.config(cell["config"], rehearsal=True)
    env = traffic.environment(cfg, cell, rehearsal=True)
    a = traffic.batches(cell, env, 2 ** 31 + 5, 3)
    b = traffic.batches(cell, env, 2 ** 31 + 5, 3)
    c = traffic.batches(cell, env, 7, 3)
    for x, y, z in zip(a, b, c):
        assert sorted(x) == sorted(cell["fields"])
        for k in x:                     # same seed same batch; another
            assert np.array_equal(x[k], y[k])      # seed, same shapes
            assert x[k].shape == z[k].shape and x[k].dtype == z[k].dtype
    assert not np.array_equal(a[0][sorted(a[0])[0]], a[1][sorted(a[0])[0]])
    # every end-to-end metric of the cell, and some per-layer metric
    assert {"setup_s"} < {m["name"] for m in spec.end_to_end_for(name)}
    assert spec.per_layer_for(name)


@pytest.mark.parametrize("name", METRICS)
def test_layer_metric_file(name):
    metric = spec.load_json("layer_metrics", name + ".json")
    entry = {m["name"]: m for m in BENCH["per_layer"]}[name]
    assert metric["name"] == name and NAME.match(name)
    for key in ("unit", "better", "source", "layer", "moves"):
        assert metric[key] == entry[key], key
    assert UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in SOURCES
    assert callable(spec.reducer(metric["reducer"]))
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert metric["moves"] in e2e and metric["moves"] != "setup_s"
    for cell in entry.get("workloads", CELLS):
        assert cell in CELLS
        assert cell in e2e[metric["moves"]].get("workloads", CELLS)
    # metrics of one layer name it letter for letter
    layers = {m["layer"] for m in BENCH["per_layer"]}
    assert sum(l.lower().split("(")[0].strip()
               == metric["layer"].lower().split("(")[0].strip()
               for l in layers) == 1


def test_benchmark_json_contract():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    assert {w["name"] for w in BENCH["workloads"]} <= set(CELLS)
    assert {c["name"] for c in BENCH["configs"]} \
        == {w["config"] for w in BENCH["workloads"]}
    for m in BENCH["end_to_end"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(BENCH["workloads"]) // 4)
    with open(os.path.join(ROOT, "BENCHMARK.json"), "rb") as f:
        assert len(f.read()) <= 64 * 1024


def test_peaks_table_refuses_an_unknown_kind():
    from benchmarks import peaks
    assert peaks.of("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(SystemExit):
        peaks.of("cpu")


def test_lr_schedule():
    sched = spec.config("bert_base")["optimizer"]["lr_schedule"]
    assert spec.lr_at(sched, 0) == pytest.approx(1e-4 * 1001 / 10000)
    assert spec.lr_at(sched, 10 ** 6) == pytest.approx(1e-4)
    assert spec.lr_at({"kind": "constant", "peak": 0.5}, 7) == 0.5


def test_step_ms_p95_is_the_tail_of_single_returns():
    """One definition in every cell: the time between successive returns
    of ``engine.step``, the first from the opening of the window."""
    from benchmarks import run
    gaps = run.return_gaps_ms([10.1, 10.2, 10.5, 10.6], 10.0)
    assert gaps == pytest.approx([100.0, 100.0, 300.0, 100.0])
    assert run.percentile(gaps, 95) == pytest.approx(270.0)
    assert run.return_gaps_ms([], 10.0) == []


# -- the trace reduction ----------------------------------------------------

def test_interval_arithmetic():
    assert trace_reduce.union([(3, 4), (0, 1), (0.5, 2), (2, 2.5)]) \
        == [(0, 2.5), (3, 4)]
    assert trace_reduce.clip([(0, 2), (3, 5), (6, 7)], 1, 4) == [(1, 2), (3, 4)]
    spans = [("bench/input", 0.0, 1.0), ("bench/dispatch", 1.0, 3.0),
             ("bench/readback", 3.0, 9.0)]
    assert trace_reduce.host_span_at(spans, 0.9, 1.3) == "dispatch"
    assert trace_reduce.host_span_at(spans, 2.5, 8.0) == "readback"
    assert trace_reduce.host_span_at(spans, 20.0, 21.0) == "none"


def test_device_view_on_hand_made_events():
    dev = {"modules": [("jit_step(1)", 0.0, 1.0), ("jit_other(2)", 1.0, 1.1),
                       ("jit_step(1)", 1.5, 2.5), ("jit_step(1)", 3.0, 4.0)],
           "ops": [("%a = f32[] add()", 0.0, 0.5),
                   ('%k = custom-call(), custom_call_target="tpu_custom_call"',
                    0.5, 1.0),
                   ("%a = f32[] add()", 1.5, 2.5), ("%a = f32[] add()", 3.2, 4.0),
                   ("%outside = f32[] add()", 4.5, 5.0)]}
    v = trace_reduce.device_view(dev)
    assert v["window_s"] == 4.0 and v["busy_s"] == pytest.approx(2.8)
    assert v["step_s"] == [1.0, 1.0, 1.0]
    assert v["gaps"] == [(1.0, 1.5), (2.5, 3.0)]
    assert v["idle"] == [(1.0, 1.5), (2.5, 3.2)]
    trace = {"devices": {"/device:TPU:0": dev},
             "spans": [("bench/dispatch", 0.9, 1.6), ("bench/readback", 2.4, 3.1)]}
    br = trace_reduce.breakdown(trace)
    assert dict(br["idle_gaps"]) == pytest.approx(
        {"between_steps/dispatch": 0.5, "between_steps/readback": 0.5,
         "within_step": 0.2})
    assert br["device_ops"][0] == ["%a f32[] add() x1", pytest.approx(2.3)]
    assert trace_reduce.op_family("%fusion.12 = bf16[8]{0} fusion(%p.3)") \
        == trace_reduce.op_family("%fusion.7 = bf16[8]{0} fusion(%p.3)")
    from benchmarks.reducers import (device_idle_pct, device_step_ms,
                                     step_gap_ms, step_mfu_pct)
    ctx = {"views": [v], "flops_per_step": 9.85e13, "peak_flops_per_s": 197e12,
           "n_devices": 1}
    assert device_step_ms.reduce(ctx, {}) == 1000.0
    assert step_gap_ms.reduce(ctx, {}) == 500.0
    assert device_idle_pct.reduce(ctx, {}) == pytest.approx(30.0)
    assert step_mfu_pct.reduce(ctx, {}) == pytest.approx(50.0)
    assert device_step_ms.reduce({**ctx, "views": []}, {}) is None


@pytest.fixture(scope="module")
def recorded():
    """A trace recorded on the TPU v5e by benchmarks/tools/record_trace.py:
    8 steps of a small program with a Pallas kernel in it, the benchmark's
    three spans round them, a readback every 4 steps."""
    packed = os.path.join(HERE, "recorded_v5e.xplane.pb.gz")
    plain = os.path.join(os.environ.get("TMPDIR", "/tmp"),
                         f"bench_yardstick_{os.getpid()}.xplane.pb")
    with gzip.open(packed, "rb") as src, open(plain, "wb") as dst:
        dst.write(src.read())
    try:
        yield trace_reduce.load(plain)
    finally:
        os.remove(plain)


def test_recorded_trace(recorded):
    assert list(recorded["devices"]) == ["/device:TPU:0"]
    names = {n for n, _, _ in recorded["spans"]}
    assert names == {"bench/input", "bench/dispatch", "bench/readback"}
    (v,) = trace_reduce.views(recorded)
    assert len(v["step_s"]) == 8 and len(v["gaps"]) == 7
    assert 0 < v["busy_s"] < v["window_s"]
    idle = sum(e - s for s, e in v["idle"])
    assert idle + v["busy_s"] == pytest.approx(v["window_s"], rel=1e-9)
    assert all(e > s for s, e in v["gaps"])
    br = trace_reduce.breakdown(recorded)
    assert 1 <= len(br["device_ops"]) <= 10 and 1 <= len(br["idle_gaps"]) <= 10
    assert all(len(n) <= 72 and s > 0 for n, s in br["device_ops"])
    assert any("custom-call" in n for n, _ in br["device_ops"])
    # the step after each readback waits for the host: the longest gap
    # between step programs is the readback's
    longest = max(v["gaps"], key=lambda g: g[1] - g[0])
    assert trace_reduce.host_span_at(recorded["spans"], *longest) == "readback"


# -- model FLOPs ------------------------------------------------------------

def test_resnet50_flops_hand_count():
    from benchmarks.model_flops import resnet50 as mf
    cfg = spec.config("resnet50")
    convs = mf.conv_layers(cfg)
    assert len(convs) == 53 and convs[0] == (7, 3, 64, 112)
    assert convs[-1] == (1, 512, 2048, 7)
    macs = mf.forward_macs(cfg, {"batch": 1})
    # He et al. quote 3.8e9 multiply-adds for the original placement;
    # v1.5 moves the stride to the 3x3 and costs 4.09e9
    assert macs == 4089184256
    first = 7 * 7 * 3 * 64 * 112 * 112
    assert mf.train_step_flops(cfg, {"batch": 2}) == 2 * 2 * (3 * macs - first)


def test_bert_flops_hand_count():
    from benchmarks.model_flops import bert_base as mf
    cfg = spec.config("bert_base")
    env = {"batch": 256, "seq": 128, "mlm_per_seq": 20}
    tokens, masked = 256 * 128, 256 * 20
    layer = 2 * tokens * (4 * 768 * 768 + 2 * 768 * 3072) \
        + 4 * 256 * 128 * 128 * 768
    heads = 2 * 256 * 768 * 768 + 2 * 256 * 768 * 2 \
        + 2 * masked * 768 * 768 + 2 * masked * 768 * 30522
    assert mf.train_step_flops(cfg, env) == 3.0 * (12 * layer + heads)
    # all positions through the head: bench.py's 6 * matmul-params * tokens
    # + 12 * L * B * S^2 * H, within the pooler's and NSP's rounding
    env = {"batch": 1, "seq": 128}
    params = 12 * (4 * 768 * 768 + 2 * 768 * 3072) + 768 * 768 + 30522 * 768
    old = 6 * params * 128 + 12 * 12 * 128 * 128 * 768
    assert mf.train_step_flops(cfg, env) == pytest.approx(old, rel=2e-4)


@pytest.mark.parametrize("name", CONFIGS)
def test_flops_against_xla_cost_analysis(name):
    """At a small size whose matrix products still dominate, XLA's count
    of the reference's forward pass is the function's, within 10%."""
    import jax
    from benchmarks.reference.numerics import Numerics
    cfg = spec.config(name, rehearsal=True)
    if cfg.get("reference", name) == "bert_base":
        cfg = {**cfg, "hidden_size": 256, "intermediate_size": 1024,
               "num_attention_heads": 4, "vocab_size": 2048,
               "num_hidden_layers": 1}   # XLA counts a scan body once
    cell = next(spec.cell(c) for c in CELLS if spec.cell(c)["config"] == name)
    env = traffic.environment(cfg, cell, rehearsal=True)
    env["batch"] = 8
    ref, mf = spec.module("reference", cfg), spec.module("model_flops", cfg)
    params = jax.eval_shape(lambda k: ref.init_params(cfg, k),
                            jax.random.key(0))
    batch = traffic.batches(cell, env, 0, 1)[0]
    compiled = jax.jit(
        lambda p, b: ref.loss(p, b, cfg, Numerics())[0]).lower(
        params, batch).compile()
    cost = compiled.cost_analysis()
    cost = cost[0] if isinstance(cost, (list, tuple)) else cost
    forward = mf.train_step_flops(cfg, env) / 3.0
    if name == "resnet50":
        forward = 2.0 * mf.forward_macs(cfg, env)
    assert cost["flops"] == pytest.approx(forward, rel=0.10)


# -- references against the framework, and what decides `correct` ----------

def _args(cell, seed=11, seconds=0.3, trace=0):
    import argparse
    return argparse.Namespace(workload=cell, seed=seed, seconds=seconds,
                              trace=trace, rehearsal=1)


@pytest.fixture(scope="module")
def one_cell_per_config():
    out = {}
    for c in CELLS:
        out.setdefault(spec.cell(c)["config"], c)
    return out


@pytest.mark.drives_a_run
@pytest.mark.parametrize("name", CONFIGS)
def test_reference_follows_the_framework_in_float32(name, one_cell_per_config,
                                                    capsys):
    """The rehearsal runs the framework in float32 at a tiny size: its
    first three losses, its first gradient and its parameters' change
    agree with the plain reference far inside the limits."""
    from benchmarks import run
    result = run.run(_args(one_cell_per_config[name]))
    out = capsys.readouterr().out
    assert result["correct"] is True and result["failed"] == 0
    gaps = [float(m) for m in re.findall(r"gap: ([0-9.e+\-]+) \(limit", out)]
    turn = "grad_direction" in spec.limits(name, rehearsal=True)
    assert len(gaps) == 6 + turn
    assert max(gaps[:-1]) < 1e-4 and gaps[-1] < 2e-3
    assert all(k.endswith("@cpu_rehearsal") for k in result["metrics"])


@pytest.mark.drives_a_run
@pytest.mark.parametrize("broken", ["state_unchanged", "half_the_batch"])
def test_a_broken_timed_path_is_not_correct(broken, monkeypatch, capsys):
    """Drive a run with the timed path broken underneath: a step that
    returns its state unchanged, or one that leaves out half the batch."""
    from benchmarks import run
    from paddle1_tpu.distributed import parallel_engine as pe
    real = pe.ParallelEngine.step

    def step(self, batch, lr=None):
        if broken == "half_the_batch":
            import jax
            half = jax.tree_util.tree_map(
                lambda a: a.at[a.shape[0] // 2:].set(a[:1]), batch)
            return real(self, half, lr)
        params, state = self.params, self.opt_state
        fut = real(self, batch, lr)
        self.params, self.opt_state = params, state
        return fut

    monkeypatch.setattr(pe.ParallelEngine, "step", step)
    if broken == "state_unchanged":
        # a step that hands back its state must not have donated it
        monkeypatch.setattr(pe.core_flags, "flag", _no_donation(pe))
    result = run.run(_args("resnet50.train_b128"))
    assert result["correct"] is False
    assert "NOT OK" in capsys.readouterr().out


def _no_donation(pe):
    real = pe.core_flags.flag
    return lambda name: False if name == "jit_donate_params" else real(name)


@pytest.mark.drives_a_run
@pytest.mark.parametrize("name", CONFIGS)
def test_lower_precision_control_is_not_correct(name, one_cell_per_config):
    """The reference in the program's place, one precision below what the
    (rehearsal) configuration states, fails the limits of its size; the
    reference itself passes them. On the chip the same comparison was read
    at each cell's own size with float8 under bfloat16 (PERF.md)."""
    import jax
    from benchmarks import check
    cell = spec.cell(one_cell_per_config[name])
    cfg = spec.config(name, rehearsal=True)
    assert cfg["precision"] == {"compute": "float32", "master": "float32",
                                "control": "bfloat16"}
    env = traffic.environment(cfg, cell, rehearsal=True)
    env["batch"] = 16
    ref = spec.module("reference", cfg)
    weights = jax.device_get(ref.init_params(cfg, jax.random.key(5)))
    batches = traffic.batches(cell, env, 5, 3)
    lrs = [spec.lr_at(cfg["optimizer"]["lr_schedule"], i) for i in range(3)]
    leaves = spec.module("program", cfg).leaves(cfg)
    limits = spec.limits(name, rehearsal=True)

    def verdict(precision):
        record = check.reference_record(cfg, weights, batches, lrs, precision)
        return check.compare(check.in_programs_place(record, leaves), sound,
                             leaves, limits)

    sound = check.reference_record(cfg, weights, batches, lrs, "float32")
    assert verdict("float32")[0]
    ok, rows = verdict(cfg["precision"]["control"])
    assert not ok, rows
    if "grad_direction" in limits:
        assert rows[5][0].startswith("grad direction") and not rows[5][3]
        # float8 operands in the products alone, the step that would tempt
        # a later PR, turn the gradient further than bfloat16 does
        turned = {p: verdict(p)[1][5][1]
                  for p in ("bfloat16", "float8_matmul")}
        assert turned["float8_matmul"] > 2 * turned["bfloat16"]


# -- the command, end to end ------------------------------------------------

def _cli(*extra):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("XLA_FLAGS", None)
    return subprocess.run(
        [sys.executable, "-m", "benchmarks.run", "--workload",
         "bert_base.pretrain_s128", "--seed", str(2 ** 31 + 12345),
         "--seconds", "1", *extra],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)


@pytest.mark.drives_a_run
def test_command_rehearsal_prints_the_contracts_last_line():
    done = _cli("--trace", "1", "--rehearsal", "1")
    assert done.returncode == 0, done.stderr[-2000:]
    last = json.loads(done.stdout.strip().splitlines()[-1])
    assert set(last) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert last["correct"] is True and last["attempted"] > 0
    assert last["device"]["platform"] == "cpu" and last["device"]["count"] == 1
    assert last["metrics"] and all(
        k.endswith("@cpu_rehearsal") and set(v) == {"value", "unit"}
        for k, v in last["metrics"].items())
    listed = {m["name"] for m in BENCH["per_layer"]}
    assert {k.split("@")[0] for k in last["metrics"]} <= listed
    assert "compiles_in_window@cpu_rehearsal" in last["metrics"]
    assert last["metrics"]["compiles_in_window@cpu_rehearsal"]["value"] == 0
    # each number compared beside its limit: the line's last key, and the
    # last lines of standard error
    assert list(last)[-1] == "compared" and len(last["compared"]) == 7
    assert all(0 <= row["value"] <= row["limit"]
               for row in last["compared"].values())
    told = done.stderr.strip().splitlines()[-len(last["compared"]):]
    assert [line.split("compared ")[1].split(":")[0] for line in told] \
        == list(last["compared"])


@pytest.mark.drives_a_run
def test_a_new_configuration_and_cell_add_files_and_edit_none(tmp_path):
    """What a later PR does: in a copy of the benchmark
    (``probe_copy.py``), a configuration with limits of its own, a cell, a
    schedule, a draw and a per-layer metric are added as new files plus
    entries at the ends of BENCHMARK.json's lists, no file that was there
    is touched, and the command runs the new cell."""
    import probe_copy
    assert probe_copy.make(tmp_path) == [
        "configs/probe.json", "draws/probe_ones.py",
        "layer_metrics/probe_ms.json", "limits/probe.json",
        "schedules/probe_flat.py", "workloads/probe.pretrain.json"]
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": ROOT}
    env.pop("XLA_FLAGS", None)
    done = subprocess.run(
        [sys.executable, "-m", "benchmarks.run", "--workload",
         "probe.pretrain", "--seed", "3", "--seconds", "0.5", "--rehearsal",
         "1"], cwd=tmp_path, env=env, capture_output=True, text=True,
        timeout=600)
    assert done.returncode == 0, done.stderr[-2000:]
    assert f"{tmp_path}" in done.stdout      # the copy ran, not the repo
    last = json.loads(done.stdout.strip().splitlines()[-1])
    assert last["correct"] is True and last["attempted"] > 0
    assert all(k.endswith("@cpu_rehearsal") for k in last["metrics"])


@pytest.mark.drives_a_run
def test_command_refuses_a_backend_that_is_not_a_tpu():
    done = _cli("--trace", "0")
    assert done.returncode != 0 and "found no TPU" in done.stderr
    assert not [l for l in done.stdout.splitlines() if l.startswith("{")]
