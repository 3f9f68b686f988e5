"""CPU tests of the per-layer metrics that read the program's names
(``benchmarks/reducers/scope_ms.py``, ``engine_phase_ms.py``): hand-made
views, maps and records, the patterns of the metric files against scopes as
the compiled step programs carry them, and a rehearsal of the command.
Nothing here is a device metric."""

import json
import os
import re
import subprocess
import sys
from collections import namedtuple

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import spec  # noqa: E402
from benchmarks.reducers import engine_phase_ms, scope_ms  # noqa: E402

FILES = spec.layer_metrics()
# every metric that reads scopes, whichever PR filed it: one reducer
SCOPED = sorted(n for n, m in FILES.items() if m["reducer"] == "scope_ms")
PARTITION = ["forward_ms", "backward_ms", "optimizer_ms", "unscoped_ms"]

# scopes as the step programs compiled for a v5e carry them (PR 25)
J = "jit(counted_step)/"
BERT = "BertForPretraining/bert/encoder/3/"
SCOPES = {
    "fusion.1": J + "jvp(loss)/" + BERT + "linear1/linear/dot_general",
    "fusion.2": J + "jvp(loss)/" + BERT
    + "self_attn/scaled_dot_product_attention/bhqd,bhkd->bhqk/dot_general",
    "p1t_layer_norm_fwd.27": J + "jvp(loss)/" + BERT
    + "norm1/layer_norm/p1t_layer_norm_fwd/pallas_call",
    "fusion.3": J + "transpose(jvp(loss))/" + BERT + "norm1/layer_norm/mul",
    "fusion.4": J + "transpose(jvp(loss))/" + BERT
    + "self_attn/scaled_dot_product_attention/transpose;" + J
    + "transpose(jvp(loss))/" + BERT + "self_attn/reshape/reshape",
    "p1t_fused_bn_bwd_dx.7": J + "transpose(jvp(loss))/ResNet/layer1/0/bn1/"
    "batch_norm_train/p1t_fused_bn_bwd_dx/pallas_call",
    "fusion.5": J + "optimizer/add",
    "convert.6": J + "jvp(amp_cast)/convert_element_type",
    "convert.7": J + "transpose(jvp(amp_cast))/convert_element_type",
    "fusion.8": J + "stat_update/add",
    "fusion.9": J + "while/body/grad_clip/mul",
    "copy.10": "params['conv1.weight']",
    "copy.11": "",
}
EXPECT = {
    "forward_ms": {"fusion.1", "fusion.2", "p1t_layer_norm_fwd.27"},
    "backward_ms": {"fusion.3", "fusion.4", "p1t_fused_bn_bwd_dx.7"},
    "optimizer_ms": {"fusion.5", "convert.6", "convert.7", "fusion.8",
                     "fusion.9"},
    "attention_ms": {"fusion.2", "fusion.4"},
    "norm_ms": {"p1t_layer_norm_fwd.27", "fusion.3", "p1t_fused_bn_bwd_dx.7"},
    "unscoped_ms": {"copy.10", "copy.11", "fusion.unknown"},
}


def _view(steps=4):
    """One chip's view whose every instruction ran 1 ms in each step,
    one of them unknown to the map."""
    names = list(SCOPES) + ["fusion.unknown"]
    ops = {f"%{n} = bf16[8,128]{{1,0}} fusion(%p.{i}), kind=kLoop": 1e-3 * steps
           for i, n in enumerate(names)}
    return {"ops": ops, "step_s": [len(names) * 1e-3] * steps,
            "busy_s": len(names) * 1e-3 * steps}


def _ctx(views):
    return {"views": views}


@pytest.mark.parametrize("name", sorted(EXPECT))
def test_scope_metric_reads_its_region(name):
    metric = FILES[name]
    re.compile(metric["match"])
    value = scope_ms.reduce(_ctx([_view(), _view()]), metric,
                            maps=(SCOPES, {}))
    assert value == pytest.approx(len(EXPECT[name]))     # 1 ms each a step
    # and names exactly those instructions
    match = re.compile(metric["match"])
    exclude = re.compile(metric["exclude"]) if metric.get("exclude") else None
    got = {n for n in list(SCOPES) + ["fusion.unknown"]
           if match.search(SCOPES.get(n, ""))
           and not (exclude and exclude.search(SCOPES.get(n, "")))}
    assert got == EXPECT[name]


def test_the_partition_sums_to_the_busy_time_a_step():
    view = _view(steps=5)
    total = sum(scope_ms.reduce(_ctx([view]), FILES[n], maps=(SCOPES, {}))
                for n in PARTITION)
    assert total == pytest.approx(1e3 * view["busy_s"] / len(view["step_s"]))
    sets = [EXPECT[n] for n in PARTITION]
    assert sum(map(len, sets)) == len(set().union(*sets))       # disjoint


def test_an_instruction_the_map_lacks_is_unscoped():
    view = _view()
    for name in PARTITION:
        with_it = scope_ms.reduce(_ctx([view]), FILES[name],
                                  maps=(SCOPES, {}))
        lean = {k: v for k, v in view["ops"].items() if "unknown" not in k}
        without = scope_ms.reduce(_ctx([{**view, "ops": lean}]), FILES[name],
                                  maps=(SCOPES, {}))
        assert with_it - without == pytest.approx(
            1.0 if name == "unscoped_ms" else 0.0)


@pytest.mark.parametrize("name", SCOPED)
def test_any_scope_metric_without_a_map_or_a_view_is_none(name):
    """Every file that names ``scope_ms``, a later PR's too: its patterns
    compile, and with no map (the parent of the PR that named the scopes)
    or no view it reads nothing and raises nothing."""
    metric = FILES[name]
    assert metric["unit"] == "ms" and metric["source"] == "device_trace"
    re.compile(metric["match"])
    re.compile(metric.get("exclude", ""))
    for maps in ((None, None), ({}, {})):
        assert scope_ms.reduce(_ctx([_view()]), metric, maps=maps) is None
    assert scope_ms.reduce(_ctx([]), metric, maps=(SCOPES, {})) is None


def test_report_lists_the_fusions_that_span_regions(capsys):
    fused = {"fusion.5": ("amp_cast", "backward", "optimizer"),
             "fusion.1": ("forward",)}
    scope_ms.reduce(_ctx([_view()]), FILES["unscoped_ms"],
                    maps=(SCOPES, fused))
    out = capsys.readouterr().out
    assert f"holds {len(SCOPES)} of the {len(SCOPES) + 1} instructions" in out
    assert "fusions that span regions take 1.000 ms a step" in out
    assert "amp_cast+backward+optimizer  %fusion" in out
    assert "instructions in no region (scope, family) take 3.000 ms" in out
    assert "backward  .../norm1/layer_norm/mul  %fusion" in out


Phases = namedtuple("Phases", "step start_ns shard_ns guard_ns dispatch_ns "
                    "inflight_wait_ns compiled shard_calls k")


def test_engine_phase_ms_on_hand_made_records(capsys):
    metric = FILES["host_step_ms"]
    assert metric["phases"] == ["shard", "guard", "dispatch"]
    records = [Phases(1, 0, 5_000_000, 1_000_000, 9_000_000_000, 0, True, 2, 1)]
    assert engine_phase_ms.reduce({}, metric, records=records) is None
    records += [Phases(i, 0, 2_000_000, 100_000, 900_000 + i, 150_000_000,
                       False, 2, 1) for i in range(2, 7)]
    # the compiling dispatch and the wait are left out; median of 5
    assert engine_phase_ms.reduce({}, metric, records=records) \
        == pytest.approx(3.000004)
    # a dispatch of k steps counts a k-th a step
    many = [Phases(1, 0, 4_000_000, 0, 2_000_000, 0, False, 4, 4)]
    assert engine_phase_ms.reduce({}, metric, records=many) \
        == pytest.approx(1.5)
    assert engine_phase_ms.reduce({}, metric, records=[]) is None
    # the report tells a wait for the device from time in the jit call
    stalled = records + [Phases(9, 0, 1_000_000, 0, 1_000_000,
                                6_427_000_000, False, 2, 1)]
    engine_phase_ms.reduce({}, metric, records=stalled)
    out = capsys.readouterr().out
    assert "the longest, dispatch 9, ms: shard 1.000, guard 0.000, " \
        "dispatch 1.000, inflight_wait 6427.000; 2 placements" in out
    assert "medians over 6 dispatches, ms: shard 2.000, guard 0.100" in out


@pytest.mark.drives_a_run
def test_command_rehearsal_prints_host_step_ms():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("XLA_FLAGS", None)
    done = subprocess.run(
        [sys.executable, "-m", "benchmarks.run", "--workload",
         "resnet50.train_b128", "--seed", str(2 ** 31 + 77), "--seconds",
         "1", "--trace", "1", "--rehearsal", "1"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-2000:]
    last = json.loads(done.stdout.strip().splitlines()[-1])
    assert last["correct"] is True
    value = last["metrics"]["host_step_ms@cpu_rehearsal"]
    assert value["unit"] == "ms" and 0 < value["value"] < 1e3
    # no device plane on the CPU: the scope metrics find no view to read
    assert not {k.split("@")[0] for k in last["metrics"]} & set(SCOPED)
