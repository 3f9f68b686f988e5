"""The names a trace reads (ISSUE 25): ``jax.named_scope`` on the traced
step's regions, layers and ops; a ``name`` on every ``pallas_call``; the
engine's own phases as profiler annotations, a ring, JSONL spans and
histograms from one set of stamps; ``obs.costmodel``'s read API."""

import glob
import os
import re
import time

import jax
import numpy as np
import pytest

import paddle1_tpu as paddle
from paddle1_tpu import obs
from paddle1_tpu.autograd import engine as autograd_engine
from paddle1_tpu.core import flags as core_flags
from paddle1_tpu.core.tensor import Tensor
from paddle1_tpu.distributed import ParallelEngine, build_mesh
from paddle1_tpu.distributed import parallel_engine as pe
from paddle1_tpu.obs import costmodel
from paddle1_tpu.obs import trace as obs_trace

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _fresh_obs():
    obs.reset_process_registry()
    obs.hbm.reset()
    yield
    obs.reset_process_registry()
    obs.hbm.reset()


def _mlp_engine(**kw):
    paddle.seed(0)
    model = paddle.nn.Sequential(paddle.nn.Linear(8, 16), paddle.nn.ReLU(),
                                 paddle.nn.Linear(16, 4))
    opt = paddle.optimizer.AdamW(learning_rate=0.01,
                                 parameters=model.parameters())
    loss_fn = lambda m, b: \
        ((m(Tensor(b["x"])) - Tensor(b["y"])) ** 2).mean()
    mesh = build_mesh(dp=1, devices=jax.devices()[:1])
    return ParallelEngine(model, opt, loss_fn, mesh=mesh, **kw)


def _batch(rows=4):
    rng = np.random.default_rng(0)
    return {"x": rng.standard_normal((rows, 8)).astype(np.float32),
            "y": rng.standard_normal((rows, 4)).astype(np.float32)}


def _rehearsal_engine(cell_name):
    """The benchmark's own tiny BERT / ResNet, as its CPU rehearsal
    builds them."""
    from benchmarks import spec, traffic
    cell = spec.cell(cell_name)
    cfg = spec.config(cell["config"], rehearsal=True)
    env = traffic.environment(cfg, cell, rehearsal=True)
    program = spec.module("program", cfg)
    reference = spec.module("reference", cfg)
    w = jax.jit(lambda k: reference.init_params(cfg, k))(jax.random.key(0))
    placed = {p: (w[r] if i is None else w[r][i])
              for p, r, i in program.leaves(cfg)}
    engine = program.build(cfg, env, placed, jax.devices()[:1])["engine"]
    return engine, traffic.batches(cell, env, 1, 2)


# -- scopes -----------------------------------------------------------------

def test_region_of():
    j = "jit(counted_step)/"
    assert costmodel.region_of(j + "jvp(loss)/bert/linear/dot") == "forward"
    assert costmodel.region_of(
        j + "transpose(jvp(loss))/bert/linear/dot") == "backward"
    assert costmodel.region_of(j + "jvp(amp_cast)/convert") == "amp_cast"
    assert costmodel.region_of(
        j + "transpose(jvp(amp_cast))/convert") == "amp_cast"
    assert costmodel.region_of(
        "jit(multi_step)/while/body/optimizer/mul") == "optimizer"
    assert costmodel.region_of("params['fc.weight']") == ""
    assert costmodel.region_of(
        j + "broadcast;" + j + "stat_update/add") == "stat_update"
    assert set(costmodel.REGIONS) >= {"forward", "backward", "optimizer"}


HLO = '''HloModule jit_counted_step, is_scheduled=true

%fused_computation (p0: f32[8]) -> f32[8] {
  %p0 = f32[8]{0} parameter(0)
  %mul.1 = f32[8]{0} multiply(%p0, %p0), metadata={op_name="jit(counted_step)/transpose(jvp(loss))/fc/linear/mul"}
  ROOT %sub.1 = f32[8]{0} subtract(%p0, %mul.1), metadata={op_name="jit(counted_step)/optimizer/sub" stack_frame_id=3}
}

%fused_computation.1 (p1: f32[8]) -> f32[8] {
  %p1 = f32[8]{0} parameter(0)
  %t.1 = f32[8]{0} tanh(%p1), metadata={op_name="jit(counted_step)/jvp(loss)/fc/tanh"}
  ROOT %t.2 = f32[8]{0} tanh(%t.1), metadata={op_name="jit(counted_step)/jvp(loss)/fc/tanh"}
}

ENTRY %main.5 (a: f32[8]) -> (f32[8], f32[8]) {
  %a = f32[8]{0} parameter(0), metadata={op_name="params[\\'w\\']"}
  %fusion.7 = f32[8]{0} fusion(%a), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(counted_step)/optimizer/sub"}
  %tanh_fusion = f32[8]{0} fusion(%a), kind=kLoop, calls=%fused_computation.1
  %p1t_layer_norm_fwd.2 = f32[8]{0} custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="jit(counted_step)/jvp(loss)/norm/layer_norm/p1t_layer_norm_fwd/pallas_call"}
  %copy.3 = f32[8]{0} copy(%a)
  ROOT %tuple.9 = (f32[8]{0}, f32[8]{0}) tuple(%fusion.7, %tanh_fusion)
}
'''


def test_parse_op_scopes_on_a_hand_made_program():
    scopes, fused = costmodel.parse_op_scopes(HLO)
    assert scopes["fusion.7"] == "jit(counted_step)/optimizer/sub"
    # a fusion that carries no name takes its instructions' commonest
    assert scopes["tanh_fusion"] == "jit(counted_step)/jvp(loss)/fc/tanh"
    assert scopes["copy.3"] == "" and scopes["tuple.9"] == ""
    assert "p1t_layer_norm_fwd" in scopes["p1t_layer_norm_fwd.2"]
    assert scopes["mul.1"].endswith("fc/linear/mul")    # every computation
    # the weight-gradient product with the update fused behind it
    assert fused == {"fusion.7": ("backward", "optimizer"),
                     "tanh_fusion": ("forward",)}


RENAMED = '''HloModule jit_counted_step, is_scheduled=true

ENTRY %main.9 (w: bf16[4,8,8]) -> bf16[16,8] {
  %w = bf16[4,8,8]{2,1,0} parameter(0), metadata={op_name="params[\\'w\\']"}
  %rows = bf16[16,8]{1,0} fusion(%w), kind=kLoop, calls=%f, metadata={op_name="jit(counted_step)/transpose(jvp(loss))/M/recompute/checkpoint/rematted_computation/1/moe/moe_dispatch/gather"}
  %dout = bf16[16,8]{1,0} fusion(%w), kind=kLoop, calls=%g, metadata={op_name="jit(counted_step)/transpose(jvp(loss))/M/recompute/checkpoint/1/moe/moe_combine/mul"}
  %copy-start = (bf16[16,8]{1,0}, bf16[16,8]{1,0}, u32[]) copy-start(%dout)
  %copy-done = bf16[16,8]{1,0} copy-done(%copy-start)
  %ragged-dot-none = bf16[4,8,8]{2,1,0} custom-call(%rows, %copy-done), custom_call_target="tpu_custom_call", frontend_attributes={mosaic_fusion_entry_point="true",p1t_scope="moe/routed_experts"}, metadata={op_name="ragged-dot-none"}
  %ragged-dot-none.1 = bf16[16,8]{1,0} custom-call(%rows, %w), custom_call_target="tpu_custom_call", frontend_attributes={p1t_scope="moe/routed_experts"}, metadata={op_name="ragged-dot-none"}
  %ragged-dot-none.2 = bf16[16,8]{1,0} custom-call(%w, %w), custom_call_target="tpu_custom_call", frontend_attributes={p1t_scope="moe/routed_experts"}, metadata={op_name="ragged-dot-none"}
  ROOT %other = bf16[16,8]{1,0} custom-call(%rows), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-none"}
}
'''


def test_parse_op_scopes_places_an_op_the_compiler_renamed():
    """``ragged-dot-none`` carries the scope the program handed the
    compiler as a frontend attribute, in the pass of its last operand
    that names one, behind a copy if need be."""
    scopes, _ = costmodel.parse_op_scopes(RENAMED)
    back = "jit(counted_step)/transpose(jvp(loss))/"
    # the weight gradient: recomputed rows x the cotangent, which decides
    assert scopes["ragged-dot-none"] == back + "moe/routed_experts"
    assert scopes["ragged-dot-none.1"] == (
        back + "M/recompute/checkpoint/rematted_computation/"
        "moe/routed_experts")
    assert costmodel.region_of(scopes["ragged-dot-none.1"]) == "backward"
    # no operand in either pass: the scope alone; no attribute: as it was
    assert scopes["ragged-dot-none.2"] == "moe/routed_experts"
    assert scopes["other"] == "ragged-dot-none"


@pytest.mark.parametrize("cell", ["bert_base.pretrain_s128",
                                  "resnet50.train_b128"])
def test_step_op_scopes_names_the_compiled_step(cell):
    assert costmodel.step_op_scopes() is None       # no engine has stepped
    assert costmodel.step_phase_records() == []
    engine, pool = _rehearsal_engine(cell)
    float(engine.step(engine.shard_batch(pool[0]), lr=1e-4))
    before = engine.cache_stats()
    scopes = costmodel.step_op_scopes()
    fused = costmodel.step_fused_regions()
    assert engine.cache_stats() == before           # the uncounted body
    assert costmodel.step_op_scopes() is scopes     # memoised
    # every instruction that kept a name from the trace (XLA's CPU
    # backend drops it from the convolutions and copies it rewrites; the
    # TPU's keeps it) lies in a region: nothing is traced outside one
    traced = [s for s in scopes.values() if s.startswith("jit(")]
    in_region = [s for s in traced if costmodel.region_of(s)]
    assert len(traced) > 200
    assert len(in_region) > 0.95 * len(traced)
    regions = {costmodel.region_of(s) for s in traced}
    assert {"forward", "backward", "optimizer"} <= regions
    text = " ".join(traced)
    if cell.startswith("bert"):
        for what in ("/bert/encoder/1/self_attn/scaled_dot_product_attention/",
                     "/norm1/layer_norm/", "/linear1/linear/", "/gelu/",
                     "BertPretrainingCriterion/"):
            assert what in text, what
    else:
        assert "/layer1/0/bn1/batch_norm_train/" in text
        assert "/conv1/conv2d/" in text and "stat_update" in regions
    assert any(len(r) > 1 for r in fused.values())
    assert set(fused) <= set(scopes)


def test_step_many_program_is_named_too():
    engine = _mlp_engine()
    b = _batch()
    float(engine.step(b))
    np.asarray(engine.step_many([b, b, b]))
    before = engine.cache_stats()
    scopes = costmodel.step_op_scopes()
    assert engine.cache_stats() == before
    assert any("/while/body/" in s and costmodel.region_of(s) == "optimizer"
               for s in scopes.values())
    last = costmodel.step_phase_records()[-1]
    assert last.k == 3 and last.shard_calls == 3 and last.compiled


def test_eager_call_enters_no_scope(monkeypatch):
    entered = []
    real = jax.named_scope
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: entered.append(name) or real(name))
    model = paddle.nn.Sequential(paddle.nn.Linear(8, 4), paddle.nn.ReLU())
    model(Tensor(np.ones((2, 8), np.float32)))
    assert entered == []
    with autograd_engine.traced_scopes():
        model(Tensor(np.ones((2, 8), np.float32)))
    assert entered[:2] == ["Sequential", "0"] and "relu" in entered
    n = len(entered)
    model(Tensor(np.ones((2, 8), np.float32)))      # and off again after
    assert len(entered) == n


def test_every_pallas_call_site_has_a_name_of_its_own():
    names = []
    for path in sorted(glob.glob(os.path.join(
            ROOT, "paddle1_tpu", "ops", "pallas", "*.py"))):
        with open(path) as f:
            src = f.read()
        calls = [m.start() for m in re.finditer(r"pl\.pallas_call\(", src)]
        for at, end in zip(calls, calls[1:] + [len(src)]):
            found = re.search(r'\bname="(p1t_[a-z0-9_]+)"', src[at:end])
            assert found, f"{os.path.basename(path)}: pallas_call at " \
                f"offset {at} has no name"
            names.append(found.group(1))
    # 9 until PR 38, which added the gated short convolution's two; PR 50
    # the state-space scan's two; PR 51 the grouped product's two sites
    # (the product's is ``dx``'s too, under a third name)
    assert len(names) == 15 and len(set(names)) == len(names), names
    assert all(re.match(r"^p1t_[a-z0-9]+(_[a-z0-9]+)*_(fwd|bwd)", n)
               for n in names), names


# -- the engine's phases ----------------------------------------------------

def test_profiler_trace_holds_the_engines_phases(tmp_path):
    from jax.profiler import ProfileData
    engine = _mlp_engine()
    b = _batch()
    float(engine.step(b))                       # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        for _ in range(3):
            fut = engine.step(engine.shard_batch(b))
        float(fut)
    finally:
        jax.profiler.stop_trace()
    found = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                          / "*.xplane.pb"))
    assert found
    host = [p for p in ProfileData.from_file(found[-1]).planes
            if p.name == "/host:CPU"]
    names = [e.name for p in host for line in p.lines for e in line.events
             if e.name.startswith("train/")]
    for phase in ("train/shard", "train/guard", "train/dispatch",
                  "train/inflight_wait", "train/readback"):
        assert phase in names, (phase, sorted(set(names)))
    assert names.count("train/dispatch") == 3
    assert names.count("train/shard") == 6      # the trainer's and step's


def test_ring_spans_and_histograms_come_from_one_set_of_stamps(tmp_path):
    engine = _mlp_engine()
    b = _batch()
    d = str(tmp_path / "spans")
    with core_flags.flags_guard(obs_metrics=True, obs_trace_dir=d):
        float(engine.step(b))
        float(engine.step(engine.shard_batch(b)))
    first, second = engine.phase_records()
    assert costmodel.step_phase_records() == [first, second]
    assert first.compiled and not second.compiled
    assert (first.step, second.step) == (1, 2)
    assert (first.shard_calls, second.shard_calls) == (1, 2)
    assert first.dispatch_ns > second.dispatch_ns > 0
    assert abs(first.start_ns - time.time_ns()) < 600e9     # time.time_ns()
    spans = [r for r in obs_trace.read_spans(d) if r["name"] != "train/step"]
    step_spans = [r for r in obs_trace.read_spans(d)
                  if r["name"] == "train/step"]
    assert [r["name"] for r in spans] == [
        "train/shard", "train/guard", "train/dispatch",
        "train/inflight_wait"] * 2
    assert {r["parent"] for r in spans[:4]} == {step_spans[0]["span"]}
    by = {r["name"]: r for r in spans[4:]}
    assert by["train/dispatch"]["dur"] == pytest.approx(
        second.dispatch_ns * 1e-3, abs=0.2)
    assert by["train/dispatch"]["ts"] == pytest.approx(
        (second.start_ns + by["train/shard"]["dur"] * 1e3
         + second.guard_ns) * 1e-3, abs=5)
    h = obs.process_registry().snapshot()["histograms"]
    for name in ("train_shard_seconds", "train_dispatch_seconds",
                 "train_inflight_wait_seconds"):
        assert h[name]["count"] == 2, name
    assert h["train_dispatch_seconds"]["sum"] == pytest.approx(
        (first.dispatch_ns + second.dispatch_ns) * 1e-9, rel=1e-3)


def test_ring_is_bounded(monkeypatch):
    monkeypatch.setattr(pe, "PHASE_RING", 5)
    engine = _mlp_engine()
    b = engine.shard_batch(_batch())
    for _ in range(8):
        engine.step(b)
    engine.drain()
    records = engine.phase_records()
    assert [r.step for r in records] == [4, 5, 6, 7, 8]
    # the in-flight window of 2 made the later steps wait
    assert any(r.inflight_wait_ns > 0 for r in records)


def test_what_the_stamps_add_to_a_step_is_under_20_us():
    """The off-cost: what ``step`` does for the names beyond dispatching
    (five stamps, four profiler annotations outside a session, one ring
    record, the flag reads of ``_note_phases``), timed by itself."""
    from jax.profiler import TraceAnnotation
    engine = _mlp_engine()
    b = engine.shard_batch(_batch())
    float(engine.step(b))
    sig = engine._last_run[2]

    def added():
        t0 = time.time_ns()
        with TraceAnnotation("train/shard"):
            pass
        t1 = time.time_ns()
        with TraceAnnotation("train/guard"):
            pass
        t2 = time.time_ns()
        with TraceAnnotation("train/dispatch"):
            pass
        t3 = time.time_ns()
        with TraceAnnotation("train/inflight_wait"):
            pass
        engine._note_phases(None, 1, "step", b, sig,
                            (t0, t1, t2, t3, time.time_ns()), False)

    best = float("inf")
    for _ in range(5):                  # the quietest of five batches
        t = time.perf_counter()
        for _ in range(2000):
            added()
        best = min(best, (time.perf_counter() - t) / 2000)
    assert best < 20e-6, f"{best * 1e6:.2f} us a step"
