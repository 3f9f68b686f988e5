"""Expert load leaves the compiled step (ISSUE 36): the counts that
``RoutedExperts`` keeps on the device against a numpy recount of the
router's picks, eager and through every form of the compiled step; the one
channel by which a forward writes state (``collect_stat_updates``) inside a
recomputed segment, for the counts and for batch norm; a model that records
nothing carries nothing; the ``/metrics`` series. CPU, tiny sizes."""

import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import paddle1_tpu as paddle  # noqa: E402
from paddle1_tpu import nn, obs  # noqa: E402
from paddle1_tpu.core import async_loss  # noqa: E402
from paddle1_tpu.core.flags import flags_guard  # noqa: E402
from paddle1_tpu.core.tensor import Tensor  # noqa: E402
from paddle1_tpu.distributed import ParallelEngine, build_mesh  # noqa: E402
from paddle1_tpu.distributed.fleet.utils import recompute  # noqa: E402
from paddle1_tpu.nn import layer_moe  # noqa: E402
from paddle1_tpu.nn.functional import norm as fnorm  # noqa: E402
from paddle1_tpu.obs import costmodel  # noqa: E402

HIDDEN, EXPERTS, TOP_K, HELD = 16, 8, 2, (2, 3)
TOKENS = 48


class Block(nn.Layer):
    def __init__(self):
        super().__init__()
        self.moe = nn.RoutedExperts(HIDDEN, 32, EXPERTS, TOP_K, held=HELD,
                                    routed_scaling_factor=2.0)

    def forward(self, x):
        return x + self.moe(x)


class Net(nn.Layer):
    """Two expert layers, each in a segment of its own where the engine
    asks for recomputation (as the decoder stacks wrap their blocks)."""

    def __init__(self, block=Block):
        super().__init__()
        self.b0, self.b1 = block(), block()
        self.enable_recompute = False

    def forward(self, x):
        for b in (self.b0, self.b1):
            x = recompute(b, x) if self.enable_recompute else b(x)
        return x


def _loss(model, batch):
    x = batch["x"]
    return (model(x if isinstance(x, Tensor) else Tensor(x)) ** 2).mean()


def _batches(n, shape=(TOKENS, HIDDEN), seed=0):
    rng = np.random.default_rng(seed)
    return [{"x": rng.standard_normal(shape).astype(np.float32)}
            for _ in range(n)]


def _recount(net, batches, capacity=None):
    """What the layers should have counted, from the router's picks alone:
    the model's forward in numpy's hands, layer by layer (the rate is 0,
    so the weights stay as they were built)."""
    want = {}
    for path, moe in (("b0.moe", net.b0.moe), ("b1.moe", net.b1.moe)):
        want[path] = {"rows": np.zeros(HELD[1], np.int64), "held_picks": 0,
                      "late_picks": 0, "late_steps": 0, "steps": 0}
    for batch in batches:
        x = jnp.asarray(batch["x"]).reshape(-1, HIDDEN)
        for path, block in (("b0.moe", net.b0), ("b1.moe", net.b1)):
            moe = block.moe
            _, chosen = layer_moe.route(
                x, moe.router.data, moe.e_score_correction_bias.data, TOP_K,
                2.0)
            local = np.asarray(chosen).reshape(-1) - HELD[0]
            held = np.sort(local[(local >= 0) & (local < HELD[1])])
            rows = held.size if capacity is None else min(capacity, held.size)
            w = want[path]
            w["rows"] += np.bincount(held[:rows], minlength=HELD[1])
            w["held_picks"] += held.size
            w["late_picks"] += held.size - rows
            w["late_steps"] += int(held.size > rows)
            w["steps"] += 1
            x = jnp.asarray(block(Tensor(x)).data)
    return want


def _check(got, want):
    assert sorted(got) == sorted(want)
    for path, w in want.items():
        g = got[path]
        assert g["rows"] == w["rows"].tolist(), path
        for key in layer_moe.LOAD_TAIL:
            assert g[key] == w[key], (path, key)
        assert (g["held"], g["num_experts"]) == (HELD[1], EXPERTS)


def _engine(net, **kw):
    opt = paddle.optimizer.SGD(learning_rate=0.0,
                               parameters=net.parameters())
    return ParallelEngine(net, opt, _loss,
                          mesh=build_mesh(devices=jax.devices()[:1]), **kw)


MODES = {
    # engine arguments, the shape of a batch, how the batches are fed
    "eager": (None, (TOKENS, HIDDEN), None),
    "step": ({}, (TOKENS, HIDDEN), "step"),
    "recompute": ({"recompute": True}, (TOKENS, HIDDEN), "step"),
    "step_many": ({}, (TOKENS, HIDDEN), "step_many"),
    "recompute_step_many": ({"recompute": True}, (TOKENS, HIDDEN),
                            "step_many"),
    "grad_accum": ({"grad_accum": 2}, (2, TOKENS // 2, HIDDEN), "step"),
    "check_finite": ({"check_finite": True}, (TOKENS, HIDDEN), "step"),
}


@pytest.mark.parametrize("mode", sorted(MODES))
def test_the_counts_are_the_recount_of_the_routers_picks(mode):
    """(a) rows an expert, held picks, steps: to the integer, whichever
    way the forward runs; a micro-step under gradient accumulation is a
    forward of its own."""
    engine_kw, shape, feed = MODES[mode]
    paddle.seed(3)
    net = Net()
    batches = _batches(4, shape)
    micro = [{"x": b["x"][i]} for b in batches for i in range(shape[0])] \
        if len(shape) == 3 else batches
    if engine_kw is None:
        for b in batches:
            _loss(net, b)
        got = {p: m.read_load(np.asarray(m.expert_load.data))
               for p, m in (("b0.moe", net.b0.moe), ("b1.moe", net.b1.moe))}
    else:
        engine = _engine(net, **engine_kw)
        if feed == "step_many":
            engine.step_many(batches[:3])
            engine.step(batches[3])
        else:
            for b in batches:
                engine.step(b)
        got = engine.expert_load()
        assert costmodel.step_expert_load() == got
        # the layer's own buffers are the engine's after sync_model, and
        # nothing of the counts is in a checkpoint's keys
        assert int(net.b0.moe.expert_load.data[-1]) == 0
        engine.sync_model()
        assert net.b0.moe.read_load(
            np.asarray(net.b0.moe.expert_load.data)) == got["b0.moe"]
        assert "b0.moe.expert_load" not in net.state_dict()
        assert "b0.moe.expert_load" not in engine.params
    picks = TOKENS * TOP_K // (shape[0] if len(shape) == 3 else 1)
    for g in got.values():
        assert g["picks_made_a_step"] == picks
        assert g["capacity_rows"] == picks      # every pick has a row here
    _check(got, _recount(net, micro))


@pytest.mark.parametrize("compiled", [False, True])
def test_a_small_capacity_counts_its_late_picks(compiled, monkeypatch):
    """(b) the capacity forced below the held picks: what found no row is
    counted as late, the step as a late step, and no expert's rows pass
    the capacity."""
    capacity = 8
    monkeypatch.setattr(layer_moe, "capacity_rows", lambda *a: capacity)
    paddle.seed(4)
    net = Net()
    batches = _batches(3)
    if compiled:
        engine = _engine(net, recompute=True)
        for b in batches:
            engine.step(b)
        got = engine.expert_load()
    else:
        for b in batches:
            _loss(net, b)
        got = {p: m.read_load(np.asarray(m.expert_load.data))
               for p, m in (("b0.moe", net.b0.moe), ("b1.moe", net.b1.moe))}
    want = _recount(net, batches, capacity)
    _check(got, want)
    for g in got.values():
        assert g["capacity_rows"] == capacity
        assert g["late_picks"] > 0 and g["late_steps"] == 3
        assert sum(g["rows"]) == 3 * capacity
        assert g["held_picks"] == sum(g["rows"]) + g["late_picks"]


class NormBlock(nn.Layer):
    def __init__(self):
        super().__init__()
        self.fc = nn.Linear(HIDDEN, HIDDEN)
        self.bn = nn.BatchNorm1D(HIDDEN)

    def forward(self, x):
        return x + self.bn(self.fc(x))


def _norm_stats(recomputed, steps=3, eager=False):
    paddle.seed(5)
    net = Net(NormBlock)
    batches = _batches(steps, seed=1)
    if eager:
        net.enable_recompute = recomputed
        for b in batches:
            _loss(net, {"x": Tensor(b["x"], stop_gradient=False)}).backward()
    else:
        engine = _engine(net, recompute=recomputed)
        for b in batches:
            engine.step(b)
        engine.sync_model()
    return {k: np.asarray(v.data) for k, v in net.state_dict().items()
            if k.endswith(("_mean", "_variance"))}


@pytest.mark.parametrize("eager", [False, True])
def test_batch_norm_in_a_recomputed_segment_keeps_its_statistics(eager):
    """(c) the running statistics written inside ``fleet.utils.recompute``
    leave the segment as its outputs and are the ones the same block
    writes without recomputation: in a compiled step (a leaked tracer
    before), and eagerly (skipped with a warning before)."""
    plain = _norm_stats(False, eager=eager)
    again = _norm_stats(True, eager=eager)
    assert len(plain) == 4 and sorted(again) == sorted(plain)
    for k, v in plain.items():
        np.testing.assert_allclose(again[k], v, rtol=1e-5, atol=1e-6)
        init = 0.0 if k.endswith("_mean") else 1.0
        assert np.abs(v - init).max() > 1e-3, k


def test_a_segment_hands_its_records_to_the_collector_outside():
    """The one channel: what is recorded inside a segment is recorded
    again in the enclosing collector with a value of the enclosing trace,
    by its rule; nothing stays behind in a side list."""
    paddle.seed(6)
    block, norm = Block(), NormBlock()
    seen = {}

    def f(x):
        with paddle.no_grad(), fnorm.collect_stat_updates() as sink:
            y = recompute(norm, recompute(block, Tensor(x)))
            seen["records"] = [(u.buffer, u.rule, u.momentum,
                                type(u.value)) for u in sink]
            return y.data, [u.value for u in sink]

    _, values = jax.jit(f)(jnp.ones((TOKENS, HIDDEN)))
    rules = [(r, m) for _, r, m, _ in seen["records"]]
    assert rules == [("add", None), ("blend", 0.9), ("blend", 0.9)]
    assert seen["records"][0][0] is block.moe.expert_load
    assert seen["records"][1][0] is norm.bn._mean
    assert int(values[0][-1]) == 1 and values[0].dtype == jnp.int32
    assert getattr(fnorm._stat_sink, "sink", None) is None
    # the buffers themselves were not touched by the trace
    assert not isinstance(block.moe.expert_load.data, jax.core.Tracer)
    assert int(block.moe.expert_load.data[-1]) == 0


def test_a_users_own_jit_counts_nothing_and_says_nothing(recwarn):
    paddle.seed(7)
    block = Block()
    jax.jit(lambda x: block(Tensor(x)).data)(jnp.ones((TOKENS, HIDDEN)))
    assert int(block.moe.expert_load.data[-1]) == 0
    assert not [w for w in recwarn if "SKIPPED" in str(w.message)]


def _bert_engine():
    from benchmarks import spec, traffic
    cell = spec.cell("bert_base.pretrain_s128")
    cfg = spec.config(cell["config"], rehearsal=True)
    env = traffic.environment(cfg, cell, rehearsal=True)
    program = spec.module("program", cfg)
    reference = spec.module("reference", cfg)
    w = reference.init_params(cfg, jax.random.key(0))
    placed = {p: (w[r] if i is None else w[r][i])
              for p, r, i in program.leaves(cfg)}
    built = program.build(cfg, env, placed, jax.devices()[:1])
    return built["engine"], traffic.batches(cell, env, 0, 1)[0]


def test_a_model_that_records_nothing_carries_nothing():
    """(d) bert at its rehearsal size: the step's state tree is empty, so
    the lowered step has the arguments and results that the parameters,
    the optimizer's state, the batch, the key and the rate account for
    and no other, and it is the text of the step called without."""
    engine, batch = _bert_engine()
    assert engine.step_state == {} and engine.expert_load() == {}
    placed = engine.shard_batch(batch)
    key, lr = jax.random.key(0), jnp.zeros((), jnp.float32)
    lowered = engine.train_step_fn.lower(
        engine.params, engine.opt_state, placed, key, lr, engine.step_state)
    leaves = jax.tree_util.tree_leaves
    n_state = len(leaves(engine.params)) + len(leaves(engine.opt_state))
    assert len(leaves(lowered.args_info)) == n_state + len(leaves(placed)) + 2
    assert len(leaves(lowered.out_info)) == 1 + n_state
    text = lowered.as_text()
    main = text[text.index("func.func public @main("):]
    results = main[main.index("->"):main.index("{\n")]
    assert len(re.findall(r"tensor<", results)) == 1 + n_state
    # jit leaves out an argument nothing reads: never more than the trees'
    args = main[:main.index("->")]
    assert len(re.findall(r"%arg\d+:", args)) <= n_state + len(
        leaves(placed)) + 2
    # and the module of the step that is handed no step state at all,
    # jitted as the engine jitted it before there was any

    def bare(p, s, b, k, r):
        return engine._step_fn(p, s, b, k, r)[:3]
    bare.__name__ = bare.__qualname__ = "counted_step"
    whole = NamedSharding(engine.mesh, PartitionSpec())
    other = jax.jit(
        bare, in_shardings=(engine._param_sh, engine._slot_sh, None, None,
                            None),
        out_shardings=(whole, engine._param_sh, engine._slot_sh),
        donate_argnums=(0, 1)).lower(
        engine.params, engine.opt_state, placed, key, lr).as_text()
    assert other == text
    engine.step(batch)
    assert costmodel.step_expert_load() is None


def _series(page, name):
    out = {}
    for line in page.splitlines():
        m = re.match(r"p1t_%s\{(.*)\} (\S+)$" % name, line)
        if m:
            out[m.group(1)] = float(m.group(2))
    return out


def test_the_metrics_page_takes_the_differences_at_a_readback():
    """Under ``obs_metrics`` the counts reach the process registry at a
    loss readback, as differences (two reads add up to the totals); with
    the flag off the step keeps no copy and the page has no series."""
    from paddle1_tpu.obs.registry import (render_process_groups,
                                          reset_process_registry)
    reset_process_registry()
    paddle.seed(8)
    net = Net()
    engine = _engine(net, recompute=True)
    batches = _batches(4)
    float(engine.step(batches[0]))
    # (the trace-time ``moe_sum_picks_arm_total{arm}`` is no load series)
    assert not engine._load_seen and not re.search(
        r"moe_(picks|late|expert_rows|capacity)", render_process_groups())
    try:
        with flags_guard(obs_metrics=True):
            float(engine.step(batches[1]))
            first = _series(render_process_groups(), "moe_picks_held_total")
            engine.step(batches[2])
            float(engine.step(batches[3]))
            page = render_process_groups()
    finally:
        async_loss.set_readback_observer(None)
    total = engine.expert_load()
    held = _series(page, "moe_picks_held_total")
    assert set(held) == {'layer="b0.moe"', 'layer="b1.moe"'}
    assert 0 < first['layer="b0.moe"'] < held['layer="b0.moe"']
    for path, load in total.items():
        assert held[f'layer="{path}"'] == load["held_picks"]
        assert _series(page, "moe_picks_late_total")[
            f'layer="{path}"'] == 0
        assert _series(page, "moe_late_steps_total")[
            f'layer="{path}"'] == 0
        assert _series(page, "moe_capacity_rows")[
            f'layer="{path}"'] == load["capacity_rows"]
        rows = _series(page, "moe_expert_rows_total")
        for e, n in enumerate(load["rows"]):
            assert rows[f'layer="{path}",expert="{HELD[0] + e}"'] == n
    reset_process_registry()


def test_differences_are_taken_modulo_two_to_the_32():
    """A 32-bit total that wrapped between two reads still gives the
    right difference."""
    from paddle1_tpu.obs.registry import (process_group,
                                          reset_process_registry)
    reset_process_registry()
    paddle.seed(9)
    net = Net()
    engine = _engine(net)
    before = np.full(HELD[1] + 4, 2 ** 31 - 5, np.int64)
    after = (before + 9).astype(np.uint32).astype(np.int32)   # wrapped
    assert after[0] < 0
    engine._load_shown = {"b0.moe": before}
    engine._load_seen.append({"b0.moe": jnp.asarray(after)})
    engine._publish_load(obs.process_registry())
    assert process_group("layer").child("b0.moe").counter(
        "moe_picks_held_total").value == 9
    reset_process_registry()
