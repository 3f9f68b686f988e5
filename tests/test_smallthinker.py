"""SmallThinker-21BA3B-Instruct (ISSUE 43): the model against the plain
reference (``benchmarks/reference/smallthinker_21b_a3b.py``) in float32
with recomputation on and off, three updates through ``ParallelEngine``,
the bfloat16 control, what the router reads, which layers carry positions
and which see a window, the gate's activation, the share test, the
vocabulary slice, and the names and counters a traced step carries. CPU,
tiny sizes, seeded weights; the kernels in interpreter mode at
tile-aligned sizes."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import paddle1_tpu as paddle  # noqa: E402
from benchmarks import check, spec, traffic  # noqa: E402
from benchmarks.reference import smallthinker_21b_a3b as ref  # noqa: E402
from benchmarks.reference.numerics import Numerics  # noqa: E402
from paddle1_tpu import nn, obs  # noqa: E402
from paddle1_tpu.core.flags import flags_guard  # noqa: E402
from paddle1_tpu.core.tensor import Tensor  # noqa: E402
from paddle1_tpu.distributed import ParallelEngine, build_mesh  # noqa: E402
from paddle1_tpu.framework.param_attr import ParamAttr  # noqa: E402
from paddle1_tpu.nn import layer_moe  # noqa: E402
from paddle1_tpu.nn.initializer import Normal  # noqa: E402
from paddle1_tpu.obs import costmodel  # noqa: E402
from paddle1_tpu.obs.registry import process_group  # noqa: E402
from paddle1_tpu.text.models import (  # noqa: E402
    SmallThinkerDecoderLayer, SmallThinkerForPretraining,
    SmallThinkerPretrainingCriterion)

# the reference's configuration keys at hidden 128: one period of the two
# published lists, 7 query heads over 1 key/value head (a group of 7, as
# the model's 28 / 4), a window of 8 keys; 16 routed experts of which
# this share holds 8 (rank 0 of 2), top-6
CFG = {"name": "smallthinker_21b_a3b", "vocab_size": 96, "hidden_size": 128,
       "num_hidden_layers": 4, "pipeline_first_layer": 0,
       "sliding_window_layout": [0, 1, 1, 1] * 2,
       "rope_layout": [0, 1, 1, 1] * 2, "sliding_window_size": 8,
       "num_attention_heads": 7, "num_key_value_heads": 1, "head_dim": 16,
       "moe_ffn_hidden_size": 16, "moe_num_primary_experts": 8,
       "expert_parallel": 2, "expert_rank": 0,
       "moe_num_active_primary_experts": 6, "rms_norm_eps": 1e-6,
       "rope_theta": 1500000, "initializer_range": 0.2,
       "embedding_initializer_range": 0.2, "residual_initializer_range": 0.2,
       "optimizer": {"kind": "adamw", "beta1": 0.9, "beta2": 0.95,
                     "epsilon": 1e-8, "weight_decay": 0.1,
                     "lr_schedule": {"kind": "constant", "peak": 1e-3}},
       "precision": {"compute": "float32", "master": "float32",
                     "control": "bfloat16"}}
NM = Numerics()


@pytest.fixture
def _fresh_obs():
    obs.reset_process_registry()
    obs.hbm.reset()
    yield
    obs.reset_process_registry()
    obs.hbm.reset()


def _model(cfg=CFG, seed=4):
    """(the Layer, the reference's weights it was loaded with)."""
    from benchmarks.programs import load_weights
    from benchmarks.programs import smallthinker_21b_a3b as program
    weights = ref.init_params(cfg, jax.random.key(seed))
    held = cfg["moe_num_primary_experts"]
    model = SmallThinkerForPretraining(
        sliding_window_layout=program.stage(cfg, "sliding_window_layout"),
        rope_layout=program.stage(cfg, "rope_layout"),
        moe_num_primary_experts=held * cfg["expert_parallel"],
        held_experts=(cfg["expert_rank"] * held, held),
        **{k: cfg[k] for k in (
            "vocab_size", "hidden_size", "sliding_window_size",
            "num_attention_heads", "num_key_value_heads", "head_dim",
            "moe_ffn_hidden_size", "moe_num_active_primary_experts",
            "rope_theta", "rms_norm_eps", "initializer_range")})
    load_weights(model, {p: weights[r] for p, r, _ in program.leaves(cfg)})
    return model, weights


def _batch(batch=2, seq=16, seed=0):
    rng = np.random.default_rng(seed)
    return {"ids": rng.integers(0, CFG["vocab_size"],
                                (batch, seq)).astype(np.int32)}


def _loss(model, batch):
    ids = Tensor(batch["ids"])
    labels = model.next_token_labels(ids)
    return SmallThinkerPretrainingCriterion()(model(ids, labels), labels)


def _grads_against_the_reference(cfg, seq=16):
    """The model's loss and gradient of every leaf beside the
    reference's, float32."""
    from benchmarks.programs import smallthinker_21b_a3b as program
    model, weights = _model(cfg)
    batch = _batch(seq=seq)
    ids = jnp.asarray(batch["ids"])
    loss = _loss(model, batch)
    want, grads = jax.value_and_grad(
        lambda w: ref.loss(w, {"ids": ids}, cfg, NM)[0])(weights)
    assert float(loss) == pytest.approx(float(want), rel=2e-5)
    loss.backward()
    named = dict(model.named_parameters())
    leaves = program.leaves(cfg)
    assert {p for p, _, _ in leaves} == set(named)     # no buffer, no bias
    for p, r, _ in leaves:
        g, w = named[p].grad.numpy(), np.asarray(grads[r])
        assert np.linalg.norm(g - w) <= 2e-4 * max(np.linalg.norm(w), 1e-4), p
    return model, weights


# -- the model against the reference ----------------------------------------

def test_the_model_follows_the_reference():
    """Logits, loss and every gradient leaf in float32. Tolerances: the
    two sides sum the same float32 products in another order (2e-5 of a
    loss, 2e-4 of a leaf's gradient norm, 1e-4 of the largest logit)."""
    model, weights = _grads_against_the_reference(CFG)
    blocks = model.layers.blocks
    assert [b.self_attn.window for b in blocks] == [None, 8, 8, 8]
    assert [b.self_attn.rotary for b in blocks] == [False, True, True, True]
    assert all(type(b.mlp).__name__ == "RoutedExperts"
               and b.mlp.gate_activation == "relu"
               and b.mlp.scoring == "softmax" for b in blocks)
    batch = _batch()
    logits = model(Tensor(batch["ids"])).numpy()
    want = np.asarray(ref.head_logits(ref.hidden(
        weights, jnp.asarray(batch["ids"]), CFG, NM), weights, CFG, NM))
    assert logits.shape == (2, 16, CFG["vocab_size"])
    np.testing.assert_allclose(logits, want, rtol=0,
                               atol=1e-4 * np.abs(want).max())


# window layers and layers with positions where the two lists say, and
# nowhere else: the lists apart from each other, and a window shorter and
# longer than the row
@pytest.mark.parametrize("windows,positions,size", [
    ([1, 0, 1, 0], [0, 0, 1, 1], 8), ([1, 1, 1, 1], [0, 0, 0, 0], 3),
    ([0, 0, 0, 0], [1, 1, 1, 1], 8), ([0, 1, 1, 1], [0, 1, 1, 1], 64)])
def test_a_layers_kind_is_what_the_two_lists_say(windows, positions, size):
    cfg = {**CFG, "sliding_window_layout": windows, "rope_layout": positions,
           "sliding_window_size": size}
    model, weights = _grads_against_the_reference(cfg)
    assert [b.self_attn.window for b in model.layers.blocks] \
        == [size if w else None for w in windows]
    assert [b.self_attn.rotary for b in model.layers.blocks] \
        == [bool(p) for p in positions]
    # the reference under the published lists is another function
    ids = jnp.asarray(_batch()["ids"])
    other = ref.loss(weights, {"ids": ids}, CFG, NM)[0]
    mine = ref.loss(weights, {"ids": ids}, cfg, NM)[0]
    if (windows, positions, size) != ([0, 1, 1, 1], [0, 1, 1, 1], 64):
        assert abs(float(other) - float(mine)) > 1e-4 * float(mine)


def test_a_stage_runs_its_own_stretch_of_the_lists():
    cfg = {**CFG, "pipeline_first_layer": 2, "num_hidden_layers": 3}
    model, _ = _grads_against_the_reference(cfg)
    assert [(b.self_attn.window, b.self_attn.rotary)
            for b in model.layers.blocks] == [(8, True), (8, True),
                                              (None, False)]
    assert ref.layer_kinds(cfg) == [(8, True), (8, True), (None, False)]


def test_the_reference_in_blocks_is_the_reference(monkeypatch):
    """At the cell's size the reference takes a row's positions through
    the experts in blocks and a block of one key/value head's queries
    against the keys at a time, through ``lax.map``; at a test's size it
    takes each whole."""
    from benchmarks.reference import kanana2_30b_a3b as blocks_of
    weights = ref.init_params(CFG, jax.random.key(4))
    batch = {"ids": jnp.asarray(_batch(seq=32)["ids"])}

    def run():
        return jax.value_and_grad(
            lambda w: ref.loss(w, batch, CFG, NM)[0])(weights)
    whole, g_whole = run()
    monkeypatch.setattr(ref, "ROW_BLOCK_POSITIONS", 8)
    monkeypatch.setattr(ref, "SCORE_BLOCK_BYTES", 4 * 32 * 7 * 8)
    monkeypatch.setattr(ref, "EXPERTS_BLOCK_BYTES", 4 * 8 * 128 * 2)
    monkeypatch.setattr(blocks_of, "BLOCK_TOKENS", 32)
    text = str(jax.make_jaxpr(lambda w: ref.loss(w, batch, CFG, NM)[0])(
        weights))
    assert text.count("scan") >= 8      # queries and experts, four layers
    blocks, g_blocks = run()
    assert float(blocks) == pytest.approx(float(whole), rel=1e-5)
    for k in g_whole:
        a, b = np.asarray(g_blocks[k]), np.asarray(g_whole[k])
        assert np.linalg.norm(a - b) <= 1e-5 * max(np.linalg.norm(b), 1e-3), k


@pytest.mark.parametrize("attention", ["dense", "kernel"])
def test_recomputation_changes_neither_loss_nor_gradients(attention):
    batch = _batch(seq=128 if attention == "kernel" else 16)
    got = {}
    with flags_guard(
            flash_attention="always" if attention == "kernel" else "never"):
        for remat in (False, True):
            model, _ = _model()
            model.layers.enable_recompute = remat
            loss = _loss(model, batch)
            loss.backward()
            got[remat] = (float(loss), {k: p.grad.numpy() for k, p in
                                        model.named_parameters()})
    assert got[True][0] == pytest.approx(got[False][0], rel=1e-6)
    for k, g in got[False][1].items():
        np.testing.assert_allclose(got[True][1][k], g, rtol=1e-4,
                                   atol=1e-6 * np.abs(g).max())


def test_the_kernels_and_the_composition_agree_on_the_model():
    """At a tile-aligned row the kernels (interpreter mode, the window's
    rule on three layers, the causal rule on one) and the dense
    composition give one loss and one gradient."""
    batch = _batch(seq=128, seed=3)
    got = {}
    for arm in ("always", "never"):
        with flags_guard(flash_attention=arm):
            model, _ = _model()
            loss = _loss(model, batch)
            loss.backward()
            got[arm] = (float(loss), {k: p.grad.numpy() for k, p in
                                      model.named_parameters()})
    assert got["always"][0] == pytest.approx(got["never"][0], rel=1e-5)
    for k, g in got["never"][1].items():
        a = got["always"][1][k]
        assert np.linalg.norm(a - g) <= 2e-4 * max(np.linalg.norm(g), 1e-4), k


# -- three updates, and the control -------------------------------------------

def _engine(amp=None, lr=1e-3):
    from benchmarks.programs import make_optimizer
    model, weights = _model()
    opt, first_grad = make_optimizer(paddle, CFG["optimizer"],
                                     model.parameters())
    crit = SmallThinkerPretrainingCriterion()

    def loss_fn(m, b):
        ids = Tensor(b["ids"])
        labels = m.next_token_labels(ids)
        return crit(m(ids, labels), labels)
    engine = ParallelEngine(model, opt, loss_fn, amp_dtype=amp,
                            mesh=build_mesh(dp=1, devices=jax.devices()[:1]),
                            recompute=True)
    return engine, weights, first_grad


def _records(batches, lrs):
    """(what three steps of the engine show, what the reference records,
    the leaf map): ``check.compare``'s two sides."""
    from benchmarks.programs import smallthinker_21b_a3b as program
    engine, weights, (slot, factor) = _engine()
    start = {k: jnp.array(v) for k, v in engine.params.items()}  # donated
    shown = {"losses": []}
    for i, (batch, lr) in enumerate(zip(batches, lrs)):
        shown["losses"].append(float(engine.step(engine.shard_batch(batch),
                                                 lr=lr)))
        if i == 0:
            first = {k: v[slot] for k, v in engine.opt_state[0].items()}
            shown["grad"] = {k: factor * float(v) for k, v in
                             check.program_norms(first).items()}
            shown["matrices"] = check.program_matrices(first, factor)
    shown["delta"] = {k: float(v) for k, v in check.program_delta_norms(
        engine.params, start).items()}
    sound = check.reference_record(CFG, jax.device_get(weights), batches, lrs)
    return shown, sound, program.leaves(CFG)


def test_three_updates_follow_the_reference_and_bfloat16_does_not():
    """Three AdamW steps through ``ParallelEngine(recompute=True)`` in
    float32 against the reference's own update rule, inside the
    rehearsal's limits; the reference in bfloat16 in the program's place
    is outside them, by the gradient's direction among others."""
    batches = [_batch(batch=8, seq=16, seed=s) for s in (1, 2, 3)]
    lrs = [1e-3] * 3
    limits = spec.limits("smallthinker_21b_a3b", rehearsal=True)
    shown, sound, leaves = _records(batches, lrs)
    ok, rows = check.compare(shown, sound, leaves, limits)
    assert ok, rows
    assert shown["losses"][2] < shown["losses"][0]
    lower = check.reference_record(CFG, {
        k: np.asarray(v) for k, v in ref.init_params(
            CFG, jax.random.key(4)).items()}, batches, lrs, "bfloat16")
    ok, rows = check.compare(check.in_programs_place(lower, leaves), sound,
                             leaves, limits)
    failed = {what for what, _, _, good, _ in rows if not good}
    assert not ok and "grad direction median-matrix gap" in failed, rows


# -- what the router reads, and the gate ----------------------------------------

def _experts(tokens, num_experts, top_k, held, hidden=32, width=8, seed=0,
             **more):
    paddle.seed(seed)
    layer = nn.RoutedExperts(
        hidden, width, num_experts, top_k, held=held, scoring="softmax",
        weight_attr=ParamAttr(initializer=Normal(std=0.3)), **more)
    x = np.random.default_rng(seed).standard_normal(
        (tokens, hidden)).astype(np.float32)
    return layer, x


def _rows(layer):
    return layer.read_load(np.asarray(layer.expert_load.data))["rows"]


def test_the_router_reads_its_own_input_and_the_experts_theirs():
    """``forward(x, router_input=r)``: a change to ``x`` alone leaves the
    picks where they were and moves the result; a change to ``r`` alone
    moves the picks; ``r`` left out is ``x``. The picks are read off the
    layer's own load counters (every expert held: a histogram of the
    choice)."""
    rng = np.random.default_rng(1)
    layer, x = _experts(64, 16, 6, None, gate_activation="relu")
    r = rng.standard_normal(x.shape).astype(np.float32)

    def run(x, r=None):
        layer.expert_load.data = jnp.zeros_like(layer.expert_load.data)
        y = layer(Tensor(x), router_input=None if r is None else Tensor(r))
        return y.numpy(), _rows(layer)
    y, picks = run(x, r)
    weights, chosen = layer_moe.route(jnp.asarray(r), layer.router.data,
                                      None, 6, 1.0, "softmax")
    assert picks == np.bincount(np.asarray(chosen).ravel(),
                                minlength=16).tolist()
    # the experts' input alone: the same picks, another result
    y_x, picks_x = run(x + 0.5 * rng.standard_normal(x.shape).astype(
        np.float32), r)
    assert picks_x == picks and np.abs(y_x - y).max() > 1e-2
    # the router's input alone: other picks
    _, picks_r = run(x, rng.standard_normal(x.shape).astype(np.float32))
    assert picks_r != picks
    # no router input is the experts' input
    y_same, picks_same = run(x, x)
    y_none, picks_none = run(x)
    assert picks_none == picks_same
    np.testing.assert_array_equal(y_none, y_same)
    # and what comes out is the picks' weighted experts over x, by hand
    want = np.zeros_like(y)
    gu, down = (np.asarray(w.data) for w in (layer.gate_up_proj,
                                             layer.down_proj))
    for t in range(x.shape[0]):
        for w, e in zip(np.asarray(weights)[t], np.asarray(chosen)[t]):
            both = x[t] @ gu[e]
            want[t] += w * ((np.maximum(both[:8], 0) * both[8:]) @ down[e])
    np.testing.assert_allclose(y, want, rtol=1e-4, atol=1e-5)


def test_a_layers_router_reads_the_attention_blocks_input():
    """In the decoder layer the router's picks are those of ``N_in(x)``;
    attention, which moves the stream the experts read, does not move
    them."""
    paddle.seed(2)
    attention = dict(num_heads=7, num_kv_heads=1, head_dim=16, window=8)
    experts = dict(expert_width=8, num_experts=16, top_k=6)
    layer = SmallThinkerDecoderLayer(
        64, attention, experts, weight_attr=ParamAttr(
            initializer=Normal(std=0.3)))
    rng = np.random.default_rng(2)
    layer.input_layernorm.weight.data = jnp.asarray(
        1 + 0.3 * rng.standard_normal(64), jnp.float32)
    x = rng.standard_normal((2, 16, 64)).astype(np.float32)

    def picks():
        layer.mlp.expert_load.data = jnp.zeros_like(
            layer.mlp.expert_load.data)
        y = layer(Tensor(x)).numpy()
        return y, _rows(layer.mlp)
    y, got = picks()
    u = ref._norm(jnp.asarray(x), layer.input_layernorm.weight.data, 1e-6)
    _, chosen = ref.route(u.reshape(-1, 64), layer.mlp.router.data,
                          {"moe_num_active_primary_experts": 6})
    assert got == np.bincount(np.asarray(chosen).ravel(),
                              minlength=16).tolist()
    # the stream after attention would have made other picks
    a = x + layer.self_attn(Tensor(np.asarray(u))).numpy()
    m = ref._norm(jnp.asarray(a), layer.post_attention_layernorm.weight.data,
                  1e-6)
    _, after = ref.route(m.reshape(-1, 64), layer.mlp.router.data,
                         {"moe_num_active_primary_experts": 6})
    assert got != np.bincount(np.asarray(after).ravel(),
                              minlength=16).tolist()
    # a planted change to attention's output alone: the picks stay
    layer.self_attn.o_proj.weight.data = 3.0 * layer.self_attn.o_proj.weight.data
    y_moved, still = picks()
    assert still == got and np.abs(y_moved - y).max() > 1e-2


@pytest.mark.parametrize("act", ["silu", "relu"])
def test_the_gate_takes_the_activation_it_is_told(act):
    """Main path and overflow path alike: more held picks than the
    grouped products have rows, so the late ones go through each held
    expert under a mask, with the same activation."""
    tokens, total, held, k, width = 1024, 32, 2, 6, 8
    layer, x = _experts(tokens, total, k, (0, held), gate_activation=act)
    # every token picks the two held experts among its six
    layer.router.data = layer.router.data.at[:, :held].add(8.0)
    x = np.abs(x)
    y = layer(Tensor(x)).numpy()
    load = layer.read_load(np.asarray(layer.expert_load.data))
    assert load["late_picks"] > 0 and load["held_picks"] == held * tokens
    weights, chosen = (np.asarray(a) for a in layer_moe.route(
        jnp.asarray(x), layer.router.data, None, k, 1.0, "softmax"))
    f = {"silu": lambda z: z / (1 + np.exp(-z)),
         "relu": lambda z: np.maximum(z, 0)}[act]
    gu, down = (np.asarray(w.data) for w in (layer.gate_up_proj,
                                             layer.down_proj))
    want = np.zeros_like(y)
    for e in range(held):
        mine = np.where(chosen == e, weights, 0).sum(-1)
        both = x @ gu[e]
        want += mine[:, None] * ((f(both[:, :width]) * both[:, width:])
                                 @ down[e])
    np.testing.assert_allclose(y, want, rtol=2e-4, atol=2e-5)
    with pytest.raises(ValueError):
        nn.RoutedExperts(32, 8, 16, 6, gate_activation="gelu")
    assert nn.RoutedExperts(32, 8, 16, 6).gate_activation == "silu"


# -- the share --------------------------------------------------------------

def test_the_eight_shares_add_up_to_the_whole_layer():
    """The share test, on a whole decoder layer: with the same weights,
    the eight shares' routed parts (8 experts of 64 each, top-6, the
    router reading the attention block's input), with the attention
    residual, which every chip computes alike, counted once, add up to
    what the uncut reference gives for the layer."""
    hidden, width, total, s = 64, 8, 64, 24
    attention = dict(num_heads=7, num_kv_heads=1, head_dim=16, window=8)
    rng = np.random.default_rng(5)
    x = rng.standard_normal((1, s, hidden)).astype(np.float32)

    def share(held):
        paddle.seed(5)
        return SmallThinkerDecoderLayer(
            hidden, attention, dict(expert_width=width, num_experts=total,
                                    top_k=6, held=held),
            weight_attr=ParamAttr(initializer=Normal(std=0.3)))
    whole = share(None)
    cfg = {"hidden_size": hidden, "num_attention_heads": 7,
           "num_key_value_heads": 1, "head_dim": 16, "rope_theta": 1.5e6,
           "rms_norm_eps": 1e-6, "moe_num_primary_experts": total,
           "expert_parallel": 1, "expert_rank": 0,
           "moe_num_active_primary_experts": 6,
           "moe_ffn_hidden_size": width}
    lp = {"n1": whole.input_layernorm.weight.data,
          "n2": whole.post_attention_layernorm.weight.data,
          "wq": whole.self_attn.q_proj.weight.data,
          "wk": whole.self_attn.k_proj.weight.data,
          "wv": whole.self_attn.v_proj.weight.data,
          "wo": whole.self_attn.o_proj.weight.data,
          "router": whole.mlp.router.data,
          "e_gate_up": whole.mlp.gate_up_proj.data,
          "e_down": whole.mlp.down_proj.data}
    want = np.asarray(ref.layer_row(jnp.asarray(x[0]), lp, cfg, NM, 8, True))
    np.testing.assert_allclose(whole(Tensor(x)).numpy()[0], want, rtol=1e-4,
                               atol=1e-5)
    # what every chip computes alike: the stream after attention
    u = whole.input_layernorm(Tensor(x))
    a = (Tensor(x) + whole.self_attn(u)).numpy()[0]
    parts = np.zeros_like(want)
    for rank in range(8):
        mine = share((8 * rank, 8))
        for name, p in whole.named_parameters():
            value = p.data
            if name in ("mlp.gate_up_proj", "mlp.down_proj"):
                value = value[8 * rank:][:8]
            dict(mine.named_parameters())[name].data = value
        got = mine(Tensor(x)).numpy()[0]
        # what a share computes is what the reference gives that share
        np.testing.assert_allclose(got, ref.layer_row(
            jnp.asarray(x[0]),
            {**lp, "e_gate_up": mine.mlp.gate_up_proj.data,
             "e_down": mine.mlp.down_proj.data},
            {**cfg, "moe_num_primary_experts": 8, "expert_parallel": 8,
             "expert_rank": rank}, NM, 8, True), rtol=1e-4, atol=1e-5)
        parts += got - a
    np.testing.assert_allclose(a + parts, want, rtol=1e-4, atol=2e-5)
    assert np.abs(parts).max() > 1e-3


def test_the_vocabulary_slices_concatenate_and_ids_come_from_the_slice():
    """Eight heads over an eighth of the columns each give logits that
    side by side are the whole head's; the cell's traffic draws its ids
    from the slice."""
    from paddle1_tpu.text.models import Kanana2Head
    rng = np.random.default_rng(8)
    hidden, vocab = 32, 64
    h = rng.standard_normal((2, 5, hidden)).astype(np.float32)
    w = (0.3 * rng.standard_normal((hidden, vocab))).astype(np.float32)
    g = (1 + 0.1 * rng.standard_normal(hidden)).astype(np.float32)
    whole = np.asarray(ref.head_logits(
        jnp.asarray(h), {"norm_f": jnp.asarray(g), "lm_head": jnp.asarray(w)},
        {"rms_norm_eps": 1e-6}, NM))
    slices = []
    for r in range(8):
        head = Kanana2Head(hidden, 8, 1e-6)
        head.norm.weight.data = jnp.asarray(g)
        head.proj.weight.data = jnp.asarray(w[:, 8 * r:8 * r + 8])
        slices.append(head(Tensor(h)).numpy())
    np.testing.assert_allclose(np.concatenate(slices, -1), whole, rtol=1e-5,
                               atol=1e-6)
    cell = spec.cell("smallthinker_21b_a3b.pretrain_s16384")
    cfg = spec.config("smallthinker_21b_a3b")
    assert cfg["vocab_size"] == 18992 == 151936 // 8
    env = traffic.environment(cfg, {**cell, "dims": {"seq": 4096}})
    ids = traffic.batches({**cell, "dims": {"seq": 4096}}, env, 2 ** 31 + 7,
                          2)[0]["ids"]
    assert ids.shape == (1, 4096) and ids.dtype == np.int32
    assert 0 <= ids.min() and 18000 < ids.max() < 18992


# -- a traced step ------------------------------------------------------------

def test_a_step_trains_and_carries_the_scopes_and_the_counters(_fresh_obs):
    engine, _, _ = _engine(amp="bfloat16")
    assert engine.model.layers.enable_recompute
    batch = engine.shard_batch(_batch(seq=128))
    with flags_guard(flash_attention="always"):
        losses = [float(engine.step(batch, lr=1e-2)) for _ in range(3)]
        arms = process_group("arm")
        assert arms.child("flash").counter("attention_arm_total").value >= 1
        assert arms.child("dense").counter("attention_arm_total").value == 0
        # the sums of a token's picks, on rows half a 32-bit lane row wide
        # (128 bf16): 3 a layer, the forward's, the recomputed segment's
        # (jax traces it again for the backward pass) and the transpose
        # of the dispatch's gather
        sums = {arm: arms.child(arm).counter("moe_sum_picks_arm_total").value
                for arm in ("kernel", "gather")}
        assert sums == {"kernel": 0, "gather": 3 * len(ref.layer_kinds(CFG))}
        scopes = costmodel.step_op_scopes()
        text = engine.compiled_step_text()
    assert losses[2] < losses[0]
    named = [s for s in scopes.values() if "jvp(loss)" in s]
    for i, (window, rotary) in enumerate(ref.layer_kinds(CFG)):
        at = f"/layers/recompute/{i}/"
        kind, other = (("window", "global") if window else
                       ("global", "window"))
        ops = ["input_layernorm/rms_norm", "post_attention_layernorm/rms_norm",
               "self_attn/q_proj/linear", "self_attn/k_proj/linear",
               "self_attn/v_proj/linear", "self_attn/o_proj/linear",
               f"self_attn/{kind}/scaled_dot_product_attention"]
        ops += ["mlp/moe/" + op for op in (
            "moe_router", "moe_dispatch", "routed_experts", "moe_combine")]
        for op in ops:
            assert any(at + op in s for s in named), (i, op)
        assert not [s for s in named if at + f"self_attn/{other}" in s]
        # positions where the list says, and nowhere else
        assert bool([s for s in named
                     if at + "self_attn/rotary_embedding" in s]) == rotary
    assert not [s for s in named if "shared_experts" in s]
    assert any("/lm_head/head_cross_entropy" in s for s in named)
    assert any("/next_token_loss" in s for s in named)
    # the two kernels under the attention op, under the layer's kind, the
    # forward not run again
    kernels = [s for s in named if "p1t_flash_attention" in s]
    assert kernels and all("/scaled_dot_product_attention/" in s
                           and ("/self_attn/window/" in s
                                or "/self_attn/global/" in s)
                           for s in kernels)
    assert not [s for s in kernels if "/rematted_computation/" in s
                and "p1t_flash_attention_fwd" in s]
    # by the rule: three layers' calls under the window's, one causal
    pairs = process_group("rule")
    window, causal = (pairs.child(n).counter("flash_pairs_total").value
                      for n in ("window", "causal"))
    a_call = 2 * 7            # batch x heads
    assert causal % (a_call * (128 * 129 // 2)) == 0 and causal > 0
    assert window % (3 * a_call * (8 * 9 // 2 + 120 * 8)) == 0 and window > 0
    assert set(pairs.labels()) == {"window", "causal"}
    # what a segment keeps: the kernels' outputs and the router's and
    # sorts' small arrays; the stream after attention carries no name
    kept = process_group("name")
    assert {n for n in kept.labels() if kept.child(n).counter(
        "recompute_kept_bytes_total").value > 0} == {
        "flash_attention_out", "flash_attention_lse", "routed_scores",
        "routed_chosen", "routed_order", "routed_where", "routed_sizes",
        "routed_overflow", "routed_row_weights"}
    # the router is a float32 island under the bf16 autocast
    router = [l for l in text.splitlines()
              if "moe_router" in l and " dot(" in l]
    assert router and all(" f32[" in l.split(" dot(")[0] for l in router)
    # the expert layers count their load: four of them, top-6 of 16
    load = engine.expert_load()
    assert len(load) == 4 and all(c["steps"] == 3 for c in load.values())
    assert all(c["num_experts"] == 16 and c["held"] == 8
               and c["picks_made_a_step"] == 2 * 128 * 6
               for c in load.values())
