"""SmallThinker-21BA3B-Instruct's expert layer and head (ISSUE 43), out of
``test_smallthinker.py`` (ISSUE 46: no file is one worker's wall): what the
router reads, in the layer and in a decoder layer, the gate's activation on
the main and the overflow path, the share test on a whole decoder layer,
and the vocabulary slice. CPU, tiny sizes, seeded weights."""

import os
import sys

import jax.numpy as jnp
import jax
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import paddle1_tpu as paddle  # noqa: E402
from benchmarks import spec, traffic  # noqa: E402
from benchmarks.reference import smallthinker_21b_a3b as ref  # noqa: E402
from decoder_cases import NM, traced_outputs  # noqa: E402
from paddle1_tpu import nn  # noqa: E402
from paddle1_tpu.core.tensor import Tensor  # noqa: E402
from paddle1_tpu.framework.param_attr import ParamAttr  # noqa: E402
from paddle1_tpu.nn import layer_moe  # noqa: E402
from paddle1_tpu.nn.initializer import Normal  # noqa: E402
from paddle1_tpu.text.models import SmallThinkerDecoderLayer  # noqa: E402


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
    # what every chip computes alike: the stream after attention
    u = whole.input_layernorm(Tensor(x))
    a = (Tensor(x) + whole.self_attn(u)).numpy()[0]
    shares = [share((8 * rank, 8)) for rank in range(8)]
    for rank, mine in enumerate(shares):
        for name, p in whole.named_parameters():
            value = p.data
            if name in ("mlp.gate_up_proj", "mlp.down_proj"):
                value = value[8 * rank:][:8]
            dict(mine.named_parameters())[name].data = value
    # the nine layers traced under one jax.jit, as a step traces one
    got, *parts = (out[0] for out in traced_outputs([whole] + shares, x))

    def plain(lp, cfg):
        return np.asarray(jax.jit(lambda row, lp: ref.layer_row(
            row, lp, cfg, NM, 8, True))(jnp.asarray(x[0]), lp))
    want = plain(lp, cfg)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
    for rank, (mine, part) in enumerate(zip(shares, parts)):
        # what a share computes is what the reference gives that share
        np.testing.assert_allclose(part, plain(
            {**lp, "e_gate_up": mine.mlp.gate_up_proj.data,
             "e_down": mine.mlp.down_proj.data},
            {**cfg, "moe_num_primary_experts": 8, "expert_parallel": 8,
             "expert_rank": rank}), rtol=1e-4, atol=1e-5)
    parts = sum(part - a for part in parts)
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
