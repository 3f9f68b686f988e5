"""Laguna-XS.2 (ISSUE 47): the model against the plain reference
(``benchmarks/reference/laguna_xs2_33b_a3b.py``) in float32 with
recomputation on and off, a layer of 6 and layers of 8 query heads a
key/value head in one stack, the per-head gate against its formula, which layers
turn how, the sixteen shares of an expert layer against the uncut one, and
the names and counters a traced step carries. (Three updates through
``ParallelEngine`` against the reference's own AdamW inside the
rehearsal's limits, and the bfloat16 control outside them:
``bench_yardstick/test_bench_yardstick.py`` drives both through the
harness for every configuration, this one among them.) CPU, tiny sizes,
seeded weights; the kernels in interpreter mode at tile-aligned sizes."""

import dataclasses
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
from benchmarks.programs import laguna_xs2_33b_a3b as program  # noqa: E402
from benchmarks.programs import make_optimizer  # noqa: E402
from benchmarks.reference import kanana2_30b_a3b as blocks_of  # noqa: E402
from benchmarks.reference import laguna_xs2_33b_a3b as ref  # noqa: E402
from decoder_cases import (  # noqa: E402,F401
    NM, Decoder, Reference, decoder, eager_kernel_run,
    eager_loss_and_grads, eager_run, fresh_obs, grads_follow, ids_batch,
    logits_follow, next_token_loss, reference,
    test_a_step_trains_and_carries_the_scopes_and_the_counters,
    test_recomputation_changes_neither_loss_nor_gradients,
    test_the_model_follows_the_reference,
    test_the_reference_in_blocks_is_the_reference, traced_outputs)
from paddle1_tpu import nn  # noqa: E402
from paddle1_tpu.core.flags import flags_guard  # noqa: E402
from paddle1_tpu.core.tensor import Tensor  # noqa: E402
from paddle1_tpu.framework.param_attr import ParamAttr  # noqa: E402
from paddle1_tpu.nn.initializer import Normal  # noqa: E402
from paddle1_tpu.obs.registry import process_group  # noqa: E402
from paddle1_tpu.text.models import (  # noqa: E402
    LagunaAttention, LagunaForPretraining, LagunaPretrainingCriterion)

# the published group, how each type of layer turns; at a head of 16 the
# full layers turn 8 channels in 4 pairs (lo 0, hi 2: pair 0 keeps its
# frequency, pair 1 is blended, pairs 2 and 3 are slowed 64 times)
ROPE = {"full_attention": {
            "rope_theta": 500000, "rope_type": "yarn", "factor": 64,
            "original_max_position_embeddings": 4096, "beta_slow": 1,
            "beta_fast": 64, "attention_factor": 1.4158883083359672,
            "partial_rotary_factor": 0.5},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                              "partial_rotary_factor": 1},
        "original_max_position_embeddings": 4096}
# the reference's configuration keys at hidden 128: the dense layer and one
# period of the published lists behind it, 6 and 8 query heads over 1
# key/value head (groups of 6 and of 8, as the model's 48 / 8 and 64 / 8),
# a window of 8 keys; 16 routed experts of which this share holds 4 (rank 0
# of 4), top-4, beside a shared expert
CFG = {"name": "laguna_xs2_33b_a3b", "vocab_size": 96, "hidden_size": 128,
       "num_hidden_layers": 5, "pipeline_first_layer": 0,
       "layer_types": ["full_attention"] + ["sliding_attention"] * 3
       + ["full_attention"] + ["sliding_attention"] * 3,
       "num_attention_heads_per_layer": [6, 8, 8, 8] * 2,
       "mlp_layer_types": ["dense"] + ["sparse"] * 7,
       "num_key_value_heads": 1, "head_dim": 16, "sliding_window": 8,
       "rope_parameters": ROPE, "intermediate_size": 64,
       "moe_intermediate_size": 8, "shared_expert_intermediate_size": 8,
       "num_experts": 4, "expert_parallel": 4, "expert_rank": 0,
       "num_experts_per_tok": 4, "moe_routed_scaling_factor": 2.5,
       "rms_norm_eps": 1e-6, "initializer_range": 0.1,
       "embedding_initializer_range": 0.1, "residual_initializer_range": 0.1,
       "optimizer": {"kind": "adamw", "beta1": 0.9, "beta2": 0.95,
                     "epsilon": 1e-8, "weight_decay": 0.1,
                     "lr_schedule": {"kind": "constant", "peak": 1e-3}},
       "precision": {"compute": "float32", "master": "float32",
                     "control": "bfloat16"}}
KINDS = [(None, 6), (8, 8), (8, 8), (8, 8), (None, 6)]


def _build(cfg):
    held = cfg["num_experts"]
    return LagunaForPretraining(
        layer_types=program.stage(cfg, "layer_types"),
        num_attention_heads_per_layer=program.stage(
            cfg, "num_attention_heads_per_layer"),
        mlp_layer_types=program.stage(cfg, "mlp_layer_types"),
        num_experts=held * cfg["expert_parallel"],
        held_experts=(cfg["expert_rank"] * held, held),
        **{k: cfg[k] for k in (
            "vocab_size", "hidden_size", "num_key_value_heads", "head_dim",
            "sliding_window", "rope_parameters", "intermediate_size",
            "moe_intermediate_size", "num_experts_per_tok",
            "shared_expert_intermediate_size", "moe_routed_scaling_factor",
            "rms_norm_eps", "initializer_range")})


def _buffers(cfg):
    """The selection biases of the expert layers: states, not weights."""
    return frozenset(p for p, r, _ in program.leaves(cfg)
                     if r.startswith("e_bias."))


_batch = ids_batch(CFG["vocab_size"])
_loss = next_token_loss(LagunaPretrainingCriterion)


def _grads_against_the_reference(cfg, weights=None):
    """The model's loss and gradient of every leaf beside the
    reference's, float32, under a configuration of the case's own."""
    own = dataclasses.replace(DECODER, cfg=cfg, buffers=_buffers(cfg))
    mine = Reference(own, cfg, weights)
    model, batch = mine.model(), _batch()
    loss, grads = eager_loss_and_grads(own, model, batch)
    assert loss == pytest.approx(grads_follow(own, grads, mine, batch),
                                 rel=2e-5)
    return model, mine


# -- the model against the reference ----------------------------------------

def _the_kinds_of_layers_and_the_logits(model, weights, batch):
    """A layer of 6 and layers of 8 query heads a key/value head in one
    stack, each of its kind, its own turn and its own feed-forward."""
    blocks = model.layers.blocks
    assert [(b.self_attn.window, b.self_attn.num_heads) for b in blocks] \
        == KINDS
    assert all(b.self_attn.num_kv_heads == 1 for b in blocks)
    assert [tuple(b.self_attn.q_proj.weight.shape) for b in blocks] \
        == [(128, heads * 16) for _, heads in KINDS]
    assert [tuple(b.self_attn.gate_proj.weight.shape) for b in blocks] \
        == [(128, heads) for _, heads in KINDS]
    for b in blocks:
        turn = b.self_attn.rotary
        if b.self_attn.window is None:    # 4 of 8 pairs, YaRN's, scaled
            assert len(turn["frequencies"]) == 4 and turn["scale"] \
                == ROPE["full_attention"]["attention_factor"]
        else:
            assert turn == {"theta": 10000}
    assert [type(b.mlp).__name__ for b in blocks] \
        == ["GatedFeedForward"] + ["RoutedExperts"] * 4
    assert all(b.mlp.scoring == "sigmoid" and b.mlp.shared_experts
               is not None and b.mlp.routed_scaling_factor == 2.5
               for b in blocks[1:])
    logits_follow(DECODER, model, weights, batch)


# the three lists apart from each other: kinds, head counts and
# feed-forwards where each says, and nowhere else
@pytest.mark.parametrize("types,heads,ffns", [
    ("sfsfs", [8, 6, 8, 6, 8], "sdsds")])
def test_a_layer_is_what_the_three_lists_say(types, heads, ffns):
    long = {"f": "full_attention", "s": "sliding_attention", "d": "dense"}
    cfg = {**CFG, "layer_types": [long[t] for t in types],
           "num_attention_heads_per_layer": heads,
           "mlp_layer_types": [long.get(f, "sparse") for f in ffns]}
    model, _ = _grads_against_the_reference(cfg)
    assert [(b.self_attn.window is not None, b.self_attn.num_heads,
             type(b.mlp).__name__ == "RoutedExperts")
            for b in model.layers.blocks] \
        == [(t == "s", h, f == "s") for t, h, f in zip(types, heads, ffns)]


def test_a_stage_runs_its_own_stretch_of_the_lists():
    cfg = {**CFG, "pipeline_first_layer": 3, "num_hidden_layers": 3}
    model, _ = _grads_against_the_reference(cfg)
    assert [(b.self_attn.window, b.self_attn.num_heads)
            for b in model.layers.blocks] == [(8, 8), (None, 6), (8, 8)]
    assert ref.layer_kinds(cfg) == [
        ("sliding_attention", 8, "sparse"), ("full_attention", 6, "sparse"),
        ("sliding_attention", 8, "sparse")]
    with pytest.raises(ValueError, match="name 2, 3 and 2 layers"):
        LagunaForPretraining(layer_types=["full_attention"] * 2,
                             num_attention_heads_per_layer=[8] * 3,
                             mlp_layer_types=["dense"] * 2)


def test_the_kernels_and_the_composition_agree_on_the_model(
        reference, eager_kernel_run):
    """At a tile-aligned row the kernels (interpreter mode, the window's
    rule on three layers in groups of 8, the causal rule on two in groups
    of 6) and the dense composition give one loss and one gradient. The
    kernels' side is the file's one eager run of that row."""
    with flags_guard(flash_attention="never"):
        loss, grads = eager_loss_and_grads(DECODER, reference.model(),
                                           eager_kernel_run.batch)
    assert eager_kernel_run.loss == pytest.approx(loss, rel=1e-5)
    for k, g in grads.items():
        a = eager_kernel_run.grads[k]
        assert np.linalg.norm(a - g) <= 2e-4 * max(np.linalg.norm(g), 1e-4), k


# -- the gate ------------------------------------------------------------------

@pytest.mark.parametrize("heads,window", [(6, None), (8, 8)])
def test_the_gate_is_one_sigmoid_a_position_a_head_before_o_proj(heads,
                                                                 window):
    """``concat_n(sigmoid(u W_gate)_n * o_n) W_o`` by hand in numpy from
    the heads' outputs ``o_n``."""
    paddle.seed(47)
    layer = LagunaAttention(
        64, heads, 2, 16, window=window, rotary=dict(theta=10000.0),
        weight_attr=ParamAttr(initializer=Normal(std=0.3)))
    rng = np.random.default_rng(heads)
    u = rng.standard_normal((2, 12, 64)).astype(np.float32)
    got = layer(Tensor(u)).numpy()
    w_gate = np.asarray(layer.gate_proj.weight.data)
    w_o = np.asarray(layer.o_proj.weight.data)
    # the heads' outputs o_n: the same q, k and v under a gate of weights
    # zero, which halves every head, and an ``o_proj`` that passes twice
    # what it is given
    passing = LagunaAttention(
        64, heads, 2, 16, window=window, rotary=dict(theta=10000.0))
    for name in ("q_proj", "k_proj", "v_proj"):
        getattr(passing, name).weight.data = getattr(layer, name).weight.data
    passing.gate_proj.weight.data = jnp.zeros((64, heads), jnp.float32)
    passing.o_proj = nn.Linear(heads * 16, heads * 16, None, False)
    passing.o_proj.weight.data = 2.0 * jnp.eye(heads * 16, dtype=jnp.float32)
    o = passing(Tensor(u)).numpy().reshape(2, 12, heads, 16)
    gate = 1 / (1 + np.exp(-(u @ w_gate)))                  # [2, 12, heads]
    want = (o * gate[..., None]).reshape(2, 12, heads * 16) @ w_o
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    # one value a head: every channel of a head is scaled alike
    assert gate.shape == (2, 12, heads)
    assert np.abs(gate - 0.5).max() > 0.2


# -- the sixteen shares ----------------------------------------------------------

def _experts(tokens, num_experts, top_k, held, hidden=32, width=8, seed=0,
             shared=8):
    paddle.seed(seed)
    layer = nn.RoutedExperts(
        hidden, width, num_experts, top_k, held=held, shared_width=shared,
        routed_scaling_factor=2.5, scoring="sigmoid",
        weight_attr=ParamAttr(initializer=Normal(std=0.3)))
    x = np.random.default_rng(seed).standard_normal(
        (tokens, hidden)).astype(np.float32)
    return layer, x


def test_the_sixteen_shares_add_up_to_the_whole_layer():
    """The share test: with the same weights, the routed parts of the
    sixteen shares (2 experts of 32 each, top-8 of sigmoid scores times
    2.5) and the shared expert's output counted once are the uncut
    layer's, which is the reference's uncut expert layer; and what a share
    computes, its shared expert with it, is what the reference gives that
    share. The seventeen layers are traced under one ``jax.jit``."""
    tokens, total, top_k, per = 48, 32, 8, 2
    whole, x = _experts(tokens, total, top_k, None)
    shares = [_experts(tokens, total, top_k, (per * rank, per))[0]
              for rank in range(16)]
    for rank, share in enumerate(shares):
        share.router.data = whole.router.data
        share.gate_up_proj.data = whole.gate_up_proj.data[per * rank:][:per]
        share.down_proj.data = whole.down_proj.data[per * rank:][:per]
        for name in ("gate_proj", "up_proj", "down_proj"):
            getattr(share.shared_experts, name).weight.data = getattr(
                whole.shared_experts, name).weight.data
    want, *parts = traced_outputs([whole] + shares, x)
    shared = whole.shared_experts(Tensor(x)).numpy()
    cfg = {"num_experts": total, "expert_parallel": 1, "expert_rank": 0,
           "num_experts_per_tok": top_k, "moe_intermediate_size": 8,
           "moe_routed_scaling_factor": 2.5}
    lp = {"router": whole.router.data,
          "e_bias": whole.e_score_correction_bias.data,
          "e_gate_up": whole.gate_up_proj.data,
          "e_down": whole.down_proj.data,
          "s_gate": whole.shared_experts.gate_proj.weight.data,
          "s_up": whole.shared_experts.up_proj.weight.data,
          "s_down": whole.shared_experts.down_proj.weight.data}

    def plain(lp, cfg):
        return jax.jit(lambda x, lp: ref.experts(x, lp, cfg, NM))(
            jnp.asarray(x), lp)
    np.testing.assert_allclose(want, plain(lp, cfg), rtol=1e-4, atol=1e-5)
    for rank, (share, part) in enumerate(zip(shares, parts)):
        np.testing.assert_allclose(part, plain(
            {**lp, "e_gate_up": share.gate_up_proj.data,
             "e_down": share.down_proj.data},
            {**cfg, "num_experts": per, "expert_parallel": 16,
             "expert_rank": rank}), rtol=1e-4, atol=1e-5)
    # every share computes the shared expert alike: counted once
    routed = sum(part - shared for part in parts)
    np.testing.assert_allclose(routed + shared, want, rtol=1e-4, atol=1e-5)
    assert np.abs(shared).max() > 1e-2 and np.abs(routed).max() > 1e-2


# -- a traced step ------------------------------------------------------------

def _the_steps_own_counters(engine):
    # the sums of a token's picks, on rows half a 32-bit lane row wide
    # (128 bf16): 3 an expert layer, the forward's, the recomputed
    # segment's and the transpose of the dispatch's gather
    arms = process_group("arm")
    sums = {arm: arms.child(arm).counter("moe_sum_picks_arm_total").value
            for arm in ("kernel", "gather")}
    assert sums == {"kernel": 0, "gather": 3 * 4}


def _the_steps_own_scopes(engine, named):
    for i, (window, heads) in enumerate(KINDS):
        at = f"/layers/recompute/{i}/"
        kind, other = (("window", "global") if window else
                       ("global", "window"))
        ops = ["input_layernorm/rms_norm", "post_attention_layernorm/rms_norm",
               "self_attn/q_proj/linear", "self_attn/k_proj/linear",
               "self_attn/v_proj/linear", "self_attn/o_proj/linear",
               "self_attn/rotary_embedding",
               f"self_attn/{kind}/scaled_dot_product_attention",
               "self_attn/gate/gate_proj/linear", "self_attn/gate/sigmoid"]
        ops += (["mlp/" + op for op in (
            "gate_proj/linear", "up_proj/linear", "down_proj/linear",
            "swiglu")] if i == 0 else ["mlp/moe/" + op for op in (
                "moe_router", "moe_dispatch", "routed_experts",
                "moe_combine", "shared_experts/gate_proj/linear",
                "shared_experts/swiglu")])
        for op in ops:
            assert any(at + op in s for s in named), (i, op)
        assert not [s for s in named if at + f"self_attn/{other}" in s]
    assert not [s for s in named if "/layers/recompute/0/mlp/moe" in s]
    assert any("/next_token_loss" in s for s in named)
    # the two kernels under the layer's kind; nothing of the gate is the
    # attention op's, and the gate's product lies under its scope
    assert all("/self_attn/window/" in s or "/self_attn/global/" in s
               for s in named if "p1t_flash_attention" in s)
    assert not [s for s in named if "/self_attn/gate/" in s
                and "scaled_dot_product_attention" in s]
    assert any("/self_attn/gate/" in s and "mul" in s.rsplit("/", 1)[-1]
               for s in named)
    # by the rule: calls of batch 2 x 8 heads under the window's, of 2 x 6
    # under the causal one
    pairs = process_group("rule")
    window, causal = (pairs.child(n).counter("flash_pairs_total").value
                      for n in ("window", "causal"))
    assert causal % (2 * 6 * (128 * 129 // 2)) == 0 and causal > 0
    assert window % (2 * 8 * (8 * 9 // 2 + 120 * 8)) == 0 and window > 0
    assert set(pairs.labels()) == {"window", "causal"}
    # what a segment keeps: the kernels' outputs, the stream after
    # attention, a feed-forward's output (the dense layer's and the shared
    # experts'), the router's and the sorts' small arrays
    kept = process_group("name")
    assert {n for n in kept.labels() if kept.child(n).counter(
        "recompute_kept_bytes_total").value > 0} == {
        "flash_attention_out", "flash_attention_lse", "stream_after_attn",
        "gated_ffn_out", "routed_scores", "routed_chosen", "routed_order", "routed_where",
        "routed_sizes", "routed_overflow", "routed_row_weights"}
    # the expert layers count their load: four of them, top-4 of 16
    load = engine.expert_load()
    assert len(load) == 4 and all(c["steps"] == 3 for c in load.values())
    assert all(c["num_experts"] == 16 and c["held"] == 4
               and c["picks_made_a_step"] == 2 * 128 * 4
               for c in load.values())


# the shared cases' model (decoder_cases.py). The reference in blocks: a
# row's positions through the feed-forward 8 at a time, the one key/value
# head and 8 of its queries a block of scores (at 8 query heads; 10 at 6),
# two experts a group, a row a block.
DECODER = Decoder(
    cfg=CFG, ref=ref, program=program, build=_build, criterion=_loss,
    batch=_batch, follows_also=_the_kinds_of_layers_and_the_logits,
    buffers=_buffers(CFG),
    blocks_seq=32, scans=10, blocks=(
        (ref, "ROW_BLOCK_POSITIONS", 16),
        (ref, "SCORE_BLOCK_BYTES", 4 * 32 * 8 * 8),
        (blocks_of, "EXPERTS_BLOCK_BYTES", 4 * 16 * 128 * 2),
        (blocks_of, "BLOCK_TOKENS", 32)),
    optimizer=lambda parameters: make_optimizer(
        paddle, CFG["optimizer"], parameters)[0],
    step_counters=_the_steps_own_counters, step_scopes=_the_steps_own_scopes,
    kernel_sides_on_the_tape=True, shared_experts=True)
