"""Nemotron 3 Nano 30B-A3B (ISSUE 50): the model against the plain
reference (``benchmarks/reference/nemotron3_nano_30b_a3b.py``: the
recurrence itself, a position after the other) in float32 with
recomputation on and off, a layer of each kind by the pattern's letters
and a stage's own stretch of them, the convolution and the grouped gated
norm against their formulas, experts without a gate against a loop, the
sixteen shares of an expert layer against the uncut one, and the names
and counters a traced step carries. (Three updates through
``ParallelEngine`` against the reference's own AdamW inside the
rehearsal's limits, and the bfloat16 control outside them:
``bench_yardstick/test_bench_yardstick.py`` drives both through the
harness for every configuration, this one among them.) CPU, tiny sizes,
seeded weights."""

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
from benchmarks.programs import make_optimizer  # noqa: E402
from benchmarks.programs import nemotron3_nano_30b_a3b as program  # noqa: E402
from benchmarks.reference import kanana2_30b_a3b as blocks_of  # noqa: E402
from benchmarks.reference import nemotron3_nano_30b_a3b as ref  # noqa: E402
from benchmarks.reference import smallthinker_21b_a3b as scores_of  # noqa: E402
from decoder_cases import (  # noqa: E402,F401
    NM, Decoder, Reference, decoder, eager_loss_and_grads, eager_run,
    fresh_obs, grads_follow, ids_batch, logits_follow, next_token_loss,
    reference, test_a_step_trains_and_carries_the_scopes_and_the_counters,
    test_recomputation_changes_neither_loss_nor_gradients,
    test_the_model_follows_the_reference,
    test_the_reference_in_blocks_is_the_reference, traced_outputs)
from paddle1_tpu import nn  # noqa: E402
from paddle1_tpu.core.tensor import Tensor  # noqa: E402
from paddle1_tpu.framework.param_attr import ParamAttr  # noqa: E402
from paddle1_tpu.nn.initializer import Normal  # noqa: E402
from paddle1_tpu.obs.registry import process_group  # noqa: E402
from paddle1_tpu.text.models import (  # noqa: E402
    Mamba2Mixer, NemotronHForPretraining, NemotronHPretrainingCriterion)
from paddle1_tpu.text.models.nemotron_h import (  # noqa: E402
    MIXER, causal_conv_silu, gated_group_norm)

PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
# the reference's configuration keys at hidden 32: the published layers 2
# to 6, ``MEM*E`` (every kind, the two that repeat twice); 4 Mamba-2 heads
# of 8 over 2 groups of state 16 in chunks of 8; 4 query heads over 1
# key/value head; 16 routed experts of which this share holds 4 (rank 0 of
# 4), top-4, beside a shared expert twice as wide
CFG = {"name": "nemotron3_nano_30b_a3b", "vocab_size": 96,
       "hidden_size": 32, "hybrid_override_pattern": PATTERN,
       "pipeline_first_layer": 2, "num_hidden_layers": 5,
       "mamba_num_heads": 4, "mamba_head_dim": 8, "n_groups": 2,
       "ssm_state_size": 16, "conv_kernel": 4, "chunk_size": 8,
       "time_step_min": 0.001, "time_step_max": 0.1,
       "time_step_floor": 0.0001,
       "num_attention_heads": 4, "num_key_value_heads": 1, "head_dim": 16,
       "moe_intermediate_size": 8, "moe_shared_expert_intermediate_size": 16,
       "n_routed_experts": 4, "expert_parallel": 4, "expert_rank": 0,
       "num_experts_per_tok": 4, "routed_scaling_factor": 2.5,
       "layer_norm_epsilon": 1e-5, "initializer_range": 0.1,
       "embedding_initializer_range": 0.1, "residual_initializer_range": 0.1,
       "optimizer": {"kind": "adamw", "beta1": 0.9, "beta2": 0.95,
                     "epsilon": 1e-8, "weight_decay": 0.1,
                     "lr_schedule": {"kind": "constant", "peak": 1e-3}},
       "precision": {"compute": "float32", "master": "float32",
                     "control": "bfloat16"}}


def _build(cfg):
    held = cfg["n_routed_experts"]
    return NemotronHForPretraining(
        pattern=program.pattern(cfg),
        n_routed_experts=held * cfg["expert_parallel"],
        held_experts=(cfg["expert_rank"] * held, held),
        **{k: cfg[k] for k in (
            "vocab_size", "hidden_size", "mamba_num_heads", "mamba_head_dim",
            "n_groups", "ssm_state_size", "conv_kernel", "chunk_size",
            "num_attention_heads", "num_key_value_heads", "head_dim",
            "moe_intermediate_size", "moe_shared_expert_intermediate_size",
            "num_experts_per_tok", "routed_scaling_factor",
            "layer_norm_epsilon", "initializer_range")})


def _buffers(cfg):
    """The selection biases of the expert layers: states, not weights."""
    return frozenset(p for p, r, _ in program.leaves(cfg)
                     if r.startswith("e_bias."))


_batch = ids_batch(CFG["vocab_size"])
_loss = next_token_loss(NemotronHPretrainingCriterion)


def _kinds(model):
    return "".join(b.kind for b in model.layers.blocks)


def _the_kinds_of_layers_and_the_logits(model, weights, batch):
    """A layer is one mixer alone, of its letter's kind."""
    blocks = model.layers.blocks
    assert _kinds(model) == "MEM*E"
    for b in blocks:
        assert [n for n in MIXER.values() if hasattr(b, n)] == [MIXER[b.kind]]
    attention = blocks[3].self_attn
    assert (attention.window, attention.rotary, attention.num_heads,
            attention.num_kv_heads) == (None, False, 4, 1)
    for b in (blocks[1], blocks[4]):
        assert not b.mlp.gated and b.mlp.gate_activation == "relu2"
        assert tuple(b.mlp.up_proj.shape) == (4, 32, 8)
        assert tuple(b.mlp.shared_experts.up_proj.weight.shape) == (32, 16)
        assert b.mlp.scoring == "sigmoid" \
            and b.mlp.routed_scaling_factor == 2.5
    assert tuple(blocks[0].mamba.in_proj.weight.shape) \
        == (32, 32 + (32 + 2 * 2 * 16) + 4)
    logits_follow(DECODER, model, weights, batch)


def _grads_against_the_reference(cfg):
    own = dataclasses.replace(DECODER, cfg=cfg, buffers=_buffers(cfg))
    mine = Reference(own, cfg)
    model, batch = mine.model(), _batch()
    loss, grads = eager_loss_and_grads(own, model, batch)
    assert loss == pytest.approx(grads_follow(own, grads, mine, batch),
                                 rel=2e-5)
    return model


def test_a_stage_runs_its_own_stretch_of_the_pattern():
    """The published layers 4 to 6, ``M*E``, and the reference's own
    reading of the same stretch."""
    cfg = {**CFG, "pipeline_first_layer": 4, "num_hidden_layers": 3}
    model = _grads_against_the_reference(cfg)
    assert _kinds(model) == ref.layer_kinds(cfg) == "M*E"
    with pytest.raises(ValueError, match="layer kind 'x'"):
        NemotronHForPretraining(vocab_size=8, hidden_size=8, pattern="x")


# -- the Mamba-2 mixer's parts -----------------------------------------------

def test_the_convolution_is_four_causal_taps_a_bias_and_a_silu():
    rng = np.random.default_rng(0)
    x = rng.standard_normal((2, 9, 6)).astype(np.float32)
    w = rng.standard_normal((6, 4)).astype(np.float32)
    b = rng.standard_normal((6,)).astype(np.float32)
    want = np.zeros_like(x)
    for t in range(9):
        for k in range(4):      # the last tap weighs the position itself
            if t - 3 + k >= 0:
                want[:, t] += w[:, k] * x[:, t - 3 + k]
    want = want + b
    want = want / (1 + np.exp(-want))
    np.testing.assert_allclose(causal_conv_silu(x, w, b), want, rtol=1e-5,
                               atol=1e-6)


def test_the_gate_multiplies_before_the_norm_over_each_group():
    rng = np.random.default_rng(1)
    y, z = (rng.standard_normal((2, 5, 12)).astype(np.float32)
            for _ in range(2))
    g = rng.standard_normal((12,)).astype(np.float32)
    v = (y * z / (1 + np.exp(-z))).reshape(2, 5, 3, 4)
    want = (v / np.sqrt((v ** 2).mean(-1, keepdims=True) + 1e-5)).reshape(
        2, 5, 12) * g
    np.testing.assert_allclose(gated_group_norm(y, z, g, groups=3, eps=1e-5),
                               want, rtol=1e-5, atol=1e-6)
    # not the norm of y gated afterwards
    u = y.reshape(2, 5, 3, 4)
    other = (u / np.sqrt((u ** 2).mean(-1, keepdims=True) + 1e-5)).reshape(
        2, 5, 12) * z / (1 + np.exp(-z)) * g
    assert np.abs(want - other).max() > 0.1


def test_a_mixer_draws_the_familys_own_starts():
    mixer = Mamba2Mixer(32, 4, 8, 2, 16)
    np.testing.assert_allclose(np.exp(mixer.A_log.numpy()), [1, 2, 3, 4],
                               rtol=1e-6)
    np.testing.assert_array_equal(mixer.D.numpy(), np.ones(4, np.float32))
    steps = np.log1p(np.exp(mixer.dt_bias.numpy()))
    np.testing.assert_allclose(steps, [1e-3, 10 ** (-7 / 3), 10 ** (-5 / 3),
                                       0.1], rtol=1e-4)
    assert [tuple(p.shape) for p in (mixer.conv_weight, mixer.conv_bias,
                                     mixer.norm_weight)] \
        == [(96, 4), (96,), (32,)]


# -- experts without a gate, and the sixteen shares -----------------------------

def _experts(tokens, num_experts, top_k, held, hidden=32, width=8, seed=0,
             shared=16):
    paddle.seed(seed)
    layer = nn.RoutedExperts(
        hidden, width, num_experts, top_k, held=held, shared_width=shared,
        routed_scaling_factor=2.5, scoring="sigmoid", gated=False,
        gate_activation="relu2",
        weight_attr=ParamAttr(initializer=Normal(std=0.3)))
    x = np.random.default_rng(seed).standard_normal(
        (tokens, hidden)).astype(np.float32)
    return layer, x


def test_experts_without_a_gate_are_a_loop_over_picks():
    """``sum_e w_e relu(x W_up_e)^2 W_down_e`` over a token's picks, by a
    loop in numpy, beside the shared expert of the same form."""
    layer, x = _experts(24, 8, 3, None)
    assert not hasattr(layer, "gate_up_proj")
    got = layer(Tensor(x)).numpy()
    router, up, down = (np.asarray(p.data, np.float64) for p in (
        layer.router, layer.up_proj, layer.down_proj))
    s_up, s_down = (np.asarray(p.weight.data, np.float64) for p in (
        layer.shared_experts.up_proj, layer.shared_experts.down_proj))
    want = np.maximum(x @ s_up, 0) ** 2 @ s_down
    scores = 1 / (1 + np.exp(-(x.astype(np.float64) @ router)))
    for t in range(24):
        picks = np.argsort(-scores[t])[:3]
        for e in picks:
            w = 2.5 * scores[t, e] / (scores[t, picks].sum() + 1e-20)
            want[t] += w * (np.maximum(x[t] @ up[e], 0) ** 2 @ down[e])
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


def test_late_picks_of_experts_without_a_gate_take_the_same_form():
    """More held picks than the grouped products have rows: the overflow
    path runs the same ``relu(.)^2`` experts."""
    layer, x = _experts(1024, 32, 8, (0, 2), seed=3)
    # every token picks the two held experts: 2,048 picks for 1,536 rows
    layer.e_score_correction_bias.data = jnp.asarray(
        [9.0, 9.0] + [0.0] * 30, jnp.float32)
    got = layer(Tensor(x)).numpy()
    load = layer.read_load(np.asarray(layer.expert_load.data))
    assert load["late_picks"] > 0
    lp = {"router": layer.router.data,
          "e_bias": layer.e_score_correction_bias.data,
          "e_up": layer.up_proj.data, "e_down": layer.down_proj.data,
          "s_up": layer.shared_experts.up_proj.weight.data,
          "s_down": layer.shared_experts.down_proj.weight.data}
    cfg = {"n_routed_experts": 2, "expert_parallel": 16, "expert_rank": 0,
           "num_experts_per_tok": 8, "routed_scaling_factor": 2.5}
    want = jax.jit(lambda x, lp: ref.experts(x, lp, cfg, NM))(
        jnp.asarray(x), lp)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


def test_the_sixteen_shares_add_up_to_the_whole_layer():
    """The share test: with the same weights, the routed parts of the
    sixteen shares (2 experts of 32 each, top-6 of sigmoid scores times
    2.5) and the shared expert's output counted once are the uncut
    layer's, which is the reference's uncut expert layer; and what a share
    computes, its shared expert with it, is what the reference gives that
    share. The seventeen layers are traced under one ``jax.jit``."""
    tokens, total, top_k, per = 48, 32, 6, 2
    whole, x = _experts(tokens, total, top_k, None)
    shares = [_experts(tokens, total, top_k, (per * rank, per))[0]
              for rank in range(16)]
    for rank, share in enumerate(shares):
        share.router.data = whole.router.data
        share.up_proj.data = whole.up_proj.data[per * rank:][:per]
        share.down_proj.data = whole.down_proj.data[per * rank:][:per]
        for name in ("up_proj", "down_proj"):
            getattr(share.shared_experts, name).weight.data = getattr(
                whole.shared_experts, name).weight.data
    want, *parts = traced_outputs([whole] + shares, x)
    shared = whole.shared_experts(Tensor(x)).numpy()
    cfg = {"n_routed_experts": total, "expert_parallel": 1, "expert_rank": 0,
           "num_experts_per_tok": top_k, "routed_scaling_factor": 2.5}
    lp = {"router": whole.router.data,
          "e_bias": whole.e_score_correction_bias.data,
          "e_up": whole.up_proj.data, "e_down": whole.down_proj.data,
          "s_up": whole.shared_experts.up_proj.weight.data,
          "s_down": whole.shared_experts.down_proj.weight.data}

    def plain(lp, cfg):
        return jax.jit(lambda x, lp: ref.experts(x, lp, cfg, NM))(
            jnp.asarray(x), lp)
    np.testing.assert_allclose(want, plain(lp, cfg), rtol=1e-4, atol=1e-5)
    for rank, (share, part) in enumerate(zip(shares, parts)):
        np.testing.assert_allclose(part, plain(
            {**lp, "e_up": share.up_proj.data,
             "e_down": share.down_proj.data},
            {**cfg, "n_routed_experts": per, "expert_parallel": 16,
             "expert_rank": rank}), rtol=1e-4, atol=1e-5)
    # every share computes the shared expert alike: counted once
    routed = sum(part - shared for part in parts)
    np.testing.assert_allclose(routed + shared, want, rtol=1e-4, atol=1e-5)
    assert np.abs(shared).max() > 1e-2 and np.abs(routed).max() > 1e-2


# -- a traced step ------------------------------------------------------------

def _the_steps_own_counters(engine):
    # two Mamba-2 layers a step, each traced forward, again in its
    # recomputed segment and once more for the backward's residuals; off
    # a TPU the composition runs
    paths = process_group("path")
    assert paths.child("kernel").counter("ssd_scan_calls_total").value == 0
    assert paths.child("xla").counter("ssd_scan_calls_total").value >= 2
    passes = process_group("pass")
    moved = {which: passes.child(which).counter(
        "ssd_scan_bytes_total").value for which in ("forward", "backward")}
    from paddle1_tpu.nn.functional.ssd import traffic_bytes
    one = traffic_bytes((2, 128, 4, 8), 2, 16, 2)       # bf16 operands
    assert moved["backward"] == 2 * one["backward"]
    assert moved["forward"] % one["forward"] == 0 and moved["forward"] > 0


def _the_steps_own_scopes(engine, named):
    for i, kind in enumerate("MEM*E"):
        at = f"/layers/recompute/{i}/"
        ops = ["norm/rms_norm"] + {
            "M": ["mamba/in_proj/linear", "mamba/conv/causal_conv_silu",
                  "mamba/ssd_scan", "mamba/gated_norm/gated_rms_norm",
                  "mamba/out_proj/linear"],
            "*": ["self_attn/q_proj/linear", "self_attn/k_proj/linear",
                  "self_attn/v_proj/linear", "self_attn/o_proj/linear",
                  "self_attn/global/scaled_dot_product_attention"],
            "E": ["mlp/moe/" + op for op in (
                "moe_router", "moe_dispatch", "routed_experts",
                "moe_combine", "shared_experts/up_proj/linear",
                "shared_experts/ffn_activation",
                "shared_experts/down_proj/linear")]}[kind]
        for op in ops:
            assert any(at + op in s for s in named), (i, op)
        # one mixer alone: nothing of another kind under the layer
        for other in set(MIXER.values()) - {MIXER[kind]}:
            assert not [s for s in named if at + other + "/" in s], (i, other)
    assert not [s for s in named if "rotary_embedding" in s]
    assert any("/next_token_loss" in s for s in named)
    # what a segment keeps: the attention kernels' outputs and the
    # router's and the sorts' small arrays; nothing of a Mamba-2 layer
    kept = process_group("name")
    assert {n for n in kept.labels() if kept.child(n).counter(
        "recompute_kept_bytes_total").value > 0} == {
        "flash_attention_out", "flash_attention_lse", "routed_scores",
        "routed_chosen", "routed_order", "routed_where", "routed_sizes",
        "routed_overflow", "routed_row_weights"}
    load = engine.expert_load()
    assert len(load) == 2 and all(c["steps"] == 3 for c in load.values())
    assert all(c["num_experts"] == 16 and c["held"] == 4
               and c["picks_made_a_step"] == 2 * 128 * 4
               for c in load.values())


# the shared cases' model (decoder_cases.py). The reference in blocks: the
# recurrence 8 positions a checkpointed block, an expert layer 16
# positions at a time, 8 queries of the one key/value head a block of
# scores, a row a block.
DECODER = Decoder(
    cfg=CFG, ref=ref, program=program, build=_build, criterion=_loss,
    batch=_batch, follows_also=_the_kinds_of_layers_and_the_logits,
    buffers=_buffers(CFG),
    blocks_seq=32, scans=6, blocks=(
        (ref, "SCAN_BLOCK_POSITIONS", 8),
        (ref, "ROW_BLOCK_POSITIONS", 16),
        (scores_of, "SCORE_BLOCK_BYTES", 4 * 32 * 4 * 8),
        (blocks_of, "BLOCK_TOKENS", 32)),
    optimizer=lambda parameters: make_optimizer(
        paddle, CFG["optimizer"], parameters)[0],
    step_counters=_the_steps_own_counters, step_scopes=_the_steps_own_scopes,
    shared_experts=True)
