"""SmallThinker-21BA3B-Instruct (ISSUE 43): the model against the plain
reference (``benchmarks/reference/smallthinker_21b_a3b.py``) in float32
with recomputation on and off, three updates through ``ParallelEngine``,
the bfloat16 control, which layers carry positions and which see a window,
and the names and counters a traced step carries. What the router reads,
the gate's activation, the share test and the vocabulary slice are in
``test_smallthinker_experts.py`` (ISSUE 46: no file is one worker's wall).
CPU, tiny sizes, seeded weights; the kernels in interpreter mode at
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
from benchmarks import check, spec  # noqa: E402
from benchmarks.programs import make_optimizer  # noqa: E402
from benchmarks.programs import smallthinker_21b_a3b as program  # noqa: E402
from benchmarks.reference import kanana2_30b_a3b as blocks_of  # noqa: E402
from benchmarks.reference import smallthinker_21b_a3b as ref  # noqa: E402
from decoder_cases import (  # noqa: E402,F401
    Decoder, Reference, decoder, eager_kernel_run, eager_loss_and_grads,
    eager_run, fresh_obs, grads_follow, ids_batch, logits_follow,
    next_token_loss, reference,
    test_a_step_trains_and_carries_the_scopes_and_the_counters,
    test_recomputation_changes_neither_loss_nor_gradients,
    test_the_model_follows_the_reference,
    test_the_reference_in_blocks_is_the_reference)
from paddle1_tpu.core.flags import flags_guard  # noqa: E402
from paddle1_tpu.obs.registry import process_group  # noqa: E402
from paddle1_tpu.text.models import (  # noqa: E402
    SmallThinkerForPretraining, SmallThinkerPretrainingCriterion)

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


def _build(cfg):
    held = cfg["moe_num_primary_experts"]
    return SmallThinkerForPretraining(
        sliding_window_layout=program.stage(cfg, "sliding_window_layout"),
        rope_layout=program.stage(cfg, "rope_layout"),
        moe_num_primary_experts=held * cfg["expert_parallel"],
        held_experts=(cfg["expert_rank"] * held, held),
        **{k: cfg[k] for k in (
            "vocab_size", "hidden_size", "sliding_window_size",
            "num_attention_heads", "num_key_value_heads", "head_dim",
            "moe_ffn_hidden_size", "moe_num_active_primary_experts",
            "rope_theta", "rms_norm_eps", "initializer_range")})


_batch = ids_batch(CFG["vocab_size"])
_loss = next_token_loss(SmallThinkerPretrainingCriterion)


def _grads_against_the_reference(cfg, weights=None):
    """The model's loss and gradient of every leaf beside the
    reference's, float32, under a configuration of the case's own."""
    mine = Reference(DECODER, cfg, weights)
    model, batch = mine.model(), _batch()
    loss, grads = eager_loss_and_grads(DECODER, model, batch)
    assert loss == pytest.approx(grads_follow(DECODER, grads, mine, batch),
                                 rel=2e-5)
    return model, mine


# -- the model against the reference ----------------------------------------

def _the_kinds_of_layers_and_the_logits(model, weights, batch):
    blocks = model.layers.blocks
    assert [b.self_attn.window for b in blocks] == [None, 8, 8, 8]
    assert [b.self_attn.rotary for b in blocks] == [False, True, True, True]
    assert all(type(b.mlp).__name__ == "RoutedExperts"
               and b.mlp.gate_activation == "relu"
               and b.mlp.scoring == "softmax" for b in blocks)
    logits_follow(DECODER, model, weights, batch)


# window layers and layers with positions where the two lists say, and
# nowhere else: the lists apart from each other, and a window shorter and
# longer than the row
@pytest.mark.parametrize("windows,positions,size", [
    ([1, 0, 1, 0], [0, 0, 1, 1], 8), ([1, 1, 1, 1], [0, 0, 0, 0], 3),
    ([0, 0, 0, 0], [1, 1, 1, 1], 8), ([0, 1, 1, 1], [0, 1, 1, 1], 64)])
def test_a_layers_kind_is_what_the_two_lists_say(reference, windows,
                                                 positions, size):
    cfg = {**CFG, "sliding_window_layout": windows, "rope_layout": positions,
           "sliding_window_size": size}
    # the lists and the window's size shape no weight: the file's draw
    model, mine = _grads_against_the_reference(cfg, reference.weights)
    assert [b.self_attn.window for b in model.layers.blocks] \
        == [size if w else None for w in windows]
    assert [b.self_attn.rotary for b in model.layers.blocks] \
        == [bool(p) for p in positions]
    # the reference under the published lists is another function
    other = reference.loss_and_grads(_batch(), mine.weights)[0]
    under = mine.loss_and_grads(_batch())[0]
    if (windows, positions, size) != ([0, 1, 1, 1], [0, 1, 1, 1], 64):
        assert abs(float(other) - float(under)) > 1e-4 * float(under)


def test_a_stage_runs_its_own_stretch_of_the_lists():
    cfg = {**CFG, "pipeline_first_layer": 2, "num_hidden_layers": 3}
    model, _ = _grads_against_the_reference(cfg)
    assert [(b.self_attn.window, b.self_attn.rotary)
            for b in model.layers.blocks] == [(8, True), (8, True),
                                              (None, False)]
    assert ref.layer_kinds(cfg) == [(8, True), (8, True), (None, False)]


def test_the_kernels_and_the_composition_agree_on_the_model(
        reference, eager_kernel_run):
    """At a tile-aligned row the kernels (interpreter mode, the window's
    rule on three layers, the causal rule on one) and the dense
    composition give one loss and one gradient. The kernels' side is the
    file's one eager run of that row."""
    with flags_guard(flash_attention="never"):
        loss, grads = eager_loss_and_grads(DECODER, reference.model(),
                                           eager_kernel_run.batch)
    assert eager_kernel_run.loss == pytest.approx(loss, rel=1e-5)
    for k, g in grads.items():
        a = eager_kernel_run.grads[k]
        assert np.linalg.norm(a - g) <= 2e-4 * max(np.linalg.norm(g), 1e-4), k


# -- three updates, and the control -------------------------------------------

def _records(reference, batches, lrs):
    """(what three steps of the engine show, what the reference records,
    the leaf map): ``check.compare``'s two sides."""
    model = reference.model()
    opt, (slot, factor) = make_optimizer(paddle, CFG["optimizer"],
                                         model.parameters())
    engine = reference.engine(model=model, optimizer=opt)
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
    sound = check.reference_record(CFG, jax.device_get(reference.weights),
                                   batches, lrs)
    return shown, sound, program.leaves(CFG)


def test_three_updates_follow_the_reference_and_bfloat16_does_not(reference):
    """Three AdamW steps through ``ParallelEngine(recompute=True)`` in
    float32 against the reference's own update rule, inside the
    rehearsal's limits; the reference in bfloat16 in the program's place
    is outside them, by the gradient's direction among others."""
    batches = [_batch(batch=8, seq=16, seed=s) for s in (1, 2, 3)]
    lrs = [1e-3] * 3
    limits = spec.limits("smallthinker_21b_a3b", rehearsal=True)
    shown, sound, leaves = _records(reference, batches, lrs)
    ok, rows = check.compare(shown, sound, leaves, limits)
    assert ok, rows
    assert shown["losses"][2] < shown["losses"][0]
    lower = check.reference_record(CFG, {
        k: np.asarray(v) for k, v in reference.weights.items()}, batches,
        lrs, "bfloat16")
    ok, rows = check.compare(check.in_programs_place(lower, leaves), sound,
                             leaves, limits)
    failed = {what for what, _, _, good, _ in rows if not good}
    assert not ok and "grad direction median-matrix gap" in failed, rows


# -- a traced step ------------------------------------------------------------

def _the_steps_own_counters(engine):
    # the sums of a token's picks, on rows half a 32-bit lane row wide
    # (128 bf16): 3 a layer, the forward's, the recomputed segment's
    # (jax traces it again for the backward pass) and the transpose
    # of the dispatch's gather
    arms = process_group("arm")
    sums = {arm: arms.child(arm).counter("moe_sum_picks_arm_total").value
            for arm in ("kernel", "gather")}
    assert sums == {"kernel": 0, "gather": 3 * len(ref.layer_kinds(CFG))}


def _the_steps_own_scopes(engine, named):
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
    assert any("/next_token_loss" in s for s in named)
    # the two kernels under the layer's kind
    assert all("/self_attn/window/" in s or "/self_attn/global/" in s
               for s in named if "p1t_flash_attention" in s)
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
    # the expert layers count their load: four of them, top-6 of 16
    load = engine.expert_load()
    assert len(load) == 4 and all(c["steps"] == 3 for c in load.values())
    assert all(c["num_experts"] == 16 and c["held"] == 8
               and c["picks_made_a_step"] == 2 * 128 * 6
               for c in load.values())


# the shared cases' model (decoder_cases.py). The reference in blocks: a
# row's positions through the experts 8 at a time, the one key/value head
# and 8 of its 7 x 32 queries a block of scores, two experts a group, a
# row a block: queries and experts, four layers.
DECODER = Decoder(
    cfg=CFG, ref=ref, program=program, build=_build, criterion=_loss,
    batch=_batch, follows_also=_the_kinds_of_layers_and_the_logits,
    blocks_seq=32, scans=8, blocks=(
        (ref, "ROW_BLOCK_POSITIONS", 8),
        (ref, "SCORE_BLOCK_BYTES", 4 * 32 * 7 * 8),
        (ref, "EXPERTS_BLOCK_BYTES", 4 * 8 * 128 * 2),
        (blocks_of, "BLOCK_TOKENS", 32)),
    optimizer=lambda parameters: make_optimizer(
        paddle, CFG["optimizer"], parameters)[0],
    step_counters=_the_steps_own_counters, step_scopes=_the_steps_own_scopes,
    # the weights drawn primitive by primitive, as the parent's: the
    # reference in blocks holds its 1e-5 by a hair (router.1)
    draw=lambda cfg: ref.init_params(cfg, jax.random.key(4)),
    kernel_sides_on_the_tape=True)
