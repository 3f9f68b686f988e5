"""LFM2-24B-A2B (ISSUE 38): the gated short convolution (forward, its
written-out backward, the counters a traced call leaves), ``nn.ShortConv``,
the sigmoid router's constant, the model against the plain reference
(``benchmarks/reference/lfm2_24b_a2b.py``) with recomputation on and off,
the tied head's one leaf, the blockwise kernels at head width 64 with 32 / 8
heads, the share test, and the names and counters a traced step carries.
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
from benchmarks.programs import lfm2_24b_a2b as program  # noqa: E402
from benchmarks.reference import kanana2_30b_a3b as blocks_of  # noqa: E402
from benchmarks.reference import lfm2_24b_a2b as ref  # noqa: E402
from decoder_cases import (  # noqa: E402,F401
    NM, Decoder, decoder, eager_kernel_run, eager_run, fresh_obs, ids_batch,
    logits_follow, next_token_loss, reference,
    test_a_step_trains_and_carries_the_scopes_and_the_counters,
    test_recomputation_changes_neither_loss_nor_gradients,
    test_the_eight_shares_add_up_to_the_whole_layer,
    test_the_model_follows_the_reference,
    test_the_reference_in_blocks_is_the_reference)
from paddle1_tpu import nn  # noqa: E402
from paddle1_tpu.core.flags import flags_guard  # noqa: E402
from paddle1_tpu.core.tensor import Tensor  # noqa: E402
from paddle1_tpu.framework.param_attr import ParamAttr  # noqa: E402
from paddle1_tpu.nn import functional as F  # noqa: E402
from paddle1_tpu.nn import layer_moe  # noqa: E402
from paddle1_tpu.nn.functional import short_conv  # noqa: E402
from paddle1_tpu.nn.functional.attention import attention_ref  # noqa: E402
from paddle1_tpu.nn.initializer import Normal  # noqa: E402
from paddle1_tpu.obs.registry import process_group  # noqa: E402
from paddle1_tpu.ops.pallas import flash_attention as fa  # noqa: E402
from paddle1_tpu.text.models import (Lfm2ForPretraining,  # noqa: E402
                                     Lfm2PretrainingCriterion)

# the reference's configuration keys at hidden 128: the stage runs the
# published layers 1..4 (conv, attention, conv, conv), the first of them
# dense; 16 routed experts of which this share holds 8 (rank 0 of 2),
# top-4; 4 query heads over 2
CFG = {"vocab_size": 96, "hidden_size": 128, "num_hidden_layers": 4,
       "pipeline_first_layer": 1, "num_dense_layers": 1,
       "layer_types": ["conv", "conv", "full_attention", "conv", "conv",
                       "conv", "full_attention", "conv"],
       "conv_L_cache": 3, "num_attention_heads": 4,
       "num_key_value_heads": 2, "head_dim": 32, "intermediate_size": 192,
       "moe_intermediate_size": 16, "num_experts": 8, "expert_parallel": 2,
       "expert_rank": 0, "num_experts_per_tok": 4,
       "routed_scaling_factor": 1, "norm_eps": 1e-5,
       "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
       "initializer_range": 0.2}


# -- the op -----------------------------------------------------------------

def _three_taps_by_hand(b, c, x, w):
    """``out_t = c_t * sum_k w[:, k] * (b * x)_{t - 2 + k}``, position by
    position, zeros before a row's start."""
    s = b * x
    out = np.zeros_like(s)
    for t in range(s.shape[1]):
        for k in range(w.shape[1]):
            if t - (w.shape[1] - 1) + k >= 0:
                out[:, t] += w[:, k] * s[:, t - (w.shape[1] - 1) + k]
    return c * out


def _operands(batch=2, seq=37, channels=24, taps=3, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.standard_normal(s).astype(np.float32)
            for s in [(batch, seq, channels)] * 3 + [(channels, taps)]]


@pytest.mark.parametrize("taps", [1, 3, 4])
def test_the_op_is_the_three_tap_sum_between_two_gates(taps):
    # 37 positions: a multiple of no block
    b, c, x, w = _operands(taps=taps)
    got = F.gated_short_conv(Tensor(np.concatenate([b, c, x], -1)),
                             Tensor(w)).numpy()
    np.testing.assert_allclose(got, _three_taps_by_hand(b, c, x, w),
                               rtol=1e-5, atol=1e-6)
    if taps == 3:
        # the first two positions against the zero history
        s = b * x
        np.testing.assert_allclose(got[:, 0], c[:, 0] * w[:, 2] * s[:, 0],
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(
            got[:, 1], c[:, 1] * (w[:, 1] * s[:, 0] + w[:, 2] * s[:, 1]),
            rtol=1e-5, atol=1e-6)
        # and the reference's own three-tap sum, a row at a time
        for row in range(b.shape[0]):
            np.testing.assert_allclose(
                got[row], c[row] * np.asarray(ref.short_conv(
                    jnp.asarray(b[row] * x[row]), jnp.asarray(w))),
                rtol=1e-5, atol=1e-6)


def test_the_ops_backward_is_autodiffs_of_the_plain_sum():
    """The written-out backward (taps included) against jax's own of the
    reference's three-tap sum, a sequence that is no multiple of a block."""
    b, c, x, w = (jnp.asarray(a) for a in _operands(seq=37, seed=1))
    dout = jnp.asarray(np.random.default_rng(2).standard_normal(
        b.shape).astype(np.float32))

    def plain(b, c, x, w):
        return jnp.stack([c[r] * ref.short_conv(b[r] * x[r], w)
                          for r in range(b.shape[0])])
    *parts, dw = jax.vjp(plain, b, c, x, w)[1](dout)
    want = (jnp.concatenate(parts, -1), dw)
    bcx = jnp.concatenate([b, c, x], -1)
    got = jax.vjp(short_conv._gated_short_conv, bcx, w)[1](dout)
    for name, g, a in zip(("dbcx", "dtaps"), got, want):
        np.testing.assert_allclose(g, a, rtol=2e-5, atol=2e-5, err_msg=name)
    # through the tape too, eagerly
    ts = [Tensor(np.asarray(a), stop_gradient=False) for a in (bcx, w)]
    (F.gated_short_conv(*ts) * Tensor(np.asarray(dout))).sum().backward()
    for t, a in zip(ts, want):
        np.testing.assert_allclose(t.grad.numpy(), a, rtol=2e-5, atol=2e-5)


def test_the_op_in_bfloat16_accumulates_in_float32():
    b, c, x, w = (jnp.asarray(a, jnp.bfloat16) for a in _operands(seed=3))
    bcx = jnp.concatenate([b, c, x], -1)
    out = short_conv._gated_short_conv(bcx, w)
    assert out.dtype == jnp.bfloat16
    want = _three_taps_by_hand(*(np.asarray(a, np.float32)
                                 for a in (b, c, x, w)))
    # one rounding of the result, none of the sum: 2^-8 of the value
    np.testing.assert_allclose(np.asarray(out, np.float32), want,
                               rtol=2 ** -7, atol=1e-3)
    grads = jax.grad(lambda *a: jnp.sum(short_conv._gated_short_conv(
        *a).astype(jnp.float32)), (0, 1))(bcx, w)
    assert [g.dtype for g in grads] == [jnp.bfloat16] * 2


def test_a_traced_call_counts_its_bytes_by_the_closed_form(fresh_obs):
    """``short_conv_bytes_total{pass}`` is a whole multiple (jax may trace
    a call's forward more than once) of :func:`traffic_bytes` of the
    call's shapes, which is the benchmark's own closed form for one
    call."""
    from benchmarks.model_flops import lfm2_24b_a2b as mf
    shape, taps = (2, 256, 3 * 64), 3
    args = [jnp.zeros(shape, jnp.bfloat16), jnp.zeros((64, taps),
                                                      jnp.bfloat16)]
    jax.jit(jax.grad(lambda *a: jnp.sum(short_conv._gated_short_conv(
        *a).astype(jnp.float32)), (0, 1))).lower(*args)
    passes = process_group("pass")
    want = short_conv.traffic_bytes(shape, 2, taps)
    assert want == {"forward": 4 * 2 * 256 * 64 * 2 + 64 * 3 * 2,
                    "backward": 7 * 2 * 256 * 64 * 2 + 2 * 64 * 3 * 2}
    for which in ("forward", "backward"):
        counted = passes.child(which).counter("short_conv_bytes_total").value
        assert counted >= want[which] and counted % want[which] == 0
    one_conv_layer = {"layer_types": ["conv"], "num_hidden_layers": 1,
                      "hidden_size": 64, "conv_L_cache": taps}
    assert mf.short_conv_bytes(one_conv_layer, {"batch": 2, "seq": 256}) \
        == want


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("taps,seq", [(3, 64), (3, 16), (4, 96), (1, 32)])
def test_the_kernels_are_the_shifted_sums(taps, seq, dtype):
    """The two Pallas kernels (interpreter mode) against the XLA form:
    several blocks of positions, so that the taps reach across a block's
    edge both ways, and two rows, so that nothing leaks from one into the
    next."""
    from paddle1_tpu.ops.pallas import short_conv as kernels
    b, c, x, w = _operands(batch=2, seq=seq, channels=128, taps=taps, seed=9)
    bcx = jnp.asarray(np.concatenate([b, c, x], -1), dtype)
    w = jnp.asarray(w, dtype)
    g = jnp.asarray(np.random.default_rng(10).standard_normal(b.shape), dtype)
    assert kernels.supported(bcx.shape, taps)
    assert not kernels.supported((2, seq + 8, 3 * 128), taps)
    assert not kernels.supported((2, seq, 3 * 96), taps)
    # one block of 16 positions a grid step, so that 64 positions are four
    saved = kernels._rows
    kernels._rows = lambda s, ch, budget: 16
    try:
        out = kernels.forward(bcx, w)
        d, dw = kernels.backward(bcx, w, g)
    finally:
        kernels._rows = saved
    want_out, pull = jax.vjp(short_conv._gated_short_conv, bcx, w)
    want_d, want_dw = pull(g)
    assert out.dtype == d.dtype == bcx.dtype and dw.dtype == jnp.float32
    tol = 1e-5 if dtype == "float32" else 2 ** -7
    for name, got, want in (("out", out, want_out), ("d", d, want_d),
                            ("dw", dw, want_dw)):
        got, want = (np.asarray(a, np.float32) for a in (got, want))
        np.testing.assert_allclose(got, want, rtol=tol,
                                   atol=tol * np.abs(want).max(),
                                   err_msg=name)


def test_the_layer_is_in_proj_the_op_and_out_proj():
    paddle.seed(5)
    layer = nn.ShortConv(32, taps=3, weight_attr=ParamAttr(
        initializer=Normal(std=0.3)))
    assert {k: tuple(v.shape) for k, v in layer.state_dict().items()} == {
        "in_proj.weight": (32, 96), "conv_weight": (32, 3),
        "out_proj.weight": (32, 32)}
    u = np.random.default_rng(5).standard_normal((2, 11, 32)).astype(
        np.float32)
    lp = {"w_in": layer.in_proj.weight.data, "taps": layer.conv_weight.data,
          "w_out": layer.out_proj.weight.data}
    got = layer(Tensor(u)).numpy()
    for row in range(2):
        np.testing.assert_allclose(
            got[row], ref.conv_operator(jnp.asarray(u[row]), lp, CFG, NM),
            rtol=1e-4, atol=1e-5)


# -- the router's constant --------------------------------------------------

def test_the_sigmoid_rule_takes_its_constant_from_the_constructor():
    """``w = s / (sum s + norm_eps)``: 1e-20 as every caller before this
    model had it, 1e-6 for the LFM2 family; the reference's rule is the
    layer's."""
    rng = np.random.default_rng(6)
    x = jnp.asarray(rng.standard_normal((12, 16)), jnp.float32)
    gate = jnp.asarray(rng.standard_normal((16, 8)), jnp.float32)
    bias = jnp.zeros((8,), jnp.float32)
    old, chosen = layer_moe.route(x, gate, bias, 4, 1.0)
    new, chosen_new = layer_moe.route(x, gate, bias, 4, 1.0, norm_eps=1e-6)
    assert np.array_equal(chosen, chosen_new)
    s = np.take_along_axis(np.asarray(jax.nn.sigmoid(x @ gate)),
                           np.asarray(chosen), -1)
    np.testing.assert_allclose(old, s / s.sum(-1, keepdims=True), rtol=1e-6)
    np.testing.assert_allclose(new, s / (s.sum(-1, keepdims=True) + 1e-6),
                               rtol=1e-6)
    want, want_chosen = ref.route(x, gate, bias, {
        "num_experts_per_tok": 4, "routed_scaling_factor": 1})
    assert np.array_equal(chosen, want_chosen)
    np.testing.assert_allclose(new, want, rtol=1e-6)
    assert nn.RoutedExperts(16, 8, 8, 4).norm_eps == 1e-20
    assert nn.RoutedExperts(16, 8, 8, 4, norm_eps=1e-6).norm_eps == 1e-6


# -- the model against the reference ----------------------------------------

def _build(cfg):
    held = cfg["num_experts"]
    return Lfm2ForPretraining(
        layer_types=ref.layer_kinds(cfg),
        num_experts=held * cfg["expert_parallel"],
        held_experts=(cfg["expert_rank"] * held, held),
        rope_theta=cfg["rope_parameters"]["rope_theta"],
        **{k: cfg[k] for k in (
            "vocab_size", "hidden_size", "num_dense_layers",
            "num_attention_heads", "num_key_value_heads", "head_dim",
            "conv_L_cache", "intermediate_size", "moe_intermediate_size",
            "num_experts_per_tok", "routed_scaling_factor", "norm_eps",
            "initializer_range")})


# one row length for every case of the model on the eager tape, the
# kernels' tile: an op's programs are compiled once a shape (ISSUE 46)
_batch = ids_batch(CFG["vocab_size"], row=128)
_loss = next_token_loss(Lfm2PretrainingCriterion)


def _the_kinds_of_layers_and_the_logits(model, weights, batch):
    kinds = [b.kind for b in model.layers.blocks]
    assert kinds == ["conv", "full_attention", "conv", "conv"]
    assert [type(b.mlp).__name__ for b in model.layers.blocks] == [
        "GatedFeedForward"] + ["RoutedExperts"] * 3
    logits_follow(DECODER, model, weights, batch)


def test_the_tied_head_is_one_leaf_with_both_gradients(reference):
    """No second [vocab, hidden] parameter; the leaf's gradient is the
    lookup's plus the head's, each of which the reference gives alone."""
    model, weights = reference.model(), reference.weights
    names = [n for n, p in model.named_parameters()
             if tuple(p.shape) == (CFG["vocab_size"], CFG["hidden_size"])]
    assert names == ["embed_tokens.weight"]
    assert [n for n, _ in model.lm_head.named_parameters()] == ["norm.weight"]
    batch = _batch(seed=1)
    ids = jnp.asarray(batch["ids"])
    _loss(model, batch).backward()

    def split(lookup, head):
        """The reference's loss with the two uses of ``E`` apart."""
        h = ref.hidden({**weights, "embed": lookup}, ids, CFG, NM)
        logits = ref.head_logits(h, {**weights, "embed": head}, CFG, NM)
        picked = jnp.take_along_axis(logits[:, :-1], ids[:, 1:, None],
                                     -1)[..., 0]
        return jnp.mean(jax.nn.logsumexp(logits[:, :-1], -1) - picked)
    g_lookup, g_head = jax.jit(jax.grad(split, (0, 1)))(weights["embed"],
                                                        weights["embed"])
    assert float(jnp.linalg.norm(g_lookup)) > 0
    assert float(jnp.linalg.norm(g_head)) > 0
    both = np.asarray(g_lookup + g_head)
    got = model.embed_tokens.weight.grad.numpy()
    assert np.linalg.norm(got - both) <= 2e-4 * np.linalg.norm(both)
    assert np.linalg.norm(got - np.asarray(g_head)) \
        > 1e-2 * np.linalg.norm(both)


# -- the kernels at this model's shape ---------------------------------------

def test_the_kernels_arm_is_taken_at_head_width_64_with_32_over_8_heads(
        fresh_obs):
    """[1, 256, 32 / 8, 64] causal under ``flash_attention=always``
    (interpreter mode): the kernels' arm is counted, and out and the three
    gradients agree with ``attention_ref``."""
    keys = jax.random.split(jax.random.key(7), 4)
    q, k, v, dout = [jax.random.normal(kk, (1, 256, h, 64), jnp.float32)
                     for kk, h in zip(keys, (32, 8, 8, 32))]
    assert fa.supported(q.shape, k.shape, causal=True, v_shape=v.shape)

    def sdpa(q, k, v):
        return F.scaled_dot_product_attention(
            Tensor(q), Tensor(k), Tensor(v), is_causal=True).data
    with flags_guard(flash_attention="always"):
        out, pull = jax.vjp(sdpa, q, k, v)
        got = (out,) + pull(dout)
    arms = process_group("arm")
    assert arms.child("flash").counter("attention_arm_total").value >= 1
    assert arms.child("dense").counter("attention_arm_total").value == 0
    out, pull = jax.vjp(lambda q, k, v: attention_ref(q, k, v,
                                                      is_causal=True), q, k, v)
    for name, g, w in zip(("out", "dq", "dk", "dv"), got,
                          (out,) + pull(dout)):
        assert g.shape == w.shape, name
        np.testing.assert_allclose(g, w, rtol=0,
                                   atol=2e-5 * float(jnp.abs(w).max()),
                                   err_msg=name)


# -- the share --------------------------------------------------------------

def _experts(tokens, num_experts, top_k, held, hidden=32, width=8, seed=0):
    paddle.seed(seed)
    layer = nn.RoutedExperts(
        hidden, width, num_experts, top_k, held=held, scoring="sigmoid",
        norm_eps=1e-6, weight_attr=ParamAttr(initializer=Normal(std=0.3)))
    x = np.random.default_rng(seed).standard_normal(
        (tokens, hidden)).astype(np.float32)
    return layer, x


def test_the_eight_vocabulary_slices_concatenate_to_the_whole_head():
    """Eight models, each with an eighth of the embedding's rows, give
    logits that side by side are the whole tied head's over the same
    stream (the reference's ``head_logits`` with all the rows)."""
    from paddle1_tpu.text.models import Lfm2Head
    rng = np.random.default_rng(8)
    hidden, vocab = 32, 64
    h = rng.standard_normal((2, 5, hidden)).astype(np.float32)
    embed = (0.3 * rng.standard_normal((vocab, hidden))).astype(np.float32)
    g = (1 + 0.1 * rng.standard_normal(hidden)).astype(np.float32)
    head = Lfm2Head(hidden, 1e-5)
    head.norm.weight.data = jnp.asarray(g)
    whole = np.asarray(ref.head_logits(
        jnp.asarray(h), {"norm_f": jnp.asarray(g), "embed": jnp.asarray(embed)},
        {"norm_eps": 1e-5}, NM))
    slices = [head(Tensor(h), Tensor(embed[r * 8:(r + 1) * 8])).numpy()
              for r in range(8)]
    np.testing.assert_allclose(np.concatenate(slices, -1), whole, rtol=1e-5,
                               atol=1e-6)
    np.testing.assert_allclose(head(Tensor(h), Tensor(embed)).numpy(), whole,
                               rtol=1e-5, atol=1e-6)


# -- a traced step ------------------------------------------------------------

def _the_steps_own_scopes_and_counters(engine, named):
    # three convolution layers: each traced forward and backward counted
    # by the closed form of [2, 128, 128] bfloat16 with 3 taps
    passes = process_group("pass")
    want = short_conv.traffic_bytes((2, 128, 3 * 128), 2, 3)
    for which in ("forward", "backward"):
        counted = passes.child(which).counter("short_conv_bytes_total").value
        assert counted >= 3 * want[which] \
            and counted % (3 * want[which]) == 0
    for i, kind in enumerate(ref.layer_kinds(CFG)):
        at = f"/layers/recompute/{i}/"
        if kind == "conv":
            ops = ("conv/in_proj/linear", "conv/gated_short_conv",
                   "conv/out_proj/linear")
            assert not [s for s in named if at + "self_attn" in s]
        else:
            ops = tuple("self_attn/" + op for op in (
                "q_proj/linear", "k_proj/linear", "v_proj/linear",
                "q_layernorm/rms_norm", "k_layernorm/rms_norm",
                "rotary_embedding", "scaled_dot_product_attention",
                "out_proj/linear"))
            assert not [s for s in named if at + "conv/" in s]
        for op in ops + ("operator_norm/rms_norm", "ffn_norm/rms_norm"):
            assert any(at + op in s for s in named), (i, op)
        ffn = (("mlp/linear",) if i < CFG["num_dense_layers"] else tuple(
            "mlp/moe/" + op for op in ("moe_router", "moe_dispatch",
                                       "routed_experts", "moe_combine")))
        for op in ffn:
            assert any(at + op in s or at + op.replace(
                "mlp/linear", "mlp/gate_proj/linear") in s
                for s in named), (i, op)
    assert any("/lm_head/norm/rms_norm" in s for s in named)
    assert any("/next_token_loss" in s for s in named)
    # the op runs again inside a recomputed segment: it keeps nothing
    again = [s for s in named if "/rematted_computation/" in s
             and "/gated_short_conv" in s]
    assert again
    # the expert layers count their load: three of them
    load = engine.expert_load()
    assert len(load) == 3 and all(c["steps"] == 3 for c in load.values())
    assert all(c["num_experts"] == 16 and c["held"] == 8
               for c in load.values())


# the shared cases' model (decoder_cases.py). The reference in blocks: a
# row's positions through a feed-forward 8 at a time, one key/value head
# and 8 of its queries a block of scores, two experts a group, a row a
# block. The share test: 8 experts of 64 each, top-4, the sigmoid rule.
DECODER = Decoder(
    cfg=CFG, ref=ref, program=program, build=_build, criterion=_loss,
    batch=_batch, dense_seq=128, kernel_sides_on_the_tape=True,
    follows_also=_the_kinds_of_layers_and_the_logits,
    buffers=frozenset(f"layers.blocks.{i}.mlp.e_score_correction_bias"
                      for i in (1, 2, 3)),
    blocks_seq=32, scans=5, blocks=(
        (ref, "ROW_BLOCK_POSITIONS", 8),
        (ref, "SCORE_BLOCK_BYTES", 4 * 32 * 2 * 8),
        (ref, "EXPERTS_BLOCK_BYTES", 4 * 8 * 128 * 2),
        (blocks_of, "BLOCK_TOKENS", 32)),
    step_scopes=_the_steps_own_scopes_and_counters,
    shares={"experts": _experts, "total": 64, "top_k": 4,
            "cfg": {"routed_scaling_factor": 1},
            "weights": {"e_bias": jnp.zeros((64,), jnp.float32)}})
