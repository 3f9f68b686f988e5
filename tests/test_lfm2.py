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
from benchmarks.reference import lfm2_24b_a2b as ref  # noqa: E402
from benchmarks.reference.numerics import Numerics  # noqa: E402
from paddle1_tpu import nn, obs  # noqa: E402
from paddle1_tpu.core.flags import flags_guard  # noqa: E402
from paddle1_tpu.core.tensor import Tensor  # noqa: E402
from paddle1_tpu.distributed import ParallelEngine, build_mesh  # noqa: E402
from paddle1_tpu.framework.param_attr import ParamAttr  # noqa: E402
from paddle1_tpu.nn import functional as F  # noqa: E402
from paddle1_tpu.nn import layer_moe  # noqa: E402
from paddle1_tpu.nn.functional import short_conv  # noqa: E402
from paddle1_tpu.nn.functional.attention import attention_ref  # noqa: E402
from paddle1_tpu.nn.initializer import Normal  # noqa: E402
from paddle1_tpu.obs import costmodel  # noqa: E402
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
NM = Numerics()


@pytest.fixture
def _fresh_obs():
    obs.reset_process_registry()
    obs.hbm.reset()
    yield
    obs.reset_process_registry()
    obs.hbm.reset()


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


def test_a_traced_call_counts_its_bytes_by_the_closed_form(_fresh_obs):
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

def _model(cfg=CFG):
    """(the Layer, the reference's weights it was loaded with)."""
    from benchmarks.programs import load_weights
    from benchmarks.programs import lfm2_24b_a2b as program
    weights = ref.init_params(cfg, jax.random.key(4))
    held = cfg["num_experts"]
    model = Lfm2ForPretraining(
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
    load_weights(model, {p: weights[r] for p, r, _ in program.leaves(cfg)})
    return model, weights


def _batch(batch=2, seq=16, seed=0):
    rng = np.random.default_rng(seed)
    return {"ids": rng.integers(0, CFG["vocab_size"],
                                (batch, seq)).astype(np.int32)}


def _loss(model, batch):
    ids = Tensor(batch["ids"])
    labels = model.next_token_labels(ids)
    return Lfm2PretrainingCriterion()(model(ids, labels), labels)


def test_the_model_follows_the_reference():
    """Logits, loss and every gradient leaf in float32. Tolerances: the
    two sides sum the same float32 products in another order (2e-5 of a
    loss, 2e-4 of a leaf's gradient norm, 1e-4 of the largest logit)."""
    from benchmarks.programs import lfm2_24b_a2b as program
    model, weights = _model()
    kinds = [b.kind for b in model.layers.blocks]
    assert kinds == ["conv", "full_attention", "conv", "conv"]
    assert [type(b.mlp).__name__ for b in model.layers.blocks] == [
        "GatedFeedForward"] + ["RoutedExperts"] * 3
    batch = _batch()
    ids = jnp.asarray(batch["ids"])
    logits = model(Tensor(batch["ids"])).numpy()
    want_logits = np.asarray(ref.head_logits(
        ref.hidden(weights, ids, CFG, NM), weights, CFG, NM))
    assert logits.shape == (2, 16, CFG["vocab_size"])
    np.testing.assert_allclose(logits, want_logits, rtol=0,
                               atol=1e-4 * np.abs(want_logits).max())
    loss = _loss(model, batch)
    want, grads = jax.value_and_grad(
        lambda w: ref.loss(w, {"ids": ids}, CFG, NM)[0])(weights)
    assert float(loss) == pytest.approx(float(want), rel=2e-5)
    loss.backward()
    named = dict(model.named_parameters())
    leaves = program.leaves(CFG)
    # every parameter is a leaf of the map, and the expert bias a buffer
    assert {p for p, _, _ in leaves} - set(named) == {
        f"layers.blocks.{i}.mlp.e_score_correction_bias" for i in (1, 2, 3)}
    for p, r, _ in leaves:
        if p not in named:
            continue
        g, w = named[p].grad.numpy(), np.asarray(grads[r])
        assert np.linalg.norm(g - w) <= 2e-4 * max(np.linalg.norm(w), 1e-4), p


def test_the_tied_head_is_one_leaf_with_both_gradients():
    """No second [vocab, hidden] parameter; the leaf's gradient is the
    lookup's plus the head's, each of which the reference gives alone."""
    model, weights = _model()
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
    g_lookup, g_head = jax.grad(split, (0, 1))(weights["embed"],
                                               weights["embed"])
    assert float(jnp.linalg.norm(g_lookup)) > 0
    assert float(jnp.linalg.norm(g_head)) > 0
    both = np.asarray(g_lookup + g_head)
    got = model.embed_tokens.weight.grad.numpy()
    assert np.linalg.norm(got - both) <= 2e-4 * np.linalg.norm(both)
    assert np.linalg.norm(got - np.asarray(g_head)) \
        > 1e-2 * np.linalg.norm(both)


def test_the_reference_in_blocks_is_the_reference(monkeypatch):
    """At the cell's size the reference takes a row's positions through a
    feed-forward in blocks and a block of one key/value head's queries
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
    monkeypatch.setattr(ref, "SCORE_BLOCK_BYTES", 4 * 32 * 2 * 8)
    monkeypatch.setattr(ref, "EXPERTS_BLOCK_BYTES", 4 * 8 * 128 * 2)
    monkeypatch.setattr(blocks_of, "BLOCK_TOKENS", 32)
    text = str(jax.make_jaxpr(lambda w: ref.loss(w, batch, CFG, NM)[0])(
        weights))
    assert text.count("scan") >= 5
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


# -- the kernels at this model's shape ---------------------------------------

def test_the_kernels_arm_is_taken_at_head_width_64_with_32_over_8_heads(
        _fresh_obs):
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


def test_the_eight_shares_add_up_to_the_whole_layer():
    """The share test: with the same weights, the routed outputs of the
    eight shares (8 experts of 64 each, top-4) are the uncut layer's, which
    is the reference's uncut expert layer."""
    tokens, width, total = 48, 8, 64
    whole, x = _experts(tokens, total, 4, None)
    want = whole(Tensor(x)).numpy()
    cfg = {"num_experts": total, "expert_parallel": 1, "expert_rank": 0,
           "num_experts_per_tok": 4, "moe_intermediate_size": width,
           "routed_scaling_factor": 1}
    lp = {"router": whole.router.data, "e_gate_up": whole.gate_up_proj.data,
          "e_down": whole.down_proj.data,
          "e_bias": jnp.zeros((total,), jnp.float32)}
    np.testing.assert_allclose(want, ref.experts(jnp.asarray(x), lp, cfg, NM),
                               rtol=1e-4, atol=1e-5)
    parts = np.zeros_like(want)
    for rank in range(8):
        share, _ = _experts(tokens, total, 4, (8 * rank, 8))
        share.router.data = whole.router.data
        share.gate_up_proj.data = whole.gate_up_proj.data[8 * rank:][:8]
        share.down_proj.data = whole.down_proj.data[8 * rank:][:8]
        part = share(Tensor(x)).numpy()
        # what a share computes is what the reference gives that share
        np.testing.assert_allclose(part, ref.experts(
            jnp.asarray(x), {**lp, "e_gate_up": share.gate_up_proj.data,
                             "e_down": share.down_proj.data},
            {**cfg, "num_experts": 8, "expert_parallel": 8,
             "expert_rank": rank}, NM), rtol=1e-4, atol=1e-5)
        parts += part
    np.testing.assert_allclose(parts, want, rtol=1e-4, atol=1e-5)


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

def _engine(amp=None):
    model, _ = _model()
    opt = paddle.optimizer.AdamW(learning_rate=1e-2, weight_decay=0.1,
                                 parameters=model.parameters())
    crit = Lfm2PretrainingCriterion()

    def loss_fn(m, b):
        ids = Tensor(b["ids"])
        labels = m.next_token_labels(ids)
        return crit(m(ids, labels), labels)
    return ParallelEngine(model, opt, loss_fn, amp_dtype=amp,
                          mesh=build_mesh(dp=1, devices=jax.devices()[:1]),
                          recompute=True)


def test_a_step_trains_and_carries_the_scopes_and_the_counters(_fresh_obs):
    engine = _engine(amp="bfloat16")
    assert engine.model.layers.enable_recompute
    batch = engine.shard_batch(_batch(seq=128))
    with flags_guard(flash_attention="always"):
        losses = [float(engine.step(batch, lr=1e-2)) for _ in range(3)]
        arms, passes = process_group("arm"), process_group("pass")
        assert arms.child("flash").counter("attention_arm_total").value >= 1
        assert arms.child("dense").counter("attention_arm_total").value == 0
        scopes = costmodel.step_op_scopes()
        text = engine.compiled_step_text()
    assert losses[2] < losses[0]
    # three convolution layers: each traced forward and backward counted
    # by the closed form of [2, 128, 128] bfloat16 with 3 taps
    want = short_conv.traffic_bytes((2, 128, 3 * 128), 2, 3)
    for which in ("forward", "backward"):
        counted = passes.child(which).counter("short_conv_bytes_total").value
        assert counted >= 3 * want[which] \
            and counted % (3 * want[which]) == 0
    named = [s for s in scopes.values() if "jvp(loss)" in s]
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
    assert not [s for s in named if "shared_experts" in s]
    assert any("/lm_head/head_cross_entropy" in s for s in named)
    assert any("/lm_head/norm/rms_norm" in s for s in named)
    assert any("/next_token_loss" in s for s in named)
    # the two kernels under the attention op, the forward not run again
    kernels = [s for s in named if "p1t_flash_attention" in s]
    assert kernels and all("/scaled_dot_product_attention/" in s
                           for s in kernels)
    assert not [s for s in kernels if "/rematted_computation/" in s
                and "p1t_flash_attention_fwd" in s]
    # the op runs again inside a recomputed segment: it keeps nothing
    again = [s for s in named if "/rematted_computation/" in s
             and "/gated_short_conv" in s]
    assert again
    # the router is a float32 island under the bf16 autocast
    router = [l for l in text.splitlines()
              if "moe_router" in l and " dot(" in l]
    assert router and all(" f32[" in l.split(" dot(")[0] for l in router)
    # the expert layers count their load: three of them
    load = engine.expert_load()
    assert len(load) == 3 and all(c["steps"] == 3 for c in load.values())
    assert all(c["num_experts"] == 16 and c["held"] == 8
               for c in load.values())
