"""Kanana-2 (ISSUE 31): interleaved rotary positions, latent attention with
keys and values of different widths on the blockwise kernels, the
routed-expert layer that is told which experts it holds, and the model
against the plain reference (``benchmarks/reference/kanana2_30b_a3b.py``);
recomputation, the selection bias through a step, the names a traced step
carries. CPU, tiny sizes, seeded weights."""

import hashlib
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import paddle1_tpu as paddle  # noqa: E402
from benchmarks.programs import kanana2_30b_a3b as program  # noqa: E402
from benchmarks.reference import kanana2_30b_a3b as ref  # noqa: E402
from benchmarks.reference.numerics import Numerics  # noqa: E402
from decoder_cases import (  # noqa: E402,F401
    NM, Decoder, decoder, eager_run, fresh_obs, ids_batch, next_token_loss,
    reference,
    test_recomputation_changes_neither_loss_nor_gradients,
    test_the_model_follows_the_reference)
from paddle1_tpu import nn, obs  # noqa: E402
from paddle1_tpu.core.flags import (auto_partitioned_region,  # noqa: E402
                                    flags_guard)
from paddle1_tpu.core.tensor import Tensor  # noqa: E402
from paddle1_tpu.framework.param_attr import ParamAttr  # noqa: E402
from paddle1_tpu.nn import functional as F  # noqa: E402
from paddle1_tpu.nn import layer_moe  # noqa: E402
from paddle1_tpu.nn.functional.attention import attention_ref  # noqa: E402
from paddle1_tpu.nn.initializer import Normal  # noqa: E402
from paddle1_tpu.obs import costmodel  # noqa: E402
from paddle1_tpu.ops.pallas import flash_attention as fa  # noqa: E402
from paddle1_tpu.ops.pallas import sum_picks  # noqa: E402
from paddle1_tpu.text.models import (Kanana2ForPretraining,  # noqa: E402
                                     Kanana2PretrainingCriterion,
                                     LatentAttention)

# the reference's configuration keys, at a tiny size: 8 routed experts of
# which this share holds 4 (rank 0 of 2), top-3
CFG = {"vocab_size": 96, "hidden_size": 32, "num_hidden_layers": 3,
       "num_attention_heads": 2, "qk_nope_head_dim": 16,
       "qk_rope_head_dim": 8, "v_head_dim": 16, "kv_lora_rank": 24,
       "intermediate_size": 48, "moe_intermediate_size": 16,
       "n_routed_experts": 4, "expert_parallel": 2, "expert_rank": 0,
       "num_experts_per_tok": 3, "n_shared_experts": 2,
       "first_k_dense_replace": 1, "routed_scaling_factor": 2.448,
       "rope_theta": 1e4, "rms_norm_eps": 1e-6, "initializer_range": 0.2}


def _build(cfg):
    held = cfg["n_routed_experts"]
    return Kanana2ForPretraining(
        n_routed_experts=held * cfg["expert_parallel"],
        held_experts=(cfg["expert_rank"] * held, held),
        **{k: cfg[k] for k in (
            "vocab_size", "hidden_size", "num_hidden_layers",
            "num_attention_heads", "qk_nope_head_dim", "qk_rope_head_dim",
            "v_head_dim", "kv_lora_rank", "intermediate_size",
            "moe_intermediate_size", "num_experts_per_tok",
            "n_shared_experts", "first_k_dense_replace",
            "routed_scaling_factor", "rope_theta", "rms_norm_eps",
            "initializer_range")})


_batch = ids_batch(CFG["vocab_size"], row=12)
_loss = next_token_loss(Kanana2PretrainingCriterion)


# -- rotary positions, pairs (2i, 2i + 1) -----------------------------------

@pytest.mark.parametrize("positions", [None, "given"])
def test_interleaved_rotary_is_a_complex_rotation(positions):
    rng = np.random.default_rng(1)
    x = rng.standard_normal((2, 5, 3, 8)).astype(np.float32)
    pos = (rng.integers(0, 50, (2, 5)) if positions else
           np.broadcast_to(np.arange(5), (2, 5)))
    got = F.rotary_embedding(
        Tensor(x), 1e4, None if positions is None else Tensor(pos),
        interleaved=True).numpy()
    z = x[..., 0::2] + 1j * x[..., 1::2]
    angle = pos[..., None, None] * 1e4 ** (-np.arange(4) / 4)
    turned = z * np.exp(1j * angle)
    want = np.stack([turned.real, turned.imag], -1).reshape(x.shape)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    # and it is the rotate-half pairing of the de-interleaved channels
    half = F.rotary_embedding(
        Tensor(np.concatenate([x[..., 0::2], x[..., 1::2]], -1)), 1e4,
        None if positions is None else Tensor(pos)).numpy()
    np.testing.assert_allclose(
        np.concatenate([got[..., 0::2], got[..., 1::2]], -1), half,
        rtol=1e-6, atol=1e-6)


# -- the kernels at a value width of their own ------------------------------

@pytest.mark.parametrize("d,dv", [(192, 128), (64, 32), (128, 64),
                                  (128, 128)])
def test_kernels_take_a_value_width_of_their_own(d, dv):
    """Forward, dq, dk, dv against ``attention_ref`` in interpret mode;
    (128, 128) is Ouro's shape, through the same code."""
    ks = jax.random.split(jax.random.key(0), 4)
    b, n, h = 1, 256, 2
    q, k = (jax.random.normal(kk, (b, n, h, d)) for kk in ks[:2])
    v, do = (jax.random.normal(kk, (b, n, h, dv)) for kk in ks[2:])
    assert fa.supported(q.shape, k.shape, causal=True, v_shape=v.shape)

    def plain(q, k, v):
        return attention_ref(q, k, v, is_causal=True)
    out = fa.flash_attention(q, k, v, causal=True)
    assert out.shape == (b, n, h, dv)
    np.testing.assert_allclose(out, plain(q, k, v), atol=2e-5)
    got = jax.grad(lambda *a: jnp.sum(
        fa.flash_attention(*a, causal=True) * do), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(plain(*a) * do), (0, 1, 2))(q, k, v)
    for a, w in zip(got, want):
        assert a.shape == w.shape
        np.testing.assert_allclose(a, w, atol=5e-5)


def test_supported_sees_the_value_width():
    q = (2, 256, 4, 192)
    assert fa.supported(q, q, v_shape=(2, 256, 4, 128))
    assert not fa.supported(q, q, v_shape=(2, 256, 4, 100))
    assert not fa.supported(q, q, v_shape=(2, 256, 4, 512))
    # the VMEM budget is the wider operand's
    assert fa.block_sizes(8192, 8192, 192, jnp.bfloat16) \
        == fa.block_sizes(8192, 8192, 256, jnp.bfloat16) == (512, 1024, 512)


def test_sdpa_passes_the_two_widths_to_the_kernels():
    rng = np.random.default_rng(3)
    q, k = (rng.standard_normal((1, 128, 2, 24)).astype(np.float32)
            for _ in range(2))
    v = rng.standard_normal((1, 128, 2, 16)).astype(np.float32)
    want = attention_ref(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                         is_causal=True)
    for flag in ("always", "never"):
        with flags_guard(flash_attention=flag):
            got = F.scaled_dot_product_attention(
                Tensor(q), Tensor(k), Tensor(v), is_causal=True).numpy()
        assert got.shape == (1, 128, 2, 16)
        np.testing.assert_allclose(got, want, atol=2e-5)


# -- latent attention -------------------------------------------------------

def _attention_weights(weights, i=0):
    return {k: weights[f"{k}.{i}"] for k in ref.ATTENTION_KEYS}


@pytest.mark.parametrize("attention", ["dense", "kernel"])
def test_latent_attention_follows_the_reference(reference, attention):
    model, weights = reference.model(), reference.weights
    seq = 128 if attention == "kernel" else 12
    u = np.random.default_rng(5).standard_normal(
        (2, seq, CFG["hidden_size"])).astype(np.float32)
    with flags_guard(
            flash_attention="always" if attention == "kernel" else "never"):
        got = model.layers.blocks[0].self_attn(Tensor(u)).numpy()
    lp = _attention_weights(weights)
    want = jax.jit(jax.vmap(
        lambda row: ref.attend(row, lp, CFG, NM) @ lp["wo"]))(jnp.asarray(u))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


def test_latent_attention_is_multi_head_attention_over_expanded_k_and_v(
        reference):
    """The latent expanded to every head's key and value, the one rotary
    key copied to every head, then plain multi-head attention."""
    model, weights = reference.model(), reference.weights
    attn = model.layers.blocks[0].self_attn
    assert isinstance(attn, LatentAttention)
    lp = _attention_weights(weights)
    s, heads = 10, CFG["num_attention_heads"]
    nope, rope, vd, rank = (CFG[k] for k in (
        "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "kv_lora_rank"))
    u = np.random.default_rng(6).standard_normal(
        (1, s, CFG["hidden_size"])).astype(np.float32)
    q = (u @ lp["wq"]).reshape(1, s, heads, nope + rope)
    both = u @ lp["wkva"]
    c = both[..., :rank]
    c = c / np.sqrt((c * c).mean(-1, keepdims=True) + 1e-6) * lp["nkv"]
    kv = np.asarray(c @ lp["wkvb"]).reshape(1, s, heads, nope + vd)

    def turn(x):
        return F.rotary_embedding(Tensor(np.asarray(x, np.float32)),
                                  CFG["rope_theta"], interleaved=True).numpy()
    k_pe = turn(both[..., None, rank:])
    assert k_pe.shape == (1, s, 1, rope)            # one a token, not a head
    q = np.concatenate([q[..., :nope], turn(q[..., nope:])], -1)
    k = np.concatenate([kv[..., :nope],
                        np.broadcast_to(k_pe, (1, s, heads, rope))], -1)
    want = attention_ref(jnp.asarray(q), jnp.asarray(k),
                         jnp.asarray(kv[..., nope:]), is_causal=True)
    want = np.asarray(want).reshape(1, s, heads * vd) @ lp["wo"]
    np.testing.assert_allclose(attn(Tensor(u)).numpy(), want, rtol=2e-4,
                               atol=2e-5)


# -- the router -------------------------------------------------------------

def test_the_router_by_hand_on_four_tokens():
    """Sigmoid scores over all the experts; the bias moves the choice and
    not the weight; the weights sum to the scaling factor."""
    logits = np.log(np.array([[4, 3, 2, 1, .5, .25], [1, 2, 3, 4, 5, 6],
                              [1, 1.1, 1.2, 1.3, 1.4, 1.5],
                              [9, 1, 8, 2, 7, 3]], np.float32))
    x = np.eye(4, dtype=np.float32)                   # token t reads row t
    s = 1 / (1 + np.exp(-logits))
    weights, chosen = layer_moe.route(jnp.asarray(x), jnp.asarray(logits),
                                      jnp.zeros(6), 2, 2.448)
    assert np.asarray(chosen).tolist() == [[0, 1], [5, 4], [5, 4], [0, 2]]
    np.testing.assert_allclose(np.sum(weights, -1), 2.448, rtol=1e-6)
    np.testing.assert_allclose(
        weights[0], 2.448 * s[0, :2] / s[0, :2].sum(), rtol=1e-6)
    bias = jnp.asarray([0, 0, 0, 5.0, 0, 0])          # lifts expert 3
    w2, c2 = layer_moe.route(jnp.asarray(x), jnp.asarray(logits), bias, 2,
                             2.448)
    assert np.asarray(c2).tolist() == [[3, 0], [3, 5], [3, 5], [3, 0]]
    np.testing.assert_allclose(np.sum(w2, -1), 2.448, rtol=1e-6)
    # the weight is made of the scores, without the bias
    np.testing.assert_allclose(
        w2[0], 2.448 * s[0, [3, 0]] / s[0, [3, 0]].sum(), rtol=1e-6)
    # float32 whatever the operands arrive in
    w3, _ = layer_moe.route(jnp.asarray(x, jnp.bfloat16),
                            jnp.asarray(logits), jnp.zeros(6), 2, 2.448)
    assert w3.dtype == jnp.float32


# -- the routed-expert layer ------------------------------------------------

def _experts(tokens, num_experts, top_k, held, seed=0, hidden=16, width=8,
             shared=0):
    paddle.seed(seed)
    layer = nn.RoutedExperts(
        hidden, width, num_experts, top_k, held=held, shared_width=shared,
        routed_scaling_factor=2.448,
        weight_attr=ParamAttr(initializer=Normal(std=0.3)))
    x = np.random.default_rng(seed).standard_normal(
        (tokens, hidden)).astype(np.float32)
    return layer, x


def _every_expert_over_every_token(layer, x, router, bias, gate_up, down):
    """The held experts' part, plainly: no sort, no capacity."""
    weights, chosen = layer_moe.route(x, router, bias, layer.top_k,
                                      layer.routed_scaling_factor)
    y = jnp.zeros_like(x)
    for e in range(layer.held):
        mine = jnp.sum(jnp.where(chosen == layer.first + e, weights, 0.), -1)
        both = x @ gate_up[e]
        width = both.shape[-1] // 2
        y = y + mine[:, None] * (
            (jax.nn.silu(both[:, :width]) * both[:, width:]) @ down[e])
    return y


def _plain(layer, x):
    return _every_expert_over_every_token(
        layer, jnp.asarray(x), layer.router.data,
        layer.e_score_correction_bias.data, layer.gate_up_proj.data,
        layer.down_proj.data)


# (tokens, experts, top_k, held, selection bias on the held experts)
ROUTING = {
    "even": (64, 8, 6, (0, 4), 0.0),
    "a_slice_in_the_middle": (640, 16, 2, (2, 2), 0.0),
    "every_token_picks_held_experts": (640, 16, 2, (2, 2), 5.0),
    "no_token_picks_a_held_expert": (640, 16, 2, (2, 2), -5.0),
}


@pytest.mark.parametrize("case", sorted(ROUTING))
def test_no_token_is_dropped_whatever_the_imbalance(case):
    tokens, num_experts, top_k, held, lift = ROUTING[case]
    layer, x = _experts(tokens, num_experts, top_k, held)
    bias = np.zeros(num_experts, np.float32)
    bias[held[0]:held[0] + held[1]] = lift
    layer.e_score_correction_bias.data = jnp.asarray(bias)
    capacity = layer_moe.capacity_rows(tokens, top_k, held[1], num_experts)
    got = layer(Tensor(x)).numpy()
    np.testing.assert_allclose(got, _plain(layer, x), rtol=1e-4, atol=1e-5)
    picks = tokens * top_k
    _, chosen = layer_moe.route(jnp.asarray(x), layer.router.data,
                                jnp.asarray(bias), top_k, 2.448)
    rows = layer_moe.sort_picks(chosen, held[0], held[1], capacity)[2]
    if lift > 0:        # every pick lands here: 1280 picks, 512 rows
        assert capacity < picks and int(np.sum(rows)) == capacity
        assert np.abs(got).min(axis=-1).max() > 0     # and none is lost
    if lift < 0:
        assert int(np.sum(rows)) == 0 and not got.any()


@pytest.mark.parametrize("case", ["even", "every_token_picks_held_experts"])
def test_the_layers_gradients_are_the_plain_ones(case):
    """The sort, the gathers written as each other's transposes and the
    overflow path under autodiff, against every expert over every token."""
    tokens, num_experts, top_k, held, lift = ROUTING[case]
    layer, x = _experts(tokens, num_experts, top_k, held)
    bias = np.zeros(num_experts, np.float32)
    bias[held[0]:held[0] + held[1]] = lift
    layer.e_score_correction_bias.data = jnp.asarray(bias)
    xt = Tensor(x, stop_gradient=False)
    layer(xt).sum().backward()
    want = jax.grad(
        lambda *a: jnp.sum(_every_expert_over_every_token(
            layer, a[0], a[1], jnp.asarray(bias), a[2], a[3])),
        (0, 1, 2, 3))(jnp.asarray(x), layer.router.data,
                      layer.gate_up_proj.data, layer.down_proj.data)
    got = (xt.grad, layer.router.grad, layer.gate_up_proj.grad,
           layer.down_proj.grad)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g.numpy(), w, rtol=2e-4,
                                   atol=1e-5 * np.abs(w).max())
    assert layer.e_score_correction_bias.stop_gradient


def test_the_eight_shares_add_up_to_the_whole_layer():
    """The share test: with the same weights, the routed outputs of the
    eight shares (2 experts of 16 each) plus the shared experts' output
    counted once are the uncut layer's."""
    tokens, hidden, width, total = 48, 16, 8, 16
    whole, x = _experts(tokens, total, 4, None, shared=12)
    want = whole(Tensor(x)).numpy()
    shared = whole.shared_experts(Tensor(x)).numpy()
    # the uncut layer against the reference's expert layer, uncut too
    cfg = {"n_routed_experts": total, "expert_parallel": 1, "expert_rank": 0,
           "num_experts_per_tok": 4, "moe_intermediate_size": width,
           "routed_scaling_factor": 2.448}
    lp = {"router": whole.router.data,
          "e_bias": whole.e_score_correction_bias.data,
          "e_gate_up": whole.gate_up_proj.data, "e_down": whole.down_proj.data,
          "s_gate": whole.shared_experts.gate_proj.weight.data,
          "s_up": whole.shared_experts.up_proj.weight.data,
          "s_down": whole.shared_experts.down_proj.weight.data}
    np.testing.assert_allclose(want, ref.experts(jnp.asarray(x), lp, cfg, NM),
                               rtol=1e-4, atol=1e-5)
    parts = np.zeros_like(want)
    for rank in range(8):
        share, _ = _experts(tokens, total, 4, (2 * rank, 2))
        share.router.data = whole.router.data
        share.gate_up_proj.data = whole.gate_up_proj.data[2 * rank:][:2]
        share.down_proj.data = whole.down_proj.data[2 * rank:][:2]
        parts += share(Tensor(x)).numpy()
    np.testing.assert_allclose(parts + shared, want, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("capacity", [4, 8, 64])
def test_sort_picks_by_hand(capacity):
    """12 picks (4 tokens x top-3) over 8 experts of which 2, 3 and 4 are
    held: the rows in the order of the held experts, each expert's rows
    within the capacity, and the held picks that found no row."""
    chosen = jnp.asarray([[2, 0, 3], [3, 4, 7], [4, 3, 2], [1, 3, 5]])
    order, where, sizes, overflow = layer_moe.sort_picks(chosen, 2, 3,
                                                         capacity)
    # expert 2 has picks 0 and 8, expert 3 has 2, 3, 7, 10, expert 4 has 4, 6
    held = [0, 8, 2, 3, 7, 10, 4, 6]
    kept = min(capacity, len(held))
    assert np.asarray(order)[:kept].tolist() == held[:kept]
    assert int(overflow) == len(held) - kept
    ends = np.minimum(np.cumsum([2, 4, 2]), capacity)
    assert np.asarray(sizes).tolist() == np.diff(ends, prepend=0).tolist()
    where = np.asarray(where)
    assert where[held[:kept]].tolist() == list(range(kept))
    # a pick of an absent expert, or beyond the capacity: the row of zeros
    late = sorted(set(range(12)) - set(held[:kept]))
    assert (where[late] == capacity).all()


# -- a token's picks, summed (ISSUE 32) -------------------------------------

def _sum_picks_before_issue_32(o, where, fan):
    """``layer_moe._sum_picks`` as PR 31 shipped it: a row of zeros behind
    the rows, every pick's row gathered, a token's picks side by side."""
    padded = jnp.concatenate([o, jnp.zeros_like(o[:1])])
    picked = padded[where].reshape((-1, fan) + o.shape[1:])
    return jnp.sum(picked.astype(jnp.float32), axis=1).astype(o.dtype)


def _f32(x):
    return np.asarray(x.astype(jnp.float32))


# (the experts the 16 tokens choose their 6 from, of 16; 0-7 are held)
HELD_PICKS = {"none": (8, 16), "some": (0, 16), "all": (0, 8)}
# (fan, the operand's trailing shape): the rows of a hidden width that
# `moe_dispatch` and `moe_combine` move, through the gather (40 wide) and
# through the kernel (interpret mode here; 2048 wide: whole tiles in
# either dtype; 2560 and 1280 wide, SmallThinker's and half of it: 10 and
# 5 or 20 and 10 lane rows of 32 bits, which travel 4, 2 or 1 deep), the
# pick weights' vector, and rows a pick each
OPERANDS = {"fan6-rows": (6, (40,)), "fan6-rows_of_tiles": (6, (2048,)),
            "fan6-rows_of_2560": (6, (2560,)),
            "fan6-rows_of_1280": (6, (1280,)),
            "fan1-vector": (1, ()), "fan1-rows": (1, (40,))}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("held", sorted(HELD_PICKS))
@pytest.mark.parametrize("capacity", ["below_the_held_picks", "every_pick"])
@pytest.mark.parametrize("operand", sorted(OPERANDS))
def test_the_two_gathers_are_one_hot_products_and_transposes(
        operand, capacity, held, dtype):
    """``rows_in_order`` and ``sum_of_picks``, values and VJPs, against
    the products with the plain one-hot matrices R [capacity, n] (sorted
    row r reads row ``order[r] // fan``) and S [n, capacity] (row i sums
    the rows its picks found); against PR 31's body to the last bit,
    whatever the dtype: the sum is float32 and cast once; and as each
    other's transposes on the rows that hold a pick."""
    fan, trailing = OPERANDS[operand]
    tokens, top_k, lo_hi = 16, 6, HELD_PICKS[held]
    rng = np.random.default_rng(sorted(HELD_PICKS).index(held))
    chosen = lo_hi[0] + np.argsort(
        rng.random((tokens, lo_hi[1] - lo_hi[0])), axis=-1)[:, :top_k]
    picks = tokens * top_k
    rows = picks if capacity == "every_pick" else 24
    order, where, sizes, _ = layer_moe.sort_picks(
        jnp.asarray(chosen, jnp.int32), 0, 8, rows)
    live = int(np.sum(sizes))
    assert live == {"none": 0, "all": rows}.get(held, live)
    n = picks // fan
    order_np, where_np = np.asarray(order), np.asarray(where)
    R = np.zeros((rows, n), np.float32)
    R[np.arange(rows), order_np // fan] = 1
    S = np.zeros((n, rows + 1), np.float32)
    np.add.at(S, (np.arange(picks) // fan, where_np), 1)
    S = S[:, :rows]                       # without the row of zeros
    np.testing.assert_array_equal(S[:, :live], R[:live].T)
    assert not S[:, live:].any()

    a = jnp.asarray(rng.standard_normal((n,) + trailing), dtype)
    o = jnp.asarray(rng.standard_normal((rows,) + trailing), dtype)
    # a whole number of 32-bit lane rows (512 bytes), of rows alone
    assert sum_picks.supported(o, where, fan) == (
        len(trailing) == 1 and trailing[0] * o.dtype.itemsize % 512 == 0)

    def product(m, v):              # float32, then the operand's rounding
        return (m @ _f32(v).reshape(v.shape[0], -1)).reshape(
            (m.shape[0],) + v.shape[1:])
    tol = dict(rtol=1e-6, atol=1e-6) if dtype == "float32" else \
        dict(rtol=2 ** -8, atol=2 ** -8)

    def close(got, want):
        assert got.dtype == jnp.dtype(dtype) and got.shape == want.shape
        np.testing.assert_allclose(_f32(got), want, **tol)
    got_rows, rows_vjp = jax.vjp(
        lambda a: layer_moe.rows_in_order(a, order, where, fan), a)
    got_sum, sum_vjp = jax.vjp(
        lambda o: layer_moe.sum_of_picks(o, order, where, fan), o)
    pulled, = rows_vjp(o)       # once: a call lowers the kernel anew
    close(got_rows, product(R, a))
    close(got_sum, product(S, o))
    close(pulled, product(S, o))
    close(sum_vjp(a)[0], product(R, a))
    # to the last bit, values and the dispatch's backward alike
    before = _sum_picks_before_issue_32(o, where, fan)
    with auto_partitioned_region():             # the gather, whatever the width
        gathered = layer_moe._sum_picks(o, where, fan)
    for got in (got_sum, pulled, gathered,
                jax.jit(layer_moe.sum_of_picks, static_argnums=3)(
                    o, order, where, fan)):
        np.testing.assert_array_equal(_f32(got), _f32(before))
    # <rows_in_order(a), d> == <a, sum_of_picks(d)>, d on the live rows
    d = jnp.where((jnp.arange(rows) < live).reshape((-1,) + (1,) * len(
        trailing)), o, 0)
    lhs = np.vdot(_f32(got_rows), _f32(d))
    rhs = np.vdot(_f32(a), _f32(layer_moe.sum_of_picks(d, order, where, fan)))
    np.testing.assert_allclose(lhs, rhs, rtol=1e-5 if dtype == "float32"
                               else 2e-2, atol=1e-5)


@pytest.mark.parametrize("tokens,lift", [(40, 0.0), (256, 0.0), (40, 5.0)])
def test_the_kernel_sums_block_after_block(tokens, lift):
    """Five blocks of 8 tokens and two of 128 (the buffer's two halves in
    turn, the next block's rows asked for while this one is summed), and
    four held picks a token against rows for fewer: bfloat16 rows against
    PR 31's body to the last bit, under ``jit`` as a step runs it."""
    rng = np.random.default_rng(tokens)
    scores = rng.random((tokens, 16))
    scores[:, :4] += lift                       # experts 0-3 are held
    chosen = np.argsort(-scores, axis=-1)[:, :6]
    rows = 96 if lift else 128
    _, where, sizes, _ = layer_moe.sort_picks(
        jnp.asarray(chosen, jnp.int32), 0, 4, rows)
    assert 0 < int(np.sum(sizes)) <= rows
    o = jnp.asarray(rng.standard_normal((rows, 2048)), jnp.bfloat16)
    assert sum_picks.supported(o, where, 6)
    got = jax.jit(layer_moe._sum_picks, static_argnums=2)(o, where, 6)
    np.testing.assert_array_equal(
        _f32(got), _f32(_sum_picks_before_issue_32(o, where, 6)))


def test_a_step_partitioned_by_xla_takes_the_gather():
    """Inside ``auto_partitioned_region`` (GSPMD refuses a Mosaic
    kernel) rows of whole tiles go through the gather, to the same bits."""
    import contextlib
    where = jnp.asarray([0, 2, 2, 1, 2, 2, 0, 2], jnp.int32)   # 2: no row
    o = jnp.asarray(np.random.default_rng(0).standard_normal((2, 1024)),
                    jnp.float32)
    assert sum_picks.supported(o, where, 1)

    def calls(region):
        with region:
            return str(jax.make_jaxpr(
                lambda o: layer_moe._sum_picks(o, where, 1))(o))
    assert "p1t_sum_picks_fwd" in calls(contextlib.nullcontext())
    assert "pallas_call" not in calls(auto_partitioned_region())
    with auto_partitioned_region():
        gathered = layer_moe._sum_picks(o, where, 1)
    np.testing.assert_array_equal(gathered, layer_moe._sum_picks(o, where, 1))
    np.testing.assert_array_equal(
        gathered, np.where((np.asarray(where) < 2)[:, None],
                           np.asarray(o)[np.minimum(where, 1)], 0))


# sha256 of the jaxpr (kernel body, index map, grid and the picks' sort
# included) of one call on bf16 rows of whole tiles at Kanana-2's and
# SDAR's shapes and on float32 rows at a test's, under jax 0.9.0, as
# ISSUE 44's parent (5d4e33b) lowered them: the widths the kernel took
# before it took any whole number of 32-bit lane rows keep their layout,
# their block and their text. Whoever changes the kernel on purpose
# re-takes them.
SUM_PICKS_JAXPRS = {
    (36864, 2048, 16384, 6, "bfloat16"):
        "0cbd06ad64d19074e70b70454a3a5e2fa8c8ea265713558f50a8e5b45d15c2ea",
    (49152, 2048, 16384, 8, "bfloat16"):
        "40f8ef7f7871f401c9f022a1f53597d8f8b20a142982d4b6709c81542f9162e5",
    (4096, 1024, 2048, 8, "float32"):
        "3474eed7167dd46596b66a5f277550fdd8150947df497aeb5e0b76c30cfd347e",
}


@pytest.mark.parametrize("shape", sorted(SUM_PICKS_JAXPRS))
def test_rows_of_whole_tiles_lower_to_the_text_they_had(shape):
    if jax.__version__ != "0.9.0":
        pytest.skip("the digests were taken under jax 0.9.0")
    rows, hidden, tokens, fan, dtype = shape
    jaxpr = jax.make_jaxpr(lambda o, w: sum_picks.sum_picks(o, w, fan))(
        jax.ShapeDtypeStruct((rows, hidden), jnp.dtype(dtype)),
        jax.ShapeDtypeStruct((tokens * fan,), jnp.int32))
    text = re.sub(r" at 0x[0-9a-f]+", "", str(jaxpr))
    assert text.count("pallas_call") == 1
    assert hashlib.sha256(text.encode()).hexdigest() \
        == SUM_PICKS_JAXPRS[shape]


@pytest.mark.parametrize("hidden,dtype,row", [
    (2048, "bfloat16", (16, 128)), (4096, "bfloat16", (32, 128)),
    (1024, "float32", (8, 128)),                # whole tiles, as before
    (2560, "bfloat16", (4, 640)), (1024, "bfloat16", (8, 128)),
    (3072, "bfloat16", (8, 384)), (256, "bfloat16", (2, 128)),
    (1280, "float32", (2, 640)), (640, "float32", (1, 640)),
    (2048 + 128, "bfloat16", ()), (64, "float32", ())])
def test_a_row_travels_as_deep_as_its_lane_rows_allow(hidden, dtype, row):
    """The one rule of the layout, off the width and the dtype alone: a
    row's tiles are its own and contiguous, so the operand, the buffer
    and the output block of a call carry that shape behind their
    leading dimensions."""
    assert sum_picks._row_shape(hidden, jnp.dtype(dtype).itemsize) == row
    o = jax.ShapeDtypeStruct((64, hidden), jnp.dtype(dtype))
    where = jax.ShapeDtypeStruct((16 * 6,), jnp.int32)
    assert sum_picks.supported(o, where, 6) == bool(row)
    if row:
        text = str(jax.make_jaxpr(
            lambda o, w: sum_picks.sum_picks(o, w, 6))(o, where))
        dims = ",".join(map(str, row))
        short = {"bfloat16": "bf16", "float32": "f32"}[dtype]
        assert f"{short}[64,{dims}]" in text       # the rows, relaid
        assert f"{short}[2,6,16,{dims}]" in text   # the buffer's two halves
        assert f"{short}[16,{dims}]" in text       # a block of the output


@pytest.mark.parametrize("hidden,arm", [(2560, "kernel"),
                                        (2048 + 128, "gather")])
def test_a_traced_layer_counts_the_arm_its_sums_took(fresh_obs, hidden,
                                                     arm):
    """``p1t_moe_sum_picks_arm_total{arm}``: one increment a traced sum
    over rows, two a layer's forward + backward (``moe_combine``, and the
    transpose of ``moe_dispatch``'s gather); the pick weights' vector,
    whose gradient is such a sum too, is counted under neither arm."""
    layer, x = _experts(16, 8, 3, (0, 4), hidden=hidden)
    state = {k: v.data.astype(jnp.bfloat16)
             for k, v in layer.state_dict().items()}

    def loss(state, x):
        from paddle1_tpu.autograd import engine as ae
        with ae.no_grad(), layer.load_functional_state(state):
            return (layer(Tensor(x)).data ** 2).mean()
    jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(
        state, jnp.asarray(x, jnp.bfloat16))
    arms = obs.process_group("arm")
    other = {"kernel": "gather", "gather": "kernel"}[arm]
    assert arms.child(arm).counter("moe_sum_picks_arm_total").value == 2
    assert arms.child(other).counter("moe_sum_picks_arm_total").value == 0
    assert f'p1t_moe_sum_picks_arm_total{{arm="{arm}"}} 2' \
        in obs.registry.render_process_groups()
    # the weights' vector alone: neither
    obs.reset_process_registry()
    layer_moe._sum_picks(jnp.ones((24,)), jnp.arange(24, dtype=jnp.int32), 1)
    assert "moe_sum_picks_arm_total" not in \
        obs.registry.render_process_groups()


# -- the model --------------------------------------------------------------

def _the_kinds_of_layers_and_the_logits(model, weights, batch):
    state = jax.eval_shape(lambda w: ref.loss(
        w, {"ids": jnp.asarray(batch["ids"])}, CFG, NM)[1], weights)
    assert sorted(state) == ["e_bias.1", "e_bias.2"]
    # a leading dense layer, then expert layers
    kinds = [type(b.mlp).__name__ for b in model.layers.blocks]
    assert kinds == ["GatedFeedForward", "RoutedExperts", "RoutedExperts"]
    logits = model(Tensor(batch["ids"])).numpy()
    assert logits.shape == (2, 12, CFG["vocab_size"])


# the shared cases' model (decoder_cases.py)
DECODER = Decoder(
    cfg=CFG, ref=ref, program=program, build=_build, criterion=_loss,
    batch=_batch, dense_seq=12,
    follows_also=_the_kinds_of_layers_and_the_logits,
    buffers=frozenset(f"layers.blocks.{i}.mlp.e_score_correction_bias"
                      for i in (1, 2)))


@pytest.mark.parametrize("precision", ["float32", "float8_matmul"])
def test_the_reference_in_blocks_is_the_reference(reference, monkeypatch,
                                                  precision):
    """At the cell's size the reference takes the rows, the heads and
    groups of the held experts one at a time through ``lax.map``, and a
    head's queries in blocks; at a test's size it takes each whole. The same loss and
    gradients either way (the control's float8 scale is a block's own, so
    there only the loss is held, loosely)."""
    batch = _batch(seq=16)
    nm = Numerics(precision)
    whole, g_whole = reference.compiled(nm)(batch)
    # one head and 8 of its 16 queries a block of scores; one expert a group
    monkeypatch.setattr(ref, "SCORE_BLOCK_BYTES", 4 * 16 * 8)
    monkeypatch.setattr(ref, "EXPERTS_BLOCK_BYTES", 4 * 16 * 32)
    monkeypatch.setattr(ref, "BLOCK_TOKENS", 16)     # and a row a block
    text = str(jax.make_jaxpr(lambda w: ref.loss(
        w, {"ids": jnp.asarray(batch["ids"])}, CFG, nm)[0])(
            reference.weights))
    assert text.count("scan") >= 3
    blocks, g_blocks = reference.compiled(nm)(batch)
    if precision != "float32":
        # other blocks, other scales: the control's noise is drawn anew
        assert float(blocks) == pytest.approx(float(whole), rel=0.05)
        assert all(np.isfinite(np.asarray(g)).all()
                   for g in g_blocks.values())
        return
    assert float(blocks) == pytest.approx(float(whole), rel=1e-5)
    for k in g_whole:
        if not k.startswith("e_bias."):
            a, b = np.asarray(g_blocks[k]), np.asarray(g_whole[k])
            assert np.linalg.norm(a - b) <= 1e-5 * max(
                np.linalg.norm(b), 1e-3), k


def test_a_recomputed_expert_layer_keeps_the_kernels_outputs_alone(reference,
                                                                   capsys):
    """Beside its inputs: the kernel's two outputs (ISSUE 30) and, by
    name (ISSUE 37), the stream after attention, the picks' scores and
    experts, the sort's four and the sorted rows' weights (the capacity
    is every pick at this size); not the shared experts' output, which
    nothing in the backward pass reads."""
    from jax.ad_checkpoint import print_saved_residuals
    from paddle1_tpu.autograd.engine import no_grad
    from paddle1_tpu.distributed.fleet.utils.recompute import recompute
    layer = reference.model().layers.blocks[1]
    state = {k: v.data for k, v in layer.state_dict().items()}
    h = jnp.asarray(np.random.default_rng(2).standard_normal(
        (2, 128, CFG["hidden_size"])), jnp.float32)

    def loss(state, h):
        with no_grad(), layer.load_functional_state(state):
            return jnp.sum(recompute(layer, Tensor(h)).data)
    with flags_guard(flash_attention="always"):
        print_saved_residuals(loss, state, h)
    lines = capsys.readouterr().out.strip().splitlines()
    beside = [l for l in lines if " from the argument " not in l]
    # + the hidden input, - the selection bias: the ``top_k`` that read it
    # does not run again
    assert len(lines) - len(beside) == len(state)
    assert not any("e_score_correction_bias" in l for l in lines)
    tokens, k = 2 * 128, CFG["num_experts_per_tok"]
    floats = sorted(l.split()[0] for l in beside if l.startswith("f32"))
    assert floats == sorted([
        "f32[4,128,16]", "f32[4,128]",                 # the kernel's two
        f"f32[2,128,{CFG['hidden_size']}]",            # the stream, once
        f"f32[{tokens},{k}]",                          # the picks' scores
        f"f32[{tokens * k}]"]), beside                 # the rows' weights
    # the rest: the picks' experts and the sort's integers (or an index
    # made of them), a pick or a row each at most
    rest = [l for l in beside if not l.startswith("f32")]
    assert len(rest) >= 4 and all(
        l.split()[0] in (f"i32[{tokens},{k}]", f"i32[{tokens * k}]",
                         f"i32[{CFG['n_routed_experts']}]", "i32[]")
        for l in rest), rest


def test_the_selection_bias_passes_through_a_step_untouched(reference):
    """A buffer with no gradient: AdamW's decay would shrink it."""
    bias = np.linspace(-0.1, 0.1, 8)
    model = reference.model()
    for _, b in model.named_buffers():
        b.data = jnp.asarray(bias, jnp.float32)
    engine = reference.engine(model=model)
    names = [k for k in engine.params if k.endswith("e_score_correction_bias")]
    assert len(names) == 2
    batch = engine.shard_batch(_batch())
    losses = [float(engine.step(batch)) for _ in range(3)]
    assert losses[2] < losses[0]
    for k in names:
        np.testing.assert_array_equal(np.asarray(engine.params[k]),
                                      bias.astype(np.float32))
    moved = engine.params["layers.blocks.1.mlp.router"]
    assert not np.allclose(np.asarray(moved), np.asarray(
        engine.model.layers.blocks[1].mlp.router.data))


def test_the_expert_layer_and_latent_attention_have_scopes(reference,
                                                           fresh_obs):
    engine = reference.engine(amp="bfloat16")
    float(engine.step(engine.shard_batch(_batch()), lr=1e-3))
    scopes = costmodel.step_op_scopes()
    named = [s for s in scopes.values() if "jvp(loss)" in s]
    assert not [s for s in named if "/while/" in s]
    for i in (1, 2):
        at = f"/layers/recompute/{i}/mlp/moe/"
        for op in ("moe_router", "moe_dispatch", "routed_experts",
                   "moe_combine", "shared_experts/gate_proj/linear",
                   "shared_experts/swiglu"):
            assert any(at + op in s for s in named), (i, op)
    assert not [s for s in named if "/layers/recompute/0/mlp/moe" in s]
    assert any("/layers/recompute/0/mlp/gate_proj/linear" in s for s in named)
    for i in range(3):
        at = f"/layers/recompute/{i}/self_attn/"
        for op in ("q_proj/linear", "kv_a_proj_with_mqa/linear",
                   "kv_a_layernorm/rms_norm", "kv_b_proj/linear",
                   "rotary_embedding", "scaled_dot_product_attention",
                   "o_proj/linear"):
            assert any(at + op in s for s in named), (i, op)
    assert any("/lm_head/head_cross_entropy" in s for s in named)
    again = [s for s in named if "/rematted_computation/" in s]
    assert any("/moe/routed_experts" in s for s in again)
    # the router is a float32 island under the bf16 autocast
    text = engine.compiled_step_text()
    router = [l for l in text.splitlines()
              if "moe_router" in l and " dot(" in l]
    assert router and all(" f32[" in l.split(" dot(")[0] for l in router)
