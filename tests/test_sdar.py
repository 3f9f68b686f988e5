"""SDAR by block diffusion (ISSUE 33): the mask rule the blockwise kernels
skip by tile, grouped key/value heads in those kernels, the softmax rule
of the routed-expert layer, and the model against the plain reference
(``benchmarks/reference/sdar_30b_a3b.py``); recomputation, the names and
counters a traced step carries, and the kernels' jaxprs held to the ones
last taken on purpose. CPU, tiny sizes, seeded weights; the kernels
in interpreter mode at tile-aligned sizes."""

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
from benchmarks.programs import sdar_30b_a3b as program  # noqa: E402
from benchmarks.reference import kanana2_30b_a3b as blocks_of  # noqa: E402
from benchmarks.reference import sdar_30b_a3b as ref  # noqa: E402
from decoder_cases import (  # noqa: E402,F401
    Decoder, decoder, eager_run, fresh_obs, reference,
    test_a_step_trains_and_carries_the_scopes_and_the_counters,
    test_recomputation_changes_neither_loss_nor_gradients,
    test_the_eight_shares_add_up_to_the_whole_layer,
    test_the_model_follows_the_reference,
    test_the_reference_in_blocks_is_the_reference)
from paddle1_tpu import nn, obs  # noqa: E402
from paddle1_tpu.core.flags import flags_guard  # noqa: E402
from paddle1_tpu.core.tensor import Tensor  # noqa: E402
from paddle1_tpu.framework.param_attr import ParamAttr  # noqa: E402
from paddle1_tpu.nn import functional as F  # noqa: E402
from paddle1_tpu.nn import layer_moe  # noqa: E402
from paddle1_tpu.nn.functional.attention import attention_ref  # noqa: E402
from paddle1_tpu.nn.initializer import Normal  # noqa: E402
from paddle1_tpu.obs.registry import process_group  # noqa: E402
from paddle1_tpu.ops.pallas import flash_attention as fa  # noqa: E402
from paddle1_tpu.ops.pallas import mask_rules, sum_picks  # noqa: E402
from paddle1_tpu.ops.pallas.mask_rules import (CAUSAL,  # noqa: E402
                                               BlockDiffusion)
from paddle1_tpu.text.models import (SdarBlockDiffusionCriterion,  # noqa: E402
                                     SdarForBlockDiffusion)

# the reference's configuration keys at hidden 128: 16 routed experts of
# which this share holds 8 (rank 0 of 2), top-8, 4 query heads over 2
CFG = {"vocab_size": 96, "hidden_size": 128, "num_hidden_layers": 2,
       "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 32,
       "moe_intermediate_size": 16, "num_experts": 8, "expert_parallel": 2,
       "expert_rank": 0, "num_experts_per_tok": 8, "block_length": 4,
       "mask_token_id": 95, "noise_eps": 1e-3, "rope_theta": 1e6,
       "rms_norm_eps": 1e-6, "initializer_range": 0.2,
       "residual_initializer_range": 0.2,
       "embedding_initializer_range": 0.2, "mask_route_logit": 8.0}


# -- the mask rule ----------------------------------------------------------

def _dense_by_hand(length, block, noisy_first):
    """The mask from its four cases, pair by pair."""
    m = np.zeros((2 * length, 2 * length), bool)
    for r in range(2 * length):
        for c in range(2 * length):
            r_noisy = (r < length) == noisy_first
            c_noisy = (c < length) == noisy_first
            br, bc = r % length // block, c % length // block
            if r_noisy and c_noisy:
                m[r, c] = bc == br
            elif r_noisy:
                m[r, c] = bc < br
            elif not c_noisy:
                m[r, c] = bc <= br
    return m


@pytest.mark.parametrize("noisy_first", [True, False])
@pytest.mark.parametrize("block", [1, 4, 32])
def test_the_rule_describes_the_mask_of_the_four_cases(block, noisy_first):
    length = 64
    rule = BlockDiffusion(length, block, noisy_first)
    seen = mask_rules.dense_mask(rule, 2 * length, 2 * length)
    assert np.array_equal(seen, _dense_by_hand(length, block, noisy_first))
    assert seen.any(axis=1).all()               # every query sees a key
    assert int(seen.sum()) == length * length + length * block \
        == mask_rules.visible_pairs(rule, 2 * length, 2 * length)
    with pytest.raises(ValueError):
        mask_rules.dense_mask(rule, length, length)


def test_a_rule_refuses_a_block_that_is_no_power_of_two():
    for length, block in ((64, 3), (64, 0), (48, 32)):
        with pytest.raises(ValueError):
            BlockDiffusion(length, block)
    assert not fa.supported((1, 512, 4, 64), (1, 512, 2, 64),
                            mask=BlockDiffusion(128, 4))     # 2L != 512
    assert not fa.supported((1, 384, 4, 64), (1, 384, 2, 64),
                            mask=BlockDiffusion(192, 4))     # L % 128
    assert fa.supported((1, 512, 4, 64), (1, 512, 2, 64),
                        mask=BlockDiffusion(256, 4))
    assert not fa.supported((1, 512, 4, 64), (1, 512, 3, 64))   # 4 % 3
    with pytest.raises(ValueError):
        fa.flash_attention(*[jnp.zeros((1, 256, 1, 64))] * 3, causal=True,
                           mask=BlockDiffusion(128, 4))


def _qkv(s, heads, kv_heads, d, seed=0, dtype=jnp.float32):
    keys = jax.random.split(jax.random.key(seed), 4)
    return [jax.random.normal(k, (1, s, h, d), dtype)
            for k, h in zip(keys, (heads, kv_heads, kv_heads, heads))]


def _out_and_grads(attn, q, k, v, dout):
    out, pull = jax.vjp(attn, q, k, v)
    return (out,) + pull(dout)


def _close(got, want, tol=2e-5):
    for name, g, w in zip(("out", "dq", "dk", "dv"), got, want):
        assert g.shape == w.shape, name
        np.testing.assert_allclose(g, w, rtol=0, atol=tol * float(
            jnp.max(jnp.abs(w))), err_msg=name)


@pytest.mark.parametrize("noisy_first", [True, False])
@pytest.mark.parametrize("block", [1, 4, 32, 256])
def test_the_kernels_under_the_rule_follow_the_dense_mask(block, noisy_first):
    """Forward and all three gradients, 4 query heads over 2 key/value
    heads, 128 x 128 score tiles over a doubled row of 2 x 256."""
    length = 256
    rule = BlockDiffusion(length, block, noisy_first)
    q, k, v, dout = _qkv(2 * length, 4, 2, 64)
    seen = jnp.asarray(_dense_by_hand(length, block, noisy_first))

    def dense(q, k, v):
        return attention_ref(q, jnp.repeat(k, 2, axis=2),
                             jnp.repeat(v, 2, axis=2), mask=seen[None, None])
    got = _out_and_grads(
        lambda q, k, v: fa.flash_attention(q, k, v, mask=rule,
                                           blocks=(128, 128, 128)),
        q, k, v, dout)
    _close(got, _out_and_grads(dense, q, k, v, dout))
    # and attention_ref builds the same mask from the description
    _close(_out_and_grads(lambda q, k, v: attention_ref(
        q, k, v, mask_rule=rule), q, k, v, dout), got)


def test_fetched_blocks_longer_than_a_chunk_and_the_default_sizes():
    """1024 positions a copy: the sizes the kernels choose themselves
    (512-row resident blocks, 1024 fetched, 512 a pass)."""
    length = 1024
    rule = BlockDiffusion(length, 4)
    q, k, v, dout = _qkv(2 * length, 2, 1, 128, seed=1)
    want = _out_and_grads(lambda q, k, v: attention_ref(
        q, k, v, mask_rule=rule), q, k, v, dout)
    _close(_out_and_grads(lambda q, k, v: fa.flash_attention(
        q, k, v, mask=rule), q, k, v, dout), want)


def _closed_form(length, block, tile):
    """Tiles of the (2L / tile)^2 a kernel runs plain, runs masked and
    skips, for blocks shorter than a tile: below the diagonal of the
    noisy-to-clean and the clean-to-clean quadrants plain, the three
    diagonals masked, the rest (all of clean-to-noisy) skipped."""
    n = length // tile
    assert block < tile
    return {"plain": n * (n - 1), "masked": 3 * n,
            "skipped": 4 * n * n - n * (n - 1) - 3 * n}


@pytest.mark.parametrize("block", [1, 4, 32])
@pytest.mark.parametrize("length,tile", [(256, 128), (8192, 512)])
def test_tiles_run_masked_and_skipped_against_the_closed_form(length, tile,
                                                              block):
    for first in (True, False):
        rule = BlockDiffusion(length, block, first)
        assert mask_rules.tile_counts(rule, 2 * length, 2 * length, tile,
                                      tile) == _closed_form(length, block,
                                                            tile)
    # ISSUE 33's count at the cell's size: 136 + 136 + 16 of 1024
    if length == 8192:
        counts = _closed_form(length, block, tile)
        assert counts["plain"] + counts["masked"] == 288
        assert mask_rules.tile_counts(CAUSAL, 16384, 16384, 512, 512) == {
            "plain": 496, "masked": 32, "skipped": 496}


def test_one_block_and_no_mask_count_their_tiles():
    rule = BlockDiffusion(256, 256)             # one block: B = L
    assert mask_rules.tile_counts(rule, 512, 512, 128, 128) == {
        "plain": 8, "masked": 0, "skipped": 8}
    assert mask_rules.tile_counts(mask_rules.NO_MASK, 512, 256, 128, 128) == {
        "plain": 8, "masked": 0, "skipped": 0}
    # the kernels' sequential axis counts the needed pairs alone: the
    # table's length, and the most steps any one query block has (what
    # every query block had on the rectangle the kernels walked before)
    cell = BlockDiffusion(8192, 4)
    for fetched, steps, most in ((1024, 160, 9),    # of the 16 there are
                                 (512, 288, 17)):   # of 32
        table = mask_rules.pair_table(cell, 16384, 16384, 512, fetched)
        assert (table.steps, table.held) == (steps, 0)
        assert np.bincount(table.q).max() == most


def test_a_lowered_kernel_call_counts_its_tiles(fresh_obs):
    """``flash_tiles_total{kind}``: the forward kernel's tiles of one
    lowered call, times its batch x heads; a wrong rule shows here."""
    length = 256
    rule = BlockDiffusion(length, 4)
    q, k, v, _ = _qkv(2 * length, 4, 2, 64, seed=2)
    jax.jit(lambda q, k, v: fa.flash_attention(
        q, k, v, mask=rule, blocks=(128, 256, 128))).lower(q, k, v)
    want = _closed_form(length, 4, 128)
    kinds = process_group("kind")
    assert {kind: kinds.child(kind).counter("flash_tiles_total").value
            for kind in want} == {k: 4 * n for k, n in want.items()}


# -- grouped key/value heads ------------------------------------------------

@pytest.mark.parametrize("mask", ["block_diffusion", "causal", "none"])
def test_grouped_heads_are_keys_and_values_repeated_eight_times(mask):
    """8 query heads over 1 key/value head against the same kernels given
    k and v repeated 8 times: the outputs and dQ equal, dK and dV the sums
    over the group."""
    s, group = 256, 8
    rule = {"block_diffusion": BlockDiffusion(128, 4), "causal": CAUSAL,
            "none": None}[mask]
    q, k, v, dout = _qkv(s, group, 1, 64, seed=3)

    def attn(q, k, v):
        return fa.flash_attention(q, k, v, mask=rule, blocks=(128, 128, 128))
    got = _out_and_grads(attn, q, k, v, dout)
    rep = _out_and_grads(attn, q, jnp.repeat(k, group, axis=2),
                         jnp.repeat(v, group, axis=2), dout)
    want = rep[:2] + tuple(jnp.sum(g, axis=2, keepdims=True)
                           for g in rep[2:])
    _close(got, want, tol=1e-5)


def test_sdpa_hands_the_rule_and_the_grouped_heads_to_the_kernels(fresh_obs):
    length = 128
    rule = BlockDiffusion(length, 4)
    q, k, v, _ = _qkv(2 * length, 4, 2, 64, seed=4)
    want = attention_ref(q, k, v, mask_rule=rule)
    arms = process_group("arm")
    for flag, arm in (("always", "flash"), ("never", "dense")):
        with flags_guard(flash_attention=flag):
            got = F.scaled_dot_product_attention(
                Tensor(q), Tensor(k), Tensor(v), mask_rule=rule).numpy()
        np.testing.assert_allclose(got, want, atol=2e-5)
        assert arms.child(arm).counter("attention_arm_total").value == 1
    # a rule the kernels do not take (a copy no multiple of 128 long)
    # goes to the composition, which builds the mask from it
    odd = BlockDiffusion(96, 4)
    q, k, v, _ = _qkv(192, 4, 2, 64, seed=5)
    with flags_guard(flash_attention="always"):
        got = F.scaled_dot_product_attention(
            Tensor(q), Tensor(k), Tensor(v), mask_rule=odd).numpy()
    np.testing.assert_allclose(got, attention_ref(q, k, v, mask_rule=odd),
                               atol=1e-6)
    assert arms.child("dense").counter("attention_arm_total").value == 2


def test_one_block_is_full_attention_and_blocks_of_one_are_causal():
    """B = L: the noisy copy attends to itself in full and to no clean
    key. B = 1: the clean copy is causal attention over the clean copy."""
    length = 128
    q, k, v, _ = _qkv(2 * length, 2, 2, 64, seed=6)
    noisy, clean = slice(0, length), slice(length, None)

    def rows(rule, part):
        out = fa.flash_attention(q, k, v, mask=rule, blocks=(128, 128, 128))
        return out[:, part]
    full = attention_ref(q[:, noisy], k[:, noisy], v[:, noisy])
    np.testing.assert_allclose(rows(BlockDiffusion(length, length), noisy),
                               full, atol=2e-5)
    causal = attention_ref(q[:, clean], k[:, clean], v[:, clean],
                           is_causal=True)
    np.testing.assert_allclose(rows(BlockDiffusion(length, 1), clean),
                               causal, atol=2e-5)
    # and a noisy position of a block of one sees itself and the clean
    # positions before it: the first sees itself alone
    first = rows(BlockDiffusion(length, 1), noisy)[0, 0]
    np.testing.assert_allclose(first, v[0, 0], atol=2e-5)


# -- the kernels lower to the text they had ---------------------------------

# sha256 of the jaxpr (kernel body, index maps and grid included) of one
# attention call's forward kernel and of its backward call
# (``flash_attention_bwd``: delta, the remapped LSE and the one kernel) at
# Ouro's and Kanana-2's shapes under the causal mask and at one shape
# under none, under jax 0.9.0. The forward's stood from ISSUE 33's parent
# (52e487e) to ISSUE 39's (89d7fd5): a rule in place of the boolean and
# one backward kernel in place of two left the forward's Mosaic body as
# it was. ISSUE 39 changed both kernels' grids on purpose (one axis over
# the table of needed pairs, by scalar prefetch) and re-took all six;
# ISSUE 48 changed the backward kernel's crossed tile on purpose (by
# sub-tile) and re-took the two backward ones under the causal mask: the
# forward's three stand, and so does the backward's under no mask (a call
# without a rule lowers to the text it had). ISSUE 49 changed the forward
# kernel on purpose (its LSE leaves as the lane-dense [B*H, 1, Nq] row,
# its running sum is kept a lane and reduced once a query block) and
# re-took the three forward ones: the three backward ones stand untouched,
# which is the proof that ``flash_attention_bwd`` is the parent's. Whoever
# changes a kernel on purpose re-takes them again.
KERNEL_JAXPRS = {
    (2, 4096, 16, 128, 128, True): (
        "e9910fbc08cca2393031cac65ba87490645b40998fb347073ae9ea2c129580d2",
        "39e786fee677d72d0a975336e1768a1b5ac5613574a0ff725ee2b3886d42fbdc"),
    (2, 8192, 32, 192, 128, True): (
        "ae7cb9e1ca1ce78371e40eb18c6f86bbe34a984c0c764b3ca191b2944b90cd43",
        "3876d04eb6fac38efbb46ad4690537af6af6f914e81dcd6fd12cc1d60f7758d2"),
    (2, 2048, 8, 128, 128, False): (
        "2f4f61d2f37f614c9db01eb603d0af834ff0208069d1c4470a4b108bff31c7e2",
        "c00e1b90afce08e0bd1eb984a983e22dcbad8dd7ffdc1ccc6e3d19eda21a783c"),
}


@pytest.mark.parametrize("which", ["forward", "backward"])
@pytest.mark.parametrize("shape", sorted(KERNEL_JAXPRS))
def test_the_parents_kernels_lower_to_the_text_they_had(shape, which):
    if jax.__version__ != "0.9.0":
        pytest.skip("the digests were taken under jax 0.9.0")
    from paddle1_tpu.ops.pallas import flash_attention_bwd as fb
    b, s, h, d, dv, causal = shape
    struct = lambda *dims, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(
        dims, dtype)
    q, k, v = (struct(b, s, h, w) for w in (d, d, dv))
    if which == "forward":
        jaxpr = jax.make_jaxpr(lambda q, k, v: fa.flash_attention(
            q, k, v, causal=causal))(q, k, v)
    else:
        rule = fa.rule_of(causal, None)
        jaxpr = jax.make_jaxpr(
            lambda q, k, v, out, lse, dout: fb.flash_attention_bwd(
                q, k, v, out, lse, dout, d ** -0.5, rule))(
            q, k, v, struct(b, s, h, dv),
            struct(b * h, s, dtype=jnp.float32), struct(b, s, h, dv))
    text = re.sub(r" at 0x[0-9a-f]+", "", str(jaxpr))
    assert text.count("pallas_call") == 1
    assert hashlib.sha256(text.encode()).hexdigest() \
        == KERNEL_JAXPRS[shape][which == "backward"]


def test_a_traced_call_at_the_cells_size_counts_two_kernels_tiles(
        fresh_obs):
    """One forward + backward call at the cell's size ([1, 2 x 8192, 32 /
    4, 128], blocks of 4), traced alone: ``flash_tiles_total`` holds two
    kernels' tiles by the closed form, 240 plain + 48 masked of 1,024 a
    head a kernel (the parent's three kernels counted three), and the
    backward is one key range."""
    rule = BlockDiffusion(8192, 4)
    q, k = (jax.ShapeDtypeStruct((1, 16384, h, 128), jnp.bfloat16)
            for h in (32, 4))
    jaxpr = jax.make_jaxpr(jax.grad(lambda q, k, v: jnp.sum(
        fa.flash_attention(q, k, v, mask=rule).astype(jnp.float32)),
        argnums=(0, 1, 2)))(q, k, k)
    assert str(jaxpr).count("pallas_call") == 2
    want = _closed_form(8192, 4, 512)
    assert (want["plain"], want["masked"]) == (240, 48)
    kinds = process_group("kind")
    assert {kind: kinds.child(kind).counter("flash_tiles_total").value
            for kind in want} == {k: 2 * 32 * n for k, n in want.items()}
    assert obs.registry.process_registry().counter(
        "flash_backward_ranges_total").value == 1


# -- the softmax router -----------------------------------------------------

def test_the_softmax_router_by_hand_on_four_tokens():
    logits = np.log(np.array([[4, 3, 2, 1, .5, .25], [1, 2, 3, 4, 5, 6],
                              [1, 1.1, 1.2, 1.3, 1.4, 1.5],
                              [9, 1, 8, 2, 7, 3]], np.float32))
    x = np.eye(4, dtype=np.float32)                   # token t reads row t
    p = np.exp(logits) / np.exp(logits).sum(-1, keepdims=True)
    weights, chosen = layer_moe.route(jnp.asarray(x), jnp.asarray(logits),
                                      None, 2, 1.0, "softmax")
    assert np.asarray(chosen).tolist() == [[0, 1], [5, 4], [5, 4], [0, 2]]
    np.testing.assert_allclose(np.sum(weights, -1), 1.0, rtol=1e-6)
    for t, picks in enumerate(np.asarray(chosen)):
        np.testing.assert_allclose(weights[t], p[t, picks] / p[t, picks].sum(),
                                   rtol=1e-6)
    # token 0: 4 / 7 and 3 / 7 whatever the other experts' scores
    np.testing.assert_allclose(weights[0], [4 / 7, 3 / 7], rtol=1e-6)
    # float32 whatever the operands arrive in, and the reference's rule
    w3, c3 = layer_moe.route(jnp.asarray(x, jnp.bfloat16),
                             jnp.asarray(logits), None, 2, 1.0, "softmax")
    assert w3.dtype == jnp.float32
    rw, rc = ref.route(jnp.asarray(x), jnp.asarray(logits),
                       {"num_experts_per_tok": 2})
    np.testing.assert_allclose(rw, weights, rtol=1e-6)
    assert np.array_equal(rc, chosen)
    with pytest.raises(ValueError):
        nn.RoutedExperts(16, 8, 4, 2, scoring="tanh")


def _experts(tokens, num_experts, top_k, held, seed=0, hidden=16, width=8,
             lift=0.0):
    """A softmax-rule layer; ``lift`` on the held experts' router columns'
    scores (through a constant input channel) moves picks to or from
    them."""
    paddle.seed(seed)
    layer = nn.RoutedExperts(
        hidden, width, num_experts, top_k, held=held, scoring="softmax",
        weight_attr=ParamAttr(initializer=Normal(std=0.3)))
    x = np.random.default_rng(seed).standard_normal(
        (tokens, hidden)).astype(np.float32)
    if lift:
        x[:, 0] = 1.0
        router = np.array(layer.router.data)
        router[0] = 0.0
        router[0, layer.first:layer.first + layer.held] = lift
        layer.router.data = jnp.asarray(router)
    return layer, x


def _every_expert_over_every_token(layer, x, router, gate_up, down):
    """The held experts' part, plainly: no sort, no capacity."""
    weights, chosen = layer_moe.route(x, router, None, layer.top_k, 1.0,
                                      "softmax")
    y = jnp.zeros_like(x)
    for e in range(layer.held):
        mine = jnp.sum(jnp.where(chosen == layer.first + e, weights, 0.), -1)
        both = x @ gate_up[e]
        width = both.shape[-1] // 2
        y = y + mine[:, None] * (
            (jax.nn.silu(both[:, :width]) * both[:, width:]) @ down[e])
    return y


# (tokens, experts, held, lift of the held experts' scores), top-8
ROUTING = {
    "even": (64, 16, (0, 4), 0.0),
    "a_slice_in_the_middle": (640, 32, (6, 4), 0.0),
    "every_token_picks_every_held_expert": (640, 32, (6, 4), 30.0),
    "no_token_picks_a_held_expert": (640, 32, (6, 4), -30.0),
}


@pytest.mark.parametrize("case", sorted(ROUTING))
def test_no_token_is_dropped_at_top_8_whatever_the_imbalance(case):
    tokens, num_experts, held, lift = ROUTING[case]
    layer, x = _experts(tokens, num_experts, 8, held, lift=lift)
    assert "e_score_correction_bias" not in layer.state_dict()
    capacity = layer_moe.capacity_rows(tokens, 8, held[1], num_experts)
    xt = Tensor(x, stop_gradient=False)
    out = layer(xt)
    got = out.numpy()
    args = (layer.router.data, layer.gate_up_proj.data, layer.down_proj.data)
    np.testing.assert_allclose(
        got, _every_expert_over_every_token(layer, jnp.asarray(x), *args),
        rtol=1e-4, atol=1e-5)
    _, chosen = layer_moe.route(jnp.asarray(x), args[0], None, 8, 1.0,
                                "softmax")
    rows = layer_moe.sort_picks(chosen, held[0], held[1], capacity)[2]
    if lift > 0:        # 4 of every token's 8 picks land here: 2560 picks
        assert capacity < tokens * held[1] and int(np.sum(rows)) == capacity
    if lift < 0:
        assert int(np.sum(rows)) == 0 and not got.any()
    # and the gradients are the plain ones, through the overflow path too
    out.sum().backward()
    want = jax.grad(lambda x, *a: jnp.sum(_every_expert_over_every_token(
        layer, x, *a)), (0, 1, 2, 3))(jnp.asarray(x), *args)
    for g, w in zip((xt.grad, layer.router.grad, layer.gate_up_proj.grad,
                     layer.down_proj.grad), want):
        np.testing.assert_allclose(g.numpy(), w, rtol=3e-4,
                                   atol=2e-5 * max(np.abs(w).max(), 1e-3))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_kernel_that_sums_picks_takes_a_fan_of_eight(dtype):
    """The largest fan its packed picks hold (3 slot bits), which no cell
    ran before this model's top-8."""
    tokens, rows, fan = 32, 40, 8
    hidden = 1024 if dtype == "float32" else 2048
    rng = np.random.default_rng(7)
    o = jnp.asarray(rng.standard_normal((rows, hidden)), dtype)
    where = rng.integers(0, 4 * rows, tokens * fan)
    where = jnp.asarray(np.where(where < rows, where, rows), jnp.int32)
    assert sum_picks.supported(o, where, fan)
    assert not sum_picks.supported(o, jnp.tile(where, 2), 2 * fan)
    padded = jnp.concatenate([o.astype(jnp.float32),
                              jnp.zeros((1, hidden), jnp.float32)])
    want = jnp.sum(padded[where.reshape(tokens, fan)], axis=1).astype(dtype)
    got = sum_picks.sum_picks(o, where, fan)
    np.testing.assert_allclose(got.astype(jnp.float32),
                               want.astype(jnp.float32),
                               rtol=1e-2 if dtype == "bfloat16" else 1e-6,
                               atol=1e-6)


def test_the_mask_row_is_spread_over_the_shares():
    """Half the noisy copy is one row, so the reference's weights send it
    to ``top_k / expert_parallel`` experts of every share in every layer,
    by a margin: no seed's share holds more of its picks than another's.
    Drawn routers alone leave that to the seed."""
    cfg = {**CFG, "num_experts": 4, "expert_parallel": 8,
           "num_experts_per_tok": 8, "initializer_range": 0.05}
    uneven = 0
    for seed in range(6):
        drawn = ref.init_params({**cfg, "mask_route_logit": 0.0},
                                jax.random.key(seed))
        weights = ref.init_params(cfg, jax.random.key(seed))
        row = weights["embed"][cfg["mask_token_id"]]
        np.testing.assert_array_equal(
            row, drawn["embed"][cfg["mask_token_id"]])
        u = row * jax.lax.rsqrt(jnp.mean(row * row) + cfg["rms_norm_eps"])
        for n in range(cfg["num_hidden_layers"]):
            logits = u @ weights[f"router.{n}"]
            top = np.sort(np.argsort(-np.asarray(logits))[:8])
            assert (top // cfg["num_experts"]).tolist() == list(range(8))
            ranked = np.sort(np.asarray(logits))
            assert ranked[-8] - ranked[-9] > 4.0
            # the columns that were not chosen are the drawn ones
            same = np.all(np.asarray(weights[f"router.{n}"])
                          == np.asarray(drawn[f"router.{n}"]), axis=0)
            assert sorted(np.nonzero(~same)[0].tolist()) == top.tolist()
            before = np.argsort(-np.asarray(u @ drawn[f"router.{n}"]))[:8]
            uneven += sorted((before // 4).tolist()) != list(range(8))
    assert uneven > 6  # of 12 drawn routers


# -- the model against the reference ----------------------------------------

def _build(cfg):
    held = cfg["num_experts"]
    return SdarForBlockDiffusion(
        num_experts=held * cfg["expert_parallel"],
        held_experts=(cfg["expert_rank"] * held, held),
        **{k: cfg[k] for k in (
            "vocab_size", "hidden_size", "num_hidden_layers",
            "num_attention_heads", "num_key_value_heads", "head_dim",
            "moe_intermediate_size", "num_experts_per_tok", "block_length",
            "mask_token_id", "noise_eps", "rope_theta", "rms_norm_eps",
            "initializer_range")})


def _batch(batch=2, seq=16, seed=0):
    rng = np.random.default_rng(seed)
    return {"ids": rng.integers(0, CFG["mask_token_id"],
                                (batch, seq)).astype(np.int32),
            "level": rng.random((batch, seq // CFG["block_length"]),
                                dtype=np.float32),
            "draw": rng.random((batch, seq), dtype=np.float32)}


def _loss(model, batch):
    return SdarBlockDiffusionCriterion()(*model(
        Tensor(batch["ids"]), Tensor(batch["level"]), Tensor(batch["draw"])))


def _two_rows_of_losses_and_weights(model, weights, batch):
    token_losses, weights_ = model(*(Tensor(batch[k])
                                     for k in ("ids", "level", "draw")))
    assert token_losses.shape == [2, 16] and weights_.shape == [2, 16]


def _the_steps_own_counters(engine):
    # 2 x 128 positions, one 128 x 128 tile a quadrant: noisy-noisy,
    # noisy-clean and clean-clean crossed, clean-noisy skipped; 2 rows x 4
    # heads a traced kernel call (the two kernels, and the forward once
    # more inside the recomputed segment, where its kept outputs spare it)
    kinds = process_group("kind")
    tiles = {kind: kinds.child(kind).counter("flash_tiles_total").value
             for kind in ("plain", "masked", "skipped")}
    assert tiles["plain"] == 0 and tiles["skipped"] % 8 == 0
    assert tiles["masked"] == 3 * tiles["skipped"] >= 3 * 24


def _the_steps_own_scopes(engine, named):
    assert any(s.endswith("SdarForBlockDiffusion/block_noise")
               or "/SdarForBlockDiffusion/block_noise/" in s for s in named)
    for i in range(CFG["num_hidden_layers"]):
        at = f"/layers/recompute/{i}/self_attn/"
        for op in ("q_proj/linear", "k_proj/linear", "v_proj/linear",
                   "q_norm/rms_norm", "k_norm/rms_norm", "rotary_embedding",
                   "scaled_dot_product_attention", "o_proj/linear"):
            assert any(at + op in s for s in named), (i, op)
        at = f"/layers/recompute/{i}/mlp/moe/"
        for op in ("moe_router", "moe_dispatch", "routed_experts",
                   "moe_combine"):
            assert any(at + op in s for s in named), (i, op)
    assert any("/diffusion_loss" in s for s in named)


# the shared cases' model (decoder_cases.py). The reference in blocks: 16
# of a doubled row's 32 positions through a layer at a time; one key/value
# head and 8 of those queries a block of scores; two experts a group; a row
# a block. The share test: 2 experts of 16 each, top-8, the softmax rule.
DECODER = Decoder(
    cfg=CFG, ref=ref, program=program, build=_build, criterion=_loss,
    batch=_batch, follows_also=_two_rows_of_losses_and_weights,
    blocks_seq=16, scans=5, blocks=(
        (ref, "ROW_BLOCK_POSITIONS", 16),
        (ref, "SCORE_BLOCK_BYTES", 4 * 32 * 2 * 8),
        (ref, "EXPERTS_BLOCK_BYTES", 4 * 16 * 128 * 2),
        (blocks_of, "BLOCK_TOKENS", 16)),
    step_counters=_the_steps_own_counters, step_scopes=_the_steps_own_scopes,
    shares={"experts": _experts, "total": 16, "top_k": 8})


def test_the_noisy_copy_and_the_weights_by_hand(reference):
    model = reference.model()
    ids = np.arange(8, dtype=np.int32)[None] + 10
    level = np.array([[0.0, 1.0]], np.float32)      # t = 0.001 and 1
    draw = np.array([[0.5, 0.0005, 0.5, 0.5, 0.9, 0.0, 0.5, 0.999]],
                    np.float32)
    z, at, labels, weights = (np.asarray(x.numpy()) for x in model.noise(
        Tensor(ids), Tensor(level), Tensor(draw)))
    mask = CFG["mask_token_id"]
    assert z.tolist() == [[10, mask, 12, 13, mask, mask, mask, mask]
                          + list(range(10, 18))]
    assert at.tolist() == list(range(8)) * 2
    assert labels.tolist() == [[-100, 11, -100, -100, 14, 15, 16, 17]]
    np.testing.assert_allclose(weights, [[0, 1000, 0, 0, 1, 1, 1, 1]],
                               rtol=1e-5)
