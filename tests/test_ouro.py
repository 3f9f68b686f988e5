"""Ouro, the looped language model (ISSUE 27): rotary positions, the
sandwich-norm block and the loop over shared weights against the plain
reference (``benchmarks/reference/ouro_2p6b.py``), the exit distribution and
its loss, recomputation, and the names a traced step carries. CPU, tiny
sizes, seeded weights."""

import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.ad_checkpoint import print_saved_residuals

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import paddle1_tpu as paddle  # noqa: E402
from benchmarks.programs import ouro_2p6b as program  # noqa: E402
from benchmarks.reference import ouro_2p6b as ref  # noqa: E402
from decoder_cases import (  # noqa: E402,F401
    NM, Decoder, Reference, decoder, eager_kernel_run, eager_run, fresh_obs,
    test_recomputation_changes_neither_loss_nor_gradients)
from op_test import rotary_by_halves  # noqa: E402
from paddle1_tpu.autograd.engine import no_grad  # noqa: E402
from paddle1_tpu.core.flags import flags_guard  # noqa: E402
from paddle1_tpu.core.tensor import Tensor  # noqa: E402
from paddle1_tpu.distributed import ParallelEngine, build_mesh  # noqa: E402
from paddle1_tpu.nn import functional as F  # noqa: E402
from paddle1_tpu.obs import costmodel  # noqa: E402
from paddle1_tpu.text.models import (OuroForPretraining,  # noqa: E402
                                     OuroPretrainingCriterion)

CFG = {"vocab_size": 96, "hidden_size": 32, "num_hidden_layers": 2,
       "num_attention_heads": 2, "head_dim": 16, "intermediate_size": 48,
       "total_ut_steps": 4, "rope_theta": 1e4, "rms_norm_eps": 1e-6,
       "initializer_range": 0.2, "exit_entropy_beta": 0.1}


def _draw(cfg):
    weights = jax.device_get(jax.jit(lambda key: ref.init_params(cfg, key))(
        jax.random.key(3)))
    # the norm scales and the gate's bias away from their 1 and 0
    rng = np.random.default_rng(0)
    for k in sorted(weights):
        if k[0] == "n" or k == "gate_b":
            weights[k] = (weights[k] + 0.3 * rng.standard_normal(
                weights[k].shape)).astype(np.float32)
    return weights


def _ids(rows=2, seq=12, seed=1):
    return np.random.default_rng(seed).integers(
        0, CFG["vocab_size"], (rows, seq)).astype(np.int32)


def _loss(model, ids, beta=0.1):
    t = Tensor(ids)
    labels = model.next_token_labels(t)
    return OuroPretrainingCriterion(beta)(*model(t, labels), labels)


def _nothing_is_recomputed_in_evaluation(model, batch, loss):
    model.eval()
    assert float(_loss(model, batch["ids"])) == pytest.approx(loss, rel=1e-6)


# the shared cases' model (decoder_cases.py): this file keeps both sides
# of the kernels' recomputation case op by op on the eager tape
DECODER = Decoder(
    cfg=CFG, ref=ref, program=program, draw=_draw, dense_seq=12,
    build=lambda cfg: OuroForPretraining(
        **{k: cfg[k] for k in cfg if k != "exit_entropy_beta"}),
    criterion=lambda model, batch: _loss(model, batch["ids"]),
    batch=lambda seq: {"ids": _ids(seq=seq)},
    recomputed_also=_nothing_is_recomputed_in_evaluation,
    kernel_sides_on_the_tape=True,
    optimizer=lambda parameters: paddle.optimizer.AdamW(
        learning_rate=1e-3, parameters=parameters))


@functools.cache
def _reference(**over):
    """The reference's side with ``over`` laid over ``CFG``: a
    configuration's weights drawn once a file."""
    return Reference(DECODER, {**CFG, **over})


@pytest.fixture(scope="module")
def reference():
    """The shared cases' (in ``decoder_cases.reference``'s place) and
    ``_model()``'s: one draw."""
    return _reference()


def _model(**over):
    """(a fresh Layer, the reference's weights it holds, the configuration)
    with ``over`` laid over ``CFG``."""
    r = _reference(**over)
    return r.model(), r.weights, r.cfg


@pytest.mark.parametrize("positions", [None, "row", "batch"])
def test_rotary_embedding_is_a_complex_rotation(positions):
    """Channel i of the first half and channel i of the second are the
    real and imaginary part of one number, turned by exp(1j * angle)."""
    rng = np.random.default_rng(2)
    x = rng.standard_normal((2, 7, 3, 8)).astype(np.float32)
    at = {None: np.arange(7), "row": np.arange(7)[::-1].copy(),
          "batch": rng.integers(0, 50, (2, 7))}[positions]
    got = F.rotary_embedding(
        Tensor(x), theta=100.0,
        positions=None if positions is None else Tensor(at.astype(np.int32)))
    z = x[..., :4] + 1j * x[..., 4:]
    angle = at[..., None, None] * 100.0 ** (-np.arange(4) / 4.0)
    want = z * np.exp(1j * angle)
    np.testing.assert_allclose(
        got.numpy(), np.concatenate([want.real, want.imag], -1), atol=2e-6)
    # a rotation: norms stay, and q.k depends on the distance alone
    np.testing.assert_allclose(np.linalg.norm(got.numpy(), axis=-1),
                               np.linalg.norm(x, axis=-1), rtol=1e-5)


def test_rotary_embedding_keeps_the_dtype_and_differentiates():
    x = Tensor(jnp.ones((1, 4, 1, 8), jnp.bfloat16), stop_gradient=False)
    y = F.rotary_embedding(x, theta=1e6)
    assert y.dtype == x.dtype
    y.sum().backward()
    assert x.grad.shape == x.shape


@pytest.mark.parametrize("positions", [None, "row", "batch"])
@pytest.mark.parametrize("d", [64, 128])
@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("interleaved", [False, True],
                         ids=["halves", "pairs"])
def test_rotary_in_one_pass_is_the_slices_and_concatenation_bit_for_bit(
        interleaved, dtype, d, positions):
    """``x * C + (x @ P) * S`` and its hand-written backward make the
    float32 products and the one sum the slice-and-concatenate form and
    its autodiff make, so every bit of the result and of the gradient is
    the same, at both pairings, dtypes, widths and kinds of positions."""
    rng = np.random.default_rng(d + interleaved)
    x = jnp.asarray(rng.standard_normal((2, 9, 3, d)), dtype)
    g = jnp.asarray(rng.standard_normal((2, 9, 3, d)), dtype)
    at = {None: np.arange(9), "row": rng.integers(0, 5000, 9),
          "batch": rng.integers(0, 5000, (2, 9))}[positions].astype(np.int32)

    def op(x):
        return F.rotary_embedding(
            Tensor(x), 1e4, None if positions is None else Tensor(at),
            interleaved=interleaved).data

    def plain(x):
        return rotary_by_halves(x, 1e4, jnp.asarray(at), interleaved)

    # op by op, as the tape runs it: inside one compiled program the
    # CPU's code generator may contract a product and a sum into a fused
    # multiply-add, in either form, and that is not the op's arithmetic
    with no_grad():
        got, pull = jax.vjp(op, x)
    want, pull_plain = jax.vjp(plain, x)
    (dx,), (dx_plain,) = pull(g), pull_plain(g)
    assert got.dtype == want.dtype == dx.dtype == dx_plain.dtype == x.dtype
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))
    np.testing.assert_array_equal(np.asarray(dx, np.float32),
                                  np.asarray(dx_plain, np.float32))


# Laguna-XS.2's full-attention layers (ISSUE 47): theta, YaRN's factor over
# the original context, beta_fast, beta_slow, the published factor on cos
# and sin, over half a head of 128
YARN = dict(theta=500000.0, factor=64.0, original=4096, beta_fast=64.0,
            beta_slow=1.0, attention_factor=1.4158883083359672, span=64)


def _yarn_by_the_formulas(span, theta, factor, original, beta_fast,
                          beta_slow, **_):
    """(lo, hi, f) written out in numpy float64 from ISSUE 47's
    equations."""
    def c(r):
        return span * np.log(original / (2 * np.pi * r)) \
            / (2 * np.log(theta))
    lo = max(int(np.floor(c(beta_fast))), 0)
    hi = min(int(np.ceil(c(beta_slow))), span - 1)
    i = np.arange(span // 2)
    b = theta ** (-i / (span // 2))
    m = 1 - np.clip((i - lo) / (hi - lo), 0, 1)
    return lo, hi, (1 - m) * b / factor + m * b


def test_yarn_frequencies_at_lagunas_constants_are_pinned():
    lo, hi, want = _yarn_by_the_formulas(**YARN)
    assert (lo, hi) == (5, 16)
    got = F.yarn_frequencies(64, 500000.0, 64.0, 4096, beta_fast=64.0,
                             beta_slow=1.0)
    np.testing.assert_allclose(got, want, rtol=1e-12)
    # pairs 0-5 keep their frequency, pairs 16-31 are slowed 64 times,
    # and in between the blend is linear in the pair's index
    base = 500000.0 ** (-np.arange(32) / 32)
    np.testing.assert_allclose(got[:6], base[:6], rtol=1e-12)
    np.testing.assert_allclose(got[16:], base[16:] / 64, rtol=1e-12)
    assert got[0] == 1.0
    assert got[31] == pytest.approx(500000.0 ** (-31 / 32) / 64, rel=1e-12)
    assert got[10] == pytest.approx(
        base[10] * ((1 - 5 / 11) + (5 / 11) / 64), rel=1e-12)
    assert np.all(np.diff(got) < 0)
    # the factor on cos and sin that the configuration publishes is YaRN's
    # own for its factor: 0.1 ln 64 + 1
    assert YARN["attention_factor"] == pytest.approx(
        0.1 * np.log(64.0) + 1, rel=1e-15)
    # no scaling is the plain table, whatever the ramp
    np.testing.assert_allclose(
        F.yarn_frequencies(64, 500000.0, 1.0, 4096), base, rtol=1e-12)


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("interleaved", [False, True],
                         ids=["halves", "pairs"])
def test_rotary_over_part_of_a_head_from_a_table(interleaved, dtype):
    """Span 64 of a head of 128 under YaRN's table and its factor (ISSUE
    47): against the formulas written out in numpy; bit for bit the plain
    form's result, and the hand-written backward bit for bit autodiff of
    the plain form; the 64 channels behind the span pass untouched,
    forward and backward."""
    rng = np.random.default_rng(47 + interleaved)
    x = jnp.asarray(rng.standard_normal((2, 9, 3, 128)), dtype)
    g = jnp.asarray(rng.standard_normal((2, 9, 3, 128)), dtype)
    at = rng.integers(0, 20000, (2, 9)).astype(np.int32)
    table = F.yarn_frequencies(64, 500000.0, 64.0, 4096, 64.0, 1.0)
    scale = YARN["attention_factor"]

    def op(x):
        return F.rotary_embedding(Tensor(x), positions=Tensor(at),
                                  interleaved=interleaved,
                                  frequencies=table, scale=scale).data

    def plain(x):
        return rotary_by_halves(x, None, jnp.asarray(at), interleaved,
                                table, scale)
    with no_grad():
        got, pull = jax.vjp(op, x)
    want, pull_plain = jax.vjp(plain, x)
    (dx,), (dx_plain,) = pull(g), pull_plain(g)
    assert got.dtype == dx.dtype == x.dtype
    for a, b in ((got, want), (dx, dx_plain)):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))
    # what passes, passes exactly
    np.testing.assert_array_equal(np.asarray(got[..., 64:], np.float32),
                                  np.asarray(x[..., 64:], np.float32))
    np.testing.assert_array_equal(np.asarray(dx[..., 64:], np.float32),
                                  np.asarray(g[..., 64:], np.float32))
    # the formulas, in float64: pair i turns by p * f_i, scaled
    _, _, f = _yarn_by_the_formulas(**YARN)
    xs = np.asarray(x, np.float64)
    first, second = ((xs[..., 0:64:2], xs[..., 1:64:2]) if interleaved
                     else (xs[..., :32], xs[..., 32:64]))
    turn = scale * np.exp(1j * at[..., None, None] * f)
    z = (first + 1j * second) * turn
    out = np.asarray(got, np.float64)
    mine = ((out[..., 0:64:2], out[..., 1:64:2]) if interleaved
            else (out[..., :32], out[..., 32:64]))
    # float32 angles of up to 20,000 radians: 1e-3 of a turn
    tol = 2e-2 if dtype == "bfloat16" else 3e-3
    np.testing.assert_allclose(mine[0], z.real, atol=tol * np.abs(z).max())
    np.testing.assert_allclose(mine[1], z.imag, atol=tol * np.abs(z).max())


def test_rotary_refuses_a_table_wider_than_the_head():
    with pytest.raises(ValueError, match="frequencies"):
        F.rotary_embedding(Tensor(jnp.ones((1, 2, 1, 8))),
                           frequencies=[1.0] * 5)


def test_swiglu_and_the_gated_feed_forward():
    rng = np.random.default_rng(4)
    g, u = rng.standard_normal((2, 3, 5)).astype(np.float32), \
        rng.standard_normal((2, 3, 5)).astype(np.float32)
    np.testing.assert_allclose(F.swiglu(Tensor(g), Tensor(u)).numpy(),
                               g / (1 + np.exp(-g)) * u, rtol=1e-6)
    ffn = paddle.nn.GatedFeedForward(4, 6)
    assert sorted(ffn.state_dict()) == [
        "down_proj.weight", "gate_proj.weight", "up_proj.weight"]
    x = rng.standard_normal((2, 4)).astype(np.float32)
    w = {k: v.numpy() for k, v in ffn.state_dict().items()}
    a = x @ w["gate_proj.weight"]
    np.testing.assert_allclose(
        ffn(Tensor(x)).numpy(),
        (a / (1 + np.exp(-a)) * (x @ w["up_proj.weight"]))
        @ w["down_proj.weight"], rtol=1e-5, atol=1e-6)


def test_the_block_follows_the_reference_layer_by_layer():
    model, weights, cfg = _model()
    x = np.random.default_rng(5).standard_normal((2, 12, 32)).astype(
        np.float32)
    ours, theirs = Tensor(x), jnp.asarray(x)
    for i, block in enumerate(model.layers.blocks):
        ours = block(ours)
        theirs = ref.layer(theirs, ref.layer_weights(weights, i), cfg, NM)
        np.testing.assert_allclose(ours.numpy(), theirs, rtol=2e-5,
                                   atol=2e-5)
    # a change of a later position leaves an earlier one alone: causal
    moved = x.copy()
    moved[:, 8:] += 1.0
    out = model.layers.blocks[0](Tensor(moved)).numpy()
    base = model.layers.blocks[0](Tensor(x)).numpy()
    np.testing.assert_array_equal(out[:, :8], base[:, :8])
    assert np.abs(out[:, 8:] - base[:, 8:]).max() > 0.1


@pytest.mark.parametrize("steps", [1, 2, 4])
def test_the_model_follows_the_reference(steps):
    model, weights, cfg = _model(total_ut_steps=steps)
    ids = _ids()
    want, _ = ref.loss({k: jnp.asarray(v) for k, v in weights.items()},
                       {"ids": jnp.asarray(ids)}, cfg, NM)
    assert float(_loss(model, ids)) == pytest.approx(float(want), rel=2e-6)
    logits, gates = model(Tensor(ids))
    assert logits.shape == [steps, 2, 12, 96] and gates.shape == [steps, 2, 12]


def test_one_loop_step_is_one_pass():
    model, _, _ = _model(total_ut_steps=1)
    ids = _ids()
    with no_grad():
        h = model.layers(model.embed_tokens(Tensor(ids)))
        logits, gate = model.exit_head(h)
        got, got_gate = model(Tensor(ids))
    np.testing.assert_allclose(got.numpy()[0], logits.numpy(), rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(got_gate.numpy()[0], gate.numpy(), rtol=1e-5,
                               atol=1e-5)


def test_a_shared_weights_gradient_is_the_sum_over_its_uses(eager_run):
    """At T = 4 the gradient of each weight of the stack and of the head
    (the file's one eager run) equals the sum of the four per-use
    gradients of an unrolled copy that has separate weights for every
    loop step."""
    model, ids, loss = eager_run.model, eager_run.batch["ids"], eager_run.loss
    shared = {"layers." + k: v for k, v in
              model.layers.functional_state().items()}
    shared.update({"exit_head." + k: v for k, v in
                   model.exit_head.functional_state().items()})
    crit = OuroPretrainingCriterion(0.1)

    def unrolled(copies):
        t = Tensor(ids)
        labels = model.next_token_labels(t)
        h, losses, gates = model.embed_tokens(t), [], []
        for own in copies:
            with model.load_functional_state(own):
                h = model.layers(h)
                l_t, g_t = model.exit_head(h, labels)
            losses.append(l_t.data)
            gates.append(g_t.data)
        return crit(Tensor(jnp.stack(losses)), Tensor(jnp.stack(gates)),
                    labels).data

    with no_grad():
        value, grads = jax.jit(jax.value_and_grad(unrolled))(
            [dict(shared) for _ in range(4)])
    assert float(value) == pytest.approx(loss, rel=1e-6)
    for name in shared:
        per_use = [np.asarray(g[name]) for g in grads]
        total = sum(per_use)
        np.testing.assert_allclose(eager_run.grads[name], total,
                                   rtol=2e-4, atol=1e-5 * np.abs(total).max())
        # and every use takes part, but the last use of the gate: the
        # last exit takes what is left, whatever its gate says
        used = [bool(np.abs(g).max() > 0) for g in per_use]
        assert used == [True, True, True,
                        not name.startswith("exit_head.gate")], name


def test_exit_distribution_and_loss_by_hand():
    """Two tokens, four exits: p sums to 1 and the loss is the hand
    formula, with a token that has no label left out of the mean."""
    gates = np.array([[[0.3, -1.2, 0.0]], [[-0.5, 2.0, 0.0]],
                      [[1.5, 0.1, 0.0]], [[0.7, -0.4, 0.0]]], np.float32)
    losses = np.array([[[4.0, 1.0, 9.0]], [[3.0, 2.0, 9.0]],
                       [[2.5, 2.5, 9.0]], [[2.0, 3.5, 9.0]]], np.float32)
    labels = np.array([[5, 7, -100]], np.int32)
    lam = 1 / (1 + np.exp(-gates.astype(np.float64)))
    p = np.stack([lam[0], lam[1] * (1 - lam[0]),
                  lam[2] * (1 - lam[0]) * (1 - lam[1]),
                  (1 - lam[0]) * (1 - lam[1]) * (1 - lam[2])])
    np.testing.assert_allclose(p.sum(0), 1.0, rtol=1e-12)
    per_token = (p * losses).sum(0) + 0.1 * (p * np.log(p)).sum(0)
    want = per_token[0, :2].mean()
    got = OuroPretrainingCriterion(0.1)(Tensor(losses), Tensor(gates),
                                        Tensor(labels))
    assert float(got) == pytest.approx(want, rel=1e-6)
    ours = ref.exit_distribution([jnp.asarray(lam[t], jnp.float32)
                                  for t in range(4)])
    np.testing.assert_allclose(np.stack(ours), p, rtol=1e-5)
    np.testing.assert_allclose(np.sum(ours, 0), 1.0, rtol=1e-6)
    # one exit: it takes everything, whatever the gate says
    one = OuroPretrainingCriterion(0.1)(Tensor(losses[:1]), Tensor(gates[:1]),
                                        Tensor(labels))
    assert float(one) == pytest.approx(2.5, rel=1e-6)


def test_the_exit_arithmetic_is_float32_under_autocast():
    model, _, _ = _model()
    h = Tensor(jnp.ones((1, 4, 32), jnp.bfloat16))
    labels = Tensor(np.array([[1, 2, 3, -100]], np.int32))
    half = {k: v.astype(jnp.bfloat16)
            for k, v in model.exit_head.functional_state().items()}
    with no_grad(), model.exit_head.load_functional_state(half):
        token_loss, gate = model.exit_head(h, labels)
    assert token_loss.data.dtype == jnp.float32
    assert gate.data.dtype == jnp.float32
    assert float(token_loss.numpy()[0, 3]) == 0.0
    out = OuroPretrainingCriterion(0.1)(
        Tensor(jnp.ones((4, 1, 4), jnp.bfloat16)),
        Tensor(jnp.zeros((4, 1, 4), jnp.bfloat16)), labels)
    assert out.data.dtype == jnp.float32


def _kernels(jaxpr):
    """The names of the ``pallas_call``s of a jaxpr and of every jaxpr
    inside it, one a call site, a kernel's own body left out."""
    names = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            names.append(eqn.params["name"])
            continue
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else (v,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    names += _kernels(inner)
    return names


# (the segment, the flag ``flash_attention``) -> forward kernels in the
# segment's gradient, and what it keeps beside its inputs: the kernel's
# ``out`` and ``lse`` (ISSUE 30) and the feed-forward's output, which the
# sandwich's last norm reads (ISSUE 37)
FFN_OUT = "f32[2,128,32] output of reduce_precision"
KEPT = {
    "kernel_attention": ("block", "always", 1,
                         ["f32[4,128,16]", "f32[4,128] named "
                          "'flash_attention_lse'", FFN_OUT]),
    "dense_attention": ("block", "never", 0, [FFN_OUT]),
    "exit_head": ("exit_head", "always", 0, []),
}


@pytest.mark.parametrize("case", sorted(KEPT))
def test_a_recomputed_segment_keeps_the_kernels_outputs_and_no_more(
        case, capsys):
    """``recompute`` keeps a segment's inputs and what carries a name
    inside: what the attention kernel made (``out``, ``lse``), so that
    the backward pass does not run the forward kernel again, and the
    feed-forward's output; a segment that took dense attention keeps the
    last alone, one with neither (the exit head) its inputs alone."""
    from paddle1_tpu.distributed.fleet.utils.recompute import recompute
    segment, flag, forward_kernels, kept = KEPT[case]
    model, _, _ = _model()
    layer = (model.layers.blocks[0] if segment == "block"
             else model.exit_head)
    state = {k: v.data for k, v in layer.state_dict().items()}
    h = jnp.asarray(np.random.default_rng(2).standard_normal(
        (2, 128, 32)), jnp.float32)
    inputs = (h,) if segment == "block" else (
        h, jnp.asarray(_ids(seq=128)))

    def loss(state, *inputs):
        with no_grad(), layer.load_functional_state(state):
            out = recompute(layer, *map(Tensor, inputs))
        return sum(jnp.sum(o.data) for o in
                   (out if isinstance(out, tuple) else (out,)))

    with flags_guard(flash_attention=flag):
        grad = jax.make_jaxpr(jax.grad(loss))(state, *inputs).jaxpr
        print_saved_residuals(loss, state, *inputs)
    # the one backward kernel keeps the name dK/dV had (PERF.md section 7)
    assert sorted(_kernels(grad)) == forward_kernels * [
        "p1t_flash_attention_bwd_dkv", "p1t_flash_attention_fwd"]
    lines = capsys.readouterr().out.strip().splitlines()
    beside = [l for l in lines if " from the argument " not in l]
    assert len(lines) - len(beside) == len(state) + 1   # + the hidden input
    assert len(beside) == len(kept), beside
    for line, what in zip(beside, kept):
        assert line.startswith(what), line
        assert ("recompute_keeps.py" if what == FFN_OUT
                else "flash_attention.py") in line


# -- through the engine -----------------------------------------------------

def test_the_engine_switches_recomputation_on_and_the_step_is_the_same():
    """Two loop steps: the switch is what the case is about, and each
    side's step is compiled twice (ISSUE 46)."""
    batch = {"ids": _ids()}
    seen = {}
    for remat in (False, True):
        engine = _reference(total_ut_steps=2).engine(recompute=remat)
        assert engine.model.layers.enable_recompute is remat
        loss = float(engine.step(engine.shard_batch(batch), lr=1e-3))
        first = {k: np.asarray(v["moment1"])
                 for k, v in engine.opt_state[0].items()}
        seen[remat] = (loss, first, engine.compiled_step_text())
    assert seen[True][0] == pytest.approx(seen[False][0], rel=1e-6)
    for k, g in seen[False][1].items():
        np.testing.assert_allclose(seen[True][1][k], g, rtol=1e-4,
                                   atol=1e-6 * np.abs(g).max())
    assert "rematted_computation" in seen[True][2]
    assert "rematted_computation" not in seen[False][2]


def test_the_loop_and_the_heads_have_scopes_of_their_own(reference, fresh_obs):
    """Loop step t's copy of the stack under ``ut_step/<t>``; the heads,
    the gate and the exit loss under ``exit_head``; nothing under both."""
    engine = reference.engine(amp="bfloat16")
    float(engine.step(engine.shard_batch({"ids": _ids()}), lr=1e-3))
    scopes = costmodel.step_op_scopes()
    named = [s for s in scopes.values() if "jvp(loss)" in s]
    stack = [s for s in named if "/ut_step/" in s]
    heads = [s for s in named if "/exit_head/" in s]
    assert stack and heads
    assert not [s for s in stack if "/exit_head/" in s]
    assert not [s for s in heads if "/ut_step/" in s]
    for t in range(CFG["total_ut_steps"]):
        for i in range(CFG["num_hidden_layers"]):
            for op in ("rms_norm", "rotary_embedding", "linear", "swiglu",
                       "scaled_dot_product_attention"):
                assert any(f"/ut_step/{t}/layers/recompute/{i}/" in s
                           and f"/{op}" in s for s in stack), (t, i, op)
        assert any(f"/ut_step/{t}/layers/norm/rms_norm" in s for s in stack)
    # no while loop: the trace reduction would count its time and its
    # body's both (PERF.md, PR 27)
    assert not [s for s in named if "/while/" in s]
    for op in ("exit_cross_entropy", "exit_gate", "exit_loss"):
        assert any(s.split(";")[0].endswith(op) or f"/{op}/" in s
                   for s in heads), op
    assert any("OuroPretrainingCriterion/exit_head/exit_loss" in s
               for s in heads)
    # forward work run again in the backward pass says so
    again = [s for s in named if "/rematted_computation/" in s]
    assert again and all("transpose(jvp(loss))" in s for s in again)
    assert {costmodel.region_of(s) for s in stack} == {"forward", "backward"}


# -- ParallelEngine(recompute=True) finds blocks by what they declare ------

class _Blocks(paddle.nn.Layer):
    """A user's own block stack that knows how to recompute."""

    def __init__(self):
        super().__init__()
        self.blocks = paddle.nn.LayerList(
            [paddle.nn.Sequential(paddle.nn.Linear(8, 8), paddle.nn.Tanh())
             for _ in range(3)])
        self.enable_recompute = False

    def forward(self, x):
        from paddle1_tpu.distributed.fleet.utils.recompute import recompute
        for block in self.blocks:
            x = recompute(block, x) if self.enable_recompute else block(x)
        return x


def _flip(model):
    opt = paddle.optimizer.SGD(learning_rate=0.1,
                               parameters=model.parameters())
    return ParallelEngine(
        model, opt, lambda m, b: (m(Tensor(b["x"])) ** 2).mean(),
        mesh=build_mesh(dp=1, devices=jax.devices()[:1]), recompute=True)


@pytest.mark.parametrize("kind", ["user_stack", "transformer_encoder",
                                  "ouro"])
def test_recompute_flips_whatever_declares_the_switch(kind):
    if kind == "user_stack":
        model = paddle.nn.Sequential(paddle.nn.Linear(8, 8), _Blocks())
        stack = model[1]
    elif kind == "transformer_encoder":
        model = paddle.nn.TransformerEncoder(
            paddle.nn.TransformerEncoderLayer(8, 2, 16, dropout=0.0), 2)
        stack = model
    else:
        model = _model()[0]
        stack = model.layers
    assert stack.enable_recompute is False
    import warnings
    with warnings.catch_warnings():
        warnings.simplefilter("error", UserWarning)
        engine = _flip(model)
    assert stack.enable_recompute is True
    if kind == "user_stack":
        x = np.ones((4, 8), np.float32)
        assert np.isfinite(float(engine.step({"x": x})))
        assert "rematted_computation" in engine.compiled_step_text()


def test_recompute_warns_where_nothing_declares_the_switch():
    model = paddle.nn.Sequential(paddle.nn.Linear(8, 8), paddle.nn.ReLU())
    with pytest.warns(UserWarning, match="no recompute-capable blocks"):
        _flip(model)
