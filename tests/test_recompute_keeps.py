"""What a recomputed segment keeps by name (ISSUE 37,
``core/recompute_keeps.py``): the values the forward pass made, to the
bit; one product and every sort fewer in the backward pass; counted by
shape where a segment is traced; free where nothing reads them."""

import collections

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.ad_checkpoint import print_saved_residuals

import paddle1_tpu as paddle
from paddle1_tpu import obs
from paddle1_tpu.autograd.engine import no_grad
from paddle1_tpu.core import recompute_keeps
from paddle1_tpu.core.flags import flags_guard
from paddle1_tpu.core.tensor import Tensor
from paddle1_tpu.distributed.fleet.utils.recompute import recompute
from paddle1_tpu.nn import TransformerEncoderLayer
from paddle1_tpu.nn.functional import norm as fnorm
from paddle1_tpu.nn.layer_moe import capacity_rows
from paddle1_tpu.obs.registry import process_group
from paddle1_tpu.text.models.kanana2 import Kanana2DecoderLayer
from paddle1_tpu.text.models.ouro import OuroDecoderLayer
from paddle1_tpu.text.models.sdar import SdarDecoderLayer

B, S, HIDDEN, HEADS, D = 2, 256, 32, 2, 16
TOKENS, F32 = B * S, 4
EXPERTS = dict(expert_width=16, num_experts=8, top_k=3, held=(0, 4))
KERNEL = ("flash_attention_out", "flash_attention_lse")
# what the issue's parent kept: the attention kernel's two outputs
PARENT = jax.checkpoint_policies.save_only_these_names(*KERNEL)


def _ouro():
    return OuroDecoderLayer(HIDDEN, HEADS, D, 48, rope_theta=1e4), ()


def _kanana2(experts=True):
    attention = dict(num_heads=HEADS, qk_nope_head_dim=16, qk_rope_head_dim=8,
                     v_head_dim=D, kv_lora_rank=24, rope_theta=1e4)
    return Kanana2DecoderLayer(
        HIDDEN, attention, 48, dict(EXPERTS, shared_width=32,
                                    routed_scaling_factor=2.448)
        if experts else None), ()


def _sdar():
    attention = dict(num_heads=4, num_kv_heads=2, head_dim=D, block_length=4,
                     rope_theta=1e4)
    return (SdarDecoderLayer(HIDDEN, attention, EXPERTS),
            (jnp.tile(jnp.arange(S // 2, dtype=jnp.int32), 2),))


def _bert():
    return TransformerEncoderLayer(HIDDEN, HEADS, 64, dropout=0.0), ()


LAYERS = {"ouro": _ouro, "kanana2": _kanana2, "sdar": _sdar,
          "kanana2_dense": lambda: _kanana2(False), "bert": _bert}
PICKS = TOKENS * EXPERTS["top_k"]
_MOE = {"routed_scores": PICKS * F32, "routed_chosen": PICKS * 4,
        "routed_order": 4 * capacity_rows(TOKENS, 3, 4, 8),
        "routed_where": PICKS * 4, "routed_sizes": 4 * 4, "routed_overflow": 4,
        "routed_row_weights": F32 * capacity_rows(TOKENS, 3, 4, 8)}
_STREAM = TOKENS * HIDDEN * F32
# name -> bytes a segment of each layer is given, from the shapes
CLOSED_FORM = {
    "ouro": {"flash_attention_out": TOKENS * HEADS * D * F32,
             "flash_attention_lse": TOKENS * HEADS * 4,
             "gated_ffn_out": _STREAM},
    "kanana2": {"flash_attention_out": TOKENS * HEADS * D * F32,
                "flash_attention_lse": TOKENS * HEADS * 4,
                "stream_after_attn": _STREAM, "gated_ffn_out": _STREAM,
                **_MOE},
    "sdar": {"flash_attention_out": TOKENS * 4 * D * F32,
             "flash_attention_lse": TOKENS * 4 * 4,
             "stream_after_attn": _STREAM, **_MOE},
    "kanana2_dense": {"flash_attention_out": TOKENS * HEADS * D * F32,
                      "flash_attention_lse": TOKENS * HEADS * 4,
                      "stream_after_attn": _STREAM,
                      "gated_ffn_out": _STREAM},
    "bert": {},
}
# the forward product that a kept value spares the backward pass, as
# (contraction width, output width), and whether the layer routes
SPARED = {"ouro": ((48, HIDDEN), False),
          "kanana2": ((HEADS * D, HIDDEN), True),
          "sdar": ((4 * D, HIDDEN), True)}


@pytest.fixture
def _fresh_obs():
    obs.reset_process_registry()
    yield
    obs.reset_process_registry()


def _setup(case, seed=0):
    """(loss(state, h) -> (scalar, written state), state, h): the layer
    of ``case`` as one recomputed segment (``remat``) or called plainly,
    its output weighed by a fixed draw."""
    paddle.seed(seed)
    layer, extra = LAYERS[case]()
    rng = np.random.default_rng(seed)
    state = {k: jnp.asarray(rng.standard_normal(v.shape) * 0.2, v.dtype)
             if jnp.issubdtype(v.dtype, jnp.floating) else v.data
             for k, v in layer.state_dict().items()}
    h = jnp.asarray(rng.standard_normal((B, S, HIDDEN)), jnp.float32)
    weigh = jnp.asarray(rng.standard_normal((B, S, HIDDEN)), jnp.float32)

    def loss(state, h, remat=True):
        with no_grad(), layer.load_functional_state(state), \
                fnorm.collect_stat_updates() as sink:
            args = (Tensor(h),) + extra
            out = recompute(layer, *args) if remat else layer(*args)
        return jnp.sum(out.data * weigh), [u.value for u in sink]
    return loss, state, h


def _kept_beside_the_inputs(capsys, loss, state, h):
    """The lines of ``print_saved_residuals`` that are no argument (nor
    the loss's own constant, the draw that weighs the output)."""
    capsys.readouterr()
    print_saved_residuals(lambda s, h: loss(s, h)[0], state, h)
    lines = capsys.readouterr().out.strip().splitlines()
    return [l for l in lines if " from the argument " not in l
            and " from a constant" not in l]


def _count(jaxpr, tally=None):
    """Primitive name -> count, and (``dot_general``, K, N) -> count,
    through every inner jaxpr."""
    tally = collections.Counter() if tally is None else tally
    for eqn in jaxpr.eqns:
        tally[eqn.primitive.name] += 1
        if eqn.primitive.name == "dot_general":
            (lc, _), _ = eqn.params["dimension_numbers"]
            lhs, out = eqn.invars[0].aval, eqn.outvars[0].aval
            tally["dot_general", lhs.shape[lc[0]], out.shape[-1]] += 1
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else (v,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    _count(inner, tally)
    return tally


@pytest.mark.parametrize("case", ["ouro", "kanana2", "sdar"])
def test_a_segment_gives_the_plain_layers_gradients_and_state_to_the_bit(
        case):
    """(a) the kept values are the forward pass's own: gradients (of the
    weights and of the input) and what the layer wrote equal those of the
    same layer called without ``recompute``."""
    loss, state, h = _setup(case)
    with flags_guard(flash_attention="always"):
        got, want = (jax.jit(jax.value_and_grad(
            lambda s, h, r=remat: loss(s, h, r), argnums=(0, 1),
            has_aux=True))(state, h) for remat in (True, False))
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert len(got[0][1]) == (0 if case == "ouro" else 1)   # expert_load


@pytest.mark.parametrize("case", ["ouro", "kanana2", "sdar"])
def test_the_backward_pass_runs_one_product_fewer_and_no_second_sort(
        case, monkeypatch):
    """(b) counted in the gradient's jaxpr against the parent's form of
    the segment (the kernel's two names alone): one ``dot_general`` of
    ``down_proj``'s / ``o_proj``'s shape fewer, one ``top_k`` and two
    sorts where the parent ran two and four."""
    (k, n), routed = SPARED[case]
    loss, state, h = _setup(case)
    tallies = {}
    for form in ("parent", "change"):
        if form == "parent":
            monkeypatch.setattr(recompute_keeps, "keeps", PARENT)
        else:
            monkeypatch.undo()
        with flags_guard(flash_attention="always"):
            tallies[form] = _count(jax.make_jaxpr(jax.grad(
                lambda s, h: loss(s, h)[0], argnums=(0, 1)))(state, h).jaxpr)
    parent, change = tallies["parent"], tallies["change"]
    assert change["dot_general", k, n] == parent["dot_general", k, n] - 1
    assert change["pallas_call"] == parent["pallas_call"]
    if routed:
        assert (parent["top_k"], parent["sort"]) == (2, 4)
        assert (change["top_k"], change["sort"]) == (1, 2)
    assert sum(v for key, v in change.items() if key[0] == "dot_general") \
        < sum(v for key, v in parent.items() if key[0] == "dot_general")


@pytest.mark.parametrize("case", sorted(CLOSED_FORM))
def test_a_traced_segment_counts_what_it_was_given_to_keep(case, _fresh_obs,
                                                           capsys):
    """(c) ``recompute_kept_bytes_total{name}`` and
    ``recompute_kept_values_total{name}`` by the closed form from the
    shapes, for every name placed; a segment with none of the named ops
    (a bert encoder layer) counts nothing and keeps its inputs alone."""
    loss, state, h = _setup(case)
    with flags_guard(flash_attention="always" if case != "bert" else "never"):
        jax.make_jaxpr(jax.grad(lambda s, h: loss(s, h)[0]))(state, h)
        beside = _kept_beside_the_inputs(capsys, loss, state, h)
    want = CLOSED_FORM[case]
    assert set(want) <= set(recompute_keeps.kept_names())
    by_name = process_group("name")
    got = {name: tuple(by_name.child(name).counter(series).value
                       for series in ("recompute_kept_bytes_total",
                                      "recompute_kept_values_total"))
           for name in recompute_keeps.kept_names()}
    # two traces: the jaxpr's and the residuals'
    assert got == {name: (2 * want.get(name, 0), 2 * (name in want))
                   for name in recompute_keeps.kept_names()}
    page = obs.registry.render_process_groups()
    for name, size in want.items():
        assert (f'p1t_recompute_kept_bytes_total{{name="{name}"}} '
                f'{2 * size}') in page
    if case == "bert":
        assert beside == [] and not any(sum(v) for v in got.values())


def test_a_name_the_backward_pass_does_not_read_is_not_held(capsys,
                                                            monkeypatch):
    """(d) Kanana-2's dense layer is pre-norm: its feed-forward's output
    is added to the stream and nothing reads it again, so the name on it
    adds no residual (the kernel's two and the stream after attention,
    with the name and with a policy that does not know it); in Ouro's
    sandwich the norm behind reads it and it is held."""
    keeps = recompute_keeps.keeps

    def without(prim, *avals, **params):
        return params.get("name") != "gated_ffn_out" and \
            keeps(prim, *avals, **params)
    held = collections.defaultdict(list)
    for case in ("kanana2_dense", "ouro"):
        loss, state, h = _setup(case)
        for policy in (None, without):
            if policy is not None:
                monkeypatch.setattr(recompute_keeps, "keeps", policy)
            with flags_guard(flash_attention="always"):
                held[case].append(_kept_beside_the_inputs(
                    capsys, loss, state, h))
            monkeypatch.undo()
    assert [len(kept) for kept in held["kanana2_dense"]] == [3, 3]
    assert held["kanana2_dense"][0] == held["kanana2_dense"][1]
    assert [len(kept) for kept in held["ouro"]] == [3, 2]
    stream = f"f32[{B},{S},{HIDDEN}]"
    assert sum(l.startswith(stream) for l in held["ouro"][0]) == 1
    assert all("recompute_keeps.py" in l or "flash_attention.py" in l
               for kept in held.values() for l in kept[0])


def test_a_name_is_inert_outside_a_segment():
    """Eagerly and in a user's own ``jax.jit`` a named value passes
    through, a Tensor stays on the tape, and nothing is counted."""
    obs.reset_process_registry()
    x = Tensor(jnp.arange(6.0).reshape(2, 3), stop_gradient=False)
    y = recompute_keeps.keep_in_recompute(x * 2.0, "a_test_name")
    paddle.sum(y).backward()
    np.testing.assert_array_equal(x.grad.numpy(), np.full((2, 3), 2.0))
    raw = jax.jit(lambda a: recompute_keeps.keep_in_recompute(
        a, "a_test_name"))(x.data)
    np.testing.assert_array_equal(np.asarray(raw), np.asarray(x.data))
    assert "a_test_name" in recompute_keeps.kept_names()
    assert "recompute_kept" not in obs.registry.render_process_groups()
    recompute_keeps._NAMES.discard("a_test_name")
