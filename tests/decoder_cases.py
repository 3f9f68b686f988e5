"""The cases every decoder's test file runs, written once (ISSUE 46).

Not collected by itself. ``tests/test_<model>.py`` sets ``DECODER``, a
:class:`Decoder` that says what the file's model is (the reduced
configuration, how to build the Layer, how to draw a batch, the criterion,
the reference and the program of ``benchmarks/``, the tolerances), and
imports the fixtures and the cases it runs::

    from decoder_cases import (decoder, reference, eager_run, fresh_obs,
                               test_the_model_follows_the_reference, ...)

(and ``eager_kernel_run`` where it sets ``kernel_sides_on_the_tape``).

pytest collects an imported case under the importing file, so the ids are
``tests/test_sdar.py::test_the_model_follows_the_reference``; a case two
files name differently is imported ``as`` each file's name. What a model
has of its own stays in its file.

What is built and compiled once a file (the fixtures are module-scoped, and
with ``--dist loadfile`` a file is one worker's):

- ``reference``: the reference's weights, drawn once; a fresh Layer holding
  them (``reference.model()``) wherever a case mutates one; the reference's
  loss and gradients as one ``jax.jit(jax.value_and_grad(...))`` a
  configuration and batch shape, in place of an eager call that compiles
  every primitive of the reference and of its transpose singly;
- ``eager_run``: the model's loss and gradients on the eager tape, op by op
  at the public surface, at the size the XLA composition takes: the model's
  side of ``test_the_model_follows_the_reference`` and the side without
  recomputation of ``test_recomputation_...[dense]``;
- ``eager_kernel_run``: the same at a row of whole tiles with the kernels
  forced, in a file that keeps ``test_recomputation_...[kernel]`` on the
  tape. Elsewhere two runs of the model that a case compares (recomputation
  on and off at that row, the eight shares and the whole layer) are traced
  under one ``jax.jit``, as a step traces the model.
"""

import dataclasses
import types
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle1_tpu as paddle
from benchmarks.programs import load_weights
from benchmarks.reference.numerics import Numerics
from paddle1_tpu import obs
from paddle1_tpu.autograd.engine import no_grad
from paddle1_tpu.core.flags import flags_guard
from paddle1_tpu.core.tensor import Tensor
from paddle1_tpu.distributed import ParallelEngine, build_mesh
from paddle1_tpu.obs import costmodel
from paddle1_tpu.obs.registry import process_group

NM = Numerics()
KERNEL_SEQ = 128        # a row of whole tiles of the kernels
LOSS_REL = 2e-5         # of the loss: the same float32 products summed in
GRAD_REL = 2e-4         # another order; of a leaf's gradient norm


def _nothing_more(*args):
    pass


@dataclasses.dataclass
class Decoder:
    """What a test file says of its model. The callables carry what differs
    between two models in more than names and sizes."""
    cfg: dict                       # the reference's keys at a tiny size
    ref: types.ModuleType           # benchmarks.reference.<config>
    program: types.ModuleType       # benchmarks.programs.<config>
    build: Callable                 # cfg -> a Layer, its own weights drawn
    criterion: Callable             # (model, batch) -> the loss, a Tensor
    batch: Callable                 # (batch=, seq=, seed=) -> {name: array}
    dense_seq: int = 16             # a row the XLA composition takes
    draw: Callable = None           # cfg -> the reference's weights
    # the leaves of the program's map that are buffers, not parameters
    buffers: frozenset = frozenset()
    # (model, weights, batch): what the model's file checks of its own
    # beside loss and gradients (kinds of layers, logits, shapes)
    follows_also: Callable = _nothing_more
    # (model, batch, loss): after the two runs, inside the flag
    recomputed_also: Callable = _nothing_more
    # test_recomputation_...[kernel]: both sides op by op on the eager tape
    # (see the case); the file then imports ``eager_kernel_run`` too
    kernel_sides_on_the_tape: bool = False
    # the reference in blocks: the row, the block sizes (module, name,
    # value) to set, and the fewest ``scan``s its jaxpr then holds
    blocks_seq: int = 32
    blocks: tuple = ()
    scans: int = 0
    # ParallelEngine's optimizer: parameters -> optimizer
    optimizer: Callable = None
    # the model's own of a step: (engine) the counters of its one lowering,
    # read before anything lowers it again, and (engine, named) its scopes
    step_counters: Callable = _nothing_more
    step_scopes: Callable = _nothing_more
    # the share test: see test_the_eight_shares_add_up_to_the_whole_layer
    shares: dict = None
    # an expert layer of the model has a shared expert beside the routed
    shared_experts: bool = False

    def weights(self, cfg):
        if self.draw is not None:
            return self.draw(cfg)
        return jax.jit(lambda key: self.ref.init_params(cfg, key))(
            jax.random.key(4))


def ids_batch(vocab, row=16):
    """``batch(batch=2, seq=row, seed=0) -> {"ids"}``: rows of token ids
    below ``vocab``."""
    def batch(batch=2, seq=row, seed=0):
        return {"ids": np.random.default_rng(seed).integers(
            0, vocab, (batch, seq)).astype(np.int32)}
    return batch


def next_token_loss(criterion):
    """``(model, batch) -> loss`` of a model that shifts its own labels."""
    def loss(model, batch):
        ids = Tensor(batch["ids"])
        labels = model.next_token_labels(ids)
        return criterion()(model(ids, labels), labels)
    return loss


def logits_follow(decoder, model, weights, batch):
    """The model's logits beside the reference's, to 1e-4 of the largest:
    the same float32 products summed in another order."""
    ref, cfg = decoder.ref, decoder.cfg
    logits = model(Tensor(batch["ids"])).numpy()
    want = np.asarray(jax.jit(lambda w, ids: ref.head_logits(ref.hidden(
        w, ids, cfg, NM), w, cfg, NM))(weights, batch["ids"]))
    assert logits.shape == batch["ids"].shape + (cfg["vocab_size"],)
    np.testing.assert_allclose(logits, want, rtol=0,
                               atol=1e-4 * np.abs(want).max())


class Reference:
    """The reference's side of a file's cases under one configuration."""

    def __init__(self, decoder, cfg=None, weights=None):
        self.decoder = decoder
        self.cfg = decoder.cfg if cfg is None else cfg
        self.weights = decoder.weights(self.cfg) if weights is None \
            else weights
        self.loss_and_grads = self.compiled()

    def compiled(self, nm=NM):
        """``batch -> (loss, gradients)`` of the reference as its module
        stands now, one compiled program a batch shape: after a patch of
        the module's block sizes, ask for a new one."""
        ref, cfg = self.decoder.ref, self.cfg
        fn = jax.jit(jax.value_and_grad(
            lambda w, batch: ref.loss(w, batch, cfg, nm)[0]))
        return lambda batch, weights=None: fn(
            self.weights if weights is None else weights,
            {k: jnp.asarray(v) for k, v in batch.items()})

    def model(self):
        """A fresh Layer that holds the reference's weights."""
        model = self.decoder.build(self.cfg)
        load_weights(model, {
            p: jnp.asarray(self.weights[r] if i is None
                           else self.weights[r][i])
            for p, r, i in self.decoder.program.leaves(self.cfg)})
        return model

    def engine(self, amp=None, recompute=True, model=None, optimizer=None):
        """The model behind ``ParallelEngine`` on one device."""
        model = model or self.model()
        make = self.decoder.optimizer or (
            lambda parameters: paddle.optimizer.AdamW(
                learning_rate=1e-2, weight_decay=0.1, parameters=parameters))
        return ParallelEngine(
            model, optimizer or make(model.parameters()),
            self.decoder.criterion,
            amp_dtype=amp, recompute=recompute,
            mesh=build_mesh(dp=1, devices=jax.devices()[:1]))


def eager_loss_and_grads(decoder, model, batch):
    """The loss and every parameter's gradient on the eager tape."""
    loss = decoder.criterion(model, batch)
    loss.backward()
    return float(loss), {k: p.grad.numpy()
                         for k, p in model.named_parameters()}


def traced_loss_and_grads(decoder, models, batch):
    """The same two of each model, the models traced whole as
    ``ParallelEngine`` traces one (the tape off, ``jax.value_and_grad``
    outside), all under one ``jax.jit``."""
    def arrays(model):
        state = model.functional_state()
        return {k: state.pop(k) for k, _ in model.named_parameters()}, state

    def loss(params, state, model, batch):
        with no_grad(), model.load_functional_state({**state, **params}):
            return decoder.criterion(model, batch).data

    def every_side(every, batch):
        return [jax.value_and_grad(loss)(params, state, model, batch)
                for model, (params, state) in zip(models, every)]
    return [(float(loss), {k: np.asarray(g) for k, g in grads.items()})
            for loss, grads in jax.jit(every_side)(
                [arrays(model) for model in models], batch)]


def traced_outputs(layers, x):
    """What each Layer makes of ``x``, every one traced under one
    ``jax.jit`` with its arrays as arguments."""
    def run(states, x):
        outs = []
        for layer, state in zip(layers, states):
            with no_grad(), layer.load_functional_state(state):
                outs.append(layer(Tensor(x)).data)
        return outs
    return [np.asarray(out) for out in jax.jit(run)(
        [layer.functional_state() for layer in layers], jnp.asarray(x))]


def grads_follow(decoder, got, reference, batch):
    """The model's gradients, by parameter name, beside the reference's of
    every leaf of the program's map; returns the reference's loss."""
    want, grads = reference.loss_and_grads(batch)
    leaves = decoder.program.leaves(reference.cfg)
    assert {p for p, _, _ in leaves} - set(got) == set(decoder.buffers)
    for p, r, i in leaves:
        if p in got:
            w = np.asarray(grads[r] if i is None else grads[r][i])
            assert np.linalg.norm(got[p] - w) <= GRAD_REL * max(
                np.linalg.norm(w), 1e-4), p
    return float(want)


# -- the fixtures a file imports with the cases -----------------------------

@pytest.fixture(scope="module")
def decoder(request):
    return request.module.DECODER


@pytest.fixture(scope="module")
def reference(decoder):
    return Reference(decoder)


def _eager_run(decoder, reference, seq, **flags):
    model = reference.model()
    batch = decoder.batch(seq=seq)
    with flags_guard(**flags):
        loss, grads = eager_loss_and_grads(decoder, model, batch)
    return types.SimpleNamespace(model=model, batch=batch, loss=loss,
                                 grads=grads)


@pytest.fixture(scope="module")
def eager_run(decoder, reference):
    """The one eager run a file makes at the composition's size (which
    the default flag takes at such a row), without recomputation: (model,
    batch, loss, gradients)."""
    return _eager_run(decoder, reference, decoder.dense_seq)


@pytest.fixture(scope="module")
def eager_kernel_run(decoder, reference):
    """The same at a row of whole tiles with the kernels forced, for a
    file that keeps ``test_recomputation_...[kernel]`` on the tape."""
    return _eager_run(decoder, reference, KERNEL_SEQ,
                      flash_attention="always")


@pytest.fixture
def fresh_obs():
    obs.reset_process_registry()
    obs.hbm.reset()
    yield
    obs.reset_process_registry()
    obs.hbm.reset()


# -- the cases ----------------------------------------------------------------

def test_the_model_follows_the_reference(decoder, reference, eager_run):
    """Loss and every gradient leaf in float32, the model op by op on the
    eager tape. Tolerances: the two sides sum the same float32 products in
    another order (2e-5 of a loss, 2e-4 of a leaf's gradient norm)."""
    want = grads_follow(decoder, eager_run.grads, reference, eager_run.batch)
    assert eager_run.loss == pytest.approx(want, rel=LOSS_REL)
    decoder.follows_also(eager_run.model, reference.weights, eager_run.batch)


def test_the_reference_in_blocks_is_the_reference(decoder, reference,
                                                  monkeypatch):
    """At the cell's size the reference takes a row's positions, a block
    of one key/value head's queries and groups of the held experts one at
    a time through ``lax.map``; at a test's size it takes each whole. The
    same loss and gradients either way."""
    batch = decoder.batch(seq=decoder.blocks_seq)
    whole, g_whole = reference.loss_and_grads(batch)
    for module, name, value in decoder.blocks:
        monkeypatch.setattr(module, name, value)
    text = str(jax.make_jaxpr(lambda w: decoder.ref.loss(
        w, {k: jnp.asarray(v) for k, v in batch.items()}, decoder.cfg,
        NM)[0])(reference.weights))
    assert text.count("scan") >= decoder.scans
    blocks, g_blocks = reference.compiled()(batch)     # after the patch
    assert float(blocks) == pytest.approx(float(whole), rel=1e-5)
    for k in g_whole:
        a, b = np.asarray(g_blocks[k]), np.asarray(g_whole[k])
        assert np.linalg.norm(a - b) <= 1e-5 * max(np.linalg.norm(b), 1e-3), (
            k, np.linalg.norm(a - b), np.linalg.norm(b))


@pytest.mark.parametrize("attention", ["dense", "kernel"])
def test_recomputation_changes_neither_loss_nor_gradients(
        request, decoder, reference, attention):
    """Whatever a recomputed segment keeps: with XLA's dense attention its
    inputs alone, with the kernels (forced: 128 is their tile, and off a
    TPU they run in interpret mode) their ``out`` and ``lse`` too, and an
    expert layer's sort, gathers and grouped products with integer
    residuals. The composition's sides run op by op on the eager tape,
    the one without recomputation being the file's one eager run of that
    row. So do the kernels' in a file that sets
    ``kernel_sides_on_the_tape``; elsewhere they are two models traced
    whole under one ``jax.jit``, as a step traces one (a row of whole
    tiles is a second set of every op's programs, 350 of them)."""
    kernel = attention == "kernel"
    with flags_guard(flash_attention="always" if kernel else "never"):
        with_it = reference.model()
        with_it.layers.enable_recompute = True
        if kernel and not decoder.kernel_sides_on_the_tape:
            batch = decoder.batch(seq=KERNEL_SEQ)
            got = traced_loss_and_grads(
                decoder, [reference.model(), with_it], batch)
        else:
            without = request.getfixturevalue(
                "eager_kernel_run" if kernel else "eager_run")
            batch = without.batch
            got = [(without.loss, without.grads),
                   eager_loss_and_grads(decoder, with_it, batch)]
        assert got[1][0] == pytest.approx(got[0][0], rel=1e-6)
        for k, g in got[0][1].items():
            np.testing.assert_allclose(got[1][1][k], g, rtol=1e-4,
                                       atol=1e-6 * np.abs(g).max())
        decoder.recomputed_also(with_it, batch, got[0][0])


def test_the_eight_shares_add_up_to_the_whole_layer(decoder):
    """The share test: with the same weights, the routed outputs of the
    eight shares are the uncut layer's, which is the reference's uncut
    expert layer; and what a share computes is what the reference gives
    that share. The nine layers are traced under one ``jax.jit``, as a
    step traces one (kanana2's file keeps them op by op on the tape).
    ``decoder.shares``: ``experts(tokens, total, top_k, held) -> (layer,
    x)``, the experts in all and a token's picks, and the reference's
    configuration keys and weights of the uncut layer."""
    s = decoder.shares
    tokens, total, top_k = 48, s["total"], s["top_k"]
    per = total // 8
    whole, x = s["experts"](tokens, total, top_k, None)
    shares = [s["experts"](tokens, total, top_k, (per * rank, per))[0]
              for rank in range(8)]
    for rank, share in enumerate(shares):
        share.router.data = whole.router.data
        share.gate_up_proj.data = whole.gate_up_proj.data[per * rank:][:per]
        share.down_proj.data = whole.down_proj.data[per * rank:][:per]
    want, *parts = traced_outputs([whole] + shares, x)
    cfg = {"num_experts": total, "expert_parallel": 1, "expert_rank": 0,
           "num_experts_per_tok": top_k, "moe_intermediate_size": 8,
           **s.get("cfg", {})}
    lp = {"router": whole.router.data, "e_gate_up": whole.gate_up_proj.data,
          "e_down": whole.down_proj.data, **s.get("weights", {})}

    def plain(lp, cfg):
        return jax.jit(lambda x, lp: decoder.ref.experts(x, lp, cfg, NM))(
            jnp.asarray(x), lp)
    np.testing.assert_allclose(want, plain(lp, cfg), rtol=1e-4, atol=1e-5)
    for rank, (share, part) in enumerate(zip(shares, parts)):
        np.testing.assert_allclose(part, plain(
            {**lp, "e_gate_up": share.gate_up_proj.data,
             "e_down": share.down_proj.data},
            {**cfg, "num_experts": per, "expert_parallel": 8,
             "expert_rank": rank}), rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(sum(parts), want, rtol=1e-4, atol=1e-5)


def test_a_step_trains_and_carries_the_scopes_and_the_counters(
        decoder, reference, fresh_obs):
    """Three bfloat16 steps through ``ParallelEngine(recompute=True)`` at
    a row of whole tiles with the kernels forced: the loss falls, the
    kernels' arm is counted and the composition's is not, the two kernels
    lie under the attention op and the forward one is not run again in a
    recomputed segment, the router is a float32 island; and the model's
    own counters and scopes (``decoder.step_counters``, ``step_scopes``)."""
    engine = reference.engine(amp="bfloat16")
    assert engine.model.layers.enable_recompute
    batch = engine.shard_batch(decoder.batch(seq=KERNEL_SEQ))
    with flags_guard(flash_attention="always"):
        losses = [float(engine.step(batch, lr=1e-2)) for _ in range(3)]
        # the counters of the one lowering: the scopes and the text lower
        # the step again
        arms = process_group("arm")
        assert arms.child("flash").counter("attention_arm_total").value >= 1
        assert arms.child("dense").counter("attention_arm_total").value == 0
        decoder.step_counters(engine)
        named = [s for s in costmodel.step_op_scopes().values()
                 if "jvp(loss)" in s]
        text = engine.compiled_step_text()
    assert losses[2] < losses[0]
    decoder.step_scopes(engine, named)
    assert bool([s for s in named if "/moe/shared_experts/" in s]) \
        == decoder.shared_experts
    assert any("/lm_head/head_cross_entropy" in s for s in named)
    # the two kernels under the attention op, the forward not run again
    kernels = [s for s in named if "p1t_flash_attention" in s]
    assert kernels and all("/scaled_dot_product_attention/" in s
                           for s in kernels)
    assert not [s for s in kernels if "/rematted_computation/" in s
                and "p1t_flash_attention_fwd" in s]
    # the router is a float32 island under the bf16 autocast
    router = [l for l in text.splitlines()
              if "moe_router" in l and " dot(" in l]
    assert router and all(" f32[" in l.split(" dot(")[0] for l in router)
