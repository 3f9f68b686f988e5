"""Compiled hybrid-parallel training engine.

This is the TPU-native replacement for the whole meta-optimizer /
ParallelExecutor stack of the reference (SURVEY §2.3, §3.1): where the
reference rewrites a ProgramDesc per strategy (insert c_allreduce for DP,
split programs for PP, prune for ZeRO — fleet/base/fleet_base.py:1212
minimize → StrategyCompiler) and interprets it op-by-op, we compose ONE pure
train-step function (loss → grad → optimizer update) and jit it over the
hybrid ``Mesh`` with `NamedSharding` annotations; GSPMD inserts every
collective (grad psum for DP, Megatron f/g for TP, reduce-scatter/all-gather
for ZeRO) and the latency-hiding scheduler overlaps them with compute — the
Reducer-overlap problem (SURVEY §7 hard part a) solved by the compiler.

Usage::

    engine = ParallelEngine(model, opt, loss_fn, strategy=dist_strategy)
    for batch in loader:
        loss = engine.step(batch)      # one fused XLA executable
    engine.sync_model()                # write params back into the Layer

Multi-step (device-resident) training: every ``step`` call pays one
host→device dispatch, and every eager ``float(loss)`` pays a
device→host readback (their cost on the v5e: not measured).
``step_many`` amortizes both: k optimizer steps run inside ONE jitted
executable via ``lax.scan`` (one dispatch, one
donation cycle), losses come back as a single lazy ``LossFuture`` over
the ``[k]`` device array — zero intermediate readbacks::

    for losses in engine.step_stream(loader):  # k steps per dispatch,
        pass                                   # k = train_steps_per_sync
    engine.sync_model()                        # drains in-flight work first
"""

from __future__ import annotations

import collections
import functools
import time
from typing import (Any, Callable, Dict, NamedTuple, Optional,
                    Sequence)

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..core import flags as core_flags
from ..core import async_loss
from ..core import jit_sanitizer
from ..core.async_loss import LossFuture, StepFuture
from ..obs import costmodel as obs_costmodel
from ..obs import flight as obs_flight
from ..obs import hbm as obs_hbm
from ..obs import trace as obs_trace
from ..core.generator import next_key, rng_scope
from ..core.tensor import Tensor
from ..autograd import engine as autograd_engine
from ..nn.layer_base import Layer
from .sharding_specs import (data_partition_spec, param_partition_specs,
                             zero_shard_spec)
from .topology import build_mesh

__all__ = ["ParallelEngine", "make_train_step"]


def _obs_step_registry():
    """The process registry iff per-step instrumentation is on
    (obs_metrics flag) — one flag read on the hot path, None otherwise
    (the bench --obs disabled-cost contract)."""
    from ..obs import registry as obs_registry
    return obs_registry.step_registry()


# process-level throughput state behind the train_samples_per_s /
# train_steps_per_readback gauges: one gauge family per process, so the
# state is process-global too — two engines in one process (train +
# eval, GAN pairs) contribute to ONE aggregate instead of clobbering
# each other with per-engine numbers against a process-wide readback
# counter
_obs_thru = {"rb_base": None, "last_t": None, "rate": None}


def _obs_note_steps(m, k: int, rows: int, t_now: float) -> None:
    """Feed the throughput gauges after an instrumented dispatch:
    samples/s as an EWMA over wall time between dispatches, and
    steps-per-readback (how well the lazy-loss window amortizes the
    host round trip — the step_many story in one number). Under
    run-ahead the time between two enqueues is not a step time, so no
    utilization is derived from it: the benchmark reads those off the
    device trace."""
    st = _obs_thru
    if st["rb_base"] is None:
        st["rb_base"] = async_loss.readback_count()
    c = m.counter("train_steps_total")
    c.inc(k)
    last, st["last_t"] = st["last_t"], t_now
    dt = (t_now - last) if (last is not None and t_now > last) else None
    if dt is not None:
        inst = (k * rows) / dt
        st["rate"] = inst if st["rate"] is None else \
            0.8 * st["rate"] + 0.2 * inst
        m.gauge("train_samples_per_s").set(st["rate"])
    rb = async_loss.readback_count() - st["rb_base"]
    total = c.value
    m.gauge("train_steps_per_readback").set(
        total / rb if rb > 0 else float(total))
    # flight ring first: if the leak detector below raises, the crash
    # dump still holds this step
    fr = obs_flight.recorder()
    if fr is not None:
        fr.note_step(step=total,
                     samples_per_s=round(st["rate"] or 0.0, 2),
                     hbm_bytes=obs_hbm.last_total())
    # HBM census: per-subsystem registered bytes, sampled (at most
    # once per interval — the walk is O(registered leaves)) and fed
    # into the flag-gated monotone-growth leak detector
    obs_hbm.step_sample(m)


_readback_obs_installed = False


def _ensure_readback_observer():
    """Route LossFuture materialization durations into the process
    registry's train_readback_seconds histogram, and at that host sync
    (it happens anyway) the live engines' expert-load counts into the
    ``moe_*`` series (idempotent; installed the first time an
    instrumented step runs, so uninstrumented processes never pay the
    per-fetch perf_counter)."""
    global _readback_obs_installed
    if _readback_obs_installed:
        return
    _readback_obs_installed = True
    from ..obs import registry as obs_registry

    def observe(dt: float) -> None:
        if obs_registry.metrics_on():
            m = obs_registry.process_registry()
            m.histogram("train_readback_seconds").observe(dt)
            for owner in obs_hbm.live_owners():
                if getattr(owner, "_load_seen", None):
                    owner._publish_load(m)

    async_loss.set_readback_observer(observe)


class StepPhases(NamedTuple):
    """What the host did in one dispatch of :meth:`ParallelEngine.step`
    / ``step_many``: one record of the engine's ring
    (:meth:`ParallelEngine.phase_records`). Times are nanoseconds of
    ``time.time_ns()``, the clock of ``obs/trace.py``'s spans."""

    step: int                # dispatch_count after this dispatch
    start_ns: int            # when step() was entered
    shard_ns: int            # placement inside step() PLUS every
    #                          shard_batch() call since the last record
    #                          (a trainer that places its batch itself
    #                          makes step()'s own call a pass-through)
    guard_ns: int            # retrace guard, sanitizer bookkeeping
    dispatch_ns: int         # the jit call (trace + compile when
    #                          ``compiled``), LR schedule step
    inflight_wait_ns: int    # blocked on the oldest outstanding step
    compiled: bool           # the dispatch traced a new executable
    shard_calls: int         # how many placements ``shard_ns`` sums
    k: int                   # optimizer steps in the dispatch


PHASE_RING = 4096            # records kept: hours of steps do not grow it
_PHASES = ("train/shard", "train/guard", "train/dispatch",
           "train/inflight_wait")


def _as_arrays(batch):
    """Tensor/np leaves → jax arrays, preserving tree structure."""
    if isinstance(batch, Tensor):
        return batch.data
    if isinstance(batch, (list, tuple)):
        return type(batch)(_as_arrays(b) for b in batch)
    if isinstance(batch, dict):
        return {k: _as_arrays(v) for k, v in batch.items()}
    return jnp.asarray(batch)


def make_train_step(layer: Layer, optimizer, loss_fn: Callable,
                    grad_accum: int = 1,
                    clip_global_norm: Optional[float] = None,
                    amp_dtype: Optional[str] = None,
                    recompute: bool = False,
                    grad_shardings=None,
                    check_finite: bool = False):
    """Build the pure train-step: (params, opt_state, batch, key, lr,
    step_state) → (loss, params, opt_state, step_state).

    ``step_state`` is the state of the run that is no parameter: the
    model's non-persistable buffers (``{name: array}``: the expert
    layer's load counts), written by a forward through
    ``nn/functional/norm.py::record_state_update``. It stays outside
    ``jax.value_and_grad``'s arguments, outside the optimizer and outside
    ``check_finite``'s keep-select (a step that is refused still ran its
    forward). Left out (None) or empty, the step carries none, returns
    ``{}`` and is the program it would be without: a model that records
    nothing compiles to the same text.

    ``check_finite=True`` folds device-side bad-step detection into the
    same executable: a non-finite loss or gradient (NaN batch, amp
    overflow) flips an on-device flag, the optimizer update is *skipped*
    via a ``where``-select back to the incoming params/opt_state (so a
    poisoned batch can never corrupt the model, even while the host is
    still dispatching ahead of the readback), and the step returns a
    packed ``[loss, notfinite]`` pair instead of the bare loss — the
    flag rides the loss's own readback, costing zero extra transfers.

    ``loss_fn(model, batch)`` runs the model's eager code; under trace the
    tape is off and jax.grad differentiates the pure function — eager and
    compiled mode share one autograd (the dygraph/static parity the
    reference maintains with two separate engines, backward.py:1363 vs
    basic_engine.cc).
    """

    # State a forward writes, functionalized (ADVICE r5 medium): the
    # rule and momentum per captured buffer, recorded at trace time — a
    # plain Python side channel, like the trace counters. The traced
    # values ride pure_loss's aux output; train_step folds them into
    # the incoming buffer values by their rule and writes the result
    # into the step's OUTPUT params (a persistable buffer: batch norm's
    # running statistics) or its output step state (a non-persistable
    # one: the expert layer's load counts), so compiled training keeps
    # them exactly like eager training and sync_model/checkpoints see
    # them — no transfers beside the step's own.
    from ..nn.functional import norm as fnorm
    stat_rule: Dict[str, tuple] = {}
    buffer_names = [name for name, _ in layer.named_buffers()]

    def pure_loss(params, batch, key):
        if amp_dtype is not None:
            # bf16 autocast: compute params in bf16, masters stay f32 in
            # the optimizer (reference pure-fp16 mode, fp16_utils.py:322)
            cdt = jnp.dtype(amp_dtype)
            with jax.named_scope("amp_cast"):
                params = {k: (v.astype(cdt)
                              if jnp.issubdtype(v.dtype, jnp.floating)
                              else v)
                          for k, v in params.items()}
                # feeds too (reference pure-fp16 casts the feed vars as
                # well, fp16_utils.py cast_model_to_fp16): f32 images x
                # bf16 conv weights is a dtype error on TPU
                batch = jax.tree_util.tree_map(
                    lambda a: a.astype(cdt)
                    if (hasattr(a, "dtype")
                        and jnp.issubdtype(a.dtype, jnp.floating)) else a,
                    batch)
        with autograd_engine.no_grad(), rng_scope(key):
            with layer.load_functional_state(params):
                with fnorm.collect_stat_updates() as stat_updates:
                    # autodiff names the forward ops jvp(loss)/... and
                    # the backward ops transpose(jvp(loss))/... itself
                    with autograd_engine.traced_scopes(), \
                            jax.named_scope("loss"):
                        out = loss_fn(layer, batch)
        out = out.data if isinstance(out, Tensor) else out
        aux = {}
        if stat_updates:
            # map each written buffer back to its key in the step's
            # state by the identity of its Tensor (load_functional_state
            # swaps arrays inside these very Tensors; a recomputed
            # segment swaps its own tracers in, so the array's identity
            # would not do), and emit the raw values as aux — the fold
            # into the old value happens in train_step, where composing
            # multiple micro-steps is well-defined
            ids = {id(t): k for k, t in
                   reversed(list(layer.named_buffers()))}
            for u in stat_updates:
                name = ids.get(id(u.buffer))
                if name is None:
                    continue  # buffer not threaded through the step
                if u.rule == "add":
                    stat_rule[name] = ("add", None)
                    aux[name] = aux[name] + u.value if name in aux \
                        else u.value
                else:
                    stat_rule[name] = ("blend", float(u.momentum))
                    aux[name] = u.value.astype(jnp.float32)
        return out.astype(jnp.float32), aux

    if recompute:
        # Rematerialisation must be per-BLOCK to cut peak memory
        # (checkpointing the whole loss would re-run the forward without
        # reducing the residual set). Flip the switch on every sublayer
        # that declares one: TransformerEncoder, a model's own block
        # stack, whatever wraps its blocks in fleet.utils.recompute
        # while ``enable_recompute`` is set.
        flipped = 0
        for sub in layer.sublayers(include_self=True):
            if hasattr(sub, "enable_recompute"):
                sub.enable_recompute = True
                flipped += 1
        if not flipped:
            import warnings
            warnings.warn(
                "recompute=True: no recompute-capable blocks found (no "
                "sublayer declares enable_recompute, as "
                "TransformerEncoder does); wrap your own blocks with "
                "fleet.utils.recompute for per-segment remat")

    def train_step(params, opt_state, batch, key, lr, step_state=None):
        step_state = {} if step_state is None else step_state
        if grad_accum > 1:
            # micro-batch scan: batch leaves are [accum, micro, ...]
            def micro(carry, xs):
                g_acc, i = carry
                mb, k = xs
                (l, aux), g = jax.value_and_grad(
                    pure_loss, has_aux=True)(params, mb, k)
                g_acc = jax.tree_util.tree_map(lambda a, b: a + b, g_acc, g)
                return (g_acc, i + 1), (l, aux)
            zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
            keys = jax.random.split(key, grad_accum)
            (grads, _), (losses, aux) = jax.lax.scan(micro, (zeros, 0),
                                                     (batch, keys))
            grads = jax.tree_util.tree_map(lambda g: g / grad_accum, grads)
            loss = jnp.mean(losses)
        else:
            (loss, aux), grads = jax.value_and_grad(
                pure_loss, has_aux=True)(params, batch, key)
        finite = None
        if check_finite:
            # detection sits at the autodiff boundary, on the RAW grads:
            # clipping/sharding transforms below keep NaN NaN, but the
            # raw position is what mirrors the reference
            # check_finite_and_unscale op (amp/check_finite_and_unscale
            # _op.cu) and stays correct if those transforms change
            with jax.named_scope("finite_check"):
                finite = jnp.isfinite(loss)
                for g in jax.tree_util.tree_leaves(grads):
                    finite &= jnp.all(jnp.isfinite(g))
        if grad_shardings is not None:
            # Pin each grad to its ZeRO layout HERE, at the autodiff
            # boundary: the batch reduction then lowers to a
            # reduce-scatter into the slot sharding. Without the pin,
            # GSPMD propagates the slot sharding backward THROUGH the
            # reduction onto the batch-sharded activation grad — a
            # batch-dim→hidden-dim transition it can only satisfy by
            # "involuntary full rematerialization" (replicate-then-slice,
            # which the compiler warns of). Reference intent:
            # sharding_optimizer.py:146 "reduce rather than allreduce".
            grads = jax.lax.with_sharding_constraint(grads, grad_shardings)
        if clip_global_norm is not None:
            with jax.named_scope("grad_clip"):
                leaves = jax.tree_util.tree_leaves(grads)
                gn = jnp.sqrt(sum(
                    jnp.sum(jnp.square(g.astype(jnp.float32)))
                    for g in leaves))
                scale = jnp.minimum(1.0, clip_global_norm / (gn + 1e-6))
                grads = jax.tree_util.tree_map(
                    lambda g: (g.astype(jnp.float32)
                               * scale).astype(g.dtype),
                    grads)
        with jax.named_scope("optimizer"):
            new_params, new_state = optimizer.functional_update(
                params, grads, opt_state, lr)
            # a buffer is no parameter: whatever the update rule made of
            # its zero gradient (AdamW's decay shrinks it), it leaves the
            # step as it came, unless a forward's write is folded in below.
            # The buffers that still take this road through the optimizer
            # are the persistable float ones, which are entries of
            # ``params`` because ``functional_state()`` is
            # ``state_dict()``: batch norm's running statistics and the
            # expert router's ``e_score_correction_bias``. Taking them out
            # of ``params`` would change every program that holds one
            # (resnet's step); the non-persistable ones never enter it:
            # they are ``step_state``
            for name in buffer_names:
                if name in params and name not in aux:
                    new_params[name] = params[name]
        new_step_state = dict(step_state)
        if aux:
            # what the forward wrote, by its rule
            # (``fold_state_update``: blend, new = m*old + (1-m)*batch;
            # add, new = old + value), one micro-step after the other
            # under grad_accum, matching eager. A persistable buffer's
            # OVERRIDES whatever
            # zero-grad update the optimizer computed for its entry, and
            # check_finite's keep-select below covers it too: a bad step
            # keeps the old stats along with the old params. The step
            # state is not kept back: the refused step ran its forward.
            with jax.named_scope("stat_update"):
                for name, stat in aux.items():
                    cur = params[name] if name in params \
                        else step_state.get(name)
                    if cur is None:
                        continue    # a step that carries no step state
                    rule, m = stat_rule[name]
                    # stacked [accum, ...] from the scan under grad_accum
                    for one in (stat if grad_accum > 1 else (stat,)):
                        cur = fnorm.fold_state_update(cur, one, rule, m)
                    (new_params if name in params
                     else new_step_state)[name] = cur
        if check_finite:
            # bad step → keep the incoming params/slots/step-count (the
            # reference update_loss_scaling "skip update" semantics),
            # selected on device so run-ahead dispatches after a NaN
            # step still consume good params
            with jax.named_scope("finite_check"):
                keep = lambda new, old: jax.tree_util.tree_map(
                    lambda n, o: jnp.where(finite, n, o), new, old)
                new_params = keep(new_params, params)
                new_state = keep(new_state, opt_state)
                packed = jnp.stack([loss, (~finite).astype(jnp.float32)])
            return packed, new_params, new_state, new_step_state
        return loss, new_params, new_state, new_step_state

    return train_step


class ParallelEngine:
    """One-mesh hybrid-parallel compiled trainer.

    Parameters
    ----------
    model : Layer — parameters may carry ``sharding_axes`` (TP tags).
    optimizer : any optimizer with functional_init/functional_update.
    loss_fn : callable(model, batch) → scalar Tensor.
    mesh : jax Mesh; built from ``degrees`` if omitted.
    degrees : dict(dp=, mp=, pp=, sharding=, sp=) hybrid degrees.
    zero_stage : 0/1/2 shard optimizer state (and grads) over 'sharding';
        3 additionally shards params (reference sharding_optimizer.py).
    grad_accum : micro-batch accumulation count (GradientMergeOptimizer).
    train_steps_per_sync : chunk size ``step_stream`` feeds to
        ``step_many`` — k optimizer steps per dispatch (the
        DistributedStrategy knob of the same name).
    inflight_window : max un-synchronized dispatches outstanding before
        ``step``/``step_many`` block on the oldest (dispatch runs ahead
        of the device without unbounded live-buffer growth).
    check_finite : fold NaN/Inf detection into the compiled step (and
        the ``step_many`` scan body): non-finite steps skip their
        update on device, and ``step``/``step_many`` return a
        :class:`~paddle1_tpu.core.async_loss.StepFuture` whose ``.bad``
        / ``.bad_mask()`` report the flag from the same packed readback
        as the loss. The knob behind ``ResilientTrainer``'s bad-step
        policies.
    """

    def __init__(self, model: Layer, optimizer, loss_fn: Callable,
                 mesh: Optional[Mesh] = None,
                 degrees: Optional[Dict[str, int]] = None,
                 zero_stage: int = 0, grad_accum: int = 1,
                 clip_global_norm: Optional[float] = None,
                 batch_spec: Optional[Any] = None,
                 donate: Optional[bool] = None,
                 amp_dtype: Optional[str] = None,
                 recompute: bool = False,
                 pp_microbatches: Optional[int] = None,
                 train_steps_per_sync: int = 1,
                 inflight_window: int = 2,
                 check_finite: bool = False):
        core_flags.maybe_enable_compilation_cache()
        # donate=None resolves from the jit_donate_params flag (the
        # reference's buffer-donation toggle) — an explicit arg wins
        donate = (bool(core_flags.flag("jit_donate_params"))
                  if donate is None else bool(donate))
        self.model = model
        self.optimizer = optimizer
        self.mesh = mesh if mesh is not None else build_mesh(
            **(degrees or {"dp": len(jax.devices())}))
        self.zero_stage = zero_stage

        # Pipeline parallelism: tag every pipelined-body sublayer so its
        # forward runs the in-graph scan+ppermute schedule over the 'pp'
        # axis (layer_transformer.TransformerEncoder._forward_pipelined).
        pp_n = int(self.mesh.shape.get("pp", 1))
        from ..nn.layer_transformer import TransformerEncoder
        if pp_n <= 1:
            # clear stale tags from a previous pp engine on the same model,
            # else _forward_pipelined would fire against the old mesh
            for sub in model.sublayers(include_self=True):
                if isinstance(sub, TransformerEncoder):
                    sub.pipeline_axis = None
        else:
            flipped = 0
            for sub in model.sublayers(include_self=True):
                if isinstance(sub, TransformerEncoder):
                    sub.pipeline_axis = "pp"
                    sub.pipeline_mesh = self.mesh
                    sub.pipeline_microbatches = pp_microbatches or pp_n
                    flipped += 1
            if not flipped:
                from ..core.errors import InvalidArgumentError
                raise InvalidArgumentError(
                    "pp degree > 1 needs a pipelined body "
                    "(TransformerEncoder) in the model; for arbitrary "
                    "heterogeneous stage graphs use distributed."
                    "meta_parallel.PipelineParallel (eager 1F1B schedule)")

        # Dedupe tied parameters (e.g. BERT's MLM decoder reuses the word
        # embedding): the same buffer must appear exactly once in the pjit
        # arguments (donation requires it) and receive ONE update combining
        # both gradient paths.
        sd = model.state_dict()
        seen: Dict[int, str] = {}
        self.params = {}
        for k, t in sd.items():
            if id(t) not in seen:  # aliases write back via shared Tensor
                seen[id(t)] = k
                self.params[k] = t.data
        shard_n = int(self.mesh.shape.get("sharding", 1))
        all_specs = param_partition_specs(model, zero_stage=zero_stage,
                                          zero_axis_size=shard_n)
        self.param_specs = {k: s for k, s in all_specs.items()
                            if k in self.params}

        # Optimizer slots shard over 'sharding' from stage 1 up (+ the
        # param's own TP axes always apply to its slots). Shapes only
        # here: the slots themselves are born on their shards below.
        slots, _ = jax.eval_shape(optimizer.functional_init, self.params)
        self.slot_specs = {}
        for k, slot_dict in slots.items():
            base = self.param_specs.get(k, P())
            per = {}
            for sname, arr in slot_dict.items():
                if arr.ndim == 0:
                    per[sname] = P()
                elif zero_stage >= 1:
                    per[sname] = zero_shard_spec(
                        base, arr.shape, zero_axis_size=shard_n)
                else:
                    per[sname] = base
            self.slot_specs[k] = per

        # Stage>=2: pin grads to the PARAM layout at the autodiff boundary
        # (see make_train_step). Left unpinned, GSPMD backward-propagates
        # the slot shardings ('sharding' on a hidden dim) through the
        # param-grad einsums onto batch-sharded activation grads — a
        # batch-dim→hidden-dim transition it can only satisfy by
        # "involuntary full rematerialization" (the compiler's warning:
        # replicate-then-repartition of every activation grad).
        # Pinned to the param spec, grads materialize via a plain
        # reduction over the batch axes and the slot-sharded update
        # consumes a local slice; XLA's allreduce+slice→reduce-scatter
        # reassociation supplies the ZeRO-2 comm pattern on TPU.
        self.grad_shardings = None
        if zero_stage >= 2:
            self.grad_shardings = {
                k: NamedSharding(self.mesh, self.param_specs.get(k, P()))
                for k in self.params}

        self.batch_spec = batch_spec  # None → infer batch-dim sharding
        self.grad_accum = grad_accum
        self.check_finite = bool(check_finite)
        self._step_fn = make_train_step(model, optimizer, loss_fn,
                                        grad_accum=grad_accum,
                                        clip_global_norm=clip_global_norm,
                                        amp_dtype=amp_dtype,
                                        recompute=recompute,
                                        grad_shardings=self.grad_shardings,
                                        check_finite=self.check_finite)
        if self.mesh.size > 1:
            # the step is partitioned over the mesh by GSPMD, which
            # cannot partition a Mosaic kernel: trace it with the `auto`
            # kernel flags resolving to their XLA compositions
            gspmd_step = self._step_fn

            def _step_fn(*args):
                with core_flags.auto_partitioned_region():
                    return gspmd_step(*args)
            self._step_fn = _step_fn

        # The state of the run that is no parameter: the model's
        # non-persistable buffers (the expert layers' load counts), a
        # small tree beside params and opt_state, replicated, in and out
        # of every step. Not donated: a few hundred bytes, and a reader
        # may hold a step's copy while later steps run. Empty for a model
        # that has none, and then the step is the program it always was.
        self.step_state = {
            name: b.data for name, b in model.named_buffers()
            if name not in sd}
        from ..nn.layer_moe import RoutedExperts
        # {layer path: (the layer, its counts' key in step_state)}
        self._expert_layers = {
            path: (sub, f"{path}.expert_load" if path else "expert_load")
            for path, sub in model.named_sublayers(include_self=True)
            if isinstance(sub, RoutedExperts)}
        # obs_metrics: the counts of the steps not yet on the /metrics
        # page, oldest first, and what the page has had (_publish_load)
        self._load_seen: collections.deque = collections.deque(
            maxlen=max(int(inflight_window), 1) + 2)
        self._load_shown: Dict[str, np.ndarray] = {}

        ns = lambda spec: NamedSharding(self.mesh, spec)
        param_sh = {k: ns(s) for k, s in self.param_specs.items()}
        slot_sh = ({k: {n: ns(s) for n, s in d.items()}
                    for k, d in self.slot_specs.items()}, ns(P()))
        self._param_sh, self._slot_sh = param_sh, slot_sh
        self._state_sh = {k: ns(P()) for k in self.step_state}
        self._donate = donate

        # Dispatch/trace accounting: one dispatch per _jit/_jit_many
        # call, one trace per actual XLA recompile (the Python body of a
        # jitted fn only runs while tracing — the increment is the
        # standard trace-side-effect counter). hits = dispatches - traces
        # is the executable-cache hit count bench.py reports.
        self.dispatch_count = 0
        self.trace_count = 0
        self._seen_sigs: Dict[str, set] = {}
        self._retrace_warned = False
        # None when debug_jit_sanitizer is off: the hot path pays one
        # pointer test per dispatch, nothing else (core/locks.py idiom)
        self._jsan = jit_sanitizer.site("ParallelEngine")

        self._jit = self._jit_body(self._step_fn, "counted_step")
        self._jit_many_cache: Dict[int, Callable] = {}

        self.train_steps_per_sync = max(int(train_steps_per_sync), 1)
        self.inflight_window = max(int(inflight_window), 1)
        self._inflight: collections.deque = collections.deque()

        # Place initial state on the mesh. The engine must OWN its param
        # buffers: with donate=True the first step donates them, and
        # device_put elides same-device copies PER SHARD — not only for
        # equivalent shardings but also e.g. single-device → replicated-
        # on-mesh, where the origin device's shard aliases the Layer's
        # own array (verified by pointer probe on the CPU sim; the PR 1
        # metadata-equivalence gate missed exactly this case and a
        # donated step deleted a live BertModel embedding out from under
        # the fluid.io registry). So copy UNCONDITIONALLY before
        # placement: one async elementwise copy per param at init, no
        # device sync (never probe buffer pointers here — that
        # serializes the async placement, PR 1's perf lesson).
        def _owned(v, sh):
            if isinstance(v, jax.Array):
                try:
                    return jax.device_put(jnp.array(v, copy=True), sh)
                except Exception:
                    pass  # exotic leaf: plain placement (donation of an
                    # alias is then possible — but nothing reached this
                    # in practice; numeric params always copy above)
            # exotic-leaf fallback; numeric params always copy above
            return jax.device_put(v, sh)  # noqa: donated-alias — see above

        self.params = {k: _owned(v, param_sh[k])
                       for k, v in self.params.items()}
        self.step_state = {k: _owned(v, self._state_sh[k])
                           for k, v in self.step_state.items()}
        # The optimizer state is born on its shards, as the outputs of
        # a jit that carry the slot shardings: built eagerly it would
        # stand whole on the default device first (found on four chips,
        # PR 23: device 0 peaked as high as a one-chip run), which a
        # model that only fits sharded cannot afford. Outputs of a jit
        # are freshly allocated — nothing else holds them to alias.
        self.opt_state = jax.jit(optimizer.functional_init,
                                 out_shardings=slot_sh)(self.params)

        # HBM census (ISSUE 13): tag the engine's device state so
        # obs.hbm.census() can attribute live bytes per subsystem.
        # Weakref-held — a list append, no registry touch, dies with
        # the engine (the structural-zero discipline).
        obs_hbm.register("params", self, lambda e: e.params,
                         name="ParallelEngine.params")
        obs_hbm.register("opt_state", self, lambda e: e.opt_state,
                         name="ParallelEngine.opt_state")
        # the Layer's own buffers are a separate live copy (the engine
        # copies unconditionally at init — the donation-aliasing
        # lesson); after a donate=False sync_model they alias the
        # engine's arrays, which the census dedups by buffer identity.
        # Tensor handles captured once ON THE ENGINE — state_dict()
        # per census walk would put a module sweep on the per-step
        # publish path, and capturing them in the getter closure would
        # pin the model past the weakref's lifetime
        self._obs_model_tensors = tuple(model.state_dict().values())
        obs_hbm.register(
            "params", self,
            lambda e: [t.data for t in e._obs_model_tensors],
            name="ParallelEngine.model")
        # per-signature executable cost (obs.costmodel), computed
        # lazily on the first INSTRUMENTED dispatch of each signature
        self._cost_cache: Dict[tuple, Any] = {}
        # the host's phases of the last PHASE_RING dispatches, always on
        self._phases: collections.deque = collections.deque(
            maxlen=PHASE_RING)
        self._preplaced_ns = 0       # shard_batch() time not yet in a record
        self._preplaced_calls = 0
        # (kind, k, signature) of the last dispatch and the abstract
        # batch of each signature: what compiled_step_text() lowers
        self._last_run: Optional[tuple] = None
        self._sig_batch: Dict[tuple, Any] = {}
        self._step_text: Dict[tuple, str] = {}

    # -- data placement -----------------------------------------------------

    def shard_batch(self, batch):
        """Host batch → device arrays sharded batch-dim over (dp, sharding).

        The time spent here goes into the next dispatch's
        :class:`StepPhases` record under ``shard_ns``, beside the
        pass-through placement ``step`` then makes itself."""
        t0 = time.time_ns()
        with TraceAnnotation("train/shard"):
            placed = self._place_batch(batch)
        self._preplaced_ns += time.time_ns() - t0
        self._preplaced_calls += 1
        return placed

    def _place_batch(self, batch):
        multi = jax.process_count() > 1
        # multi-host: keep leaves on HOST — make_array_from_process_local_data
        # consumes numpy directly; converting to device first would buy a
        # device→host→device round-trip per leaf per step
        # multi-host leaves stay numpy (host RAM); single-host leaves go
        # through _as_arrays as before
        arrs = jax.tree_util.tree_map(
            lambda x: x if isinstance(x, jax.Array)  # pre-staged leaf
            else np.asarray(x.data if isinstance(x, Tensor) else x),
            batch, is_leaf=lambda x: isinstance(x, Tensor)) \
            if multi else _as_arrays(batch)
        spec = self.batch_spec

        def place(a):
            # pass-through for leaves that are already global jax Arrays
            # on this mesh (pre-staged batches re-fed to step): re-
            # sharding would be a no-op single-host but np.asarray on a
            # non-fully-addressable Array raises multi-host. The check is
            # mesh IDENTITY (same device array, same order), not just
            # axis-size equality (ADVICE r5): a same-shaped mesh over
            # different devices (or a different device order) must be
            # re-placed, or the step consumes misplaced data.
            if isinstance(a, jax.Array) and not isinstance(
                    a, jax.core.Tracer):
                sh = getattr(a, "sharding", None)
                m = getattr(sh, "mesh", None)
                devs = getattr(m, "devices", None)
                if m is not None and devs is not None and (
                        m is self.mesh
                        or (getattr(m, "axis_names", None)
                            == self.mesh.axis_names
                            and np.shape(devs)
                            == np.shape(self.mesh.devices)
                            and np.asarray(devs).tolist()
                            == np.asarray(self.mesh.devices).tolist())):
                    return a
                # different mesh → fall through and re-place the leaf
            s = spec if spec is not None else data_partition_spec(
                tuple(ax for ax in ("dp", "sharding")
                      if ax in self.mesh.shape))
            axes = list(s)
            if self.grad_accum > 1:
                axes = [None] + axes  # leading dim = accumulation steps
            # every leaf must carry the leading accumulation dim under
            # grad_accum (lax.scan consumes the whole batch pytree as xs,
            # scalars included) — a leaf missing it would scan the batch
            # dim or die inside scan; error at placement, where the
            # message can say so, not at jit trace time. With
            # grad_accum=1, 0-d leaves (loss weights, step counters) and
            # trailing spec axes absent from a leaf (e.g. a per-sample
            # weight without the seq dim) truncate-and-replicate.
            if (self.grad_accum > 1
                    and (a.ndim == 0 or a.shape[0] != self.grad_accum)):
                from ..core.errors import InvalidArgumentError
                raise InvalidArgumentError(
                    f"grad_accum={self.grad_accum} needs every batch leaf "
                    "shaped [grad_accum, ...] (scalars too — broadcast "
                    "them to the accumulation dim or close over them in "
                    "loss_fn); got leaf with shape "
                    f"{tuple(a.shape)}")
            axes = axes[:a.ndim]
            ndim_spec = P(*(axes + [None] * (a.ndim - len(axes))))
            sh = NamedSharding(self.mesh, ndim_spec)
            if multi and not isinstance(a, jax.Array):
                # multi-host: each process feeds its LOCAL batch shard;
                # assemble the global array over the coordination service
                # (reference: each trainer feeds its own data partition)
                return jax.make_array_from_process_local_data(sh, a)
            # numpy single-host, or a jax.Array from a DIFFERENT mesh
            # (device_put reshards global arrays on either topology)
            return jax.device_put(a, sh)  # noqa: donated-alias — batch leaves are never donated
        return jax.tree_util.tree_map(place, arrs)

    # -- training -----------------------------------------------------------

    def _jit_body(self, body, name: str, counted: bool = True):
        """``body`` (the step, or the k-step scan over it) jitted over
        the mesh under ``name``. ``counted=False`` gives the same
        program without the trace-side-effect counter, for lowerings
        that must leave ``cache_stats()`` alone (``step_cost``,
        ``compiled_step_text``): the same name, arguments, shardings and
        donation make the same module, hence the same entry of the
        persistent compile cache."""
        def fn(params, opt_state, batch, key, lr, step_state):
            if counted:
                self.trace_count += 1
            return body(params, opt_state, batch, key, lr, step_state)

        fn.__name__ = fn.__qualname__ = name
        return jax.jit(
            fn,
            in_shardings=(self._param_sh, self._slot_sh, None, None, None,
                          self._state_sh),
            out_shardings=(NamedSharding(self.mesh, P()), self._param_sh,
                           self._slot_sh, self._state_sh),
            donate_argnums=(0, 1) if self._donate else ())

    def _shape_sig(self, tree) -> tuple:
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        return (str(treedef),) + tuple(
            (tuple(np.shape(l)), str(getattr(l, "dtype", type(l))))
            for l in leaves)

    def _guard_retrace(self, kind: str, batch, feeds=None) -> tuple:
        """Warn once when a new batch-shape signature forces a retrace
        (each retrace is a full XLA recompile — the silent host-loop
        serializer the jit_retrace_warn flag exists to surface).
        Returns the signature so instrumentation (step_cost) reuses it
        instead of re-walking the batch tree. ``feeds`` is what the
        dispatch hands the executable where that is not ``batch``
        (``step_many``'s stack)."""
        seen = self._seen_sigs.setdefault(kind, set())
        sig = self._shape_sig(batch)
        if sig in seen:
            return sig
        if self._jsan is not None:
            # sanitizer lane: the warn-once below becomes enforceable —
            # a site compiling past its signature limit raises typed
            self._jsan.note_signatures(len(seen) + 1, kind=kind)
        if seen and not self._retrace_warned \
                and core_flags.flag("jit_retrace_warn"):
            self._retrace_warned = True
            import warnings
            warnings.warn(
                f"ParallelEngine.{kind} is retracing: batch arrived with "
                f"a new shape signature (seen {len(seen)} before). Each "
                "distinct shape costs a full XLA compile — pad or bucket "
                "batches to fixed shapes (set FLAGS_jit_retrace_warn=0 "
                "to silence).")
        seen.add(sig)
        # the shapes (not the arrays) of the signature, for
        # compiled_step_text() to lower the same program later
        self._sig_batch[(kind, sig)] = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(
                a.shape, a.dtype, sharding=getattr(a, "sharding", None)),
            batch if feeds is None else feeds)
        return sig

    def _push_inflight(self, fut: LossFuture) -> LossFuture:
        self._inflight.append(fut)
        with TraceAnnotation("train/inflight_wait"):
            while len(self._inflight) > self.inflight_window:
                # bound dispatch run-ahead: wait on (don't read back)
                # the oldest outstanding executable
                self._inflight.popleft().block()
        return fut

    def _note_phases(self, m, k: int, kind: str, batch, sig, stamps,
                     compiled: bool) -> None:
        """The one set of stamps of a dispatch (``time.time_ns()`` at
        each phase boundary) and its three readers: the ring, the JSONL
        span sink and the ``obs_metrics`` histograms. The profiler reads
        the same phases through the ``TraceAnnotation``s round them."""
        t0, t1, t2, t3, t4 = stamps
        shard_ns = (t1 - t0) + self._preplaced_ns
        self._phases.append(StepPhases(
            self.dispatch_count, t0, shard_ns, t2 - t1, t3 - t2, t4 - t3,
            compiled, k + self._preplaced_calls, k))
        self._preplaced_ns = self._preplaced_calls = 0
        self._last_run = (kind, k, sig)
        obs_trace.record_phases(
            "train/step" if k == 1 else "train/step_many", _PHASES, stamps,
            cat="Engine", args={"k": k} if k > 1 else None)
        if m is not None:
            m.histogram("train_shard_seconds").observe(shard_ns * 1e-9)
            m.histogram("train_dispatch_seconds").observe((t3 - t2) * 1e-9)
            m.histogram("train_inflight_wait_seconds").observe(
                (t4 - t3) * 1e-9)
            cost = self._cost_cache.get(sig)
            if cost is None:
                # once a signature, never per step: the lowering traces
                cost = self.step_cost(batch, sharded=True, sig=sig)
            if cost.flops:
                m.gauge("train_step_flops").set(cost.flops)
                m.gauge("train_step_bytes").set(cost.bytes_accessed)
                m.gauge("train_cost_exact").set(1.0 if cost.exact else 0.0)
            _obs_note_steps(m, k, self._obs_rows(batch, self.grad_accum),
                            t3 * 1e-9)
            if self._expert_layers:
                self._load_seen.append(self._load_arrays())

    def phase_records(self) -> list:
        """The :class:`StepPhases` of the last ``PHASE_RING``
        dispatches, oldest first."""
        return list(self._phases)

    def _load_arrays(self) -> Dict[str, Any]:
        """{layer path: the newest step's ``expert_load`` array}."""
        return {path: self.step_state[key]
                for path, (_, key) in self._expert_layers.items()}

    def expert_load(self) -> Dict[str, Dict[str, Any]]:
        """What every ``RoutedExperts`` layer of the model has counted on
        the device (the engine starts from the layer's own counts, as it
        does from its parameters), ``{layer path: {"rows":
        [held], "held_picks", "late_picks", "late_steps", "steps",
        "picks_made_a_step", "capacity_rows", "held", "num_experts"}}``
        (``nn/layer_moe.py``'s docstring says what each is); ``{}`` for a
        model with no such layer. One ``device_get``, on demand: it waits
        for the newest dispatched step, as any read of ``engine.params``
        does. The counts are 32-bit totals: a field reads negative after
        2^31 (see the layer's docstring); the ``moe_*`` series of the
        process registry are fed by differences and do not."""
        counts = jax.device_get(self._load_arrays())
        return {path: self._expert_layers[path][0].read_load(c)
                for path, c in counts.items()}

    def _publish_load(self, m) -> None:
        """``obs_metrics``, at a loss readback: the newest counts whose
        step has finished (no wait: the step whose loss was just read
        has) go to the process registry as differences since the last
        call, modulo 2^32. ``moe_picks_held_total{layer}``,
        ``moe_picks_late_total{layer}``, ``moe_late_steps_total{layer}``,
        ``moe_expert_rows_total{layer,expert}`` and the gauge
        ``moe_capacity_rows{layer}``."""
        ready = None
        while self._load_seen and all(
                a.is_ready() for a in self._load_seen[0].values()):
            ready = self._load_seen.popleft()
        if ready is None:
            return
        from ..obs import registry as obs_registry
        by_layer = obs_registry.process_group("layer")
        by_expert = obs_registry.process_group(("layer", "expert"))
        for path, now in jax.device_get(ready).items():
            layer = self._expert_layers[path][0]
            now = now.astype(np.int64)
            new = (now - self._load_shown.get(path, 0)) & 0xFFFFFFFF
            self._load_shown[path] = now
            load = layer.read_load(new)
            one = by_layer.child(path or ".")
            one.counter("moe_picks_held_total").inc(load["held_picks"])
            one.counter("moe_picks_late_total").inc(load["late_picks"])
            one.counter("moe_late_steps_total").inc(load["late_steps"])
            one.gauge("moe_capacity_rows").set(load["capacity_rows"] or 0)
            for e, rows in enumerate(load["rows"]):
                by_expert.child((path or ".", layer.first + e)).counter(
                    "moe_expert_rows_total").inc(rows)

    # -- per-step observability (obs_metrics flag; ISSUE 10) ---------------

    @staticmethod
    def _obs_rows(batch, grad_accum: int) -> int:
        """Leading-dim sample count of one (sharded) batch — the
        samples/s numerator. Under grad_accum the leading dim is the
        accumulation axis and the per-micro-batch dim sits behind it."""
        leaves = jax.tree_util.tree_leaves(batch)
        if not leaves or np.ndim(leaves[0]) == 0:
            return 1
        shape = np.shape(leaves[0])
        if grad_accum > 1 and len(shape) > 1:
            return int(shape[0]) * int(shape[1])
        return int(shape[0])

    def step_cost(self, batch, sharded: bool = False, sig=None):
        """FLOPs + bytes of ONE optimizer step at this batch's shape
        signature (:class:`~paddle1_tpu.obs.costmodel.ExecutableCost`)
        — XLA cost analysis of the lowered train step, memoized per
        signature, labeled tree-size heuristic on failure. Called
        automatically per instrumented dispatch (``obs_metrics``,
        which hands the retrace guard's already-computed ``sig`` so
        the hot path never re-walks the batch tree); callable directly
        for on-demand attribution (bench --cost). One Python trace per
        new signature, no XLA compile."""
        if not sharded:
            batch = self.shard_batch(batch)
        if sig is None:
            sig = self._shape_sig(batch)
        c = self._cost_cache.get(sig)
        if c is None:
            def lower():
                # a SEPARATE jit of the uncounted step body: lowering
                # the counted self._jit would run its trace-side-effect
                # counters and corrupt the compile accounting the
                # acceptance gates read
                return self._jit_body(
                    self._step_fn, "counted_step", counted=False).lower(
                    self.params, self.opt_state, batch,
                    jax.random.key(0), jnp.asarray(0.0, jnp.float32),
                    self.step_state)

            fb = obs_costmodel.tree_size_cost(
                self.params, batch=batch, extra=self.opt_state)
            c = obs_costmodel.analyze(lower, fallback=fb)
            self._cost_cache[sig] = c
        return c

    def step(self, batch,  # hot-path: one dispatch per call
             lr: Optional[float] = None) -> LossFuture:
        m = _obs_step_registry()
        if m is not None:
            _ensure_readback_observer()
        lr_val = jnp.asarray(lr if lr is not None else
                             self.optimizer.get_lr(), jnp.float32)
        # one stamp at each phase boundary; _note_phases feeds every
        # reader from them
        t0 = time.time_ns()
        with TraceAnnotation("train/shard"):
            batch = self._place_batch(batch)
        t1 = time.time_ns()
        with TraceAnnotation("train/guard"):
            sig = self._guard_retrace("step", batch)
            self.dispatch_count += 1
            donated = None
            if self._jsan is not None and self._donate:
                donated = jax.tree_util.tree_leaves(
                    (self.params, self.opt_state))
                self._jsan.guard_args(donated, "step")
        t2 = time.time_ns()
        traces = self.trace_count
        with TraceAnnotation("train/dispatch"):
            loss, self.params, self.opt_state, self.step_state = self._jit(
                self.params, self.opt_state, batch, next_key(), lr_val,
                self.step_state)
            if donated is not None:
                # the old params/opt_state buffers were donated: poison
                # them so a use-after-donate (a stale alias anywhere)
                # fails deterministically instead of silently reading
                # XLA-owned storage on TPU while passing on CPU
                self._jsan.poison_donated(donated)
            sched = getattr(self.optimizer, "_learning_rate", None)
            if hasattr(sched, "step"):
                sched.step()
        t3 = time.time_ns()
        wrap = StepFuture if self.check_finite else LossFuture
        fut = self._push_inflight(wrap(loss))
        self._note_phases(m, 1, "step", batch, sig,
                          (t0, t1, t2, t3, time.time_ns()),
                          self.trace_count != traces)
        return fut

    def _many_body(self):
        """k optimizer steps as one ``lax.scan`` over the step."""
        def multi_step(params, opt_state, batches, keys, lrs, step_state):
            def body(carry, xs):
                p, s, st = carry
                b, key, lr_ = xs
                loss, p, s, st = self._step_fn(p, s, b, key, lr_, st)
                return (p, s, st), loss

            (params, opt_state, step_state), losses = jax.lax.scan(
                body, (params, opt_state, step_state), (batches, keys, lrs))
            return losses, params, opt_state, step_state
        return multi_step

    def _jit_many(self, k: int):
        fn = self._jit_many_cache.get(k)
        if fn is None:
            fn = self._jit_many_cache[k] = self._jit_body(
                self._many_body(), "multi_step")
        return fn

    def compiled_step_text(self) -> Optional[str]:
        """The HLO text of the compiled program that the last dispatch
        ran (``lower(...).compile().as_text()``): every instruction with
        its ``op_name``, the layer path and op that ``make_train_step``'s
        scopes gave it. ``obs.costmodel.step_op_scopes`` parses it.

        Lowers the UNCOUNTED body under the step's own module name, so
        ``cache_stats()`` stays as it is and the executable comes out of
        the persistent compile cache where one is kept. Still a trace, a
        lowering and a cache load: seconds at real size. Memoised per
        signature; for after a run, never for the step loop. None before
        the first dispatch."""
        if self._last_run is None:
            return None
        kind, k, sig = self._last_run
        text = self._step_text.get((kind, sig))
        if text is None:
            feeds = self._sig_batch[(kind, sig)]
            if k == 1:
                fn = self._jit_body(self._step_fn, "counted_step",
                                    counted=False)
                key, lr = jax.random.key(0), jnp.zeros((), jnp.float32)
            else:
                fn = self._jit_body(self._many_body(), "multi_step",
                                    counted=False)
                key = jax.random.split(jax.random.key(0), k)
                lr = jnp.zeros((k,), jnp.float32)
            text = fn.lower(self.params, self.opt_state, feeds, key, lr,
                            self.step_state).compile().as_text()
            self._step_text[(kind, sig)] = text
        return text

    def step_many(self, batches: Sequence[Any],  # hot-path: k steps, one dispatch
                  lr: Optional[float] = None) -> LossFuture:
        """Run ``len(batches)`` optimizer steps inside ONE jitted
        executable (``lax.scan`` over steps, composing with the
        grad-accum inner scan): one dispatch, one donation cycle, zero
        intermediate readbacks. Returns a lazy :class:`LossFuture` over
        the ``[k]`` loss vector; the LR schedule advances k times, and
        the RNG stream consumes k keys — bit-compatible with k
        sequential ``step`` calls."""
        k = len(batches)
        if k == 0:
            from ..core.errors import InvalidArgumentError
            raise InvalidArgumentError("step_many needs >= 1 batch")
        if k == 1:
            return self.step(batches[0], lr)
        m = _obs_step_registry()
        if m is not None:
            _ensure_readback_observer()
        t0 = time.time_ns()
        with TraceAnnotation("train/shard"):
            sharded = [self._place_batch(b) for b in batches]
            stacked = jax.tree_util.tree_map(
                lambda *xs: jnp.stack(xs), *sharded)
        t1 = time.time_ns()
        kind = f"step_many[k={k}]"
        with TraceAnnotation("train/guard"):
            sig = self._guard_retrace(kind, sharded[0], feeds=stacked)
            sched = getattr(self.optimizer, "_learning_rate", None)
            lrs = []
            for _ in range(k):
                lrs.append(lr if lr is not None
                           else self.optimizer.get_lr())
                if hasattr(sched, "step"):
                    sched.step()
            lrs = jnp.asarray(lrs, jnp.float32)
            keys = jnp.stack([next_key() for _ in range(k)])
            self.dispatch_count += 1
            donated = None
            if self._jsan is not None and self._donate:
                donated = jax.tree_util.tree_leaves(
                    (self.params, self.opt_state))
                self._jsan.guard_args(donated, "step_many")
        t2 = time.time_ns()
        traces = self.trace_count
        with TraceAnnotation("train/dispatch"):
            losses, self.params, self.opt_state, self.step_state = \
                self._jit_many(k)(self.params, self.opt_state, stacked,
                                  keys, lrs, self.step_state)
            if donated is not None:
                self._jsan.poison_donated(donated)
        t3 = time.time_ns()
        # check_finite: the scan body already emits packed [loss,
        # notfinite] pairs, so `losses` is [k, 2] and the per-step flags
        # ride the same single readback
        wrap = StepFuture if self.check_finite else LossFuture
        fut = self._push_inflight(wrap(losses))
        # cost of the k-step scan = k x the single-step executable
        # (same signature — the scan body IS the step fn)
        self._note_phases(m, k, kind, sharded[0], sig,
                          (t0, t1, t2, t3, time.time_ns()),
                          self.trace_count != traces)
        return fut

    def step_stream(self, batches, lr: Optional[float] = None):
        """Drive training from any batch iterable at the engine's
        ``train_steps_per_sync`` chunk size: full chunks dispatch through
        ``step_many`` (pulling pre-staged device batches via the
        iterator's ``peek_many`` when it has one — io.DataLoader's
        buffered readers do); a short trailing chunk falls back to
        sequential ``step`` so the remainder never compiles a fresh
        scan. Yields one LossFuture per dispatch."""
        k = self.train_steps_per_sync
        it = iter(batches)
        # hot-path: the engine step loop (syncs here stall dispatch)
        with jit_sanitizer.hot_section("engine_step_loop"):
            yield from self._step_stream(it, k, lr)

    def _step_stream(self, it, k: int, lr: Optional[float]):  # hot-path
        while True:
            m = _obs_step_registry()
            t0 = time.perf_counter() if m is not None else 0.0
            if hasattr(it, "peek_many"):
                try:
                    chunk = it.peek_many(k)
                except StopIteration:
                    return
            else:
                chunk = []
                for _ in range(k):
                    try:
                        chunk.append(next(it))
                    except StopIteration:
                        break
            if m is not None:
                # host data wait: time the step loop spent blocked on
                # the input pipeline before it could even dispatch
                m.histogram("train_data_wait_seconds").observe(
                    time.perf_counter() - t0)
            if not chunk:
                return
            if len(chunk) == k and k > 1:
                yield self.step_many(chunk, lr)
            else:
                for b in chunk:
                    yield self.step(b, lr)
                if len(chunk) < k:
                    return

    def drain(self) -> None:
        """Block until every in-flight dispatched step has finished on
        device (no readback — a sync, not a fetch). Required before
        reading params for checkpointing/eval; ``sync_model``/
        ``save_checkpoint`` call it."""
        while self._inflight:
            self._inflight.popleft().block()
        jax.block_until_ready(self.params)

    def cache_stats(self) -> Dict[str, int]:
        """Executable-cache accounting: every retrace is a miss, every
        dispatch that reused a compiled executable is a hit."""
        return {"hits": self.dispatch_count - self.trace_count,
                "misses": self.trace_count}

    def sync_model(self) -> None:
        """Write engine params back into the Layer (for save/eval).
        Drains in-flight multi-step work first. With donation on, the
        Layer gets sharding-preserving COPIES — handing it the engine's
        live buffers would let the next donating step delete the
        model's tensors out from under eager code / registry saves
        (the resume-then-continue-training pattern ResilientTrainer
        relies on)."""
        self.drain()
        sd = self.model.state_dict()
        for k, arr in self.params.items():
            if k in sd:
                sd[k]._data = jnp.array(arr, copy=True) if self._donate \
                    else arr
        for k, b in self.model.named_buffers():
            if k in self.step_state:        # never donated: no copy
                b._data = self.step_state[k]

    # -- sharded checkpoint (reference save_persistables sliced-vars
    # analog; see distributed/checkpoint.py) ---------------------------------

    def save_checkpoint(self, path: str) -> str:
        """Save params + optimizer state shard-by-shard (each process
        writes what it owns — no host gather, ZeRO-compatible). Drains
        in-flight multi-step work first."""
        self.drain()
        from . import checkpoint as dckpt
        return dckpt.save_sharded(path, {"params": self.params,
                                         "opt_state": self.opt_state})

    def load_checkpoint(self, path: str) -> None:
        """Restore directly into the engine's current shardings and push
        the weights back into the Layer."""
        from . import checkpoint as dckpt
        restored = dckpt.load_sharded(path, {"params": self.params,
                                             "opt_state": self.opt_state})
        self.params = restored["params"]
        self.opt_state = restored["opt_state"]
        self.sync_model()

    @property
    def train_step_fn(self):
        return self._jit
