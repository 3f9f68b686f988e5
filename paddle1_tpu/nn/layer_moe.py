"""A routed-expert feed-forward (mixture of experts) that is told which
experts it holds; no reference analog (the reference's model-parallel
layers split one dense product, ``fleet/meta_parallel``).

The layer routes every token over ALL ``num_experts`` of the model, and
computes the part of the result that the ``held`` experts give: what one
chip of an expert-parallel deployment computes of the layer. What the
absent experts would add is left out (on several chips it arrives by an
exchange this layer does not make), the shared experts' output is added
whole. Per token ``u`` (``scoring="sigmoid"``: DeepSeek-V3's router,
``topk_method: noaux_tc`` with one group; ``scoring="softmax"``: ``p =
softmax(float32(u) W_g)`` over all the experts, chosen = the ``top_k`` of
``p``, ``w = p[chosen] / sum p[chosen] * routed_scaling_factor``, no
selection bias):

  ``s = sigmoid(float32(u) W_g)`` over all the experts;
  chosen = the ``top_k`` of ``s + b``: the selection bias ``b`` takes part
  in the choice and not in the weight, is a buffer and has no gradient;
  ``w = s[chosen] / (sum s[chosen] + norm_eps) * routed_scaling_factor``
  (``norm_eps`` 1e-20 unless the constructor is told another);
  ``y = sum_{e chosen and held} w_e E_e(u) + S(u)``, ``E_e`` and ``S``
  SwiGLU feed-forwards.

Two things a caller may say beside that (SmallThinker says both). **What
the router reads**: ``forward(x, router_input=r)`` scores and chooses from
``r`` and computes the experts from ``x`` (``s = score(float32(r) W_g)``,
``y = sum w_e E_e(x)``): a model whose router stands before its attention
block routes on that block's input and feeds the experts the stream after
it, so the choice, the sorts and the counts wait for ``r`` alone. ``r``
has ``x``'s leading shape; left out, it is ``x``. **The gate's
activation**: ``E_e(u) = (act(u W_gate_e) * (u W_up_e)) W_down_e`` with
``act`` the constructor's ``gate_activation``, ``"silu"`` (SwiGLU) unless
told ``"relu"`` (ReGLU); the overflow path takes the same one. The shared
experts stay SwiGLU. **An expert without a gate** (``gated=False``, the
``nemotron_h`` family's): ``E_e(u) = act(u W_up_e) W_down_e`` with ``act``
the same constructor argument, which then also takes ``"relu2"``
(``relu(.)^2``): the first grouped product's operand is ``up_proj``
``[held, d_model, width]``, not a fused ``gate_up_proj`` of twice the
width, and the shared expert has the same form at its own width
(``nn.PlainFeedForward``). The sort, the gathers, the sum over picks and
the counters are the gated layer's.

**No token is dropped, shapes are static, and the work follows the picks
that land here.** The ``tokens x top_k`` picks are sorted by held expert
(the picks of absent experts last); the first ``capacity`` rows of that
order are gathered, run through two grouped matrix products whose groups
are the held experts' picks, weighted and summed back per token.
``capacity`` is ``CAPACITY_FACTOR`` (3) times the picks this share expects
under even routing (``tokens x top_k x held / num_experts``), a multiple
of 512, at most every pick. A share that computes alone is favoured by
its own router as training goes on (the absent experts add nothing, so
the gradient lifts the held ones): at twice the even count Kanana-2's
share ran out of rows some 67 steps into a run on the chip, at three
times it does not within 68 (PERF.md section 6, PR 31). The picks of
held experts beyond it, under any imbalance up to every token choosing
held experts alone, run under a ``lax.cond`` through each held expert
over all tokens with a mask: slow, exact, and not executed while the
main path holds them all. No data movement is a scatter, forward or
backward: the transpose of "rows in sorted order" (a gather of
``capacity`` rows from the tokens) is "sum a token's picks", and that
reads the rows that hold a pick and no other: ``capacity`` rows of the
hidden width relaid so that a row is whole tiles, each held row fetched
once into a zeroed ``[top_k, block, hidden]`` buffer in VMEM, ``tokens``
rows written (``ops/pallas/sum_picks.py``: any hidden width of a whole
number of 32-bit lane rows, a multiple of 256 two-byte or 128 four-byte
elements; whole ``(8, 128)`` tiles as ``[hidden / 128, 128]``, any other
as ``[depth, hidden / depth]``, PR 44; the pick weights' vector and rows
of another width take a gather with an index for every pick). A gather
over every pick paid 36.7 ns for each of the 98,304 rows it fetched from
HBM at Kanana-2's size, 87% of them a row of zeros (PERF.md section 6,
PR 32), and 46.8 ns a row at SmallThinker's 2,560 (PR 43). Which arm a
traced sum over rows took is counted in the process registry:
``p1t_moe_sum_picks_arm_total{arm="kernel"|"gather"}``.

**The load is counted where it falls** (PR 36): every forward adds to
the buffer ``expert_load`` (int32 ``[held + 4]``, non-persistable: state
of a run, not of the model, so not in ``state_dict()``) what
``sort_picks`` already made: the rows each held expert ran within the
capacity, then ``held_picks`` (those rows plus the late picks),
``late_picks`` (held picks that found no row and went through
``moe_overflow``), ``late_steps`` (forwards with a late pick) and
``steps`` (forwards counted: one a step, one a micro-step under
gradient accumulation). Exact integers, on the device, always on: the
eager path adds into the buffer, a compiled step records the add through
``nn/functional/norm.py::record_state_update`` and ``ParallelEngine``
carries the buffer beside the parameters (``ParallelEngine.expert_load()``
reads it; :meth:`RoutedExperts.read_load` names the fields). A forward
compiled by the user's own ``jax.jit`` has no collector and counts
nothing. 32 bits: a raw read wraps to negative after 2^31 of a field
(131,072 held picks a step reach that in 16 k steps); a reader that takes
differences modulo 2^32 at least that often stays exact, as the process
registry's ``moe_*`` series do.

**Under ``fleet.utils.recompute``** the small things that are dear to
make again carry a name (``core/recompute_keeps.py``) and are kept: a
token's picked scores and experts (:func:`route`), the sorted order, each
pick's row, the rows an expert and the late picks (:func:`sort_picks`),
and the pick weights in sorted order: 1.6 MB a layer at Kanana-2's size
against a ``top_k``, two sorts and two gathers of scalars (3 ms a layer;
PERF.md, PR 37). The gathers of whole rows and the grouped products run
again: they are cheap for their bytes.

**The grouped products have two arms, and the operands' shape chooses**
(:func:`grouped_matmul`). The TPU's compiler lowers ``lax.ragged_dot`` to
a kernel of its own that walks the (row tile of 512, group) pairs that
hold a row (not the capacity: a fill of a third of the rows takes a third
of the time) with each width tiled at the largest power of two up to 512
that divides it. At 512-wide tiles a pair runs at the matrix unit's peak
(Kanana-2, SDAR, LFM2, SmallThinker, Laguna-XS.2: arm ``xla``). Where a
width falls to 128 (Nemotron 3 Nano's 2688 = 21 x 128 and 1856 = 29 x 64:
a pair is 21 x 15 grid steps of 17 MFLOP, five times slower than the
pairs' work; PERF.md section 6, PR 51) the products, and their two
transposes, are ``ops/pallas/grouped_matmul.py``'s: the same pairs at
tiles that fit the widths, whole where they fit VMEM (arm ``kernel``).
No flag, argument or model's name: ``supported(rows, weights)`` there
reads the widths. Which arm a traced product took is counted in the
process registry: ``p1t_moe_grouped_matmul_arm_total{arm="kernel"|"xla"}``
(two a layer's forward, three under ``fleet.utils.recompute``, whose
segment jax traces again for the backward pass).

Names in a traced step: everything under the scope ``moe``; ops
``moe_router`` (float32 whatever the autocast), ``moe_dispatch`` (sort,
gather; backward, the kernel ``p1t_sum_picks_fwd``), ``routed_experts``
(the grouped products: XLA's ``ragged-dot-none``, which carries the scope
as a frontend attribute, or the kernels ``p1t_grouped_matmul_fwd``,
``p1t_grouped_matmul_bwd_dx`` and ``p1t_grouped_matmul_bwd_dw``, which carry it in
their ``op_name``), ``moe_combine`` (``p1t_sum_picks_fwd`` again),
``moe_overflow``; the shared experts a ``GatedFeedForward`` (without a
gate: a ``PlainFeedForward``) named ``shared_experts``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental.xla_metadata import set_xla_metadata

from ..autograd.engine import apply, scope
from ..core.flags import in_auto_partitioned_region
from ..core.recompute_keeps import keep_in_recompute
from ..core.tensor import Tensor
from ..obs.costmodel import SCOPE_ATTRIBUTE
from ..obs.registry import process_group
from ..ops.pallas import grouped_matmul as grouped_kernel
from ..ops.pallas import sum_picks as sum_picks_kernel
from .functional.norm import record_state_update
from .initializer import Constant
from .layer_base import Layer
from .layer_transformer import GatedFeedForward, PlainFeedForward

__all__ = ["RoutedExperts"]

_ROW_TILE = 512     # capacity is a multiple: a grouped product's row tile
CAPACITY_FACTOR = 3  # rows of the grouped products over even routing's picks
# ``expert_load`` behind its first ``held`` entries (rows an expert)
LOAD_TAIL = ("held_picks", "late_picks", "late_steps", "steps")


def route(x, w_gate, bias, top_k, scale, scoring="sigmoid",
          norm_eps=1e-20):
    """[tokens, hidden] -> (weights float32 [tokens, top_k], experts int32
    [tokens, top_k]). Float32 products whatever x and w_gate arrive in:
    a score rounded to bf16 moves the choice. ``scoring``: ``sigmoid``
    (DeepSeek-V3's rule, the module's docstring) or ``softmax`` (the
    Qwen3-MoE family's, ``norm_topk_prob`` true: probabilities over all
    the experts, the ``top_k`` largest, renormalised to sum ``scale``;
    ``bias`` takes no part). ``norm_eps``: what the sigmoid rule adds to
    the picked scores' sum before it divides (DeepSeek-V3's file 1e-20,
    the LFM2 family's 1e-6)."""
    logits = jnp.dot(x.astype(jnp.float32), w_gate.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    if scoring == "softmax":
        picked, chosen = top_scores(jax.nn.softmax(logits, axis=-1), top_k)
        weights = picked / jnp.sum(picked, -1, keepdims=True) * scale
        return weights, chosen
    s = jax.nn.sigmoid(logits)
    chosen = _named_chosen(
        jax.lax.top_k(s + bias.astype(jnp.float32), top_k)[1])
    picked = _named_scores(jnp.take_along_axis(s, chosen, axis=-1))
    weights = picked / (jnp.sum(picked, -1, keepdims=True)
                        + norm_eps) * scale
    return weights, chosen


# A token's picked scores (float32) and experts (int32), each under its
# name: a recomputed segment keeps 8 bytes a pick and runs no second
# ``top_k`` over every token's scores (0.20 ms for 0.39 MB of Kanana-2's
# picks, 0.5 ms a MB on a v5e) nor the gather of the picked ones (0.78
# ms, 2.0 ms a MB; PERF.md PR 37). Every later reader takes the named
# value: one that reads what ``top_k`` itself returned makes the segment
# run it again.
def _named_scores(picked):
    return keep_in_recompute(picked, "routed_scores")


def _named_chosen(chosen):
    return keep_in_recompute(chosen.astype(jnp.int32), "routed_chosen")


def _top_scores(scores, k):
    values, chosen = jax.lax.top_k(scores, k)
    return _named_scores(values), _named_chosen(chosen)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def top_scores(scores, k):
    """``jax.lax.top_k`` over the last axis -> (values, int32 indices),
    both named, with a backward pass that reads the named indices
    (``jax.lax.top_k``'s own reads the ones it made, so a recomputed
    segment would make them again): a value's gradient goes to the score
    it was picked from."""
    return _top_scores(scores, k)


def _top_scores_fwd(scores, k):
    # not through ``top_scores``: a segment's policy has to see the names
    values, chosen = _top_scores(scores, k)
    return (values, chosen), (chosen, jax.ShapeDtypeStruct(scores.shape,
                                                           scores.dtype))


def _top_scores_bwd(k, res, d):
    chosen, scores = res
    return jax.linear_transpose(
        lambda s: jnp.take_along_axis(s, chosen, axis=-1), scores)(d[0])


top_scores.defvjp(_top_scores_fwd, _top_scores_bwd)


def sort_picks(chosen, first, held, capacity):
    """The picks in the order the grouped products take them.

    -> (``order`` [capacity]: the flat pick (token * top_k + slot) at each
    sorted row; ``where`` [tokens * top_k]: the sorted row of each pick, or
    ``capacity`` (no row: it adds nothing) for a pick that is not held or
    lies beyond the capacity; ``sizes`` [held]: rows of each held expert
    within the capacity; ``overflow``: held picks beyond it)."""
    local = chosen.reshape(-1) - first
    here = (local >= 0) & (local < held)
    key = jnp.where(here, local, held)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    row = jnp.argsort(order).astype(jnp.int32)      # the inverse
    counts = jnp.sum(key[:, None] == jnp.arange(held)[None, :], axis=0,
                     dtype=jnp.int32)
    ends = jnp.cumsum(counts)
    sizes = jnp.minimum(ends, capacity) - jnp.minimum(ends - counts, capacity)
    where = jnp.where(here & (row < capacity), row, capacity)
    # a recomputed segment keeps the four: 4 bytes a pick and a row
    # against two ``argsort``s over every pick (0.16 ms for 0.54 MB at
    # Kanana-2's size, 0.29 ms a MB on a v5e, PERF.md PR 37); integers,
    # no gradient
    return (keep_in_recompute(order[:capacity], "routed_order"),
            keep_in_recompute(where, "routed_where"),
            keep_in_recompute(sizes, "routed_sizes"),
            keep_in_recompute(ends[-1] - jnp.sum(sizes), "routed_overflow"))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def rows_in_order(a, order, where, fan):
    """``a`` [n, ...] -> [capacity, ...]: row ``order // fan`` of ``a``
    at each sorted row (``fan`` picks a row of ``a``)."""
    return a[order // fan]


def _rows_fwd(a, order, where, fan):
    return a[order // fan], (order, where)


def _rows_bwd(fan, res, d):
    order, where = res
    return _sum_picks(d, where, fan), None, None


rows_in_order.defvjp(_rows_fwd, _rows_bwd)


def _sum_picks(o, where, fan):
    """[capacity, ...] -> [picks / fan, ...]: each pick's sorted row (or
    nothing, where ``where`` says ``capacity``), summed over the ``fan``
    picks of a row in float32 and cast once.

    Rows of a whole number of 32-bit lane rows (hidden a multiple of
    256 two-byte or 128 four-byte elements) go through
    ``ops/pallas/sum_picks.py``, which reads the rows that hold a pick
    and no other (its docstring: a gather pays for every pick, held or
    not; ``_row_shape`` there has the layout a width travels in). A
    vector (the pick weights' gradient), rows of another width and a
    step that XLA partitions by itself take the gather: indexed ``[fan,
    picks / fan]`` so that the sum runs over the leading axis, an index
    past the rows filled with zeros. Which arm a traced call on rows
    took is counted: ``p1t_moe_sum_picks_arm_total{arm}``, ``kernel`` or
    ``gather``."""
    kernel = (sum_picks_kernel.supported(o, where, fan)
              and not in_auto_partitioned_region())
    if o.ndim == 2:
        process_group("arm").child("kernel" if kernel else "gather") \
            .counter("moe_sum_picks_arm_total").inc()
    if kernel:
        return sum_picks_kernel.sum_picks(o, where, fan)
    picked = jnp.take(o, where.reshape(-1, fan).T, axis=0, mode="fill",
                      fill_value=0)
    return jnp.sum(picked.astype(jnp.float32), axis=0).astype(o.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def sum_of_picks(o, order, where, fan):
    """The transpose of :func:`rows_in_order`."""
    return _sum_picks(o, where, fan)


def _sum_fwd(o, order, where, fan):
    return _sum_picks(o, where, fan), (order, where)


def _sum_bwd(fan, res, d):
    order, where = res
    return d[order // fan], None, None


sum_of_picks.defvjp(_sum_fwd, _sum_bwd)


def grouped_matmul(rows, weights, sizes):
    """``rows`` [m, k] x ``weights`` [groups, k, n] -> [m, n]: the first
    ``sizes[0]`` rows through ``weights[0]`` and so on; rows past the
    groups are not to be read, and what stands in their output is not
    defined.

    Two arms, chosen by the operands' shape. **``xla``**: the TPU's
    compiler lowers ``lax.ragged_dot``, and autodiff's two transposes of
    it, to a grouped kernel of its own under its own name
    (``ragged-dot-none``) and drops the scope; the frontend attribute it
    keeps says where ``obs.costmodel`` is to count them. That kernel walks
    the (row tile of 512, group) pairs that hold a row, each width tiled
    at the largest power of two up to 512 that divides it, and runs at
    the matrix unit's peak a pair at 512-wide tiles. **``kernel``**:
    where it would tile a width under 256 (2688 and 1856 both fall to
    128, and the grid steps' own overhead sets the time),
    ``ops/pallas/grouped_matmul.py`` runs the same pairs with tiles that
    fit the widths (``supported`` there has the rule; a step that XLA
    partitions by itself stays with ``xla``). Which arm a traced call
    took is counted: ``p1t_moe_grouped_matmul_arm_total{arm}``."""
    kernel = (grouped_kernel.supported(rows, weights)
              and not in_auto_partitioned_region())
    process_group("arm").child("kernel" if kernel else "xla") \
        .counter("moe_grouped_matmul_arm_total").inc()
    if kernel:
        return grouped_kernel.grouped_matmul(rows, weights, sizes)
    with set_xla_metadata(**{SCOPE_ATTRIBUTE: "moe/routed_experts"}):
        return jax.lax.ragged_dot(rows, weights, sizes)


# one table for a gate's activation and for an expert's without a gate
GATE_ACTIVATIONS = PlainFeedForward.ACTIVATIONS


def expert_ffn(xs, sizes, gate_up, down, act=jax.nn.silu):
    """Held experts' gated feed-forward over their own rows: ``gate_up``
    [held, hidden, 2 * width] (gate columns first), ``down`` [held, width,
    hidden]; ``act`` on the gate (SwiGLU unless told another)."""
    both = grouped_matmul(xs, gate_up, sizes)
    width = both.shape[-1] // 2
    return grouped_matmul(act(both[:, :width]) * both[:, width:], down,
                          sizes)


def plain_expert_ffn(xs, sizes, up, down, act):
    """Held experts without a gate over their own rows: ``up`` [held,
    hidden, width], ``down`` [held, width, hidden]."""
    return grouped_matmul(act(grouped_matmul(xs, up, sizes)), down, sizes)


def capacity_rows(tokens, top_k, held, num_experts):
    """Rows of the grouped products: ``CAPACITY_FACTOR`` times the picks
    that land on ``held`` of ``num_experts`` experts under even routing, a
    multiple of the row tile, at most every pick there is."""
    picks = tokens * top_k
    even = -(-picks * held // num_experts)
    return min(picks, -(-CAPACITY_FACTOR * even // _ROW_TILE) * _ROW_TILE)


class RoutedExperts(Layer):
    """See the module's docstring. ``held = (first, count)``: the experts
    this layer holds, all of them when None. ``gate_activation``: what an
    expert puts on its gate, ``"silu"`` or ``"relu"``; ``gated=False``: an
    expert is ``down(act(up(x)))`` with that activation (or ``"relu2"``),
    and so is the shared one. ``forward(x,
    router_input=None)``: [..., d_model] -> the same shape; the router
    scores ``router_input`` (``x`` where None), the experts compute from
    ``x``."""

    def __init__(self, d_model, expert_width, num_experts, top_k,
                 held=None, shared_width=0, routed_scaling_factor=1.0,
                 weight_attr=None, scoring="sigmoid", norm_eps=1e-20,
                 gate_activation="silu", gated=True):
        super().__init__()
        if scoring not in ("sigmoid", "softmax"):
            raise ValueError(f"scoring={scoring!r}")
        if gate_activation not in GATE_ACTIVATIONS:
            raise ValueError(f"gate_activation={gate_activation!r}")
        self.gate_activation, self.gated = gate_activation, gated
        self.num_experts, self.top_k = num_experts, top_k
        self.scoring, self.norm_eps = scoring, norm_eps
        self.first, self.held = held if held is not None else (0, num_experts)
        if not 0 <= self.first <= self.first + self.held <= num_experts:
            raise ValueError(f"held={held} of {num_experts} experts")
        self.routed_scaling_factor = routed_scaling_factor
        self.router = self.create_parameter([d_model, num_experts],
                                            attr=weight_attr)
        # DeepSeek-V3's e_score_correction_bias: moved by a balancing
        # rule outside the gradient, never by the optimizer. The softmax
        # rule has none
        if scoring == "sigmoid":
            self.register_buffer("e_score_correction_bias", Tensor(
                Constant(0.0)([num_experts], "float32"),
                stop_gradient=True))
        if gated:
            self.gate_up_proj = self.create_parameter(
                [self.held, d_model, 2 * expert_width], attr=weight_attr)
        else:
            self.up_proj = self.create_parameter(
                [self.held, d_model, expert_width], attr=weight_attr)
        self.down_proj = self.create_parameter(
            [self.held, expert_width, d_model], attr=weight_attr)
        self.shared_experts = (
            None if not shared_width else
            GatedFeedForward(d_model, shared_width, weight_attr) if gated
            else PlainFeedForward(d_model, shared_width, gate_activation,
                                  weight_attr))
        # the module's docstring: rows of each held expert, LOAD_TAIL
        self.register_buffer("expert_load", Tensor(
            jnp.zeros((self.held + len(LOAD_TAIL),), jnp.int32),
            stop_gradient=True), persistable=False)
        self._load_shape = None     # (picks made, capacity) of a forward

    def read_load(self, counts):
        """``expert_load`` (or a copy of it from a compiled step) as a
        dict: ``rows`` [held], the fields of :data:`LOAD_TAIL`, and what
        is static beside them (``picks_made_a_step`` and ``capacity_rows``
        of the last forward's shape, None before the first)."""
        counts = [int(c) for c in counts]
        made, capacity = self._load_shape or (None, None)
        return {"rows": counts[:self.held],
                **dict(zip(LOAD_TAIL, counts[self.held:])),
                "picks_made_a_step": made, "capacity_rows": capacity,
                "held": self.held, "num_experts": self.num_experts}

    def forward(self, x, router_input=None):
        from ..ops import manip_ops
        shape = list(x.shape)
        with scope("moe"):
            flat = manip_ops.reshape(x, [-1, shape[-1]])
            y = self._routed(flat, flat if router_input is None else
                             manip_ops.reshape(router_input,
                                               [-1, shape[-1]]))
            if self.shared_experts is not None:
                y = y + self.shared_experts(flat)
            return manip_ops.reshape(y, shape)

    def _routed(self, x, scored):
        """``x`` [tokens, d_model] through the held experts that the
        router picks from ``scored`` [tokens, d_model]."""
        k, first, held = self.top_k, self.first, self.held
        act = GATE_ACTIVATIONS[self.gate_activation]
        tokens = x.shape[0]
        capacity = capacity_rows(tokens, k, held, self.num_experts)
        # the softmax rule has no selection bias to hand in
        bias = ((self.e_score_correction_bias,)
                if self.scoring == "sigmoid" else ())
        weights, chosen = apply(
            "moe_router", lambda x, w, b=None: route(
                x, w, b, k, self.routed_scaling_factor, self.scoring,
                self.norm_eps),
            (scored, self.router) + bias)

        def dispatch(x, weights, chosen):
            order, where, sizes, overflow = sort_picks(chosen, first, held,
                                                       capacity)
            load = jnp.concatenate([sizes, jnp.stack([
                jnp.sum(sizes) + overflow, overflow,
                (overflow > 0).astype(jnp.int32), jnp.int32(1)])])
            # the pick weights in sorted order: a gather of scalars, 0.32
            # ms for 0.15 MB (2.2 ms a MB on a v5e, PERF.md PR 37)
            return (rows_in_order(x, order, where, k),
                    keep_in_recompute(rows_in_order(
                        weights.reshape(-1), order, where, 1),
                        "routed_row_weights"),
                    order, where, sizes, overflow, load)
        xs, ws, order, where, sizes, overflow, load = apply(
            "moe_dispatch", dispatch, (x, weights, chosen))
        self._load_shape = (tokens * k, capacity)
        record_state_update(self.expert_load, load.data, "add")
        first_proj = self.gate_up_proj if self.gated else self.up_proj
        out = apply("routed_experts",
                    expert_ffn if self.gated else plain_expert_ffn,
                    (xs, sizes, first_proj, self.down_proj), act=act)

        def combine(out, ws, order, where, sizes):
            # a row past the groups holds whatever the product left there
            live = jnp.arange(capacity) < jnp.sum(sizes)
            weighted = (out.astype(jnp.float32) * ws[:, None]).astype(out.dtype)
            return sum_of_picks(jnp.where(live[:, None], weighted, 0),
                                order, where, k)
        y = apply("moe_combine", combine, (out, ws, order, where, sizes))
        if capacity == tokens * k:
            return y            # the main path holds every pick there is

        @jax.checkpoint
        def through_each(y, x, weights, late, gate_up, down):
            """Every held expert over all tokens, the late picks' weights
            as the mask."""
            for e in range(held):
                mine = jnp.sum(jnp.where(late == e, weights, 0.0), -1)
                both = jnp.dot(x, gate_up[e])
                width = both.shape[-1] // 2
                out = jnp.dot(act(both[:, :width]) * both[:, width:]
                              if self.gated else act(both), down[e])
                y = y + (mine[:, None] * out).astype(y.dtype)
            return y

        def beyond(y, x, weights, chosen, where, overflow, gate_up, down):
            # the held expert of each pick that found no row, else -1
            local = chosen - first
            late = jnp.where((local >= 0) & (local < held)
                             & (where.reshape(chosen.shape) == capacity),
                             local, -1)
            return jax.lax.cond(
                overflow > 0, through_each, lambda y, *_: y,
                y, x, weights, late, gate_up, down)
        return apply("moe_overflow", beyond,
                     (y, x, weights, chosen, where, overflow,
                      first_proj, self.down_proj))
