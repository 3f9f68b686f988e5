"""Collective communication API.

Analog of the reference's ``python/paddle/distributed/collective.py``
(new_group :198, broadcast :330, all_reduce :397, all_gather :572, scatter
:650, barrier :158, TP internals _c_identity/_c_concat/_c_split :732-813)
and the collective op layer (`paddle/fluid/operators/collective/` — the
c_allreduce_sum / c_allgather / send_v2 / recv_v2 kernels over NCCL).

TPU-native design: a collective is not a kernel against a comm handle — it is
a *named-axis operation inside an SPMD trace*. Under ``shard_map`` over a
``Mesh`` axis, these functions lower to ``lax.psum``/``all_gather``/
``ppermute`` etc., which XLA compiles to ICI collectives. Outside a trace
(eager, single process) they act on the process group: world-size-1 groups
are identity — mirroring the reference's behavior where collectives on a
single rank are no-ops — and the simulated-mesh test backend (see
tests/test_collective.py) exercises the real multi-device lowering on a
virtual CPU mesh, which the reference could not do (SURVEY §4).

Autograd: each collective goes through ``engine.apply`` so it is recorded on
the eager tape with the correct XLA-derived vjp (psum ↔ psum, all_gather ↔
reduce_scatter, ppermute ↔ inverse ppermute).
"""

from __future__ import annotations

import threading
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
from jax import lax

from ..autograd.engine import apply
from ..core import chaos, collective_sanitizer
from ..core.errors import InvalidArgumentError, PreconditionNotMetError
from ..core.tensor import Tensor, to_tensor
from . import env
from .topology import _AxisGroup

__all__ = ["ReduceOp", "Group", "new_group", "get_group", "destroy_process_group",
           "hierarchical_all_reduce",
           "is_initialized", "all_reduce", "all_gather", "all_gather_object",
           "reduce", "broadcast", "scatter", "reduce_scatter", "alltoall",
           "all_to_all", "send", "recv", "isend", "irecv", "barrier", "wait",
           "get_rank", "get_world_size", "_c_identity", "_c_concat",
           "_c_split", "split"]


class ReduceOp:
    SUM = 0
    MAX = 1
    MIN = 2
    PROD = 3
    AVG = 4


def _pprod(x, axis):
    """Product over a mesh axis via log-magnitude psum + sign/zero tracking
    (XLA has no native product collective; exp∘psum∘log alone NaNs on
    negatives and -infs on zeros)."""
    mag = jnp.exp(lax.psum(jnp.log(jnp.where(x == 0, 1.0, jnp.abs(x))),
                           axis))
    neg = lax.psum((x < 0).astype(jnp.int32), axis)
    has_zero = lax.psum((x == 0).astype(jnp.int32), axis) > 0
    sign = jnp.where(neg % 2 == 0, 1.0, -1.0)
    return jnp.where(has_zero, 0.0, sign * mag).astype(x.dtype)


_REDUCERS = {
    ReduceOp.SUM: lax.psum,
    ReduceOp.MAX: lax.pmax,
    ReduceOp.MIN: lax.pmin,
    ReduceOp.PROD: _pprod,
}


class Group:
    """A communicator group (reference collective.py Group). On TPU a group
    is (axis_name | explicit rank list); inside SPMD traces only axis-bound
    groups are meaningful."""

    def __init__(self, rank: int, nranks: int, gid: int = 0,
                 ranks: Optional[List[int]] = None,
                 axis: Optional[str] = None):
        self.rank = rank
        self.nranks = nranks
        self.id = gid
        self.ranks = ranks if ranks is not None else list(range(nranks))
        self.axis = axis

    @property
    def world_size(self) -> int:
        return self.nranks

    def get_group_rank(self, rank: int) -> int:
        return self.ranks.index(rank) if rank in self.ranks else -1

    def __repr__(self):
        return (f"Group(rank={self.rank}, nranks={self.nranks}, "
                f"id={self.id}, axis={self.axis!r})")


_group_lock = threading.Lock()
_group_map: Dict[int, Group] = {}
_next_gid = [1]
_default_group: Optional[Group] = None


def _get_default_group() -> Group:
    global _default_group
    if _default_group is None:
        ws = env.get_world_size()
        _default_group = Group(env.get_rank(), ws, gid=0,
                               ranks=list(range(ws)),
                               axis=env.current_spmd_axis("dp"))
        _group_map[0] = _default_group
    return _default_group


def is_initialized() -> bool:
    return _default_group is not None


def destroy_process_group(group: Optional[Group] = None) -> None:
    global _default_group
    with _group_lock:
        if group is None:
            _group_map.clear()
            _default_group = None
        else:
            _group_map.pop(group.id, None)


def new_group(ranks: Optional[Sequence[int]] = None, backend: Optional[str]
              = None, axis: Optional[str] = None) -> Group:
    """Create a comm group (reference collective.py:198 — there it spawns an
    NCCL ring per group; here a group is an axis handle / rank subset)."""
    with _group_lock:
        gid = _next_gid[0]
        _next_gid[0] += 1
    me = env.get_rank()
    ranks = sorted(ranks) if ranks is not None else \
        list(range(env.get_world_size()))
    grank = ranks.index(me) if me in ranks else -1
    g = Group(grank, len(ranks), gid=gid, ranks=ranks, axis=axis)
    _group_map[gid] = g
    return g


def get_group(gid: int = 0) -> Group:
    if gid == 0:
        return _get_default_group()
    if gid not in _group_map:
        raise PreconditionNotMetError(f"Group {gid} not created")
    return _group_map[gid]


def get_rank(group: Optional[Group] = None) -> int:
    return group.rank if group is not None else env.get_rank()


def get_world_size(group: Optional[Group] = None) -> int:
    return group.nranks if group is not None else env.get_world_size()


# ---------------------------------------------------------------------------
# axis resolution
# ---------------------------------------------------------------------------


def _resolve_axis(group, default_logical: str = "dp") -> Optional[str]:
    """Mesh-axis name for this collective: explicit group axis > thread-bound
    SPMD axis mapping > None (eager/no-op path)."""
    if isinstance(group, _AxisGroup):
        return group.axis
    if isinstance(group, Group) and group.axis is not None:
        return group.axis
    if isinstance(group, str):
        return group
    return env.current_spmd_axis(default_logical)


def _in_trace(x) -> bool:
    return isinstance(x, jax.core.Tracer)


def _nranks(group) -> int:
    if isinstance(group, (_AxisGroup, Group)):
        return group.nranks
    return env.get_world_size()


def _assign(tensor: Tensor, result: Tensor) -> Tensor:
    """In-place update semantics: the reference's collectives mutate their
    input var; we swap the produced value/grad-node into the same Tensor."""
    tensor._replace_impl(result)
    return tensor


# ---------------------------------------------------------------------------
# collectives
# ---------------------------------------------------------------------------


def _skip_collective(op: str, args) -> bool:
    """Per-wrapper entry for the SPMD-discipline runtime (ISSUE 14):
    journals this op into the collective-schedule sanitizer (site = the
    USER'S call line; free when the flag is off) and returns True when
    an armed ``collective_skip`` chaos point says THIS rank skips it —
    the wrapper then returns its input untouched and journals nothing,
    seeding the rank-divergent schedule the cross-rank verifier must
    catch. Both checks are one bool test when nothing is armed."""
    if chaos.enabled() and chaos.check_collective(env.get_rank()):
        return True
    # depth 3: note_collective <- here <- wrapper <- USER call site
    collective_sanitizer.note_collective(op, args, depth=3)
    return False


def all_reduce(tensor: Tensor, op: int = ReduceOp.SUM,
               group: Optional[Group] = None, sync_op: bool = True) -> Tensor:
    """In-place all-reduce (reference collective.py:397 → c_allreduce_sum
    kernel c_allreduce_op.h:253). Under SPMD trace → lax.psum over the
    group's mesh axis."""
    if _skip_collective("all_reduce", (tensor,)):
        return tensor
    axis = _resolve_axis(group)

    def f(x):
        if axis is not None and _in_trace(x):
            if op == ReduceOp.AVG:
                return lax.pmean(x, axis)
            return _REDUCERS[op](x, axis)
        return x  # world-size-1 eager: identity

    return _assign(tensor, apply("all_reduce", f, (tensor,)))


def reduce(tensor: Tensor, dst: int = 0, op: int = ReduceOp.SUM,
           group: Optional[Group] = None, sync_op: bool = True) -> Tensor:
    """Reduce-to-root. XLA has no single-destination reduce on a mesh axis;
    all-reduce and mask is the idiomatic (and on ICI, equal-cost ring) form."""
    if _skip_collective("reduce", (tensor,)):
        return tensor
    axis = _resolve_axis(group)

    def f(x):
        if axis is not None and _in_trace(x):
            if op == ReduceOp.AVG:
                red = lax.pmean(x, axis)
            else:
                red = _REDUCERS[op](x, axis)
            idx = lax.axis_index(axis)
            return jnp.where(idx == dst, red, x)
        return x

    return _assign(tensor, apply("reduce", f, (tensor,)))


def broadcast(tensor: Tensor, src: int = 0,
              group: Optional[Group] = None, sync_op: bool = True) -> Tensor:
    """Broadcast from group-rank ``src`` (reference collective.py:330 →
    c_broadcast). In-graph form: select src's shard and psum the rest away."""
    if _skip_collective("broadcast", (tensor,)):
        return tensor
    axis = _resolve_axis(group)

    def f(x):
        if axis is not None and _in_trace(x):
            idx = lax.axis_index(axis)
            masked = jnp.where(idx == src, x, jnp.zeros_like(x))
            return lax.psum(masked, axis)
        return x

    return _assign(tensor, apply("broadcast", f, (tensor,)))


def all_gather(tensor_list: Optional[List[Tensor]], tensor: Tensor,
               group: Optional[Group] = None, sync_op: bool = True):
    """Gather shards from every rank (reference collective.py:572 →
    c_allgather). Appends per-rank tensors to ``tensor_list``; also returns
    the stacked result for functional use."""
    if _skip_collective("all_gather", (tensor,)):
        if tensor_list is not None:
            tensor_list.append(tensor)
        return tensor
    axis = _resolve_axis(group)
    n = _nranks(group)

    def f(x):
        if axis is not None and _in_trace(x):
            return lax.all_gather(x, axis, axis=0)  # [n, ...]
        return jnp.expand_dims(x, 0)

    stacked = apply("all_gather", f, (tensor,))
    if tensor_list is not None:
        from ..ops import manip_ops
        parts = manip_ops.unstack(stacked, axis=0)
        tensor_list.extend(parts)
    return stacked


def all_gather_object(object_list: list, obj: Any,
                      group: Optional[Group] = None):
    """Single-process world: the object itself (multi-host object gather
    rides the coordination service, not ICI)."""
    object_list.extend([obj] * _nranks(group))


def reduce_scatter(tensor: Tensor, tensor_or_tensor_list,
                   op: int = ReduceOp.SUM, group: Optional[Group] = None,
                   sync_op: bool = True) -> Tensor:
    """Reduce-scatter (reference c_reducescatter op). Input: concatenated
    [n*chunk, ...] or list of n tensors; output shard into ``tensor``."""
    if _skip_collective("reduce_scatter", (tensor,)):
        return tensor
    axis = _resolve_axis(group)
    if isinstance(tensor_or_tensor_list, (list, tuple)):
        from ..ops import manip_ops
        src = manip_ops.concat(list(tensor_or_tensor_list), axis=0)
    else:
        src = tensor_or_tensor_list
    n = _nranks(group)

    def f(x):
        if axis is not None and _in_trace(x):
            return lax.psum_scatter(x, axis, scatter_dimension=0,
                                    tiled=True)
        return x

    return _assign(tensor, apply("reduce_scatter", f, (src,)))


def scatter(tensor: Tensor, tensor_list: Optional[List[Tensor]] = None,
            src: int = 0, group: Optional[Group] = None,
            sync_op: bool = True) -> Tensor:
    """Scatter list from src (reference collective.py:650 → c_scatter:
    broadcast + slice by rank)."""
    if _skip_collective("scatter", (tensor,)):
        return tensor
    axis = _resolve_axis(group)
    if tensor_list:
        from ..ops import manip_ops
        stacked = manip_ops.stack(tensor_list, axis=0)

        def f(x):
            if axis is not None and _in_trace(x):
                idx = lax.axis_index(axis)
                full = lax.psum(jnp.where(lax.axis_index(axis) == src, x,
                                          jnp.zeros_like(x)), axis)
                return lax.dynamic_index_in_dim(full, idx, 0,
                                                keepdims=False)
            return x[0]

        return _assign(tensor, apply("scatter", f, (stacked,)))
    return tensor


def alltoall(in_tensor_list, out_tensor_list: Optional[list] = None,
             group: Optional[Group] = None, sync_op: bool = True):
    """All-to-all (reference operators/collective/alltoall_op). Accepts a
    list of n tensors (one per peer) or a single [n*chunk,...] tensor; under
    trace lowers to lax.all_to_all over the axis."""
    if _skip_collective("alltoall", (in_tensor_list,)):
        if out_tensor_list is not None and isinstance(
                in_tensor_list, (list, tuple)):
            out_tensor_list.extend(in_tensor_list)
        return in_tensor_list
    axis = _resolve_axis(group)
    if isinstance(in_tensor_list, (list, tuple)):
        from ..ops import manip_ops
        src = manip_ops.stack(list(in_tensor_list), axis=0)  # [n, ...]
    else:
        src = in_tensor_list

    def f(x):
        if axis is not None and _in_trace(x):
            return lax.all_to_all(x, axis, split_axis=0, concat_axis=0,
                                  tiled=False)
        return x

    out = apply("alltoall", f, (src,))
    if out_tensor_list is not None:
        from ..ops import manip_ops
        out_tensor_list.extend(manip_ops.unstack(out, axis=0))
    return out


all_to_all = alltoall


def send(tensor: Tensor, dst: int = 0, group: Optional[Group] = None,
         sync_op: bool = True) -> None:
    """P2P send (reference send_v2 — pipeline edges). In-graph equivalent is
    ``ppermute``; use paddle1_tpu.distributed.p2p.ppermute inside pipeline
    schedules. Eager single-process: buffered locally."""
    _p2p_buffer.setdefault(dst, []).append(tensor)


def recv(tensor: Tensor, src: int = 0, group: Optional[Group] = None,
         sync_op: bool = True) -> Tensor:
    """P2P recv (reference recv_v2)."""
    me = env.get_rank()
    buf = _p2p_buffer.get(me, [])
    if buf:
        return _assign(tensor, buf.pop(0))
    return tensor


_p2p_buffer: Dict[int, List[Tensor]] = {}


class _Work:
    def wait(self):
        return None

    def is_completed(self):
        return True


def isend(tensor, dst=0, group=None):
    send(tensor, dst, group)
    return _Work()


def irecv(tensor, src=0, group=None):
    recv(tensor, src, group)
    return _Work()


def barrier(group: Optional[Group] = None) -> None:
    """Reference collective.py:158 barrier op. XLA programs are globally
    scheduled, so in-graph barriers are unnecessary; across hosts this
    syncs via the coordination service when multi-process."""
    if _skip_collective("barrier", ()):
        return
    try:
        if jax.process_count() > 1:
            from jax.experimental.multihost_utils import \
                sync_global_devices
            # best-effort by design: single-host runs have no
            # coordination service (the sync raising there must not
            # fail the barrier API), and a real multi-host init
            # failure already surfaced at jax.distributed.initialize
            sync_global_devices("paddle1_tpu_barrier")  # noqa: collective-swallow — see note
    except Exception:
        pass


def wait(tensor: Tensor, group: Optional[Group] = None,
         use_calc_stream: bool = True) -> None:
    """Reference c_wait_comm/c_wait_compute — stream ordering. XLA's token
    ordering makes this a no-op; kept for API parity."""
    return None


# ---------------------------------------------------------------------------
# TP internals (reference collective.py:732-813)
# ---------------------------------------------------------------------------


def _c_identity(tensor: Tensor, group: Optional[Group] = None,
                skip_c_identity_dynamic: bool = False) -> Tensor:
    """Forward identity / backward all-reduce (the f operator of Megatron).
    Reference collective.py:732."""
    axis = _resolve_axis(group, "mp")

    def f(x):
        if axis is not None and _in_trace(x):
            # identity fwd; psum in bwd comes from custom vjp
            return _ident_psum_bwd(x, axis)
        return x

    return apply("c_identity", f, (tensor,))


def _vary_like(g, vma):
    """Cast cotangent ``g`` up to the primal's varying manual axes
    ``vma``: a custom-VJP bwd rule must return the primal's type, and
    under shard_map that type says over which mesh axes the value
    differs — a ``psum`` result, or the cotangent of one, varies over
    fewer than the primal did."""
    missing = tuple(a for a in vma if a not in jax.typeof(g).vma)
    return lax.pcast(g, missing, to="varying") if missing else g


def _ident_psum_bwd(x, axis):
    vma = jax.typeof(x).vma

    @jax.custom_vjp
    def ident(x):
        return x

    def fwd(x):
        return x, None

    def bwd(_, g):
        return (_vary_like(lax.psum(g, axis), vma),)

    ident.defvjp(fwd, bwd)
    return ident(x)


def _psum_ident_bwd(x, axis):
    vma = jax.typeof(x).vma

    @jax.custom_vjp
    def red(x):
        return lax.psum(x, axis)

    def fwd(x):
        return lax.psum(x, axis), None

    def bwd(_, g):
        return (_vary_like(g, vma),)

    red.defvjp(fwd, bwd)
    return red(x)


def _mp_allreduce(tensor: Tensor, group: Optional[Group] = None) -> Tensor:
    """Forward all-reduce / backward identity (the g operator of Megatron).
    Reference mp_ops c_allreduce_sum with use_model_parallel=True."""
    axis = _resolve_axis(group, "mp")

    def f(x):
        if axis is not None and _in_trace(x):
            return _psum_ident_bwd(x, axis)
        return x

    return apply("mp_allreduce", f, (tensor,))


def _c_concat(tensor: Tensor, group: Optional[Group] = None) -> Tensor:
    """All-gather along the last dim (reference collective.py:770 c_concat:
    column-parallel output gather)."""
    axis = _resolve_axis(group, "mp")

    def f(x):
        if axis is not None and _in_trace(x):
            return lax.all_gather(x, axis, axis=x.ndim - 1, tiled=True)
        return x

    return apply("c_concat", f, (tensor,))


def _c_split(tensor: Tensor, group: Optional[Group] = None) -> Tensor:
    """Take this rank's slice of the last dim (reference collective.py:813
    c_split — row-parallel input scatter)."""
    axis = _resolve_axis(group, "mp")
    n = _nranks(group)

    def f(x):
        if axis is not None and _in_trace(x):
            n_ranks = lax.axis_size(axis)
            if x.shape[-1] % n_ranks != 0:
                raise InvalidArgumentError(
                    f"c_split: last dim {x.shape[-1]} not divisible by "
                    f"axis '{axis}' size {n_ranks}")
            idx = lax.axis_index(axis)
            chunk = x.shape[-1] // n_ranks
            return lax.dynamic_slice_in_dim(x, idx * chunk, chunk,
                                            axis=x.ndim - 1)
        return x

    return apply("c_split", f, (tensor,))


def split(x, num_or_sections, axis=0, group=None):
    """paddle.distributed.split — deprecated TP helper; use meta_parallel
    layers. Only the last-dim even split (the c_split semantics) is
    supported; anything else raises rather than silently mis-slicing."""
    ndim = len(x.shape)
    if axis not in (-1, ndim - 1):
        raise InvalidArgumentError(
            "paddle1_tpu.distributed.split only supports splitting the "
            "last dim over mp (c_split); for other layouts use "
            "distributed.fleet ColumnParallelLinear/RowParallelLinear")
    n = _nranks(group)
    if isinstance(num_or_sections, int) and num_or_sections not in (n, -1):
        raise InvalidArgumentError(
            f"split num_or_sections={num_or_sections} must equal the "
            f"group size {n}")
    return _c_split(x, group)


def hierarchical_all_reduce(x, intra_axis: str, inter_axis: str):
    """Two-level all-reduce for multi-slice meshes (the functional form
    of the reference's hierarchical_allreduce strategy toggle,
    distributed_strategy.py proto :146-196: intra-node reduce →
    inter-node allreduce over node leaders → intra-node broadcast).

    TPU-native mapping over a mesh with a fast axis (ICI, within a
    slice) and a slow axis (DCN, across slices): reduce-scatter over
    ``intra_axis`` so each chip owns 1/n of the payload, all-reduce the
    shards over ``inter_axis`` (the only traffic that crosses DCN —
    bandwidth-optimal: payload/n per chip instead of the full payload),
    then all-gather back over ``intra_axis``. Call inside a shard_map
    over both axes; when dim 0 is not divisible by the intra size the
    op falls back to the flat two-axis psum (correct, more DCN bytes).

    For jit/GSPMD code, multi-axis ``psum`` already lowers
    hierarchically per the mesh topology — this explicit form exists
    for shard_map code paths and for strategy parity.
    """
    if _skip_collective("hierarchical_all_reduce", (x,)):
        return x
    import jax

    def f(v):
        if not _in_trace(v):
            return v  # single-process eager: identity
        n = lax.axis_size(intra_axis)
        if v.ndim >= 1 and v.shape[0] % n == 0:
            shard = lax.psum_scatter(v, intra_axis, scatter_dimension=0,
                                     tiled=True)
            shard = lax.psum(shard, inter_axis)
            return lax.all_gather(shard, intra_axis, axis=0, tiled=True)
        return lax.psum(lax.psum(v, intra_axis), inter_axis)

    if isinstance(x, Tensor):
        return apply("hierarchical_all_reduce", f, (x,))
    return f(x)
