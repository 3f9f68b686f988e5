"""Analytical cost attribution: FLOPs + bytes per compiled executable.

The pane of glass (ISSUE 10) can see *when* things happen but not what
they *cost*: MFU and HBM-bandwidth utilization were hand-computed in
``bench.py`` from a per-model FLOPs formula, and nothing on the hot
paths knew its own arithmetic intensity. This module derives both
numbers from the compiler itself — ``jax.jit(fn).lower(*args)
.cost_analysis()`` runs XLA's HLO cost analysis on the lowered program
(no XLA compile, one Python trace) and reports ``flops`` and
``bytes accessed`` for exactly the graph that will run. The reference
ships the same organ as its profiler's op-level FLOPs tables; here the
unit of attribution is the *executable* (one jit site x one signature),
which is the unit the TPU runtime actually dispatches.

Contract (the ``bench.py --cost`` gate):

* **exact when possible** — :func:`analyze` returns
  ``ExecutableCost(flops, bytes_accessed, source="xla_cost_analysis")``
  from the lowered HLO; the BERT acceptance run cross-checks it within
  15% of the hand-derived ``6 * params * tokens`` formula;
* **labeled fallback** — when cost analysis is unavailable (exotic
  backend, lowering failure) the tree-size heuristic kicks in
  (``source="tree_size_heuristic"``: 2 flops per parameter element per
  batch row, bytes = one read of every input leaf + one write of every
  parameter-shaped output) so consumers can tell a measured number
  from a guess;
* **cached per jit-site signature** — :func:`site_cost` memoizes by an
  engine-supplied key, so the one-time Python trace of the cost
  lowering is paid once per (site, signature), never per step;
* **zero when off** — engines only call in under ``obs_metrics`` (the
  PR 9 structural-zero discipline).

Peak-rate tables (:func:`device_peak_flops`,
:func:`device_peak_hbm_bw`): ``bench.py`` divides its analytic and its
cost-model MFU by the same peak. The engine derives no utilization
itself: under run-ahead the time between two enqueues is not a step
time, so a share of peak comes from a device trace (``benchmarks/``).

**Which instruction is whose** (:func:`step_op_scopes`,
:func:`step_fused_regions`, :func:`step_phase_records`): a device trace
names each instruction by its HLO text, which holds no metadata. The
compiled program's text does: every instruction's ``op_name`` is the
path of ``jax.named_scope``s it was traced under (``make_train_step``'s
regions, the layer path, the op's name). These functions read it from
the live engine (found through ``obs.hbm``'s weak registry, so a reader
needs no handle on one), after a run and never inside it.
"""

from __future__ import annotations

import functools
import re
import threading
from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional, Tuple

__all__ = ["ExecutableCost", "analyze", "site_cost", "tree_bytes",
           "tree_size_cost", "forward_cost", "device_peak_flops",
           "device_peak_hbm_bw", "clear_cache", "parse_op_scopes",
           "region_of", "step_op_scopes", "step_fused_regions",
           "step_phase_records", "step_expert_load"]


@dataclass(frozen=True)
class ExecutableCost:
    """What one dispatch of one executable costs.

    ``source`` is ``"xla_cost_analysis"`` when the numbers came from
    the lowered HLO, ``"tree_size_heuristic"`` when they are the
    labeled fallback guess — consumers (gauges, ``hapi.summary``,
    ``bench --cost``) surface the label so a heuristic can never
    masquerade as a measurement.
    """

    flops: float
    bytes_accessed: float
    source: str

    @property
    def exact(self) -> bool:
        return self.source == "xla_cost_analysis"


def tree_bytes(tree) -> int:
    """Total ``nbytes`` over a pytree's array leaves (leaves without
    ``nbytes`` — python scalars, None — count 0)."""
    import jax
    total = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        total += int(getattr(leaf, "nbytes", 0) or 0)
    return total


def _tree_rows(tree) -> int:
    """Leading-dim row count of the first array leaf (>=1)."""
    import numpy as np
    import jax
    for leaf in jax.tree_util.tree_leaves(tree):
        shape = np.shape(leaf)
        if shape:
            return max(int(shape[0]), 1)
    return 1


def tree_size_cost(params, batch=None, extra=None) -> ExecutableCost:
    """The labeled fallback: 2 flops per parameter element per batch
    row (one multiply-accumulate touching each weight once per row —
    a dense-forward floor, NOT a measurement), bytes = one read of
    every input tree + one parameter-sized write."""
    import numpy as np
    import jax
    p_elems = 0
    for leaf in jax.tree_util.tree_leaves(params):
        p_elems += int(np.prod(np.shape(leaf))) if np.shape(leaf) else 1
    rows = _tree_rows(batch) if batch is not None else 1
    read = tree_bytes(params) + tree_bytes(batch) + tree_bytes(extra)
    return ExecutableCost(flops=2.0 * p_elems * rows,
                          bytes_accessed=float(read + tree_bytes(params)),
                          source="tree_size_heuristic")


def analyze(lower_thunk: Callable[[], Any],
            fallback: Optional[ExecutableCost] = None) -> ExecutableCost:
    """Run ``lower_thunk()`` (returning a ``jax.stages.Lowered``) and
    read XLA's cost analysis off it. Any failure — lowering error,
    backend without cost analysis, missing keys — degrades to
    ``fallback`` (or a zero-cost heuristic record), never an exception:
    cost attribution must not be able to kill the step it measures."""
    try:
        lowered = lower_thunk()
        cost = lowered.cost_analysis()
        # jax returns a dict (or a 1-list of dicts from Compiled)
        if isinstance(cost, (list, tuple)):
            cost = cost[0] if cost else None
        if cost and "flops" in cost:
            return ExecutableCost(
                flops=float(cost.get("flops", 0.0) or 0.0),
                bytes_accessed=float(cost.get("bytes accessed", 0.0)
                                     or 0.0),
                source="xla_cost_analysis")
    except Exception:  # noqa: broad-except — cost attribution is
        # telemetry; a lowering quirk must degrade to the labeled
        # heuristic, never kill the training/serving step it measures
        pass
    if fallback is not None:
        return fallback
    return ExecutableCost(0.0, 0.0, source="tree_size_heuristic")


# -- per-site cache ---------------------------------------------------------

_cache_lock = threading.Lock()
_site_cache: Dict[Tuple, ExecutableCost] = {}


def site_cost(site: str, signature: Tuple,
              lower_thunk: Callable[[], Any],
              fallback: Optional[ExecutableCost] = None
              ) -> ExecutableCost:
    """Memoized :func:`analyze`: one Python trace per (site,
    signature), shared process-wide — the same executable dispatched
    by two engines costs one analysis."""
    key = (site, signature)
    c = _site_cache.get(key)
    if c is None:
        c = analyze(lower_thunk, fallback=fallback)
        with _cache_lock:
            c = _site_cache.setdefault(key, c)
    return c


def clear_cache() -> None:
    """Drop every cached site cost (test isolation)."""
    with _cache_lock:
        _site_cache.clear()


# -- model-level forward cost (hapi.summary / paddle.flops) -----------------

def forward_cost(net, input_size, dtype="float32") -> ExecutableCost:
    """FLOPs + bytes of one compiled eval forward of ``net`` at
    ``input_size`` (batch included) — the ``paddle.summary`` /
    ``paddle.flops`` parity surface. Falls back to the labeled
    tree-size heuristic when cost analysis is unavailable."""
    import jax
    import jax.numpy as jnp
    from ..incubate.functional import functional_call
    params = net.functional_state()
    x = jnp.zeros(tuple(input_size), jnp.dtype(dtype))
    fb = tree_size_cost(params, batch=x)
    return analyze(
        lambda: jax.jit(
            lambda p, a: functional_call(net, p, a)).lower(params, x),
        fallback=fb)


# -- peak-rate tables -------------------------------------------------------

# (peak_bf16_flops, peak_hbm_bytes_per_s) per chip, keyed by needles of
# ``device.device_kind``; first match wins. A device no row names is an
# error, never a default: a wrong denominator makes every MFU and
# bandwidth share after it wrong in silence.
_PEAKS = (
    # Google Cloud docs, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s HBM
    (("v5 lite", "v5e", "v5lite"), (197e12, 819e9)),
    # Google Cloud docs, "TPU v5p": 459 TFLOP/s bf16, 2765 GB/s HBM
    (("v5p", "v5"), (459e12, 2765e9)),
    # Google Cloud docs, "TPU v4": 275 TFLOP/s bf16, 1228 GB/s HBM
    (("v4",), (275e12, 1228e9)),
    # Google Cloud docs, "TPU v6e" (Trillium): 918 TFLOP/s bf16,
    # 1640 GB/s HBM
    (("v6", "trillium"), (918e12, 1640e9)),
    # NOMINAL, for tests only: no CPU has these peaks. The row lets the
    # CPU test runs exercise the MFU / bandwidth gauges' arithmetic; a
    # utilization computed from it is a count, not a measurement.
    (("cpu",), (1e12, 100e9)),
)


def _peaks(device):
    kind = getattr(device, "device_kind", "").lower()
    for needles, peaks in _PEAKS:
        if any(n in kind for n in needles):
            return peaks
    raise ValueError(
        f"no peak FLOP/s / HBM bandwidth known for device kind "
        f"{getattr(device, 'device_kind', None)!r} (platform "
        f"{getattr(device, 'platform', None)!r}): add a sourced row to "
        "obs.costmodel._PEAKS")


def device_peak_flops(device) -> float:
    """bf16 peak FLOP/s per chip by device kind (the bench.py table,
    promoted here so the bench's analytic MFU and the engine's
    cost-model MFU divide by the same peak)."""
    return _peaks(device)[0]


def device_peak_hbm_bw(device) -> float:
    """Peak HBM bandwidth (bytes/s) per chip by device kind — the
    denominator of ``train_hbm_bw_util``."""
    return _peaks(device)[1]


# -- which instruction is whose ---------------------------------------------

# the regions make_train_step names; everything the loss function traces
# is "forward" or, behind autodiff's transpose(...), "backward"
REGIONS = ("forward", "backward", "amp_cast", "finite_check", "grad_clip",
           "optimizer", "stat_update")

_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%([^\s(]+)\s*\(.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT\s+)?%([^\s=]+)\s*=\s*(.*)$")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_CALLS = re.compile(r"\bcalls=%([^\s,)}]+)")
# the frontend attribute under which the program hands the compiler the
# scope of an op whose instruction the compiler renames
SCOPE_ATTRIBUTE = "p1t_scope"
_SCOPE_ATTR = re.compile(SCOPE_ATTRIBUTE + r'="([^"]*)"')
_OPERAND = re.compile(r"%([^\s,(){}]+)")


def region_of(op_name: str) -> str:
    """The region of :data:`REGIONS` an ``op_name`` lies in, "" when in
    none. ``jit(counted_step)/transpose(jvp(loss))/bert/...`` is
    ``backward``; under ``step_many`` or gradient accumulation the
    regions sit behind ``while/body/``. XLA joins the names of merged
    instructions with ``;``: the first that names a region decides."""
    for one in op_name.split(";"):
        for part in one.split("/"):
            inner = part.replace("transpose(", "").replace("jvp(", "")
            inner = inner.rstrip(")")
            if inner == "loss":
                return "backward" if "transpose(" in part else "forward"
            if inner in REGIONS:
                return inner
    return ""


def _pass_of(scopes, operands, unnamed, depth=4) -> str:
    """The start of an ``op_name``, through the part that names the
    forward or backward pass or, where it lies in a recomputed segment,
    through ``rematted_computation``, ``/`` at its end; "" where no
    operand lies in either pass. Taken from the last operand that does
    (autodiff hands a transposed product the cotangent last, and its
    other operand may be a recomputed one), looked for behind the copies
    and bitcasts that carry no name (``unnamed``: their operands)."""
    for operand in reversed(operands):
        path = scopes.get(operand, "").split(";")[0]
        if region_of(path) in ("forward", "backward"):
            parts = path.split("/")
            if "rematted_computation" in parts:
                upto = parts.index("rematted_computation")
            else:
                upto = next(i for i, part in enumerate(parts)
                            if region_of(part))
            return "/".join(parts[:upto + 1]) + "/"
        if depth and operand in unnamed:
            behind = _pass_of(scopes, unnamed[operand], unnamed, depth - 1)
            if behind:
                return behind
    return ""


@functools.lru_cache(maxsize=2)     # the engine hands the same string back
def parse_op_scopes(text: str):
    """``compiled.as_text()`` -> (``{instruction name: op_name}``,
    ``{fusion name: regions inside}``). Every instruction of every
    computation is in the first (names are unique in a module), with ""
    where it carries no ``op_name``; a fusion that carries none takes
    the commonest of its called computation's, as the profiler's
    framework-op view does. The second holds, per fusion, the distinct
    regions (:func:`region_of`) of the instructions fused into it.
    The compiler renames some ops it lowers itself and drops their
    ``op_name`` (the TPU's ``ragged-dot-none`` for ``lax.ragged_dot``),
    but keeps their frontend attributes: such an instruction that carries
    :data:`SCOPE_ATTRIBUTE` is put under that scope, in the pass
    (:func:`_pass_of`) its operands were made in.
    Memoised: every caller gets the same two dicts, to read."""
    bodies: Dict[str, list] = {}
    scopes: Dict[str, str] = {}
    fusions: Dict[str, str] = {}
    renamed: Dict[str, Tuple[str, list]] = {}
    unnamed: Dict[str, list] = {}
    body = None
    for line in text.splitlines():
        if body is not None:
            m = _INSTRUCTION.match(line)
            if m:
                name, rhs = m.groups()
                found = _OP_NAME.search(rhs)
                scopes[name] = found.group(1) if found else ""
                label = _SCOPE_ATTR.search(rhs)
                if label and "/" not in scopes[name]:
                    renamed[name] = (label.group(1), _OPERAND.findall(rhs))
                elif not found:
                    unnamed[name] = _OPERAND.findall(rhs)
                body.append(scopes[name])
                called = _CALLS.search(rhs)
                if called and " fusion(" in rhs:
                    fusions[name] = called.group(1)
            elif line.startswith("}"):
                body = None
        else:
            m = _COMPUTATION.match(line)
            if m:
                body = bodies.setdefault(m.group(1), [])
    for name, (label, operands) in renamed.items():
        scopes[name] = _pass_of(scopes, operands, unnamed) + label
    regions: Dict[str, Tuple[str, ...]] = {}
    for name, called in fusions.items():
        inside = [s for s in bodies.get(called, ()) if s]
        if not scopes[name] and inside:
            scopes[name] = max(set(inside), key=inside.count)
        regions[name] = tuple(sorted(
            {region_of(s) for s in inside} - {""}))
    return scopes, regions


def _stepping_engine():
    """The engine that dispatched last among the live ones, or None."""
    from . import hbm
    engine, at = None, -1
    for owner in hbm.live_owners():
        records = getattr(owner, "phase_records", list)()
        if records and records[-1].start_ns > at:
            engine, at = owner, records[-1].start_ns
    return engine


def _step_parsed():
    engine = _stepping_engine()
    text = engine.compiled_step_text() if engine is not None else None
    return None if text is None else parse_op_scopes(text)


def step_op_scopes() -> Optional[Dict[str, str]]:
    """``{HLO instruction name: op_name}`` of the compiled step program
    that the process's live engine dispatched last; None where no engine
    has stepped. Lowers and loads the program on the first call for a
    signature (seconds at real size; see
    ``ParallelEngine.compiled_step_text``), so call it after the run."""
    parsed = _step_parsed()
    return None if parsed is None else parsed[0]


def step_fused_regions() -> Optional[Dict[str, Tuple[str, ...]]]:
    """``{fusion instruction name: regions of the instructions inside}``
    of the same program: XLA fuses across regions (a weight-gradient
    matmul with the optimizer update behind it), and a fusion's time is
    counted under its own ``op_name`` alone."""
    parsed = _step_parsed()
    return None if parsed is None else parsed[1]


def step_expert_load() -> Optional[Dict[str, dict]]:
    """``ParallelEngine.expert_load()`` of the same engine: what its
    routed-expert layers counted on the device, by layer path. None
    where no engine has stepped or no layer of its model counts."""
    engine = _stepping_engine()
    load = getattr(engine, "expert_load", dict)()
    return load or None


def step_phase_records() -> list:
    """The ``StepPhases`` ring of the same engine (``[]`` with none):
    what the host did in each of its last dispatches."""
    engine = _stepping_engine()
    return engine.phase_records() if engine is not None else []
