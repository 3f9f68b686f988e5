"""Shared benchmark timing helpers.

This box is a NOISY shared host: single-run wall-clock comparisons
flake — an 86ms scheduler stall was observed inside one 0.4ms serving
dispatch, and whole seconds-long slow windows come and go (the chronic
``test_process_trainer`` throughput flake under tier-1 contention was
the same mode). Every timing gate therefore scores **best-of-N with
interleaved phases**: the phases sample the same noise windows, and the
fastest round of each is the design signal — anything slower is
scheduler noise, not the code under test.

``best_of`` is that policy as one reusable helper, shared by
``bench.py --serving``, ``--loader-chaos``, ``--serving-fleet``, and
the process-trainer throughput test.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Callable, List

__all__ = ["BestOf", "SelfTimed", "best_of",
           "compiled_hlo_layout_census"]


@dataclass
class SelfTimed:
    """Return this from a phase callable when only part of the call is
    the critical section (e.g. the serving bench times submit→result
    but not the per-round Server construction/drain): ``seconds`` is
    used as the round's time, ``value`` as its result."""
    seconds: float
    value: Any = None


@dataclass
class BestOf:
    """Per-phase outcome of :func:`best_of`."""
    times: List[float] = field(default_factory=list)   # per round, s
    results: List[Any] = field(default_factory=list)   # per round

    @property
    def best_s(self) -> float:
        return min(self.times)

    @property
    def best_round(self) -> int:
        return self.times.index(self.best_s)

    @property
    def best_result(self) -> Any:
        return self.results[self.best_round]


def best_of(n: int, *fns: Callable[[], Any]) -> List[BestOf]:
    """Interleaved best-of-``n`` timing of one or more phases.

    Runs every callable once per round, in order, for ``n`` rounds —
    interleaving makes all phases sample the same noise windows, so a
    slow window penalizes them together instead of whichever phase it
    landed on. Each call is wall-clock timed; correctness assertions
    belong INSIDE the callables (they must hold on every round — only
    the timing takes the best). Returns one :class:`BestOf` per
    callable, round-aligned (``results[i]`` of every phase came from
    the same round ``i``, so cross-phase parity checks can zip them).
    """
    if n < 1:
        raise ValueError(f"best_of needs n >= 1, got {n}")
    if not fns:
        raise ValueError("best_of needs at least one callable")
    outs = [BestOf() for _ in fns]
    for _ in range(n):
        for out, fn in zip(outs, fns):
            t0 = time.perf_counter()
            r = fn()
            dt = time.perf_counter() - t0
            if isinstance(r, SelfTimed):
                dt, r = r.seconds, r.value
            out.times.append(dt)
            out.results.append(r)
    return outs


def compiled_hlo_layout_census(fn, *args) -> dict:
    """jit-compile ``fn(*args)`` and count layout ops in the OPTIMIZED
    HLO — the channels-last region's CPU-measurable layout-stability
    probe (transposes/copies that survived XLA's cancellation), read by
    the ``TestConvBlockLayoutStability`` regression."""
    import re

    import jax

    hlo = jax.jit(fn).lower(*args).compile().as_text()
    return {
        "transposes": len(re.findall(r"= \S+ transpose\(", hlo)),
        "copies": len(re.findall(r"= \S+ copy\(", hlo)),
    }
