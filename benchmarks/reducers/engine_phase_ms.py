"""Host time a step in the engine's own phases, in ms: the median over
the ring's steady dispatches of the sum of the phases the metric's file
names (``phases``, fields of ``ParallelEngine``'s ``StepPhases`` without
the ``_ns``).

The engine stamps ``time.time_ns()`` once at each phase boundary of
``step`` and keeps the last few thousand records in a ring; the benchmark
reads it through ``paddle1_tpu.obs.costmodel.step_phase_records`` after
the window, with no handle on the engine. A dispatch that compiled is left
out. No ring (a program without it): None.
"""

from statistics import median


def program_records():
    try:
        from paddle1_tpu.obs import costmodel
        return costmodel.step_phase_records()
    except (ImportError, AttributeError):
        return []


PHASES = ("shard", "guard", "dispatch", "inflight_wait")


def reduce(ctx, metric, records=None):
    records = program_records() if records is None else records
    steady = [r for r in records if not r.compiled]
    if not steady:
        return None
    if metric.get("report"):
        report(steady)
    fields = [p + "_ns" for p in metric["phases"]]
    return 1e-6 * median(sum(getattr(r, f) for f in fields) / r.k
                         for r in steady)


def report(steady):
    """Each phase's median, and the dispatch that took the host longest
    with the phase it spent the time in: a wait for the device and a
    host that froze read differently here."""
    def ms(of):
        return ", ".join(f"{p} {1e-6 * of(p + '_ns'):.3f}" for p in PHASES)

    worst = max(steady, key=lambda r: sum(getattr(r, p + "_ns")
                                          for p in PHASES))
    print(f"benchmarks: phases: medians over {len(steady)} dispatches, ms: "
          + ms(lambda f: median(getattr(r, f) for r in steady)), flush=True)
    print(f"benchmarks: phases: the longest, dispatch {worst.step}, ms: "
          f"{ms(lambda f: getattr(worst, f))}; {worst.shard_calls} "
          "placements", flush=True)
