"""Device time a step in the instructions whose scope matches the metric's
file, in ms, averaged over the chips.

A device event is named by its instruction's HLO text, which holds no
metadata; the program maps the instruction's name to the path of
``jax.named_scope``s it was traced under
(``paddle1_tpu.obs.costmodel.step_op_scopes``: ``make_train_step``'s
regions, the layer path, the op's name). The metric's file gives ``match``
and, optionally, ``exclude``: regular expressions searched in that path. An
instruction the map does not hold has the empty path, so a file whose
``match`` is empty and whose ``exclude`` names every region reads what is
left over. A fusion is counted under its own path alone, as the profiler's
framework-op view does; ``"report": true`` prints the fusions whose
instructions come from more than one region, with their time a step.

No map (a program without the scopes, as the parent of the PR that added
them) or no view: None, and the metric is left out of the line.
"""

import re
import time
from statistics import fmean


def instruction(event_name):
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    return event_name.partition(" = ")[0].strip().lstrip("%")


def program_maps():
    """(scopes, fused regions) of the live engine's step program, or
    (None, None) where the program cannot say."""
    try:
        from paddle1_tpu.obs import costmodel
        t = time.perf_counter()
        maps = costmodel.step_op_scopes(), costmodel.step_fused_regions()
    except (ImportError, AttributeError):
        return None, None
    if time.perf_counter() - t > 0.5:       # the first call lowers and loads
        print("benchmarks: scopes: reading the program's map took "
              f"{time.perf_counter() - t:.1f} s, after the window", flush=True)
    return maps


def seconds_a_step(view, scopes, match, exclude=None):
    """One chip's seconds a step in the matching instructions."""
    total = 0.0
    for name, seconds in view["ops"].items():
        path = scopes.get(instruction(name), "")
        if match.search(path) and not (exclude and exclude.search(path)):
            total += seconds
    return total / len(view["step_s"])


def reduce(ctx, metric, maps=None):
    if not ctx["views"]:
        return None
    scopes, fused = maps if maps is not None else program_maps()
    if not scopes:
        return None
    match = re.compile(metric["match"])
    exclude = re.compile(metric["exclude"]) if metric.get("exclude") else None
    if metric.get("report"):
        report(ctx["views"][0], scopes, fused or {})
    return 1e3 * fmean(seconds_a_step(v, scopes, match, exclude)
                       for v in ctx["views"])


def report(view, scopes, fused, top=12):
    """What PERF.md lists, in ms a step on the first chip: how many of
    the trace's instructions the map holds, the families that take most
    time with the end of their scope, those in no region, and the fusions
    whose instructions come from more than one region."""
    from ..trace_reduce import op_family
    from paddle1_tpu.obs.costmodel import region_of
    steps = len(view["step_s"])
    rows = [(op_family(name), scopes.get(instruction(name), ""),
             "+".join(fused.get(instruction(name), ())), seconds)
            for name, seconds in view["ops"].items()]

    def table(title, keyed, n):
        """``keyed``: (key, seconds) pairs; the n largest keys."""
        sums = {}
        for key, seconds in keyed:
            count, total = sums.get(key, (0, 0.0))
            sums[key] = (count + 1, total + seconds)
        ms = 1e3 * sum(t for _, t in sums.values()) / steps
        print(f"benchmarks: scopes: {title} take {ms:.3f} ms a step; "
              "the largest:", flush=True)
        for key, (count, total) in sorted(
                sums.items(), key=lambda kv: -kv[1][1])[:n]:
            print(f"benchmarks: scopes:   {1e3 * total / steps:8.3f} ms  "
                  + "  ".join(key) + f" x{count}", flush=True)

    held = sum(instruction(n) in scopes for n in view["ops"])
    print(f"benchmarks: scopes: the program's map holds {held} of the "
          f"{len(view['ops'])} instructions in the trace", flush=True)
    table("all instructions (region, end of scope, family)",
          (((region_of(path) or "no region",
             ".../" + "/".join(path.split(";")[0].split("/")[-3:]), family),
            s) for family, path, _, s in rows), top)
    table("instructions in no region (scope, family)",
          (((repr(path[-60:]), family), s) for family, path, _, s in rows
           if not region_of(path)), 6)
    table("fusions that span regions",
          (((regions, family), s) for family, _, regions, s in rows
           if "+" in regions), top)
