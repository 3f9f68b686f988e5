"""What the routed-expert layers counted on the device, as one number: the
metric's file says which (``read``).

``paddle1_tpu``'s ``RoutedExperts`` adds, in every forward of a compiled
step, the rows each held expert ran, the held picks, the picks that found
no row within the capacity and went through ``moe_overflow``, and 1 to
``steps``; ``ParallelEngine`` carries the counts beside the parameters and
the benchmark reads them through
``paddle1_tpu.obs.costmodel.step_expert_load`` after the window, with no
handle on the engine (as ``engine_phase_ms`` reads the phases' ring).

**The mean is over every step the engine ran, the set-up's 10 included**
(3 checked and 7 warm steps before the window opens): ``run.py`` gives a
reducer no hook at the window's start, so there is no count to subtract.
At 60 to 90 steps a window the set-up is a tenth of the mean, and the
counts only rise through a run.

  ``held_picks_pct``   held picks of the picks made (``tokens x top_k`` a
                       layer a step), in %, the mean over layers and steps;
                       ``100 x held / num_experts`` under even routing
  ``expert_rows_max``  rows a step of the fullest (layer, expert)
  ``late_picks``       held picks a step, over all layers, that found no
                       row: the overflow path ran for them

A program without the counters (the reader is missing, no engine stepped,
no layer counts): None.
"""


def program_load():
    try:
        from paddle1_tpu.obs import costmodel
        return costmodel.step_expert_load()
    except (ImportError, AttributeError):
        return None


def reduce(ctx, metric, load=None):
    load = program_load() if load is None else load
    layers = [c for c in (load or {}).values() if c["steps"] > 0]
    if not layers:
        return None
    read = metric["read"]
    if read == "held_picks_pct":
        return sum(100.0 * c["held_picks"]
                   / (c["steps"] * c["picks_made_a_step"])
                   for c in layers) / len(layers)
    if read == "expert_rows_max":
        return max(rows / c["steps"] for c in layers for rows in c["rows"])
    if read == "late_picks":
        return sum(c["late_picks"] / c["steps"] for c in layers)
    raise ValueError(f"{metric['name']}: read={read!r}")
