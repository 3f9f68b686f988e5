"""One small reader per per-layer metric: ``reduce(ctx, metric) -> number |
None``, ``metric`` being the metric's file under ``layer_metrics/``.

``ctx`` is what a traced run collected:
  ``views``      per-chip reductions of the device trace
                 (:func:`benchmarks.trace_reduce.device_view`), [] if none;
  ``spans``      the benchmark's host spans inside the trace, (name, s, e);
  ``counters``   {"compiles_in_window": int, ...} read from the program;
  ``peak_bytes`` [peak bytes in use of every chip of the cell];
  ``flops_per_step``, ``peak_flops_per_s``, ``n_devices``;
  ``cell``       the running cell's file (``workloads/<cell>.json``) and
  ``config``     its configuration as it runs, so that a reader takes sizes
                 from what runs and not from a cell its file names;
  ``peaks``      the chip's row of ``peaks.json``, None in a rehearsal.
A reader that finds nothing to read returns None and the metric is left out
of the line.
"""


def model_count(ctx, name):
    """What the function ``name`` of the running configuration's
    ``model_flops`` module counts (a dict of FLOPs or of bytes a step) at
    the running cell's size, or None where the module has no such
    function: the configuration has nothing of the kind to read. Not for a
    rehearsal, whose sizes are not the cell's."""
    from .. import spec, traffic
    cfg = ctx["config"]
    count = getattr(spec.module("model_flops", cfg), name, None)
    return count and count(cfg, traffic.environment(cfg, ctx["cell"]))
