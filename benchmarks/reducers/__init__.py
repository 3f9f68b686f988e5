"""One small reader per per-layer metric: ``reduce(ctx) -> number | None``.

``ctx`` is what a traced run collected:
  ``views``      per-chip reductions of the device trace
                 (:func:`benchmarks.trace_reduce.device_view`), [] if none;
  ``spans``      the benchmark's host spans inside the trace, (name, s, e);
  ``counters``   {"compiles_in_window": int, ...} read from the program;
  ``peak_bytes`` [peak bytes in use of every chip of the cell];
  ``flops_per_step``, ``peak_flops_per_s``, ``n_devices``.
A reader that finds nothing to read returns None and the metric is left out
of the line.
"""
