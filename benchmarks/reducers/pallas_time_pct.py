"""Share of the step programs' device time spent in ``tpu_custom_call``
operations (the Pallas kernels), in %."""


def reduce(ctx, metric):
    total = sum(sum(v["step_s"]) for v in ctx["views"])
    if not total:
        return None
    return 100.0 * sum(v["pallas_s"] for v in ctx["views"]) / total
