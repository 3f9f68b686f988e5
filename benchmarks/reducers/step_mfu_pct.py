"""Model FLOPs of one step over the step program's median device time and
the chips' peak, in %. Recomputation is not counted, so this cannot pass
100% honestly; the harness fails a run that reads above 105%."""

from . import device_step_ms


def reduce(ctx, metric):
    step_ms = device_step_ms.reduce(ctx, metric)
    if not step_ms:
        return None
    return 100.0 * ctx["flops_per_step"] / (step_ms * 1e-3) / (
        ctx["peak_flops_per_s"] * ctx["n_devices"])
