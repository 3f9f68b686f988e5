"""Median duration of the step program on the device, in ms, averaged over
the chips."""

from statistics import fmean, median


def reduce(ctx, metric):
    per_chip = [median(v["step_s"]) for v in ctx["views"]]
    return 1e3 * fmean(per_chip) if per_chip else None
