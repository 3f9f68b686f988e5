"""Median gap between the end of one step program and the start of the
next on the device, in ms, averaged over the chips."""

from statistics import fmean, median


def reduce(ctx, metric):
    per_chip = [median([e - s for s, e in v["gaps"]]) for v in ctx["views"]]
    return 1e3 * fmean(per_chip) if per_chip else None
