"""1 - (union of the intervals in which an operation ran) / (traced
window), in %, averaged over the chips."""

from statistics import fmean


def reduce(ctx, metric):
    shares = [1.0 - v["busy_s"] / v["window_s"] for v in ctx["views"]]
    return 100.0 * fmean(shares) if shares else None
