"""Mean length of one of the benchmark's host spans, in ms. The span is
named by the metric's file (``span``)."""


def reduce(ctx, metric):
    lens = [e - s for name, s, e in ctx["spans"] if name == metric["span"]]
    return 1e3 * sum(lens) / len(lens) if lens else None
