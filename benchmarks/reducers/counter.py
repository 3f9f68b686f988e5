"""A count the harness read from the program, named by the metric's file
(``counter``)."""


def reduce(ctx, metric):
    return ctx["counters"].get(metric["counter"])
