"""Peak bytes on the fullest chip of the cell (in use plus reserved for
the step program's scratch), in GiB, read from ``memory_stats()`` after the
window and before the reference runs."""


def reduce(ctx, metric):
    return max(ctx["peak_bytes"]) / 2 ** 30 if ctx["peak_bytes"] else None
