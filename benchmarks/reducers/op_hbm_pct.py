"""An op's share of the memory's peak, in %: the bytes the op's calls of
one step have to read and write over the device time a step of the
instructions under the op's scope and the chip's HBM peak.

The bytes come from the function the metric's file names under ``bytes``,
in the ``model_flops`` module of the configuration that runs
(``ctx["config"]``), at the running cell's size (``ctx["cell"]``): a dict
whose values are summed. It counts what the op has to move once, by the
closed form of its shapes: a forward that a recomputed segment runs again
adds to the time and not to the bytes, so the share cannot pass 100% by
counting it. The scope is the metric's ``match`` (and ``exclude``), as
``scope_ms`` reads it; the peak is ``hbm_bytes_per_s`` of the chip's row in
``peaks.json`` (``ctx["peaks"]``).

No view, no peak (a rehearsal), no map of scopes, no such function in the
configuration's module or no instruction under the scope: None, and the
metric is left out of the line.
"""

from . import model_count, scope_ms


def reduce(ctx, metric, maps=None):
    peak = (ctx.get("peaks") or {}).get("hbm_bytes_per_s")
    if not ctx["views"] or not peak:
        return None
    moved = model_count(ctx, metric["bytes"])
    ms = moved and scope_ms.reduce(ctx, metric, maps)
    if not ms:
        return None
    return 100.0 * sum(moved.values()) / (1e-3 * ms) / peak
