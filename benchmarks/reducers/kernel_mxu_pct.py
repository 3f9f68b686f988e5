"""A kernel family's share of the matrix unit's peak, in %: the FLOPs the
algorithm needs of the family's calls in one step (the function the
metric's file names under ``flops``, in the ``model_flops`` module of the
configuration of the cell it names under ``cell``: {kernel name: FLOPs a
step}) over the summed device time a step of the instructions of those
names and the chip's bf16 peak. The function counts what the mask lets
through, not the tiles a kernel runs, so the share cannot pass 100% by
counting hidden work.

**The share is right in the cell the file names and in no other**: a
reducer's ``ctx`` carries neither the cell that runs nor its
configuration, so the FLOPs are the named cell's whatever the trace was
taken from. A metric file of this reducer is listed under that one cell
in ``BENCHMARK.json`` (``test_sdar_yardstick.py`` holds it to that); a
second cell takes a file of its own until ``ctx`` says which cell runs
(PERF.md section 7). The name ends in ``_pct``, so the harness's refusal
of a ``_roofline`` or ``mfu`` share above 105% does not watch it either.

On the chip a Pallas kernel's instruction carries the kernel's own name
(``%p1t_flash_attention_fwd.26``). No view, no peak (a rehearsal), or no
such instruction in the trace (a program without the kernels, or one that
took XLA's composition): None, and the metric is left out of the line.
"""

import re
from statistics import fmean

from .. import spec, traffic
from .scope_ms import instruction


def seconds_a_step(view, names):
    """{kernel: one chip's seconds a step in its instructions}."""
    named = re.compile(r"^(%s)(\.\d+)?$" % "|".join(map(re.escape, names)))
    out = dict.fromkeys(names, 0.0)
    for name, seconds in view["ops"].items():
        hit = named.match(instruction(name))
        if hit:
            out[hit.group(1)] += seconds / len(view["step_s"])
    return out


def reduce(ctx, metric):
    if not ctx["views"] or not ctx.get("peak_flops_per_s"):
        return None
    cell = spec.load_json("workloads", metric["cell"] + ".json")
    cfg = spec.config(cell["config"])
    flops = getattr(spec.module("model_flops", cfg), metric["flops"])(
        cfg, traffic.environment(cfg, cell))
    seconds = fmean(sum(seconds_a_step(v, list(flops)).values())
                    for v in ctx["views"])
    if not seconds:
        return None
    return 100.0 * sum(flops.values()) / seconds / ctx["peak_flops_per_s"]
