"""A kernel family's share of the matrix unit's peak, in %: the FLOPs the
algorithm needs of the family's calls in one step over the summed device
time a step of the family's instructions and the chip's bf16 peak.

The FLOPs come from the function the metric's file names under ``flops``,
in the ``model_flops`` module of the configuration that runs
(``ctx["config"]``), at the running cell's size (``ctx["cell"]``):
{kernel name: FLOPs a step}. The function counts what the mask lets
through, not the tiles a kernel runs, so the share cannot pass 100% by
counting hidden work. A configuration whose module has no such function
has no such kernels to read: None.

On the chip a Pallas kernel's instruction carries the kernel's own name
(``%p1t_flash_attention_fwd.26``). A name of the function's stands for the
instructions called so with or without ``_<more>`` behind it
(``p1t_flash_attention_bwd`` finds ``p1t_flash_attention_bwd_dkv.17``),
so the program may say more in a kernel's name than the benchmark knows.
No view, no peak (a rehearsal), or no such instruction in the trace (a
program without the kernels, or one that took XLA's composition): None,
and the metric is left out of the line.
"""

import re
from statistics import fmean

from . import model_count
from .scope_ms import instruction


def seconds_a_step(view, names):
    """{kernel: one chip's seconds a step in its instructions}."""
    longest_first = sorted(names, key=len, reverse=True)
    named = re.compile(r"^(%s)(_\w+)?(\.\d+)?$"
                       % "|".join(map(re.escape, longest_first)))
    out = dict.fromkeys(names, 0.0)
    for name, seconds in view["ops"].items():
        hit = named.match(instruction(name))
        if hit:
            out[hit.group(1)] += seconds / len(view["step_s"])
    return out


def reduce(ctx, metric):
    if not ctx["views"] or not ctx.get("peak_flops_per_s"):
        return None
    flops = model_count(ctx, metric["flops"])
    if not flops:
        return None
    seconds = fmean(sum(seconds_a_step(v, list(flops)).values())
                    for v in ctx["views"])
    if not seconds:
        return None
    return 100.0 * sum(flops.values()) / seconds / ctx["peak_flops_per_s"]
