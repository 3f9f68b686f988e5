"""The one general generator of training traffic.

A cell's file gives ``fields``: for each leaf of a batch how it is drawn (a
module of ``draws/``), its shape in symbols and its dtype. Symbols resolve
to the cell's ``batch``, its ``dims`` or a number of the configuration.
Every seed draws the same shapes: a seed changes values, never the work.
"""

import numpy as np

from . import spec


def _resolve(sym, env):
    if isinstance(sym, (int, float)):
        return int(sym)
    if isinstance(sym, list):
        out = 1
        for s in sym:
            out *= _resolve(s, env)
        return out
    return int(env[sym])


def environment(cfg, cell, rehearsal=False):
    src = cell["rehearsal"] if rehearsal else cell
    env = {k: v for k, v in cfg.items() if isinstance(v, (int, float))}
    env.update(src["dims"])
    env["batch"] = src["batch"]
    return env


def batches(cell, env, seed, count):
    """``count`` distinct host batches (dicts of numpy arrays)."""
    rng = np.random.default_rng([int(seed), 0x7261])

    def resolve(sym):
        return _resolve(sym, env)

    return [{name: spec.draw(field["draw"])(rng, field, resolve)
             for name, field in sorted(cell["fields"].items())}
            for _ in range(count)]
