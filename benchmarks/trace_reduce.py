"""From the profiler's trace (``*.xplane.pb``) to intervals and shares.

What a trace of this system looks like on a TPU v5e (JAX 0.9): one plane
``/device:TPU:<n>`` per chip with the lines ``XLA Modules`` (one event per
executed program, named ``jit_<fn>(<fingerprint>)``), ``XLA Ops`` (one event
per HLO instruction, named by its whole HLO text) and ``Async XLA Ops``
(copies in flight, which overlap compute and are not counted as busy); one
plane ``/host:CPU`` whose ``python`` line holds the ``TraceAnnotation``
spans. All on one clock, in nanoseconds.
"""

import glob
import os
import re

MODULES, OPS = "XLA Modules", "XLA Ops"


def find_xplane(trace_dir):
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no xplane.pb under {trace_dir}")
    return found[-1]


def load(path, span_prefix="bench/"):
    """-> {"devices": {plane: {"modules": [...], "ops": [...]}},
    "spans": [...]}; every event is (name, start_s, end_s)."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices, spans = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {line.name: line for line in plane.lines}
            if MODULES not in lines or OPS not in lines:
                continue
            devices[plane.name] = {
                "modules": _events(lines[MODULES]),
                "ops": _events(lines[OPS])}
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                spans += [e for e in _events(line)
                          if e[0].startswith(span_prefix)]
    return {"devices": devices, "spans": sorted(spans, key=lambda e: e[1])}


def _events(line):
    out = [(e.name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
           for e in line.events]
    out.sort(key=lambda e: e[1])
    return out


def step_events(modules):
    """The executions of the program that took most device time: the
    train step."""
    total = {}
    for name, start, end in modules:
        total[name] = total.get(name, 0.0) + (end - start)
    if not total:
        return []
    top = max(total, key=total.get)
    return [e for e in modules if e[0] == top]


def union(intervals):
    """Merge (start, end) intervals -> sorted, disjoint."""
    out = []
    for start, end in sorted(intervals):
        if out and start <= out[-1][1]:
            if end > out[-1][1]:
                out[-1] = (out[-1][0], end)
        else:
            out.append((start, end))
    return out


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def device_view(dev):
    """One chip's reduction over the window from the start of its first
    whole step program to the end of its last.

    -> None when the trace holds fewer than two step programs, else a dict:
    window_s, busy_s, step_s (list), gap_s (list of (start, end) between
    step programs), ops (name -> seconds), idle (disjoint idle intervals
    inside the window).
    """
    steps = step_events(dev["modules"])
    if len(steps) < 2:
        return None
    lo, hi = steps[0][1], steps[-1][2]
    busy = union(clip([(s, e) for _, s, e in dev["ops"]], lo, hi))
    busy_s = sum(e - s for s, e in busy)
    idle, at = [], lo
    for s, e in busy:
        if s > at:
            idle.append((at, s))
        at = max(at, e)
    if hi > at:
        idle.append((at, hi))
    ops = {}
    for name, s, e in dev["ops"]:
        if e <= lo or s >= hi:
            continue
        ops[name] = ops.get(name, 0.0) + (e - s)
    return {"window_s": hi - lo, "busy_s": busy_s,
            "step_s": [e - s for _, s, e in steps],
            "gaps": [(a[2], b[1]) for a, b in zip(steps, steps[1:])],
            "ops": ops, "idle": idle}


def views(trace):
    out = [device_view(d) for _, d in sorted(trace["devices"].items())]
    return [v for v in out if v is not None]


def host_span_at(spans, start, end):
    """Which of the benchmark's host spans covers most of [start, end]:
    its name without the prefix, or "none"."""
    best, best_s = "none", 0.0
    for name, s, e in spans:
        if s >= end:
            break
        cover = min(e, end) - max(s, start)
        if cover > best_s:
            best, best_s = name.split("/", 1)[-1], cover
    return best


def op_family(name, width=60):
    """An HLO instruction's text cut to its name without the running
    number and the start of its right-hand side: the twelve per-layer
    copies of one fusion then share a family."""
    lhs, _, rhs = name.partition(" = ")
    return (re.sub(r"\.\d+$", "", lhs) + " " + rhs)[:width].strip()


def breakdown(trace, top=10):
    """The contract's ``breakdown``: the device operations that took most
    time, by family (``x<n>``: how many instructions share it), and the
    idle time by what the host was doing. Idle between two
    step programs is named ``between_steps/<span>``; idle inside a step
    program is summed as ``within_step``. Seconds over the traced window
    of the first chip."""
    vs = views(trace)
    if not vs:
        return None
    v = vs[0]
    families = {}
    for name, seconds in v["ops"].items():
        n, total = families.get(op_family(name), (0, 0.0))
        families[op_family(name)] = (n + 1, total + seconds)
    ops = sorted(families.items(), key=lambda kv: -kv[1][1])[:top]
    idle = {}
    between = union(v["gaps"])
    for s, e in v["idle"]:
        inside = sum(min(e, ge) - max(s, gs) for gs, ge in between
                     if min(e, ge) > max(s, gs))
        if inside > 0:
            key = "between_steps/" + host_span_at(trace["spans"], s, e)
            idle[key] = idle.get(key, 0.0) + inside
        if (e - s) - inside > 0:
            idle["within_step"] = idle.get("within_step", 0.0) + (e - s) - inside
    gaps = sorted(idle.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[f"{k} x{n}", s] for k, (n, s) in ops],
            "idle_gaps": [[k, s] for k, s in gaps]}
