"""Run one cell of the benchmark once.

    python -m benchmarks.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Builds the cell's model from ``--seed`` on the device, builds
``ParallelEngine`` as a trainer would, drives it through its first steps
(which compile, and which the reference follows afterwards), measures a
closed loop for ``--seconds``, checks the outputs against the plain
reference and prints the contract's JSON object as the last line of
standard output. With ``--trace 0`` the metrics are the cell's end-to-end
metrics, with ``--trace 1`` its per-layer metrics.

A backend that is not a TPU is an error. ``--rehearsal 1`` (CPU only) runs
the same path at the tiny sizes the data files give for it; every metric it
prints is named ``<metric>@cpu_rehearsal``.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402

import numpy as np  # noqa: E402

from . import spec, traffic  # noqa: E402

REHEARSAL_TAG = "@cpu_rehearsal"
MFU_REFUSED_ABOVE = 105.0
HOST_CLOCK_SPAN_MS = 250.0


def say(*words):
    print("benchmarks:", *words, flush=True)


def refuse(why, code=2):
    print(f"benchmarks: {why}", file=sys.stderr, flush=True)
    raise SystemExit(code)


def devices_for(chips, rehearsal):
    """Place the compile cache, then the cell's devices, or exit."""
    import jax
    if not rehearsal:
        # the cache sits where the environment says, or at one fixed
        # path inside the checkout: the path is part of the cache's key
        if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
            jax.config.update("jax_compilation_cache_dir",
                              os.path.join(spec.ROOT, ".jax_cache"))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    devs = jax.devices()
    platform = devs[0].platform
    if rehearsal:
        if platform != "cpu":
            refuse(f"--rehearsal is for the CPU; JAX reports {platform!r}")
        return devs[:chips]
    if platform != "tpu":
        refuse(f"found no TPU: JAX's backend is {platform!r} "
               f"({devs[0].device_kind!r}). The benchmark measures on the "
               "chip only; --rehearsal 1 rehearses on a CPU")
    if len(devs) < chips:
        refuse(f"the cell needs {chips} chips, JAX reports {len(devs)}")
    return devs[:chips]


def seed_key(seed):
    import jax
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)


class Loop:
    """The trainer's loop: next host batch -> ``shard_batch`` ->
    ``engine.step`` under the engine's own in-flight window, one loss read
    to the host every ``readback_every`` steps."""

    def __init__(self, engine, pool, schedule, readback_every):
        from jax.profiler import TraceAnnotation
        self.engine, self.pool, self.schedule = engine, pool, schedule
        self.readback_every = readback_every
        self.span = TraceAnnotation
        self.steps = 0
        self.raised = 0
        self.pending = []       # futures not yet fetched
        self.values = []        # every fetched loss, in step order
        self.stamps = []        # (start, placed, returned, after readback)

    def step(self, readback=False):
        i = self.steps
        self.steps += 1
        t0 = time.perf_counter()
        t1 = t0
        fut = None
        try:
            with self.span("bench/input"):
                placed = self.engine.shard_batch(self.pool[i % len(self.pool)])
            t1 = time.perf_counter()
            with self.span("bench/dispatch"):
                fut = self.engine.step(placed,
                                       lr=spec.lr_at(self.schedule, i))
            self.pending.append(fut)
        except Exception as e:     # counted, and the run goes on
            say(f"step {i} raised {type(e).__name__}: {e}")
            self.raised += 1
        t2 = time.perf_counter()
        value = None
        if fut is not None and (readback or (i + 1) % self.readback_every == 0):
            with self.span("bench/readback"):
                value = float(fut)
        self.stamps.append((t0, t1, t2, time.perf_counter()))
        return value

    def fetch(self):
        """Bring every loss not yet fetched to the host."""
        import jax
        self.values += [float(x) for x in jax.device_get(
            [f.data for f in self.pending])]
        self.pending.clear()
        return self.values


def percentile(values, q):
    return float(np.percentile(np.asarray(values, np.float64), q))


def return_gaps_ms(returned, t0):
    """The time between successive returns of ``engine.step``, the first
    from the opening of the window."""
    edges = [t0] + list(returned)
    return [1e3 * (b - a) for a, b in zip(edges, edges[1:])]


def first_steps(cell, cfg, env, devices, seed, loop_cfg, limits):
    """Set-up: the weights from the seed in one jitted call on the device,
    the program built on them, and its first steps driven through the
    loop's own call and feed. The loop that comes back is the one the
    window drives.

    -> (loop, shown, host_weights, pool, leaves): ``shown`` holds what the
    reference is compared with (check.compare's ``program``).
    """
    import jax
    from . import check
    reference = spec.module("reference", cfg)
    program = spec.module("program", cfg)
    leaves = program.leaves(cfg)

    @jax.jit
    def make(key):
        w = reference.init_params(cfg, key)
        return w, {p: (w[r] if i is None else w[r][i]) for p, r, i in leaves}

    weights, placed = make(seed_key(seed))
    host_weights = jax.device_get(weights)
    del weights
    pool = traffic.batches(cell, env, seed, loop_cfg["pool"])
    say(f"weights and batches from the seed "
        f"{time.perf_counter() - T_START:.2f}s after process start")
    built = program.build(cfg, env, placed, devices)
    del placed
    engine, model = built["engine"], built["model"]
    start = {k: model.state_dict()[k].data for k in engine.params}
    say(f"built the model and the engine {time.perf_counter() - T_START:.2f}s "
        "after process start")

    loop = Loop(engine, pool, cfg["optimizer"]["lr_schedule"],
                loop_cfg["readback_every"])
    slot, factor = built["first_grad"]
    shown = {"losses": []}
    for i in range(loop_cfg["check_steps"]):
        shown["losses"].append(loop.step(readback=True))
        if i == 0:
            say(f"first step, with its compile or cache load, returned "
                f"{time.perf_counter() - T_START:.2f}s after process start")
            first = {k: v[slot] for k, v in engine.opt_state[0].items()}
            grad = check.program_norms(first)
            if "grad_direction" in limits:
                shown["matrices"] = check.program_matrices(first, factor)
            del first
    delta = check.program_delta_norms(engine.params, start)
    shown["grad"] = {k: factor * float(v) for k, v in grad.items()}
    shown["delta"] = {k: float(v) for k, v in delta.items()}
    return loop, shown, host_weights, pool, leaves


def run(args):
    rehearsal = bool(args.rehearsal)
    cell = spec.cell(args.workload)
    loop_cfg = spec.loop(cell, rehearsal)
    cfg = spec.config(cell["config"], rehearsal)
    limits = spec.limits(cfg["name"], rehearsal)
    env = traffic.environment(cfg, cell, rehearsal)
    devices = devices_for(cell["chips"], rehearsal)
    import jax
    from . import check, peaks, trace_reduce
    flops = spec.module("model_flops", cfg).train_step_flops(cfg, env)
    kind = devices[0].device_kind
    peak = None if rehearsal else peaks.of(kind)
    peak_flops = peak and peak["bf16_flops_per_s"]
    say(f"cell {cell['name']} of {spec.ROOT} on {len(devices)} x {kind}; batch "
        f"{env['batch']}, dims {cell['rehearsal']['dims'] if rehearsal else cell['dims']}; "
        f"model FLOPs a step {flops:.6g}")

    loop, shown, host_weights, pool, leaves = first_steps(
        cell, cfg, env, devices, args.seed, loop_cfg, limits)
    engine = loop.engine
    n_check = loop_cfg["check_steps"]
    for _ in range(loop_cfg["warm_steps"]):
        loop.step()
    if loop.steps % loop_cfg["readback_every"]:
        refuse("check_steps + warm_steps must be a multiple of "
               "readback_every, so that the window opens on a drained "
               "device", 1)
    misses0 = engine.cache_stats()["misses"]
    first, first_value = loop.steps, len(loop.fetch())
    trace_dir = os.path.join(spec.ROOT, ".bench_trace", cell["name"])
    trace_at = first + loop_cfg["trace_first_step"] if args.trace else None
    trace_until = (trace_at + loop_cfg["trace_steps"]) if args.trace else None
    tracing = False

    # -- the measured window
    t0 = time.perf_counter()
    setup_s = t0 - T_START
    deadline = t0 + args.seconds
    while time.perf_counter() < deadline:
        i = loop.steps
        if i == trace_at:
            shutil.rmtree(trace_dir, ignore_errors=True)
            jax.profiler.start_trace(trace_dir)
            tracing = True
        elif tracing and i == trace_until:
            jax.profiler.stop_trace()
            tracing = False
        loop.step()
    if loop.pending:
        float(loop.pending[-1])          # every step has run by now
    t1 = time.perf_counter()
    if tracing:
        jax.profiler.stop_trace()
    misses1 = engine.cache_stats()["misses"]
    n_steps = loop.steps - first
    window_losses = loop.fetch()[first_value:]
    failed = loop.raised + sum(not np.isfinite(x) for x in window_losses)
    # the v5e's runtime counts a program's scratch as reserved, not as in
    # use: the chip's peak is the two together
    stats = [d.memory_stats() or {} for d in devices]
    peak_bytes = [int(m.get("peak_bytes_in_use", 0))
                  + int(m.get("peak_bytes_reserved", 0)) for m in stats]
    say(f"memory_stats of the first chip: {stats[0]}")

    stamps = loop.stamps[first:]
    gaps = return_gaps_ms([st[2] for st in stamps], t0)
    p95 = percentile(gaps, 95) if gaps else None
    end_to_end = {
        "samples_per_s": (n_steps * env["batch"] / (t1 - t0), "samples/s"),
        "step_ms_p95": (p95, "ms"),
        "setup_s": (setup_s, "s")}
    say(f"window {t1 - t0:.3f}s, {n_steps} steps, {failed} failed; "
        f"step_ms_p95 over {len(gaps)} returns of engine.step (median "
        f"{percentile(gaps, 50) if gaps else float('nan'):.3f} ms)")
    if p95 is not None and p95 < HOST_CLOCK_SPAN_MS:
        say(f"step_ms_p95 reads {p95:.3f} ms, a span of under "
            f"{HOST_CLOCK_SPAN_MS:g} ms: the host clock's half millisecond "
            "is over 0.2% of it")
    if stamps:
        k = max(range(len(stamps)), key=lambda j: stamps[j][3] - stamps[j][0])
        a, b, c, d = stamps[k]
        say(f"longest step of the loop: window step {k}, input "
            f"{1e3 * (b - a):.1f} ms, dispatch {1e3 * (c - b):.1f} ms, "
            f"readback {1e3 * (d - c):.1f} ms; {misses1 - misses0} compiles "
            "in the window")
    if peak_flops:
        say(f"end-to-end model FLOPs utilisation "
            f"{100 * flops * n_steps / (t1 - t0) / (peak_flops * len(devices)):.2f}% "
            f"of {len(devices)} x {peak_flops:.3g} FLOP/s")

    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": max(peak_bytes)}
    result = {"attempted": n_steps, "failed": int(failed)}
    if args.trace:
        say("end-to-end readings of this traced run (not its metrics): "
            + ", ".join(f"{k} {v[0]}" for k, v in end_to_end.items()))
        trace = {"devices": {}, "spans": []}
        try:
            trace = trace_reduce.load(trace_reduce.find_xplane(trace_dir))
        except FileNotFoundError as e:
            say(f"no trace to reduce: {e}")
        shutil.rmtree(trace_dir, ignore_errors=True)
        views = trace_reduce.views(trace)
        ctx = {"views": views, "spans": trace["spans"],
               "counters": {"compiles_in_window": misses1 - misses0},
               "peak_bytes": [b for b in peak_bytes if b],
               "flops_per_step": flops, "peak_flops_per_s": peak_flops,
               "n_devices": len(devices), "cell": cell, "config": cfg,
               "peaks": peak}
        files = spec.layer_metrics()
        metrics = {}
        for entry in spec.per_layer_for(cell["name"]):
            metric = files[entry["name"]]
            value = spec.reducer(metric["reducer"])(ctx, metric)
            if value is not None:
                metrics[entry["name"]] = (value, metric["unit"])
        for name, (value, _) in metrics.items():
            if (name.endswith("_roofline") or "mfu" in name) \
                    and value > MFU_REFUSED_ABOVE:
                refuse(f"{name} reads {value:.2f}% > {MFU_REFUSED_ABOVE}%: "
                       "the FLOPs are counted too high or the time leaves "
                       "out part of the work", 1)
        if views:
            device["busy_s"] = statistics.fmean(v["busy_s"] for v in views)
            device["window_s"] = statistics.fmean(v["window_s"] for v in views)
            result["breakdown"] = trace_reduce.breakdown(trace)
    else:
        wanted = {m["name"] for m in spec.end_to_end_for(cell["name"])}
        metrics = {k: v for k, v in end_to_end.items()
                   if k in wanted and v[0] is not None}

    # -- correct: the plain reference follows the first steps, after the
    # engine is freed and the peak is read
    del loop.engine, engine
    gc.collect()
    t = time.perf_counter()
    lrs = [spec.lr_at(cfg["optimizer"]["lr_schedule"], i)
           for i in range(n_check)]
    ref = check.reference_record(cfg, host_weights, pool[:n_check], lrs,
                                 keep_matrices="grad_direction" in limits)
    ok, rows = check.compare(shown, ref, leaves, limits)
    for what, value, limit, good, note in rows:
        say(f"compare {what}: {value:.6g} (limit {limit:g}) "
            f"{'ok' if good else 'NOT OK'}; {note}")
    say(f"reference and comparison took {time.perf_counter() - t:.2f}s")
    # a gap that is no number (a loss that is none) stays valid JSON
    result["compared"] = {
        what: {"value": value if np.isfinite(value) else None, "limit": limit}
        for what, value, limit, _, _ in rows}

    tag = REHEARSAL_TAG if rehearsal else ""
    result.update({
        "correct": bool(ok and n_steps > 0 and failed == 0),
        "metrics": {k + tag: {"value": v, "unit": u}
                    for k, (v, u) in metrics.items()},
        "device": device})
    return result


def main(argv=None):
    ap = argparse.ArgumentParser(prog="python -m benchmarks.run")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearsal", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    result = run(args)
    # each number compared beside its limit: the last lines of standard
    # error, and the last key of the result's line
    for what, row in result["compared"].items():
        print(f"benchmarks: compared {what}: {row['value']} "
              f"(limit {row['limit']})", file=sys.stderr, flush=True)
    keys = ("correct", "attempted", "failed", "metrics", "device", "breakdown",
            "compared")
    print(json.dumps({k: result[k] for k in keys if k in result}), flush=True)


if __name__ == "__main__":
    main()
