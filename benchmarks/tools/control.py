"""The readings that the limits of ``correct`` are set from, on the chip,
at a cell's own size, in one process.

    python -m benchmarks.tools.control --workload <cell> \
        --seeds 1,2,...,12 --control-seeds 1,2,3

For each seed the program is built and driven through the harness's first
steps, then freed, and the plain float32 reference follows the same steps:
the gaps printed are the sound runs'. For a control seed (one of
``--seeds`` or not) the reference follows them again in each of
``--precisions`` (default: the control the configuration names, the
nearest precision below the one it states) and is put in the program's
place, leaf by leaf of the program's own map. One JSON line per reading;
the benchmark's own runs never run this.
"""

import argparse
import gc
import json

from benchmarks import check, spec, traffic
from benchmarks.run import devices_for, first_steps, seed_key


def reading(rows, **what):
    print(json.dumps({**what,
                      "passes_limits": all(good for _, _, _, good, _ in rows),
                      "gaps": {name: value for name, value, *_ in rows},
                      "notes": {name: note for name, _, _, _, note in rows}}),
          flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--precisions", default="")
    ap.add_argument("--rehearsal", type=int, default=0)
    args = ap.parse_args()
    rehearsal = bool(args.rehearsal)
    cell = spec.cell(args.workload)
    cfg = spec.config(cell["config"], rehearsal)
    env = traffic.environment(cfg, cell, rehearsal)
    devices = devices_for(cell["chips"], rehearsal)
    loop_cfg = spec.loop(cell, rehearsal)
    limits = spec.limits(cfg["name"], rehearsal)
    turn = "grad_direction" in limits
    n = loop_cfg["check_steps"]
    lrs = [spec.lr_at(cfg["optimizer"]["lr_schedule"], i) for i in range(n)]
    precisions = [p for p in args.precisions.split(",") if p] \
        or [cfg["precision"]["control"]]
    control_seeds = [int(s) for s in args.control_seeds.split(",") if s]
    seeds = [int(s) for s in args.seeds.split(",") if s]
    reference = spec.module("reference", cfg)
    leaves = spec.module("program", cfg).leaves(cfg)
    for seed in seeds + [s for s in control_seeds if s not in seeds]:
        if seed in seeds:
            loop, shown, weights, pool, _ = first_steps(
                cell, cfg, env, devices, seed, loop_cfg, limits)
            del loop
            gc.collect()
        else:
            import jax
            weights = jax.device_get(jax.jit(
                lambda k: reference.init_params(cfg, k))(seed_key(seed)))
            pool = traffic.batches(cell, env, seed, n)
        sound = check.reference_record(cfg, weights, pool[:n], lrs,
                                       keep_matrices=turn)
        if seed in seeds:
            reading(check.compare(shown, sound, leaves, limits)[1],
                    cell=cell["name"], seed=seed, what="program")
        if seed not in control_seeds:
            continue
        for precision in precisions:
            other = check.reference_record(cfg, weights, pool[:n], lrs,
                                           precision, keep_matrices=turn)
            reading(check.compare(check.in_programs_place(other, leaves),
                                  sound, leaves, limits)[1],
                    cell=cell["name"], seed=seed, what=precision)


if __name__ == "__main__":
    main()
