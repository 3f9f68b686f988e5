#!/usr/bin/env python
"""Config-driven operator micro-benchmark harness.

Analog of the reference's
/root/reference/paddle/fluid/operators/benchmark/op_tester.cc +
op_tester_config.cc: a config file describes {op, input shapes, dtype,
repeat}; the harness builds random inputs, runs the op, and reports
timing. TPU-native: each case is timed eagerly AND under jit (compiled,
block_until_ready per repeat), since the jit number is the one that
matters on TPU.

Usage:
    python tools/op_benchmark.py --config tools/op_bench_example.json
    python tools/op_benchmark.py --op matmul --shapes 512x512,512x512 \
        --dtype float32 --repeat 20

Config JSON: a list of cases:
    [{"op": "nn.functional.relu", "shapes": ["1024x1024"],
      "dtype": "float32", "repeat": 50, "backward": true}]

Op names resolve inside the paddle1_tpu namespace (e.g. "add",
"ops.math_ops.matmul", "nn.functional.softmax").
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as np  # noqa: E402


def _resolve(op_name: str):
    import paddle1_tpu as paddle
    obj = paddle
    for part in op_name.split("."):
        obj = getattr(obj, part, None)
        if obj is None:
            break
    if obj is None or not callable(obj):
        # common fallbacks: paddle.<name>, nn.functional.<name>,
        # ops.math_ops.<name>
        for prefix in ("", "nn.functional.", "ops.math_ops.",
                       "ops.manip_ops.", "ops.linalg_ops."):
            obj = paddle
            ok = True
            for part in (prefix + op_name).split("."):
                if not part:
                    continue
                obj = getattr(obj, part, None)
                if obj is None:
                    ok = False
                    break
            if ok and callable(obj):
                return obj
        raise SystemExit(f"cannot resolve op {op_name!r}")
    return obj


def _parse_shape(s: str):
    return tuple(int(d) for d in s.lower().split("x"))


def run_case(case: dict) -> dict:
    import jax
    import jax.numpy as jnp
    from paddle1_tpu.core.tensor import to_tensor

    op = _resolve(case["op"])
    shapes = [_parse_shape(s) for s in case["shapes"]]
    dtype = case.get("dtype", "float32")
    repeat = int(case.get("repeat", 10))
    backward = bool(case.get("backward", False))
    rng = np.random.default_rng(int(case.get("seed", 0)))
    arrays = [rng.standard_normal(s).astype(dtype) for s in shapes]

    # eager timing (tape on, per-op dispatch — the dygraph number)
    tensors = [to_tensor(a) for a in arrays]
    op(*tensors)  # warmup
    t_eager = []
    for _ in range(repeat):
        t0 = time.perf_counter()
        out = op(*tensors)
        jax.block_until_ready(out.data if hasattr(out, "data") else
                              [o.data for o in out])
        t_eager.append(time.perf_counter() - t0)

    # jit timing (compiled — the deployment number)
    def f(*arrs):
        r = op(*[to_tensor(a) for a in arrs])
        return r.data if hasattr(r, "data") else [o.data for o in r]

    jf = jax.jit(f)
    jax.block_until_ready(jf(*arrays))  # compile
    t_jit = []
    for _ in range(repeat):
        t0 = time.perf_counter()
        jax.block_until_ready(jf(*arrays))
        t_jit.append(time.perf_counter() - t0)

    dev = jax.devices()[0]
    rec = {"op": case["op"], "shapes": case["shapes"], "dtype": dtype,
           "repeat": repeat,
           # the backend JAX gave: a CPU line is a CPU timing, never a
           # device metric
           "device": {"platform": dev.platform, "kind": dev.device_kind},
           "eager_us_median": round(statistics.median(t_eager) * 1e6, 2),
           "jit_us_median": round(statistics.median(t_jit) * 1e6, 2),
           "jit_us_min": round(min(t_jit) * 1e6, 2)}

    if backward:
        def loss(*arrs):
            r = op(*[to_tensor(a) for a in arrs])
            d = r.data if hasattr(r, "data") else r[0].data
            return (d.astype(jnp.float32) ** 2).sum()

        g = jax.jit(jax.grad(loss, argnums=tuple(range(len(arrays)))))
        jax.block_until_ready(g(*arrays))
        t_bwd = []
        for _ in range(repeat):
            t0 = time.perf_counter()
            jax.block_until_ready(g(*arrays))
            t_bwd.append(time.perf_counter() - t0)
        rec["fwd_bwd_us_median"] = round(
            statistics.median(t_bwd) * 1e6, 2)
    return rec


def main():
    ap = argparse.ArgumentParser(__doc__)
    ap.add_argument("--config", help="JSON file with a list of cases")
    ap.add_argument("--op", help="single-case op name")
    ap.add_argument("--shapes", help="comma-separated, e.g. 64x64,64x64")
    ap.add_argument("--dtype", default="float32")
    ap.add_argument("--repeat", type=int, default=10)
    ap.add_argument("--backward", action="store_true")
    args = ap.parse_args()

    if args.config:
        with open(args.config) as f:
            cases = json.load(f)
    elif args.op:
        cases = [{"op": args.op, "shapes": args.shapes.split(","),
                  "dtype": args.dtype, "repeat": args.repeat,
                  "backward": args.backward}]
    else:
        ap.error("need --config or --op")
    for case in cases:
        print(json.dumps(run_case(case)))


if __name__ == "__main__":
    main()
