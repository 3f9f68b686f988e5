"""Record the small trace that tests/bench_yardstick keeps: 8 steps of a
small program with a Pallas kernel (the program's fused layer norm) in it,
the benchmark's three spans round them, a readback every 4 steps.

    chiprun --chips 1 -- python -m benchmarks.tools.record_trace
    -> chiprun_out/recorded/recorded_v5e.xplane.pb.gz
"""

import glob
import gzip
import os
import shutil
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from benchmarks import spec
from paddle1_tpu.ops.pallas import layer_norm


def main():
    assert jax.devices()[0].platform == "tpu"
    g, b = jnp.ones((512,), jnp.float32), jnp.zeros((512,), jnp.float32)

    @jax.jit
    def step(w, x):
        h = layer_norm.fused_layer_norm(jnp.tanh(x @ w), g, b)
        return w - 1e-3 * (x.T @ h).astype(w.dtype), \
            (h.astype(jnp.float32) ** 2).mean()

    w = jnp.ones((512, 512), jnp.bfloat16) * 0.01
    xs = [np.random.default_rng(i).standard_normal((2048, 512)).astype(
        np.float32) for i in range(4)]
    w, loss = step(w, jnp.asarray(xs[0], jnp.bfloat16))
    float(loss)
    out = os.path.join(spec.ROOT, "chiprun_out", "recorded")
    tdir = os.path.join(out, "trace")
    shutil.rmtree(out, ignore_errors=True)
    jax.profiler.start_trace(tdir)
    for i in range(8):
        with TraceAnnotation("bench/input"):
            x = jax.device_put(xs[i % 4].astype(jnp.bfloat16))
        with TraceAnnotation("bench/dispatch"):
            w, loss = step(w, x)
        if i % 4 == 3:
            with TraceAnnotation("bench/readback"):
                float(loss)
                time.sleep(0.003)
    jax.profiler.stop_trace()
    pb = glob.glob(os.path.join(tdir, "plugins/profile/*/*.xplane.pb"))[0]
    with open(pb, "rb") as src, gzip.open(
            os.path.join(out, "recorded_v5e.xplane.pb.gz"), "wb") as dst:
        dst.write(src.read())
    shutil.rmtree(tdir)
    print("recorded", os.path.getsize(
        os.path.join(out, "recorded_v5e.xplane.pb.gz")), "bytes")


if __name__ == "__main__":
    main()
