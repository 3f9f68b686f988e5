"""Scratch: compile each cell's step program at its real size for a
described TPU v5e, here, without the chip, and print what the compiler
says it needs. An out-of-memory batch then costs no chip call.

    JAX_PLATFORMS=cpu python -m benchmarks.tools.aot_compile [cell ...]

A compile that passes is not a chip run: nothing here is a measurement.
"""

import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from benchmarks import spec, traffic  # noqa: E402


def main(cells):
    from paddle1_tpu.ops.pallas import _common
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    # `auto` kernel flags ask the default backend, which is the CPU here
    jax.default_backend = lambda: "tpu"
    _common.interpret = lambda: False
    jax.config.update("jax_enable_compilation_cache", False)

    def struct(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one)

    for name in cells:
        cell = spec.load_json("workloads", name + ".json")
        cfg = spec.config(cell["config"])
        env = traffic.environment(cfg, cell)
        program = spec.module("program", cfg)
        reference = spec.module("reference", cfg)
        leaves = program.leaves(cfg)
        w = jax.jit(lambda k: reference.init_params(cfg, k))(jax.random.key(0))
        placed = {p: (w[r] if i is None else w[r][i]) for p, r, i in leaves}
        built = program.build(cfg, env, placed, jax.devices()[:1])
        engine = built["engine"]
        batch = traffic.batches(cell, env, 0, 1)[0]
        args = jax.tree_util.tree_map(
            struct, (engine.params, engine.opt_state,
                     {k: jnp.asarray(v) for k, v in batch.items()},
                     jax.random.key(0), jnp.float32(0)))
        t = time.perf_counter()
        compiled = jax.jit(engine._step_fn, donate_argnums=(0, 1)).lower(
            *args).compile()
        m = compiled.memory_analysis()
        live = (m.argument_size_in_bytes + m.temp_size_in_bytes
                + m.output_size_in_bytes - m.alias_size_in_bytes)
        text = compiled.as_text()
        print(f"{name}: compiled for a described v5e in "
              f"{time.perf_counter() - t:.1f}s; arguments "
              f"{m.argument_size_in_bytes / 2**30:.2f} GiB, temporaries "
              f"{m.temp_size_in_bytes / 2**30:.2f} GiB, program "
              f"{m.generated_code_size_in_bytes / 2**30:.2f} GiB, live "
              f"upper bound {live / 2**30:.2f} GiB of 15.75; "
              f"{text.count('tpu_custom_call')} tpu_custom_call sites",
              flush=True)
        del built, engine, compiled


if __name__ == "__main__":
    main(sys.argv[1:] or spec.names_in("workloads"))
