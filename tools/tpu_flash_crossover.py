"""Measure the flash-vs-dense attention crossover on chip (r5): at
seq 128 XLA's dense attention beat the Pallas flash path by 1.5x at the
BERT-step level; find the sequence length where flash starts winning so
the dispatch can pick per-shape. Constant token count (b*s = 16384),
BERT-base head geometry, fwd+bwd via the public functional API.

``python tools/tpu_flash_crossover.py``
"""

import sys
import time

import numpy as np


def _min_time(f, k=6, trials=4):
    import jax
    np.asarray(jax.device_get(f()))
    best = None
    for _ in range(trials):
        t0 = time.perf_counter()
        r = None
        for _ in range(k):
            r = f()
        np.asarray(jax.device_get(r))
        dt = (time.perf_counter() - t0) / k
        best = dt if best is None else min(best, dt)
    return best


def main():
    import jax
    import jax.numpy as jnp
    from paddle1_tpu.core.flags import flags_guard
    from paddle1_tpu.nn.functional.attention import \
        scaled_dot_product_attention as sdpa
    from paddle1_tpu.core.tensor import Tensor

    heads, d = 12, 64
    print("device:", jax.devices()[0])
    for b, s in [(128, 128), (64, 256), (32, 512), (16, 1024),
                 (8, 2048), (4, 4096)]:
        rng = np.random.default_rng(0)
        q = jnp.asarray(rng.standard_normal((b, s, heads, d)),
                        jnp.bfloat16)
        # grad wrt q through the public functional path
        def make(mode):
            def loss(q):
                with flags_guard(flash_attention=mode,
                                 flash_backward=mode):
                    out = sdpa(Tensor(q), Tensor(q), Tensor(q),
                               is_causal=False)
                return jnp.sum(out.data.astype(jnp.float32))
            # scalar output only: downloading dq (25 MB) to the host
            # would swamp the op time
            g = jax.jit(lambda q: jnp.sum(
                jax.grad(loss)(q).astype(jnp.float32)))
            return lambda: g(q)
        # fwd = 2 matmuls (qk^T, av) = 4*b*h*s^2*d FLOPs; bwd ~ 2x fwd
        fl = 4 * b * heads * s * s * d * 3
        t_flash = _min_time(make("always"))
        t_dense = _min_time(make("never"))
        w = "flash" if t_flash < t_dense else "dense"
        print(f"b={b:4d} s={s:5d}: flash {t_flash * 1e3:8.2f} ms "
              f"({fl / t_flash / 1e12:5.1f} TF/s)  dense "
              f"{t_dense * 1e3:8.2f} ms ({fl / t_dense / 1e12:5.1f} "
              f"TF/s)  -> {w}")


if __name__ == "__main__":
    sys.exit(main())
