"""Real-chip Mosaic smoke for every Pallas kernel — CPU interpret mode
does not enforce Mosaic's tiling rules (the r3 flash-attention LSE bug
only surfaced on hardware), so this script compiles and numerically
checks each kernel on the actual TPU. Run: python tools/tpu_kernel_smoke.py"""

import sys

import numpy as np


def main():
    import jax
    import jax.numpy as jnp
    dev = jax.devices()[0]
    assert dev.platform == "tpu", f"need a TPU, got {dev.platform}"
    print("device:", getattr(dev, "device_kind", dev))
    rng = np.random.default_rng(0)
    failures = []

    def check(name, fn, ref, atol):
        try:
            got = np.asarray(jax.device_get(fn()))
            want = np.asarray(ref())
            err = float(np.max(np.abs(got - want)))
            ok = err <= atol
            print(f"{name:>18}: max_err={err:.2e} "
                  f"{'OK' if ok else f'FAIL (atol {atol})'}")
            if not ok:
                failures.append(name)
        except Exception as e:  # noqa: BLE001
            print(f"{name:>18}: EXCEPTION {type(e).__name__}: {e}")
            failures.append(name)

    # flash attention (mask + causal + grads)
    from paddle1_tpu.nn.functional.attention import attention_ref
    from paddle1_tpu.ops.pallas.flash_attention import flash_attention
    q, k, v = (jnp.asarray(rng.standard_normal((2, 256, 4, 64))
                           .astype(np.float32)) for _ in range(3))
    pm = jnp.asarray((rng.random((2, 256)) > 0.2).astype(np.float32))
    check("flash", lambda: jax.jit(flash_attention)(q, k, v),
          lambda: attention_ref(q, k, v), 5e-2)
    check("flash_causal",
          lambda: jax.jit(lambda q, k, v: flash_attention(
              q, k, v, causal=True))(q, k, v),
          lambda: attention_ref(q, k, v, is_causal=True), 5e-2)
    check("flash_masked",
          lambda: jax.jit(lambda q, k, v, pm: flash_attention(
              q, k, v, padding_mask=pm))(q, k, v, pm),
          lambda: attention_ref(q, k, v,
                                mask=(pm[:, None, None, :] > 0.5)), 5e-2)
    check("flash_grad",
          lambda: jax.jit(jax.grad(lambda q: flash_attention(
              q, k, v, padding_mask=pm).astype(jnp.float32).sum()))(q),
          lambda: jax.grad(lambda q: attention_ref(
              q, k, v, mask=(pm[:, None, None, :] > 0.5))
              .astype(jnp.float32).sum())(q), 8e-2)

    # flash BACKWARD kernels against autodiff of the dense reference
    from paddle1_tpu.ops.pallas import flash_attention as fa_mod
    from paddle1_tpu.ops.pallas.flash_attention_bwd import \
        flash_attention_bwd
    dout = jnp.asarray(rng.standard_normal(q.shape).astype(np.float32))
    scale = 1.0 / (q.shape[-1] ** 0.5)

    def bwd_pair(causal, mask):
        out, lse = fa_mod._flash_fwd(q, k, v, scale, causal,
                                     padding_mask=mask)
        got = flash_attention_bwd(q, k, v, out, lse, dout, scale,
                                  causal, padding_mask=mask)
        m4 = None if mask is None else mask[:, None, None, :] > 0.5
        want = jax.vjp(lambda q, k, v: attention_ref(
            q, k, v, mask=m4, is_causal=causal), q, k, v)[1](dout)
        return got, want
    for nm, ca, mk in (("flash_bwd", False, None),
                       ("flash_bwd_causal", True, None),
                       ("flash_bwd_masked", False, pm)):
        got, want = bwd_pair(ca, mk)  # compute ONCE per config
        for which, g, w in zip(("dq", "dk", "dv"), got, want):
            check(f"{nm}.{which}", lambda g=g: g, lambda w=w: w, 8e-2)

    # fused layer norm
    from paddle1_tpu.ops.pallas.layer_norm import fused_layer_norm
    x = jnp.asarray(rng.standard_normal((512, 768)).astype(np.float32))
    w = jnp.asarray(rng.standard_normal((768,)).astype(np.float32))
    b = jnp.asarray(rng.standard_normal((768,)).astype(np.float32))

    def ln_ref():
        mu = x.mean(axis=-1, keepdims=True)
        var = ((x - mu) ** 2).mean(axis=-1, keepdims=True)
        return (x - mu) / jnp.sqrt(var + 1e-5) * w + b
    check("layer_norm",
          lambda: jax.jit(fused_layer_norm)(x, w, b), ln_ref, 5e-3)

    # fused softmax
    from paddle1_tpu.ops.pallas.softmax import fused_softmax
    s = jnp.asarray(rng.standard_normal((384, 512)).astype(np.float32))
    check("softmax", lambda: jax.jit(fused_softmax)(s),
          lambda: jax.nn.softmax(s, axis=-1), 5e-4)

    # fused batch norm (train + eval, fp32 + bf16, +/- residual,
    # forward AND the one-pass backward kernels vs the XLA
    # compositions — the ISSUE 15 family; CPU interpret mode cannot
    # enforce Mosaic's tiling or the two-phase accumulator grid)
    from paddle1_tpu.core.flags import flags_guard
    from paddle1_tpu.ops.pallas import fused_bn as pbn
    from paddle1_tpu.ops.pallas import fused_bn_bwd as pbnb
    rows, c = 2048, 128
    xb = jnp.asarray((rng.standard_normal((rows, c)) * 2 + 1)
                     .astype(np.float32))
    gb = jnp.asarray(rng.standard_normal((c,)).astype(np.float32))
    bb = jnp.asarray(rng.standard_normal((c,)).astype(np.float32))
    resb = jnp.asarray(rng.standard_normal((rows, c))
                       .astype(np.float32))
    dyb = jnp.asarray(rng.standard_normal((rows, c)).astype(np.float32))
    bn_eps = 1e-5

    def bn_ref(x, res=None, act="relu"):
        m = x.mean(0)
        v = x.var(0)
        y = (x - m) / jnp.sqrt(v + bn_eps) * gb + bb
        if res is not None:
            y = y + res
        return jnp.maximum(y, 0.0) if act == "relu" else y

    check("bn_train",
          lambda: jax.jit(lambda x: pbn.fused_bn_train(
              x, gb, bb, bn_eps, act="relu")[0])(xb),
          lambda: bn_ref(xb), 5e-3)
    check("bn_train_res",
          lambda: jax.jit(lambda x, r: pbn.fused_bn_train(
              x, gb, bb, bn_eps, act="relu", residual=r)[0])(xb, resb),
          lambda: bn_ref(xb, resb), 5e-3)
    check("bn_train_bf16",
          lambda: jax.jit(lambda x: pbn.fused_bn_train(
              x, gb, bb, bn_eps)[0])(
              xb.astype(jnp.bfloat16)).astype(jnp.float32),
          lambda: bn_ref(xb.astype(jnp.bfloat16).astype(jnp.float32),
                         act="identity"), 5e-2)
    mstat = xb.mean(0)
    vstat = xb.var(0)
    check("bn_eval",
          lambda: jax.jit(lambda x: pbn.fused_bn_norm(
              x, mstat, vstat, gb, bb, bn_eps, act="relu"))(xb),
          lambda: bn_ref(xb), 5e-3)
    check("bn_local_moments",
          lambda: (lambda s, ss: s + ss)(*pbn.local_moments(xb)),
          lambda: xb.sum(0) + (xb * xb).sum(0), 5e-2)

    # backward kernels: the shared forward/setup runs INSIDE the
    # harness too — a Mosaic failure here must print a named FAIL and
    # let the remaining kernel families run, not abort the script
    try:
        y_act = pbn.fused_bn_train(xb, gb, bb, bn_eps, act="relu")[0]
        with flags_guard(fused_bn_bwd="always"):
            got_tb = jax.jit(lambda *a: pbnb.train_bwd(
                *a, bn_eps, "relu", with_res=True))(
                xb, gb, mstat, vstat, y_act, dyb)
            got_nb = jax.jit(lambda *a: pbnb.norm_bwd(
                *a, bn_eps, "relu"))(xb, gb, mstat, vstat, y_act, dyb)
    except Exception as e:  # noqa: BLE001
        print(f"      bn_bwd.setup: EXCEPTION {type(e).__name__}: {e}")
        failures.append("bn_bwd.setup")
    else:
        want_tb = pbnb.train_bwd_xla(xb, gb, mstat, vstat, y_act, dyb,
                                     bn_eps, "relu", with_res=True)
        for which, gg, ww in zip(("dx", "dgamma", "dbeta", "dres"),
                                 got_tb, want_tb):
            check(f"bn_bwd.{which}", lambda gg=gg: gg,
                  lambda ww=ww: ww, 5e-2)
        want_nb = pbnb.norm_bwd_xla(xb, gb, mstat, vstat, y_act, dyb,
                                    bn_eps, "relu")
        for which, gg, ww in zip(("dx", "dgamma", "dbeta"), got_nb,
                                 want_nb):
            check(f"bn_eval_bwd.{which}", lambda gg=gg: gg,
                  lambda ww=ww: ww, 5e-2)

    # fused adam
    from paddle1_tpu.ops.pallas.fused_adam import fused_adam_update
    n = 8192 * 2
    p = jnp.asarray(rng.standard_normal(n).astype(np.float32))
    g = jnp.asarray(rng.standard_normal(n).astype(np.float32))
    m1 = jnp.zeros(n, jnp.float32)
    m2 = jnp.zeros(n, jnp.float32)

    def adam_fused():
        return jax.jit(lambda p, g, m1, m2: fused_adam_update(
            p, g, m1, m2, 1e-3, 1, 0.9, 0.999, 1e-8, 0.01))(p, g, m1,
                                                            m2)[0]

    def adam_ref():
        nm1 = 0.1 * g
        nm2 = 0.001 * g * g
        upd = (nm1 / (1 - 0.9)) / (jnp.sqrt(nm2 / (1 - 0.999)) + 1e-8)
        return p * (1 - 1e-3 * 0.01) - 1e-3 * upd
    check("fused_adam", adam_fused, adam_ref, 1e-5)

    if failures:
        print("FAILURES:", failures)
        return 1
    print("ALL PALLAS KERNELS OK ON CHIP")
    return 0


if __name__ == "__main__":
    sys.exit(main())
