"""Real-chip Mosaic smoke for every Pallas kernel — CPU interpret mode
does not enforce Mosaic's tiling rules (the r3 flash-attention LSE bug
only surfaced on hardware), so this script compiles and numerically
checks each kernel on the actual TPU. Run: python tools/tpu_kernel_smoke.py"""

import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


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

    # the flash BACKWARD kernel (one call: dq, dk and dv) against autodiff
    # of the dense reference
    from paddle1_tpu.ops.pallas import flash_attention as fa_mod
    from paddle1_tpu.ops.pallas.flash_attention_bwd import \
        flash_attention_bwd
    dout = jnp.asarray(rng.standard_normal(q.shape).astype(np.float32))
    scale = 1.0 / (q.shape[-1] ** 0.5)

    def bwd_pair(causal, mask):
        rule = fa_mod.rule_of(causal, None)
        out, lse = fa_mod._flash_fwd(q, k, v, scale, rule,
                                     padding_mask=mask)
        got = flash_attention_bwd(q, k, v, out, lse, dout, scale,
                                  rule, padding_mask=mask)
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

    # fused batch norm for given statistics (normalize +/- residual,
    # fp32 + bf16, local moments, and the one-pass backward kernel
    # against autodiff of the plain composition); CPU interpret mode
    # cannot enforce Mosaic's tiling or the accumulator grid
    from paddle1_tpu.ops.pallas import fused_bn as pbn
    rows, c = 2048, 128
    xb = jnp.asarray((rng.standard_normal((rows, c)) * 2 + 1)
                     .astype(np.float32))
    gb = jnp.asarray(rng.standard_normal((c,)).astype(np.float32))
    bb = jnp.asarray(rng.standard_normal((c,)).astype(np.float32))
    resb = jnp.asarray(rng.standard_normal((rows, c))
                       .astype(np.float32))
    bn_eps = 1e-5
    mstat = xb.mean(0)
    vstat = xb.var(0)

    def bn_ref(x, g=gb, b=bb, res=None, act="relu"):
        y = (x - mstat) / jnp.sqrt(vstat + bn_eps) * g + b
        if res is not None:
            y = y + res
        return jnp.maximum(y, 0.0) if act == "relu" else y

    def bn_kernel(x, g=gb, b=bb, res=None, act="relu"):
        return pbn.fused_bn_norm(x, mstat, vstat, g, b, bn_eps, act=act,
                                 residual=res)

    check("bn_eval", lambda: jax.jit(bn_kernel)(xb),
          lambda: bn_ref(xb), 5e-3)
    check("bn_eval_res",
          lambda: jax.jit(lambda x, r: bn_kernel(x, res=r))(xb, resb),
          lambda: bn_ref(xb, res=resb), 5e-3)
    check("bn_eval_bf16",
          lambda: jax.jit(lambda x: bn_kernel(x, act="identity"))(
              xb.astype(jnp.bfloat16)).astype(jnp.float32),
          lambda: bn_ref(xb.astype(jnp.bfloat16).astype(jnp.float32),
                         act="identity"), 5e-2)
    check("bn_local_moments",
          lambda: (lambda s, ss: s + ss)(*pbn.local_moments(xb)),
          lambda: xb.sum(0) + (xb * xb).sum(0), 5e-2)

    # the backward kernel: the cotangent is zero where y sits on the
    # ReLU's knife edge, so a last-bit difference in y cannot flip a
    # mask; its setup runs INSIDE the harness too — a Mosaic failure
    # here must print a named FAIL, not abort the script
    y_ref = bn_ref(xb, res=resb)
    dyb = jnp.asarray(rng.standard_normal((rows, c)).astype(np.float32))
    dyb = dyb * (jnp.abs(y_ref) > 1e-3)

    def bn_grads(fn):
        return jax.grad(lambda x, g, b, r: jnp.sum(fn(x, g, b, r) * dyb),
                        argnums=(0, 1, 2, 3))

    try:
        got_nb = jax.jit(bn_grads(bn_kernel))(xb, gb, bb, resb)
    except Exception as e:  # noqa: BLE001
        print(f"      bn_bwd.setup: EXCEPTION {type(e).__name__}: {e}")
        failures.append("bn_bwd.setup")
    else:
        want_nb = bn_grads(bn_ref)(xb, gb, bb, resb)
        for which, gg, ww in zip(("dx", "dgamma", "dbeta", "dres"),
                                 got_nb, want_nb):
            check(f"bn_eval_bwd.{which}", lambda gg=gg: gg,
                  lambda ww=ww: ww, 5e-2)

    if failures:
        print("FAILURES:", failures)
        return 1
    print("ALL PALLAS KERNELS OK ON CHIP")
    return 0


if __name__ == "__main__":
    sys.exit(main())
