"""Fused Pallas kernel tests (flash padding mask, fused LayerNorm, fused
Adam) — run in interpreter mode on the CPU sim, exercising the same kernel
code the TPU executes. Mirrors the reference's fused-op unit tests
(test_fused_* over operators/fused/)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle1_tpu.core.flags import flags_guard


class TestFlashPaddingMask:
    def _qkv(self, b=2, n=128, h=2, d=32, seed=0):
        rng = np.random.default_rng(seed)
        mk = lambda: jnp.asarray(
            rng.standard_normal((b, n, h, d)).astype(np.float32) * 0.5)
        return mk(), mk(), mk()

    def test_masked_matches_ref(self):
        from paddle1_tpu.nn.functional.attention import attention_ref
        from paddle1_tpu.ops.pallas import flash_attention as fa
        q, k, v = self._qkv()
        b, n = q.shape[0], k.shape[1]
        rng = np.random.default_rng(1)
        keep = np.ones((b, n), np.float32)
        keep[:, n // 2:] = 0.0  # second half = padding
        out = fa.flash_attention(q, k, v, padding_mask=jnp.asarray(keep))
        add = jnp.where(jnp.asarray(keep)[:, None, None, :] > 0, 0.0,
                        -1e9).astype(jnp.float32)
        ref = attention_ref(q, k, v, mask=add)
        # only non-padded query rows are meaningful downstream
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-5)

    def test_masked_grads_finite_and_match(self):
        from paddle1_tpu.nn.functional.attention import attention_ref
        from paddle1_tpu.ops.pallas import flash_attention as fa
        q, k, v = self._qkv(b=1, n=128, h=1, d=16)
        keep = np.ones((1, 128), np.float32)
        keep[:, 100:] = 0.0
        keepj = jnp.asarray(keep)

        def loss_flash(q, k, v):
            return jnp.sum(fa.flash_attention(
                q, k, v, padding_mask=keepj) ** 2)

        def loss_ref(q, k, v):
            add = jnp.where(keepj[:, None, None, :] > 0, 0.0, -1e9)
            return jnp.sum(attention_ref(q, k, v, mask=add) ** 2)

        gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gf, gr):
            assert np.isfinite(np.asarray(a)).all()
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=5e-3, atol=5e-4)

    def test_fully_padded_row_zero_output_and_grads(self):
        """Review finding: an all-padding batch entry must produce zero
        output and exactly zero gradients, not exp(0)=1 garbage."""
        from paddle1_tpu.ops.pallas import flash_attention as fa
        q, k, v = self._qkv(b=2, n=128, h=1, d=16)
        keep = np.ones((2, 128), np.float32)
        keep[1, :] = 0.0  # batch entry 1 fully padded
        keepj = jnp.asarray(keep)

        out = fa.flash_attention(q, k, v, padding_mask=keepj)
        np.testing.assert_allclose(np.asarray(out)[1], 0.0)

        def loss(q, k, v):
            return jnp.sum(fa.flash_attention(
                q, k, v, padding_mask=keepj) ** 2)

        gq, gk, gv = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        for g in (gq, gk, gv):
            ga = np.asarray(g)
            assert np.isfinite(ga).all()
            np.testing.assert_allclose(ga[1], 0.0)

    def test_soft_bias_mask_falls_back_to_ref(self):
        """Review finding: a finite additive bias (not a padding mask) must
        NOT route to the flash kernel, which would drop it."""
        from paddle1_tpu.ops.pallas import flash_attention as fa
        from paddle1_tpu.nn import functional as F
        from paddle1_tpu.core.tensor import to_tensor
        q = np.random.default_rng(3).standard_normal(
            (2, 128, 2, 32)).astype(np.float32)
        bias = np.full((2, 1, 1, 128), -5.0, np.float32)  # soft penalty
        called = {}
        orig = fa.flash_attention

        def spy(*a, **kw):
            called["yes"] = True
            return orig(*a, **kw)

        fa.flash_attention = spy
        try:
            with flags_guard({"flash_attention": "always"}):
                out = F.scaled_dot_product_attention(
                    to_tensor(q), to_tensor(q), to_tensor(q),
                    attn_mask=to_tensor(bias), dropout_p=0.0)
        finally:
            fa.flash_attention = orig
        assert "yes" not in called, "soft bias was dropped by flash routing"
        # and the bias genuinely shifted nothing (uniform): output finite
        assert np.isfinite(np.asarray(out.data)).all()

    def test_bool_mask_routes_flash_under_trace(self):
        """BERT's bool keep-mask must stay flash-routable inside jit."""
        from paddle1_tpu.ops.pallas import flash_attention as fa
        from paddle1_tpu.nn import functional as F
        from paddle1_tpu.core.tensor import to_tensor
        q = np.random.default_rng(4).standard_normal(
            (2, 128, 2, 32)).astype(np.float32)
        keep = np.ones((2, 1, 1, 128), bool)
        keep[:, :, :, 100:] = False
        called = {}
        orig = fa.flash_attention

        def spy(*a, **kw):
            called["yes"] = True
            return orig(*a, **kw)

        fa.flash_attention = spy
        try:
            with flags_guard({"flash_attention": "always"}):
                def fwd(qa):
                    return F.scaled_dot_product_attention(
                        to_tensor(qa), to_tensor(qa), to_tensor(qa),
                        attn_mask=to_tensor(jnp.asarray(keep)),
                        dropout_p=0.0).data
                out = jax.jit(fwd)(jnp.asarray(q))
        finally:
            fa.flash_attention = orig
        assert called.get("yes"), "bool mask fell off the flash path in jit"
        assert np.isfinite(np.asarray(out)).all()

    def test_bert_routes_flash_for_bench_shapes(self):
        """The flagship-path regression: BERT's padding
        mask must not knock attention off the flash path."""
        from paddle1_tpu.ops.pallas import flash_attention as fa
        from paddle1_tpu.nn import functional as F
        from paddle1_tpu.core.tensor import to_tensor
        q = np.random.default_rng(0).standard_normal(
            (2, 128, 2, 32)).astype(np.float32)
        mask = np.zeros((2, 1, 1, 128), np.float32)  # additive, no padding
        mask[:, :, :, 120:] = -1e9
        called = {}
        orig = fa.flash_attention

        def spy(*a, **kw):
            called["yes"] = True
            return orig(*a, **kw)

        fa.flash_attention = spy
        try:
            with flags_guard({"flash_attention": "always"}):
                out = F.scaled_dot_product_attention(
                    to_tensor(q), to_tensor(q), to_tensor(q),
                    attn_mask=to_tensor(mask), dropout_p=0.0)
        finally:
            fa.flash_attention = orig
        assert called.get("yes"), (
            "padding-shaped mask did not route to the flash kernel")
        assert np.isfinite(np.asarray(out.data)).all()


class TestFusedLayerNorm:
    @pytest.mark.parametrize("shape", [(16, 128), (4, 32, 256)])
    def test_matches_plain(self, shape):
        from paddle1_tpu.ops.pallas import layer_norm as pln
        rng = np.random.default_rng(0)
        h = shape[-1]
        x = jnp.asarray(rng.standard_normal(shape).astype(np.float32) * 3 + 1)
        w = jnp.asarray(rng.standard_normal((h,)).astype(np.float32))
        b = jnp.asarray(rng.standard_normal((h,)).astype(np.float32))
        assert pln.supported(shape, 1)
        y = pln.fused_layer_norm(x, w, b, 1e-5)
        mean = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.var(x, axis=-1, keepdims=True)
        ref = (x - mean) * jax.lax.rsqrt(var + 1e-5) * w + b
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_grads_match_plain(self):
        from paddle1_tpu.ops.pallas import layer_norm as pln
        rng = np.random.default_rng(1)
        x = jnp.asarray(rng.standard_normal((8, 128)).astype(np.float32))
        w = jnp.asarray(rng.standard_normal((128,)).astype(np.float32))
        b = jnp.asarray(rng.standard_normal((128,)).astype(np.float32))

        def plain(x, w, b):
            mean = jnp.mean(x, axis=-1, keepdims=True)
            var = jnp.var(x, axis=-1, keepdims=True)
            return jnp.sum(((x - mean) * jax.lax.rsqrt(var + 1e-5) * w + b)
                           ** 2)

        def fused(x, w, b):
            return jnp.sum(pln.fused_layer_norm(x, w, b, 1e-5) ** 2)

        gp = jax.grad(plain, argnums=(0, 1, 2))(x, w, b)
        gf = jax.grad(fused, argnums=(0, 1, 2))(x, w, b)
        for a, bb in zip(gf, gp):
            np.testing.assert_allclose(np.asarray(a), np.asarray(bb),
                                       rtol=1e-4, atol=1e-4)

    def test_functional_routes_fused(self):
        from paddle1_tpu.ops.pallas import layer_norm as pln
        from paddle1_tpu.nn import functional as F
        from paddle1_tpu.core.tensor import to_tensor
        x = np.random.default_rng(2).standard_normal(
            (16, 128)).astype(np.float32)
        w = np.ones(128, np.float32)
        b = np.zeros(128, np.float32)
        called = {}
        orig = pln.fused_layer_norm

        def spy(*a, **kw):
            called["yes"] = True
            return orig(*a, **kw)

        pln.fused_layer_norm = spy
        try:
            with flags_guard({"fused_layer_norm": "always"}):
                y = F.layer_norm(to_tensor(x), 128, to_tensor(w),
                                 to_tensor(b))
        finally:
            pln.fused_layer_norm = orig
        assert called.get("yes")
        np.testing.assert_allclose(np.asarray(y.data).mean(), 0.0, atol=1e-5)


class TestFusedSoftmax:
    @pytest.mark.parametrize("shape", [(16, 128), (2, 8, 256)])
    def test_matches_jax(self, shape):
        from paddle1_tpu.ops.pallas import softmax as psm
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.standard_normal(shape).astype(np.float32) * 4)
        assert psm.supported(shape, -1)
        y = psm.fused_softmax(x)
        np.testing.assert_allclose(np.asarray(y),
                                   np.asarray(jax.nn.softmax(x, axis=-1)),
                                   rtol=1e-5, atol=1e-6)

    def test_grads_match(self):
        from paddle1_tpu.ops.pallas import softmax as psm
        rng = np.random.default_rng(1)
        x = jnp.asarray(rng.standard_normal((8, 128)).astype(np.float32))
        gf = jax.grad(lambda a: jnp.sum(psm.fused_softmax(a) ** 2))(x)
        gr = jax.grad(lambda a: jnp.sum(jax.nn.softmax(a, -1) ** 2))(x)
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gr),
                                   rtol=1e-4, atol=1e-5)

    def test_functional_routes(self):
        from paddle1_tpu.ops.pallas import softmax as psm
        from paddle1_tpu.nn import functional as F
        from paddle1_tpu.core.tensor import to_tensor
        x = np.random.default_rng(2).standard_normal(
            (16, 128)).astype(np.float32)
        called = {}
        orig = psm.fused_softmax

        def spy(*a, **kw):
            called["yes"] = True
            return orig(*a, **kw)

        psm.fused_softmax = spy
        try:
            with flags_guard({"fused_softmax": "always"}):
                y = F.softmax(to_tensor(x))
        finally:
            psm.fused_softmax = orig
        assert called.get("yes")
        np.testing.assert_allclose(np.asarray(y.data).sum(-1), 1.0,
                                   rtol=1e-5)

    def test_non_last_axis_falls_back(self):
        from paddle1_tpu.nn import functional as F
        from paddle1_tpu.core.tensor import to_tensor
        x = np.random.default_rng(3).standard_normal(
            (16, 128)).astype(np.float32)
        with flags_guard({"fused_softmax": "always"}):
            y = F.softmax(to_tensor(x), axis=0)   # not kernel-shaped
        np.testing.assert_allclose(np.asarray(y.data).sum(0), 1.0,
                                   rtol=1e-5)


class TestFusedAdam:
    def test_matches_plain_adamw(self):
        from paddle1_tpu.ops.pallas import fused_adam as fadam
        rng = np.random.default_rng(0)
        n = fadam._CHUNK + 123  # force padding path
        p = jnp.asarray(rng.standard_normal((n,)).astype(np.float32))
        g = jnp.asarray(rng.standard_normal((n,)).astype(np.float32))
        m1 = jnp.asarray(rng.standard_normal((n,)).astype(np.float32) * 0.01)
        m2 = jnp.abs(jnp.asarray(
            rng.standard_normal((n,)).astype(np.float32) * 0.01))
        beta1, beta2, eps, decay, lr = 0.9, 0.999, 1e-8, 0.01, 1e-3
        step = jnp.asarray(3, jnp.int32)

        np_, nm1, nm2 = fadam.fused_adam_update(
            p, g, m1, m2, lr, step, beta1, beta2, eps, decay)

        em1 = beta1 * m1 + (1 - beta1) * g
        em2 = beta2 * m2 + (1 - beta2) * g * g
        bc1 = 1 - beta1 ** 3
        bc2 = 1 - beta2 ** 3
        upd = (em1 / bc1) / (jnp.sqrt(em2 / bc2) + eps)
        ep = p * (1 - lr * decay) - lr * upd
        np.testing.assert_allclose(np.asarray(np_), np.asarray(ep),
                                   rtol=1e-6, atol=1e-7)
        np.testing.assert_allclose(np.asarray(nm1), np.asarray(em1),
                                   rtol=1e-6, atol=1e-7)
        np.testing.assert_allclose(np.asarray(nm2), np.asarray(em2),
                                   rtol=1e-6, atol=1e-7)

    def test_optimizer_fused_equals_unfused(self):
        """AdamW.functional_update with the flag on vs off is bit-close."""
        import paddle1_tpu as paddle
        from paddle1_tpu.ops.pallas import fused_adam as fadam
        from paddle1_tpu.nn.layer_common import Linear
        rng = np.random.default_rng(3)
        lin = Linear(128, 128)  # 16k params >= _CHUNK? ensure threshold
        n = int(np.prod(lin.weight.shape))
        params = {k: t.data for k, t in lin.state_dict().items()}
        grads = {k: jnp.asarray(
            rng.standard_normal(v.shape).astype(np.float32) * 0.01)
            for k, v in params.items()}

        def run(flag_val):
            opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                         parameters=lin.parameters())
            state = opt.functional_init(params)
            with flags_guard({"fused_adam": flag_val}):
                newp, _ = opt.functional_update(params, grads, state,
                                                jnp.float32(1e-3))
            return newp

        p_plain = run("never")
        p_fused = run("always")
        for k in params:
            np.testing.assert_allclose(np.asarray(p_fused[k]),
                                       np.asarray(p_plain[k]),
                                       rtol=1e-6, atol=1e-7)
        assert n >= fadam._CHUNK  # the weight actually took the fused path


class TestFlashBackwardKernels:
    """Pallas flash BACKWARD (ops/pallas/flash_attention_bwd.py) vs the
    XLA recompute backward and vs autodiff of the dense reference —
    interpret mode (flag default stays 'never' until the chip smoke)."""

    def _problem(self, causal=False, masked=False, nq=256, nk=256):
        rng = np.random.default_rng(0)
        B, H, D = 2, 4, 64
        q, k, v = (jnp.asarray(rng.standard_normal((B, n, H, D))
                               .astype(np.float32))
                   for n in (nq, nk, nk))
        pm = jnp.asarray((rng.random((B, nk)) > 0.25)
                         .astype(np.float32)) if masked else None
        dout = jnp.asarray(rng.standard_normal((B, nq, H, D))
                           .astype(np.float32))
        return q, k, v, pm, dout

    def _grads(self, q, k, v, pm, dout, causal):
        from paddle1_tpu.ops.pallas import flash_attention as fa
        from paddle1_tpu.ops.pallas.flash_attention_bwd import \
            flash_attention_bwd
        scale = 1.0 / (q.shape[-1] ** 0.5)
        out, lse = fa._flash_fwd(q, k, v, scale, causal,
                                 padding_mask=pm)
        got = flash_attention_bwd(q, k, v, out, lse, dout, scale,
                                  causal, padding_mask=pm)
        want = fa._bwd_xla(q, k, v, out, lse, dout, scale, causal,
                           padding_mask=pm)
        return got, want

    def _check(self, got, want):
        for g, w, name in zip(got, want, "q k v".split()):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       rtol=2e-3, atol=2e-3,
                                       err_msg=f"d{name}")

    def test_plain(self):
        q, k, v, pm, dout = self._problem()
        got, want = self._grads(q, k, v, None, dout, causal=False)
        self._check(got, want)

    def test_causal(self):
        q, k, v, pm, dout = self._problem(causal=True)
        got, want = self._grads(q, k, v, None, dout, causal=True)
        self._check(got, want)

    def test_padding_mask(self):
        q, k, v, pm, dout = self._problem(masked=True)
        got, want = self._grads(q, k, v, pm, dout, causal=False)
        self._check(got, want)

    def test_causal_rectangular(self):
        # nq < nk (bottom-right alignment)
        q, k, v, pm, dout = self._problem(causal=True, nq=128, nk=256)
        got, want = self._grads(q, k, v, None, dout, causal=True)
        self._check(got, want)

    def test_matches_dense_autodiff_end_to_end(self):
        from paddle1_tpu.core.flags import flags_guard
        from paddle1_tpu.nn.functional.attention import attention_ref
        from paddle1_tpu.ops.pallas.flash_attention import flash_attention
        q, k, v, pm, dout = self._problem(masked=True)

        with flags_guard(flash_backward="always"):
            dq_p = jax.grad(lambda q: jnp.sum(
                flash_attention(q, k, v, padding_mask=pm) * dout))(q)
        dq_ref = jax.grad(lambda q: jnp.sum(attention_ref(
            q, k, v, mask=(pm[:, None, None, :] > 0.5)) * dout))(q)
        np.testing.assert_allclose(np.asarray(dq_p), np.asarray(dq_ref),
                                   rtol=5e-3, atol=5e-3)

    def test_flag_default_is_auto(self):
        # auto: chip_smoke.py checks dq/dk/dv on the chip against the
        # XLA reference, tests/test_chip_compile.py compiles them
        from paddle1_tpu.core.flags import flag
        assert flag("flash_backward") == "auto"

    def test_fully_padded_row_zero_grads(self):
        # one batch entry entirely padded: all three grads must be EXACT
        # zeros for it (the sentinel-LSE remap; review r3 finding)
        q, k, v, pm, dout = self._problem(masked=True)
        pm = pm.at[1].set(0.0)
        got, want = self._grads(q, k, v, pm, dout, causal=False)
        for g, name in zip(got, "q k v".split()):
            np.testing.assert_array_equal(
                np.asarray(g)[1], 0.0,
                err_msg=f"d{name} row 1 must be exactly zero")
        self._check(got, want)

    def test_supported_bounds_full_sequence_residency(self):
        from paddle1_tpu.ops.pallas.flash_attention_bwd import supported
        assert supported((2, 256, 4, 64), (2, 256, 4, 64))
        # 65536 q rows x 128 head dim: full q+do residency > VMEM budget
        assert not supported((1, 65536, 1, 128), (1, 1024, 1, 128))


class TestFlashAutoDispatch:
    """r5: flash_attention=auto is memory-adaptive — XLA dense attention
    below flash_auto_score_mb, Pallas flash above (a sweep older than
    PRs 1-20, on another machine, found dense faster at every
    compute-bound length; not re-measured on the v5e)."""

    def _route(self, monkeypatch, b, s, h=4, d=64, threshold_mb=4,
               mode="auto"):
        import jax
        import numpy as np
        from paddle1_tpu.core import flags as core_flags
        from paddle1_tpu.core.tensor import Tensor
        from paddle1_tpu.nn.functional.attention import \
            scaled_dot_product_attention as sdpa
        from paddle1_tpu.ops.pallas import flash_attention as fa

        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        hit = {"flash": False}

        def spy(*a, **k):
            hit["flash"] = True
            raise RuntimeError("stop-at-dispatch")
        monkeypatch.setattr(fa, "flash_attention", spy)
        x = Tensor(np.zeros((b, s, h, d), np.float32))
        with core_flags.flags_guard(flash_attention=mode,
                                    flash_auto_score_mb=threshold_mb):
            try:
                sdpa(x, x, x)
            except RuntimeError as e:
                assert "stop-at-dispatch" in str(e)
        return hit["flash"]

    def test_small_seq_routes_dense(self, monkeypatch):
        # est = 2*4*128*128*(2*4+8)B = 2 MiB < 4 MiB -> dense
        assert self._route(monkeypatch, b=2, s=128) is False

    def test_large_seq_routes_flash(self, monkeypatch):
        # est = 2*4*1024*1024*(2*4+8)B = 128 MiB >= 4 MiB -> flash
        assert self._route(monkeypatch, b=2, s=1024) is True

    def test_always_ignores_threshold(self, monkeypatch):
        assert self._route(monkeypatch, b=2, s=128, threshold_mb=10**6,
                           mode="always") is True

    def test_bad_threshold_rejected(self):
        import pytest
        from paddle1_tpu.core import flags as core_flags
        from paddle1_tpu.core.errors import InvalidArgumentError
        for bad in (0, -5):
            with pytest.raises(InvalidArgumentError):
                core_flags.set_flags({"flash_auto_score_mb": bad})
        # fractional thresholds are legal (float flag, not int)
        with core_flags.flags_guard(flash_auto_score_mb=0.5):
            assert core_flags.flag("flash_auto_score_mb") == 0.5


class TestChunkedXlaBackward:
    """r5: _bwd_xla scans over query chunks for long sequences (the
    memory-escape backward when the Pallas kernels' VMEM model rejects
    the shape). Chunked must equal dense exactly."""

    def _problem(self, b=2, nq=256, nk=256, h=2, d=32, masked=False):
        rng = np.random.default_rng(0)
        mk = lambda *s: jnp.asarray(
            rng.standard_normal(s).astype(np.float32) * 0.3)
        q, k, v = mk(b, nq, h, d), mk(b, nk, h, d), mk(b, nk, h, d)
        dout = mk(b, nq, h, d)
        pm = None
        if masked:
            keep = np.ones((b, nk), np.float32)
            keep[:, nk - 40:] = 0.0
            pm = jnp.asarray(keep)
        return q, k, v, pm, dout

    @pytest.mark.parametrize("causal,masked,nq,nk", [
        (False, False, 256, 256),
        (True, False, 256, 256),
        (True, False, 128, 256),     # rectangular bottom-right causal
        (False, True, 256, 256),
    ])
    def test_chunked_equals_dense(self, causal, masked, nq, nk):
        from paddle1_tpu.ops.pallas import flash_attention as fa
        q, k, v, pm, dout = self._problem(nq=nq, nk=nk, masked=masked)
        scale = 1.0 / (q.shape[-1] ** 0.5)
        out, lse = fa._flash_fwd(q, k, v, scale, causal,
                                 padding_mask=pm)
        dense = fa._bwd_xla(q, k, v, out, lse, dout, scale, causal,
                            padding_mask=pm, q_chunk=nq)
        chunked = fa._bwd_xla(q, k, v, out, lse, dout, scale, causal,
                              padding_mask=pm, q_chunk=64)
        for g1, g2, name in zip(dense, chunked, "dq dk dv".split()):
            np.testing.assert_allclose(
                np.asarray(g1), np.asarray(g2), rtol=1e-5, atol=1e-5,
                err_msg=f"{name} causal={causal} masked={masked}")

    def test_vmem_model_rejects_long_seq(self):
        from paddle1_tpu.ops.pallas.flash_attention_bwd import supported
        assert supported((1, 4096, 12, 64), (1, 4096, 12, 64))
        # 32 * 16384 * 64 = 32 MiB > the 14 MiB budget (measured OOM
        # at 32.25 MiB scoped vmem on chip)
        assert not supported((1, 16384, 12, 64), (1, 16384, 12, 64))
        assert not supported((1, 8192, 12, 64), (1, 8192, 12, 64))
