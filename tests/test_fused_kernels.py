"""Fused Pallas kernel tests (flash padding mask, fused LayerNorm, fused
Adam) — run in interpreter mode on the CPU sim, exercising the same kernel
code the TPU executes. Mirrors the reference's fused-op unit tests
(test_fused_* over operators/fused/)."""

import contextlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle1_tpu.core.flags import flags_guard
from paddle1_tpu.nn.functional.attention import FLASH_MIN_SEQ as N


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


@pytest.mark.parametrize("name", ["Adam", "AdamW"])
def test_adam_is_the_plain_update_chain(name, monkeypatch):
    """The Adam rule has no kernel: on a backend that says "tpu" the
    update traces to no ``pallas_call`` (XLA fuses the chain behind the
    weight-gradient matmuls: PERF.md, PR 29) and is the plain AdamW
    rule, moments and padding-sized tensors included."""
    import paddle1_tpu as paddle
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    rng = np.random.default_rng(0)
    n = 16384 + 123
    mk = lambda scale=1.0: jnp.asarray(
        rng.standard_normal((n,)).astype(np.float32) * scale)
    p, g, m1, m2 = mk(), mk(), mk(0.01), jnp.abs(mk(0.01))
    beta1, beta2, eps, lr = 0.9, 0.999, 1e-8, 1e-3
    decay = 0.01 if name == "AdamW" else 0.0
    opt = getattr(paddle.optimizer, name)(learning_rate=lr)
    state = ({"w": {"moment1": m1, "moment2": m2}},
             jnp.asarray(2, jnp.int32))

    def update(p, g):
        return opt.functional_update({"w": p}, {"w": g}, state,
                                     jnp.float32(lr))

    assert "pallas_call" not in _primitives(
        jax.make_jaxpr(update)(p, g).jaxpr)
    new_p, (slots, step) = update(p, g)
    em1 = beta1 * m1 + (1 - beta1) * g
    em2 = beta2 * m2 + (1 - beta2) * g * g
    upd = (em1 / (1 - beta1 ** 3)) / (jnp.sqrt(em2 / (1 - beta2 ** 3))
                                      + eps)
    assert int(step) == 3
    for got, want in [(new_p["w"], p * (1 - lr * decay) - lr * upd),
                      (slots["w"]["moment1"], em1),
                      (slots["w"]["moment2"], em2)]:
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-6, atol=1e-7)


def _attention_problem(nq, nk, d, dtype, mask, b=2, h=2, seed=0, h_kv=None,
                       dv=None):
    """q and dout with ``h`` heads, k and v with ``h_kv`` (``h`` when
    None); q and k ``d`` wide, v and dout ``dv`` (``d`` when None)."""
    rng = np.random.default_rng(seed)
    mk = lambda n, heads, width: jnp.asarray(
        rng.standard_normal((b, n, heads, width)), dtype)
    h_kv, dv = h_kv or h, dv or d
    q, k, v, dout = (mk(nq, h, d), mk(nk, h_kv, d), mk(nk, h_kv, dv),
                     mk(nq, h, dv))
    keep = None
    if mask:
        keep = np.ones((b, nk), bool)
        keep[0, nk - 37:] = False            # a ragged tail in one block
        if mask == "padded_row":
            keep[1, :] = False               # batch entry 1 sees nothing
    return q, k, v, dout, keep


def _flash_and_ref(q, k, v, dout, keep, causal, blocks, rule=None):
    """(out, dq, dk, dv) of the kernels, and of attention_ref followed
    in float32 (a fully padded batch entry's rows zeroed: the kernels
    give zeros there, a softmax over nothing gives a mean). The
    reference sees a key/value head once a query head of its group, so
    autodiff sums dK and dV over the group; a ``rule`` of ``mask_rules``
    as its dense mask."""
    from paddle1_tpu.ops.pallas import mask_rules
    from paddle1_tpu.nn.functional.attention import attention_ref
    from paddle1_tpu.ops.pallas import flash_attention as fa
    f32 = lambda x: x.astype(jnp.float32)
    pm = None if keep is None else jnp.asarray(keep)
    dead = (np.zeros(q.shape[0], bool) if keep is None
            else ~keep.any(axis=1))
    alive = jnp.asarray(~dead, jnp.float32)[:, None, None, None]

    group = q.shape[2] // k.shape[2]

    def kernel(q, k, v):
        return fa.flash_attention(q, k, v, causal=causal, padding_mask=pm,
                                  blocks=blocks, mask=rule)

    def plain(q, k, v):
        m4 = None if pm is None else pm[:, None, None, :]
        if rule is not None:
            seen = jnp.asarray(mask_rules.dense_mask(
                rule, q.shape[1], k.shape[1]))[None, None]
            m4 = seen if m4 is None else m4 & seen
        k, v = (jnp.repeat(x, group, axis=2) for x in (k, v))
        return attention_ref(q, k, v, mask=m4, is_causal=causal) * alive

    # each side one compiled program, as a step runs the kernels: op by
    # op the two ``jax.vjp``s are 14 programs a case
    def out_and_grads(attention):
        def run(q, k, v, dout):
            out, vjp = jax.vjp(attention, q, k, v)
            return (out,) + vjp(dout)
        return jax.jit(run)
    return (out_and_grads(kernel)(q, k, v, dout),
            out_and_grads(plain)(f32(q), f32(k), f32(v), f32(dout)), dead)


# (causal, nq, nk, d, dtype, mask, blocks): every pairing the kernels
# branch on. blocks = (resident, fetched, chunk) of all three kernels.
_B128 = (128, 128, 128)
FLASH_CASES = [
    (causal, nq, nk, d, dtype, mask, _B128)
    for causal in (False, True)
    for nq, nk in ((256, 256), (128, 384))    # nq < nk: bottom-right
    for d in (64, 128)                        # transposed / packed layout
    for dtype, mask in (("float32", None), ("float32", "padding"),
                        ("bfloat16", "padding" if d == 64 else None))
] + [
    (False, 256, 256, 64, "float32", "padded_row", _B128),
    (True, 256, 256, 128, "float32", "padded_row", _B128),
    (True, 128, 384, 64, "bfloat16", "padded_row", _B128),
    # some key blocks wholly above the diagonal: not in the table of
    # pairs; fetched block of two chunks: the chunk-level skip too
    (True, 512, 512, 128, "float32", None, (128, 256, 128)),
    (True, 512, 512, 64, "float32", "padding", (128, 256, 128)),
    (True, 512, 512, 128, "bfloat16", None, (256, 128, 128)),
    (True, 256, 512, 64, "float32", None, (128, 256, 128)),
    (True, 384, 640, 128, "float32", "padding", (128, 128, 128)),
    # the sizes the code picks by itself
    (True, 512, 512, 128, "bfloat16", None, None),
    (False, 256, 384, 64, "float32", "padding", None),
]


# (mask rule, nq, nk, heads, key/value heads, d, dv, dtype, padding,
# blocks): what the one backward kernel branches on that FLASH_CASES does
# not reach. blocks = (forward triple, backward (block_q, block_k, chunk,
# keys resident at a time)): fewer resident keys than there are keys take
# the kernel a key range at a time, dQ summed over the ranges.
_BD = ("block_diffusion", 4)
_RANGES = (_B128, (128, 128, 128, 128))
BACKWARD_CASES = [
    # a group of query heads passes each resident dK / dV
    ("causal", 256, 256, 8, 2, 64, 64, "float32", None, _B128),
    ("none", 256, 256, 8, 2, 128, 128, "float32", "padding", _B128),
    (_BD, 256, 256, 8, 2, 64, 64, "float32", None, _B128),
    ("causal", 128, 128, 32, 4, 128, 128, "float32", None, _B128),
    (_BD, 256, 256, 32, 4, 128, 128, "bfloat16", None, _B128),
    # keys 192 wide, values 128: one layout each
    ("causal", 256, 256, 2, 2, 192, 128, "float32", None, _B128),
    ("none", 128, 384, 2, 2, 192, 128, "float32", "padding", _B128),
    ("causal", 256, 256, 4, 2, 192, 128, "bfloat16", None, _B128),
    (_BD, 256, 256, 4, 1, 192, 128, "float32", None, _B128),
    # block diffusion at h == h_kv, fetched blocks of two chunks
    (_BD, 512, 512, 2, 2, 128, 128, "float32", None, (128, 256, 128)),
    (("block_diffusion", 32), 256, 256, 2, 2, 64, 64, "float32", None,
     _B128),
    # more than one key range
    ("causal", 256, 256, 2, 2, 64, 64, "float32", None, _RANGES),
    ("causal", 384, 640, 2, 2, 128, 128, "float32", "padding", _RANGES),
    ("none", 256, 256, 2, 2, 128, 128, "float32", None, _RANGES),
    ("none", 256, 512, 4, 2, 64, 64, "float32", "padded_row",
     (_B128, (128, 256, 128, 256))),
    (_BD, 256, 256, 8, 2, 128, 128, "float32", None, _RANGES),
    (_BD, 512, 512, 2, 1, 192, 128, "bfloat16", None,
     (_B128, (128, 256, 128, 512))),
    ("causal", 512, 512, 2, 2, 128, 128, "bfloat16", "padding",
     (_B128, (256, 256, 128, 256))),
    # a crossed tile by sub-tile (ISSUE 48): tiles of 512 rows or keys in
    # sub-tiles of 256, crossed by the diagonal (a group of 7, keys and
    # values of two widths), by a window's far edge, by both at once (a
    # window shorter than a sub-tile, beside a padding mask), by block
    # diffusion's quadrants, with fewer queries than keys, and at the
    # sizes the code picks (512 x 512) in bfloat16
    ("causal", 512, 512, 7, 1, 64, 128, "float32", None, (512, 512, 512)),
    (("window", 256), 1024, 1024, 2, 2, 128, 128, "float32", None,
     (512, 512, 512)),
    (("window", 100), 512, 512, 7, 1, 64, 64, "float32", "padding",
     (512, 512, 256)),
    (_BD, 1024, 1024, 1, 1, 128, 128, "float32", None, (512, 512, 512)),
    (("window", 300), 512, 1024, 2, 1, 192, 128, "float32", "padding",
     (512, 512, 512)),
    (("window", 512), 1024, 1024, 1, 1, 128, 128, "bfloat16", None, None),
]


# (mask rule, nq, nk, heads, key/value heads, d, dv, dtype, padding): the
# row statistic the forward kernel hands on (ISSUE 49: its LSE leaves as
# the lane-dense row the backward reads, summed a lane and reduced once a
# query block), at head widths 64 / 128 / 192-with-128 values, query
# heads in groups of 1 / 4 / 8, under every rule and under none, at the
# sizes the code picks; a fully padded batch entry carries the sentinel
LSE_CASES = [
    ("causal", 512, 512, 4, 4, 64, 64, "float32", None),
    ("causal", 512, 512, 8, 2, 128, 128, "float32", None),
    ("causal", 256, 256, 8, 1, 192, 128, "bfloat16", None),
    ("none", 256, 384, 8, 1, 192, 128, "float32", "padding"),
    ("none", 256, 256, 4, 1, 128, 128, "float32", None),
    (("window", 512), 1024, 1024, 8, 1, 128, 128, "bfloat16", None),
    (("window", 512), 1024, 1024, 4, 1, 64, 64, "float32", None),
    (_BD, 512, 512, 4, 1, 128, 128, "float32", None),
    (_BD, 256, 256, 2, 2, 192, 128, "float32", None),
    ("none", 256, 256, 2, 2, 64, 64, "float32", "padded_row"),
    ("causal", 256, 256, 8, 1, 128, 128, "float32", "padded_row"),
]


def _rule_of_case(rule, nq):
    """A case's ``"causal"``, ``"none"``, ``("window", keys)`` or
    ``("block_diffusion", block)`` as its rule of ``mask_rules``."""
    from paddle1_tpu.ops.pallas import mask_rules
    if isinstance(rule, tuple) and rule[0] == "window":
        return mask_rules.SlidingWindow(rule[1])
    if isinstance(rule, tuple):
        return mask_rules.BlockDiffusion(nq // 2, rule[1])
    return {"causal": mask_rules.CAUSAL, "none": mask_rules.NO_MASK}[rule]


class TestFlashKernels:
    """The blockwise forward kernel and the one backward kernel
    (ops/pallas/flash_attention.py, flash_attention_bwd.py) against
    autodiff of the dense reference, in interpret mode."""

    @pytest.mark.parametrize(
        "causal,nq,nk,d,dtype,mask,blocks", FLASH_CASES,
        ids=lambda c: "x".join(map(str, c)) if isinstance(c, tuple)
        else str(c))
    def test_forward_and_gradients_match_the_reference(
            self, causal, nq, nk, d, dtype, mask, blocks):
        q, k, v, dout, keep = _attention_problem(nq, nk, d,
                                                 jnp.dtype(dtype), mask)
        self._held_to_the_reference(q, *_flash_and_ref(
            q, k, v, dout, keep, causal, blocks), dtype)

    @staticmethod
    def _held_to_the_reference(q, got, want, dead, dtype, group=1):
        # bf16: the probabilities and ds are rounded once (2^-9 of
        # values up to 1 and up to |dout| |v| sqrt(d)); dK and dV sum a
        # group's heads, each rounded so
        tol = 2e-5 if dtype == "float32" else 6e-2
        for g, w, name in zip(got, want, ("out", "dq", "dk", "dv")):
            assert g.dtype == q.dtype and g.shape == w.shape, name
            g = np.asarray(g.astype(jnp.float32))
            assert np.isfinite(g).all(), name
            loose = tol * (group ** 0.5 if name in ("dk", "dv") else 1)
            np.testing.assert_allclose(g, np.asarray(w), rtol=loose,
                                       atol=loose, err_msg=name)
            # a batch entry that sees nothing: exact zeros, not the
            # exp(0) = 1 of a sentinel maximum
            np.testing.assert_array_equal(g[dead], 0.0, err_msg=name)

    @pytest.mark.parametrize(
        "rule,nq,nk,h,h_kv,d,dv,dtype,mask,blocks", BACKWARD_CASES,
        ids=lambda c: "x".join(map(str, c)) if isinstance(c, tuple)
        else str(c))
    def test_the_one_backward_kernel_gives_all_three_gradients(
            self, rule, nq, nk, h, h_kv, d, dv, dtype, mask, blocks):
        """dQ, dK and dV of one ``pallas_call``: grouped heads, the two
        widths of latent attention, block diffusion's rule, and the keys
        a range at a time, within the limits the split kernels had."""
        q, k, v, dout, keep = _attention_problem(
            nq, nk, d, jnp.dtype(dtype), mask, h=h, h_kv=h_kv, dv=dv)
        self._held_to_the_reference(q, *_flash_and_ref(
            q, k, v, dout, keep, False, blocks,
            rule=_rule_of_case(rule, nq)), dtype, group=h // h_kv)

    @pytest.mark.parametrize(
        "rule,nq,nk,h,h_kv,d,dv,dtype,mask", LSE_CASES,
        ids=lambda c: "x".join(map(str, c)) if isinstance(c, tuple)
        else str(c))
    def test_the_forward_kernels_lse_is_the_references(
            self, rule, nq, nk, h, h_kv, d, dv, dtype, mask):
        """``_flash_fwd``'s second value is ``[B*H, Nq]`` float32, the
        log-sum-exp of the visible scores as float32 computes it; a row
        that sees no key carries the sentinel, to the bit."""
        from paddle1_tpu.ops.pallas import flash_attention as fa
        from paddle1_tpu.ops.pallas import mask_rules
        q, k, v, _, keep = _attention_problem(
            nq, nk, d, jnp.dtype(dtype), mask, h=h, h_kv=h_kv, dv=dv)
        rule = _rule_of_case(rule, nq)
        scale = d ** -0.5
        out, lse = fa._flash_fwd(
            q, k, v, scale, rule,
            None if keep is None else jnp.asarray(keep, jnp.float32))
        b = q.shape[0]
        assert out.shape == (b, nq, h, dv) and out.dtype == q.dtype
        assert lse.shape == (b * h, nq) and lse.dtype == jnp.float32
        seen = np.broadcast_to(
            mask_rules.dense_mask(rule, nq, nk)[None, None],
            (b, 1, nq, nk))
        if keep is not None:
            seen = seen & keep[:, None, None, :]
        scores = jnp.einsum(
            "bqhd,bkhd->bhqk", q.astype(jnp.float32),
            jnp.repeat(k, h // h_kv, axis=2).astype(jnp.float32)) * scale
        want = np.asarray(jax.nn.logsumexp(
            jnp.where(seen, scores, -jnp.inf), axis=-1))
        got = np.asarray(lse).reshape(b, h, nq)
        dead = ~seen.any(axis=(1, 3))                       # [b, nq]
        dead = np.broadcast_to(dead[:, None], got.shape)
        assert dead.any() == (mask == "padded_row")
        np.testing.assert_array_equal(got[dead], np.float32(fa._NEG_INF))
        # bf16: q * scale is rounded once before the product
        tol = 2e-5 if dtype == "float32" else 3e-2
        np.testing.assert_allclose(got[~dead], want[~dead], rtol=tol,
                                   atol=tol)

    @pytest.mark.parametrize(
        "rule,nq,nk,h,h_kv,d,dv,dtype,mask", LSE_CASES,
        ids=lambda c: "x".join(map(str, c)) if isinstance(c, tuple)
        else str(c))
    def test_the_gradients_behind_the_compact_lse_are_the_references(
            self, rule, nq, nk, h, h_kv, d, dv, dtype, mask):
        """Forward + backward through the LSE row at ``LSE_CASES``'
        shapes against ``attention_ref`` in float32, within the limits
        the file has; exact zeros behind a sentinel."""
        q, k, v, dout, keep = _attention_problem(
            nq, nk, d, jnp.dtype(dtype), mask, h=h, h_kv=h_kv, dv=dv)
        rule = _rule_of_case(rule, nq)
        self._held_to_the_reference(q, *_flash_and_ref(
            q, k, v, dout, keep, False, None, rule=rule), dtype,
            group=h // h_kv)

    def test_a_traced_call_counts_the_statistics_it_writes(self):
        """``flash_stat_bytes_total{kind="lse"}``: 4 bytes a (head,
        query) of a traced forward call, the one float32 row a head the
        kernel writes beside ``out`` (lane-replicated it read 128 times
        that: laguna's 64 heads of 16,384 queries 537 MB a call)."""
        from paddle1_tpu import obs
        from paddle1_tpu.ops.pallas import flash_attention as fa
        from paddle1_tpu.ops.pallas import mask_rules
        obs.reset_process_registry()
        count = lambda: obs.registry.process_group("kind").child(
            "lse").counter("flash_stat_bytes_total").value
        total = 0
        for (b, s, h, h_kv, d, dv), rule in [
                ((1, 16384, 64, 8, 128, 128), mask_rules.SlidingWindow(512)),
                ((2, 8192, 32, 32, 192, 128), mask_rules.CAUSAL),
                ((1, 16384, 32, 8, 64, 64), mask_rules.CAUSAL)]:
            q, k, v = (jax.ShapeDtypeStruct((b, s, heads, w), jnp.bfloat16)
                       for heads, w in ((h, d), (h_kv, d), (h_kv, dv)))
            jaxpr = jax.make_jaxpr(lambda q, k, v: fa._flash_fwd(
                q, k, v, d ** -0.5, rule))(q, k, v)
            total += b * h * s * 4
            assert count() == total
            call, = (e for e in _equations(jaxpr.jaxpr)
                     if e.primitive.name == "pallas_call")
            # out, and one float32 row a head: nothing 128 lanes wide
            # that is not out
            assert [(o.aval.shape, str(o.aval.dtype))
                    for o in call.outvars][1:] == [((b * h, 1, s),
                                                    "float32")]
        obs.reset_process_registry()

    def test_a_call_counts_its_key_ranges(self):
        """``flash_backward_ranges_total``: 1 a traced backward call
        whose dK and dV are resident whole, the ranges where not."""
        from paddle1_tpu import obs
        from paddle1_tpu.ops.pallas import flash_attention as fa
        obs.reset_process_registry()
        x = jax.ShapeDtypeStruct((1, 512, 2, 64), jnp.float32)

        def grad(blocks):
            return jax.make_jaxpr(jax.grad(lambda q, k, v: jnp.sum(
                fa.flash_attention(q, k, v, causal=True, blocks=blocks)),
                argnums=(0, 1, 2)))(x, x, x)
        count = lambda: obs.registry.process_registry().counter(
            "flash_backward_ranges_total").value
        assert "pallas_call" in str(grad((128, 256, 128)))
        assert count() == 1
        grad((_B128, (128, 128, 128, 128)))
        assert count() == 1 + 4
        obs.reset_process_registry()

    def test_degenerate_alignments_are_left_to_the_reference(self):
        from paddle1_tpu.ops.pallas import flash_attention as fa
        ok = (2, 256, 4, 64)
        assert fa.supported(ok, ok, causal=True)
        assert fa.supported((1, 65536, 1, 128), (1, 65536, 1, 128), True)
        assert fa.supported((2, 128, 4, 64), ok, causal=True)
        assert not fa.supported(ok, (2, 128, 4, 64), causal=True)
        assert fa.supported(ok, (2, 128, 4, 64), causal=False)
        assert not fa.supported((2, 200, 4, 64), ok)
        assert not fa.supported(ok, (2, 200, 4, 64))
        assert not fa.supported((2, 256, 4, 260), (2, 256, 4, 260))
        assert not fa.supported((2, 256, 4, 60), (2, 256, 4, 60))
        assert not fa.supported((256, 4, 64), (256, 4, 64))

    @pytest.mark.parametrize("d,dtype", [(128, "bfloat16"), (256, "float32")])
    @pytest.mark.parametrize("nq,nk", [(128, 128), (384, 640), (4096, 4096),
                                       (512, 8192), (1536, 1536)])
    def test_the_chosen_blocks_divide_the_lengths_and_fit(self, nq, nk, d,
                                                          dtype):
        from paddle1_tpu.ops.pallas import flash_attention as fa
        from paddle1_tpu.ops.pallas import flash_attention_bwd as fb
        bq, bk, chunk = fa.block_sizes(nq, nk, d, dtype)
        assert bk * d * jnp.dtype(dtype).itemsize <= 512 << 10
        assert nq % bq == 0 and nk % bk == 0 and bk % chunk == 0
        # the backward takes the forward's, and keeps every key's dK and
        # dV resident where they fit what it may ask of the VMEM
        assert fb.block_sizes(nq, nk, d, dtype) == (bq, bk, chunk)
        span = fb.key_span(nk, bq, bk, chunk, d, d, dtype)
        assert span == nk
        assert fb._vmem_bytes(span, bq, bk, chunk, d, d, dtype) \
            <= fb._VMEM_CAP
        # 65,536 keys do not: the fewest halvings that do
        long = fb.key_span(65536, bq, 512, 512, d, d, dtype)
        assert long == (32768 if d == 128 else 8192)
        need = lambda n: fb._vmem_bytes(n, bq, 512, 512, d, d, dtype)
        assert need(long) <= fb._VMEM_CAP < need(2 * long)


def _equations(jaxpr):
    """The equations of a jaxpr and of every jaxpr inside it, a
    ``pallas_call``'s own body left out."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name == "pallas_call":
            continue
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else (v,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _equations(inner)


def _primitives(jaxpr):
    """Names of the primitives of a jaxpr and of every jaxpr inside it,
    a ``pallas_call``'s own body left out (its grid is the kernel's)."""
    return [eqn.primitive.name for eqn in _equations(jaxpr)]


def test_a_causal_models_step_holds_no_while_on_the_kernels_path():
    """``trace_reduce`` counts a ``while`` instruction's event and its
    body's events both (PERF.md, PR 27): the kernels' wrappers put no
    ``scan`` / ``while`` / ``fori_loop`` in the step they are traced
    into, forward, recomputed forward or backward."""
    import paddle1_tpu as paddle
    from paddle1_tpu.core.tensor import Tensor
    from paddle1_tpu.distributed import ParallelEngine, build_mesh
    from paddle1_tpu.text.models import (OuroForPretraining,
                                         OuroPretrainingCriterion)
    model = OuroForPretraining(
        vocab_size=96, hidden_size=32, num_hidden_layers=1,
        num_attention_heads=2, head_dim=16, intermediate_size=48,
        total_ut_steps=2, rope_theta=1e4, rms_norm_eps=1e-6,
        initializer_range=0.2)
    crit = OuroPretrainingCriterion(0.1)

    def loss_fn(m, b):
        ids = Tensor(b["ids"])
        labels = m.next_token_labels(ids)
        return crit(*m(ids, labels), labels)
    engine = ParallelEngine(
        model, paddle.optimizer.AdamW(learning_rate=1e-3,
                                      parameters=model.parameters()),
        loss_fn, amp_dtype="bfloat16", recompute=True,
        mesh=build_mesh(dp=1, devices=jax.devices()[:1]))
    ids = jnp.zeros((2, 128), jnp.int32)
    with flags_guard(flash_attention="always"):
        names = _primitives(jax.make_jaxpr(engine._step_fn)(
            engine.params, engine.opt_state, {"ids": ids},
            jax.random.key(0), jnp.float32(1e-3)).jaxpr)
    # 2 loop steps x 1 layer: forward, backward (the recomputation keeps
    # the forward kernel's outputs: no second call of it)
    assert names.count("pallas_call") == 4
    assert not {"while", "scan"} & set(names)


# (platform, one device's step, flag, causal, seq_q, seq_k, d) -> arm
ARM_RULE = [
    ("tpu", True, "auto", True, 4096, 4096, 128, "flash"),      # ouro
    ("tpu", True, "auto", True, N, N, 128, "flash"),
    ("tpu", True, "auto", True, N, N, 64, "flash"),
    ("tpu", True, "auto", True, N - 128, N - 128, 128, "dense"),
    ("tpu", True, "auto", True, N, 2 * N, 128, "flash"),
    ("tpu", True, "auto", True, N - 128, 2 * N, 128, "dense"),
    ("tpu", True, "auto", False, 128, 128, 64, "dense"),        # bert
    ("tpu", True, "auto", False, 512, 512, 64, "dense"),
    ("tpu", True, "auto", False, N, N, 64, "flash"),            # mask or
    ("tpu", True, "auto", False, 4096, 4096, 128, "flash"),     # not
    ("tpu", True, "auto", True, 4096 + 64, 4096 + 64, 128, "dense"),
    ("tpu", True, "auto", True, 4096, 4096, 132, "dense"),
    ("tpu", False, "auto", True, 4096, 4096, 128, "dense"),     # GSPMD
    ("cpu", True, "auto", True, 4096, 4096, 128, "dense"),
    ("cpu", True, "always", False, 128, 128, 64, "flash"),
    ("tpu", False, "always", True, 256, 256, 64, "flash"),
    ("tpu", True, "never", True, 4096, 4096, 128, "dense"),
]


class TestAttentionArm:
    """``use_flash_for``: which arm ``scaled_dot_product_attention``
    traces, from platform, devices, mask and shape alone, and the
    counter that says so."""

    @pytest.mark.parametrize("platform,single,mode,causal,sq,sk,d,arm",
                             ARM_RULE)
    def test_the_rule(self, platform, single, mode, causal, sq, sk, d, arm,
                      monkeypatch):
        from paddle1_tpu import obs
        from paddle1_tpu.core import flags as core_flags
        from paddle1_tpu.core.tensor import Tensor
        from paddle1_tpu.nn.functional import attention as A
        from paddle1_tpu.ops.pallas import flash_attention as fa
        monkeypatch.setattr(core_flags, "_on_tpu",
                            lambda: platform == "tpu")
        took = []

        def stop(name):
            def spy(*a, **k):
                took.append(name)
                raise RuntimeError("stop-at-dispatch")
            return spy
        monkeypatch.setattr(fa, "flash_attention", stop("flash"))
        monkeypatch.setattr(A, "attention_ref", stop("dense"))
        obs.reset_process_registry()
        q = Tensor(np.zeros((1, sq, 1, d), np.float32))
        k = Tensor(np.zeros((1, sk, 1, d), np.float32))
        region = (contextlib.nullcontext() if single
                  else core_flags.auto_partitioned_region())
        with core_flags.flags_guard(flash_attention=mode), region:
            with pytest.raises(RuntimeError, match="stop-at-dispatch"):
                A.scaled_dot_product_attention(q, k, k, is_causal=causal)
        assert took == [arm]
        arms = obs.registry.process_group("arm")
        assert arms.labels() == [arm]
        assert arms.child(arm).counter("attention_arm_total").value == 1
        obs.reset_process_registry()

    def test_the_counter_counts_traces_not_runs(self):
        from paddle1_tpu import obs
        from paddle1_tpu.core.tensor import Tensor
        from paddle1_tpu.nn.functional import attention as A
        from paddle1_tpu.obs import http
        obs.reset_process_registry()
        x = jnp.ones((1, 128, 2, 16), jnp.float32)

        @jax.jit
        def f(x):
            return A.scaled_dot_product_attention(
                Tensor(x), Tensor(x), Tensor(x), is_causal=True).data
        with flags_guard(flash_attention="always"):
            f(x), f(x), f(x)
        with flags_guard(flash_attention="never"):
            A.scaled_dot_product_attention(Tensor(x), Tensor(x), Tensor(x))
        arms = obs.registry.process_group("arm")
        count = lambda a: arms.child(a).counter(
            "attention_arm_total").value
        assert (count("flash"), count("dense")) == (1, 1)
        server = http.TelemetryServer(port=0)
        page = server._metrics_page()
        server._httpd.server_close()   # never started: nothing to stop
        assert 'p1t_attention_arm_total{arm="flash"} 1' in page
        assert 'p1t_attention_arm_total{arm="dense"} 1' in page
        obs.reset_process_registry()
        assert obs.registry.process_group("arm").labels() == []
