"""Regression tests for the ADVICE r1 findings:
QAT-under-jit silent collapse, NMS negative-coordinate category offsets,
box_coder axis semantics, shm create/attach ftruncate discipline, profiler
cross-thread trace state."""

import threading
import unittest

import numpy as np
import jax
import jax.numpy as jnp

from paddle1_tpu.core.tensor import to_tensor


class TestQATUnderJit(unittest.TestCase):
    def test_uncalibrated_activation_quant_passes_through_under_jit(self):
        """An uninited EMA observer inside a jitted/functionalized forward
        must pass activations through, not clamp them to ~0."""
        from paddle1_tpu.nn.layer_common import Linear
        from paddle1_tpu.quantization import QAT

        lin = Linear(8, 8)
        q = QAT()
        model = q.quantize(lin)
        model.eval()

        x = np.random.default_rng(0).standard_normal((4, 8)).astype(
            np.float32)
        params = model.functional_state()

        def fwd(params, x):
            from paddle1_tpu.autograd import engine as ag
            with ag.no_grad(), model.load_functional_state(params):
                return model(to_tensor(x)).data

        out_jit = np.asarray(jax.jit(fwd)(params, x))
        out_eager = np.asarray(fwd(params, x))
        # pre-fix the jitted path quantized with scale=0 → all ~0 outputs
        self.assertGreater(np.abs(out_jit).max(), 1e-3)
        np.testing.assert_allclose(out_jit, out_eager, rtol=1e-5, atol=1e-6)

    def test_calibrated_observer_quantizes_under_jit(self):
        from paddle1_tpu.quantization import FakeQuantMovingAverageAbsMax
        obs = FakeQuantMovingAverageAbsMax(bits=8)
        x = np.linspace(-1, 1, 1000).astype(np.float32)
        obs.train()
        obs(to_tensor(x))  # calibrates scale
        obs.eval()
        params = obs.functional_state()

        def fwd(params, x):
            from paddle1_tpu.autograd import engine as ag
            with ag.no_grad(), obs.load_functional_state(params):
                return obs(to_tensor(x)).data

        out = np.asarray(jax.jit(fwd)(params, x))
        # quantized: at most 2^bits levels, but non-degenerate
        self.assertGreater(np.abs(out).max(), 0.5)
        self.assertLess(len(np.unique(np.round(out, 5))), 260)


class TestNMSNegativeCoords(unittest.TestCase):
    def test_category_offset_with_negative_boxes(self):
        """Identical overlapping boxes in different categories must BOTH
        survive even when coordinates are negative (the max+1 offset
        collapsed categories then)."""
        from paddle1_tpu.vision import ops as V
        boxes = np.array([[-50, -50, -40, -40],
                          [-50, -50, -40, -40]], np.float32)
        scores = np.array([0.9, 0.8], np.float32)
        cats = np.array([0, 1], np.int32)
        keep = V.nms(to_tensor(boxes), 0.5, to_tensor(scores),
                     category_idxs=to_tensor(cats))
        self.assertEqual(sorted(np.asarray(keep.numpy()).tolist()), [0, 1])

    def test_same_category_still_suppressed(self):
        from paddle1_tpu.vision import ops as V
        boxes = np.array([[-50, -50, -40, -40],
                          [-50, -50, -40, -40]], np.float32)
        scores = np.array([0.9, 0.8], np.float32)
        cats = np.array([0, 0], np.int32)
        keep = V.nms(to_tensor(boxes), 0.5, to_tensor(scores),
                     category_idxs=to_tensor(cats))
        self.assertEqual(np.asarray(keep.numpy()).tolist(), [0])


class TestBoxCoderAxis(unittest.TestCase):
    def _roundtrip(self, axis):
        from paddle1_tpu.vision import ops as V
        rng = np.random.default_rng(0)
        m = 3
        prior = np.abs(rng.standard_normal((m, 4))).astype(np.float32)
        prior[:, 2:] = prior[:, :2] + 1.0 + prior[:, 2:]
        # encode m targets against m priors → [m, m, 4]; diagonal is each
        # target vs its own prior
        target = prior + 0.1
        enc = np.asarray(V.box_coder(to_tensor(prior), None,
                                     to_tensor(target),
                                     code_type="encode_center_size").numpy())
        self.assertEqual(enc.shape, (m, m, 4))
        # decode with target [N=m, M=m, 4]
        dec = np.asarray(V.box_coder(
            to_tensor(prior), None, to_tensor(enc),
            code_type="decode_center_size", axis=axis).numpy())
        return target, enc, dec

    def test_axis0_roundtrip_diagonal(self):
        target, enc, dec = self._roundtrip(axis=0)
        # axis=0: prior aligns with dim 1 → dec[i, i] recovers target[i]
        for i in range(3):
            np.testing.assert_allclose(dec[i, i], target[i], rtol=1e-5,
                                       atol=1e-5)

    def test_axis1_differs_from_axis0(self):
        from paddle1_tpu.vision import ops as V
        rng = np.random.default_rng(1)
        m = 3
        prior = np.abs(rng.standard_normal((m, 4))).astype(np.float32)
        prior[:, 2:] = prior[:, :2] + 1.0 + prior[:, 2:]
        deltas = rng.standard_normal((m, m, 4)).astype(np.float32) * 0.1
        d0 = np.asarray(V.box_coder(to_tensor(prior), None,
                                    to_tensor(deltas),
                                    code_type="decode_center_size",
                                    axis=0).numpy())
        d1 = np.asarray(V.box_coder(to_tensor(prior), None,
                                    to_tensor(deltas),
                                    code_type="decode_center_size",
                                    axis=1).numpy())
        self.assertEqual(d0.shape, d1.shape)
        self.assertFalse(np.allclose(d0, d1))
        # axis=1 on transposed deltas == transpose of axis=0
        d1t = np.asarray(V.box_coder(
            to_tensor(prior), None,
            to_tensor(np.swapaxes(deltas, 0, 1).copy()),
            code_type="decode_center_size", axis=1).numpy())
        np.testing.assert_allclose(np.swapaxes(d1t, 0, 1), d0, rtol=1e-5,
                                   atol=1e-5)


class TestShmDiscipline(unittest.TestCase):
    def test_attach_existing_does_not_resize(self):
        from paddle1_tpu.core import native
        if not native.available():
            self.skipTest("native lib unavailable")
        name = "/p1t_test_resize"
        lib = native._load()
        lib.shm_arena_unlink(name.encode())
        a = native.ShmArena(name, 1 << 16)
        try:
            # a second create must ATTACH at the existing size, never
            # ftruncate an arena another process already mapped
            b = native.ShmArena(name, 1 << 14)  # smaller request: ok
            self.assertEqual(a.size, b.size)
            off = lib.shm_alloc(a._base, 100)
            self.assertGreater(off, 0)
        finally:
            lib.shm_arena_unlink(name.encode())

    def test_concurrent_alloc_no_overlap(self):
        from paddle1_tpu.core import native
        if not native.available():
            self.skipTest("native lib unavailable")
        name = "/p1t_test_race"
        lib = native._load()
        lib.shm_arena_unlink(name.encode())
        arena = native.ShmArena(name, 1 << 20)
        offsets = []
        lock = threading.Lock()

        def worker():
            got = []
            for _ in range(200):
                off = lib.shm_alloc(arena._base, 64)
                if off:
                    got.append(off)
            with lock:
                offsets.extend(got)

        ts = [threading.Thread(target=worker) for _ in range(8)]
        [t.start() for t in ts]
        [t.join() for t in ts]
        try:
            self.assertEqual(len(offsets), len(set(offsets)))
        finally:
            lib.shm_arena_unlink(name.encode())


class TestProfilerCrossThread(unittest.TestCase):
    def test_stop_on_other_thread_sees_trace_state(self):
        import paddle1_tpu.profiler as prof
        # no real device trace (log_dir None keeps jax out of it); assert
        # the module-global state is visible across threads
        prof._trace_dir = "/tmp/fake_dir_sentinel"
        seen = {}

        def other():
            seen["dir"] = prof._trace_dir

        t = threading.Thread(target=other)
        t.start()
        t.join()
        prof._trace_dir = None
        self.assertEqual(seen["dir"], "/tmp/fake_dir_sentinel")




class TestAdviceR3Fixes(unittest.TestCase):
    """Regression tests for the ADVICE r3 findings."""

    def test_fluid_cross_entropy_soft_label(self):
        import paddle1_tpu.fluid.layers as L
        rng = np.random.default_rng(0)
        logits = rng.standard_normal((4, 5)).astype(np.float32)
        probs = np.exp(logits) / np.exp(logits).sum(-1, keepdims=True)
        soft = rng.random((4, 5)).astype(np.float32)
        soft /= soft.sum(-1, keepdims=True)
        out = L.cross_entropy(to_tensor(probs), to_tensor(soft),
                              soft_label=True)
        expect = -(soft * np.log(probs)).sum(-1)
        np.testing.assert_allclose(np.asarray(out.data), expect, rtol=1e-5)

    def test_fluid_cross_entropy_soft_label_shape_mismatch_raises(self):
        import paddle1_tpu.fluid.layers as L
        from paddle1_tpu.core.errors import InvalidArgumentError
        probs = np.full((4, 5), 0.2, np.float32)
        lab = np.zeros((4, 1), np.int64)
        with self.assertRaises(InvalidArgumentError):
            L.cross_entropy(to_tensor(probs), to_tensor(lab),
                            soft_label=True)

    def test_reader_compose_alignment_raises(self):
        from paddle1_tpu import reader
        r1 = lambda: iter([1, 2, 3])
        r2 = lambda: iter([10, 20])
        with self.assertRaises(reader.ComposeNotAligned):
            list(reader.compose(r1, r2)())

    def test_reader_compose_unchecked_truncates(self):
        from paddle1_tpu import reader
        r1 = lambda: iter([1, 2, 3])
        r2 = lambda: iter([10, 20])
        out = list(reader.compose(r1, r2, check_alignment=False)())
        self.assertEqual(out, [(1, 10), (2, 20)])

    def test_reader_compose_aligned_ok(self):
        from paddle1_tpu import reader
        r1 = lambda: iter([(1, 2), (3, 4)])
        r2 = lambda: iter([10, 20])
        out = list(reader.compose(r1, r2)())
        self.assertEqual(out, [(1, 2, 10), (3, 4, 20)])

    def test_ps_frame_hmac_rejects_unauthenticated(self):
        import os
        from paddle1_tpu.distributed import ps, ps_server
        os.environ["PADDLE_PS_SECRET"] = "topsecret"
        try:
            srv = ps_server.TableServer(ps.SparseTable(dim=4)).start()
            good = ps_server.RemoteTable(srv.endpoint)
            self.assertTrue(good.ping())
            # a frame with a forged tag must be dropped BEFORE the server
            # unpickles it: the connection closes with no reply
            import pickle
            import socket as socketlib
            def _drain(sock):
                out = b""
                while True:
                    b_ = sock.recv(4096)
                    if not b_:
                        return out
                    out += b_

            payload = pickle.dumps(("ping", None))
            raw = socketlib.create_connection(
                (srv.host, srv.port), timeout=5.0)
            raw.sendall(ps_server._HDR.pack(1, len(payload)) +
                        b"\x00" * ps_server._TAG_LEN + payload)
            reply = _drain(raw)  # err frame explaining, then close
            self.assertIn(b"HMAC", reply)
            self.assertNotIn(b"pong", reply)  # the op never executed
            raw.close()
            # an UNTAGGED frame against a secret-bearing server is a loud
            # drop too (flag byte prevents the read-deadlock)
            raw2 = socketlib.create_connection(
                (srv.host, srv.port), timeout=5.0)
            raw2.sendall(ps_server._HDR.pack(0, len(payload)) + payload)
            reply2 = _drain(raw2)
            self.assertIn(b"PADDLE_PS_SECRET", reply2)
            self.assertNotIn(b"pong", reply2)
            raw2.close()
            self.assertTrue(good.ping())  # authed session unaffected
            good.shutdown_server()
        finally:
            os.environ.pop("PADDLE_PS_SECRET", None)

    def test_engine_place_rejects_silent_spec_drop(self):
        """A 1-D leaf that would drop a sharded batch-spec axis under
        grad_accum errors at placement, not deep inside jit."""
        import paddle1_tpu as paddle
        from paddle1_tpu.core.errors import InvalidArgumentError
        from paddle1_tpu.distributed import ParallelEngine
        from paddle1_tpu.nn.layer_common import Linear

        model = Linear(4, 4)
        opt = paddle.optimizer.SGD(learning_rate=0.1,
                                   parameters=model.parameters())
        loss = lambda m, b: m(b["x"]).mean() + b["w"].mean()
        eng = ParallelEngine(model, opt, loss,
                             degrees={"dp": len(jax.devices())},
                             grad_accum=2)
        bad = {"x": np.zeros((2, 8, 4), np.float32),
               "w": np.zeros((8,), np.float32)}  # missing accum dim
        with self.assertRaises(InvalidArgumentError):
            eng.shard_batch(bad)
        # a 0-d leaf dies inside lax.scan under grad_accum — also caught
        # at placement with the friendly message
        with self.assertRaises(InvalidArgumentError):
            eng.shard_batch({"x": np.zeros((2, 8, 4), np.float32),
                             "s": np.float32(2.0)})
        ok = {"x": np.zeros((2, 8, 4), np.float32),
              "w": np.zeros((2, 8), np.float32)}
        eng.shard_batch(ok)  # placement fine; scalars still replicate

    def test_engine_place_scalar_leaf_still_replicates(self):
        import paddle1_tpu as paddle
        from paddle1_tpu.distributed import ParallelEngine
        from paddle1_tpu.nn.layer_common import Linear
        model = Linear(4, 4)
        opt = paddle.optimizer.SGD(learning_rate=0.1,
                                   parameters=model.parameters())
        eng = ParallelEngine(model, opt,
                             lambda m, b: m(b["x"]).mean(),
                             degrees={"dp": len(jax.devices())})
        placed = eng.shard_batch({"x": np.zeros((8, 4), np.float32),
                                  "s": np.float32(2.0)})
        self.assertEqual(placed["s"].shape, ())


if __name__ == "__main__":
    unittest.main()


class TestBatchNormTracedStatsWarning(unittest.TestCase):
    """ADVICE r6 medium (nn/functional/norm.py): the silent skip of
    running mean/var updates under jit/shard_map tracing must warn —
    once per buffer — so eval-after-compiled-training divergence has a
    signal."""

    def test_warns_once_per_buffer_under_tracing(self):
        import warnings

        import paddle1_tpu.nn.functional as F

        rm = to_tensor(np.zeros(3, np.float32))
        rv = to_tensor(np.ones(3, np.float32))
        x = np.random.default_rng(0).standard_normal((4, 3)).astype(
            np.float32)

        def f(xx):
            return F.batch_norm(to_tensor(xx), rm, rv, training=True).data

        with warnings.catch_warnings(record=True) as rec:
            warnings.simplefilter("always")
            jax.make_jaxpr(f)(x)
        skipped = [r for r in rec if "SKIPPED" in str(r.message)]
        self.assertEqual(len(skipped), 1, [str(r.message) for r in rec])

        # once per buffer: a second trace over the SAME buffers is quiet
        with warnings.catch_warnings(record=True) as rec2:
            warnings.simplefilter("always")
            jax.make_jaxpr(f)(x)
        self.assertFalse([r for r in rec2 if "SKIPPED" in str(r.message)])

        # the dedup contract itself (not trace caching): same buffer
        # quiet, a DIFFERENT buffer still warns
        from paddle1_tpu.nn.functional.norm import warn_traced_stats_skipped
        with warnings.catch_warnings(record=True) as rec2b:
            warnings.simplefilter("always")
            warn_traced_stats_skipped(rm, "batch_norm")
        self.assertFalse([r for r in rec2b if "SKIPPED" in str(r.message)])
        other = to_tensor(np.zeros(3, np.float32))
        with warnings.catch_warnings(record=True) as rec2c:
            warnings.simplefilter("always")
            warn_traced_stats_skipped(other, "batch_norm")
        self.assertEqual(
            1, len([r for r in rec2c if "SKIPPED" in str(r.message)]))

        # ... and eager training still updates the stats silently
        with warnings.catch_warnings(record=True) as rec3:
            warnings.simplefilter("always")
            F.batch_norm(to_tensor(x), rm, rv, training=True)
        self.assertFalse([r for r in rec3 if "SKIPPED" in str(r.message)])
        self.assertGreater(
            float(np.abs(np.asarray(rm.numpy())).max()), 0.0)
