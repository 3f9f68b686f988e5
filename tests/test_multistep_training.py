"""Device-resident multi-step training (ISSUE 1 tentpole): step_many
fuses k optimizer steps into ONE jitted executable; losses come back as
lazy LossFutures so the host loop never pays a per-step device→host
readback. Acceptance: step_many(k) parity with k sequential step()
calls (params + losses, atol 1e-6, CPU) with exactly one dispatch per
call; Model.fit completes an epoch with zero per-batch readbacks;
DataLoader prefetch threads shut down cleanly after a broken-out loop;
bench.py parses its own JSON line."""

import os
import threading
import time
import unittest
import warnings

import numpy as np
import pytest

import paddle1_tpu as paddle
from paddle1_tpu import nn
from paddle1_tpu.core import async_loss
from paddle1_tpu.core.async_loss import LossFuture
from paddle1_tpu.distributed import ParallelEngine, build_mesh


def _mlp(seed=0):
    paddle.seed(seed)
    return nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 4))


def _clone_into(src, dst):
    dst.set_state_dict({k: paddle.to_tensor(v.numpy().copy())
                        for k, v in src.state_dict().items()})


def _mse_loss(m, b):
    out = m(paddle.to_tensor(b["x"]))
    return ((out - paddle.to_tensor(b["y"])) ** 2).mean()


def _batches(n, bs=4, accum=1, seed=0):
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n):
        b = {"x": rng.standard_normal((bs * accum, 8)).astype(np.float32),
             "y": rng.standard_normal((bs * accum, 4)).astype(np.float32)}
        if accum > 1:
            b = {k: v.reshape((accum, bs) + v.shape[1:])
                 for k, v in b.items()}
        out.append(b)
    return out


def _single_dev_mesh():
    import jax
    return build_mesh(dp=1, devices=jax.devices()[:1])


def _engines(opt_factory, grad_accum=1, **kw):
    net_a, net_b = _mlp(0), _mlp(1)
    _clone_into(net_a, net_b)
    ea = ParallelEngine(net_a, opt_factory(net_a), _mse_loss,
                        mesh=_single_dev_mesh(), grad_accum=grad_accum,
                        **kw)
    eb = ParallelEngine(net_b, opt_factory(net_b), _mse_loss,
                        mesh=_single_dev_mesh(), grad_accum=grad_accum,
                        **kw)
    return (net_a, ea), (net_b, eb)


class TestStepManyParity(unittest.TestCase):
    def _assert_parity(self, opt_factory, k=5, grad_accum=1):
        (net_a, ea), (net_b, eb) = _engines(opt_factory,
                                            grad_accum=grad_accum)
        batches = _batches(k, accum=grad_accum)
        paddle.seed(42)
        seq = [float(ea.step(b)) for b in batches]
        paddle.seed(42)
        fut = eb.step_many(batches)
        self.assertIsInstance(fut, LossFuture)
        many = np.asarray(fut)
        self.assertEqual(many.shape, (k,))
        np.testing.assert_allclose(seq, many, atol=1e-6)
        ea.sync_model()
        eb.sync_model()
        sa, sb = net_a.state_dict(), net_b.state_dict()
        for key in sa:
            np.testing.assert_allclose(np.asarray(sa[key].numpy()),
                                       np.asarray(sb[key].numpy()),
                                       atol=1e-6, err_msg=key)
        return ea, eb

    def test_adamw_parity(self):
        self._assert_parity(lambda m: paddle.optimizer.AdamW(
            learning_rate=1e-2, parameters=m.parameters()))

    def test_grad_accum_composes_with_step_scan(self):
        # outer scan over steps wraps the existing grad-accum inner scan
        self._assert_parity(lambda m: paddle.optimizer.SGD(
            learning_rate=0.05, parameters=m.parameters()), grad_accum=2)

    def test_lr_schedule_advances_k_times(self):
        from paddle1_tpu.optimizer.lr import StepDecay
        scheds = []

        def factory(m):
            s = StepDecay(learning_rate=0.1, step_size=2, gamma=0.5)
            scheds.append(s)
            return paddle.optimizer.SGD(learning_rate=s,
                                        parameters=m.parameters())

        self._assert_parity(factory, k=5)
        # both schedulers saw exactly 5 steps
        self.assertEqual(scheds[0].last_epoch, scheds[1].last_epoch)
        self.assertEqual(scheds[0].last_lr, scheds[1].last_lr)

    def test_exactly_one_dispatch_per_step_many(self):
        (_, ea), (_, eb) = _engines(lambda m: paddle.optimizer.SGD(
            learning_rate=0.05, parameters=m.parameters()))
        batches = _batches(4)
        for b in batches:
            ea.step(b)
        self.assertEqual(ea.dispatch_count, 4)
        eb.step_many(batches)
        self.assertEqual(eb.dispatch_count, 1)
        self.assertEqual(eb.trace_count, 1)
        # second step_many(k=4) reuses the compiled executable
        paddle.seed(7)
        eb.step_many(batches)
        self.assertEqual(eb.dispatch_count, 2)
        self.assertEqual(eb.trace_count, 1)
        self.assertEqual(eb.cache_stats(), {"hits": 1, "misses": 1})

    def test_step_many_of_one_delegates_to_step(self):
        (_, ea), _ = _engines(lambda m: paddle.optimizer.SGD(
            learning_rate=0.05, parameters=m.parameters()))
        fut = ea.step_many(_batches(1))
        self.assertTrue(np.isfinite(float(fut)))
        self.assertEqual(ea.dispatch_count, 1)


class TestRetraceGuard(unittest.TestCase):
    def test_new_batch_shape_warns_once(self):
        (_, ea), _ = _engines(lambda m: paddle.optimizer.SGD(
            learning_rate=0.05, parameters=m.parameters()))
        ea.step(_batches(1, bs=4)[0])
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            ea.step(_batches(1, bs=6)[0])   # new shape → retrace warning
            ea.step(_batches(1, bs=2)[0])   # warned once already
        msgs = [str(x.message) for x in w if "retracing" in str(x.message)]
        self.assertEqual(len(msgs), 1)

    def test_guard_respects_flag(self):
        from paddle1_tpu.core.flags import flags_guard
        (_, ea), _ = _engines(lambda m: paddle.optimizer.SGD(
            learning_rate=0.05, parameters=m.parameters()))
        ea.step(_batches(1, bs=4)[0])
        with flags_guard(jit_retrace_warn=False), \
                warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            ea.step(_batches(1, bs=6)[0])
        self.assertFalse([x for x in w
                          if "retracing" in str(x.message)])


class TestAsyncLoss(unittest.TestCase):
    def test_handle_matches_eager_readback(self):
        (net_a, ea), (net_b, eb) = _engines(lambda m: paddle.optimizer.SGD(
            learning_rate=0.05, parameters=m.parameters()))
        b = _batches(1)[0]
        paddle.seed(3)
        eager = float(np.asarray(ea.step(b).data))  # direct device fetch
        paddle.seed(3)
        fut = eb.step(b)
        self.assertFalse(fut.materialized)
        self.assertEqual(float(fut), eager)
        self.assertTrue(fut.materialized)
        self.assertEqual(fut.item(), eager)  # cached, same value

    def test_readback_counted_once_per_handle(self):
        async_loss.reset_readback_count()
        (_, ea), _ = _engines(lambda m: paddle.optimizer.SGD(
            learning_rate=0.05, parameters=m.parameters()))
        fut = ea.step(_batches(1)[0])
        self.assertEqual(async_loss.readback_count(), 0)
        fut.block()                       # sync is NOT a readback
        self.assertEqual(async_loss.readback_count(), 0)
        float(fut)
        fut.item()
        np.asarray(fut)
        self.assertEqual(async_loss.readback_count(), 1)

    def test_inflight_window_bounds_queue(self):
        (_, ea), _ = _engines(lambda m: paddle.optimizer.SGD(
            learning_rate=0.05, parameters=m.parameters()))
        self.assertEqual(ea.inflight_window, 2)
        for b in _batches(6):
            ea.step(b)
        self.assertLessEqual(len(ea._inflight), 2)
        ea.drain()
        self.assertEqual(len(ea._inflight), 0)

    def test_numeric_protocol_matches_old_float_returns(self):
        (_, ea), _ = _engines(lambda m: paddle.optimizer.SGD(
            learning_rate=0.05, parameters=m.parameters()))
        fut = ea.step(_batches(1)[0])
        v = fut.item()
        self.assertTrue(fut < v + 1 and fut > v - 1)
        self.assertTrue(v - 1 < fut <= v)
        self.assertEqual(fut + 1.0, v + 1.0)
        self.assertEqual(1.0 + fut, 1.0 + v)
        self.assertEqual(min([fut, v + 5]), v)
        self.assertAlmostEqual(2.0 / fut, 2.0 / v)
        self.assertEqual(-fut, -v)

    def test_formatting_materializes(self):
        (_, ea), _ = _engines(lambda m: paddle.optimizer.SGD(
            learning_rate=0.05, parameters=m.parameters()))
        fut = ea.step(_batches(1)[0])
        s = f"{fut:.4f}"
        self.assertRegex(s, r"^\d+\.\d{4}$")


class _SyntheticDS(paddle.io.Dataset):
    def __init__(self, n=32):
        self.n = n

    def __len__(self):
        return self.n

    def __getitem__(self, i):
        rng = np.random.default_rng(i)
        return (rng.standard_normal(8).astype(np.float32),
                np.int64(i % 3))


class TestModelFitNoPerBatchReadback(unittest.TestCase):
    def test_silent_epoch_has_zero_readbacks(self):
        paddle.seed(0)
        net = nn.Sequential(nn.Linear(8, 3))
        model = paddle.Model(net)
        model.prepare(optimizer=paddle.optimizer.SGD(
            learning_rate=0.05, parameters=net.parameters()),
            loss=nn.CrossEntropyLoss())
        async_loss.reset_readback_count()
        model.fit(_SyntheticDS(), epochs=1, batch_size=8, verbose=0)
        self.assertEqual(async_loss.readback_count(), 0)

    def test_train_batch_returns_lazy_handles(self):
        paddle.seed(0)
        net = nn.Sequential(nn.Linear(8, 3))
        model = paddle.Model(net)
        model.prepare(optimizer=paddle.optimizer.SGD(
            learning_rate=0.05, parameters=net.parameters()),
            loss=nn.CrossEntropyLoss())
        out = model.train_batch([np.zeros((4, 8), np.float32)],
                                [np.zeros((4,), np.int64)])
        self.assertIsInstance(out[0], LossFuture)
        self.assertTrue(np.isfinite(float(out[0])))

    def test_verbose_epoch_end_materializes(self):
        # formatting the epoch-end log line IS the materialization point
        paddle.seed(0)
        net = nn.Sequential(nn.Linear(8, 3))
        model = paddle.Model(net)
        model.prepare(optimizer=paddle.optimizer.SGD(
            learning_rate=0.05, parameters=net.parameters()),
            loss=nn.CrossEntropyLoss())
        async_loss.reset_readback_count()
        model.fit(_SyntheticDS(64), epochs=1, batch_size=8, verbose=2,
                  log_freq=100)   # one step-0 line + the epoch-end line
        n_batches = 8
        self.assertLessEqual(async_loss.readback_count(), 2)
        self.assertLess(async_loss.readback_count(), n_batches)


class TestDataLoaderMultiStepFeed(unittest.TestCase):
    def test_peek_many_pops_chunks(self):
        loader = paddle.io.DataLoader(_SyntheticDS(32), batch_size=4)
        it = iter(loader)
        chunk = it.peek_many(3)
        self.assertEqual(len(chunk), 3)
        rest = it.peek_many(100)   # truncates at epoch end
        self.assertEqual(len(rest), 5)
        with self.assertRaises(StopIteration):
            it.peek_many(2)

    def test_prefetch_thread_shuts_down_after_break(self):
        loader = paddle.io.DataLoader(_SyntheticDS(64), batch_size=2,
                                      prefetch_factor=2)
        it = iter(loader)
        for i, _ in enumerate(it):
            if i == 1:
                break                      # queue still full, producer live
        it.shutdown()
        deadline = time.time() + 5
        while it._thread.is_alive() and time.time() < deadline:
            time.sleep(0.01)
        self.assertFalse(it._thread.is_alive())

    def test_step_stream_uses_chunk_size(self):
        net = _mlp(0)
        eng = ParallelEngine(
            net, paddle.optimizer.SGD(learning_rate=0.05,
                                      parameters=net.parameters()),
            _mse_loss, mesh=_single_dev_mesh(), train_steps_per_sync=3)
        batches = _batches(7)
        futs = list(eng.step_stream(batches))
        # 7 batches at k=3 → two fused dispatches + 1 sequential
        # remainder step (the tail never compiles a fresh scan)
        self.assertEqual(eng.dispatch_count, 3)
        self.assertEqual(np.asarray(futs[0]).shape, (3,))
        total = sum(np.asarray(f).size for f in futs)
        self.assertEqual(total, 7)

    def test_strategy_knob_reaches_engine(self):
        from paddle1_tpu.distributed.fleet import (DistributedStrategy,
                                                   compile_strategy)
        s = DistributedStrategy()
        s.train_steps_per_sync = 8
        cfg = compile_strategy(s, n_devices=8)
        self.assertEqual(cfg["train_steps_per_sync"], 8)


class TestBenchJson(unittest.TestCase):
    def test_bench_parses_its_own_json_line(self, capsys=None):
        import io
        import sys
        sys.path.insert(0, __file__.rsplit("/tests/", 1)[0])
        import bench
        buf = io.StringIO()
        stdout, sys.stdout = sys.stdout, buf
        try:
            rec = bench._emit(
                "bert_base_pretrain_samples_per_sec_per_chip", 123.4,
                "samples/s", 0.5,
                {"steps_per_dispatch": 8, "steps_per_readback": 24,
                 "compile_cache": {"hits": 2, "misses": 1}})
        finally:
            sys.stdout = stdout
        line = buf.getvalue().strip()
        parsed = bench.parse_result_line(line)
        self.assertEqual(parsed, rec)
        self.assertEqual(parsed["detail"]["steps_per_readback"], 24)
        self.assertEqual(parsed["detail"]["compile_cache"],
                         {"hits": 2, "misses": 1})
        with self.assertRaises(ValueError):
            bench.parse_result_line('{"metric": "x"}')
        with self.assertRaises(ValueError):
            bench.parse_result_line("not json at all")


@pytest.mark.parametrize("env_dir,flag_dir,default_dir,want", [
    # the environment places the cache: nothing is set in code
    ("/env/cache", "", "", None),
    ("/env/cache", "", "DEFAULT", None),
    ("/env/cache", "FLAG", "", None),          # flag ignored, one warning
    # unset: the flag, else the entry point's fixed path, else no cache
    (None, "FLAG", "DEFAULT", "FLAG"),
    (None, "", "DEFAULT", "DEFAULT"),
    (None, "", "", None),
])
def test_compilation_cache_rule(monkeypatch, tmp_path, env_dir, flag_dir,
                                default_dir, want):
    import jax
    from paddle1_tpu.core import flags as core_flags
    place = lambda name: str(tmp_path / name) if name else ""
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    updates = {}
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: updates.__setitem__(k, v))
    monkeypatch.setattr(core_flags, "_compilation_cache_wired", False)
    with core_flags.flags_guard(jit_cache_dir=place(flag_dir)):
        if env_dir and flag_dir:
            with pytest.warns(UserWarning, match="jit_cache_dir=.*ignored"):
                did = core_flags.maybe_enable_compilation_cache(
                    place(default_dir))
        else:
            did = core_flags.maybe_enable_compilation_cache(
                place(default_dir))
        # idempotent: a second call sets nothing more
        assert core_flags.maybe_enable_compilation_cache(
            place(default_dir)) is False
    assert did is (want is not None)
    assert updates.get("jax_compilation_cache_dir") == (
        place(want) if want else None)
    if want:
        assert os.path.isdir(place(want))


class TestMeshIdentityPassThrough(unittest.TestCase):
    def test_prestaged_same_mesh_passes_other_mesh_replaces(self):
        import jax
        net = _mlp(0)
        eng = ParallelEngine(
            net, paddle.optimizer.SGD(learning_rate=0.05,
                                      parameters=net.parameters()),
            _mse_loss, mesh=build_mesh(dp=2, devices=jax.devices()[:2]))
        b = _batches(1)[0]
        staged = eng.shard_batch(b)
        # same mesh: leaves pass through untouched (no re-placement)
        again = eng.shard_batch(staged)
        for l1, l2 in zip(jax.tree_util.tree_leaves(staged),
                          jax.tree_util.tree_leaves(again)):
            self.assertIs(l1, l2)
        # same axis sizes, DIFFERENT devices: must be re-placed, not
        # passed through (ADVICE r5 mesh-identity fix)
        other = build_mesh(dp=2, devices=jax.devices()[2:4])
        net2 = _mlp(1)
        eng2 = ParallelEngine(
            net2, paddle.optimizer.SGD(learning_rate=0.05,
                                       parameters=net2.parameters()),
            _mse_loss, mesh=other)
        self.assertEqual(dict(other.shape), dict(eng.mesh.shape))
        replaced = eng2.shard_batch(staged)
        for leaf in jax.tree_util.tree_leaves(replaced):
            self.assertTrue(set(leaf.sharding.device_set)
                            <= set(np.ravel(other.devices).tolist()))
        # and the re-placed batch still trains
        self.assertTrue(np.isfinite(float(eng2.step(replaced))))


if __name__ == "__main__":
    unittest.main()
