"""Serving runtime (ISSUE 4): dynamic micro-batching inference server —
shape-bucketed executables, batcher parity, admission control,
deadlines, chaos-driven shed paths, graceful SIGTERM drain, metrics.

Fast cases ride tier-1; the loaded smoke (p99 bound) and the
subprocess/Supervisor SIGTERM drains are slow-marked (CI's serving
lane runs them, like --elastic)."""

import json
import os
import signal
import subprocess
import sys
import textwrap
import threading
import time

import numpy as np
import pytest

import paddle1_tpu as paddle
from paddle1_tpu.core import chaos, health
from paddle1_tpu.core.flags import flags_guard
from paddle1_tpu.serving import (DeadlineExceeded, InferenceEngine,
                                 Server, ServerClosed, ServerOverloaded,
                                 ServingMetrics, resolve_buckets)


@pytest.fixture(autouse=True)
def _isolate():
    health.reset()
    chaos.reset()
    yield
    health.reset()
    chaos.reset()


def _mlp(seed=0, din=8, dout=4):
    paddle.seed(seed)
    m = paddle.nn.Sequential(paddle.nn.Linear(din, 16), paddle.nn.ReLU(),
                             paddle.nn.Linear(16, dout))
    m.eval()
    return m


def _eager(model, x):
    from paddle1_tpu.core.tensor import to_tensor
    return np.asarray(model(to_tensor(x)).numpy())


class TestMetrics:
    def test_counter_histogram_snapshot(self):
        m = ServingMetrics()
        m.counter("requests_total").inc()
        m.counter("requests_total").inc(2)
        h = m.histogram("queue_ms")
        for v in range(100):
            h.observe(float(v))
        m.record_response(3)
        snap = m.snapshot()
        assert snap["counters"]["requests_total"] == 3
        s = snap["histograms"]["queue_ms"]
        assert s["count"] == 100 and s["max"] == 99.0
        assert 48 <= s["p50"] <= 51 and 97 <= s["p99"] <= 99
        assert snap["qps"] > 0
        text = m.render_text()
        assert "p1t_serving_requests_total 3" in text
        assert "p1t_serving_queue_ms_p99" in text

    def test_histogram_empty(self):
        h = ServingMetrics().histogram("x")
        assert h.percentile(99) == 0.0
        assert h.summary()["count"] == 0

    def test_render_text_prometheus_exposition(self):
        """Snapshot of the exposition format (ISSUE 7 satellite): a
        histogram exports as a Prometheus summary — TYPE header,
        quantile-labeled gauges, and RAW monotone _sum/_count series so
        rate(..._sum[1m]) / rate(..._count[1m]) works — plus the legacy
        stat gauges for existing scrapers."""
        m = ServingMetrics()
        m.counter("requests_total").inc(7)
        h = m.histogram("e2e_ms")
        h.observe(1.5)
        h.observe(2.25)
        text = m.render_text()
        lines = text.splitlines()
        assert "p1t_serving_requests_total 7" in lines
        assert "# TYPE p1t_serving_e2e_ms summary" in lines
        assert 'p1t_serving_e2e_ms{quantile="0.5"} 1.5' in lines
        assert 'p1t_serving_e2e_ms{quantile="0.95"} 2.25' in lines
        assert 'p1t_serving_e2e_ms{quantile="0.99"} 2.25' in lines
        # raw, unrounded totals (repr of the float sum, exact int count)
        assert "p1t_serving_e2e_ms_sum 3.75" in lines
        assert "p1t_serving_e2e_ms_count 2" in lines
        # legacy gauge lines survive for existing scrapers
        assert any(l.startswith("p1t_serving_e2e_ms_p99 ")
                   for l in lines)
        assert any(l.startswith("p1t_serving_e2e_ms_max ")
                   for l in lines)
        # the raw sum must not be the 4-digit-rounded summary value
        h2 = ServingMetrics()
        hh = h2.histogram("t")
        for _ in range(3):
            hh.observe(0.1)  # 0.30000000000000004 raw
        assert f"p1t_serving_t_sum {repr(0.1 + 0.1 + 0.1)}" \
            in h2.render_text()

    def test_generation_metrics_exposition(self):
        """ISSUE 9 satellite, extending the PR 7 format snapshot: the
        generation counters/gauge/histogram export — a gauge gets a
        ``# TYPE ... gauge`` header and a plain sample line, the
        per-request tokens_per_s rides the summary format, and
        tokens_generated_total is an ordinary counter line."""
        m = ServingMetrics()
        m.counter("tokens_generated_total").inc(37)
        m.gauge("slot_occupancy").set(0.75)
        m.histogram("tokens_per_s").observe(120.0)
        m.histogram("tokens_per_s").observe(80.0)
        lines = m.render_text().splitlines()
        assert "p1t_serving_tokens_generated_total 37" in lines
        assert "# TYPE p1t_serving_slot_occupancy gauge" in lines
        assert "p1t_serving_slot_occupancy 0.75" in lines
        assert "# TYPE p1t_serving_tokens_per_s summary" in lines
        assert "p1t_serving_tokens_per_s_count 2" in lines
        assert "p1t_serving_tokens_per_s_sum 200.0" in lines
        # snapshot carries the gauge; labeled multi-child pages drop
        # the TYPE header but keep the labeled sample (PR 7 rule)
        assert m.snapshot()["gauges"]["slot_occupancy"] == 0.75
        labeled = m.render_text(label=("version", "v2"),
                                type_headers=False)
        assert 'p1t_serving_slot_occupancy{version="v2"} 0.75' \
            in labeled.splitlines()
        assert "# TYPE p1t_serving_slot_occupancy gauge" not in labeled

    def test_gauges_merge_worst_child(self):
        from paddle1_tpu.serving import merge_snapshots
        a, b = ServingMetrics(), ServingMetrics()
        a.gauge("slot_occupancy").set(0.25)
        b.gauge("slot_occupancy").set(0.9)
        a.counter("tokens_generated_total").inc(10)
        b.counter("tokens_generated_total").inc(5)
        agg = merge_snapshots([a.snapshot(), b.snapshot()])
        assert agg["gauges"]["slot_occupancy"] == 0.9
        assert agg["counters"]["tokens_generated_total"] == 15


class TestBuckets:
    def test_auto_powers_of_two(self):
        assert resolve_buckets(None, 16) == (1, 2, 4, 8, 16)
        assert resolve_buckets(None, 12) == (1, 2, 4, 8, 12)

    def test_explicit_and_flag(self):
        assert resolve_buckets((8, 1, 4, 4), None) == (1, 4, 8)
        with flags_guard(serve_buckets="2,6"):
            assert resolve_buckets(None, None) == (2, 6)
        with pytest.raises(Exception, match="comma-separated"):
            with flags_guard(serve_buckets="2,six"):
                resolve_buckets(None, None)

    def test_bucket_for_and_oversize(self):
        eng = InferenceEngine(lambda x: x, buckets=(1, 4, 8))
        assert eng.bucket_for(1) == 1
        assert eng.bucket_for(3) == 4
        assert eng.bucket_for(8) == 8
        from paddle1_tpu.core.errors import InvalidArgumentError
        with pytest.raises(InvalidArgumentError, match="largest bucket"):
            eng.bucket_for(9)


class TestInferenceEngine:
    def test_ragged_parity_and_one_compile_per_bucket(self):
        model = _mlp(0)
        eng = InferenceEngine(model, buckets=(1, 4, 8),
                              input_specs=[((8,), "float32")])
        rng = np.random.default_rng(0)
        for rows in (1, 3, 5, 8, 3, 5, 1):  # repeats hit warm buckets
            x = rng.standard_normal((rows, 8)).astype(np.float32)
            out = eng.infer([x])[0]
            assert out.shape == (rows, 4)
            np.testing.assert_allclose(out, _eager(model, x), rtol=1e-5,
                                       atol=1e-6)
        # buckets touched: 1 (rows 1), 4 (rows 3), 8 (rows 5, 8) —
        # exactly one compile each despite 7 dispatches
        assert eng.compile_counts == {1: 1, 4: 1, 8: 1}
        assert sum(eng.dispatch_counts.values()) == 7
        assert eng.cache_stats()["misses"] == 3

    def test_warmup_precompiles_every_bucket(self):
        eng = InferenceEngine(_mlp(1), buckets=(1, 2, 4),
                              input_specs=[((8,), "float32")])
        assert eng.warm_up() == 3
        assert eng.compile_counts == {1: 1, 2: 1, 4: 1}
        x = np.zeros((2, 8), np.float32)
        eng.infer([x])
        assert eng.compile_counts[2] == 1  # served warm, no recompile

    def test_retrace_guard_warns_once_on_new_inner_sig(self):
        import warnings
        eng = InferenceEngine(lambda x: x * 2, buckets=(1, 4))
        eng.infer([np.zeros((1, 8), np.float32)])
        with warnings.catch_warnings(record=True) as rec:
            warnings.simplefilter("always")
            eng.infer([np.zeros((1, 9), np.float32)])   # new inner dim
            eng.infer([np.zeros((1, 10), np.float32)])  # third sig
        msgs = [r for r in rec if "retracing" in str(r.message)]
        assert len(msgs) == 1  # warn-once (jit_retrace_warn idiom)

    def test_pad_rows_do_not_leak(self):
        # zero padding must never change the real rows' outputs
        model = _mlp(2)
        eng = InferenceEngine(model, buckets=(8,))
        x = np.random.default_rng(1).standard_normal((3, 8)).astype(
            np.float32)
        np.testing.assert_allclose(eng.infer([x])[0], _eager(model, x),
                                   rtol=1e-5, atol=1e-6)


class TestServerBatching:
    def test_mixed_size_parity_across_ragged_boundaries(self):
        model = _mlp(3)
        srv = Server(model, max_batch=8, buckets=(1, 4, 8),
                     batch_timeout_ms=5, queue_depth=64).start()
        rng = np.random.default_rng(2)
        reqs = [rng.standard_normal((rows, 8)).astype(np.float32)
                for rows in (1, 3, 1, 2, 5, 1, 1, 4, 2, 1)]  # 21 rows
        futs = [srv.submit(r) for r in reqs]
        for r, f in zip(reqs, futs):
            out = f.result(timeout=30)
            assert out.shape == (r.shape[0], 4)
            np.testing.assert_allclose(out, _eager(model, r), rtol=1e-5,
                                       atol=1e-6)
        rep = srv.drain()
        assert rep["accepted"] == 10 and rep["completed"] == 10
        assert rep["unaccounted"] == 0
        snap = srv.metrics.snapshot()
        occ = snap["histograms"]["batch_occupancy"]
        assert 0 < occ["max"] <= 1.0
        assert snap["counters"]["batches_total"] <= 10  # coalesced

    def test_full_batch_vs_timeout_flush_paths(self):
        with flags_guard(serve_chaos_slow_s=0.4):
            chaos.configure("serve_slow_step@1")
            srv = Server(_mlp(4), max_batch=4, buckets=(1, 4),
                         batch_timeout_ms=10, queue_depth=64).start()
            x = np.zeros((1, 8), np.float32)
            first = srv.submit(x)          # batch 1: stalled by chaos
            time.sleep(0.1)                # batcher is inside the stall
            futs = [srv.submit(x) for _ in range(4)]  # queue a FULL batch
            first.result(timeout=30)
            for f in futs:
                f.result(timeout=30)
            # one more after the burst: flushes on the timeout path
            srv.submit(x).result(timeout=30)
            snap = srv.metrics.snapshot()["counters"]
            srv.drain()
        assert snap["batches_full_total"] >= 1
        assert snap["batches_timeout_total"] >= 1
        assert chaos.counts().get("serve_slow_step") >= 1

    def test_incompatible_signature_splits_batch(self):
        model_in8 = _mlp(5)
        srv = Server(model_in8, max_batch=8, buckets=(8,),
                     batch_timeout_ms=20, queue_depth=64).start()
        a = np.zeros((1, 8), np.float32)
        b = np.ones((2, 8), np.float64)  # same rank, new dtype → new sig
        import warnings
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")  # expected retrace warning
            fa, fb = srv.submit(a), srv.submit(b)
            fa.result(timeout=30)
            fb.result(timeout=30)
        rep = srv.drain()
        assert rep["batches"] == 2 and rep["unaccounted"] == 0


class TestAdmissionControl:
    def test_overload_sheds_typed(self):
        with flags_guard(serve_chaos_slow_s=0.5):
            chaos.configure("serve_slow_step@1")
            srv = Server(_mlp(6), max_batch=1, buckets=(1,),
                         batch_timeout_ms=0, queue_depth=2).start()
            x = np.zeros((1, 8), np.float32)
            first = srv.submit(x)     # picked up, stalled in dispatch
            time.sleep(0.1)
            q1, q2 = srv.submit(x), srv.submit(x)  # fill the queue
            with pytest.raises(ServerOverloaded):
                srv.submit(x)
            snap = srv.metrics.snapshot()["counters"]
            assert snap["shed_total"] == 1
            for f in (first, q1, q2):
                f.result(timeout=30)
            rep = srv.drain()
        # sheds are NOT accepted: accounting stays exact
        assert rep["accepted"] == 3 and rep["completed"] == 3
        assert rep["unaccounted"] == 0

    def test_deadline_expiry_via_slow_step_chaos(self):
        """The serve_slow_step@N chaos point proving the deadline/shed
        path: the stalled dispatch ages queued requests past their
        deadline; they fail typed, never dispatched, all accounted."""
        with flags_guard(serve_chaos_slow_s=0.5):
            chaos.configure("serve_slow_step@1")
            srv = Server(_mlp(7), max_batch=4, buckets=(1, 4),
                         batch_timeout_ms=5, queue_depth=64).start()
            x = np.zeros((1, 8), np.float32)
            first = srv.submit(x)  # its dispatch stalls 0.5s
            time.sleep(0.1)
            doomed = [srv.submit(x, deadline_ms=100) for _ in range(2)]
            assert first.result(timeout=30).shape == (1, 4)
            for f in doomed:
                with pytest.raises(DeadlineExceeded, match="never"):
                    f.result(timeout=30)
            rep = srv.drain()
        assert rep["deadline_failed"] == 2
        assert rep["accepted"] == 3
        assert rep["completed"] == 1 and rep["unaccounted"] == 0

    def test_result_timeout_typed_on_wedged_batch(self):
        """ISSUE 7 satellite: a reader blocking on a wedged batch must
        not wait forever — result(timeout=...) raises the typed
        DeadlineExceeded. The request itself stays in flight (first-
        wins), so a later read succeeds and the books still balance."""
        with flags_guard(serve_chaos_slow_s=1.0):
            chaos.configure("serve_slow_step@1")
            srv = Server(_mlp(21), max_batch=1, buckets=(1,),
                         batch_timeout_ms=0, queue_depth=8).start()
            x = np.zeros((1, 8), np.float32)
            fut = srv.submit(x)   # its dispatch stalls 1s
            t0 = time.monotonic()
            with pytest.raises(DeadlineExceeded, match="still in "
                               "flight"):
                fut.result(timeout=0.1)
            assert time.monotonic() - t0 < 0.9  # didn't ride the stall
            # the request was NOT cancelled: it completes and accounts
            assert fut.result(timeout=30).shape == (1, 4)
            rep = srv.drain()
        assert rep["accepted"] == 1 and rep["completed"] == 1
        assert rep["unaccounted"] == 0

    def test_submit_validation(self):
        srv = Server(_mlp(8), max_batch=4, buckets=(4,),
                     batch_timeout_ms=1).start()
        from paddle1_tpu.core.errors import InvalidArgumentError
        with pytest.raises(InvalidArgumentError, match="split"):
            srv.submit(np.zeros((5, 8), np.float32))
        with pytest.raises(InvalidArgumentError, match="batch dim"):
            srv.submit(np.float32(3.0))
        srv.drain()

    def test_prebuilt_engine_rejects_unappliable_kwargs(self):
        eng = InferenceEngine(_mlp(8), buckets=(1, 4))
        from paddle1_tpu.core.errors import InvalidArgumentError
        with pytest.raises(InvalidArgumentError, match="pre-built"):
            Server(eng, buckets=(1, 2))
        with pytest.raises(InvalidArgumentError, match="pre-built"):
            Server(eng, input_specs=[((8,), "float32")])
        srv = Server(eng, max_batch=4)  # compatible kwargs still fine
        assert srv.engine is eng and eng.metrics is srv.metrics

    def test_submit_drain_race_accounting(self):
        """Submits hammering a server while it drains must never leave
        unaccounted != 0: the admission lock pairs the accepted count
        with the enqueue, so a drain's snapshot can't land between
        them. (Pre-fix this raced ~1/LOTS into accepted=completed+1.)"""
        eng = InferenceEngine(_mlp(13), buckets=(4,))
        x = np.zeros((1, 8), np.float32)
        for _ in range(8):
            srv = Server(eng, max_batch=4, batch_timeout_ms=1,
                         queue_depth=64).start()
            stop = threading.Event()

            def hammer():
                while not stop.is_set():
                    try:
                        srv.submit(x)
                    except (ServerClosed, ServerOverloaded):
                        return

            ts = [threading.Thread(target=hammer) for _ in range(4)]
            for t in ts:
                t.start()
            time.sleep(0.02)
            rep = srv.drain()
            stop.set()
            for t in ts:
                t.join()
            assert rep["unaccounted"] == 0, rep

    def test_mismatched_multi_input_rejected_before_enqueue(self):
        """One malformed multi-input request must fail at submit(),
        not poison the micro-batch it would have been coalesced into."""
        srv = Server(lambda x, y: x + y, max_batch=4, buckets=(4,),
                     batch_timeout_ms=5).start()
        from paddle1_tpu.core.errors import InvalidArgumentError
        good = (np.ones((2, 4), np.float32), np.ones((2, 4), np.float32))
        f0 = srv.submit(*good)
        with pytest.raises(InvalidArgumentError, match="share the batch"):
            srv.submit(np.ones((2, 4), np.float32),
                       np.ones((3, 4), np.float32))
        with pytest.raises(InvalidArgumentError, match="share the batch"):
            srv.submit(np.ones((2, 4), np.float32), np.float32(1.0))
        f1 = srv.submit(*good)  # innocents keep flowing
        np.testing.assert_allclose(f0.result(timeout=30), 2.0)
        np.testing.assert_allclose(f1.result(timeout=30), 2.0)
        rep = srv.drain()
        assert rep["accepted"] == 2 and rep["unaccounted"] == 0


class TestDrain:
    def test_drain_under_load_accounts_every_request(self):
        srv = Server(_mlp(9), max_batch=4, buckets=(1, 4),
                     batch_timeout_ms=5, queue_depth=128).start()
        x = np.zeros((1, 8), np.float32)
        futs = [srv.submit(x) for _ in range(24)]
        health.request_drain()  # programmatic SIGTERM equivalent
        rep = srv.wait(poll_s=0.01, timeout=30)
        assert rep["drained"] is True
        # the no-silent-drops contract: every accepted request resolved
        assert all(f.done() for f in futs)
        assert rep["accepted"] == 24
        assert rep["completed"] + rep["deadline_failed"] + \
            rep["errors"] == 24
        assert rep["unaccounted"] == 0
        for f in futs:
            assert f.result(timeout=1).shape == (1, 4)

    def test_submit_after_drain_is_typed(self):
        srv = Server(_mlp(10), buckets=(1,), batch_timeout_ms=1).start()
        srv.drain()
        with pytest.raises(ServerClosed):
            srv.submit(np.zeros((1, 8), np.float32))

    def test_batcher_death_latches_drain_and_reports_fatal(self,
                                                           monkeypatch):
        """A dead batcher must not leave a healthy-looking zombie:
        wait() returns instead of polling forever, drain() reports the
        fatal, and submit() fails typed."""
        srv = Server(_mlp(10), buckets=(1,), batch_timeout_ms=1).start()
        from paddle1_tpu.serving import batcher as batcher_mod
        real = batcher_mod.core_health

        class _BrokenHealth:  # only the BATCHER's binding is replaced
            @staticmethod
            def beat():
                raise RuntimeError("beat broke")
            report_unhealthy = staticmethod(real.report_unhealthy)
        monkeypatch.setattr(batcher_mod, "core_health", _BrokenHealth)
        rep = srv.wait(poll_s=0.01, timeout=30)  # returns via the latch
        assert rep["fatal"] is not None and "beat broke" in rep["fatal"]
        with pytest.raises(ServerClosed):
            srv.submit(np.zeros((1, 8), np.float32))

    def test_drain_timeout_fails_inflight_typed(self):
        """drain() on a WEDGED dispatch resolves the popped-but-
        unresolved futures typed — no client hangs forever on a future
        whose batch never completed."""
        from paddle1_tpu.core.errors import PreconditionNotMetError
        with flags_guard(serve_chaos_slow_s=1.5):
            chaos.configure("serve_slow_step@1")
            srv = Server(_mlp(10), buckets=(1,),
                         batch_timeout_ms=1).start()
            fut = srv.submit(np.zeros((1, 8), np.float32))
            time.sleep(0.15)  # batcher pops it and stalls in dispatch
            rep = srv.drain(timeout=0.2)
        assert rep["drained"] is False
        with pytest.raises(PreconditionNotMetError, match="timed out"):
            fut.result(timeout=1)
        assert rep["unaccounted"] == 0  # failed typed, not dropped
        # let the stalled thread unwedge before the next test
        srv._batcher.join(timeout=5)

    def test_sigterm_handler_installed_once_across_restarts(self):
        """Restart-after-drain must not stack a new SIGTERM closure per
        cycle (each SIGTERM would re-run the drain chain N times)."""
        import signal
        prev = signal.getsignal(signal.SIGTERM)
        try:
            srv = Server(_mlp(10), buckets=(1,), batch_timeout_ms=1)
            srv.start()
            h1 = signal.getsignal(signal.SIGTERM)
            srv.drain()
            srv.start()
            assert signal.getsignal(signal.SIGTERM) is h1
            srv.drain()
        finally:
            signal.signal(signal.SIGTERM, prev)

    def test_restart_after_drain_serves_again(self):
        """start() is restartable: a drained server reopened with
        start() admits and completes requests (model-reload flow)."""
        srv = Server(_mlp(10), buckets=(1,), batch_timeout_ms=1).start()
        x = np.zeros((1, 8), np.float32)
        assert srv.infer(x, timeout=30).shape == (1, 4)
        srv.drain()
        srv.start()
        assert srv.running
        assert srv.infer(x, timeout=30).shape == (1, 4)
        rep = srv.drain()
        assert rep["unaccounted"] == 0

    def test_context_manager_drains(self):
        with Server(_mlp(11), buckets=(1, 2),
                    batch_timeout_ms=1) as srv:
            out = srv.infer(np.zeros((1, 8), np.float32), timeout=30)
            assert out.shape == (1, 4)
        assert not srv.running


class TestPredictorServe:
    @pytest.fixture(scope="class")
    def artifact(self, tmp_path_factory):
        from paddle1_tpu.jit import InputSpec
        model = _mlp(12)
        base = str(tmp_path_factory.mktemp("srv") / "m")
        paddle.jit.save(model, base,
                        input_spec=[InputSpec([4, 8], "float32", "x")])
        return base

    def test_serve_matches_run_and_buckets_at_export_batch(self,
                                                           artifact):
        from paddle1_tpu import inference
        pred = inference.create_predictor(
            inference.Config(artifact + ".pdmodel"))
        x = np.random.default_rng(3).standard_normal((4, 8)).astype(
            np.float32)
        ref = pred.run([x])[0]
        srv = pred.serve(batch_timeout_ms=5, warmup=True).start()
        # the exported artifact fixes the batch: one bucket, = export B
        assert srv.engine.buckets == (4,)
        futs = [srv.submit(x[i:i + 1]) for i in range(4)]
        got = np.concatenate([f.result(timeout=30) for f in futs])
        np.testing.assert_allclose(got, ref, rtol=1e-6, atol=1e-6)
        rep = srv.drain()
        assert rep["compile_counts"] == {4: 1}

    def test_conflicting_buckets_on_fixed_artifact_typed(self, artifact):
        """Explicit buckets that disagree with the export batch fail
        typed at construction, not deep inside jax.export at dispatch."""
        from paddle1_tpu import inference
        from paddle1_tpu.core.errors import InvalidArgumentError
        pred = inference.create_predictor(
            inference.Config(artifact + ".pdmodel"))
        with pytest.raises(InvalidArgumentError, match="exported at"):
            pred.serve(buckets=(1, 16))
        with pytest.raises(InvalidArgumentError, match="exported at"):
            pred.serve(max_batch=8)
        # matching override is fine
        assert pred.serve(buckets=(4,)).engine.buckets == (4,)

    def test_predictor_subclass_routes_through_adapter(self, artifact):
        """isinstance, not a class-name string: a Predictor SUBCLASS
        must still unwrap the artifact (export-pinned bucket, sidecar
        specs) instead of dying as 'not a Layer or callable'."""
        from paddle1_tpu import inference

        class AuditedPredictor(inference.Predictor):
            pass

        pred = AuditedPredictor(inference.Config(artifact + ".pdmodel"))
        srv = Server(pred, batch_timeout_ms=5)
        assert srv.engine.buckets == (4,)
        srv.start()
        x = np.random.default_rng(4).standard_normal((4, 8)).astype(
            np.float32)
        ref = pred.run([x])[0]
        np.testing.assert_allclose(srv.infer(x[:1], timeout=30),
                                   ref[:1], rtol=1e-6, atol=1e-6)
        srv.drain()

    def test_quantized_predictor_teaches(self, artifact):
        from paddle1_tpu import inference
        from paddle1_tpu.core.errors import UnimplementedError
        cfg = inference.Config(artifact + ".pdmodel")
        cfg.enable_quantized_inference()
        pred = inference.create_predictor(cfg)
        with pytest.raises(UnimplementedError, match="fp32"):
            pred.serve()


class TestPredictorTypedErrors:
    """Satellite: unfilled-handle failures are typed and teach, instead
    of a bare KeyError/RuntimeError."""

    def test_run_with_unfilled_handle(self, tmp_path):
        from paddle1_tpu import inference
        from paddle1_tpu.jit import InputSpec
        from paddle1_tpu.core.errors import PreconditionNotMetError
        base = str(tmp_path / "m")
        paddle.jit.save(_mlp(13), base,
                        input_spec=[InputSpec([2, 8], "float32", "x")])
        pred = inference.create_predictor(
            inference.Config(base + ".pdmodel"))
        with pytest.raises(PreconditionNotMetError,
                           match="never filled"):
            pred.run()
        # reshape() alone is metadata — copy_to_cpu says so
        h = pred.get_input_handle("x")
        h.reshape([2, 8])
        with pytest.raises(PreconditionNotMetError,
                           match="copy_from_cpu"):
            h.copy_to_cpu()
        from paddle1_tpu.core.errors import NotFoundError
        with pytest.raises(NotFoundError):
            pred.get_input_handle("nope")
        # filled handles still work end to end
        x = np.zeros((2, 8), np.float32)
        h.copy_from_cpu(x)
        assert pred.run()[0].shape == (2, 4)


class TestBNServing:
    """Satellite: a model whose BN stats were learned entirely under the
    compiled trainer serves EVAL with those stats (functionalized
    running-stat updates), not with init stats."""

    def test_compiled_training_feeds_eval_serving(self):
        import jax
        from paddle1_tpu.core.tensor import Tensor
        from paddle1_tpu.distributed import ParallelEngine, build_mesh
        paddle.seed(14)
        m = paddle.nn.Sequential(paddle.nn.Linear(8, 6),
                                 paddle.nn.BatchNorm1D(6),
                                 paddle.nn.Linear(6, 4))
        m.train()
        opt = paddle.optimizer.SGD(learning_rate=0.05,
                                   parameters=m.parameters())
        loss_fn = lambda mm, b: \
            ((mm(Tensor(b["x"])) - Tensor(b["y"])) ** 2).mean()
        eng = ParallelEngine(m, opt, loss_fn,
                             mesh=build_mesh(dp=1,
                                             devices=jax.devices()[:1]))
        rng = np.random.default_rng(4)
        import warnings
        with warnings.catch_warnings(record=True) as rec:
            warnings.simplefilter("always")
            for _ in range(3):
                # biased inputs so running mean must move off init 0
                eng.step({"x": (rng.standard_normal((16, 8)) + 3.0)
                          .astype(np.float32),
                          "y": rng.standard_normal((16, 4))
                          .astype(np.float32)})
        # functionalized: no warn-and-skip under the framework engine
        assert not [r for r in rec if "SKIPPED" in str(r.message)]
        eng.sync_model()
        mean = np.asarray(m[1]._mean.numpy())
        assert np.abs(mean).max() > 0.1  # stats genuinely learned
        # eval serving consumes the learned stats
        m.eval()
        srv = Server(m, buckets=(1, 4), batch_timeout_ms=1).start()
        x = (rng.standard_normal((2, 8)) + 3.0).astype(np.float32)
        out = srv.infer(x, timeout=30)
        np.testing.assert_allclose(out, _eager(m, x), rtol=1e-5,
                                   atol=1e-6)
        srv.drain()


_SIGTERM_WORKER = textwrap.dedent('''
    """Loaded serving worker: drains cleanly on SIGTERM, exits 0."""
    import json, sys, threading
    import numpy as np
    import jax
    jax.config.update("jax_platforms", "cpu")
    import paddle1_tpu as paddle
    from paddle1_tpu.serving import (Server, ServerClosed,
                                     ServerOverloaded)

    paddle.seed(0)
    m = paddle.nn.Sequential(paddle.nn.Linear(8, 16), paddle.nn.ReLU(),
                             paddle.nn.Linear(16, 4))
    m.eval()
    srv = Server(m, max_batch=4, buckets=(1, 4), batch_timeout_ms=5,
                 queue_depth=256).start()
    results = {"ok": 0, "typed_fail": 0}
    lock = threading.Lock()

    def client():
        x = np.zeros((1, 8), np.float32)
        while True:
            try:
                srv.submit(x).result(timeout=30)
                with lock:
                    results["ok"] += 1
            except (ServerClosed, ServerOverloaded):
                return  # draining/shed: stop submitting
            except Exception:
                with lock:
                    results["typed_fail"] += 1

    threads = [threading.Thread(target=client, daemon=True)
               for _ in range(3)]
    for t in threads:
        t.start()
    print("READY", flush=True)
    report = srv.wait(poll_s=0.02)   # returns after SIGTERM -> drain
    for t in threads:
        t.join(timeout=10)
    report["client_ok"] = results["ok"]
    report["client_typed_fail"] = results["typed_fail"]
    print("REPORT " + json.dumps(report), flush=True)
    sys.exit(0 if report["unaccounted"] == 0 and report["drained"]
             else 3)
''')


def _run_sigterm_worker(tmp_path, supervised: bool):
    script = tmp_path / "worker.py"
    script.write_text(_SIGTERM_WORKER)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("TPU_")}
    env.update({"PYTHONPATH": repo, "JAX_PLATFORMS": "cpu"})
    if supervised:
        from paddle1_tpu.distributed import Supervisor
        sup = Supervisor(policy="fail_fast",
                         heartbeat_dir=str(tmp_path / "hb"),
                         poll_s=0.1, grace_s=5.0)
        log = str(tmp_path / "workerlog.0")
        sup.add_worker(0, [sys.executable, "-u", str(script)], env=env,
                       log_path=log)
        sup.start()
        # wait for the worker to be serving, then SIGTERM it mid-load
        t0 = time.monotonic()
        while time.monotonic() - t0 < 60:
            if os.path.exists(log) and "READY" in open(log).read():
                break
            time.sleep(0.05)
        else:
            raise AssertionError("worker never became ready")
        time.sleep(0.3)  # let the clients build up load
        w = sup._workers[0]
        w.proc.send_signal(signal.SIGTERM)
        rc = sup.run()
        out = open(log).read()
        return rc, out
    proc = subprocess.Popen([sys.executable, "-u", str(script)],
                            stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT, env=env, text=True)
    line = proc.stdout.readline()
    assert "READY" in line, line
    time.sleep(0.3)
    proc.send_signal(signal.SIGTERM)
    out, _ = proc.communicate(timeout=60)
    return proc.returncode, "READY\n" + out


class TestSigtermDrain:
    @pytest.mark.slow  # subprocess + jax import ~12s; the in-process
    # drain-under-load test covers the accounting contract in-tier
    def test_standalone_sigterm_drains_cleanly(self, tmp_path):
        """Acceptance: SIGTERM during a loaded run — every accepted
        request completes or fails typed, none silently dropped, clean
        exit."""
        rc, out = _run_sigterm_worker(tmp_path, supervised=False)
        assert rc == 0, out[-2000:]
        rep = json.loads(out.split("REPORT ", 1)[1].splitlines()[0])
        assert rep["drained"] is True and rep["unaccounted"] == 0
        assert rep["accepted"] == rep["completed"] + \
            rep["deadline_failed"] + rep["errors"]
        assert rep["client_typed_fail"] == 0
        assert rep["client_ok"] >= 1  # it really was loaded

    @pytest.mark.slow
    def test_supervised_sigterm_clean_exit(self, tmp_path):
        """Acceptance: the Supervisor sees a clean exit (rc 0) from a
        SIGTERM'd serving worker — serving workers are supervisable
        with the PR 3 machinery."""
        rc, out = _run_sigterm_worker(tmp_path, supervised=True)
        assert rc == 0, out[-2000:]
        assert "REPORT" in out
        rep = json.loads(out.split("REPORT ", 1)[1].splitlines()[0])
        assert rep["drained"] is True and rep["unaccounted"] == 0


@pytest.mark.slow
class TestServingSmoke:
    def test_concurrent_low_load_p99_and_zero_sheds(self):
        """CI serving smoke: concurrent client threads at low load —
        p99 under a generous CPU bound, zero sheds."""
        srv = Server(_mlp(15), max_batch=8, buckets=(1, 4, 8),
                     batch_timeout_ms=2, queue_depth=256,
                     warmup=False).start()
        srv.engine.warm_up(example=[np.zeros((1, 8), np.float32)])
        n_per, n_cli = 50, 4
        errs = []

        def client(i):
            rng = np.random.default_rng(i)
            for _ in range(n_per):
                x = rng.standard_normal((1, 8)).astype(np.float32)
                try:
                    out = srv.submit(x).result(timeout=30)
                    assert out.shape == (1, 4)
                except Exception as e:
                    errs.append(e)
                time.sleep(0.002)

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(n_cli)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        rep = srv.drain()
        assert not errs, errs[:3]
        assert rep["shed"] == 0
        assert rep["accepted"] == n_per * n_cli
        assert rep["completed"] == n_per * n_cli
        p99 = srv.metrics.histogram("e2e_ms").percentile(99)
        assert 0 < p99 < 1000, p99  # generous CPU bound, loud if wild
