"""ISSUE 13: the cost observatory — per-executable FLOPs/bytes
attribution (obs.costmodel), live HBM census + leak detector
(obs.hbm), declarative SLOs (obs.slo), the crash flight recorder
(obs.flight), the scrape-vs-drain staleness fix (obs.http), and the
bench trajectory tool (tools/bench_history)."""

import json
import os
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from paddle1_tpu import obs
from paddle1_tpu.core import flags as core_flags
from paddle1_tpu.core.errors import InvalidArgumentError
from paddle1_tpu.obs import costmodel, flight as obs_flight
from paddle1_tpu.obs import hbm as obs_hbm
from paddle1_tpu.obs import slo as obs_slo
from paddle1_tpu.obs import trace as obs_trace

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _fresh_obs():
    obs.reset_process_registry()
    obs_hbm.reset()
    obs_flight.reset()
    obs_slo.set_process_slos(None)
    yield
    obs.reset_process_registry()
    obs_hbm.reset()
    obs_flight.reset()
    obs_slo.set_process_slos(None)


def _mlp_engine():
    import jax
    import paddle1_tpu as paddle
    from paddle1_tpu.core.tensor import Tensor
    from paddle1_tpu.distributed import ParallelEngine, build_mesh
    paddle.seed(0)
    model = paddle.nn.Sequential(paddle.nn.Linear(8, 16),
                                 paddle.nn.ReLU(),
                                 paddle.nn.Linear(16, 4))
    opt = paddle.optimizer.AdamW(learning_rate=0.01,
                                 parameters=model.parameters())
    loss_fn = lambda m, b: \
        ((m(Tensor(b["x"])) - Tensor(b["y"])) ** 2).mean()
    mesh = build_mesh(dp=1, devices=jax.devices()[:1])
    return ParallelEngine(model, opt, loss_fn, mesh=mesh)


def _batch(rows=4):
    rng = np.random.default_rng(0)
    return {"x": rng.standard_normal((rows, 8)).astype(np.float32),
            "y": rng.standard_normal((rows, 4)).astype(np.float32)}


class TestCostModel:
    def test_analyze_exact_matmul(self):
        import jax
        import jax.numpy as jnp
        x = jnp.ones((32, 32))
        cost = costmodel.analyze(
            lambda: jax.jit(lambda a, b: a @ b).lower(x, x))
        assert cost.exact and cost.source == "xla_cost_analysis"
        # 2*M*N*K MACs-as-2-flops, give or take fusion bookkeeping
        assert cost.flops == pytest.approx(2 * 32 ** 3, rel=0.2)
        assert cost.bytes_accessed > 0

    def test_analyze_failure_degrades_to_labeled_fallback(self):
        fb = costmodel.tree_size_cost({"w": np.zeros((4, 4))},
                                      batch=np.zeros((8, 4)))
        cost = costmodel.analyze(
            lambda: (_ for _ in ()).throw(RuntimeError("no backend")),
            fallback=fb)
        assert cost is fb
        assert not cost.exact
        assert cost.source == "tree_size_heuristic"

    def test_tree_size_heuristic_formula(self):
        params = {"w": np.zeros((4, 4), np.float32)}
        cost = costmodel.tree_size_cost(
            params, batch=np.zeros((8, 4), np.float32))
        assert cost.flops == 2.0 * 16 * 8   # 2 * param elems * rows
        # one read of params+batch, one param-sized write
        assert cost.bytes_accessed == (16 * 4) * 2 + 8 * 4 * 4

    def test_site_cost_memoizes(self):
        costmodel.clear_cache()
        calls = []

        def thunk():
            calls.append(1)
            raise RuntimeError("forces the fallback, still cached")

        fb = costmodel.tree_size_cost({"w": np.zeros((2, 2))})
        a = costmodel.site_cost("site", ("sig",), thunk, fallback=fb)
        b = costmodel.site_cost("site", ("sig",), thunk, fallback=fb)
        assert a is b and len(calls) == 1
        costmodel.clear_cache()

    def test_forward_cost_exact_for_layer(self):
        import paddle1_tpu as paddle
        net = paddle.nn.Sequential(paddle.nn.Linear(16, 32),
                                   paddle.nn.ReLU(),
                                   paddle.nn.Linear(32, 4))
        cost = costmodel.forward_cost(net, (8, 16))
        assert cost.exact
        # dominated by the two matmuls: 2*8*(16*32 + 32*4)
        assert cost.flops == pytest.approx(2 * 8 * (16 * 32 + 32 * 4),
                                           rel=0.3)

    def test_peak_tables(self):
        import jax
        dev = jax.devices()[0]
        assert costmodel.device_peak_flops(dev) > 0
        assert costmodel.device_peak_hbm_bw(dev) > 0

    def test_unknown_tpu_kind_is_an_error(self):
        # no device may borrow another's peaks: a TPU whose kind matches
        # no row raises, naming the kind
        class Dev:
            platform = "tpu"
            device_kind = "TPU v9 imaginary"
        with pytest.raises(ValueError, match="TPU v9 imaginary"):
            costmodel.device_peak_flops(Dev())
        with pytest.raises(ValueError, match="TPU v9 imaginary"):
            costmodel.device_peak_hbm_bw(Dev())

        class V5e:
            platform = "tpu"
            device_kind = "TPU v5 lite"
        assert costmodel.device_peak_flops(V5e()) == 197e12
        assert costmodel.device_peak_hbm_bw(V5e()) == 819e9

    def test_summary_gains_flops_column(self, capsys):
        import paddle1_tpu as paddle
        net = paddle.nn.Sequential(paddle.nn.Linear(8, 8))
        out = paddle.summary(net, input_size=(4, 8))
        text = capsys.readouterr().out
        assert "FLOPs" in text
        assert out["flops_source"] == "xla_cost_analysis"
        assert out["total_flops"] > 0
        # without an input size the table stays the legacy shape
        out2 = paddle.summary(net)
        assert "total_flops" not in out2


class TestEngineCost:
    def test_step_cost_exact_and_cached(self):
        eng = _mlp_engine()
        b = _batch()
        c1 = eng.step_cost(b)
        c2 = eng.step_cost(b)
        assert c1.exact and c1 is c2
        n_params = 8 * 16 + 16 + 16 * 4 + 4
        # fwd+bwd+opt of a dense MLP: >= the 2*params*rows forward floor
        assert c1.flops >= 2 * n_params * 4

    def test_step_cost_does_not_touch_compile_accounting(self):
        # the acceptance gates read trace_count — the cost lowering
        # must trace the UNCOUNTED body
        eng = _mlp_engine()
        b = _batch()
        float(eng.step(b))
        before = eng.cache_stats()
        eng.step_cost(b)
        assert eng.cache_stats() == before

    def test_mfu_and_cost_gauges_published(self):
        eng = _mlp_engine()
        b = _batch()
        with core_flags.flags_guard(obs_metrics=True):
            for _ in range(3):
                float(eng.step(b))
        g = obs.process_registry().snapshot()["gauges"]
        assert g["train_step_flops"] > 0
        assert g["train_step_bytes"] > 0
        assert g["train_cost_exact"] == 1.0
        # the time between two enqueues is not a step time under
        # run-ahead: the engine derives no utilization from it
        assert "train_mfu" not in g and "train_hbm_bw_util" not in g
        assert g["hbm_params_bytes"] > 0
        assert g["hbm_census_bytes"] > 0

    def test_disabled_still_structurally_zero(self):
        eng = _mlp_engine()
        float(eng.step(_batch()))
        assert obs.process_registry().empty()
        assert obs_flight.recorder() is None


class TestServingCost:
    def test_bucket_cost_gauges_and_compile_counts(self):
        import paddle1_tpu as paddle
        from paddle1_tpu.serving import InferenceEngine, ServingMetrics
        paddle.seed(0)
        model = paddle.nn.Sequential(paddle.nn.Linear(8, 8))
        model.eval()
        m = ServingMetrics()
        eng = InferenceEngine(model, buckets=(1, 4), metrics=m)
        x = np.ones((1, 8), np.float32)
        with core_flags.flags_guard(obs_metrics=True):
            eng.infer([x])
        cost = eng.bucket_cost([x])
        assert cost.exact
        g = m.snapshot()["gauges"]
        assert g["cost_bucket_1_flops"] > 0
        assert g["cost_bucket_1_bytes"] > 0
        # the uncounted cost lowering left compile accounting intact
        assert eng.compile_counts == {1: 1}

    def test_generation_decode_cost_uncounted(self):
        from paddle1_tpu.serving import CausalLM, GenerationEngine
        lm = CausalLM(vocab_size=16, d_model=8, nhead=2,
                      dim_feedforward=16, num_layers=1, max_seq=16)
        eng = GenerationEngine(lm, slots=2, max_seq=16,
                               prefill_buckets=(4,))
        cost = eng.decode_cost()
        assert cost.exact and cost.flops > 0
        # the compile-ONCE contract untouched: no decode compile ran
        assert eng.decode_compile_count == 0
        pc = eng.prefill_cost(4)
        assert pc.exact and pc.flops > 0
        assert eng.prefill_compile_counts == {}


class TestHbmCensus:
    def test_register_census_and_weakref_death(self):
        class Owner:
            tree = {"a": np.zeros((10,), np.float32)}
        o = Owner()
        obs_hbm.register("params", o, lambda x: x.tree)
        per = obs_hbm.registered_bytes()
        assert per["params"] == 40
        del o
        import gc
        gc.collect()
        assert obs_hbm.registered_bytes()["params"] == 0

    def test_alias_dedup_counts_once(self):
        shared = np.zeros((10,), np.float32)

        class A:
            pass
        a, b = A(), A()
        obs_hbm.register("params", a, lambda x: [shared])
        obs_hbm.register("other", b, lambda x: [shared])
        per = obs_hbm.registered_bytes()
        assert per["params"] == 40 and per["other"] == 0

    def test_unknown_subsystem_folds_into_other(self):
        class A:
            pass
        a = A()
        obs_hbm.register("weird", a, lambda x: [np.zeros(4, np.int8)])
        assert obs_hbm.registered_bytes()["other"] == 4

    def test_census_device_side(self):
        eng = _mlp_engine()
        c = obs_hbm.census()
        assert c["subsystems"]["params"] > 0
        assert c["subsystems"]["opt_state"] > 0
        assert c["device_bytes_in_use"] > 0
        assert 0 < c["coverage_ratio"] <= 1.01
        assert eng is not None  # keep the engine (and weakrefs) alive

    def test_leak_detector_flag_gated(self):
        # disarmed: monotone growth never raises
        for i in range(10):
            obs_hbm.leak_note(1000 + i)
        with core_flags.flags_guard(obs_hbm_leak_steps=3):
            obs_hbm.reset()
            obs_hbm.leak_note(100)
            obs_hbm.leak_note(200)
            obs_hbm.leak_note(300)
            with pytest.raises(obs.HbmLeakSuspected) as ei:
                obs_hbm.leak_note(400)
            assert "consecutive" in str(ei.value)
            # a plateau resets the streak
            obs_hbm.leak_note(100)
            obs_hbm.leak_note(200)
            obs_hbm.leak_note(200)
            obs_hbm.leak_note(300)
            obs_hbm.leak_note(400)
            with pytest.raises(obs.HbmLeakSuspected):
                obs_hbm.leak_note(500)

    def test_publish_gauges(self):
        class A:
            pass
        a = A()
        obs_hbm.register("kv_cache", a,
                         lambda x: [np.zeros((8,), np.float32)])
        m = obs.MetricsRegistry(namespace="p1t")
        total = obs_hbm.publish(m, full=True)
        g = m.snapshot()["gauges"]
        assert g["hbm_kv_cache_bytes"] == 32 and total == 32
        assert "hbm_census_coverage_ratio" in g
        assert "hbm_device_bytes_in_use" in g


class TestSlo:
    def test_parse_grammar(self):
        s = obs_slo.parse_slos(
            "lat=p99(e2e_ms)<50;err=rate(errors_total/requests_total)"
            "<0.01;fresh=stale(age_seconds)<600")
        kinds = [sp.kind for sp in s.specs]
        assert kinds == ["latency_quantile", "error_rate", "staleness"]
        assert s.specs[0].quantile == 99.0

    def test_parse_teaching_errors(self):
        with pytest.raises(InvalidArgumentError) as ei:
            obs_slo.parse_slos("lat=p42(e2e_ms)<50")
        assert "grammar" in str(ei.value)
        with pytest.raises(InvalidArgumentError):
            obs_slo.parse_slos("err=rate(only_one)<0.01")
        with pytest.raises(InvalidArgumentError):
            obs_slo.parse_slos("dup=stale(a)<1;dup=stale(b)<1")

    def test_evaluate_publishes_burn_gauges(self):
        m = obs.MetricsRegistry(namespace="p1t")
        h = m.histogram("e2e_ms")
        for _ in range(10):
            h.observe(80.0)
        s = obs_slo.parse_slos("lat=p99(e2e_ms)<50")
        v = s.evaluate(m)
        assert v["lat"]["ok"] is False
        assert v["lat"]["burn_rate"] == pytest.approx(1.6)
        g = m.snapshot()["gauges"]
        assert g["slo_lat_burn_rate_ratio"] == pytest.approx(1.6)
        assert g["slo_lat_ok"] == 0.0

    def test_evaluate_peek_only_no_family_creation(self):
        m = obs.MetricsRegistry(namespace="p1t")
        s = obs_slo.parse_slos("lat=p99(never_fired_ms)<50")
        v = s.evaluate(m, publish=False)
        assert v["lat"]["ok"] is True and v["lat"]["observed"] is None
        assert m.empty()  # evaluating must not create empty families

    def test_healthz_verdicts(self):
        m = obs.process_registry()
        h = m.histogram("e2e_ms")
        for _ in range(5):
            h.observe(10.0)
        with core_flags.flags_guard(obs_slos="lat=p99(e2e_ms)<50"):
            srv = obs.TelemetryServer(port=0).start()
            try:
                hz = json.loads(urllib.request.urlopen(
                    srv.url + "/healthz", timeout=10).read())
            finally:
                srv.stop()
        assert hz["slo_ok"] is True
        assert hz["slo"]["lat"]["ok"] is True


class TestFlightRecorder:
    def test_disarmed_is_none(self):
        assert obs_flight.recorder() is None

    def test_ring_keeps_last_n_and_dump_atomic(self, tmp_path):
        with core_flags.flags_guard(obs_flight_steps=5,
                                    obs_flight_dir=str(tmp_path)):
            r = obs_flight.recorder()
            assert r is not None
            for i in range(12):
                r.note_step(step=i)
            path = r.dump(reason="unit")
        recs = obs_flight.read_bundle(path)
        hdr = recs[0]
        assert hdr["kind"] == "flight_header" and hdr["reason"] == "unit"
        steps = [x["step"] for x in recs if x.get("kind") == "step"]
        assert steps == [7, 8, 9, 10, 11]
        assert not [f for f in os.listdir(tmp_path) if ".tmp." in f]

    def test_event_and_span_taps(self, tmp_path):
        from paddle1_tpu.obs import events as obs_events
        with core_flags.flags_guard(obs_flight_steps=4,
                                    obs_flight_dir=str(tmp_path)):
            r = obs_flight.recorder()
            # no events file, no trace dir — the ring still sees both
            obs_events.emit("worker_restart", rank=3)
            with obs_trace.span("train/step", cat="Engine"):
                pass
            text = r.dump_text()
        assert '"worker_restart"' in text
        assert '"train/step"' in text

    def test_debug_flight_route(self, tmp_path):
        srv = obs.TelemetryServer(port=0).start()
        try:
            with pytest.raises(urllib.error.HTTPError):
                urllib.request.urlopen(srv.url + "/debug/flight",
                                       timeout=10)
            with core_flags.flags_guard(obs_flight_steps=4,
                                        obs_flight_dir=str(tmp_path)):
                obs_flight.recorder().note_step(step=1)
                body = urllib.request.urlopen(
                    srv.url + "/debug/flight", timeout=10).read()
                assert b"flight_header" in body
                # the route also wrote the on-demand disk dump
                assert [f for f in os.listdir(tmp_path)
                        if f.startswith("flight-")]
        finally:
            srv.stop()

    def test_export_chrome_trace_merges_flight(self, tmp_path):
        d = str(tmp_path / "tr")
        with core_flags.flags_guard(obs_trace_dir=d,
                                    obs_flight_steps=4,
                                    obs_flight_dir=d):
            with obs_trace.span("train/step", cat="Engine"):
                pass
            r = obs_flight.recorder()
            r.note_step(step=7)
            r.dump(reason="unit")
        stats = obs_trace.export_chrome_trace(
            d, str(tmp_path / "chrome.json"))
        assert "flight/step" in stats["names"]
        assert "flight/dump" in stats["names"]
        # the span flushed to the live sink is not duplicated by its
        # shadow copy in the flight bundle
        ev = json.load(open(tmp_path / "chrome.json"))["traceEvents"]
        assert len([e for e in ev if e["name"] == "train/step"]) == 1

    def test_crash_dump_via_excepthook_subprocess(self, tmp_path):
        code = (
            "import sys\n"
            "sys.path.insert(0, %r)\n"
            "from paddle1_tpu.core import flags as core_flags\n"
            "from paddle1_tpu.obs import flight\n"
            "core_flags.set_flags({'obs_flight_steps': 3,\n"
            "                      'obs_flight_dir': %r})\n"
            "r = flight.recorder()\n"
            "for i in range(9):\n"
            "    r.note_step(step=i)\n"
            "raise RuntimeError('injected')\n"
        ) % (_ROOT, str(tmp_path))
        r = subprocess.run([sys.executable, "-c", code],
                           capture_output=True, timeout=120)
        assert r.returncode != 0
        bundles = [f for f in os.listdir(tmp_path)
                   if f.startswith("flight-")]
        assert bundles, r.stderr.decode()[-2000:]
        recs = obs_flight.read_bundle(str(tmp_path / bundles[0]))
        assert recs[0]["reason"] == "crash"
        assert "injected" in recs[0]["error"]
        assert [x["step"] for x in recs
                if x.get("kind") == "step"] == [6, 7, 8]


class TestTelemetryStaleProviders:
    def _get(self, url):
        return urllib.request.urlopen(url, timeout=10).read().decode()

    def test_stale_page_served_after_provider_breaks(self):
        state = {"broken": False}

        def provider():
            if state["broken"]:
                raise RuntimeError("drained")
            return "good_page 1\n"

        srv = obs.TelemetryServer(port=0, registry=False,
                                  providers=[provider])
        srv.start()
        try:
            page = self._get(srv.url + "/metrics")
            assert "good_page 1" in page
            state["broken"] = True
            page = self._get(srv.url + "/metrics")
            assert "good_page 1" in page
            assert "# provider stale" in page
            assert "# provider error" not in page
        finally:
            srv.stop()

    def test_never_succeeded_provider_keeps_error_comment(self):
        def boom():
            raise RuntimeError("never worked")
        srv = obs.TelemetryServer(port=0, registry=False,
                                  providers=[boom])
        srv.start()
        try:
            assert "# provider error" in self._get(srv.url + "/metrics")
        finally:
            srv.stop()

    def test_scrape_vs_drain_hammer(self):
        """Concurrent scrapes racing a provider being torn down and
        revived: every response must carry the data page (fresh or
        stale), never the provider-error hole."""
        state = {"broken": False}

        def provider():
            if state["broken"]:
                raise RuntimeError("torn down")
            return "hammer_page 1\n"

        srv = obs.TelemetryServer(port=0, registry=False,
                                  providers=[provider])
        srv.start()
        pages, errors = [], []
        stop = threading.Event()

        def scraper():
            while not stop.is_set():
                try:
                    pages.append(self._get(srv.url + "/metrics"))
                except Exception as e:  # noqa: broad-except — any
                    # scrape failure fails the hammer below
                    errors.append(repr(e))

        def toggler():
            while not stop.is_set():
                state["broken"] = not state["broken"]
                time.sleep(0.002)

        try:
            self._get(srv.url + "/metrics")  # seed the good page
            threads = [threading.Thread(target=scraper)
                       for _ in range(6)]
            threads.append(threading.Thread(target=toggler))
            for t in threads:
                t.start()
            time.sleep(1.0)
            stop.set()
            for t in threads:
                t.join(timeout=10)
        finally:
            srv.stop()
        assert not errors
        assert len(pages) > 20
        assert all("hammer_page 1" in p for p in pages)
        assert not any("# provider error" in p for p in pages)
        assert any("# provider stale" in p for p in pages)


class TestBenchHistory:
    def _tool(self):
        sys.path.insert(0, os.path.join(_ROOT, "tools"))
        try:
            import bench_history
        finally:
            sys.path.pop(0)
        return bench_history

    def _rec(self, metric, value, unit="req/s", vs=1.0):
        return {"metric": metric, "value": value, "unit": unit,
                "vs_baseline": vs, "detail": {}}

    def test_regression_ratchet(self):
        bh = self._tool()
        prior = [self._rec("qps", v) for v in (90, 100, 95)]
        assert bh.check_regressions(prior, [self._rec("qps", 91)]) == []
        probs = bh.check_regressions(prior, [self._rec("qps", 80)])
        assert probs and "down more than" in probs[0]

    def test_first_run_seeds_the_bar(self):
        bh = self._tool()
        assert bh.check_regressions([], [self._rec("new", 1.0)]) == []

    def test_lower_is_better_with_absolute_floor(self):
        bh = self._tool()
        prior = [self._rec("obs_overhead_frac", 0.005,
                           unit="fraction")]
        # 2x relative but noise-level absolute: not a regression
        assert bh.check_regressions(
            prior, [self._rec("obs_overhead_frac", 0.01,
                              unit="fraction")]) == []
        probs = bh.check_regressions(
            prior, [self._rec("obs_overhead_frac", 0.04,
                              unit="fraction")])
        assert probs and "up more than" in probs[0]

    def test_vs_baseline_contract_break(self):
        bh = self._tool()
        prior = [self._rec("soak", 10.0, unit="steps/s", vs=1.0)]
        probs = bh.check_regressions(
            prior, [self._rec("soak", 10.0, unit="steps/s", vs=0.0)])
        assert probs and "contract broke" in probs[0]

    def test_append_roundtrip_and_window(self, tmp_path):
        bh = self._tool()
        path = str(tmp_path / "hist.jsonl")
        for v in (100, 101, 102, 103, 104, 105, 40):
            bh.append_records(path, [self._rec("qps", v)])
        hist = bh.read_history(path)
        assert len(hist) == 7
        # the window is the LAST 5 priors: an ancient best outside it
        # does not gate
        prior, fresh = hist[:-1], [hist[-1]]
        probs = bh.check_regressions(prior, fresh)
        assert probs  # 40 vs best-of-last-5 (105)


class TestExpositionConformanceCostFamilies:
    def test_cost_hbm_slo_gauge_families_conform(self):
        from tests.test_obs import parse_exposition
        m = obs.MetricsRegistry(namespace="p1t")
        m.gauge("train_cost_exact").set(1.0)
        m.gauge("train_step_flops").set(1e12)
        m.gauge("train_step_bytes").set(2e9)
        m.gauge("hbm_params_bytes").set(4.4e8)
        m.gauge("hbm_census_coverage_ratio").set(0.98)
        m.gauge("slo_lat_burn_rate_ratio").set(0.5)
        m.gauge("slo_lat_ok").set(1.0)
        m.histogram("train_readback_seconds").observe(0.01)
        types, samples = parse_exposition(m.render_text())
        for fam in ("p1t_train_cost_exact", "p1t_train_step_flops",
                    "p1t_hbm_params_bytes",
                    "p1t_hbm_census_coverage_ratio",
                    "p1t_slo_lat_burn_rate_ratio"):
            assert types[fam] == "gauge"
        assert types["p1t_train_readback_seconds"] == "summary"
