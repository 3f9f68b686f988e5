"""Process-wide observability: one pane of glass (ISSUE 10).

Three layers, each opt-in and independently cheap:

* **Metrics** — :mod:`obs.registry` promotes the serving runtime's
  Counter/Gauge/Histogram into the single process-wide
  :class:`MetricsRegistry`. Serving keeps its per-Server registries
  (re-exported from ``serving/metrics.py``, zero API break); training
  (:class:`~paddle1_tpu.distributed.ParallelEngine` step phases,
  :class:`~paddle1_tpu.distributed.ResilientTrainer` checkpoints,
  loader resilience, :class:`~paddle1_tpu.hapi.callbacks.
  MetricsCallback`) reports into :func:`process_registry`. Per-step
  phase timing is gated by the ``obs_metrics`` flag so the disabled
  cost is ≈ 0 (the ``bench.py --obs`` gate); rare lifecycle counters
  (checkpoints, restarts, quarantines) are always on.
* **Tracing** — :mod:`obs.trace` extends profiler spans with
  trace_id/span_id context that crosses process boundaries: over the
  serving wire protocol's frame header, and into Supervisor worker env
  via ``PADDLE_OBS_TRACE_CTX``. With ``obs_trace_dir`` set, every
  process appends completed spans to ``spans-<pid>.jsonl`` there and
  :func:`~paddle1_tpu.obs.trace.export_chrome_trace` merges them into
  ONE chrome://tracing view with flow arrows — a request flowing
  client → fleet router → replica → batcher → dispatch, or a training
  step's host-side phase breakdown.
* **Live telemetry** — :mod:`obs.http` serves ``/metrics`` (Prometheus
  text exposition) and ``/healthz`` from a stdlib daemon thread (flag
  ``obs_port``); ``ServingFleet.start_telemetry`` and
  ``Supervisor.start_telemetry`` aggregate child pages via
  :func:`merge_snapshots`. :mod:`obs.events` is the structured JSONL
  lifecycle journal (restart, resize, deploy, shed, quarantine,
  checkpoint commit) behind ``obs_events_file``.

The cost observatory (ISSUE 13) adds what things *cost*:
:mod:`obs.costmodel` derives per-executable FLOPs/bytes from XLA's
cost analysis (``train_step_flops`` / ``train_step_bytes`` gauges, the
``hapi.summary`` FLOPs column) and maps the compiled step's
instructions to the scopes they were traced under, :mod:`obs.hbm` is the live-buffer
census by subsystem plus the flag-gated monotone-growth leak detector,
:mod:`obs.slo` evaluates declarative SLOs (burn-rate gauges +
``/healthz`` verdicts — ROADMAP #4's sensor), and :mod:`obs.flight` is
the crash flight recorder (bounded ring of recent steps/spans/events,
dumped atomically on crash/preemption/``GET /debug/flight``, merged by
``export_chrome_trace``). All of it rides the same discipline:
structurally zero when off, < 5% enabled (``bench.py --cost``).
"""

from __future__ import annotations

from . import costmodel, events, flight, hbm, slo, trace
from .costmodel import ExecutableCost
from .flight import FlightRecorder
from .hbm import HbmLeakSuspected
from .http import TelemetryServer, start_telemetry_from_flags
from .registry import (Counter, Gauge, Histogram, MetricsGroup,
                       MetricsRegistry, ServingMetrics, merge_snapshots,
                       metrics_on, process_group, process_registry,
                       render_snapshot_text,
                       reset_process_registry, step_registry)
from .slo import SloSet, SloSpec, parse_slos

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "ServingMetrics",
    "MetricsGroup", "merge_snapshots", "render_snapshot_text",
    "process_registry", "process_group", "reset_process_registry",
    "metrics_on",
    "step_registry", "TelemetryServer", "start_telemetry_from_flags",
    "trace", "events", "costmodel", "hbm", "slo", "flight",
    "ExecutableCost", "FlightRecorder", "HbmLeakSuspected",
    "SloSet", "SloSpec", "parse_slos",
]
